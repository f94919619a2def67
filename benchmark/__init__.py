"""The benchmark: ``python3 -m benchmark.run --workload <cell> --seed <n>
--seconds <s> --trace <0|1>`` (contract and cells: ``BENCHMARK.json``;
what it measures and why: ``PERF.md``).

    run.py            one command, one process, one cell, one run
    manifest.py       BENCHMARK.json, the files it names, the driver's rules
    harness.py        clock, device, profiler, compile counter, result line
    traffic.py        the one traffic generator (seed + parameters -> inputs)
    serve_loop.py     the serving driver serving kinds share
    stats.py          percentiles, spread and window arithmetic
    roofline.py       required operations and bytes; peaks.json by device_kind
    trace_reduce.py   profiler trace -> busy time, self time, gaps, step time
    readers.py        reductions several per-layer readers share
    configs/ workloads/ kinds/ families/ reference/ layer_metrics/
                      one file per configuration, cell, traffic kind, model
                      family, plain reference and per-layer metric
    tools/            rehearse_compile, measure, trace_look
                      (never run by the driver)
"""
