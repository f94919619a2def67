"""One command, one process, one cell, one run.

    python -m benchmark.run --workload <cell> --seed <n> --seconds <s> --trace <0|1>

Finds the cell in ``BENCHMARK.json``, its configuration, traffic, kind and
family files by name (the family file, ``benchmark/families/<family>.py``,
is what knows the model: neither this command nor the kinds read a model's
key), refuses to run without the cell's count of TPU
devices, warms up, measures for ``--seconds`` and prints as the LAST line
of standard output one JSON object: ``correct``, ``attempted``, ``failed``,
``metrics`` (the cell's end-to-end metrics, or with ``--trace 1`` its
per-layer metrics), ``device`` and, traced, ``breakdown``. Everything else
goes to standard error or under ``benchmark/out/``.

``--rehearse-cpu`` runs the same control flow at the tiny sizes the
configuration file carries, on the CPU backend: it finds wrong paths
before chip time is spent, prints no metric and never ``correct: true``.
``--candidate`` runs a cell whose files are in the tree but which
``BENCHMARK.json`` does not list yet (tools and rehearsals only).
"""

import argparse
import json
import sys

from benchmark import harness

T_START = harness.process_start()


def main(argv=None):
    ap = argparse.ArgumentParser(description=__doc__.splitlines()[0])
    ap.add_argument("--workload", required=True)
    ap.add_argument("--seed", type=int, default=0)
    ap.add_argument("--seconds", type=float, default=None)
    ap.add_argument("--trace", type=int, choices=(0, 1), default=0)
    ap.add_argument("--rehearse-cpu", action="store_true")
    ap.add_argument("--candidate", action="store_true",
                    help="the cell has its files but no entry in "
                         "BENCHMARK.json yet (manifest.with_candidate); "
                         "never passed by the driver")
    args = ap.parse_args(argv)

    from benchmark import manifest
    bench = manifest.load()
    if args.candidate:
        bench = manifest.with_candidate(bench, args.workload)
    cell = manifest.cell_of(bench, args.workload)
    seconds = args.seconds if args.seconds is not None \
        else bench["run_seconds"]
    ctx = harness.context(bench, cell, args.seed, seconds, bool(args.trace),
                          args.rehearse_cpu, T_START)
    kind = manifest.kind_module(ctx.traffic)

    from deepspeed_tpu.utils.platform import enable_compile_cache
    need = "cpu" if args.rehearse_cpu else "tpu"
    if ctx.device["platform"] != need or ctx.device["visible"] < cell["chips"]:
        harness.log(f"cell {cell['name']} needs {cell['chips']} {need} "
                    f"device(s); JAX reports {ctx.device}. No result.")
        return 3
    if not args.rehearse_cpu:
        harness.log("compile cache:", enable_compile_cache())
    record = kind.run(ctx)
    line = harness.result_line(bench, record, bool(args.trace))
    harness.write_detail(record, line, ctx.tag)
    harness.log("checks:", json.dumps(record.checks),
                json.dumps(record.detail, default=str))
    print(json.dumps(line), flush=True)
    ok = all(record.checks.values()) and bool(record.checks)
    return 0 if ok else 1


if __name__ == "__main__":
    sys.exit(main())
