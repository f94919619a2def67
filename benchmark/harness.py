"""What every traffic kind shares: the clock, the device, the profiler, the
compile counter, the run's record and the result line.

The benchmark takes from the program only the system under test, its
registry, its ``TraceAnnotation`` spans and its kernel names; every clock,
count and reduction here is the benchmark's own.
"""

import glob
import json
import os
import shutil
import sys
import time
import types

from benchmark import manifest, roofline, trace_reduce

OUT_DIR = os.path.join(manifest.HERE, "out")
SPAN_PREFIXES = ("bench/", "train/", "serving/")


def process_start():
    """``time.monotonic()`` value at which this process was started (from
    /proc, so the interpreter's own start-up counts as set-up)."""
    try:
        with open("/proc/self/stat") as f:
            ticks = int(f.read().rsplit(")", 1)[1].split()[19])
        with open("/proc/uptime") as f:
            uptime = float(f.read().split()[0])
        age = uptime - ticks / os.sysconf("SC_CLK_TCK")
        if 0 <= age < 3600:
            return time.monotonic() - age
    except (OSError, ValueError, IndexError):
        pass
    return time.monotonic()


def log(*parts):
    print("[benchmark]", *parts, file=sys.stderr, flush=True)


def mark(ctx, what):
    """Log how long after process start a set-up phase ended, and the
    process's peak of live device bytes so far (which phase set
    ``*_peak_hbm_gb``; the CPU reports none)."""
    peak = memory_peak_bytes(ctx.cell["chips"])
    log(f"t+{time.monotonic() - ctx.t_start:.1f}s {what}"
        + (f" (peak {peak / 1e9:.3f} GB live)" if peak else ""))


def span(name):
    """A host span on the profiler's timeline (no cost outside a trace)."""
    import jax
    return jax.profiler.TraceAnnotation(name)


def device_info(chips):
    import jax
    devs = jax.devices()
    return {"platform": devs[0].platform, "kind": devs[0].device_kind,
            "count": chips, "visible": len(devs)}


def memory_peak_bytes(chips):
    """Largest ``peak_bytes_in_use`` over the cell's chips (0 where the
    backend reports none, as the CPU does)."""
    import jax
    peak = 0
    for d in jax.devices()[:chips]:
        stats = d.memory_stats() or {}
        peak = max(peak, int(stats.get("peak_bytes_in_use", 0)))
    return peak


class CompileCounter:
    """Counts JAX's backend compiles (a persistent-cache fetch counts too:
    either way a program was not ready when it was called)."""

    EVENT = "/jax/core/compile/backend_compile_duration"

    def __init__(self):
        import jax.monitoring as mon
        self.count = 0
        self.seconds = 0.0
        mon.register_event_duration_secs_listener(self._on)

    def _on(self, event, secs, **_):
        if event == self.EVENT:
            self.count += 1
            self.seconds += secs


class Profiler:
    """One traced slice, written under ``benchmark/out/trace/<tag>``."""

    def __init__(self, tag):
        self.dir = os.path.join(OUT_DIR, "trace", tag)

    def start(self):
        import jax
        shutil.rmtree(self.dir, ignore_errors=True)
        os.makedirs(self.dir, exist_ok=True)
        jax.profiler.start_trace(self.dir)

    def stop(self):
        import jax
        jax.profiler.stop_trace()
        found = sorted(glob.glob(os.path.join(self.dir, "**", "*.xplane.pb"),
                                 recursive=True))
        return trace_reduce.load(found[-1])


class Record:
    """What one run learned; the per-layer readers read from it.

    cell, traffic, config   the manifest entries and files of this run
    family                  the family module
    rehearse                True on the CPU rehearsal (no device metric)
    device, peaks           device_info(); peaks.json's row (None on CPU)
    setup_s, window_s       seconds
    e2e                     {end-to-end metric: value} of the window
    samples                 {name: [values]} the harness collected itself
    registry                {histogram name: [values]} of the program's
                            registry inside the window (serving)
    compiles_in_window      backend compiles between window start and end
    compiled_text           the train step's compiled HLO text (traced runs)
    trace, slice            the reduced trace and its (t0, t1) in trace ns
    extra                   whatever a kind adds for its own readers
    """

    def __init__(self, **kw):
        self.e2e, self.samples, self.registry, self.extra = {}, {}, {}, {}
        self.trace = self.slice = self.compiled_text = None
        self.compiles_in_window = None
        self.checks, self.detail = {}, {}
        self.attempted = self.failed = 0
        self.__dict__.update(kw)

    def planes(self):
        return sorted(self.trace.devices) if self.trace else []


def busy_and_window(record):
    """(busy seconds averaged over the chips used, slice seconds)."""
    t0, t1 = record.slice
    busy = [trace_reduce.busy_ns(record.trace, p, t0, t1)
            for p in record.planes()]
    return sum(busy) / len(busy) / 1e9, (t1 - t0) / 1e9


def breakdown(record):
    """Top device operations and the longest idle gaps by host span, of the
    chip that idled most (the only one on one chip)."""
    tr, (t0, t1) = record.trace, record.slice
    plane = min(record.planes(),
                key=lambda p: trace_reduce.busy_ns(tr, p, t0, t1))
    idle = trace_reduce.gaps(trace_reduce.ops(tr, plane), t0, t1)
    spans = trace_reduce.annotations(tr, SPAN_PREFIXES)
    skew = trace_reduce.clock_skew_ns(tr, plane)
    return {"device_ops": trace_reduce.top_ops(tr, plane, t0, t1),
            "idle_gaps": trace_reduce.attribute_gaps(idle, spans, skew)}


def result_line(bench, record, traced):
    """The contract's last line, as a dict."""
    cell = record.cell
    units = {m["name"]: m["unit"]
             for m in bench["end_to_end"] + bench["per_layer"]}
    metrics = {}
    if traced:
        for m in manifest.metrics_for(bench, cell, "per_layer"):
            value = manifest.metric_module(m["name"]).read(record)
            if value is not None:
                metrics[m["name"]] = {"value": value, "unit": units[m["name"]]}
    else:
        for m in manifest.metrics_for(bench, cell, "end_to_end"):
            value = record.setup_s if m["name"] == "setup_s" \
                else record.e2e.get(m["name"])
            if value is not None:
                metrics[m["name"]] = {
                    "value": value, "unit": units[m["name"]],
                    "samples": len(record.samples.get(m["name"], ())) or 1}
    device = {k: record.device[k] for k in ("platform", "kind", "count")}
    device["memory_peak_bytes"] = record.memory_peak_bytes
    line = {"correct": bool(record.checks) and all(record.checks.values())
            and not record.rehearse,
            "attempted": record.attempted, "failed": record.failed,
            "metrics": metrics, "device": device}
    if "requests_finished" in record.extra:
        line["requests_finished"] = record.extra["requests_finished"]
    if traced and record.trace is not None and not record.rehearse:
        device["busy_s"], device["window_s"] = busy_and_window(record)
        line["breakdown"] = breakdown(record)
    if record.rehearse:
        # a CPU run gives no device metric: names only, to check the flow
        line["metrics"] = {}
        line["rehearsal"] = True
        line["rehearsal_metric_names"] = sorted(metrics)
        line["rehearsal_checks_passed"] = all(record.checks.values())
    return line


def write_detail(record, line, tag):
    os.makedirs(OUT_DIR, exist_ok=True)
    with open(os.path.join(OUT_DIR, tag + ".json"), "w") as f:
        json.dump({"line": line, "checks": record.checks,
                   "detail": record.detail, "e2e": record.e2e,
                   "setup_s": record.setup_s, "window_s": record.window_s,
                   "compiles_in_window": record.compiles_in_window,
                   "extra": {k: v for k, v in record.extra.items()
                             if isinstance(v, (int, float, str, list, dict))}},
                  f, indent=1, default=str)


def context(bench, cell, seed, seconds, trace, rehearse, t_start):
    """What a traffic kind's ``run(ctx)`` is handed: the cell's files, the
    run's arguments, the device, and the base of its Record."""
    config = manifest.config_of(bench, cell)
    traffic = manifest.traffic_of(cell)
    family = manifest.family_module(config)
    device = device_info(cell["chips"])
    peaks = None if rehearse or device["platform"] != "tpu" \
        else roofline.peaks_for(device["kind"])
    return types.SimpleNamespace(
        bench=bench, cell=cell, config=config, traffic=traffic,
        family=family, seed=seed, seconds=seconds, trace=trace,
        rehearse=rehearse, t_start=t_start, device=device,
        tag=f"{cell['name']}-seed{seed}-trace{int(trace)}",
        compiles=CompileCounter(),
        base=dict(cell=cell, traffic=traffic, config=config, family=family,
                  rehearse=rehearse, device=device, peaks=peaks))
