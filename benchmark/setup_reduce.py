"""Set-up's timeline, from the program's own spans and compile events.

``setup_s`` is the harness's clock: process start to the window's first
instant. What fills it is told by the PROGRAM, which since PR 35 leaves in
its flight recorder (``deepspeed_tpu/telemetry``) one ``span`` event for each
one-time phase of the engine's start-up — ``startup/sharded_init``,
``startup/engine_init``, ``startup/state_init``, ``startup/build_fns`` — and
for every ``train/step_dispatch`` (step 0's holds the step's trace, lowering
and compile-or-fetch: the jit call blocks on them), and one ``compile`` event
for each trace, lowering and backend compile FROM THE FIRST ``sharded_init``
OR ``initialize`` ON (the listener is installed there: what a family compiled
before it — its ``PRNGKey``, its example input — lies in ``before_first_span``
uncounted), with the function's name and, on a backend phase, what the
persistent cache said (a trace under 10 ms, an eager call finding its
program, leaves no event: a cell's thousands of them are ~0.1 s that
``setup_compile_s`` lacks).
Every such event carries ``t0_mono``, its start on ``time.monotonic()``: the
clock of ``harness.process_start()`` and of the window's stamps (a training
run's samples hold the opening itself). So the
events lie on ``setup_s``'s own axis and nothing is estimated.

The timeline is six rows, cut at the program's spans, in order, without
overlap, from 0 (process start) to ``setup_s`` (the window's opening):

    before_first_span   interpreter, imports, backend start, host batches:
                        UNATTRIBUTED (no span, and no compile listener yet)
    sharded_init        from that span's start to ``engine_init``'s
    engine_init         ``dstpu.initialize``, entry to return
    reference           ``engine_init``'s end to the first ``train_batch``:
                        the benchmark's float32 reference (benchmark-side,
                        placed by the program's spans on either side of it)
    first_step          ``state_init`` + ``build_fns`` + step 0's dispatch
    warmup_rest         step 0's execution and the warm-up steps (a traced
                        run's ``step_program`` is served by jit's own
                        caches: milliseconds)

Each row has its seconds, the seconds of program spans inside it, and the
compile seconds (union of the compile events' intervals: a compile inside a
trace is not counted twice), backend compile requests and cache misses that
BEGAN inside it; beside the rows, the compile seconds by phase (trace,
lowering, backend: each the union of that phase's intervals, so a jitted
function traced inside a trace counts once) and the ten longest backend
phases by function name. An event that began at or after the window's opening
is no part of set-up. Nothing here raises on a program without these events
(the parent of PR 35, an engine built another way): ``attribution`` is then
None and every reader gives None. It is also None when the ring has pushed
anything out (its oldest ``seq`` is not 1): a sum that may be short is not
given. ``of`` logs which of the cases it was, once a run.

The six metrics of ``METRICS`` are in ``BENCHMARK.json`` since PR 37, in
every cell that reports ``train_tokens_per_s`` (the window's opening is read
from a training run's samples), each entry as its reader states it: every
traced line carries them, and the detail file (``benchmark/out/<tag>.json``)
the timeline under ``extra.setup_attribution``. A later training cell adds
its name to their ``workloads``.
"""

from benchmark import harness

SLOT = "setup_attribution"                  # where record.extra keeps it
ROWS = ("before_first_span", "sharded_init", "engine_init", "reference",
        "first_step", "warmup_rest")
METRICS = ("setup_engine_init_s", "setup_first_step_s",
           "setup_outside_program_s", "setup_compile_s",
           "setup_programs_compiled", "setup_cache_misses")


def union_s(intervals):
    """Seconds covered by ``intervals`` [(start, end)], overlaps once."""
    total, reach = 0.0, None
    for a, b in sorted(intervals):
        if reach is None or a > reach:
            total, reach = total + (b - a), b
        elif b > reach:
            total, reach = total + (b - reach), b
    return total


def _clip(intervals, lo, hi):
    return [(max(a, lo), min(b, hi)) for a, b in intervals
            if a < hi and b > lo]


def _interval(event):
    return event["t0_mono"], event["t0_mono"] + event["dur_s"]


def attribution(events, t_start, t_open, say=lambda why: None):
    """The timeline of ``events`` (a flight recorder's ring, oldest first)
    between ``t_start`` (process start) and ``t_open`` (the window's first
    instant), both on ``time.monotonic()``; None where it cannot be told,
    and ``say`` is then told why."""
    if not events:
        return say("the flight recorder holds no event")
    if events[0].get("seq") != 1:
        return say(f"the ring has pushed out its first {events[0]['seq'] - 1}"
                   f" events (it holds {len(events)}): a sum might be short")
    timed = [e for e in events
             if e.get("t0_mono") is not None and e["t0_mono"] < t_open]
    spans = {}
    for e in timed:
        if e["kind"] == "span":
            spans.setdefault(e["tag"], []).append(_interval(e))
    if "startup/engine_init" not in spans:
        return say("no startup/engine_init span before the window: a "
                   "program older than PR 35, or an engine built another way")
    init = spans["startup/engine_init"][-1]         # this run's engine

    def first_after_init(tag):
        return [s for s in spans.get(tag, ()) if s[0] >= init[1]][:1]
    state, build, step = map(first_after_init, (
        "startup/state_init", "startup/build_fns", "train/step_dispatch"))
    sharded = [s for s in spans.get("startup/sharded_init", ())
               if s[1] <= init[0]][-1:]
    startup = sharded + [init] + state + build
    # without a first train_batch before the window, the rows after
    # engine_init have no cut: one row to the opening
    cuts = [t_start, min(startup)[0], init[0], init[1]] + (
        [min(state + build + step)[0], step[0][1]] if step else []) \
        + [t_open]
    cuts = [min(max(c, t_start), t_open) for c in cuts]
    compiles = [e for e in timed if e["kind"] == "compile"]
    rows = []
    for name, lo, hi in zip(ROWS, cuts, cuts[1:]):
        mine = [e for e in compiles if lo <= e["t0_mono"] < hi]
        backend = [e for e in mine if e["phase"] == "backend"]
        rows.append({
            "row": name, "start_s": lo - t_start, "end_s": hi - t_start,
            "seconds": hi - lo,
            "span_s": union_s(_clip(startup + step, lo, hi)),
            "compile_s": union_s(map(_interval, mine)),
            "programs": len(backend),
            "cache_misses": sum(e.get("cache") == "miss" for e in backend)})
    backend = [e for e in compiles if e["phase"] == "backend"]

    def span_s(intervals):
        return union_s(_clip(intervals, t_start, t_open))
    return {
        "setup_s": t_open - t_start, "rows": rows,
        "engine_init_s": span_s(startup),
        "first_step_s": span_s(step) if step else None,
        "outside_program_s": (t_open - t_start) - span_s(startup + step),
        "compile_s": span_s(map(_interval, compiles)),
        # by phase (a warm cache shortens the backend's alone)
        "compile_phase_s": {
            phase: span_s(_interval(e) for e in compiles
                          if e["phase"] == phase)
            for phase in ("trace", "lower", "backend")},
        "programs_compiled": sum(r["programs"] for r in rows),
        "cache_misses": sum(r["cache_misses"] for r in rows),
        "longest_compiles": [
            {"fun_name": e["fun_name"], "seconds": e["dur_s"],
             "cache": e.get("cache"), "start_s": e["t0_mono"] - t_start}
            for e in sorted(backend, key=lambda e: -e["dur_s"])[:10]]}


def window_opening(record):
    """(process start, the window's first instant) on ``time.monotonic()``,
    from a training run's own samples: the first step's fence less that
    step's seconds is the opening, and ``setup_s`` before it the start.
    None for a record without them."""
    done = record.samples.get("train_tokens_per_s")
    step_s = record.samples.get("step_s")
    if not (done and step_s):
        return None
    t_open = done[0] - step_s[0]
    return t_open - record.setup_s, t_open


def of(record):
    """The run's attribution, worked out once from this process's recorder
    and kept under ``record.extra`` (the detail file takes it from there)."""
    if SLOT not in record.extra:
        from deepspeed_tpu.telemetry.recorder import default_recorder
        def say(why):
            harness.log(f"no {SLOT}, so no setup_* metric: {why}")
        opening = window_opening(record)
        record.extra[SLOT] = attribution(
            default_recorder().events(), *opening, say=say) if opening \
            else say("the record has no training window's samples")
    return record.extra[SLOT]


def metric(record, key):
    """One number of the attribution; None where there is none."""
    found = of(record)
    return None if found is None else found[key]

