"""Traffic kind ``train_steps``: optimizer steps on seeded token batches.

Set-up: the engine is built on the cell's chips, the weights are made on
the device from the seed, the plain reference computes loss and gradient
norm on the first batch at those weights, and ``warmup_steps`` steps run
(the first is the one held to the reference). The window then dispatches
steps back to back through ``engine.train_batch`` with host batches (the
copy to the device is on the path) and a rolling fence ``fence_lag_steps``
behind, so the device queue never drains. The clock starts on a fence and
ends on the fence of the last step dispatched before ``--seconds`` ran
out: ``train_tokens_per_s`` is the tokens of all those steps over that
span. A traced run adds ``trace_steps`` more steps under the profiler
after the window, and keeps the step's executable: its text for the scope
join and its ``memory_analysis()`` for ``train_program_hbm_gb``.

Nothing here reads a model's key: sizes, lengths and the vocabulary come
from the cell's family (``benchmark/families/__init__.py``).
"""

import time

import numpy as np

from benchmark import harness, trace_reduce, traffic
from benchmark.harness import span


def _steps(engine, batches, start, stop_after_s, lag, max_steps=None):
    """Dispatch steps from batch index ``start`` until ``stop_after_s`` has
    passed (or ``max_steps``), fencing ``lag`` behind; then fence the rest.
    Returns (losses, completion times)."""
    import jax
    losses, done = [], []
    t0 = time.monotonic()
    i = 0
    while True:
        with span("bench/train_batch"):
            losses.append(engine.train_batch(
                {"input_ids": batches[(start + i) % len(batches)]}))
        i += 1
        if i > lag:
            with span("bench/rolling_fence"):
                jax.block_until_ready(losses[i - 1 - lag])
            done.append(time.monotonic())
        if max_steps is not None and i >= max_steps:
            break
        if max_steps is None and time.monotonic() - t0 >= stop_after_s:
            break
    with span("bench/final_fence"):
        for j in range(len(done), i):
            jax.block_until_ready(losses[j])
            done.append(time.monotonic())
    return losses, done


MEMORY_FIELDS = ("argument_size", "output_size", "alias_size", "temp_size",
                 "generated_code_size", "peak_memory")


def step_program(engine, batch_ids):
    """(compiled text, {field: bytes on one chip}) of the step that ran (an
    executable-cache hit after the first ``train_batch``). The fields are
    the compiler's ``memory_analysis()``: buffer assignment, exact, one
    program's — where ``memory_stats()`` reports the process's live buffers."""
    compiled = engine.lower_train_step({"input_ids": batch_ids}).compile()
    analysis = compiled.memory_analysis()
    return compiled.as_text(), {
        f: int(getattr(analysis, f + "_in_bytes")) for f in MEMORY_FIELDS}


def run(ctx):
    import jax
    p, config, family = ctx.traffic, ctx.config, ctx.family
    chips = ctx.cell["chips"]
    devices = jax.devices()[:chips]
    shapes = family.traffic_shapes(config, ctx.rehearse)
    batches = traffic.train_batches(p, ctx.seed, shapes["vocab_size"],
                                    shapes["seq_scale"])
    tokens_per_step = batches[0].size

    with span("bench/build"):
        engine, params = family.build_train(
            config, p["global_batch"], ctx.seed, devices, ctx.rehearse)
    harness.mark(ctx, "engine built, weights on the device")
    with span("bench/reference"):
        want = family.reference_train(config, params, batches[0], devices,
                                      ctx.rehearse)
    del params
    harness.mark(ctx, "reference loss and gradient norm computed")
    with span("bench/warmup"):
        loss0 = engine.train_batch({"input_ids": batches[0]})
        got = (float(loss0), float(engine.get_global_grad_norm()))
        _steps(engine, batches, 1, 0, p["fence_lag_steps"],
               max_steps=p["warmup_steps"] - 1)
    checks, detail = family.judge_train(config, *got, *want)
    harness.mark(ctx, "warmed up")

    record = harness.Record(**ctx.base)
    if ctx.trace:
        record.compiled_text, record.extra["step_program_memory"] = \
            step_program(engine, batches[0])

    compiles = ctx.compiles.count
    t0 = time.monotonic()
    record.setup_s = t0 - ctx.t_start
    losses, done = _steps(engine, batches, p["warmup_steps"], ctx.seconds,
                          p["fence_lag_steps"])
    record.window_s = done[-1] - t0
    record.compiles_in_window = ctx.compiles.count - compiles
    host_losses = np.asarray(jax.device_get(losses), np.float64)
    checks["window_losses_finite"] = bool(np.all(np.isfinite(host_losses)))
    checks["no_compile_in_window"] = record.compiles_in_window == 0
    detail["window_losses_first_last"] = [host_losses[0], host_losses[-1]]

    record.e2e["train_tokens_per_s"] = \
        len(done) * tokens_per_step / record.window_s
    record.samples["train_tokens_per_s"] = done
    record.samples["step_s"] = list(np.diff([t0] + done))
    # one number a step: a run that reads far off says here whether one
    # fence waited (a stalled host, a drained queue) or every step was slow
    step_s = record.samples["step_s"]
    detail["window_step_s_min_median_max_slowest"] = [
        float(np.min(step_s)), float(np.median(step_s)), float(np.max(step_s)),
        int(np.argmax(step_s))]
    record.attempted, record.failed = len(done), 0
    record.extra.update(tokens_per_step=tokens_per_step,
                        global_batch=p["global_batch"],
                        seq_len=batches[0].shape[1],
                        steps=len(done), step_module="jit_train_batch_fn")

    if ctx.trace:
        prof = harness.Profiler(ctx.tag)
        prof.start()
        with span("bench/traced_slice"):
            _steps(engine, batches, p["warmup_steps"] + len(done), 0,
                   p["fence_lag_steps"], max_steps=p["trace_steps"])
        record.trace = prof.stop()
        mods = [m for pl in record.planes() for m in trace_reduce.modules(
            record.trace, pl, record.extra["step_module"])]
        if mods:
            record.slice = (min(m.start for m in mods),
                            max(m.end for m in mods))
    record.memory_peak_bytes = harness.memory_peak_bytes(chips)
    record.checks, record.detail = checks, detail
    return record
