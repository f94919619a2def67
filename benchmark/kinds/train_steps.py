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
after the window.
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


def run(ctx):
    import jax
    p, config, family = ctx.traffic, ctx.config, ctx.family
    chips = ctx.cell["chips"]
    devices = jax.devices()[:chips]
    s = family.sizes(config, ctx.rehearse)
    scale = s["n_positions"] / config["n_positions"]
    batches = traffic.train_batches(p, ctx.seed, s["vocab_size"], scale)
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
        record.compiled_text = engine.lower_train_step(
            {"input_ids": batches[0]}).compile().as_text()

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
