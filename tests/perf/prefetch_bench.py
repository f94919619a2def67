"""ZeRO-3 layer-wise parameter-gather prefetch benchmark — step time
across gather modes (ISSUE 3 acceptance: prefetch on >= off; ISSUE 8
acceptance: ``fused_matmul`` >= 1.1x over ring-mode prefetch with equal
losses, exposure breakdown recorded).

Three engine variants over the same GPT-2 model/batch:

  fused_gspmd   stage 3, stage3_prefetch=False — every per-layer gather
                implicit (a sharding constraint), XLA schedules freely
  ring          stage3_prefetch=True, gather="ring" — the explicit
                double-buffered per-layer packed gather pipeline
                (parallel/prefetch.py)
  fused_matmul  gather="fused_matmul" (ISSUE 8) — the layer's dominant
                projection weights skip the packed full-param buffer
                and stream chunk-by-chunk through the tile-granular
                fused all-gather+matmul / matmul+reduce-scatter path
                (ops/pallas/fused_collective.py; the lax decomposed
                ring on this CPU harness, the pallas kernels on TPU)

Exposure breakdown (gather-wait vs compute): with T_comm the timing of
a standalone comm-only program replaying ring mode's per-step
collective stream (per layer: forward gather + backward re-gather +
grad reduce-scatter of the packed sharded-leaf buffer), and the
fused_gspmd step as the compute proxy (XLA's own schedule of the
IDENTICAL computation — the floor the explicit pipelines chase; a
replicated-params engine is NOT usable as the proxy here because its
whole-gradient allreduce dwarfs the sharded exchanges),

  exposed(mode) = step(mode) - step(fused_gspmd)    # comm NOT hidden
  hidden(mode)  = T_comm - exposed(mode)            # comm overlapped

both clamped at 0 and recorded as ``comm/zero3_prefetch_<mode>/
{exposed,hidden}_s`` counters in the telemetry registry (ISSUE 8
satellite). On the CPU-emulated mesh the collectives are memcpy-bound
and the 8 virtual devices timeshare the host cores, so the numbers
calibrate plumbing overhead + copy elision (fused_matmul's win here is
skipping the pack/moveaxis/unpack of the packed buffer and never
materializing full weights or weight grads), not real ICI overlap —
run on a TPU slice for the true overlap win. Prints one JSON object.

Run directly: python tests/perf/prefetch_bench.py [n_embd] [n_layer]
"""

import json
import os
import sys
import time

sys.path.insert(0, os.path.join(os.path.dirname(__file__), '..', '..'))


def _build_engine(model_cfg, n, batch_size, gather, threshold=0):
    import jax
    import deepspeed_tpu as dstpu
    from deepspeed_tpu.models.gpt2 import GPT2LMHeadModel
    from deepspeed_tpu.parallel.mesh import make_mesh, MeshConfig
    cfg = {
        "train_batch_size": batch_size,
        "gradient_accumulation_steps": 1,
        "steps_per_print": 10**9,
        "optimizer": {"type": "AdamW", "params": {"lr": 1e-4}},
        "zero_optimization": {
            "stage": 3,
            "stage3_prefetch": gather is not None,
            "stage3_prefetch_gather": gather or "ring",
            "collective_matmul": {"backend": "auto"},
            "stage3_param_persistence_threshold": threshold},
    }
    mesh = make_mesh(MeshConfig(data=n), devices=jax.devices())
    engine, _, _, _ = dstpu.initialize(
        config=cfg, model=GPT2LMHeadModel(model_cfg), mesh=mesh)
    return engine


def _time_comm_stream(engine, steps):
    """Standalone comm-only program: ring mode's per-step collective
    volume over the engine's ACTUAL sharded layer stack (per layer:
    2 packed gathers + 1 packed reduce-scatter), timed under the same
    virtual-device contention as the engines."""
    import jax
    import jax.numpy as jnp
    from jax.sharding import PartitionSpec
    from deepspeed_tpu.parallel import mesh as mesh_lib
    from deepspeed_tpu.parallel import overlap as overlap_lib

    mesh = engine.mesh
    axis = mesh_lib.DATA_AXIS
    n = mesh_lib.mesh_axis_size(mesh, axis)
    subtree = engine.module.prefetch_layer_subtree
    params = engine.state.params[subtree]
    spec_tree = engine.zero.param_specs(engine.state.params)[subtree]
    plan = engine.zero.explicit_shard_plan(params, specs=spec_tree)
    leaves = jax.tree_util.tree_leaves(params)
    spec_leaves = jax.tree_util.tree_leaves(
        spec_tree, is_leaf=lambda x: isinstance(x, PartitionSpec))
    sharded = [l for l, e in zip(leaves, plan) if e is not None]
    sharded_specs = [s for s, e in zip(spec_leaves, plan)
                     if e is not None]
    if not sharded:
        return 0.0
    L = sharded[0].shape[0]

    def comm_only(*stacks):
        total = jnp.float32(0.0)
        for l in range(L):
            flat = jnp.concatenate(
                [s[l].reshape(-1) for s in stacks]) if len(stacks) > 1 \
                else stacks[0][l].reshape(-1)
            g1 = overlap_lib.ring_all_gather(flat, axis, n)     # forward
            rs = overlap_lib.ring_reduce_scatter(g1, axis, n)   # grad RS
            # backward re-gather: data-depends on the RS so XLA cannot
            # CSE it with g1 (two identical pure gathers would collapse
            # into one and undercount the stream by a third)
            g2 = overlap_lib.ring_all_gather(flat + 0.0 * rs, axis, n)
            total = total + g2[0] + rs[0]
        return total

    # shard_map with the resting specs hands each device its local shard
    fn = jax.jit(jax.shard_map(
        comm_only, mesh=mesh,
        in_specs=tuple(sharded_specs),
        out_specs=PartitionSpec(), check_vma=False))
    fn(*sharded)  # compile + warm
    t0 = time.perf_counter()
    for _ in range(steps):
        out = fn(*sharded)
    jax.block_until_ready(out)  # sync-ok: bench timing fence
    return (time.perf_counter() - t0) / steps * 1e3


def run_prefetch_bench(n_embd=512, n_layer=8, seq=64, vocab=2048,
                       steps=6, batch_per_dev=1):
    import numpy as np
    import jax
    import jax.numpy as jnp
    from deepspeed_tpu.models.gpt2 import GPT2Config
    from deepspeed_tpu.telemetry.registry import (
        default_registry, record_comm_exposure)

    n = len(jax.devices())
    bs = batch_per_dev * n
    model_cfg = GPT2Config(vocab_size=vocab, n_positions=seq,
                           n_embd=n_embd, n_layer=n_layer,
                           n_head=max(2, n_embd // 64),
                           dtype=jnp.float32, param_dtype=jnp.float32,
                           scan_layers=True)
    rng = np.random.RandomState(0)
    batch = {"input_ids": rng.randint(0, vocab, size=(bs, seq))
             .astype(np.int32)}

    def time_steps(engine):
        engine.train_batch(batch)                       # compile + warm
        t0 = time.perf_counter()
        for _ in range(steps):
            loss = engine.train_batch(batch)
        jax.block_until_ready(engine.state.params)  # sync-ok: bench fence
        return (time.perf_counter() - t0) / steps * 1e3, float(loss)

    result = {"devices": n, "n_embd": n_embd, "n_layer": n_layer,
              "seq": seq, "batch_per_dev": batch_per_dev,
              "step_ms": {}, "loss": {}}
    comm_stream_ms = None
    variants = (("fused_gspmd", None, 0),
                ("ring", "ring", 0),
                ("fused_matmul", "fused_matmul", 0))
    for name, gather, threshold in variants:
        engine = _build_engine(model_cfg, n, bs, gather, threshold)
        if gather is not None and threshold == 0:
            assert engine._prefetch_active(), \
                "prefetch pipeline did not activate on this mesh"
        ms, loss = time_steps(engine)
        if name == "fused_matmul":
            stats = engine.prefetch_live_param_stats()
            result["live_param_bytes"] = stats["live_param_bytes"]
            result["fused_leaves_per_layer"] = \
                stats["fused_leaves_per_layer"]
            result["fused_stream_bytes"] = stats["fused_stream_bytes"]
        if name == "ring":
            stats = engine.prefetch_live_param_stats()
            result["per_layer_gather_bytes"] = \
                stats["per_layer_gather_bytes"]
            comm_stream_ms = _time_comm_stream(engine, steps)
        result["step_ms"][name] = round(ms, 3)
        result["loss"][name] = round(loss, 6)
        del engine
        jax.clear_caches()

    result["prefetch_speedup"] = round(
        result["step_ms"]["fused_gspmd"] / result["step_ms"]["ring"], 3)
    result["fused_vs_ring"] = round(
        result["step_ms"]["ring"] / result["step_ms"]["fused_matmul"], 3)
    # gather-wait vs compute decomposition (see module docstring) —
    # recorded as per-site telemetry counters and echoed in the JSON
    compute_ms = result["step_ms"]["fused_gspmd"]
    result["exposure"] = {"comm_stream_ms": round(comm_stream_ms or 0.0, 3),
                          "compute_proxy_ms": compute_ms}
    for mode in ("ring", "fused_matmul"):
        exposed = max(0.0, result["step_ms"][mode] - compute_ms)
        hidden = max(0.0, (comm_stream_ms or 0.0) - exposed)
        record_comm_exposure(f"zero3_prefetch_{mode}",
                             exposed / 1e3, hidden / 1e3)
        result["exposure"][mode] = {"exposed_comm_ms": round(exposed, 3),
                                    "hidden_comm_ms": round(hidden, 3)}
    result["telemetry_counters"] = {
        k: round(v, 6) for k, v in
        default_registry().snapshot(prefix="comm/")["counters"].items()}
    return result


def main(n_embd=512, n_layer=8):
    import jax
    if "xla_force_host_platform_device_count" in \
            os.environ.get("XLA_FLAGS", ""):
        jax.config.update("jax_platforms", "cpu")
    print(json.dumps(run_prefetch_bench(n_embd=n_embd, n_layer=n_layer),
                     indent=2))


if __name__ == "__main__":
    if "xla_force_host_platform_device_count" not in \
            os.environ.get("XLA_FLAGS", ""):
        # re-exec with the multi-device CPU env (XLA_FLAGS is read at
        # interpreter start)
        env = dict(os.environ)
        env["XLA_FLAGS"] = (env.get("XLA_FLAGS", "")
                            + " --xla_force_host_platform_device_count=8")
        env.setdefault("JAX_PLATFORMS", "cpu")
        os.execve(sys.executable, [sys.executable, __file__] + sys.argv[1:],
                  env)
    main(*(int(a) for a in sys.argv[1:]))
