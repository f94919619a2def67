"""Pipelined swap-tier correctness (PR 5): write-behind + drain fence,
staging-pool byte cache, sliding read window, release-mid-flight, and
engine-level loss parity of pipelined == blocking == in-memory stage 3.

The contract under test: ``pipeline_write`` makes the park asynchronous,
but a swap-in issued immediately after MUST return the updated values
(the drain fence runs before any pending leaf is re-read from disk, and
cache-served leaves read the authoritative staged bytes); releasing a
swapper with writes in flight must wait them out rather than leak
pending aio against freed buffers.
"""

import glob
import os

import numpy as np
import pytest
import jax
import jax.numpy as jnp
from jax.sharding import NamedSharding, PartitionSpec as P

import deepspeed_tpu as dstpu
from tests.simple_model import SimpleModel, random_batch, base_config


def _sh():
    from deepspeed_tpu.parallel.mesh import make_mesh, MeshConfig
    mesh = make_mesh(MeshConfig(data=1), devices=jax.devices()[:1])
    return mesh, NamedSharding(mesh, P())


def _leaves(seed=0):
    rng = np.random.RandomState(seed)
    return [jnp.asarray(rng.randn(64, 32).astype(np.float32), jnp.bfloat16),
            jnp.asarray(rng.randn(1000).astype(np.float32)),
            jnp.asarray(rng.randint(-5, 5, (7,)).astype(np.int32))]


def test_write_behind_then_reread_returns_updated(tmp_path):
    """The core fence: park write-behind, then immediately re-read —
    values are the UPDATED ones, and after an explicit drain the files
    on disk hold the same bytes (durability, not just cache)."""
    from deepspeed_tpu.runtime.swap_tensor import PartitionedParamSwapper
    _, sh = _sh()
    leaves = _leaves()
    sw = PartitionedParamSwapper(str(tmp_path), pipeline_read=True,
                                 pipeline_write=True, buffer_count=4)
    sw.write_all(leaves)
    got = sw.swap_in_device([sh] * 3)
    for step in range(3):
        upd = [jnp.asarray(np.asarray(g, np.float32) * 2 + step, g.dtype)
               for g in got]
        sw.swap_out_device(upd)          # async: returns with writes in
        assert sw.has_pending_writes     # flight on the dedicated handle
        got = sw.swap_in_device([sh] * 3)
        for a, b in zip(upd, got):
            np.testing.assert_array_equal(np.asarray(a), np.asarray(b))
    sw.drain_writes()
    assert not sw.has_pending_writes
    for i, leaf in enumerate(got):
        raw = np.fromfile(sw._path(i), dtype=np.uint8)
        want = np.ascontiguousarray(np.asarray(leaf)).view(np.uint8)
        np.testing.assert_array_equal(raw, want.reshape(-1))
    sw.release()


def test_cache_hit_serves_staged_bytes(tmp_path):
    """A pool large enough to cache every leaf serves the re-read
    without touching the files — proven by corrupting the files after
    the drain and still reading correct values — while the files
    themselves stayed byte-valid at drain time."""
    from deepspeed_tpu.runtime.swap_tensor import PartitionedParamSwapper
    from deepspeed_tpu.telemetry import MetricsRegistry
    _, sh = _sh()
    leaves = _leaves()
    reg = MetricsRegistry()
    sw = PartitionedParamSwapper(str(tmp_path), pipeline_read=True,
                                 pipeline_write=True, buffer_count=3,
                                 registry=reg)
    sw.write_all(leaves)
    got = sw.swap_in_device([sh] * 3)
    upd = [jnp.asarray(np.asarray(g, np.float32) * 3 + 1, g.dtype)
           for g in got]
    sw.swap_out_device(upd)
    sw.drain_writes()
    for i in range(3):                       # rot the files
        with open(sw._path(i), "r+b") as f:
            f.write(b"\xff" * 8)
    again = sw.swap_in_device([sh] * 3)      # served from the pool cache
    for a, b in zip(upd, again):
        np.testing.assert_array_equal(np.asarray(a), np.asarray(b))
    snap = reg.snapshot("swap/")
    assert snap["counters"]["swap/cache_hit_bytes"] > 0
    sw.release()


def test_release_mid_flight_leaves_no_pending_aio(tmp_path):
    """release() with writes in flight drains them (no aio completion
    can land in a freed buffer) and clears the pending state."""
    from deepspeed_tpu.runtime.swap_tensor import PartitionedParamSwapper
    _, sh = _sh()
    rng = np.random.RandomState(1)
    leaves = [jnp.asarray(rng.randn(256, 256).astype(np.float32))
              for _ in range(6)]
    sw = PartitionedParamSwapper(str(tmp_path), pipeline_read=True,
                                 pipeline_write=True, buffer_count=3)
    sw.write_all(leaves)
    sw.swap_out_device(leaves)
    assert sw.has_pending_writes
    sw.release()
    assert not sw.has_pending_writes
    assert not sw._wbusy and not sw._wfds
    # the write handle has nothing outstanding: wait() returns 0 done
    assert sw._write_handle().wait() == 0


def test_read_window_any_order_many_leaves(tmp_path):
    """More leaves than staging slots, arbitrary swap schedule: the
    sliding window reassembles every leaf bit-exactly."""
    from deepspeed_tpu.runtime.swap_tensor import PartitionedParamSwapper
    _, sh = _sh()
    rng = np.random.RandomState(2)
    leaves = [jnp.asarray(rng.randn(50 + 7 * i).astype(np.float32))
              for i in range(9)]
    sw = PartitionedParamSwapper(str(tmp_path), pipeline_read=True,
                                 pipeline_write=True, buffer_count=3)
    sw.write_all(leaves)
    order = [8, 6, 7, 0, 1, 2, 5, 3, 4]
    got = sw.swap_in_device([sh] * 9, order=order)
    for a, b in zip(leaves, got):
        np.testing.assert_array_equal(np.asarray(a), np.asarray(b))
    # a second write+reread cycle mixes cache hits and disk reads
    upd = [jnp.asarray(np.asarray(x) + 1) for x in got]
    sw.swap_out_device(upd)
    got2 = sw.swap_in_device([sh] * 9, order=order)
    for a, b in zip(upd, got2):
        np.testing.assert_array_equal(np.asarray(a), np.asarray(b))
    sw.release()


def test_staged_leaf_snapshot_contract(tmp_path):
    """The ISSUE-7 snapshot API: after a drained park, ``staged_leaf``
    serves recently parked leaves as byte-exact cache views and the
    rest as their swap-file paths — the contract the engine's
    snapshot-from-parked-leaves path depends on."""
    from deepspeed_tpu.runtime.swap_tensor import PartitionedParamSwapper
    rng = np.random.RandomState(3)
    leaves = [jnp.asarray(rng.randn(32, 16).astype(np.float32))
              for _ in range(4)]
    sw = PartitionedParamSwapper(str(tmp_path), pipeline_read=True,
                                 pipeline_write=True, buffer_count=2)
    sw.write_all(leaves)
    sw.swap_out_device(leaves)           # pool of 2 < 4 leaves
    assert sw.has_pending_writes
    sw.drain_writes()
    sources = {}
    for i, leaf in enumerate(leaves):
        value, source = sw.staged_leaf(i)
        sources[source] = sources.get(source, 0) + 1
        if source == "cache":
            np.testing.assert_array_equal(np.asarray(value),
                                          np.asarray(leaf))
        else:
            raw = np.fromfile(value, np.float32).reshape(32, 16)
            np.testing.assert_array_equal(raw, np.asarray(leaf))
    assert sources.get("cache", 0) >= 1 and sources.get("file", 0) >= 1
    sw.release()


def test_optimizer_swapper_pipeline_write_roundtrip(tmp_path):
    """OptimizerStateSwapper with write-behind stores: prefetch/fetch of
    a pending leaf drains first; moments accumulate across steps exactly
    as the sync path does."""
    from deepspeed_tpu.runtime.swap_tensor import OptimizerStateSwapper
    shapes = [(64, 32), (1000,), (7,)]
    osw = OptimizerStateSwapper(str(tmp_path), pipeline_write=True,
                                buffer_count=3)
    for i, s in enumerate(shapes):
        osw.init_state(i, s)
    for step in range(3):
        osw.prefetch(0)
        for i, s in enumerate(shapes):
            m, v = osw.fetch(i)
            if i + 1 < len(shapes):
                osw.prefetch(i + 1)
            m += 1.0 + step
            v += 2.0 + step
            osw.store(i, m, v)
    for i, s in enumerate(shapes):
        m, v = osw.fetch(i)
        np.testing.assert_allclose(m, np.full(s, 6.0, np.float32))
        np.testing.assert_allclose(v, np.full(s, 9.0, np.float32))
    osw.release()


# ---------------------------------------------------------------------------
# engine-level parity: pipelined == blocking == in-memory stage 3
# ---------------------------------------------------------------------------

def _train(cfg_zero, steps=5):
    from deepspeed_tpu.parallel.mesh import make_mesh, MeshConfig
    cfg = base_config()
    cfg["zero_optimization"] = {"stage": 3, **cfg_zero}
    e, _, _, _ = dstpu.initialize(
        config=cfg, model=SimpleModel(),
        mesh=make_mesh(MeshConfig(data=1), devices=jax.devices()[:1]))
    batch = random_batch()
    losses = [float(e.train_batch(batch)) for _ in range(steps)]
    return e, losses


def test_engine_nvme_pipelined_matches_blocking_and_memory(tmp_path):
    """The satellite contract: losses under offload_param device=nvme
    pipelined == blocking == in-memory stage 3 on a tiny model, with
    params genuinely parked (files on disk, device arrays freed) and the
    swap telemetry moving."""
    _, mem = _train({})
    e_b, blocking = _train({
        "offload_param": {"device": "nvme", "nvme_path": str(tmp_path / "b")},
        "offload_optimizer": {"device": "cpu"}})
    e_p, pipelined = _train({
        "offload_param": {"device": "nvme", "nvme_path": str(tmp_path / "p"),
                          "pipeline_read": True, "pipeline_write": True,
                          "buffer_count": 4},
        "offload_optimizer": {"device": "cpu"}})
    np.testing.assert_allclose(blocking, mem, rtol=2e-3)
    np.testing.assert_allclose(pipelined, blocking, rtol=1e-6)
    for e, sub in ((e_b, "b"), (e_p, "p")):
        assert e._params_parked
        for leaf in jax.tree_util.tree_leaves(e.state.params):
            assert leaf.is_deleted()
        assert glob.glob(str(tmp_path / sub) + "/param_swap_*/param_*.swp")
    snap = e_p.telemetry.snapshot("swap/")
    assert snap["counters"]["swap/bytes_written"] > 0
    assert "swap/stall_s" in snap["histograms"]
    assert snap["gauges"].get("swap/staging_bytes", 0) > 0
    e_p.telemetry.reset()


def test_engine_host_runner_park_via_push(tmp_path):
    """HostOffloadOptimizer + pipelined NVMe params: the updated leaves
    park straight from the SIMD step's host output (no h2d push / d2h
    re-read round trip) and training still matches the blocking tier."""
    _, mem = _train({})
    e, got = _train({
        "offload_param": {"device": "nvme", "nvme_path": str(tmp_path),
                          "pipeline_read": True, "pipeline_write": True},
        "offload_optimizer": {"device": "cpu", "stream": "host"}})
    np.testing.assert_allclose(got, mem, rtol=2e-3)
    assert e._params_parked
    # eval + continued training transparently restore residency
    x, _ = random_batch()
    out = e.eval_batch(x)
    assert np.all(np.isfinite(np.asarray(out, np.float32)))
    assert np.isfinite(float(e.train_batch(random_batch())))


@pytest.mark.slow
def test_stage3_dp2_composes_with_nvme_tier(tmp_path):
    """Stage 3 over data=2 on a layer-stacked model + offload_param nvme
    (no optimizer offload): the disk→host→device swap schedule follows
    the model's ``layer_stacked_subtree`` and feeds the GSPMD step with
    its gather edge; losses match the in-memory run at fp32 tolerance."""
    from deepspeed_tpu.parallel.mesh import make_mesh, MeshConfig
    from deepspeed_tpu.models.gpt2 import GPT2Config, GPT2LMHeadModel
    if len(jax.devices()) < 2:
        pytest.skip("needs >=2 devices")

    def run(extra_zero):
        cfg = {
            "train_batch_size": 8,
            "zero_optimization": {
                "stage": 3,
                "stage3_param_persistence_threshold": 0, **extra_zero},
            "optimizer": {"type": "AdamW", "params": {"lr": 1e-3}},
            "steps_per_print": 1000,
        }
        mesh = make_mesh(MeshConfig(data=2), devices=jax.devices()[:2])
        model = GPT2LMHeadModel(GPT2Config(
            vocab_size=512, n_positions=64, n_embd=64, n_layer=2,
            n_head=2, dtype=jnp.float32, param_dtype=jnp.float32,
            scan_layers=True))
        e, _, _, _ = dstpu.initialize(config=cfg, model=model, mesh=mesh)
        batch = {"input_ids": np.random.RandomState(0).randint(
            0, 512, (8, 64)).astype(np.int32)}
        losses = [float(e.train_batch(batch)) for _ in range(3)]
        return e, losses

    e0, base = run({})
    assert e0._gather_edge is not None
    e1, got = run({"offload_param": {
        "device": "nvme", "nvme_path": str(tmp_path),
        "pipeline_read": True, "pipeline_write": True, "buffer_count": 4}})
    assert e1._gather_edge is not None
    assert e1._params_parked
    np.testing.assert_allclose(got, base, rtol=2e-5)
