"""Bucketed gradient-sync scheduler tests (parallel/overlap.py).

The numerics contract: the bucket stream's ring reduce-scatter + all-gather
(and the per-bucket fused psum) must reproduce the monolithic psum exchange
at fp32 rounding tolerance across bucket layouts — including the uneven
last bucket and the single-bucket degenerate case — and the engine's
overlap_comm train path must match the fused GSPMD train path step for
step."""

import functools

import numpy as np
import pytest
import jax
import jax.numpy as jnp
from jax.sharding import Mesh, PartitionSpec as P

import deepspeed_tpu as dstpu
from deepspeed_tpu.parallel import overlap
from jax import shard_map
from deepspeed_tpu.parallel.mesh import make_mesh, MeshConfig
from tests.simple_model import SimpleModel, random_batch, base_config

N = 8


def _mesh():
    devs = jax.devices()
    assert len(devs) >= N
    return Mesh(np.asarray(devs[:N]), ("data",))


# ---------------------------------------------------------------------------
# bucket planning
# ---------------------------------------------------------------------------

def test_plan_buckets_greedy_packing():
    shapes = [(128,), (16,), (8, 8), (4,)]          # 128, 16, 64, 4 elems
    buckets = overlap.plan_buckets(shapes, bucket_elems=100, axis_size=N)
    assert [b.leaf_ids for b in buckets] == [(0,), (1, 2, 3)]
    assert buckets[0].numel == 128 and buckets[0].padded == 128
    # 84 elems → padded up to the next multiple of the axis size
    assert buckets[1].numel == 84 and buckets[1].padded == 88


def test_plan_buckets_oversized_leaf_gets_own_bucket():
    buckets = overlap.plan_buckets([(10,), (1000,), (10,)], 64, 4)
    assert [b.leaf_ids for b in buckets] == [(0,), (1,), (2,)]


def test_plan_buckets_single_bucket_degenerate():
    buckets = overlap.plan_buckets([(3,), (5,), (7,)], 10**9, 4)
    assert len(buckets) == 1
    assert buckets[0].numel == 15 and buckets[0].padded == 16


def test_plan_buckets_scalar_leaves():
    buckets = overlap.plan_buckets([(), ()], 10, 4)
    assert len(buckets) == 1 and buckets[0].numel == 2


# ---------------------------------------------------------------------------
# ring collectives vs psum
# ---------------------------------------------------------------------------

def _stacked(shape, seed=0):
    """Per-device distinct local buffers, stacked on the data axis."""
    return jnp.asarray(
        np.random.RandomState(seed).randn(N, *shape).astype(np.float32))


def test_ring_reduce_scatter_matches_sum():
    mesh = _mesh()
    L = N * 6
    bufs = _stacked((L,))

    @jax.jit
    @functools.partial(shard_map, mesh=mesh, in_specs=P("data"),
                       out_specs=P("data"))
    def run(b):
        return overlap.ring_reduce_scatter(b.reshape(-1), "data", N) \
            .reshape(1, -1)

    out = np.asarray(run(bufs)).reshape(-1)          # chunk i from device i
    np.testing.assert_allclose(out, np.asarray(bufs).sum(0), rtol=1e-5,
                               atol=1e-6)


def test_ring_all_gather_roundtrip():
    mesh = _mesh()
    full = np.random.RandomState(1).randn(N * 5).astype(np.float32)
    shards = jnp.asarray(full.reshape(N, 5))         # device i owns chunk i

    @jax.jit
    @functools.partial(shard_map, mesh=mesh, in_specs=P("data"),
                       out_specs=P("data"))
    def run(s):
        return overlap.ring_all_gather(s.reshape(-1), "data", N) \
            .reshape(1, -1)

    out = np.asarray(run(shards))                    # [N, N*5]: per-device copy
    for row in out:
        np.testing.assert_array_equal(row, full)


def test_ring_scan_path_matches_unrolled(monkeypatch):
    """Force the scan (large-mesh) lowering and pin it to the unrolled one."""
    mesh = _mesh()
    bufs = _stacked((N * 4,), seed=2)

    def run_once():
        @jax.jit
        @functools.partial(shard_map, mesh=mesh, in_specs=P("data"),
                           out_specs=P("data"))
        def run(b):
            s = overlap.ring_reduce_scatter(b.reshape(-1), "data", N)
            return overlap.ring_all_gather(s, "data", N).reshape(1, -1)
        return np.asarray(run(bufs))

    unrolled = run_once()
    monkeypatch.setattr(overlap, "_ring_hops", lambda fn, n, **kw: False)
    scanned = run_once()
    np.testing.assert_allclose(scanned, unrolled, rtol=1e-6)


# ---------------------------------------------------------------------------
# bucketed tree sync vs monolithic psum
# ---------------------------------------------------------------------------

def _grad_tree(seed=0):
    """Varied shapes/dtypes; sizes chosen so small bucket budgets produce
    several buckets with an uneven (padded) last one."""
    r = np.random.RandomState(seed)
    return {
        "w1": jnp.asarray(r.randn(N, 16, 8), jnp.float32),
        "b1": jnp.asarray(r.randn(N, 8), jnp.float32),
        "w2": jnp.asarray(r.randn(N, 8, 5), jnp.bfloat16),
        "scalar": jnp.asarray(r.randn(N), jnp.float32),
    }


def _reference_mean(tree):
    return {k: np.asarray(v, np.float32).mean(0) for k, v in tree.items()}


@pytest.mark.parametrize("mode", ["ring", "fused"])
@pytest.mark.parametrize("bucket_elems", [1, 50, 10**9])
def test_bucketed_allreduce_matches_psum(mode, bucket_elems):
    """bucket_elems=1 → one bucket per leaf; 50 → multi-leaf buckets with
    an uneven tail; 1e9 → single-bucket degenerate. All must agree with
    the monolithic mean."""
    mesh = _mesh()
    tree = _grad_tree()
    specs = {k: P("data") for k in tree}

    @jax.jit
    @functools.partial(shard_map, mesh=mesh, in_specs=(specs,),
                       out_specs=specs)
    def run(t):
        local = jax.tree_util.tree_map(lambda x: x[0], t)
        red = overlap.bucketed_allreduce(local, "data", N, bucket_elems,
                                         mode=mode, mean=True)
        return jax.tree_util.tree_map(lambda x: x[None], red)

    out = run(tree)
    want = _reference_mean(tree)
    for k in tree:
        got = np.asarray(out[k], np.float32)
        assert out[k].dtype == tree[k].dtype        # dtype round-trips
        tol = 2e-2 if tree[k].dtype == jnp.bfloat16 else 1e-5
        for dev in range(N):                        # identical on every device
            np.testing.assert_allclose(got[dev], want[k], rtol=tol, atol=tol)


def test_bucketed_allreduce_sum_and_single_device():
    mesh = _mesh()
    tree = {"w": jnp.asarray(np.ones((N, 4), np.float32))}

    @jax.jit
    @functools.partial(shard_map, mesh=mesh, in_specs=({"w": P("data")},),
                       out_specs={"w": P("data")})
    def run(t):
        local = jax.tree_util.tree_map(lambda x: x[0], t)
        red = overlap.bucketed_allreduce(local, "data", N, 16, mean=False)
        return jax.tree_util.tree_map(lambda x: x[None], red)

    np.testing.assert_array_equal(np.asarray(run(tree)["w"]),
                                  np.full((N, 4), N, np.float32))
    # n=1 passthrough never touches the axis
    t = {"w": jnp.ones((3,))}
    assert overlap.bucketed_allreduce(t, "data", 1, 16) is t


def test_bucketed_allreduce_rejects_bad_mode():
    with pytest.raises(ValueError):
        overlap.bucketed_allreduce({"w": jnp.ones(3)}, "data", 2, 8,
                                   mode="tree")


def test_bucketed_reduce_scatter_shards():
    mesh = _mesh()
    tree = _grad_tree(seed=3)
    specs = {k: P("data") for k in tree}

    @jax.jit
    @functools.partial(shard_map, mesh=mesh, in_specs=(specs,),
                       out_specs=P("data"))
    def run(t):
        local = jax.tree_util.tree_map(lambda x: x[0], t)
        shards, _ = overlap.bucketed_reduce_scatter(local, "data", N, 50)
        return jnp.concatenate(shards)[None]

    leaves = jax.tree_util.tree_leaves(
        {k: jnp.asarray(v[0]) for k, v in tree.items()})
    buckets = overlap.plan_buckets([l.shape for l in leaves], 50, N)
    out = np.asarray(run(tree))                      # [N, sum(padded)/N]
    # reassembling the per-device chunks bucket by bucket gives the mean
    flat_mean = np.concatenate(
        [np.asarray(l, np.float32) for l in jax.tree_util.tree_leaves(
            {k: np.asarray(v, np.float32).mean(0) for k, v in tree.items()})],
        axis=None)
    off_out, off_ref = 0, 0
    for b in buckets:
        per_dev = b.padded // N
        chunk = out[:, off_out:off_out + per_dev].reshape(-1)[:b.numel]
        np.testing.assert_allclose(
            chunk, flat_mean[off_ref:off_ref + b.numel], rtol=1e-5, atol=1e-6)
        off_out += per_dev
        off_ref += b.numel


def test_bucketed_compressed_allreduce_runs_and_converges_direction():
    """The 1-bit bucket stream: error states align with the bucket plan and
    the first-pass result preserves the sign structure of the true mean
    (exactness is the compression suite's job; here we pin the plumbing)."""
    mesh = _mesh()
    r = np.random.RandomState(4)
    tree = {"a": jnp.asarray(r.randn(N, 10, 10), jnp.float32),
            "b": jnp.asarray(r.randn(N, 96), jnp.float32),
            "c": jnp.asarray(r.randn(N, 60), jnp.float32)}
    wes, ses = overlap.compressed_error_states(
        {k: jnp.zeros(v.shape[1:]) for k, v in tree.items()},
        N, bucket_elems=100)
    assert len(wes) == len(ses) == 3                 # whole-leaf buckets

    specs = {k: P("data") for k in tree}

    @jax.jit
    @functools.partial(
        shard_map, mesh=mesh,
        in_specs=(specs, [P()] * 3, [P()] * 3),
        out_specs=(specs, [P()] * 3, [P()] * 3),
        check_vma=False)
    def run(t, wes, ses):
        local = jax.tree_util.tree_map(lambda x: x[0], t)
        red, we2, se2 = overlap.bucketed_compressed_allreduce(
            local, wes, ses, "data", N, 100)
        return jax.tree_util.tree_map(lambda x: x[None], red), we2, se2

    red, we2, se2 = run(tree, wes, ses)
    for a, b in zip(we2, wes):
        assert a.shape == b.shape
    got = np.asarray(red["a"][0])
    want = np.asarray(tree["a"], np.float32).mean(0)
    assert np.isfinite(got).all()
    # 1-bit first pass: magnitudes are quantized but signs track the mean
    # (a mean of N gaussians sits near zero, so agreement is well below
    # 1.0 — error feedback recovers the residual over steps; chance = 0.5)
    agree = (np.sign(got) == np.sign(want)).mean()
    assert agree > 0.7, agree


# ---------------------------------------------------------------------------
# engine integration: overlap_comm train path == fused GSPMD path
# ---------------------------------------------------------------------------

def _train(overlap_on, stage, mode="ring", bucket=100, steps=3,
           optimizer=None, data=N):
    cfg = base_config()
    if optimizer is not None:
        cfg["optimizer"] = optimizer
    cfg["zero_optimization"] = {
        "stage": stage, "overlap_comm": overlap_on,
        "reduce_bucket_size": bucket, "overlap_reduce": mode}
    mesh = make_mesh(MeshConfig(data=data), devices=jax.devices()[:data])
    engine, _, _, _ = dstpu.initialize(config=cfg, model=SimpleModel(),
                                       mesh=mesh)
    losses = [float(engine.train_batch(random_batch())) for _ in range(steps)]
    return engine, losses, jax.tree_util.tree_map(np.asarray,
                                                  engine.state.params)


_BASELINE = {}


def _fused_baseline(stage):
    """One fused-path run per stage, shared across the parametrized overlap
    cases (each build jit-compiles a full train step — worth caching)."""
    if stage not in _BASELINE:
        eng, losses, params = _train(False, stage)
        assert not eng._overlap_comm_active()
        _BASELINE[stage] = (losses, params)
    return _BASELINE[stage]


@pytest.mark.parametrize("stage,mode", [(1, "ring"), (2, "ring"),
                                        (2, "fused")])
def test_engine_overlap_matches_fused_path(stage, mode):
    """bucket=100 elems forces multiple buckets over SimpleModel's leaves
    (128/16/64/4), including a padded uneven tail."""
    loss_b, params_b = _fused_baseline(stage)
    eng_o, loss_o, params_o = _train(True, stage, mode)
    assert eng_o._overlap_comm_active()
    np.testing.assert_allclose(loss_o, loss_b, rtol=1e-5)
    for a, b in zip(jax.tree_util.tree_leaves(params_o),
                    jax.tree_util.tree_leaves(params_b)):
        np.testing.assert_allclose(a, b, rtol=2e-5, atol=2e-6)


def test_engine_overlap_gating():
    # single-device data axis → nothing to overlap
    eng, _, _ = _train(True, 2, data=1)
    assert not eng._overlap_comm_active()
    # LAMB's per-tensor trust ratio is not elementwise → fused fallback
    eng, losses, _ = _train(True, 2, optimizer={
        "type": "Lamb", "params": {"lr": 1e-2}})
    assert not eng._overlap_comm_active()
    assert np.isfinite(losses).all()
    # stage 3 shards params at rest → fused fallback
    eng, _, _ = _train(True, 3)
    assert not eng._overlap_comm_active()


def test_overlap_config_validation():
    from deepspeed_tpu.config.config import (DeepSpeedConfig,
                                             DeepSpeedConfigError)
    cfg = DeepSpeedConfig({
        "train_batch_size": 8,
        "zero_optimization": {"stage": 2, "overlap_comm": True,
                              "overlap_reduce": "fused",
                              "reduce_bucket_size": 1024}}, world_size=1)
    assert cfg.zero_config.overlap_comm
    assert cfg.zero_config.overlap_reduce == "fused"
    assert "overlap_reduce" in cfg.zero_config.repr_dict()
    with pytest.raises(DeepSpeedConfigError):
        DeepSpeedConfig({"train_batch_size": 8,
                         "zero_optimization": {"overlap_reduce": "tree"}},
                        world_size=1)
    with pytest.raises(DeepSpeedConfigError):
        DeepSpeedConfig({"train_batch_size": 8,
                         "zero_optimization": {"overlap_comm": True,
                                               "reduce_bucket_size": 0}},
                        world_size=1)
    # parity configs (knob unused) keep accepting any value
    cfg = DeepSpeedConfig({"train_batch_size": 8,
                           "zero_optimization": {"reduce_bucket_size": 0}},
                          world_size=1)
    assert cfg.zero_config.reduce_bucket_size == 0
    # with optimizer offload, overlap_comm means d2h grad streaming and
    # never reads the bucket size — also accepted
    cfg = DeepSpeedConfig({"train_batch_size": 8,
                           "zero_optimization": {
                               "overlap_comm": True,
                               "reduce_bucket_size": 0,
                               "offload_optimizer": {"device": "cpu"}}},
                          world_size=1)
    assert cfg.zero_config.overlap_comm
