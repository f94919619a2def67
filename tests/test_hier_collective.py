"""Link-aware two-level collective stream (ISSUE 16).

Numerics contract: with ``CollectiveMatmulConfig.hierarchy`` set, both
fused-collective ops must reproduce the flat single-ring schedule (and
the dense einsum it is pinned against) to fp32 partial-sum rounding —
the two-level lowering only reorders the partial sums, it never changes
what is summed. Same for the overlap-layer two-level gather/reduce
primitives vs their numpy references, and for the compressed slow hop
vs the flat 1-bit primitive when the split is degenerate (intra=1).
Also pins the `comm.hierarchy` x `stage3_prefetch` config composition
rules and the per-(axis, reason) fallback-warning latch.
"""

import numpy as np
import pytest
import jax
import jax.numpy as jnp
from jax.sharding import Mesh, PartitionSpec as P

import deepspeed_tpu as dstpu
from deepspeed_tpu.config.config import DeepSpeedConfig, DeepSpeedConfigError
from deepspeed_tpu.models.gpt2 import GPT2Config, GPT2LMHeadModel
from deepspeed_tpu.ops.pallas import fused_collective as fc
from deepspeed_tpu.parallel import compression as comp
from deepspeed_tpu.parallel import overlap as ov
from deepspeed_tpu.parallel import topology as topo
from jax import shard_map
from deepspeed_tpu.parallel.mesh import MeshConfig, make_mesh

SPLITS = [(2, 4), (4, 2)]


def _flat_mesh(n):
    devs = jax.devices()
    assert len(devs) >= n
    return Mesh(np.asarray(devs[:n]), ("data",))


def _split_mesh(ni, k):
    devs = jax.devices()
    assert len(devs) >= ni * k
    return Mesh(np.asarray(devs[:ni * k]).reshape(ni, k), ("di", "dt"))


def _hier_cfg(ni, k, backend="lax", tile_m=8):
    # axis_name is the axes tuple, mirroring how the engine passes
    # plan.axes — the hierarchical lowering routes every collective
    # through inter_axis/intra_axis and never uses the flat name
    return fc.CollectiveMatmulConfig(
        axis_name=("di", "dt"), axis_size=ni * k, backend=backend,
        tile_m=tile_m, min_shard_bytes=0, interpret=True,
        hierarchy=fc.RingHierarchy(inter_axis="di", intra_axis="dt",
                                   inter=ni, intra=k))


def _flat_cfg(n, tile_m=8):
    return fc.CollectiveMatmulConfig(
        axis_name="data", axis_size=n, backend="lax", tile_m=tile_m,
        min_shard_bytes=0, interpret=True)


# ---------------------------------------------------------------------------
# forward parity: hier all_gather_matmul / matmul_reduce_scatter
# ---------------------------------------------------------------------------

def _ag_inputs(dtype, transpose_w, M, K, N):
    rng = np.random.RandomState(0)
    x = jnp.asarray(rng.randn(M, N if transpose_w else K)
                    .astype(np.float32) * 0.1, dtype)
    w = jnp.asarray(rng.randn(K, N).astype(np.float32) * 0.1, dtype)
    ref = x.astype(jnp.float32) @ \
        (w.T if transpose_w else w).astype(jnp.float32)
    return x, w, np.asarray(ref)


def _run_hier_ag(ni, k, dtype, shard_dim, transpose_w, backend="lax",
                 M=32, K=48, N=64, tile_m=8):
    n = ni * k
    mesh = _split_mesh(ni, k)
    x, w, ref = _ag_inputs(dtype, transpose_w, M, K, N)
    cfg = _hier_cfg(ni, k, backend, tile_m)

    def f(x_l, w_l):
        return fc.all_gather_matmul(
            x_l, w_l, shard_dim=shard_dim, axis_name=("di", "dt"),
            axis_size=n, transpose_w=transpose_w, cfg=cfg,
            out_dtype=jnp.float32)

    wspec = P(("di", "dt"), None) if shard_dim == 0 \
        else P(None, ("di", "dt"))
    g = jax.jit(shard_map(f, mesh=mesh, in_specs=(P(), wspec),
                          out_specs=P(), check_vma=False))
    return np.asarray(g(x, w)), ref


def _run_flat_ag(n, dtype, shard_dim, transpose_w, M=32, K=48, N=64):
    mesh = _flat_mesh(n)
    x, w, _ = _ag_inputs(dtype, transpose_w, M, K, N)
    cfg = _flat_cfg(n)

    def f(x_l, w_l):
        return fc.all_gather_matmul(
            x_l, w_l, shard_dim=shard_dim, axis_name="data", axis_size=n,
            transpose_w=transpose_w, cfg=cfg, out_dtype=jnp.float32)

    wspec = P("data", None) if shard_dim == 0 else P(None, "data")
    g = jax.jit(shard_map(f, mesh=mesh, in_specs=(P(), wspec),
                          out_specs=P(), check_vma=False))
    return np.asarray(g(x, w))


@pytest.mark.parametrize("ni,k", SPLITS)
@pytest.mark.parametrize("shard_dim", [0, 1])
def test_hier_ag_matmul_matches_dense_and_flat(ni, k, shard_dim):
    out, ref = _run_hier_ag(ni, k, jnp.float32, shard_dim, False)
    flat = _run_flat_ag(ni * k, jnp.float32, shard_dim, False)
    np.testing.assert_allclose(out, ref, atol=2e-5)
    np.testing.assert_allclose(out, flat, atol=2e-5)


@pytest.mark.parametrize("ni,k", SPLITS)
def test_hier_ag_matmul_transpose_w(ni, k):
    out, ref = _run_hier_ag(ni, k, jnp.float32, 1, True)
    np.testing.assert_allclose(out, ref, atol=2e-5)


def test_hier_ag_matmul_bf16():
    out, ref = _run_hier_ag(2, 4, jnp.bfloat16, 0, False)
    np.testing.assert_allclose(out, ref, atol=5e-2)


def test_hier_ag_matmul_uneven_chunks():
    # K=56 over n=8 -> 7-wide shards; tile_m=7 exercises the divisor
    # clamp inside the per-block intra rings
    out, ref = _run_hier_ag(2, 4, jnp.float32, 0, False,
                            M=24, K=56, N=40, tile_m=7)
    np.testing.assert_allclose(out, ref, atol=2e-5)


def test_hier_ag_matmul_fused_backend_routes_to_lax():
    # pallas remote DMA cannot address a two-named-axis env, so a
    # "fused" backend under a hierarchy must still lower (via the lax
    # intra ring) instead of crashing in dma_start
    out, ref = _run_hier_ag(2, 4, jnp.float32, 0, False, backend="fused")
    np.testing.assert_allclose(out, ref, atol=2e-5)


def _rs_inputs(dtype, M, K, N):
    rng = np.random.RandomState(1)
    lhs = jnp.asarray(rng.randn(M, K).astype(np.float32) * 0.1, dtype)
    rhs = jnp.asarray(rng.randn(M, N).astype(np.float32) * 0.1, dtype)
    return lhs, rhs


def _run_hier_rs(ni, k, dtype, shard_dim, backend="lax",
                 M=32, K=48, N=64):
    n = ni * k
    mesh = _split_mesh(ni, k)
    lhs, rhs = _rs_inputs(dtype, M, K, N)
    # identical local operands -> the SUM over the axis is n * dense
    ref = np.asarray(lhs.astype(jnp.float32).T
                     @ rhs.astype(jnp.float32)) * n
    cfg = _hier_cfg(ni, k, backend)

    def f(l, r):
        return fc.matmul_reduce_scatter(
            l, r, shard_dim=shard_dim, axis_name=("di", "dt"),
            axis_size=n, cfg=cfg)

    out_spec = P(("di", "dt"), None) if shard_dim == 0 \
        else P(None, ("di", "dt"))
    g = jax.jit(shard_map(f, mesh=mesh, in_specs=(P(), P()),
                          out_specs=out_spec, check_vma=False))
    return np.asarray(g(lhs, rhs)).astype(np.float32), ref


@pytest.mark.parametrize("ni,k", SPLITS)
@pytest.mark.parametrize("shard_dim", [0, 1])
def test_hier_mm_rs_matches_dense(ni, k, shard_dim):
    out, ref = _run_hier_rs(ni, k, jnp.float32, shard_dim)
    np.testing.assert_allclose(out, ref, atol=2e-5)


def test_hier_mm_rs_bf16():
    out, ref = _run_hier_rs(2, 4, jnp.bfloat16, 0, M=24, K=32, N=16)
    np.testing.assert_allclose(out, ref, atol=5e-2)


# ---------------------------------------------------------------------------
# custom-VJP parity vs dense autodiff (the prefetch grad contract)
# ---------------------------------------------------------------------------

@pytest.mark.parametrize("ni,k", SPLITS)
@pytest.mark.parametrize("shard_dim", [0, 1])
def test_hier_collective_matmul_vjp_matches_dense(ni, k, shard_dim):
    n, M, K, N = ni * k, 16, 32, 24
    mesh = _split_mesh(ni, k)
    rng = np.random.RandomState(2)
    x = rng.randn(n * M, K).astype(np.float32) * 0.1
    w = rng.randn(K, N).astype(np.float32) * 0.1
    cfg = _hier_cfg(ni, k)

    def local_loss(x_l, w_l):
        y = fc.collective_matmul(x_l, w_l, shard_dim=shard_dim,
                                 axis_name=("di", "dt"), axis_size=n,
                                 cfg=cfg)
        return jnp.sum(y.astype(jnp.float32) ** 2)

    def f(x_l, w_l):
        loss = local_loss(x_l, w_l)
        gx, gw = jax.grad(local_loss, argnums=(0, 1))(x_l, w_l)
        return jax.lax.psum(loss, ("di", "dt")), gx, gw

    wspec = P(("di", "dt"), None) if shard_dim == 0 \
        else P(None, ("di", "dt"))
    g = jax.jit(shard_map(f, mesh=mesh,
                          in_specs=(P(("di", "dt"), None), wspec),
                          out_specs=(P(), P(("di", "dt"), None), wspec),
                          check_vma=False))
    loss, gx, gw = g(jnp.asarray(x), jnp.asarray(w))

    def ref_loss(x_r, w_r):
        return jnp.sum((x_r @ w_r) ** 2)

    rl = ref_loss(jnp.asarray(x), jnp.asarray(w))
    rgx, rgw = jax.grad(ref_loss, argnums=(0, 1))(
        jnp.asarray(x), jnp.asarray(w))
    # dW comes back as the SUM over the whole split axis — the
    # two-level reduce-scatter must land the same total as the flat ring
    np.testing.assert_allclose(float(loss), float(rl), rtol=1e-5)
    np.testing.assert_allclose(np.asarray(gx), np.asarray(rgx),
                               atol=2e-5)
    np.testing.assert_allclose(np.asarray(gw), np.asarray(rgw),
                               atol=2e-4, rtol=1e-4)


def test_hier_collective_matmul_vjp_bf16():
    n, M, K, N = 8, 16, 32, 24
    mesh = _split_mesh(2, 4)
    rng = np.random.RandomState(5)
    x = (rng.randn(n * M, K) * 0.1).astype(np.float32)
    w = (rng.randn(K, N) * 0.1).astype(np.float32)
    cfg = _hier_cfg(2, 4)

    def local_loss(x_l, w_l):
        y = fc.collective_matmul(x_l, w_l, shard_dim=0,
                                 axis_name=("di", "dt"), axis_size=n,
                                 cfg=cfg)
        return jnp.sum(y.astype(jnp.float32) ** 2)

    def f(x_l, w_l):
        return jax.grad(local_loss, argnums=1)(x_l, w_l)

    g = jax.jit(shard_map(f, mesh=mesh,
                          in_specs=(P(("di", "dt"), None),
                                    P(("di", "dt"), None)),
                          out_specs=P(("di", "dt"), None),
                          check_vma=False))
    gw = g(jnp.asarray(x, jnp.bfloat16), jnp.asarray(w, jnp.bfloat16))
    assert gw.dtype == jnp.bfloat16
    rgw = jax.grad(lambda wr: jnp.sum((
        jnp.asarray(x, jnp.bfloat16).astype(jnp.float32)
        @ wr.astype(jnp.float32)) ** 2))(jnp.asarray(w, jnp.bfloat16))
    np.testing.assert_allclose(np.asarray(gw, np.float32),
                               np.asarray(rgw, np.float32),
                               rtol=2e-2, atol=2e-2)


def test_hier_world_mismatch_asserts():
    # hierarchy inter*intra must equal axis_size — a split that does not
    # cover the axis would silently drop shards
    cfg = fc.CollectiveMatmulConfig(
        axis_name=("di", "dt"), axis_size=8, backend="lax",
        min_shard_bytes=0, interpret=True,
        hierarchy=fc.RingHierarchy("di", "dt", 2, 2))
    mesh = _split_mesh(2, 4)

    def f(x_l, w_l):
        return fc.all_gather_matmul(
            x_l, w_l, shard_dim=0, axis_name=("di", "dt"), axis_size=8,
            cfg=cfg, out_dtype=jnp.float32)

    g = shard_map(f, mesh=mesh,
                  in_specs=(P(), P(("di", "dt"), None)),
                  out_specs=P(), check_vma=False)
    with pytest.raises(AssertionError):
        jax.jit(g)(jnp.zeros((16, 48)), jnp.zeros((48, 32)))


# ---------------------------------------------------------------------------
# overlap-layer two-level primitives
# ---------------------------------------------------------------------------

@pytest.mark.parametrize("ni,k", SPLITS + [(8, 1), (1, 8)])
def test_two_level_all_gather_natural_order(ni, k):
    n, c = ni * k, 6
    mesh = _split_mesh(ni, k)
    data = np.arange(n * c, dtype=np.float32).reshape(n, c)
    plan = ov.HierarchyPlan("di", "dt", ni, k)

    def f(sh):
        return ov.two_level_all_gather(sh[0], plan)

    g = jax.jit(shard_map(f, mesh=mesh,
                          in_specs=P(("di", "dt"), None),
                          out_specs=P(), check_vma=False))
    # every device must reassemble the full stack in natural data order
    np.testing.assert_array_equal(np.asarray(g(data)), data)


@pytest.mark.parametrize("ni,k", SPLITS)
def test_two_level_reduce_scatter_sum_matches_numpy(ni, k):
    n, c = ni * k, 5
    mesh = _split_mesh(ni, k)
    rng = np.random.RandomState(3)
    pieces = rng.randn(n, n, c).astype(np.float32)
    plan = ov.HierarchyPlan("di", "dt", ni, k)

    def f(p):
        return ov.two_level_reduce_scatter_sum(p[0], plan)[None]

    g = jax.jit(shard_map(f, mesh=mesh,
                          in_specs=P(("di", "dt"), None, None),
                          out_specs=P(("di", "dt"), None),
                          check_vma=False))
    np.testing.assert_allclose(np.asarray(g(pieces)),
                               pieces.sum(axis=0), rtol=1e-6, atol=1e-6)


def test_two_level_compressed_degenerate_matches_flat_primitive():
    """intra=1 collapses the two-level schedule to exactly the flat
    1-bit exchange: same piece order, same padding, same axis — the
    outputs and carried errors must be bit-identical."""
    n, c = 8, 16
    rng = np.random.RandomState(4)
    pieces = rng.randn(n, n, c).astype(np.float32)
    plan = ov.HierarchyPlan("di", "dt", 8, 1, compression="always")
    assert ov.two_level_error_numel(c, plan) == n * c
    err = np.zeros((n, n * c), np.float32)

    mesh = _split_mesh(8, 1)

    def f(p, e):
        out, ne = ov.two_level_reduce_scatter_compressed(p[0], e[0], plan)
        return out[None], ne[None]

    g = jax.jit(shard_map(
        f, mesh=mesh,
        in_specs=(P(("di", "dt"), None, None), P(("di", "dt"), None)),
        out_specs=(P(("di", "dt"), None), P(("di", "dt"), None)),
        check_vma=False))
    out_h, err_h = g(pieces, err)

    flat = _flat_mesh(n)

    def ff(p, e):
        out, ne = comp.compressed_reduce_scatter_sum(
            p[0].reshape(-1), e[0], "data")
        return out[None], ne[None]

    gf = jax.jit(shard_map(
        ff, mesh=flat,
        in_specs=(P("data", None, None), P("data", None)),
        out_specs=(P("data", None), P("data", None)),
        check_vma=False))
    out_f, err_f = gf(pieces, err)

    np.testing.assert_array_equal(np.asarray(out_h), np.asarray(out_f))
    np.testing.assert_array_equal(np.asarray(err_h), np.asarray(err_f))
    assert float(np.abs(np.asarray(err_h)).sum()) > 0


def test_two_level_compressed_error_feedback_converges():
    """Worker-error feedback: re-applying the compressed reduce on the
    SAME pieces with the carried residual must beat round 1 on average —
    the residual re-enters the next round, so the running mean of the
    outputs approaches the exact sum."""
    ni, k = 2, 4
    n, c, rounds = ni * k, 16, 8
    rng = np.random.RandomState(6)
    pieces = rng.randn(n, n, c).astype(np.float32)
    plan = ov.HierarchyPlan("di", "dt", ni, k, compression="always")
    err = np.zeros((n, ov.two_level_error_numel(c, plan)), np.float32)
    mesh = _split_mesh(ni, k)

    def f(p, e):
        out, ne = ov.two_level_reduce_scatter_compressed(p[0], e[0], plan)
        return out[None], ne[None]

    g = jax.jit(shard_map(
        f, mesh=mesh,
        in_specs=(P(("di", "dt"), None, None), P(("di", "dt"), None)),
        out_specs=(P(("di", "dt"), None), P(("di", "dt"), None)),
        check_vma=False))
    exact = pieces.sum(axis=0)
    outs = []
    e = jnp.asarray(err)
    for _ in range(rounds):
        out, e = g(pieces, e)
        outs.append(np.asarray(out))
    scale = np.linalg.norm(exact)
    first_err = np.linalg.norm(outs[0] - exact) / scale
    avg_err = np.linalg.norm(np.mean(outs, axis=0) - exact) / scale
    assert np.isfinite(first_err) and first_err > 0
    assert avg_err < first_err * 0.7, (avg_err, first_err)


# ---------------------------------------------------------------------------
# config composition + fallback latch
# ---------------------------------------------------------------------------

def _cfg_dict(gather, hierarchy=True, prefetch=True):
    d = {
        "train_batch_size": 8,
        "zero_optimization": {"stage": 3, "stage3_prefetch": prefetch,
                              "stage3_prefetch_gather": gather},
        "optimizer": {"type": "AdamW", "params": {"lr": 1e-3}},
    }
    if hierarchy:
        d["comm"] = {"hierarchy": {"slow_axis": 2,
                                   "compression": "always"}}
    return d


def test_hierarchy_prefetch_gather_fused_rejected():
    # "fused" hands the gather schedule to XLA, which cannot honor the
    # two-level link split — must fail loudly at config time
    with pytest.raises(DeepSpeedConfigError, match="fused"):
        DeepSpeedConfig(_cfg_dict("fused"), world_size=8)


@pytest.mark.parametrize("gather", ["ring", "fused_matmul"])
def test_hierarchy_prefetch_explicit_gathers_accepted(gather):
    cfg = DeepSpeedConfig(_cfg_dict(gather), world_size=8)
    assert cfg.comm_config.hierarchy.enabled
    assert cfg.zero_config.stage3_prefetch_gather == gather


def test_hierarchy_off_or_no_prefetch_allows_fused():
    DeepSpeedConfig(_cfg_dict("fused", hierarchy=False), world_size=8)
    DeepSpeedConfig(_cfg_dict("fused", prefetch=False), world_size=8)


def test_fallback_latch_once_per_axis_reason():
    topo.reset_fallback_latch()
    try:
        assert topo.latch_fallback("auto", "single process")
        # same (axis, reason) pair: latched, warn only once
        assert not topo.latch_fallback("auto", "single process")
        # distinct reason or axis latches independently
        assert topo.latch_fallback("auto", "axis size 1")
        assert topo.latch_fallback(3, "single process")
        assert not topo.latch_fallback(3, "single process")
        topo.reset_fallback_latch()
        assert topo.latch_fallback("auto", "single process")
    finally:
        topo.reset_fallback_latch()


# ---------------------------------------------------------------------------
# engine-level trajectory parity (single process, synthetic split)
# ---------------------------------------------------------------------------

def _gpt2_tiny():
    return GPT2Config(vocab_size=512, n_positions=64, n_embd=64,
                      n_layer=2, n_head=2, dtype=jnp.float32,
                      param_dtype=jnp.float32, scan_layers=True)


def _make_engine(hier, gather="ring", cm=None):
    cfg = {
        "train_batch_size": 8,
        "zero_optimization": {"stage": 3, "stage3_prefetch": True,
                              "stage3_prefetch_gather": gather,
                              "stage3_param_persistence_threshold": 0,
                              **({"collective_matmul": cm} if cm else {})},
        "optimizer": {"type": "AdamW", "params": {"lr": 1e-3}},
        "steps_per_print": 1000,
    }
    if hier is not None:
        cfg["comm"] = {"hierarchy": hier}
    mesh = make_mesh(MeshConfig(data=8), devices=jax.devices()[:8])
    engine, _, _, _ = dstpu.initialize(config=cfg, model=model_fn(),
                                       mesh=mesh)
    return engine


def model_fn():
    return GPT2LMHeadModel(_gpt2_tiny())


def _batch():
    return {"input_ids": np.random.RandomState(0).randint(
        0, 512, (8, 64)).astype(np.int32)}


def test_engine_hier_exact_matches_flat():
    """comm.hierarchy with compression 'never' is a pure reschedule of
    the stage-3 stream — the training trajectory must match the flat
    engine to fp32 reduction-order noise."""
    batch = _batch()
    eng_h = _make_engine({"slow_axis": 2, "compression": "never"})
    l_h = [float(eng_h.train_batch(batch)) for _ in range(3)]
    eng_f = _make_engine(None)
    l_f = [float(eng_f.train_batch(batch)) for _ in range(3)]
    np.testing.assert_allclose(l_h, l_f, rtol=2e-5, atol=1e-5)
    for (pa, a), (pb, b) in zip(
            jax.tree_util.tree_leaves_with_path(eng_h.state.params),
            jax.tree_util.tree_leaves_with_path(eng_f.state.params)):
        np.testing.assert_allclose(
            np.asarray(a, np.float32), np.asarray(b, np.float32),
            rtol=2e-5, atol=1e-5, err_msg=jax.tree_util.keystr(pa))


def test_engine_hier_compressed_wire_reduction():
    """The acceptance bar of ISSUE 16 as a pinned test: the compressed
    slow hop must cut modeled inter-host bytes by >= 2x vs the flat-ring
    baseline on a 2x4 synthetic split, while training stays finite and
    the error residuals ride the optimizer state."""
    batch = _batch()
    eng = _make_engine({"slow_axis": 2, "compression": "always"})
    losses = [float(eng.train_batch(batch)) for _ in range(2)]
    assert np.isfinite(losses).all()
    assert losses[1] < losses[0]
    assert any(key.startswith("pf_") for key in eng.state.opt_state)
    wire = eng._pf_wire_model
    assert 0 < wire["inter"] < wire["inter_uncompressed"]
    assert wire["inter_uncompressed"] / wire["inter"] >= 2.0, wire
    counters = eng.telemetry.snapshot("comm/")["counters"]
    assert counters["comm/bytes_on_wire/inter"] > 0
    assert counters["comm/bytes_on_wire/inter_uncompressed"] \
        > counters["comm/bytes_on_wire/inter"]


@pytest.mark.slow
def test_engine_hier_fused_matmul_exact_matches_flat():
    batch = _batch()
    cm = {"backend": "lax", "min_shard_bytes": 0}
    eng_h = _make_engine({"slow_axis": 2, "compression": "never"},
                         gather="fused_matmul", cm=cm)
    l_h = [float(eng_h.train_batch(batch)) for _ in range(3)]
    eng_f = _make_engine(None, gather="fused_matmul", cm=cm)
    l_f = [float(eng_f.train_batch(batch)) for _ in range(3)]
    np.testing.assert_allclose(l_h, l_f, rtol=2e-5, atol=1e-5)
