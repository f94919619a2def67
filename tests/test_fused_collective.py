"""Tile-granularity fused matmul+collective kernels (ISSUE 8,
ops/pallas/fused_collective.py).

Numerics contract: both kernels must reproduce a plain ``jnp.einsum``
over the gathered full weight to fp32 partial-sum rounding — across
backends (the lax decomposed ring and the pallas kernels in interpret
mode), shard dims, transposes, dtypes (fp32/bf16), uneven chunk
shapes, and mesh sizes 2/4/8. The custom-VJP pairing must match dense
autodiff, with dW returned as the shard-shaped SUM over the axis (the
prefetch pipeline's sharded-leaf contract). The compiled lowering
(``interpret=False``) is the slow/skipif-gated test at the bottom; the
TPU compiler currently refuses both kernels (tests/test_tpu_compile.py).
"""

import numpy as np
import pytest
import jax
import jax.numpy as jnp
from jax.sharding import Mesh, PartitionSpec as P

from jax import shard_map
from deepspeed_tpu.ops.pallas import fused_collective as fc


def _mesh(n):
    devs = jax.devices()
    assert len(devs) >= n
    return Mesh(np.asarray(devs[:n]), ("data",))


def _cfg(n, backend, tile_m=8, interpret=True):
    return fc.CollectiveMatmulConfig(
        axis_name="data", axis_size=n, backend=backend, tile_m=tile_m,
        min_shard_bytes=0, interpret=interpret)


def _run_ag(n, dtype, shard_dim, transpose_w, backend, M=32, K=48, N=64,
            tile_m=8, interpret=True):
    """all_gather_matmul vs einsum over the gathered weight; returns
    max abs error."""
    mesh = _mesh(n)
    rng = np.random.RandomState(0)
    x = jnp.asarray(rng.randn(M, N if transpose_w else K)
                    .astype(np.float32) * 0.1, dtype)
    w = jnp.asarray(rng.randn(K, N).astype(np.float32) * 0.1, dtype)
    ref = x.astype(jnp.float32) @ \
        (w.T if transpose_w else w).astype(jnp.float32)
    cfg = _cfg(n, backend, tile_m, interpret)

    def f(x_l, w_l):
        return fc.all_gather_matmul(
            x_l, w_l, shard_dim=shard_dim, axis_name="data", axis_size=n,
            transpose_w=transpose_w, cfg=cfg, out_dtype=jnp.float32)

    wspec = P("data", None) if shard_dim == 0 else P(None, "data")
    g = jax.jit(shard_map(f, mesh=mesh, in_specs=(P(), wspec),
                          out_specs=P(), check_vma=False))
    return float(jnp.max(jnp.abs(g(x, w) - ref)))


def _run_rs(n, dtype, shard_dim, backend, M=32, K=48, N=64, tile_m=8):
    """matmul_reduce_scatter vs the dense lhs^T @ rhs (x axis_size:
    identical local operands, so the SUM over the axis is n * dense);
    returns max abs error on the reassembled full gradient."""
    mesh = _mesh(n)
    rng = np.random.RandomState(1)
    lhs = jnp.asarray(rng.randn(M, K).astype(np.float32) * 0.1, dtype)
    rhs = jnp.asarray(rng.randn(M, N).astype(np.float32) * 0.1, dtype)
    ref = lhs.astype(jnp.float32).T @ rhs.astype(jnp.float32) * n
    cfg = _cfg(n, backend)

    def f(l, r):
        return fc.matmul_reduce_scatter(
            l, r, shard_dim=shard_dim, axis_name="data", axis_size=n,
            cfg=cfg)

    out_spec = P("data", None) if shard_dim == 0 else P(None, "data")
    g = jax.jit(shard_map(f, mesh=mesh, in_specs=(P(), P()),
                          out_specs=out_spec, check_vma=False))
    return float(jnp.max(jnp.abs(g(lhs, rhs) - ref)))


# ---------------------------------------------------------------------------
# all-gather+matmul numerics
# ---------------------------------------------------------------------------

@pytest.mark.parametrize("shard_dim", [0, 1])
@pytest.mark.parametrize("transpose_w", [False, True])
@pytest.mark.parametrize("backend", ["lax", "fused"])
def test_ag_matmul_matches_einsum(shard_dim, transpose_w, backend):
    err = _run_ag(4, jnp.float32, shard_dim, transpose_w, backend)
    assert err < 1e-5, err


@pytest.mark.parametrize("n", [2, 8])
@pytest.mark.parametrize("backend", ["lax", "fused"])
def test_ag_matmul_mesh_sizes(n, backend):
    assert _run_ag(n, jnp.float32, 0, False, backend) < 1e-5
    assert _run_ag(n, jnp.float32, 1, False, backend) < 1e-5


@pytest.mark.parametrize("backend", ["lax", "fused"])
def test_ag_matmul_bf16(backend):
    # bf16 inputs, fp32 accumulation: tolerance is bf16 input rounding
    assert _run_ag(4, jnp.bfloat16, 0, False, backend) < 5e-2
    assert _run_ag(4, jnp.bfloat16, 1, True, backend) < 5e-2


@pytest.mark.parametrize("backend", ["lax", "fused"])
def test_ag_matmul_uneven_chunks(backend):
    # K=56 over n=8 -> 7-wide chunks; M=24 with tile_m=7 exercises the
    # divisor clamp (7 does not divide 24; largest divisor <= 7 is 6)
    assert _run_ag(8, jnp.float32, 0, False, backend,
                   M=24, K=56, N=40, tile_m=7) < 1e-5


# ---------------------------------------------------------------------------
# matmul+reduce-scatter numerics
# ---------------------------------------------------------------------------

@pytest.mark.parametrize("shard_dim", [0, 1])
@pytest.mark.parametrize("backend", ["lax", "fused"])
def test_mm_rs_matches_dense(shard_dim, backend):
    assert _run_rs(4, jnp.float32, shard_dim, backend) < 1e-5


@pytest.mark.parametrize("backend", ["lax", "fused"])
def test_mm_rs_mesh_sizes_and_bf16(backend):
    assert _run_rs(2, jnp.float32, 0, backend) < 1e-5
    assert _run_rs(8, jnp.float32, 1, backend) < 1e-5
    assert _run_rs(4, jnp.bfloat16, 0, backend, M=24, K=32, N=16) < 5e-2


# ---------------------------------------------------------------------------
# custom-VJP pairing (the prefetch pipeline's grad contract)
# ---------------------------------------------------------------------------

@pytest.mark.parametrize("shard_dim", [0, 1])
@pytest.mark.parametrize("backend", ["lax", "fused"])
def test_collective_matmul_vjp_matches_dense(shard_dim, backend):
    n, M, K, N = 4, 16, 32, 24
    mesh = _mesh(n)
    rng = np.random.RandomState(2)
    x = rng.randn(n * M, K).astype(np.float32) * 0.1
    w = rng.randn(K, N).astype(np.float32) * 0.1
    cfg = _cfg(n, backend)

    def local_loss(x_l, w_l):
        y = fc.collective_matmul(x_l, w_l, shard_dim=shard_dim,
                                 axis_name="data", axis_size=n, cfg=cfg)
        return jnp.sum(y.astype(jnp.float32) ** 2)

    def f(x_l, w_l):
        loss = local_loss(x_l, w_l)
        gx, gw = jax.grad(local_loss, argnums=(0, 1))(x_l, w_l)
        return jax.lax.psum(loss, "data"), gx, gw

    wspec = P("data", None) if shard_dim == 0 else P(None, "data")
    g = jax.jit(shard_map(f, mesh=mesh,
                          in_specs=(P("data", None), wspec),
                          out_specs=(P(), P("data", None), wspec),
                          check_vma=False))
    loss, gx, gw = g(jnp.asarray(x), jnp.asarray(w))

    def ref_loss(x_r, w_r):
        return jnp.sum((x_r @ w_r) ** 2)

    rl = ref_loss(jnp.asarray(x), jnp.asarray(w))
    rgx, rgw = jax.grad(ref_loss, argnums=(0, 1))(
        jnp.asarray(x), jnp.asarray(w))
    # dW comes back as the SUM over the axis (each device contributed
    # its local batch rows exactly once -> reassembled == dense total)
    np.testing.assert_allclose(float(loss), float(rl), rtol=1e-5)
    np.testing.assert_allclose(np.asarray(gx), np.asarray(rgx),
                               atol=2e-5)
    np.testing.assert_allclose(np.asarray(gw), np.asarray(rgw),
                               atol=2e-4, rtol=1e-4)


@pytest.mark.parametrize("backend", ["lax", "fused"])
def test_collective_matmul_vjp_bf16(backend):
    """bf16 primal / bf16 dW contract: the matmul+RS accumulates the
    true partial sums in fp32 and rounds ONCE to the param dtype on
    output — dW must land within bf16 rounding of the dense fp32
    gradient (the prefetch fused-leaf contract under grad_dtype=bf16)."""
    n, M, K, N = 4, 16, 32, 24
    mesh = _mesh(n)
    rng = np.random.RandomState(5)
    x = (rng.randn(n * M, K) * 0.1).astype(np.float32)
    w = (rng.randn(K, N) * 0.1).astype(np.float32)
    cfg = _cfg(n, backend)

    def local_loss(x_l, w_l):
        y = fc.collective_matmul(x_l, w_l, shard_dim=0,
                                 axis_name="data", axis_size=n, cfg=cfg)
        return jnp.sum(y.astype(jnp.float32) ** 2)

    def f(x_l, w_l):
        gw = jax.grad(local_loss, argnums=1)(x_l, w_l)
        return gw

    g = jax.jit(shard_map(f, mesh=mesh,
                          in_specs=(P("data", None), P("data", None)),
                          out_specs=P("data", None), check_vma=False))
    gw = g(jnp.asarray(x, jnp.bfloat16), jnp.asarray(w, jnp.bfloat16))
    assert gw.dtype == jnp.bfloat16
    rgw = jax.grad(lambda wr: jnp.sum((
        jnp.asarray(x, jnp.bfloat16).astype(jnp.float32)
        @ wr.astype(jnp.float32)) ** 2))(jnp.asarray(w, jnp.bfloat16))
    np.testing.assert_allclose(np.asarray(gw, np.float32),
                               np.asarray(rgw, np.float32),
                               rtol=2e-2, atol=2e-2)


# ---------------------------------------------------------------------------
# dispatch plumbing
# ---------------------------------------------------------------------------

def test_infer_shard_dim():
    assert fc.infer_shard_dim((16, 8), 16, 8, 4) is None     # full
    assert fc.infer_shard_dim((4, 8), 16, 8, 4) == 0
    assert fc.infer_shard_dim((16, 2), 16, 8, 4) == 1
    with pytest.raises(ValueError):
        fc.infer_shard_dim((5, 8), 16, 8, 4)


def test_gather_scope_nesting():
    assert fc.gather_ctx() is None
    c1 = fc.CollectiveMatmulConfig(axis_size=2)
    c2 = fc.CollectiveMatmulConfig(axis_size=4)
    with fc.gather_scope(c1):
        assert fc.gather_ctx() is c1
        with fc.gather_scope(c2):
            assert fc.gather_ctx() is c2
        assert fc.gather_ctx() is c1
    assert fc.gather_ctx() is None


def test_backend_validation():
    with pytest.raises(ValueError):
        fc.all_gather_matmul(
            jnp.zeros((4, 8)), jnp.zeros((4, 4)), shard_dim=0,
            axis_name="data", axis_size=2,
            cfg=fc.CollectiveMatmulConfig(backend="nope"))


def test_auto_backend_feasibility_gates():
    """backend="auto" must route through the lax ring when the pallas
    kernel is infeasible: the contracting kernel's VMEM chunk stash
    over budget, or unaligned lane minors on compiled (non-interpret)
    hardware. The gates are pure host math — pinned directly."""
    cfg = fc.CollectiveMatmulConfig(vmem_budget_bytes=8 << 20)
    # (1024, 4096) fp32 shard x n=4 -> 64 MiB full W: over budget when
    # contracting (full-W stash); the non-contracting kernel's 2
    # chunk-sized comm slots (2 x 16 MiB) are over budget too
    assert fc._ag_auto_fallback(cfg, (1024, 4096), 4, True, 4,
                                True) == "vmem_budget"
    assert fc._ag_auto_fallback(cfg, (1024, 4096), 4, False, 4,
                                True) == "vmem_budget"
    # (256, 1024) fp32 shard -> 2 x 1 MiB comm slots: inside budget
    assert fc._ag_auto_fallback(cfg, (256, 1024), 4, False, 4,
                                True) is None
    # unaligned minors: fine in interpret, unlower on real Mosaic —
    # BOTH shard dims count (each is a lane minor in some variant of
    # the fwd/dx/dW kernel family, e.g. a dim-0 shard's row count is
    # the x-block minor of the contracting forward)
    assert fc._ag_auto_fallback(cfg, (128, 120), 4, False, 4,
                                True) is None
    assert fc._ag_auto_fallback(cfg, (128, 120), 4, False, 4,
                                False) == "lane_alignment"
    assert fc._ag_auto_fallback(cfg, (96, 2304), 4, False, 4,
                                False) == "lane_alignment"
    assert fc._ag_auto_fallback(cfg, (128, 256), 4, False, 4,
                                False) is None
    # RS: acc + 2 carry slots of fp32 shard scratch
    assert fc._rs_auto_fallback(cfg, 8192, 4096, True, 4,
                                True) == "vmem_budget"
    assert fc._rs_auto_fallback(cfg, 512, 256, True, 4, True) is None
    assert fc._rs_auto_fallback(cfg, 512, 240, True, 4,
                                False) == "lane_alignment"
    assert fc._rs_auto_fallback(cfg, 520, 256, True, 4,
                                False) == "lane_alignment"
    assert fc._rs_auto_fallback(cfg, 512, 256, True, 4, False) is None


def test_single_device_bypasses_collectives():
    # n == 1: plain dot, no axis binding required
    x = jnp.asarray(np.random.RandomState(0).randn(4, 8), jnp.float32)
    w = jnp.asarray(np.random.RandomState(1).randn(8, 6), jnp.float32)
    y = fc.all_gather_matmul(x, w, shard_dim=0, axis_name="data",
                             axis_size=1)
    np.testing.assert_allclose(np.asarray(y), np.asarray(x @ w),
                               atol=1e-6)
    g = fc.matmul_reduce_scatter(x, x, shard_dim=0,
                                 axis_name="data", axis_size=1)
    np.testing.assert_allclose(np.asarray(g), np.asarray(x.T @ x),
                               atol=1e-6)


# ---------------------------------------------------------------------------
# CollectiveDense (models/gpt2.py) — the body-side consumer
# ---------------------------------------------------------------------------

def test_collective_dense_is_dense_outside_scope():
    import flax.linen as nn
    from deepspeed_tpu.models.gpt2 import CollectiveDense
    x = jnp.asarray(np.random.RandomState(0).randn(4, 16), jnp.float32)
    d_ref = nn.Dense(24, dtype=jnp.float32)
    d_col = CollectiveDense(24, dtype=jnp.float32)
    p_ref = d_ref.init(jax.random.PRNGKey(0), x)
    p_col = d_col.init(jax.random.PRNGKey(0), x)
    assert jax.tree_util.tree_structure(p_ref) == \
        jax.tree_util.tree_structure(p_col)
    np.testing.assert_array_equal(np.asarray(d_ref.apply(p_ref, x)),
                                  np.asarray(d_col.apply(p_col, x)))


def test_collective_dense_consumes_shard_in_scope():
    from deepspeed_tpu.models.gpt2 import CollectiveDense
    n = 4
    mesh = _mesh(n)
    x = jnp.asarray(np.random.RandomState(0).randn(4, 16), jnp.float32)
    dense = CollectiveDense(24, dtype=jnp.float32)
    params = dense.init(jax.random.PRNGKey(0), x)["params"]
    full = dense.apply({"params": params}, x)
    cfg = _cfg(n, "lax")

    def f(x_l, k_shard, b):
        with fc.gather_scope(cfg):
            return dense.apply(
                {"params": {"kernel": k_shard, "bias": b}}, x_l)

    g = jax.jit(shard_map(f, mesh=mesh,
                          in_specs=(P(), P(None, "data"), P()),
                          out_specs=P(), check_vma=False))
    out = g(x, params["kernel"], params["bias"])
    np.testing.assert_allclose(np.asarray(out), np.asarray(full),
                               atol=2e-6)


# ---------------------------------------------------------------------------
# compiled lowering on a multi-chip TPU host (ROADMAP S2)
# ---------------------------------------------------------------------------

@pytest.mark.slow
@pytest.mark.skipif(jax.default_backend() != "tpu",
                    reason="runs on a multi-chip TPU host only (compiled "
                           "in-kernel RDMA ring + neighbor credits): "
                           "`chiprun --chips 4 -- python -m pytest "
                           "--noconftest -m slow -k real_chip "
                           "tests/test_fused_collective.py`. Not worth "
                           "the chip time until the strict xfails in "
                           "tests/test_tpu_compile.py turn — the TPU "
                           "compiler refuses both kernels today.")
def test_fused_kernels_real_chip_parity():
    """interpret=False parity for BOTH kernels on a real slice: the
    compiled Mosaic ring (RDMA + credit semaphores, which interpret
    mode skips) against the lax decomposed-ring reference."""
    n = len(jax.devices())
    assert n >= 2
    for shard_dim in (0, 1):
        e_f = _run_ag(n, jnp.float32, shard_dim, False, "fused",
                      M=256, K=128 * n, N=256, tile_m=128,
                      interpret=False)
        assert e_f < 1e-4, (shard_dim, e_f)
    mesh = _mesh(n)
    rng = np.random.RandomState(3)
    lhs = jnp.asarray(rng.randn(256, 128 * n).astype(np.float32))
    rhs = jnp.asarray(rng.randn(256, 256).astype(np.float32))
    for shard_dim in (0, 1):
        outs = {}
        for backend in ("lax", "fused"):
            cfg = fc.CollectiveMatmulConfig(
                "data", n, backend, 128, 0, False)

            def f(l, r):
                return fc.matmul_reduce_scatter(
                    l, r, shard_dim=shard_dim, axis_name="data",
                    axis_size=n, cfg=cfg)

            out_spec = P("data", None) if shard_dim == 0 \
                else P(None, "data")
            g = jax.jit(shard_map(f, mesh=mesh, in_specs=(P(), P()),
                                  out_specs=out_spec, check_vma=False))
            outs[backend] = np.asarray(g(lhs, rhs))
        np.testing.assert_allclose(outs["fused"], outs["lax"],
                                   rtol=1e-5, atol=1e-4)
