"""Pallas kernel numerics vs jnp reference — the reference's
test_cuda_forward.py / test_cuda_backward.py methodology (CUDA-vs-HF becomes
Pallas-interpret-vs-jnp, SURVEY §4)."""

import functools

import numpy as np
import jax
import jax.numpy as jnp
import pytest

from deepspeed_tpu.ops.attention import (from_head_major,
                                         reference_attention, to_head_major)
from deepspeed_tpu.ops.pallas.flash_attention import flash_attention
from deepspeed_tpu.ops.pallas.blocksparse import blocksparse_attention
from tests.hlo_text import pallas_element_rows, pallas_grids


def _qkv(shape=(2, 2, 128, 32), seed=0, dtype=jnp.float32):
    rng = jax.random.PRNGKey(seed)
    ks = jax.random.split(rng, 3)
    return tuple(jax.random.normal(k, shape, dtype) for k in ks)


@pytest.mark.parametrize("causal", [False, True])
def test_flash_forward_matches_reference(causal):
    q, k, v = _qkv()
    out = flash_attention(q, k, v, causal=causal, interpret=True, block_q=64,
                          block_k=64)
    ref = reference_attention(q, k, v, causal=causal)
    np.testing.assert_allclose(np.asarray(out), np.asarray(ref),
                               rtol=2e-4, atol=2e-5)


@pytest.mark.parametrize("causal", [False, True])
def test_flash_backward_matches_reference(causal):
    q, k, v = _qkv(shape=(1, 2, 128, 16))

    def loss_flash(q, k, v):
        return jnp.sum(flash_attention(q, k, v, causal=causal, interpret=True,
                                       block_q=64, block_k=64) ** 2)

    def loss_ref(q, k, v):
        return jnp.sum(reference_attention(q, k, v, causal=causal) ** 2)

    g_flash = jax.grad(loss_flash, argnums=(0, 1, 2))(q, k, v)
    g_ref = jax.grad(loss_ref, argnums=(0, 1, 2))(q, k, v)
    for gf, gr in zip(g_flash, g_ref):
        np.testing.assert_allclose(np.asarray(gf), np.asarray(gr),
                                   rtol=5e-3, atol=5e-4)


def test_flash_uneven_shape_falls_back():
    q, k, v = _qkv(shape=(1, 1, 100, 16))
    out = flash_attention(q, k, v, causal=True, interpret=True)
    ref = reference_attention(q, k, v, causal=True)
    np.testing.assert_allclose(np.asarray(out), np.asarray(ref),
                               rtol=2e-4, atol=2e-5)


def test_flash_bf16():
    q, k, v = _qkv(dtype=jnp.bfloat16)
    out = flash_attention(q, k, v, causal=True, interpret=True, block_q=64,
                          block_k=64)
    ref = reference_attention(q, k, v, causal=True)
    np.testing.assert_allclose(np.asarray(out, np.float32),
                               np.asarray(ref, np.float32),
                               rtol=5e-2, atol=5e-2)


def test_blocksparse_kernel_dense_layout_matches_reference():
    q, k, v = _qkv(shape=(1, 2, 128, 16))
    layout = np.ones((2, 4, 4), np.int64)  # block 32, fully dense
    out = blocksparse_attention(q, k, v, layout, block=32, interpret=True)
    ref = reference_attention(q, k, v)
    np.testing.assert_allclose(np.asarray(out), np.asarray(ref),
                               rtol=2e-4, atol=2e-5)


def test_blocksparse_kernel_respects_layout():
    q, k, v = _qkv(shape=(1, 1, 128, 16), seed=3)
    layout = np.zeros((1, 4, 4), np.int64)
    for i in range(4):
        layout[0, i, i] = 1
    out = blocksparse_attention(q, k, v, layout, block=32, interpret=True)
    # block-diagonal attention == attention computed per 32-wide chunk
    for i in range(4):
        sl = slice(32 * i, 32 * (i + 1))
        ref = reference_attention(q[:, :, sl], k[:, :, sl], v[:, :, sl])
        np.testing.assert_allclose(np.asarray(out[:, :, sl]), np.asarray(ref),
                                   rtol=2e-4, atol=2e-5)


def test_chunked_kernels_match_reference():
    """The long-S chunked kernels (third grid dim, revisited fp32 output
    accumulation) must match the jnp reference fwd AND grads — forced via
    chunk= on small shapes so CI covers the same code path the S*D > 256k
    dispatch takes on hardware."""
    from deepspeed_tpu.ops.attention import reference_attention
    rng = np.random.RandomState(0)
    B, H, S, D = 2, 2, 256, 16
    q = jnp.asarray(rng.randn(B, H, S, D), jnp.float32)
    k = jnp.asarray(rng.randn(B, H, S, D), jnp.float32)
    v = jnp.asarray(rng.randn(B, H, S, D), jnp.float32)
    for causal in (False, True):
        def loss_k(q, k, v):
            o = flash_attention(q, k, v, causal=causal, block_q=64,
                                block_k=64, chunk=128, interpret=True)
            return jnp.sum(jnp.sin(o))

        def loss_r(q, k, v):
            return jnp.sum(jnp.sin(reference_attention(q, k, v,
                                                       causal=causal)))

        v1, g1 = jax.value_and_grad(loss_k, argnums=(0, 1, 2))(q, k, v)
        v2, g2 = jax.value_and_grad(loss_r, argnums=(0, 1, 2))(q, k, v)
        np.testing.assert_allclose(v1, v2, rtol=2e-5, atol=2e-5)
        for a, b, name in zip(g1, g2, "qkv"):
            np.testing.assert_allclose(np.asarray(a), np.asarray(b),
                                       rtol=2e-4, atol=2e-5,
                                       err_msg=f"causal={causal} d{name}")


def test_chunked_causal_kernels_take_a_kv_group_of_seven():
    """28 / 4 heads' ratio (SmallThinker): the chunked causal kernels with
    seven query heads a KV head, forward and all three gradients, q and k
    handed over as projected (no rotation in front)."""
    from deepspeed_tpu.ops.attention import reference_attention
    q, _, _ = _qkv((1, 14, 256, 32), seed=7)
    _, k, v = _qkv((1, 2, 256, 32), seed=8)

    def both(attend):
        return jax.value_and_grad(
            lambda *a: jnp.sum(jnp.sin(attend(*a))), argnums=(0, 1, 2))(
            q, k, v)

    v1, g1 = both(functools.partial(flash_attention, causal=True, block_q=64,
                                    block_k=64, chunk=128, interpret=True))
    v2, g2 = both(functools.partial(reference_attention, causal=True))
    np.testing.assert_allclose(v1, v2, rtol=2e-5, atol=2e-5)
    assert g1[1].shape == (1, 2, 256, 32)
    for a, b, name in zip(g1, g2, "qkv"):
        np.testing.assert_allclose(np.asarray(a), np.asarray(b), rtol=2e-4,
                                   atol=2e-5, err_msg=f"d{name}")


def test_auto_chunk_dispatch(monkeypatch):
    """The S*D*itemsize budget dispatch really selects the chunked path
    (and its chunk satisfies the divisibility constraints) — exercised in
    CI by shrinking the budget instead of allocating 32k sequences."""
    import importlib
    fa = importlib.import_module(
        "deepspeed_tpu.ops.pallas.flash_attention")
    calls = {}
    real = fa._flash_fwd_chunked

    def spy(q, k, v, scale, causal, block_q, block_k, chunk, interpret):
        calls["chunk"] = chunk
        return real(q, k, v, scale, causal, block_q, block_k, chunk,
                    interpret)

    monkeypatch.setattr(fa, "_flash_fwd_chunked", spy)
    # dispatch cutoff shrunk so S=512 routes to the chunked path, and the
    # one chunk budget to 128 rows of K + V, each a 128-lane float32 tile
    # wide in VMEM whatever D is -> candidate 128 picked
    monkeypatch.setattr(fa, "_UNCHUNKED_ROW_BYTES", 128 * 2 * 16 * 4)
    monkeypatch.setattr(fa, "_CHUNK_BYTES", 128 * 2 * 128 * 4)
    from deepspeed_tpu.ops.attention import reference_attention
    rng = np.random.RandomState(1)
    q = jnp.asarray(rng.randn(1, 2, 512, 16), jnp.float32)
    o = fa.flash_attention(q, q, q, causal=True, block_q=64, block_k=64,
                           interpret=True)
    assert calls.get("chunk") == 128, calls
    ref = reference_attention(q, q, q, causal=True)
    np.testing.assert_allclose(np.asarray(o), np.asarray(ref),
                               rtol=2e-5, atol=2e-5)


@pytest.mark.parametrize("S,H,Hkv,D,Dv,dtype,chunk", [
    (16384, 6, 1, 128, 128, jnp.bfloat16, 4096),    # Laguna, SmallThinker,
    (16384, 32, 32, 192, 128, jnp.bfloat16, 4096),  # Nemotron; Kanana-2
    (8192, 8, 1, 256, 256, jnp.bfloat16, 2048),     # Qwen3-Next
    (4096, 2, 2, 128, 128, jnp.bfloat16, 4096),     # OLMoE: chunk = S
    (32768, 2, 2, 64, 64, jnp.bfloat16, 4096),      # 64 lanes pad to 128
    (16384, 2, 2, 128, 128, jnp.float32, 2048),     # float32: half the rows
    (16384, 2, 2, 192, 128, jnp.float32, 2048),
    (8192, 2, 2, 256, 256, jnp.float32, 1024),
    (1024, 2, 2, 192, 128, jnp.bfloat16, 1024),     # unequal at a short S
    (6144, 2, 2, 128, 128, jnp.bfloat16, 2048),     # 4,096 does not tile S
], ids=lambda v: getattr(v, "__name__", str(v)))
def test_one_budget_picks_the_chunk(S, H, Hkv, D, Dv, dtype, chunk,
                                    monkeypatch):
    """Where the caller names no chunk, ONE rule picks it for equal and
    unequal widths alike (ISSUE 48): the widest of ``_CHUNK_ROWS`` whose K +
    V rows, lane-padded, fit ``_CHUNK_BYTES`` and that tiles S — the plans
    the sweep timed and each cell's whole step compiled with (PERF.md
    Findings PR 48), Kanana-2's what ``_UNEQUAL_CHUNK_ROWS`` gave it. The
    gauge ``attention/flash_chunk_rows`` says which."""
    from deepspeed_tpu.telemetry.registry import default_registry
    fa = _fa()
    seen = {}
    real = fa._flash_attention

    def spy(q, k, v, scale, causal, block_q, block_k, chunk, *rest):
        seen.update(block=(block_q, block_k), chunk=chunk)
        return real(q, k, v, scale, causal, block_q, block_k, chunk, *rest)

    monkeypatch.setattr(fa, "_flash_attention", spy)
    jax.eval_shape(
        lambda *a: fa.flash_attention(*a, causal=True, interpret=False),
        jax.ShapeDtypeStruct((1, H, S, D), dtype),
        jax.ShapeDtypeStruct((1, Hkv, S, D), dtype),
        jax.ShapeDtypeStruct((1, Hkv, S, Dv), dtype))
    assert seen == {"block": (512, 512), "chunk": chunk}
    assert default_registry().peek_gauge("attention/flash_chunk_rows") \
        == chunk


def test_user_chunk_validation():
    from deepspeed_tpu.ops.pallas.flash_attention import flash_attention
    q = jnp.zeros((1, 1, 192, 16), jnp.float32)
    with pytest.raises(ValueError, match="chunk"):
        flash_attention(q, q, q, block_q=64, block_k=64, chunk=128,
                        interpret=True)


def test_flash_gqa_forward_matches_reference():
    """Hkv < H: the kernel consumes REDUCED-head K/V via Hkv-aware block
    maps. Numerics must equal the repeat-then-attend reference."""
    B, H, Hkv, S, D = 2, 8, 2, 128, 32
    q, _, _ = _qkv((B, H, S, D), seed=1)
    _, k, v = _qkv((B, Hkv, S, D), seed=2)
    out = flash_attention(q, k, v, causal=True, interpret=True,
                          block_q=64, block_k=64)
    ref = reference_attention(q, k, v, causal=True)   # repeats internally
    np.testing.assert_allclose(np.asarray(out), np.asarray(ref),
                               rtol=2e-5, atol=2e-5)


def test_flash_gqa_forward_never_materializes_full_head_kv():
    """The GQA memory promise (models/llama.py): the forward's
    pallas_call streams K/V at [B*Hkv, S, D] — no full-head copy exists
    anywhere in the forward jaxpr."""
    B, H, Hkv, S, D = 2, 8, 2, 128, 32
    q, _, _ = _qkv((B, H, S, D), seed=1)
    _, k, v = _qkv((B, Hkv, S, D), seed=2)

    jaxpr = jax.make_jaxpr(
        lambda a, b, c: flash_attention(a, b, c, causal=True,
                                        interpret=True, block_q=64,
                                        block_k=64))(q, k, v)

    def walk(jx):
        for eqn in jx.eqns:
            yield eqn
            for sub in eqn.params.values():
                if hasattr(sub, "jaxpr"):
                    yield from walk(sub.jaxpr)

    pallas_eqns = [e for e in walk(jaxpr.jaxpr)
                   if "pallas" in e.primitive.name]
    assert pallas_eqns, "flash kernel not dispatched"
    kv_shape = (B * Hkv, S, D)
    full_shape = (B * H, S, D)
    kv_ins = [tuple(v_.aval.shape) for v_ in pallas_eqns[0].invars]
    assert kv_ins.count(kv_shape) == 2, kv_ins   # k and v, reduced
    # nothing anywhere in the fwd COMPUTES a full-head K/V-sized array:
    # the only producers of that shape are q's own flatten-reshape and
    # the attention output o passing through the wrapper levels — no
    # repeat/broadcast/gather (what a K/V head-repeat lowers to)
    producers = {e.primitive.name for e in walk(jaxpr.jaxpr)
                 for ov in e.outvars
                 if tuple(ov.aval.shape) == full_shape}
    # (custom_vjp_call spells itself custom_vjp_call_jaxpr on jax <= 0.4.x)
    assert producers <= {"reshape", "custom_vjp_call",
                         "custom_vjp_call_jaxpr", "pallas_call"}, producers


def test_flash_gqa_backward_matches_reference():
    """dk/dv come back at the REDUCED head count (summed over the rep
    query heads); grads must match autodiff through the reference."""
    B, H, Hkv, S, D = 1, 4, 2, 128, 32
    q, _, _ = _qkv((B, H, S, D), seed=3)
    _, k, v = _qkv((B, Hkv, S, D), seed=4)

    def loss_fl(q, k, v):
        return jnp.sum(flash_attention(q, k, v, causal=True,
                                       interpret=True, block_q=64,
                                       block_k=64).astype(jnp.float32) ** 2)

    def loss_ref(q, k, v):
        return jnp.sum(reference_attention(
            q, k, v, causal=True).astype(jnp.float32) ** 2)

    g_fl = jax.grad(loss_fl, argnums=(0, 1, 2))(q, k, v)
    g_ref = jax.grad(loss_ref, argnums=(0, 1, 2))(q, k, v)
    assert g_fl[1].shape == (B, Hkv, S, D)
    for a, b in zip(g_fl, g_ref):
        np.testing.assert_allclose(np.asarray(a), np.asarray(b),
                                   rtol=5e-4, atol=5e-4)


# ------------------------------------------------ strip-granular whole-row
# kernels (ISSUE 28): the diagonal region of a grid block goes in
# sub-blocks of one strip's rows (256 of a 1024- or 512-row block, 128 of
# a 256-row one), each up to its own diagonal square

def _fa():
    """The kernel MODULE (the package exports the function of its name)."""
    import importlib
    return importlib.import_module(
        "deepspeed_tpu.ops.pallas.flash_attention")


def _tpu_block(S):
    """``pick_block``'s choice on a TPU (interpret mode caps it at 64)."""
    return next(c for c in (1024, 512, 256, 128, 64, 32) if S % c == 0)


def _loss_pair(causal, **kw):
    def loss_flash(q, k, v):
        o = flash_attention(q, k, v, causal=causal, interpret=True, **kw)
        return jnp.sum(jnp.sin(o.astype(jnp.float32)))

    def loss_ref(q, k, v):
        o = reference_attention(q, k, v, causal=causal)
        return jnp.sum(jnp.sin(o.astype(jnp.float32)))
    return loss_flash, loss_ref


def _assert_fwd_and_grads(shape, dtype, causal, block_q, block_k,
                          kv_heads=None, seed=0):
    B, H, S, D = shape
    q, _, _ = _qkv(shape, seed=seed, dtype=dtype)
    _, k, v = _qkv((B, kv_heads or H, S, D), seed=seed + 1, dtype=dtype)
    loss_flash, loss_ref = _loss_pair(causal, block_q=block_q,
                                      block_k=block_k)
    out = flash_attention(q, k, v, causal=causal, interpret=True,
                          block_q=block_q, block_k=block_k)
    ref = reference_attention(q, k, v, causal=causal)
    g_flash = jax.grad(loss_flash, argnums=(0, 1, 2))(q, k, v)
    g_ref = jax.grad(loss_ref, argnums=(0, 1, 2))(q, k, v)
    # the tolerances the first tests of this file hold: fp32 2e-4 / 2e-5
    # forward and 5e-3 / 5e-4 gradients, bf16 5e-2
    f32 = dtype == jnp.float32
    np.testing.assert_allclose(
        np.asarray(out, np.float32), np.asarray(ref, np.float32),
        rtol=2e-4 if f32 else 5e-2, atol=2e-5 if f32 else 5e-2)
    for a, b, name in zip(g_flash, g_ref, "qkv"):
        assert a.shape == b.shape and a.dtype == b.dtype
        np.testing.assert_allclose(
            np.asarray(a, np.float32), np.asarray(b, np.float32),
            rtol=5e-3 if f32 else 5e-2, atol=5e-4 if f32 else 5e-2,
            err_msg=f"d{name} S={S} D={D} {block_q}/{block_k}")


@pytest.mark.parametrize("S", [128, 256, 512, 768, 1024, 1280, 2048])
def test_strip_kernels_causal_at_the_tpu_block_choice(S):
    """Forward AND gradients at every strip edge: one strip (S 128), a
    block of two (S 256, 512, 768, 1280), a block of four alone (S 1024)
    and with a whole block below it (S 2048)."""
    b = _tpu_block(S)
    _assert_fwd_and_grads((1, 1, S, 64), jnp.float32, True, b, b)


@pytest.mark.parametrize("S", [512, 1024, 2048])
def test_strip_kernels_causal_block_512_forced(S):
    """Blocks of 512: two strips of 256 with 0..3 whole blocks below."""
    _assert_fwd_and_grads((1, 1, S, 64), jnp.float32, True, 512, 512)


@pytest.mark.parametrize("S", [512, 1024, 2048])
def test_strip_kernels_noncausal(S):
    _assert_fwd_and_grads((1, 1, S, 64), jnp.float32, False, 512, 512)


@pytest.mark.parametrize("causal", [False, True])
def test_strip_kernels_head_dim_128(causal):
    """2^-3.5 is no power of two: the scale stays on the fp32 scores."""
    _assert_fwd_and_grads((1, 2, 1024, 128), jnp.float32, causal, 512, 512)


@pytest.mark.parametrize("S,D", [(512, 64), (1024, 64), (1024, 128)])
def test_strip_kernels_bf16(S, D):
    _assert_fwd_and_grads((1, 2, S, D), jnp.bfloat16, True, 512, 512)


@pytest.mark.parametrize("block_q,block_k", [(512, 256), (256, 512),
                                             (256, 128), (128, 512)])
def test_strip_kernels_unequal_blocks(block_q, block_k):
    """The off-diagonal walk takes the widest tile that divides both
    blocks; a k-block wider than the q-block must leave no gap below the
    diagonal region."""
    _assert_fwd_and_grads((1, 1, 1024, 64), jnp.float32, True, block_q,
                          block_k)


@pytest.mark.parametrize("block_q,block_k", [(384, 256), (192, 256)])
def test_strip_kernels_diagonal_mid_tile(block_q, block_k):
    """q-blocks that start in the middle of a k-block (384 = 1.5 x 256),
    and a block 128 does not divide (192: one strip of 192 rows)."""
    _assert_fwd_and_grads((1, 1, 768, 64), jnp.float32, True, block_q,
                          block_k)


@pytest.mark.parametrize("dtype", [jnp.float32, jnp.bfloat16])
def test_strip_kernels_gqa(dtype):
    """Reduced-head K/V through the strip forward (block 512) and the
    repeat-and-sum backward."""
    _assert_fwd_and_grads((1, 4, 1024, 64), dtype, True, 512, 512,
                          kv_heads=2)


@pytest.mark.parametrize("dtype", [jnp.float32, jnp.bfloat16])
def test_prescaled_q_is_bit_identical_at_head_dim_64(dtype, monkeypatch):
    """head_dim 64: scale 0.125 is a power of two, so (q·scale)·kᵀ equals
    (q·kᵀ)·scale bit for bit — forward and all three gradients — and the
    kernels move the multiply from the score tile onto q."""
    fa = _fa()
    assert fa._scale_folds(64 ** -0.5) and fa._scale_folds(256 ** -0.5)
    q, k, v = _qkv((1, 2, 1024, 64), seed=5, dtype=dtype)
    loss, _ = _loss_pair(True, block_q=512, block_k=512)

    def run():
        return (flash_attention(q, k, v, causal=True, interpret=True,
                                block_q=512, block_k=512),
                *jax.grad(loss, argnums=(0, 1, 2))(q, k, v))

    folded = run()
    monkeypatch.setattr(fa, "_scale_folds", lambda scale: False)
    on_scores = run()
    for a, b in zip(folded, on_scores):
        np.testing.assert_array_equal(np.asarray(a, np.float32),
                                      np.asarray(b, np.float32))


def test_prescaled_q_is_not_taken_at_head_dim_128(monkeypatch):
    """2^-3.5 rounds in bf16: every kernel of a head_dim-128 call, plain
    and chunked, forward and backward, keeps the scale on the scores."""
    fa = _fa()
    asked = []
    real = fa._scale_folds

    def spy(scale):
        asked.append((scale, real(scale)))
        return asked[-1][1]

    monkeypatch.setattr(fa, "_scale_folds", spy)
    q, k, v = _qkv((1, 1, 256, 128), seed=6)
    for chunk in (None, 128):
        loss, _ = _loss_pair(True, block_q=128, block_k=128, chunk=chunk)
        jax.grad(loss, argnums=(0, 1, 2))(q, k, v)
    assert len(asked) >= 5 and not any(folds for _, folds in asked), asked
    assert all(abs(scale - 128 ** -0.5) < 1e-12 for scale, _ in asked)


@pytest.mark.parametrize("S,block,chunk,expected", [
    (1024, 1024, None, 1.2488),                 # strips of 256
    (1024, 512, None, 1.2488),
    (2048, 512, None, 1.1245),
    (768, 256, None, 1.1651),                   # strips of 128
    (4096, 512, 1024, 1.1247),                  # chunked: unchanged
])
def test_flash_tile_overcompute_gauge(S, block, chunk, expected):
    """``attention/flash_tile_overcompute``: computed over needed score
    elements of the loops chosen for the call. S 1024 was 1.50 when a
    diagonal block was computed whole; a strip walk holds it <= 1.25."""
    from deepspeed_tpu.telemetry.registry import default_registry
    fa = _fa()
    q = jax.ShapeDtypeStruct((1, 1, S, 16), jnp.float32)
    jax.eval_shape(lambda a: flash_attention(
        a, a, a, causal=True, interpret=True, block_q=block, block_k=block,
        chunk=chunk), q)
    got = default_registry().peek_gauge("attention/flash_tile_overcompute")
    assert got == pytest.approx(expected, abs=1e-4)
    assert got <= 1.25
    assert fa.tile_overcompute(S, block, block, chunk or 0, False) == 1.0
    jax.eval_shape(lambda a: flash_attention(
        a, a, a, causal=False, interpret=True, block_q=block, block_k=block,
        chunk=chunk), q)
    assert default_registry().peek_gauge(
        "attention/flash_tile_overcompute") == 1.0


# ------------------------------------------------------------------------
# the whole-row kernels on the model's own layout (ISSUE 30): heads as
# 128-lane COLUMN blocks of [B, S, H*D] operands — a fused projection read
# in place, or q, k, v apart where a third does not start on a lane block

def _bse_case(heads, D, apart, causal, dtype, S=128, B=1):
    """(column-block out and d(qkv), reference's, head-major kernels')."""
    fa = _fa()
    qkv = jax.random.normal(jax.random.PRNGKey(heads * D + S),
                            (B, S, 3 * heads * D), jnp.float32).astype(dtype)

    def columns(x):
        operands = jnp.split(x, 3, axis=-1) if apart else (x,)
        return fa.flash_attention_bse(*operands, heads=heads, causal=causal,
                                      interpret=True)

    def through(attend):
        return lambda x: from_head_major(attend(*(
            to_head_major(t, heads) for t in jnp.split(x, 3, axis=-1))))
    reference = through(functools.partial(reference_attention,
                                          causal=causal))
    head_major = through(functools.partial(flash_attention, causal=causal,
                                           interpret=True))

    def both(f):
        return f(qkv), jax.grad(lambda x: jnp.sum(jnp.sin(
            f(x).astype(jnp.float32))))(qkv)
    return both(columns), both(reference), both(head_major)


@pytest.mark.parametrize("dtype", [jnp.float32, jnp.bfloat16],
                         ids=["fp32", "bf16"])
@pytest.mark.parametrize("causal", [True, False], ids=["causal", "full"])
@pytest.mark.parametrize("heads,D,apart,per_block", [
    (4, 64, False, 2),      # E 256: qkv in place, two pairs
    (5, 64, False, 2),      # E 320 = 2.5 lane blocks: split, the tail head
    (4, 64, True, 2),       # the caller's own q, k, v
    (2, 128, False, 1),     # a head a block, in place
    (3, 128, True, 1),
    (4, 32, False, 4),      # four heads a block
], ids=["h4d64-inplace", "h5d64-split-tail", "h4d64-apart", "h2d128-inplace",
        "h3d128-apart", "h4d32-inplace"])
def test_column_block_kernels_match_reference_and_head_major(
        heads, D, apart, per_block, causal, dtype):
    """Forward and d(qkv) (dq | dk | dv) of the column-block entry against
    the float reference at the flash tests' tolerances, and against the
    head-major kernels, whose arithmetic it shares product for product
    (the added terms are exact zeros; delta is summed in the kernel)."""
    from deepspeed_tpu.telemetry.registry import default_registry
    cols, ref, hm = _bse_case(heads, D, apart, causal, dtype)
    assert default_registry().peek_gauge(
        "attention/flash_heads_per_block") == 0       # the head-major run
    f32 = dtype == jnp.float32
    for name, a, b, c, (rtol, atol) in zip(
            ("out", "d(qkv)"), cols, ref, hm,
            ((2e-4, 2e-5), (5e-3, 5e-4)) if f32 else ((5e-2, 5e-2),) * 2):
        assert a.shape == b.shape and a.dtype == b.dtype == dtype
        a, b, c = (np.asarray(t, np.float32) for t in (a, b, c))
        np.testing.assert_allclose(a, b, rtol=rtol, atol=atol, err_msg=name)
        np.testing.assert_allclose(a, c, rtol=1e-5 if f32 else 2e-2,
                                   atol=1e-5 if f32 else 2e-2, err_msg=name)
    jax.eval_shape(lambda x: _fa().flash_attention_bse(
        x, heads=heads, causal=causal, interpret=True),
        jax.ShapeDtypeStruct((1, 128, 3 * heads * D), dtype))
    assert default_registry().peek_gauge(
        "attention/flash_heads_per_block") == per_block


def test_column_block_entry_goes_head_major_where_heads_do_not_tile():
    """head_dim 48 tiles no lane block and E 64 fills not one: both run
    the head-major kernels through a transpose, and say so (gauge 0)."""
    from deepspeed_tpu.telemetry.registry import default_registry
    for heads, D in ((4, 48), (2, 32)):
        (out, grad), (ref, ref_grad), _ = _bse_case(heads, D, False, True,
                                                    jnp.float32)
        assert default_registry().peek_gauge(
            "attention/flash_heads_per_block") == 0
        np.testing.assert_allclose(out, ref, rtol=2e-4, atol=2e-5)
        np.testing.assert_allclose(grad, ref_grad, rtol=5e-3, atol=5e-4)


def test_column_block_residuals_bind_under_dots_flash_fc_lean():
    """Under ``jax.checkpoint`` with the benchmark's remat policy the
    column-block VJP's ``flash_o`` / ``flash_lse`` are SAVED: the gradient
    program holds two Pallas calls (the forward kernel once, the backward
    kernel), where full remat holds a second forward; the gradients are
    the unrematted ones."""
    from jax.ad_checkpoint import checkpoint_name
    from deepspeed_tpu.models.gpt2 import _remat_policy
    fa = _fa()
    x = jax.random.normal(jax.random.PRNGKey(0), (1, 128, 256), jnp.float32)
    w = jax.random.normal(jax.random.PRNGKey(1), (256, 768), jnp.float32) / 16

    def block(x, w):
        qkv = checkpoint_name(x @ w, "qkv")
        o = fa.flash_attention_bse(qkv, heads=4, causal=True, interpret=True)
        return jnp.sum(jnp.sin(o))

    lean = jax.checkpoint(block, policy=_remat_policy("dots_flash_fc_lean"))
    full = jax.checkpoint(block)
    kernels = {}
    for name, f in (("lean", lean), ("full", full)):
        text = str(jax.make_jaxpr(jax.grad(f, argnums=(0, 1)))(x, w))
        kernels[name] = text.count("pallas_call[")
    assert kernels == {"lean": 2, "full": 3}, kernels
    for a, b in zip(jax.grad(lean, argnums=(0, 1))(x, w),
                    jax.grad(block, argnums=(0, 1))(x, w)):
        np.testing.assert_allclose(a, b, rtol=1e-5, atol=1e-6)


@pytest.mark.parametrize("axes", [dict(data=4), dict(data=2, model=2)],
                         ids=["data4", "data2xmodel2"])
def test_fused_qkv_attention_runs_per_device_on_the_engine_mesh(axes):
    """``ops.attention.fused_qkv_attention`` under an engine's pinned mesh:
    the kernels run per device inside a shard_map — batch on the data
    axis; with heads on a model axis the thirds are split first and each
    device takes its own column range of q, k and v — and forward and
    gradient are the reference's."""
    from deepspeed_tpu.ops.attention import fused_qkv_attention
    from deepspeed_tpu.parallel import mesh as mesh_lib
    from deepspeed_tpu.parallel.mesh import MeshConfig, make_mesh
    from deepspeed_tpu.telemetry.registry import default_registry
    if len(jax.devices()) < 4:
        pytest.skip("need 4 devices")
    mesh = make_mesh(MeshConfig(**axes), devices=jax.devices()[:4])
    qkv = jax.random.normal(jax.random.PRNGKey(3), (4, 128, 3 * 256))

    def loss(x, use_flash):
        o = fused_qkv_attention(x, 4, causal=True, use_flash=use_flash)
        return jnp.sum(jnp.sin(o)), o

    with mesh_lib.layout_pins(mesh):
        (_, out), grad = jax.jit(jax.value_and_grad(
            functools.partial(loss, use_flash=True), has_aux=True))(qkv)
    assert default_registry().peek_gauge(
        "attention/flash_heads_per_block") == 2
    (_, ref), ref_grad = jax.value_and_grad(
        functools.partial(loss, use_flash=False), has_aux=True)(qkv)
    np.testing.assert_allclose(out, ref, rtol=2e-4, atol=2e-5)
    np.testing.assert_allclose(grad, ref_grad, rtol=5e-3, atol=5e-4)


# ------------------------------------------------------------------------
# the window kernels (ISSUE 33): a causal band of ``window`` keys; since
# ISSUE 43 a block's whole band is ONE operand block at an element offset
# (``chunk=``: a cap on its rows, which puts a band into several grid steps)

def _window_case(S, H, Hkv, W, block_q, block_k, chunk, dtype=jnp.float32,
                 D=32):
    """((out, dq, dk, dv) of the window kernels, of the masked reference)."""
    ks = jax.random.split(jax.random.PRNGKey(S + H + W), 4)
    q = jax.random.normal(ks[0], (1, H, S, D), jnp.float32).astype(dtype)
    k, v = (jax.random.normal(key, (1, Hkv, S, D), jnp.float32).astype(dtype)
            for key in ks[1:3])
    g = jax.random.normal(ks[3], (1, H, S, D), jnp.float32)

    def both(attend):
        out = attend(q, k, v)
        return (out,) + jax.grad(
            lambda *a: jnp.sum(attend(*a).astype(jnp.float32) * g),
            argnums=(0, 1, 2))(q, k, v)

    return (both(functools.partial(
        flash_attention, causal=True, window=W, block_q=block_q,
        block_k=block_k, chunk=chunk, interpret=True)),
        both(functools.partial(reference_attention, causal=True, window=W)))


@pytest.mark.parametrize("S,H,Hkv,W,block_q,block_k,chunk", [
    (256, 2, 2, 32, 64, 64, None),      # W smaller than the block
    (256, 2, 1, 64, 64, 64, None),      # W equal to the block
    (256, 2, 1, 128, 64, 64, None),     # W a multiple of the block
    (256, 2, 1, 100, 64, 64, None),     # W no multiple of the block
    (256, 2, 1, 255, 64, 64, None),     # all but the first key of the last
    (256, 4, 2, 100, 32, 64, 64),       # unequal blocks
    (256, 2, 1, 16, 64, 32, 128),       # several blocks a chunk
    (512, 6, 1, 130, 64, 64, 128),      # GQA 6:1, band across chunk edges
    (256, 8, 1, 48, 64, 64, None),      # GQA 8:1
    (192, 3, 1, 40, 64, 64, None),      # S no power of two, odd head count
    (512, 7, 1, 288, 64, 64, 64),       # GQA 7:1, a band of 6 one-tile steps
    (384, 14, 2, 200, 64, 64, 128),     # 2 KV heads x 7, band over 3 steps
    # ISSUE 43: the band as one operand block of round_up(block + W - 1)
    # rows, clamped at the sequence's start (forward, dq) and end (dkv)
    (512, 2, 1, 200, 64, 64, None),     # 5 tiles a step, 4 blocks clamped
    (256, 2, 1, 255, 32, 32, None),     # a band as wide as the sequence
    (128, 2, 2, 127, 64, 64, None),     # round_up(64 + 126) = 192 rows > S
    (512, 2, 1, 200, 64, 64, 192),      # a small budget: 5 tiles in 2 steps
    (512, 2, 1, 300, 64, 64, 256),      # 6 tiles in 2 steps of 3
    (256, 2, 1, 100, 32, 64, None),     # unequal blocks, the band in a step
    (256, 2, 1, 100, 64, 32, None),     # ... and the other way round
    (512, 4, 2, 130, 128, 64, None),    # block_q twice block_k, GQA 2:1
    (512, 7, 1, 288, 64, 64, None),     # GQA 7:1, the band in one step
    (384, 14, 2, 200, 64, 64, None),    # 2 KV heads x 7, one step
    (256, 8, 1, 100, 64, 64, None),     # GQA 8:1, one step of 3 tiles
    (512, 8, 1, 200, 64, 64, 128),      # GQA 8:1, 5 tiles in 3 steps
], ids=lambda v: str(v))
def test_window_kernels_match_the_masked_reference(S, H, Hkv, W, block_q,
                                                   block_k, chunk):
    got, want = _window_case(S, H, Hkv, W, block_q, block_k, chunk)
    for a, b, name in zip(got, want, ("out", "dq", "dk", "dv")):
        np.testing.assert_allclose(np.asarray(a), np.asarray(b), rtol=5e-4,
                                   atol=5e-5, err_msg=name)


def test_window_kernels_bf16():
    got, want = _window_case(256, 4, 1, 64, 64, 64, None, dtype=jnp.bfloat16)
    for a, b in zip(got, want):
        np.testing.assert_allclose(np.asarray(a, np.float32),
                                   np.asarray(b, np.float32), rtol=5e-2,
                                   atol=5e-2)


@pytest.mark.parametrize("W", [256, 300])
def test_a_window_that_covers_the_sequence_is_causal_attention(W):
    """W >= S: the causal kernels, bit for bit (no window kernel runs)."""
    q, k, v = _qkv(shape=(1, 2, 256, 32))
    kw = dict(causal=True, interpret=True, block_q=64, block_k=64)
    np.testing.assert_array_equal(
        np.asarray(flash_attention(q, k, v, window=W, **kw)),
        np.asarray(flash_attention(q, k, v, **kw)))


@pytest.mark.parametrize("S,W,block,chunk,rows,steps,tiles", [
    (1024, 64, 64, None, 128, 1, 2.325),     # round_up(64 + 63, 64) rows
    (1024, 128, 64, None, 192, 1, 3.375),
    (1024, 100, 64, None, 192, 1, 3.375),
    (1024, 64, 64, 64, 64, 2, 0.969),        # a cap of one tile: 2 steps
    (1024, 512, 64, 256, 192, 3, 2.25),     # 9 tiles under a cap of 4: 3 x 3
    (16384, 512, 256, None, 768, 1, 3.544),  # Laguna's window at blocks of 256
    (16384, 512, 128, None, 640, 1, 5.906),
    (16384, 512, 512, None, 1024, 1, 2.3625),     # the Laguna cell's, 2 heads
    (16384, 4096, 512, None, 4608, 1, 7.875),    # the SmallThinker cell's
])
def test_window_grid_walks_the_static_band_count(S, W, block, chunk, rows,
                                                 steps, tiles):
    """A block's band is one operand block of ``rows`` rows: the third grid
    extent of the forward and dq ``pallas_call``s is the band's step count —
    1 where the rows fit the budget (or the caller's ``chunk=`` cap) — and
    of the dkv call that times the group's query heads, never S / block;
    the two gauges say what the tiles compute and how many a step takes."""
    from deepspeed_tpu.telemetry.registry import default_registry
    fa = _fa()
    H, Hkv = 4, 2
    band = fa._band_plan(S, block, block, W, 128 * 2, H // Hkv,
                         chunk or 0)
    # both of a group's heads a dkv step where 2 x the band's rows fit
    heads = 2 if steps == 1 and 2 * rows * 256 <= fa._BAND_BYTES else 1
    assert band == ((rows // block, steps), (rows // block, steps, heads))
    if not chunk:
        assert rows == -(-(block + W - 1) // block) * block
    q = jax.ShapeDtypeStruct((1, H, S, 128), jnp.bfloat16)   # the cells'
    kv = jax.ShapeDtypeStruct((1, Hkv, S, 128), jnp.bfloat16)
    jaxpr = jax.make_jaxpr(jax.grad(lambda q, k, v: jnp.sum(flash_attention(
        q, k, v, causal=True, window=W, block_q=block, block_k=block,
        chunk=chunk, interpret=True).astype(jnp.float32)),
        argnums=(0, 1, 2)))(q, kv, kv)
    grids = sorted(pallas_grids(jaxpr.jaxpr))
    assert grids == sorted([
        (H, S // block, steps), (H, S // block, steps),
        (Hkv, S // block, H // Hkv // heads * steps)]), grids
    # the band's operands: K, V (forward, dq) and Q, dO (dkv)
    assert pallas_element_rows(jaxpr.jaxpr) == [rows] * 6
    over = default_registry().peek_gauge("attention/window_tile_overcompute")
    assert over == pytest.approx(
        fa.window_tile_overcompute(S, block, block, W))
    assert default_registry().peek_gauge(
        "attention/window_tiles_per_grid_step") == pytest.approx(
        fa.window_tiles_per_grid_step(S, block, block, W, band)) \
        == pytest.approx(tiles, abs=0.001)
    if (S, W) == (16384, 512):
        assert over == pytest.approx({512: 2.0, 256: 1.5, 128: 1.25}[block],
                                     abs=0.02)
    if (S, W) == (16384, 4096):
        assert over == pytest.approx(1.125, abs=0.005)


@pytest.mark.parametrize("budget,band", [
    (2 ** 21, ((5, 1), (5, 1, 2))),     # the module's: 320 rows fit whole
    (320 * 512, ((5, 1), (5, 1, 1))),   # ... for one head of the two
    (200 * 512, ((3, 2), (3, 2, 1))),   # 200 rows of 128 float32 lanes
    (64 * 512, ((1, 5), (1, 5, 1))),    # one tile: the parent's step count
])
def test_a_band_past_the_budget_goes_in_the_fewest_steps_that_fit(
        monkeypatch, budget, band):
    """No knob: the band's rows follow from window, block, head_dim and
    dtype against ``_BAND_BYTES``, and the steps are the fewest equal ones
    that fit — out, dq, dk, dv are the reference's either way."""
    fa = _fa()
    monkeypatch.setattr(fa, "_BAND_BYTES", budget)
    assert fa._band_plan(512, 64, 64, 200, 128 * 4, 2) == band
    got, want = _window_case(512, 4, 2, 200, 64, 64, None)
    for a, b, name in zip(got, want, ("out", "dq", "dk", "dv")):
        np.testing.assert_allclose(np.asarray(a), np.asarray(b), rtol=5e-4,
                                   atol=5e-5, err_msg=name)


def test_window_overcompute_counts_blocks_over_the_band():
    fa = _fa()
    # one block a band row but the first: 4 x 4 blocks of 64 x 64 touched
    # twice (the causal and the lower edge), over 256 x 64 - 64 x 63 / 2
    assert fa.window_tile_overcompute(256, 64, 64, 64) == pytest.approx(
        (4 + 3) * 64 * 64 / (256 * 64 - 64 * 63 // 2))
    assert fa.window_tile_overcompute(256, 64, 64, 1) == pytest.approx(
        4 * 64 * 64 / 256)


def test_a_window_shape_no_kernel_takes_raises():
    """Never [S, S] scores behind the caller's back: an S no block tiles,
    a chunk that is no multiple of the blocks, a window without causal."""
    q, k, v = _qkv(shape=(1, 1, 100, 16))
    with pytest.raises(ValueError, match="never falls back"):
        flash_attention(q, k, v, causal=True, window=8, interpret=True)
    q, k, v = _qkv(shape=(1, 1, 256, 16))
    with pytest.raises(ValueError, match="chunk=96"):
        flash_attention(q, k, v, causal=True, window=8, interpret=True,
                        block_q=64, block_k=64, chunk=96)
    with pytest.raises(ValueError, match="causal"):
        flash_attention(q, k, v, causal=False, window=8, interpret=True)
    from deepspeed_tpu.ops.attention import dot_product_attention
    with pytest.raises(ValueError, match="causal"):
        dot_product_attention(q, k, v, causal=False, window=8)


def test_dot_product_attention_passes_the_window_through_its_shard_map():
    """``ops.attention._flash`` under an engine's pinned mesh: the window
    kernels run per device inside the shard_map, ``window`` handed through
    exactly as ``causal`` is."""
    from deepspeed_tpu.ops.attention import dot_product_attention
    from deepspeed_tpu.parallel import mesh as mesh_lib
    from deepspeed_tpu.parallel.mesh import MeshConfig, make_mesh
    if len(jax.devices()) < 4:
        pytest.skip("need 4 devices")
    mesh = make_mesh(MeshConfig(data=2, model=2), devices=jax.devices()[:4])
    ks = jax.random.split(jax.random.PRNGKey(5), 3)
    q = jax.random.normal(ks[0], (2, 4, 128, 32))
    k, v = (jax.random.normal(key, (2, 2, 128, 32)) for key in ks[1:])

    def loss(q, k, v, use_flash):
        o = dot_product_attention(q, k, v, causal=True, window=24,
                                  use_flash=use_flash)
        return jnp.sum(jnp.sin(o)), o

    with mesh_lib.layout_pins(mesh):
        (_, out), grads = jax.jit(jax.value_and_grad(
            functools.partial(loss, use_flash=True), argnums=(0, 1, 2),
            has_aux=True))(q, k, v)
    (_, ref), ref_grads = jax.value_and_grad(
        functools.partial(loss, use_flash=False), argnums=(0, 1, 2),
        has_aux=True)(q, k, v)
    np.testing.assert_allclose(out, ref, rtol=2e-4, atol=2e-5)
    for a, b in zip(grads, ref_grads):
        np.testing.assert_allclose(a, b, rtol=5e-3, atol=5e-4)


# ------------------------------------------------------------------------
# the chunked kernels' grid (ISSUE 39): (B*H, pairs) — the (block, chunk)
# pairs that hold work, read by the index maps from two scalar-prefetch
# arrays; a causal call leaves out the pairs above the diagonal

@pytest.mark.parametrize("H,Hkv,S,D,causal,block_q,block_k,chunk", [
    (2, 2, 256, 16, True, 64, 64, 128),     # block < chunk: two tiles a step
    (2, 2, 256, 16, True, 64, 64, 64),      # block == chunk: Qwen3-Next's
    (2, 2, 256, 16, True, 64, 32, 128),     # block_q != block_k
    (2, 2, 256, 16, True, 32, 64, 64),
    (2, 2, 256, 16, False, 64, 64, 128),    # nothing masked: the rectangle
    (2, 2, 256, 16, False, 32, 64, 64),
    (6, 1, 256, 16, True, 64, 64, 128),     # Laguna's full layers' groups
    (7, 1, 256, 16, True, 64, 64, 64),      # SmallThinker's
    (4, 2, 512, 16, True, 64, 64, 256),     # four blocks a chunk: a block's
    (4, 2, 512, 16, True, 32, 64, 256),     # diagonal falls mid-chunk (PR 48)
    (4, 2, 512, 16, True, 64, 64, 512),     # one chunk: chunk = S, OLMoE's
    (8, 1, 256, 16, True, 64, 64, 128),     # Qwen3-Next's
    (8, 2, 128, 32, False, 32, 32, 64),
], ids=lambda v: str(v))
def test_pair_list_kernels_match_reference(H, Hkv, S, D, causal, block_q,
                                           block_k, chunk):
    """Forward and all three gradients of the chunked kernels on their
    pair-list grid against the reference, over the grid's forms (several
    tiles a step, one, unequal blocks, masked and not) and the cells'
    grouped-query ratios."""
    q, _, _ = _qkv((1, H, S, D), seed=H + S)
    _, k, v = _qkv((1, Hkv, S, D), seed=H + S + 1)

    def both(attend):
        return (attend(q, k, v),) + jax.grad(
            lambda *a: jnp.sum(jnp.sin(attend(*a))), argnums=(0, 1, 2))(
            q, k, v)

    got = both(functools.partial(flash_attention, causal=causal,
                                 block_q=block_q, block_k=block_k,
                                 chunk=chunk, interpret=True))
    want = both(functools.partial(reference_attention, causal=causal))
    for a, b, name in zip(got, want, ("out", "dq", "dk", "dv")):
        assert a.shape == b.shape
        fwd = name == "out"
        np.testing.assert_allclose(a, b, rtol=2e-4 if fwd else 5e-3,
                                   atol=2e-5 if fwd else 5e-4, err_msg=name)


@pytest.mark.parametrize("dtype,block_q,block_k,chunk", [
    (jnp.float32, 64, 64, 128), (jnp.bfloat16, 64, 32, 128),
    (jnp.float32, 64, 64, 64)], ids=lambda v: str(v))
def test_pair_list_skips_only_steps_that_did_nothing(dtype, block_q, block_k,
                                                     chunk, monkeypatch):
    """o, dq, dk, dv of a causal call are BIT-equal to the same tile math
    walked over the whole rectangle (what the grid was before ISSUE 39: the
    steps above the diagonal run empty loops — in the backward they leave a
    dq partial of zeros, which ``_sum_dq_slabs`` adds — and a walk's first
    and last step are where they were), so the pair list changes no
    arithmetic and no order of accumulation."""
    fa = _fa()
    q, _, _ = _qkv((1, 4, 256, 32), seed=39, dtype=dtype)
    _, k, v = _qkv((1, 2, 256, 32), seed=40, dtype=dtype)

    def run():
        o, vjp = jax.vjp(functools.partial(
            flash_attention, causal=True, block_q=block_q, block_k=block_k,
            chunk=chunk, interpret=True), q, k, v)
        return (o,) + vjp(jnp.cos(o.astype(jnp.float32)).astype(dtype))

    got = run()
    pairs = fa._pair_walk
    monkeypatch.setattr(fa, "_pair_walk", lambda S, block, chunk, causal,
                        by_chunk: pairs(S, block, chunk, False, by_chunk))
    rectangle = run()
    for by_chunk in (False, True):
        assert len(fa._pair_walk(256, block_q, chunk, True, by_chunk)[0]) \
            == (256 // block_q) * (256 // chunk)
    for a, b, name in zip(got, rectangle, ("out", "dq", "dk", "dv")):
        np.testing.assert_array_equal(np.asarray(a, np.float32),
                                      np.asarray(b, np.float32), err_msg=name)


@pytest.mark.parametrize("S,block,chunk,causal,pairs", [
    (16384, 512, 4096, True, 80),       # Laguna, SmallThinker, Nemotron,
    (8192, 512, 2048, True, 40),        # Kanana-2: of 128; Qwen3-Next: of 64
    (4096, 512, 4096, True, 8),         # OLMoE: chunk = S, of 8
    (16384, 512, 1024, True, 272),      # the plans before PR 48: of 512
    (8192, 512, 512, True, 136),        # of 256
    (4096, 512, 1024, True, 20),        # of 32
    (16384, 512, 1024, False, 512),     # nothing masked: the rectangle
    (8192, 512, 512, False, 256),
    (4096, 512, 1024, False, 32),
])
def test_chunked_grid_is_the_pair_list(S, block, chunk, causal, pairs):
    """The two chunked ``pallas_call``s — the forward and the single-pass
    backward (ISSUE 49) — run on grid (B*H, pairs): two dimensions, the
    second the cells' 80 / 40 / 8 pairs under a causal mask (272 / 136 / 20
    at the chunks they had before PR 48) and the rectangle's count without
    one — and the gauge ``attention/flash_grid_steps_walked_share`` is
    their sum over the rectangle's, ``attention/flash_chunk_rows`` the
    chunk, ``attention/flash_bwd_dq_slabs`` the key chunks (a slab of dq
    partials each) and ``attention/flash_bwd_products_per_tile`` 5."""
    from deepspeed_tpu.telemetry.registry import default_registry
    H, Hkv = 4, 2
    q = jax.ShapeDtypeStruct((1, H, S, 16), jnp.float32)
    kv = jax.ShapeDtypeStruct((1, Hkv, S, 16), jnp.float32)
    jaxpr = jax.make_jaxpr(jax.grad(lambda q, k, v: jnp.sum(flash_attention(
        q, k, v, causal=causal, block_q=block, block_k=block, chunk=chunk,
        interpret=True)), argnums=(0, 1, 2)))(q, kv, kv)
    assert pallas_grids(jaxpr.jaxpr) == [(H, pairs)] * 2
    rectangle = (S // block) * (S // chunk)
    assert default_registry().peek_gauge(
        "attention/flash_grid_steps_walked_share") == pytest.approx(
        pairs / rectangle)
    assert default_registry().peek_gauge("attention/flash_chunk_rows") \
        == chunk
    assert default_registry().peek_gauge("attention/flash_bwd_dq_slabs") \
        == S // chunk
    assert default_registry().peek_gauge(
        "attention/flash_bwd_products_per_tile") == 5
    assert _fa().grid_steps_walked(S, block, block, chunk, causal) \
        == (2 * pairs, 2 * rectangle)


@pytest.mark.parametrize("S,block,chunk", [
    (256, 64, 128), (256, 64, 64), (256, 32, 128), (512, 128, 256),
    (384, 128, 128), (16384, 512, 1024)])
@pytest.mark.parametrize("by_chunk", [False, True], ids=["fwd", "bwd"])
def test_pair_walk_holds_every_pair_with_a_visible_score(S, block, chunk,
                                                         by_chunk):
    """``_pair_walk``'s causal list: a (query block, key chunk) pair is in
    it exactly when some query of the block sees some key of the chunk. The
    forward's order: a block's pairs are consecutive with chunks ascending
    from ``_walk_ends``'s first to its last, and blocks ascend; the
    backward's (``by_chunk``): a chunk's pairs are consecutive with blocks
    ascending from the first that sees it to the last, and chunks ascend.
    Without a mask it is the rectangle, in the rectangular grid's order or
    that grid's transposed."""
    fa = _fa()
    i_of, c_of = fa._pair_walk(S, block, chunk, True, by_chunk)
    assert i_of.dtype == c_of.dtype == np.int32
    walked = list(zip(i_of.tolist(), c_of.tolist()))
    # the block's last query sees the chunk's first key
    visible = {(i, c) for i in range(S // block) for c in range(S // chunk)
               if (i + 1) * block - 1 >= c * chunk}
    assert set(walked) == visible and len(walked) == len(visible)
    if by_chunk:
        assert walked == sorted(walked, key=lambda pair: pair[::-1])
        for c in range(S // chunk):
            mine = [b for b, kc in walked if kc == c]
            assert mine == list(range(c * chunk // block, S // block))
    else:
        assert walked == sorted(walked)  # blocks ascend, chunks within them
        for i in range(S // block):
            mine = [c for b, c in walked if b == i]
            first, last = fa._walk_ends(i, block, chunk, S // chunk, True)
            assert mine == list(range(first, last + 1)) and mine
    full = fa._pair_walk(S, block, chunk, False, by_chunk)
    grid = [(i, c) for i in range(S // block) for c in range(S // chunk)]
    assert list(zip(*map(np.ndarray.tolist, full))) == (
        sorted(grid, key=lambda pair: pair[::-1]) if by_chunk else grid)


def test_pair_walk_is_built_once_a_plan_and_logged(caplog):
    """The lists are cached per (S, block, chunk, causal, walk) — a second
    trace of a plan builds nothing — and the plan's log line names the pairs
    walked beside ``chunk=``."""
    import logging
    fa = _fa()
    fa._pair_walk.cache_clear()
    fa._plans_logged.clear()
    q = jax.ShapeDtypeStruct((1, 2, 512, 16), jnp.float32)

    def trace():
        jax.make_jaxpr(jax.grad(lambda q, k, v: jnp.sum(flash_attention(
            q, k, v, causal=True, block_q=64, block_k=64, chunk=128,
            interpret=True)), argnums=(0, 1, 2)))(q, q, q)

    from deepspeed_tpu.utils.logging import logger
    logger.addHandler(caplog.handler)
    try:
        with caplog.at_level(logging.INFO, logger=logger.name):
            trace()
            built = fa._pair_walk.cache_info().misses
            trace()
    finally:
        logger.removeHandler(caplog.handler)
    assert built == 2                   # the forward's order, the backward's
    assert fa._pair_walk.cache_info().misses == built
    lines = [r.getMessage() for r in caplog.records
             if "flash attention S=512" in r.getMessage()]
    assert len(lines) == 1, lines
    # 8 blocks x 4 chunks: 1 + 1 + 2 + 2 + 3 + 3 + 4 + 4 = 20 of 32, twice
    assert "chunk=128 (40 of 64 (block, chunk) pairs walked, forward + " \
        "backward; backward 5 products a tile, dq in 4 slab(s))" in lines[0]


# the chunked family's single-pass backward (ISSUE 49): ONE kernel walks the
# pairs by key chunk and gives dq, dk and dv from one score tile each; dk and
# dv accumulate in VMEM over a chunk's run of steps, dq leaves as float32
# partials, a slab a chunk, that ``_sum_dq_slabs`` adds

def _reference_grads(q, k, v, do, scale, causal):
    """float32 (dq, dk, dv per QUERY head) of [H, S, D] q and do against
    [Hkv, S, D] k and v, as ``_flash_bwd_chunked`` returns them."""
    rep = q.shape[0] // k.shape[0]

    def attend(q, k, v):
        return reference_attention(q[None], k[None], v[None], causal=causal,
                                   scale=scale)[0]
    _, vjp = jax.vjp(attend, q, jnp.repeat(k, rep, axis=0),
                     jnp.repeat(v, rep, axis=0))
    return vjp(do)


@pytest.mark.parametrize("H,Hkv,S,D,Dv,dtype,causal,blocks,chunk", [
    (2, 2, 256, 16, 16, jnp.float32, True, (64, 64), 256),    # one slab
    (2, 2, 256, 16, 16, jnp.float32, True, (64, 64), 128),    # two
    (2, 2, 256, 16, 16, jnp.float32, True, (64, 64), 64),     # four
    (2, 2, 256, 16, 16, jnp.float32, False, (64, 64), 256),   # the rectangle
    (2, 2, 256, 16, 16, jnp.float32, False, (64, 64), 64),
    (2, 2, 256, 16, 16, jnp.float32, True, (32, 64), 128),    # unequal blocks
    (2, 2, 256, 16, 16, jnp.float32, True, (64, 32), 64),
    (4, 2, 256, 16, 16, jnp.float32, True, (64, 64), 64),     # a group of 2
    (6, 1, 256, 16, 16, jnp.float32, True, (64, 64), 128),    # of 6: Laguna's
    (6, 1, 256, 16, 16, jnp.bfloat16, False, (64, 64), 64),
    (2, 2, 128, 192, 128, jnp.float32, True, (32, 32), 64),   # latent widths
    (2, 2, 128, 192, 128, jnp.bfloat16, True, (32, 32), 32),  # (scale on the
    (2, 1, 128, 192, 128, jnp.bfloat16, False, (32, 32), 128),  # scores)
    (2, 2, 256, 24, 16, jnp.float32, True, (64, 64), 64),
    (2, 2, 256, 64, 64, jnp.bfloat16, True, (64, 64), 64),    # scale on q
    (2, 1, 256, 128, 128, jnp.bfloat16, True, (64, 64), 128),  # on the scores
], ids=lambda v: getattr(v, "__name__", str(v)))
def test_single_pass_backward_matches_reference(H, Hkv, S, D, Dv, dtype,
                                                causal, blocks, chunk):
    """``_flash_bwd_chunked``'s dq, dk and dv — ONE ``pallas_call`` and,
    past one chunk, the slabs' sum — against the reference's gradients:
    causal and not, 1 / 2 / 4 chunks, unequal blocks, grouped keys (dk and
    dv per QUERY head, in the operands' dtype), the latent widths, bf16 and
    float32, a scale that folds onto q (head_dim 16, 64) and one that stays
    on the scores (24, 128, 192)."""
    fa = _fa()
    scale = D ** -0.5
    q, k, _ = _qkv((H, S, D), seed=S + D, dtype=dtype)
    k = k[:Hkv]
    v, do = _qkv((H, S, Dv), seed=Dv, dtype=dtype)[:2]
    v = v[:Hkv]
    static = (scale, causal, *blocks, chunk, True, H, Hkv)
    o, lse = fa._flash_fwd_chunked(q, k, v, *static)
    bwd = functools.partial(fa._flash_bwd_chunked, q, k, v, o, lse, do,
                            *static)
    got = bwd()
    f32 = [t.astype(jnp.float32) for t in (q, k, v, do)]
    want = _reference_grads(*f32, scale, causal)
    coarse = dtype == jnp.bfloat16
    for a, b, like, name in zip(got, want, (q, q, do), ("dq", "dk", "dv")):
        assert a.shape == like.shape and a.dtype == dtype, name
        np.testing.assert_allclose(
            a.astype(jnp.float32), b, rtol=5e-2 if coarse else 5e-3,
            atol=(6e-2 if coarse else 5e-4) * max(1.0, float(
                jnp.max(jnp.abs(b))) / 4), err_msg=name)
    jaxpr = jax.make_jaxpr(bwd)().jaxpr
    pairs = len(fa._pair_walk(S, blocks[0], chunk, causal, True)[0])
    assert pallas_grids(jaxpr) == [(H, pairs)]
    (call,) = [e for e in jaxpr.eqns if e.primitive.name == "pallas_call"]
    parts = call.outvars[0].aval
    assert parts.shape == (H, pairs, blocks[0], D)
    # one chunk: dq leaves the kernel whole, in the operands' dtype
    assert parts.dtype == (dtype if chunk == S else jnp.float32)


@pytest.mark.parametrize("S,block,chunk,causal", [
    (512, 64, 128, True), (512, 64, 128, False), (512, 128, 128, True),
    (256, 32, 256, True), (16384, 512, 4096, True)])
def test_dq_slabs_sum_to_each_blocks_rows(S, block, chunk, causal):
    """``_sum_dq_slabs`` on partials that name their pair: block ``i``'s
    rows of dq are the sum over exactly the chunks ``c`` the block sees of
    pair (i, c)'s partial, times the scale — whichever slab layout the walk
    gives (under a causal mask a slab starts at its chunk's own rows: 80 of
    128 block-rows at S 16,384)."""
    fa = _fa()
    walk = fa._pair_walk(S, block, chunk, causal, True)
    i_of, c_of = (x.astype(np.int64) for x in walk)
    # pair (i, c) holds 3 ** c in every element: a sum names its terms
    parts = jnp.broadcast_to(jnp.asarray(3.0 ** c_of, jnp.float32)[
        None, :, None, None], (1, len(c_of), block, 8))
    dq = fa._sum_dq_slabs(parts, walk, S, chunk, 0.5, jnp.float32)
    assert dq.shape == (1, S, 8)
    for i in range(S // block):
        seen = [c for c in range(S // chunk)
                if not causal or (i + 1) * block - 1 >= c * chunk]
        assert sorted(c_of[i_of == i].tolist()) == seen
        np.testing.assert_array_equal(
            dq[0, i * block:(i + 1) * block],
            0.5 * sum(3.0 ** c for c in seen))


@pytest.mark.parametrize("S,D,chunk,slabs", [
    (256, 16, 64, 4), (256, 16, 128, 2), (256, 16, 256, 1),
    (128, 16, None, 0)], ids=["four_chunks", "two", "one", "whole_row"])
def test_backward_gauges_name_the_plan(S, D, chunk, slabs, caplog):
    """``attention/flash_bwd_products_per_tile`` reads 5 on every call (a
    whole-row call's backward was single-pass before) and
    ``attention/flash_bwd_dq_slabs`` the slabs ``_sum_dq_slabs`` adds: one a
    key chunk, 1 where the chunk is the sequence (nothing is added), 0 for
    a whole row, whose dq is VMEM-resident; a chunked plan's log line names
    both."""
    import logging
    from deepspeed_tpu.telemetry.registry import default_registry
    from deepspeed_tpu.utils.logging import logger
    fa = _fa()
    fa._plans_logged.clear()
    for name in ("products_per_tile", "dq_slabs"):
        default_registry().gauge(f"attention/flash_bwd_{name}").set(-1)
    q = jax.ShapeDtypeStruct((1, 2, S, D), jnp.float32)
    logger.addHandler(caplog.handler)
    try:
        with caplog.at_level(logging.INFO, logger=logger.name):
            jax.eval_shape(lambda a: flash_attention(
                a, a, a, causal=True, interpret=True, block_q=64, block_k=64,
                chunk=chunk), q)
    finally:
        logger.removeHandler(caplog.handler)
    gauges = default_registry().snapshot()["gauges"]
    assert gauges["attention/flash_bwd_products_per_tile"] == 5
    assert gauges["attention/flash_bwd_dq_slabs"] == slabs
    (line,) = [r.getMessage() for r in caplog.records
               if f"flash attention S={S}" in r.getMessage()]
    assert (f"backward 5 products a tile, dq in {slabs} slab(s)" in line) \
        == bool(chunk)


# the log-sum-exp the chunked and the window kernels hand the backward pass
# (ISSUE 34): lane-dense, so that a rematted block can afford to keep it

def _named(jaxpr, found=None):
    """{checkpoint name: [avals]} of a jaxpr and every jaxpr inside it."""
    found = {} if found is None else found
    for eqn in jaxpr.eqns:
        if eqn.primitive.name == "name":
            found.setdefault(eqn.params["name"], []).append(
                eqn.outvars[0].aval)
        for value in eqn.params.values():
            for sub in value if isinstance(value, (list, tuple)) else [value]:
                inner = getattr(sub, "jaxpr", sub)
                if hasattr(inner, "eqns"):
                    _named(inner, found)
    return found


def _lse_reference(q, k, causal, window):
    rep = q.shape[1] // k.shape[1]
    s = jnp.einsum("bhqd,bhkd->bhqk", q, jnp.repeat(k, rep, axis=1),
                   precision="highest") / np.sqrt(q.shape[-1])
    rel = jnp.arange(q.shape[2])[:, None] - jnp.arange(q.shape[2])[None]
    seen = (rel >= 0) if causal else jnp.ones_like(rel, bool)
    if window:
        seen &= rel < window
    return jax.nn.logsumexp(jnp.where(seen, s, -jnp.inf), axis=-1)


@pytest.mark.parametrize("H,Hkv,D,window", [
    (2, 2, 128, None), (2, 1, 128, None),       # chunked causal, MHA / GQA
    (2, 2, 256, None), (4, 1, 256, None),
    (2, 2, 128, 100), (4, 1, 128, 160),         # the window kernels
], ids=lambda v: str(v))
def test_chunked_and_window_lse_is_lane_dense(H, Hkv, D, window):
    """What the VJP names ``flash_lse`` is float32 [B*H, S / 128, 1, 128]
    — 128 real values a row, the reference's log-sum-exp, no trailing 1 —
    and forward, dq, dk, dv hold the float32 reference's within the limits
    the parity tests above hold."""
    fa = _fa()
    S, block, chunk = 384, 128, 128         # the window: 2-3 steps a band
    q, _, _ = _qkv((1, H, S, D), seed=H + D)
    _, k, v = _qkv((1, Hkv, S, D), seed=H + D + 1)
    attend = functools.partial(flash_attention, causal=True, window=window,
                               block_q=block, block_k=block, chunk=chunk,
                               interpret=True)
    named = _named(jax.make_jaxpr(
        lambda *a: jax.vjp(attend, *a)[1](a[0]))(q, k, v).jaxpr)
    (lse,), (o,) = named["flash_lse"], named["flash_o"]
    assert lse.shape == (H, S // 128, 1, 128) and lse.dtype == jnp.float32
    assert o.shape == (H, S, D)

    if window:
        band = fa._band_plan(S, block, block, window, D * 4, H // Hkv,
                             chunk)
        assert band[0][1] > 1              # a walk with a carry
        _, got = fa._swa_fwd(q[0], k[0], v[0], D ** -0.5, window, block,
                             block, band, True, H, Hkv)
    else:
        _, got = fa._flash_fwd_chunked(q[0], k[0], v[0], D ** -0.5, True,
                                       block, block, chunk, True, H, Hkv)
    np.testing.assert_allclose(got.reshape(H, S),
                               _lse_reference(q, k, True, window)[0],
                               rtol=2e-5, atol=2e-5)

    def both(f):
        return (f(q, k, v),) + jax.grad(lambda *a: jnp.sum(jnp.sin(f(*a))),
                                        argnums=(0, 1, 2))(q, k, v)
    want = both(functools.partial(reference_attention, causal=True,
                                  window=window))
    for a, b, name in zip(both(attend), want, ("out", "dq", "dk", "dv")):
        assert a.shape == b.shape
        fwd = name == "out"
        np.testing.assert_allclose(a, b, rtol=2e-4 if fwd else 5e-3,
                                   atol=2e-5 if fwd else 5e-4, err_msg=name)


def test_flash_residual_gauge_counts_hbm_tiles_of_one_differentiation():
    """``attention/flash_residual_mb``: MB of the (o, lse) pairs one
    differentiation's forward rules name, a minor dimension counted in
    128-lane tiles — a padded [BH, S, 1] statistic could not hide in it —
    and a second differentiation starts from nothing."""
    from deepspeed_tpu.telemetry.registry import default_registry
    fa = _fa()
    q = jnp.zeros((1, 2, 256, 128), jnp.bfloat16)

    def two_layers(x):
        for window in (None, 64):
            x = flash_attention(x, x, x, causal=True, window=window,
                                block_q=128, block_k=128, chunk=128,
                                interpret=True)
        return jnp.sum(x.astype(jnp.float32))

    one = (2 * 256 * 128 * 2 + 2 * 256 * 4) / 1e6       # bf16 o + f32 lse
    for _ in range(2):
        jax.make_jaxpr(jax.grad(two_layers))(q)
        assert default_registry().peek_gauge(
            "attention/flash_residual_mb") == pytest.approx(2 * one)
    column = jax.ShapeDtypeStruct((2, 256, 1), jnp.float32)
    fa._name_residuals(jax.ShapeDtypeStruct((2, 256, 128), jnp.bfloat16),
                       column)
    # ... and a [BH, S, 1] column reads the 128 lanes a value it is stored in
    assert default_registry().peek_gauge("attention/flash_residual_mb") \
        == pytest.approx((2 * 256 * 128 * 2 + 2 * 256 * 128 * 4) / 1e6)


def test_gpt2_dots_flash_fc_lean_is_unchanged_by_the_block_policy(
        monkeypatch):
    """GPT-2's blocks take their named policy as before
    (``_maybe_remat``), and joining ``block_remat_policy``'s base set to
    ``dots_flash_fc_lean`` would change nothing there: the policy keeps
    both flash names already and GPT-2 names no ``moe_experts`` — the
    gradient jaxpr is the same but for the policy function's name."""
    import re
    from deepspeed_tpu.models import gpt2
    cfg = gpt2.GPT2Config(vocab_size=128, n_positions=64, n_embd=64,
                          n_layer=2, n_head=2, scan_layers=True, remat=True,
                          remat_policy="dots_flash_fc_lean", use_flash=True,
                          dtype=jnp.float32)
    model = gpt2.GPT2LMHeadModel(cfg)
    ids = jnp.zeros((1, 64), jnp.int32)
    params = model.init(jax.random.PRNGKey(0), ids)["params"]

    def jaxpr():
        text = str(jax.make_jaxpr(jax.grad(lambda p: model.apply(
            {"params": p}, ids, labels=ids)))(params))
        assert "flash_lse" in text
        return re.sub(r"policy=[^\n]*", "policy=", text)

    named = jaxpr()
    monkeypatch.setattr(gpt2, "_maybe_remat", lambda cfg, parent, name: (
        gpt2.nn.remat(gpt2.gather_edge_block(gpt2.Block, parent, name),
                      prevent_cse=False, static_argnums=(2,),
                      policy=gpt2.block_remat_policy(cfg.remat_policy))))
    assert jaxpr() == named


# ------------------ a q·k width that is not the value width (latent attention)

@pytest.mark.parametrize("H,Hkv,S,D,Dv,causal,blocks,chunk", [
    (2, 2, 128, 192, 128, True, (64, 64), 128),   # the published widths
    (2, 2, 128, 192, 128, True, (64, 64), 64),    # block == chunk
    (3, 3, 256, 48, 32, True, (64, 64), 128),     # small, 1.5 x
    (3, 3, 256, 48, 32, True, (64, 32), None),    # the entry's own chunk
    (2, 2, 128, 48, 32, False, (32, 64), 64),     # nothing masked
    (4, 2, 128, 48, 32, True, (64, 64), 128),     # grouped keys and values
    (2, 2, 128, 32, 48, True, (64, 64), 64),      # values the wider
    (1, 1, 48, 48, 32, True, (None, None), None),  # one block spans S
], ids=lambda v: str(v))
def test_chunked_kernels_take_unequal_qk_and_value_widths(H, Hkv, S, D, Dv,
                                                          causal, blocks,
                                                          chunk):
    """The chunked forward and backward kernels with q and k ``D`` wide and v
    ``Dv`` wide against the reference (scale 1 / sqrt(D)): the output and dv
    are ``Dv`` wide, dq and dk ``D`` wide; every call is the chunked
    family's whatever S."""
    q, k, _ = _qkv((1, H, S, D), seed=D + S)
    k = k[:, :Hkv]
    v = _qkv((1, Hkv, S, Dv), seed=Dv)[2]

    def both(attend):
        return (attend(q, k, v),) + jax.grad(
            lambda *a: jnp.sum(jnp.sin(attend(*a))), argnums=(0, 1, 2))(
            q, k, v)

    flash = functools.partial(flash_attention, causal=causal,
                              block_q=blocks[0], block_k=blocks[1],
                              chunk=chunk, interpret=True)
    got = both(flash)
    want = both(functools.partial(reference_attention, causal=causal))
    assert got[0].shape == (1, H, S, Dv) and got[1].shape == q.shape \
        and got[2].shape == k.shape and got[3].shape == v.shape
    for a, b, name in zip(got, want, ("out", "dq", "dk", "dv")):
        fwd = name == "out"
        np.testing.assert_allclose(a, b, rtol=2e-4 if fwd else 5e-3,
                                   atol=2e-5 if fwd else 5e-4, err_msg=name)
    # forward and backward: two calls on the chunked family's (B*H, pairs)
    # grid
    grids = pallas_grids(jax.make_jaxpr(jax.grad(
        lambda *a: flash(*a).sum(), argnums=(0, 1, 2)))(q, k, v).jaxpr)
    assert len(grids) == 2 and all(len(g) == 2 and g[0] == H for g in grids)


def test_unequal_widths_in_bf16_and_their_gauges():
    from deepspeed_tpu.telemetry.registry import default_registry
    q, k, _ = _qkv((1, 2, 128, 192), dtype=jnp.bfloat16)
    v = _qkv((1, 2, 128, 128), seed=1, dtype=jnp.bfloat16)[2]
    got = flash_attention(q, k, v, causal=True, interpret=True)
    want = reference_attention(q, k, v, causal=True)
    assert got.dtype == jnp.bfloat16 and got.shape == (1, 2, 128, 128)
    np.testing.assert_allclose(got.astype(jnp.float32),
                               want.astype(jnp.float32), atol=3e-2)
    gauges = default_registry().snapshot()["gauges"]
    assert gauges["attention/mla_qk_dim"] == 192
    assert gauges["attention/mla_v_dim"] == 128


def test_equal_widths_trace_the_same_calls_as_before_the_value_width():
    """The value width changes nothing where it is the q·k width: the
    traced call's block shapes hold one D, and the ``mla`` gauges are not
    touched."""
    from deepspeed_tpu.telemetry.registry import default_registry
    default_registry().gauge("attention/mla_v_dim").set(-1)
    q, k, v = _qkv((1, 2, 256, 32))
    text = str(jax.make_jaxpr(jax.grad(lambda *a: flash_attention(
        *a, causal=True, chunk=128, interpret=True).sum(),
        argnums=(0, 1, 2)))(q, k, v))
    assert "pallas_call" in text and ",48]" not in text
    assert default_registry().snapshot()["gauges"][
        "attention/mla_v_dim"] == -1


@pytest.mark.parametrize("family", ["whole-row", "whole-row backward",
                                    "column-block", "window", "dispatch",
                                    "dispatch window", "no tiling", "k"])
def test_the_other_kernel_families_refuse_unequal_widths_by_name(family):
    """Unequal widths are the chunked family's alone: the whole-row, the
    column-block and the window kernels raise with the shapes, and nothing
    routes to ``reference_attention`` behind the caller's back."""
    import importlib
    from deepspeed_tpu.ops import attention
    fa = importlib.import_module("deepspeed_tpu.ops.pallas.flash_attention")
    q, k, _ = _qkv((1, 2, 128, 48))
    v = _qkv((1, 2, 128, 32), seed=1)[2]
    flat = tuple(t.reshape(2, 128, -1) for t in (q, k, v))
    if family == "whole-row":
        with pytest.raises(ValueError, match=r"whole-row.*\(2, 128, 48\).*"
                                             r"\(2, 128, 32\)"):
            fa._flash_fwd(*flat, 0.1, True, 64, 64, True)
    elif family == "whole-row backward":
        with pytest.raises(ValueError, match="whole-row"):
            fa._flash_bwd(*flat, flat[2], None, flat[2], 0.1, True, 64, 64,
                          True)
    elif family == "column-block":
        with pytest.raises(ValueError, match=r"column-block.*96.*64"):
            fa.flash_attention_bse(*(from_head_major(t) for t in (q, k, v)),
                                   heads=2, causal=True, interpret=True)
    elif family == "window":
        with pytest.raises(ValueError, match=r"window.*\(1, 2, 128, 32\)"):
            flash_attention(q, k, v, causal=True, window=16, interpret=True)
    elif family == "dispatch":
        # the reference path takes them (the CPU's path); a k that is not
        # q's width is refused on every path
        out = attention.dot_product_attention(q, k, v, causal=True,
                                              use_flash=False)
        assert out.shape == (1, 2, 128, 32)
    elif family == "dispatch window":
        with pytest.raises(ValueError, match=r"window=16.*48.*32"):
            attention.dot_product_attention(q, k, v, causal=True, window=16,
                                            use_flash=True)
    elif family == "no tiling":
        odd = tuple(t[:, :, :100] for t in (q, k, v))
        with pytest.raises(ValueError, match=r"chunked kernels alone.*"
                                             r"\(1, 2, 100, 48\)"):
            flash_attention(*odd, causal=True, interpret=True, block_q=64,
                            block_k=64)
    else:
        for call in (functools.partial(flash_attention, interpret=True),
                     functools.partial(attention.dot_product_attention,
                                       use_flash=False)):
            with pytest.raises(ValueError, match=r"one head width.*"
                                                 r"\(1, 2, 128, 32\)"):
                call(q, v, v, causal=True)
