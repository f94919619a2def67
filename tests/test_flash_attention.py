"""Pallas kernel numerics vs jnp reference — the reference's
test_cuda_forward.py / test_cuda_backward.py methodology (CUDA-vs-HF becomes
Pallas-interpret-vs-jnp, SURVEY §4). This file: the whole-row kernels, the
chunked dispatch and the strip-granular kernels. The other kernel families
have a file each (``tests/test_flash_column_block.py``, ``_window``,
``_chunked``, ``_unequal_widths``) so that ``--dist loadfile`` deals them to
several workers; what they share is ``tests/flash_cases.py``."""

import functools

import numpy as np
import jax
import jax.numpy as jnp
import pytest

from deepspeed_tpu.ops.attention import reference_attention
from deepspeed_tpu.ops.pallas.flash_attention import flash_attention
from deepspeed_tpu.ops.pallas.blocksparse import blocksparse_attention
from tests.flash_cases import (_assert_fwd_and_grads, _fa, _loss_pair, _qkv,
                               _tpu_block)


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
