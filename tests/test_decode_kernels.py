"""Fused decode-kernel parity tests (ops/pallas/decode.py) vs plain-XLA
references, in interpret mode. The e2e serving path (prompt fill through
the general path + fused single-token decode) is covered by
tests/test_gpt2_inference.py; these pin each kernel's math in isolation.

Reference role: the reference validates its fused inference CUDA kernels
against torch baselines the same way
(tests/unit/test_cuda_forward.py methodology)."""

import numpy as np
import jax
import jax.numpy as jnp
import pytest

from deepspeed_tpu.ops.pallas.decode import (
    matvec_int8, ln_qkv_int8, kv_quant_int8,
    decode_attention_int8, out_ffn_int8)


def _ln_ref(x, w, b, eps=1e-5):
    mu = x.mean(-1, keepdims=True)
    var = ((x - mu) ** 2).mean(-1, keepdims=True)
    return (x - mu) / np.sqrt(var + eps) * w + b


@pytest.fixture
def rs():
    return np.random.RandomState(0)


def test_matvec_int8_matches_xla(rs):
    B, E, N = 2, 256, 512
    x = jnp.asarray(rs.randn(B, E), jnp.float32) * 0.3
    wq = jnp.asarray(rs.randint(-127, 128, (E, N)), jnp.int8)
    b = jnp.asarray(rs.randn(N), jnp.float32) * 0.01
    s = 0.002
    ref = x @ (wq.astype(jnp.float32) * s) + b
    got = matvec_int8(x, wq, s, b)
    np.testing.assert_allclose(np.asarray(got), np.asarray(ref),
                               rtol=1e-5, atol=1e-5)


def test_matvec_int8_gelu(rs):
    B, E, N = 1, 128, 256
    x = jnp.asarray(rs.randn(B, E), jnp.float32) * 0.3
    wq = jnp.asarray(rs.randint(-127, 128, (E, N)), jnp.int8)
    b = jnp.zeros((N,), jnp.float32)
    s = 0.001
    ref = jax.nn.gelu((x @ (wq.astype(jnp.float32) * s) + b),
                      approximate=True)
    got = matvec_int8(x, wq, s, b, act="gelu_tanh")
    np.testing.assert_allclose(np.asarray(got), np.asarray(ref),
                               rtol=1e-5, atol=1e-5)


def test_ln_qkv_int8_matches_xla(rs):
    B, E = 2, 256
    x = jnp.asarray(rs.randn(B, E), jnp.float32)
    lw = jnp.asarray(1.0 + 0.1 * rs.randn(E), jnp.float32)
    lb = jnp.asarray(0.1 * rs.randn(E), jnp.float32)
    wq = jnp.asarray(rs.randint(-127, 128, (E, 3 * E)), jnp.int8)
    b = jnp.asarray(rs.randn(3 * E), jnp.float32) * 0.01
    s = 0.001
    u = _ln_ref(np.asarray(x), np.asarray(lw), np.asarray(lb))
    ref = u @ (np.asarray(wq, np.float32) * s) + np.asarray(b)
    got = ln_qkv_int8(x, lw, lb, wq, s, b)
    np.testing.assert_allclose(np.asarray(got), ref, rtol=2e-4, atol=2e-4)


def test_kv_quant_int8_roundtrip(rs):
    B, H, D = 2, 4, 64
    k = jnp.asarray(rs.randn(B, H, D), jnp.float32)
    v = jnp.asarray(rs.randn(B, H, D), jnp.float32) * 3.0
    kq, ks, vq, vs = kv_quant_int8(k, v)
    assert kq.dtype == jnp.int8 and ks.shape == (B, H, 1)
    k_rt = np.asarray(kq, np.float32) * np.asarray(ks)
    v_rt = np.asarray(vq, np.float32) * np.asarray(vs)
    # symmetric per-head absmax quant: error bounded by scale/2
    assert np.max(np.abs(k_rt - np.asarray(k))) <= np.max(np.asarray(ks))
    assert np.max(np.abs(v_rt - np.asarray(v))) <= np.max(np.asarray(vs))


def test_decode_attention_int8_matches_xla(rs):
    B, H, D, L, pos = 2, 4, 64, 256, 150
    q = jnp.asarray(rs.randn(B, H, 1, D), jnp.float32) * 0.3
    kc = jnp.asarray(rs.randint(-127, 128, (B, H, L, D)), jnp.int8)
    vc = jnp.asarray(rs.randint(-127, 128, (B, H, L, D)), jnp.int8)
    ks = jnp.asarray(np.abs(rs.randn(B, H, L)), jnp.float32) * 0.01 + 1e-3
    vs = jnp.asarray(np.abs(rs.randn(B, H, L)), jnp.float32) * 0.01 + 1e-3
    dn_qk = (((3,), (3,)), ((0, 1), (0, 1)))
    scores = jax.lax.dot_general(q, kc.astype(q.dtype), dn_qk)
    scores = scores * ks[:, :, None, :] * (1.0 / np.sqrt(D))
    vis = jnp.arange(L)[None, None, None, :] <= pos
    scores = jnp.where(vis, scores, -1e30)
    p = jax.nn.softmax(scores, axis=-1) * vs[:, :, None, :]
    ref = jax.lax.dot_general(p.astype(q.dtype), vc.astype(q.dtype),
                              (((3,), (2,)), ((0, 1), (0, 1))))
    # block_l below L exercises the online-softmax carry across blocks
    # (round-4 regression: a missing m_ref writeback only showed multi-block)
    got = decode_attention_int8(q, kc, ks, vc, vs, pos, block_l=64)
    np.testing.assert_allclose(np.asarray(got), np.asarray(ref),
                               rtol=1e-5, atol=1e-6)


def test_decode_attention_pos_zero(rs):
    """First decode step: only position 0 visible -> output == v[0]·vs."""
    B, H, D, L = 1, 2, 64, 128
    q = jnp.asarray(rs.randn(B, H, 1, D), jnp.float32)
    kc = jnp.asarray(rs.randint(-127, 128, (B, H, L, D)), jnp.int8)
    vc = jnp.asarray(rs.randint(-127, 128, (B, H, L, D)), jnp.int8)
    ks = jnp.ones((B, H, L), jnp.float32)
    vs = jnp.full((B, H, L), 0.5, jnp.float32)
    got = decode_attention_int8(q, kc, ks, vc, vs, 0, block_l=64)
    ref = vc[:, :, 0].astype(jnp.float32) * 0.5
    np.testing.assert_allclose(np.asarray(got[:, :, 0]), np.asarray(ref),
                               rtol=1e-6)


def test_out_ffn_int8_matches_xla(rs):
    B, E, F = 1, 256, 512
    ctx = jnp.asarray(rs.randn(B, E), jnp.float32) * 0.3
    x = jnp.asarray(rs.randn(B, E), jnp.float32) * 0.3
    wp = jnp.asarray(rs.randint(-127, 128, (E, E)), jnp.int8)
    w1 = jnp.asarray(rs.randint(-127, 128, (E, F)), jnp.int8)
    w2 = jnp.asarray(rs.randint(-127, 128, (F, E)), jnp.int8)
    bp = jnp.asarray(rs.randn(E), jnp.float32) * 0.01
    b1 = jnp.asarray(rs.randn(F), jnp.float32) * 0.01
    b2 = jnp.asarray(rs.randn(E), jnp.float32) * 0.01
    lw = jnp.asarray(1.0 + 0.1 * rs.randn(E), jnp.float32)
    lb = jnp.asarray(0.1 * rs.randn(E), jnp.float32)
    sp, s1, s2 = 0.002, 0.001, 0.0015
    x1 = np.asarray(x) + (np.asarray(ctx)
                          @ (np.asarray(wp, np.float32) * sp)
                          + np.asarray(bp))
    u = _ln_ref(x1, np.asarray(lw), np.asarray(lb))
    h = np.asarray(jax.nn.gelu(
        jnp.asarray(u @ (np.asarray(w1, np.float32) * s1) + np.asarray(b1)),
        approximate=True))
    ref = x1 + h @ (np.asarray(w2, np.float32) * s2) + np.asarray(b2)
    got = out_ffn_int8(ctx, x, wp, sp, bp, lw, lb, w1, s1, b1, w2, s2, b2,
                       block_f=256)
    np.testing.assert_allclose(np.asarray(got), ref, rtol=2e-4, atol=2e-4)


def test_decode_attention_fp_stacked_multiblock(rs):
    """Full-precision stacked cache variant with block_l below L — pins
    the cross-block online-softmax carry (alpha rescale + m writeback)
    of the shared kernel body on its quantized=False operand layout, and
    the layer block-index maps."""
    from deepspeed_tpu.ops.pallas.decode import decode_attention_fp_stacked
    Lyr, B, H, D, L, pos, layer = 3, 2, 4, 64, 256, 150, 1
    q = jnp.asarray(rs.randn(B, H, 1, D), jnp.float32) * 0.3
    kc = jnp.asarray(rs.randn(Lyr, B, H, L, D), jnp.float32)
    vc = jnp.asarray(rs.randn(Lyr, B, H, L, D), jnp.float32)
    dn_qk = (((3,), (3,)), ((0, 1), (0, 1)))
    scores = jax.lax.dot_general(q, kc[layer], dn_qk) * (1.0 / np.sqrt(D))
    vis = jnp.arange(L)[None, None, None, :] <= pos
    scores = jnp.where(vis, scores, -1e30)
    p = jax.nn.softmax(scores, axis=-1)
    ref = jax.lax.dot_general(p, vc[layer],
                              (((3,), (2,)), ((0, 1), (0, 1))))
    got = decode_attention_fp_stacked(q, kc, vc, pos, layer, block_l=64)
    np.testing.assert_allclose(np.asarray(got), np.asarray(ref),
                               rtol=1e-5, atol=1e-6)


def test_rms_qkv_stacked_matches_xla(rs):
    """norm='rms' mode: RMSNorm + bias-free packed projection over an
    int8 stack — pins the LLaMA qkv kernel math."""
    from deepspeed_tpu.ops.pallas.decode import ln_qkv_int8_stacked
    Lyr, B, E, N, layer = 3, 2, 128, 256, 1
    x = jnp.asarray(rs.randn(B, E), jnp.float32) * 0.5
    lw = jnp.asarray(1.0 + 0.1 * rs.randn(Lyr, E), jnp.float32)
    wq = jnp.asarray(rs.randint(-127, 128, (Lyr, E, N)), jnp.int8)
    s = jnp.full((Lyr,), 0.002, jnp.float32)
    xf = np.asarray(x)
    u = xf / np.sqrt((xf ** 2).mean(-1, keepdims=True) + 1e-5) \
        * np.asarray(lw[layer])
    ref = u @ (np.asarray(wq[layer], np.float32) * 0.002)
    got = ln_qkv_int8_stacked(x, lw, None, wq, s, None, layer,
                              norm="rms")
    np.testing.assert_allclose(np.asarray(got), ref, rtol=2e-4,
                               atol=2e-4)


def test_swiglu_out_ffn_stacked_matches_xla(rs):
    """norm='rms' + act='swiglu': o_proj + residual + RMSNorm + gated
    FFN + residual, bias-free — pins the LLaMA ffn kernel math."""
    from deepspeed_tpu.ops.pallas.decode import out_ffn_int8_stacked
    Lyr, B, E, F, layer = 2, 2, 128, 256, 1
    ctx = jnp.asarray(rs.randn(B, E), jnp.float32) * 0.3
    x = jnp.asarray(rs.randn(B, E), jnp.float32) * 0.3
    wo = jnp.asarray(rs.randint(-127, 128, (Lyr, E, E)), jnp.int8)
    wg = jnp.asarray(rs.randint(-127, 128, (Lyr, E, F)), jnp.int8)
    wu = jnp.asarray(rs.randint(-127, 128, (Lyr, E, F)), jnp.int8)
    wd = jnp.asarray(rs.randint(-127, 128, (Lyr, F, E)), jnp.int8)
    nw = jnp.asarray(1.0 + 0.1 * rs.randn(Lyr, E), jnp.float32)
    so, sg, su, sd = (jnp.full((Lyr,), v, jnp.float32)
                      for v in (0.002, 0.001, 0.0015, 0.001))
    x1 = np.asarray(x) + np.asarray(ctx) @ (
        np.asarray(wo[layer], np.float32) * 0.002)
    u = x1 / np.sqrt((x1 ** 2).mean(-1, keepdims=True) + 1e-5) \
        * np.asarray(nw[layer])
    g = u @ (np.asarray(wg[layer], np.float32) * 0.001)
    up = u @ (np.asarray(wu[layer], np.float32) * 0.0015)
    h = np.asarray(jax.nn.silu(jnp.asarray(g))) * up
    ref = x1 + h @ (np.asarray(wd[layer], np.float32) * 0.001)
    got = out_ffn_int8_stacked(
        ctx, x, wo, so, None, nw, None, wg, sg, None, wd, sd, None,
        layer, act="swiglu", norm="rms", w1b_stack=wu, s1b=su,
        block_f=128)
    np.testing.assert_allclose(np.asarray(got), ref, rtol=3e-4,
                               atol=3e-4)


def test_decode_attention_stacked_gqa_rows(rs):
    """R > 1 grouped-query rows: the R = H/Hkv query heads sharing each
    KV head ride the row axis; the cache is read once. Must equal the
    per-row XLA reference (multi-block path via block_l < L)."""
    from deepspeed_tpu.ops.pallas.decode import (
        decode_attention_int8_stacked)
    Lyr, B, Hkv, R, D, L, pos, layer = 2, 2, 2, 4, 64, 256, 130, 1
    q = jnp.asarray(rs.randn(B, Hkv, R, D), jnp.float32) * 0.3
    kc = jnp.asarray(rs.randint(-127, 128, (Lyr, B, Hkv, L, D)),
                     jnp.int8)
    vc = jnp.asarray(rs.randint(-127, 128, (Lyr, B, Hkv, L, D)),
                     jnp.int8)
    ks = jnp.asarray(np.abs(rs.randn(Lyr, B, Hkv, L)),
                     jnp.float32) * 0.01 + 1e-3
    vs = jnp.asarray(np.abs(rs.randn(Lyr, B, Hkv, L)),
                     jnp.float32) * 0.01 + 1e-3
    dn_qk = (((3,), (3,)), ((0, 1), (0, 1)))
    scores = jax.lax.dot_general(q, kc[layer].astype(q.dtype), dn_qk)
    scores = scores * ks[layer][:, :, None, :] * (1.0 / np.sqrt(D))
    vis = jnp.arange(L)[None, None, None, :] <= pos
    scores = jnp.where(vis, scores, -1e30)
    p = jax.nn.softmax(scores, axis=-1) * vs[layer][:, :, None, :]
    ref = jax.lax.dot_general(p.astype(q.dtype),
                              vc[layer].astype(q.dtype),
                              (((3,), (2,)), ((0, 1), (0, 1))))
    got = decode_attention_int8_stacked(
        q, kc, ks.reshape(Lyr, B, Hkv, 1, L), vc,
        vs.reshape(Lyr, B, Hkv, 1, L), pos, layer, block_l=64)
    np.testing.assert_allclose(np.asarray(got), np.asarray(ref),
                               rtol=1e-5, atol=1e-6)


def test_pick_block_l_refuses_cache_lengths_without_an_aligned_block():
    """ADVICE r5: an odd cache length used to halve down to a 1-row block
    and trip a bare assert (or crawl); now it is a stated precondition."""
    from deepspeed_tpu.ops.pallas.decode import _pick_block_l
    assert _pick_block_l(1024, 20, 64, 2) == 512
    assert _pick_block_l(1000, 20, 64, 2) == 8
    assert _pick_block_l(24, 4, 32, 4) == 24          # one block spans it
    assert _pick_block_l(5, 4, 32, 4) == 5
    with pytest.raises(ValueError, match="cache length 1023"):
        _pick_block_l(1023, 20, 64, 2)
