"""The chunked flash kernels' pair-list grid, their single-pass backward and
the log-sum-exp they keep, against the jnp reference in the interpreter (one
kernel family a file: ``tests/test_flash_attention.py``)."""

import functools

import numpy as np
import jax
import jax.numpy as jnp
import pytest

from deepspeed_tpu.ops.attention import reference_attention
from deepspeed_tpu.ops.pallas.flash_attention import flash_attention
from tests.hlo_text import pallas_grids
from tests.flash_cases import (CHUNKED_WALKS, WINDOW_WALKS, _fa,
                               _lse_reference, _named, _qkv,
                               _reference_grads, carried_walk)


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


# ------------------------------------------------------------------------
# the forward walk keeps (o, m, l) in VMEM and carries nothing through its
# loops (ISSUE 67: ``_fwd_walk``): the same tiles in the same order with the
# same arithmetic as the carried walk it replaced, so (o, lse) are its to
# the bit — for the chunked, window, block-diffusion and learned-sparse
# forward alike

def _both_walks(monkeypatch, call):
    """(``call()`` under ``_fwd_walk``, under the carried walk)."""
    got = call()
    count = []
    monkeypatch.setattr(_fa(), "_fwd_walk", carried_walk(count))
    want = call()
    assert count, "the reference walk was never traced"
    return got, want


def _assert_bit_equal(got, want):
    for a, b, name in zip(got, want, ("o", "lse")):
        assert a.shape == b.shape and a.dtype == b.dtype
        assert np.isfinite(np.asarray(a, np.float32)).all(), name
        np.testing.assert_array_equal(np.asarray(a, np.float32),
                                      np.asarray(b, np.float32),
                                      err_msg=name)


@pytest.mark.parametrize("case", list(CHUNKED_WALKS))
def test_forward_walk_in_place_is_the_carried_walk_to_the_bit(case,
                                                             monkeypatch):
    (H, Hkv, S, D, Dv, dtype, causal, block_q, block_k,
     chunk) = CHUNKED_WALKS[case]
    fa = _fa()
    q, _, _ = _qkv((H, S, D), seed=67, dtype=dtype)
    _, k, _ = _qkv((Hkv, S, D), seed=68, dtype=dtype)
    _, _, v = _qkv((Hkv, S, Dv), seed=69, dtype=dtype)
    got, want = _both_walks(monkeypatch, lambda: fa._flash_fwd_chunked(
        q, k, v, D ** -0.5, causal, block_q, block_k, chunk, True, H, Hkv))
    _assert_bit_equal(got, want)
    ref = reference_attention(q[None], k[None], v[None], causal=causal)[0]
    np.testing.assert_allclose(np.asarray(got[0], np.float32),
                               np.asarray(ref, np.float32),
                               **(dict(rtol=2e-4, atol=2e-5)
                                  if dtype == jnp.float32
                                  else dict(rtol=5e-2, atol=5e-2)))


@pytest.mark.parametrize("case", list(WINDOW_WALKS))
def test_window_forward_walk_is_the_carried_walk_to_the_bit(case,
                                                            monkeypatch):
    S, window, cap, walk = WINDOW_WALKS[case]
    fa = _fa()
    H, Hkv, D, block = 4, 2, 64, 64
    q, _, _ = _qkv((H, S, D), seed=70, dtype=jnp.bfloat16)
    _, k, v = _qkv((Hkv, S, D), seed=71, dtype=jnp.bfloat16)
    band = fa._band_plan(S, block, block, window, 2 * D, H // Hkv, cap)
    assert band[0] == walk
    got, want = _both_walks(monkeypatch, lambda: fa._swa_fwd(
        q, k, v, D ** -0.5, window, block, block, band, True, H, Hkv))
    _assert_bit_equal(got, want)
    np.testing.assert_allclose(
        np.asarray(got[1]).reshape(1, H, S),
        _lse_reference(q[None], k[None], True, window), rtol=2e-2, atol=2e-2)


def test_block_diffusion_forward_walk_is_the_carried_walk_to_the_bit(
        monkeypatch):
    """Block length 4 under tiles of 64 and chunks of 128: a noised query
    block's own diagonal tile is a grid step of ONE tile, a clean one's
    chunks walk two."""
    from deepspeed_tpu.ops.pallas import block_diffusion_attention as bd
    H, Hkv, L, D = 4, 2, 256, 64
    q, _, _ = _qkv((H, 2 * L, D), seed=72, dtype=jnp.bfloat16)
    _, k, v = _qkv((Hkv, 2 * L, D), seed=73, dtype=jnp.bfloat16)
    walk = bd._bd_walk(L, 4, 64, 128, False)
    spans = {int(hi - lo) for lo, hi in zip(walk[2], walk[4])}
    assert {1, 2} <= spans
    got, want = _both_walks(monkeypatch, lambda: bd._fwd(
        q, k, v, D ** -0.5, 4, 64, 128, True, H, Hkv))
    _assert_bit_equal(got, want)


def test_learned_sparse_forward_walk_is_the_carried_walk_to_the_bit(
        monkeypatch):
    """A mask tile that is ALL ZERO inside a row's walk (the selection kept
    none of a tile's keys): the row statistics stay finite and the in-place
    walk is still the carried one to the bit."""
    from deepspeed_tpu.ops.pallas import learned_sparse_attention as lsa
    H, Hkv, S, D, block, chunk = 4, 2, 512, 64, 128, 256
    q, _, _ = _qkv((H, S, D), seed=74, dtype=jnp.bfloat16)
    _, k, v = _qkv((Hkv, S, D), seed=75, dtype=jnp.bfloat16)
    rows = np.arange(S)
    keep = (rows[:, None] >= rows[None]) & ((rows[:, None] * 7 + rows[None])
                                            % 3 != 0)
    keep[np.arange(S), np.arange(S)] = True      # a query sees itself
    keep[2 * block:3 * block, :block] = False    # tile (2, 0): none kept
    keep[3 * block:, block:2 * block] = False    # tile (3, 1): none kept
    mask = jnp.asarray(keep[None], jnp.int8)
    got, want = _both_walks(monkeypatch, lambda: lsa._masked_fwd(
        q, k, v, mask, D ** -0.5, block, chunk, True, H, Hkv))
    _assert_bit_equal(got, want)
    s = jnp.einsum("hqd,hkd->hqk", q.astype(jnp.float32),
                   jnp.repeat(k, H // Hkv, axis=0).astype(jnp.float32)) \
        * D ** -0.5
    lse = jax.nn.logsumexp(jnp.where(keep[None], s, -jnp.inf), axis=-1)
    np.testing.assert_allclose(np.asarray(got[1]).reshape(H, S), lse,
                               rtol=2e-2, atol=2e-2)
