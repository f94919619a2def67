"""The flash window kernels against the masked jnp reference, in the
interpreter (one kernel family a file: ``tests/test_flash_attention.py``)."""

import functools

import numpy as np
import jax
import jax.numpy as jnp
import pytest

from deepspeed_tpu.ops.attention import reference_attention
from deepspeed_tpu.ops.pallas.flash_attention import flash_attention
from tests.hlo_text import pallas_calls, pallas_element_rows, pallas_grids
from tests.flash_cases import _fa, _qkv


# ------------------------------------------------------------------------
# the window kernels (ISSUE 33): a causal band of ``window`` keys; since
# ISSUE 43 a block's whole band is ONE operand block at an element offset
# (``chunk=``: a cap on its rows, which puts a band into several grid steps);
# since ISSUE 53 the backward is ONE call that computes a score tile once,
# a KV head's query heads inside a step and its dk / dv in a float32 ring

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


def _reference_window_grads(q, k, v, do, W):
    """float32 (dq [H, S, D], dk, dv [Hkv, S, D]) of the masked reference:
    the group's query heads summed at their KV head."""
    _, vjp = jax.vjp(lambda *a: functools.partial(
        reference_attention, causal=True, window=W)(*(t[None] for t in a))[0],
        q, k, v)
    return vjp(do)


@pytest.mark.parametrize("S,H,Hkv,W,block_q,block_k,chunk,band", [
    # (tiles a step, steps), (lag, ring rows, heads a step)
    (512, 7, 1, 288, 64, 64, None, ((6, 1), (5, 384, 7))),   # a group of 7
    (512, 8, 1, 200, 64, 64, None, ((5, 1), (4, 320, 8))),   # ... and of 8
    (512, 8, 1, 200, 64, 64, 128, ((2, 3), (4, 320, 8))),    # a band in 3
    (384, 14, 2, 200, 64, 64, 64, ((1, 5), (4, 320, 7))),    # 2 x 7, 5 steps
    (512, 4, 2, 130, 128, 64, None, ((5, 1), (2, 384, 2))),  # block_q 2 x
    (512, 4, 2, 130, 64, 128, None, ((3, 1), (3, 384, 2))),  # block_k 2 x
    (256, 2, 1, 255, 64, 64, None, ((4, 1), (4, 256, 2))),   # lag: every block
], ids=lambda v: str(v))
@pytest.mark.parametrize("where", ["first", "last"])
def test_a_key_block_leaves_once_with_its_groups_sum(S, H, Hkv, W, block_q,
                                                     block_k, chunk, band,
                                                     where):
    """``_swa_bwd`` itself: ONE call whose dk and dv come back at the KV
    heads, in the operands' dtype, a group's query heads already summed —
    block by block the masked reference's at the sequence's FIRST blocks
    (whose band the sequence's start clips, and which leave while the ring
    still fills) and at its LAST (which the flush steps past the last query
    block let out); a block that left twice, early, or from another's slot
    would read wrong there. dq is whole beside them."""
    fa = _fa()
    D = 32
    assert fa._band_plan(S, block_q, block_k, W, 128 * 4, H // Hkv,
                         chunk or 0) == band
    ks = jax.random.split(jax.random.PRNGKey(S + H + W), 4)
    q, do = (jax.random.normal(key, (H, S, D)) for key in (ks[0], ks[3]))
    k, v = (jax.random.normal(key, (Hkv, S, D)) for key in ks[1:3])
    scale = D ** -0.5
    o, lse = fa._swa_fwd(q, k, v, scale, W, block_q, block_k, band, True, H,
                         Hkv)
    got = fa._swa_bwd(q, k, v, o, lse, do, scale, W, block_q, block_k, band,
                      True, H, Hkv)
    want = _reference_window_grads(q, k, v, do, W)
    lag = band[1][0]
    rows = slice(0, (lag + 1) * block_q) if where == "first" \
        else slice(S - (lag + 1) * block_q, S)
    for a, b, name in zip(got, want, ("dq", "dk", "dv")):
        assert a.shape == b.shape and a.dtype == q.dtype, name
        for r in range(rows.start, rows.stop, block_q):
            np.testing.assert_allclose(
                np.asarray(a[:, r:r + block_q]),
                np.asarray(b[:, r:r + block_q]), rtol=5e-4, atol=5e-5,
                err_msg=f"{name} rows {r}..{r + block_q}")


@pytest.mark.parametrize("S,W,block,chunk,rows,steps,lag,tiles", [
    (1024, 64, 64, None, 128, 1, 1, 2.531),     # round_up(64 + 63, 64) rows
    (1024, 128, 64, None, 192, 1, 2, 3.6),
    (1024, 100, 64, None, 192, 1, 2, 3.6),
    (1024, 64, 64, 64, 64, 2, 1, 1.266),        # a cap of one tile: 2 steps
    (1024, 512, 64, 256, 192, 3, 8, 2.571),    # 9 tiles under a cap of 4: 3 x 3
    (16384, 512, 256, None, 768, 1, 2, 3.897),  # Laguna's window at 256
    (16384, 512, 128, None, 640, 1, 4, 6.495),
    (16384, 512, 512, None, 1024, 1, 1, 2.598),    # the Laguna cell's, 2 heads
    (16384, 4096, 512, None, 4608, 1, 8, 9.692),   # the SmallThinker cell's
])
def test_window_grid_walks_the_static_band_count(S, W, block, chunk, rows,
                                                 steps, lag, tiles):
    """A block's band is one operand block of ``rows`` rows: the third grid
    extent of the forward ``pallas_call`` is the band's step count — 1 where
    the rows fit the budget (or the caller's ``chunk=`` cap) — and of the
    ONE backward call that times the group's query heads over those a step
    holds; its second runs ``lag`` steps past S / block, for the last key
    blocks to leave the ring of ``(lag + 1) * block`` rows, and is never a
    tile count; the three gauges say what the tiles compute and how many a
    step takes."""
    from deepspeed_tpu.telemetry.registry import default_registry
    fa = _fa()
    H, Hkv = 4, 2
    band = fa._band_plan(S, block, block, W, 128 * 2, H // Hkv,
                         chunk or 0)
    # both of a group's heads a backward step: 2 x block rows fit
    assert band == ((rows // block, steps), (lag, (lag + 1) * block, 2))
    assert lag == -(-(W - 1) // block)
    if not chunk:
        assert rows == -(-(block + W - 1) // block) * block
    q = jax.ShapeDtypeStruct((1, H, S, 128), jnp.bfloat16)   # the cells'
    kv = jax.ShapeDtypeStruct((1, Hkv, S, 128), jnp.bfloat16)
    jaxpr = jax.make_jaxpr(jax.grad(lambda q, k, v: jnp.sum(flash_attention(
        q, k, v, causal=True, window=W, block_q=block, block_k=block,
        chunk=chunk, interpret=True).astype(jnp.float32)),
        argnums=(0, 1, 2)))(q, kv, kv)
    grids = pallas_grids(jaxpr.jaxpr)
    assert grids == [(H, S // block, steps),
                     (Hkv, S // block + lag, steps)], grids
    # the band's operands: K and V, forward and backward
    assert pallas_element_rows(jaxpr.jaxpr) == [rows] * 4
    # dq, dk and dv leave the one call in the operands' dtype, dk and dv at
    # the KV heads; the ring and nothing else is float32 scratch
    bwd = list(pallas_calls(jaxpr.jaxpr))[-1]
    assert [(v.aval.shape, v.aval.dtype) for v in bwd.outvars] == [
        ((H, S, 128), jnp.bfloat16)] + [((Hkv, S, 128), jnp.bfloat16)] * 2
    over = default_registry().peek_gauge("attention/window_tile_overcompute")
    assert over == pytest.approx(
        fa.window_tile_overcompute(S, block, block, W))
    assert default_registry().peek_gauge(
        "attention/window_tiles_per_grid_step") == pytest.approx(
        fa.window_tiles_per_grid_step(S, block, block, W, band)) \
        == pytest.approx(tiles, abs=0.001)
    assert default_registry().peek_gauge(
        "attention/window_bwd_tiles_per_grid_step") == 2 * rows // block
    if (S, W) == (16384, 512):
        assert over == pytest.approx({512: 2.0, 256: 1.5, 128: 1.25}[block],
                                     abs=0.02)
    if (S, W) == (16384, 4096):
        assert over == pytest.approx(1.125, abs=0.005)


@pytest.mark.parametrize("budget,band", [
    (2 ** 21, ((5, 1), (4, 320, 2))),     # the module's: 320 rows fit whole
    (320 * 512, ((5, 1), (4, 320, 2))),   # ... and so do two heads' blocks
    (200 * 512, ((3, 2), (4, 320, 2))),   # 200 rows of 128 float32 lanes
    (64 * 512, ((1, 5), (4, 320, 1))),    # one tile a step, one head's block
])
def test_a_band_past_the_budget_goes_in_the_fewest_steps_that_fit(
        monkeypatch, budget, band):
    """No knob: the band's rows and the heads a backward step holds follow
    from window, block, head_dim and dtype against ``_BAND_BYTES``, the
    steps are the fewest equal ones that fit and the ring stays whole —
    out, dq, dk, dv are the reference's either way."""
    fa = _fa()
    monkeypatch.setattr(fa, "_BAND_BYTES", budget)
    assert fa._band_plan(512, 64, 64, 200, 128 * 4, 2) == band
    got, want = _window_case(512, 4, 2, 200, 64, 64, None)
    for a, b, name in zip(got, want, ("out", "dq", "dk", "dv")):
        np.testing.assert_allclose(np.asarray(a), np.asarray(b), rtol=5e-4,
                                   atol=5e-5, err_msg=name)


@pytest.mark.parametrize("S,block_q,block_k,W,ring", [
    (16384, 512, 512, 4096, 4608), (16384, 512, 512, 512, 1024),
    (16384, 512, 512, 300, 1024),            # W no multiple of the block
    (16384, 256, 512, 4096, 4608), (16384, 512, 256, 4096, 4608),
    (32768, 512, 512, 16384, 16896),
    (256, 64, 64, 255, 256),                 # never longer than the sequence
    (256, 32, 64, 100, 192),                 # a key tile's rows past the lag
])
def test_the_ring_holds_every_row_a_step_touches(S, block_q, block_k, W, ring):
    """The dk / dv ring: whole blocks of both sizes (no tile and no leaving
    block wraps), at least the rows between the oldest block that has not
    left and the end of the diagonal's key tile, for every query block."""
    fa = _fa()
    lag = -(-(W - 1) // block_q)
    assert fa._band_ring(S, block_q, block_k, W, lag) == ring
    assert ring % block_q == 0 and ring % block_k == 0
    for i in range(S // block_q):
        lo = min(max(i - lag, 0) * block_q,
                 max(i * block_q - W + 1, 0) // block_k * block_k)
        assert -(-(i + 1) * block_q // block_k) * block_k - lo <= ring


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
    # a band whose float32 dk / dv ring no kernel's VMEM holds: the backward
    # says so when it is traced (the forward alone takes the shape)
    wide = jax.ShapeDtypeStruct((1, 1, 65536, 256), jnp.bfloat16)
    attend = functools.partial(flash_attention, causal=True, window=40000,
                               interpret=True, block_q=512, block_k=512)
    assert jax.eval_shape(attend, wide, wide, wide).shape == wide.shape
    with pytest.raises(ValueError, match="ring of 40960 rows"):
        jax.eval_shape(jax.grad(lambda *a: attend(*a).astype(
            jnp.float32).sum(), argnums=(0, 1, 2)), wide, wide, wide)
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
