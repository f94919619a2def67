"""The flash window kernels against the masked jnp reference, in the
interpreter (one kernel family a file: ``tests/test_flash_attention.py``)."""

import functools

import numpy as np
import jax
import jax.numpy as jnp
import pytest

from deepspeed_tpu.ops.attention import reference_attention
from deepspeed_tpu.ops.pallas.flash_attention import flash_attention
from tests.hlo_text import pallas_element_rows, pallas_grids
from tests.flash_cases import _fa, _qkv


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
