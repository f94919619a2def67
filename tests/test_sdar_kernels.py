"""The block-diffusion mask kernels
(``ops/pallas/block_diffusion_attention.py``, in the interpreter) against the
dense-mask oracle of ``ops/attention.py``, forward and backward; the walk
against the dense mask by brute force; what the two halves may see. The
model, the reference and the engine: ``tests/test_sdar.py``."""

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from benchmark.families import sdar as family
from deepspeed_tpu.ops import attention as attn_ops
from deepspeed_tpu.ops.pallas import block_diffusion_attention as bd


# ------------------------------------------------------------ the kernels

def _qkv(L, H, Hkv, D, dtype, seed=0):
    ks = jax.random.split(jax.random.PRNGKey(seed), 4)
    shape = lambda h: (1, h, 2 * L, D)          # noqa: E731
    return tuple(jax.random.normal(k, shape(h), dtype)
                 for k, h in zip(ks, (H, Hkv, Hkv, H)))


def _rel(a, b):
    a, b = (np.asarray(t, np.float32) for t in (a, b))
    return np.linalg.norm(a - b) / np.linalg.norm(b)


@pytest.mark.parametrize("L,block_length,block,chunk", [
    (192, 4, None, None),       # L no multiple of 128: tiles of 64
    (192, 32, None, None),
    (256, 4, 32, 64),           # two tiles a key chunk
    (128, 128, 32, 64),         # one diffusion block: nothing masked in a tile
], ids=["L192-b4", "L192-b32", "L256-b4-chunked", "L128-one-block"])
@pytest.mark.parametrize("dtype,tol", [(jnp.float32, 2e-6), (jnp.bfloat16, 2e-2)],
                         ids=["f32", "bf16"])
def test_the_mask_kernels_equal_the_dense_mask_oracle(L, block_length, block,
                                                      chunk, dtype, tol):
    """Forward and backward, grouped-query 8:1 at head_dim 128."""
    q, k, v, do = _qkv(L, 8, 1, 128, dtype)

    def kernel(q, k, v):
        return bd.block_diffusion_attention(q, k, v, block_length,
                                            block=block, chunk=chunk)

    def oracle(q, k, v):
        return attn_ops.reference_block_diffusion_attention(q, k, v,
                                                            block_length)

    def grads(fn):
        return jax.jit(jax.grad(lambda *a: jnp.sum(
            fn(*a).astype(jnp.float32) * do.astype(jnp.float32)),
            argnums=(0, 1, 2)))(q, k, v)

    assert _rel(jax.jit(kernel)(q, k, v), oracle(q, k, v)) < tol
    for got, want in zip(grads(kernel), grads(oracle)):
        assert _rel(got, want) < tol


@pytest.mark.parametrize("L,block_length,block", [
    (8192, 4, 512), (256, 4, 32), (256, 64, 32), (192, 32, 64), (96, 32, 32)])
def test_the_walk_holds_every_allowed_pair_and_no_empty_tile(L, block_length,
                                                             block):
    """The tiles a head's walk computes, against the dense mask by brute
    force: every allowed pair lies in a walked tile, every walked tile holds
    one, and where a diffusion block is shorter than a tile they are
    ``n^2 + 2n``; the same set forward and backward."""
    n = L // block
    small = L if L <= 256 else 1024          # the dense mask at a small L
    scale = L // small
    mask = np.asarray(attn_ops.block_diffusion_mask(small, max(
        1, block_length // scale) if scale > 1 else block_length))
    if scale == 1:
        tiles = mask.reshape(2 * n, block, 2 * n, block).any(axis=(1, 3))
        assert mask.sum() == bd.allowed_pairs(L, block_length) \
            == family.allowed_pairs(L, block_length)
    for by_chunk in (False, True):
        qi, kc, lo, _, hi, *_ = bd._bd_walk(L, block_length, block,
                                            2 * block if n % 2 == 0 else block,
                                            by_chunk)
        cb = 2 if n % 2 == 0 else 1
        walked = np.zeros((2 * n, 2 * n), bool)
        for i, c, a, b in zip(qi, kc, lo, hi):
            assert not walked[i, c * cb + a:c * cb + b].any()
            walked[i, c * cb + a:c * cb + b] = True
        if scale == 1:
            assert (walked == tiles).all()
        assert walked.sum() == bd.tiles_walked(L, block_length, block)
    if block_length < block:
        assert bd.tiles_walked(L, block_length, block) == n * n + 2 * n
    assert bd.tile_overcompute(L, block_length, block) == pytest.approx(
        walked.sum() * block * block / (L * L + L * block_length))


def test_the_clean_half_is_a_block_causal_pass_over_the_clean_rows_alone():
    L, Bk = 128, 4
    q, k, v, _ = _qkv(L, 4, 2, 32, jnp.float32, seed=3)
    out = bd.block_diffusion_attention(q, k, v, Bk)
    pos = np.arange(L)
    seen = (pos[None, :] // Bk) <= (pos[:, None] // Bk)
    alone = attn_ops.reference_attention(
        q[:, :, L:], k[:, :, L:], v[:, :, L:],
        bias=jnp.where(seen, 0.0, -1e30)[None, None])
    assert np.allclose(out[:, :, L:], alone, atol=2e-6)


def test_one_block_is_one_bidirectional_pass_that_sees_no_clean_row():
    L = 64
    q, k, v, _ = _qkv(L, 2, 2, 32, jnp.float32, seed=4)
    out = bd.block_diffusion_attention(q, k, v, L)
    alone = attn_ops.reference_attention(q[:, :, :L], k[:, :, :L],
                                         v[:, :, :L], causal=False)
    assert np.allclose(out[:, :, :L], alone, atol=2e-6)
    moved = bd.block_diffusion_attention(q, k.at[:, :, L:].add(1.0),
                                         v.at[:, :, L:].add(1.0), L)
    assert np.array_equal(out[:, :, :L], moved[:, :, :L])


def test_no_tile_means_a_raise_and_never_a_dense_mask():
    q, k, v, _ = _qkv(96, 2, 2, 32, jnp.float32)
    with pytest.raises(ValueError, match="no tile divides"):
        bd.block_diffusion_attention(q, k, v, 5)
