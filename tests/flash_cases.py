"""What the ``tests/test_flash_*.py`` files share: inputs, the kernel module,
the loss pairs and the references their cases compare against (as
``tests/zero_matrix.py`` serves the ``test_zero_matrix*.py`` files)."""

import numpy as np
import jax
import jax.numpy as jnp

from deepspeed_tpu.ops.attention import reference_attention
from deepspeed_tpu.ops.pallas.flash_attention import flash_attention


def _qkv(shape=(2, 2, 128, 32), seed=0, dtype=jnp.float32):
    rng = jax.random.PRNGKey(seed)
    ks = jax.random.split(rng, 3)
    return tuple(jax.random.normal(k, shape, dtype) for k in ks)


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


def carried_walk(count):
    """The forward walk that ``flash_attention._fwd_walk`` replaced (PR 67),
    under its signature: the state cleared on a block's first step as its
    kernels cleared it, then ``_fwd_block_step`` on the same tiles in the
    same order with (o, m, l) as the loops' CARRIED values, read from the
    state's refs before the first tile and written back after the last.
    ``count``: a list that grows by one a traced walk, so a test can tell
    that the kernel it compared against really ran this walk."""
    from jax.experimental import pallas as pl
    fa = _fa()

    def walk(q, tile, segments, o_ref, m_ref, l_ref, scale, first):
        count.append(1)

        @pl.when(first)
        def _init():
            o_ref[...] = jnp.zeros_like(o_ref)
            m_ref[...] = jnp.full_like(m_ref, fa.NEG_INF)
            l_ref[...] = jnp.zeros_like(l_ref)

        carry = (o_ref[...], m_ref[...], l_ref[...])
        for lo, hi, masked in segments:
            def step(j, c, masked=masked):
                k, v, mask = tile(j, masked)
                return fa._fwd_block_step(q, k, v, c, mask, scale)
            carry = jax.lax.fori_loop(lo, hi, step, carry)
        o_ref[...], m_ref[...], l_ref[...] = carry
    return walk


# H, Hkv, S, D, Dv, dtype, causal, block_q, block_k, chunk — the chunked
# forward's walks (PR 67): 1, 2 and 8 tiles a grid step, masked and not, the
# three widths of the cells (64; 128; 192 with values of 128), grouped keys,
# and a walk that crosses from the unmasked to the masked tiles INSIDE a chunk
F32, BF16 = jnp.float32, jnp.bfloat16
CHUNKED_WALKS = {
    "causal-1-tile-a-step-d64": (2, 2, 256, 64, 64, F32, True, 64, 64, 64),
    "causal-2-tiles-d64": (2, 2, 256, 64, 64, BF16, True, 64, 64, 128),
    "causal-8-tiles-crosses-mid-chunk-d128":
        (2, 1, 1024, 128, 128, BF16, True, 64, 64, 512),
    "causal-8-tiles-d192-values-128":
        (2, 2, 512, 192, 128, BF16, True, 64, 64, 512),
    "causal-grouped-6-to-1-d128":
        (6, 1, 256, 128, 128, F32, True, 64, 64, 128),
    "causal-unequal-blocks-d64": (2, 2, 512, 64, 64, F32, True, 64, 32, 256),
    "full-1-tile-a-step-d128": (2, 2, 256, 128, 128, BF16, False, 64, 64, 64),
    "full-2-tiles-d192-values-128":
        (2, 1, 256, 192, 128, F32, False, 64, 64, 128),
    "full-8-tiles-d64": (4, 2, 512, 64, 64, BF16, False, 64, 64, 512),
}
# S, window, cap of a step's rows (0: the band whole), (tiles a step, steps)
# — the window forward's bands of 1, 2 and 9 tiles at blocks of 64, and a
# band in several steps, the sequence's first blocks walking steps with no
# tile at all
WINDOW_WALKS = {"band-1-tile": (256, 1, 0, (1, 1)),
                "band-2-tiles": (256, 64, 0, (2, 1)),
                "band-9-tiles": (1024, 512, 0, (9, 1)),
                "band-9-tiles-in-5-steps": (1024, 512, 128, (2, 5))}
