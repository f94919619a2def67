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
