"""What the ``tests/test_gated_delta_kernel*.py`` files share: the operands as
a DeltaNet layer hands them over, and the gradients their cases compare."""

import jax
import jax.numpy as jnp


def _inputs(S, rep=2, D=16, B=2, Hk=2, dtype=jnp.float32, seed=0, Dv=None,
            beta_scale=1.0):
    """``Dv``: the value head's width where it is not the key head's ``D``;
    ``beta_scale`` 2: beta in (0, 2), a layer with negative eigenvalues."""
    ks = jax.random.split(jax.random.PRNGKey(seed), 5)
    unit = lambda t: t / jnp.linalg.norm(t, axis=-1, keepdims=True)  # noqa: E731
    q = unit(jax.random.normal(ks[0], (B, S, Hk, D))) * D ** -0.5
    k = unit(jax.random.normal(ks[1], (B, S, Hk, D)))
    v = jax.random.normal(ks[2], (B, S, Hk * rep, Dv or D))
    g = -2.0 * jax.nn.softplus(jax.random.normal(ks[3], (B, S, Hk * rep)))
    beta = beta_scale * jax.nn.sigmoid(
        jax.random.normal(ks[4], (B, S, Hk * rep)))
    return q.astype(dtype), k.astype(dtype), v.astype(dtype), g, beta


def _out_and_grads(fn, args):
    """(``fn(*args)``, the gradient of ``sum(sin(3 out))`` in each of the five
    operands) as ONE program: unjitted, every primitive of the recurrence's
    (or the XLA form's) backward pass is dispatched and compiled on its own."""
    def loss(*a):
        out = fn(*a)
        return jnp.sum(jnp.sin(3.0 * out.astype(jnp.float32))), out
    (_, out), grads = jax.jit(jax.value_and_grad(
        loss, argnums=(0, 1, 2, 3, 4), has_aux=True))(*args)
    return out, grads


def _grads(fn, args):
    return _out_and_grads(fn, args)[1]


def _worst(got, want):
    return {name: float(jnp.abs(a.astype(jnp.float32) - b).max()
                        / jnp.abs(b).max())
            for name, a, b in zip("q k v g beta".split(), got, want)}
