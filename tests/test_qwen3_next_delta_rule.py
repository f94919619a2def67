"""Qwen3-Next's chunked gated delta rule on the CPU at small sizes (its XLA
form here; the model's layers run its Pallas kernels in the interpreter)
against the recurrence as written, and the chunked flash kernels reading
grouped-query K and V in place. The model against the reference:
``tests/test_qwen3_next.py``.
"""

import jax
import jax.numpy as jnp
import numpy as np
import pytest

# the XLA chunked form, whatever the backend: the Pallas kernels that take
# lane-aligned heads on a TPU (and every head size in the interpreter) have
# the same tests in tests/test_gated_delta_kernel.py
from deepspeed_tpu.ops.gated_delta import (CHUNK, gated_delta_recurrence,
                                           unit_lower_inverse)
from deepspeed_tpu.ops.gated_delta import \
    gated_delta_rule_xla as gated_delta_rule
from tests.gated_delta_cases import _grads, _out_and_grads


# ------------------------------------------------- the gated delta rule

def _delta_inputs(S, seed=0, B=2, Hk=2, Hv=4, D=16):
    ks = jax.random.split(jax.random.PRNGKey(seed), 5)
    unit = lambda t: t / jnp.linalg.norm(t, axis=-1, keepdims=True)  # noqa: E731
    q = unit(jax.random.normal(ks[0], (B, S, Hk, D))) * D ** -0.5
    k = unit(jax.random.normal(ks[1], (B, S, Hk, D)))
    v = jax.random.normal(ks[2], (B, S, Hv, D))
    g = -2.0 * jax.nn.softplus(jax.random.normal(ks[3], (B, S, Hv)))
    beta = jax.nn.sigmoid(jax.random.normal(ks[4], (B, S, Hv)))
    return q, k, v, g, beta


@pytest.mark.parametrize("S", [2 * CHUNK, 4 * CHUNK, 3 * CHUNK, 100, 37])
def test_chunked_delta_rule_is_the_recurrence_forward_and_backward(S):
    args = _delta_inputs(S)
    # the rule's undifferentiated call: a gradient program runs the kernels'
    # forward RULE in its place
    got = gated_delta_rule(*args)
    got_grads = _grads(gated_delta_rule, args)
    want, want_grads = _out_and_grads(gated_delta_recurrence, args)
    assert got.shape == want.shape
    np.testing.assert_allclose(got, want, atol=5e-6)
    for name, a, b in zip("q k v g beta".split(), got_grads, want_grads):
        assert float(jnp.abs(a - b).max() / jnp.abs(b).max()) < 2e-5, name


def test_delta_rule_without_writes_reads_nothing_and_keys_alike_are_stable():
    q, k, v, g, beta = _delta_inputs(2 * CHUNK)
    assert not np.any(gated_delta_rule(q, k, v, g, jnp.zeros_like(beta)))
    # every key the same, no decay, beta near one: a Neumann series of L
    # would overflow float32 here; block substitution is exact
    k = jnp.broadcast_to(k[:, :1], k.shape)
    g, beta = jnp.zeros_like(g), jnp.full_like(beta, 0.999)
    np.testing.assert_allclose(gated_delta_rule(q, k, v, g, beta),
                               gated_delta_recurrence(q, k, v, g, beta),
                               atol=2e-5)


def test_unit_lower_inverse_and_its_cotangent():
    lower = jnp.tril(jax.random.normal(jax.random.PRNGKey(0), (3, 64, 64)),
                     -1) * 0.3
    eye = jnp.eye(64)
    inv = unit_lower_inverse(lower)
    np.testing.assert_allclose(inv @ (eye + lower), jnp.broadcast_to(
        eye, inv.shape), atol=1e-4)
    f = lambda fn, x: jnp.sum(jnp.cos(fn(x)))  # noqa: E731
    got = jax.grad(lambda x: f(unit_lower_inverse, x))(lower)
    want = jax.grad(lambda x: f(lambda t: jnp.linalg.inv(eye + t), x))(lower)
    np.testing.assert_allclose(got, want, rtol=1e-3, atol=1e-3)


def test_chunked_flash_kernels_read_grouped_query_kv_in_place():
    """4 query / 2 KV heads through the CHUNKED kernels (forced ``chunk``):
    K and V go in at their own head count, forward and backward, and dk, dv
    come back summed over each group's query heads."""
    from deepspeed_tpu.ops.attention import reference_attention
    from deepspeed_tpu.ops.pallas.flash_attention import flash_attention
    ks = jax.random.split(jax.random.PRNGKey(0), 3)
    q = jax.random.normal(ks[0], (2, 4, 256, 32))
    k = jax.random.normal(ks[1], (2, 2, 256, 32))
    v = jax.random.normal(ks[2], (2, 2, 256, 32))

    def both(fn):
        return jax.value_and_grad(
            lambda *a: jnp.sum(jnp.sin(fn(*a))), argnums=(0, 1, 2))(q, k, v)

    got = both(lambda *a: flash_attention(*a, causal=True, block_q=64,
                                          block_k=64, chunk=128,
                                          interpret=True))
    want = both(lambda *a: reference_attention(*a, causal=True))
    assert float(got[0]) == pytest.approx(float(want[0]), abs=1e-3)
    for a, b in zip(got[1], want[1]):
        assert a.shape == b.shape
        np.testing.assert_allclose(a, b, atol=2e-5)
