"""Qwen3-Next's expert layer told which experts it holds, on the CPU at small
sizes: the shares add up to the uncut layer, a share matches the reference
forward and backward, all rows held, and ``rows_to_tokens`` as the transpose
of ``tokens_to_rows``. The model against the reference:
``tests/test_qwen3_next.py``.
"""

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from benchmark.reference import qwen3_next as ref
from deepspeed_tpu.moe.dropless import (DroplessMoE, rows_to_tokens,
                                        tokens_to_rows)


# ---------------------------------- the expert layer told what it holds

H, E, K, F, RANKS = 32, 32, 4, 16, 16


def _layer_weights(seed=0):
    ks = jax.random.split(jax.random.PRNGKey(seed), 8)
    n = lambda i, *shape: 0.3 * jax.random.normal(ks[i], shape)  # noqa: E731
    return {"router": n(0, H, E), "gate": n(1, E, H, F), "up": n(2, E, H, F),
            "down": n(3, E, F, H), "shared_gate": n(4, H, F),
            "shared_up": n(5, H, F), "shared_down": n(6, F, H),
            "shared_expert_gate": n(7, H, 1)}


def _share(p, x, rank, held=E // RANKS, shared=False):
    """The system's layer holding ``held`` experts from ``rank * held``."""
    layer = DroplessMoE(E, K, F, norm_topk_prob=True, dtype=jnp.float32,
                        experts_held=held, expert_share=rank,
                        shared_d_ff=F if shared else 0)
    lo = rank * held
    weights = {"router": p["router"], "gate_proj": p["gate"][lo:lo + held],
               "up_proj": p["up"][lo:lo + held],
               "down_proj": p["down"][lo:lo + held]}
    if shared:
        weights.update({f"shared_{n}_proj": p[f"shared_{n}"]
                        for n in ("gate", "up", "down")},
                       shared_expert_gate=p["shared_expert_gate"])
    out, vs = layer.apply({"params": weights}, x, mutable=["stats"])
    return out, {k: float(v[0]) for k, v in vs["stats"].items()}


def test_the_sixteen_shares_and_the_shared_expert_once_are_the_whole_layer():
    p = _layer_weights()
    x = jax.random.normal(jax.random.PRNGKey(9), (2, 24, H))
    with jax.default_matmul_precision("highest"):
        whole, _, _, shared, _ = ref.moe(x.reshape(-1, H), p, K, 0)
        parts, held = [], 0.0
        for rank in range(RANKS):
            out, stats = _share(p, x, rank)
            parts.append(out)
            held += stats["moe_rows_held_share"]
            assert stats["moe_dropped_rows"] == 0
    assert held == pytest.approx(1.0)       # every routed row is somewhere
    np.testing.assert_allclose(
        sum(parts).reshape(-1, H) + shared, whole, atol=2e-5)
    # a rank's own output carries the shared expert in full
    with jax.default_matmul_precision("highest"):
        out, _ = _share(p, x, 3, shared=True)
    np.testing.assert_allclose(out.reshape(-1, H),
                               parts[3].reshape(-1, H) + shared, atol=2e-5)


@pytest.mark.parametrize("boost,held,slabs", [
    (0.0, 8, 1), (3.0, 8, 2), (20.0, 4, 4)],
    ids=["first_slab", "second_slab", "further_slabs"])
def test_a_share_matches_the_reference_forward_and_backward(boost, held,
                                                            slabs):
    """Rank 1 of 4 (8 experts) or of 8 (4 experts), against the reference
    holding the same share: outputs and the gradient of every weight and of
    the input, on the row arrays cut to the static cap (the routing sends
    its share here, under twice the mean); with a router that prefers the
    held experts so that more than the cap arrives, through the second slab;
    and with every row here, through the checkpointed scan over the slabs
    past the second."""
    p = _layer_weights(1)
    rank = 1
    lo = rank * held
    p["router"] = p["router"].at[0, lo:lo + held].add(boost)
    x = jax.random.normal(jax.random.PRNGKey(3), (2, 24, H)).at[..., 0].set(1.0)
    assert _share(p, x, rank, held, True)[1]["moe_held_slabs"] == slabs

    def system(p, x):
        return jnp.sum(jnp.sin(_share(p, x, rank, held, True)[0]))

    def reference(p, x):
        q = dict(p, **{n: p[n][lo:lo + held] for n in ("gate", "up", "down")})
        return jnp.sum(jnp.sin(ref.moe(x.reshape(-1, H), q, K, lo)[0]))

    with jax.default_matmul_precision("highest"):
        assert float(system(p, x)) == pytest.approx(float(reference(p, x)),
                                                    abs=1e-4)
        got = jax.grad(system, argnums=(0, 1))(p, x)
        want = jax.grad(reference, argnums=(0, 1))(p, x)
    for a, b in zip(jax.tree_util.tree_leaves(got),
                    jax.tree_util.tree_leaves(want)):
        np.testing.assert_allclose(a.reshape(b.shape), b, atol=3e-5)
    # experts outside the share got no gradient from the reference either
    assert not np.any(want[0]["gate"][:lo]) and np.any(want[0]["gate"][lo])


def test_every_row_held_takes_the_uncut_arrays_and_is_exact():
    """A router that sends every token's k choices to the held experts: all
    T x k rows are here, eight times the static cap."""
    p = _layer_weights(2)
    held, rank = 4, 2
    lo = rank * held
    p["router"] = p["router"].at[0, lo:lo + held].add(20.0)
    x = jax.random.normal(jax.random.PRNGKey(4), (2, 24, H)).at[..., 0].set(2.0)
    with jax.default_matmul_precision("highest"):
        out, stats = _share(p, x, rank, held, shared=True)
        q = dict(p, **{n: p[n][lo:lo + held] for n in ("gate", "up", "down")})
        want = ref.moe(x.reshape(-1, H), q, K, lo)[0]
    assert stats["moe_rows_held_share"] == 1.0
    assert stats["moe_dropped_rows"] == 0
    np.testing.assert_allclose(out.reshape(-1, H), want, atol=3e-5)
    # and none: a rank nothing is routed to returns the shared expert alone
    with jax.default_matmul_precision("highest"):
        none, stats = _share(p, x, 0, held, shared=True)
        shared = ref.moe(x.reshape(-1, H), q, K, lo)[3]
    assert stats["moe_rows_held_share"] == 0.0
    np.testing.assert_allclose(none.reshape(-1, H), shared, atol=3e-5)


def _by_hand():
    return 10, 3, 4, [0, 0, 0, 2, 2, 9, 5, 5, 5, 7, 1, 1, 10, 10, 10, 10]


def _drawn(T, k, M, width, held):
    """``held`` of a random routing's T x k assignments, M rows long."""
    picked = np.random.default_rng(0).permutation(T * k)[:held] // k
    return T, k, width, picked.tolist() + [T] * (M - held)


@pytest.mark.parametrize("case", [
    _by_hand(), _drawn(200, 10, 136, 256, 102), _drawn(32, 1, 24, 128, 0)],
    ids=["by_hand", "T200_k10_three_quarters_held", "T32_k1_no_row"])
def test_rows_to_tokens_is_the_transpose_of_tokens_to_rows(case):
    T, k, width, tok = case
    tok = jnp.asarray(tok)
    M, held = tok.shape[0], int(jnp.sum(tok < T))
    x = jax.random.normal(jax.random.PRNGKey(0), (T, width))
    rows = tokens_to_rows(x, tok, k)
    assert rows.shape == (M, width) and not np.any(rows[held:])
    if held:
        np.testing.assert_array_equal(rows[held - 1], x[tok[held - 1]])
    r = jax.random.normal(jax.random.PRNGKey(1), (M, width))
    want = jnp.zeros((T + 1, width)).at[tok].add(r)[:T]
    np.testing.assert_allclose(rows_to_tokens(r, tok, T, k), want, atol=1e-6)
    # <P x, r> == <x, P^T r>, through the custom VJPs both ways
    np.testing.assert_allclose(jax.grad(
        lambda x: jnp.sum(tokens_to_rows(x, tok, k) * r))(x), want, atol=1e-6)
    np.testing.assert_allclose(jax.grad(
        lambda r: jnp.sum(rows_to_tokens(r, tok, T, k) * x))(r), rows,
        atol=1e-6)
