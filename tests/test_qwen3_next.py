"""Qwen3-Next on the CPU at small sizes: the program's model against the
benchmark's plain reference (``benchmark/reference/qwen3_next.py``) for
every layer kind and every gradient leaf, the chunked gated delta rule (its
XLA form here; the model's layers run its Pallas kernels in the interpreter)
against the recurrence as written, the expert layer told which experts it
holds (the shares add up to the uncut layer; all rows held), and each named
omission failing the benchmark's check. Two periods, seeded weights, float32.
Remat and the router's choice: ``tests/test_qwen3_next_remat.py``; the model
on the engine: ``tests/test_qwen3_next_engine.py``.
"""

import copy

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from benchmark.families import qwen3_next as fam
from benchmark.reference import qwen3_next as ref
from deepspeed_tpu.moe.dropless import (DroplessMoE, rows_to_tokens,
                                        tokens_to_rows)
# the XLA chunked form, whatever the backend: the Pallas kernels that take
# lane-aligned heads on a TPU (and every head size in the interpreter) have
# the same tests in tests/test_gated_delta_kernel.py
from deepspeed_tpu.ops.gated_delta import (CHUNK, gated_delta_recurrence,
                                           unit_lower_inverse)
from deepspeed_tpu.ops.gated_delta import \
    gated_delta_rule_xla as gated_delta_rule
from tests.cell_config import config_file

FILE = config_file("qwen3-next-80b-a3b-ep16-depth4")


def _float32(config):
    """The configuration's rehearsal sizes with every dtype float32: what
    is left between system and reference is the order of operations."""
    config = copy.deepcopy(config)
    config["rehearse_cpu"]["model"]["dtype"] = "float32"
    engine = config["rehearse_cpu"]["train"]["engine"]
    engine["bf16"] = {"enabled": False}
    engine["data_types"] = {"grad_dtype": "fp32"}
    return config


@pytest.fixture(scope="module")
def tiny():
    """(config, weights, ids, the system's step): two periods; the norm
    weights, gates and biases moved off their initial values so that a
    weight read as ``w`` where ``1 + w`` is meant cannot pass."""
    config = _float32(FILE)
    assert fam.sizes(config, True)["num_hidden_layers"] == 8
    ids = np.random.default_rng(0).integers(0, 512, (2, 96)).astype(np.int32)
    params = fam._model(config, True).init(jax.random.PRNGKey(0),
                                           jnp.asarray(ids))["params"]
    keys = iter(jax.random.split(jax.random.PRNGKey(1), 1000))
    params = jax.tree_util.tree_map(
        lambda x: x + 0.1 * jax.random.normal(next(keys), x.shape)
        if x.shape[-1] < 64 or x.ndim == 1 else x, params)
    system = fam.system_step(config, params, ids, jax.devices()[0], True)
    return config, params, ids, system


def test_system_matches_reference_branch_by_branch_and_leaf_by_leaf(tiny):
    config, params, ids, system = tiny
    loss, gnorm, diffs = fam.compare(config, params, ids, jax.devices()[0],
                                     True, system)
    assert float(system[0]) == pytest.approx(loss, abs=2e-5)
    assert diffs["system_grad_norm"] == pytest.approx(gnorm, rel=1e-4)
    assert diffs["routing_differs"] == 0
    assert diffs["routing_assignments"] == 8 * 2 * 96 * 2
    for branch in ("gdn_out_rel", "attn_out_rel", "ffn_out_rel"):
        assert diffs[branch] < 1e-5, branch
    assert len(diffs["by_layer"]) == len(diffs["own_stream_by_layer"]) == 8
    # not pinned: float32 on both sides, so the first layer and the adds
    # agree to rounding
    assert max(diffs["own_stream_by_layer"][0][1:]) < 1e-5
    assert diffs["stream_add_rel"] < 1e-6
    leaves = diffs["grad_leaf_rel"]
    assert set(leaves) == set(FILE["train"]["tolerance"]["grad_leaf_rel"])
    for name, rel in leaves.items():
        # the decay's two scalars a head: a sum over every token of terms
        # the chunked form gets as differences of larger ones
        assert rel < (0.1 if name in ("A_log", "dt_bias") else 1e-4), name
    # and the benchmark's own verdict at the file's tolerances
    checks, _ = fam.judge_train(config, float(system[0]),
                                diffs["system_grad_norm"], loss, gnorm, diffs)
    assert all(checks.values()), checks


@pytest.mark.parametrize("omission,override,branch", [
    ("no decay gate", {"decay_gate": False}, "gdn_out_rel"),
    ("beta left out", {"use_beta": False}, "gdn_out_rel"),
    ("RoPE over the whole head", {"rotary_dim": 32}, "attn_out_rel"),
    ("attention's output gate left out", {"output_gate": False},
     "attn_out_rel"),
    ("shared expert ungated", {"shared_gate": False}, "ffn_out_rel"),
    ("top-k not renormalised", {"norm_topk_prob": False}, "ffn_out_rel"),
])
def test_each_omission_fails_the_check(tiny, monkeypatch, omission, override,
                                       branch):
    """The reference WITH the omission is a model the system is not: the
    benchmark's comparison must say so, by the branch the omission is in."""
    config, params, ids, (loss, layers, _) = tiny
    sizes = fam.reference_sizes(config, True)
    assert override.keys() <= ref.forward.__kwdefaults__.keys() | sizes.keys()
    monkeypatch.setattr(fam, "reference_sizes",
                        lambda *a: dict(sizes, **override))
    _, detail = fam._reference("forward", config, params, ids,
                               jax.devices()[0], True, tuple(layers))
    kinds = fam.layer_kinds(8, 4)
    diffs = jax.tree_util.tree_map(float, fam.branch_differences(
        layers, detail["layers"], kinds))
    tol = FILE["train"]["tolerance"]
    assert diffs[branch] > 3 * tol[branch], (omission, diffs)
    if branch == "attn_out_rel":
        # the first DeltaNet layer runs before any attention layer and is
        # still right: the fault is told apart
        assert diffs["by_layer"][0][0] < 1e-4
    whole = dict(diffs, system_grad_norm=1.0, grad_leaf_rel={
        name: 0.0 for name in tol["grad_leaf_rel"]},
        own_stream_by_layer=[["linear", 0.0, 0.0, 0.0]], stream_add_rel=0.0)
    checks, _ = fam.judge_train(config, 1.0, 1.0, 1.0, 1.0, whole)
    assert not all(checks.values()), omission


@pytest.mark.parametrize("fault", ["mixer branch lost", "expert branch lost"])
def test_a_wrong_residual_add_fails_the_stream_check(tiny, fault):
    """The pinned comparison hands the reference the system's stream, so a
    wrong add in the system is invisible to it; ``stream_add_rel`` is what
    sees it: a block whose stream after the mixer is its input alone, and a
    block whose output lost its expert branch (the next block then starts
    from another input than the one recorded). Each reads the lost branch's
    share of the stream."""
    config, params, ids, (_, layers, _) = tiny
    x_in = params["embed_tokens"][ids]
    tol = FILE["train"]["tolerance"]["stream_add_rel"]
    worst, by_layer = fam.stream_add_differences(x_in, layers)
    assert float(worst) < 1e-6 and len(by_layer) == 8
    layers = [dict(layer) for layer in layers]
    if fault == "mixer branch lost":
        layers[2]["x_mid"] = layers[2]["x_mid"] - layers[2]["mixer_out"]
    else:
        layers[3]["x_mid"] = layers[3]["x_mid"] - layers[2]["ffn_out"]
    worst, by_layer = fam.stream_add_differences(x_in, layers)
    assert float(worst) > tol
    if fault == "mixer branch lost":
        assert float(worst) == pytest.approx(float(by_layer[2][1]), rel=1e-3)


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
    got = gated_delta_rule(*args)
    want = gated_delta_recurrence(*args)
    assert got.shape == want.shape
    np.testing.assert_allclose(got, want, atol=5e-6)

    def grads(fn):
        return jax.grad(lambda *a: jnp.sum(jnp.sin(3.0 * fn(*a))),
                        argnums=(0, 1, 2, 3, 4))(*args)

    for name, a, b in zip("q k v g beta".split(), grads(gated_delta_rule),
                          grads(gated_delta_recurrence)):
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
