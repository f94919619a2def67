"""Qwen3-Next on the CPU at small sizes: the program's model against the
benchmark's plain reference (``benchmark/reference/qwen3_next.py``) for
every layer kind and every gradient leaf, and each named omission failing the
benchmark's check. Two periods, seeded weights, float32. The chunked gated
delta rule against the recurrence: ``tests/test_qwen3_next_delta_rule.py``;
the expert layer told which experts it holds:
``tests/test_qwen3_next_experts.py``; remat and the router's choice:
``tests/test_qwen3_next_remat.py``; the model on the engine:
``tests/test_qwen3_next_engine.py``.
"""

import copy

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from benchmark.families import qwen3_next as fam
from benchmark.reference import qwen3_next as ref
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
    params = jax.jit(fam._model(config, True).init)(
        jax.random.PRNGKey(0), jnp.asarray(ids))["params"]
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
