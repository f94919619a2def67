"""Laguna on the CPU at small sizes: the program's model against the
benchmark's plain reference (``benchmark/reference/laguna.py``) for every
layer kind and every gradient leaf, with all experts held and with a share;
each named omission failing the benchmark's check; the layer plan of the
published depth and of the cut; YaRN's angles against the formula written
out by hand. Seeded weights, float32. The eight shares adding up to the
uncut layer, and the window kernels where flash is on:
``tests/test_laguna_layers.py``; remat and the router's choice:
``tests/test_laguna_remat.py``; the model on the engine:
``tests/test_laguna_engine.py``.
"""

import copy
import dataclasses
import math

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from benchmark.families import laguna as fam
from benchmark.reference import laguna as ref
from deepspeed_tpu.models.laguna import (FULL, SLIDING, LagunaConfig,
                                         LagunaForCausalLM, block_paths,
                                         laguna_tiny,
                                         yarn_rope_angles)
from deepspeed_tpu.models.llama import rope_angles
from tests.cell_config import config_file

FILE = config_file("laguna-xs2-33b-a3b-ep8-depth5")


def _published(**over):
    """The published model from the configuration file: its ``published``
    block over the cut's keys (both RoPE sets are in the cut as published),
    all 256 experts on one rank unless ``over`` says otherwise."""
    config = {**FILE, **{k: v for k, v in FILE["published"].items()
                         if k in fam._SIZE_KEYS},
              "expert_parallel_size": 1, **over}
    return fam.model_config(config, rehearse=False)


def _float32(config, **sizes):
    """The configuration's rehearsal sizes with every dtype float32: what
    is left between system and reference is the order of operations."""
    config = copy.deepcopy(config)
    config["rehearse_cpu"]["model"]["dtype"] = "float32"
    engine = config["rehearse_cpu"]["train"]["engine"]
    engine["bf16"] = {"enabled": False}
    engine["data_types"] = {"grad_dtype": "fp32"}
    config["rehearse_cpu"].update(sizes)
    return config


def _tiny(config, seed=0, seq=96):
    """(config, weights, ids, the system's step); the norm weights and the
    narrow matrices moved off their initial values so that a weight left
    out (a gate read as one) cannot pass."""
    vocab = fam.sizes(config, True)["vocab_size"]
    ids = np.random.default_rng(seed).integers(0, vocab, (2, seq)).astype(
        np.int32)
    params = jax.jit(fam._model(config, True).init)(
        jax.random.PRNGKey(seed), jnp.asarray(ids))["params"]
    keys = iter(jax.random.split(jax.random.PRNGKey(1), 1000))
    params = jax.tree_util.tree_map(
        lambda x: x + 0.1 * jax.random.normal(next(keys), x.shape)
        if x.shape[-1] < 64 or x.ndim == 1 else x, params)
    system = fam.system_step(config, params, ids, jax.devices()[0], True)
    return config, params, ids, system


@pytest.fixture(scope="module")
def tiny():
    """The file's five layers (dense-full, 3 sliding, sparse-full), one of
    four expert shares held."""
    return _tiny(_float32(FILE))


@pytest.fixture(scope="module")
def tiny_all_experts():
    """Nine layers (1 + 2 periods), every expert held."""
    nine = {"num_hidden_layers": 9, "expert_parallel_size": 1,
            "num_experts": 16,
            "layer_types": [FULL if i % 4 == 0 else SLIDING
                            for i in range(9)],
            "mlp_layer_types": ["dense"] + ["sparse"] * 8,
            "num_attention_heads_per_layer": [3 if i % 4 == 0 else 4
                                              for i in range(9)]}
    return _tiny(_float32(FILE, **nine), seed=3)


@pytest.mark.parametrize("which", ["tiny", "tiny_all_experts"])
def test_system_matches_reference_branch_by_branch_and_leaf_by_leaf(
        which, request):
    config, params, ids, system = request.getfixturevalue(which)
    n = fam.sizes(config, True)["num_hidden_layers"]
    loss, gnorm, diffs = fam.compare(config, params, ids, jax.devices()[0],
                                     True, system)
    assert float(system[0]) == pytest.approx(loss, abs=2e-5)
    assert diffs["system_grad_norm"] == pytest.approx(gnorm, rel=1e-4)
    assert diffs["routing_differs"] == 0
    assert diffs["routing_assignments"] == (n - 1) * 2 * 96 * 2
    for branch in ("full_out_rel", "swa_out_rel", "dense_out_rel",
                   "ffn_out_rel"):
        assert diffs[branch] < 1e-5, branch
    assert len(diffs["by_layer"]) == len(diffs["own_stream_by_layer"]) == n
    # not pinned: float32 on both sides, so every layer and the adds agree
    assert max(max(r[2:]) for r in diffs["own_stream_by_layer"]) < 1e-4
    assert diffs["stream_add_rel"] < 1e-6
    # the window: far from causal, and blind past its 32 keys
    assert diffs["window_vs_causal_rel"] > 0.3
    assert diffs["window_leak_rel"] == 0.0 < 0.3 < diffs["causal_leak_rel"]
    leaves = diffs["grad_leaf_rel"]
    assert set(leaves) == set(FILE["train"]["tolerance"]["grad_leaf_rel"])
    assert max(leaves.values()) < 1e-4, leaves
    checks, _ = fam.judge_train(config, float(system[0]),
                                diffs["system_grad_norm"], loss, gnorm, diffs)
    assert all(checks.values()), checks


def test_logits_match_the_reference(tiny):
    """Without labels the model gives logits: the reference's final stream
    through its norm and head."""
    config, params, ids, _ = tiny
    logits = jax.jit(fam._model(config, True).apply)({"params": params},
                                                     jnp.asarray(ids))
    top, layers = fam.reference_view(params, config, True)
    sizes = fam.reference_sizes(config, True)
    with jax.default_matmul_precision("highest"):
        _, detail = jax.jit(lambda *a: ref.forward(*a, **sizes))(
            top, layers, jnp.asarray(ids))
        last = detail["layers"][-1]
        x = last["x_mid"] + last["ffn_out"]
        want = ref.norm(x, top["norm"], sizes["eps"]) @ top["lm_head"].T
    np.testing.assert_allclose(logits, want, atol=2e-4)


@pytest.mark.parametrize("omission,override,branch", [
    ("the window left out (full causal)", {"window": None}, "swa_out_rel"),
    ("attention's per-head gate left out", {"output_gate": False},
     "full_out_rel"),
    ("YaRN's scaling left out", {"yarn": False}, "full_out_rel"),
    ("the routed scaling factor left out", {"routed_scale": 1.0},
     "ffn_out_rel"),
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
    fam._reference_program.cache_clear()
    try:
        _, detail = fam._reference("forward", config, params, ids,
                                   jax.devices()[0], True, tuple(layers))
    finally:
        fam._reference_program.cache_clear()
    diffs = jax.tree_util.tree_map(float, fam.branch_differences(
        layers, detail["layers"], fam._kinds(config, True)))
    tol = FILE["train"]["tolerance"]
    assert diffs[branch] > 3 * tol[branch], (omission, diffs)
    if branch == "swa_out_rel":
        # the leading full-attention layer runs under it and is still right
        assert diffs["by_layer"][0][0] < 1e-5


# --------------------------------------------------------- the layer plan

def test_the_published_depth_builds_as_1_9x4_3_and_the_cut_as_1_4():
    cfg = _published(num_experts=32, expert_parallel_size=8)
    assert (cfg.num_experts, cfg.experts_held, cfg.vocab_size) == (256, 32,
                                                                  100352)
    assert cfg.plan == (1, 4, 9, 3)
    assert [p[0] for p in block_paths(cfg)] == \
        ["lead_0"] + ["layers"] * 36 + ["tail_0", "tail_1", "tail_2"]
    shapes = jax.eval_shape(
        lambda r, x: LagunaForCausalLM(cfg).init(r, x)["params"],
        jax.random.PRNGKey(0), jnp.zeros((1, 128), jnp.int32))
    assert sorted(shapes) == ["embed_tokens", "layers", "lead_0", "lm_head",
                              "norm", "tail_0", "tail_1", "tail_2"]
    layers = shapes["layers"]
    assert sorted(layers) == ["l0", "l1", "l2", "l3"]
    # the period after the leading layer: 3 sliding (64 heads), 1 full (48)
    for j, heads in enumerate((64, 64, 64, 48)):
        attn = layers[f"l{j}"]["attn"]
        assert attn["q_proj"]["kernel"].shape == (9, 2048, heads * 128)
        assert attn["g_proj"]["kernel"].shape == (9, 2048, heads)
        assert attn["k_proj"]["kernel"].shape == (9, 2048, 1024)
    assert layers["l0"]["mlp"]["router"].shape == (9, 2048, 256)
    assert layers["l0"]["mlp"]["gate_proj"].shape == (9, 32, 2048, 512)
    assert shapes["lead_0"]["attn"]["q_proj"]["kernel"].shape == (2048, 6144)
    assert shapes["lead_0"]["mlp"]["gate_proj"]["kernel"].shape == (2048,
                                                                    8192)
    assert shapes["tail_2"]["attn"]["q_proj"]["kernel"].shape == (2048, 8192)
    count = sum(int(np.prod(x.shape))
                for x in jax.tree_util.tree_leaves(shapes))
    assert count == cfg.num_params()
    # all 256 experts held: the published model, 33.443B by this count
    # (34.067B with an element-wise gate: the configuration's assumed (a))
    whole = _published()
    assert whole.num_params() == 33_442_676_736
    elementwise = sum(2048 * h * 127
                      for h in whole.num_attention_heads_per_layer)
    assert (whole.num_params() + elementwise) / 1e9 == \
        pytest.approx(34.067, abs=1e-3)

    cut = fam.model_config(FILE, rehearse=False)
    assert cut.plan == (1, 4, 1, 0)
    assert [k[1] for k in cut.layer_kinds] == [48, 64, 64, 64, 48]
    assert [k[0] for k in cut.layer_kinds] == [FULL, SLIDING, SLIDING,
                                               SLIDING, FULL]
    assert [k[2] for k in cut.layer_kinds] == ["dense"] + ["sparse"] * 4
    assert (cut.num_experts, cut.experts_held, cut.expert_share) == (256, 32,
                                                                     0)
    assert cut.num_params() == 691_632_128


def test_the_plan_follows_the_lists_and_nothing_else():
    """Two leading dense layers, a period of two, one layer left over."""
    cfg = LagunaConfig(
        num_hidden_layers=7, layer_types=[FULL] * 2 + [SLIDING, FULL] * 2
        + [SLIDING], mlp_layer_types=["dense"] * 2 + ["sparse"] * 5,
        num_attention_heads_per_layer=[2, 2, 4, 2, 4, 2, 4],
        rope_parameters=laguna_tiny().rope_parameters)
    assert cfg.plan == (2, 2, 2, 1)
    assert [p[0] for p in block_paths(cfg)] == [
        "lead_0", "lead_1", "layers", "layers", "layers", "layers", "tail_0"]
    with pytest.raises(AssertionError, match="layer_types has 3"):
        dataclasses.replace(cfg, num_hidden_layers=4,
                            layer_types=[FULL] * 3)
    with pytest.raises(TypeError, match="mlp_layer_types"):
        LagunaConfig(num_hidden_layers=1, layer_types=[FULL])


# ------------------------------------------------------------------- YaRN

def test_yarn_angles_are_the_formula_written_out_by_hand():
    """The published full-attention set: rotary dim 64, theta 5e5, factor
    64, original context 4096, beta_fast 64, beta_slow 1."""
    dim, theta, factor, orig = 64, 5e5, 64.0, 4096
    low = dim * math.log(orig / (64 * 2 * math.pi)) / (2 * math.log(theta))
    high = dim * math.log(orig / (1 * 2 * math.pi)) / (2 * math.log(theta))
    assert (math.floor(low), math.ceil(high)) == (5, 16)
    positions = jnp.asarray([0, 1, 17, 4095, 16383])
    cos, sin = yarn_rope_angles(positions, dim, theta, factor, orig, 64.0,
                                1.0, 1.4158883083359672)
    assert cos.shape == sin.shape == (5, 32)
    for i in (0, 5, 9, 16, 31):
        plain = theta ** (-2 * i / dim)
        ramp = min(max((i - 5) / (16 - 5), 0.0), 1.0)
        inv = plain / factor * ramp + plain * (1 - ramp)
        for row, pos in enumerate((0, 1, 17, 4095, 16383)):
            assert float(cos[row, i]) == pytest.approx(
                1.4158883083359672 * math.cos(pos * inv), abs=2e-3)
            assert float(sin[row, i]) == pytest.approx(
                1.4158883083359672 * math.sin(pos * inv), abs=2e-3)
    # below the ramp the plain frequency, above it the scaled one
    np.testing.assert_allclose(
        ref.yarn_inv_freq(dim, theta, factor, orig, 64.0, 1.0)[:6],
        [theta ** (-2 * i / dim) for i in range(6)], rtol=1e-6)
    np.testing.assert_allclose(
        ref.yarn_inv_freq(dim, theta, factor, orig, 64.0, 1.0)[16:],
        [theta ** (-2 * i / dim) / factor for i in range(16, 32)], rtol=1e-6)
    # the attention factor is 0.1 ln(factor) + 1 where not given
    assert 0.1 * math.log(64) + 1 == pytest.approx(1.4158883083359672)
    np.testing.assert_allclose(
        yarn_rope_angles(positions, dim, theta, factor, orig, 64.0, 1.0)[0],
        cos, rtol=1e-6)


def test_yarn_at_factor_one_is_plain_rope():
    positions = jnp.arange(300)
    got = yarn_rope_angles(positions, 64, 5e5, 1.0, 4096, 64.0, 1.0)
    want = rope_angles(positions, 64, 5e5)
    for a, b in zip(got, want):
        # float32 angles up to 300 rad: the blend's rounding, no more
        np.testing.assert_allclose(a, b, atol=2e-5)
