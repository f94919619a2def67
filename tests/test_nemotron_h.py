"""Nemotron-H on the CPU at small sizes: the program's model against the
benchmark's plain reference (``benchmark/reference/nemotron_h.py``) for all
three layer kinds and every gradient leaf, with a share of the experts and
with all of them held; each named omission failing the benchmark's
check; the layer plan of the published pattern string and of the cut.
Seeded weights, float32. The 16 shares adding up to the uncut layer, and the
model on the engine under ZeRO-3 and remat:
``tests/test_nemotron_h_engine.py``.
"""

import copy
import json
import os

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from benchmark import manifest
from benchmark.families import nemotron_h as fam
from benchmark.reference import nemotron_h as ref
from deepspeed_tpu.models.nemotron_h import (NemotronHConfig,
                                             NemotronHForCausalLM,
                                             nemotron_h_tiny)

with open(os.path.join(manifest.HERE, "configs",
                       "nemotron-3-nano-30b-a3b-ep16-depth9.json")) as f:
    FILE = json.load(f)
PUBLISHED = "MEMEM*EMEMEM*EMEMEM*EMEMEM*EMEMEM*EMEMEMEM*EMEMEMEME"


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


def _tiny(config, seed=0, seq=80):
    """(config, weights, ids, the system's step); every vector and narrow
    matrix moved off its initial value so that a weight left out cannot
    pass (the selection bias to a spread that changes the choice), and the
    query and key projections ten times as large, so that the scores are
    far from uniform and a rotation shows, and the Mamba-2 input projection
    too, so that B, C and the steps are of the published model's size and
    the scan is a large part of its branch beside the skip."""
    vocab = fam.sizes(config, True)["vocab_size"]
    ids = np.random.default_rng(seed).integers(0, vocab, (2, seq)).astype(
        np.int32)
    params = jax.jit(fam._model(config, True).init)(
        jax.random.PRNGKey(seed), jnp.asarray(ids))["params"]
    keys = iter(jax.random.split(jax.random.PRNGKey(1), 1000))
    params = jax.tree_util.tree_map(
        lambda x: x + 0.1 * jax.random.normal(next(keys), x.shape)
        if x.shape[-1] < 64 or x.ndim == 1 else x, params)
    params = jax.tree_util.tree_map_with_path(
        lambda path, x: 10.0 * x if any(
            getattr(k, "key", None) in ("q_proj", "k_proj", "in_proj")
            for k in path) else x, params)
    system = fam.system_step(config, params, ids, jax.devices()[0], True)
    return config, params, ids, system


@pytest.fixture(scope="module")
def tiny():
    """The file's nine layers ``MEMEM*EME``, one of four expert shares held
    (2 of 8 experts), 4 Mamba heads of 8 in 2 groups, chunks of 32."""
    return _tiny(_float32(FILE))


@pytest.fixture(scope="module")
def tiny_all_experts():
    """Five layers with every expert held."""
    return _tiny(_float32(FILE, num_hidden_layers=5,
                          hybrid_override_pattern="ME*ME",
                          expert_parallel_size=1, n_routed_experts=8),
                 seed=3)


@pytest.mark.parametrize("which", ["tiny", "tiny_all_experts"])
def test_system_matches_reference_branch_by_branch_and_leaf_by_leaf(
        which, request):
    config, params, ids, system = request.getfixturevalue(which)
    pattern = fam.sizes(config, True)["hybrid_override_pattern"]
    loss, gnorm, diffs = fam.compare(config, params, ids, jax.devices()[0],
                                     True, system)
    assert float(system[0]) == pytest.approx(loss, abs=2e-5)
    assert diffs["system_grad_norm"] == pytest.approx(gnorm, rel=1e-4)
    assert diffs["routing_differs"] == 0
    assert diffs["routing_assignments"] == pattern.count("E") * 2 * 80 * 2
    for branch in ("ssm_out_rel", "attn_out_rel", "ffn_out_rel"):
        assert 0 <= diffs[branch] < 2e-5, branch
    assert len(diffs["by_layer"]) == len(diffs["own_stream_by_layer"]) \
        == len(pattern)
    # not pinned: float32 on both sides, so every layer and the adds agree
    assert max(max(r[1:]) for r in diffs["own_stream_by_layer"]) < 1e-4
    assert diffs["stream_add_rel"] < 1e-6
    leaves = diffs["grad_leaf_rel"]
    assert set(leaves) == set(FILE["train"]["tolerance"]["grad_leaf_rel"])
    assert max(leaves.values()) < 2e-4, leaves
    assert diffs["bias_grad_abs"] == 0.0
    checks, _ = fam.judge_train(config, float(system[0]),
                                diffs["system_grad_norm"], loss, gnorm, diffs)
    assert all(checks.values()), checks


def test_logits_match_the_reference(tiny):
    """Without labels the model gives logits: the reference's final stream
    through its norm and head."""
    config, params, ids, _ = tiny
    logits = jax.jit(fam._model(config, True).apply)({"params": params},
                                                     jnp.asarray(ids))
    sizes = fam.reference_sizes(config, True)
    top, layers = fam.reference_view(params, sizes["pattern"])
    with jax.default_matmul_precision("highest"):
        _, detail = jax.jit(lambda *a: ref.forward(*a, **sizes))(
            top, layers, jnp.asarray(ids))
        # every layer adds its one branch to the stream
        x = top["embed"][jnp.asarray(ids)] + sum(
            row["branch_out"] for row in detail["layers"])
        want = ref.norm(x, top["norm"], sizes["eps"]) @ top["lm_head"].T
    np.testing.assert_allclose(logits, want, atol=3e-4)


# (the omission, what the reference is told instead, the reading that must
# say so)
OMISSIONS = [
    ("softmax for sigmoid", {"experts_over": {"score": "softmax"}},
     "ffn_out_rel"),
    ("the bias left out of the choice",
     {"experts_over": {"use_choice_bias": False}}, "routing"),
    ("the bias added to the weights",
     {"experts_over": {"bias_in_weights": True}}, "ffn_out_rel"),
    ("top-k not renormalised", {"norm_topk_prob": False}, "ffn_out_rel"),
    ("the 2.5 left out", {"routed_scale": 1.0}, "ffn_out_rel"),
    ("relu for relu^2", {"experts_over": {"act": jax.nn.relu}},
     "ffn_out_rel"),
    ("a gated expert", {"experts_over": {"gated": True}}, "ffn_out_rel"),
    ("a gated shared expert", {"experts_over": {"shared": "gated"}},
     "ffn_out_rel"),
    ("a missing shared expert", {"experts_over": {"shared": "missing"}},
     "ffn_out_rel"),
    ("D x dropped", {"mamba_over": {"use_D": False}}, "ssm_out_rel"),
    ("dt_bias dropped", {"mamba_over": {"use_dt_bias": False}},
     "ssm_out_rel"),
    ("the softplus dropped", {"mamba_over": {"use_softplus": False}},
     "ssm_out_rel"),
    ("the gate applied after the norm",
     {"mamba_over": {"gate_before_norm": False}}, "ssm_out_rel"),
    ("the norm over all channels", {"mamba_over": {"norm_groups": 1}},
     "ssm_out_rel"),
    ("the conv bias dropped", {"mamba_over": {"use_conv_bias": False}},
     "ssm_out_rel"),
    ("a rotation applied to q and k", {"theta": 10000.0}, "attn_out_rel"),
]


@pytest.mark.parametrize("omission,override,reading", OMISSIONS,
                         ids=[o[0] for o in OMISSIONS])
def test_each_omission_fails_the_check(tiny, monkeypatch, omission, override,
                                       reading):
    """The reference WITH the omission is a model the system is not: the
    benchmark's comparison must say so, by the reading the omission is
    in."""
    config, params, ids, (loss, layers, _) = tiny
    sizes = fam.reference_sizes(config, True)
    assert override.keys() <= ref.forward.__kwdefaults__.keys() | sizes.keys()
    for over, fn in (("experts_over", ref.experts), ("mamba_over", ref.mamba)):
        assert override.get(over, {}).keys() <= fn.__kwdefaults__.keys()
    monkeypatch.setattr(fam, "reference_sizes", lambda *a: dict(
        sizes, **{k: tuple(sorted(v.items())) if isinstance(v, dict) else v
                  for k, v in override.items()}))
    monkeypatch.setattr(ref, "forward", _with_dicts(ref.forward))
    fam._reference_program.cache_clear()
    try:
        _, detail = fam._reference("forward", config, params, ids,
                                   jax.devices()[0], True, tuple(layers))
    finally:
        fam._reference_program.cache_clear()
    pattern = sizes["pattern"]
    tol = FILE["train"]["tolerance"]
    if reading == "routing":
        # both routers chose on their own streams: the first expert layer's
        # differ little, and the choices differ widely
        own = fam.own_stream_differences(layers, detail["layers"], pattern)
        first = next(row for row in own if row[0] == "E")
        assert first[2] > 5 * tol["own_stream_first_layers"][
            "routing_share"], (omission, own)
        return
    # each layer's branch on its OWN stream: the layers under the first
    # layer of the omission's kind are right, so that layer reads it alone
    own = fam.own_stream_differences(layers, detail["layers"], pattern)
    kind = {"ssm": "M", "ffn": "E", "attn": "*"}[reading.split("_")[0]]
    first = next(row for row in own if row[0] == kind)
    # (a reading that is not a number — a negative step's overflow — fails)
    assert not first[1] <= 3 * tol[reading], (omission, own)


def _with_dicts(forward):
    """``forward`` taking the two ``*_over`` overrides as sorted item tuples
    (hashable, as ``_reference_program``'s cache key needs them)."""
    def wrapped(*a, mamba_over=None, experts_over=None, **kw):
        return forward(*a, mamba_over=dict(mamba_over or ()),
                       experts_over=dict(experts_over or ()), **kw)
    wrapped.__kwdefaults__ = forward.__kwdefaults__
    return wrapped


# --------------------------------------------------------- the layer plan

def test_the_published_pattern_builds_52_layers_and_the_cut_nine():
    whole = NemotronHConfig()
    assert whole.hybrid_override_pattern == PUBLISHED \
        == FILE["published"]["hybrid_override_pattern"]
    assert len(whole.plan) == 52
    assert {k: whole.plan.count(k) for k in "ME*"} == {"M": 23, "E": 23,
                                                       "*": 6}
    assert [i for i, k in enumerate(whole.plan) if k == "*"] == [
        5, 12, 19, 26, 33, 42]
    assert whole.segments == (6, 7, 7, 7, 7, 9, 9)
    assert whole.num_params() == 31_577_940_288
    published = {**FILE, **{k: v for k, v in FILE["published"].items()
                            if k in fam._SIZE_KEYS},
                 "expert_parallel_size": 1}
    built = fam.model_config(published, rehearse=False)
    assert (built.num_params(), built.experts_held, built.n_routed_experts,
            built.vocab_size) == (31_577_940_288, 0, 128, 131072)

    cut = fam.model_config(FILE, rehearse=False)
    assert cut.plan == tuple("MEMEM*EME") and cut.segments == (6, 3)
    assert (cut.n_routed_experts, cut.experts_held, cut.expert_share) == (
        128, 8, 0)
    assert cut.num_params() == 666_963_456
    shapes = jax.eval_shape(
        lambda r, x: NemotronHForCausalLM(cut).init(r, x)["params"],
        jax.random.PRNGKey(0), jnp.zeros((1, 128), jnp.int32))
    assert sorted(shapes) == ["embed_tokens"] + sorted(
        f"layer_{i}" for i in range(9)) + ["lm_head", "norm_f"]
    mamba, moe, attn = (shapes[f"layer_{i}"] for i in (0, 1, 5))
    assert mamba["mamba"]["in_proj"]["kernel"].shape == (2688, 10304)
    assert mamba["mamba"]["conv"].shape == (4, 6144)
    assert mamba["mamba"]["conv_bias"].shape == (6144,)
    assert mamba["mamba"]["out_proj"]["kernel"].shape == (4096, 2688)
    assert moe["mixer"]["router"].shape == (2688, 128)
    assert moe["mixer"]["e_score_correction_bias"].shape == (128,)
    assert moe["mixer"]["up_proj"].shape == (8, 2688, 1856)
    assert moe["mixer"]["down_proj"].shape == (8, 1856, 2688)
    assert moe["mixer"]["shared_up_proj"].shape == (2688, 3712)
    assert not {"gate_proj", "shared_gate_proj",
                "shared_expert_gate"} & set(moe["mixer"])
    assert attn["mixer"]["q_proj"]["kernel"].shape == (2688, 4096)
    assert attn["mixer"]["k_proj"]["kernel"].shape == (2688, 256)
    assert "g_proj" not in attn["mixer"]
    assert sum(int(np.prod(x.shape))
               for x in jax.tree_util.tree_leaves(shapes)) == cut.num_params()


def test_the_plan_follows_the_string_and_nothing_else():
    cfg = nemotron_h_tiny(hybrid_override_pattern="*MM*E")
    assert cfg.plan == ("*", "M", "M", "*", "E") and cfg.segments == (1, 3, 1)
    tree = jax.eval_shape(
        lambda r, x: NemotronHForCausalLM(cfg).init(r, x)["params"],
        jax.random.PRNGKey(0), jnp.zeros((1, 16), jnp.int32))
    assert "mamba" in tree["layer_1"] and "mamba" not in tree["layer_0"]
    assert sum(int(np.prod(x.shape)) for x in
               jax.tree_util.tree_leaves(tree)) == cfg.num_params()
    with pytest.raises(AssertionError, match="3 entries for 9"):
        nemotron_h_tiny(hybrid_override_pattern="ME*", num_hidden_layers=9)
    with pytest.raises(AssertionError, match="not written here"):
        NemotronHConfig(num_hidden_layers=2, hybrid_override_pattern="M-")


def test_attention_is_not_rotated():
    """No cos / sin anywhere in the traced model: q and k go to the kernel
    as projected."""
    cfg = nemotron_h_tiny(hybrid_override_pattern="*")
    ids = jnp.zeros((1, 32), jnp.int32)
    model = NemotronHForCausalLM(cfg)
    params = jax.jit(model.init)(jax.random.PRNGKey(0), ids)["params"]
    text = str(jax.make_jaxpr(lambda p: model.apply({"params": p}, ids))(
        params))
    assert " cos " not in text and " sin " not in text
