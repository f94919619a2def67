"""The DeepSeek-V3 model (Kanana-2's ``model_type``) on the CPU at small
sizes: the program's model against the benchmark's plain reference
(``benchmark/reference/deepseek_v3.py``) for the latent attention, the dense
and the expert branch and every gradient leaf, with a share of the experts
and with all of them held; the logits; the RoPE layout as a relabelling;
each named omission failing the benchmark's check; the selection bias's zero
gradient and its absence from the weights; the parameter count of the
published shapes and of the cut. Seeded weights, float32. The 8 shares
adding up to the uncut layer, the model through the flash kernels and on the
engine under ZeRO-3 and remat: ``tests/test_deepseek_v3_engine.py``.
"""

import copy
import json
import os

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from benchmark import manifest
from benchmark.families import deepseek_v3 as fam
from benchmark.reference import deepseek_v3 as ref
from deepspeed_tpu.models.deepseek_v3 import (DeepseekV3Config,
                                              DeepseekV3ForCausalLM,
                                              deepseek_v3_tiny, rope_pairs)
from deepspeed_tpu.models.llama import rope_angles

with open(os.path.join(manifest.HERE, "configs",
                       "kanana-2-30b-a3b-ep8-depth6.json")) as f:
    FILE = json.load(f)


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
    query and the two key / value projections several times as large, so
    that the scores are far from uniform and a rotation, the latent's norm
    and the shared rotated key show."""
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
        lambda path, x: 6.0 * x if any(
            getattr(k, "key", None) in ("q_proj", "kv_a_proj", "kv_b_proj")
            for k in path) else x, params)
    # ... and the shared expert's up-projection, so that the shared expert
    # is as large a part of an expert layer's output as at the published
    # widths (where a rank holds an eighth of the routed rows beside it)
    params = jax.tree_util.tree_map_with_path(
        lambda path, x: 3.0 * x if getattr(path[-1], "key", None)
        == "shared_up_proj" else x, params)
    system = fam.system_step(config, params, ids, jax.devices()[0], True)
    return config, params, ids, system


@pytest.fixture(scope="module")
def tiny():
    """The file's six layers (1 dense + 5 expert), one of four expert shares
    held (2 of 8 experts), 4 heads of 48 = 32 + 16 / 32 over a latent of
    32."""
    return _tiny(_float32(FILE))


@pytest.fixture(scope="module")
def tiny_all_experts():
    """Three layers with every expert held."""
    return _tiny(_float32(FILE, num_hidden_layers=3, expert_parallel_size=1,
                          n_routed_experts=8), seed=3)


@pytest.mark.parametrize("which", ["tiny", "tiny_all_experts"])
def test_system_matches_reference_branch_by_branch_and_leaf_by_leaf(
        which, request):
    config, params, ids, system = request.getfixturevalue(which)
    kinds = fam._kinds(config, True)
    loss, gnorm, diffs = fam.compare(config, params, ids, jax.devices()[0],
                                     True, system)
    assert float(system[0]) == pytest.approx(loss, abs=2e-5)
    assert diffs["system_grad_norm"] == pytest.approx(gnorm, rel=1e-4)
    assert diffs["routing_differs"] == 0
    assert diffs["routing_assignments"] == kinds.count("sparse") * 2 * 80 * 2
    for branch in ("mla_out_rel", "dense_out_rel", "ffn_out_rel"):
        assert 0 <= diffs[branch] < 2e-5, branch
    assert len(diffs["by_layer"]) == len(diffs["own_stream_by_layer"]) \
        == len(kinds)
    # not pinned: float32 on both sides, so every layer and the adds agree
    assert max(max(r[1:]) for r in diffs["own_stream_by_layer"]) < 1e-4
    assert diffs["stream_add_rel"] < 1e-6
    leaves = diffs["grad_leaf_rel"]
    assert set(leaves) == set(FILE["train"]["tolerance"]["grad_leaf_rel"])
    assert {"attn.q", "attn.kv_a", "attn.kv_a_norm", "attn.kv_b",
            "attn.o"} <= set(leaves)
    assert max(leaves.values()) < 2e-4, leaves
    assert diffs["bias_grad_abs"] == 0.0
    checks, _ = fam.judge_train(config, float(system[0]),
                                diffs["system_grad_norm"], loss, gnorm, diffs)
    assert all(checks.values()), checks


def test_logits_match_the_reference(tiny):
    """Without labels the model gives logits: the reference's final stream
    through its norm and head."""
    config, params, ids, _ = tiny
    got = jax.jit(fam._model(config, True).apply)({"params": params},
                                                  jnp.asarray(ids))
    sizes = fam.reference_sizes(config, True)
    top, layers = fam.reference_view(params, config, True)
    with jax.default_matmul_precision("highest"):
        _, detail = jax.jit(lambda *a: ref.forward(*a, **sizes))(
            top, layers, jnp.asarray(ids))
        last = detail["layers"][-1]         # the final stream, then the head
        want = ref.norm(last["x_mid"] + last["ffn_out"], top["norm"],
                        sizes["eps"]) @ top["lm_head"].T
    assert got.shape == (2, 80, 512)
    np.testing.assert_allclose(got, want, atol=3e-4)


# ------------------------------------------------------ the RoPE layout

def test_the_interleaved_layout_is_a_relabelling_of_the_columns():
    """The model de-interleaves and rotates halves (HF's way), the
    reference turns the pairs (2i, 2i+1) where they lie: the same
    permutation on a query and on the key it meets, so every q . k is the
    same number — and the half-split reading of the SAME columns is not."""
    S, d, theta = 24, 16, 1e6
    q, k = (jax.random.normal(key, (1, 3, S, d))
            for key in jax.random.split(jax.random.PRNGKey(0)))
    cos, sin = rope_angles(jnp.arange(S), d, theta)

    def model(x, interleaved=True):         # [B, heads, S, d] through
        return rope_pairs(x.transpose(0, 2, 1, 3), cos, sin,
                          interleaved).transpose(0, 2, 1, 3)

    def scores(a, b):
        return jnp.einsum("bhsd,bhtd->bhst", a, b)

    want = scores(ref.rope_in_place(q, theta), ref.rope_in_place(k, theta))
    np.testing.assert_allclose(scores(model(q), model(k)), want, atol=1e-5)
    # the model's output IS the reference's with its columns de-interleaved
    np.testing.assert_allclose(
        model(q), jnp.concatenate([ref.rope_in_place(q, theta)[..., 0::2],
                                   ref.rope_in_place(q, theta)[..., 1::2]],
                                  axis=-1), atol=1e-6)
    # pair i is turned by pos x theta^(-2i/d): by hand at one position
    i, pos = 3, 17
    ang = pos * theta ** (-2 * i / d)
    a, b = q[0, 0, pos, 2 * i], q[0, 0, pos, 2 * i + 1]
    turned = ref.rope_in_place(q, theta)[0, 0, pos]
    assert float(turned[2 * i]) == pytest.approx(
        float(a * np.cos(ang) - b * np.sin(ang)), abs=1e-5)
    assert float(turned[2 * i + 1]) == pytest.approx(
        float(b * np.cos(ang) + a * np.sin(ang)), abs=1e-5)
    wrong = scores(model(q, False), model(k, False))
    assert float(jnp.max(jnp.abs(wrong - want))) > 0.5


# (the omission, what the reference is told instead, the reading that must
# say so)
OMISSIONS = [
    ("no rotation", {"attn_over": {"rotate": False}}, "mla_out_rel"),
    ("the latent not normed", {"attn_over": {"latent_norm": False}},
     "mla_out_rel"),
    ("the rotated key seen by one head alone",
     {"attn_over": {"shared_rope_key": False}}, "mla_out_rel"),
    ("scores over sqrt(128) for sqrt(192)",
     {"attn_over": {"scale_dim": 32}}, "mla_out_rel"),
    ("softmax for sigmoid", {"experts_over": {"score": "softmax"}},
     "ffn_out_rel"),
    ("the bias left out of the choice",
     {"experts_over": {"use_choice_bias": False}}, "routing"),
    ("the bias added to the weights",
     {"experts_over": {"bias_in_weights": True}}, "ffn_out_rel"),
    ("top-k not renormalised", {"norm_topk_prob": False}, "ffn_out_rel"),
    ("the 2.448 left out", {"routed_scale": 1.0}, "ffn_out_rel"),
    ("relu for silu", {"experts_over": {"act": jax.nn.relu}}, "ffn_out_rel"),
    ("an ungated expert", {"experts_over": {"gated": False}}, "ffn_out_rel"),
    ("a missing shared expert", {"experts_over": {"shared": "missing"}},
     "ffn_out_rel"),
]


def _with_dicts(forward):
    """``forward`` taking the two ``*_over`` overrides as sorted item tuples
    (hashable, as the test's patched sizes hand them over)."""
    def wrapped(*a, attn_over=None, experts_over=None, **kw):
        return forward(*a, attn_over=dict(attn_over or ()),
                       experts_over=dict(experts_over or ()), **kw)
    wrapped.__kwdefaults__ = forward.__kwdefaults__
    return wrapped


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
    for over, fn in (("experts_over", ref.experts),
                     ("attn_over", ref.attention)):
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
    kinds = fam._kinds(config, True)
    tol = FILE["train"]["tolerance"]
    # each layer's branches on its OWN stream: the layers under the first
    # layer of the omission's kind are right, so that layer reads it alone
    own = fam.own_stream_differences(layers, detail["layers"], kinds)
    if reading == "routing":
        first = next(row for row in own if row[0] == "sparse")
        assert first[3] > 5 * tol["own_stream_first_layer"][
            "routing_share"], (omission, own)
    elif reading == "mla_out_rel":
        assert not own[0][1] <= 3 * tol[reading], (omission, own)
    else:
        first = next(row for row in own if row[0] == "sparse")
        assert not first[2] <= 3 * tol[reading], (omission, own)


def test_the_selection_bias_takes_no_gradient_and_is_not_in_the_weights(tiny):
    """The bias enters the CHOICE of the experts alone: its gradient is
    exactly zero in the system's step, it moves the routing, and with the
    routing pinned it moves nothing of the layer's output."""
    config, params, ids, (_, layers, grads) = tiny
    for name in ("layer_1", "layer_5"):
        assert not np.any(np.asarray(
            grads[name]["mlp"]["e_score_correction_bias"]))
        assert np.any(np.asarray(grads[name]["mlp"]["router"]))
    p = fam.reference_view(params, config, True)[1][1]
    h = jax.random.normal(jax.random.PRNGKey(4), (64, p["router"].shape[0]))
    with jax.default_matmul_precision("highest"):
        out, top_e, _ = ref.experts(h, p, 2, 0)
        moved = dict(p, bias=p["bias"] + 0.3 * jax.random.normal(
            jax.random.PRNGKey(5), p["bias"].shape))
        _, other_e, _ = ref.experts(h, moved, 2, 0)
        pinned, _, _ = ref.experts(h, moved, 2, 0, chosen=top_e)
    assert np.any(np.asarray(top_e) != np.asarray(other_e))
    np.testing.assert_array_equal(pinned, out)


# ------------------------------------------------------------- the counts

def test_the_published_shapes_count_30_67_b_and_the_cut_688_m():
    whole = DeepseekV3Config()
    assert whole.attention_params() == 26_345_984
    assert whole.num_params() == 30_670_815_104
    cut = fam.model_config(FILE, False)
    assert (cut.num_hidden_layers, cut.n_routed_experts, cut.experts_held,
            cut.vocab_size) == (6, 128, 16, 16032)
    assert cut.num_params() == 687_502_976
    tiny = deepseek_v3_tiny(experts_held=2)
    tree = jax.eval_shape(
        lambda: DeepseekV3ForCausalLM(tiny).init(
            jax.random.PRNGKey(0), jnp.zeros((1, 8), jnp.int32))["params"])
    assert sum(int(np.prod(x.shape))
               for x in jax.tree_util.tree_leaves(tree)) == tiny.num_params()


def test_group_limits_are_refused_by_name_and_query_compression_counts():
    """Group-limited routing is not written and says so; query compression
    is (ISSUE 56: ``tests/test_xing4.py`` holds it to the reference) and
    counts its three leaves in place of ``q_proj``."""
    with pytest.raises(NotImplementedError, match="n_group=8"):
        DeepseekV3Config(n_group=8, topk_group=4)
    whole, cut = DeepseekV3Config(), DeepseekV3Config(q_lora_rank=1536)
    assert cut.attention_params() - whole.attention_params() \
        == 2048 * 1536 + 1536 + 1536 * 32 * 192 - 2048 * 32 * 192
