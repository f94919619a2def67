"""SmallThinker on the CPU at small sizes: the program's model against the
benchmark's plain reference (``benchmark/reference/smallthinker.py``) for
both layer kinds and every gradient leaf, with all experts held and with a
share; the four shares adding up to the uncut layer; each named omission
failing the benchmark's check; the layer plan of the published depth and of
the cut. Seeded weights, float32. The model on the engine under ZeRO-3 and
remat: ``tests/test_smallthinker_engine.py``.
"""

import copy
import json
import os

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from benchmark import manifest
from benchmark.families import laguna as laguna_fam, smallthinker as fam
from benchmark.reference import smallthinker as ref
from deepspeed_tpu.models.laguna import FULL, SLIDING
from deepspeed_tpu.models.smallthinker import (SmallThinkerConfig,
                                               SmallThinkerForCausalLM,
                                               block_paths, smallthinker_tiny)
from deepspeed_tpu.moe.dropless import DroplessMoE

with open(os.path.join(manifest.HERE, "configs",
                       "smallthinker-21b-a3b-ep4-depth4.json")) as f:
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


def _tiny(config, seed=0, seq=96):
    """(config, weights, ids, the system's step); the norm weights and the
    narrow matrices moved off their initial values so that a weight left
    out cannot pass, and the query and key projections ten times as large,
    so that the scores are far from uniform and a rotation or a head read
    from the wrong KV head shows."""
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
            getattr(k, "key", None) in ("q_proj", "k_proj") for k in path)
        else x, params)
    system = fam.system_step(config, params, ids, jax.devices()[0], True)
    return config, params, ids, system


@pytest.fixture(scope="module")
def tiny():
    """The file's four layers (full without RoPE, 3 sliding), one of four
    expert shares held, 14 query heads over 2 KV heads (groups of 7)."""
    return _tiny(_float32(FILE))


@pytest.fixture(scope="module")
def tiny_all_experts():
    """Eight layers (2 periods), every expert held."""
    eight = {"num_hidden_layers": 8, "expert_parallel_size": 1,
             "moe_num_primary_experts": 16,
             "sliding_window_layout": [0, 1, 1, 1] * 2,
             "rope_layout": [0, 1, 1, 1] * 2}
    return _tiny(_float32(FILE, **eight), seed=3)


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
    assert diffs["routing_assignments"] == n * 2 * 96 * 2
    for branch in ("full_out_rel", "swa_out_rel", "ffn_out_rel"):
        assert diffs[branch] < 1e-5, branch
    assert "dense_out_rel" not in diffs
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


# (the omission, what the reference is told instead, the reading that must
# say so, the layer whose reading it is: 0 is the full layer)
OMISSIONS = [
    ("router fed the post-attention normed stream",
     {"router_input": "post_attn_norm"}, "routing", None),
    ("silu for relu", {"act": jax.nn.silu}, "ffn_out_rel", None),
    ("RoPE applied on the full layer", {"rope_layout": (1, 1, 1, 1)},
     "full_out_rel", 0),
    ("RoPE left off a sliding layer", {"rope_layout": (0, 0, 1, 1)},
     "swa_out_rel", 1),
    ("the window not applied", {"sliding_window_layout": (0, 0, 0, 0)},
     "swa_out_rel", 1),
    ("top-6 not renormalised", {"norm_topk_prob": False}, "ffn_out_rel",
     None),
    ("KV head n // 7 mapped wrongly (n % 2)",
     {"kv_of_head": tuple(n % 2 for n in range(14))}, "full_out_rel", 0),
]


@pytest.mark.parametrize("omission,override,reading,layer", OMISSIONS,
                         ids=[o[0] for o in OMISSIONS])
def test_each_omission_fails_the_check(tiny, monkeypatch, omission, override,
                                       reading, layer):
    """The reference WITH the omission is a model the system is not: the
    benchmark's comparison must say so, by the reading the omission is
    in."""
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
    kinds = fam._kinds(config, True)
    tol = FILE["train"]["tolerance"]
    if reading == "routing":
        # both routers chose on their own streams: the first layer's are the
        # same embedding rows, and the choices differ widely
        own = laguna_fam.own_stream_differences(layers, detail["layers"],
                                                kinds)
        assert own[0][4] > 5 * tol["own_stream_first_layer"][
            "routing_share"], (omission, own)
        return
    diffs = jax.tree_util.tree_map(float, laguna_fam.branch_differences(
        layers, detail["layers"], kinds))
    assert diffs[reading] > 3 * tol[reading], (omission, diffs)
    if layer is not None:
        # the reading is that layer's; the full layer under a sliding one
        # runs on the same stream and is still right
        assert diffs["by_layer"][layer][0] > 3 * tol[reading]
        if layer:
            assert diffs["by_layer"][0][0] < 1e-5


def test_a_layer_without_rope_gets_no_table_and_no_rotation():
    """``rope_tables`` gives a NoPE layer type ``None`` and the attention
    module then multiplies by nothing: no cos / sin in the traced full
    layer, both in a sliding one."""
    from deepspeed_tpu.models.laguna import LagunaAttention, rope_tables
    cfg = smallthinker_tiny(num_hidden_layers=4)
    rope = rope_tables(cfg, jnp.arange(64))
    assert rope[FULL] is None and rope[SLIDING][0].shape == (64, 16)
    x = jnp.ones((1, 64, 64))

    def traced(kind):
        attn = LagunaAttention(cfg, kind, cfg.num_attention_heads)
        params = attn.init(jax.random.PRNGKey(0), x, rope)
        return str(jax.make_jaxpr(
            lambda p, r: attn.apply(p, x, r))(params, rope))

    # the full layer does not read the tables it is handed
    assert "cos" not in traced(FULL) and "sin" not in traced(FULL)
    assert traced(FULL).count(" mul ") < traced(SLIDING).count(" mul ")


# ------------------------------------------------------- the expert layer

H, E, K, F, RANKS = 32, 32, 4, 16, 4


def _layer_weights(seed=0):
    ks = jax.random.split(jax.random.PRNGKey(seed), 4)
    n = lambda k, *s: 0.3 * jax.random.normal(k, s)  # noqa: E731
    return {"router": n(ks[0], H, E), "gate": n(ks[1], E, H, F),
            "up": n(ks[2], E, H, F), "down": n(ks[3], E, F, H)}


def _share(p, x, router_x, rank, held=E // RANKS, act="relu"):
    layer = DroplessMoE(E, K, F, norm_topk_prob=True, dtype=jnp.float32,
                        experts_held=held, expert_share=rank, act=act)
    lo = rank * held
    params = {"router": p["router"], "gate_proj": p["gate"][lo:lo + held],
              "up_proj": p["up"][lo:lo + held],
              "down_proj": p["down"][lo:lo + held]}
    out, vs = layer.apply({"params": params}, x, router_x, mutable=["stats"])
    return out, {k: float(v[0]) for k, v in vs["stats"].items()}


def test_the_four_shares_are_the_whole_layer():
    """The parts all 4 ranks give (each its 8 experts' rows, routed by the
    logits of ANOTHER tensor than the experts read) add up to the uncut
    reference's layer; there is no shared expert to count once."""
    p = _layer_weights()
    x = jax.random.normal(jax.random.PRNGKey(9), (2, 24, H))
    r = jax.random.normal(jax.random.PRNGKey(10), (2, 24, H))
    h, rt = x.reshape(-1, H), r.reshape(-1, H)
    with jax.default_matmul_precision("highest"):
        whole = ref.moe(h, rt @ p["router"], p, K, 0)[0]
        wrong_input = ref.moe(h, h @ p["router"], p, K, 0)[0]
        parts, held = [], 0.0
        for rank in range(RANKS):
            out, stats = _share(p, x, r, rank)
            parts.append(out)
            held += stats["moe_rows_held_share"]
            assert stats["moe_dropped_rows"] == 0
        all_held, _ = DroplessMoE(
            E, K, F, norm_topk_prob=True, dtype=jnp.float32,
            act="relu").apply(
            {"params": {"router": p["router"], "gate_proj": p["gate"],
                        "up_proj": p["up"], "down_proj": p["down"]}},
            x, r, mutable=["stats"])
    assert held == pytest.approx(1.0)       # every routed row is somewhere
    np.testing.assert_allclose(sum(parts).reshape(-1, H), whole, atol=5e-5)
    np.testing.assert_allclose(all_held.reshape(-1, H), whole, atol=5e-5)
    assert float(jnp.linalg.norm(whole - wrong_input)
                 / jnp.linalg.norm(whole)) > 0.3


def test_the_defaults_of_the_expert_layer_trace_the_program_they_did():
    """One input and ``silu`` are the defaults: handing the layer its own
    input as ``router_x`` and naming ``silu`` trace the same program, and
    ``relu`` / another router input each trace another."""
    p = _layer_weights()
    x = jax.random.normal(jax.random.PRNGKey(9), (2, 24, H))
    params = {"router": p["router"], "gate_proj": p["gate"][:8],
              "up_proj": p["up"][:8], "down_proj": p["down"][:8]}

    def layer(**kw):
        return DroplessMoE(E, K, F, experts_held=8, dtype=jnp.float32, **kw)

    def text(*more, **kw):
        return str(jax.make_jaxpr(lambda v, x, *more: layer(**kw).apply(
            {"params": v}, x, *more))(params, x, *more))

    assert text() == text(act="silu") != text(act="relu")
    assert text(x) != text()
    np.testing.assert_array_equal(
        layer().apply({"params": params}, x),
        layer().apply({"params": params}, x, x))
    with pytest.raises(KeyError):
        text(act="gelu")
    # the router's gradient reaches the tensor it read, not the experts'
    whole = DroplessMoE(E, K, F, dtype=jnp.float32, act="relu",
                        balance_coeff=1.0)

    def aux(r):
        _, vs = whole.apply({"params": {
            "router": p["router"], "gate_proj": p["gate"],
            "up_proj": p["up"], "down_proj": p["down"]}}, x, r,
            mutable=["losses"])
        return sum(jnp.sum(v) for v in jax.tree_util.tree_leaves(vs))

    assert float(jnp.linalg.norm(jax.grad(aux)(x + 1.0))) > 0


# --------------------------------------------------------- the layer plan

def test_the_published_depth_builds_as_13_periods_and_the_cut_as_one():
    published = {**FILE, **{k: v for k, v in FILE["published"].items()
                            if k in fam._SIZE_KEYS},
                 "expert_parallel_size": 1}
    whole = fam.model_config(published, rehearse=False)
    assert whole.plan == (4, 13, 0)
    assert whole.layer_types[:5] == (FULL, SLIDING, SLIDING, SLIDING, FULL)
    assert whole.rope_of(FULL) is None
    assert whole.rope_of(SLIDING)["rope_theta"] == 1500000
    assert (whole.moe_num_primary_experts, whole.experts_held,
            whole.vocab_size) == (64, 0, 151936)
    assert whole.num_params() == 21_506_562_560
    assert [p[0] for p in block_paths(whole)] == ["layers"] * 52

    cut = fam.model_config(FILE, rehearse=False)
    assert cut.plan == (4, 1, 0)
    assert cut.layer_types == (FULL, SLIDING, SLIDING, SLIDING)
    assert (cut.moe_num_primary_experts, cut.experts_held,
            cut.expert_share) == (64, 16, 0)
    assert cut.num_params() == 656_529_920
    shapes = jax.eval_shape(
        lambda r, x: SmallThinkerForCausalLM(cut).init(r, x)["params"],
        jax.random.PRNGKey(0), jnp.zeros((1, 128), jnp.int32))
    assert sorted(shapes) == ["embed_tokens", "layers", "lm_head", "norm"]
    for j in range(4):
        blk = shapes["layers"][f"l{j}"]
        assert blk["attn"]["q_proj"]["kernel"].shape == (1, 2560, 3584)
        assert blk["attn"]["k_proj"]["kernel"].shape == (1, 2560, 512)
        assert blk["attn"]["o_proj"]["kernel"].shape == (1, 3584, 2560)
        assert "g_proj" not in blk["attn"]
        assert blk["mlp"]["router"].shape == (1, 2560, 64)
        assert blk["mlp"]["gate_proj"].shape == (1, 16, 2560, 768)
        assert blk["mlp"]["down_proj"].shape == (1, 16, 768, 2560)
    assert sum(int(np.prod(x.shape))
               for x in jax.tree_util.tree_leaves(shapes)) == cut.num_params()


def test_the_plan_follows_the_lists_and_nothing_else():
    """A period of two and one layer left over; lists of the wrong length
    and a window kind that both rotates and does not are refused."""
    cfg = smallthinker_tiny(num_hidden_layers=5,
                            sliding_window_layout=[0, 1, 0, 1, 0],
                            rope_layout=[1, 0, 1, 0, 1])
    assert cfg.plan == (2, 2, 1)
    assert cfg.rope_of(SLIDING) is None and cfg.rope_of(FULL) is not None
    assert [p[0] for p in block_paths(cfg)] == ["layers"] * 4 + ["tail_0"]
    with pytest.raises(AssertionError, match="rope_layout has 3"):
        smallthinker_tiny(num_hidden_layers=4, rope_layout=[0, 1, 1])
    with pytest.raises(AssertionError, match="one window kind"):
        smallthinker_tiny(num_hidden_layers=4, rope_layout=[0, 1, 0, 1])
    with pytest.raises(TypeError, match="rope_layout"):
        SmallThinkerConfig(num_hidden_layers=1, sliding_window_layout=[0])
