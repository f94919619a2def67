"""Olmo Hybrid on the CPU at small sizes: the program's model against the
benchmark's plain reference (``benchmark/reference/olmo_hybrid.py``) for both
layer kinds — loss, each branch and every gradient leaf — at heads of the
PUBLISHED 96 x 192 (zero-padded to whole lane tiles, all three stages in
their kernels in the interpreter); beta reaching past 1; each of the
reference's controls shown to matter; the walked gradients; remat on equal
to remat off; the parameter count at the published and at the cut sizes.
Seeded weights, float32. The model on the engine under ZeRO-3 and remat is
the cell's rehearsal (``tests/benchmark_checks/test_bm_olmo_hybrid.py``).
"""

import copy
import json
import os

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from benchmark import manifest
from benchmark.families import olmo_hybrid as fam
from benchmark.reference import olmo_hybrid as ref
from deepspeed_tpu.models.olmo_hybrid import (FULL, LINEAR, OlmoHybridConfig,
                                              OlmoHybridForCausalLM,
                                              olmo_hybrid_tiny)
from deepspeed_tpu.telemetry.registry import default_registry
from tests.model_cases import gradients_without_and_with_remat

with open(os.path.join(manifest.HERE, "configs",
                       "olmo-hybrid-7b-vp8-depth4.json")) as f:
    FILE = json.load(f)
PUBLISHED_TYPES = ([LINEAR] * 3 + [FULL]) * 8


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


def _moved(params, seed=1):
    """Every vector and narrow matrix moved off its initial value, so that
    a weight left out cannot pass; the attention layer's query and key
    projections forty times as large, so that the scores are far from
    uniform; the DeltaNet's gate projection (b | a) fifty times, so that
    beta = 2 sigmoid(b) spreads over (0, 2)."""
    keys = iter(jax.random.split(jax.random.PRNGKey(seed), 1000))
    params = jax.tree_util.tree_map(
        lambda x: x + 0.1 * jax.random.normal(next(keys), x.shape)
        if x.shape[-1] < 64 or x.ndim == 1 else x, params)
    scale = {"q_proj": 40.0, "k_proj": 40.0, "in_proj_ba": 50.0}
    return jax.tree_util.tree_map_with_path(
        lambda path, x: x * next((scale[k.key] for k in path if getattr(
            k, "key", None) in scale), 1.0), params)


@pytest.fixture(scope="module")
def tiny():
    """(config, weights, ids, the system's step) of the rehearsal's one
    period: 2 DeltaNet heads of the published 96 x 192 (run at 128 x 256),
    2 attention heads of 32, two chunks of 64 tokens."""
    config = _float32(FILE)
    vocab = fam.sizes(config, True)["vocab_size"]
    ids = np.random.default_rng(0).integers(0, vocab, (2, 128)).astype(
        np.int32)
    params = _moved(jax.jit(fam._model(config, True).init)(
        jax.random.PRNGKey(0), jnp.asarray(ids))["params"])
    system = fam.system_step(config, params, ids, jax.devices()[0], True)
    return config, params, ids, system


def test_system_matches_reference_branch_by_branch_and_leaf_by_leaf(tiny):
    config, params, ids, system = tiny
    gauge = default_registry().peek_gauge
    # every stage took its kernel, on 1.77 x the published heads' lanes
    assert gauge("linear_attn/gdn_kernel_heads_per_step") == 2
    assert gauge("linear_attn/gdn_lane_overcompute") == pytest.approx(
        (2 * 128 + 256 + 128 * 256) / (2 * 96 + 192 + 96 * 192))
    loss, gnorm, diffs = fam.compare(config, params, ids, jax.devices()[0],
                                     True, system)
    assert float(system[0]) == pytest.approx(loss, abs=2e-5)
    assert diffs["system_grad_norm"] == pytest.approx(gnorm, rel=1e-4)
    for branch in ("gdn_out_rel", "attn_out_rel", "mlp_out_rel"):
        assert 0 <= diffs[branch] < 5e-5, (branch, diffs[branch])
    assert len(diffs["pinned_by_layer"]) == len(
        diffs["own_stream_by_layer"]) == 4
    # not pinned: float32 on both sides, so every layer and the adds agree
    assert max(max(r) for r in diffs["own_stream_by_layer"]) < 2e-4
    assert diffs["stream_add_rel"] < 1e-6
    assert diffs["stream_start_rel"] < 1e-6
    leaves = diffs["grad_leaf_rel"]
    assert set(leaves) == set(FILE["train"]["tolerance"]["grad_leaf_rel"])
    # two leaves of two elements each, sums of terms of both signs over
    # the tokens, through the kernels' three-pass float32 products
    small = ("gdn.A_log", "gdn.dt_bias")
    assert max(v for k, v in leaves.items() if k not in small) < 2e-5, leaves
    assert max(leaves[k] for k in small) < 5e-3, leaves
    checks, _ = fam.judge_train(config, float(system[0]),
                                diffs["system_grad_norm"], loss, gnorm, diffs)
    assert all(checks.values()), checks


def test_beta_reaches_past_one(tiny):
    """``linear_allow_neg_eigval``: with the fixture's gate projection beta
    = 2 sigmoid(b) stands on both sides of 1, and a reference with beta =
    sigmoid(b) is another model."""
    config, params, ids, _ = tiny
    top, layers = fam.reference_view(params, fam.sizes(config, True)[
        "layer_types"])
    x = top["embed"][jnp.asarray(ids)]
    beta = 2 * jax.nn.sigmoid((x @ layers[0]["in_ba"])[..., :2])
    assert float(beta.max()) > 1.5 and float(beta.min()) < 0.5
    assert float(jnp.mean(beta > 1.0)) > 0.2


@pytest.mark.parametrize("control", ref.CONTROLS)
def test_each_control_is_another_model(tiny, control):
    """The reference computed with one fault of ``CONTROLS`` against the
    honest reference, each on its own stream: the first layer of the kind
    the fault is in (and with the norms moved, every branch) leaves by more
    than the file's limit for that branch, the other kinds' first layers
    as they were unless the stream below them moved."""
    config, params, ids, _ = tiny
    sizes = fam.reference_sizes(config, True)
    view = lambda w: fam.reference_view(w, sizes["layer_types"])  # noqa: E731
    run = jax.jit(lambda p, fault: ref.loss(
        p, jnp.asarray(ids), view, fault=fault, **sizes)[1]["layers"],
        static_argnums=1)
    honest, faulty = run(params, None), run(params, control)
    rel = lambda i, k: float(fam._rel(faulty[i][k], honest[i][k]))  # noqa: E731
    tol = FILE["train"]["tolerance"]
    layer, key = {"qk_norm_per_head": (3, "attn_out_rel"),
                  "rotation_added": (3, "attn_out_rel")}.get(
        control, (0, "gdn_out_rel"))
    assert rel(layer, "mixer_out") > 3 * tol[key], (control, rel(
        layer, "mixer_out"))
    if layer == 3:
        # a fault of the attention layer leaves the DeltaNet layers alone
        assert rel(0, "mixer_out") == 0.0
    if control == "norms_on_inputs":
        assert rel(0, "mlp_out") > 3 * tol["mlp_out_rel"]


def test_the_walked_gradients_are_the_pinned_losss_gradients(tiny):
    """``reference.pinned_backward`` (a branch at a time, from the head
    down) against ``jax.grad`` of the same loss written in one piece: every
    branch started from the system's values with this model's
    derivatives."""
    config, params, ids, (_, rows, _) = tiny
    sizes = fam.reference_sizes(config, True)
    top, layers = fam.reference_view(params, sizes["layer_types"])
    ids = jnp.asarray(ids)

    def pinned(x, to):
        return x + jax.lax.stop_gradient(to - x)

    def whole(top, layers):
        with jax.default_matmul_precision("highest"):
            x = top["embed"][ids]
            for kind, p, row in zip(sizes["layer_types"], layers, rows):
                mixer, mlp = ref.branches(kind, **sizes)
                x = pinned(x, row["x_in"])
                x = pinned(x + mixer(x, p), row["x_in"] + row["mixer_out"])
                x = pinned(x + mlp(x, p), row["x_in"] + row["mixer_out"]
                           + row["mlp_out"])
            return ref.head_loss(x, top, ids, eps=sizes["eps"])

    want_loss, (want_top, want_layers) = jax.jit(jax.value_and_grad(
        whole, argnums=(0, 1)))(top, layers)
    loss, got_layers, got_top = jax.jit(lambda t, ls: ref.pinned_backward(
        t, ls, ids, rows, lambda i, kind, g, *outs: g, **sizes))(top, layers)
    assert float(loss) == pytest.approx(float(want_loss), abs=1e-6)
    for got, want in zip(got_layers + [got_top], want_layers + [want_top]):
        assert got.keys() == want.keys()
        for name in want:
            np.testing.assert_allclose(got[name], want[name], rtol=5e-4,
                                       atol=1e-7, err_msg=name)


def test_remat_on_equals_remat_off():
    """Every block under ``nn.remat`` with its gather edge: the gradients of
    the unrematted model, leaf by leaf (tiny heads: the rule in the
    interpreter at 8 x 16, the elementwise stages on their XLA forms)."""
    ids = jnp.asarray(np.random.default_rng(2).integers(0, 256, (2, 64)),
                      jnp.int32)
    (plain, _), (remat, text) = gradients_without_and_with_remat(
        lambda remat: OlmoHybridForCausalLM(olmo_hybrid_tiny(
            num_hidden_layers=4, remat=remat)), ids)
    assert text
    for a, b in zip(jax.tree_util.tree_leaves(plain),
                    jax.tree_util.tree_leaves(remat)):
        np.testing.assert_allclose(a, b, rtol=2e-4, atol=1e-7)


@pytest.mark.parametrize("what,over,want", [
    ("published", {}, 7_430_870_688),
    ("the_cut", {"num_hidden_layers": 4, "layer_types": PUBLISHED_TYPES[:4],
                 "vocab_size": 12544}, 928_862_196),
    ("tiny", None, None)])
def test_num_params_equals_the_initialised_trees(what, over, want):
    """The builder's count against the tree ``init`` makes (abstractly: no
    array exists), at the published sizes, at the cell's cut and at the
    tests' tiny sizes; the two large counts are the configuration file's."""
    cfg = olmo_hybrid_tiny() if over is None else OlmoHybridConfig(**over)
    tree = jax.eval_shape(
        lambda r, x: OlmoHybridForCausalLM(cfg).init(r, x)["params"],
        jax.random.PRNGKey(0), jax.ShapeDtypeStruct((1, 64), jnp.int32))
    count = sum(int(np.prod(x.shape))
                for x in jax.tree_util.tree_leaves(tree))
    assert count == cfg.num_params()
    assert cfg.period == 4
    if want is not None:
        assert count == want
        assert f"{want:,}" in (FILE["published"]["parameters"]
                               + FILE["changed_why"]["num_hidden_layers"])
    if what == "published":
        assert list(cfg.layer_types) == PUBLISHED_TYPES \
            == FILE["published"]["layer_types"]
        assert cfg.head_dim == 128
        # eight periods stacked under the four layers of the scan's body
        assert tree["layers"]["l0"]["linear_attn"]["conv"].shape \
            == (8, 4, 11520)
        assert tree["layers"]["l3"]["attn"]["q_norm"]["scale"].shape \
            == (8, 3840)


def test_the_model_reuses_the_deltanet_and_rotates_nothing():
    """The DeltaNet mixer is Qwen3-Next's own class, not a copy; no cos /
    sin is anywhere in the traced model: q and k go to the kernel as normed;
    the published list's period is found, whatever its length."""
    from deepspeed_tpu.models import olmo_hybrid, qwen3_next
    assert olmo_hybrid.GatedDeltaNet is qwen3_next.GatedDeltaNet
    assert not qwen3_next.Qwen3NextConfig().linear_allow_neg_eigval
    cfg = olmo_hybrid_tiny(num_hidden_layers=4)
    model = OlmoHybridForCausalLM(cfg)
    ids = jnp.zeros((1, 64), jnp.int32)
    params = jax.jit(model.init)(jax.random.PRNGKey(0), ids)["params"]
    primitives = set()

    def walk(jaxpr):
        for eqn in jaxpr.eqns:
            primitives.add(eqn.primitive.name)
            for sub in jax.core.jaxprs_in_params(eqn.params):
                walk(sub)

    walk(jax.make_jaxpr(lambda p: model.apply({"params": p}, ids))(
        params).jaxpr)
    assert "dot_general" in primitives and not {"cos", "sin"} & primitives
    assert olmo_hybrid_tiny(
        num_hidden_layers=6, layer_types=[LINEAR, FULL] * 3).period == 2
    assert olmo_hybrid_tiny(
        num_hidden_layers=3, layer_types=[LINEAR, LINEAR, FULL]).period == 3
