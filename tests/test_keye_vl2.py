"""Keye-VL-2.0's learned sparse attention (``models/llama.py`` with
``index_topk`` > 0) against its plain reference
(``benchmark/reference/keye_vl2.py``): the loss, the indexer's KL, the kept
sets, every gradient leaf, the two seams, the rotary's three rows, remat, the
controls, and that the switch is off for every other configuration. The
kernels against the dense oracle: ``tests/test_keye_vl2_kernels.py``.

Sizes are the benchmark configuration's rehearsal sizes (hidden 64, 2 layers,
8 / 2 heads of 16, an indexer of 4 heads of 8 keeping 32 keys, 2 of 8 experts
held top-2, 128 tokens, vocabulary 512), the model in float32 so that system
and reference agree to float32 rounding.
"""

import dataclasses
import json
import os

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from benchmark import manifest
from benchmark.families import keye_vl2 as family
from benchmark.reference import keye_vl2 as ref
from deepspeed_tpu.models import llama

with open(os.path.join(manifest.HERE, "configs",
                       "keye-vl-2.0-30b-a3b-ep8-depth6.json")) as f:
    CONFIG = json.load(f)
F32_CONFIG = dict(CONFIG, rehearse_cpu=dict(
    CONFIG["rehearse_cpu"], model={"loss_chunk": 128, "dtype": "float32"},
    train=dict(CONFIG["rehearse_cpu"]["train"], engine=dict(
        CONFIG["rehearse_cpu"]["train"]["engine"], bf16={"enabled": False},
        data_types={"grad_dtype": "fp32"}))))
SEQ = 128
SIZES = family.reference_sizes(F32_CONFIG, True)


def _ids(seed=0, rows=1):
    return np.random.default_rng(seed).integers(
        0, 512, (rows, SEQ), dtype=np.int32)


def _view(w):
    return family.reference_view(w, 2)


@pytest.fixture(scope="module")
def weights():
    model = family._model(F32_CONFIG, True)
    return jax.jit(lambda: model.init(jax.random.PRNGKey(0),
                                      jnp.zeros((1, 16), jnp.int32)))()["params"]


@pytest.fixture(scope="module")
def compared(weights):
    """(system, (reference loss, gradient norm, differences)) in float32."""
    system = family.system_step(F32_CONFIG, weights, _ids(), jax.devices()[0],
                                True)
    return system, family.compare(F32_CONFIG, weights, _ids(),
                                  jax.devices()[0], True, system)


# ------------------------------------------------ system against reference

def test_the_step_equals_the_reference_loss_kl_selection_and_every_leaf(
        weights, compared):
    (loss, layers, grads, seams), (want, gnorm, diffs) = compared
    assert float(loss) == pytest.approx(want, abs=2e-5)
    assert diffs["dsa_kl_abs"] < 1e-5 and diffs["index_kl"][1] > 1e-3
    assert diffs["selection_differs_share"] == 0
    assert diffs["selected_pairs"][0] == diffs["selected_pairs"][1] \
        == 2 * family.selected_pairs(SEQ, 32)
    assert diffs["routing_differs"] == 0
    assert diffs["index_scores_rel"] < 1e-5 and diffs["attn_out_rel"] < 1e-5
    assert diffs["ffn_out_rel"] < 1e-5
    assert diffs["system_grad_norm"] == pytest.approx(gnorm, rel=1e-5)
    assert set(diffs["grad_leaf_rel"]) == set(_view(weights)[1][0]) | {
        "embed", "norm", "lm_head"}
    assert max(diffs["grad_leaf_rel"].values()) < 2e-5, diffs["grad_leaf_rel"]
    assert set(family.INDEXER_LEAVES) <= set(diffs["grad_leaf_rel"])


def test_the_seams_are_exact_zeros(compared):
    """The cross-entropy reaches no indexer leaf and the KL nothing else: the
    indexer's input and the attention's probabilities are detached."""
    (_, _, grads, seams), (_, _, diffs) = compared
    assert seams["ce_on_indexer"] == 0.0 and seams["kl_on_trunk"] == 0.0
    assert diffs["ce_on_indexer"] == 0.0 and diffs["kl_on_trunk"] == 0.0
    attn = grads["layers"]["blk"]["attn"]
    for leaf in ("index_q", "index_k", "index_w"):
        assert float(jnp.abs(attn[leaf]["kernel"]).max()) > 0, leaf


@pytest.fixture(scope="module")
def forward(weights):
    """control -> the reference's (loss, detail) on one batch, jitted."""
    ids = jnp.asarray(_ids())
    run = jax.jit(lambda w, c: ref.loss(w, ids, None, _view, control=c,
                                        **SIZES), static_argnums=1)
    honest = run(weights, None)
    return lambda control: honest if control is None else run(weights,
                                                              control)


@pytest.mark.parametrize("control", ref.CONTROLS)
def test_a_reference_with_one_fault_is_told_apart(weights, forward, control):
    """Each fault moves what it should: the kept set, the KL, or the seam."""
    (_, honest), (_, faulty) = forward(None), forward(control)
    moved = sum(int((np.asarray(a["selection"])
                     != np.asarray(b["selection"])).sum())
                for a, b in zip(honest["layers"], faulty["layers"]))
    kl = abs(float(honest["index_kl"] - faulty["index_kl"]))
    if control in ("topk_less_one", "relu_left_out", "head_weight_dropped"):
        assert moved > 0
    if control == "topk_less_one":      # a key fewer a row from the 32nd on
        assert moved >= SEQ - 32
    if control in ("relu_left_out", "head_weight_dropped",
                   "kl_over_all_causal"):
        assert kl > 1e-4
    if control == "kl_over_all_causal":
        assert moved == 0
    if control == "stop_gradient_left_out":
        # the forward pass is the honest one; the KL now reaches the trunk
        assert moved == 0 and kl < 1e-7
        ids = jnp.asarray(_ids())
        trunk = jax.jit(jax.grad(lambda w, c: ref.layer(
            jnp.take(w["embed"], ids, axis=0), w["layer"], control=c,
            **SIZES)[1]), static_argnums=1)
        w = {"embed": _view(weights)[0]["embed"],
             "layer": _view(weights)[1][0]}
        assert float(jnp.abs(trunk(w, None)["layer"]["input_norm"]).max()) \
            == 0.0
        assert float(jnp.abs(trunk(w, control)["layer"]["input_norm"])
                     .max()) > 1e-6


def test_the_step_in_a_lower_precision_is_told_apart(weights, compared):
    """Every weight matrix rounded to fp8
    (``benchmark/tools/precision_control.py``) reads far over the honest
    float32 step on the selection, the scores and the leaves."""
    from benchmark.tools.precision_control import fp8_matrices
    system = family.system_step(F32_CONFIG, fp8_matrices(weights), _ids(),
                                jax.devices()[0], True)
    _, _, diffs = family.compare(F32_CONFIG, weights, _ids(),
                                 jax.devices()[0], True, system)
    assert diffs["selection_differs_share"] > 1e-2
    assert diffs["index_scores_rel"] > 1e-2 and diffs["attn_out_rel"] > 1e-2
    assert min(diffs["grad_leaf_rel"].values()) > 1e-3


# ------------------------------------------------------------- the rotary

def test_equal_position_rows_are_one_dimensional_rope_bit_for_bit():
    pos = jnp.arange(SEQ) + 3
    for got, want in zip(llama.mrope_angles(pos, 16, 1e7, (2, 3, 3)),
                         llama.rope_angles(pos, 16, 1e7)):
        assert np.array_equal(np.asarray(got), np.asarray(want))
    rows = jnp.stack([pos, pos, pos])
    for got, want in zip(llama.mrope_angles(rows, 16, 1e7, (2, 3, 3)),
                         llama.rope_angles(pos, 16, 1e7)):
        assert np.array_equal(np.asarray(got), np.asarray(want))
    with pytest.raises(ValueError, match="mrope_section"):
        llama.mrope_angles(pos, 16, 1e7, (2, 3, 4))


def test_unequal_position_rows_follow_the_reference(weights):
    """A grid of image patches in the middle of the text: temporal, height
    and width rows differ, and sections (2, 3, 3) turn pairs 0-1 by the
    first, 2-4 by the second, 5-7 by the third; the indexer turns by the
    temporal row alone."""
    t = np.arange(SEQ)
    rows = np.stack([np.where((t >= 32) & (t < 96), 32, t),
                     np.where((t >= 32) & (t < 96), 32 + (t - 32) // 8, t),
                     np.where((t >= 32) & (t < 96), 32 + (t - 32) % 8, t)])
    ids = jnp.asarray(_ids(3))
    model = family._model(F32_CONFIG, True)

    @jax.jit
    def system(p, positions):
        out, vs = model.apply({"params": p}, ids, labels=ids,
                              positions=positions, mutable=["losses"])
        return out + sum(jnp.sum(x) for x in jax.tree_util.tree_leaves(
            vs["losses"]))
    want = jax.jit(lambda p, pos, c=None: ref.loss(
        p, ids, pos, _view, control=c, **SIZES)[0], static_argnums=2)
    got = float(system(weights, jnp.asarray(rows)))
    assert got == pytest.approx(float(want(weights, jnp.asarray(rows))),
                                abs=2e-5)
    # the rows matter: text positions, and every pair on the temporal row
    assert abs(got - float(system(weights, None))) > 1e-4
    assert abs(got - float(want(weights, jnp.asarray(rows),
                                "mrope_one_row"))) > 1e-5


# ---------------------------------------------------------------- remat

def test_a_rematted_block_selects_as_the_first_forward_did(weights):
    """Remat on (the blocks' policy, the selection's name kept) gives the
    gradients of remat off; the policy names the selection."""
    ids = jnp.asarray(_ids(5))
    cfg = family.model_config(F32_CONFIG, True)
    assert cfg.remat and cfg.remat_policy == "block"

    def grads(cfg):
        model = llama.LlamaForCausalLM(cfg)

        def loss(p):
            out, vs = model.apply({"params": p}, ids, labels=ids,
                                  mutable=["losses", "stats"])
            return out + sum(jnp.sum(x) for x in jax.tree_util.tree_leaves(
                vs["losses"]))
        return jax.jit(jax.grad(loss))(weights)

    on, off = grads(cfg), grads(dataclasses.replace(cfg, remat=False))
    for a, b in zip(jax.tree_util.tree_leaves(on),
                    jax.tree_util.tree_leaves(off)):
        assert np.allclose(a, b, rtol=1e-5, atol=1e-7)
    from deepspeed_tpu.runtime import remat_budget
    assert remat_budget.selection_pin_bytes(1, 16384, 6) \
        == 6 * (16384 * 16384 // 8 + 8 * 16384)
    assert remat_budget.selection_pin_bytes(2, 100, 1, tile=128) \
        == 2 * (128 * 128 // 8 + 8 * 128)


@pytest.mark.parametrize("free,kept", [
    (10 ** 9, 1),
    # the name's own bytes and not one more: nothing for the selection's pin
    # and the reserve
    (2 * 512 * 512 * 4, 0),
    (0, 0)], ids=["fits", "does-not-fit", "no-engine"])
def test_the_kl_gradients_name_joins_the_policy_where_its_bytes_fit(
        weights, free, kept):
    """The rematted two-layer scanned model on the kernels (interpreted):
    ``_pinned`` puts ``KL_GRAD_NAME`` into the blocks' policy when the
    trace's free bytes cover it beside the selection's pin and the reserve —
    the backward pass then holds no indexer or KL kernel — and leaves
    today's two passes when they do not; the two gauges say which."""
    import collections
    from deepspeed_tpu.parallel import mesh as mesh_lib
    from deepspeed_tpu.runtime import remat_budget
    from deepspeed_tpu.telemetry.registry import default_registry
    from tests.hlo_text import pallas_calls
    ids = jnp.asarray(_ids(5))
    cfg = dataclasses.replace(family.model_config(F32_CONFIG, True),
                              use_flash=True)
    assert cfg.remat and cfg.scan_layers and cfg.n_layers == 2
    model = llama.LlamaForCausalLM(cfg)

    def loss(p):
        out, vs = model.apply({"params": p}, ids, labels=ids,
                              mutable=["losses", "stats"])
        return out + sum(jnp.sum(x) for x in jax.tree_util.tree_leaves(
            vs["losses"]))

    with mesh_lib.layout_pins(None, remat_free_bytes=free):
        jaxpr = jax.make_jaxpr(jax.grad(loss))(weights).jaxpr
    kernels = collections.Counter(
        eqn.params["jaxpr"].debug_info.func_name
        for eqn in pallas_calls(jaxpr))
    again = 1 - kept
    assert (kernels["_indexer_kernel"], kernels["_kl_kernel"]) \
        == (1 + again, 1 + again)
    assert (kernels["_select_kernel"], kernels["_masked_fwd_kernel"],
            kernels["_masked_bwd_kernel"], kernels["_indexer_bwd_kernel"]) \
        == (1, 1, 1, 1)
    gauge = default_registry().peek_gauge
    assert gauge("remat/dsa_kl_grad_kept") == kept
    # 128 tokens are one tile of the chip's 512, float32, two layers
    assert gauge("remat/dsa_kl_grad_mb") * 1e6 == 2 * 512 * 512 * 4 \
        == remat_budget.kl_grad_bytes(1, SEQ, 2, 4)


# ------------------------------------------------------ the switch is off

def test_without_an_indexer_the_model_and_the_step_are_as_before(weights):
    """``index_topk`` 0 (every accepted configuration): no indexer leaf, no
    KL, no gauge of it, the causal path and one position row, and nothing of
    this PR in the lowered forward. (That the eleven accepted cells' lowered
    steps are the parent's texts is ``benchmark.tools.lowered_step_hash``'s:
    CHANGES.md's PR 65 entry.)"""
    from benchmark.families import olmoe
    with open(os.path.join(manifest.HERE, "configs",
                           "olmoe-1b-7b-0125-depth1.json")) as f:
        cfg = olmoe.model_config(json.load(f), rehearse=True)
    assert cfg.index_topk == 0 and cfg.mrope_section == ()
    assert llama._attn_cls(cfg) is llama.LlamaAttention
    assert llama._attn_cls(family.model_config(F32_CONFIG, True)) \
        is llama._IndexedLlamaAttention
    model = llama.LlamaForCausalLM(cfg)
    assert not [g for g in model.stat_gauges.values() if "dsa" in g]
    ids = jnp.asarray(_ids())
    shapes = jax.eval_shape(lambda: model.init(jax.random.PRNGKey(0), ids))
    assert not [k for k in shapes["params"]["layers"]["blk"]["attn"]
                if k.startswith("index")]
    text = jax.jit(lambda p: model.apply(p, ids, labels=ids,
                                         mutable=["losses", "stats"])) \
        .lower(shapes).as_text(debug_info=True)
    for word in ("dsa_", "index_q", "index_k"):
        assert word not in text, word
    # ... and with it the same file's model grows exactly the indexer
    mine = family.model_config(F32_CONFIG, True)
    bare = dataclasses.replace(mine, index_topk=0)
    assert mine.num_params() - bare.num_params() == 2 * (
        64 * 4 * 8 + 64 * 8 + 2 * 8 + 64 * 4)
    assert mine.num_params() == sum(
        int(np.prod(x.shape)) for x in jax.tree_util.tree_leaves(weights))


@pytest.mark.parametrize("name", ["attention/dsa_tile_overcompute",
                                  "attention/dsa_selected_share",
                                  "attention/dsa_kl",
                                  "remat/selection_pin_mb",
                                  "remat/dsa_kl_grad_mb",
                                  "remat/dsa_kl_grad_kept"])
def test_the_gauges_are_documented_and_the_scopes_listed(name):
    """docs/observability.md's tables and ``spans.annotate``'s list."""
    from deepspeed_tpu.telemetry import spans
    from tests.test_metric_names import documented_metric_names
    assert name in documented_metric_names()
    assert name in spans.annotate.__doc__
    docs = open(os.path.join(manifest.ROOT, "docs",
                             "observability.md")).read()
    # an XLA pass no program has since PR 66 (the family's module tags keep
    # the name: that file is the benchmark's)
    assert "dsa_kl_bwd" not in spans.annotate.__doc__ + docs
    names = ("dsa_selection", "dsa_kl_grad")
    for scope in family.DSA_TAGS + ("dsa_index_proj", "dsa_select_pin",
                                    "dsa_bwd_dq_sum") + names:
        assert scope in docs, scope
        assert scope in names or scope in spans.annotate.__doc__
