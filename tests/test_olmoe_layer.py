"""The pieces ISSUE 27 added for OLMoE, on the CPU at small sizes: the dropless
expert layer against the dense computation (every option of it), the grouped
matmul against a per-expert loop, a buffer leaf through the engine's step;
and LLaMA itself, which must not have moved. The engine's step against the
reference: ``tests/test_olmoe.py``.
"""

import dataclasses
import hashlib

import jax
import jax.numpy as jnp
import numpy as np
import pytest

import deepspeed_tpu as dstpu
from deepspeed_tpu.models import llama
from deepspeed_tpu.moe import dropless
from deepspeed_tpu.ops.pallas.grouped_matmul import grouped_matmul
from deepspeed_tpu.parallel.mesh import MeshConfig, make_mesh
from tests.cell_config import config_file

CONFIG = config_file("olmoe-1b-7b-0125-depth1")


# ----------------------------------------------------- the dropless layer

def _layer():
    return dropless.DroplessMoE(num_experts=8, k=2, d_ff=32,
                                dtype=jnp.float32)


def _dense_moe(p, x, k):
    """Every expert on every token, masked by the top-k weights."""
    h = x.reshape(-1, x.shape[-1])
    probs = jax.nn.softmax(h @ p["router"], axis=-1)
    w, e = jax.lax.top_k(probs, k)
    y = jnp.zeros_like(h)
    for i in range(p["router"].shape[1]):
        out = (jax.nn.silu(h @ p["gate_proj"][i]) * (h @ p["up_proj"][i])) \
            @ p["down_proj"][i]
        y = y + jnp.sum(jnp.where(e == i, w, 0.0), axis=1)[:, None] * out
    return y.reshape(x.shape)


def test_every_row_arrives_and_no_routing_pattern_recompiles():
    """A router forced to send every token to the same two experts: all
    T x k rows arrive (``moe_dropped_rows`` 0, the fullest expert holds
    E / k times the mean), the output is the dense computation's, and a
    second, scattered routing pattern runs the SAME compiled program."""
    layer = _layer()
    x = 1.0 + 0.1 * jax.random.normal(jax.random.PRNGKey(0), (2, 64, 64))
    p = layer.init(jax.random.PRNGKey(1), x)["params"]
    forced = dict(p, router=jnp.full_like(p["router"], -1.0)
                  .at[:, 3].set(1.0).at[:, 5].set(0.5))
    compiles = []
    jax.monitoring.register_event_duration_secs_listener(
        lambda event, secs, **_: compiles.append(event) if event ==
        "/jax/core/compile/backend_compile_duration" else None)

    @jax.jit
    def run(params, x):
        return layer.apply({"params": params}, x, mutable=["stats"])

    y, vs = run(forced, x)
    assert compiles, "the listener saw the first pattern's compile"
    stats = {k: float(v[0]) for k, v in vs["stats"].items()}
    assert stats["moe_dropped_rows"] == 0
    assert stats["moe_rows_max_over_mean"] == pytest.approx(8 / 2)
    assert np.allclose(y, _dense_moe(forced, x, 2), atol=1e-5)
    x2 = jax.block_until_ready(
        jax.random.normal(jax.random.PRNGKey(2), x.shape))
    before = len(compiles)
    y2, vs2 = jax.block_until_ready(run(p, x2))
    assert len(compiles) == before and run._cache_size() == 1, \
        "a routing pattern recompiled the layer"
    assert float(vs2["stats"]["moe_dropped_rows"][0]) == 0
    assert float(vs2["stats"]["moe_rows_max_over_mean"][0]) < 8 / 2
    assert np.allclose(y2, _dense_moe(p, x2, 2), atol=1e-5)


def test_the_layers_gradients_are_the_dense_computations():
    layer = _layer()
    x = jax.random.normal(jax.random.PRNGKey(0), (2, 32, 64))
    p = layer.init(jax.random.PRNGKey(1), x)["params"]
    got = jax.grad(lambda p, x: jnp.sum(jnp.sin(
        layer.apply({"params": p}, x))), argnums=(0, 1))(p, x)
    want = jax.grad(lambda p, x: jnp.sum(jnp.sin(_dense_moe(p, x, 2))),
                    argnums=(0, 1))(p, x)
    for g, w in zip(jax.tree_util.tree_leaves(got),
                    jax.tree_util.tree_leaves(want)):
        assert np.allclose(g, w, atol=1e-5), np.abs(g - w).max()


# what a model may say otherwise (PR 40): the router's score and selection
# bias, an ungated expert, relu^2, an ungated shared expert

def _dense_variant(p, x, k, score="softmax", bias=None, gated=True,
                   act=jax.nn.silu, shared_gate=True, scale=1.0):
    """``_dense_moe`` with each of ``DroplessMoE``'s later options, written
    out: every expert on every token, masked by the chosen weights."""
    h = x.reshape(-1, x.shape[-1])
    logits = h @ p["router"]
    s = jax.nn.sigmoid(logits) if score == "sigmoid" \
        else jax.nn.softmax(logits, axis=-1)
    _, e = jax.lax.top_k(s if bias is None else s + bias, k)
    w = jnp.take_along_axis(s, e, axis=1) * scale

    def unit(up, down, gate=None):
        u = h @ up
        return (act(u) if gate is None else act(h @ gate) * u) @ down

    y = jnp.zeros_like(h)
    for i in range(p["router"].shape[1]):
        out = unit(p["up_proj"][i], p["down_proj"][i],
                   p["gate_proj"][i] if gated else None)
        y = y + jnp.sum(jnp.where(e == i, w, 0.0), axis=1)[:, None] * out
    if "shared_up_proj" in p:
        ys = unit(p["shared_up_proj"], p["shared_down_proj"],
                  p["shared_gate_proj"] if gated else None)
        if shared_gate:
            ys = ys * jax.nn.sigmoid(h @ p["shared_expert_gate"])
        y = y + ys
    return y.reshape(x.shape)


_RELU2 = lambda t: jnp.square(jax.nn.relu(t))  # noqa: E731
VARIANTS = {
    "sigmoid_score": (dict(score="sigmoid"), dict(score="sigmoid")),
    "selection_bias": (dict(score="sigmoid", choice_bias=True),
                       dict(score="sigmoid", bias=True)),
    "bias_over_softmax": (dict(choice_bias=True), dict(bias=True)),
    "ungated_relu2": (dict(gated=False, act="relu2"),
                      dict(gated=False, act=_RELU2)),
    "gated_relu2": (dict(act="relu2"), dict(act=_RELU2)),
    "shared_ungated_both_ways": (
        dict(gated=False, act="relu2", shared_d_ff=48, shared_gate=False),
        dict(gated=False, act=_RELU2, shared_gate=False)),
    "shared_gated_relu": (dict(act="relu", shared_d_ff=48),
                          dict(act=jax.nn.relu)),
    "all_of_nemotrons": (
        dict(score="sigmoid", choice_bias=True, gated=False, act="relu2",
             shared_d_ff=48, shared_gate=False, routed_scale=2.5,
             balance_coeff=0.0, z_coeff=0.0),
        dict(score="sigmoid", bias=True, gated=False, act=_RELU2,
             shared_gate=False, scale=2.5)),
}


@pytest.mark.parametrize("name", VARIANTS, ids=str)
def test_each_option_of_the_layer_is_the_dense_computation(name):
    """Values and every gradient of the layer under each option against the
    computation written out; the selection bias moves the CHOICE (the
    output differs from the layer without it) and takes no gradient."""
    layer_kw, dense_kw = VARIANTS[name]
    layer = dropless.DroplessMoE(num_experts=8, k=2, d_ff=32,
                                 dtype=jnp.float32, **layer_kw)
    x = jax.random.normal(jax.random.PRNGKey(0), (2, 32, 64))
    p = layer.init(jax.random.PRNGKey(1), x)["params"]
    p = jax.tree_util.tree_map(lambda w: 5.0 * w, p)     # far from uniform
    assert ("gate_proj" in p) == layer_kw.get("gated", True)
    assert ("shared_expert_gate" in p) == (
        "shared_d_ff" in layer_kw and layer_kw.get("shared_gate", True))
    if dense_kw.get("bias"):
        assert float(jnp.abs(p["e_score_correction_bias"]).max()) == 0.0
        p["e_score_correction_bias"] = 0.3 * jax.random.normal(
            jax.random.PRNGKey(2), (8,))
        dense_kw = dict(dense_kw, bias=p["e_score_correction_bias"])

    def dense(p, x):
        return _dense_variant(p, x, 2, **dense_kw)

    got = layer.apply({"params": p}, x)
    np.testing.assert_allclose(got, dense(p, x), atol=2e-5)
    g1 = jax.grad(lambda p, x: jnp.sum(jnp.sin(
        layer.apply({"params": p}, x))), argnums=(0, 1))(p, x)
    g2 = jax.grad(lambda p, x: jnp.sum(jnp.sin(dense(p, x))),
                  argnums=(0, 1))(p, x)
    for (path, g), w in zip(jax.tree_util.tree_leaves_with_path(g1),
                            jax.tree_util.tree_leaves(g2)):
        assert np.allclose(g, w, atol=2e-5), (path, np.abs(g - w).max())
    if dense_kw.get("bias") is not None:
        assert float(jnp.abs(g1[0]["e_score_correction_bias"]).max()) == 0.0
        unbiased = layer.apply({"params": dict(
            p, e_score_correction_bias=jnp.zeros((8,)))}, x)
        assert float(jnp.abs(unbiased - got).max()) > 1e-3


def test_route_scores_chooses_and_weighs_as_it_is_told():
    """``route`` by hand on one token: the softmax default; a sigmoid's own
    scores; a bias that moves the choice and stays out of the weights; and
    that the defaults trace what they traced before the options existed."""
    logits = jnp.asarray([[2.0, 1.0, 0.0, -1.0]])
    bias = jnp.asarray([-5.0, 0.0, 0.0, 5.0])
    w, e, probs = dropless.route(logits, 2, False)
    np.testing.assert_allclose(probs, jax.nn.softmax(logits))
    assert e.tolist() == [[0, 1]]
    w, e, s = dropless.route(logits, 2, False, score="sigmoid")
    np.testing.assert_allclose(s, jax.nn.sigmoid(logits))
    np.testing.assert_allclose(w, s[:, :2])
    w, e, s = dropless.route(logits, 2, True, score="sigmoid",
                             choice_bias=bias, routed_scale=2.5)
    assert e.tolist() == [[3, 1]]           # 0.27 + 5, 0.73: not expert 0
    want = s[0, jnp.asarray([3, 1])]
    np.testing.assert_allclose(w[0], 2.5 * want / want.sum(), rtol=1e-6)
    text = lambda **kw: str(jax.make_jaxpr(  # noqa: E731
        lambda x: dropless.route(x, 2, True, **kw))(logits))
    assert text() == text(score="softmax", choice_bias=None) \
        != text(score="sigmoid")


def test_both_coefficients_zero_trace_no_auxiliary_term():
    """With no auxiliary loss the layer computes neither term: no
    ``logsumexp`` in the program, nothing in ``losses``, no such statistic;
    ONE zero coefficient keeps both values in ``stats`` as before."""
    x = jax.random.normal(jax.random.PRNGKey(0), (1, 16, 64))

    def sown(**kw):
        layer = dropless.DroplessMoE(num_experts=8, k=2, d_ff=32,
                                     dtype=jnp.float32, **kw)
        p = layer.init(jax.random.PRNGKey(1), x)["params"]
        _, vs = layer.apply({"params": p}, x, mutable=["losses", "stats"])
        text = str(jax.make_jaxpr(lambda p: layer.apply(
            {"params": p}, x, mutable=["losses", "stats"]))(p))
        return vs, text

    vs, text = sown(balance_coeff=0.0, z_coeff=0.0)
    assert "losses" not in vs or not vs["losses"]
    assert set(vs["stats"]) == {"moe_rows_max_over_mean", "moe_dropped_rows"}
    assert "logsumexp" not in text and "reduce_logsumexp" not in text
    vs, text = sown(z_coeff=0.0)
    assert set(vs["losses"]) == {"moe_balance", "moe_z"}
    assert float(vs["losses"]["moe_z"][0]) == 0.0
    assert float(vs["stats"]["moe_z_loss"][0]) > 0.0


def test_a_width_no_multiple_of_128_divides_is_padded_once_in_the_layer():
    """An expert width of 3 x 64 = 192: the layer pads its weights to 256
    lanes (one ``pad`` a matrix, none on the rows), the three grouped
    matmuls run at 256, and the output and gradients are the dense
    computation's at 192."""
    layer = dropless.DroplessMoE(num_experts=4, k=2, d_ff=192, gated=False,
                                 act="relu2", dtype=jnp.float32)
    x = jax.random.normal(jax.random.PRNGKey(0), (1, 64, 128))
    p = layer.init(jax.random.PRNGKey(1), x)["params"]
    p = jax.tree_util.tree_map(lambda w: 3.0 * w, p)
    assert p["up_proj"].shape == (4, 128, 192)
    text = str(jax.make_jaxpr(lambda p: layer.apply({"params": p}, x))(p))
    assert text.count(" pad[") == 2 and "256" in text

    def dense(p, x):
        return _dense_variant(p, x, 2, gated=False, act=_RELU2)

    np.testing.assert_allclose(layer.apply({"params": p}, x), dense(p, x),
                               atol=2e-5)
    g1 = jax.grad(lambda p: jnp.sum(jnp.sin(layer.apply({"params": p}, x))))(
        p)
    g2 = jax.grad(lambda p: jnp.sum(jnp.sin(dense(p, x))))(p)
    for name in g2:
        assert g1[name].shape == p[name].shape
        np.testing.assert_allclose(g1[name], g2[name], atol=2e-5)


def test_a_buffer_leaf_is_unmoved_by_the_engines_step():
    """A model that lists a leaf in ``buffer_leaves`` gets it back from
    AdamW with weight decay as it went in (no gradient step, no decay),
    while its neighbours move; a model that lists none traces no such
    selection."""
    from deepspeed_tpu.models.nemotron_h import (NemotronHForCausalLM,
                                                 nemotron_h_tiny)
    model = NemotronHForCausalLM(nemotron_h_tiny(
        hybrid_override_pattern="ME"))
    assert model.buffer_leaves == (dropless.CHOICE_BIAS,)
    ids = np.random.default_rng(0).integers(0, 256, (2, 32)).astype(np.int32)
    engine, _, _, _ = dstpu.initialize(
        config={"train_batch_size": 2, "seed": 0,
                "optimizer": {"type": "AdamW", "params": {
                    "lr": 0.01, "weight_decay": 0.1}},
                "steps_per_print": 10 ** 9},
        model=model, mesh=make_mesh(MeshConfig(data=1),
                                    devices=jax.devices()[:1]))
    engine.train_batch({"input_ids": ids})
    before = jax.tree_util.tree_map(np.asarray, engine.state.params)
    assert np.abs(before["layer_1"]["mixer"][dropless.CHOICE_BIAS]).max() > 0
    for _ in range(2):
        engine.train_batch({"input_ids": ids})
    after = engine.state.params["layer_1"]["mixer"]
    np.testing.assert_array_equal(np.asarray(after[dropless.CHOICE_BIAS]),
                                  before["layer_1"]["mixer"][
                                      dropless.CHOICE_BIAS])
    assert np.abs(np.asarray(after["router"])
                  - before["layer_1"]["mixer"]["router"]).max() > 1e-4
    assert not hasattr(llama.LlamaForCausalLM, "buffer_leaves")


@pytest.mark.parametrize("sizes,rows", [
    ([5, 0, 20, 7, 0, 0, 1, 7], 40),     # uneven, empty groups
    ([40, 0, 0, 0, 0, 0, 0, 0], 40),     # everything on one expert
    ([5, 5, 5, 5, 5, 5, 5, 5], 40),      # even
    ([3, 9, 0, 4, 0, 4, 11, 6], 37),     # rows not a whole sublane: padded
], ids=["uneven-empty", "one-expert", "even", "padded"])
def test_grouped_matmul_matches_a_per_expert_loop(sizes, rows):
    """Forward and both gradients (the layer always routes exactly as many
    rows as it hands over: the sizes sum to the rows)."""
    assert sum(sizes) == rows
    sizes = np.asarray(sizes, np.int32)
    lhs = jax.random.normal(jax.random.PRNGKey(0), (rows, 64))
    rhs = jax.random.normal(jax.random.PRNGKey(1), (8, 64, 32))
    ends = np.cumsum(sizes)
    group = np.searchsorted(ends, np.arange(rows), side="right")

    def loop(a, b):
        out = jnp.zeros((rows, 32))
        for g in range(8):
            out = out + jnp.where((group == g)[:, None], a @ b[g], 0.0)
        return out

    f = lambda a, b: jnp.sum(jnp.sin(grouped_matmul(  # noqa: E731
        a, b, jnp.asarray(sizes))))
    g = lambda a, b: jnp.sum(jnp.sin(loop(a, b)))  # noqa: E731
    assert np.allclose(grouped_matmul(lhs, rhs, jnp.asarray(sizes)),
                       loop(lhs, rhs), atol=1e-4)
    for got, want in zip(jax.grad(f, (0, 1))(lhs, rhs),
                         jax.grad(g, (0, 1))(lhs, rhs)):
        assert np.allclose(got, want, atol=1e-4), np.abs(got - want).max()


# ------------------------------------------------------ LLaMA did not move

# md5 of ``jax.jit(grad of llama_tiny(loss_chunk=32)'s loss).lower(...)
# .as_text()`` at [2, 64] (jax 0.9.0), made by running these very lines: on
# PR 27's parent commit 9980070 it read e79a50eedb69b233793f1c646ed3a028,
# and did until PR 51 moved the HEAD this text ends in
# (``models/gpt2.chunked_lm_loss`` forms its gradient in the forward chunk);
# made again there — the same model on its full logits lowers to one text
# (462bd48effe01460510674246247a968) at PR 51 and at its parent
LLAMA_TINY_PARENT_MD5 = "979c513c645e74df49af461962a1a8ff"


def test_llama_tiny_lowers_to_the_parents_text():
    m = llama.LlamaForCausalLM(llama.llama_tiny(loss_chunk=32))
    ids = jnp.zeros((2, 64), jnp.int32)
    p = jax.eval_shape(lambda: m.init(jax.random.PRNGKey(0), ids))
    text = jax.jit(lambda p, i: jax.grad(
        lambda pp: m.apply(pp, i, labels=i))(p)).lower(p, ids).as_text()
    assert hashlib.md5((text + "\n").encode()).hexdigest() == \
        LLAMA_TINY_PARENT_MD5


@pytest.mark.parametrize("preset", [llama.llama_tiny, llama.llama_7b,
                                    llama.llama3_8b])
def test_the_llama_presets_keep_llamas_behaviour(preset):
    cfg = preset()
    assert (cfg.num_experts, cfg.num_experts_per_tok, cfg.qk_norm,
            cfg.norm_topk_prob) == (0, 0, False, False)
    model = llama.LlamaForCausalLM(dataclasses.replace(
        cfg, n_layers=1, hidden_size=64, intermediate_size=32, n_heads=4,
        n_kv_heads=min(cfg.kv_heads, 2), vocab_size=128))
    assert model.sown_collections == ()
    shapes = jax.eval_shape(lambda: model.init(
        jax.random.PRNGKey(0), jnp.zeros((1, 8), jnp.int32)))
    assert set(shapes) == {"params"}
    blk = shapes["params"]["layers"]["blk"]
    assert set(blk["mlp"]) == {"gate_proj", "up_proj", "down_proj"}
    assert set(blk["attn"]) == {"q_proj", "k_proj", "v_proj", "o_proj"}


def test_the_olmoe_preset_is_the_published_configuration():
    cfg = llama.olmoe_1b_7b()
    pub = dict(CONFIG, **CONFIG["published"])
    assert (cfg.hidden_size, cfg.intermediate_size, cfg.n_layers,
            cfg.n_heads, cfg.kv_heads, cfg.head_dim) == (
        pub["hidden_size"], pub["intermediate_size"],
        pub["num_hidden_layers"], pub["num_attention_heads"],
        pub["num_key_value_heads"], 128)
    assert (cfg.num_experts, cfg.num_experts_per_tok, cfg.norm_topk_prob,
            cfg.qk_norm, cfg.vocab_size, cfg.max_seq_len) == (
        64, 8, False, True, 50304, 4096)
    assert (cfg.router_aux_loss_coef, cfg.router_z_loss_coef) == (0.01, 0.001)
    # 6.9B in all; the benchmark's depth-1 cut is ISSUE 27's 625.6M
    assert 6.9e9 < cfg.num_params() < 6.93e9
    one = dataclasses.replace(cfg, n_layers=1).num_params()
    assert abs(one - 625.6e6) < 0.2e6
