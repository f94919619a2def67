"""The DeepSeek-V3 family (``model_type: deepseek_v3``; Kanana-2 is its one
configuration): how its configuration file becomes a running system.

The members ``benchmark/families/__init__.py`` lists for training, none of
serving's. The model is ``deepspeed_tpu.models.deepseek_v3`` built through
``dstpu.initialize`` as the other cells' are; the plain reference is
``benchmark/reference/deepseek_v3.py``. Key names are the published
config's.

A configuration of this family is ONE RANK'S SHARE of an expert-parallel
layout, as the Nemotron-H family's is: ``n_routed_experts`` is the experts
held here, ``expert_parallel_size`` how many such shares the router chooses
among (the router is ``n_routed_experts x expert_parallel_size`` wide, the
published count), ``expert_parallel_rank`` which of them this is;
``vocab_size`` is the slice of the vocabulary held here. The weights are the
seed's, but for the routers' selection biases, which set-up then moves by
the balancing rule until the loads are level (``balanced_selection_bias``).

``correct`` is OLMoE's comparison as the Nemotron-H and Laguna families
adapted it (``families/olmoe.py`` says why loss and gradient norm alone see
nothing of a layer at random initialisation): the loss and gradient norm of
the two own passes; then, of a reference pass PINNED to the system's experts
and to the system's residual stream (``reference/qwen3_next.forward`` says
why), the routing (assignments the reference's own sigmoid router with its
selection bias, on the system's stream, would have made otherwise), each
branch as one vector — ``mla_out_rel`` (latent attention, every layer),
``dense_out_rel`` (the leading dense layer), ``ffn_out_rel`` (the expert
layers) — and every gradient leaf as a vector; that the selection bias's
gradient is exactly zero; and, because a pinned pass is blind to the stream
itself, two checks that are NOT pinned: the first layer (and the first
expert layer's routing) of the two own passes, and the system's residual
adds; each against the file's ``train.tolerance``.
"""

import functools

import numpy as np

from benchmark.families import common, olmoe as shared
from benchmark.families.common import (at as _at, rel as _rel,
                                       routing_differs as _routing_differs)
from benchmark.families.qwen3_next import stream_add_differences
from benchmark.reference import deepseek_v3 as ref

WIDTH_KEYS = ("hidden_size", "intermediate_size", "moe_intermediate_size",
              "num_attention_heads", "num_key_value_heads", "head_dim",
              "kv_lora_rank", "q_lora_rank", "qk_nope_head_dim",
              "qk_rope_head_dim", "qk_head_dim", "v_head_dim",
              "n_shared_experts", "num_experts_per_tok")
KERNEL_TAGS = ("flash_fwd", "flash_bwd", "moe_gmm")
MODULE_TAGS = ("ds_loss_head", "ds_embed", "moe_router", "moe_dispatch",
               "moe_act", "moe_combine", "moe_shared", "mla_latent",
               "mla_expand", "mla_rope", "dense_mlp", "mla_attn", "mlp",
               "input_norm", "post_attn_norm", "norm")
DISPATCH_TAGS = shared.DISPATCH_TAGS
# every tag a path under the module ``mla_attn`` can take (``mla_layer_ms``)
# and those of the latent form round the kernels (``mla_expand_ms``)
MLA_EXPAND_TAGS = ("mla_latent", "mla_expand", "mla_rope")
MLA_LAYER_TAGS = ("flash_fwd", "flash_bwd") + MLA_EXPAND_TAGS + ("mla_attn",)
# this process's engine of THIS family, and its gauges as ``judge_train``
# folded them
_LIVE = {}

_SIZE_KEYS = ("vocab_size", "max_position_embeddings", "hidden_size",
              "intermediate_size", "moe_intermediate_size",
              "num_hidden_layers", "num_attention_heads", "q_lora_rank",
              "kv_lora_rank", "qk_nope_head_dim", "qk_rope_head_dim",
              "v_head_dim", "rope_theta", "rope_interleave",
              "first_k_dense_replace", "n_routed_experts",
              "expert_parallel_size", "expert_parallel_rank",
              "n_shared_experts", "num_experts_per_tok", "n_group",
              "topk_group", "norm_topk_prob", "routed_scaling_factor",
              "rms_norm_eps", "initializer_range",
              "e_score_correction_bias_std")
_NOT_THE_MODELS = ("n_routed_experts", "expert_parallel_size",
                   "expert_parallel_rank")


def sizes(config, rehearse):
    out = {k: config[k] for k in _SIZE_KEYS}
    if rehearse:
        out.update({k: v for k, v in config["rehearse_cpu"].items()
                    if k in _SIZE_KEYS})
    return out


def traffic_shapes(config, rehearse):
    s = sizes(config, rehearse)
    return {"vocab_size": s["vocab_size"],
            "max_positions": s["max_position_embeddings"],
            "seq_scale": s["max_position_embeddings"]
            / config["max_position_embeddings"]}


def model_config(config, rehearse):
    import jax.numpy as jnp
    from deepspeed_tpu.models.deepseek_v3 import DeepseekV3Config
    s, m = sizes(config, rehearse), common.merged(config, "model", rehearse)
    dtypes = {"bfloat16": jnp.bfloat16, "float32": jnp.float32}
    ranks = s["expert_parallel_size"]
    return DeepseekV3Config(
        **{k: s[k] for k in _SIZE_KEYS if k not in _NOT_THE_MODELS},
        n_routed_experts=s["n_routed_experts"] * ranks,
        experts_held=s["n_routed_experts"] if ranks > 1 else 0,
        expert_share=s["expert_parallel_rank"],
        dtype=dtypes[m["dtype"]], param_dtype=dtypes[m["param_dtype"]],
        remat=m["remat"], remat_policy=m["remat_policy"],
        loss_chunk=m["loss_chunk"])


# ----------------------------------------------------------------- training

def _model(config, rehearse):
    from deepspeed_tpu.models.deepseek_v3 import DeepseekV3ForCausalLM
    return DeepseekV3ForCausalLM(model_config(config, rehearse))


def build_train(config, global_batch, seed, devices, rehearse):
    """(engine, initial parameters): ``common.build_train``'s recipe over
    ``DeepseekV3ForCausalLM`` (a program without this model fails at
    ``_model``, before any work), the weights made from 64 example
    positions, the selection biases then levelled."""
    engine, params = common.build_train(
        _model(config, rehearse), config, global_batch, seed, devices,
        rehearse, example_len=64)
    params, _LIVE["balance"] = balanced_selection_bias(
        config, params, global_batch, seed, rehearse)
    # the engine adopted the buffers ``common.build_train`` made: it is
    # handed the tree whose selection biases moved, every other leaf the same
    engine.state = engine.state.replace(params=params)
    _LIVE["engine"] = engine         # ``judge_train`` folds its gauges
    return engine, params


def balanced_selection_bias(config, params, global_batch, seed, rehearse):
    """``common.balanced_selection_bias`` (its docstring says what the rule
    is and why set-up runs it) over this model's expert layers: module
    ``mlp`` of every sparse ``layer_<i>``, as
    ``train.selection_bias_balance`` sets the rounds and rates."""
    s = sizes(config, rehearse)
    return common.balanced_selection_bias(
        _model(config, rehearse), params, "mlp",
        [f"layer_{i}" for i, kind
         in enumerate(_kinds(config, rehearse)) if kind == "sparse"],
        common.merged(config, "train", rehearse)["selection_bias_balance"],
        global_batch, s["vocab_size"], seed)


def program_gauges():
    """The program's ``moe/*`` and ``attention/*`` gauges of the LAST
    WARM-UP STEP, as ``judge_train`` folded them ({} before it)."""
    return _LIVE.get("gauges", {})


def lower_train_step(config, traffic, devices):
    """The cell's train step at real size, lowered over abstract state on
    ``devices`` (described chips)."""
    return common.lower_train_step(_model(config, rehearse=False), config,
                                   traffic, devices)


# what the reference calls each leaf of a layer, by the program's path
_ATTN_LEAVES = {
    "input_norm": ("input_norm", "scale"),
    "post_attn_norm": ("post_attn_norm", "scale"),
    "q": ("mla_attn", "q_proj", "kernel"),
    "kv_a": ("mla_attn", "kv_a_proj", "kernel"),
    "kv_a_norm": ("mla_attn", "kv_a_norm", "scale"),
    "kv_b": ("mla_attn", "kv_b_proj", "kernel"),
    "o": ("mla_attn", "o_proj", "kernel")}
LAYER_LEAVES = {
    "dense": dict(
        _ATTN_LEAVES, mlp_gate=("mlp", "gate_proj", "kernel"),
        mlp_up=("mlp", "up_proj", "kernel"),
        mlp_down=("mlp", "down_proj", "kernel")),
    "sparse": dict(
        _ATTN_LEAVES, router=("mlp", "router"),
        bias=("mlp", "e_score_correction_bias"), gate=("mlp", "gate_proj"),
        up=("mlp", "up_proj"), down=("mlp", "down_proj"),
        shared_gate=("mlp", "shared_gate_proj"),
        shared_up=("mlp", "shared_up_proj"),
        shared_down=("mlp", "shared_down_proj"))}
# a gradient leaf's name in ``grad_leaf_rel``: the attention leaves told
# apart under ``attn.``, the dense layer's under ``dense.``, the expert
# layers' under ``ffn.``
_LEAF_GROUP = {**{n: "attn" for n in ("q", "kv_a", "kv_a_norm", "kv_b", "o")},
               **{n: "dense" for n in ("mlp_gate", "mlp_up", "mlp_down")},
               **{n: "ffn" for n in ("router", "gate", "up", "down",
                                     "shared_gate", "shared_up",
                                     "shared_down")}}


def _kinds(config, rehearse):
    """"dense" | "sparse" of every layer."""
    s = sizes(config, rehearse)
    lead = s["first_k_dense_replace"]
    return ["dense"] * lead + ["sparse"] * (s["num_hidden_layers"] - lead)


def _blocks(tree, config, rehearse):
    """Layer i's sub-tree of a tree laid out as the model's parameters (or
    its sown values) are, in layer order."""
    return [tree[f"layer_{i}"]
            for i in range(sizes(config, rehearse)["num_hidden_layers"])]


def reference_view(params, config, rehearse):
    """(top, layers) in the reference's layout, float32, from
    ``DeepseekV3ForCausalLM``'s tree."""
    import jax
    import jax.numpy as jnp
    top = {"embed": params["embed_tokens"], "norm": params["norm"]["scale"],
           "lm_head": params["lm_head"]}
    layers = [{name: _at(blk, path)
               for name, path in LAYER_LEAVES[kind].items()}
              for blk, kind in zip(_blocks(params, config, rehearse),
                                   _kinds(config, rehearse))]
    return jax.tree_util.tree_map(lambda x: x.astype(jnp.float32),
                                  (top, layers))


def reference_sizes(config, rehearse):
    s = sizes(config, rehearse)
    return dict(n_head=s["num_attention_heads"], nope=s["qk_nope_head_dim"],
                rope_dim=s["qk_rope_head_dim"], v_dim=s["v_head_dim"],
                theta=float(s["rope_theta"]), eps=s["rms_norm_eps"],
                k=s["num_experts_per_tok"],
                expert_lo=s["n_routed_experts"] * s["expert_parallel_rank"],
                routed_scale=s["routed_scaling_factor"],
                norm_topk_prob=s["norm_topk_prob"])


def _bf16_grads(config, rehearse):
    return common.merged(config, "train", rehearse)["engine"].get(
        "data_types", {}).get("grad_dtype") == "bf16"


def system_step(config, params, batch_ids, device, rehearse):
    """(loss, per-layer intermediates, gradients) of the PROGRAM's model on
    ``batch_ids`` in one jitted program, weights cast and loss formed as the
    engine's step does (``families/olmoe.system_step``). Per layer
    {"top_e" (None for the dense layer), "x_mid" (the residual stream after
    the mixer), "mixer_out", "ffn_out"}."""
    import jax
    import jax.numpy as jnp
    model = _model(config, rehearse)
    bf16 = _bf16_grads(config, rehearse)

    def loss_fn(p, ids):
        out, vs = model.apply({"params": p}, ids, labels=ids,
                              mutable=["losses", "intermediates"])
        return out + sum(jnp.sum(x) for x in jax.tree_util.tree_leaves(
            vs.get("losses", {}))), vs["intermediates"]

    @jax.jit
    def step(p, ids):
        if bf16:
            p = jax.tree_util.tree_map(
                lambda x: x.astype(jnp.bfloat16)
                if x.dtype == jnp.float32 else x, p)
        (loss, got), grads = jax.value_and_grad(loss_fn, has_aux=True)(p, ids)
        return loss, got, grads

    loss, got, grads = step(jax.device_put(params, device),
                            jax.device_put(np.asarray(batch_ids), device))
    layers = [{"top_e": blk["mlp"]["top_e"][0] if "mlp" in blk else None,
               "x_mid": blk["x_mid"][0], "mixer_out": blk["mixer_out"][0],
               "ffn_out": blk["ffn_out"][0]}
              for blk in _blocks(got, config, rehearse)]
    return loss, layers, grads


def own_stream_differences(system, reference, kinds):
    """Of two passes that each ran on their OWN residual stream, every
    layer's [FFN kind, attention branch's relative error, FFN branch's,
    share of the T x k assignments that differ (0 for a dense layer)]: each
    holds what the layers under it left, so they are reported and only the
    first held."""
    out = []
    for got, want, kind in zip(system, reference, kinds):
        routing = 0.0 if got["top_e"] is None else float(_routing_differs(
            got["top_e"], want["top_e"])) / want["top_e"].size
        out.append([kind, float(_rel(got["mixer_out"], want["mixer_out"])),
                    float(_rel(got["ffn_out"], want["ffn_out"])), routing])
    return out


def branch_differences(system, reference, kinds):
    """Of a reference pass PINNED to the system's experts and residual
    stream, the worst layer's of its kind: ``mla_out_rel`` (the latent
    attention branch), ``dense_out_rel`` / ``ffn_out_rel`` (the dense / the
    expert FFN branch), each as one vector, and the routing the reference's
    own router would have chosen otherwise on the system's stream."""
    import jax.numpy as jnp
    out = {"mla_out_rel": 0.0, "dense_out_rel": 0.0, "ffn_out_rel": 0.0,
           "routing_differs": 0, "routing_assignments": 0}
    by_layer = []
    for got, want, kind in zip(system, reference, kinds):
        mixer = _rel(got["mixer_out"], want["mixer_out"])
        ffn = _rel(got["ffn_out"], want["ffn_out"])
        out["mla_out_rel"] = jnp.maximum(out["mla_out_rel"], mixer)
        key = "dense_out_rel" if kind == "dense" else "ffn_out_rel"
        out[key] = jnp.maximum(out[key], ffn)
        if got["top_e"] is not None:
            out["routing_differs"] += _routing_differs(got["top_e"],
                                                       want["own_top_e"])
            out["routing_assignments"] += want["own_top_e"].size
        by_layer.append([mixer, ffn])
    return dict(out, by_layer=by_layer)


def gradient_differences(system, reference, config, rehearse):
    """({leaf: |system - reference| / |reference|} of the system's gradient
    tree (the program's layout) against the reference's (``reference_view``'s
    layout: (top, layers)), the worst layer's for a layer's leaf, named
    ``attn.`` / ``dense.`` / ``ffn.`` + the reference's name (the two block
    norms and the top leaves plain); the largest magnitude of the SYSTEM's
    gradient of the selection bias, which is exactly zero on both sides)."""
    import jax.numpy as jnp

    def rel(a, b):
        return jnp.linalg.norm((a - b).ravel()) / jnp.linalg.norm(b.ravel())

    top_s, layers_s = reference_view(system, config, rehearse)
    top_r, layers_r = reference
    out = {name: rel(top_s[name], top_r[name]) for name in top_r}
    bias = jnp.zeros((), jnp.float32)
    for got, want in zip(layers_s, layers_r):
        for name in want:
            if name == "bias":
                bias = jnp.maximum(bias, jnp.max(jnp.abs(got[name])))
                continue
            key = f"{_LEAF_GROUP[name]}.{name.removeprefix('mlp_')}" \
                if name in _LEAF_GROUP else name
            out[key] = jnp.maximum(out.get(key, 0.0),
                                   rel(got[name], want[name]))
    return out, bias


@functools.lru_cache(maxsize=None)
def _reference_program(config_key, rehearse, mode):
    """The reference as ONE jitted program over the program's weight tree
    (``families/qwen3_next._reference_program``): "forward" -> (loss,
    detail) of its own pass; "backward" -> (gradient norm, {leaf: relative
    error}, the bias's gradient, branch differences) of the reference pinned
    to the experts the system chose and to the system's residual stream."""
    import jax
    config = _CONFIGS[config_key]
    sizes_ = reference_sizes(config, rehearse)
    kinds = _kinds(config, rehearse)

    def view(w):
        return reference_view(w, config, rehearse)

    @jax.jit
    def forward(p, ids, system_layers):
        loss, detail = ref.loss(p, ids, view, **sizes_)
        worst, adds = stream_add_differences(view(p)[0]["embed"][ids],
                                             system_layers)
        return loss, dict(detail, stream_add_rel=worst,
                          stream_add_by_layer=adds)

    @jax.jit
    def backward(p, ids, system_layers, system_grads):
        chosen = tuple(layer["top_e"] for layer in system_layers)
        streams = tuple((layer["x_mid"], layer["x_mid"] + layer["ffn_out"])
                        for layer in system_layers)
        # gradients in the reference's own layout, a layer a leaf
        (_, detail), g = ref.loss_and_grads(view(p), ids, chosen=chosen,
                                            streams=streams, **sizes_)
        leaves, bias = gradient_differences(system_grads, g, config, rehearse)
        return (ref.grad_norm(g), leaves, bias,
                branch_differences(system_layers, detail["layers"], kinds))

    return {"forward": forward, "backward": backward}[mode]


# configurations by their sizes, for ``_reference_program``'s cache key (a
# dict is not hashable)
_CONFIGS = {}


def _reference(mode, config, params, batch_ids, device, rehearse, *more):
    import json
    import jax
    key = json.dumps(sizes(config, rehearse), sort_keys=True)
    _CONFIGS[key] = config
    run = _reference_program(key, bool(rehearse), mode)
    return run(jax.device_put(params, device),
               jax.device_put(np.asarray(batch_ids), device), *more)


def compare(config, params, batch_ids, device, rehearse, system):
    """(reference loss, reference gradient norm, differences) of ``system``
    (``system_step``'s three values) against the plain reference on the same
    weights and batch: the reference's OWN forward pass first (handed
    nothing of the system's) for the loss; then its pass pinned to the
    experts the system chose and to the system's residual stream, forward
    for the routing and each branch's output, backward for the gradient norm
    and every gradient leaf."""
    import jax
    kinds = _kinds(config, rehearse)
    _, layers, grads = system
    loss, detail = _reference("forward", config, params, batch_ids, device,
                              rehearse, tuple(layers))
    diffs = {"own_stream_by_layer": own_stream_differences(
        layers, detail["layers"], kinds),
        "stream_add_rel": float(detail["stream_add_rel"]),
        "stream_add_by_layer": [[float(v) for v in pair]
                                for pair in detail["stream_add_by_layer"]],
        "reference_ce": float(detail["ce"])}
    del detail
    diffs["system_grad_norm"] = float(ref.grad_norm(
        jax.tree_util.tree_map(lambda g: g.astype("float32"), grads)))
    gnorm, leaves, bias, branches = jax.device_get(_reference(
        "backward", config, params, batch_ids, device, rehearse,
        tuple(layers), grads))
    diffs["grad_leaf_rel"] = {n: float(v) for n, v in leaves.items()}
    diffs["bias_grad_abs"] = float(bias)
    diffs.update(jax.tree_util.tree_map(
        lambda v: int(v) if v.dtype.kind == "i" else float(v), branches))
    return float(loss), float(gnorm), diffs


def reference_train(config, params, batch_ids, devices, rehearse):
    """``compare`` of the program's model as the configuration builds it.
    Call before the engine's first step."""
    return compare(config, params, batch_ids, devices[0], rehearse,
                   system_step(config, params, batch_ids, devices[0],
                               rehearse))


def judge_train(config, got_loss, got_gnorm, want_loss, want_gnorm,
                differences=None):
    """``families/olmoe.judge_train`` (loss, gradient norm, routing, the
    attention and the expert branch, every gradient leaf, no routed row
    dropped) with the latent-attention branch under OLMoE's attention key,
    and the dense branch, the bias's zero gradient and the two unpinned
    checks. The expert branch is held as one vector (``ffn_out_rel``) where
    OLMoE's is held by its worst row: handed over under OLMoE's key."""
    tol = config["train"]["tolerance"]
    if differences is not None:
        differences = dict(differences,
                           ffn_out_row_rel=differences["ffn_out_rel"],
                           attn_out_rel=differences["mla_out_rel"])
        config = dict(config, train=dict(config["train"], tolerance=dict(
            tol, ffn_out_row_rel=tol["ffn_out_rel"],
            attn_out_rel=tol["mla_out_rel"])))
    checks, detail = shared.judge_train(config, got_loss, got_gnorm,
                                        want_loss, want_gnorm, differences)
    # OLMoE's judge folded ITS family's engine (none here)
    checks.pop("no_routed_row_dropped", None)
    if differences is not None:
        checks["dense_branch_matches_reference"] = \
            differences["dense_out_rel"] <= tol["dense_out_rel"]
        checks["selection_bias_takes_no_gradient"] = \
            differences["bias_grad_abs"] == 0.0
        # not pinned: the first layer of the two own passes (both start
        # from the same embedding rows), the first EXPERT layer's routing on
        # the stream the dense layer left, and the system's residual adds
        own, first = differences["own_stream_by_layer"], \
            tol["own_stream_first_layer"]
        _, mixer, ffn, _ = own[0]
        routing = next(row[3] for row in own if row[0] == "sparse")
        checks["first_layer_matches_reference_on_its_own_stream"] = \
            mixer <= first["mixer_rel"] and ffn <= first["ffn_rel"] \
            and routing <= first["routing_share"]
        checks["residual_stream_adds_up"] = \
            differences["stream_add_rel"] <= tol["stream_add_rel"]
        detail["differences"]["tolerances"].update(
            {k: tol[k] for k in ("mla_out_rel", "dense_out_rel",
                                 "ffn_out_rel", "own_stream_first_layer",
                                 "stream_add_rel")})
    # this family's own engine, fenced and folded here, after warm-up
    engine = _LIVE.get("engine")
    gauges = _LIVE["gauges"] = \
        engine.telemetry_flush()["gauges"] if engine is not None else {}
    if "balance" in _LIVE:
        detail["selection_bias_balance"] = _LIVE["balance"]
    if "moe/dropped_rows" in gauges:
        checks["no_routed_row_dropped"] = gauges["moe/dropped_rows"] == 0
        detail["moe_gauges"] = {k: v for k, v in gauges.items()
                                if k.startswith(("moe/", "attention/"))}
    return checks, detail


# ------------------------------------------------- operations and bytes

def _layer_counts(config, rehearse):
    """(sizes, dense layers, expert layers)."""
    s = sizes(config, rehearse)
    lead = s["first_k_dense_replace"]
    return s, lead, s["num_hidden_layers"] - lead


def rows_held_share(config, rehearse=False):
    """Share of the T x k routed rows a uniform router sends to the experts
    held here: 1 / ``expert_parallel_size``."""
    return 1.0 / sizes(config, rehearse)["expert_parallel_size"]


def attention_matmul_params(s):
    """The four projections of one latent-attention module: q, the
    down-projection (latent + rotated key), the up-projection (keys without
    position + values), o."""
    H, n = s["hidden_size"], s["num_attention_heads"]
    return H * n * (s["qk_nope_head_dim"] + s["qk_rope_head_dim"]) \
        + H * (s["kv_lora_rank"] + s["qk_rope_head_dim"]) \
        + s["kv_lora_rank"] * n * (s["qk_nope_head_dim"] + s["v_head_dim"]) \
        + n * s["v_head_dim"] * H


def active_matmul_params(config, rehearse=False):
    """Parameters one token is multiplied with HERE: every layer's four
    attention projections; the dense layer's SwiGLU or an expert layer's
    router, its shared experts and the k experts times the share of them
    held here; and the output head (the embedding lookup is a gather)."""
    s, dense, sparse = _layer_counts(config, rehearse)
    H, F = s["hidden_size"], s["moe_intermediate_size"]
    experts = H * s["n_routed_experts"] * s["expert_parallel_size"] \
        + 3 * H * s["n_shared_experts"] * F \
        + s["num_experts_per_tok"] * rows_held_share(config, rehearse) \
        * 3 * H * F
    return (dense + sparse) * attention_matmul_params(s) \
        + dense * 3 * H * s["intermediate_size"] + sparse * experts \
        + s["vocab_size"] * H


def train_attention_flops_per_step(config, batch, seq_len, rehearse=False):
    """Causal flops of the flash forward and backward kernels in one step:
    six S x S products a head a layer, each halved by the mask — q.k, dP's
    partner dS.k (dq) and dS.q (dk) over the q·k width, P.v, dO.v (dp) and
    P.dO (dv) over the value width: ``S^2 x (3 x 192 + 3 x 128)``. The
    forward's two products are (192 + 128) / 960 = one third of it exactly,
    which is the split ``flash_fwd_roofline`` / ``flash_bwd_roofline`` take."""
    s = sizes(config, rehearse)
    qk = s["qk_nope_head_dim"] + s["qk_rope_head_dim"]
    return s["num_hidden_layers"] * batch * s["num_attention_heads"] \
        * seq_len * seq_len * (3 * qk + 3 * s["v_head_dim"])


def train_flops_per_token(config, seq_len, rehearse=False):
    """6 a matmul parameter a token meets on THIS rank (2 forward, 4
    backward) + causal attention in every layer."""
    return 6 * active_matmul_params(config, rehearse) \
        + train_attention_flops_per_step(config, 1, seq_len, rehearse) \
        / seq_len


def moe_gmm_flops_per_step(config, tokens, rehearse=False):
    """Flops the grouped matmuls of one step NEED for the rows held here:
    three products (forward, dlhs, drhs) of three matrices (gate, up, down),
    every expert layer. The rows are the share the PROGRAM counted at the
    last warm-up step (the gauge ``moe/rows_held_share``,
    ``program_gauges``) where a run has folded it, the uniform router's
    1 / ``expert_parallel_size`` before
    (``families/smallthinker.moe_gmm_flops_per_step`` says why)."""
    s, _, sparse = _layer_counts(config, rehearse)
    share = program_gauges().get("moe/rows_held_share") \
        or rows_held_share(config, rehearse)
    rows = tokens * s["num_experts_per_tok"] * share
    return sparse * 3 * 3 * 2 * rows * s["hidden_size"] \
        * s["moe_intermediate_size"]
