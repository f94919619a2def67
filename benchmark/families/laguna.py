"""The Laguna family: how its configuration file becomes a running system.

The members ``benchmark/families/__init__.py`` lists for training, none of
serving's. The model is ``deepspeed_tpu.models.laguna`` built through
``dstpu.initialize`` as the other cells' are; the plain reference is
``benchmark/reference/laguna.py``. Key names are the published config's; the
three per-layer lists (``layer_types``, ``mlp_layer_types``,
``num_attention_heads_per_layer``) and ``rope_parameters`` are handed to the
model and to the reference as the file has them.

A configuration of this family is ONE RANK'S SHARE of an expert-parallel
layout, as the Qwen3-Next family's is (``families/qwen3_next.py``):
``num_experts`` is the experts held here, ``expert_parallel_size`` how many
such shares the router chooses among, ``expert_parallel_rank`` which of them
this is; ``vocab_size`` is the slice of the vocabulary held here.

``correct`` is the Qwen3-Next family's comparison with the mixer branch told
apart by layer KIND — ``full_out_rel`` (full-attention layers: 48 heads,
YaRN partial RoPE), ``swa_out_rel`` (sliding layers: 64 heads, window) — and
the FFN branch by its kind — ``dense_out_rel`` (the leading dense layer),
``ffn_out_rel`` (expert layers): the loss of the two own forward passes; the
routing, each branch and every gradient leaf as a vector of the reference's
pass PINNED to the system's experts and residual stream; and two checks that
are not pinned: the first layer of the two own passes and the system's
residual adds. And one check that no mask can hide
(``window_differences``): a sliding layer of the SYSTEM, run on the timed
batch's first-layer input, must differ from the same layer under full causal
attention by far more than any tolerance, and its outputs at the last
``window`` positions must not change when every token more than ``window``
behind them changes.
"""

import functools

import numpy as np

from benchmark import roofline
from benchmark.families import common, olmoe as shared
from benchmark.families.common import (at as _at, rel as _rel,
                                       routing_differs as _routing_differs)
from benchmark.families.qwen3_next import stream_add_differences
from benchmark.reference import laguna as ref

WIDTH_KEYS = ("hidden_size", "intermediate_size", "moe_intermediate_size",
              "shared_expert_intermediate_size", "num_attention_heads",
              "num_key_value_heads", "head_dim", "num_experts_per_tok",
              "sliding_window")
# ``tag_of`` matches a kernel tag by prefix, the first listed first
# (``flash_bwd`` takes ``flash_bwd_chunk``; the window backward is the ONE
# scope ``swa_bwd`` since PR 53)
KERNEL_TAGS = ("swa_fwd", "swa_bwd", "flash_fwd", "flash_bwd", "moe_gmm")
MODULE_TAGS = ("ds_loss_head", "ds_embed", "moe_router", "moe_dispatch",
               "moe_act", "moe_combine", "moe_shared", "attn_gate",
               "dense_mlp", "attn", "mlp", "input_norm", "post_attn_norm",
               "norm")
DISPATCH_TAGS = shared.DISPATCH_TAGS
FULL, SLIDING = ref.FULL, ref.SLIDING
# this process's engine of THIS family, and its gauges as ``judge_train``
# folded them
_LIVE = {}

_SIZE_KEYS = ("vocab_size", "max_position_embeddings", "hidden_size",
              "intermediate_size", "num_hidden_layers", "num_attention_heads",
              "num_key_value_heads", "head_dim", "sliding_window", "gating",
              "layer_types", "mlp_layer_types",
              "num_attention_heads_per_layer", "rope_parameters",
              "num_experts", "expert_parallel_size", "expert_parallel_rank",
              "num_experts_per_tok", "moe_intermediate_size",
              "shared_expert_intermediate_size", "moe_routed_scaling_factor",
              "norm_topk_prob", "rms_norm_eps", "router_aux_loss_coef")
_NOT_THE_MODELS = ("num_experts", "expert_parallel_size",
                   "expert_parallel_rank")


def sizes(config, rehearse):
    out = {k: config[k] for k in _SIZE_KEYS}
    if rehearse:
        out.update({k: v for k, v in config["rehearse_cpu"].items()
                    if k in _SIZE_KEYS})
    return out


def _rope_sets(s):
    """The per-layer-type parameter sets of ``rope_parameters`` alone: the
    published block also repeats ``original_max_position_embeddings``
    beside them."""
    return {k: v for k, v in s["rope_parameters"].items()
            if isinstance(v, dict)}


def traffic_shapes(config, rehearse):
    s = sizes(config, rehearse)
    return {"vocab_size": s["vocab_size"],
            "max_positions": s["max_position_embeddings"],
            "seq_scale": s["max_position_embeddings"]
            / config["max_position_embeddings"]}


def model_config(config, rehearse):
    import jax.numpy as jnp
    from deepspeed_tpu.models.laguna import LagunaConfig
    s, m = sizes(config, rehearse), common.merged(config, "model", rehearse)
    dtypes = {"bfloat16": jnp.bfloat16, "float32": jnp.float32}
    return LagunaConfig(
        **{k: s[k] for k in _SIZE_KEYS
           if k not in _NOT_THE_MODELS + ("rope_parameters",)},
        rope_parameters=_rope_sets(s),
        num_experts=s["num_experts"] * s["expert_parallel_size"],
        experts_held=s["num_experts"] if s["expert_parallel_size"] > 1 else 0,
        expert_share=s["expert_parallel_rank"],
        dtype=dtypes[m["dtype"]], param_dtype=dtypes[m["param_dtype"]],
        remat=m["remat"], remat_policy=m["remat_policy"],
        loss_chunk=m["loss_chunk"])


# ----------------------------------------------------------------- training

def _model(config, rehearse):
    from deepspeed_tpu.models.laguna import LagunaForCausalLM
    return LagunaForCausalLM(model_config(config, rehearse))


def build_train(config, global_batch, seed, devices, rehearse):
    """(engine, initial parameters): ``common.build_train``'s recipe over
    ``LagunaForCausalLM`` (a program without this model fails at ``_model``,
    before any work), the weights made from 64 example positions."""
    engine, params = common.build_train(
        _model(config, rehearse), config, global_batch, seed, devices,
        rehearse, example_len=64)
    _LIVE["engine"] = engine         # ``judge_train`` folds its gauges
    return engine, params


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
    "q": ("attn", "q_proj", "kernel"), "k": ("attn", "k_proj", "kernel"),
    "v": ("attn", "v_proj", "kernel"), "g": ("attn", "g_proj", "kernel"),
    "o": ("attn", "o_proj", "kernel")}
LAYER_LEAVES = {
    "dense": dict(
        _ATTN_LEAVES, mlp_gate=("mlp", "gate_proj", "kernel"),
        mlp_up=("mlp", "up_proj", "kernel"),
        mlp_down=("mlp", "down_proj", "kernel")),
    "sparse": dict(
        _ATTN_LEAVES, router=("mlp", "router"), gate=("mlp", "gate_proj"),
        up=("mlp", "up_proj"), down=("mlp", "down_proj"),
        shared_gate=("mlp", "shared_gate_proj"),
        shared_up=("mlp", "shared_up_proj"),
        shared_down=("mlp", "shared_down_proj"),
        shared_expert_gate=("mlp", "shared_expert_gate"))}


def _blocks(tree, config, rehearse):
    """Layer i's sub-tree of a tree laid out as the model's parameters (or
    its sown values) are, in layer order (``models/laguna.block_paths``)."""
    import jax
    from deepspeed_tpu.models.laguna import block_paths
    out = []
    for top, sub, p in block_paths(model_config(config, rehearse)):
        blk = tree[top] if sub is None else jax.tree_util.tree_map(
            lambda x: x[p], tree[top][sub])
        out.append(blk)
    return out


def reference_view(params, config, rehearse):
    """(top, layers) in the reference's layout, float32, from
    ``LagunaForCausalLM``'s tree."""
    import jax
    import jax.numpy as jnp
    params = jax.tree_util.tree_map(lambda x: x.astype(jnp.float32), params)
    top = {"embed": params["embed_tokens"], "norm": params["norm"]["scale"],
           "lm_head": params["lm_head"]}
    mlp_types = sizes(config, rehearse)["mlp_layer_types"]
    layers = [{name: _at(blk, path)
               for name, path in LAYER_LEAVES[kind].items()}
              for blk, kind in zip(_blocks(params, config, rehearse),
                                   mlp_types)]
    return top, layers


def reference_sizes(config, rehearse):
    s = sizes(config, rehearse)
    return dict(layer_types=tuple(s["layer_types"]),
                rope_parameters=tuple(sorted(
                    (k, tuple(sorted(v.items())))
                    for k, v in _rope_sets(s).items())),
                n_kv_head=s["num_key_value_heads"], head_dim=s["head_dim"],
                window=s["sliding_window"], eps=s["rms_norm_eps"],
                k=s["num_experts_per_tok"],
                routed_scale=s["moe_routed_scaling_factor"],
                expert_lo=s["num_experts"] * s["expert_parallel_rank"],
                balance_coeff=s["router_aux_loss_coef"],
                norm_topk_prob=s["norm_topk_prob"])


def _bf16_grads(config, rehearse):
    return common.merged(config, "train", rehearse)["engine"].get(
        "data_types", {}).get("grad_dtype") == "bf16"


def _engine_cast(p):
    import jax
    import jax.numpy as jnp
    return jax.tree_util.tree_map(
        lambda x: x.astype(jnp.bfloat16) if x.dtype == jnp.float32 else x, p)


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
            vs["losses"])), vs["intermediates"]

    @jax.jit
    def step(p, ids):
        if bf16:
            p = _engine_cast(p)
        (loss, got), grads = jax.value_and_grad(loss_fn, has_aux=True)(p, ids)
        return loss, got, grads

    loss, got, grads = step(jax.device_put(params, device),
                            jax.device_put(np.asarray(batch_ids), device))
    layers = [{"top_e": blk["mlp"]["top_e"][0] if "mlp" in blk else None,
               "x_mid": blk["x_mid"][0], "mixer_out": blk["mixer_out"][0],
               "ffn_out": blk["ffn_out"][0]}
              for blk in _blocks(got, config, rehearse)]
    return loss, layers, grads


def window_differences(config, params, batch_ids, device, rehearse):
    """What no mask can hide, on the SYSTEM's first sliding layer (its
    weights as the engine casts them, its input the timed batch's embedding
    rows through the layer's own norm), at the cell's sequence length:
    ``window_vs_causal_rel`` — the attention branch against the same branch
    with ``sliding_window`` no shorter than the sequence (full causal
    attention), as ``|a - b| / |b|``: a window that is not applied reads 0;
    ``window_leak_rel`` — the branch's last ``window`` positions against the
    same positions after every token more than ``window`` behind the FIRST
    of them was replaced: attention that reaches past its window reads far
    from 0, one that does not reads 0 exactly."""
    import dataclasses
    import jax
    import jax.numpy as jnp
    from deepspeed_tpu.models.laguna import LagunaAttention, rope_tables
    from deepspeed_tpu.models.llama import RMSNorm
    cfg = model_config(config, rehearse)
    i = cfg.layer_types.index(SLIDING)
    W = cfg.sliding_window
    S = np.asarray(batch_ids).shape[1]
    if S <= 2 * W:
        return {}

    @jax.jit
    def run(p, ids, other_ids):
        p = _engine_cast(p) if _bf16_grads(config, rehearse) else p
        blk = _blocks(p, config, rehearse)[i]

        def branch(c, tokens):
            x = jnp.take(p["embed_tokens"], tokens, axis=0).astype(c.dtype)
            h = RMSNorm(eps=c.rms_norm_eps, dtype=c.dtype,
                        param_dtype=c.param_dtype).apply(
                {"params": blk["input_norm"]}, x)
            return LagunaAttention(c, SLIDING, cfg.layer_kinds[i][1]).apply(
                {"params": blk["attn"]}, h,
                rope_tables(c, jnp.arange(S))).astype(jnp.float32)

        windowed = branch(cfg, ids)
        causal = branch(dataclasses.replace(cfg, sliding_window=S), ids)
        # tokens before S - 2W + 1 replaced: each of the last W positions
        # keeps its own window whole
        keep = jnp.arange(S) >= S - 2 * W + 1
        moved = branch(cfg, jnp.where(keep[None], ids, other_ids))
        tail = slice(S - W, S)
        return {"window_vs_causal_rel": _rel(windowed, causal),
                "window_leak_rel": _rel(moved[:, tail], windowed[:, tail]),
                "causal_leak_rel": _rel(
                    branch(dataclasses.replace(cfg, sliding_window=S),
                           jnp.where(keep[None], ids, other_ids))[:, tail],
                    causal[:, tail])}

    ids = np.asarray(batch_ids)
    other = (ids + 1 + np.arange(S)[None] % 7) % sizes(config, rehearse)[
        "vocab_size"]
    out = run(jax.device_put(params, device), jax.device_put(ids, device),
              jax.device_put(other.astype(ids.dtype), device))
    return {k: float(v) for k, v in out.items()}


def _mixer_key(layer_type):
    return "full_out_rel" if layer_type == FULL else "swa_out_rel"


def own_stream_differences(system, reference, kinds):
    """Of two passes that each ran on their OWN residual stream, every
    layer's [layer type, FFN type, mixer branch's relative error, FFN
    branch's, share of the T x k assignments that differ (0 for a dense
    layer)]: each holds what the layers under it left, so they are reported
    and only the first is held."""
    out = []
    for got, want, (layer_type, mlp_type) in zip(system, reference, kinds):
        routing = 0.0 if got["top_e"] is None else float(_routing_differs(
            got["top_e"], want["top_e"])) / want["top_e"].size
        out.append([layer_type, mlp_type,
                    float(_rel(got["mixer_out"], want["mixer_out"])),
                    float(_rel(got["ffn_out"], want["ffn_out"])), routing])
    return out


def branch_differences(system, reference, kinds):
    """Of a reference pass PINNED to the system's experts and residual
    stream (``families/qwen3_next.branch_differences``), the worst layer's
    of its kind: ``full_out_rel`` / ``swa_out_rel`` (the attention branch of
    a full / a sliding layer), ``dense_out_rel`` / ``ffn_out_rel`` (the
    dense / the expert FFN branch), and the routing the reference's own
    router would have chosen otherwise on the system's stream."""
    import jax.numpy as jnp
    out = {"full_out_rel": 0.0, "swa_out_rel": 0.0, "dense_out_rel": 0.0,
           "ffn_out_rel": 0.0, "routing_differs": 0,
           "routing_assignments": 0}
    by_layer = []
    for got, want, (layer_type, mlp_type) in zip(system, reference, kinds):
        mixer = _rel(got["mixer_out"], want["mixer_out"])
        ffn = _rel(got["ffn_out"], want["ffn_out"])
        key = _mixer_key(layer_type)
        out[key] = jnp.maximum(out[key], mixer)
        key = "dense_out_rel" if mlp_type == "dense" else "ffn_out_rel"
        out[key] = jnp.maximum(out[key], ffn)
        if got["top_e"] is not None:
            out["routing_differs"] += _routing_differs(got["top_e"],
                                                       want["own_top_e"])
            out["routing_assignments"] += want["own_top_e"].size
        by_layer.append([mixer, ffn])
    return dict(out, by_layer=by_layer)


def gradient_differences(system, reference, config, rehearse):
    """{leaf, by the reference's name: |system - reference| / |reference|} of
    two gradient trees in the program's layout, the worst layer's for a
    layer's leaf; an attention leaf by its layer's type (``q.full`` /
    ``q.swa``: the two have unlike shapes and unlike kernels)."""
    import jax.numpy as jnp

    def rel(a, b):
        return jnp.linalg.norm((a - b).ravel()) / jnp.linalg.norm(b.ravel())

    (top_s, layers_s), (top_r, layers_r) = (
        reference_view(g, config, rehearse) for g in (system, reference))
    out = {name: rel(top_s[name], top_r[name]) for name in top_r}
    types = sizes(config, rehearse)["layer_types"]
    for got, want, layer_type in zip(layers_s, layers_r, types):
        for name in want:
            key = name if name not in ("q", "k", "v", "g", "o") else \
                f"{name}.{'full' if layer_type == FULL else 'swa'}"
            out[key] = jnp.maximum(out.get(key, 0.0),
                                   rel(got[name], want[name]))
    return out


def _kinds(config, rehearse):
    s = sizes(config, rehearse)
    return list(zip(s["layer_types"], s["mlp_layer_types"]))


@functools.lru_cache(maxsize=None)
def _reference_program(config_key, rehearse, mode):
    """The reference as ONE jitted program over the program's weight tree
    (``families/qwen3_next._reference_program``): "forward" -> (loss,
    detail) of its own pass; "backward" -> (gradient norm, {leaf: relative
    error}, branch differences) of the reference pinned to the experts the
    system chose and to the system's residual stream."""
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
        experts = tuple(layer["top_e"] for layer in system_layers)
        streams = tuple((layer["x_mid"], layer["x_mid"] + layer["ffn_out"])
                        for layer in system_layers)
        (_, detail), g = ref.loss_and_grads(p, ids, view, experts=experts,
                                            streams=streams, **sizes_)
        return (ref.grad_norm(g),
                gradient_differences(system_grads, g, config, rehearse),
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
    weights and batch (``families/qwen3_next.compare``), and
    ``window_differences`` of the weights ``system`` ran on — the caller's
    ``params`` as the engine casts them."""
    import jax
    _, layers, grads = system
    loss, detail = _reference("forward", config, params, batch_ids, device,
                              rehearse, tuple(layers))
    diffs = {"own_stream_by_layer": own_stream_differences(
        layers, detail["layers"], _kinds(config, rehearse)),
        "stream_add_rel": float(detail["stream_add_rel"]),
        "stream_add_by_layer": [[float(v) for v in pair]
                                for pair in detail["stream_add_by_layer"]],
        "reference_ce": float(detail["ce"]),
        "reference_balance": float(detail["balance"])}
    del detail
    diffs["system_grad_norm"] = float(ref.grad_norm(
        jax.tree_util.tree_map(lambda g: g.astype("float32"), grads)))
    gnorm, leaves, branches = jax.device_get(_reference(
        "backward", config, params, batch_ids, device, rehearse,
        tuple(layers), grads))
    diffs["grad_leaf_rel"] = {n: float(v) for n, v in leaves.items()}
    diffs.update(jax.tree_util.tree_map(
        lambda v: int(v) if v.dtype.kind == "i" else float(v), branches))
    diffs.update(window_differences(config, params, batch_ids, device,
                                    rehearse))
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
    expert branch, every gradient leaf, no routed row dropped) with the
    attention branch held by layer kind, the dense branch, the two
    unpinned checks of the Qwen3-Next family and the window check."""
    tol = config["train"]["tolerance"]
    if differences is not None:
        # OLMoE's two keys: the worse attention kind, each against its own
        # limit, and the expert branch as one vector
        differences = dict(
            differences, ffn_out_row_rel=differences["ffn_out_rel"],
            attn_out_rel=max(differences["full_out_rel"] / tol["full_out_rel"],
                             differences["swa_out_rel"] / tol["swa_out_rel"]))
        config = dict(config, train=dict(config["train"], tolerance=dict(
            tol, ffn_out_row_rel=tol["ffn_out_rel"], attn_out_rel=1.0)))
    checks, detail = shared.judge_train(config, got_loss, got_gnorm,
                                        want_loss, want_gnorm, differences)
    if differences is not None:
        checks["dense_branch_matches_reference"] = \
            differences["dense_out_rel"] <= tol["dense_out_rel"]
        _, _, mixer, ffn, _ = differences["own_stream_by_layer"][0]
        # the first SPARSE layer's routing on its own stream: one dense
        # layer's drift under it
        routing = next(r[4] for r in differences["own_stream_by_layer"]
                       if r[1] == "sparse")
        first = tol["own_stream_first_layer"]
        checks["first_layer_matches_reference_on_its_own_stream"] = \
            mixer <= first["mixer_rel"] and ffn <= first["ffn_rel"] \
            and routing <= first["routing_share"]
        checks["residual_stream_adds_up"] = \
            differences["stream_add_rel"] <= tol["stream_add_rel"]
        if "window_vs_causal_rel" in differences:
            checks["window_is_applied_and_nothing_reaches_past_it"] = \
                differences["window_vs_causal_rel"] \
                >= tol["window_vs_causal_rel_min"] \
                and differences["window_leak_rel"] <= tol["window_leak_rel"] \
                and differences["causal_leak_rel"] \
                >= tol["window_vs_causal_rel_min"]
        detail["differences"]["tolerances"].update(
            {k: tol[k] for k in ("full_out_rel", "swa_out_rel",
                                 "dense_out_rel", "ffn_out_rel",
                                 "own_stream_first_layer", "stream_add_rel",
                                 "window_vs_causal_rel_min",
                                 "window_leak_rel")})
    # this family's own engine, fenced and folded here, after warm-up
    engine = _LIVE.get("engine")
    gauges = _LIVE["gauges"] = \
        engine.telemetry_flush()["gauges"] if engine is not None else {}
    if "moe/dropped_rows" in gauges:
        checks["no_routed_row_dropped"] = gauges["moe/dropped_rows"] == 0
        detail["moe_gauges"] = {k: v for k, v in gauges.items()
                                if k.startswith(("moe/", "attention/"))}
    return checks, detail


# ------------------------------------------------- operations and bytes

def _layers(config, rehearse):
    """(sizes, [(layer type, query heads, FFN type)])."""
    s = sizes(config, rehearse)
    return s, list(zip(s["layer_types"], s["num_attention_heads_per_layer"],
                       s["mlp_layer_types"]))


def rows_held_share(config, rehearse=False):
    """Share of the T x k routed rows a uniform router sends to the experts
    held here: 1 / ``expert_parallel_size``."""
    return 1.0 / sizes(config, rehearse)["expert_parallel_size"]


def active_matmul_params(config, rehearse=False):
    """Parameters one token is multiplied with HERE: each layer's attention
    projections at ITS head count and its gate, the dense MLP or (router,
    shared expert and its gate, the k experts times the share of them held
    here), and the output head (the embedding lookup is a gather)."""
    s, layers = _layers(config, rehearse)
    H, D = s["hidden_size"], s["head_dim"]
    kv = s["num_key_value_heads"] * D
    sparse = H * s["num_experts"] * s["expert_parallel_size"] \
        + 3 * H * s["shared_expert_intermediate_size"] + H \
        + s["num_experts_per_tok"] * rows_held_share(config, rehearse) \
        * 3 * H * s["moe_intermediate_size"]
    total = s["vocab_size"] * H
    for _, heads, mlp in layers:
        total += 2 * H * heads * D + 2 * H * kv \
            + (H * heads if s["gating"] else 0) \
            + (3 * H * s["intermediate_size"] if mlp == "dense" else sparse)
    return total


def _band(seq_len, window):
    """Scores a head's band holds: ``S*W - W(W-1)/2`` (all S(S+1)/2 where
    the window covers the sequence)."""
    w = min(window, seq_len)
    return seq_len * w - w * (w - 1) // 2


def swa_flops_per_step(config, batch, seq_len, rehearse=False):
    """(forward, backward) flops the sliding layers' attention NEEDS in one
    step: the band's scores a head (``_band``) x 2 head_dim a product; QK^T
    and PV forward; dV, dP, dQ, dK backward (the five products of the
    backward kernels less the recomputed QK^T)."""
    s, layers = _layers(config, rehearse)
    heads = sum(h for kind, h, _ in layers if kind == SLIDING)
    product = 2 * batch * heads * _band(seq_len, s["sliding_window"]) \
        * s["head_dim"]
    return 2 * product, 4 * product


def train_attention_flops_per_step(config, batch, seq_len, rehearse=False):
    """Causal flops of the flash forward and backward kernels in one step —
    the FULL layers', the kernels under ``flash_*`` in this family's step
    (the sliding layers' are ``swa_flops_per_step``, under ``swa_*``)."""
    s, layers = _layers(config, rehearse)
    return sum(roofline.causal_attention_train_flops(
        batch, h, seq_len, s["head_dim"])
        for kind, h, _ in layers if kind == FULL)


def train_flops_per_token(config, seq_len, rehearse=False):
    """6 a matmul parameter (2 forward, 4 backward) + attention of both
    kinds: causal in the full layers (6 S heads head_dim a layer), the
    band's in the sliding ones."""
    return 6 * active_matmul_params(config, rehearse) \
        + (train_attention_flops_per_step(config, 1, seq_len, rehearse)
           + sum(swa_flops_per_step(config, 1, seq_len, rehearse))) / seq_len


def moe_gmm_flops_per_step(config, tokens, rehearse=False):
    """Flops the grouped matmuls of one step NEED for the rows a uniform
    router holds here (``families/qwen3_next.moe_gmm_flops_per_step``), over
    the SPARSE layers."""
    s, layers = _layers(config, rehearse)
    rows = tokens * s["num_experts_per_tok"] * rows_held_share(config,
                                                               rehearse)
    return sum(mlp == "sparse" for _, _, mlp in layers) * 3 * 3 * 2 * rows \
        * s["hidden_size"] * s["moe_intermediate_size"]
