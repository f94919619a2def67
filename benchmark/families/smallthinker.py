"""The SmallThinker family: how its configuration file becomes a running
system.

The members ``benchmark/families/__init__.py`` lists for training, none of
serving's. The model is ``deepspeed_tpu.models.smallthinker`` built through
``dstpu.initialize`` as the other cells' are; the plain reference is
``benchmark/reference/smallthinker.py``. Key names are the published
config's; the two per-layer lists (``sliding_window_layout``,
``rope_layout``) are handed to the model and to the reference as the file
has them.

A configuration of this family is ONE RANK'S SHARE of an expert-parallel
layout, as the Laguna family's is (``families/laguna.py``):
``moe_num_primary_experts`` is the experts held here,
``expert_parallel_size`` how many such shares the router chooses among,
``expert_parallel_rank`` which of them this is; ``vocab_size`` is the slice
of the vocabulary held here.

``correct`` is the Laguna family's comparison without a dense layer: the
loss of the two own forward passes; the routing (read from the block's
INPUT), each branch — ``full_out_rel`` (full layers: no position encoding),
``swa_out_rel`` (sliding layers: RoPE and the window), ``ffn_out_rel`` (the
ReGLU experts' partial sum) — and every gradient leaf as a vector of the
reference's pass PINNED to the system's experts and residual stream; the
first layer of the two own passes and the system's residual adds, not
pinned; and ``families/laguna.window_differences`` on this model's first
sliding layer (the same attention module: ``as_laguna`` hands it the
configuration under Laguna's key names).
"""

import functools

import numpy as np

from benchmark import roofline
from benchmark.families import common, laguna, olmoe as shared
from benchmark.families.common import at as _at
from benchmark.families.qwen3_next import stream_add_differences
from benchmark.reference import smallthinker as ref

WIDTH_KEYS = ("hidden_size", "moe_ffn_hidden_size", "num_attention_heads",
              "num_key_value_heads", "head_dim",
              "moe_num_active_primary_experts", "sliding_window_size")
KERNEL_TAGS = laguna.KERNEL_TAGS
MODULE_TAGS = ("ds_loss_head", "ds_embed", "moe_router", "moe_dispatch",
               "moe_act", "moe_combine", "attn", "mlp", "input_norm",
               "post_attn_norm", "norm")
DISPATCH_TAGS = shared.DISPATCH_TAGS
FULL, SLIDING = laguna.FULL, laguna.SLIDING
# this process's engine of THIS family, and its gauges as ``judge_train``
# folded them
_LIVE = {}

_SIZE_KEYS = ("vocab_size", "max_position_embeddings", "hidden_size",
              "num_hidden_layers", "num_attention_heads",
              "num_key_value_heads", "head_dim", "sliding_window_size",
              "rope_theta", "sliding_window_layout", "rope_layout",
              "moe_num_primary_experts", "expert_parallel_size",
              "expert_parallel_rank", "moe_num_active_primary_experts",
              "moe_ffn_hidden_size", "moe_primary_router_apply_softmax",
              "norm_topk_prob", "rms_norm_eps", "router_aux_loss_coef")
_NOT_THE_MODELS = ("moe_num_primary_experts", "expert_parallel_size",
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
    from deepspeed_tpu.models.smallthinker import SmallThinkerConfig
    s, m = sizes(config, rehearse), common.merged(config, "model", rehearse)
    dtypes = {"bfloat16": jnp.bfloat16, "float32": jnp.float32}
    ranks = s["expert_parallel_size"]
    return SmallThinkerConfig(
        **{k: s[k] for k in _SIZE_KEYS if k not in _NOT_THE_MODELS},
        moe_num_primary_experts=s["moe_num_primary_experts"] * ranks,
        experts_held=s["moe_num_primary_experts"] if ranks > 1 else 0,
        expert_share=s["expert_parallel_rank"],
        dtype=dtypes[m["dtype"]], param_dtype=dtypes[m["param_dtype"]],
        remat=m["remat"], remat_policy=m["remat_policy"],
        loss_chunk=m["loss_chunk"])


def _layer_types(s):
    return [SLIDING if w else FULL for w in s["sliding_window_layout"]]


def as_laguna(config, rehearse):
    """The configuration under the Laguna family's key names, its sizes
    already the rehearsal's where ``rehearse``: what
    ``families/laguna.window_differences`` needs to find this model's first
    sliding layer — the same ``LagunaAttention`` with no gate, under the
    same parameter paths (``layers/l<j>/{input_norm, attn}``)."""
    s = sizes(config, rehearse)
    L = s["num_hidden_layers"]
    plain = {"rope_type": "default", "rope_theta": s["rope_theta"],
             "partial_rotary_factor": 1}
    out = {k: s[k] for k in (
        "vocab_size", "max_position_embeddings", "hidden_size",
        "num_hidden_layers", "num_attention_heads", "num_key_value_heads",
        "head_dim", "expert_parallel_size", "expert_parallel_rank",
        "norm_topk_prob", "rms_norm_eps", "router_aux_loss_coef")}
    out.update(
        intermediate_size=s["moe_ffn_hidden_size"],
        sliding_window=s["sliding_window_size"], gating=False,
        layer_types=_layer_types(s), mlp_layer_types=["sparse"] * L,
        num_attention_heads_per_layer=[s["num_attention_heads"]] * L,
        # only the sliding layer's set is read
        rope_parameters={FULL: plain, SLIDING: plain},
        num_experts=s["moe_num_primary_experts"],
        num_experts_per_tok=s["moe_num_active_primary_experts"],
        moe_intermediate_size=s["moe_ffn_hidden_size"],
        shared_expert_intermediate_size=0, moe_routed_scaling_factor=1.0,
        model=common.merged(config, "model", rehearse),
        train=common.merged(config, "train", rehearse))
    return out


# ----------------------------------------------------------------- training

def _model(config, rehearse):
    from deepspeed_tpu.models.smallthinker import SmallThinkerForCausalLM
    return SmallThinkerForCausalLM(model_config(config, rehearse))


def build_train(config, global_batch, seed, devices, rehearse):
    """(engine, initial parameters): ``common.build_train``'s recipe over
    ``SmallThinkerForCausalLM`` (a program without this model fails at
    ``_model``, before any work), the weights made from 64 example
    positions."""
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
LAYER_LEAVES = {
    "input_norm": ("input_norm", "scale"),
    "post_attn_norm": ("post_attn_norm", "scale"),
    "q": ("attn", "q_proj", "kernel"), "k": ("attn", "k_proj", "kernel"),
    "v": ("attn", "v_proj", "kernel"), "o": ("attn", "o_proj", "kernel"),
    "router": ("mlp", "router"), "gate": ("mlp", "gate_proj"),
    "up": ("mlp", "up_proj"), "down": ("mlp", "down_proj")}


def _blocks(tree, config, rehearse):
    """Layer i's sub-tree of a tree laid out as the model's parameters (or
    its sown values) are, in layer order
    (``models/smallthinker.block_paths``)."""
    import jax
    from deepspeed_tpu.models.smallthinker import block_paths
    out = []
    for top, sub, p in block_paths(model_config(config, rehearse)):
        blk = tree[top] if sub is None else jax.tree_util.tree_map(
            lambda x: x[p], tree[top][sub])
        out.append(blk)
    return out


def reference_view(params, config, rehearse):
    """(top, layers) in the reference's layout, float32, from
    ``SmallThinkerForCausalLM``'s tree."""
    import jax
    import jax.numpy as jnp
    params = jax.tree_util.tree_map(lambda x: x.astype(jnp.float32), params)
    top = {"embed": params["embed_tokens"], "norm": params["norm"]["scale"],
           "lm_head": params["lm_head"]}
    layers = [{name: _at(blk, path) for name, path in LAYER_LEAVES.items()}
              for blk in _blocks(params, config, rehearse)]
    return top, layers


def reference_sizes(config, rehearse):
    s = sizes(config, rehearse)
    return dict(sliding_window_layout=tuple(s["sliding_window_layout"]),
                rope_layout=tuple(s["rope_layout"]),
                n_kv_head=s["num_key_value_heads"], head_dim=s["head_dim"],
                window=s["sliding_window_size"], theta=float(s["rope_theta"]),
                eps=s["rms_norm_eps"], k=s["moe_num_active_primary_experts"],
                expert_lo=s["moe_num_primary_experts"]
                * s["expert_parallel_rank"],
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
    {"top_e", "x_mid" (the residual stream after the mixer), "mixer_out",
    "ffn_out"}."""
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
    layers = [{"top_e": blk["mlp"]["top_e"][0], "x_mid": blk["x_mid"][0],
               "mixer_out": blk["mixer_out"][0], "ffn_out": blk["ffn_out"][0]}
              for blk in _blocks(got, config, rehearse)]
    return loss, layers, grads


def window_differences(config, params, batch_ids, device, rehearse):
    """``families/laguna.window_differences`` on this model's first sliding
    layer at this configuration's window."""
    return laguna.window_differences(as_laguna(config, rehearse), params,
                                     batch_ids, device, False)


def _kinds(config, rehearse):
    """[(layer type, "sparse")]: what the Laguna family's per-layer
    comparisons are told of a layer."""
    return [(t, "sparse") for t in _layer_types(sizes(config, rehearse))]


def gradient_differences(system, reference, config, rehearse):
    """{leaf, by the reference's name: |system - reference| / |reference|} of
    two gradient trees in the program's layout, the worst layer's for a
    layer's leaf; an attention leaf and the first norm by the layer's type
    (``q.full`` / ``q.swa``: unlike kernels, and a rotation or none)."""
    import jax.numpy as jnp

    def rel(a, b):
        return jnp.linalg.norm((a - b).ravel()) / jnp.linalg.norm(b.ravel())

    (top_s, layers_s), (top_r, layers_r) = (
        reference_view(g, config, rehearse) for g in (system, reference))
    out = {name: rel(top_s[name], top_r[name]) for name in top_r}
    types = _layer_types(sizes(config, rehearse))
    for got, want, layer_type in zip(layers_s, layers_r, types):
        for name in want:
            key = name if name not in ("q", "k", "v", "o") else \
                f"{name}.{'full' if layer_type == FULL else 'swa'}"
            out[key] = jnp.maximum(out.get(key, 0.0),
                                   rel(got[name], want[name]))
    return out


@functools.lru_cache(maxsize=None)
def _reference_program(config_key, rehearse, mode):
    """The reference as ONE jitted program over the program's weight tree
    (``families/laguna._reference_program``'s shape): "forward" -> (loss,
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
                laguna.branch_differences(system_layers, detail["layers"],
                                          kinds))

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
    weights and batch (``families/laguna.compare``'s shape), and
    ``window_differences`` of the weights ``system`` ran on."""
    import jax
    _, layers, grads = system
    loss, detail = _reference("forward", config, params, batch_ids, device,
                              rehearse, tuple(layers))
    diffs = {"own_stream_by_layer": laguna.own_stream_differences(
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
    del diffs["dense_out_rel"]       # no dense layer: the reading is empty
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
    attention branch held by layer kind, the two unpinned checks of the
    Qwen3-Next family and Laguna's window check."""
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
    # OLMoE's judge folded ITS family's engine (none here); this family's
    # own gauges come below
    checks.pop("no_routed_row_dropped", None)
    if differences is not None:
        _, _, mixer, ffn, routing = differences["own_stream_by_layer"][0]
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
            {k: tol[k] for k in ("full_out_rel", "swa_out_rel", "ffn_out_rel",
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

def rows_held_share(config, rehearse=False):
    """Share of the T x k routed rows a uniform router sends to the experts
    held here: 1 / ``expert_parallel_size``."""
    return 1.0 / sizes(config, rehearse)["expert_parallel_size"]


def active_matmul_params(config, rehearse=False):
    """Parameters one token is multiplied with HERE: each layer's attention
    projections, its router, the k experts times the share of them held
    here, and the output head (the embedding lookup is a gather)."""
    s = sizes(config, rehearse)
    H, D = s["hidden_size"], s["head_dim"]
    layer = 2 * H * s["num_attention_heads"] * D \
        + 2 * H * s["num_key_value_heads"] * D \
        + H * s["moe_num_primary_experts"] * s["expert_parallel_size"] \
        + s["moe_num_active_primary_experts"] \
        * rows_held_share(config, rehearse) \
        * 3 * H * s["moe_ffn_hidden_size"]
    return s["vocab_size"] * H + s["num_hidden_layers"] * layer


def _band(seq_len, window):
    """Scores a head's band holds: ``S*W - W(W-1)/2`` (all S(S+1)/2 where
    the window covers the sequence)."""
    w = min(window, seq_len)
    return seq_len * w - w * (w - 1) // 2


def swa_flops_per_step(config, batch, seq_len, rehearse=False):
    """(forward, backward) flops the sliding layers' attention NEEDS in one
    step (``families/laguna.swa_flops_per_step``'s count): the band's scores
    a head x 2 head_dim a product; QK^T and PV forward; dV, dP, dQ, dK
    backward."""
    s = sizes(config, rehearse)
    heads = sum(s["sliding_window_layout"]) * s["num_attention_heads"]
    product = 2 * batch * heads * _band(seq_len, s["sliding_window_size"]) \
        * s["head_dim"]
    return 2 * product, 4 * product


def train_attention_flops_per_step(config, batch, seq_len, rehearse=False):
    """Causal flops of the flash forward and backward kernels in one step —
    the FULL layers', the kernels under ``flash_*`` in this family's step
    (the sliding layers' are ``swa_flops_per_step``, under ``swa_*``)."""
    s = sizes(config, rehearse)
    full = len(s["sliding_window_layout"]) - sum(s["sliding_window_layout"])
    return full * roofline.causal_attention_train_flops(
        batch, s["num_attention_heads"], seq_len, s["head_dim"])


def train_flops_per_token(config, seq_len, rehearse=False):
    """6 a matmul parameter (2 forward, 4 backward) + attention of both
    kinds: causal in the full layers, the band's in the sliding ones."""
    return 6 * active_matmul_params(config, rehearse) \
        + (train_attention_flops_per_step(config, 1, seq_len, rehearse)
           + sum(swa_flops_per_step(config, 1, seq_len, rehearse))) / seq_len


def moe_gmm_flops_per_step(config, tokens, rehearse=False):
    """Flops the grouped matmuls of one step NEED for the rows held here:
    three products (forward, dlhs, drhs) of gate, up and down, every layer.
    The rows are the share the PROGRAM counted at the last warm-up step
    (the gauge ``moe/rows_held_share``, ``program_gauges``) where a run has
    folded it, the uniform router's 1 / ``expert_parallel_size`` before: a
    seed's routing holds 21-30 % here, the kernels stop at the rows they are
    given, and a count of the EXPECTED quarter over the time of fewer rows
    read 96 % where the kernels reach 81 (PERF.md Findings PR 38)."""
    s = sizes(config, rehearse)
    share = program_gauges().get("moe/rows_held_share") \
        or rows_held_share(config, rehearse)
    rows = tokens * s["moe_num_active_primary_experts"] * share
    return s["num_hidden_layers"] * 3 * 3 * 2 * rows * s["hidden_size"] \
        * s["moe_ffn_hidden_size"]
