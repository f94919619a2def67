"""The Nemotron-H family: how its configuration file becomes a running
system.

The members ``benchmark/families/__init__.py`` lists for training, none of
serving's. The model is ``deepspeed_tpu.models.nemotron_h`` built through
``dstpu.initialize`` as the other cells' are; the plain reference is
``benchmark/reference/nemotron_h.py``. Key names are the published
config's; the layer plan is the pattern STRING ``hybrid_override_pattern``,
handed to the model and to the reference as the file has it.

A configuration of this family is ONE RANK'S SHARE of an expert-parallel
layout, as the Qwen3-Next family's is: ``n_routed_experts`` is the experts
held here, ``expert_parallel_size`` how many such shares the router chooses
among (the router is ``n_routed_experts x expert_parallel_size`` wide, the
published count), ``expert_parallel_rank`` which of them this is;
``vocab_size`` is the slice of the vocabulary held here. The weights are the
seed's, but for the routers' selection biases, which set-up then moves by
the balancing rule until the loads are level (``balanced_selection_bias``).

``correct`` is OLMoE's comparison (``families/olmoe.py`` says why loss and
gradient norm alone see nothing of a layer at random initialisation) for a
model of ONE branch a layer, the branch told apart by the layer's kind: the
loss of the two own forward passes; then, of a reference pass PINNED to the
system's experts and to the system's residual stream
(``reference/qwen3_next.forward`` says why), the routing (assignments the
reference's own sigmoid router with its selection bias, on the system's
stream, would have made otherwise), each kind's branch as one vector
(``ssm_out_rel``, ``attn_out_rel``, ``ffn_out_rel``) and every gradient leaf
as a vector, a leaf named by its layer's kind; that the selection bias's
gradient is exactly zero; and, because a pinned pass is blind to the stream
itself, two checks that are NOT pinned: the first Mamba-2 layer and the first
expert layer of the two own passes, and the system's residual adds; each
against the file's ``train.tolerance``.
"""

import functools

import numpy as np

from benchmark import roofline
from benchmark.families import common, olmoe as shared
from benchmark.families.common import (at as _at, rel as _rel,
                                       routing_differs as _routing_differs)
from benchmark.reference import nemotron_h as ref

WIDTH_KEYS = ("hidden_size", "intermediate_size", "moe_intermediate_size",
              "moe_shared_expert_intermediate_size", "num_attention_heads",
              "num_key_value_heads", "head_dim", "mamba_num_heads",
              "mamba_head_dim", "n_groups", "ssm_state_size", "conv_kernel",
              "expand", "num_experts_per_tok")
# ``ssd_scan`` takes every scope that starts with it (``tag_of`` matches a
# kernel tag by prefix): the kernels' ``ssd_scan_fwd`` / ``ssd_scan_bwd``, the
# re-layout round them ``ssd_scan_prep`` and the XLA form's ``ssd_scan``
KERNEL_TAGS = ("flash_fwd", "flash_bwd", "moe_gmm", "ssd_scan")
MODULE_TAGS = ("ds_loss_head", "ds_embed", "moe_router", "moe_dispatch",
               "moe_act", "moe_combine", "moe_shared", "ssm_conv",
               "ssm_gates", "ssm_norm", "mamba", "mixer", "norm", "norm_f")
DISPATCH_TAGS = shared.DISPATCH_TAGS
# every tag a path under the module ``mamba`` can take (``ssm_layer_ms``)
SSM_LAYER_TAGS = ("ssd_scan", "ssm_conv", "ssm_gates", "ssm_norm", "mamba")
MAMBA, EXPERTS, ATTENTION = "M", "E", "*"
KIND_NAMES = {MAMBA: "ssm", EXPERTS: "ffn", ATTENTION: "attn"}
# this process's engine of THIS family, and its gauges as ``judge_train``
# folded them
_LIVE = {}

_SIZE_KEYS = ("vocab_size", "max_position_embeddings", "hidden_size",
              "num_hidden_layers", "hybrid_override_pattern",
              "layer_norm_epsilon", "mamba_num_heads", "mamba_head_dim",
              "n_groups", "ssm_state_size", "conv_kernel", "chunk_size",
              "use_conv_bias", "time_step_min", "time_step_max",
              "time_step_floor", "num_attention_heads",
              "num_key_value_heads", "head_dim", "rope_theta",
              "partial_rotary_factor", "n_routed_experts",
              "expert_parallel_size", "expert_parallel_rank",
              "num_experts_per_tok", "moe_intermediate_size",
              "moe_shared_expert_intermediate_size", "n_shared_experts",
              "norm_topk_prob", "routed_scaling_factor",
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
    from deepspeed_tpu.models.nemotron_h import NemotronHConfig
    s, m = sizes(config, rehearse), common.merged(config, "model", rehearse)
    dtypes = {"bfloat16": jnp.bfloat16, "float32": jnp.float32}
    ranks = s["expert_parallel_size"]
    return NemotronHConfig(
        **{k: s[k] for k in _SIZE_KEYS if k not in _NOT_THE_MODELS},
        n_routed_experts=s["n_routed_experts"] * ranks,
        experts_held=s["n_routed_experts"] if ranks > 1 else 0,
        expert_share=s["expert_parallel_rank"],
        dtype=dtypes[m["dtype"]], param_dtype=dtypes[m["param_dtype"]],
        remat=m["remat"], remat_policy=m["remat_policy"],
        loss_chunk=m["loss_chunk"])


# ----------------------------------------------------------------- training

def _model(config, rehearse):
    from deepspeed_tpu.models.nemotron_h import NemotronHForCausalLM
    return NemotronHForCausalLM(model_config(config, rehearse))


def build_train(config, global_batch, seed, devices, rehearse):
    """(engine, initial parameters): ``common.build_train``'s recipe over
    ``NemotronHForCausalLM`` (a program without this model fails at
    ``_model``, before any work), the weights made from 64 example
    positions."""
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
    ``mixer`` of every ``layer_<i>`` whose kind is ``E``, as
    ``train.selection_bias_balance`` sets the rounds and rates."""
    s = sizes(config, rehearse)
    return common.balanced_selection_bias(
        _model(config, rehearse), params, "mixer",
        [f"layer_{i}" for i, kind
         in enumerate(s["hybrid_override_pattern"]) if kind == EXPERTS],
        common.merged(config, "train", rehearse)["selection_bias_balance"],
        global_batch, s["vocab_size"], seed)


def program_gauges():
    """The program's ``moe/*`` and ``ssm/*`` gauges of the LAST WARM-UP
    STEP, as ``judge_train`` folded them ({} before it)."""
    return _LIVE.get("gauges", {})


def lower_train_step(config, traffic, devices):
    """The cell's train step at real size, lowered over abstract state on
    ``devices`` (described chips)."""
    return common.lower_train_step(_model(config, rehearse=False), config,
                                   traffic, devices)


# what the reference calls each leaf of a layer, by the program's path
LAYER_LEAVES = {
    MAMBA: {"norm": ("norm", "scale"),
            "in_proj": ("mamba", "in_proj", "kernel"),
            "conv": ("mamba", "conv"), "conv_bias": ("mamba", "conv_bias"),
            "A_log": ("mamba", "A_log"), "dt_bias": ("mamba", "dt_bias"),
            "D": ("mamba", "D"), "ssm_norm": ("mamba", "norm"),
            "out_proj": ("mamba", "out_proj", "kernel")},
    ATTENTION: {"norm": ("norm", "scale"),
                "q": ("mixer", "q_proj", "kernel"),
                "k": ("mixer", "k_proj", "kernel"),
                "v": ("mixer", "v_proj", "kernel"),
                "o": ("mixer", "o_proj", "kernel")},
    EXPERTS: {"norm": ("norm", "scale"), "router": ("mixer", "router"),
              "bias": ("mixer", "e_score_correction_bias"),
              "up": ("mixer", "up_proj"), "down": ("mixer", "down_proj"),
              "shared_up": ("mixer", "shared_up_proj"),
              "shared_down": ("mixer", "shared_down_proj")}}


def reference_view(params, pattern):
    """(top, layers) in the reference's layout, float32, from
    ``NemotronHForCausalLM``'s tree: layer i is ``layer_<i>``."""
    import jax
    import jax.numpy as jnp
    params = jax.tree_util.tree_map(lambda x: x.astype(jnp.float32), params)
    top = {"embed": params["embed_tokens"],
           "norm": params["norm_f"]["scale"], "lm_head": params["lm_head"]}
    layers = [{name: _at(params[f"layer_{i}"], path)
               for name, path in LAYER_LEAVES[kind].items()}
              for i, kind in enumerate(pattern)]
    return top, layers


def reference_sizes(config, rehearse):
    s = sizes(config, rehearse)
    return dict(pattern=s["hybrid_override_pattern"],
                n_kv_head=s["num_key_value_heads"], head_dim=s["head_dim"],
                eps=s["layer_norm_epsilon"], heads=s["mamba_num_heads"],
                mamba_head_dim=s["mamba_head_dim"], n_groups=s["n_groups"],
                state=s["ssm_state_size"], k=s["num_experts_per_tok"],
                expert_lo=s["n_routed_experts"] * s["expert_parallel_rank"],
                routed_scale=s["routed_scaling_factor"],
                norm_topk_prob=s["norm_topk_prob"])


def system_step(config, params, batch_ids, device, rehearse):
    """(loss, per-layer intermediates, gradients) of the PROGRAM's model on
    ``batch_ids`` in one jitted program, weights cast and loss formed as the
    engine's step does (``families/olmoe.system_step``). Per layer {"x_in"
    (the residual stream the layer starts from), "branch_out"} and, for an
    expert layer, "top_e"."""
    import jax
    import jax.numpy as jnp
    model = _model(config, rehearse)
    pattern = sizes(config, rehearse)["hybrid_override_pattern"]
    bf16 = common.merged(config, "train", rehearse)["engine"].get(
        "data_types", {}).get("grad_dtype") == "bf16"

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
    layers = []
    for i, kind in enumerate(pattern):
        blk = got[f"layer_{i}"]
        row = {"x_in": blk["x_in"][0], "branch_out": blk["branch_out"][0]}
        if kind == EXPERTS:
            row["top_e"] = blk["mixer"]["top_e"][0]
        layers.append(row)
    return loss, layers, grads


def streams_after(system):
    """The residual stream AFTER every layer, from the system's values: the
    next layer's input; the last layer's input plus its branch."""
    import jax.numpy as jnp
    last = system[-1]
    return tuple(layer["x_in"] for layer in system[1:]) + (
        last["x_in"].astype(jnp.float32)
        + last["branch_out"].astype(jnp.float32),)


def stream_add_differences(system):
    """(worst layer's ``|x_next - (x_in + branch_out)| / |x_next|``, every
    layer's [that, ``|branch_out| / |x_next|``]) over the SYSTEM's own
    values, for every layer but the last (whose add only the loss sees). An
    honest run leaves the bf16 rounding of the sum; a branch lost reads its
    share of the stream (the second number)."""
    import jax.numpy as jnp
    by_layer = []
    for layer, after in zip(system[:-1], system[1:]):
        x_in, out, nxt = (t.astype(jnp.float32) for t in (
            layer["x_in"], layer["branch_out"], after["x_in"]))
        size = jnp.linalg.norm(nxt)
        by_layer.append([jnp.linalg.norm(nxt - (x_in + out)) / size,
                         jnp.linalg.norm(out) / size])
    return jnp.max(jnp.stack([err for err, _ in by_layer])), by_layer


def own_stream_differences(system, reference, pattern):
    """Of two passes that each ran on their OWN residual stream, every
    layer's [kind, branch's relative error, share of the T x k assignments
    that differ (0 where the layer has no router)]: each holds what the
    layers under it left, so they are reported and only the first layers
    held."""
    out = []
    for got, want, kind in zip(system, reference, pattern):
        routing = 0.0
        if kind == EXPERTS:
            routing = float(_routing_differs(got["top_e"], want["top_e"])) \
                / want["top_e"].size
        out.append([kind, float(_rel(got["branch_out"], want["branch_out"])),
                    routing])
    return out


def branch_differences(system, reference, pattern):
    """Of a reference pass PINNED to the system's experts and residual
    stream: the T x k assignments the reference's own router, on the
    system's stream, would have made otherwise; each kind's branch's
    relative error as one vector, the worst layer's of its kind —
    ``ssm_out_rel``, ``attn_out_rel``, ``ffn_out_rel`` — and every layer's."""
    import jax.numpy as jnp
    out = {"ssm_out_rel": 0.0, "attn_out_rel": 0.0, "ffn_out_rel": 0.0,
           "routing_differs": 0, "routing_assignments": 0}
    by_layer = []
    for got, want, kind in zip(system, reference, pattern):
        err = _rel(got["branch_out"], want["branch_out"])
        key = KIND_NAMES[kind] + "_out_rel"
        out[key] = jnp.maximum(out[key], err)
        if kind == EXPERTS:
            out["routing_differs"] += _routing_differs(got["top_e"],
                                                       want["own_top_e"])
            out["routing_assignments"] += want["own_top_e"].size
        by_layer.append(err)
    return dict(out, by_layer=by_layer)


def gradient_differences(system, reference, pattern):
    """{leaf, ``<kind>.<the reference's name>``: |system - reference| /
    |reference|} of two gradient trees in the program's layout, the worst
    layer's of its kind; the selection bias (whose gradient is exactly zero
    on both sides) is left to ``bias_grad_abs``: the largest magnitude of
    the SYSTEM's gradient of it."""
    import jax.numpy as jnp

    def rel(a, b):
        return jnp.linalg.norm((a - b).ravel()) / jnp.linalg.norm(b.ravel())

    (top_s, layers_s), (top_r, layers_r) = (
        reference_view(g, pattern) for g in (system, reference))
    out = {name: rel(top_s[name], top_r[name]) for name in top_r}
    bias = jnp.zeros((), jnp.float32)
    for got, want, kind in zip(layers_s, layers_r, pattern):
        for name in want:
            if name == "bias":
                bias = jnp.maximum(bias, jnp.max(jnp.abs(got[name])))
                continue
            key = f"{KIND_NAMES[kind]}.{name}"
            out[key] = jnp.maximum(out.get(key, 0.0),
                                   rel(got[name], want[name]))
    return out, bias


@functools.lru_cache(maxsize=None)
def _reference_program(mode, sizes_items):
    """The reference as ONE jitted program over the program's weight tree
    (``families/olmoe._reference_program``): "forward" -> (loss, detail) of
    its own pass; "backward" -> (gradient norm, {leaf: relative error}, the
    bias's gradient, branch differences) of the reference pinned to the
    experts the system chose and to the system's residual stream."""
    import jax
    sizes_ = dict(sizes_items)
    pattern = sizes_["pattern"]

    def view(w):
        return reference_view(w, pattern)

    def chosen_of(system_layers):
        return tuple(layer.get("top_e") for layer in system_layers)

    @jax.jit
    def forward(p, ids, system_layers):
        loss, detail = ref.loss(p, ids, view, **sizes_)
        worst, adds = stream_add_differences(system_layers)
        return loss, dict(detail, stream_add_rel=worst,
                          stream_add_by_layer=adds)

    @jax.jit
    def backward(p, ids, system_layers, system_grads):
        (_, detail), g = ref.loss_and_grads(
            p, ids, view, chosen=chosen_of(system_layers),
            streams=streams_after(system_layers), **sizes_)
        leaves, bias = gradient_differences(system_grads, g, pattern)
        return (ref.grad_norm(g), leaves, bias,
                branch_differences(system_layers, detail["layers"], pattern))

    return {"forward": forward, "backward": backward}[mode]


def _reference(mode, config, params, batch_ids, device, rehearse, *more):
    import jax
    run = _reference_program(
        mode, tuple(sorted(reference_sizes(config, rehearse).items())))
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
    pattern = sizes(config, rehearse)["hybrid_override_pattern"]
    _, layers, grads = system
    loss, detail = _reference("forward", config, params, batch_ids, device,
                              rehearse, tuple(layers))
    diffs = {"own_stream_by_layer": own_stream_differences(
        layers, detail["layers"], pattern),
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
    dropped) and the Mamba-2 branch, the bias's zero gradient, and the two
    unpinned checks. The expert branch is held as one vector
    (``ffn_out_rel``) where OLMoE's is held by its worst row: handed over
    under OLMoE's key."""
    tol = config["train"]["tolerance"]
    if differences is not None:
        differences = dict(differences,
                           ffn_out_row_rel=differences["ffn_out_rel"])
        config = dict(config, train=dict(config["train"], tolerance=dict(
            tol, ffn_out_row_rel=tol["ffn_out_rel"])))
    checks, detail = shared.judge_train(config, got_loss, got_gnorm,
                                        want_loss, want_gnorm, differences)
    # OLMoE's judge folded ITS family's engine (none here)
    checks.pop("no_routed_row_dropped", None)
    if differences is not None:
        checks["state_space_branch_matches_reference"] = \
            differences["ssm_out_rel"] <= tol["ssm_out_rel"]
        checks["selection_bias_takes_no_gradient"] = \
            differences["bias_grad_abs"] == 0.0
        # not pinned: the first Mamba-2 layer and the first expert layer of
        # the two own passes, and the system's residual adds
        own, first = differences["own_stream_by_layer"], \
            tol["own_stream_first_layers"]
        ssm = next(row for row in own if row[0] == MAMBA)
        ffn = next(row for row in own if row[0] == EXPERTS)
        checks["first_layers_match_reference_on_their_own_stream"] = \
            ssm[1] <= first["ssm_rel"] and ffn[1] <= first["ffn_rel"] \
            and ffn[2] <= first["routing_share"]
        checks["residual_stream_adds_up"] = \
            differences["stream_add_rel"] <= tol["stream_add_rel"]
        detail["differences"]["tolerances"].update(
            {k: tol[k] for k in ("ssm_out_rel", "ffn_out_rel",
                                 "own_stream_first_layers",
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
                                if k.startswith(("moe/", "ssm/"))}
    return checks, detail


# ------------------------------------------------- operations and bytes

def _layer_counts(config, rehearse):
    s = sizes(config, rehearse)
    pattern = s["hybrid_override_pattern"]
    return s, {kind: pattern.count(kind)
               for kind in (MAMBA, EXPERTS, ATTENTION)}


def rows_held_share(config, rehearse=False):
    """Share of the T x k routed rows a uniform router sends to the experts
    held here: 1 / ``expert_parallel_size``."""
    return 1.0 / sizes(config, rehearse)["expert_parallel_size"]


def active_matmul_params(config, rehearse=False):
    """Parameters one token is multiplied with HERE: a Mamba-2 layer's two
    projections, an attention layer's four, an expert layer's router, its
    shared expert and the k experts times the share of them held here, and
    the output head (the embedding lookup is a gather; the convolution's
    taps, the gates and the scan are not matmul parameters)."""
    s, n = _layer_counts(config, rehearse)
    H = s["hidden_size"]
    d_inner = s["mamba_num_heads"] * s["mamba_head_dim"]
    conv_dim = d_inner + 2 * s["n_groups"] * s["ssm_state_size"]
    mamba = H * (d_inner + conv_dim + s["mamba_num_heads"]) + d_inner * H
    attention = 2 * H * s["num_attention_heads"] * s["head_dim"] \
        + 2 * H * s["num_key_value_heads"] * s["head_dim"]
    experts = H * s["n_routed_experts"] * s["expert_parallel_size"] \
        + s["n_shared_experts"] * 2 * H \
        * s["moe_shared_expert_intermediate_size"] \
        + s["num_experts_per_tok"] * rows_held_share(config, rehearse) \
        * 2 * H * s["moe_intermediate_size"]
    return n[MAMBA] * mamba + n[ATTENTION] * attention \
        + n[EXPERTS] * experts + s["vocab_size"] * H


def _scan_flops_per_token(s):
    """The RECURRENCE's flops a token a layer, forward: a head's state is
    [P, N]; the decay (P N multiplies), the outer product ``dt x (x) B``
    added in (2 P N) and the read-out ``h C`` (2 P N): 5 P N a head."""
    return 5 * s["mamba_head_dim"] * s["ssm_state_size"] \
        * s["mamba_num_heads"]


def train_flops_per_token(config, seq_len, rehearse=False):
    """6 a matmul parameter (2 forward, 4 backward) + causal attention in
    the attention layers alone (6 S heads head_dim a layer) + the
    state-space recurrence in the Mamba-2 layers (3 x 5 P N a head)."""
    s, n = _layer_counts(config, rehearse)
    return 6 * active_matmul_params(config, rehearse) \
        + 6 * n[ATTENTION] * seq_len * s["num_attention_heads"] \
        * s["head_dim"] + n[MAMBA] * 3 * _scan_flops_per_token(s)


def train_attention_flops_per_step(config, batch, seq_len, rehearse=False):
    """Causal flops of the flash forward and backward kernels in one step:
    the attention layers the pattern has."""
    s, n = _layer_counts(config, rehearse)
    return n[ATTENTION] * roofline.causal_attention_train_flops(
        batch, s["num_attention_heads"], seq_len, s["head_dim"])


def moe_gmm_flops_per_step(config, tokens, rehearse=False):
    """Flops the grouped matmuls of one step NEED for the rows held here:
    three products (forward, dlhs, drhs) of up and down — an expert is two
    matrices — every expert layer, at the PUBLISHED expert width (1,856; the
    kernels run at 1,920 lanes, and the padding is time and no counted
    work). The rows are the share the PROGRAM counted at the last warm-up
    step (the gauge ``moe/rows_held_share``, ``program_gauges``) where a
    run has folded it, the uniform router's 1 / ``expert_parallel_size``
    before (``families/smallthinker.moe_gmm_flops_per_step`` says why)."""
    s, n = _layer_counts(config, rehearse)
    share = program_gauges().get("moe/rows_held_share") \
        or rows_held_share(config, rehearse)
    rows = tokens * s["num_experts_per_tok"] * share
    return n[EXPERTS] * 3 * 2 * 2 * rows * s["hidden_size"] \
        * s["moe_intermediate_size"]


def ssd_scan_flops_and_bytes(config, tokens, rehearse=False, itemsize=2):
    """(flops, bytes) the state-space scan of one step NEEDS over all
    Mamba-2 layers for ``tokens`` tokens — the RECURRENCE's work, whatever
    implements it. Flops: 5 P N a token a head forward
    (``_scan_flops_per_token``: decay, outer product, read-out), x 3 with
    the backward pass. Bytes: x and y [heads x P] and B, C [groups x N] at
    ``itemsize`` and dt (float32, a head) once forward; their five
    cotangents once; and x, B, C, dt read once more by the backward pass —
    nothing a chunking recomputes, forms inside a chunk or keeps between
    chunks."""
    s, n = _layer_counts(config, rehearse)
    x = itemsize * s["mamba_num_heads"] * s["mamba_head_dim"]
    bc = 2 * itemsize * s["n_groups"] * s["ssm_state_size"]
    dt = 4 * s["mamba_num_heads"]
    inputs, out = x + bc + dt, x
    return (n[MAMBA] * tokens * 3 * _scan_flops_per_token(s),
            n[MAMBA] * tokens * (3 * inputs + 2 * out))
