"""The Qwen3-Next family: how its configuration file becomes a running system.

The members ``benchmark/families/__init__.py`` lists for training, none of
serving's. The model is ``deepspeed_tpu.models.qwen3_next`` built through
``dstpu.initialize`` as the other cells' are; the plain reference is
``benchmark/reference/qwen3_next.py``. Key names are the published config's.

A configuration of this family is ONE RANK'S SHARE of an expert-parallel
layout: ``num_experts`` is the experts held here, ``expert_parallel_size``
how many such shares the router chooses among (the router is ``num_experts x
expert_parallel_size`` wide, the published count) and
``expert_parallel_rank`` which of them this is; ``vocab_size`` is the slice
of the vocabulary held here. Program and reference compute the same share.

``correct`` is OLMoE's comparison (``families/olmoe.py`` says why loss and
gradient norm alone see nothing of a layer at random initialisation) with
the mixer branch told apart by layer kind, and with the reference's second
pass pinned to the system's residual stream as well as to its experts
(``reference/qwen3_next.forward`` says why): the loss of the two own
forward passes; the routing, the DeltaNet branch, the attention branch, the
expert branch and every gradient leaf as a vector of the pinned pass; and,
because a pinned pass is blind to the stream itself, two checks that are NOT
pinned: the FIRST layer of the two own passes (mixer, expert branch,
routing: both sides start from the same embedding rows, so nothing has
drifted yet) and the system's residual adds (``stream_add_rel``); each
against the file's ``train.tolerance``.
"""

import functools

import numpy as np

from benchmark import roofline
from benchmark.families import common, olmoe as shared
from benchmark.families.common import (at as _at, rel as _rel,
                                       routing_differs as _routing_differs)
from benchmark.reference import qwen3_next as ref

WIDTH_KEYS = ("hidden_size", "intermediate_size", "moe_intermediate_size",
              "shared_expert_intermediate_size", "num_attention_heads",
              "num_key_value_heads", "head_dim", "linear_num_key_heads",
              "linear_key_head_dim", "linear_num_value_heads",
              "linear_value_head_dim", "linear_conv_kernel_dim",
              "num_experts_per_tok")
# ``gdn_scan`` takes every scope that starts with it (``tag_of`` matches a
# kernel tag by prefix, a module tag whole): listed as a kernel tag so that a
# later Pallas kernel under ``gdn_scan_fwd`` / ``gdn_scan_bwd`` keeps the tag
KERNEL_TAGS = ("flash_fwd", "flash_bwd", "moe_gmm", "gdn_scan")
MODULE_TAGS = ("ds_loss_head", "ds_embed", "moe_router", "moe_dispatch",
               "moe_act", "moe_combine", "moe_shared", "gdn_conv",
               "gdn_gates", "gdn_out_norm", "attn_gate", "qk_norm",
               "linear_attn", "attn", "mlp", "input_norm", "post_attn_norm",
               "norm")
DISPATCH_TAGS = shared.DISPATCH_TAGS
# every tag a path under the module ``linear_attn`` can take
# (``gdn_layer_ms``)
GDN_LAYER_TAGS = ("gdn_scan", "gdn_conv", "gdn_gates", "gdn_out_norm",
                  "linear_attn")
# this process's engine of THIS family, and its gauges as ``judge_train``
# folded them (OLMoE's slot is OLMoE's: its readers must find nothing of a
# layer that holds a share)
_LIVE = {}

_SIZE_KEYS = ("vocab_size", "max_position_embeddings", "hidden_size",
              "num_hidden_layers", "full_attention_interval",
              "num_attention_heads", "num_key_value_heads", "head_dim",
              "partial_rotary_factor", "rope_theta", "linear_num_key_heads",
              "linear_key_head_dim", "linear_num_value_heads",
              "linear_value_head_dim", "linear_conv_kernel_dim",
              "num_experts", "expert_parallel_size", "expert_parallel_rank",
              "num_experts_per_tok", "moe_intermediate_size",
              "shared_expert_intermediate_size", "norm_topk_prob",
              "rms_norm_eps", "router_aux_loss_coef")


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
    from deepspeed_tpu.models.qwen3_next import Qwen3NextConfig
    s, m = sizes(config, rehearse), common.merged(config, "model", rehearse)
    dtypes = {"bfloat16": jnp.bfloat16, "float32": jnp.float32}
    own = {k: s[k] for k in _SIZE_KEYS if k not in (
        "num_experts", "expert_parallel_size", "expert_parallel_rank",
        "rope_theta")}
    return Qwen3NextConfig(
        **own, rope_theta=float(s["rope_theta"]),
        num_experts=s["num_experts"] * s["expert_parallel_size"],
        experts_held=s["num_experts"] if s["expert_parallel_size"] > 1 else 0,
        expert_share=s["expert_parallel_rank"],
        dtype=dtypes[m["dtype"]], param_dtype=dtypes[m["param_dtype"]],
        remat=m["remat"], remat_policy=m["remat_policy"],
        loss_chunk=m["loss_chunk"])


# ----------------------------------------------------------------- training

def _model(config, rehearse):
    from deepspeed_tpu.models.qwen3_next import Qwen3NextForCausalLM
    return Qwen3NextForCausalLM(model_config(config, rehearse))


def build_train(config, global_batch, seed, devices, rehearse):
    """(engine, initial parameters): ``common.build_train``'s recipe over
    ``Qwen3NextForCausalLM`` (a program without this model fails at
    ``_model``, before any work), the weights made from 64 example
    positions."""
    engine, params = common.build_train(
        _model(config, rehearse), config, global_batch, seed, devices,
        rehearse, example_len=64)
    _LIVE["engine"] = engine         # ``judge_train`` folds its gauges
    return engine, params


def program_gauges():
    """The program's ``moe/*`` gauges of the LAST WARM-UP STEP, as
    ``judge_train`` folded them ({} before it)."""
    return _LIVE.get("gauges", {})


def lower_train_step(config, traffic, devices):
    """The cell's train step at real size, lowered over abstract state on
    ``devices`` (described chips)."""
    return common.lower_train_step(_model(config, rehearse=False), config,
                                   traffic, devices)


# what the reference calls each leaf of a layer, by the program's path
_MOE_LEAVES = {
    "input_norm": ("input_norm", "scale"),
    "post_attn_norm": ("post_attn_norm", "scale"),
    "router": ("mlp", "router"), "gate": ("mlp", "gate_proj"),
    "up": ("mlp", "up_proj"), "down": ("mlp", "down_proj"),
    "shared_gate": ("mlp", "shared_gate_proj"),
    "shared_up": ("mlp", "shared_up_proj"),
    "shared_down": ("mlp", "shared_down_proj"),
    "shared_expert_gate": ("mlp", "shared_expert_gate")}
LAYER_LEAVES = {
    "linear": dict(
        _MOE_LEAVES,
        in_qkvz=("linear_attn", "in_proj_qkvz", "kernel"),
        in_ba=("linear_attn", "in_proj_ba", "kernel"),
        conv=("linear_attn", "conv"), A_log=("linear_attn", "A_log"),
        dt_bias=("linear_attn", "dt_bias"), gdn_norm=("linear_attn", "norm"),
        out=("linear_attn", "out_proj", "kernel")),
    "attention": dict(
        _MOE_LEAVES,
        q=("attn", "q_proj", "kernel"), k=("attn", "k_proj", "kernel"),
        v=("attn", "v_proj", "kernel"), o=("attn", "o_proj", "kernel"),
        q_norm=("attn", "q_norm", "scale"),
        k_norm=("attn", "k_norm", "scale"))}


def layer_kinds(n_layers, interval):
    """The reference's own reading of ``full_attention_interval``."""
    return ["attention" if (i + 1) % interval == 0 else "linear"
            for i in range(n_layers)]


def reference_view(params, n_layers, interval):
    """(top, layers) in the reference's layout, float32, from
    ``Qwen3NextForCausalLM``'s tree: layer i is slice i // interval of the
    leaves under ``layers/l<i % interval>``."""
    import jax
    import jax.numpy as jnp
    params = jax.tree_util.tree_map(lambda x: x.astype(jnp.float32), params)
    top = {"embed": params["embed_tokens"], "norm": params["norm"]["scale"],
           "lm_head": params["lm_head"]}
    layers = []
    for i, kind in enumerate(layer_kinds(n_layers, interval)):
        blk = jax.tree_util.tree_map(lambda x: x[i // interval],
                                     params["layers"][f"l{i % interval}"])
        layers.append({name: _at(blk, path)
                       for name, path in LAYER_LEAVES[kind].items()})
    return top, layers


def reference_sizes(config, rehearse):
    s = sizes(config, rehearse)
    return dict(n_head=s["num_attention_heads"],
                n_kv_head=s["num_key_value_heads"], head_dim=s["head_dim"],
                rotary_dim=int(s["head_dim"] * s["partial_rotary_factor"]),
                theta=float(s["rope_theta"]), eps=s["rms_norm_eps"],
                hk=s["linear_num_key_heads"], dk=s["linear_key_head_dim"],
                hv=s["linear_num_value_heads"],
                dv=s["linear_value_head_dim"], k=s["num_experts_per_tok"],
                expert_lo=s["num_experts"] * s["expert_parallel_rank"],
                balance_coeff=s["router_aux_loss_coef"],
                norm_topk_prob=s["norm_topk_prob"])


def system_step(config, params, batch_ids, device, rehearse):
    """(loss, per-layer intermediates, gradients) of the PROGRAM's model on
    ``batch_ids`` in one jitted program, weights cast and loss formed as the
    engine's step does (``families/olmoe.system_step``). Per layer
    {"top_e", "x_mid" (the residual stream after the mixer), "mixer_out",
    "ffn_out"}."""
    import jax
    import jax.numpy as jnp
    model = _model(config, rehearse)
    s = sizes(config, rehearse)
    bf16 = common.merged(config, "train", rehearse)["engine"].get(
        "data_types", {}).get("grad_dtype") == "bf16"

    def loss_fn(p, ids):
        out, vs = model.apply({"params": p}, ids, labels=ids,
                              mutable=["losses", "intermediates"])
        return out + sum(jnp.sum(x) for x in jax.tree_util.tree_leaves(
            vs["losses"])), vs["intermediates"]

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
    interval = s["full_attention_interval"]
    layers = []
    for i in range(s["num_hidden_layers"]):
        blk = got["layers"][f"l{i % interval}"]
        layers.append({"top_e": blk["mlp"]["top_e"][0][i // interval],
                       "x_mid": blk["x_mid"][0][i // interval],
                       "mixer_out": blk["mixer_out"][0][i // interval],
                       "ffn_out": blk["ffn_out"][0][i // interval]})
    return loss, layers, grads


def own_stream_differences(system, reference, kinds):
    """Of two passes that each ran on their OWN residual stream, every
    layer's [kind, mixer branch's relative error, expert branch's, share of
    the T x k assignments that differ]: each holds what the layers under it
    left (``reference.forward`` says why they grow layer over layer), so
    they are reported and held by nothing."""
    return [[kind, float(_rel(got["mixer_out"], want["mixer_out"])),
             float(_rel(got["ffn_out"], want["ffn_out"])),
             float(_routing_differs(got["top_e"], want["top_e"]))
             / want["top_e"].size]
            for got, want, kind in zip(system, reference, kinds)]


def stream_add_differences(x_in, system):
    """(worst block's ``|x_mid - (x_in + mixer_out)| / |x_mid|``, every
    block's [that, ``|mixer_out| / |x_mid|``]) over the SYSTEM's own values:
    whether its residual stream after the mixer is the block's input plus
    the mixer's branch. Block i's input is block i - 1's stream after the
    mixer plus its expert branch (``x_in``, the embedding rows, for block
    0), so the second add of every block but the last is held through the
    next block's first (the last one's only the loss sees). An honest run
    leaves the bf16 rounding of the two sums, whatever the sizes; a branch
    lost reads its share of the stream (the second number)."""
    import jax.numpy as jnp
    by_layer = []
    for layer in system:
        mid, mixed = (layer[k].astype(jnp.float32)
                      for k in ("x_mid", "mixer_out"))
        size = jnp.linalg.norm(mid)
        by_layer.append([jnp.linalg.norm(
            mid - (x_in.astype(jnp.float32) + mixed)) / size,
            jnp.linalg.norm(mixed) / size])
        x_in = mid + layer["ffn_out"].astype(jnp.float32)
    return jnp.max(jnp.stack([err for err, _ in by_layer])), by_layer


def branch_differences(system, reference, kinds):
    """Of a reference pass PINNED to the system's experts and residual
    stream: the T x k assignments the reference's own router, on the
    system's stream, would have made otherwise (``routing_differs`` of
    ``routing_assignments``: ties broken by bf16 rounding); each branch's
    relative error as one vector, the worst layer's of its kind —
    ``gdn_out_rel`` (DeltaNet mixers), ``attn_out_rel`` (attention mixers),
    ``ffn_out_rel`` (expert branches: the held experts' partial sum and the
    gated shared expert) — and every layer's pair."""
    import jax.numpy as jnp
    out = {"gdn_out_rel": 0.0, "attn_out_rel": 0.0, "ffn_out_rel": 0.0,
           "routing_differs": 0, "routing_assignments": 0}
    by_layer = []
    for got, want, kind in zip(system, reference, kinds):
        mixer = _rel(got["mixer_out"], want["mixer_out"])
        ffn = _rel(got["ffn_out"], want["ffn_out"])
        key = "gdn_out_rel" if kind == "linear" else "attn_out_rel"
        out[key] = jnp.maximum(out[key], mixer)
        out["ffn_out_rel"] = jnp.maximum(out["ffn_out_rel"], ffn)
        out["routing_differs"] += _routing_differs(got["top_e"],
                                                   want["own_top_e"])
        out["routing_assignments"] += want["own_top_e"].size
        by_layer.append([mixer, ffn])
    return dict(out, by_layer=by_layer)


def gradient_differences(system, reference, n_layers, interval):
    """{leaf, by the reference's name: |system - reference| / |reference|} of
    two gradient trees in the program's layout, the worst layer's for a
    layer's leaf: the relative error of each gradient as a VECTOR."""
    import jax.numpy as jnp

    def rel(a, b):
        return jnp.linalg.norm((a - b).ravel()) / jnp.linalg.norm(b.ravel())

    (top_s, layers_s), (top_r, layers_r) = (
        reference_view(g, n_layers, interval) for g in (system, reference))
    out = {name: rel(top_s[name], top_r[name]) for name in top_r}
    for got, want in zip(layers_s, layers_r):
        for name in want:
            out[name] = jnp.maximum(out.get(name, 0.0),
                                    rel(got[name], want[name]))
    return out


@functools.lru_cache(maxsize=None)
def _reference_program(n_layers, interval, mode, sizes_items):
    """The reference as ONE jitted program over the program's weight tree
    (``families/olmoe._reference_program``): "forward" -> (loss, detail) of
    its own pass; "backward" -> (gradient norm, {leaf: relative error},
    branch differences) of the reference pinned to the experts the system
    chose and to the system's residual stream (``reference.forward``)
    against the system's gradients and branches."""
    import jax
    sizes_ = dict(sizes_items)

    def view(w):
        return reference_view(w, n_layers, interval)

    @jax.jit
    def forward(p, ids, system_layers):
        loss, detail = ref.loss(p, ids, view, **sizes_)
        # the system's bf16 embedding rows against the reference's
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
                gradient_differences(system_grads, g, n_layers, interval),
                branch_differences(system_layers, detail["layers"],
                                   layer_kinds(n_layers, interval)))

    return {"forward": forward, "backward": backward}[mode]


def _reference(mode, config, params, batch_ids, device, rehearse, *more):
    import jax
    s = sizes(config, rehearse)
    run = _reference_program(
        s["num_hidden_layers"], s["full_attention_interval"], mode,
        tuple(sorted(reference_sizes(config, rehearse).items())))
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
    s = sizes(config, rehearse)
    _, layers, grads = system
    loss, detail = _reference("forward", config, params, batch_ids, device,
                              rehearse, tuple(layers))
    diffs = {"own_stream_by_layer": own_stream_differences(
        layers, detail["layers"],
        layer_kinds(s["num_hidden_layers"], s["full_attention_interval"])),
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
    dropped) and the DeltaNet branch. The expert branch is held as one
    vector (``ffn_out_rel``, ``branch_differences``) where OLMoE's is held
    by its worst row: handed over under OLMoE's key."""
    tol = config["train"]["tolerance"]
    if differences is not None:
        differences = dict(differences,
                           ffn_out_row_rel=differences["ffn_out_rel"])
        config = dict(config, train=dict(config["train"], tolerance=dict(
            tol, ffn_out_row_rel=tol["ffn_out_rel"])))
    checks, detail = shared.judge_train(config, got_loss, got_gnorm,
                                        want_loss, want_gnorm, differences)
    if differences is not None:
        checks["deltanet_branch_matches_reference"] = \
            differences["gdn_out_rel"] <= tol["gdn_out_rel"]
        # not pinned: the first layer of the two own passes, and the
        # system's residual adds
        _, mixer, ffn, routing = differences["own_stream_by_layer"][0]
        first = tol["own_stream_first_layer"]
        checks["first_layer_matches_reference_on_its_own_stream"] = \
            mixer <= first["mixer_rel"] and ffn <= first["ffn_rel"] \
            and routing <= first["routing_share"]
        checks["residual_stream_adds_up"] = \
            differences["stream_add_rel"] <= tol["stream_add_rel"]
        detail["differences"]["tolerances"].update(
            {k: tol[k] for k in ("gdn_out_rel", "ffn_out_rel",
                                 "own_stream_first_layer", "stream_add_rel")})
    # this family's own engine, fenced and folded here, after warm-up
    # (``shared.judge_train`` folds OLMoE's slot, which holds none of ours)
    engine = _LIVE.get("engine")
    gauges = _LIVE["gauges"] = \
        engine.telemetry_flush()["gauges"] if engine is not None else {}
    if "moe/dropped_rows" in gauges:
        checks["no_routed_row_dropped"] = gauges["moe/dropped_rows"] == 0
        detail["moe_gauges"] = {k: v for k, v in gauges.items()
                                if k.startswith("moe/")}
    return checks, detail


# ------------------------------------------------- operations and bytes

def _layer_counts(config, rehearse):
    s = sizes(config, rehearse)
    attention = s["num_hidden_layers"] // s["full_attention_interval"]
    return s, s["num_hidden_layers"] - attention, attention


def rows_held_share(config, rehearse=False):
    """Share of the T x k routed rows a uniform router sends to the experts
    held here: 1 / ``expert_parallel_size``."""
    return 1.0 / sizes(config, rehearse)["expert_parallel_size"]


def active_matmul_params(config, rehearse=False):
    """Parameters one token is multiplied with HERE: the mixers'
    projections, the router, the shared expert and its gate, the k experts
    times the share of them held here, per layer, and the output head (the
    embedding lookup is a gather; the convolution's taps and the gates are
    elementwise)."""
    s, n_linear, n_attention = _layer_counts(config, rehearse)
    H = s["hidden_size"]
    key = s["linear_num_key_heads"] * s["linear_key_head_dim"]
    val = s["linear_num_value_heads"] * s["linear_value_head_dim"]
    linear = H * (2 * key + 2 * val + 2 * s["linear_num_value_heads"]) \
        + val * H
    q = s["num_attention_heads"] * s["head_dim"]
    kv = s["num_key_value_heads"] * s["head_dim"]
    attention = H * (2 * q + 2 * kv) + q * H
    moe = H * s["num_experts"] * s["expert_parallel_size"] \
        + 3 * H * s["shared_expert_intermediate_size"] + H \
        + s["num_experts_per_tok"] * rows_held_share(config, rehearse) \
        * 3 * H * s["moe_intermediate_size"]
    return n_linear * linear + n_attention * attention \
        + s["num_hidden_layers"] * moe + s["vocab_size"] * H


def train_flops_per_token(config, seq_len, rehearse=False):
    """6 a matmul parameter (2 forward, 4 backward) + causal attention in
    the attention layers alone (6 S heads head_dim a layer) + the delta
    rule's recurrence in the DeltaNet layers (3 x 6 Dk Dv a value head)."""
    s, n_linear, n_attention = _layer_counts(config, rehearse)
    scan = 3 * 6 * s["linear_key_head_dim"] * s["linear_value_head_dim"] \
        * s["linear_num_value_heads"]
    return 6 * active_matmul_params(config, rehearse) \
        + 6 * n_attention * seq_len * s["num_attention_heads"] \
        * s["head_dim"] + n_linear * scan


def train_attention_flops_per_step(config, batch, seq_len, rehearse=False):
    """Causal flops of the flash forward and backward kernels in one step:
    the ONE attention layer a period has."""
    s, _, n_attention = _layer_counts(config, rehearse)
    return n_attention * roofline.causal_attention_train_flops(
        batch, s["num_attention_heads"], seq_len, s["head_dim"])


def moe_gmm_flops_per_step(config, tokens, rehearse=False):
    """Flops the grouped matmuls of one step NEED for the rows a uniform
    router holds here (``tokens x k / expert_parallel_size``): three products
    of three matrices, 2 x rows x hidden x expert width each, per layer.
    An EXPECTED row count: ``moe_gmm_roofline``'s reader takes this and
    nothing of the run, so a router that sends more than its share here
    reads high (``moe_rows_held_share`` beside it says by how much)."""
    s = sizes(config, rehearse)
    rows = tokens * s["num_experts_per_tok"] * rows_held_share(config,
                                                               rehearse)
    return s["num_hidden_layers"] * 3 * 3 * 2 * rows \
        * s["hidden_size"] * s["moe_intermediate_size"]


def gdn_scan_flops_and_bytes(config, tokens, rehearse=False, itemsize=2):
    """(flops, bytes) the delta rule of one step NEEDS over all DeltaNet
    layers for ``tokens`` tokens. Flops: the recurrence's 6 Dk Dv a token a
    value head forward (decay-free: read S^T k, the rank-one update, read
    S^T q, 2 Dk Dv each), x 3 with the backward pass. Bytes: q, k
    (per key head), v, o (per value head) at ``itemsize`` and g, beta
    (float32), forward; the same again as cotangents, and q, k, v, g, beta
    read once more by the backward pass — nothing a chunking recomputes or
    keeps between chunks."""
    s, n_linear, _ = _layer_counts(config, rehearse)
    hk, dk = s["linear_num_key_heads"], s["linear_key_head_dim"]
    hv, dv = s["linear_num_value_heads"], s["linear_value_head_dim"]
    flops = 3 * 6 * dk * dv * hv
    inputs = itemsize * (2 * hk * dk + hv * dv) + 2 * 4 * hv
    out = itemsize * hv * dv
    return (n_linear * tokens * flops,
            n_linear * tokens * (3 * inputs + 2 * out))
