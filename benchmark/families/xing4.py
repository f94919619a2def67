"""The Xing4.0 family (``model_type: xing4_0``; Xing4.0-29B-A4B is its one
configuration): how its configuration file becomes a running system.

The members ``benchmark/families/__init__.py`` lists for training, none of
serving's. The model is ``deepspeed_tpu.models.deepseek_v3`` — ONE family of
code with Kanana-2's, with what this row adds switched on: compressed
queries (``q_lora_rank``), YaRN (``rope_scaling``), four residual streams
(``hc_mult``, ``models/hyper_connections.py``) and the multi-token-prediction
module (``num_nextn_predict_layers``) — built through ``dstpu.initialize`` as
the other cells' are; the plain reference is ``benchmark/reference/xing4.py``.
Key names are the published config's.

A configuration of this family is ONE RANK'S SHARE of an expert-parallel
layout, as the DeepSeek-V3 family's is: ``n_routed_experts`` is the experts
held here, ``expert_parallel_size`` how many such shares the router chooses
among, ``vocab_size`` the slice of the vocabulary held here. The weights are
the seed's, but for the routers' selection biases, which set-up then levels
(``families/common.balanced_selection_bias``, the prediction module's layer
among them).

``correct`` is the DeepSeek-V3 family's comparison on this residual path:
loss (both terms) and gradient norm of the two own passes; then, of the
reference's gradient WALKED a branch at a time from the two heads down and
PINNED to the system's experts and streams (``reference/xing4.pinned_
backward``; the engine's state leaves no room for a float32 gradient tree),
the routing, each branch as one vector, the three COEFFICIENT sets of every
branch's stream mixer on the system's stream (``mhc_coeff_abs``), and every
gradient leaf as a vector (a stream mixer's three leaves pooled over the 12
mixers: ``fold_block``); that the selection bias's gradient is exactly zero;
the prediction module's loss alone (``mtp_loss_abs``); and, because a pinned
pass is blind to the stream itself, two checks that are NOT pinned: the first
layer of the two own passes, and the system's stream mixes (``stream_mix_rel``:
every ``X_new`` against ``H_res X + H_post y`` from the system's own values);
each against the file's ``train.tolerance``.
"""

import functools

import numpy as np

from benchmark.families import common, deepseek_v3 as base, olmoe as shared
from benchmark.families.common import (at as _at, rel as _rel,
                                       routing_differs as _routing_differs)
from benchmark.reference import xing4 as ref

WIDTH_KEYS = base.WIDTH_KEYS + ("hc_mult",)
KERNEL_TAGS = base.KERNEL_TAGS
# the stream mixer's three scopes lie in a block beside its modules; ``mtp``
# stands last: a path under it keeps the tag of what it runs through (the
# second head pass is ``ds_loss_head``, the module's attention ``mla_*``) and
# what is left — the join, its norms — is the module's own row. ``mtp_ms``
# reads the scope itself (``benchmark/layer_metrics/mtp_ms.py``)
MHC_TAGS = ("mhc_coeff", "mhc_read", "mhc_write")
MTP_SCOPE = "mtp"
MODULE_TAGS = MHC_TAGS + base.MODULE_TAGS + (MTP_SCOPE,)
DISPATCH_TAGS = shared.DISPATCH_TAGS
MLA_EXPAND_TAGS = base.MLA_EXPAND_TAGS
MLA_LAYER_TAGS = base.MLA_LAYER_TAGS
# this process's engine of THIS family, and its gauges as ``judge_train``
# folded them; configurations by their sizes (``_config_key``)
_LIVE, _CONFIGS = {}, {}

_HC_KEYS = ("hc_mult", "hc_sinkhorn_iters", "hc_eps", "mhc_h_res_clamp_min",
            "mhc_h_res_clamp_max", "hc_phi_std", "hc_gate_mean",
            "hc_gate_std", "hc_bias_std")
_SIZE_KEYS = (
    "vocab_size", "max_position_embeddings", "hidden_size",
    "intermediate_size", "moe_intermediate_size", "num_hidden_layers",
    "num_attention_heads", "q_lora_rank", "kv_lora_rank", "qk_nope_head_dim",
    "qk_rope_head_dim", "v_head_dim", "rope_theta", "rope_interleave",
    "rope_scaling", "first_k_dense_replace", "n_routed_experts",
    "expert_parallel_size", "expert_parallel_rank", "n_shared_experts",
    "num_experts_per_tok", "n_group", "topk_group", "norm_topk_prob",
    "routed_scaling_factor", "rms_norm_eps", "initializer_range",
    "e_score_correction_bias_std", "num_nextn_predict_layers",
    "mtp_loss_weight") + _HC_KEYS
_NOT_THE_MODELS = ("n_routed_experts", "expert_parallel_size",
                   "expert_parallel_rank")
MTP_LAYER = "mtp_layer"


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


class _WithLabels:
    """The model as ``common.balanced_selection_bias`` calls it (ids alone),
    run WITH labels: the prediction module is a loss term and runs only where
    there is one, and its router is levelled with the trunk's."""

    def __init__(self, model):
        self.model = model

    def apply(self, variables, ids, **kwargs):
        return self.model.apply(variables, ids, labels=ids, **kwargs)


def _layer_names(config, rehearse, mtp=True):
    """The blocks' names in the program's tree, trunk first."""
    s = sizes(config, rehearse)
    return [f"layer_{i}" for i in range(s["num_hidden_layers"])] \
        + ([MTP_LAYER] if mtp and s["num_nextn_predict_layers"] else [])


def _kinds(config, rehearse, mtp=True):
    """"dense" | "sparse" of every block of ``_layer_names``."""
    s = sizes(config, rehearse)
    lead = s["first_k_dense_replace"]
    return ["dense"] * lead + ["sparse"] * (
        len(_layer_names(config, rehearse, mtp)) - lead)


def build_train(config, global_batch, seed, devices, rehearse):
    """(engine, initial parameters): ``common.build_train``'s recipe over
    ``DeepseekV3ForCausalLM`` (a program whose model lacks this row's keys
    fails at ``model_config``, before any work), the weights made from 64
    example positions, the selection biases then levelled."""
    engine, params = common.build_train(
        _model(config, rehearse), config, global_batch, seed, devices,
        rehearse, example_len=64)
    params, _LIVE["balance"] = balanced_selection_bias(
        config, params, global_batch, seed, rehearse)
    engine.state = engine.state.replace(params=params)
    _LIVE["engine"] = engine         # ``judge_train`` folds its gauges
    return engine, params


def balanced_selection_bias(config, params, global_batch, seed, rehearse):
    """``common.balanced_selection_bias`` over every expert block, the
    prediction module's among them, as ``train.selection_bias_balance`` sets
    the rounds and rates."""
    s = sizes(config, rehearse)
    names = [name for name, kind in zip(_layer_names(config, rehearse),
                                        _kinds(config, rehearse))
             if kind == "sparse"]
    return common.balanced_selection_bias(
        _WithLabels(_model(config, rehearse)), params, "mlp", names,
        common.merged(config, "train", rehearse)["selection_bias_balance"],
        global_batch, s["vocab_size"], seed)


def program_gauges():
    """The program's ``moe/*``, ``attention/*``, ``mhc/*`` and ``mtp/*``
    gauges of the LAST WARM-UP STEP, as ``judge_train`` folded them."""
    return _LIVE.get("gauges", {})


def lower_train_step(config, traffic, devices):
    return common.lower_train_step(_model(config, rehearse=False), config,
                                   traffic, devices)


# what the reference calls each leaf of a layer, by the program's path
_HC_LEAVES = ("phi", "bias", "gate")
_ATTN_LEAVES = {
    **{k: v for k, v in base.LAYER_LEAVES["dense"].items()
       if v[0] != "mlp" and k != "q"},
    "q_a": ("mla_attn", "q_a_proj", "kernel"),
    "q_a_norm": ("mla_attn", "q_a_norm", "scale"),
    "q_b": ("mla_attn", "q_b_proj", "kernel")}
LAYER_LEAVES = {kind: {**{k: v for k, v in leaves.items() if v[0] == "mlp"},
                       **_ATTN_LEAVES}
                for kind, leaves in base.LAYER_LEAVES.items()}
MTP_LEAVES = {"hnorm": ("mtp_hnorm", "scale"), "enorm": ("mtp_enorm", "scale"),
              "norm": ("mtp_norm", "scale"),
              "eh_proj": ("mtp_eh_proj", "kernel")}
# a gradient leaf's name in ``grad_leaf_rel``
_LEAF_GROUP = {**{n: "attn" for n in ("q_a", "q_a_norm", "q_b", "kv_a",
                                      "kv_a_norm", "kv_b", "o")},
               **{n: "dense" for n in ("mlp_gate", "mlp_up", "mlp_down")},
               **{n: "ffn" for n in ("router", "gate", "up", "down",
                                     "shared_gate", "shared_up",
                                     "shared_down")}}


def layer_view(block, kind):
    """One block's sub-tree in the reference's layout."""
    out = {name: _at(block, path) for name, path in LAYER_LEAVES[kind].items()}
    for hc in ("attn_hc", "ffn_hc"):
        out[hc] = {leaf: block[hc][leaf] for leaf in _HC_LEAVES}
    return out


def reference_view(params, config, rehearse):
    """(top, layers, mtp) in the reference's layout from
    ``DeepseekV3ForCausalLM``'s tree: the leaves themselves where they are
    float32 already (the engine's master weights are read in place)."""
    import jax
    import jax.numpy as jnp
    kinds = _kinds(config, rehearse)
    top = {"embed": params["embed_tokens"], "norm": params["norm"]["scale"],
           "lm_head": params["lm_head"]}
    layers = [layer_view(params[name], kind) for name, kind
              in zip(_layer_names(config, rehearse), kinds)]
    mtp = None
    if sizes(config, rehearse)["num_nextn_predict_layers"]:
        mtp = dict({k: _at(params, path) for k, path in MTP_LEAVES.items()},
                   layer=layers.pop())
    return jax.tree_util.tree_map(lambda x: x.astype(jnp.float32),
                                  (top, layers, mtp))


def reference_sizes(config, rehearse):
    s = sizes(config, rehearse)
    return dict(
        n=s["hc_mult"], hc_eps=s["hc_eps"], iters=s["hc_sinkhorn_iters"],
        clamp=(s["mhc_h_res_clamp_min"], s["mhc_h_res_clamp_max"]),
        n_head=s["num_attention_heads"], nope=s["qk_nope_head_dim"],
        rope_dim=s["qk_rope_head_dim"], v_dim=s["v_head_dim"],
        theta=float(s["rope_theta"]), yarn=s["rope_scaling"],
        eps=s["rms_norm_eps"], k=s["num_experts_per_tok"],
        expert_lo=s["n_routed_experts"] * s["expert_parallel_rank"],
        routed_scale=s["routed_scaling_factor"],
        norm_topk_prob=s["norm_topk_prob"], mtp_weight=s["mtp_loss_weight"])


def _bf16_grads(config, rehearse):
    return common.merged(config, "train", rehearse)["engine"].get(
        "data_types", {}).get("grad_dtype") == "bf16"


def system_rows(got, config, rehearse, batch_shape):
    """Per block (``_layer_names``' order) what the program's model sowed,
    in the REFERENCE's layout: the streams [B, S, n, C], the coefficient
    sets token-major ([B, S, n], [B, S, n, n])."""
    n = sizes(config, rehearse)["hc_mult"]
    B, S = batch_shape

    def stream(x):
        return x.reshape(B, S, n, -1)

    def coeff(sets):
        h_pre, h_post, h_res = sets
        return (h_pre.T.reshape(B, S, n), h_post.T.reshape(B, S, n),
                h_res.transpose(2, 0, 1).reshape(B, S, n, n))

    return [{"top_e": blk["mlp"]["top_e"][0] if "mlp" in blk else None,
             "x_mid": stream(blk["x_mid"][0]),
             "x_out": stream(blk["x_out"][0]),
             "mixer_out": blk["mixer_out"][0], "ffn_out": blk["ffn_out"][0],
             "attn_hc": coeff(blk["attn_hc_coeff"][0]),
             "ffn_hc": coeff(blk["ffn_hc_coeff"][0])}
            for blk in (got[name] for name in _layer_names(config, rehearse))]


@functools.lru_cache(maxsize=None)
def _system_program(config_key, rehearse):
    """``system_step``'s jitted program: (weights, ids) -> (loss, what the
    model sowed, gradients)."""
    import jax
    import jax.numpy as jnp
    config = _CONFIGS[config_key]
    model = _model(config, rehearse)
    bf16 = _bf16_grads(config, rehearse)
    has_mtp = bool(sizes(config, rehearse)["num_nextn_predict_layers"])

    def loss_fn(p, ids):
        out, vs = model.apply({"params": p}, ids, labels=ids,
                              mutable=["losses", "stats", "intermediates"])
        rows = system_rows(vs["intermediates"], config, rehearse, ids.shape)
        seen = {"layers": rows[:-1] if has_mtp else rows,
                "mtp": rows[-1] if has_mtp else None,
                "mtp_joined": vs["intermediates"]["mtp_joined"][0]
                if has_mtp else None,
                "mtp_loss": vs["stats"]["mtp_loss"][0] if has_mtp else 0.0}
        return out + sum(jnp.sum(x) for x in jax.tree_util.tree_leaves(
            vs.get("losses", {}))), seen

    @jax.jit
    def step(p, ids):
        if bf16:
            p = jax.tree_util.tree_map(
                lambda x: x.astype(jnp.bfloat16)
                if x.dtype == jnp.float32 else x, p)
        (loss, got), grads = jax.value_and_grad(loss_fn, has_aux=True)(p, ids)
        return loss, got, grads

    return step


def _config_key(config, rehearse):
    """``config`` by its sizes: the programs' cache key (a dict is not
    hashable)."""
    import json
    key = json.dumps(sizes(config, rehearse), sort_keys=True)
    _CONFIGS[key] = config
    return key


def system_step(config, params, batch_ids, device, rehearse):
    """(loss, {"layers": per trunk block ``system_rows``' row, "mtp": the
    prediction block's, "mtp_joined": the join it starts from (both None
    without one), "mtp_loss": its loss alone}, gradients) of the PROGRAM's
    model on ``batch_ids`` in one jitted program, weights cast and loss
    formed as the engine's step does."""
    import jax
    run = _system_program(_config_key(config, rehearse), bool(rehearse))
    return run(jax.device_put(params, device),
               jax.device_put(np.asarray(batch_ids), device))


def stream_mix_differences(start, rows):
    """(worst branch's ``|X_new - (H_res X + H_post y)| / |X_new|``, every
    block's [[that, ``|H_post y| / |X_new|``] of its attention branch, of its
    FFN branch]) over the SYSTEM's own values, a chain of blocks ``rows``
    from the stream ``start``: whether each write is the mix of the stream it
    read and the branch it ran. An honest run leaves the rounding of the
    stream's dtype; a branch lost reads its share of the stream (the second
    number), a stream mix lost far more."""
    import jax.numpy as jnp
    f32 = jnp.float32
    by_block, x_in = [], start.astype(f32)
    for row in rows:
        pair = []
        for hc, y, new in (("attn_hc", "mixer_out", "x_mid"),
                           ("ffn_hc", "ffn_out", "x_out")):
            _, h_post, h_res = row[hc]
            added = h_post[..., None] * row[y].astype(f32)[:, :, None, :]
            want = jnp.einsum("bsij,bsjc->bsic", h_res, x_in) + added
            got = row[new].astype(f32)
            size = jnp.linalg.norm(got)
            pair.append([jnp.linalg.norm(got - want) / size,
                         jnp.linalg.norm(added) / size])
            x_in = got
        by_block.append(pair)
    return jnp.max(jnp.stack([b[0] for pair in by_block for b in pair])), \
        by_block


def _own_row(got, want):
    """[attention branch's relative error, FFN branch's, share of the T x k
    assignments that differ (0 for a dense layer)] of two passes that each
    ran on their OWN stream."""
    routing = 0.0 if got["top_e"] is None else _routing_differs(
        got["top_e"], want["top_e"]) / want["top_e"].size
    return [_rel(got["mixer_out"], want["mixer_out"]),
            _rel(got["ffn_out"], want["ffn_out"]), routing]


def _coeff_abs(got, want):
    """The largest |system - reference| over a branch's three coefficient
    sets."""
    import jax.numpy as jnp
    return jnp.max(jnp.stack([jnp.max(jnp.abs(g - w))
                              for g, w in zip(got, want)]))


def fold_block(kind, system_grads, row, grads, at):
    """One block's part of the pinned comparison: (sum of squares of the
    reference's gradients, {leaf: relative error}, {stream-mixer leaf:
    [squared error, squared size]}, the selection bias's largest gradient in
    the system, {"mla_out_rel", "ffn_out_rel" / "dense_out_rel",
    "mhc_coeff_abs", "routing_differs", "routing_assignments"}). A stream
    mixer's three leaves are POOLED over the model's 12 mixers (``hc.gate``
    is 36 numbers, ``hc.bias`` 288, each a sum over the step's tokens of
    signed terms that cancel): one mixer's gate alone reads 4-90 % off in
    bf16 from seed to seed where the cancellation is deep, the pooled vector
    does not."""
    import jax
    import jax.numpy as jnp
    got = layer_view(system_grads, kind)
    rels, pooled, bias = {}, {}, jnp.zeros((), jnp.float32)
    for name, want in grads.items():
        if name == "bias":
            bias = jnp.max(jnp.abs(got[name]))
        elif name in ("attn_hc", "ffn_hc"):
            for leaf in _HC_LEAVES:
                err, size = pooled.get(f"hc.{leaf}", (0.0, 0.0))
                have = got[name][leaf].astype(jnp.float32)
                pooled[f"hc.{leaf}"] = (
                    err + jnp.sum(jnp.square(have - want[leaf])),
                    size + jnp.sum(jnp.square(want[leaf])))
        else:
            key = f"{_LEAF_GROUP[name]}.{name.removeprefix('mlp_')}" \
                if name in _LEAF_GROUP else name
            rels[key] = _rel(got[name], want)
    squares = sum(jnp.sum(jnp.square(g)) for name, g in grads.items()
                  if name != "bias" for g in jax.tree_util.tree_leaves(g))
    out = {"mla_out_rel": _rel(row["mixer_out"], at["mixer_out"]),
           "dense_out_rel" if kind == "dense" else "ffn_out_rel":
               _rel(row["ffn_out"], at["ffn_out"]),
           "mhc_coeff_abs": jnp.maximum(
               _coeff_abs(row["attn_hc"], at["attn_hc"]),
               _coeff_abs(row["ffn_hc"], at["ffn_hc"]))}
    if row["top_e"] is not None:
        out["routing_differs"] = _routing_differs(row["top_e"],
                                                 at["own_top_e"])
        out["routing_assignments"] = at["own_top_e"].size
    return squares, rels, pooled, bias, out


@functools.lru_cache(maxsize=None)
def _reference_program(config_key, rehearse, mode):
    """The reference as ONE jitted program over the program's weight tree,
    scalars out: "forward" -> (loss, the unpinned differences) of its own
    pass; "backward" -> (gradient norm, {leaf: relative error}, the bias's
    gradient, the pinned differences) of the gradient walked a branch at a
    time, pinned to the system's experts and streams."""
    import jax
    import jax.numpy as jnp
    config = _CONFIGS[config_key]
    sizes_ = reference_sizes(config, rehearse)
    kinds = _kinds(config, rehearse)

    def view(w):
        return reference_view(w, config, rehearse)

    @jax.jit
    def forward(p, ids, system):
        top, _, _ = view(p)
        loss, detail = ref.loss(p, ids, view, **sizes_)
        start = ref.spread(top["embed"][ids], sizes_["n"])
        worst, mixes = stream_mix_differences(start, system["layers"])
        own = [_own_row(got, want) for got, want
               in zip(system["layers"], detail["layers"])]
        out = {"own_stream_by_layer": own, "reference_ce": detail["ce"]}
        if system["mtp"] is not None:
            worst_mtp, mix_mtp = stream_mix_differences(
                ref.spread(system["mtp_joined"], sizes_["n"]),
                [system["mtp"]])
            worst, mixes = jnp.maximum(worst, worst_mtp), mixes + mix_mtp
            out.update(reference_mtp_ce=detail["mtp_ce"],
                       mtp_own_stream=_own_row(system["mtp"], detail["mtp"]))
        return loss, dict(out, stream_mix_rel=worst,
                          stream_mix_by_layer=mixes)

    @jax.jit
    def backward(p, ids, system, system_grads):
        top, layers, mtp = view(p)
        names = _layer_names(config, rehearse)

        def fold(where, grads, at):
            if where == "top":
                got = {"embed": system_grads["embed_tokens"],
                       "norm": system_grads["norm"]["scale"],
                       "lm_head": system_grads["lm_head"]}
                return sum(jnp.sum(jnp.square(g)) for g in grads.values()), \
                    {n: _rel(got[n], grads[n]) for n in grads}
            if where == "mtp":
                small = {k: g for k, g in grads.items() if k != "layer"}
                sq, rels, pooled, bias, out = fold_block(
                    "sparse", system_grads[MTP_LAYER], system["mtp"],
                    grads["layer"], at)
                rels.update({f"mtp.{k}": _rel(_at(system_grads, MTP_LEAVES[k]),
                                              g) for k, g in small.items()})
                return sq + sum(jnp.sum(jnp.square(g))
                                for g in small.values()), rels, pooled, \
                    bias, out
            return fold_block(kinds[where], system_grads[names[where]],
                              system["layers"][where], grads, at)

        (ce, mtp_ce), folded = ref.pinned_backward(
            top, layers, mtp, ids, system, fold, **sizes_)
        squares, leaves = folded.pop("top")
        bias = jnp.zeros((), jnp.float32)
        pinned = {"mla_out_rel": 0.0, "dense_out_rel": 0.0,
                  "ffn_out_rel": 0.0, "mhc_coeff_abs": 0.0,
                  "routing_differs": 0, "routing_assignments": 0}
        by_block, mixers = {}, {}
        for where, (sq, rels, pooled, b, out) in folded.items():
            squares, bias = squares + sq, jnp.maximum(bias, b)
            for name, err in rels.items():
                leaves[name] = jnp.maximum(leaves.get(name, 0.0), err)
            for name, (err, size) in pooled.items():
                have = mixers.get(name, (0.0, 0.0))
                mixers[name] = (have[0] + err, have[1] + size)
            for key, v in out.items():
                pinned[key] = pinned[key] + v if key.startswith("routing") \
                    else jnp.maximum(pinned[key], v)
            by_block[str(where)] = out
        leaves.update({name: jnp.sqrt(err / size)
                       for name, (err, size) in mixers.items()})
        return jnp.sqrt(squares), leaves, bias, dict(
            pinned, pinned_by_block=by_block, pinned_ce=ce,
            pinned_mtp_ce=mtp_ce)

    return {"forward": forward, "backward": backward}[mode]


def _reference(mode, config, params, batch_ids, device, rehearse, *more):
    import jax
    run = _reference_program(_config_key(config, rehearse), bool(rehearse),
                             mode)
    return run(jax.device_put(params, device),
               jax.device_put(np.asarray(batch_ids), device), *more)


def _plain(tree):
    import jax
    return jax.tree_util.tree_map(
        lambda v: int(v) if np.asarray(v).dtype.kind == "i" else float(v),
        jax.device_get(tree))


def compare(config, params, batch_ids, device, rehearse, system):
    """(reference loss, reference gradient norm, differences) of ``system``
    (``system_step``'s three values) against the plain reference on the same
    weights and batch: the reference's OWN forward pass first (handed the
    system's values only to compare with) for the loss and the unpinned
    checks; then its gradient walked a branch at a time, pinned to the
    system's experts and streams."""
    import jax
    _, seen, grads = system
    loss, detail = _reference("forward", config, params, batch_ids, device,
                              rehearse, seen)
    diffs = dict(_plain(detail), own_stream_kinds=_kinds(
        config, rehearse, mtp=False))
    diffs["system_mtp_loss"] = float(seen["mtp_loss"])
    diffs["system_grad_norm"] = float(jax.jit(lambda g: ref.grad_norm(
        jax.tree_util.tree_map(lambda x: x.astype("float32"), g)))(grads))
    gnorm, leaves, bias, pinned = _reference(
        "backward", config, params, batch_ids, device, rehearse, seen, grads)
    diffs["grad_leaf_rel"] = _plain(leaves)
    diffs["bias_grad_abs"] = float(bias)
    diffs.update(_plain(pinned))
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
    attention and the expert branch, every gradient leaf) as the DeepSeek-V3
    family hands it over, and this residual path's own: the dense branch, the
    bias's zero gradient, the stream mixers' coefficients, the prediction
    module's loss, and the two unpinned checks."""
    tol = config["train"]["tolerance"]
    has_mtp = bool(config["num_nextn_predict_layers"])
    if differences is not None:
        differences = dict(differences,
                           ffn_out_row_rel=differences["ffn_out_rel"],
                           attn_out_rel=differences["mla_out_rel"])
        config = dict(config, train=dict(config["train"], tolerance=dict(
            tol, ffn_out_row_rel=tol["ffn_out_rel"],
            attn_out_rel=tol["mla_out_rel"])))
    checks, detail = shared.judge_train(config, got_loss, got_gnorm,
                                        want_loss, want_gnorm, differences)
    checks.pop("no_routed_row_dropped", None)
    if differences is not None:
        checks["dense_branch_matches_reference"] = \
            differences["dense_out_rel"] <= tol["dense_out_rel"]
        checks["selection_bias_takes_no_gradient"] = \
            differences["bias_grad_abs"] == 0.0
        checks["stream_coefficients_match_reference"] = \
            differences["mhc_coeff_abs"] <= tol["mhc_coeff_abs"]
        if has_mtp:
            checks["prediction_loss_matches_reference"] = abs(
                differences["system_mtp_loss"]
                - differences["reference_mtp_ce"]) <= tol["mtp_loss_abs"]
        # not pinned: the first layer of the two own passes (both start from
        # the same embedding rows), the first EXPERT layer's routing on the
        # stream the dense layer left, and the system's stream mixes
        own, first = differences["own_stream_by_layer"], \
            tol["own_stream_first_layer"]
        mixer, ffn, _ = own[0]
        routing = next(row[2] for row, kind in zip(
            own, differences["own_stream_kinds"]) if kind == "sparse")
        checks["first_layer_matches_reference_on_its_own_stream"] = \
            mixer <= first["mixer_rel"] and ffn <= first["ffn_rel"] \
            and routing <= first["routing_share"]
        checks["stream_mixes_add_up"] = \
            differences["stream_mix_rel"] <= tol["stream_mix_rel"]
        detail["differences"]["tolerances"].update(
            {k: tol[k] for k in ("mla_out_rel", "dense_out_rel",
                                 "ffn_out_rel", "own_stream_first_layer",
                                 "stream_mix_rel", "mhc_coeff_abs")
             + (("mtp_loss_abs",) if has_mtp else ())})
    # this family's own engine, fenced and folded here, after warm-up
    engine = _LIVE.get("engine")
    gauges = _LIVE["gauges"] = \
        engine.telemetry_flush()["gauges"] if engine is not None else {}
    if "balance" in _LIVE:
        detail["selection_bias_balance"] = _LIVE["balance"]
    if "moe/dropped_rows" in gauges:
        checks["no_routed_row_dropped"] = gauges["moe/dropped_rows"] == 0
        detail["moe_gauges"] = {
            k: v for k, v in gauges.items()
            if k.startswith(("moe/", "attention/", "mhc/", "mtp/"))}
    if engine is not None:
        detail["collector_settled"] = _settle_the_collector()
    return checks, detail


def _settle_the_collector():
    """Set-up's garbage is collected once and what is left is FROZEN, here,
    after warm-up and ahead of the window. The reference's two programs and
    the system's own step leave ~1.5 M tracked Python objects behind (their
    jaxprs: Sinkhorn's 20 rounds unrolled round 12 branches, forward, walked
    backward); a full collection over them holds the interpreter for 1-4 s,
    and one that lands inside the window outlasts the two-step fence lag:
    three of the first six untraced runs of this cell lost 3-10 % of their
    window to ONE such fence each (PERF.md Findings PR 56). They are the
    BENCHMARK's objects — a deployment traces no float32 reference — so they
    are taken out of the collector's sight, not out of the measurement of
    anything the engine does. Returns {"objects": tracked before, "seconds":
    what that one full collection took}."""
    import gc
    import time
    objects, t0 = len(gc.get_objects()), time.monotonic()
    gc.collect()
    gc.freeze()
    return {"objects": objects, "seconds": time.monotonic() - t0}


# ------------------------------------------------- operations and bytes

def _layer_counts(config, rehearse):
    """(sizes, dense blocks, expert blocks — the prediction module's among
    them)."""
    s = sizes(config, rehearse)
    lead = s["first_k_dense_replace"]
    return s, lead, s["num_hidden_layers"] - lead \
        + s["num_nextn_predict_layers"]


def rows_held_share(config, rehearse=False):
    return 1.0 / sizes(config, rehearse)["expert_parallel_size"]


def attention_matmul_params(s):
    """The five projections of one latent-attention module with compressed
    queries: q_a, q_b, the down-projection (latent + rotated key), the
    up-projection (keys without position + values), o."""
    H, n, Q = s["hidden_size"], s["num_attention_heads"], s["q_lora_rank"]
    return H * Q + Q * n * (s["qk_nope_head_dim"] + s["qk_rope_head_dim"]) \
        + H * (s["kv_lora_rank"] + s["qk_rope_head_dim"]) \
        + s["kv_lora_rank"] * n * (s["qk_nope_head_dim"] + s["v_head_dim"]) \
        + n * s["v_head_dim"] * H


def stream_mixer_matmul_params(s):
    """One branch's ``phi``: [n C, 2n + n^2]."""
    n = s["hc_mult"]
    return n * s["hidden_size"] * (2 * n + n * n)


def active_matmul_params(config, rehearse=False):
    """Parameters one token is multiplied with HERE: every block's five
    attention projections and two stream-mixer projections; the dense
    block's SwiGLU or an expert block's router, its shared expert and the k
    experts times the share of them held here; the prediction module's join
    and its second pass through the head; and the output head (the
    embedding lookups are gathers)."""
    s, dense, sparse = _layer_counts(config, rehearse)
    H, F = s["hidden_size"], s["moe_intermediate_size"]
    experts = H * s["n_routed_experts"] * s["expert_parallel_size"] \
        + 3 * H * s["n_shared_experts"] * F \
        + s["num_experts_per_tok"] * rows_held_share(config, rehearse) \
        * 3 * H * F
    return (dense + sparse) * (attention_matmul_params(s)
                               + 2 * stream_mixer_matmul_params(s)) \
        + dense * 3 * H * s["intermediate_size"] + sparse * experts \
        + s["num_nextn_predict_layers"] * (2 * H * H + s["vocab_size"] * H) \
        + s["vocab_size"] * H


def train_attention_flops_per_step(config, batch, seq_len, rehearse=False):
    """Causal flops of the flash forward and backward kernels in one step
    (``families/deepseek_v3.train_attention_flops_per_step``'s count), the
    prediction module's block among the layers."""
    s, dense, sparse = _layer_counts(config, rehearse)
    qk = s["qk_nope_head_dim"] + s["qk_rope_head_dim"]
    return (dense + sparse) * batch * s["num_attention_heads"] \
        * seq_len * seq_len * (3 * qk + 3 * s["v_head_dim"])


def train_flops_per_token(config, seq_len, rehearse=False):
    """6 a matmul parameter a token meets on THIS rank (2 forward, 4
    backward) + causal attention in every block."""
    return 6 * active_matmul_params(config, rehearse) \
        + train_attention_flops_per_step(config, 1, seq_len, rehearse) \
        / seq_len


def moe_gmm_flops_per_step(config, tokens, rehearse=False):
    """``families/deepseek_v3.moe_gmm_flops_per_step`` over this model's
    expert blocks."""
    s, _, sparse = _layer_counts(config, rehearse)
    share = program_gauges().get("moe/rows_held_share") \
        or rows_held_share(config, rehearse)
    rows = tokens * s["num_experts_per_tok"] * share
    return sparse * 3 * 3 * 2 * rows * s["hidden_size"] \
        * s["moe_intermediate_size"]


def mhc_stream_bytes_per_step(config, tokens, rehearse=False):
    """Bytes the residual streams' mixers HAVE to move in one step, the
    least any implementation moves, at the configuration's activation dtype
    (``e`` bytes) and 4-byte coefficients. A branch, a token: forward reads
    X (n C) and y (C) once and writes X_new (n C) once — u is formed from the
    same read of X; backward reads dX_new, X and y once and writes dX and dy
    once; the 2n + n^2 coefficients a token once each way besides. A chain's
    ends: the copy into the streams reads C and writes n C forward and the
    reverse backward; the sum the reverse of that. Recomputation is not
    needed work."""
    s, dense, sparse = _layer_counts(config, rehearse)
    n, C = s["hc_mult"], s["hidden_size"]
    e = 2 if common.merged(config, "model", rehearse)["dtype"] == "bfloat16" \
        else 4
    coeff = 2 * 4 * (2 * n + n * n)
    branch = e * C * ((2 * n + 1) + (2 * n + 1) + (n + 1)) + coeff
    chains = 1 + s["num_nextn_predict_layers"]
    ends = chains * 2 * 2 * e * C * (n + 1)
    return tokens * (2 * (dense + sparse) * branch + ends)
