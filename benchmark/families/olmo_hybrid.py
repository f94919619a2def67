"""The Olmo Hybrid family (``model_type: olmo_hybrid``): how its
configuration file becomes a running system.

The members ``benchmark/families/__init__.py`` lists for training, none of
serving's. The model is ``deepspeed_tpu.models.olmo_hybrid`` built through
``dstpu.initialize`` as the other cells' are; the plain reference is
``benchmark/reference/olmo_hybrid.py``. Key names are the published
config's; the layer plan is the published list ``layer_types``, handed to
the model and to the reference as the file has it. A configuration holds
every matrix whole but the embedding and the head, of which ``vocab_size``
is the slice held here.

``correct`` is the comparison of ``families/granite_hybrid.py`` for a model
of TWO dense branches a layer (no router, so nothing to pin but the stream):
the loss of the two OWN forward passes; then, of a reference pass PINNED to
the system's residual stream (``reference/olmo_hybrid.pinned_backward``:
every branch starts from the system's values, and a bf16 run drifts from a
float32 one layer over layer), each kind's branch AS ADDED (its output norm
included) as one vector (``gdn_out_rel``, ``attn_out_rel``, ``mlp_out_rel``),
the gradient norm and every gradient leaf as a vector, a leaf named by its
layer's kind; and, because a pinned pass is blind to the stream itself,
three checks that are NOT pinned: the first layer's mixer of the two own
passes, the stream's start against ``E[ids]``, and the system's residual
adds; each against the file's ``train.tolerance``. The reference's gradients
are walked a branch at a time and folded into those numbers as they come:
the float32 tree (3.7 GB) never stands whole beside the engine's state.
``CONTROLS`` are the reference's faults (``compare(..., control=)``:
``benchmark.tools.reference_controls``).
"""

import functools

import numpy as np

from benchmark import roofline
from benchmark.families import common
from benchmark.families.common import at as _at, rel as _rel
from benchmark.reference import olmo_hybrid as ref

WIDTH_KEYS = ("hidden_size", "intermediate_size", "num_attention_heads",
              "num_key_value_heads", "linear_num_key_heads",
              "linear_num_value_heads", "linear_key_head_dim",
              "linear_value_head_dim", "linear_conv_kernel_dim")
# ``gdn_scan`` takes every scope that starts with it (``tag_of`` matches a
# kernel tag by prefix): the kernels' ``gdn_scan_fwd`` / ``gdn_scan_bwd`` and
# the re-layout round them ``gdn_scan_prep``
KERNEL_TAGS = ("flash_fwd", "flash_bwd", "gdn_scan")
MODULE_TAGS = ("ds_loss_head", "ds_embed", "gdn_conv", "gdn_gates",
               "gdn_out_norm", "qk_norm", "linear_attn", "attn", "mlp",
               "post_attn_norm", "post_ffn_norm", "norm")
# every tag a path under the module ``linear_attn`` can take
# (``gdn_layer_ms``)
GDN_LAYER_TAGS = ("gdn_scan", "gdn_conv", "gdn_gates", "gdn_out_norm",
                  "linear_attn")
# the two elementwise stages round the scan (``gdn_elementwise_ms`` /
# ``gdn_elementwise_roofline``), the re-layout of the heads to whole lane
# tiles and back among them
GDN_ELEMENTWISE_TAGS = ("gdn_conv", "gdn_out_norm")
MLP_TAG = "mlp"                     # ``dense_mlp_ms``
LINEAR, FULL = ref.LINEAR, ref.FULL
KIND_NAMES = {LINEAR: "gdn", FULL: "attn"}
CONTROLS = ref.CONTROLS
# this process's engine of THIS family, and its gauges as ``judge_train``
# folded them
_LIVE = {}

_SIZE_KEYS = ("vocab_size", "max_position_embeddings", "hidden_size",
              "intermediate_size", "num_hidden_layers", "layer_types",
              "num_attention_heads", "num_key_value_heads", "rms_norm_eps",
              "linear_num_key_heads", "linear_num_value_heads",
              "linear_key_head_dim", "linear_value_head_dim",
              "linear_conv_kernel_dim", "linear_allow_neg_eigval")


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
    from deepspeed_tpu.models.olmo_hybrid import OlmoHybridConfig
    s, m = sizes(config, rehearse), common.merged(config, "model", rehearse)
    dtypes = {"bfloat16": jnp.bfloat16, "float32": jnp.float32}
    return OlmoHybridConfig(
        **s, dtype=dtypes[m["dtype"]], param_dtype=dtypes[m["param_dtype"]],
        remat=m["remat"], remat_policy=m["remat_policy"],
        loss_chunk=m["loss_chunk"])


# ----------------------------------------------------------------- training

def _model(config, rehearse):
    from deepspeed_tpu.models.olmo_hybrid import OlmoHybridForCausalLM
    return OlmoHybridForCausalLM(model_config(config, rehearse))


def build_train(config, global_batch, seed, devices, rehearse):
    """(engine, initial parameters): ``common.build_train``'s recipe over
    ``OlmoHybridForCausalLM`` (a program without this model fails at
    ``_model``, before any work), the weights made from 64 example
    positions."""
    engine, params = common.build_train(
        _model(config, rehearse), config, global_batch, seed, devices,
        rehearse, example_len=64)
    _LIVE["engine"] = engine         # ``judge_train`` folds its gauges
    return engine, params


def program_gauges():
    """The program's ``linear_attn/*``, ``mixer/*`` and ``remat/*`` gauges
    of the LAST WARM-UP STEP, as ``judge_train`` folded them ({} before
    it)."""
    return _LIVE.get("gauges", {})


def lower_train_step(config, traffic, devices):
    """The cell's train step at real size, lowered over abstract state on
    ``devices`` (described chips)."""
    return common.lower_train_step(_model(config, rehearse=False), config,
                                   traffic, devices)


# what the reference calls each leaf of a layer, by the program's path
_SHARED_LEAVES = {"attn_norm": ("post_attn_norm", "scale"),
                  "ffn_norm": ("post_ffn_norm", "scale"),
                  "gate": ("mlp", "gate_proj", "kernel"),
                  "up": ("mlp", "up_proj", "kernel"),
                  "down": ("mlp", "down_proj", "kernel")}
LAYER_LEAVES = {
    LINEAR: dict(_SHARED_LEAVES, **{
        "in_qkvz": ("linear_attn", "in_proj_qkvz", "kernel"),
        "in_ba": ("linear_attn", "in_proj_ba", "kernel"),
        "conv": ("linear_attn", "conv"), "A_log": ("linear_attn", "A_log"),
        "dt_bias": ("linear_attn", "dt_bias"),
        "gdn_norm": ("linear_attn", "norm"),
        "out": ("linear_attn", "out_proj", "kernel")}),
    FULL: dict(_SHARED_LEAVES, **{
        "q": ("attn", "q_proj", "kernel"), "k": ("attn", "k_proj", "kernel"),
        "v": ("attn", "v_proj", "kernel"), "o": ("attn", "o_proj", "kernel"),
        "q_norm": ("attn", "q_norm", "scale"),
        "k_norm": ("attn", "k_norm", "scale")})}
# the dense branch's leaves are named alike in every layer's kind
_MLP_LEAVES = ("ffn_norm", "gate", "up", "down")


def period_of(layer_types):
    """The reference's own reading of the list: the shortest prefix whose
    repetition it is (the layer scan's body)."""
    kinds = tuple(layer_types)
    return next(n for n in range(1, len(kinds) + 1) if len(kinds) % n == 0
                and kinds == kinds[:n] * (len(kinds) // n))


def leaf_name(kind, name):
    """``gdn.in_qkvz`` / ``attn.q`` / ``mlp.gate``: a leaf by the branch it
    belongs to, as ``train.tolerance.grad_leaf_rel`` names it."""
    return ("mlp." if name in _MLP_LEAVES else KIND_NAMES[kind] + ".") + name


def layer_view(params, i, layer_types):
    """Layer i in the reference's layout, float32, from
    ``OlmoHybridForCausalLM``'s tree: slice ``i // period`` of the leaves
    under ``layers/l<i % period>``."""
    import jax.numpy as jnp
    period = period_of(layer_types)
    block = params["layers"][f"l{i % period}"]
    return {name: _at(block, path)[i // period].astype(jnp.float32)
            for name, path in LAYER_LEAVES[layer_types[i]].items()}


def top_view(params):
    import jax.numpy as jnp
    return {"embed": params["embed_tokens"].astype(jnp.float32),
            "norm": params["norm"]["scale"].astype(jnp.float32),
            "lm_head": params["lm_head"].astype(jnp.float32)}


def reference_view(params, layer_types):
    """(top, layers) in the reference's layout, float32."""
    return top_view(params), [layer_view(params, i, layer_types)
                              for i in range(len(layer_types))]


def reference_sizes(config, rehearse):
    s = sizes(config, rehearse)
    return dict(layer_types=tuple(s["layer_types"]),
                n_head=s["num_attention_heads"],
                heads=s["linear_num_value_heads"],
                dk=s["linear_key_head_dim"], dv=s["linear_value_head_dim"],
                eps=s["rms_norm_eps"])


def system_step(config, params, batch_ids, device, rehearse):
    """(loss, per-layer intermediates, gradients) of the PROGRAM's model on
    ``batch_ids`` in one jitted program, weights cast and loss formed as the
    engine's step does (``families/olmoe.system_step``). Per layer {"x_in"
    (the residual stream the layer starts from), "mixer_out", "mlp_out"
    (the branches as they are added: after their output norms)}."""
    import jax
    import jax.numpy as jnp
    model = _model(config, rehearse)
    kinds = sizes(config, rehearse)["layer_types"]
    period = period_of(kinds)
    bf16 = common.merged(config, "train", rehearse)["engine"].get(
        "data_types", {}).get("grad_dtype") == "bf16"

    def loss_fn(p, ids):
        out, vs = model.apply({"params": p}, ids, labels=ids,
                              mutable=["intermediates"])
        return out, vs["intermediates"]

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
    layers = [{k: got["layers"][f"l{i % period}"][k][0][i // period]
               for k in ("x_in", "mixer_out", "mlp_out")}
              for i in range(len(kinds))]
    return loss, layers, grads


def stream_add_differences(system):
    """(worst layer's ``|x_next - (x_in + mixer_out + mlp_out)| / |x_next|``,
    every layer's [that, ``|mixer_out + mlp_out| / |x_next|``]) over the
    SYSTEM's own values, for every layer but the last (whose adds only the
    loss sees). An honest run leaves the bf16 roundings of the two sums; a
    branch lost reads its share of the stream (the second number)."""
    import jax.numpy as jnp
    by_layer = []
    for layer, after in zip(system[:-1], system[1:]):
        x_in, mixed, mlp, nxt = (t.astype(jnp.float32) for t in (
            layer["x_in"], layer["mixer_out"], layer["mlp_out"],
            after["x_in"]))
        size = jnp.linalg.norm(nxt)
        by_layer.append([jnp.linalg.norm(nxt - (x_in + mixed + mlp)) / size,
                         jnp.linalg.norm(mixed + mlp) / size])
    return jnp.max(jnp.stack([err for err, _ in by_layer])), by_layer


@functools.lru_cache(maxsize=None)
def _reference_program(mode, sizes_items, fault=None):
    """The reference as ONE jitted program over the program's weight tree
    (``families/olmoe._reference_program``), scalars out: "forward" ->
    (loss, the unpinned differences) of its own pass; "backward" ->
    (gradient norm, {leaf: relative error}, {kind: worst branch}, every
    layer's two branches) of the reference pinned to the system's residual
    stream. ``fault``: one of ``CONTROLS``."""
    import jax
    import jax.numpy as jnp
    sizes_ = dict(sizes_items, fault=fault)
    kinds = sizes_["layer_types"]

    def view(w):
        return reference_view(w, kinds)

    @jax.jit
    def forward(p, ids, system_layers):
        loss, detail = ref.loss(p, ids, view, look=lambda i, want: [
            _rel(system_layers[i][k], want[k])
            for k in ("mixer_out", "mlp_out")], **sizes_)
        worst, adds = stream_add_differences(system_layers)
        return loss, {"own_stream_by_layer": detail["layers"],
                      "stream_add_rel": worst, "stream_add_by_layer": adds,
                      "stream_start_rel": _rel(system_layers[0]["x_in"],
                                               top_view(p)["embed"][ids])}

    @jax.jit
    def backward(p, ids, system_layers, system_grads):
        top, layers = view(p)

        def fold(i, kind, grads, mixer_out, mlp_out):
            """(sum of squares, {leaf: relative error}, the two branches'
            relative errors) of one layer's gradients against the
            system's; the top's with ``i`` None."""
            if i is None:
                got = top_view(system_grads)
                names = {name: name for name in grads}
            else:
                got = layer_view(system_grads, i, kinds)
                names = {name: leaf_name(kind, name) for name in grads}
            rels = {names[n]: _rel(got[n], grads[n]) for n in grads}
            outs = None if i is None else [
                _rel(system_layers[i]["mixer_out"], mixer_out),
                _rel(system_layers[i]["mlp_out"], mlp_out)]
            return sum(jnp.sum(jnp.square(g)) for g in grads.values()), \
                rels, outs

        _, folded, (top_sq, leaves, _) = ref.pinned_backward(
            top, layers, ids, system_layers, fold, **sizes_)
        branches = {"gdn_out_rel": 0.0, "attn_out_rel": 0.0,
                    "mlp_out_rel": 0.0}
        squares = top_sq
        for kind, (sq, rels, (mixer, mlp)) in zip(kinds, folded):
            squares += sq
            for name, err in rels.items():
                leaves[name] = jnp.maximum(leaves.get(name, 0.0), err)
            key = KIND_NAMES[kind] + "_out_rel"
            branches[key] = jnp.maximum(branches[key], mixer)
            branches["mlp_out_rel"] = jnp.maximum(branches["mlp_out_rel"],
                                                  mlp)
        return jnp.sqrt(squares), leaves, branches, \
            [outs for _, _, outs in folded]

    return {"forward": forward, "backward": backward}[mode]


def _reference(mode, config, params, batch_ids, device, rehearse, control,
               *more):
    import jax
    run = _reference_program(
        mode, tuple(sorted(reference_sizes(config, rehearse).items())),
        control)
    return run(jax.device_put(params, device),
               jax.device_put(np.asarray(batch_ids), device), *more)


def system_grad_norm(grads):
    """The norm of the system's gradient tree, in float32."""
    import jax
    import jax.numpy as jnp
    return jax.jit(lambda g: ref.grad_norm(jax.tree_util.tree_map(
        lambda x: x.astype(jnp.float32), g)))(grads)


def compare(config, params, batch_ids, device, rehearse, system,
            control=None):
    """(reference loss, reference gradient norm, differences) of ``system``
    (``system_step``'s three values) against the plain reference on the same
    weights and batch: the reference's OWN forward pass first (handed the
    system's values only to compare with) for the loss and the unpinned
    checks; then its pass pinned to the system's residual stream for each
    branch's output, the gradient norm and every gradient leaf.
    ``control``: one of ``CONTROLS``, the reference computed with that
    fault."""
    import jax
    _, layers, grads = system
    loss, detail = jax.device_get(_reference(
        "forward", config, params, batch_ids, device, rehearse, control,
        layers))
    diffs = jax.tree_util.tree_map(float, detail)
    diffs["system_grad_norm"] = float(system_grad_norm(grads))
    gnorm, leaves, branches, by_layer = jax.device_get(_reference(
        "backward", config, params, batch_ids, device, rehearse, control,
        layers, grads))
    diffs["grad_leaf_rel"] = {n: float(v) for n, v in leaves.items()}
    diffs.update({k: float(v) for k, v in branches.items()})
    diffs["pinned_by_layer"] = [[float(v) for v in pair]
                                for pair in by_layer]
    return float(loss), float(gnorm), diffs


def reference_train(config, params, batch_ids, devices, rehearse):
    """``compare`` of the program's model as the configuration builds it.
    Call before the engine's first step."""
    return compare(config, params, batch_ids, devices[0], rehearse,
                   system_step(config, params, batch_ids, devices[0],
                               rehearse))


BRANCH_CHECKS = {"gdn_out_rel": "deltanet_branch_matches_reference",
                 "attn_out_rel": "attention_branch_matches_reference",
                 "mlp_out_rel": "mlp_branch_matches_reference"}
_GAUGES = ("linear_attn/", "mixer/", "remat/")


def judge_train(config, got_loss, got_gnorm, want_loss, want_gnorm,
                differences=None):
    """The first step's loss and gradient norm against the reference's;
    with ``differences`` (``reference_train``'s third value) each kind's
    branch, every gradient leaf, and the three unpinned checks; and the
    program's ``linear_attn/*`` / ``mixer/*`` / ``remat/*`` gauges, folded
    here after warm-up."""
    tol = config["train"]["tolerance"]
    checks = {
        "first_loss_matches_reference":
            abs(got_loss - want_loss) <= tol["loss_abs"],
        "first_grad_norm_matches_reference":
            abs(got_gnorm - want_gnorm) <= tol["grad_norm_rel"] * want_gnorm}
    detail = {"loss": [got_loss, want_loss], "loss_abs_tol": tol["loss_abs"],
              "grad_norm": [got_gnorm, want_gnorm],
              "grad_norm_rel_tol": tol["grad_norm_rel"]}
    if differences is not None:
        diffs = dict(differences)
        for key, check in BRANCH_CHECKS.items():
            checks[check] = diffs[key] <= tol[key]
        # the gradients compared leaf by leaf are the step's own: the
        # engine's norm is of the same bf16 gradients
        checks["compared_gradients_are_the_steps"] = \
            abs(diffs["system_grad_norm"] - got_gnorm) \
            <= tol["grad_norm_rel"] * got_gnorm
        leaves, limits = diffs["grad_leaf_rel"], tol["grad_leaf_rel"]
        over = sorted(n for n in limits
                      if not leaves.get(n, float("inf")) <= limits[n])
        checks["gradients_match_reference_leaf_by_leaf"] = \
            not over and set(leaves) == set(limits)
        # not pinned: the first layer's mixer of the two own passes (both
        # start from the same embedding rows), the stream's start and the
        # system's residual adds
        checks["first_mixer_matches_reference_on_its_own_stream"] = \
            diffs["own_stream_by_layer"][0][0] <= tol["own_stream_first_rel"]
        checks["stream_starts_from_the_embedding"] = \
            diffs["stream_start_rel"] <= tol["stream_start_rel"]
        checks["residual_stream_adds_up"] = \
            diffs["stream_add_rel"] <= tol["stream_add_rel"]
        detail["differences"] = dict(
            diffs, gradient_leaves_over=over,
            tolerances={k: tol[k] for k in (
                *BRANCH_CHECKS, "grad_leaf_rel", "own_stream_first_rel",
                "stream_start_rel", "stream_add_rel")})
    # this family's own engine, fenced and folded here, after warm-up
    engine = _LIVE.get("engine")
    gauges = _LIVE["gauges"] = \
        engine.telemetry_flush()["gauges"] if engine is not None else {}
    found = {k: v for k, v in gauges.items() if k.startswith(_GAUGES)}
    if found:
        detail["program_gauges"] = found
    return checks, detail


# ------------------------------------------------- operations and bytes

def _layer_counts(config, rehearse):
    s = sizes(config, rehearse)
    return s, {kind: list(s["layer_types"]).count(kind)
               for kind in (LINEAR, FULL)}


def active_matmul_params(config, rehearse=False):
    """Parameters one token is multiplied with: a DeltaNet layer's three
    projections, the attention layer's four, every layer's SwiGLU, and the
    output head (the embedding lookup is a gather; the convolution's taps,
    the gates and the rule are not matmul parameters)."""
    s, n = _layer_counts(config, rehearse)
    H = s["hidden_size"]
    key = s["linear_num_key_heads"] * s["linear_key_head_dim"]
    val = s["linear_num_value_heads"] * s["linear_value_head_dim"]
    linear = H * (2 * key + 2 * val + 2 * s["linear_num_value_heads"]) \
        + val * H
    return n[LINEAR] * linear + n[FULL] * 4 * H * H \
        + s["num_hidden_layers"] * 3 * H * s["intermediate_size"] \
        + s["vocab_size"] * H


def _scan_flops_per_token(s):
    """The RECURRENCE's flops a token a layer, forward, at the PUBLISHED
    head sizes: read S^T k, the rank-one update, read S^T q, 2 Dk Dv each a
    value head."""
    return 6 * s["linear_key_head_dim"] * s["linear_value_head_dim"] \
        * s["linear_num_value_heads"]


def train_flops_per_token(config, seq_len, rehearse=False):
    """6 a matmul parameter (2 forward, 4 backward) + causal attention in
    the attention layers alone (6 S hidden a layer: heads x head_dim is the
    hidden size) + the delta rule's recurrence in the DeltaNet layers (3 x
    6 Dk Dv a value head)."""
    s, n = _layer_counts(config, rehearse)
    return 6 * active_matmul_params(config, rehearse) \
        + 6 * n[FULL] * seq_len * s["hidden_size"] \
        + n[LINEAR] * 3 * _scan_flops_per_token(s)


def train_attention_flops_per_step(config, batch, seq_len, rehearse=False):
    """Causal flops of the flash forward and backward kernels in one step:
    the attention layers the list has."""
    s, n = _layer_counts(config, rehearse)
    return n[FULL] * roofline.causal_attention_train_flops(
        batch, s["num_attention_heads"], seq_len,
        s["hidden_size"] // s["num_attention_heads"])


def gdn_scan_flops_and_bytes(config, tokens, rehearse=False, itemsize=2):
    """(flops, bytes) the delta rule of one step NEEDS over all DeltaNet
    layers for ``tokens`` tokens, at the PUBLISHED head sizes whatever lanes
    the kernels compute on (``families/qwen3_next.gdn_scan_flops_and_bytes``'s
    count at this family's keys; that function reads Qwen3-Next's
    ``full_attention_interval``, which this family's file does not have).
    Flops: 6 Dk Dv a token a value head forward, x 3 with the backward
    pass. Bytes: q, k (per key head), v, o (per value head) at ``itemsize``
    and g, beta (float32), forward; the same again as cotangents, and q, k,
    v, g, beta read once more by the backward pass."""
    s, n = _layer_counts(config, rehearse)
    hk, dk = s["linear_num_key_heads"], s["linear_key_head_dim"]
    hv, dv = s["linear_num_value_heads"], s["linear_value_head_dim"]
    inputs = itemsize * (2 * hk * dk + hv * dv) + 2 * 4 * hv
    out = itemsize * hv * dv
    return (n[LINEAR] * tokens * 3 * _scan_flops_per_token(s),
            n[LINEAR] * tokens * (3 * inputs + 2 * out))


def gdn_elementwise_bytes_per_step(config, tokens, rehearse=False,
                                   itemsize=2):
    """Bytes the two elementwise stages round the rule NEED to move in one
    step over all DeltaNet layers, each array once, at the PUBLISHED head
    sizes whatever form or layout runs. The convolution: the projection's q
    | k | v columns read and q, k, v written forward; their cotangents and
    the columns read and the columns' cotangent written backward — five
    arrays of ``2 Hk Dk + Hv Dv`` columns. The gated norm: o and z read and
    the result written forward; the cotangent, o and z read and the
    cotangents of o and z written backward — eight arrays of ``Hv Dv``
    columns. The taps and the weight (KBs), what a re-layout to whole lane
    tiles moves and what a recomputation under remat reads again are not
    counted: the share can only fall short."""
    s, n = _layer_counts(config, rehearse)
    key = s["linear_num_key_heads"] * s["linear_key_head_dim"]
    val = s["linear_num_value_heads"] * s["linear_value_head_dim"]
    return n[LINEAR] * tokens * itemsize * (5 * (2 * key + val) + 8 * val)
