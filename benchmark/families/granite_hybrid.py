"""The Granite 4.0-H family (``model_type: granitemoehybrid``): how its
configuration file becomes a running system.

The members ``benchmark/families/__init__.py`` lists for training, none of
serving's. The model is ``deepspeed_tpu.models.granite_hybrid`` built
through ``dstpu.initialize`` as the other cells' are; the plain reference is
``benchmark/reference/granite_hybrid.py``. Key names are the published
config's; the layer plan is the published list ``layer_types``, handed to
the model and to the reference as the file has it. A configuration holds
every matrix whole but the embedding (which is the head), of which
``vocab_size`` is the slice held here.

``correct`` is the comparison of ``families/nemotron_h.py`` for a model of
TWO dense branches a layer (no router, so nothing to pin but the stream):
the loss of the two OWN forward passes; then, of a reference pass PINNED to
the system's residual stream (``reference/granite_hybrid.pinned_backward``:
every branch starts from the system's values — at random initialisation the
branches are the stream, and a bf16 run drifts from a float32 one layer over
layer), each kind's branch as one vector (``ssm_out_rel``, ``attn_out_rel``,
``mlp_out_rel``), the gradient norm and every gradient leaf as a vector, a
leaf named by its layer's kind; and, because a pinned pass is blind to the
stream itself, three checks that are NOT pinned: the first layer's mixer of
the two own passes, the stream's start against ``embedding_multiplier x
E[ids]``, and the system's residual adds WITH ``residual_multiplier`` — the
multipliers read from the file here, not from the program; each against the
file's ``train.tolerance``. The reference's gradients are walked a branch at
a time and folded into those numbers as they come: the float32 tree (3.1 GB)
never stands whole beside the engine's state.
"""

import functools

import numpy as np

from benchmark import roofline
from benchmark.families import common
from benchmark.families.common import at as _at, rel as _rel
from benchmark.reference import granite_hybrid as ref

WIDTH_KEYS = ("hidden_size", "intermediate_size", "shared_intermediate_size",
              "num_attention_heads", "num_key_value_heads", "mamba_n_heads",
              "mamba_d_head", "mamba_d_state", "mamba_n_groups",
              "mamba_d_conv", "mamba_expand", "embedding_multiplier",
              "residual_multiplier", "attention_multiplier",
              "logits_scaling")
# ``ssd_scan`` takes every scope that starts with it (``tag_of`` matches a
# kernel tag by prefix): the kernels' ``ssd_scan_fwd`` / ``ssd_scan_bwd``, the
# re-layout round them ``ssd_scan_prep`` and the XLA form's ``ssd_scan``
KERNEL_TAGS = ("flash_fwd", "flash_bwd", "ssd_scan")
MODULE_TAGS = ("ds_loss_head", "ds_embed", "ssm_conv", "ssm_gates",
               "ssm_norm", "mamba", "attn", "shared_mlp", "input_norm",
               "post_norm", "norm")
# every tag a path under the module ``mamba`` can take (``ssm_layer_ms``)
SSM_LAYER_TAGS = ("ssd_scan", "ssm_conv", "ssm_gates", "ssm_norm", "mamba")
MLP_TAG = "shared_mlp"              # ``dense_mlp_ms``
SSM_NORM_TAG = "ssm_norm"           # ``ssm_norm_roofline``
MAMBA, ATTENTION = "mamba", "attention"
KIND_NAMES = {MAMBA: "ssm", ATTENTION: "attn"}
# this process's engine of THIS family, and its gauges as ``judge_train``
# folded them
_LIVE = {}

_SIZE_KEYS = ("vocab_size", "max_position_embeddings", "hidden_size",
              "shared_intermediate_size", "num_hidden_layers", "layer_types",
              "rms_norm_eps", "mamba_n_heads", "mamba_d_head",
              "mamba_d_state", "mamba_n_groups", "mamba_d_conv",
              "mamba_conv_bias", "ssd_chunk", "num_attention_heads",
              "num_key_value_heads", "embedding_multiplier",
              "residual_multiplier", "attention_multiplier",
              "logits_scaling")


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
    from deepspeed_tpu.models.granite_hybrid import GraniteHybridConfig
    s, m = sizes(config, rehearse), common.merged(config, "model", rehearse)
    dtypes = {"bfloat16": jnp.bfloat16, "float32": jnp.float32}
    return GraniteHybridConfig(
        **s, dtype=dtypes[m["dtype"]], param_dtype=dtypes[m["param_dtype"]],
        remat=m["remat"], remat_policy=m["remat_policy"],
        loss_chunk=m["loss_chunk"])


# ----------------------------------------------------------------- training

def _model(config, rehearse):
    from deepspeed_tpu.models.granite_hybrid import GraniteHybridForCausalLM
    return GraniteHybridForCausalLM(model_config(config, rehearse))


def build_train(config, global_batch, seed, devices, rehearse):
    """(engine, initial parameters): ``common.build_train``'s recipe over
    ``GraniteHybridForCausalLM`` (a program without this model fails at
    ``_model``, before any work), the weights made from 64 example
    positions."""
    engine, params = common.build_train(
        _model(config, rehearse), config, global_batch, seed, devices,
        rehearse, example_len=64)
    _LIVE["engine"] = engine         # ``judge_train`` folds its gauges
    return engine, params


def program_gauges():
    """The program's ``ssm/*`` and ``mixer/*`` gauges of the LAST WARM-UP
    STEP, as ``judge_train`` folded them ({} before it)."""
    return _LIVE.get("gauges", {})


def lower_train_step(config, traffic, devices):
    """The cell's train step at real size, lowered over abstract state on
    ``devices`` (described chips)."""
    return common.lower_train_step(_model(config, rehearse=False), config,
                                   traffic, devices)


# what the reference calls each leaf of a layer, by the program's path
_SHARED_LEAVES = {"in_norm": ("input_norm", "scale"),
                  "post_norm": ("post_norm", "scale"),
                  "mlp_in": ("shared_mlp", "input_linear", "kernel"),
                  "mlp_out": ("shared_mlp", "output_linear", "kernel")}
LAYER_LEAVES = {
    MAMBA: dict(_SHARED_LEAVES, **{
        "in_proj": ("mamba", "in_proj", "kernel"),
        "conv": ("mamba", "conv"), "conv_bias": ("mamba", "conv_bias"),
        "A_log": ("mamba", "A_log"), "dt_bias": ("mamba", "dt_bias"),
        "D": ("mamba", "D"), "ssm_norm": ("mamba", "norm"),
        "out_proj": ("mamba", "out_proj", "kernel")}),
    ATTENTION: dict(_SHARED_LEAVES, **{
        "q": ("attn", "q_proj", "kernel"), "k": ("attn", "k_proj", "kernel"),
        "v": ("attn", "v_proj", "kernel"),
        "o": ("attn", "o_proj", "kernel")})}
# the two dense branches' leaves are named alike in every layer's kind
_MLP_LEAVES = ("post_norm", "mlp_in", "mlp_out")


def leaf_name(kind, name):
    """``ssm.in_proj`` / ``attn.q`` / ``mlp.mlp_in``: a leaf by the branch
    it belongs to, as ``train.tolerance.grad_leaf_rel`` names it."""
    return ("mlp." if name in _MLP_LEAVES else KIND_NAMES[kind] + ".") + name


def layer_view(block, kind):
    """One ``layer_<i>`` subtree in the reference's layout, float32."""
    import jax.numpy as jnp
    return {name: _at(block, path).astype(jnp.float32)
            for name, path in LAYER_LEAVES[kind].items()}


def reference_view(params, layer_types):
    """(top, layers) in the reference's layout, float32, from
    ``GraniteHybridForCausalLM``'s tree: layer i is ``layer_<i>``."""
    import jax.numpy as jnp
    top = {"embed": params["embed_tokens"].astype(jnp.float32),
           "norm": params["norm"]["scale"].astype(jnp.float32)}
    return top, [layer_view(params[f"layer_{i}"], kind)
                 for i, kind in enumerate(layer_types)]


def reference_sizes(config, rehearse):
    s = sizes(config, rehearse)
    return dict(layer_types=tuple(s["layer_types"]),
                n_kv_head=s["num_key_value_heads"],
                head_dim=s["hidden_size"] // s["num_attention_heads"],
                eps=s["rms_norm_eps"], heads=s["mamba_n_heads"],
                mamba_head_dim=s["mamba_d_head"],
                n_groups=s["mamba_n_groups"], state=s["mamba_d_state"],
                embedding_multiplier=float(s["embedding_multiplier"]),
                residual_multiplier=float(s["residual_multiplier"]),
                attention_multiplier=float(s["attention_multiplier"]),
                logits_scaling=float(s["logits_scaling"]))


def system_step(config, params, batch_ids, device, rehearse):
    """(loss, per-layer intermediates, gradients) of the PROGRAM's model on
    ``batch_ids`` in one jitted program, weights cast and loss formed as the
    engine's step does (``families/olmoe.system_step``). Per layer {"x_in"
    (the residual stream the layer starts from), "mixer_out", "mlp_out"
    (the branches as they leave their modules, before the multiplier)}."""
    import jax
    import jax.numpy as jnp
    model = _model(config, rehearse)
    n_layers = sizes(config, rehearse)["num_hidden_layers"]
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
    layers = [{k: got[f"layer_{i}"][k][0]
               for k in ("x_in", "mixer_out", "mlp_out")}
              for i in range(n_layers)]
    return loss, layers, grads


def stream_add_differences(system, residual_multiplier):
    """(worst layer's ``|x_next - (x_in + r mixer_out + r mlp_out)| /
    |x_next|``, every layer's [that, ``r |mixer_out + mlp_out| / |x_next|``])
    over the SYSTEM's own values with the FILE's multiplier, for every
    layer but the last (whose adds only the loss sees). An honest run
    leaves the bf16 roundings of the two sums; a branch lost, or added
    without the multiplier, reads its share of the stream (the second
    number, or 3.5 x it)."""
    import jax.numpy as jnp
    by_layer = []
    for layer, after in zip(system[:-1], system[1:]):
        x_in, mixed, mlp, nxt = (t.astype(jnp.float32) for t in (
            layer["x_in"], layer["mixer_out"], layer["mlp_out"],
            after["x_in"]))
        size = jnp.linalg.norm(nxt)
        added = residual_multiplier * (mixed + mlp)
        by_layer.append([jnp.linalg.norm(nxt - (x_in + added)) / size,
                         jnp.linalg.norm(added) / size])
    return jnp.max(jnp.stack([err for err, _ in by_layer])), by_layer


@functools.lru_cache(maxsize=None)
def _reference_program(mode, sizes_items):
    """The reference as ONE jitted program over the program's weight tree
    (``families/olmoe._reference_program``), scalars out: "forward" ->
    (loss, the unpinned differences) of its own pass; "backward" ->
    (gradient norm, {leaf: relative error}, {kind: worst branch}, every
    layer's two branches) of the reference pinned to the system's residual
    stream."""
    import jax
    import jax.numpy as jnp
    sizes_ = dict(sizes_items)
    kinds, r = sizes_["layer_types"], sizes_["residual_multiplier"]

    def view(w):
        return reference_view(w, kinds)

    @jax.jit
    def forward(p, ids, system_layers):
        loss, detail = ref.loss(p, ids, view, look=lambda i, want: [
            _rel(system_layers[i][k], want[k])
            for k in ("mixer_out", "mlp_out")], **sizes_)
        own = detail["layers"]
        worst, adds = stream_add_differences(system_layers, r)
        start = ref.embed(view(p)[0], ids, sizes_["embedding_multiplier"])
        return loss, {"own_stream_by_layer": own, "stream_add_rel": worst,
                      "stream_add_by_layer": adds,
                      "stream_start_rel": _rel(system_layers[0]["x_in"],
                                               start)}

    @jax.jit
    def backward(p, ids, system_layers, system_grads):
        top, layers = view(p)

        def fold(i, kind, grads, mixer_out, mlp_out):
            """(sum of squares, {leaf: relative error}, the two branches'
            relative errors) of one layer's gradients against the
            system's; the top's with ``i`` None."""
            if i is None:
                got = reference_view(system_grads, ())[0]
                names = {name: name for name in grads}
            else:
                got = layer_view(system_grads[f"layer_{i}"], kind)
                names = {name: leaf_name(kind, name) for name in grads}
            rels = {names[n]: _rel(got[n], grads[n]) for n in grads}
            outs = None if i is None else [
                _rel(system_layers[i]["mixer_out"], mixer_out),
                _rel(system_layers[i]["mlp_out"], mlp_out)]
            return sum(jnp.sum(jnp.square(g)) for g in grads.values()), \
                rels, outs

        _, folded, (top_sq, leaves, _) = ref.pinned_backward(
            top, layers, ids, system_layers, fold, **sizes_)
        branches = {"ssm_out_rel": 0.0, "attn_out_rel": 0.0,
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


def _reference(mode, config, params, batch_ids, device, rehearse, *more):
    import jax
    run = _reference_program(
        mode, tuple(sorted(reference_sizes(config, rehearse).items())))
    return run(jax.device_put(params, device),
               jax.device_put(np.asarray(batch_ids), device), *more)


def system_grad_norm(grads):
    """The norm of the system's gradient tree, in float32."""
    import jax
    import jax.numpy as jnp
    return jax.jit(lambda g: ref.grad_norm(jax.tree_util.tree_map(
        lambda x: x.astype(jnp.float32), g)))(grads)


def compare(config, params, batch_ids, device, rehearse, system):
    """(reference loss, reference gradient norm, differences) of ``system``
    (``system_step``'s three values) against the plain reference on the same
    weights and batch: the reference's OWN forward pass first (handed the
    system's values only to compare with) for the loss and the unpinned
    checks; then its pass pinned to the system's residual stream for each
    branch's output, the gradient norm and every gradient leaf."""
    import jax
    _, layers, grads = system
    loss, detail = jax.device_get(_reference(
        "forward", config, params, batch_ids, device, rehearse, layers))
    diffs = jax.tree_util.tree_map(float, detail)
    diffs["system_grad_norm"] = float(system_grad_norm(grads))
    gnorm, leaves, branches, by_layer = jax.device_get(_reference(
        "backward", config, params, batch_ids, device, rehearse, layers,
        grads))
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


BRANCH_CHECKS = {"ssm_out_rel": "state_space_branch_matches_reference",
                 "attn_out_rel": "attention_branch_matches_reference",
                 "mlp_out_rel": "mlp_branch_matches_reference"}


def judge_train(config, got_loss, got_gnorm, want_loss, want_gnorm,
                differences=None):
    """The first step's loss and gradient norm against the reference's;
    with ``differences`` (``reference_train``'s third value) each kind's
    branch, every gradient leaf, and the three unpinned checks; and the
    program's ``ssm/*`` / ``mixer/*`` gauges, folded here after warm-up:
    the scan took the kernels, no mixer stage fell to its XLA form."""
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
        checks["stream_starts_from_the_scaled_embedding"] = \
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
    found = {k: v for k, v in gauges.items()
             if k.startswith(("ssm/", "mixer/"))}
    if found:
        detail["program_gauges"] = found
    return checks, detail


# ------------------------------------------------- operations and bytes

def _layer_counts(config, rehearse):
    s = sizes(config, rehearse)
    return s, {kind: list(s["layer_types"]).count(kind)
               for kind in (MAMBA, ATTENTION)}


def active_matmul_params(config, rehearse=False):
    """Parameters one token is multiplied with: a Mamba-2 layer's two
    projections, the attention layer's four, every layer's MLP, and the
    output head (the embedding lookup is a gather; the convolution's taps,
    the gates and the scan are not matmul parameters)."""
    s, n = _layer_counts(config, rehearse)
    H = s["hidden_size"]
    d_inner = s["mamba_n_heads"] * s["mamba_d_head"]
    conv_dim = d_inner + 2 * s["mamba_n_groups"] * s["mamba_d_state"]
    mamba = H * (d_inner + conv_dim + s["mamba_n_heads"]) + d_inner * H
    kv = H * s["num_key_value_heads"] // s["num_attention_heads"]
    attention = 2 * H * H + 2 * H * kv
    mlp = 3 * H * s["shared_intermediate_size"]
    return n[MAMBA] * mamba + n[ATTENTION] * attention \
        + s["num_hidden_layers"] * mlp + s["vocab_size"] * H


def _scan_flops_per_token(s):
    """The RECURRENCE's flops a token a layer, forward: a head's state is
    [P, N]; the decay (P N multiplies), the outer product ``dt x (x) B``
    added in (2 P N) and the read-out ``S C`` (2 P N): 5 P N a head."""
    return 5 * s["mamba_d_head"] * s["mamba_d_state"] * s["mamba_n_heads"]


def train_flops_per_token(config, seq_len, rehearse=False):
    """6 a matmul parameter (2 forward, 4 backward) + causal attention in
    the attention layers alone (6 S hidden a layer: heads x head_dim is the
    hidden size) + the state-space recurrence in the Mamba-2 layers (3 x
    5 P N a head)."""
    s, n = _layer_counts(config, rehearse)
    return 6 * active_matmul_params(config, rehearse) \
        + 6 * n[ATTENTION] * seq_len * s["hidden_size"] \
        + n[MAMBA] * 3 * _scan_flops_per_token(s)


def train_attention_flops_per_step(config, batch, seq_len, rehearse=False):
    """Causal flops of the flash forward and backward kernels in one step:
    the attention layers the list has."""
    s, n = _layer_counts(config, rehearse)
    return n[ATTENTION] * roofline.causal_attention_train_flops(
        batch, s["num_attention_heads"], seq_len,
        s["hidden_size"] // s["num_attention_heads"])


def ssd_scan_flops_and_bytes(config, tokens, rehearse=False, itemsize=2):
    """(flops, bytes) the state-space scan of one step NEEDS over all
    Mamba-2 layers for ``tokens`` tokens — the RECURRENCE's work, whatever
    implements it (``families/nemotron_h.ssd_scan_flops_and_bytes``'s count
    at this family's keys: 64 heads in ONE group, so B and C are 128
    columns each where Nemotron's are 1,024). Flops: 5 P N a token a head
    forward, x 3 with the backward pass. Bytes: x and y [heads x P] and B, C
    [groups x N] at ``itemsize`` and dt (float32, a head) once forward;
    their five cotangents once; and x, B, C, dt read once more by the
    backward pass."""
    s, n = _layer_counts(config, rehearse)
    x = itemsize * s["mamba_n_heads"] * s["mamba_d_head"]
    bc = 2 * itemsize * s["mamba_n_groups"] * s["mamba_d_state"]
    dt = 4 * s["mamba_n_heads"]
    inputs, out = x + bc + dt, x
    return (n[MAMBA] * tokens * 3 * _scan_flops_per_token(s),
            n[MAMBA] * tokens * (3 * inputs + 2 * out))


def ssm_norm_bytes_per_step(config, tokens, rehearse=False, itemsize=2):
    """Bytes the gated RMS norms of one step NEED to move over all Mamba-2
    layers, each array once: y and z in and the normed y out forward; the
    cotangent, y and z in and the cotangents of y and z out backward —
    eight arrays of ``d_inner`` columns at ``itemsize`` a token. The weight
    (16 KB) and what a recomputation under remat reads again are not
    counted: the share can only fall short."""
    s, n = _layer_counts(config, rehearse)
    return n[MAMBA] * tokens * 8 * itemsize \
        * s["mamba_n_heads"] * s["mamba_d_head"]
