"""The OLMoE family: how its configuration file becomes a running system.

The members ``benchmark/families/__init__.py`` lists for training, none of
serving's. The model is ``deepspeed_tpu.models.llama`` with experts and
QK-norm on (``olmoe_1b_7b``), built through ``dstpu.initialize`` as the
GPT-2 cells' is; the plain reference is ``benchmark/reference/olmoe.py``.
Key names are the published config's (``hidden_size``,
``num_hidden_layers``, ...).

``correct`` holds more than the first step's loss and gradient norm here. At
random initialisation (normal 0.02) everything one layer does is a ~1 %
perturbation of the residual stream: the loss is ln(vocabulary) and the
gradient norm the head's and the embedding's whatever the layer computes
(the expert and router gradients are half a percent of the norm's square),
so those two numbers cannot see a renormalised top-k, a dropped token, a
missing QK-norm, or an expert gradient that is zero or rounded to fp8.
``reference_train`` therefore also runs the SYSTEM's forward and backward
pass once (``system_step``: the program's model on the engine's weights, cast
as the engine casts them, the loss formed as the engine forms it) and
compares, against the reference's own passes, which experts each token was
sent to, what the attention branch and the expert branch put out, and EVERY
gradient leaf as a vector; ``judge_train`` holds all of it to the file's
``train.tolerance``.
"""

import functools

import numpy as np

from benchmark import roofline
from benchmark.families import common
from benchmark.families.common import at as _at
from benchmark.reference import olmoe as ref

WIDTH_KEYS = ("hidden_size", "intermediate_size", "num_attention_heads",
              "num_key_value_heads", "num_experts", "num_experts_per_tok")
KERNEL_TAGS = ("flash_fwd", "flash_bwd", "moe_gmm")   # and their variants
MODULE_TAGS = ("ds_loss_head", "ds_embed", "moe_router", "moe_dispatch",
               "moe_act", "moe_combine", "qk_norm", "attn", "mlp",
               "input_norm", "post_attn_norm", "norm")
# the scopes whose self time is what the expert mechanism costs outside its
# matmuls (``moe_dispatch_ms``)
DISPATCH_TAGS = ("moe_router", "moe_dispatch", "moe_combine")

_SIZE_KEYS = ("vocab_size", "max_position_embeddings", "hidden_size",
              "intermediate_size", "num_hidden_layers", "num_attention_heads",
              "num_key_value_heads", "num_experts", "num_experts_per_tok",
              "norm_topk_prob", "rms_norm_eps", "rope_theta",
              "router_aux_loss_coef", "router_z_loss_coef", "qk_norm")
# this process's engine, and its gauges as ``judge_train`` folded them
_LIVE = {}


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
    from deepspeed_tpu.models.llama import olmoe_1b_7b
    s, m = sizes(config, rehearse), common.merged(config, "model", rehearse)
    dtypes = {"bfloat16": jnp.bfloat16, "float32": jnp.float32}
    heads, kv = s["num_attention_heads"], s["num_key_value_heads"]
    return olmoe_1b_7b(
        vocab_size=s["vocab_size"], hidden_size=s["hidden_size"],
        intermediate_size=s["intermediate_size"],
        n_layers=s["num_hidden_layers"], n_heads=heads,
        n_kv_heads=0 if kv == heads else kv,
        max_seq_len=s["max_position_embeddings"],
        rope_theta=float(s["rope_theta"]), rms_eps=s["rms_norm_eps"],
        num_experts=s["num_experts"],
        num_experts_per_tok=s["num_experts_per_tok"],
        norm_topk_prob=s["norm_topk_prob"], qk_norm=s["qk_norm"],
        router_aux_loss_coef=s["router_aux_loss_coef"],
        router_z_loss_coef=s["router_z_loss_coef"],
        dtype=dtypes[m["dtype"]], param_dtype=dtypes[m["param_dtype"]],
        scan_layers=m["scan_layers"], remat=m["remat"],
        remat_policy=m["remat_policy"], loss_chunk=m["loss_chunk"])


# ----------------------------------------------------------------- training

def _model(config, rehearse):
    from deepspeed_tpu.models.llama import LlamaForCausalLM
    return LlamaForCausalLM(model_config(config, rehearse))


def build_train(config, global_batch, seed, devices, rehearse):
    """(engine, initial parameters): ``common.build_train``'s recipe over
    ``LlamaForCausalLM`` (a program without this model fails at ``_model``,
    before any work), the weights made from a full-length example."""
    model = _model(config, rehearse)
    engine, params = common.build_train(
        model, config, global_batch, seed, devices, rehearse,
        example_len=model.config.max_seq_len)
    _LIVE["engine"] = engine
    return engine, params


def program_gauges():
    """The program's gauges as ``judge_train`` folded them ({} before it):
    the ``moe/*`` gauges of the LAST WARM-UP STEP, the same step of every
    run whatever the window's length, read outside any window."""
    return _LIVE.get("gauges", {})


def lower_train_step(config, traffic, devices):
    """The cell's train step at real size, lowered over abstract state on
    ``devices`` (described chips)."""
    return common.lower_train_step(_model(config, rehearse=False), config,
                                   traffic, devices)


# what the reference calls each leaf of a layer, by the program's path
_LAYER_LEAVES = {
    "input_norm": ("input_norm", "scale"),
    "post_attn_norm": ("post_attn_norm", "scale"),
    "q": ("attn", "q_proj", "kernel"), "k": ("attn", "k_proj", "kernel"),
    "v": ("attn", "v_proj", "kernel"), "o": ("attn", "o_proj", "kernel"),
    "q_norm": ("attn", "q_norm", "scale"),
    "k_norm": ("attn", "k_norm", "scale"),
    "router": ("mlp", "router"), "gate": ("mlp", "gate_proj"),
    "up": ("mlp", "up_proj"), "down": ("mlp", "down_proj")}


def reference_view(params, n_layers):
    """(top, layers) in the reference's layout, float32, from
    ``LlamaForCausalLM``'s tree, layer-stacked or not."""
    import jax
    import jax.numpy as jnp
    params = jax.tree_util.tree_map(lambda x: x.astype(jnp.float32), params)
    top = {"embed": params["embed_tokens"], "norm": params["norm"]["scale"],
           "lm_head": params["lm_head"]}
    if "layers" in params:
        blocks = [jax.tree_util.tree_map(lambda x: x[i],
                                         params["layers"]["blk"])
                  for i in range(n_layers)]
    else:
        blocks = [params[f"layers_{i}"] for i in range(n_layers)]
    layers = [{name: _at(blk, path) for name, path in _LAYER_LEAVES.items()}
              for blk in blocks]
    return top, layers


def reference_sizes(config, rehearse):
    s = sizes(config, rehearse)
    return dict(n_head=s["num_attention_heads"],
                k=s["num_experts_per_tok"], eps=s["rms_norm_eps"],
                theta=float(s["rope_theta"]),
                norm_topk_prob=s["norm_topk_prob"], qk_norm=s["qk_norm"],
                balance_coeff=s["router_aux_loss_coef"],
                z_coeff=s["router_z_loss_coef"])


def system_step(config, params, batch_ids, device, rehearse):
    """(loss, per-layer intermediates, gradients) of the PROGRAM's model on
    ``batch_ids``, in one jitted program: the weights cast as the engine's
    step casts them, the loss formed as the engine forms it (cross-entropy
    plus what the model sows into ``losses``), differentiated in the cast
    weights, so the gradients are the step's own bf16 ones in the program's
    tree. The intermediates are what ``LlamaBlock`` and the expert layer sow
    ({"top_e", "attn_out", "ffn_out"} per layer). One device: the weights
    are gathered onto it."""
    import jax
    import jax.numpy as jnp
    from deepspeed_tpu.models.llama import LlamaForCausalLM
    cfg = model_config(config, rehearse)
    model = LlamaForCausalLM(cfg)
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
    n = cfg.n_layers
    if "layers" in got:
        blk = got["layers"]["blk"]
        layers = [{"top_e": blk["mlp"]["top_e"][0][i],
                   "attn_out": blk["attn_out"][0][i],
                   "ffn_out": blk["ffn_out"][0][i]} for i in range(n)]
    else:
        layers = [{"top_e": got[f"layers_{i}"]["mlp"]["top_e"][0],
                   "attn_out": got[f"layers_{i}"]["attn_out"][0],
                   "ffn_out": got[f"layers_{i}"]["ffn_out"][0]}
                  for i in range(n)]
    return loss, layers, grads


def forward_differences(system, reference):
    """Per-layer worst of: the share of the T x k assignments that differ
    (experts of a token's set the other side did not choose), the attention
    branch's relative error, and the expert branch's largest relative error
    of a token's row among the tokens both sides routed alike (a bf16 step
    and a float32 reference can pick a different k-th expert where the k-th
    and (k+1)-th probabilities tie to bf16 rounding)."""
    import jax.numpy as jnp
    out = {"routing_differs": 0, "routing_assignments": 0,
           "attn_out_rel": 0.0, "ffn_out_row_rel": 0.0}
    for got, want in zip(system, reference):
        E = int(max(got["top_e"].max(), want["top_e"].max())) + 1
        sets = [jnp.zeros((t["top_e"].shape[0], E), bool).at[
            jnp.arange(t["top_e"].shape[0])[:, None], t["top_e"]].set(True)
            for t in (got, want)]
        missing = jnp.sum(sets[1] & ~sets[0], axis=1)          # per token
        out["routing_differs"] += int(missing.sum())
        out["routing_assignments"] += int(want["top_e"].size)
        a, b = (t["attn_out"].astype(jnp.float32) for t in (got, want))
        out["attn_out_rel"] = max(out["attn_out_rel"], float(
            jnp.linalg.norm(a - b) / jnp.linalg.norm(b)))
        a, b = (t["ffn_out"].astype(jnp.float32).reshape(
            missing.shape[0], -1) for t in (got, want))
        rows = jnp.linalg.norm(a - b, axis=1) / jnp.linalg.norm(b, axis=1)
        out["ffn_out_row_rel"] = max(out["ffn_out_row_rel"], float(
            jnp.max(jnp.where(missing == 0, rows, 0.0))))
    return out


def gradient_differences(system, reference, n_layers):
    """{leaf, by the reference's name: |system - reference| / |reference|} of
    two gradient trees in the program's layout, the worst layer's for a
    layer's leaf: the relative error of each gradient as a VECTOR, which a
    rounding of the backward pass moves and a norm does not show."""
    import jax.numpy as jnp

    def rel(a, b):
        return jnp.linalg.norm((a - b).ravel()) / jnp.linalg.norm(b.ravel())

    (top_s, layers_s), (top_r, layers_r) = (
        reference_view(g, n_layers) for g in (system, reference))
    out = {name: rel(top_s[name], top_r[name]) for name in top_r}
    for got, want in zip(layers_s, layers_r):
        for name in want:
            out[name] = jnp.maximum(out.get(name, 0.0),
                                    rel(got[name], want[name]))
    return out


@functools.lru_cache(maxsize=None)
def _reference_program(n_layers, mode, sizes_items):
    """The reference as ONE jitted program over the program's weight tree
    (viewed in the reference's layout inside it, so no second copy of the
    weights exists). ``mode`` "forward": (loss, detail), handed nothing of
    the system's; "grads": (loss, detail, gradient norm, gradients);
    "backward": (gradient norm, {leaf: relative error}) of the reference's
    gradients at the experts the system chose (``reference.forward`` says
    why) against the system's — they live only inside the program."""
    import jax
    sizes_ = dict(sizes_items)

    def view(w):
        return reference_view(w, n_layers)

    @jax.jit
    def forward(p, ids):
        return ref.loss(p, ids, view, **sizes_)

    @jax.jit
    def grads(p, ids):
        (loss, detail), g = ref.loss_and_grads(p, ids, view, **sizes_)
        return loss, detail, ref.grad_norm(g), g

    @jax.jit
    def backward(p, ids, experts, system_grads):
        _, g = ref.loss_and_grads(p, ids, view, experts=experts, **sizes_)
        return ref.grad_norm(g), gradient_differences(system_grads, g,
                                                      n_layers)

    return {"forward": forward, "grads": grads, "backward": backward}[mode]


def _reference(mode, config, params, batch_ids, device, rehearse, *more):
    import jax
    run = _reference_program(
        sizes(config, rehearse)["num_hidden_layers"], mode,
        tuple(sorted(reference_sizes(config, rehearse).items())))
    return run(jax.device_put(params, device),
               jax.device_put(np.asarray(batch_ids), device), *more)


def reference_forward(config, params, batch_ids, device, rehearse):
    """(loss, detail) of the plain reference's own forward pass at the
    weights ``params`` holds."""
    return _reference("forward", config, params, batch_ids, device, rehearse)


def reference_run(config, params, batch_ids, device, rehearse):
    """(loss, detail, gradient norm, gradients in the program's tree) of the
    plain reference, forward and backward, handed nothing of a system's."""
    return _reference("grads", config, params, batch_ids, device, rehearse)


def reference_backward(config, params, batch_ids, device, rehearse,
                       experts, system_grads):
    """(the reference's gradient norm, {leaf: relative error of
    ``system_grads`` against the reference's gradients}) at the same
    weights, batch and ``experts`` (per layer [T, k], the system's own
    choice)."""
    gnorm, leaves = _reference("backward", config, params, batch_ids, device,
                               rehearse, tuple(experts), system_grads)
    return float(gnorm), {name: float(v) for name, v in leaves.items()}


def compare(config, params, batch_ids, device, rehearse, system):
    """(reference loss, reference gradient norm, differences) of ``system``
    (``system_step``'s three values) against the plain reference on the same
    weights and batch: the reference's own forward pass first (it is handed
    nothing of the system's) for the loss, the routing and the two branches;
    then its backward pass at the experts the system chose, for the gradient
    norm and every gradient leaf."""
    import jax
    _, layers, grads = system
    loss, detail = reference_forward(config, params, batch_ids, device,
                                     rehearse)
    diffs = forward_differences(layers, detail["layers"])
    diffs.update(reference_ce=float(detail["ce"]),
                 reference_balance=float(detail["balance"]),
                 reference_z=float(detail["z"]))
    del detail
    diffs["system_grad_norm"] = float(ref.grad_norm(
        jax.tree_util.tree_map(lambda g: g.astype("float32"), grads)))
    gnorm, diffs["grad_leaf_rel"] = reference_backward(
        config, params, batch_ids, device, rehearse,
        [layer["top_e"] for layer in layers], grads)
    return float(loss), gnorm, diffs


def reference_train(config, params, batch_ids, devices, rehearse):
    """``compare`` of the program's model as the configuration builds it.
    Call before the engine's first step."""
    return compare(config, params, batch_ids, devices[0], rehearse,
                   system_step(config, params, batch_ids, devices[0],
                               rehearse))


def judge_train(config, got_loss, got_gnorm, want_loss, want_gnorm,
                differences=None):
    """The first step's loss and gradient norm against the reference's, the
    two forward and the two backward passes' differences (``differences``:
    ``reference_train``'s third value), and — once an engine of this process
    has stepped — that no routed row was dropped."""
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
        share = diffs["routing_differs"] / diffs["routing_assignments"]
        checks["routing_matches_reference"] = \
            share <= tol["routing_differs_share"]
        checks["attention_branch_matches_reference"] = \
            diffs["attn_out_rel"] <= tol["attn_out_rel"]
        checks["expert_branch_matches_reference"] = \
            diffs["ffn_out_row_rel"] <= tol["ffn_out_row_rel"]
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
        detail["differences"] = dict(
            diffs, routing_differs_share=share, gradient_leaves_over=over,
            tolerances={k: tol[k] for k in (
                "routing_differs_share", "attn_out_rel", "ffn_out_row_rel",
                "grad_leaf_rel")})
    engine = _LIVE.get("engine")     # fenced and folded here, after warm-up
    gauges = _LIVE["gauges"] = \
        engine.telemetry_flush()["gauges"] if engine is not None else {}
    if "moe/dropped_rows" in gauges:
        checks["no_routed_row_dropped"] = gauges["moe/dropped_rows"] == 0
        detail["moe_gauges"] = {k: v for k, v in gauges.items()
                                if k.startswith("moe/")}
    return checks, detail


# ------------------------------------------------- operations and bytes

def active_matmul_params(config, rehearse=False):
    """Parameters one token is multiplied with: the attention projections,
    the router, its k experts — the ACTIVE ones, never all E — per layer,
    and the output head (the embedding lookup is a gather)."""
    s = sizes(config, rehearse)
    H, F = s["hidden_size"], s["intermediate_size"]
    kv = H * s["num_key_value_heads"] // s["num_attention_heads"]
    per_layer = 2 * H * H + 2 * H * kv \
        + s["num_experts_per_tok"] * 3 * H * F + H * s["num_experts"]
    return s["num_hidden_layers"] * per_layer + s["vocab_size"] * H


def train_flops_per_token(config, seq_len, rehearse=False):
    """6 a matmul parameter (2 forward, 4 backward) + causal attention
    (6 S H a layer: ``roofline.dense_train_flops_per_token``'s count)."""
    s = sizes(config, rehearse)
    return 6 * active_matmul_params(config, rehearse) \
        + 6 * s["num_hidden_layers"] * seq_len * s["hidden_size"]


def train_attention_flops_per_step(config, batch, seq_len, rehearse=False):
    """Causal flops of the flash forward and backward kernels in one step."""
    s = sizes(config, rehearse)
    return s["num_hidden_layers"] * roofline.causal_attention_train_flops(
        batch, s["num_attention_heads"], seq_len,
        s["hidden_size"] // s["num_attention_heads"])


def moe_gmm_flops_per_step(config, tokens, rehearse=False):
    """Flops the grouped matmuls of one step NEED for ``tokens`` tokens:
    three products (forward, dlhs, drhs) of three matrices (gate, up, down),
    each 2 x (tokens x k rows) x hidden x expert width, per layer. Rows of
    tiles that straddle two experts, and anything remat re-runs, are work
    the kernel adds and are not counted."""
    s = sizes(config, rehearse)
    rows = tokens * s["num_experts_per_tok"]
    return s["num_hidden_layers"] * 3 * 3 * 2 * rows \
        * s["hidden_size"] * s["intermediate_size"]
