"""The Keye-VL-2.0 family: how its configuration file becomes a running system.

The members ``benchmark/families/__init__.py`` lists for training, none of
serving's. The model is ``deepspeed_tpu.models.llama`` — the OLMoE and SDAR
families' model file — with a head size of its own, QK-norm a head, a SHARE
of the experts held, multimodal rotary sections and ``index_topk`` > 0: every
layer's attention is pruned by a learned indexer (its scores over all causal
pairs, an exact top-k a query, the pruned kernels, the indexer's KL against
the attention it prunes: ``ops/pallas/learned_sparse_attention.py``). Built
through ``dstpu.initialize`` as the other cells' are; the plain reference is
``benchmark/reference/keye_vl2.py``. Key names are the published config's;
the two nested groups (``sa_config``, ``rope_scaling``) are read whole. The
configuration is the LANGUAGE MODEL on text tokens: the three position rows
are equal (the vision tower is not in the published ``config``).

A configuration of this family is ONE RANK'S SHARE of an expert-parallel
layout, as SDAR's is (``families/sdar.py``): ``num_experts`` the experts held
here, ``expert_parallel_size`` the shares the router chooses among.

``correct`` is the SDAR family's comparison (the loss; which experts each
row chose; each layer's attention branch and the held experts' partial sum;
every gradient leaf as a vector, the reference pinned to the system's
experts) plus what the indexer adds: the pairs the reference's indexer, FED
THE SYSTEM'S STREAM (each layer's normed input as the program sowed it),
would have chosen otherwise (``selection_differs_share``: rounding of scores
next to the 2,048th), its scores there as a vector against the scores formed
in float32 from the system's operands, the indexer's loss L_I
(``dsa_kl_abs``), the indexer's leaves among ``grad_leaf_rel``, and the two
seams as exact zeros. Every OTHER pass of the reference — the forward pass
that gives the loss, L_I and the two branches, and the backward pass — is
PINNED to the system's selection, as the backward pass is to its experts: a
top-k is not continuous, and at random initialisation attention is near
uniform over the kept keys, so a kept set that differs in a share s of its
keys moves a layer's attention branch by ~sqrt(2 s) of its length (4 % of
the keys: 12 % of the branch, my CPU reading at the published widths, PR
65) and the next layer's scores with it — a comparison of arithmetic would
read the choice's noise. The seams: the cross-entropy's gradient of
every indexer leaf and the KL's gradient of every other leaf
(``system_step`` runs its program twice, the KL alone and the step's own
objective: the KL leaves an exact zero on every leaf that is not the
indexer's, and the step's gradient of an indexer leaf IS the KL's alone).
"""

import functools
import json

import numpy as np

from benchmark.families import common, olmoe as shared, sdar
from benchmark.reference import keye_vl2 as ref

WIDTH_KEYS = ("hidden_size", "intermediate_size", "moe_intermediate_size",
              "num_attention_heads", "num_key_value_heads", "head_dim",
              "num_experts_per_tok")
KERNEL_TAGS = ("dsa_indexer_bwd", "dsa_indexer", "dsa_select", "dsa_fwd",
               "dsa_bwd", "dsa_kl", "moe_gmm")
MODULE_TAGS = ("ds_loss_head", "ds_embed", "dsa_index_proj", "dsa_select_pin",
               "dsa_kl_bwd", "dsa_indexer_bwd_sum", "dsa_bwd_dq_sum",
               "moe_router", "moe_dispatch", "moe_act", "moe_combine",
               "qk_norm", "attn", "mlp", "input_norm", "post_attn_norm",
               "norm")
DISPATCH_TAGS = shared.DISPATCH_TAGS
# every scope of the learned sparse attention: ``dsa_attn_share``
DSA_TAGS = ("dsa_indexer_bwd", "dsa_indexer", "dsa_select", "dsa_fwd",
            "dsa_bwd", "dsa_kl")
CONTROLS = ref.CONTROLS
_LIVE = {}

_SIZE_KEYS = ("vocab_size", "train_seq_len", "hidden_size",
              "moe_intermediate_size", "num_hidden_layers",
              "num_attention_heads", "num_key_value_heads", "head_dim",
              "num_experts", "expert_parallel_size", "expert_parallel_rank",
              "num_experts_per_tok", "norm_topk_prob", "rms_norm_eps",
              "rope_theta", "rope_scaling", "sa_config", "dsa_kl_weight")


def sizes(config, rehearse):
    out = {k: config[k] for k in _SIZE_KEYS}
    if rehearse:
        out.update({k: v for k, v in config["rehearse_cpu"].items()
                    if k in _SIZE_KEYS})
    return out


def traffic_shapes(config, rehearse):
    s = sizes(config, rehearse)
    return {"vocab_size": s["vocab_size"],
            "max_positions": s["train_seq_len"],
            "seq_scale": s["train_seq_len"] / config["train_seq_len"]}


def model_config(config, rehearse):
    import jax.numpy as jnp
    from deepspeed_tpu.models.llama import LlamaConfig
    s, m = sizes(config, rehearse), common.merged(config, "model", rehearse)
    dtypes = {"bfloat16": jnp.bfloat16, "float32": jnp.float32}
    ranks, sa = s["expert_parallel_size"], s["sa_config"]
    assert sa["indexer_num_kv_heads"] == 1      # one indexer key a token
    return LlamaConfig(
        vocab_size=s["vocab_size"], hidden_size=s["hidden_size"],
        intermediate_size=s["moe_intermediate_size"],
        n_layers=s["num_hidden_layers"], n_heads=s["num_attention_heads"],
        n_kv_heads=s["num_key_value_heads"], head_width=s["head_dim"],
        max_seq_len=s["train_seq_len"], rope_theta=float(s["rope_theta"]),
        rms_eps=s["rms_norm_eps"], num_experts=s["num_experts"] * ranks,
        experts_held=s["num_experts"] if ranks > 1 else 0,
        expert_share=s["expert_parallel_rank"],
        num_experts_per_tok=s["num_experts_per_tok"],
        norm_topk_prob=s["norm_topk_prob"], qk_norm="head",
        router_aux_loss_coef=0.0, router_z_loss_coef=0.0,
        index_topk=sa["topk"], index_heads=sa["indexer_num_heads"],
        index_head_dim=sa["indexer_head_dim"],
        dsa_kl_weight=s["dsa_kl_weight"],
        mrope_section=tuple(s["rope_scaling"]["mrope_section"]),
        dtype=dtypes[m["dtype"]], param_dtype=dtypes[m["param_dtype"]],
        scan_layers=m["scan_layers"], remat=m["remat"],
        remat_policy=m["remat_policy"], loss_chunk=m["loss_chunk"])


# ----------------------------------------------------------------- training

def _model(config, rehearse):
    from deepspeed_tpu.models.llama import LlamaForCausalLM
    cfg = model_config(config, rehearse)     # a program without the
    assert cfg.index_topk > 0                # indexer fails HERE
    return LlamaForCausalLM(cfg)


def build_train(config, global_batch, seed, devices, rehearse):
    """(engine, initial parameters): ``common.build_train``'s recipe over
    ``LlamaForCausalLM``, the weights made from 64 example positions, then
    the experts placed on the ranks by their measured load
    (``placed_experts``)."""
    engine, params = common.build_train(
        _model(config, rehearse), config, global_batch, seed, devices,
        rehearse, example_len=64)
    params, _LIVE["placement"] = placed_experts(config, params, global_batch,
                                                seed, rehearse)
    engine.state = engine.state.replace(params=params)
    _LIVE.update(engine=engine, seed=seed)
    return engine, params


def placed_experts(config, params, global_batch, seed, rehearse):
    """``families/sdar.placed_experts``'s deal (its docstring has the
    mechanism and why a cell of seeded weights and uniform tokens needs it;
    ``sdar.place_by_load`` is the deal itself) under THIS family's forward:
    each expert's rows counted over the run's own pool of batches with the
    pruned attention on, every layer's experts re-dealt over the ranks,
    ``rounds`` times, this rank keeping its columns."""
    import jax
    import jax.numpy as jnp
    from benchmark import traffic
    how = common.merged(config, "train", rehearse)["expert_placement"]
    s, shapes = sizes(config, rehearse), traffic_shapes(config, rehearse)
    model = _model(config, rehearse)
    ranks, held, rank = (s["expert_parallel_size"], s["num_experts"],
                         s["expert_parallel_rank"])
    pool = traffic.train_batches(
        dict(how, global_batch=global_batch), seed, shapes["vocab_size"],
        shapes["seq_scale"])

    @jax.jit
    def loads(p, ids):
        _, seen = model.apply({"params": p}, ids, labels=ids,
                              mutable=["intermediates", "stats", "losses"])
        top_e = seen["intermediates"]["layers"]["blk"]["mlp"]["top_e"][0]
        return jax.vmap(lambda t: jnp.bincount(
            t.reshape(-1), length=ranks * held))(top_e)

    shares = []
    for _ in range(how["rounds"] + 1):
        rows = np.asarray(jax.device_get([loads(params, ids)
                                          for ids in pool]), np.float64)
        mine = rows[:, :, rank * held:(rank + 1) * held].sum(axis=2) \
            / rows.sum(axis=2)
        shares.append({"pool": mine.mean(axis=0).tolist(),
                       "a_batch_min_max": [float(mine.min()),
                                           float(mine.max())]})
        if len(shares) > how["rounds"]:      # the last round only counts
            break
        perm = jnp.asarray(np.stack([sdar.place_by_load(rows[:, layer], ranks)
                                     for layer in range(rows.shape[1])]))
        router = params["layers"]["blk"]["mlp"]["router"]
        moved = jax.device_put(
            jnp.take_along_axis(router, perm[:, None, :], axis=2),
            router.sharding)
        params = {**params, "layers": {"blk": {
            **params["layers"]["blk"], "mlp": {
                **params["layers"]["blk"]["mlp"], "router": moved}}}}
    return params, {"rows_held_share_by_round": shares}


def program_gauges():
    """The program's ``moe/*`` and ``attention/*`` gauges of the LAST WARM-UP
    STEP, as ``judge_train`` folded them ({} before it)."""
    return _LIVE.get("gauges", {})


def lower_train_step(config, traffic, devices):
    """The cell's train step at real size, lowered over abstract state on
    ``devices`` (described chips)."""
    return common.lower_train_step(_model(config, rehearse=False), config,
                                   traffic, devices)


def reference_sizes(config, rehearse):
    s = sizes(config, rehearse)
    sa = s["sa_config"]
    return dict(n_kv_head=s["num_key_value_heads"], head_dim=s["head_dim"],
                k=s["num_experts_per_tok"], eps=s["rms_norm_eps"],
                theta=float(s["rope_theta"]),
                mrope_section=tuple(s["rope_scaling"]["mrope_section"]),
                index_heads=sa["indexer_num_heads"],
                index_head_dim=sa["indexer_head_dim"], topk=sa["topk"],
                expert_lo=s["num_experts"] * s["expert_parallel_rank"])


# what the reference calls each of the indexer's leaves, by the program's
# path under a block's ``attn`` (the others: ``families/olmoe.reference_view``)
INDEXER_LEAVES = {"index_q": ("index_q", "kernel"),
                  "index_k": ("index_k", "kernel"),
                  "index_k_norm": ("index_k_norm", "scale"),
                  "index_k_bias": ("index_k_norm", "bias"),
                  "index_w": ("index_w", "kernel")}


def reference_view(params, n_layers):
    """(top, layers) in the reference's layout, float32, from
    ``LlamaForCausalLM``'s layer-stacked tree: the OLMoE family's view and
    the indexer's leaves."""
    import jax.numpy as jnp
    top, layers = shared.reference_view(params, n_layers)
    attn = params["layers"]["blk"]["attn"]
    for i, layer in enumerate(layers):
        layer.update({name: common.at(attn, path)[i].astype(jnp.float32)
                      for name, path in INDEXER_LEAVES.items()})
    return top, layers


@functools.lru_cache(maxsize=None)
def _system_program(config_json, rehearse):
    """The program's model and ``system_step``'s jitted step, once a
    configuration a process (the tools run it on several weight trees)."""
    import jax
    import jax.numpy as jnp
    from deepspeed_tpu.models.llama import LlamaForCausalLM
    config = json.loads(config_json)
    model = LlamaForCausalLM(model_config(config, rehearse))
    bf16 = common.merged(config, "train", rehearse)["engine"].get(
        "data_types", {}).get("grad_dtype") == "bf16"

    def terms(p, ids):
        ce, vs = model.apply({"params": p}, ids, labels=ids,
                             mutable=["losses", "intermediates"])
        kl = sum(jnp.sum(x) for x in jax.tree_util.tree_leaves(
            vs.get("losses", {})))
        return (ce, kl), vs["intermediates"]

    @jax.jit
    def step(p, ids, weights):
        if bf16:
            p = jax.tree_util.tree_map(
                lambda x: x.astype(jnp.bfloat16)
                if x.dtype == jnp.float32 else x, p)

        def objective(w):
            (ce, kl), got = terms(w, ids)
            return weights[0] * ce + weights[1] * kl, (ce, kl, got)

        (_, (ce, kl, got)), grads = jax.value_and_grad(
            objective, has_aux=True)(p)
        return ce + kl, kl, got, grads

    return model.config.n_layers, step


def _is_indexer(path):
    modules = {of[0] for of in INDEXER_LEAVES.values()}
    return any(getattr(k, "key", None) in modules for k in path)


def system_step(config, params, batch_ids, device, rehearse):
    """(loss, per-layer intermediates, gradients, seams) of the PROGRAM's
    model on ``batch_ids``: weights cast and the loss formed as the engine's
    step does (``families/olmoe.system_step``), in ONE jitted program that
    weighs the cross-entropy and the sown KL by two numbers it is handed, run
    twice: with (0, 1), the KL alone — of whose gradients the indexer's
    leaves and the largest |value| on any other leaf are kept — then with
    (1, 1), the step's own. Per layer {"top_e", "attn_out", "ffn_out",
    "selection" (the kept set's bits, [S / 8, S] by key and query),
    "attn_in" (the normed input the indexer read), "index_operands"};
    ``seams``: the largest |gradient| the KL left on a
    leaf that is not the indexer's, and the largest difference between the
    step's gradient of an indexer leaf and the KL's alone (what the
    cross-entropy put there). Two runs of one program and not two
    cotangents in one: that program's peak, beside the engine's state, is
    0.65 GB under the chip's memory."""
    import jax
    import jax.numpy as jnp
    n, step = _system_program(json.dumps(config, sort_keys=True), rehearse)
    params = jax.device_put(params, device)
    ids = jax.device_put(np.asarray(batch_ids), device)
    flat = jax.tree_util.tree_flatten_with_path

    @jax.jit
    def of_the_kl(grads):
        return ({jax.tree_util.keystr(path): g for path, g in flat(grads)[0]
                 if _is_indexer(path)},
                jnp.max(jnp.stack([jnp.max(jnp.abs(g.astype(jnp.float32)))
                                   for path, g in flat(grads)[0]
                                   if not _is_indexer(path)])))

    indexer, on_trunk = of_the_kl(step(params, ids,
                                       jnp.asarray([0.0, 1.0]))[3])
    loss, kl, got, grads = step(params, ids, jnp.asarray([1.0, 1.0]))
    on_indexer = max(
        float(jnp.max(jnp.abs(g.astype(jnp.float32)
                              - indexer[jax.tree_util.keystr(path)]
                              .astype(jnp.float32))))
        for path, g in flat(grads)[0] if _is_indexer(path))
    blk = got["layers"]["blk"]         # the layer scan's stacked values
    layers = [{"top_e": blk["mlp"]["top_e"][0][i],
               "attn_out": blk["attn_out"][0][i],
               "ffn_out": blk["ffn_out"][0][i],
               "selection": blk["attn"]["selection"][0][i],
               "attn_in": blk["attn"]["attn_in"][0][i],
               "index_operands": tuple(
                   x[i] for x in blk["attn"]["index_operands"][0])}
              for i in range(n)]
    return loss, layers, grads, {
        "ce_on_indexer": on_indexer, "kl_on_trunk": float(on_trunk),
        "index_kl": float(kl)}


def _kept(bits):
    """bool [B, S_q, S_k] of the program's packed selection [B, ceil(S / 8),
    S] by key and query (key 8 r + i is bit i of row r)."""
    import jax.numpy as jnp
    B, R, S = bits.shape
    keys = (bits[:, :, None, :] >> jnp.arange(8, dtype=jnp.uint8)[
        None, None, :, None]) & 1
    return jnp.swapaxes(keys.reshape(B, R * 8, S)[:, :S], 1, 2) != 0


def _scores_pair(operands, want):
    """(squared error, squared norm) over the causal pairs of the indexer's
    scores formed in float32 from the SYSTEM's operands (iq, ik, iw) against
    the reference's ``want`` [B, S, S], a block of query rows at a time."""
    import jax
    import jax.numpy as jnp
    iq, ik, iw = (t.astype(jnp.float32) for t in operands)
    S = want.shape[1]
    step = next(s for s in (512, 256, 128, 64, 32, 16, 8, 4, 2, 1)
                if S % s == 0)

    def rows(carry, r):
        q = jax.lax.dynamic_slice_in_dim(iq, r * step, step, axis=2)
        w = jax.lax.dynamic_slice_in_dim(iw, r * step, step, axis=1)
        b = jax.lax.dynamic_slice_in_dim(want, r * step, step, axis=1)
        t = jnp.einsum("bjts,btj->bts", jax.nn.relu(
            jnp.einsum("bjtd,bsd->bjts", q, ik)), w)
        seen = jnp.arange(S)[None, :] <= r * step + jnp.arange(step)[:, None]
        d = jnp.where(seen, t - b, 0.0)
        return (carry[0] + jnp.sum(jnp.square(d)),
                carry[1] + jnp.sum(jnp.square(jnp.where(seen, b, 0.0)))), None

    out, _ = jax.lax.scan(rows, (jnp.zeros(()), jnp.zeros(())),
                          jnp.arange(S // step))
    return out


def _one_layer(tree, i):
    """Layer ``i`` (a traced index) of a layer-stacked tree in the program's
    layout, under the reference's leaf names, float32."""
    import jax
    blk = jax.tree_util.tree_map(lambda x: x[i][None], tree["layers"]["blk"])
    return reference_view({**tree, "layers": {"blk": blk}}, 1)[1][0]


@functools.lru_cache(maxsize=None)
def _reference_programs(n_layers, sizes_items, kl_weight, control):
    """The reference as jitted programs, by name, over the program's weight
    tree, WALKED a layer at a time in both directions (a layer's float32
    scores, kept set and mean probabilities are [S, S] each, and the engine's
    state lies beside them): "own" -> one layer of the reference's OWN
    forward pass at the system's kept set (its stream in, its stream out, the
    layer's KL and the sums the differences are made of, against the system's
    layer; its indexer's own choice on the SYSTEM's stream beside them);
    "pinned" -> one layer's output at the experts AND the selection the
    system chose; "head"
    -> (cross-entropy, the cotangent of the final stream, the head's
    gradient pairs); "layer" -> (the cotangent of the layer's input, the
    pairs of its leaves) from the cotangent of its output and of its KL;
    "embed" -> the embedding's pair. One program serves every layer (the
    index is traced)."""
    import jax
    import jax.numpy as jnp
    sizes_ = dict(sizes_items)
    eps = sizes_["eps"]
    # the fault of a control that is the SYSTEM's is not the reference's
    fault = control if control in ref.CONTROLS else None

    def pair(got, want):
        got = got.astype(jnp.float32)
        return jnp.sum(jnp.square(got - want)), jnp.sum(jnp.square(want))

    @jax.jit
    def embed_rows(p, ids):
        return p["embed_tokens"].astype(jnp.float32)[ids]

    @jax.jit
    def own(p, i, x, system):
        kept = _kept(system["selection"])
        with jax.default_matmul_precision("highest"):
            lyr = _one_layer(p, i)
            # the reference's stream and experts, the system's kept set
            x, kl, want = ref.layer(x, lyr, None, kept, control=fault,
                                    **sizes_)
            # ... and what ITS indexer would keep, fed the system's stream
            a = system["attn_in"].astype(jnp.float32)
            S = a.shape[1]
            scores = ref.index_scores(
                a, lyr, jnp.broadcast_to(jnp.arange(S), (3, S)),
                index_heads=sizes_["index_heads"],
                index_head_dim=sizes_["index_head_dim"],
                theta=sizes_["theta"], control=fault)
            mine = ref.select(scores, sizes_["topk"], fault)
            scores = _scores_pair(system["index_operands"], scores)
        missing = jnp.sum(jnp.all(
            want["top_e"][:, :, None] != system["top_e"][:, None, :], axis=2),
            axis=1)
        a, b = (t.astype(jnp.float32).reshape(missing.shape[0], -1)
                for t in (system["ffn_out"], want["ffn_out"]))
        alike = (missing == 0)[:, None]
        return x, kl, {
            "routing_differs": jnp.sum(missing),
            "selection_differs": jnp.sum(mine & ~kept),
            "selected": jnp.sum(mine), "system_selected": jnp.sum(kept),
            "index_scores": scores,
            "attn_out": pair(system["attn_out"], want["attn_out"]),
            "ffn_out": pair(jnp.where(alike, a, 0.0),
                            jnp.where(alike, b, 0.0))}

    @jax.jit
    def pinned(p, i, x, experts, bits):
        with jax.default_matmul_precision("highest"):
            return ref.layer(x, _one_layer(p, i), experts, _kept(bits),
                             control=fault, **sizes_)[:2]

    @jax.jit
    def head(p, x, ids, system_grads):
        with jax.default_matmul_precision("highest"):
            small = {"norm": p["norm"]["scale"].astype(jnp.float32),
                     "lm_head": p["lm_head"].astype(jnp.float32)}
            loss, back = jax.vjp(
                lambda x, w: ref.head_loss(x, w, ids, eps=eps), x, small)
            c, g = back(jnp.ones((), jnp.float32))
        got = {"norm": system_grads["norm"]["scale"],
               "lm_head": system_grads["lm_head"]}
        return loss, c, {n: pair(got[n], g[n]) for n in g}

    @jax.jit
    def layer(p, system_grads, i, x, c, experts, bits):
        with jax.default_matmul_precision("highest"):
            _, back = jax.vjp(
                lambda x, w: ref.layer(x, w, experts, _kept(bits),
                                       control=fault, **sizes_)[:2],
                x, _one_layer(p, i))
            c, g = back((c, jnp.full((), kl_weight / n_layers, jnp.float32)))
        got = _one_layer(system_grads, i)
        return c, {n: pair(got[n], g[n]) for n in g}

    @jax.jit
    def embed(p, ids, c, system_grads):
        rows = ids.reshape(-1)
        want = jnp.zeros(p["embed_tokens"].shape, jnp.float32).at[rows].add(
            c.reshape(rows.shape[0], -1))
        return pair(system_grads["embed_tokens"], want)

    return {"embed_rows": embed_rows, "own": own, "pinned": pinned,
            "head": head, "layer": layer, "embed": embed}


def _program(mode, config, rehearse, control):
    s = sizes(config, rehearse)
    return _reference_programs(
        s["num_hidden_layers"],
        tuple(sorted(reference_sizes(config, rehearse).items())),
        float(s["dsa_kl_weight"]), control)[mode]


def compare(config, params, batch_ids, device, rehearse, system,
            control=None):
    """(reference loss, reference gradient norm, differences) of ``system``
    (``system_step``'s four values) against the plain reference on the same
    weights and batch: the reference's own forward pass at the system's kept
    sets, walked, for the loss, L_I, the routing and the two branches, its
    indexer fed the system's stream for the selection and the scores; then
    its backward pass at the experts and the selection the system chose for
    the gradient norm and every gradient leaf. ``control``: one of ``CONTROLS``."""
    import jax
    import jax.numpy as jnp
    _, layers, grads, seams = system
    run = functools.partial(_program, config=config, rehearse=rehearse,
                            control=control)
    params = jax.device_put(params, device)
    ids = jax.device_put(np.asarray(batch_ids), device)
    weight = float(sizes(config, rehearse)["dsa_kl_weight"])
    # the reference's own pass
    x = run("embed_rows")(params, ids)
    kls, sums = [], []
    for i, mine in enumerate(layers):
        seen = {k: mine[k] for k in ("top_e", "attn_out", "ffn_out",
                                     "selection", "attn_in",
                                     "index_operands")}
        x, kl, s = run("own")(params, jnp.int32(i), x, seen)
        kls.append(kl)
        sums.append(s)
    ce = run("head")(params, x, ids, grads)[0]
    kls, sums = jax.device_get((kls, sums))
    index_kl = float(np.mean(kls))
    own_loss = float(ce) + weight * index_kl

    def worst(name):
        return float(max(np.sqrt(s[name][0] / s[name][1]) for s in sums))

    diffs = {
        "routing_differs": int(sum(s["routing_differs"] for s in sums)),
        "routing_assignments": int(sum(t["top_e"].size for t in layers)),
        "selection_differs_share": float(
            sum(s["selection_differs"] for s in sums)
            / sum(s["selected"] for s in sums)),
        "selected_pairs": [int(sum(s["system_selected"] for s in sums)),
                           int(sum(s["selected"] for s in sums))],
        "index_scores_rel": worst("index_scores"),
        "attn_out_rel": worst("attn_out"), "ffn_out_rel": worst("ffn_out"),
        "dsa_kl_abs": abs(seams["index_kl"] - weight * index_kl),
        "index_kl": [seams["index_kl"], weight * index_kl],
        "ce_on_indexer": seams["ce_on_indexer"],
        "kl_on_trunk": seams["kl_on_trunk"]}
    diffs["system_grad_norm"] = float(ref.grad_norm(
        jax.tree_util.tree_map(lambda g: g.astype("float32"), grads)))
    # the backward pass, pinned to the system's experts and selection
    x = run("embed_rows")(params, ids)
    xs = []
    for i, mine in enumerate(layers):
        xs.append(x)
        x, _ = run("pinned")(params, jnp.int32(i), x, mine["top_e"],
                             mine["selection"])
    _, c, sums = run("head")(params, x, ids, grads)
    del x
    sums = dict(sums)
    for i in reversed(range(len(layers))):
        c, pairs = run("layer")(params, grads, jnp.int32(i), xs.pop(), c,
                                layers[i]["top_e"], layers[i]["selection"])
        for n, pair in pairs.items():
            sums[n] = tuple(a + b for a, b in zip(sums.get(n, (0.0, 0.0)),
                                                  pair))
    sums["embed"] = run("embed")(params, ids, c, grads)
    sums = jax.device_get(sums)
    gnorm = float(np.sqrt(sum(ref_sq for _, ref_sq in sums.values())))
    diffs["grad_leaf_rel"] = {n: float(np.sqrt(err / ref_sq))
                              for n, (err, ref_sq) in sums.items()}
    return own_loss, gnorm, diffs


def reference_train(config, params, batch_ids, devices, rehearse):
    """``compare`` of the program's model as the configuration builds it.
    Call before the engine's first step."""
    return compare(config, params, batch_ids, devices[0], rehearse,
                   system_step(config, params, batch_ids, devices[0],
                               rehearse))


def judge_train(config, got_loss, got_gnorm, want_loss, want_gnorm,
                differences=None):
    """``families/olmoe.judge_train`` (loss, gradient norm, routing, the two
    branches, every gradient leaf) with the expert branch held as one vector,
    what the indexer adds (the selection, its scores, L_I, the two seams as
    exact zeros), this family's own engine folded for the gauges, and that
    the step pruned at all."""
    tol = config["train"]["tolerance"]
    if differences is not None:
        differences = dict(differences,
                           ffn_out_row_rel=differences["ffn_out_rel"])
        config = dict(config, train=dict(config["train"], tolerance=dict(
            tol, ffn_out_row_rel=tol["ffn_out_rel"])))
    checks, detail = shared.judge_train(config, got_loss, got_gnorm,
                                        want_loss, want_gnorm, differences)
    if differences is not None:
        checks["selection_matches_reference"] = \
            differences["selection_differs_share"] \
            <= tol["selection_differs_share"]
        # as many keys as the reference keeps, to the pair: sum_t min(t + 1,
        # top-k) whatever the scores (a top-(k - 1) differs in one pair of
        # 2,048 a row, under any share a rounding leaves)
        kept, wanted = differences["selected_pairs"]
        checks["keeps_as_many_keys_as_reference"] = kept == wanted
        checks["index_scores_match_reference"] = \
            differences["index_scores_rel"] <= tol["index_scores_rel"]
        checks["index_kl_matches_reference"] = \
            differences["dsa_kl_abs"] <= tol["dsa_kl_abs"]
        checks["indexer_takes_no_ce_gradient"] = \
            differences["ce_on_indexer"] == 0.0
        checks["trunk_takes_no_kl_gradient"] = \
            differences["kl_on_trunk"] == 0.0
        detail["differences"]["tolerances"].update(
            {k: tol[k] for k in ("selection_differs_share",
                                 "index_scores_rel", "dsa_kl_abs",
                                 "ffn_out_rel")})
    checks.pop("no_routed_row_dropped", None)    # that family's engine's
    engine = _LIVE.get("engine")
    gauges = _LIVE["gauges"] = \
        engine.telemetry_flush()["gauges"] if engine is not None else {}
    if "moe/dropped_rows" in gauges:
        checks["no_routed_row_dropped"] = gauges["moe/dropped_rows"] == 0
        checks["keys_were_pruned"] = \
            0.0 < gauges.get("attention/dsa_selected_share", 0.0) < 1.0
        detail["expert_placement"] = _LIVE.get("placement")
        detail["program_gauges"] = {
            k: v for k, v in gauges.items()
            if k.startswith(("moe/", "attention/dsa_", "remat/"))}
    return checks, detail


# ------------------------------------------------- operations and bytes

def selected_pairs(seq_len, topk):
    """(query, key) pairs a head attends to over one sequence:
    sum_t min(t + 1, topk). 31,458,304 at 16,384 / 2,048: 23.4 % of the
    134,225,920 causal pairs."""
    k = min(seq_len, topk)
    return k * (k + 1) // 2 + (seq_len - k) * k


def causal_pairs(seq_len):
    return seq_len * (seq_len + 1) // 2


def rows_held_share(config, rehearse=False):
    """Share of the T x k routed rows a uniform router sends to the experts
    held here: 1 / ``expert_parallel_size``."""
    return 1.0 / sizes(config, rehearse)["expert_parallel_size"]


def layer_matmul_params(config, rehearse=False):
    """Parameters one ROW is multiplied with in a layer HERE: the attention
    projections, the indexer's three, the router (all published experts
    wide), and the k experts times the share of them held here."""
    s = sizes(config, rehearse)
    H, D, sa = s["hidden_size"], s["head_dim"], s["sa_config"]
    return 2 * H * s["num_attention_heads"] * D \
        + 2 * H * s["num_key_value_heads"] * D \
        + H * (sa["indexer_num_heads"] * sa["indexer_head_dim"]
               + sa["indexer_head_dim"] + sa["indexer_num_heads"]) \
        + H * s["num_experts"] * s["expert_parallel_size"] \
        + s["num_experts_per_tok"] * rows_held_share(config, rehearse) \
        * 3 * H * s["moe_intermediate_size"]


def train_attention_flops_per_step(config, batch, seq_len, rehearse=False):
    """Flops the pruned kernels NEED in one step, forward + backward: six
    products (QK^T, PV; dV, dP, dQ, dK) of 2 x head_dim a pair over the
    SELECTED pairs alone: layers x heads x 12 x pairs x head_dim. The causal
    tiles the kernels walk are 4.27 x that at 16,384 / 2,048 and are not
    what the mathematics needs."""
    s = sizes(config, rehearse)
    return s["num_hidden_layers"] * batch * s["num_attention_heads"] * 12 \
        * selected_pairs(seq_len, s["sa_config"]["topk"]) * s["head_dim"]


def indexer_flops_per_step(config, batch, seq_len, rehearse=False):
    """(forward, backward) flops the indexer's scores NEED in one step: a
    product of 2 x indexer_head_dim a causal pair a head forward; backward
    the product again, the query side's and the key side's (three)."""
    s = sizes(config, rehearse)
    sa = s["sa_config"]
    one = s["num_hidden_layers"] * batch * causal_pairs(seq_len) * 2 \
        * sa["indexer_num_heads"] * sa["indexer_head_dim"]
    return one, 3 * one


def kl_flops_per_step(config, batch, seq_len, rehearse=False):
    """Flops the KL pass needs: QK^T of every head over the selected pairs."""
    s = sizes(config, rehearse)
    return s["num_hidden_layers"] * batch * s["num_attention_heads"] * 2 \
        * selected_pairs(seq_len, s["sa_config"]["topk"]) * s["head_dim"]


def select_bytes_per_step(config, batch, seq_len, rehearse=False):
    """Bytes the selection must move: every causal score read once (float32)
    and a byte of mask a causal pair written."""
    s = sizes(config, rehearse)
    return s["num_hidden_layers"] * batch * causal_pairs(seq_len) * (4 + 1)


def train_flops_per_token(config, seq_len, rehearse=False):
    """6 a matmul parameter (2 forward, 4 backward), the head, the pruned
    attention over the selected pairs, the indexer's scores over the causal
    pairs (forward and backward) and the KL's products."""
    s = sizes(config, rehearse)
    fwd, bwd = indexer_flops_per_step(config, 1, seq_len, rehearse)
    return 6 * (s["num_hidden_layers"] * layer_matmul_params(config, rehearse)
                + s["vocab_size"] * s["hidden_size"]) \
        + (train_attention_flops_per_step(config, 1, seq_len, rehearse)
           + fwd + bwd + kl_flops_per_step(config, 1, seq_len, rehearse)) \
        / seq_len


def moe_gmm_flops_per_step(config, tokens, rehearse=False):
    """Flops the grouped matmuls of one step NEED for ``tokens`` tokens:
    three products (forward, dlhs, drhs) of gate, up and down, every layer,
    over the EXPECTED rows held — 1 / ``expert_parallel_size`` of the
    tokens x k rows the router assigns. Held against ``moe_rows_held_share``
    (the step's own count) before it is believed."""
    s = sizes(config, rehearse)
    rows = tokens * s["num_experts_per_tok"] * rows_held_share(config,
                                                               rehearse)
    return s["num_hidden_layers"] * 3 * 3 * 2 * rows * s["hidden_size"] \
        * s["moe_intermediate_size"]
