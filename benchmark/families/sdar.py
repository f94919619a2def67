"""The SDAR family: how its configuration file becomes a running system.

The members ``benchmark/families/__init__.py`` lists for training, none of
serving's. The model is ``deepspeed_tpu.models.llama`` — the OLMoE family's
model file — with a head size of its own, QK-norm a head, a SHARE of the
experts held and ``block_length`` > 0: a forward with labels is a
block-diffusion training step (a noised copy beside the clean sequence, the
block-diffusion mask, the loss over the masked rows weighted 1 / t). Built
through ``dstpu.initialize`` as the other cells' are; the plain reference is
``benchmark/reference/sdar.py``. Key names are the published config's.

A configuration of this family is ONE RANK'S SHARE of an expert-parallel
layout (``families/smallthinker.py``): ``num_experts`` is the experts held
here, ``expert_parallel_size`` how many such shares the router chooses
among, ``expert_parallel_rank`` which of them this is; ``vocab_size`` the
slice of the vocabulary held here.

The step draws its noise from a key, so ``correct`` has to know the key:
the engine is built with ``seed`` (``common.engine_config``), its first step
takes ``split(PRNGKey(seed))[1]`` (``engine._next_rng``) and hands it to the
model's ``diffusion`` stream; ``system_step`` runs the SAME model call with
the same key, reads the noise the model sowed, and the reference takes that
noise as its input. The engine's first loss against the reference's then
says the two keys were one (another key's loss differs by ~0.03 of ln V).

``correct`` is the OLMoE family's comparison (``families/olmoe.py`` says why
loss and gradient norm alone see nothing of a layer) over 2L rows: the loss;
which experts each row chose; the attention branch of every layer on BOTH
halves, each against its own reading; the expert branch's partial sum; every
gradient leaf as a vector, the reference pinned to the system's experts.
"""

import functools

import numpy as np

from benchmark.families import common, olmoe as shared
from benchmark.reference import sdar as ref

WIDTH_KEYS = ("hidden_size", "intermediate_size", "moe_intermediate_size",
              "num_attention_heads", "num_key_value_heads", "head_dim",
              "num_experts_per_tok")
KERNEL_TAGS = ("bd_fwd", "bd_bwd", "moe_gmm")
MODULE_TAGS = ("ds_loss_head", "ds_embed", "bd_noise", "moe_router",
               "moe_dispatch", "moe_act", "moe_combine", "qk_norm", "attn",
               "mlp", "input_norm", "post_attn_norm", "norm")
DISPATCH_TAGS = shared.DISPATCH_TAGS
# the faults the plain reference can be asked for (``compare(control=)``;
# ``benchmark/tools/reference_controls.py`` reads each on the chip)
CONTROLS = ref.CONTROLS
# this process's engine of THIS family, its seed, and its gauges as
# ``judge_train`` folded them
_LIVE = {}

_SIZE_KEYS = ("vocab_size", "train_seq_len", "hidden_size",
              "moe_intermediate_size", "num_hidden_layers",
              "num_attention_heads", "num_key_value_heads", "head_dim",
              "num_experts", "expert_parallel_size", "expert_parallel_rank",
              "num_experts_per_tok", "norm_topk_prob", "rms_norm_eps",
              "rope_theta", "block_length", "noise_eps", "mask_token_id")


def sizes(config, rehearse):
    out = {k: config[k] for k in _SIZE_KEYS}
    if rehearse:
        out.update({k: v for k, v in config["rehearse_cpu"].items()
                    if k in _SIZE_KEYS})
    return out


def traffic_shapes(config, rehearse):
    s = sizes(config, rehearse)
    # ids are drawn below the mask id: a clean token is never the mask
    return {"vocab_size": s["mask_token_id"],
            "max_positions": s["train_seq_len"],
            "seq_scale": s["train_seq_len"] / config["train_seq_len"]}


def model_config(config, rehearse):
    import jax.numpy as jnp
    from deepspeed_tpu.models.llama import LlamaConfig
    s, m = sizes(config, rehearse), common.merged(config, "model", rehearse)
    dtypes = {"bfloat16": jnp.bfloat16, "float32": jnp.float32}
    ranks = s["expert_parallel_size"]
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
        block_length=s["block_length"], noise_eps=s["noise_eps"],
        mask_token_id=s["mask_token_id"],
        dtype=dtypes[m["dtype"]], param_dtype=dtypes[m["param_dtype"]],
        scan_layers=m["scan_layers"], remat=m["remat"],
        remat_policy=m["remat_policy"], loss_chunk=m["loss_chunk"])


# ----------------------------------------------------------------- training

def _model(config, rehearse):
    from deepspeed_tpu.models.llama import LlamaForCausalLM
    cfg = model_config(config, rehearse)     # a program without
    assert cfg.block_length > 0              # block diffusion fails HERE
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
    # the engine adopted the buffers ``common.build_train`` made: it is
    # handed the tree whose router columns moved, every other leaf the same
    engine.state = engine.state.replace(params=params)
    _LIVE.update(engine=engine, seed=seed)
    return engine, params


def place_by_load(loads, ranks):
    """Which expert sits in each of a layer's E columns, rank-major (a rank
    holds E / ranks consecutive columns), from the rows ``loads`` [batches,
    E] each expert drew in each batch: the experts go out heaviest first,
    each to the rank with a place left whose rows, batch by batch, stay
    smallest with it (the least sum of squares over the batches: longest-
    processing-time-first, the greedy rule of a load-balanced placement,
    held to EVERY batch and not to their sum — a rank level on the pool's
    total can still hold two of one batch's hot experts and none of the
    next's), then exchanges of two experts between two ranks while one
    lowers it further."""
    loads = np.asarray(loads, np.float64)
    held = loads.shape[1] // ranks
    members, total = [[] for _ in range(ranks)], np.zeros((ranks, len(loads)))
    for e in np.argsort(-loads.sum(axis=0), kind="stable"):
        r = min((r for r in range(ranks) if len(members[r]) < held),
                key=lambda r: (np.sum(np.square(total[r] + loads[:, e])), r))
        members[r].append(int(e))
        total[r] += loads[:, e]
    # then the best single exchange of two experts between two ranks, while
    # one lowers that sum of squares (the greedy deal serves rank 0 first
    # and leaves it a few per cent heavy)
    members = np.asarray(members)
    for _ in range(4 * loads.shape[1]):
        best = (-1e-9 * np.sum(np.square(total)), None)
        for a in range(ranks):
            for b in range(a + 1, ranks):
                # moved[i, j]: rows rank a gains, batch by batch, when its
                # i-th expert goes to b and b's j-th comes to a
                moved = loads[:, members[b]].T[None] \
                    - loads[:, members[a]].T[:, None]
                gain = np.sum(np.square(total[a] + moved)
                              + np.square(total[b] - moved), axis=2) \
                    - np.sum(np.square(total[a]) + np.square(total[b]))
                i, j = np.unravel_index(np.argmin(gain), gain.shape)
                best = min(best, (gain[i, j], (a, b, i, j)),
                           key=lambda g: g[0])
        if best[1] is None:
            break
        a, b, i, j = best[1]
        moved = loads[:, members[b, j]] - loads[:, members[a, i]]
        total[a] += moved
        total[b] -= moved
        members[a, i], members[b, j] = members[b, j], members[a, i]
    return members.reshape(-1)


def placed_experts(config, params, global_batch, seed, rehearse):
    """(``params`` with every layer's router columns permuted so that the
    experts are spread over the expert-parallel ranks by the load the RUN'S
    OWN batches put on them, {"rows_held_share_by_round": this rank's share
    of each layer's routed rows over the pool, and the smallest and largest
    share of a (batch, layer), before each deal and after the last}).

    Which experts live on which rank is the deployment's to choose, and a
    deployment balances it over its data (an expert-parallel job that left
    one rank twice the rows of another would wait on it every layer). With
    seeded weights and uniform random tokens the choice decides the step's
    time: at random weights the attention branch — a running mean of value
    vectors, 52 / sqrt(keys seen) long against an embedding of 0.9 —
    outweighs the token in the stream a router reads over the first
    thousands of positions, so those rows go to the same few experts
    (``moe_rows_max_over_mean`` 7-11), WHICH experts is the batch's, and how
    many of them are among the 16 columns this rank holds is luck:
    ``moe_rows_held_share`` read 6-21 % from step to step and seed to seed
    and ``train_tokens_per_s`` spread 1.1-1.2 % between the quartiles of
    five and of six seeds (my chip runs, PR 60), each run's mean being that
    of its 16 pool batches. So set-up makes the pool the cell's traffic file
    names (``train.expert_placement`` repeats its ``batch_pool``,
    ``seq_len`` and ``token_below``; ``benchmark/traffic.train_batches``
    under the run's seed: the very batches the window cycles through),
    counts each expert's rows over it under the block-diffusion forward,
    and re-deals every layer's experts over the ranks (``place_by_load``),
    ``rounds`` times because a layer's deal moves the stream the later
    layers route on; a last count, after the last deal, is reported. This
    rank keeps its columns [rank x held, (rank + 1) x held) and the expert
    weights it was born with (they are i.i.d.: an expert is its router
    column). Nothing else of the weights changes, and the reference reads
    the weights this leaves. What it cannot level: a batch's hot experts
    are few and large, so one step's share still moves with its batch and
    its noise (the range is reported); the mean over a pass of the pool is
    what is held at 1 / ranks."""
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
    def loads(p, ids, key):
        _, seen = model.apply({"params": p}, ids, labels=ids,
                              mutable=["intermediates", "stats", "losses"],
                              rngs={"diffusion": key})
        top_e = seen["intermediates"]["layers"]["blk"]["mlp"]["top_e"][0]
        return jax.vmap(lambda t: jnp.bincount(
            t.reshape(-1), length=ranks * held))(top_e)

    shares = []
    keys = jax.random.split(jax.random.fold_in(jax.random.PRNGKey(seed), 2),
                            (how["rounds"] + 1) * len(pool))
    for round_keys in keys.reshape(how["rounds"] + 1, len(pool), -1):
        # [batches, layers, E]
        rows = np.asarray(jax.device_get([
            loads(params, ids, key) for ids, key in zip(pool, round_keys)]),
            np.float64)
        mine = rows[:, :, rank * held:(rank + 1) * held].sum(axis=2) \
            / rows.sum(axis=2)
        shares.append({"pool": mine.mean(axis=0).tolist(),
                       "a_batch_min_max": [float(mine.min()),
                                           float(mine.max())]})
        if len(shares) > how["rounds"]:      # the last round only counts
            break
        perm = jnp.asarray(np.stack([place_by_load(rows[:, layer], ranks)
                                     for layer in range(rows.shape[1])]))
        router = params["layers"]["blk"]["mlp"]["router"]
        moved = jax.device_put(
            jnp.take_along_axis(router, perm[:, None, :], axis=2),
            router.sharding)
        params = {**params, "layers": {"blk": {
            **params["layers"]["blk"], "mlp": {
                **params["layers"]["blk"]["mlp"], "router": moved}}}}
    return params, {"rows_held_share_by_round": shares}


def first_step_key(seed):
    """The key the engine's FIRST step hands the model: the engine holds
    ``PRNGKey(seed)`` and every step takes the second half of a split."""
    import jax
    return jax.random.split(jax.random.PRNGKey(seed))[1]


def program_gauges():
    """The program's ``moe/*``, ``attention/*`` and ``diffusion/*`` gauges of
    the LAST WARM-UP STEP, as ``judge_train`` folded them ({} before it)."""
    return _LIVE.get("gauges", {})


def lower_train_step(config, traffic, devices):
    """The cell's train step at real size, lowered over abstract state on
    ``devices`` (described chips)."""
    return common.lower_train_step(_model(config, rehearse=False), config,
                                   traffic, devices)


def reference_sizes(config, rehearse):
    s = sizes(config, rehearse)
    return dict(n_kv_head=s["num_key_value_heads"], head_dim=s["head_dim"],
                k=s["num_experts_per_tok"], eps=s["rms_norm_eps"],
                theta=float(s["rope_theta"]),
                block_length=s["block_length"],
                expert_lo=s["num_experts"] * s["expert_parallel_rank"])


def system_step(config, params, batch_ids, device, rehearse, key=None):
    """(loss, per-layer intermediates, gradients, noise) of the PROGRAM's
    model on ``batch_ids`` under the first step's key, in one jitted
    program: weights cast and loss formed as the engine's step does
    (``families/olmoe.system_step``). Per layer {"top_e", "attn_out",
    "ffn_out"} over all 2L rows; ``noise`` is the (noisy_ids, masked, t_row)
    the model drew."""
    import jax
    import jax.numpy as jnp
    model = _model(config, rehearse)
    n = model.config.n_layers
    bf16 = common.merged(config, "train", rehearse)["engine"].get(
        "data_types", {}).get("grad_dtype") == "bf16"
    key = first_step_key(_LIVE["seed"]) if key is None else key

    def loss_fn(p, ids):
        out, vs = model.apply({"params": p}, ids, labels=ids,
                              mutable=["losses", "intermediates"],
                              rngs={"diffusion": key})
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
    blk = got["layers"]["blk"]         # the layer scan's stacked values
    layers = [{"top_e": blk["mlp"]["top_e"][0][i],
               "attn_out": blk["attn_out"][0][i],
               "ffn_out": blk["ffn_out"][0][i]} for i in range(n)]
    return loss, layers, grads, got["bd_noise"][0]


def forward_differences(system, reference):
    """The worst layer's: routing assignments the system did not choose (a
    count, and the total), the attention branch's relative error on the
    NOISED and on the CLEAN half, and the expert branch's — the held
    experts' partial sum as ONE vector over the rows both sides routed alike
    (``ffn_out_rel``; the largest single ROW's error, OLMoE's statistic, is
    reported beside it and not held: a row none or one of whose experts is
    held here is a small vector, and its error read 0.15-0.25 on honest runs
    against 0.32 under fp8: my chip runs, PR 60)."""
    import jax.numpy as jnp
    differs = jnp.zeros((), jnp.int32)
    noised = clean = ffn = ffn_row = jnp.zeros((), jnp.float32)
    for got, want in zip(system, reference):
        missing = jnp.sum(jnp.all(
            want["top_e"][:, :, None] != got["top_e"][:, None, :], axis=2),
            axis=1)
        differs += jnp.sum(missing)
        a, b = (t["attn_out"].astype(jnp.float32) for t in (got, want))
        L = a.shape[1] // 2
        noised = jnp.maximum(noised, common.rel(a[:, :L], b[:, :L]))
        clean = jnp.maximum(clean, common.rel(a[:, L:], b[:, L:]))
        a, b = (t["ffn_out"].astype(jnp.float32).reshape(missing.shape[0], -1)
                for t in (got, want))
        alike = (missing == 0)[:, None]
        ffn = jnp.maximum(ffn, common.rel(jnp.where(alike, a, 0.0),
                                          jnp.where(alike, b, 0.0)))
        # a row none of whose experts is held here is zero on both sides
        rows = jnp.linalg.norm(a - b, axis=1) \
            / jnp.maximum(jnp.linalg.norm(b, axis=1), 1e-30)
        ffn_row = jnp.maximum(ffn_row,
                              jnp.max(jnp.where(missing == 0, rows, 0.0)))
    return {"routing_differs": differs,
            "routing_assignments": sum(t["top_e"].size for t in reference),
            "attn_out_noised_rel": noised, "attn_out_clean_rel": clean,
            "ffn_out_rel": ffn, "ffn_out_worst_row_rel": ffn_row}


def _one_layer(tree, i):
    """Layer ``i`` (a traced index) of a layer-stacked tree in the program's
    layout, under the reference's leaf names, float32."""
    import jax
    blk = jax.tree_util.tree_map(lambda x: x[i][None], tree["layers"]["blk"])
    return shared.reference_view({**tree, "layers": {"blk": blk}}, 1)[1][0]


@functools.lru_cache(maxsize=None)
def _reference_programs(n_layers, sizes_items, control):
    """The reference as jitted programs, by name, over the program's weight
    tree (viewed in the reference's layout inside each, so no second copy of
    the weights exists). "forward" -> (loss, ``forward_differences`` against the
    system's layers) of its OWN pass. The backward pass is WALKED, one
    program a piece, because six layers' float32 activations and a whole
    float32 gradient tree do not fit beside the engine's state (the first
    chip run: 11.3 GB asked of 7.8 free) — the reference pinned to the
    experts the system chose: "streams" -> every layer's input stream and
    the last one's output; "head" -> (loss, the cotangent of that output,
    {leaf: (squared error, squared norm)} of the head's and the final
    norm's gradients against the system's); "layer" -> (the cotangent of the
    layer's input, the same pairs for the layer's leaves) from the cotangent
    of its output; "embed" -> the pair for the embedding. One "layer"
    program serves every layer (the index is traced)."""
    import jax
    import jax.numpy as jnp
    sizes_ = dict(sizes_items)
    eps = sizes_["eps"]

    def view(w):
        return shared.reference_view(w, n_layers)

    def pair(got, want):
        got = got.astype(jnp.float32)
        return jnp.sum(jnp.square(got - want)), jnp.sum(jnp.square(want))

    @jax.jit
    def forward(p, ids, noise, system_layers):
        loss, detail = ref.loss(p, ids, noise, view, control=control,
                                **sizes_)
        return loss, forward_differences(system_layers, detail["layers"])

    @jax.jit
    def streams(p, ids, noise, experts):
        with jax.default_matmul_precision("highest"):
            top, layers = view(p)
            x = ref.embed_rows(top, ids, noise[0])
            xs = []
            for lyr, e in zip(layers, experts):
                xs.append(x)
                x, _ = ref.layer(x, lyr, e, control=control, **sizes_)
            return tuple(xs), x

    @jax.jit
    def head(p, x, ids, noise, system_grads):
        with jax.default_matmul_precision("highest"):
            top = view(p)[0]
            small = {"norm": top["norm"], "lm_head": top["lm_head"]}
            loss, back = jax.vjp(
                lambda x, w: ref.head_loss(x, w, ids, noise[1], noise[2],
                                           eps=eps, control=control),
                x, small)
            c, g = back(jnp.ones((), jnp.float32))
        got = view(system_grads)[0]
        return loss, c, {n: pair(got[n], g[n]) for n in g}

    @jax.jit
    def layer(p, system_grads, i, x, c, experts):
        with jax.default_matmul_precision("highest"):
            _, back = jax.vjp(
                lambda x, w: ref.layer(x, w, experts, control=control,
                                       **sizes_)[0], x, _one_layer(p, i))
            c, g = back(c)
        got = _one_layer(system_grads, i)
        return c, {n: pair(got[n], g[n]) for n in g}

    @jax.jit
    def embed(p, ids, noise, c, system_grads):
        rows = jnp.concatenate([noise[0], ids], axis=1).reshape(-1)
        want = jnp.zeros(p["embed_tokens"].shape, jnp.float32).at[rows].add(
            c.reshape(rows.shape[0], -1))
        return pair(system_grads["embed_tokens"], want)

    return {"forward": forward, "streams": streams, "head": head,
            "layer": layer, "embed": embed}


def _program(mode, config, rehearse, control):
    return _reference_programs(
        sizes(config, rehearse)["num_hidden_layers"],
        tuple(sorted(reference_sizes(config, rehearse).items())),
        control)[mode]


def reference_backward(config, params, ids, noise, experts, system_grads,
                       rehearse, control=None):
    """(the reference's loss and gradient norm at the experts the system
    chose, {leaf: |system - reference| / |reference|, a layer's leaf as ONE
    vector over the layers}), walked from the head down a layer at a time
    (``_reference_programs``). Pooled over the layers and not the worst
    layer's: a layer none of whose held experts drew a row of this batch has
    expert, router and second-norm gradients of exactly zero on both sides,
    and its 0 / 0 made a run not correct (seed 6000000505: my chip run, PR
    60); a fault in one layer of six still reads 0.4 of its size."""
    import jax
    import jax.numpy as jnp
    run = functools.partial(_program, config=config, rehearse=rehearse,
                            control=control)
    xs, x = run("streams")(params, ids, noise, experts)
    loss, c, sums = run("head")(params, x, ids, noise, system_grads)
    del x
    sums, xs = dict(sums), list(xs)
    for i in reversed(range(len(experts))):
        c, pairs = run("layer")(params, system_grads, jnp.int32(i), xs.pop(),
                                c, experts[i])
        for n, pair in pairs.items():
            sums[n] = tuple(a + b for a, b in zip(sums.get(n, (0.0, 0.0)),
                                                  pair))
    sums["embed"] = run("embed")(params, ids, noise, c, system_grads)
    sums = jax.device_get(sums)
    return (float(loss),
            float(np.sqrt(sum(ref_sq for _, ref_sq in sums.values()))),
            {n: float(np.sqrt(err / ref_sq))
             for n, (err, ref_sq) in sums.items()})


def compare(config, params, batch_ids, device, rehearse, system,
            control=None):
    """(reference loss, reference gradient norm, differences) of ``system``
    (``system_step``'s four values) against the plain reference on the same
    weights, batch and noise: the reference's own forward pass for the loss,
    the routing and the two branches, then its backward pass at the experts
    the system chose for the gradient norm and every gradient leaf.
    ``control``: one of ``reference/sdar.CONTROLS``, the reference computed
    with that fault."""
    import jax
    _, layers, grads, noise = system
    params = jax.device_put(params, device)
    ids = jax.device_put(np.asarray(batch_ids), device)
    loss, diffs = jax.device_get(_program("forward", config, rehearse,
                                          control)(
        params, ids, noise, tuple(layers)))
    diffs = {k: int(v) if k.startswith("routing") else float(v)
             for k, v in diffs.items()}
    diffs["masked_share"] = float(np.mean(np.asarray(noise[1])))
    diffs["system_grad_norm"] = float(ref.grad_norm(
        jax.tree_util.tree_map(lambda g: g.astype("float32"), grads)))
    _, gnorm, diffs["grad_leaf_rel"] = reference_backward(
        config, params, ids, noise,
        tuple(layer["top_e"] for layer in layers), grads, rehearse, control)
    return float(loss), gnorm, diffs


def reference_train(config, params, batch_ids, devices, rehearse):
    """``compare`` of the program's model as the configuration builds it,
    under the key the engine's first step will take. Call before it."""
    return compare(config, params, batch_ids, devices[0], rehearse,
                   system_step(config, params, batch_ids, devices[0],
                               rehearse))


def judge_train(config, got_loss, got_gnorm, want_loss, want_gnorm,
                differences=None):
    """``families/olmoe.judge_train`` (loss, gradient norm, routing, the two
    branches, every gradient leaf) with the attention branch held on each
    half against that half's own limit, this family's own engine folded for
    the gauges, and that the step masked rows at all."""
    tol = config["train"]["tolerance"]
    if differences is not None:
        # OLMoE's two keys: the worse half, each against its own limit, and
        # the expert branch as one vector
        differences = dict(differences, attn_out_rel=max(
            differences[f"attn_out_{half}_rel"] / tol[f"attn_out_{half}_rel"]
            for half in ("noised", "clean")),
            ffn_out_row_rel=differences["ffn_out_rel"])
        config = dict(config, train=dict(config["train"], tolerance=dict(
            tol, attn_out_rel=1.0, ffn_out_row_rel=tol["ffn_out_rel"])))
    checks, detail = shared.judge_train(config, got_loss, got_gnorm,
                                        want_loss, want_gnorm, differences)
    if differences is not None:
        detail["differences"]["tolerances"].update(
            {k: tol[k] for k in ("attn_out_noised_rel", "attn_out_clean_rel",
                                 "ffn_out_rel")})
    checks.pop("no_routed_row_dropped", None)    # that family's engine's
    engine = _LIVE.get("engine")
    gauges = _LIVE["gauges"] = \
        engine.telemetry_flush()["gauges"] if engine is not None else {}
    if "moe/dropped_rows" in gauges:
        checks["no_routed_row_dropped"] = gauges["moe/dropped_rows"] == 0
        checks["rows_were_masked"] = \
            0.0 < gauges.get("diffusion/masked_share", 0.0) < 1.0
        detail["expert_placement"] = _LIVE.get("placement")
        detail["program_gauges"] = {
            k: v for k, v in gauges.items()
            if k.startswith(("moe/", "attention/bd_", "diffusion/"))}
    return checks, detail


# ------------------------------------------------- operations and bytes

def allowed_pairs(seq_len, block_length):
    """(query, key) pairs the block-diffusion mask allows a head, over the 2L
    rows of one sequence: L Bk (noised -> its own block) + L (L - Bk) / 2
    (noised -> the clean rows of earlier blocks) + L (L + Bk) / 2 (clean ->
    clean, block-causal) = L^2 + L Bk. 67,141,632 at L 8,192, Bk 4."""
    return seq_len * seq_len + seq_len * block_length


def rows_held_share(config, rehearse=False):
    """Share of the T x k routed rows a uniform router sends to the experts
    held here: 1 / ``expert_parallel_size``."""
    return 1.0 / sizes(config, rehearse)["expert_parallel_size"]


def layer_matmul_params(config, rehearse=False):
    """Parameters one ROW is multiplied with in a layer HERE: the attention
    projections, the router (all published experts wide), and the k experts
    times the share of them held here."""
    s = sizes(config, rehearse)
    H, D = s["hidden_size"], s["head_dim"]
    return 2 * H * s["num_attention_heads"] * D \
        + 2 * H * s["num_key_value_heads"] * D \
        + H * s["num_experts"] * s["expert_parallel_size"] \
        + s["num_experts_per_tok"] * rows_held_share(config, rehearse) \
        * 3 * H * s["moe_intermediate_size"]


def train_attention_flops_per_step(config, batch, seq_len, rehearse=False):
    """Flops the mask kernels NEED in one step, forward + backward: six
    products (QK^T, PV; dV, dP, dQ, dK) of 2 x head_dim a pair, over the
    allowed pairs alone: layers x heads x 12 x pairs x head_dim. The dense
    (2L)^2 is not what the mathematics needs and would let a share read
    over 100 %."""
    s = sizes(config, rehearse)
    return s["num_hidden_layers"] * batch * s["num_attention_heads"] * 12 \
        * allowed_pairs(seq_len, s["block_length"]) * s["head_dim"]


def train_flops_per_token(config, seq_len, rehearse=False):
    """Per CLEAN token (what ``train_tokens_per_s`` counts): 6 a matmul
    parameter (2 forward, 4 backward) over the TWO rows a token puts through
    every layer, the head over the noised row alone, and the attention of
    ``train_attention_flops_per_step``."""
    s = sizes(config, rehearse)
    return 6 * (2 * s["num_hidden_layers"]
                * layer_matmul_params(config, rehearse)
                + s["vocab_size"] * s["hidden_size"]) \
        + train_attention_flops_per_step(config, 1, seq_len, rehearse) \
        / seq_len


def attention_bytes_per_step(config, batch, seq_len, rehearse=False):
    """Bytes the mask kernels must move in one step at the least, bf16: the
    forward reads q, k, v and writes o; the backward reads q, k, v, o, do
    and writes dq, dk, dv (K and V at their own heads). The kernels are
    compute-bound (``train_attention_flops_per_step`` over this is ~1,700
    flops a byte at L 8,192); the count is here for the roofline's other
    side."""
    s = sizes(config, rehearse)
    rows, D = 2 * seq_len * batch, s["head_dim"]
    q, kv = s["num_attention_heads"] * D, s["num_key_value_heads"] * D
    return s["num_hidden_layers"] * 2 * rows * ((2 * q + 2 * kv)
                                                + (4 * q + 4 * kv))


def moe_gmm_flops_per_step(config, tokens, rehearse=False):
    """Flops the grouped matmuls of one step NEED for ``tokens`` clean
    tokens: three products (forward, dlhs, drhs) of gate, up and down, every
    layer, over the EXPECTED rows held — 1 / ``expert_parallel_size`` of the
    2 x tokens x k rows the router assigns (two rows a token). Held against
    ``moe_rows_held_share`` (the step's own count) before it is believed."""
    s = sizes(config, rehearse)
    rows = 2 * tokens * s["num_experts_per_tok"] \
        * rows_held_share(config, rehearse)
    return s["num_hidden_layers"] * 3 * 3 * 2 * rows * s["hidden_size"] \
        * s["moe_intermediate_size"]
