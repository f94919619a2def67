"""The GPT-2 family: how a configuration file becomes a running system.

A family file is the only place that knows a model's classes. It builds
the model from a configuration's sizes, makes the weights on the device
from the seed, hands the system under test to a traffic kind through the
program's normal entry points (``dstpu.initialize``,
``serving.build_engine``), maps the system's parameter tree onto the plain
reference's layout, and decides ``correct``. A later family is a new file
here with the same members (``benchmark/families/__init__.py`` is the list).
"""

import dataclasses

import numpy as np

from benchmark import roofline
from benchmark.families import common
from benchmark.reference import gpt2 as ref

WIDTH_KEYS = ("n_embd", "n_head", "n_inner")
KERNEL_TAGS = ("flash_fwd", "flash_bwd")    # also their long-S variants
MODULE_TAGS = ("ds_loss_head", "ds_embed", "attn", "mlp", "ln_1", "ln_2",
               "ln_f")


def sizes(config, rehearse):
    """The configuration's sizes; a CPU rehearsal overrides them with the
    tiny ones the file carries."""
    keys = ("vocab_size", "n_positions", "n_embd", "n_layer", "n_head",
            "layer_norm_epsilon")
    out = {k: config[k] for k in keys}
    if rehearse:
        out.update({k: v for k, v in config["rehearse_cpu"].items()
                    if k in keys})
    return out


def traffic_shapes(config, rehearse):
    s = sizes(config, rehearse)
    return {"vocab_size": s["vocab_size"], "max_positions": s["n_positions"],
            "seq_scale": s["n_positions"] / config["n_positions"]}


def model_config(config, rehearse, serving=False):
    import jax.numpy as jnp
    from deepspeed_tpu.models.gpt2 import GPT2Config
    m = common.merged(config, "model", rehearse)
    dtypes = {"bfloat16": jnp.bfloat16, "float32": jnp.float32}
    cfg = GPT2Config(dtype=dtypes[m["dtype"]],
                     param_dtype=dtypes[m["param_dtype"]],
                     scan_layers=m["scan_layers"], remat=m["remat"],
                     remat_policy=m["remat_policy"],
                     loss_chunk=m["loss_chunk"], dropout=config["resid_pdrop"],
                     **sizes(config, rehearse))
    if serving:
        cfg = dataclasses.replace(cfg, remat=False, loss_chunk=0)
    return cfg


# ----------------------------------------------------------------- training

def _model(config, rehearse):
    from deepspeed_tpu.models.gpt2 import GPT2LMHeadModel
    return GPT2LMHeadModel(model_config(config, rehearse))


def build_train(config, global_batch, seed, devices, rehearse):
    """(engine, initial parameters): ``common.build_train``'s recipe over
    ``GPT2LMHeadModel``, the weights made from a full-length example."""
    model = _model(config, rehearse)
    return common.build_train(model, config, global_batch, seed, devices,
                              rehearse, example_len=model.config.n_positions)


def lower_train_step(config, traffic, devices):
    """The cell's train step at real size, lowered over abstract state on
    ``devices`` (described chips)."""
    return common.lower_train_step(_model(config, rehearse=False), config,
                                   traffic, devices)


def _reference_view(params, n_layer, device):
    """(top, layer(i)) in the reference's layout, as float32 on ``device``,
    from the scan-stacked tree of ``GPT2LMHeadModel`` (or its bf16 cast)."""
    import jax
    import jax.numpy as jnp

    def put(x):
        return jax.device_put(x, device).astype(jnp.float32)

    def pair(d, a, b):
        return (put(d[a]), put(d[b]))

    blk = params["h"]["blk"]
    top = {"wte": put(params["wte"]), "wpe": put(params["wpe"]),
           "ln_f": pair(params["ln_f"], "scale", "bias")}

    def layer(i):
        at = jax.tree_util.tree_map(lambda x: x[i], blk)
        return {"ln_1": pair(at["ln_1"], "scale", "bias"),
                "c_attn": pair(at["attn"]["c_attn"], "kernel", "bias"),
                "c_proj": pair(at["attn"]["c_proj"], "kernel", "bias"),
                "ln_2": pair(at["ln_2"], "scale", "bias"),
                "c_fc": pair(at["mlp"]["c_fc"], "kernel", "bias"),
                "mlp_proj": pair(at["mlp"]["c_proj"], "kernel", "bias")}
    return top, layer


def reference_train(config, params, batch_ids, devices, rehearse):
    """(loss, gradient norm) of the plain reference on ``batch_ids`` at the
    weights ``params`` holds, as Python floats; the batch's sequences are
    dealt onto ``devices``. Call before the engine's first step (it donates
    ``params``)."""
    import jax
    s = sizes(config, rehearse)
    top0, layer0 = _reference_view(params, s["n_layer"], devices[0])
    tops = {d: jax.device_put(top0, d) for d in devices}
    held = {}

    def layer(i, device):
        # one layer at a time: gathered and cast once, then copied per chip
        if held.get("i") != i:
            held.clear()
            held.update(i=i, first=layer0(i))
        if device not in held:
            held[device] = jax.device_put(held["first"], device)
        return held[device]

    loss, gnorm = ref.loss_and_grad_norm(
        tops.__getitem__, layer, s["n_layer"], s["n_head"],
        s["layer_norm_epsilon"], np.asarray(batch_ids), devices)
    return float(loss), float(gnorm)


def judge_train(config, got_loss, got_gnorm, want_loss, want_gnorm):
    tol = config["train"]["tolerance"]
    checks = {
        "first_loss_matches_reference":
            abs(got_loss - want_loss) <= tol["loss_abs"],
        "first_grad_norm_matches_reference":
            abs(got_gnorm - want_gnorm) <= tol["grad_norm_rel"] * want_gnorm}
    detail = {"loss": [got_loss, want_loss], "loss_abs_tol": tol["loss_abs"],
              "grad_norm": [got_gnorm, want_gnorm],
              "grad_norm_rel_tol": tol["grad_norm_rel"]}
    return checks, detail


# ------------------------------------------------------------------ serving

def build_serving(config, seed, rehearse, registry):
    """(engine, weights as served). Weights are made and cast to the served
    dtype in one jitted call on the device; no float32 copy outlives it."""
    import jax
    import jax.numpy as jnp
    import deepspeed_tpu.serving as serving
    from deepspeed_tpu.models.gpt2 import GPT2LMHeadModel

    cfg = model_config(config, rehearse, serving=True)
    sv = common.merged(config, "serve", rehearse)
    served = {"bfloat16": jnp.bfloat16, "float32": jnp.float32}[
        sv["weights_dtype"]]
    model = GPT2LMHeadModel(cfg)

    @jax.jit
    def make(key):
        p = model.init(key, jnp.zeros((1, 8), jnp.int32))["params"]
        return jax.tree_util.tree_map(lambda a: a.astype(served), p)

    params = make(jax.random.PRNGKey(seed))
    eng = serving.build_engine("gpt2", cfg, params,
                               config={"serving": sv["serving"]},
                               registry=registry)
    return eng, params


def check_serving(config, eng, params, prompts, rehearse, pad_to,
                  decoded=16):
    """Serve ``prompts`` for ``decoded + 1`` tokens each on the idle engine
    and hold two rows of logits per request to the reference's full forward
    over prompt + generated tokens: the prefill's (which chose the first
    token) and the last decode step's, ``decoded`` tokens later through the
    paged cache. Logits, not tokens: with random weights the largest logit
    changes on rounding. The reference runs every sequence zero-padded to
    ``pad_to`` tokens — under a causal mask what follows a position cannot
    reach it — so that it compiles one shape per cell and not one per seed."""
    import jax
    import jax.numpy as jnp
    import deepspeed_tpu.serving as serving
    from deepspeed_tpu.serving.paged_cache import (TRASH_BLOCK,
                                                   padded_prefill_inputs)
    s = sizes(config, rehearse)
    tol = config["serve"]["tolerance"]["logits_abs"]
    assert eng.pending == 0 and len(prompts) <= eng.spec.slots
    # the adapter's prefill program for each prompt (the executable admission
    # runs), K/V written to the trash block so that no live page is touched
    P = eng.spec.page_size
    prefill_logits = []
    for prompt in prompts:
        ids, pages = padded_prefill_inputs(
            prompt, [], P, eng.adapter.max_prompt_len() // P)
        assert set(pages.tolist()) == {TRASH_BLOCK}
        eng.cache.pool, got = eng.adapter.prefill(
            eng.cache.pool, jnp.asarray(ids),
            jnp.asarray(len(prompt), jnp.int32), jnp.asarray(pages))
        prefill_logits.append(np.asarray(got, np.float32).reshape(-1))
    # an idle engine admits FIFO into slots 0..n-1, and with equal budgets
    # decodes them in one tick of ``decoded`` steps: last_logits[i] is
    # request i's final step
    reqs = [serving.Request(("check", i), p, max_new_tokens=decoded + 1)
            for i, p in enumerate(prompts)]
    for r in reqs:
        eng.submit(r)
    while eng.pending:
        eng.step()
    last = np.asarray(eng.last_logits, np.float32)

    device = jax.devices()[0]
    top, layer = _reference_view(params, s["n_layer"], device)
    diffs, first_ok = [], []
    for i, (req, prompt) in enumerate(zip(reqs, prompts)):
        S = len(prompt)
        seq = np.zeros(max(pad_to, S + decoded), np.int32)
        seq[:S] = prompt
        seq[S:S + decoded] = req.generated[:decoded]
        want = np.asarray(ref.logits(
            top, layer, s["n_layer"], s["n_head"], s["layer_norm_epsilon"],
            jax.device_put(seq, device), [S - 1, S + decoded - 1]))
        diffs.append([float(np.max(np.abs(prefill_logits[i] - want[0]))),
                      float(np.max(np.abs(last[i] - want[1])))])
        first_ok.append(len(req.generated) == decoded + 1 and
                        int(np.argmax(prefill_logits[i])) == req.generated[0])
    checks = {
        "prefill_logits_match_reference": max(d[0] for d in diffs) <= tol,
        "decode_logits_match_reference": max(d[1] for d in diffs) <= tol,
        "first_token_is_argmax_of_prefill_logits": all(first_ok)}
    detail = {"logit_max_abs_diff_prefill_then_decode": diffs,
              "logits_abs_tol": tol, "prompt_tokens": [len(p) for p in prompts]}
    return checks, detail


def lower_serving(config, traffic, device):
    """(facts, programs): the pool's size, and lazily (name, Lowered) of
    every tick program (each step count the engine uses) and prefill bucket
    of the cell, over abstract weights and the configured pool on
    ``device``."""
    import jax
    import jax.numpy as jnp
    from jax.sharding import SingleDeviceSharding
    from deepspeed_tpu.models.gpt2 import GPT2LMHeadModel
    from deepspeed_tpu.models.gpt2_inference import convert_gpt2_params
    from deepspeed_tpu.serving import (GPT2ServingAdapter,
                                       cache_spec_from_config)
    SDS, I32 = jax.ShapeDtypeStruct, jnp.int32
    cfg = model_config(config, rehearse=False, serving=True)
    one = SingleDeviceSharding(device)

    def on_chip(tree):
        return jax.tree_util.tree_map(
            lambda s: SDS(s.shape, s.dtype, sharding=one), tree)

    served = jnp.dtype(config["serve"]["weights_dtype"])
    ip = jax.eval_shape(
        lambda r: convert_gpt2_params(jax.tree_util.tree_map(
            lambda a: a.astype(served), GPT2LMHeadModel(cfg).init(
                r, jnp.zeros((1, 8), I32))["params"]), cfg),
        jax.random.PRNGKey(0))
    spec = cache_spec_from_config(cfg, "gpt2",
                                  {"serving": config["serve"]["serving"]})
    nb = spec.resolved_num_blocks()
    shape = (spec.n_layers, nb, spec.kv_heads, spec.page_size, spec.head_dim)
    pool = (SDS(shape, spec.dtype), SDS(shape, spec.dtype))
    adapter = GPT2ServingAdapter(cfg, ip, spec)
    B, MAXP, Pg = spec.slots, spec.max_pages_per_slot, spec.page_size

    def vec(dt):
        return SDS((B,), dt)

    def programs():
        for steps in traffic.get("tick_steps", [1, 2, 4, 8, 16, 32]):
            yield f"tick x{steps}", adapter._tick_fn(steps).lower(*on_chip((
                adapter._p, adapter._blk, pool, vec(I32), vec(I32),
                SDS((B, MAXP), I32), vec(jnp.uint32), vec(I32),
                vec(jnp.float32))))
        for pages in traffic["prefill_page_buckets"]:
            yield f"prefill {pages * Pg}", adapter._prefill_fn(pages).lower(
                *on_chip((adapter._p, adapter._blk, pool,
                          SDS((1, pages * Pg), I32), SDS((), I32),
                          SDS((pages,), I32))))

    return {"pool_blocks": nb, "pool_gb": 2 * np.prod(shape) * 2 / 1e9}, \
        programs()


# ------------------------------------------------- operations and bytes

def train_flops_per_token(config, seq_len, rehearse=False):
    s = sizes(config, rehearse)
    return roofline.dense_train_flops_per_token(
        s["n_layer"], s["n_embd"], s["vocab_size"], seq_len)


def train_attention_flops_per_step(config, batch, seq_len, rehearse=False):
    """Causal flops of the flash forward and backward kernels in one step."""
    s = sizes(config, rehearse)
    return s["n_layer"] * roofline.causal_attention_train_flops(
        batch, s["n_head"], seq_len, s["n_embd"] // s["n_head"])


def decode_kv_bytes(config, contexts, rehearse=False):
    """Bytes of K and V one decode step must read for slots holding
    ``contexts`` tokens each, in the cache's served dtype (bf16)."""
    s = sizes(config, rehearse)
    return roofline.kv_read_bytes(s["n_layer"], s["n_embd"], contexts, 2)


def weight_bytes(config, rehearse=False):
    s = sizes(config, rehearse)
    E, L = s["n_embd"], s["n_layer"]
    n = L * (12 * E * E + 13 * E) + (s["vocab_size"] + s["n_positions"]) * E \
        + 2 * E
    return 2 * n


def kv_bytes_per_token(config, rehearse=False):
    s = sizes(config, rehearse)
    return 2 * s["n_layer"] * s["n_embd"] * 2
