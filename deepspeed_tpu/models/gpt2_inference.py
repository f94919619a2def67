"""GPT-2 serving path — the fused inference stack + KV-cache generation.

The reference serves GPT-2/Megatron by injecting fused inference kernels
into a live torch model (module_inject/replace_module.py:8 with
`MegatronLayerPolicy`, kernels in csrc/transformer/inference/). Here the
same role is a pure pytree conversion: training `GPT2LMHeadModel` params →
`GPT2InferenceModel` (a stack of `DeepSpeedTransformerInference` layers with
flax cache collections) + a jitted incremental `generate` loop.

Decode step cost is one [B,1,E] pass over cached K/V — bandwidth-bound,
static shapes, compiled once.
"""

import dataclasses
import functools
from typing import Any, Optional

import numpy as np
import jax
import jax.numpy as jnp
import flax.linen as nn

from deepspeed_tpu.models.gpt2 import GPT2Config
from deepspeed_tpu.ops.transformer.inference import (
    DeepSpeedInferenceConfig,
    DeepSpeedTransformerInference,
)


def inference_config(cfg: GPT2Config, max_out_tokens: int = 0,
                     dtype=None, quantize_bits: int = 0,
                     quantize_groups: int = 1,
                     kv_cache_bits: int = 0,
                     mp_size: int = 1) -> DeepSpeedInferenceConfig:
    return DeepSpeedInferenceConfig(
        mp_size=mp_size,
        hidden_size=cfg.n_embd,
        heads=cfg.n_head,
        layer_norm_eps=cfg.layer_norm_epsilon,
        pre_layer_norm=True,
        triangular_masking=True,
        max_out_tokens=max_out_tokens or cfg.n_positions,
        gelu_approximate=True,   # GPT-2 trains with tanh-approx GELU
        moe_experts=cfg.moe_experts,
        moe_k=cfg.moe_k,
        moe_capacity_factor=cfg.moe_capacity_factor,
        quantize_bits=quantize_bits,
        quantize_groups=quantize_groups,
        kv_cache_bits=kv_cache_bits,
        dtype=dtype or cfg.dtype,
        param_dtype=cfg.param_dtype,
    )


class _ScanInferenceLayer(nn.Module):
    config: DeepSpeedInferenceConfig

    @nn.compact
    def __call__(self, x, attention_mask):
        layer = DeepSpeedTransformerInference(self.config, name="blk")
        return layer(x, attention_mask), None


class GPT2InferenceModel(nn.Module):
    """GPT-2 LM built on the fused inference layer. Param layout mirrors the
    training model's embeddings (`wte`/`wpe`/`ln_f`) with injected fused
    blocks under `h/blk` (scan) — produced by `convert_gpt2_params`."""
    config: GPT2Config
    max_out_tokens: int = 0
    quantize_bits: int = 0      # int8-storage serving (4x weight memory)
    quantize_groups: int = 1
    kv_cache_bits: int = 0      # int8 KV cache (2x cache memory vs bf16)
    mp_size: int = 1            # model-axis TP shards (reference mp_size)

    @nn.compact
    def __call__(self, input_ids, position_offset=0):
        cfg = self.config
        icfg = inference_config(cfg, self.max_out_tokens,
                                quantize_bits=self.quantize_bits,
                                quantize_groups=self.quantize_groups,
                                kv_cache_bits=self.kv_cache_bits,
                                mp_size=self.mp_size)
        B, S = input_ids.shape
        wte = self.param("wte", nn.initializers.normal(0.02),
                         (cfg.vocab_size, cfg.n_embd), cfg.param_dtype)
        wpe = self.param("wpe", nn.initializers.normal(0.01),
                         (cfg.n_positions, cfg.n_embd), cfg.param_dtype)
        pos = position_offset + jnp.arange(S)
        x = wte[input_ids].astype(cfg.dtype) \
            + wpe[pos][None].astype(cfg.dtype)

        # unroll the layer scan (GPT2Config.scan_unroll): decode ticks are
        # ~15 small ops per layer, so per-iteration fixed costs are a real
        # fraction of the token; unrolling also lets XLA fuse elementwise
        # chains across layers. Measured serving-config dependent (r4
        # ablation) — the serving entry points pick their measured best.
        scanned = nn.scan(_ScanInferenceLayer,
                          variable_axes={"params": 0, "cache": 0},
                          split_rngs={"params": True},
                          in_axes=(nn.broadcast,),
                          length=cfg.n_layer,
                          unroll=max(1, cfg.scan_unroll))
        x, _ = scanned(icfg, name="h")(x, None)

        x = nn.LayerNorm(epsilon=cfg.layer_norm_epsilon, dtype=cfg.dtype,
                         param_dtype=cfg.param_dtype, name="ln_f")(x)
        if cfg.tie_word_embeddings:
            return jnp.einsum("bse,ve->bsv", x, wte.astype(cfg.dtype))
        return nn.Dense(cfg.vocab_size, use_bias=False, dtype=cfg.dtype,
                        param_dtype=cfg.param_dtype, name="lm_head")(x)


def _convert_block(blk):
    """Training Block subtree → fused inference layer subtree (the weight
    copy of replace_module.py:24-79; orientations are identical since both
    sides are flax Dense kernels [in, out]). MoE blocks carry their
    gate+expert bank through verbatim (the inference layer instantiates
    the same MoE module under the same name)."""
    out = {
        "attn_nw": dict(blk["ln_1"]),
        "attn_qkvw": dict(blk["attn"]["c_attn"]),
        "attn_ow": dict(blk["attn"]["c_proj"]),
        "norm_w": dict(blk["ln_2"]),
    }
    if "moe" in blk:
        out["moe"] = dict(blk["moe"])
    else:
        out["inter_w"] = dict(blk["mlp"]["c_fc"])
        out["output_w"] = dict(blk["mlp"]["c_proj"])
    return out


def convert_gpt2_params(params, cfg: GPT2Config):
    """Training `GPT2LMHeadModel` params → `GPT2InferenceModel` params.

    Handles both layouts: scan-stacked (`h/blk/...` leaves with a leading
    [L] axis — converted wholesale, the stacking carries over) and unrolled
    (`h_0`..`h_{L-1}` — re-stacked onto a leading layer axis)."""
    out = {"wte": params["wte"], "wpe": params["wpe"],
           "ln_f": dict(params["ln_f"])}
    if not cfg.tie_word_embeddings:
        out["lm_head"] = dict(params["lm_head"])
    if "h" in params:
        out["h"] = {"blk": _convert_block(params["h"]["blk"])}
    else:
        blocks = [_convert_block(params[f"h_{i}"])
                  for i in range(cfg.n_layer)]
        out["h"] = {"blk": jax.tree_util.tree_map(
            lambda *xs: jnp.stack(xs), *blocks)}
    return out


_STEP_CACHE = {}


def _compiled_steps(cfg: GPT2Config, max_out: int, quantize_bits: int = 0,
                    quantize_groups: int = 1, kv_cache_bits: int = 0,
                    mp_size: int = 1):
    """(prompt_pass, decode_step, decode_scan) jitted once per (config,
    cache length) — repeated generate() calls hit jit's cache instead of
    retracing the whole model per request. decode_scan additionally
    recompiles per distinct step COUNT (its scan length is static);
    callers generating many different lengths should bucket them or use
    the per-token decode_step path (generate(..., scan_decode=False))."""
    key = (cfg, max_out, quantize_bits, quantize_groups, kv_cache_bits,
           mp_size)
    if key not in _STEP_CACHE:
        model = GPT2InferenceModel(cfg, max_out_tokens=max_out,
                                   quantize_bits=quantize_bits,
                                   quantize_groups=quantize_groups,
                                   kv_cache_bits=kv_cache_bits,
                                   mp_size=mp_size)

        @jax.jit
        def prompt_pass(p, ids):
            logits, vars_ = model.apply({"params": p}, ids,
                                        mutable=["cache"])
            return logits[:, -1], vars_["cache"]

        @functools.partial(jax.jit, donate_argnums=(1,))
        def decode_step(p, cache, tok, offset):
            # donated cache: the update aliases in place instead of
            # copying the (multi-GB at batch) KV buffers every token
            logits, vars_ = model.apply(
                {"params": p, "cache": cache}, tok[:, None],
                position_offset=offset, mutable=["cache"])
            return logits[:, -1], vars_["cache"]

        @functools.partial(jax.jit, static_argnums=(5,),
                           donate_argnums=(1,))
        def decode_scan(p, cache, first_tok, start, rngs, steps,
                        temperature):
            """The whole decode loop as ONE compiled program (one host
            dispatch for `steps` tokens — on dispatch-latency-bound
            backends the python per-token loop costs more than the math).
            `temperature` is a traced operand so per-request sampling
            temperatures don't recompile."""
            def tick(carry, r):
                cache, tok, offset = carry
                logits, vars_ = model.apply(
                    {"params": p, "cache": cache}, tok[:, None],
                    position_offset=offset, mutable=["cache"])
                logits = logits[:, -1]
                # cond, not where: greedy decode must not pay the Gumbel
                # sampling over [B, V] every tick (the tick body is
                # collective-free, so diverging branches are safe here)
                nxt = jax.lax.cond(
                    temperature > 0,
                    lambda: jax.random.categorical(
                        r, logits / jnp.maximum(temperature, 1e-6), axis=-1),
                    lambda: jnp.argmax(logits, axis=-1))
                return (vars_["cache"], nxt, offset + 1), tok
            (final_cache, last, _), toks = jax.lax.scan(
                tick, (cache, first_tok, start), rngs, length=steps)
            # toks are the INPUT tokens of each tick: [steps, B] starting
            # with first_tok; append the final pick for steps+1 outputs.
            # The final cache is RETURNED (callers discard it) so the
            # donated input cache has an output to alias: without it XLA
            # cannot run the per-tick cache updates in place and copies
            # the full multi-MB caches through slice/update fusions every
            # layer every tick (~0.9 ms/token at GPT-2-large/2k — the
            # device trace's dynamic-slice/update fusions).
            return jnp.concatenate(
                [toks.transpose(1, 0), last[:, None]], axis=1), final_cache

        _STEP_CACHE[key] = (prompt_pass, decode_step, decode_scan)
    return _STEP_CACHE[key]


def quantize_gpt2_inference_params(iparams, groups: int = 1):
    """Injected inference params → int8-storage params (serve with
    `generate(..., quantize_bits=8)`): ~4x less HBM for the layer weights."""
    from deepspeed_tpu.ops.transformer.inference import \
        quantize_inference_params
    return quantize_inference_params(iparams, bits=8, groups=groups)




def gpt2_inference_tp_specs(iparams):
    """PartitionSpec tree for mp_size-sharded GPT-2 serving over the mesh
    'model' axis (the reference's module_inject mp_size sharding,
    replace_module.py:16-17), extended to the scan-stacked [L, ...] leaf
    layout this model uses: qkv + FFN-in column-parallel, output
    projections row-parallel, embeddings/norms/scales replicated. Works
    for both bf16 (`kernel`) and int8-storage (`kernel_q`) trees."""
    from deepspeed_tpu.parallel.mesh import MODEL_AXIS
    from jax.sharding import PartitionSpec as P

    def leaf_spec(path, leaf):
        names = [str(getattr(k, "key", k)) for k in path]
        nd = getattr(leaf, "ndim", 0)
        col = any(n in ("attn_qkvw", "inter_w") for n in names)
        row = any(n in ("attn_ow", "output_w") for n in names)
        last = names[-1] if names else ""
        if last in ("kernel", "kernel_q") and nd >= 2:
            if col:
                return P(*([None] * (nd - 1) + [MODEL_AXIS]))
            if row:
                return P(*([None] * (nd - 2) + [MODEL_AXIS, None]))
        if last == "bias" and col and nd >= 1:
            return P(*([None] * (nd - 1) + [MODEL_AXIS]))
        return P()
    return jax.tree_util.tree_map_with_path(leaf_spec, iparams)




def shard_inference_params(iparams, mesh):
    """device_put the (converted) inference params onto the mesh with the
    mp_size TP layout. Serving loops should call this ONCE and pass the
    sharded tree to every generate(): generate() skips the transfer when
    the leaves already carry the target shardings, but host/unsharded
    trees would otherwise be re-transferred per request."""
    from jax.sharding import NamedSharding
    specs = gpt2_inference_tp_specs(iparams)
    targets = jax.tree_util.tree_map(
        lambda sp: NamedSharding(mesh, sp), specs)
    # device_put is a no-op per leaf whose sharding already matches, so
    # repeated calls with a pre-sharded tree transfer nothing
    return jax.device_put(iparams, targets)




def _is_converted(params):
    """True for a `convert_gpt2_params` tree (fused blocks under h/blk),
    False for the training `GPT2LMHeadModel` tree."""
    return "attn_qkvw" in params.get("h", {}).get("blk", {})


def _ln_x(x, w, b, eps):
    from deepspeed_tpu.ops.pallas.decode import _ln
    return _ln(x, w, b, eps).astype(x.dtype)


# ------------------------------------------------ paged-serving layer math
#
# GPT-2 as serving/adapters.PagedServingAdapter sees it (docs/serving.md
# "Adding a family"): geometry, the params a program takes, and the
# embedding, qkv half, out+FFN half and head — once for decode ROWS
# ([N, E], one row a token, the stacked fused kernels) and once for a
# whole PROMPT ([1, S, E], de-quantised XLA matmuls). None of them sees
# the KV pool, the page table or the sampler.

def serving_geometry(cfg: GPT2Config):
    assert cfg.n_embd % cfg.n_head == 0
    return dict(n_layers=cfg.n_layer, kv_heads=cfg.n_head,
                head_dim=cfg.n_embd // cfg.n_head, dtype=cfg.dtype,
                max_prompt_len=cfg.n_positions, vocab_size=cfg.vocab_size)


def serving_params(cfg: GPT2Config, params, quantize_bits: int = 0):
    """(p, blk) a program takes, from the training tree or the converted
    (optionally int8) inference tree; ``quantize_bits=8`` quantises a
    full-precision tree to the int8 serving storage here, at build."""
    assert cfg.tie_word_embeddings, \
        "paged GPT-2 serving assumes the tied-embedding LM head"
    iparams = params if _is_converted(params) \
        else convert_gpt2_params(params, cfg)
    if quantize_bits == 8 \
            and "kernel_q" not in iparams["h"]["blk"]["attn_qkvw"]:
        iparams = quantize_gpt2_inference_params(iparams)
    return ({"wte": iparams["wte"], "wpe": iparams["wpe"],
             "ln_f": iparams["ln_f"]}, iparams["h"]["blk"])


def serving_row_weights(cfg: GPT2Config, p, blk):
    """What a decode program hoists out of its scans. Every per-layer
    parameter stays STACKED — the kernels fetch their own layer's
    LN/bias tiles via layer-indexed block maps and read the per-tensor
    scales from SMEM prefetch vectors (13 per-layer xs cost ~15-20 us
    of slice/copy EACH per layer — r5 b32 device trace) — reshaped
    [Lyr, 1, cols] ONCE here, not per layer call (layout copy). bf16
    stacks run the same kernels with scale 1."""
    Lyr = cfg.n_layer
    q8 = "kernel_q" in blk["attn_qkvw"]

    def r3(a):
        return a.reshape(Lyr, 1, a.shape[-1])

    def proj(sub):
        scale = sub["kernel_scale"].reshape(Lyr) if q8 \
            else jnp.ones((Lyr,), jnp.float32)
        return sub["kernel_q" if q8 else "kernel"], scale, r3(sub["bias"])

    def norm(sub):
        return r3(sub["scale"]), r3(sub["bias"])

    return {"wte": jnp.asarray(p["wte"]).astype(cfg.dtype),
            "wpe": jnp.asarray(p["wpe"]).astype(cfg.dtype),
            "ln_f": p["ln_f"],
            "ln1": norm(blk["attn_nw"]), "ln2": norm(blk["norm_w"]),
            "qkv": proj(blk["attn_qkvw"]), "o": proj(blk["attn_ow"]),
            "fc": proj(blk["inter_w"]), "out": proj(blk["output_w"])}


def serving_row_embed(cfg: GPT2Config, w, toks, pos):
    return w["wte"][toks] + w["wpe"][jnp.clip(pos, 0, cfg.n_positions - 1)]


def serving_row_qkv(cfg: GPT2Config, w, x, l, pos):
    """x [N, E] -> q, k, v [N, H, D] at layer ``l`` (positions live in
    the embedding, so ``pos`` is unused)."""
    from deepspeed_tpu.ops.pallas.decode import ln_qkv_int8_stacked
    E, H = cfg.n_embd, cfg.n_head
    qkv = ln_qkv_int8_stacked(x, *w["ln1"], *w["qkv"], l,
                              eps=cfg.layer_norm_epsilon)
    return tuple(qkv[:, i * E:(i + 1) * E].reshape(-1, H, E // H)
                 for i in range(3))


def serving_row_out_ffn(cfg: GPT2Config, w, ctx, x, l):
    from deepspeed_tpu.ops.pallas.decode import out_ffn_int8_stacked
    return out_ffn_int8_stacked(ctx, x, *w["o"], *w["ln2"], *w["fc"],
                                *w["out"], l, act="gelu_tanh",
                                eps=cfg.layer_norm_epsilon)


def serving_row_head(cfg: GPT2Config, w, x):
    return jnp.einsum(
        "be,ve->bv", _ln_x(x, w["ln_f"]["scale"], w["ln_f"]["bias"],
                           cfg.layer_norm_epsilon), w["wte"])


def serving_prompt_weights(cfg: GPT2Config, p, blk, positions):
    """What a prompt pass hoists out of its layer scan; ``positions``
    [S] are the rows' absolute positions."""
    return {"wte": jnp.asarray(p["wte"]).astype(cfg.dtype),
            "wpe": jnp.asarray(p["wpe"]).astype(cfg.dtype),
            "ln_f": p["ln_f"], "blk": blk, "positions": positions}


def _prompt_dense(cfg, sub, l, u):
    """u @ layer ``l`` of a stacked projection, de-quantised on the fly
    (the bias is the caller's: where it is added fixes the rounding)."""
    if "kernel_q" in sub:
        scale = sub["kernel_scale"].reshape(cfg.n_layer)[l]
        return u @ (sub["kernel_q"][l].astype(jnp.float32)
                    * scale).astype(cfg.dtype)
    return u @ sub["kernel"][l].astype(cfg.dtype)


def serving_prompt_embed(cfg: GPT2Config, w, ids):
    pos = jnp.clip(w["positions"], 0, cfg.n_positions - 1)
    return w["wte"][ids] + w["wpe"][pos][None]             # [1, S, E]


def serving_prompt_qkv(cfg: GPT2Config, w, x, l):
    """x [B, S, E] -> q, k, v [B, H, S, D] at layer ``l``."""
    blk, E, H = w["blk"], cfg.n_embd, cfg.n_head
    B, S = x.shape[:2]
    u = _ln_x(x, blk["attn_nw"]["scale"][l], blk["attn_nw"]["bias"][l],
              cfg.layer_norm_epsilon)
    qkv = _prompt_dense(cfg, blk["attn_qkvw"], l, u) \
        + blk["attn_qkvw"]["bias"][l].astype(cfg.dtype)
    return tuple(qkv[..., i * E:(i + 1) * E].reshape(B, S, H, E // H)
                 .transpose(0, 2, 1, 3) for i in range(3))


def serving_prompt_out_ffn(cfg: GPT2Config, w, ctx, x, l):
    blk = w["blk"]
    x = x + _prompt_dense(cfg, blk["attn_ow"], l, ctx) \
        + blk["attn_ow"]["bias"][l].astype(cfg.dtype)
    u = _ln_x(x, blk["norm_w"]["scale"][l], blk["norm_w"]["bias"][l],
              cfg.layer_norm_epsilon)
    h = jax.nn.gelu(_prompt_dense(cfg, blk["inter_w"], l, u)
                    + blk["inter_w"]["bias"][l].astype(cfg.dtype),
                    approximate=True)
    return x + _prompt_dense(cfg, blk["output_w"], l, h) \
        + blk["output_w"]["bias"][l].astype(cfg.dtype)


def serving_prompt_head(cfg: GPT2Config, w, xl):
    """Logits of ONE row xl [E] (the prompt's last position)."""
    return _ln_x(xl, w["ln_f"]["scale"], w["ln_f"]["bias"],
                 cfg.layer_norm_epsilon) @ w["wte"].T


def _supports_fast_decode(cfg: GPT2Config, B, quantize_bits,
                          quantize_groups, kv_cache_bits, mp_size):
    """Gate for the fused manual serving loop. Any combination of
    {bf16, int8} weights x {bf16, int8} KV cache is fused — the decode
    kernels are dtype-agnostic on the weight path (the reference's
    inference kernels are fp16-FIRST; quantization is an option, not a
    prerequisite: csrc/transformer/inference/csrc/pt_binding.cpp)."""
    return (quantize_bits in (0, 8) and kv_cache_bits in (0, 8)
            and (quantize_bits == 0 or quantize_groups == 1)
            and mp_size == 1 and B <= 64
            and cfg.n_embd % 128 == 0 and (4 * cfg.n_embd) % 128 == 0
            and cfg.scan_layers and cfg.moe_experts == 0
            and cfg.tie_word_embeddings)


def _fast_decode_scan_fn(cfg: GPT2Config, max_out: int,
                         weights_q8: bool = True, cache_q8: bool = True):
    """Manual serving loop over STACKED weights/caches — the flax
    nn.scan path slices every stacked array per layer per tick (~60% of
    the decode token in slice/unslice copies, device trace r4c); here
    the layer loop carries the whole caches (one in-place row update
    each) and the Pallas kernels index the weight/cache stacks directly
    via scalar-prefetched block maps (ops/pallas/decode.py *_stacked).

    ``weights_q8``/``cache_q8`` select int8 vs bf16 storage per side:
    the weight kernels are dtype-agnostic (bf16 stacks run with
    scale=1), the attention kernel has int8- and fp-cache variants, and
    bf16 caches skip the kv-quant kernel entirely (3 Pallas calls per
    layer instead of 4)."""
    key = ("fast", cfg, max_out, weights_q8, cache_q8)
    if key in _STEP_CACHE:
        return _STEP_CACHE[key]
    from deepspeed_tpu.ops.pallas.decode import (
        kv_quant_int8, decode_attention_int8_stacked,
        decode_attention_fp_stacked)
    E, H = cfg.n_embd, cfg.n_head
    D = E // H
    Lyr = cfg.n_layer

    @functools.partial(jax.jit, static_argnums=(4,),
                       donate_argnums=(2,))
    def fast_scan(p, blk, caches, first_tok, steps, start, rngs,
                  temperature):
        w = serving_row_weights(cfg, p, blk)
        B = first_tok.shape[0]
        L_cache = caches[0].shape[3]
        if cache_q8:
            # scale arrays live lane-major [Lyr, B, H, 1, L] for the
            # attention kernel's block maps; reshaping per layer call
            # materializes a full-stack copy each time (tiled layouts
            # differ), so do it ONCE here
            kc, ks, vc, vs = caches
            caches = (kc, ks.reshape(Lyr, B, H, 1, L_cache),
                      vc, vs.reshape(Lyr, B, H, 1, L_cache))

        def tick(carry, r):
            caches, tok, offset = carry
            x = serving_row_embed(cfg, w, tok,
                                  jnp.broadcast_to(offset, tok.shape))
            # overflow: clamped row writes would silently serve stale
            # context — poison, same contract as the flax path
            x = jnp.where(offset >= L_cache,
                          jnp.float32(jnp.nan).astype(x.dtype), x)

            def layer(car, l):
                x, caches = car
                q3, k3, v3 = serving_row_qkv(cfg, w, x, l, None)
                dus = jax.lax.dynamic_update_slice
                qh = q3[:, :, None, :]
                if cache_q8:
                    kc, ks, vc, vs = caches
                    kq8, ksc, vq8, vsc = kv_quant_int8(k3, v3)
                    kc = dus(kc, kq8[None, :, :, None, :],
                             (l, 0, 0, offset, 0))
                    vc = dus(vc, vq8[None, :, :, None, :],
                             (l, 0, 0, offset, 0))
                    ks = dus(ks, ksc.reshape(1, B, H, 1, 1),
                             (l, 0, 0, 0, offset))
                    vs = dus(vs, vsc.reshape(1, B, H, 1, 1),
                             (l, 0, 0, 0, offset))
                    ctx = decode_attention_int8_stacked(
                        qh, kc, ks, vc, vs, offset, l,
                        scale=1.0 / np.sqrt(D))
                    caches = (kc, ks, vc, vs)
                else:
                    kc, vc = caches
                    kc = dus(kc, k3[None, :, :, None, :].astype(kc.dtype),
                             (l, 0, 0, offset, 0))
                    vc = dus(vc, v3[None, :, :, None, :].astype(vc.dtype),
                             (l, 0, 0, offset, 0))
                    ctx = decode_attention_fp_stacked(
                        qh, kc, vc, offset, l, scale=1.0 / np.sqrt(D))
                    caches = (kc, vc)
                ctx2 = ctx.transpose(0, 2, 1, 3).reshape(B, E)
                return (serving_row_out_ffn(cfg, w, ctx2, x, l),
                        caches), None

            (x, caches), _ = jax.lax.scan(
                layer, (x, caches), jnp.arange(Lyr, dtype=jnp.int32))
            logits = serving_row_head(cfg, w, x)
            nxt = jax.lax.cond(
                temperature > 0,
                lambda: jax.random.categorical(
                    r, logits.astype(jnp.float32)
                    / jnp.maximum(temperature, 1e-6), axis=-1),
                lambda: jnp.argmax(logits, axis=-1))
            return (caches, nxt, offset + 1), tok

        (caches, last, _), toks = jax.lax.scan(
            tick, (caches, first_tok, start), rngs, length=steps)
        return (jnp.concatenate([toks.transpose(1, 0), last[:, None]],
                                axis=1), caches)

    _STEP_CACHE[key] = fast_scan
    return fast_scan


def generate(cfg: GPT2Config, params, input_ids, max_new_tokens=20,
             temperature: float = 0.0, rng=None, max_out_tokens: int = 0,
             quantize_bits: int = 0, quantize_groups: int = 1,
             kv_cache_bits: int = 0, scan_decode: bool = True,
             mesh=None):
    """KV-cache generation. ``temperature == 0`` → greedy. Returns
    [B, S + max_new_tokens] token ids.

    Prompt processing fills the cache in one pass. With ``scan_decode``
    (default) the whole decode loop is one compiled ``lax.scan`` program —
    a single host dispatch for all new tokens, so per-token host
    dispatch stays off the decode path. ``scan_decode=False`` keeps the
    one-jitted-step-per-token loop (compiled once per config; useful for
    streaming callers).
    ``quantize_bits=8`` serves int8-stored weights (params must come from
    `quantize_gpt2_inference_params`)."""
    input_ids = jnp.asarray(input_ids)
    B, S = input_ids.shape
    total = S + max_new_tokens
    # every emitted position needs a real learned position embedding —
    # beyond n_positions the wpe gather would clamp and silently corrupt
    assert total <= cfg.n_positions, (
        f"prompt {S} + max_new_tokens {max_new_tokens} exceeds "
        f"n_positions {cfg.n_positions}")
    max_out = max_out_tokens or cfg.n_positions
    assert total <= max_out, (total, max_out)
    # mp_size serving (reference module_inject mp_size): layer weights
    # shard over the mesh model axis; GSPMD propagates the head sharding
    # onto the KV caches and inserts the row-parallel psums
    mp_size = 1
    if mesh is not None:
        from deepspeed_tpu.parallel.mesh import MODEL_AXIS
        mp_size = int(mesh.shape.get(MODEL_AXIS, 1))
        if mp_size > 1:
            assert cfg.n_head % mp_size == 0, (
                f"n_head {cfg.n_head} must divide over the model axis "
                f"({mp_size} shards)")
    prompt_pass, decode_step, decode_scan = _compiled_steps(
        cfg, max_out, quantize_bits, quantize_groups, kv_cache_bits,
        mp_size)
    iparams = params if _is_converted(params) \
        else convert_gpt2_params(params, cfg)
    if mp_size > 1:
        iparams = shard_inference_params(iparams, mesh)

    def pick(logits, r):
        if temperature and temperature > 0:
            return jax.random.categorical(r, logits / temperature, axis=-1)
        return jnp.argmax(logits, axis=-1)

    rng = rng if rng is not None else jax.random.PRNGKey(0)
    logits, cache = prompt_pass(iparams, input_ids)

    if scan_decode and max_new_tokens > 1:
        rng, sub = jax.random.split(rng)
        first = pick(logits, sub)
        if _supports_fast_decode(cfg, B, quantize_bits, quantize_groups,
                                 kv_cache_bits, mp_size):
            fast = _fast_decode_scan_fn(cfg, max_out,
                                        weights_q8=quantize_bits == 8,
                                        cache_q8=kv_cache_bits == 8)
            blk = iparams["h"]["blk"]
            cblk = cache["h"]["blk"]
            if kv_cache_bits == 8:
                caches = (cblk["cached_key_q8"], cblk["key_scale"],
                          cblk["cached_value_q8"], cblk["value_scale"])
            else:
                caches = (cblk["cached_key"], cblk["cached_value"])
            new, _ = fast(
                {"wte": iparams["wte"], "wpe": iparams["wpe"],
                 "ln_f": iparams["ln_f"]}, blk, caches,
                first, max_new_tokens - 1, jnp.asarray(S, jnp.int32),
                jax.random.split(rng, max_new_tokens - 1),
                jnp.float32(temperature or 0.0))
            return jnp.concatenate([input_ids, new], axis=1)
        new, _ = decode_scan(iparams, cache, first,
                             jnp.asarray(S, jnp.int32),
                             jax.random.split(rng, max_new_tokens - 1),
                             max_new_tokens - 1,
                             jnp.float32(temperature or 0.0))
        return jnp.concatenate([input_ids, new], axis=1)

    toks = [input_ids]
    for i in range(max_new_tokens):
        rng, sub = jax.random.split(rng)
        nxt = pick(logits, sub)
        toks.append(nxt[:, None])
        if i + 1 < max_new_tokens:
            # offset as a device scalar so the step compiles exactly once
            logits, cache = decode_step(iparams, cache, nxt,
                                        jnp.asarray(S + i, jnp.int32))
    return jnp.concatenate(toks, axis=1)
