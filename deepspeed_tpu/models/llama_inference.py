"""LLaMA fused serving — the GPT-2 fast-decode stack for the
RMSNorm + split-qkv/GQA + SwiGLU family.

Reference role: the reference applies its fused inference kernels +
int8 quantization across client architectures via module injection
(deepspeed/module_inject/replace_module.py:8, module_quantize.py). Here
the family-specific pieces are STATIC FLAGS on the same stacked Pallas
kernels GPT-2 serves through (ops/pallas/decode.py): ``norm='rms'``
turns the fused norm into RMSNorm and drops every bias operand,
``act='swiglu'`` streams the gate and up tiles together, and the
cached-attention kernel takes R = H/Hkv grouped query rows per KV head
so the GQA cache is read once per token at its reduced head count.

Layout: serving params are PACKED stacks —

    qkv_w [L, E, (H + 2*Hkv) * D]   (q | k | v column blocks)
    o_w   [L, H*D, E]   gate_w/up_w [L, E, F]   down_w [L, F, E]
    norm1/norm2 [L, E]; embed [V, E]; head [V, E]; norm_scale [E]

optionally int8 (kernel_q + per-tensor-per-layer scale). The prompt
pass runs on the SAME packed (de-quantized on the fly) stacks — the
original flax tree never has to coexist with the packed one in HBM,
which is what lets a 7B model serve quantized on a 16 GB chip.
"""

import functools
from typing import Optional

import numpy as np
import jax
import jax.numpy as jnp

from deepspeed_tpu.models.llama import (LlamaConfig, rope_angles,
                                        apply_rope)


_STEP_CACHE = {}


# ------------------------------------------------------------- packing

def convert_llama_serving_params(params, cfg: LlamaConfig):
    """LlamaForCausalLM (scan-stacked) params → packed serving tree."""
    assert cfg.scan_layers, "serving packs the scan-stacked layout"
    blk = params["layers"]["blk"]
    qkv = jnp.concatenate([blk["attn"]["q_proj"]["kernel"],
                           blk["attn"]["k_proj"]["kernel"],
                           blk["attn"]["v_proj"]["kernel"]], axis=-1)
    return {
        "embed": params["embed_tokens"],
        "head": params["lm_head"],
        "norm_scale": params["norm"]["scale"],
        "blk": {
            "qkv_w": {"kernel": qkv},
            "o_w": {"kernel": blk["attn"]["o_proj"]["kernel"]},
            "gate_w": {"kernel": blk["mlp"]["gate_proj"]["kernel"]},
            "up_w": {"kernel": blk["mlp"]["up_proj"]["kernel"]},
            "down_w": {"kernel": blk["mlp"]["down_proj"]["kernel"]},
            "norm1": blk["input_norm"]["scale"],
            "norm2": blk["post_attn_norm"]["scale"],
        },
    }


def quantize_llama_serving_params(sparams):
    """Packed serving tree → int8 storage (kernel_q int8 + kernel_scale
    [L] fp32 per-tensor-per-layer symmetric scales). Embeddings, head
    and norms stay full precision (matching the GPT-2 int8 recipe)."""
    out = {k: v for k, v in sparams.items() if k != "blk"}
    blk = {}
    for name, sub in sparams["blk"].items():
        if not (isinstance(sub, dict) and "kernel" in sub):
            blk[name] = sub
            continue
        w = jnp.asarray(sub["kernel"])
        L = w.shape[0]
        flat = w.reshape(L, -1).astype(jnp.float32)
        amax = jnp.max(jnp.abs(flat), axis=1, keepdims=True)
        scale = jnp.maximum(amax / 127.0, 1e-12)
        q = jnp.clip(jnp.round(flat / scale), -127, 127)
        blk[name] = {"kernel_q": q.astype(jnp.int8).reshape(w.shape),
                     "kernel_scale": scale.reshape(L)}
    out["blk"] = blk
    return out


def random_int8_serving_params(cfg: LlamaConfig, seed=0):
    """Random int8 packed serving tree — a harness reads
    exactly the bytes a converted checkpoint would without
    materializing the bf16 model first (13.5 GB at 7B)."""
    rs = np.random.RandomState(seed)
    E, H, Hkv, D = (cfg.hidden_size, cfg.n_heads, cfg.kv_heads,
                    cfg.head_dim)
    F, L, V = cfg.intermediate_size, cfg.n_layers, cfg.vocab_size

    def q8(shape):
        return {"kernel_q": jnp.asarray(
            rs.randint(-80, 80, size=shape), jnp.int8),
            "kernel_scale": jnp.full((shape[0],), 2e-3, jnp.float32)}

    return {
        "embed": jnp.asarray(rs.randn(V, E) * 0.01, jnp.bfloat16),
        "head": jnp.asarray(rs.randn(V, E) * 0.01, jnp.bfloat16),
        "norm_scale": jnp.ones((E,), jnp.float32),
        "blk": {
            "qkv_w": q8((L, E, (H + 2 * Hkv) * D)),
            "o_w": q8((L, H * D, E)),
            "gate_w": q8((L, E, F)),
            "up_w": q8((L, E, F)),
            "down_w": q8((L, F, E)),
            "norm1": jnp.ones((L, E), jnp.float32),
            "norm2": jnp.ones((L, E), jnp.float32),
        },
    }


def _weights(blk, name, Lyr):
    """(stack, scale_vec) for either storage."""
    sub = blk[name]
    if "kernel_q" in sub:
        return sub["kernel_q"], sub["kernel_scale"].reshape(Lyr)
    return sub["kernel"], jnp.ones((Lyr,), jnp.float32)


def _rms_x(x, w, eps):
    from deepspeed_tpu.ops.pallas.decode import _rms
    return _rms(x, w, eps).astype(x.dtype)


def _rope_rows(x, pos, theta):
    """RoPE on [N, Hx, D] rows at PER-ROW positions ``pos`` [N]
    (continuous batching decodes every slot at its own offset)."""
    N, H, D = x.shape
    inv = 1.0 / (theta ** (jnp.arange(0, D, 2, dtype=jnp.float32) / D))
    ang = pos.astype(jnp.float32)[:, None] * inv[None]    # [N, D//2]
    cos = jnp.cos(ang)[:, None].astype(x.dtype)           # [N, 1, D//2]
    sin = jnp.sin(ang)[:, None].astype(x.dtype)
    half = D // 2
    x1, x2 = x[..., :half], x[..., half:]
    return jnp.concatenate([x1 * cos - x2 * sin,
                            x2 * cos + x1 * sin], axis=-1)


# ------------------------------------------------ paged-serving layer math
#
# LLaMA as serving/adapters.PagedServingAdapter sees it (docs/serving.md
# "Adding a family"), over the PACKED serving tree: geometry, the params
# a program takes, and the embedding, qkv half, out+FFN half and head —
# once for decode ROWS ([N, E], the stacked fused kernels) and once for
# a whole PROMPT ([B, S, E], de-quantised XLA matmuls). GQA: k and v
# come back at Hkv heads, q at H. None of them sees the KV pool, the
# page table or the sampler.

def serving_geometry(cfg: LlamaConfig):
    return dict(n_layers=cfg.n_layers, kv_heads=cfg.kv_heads,
                head_dim=cfg.head_dim, dtype=cfg.dtype,
                max_prompt_len=cfg.max_seq_len, vocab_size=cfg.vocab_size)


def serving_params(cfg: LlamaConfig, sparams, quantize_bits: int = 0):
    """(p, blk) a program takes from the packed tree; ``quantize_bits=8``
    quantises a full-precision tree to the int8 storage here, at build."""
    if quantize_bits == 8 and "kernel_q" not in sparams["blk"]["qkv_w"]:
        sparams = quantize_llama_serving_params(sparams)
    return ({k: v for k, v in sparams.items() if k != "blk"},
            sparams["blk"])


_PROJECTIONS = ("qkv_w", "o_w", "gate_w", "up_w", "down_w")


def serving_row_weights(cfg: LlamaConfig, p, blk):
    """What a decode program hoists out of its scans: the embeddings in
    compute dtype, each projection's (stack, scale) and the norms as
    [Lyr, 1, E] for the kernels' per-layer blocks."""
    Lyr, E = cfg.n_layers, cfg.hidden_size
    w = {name: _weights(blk, name, Lyr) for name in _PROJECTIONS}
    w.update(embed=p["embed"].astype(cfg.dtype),
             head=p["head"].astype(cfg.dtype),
             norm_scale=p["norm_scale"],
             norm1=blk["norm1"].reshape(Lyr, 1, E),
             norm2=blk["norm2"].reshape(Lyr, 1, E))
    return w


def serving_row_embed(cfg: LlamaConfig, w, toks, pos):
    return w["embed"][toks]


def serving_row_qkv(cfg: LlamaConfig, w, x, l, pos):
    """x [N, E] -> q [N, H, D], k, v [N, Hkv, D] at layer ``l``, q and
    k rotated to the rows' positions ``pos`` [N]."""
    from deepspeed_tpu.ops.pallas.decode import ln_qkv_int8_stacked
    H, Hkv, D = cfg.n_heads, cfg.kv_heads, cfg.head_dim
    qkv = ln_qkv_int8_stacked(x, w["norm1"], None, *w["qkv_w"], None, l,
                              eps=cfg.rms_eps, norm="rms")
    q = qkv[:, :H * D].reshape(-1, H, D)
    k = qkv[:, H * D:(H + Hkv) * D].reshape(-1, Hkv, D)
    v = qkv[:, (H + Hkv) * D:].reshape(-1, Hkv, D)
    return (_rope_rows(q, pos, cfg.rope_theta),
            _rope_rows(k, pos, cfg.rope_theta), v)


def serving_row_out_ffn(cfg: LlamaConfig, w, ctx, x, l):
    from deepspeed_tpu.ops.pallas.decode import (out_ffn_int8_stacked,
                                                 matvec_int8_stacked)
    E = cfg.hidden_size
    (Wo, so), (Wg, sg), (Wu, su), (Wd, sd) = (
        w[n] for n in ("o_w", "gate_w", "up_w", "down_w"))
    # whole-[E,E] o_proj blocks blow scoped VMEM past E~2048; split it
    # onto the tiled stacked matvec there
    if E * E * Wo.dtype.itemsize <= (6 << 20):
        return out_ffn_int8_stacked(
            ctx, x, Wo, so, None, w["norm2"], None, Wg, sg, None, Wd, sd,
            None, l, act="swiglu", eps=cfg.rms_eps, norm="rms",
            w1b_stack=Wu, s1b=su)
    x1 = x + matvec_int8_stacked(ctx, Wo, so, l)
    return out_ffn_int8_stacked(
        None, x1, None, None, None, w["norm2"], None, Wg, sg, None, Wd,
        sd, None, l, act="swiglu", eps=cfg.rms_eps, norm="rms",
        w1b_stack=Wu, s1b=su, fuse_proj=False)


def serving_row_head(cfg: LlamaConfig, w, x):
    return jnp.einsum("be,ve->bv",
                      _rms_x(x, w["norm_scale"], cfg.rms_eps), w["head"])


def serving_prompt_weights(cfg: LlamaConfig, p, blk, positions):
    """What a prompt pass hoists out of its layer scan; ``positions``
    [S] are the rows' absolute positions (the RoPE tables)."""
    w = {name: _weights(blk, name, cfg.n_layers) for name in _PROJECTIONS}
    w.update(embed=p["embed"], head=p["head"], norm_scale=p["norm_scale"],
             norm1=blk["norm1"], norm2=blk["norm2"],
             rope=rope_angles(positions, cfg.head_dim, cfg.rope_theta))
    return w


def _prompt_dense(cfg, stack_scale, l, u):
    """u @ layer ``l`` of a (stack, scale) pair, de-quantised on the
    fly."""
    stack, scale = stack_scale
    if stack.dtype == jnp.int8:
        return u @ (stack[l].astype(jnp.float32)
                    * scale[l]).astype(cfg.dtype)
    return u @ stack[l].astype(cfg.dtype)


def serving_prompt_embed(cfg: LlamaConfig, w, ids):
    return w["embed"][ids].astype(cfg.dtype)               # [B, S, E]


def serving_prompt_qkv(cfg: LlamaConfig, w, x, l):
    """x [B, S, E] -> q [B, H, S, D], k, v [B, Hkv, S, D] at layer
    ``l``, q and k rotated."""
    H, Hkv, D = cfg.n_heads, cfg.kv_heads, cfg.head_dim
    B, S = x.shape[:2]
    u = _rms_x(x, w["norm1"][l], cfg.rms_eps)
    qkv = _prompt_dense(cfg, w["qkv_w"], l, u)
    q = qkv[..., :H * D].reshape(B, S, H, D).transpose(0, 2, 1, 3)
    k = qkv[..., H * D:(H + Hkv) * D] \
        .reshape(B, S, Hkv, D).transpose(0, 2, 1, 3)
    v = qkv[..., (H + Hkv) * D:] \
        .reshape(B, S, Hkv, D).transpose(0, 2, 1, 3)
    return apply_rope(q, *w["rope"]), apply_rope(k, *w["rope"]), v


def serving_prompt_out_ffn(cfg: LlamaConfig, w, ctx, x, l):
    x = x + _prompt_dense(cfg, w["o_w"], l, ctx)
    u = _rms_x(x, w["norm2"][l], cfg.rms_eps)
    h = jax.nn.silu(_prompt_dense(cfg, w["gate_w"], l, u)) \
        * _prompt_dense(cfg, w["up_w"], l, u)
    return x + _prompt_dense(cfg, w["down_w"], l, h)


def serving_prompt_head(cfg: LlamaConfig, w, xl):
    """Logits of ONE row xl [E] (the prompt's last position)."""
    return _rms_x(xl, w["norm_scale"], cfg.rms_eps) \
        @ w["head"].astype(cfg.dtype).T


# ------------------------------------------------------------- fast loop

def _supports_fast_decode(cfg: LlamaConfig, B, quantize_bits,
                          kv_cache_bits):
    """D < 128 is fine as long as every PACKED projection width is
    lane-aligned — the kernels tile the packed columns, not heads."""
    E, H, Hkv, D = (cfg.hidden_size, cfg.n_heads, cfg.kv_heads,
                    cfg.head_dim)
    return (quantize_bits in (0, 8) and kv_cache_bits in (0, 8)
            and B <= 64 and cfg.scan_layers and E % 128 == 0
            and ((H + 2 * Hkv) * D) % 128 == 0 and (H * D) % 128 == 0
            and cfg.intermediate_size % 128 == 0)


def _fast_fns(cfg: LlamaConfig, max_out: int, weights_q8: bool,
              cache_q8: bool):
    """(prompt, decode) jitted once per (config, cache length, storage).

    The prompt pass runs on the packed stacks (dequantizing per layer in
    XLA — a one-time ~bandwidth cost) and fills the caches directly in
    their serving storage; the decode loop is the stacked-kernel manual
    scan, one compiled program for all new tokens."""
    key = (cfg, max_out, weights_q8, cache_q8)
    if key in _STEP_CACHE:
        return _STEP_CACHE[key]
    from deepspeed_tpu.ops.pallas.decode import (
        kv_quant_int8, decode_attention_int8_stacked,
        decode_attention_fp_stacked)
    H, Hkv, D = cfg.n_heads, cfg.kv_heads, cfg.head_dim
    Lyr = cfg.n_layers
    rep = H // Hkv
    eps = cfg.rms_eps
    L_cache = max_out

    @functools.partial(jax.jit, donate_argnums=())
    def prompt(p, ids):
        from deepspeed_tpu.ops.attention import dot_product_attention
        blk = p["blk"]
        B, S = ids.shape
        # pad to a flash-tileable length: an arbitrary prompt length
        # (e.g. 1968) divides none of the flash block sizes, and the
        # reference fallback materializes [B, H, S, S] fp32 scores —
        # 3.8 GB at 7B/b8 (the r5 OOM). Causal masking makes the tail
        # padding inert for every real position.
        Sp = -(-S // 128) * 128
        w = serving_prompt_weights(cfg, p, blk, jnp.arange(Sp))
        x = serving_prompt_embed(cfg, w, ids)
        if Sp != S:
            x = jnp.pad(x, [(0, 0), (0, Sp - S), (0, 0)])

        def quant_rows(t):
            # per-(b, head, pos) symmetric int8 — INSIDE the layer scan
            # so the fp32 transient is one layer's K or V (~MBs), not
            # the whole stacked cache (4.3 GB at 7B/2k — the r5 OOM)
            tf = t.astype(jnp.float32)
            sc = jnp.maximum(jnp.max(jnp.abs(tf), axis=-1) / 127.0,
                             1e-12)
            codes = jnp.clip(jnp.round(tf / sc[..., None]),
                             -127, 127).astype(jnp.int8)
            return codes, sc

        def layer(x, l):
            q, k, v = serving_prompt_qkv(cfg, w, x, l)
            ctx = dot_product_attention(q, k, v, causal=True)
            ctx = ctx.transpose(0, 2, 1, 3).reshape(B, Sp, H * D)
            x = serving_prompt_out_ffn(cfg, w, ctx, x, l)
            if cache_q8:
                kcod, ksc = quant_rows(k)
                vcod, vsc = quant_rows(v)
                return x, (kcod, ksc, vcod, vsc)
            return x, (k.astype(cfg.dtype), v.astype(cfg.dtype))

        x, ys = jax.lax.scan(layer, x, jnp.arange(Lyr))

        def to_cache(t):
            # drop the pad tail, keep the first S real rows, pad to the
            # cache length (position axis is 3)
            t = t[:, :, :, :S]
            pad = [(0, 0)] * t.ndim
            pad[3] = (0, L_cache - S)
            return jnp.pad(t, pad)

        if cache_q8:
            kcod, ksc, vcod, vsc = ys       # scales [Lyr, B, Hkv, Sp]
            caches = (to_cache(kcod),
                      to_cache(ksc).reshape(Lyr, B, Hkv, 1, L_cache),
                      to_cache(vcod),
                      to_cache(vsc).reshape(Lyr, B, Hkv, 1, L_cache))
        else:
            ks, vs = ys
            caches = (to_cache(ks), to_cache(vs))
        logits = jnp.einsum(
            "be,ve->bv", _rms_x(x[:, S - 1], p["norm_scale"], eps),
            p["head"].astype(cfg.dtype))
        return logits, caches

    @functools.partial(jax.jit, static_argnums=(4,), donate_argnums=(2,))
    def fast_scan(p, blk, caches, first_tok, steps, start, rngs,
                  temperature):
        w = serving_row_weights(cfg, p, blk)
        B = first_tok.shape[0]

        def tick(carry, r):
            caches, tok, offset = carry
            x = serving_row_embed(cfg, w, tok, None)  # [B, E]
            x = jnp.where(offset >= L_cache,
                          jnp.float32(jnp.nan).astype(x.dtype), x)

            def layer(car, l):
                x, caches = car
                q3, k3, v3 = serving_row_qkv(
                    cfg, w, x, l, jnp.broadcast_to(offset, (B,)))
                qg = q3.reshape(B, Hkv, rep, D)
                dus = jax.lax.dynamic_update_slice
                if cache_q8:
                    kc, ks, vc, vs = caches
                    kq8, ksc, vq8, vsc = kv_quant_int8(k3, v3)
                    kc = dus(kc, kq8[None, :, :, None, :],
                             (l, 0, 0, offset, 0))
                    vc = dus(vc, vq8[None, :, :, None, :],
                             (l, 0, 0, offset, 0))
                    ks = dus(ks, ksc.reshape(1, B, Hkv, 1, 1),
                             (l, 0, 0, 0, offset))
                    vs = dus(vs, vsc.reshape(1, B, Hkv, 1, 1),
                             (l, 0, 0, 0, offset))
                    ctx = decode_attention_int8_stacked(
                        qg, kc, ks, vc, vs, offset, l,
                        scale=1.0 / np.sqrt(D))
                    caches = (kc, ks, vc, vs)
                else:
                    kc, vc = caches
                    kc = dus(kc, k3[None, :, :, None, :].astype(kc.dtype),
                             (l, 0, 0, offset, 0))
                    vc = dus(vc, v3[None, :, :, None, :].astype(vc.dtype),
                             (l, 0, 0, offset, 0))
                    ctx = decode_attention_fp_stacked(
                        qg, kc, vc, offset, l, scale=1.0 / np.sqrt(D))
                    caches = (kc, vc)
                ctx2 = ctx.reshape(B, H * D)
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

    _STEP_CACHE[key] = (prompt, fast_scan)
    return _STEP_CACHE[key]


def llama_fast_generate(cfg: LlamaConfig, sparams, input_ids,
                        max_new_tokens=20, temperature: float = 0.0,
                        rng=None, max_out_tokens: int = 0,
                        kv_cache_bits: int = 0):
    """Fused-kernel generation over PACKED serving params (see
    convert_llama_serving_params / quantize_llama_serving_params).
    Same contract as models.gpt2_inference.generate; the whole decode
    loop is one compiled program over the stacked kernels."""
    input_ids = jnp.asarray(input_ids)
    if max_new_tokens <= 0:
        return input_ids
    B, S = input_ids.shape
    total = S + max_new_tokens
    max_out = max_out_tokens or cfg.max_seq_len
    assert total <= max_out, (total, max_out)
    weights_q8 = "kernel_q" in sparams["blk"]["qkv_w"]
    if not _supports_fast_decode(cfg, B, 8 if weights_q8 else 0,
                                 kv_cache_bits):
        raise ValueError(
            f"config outside the fused fast-decode envelope (B={B}, "
            f"E={cfg.hidden_size}, packed qkv width "
            f"{(cfg.n_heads + 2 * cfg.kv_heads) * cfg.head_dim}, "
            f"F={cfg.intermediate_size}, scan_layers={cfg.scan_layers}) "
            "— see _supports_fast_decode; serve via models.llama."
            "llama_generate (unpacked flax path) instead")
    prompt, fast_scan = _fast_fns(cfg, max_out, weights_q8,
                                  kv_cache_bits == 8)
    rng = rng if rng is not None else jax.random.PRNGKey(0)
    logits, caches = prompt(sparams, input_ids)
    rng, sub = jax.random.split(rng)
    if temperature and temperature > 0:
        first = jax.random.categorical(
            sub, logits.astype(jnp.float32) / temperature, axis=-1)
    else:
        first = jnp.argmax(logits, axis=-1)
    if max_new_tokens <= 1:
        return jnp.concatenate([input_ids, first[:, None]], axis=1)
    new, _ = fast_scan(
        {k: v for k, v in sparams.items() if k != "blk"},
        sparams["blk"], caches, first, max_new_tokens - 1,
        jnp.asarray(S, jnp.int32),
        jax.random.split(rng, max_new_tokens - 1),
        jnp.float32(temperature or 0.0))
    return jnp.concatenate([input_ids, new], axis=1)
