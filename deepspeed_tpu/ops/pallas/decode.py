"""Fused decode (S=1) kernels for int8- and bf16-weight serving.

The scan-decode step at GPT-2-large b1/ctx2048 spends ~1.6 ms/token on
weight+cache reads but ~5.2 ms/token wall — the rest is per-op fixed cost
across ~30 small XLA ops per layer (docs/perf_tuning.md r4 ablation).
These kernels collapse the big ones:

- ``matvec_int8``: y = act(x @ dequant(Wq)·s + b) — one kernel per
  projection instead of dequant+dot+bias(+act) chains. The int8 codes are
  cast to the compute dtype INSIDE the kernel (VMEM), so HBM traffic is
  the 1-byte codes — the XLA path materializes a bf16 weight copy for
  some shapes, which doubles effective weight read.
- ``decode_attention_int8``: one (B,H)-grid kernel for the S=1 cached-
  attention read: scores over the int8 K cache, masked online softmax,
  context over the int8 V cache — replaces the dequant/dot/mask/softmax/
  dot chain (~10 ops).

Reference role: csrc/transformer/inference/csrc/pt_binding.cpp ships
fused decode GEMM+softmax CUDA kernels for exactly this regime.

All kernels are bandwidth-bound at decode shapes; grids are sized so each
program's working set fits VMEM with double-buffered DMA.

The weight-consuming kernels are dtype-agnostic: the in-kernel
``astype(compute)`` that dequantizes int8 codes is an identity cast for
bf16 stacks, and the per-tensor scale multiply is harmless at 1.0 — so
the SAME kernels serve plain bf16 weights (the reference's fp16-first
inference kernels, csrc/transformer/inference/csrc/pt_binding.cpp) by
passing the raw kernel stacks with scale=1. Only the cached-attention
kernel needs a real variant (``decode_attention_fp_stacked``): its int8
form reads per-(b,h,pos) scale ARRAYS, which have no fp counterpart.
Block budgets are byte-based, so bf16 tiles automatically halve their
column counts to stay inside VMEM.
"""

import functools

import numpy as np
import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu


def _interpret_default():
    from deepspeed_tpu.utils.platform import is_tpu_backend
    return not is_tpu_backend()


def _pick_block(n, budget_cols):
    """Largest lane-aligned (multiple-of-128) divisor of ``n`` whose
    column count stays within the VMEM tile budget; falls back to ``n``
    itself for small/irregular shapes (one whole-array block)."""
    cap = min(n, max(128, budget_cols))
    for cand in range(cap - cap % 128, 0, -128):
        if n % cand == 0:
            return cand
    return n


# ------------------------------------------------------------ int8 matvec

def _matvec_kernel(x_ref, w_ref, s_ref, b_ref, o_ref, *, act, out_dtype):
    x = x_ref[...]                              # [B, E] compute dtype
    w = w_ref[...].astype(x.dtype)              # [E, bn] int8 -> compute
    y = jax.lax.dot(x, w, preferred_element_type=jnp.float32)
    y = y * s_ref[0, 0] + b_ref[...].astype(jnp.float32)
    if act == "gelu_tanh":
        y = jax.nn.gelu(y, approximate=True)
    elif act == "gelu":
        y = jax.nn.gelu(y, approximate=False)
    o_ref[...] = y.astype(out_dtype)


def matvec_int8(x, wq, scale, bias, act=None, block_n=None, interpret=None):
    """x [B, E] @ int8 Wq [E, N] · scale (+ bias, + act) → [B, N].

    ``scale`` is the per-tensor (quantize_groups=1) symmetric scale; the
    kernel applies it to the fp32 accumulator, so dequantized weights
    never exist outside VMEM."""
    if interpret is None:
        interpret = _interpret_default()
    B, E = x.shape
    E2, N = wq.shape
    assert E == E2, (x.shape, wq.shape)
    if block_n is None:
        block_n = _pick_block(
            N, budget_cols=(1 << 21) // max(E * wq.dtype.itemsize, 1))
    assert N % block_n == 0, (N, block_n)
    scale = jnp.asarray(scale, jnp.float32).reshape(1, 1)
    bias2 = jnp.asarray(bias).reshape(1, N)     # 2-D: Mosaic tiles 1-D
    out = pl.pallas_call(                       # operands at 1024
        functools.partial(_matvec_kernel, act=act, out_dtype=x.dtype),
        grid=(N // block_n,),
        in_specs=[
            pl.BlockSpec((B, E), lambda j: (0, 0)),
            pl.BlockSpec((E, block_n), lambda j: (0, j)),
            pl.BlockSpec((1, 1), lambda j: (0, 0)),
            pl.BlockSpec((1, block_n), lambda j: (0, j)),
        ],
        out_specs=pl.BlockSpec((B, block_n), lambda j: (0, j)),
        out_shape=jax.ShapeDtypeStruct((B, N), x.dtype),
        interpret=interpret,
    )(x, wq, scale, bias2)
    return out


# ------------------------------------------- fused int8-cache decode attn

def _decode_attn_kernel(pos_ref, q_ref, k_ref, ks_ref, v_ref, vs_ref,
                        o_ref, m_ref, l_ref, acc_ref, *, scale, block_l,
                        seq_len):
    """grid=(B, L/block_l): ALL heads of one batch element per program —
    a per-(b,h) grid pays ~4 us of program overhead x H x layers, which
    measured 3.0 of 4.7 ms/token at GPT-2-large (H=20, 36 layers). Head-
    batched MXU dot_generals give [H, 1, bl] scores LANE-major, matching
    the [B, H, 1, L] scale layout (lane-major scales — a trailing-1
    [B,H,L,1] layout pads every scale block to 128 lanes and made DMA the
    bottleneck). Softmax state is carried across L-blocks in scratch with
    online rescaling; blocks past ``pos`` skip compute."""
    lb = pl.program_id(1)
    nb = seq_len // block_l
    pos = pos_ref[0]

    @pl.when(lb == 0)
    def _init():
        m_ref[...] = jnp.full_like(m_ref[...], -1e30)
        l_ref[...] = jnp.zeros_like(l_ref[...])
        acc_ref[...] = jnp.zeros_like(acc_ref[...])

    base = lb * block_l

    @pl.when(base <= pos)
    def _block():
        q = q_ref[0]                                # [H, 1, D]
        k = k_ref[0].astype(q.dtype)                # [H, bl, D]
        s = jax.lax.dot_general(                    # [H, 1, bl]
            q, k, (((2,), (2,)), ((0,), (0,))),
            preferred_element_type=jnp.float32)
        s = s * ks_ref[0] * scale                   # ks [H, 1, bl]
        k_pos = base + jax.lax.broadcasted_iota(jnp.int32, s.shape, 2)
        s = jnp.where(k_pos <= pos, s, -1e30)
        m_acc = m_ref[...]                          # [H, 1, 1]
        m_new = jnp.maximum(m_acc, jnp.max(s, axis=2, keepdims=True))
        m_ref[...] = m_new
        alpha = jnp.exp(m_acc - m_new)              # [H, 1, 1]
        p = jnp.exp(s - m_new)                      # [H, 1, bl]
        l_ref[...] = l_ref[...] * alpha + jnp.sum(p, axis=2,
                                                  keepdims=True)
        pv = (p * vs_ref[0]).astype(q.dtype)        # [H, 1, bl]
        v = v_ref[0].astype(q.dtype)                # [H, bl, D]
        ctx = jax.lax.dot_general(                  # [H, 1, D]
            pv, v, (((2,), (1,)), ((0,), (0,))),
            preferred_element_type=jnp.float32)
        acc_ref[...] = acc_ref[...] * alpha + ctx

    @pl.when(lb == nb - 1)
    def _finish():
        l_safe = jnp.maximum(l_ref[...], 1e-30)     # [H, 1, 1]
        o_ref[0] = (acc_ref[...] / l_safe).astype(o_ref.dtype)


def decode_attention_int8(q, k_codes, k_scale, v_codes, v_scale, pos,
                          scale=None, block_l=None, interpret=None):
    """S=1 cached attention over the int8 head-major cache.

    q [B, H, 1, D]; k_codes/v_codes [B, H, L, D] int8;
    k_scale/v_scale [B, H, L] fp32; pos: scalar int32 — index of the
    newest valid cache row (queries attend to positions <= pos).
    Returns [B, H, 1, D] in q.dtype."""
    if interpret is None:
        interpret = _interpret_default()
    B, H, S, D = q.shape
    assert S == 1, "decode kernel is S=1 only"
    L = k_codes.shape[2]
    scale = float(scale) if scale is not None else 1.0 / float(np.sqrt(D))
    if block_l is None:
        block_l = min(L, 512)
        while L % block_l:
            block_l //= 2
    assert L % block_l == 0, (L, block_l)
    ks4 = k_scale.reshape(B, H, 1, L)
    vs4 = v_scale.reshape(B, H, 1, L)
    pos = jnp.asarray(pos, jnp.int32).reshape(1)
    grid_spec = pltpu.PrefetchScalarGridSpec(
        num_scalar_prefetch=1,
        grid=(B, L // block_l),
        in_specs=[
            pl.BlockSpec((1, H, 1, D), lambda b, lb, *_: (b, 0, 0, 0)),
            pl.BlockSpec((1, H, block_l, D),
                         lambda b, lb, *_: (b, 0, lb, 0)),
            pl.BlockSpec((1, H, 1, block_l),
                         lambda b, lb, *_: (b, 0, 0, lb)),
            pl.BlockSpec((1, H, block_l, D),
                         lambda b, lb, *_: (b, 0, lb, 0)),
            pl.BlockSpec((1, H, 1, block_l),
                         lambda b, lb, *_: (b, 0, 0, lb)),
        ],
        out_specs=pl.BlockSpec((1, H, 1, D),
                               lambda b, lb, *_: (b, 0, 0, 0)),
        scratch_shapes=[
            pltpu.VMEM((H, 1, 1), jnp.float32),
            pltpu.VMEM((H, 1, 1), jnp.float32),
            pltpu.VMEM((H, 1, D), jnp.float32),
        ],
    )
    out = pl.pallas_call(
        functools.partial(_decode_attn_kernel, scale=scale,
                          block_l=block_l, seq_len=L),
        grid_spec=grid_spec,
        out_shape=jax.ShapeDtypeStruct((B, H, 1, D), q.dtype),
        interpret=interpret,
    )(pos, q, k_codes, ks4, v_codes, vs4)
    return out


def _ln(x, w, b, eps):
    xf = x.astype(jnp.float32)
    mu = jnp.mean(xf, axis=-1, keepdims=True)
    var = jnp.mean((xf - mu) ** 2, axis=-1, keepdims=True)
    y = (xf - mu) * jax.lax.rsqrt(var + eps)
    return y * w.astype(jnp.float32) + b.astype(jnp.float32)


def _ln_qkv_kernel(x_ref, lnw_ref, lnb_ref, w_ref, s_ref, b_ref,
                   o_ref, u_ref, *, eps):
    """grid over column tiles of the packed qkv projection: j=0 computes
    LN once into scratch; every j projects one tile. No in-kernel
    reshapes (Mosaic cannot shape-cast across lanes)."""
    j = pl.program_id(0)
    dt = x_ref.dtype

    @pl.when(j == 0)
    def _ln_pass():
        u_ref[...] = _ln(x_ref[...], lnw_ref[...], lnb_ref[...],
                         eps).astype(dt)

    u = u_ref[...]                                  # [B, E]
    w = w_ref[...].astype(dt)                       # [E, bn]
    y = jax.lax.dot(u, w, preferred_element_type=jnp.float32)
    o_ref[...] = (y * s_ref[0, 0]
                  + b_ref[...].astype(jnp.float32)).astype(dt)


def ln_qkv_int8(x, ln_w, ln_b, wq, s, b, eps=1e-5, block_n=None,
                interpret=None):
    """Fused LayerNorm + int8 qkv projection: x [B, E] -> qkv [B, 3E]
    (one kernel instead of LN + dequant + matmul + bias chains)."""
    if interpret is None:
        interpret = _interpret_default()
    B, E = x.shape
    N = 3 * E
    assert wq.shape == (E, N)
    if block_n is None:
        block_n = _pick_block(
            N, budget_cols=(1 << 23) // max(E * wq.dtype.itemsize, 1))
    assert N % block_n == 0
    s = jnp.asarray(s, jnp.float32).reshape(1, 1)
    out = pl.pallas_call(
        functools.partial(_ln_qkv_kernel, eps=eps),
        grid=(N // block_n,),
        in_specs=[
            pl.BlockSpec((B, E), lambda j: (0, 0)),
            pl.BlockSpec((1, E), lambda j: (0, 0)),
            pl.BlockSpec((1, E), lambda j: (0, 0)),
            pl.BlockSpec((E, block_n), lambda j: (0, j)),
            pl.BlockSpec((1, 1), lambda j: (0, 0)),
            pl.BlockSpec((1, block_n), lambda j: (0, j)),
        ],
        out_specs=pl.BlockSpec((B, block_n), lambda j: (0, j)),
        out_shape=jax.ShapeDtypeStruct((B, N), x.dtype),
        scratch_shapes=[pltpu.VMEM((B, E), x.dtype)],
        interpret=interpret,
    )(x, ln_w.reshape(1, E), ln_b.reshape(1, E), wq, s,
      jnp.asarray(b).reshape(1, N))
    return out


def _kv_quant_kernel(k_ref, v_ref, kq_ref, ks_ref, vq_ref, vs_ref):
    """Per-head symmetric int8 quant of the new K/V rows ([B, H, D],
    head axis on sublanes — no reshape needed). The cache append itself
    stays an XLA dynamic_update_slice: Mosaic cannot DMA a single row of
    a sublane-tiled cache axis (slices on tiled dims must be 8-aligned),
    and XLA updates the donated cache in place anyway."""
    def quant(t_ref, q_ref, s_ref):
        t = t_ref[...].astype(jnp.float32)          # [B, H, D]
        amax = jnp.max(jnp.abs(t), axis=-1, keepdims=True)
        sc = jnp.maximum(amax / 127.0, 1e-12)       # [B, H, 1]
        q_ref[...] = jnp.clip(jnp.round(t / sc), -127,
                              127).astype(jnp.int8)
        s_ref[...] = sc.astype(jnp.float32)

    quant(k_ref, kq_ref, ks_ref)
    quant(v_ref, vq_ref, vs_ref)


def kv_quant_int8(k, v, interpret=None):
    """Quantize new K/V rows per head in one kernel. k/v: [B, H, D] ->
    (k_codes int8 [B,H,D], k_scale fp32 [B,H,1], v_codes, v_scale)."""
    if interpret is None:
        interpret = _interpret_default()
    B, H, D = k.shape
    spec = pl.BlockSpec((B, H, D), lambda: (0, 0, 0))
    sspec = pl.BlockSpec((B, H, 1), lambda: (0, 0, 0))
    kq, ks, vq, vs = pl.pallas_call(
        _kv_quant_kernel,
        in_specs=[spec, spec],
        out_specs=[spec, sspec, spec, sspec],
        out_shape=[
            jax.ShapeDtypeStruct((B, H, D), jnp.int8),
            jax.ShapeDtypeStruct((B, H, 1), jnp.float32),
            jax.ShapeDtypeStruct((B, H, D), jnp.int8),
            jax.ShapeDtypeStruct((B, H, 1), jnp.float32),
        ],
        interpret=interpret,
    )(k, v)
    return kq, ks, vq, vs


def _out_ffn_kernel(ctx_ref, x_ref, wp_ref, lnw_ref, lnb_ref, sc_ref,
                    bp_ref, w1_ref, b1_ref, w2_ref, b2_ref, o_ref,
                    x1_ref, u_ref, acc_ref, *, eps, act, n_tiles):
    """grid=(n_tiles,) over FFN columns: j=0 additionally runs the
    attention output projection + residual + LN; every j accumulates one
    FFN tile; the last j adds the second residual and writes out."""
    j = pl.program_id(0)
    dt = ctx_ref.dtype

    @pl.when(j == 0)
    def _proj():
        ctx = ctx_ref[...]
        wp = wp_ref[...].astype(dt)
        t = jax.lax.dot(ctx, wp, preferred_element_type=jnp.float32)
        t = t * sc_ref[0, 0] + bp_ref[...].astype(jnp.float32)
        x1 = x_ref[...].astype(jnp.float32) + t
        x1_ref[...] = x1.astype(dt)
        u_ref[...] = _ln(x1, lnw_ref[...], lnb_ref[...], eps).astype(dt)
        acc_ref[...] = jnp.zeros_like(acc_ref[...])

    u = u_ref[...]
    w1 = w1_ref[...].astype(dt)
    h = jax.lax.dot(u, w1, preferred_element_type=jnp.float32)
    h = h * sc_ref[0, 1] + b1_ref[...].astype(jnp.float32)
    if act == "gelu_tanh":
        h = jax.nn.gelu(h, approximate=True)
    else:
        h = jax.nn.gelu(h, approximate=False)
    w2 = w2_ref[...].astype(dt)
    acc_ref[...] += jax.lax.dot(h.astype(dt), w2,
                                preferred_element_type=jnp.float32)

    @pl.when(j == n_tiles - 1)
    def _finish():
        o_ref[...] = (x1_ref[...].astype(jnp.float32)
                      + acc_ref[...] * sc_ref[0, 2]
                      + b2_ref[...].astype(jnp.float32)).astype(o_ref.dtype)


def out_ffn_int8(ctx, x, wp, sp, bp, ln_w, ln_b, w1, s1, b1, w2, s2, b2,
                 act="gelu_tanh", eps=1e-5, block_f=None, interpret=None):
    """Fused decode output path: x + proj(ctx), then LN and the whole
    FFN with a second residual — one kernel instead of ~12 ops. All
    weights int8 with per-tensor scales."""
    if interpret is None:
        interpret = _interpret_default()
    B, E = ctx.shape
    Ew, F = w1.shape
    assert Ew == E and w2.shape == (F, E) and wp.shape == (E, E)
    if block_f is None:
        block_f = _pick_block(
            F, budget_cols=(1 << 21) // max(E * w1.dtype.itemsize, 1))
    assert F % block_f == 0, (F, block_f)
    n_tiles = F // block_f
    scales = jnp.stack([jnp.asarray(v, jnp.float32).reshape(())
                        for v in (sp, s1, s2)]).reshape(1, 3)
    out = pl.pallas_call(
        functools.partial(_out_ffn_kernel, eps=eps, act=act,
                          n_tiles=n_tiles),
        grid=(n_tiles,),
        in_specs=[
            pl.BlockSpec((B, E), lambda j: (0, 0)),
            pl.BlockSpec((B, E), lambda j: (0, 0)),
            pl.BlockSpec((E, E), lambda j: (0, 0)),
            pl.BlockSpec((1, E), lambda j: (0, 0)),
            pl.BlockSpec((1, E), lambda j: (0, 0)),
            pl.BlockSpec((1, 3), lambda j: (0, 0)),
            pl.BlockSpec((1, E), lambda j: (0, 0)),
            pl.BlockSpec((E, block_f), lambda j: (0, j)),
            pl.BlockSpec((1, block_f), lambda j: (0, j)),
            pl.BlockSpec((block_f, E), lambda j: (j, 0)),
            pl.BlockSpec((1, E), lambda j: (0, 0)),
        ],
        out_specs=pl.BlockSpec((B, E), lambda j: (0, 0)),
        out_shape=jax.ShapeDtypeStruct((B, E), ctx.dtype),
        scratch_shapes=[
            pltpu.VMEM((B, E), ctx.dtype),
            pltpu.VMEM((B, E), ctx.dtype),
            pltpu.VMEM((B, E), jnp.float32),
        ],
        interpret=interpret,
    )(ctx, x, wp, ln_w.reshape(1, E), ln_b.reshape(1, E), scales,
      jnp.asarray(bp).reshape(1, E), w1, jnp.asarray(b1).reshape(1, F),
      w2, jnp.asarray(b2).reshape(1, E))
    return out


# ----------------------------- stacked-weight serving kernels (no slices)
#
# flax's nn.scan over layers SLICES every stacked array before the layer
# body sees it: per tick per layer that is ~24 us of weight-slice copies
# plus ~37 us of cache slice/unslice (device trace, b1/ctx2048 int8 —
# ~60% of the token). These variants take the FULL [L, ...] stacks and
# index the layer via scalar-prefetched block index maps, so the kernels
# DMA exactly the tiles they need straight from the stacked HBM arrays.
# The manual serving loop (models/gpt2_inference._fast_decode_scan) scans
# layer INDICES and keeps the caches whole, updating one row in place.
#
# EVERY per-layer parameter is stacked — LN scales/biases and projection
# biases ride [Lyr, ...] operands with layer-indexed block maps, and the
# per-tensor weight scales ride SMEM as scalar-prefetch vectors indexed
# at the layer id in-kernel. The r5 device trace showed the alternative
# (per-layer xs through lax.scan) costs ~15-20 us of slice/copy fixed
# overhead PER ARRAY PER LAYER on this target — ~2.5 ms/tick at 13 xs.

def _rms(x, w, eps):
    xf = x.astype(jnp.float32)
    n = xf * jax.lax.rsqrt(jnp.mean(xf * xf, axis=-1, keepdims=True)
                           + eps)
    return n * w.astype(jnp.float32)


def ln_qkv_int8_stacked(x, ln_w, ln_b, wq_stack, s, b, layer, eps=1e-5,
                        block_n=None, interpret=None, norm="layer"):
    """Fused norm + packed qkv projection over stacked weights: wq_stack
    [L, E, N] (int8 or bf16) indexed at ``layer`` by the block index map
    — no layer-slice copy. ln_w/ln_b [L, 1, E], b [L, 1, N] (the middle
    unit axis makes the per-layer block (1, 1, cols), which the TPU
    block-shape rules accept; serving loops pre-reshape ONCE outside the
    layer scan — 2-D [L, cols] is accepted here but reshapes per call, a
    layout copy); s [L] fp32 per-tensor scales (SMEM-prefetched, indexed
    in-kernel — pass ones for bf16 stacks).

    ``norm='rms'`` selects RMSNorm (LLaMA): ``ln_b`` is unused and the
    projection is bias-free — pass ``None`` for both. N may be any
    lane-aligned packed width (GPT-2 packs 3E; LLaMA packs
    (H + 2*Hkv) * head_dim at reduced-KV widths)."""
    if interpret is None:
        interpret = _interpret_default()
    B, E = x.shape
    Lyr, Ew, N = wq_stack.shape
    assert Ew == E
    use_bias = norm != "rms"
    ln_w = ln_w.reshape(Lyr, 1, E)
    if block_n is None:
        # 7 MiB per weight block: 2x (double-buffered DMA) + the x/u
        # scratch must stay under the 16 MiB scoped-VMEM limit — 8 MiB
        # blocks hit it exactly and overflow by the scratch bytes at
        # LLaMA-7B widths (E=4096, N=12288)
        block_n = _pick_block(
            N, budget_cols=(7 << 20) // max(E * wq_stack.dtype.itemsize, 1))
    assert N % block_n == 0
    s = jnp.asarray(s, jnp.float32).reshape(Lyr)
    layer = jnp.asarray(layer, jnp.int32).reshape(1)
    in_specs = [
        pl.BlockSpec((B, E), lambda j, l, s: (0, 0)),
        pl.BlockSpec((1, 1, E), lambda j, l, s: (l[0], 0, 0)),
    ]
    operands = [x, ln_w]
    if use_bias:
        in_specs.append(pl.BlockSpec((1, 1, E),
                                     lambda j, l, s: (l[0], 0, 0)))
        operands.append(ln_b.reshape(Lyr, 1, E))
    in_specs.append(pl.BlockSpec((1, E, block_n),
                                 lambda j, l, s: (l[0], 0, j)))
    operands.append(wq_stack)
    if use_bias:
        in_specs.append(pl.BlockSpec((1, 1, block_n),
                                     lambda j, l, s: (l[0], 0, j)))
        operands.append(jnp.asarray(b).reshape(Lyr, 1, N))
    grid_spec = pltpu.PrefetchScalarGridSpec(
        num_scalar_prefetch=2,
        grid=(N // block_n,),
        in_specs=in_specs,
        out_specs=pl.BlockSpec((B, block_n), lambda j, l, s: (0, j)),
        scratch_shapes=[pltpu.VMEM((B, E), x.dtype)],
    )
    out = pl.pallas_call(
        functools.partial(_ln_qkv_stacked_kernel, eps=eps, norm=norm),
        grid_spec=grid_spec,
        out_shape=jax.ShapeDtypeStruct((B, N), x.dtype),
        interpret=interpret,
    )(layer, s, *operands)
    return out


def _ln_qkv_stacked_kernel(l_ref, s_ref, x_ref, lnw_ref, *rest, eps,
                           norm):
    if norm == "rms":
        w_ref, o_ref, u_ref = rest
        lnb_ref = b_ref = None
    else:
        lnb_ref, w_ref, b_ref, o_ref, u_ref = rest
    j = pl.program_id(0)
    dt = x_ref.dtype

    @pl.when(j == 0)
    def _norm_pass():
        if norm == "rms":
            u_ref[...] = _rms(x_ref[...], lnw_ref[0], eps).astype(dt)
        else:
            u_ref[...] = _ln(x_ref[...], lnw_ref[0], lnb_ref[0],
                             eps).astype(dt)

    u = u_ref[...]
    w = w_ref[0].astype(dt)                        # [E, bn]
    y = jax.lax.dot(u, w, preferred_element_type=jnp.float32)
    y = y * s_ref[l_ref[0]]
    if b_ref is not None:
        y = y + b_ref[0].astype(jnp.float32)
    o_ref[...] = y.astype(dt)


def matvec_int8_stacked(x, w_stack, s, layer, block_n=None,
                        interpret=None):
    """x [B, E] @ stacked (int8 or bf16) w [L, E, N] · s[layer] → [B, N],
    bias-free, layer-indexed block maps — the large-E o_proj path where
    fusing the whole [E, E] matrix into the ffn kernel's first grid step
    would blow scoped VMEM (LLaMA-7B: 16.7 MB at E=4096)."""
    if interpret is None:
        interpret = _interpret_default()
    B, E = x.shape
    Lyr, Ew, N = w_stack.shape
    assert Ew == E
    if block_n is None:
        block_n = _pick_block(
            N, budget_cols=(7 << 20) // max(E * w_stack.dtype.itemsize, 1))
    assert N % block_n == 0
    s = jnp.asarray(s, jnp.float32).reshape(Lyr)
    layer = jnp.asarray(layer, jnp.int32).reshape(1)
    grid_spec = pltpu.PrefetchScalarGridSpec(
        num_scalar_prefetch=2,
        grid=(N // block_n,),
        in_specs=[
            pl.BlockSpec((B, E), lambda j, l, s: (0, 0)),
            pl.BlockSpec((1, E, block_n), lambda j, l, s: (l[0], 0, j)),
        ],
        out_specs=pl.BlockSpec((B, block_n), lambda j, l, s: (0, j)),
    )
    out = pl.pallas_call(
        _matvec_stacked_kernel,
        grid_spec=grid_spec,
        out_shape=jax.ShapeDtypeStruct((B, N), x.dtype),
        interpret=interpret,
    )(layer, s, x, w_stack)
    return out


def _matvec_stacked_kernel(l_ref, s_ref, x_ref, w_ref, o_ref):
    x = x_ref[...]
    w = w_ref[0].astype(x.dtype)
    y = jax.lax.dot(x, w, preferred_element_type=jnp.float32)
    o_ref[...] = (y * s_ref[l_ref[0]]).astype(x.dtype)


def decode_attention_int8_stacked(q, k_stack, k_scale, v_stack, v_scale,
                                  pos, layer, scale=None, block_l=None,
                                  interpret=None):
    """decode_attention_int8 over the stacked caches: k/v [L_layers, B,
    H, L, D] int8 + scales [L_layers, B, H, 1, L] fp32 indexed at
    ``layer`` by the block maps — the serving loop never slices a
    per-layer cache out (which copied the full multi-MB cache each layer
    each tick).

    Scales must arrive ALREADY lane-major 5-D: a 4-D [Lyr, B, H, L]
    array is accepted but reshaped here, and because the tiled layouts
    differ (T(8,128) vs T(1,128)) XLA materializes that reshape as a
    full-stack copy PER CALL — the r5 b32 trace measured it at 5.4
    ms/tick. Serving loops reshape once outside the layer scan.

    Grouped-query attention: q may carry R > 1 query rows per cache
    head ([B, Hkv, R, D] — the rep = H/Hkv query heads sharing each KV
    head fold into the row dim, consecutive-grouping as in the LLaMA
    layout). All R rows share the decode position, so the mask/softmax
    state just grows a row axis; the cache is read ONCE for all R."""
    if interpret is None:
        interpret = _interpret_default()
    B, H, R, D = q.shape
    Lyr = k_stack.shape[0]
    L = k_stack.shape[3]
    assert k_stack.shape[2] == H, (q.shape, k_stack.shape)
    scale = float(scale) if scale is not None else 1.0 / float(np.sqrt(D))
    if block_l is None:
        block_l = _pick_block_l(L, H, D, k_stack.dtype.itemsize)
    assert L % block_l == 0, (L, block_l)
    ks5 = k_scale.reshape(Lyr, B, H, 1, L)
    vs5 = v_scale.reshape(Lyr, B, H, 1, L)
    scalars = jnp.stack([jnp.asarray(layer, jnp.int32).reshape(()),
                         jnp.asarray(pos, jnp.int32).reshape(())])
    grid_spec = pltpu.PrefetchScalarGridSpec(
        num_scalar_prefetch=1,
        grid=(B, L // block_l),
        in_specs=[
            pl.BlockSpec((1, H, R, D), lambda b, lb, sc: (b, 0, 0, 0)),
            pl.BlockSpec((1, 1, H, block_l, D),
                         lambda b, lb, sc: (sc[0], b, 0, lb, 0)),
            pl.BlockSpec((1, 1, H, 1, block_l),
                         lambda b, lb, sc: (sc[0], b, 0, 0, lb)),
            pl.BlockSpec((1, 1, H, block_l, D),
                         lambda b, lb, sc: (sc[0], b, 0, lb, 0)),
            pl.BlockSpec((1, 1, H, 1, block_l),
                         lambda b, lb, sc: (sc[0], b, 0, 0, lb)),
        ],
        out_specs=pl.BlockSpec((1, H, R, D),
                               lambda b, lb, sc: (b, 0, 0, 0)),
        scratch_shapes=[
            pltpu.VMEM((H, R, 1), jnp.float32),
            pltpu.VMEM((H, R, 1), jnp.float32),
            pltpu.VMEM((H, R, D), jnp.float32),
        ],
    )
    out = pl.pallas_call(
        functools.partial(_decode_attn_stacked_kernel, scale=scale,
                          block_l=block_l, seq_len=L, quantized=True),
        grid_spec=grid_spec,
        out_shape=jax.ShapeDtypeStruct((B, H, R, D), q.dtype),
        interpret=interpret,
    )(scalars, q, k_stack, ks5, v_stack, vs5)
    return out


def _pick_block_l(L, H, D, itemsize, budget_bytes=1 << 21):
    """Largest cache-row block (≤512, dividing L) whose [H, block, D]
    tile stays inside the per-block VMEM byte budget — bf16 caches halve
    their row count vs int8 automatically."""
    blk = min(L, 512)
    while blk > 128 and H * blk * D * itemsize > budget_bytes:
        blk //= 2
    while L % blk:
        blk //= 2
    if blk < 8 and blk != L:
        # an odd-ish cache length halves down to a 1-4 row block, which
        # the TPU tiling refuses and which would crawl where it ran
        raise ValueError(
            f"cache length {L} has no row block of 8 or more dividing "
            f"it; size the static KV cache to a multiple of 8 (128 for "
            f"full tiles)")
    return blk


def _decode_attn_stacked_kernel(sc_ref, q_ref, *rest, scale, block_l,
                                seq_len, quantized):
    """One online-softmax body for BOTH cache storages: ``quantized``
    (static) selects whether per-(b,h,pos) scale refs exist in the
    operand list — the masking/rescale/finish logic stays single-copy."""
    if quantized:
        k_ref, ks_ref, v_ref, vs_ref, o_ref, m_ref, l_ref, acc_ref = rest
    else:
        k_ref, v_ref, o_ref, m_ref, l_ref, acc_ref = rest
    lb = pl.program_id(1)
    nb = seq_len // block_l
    pos = sc_ref[1]

    @pl.when(lb == 0)
    def _init():
        m_ref[...] = jnp.full_like(m_ref[...], -1e30)
        l_ref[...] = jnp.zeros_like(l_ref[...])
        acc_ref[...] = jnp.zeros_like(acc_ref[...])

    base = lb * block_l

    @pl.when(base <= pos)
    def _block():
        q = q_ref[0]                                # [H, R, D]
        k = k_ref[0, 0].astype(q.dtype)             # [H, bl, D]
        s = jax.lax.dot_general(
            q, k, (((2,), (2,)), ((0,), (0,))),
            preferred_element_type=jnp.float32)     # [H, R, bl]
        s = s * scale
        if quantized:
            s = s * ks_ref[0, 0]                    # ks [H, 1, bl]
        k_pos = base + jax.lax.broadcasted_iota(jnp.int32, s.shape, 2)
        s = jnp.where(k_pos <= pos, s, -1e30)
        m_acc = m_ref[...]
        m_new = jnp.maximum(m_acc, jnp.max(s, axis=2, keepdims=True))
        m_ref[...] = m_new
        alpha = jnp.exp(m_acc - m_new)
        p = jnp.exp(s - m_new)
        l_ref[...] = l_ref[...] * alpha + jnp.sum(p, axis=2,
                                                  keepdims=True)
        if quantized:
            p = p * vs_ref[0, 0]
        pv = p.astype(q.dtype)
        v = v_ref[0, 0].astype(q.dtype)
        ctx = jax.lax.dot_general(
            pv, v, (((2,), (1,)), ((0,), (0,))),
            preferred_element_type=jnp.float32)     # [H, R, D]
        acc_ref[...] = acc_ref[...] * alpha + ctx

    @pl.when(lb == nb - 1)
    def _finish():
        l_safe = jnp.maximum(l_ref[...], 1e-30)
        o_ref[0] = (acc_ref[...] / l_safe).astype(o_ref.dtype)


def out_ffn_int8_stacked(ctx, x, wp_stack, sp, bp, ln_w, ln_b, w1_stack,
                         s1, b1, w2_stack, s2, b2, layer, act="gelu_tanh",
                         eps=1e-5, block_f=None, interpret=None,
                         norm="layer", w1b_stack=None, s1b=None,
                         fuse_proj=True):
    """out_ffn_int8 over stacked weights: wp [L,E,E], w1 [L,E,F],
    w2 [L,F,E] (int8 or bf16) indexed at ``layer`` by the block maps.
    Per-layer params are stacked too: ln_w/ln_b/bp/b2 [L, 1, E],
    b1 [L, 1, F] (2-D accepted, reshaped — see ln_qkv_int8_stacked);
    sp/s1/s2 [L] fp32 scale vectors ride SMEM via scalar prefetch.

    ``norm='rms'`` (LLaMA) drops ln_b and ALL projection biases (pass
    None); ``act='swiglu'`` takes the gate stack as ``w1_stack`` and
    the up stack as ``w1b_stack`` (scales ``s1``/``s1b``) — each tile
    computes silu(u@Wg)*(u@Wu) @ W2-tile with both [E, block_f] tiles
    streamed together.

    ``fuse_proj=False`` drops the attention-output projection phase:
    ``x`` must arrive as the POST-residual x1 (caller runs o_proj via
    matvec_int8_stacked + an XLA add) and ``ctx``/``wp_stack``/``sp``/
    ``bp`` are ignored — the large-E escape where a whole [E, E] proj
    block would blow scoped VMEM."""
    if interpret is None:
        interpret = _interpret_default()
    B, E = x.shape
    Lyr, Ew, F = w1_stack.shape
    assert Ew == E and w2_stack.shape[1:] == (F, E)
    assert (not fuse_proj) or wp_stack.shape[1:] == (E, E)
    use_bias = norm != "rms"
    assert (act == "swiglu") == (w1b_stack is not None), \
        "act='swiglu' takes the up-projection stack via w1b_stack"
    ln_w = ln_w.reshape(Lyr, 1, E)
    if block_f is None:
        block_f = _pick_block(
            F, budget_cols=(1 << 21) // max(E * w1_stack.dtype.itemsize, 1))
    assert F % block_f == 0, (F, block_f)
    n_tiles = F // block_f
    if not fuse_proj:
        sp = jnp.ones((Lyr,), jnp.float32)   # keep the scale layout
    svecs = [sp, s1, s2] + ([s1b] if act == "swiglu" else [])
    scales = jnp.stack([jnp.asarray(v, jnp.float32).reshape(Lyr)
                        for v in svecs], axis=1)        # [L, 3 or 4]
    layer = jnp.asarray(layer, jnp.int32).reshape(1)
    spec_be = pl.BlockSpec((B, E), lambda j, l, s: (0, 0))
    spec_e = pl.BlockSpec((1, 1, E), lambda j, l, s: (l[0], 0, 0))
    spec_w1 = pl.BlockSpec((1, E, block_f),
                           lambda j, l, s: (l[0], 0, j))
    if fuse_proj:
        in_specs = [spec_be, spec_be,
                    pl.BlockSpec((1, E, E), lambda j, l, s: (l[0], 0, 0)),
                    spec_e]
        operands = [ctx, x, wp_stack, ln_w]
    else:
        in_specs = [spec_be, spec_e]
        operands = [x, ln_w]
    if use_bias:
        in_specs += [spec_e, spec_e]
        operands += [ln_b.reshape(Lyr, 1, E),
                     jnp.asarray(bp).reshape(Lyr, 1, E)]
    in_specs.append(spec_w1)
    operands.append(w1_stack)
    if act == "swiglu":
        in_specs.append(spec_w1)
        operands.append(w1b_stack)
    if use_bias:
        in_specs.append(pl.BlockSpec((1, 1, block_f),
                                     lambda j, l, s: (l[0], 0, j)))
        operands.append(jnp.asarray(b1).reshape(Lyr, 1, F))
    in_specs.append(pl.BlockSpec((1, block_f, E),
                                 lambda j, l, s: (l[0], j, 0)))
    operands.append(w2_stack)
    if use_bias:
        in_specs.append(spec_e)
        operands.append(jnp.asarray(b2).reshape(Lyr, 1, E))
    grid_spec = pltpu.PrefetchScalarGridSpec(
        num_scalar_prefetch=2,
        grid=(n_tiles,),
        in_specs=in_specs,
        out_specs=pl.BlockSpec((B, E), lambda j, l, s: (0, 0)),
        scratch_shapes=[
            pltpu.VMEM((B, E), x.dtype),
            pltpu.VMEM((B, E), x.dtype),
            pltpu.VMEM((B, E), jnp.float32),
        ],
    )
    out = pl.pallas_call(
        functools.partial(_out_ffn_stacked_kernel, eps=eps, act=act,
                          n_tiles=n_tiles, norm=norm,
                          fuse_proj=fuse_proj),
        grid_spec=grid_spec,
        out_shape=jax.ShapeDtypeStruct((B, E), x.dtype),
        interpret=interpret,
    )(layer, scales, *operands)
    return out


def decode_attention_fp_stacked(q, k_stack, v_stack, pos, layer,
                                scale=None, block_l=None, interpret=None):
    """decode_attention over stacked FULL-PRECISION (bf16/fp32) caches:
    k/v [L_layers, B, H, L, D] indexed at ``layer`` by the block maps.
    Same online-softmax structure as the int8 variant minus the per-
    (b, h, pos) scale arrays (which have no fp counterpart). Supports
    grouped-query rows R > 1 like the int8 variant."""
    if interpret is None:
        interpret = _interpret_default()
    B, H, R, D = q.shape
    L = k_stack.shape[3]
    assert k_stack.shape[2] == H, (q.shape, k_stack.shape)
    scale = float(scale) if scale is not None else 1.0 / float(np.sqrt(D))
    if block_l is None:
        block_l = _pick_block_l(L, H, D, k_stack.dtype.itemsize)
    assert L % block_l == 0, (L, block_l)
    scalars = jnp.stack([jnp.asarray(layer, jnp.int32).reshape(()),
                         jnp.asarray(pos, jnp.int32).reshape(())])
    grid_spec = pltpu.PrefetchScalarGridSpec(
        num_scalar_prefetch=1,
        grid=(B, L // block_l),
        in_specs=[
            pl.BlockSpec((1, H, R, D), lambda b, lb, sc: (b, 0, 0, 0)),
            pl.BlockSpec((1, 1, H, block_l, D),
                         lambda b, lb, sc: (sc[0], b, 0, lb, 0)),
            pl.BlockSpec((1, 1, H, block_l, D),
                         lambda b, lb, sc: (sc[0], b, 0, lb, 0)),
        ],
        out_specs=pl.BlockSpec((1, H, R, D),
                               lambda b, lb, sc: (b, 0, 0, 0)),
        scratch_shapes=[
            pltpu.VMEM((H, R, 1), jnp.float32),
            pltpu.VMEM((H, R, 1), jnp.float32),
            pltpu.VMEM((H, R, D), jnp.float32),
        ],
    )
    out = pl.pallas_call(
        functools.partial(_decode_attn_stacked_kernel, scale=scale,
                          block_l=block_l, seq_len=L, quantized=False),
        grid_spec=grid_spec,
        out_shape=jax.ShapeDtypeStruct((B, H, R, D), q.dtype),
        interpret=interpret,
    )(scalars, q, k_stack, v_stack)
    return out


# ------------------------------------------------- paged decode attention
#
# The serving engine (deepspeed_tpu/serving) stores the KV cache as a POOL
# of fixed-size blocks [Lyr, NB, H, page, D] plus a per-slot page table
# [B, MAXP] int32; a slot's cache rows for positions [p*page, (p+1)*page)
# live in pool block page_table[b, p]. The kernel grid is (B, MAXP) and the
# K/V block index maps GATHER through the scalar-prefetched page table —
# same online-softmax body as the dense stacked kernel, but the slot's
# pages can live anywhere in the pool, so slots are admitted/freed without
# reshaping anyone else's cache. Per-slot ``pos`` (a VECTOR, unlike the
# dense kernels' scalar) masks each slot independently: slots decode at
# different sequence lengths in the same program, and pos[b] < 0 marks an
# idle slot (every page skipped, output rows zero).

def decode_attention_paged(q, k_pool, v_pool, pos, page_table, layer,
                           k_scale=None, v_scale=None, scale=None,
                           interpret=None, rows_per_step=None):
    """S=1 cached attention through a paged KV pool.

    q [B, H, R, D] (R = grouped-query rows per KV head, 1 for MHA);
    k_pool/v_pool [Lyr, NB, H, page, D] int8 or bf16/fp32 blocks;
    k_scale/v_scale [Lyr, NB, H, 1, page] fp32 per-(block, head, row)
    absmax scales — pass None for full-precision pools (both or neither);
    pos [B] int32 — per-slot index of the newest valid cache row (< 0 →
    idle slot, output zeros); page_table [B, MAXP] int32 — pool block ids
    per slot page; entries past the slot's live pages must still be VALID
    pool indices (the engine points them at the reserved trash block 0).
    layer: scalar int32. Returns [B, H, R, D] in q.dtype.

    ``rows_per_step`` switches the kernel into MULTI-QUERY mode
    (speculative-decode verification): q's row axis carries
    ``n_steps x rows_per_step`` query rows in STEP-MAJOR order (row j is
    spec step ``j // rows_per_step``), and row j masks keys at
    ``k_pos <= pos[b] + j // rows_per_step`` — each drafted token
    attends through the page table at its own successive position, so
    the target model verifies all K draft tokens in ONE paged-attention
    call instead of K sequential ticks. ``rows_per_step=None`` keeps the
    single-position mask (all rows share ``pos``)."""
    if interpret is None:
        interpret = _interpret_default()
    quantized = k_scale is not None
    assert (v_scale is not None) == quantized
    B, H, R, D = q.shape
    if rows_per_step is not None:
        assert R % rows_per_step == 0, (R, rows_per_step)
    Lyr, NB, Hp, page, Dp = k_pool.shape
    assert (Hp, Dp) == (H, D), (q.shape, k_pool.shape)
    MAXP = page_table.shape[1]
    assert page_table.shape == (B, MAXP), (page_table.shape, B)
    scale = float(scale) if scale is not None else 1.0 / float(np.sqrt(D))
    layer = jnp.asarray(layer, jnp.int32).reshape(1)
    pos = jnp.asarray(pos, jnp.int32).reshape(B)
    page_table = jnp.asarray(page_table, jnp.int32)
    kv_spec = pl.BlockSpec(
        (1, 1, H, page, D),
        lambda b, pb, lr, pr, pt: (lr[0], pt[b, pb], 0, 0, 0))
    sc_spec = pl.BlockSpec(
        (1, 1, H, 1, page),
        lambda b, pb, lr, pr, pt: (lr[0], pt[b, pb], 0, 0, 0))
    in_specs = [pl.BlockSpec((1, H, R, D),
                             lambda b, pb, lr, pr, pt: (b, 0, 0, 0))]
    operands = [q]
    if quantized:
        in_specs += [kv_spec, sc_spec, kv_spec, sc_spec]
        operands += [k_pool, k_scale, v_pool, v_scale]
    else:
        in_specs += [kv_spec, kv_spec]
        operands += [k_pool, v_pool]
    grid_spec = pltpu.PrefetchScalarGridSpec(
        num_scalar_prefetch=3,
        grid=(B, MAXP),
        in_specs=in_specs,
        out_specs=pl.BlockSpec((1, H, R, D),
                               lambda b, pb, lr, pr, pt: (b, 0, 0, 0)),
        scratch_shapes=[
            pltpu.VMEM((H, R, 1), jnp.float32),
            pltpu.VMEM((H, R, 1), jnp.float32),
            pltpu.VMEM((H, R, D), jnp.float32),
        ],
    )
    out = pl.pallas_call(
        functools.partial(_decode_attn_paged_kernel, scale=scale,
                          page=page, quantized=quantized,
                          rows_per_step=rows_per_step),
        grid_spec=grid_spec,
        out_shape=jax.ShapeDtypeStruct((B, H, R, D), q.dtype),
        interpret=interpret,
    )(layer, pos, page_table, *operands)
    return out


def _decode_attn_paged_kernel(lyr_ref, pos_ref, pt_ref, q_ref, *rest,
                              scale, page, quantized, rows_per_step=None):
    """grid=(B, MAXP): same online-softmax state machine as the dense
    stacked kernel, but the block index maps already gathered this
    program's K/V page through the page table, and ``pos`` is read per
    slot so every batch row masks at its own length. In multi-query mode
    (``rows_per_step``) each query row masks at its own spec-step offset
    and pages up to the LAST step's position participate."""
    if quantized:
        k_ref, ks_ref, v_ref, vs_ref, o_ref, m_ref, d_ref, acc_ref = rest
    else:
        k_ref, v_ref, o_ref, m_ref, d_ref, acc_ref = rest
    b = pl.program_id(0)
    pb = pl.program_id(1)
    npg = pl.num_programs(1)
    pos = pos_ref[b]
    n_rows = q_ref.shape[2]
    max_step = 0 if rows_per_step is None \
        else n_rows // rows_per_step - 1

    @pl.when(pb == 0)
    def _init():
        m_ref[...] = jnp.full_like(m_ref[...], -1e30)
        d_ref[...] = jnp.zeros_like(d_ref[...])
        acc_ref[...] = jnp.zeros_like(acc_ref[...])

    base = pb * page

    # idle slots (pos < 0) must skip EVERY page even when max_step > 0
    # would otherwise pull page 0 in — their output stays zeros
    @pl.when((pos >= 0) & (base <= pos + max_step))
    def _block():
        q = q_ref[0]                                # [H, R, D]
        k = k_ref[0, 0].astype(q.dtype)             # [H, page, D]
        s = jax.lax.dot_general(
            q, k, (((2,), (2,)), ((0,), (0,))),
            preferred_element_type=jnp.float32)     # [H, R, page]
        s = s * scale
        if quantized:
            s = s * ks_ref[0, 0]                    # [H, 1, page]
        k_pos = base + jax.lax.broadcasted_iota(jnp.int32, s.shape, 2)
        if rows_per_step is None:
            s = jnp.where(k_pos <= pos, s, -1e30)
        else:
            step = jax.lax.broadcasted_iota(
                jnp.int32, s.shape, 1) // rows_per_step
            s = jnp.where(k_pos <= pos + step, s, -1e30)
        m_acc = m_ref[...]
        m_new = jnp.maximum(m_acc, jnp.max(s, axis=2, keepdims=True))
        m_ref[...] = m_new
        alpha = jnp.exp(m_acc - m_new)
        p = jnp.exp(s - m_new)
        d_ref[...] = d_ref[...] * alpha + jnp.sum(p, axis=2,
                                                  keepdims=True)
        if quantized:
            p = p * vs_ref[0, 0]
        pv = p.astype(q.dtype)
        v = v_ref[0, 0].astype(q.dtype)
        ctx = jax.lax.dot_general(
            pv, v, (((2,), (1,)), ((0,), (0,))),
            preferred_element_type=jnp.float32)     # [H, R, D]
        acc_ref[...] = acc_ref[...] * alpha + ctx

    @pl.when(pb == npg - 1)
    def _finish():
        d_safe = jnp.maximum(d_ref[...], 1e-30)
        o_ref[0] = (acc_ref[...] / d_safe).astype(o_ref.dtype)


def _out_ffn_stacked_kernel(l_ref, sc_ref, *args, eps, act, n_tiles,
                            norm, fuse_proj=True):
    if fuse_proj:
        ctx_ref, x_ref, wp_ref, lnw_ref, *rest = args
    else:
        x_ref, lnw_ref, *rest = args
        ctx_ref = wp_ref = None
    if norm == "rms":
        if act == "swiglu":
            w1_ref, w1b_ref, w2_ref, o_ref, x1_ref, u_ref, acc_ref = rest
        else:
            w1_ref, w2_ref, o_ref, x1_ref, u_ref, acc_ref = rest
            w1b_ref = None
        lnb_ref = bp_ref = b1_ref = b2_ref = None
    else:
        assert act != "swiglu", "swiglu implies the bias-free rms layout"
        (lnb_ref, bp_ref, w1_ref, b1_ref, w2_ref, b2_ref, o_ref,
         x1_ref, u_ref, acc_ref) = rest
        w1b_ref = None
    j = pl.program_id(0)
    dt = x_ref.dtype
    lidx = l_ref[0]

    @pl.when(j == 0)
    def _proj():
        if fuse_proj:
            ctx = ctx_ref[...]
            wp = wp_ref[0].astype(dt)
            t = jax.lax.dot(ctx, wp, preferred_element_type=jnp.float32)
            t = t * sc_ref[lidx, 0]
            if bp_ref is not None:
                t = t + bp_ref[0].astype(jnp.float32)
            x1 = x_ref[...].astype(jnp.float32) + t
        else:
            x1 = x_ref[...].astype(jnp.float32)
        x1_ref[...] = x1.astype(dt)
        if norm == "rms":
            u_ref[...] = _rms(x1, lnw_ref[0], eps).astype(dt)
        else:
            u_ref[...] = _ln(x1, lnw_ref[0], lnb_ref[0], eps).astype(dt)
        acc_ref[...] = jnp.zeros_like(acc_ref[...])

    u = u_ref[...]
    w1 = w1_ref[0].astype(dt)
    h = jax.lax.dot(u, w1, preferred_element_type=jnp.float32)
    h = h * sc_ref[lidx, 1]
    if b1_ref is not None:
        h = h + b1_ref[0].astype(jnp.float32)
    if act == "swiglu":
        up = jax.lax.dot(u, w1b_ref[0].astype(dt),
                         preferred_element_type=jnp.float32)
        h = jax.nn.silu(h) * (up * sc_ref[lidx, 3])
    elif act == "gelu_tanh":
        h = jax.nn.gelu(h, approximate=True)
    else:
        h = jax.nn.gelu(h, approximate=False)
    w2 = w2_ref[0].astype(dt)
    acc_ref[...] += jax.lax.dot(h.astype(dt), w2,
                                preferred_element_type=jnp.float32)

    @pl.when(j == n_tiles - 1)
    def _finish():
        y = x1_ref[...].astype(jnp.float32) + acc_ref[...] * sc_ref[lidx, 2]
        if b2_ref is not None:
            y = y + b2_ref[0].astype(jnp.float32)
        o_ref[...] = y.astype(o_ref.dtype)
