"""Flash attention — the TPU replacement for the reference's fused attention
CUDA path (csrc/transformer/softmax_kernels.cu + the score/context matmuls in
ds_transformer_cuda.cpp): one Pallas kernel per pass that never materializes
the [S, S] score matrix in HBM, with online softmax and a recompute-based
backward (custom VJP), accumulating in fp32 on the MXU.

Layout: q/k/v as [B, H, S, D] → kernels run on [B*H] × q-block grid. Two
kernel families share the same per-block math (`_fwd_block_step` /
`_bwd_ds_block`):

- **plain**: K/V (fwd, dq) or Q/dO (dkv) rows for one (batch, head) live
  whole in VMEM — fastest, used while S·D·itemsize fits the measured
  ~512 KB row budget (S=4k at D=64 in bf16).
- **chunked**: a third grid dimension streams sequence CHUNKS and
  accumulates into revisited fp32 output blocks (forward softmax m/l state
  rides in revisited outputs; normalization happens in-kernel on the last
  chunk). This is how single-chip attention training reaches 32k context;
  beyond that, sequence parallelism shards S first
  (deepspeed_tpu/parallel/ring_attention.py).

The softmax scale is folded into the [block, D] q-loads (one small VPU
multiply instead of one per [block_q, block_k] score tile), and causal
loops split into unmasked below-diagonal blocks + masked diagonal blocks —
at D < 128 the kernels are VPU-bound, so score-tile passes are the cost
that matters.

On non-TPU backends the kernels run in interpreter mode so unit tests check
the same code path numerically against the jnp reference (the
test_cuda_forward.py methodology, SURVEY §4).
"""

import functools

import numpy as np
import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl

from deepspeed_tpu.telemetry.spans import annotate

NEG_INF = -1e30

# measured scoped-VMEM ceiling for whole-row residency on v5e. The r4
# FUSED backward additionally keeps a fp32 [S, D] dq row resident, which
# moved the ceiling DOWN: bf16 S=4096, D=64 compiled in a small harness
# but the same shapes inside a larger program (bench.py's S=4096 dense
# case, BH=64) overflow scoped vmem by 284 KB — so the unchunked cutoff
# is now S*D*itemsize <= 256 KB (S=2048 at D=64 bf16) and S=4096 routes
# to the chunked kernels, whose per-chunk residency is bounded. The
# chunked kernels use half of this per chunk for pipeline double
# buffering (chunk 4096 at S=32k overflowed by 0.9 MB; 2048 fits).
_UNCHUNKED_ROW_BYTES = 262144
# per-chunk budget for the CHUNKED kernels (independent of the unchunked
# cutoff above — they have no resident dq row): measured on v5e, chunk
# 4096 at S=32k overflowed by 0.9 MB; 2048 fits
_CHUNK_ROW_BYTES = 524288


def _interpret_default():
    from deepspeed_tpu.utils.platform import is_tpu_backend
    return not is_tpu_backend()


# ------------------------------------------------------ shared block math

def _causal_mask(s, q_pos0, k_pos0, block_q, block_k):
    q_pos = q_pos0 + jax.lax.broadcasted_iota(
        jnp.int32, (block_q, block_k), 0)
    k_pos = k_pos0 + jax.lax.broadcasted_iota(
        jnp.int32, (block_q, block_k), 1)
    return jnp.where(q_pos >= k_pos, s, NEG_INF)


def _fwd_block_step(q, k, v, carry, q_pos0, k_pos0, block_q, block_k,
                    masked, scale):
    """One k-block of online-softmax forward. q/k/v stay in their native
    (typically bf16) dtype so the MXU runs at full rate — fp32 dot inputs
    run the systolic array at ~1/8 throughput, which made attention ~10%
    of peak and THE forward bottleneck at S=1k (r4 measurement). All dots
    accumulate fp32 (preferred_element_type); softmax state is fp32; the
    scale is applied to the fp32 scores (exactly equivalent to pre-scaled
    q up to bf16 rounding of q·scale, and independent of D).
    carry = (o_acc [bq, D] f32, m_acc [bq] f32, l_acc [bq] f32)."""
    o_acc, m_acc, l_acc = carry
    s = jax.lax.dot_general(q, k, (((1,), (1,)), ((), ())),
                            preferred_element_type=jnp.float32) * scale
    if masked:
        s = _causal_mask(s, q_pos0, k_pos0, block_q, block_k)
    m_new = jnp.maximum(m_acc, jnp.max(s, axis=1))
    alpha = jnp.exp(m_acc - m_new)
    p = jnp.exp(s - m_new[:, None])
    l_new = l_acc * alpha + jnp.sum(p, axis=1)
    o_new = o_acc * alpha[:, None] + jax.lax.dot(
        p.astype(v.dtype), v, preferred_element_type=jnp.float32)
    return o_new, m_new, l_new


def _bwd_ds_block(q, do, lse, delta, k, v, q_pos0, k_pos0, block_q, block_k,
                  masked, scale):
    """(p, ds) fp32 for one score tile of the backward; dot inputs stay in
    the native dtype (see _fwd_block_step). ds is d(loss)/d(s) with
    s = scale·q·kᵀ, so dq = scale·(ds·k) and dk = scale·(dsᵀ·q) — callers
    apply the final ·scale once on the accumulated result."""
    s = jax.lax.dot_general(q, k, (((1,), (1,)), ((), ())),
                            preferred_element_type=jnp.float32) * scale
    if masked:
        s = _causal_mask(s, q_pos0, k_pos0, block_q, block_k)
    p = jnp.exp(s - lse[:, None])
    dp = jax.lax.dot_general(do, v, (((1,), (1,)), ((), ())),
                             preferred_element_type=jnp.float32)
    ds = p * (dp - delta[:, None])
    return p, ds


def _causal_split_loop(lo, full, hi, body, carry):
    """fori_loop [lo, full) unmasked + [full, hi) masked."""
    carry = jax.lax.fori_loop(lo, full, lambda i, c: body(i, c, False),
                              carry)
    return jax.lax.fori_loop(full, hi, lambda i, c: body(i, c, True), carry)


# ---------------------------------------------------------------- forward

def _fwd_kernel(q_ref, k_ref, v_ref, o_ref, lse_ref, *, scale, causal,
                block_q, block_k, seq_len):
    qi = pl.program_id(1)
    q = q_ref[0]
    num_kb = seq_len // block_k

    def body(kb, carry, masked):
        k = k_ref[0, pl.ds(kb * block_k, block_k), :]
        v = v_ref[0, pl.ds(kb * block_k, block_k), :]
        return _fwd_block_step(q, k, v, carry, qi * block_q, kb * block_k,
                               block_q, block_k, masked, scale)

    carry0 = (jnp.zeros((block_q, q.shape[1]), jnp.float32),
              jnp.full((block_q,), NEG_INF, jnp.float32),
              jnp.zeros((block_q,), jnp.float32))
    if causal:
        num_full = (qi * block_q) // block_k
        num_active = ((qi + 1) * block_q + block_k - 1) // block_k
        o, m, l = _causal_split_loop(0, num_full, num_active, body, carry0)
    else:
        o, m, l = _causal_split_loop(0, num_kb, num_kb, body, carry0)

    l_safe = jnp.maximum(l, 1e-30)
    o_ref[0] = (o / l_safe[:, None]).astype(o_ref.dtype)
    lse_ref[0, :, 0] = m + jnp.log(l_safe)


def _flash_fwd(q, k, v, scale, causal, block_q, block_k, interpret,
               heads=0, kv_heads=0):
    """``heads``/``kv_heads`` > 0 enable grouped-query K/V: q is
    [B*heads, S, D] while k/v stay [B*kv_heads, S, D] — the K/V block
    index maps fold the q head onto its KV head, so the reduced-head
    cache streams once per rep q heads and the full-head K/V is NEVER
    materialized in HBM (the GQA memory promise, models/llama.py)."""
    BH, S, D = q.shape
    grid = (BH, S // block_q)
    if heads and kv_heads and heads != kv_heads:
        rep = heads // kv_heads
        H = heads

        def kv_map(b, i):
            return ((b // H) * kv_heads + (b % H) // rep, 0, 0)
    else:
        def kv_map(b, i):
            return (b, 0, 0)
    kernel = functools.partial(_fwd_kernel, scale=scale, causal=causal,
                               block_q=block_q, block_k=block_k, seq_len=S)
    call = pl.pallas_call(
        kernel,
        grid=grid,
        in_specs=[
            pl.BlockSpec((1, block_q, D), lambda b, i: (b, i, 0)),
            pl.BlockSpec((1, S, D), kv_map),
            pl.BlockSpec((1, S, D), kv_map),
        ],
        out_specs=[
            pl.BlockSpec((1, block_q, D), lambda b, i: (b, i, 0)),
            pl.BlockSpec((1, block_q, 1), lambda b, i: (b, i, 0)),
        ],
        out_shape=[
            jax.ShapeDtypeStruct((BH, S, D), q.dtype),
            jax.ShapeDtypeStruct((BH, S, 1), jnp.float32),
        ],
        interpret=interpret,
    )
    with annotate("flash_fwd"):
        o, lse = call(q, k, v)
    return o, lse


# ---------------------------------------------------------------- backward

def _bwd_fused_kernel(q_ref, k_ref, v_ref, do_ref, lse_ref, delta_ref,
                      dq_ref, dk_ref, dv_ref, *, scale, causal, block_q,
                      block_k, seq_len):
    """Single-pass backward: the grid walks k-blocks; dk/dv accumulate
    block-locally over the q-blocks of the inner loop, while dq
    accumulates into a VMEM-resident full row (its index map ignores the
    k-block grid dim, so Pallas keeps the block resident across grid
    steps). Each (q-block, k-block) score tile — the dots AND the exp —
    is computed ONCE, where the split dq/dkv kernels computed everything
    but the final products twice; the exp on [bq, bk] fp32 tiles is
    VPU-bound, so halving it is the biggest attention-bwd lever at
    training shapes (measured 2.4 ms/layer -> target <1.5 at the 774M
    headline: B*H=160, S=1024, D=64)."""
    ki = pl.program_id(1)
    num_kb = seq_len // block_k
    num_qb = seq_len // block_q
    k = k_ref[0]   # [block_k, D]
    v = v_ref[0]

    @pl.when(ki == 0)
    def _init():
        dq_ref[0] = jnp.zeros_like(dq_ref[0])

    def body(qb, carry, masked):
        dk_acc, dv_acc = carry
        q = q_ref[0, pl.ds(qb * block_q, block_q), :]
        do = do_ref[0, pl.ds(qb * block_q, block_q), :]
        lse = lse_ref[0, pl.ds(qb * block_q, block_q), 0]
        delta = delta_ref[0, pl.ds(qb * block_q, block_q), 0]
        p, ds = _bwd_ds_block(q, do, lse, delta, k, v, qb * block_q,
                              ki * block_k, block_q, block_k, masked,
                              scale)
        dsl = ds.astype(q.dtype)
        dv_new = dv_acc + jax.lax.dot_general(
            p.astype(do.dtype), do, (((0,), (0,)), ((), ())),
            preferred_element_type=jnp.float32)
        dk_new = dk_acc + jax.lax.dot_general(
            dsl, q, (((0,), (0,)), ((), ())),
            preferred_element_type=jnp.float32)
        sl = pl.ds(qb * block_q, block_q)
        dq_ref[0, sl, :] += jax.lax.dot(
            dsl, k, preferred_element_type=jnp.float32)
        return dk_new, dv_new

    carry0 = (jnp.zeros(k.shape, jnp.float32),
              jnp.zeros(v.shape, jnp.float32))
    if causal:
        first_active = (ki * block_k) // block_q
        first_full = ((ki + 1) * block_k + block_q - 1) // block_q
        carry = jax.lax.fori_loop(
            first_active, jnp.minimum(first_full, num_qb),
            lambda qb, c: body(qb, c, True), carry0)
        dk, dv = jax.lax.fori_loop(
            first_full, num_qb, lambda qb, c: body(qb, c, False), carry)
    else:
        dk, dv = _causal_split_loop(0, num_qb, num_qb, body, carry0)
    dk_ref[0] = (dk * scale).astype(dk_ref.dtype)   # dk = scale·Σ dsᵀ·q
    dv_ref[0] = dv.astype(dv_ref.dtype)

    @pl.when(ki == num_kb - 1)
    def _finish():
        # dq = scale·Σ ds·k, applied once after every k-block contributed
        dq_ref[0] *= scale


def _flash_bwd(q, k, v, o, lse, do, scale, causal, block_q, block_k,
               interpret):
    BH, S, D = q.shape
    delta = jnp.sum(do.astype(jnp.float32) * o.astype(jnp.float32),
                    axis=-1)[:, :, None]  # [BH, S, 1]

    call = pl.pallas_call(
        functools.partial(_bwd_fused_kernel, scale=scale, causal=causal,
                          block_q=block_q, block_k=block_k, seq_len=S),
        grid=(BH, S // block_k),
        in_specs=[
            pl.BlockSpec((1, S, D), lambda b, i: (b, 0, 0)),
            pl.BlockSpec((1, block_k, D), lambda b, i: (b, i, 0)),
            pl.BlockSpec((1, block_k, D), lambda b, i: (b, i, 0)),
            pl.BlockSpec((1, S, D), lambda b, i: (b, 0, 0)),
            pl.BlockSpec((1, S, 1), lambda b, i: (b, 0, 0)),
            pl.BlockSpec((1, S, 1), lambda b, i: (b, 0, 0)),
        ],
        out_specs=[
            pl.BlockSpec((1, S, D), lambda b, i: (b, 0, 0)),
            pl.BlockSpec((1, block_k, D), lambda b, i: (b, i, 0)),
            pl.BlockSpec((1, block_k, D), lambda b, i: (b, i, 0)),
        ],
        out_shape=[
            jax.ShapeDtypeStruct((BH, S, D), jnp.float32),
            jax.ShapeDtypeStruct((BH, S, D), q.dtype),
            jax.ShapeDtypeStruct((BH, S, D), q.dtype),
        ],
        interpret=interpret,
    )
    with annotate("flash_bwd"):
        dq, dk, dv = call(q, k, v, do, lse, delta)
    return dq.astype(q.dtype), dk, dv


# ------------------------------------------------- long-S chunked variants

def _fwd_kernel_chunked(q_ref, k_ref, v_ref, o_ref, m_ref, l_ref,
                        *, scale, causal, block_q, block_k, chunk,
                        n_chunks):
    qi = pl.program_id(1)
    kc = pl.program_id(2)
    cb = chunk // block_k                      # k-blocks per chunk
    q = q_ref[0]

    @pl.when(kc == 0)
    def _init():
        o_ref[0] = jnp.zeros_like(o_ref[0])
        m_ref[0] = jnp.full_like(m_ref[0], NEG_INF)
        l_ref[0] = jnp.zeros_like(l_ref[0])

    def body(j, carry, masked):
        kb = kc * cb + j                       # global k-block index
        k = k_ref[0, pl.ds(j * block_k, block_k), :]
        v = v_ref[0, pl.ds(j * block_k, block_k), :]
        return _fwd_block_step(q, k, v, carry, qi * block_q, kb * block_k,
                               block_q, block_k, masked, scale)

    carry0 = (o_ref[0], m_ref[0, :, 0], l_ref[0, :, 0])
    if causal:
        num_full = (qi * block_q) // block_k
        num_active = ((qi + 1) * block_q + block_k - 1) // block_k
        j_full = jnp.clip(num_full - kc * cb, 0, cb)
        j_hi = jnp.clip(num_active - kc * cb, 0, cb)
        o, m, l = _causal_split_loop(0, j_full, j_hi, body, carry0)
    else:
        o, m, l = _causal_split_loop(0, cb, cb, body, carry0)

    # accumulate raw (o, m, l) across chunk revisits; the last chunk holds
    # the final softmax state, so normalize in-kernel there — no separate
    # [BH, S, D] normalization pass in HBM
    last = kc == n_chunks - 1
    l_safe = jnp.maximum(l, 1e-30)
    o_ref[0] = jnp.where(last,
                         jnp.where((l > 0)[:, None], o / l_safe[:, None],
                                   0.0),
                         o)
    m_ref[0, :, 0] = jnp.where(last, m + jnp.log(l_safe), m)
    l_ref[0, :, 0] = l


def _flash_fwd_chunked(q, k, v, scale, causal, block_q, block_k, chunk,
                       interpret):
    BH, S, D = q.shape
    n_chunks = S // chunk
    kernel = functools.partial(_fwd_kernel_chunked, scale=scale,
                               causal=causal, block_q=block_q,
                               block_k=block_k, chunk=chunk,
                               n_chunks=n_chunks)
    call = pl.pallas_call(
        kernel,
        grid=(BH, S // block_q, n_chunks),
        in_specs=[
            pl.BlockSpec((1, block_q, D), lambda b, i, c: (b, i, 0)),
            pl.BlockSpec((1, chunk, D), lambda b, i, c: (b, c, 0)),
            pl.BlockSpec((1, chunk, D), lambda b, i, c: (b, c, 0)),
        ],
        out_specs=[
            pl.BlockSpec((1, block_q, D), lambda b, i, c: (b, i, 0)),
            pl.BlockSpec((1, block_q, 1), lambda b, i, c: (b, i, 0)),
            pl.BlockSpec((1, block_q, 1), lambda b, i, c: (b, i, 0)),
        ],
        out_shape=[
            jax.ShapeDtypeStruct((BH, S, D), jnp.float32),
            jax.ShapeDtypeStruct((BH, S, 1), jnp.float32),
            jax.ShapeDtypeStruct((BH, S, 1), jnp.float32),
        ],
        interpret=interpret,
    )
    with annotate("flash_fwd_chunk"):
        o32, lse, _ = call(q, k, v)
    return o32.astype(q.dtype), lse


def _bwd_dq_kernel_chunked(q_ref, k_ref, v_ref, do_ref, lse_ref, delta_ref,
                           dq_ref, *, scale, causal, block_q, block_k,
                           chunk, n_chunks):
    qi = pl.program_id(1)
    kc = pl.program_id(2)
    cb = chunk // block_k
    q = q_ref[0]
    do = do_ref[0]
    lse = lse_ref[0, :, 0]
    delta = delta_ref[0, :, 0]

    @pl.when(kc == 0)
    def _init():
        dq_ref[0] = jnp.zeros_like(dq_ref[0])

    def body(j, dq_acc, masked):
        kb = kc * cb + j
        k = k_ref[0, pl.ds(j * block_k, block_k), :]
        v = v_ref[0, pl.ds(j * block_k, block_k), :]
        _, ds = _bwd_ds_block(q, do, lse, delta, k, v, qi * block_q,
                              kb * block_k, block_q, block_k, masked,
                              scale)
        return dq_acc + jax.lax.dot(ds.astype(k.dtype), k,
                                    preferred_element_type=jnp.float32)

    if causal:
        num_full = (qi * block_q) // block_k
        num_active = ((qi + 1) * block_q + block_k - 1) // block_k
        j_full = jnp.clip(num_full - kc * cb, 0, cb)
        j_hi = jnp.clip(num_active - kc * cb, 0, cb)
        dq = _causal_split_loop(0, j_full, j_hi, body, dq_ref[0])
    else:
        dq = _causal_split_loop(0, cb, cb, body, dq_ref[0])
    # accumulate UNscaled across chunk revisits; apply the folded-scale
    # chain rule once on the final chunk (dq = scale · Σ ds·k)
    dq_ref[0] = jnp.where(pl.program_id(2) == n_chunks - 1, dq * scale, dq)


def _bwd_dkv_kernel_chunked(q_ref, k_ref, v_ref, do_ref, lse_ref, delta_ref,
                            dk_ref, dv_ref, *, scale, causal, block_q,
                            block_k, chunk, n_chunks):
    ki = pl.program_id(1)
    qc = pl.program_id(2)
    cb = chunk // block_q
    k = k_ref[0]
    v = v_ref[0]

    @pl.when(qc == 0)
    def _init():
        dk_ref[0] = jnp.zeros_like(dk_ref[0])
        dv_ref[0] = jnp.zeros_like(dv_ref[0])

    def body(j, carry, masked):
        dk_acc, dv_acc = carry
        qb = qc * cb + j
        q = q_ref[0, pl.ds(j * block_q, block_q), :]
        do = do_ref[0, pl.ds(j * block_q, block_q), :]
        lse = lse_ref[0, pl.ds(j * block_q, block_q), 0]
        delta = delta_ref[0, pl.ds(j * block_q, block_q), 0]
        p, ds = _bwd_ds_block(q, do, lse, delta, k, v, qb * block_q,
                              ki * block_k, block_q, block_k, masked,
                              scale)
        dv_new = dv_acc + jax.lax.dot_general(
            p.astype(do.dtype), do, (((0,), (0,)), ((), ())),
            preferred_element_type=jnp.float32)
        dk_new = dk_acc + jax.lax.dot_general(
            ds.astype(q.dtype), q, (((0,), (0,)), ((), ())),
            preferred_element_type=jnp.float32)
        return dk_new, dv_new

    carry0 = (dk_ref[0], dv_ref[0])
    if causal:
        # within this q-chunk: blocks before the diagonal skip entirely,
        # blocks straddling it run masked, strictly-after blocks unmasked
        first_active = (ki * block_k) // block_q
        first_full = ((ki + 1) * block_k + block_q - 1) // block_q
        j_lo = jnp.clip(first_active - qc * cb, 0, cb)
        j_mid = jnp.clip(first_full - qc * cb, 0, cb)
        carry = jax.lax.fori_loop(
            j_lo, j_mid, lambda j, c: body(j, c, True), carry0)
        dk, dv = jax.lax.fori_loop(
            j_mid, cb, lambda j, c: body(j, c, False), carry)
    else:
        dk, dv = _causal_split_loop(0, cb, cb, body, carry0)
    # dk accumulates UNscaled across chunk revisits; the folded-scale
    # chain rule (dk = scale·Σ dsᵀ·q) lands once on the final chunk
    dk_ref[0] = jnp.where(qc == n_chunks - 1, dk * scale, dk)
    dv_ref[0] = dv


def _flash_bwd_chunked(q, k, v, o, lse, do, scale, causal, block_q, block_k,
                       chunk, interpret):
    BH, S, D = q.shape
    n_chunks = S // chunk
    delta = jnp.sum(do.astype(jnp.float32) * o.astype(jnp.float32),
                    axis=-1)[:, :, None]

    call_dq = pl.pallas_call(
        functools.partial(_bwd_dq_kernel_chunked, scale=scale, causal=causal,
                          block_q=block_q, block_k=block_k, chunk=chunk,
                          n_chunks=n_chunks),
        grid=(BH, S // block_q, n_chunks),
        in_specs=[
            pl.BlockSpec((1, block_q, D), lambda b, i, c: (b, i, 0)),
            pl.BlockSpec((1, chunk, D), lambda b, i, c: (b, c, 0)),
            pl.BlockSpec((1, chunk, D), lambda b, i, c: (b, c, 0)),
            pl.BlockSpec((1, block_q, D), lambda b, i, c: (b, i, 0)),
            pl.BlockSpec((1, block_q, 1), lambda b, i, c: (b, i, 0)),
            pl.BlockSpec((1, block_q, 1), lambda b, i, c: (b, i, 0)),
        ],
        out_specs=pl.BlockSpec((1, block_q, D), lambda b, i, c: (b, i, 0)),
        out_shape=jax.ShapeDtypeStruct((BH, S, D), jnp.float32),
        interpret=interpret,
    )
    with annotate("flash_bwd_dq"):
        dq = call_dq(q, k, v, do, lse, delta)

    call_dkv = pl.pallas_call(
        functools.partial(_bwd_dkv_kernel_chunked, scale=scale,
                          causal=causal, block_q=block_q, block_k=block_k,
                          chunk=chunk, n_chunks=n_chunks),
        grid=(BH, S // block_k, n_chunks),
        in_specs=[
            pl.BlockSpec((1, chunk, D), lambda b, i, c: (b, c, 0)),
            pl.BlockSpec((1, block_k, D), lambda b, i, c: (b, i, 0)),
            pl.BlockSpec((1, block_k, D), lambda b, i, c: (b, i, 0)),
            pl.BlockSpec((1, chunk, D), lambda b, i, c: (b, c, 0)),
            pl.BlockSpec((1, chunk, 1), lambda b, i, c: (b, c, 0)),
            pl.BlockSpec((1, chunk, 1), lambda b, i, c: (b, c, 0)),
        ],
        out_specs=[
            pl.BlockSpec((1, block_k, D), lambda b, i, c: (b, i, 0)),
            pl.BlockSpec((1, block_k, D), lambda b, i, c: (b, i, 0)),
        ],
        out_shape=[
            jax.ShapeDtypeStruct((BH, S, D), jnp.float32),
            jax.ShapeDtypeStruct((BH, S, D), jnp.float32),
        ],
        interpret=interpret,
    )
    with annotate("flash_bwd_dkv"):
        dk, dv = call_dkv(q, k, v, do, lse, delta)
    return dq.astype(q.dtype), dk.astype(q.dtype), dv.astype(q.dtype)


# ---------------------------------------------------------------- public op

def _dispatch_fwd(q, k, v, scale, causal, block_q, block_k, chunk,
                  interpret, heads=0, kv_heads=0):
    if chunk:
        assert not (heads and kv_heads and heads != kv_heads), \
            "GQA rides the unchunked kernel (caller repeats for chunked)"
        return _flash_fwd_chunked(q, k, v, scale, causal, block_q, block_k,
                                  chunk, interpret)
    return _flash_fwd(q, k, v, scale, causal, block_q, block_k, interpret,
                      heads=heads, kv_heads=kv_heads)


@functools.partial(jax.custom_vjp,
                   nondiff_argnums=(3, 4, 5, 6, 7, 8, 9, 10))
def _flash_attention(q, k, v, scale, causal, block_q, block_k, chunk,
                     interpret, heads=0, kv_heads=0):
    o, _ = _dispatch_fwd(q, k, v, scale, causal, block_q, block_k, chunk,
                         interpret, heads, kv_heads)
    return o


def _flash_attention_fwd(q, k, v, scale, causal, block_q, block_k, chunk,
                         interpret, heads=0, kv_heads=0):
    o, lse = _dispatch_fwd(q, k, v, scale, causal, block_q, block_k, chunk,
                           interpret, heads, kv_heads)
    # name the residuals so remat policies can elect to keep them: saving
    # o (+tiny lse) lets the backward kernels run without re-executing the
    # forward kernel under rematerialization (models/gpt2.py "dots_flash")
    from jax.ad_checkpoint import checkpoint_name
    o = checkpoint_name(o, "flash_o")
    lse = checkpoint_name(lse, "flash_lse")
    return o, (q, k, v, o, lse)


def _flash_attention_bwd(scale, causal, block_q, block_k, chunk, interpret,
                         heads, kv_heads, residuals, do):
    q, k, v, o, lse = residuals
    gqa = bool(heads and kv_heads and heads != kv_heads)
    if gqa:
        # backward still runs the full-head kernels: K/V repeat to
        # [B*H, S, D] HERE (transient, bwd-only) and dk/dv sum back over
        # the rep query heads sharing each KV head. A dk/dv-accumulating
        # GQA backward kernel would remove this transient — the forward
        # and prefill (the steady-state memory) no longer materialize it.
        B = q.shape[0] // heads
        rep = heads // kv_heads
        S, D = k.shape[1], k.shape[2]

        def rep_kv(t):
            return jnp.repeat(t.reshape(B, kv_heads, S, D), rep,
                              axis=1).reshape(B * heads, S, D)
        k = rep_kv(k)
        v = rep_kv(v)
    if chunk:
        dq, dk, dv = _flash_bwd_chunked(q, k, v, o, lse, do, scale, causal,
                                        block_q, block_k, chunk, interpret)
    else:
        dq, dk, dv = _flash_bwd(q, k, v, o, lse, do, scale, causal,
                                block_q, block_k, interpret)
    if gqa:
        def sum_rep(t):
            return t.reshape(B, kv_heads, rep, S, D).sum(axis=2) \
                .astype(t.dtype).reshape(B * kv_heads, S, D)
        dk = sum_rep(dk)
        dv = sum_rep(dv)
    return dq, dk, dv


_flash_attention.defvjp(_flash_attention_fwd, _flash_attention_bwd)


def flash_attention(q, k, v, causal=False, scale=None, block_q=None,
                    block_k=None, interpret=None, chunk=None):
    """[B, H, S, D] flash attention. Falls back to the jnp reference for
    shapes the kernel can't tile (tiny S/D in unit tests). ``chunk``
    forces the long-S chunked kernels (auto-selected past the VMEM row
    budget); it must divide S and be a multiple of both block sizes."""
    B, H, S, D = q.shape
    scale = float(scale) if scale is not None else 1.0 / float(np.sqrt(D))
    if interpret is None:
        interpret = _interpret_default()
    # 512/512 measured fastest on v5e at S=1k-4k, D=64 (27% over 256/256:
    # fewer grid steps amortize the half-rate D<128 contraction better).
    # For S not divisible by 512 take the largest power-of-two divisor so
    # e.g. S=768/1280/2560 keep the flash kernel instead of silently
    # materializing [S, S] scores in the reference fallback.
    def pick_block(requested):
        if requested:
            return requested
        top = 64 if interpret else 512
        for cand in (top, 256, 128, 64, 32):
            if cand <= top and S % cand == 0:
                return cand
        # irregular short sequences (e.g. S=80): one block spanning S keeps
        # the kernel path, matching the old min(block, S) behavior
        return S if S <= top else 0
    block_q = pick_block(block_q)
    block_k = pick_block(block_k)
    Hkv = k.shape[1]
    assert v.shape[1] == Hkv and H % Hkv == 0, (q.shape, k.shape)

    if not block_q or not block_k or S % block_q or S % block_k:
        from deepspeed_tpu.ops.attention import reference_attention
        # reference_attention repeats reduced-head K/V itself
        return reference_attention(q, k, v, causal=causal, scale=scale)
    if chunk is not None:
        if S % chunk or chunk % block_q or chunk % block_k:
            raise ValueError(
                f"chunk={chunk} must divide S={S} and be a multiple of "
                f"block_q={block_q} and block_k={block_k}")
    itemsize = jnp.dtype(q.dtype).itemsize
    if chunk is None and S * D * itemsize > _UNCHUNKED_ROW_BYTES:
        # whole-row residency stops fitting scoped VMEM — stream chunks
        budget = max(_CHUNK_ROW_BYTES // 2 // (D * itemsize), 1)
        for cand in (4096, 2048, 1024, 512, 256, 128, 64):
            if cand <= budget and S % cand == 0 \
                    and cand % block_q == 0 and cand % block_k == 0:
                chunk = cand
                break
        else:
            from deepspeed_tpu.ops.attention import reference_attention
            return reference_attention(q, k, v, causal=causal,
                                       scale=scale)

    qf = q.reshape(B * H, S, D)
    if chunk and Hkv != H:
        # the chunked kernels keep full-head maps; GQA rides the
        # unchunked kernel — repeat here for the long-S streaming path
        k = jnp.repeat(k, H // Hkv, axis=1)
        v = jnp.repeat(v, H // Hkv, axis=1)
        Hkv = H
    kf = k.reshape(B * k.shape[1], S, D)
    vf = v.reshape(B * v.shape[1], S, D)
    o = _flash_attention(qf, kf, vf, scale, causal, block_q, block_k,
                         int(chunk) if chunk else 0, bool(interpret),
                         H, Hkv)
    return o.reshape(B, H, S, D)
