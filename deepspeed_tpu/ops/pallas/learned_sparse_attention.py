"""Attention whose mask is DATA: a learned indexer scores every causal pair,
each query keeps its ``topk`` best keys, and the heads attend to those alone.

DeepSeek-V3.2's sparse attention on grouped-query heads. Per sequence, with
queries t and keys s <= t:

    I[t, s]  = sum_j w[t, j] * relu(iq[t, j] . ik[s])        (float32)
    S_t      = the topk keys of largest I[t, .], all while t < topk; a tie
               goes to the smaller s
    o[t, h]  = softmax over S_t of (q[t, h] . k[s, g(h)] * scale) v[s, g(h)]
    kl[t]    = sum_{s in S_t} p (log p - log softmax_{S_t}(I[t, .])),
               p[t, s] = mean_h of the attention's probabilities

Six kernels, every [S, S] array held TRANSPOSED, [key, query] (queries on the
lanes: a query's threshold, log-sum-exp and weight are lane-dense rows, and a
count over keys is a sum down the sublanes):

    ``dsa_indexer``      IT [B, S, S] float32, tile by causal tile
    ``dsa_select``       the exact top-k of every query: the k-th largest
                         score found bit by bit on the float's sortable
                         integer image (32 counts over the query's keys in
                         VMEM), then the index cut-off among the scores that
                         tie with it; leaves the mask MT int8 [B, S, S], the
                         log-sum-exp of the kept scores and their count
    ``dsa_fwd`` / ``dsa_bwd``   the chunked flash kernels of
                         ``flash_attention.py`` (their tile math imported)
                         walking the causal tiles under the mask's tile; ONE
                         mask for all heads, read a head
    ``dsa_kl``           the heads' probabilities summed a tile, the KL of a
                         query and GT = softmax_S(I) - p, its gradient in I,
                         written as the causal tiles alone (``packed_tile``)
    ``dsa_indexer_bwd``  GT, times the KL's cotangent a query, through the
                         indexer: relu's gate a head, the query side
                         accumulated over a query block's keys, the key side
                         left as partials a query block

What is walked is every causal tile (the mask is data: a tile may hold any
of its pairs); what the mathematics needs is the selected pairs alone, and
the rooflines count those (``selected_pairs``). The mask is what a rematted
block keeps of the selection (``pin_selection``: packed to bits, 1/8 byte a
pair, under the name ``SELECTION_NAME``) so that a recomputed forward attends
to the keys the first one chose. GT carries a name of its own
(``KL_GRAD_NAME``): a block whose policy keeps it (``runtime/remat_budget.
keep_kl_grad``, where the bytes fit) hands the first forward's GT to the
indexer's backward, and its recomputed forward runs neither ``dsa_indexer``
nor ``dsa_kl``; one that does not runs both again.

``reference_*``: the same mathematics in plain XLA, dense, the kernels'
oracle and the path off a TPU.
"""

import functools
import importlib
import math

import numpy as np
import jax
import jax.numpy as jnp
from jax.ad_checkpoint import checkpoint_name
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu

from deepspeed_tpu.telemetry.registry import default_registry
from deepspeed_tpu.telemetry.spans import annotate

fa = importlib.import_module("deepspeed_tpu.ops.pallas.flash_attention")

# the name a remat policy keeps the selection by (``pin_selection``)
SELECTION_NAME = "dsa_selection"
# ... and the KL's gradient in the scores, as causal tiles (``_index_kl``)
KL_GRAD_NAME = "dsa_kl_grad"
INT_MIN = -2 ** 31
# scoped VMEM the selection may take: a query block's scores over all keys,
# twice (the pipeline's), their integer image and the mask
_SELECT_VMEM_BYTES = 100 * 2 ** 20
# ... and the KL's and the indexer's backward: a query block of every head
# (lane-padded), twice, beside the tile's float32 temporaries
_KL_VMEM_BYTES = 64 * 2 ** 20


def selected_pairs(S, topk):
    """sum_t min(t + 1, topk): the pairs a sequence of S keeps."""
    k = min(S, topk)
    return k * (k + 1) // 2 + (S - k) * k


def causal_pairs(S):
    return S * (S + 1) // 2


def tiles_walked(S, tile):
    """Causal tiles of ``tile`` x ``tile`` a pass walks."""
    n = -(-S // tile)
    return n * (n + 1) // 2


def packed_tile(i, c):
    """Where the [key block c, query block i] tile of an [S, S] array lies
    among its causal tiles alone, query block by query block; a tile above
    the diagonal (c > i: never written, never read) maps to the diagonal's."""
    return i * (i + 1) // 2 + jnp.minimum(c, i)


def tile_overcompute(S, topk, tile):
    """Score elements the walked tiles compute over the pairs selected."""
    return tiles_walked(S, tile) * tile * tile / selected_pairs(S, topk)


# ------------------------------------------------------------ plain XLA

def reference_index_scores(iq, ik, iw):
    """I [B, S_q, S_k] float32 of iq [B, J, S, Di], ik [B, S, Di], iw
    [B, S, J] (float32, the scale folded in): every pair, causal or not."""
    s = jnp.einsum("bjtd,bsd->bjts", iq, ik,
                   preferred_element_type=jnp.float32)
    score = jnp.einsum("bjts,btj->bts", jax.nn.relu(s),
                       iw.astype(jnp.float32))
    # -0.0 sorts as 0.0 (the gradient passes: the correction is a constant)
    return score + jax.lax.stop_gradient(
        jnp.where(score == 0, 0.0, score) - score)


def reference_select(scores, topk):
    """bool [B, S_q, S_k]: the ``topk`` causal keys of largest score a
    query, a tie to the smaller key. The k-th largest value by ``lax.top_k``
    (exact), then the keys that tie with it counted in order."""
    B, S, _ = scores.shape
    t, s = jnp.arange(S)[:, None], jnp.arange(S)[None, :]
    causal = s <= t
    if topk >= S:
        return jnp.broadcast_to(causal, scores.shape)
    masked = jnp.where(causal, scores, -jnp.inf)
    kth = jax.lax.top_k(masked, topk)[0][..., -1:]
    above = masked > kth
    ties = (masked == kth) & causal
    need = topk - jnp.sum(above, axis=-1, keepdims=True)
    rank = jnp.cumsum(ties, axis=-1)                # 1-based among the ties
    chosen = above | (ties & (rank <= need))
    return jnp.where(t < topk, causal, chosen)


def reference_masked_attention(q, k, v, mask, scale):
    """(o [B, H, S, D] as q, lse [B, H, S] float32) under ``mask`` [B, S_q,
    S_k] bool; K and V may carry fewer heads."""
    H = q.shape[1]
    rep = H // k.shape[1]
    k, v = (jnp.repeat(x, rep, axis=1) for x in (k, v))
    s = jnp.einsum("bhtd,bhsd->bhts", q, k,
                   preferred_element_type=jnp.float32) * scale
    s = jnp.where(mask[:, None], s, fa.NEG_INF)
    lse = jax.nn.logsumexp(s, axis=-1)
    p = jnp.exp(s - lse[..., None])
    o = jnp.einsum("bhts,bhsd->bhtd", p.astype(v.dtype), v,
                   preferred_element_type=jnp.float32)
    return o.astype(q.dtype), lse


def reference_index_kl(scores, q, k, lse, mask, scale):
    """kl [B, S] float32: KL(p || softmax_S(I)) a query, p the heads' mean
    probability over the selected keys (no gradient reaches q, k or lse)."""
    q, k, lse = (jax.lax.stop_gradient(x) for x in (q, k, lse))
    H = q.shape[1]
    k = jnp.repeat(k, H // k.shape[1], axis=1)
    s = jnp.einsum("bhtd,bhsd->bhts", q, k,
                   preferred_element_type=jnp.float32) * scale
    p = jnp.mean(jnp.where(mask[:, None], jnp.exp(s - lse[..., None]), 0.0),
                 axis=1)
    logq = jax.nn.log_softmax(jnp.where(mask, scores, -jnp.inf), axis=-1)
    safe = jnp.where(mask & (p > 0), p, 1.0)
    return jnp.sum(jnp.where(mask & (p > 0),
                             p * (jnp.log(safe) - jnp.where(mask, logq, 0.0)),
                             0.0), axis=-1)


def reference_learned_sparse_attention(q, k, v, iq, ik, iw, topk, scale):
    """(o, kl [B, S], kept keys a query [B, S], the kept set's bits as
    ``pack_mask`` leaves them) in plain XLA, dense."""
    scores = reference_index_scores(iq, ik, iw)
    mask = reference_select(jax.lax.stop_gradient(scores), topk)
    o, lse = reference_masked_attention(q, k, v, mask, scale)
    kl = reference_index_kl(scores, q, k, lse, mask, scale)
    bits = pack_mask(_pad_rows(jnp.swapaxes(mask, 1, 2).astype(jnp.int8),
                               1, 8))
    return o, kl, jnp.sum(mask, axis=2, dtype=jnp.int32), bits


# -------------------------------------------------------------- helpers

def _tile(interpret):
    """Side of a tile of the [S, S] kernels: 512 on the chip, 128 in the
    interpreter; a sequence is padded to whole tiles (``_pad_rows``)."""
    return 128 if interpret else 512


def _pad_rows(x, axis, to):
    pad = (-x.shape[axis]) % to
    if not pad:
        return x
    widths = [(0, 0)] * x.ndim
    widths[axis] = (0, pad)
    return jnp.pad(x, widths)


def _params(interpret, vmem=None, semantics=None):
    if interpret:
        return None
    return pltpu.CompilerParams(vmem_limit_bytes=vmem,
                                dimension_semantics=semantics)


def _sortable(x):
    """float32 -> int32 whose signed order is the floats' order."""
    b = jax.lax.bitcast_convert_type(x, jnp.int32)
    return jnp.where(b >= 0, b, b ^ jnp.int32(0x7FFFFFFF))


# -------------------------------------------------------- indexer scores

def _indexer_kernel(iq_ref, ik_ref, wt_ref, out_ref, *, heads):
    qi, ki = pl.program_id(1), pl.program_id(2)

    @pl.when(ki <= qi)
    def _tile_scores():
        k = ik_ref[0]                                   # [Tk, Di]
        acc = None
        for j in range(heads):
            s = jax.lax.dot_general(k, iq_ref[0, j], (((1,), (1,)), ((), ())),
                                    preferred_element_type=jnp.float32)
            term = jnp.maximum(s, 0.0) * wt_ref[0, j:j + 1, :]
            acc = term if acc is None else acc + term
        out_ref[0] = jnp.where(acc == 0, 0.0, acc)      # -0.0 sorts as 0.0


def _index_scores(iq, ik, iw, tile, interpret):
    """IT [B, S_k, S_q] float32. No derivative of its own: the selection
    reads it under ``stop_gradient`` and the KL's rule (``_index_kl``) goes
    from its cotangent to the three operands itself."""
    B, J, S, Di = iq.shape
    n = S // tile
    call = pl.pallas_call(
        functools.partial(_indexer_kernel, heads=J),
        grid=(B, n, n),
        in_specs=[
            pl.BlockSpec((1, J, tile, Di), lambda b, i, c: (b, 0, i, 0)),
            pl.BlockSpec((1, tile, Di),
                         lambda b, i, c: (b, jnp.minimum(c, i), 0)),
            pl.BlockSpec((1, J, tile), lambda b, i, c: (b, 0, i))],
        out_specs=pl.BlockSpec((1, tile, tile),
                               lambda b, i, c: (b, jnp.minimum(c, i), i)),
        out_shape=jax.ShapeDtypeStruct((B, S, S), jnp.float32),
        interpret=interpret,
        compiler_params=_params(interpret, None,
                                ("parallel", "arbitrary", "arbitrary")))
    with annotate("dsa_indexer"):
        return call(iq, ik, jnp.swapaxes(iw, 1, 2))


def _indexer_bwd_kernel(iq_ref, qw_ref, ik_ref, gt_ref, g_ref, u_ref, dk_ref,
                        *, heads):
    qi, ki = pl.program_id(1), pl.program_id(2)

    @pl.when(ki == 0)
    def _init():
        u_ref[...] = jnp.zeros_like(u_ref)

    @pl.when(ki <= qi)
    def _tile_grads():
        k = ik_ref[0]
        # [Tk, Tq]: the KL's gradient times its cotangent a query
        g_all = (gt_ref[0, 0].astype(jnp.float32) * g_ref[0]) \
            .astype(gt_ref.dtype)
        dk = jnp.zeros(k.shape, jnp.float32)
        for j in range(heads):
            q = iq_ref[0, j]
            s = jax.lax.dot_general(k, q, (((1,), (1,)), ((), ())),
                                    preferred_element_type=jnp.float32)
            g = jnp.where(s > 0, g_all, jnp.zeros_like(g_all))
            u_ref[0, j] += jax.lax.dot_general(
                g, k, (((0,), (0,)), ((), ())),
                preferred_element_type=jnp.float32)
            dk = dk + jax.lax.dot(g, qw_ref[0, j],
                                  preferred_element_type=jnp.float32)
        dk_ref[0, 0] = dk


def _index_scores_bwd_call(iq, qw, ik, gt, g, tile, interpret):
    """(u [B, J, S, Di] float32: sum_s G[t, s] 1[iq . ik > 0] ik[s], without
    the weight; dik partials [B, S / tile, S, Di] float32, a query block's
    share of every key block at or under it, the rest never written) of G =
    ``gt`` (the causal tiles, ``_kl_call``'s) x ``g`` [B, 1, S] float32 a
    query, rounded to ``gt``'s dtype."""
    B, J, S, Di = iq.shape
    n = S // tile
    q_spec = pl.BlockSpec((1, J, tile, Di), lambda b, i, c: (b, 0, i, 0))
    call = pl.pallas_call(
        functools.partial(_indexer_bwd_kernel, heads=J),
        grid=(B, n, n),
        in_specs=[
            q_spec, q_spec,
            pl.BlockSpec((1, tile, Di),
                         lambda b, i, c: (b, jnp.minimum(c, i), 0)),
            pl.BlockSpec((1, 1, tile, tile),
                         lambda b, i, c: (b, packed_tile(i, c), 0, 0)),
            pl.BlockSpec((1, 1, tile), lambda b, i, c: (b, 0, i))],
        out_specs=[
            q_spec,
            pl.BlockSpec((1, 1, tile, Di),
                         lambda b, i, c: (b, i, jnp.minimum(c, i), 0))],
        out_shape=[jax.ShapeDtypeStruct((B, J, S, Di), jnp.float32),
                   jax.ShapeDtypeStruct((B, n, S, Di), jnp.float32)],
        interpret=interpret,
        compiler_params=_params(interpret, _KL_VMEM_BYTES,
                                ("parallel", "arbitrary", "arbitrary")))
    with annotate("dsa_indexer_bwd"):
        return call(iq, qw, ik, gt, g)


# ------------------------------------------------------------ selection

def _select_kernel(it_ref, mt_ref, lse_ref, n_ref, key_ref, *, topk, lanes,
                   rows, bits):
    S = it_ref.shape[1]
    q0 = pl.program_id(1) * lanes
    chunks = (q0 + lanes + rows - 1) // rows      # key chunks under the diagonal
    query = q0 + jax.lax.broadcasted_iota(jnp.int32, (1, lanes), 1)
    want = jnp.minimum(query + 1, topk)           # keys a query keeps

    def keys_of(c):
        return c * rows + jax.lax.broadcasted_iota(jnp.int32, (rows, lanes), 0)

    def at(c):
        return pl.ds(pl.multiple_of(c * rows, rows), rows)

    def fill(c, _):
        image = _sortable(it_ref[0, at(c), :])
        key_ref[at(c), :] = jnp.where(keys_of(c) <= query, image, INT_MIN)
        return 0
    jax.lax.fori_loop(0, chunks, fill, 0)

    def count(pred):
        def body(c, acc):
            return acc + jnp.sum(pred(key_ref[at(c), :], keys_of(c))
                                 .astype(jnp.int32), axis=0, keepdims=True)
        return jax.lax.fori_loop(0, chunks, body,
                                 jnp.zeros((1, lanes), jnp.int32))

    # the want-th largest image, bit by bit from the top: the images are
    # signed, the bits are built on their unsigned order (x ^ INT_MIN)
    def bit_of_threshold(i, tau):
        cand = tau | (jnp.int32(1) << (31 - i))
        enough = count(lambda x, _: x >= (cand ^ INT_MIN)) >= want
        return jnp.where(enough, cand, tau)
    tau = jax.lax.fori_loop(0, 32, bit_of_threshold,
                            jnp.zeros((1, lanes), jnp.int32)) ^ INT_MIN
    # of the keys that tie with it, the smallest: the largest cut such that
    # fewer than ``need`` of them lie under it is the last one kept
    need = want - count(lambda x, _: x > tau)

    def bit_of_cut(i, cut):
        cand = cut | (jnp.int32(1) << (bits - 1 - i))
        few = count(lambda x, s: (x == tau) & (s < cand)) < need
        return jnp.where(few, cand, cut)
    cut = jax.lax.fori_loop(0, bits, bit_of_cut,
                            jnp.zeros((1, lanes), jnp.int32))

    def chosen(c):
        x, s = key_ref[at(c), :], keys_of(c)
        return (s <= query) & ((x > tau) | ((x == tau) & (s <= cut)))

    def top(c, m):
        return jnp.maximum(m, jnp.max(
            jnp.where(chosen(c), it_ref[0, at(c), :], -jnp.inf), axis=0,
            keepdims=True))
    m = jax.lax.fori_loop(0, chunks, top,
                          jnp.full((1, lanes), -jnp.inf, jnp.float32))

    def leave(c, carry):
        total, n = carry
        keep = chosen(c)
        mt_ref[0, at(c), :] = keep.astype(jnp.int32).astype(jnp.int8)
        e = jnp.where(keep, jnp.exp(it_ref[0, at(c), :] - m), 0.0)
        return (total + jnp.sum(e, axis=0, keepdims=True),
                n + jnp.sum(keep.astype(jnp.int32), axis=0, keepdims=True))
    total, n = jax.lax.fori_loop(
        0, chunks, leave, (jnp.zeros((1, lanes), jnp.float32),
                           jnp.zeros((1, lanes), jnp.int32)))

    def clear(c, _):
        mt_ref[0, at(c), :] = jnp.zeros((rows, lanes), jnp.int8)
        return 0
    jax.lax.fori_loop(chunks, S // rows, clear, 0)
    lse_ref[0] = m + jnp.log(total)
    n_ref[0] = n


def _select_call(it, topk, interpret):
    """(MT int8 [B, S, S], log-sum-exp of a query's kept scores [B, 1, S]
    float32, kept keys a query [B, 1, S] int32) of IT [B, S_k, S_q]."""
    B, S, _ = it.shape
    lanes = 128
    rows = next(r for r in (2048, 1024, 512, 256, 128) if S % r == 0)
    row = pl.BlockSpec((1, 1, lanes), lambda b, i: (b, 0, i))
    col = pl.BlockSpec((1, S, lanes), lambda b, i: (b, 0, i))
    call = pl.pallas_call(
        functools.partial(_select_kernel, topk=topk, lanes=lanes, rows=rows,
                          bits=max(1, (S - 1).bit_length())),
        grid=(B, S // lanes),
        in_specs=[col], out_specs=[col, row, row],
        out_shape=[jax.ShapeDtypeStruct((B, S, S), jnp.int8),
                   jax.ShapeDtypeStruct((B, 1, S), jnp.float32),
                   jax.ShapeDtypeStruct((B, 1, S), jnp.int32)],
        scratch_shapes=[pltpu.VMEM((S, lanes), jnp.int32)],
        interpret=interpret,
        compiler_params=_params(interpret, _SELECT_VMEM_BYTES,
                                ("parallel", "arbitrary")))
    with annotate("dsa_select"):
        return call(it)


def pack_mask(mt):
    """MT int8 [B, S_k, S_q] of 0 / 1 -> its bits, uint8 [B, S_k / 8, S_q]:
    key 8 r + i is bit i of row r."""
    B, S, Sq = mt.shape
    bits = mt.reshape(B, S // 8, 8, Sq).astype(jnp.uint8) \
        << jnp.arange(8, dtype=jnp.uint8)[None, None, :, None]
    return jnp.sum(bits, axis=2, dtype=jnp.uint8)


def unpack_mask(packed):
    B, R, Sq = packed.shape
    bits = (packed[:, :, None, :]
            >> jnp.arange(8, dtype=jnp.uint8)[None, None, :, None]) & 1
    return bits.reshape(B, R * 8, Sq).astype(jnp.int8)


def pin_selection(mt, lse_i, count):
    """The selection as a rematted block keeps it, under ``SELECTION_NAME``:
    the mask as bits, the kept scores' log-sum-exp and their count; handed
    back as the kernels take them, the bits last. With the name kept the
    recomputed forward reads these and its own selection is dead code."""
    with annotate("dsa_select_pin"):
        packed = checkpoint_name(pack_mask(mt), SELECTION_NAME)
        return (unpack_mask(packed), checkpoint_name(lse_i, SELECTION_NAME),
                checkpoint_name(count, SELECTION_NAME), packed)


# ------------------------------------------------------ masked attention

def _masked_fwd_kernel(i_of, c_of, q_ref, k_ref, v_ref, m_ref, o_ref,
                       lse_ref, mx_ref, l_ref, *, scale, block, chunk,
                       n_chunks):
    t = pl.program_id(1)
    qi, kc = i_of[t], c_of[t]
    first, last = fa._walk_ends(qi, block, chunk, n_chunks, True)
    cb = chunk // block
    fold = fa._scale_folds(scale)
    s_scale = None if fold else scale
    q = q_ref[0] * scale if fold else q_ref[0]

    def tile(j, masked):
        rows = pl.ds(pl.multiple_of(j * block, block), block)
        return (k_ref[0, rows, :], v_ref[0, rows, :],
                m_ref[0, :, rows].astype(jnp.int32) != 0)

    hi = jnp.clip(qi + 1 - kc * cb, 0, cb)
    fa._fwd_walk(q, tile, [(0, hi, True)], o_ref.at[0], mx_ref, l_ref,
                 s_scale, kc == first)
    fa._finish_chunked_fwd(o_ref, lse_ref, mx_ref, l_ref, kc == last)


def _plan(S, D, itemsize, interpret):
    block = _tile(interpret)
    chunk = fa._pick_chunk(S, D, D, itemsize, block, block) or block
    return block, chunk


def _masked_fwd(q, k, v, mask, scale, block, chunk, interpret, heads,
                kv_heads):
    """q [BH, S, D], k and v [B Hkv, S, D], mask int8 [B, S_q, S_k]."""
    BH, S, D = q.shape
    kv = fa._kv_row(heads, kv_heads)
    out_specs, out_shape, scratch = fa._chunked_fwd_outputs(
        q, block, block, fa._of_block)
    call = fa._pair_call(
        functools.partial(_masked_fwd_kernel, scale=scale, block=block,
                          chunk=chunk, n_chunks=S // chunk),
        fa._pair_walk(S, block, chunk, True, False), BH,
        [fa._rows_spec(block, D, fa._of_block),
         fa._rows_spec(chunk, D, fa._of_chunk, kv),
         fa._rows_spec(chunk, D, fa._of_chunk, kv),
         pl.BlockSpec((1, block, chunk),
                      lambda b, t, i_of, c_of: (b // heads, i_of[t], c_of[t]))],
        out_specs, out_shape, scratch, interpret)
    with annotate("dsa_fwd"):
        o32, lse = call(q, k, v, mask)
    return o32.astype(q.dtype), lse


def _masked_bwd_kernel(i_of, c_of, q_ref, k_ref, v_ref, do_ref, lse_ref,
                       delta_ref, mt_ref, dq_ref, dk_ref, dv_ref, dk_acc,
                       dv_acc, *, scale, block, chunk):
    """``flash_attention._bwd_kernel_chunked`` under the mask's tile (held
    transposed, [key, query], as the score tile is)."""
    t = pl.program_id(1)
    steps = pl.num_programs(1)
    qi, kc = i_of[t], c_of[t]
    first = jnp.logical_or(t == 0, c_of[jnp.maximum(t - 1, 0)] != kc)
    last = jnp.logical_or(t == steps - 1,
                          c_of[jnp.minimum(t + 1, steps - 1)] != kc)
    cb = chunk // block
    fold = fa._scale_folds(scale)
    s_scale = None if fold else scale
    q = q_ref[0] * scale if fold else q_ref[0]
    do = do_ref[0]
    lse = fa._stat_row(lse_ref, (0,), 0, block)
    delta = fa._stat_row(delta_ref, (0,), 0, block)

    @pl.when(first)
    def _init():
        dk_acc[...] = jnp.zeros_like(dk_acc)
        dv_acc[...] = jnp.zeros_like(dv_acc)

    def body(j, dq):
        rows = pl.ds(pl.multiple_of(j * block, block), block)
        k = k_ref[0, rows, :]
        mask = mt_ref[0, rows, :].astype(jnp.int32) != 0
        p, ds = fa._bwd_ds_block(k, v_ref[0, rows, :], lse, delta, q, do,
                                 mask, s_scale)
        dv_acc[rows, :] += jax.lax.dot(p, do,
                                       preferred_element_type=jnp.float32)
        dk_acc[rows, :] += jax.lax.dot(ds, q,
                                       preferred_element_type=jnp.float32)
        return dq + jax.lax.dot_general(ds, k, (((0,), (0,)), ((), ())),
                                        preferred_element_type=jnp.float32)

    hi = jnp.clip(qi + 1 - kc * cb, 0, cb)
    dq_ref[0, 0] = jax.lax.fori_loop(0, hi, body,
                                     jnp.zeros(q.shape, jnp.float32))

    @pl.when(last)
    def _leave():
        dk = dk_acc[...] if fold else dk_acc[...] * scale
        dk_ref[0] = dk.astype(dk_ref.dtype)
        dv_ref[0] = dv_acc[...].astype(dv_ref.dtype)


def _masked_bwd(q, k, v, o, lse, do, mt, scale, block, chunk, interpret,
                heads, kv_heads):
    BH, S, D = q.shape
    kv = fa._kv_row(heads, kv_heads)
    delta = jnp.sum(do.astype(jnp.float32) * o.astype(jnp.float32),
                    axis=-1).reshape(lse.shape)
    piece = lse.shape[-1]
    walk = fa._pair_walk(S, block, chunk, True, True)
    call = fa._pair_call(
        functools.partial(_masked_bwd_kernel, scale=scale, block=block,
                          chunk=chunk),
        walk, BH,
        [fa._rows_spec(block, D, fa._of_block),
         fa._rows_spec(chunk, D, fa._of_chunk, kv),
         fa._rows_spec(chunk, D, fa._of_chunk, kv),
         fa._rows_spec(block, D, fa._of_block)]
        + [fa._stat_spec(block, piece, fa._of_block)] * 2
        + [pl.BlockSpec((1, chunk, block),
                        lambda b, t, i_of, c_of: (b // heads, c_of[t],
                                                  i_of[t]))],
        [pl.BlockSpec((1, 1, block, D), lambda b, t, *_: (b, t, 0, 0)),
         fa._rows_spec(chunk, D, fa._of_chunk),
         fa._rows_spec(chunk, D, fa._of_chunk)],
        [jax.ShapeDtypeStruct((BH, len(walk[0]), block, D), jnp.float32),
         jax.ShapeDtypeStruct((BH, S, D), k.dtype),
         jax.ShapeDtypeStruct((BH, S, D), v.dtype)],
        [pltpu.VMEM((chunk, D), jnp.float32)] * 2,
        interpret, fa._BWD_VMEM_BYTES)
    with annotate("dsa_bwd"):
        dq, dk, dv = call(q, k, v, do, lse, delta, mt)
    with annotate("dsa_bwd_dq_sum"):
        dq = fa._sum_dq_slabs(dq, walk, S, chunk, scale, q.dtype)
    return dq, dk, dv


@functools.partial(jax.custom_vjp, nondiff_argnums=(4, 5, 6, 7, 8, 9))
def _masked_attention(q, k, v, mt, scale, block, chunk, interpret, heads,
                      kv_heads):
    """(o [BH, S, D], lse) under MT int8 [B, S_k, S_q]."""
    return _masked_fwd(q, k, v, jnp.swapaxes(mt, 1, 2), scale, block, chunk,
                       interpret, heads, kv_heads)


def _masked_attention_fwd(q, k, v, mt, scale, block, chunk, interpret, heads,
                          kv_heads):
    o, lse = fa._name_residuals(*_masked_fwd(
        q, k, v, jnp.swapaxes(mt, 1, 2), scale, block, chunk, interpret,
        heads, kv_heads))
    return (o, lse), (q, k, v, o, lse, mt)


def _masked_attention_bwd(scale, block, chunk, interpret, heads, kv_heads,
                          residuals, cotangents):
    q, k, v, o, lse, mt = residuals
    do, _ = cotangents          # the log-sum-exp is read under stop_gradient
    fa._named["closed"] = True
    dq, dk, dv = _masked_bwd(q, k, v, o, lse, do, mt, scale, block, chunk,
                             interpret, heads, kv_heads)
    if heads != kv_heads:
        def sum_group(t):
            return t.reshape(-1, kv_heads, heads // kv_heads, *t.shape[1:]) \
                .sum(axis=2).astype(t.dtype).reshape(-1, *t.shape[1:])
        dk, dv = sum_group(dk), sum_group(dv)
    return dq, dk, dv, None


_masked_attention.defvjp(_masked_attention_fwd, _masked_attention_bwd)


# ------------------------------------------------------- the indexer's KL

def _kl_kernel(q_ref, k_ref, lse_ref, mt_ref, it_ref, lsei_ref, kl_ref,
               gt_ref, acc_ref, *, scale, heads, kv_heads, tile):
    qi, ki = pl.program_id(1), pl.program_id(2)
    rep = heads // kv_heads

    @pl.when(ki == 0)
    def _init():
        kl_ref[...] = jnp.zeros_like(kl_ref)

    @pl.when(ki <= qi)
    def _tile_kl():
        acc_ref[...] = jnp.zeros_like(acc_ref)

        def one_head(h, _):
            s = jax.lax.dot_general(
                k_ref[0, h // rep], q_ref[0, h], (((1,), (1,)), ((), ())),
                preferred_element_type=jnp.float32) * scale
            acc_ref[...] += jnp.exp(s - fa._stat_row(lse_ref, (0, h), 0,
                                                      tile))
            return 0
        jax.lax.fori_loop(0, heads, one_head, 0)
        keep = mt_ref[0].astype(jnp.int32) != 0
        p = jnp.where(keep, acc_ref[...] * (1.0 / heads), 0.0)
        logq = it_ref[0] - lsei_ref[0]
        seen = keep & (p > 0)
        term = jnp.where(seen, p * (jnp.log(jnp.where(seen, p, 1.0)) - logq),
                         0.0)
        kl_ref[0] += jnp.sum(term, axis=0, keepdims=True)
        gt_ref[0, 0] = jnp.where(keep, jnp.exp(logq) - p, 0.0) \
            .astype(gt_ref.dtype)


def _kl_call(it, lse_i, q, k, lse, mt, scale, tile, interpret, gt_dtype):
    """(kl [B, 1, S] float32, GT [B, n (n + 1) / 2, tile, tile] of n = S /
    tile: softmax_S(I) - p on the selected pairs, 0 on the others, the
    [key, query] tiles at or under the diagonal alone, at ``packed_tile``)."""
    B, H, S, D = q.shape
    Hkv = k.shape[1]
    n = S // tile
    piece = lse.shape[-1]
    lse = lse.reshape(B, H, S // piece, 1, piece)
    tile_spec = pl.BlockSpec((1, tile, tile),
                             lambda b, i, c: (b, jnp.minimum(c, i), i))
    row = pl.BlockSpec((1, 1, tile), lambda b, i, c: (b, 0, i))
    call = pl.pallas_call(
        functools.partial(_kl_kernel, scale=scale, heads=H, kv_heads=Hkv,
                          tile=tile),
        grid=(B, n, n),
        in_specs=[
            pl.BlockSpec((1, H, tile, D), lambda b, i, c: (b, 0, i, 0)),
            pl.BlockSpec((1, Hkv, tile, D),
                         lambda b, i, c: (b, 0, jnp.minimum(c, i), 0)),
            pl.BlockSpec((1, H, tile // piece, 1, piece),
                         lambda b, i, c: (b, 0, i, 0, 0)),
            tile_spec, tile_spec, row],
        out_specs=[row, pl.BlockSpec(
            (1, 1, tile, tile), lambda b, i, c: (b, packed_tile(i, c), 0, 0))],
        out_shape=[jax.ShapeDtypeStruct((B, 1, S), jnp.float32),
                   jax.ShapeDtypeStruct((B, n * (n + 1) // 2, tile, tile),
                                        gt_dtype)],
        scratch_shapes=[pltpu.VMEM((tile, tile), jnp.float32)],
        interpret=interpret,
        compiler_params=_params(interpret, _KL_VMEM_BYTES,
                                ("parallel", "arbitrary", "arbitrary")))
    with annotate("dsa_kl"):
        return call(q, k, lse, mt, it, lse_i)


@functools.partial(jax.custom_vjp, nondiff_argnums=(9, 10, 11))
def _index_kl(iq, ik, iw, it, lse_i, q, k, lse, mt, scale, tile, interpret):
    """kl [B, 1, S] of IT = ``_index_scores(iq, ik, iw)``, the caller's. ONE
    rule from d kl to the indexer's three operands, which is all it reaches:
    the forward rule leaves GT under ``KL_GRAD_NAME``, the backward rule
    hands it with the cotangent to ``dsa_indexer_bwd``."""
    return _kl_call(it, lse_i, q, k, lse, mt, scale, tile, interpret,
                    iq.dtype)[0]


def _index_kl_fwd(iq, ik, iw, it, lse_i, q, k, lse, mt, scale, tile,
                  interpret):
    kl, gt = _kl_call(it, lse_i, q, k, lse, mt, scale, tile, interpret,
                      iq.dtype)
    return kl, (iq, ik, iw, checkpoint_name(gt, KL_GRAD_NAME))


def _index_kl_bwd(scale, tile, interpret, residuals, g):
    iq, ik, iw, gt = residuals
    S, n = iq.shape[2], iq.shape[2] // tile
    w = jnp.swapaxes(iw, 1, 2)[..., None]               # [B, J, S, 1]
    qw = (iq.astype(jnp.float32) * w).astype(iq.dtype)
    u, parts = _index_scores_bwd_call(iq, qw, ik, gt, g, tile, interpret)
    with annotate("dsa_indexer_bwd_sum"):
        diq = (u * w).astype(iq.dtype)
        diw = jnp.swapaxes(jnp.sum(u * iq.astype(jnp.float32), axis=-1), 1, 2)
        # a query block's partial counts for the key blocks at or under it
        held = np.tril(np.ones((n, n), bool))[None, :, :, None, None]
        parts = parts.reshape(parts.shape[0], n, n, tile, -1)
        dik = jnp.sum(jnp.where(held, parts, 0.0), axis=1) \
            .reshape(-1, S, ik.shape[-1]).astype(ik.dtype)
    return (diq, dik, diw.astype(iw.dtype)) + (None,) * 6


_index_kl.defvjp(_index_kl_fwd, _index_kl_bwd)


# ------------------------------------------------------------ the entry

def learned_sparse_attention(q, k, v, index_q, index_k, index_w, topk,
                             scale=None, interpret=None):
    """(o [B, H, S, D], kl [B, S] float32, kept keys a query [B, S] int32,
    the kept set's bits uint8 [B, ceil(S / 8), S] by key and query):
    q [B, H, S, D], k and v [B, Hkv, S, D]; the indexer's index_q [B, J, S,
    Di], index_k [B, S, Di] and index_w [B, S, J] (float32, every scale
    folded in). The caller stops the gradient where the model says so: the
    kernels hand d kl to the indexer's three operands alone and d o to q, k
    and v alone."""
    B, H, S, D = q.shape
    Hkv = k.shape[1]
    if interpret is None:
        interpret = fa._interpret_default()
    scale = float(scale) if scale is not None else 1.0 / math.sqrt(D)
    tile = _tile(interpret)
    q, k, v, index_q = (_pad_rows(x, 2, tile) for x in (q, k, v, index_q))
    index_k, index_w = (_pad_rows(x, 1, tile) for x in (index_k, index_w))
    Sp = q.shape[2]
    block, chunk = _plan(Sp, D, jnp.dtype(q.dtype).itemsize, interpret)
    default_registry().gauge("attention/dsa_tile_overcompute").set(
        tile_overcompute(S, int(topk), tile))
    index_w = index_w.astype(jnp.float32)
    it = _index_scores(*(jax.lax.stop_gradient(x)
                         for x in (index_q, index_k, index_w)), tile,
                       bool(interpret))
    mt, lse_i, count, bits = pin_selection(*_select_call(
        it, int(topk), bool(interpret)))
    o, lse = _masked_attention(
        q.reshape(B * H, Sp, D), k.reshape(B * Hkv, Sp, D),
        v.reshape(B * Hkv, Sp, D), mt, scale, block, chunk, bool(interpret),
        H, Hkv)
    kl = _index_kl(index_q, index_k, index_w, it, lse_i,
                   jax.lax.stop_gradient(q), jax.lax.stop_gradient(k),
                   jax.lax.stop_gradient(lse), mt, scale, tile,
                   bool(interpret))
    return (o.reshape(B, H, Sp, D)[:, :, :S], kl[:, 0, :S], count[:, 0, :S],
            bits[:, :-(-S // 8), :S])
