"""Flash attention — the TPU replacement for the reference's fused attention
CUDA path (csrc/transformer/softmax_kernels.cu + the score/context matmuls in
ds_transformer_cuda.cpp): one Pallas kernel per pass that never materializes
the [S, S] score matrix in HBM, with online softmax and a recompute-based
backward (custom VJP), accumulating in fp32 on the MXU.

Two entries, one per operand layout. `flash_attention` takes head-major
q/k/v [B, H, S, D] (kernels on a [B*H] × block grid) and reaches every kernel
family, grouped-query K/V included. `flash_attention_bse` takes the model's
own [B, S, H*D] arrays — q, k, v apart or one fused projection [B, S, 3*H*D]
read in place — and runs the whole-row kernels on 128-lane COLUMN blocks of
them, 128 // D heads a block (grid [B] × column blocks × blocks): no
head-major copy of q, k, v, o, dq, dk or dv is ever built, and at head_dim 64
no operand is padded from 64 to 128 lanes in HBM (XLA built 20 such copies a
GPT-2 layer round the head-major kernels: PERF.md, PR 30). A head of a column
block takes the same tiles with the other heads' lanes zeroed, so every
product keeps its MXU cost (a contraction over 128 lanes where head-major
contracts over 64 of a 128-deep array; 128 output columns where 64 of 128
were idle); shapes it does not take (long rows, grouped-query, head widths
that do not tile 128 lanes) it transposes into `flash_attention`.

Three kernel families share the same per-tile math (`_fwd_block_step` /
`_bwd_ds_block`):

- **plain** ("whole-row"): K/V (fwd) or Q/dO (bwd) rows for one
  (batch, head) live whole in VMEM — fastest, used while S·D·itemsize fits
  `_UNCHUNKED_ROW_BYTES` (S=2048 at D=64, S=1024 at D=128 in bf16). The
  grid block is up to 1024 rows; the causal structure is finer than that INSIDE
  the block: its diagonal region goes in sub-blocks of a strip's rows, each
  up to its own diagonal square (`tile_overcompute`: 1.25 x the needed
  scores at S 1024, where whole diagonal blocks were 1.50 x), and the
  tiles wholly off the diagonal run unmasked at full block width. The
  softmax state and the backward's dq/dk/dv accumulators are fp32 VMEM
  scratch; lse and delta travel lane-dense; dq leaves as the input dtype.
- **chunked**: the grid is (B*H, PAIRS) — its second dimension walks a
  list of the (query block, key chunk) pairs that hold work, built with
  numpy at trace time (``_pair_walk``: two int32 arrays, scalar-prefetch
  operands that every index map reads its block and chunk from). The
  FORWARD walks them a block's pairs consecutive and its chunks ascending,
  so each step streams one CHUNK of K and V and accumulates into a revisited
  fp32 output block. The BACKWARD is ONE kernel too (``_bwd_kernel_chunked``,
  PR 49; a dq and a dkv kernel before it, which computed every score tile —
  two of seven products and the exp chain — twice): it walks the same pairs
  a CHUNK's together, the chunk's K and V resident and its dk and dv in
  fp32 VMEM scratch while the query blocks that see it stream by, computes
  each tile once — five products, held [k, q] as the whole-row kernel holds
  it — and writes dk and dv once a chunk, in the operands' dtype. dq, which
  accumulates ACROSS chunks (a whole fp32 row is 8 MiB a head at S 16,384),
  leaves as fp32 partials, one [block_q, D] block a pair — a slab a chunk —
  and one XLA pass adds a block's partials, scales and casts
  (``_sum_dq_slabs``; scopes ``flash_bwd_chunk`` / ``flash_bwd_dq_sum``,
  gauges ``attention/flash_bwd_products_per_tile`` and
  ``attention/flash_bwd_dq_slabs``); a plan of one chunk has nothing to add.
  Under a causal mask the list leaves out the pairs wholly above the
  diagonal, which on a rectangular (S / block, S / chunk) grid were steps
  with empty loops that still fetched their chunk, 0.64-1.5 us each on a
  v5e (PERF.md Findings PR 39; gauge
  ``attention/flash_grid_steps_walked_share``); a call that is not
  causal walks the rectangle, and a band would be a third list for the
  same kernels. A step that DOES work has a fixed cost too (q re-scaled,
  the pipeline's turn-over; until PR 67 also the (o, m, l) carry's round
  trip and a relative-position tile rebuilt), so a chunk is as many rows as
  ``_CHUNK_BYTES`` of K + V allow (``_pick_chunk``; gauge
  ``attention/flash_chunk_rows``): 80 pairs a head of 32 x 4 at S 16,384
  with blocks of 512 and chunks of 4,096 (272 of 32 x 16 at the 1,024 rows
  they had before PR 48), 40 of 16 x 4 at S 8,192 / head_dim 256 / 2,048
  (136), 8 at S 4,096 / one chunk (20). The forward's softmax m/l state
  lives in fp32 VMEM scratch and o in its output block (``_fwd_walk``: no
  loop carries them); it is normalized in-kernel on a block's last chunk, which
  also writes lse lane-dense ([BH, S / 128, 1, 128] as the whole-row
  kernels store it — a [BH, S, 1] column is 128 x its
  values' size in HBM, which kept a rematted block from holding it:
  PERF.md, PR 34; the backward kernels hold the score tile [k, q], where a
  block's rows of lse and delta are rows as stored, ``_stat_row``). This is how
  single-chip attention training reaches 32k context;
  beyond that, sequence parallelism shards S first
  (deepspeed_tpu/parallel/ring_attention.py). Grouped-query K/V
  ([B, Hkv, S, D], Hkv < H) go into both chunked kernels AS THEY ARE
  since PR 31: the K/V index maps fold a query head onto its group's row
  (`_kv_row`), so K and V are never repeated in HBM, forward or backward;
  dk and dv still leave the backward kernel per QUERY head and are
  summed over a group's heads after it. Measured on a v5e at
  (S 4096, head_dim 128, 16 / 16 heads: OLMoE's cell) and at
  (S 8192, head_dim 256, 16 query / 2 KV heads: Qwen3-Next's cell); PERF.md
  Findings PR 27, PR 31 and PR 48 have the numbers. Since PR 47 the chunked
  family — and it ALONE — takes a q·k width that is not the value width
  (latent attention: q and k [.., S, 192] = 128 + the 64 rotated, v
  [.., S, 128]): the score contracts over q's width, o, do and dv are as
  wide as v, dq and dk as wide as q, and V is never padded to the score's
  width in HBM. ``flash_attention`` sends such a call here at EVERY S
  (its chunks under the one budget, ``_pick_chunk``) and raises, with the
  shapes, where no block tiles S; the whole-row, the column-block and the
  window kernels refuse unequal widths by name
  (``_refuse_unequal_widths``). For equal widths every call is what it was.

- **window** (a causal band of ``window`` keys, ``flash_attention(...,
  window=W)`` with W < S; PR 33): grid (B*H, S / block, steps) forward,
  where a grid block's whole BAND is one operand block (PR 43) — the
  ``round_up(block + W - 1, block)`` rows of K and V that END at a query
  block's last row, read at an element offset (``pl.Element``: a block
  index times the block, so the compiler sees it lie on a tile's edge)
  clamped at the sequence's start — so ``steps`` is 1 and the band's tiles
  are walked by the loops inside the step, where one aligned chunk a step
  cost 2.1-3.1 us for a 512 x 512 x 128 tile (PERF.md Findings PR 43):
  4,608 rows at W 4,096, 1,024 at W 512. A band whose rows pass
  ``_BAND_BYTES`` (or the caller's ``chunk=`` cap) goes in the FEWEST equal
  steps that fit (``_band_plan``), through the raw (o, m, l) state of the
  chunked family; neither compute nor DMA is spent outside the band.
  Blocks wholly inside the band run unmasked; the edge blocks take the
  causal and the lower-bound compare (``_band_mask``). The BACKWARD is ONE
  kernel (``_swa_bwd_kernel``, PR 53; a dq and a dkv kernel before it, which
  computed every score tile twice): grid (B * kv_heads, S / block_q + lag,
  head groups x steps). A step holds the KV head's band ONCE and loops its
  group's query heads' q and dO blocks against it (all 7 or 8 of the cells'
  groups; fewer where their blocks pass ``_BAND_BYTES``), each tile held
  [k, q] and computed once — five products — for dq, dk and dv. dq of the
  step's heads is whole when the band's walk ends and leaves scaled, in the
  operands' dtype; dk and dv of the KV head accumulate over the query blocks
  that see a key and over the group's heads in a float32 VMEM RING of the
  band's rows (``_band_ring``), from which key block ``e`` leaves once, in
  the operands' dtype, on step ``e + lag`` (``lag = ceil((W - 1) /
  block_q)``: every query that sees it is done) — so the grid runs ``lag``
  steps past the last query block, which compute nothing — and no
  per-query-head dk / dv, no float32 gradient and no per-tile partial
  reaches HBM. Grouped-query K/V are read in place. Scopes ``swa_fwd`` /
  ``swa_bwd``, gauges ``attention/window_tile_overcompute``,
  ``attention/window_tiles_per_grid_step`` and
  ``attention/window_bwd_tiles_per_grid_step``. A shape the family does not
  take raises. Measured on a v5e at (S 16,384, head_dim 128, 64 / 8 heads,
  W 512: Laguna's cell; 28 / 4 heads, W 4,096: SmallThinker's): PERF.md
  Findings PR 33, PR 43 and PR 53.

What a score tile costs beside its two (five, backward) MXU products is
what these kernels are written around (per 512 x 512 tile at D=64 the
compiler's own schedule had 1,425 bundles forward for 925 of MXU, the
single vector-store slot the fullest at 1,019, nearly all of it spills of
256-vreg tiles; PERF.md, PR 28):

- the products take every row of a tile at once, the chain between them
  (max, subtract, exp, sum, cast) goes `_CHAIN_ROWS` rows at a time, so a
  chain's tile fits the 64-vreg file instead of passing through VMEM once
  per op;
- row statistics stay replicated across the 128 lanes (`_LANES`): a lane
  broadcast is a trip through the XLU, which at one max and one sum per
  8 rows per tile is already the second-fullest unit; the row SUM is kept
  as per-lane partial sums on the VPU and crosses lanes once per row;
- the scale goes onto the q rows where it is a power of two (head_dim 64,
  256: bit-identical scores; `_scale_folds`) and stays on the fp32 scores
  elsewhere (head_dim 128);
- a causal mask is one compare against a relative-position tile built
  once per grid step, on the diagonal squares alone.

On non-TPU backends the kernels run in interpreter mode so unit tests check
the same code path numerically against the jnp reference (the
test_cuda_forward.py methodology, SURVEY §4).
"""

import functools
import math

import numpy as np
import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu

from deepspeed_tpu.telemetry.registry import default_registry
from deepspeed_tpu.telemetry.spans import annotate
from deepspeed_tpu.utils.logging import logger

NEG_INF = -1e30

# measured scoped-VMEM ceiling for whole-row residency on v5e. The fused
# backward keeps a fp32 [S, D] dq row resident (VMEM scratch since PR 28,
# a fp32 output block before; the bf16 output block beside it is half
# what that was), which moved the ceiling DOWN: bf16 S=4096, D=64 compiled
# in a small harness but the same shapes inside a larger program
# (a dense S=4096 train step, BH=64) overflowed scoped vmem by 284 KB
# — so the unchunked cutoff is S*D*itemsize <= 256 KB (S=2048 at D=64
# bf16) and S=4096 routes to the chunked kernels, whose per-chunk
# residency is bounded (``_CHUNK_BYTES``).
_UNCHUNKED_ROW_BYTES = 262144
# what a grid step of the CHUNKED kernels may hold of a sequence chunk: the
# lane-padded bytes of its K + V (streamed a step forward, the pipeline
# double-buffering them; resident over the chunk's run of steps backward).
# ONE budget for equal and unequal q·k / value widths, measured on a v5e at
# blocks of 512, bf16 causal, in the kernels of PR 48 — forward + dq + dkv a
# call (tests/perf/flash_chunked_bench.py --plans, mla_flash_bench.py;
# PERF.md Findings PR 48 and PR 47) — a grid step's fixed cost, not its tile,
# is what these kernels pay:
#   [48 / 8, 16384, 128]   chunk 512 / 1,024 / 2,048 / 4,096 (528 / 272 / 144
#                          / 80 steps a head): 175.1 / 140.6 / 123.3 / 115.2 ms
#   [2 x 16 / 2, 8192, 256]  512 / 1,024 / 2,048 (136 / 72 / 40): 51.5 / 42.5
#                          / 38.0 ms; 4,096 (4 MiB of K + V) is REFUSED: the
#                          dkv kernel runs out of scoped VMEM
#   [4 x 16, 4096, 128]    512 / 1,024 / 2,048 / 4,096 = S (36 / 20 / 12 / 8):
#                          16.2 / 13.4 / 12.0 / 11.3 ms
#   [16, 32768, 64]        1,024 / 2,048 / 4,096: 179.1 / 156.1 / 145.2 ms
#                          (the shape whose overflow at 4,096 rows set PR 27's
#                          budget, in kernels PR 28 / 34 / 39 rewrote since)
#   [32, 16384, 192 / 128] 512 / 1,024 / 2,048 / 4,096: 162.8 / 131.7 / 116.1
#                          / 109.2 ms (3 MiB: the widest step that runs)
# 3 MiB is the widest a step has run with; every cell's whole step compiles
# with the plan it gives (tests/test_tpu_compile.py). float32 operands take
# half the rows (their times are bf16's: the MXU takes them in bf16 passes);
# 4,096 float32 rows at head_dim 128 (4 MiB) measured 5 % under 2,048 alone
# and are left out with head_dim 256's. The single-pass backward of PR 49
# takes the forward's chunk (one plan a call) and at it, kernel + the XLA
# passes round it, where dq + dkv + theirs stood (PERF.md Findings PR 49):
#   [48 / 8, 16384, 128] 53.2 for 86.6 ms; [2 x 16 / 2, 8192, 256] 19.5 for
#   30.0; [4 x 16, 4096, 128] 5.0 for 9.1; [16, 32768, 64] 69.7 for 108.9;
#   [32, 16384, 192 / 128] 60.5 for 87.9; without a mask 97.1 for 155.7 at
#   the first shape; in float32 59.2 for 88.4 (chunk 2,048).
_CHUNK_BYTES = 3 * 2 ** 20
# rows a chunk may have, widest first: ``_pick_chunk`` takes the first that
# fits ``_CHUNK_BYTES`` and tiles the sequence in whole blocks
_CHUNK_ROWS = (4096, 2048, 1024, 512, 256, 128, 64)


def _interpret_default():
    from deepspeed_tpu.utils.platform import is_tpu_backend
    return not is_tpu_backend()


def _refuse_unequal_widths(family, q, k, v):
    """The whole-row, column-block and window families hold q, k, v and o at
    ONE head width; a q·k width that is not the value width (latent
    attention: 192 / 128) is the chunked family's alone."""
    if not q.shape[-1] == k.shape[-1] == v.shape[-1]:
        raise ValueError(
            f"the {family} flash kernels take one head width for q, k and v:"
            f" q {tuple(q.shape)}, k {tuple(k.shape)}, v {tuple(v.shape)}; "
            "unequal q·k and value widths go to the chunked kernels "
            "(flash_attention without window=)")


# ------------------------------------------------------ shared block math

# sides of a diagonal square of the whole-row kernels, widest first: the
# sub-blocks a grid block's diagonal region goes in. Measured on a v5e at
# [160, 1024, 64] (PERF.md, PR 28): 256 forward 0.433 ms against 0.489
# at 128 (a sub-block's chain is latency-bound, so fewer and wider ones
# win over the eighth of the scores 128 would save), backward 0.827 / 0.830
_STRIPS = (256, 128)


def _pick_strip(block):
    """Sub-block height for a grid block: the widest strip that splits it
    (1024 and 512 -> 256, 256 -> 128); a block none splits (128, the
    tests' 64) runs as one strip."""
    return next((s for s in _STRIPS if block % s == 0 and block > s), block)


def _scale_folds(scale):
    """True where ``scale`` is a power of two (head_dim 64, 256): q·scale
    is then exact in any float dtype and (q·scale)·kᵀ equals (q·kᵀ)·scale
    bit for bit, so the multiply moves from the score tile to the q rows.
    Elsewhere (head_dim 128: 2^-3.5) the float32 scores keep it."""
    return math.frexp(scale)[0] == 0.5


def _rel_pos(rows, cols):
    """row - col over a [rows, cols] tile, built once per grid step: a
    causal mask is then one compare, ``rel >= k_pos0 - q_pos0``."""
    return (jax.lax.broadcasted_iota(jnp.int32, (rows, cols), 0)
            - jax.lax.broadcasted_iota(jnp.int32, (rows, cols), 1))


def _block_mask(rel, masked, q_pos0, k_pos0):
    """Causal mask of the block at (q_pos0, k_pos0) from ``_rel_pos``'s
    tile; None for a block below the diagonal or a non-causal call."""
    return rel >= k_pos0 - q_pos0 if masked and rel is not None else None


def _scores(a, b, mask, scale):
    """float32 a·bᵀ; ``scale`` None where q came pre-scaled. ``mask``
    (bool) covers the bottom-right corner of the tile, whole in one
    direction: [rows, w] masks the TRAILING w columns of q·kᵀ (what comes
    before them lies wholly below the diagonal), [w, cols] the trailing w
    rows of the transposed tile k·qᵀ."""
    s = jax.lax.dot_general(a, b, (((1,), (1,)), ((), ())),
                            preferred_element_type=jnp.float32)
    if scale is not None:
        s = s * scale
    if mask is not None:
        r0, c0 = s.shape[0] - mask.shape[0], s.shape[1] - mask.shape[1]
        corner = jnp.where(mask, s[r0:, c0:], NEG_INF)
        if c0:
            corner = jnp.concatenate([s[:, :c0], corner], axis=1)
        s = jnp.concatenate([s[:r0], corner], axis=0) if r0 else corner
    return s


# rows of a score tile one softmax chain takes at a time: [128, 512] fp32
# is the 64-vreg file (64, 128 and 256 measured alike on a v5e; a whole
# 512-row tile is what spilled a store a bundle: PERF.md, PR 28)
_CHAIN_ROWS = 128
# lanes of a vreg: a row statistic (max, sum, lse, delta) lives replicated
# across them, [rows, _LANES], in VMEM scratch and in the kernels' carries
_LANES = 128


def _cat(parts, axis):
    return parts[0] if len(parts) == 1 else jnp.concatenate(parts, axis=axis)


def _chains(rows):
    """Row slices of a tile, one per softmax chain."""
    step = _CHAIN_ROWS if rows % _CHAIN_ROWS == 0 else rows
    return [slice(r, r + step) for r in range(0, rows, step)]


def _lanes(x, width):
    """[rows, _LANES] lane-replicated statistic -> [rows, width], for use
    against a tile of that width: a lane slice or a repeat of whole vregs,
    never a lane broadcast (which on the TPU is a trip through the XLU)."""
    if width == x.shape[1]:
        return x
    if width < x.shape[1]:
        return x[:, :width]
    reps, rest = divmod(width, x.shape[1])
    return _cat([x] * reps + ([x[:, :rest]] if rest else []), 1)


def _lane_sums(p):
    """[rows, w] -> [rows, _LANES] partial row sums, lane by lane: whole
    vregs added on the VPU. The one cross-lane reduction a row sum needs
    is left to whoever reads the total (``_row_total``), once a row and
    not once a tile."""
    rows, width = p.shape
    full = width // _LANES * _LANES
    parts = [p[:, c:c + _LANES] for c in range(0, full, _LANES)]
    if full < width:
        parts.append(jnp.concatenate(
            [p[:, full:], jnp.zeros((rows, _LANES - (width - full)),
                                    p.dtype)], axis=1))
    return functools.reduce(jnp.add, parts)


def _row_total(l_acc):
    """The row sums ``_lane_sums`` has been keeping, [rows, 1]."""
    return jnp.sum(l_acc, axis=1, keepdims=True)


def _stat_piece(block_q, block_k):
    """Lanes of one piece of the whole-row kernels' row statistics (lse,
    delta), stored lane-dense as [BH, S / piece, 1, piece]: a [S, 1]
    column costs a 128-lane tile per 8 values, in VMEM and in HBM alike."""
    return math.gcd(math.gcd(block_q, block_k), _LANES)


def _dense_row(x):
    """[piece, _LANES] lane-replicated statistic -> its [1, piece] row:
    the diagonal of the tile, summed down the sublanes."""
    piece = x.shape[0]
    eye = _rel_pos(piece, x.shape[1]) == 0
    return jnp.sum(jnp.where(eye, x, 0.0), axis=0, keepdims=True)[:, :piece]


def _stat_row(ref, head, row0, rows):
    """[1, rows] of a [.., S / piece, 1, piece] statistic's block, from row
    ``row0`` (a multiple of the piece) of the head at index ``head`` — (0,)
    in a head-major block, (0, h) or (h,) in a column block."""
    piece = ref.shape[-1]
    return _cat([ref[head + (row0 // piece + j,)]
                 for j in range(rows // piece)], 1)


def _stat_spec(rows, piece, index):
    """BlockSpec of ``rows`` rows of a [BH, S / piece, 1, piece] statistic:
    ``index`` maps the grid to the (row of BH, block of ``rows``) it reads
    or writes."""
    return pl.BlockSpec((1, rows // piece, 1, piece),
                        lambda *g: tuple(index(*g)) + (0, 0))


def _own_lanes(x, h, width):
    """``x`` [rows, lanes] with every lane outside head ``h``'s ``width``
    zeroed: what lets a product contract over a whole 128-lane block of
    ``[B, S, H*D]`` and still see one head. A select, not a multiply: the
    lanes past the last head of a ragged block are undefined."""
    lane = jax.lax.broadcasted_iota(jnp.int32, x.shape, 1)
    return jnp.where((lane >= h * width) & (lane < (h + 1) * width), x,
                     jnp.zeros_like(x))


def _pack_heads(pack, h, run):
    """Run head ``h``'s share of a grid step; in a column block, a head
    past the last one (an odd head count's ragged last block) is skipped."""
    g, _, heads = pack or (1, 0, 0)
    if pack and h >= (heads % g or g):
        pl.when(pl.program_id(1) * g + h < heads)(run)
    else:
        run()


def _fwd_block_step(q, k, v, carry, mask, scale):
    """One k-tile of online-softmax forward. q/k/v stay in their native
    (typically bf16) dtype so the MXU runs at full rate — fp32 dot inputs
    run the systolic array at ~1/8 throughput. All dots accumulate fp32
    (preferred_element_type); scores and softmax state are fp32.
    carry = (o_acc [r, D], m_acc [r, _LANES], l_acc [r, _LANES]), fp32:
    m replicated across the lanes, l as per-lane partial sums
    (``_lane_sums``); None for a row's first tile.

    The two products take every row at once (the k-tile is pushed into
    the MXU once); between them the softmax chain — max, subtract, exp,
    sum, cast — runs ``_CHAIN_ROWS`` rows at a time, one chain's tile
    small enough for the register file, instead of op by op over the
    whole tile through VMEM."""
    s = _scores(q, k, mask, scale)
    width = s.shape[1]
    ps, ms, ls, alphas = [], [], [], []
    for sl in _chains(s.shape[0]):
        m_new = jnp.max(s[sl], axis=1, keepdims=True)
        if carry is None:
            m_new = jnp.broadcast_to(m_new, (m_new.shape[0], _LANES))
        else:
            m_new = jnp.maximum(carry[1][sl], m_new)
            alphas.append(jnp.exp(carry[1][sl] - m_new))
        p = jnp.exp(s[sl] - _lanes(m_new, width))
        l_new = _lane_sums(p)
        if carry is not None:
            l_new = carry[2][sl] * alphas[-1] + l_new
        ls.append(l_new)
        ps.append(p.astype(v.dtype))
        ms.append(m_new)
    o_new = jax.lax.dot(_cat(ps, 0), v, preferred_element_type=jnp.float32)
    if carry is not None:
        o_new = carry[0] * _lanes(_cat(alphas, 0), o_new.shape[1]) + o_new
    return o_new, _cat(ms, 0), _cat(ls, 0)


def _bwd_ds_block(a, da, lse, delta, b, db, mask, scale):
    """(p, ds) for one score tile of the backward, both already cast to
    the dtype their products take them in; dot inputs stay in the native
    dtype (see _fwd_block_step). Either orientation: (q, do, ·, ·, k, v)
    gives the [q, k] tile with lse/delta as [r, 1] columns; (k, v, ·, ·,
    q, do) gives its TRANSPOSE, [k, q], with lse/delta as [1, n] rows that
    spread over sublanes for nothing (every backward kernel of the file: dv
    = pᵀ·do and dk = dsᵀ·q are then plain products).
    ds is d(loss)/d(s) with s = scale·q·kᵀ, so dq = scale·(ds·k) and
    dk = scale·(dsᵀ·q) — callers apply the final ·scale once on the
    accumulated result (dk's rides a pre-scaled q where the scale folds).
    As in the forward, the two products take every row at once and the
    chain between them (exp, subtract, multiply, cast: fp32) goes
    ``_CHAIN_ROWS`` rows at a time."""
    s = _scores(a, b, mask, scale)
    dp = jax.lax.dot_general(da, db, (((1,), (1,)), ((), ())),
                             preferred_element_type=jnp.float32)
    ps, dss = [], []
    for sl in _chains(s.shape[0]):
        stat = (lambda x: x if x.shape[0] == 1 else x[sl])
        p = jnp.exp(s[sl] - stat(lse))
        dss.append((p * (dp[sl] - stat(delta))).astype(a.dtype))
        ps.append(p.astype(a.dtype))
    return _cat(ps, 0), _cat(dss, 0)


def _causal_split_loop(lo, full, hi, body, carry):
    """fori_loop [lo, full) unmasked + [full, hi) masked."""
    carry = jax.lax.fori_loop(lo, full, lambda i, c: body(i, c, False),
                              carry)
    return jax.lax.fori_loop(full, hi, lambda i, c: body(i, c, True), carry)


# ---------------------------------------------------------------- forward

def _fwd_kernel(q_ref, k_ref, v_ref, o_ref, lse_ref, acc_ref, m_ref, l_ref,
                *, scale, causal, block_q, k_tile, strip, seq_len,
                pack=None):
    """Whole-row forward; the softmax state (o, m, l: fp32) lives in VMEM
    scratch between k-tiles.

    Causal: the block's own diagonal region goes FIRST, from a clean
    state, in sub-blocks of ``strip`` rows: each takes ONE tile of static
    width, from the block's first column to its own diagonal square, the
    mask on the square alone — nothing above a sub-block's diagonal
    square is computed. Then the k-tiles wholly below the block run
    unmasked for all its rows at once (a dynamic count; online softmax
    does not mind the order).

    ``pack`` None: one head a grid step, blocks of [B*H, S, D], grid
    (B*H, q blocks). ``pack`` = (g, D, H): a grid step holds a 128-lane
    COLUMN block of [B, S, H*D] — g heads of width D side by side — on
    grid (B, column blocks, q blocks). Each head runs the same loops on
    the whole block with the other heads' lanes of q zeroed, so q·kᵀ
    contracts over 128 lanes where a head-major block contracts over D of
    a 128-deep array, and p·v fills 128 columns where it filled D; a
    head's state has the block's width and its own lanes of it leave."""
    g, D, heads = pack or (1, 0, 0)
    qi = pl.program_id(2 if pack else 1)
    fold = _scale_folds(scale)
    s_scale = None if fold else scale
    piece = lse_ref.shape[-1]
    tri = _rel_pos(strip, strip) >= 0 if causal else None

    def state(h):
        return ((acc_ref.at[h], m_ref.at[h], l_ref.at[h]) if pack
                else (acc_ref, m_ref, l_ref))

    def step(h, rows, cols, first, mask):
        acc, m, l = state(h)
        q, k = q_ref[0, rows, :], k_ref[0, cols, :]
        if g > 1:
            q = _own_lanes(q, h, D)
            if heads % g:
                # a block can be ragged: 0 x undefined is undefined
                k = _own_lanes(k, h, D)
        if fold:
            q = q * scale
        carry = None if first else (acc[rows, :], m[rows, :], l[rows, :])
        acc[rows, :], m[rows, :], l[rows, :] = _fwd_block_step(
            q, k, v_ref[0, cols, :], carry, mask, s_scale)

    def head(h):
        def body(t, _):
            step(h, slice(None),
                 pl.ds(pl.multiple_of(t * k_tile, k_tile), k_tile), False,
                 None)
            return _

        if causal:
            c0 = pl.multiple_of(qi * block_q, block_q)
            for qs in range(block_q // strip):
                step(h, slice(qs * strip, (qs + 1) * strip),
                     pl.ds(c0, (qs + 1) * strip), True, tri)
            jax.lax.fori_loop(0, qi * (block_q // k_tile), body, 0)
        else:
            step(h, slice(None), slice(0, k_tile), True, None)
            jax.lax.fori_loop(1, seq_len // k_tile, body, 0)

    out = None
    for h in range(g):
        _pack_heads(pack, h, functools.partial(head, h))
        acc, m, l = state(h)
        l_safe = jnp.maximum(_row_total(l[...]), 1e-30)
        o_h = acc[...] / l_safe
        lse = m[...] + jnp.log(l_safe)
        for j in range(block_q // piece):
            lse_ref[(0, h, j) if pack else (0, j)] = _dense_row(
                lse[j * piece:(j + 1) * piece])
        if out is None:
            out = o_h
        else:       # the head's own lanes of the block (and those after)
            lane = jax.lax.broadcasted_iota(jnp.int32, out.shape, 1)
            out = jnp.where(lane >= h * D, o_h, out)
    o_ref[0] = out.astype(o_ref.dtype)


# widest off-diagonal tile: [1024, 512] fp32 scores are 2 MB of VMEM
_MAX_TILE = 512


def _plain_tiles(block, other):
    """(strip, tile) of a whole-row kernel whose grid walks ``block``
    (q rows forward, k columns backward): sub-blocks of one strip along
    the block's diagonal (``_pick_strip``), and the off-diagonal walk
    along the other axis in the widest tile that divides both blocks, at
    most ``_MAX_TILE``."""
    tile = math.gcd(block, other)
    if tile > _MAX_TILE and tile % _MAX_TILE == 0:
        tile = _MAX_TILE
    return _pick_strip(block), tile


def _flash_fwd(q, k, v, scale, causal, block_q, block_k, interpret,
               heads=0, kv_heads=0):
    """``heads``/``kv_heads`` > 0 enable grouped-query K/V: q is
    [B*heads, S, D] while k/v stay [B*kv_heads, S, D] — the K/V block
    index maps fold the q head onto its KV head, so the reduced-head
    cache streams once per rep q heads and the full-head K/V is NEVER
    materialized in HBM (the GQA memory promise, models/llama.py)."""
    _refuse_unequal_widths("whole-row", q, k, v)
    BH, S, D = q.shape
    if heads and kv_heads and heads != kv_heads:
        rep = heads // kv_heads
        H = heads

        def kv_map(b, i):
            return ((b // H) * kv_heads + (b % H) // rep, 0, 0)
    else:
        def kv_map(b, i):
            return (b, 0, 0)
    strip, k_tile = _plain_tiles(block_q, block_k)
    piece = _stat_piece(block_q, block_k)
    kernel = functools.partial(_fwd_kernel, scale=scale, causal=causal,
                               block_q=block_q, k_tile=k_tile, strip=strip,
                               seq_len=S)
    call = pl.pallas_call(
        kernel,
        grid=(BH, S // block_q),
        in_specs=[
            pl.BlockSpec((1, block_q, D), lambda b, i: (b, i, 0)),
            pl.BlockSpec((1, S, D), kv_map),
            pl.BlockSpec((1, S, D), kv_map),
        ],
        out_specs=[
            pl.BlockSpec((1, block_q, D), lambda b, i: (b, i, 0)),
            pl.BlockSpec((1, block_q // piece, 1, piece),
                         lambda b, i: (b, i, 0, 0)),
        ],
        out_shape=[
            jax.ShapeDtypeStruct((BH, S, D), q.dtype),
            jax.ShapeDtypeStruct((BH, S // piece, 1, piece), jnp.float32),
        ],
        scratch_shapes=[pltpu.VMEM((block_q, D), jnp.float32),
                        pltpu.VMEM((block_q, _LANES), jnp.float32),
                        pltpu.VMEM((block_q, _LANES), jnp.float32)],
        interpret=interpret,
    )
    with annotate("flash_fwd"):
        o, lse = call(q, k, v)
    return o, lse


# ---------------------------------------------------------------- backward

def _bwd_fused_kernel(q_ref, k_ref, v_ref, do_ref, lse_ref, delta_ref,
                      dq_ref, dk_ref, dv_ref, dq_acc, dk_acc, dv_acc,
                      delta_acc=None, *, scale, causal, block_k, q_tile,
                      strip, seq_len, pack=None):
    """Single-pass backward: the grid walks k-blocks; dk/dv accumulate
    block-locally (fp32 VMEM scratch) over the q rows of the inner loops,
    while dq accumulates into a VMEM-resident fp32 row that outlives the
    k-block grid dim and leaves as the input dtype on the last k-block.
    Each score tile — the dots AND the exp — is computed ONCE, where
    split dq/dkv kernels compute everything but the final products
    twice.

    The tile is held TRANSPOSED, [k, q]: lse and delta are then lane-dense
    rows, and of pᵀ·do, dsᵀ·q and ds·k only the last needs its left
    operand turned.

    Causal: the k-block's own q rows go first in sub-blocks of ``strip``,
    each against the block's k rows up to its own diagonal square (a
    static height), the mask on the square alone; then the q rows past
    the k-block run ``q_tile`` at a time against the whole block,
    unmasked (a dynamic count). q rows before the block are not visited
    and nothing above a sub-block's diagonal square is computed.

    ``pack`` as in ``_fwd_kernel``: with (g, D, H) a grid step holds g
    heads side by side in a 128-lane column block of [B, S, H*D]. Each
    head takes its tiles with the other heads' lanes zeroed in all four
    operands: k·qᵀ and v·doᵀ contract over the block's 128 lanes, and
    p·do, ds·q and dsᵀ·k land in the head's own lanes of the ONE
    dv / dk / dq accumulator the block has, as exact zeros elsewhere.
    ``delta_ref`` is then o itself and delta = rowsum(do·o) is taken here,
    once a head, into ``delta_acc`` (fp32, lane-dense like lse): from
    [B, S, H*D] operands XLA builds it through a transposed fp32 copy of
    do·o."""
    g, D, heads = pack or (1, 0, 0)
    ki = pl.program_id(2 if pack else 1)
    num_kb = seq_len // block_k
    fold = _scale_folds(scale)
    s_scale = None if fold else scale

    @pl.when(ki == 0)
    def _init():
        dq_acc[...] = jnp.zeros_like(dq_acc)
        if pack:
            piece = delta_acc.shape[-1]
            for j in range(seq_len // piece):
                rows = slice(j * piece, (j + 1) * piece)
                prod = (do_ref[0, rows, :].astype(jnp.float32)
                        * delta_ref[0, rows, :].astype(jnp.float32))
                for h in range(g):
                    total = jnp.sum(prod[:, h * D:(h + 1) * D], axis=1,
                                    keepdims=True)
                    delta_acc[h, j] = _dense_row(
                        jnp.broadcast_to(total, (piece, _LANES)))

    dk_acc[...] = jnp.zeros_like(dk_acc)
    dv_acc[...] = jnp.zeros_like(dv_acc)
    tri = _rel_pos(strip, strip) <= 0 if causal else None   # [k, q]

    def tile(h, row0, rows, cols, mask):
        def take(ref, at):
            x = ref[0, at, :]
            return _own_lanes(x, h, D) if g > 1 else x

        q_rows = pl.ds(row0, rows)
        q = take(q_ref, q_rows)
        if fold:
            q = q * scale
        do = take(do_ref, q_rows)
        k = take(k_ref, cols)
        # the head's lse, a block [1, g, ..], and the delta taken above,
        # [g, ..] — or both the head-major [1, ..]
        lse = _stat_row(lse_ref, (0, h) if pack else (0,), row0, rows)
        delta = (_stat_row(delta_acc, (h,), row0, rows) if pack
                 else _stat_row(delta_ref, (0,), row0, rows))
        p, ds = _bwd_ds_block(k, take(v_ref, cols), lse, delta, q, do, mask,
                              s_scale)
        dv_acc[cols, :] += jax.lax.dot(p, do,
                                       preferred_element_type=jnp.float32)
        dk_acc[cols, :] += jax.lax.dot(ds, q,
                                       preferred_element_type=jnp.float32)
        dq_acc[q_rows, :] += jax.lax.dot_general(
            ds, k, (((0,), (0,)), ((), ())),
            preferred_element_type=jnp.float32)

    def head(h):
        def body(t, _):
            tile(h, pl.multiple_of(t * q_tile, q_tile), q_tile, slice(None),
                 None)
            return _

        if causal:
            r0 = ki * block_k
            for qs in range(block_k // strip):
                tile(h, pl.multiple_of(r0 + qs * strip, strip), strip,
                     slice(0, (qs + 1) * strip), tri)
            jax.lax.fori_loop((ki + 1) * (block_k // q_tile),
                              seq_len // q_tile, body, 0)
        else:
            jax.lax.fori_loop(0, seq_len // q_tile, body, 0)

    for h in range(g):
        _pack_heads(pack, h, functools.partial(head, h))
    # dk = scale·Σ dsᵀ·q: a pre-scaled q has carried it
    dk = dk_acc[...] if fold else dk_acc[...] * scale
    dk_ref[0] = dk.astype(dk_ref.dtype)
    dv_ref[0] = dv_acc[...].astype(dv_ref.dtype)

    @pl.when(ki == num_kb - 1)
    def _finish():
        # dq = scale·Σ ds·k, applied once after every k-block contributed
        dq_ref[0] = (dq_acc[...] * scale).astype(dq_ref.dtype)


def _flash_bwd(q, k, v, o, lse, do, scale, causal, block_q, block_k,
               interpret):
    _refuse_unequal_widths("whole-row", q, k, v)
    BH, S, D = q.shape
    pieces = lse.shape[1:]                  # (S / piece, 1, piece)
    delta = jnp.sum(do.astype(jnp.float32) * o.astype(jnp.float32),
                    axis=-1).reshape(lse.shape)
    strip, q_tile = _plain_tiles(block_k, block_q)

    def row(b, i):
        return (b, 0, 0)

    def block(b, i):
        return (b, i, 0)

    call = pl.pallas_call(
        functools.partial(_bwd_fused_kernel, scale=scale, causal=causal,
                          block_k=block_k, q_tile=q_tile, strip=strip,
                          seq_len=S),
        grid=(BH, S // block_k),
        in_specs=[
            pl.BlockSpec((1, S, D), row),
            pl.BlockSpec((1, block_k, D), block),
            pl.BlockSpec((1, block_k, D), block),
            pl.BlockSpec((1, S, D), row),
            pl.BlockSpec((1,) + pieces, lambda b, i: (b, 0, 0, 0)),
            pl.BlockSpec((1,) + pieces, lambda b, i: (b, 0, 0, 0)),
        ],
        out_specs=[
            pl.BlockSpec((1, S, D), row),
            pl.BlockSpec((1, block_k, D), block),
            pl.BlockSpec((1, block_k, D), block),
        ],
        out_shape=[jax.ShapeDtypeStruct((BH, S, D), q.dtype)] * 3,
        scratch_shapes=[pltpu.VMEM((S, D), jnp.float32),
                        pltpu.VMEM((block_k, D), jnp.float32),
                        pltpu.VMEM((block_k, D), jnp.float32)],
        interpret=interpret,
    )
    with annotate("flash_bwd"):
        dq, dk, dv = call(q, k, v, do, lse, delta)
    return dq, dk, dv


# ----------------------------- the whole-row kernels on [B, S, H*D] operands

def _column_plan(E, heads):
    """(g, D, width) of the column blocks of a [B, S, H*D] operand: g
    heads of width D share a block of ``width`` = max(D, 128) lanes (2 at
    head_dim 64, 1 at 128 or 256). None where heads do not tile lane
    blocks (neither 128 % D nor D % 128 is 0) or fill not even one."""
    D = E // heads
    if D * heads != E or (_LANES % D and D % _LANES) or E < _LANES:
        return None
    width = max(D, _LANES)
    return width // D, D, width


def _columns(operands, heads):
    """((q, k, v) arrays, their first column blocks, E, ``_column_plan``):
    a fused projection [B, S, 3*E] is read IN PLACE as three views of
    itself, E / width column blocks apart; three [B, S, E] arrays each
    start at block 0."""
    fused = len(operands) == 1
    if not fused:
        _refuse_unequal_widths("column-block", *operands)
    E = operands[0].shape[-1] // (3 if fused else 1)
    plan = _column_plan(E, heads)
    step = E // plan[2] if fused and plan else 0
    return operands * (3 if fused else 1), (0, step, 2 * step), E, plan


def _flash_fwd_cols(operands, heads, scale, causal, block_q, block_k,
                    interpret):
    """``_fwd_kernel`` over column blocks: q, k, v and o stay [B, S, H*D]
    — dense 128-lane tiles in HBM, where [B*H, S, 64] pads every row to
    128 lanes — and lse is [B, H, S / piece, 1, piece]."""
    B, S, _ = operands[0].shape
    (q, k, v), (q0, k0, v0), E, (g, D, width) = _columns(operands, heads)
    strip, k_tile = _plain_tiles(block_q, block_k)
    piece = _stat_piece(block_q, block_k)
    call = pl.pallas_call(
        functools.partial(_fwd_kernel, scale=scale, causal=causal,
                          block_q=block_q, k_tile=k_tile, strip=strip,
                          seq_len=S, pack=(g, D, heads)),
        grid=(B, pl.cdiv(heads, g), S // block_q),
        in_specs=[
            pl.BlockSpec((1, block_q, width), lambda b, j, i: (b, i, q0 + j)),
            pl.BlockSpec((1, S, width), lambda b, j, i: (b, 0, k0 + j)),
            pl.BlockSpec((1, S, width), lambda b, j, i: (b, 0, v0 + j)),
        ],
        out_specs=[
            pl.BlockSpec((1, block_q, width), lambda b, j, i: (b, i, j)),
            pl.BlockSpec((1, g, block_q // piece, 1, piece),
                         lambda b, j, i: (b, j, i, 0, 0)),
        ],
        out_shape=[
            jax.ShapeDtypeStruct((B, S, E), q.dtype),
            jax.ShapeDtypeStruct((B, heads, S // piece, 1, piece),
                                 jnp.float32),
        ],
        scratch_shapes=[pltpu.VMEM((g, block_q, width), jnp.float32),
                        pltpu.VMEM((g, block_q, _LANES), jnp.float32),
                        pltpu.VMEM((g, block_q, _LANES), jnp.float32)],
        interpret=interpret,
    )
    with annotate("flash_fwd"):
        o, lse = call(q, k, v)
    return o, lse


def _flash_bwd_cols(operands, heads, o, lse, do, scale, causal, block_q,
                    block_k, interpret):
    """``_bwd_fused_kernel`` over column blocks: dq, dk, dv leave as three
    [B, S, H*D] arrays."""
    B, S, _ = o.shape
    (q, k, v), (q0, k0, v0), E, (g, D, width) = _columns(operands, heads)
    stats = lse.shape[2:]                   # (S / piece, 1, piece)
    strip, q_tile = _plain_tiles(block_k, block_q)

    def row(b, j, i):
        return (b, 0, j)

    call = pl.pallas_call(
        functools.partial(_bwd_fused_kernel, scale=scale, causal=causal,
                          block_k=block_k, q_tile=q_tile, strip=strip,
                          seq_len=S, pack=(g, D, heads)),
        grid=(B, pl.cdiv(heads, g), S // block_k),
        in_specs=[
            pl.BlockSpec((1, S, width), lambda b, j, i: (b, 0, q0 + j)),
            pl.BlockSpec((1, block_k, width), lambda b, j, i: (b, i, k0 + j)),
            pl.BlockSpec((1, block_k, width), lambda b, j, i: (b, i, v0 + j)),
            pl.BlockSpec((1, S, width), row),
            pl.BlockSpec((1, g) + stats, lambda b, j, i: (b, j, 0, 0, 0)),
            pl.BlockSpec((1, S, width), row),
        ],
        out_specs=[
            pl.BlockSpec((1, S, width), row),
            pl.BlockSpec((1, block_k, width), lambda b, j, i: (b, i, j)),
            pl.BlockSpec((1, block_k, width), lambda b, j, i: (b, i, j)),
        ],
        out_shape=[jax.ShapeDtypeStruct((B, S, E), o.dtype)] * 3,
        scratch_shapes=[pltpu.VMEM((S, width), jnp.float32),
                        pltpu.VMEM((block_k, width), jnp.float32),
                        pltpu.VMEM((block_k, width), jnp.float32),
                        pltpu.VMEM((g,) + stats, jnp.float32)],
        interpret=interpret,
    )
    with annotate("flash_bwd"):
        dq, dk, dv = call(q, k, v, do, lse, o)
    return dq, dk, dv


# MB the forward rules of the differentiation being traced have named so
# far, and whether a backward rule has been traced since (the next forward
# rule then belongs to another program's trace and starts a new sum)
_named = {"mb": 0.0, "closed": False}


def _name_residuals(o, lse):
    """(o, lse) under the names a remat policy keeps them by — ``flash_o``,
    ``flash_lse``: with both saved the backward kernels run without the
    forward kernel being run again (``models/gpt2.py``: the ``dots_flash*``
    policies and ``block_remat_policy``) — and the gauge
    ``attention/flash_residual_mb``: decimal MB of HBM the pairs named by
    one differentiation's forward rules take, a minor dimension counted as
    the 128-lane tiles it is stored in (a [BH, S, 1] statistic reads 128 x
    its values' size; every kernel here writes lse lane-dense, 1/64 of a
    bf16 o at head_dim 128). A forward rule is traced once a call site,
    all of them before the first backward rule: a scanned layer counts
    once."""
    from jax.ad_checkpoint import checkpoint_name
    if _named["closed"]:
        _named.update(mb=0.0, closed=False)
    _named["mb"] += sum(
        math.prod(x.shape[:-1]) * -(-x.shape[-1] // _LANES) * _LANES
        * x.dtype.itemsize for x in (o, lse)) / 1e6
    default_registry().gauge("attention/flash_residual_mb").set(
        _named["mb"])
    return checkpoint_name(o, "flash_o"), checkpoint_name(lse, "flash_lse")


@functools.partial(jax.custom_vjp, nondiff_argnums=(1, 2, 3, 4, 5, 6))
def _flash_attention_cols(operands, heads, scale, causal, block_q, block_k,
                          interpret):
    """``operands``: (qkv [B, S, 3*E],) read in place, or (q, k, v)."""
    return _flash_fwd_cols(operands, heads, scale, causal, block_q, block_k,
                           interpret)[0]


def _flash_attention_cols_fwd(operands, heads, scale, causal, block_q,
                              block_k, interpret):
    o, lse = _flash_fwd_cols(operands, heads, scale, causal, block_q,
                             block_k, interpret)
    # the other residual is the projection itself, in place
    o, lse = _name_residuals(o, lse)
    return o, (operands, o, lse)


def _flash_attention_cols_bwd(heads, scale, causal, block_q, block_k,
                              interpret, residuals, do):
    operands, o, lse = residuals
    _named["closed"] = True
    grads = _flash_bwd_cols(operands, heads, o, lse, do, scale, causal,
                            block_q, block_k, interpret)
    if len(operands) == 1:
        return ((jnp.concatenate(grads, axis=-1),),)
    return (grads,)


_flash_attention_cols.defvjp(_flash_attention_cols_fwd,
                             _flash_attention_cols_bwd)


# ------------------------------------------------- long-S chunked variants

def _kv_row(heads, kv_heads):
    """Row of the [B * kv_heads, S, D] K/V arrays that grid row ``b`` of
    [B * heads] reads: its own under multi-head attention, its group's under
    grouped-query attention (query heads are grouped consecutively per KV
    head), so K and V are never repeated in HBM."""
    if heads and kv_heads and heads != kv_heads:
        rep = heads // kv_heads
        return lambda b: (b // heads) * kv_heads + (b % heads) // rep
    return lambda b: b


def _fwd_walk(q, tile, segments, o_ref, m_ref, l_ref, scale, first):
    """The forward walk of one grid step over its k-tiles, IN PLACE:
    ``_fwd_block_step`` on tile after tile with the softmax state where it
    lives between grid steps anyway — o in the revisited float32 output block
    (``o_ref``: its [rows, D] view), m and l in VMEM scratch — cleared on a
    query block's ``first`` grid step, read and written a tile, and NOTHING
    carried by the loops: as loop-carried values the 192 vregs of (o, m, l)
    at 512 rows were spilled and copied slot to slot at every tile's head and
    tail, ~370 of its 1,426 bundles with the MXU idle and the one store slot
    full (PERF.md, PR 67). ``segments``: [(lo, hi, masked)] ranges of tile
    indices (traced bounds, ``masked`` static), walked in order;
    ``tile(j, masked)`` -> (k, v, mask) of tile j."""
    @pl.when(first)
    def _init():
        o_ref[...] = jnp.zeros_like(o_ref)
        m_ref[...] = jnp.full_like(m_ref, NEG_INF)
        l_ref[...] = jnp.zeros_like(l_ref)

    def step(j, _, masked):
        k, v, mask = tile(j, masked)
        o_ref[...], m_ref[...], l_ref[...] = _fwd_block_step(
            q, k, v, (o_ref[...], m_ref[...], l_ref[...]), mask, scale)
        return _

    for lo, hi, masked in segments:
        jax.lax.fori_loop(lo, hi, functools.partial(step, masked=masked), 0)


def _finish_chunked_fwd(o_ref, lse_ref, m_ref, l_ref, last):
    """What the ``last`` grid step of a query block's walk leaves, chunked
    and window forward alike: the normalised o over the raw one ``_fwd_walk``
    has been keeping in the revisited float32 output block, and lse = m +
    log l from the scratch m (lane-replicated) and l (per-lane partial sums),
    lane-dense through ``_dense_row``: no separate [BH, S, D] normalisation
    pass, and no [BH, S, 1] statistic, in HBM. ``last`` may be Python's own
    True (``_walk_phase``: a walk of one step)."""
    piece = lse_ref.shape[-1]

    @pl.when(last)
    def _finish():
        m, l = m_ref[...], l_ref[...]
        total = _row_total(l)
        l_safe = jnp.maximum(total, 1e-30)
        o_ref[0] = jnp.where(total > 0, o_ref[0] / l_safe, 0.0)
        lse = m + jnp.log(l_safe)
        for j in range(lse.shape[0] // piece):
            lse_ref[0, j] = _dense_row(lse[j * piece:(j + 1) * piece])


def _walk_ends(i, block, chunk, n_chunks, causal):
    """(first, last) key chunk that query block ``i`` (``block`` rows) sees:
    under a causal mask from chunk 0 to the one that holds the block's
    diagonal, every chunk where nothing is masked. ``i`` a Python int
    (``_pair_walk`` builds the grid from this) or traced (the forward kernel
    tells a walk's first and last grid step by it)."""
    if not causal:
        return 0, n_chunks - 1
    return 0, ((i + 1) * block - 1) // chunk


@functools.lru_cache(maxsize=None)
def _pair_walk(S, block, chunk, causal, by_chunk):
    """The second grid dimension of a chunked kernel: the (query block, key
    chunk) pairs that hold work, as two int32 arrays (``i_of``, ``c_of``)
    indexed by grid step. The forward's order is the one a rectangular
    (S / block, S / chunk) grid visits them in — a block's pairs consecutive
    and its chunks ascending (``_walk_ends``), so its revisited output block
    and its softmax state accumulate over one unbroken run of steps;
    ``by_chunk`` gives the SAME pairs a chunk's together and its blocks
    ascending, the backward's order: a key chunk's K and V stay where they
    are and its dk and dv accumulate in VMEM while the query blocks that see
    it stream by. A causal call leaves out the pairs wholly above the
    diagonal: 80 of 128 at S 16,384 with blocks of 512 and chunks of 4,096,
    40 of 64 at S 8,192 / 512 / 2,048, all 8 at S 4,096 / 512 / 4,096 (272
    of 512, 136 of 256 and 20 of 32 at the chunks of 1,024 / 512 / 1,024 rows
    before PR 48; a grid step with an empty loop still fetched its chunk and
    cost 0.6-1 us: PERF.md, PR 39); a call that is not causal walks the
    rectangle. Built with numpy at trace time, once a plan, and handed to
    the call as scalar-prefetch operands."""
    blocks, chunks = [], []
    for i in range(S // block):
        first, last = _walk_ends(i, block, chunk, S // chunk, causal)
        blocks += [i] * (last - first + 1)
        chunks += range(first, last + 1)
    walk = np.asarray(blocks, np.int32), np.asarray(chunks, np.int32)
    if by_chunk:
        order = np.argsort(walk[1], kind="stable")
        walk = tuple(np.ascontiguousarray(x[order]) for x in walk)
    for x in walk:              # cached: shared by every call of the plan
        x.flags.writeable = False
    return walk


def _fwd_kernel_chunked(i_of, c_of, q_ref, k_ref, v_ref, o_ref, lse_ref,
                        m_ref, l_ref, *, scale, causal, block_q, block_k,
                        chunk, n_chunks):
    t = pl.program_id(1)
    qi, kc = i_of[t], c_of[t]
    first, last = _walk_ends(qi, block_q, chunk, n_chunks, causal)
    cb = chunk // block_k                      # k-blocks per chunk
    fold = _scale_folds(scale)
    s_scale = None if fold else scale
    q = q_ref[0] * scale if fold else q_ref[0]

    def tile(j, masked):
        rows = pl.ds(j * block_k, block_k)
        kb = kc * cb + j                       # global k-block index
        return (k_ref[0, rows, :], v_ref[0, rows, :],
                _block_mask(_rel_pos(block_q, block_k) if causal else None,
                            masked, qi * block_q, kb * block_k)
                if masked else None)

    if causal:
        num_full = (qi * block_q) // block_k
        num_active = ((qi + 1) * block_q + block_k - 1) // block_k
        j_full = jnp.clip(num_full - kc * cb, 0, cb)
        j_hi = jnp.clip(num_active - kc * cb, 0, cb)
        segments = [(0, j_full, False), (j_full, j_hi, True)]
    else:
        segments = [(0, cb, False)]
    _fwd_walk(q, tile, segments, o_ref.at[0], m_ref, l_ref, s_scale,
              kc == first)
    _finish_chunked_fwd(o_ref, lse_ref, m_ref, l_ref, kc == last)


def _chunked_fwd_outputs(q, block_q, block_k, block_of, width=None):
    """(out_specs, out_shape, scratch_shapes) of the chunked and the window
    forward, whose grid steps walk a query block's chunks (``block_of``: the
    grid's arguments -> (row of BH, query block)): o [BH, S, D] float32
    (D the VALUE width, ``width``, where it is not q's), revisited over a
    block's walk; lse [BH, S / piece, 1, piece] float32,
    lane-dense as the whole-row kernels store it (``_stat_piece``), written
    on the walk's last step; the running m and l, [block_q, 128] VMEM
    scratch."""
    BH, S, D = q.shape
    D = width or D
    piece = _stat_piece(block_q, block_k)
    return (
        [_rows_spec(block_q, D, block_of),
         _stat_spec(block_q, piece, block_of)],
        [jax.ShapeDtypeStruct((BH, S, D), jnp.float32),
         jax.ShapeDtypeStruct((BH, S // piece, 1, piece), jnp.float32)],
        [pltpu.VMEM((block_q, _LANES), jnp.float32),
         pltpu.VMEM((block_q, _LANES), jnp.float32)])


def _of_block(b, t, i_of, c_of):
    """(row of BH, grid block) of step ``t`` of a ``_pair_walk`` grid."""
    return b, i_of[t]


def _of_chunk(b, t, i_of, c_of):
    """(row of BH, sequence chunk) of step ``t`` of a ``_pair_walk`` grid."""
    return b, c_of[t]


def _rows_spec(rows, D, index, row=lambda b: b):
    """BlockSpec of ``rows`` rows of a [BH, S, D] operand: ``index`` maps the
    grid to (row of BH, block of ``rows``), ``row`` that row of BH to the
    operand's own (``_kv_row`` for K and V)."""
    def at(*g):
        b, i = index(*g)
        return row(b), i, 0
    return pl.BlockSpec((1, rows, D), at)


def _pair_call(kernel, walk, BH, in_specs, out_specs, out_shape, scratch,
               interpret, vmem_limit=None):
    """``pallas_call`` of a chunked kernel on grid (BH, pairs of ``walk``):
    ``_pair_walk``'s two arrays are the call's first two operands, and every
    index map reads its (block, chunk) from them — ``(b, t, i_of, c_of)``.
    ``vmem_limit``: bytes of scoped VMEM the compiled call may take, where
    the default 16 MiB does not hold its blocks."""
    call = pl.pallas_call(
        kernel,
        grid_spec=pltpu.PrefetchScalarGridSpec(
            num_scalar_prefetch=2, grid=(BH, len(walk[0])),
            in_specs=in_specs, out_specs=out_specs, scratch_shapes=scratch),
        out_shape=out_shape,
        interpret=interpret,
        compiler_params=pltpu.CompilerParams(vmem_limit_bytes=vmem_limit)
        if vmem_limit and not interpret else None)
    return functools.partial(call, *walk)


def _flash_fwd_chunked(q, k, v, scale, causal, block_q, block_k, chunk,
                       interpret, heads=0, kv_heads=0):
    """q and k [.., S, D], v [.., S, Dv] and o as wide as v: the score
    contracts over D, the output over the keys, and nothing in the kernel
    ties the two widths (latent attention: D 192 = 128 + the 64 rotated,
    Dv 128). Equal widths are the same call as before."""
    BH, S, D = q.shape
    Dv = v.shape[-1]
    kv = _kv_row(heads, kv_heads)
    out_specs, out_shape, scratch = _chunked_fwd_outputs(
        q, block_q, block_k, _of_block, Dv)
    kernel = functools.partial(_fwd_kernel_chunked, scale=scale,
                               causal=causal, block_q=block_q,
                               block_k=block_k, chunk=chunk,
                               n_chunks=S // chunk)
    call = _pair_call(
        kernel, _pair_walk(S, block_q, chunk, causal, False), BH,
        [_rows_spec(block_q, D, _of_block),
         _rows_spec(chunk, D, _of_chunk, kv),
         _rows_spec(chunk, Dv, _of_chunk, kv)],
        out_specs, out_shape, scratch, interpret)
    with annotate("flash_fwd_chunk"):
        o32, lse = call(q, k, v)
    return o32.astype(q.dtype), lse


# scoped VMEM the chunked backward may take: a chunk's K, V, dk and dv blocks
# (double-buffered) and its two float32 accumulators are 12 MiB at 4,096 rows
# of head_dim 128 in bf16 and 18 MiB at latent attention's 192 / 128, beside
# the tile's own temporaries; a v5e core has 128 MiB
_BWD_VMEM_BYTES = 64 * 2 ** 20


def _bwd_kernel_chunked(i_of, c_of, q_ref, k_ref, v_ref, do_ref, lse_ref,
                        delta_ref, dq_ref, dk_ref, dv_ref, dk_acc, dv_acc, *,
                        scale, causal, block_q, block_k, chunk, dq_leaves):
    """Single-pass chunked backward: a grid step is one (query block, key
    chunk) pair of the walk ``_pair_walk`` orders BY CHUNK, and each score
    tile of it — the two dots AND the exp — is computed ONCE for all three
    gradients (five MXU products a tile, where a dq and a dkv kernel ran
    seven and the softmax chain twice), as ``_bwd_fused_kernel`` does for a
    whole row. The chunk's K and V stay in VMEM over its run of steps and
    its dk and dv accumulate in float32 scratch, leaving once — in the
    operands' dtype — on the run's last step; what a whole [S, D] float32
    dq row would take does not fit (8 MiB a head at S 16,384), so a step's
    dq — the block's rows against THIS chunk's keys, float32, unscaled —
    leaves as block ``t`` of a [BH, pairs, block_q, D] array and
    ``_sum_dq_slabs`` adds a block's partials of every chunk. ``dq_leaves``
    (a plan of ONE chunk): nothing is left to add, and dq leaves scaled in
    the operands' dtype.

    The tile is held TRANSPOSED, [k, q], as the whole-row kernel holds it:
    lse and delta are lane-dense rows as they are stored, and of p·do, ds·q
    and dsᵀ·k only the last needs its left operand turned."""
    t = pl.program_id(1)
    steps = pl.num_programs(1)
    qi, kc = i_of[t], c_of[t]
    # the chunk's run of steps, told by the walk itself
    first = jnp.logical_or(t == 0, c_of[jnp.maximum(t - 1, 0)] != kc)
    last = jnp.logical_or(t == steps - 1,
                          c_of[jnp.minimum(t + 1, steps - 1)] != kc)
    cb = chunk // block_k
    fold = _scale_folds(scale)
    s_scale = None if fold else scale
    q = q_ref[0] * scale if fold else q_ref[0]
    do = do_ref[0]
    lse = _stat_row(lse_ref, (0,), 0, block_q)
    delta = _stat_row(delta_ref, (0,), 0, block_q)
    rel = -_rel_pos(block_k, block_q) if causal else None   # query - key

    @pl.when(first)
    def _init():
        dk_acc[...] = jnp.zeros_like(dk_acc)
        dv_acc[...] = jnp.zeros_like(dv_acc)

    def body(j, dq, masked):
        kb = kc * cb + j
        rows = pl.ds(pl.multiple_of(j * block_k, block_k), block_k)
        k = k_ref[0, rows, :]
        mask = _block_mask(rel, masked, qi * block_q, kb * block_k)
        p, ds = _bwd_ds_block(k, v_ref[0, rows, :], lse, delta, q, do, mask,
                              s_scale)
        dv_acc[rows, :] += jax.lax.dot(p, do,
                                       preferred_element_type=jnp.float32)
        dk_acc[rows, :] += jax.lax.dot(ds, q,
                                       preferred_element_type=jnp.float32)
        return dq + jax.lax.dot_general(ds, k, (((0,), (0,)), ((), ())),
                                        preferred_element_type=jnp.float32)

    dq0 = jnp.zeros(q.shape, jnp.float32)
    if causal:
        num_full = (qi * block_q) // block_k
        num_active = ((qi + 1) * block_q + block_k - 1) // block_k
        j_full = jnp.clip(num_full - kc * cb, 0, cb)
        j_hi = jnp.clip(num_active - kc * cb, 0, cb)
        dq = _causal_split_loop(0, j_full, j_hi, body, dq0)
    else:
        dq = _causal_split_loop(0, cb, cb, body, dq0)
    # dq = scale · Σ ds·k: once, where a block's partials have been added
    dq_ref[0, 0] = (dq * scale).astype(dq_ref.dtype) if dq_leaves else dq

    @pl.when(last)
    def _leave():
        # dk = scale · Σ dsᵀ·q: a pre-scaled q has carried it
        dk = dk_acc[...] if fold else dk_acc[...] * scale
        dk_ref[0] = dk.astype(dk_ref.dtype)
        dv_ref[0] = dv_acc[...].astype(dv_ref.dtype)


def _sum_dq_slabs(parts, walk, S, chunk, scale, dtype):
    """dq [BH, S, D] from the single-pass backward's partials [BH, pairs,
    block_q, D] (float32, unscaled): pair ``t`` holds query block
    ``i_of[t]``'s rows against chunk ``c_of[t]``'s keys, a chunk's pairs one
    SLAB of consecutive blocks (``_pair_walk`` by chunk: under a causal mask
    a slab starts at its chunk's own rows — 10 of 16 block-rows at four
    chunks). A chunk's rows of dq are the float32 sum of the slabs that
    hold them, times the scale, cast once: one XLA pass where the split
    kernels' float32 dq took one to be cast."""
    BH, _, block_q, D = parts.shape
    i_of, c_of = walk
    cb = chunk // block_q
    # slab c: pairs [at[c], at[c + 1]), query blocks from lead[c] on
    at = np.searchsorted(c_of, np.arange(S // chunk + 1))
    lead = i_of[at[:-1]]
    rows = []
    for r in range(S // chunk):
        held = [parts[:, at[c] + r * cb - lead[c]:
                      at[c] + (r + 1) * cb - lead[c]]
                for c in range(S // chunk) if lead[c] <= r * cb]
        rows.append(functools.reduce(jnp.add, held))
    return (_cat(rows, 1) * scale).astype(dtype).reshape(BH, S, D)


def _flash_bwd_chunked(q, k, v, o, lse, do, scale, causal, block_q, block_k,
                       chunk, interpret, heads=0, kv_heads=0):
    """K and V may be [B * kv_heads, S, D] (grouped-query): the kernel
    reads a query head's group through ``_kv_row``; dk and dv still come
    back per QUERY head ([B * heads, S, D]) for the caller to sum. v, o, do
    and dv are ``Dv`` wide where the value width is not the q·k width
    (``_flash_fwd_chunked``)."""
    BH, S, D = q.shape
    Dv = v.shape[-1]
    kv = _kv_row(heads, kv_heads)
    delta = jnp.sum(do.astype(jnp.float32) * o.astype(jnp.float32),
                    axis=-1).reshape(lse.shape)
    piece = lse.shape[-1]
    walk = _pair_walk(S, block_q, chunk, causal, True)
    one = chunk == S                # one slab: dq leaves the kernel whole
    call = _pair_call(
        functools.partial(_bwd_kernel_chunked, scale=scale, causal=causal,
                          block_q=block_q, block_k=block_k, chunk=chunk,
                          dq_leaves=one),
        walk, BH,
        [_rows_spec(block_q, D, _of_block),
         _rows_spec(chunk, D, _of_chunk, kv),
         _rows_spec(chunk, Dv, _of_chunk, kv),
         _rows_spec(block_q, Dv, _of_block)]
        + [_stat_spec(block_q, piece, _of_block)] * 2,
        [pl.BlockSpec((1, 1, block_q, D), lambda b, t, *_: (b, t, 0, 0)),
         _rows_spec(chunk, D, _of_chunk),
         _rows_spec(chunk, Dv, _of_chunk)],
        [jax.ShapeDtypeStruct((BH, len(walk[0]), block_q, D),
                              q.dtype if one else jnp.float32),
         jax.ShapeDtypeStruct((BH, S, D), k.dtype),
         jax.ShapeDtypeStruct((BH, S, Dv), v.dtype)],
        [pltpu.VMEM((chunk, D), jnp.float32),
         pltpu.VMEM((chunk, Dv), jnp.float32)],
        interpret, _BWD_VMEM_BYTES)
    with annotate("flash_bwd_chunk"):
        dq, dk, dv = call(q, k, v, do, lse, delta)
    if one:
        return dq.reshape(BH, S, D), dk, dv
    with annotate("flash_bwd_dq_sum"):
        dq = _sum_dq_slabs(dq, walk, S, chunk, scale, q.dtype)
    return dq, dk, dv


# ------------------------------------------ sliding-window (band) variants

def _band_mask(rel, q_pos0, k_pos0, window):
    """Mask of an EDGE block of a window band from ``_rel_pos``'s tile: key j
    is visible to query i iff ``0 <= i - j < window`` — the causal compare of
    ``_block_mask`` and the band's lower bound beside it (a block narrower
    than the window needs one of the two, one wider can need both)."""
    return _block_mask(rel, True, q_pos0, k_pos0) \
        & (rel < window + k_pos0 - q_pos0)


# bytes of ONE band operand's block a grid step of the window kernels may
# hold — the band's rows of K or V, forward and backward; the ``heads`` query
# heads' [block_q, D] blocks of Q or dO, backward: two such operands, double
# buffered, lie beside the score tiles in scoped VMEM. 4,608 rows of
# head_dim 128 in bf16 (W 4,096 under blocks of 512: 1.18 MB, 4.7 MB in all)
# fit whole; a band past the budget goes in the fewest steps that fit
_BAND_BYTES = 2 * 2 ** 20


def _band_tile_counts(S, block_q, block_k, window):
    """``block_k``-row key blocks the band of each query block touches: the
    block is ``block_q`` query rows from ``p0`` and the band the keys
    ``[p0 - window + 1, p0 + block_q)`` it sees."""
    return [(p0 + block_q - 1) // block_k
            - max(p0 - window + 1, 0) // block_k + 1
            for p0 in range(0, S, block_q)]


def _band_tiles(S, block_q, block_k, window):
    """STATIC count of key blocks a query block's band touches, the most
    over the blocks: ``ceil((block_q + window - 1) / block_k)``, one more
    where a band can straddle a tile's edge, and never more than the
    sequence holds."""
    return max(_band_tile_counts(S, block_q, block_k, window))


def _band_ring(S, block_q, block_k, window, lag):
    """Rows of the backward's dk / dv ring: the widest span of key rows a
    query block's step can touch — from the oldest block that has not left
    (``lag`` query blocks back), or the band's first key tile where that
    starts lower, to the end of the tile that holds the diagonal — in whole
    blocks of both sizes, so that neither a key tile nor a leaving block
    wraps; a ring as long as the sequence does not turn at all."""
    most = max(
        -(-(i + 1) * block_q // block_k) * block_k
        - min(max(i - lag, 0) * block_q,
              max(i * block_q - window + 1, 0) // block_k * block_k)
        for i in range(S // block_q))
    unit = math.lcm(block_q, block_k)
    return min(-(-most // unit) * unit, S)


def _band_plan(S, block_q, block_k, window, row_bytes, rep=1, chunk=0):
    """((tiles a grid step, grid steps) of a query block's walk over its
    band of keys — forward and backward alike — and (lag, ring rows, heads a
    step) of the backward): the band is ONE operand block where its rows fit
    ``_BAND_BYTES`` (``chunk``: the caller's own cap, in rows), else the
    fewest equal steps that do. The backward walks the query blocks too, a KV
    head's ``rep`` query heads inside a step — as many as fit the budget
    with their [block_q, D] blocks (the most that divide ``rep``) — with dk
    and dv in a float32 ring of ``_band_ring`` rows: key block ``e`` (of
    ``block_q`` rows) has been seen by every query once query block
    ``e + lag`` is done, ``lag = ceil((window - 1) / block_q)``, and leaves
    then, so the grid runs ``lag`` steps past the sequence's last block."""
    tiles = _band_tiles(S, block_q, block_k, window)
    cap = max((chunk or _BAND_BYTES // row_bytes) // block_k, 1)
    steps = -(-tiles // cap)
    lag = -(-(window - 1) // block_q)
    heads = max(h for h in range(1, rep + 1) if rep % h == 0
                and (h == 1 or h * block_q * row_bytes <= _BAND_BYTES))
    return ((-(-tiles // steps), steps),
            (lag, _band_ring(S, block_q, block_k, window, lag), heads))


def _band_k_first(i, c, block_q, block_k, walk):
    """Key block that grid step ``c`` of query block ``i``'s walk starts at:
    the steps END at the block that holds the block's diagonal, so this is
    negative for the sequence's first blocks (the operand then starts at
    key 0, and the kernel walks what of the step lies in the sequence)."""
    per, steps = walk
    return ((i + 1) * block_q - 1) // block_k + 1 - (steps - c) * per


def _band_k_ranges(q0, first, per, block_q, block_k, window):
    """(operand's first key block, (j_lo, j_a, j_b, j_hi) within it) for the
    query block at ``q0`` and a grid step of ``per`` key blocks from
    ``first`` (``_band_k_first``): blocks [j_lo, j_a) hold the band's lower
    edge, [j_a, j_b) lie wholly inside it, [j_b, j_hi) hold the
    diagonal."""
    lo = jnp.maximum(q0 - window + 1, 0) // block_k
    hi = (q0 + block_q - 1) // block_k + 1
    a = jnp.clip((jnp.maximum(q0 + block_q - window, 0) + block_k - 1)
                 // block_k, lo, hi)
    b = jnp.clip((q0 + 1) // block_k, a, hi)
    at = jnp.maximum(first, 0)
    return at, tuple(jnp.clip(x, at, jnp.maximum(first + per, at)) - at
                     for x in (lo, a, b, hi))


def _band_loop(ranges, body, carry):
    """fori_loop over a step's blocks: edge (masked), inside (unmasked),
    edge (masked)."""
    j_lo, j_a, j_b, j_hi = ranges
    carry = jax.lax.fori_loop(j_lo, j_a, lambda j, c: body(j, c, True),
                              carry)
    carry = jax.lax.fori_loop(j_a, j_b, lambda j, c: body(j, c, False),
                              carry)
    return jax.lax.fori_loop(j_b, j_hi, lambda j, c: body(j, c, True), carry)


def _band_keys_spec(walk, block_q, block_k, D, at):
    """K / V operand of the window calls: the rows of one step of a query
    block's ``walk``, ``at`` mapping the grid to (KV row, query block, step
    of the walk). The block sits at ELEMENT offsets — the sequence's a key
    block's index times its size, so the compiler sees it lie on a tile's
    edge — and the kernel's ref is [1, rows, D]."""
    def index(*g):
        row, i, c = at(*g)
        return row, jnp.maximum(
            _band_k_first(i, c, block_q, block_k, walk), 0) * block_k, 0
    return pl.BlockSpec((pl.Element(1), pl.Element(walk[0] * block_k),
                         pl.Element(D)), index)


def _walk_phase(c, steps):
    """(first, last) grid step of a walk of ``steps``: Python's own True
    for a band in one step — the backward then has no dq to carry between
    steps, the forward clears and normalises its state in the one step
    (``_fwd_walk``, ``_finish_chunked_fwd``)."""
    return (True, True) if steps == 1 else (c == 0, c == steps - 1)


def _swa_fwd_kernel(q_ref, k_ref, v_ref, o_ref, lse_ref, m_ref, l_ref, *,
                    scale, window, block_q, block_k, walk):
    qi = pl.program_id(1)
    c = pl.program_id(2)
    first, last = _walk_phase(c, walk[1])
    fold = _scale_folds(scale)
    s_scale = None if fold else scale
    q = q_ref[0] * scale if fold else q_ref[0]
    rel = _rel_pos(block_q, block_k)
    q0 = qi * block_q
    at, (j_lo, j_a, j_b, j_hi) = _band_k_ranges(
        q0, _band_k_first(qi, c, block_q, block_k, walk), walk[0], block_q,
        block_k, window)

    def tile(j, masked):
        rows = pl.ds(j * block_k, block_k)
        return (k_ref[0, rows, :], v_ref[0, rows, :],
                _band_mask(rel, q0, (at + j) * block_k, window) if masked
                else None)

    # edge (masked), inside (unmasked), edge (masked), as ``_band_loop``
    _fwd_walk(q, tile, [(j_lo, j_a, True), (j_a, j_b, False),
                        (j_b, j_hi, True)], o_ref.at[0], m_ref, l_ref, s_scale,
              first)
    # as ``_fwd_kernel_chunked``: raw (o, m, l) between a block's steps, the
    # last step (the diagonal's) normalises in the kernel. A row its band's
    # first block hides whole takes exp(0) there; the next visible key's
    # alpha = exp(NEG_INF - m) = 0 wipes it, and the diagonal is always
    # visible and always last
    _finish_chunked_fwd(o_ref, lse_ref, m_ref, l_ref, last)


def _swa_fwd(q, k, v, scale, window, block_q, block_k, band, interpret,
             heads, kv_heads):
    """``band``: ``_band_plan``'s two walks; the forward takes the first."""
    BH, S, D = q.shape
    walk = _, steps = band[0]
    kv = _kv_row(heads, kv_heads)
    keys = _band_keys_spec(walk, block_q, block_k, D,
                           lambda b, i, c: (kv(b), i, c))
    out_specs, out_shape, scratch = _chunked_fwd_outputs(
        q, block_q, block_k, lambda b, i, c: (b, i))
    call = pl.pallas_call(
        functools.partial(_swa_fwd_kernel, scale=scale, window=window,
                          block_q=block_q, block_k=block_k, walk=walk),
        grid=(BH, S // block_q, steps),
        in_specs=[pl.BlockSpec((1, block_q, D), lambda b, i, c: (b, i, 0)),
                  keys, keys],
        out_specs=out_specs,
        out_shape=out_shape,
        # m and l are VMEM scratch for a band in one step too: the walk
        # keeps its state there whatever the steps (``_fwd_walk``)
        scratch_shapes=scratch,
        interpret=interpret,
    )
    with annotate("swa_fwd"):
        o32, lse = call(q, k, v)
    return o32.astype(q.dtype), lse


def _swa_bwd_kernel(q_ref, k_ref, v_ref, do_ref, lse_ref, delta_ref, dq_ref,
                    dk_ref, dv_ref, dk_ring, dv_ring, *dq_acc, scale, window,
                    block_q, block_k, walk, plan, groups, seq_len):
    """Single-pass window backward: grid (KV head, query block, head group x
    step of the band), and each score tile of the band — the two dots AND the
    exp — is computed ONCE for all three gradients (five MXU products a tile,
    where a dq and a dkv kernel ran seven and the softmax chain twice), as
    ``_bwd_kernel_chunked`` does for a causal chunk. A step holds one step of
    the KV head's band of K and V and loops ``heads`` of its group's query
    heads' [block_q, D] q and dO blocks against it; a head's dq is whole
    when the band's walk ends and leaves scaled, in the operands' dtype. dk
    and dv of the KV head accumulate — over the query blocks that see a key
    and over the group's heads — in two float32 rings of ``ring`` rows, key
    row r in slot r mod ring: after query block ``i`` every key below
    ``(i + 1) * block_q - window + 1`` is final, so block ``i - lag`` (of
    ``block_q`` rows) leaves, once, in the operands' dtype, and its slot is
    zeroed for the rows that take it next; the ``lag`` steps past the
    sequence's last query block run no product and only let the last
    blocks out.

    The tile is held TRANSPOSED, [k, q], as the chunked and the whole-row
    kernels hold it: lse and delta are the lane-dense rows they are stored
    as, and of p·do, ds·q and dsᵀ·k only the last needs its left operand
    turned."""
    i = pl.program_id(1)
    t = pl.program_id(2)
    per, steps = walk
    lag, ring, heads = plan
    dq_acc, = dq_acc or (None,)         # none for a band in one step
    c = 0 if steps == 1 else t % steps
    first, last = _walk_phase(c, steps)
    fold = _scale_folds(scale)
    s_scale = None if fold else scale
    rel = -_rel_pos(block_k, block_q)               # query - key
    q0 = i * block_q

    @pl.when((i == 0) & (t == 0))
    def _clear():
        dk_ring[...] = jnp.zeros_like(dk_ring)
        dv_ring[...] = jnp.zeros_like(dv_ring)

    @pl.when(i < seq_len // block_q)
    def _tiles():
        at, ranges = _band_k_ranges(
            q0, _band_k_first(i, c, block_q, block_k, walk), per, block_q,
            block_k, window)
        if first is not True:
            # dq of the step's heads, between the steps of a band's walk
            @pl.when(first)
            def _start():
                dq_acc[...] = jnp.zeros_like(dq_acc)

        def head(h, _):
            q = q_ref[h] * scale if fold else q_ref[h]
            do = do_ref[h]
            lse = _stat_row(lse_ref, (h,), 0, block_q)
            delta = _stat_row(delta_ref, (h,), 0, block_q)

            def body(j, dq, masked):
                rows = pl.ds(pl.multiple_of(j * block_k, block_k), block_k)
                k = k_ref[0, rows, :]
                mask = (_band_mask(rel, q0, (at + j) * block_k, window)
                        if masked else None)
                p, ds = _bwd_ds_block(k, v_ref[0, rows, :], lse, delta, q, do,
                                      mask, s_scale)
                slot = pl.ds(pl.multiple_of(((at + j) * block_k) % ring,
                                            block_k), block_k)
                dv_ring[slot, :] += jax.lax.dot(
                    p, do, preferred_element_type=jnp.float32)
                dk_ring[slot, :] += jax.lax.dot(
                    ds, q, preferred_element_type=jnp.float32)
                return dq + jax.lax.dot_general(
                    ds, k, (((0,), (0,)), ((), ())),
                    preferred_element_type=jnp.float32)

            dq = _band_loop(ranges, body, jnp.zeros(q.shape, jnp.float32)
                            if first is True else dq_acc[h])

            # dq = scale · Σ ds·k: once, where the band's walk ends
            def _whole():
                dq_ref[h] = (dq * scale).astype(dq_ref.dtype)

            if last is True:
                _whole()
            else:
                dq_acc[h] = dq
                pl.when(last)(_whole)
            return 0

        jax.lax.fori_loop(0, heads, head, 0)

    @pl.when((i >= lag) & (t == groups * steps - 1))
    def _leave():
        slot = pl.ds(pl.multiple_of(((i - lag) * block_q) % ring, block_q),
                     block_q)
        # dk = scale · Σ dsᵀ·q: a pre-scaled q has carried it
        dk = dk_ring[slot, :] if fold else dk_ring[slot, :] * scale
        dk_ref[0] = dk.astype(dk_ref.dtype)
        dv_ref[0] = dv_ring[slot, :].astype(dv_ref.dtype)
        dk_ring[slot, :] = jnp.zeros((block_q, dk_ring.shape[1]), jnp.float32)
        dv_ring[slot, :] = jnp.zeros((block_q, dv_ring.shape[1]), jnp.float32)


def _swa_bwd(q, k, v, o, lse, do, scale, window, block_q, block_k, band,
             interpret, heads, kv_heads):
    """(dq [B * heads, S, D], dk, dv [B * kv_heads, S, D]), all in the
    operands' dtype, from ONE call. ``band``: ``_band_plan``'s pair."""
    BH, S, D = q.shape
    BHkv = k.shape[0]
    delta = jnp.sum(do.astype(jnp.float32) * o.astype(jnp.float32),
                    axis=-1).reshape(lse.shape)
    piece = lse.shape[-1]
    walk, plan = band
    steps = walk[1]
    lag, ring, per_step = plan
    groups = BH // BHkv // per_step
    nq = S // block_q
    if 2 * ring * max(D, _LANES) * 4 > _BWD_VMEM_BYTES // 2:
        raise ValueError(
            f"window attention (window={window}) over S={S}, head_dim {D}: "
            f"the backward's dk / dv ring of {ring} rows passes half the "
            f"{_BWD_VMEM_BYTES} bytes of scoped VMEM the kernel may take; "
            "shard the sequence first (parallel/ring_attention.py)")

    def held(b, i, t):
        """(head group, query block, step of the band) of a grid step; the
        flush steps stay on the last of each, so nothing is fetched."""
        live = i < nq
        return (b * groups + jnp.where(live, t // steps, groups - 1),
                jnp.minimum(i, nq - 1), jnp.where(live, t % steps, steps - 1))

    keys = _band_keys_spec(walk, block_q, block_k, D,
                           lambda b, i, t: (b,) + held(b, i, t)[1:])
    rows = pl.BlockSpec((per_step, block_q, D),
                        lambda b, i, t: held(b, i, t)[:2] + (0,))
    stat = pl.BlockSpec((per_step, block_q // piece, 1, piece),
                        lambda b, i, t: held(b, i, t)[:2] + (0, 0))
    left = pl.BlockSpec((1, block_q, D),
                        lambda b, i, t: (b, jnp.maximum(i - lag, 0), 0))
    call = pl.pallas_call(
        functools.partial(_swa_bwd_kernel, scale=scale, window=window,
                          block_q=block_q, block_k=block_k, walk=walk,
                          plan=plan, groups=groups, seq_len=S),
        grid=(BHkv, nq + lag, groups * steps),
        in_specs=[rows, keys, keys, rows, stat, stat],
        out_specs=[rows, left, left],
        out_shape=[jax.ShapeDtypeStruct((BH, S, D), q.dtype),
                   jax.ShapeDtypeStruct((BHkv, S, D), k.dtype),
                   jax.ShapeDtypeStruct((BHkv, S, D), v.dtype)],
        scratch_shapes=[pltpu.VMEM((ring, D), jnp.float32)] * 2
        + ([pltpu.VMEM((per_step, block_q, D), jnp.float32)]
           if steps > 1 else []),
        interpret=interpret,
        compiler_params=None if interpret else pltpu.CompilerParams(
            vmem_limit_bytes=_BWD_VMEM_BYTES),
    )
    with annotate("swa_bwd"):
        return tuple(call(q, k, v, do, lse, delta))


@functools.partial(jax.custom_vjp, nondiff_argnums=(3, 4, 5, 6, 7, 8, 9, 10))
def _flash_attention_swa(q, k, v, scale, window, block_q, block_k, band,
                         interpret, heads, kv_heads):
    return _swa_fwd(q, k, v, scale, window, block_q, block_k, band,
                    interpret, heads, kv_heads)[0]


def _flash_attention_swa_fwd(q, k, v, scale, window, block_q, block_k, band,
                             interpret, heads, kv_heads):
    o, lse = _name_residuals(*_swa_fwd(
        q, k, v, scale, window, block_q, block_k, band, interpret, heads,
        kv_heads))
    return o, (q, k, v, o, lse)


def _flash_attention_swa_bwd(scale, window, block_q, block_k, band,
                             interpret, heads, kv_heads, residuals, do):
    q, k, v, o, lse = residuals
    _named["closed"] = True
    return _swa_bwd(q, k, v, o, lse, do, scale, window, block_q, block_k,
                    band, interpret, heads, kv_heads)


_flash_attention_swa.defvjp(_flash_attention_swa_fwd,
                            _flash_attention_swa_bwd)


# ---------------------------------------------------------------- public op

def _dispatch_fwd(q, k, v, scale, causal, block_q, block_k, chunk,
                  interpret, heads=0, kv_heads=0):
    if chunk:
        # the head counts only where K/V carry fewer heads than q
        gqa = (heads, kv_heads) if heads and kv_heads \
            and heads != kv_heads else ()
        return _flash_fwd_chunked(q, k, v, scale, causal, block_q, block_k,
                                  chunk, interpret, *gqa)
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
    o, lse = _name_residuals(*_dispatch_fwd(
        q, k, v, scale, causal, block_q, block_k, chunk, interpret, heads,
        kv_heads))
    return o, (q, k, v, o, lse)


def _flash_attention_bwd(scale, causal, block_q, block_k, chunk, interpret,
                         heads, kv_heads, residuals, do):
    q, k, v, o, lse = residuals
    _named["closed"] = True
    gqa = bool(heads and kv_heads and heads != kv_heads)
    if gqa:
        B = q.shape[0] // heads
        rep = heads // kv_heads
        S = k.shape[1]
    if chunk:
        # grouped-query K/V are read in place (``_kv_row``)
        dq, dk, dv = _flash_bwd_chunked(q, k, v, o, lse, do, scale, causal,
                                        block_q, block_k, chunk, interpret,
                                        heads, kv_heads)
    else:
        if gqa:
            # the whole-row backward runs the full-head kernel: K/V repeat
            # to [B*H, S, D] HERE (transient, bwd-only)
            def rep_kv(t):
                return jnp.repeat(t.reshape(B, kv_heads, S, -1), rep,
                                  axis=1).reshape(B * heads, S, -1)
            k = rep_kv(k)
            v = rep_kv(v)
        dq, dk, dv = _flash_bwd(q, k, v, o, lse, do, scale, causal,
                                block_q, block_k, interpret)
    if gqa:
        # dk/dv come back per query head: summed over the rep query heads
        # sharing each KV head
        def sum_rep(t):
            return t.reshape(B, kv_heads, rep, S, t.shape[-1]).sum(axis=2) \
                .astype(t.dtype).reshape(B * kv_heads, S, t.shape[-1])
        dk = sum_rep(dk)
        dv = sum_rep(dv)
    return dq, dk, dv


_flash_attention.defvjp(_flash_attention_fwd, _flash_attention_bwd)


def tile_overcompute(S, block_q, block_k, chunk, causal):
    """Score elements the chosen loops compute over the elements a causal
    (or full) softmax needs, forward and backward together: 1.0 would be
    no waste. The whole-row kernels waste half of each diagonal SQUARE of
    one strip (1.249 at S 1024 with strips of 256; 1.50 when the square
    was the 512 block); the chunked kernels waste half of each diagonal
    BLOCK (1.125 at S 4096 / 512)."""
    if not causal:
        return 1.0
    if chunk:
        def walked(rows, cols):
            return sum(rows * cols * -(-((i + 1) * rows) // cols)
                       for i in range(S // rows))
        computed = 2 * walked(block_q, block_k)
    else:
        def walked(block):
            strip = _pick_strip(block)
            squares = block // strip
            diagonal = strip * strip * squares * (squares + 1) // 2
            return sum(diagonal + block * i * block
                       for i in range(S // block))
        computed = walked(block_q) + walked(block_k)
    return computed / (S * (S + 1))


def _window_tiles(S, block_q, block_k, window):
    """Score tiles a head's band takes in one walk over its query blocks:
    the forward's, and the single-pass backward's again."""
    return sum(_band_tile_counts(S, block_q, block_k, window))


def window_tile_overcompute(S, block_q, block_k, window):
    """``tile_overcompute`` for the window kernels: score elements of the
    blocks the band's two walks touch (forward and backward, each over the
    query blocks) over the ``S*W - W(W-1)/2`` elements a head's band holds,
    twice. Blocks of 512 at W 512 compute 2.0 x, 256 1.5 x, 128 1.25 x."""
    window = min(window, S)
    return _window_tiles(S, block_q, block_k, window) * block_q * block_k \
        / (S * window - window * (window - 1) // 2)


def window_tiles_per_grid_step(S, block_q, block_k, window, band):
    """Score tiles the two window calls of a head compute over the grid
    steps they take under ``band`` (``_band_plan``): a forward step is a
    query block's band — its tile count less what the sequence's first
    blocks clip, 7.9 of 9 at S 16,384 / W 4,096 / 512 — and a backward step
    that of the ``heads`` query heads it holds, over the ``lag`` steps more
    that let the last key blocks out: 13.4 a step at that shape with 7
    heads, 3.5 at W 512 with 8, where one tile a step read under 1."""
    (_, steps), (lag, _, heads) = band
    blocks = S // block_q
    return 2 * _window_tiles(S, block_q, block_k, min(window, S)) / (
        blocks * steps + (blocks + lag) * steps / heads)


def grid_steps_walked(S, block_q, block_k, chunk, causal):
    """(grid steps a head of the two chunked kernels of one call — forward
    and the single-pass backward: ``_pair_walk`` — and of the rectangular
    (S / block, S / chunk) grids those would be): 160 of 256 at S 16,384
    with blocks of 512 and chunks of 4,096."""
    pairs = len(_pair_walk(S, block_q, chunk, causal, False)[0])
    return 2 * pairs, 2 * (S // block_q) * (S // chunk)


_plans_logged = set()


def _note_plan(S, D, dtype, scale, causal, block_q, block_k, chunk,
               heads_per_block=0, window=0, band=None, value_dim=0):
    """Trace-time engagement record of one flash call: the gauges
    ``attention/flash_tile_overcompute``,
    ``attention/flash_heads_per_block`` (heads a 128-lane column block of
    [B, S, H*D] operands; 0 for a head-major call) and, for a chunked call,
    ``attention/flash_grid_steps_walked_share`` (``grid_steps_walked``: grid
    steps of its two kernels over those of the rectangular grid — 0.625
    causal at S 16,384 / 512 / 4,096, 1.0 where nothing is masked) and
    ``attention/flash_chunk_rows`` (sequence rows a grid step holds: the
    chunk), for every call ``attention/flash_bwd_products_per_tile`` (MXU
    products a score tile of its backward takes: 5, each tile computed once)
    and ``attention/flash_bwd_dq_slabs`` (float32 dq slabs its backward
    leaves to be added: one a key chunk, 0 for a whole-row call) and, once
    per distinct shape, a log line of the layout (the
    operands' and the log-sum-exp's) and loop structure chosen for it.
    ``window``: a call of the window kernels under ``band``
    (``_band_plan``) — the gauges ``attention/window_tile_overcompute``,
    ``attention/window_tiles_per_grid_step`` and
    ``attention/window_bwd_tiles_per_grid_step`` (query heads a backward
    step holds x tiles of the band's step: 63 at W 4,096 / 7 heads, 16 at
    W 512 / 8; a plan that fell to fewer heads or more steps reads lower)
    and the band's plan instead.
    ``value_dim``: the value width of a call whose q·k width ``D`` is another
    (latent attention) — the gauges ``attention/mla_qk_dim`` and
    ``attention/mla_v_dim``, the widths as the kernels saw them."""
    piece = _stat_piece(block_q, block_k)
    if window:
        over = window_tile_overcompute(S, block_q, block_k, window)
        tiles = window_tiles_per_grid_step(S, block_q, block_k, window, band)
        default_registry().gauge("attention/window_tile_overcompute").set(
            over)
        default_registry().gauge("attention/window_tiles_per_grid_step").set(
            tiles)
        (per, steps), (lag, ring, heads) = band
        default_registry().gauge(
            "attention/window_bwd_tiles_per_grid_step").set(heads * per)
        plan = (S, D, jnp.dtype(dtype).name, window, block_q, block_k, band)
        if plan not in _plans_logged:
            _plans_logged.add(plan)
            logger.info(
                f"flash attention S={S} D={D} {plan[2]} window={window}: "
                f"layout [B*H, S, D] head-major, lse [B*H, S/{piece}, 1, "
                f"{piece}], block_q={block_q} block_k={block_k}, a query "
                f"block's band is {steps} grid step(s) of "
                f"{per * block_k} keys; the backward holds {heads} head(s) "
                f"of a group a step, 5 products a tile, dk and dv in a ring "
                f"of {ring} rows that lets a block out {lag} step(s) on, "
                f"{tiles:.2f} score tiles a grid step, scale "
                f"{'on q' if _scale_folds(scale) else 'on scores'}"
                f", computes {over:.3f} x the band's scores")
        return
    over = tile_overcompute(S, block_q, block_k, chunk, causal)
    default_registry().gauge("attention/flash_tile_overcompute").set(over)
    default_registry().gauge("attention/flash_heads_per_block").set(
        heads_per_block)
    walked = ""
    # every family's backward computes a score tile once (five products:
    # k·qᵀ, v·doᵀ, p·do, ds·q, dsᵀ·k); a chunked call's dq leaves as one
    # float32 slab a key chunk for ``_sum_dq_slabs`` (a single slab leaves
    # whole: nothing is added), a whole row's is VMEM-resident (0 slabs)
    slabs = S // chunk if chunk else 0
    default_registry().gauge("attention/flash_bwd_products_per_tile").set(5)
    default_registry().gauge("attention/flash_bwd_dq_slabs").set(slabs)
    if chunk:
        steps, rectangle = grid_steps_walked(S, block_q, block_k, chunk,
                                             causal)
        default_registry().gauge(
            "attention/flash_grid_steps_walked_share").set(steps / rectangle)
        default_registry().gauge("attention/flash_chunk_rows").set(chunk)
        walked = (f" ({steps} of {rectangle} (block, chunk) pairs walked, "
                  f"forward + backward; backward 5 products a tile, dq in "
                  f"{slabs} slab(s))")
    if value_dim:
        default_registry().gauge("attention/mla_qk_dim").set(D)
        default_registry().gauge("attention/mla_v_dim").set(value_dim)
        walked += f", values and output {value_dim} wide"
    plan = (S, D, jnp.dtype(dtype).name, causal, block_q, block_k, chunk,
            heads_per_block, value_dim)
    if plan not in _plans_logged:
        _plans_logged.add(plan)
        strip = 0 if chunk else _pick_strip(block_q)
        layout = ((f"[B, S, H*D] column blocks of {heads_per_block} heads"
                   f", lse [B, H, S/{piece}, 1, {piece}]")
                  if heads_per_block else
                  f"[B*H, S, D] head-major, lse [B*H, S/{piece}, 1, {piece}]")
        logger.info(
            f"flash attention S={S} D={D} {plan[2]} causal={causal}: "
            f"layout {layout}, "
            f"block_q={block_q} block_k={block_k} strip={strip} "
            f"chunk={chunk}{walked} scale "
            f"{'on q' if _scale_folds(scale) else 'on scores'}"
            f", computes {over:.3f} x the scores needed")


def _pick_block(S, requested, interpret, whole_row):
    """The widest grid block S allows, up to 1024 rows for the whole-row
    kernels and 512 for the chunked ones: a grid step has a fixed cost
    (at [160, 1024, 64] blocks of 256 measured 1.8 x the forward time of
    blocks of 512, and those 1.2 x blocks of 1024: PERF.md, PR 28), and
    the causal structure finer than a block lives INSIDE it
    (``_pick_strip``). For S not divisible by 512 take the largest
    power-of-two divisor so e.g. S=768/1280/2560 keep the flash kernel
    instead of silently materializing [S, S] scores in the reference
    fallback. 0: no block tiles S."""
    if requested:
        return requested
    top = 64 if interpret else 1024 if whole_row else 512
    for cand in (1024, 512, 256, 128, 64, 32):
        if cand <= top and S % cand == 0:
            return cand
    # irregular short sequences (e.g. S=80): one block spanning S keeps
    # the kernel path, matching the old min(block, S) behavior
    return S if S <= top else 0


def _pick_chunk(S, D, Dv, itemsize, block_q, block_k):
    """Rows of a sequence chunk of the chunked kernels where the caller names
    none: the widest of ``_CHUNK_ROWS`` whose two streamed operands — K
    [chunk, D] and V [chunk, Dv] forward and in dq, Q and dO in dkv, each
    padded to whole 128-lane tiles as VMEM holds them — fit ``_CHUNK_BYTES``
    and that tiles S in whole blocks: 4,096 rows in bf16 at head_dim 64 and
    128 (2 MiB) and at latent attention's 192 / 128 (3 MiB), 2,048 at
    head_dim 256, half as many in float32. A q·k width that is not the value
    width, which no other family takes, may also go as ONE chunk of S rows.
    0: nothing tiles S."""
    row = (-(-D // _LANES) + -(-Dv // _LANES)) * _LANES * itemsize
    for cand in _CHUNK_ROWS + ((S,) if Dv != D else ()):
        if cand * row <= _CHUNK_BYTES and S % cand == 0 \
                and cand % block_q == 0 and cand % block_k == 0:
            return cand
    return 0


# The window kernels take the chunked family's grid blocks (``_pick_block``:
# up to 512 rows) and a block's whole band in one grid step (``_band_plan``).
# Measured on a v5e at [64 / 8, 16384, 128] bf16, W 512
# (tests/perf/swa_bench.py; PERF.md Findings PR 33, at one aligned chunk a
# step): a grid step's fixed cost outweighs what a finer tiling saves —
# blocks of 512 compute 2.0 x the band's scores and ran 13.8 ms forward /
# 36.5 forward + backward, 256 (1.5 x) 19.3 / 48.2 (16.2 / 42.1 at two
# blocks a chunk), 128 (1.25 x) 37.5 / 92.6


def _flash_attention_window(q, k, v, scale, window, block_q, block_k, chunk,
                            interpret):
    """``flash_attention``'s window branch: the band kernels or a raise,
    never [S, S] scores."""
    _refuse_unequal_widths("window", q, k, v)
    B, H, S, D = q.shape
    Hkv = k.shape[1]
    block_q, block_k = (_pick_block(S, b, interpret, False)
                        for b in (block_q, block_k))
    if not block_q or not block_k or S % block_q or S % block_k:
        raise ValueError(
            f"window attention (window={window}) over S={S}: no block "
            f"tiles the sequence (block_q={block_q}, block_k={block_k}) and "
            "a window layer never falls back to [S, S] scores")
    if chunk and (chunk % block_q or chunk % block_k):
        raise ValueError(
            f"chunk={chunk} (the most rows of a band a grid step holds) must "
            f"be a multiple of block_q={block_q} and block_k={block_k}")
    band = _band_plan(S, block_q, block_k, int(window),
                      max(D, _LANES) * jnp.dtype(q.dtype).itemsize, H // Hkv,
                      int(chunk or 0))
    _note_plan(S, D, q.dtype, scale, True, block_q, block_k, 0,
               window=window, band=band)
    o = _flash_attention_swa(
        q.reshape(B * H, S, D), k.reshape(B * Hkv, S, D),
        v.reshape(B * Hkv, S, D), scale, int(window), block_q, block_k,
        band, bool(interpret), H, Hkv)
    return o.reshape(B, H, S, D)


def flash_attention(q, k, v, causal=False, scale=None, block_q=None,
                    block_k=None, interpret=None, chunk=None, window=None):
    """[B, H, S, D] (head-major) flash attention: every kernel family,
    grouped-query K/V included. Falls back to the jnp reference for
    shapes the kernel can't tile (tiny S/D in unit tests). ``chunk``
    forces the long-S chunked kernels (auto-selected past the VMEM row
    budget); it must divide S and be a multiple of both block sizes.
    A caller whose q, k, v are columns of [B, S, H*D] arrays — a fused
    projection — has ``flash_attention_bse``, which spares the
    transposes into this layout where the whole-row kernels run.

    ``window`` (with ``causal``): key j is visible to query i iff
    ``0 <= i - j < window``. A window shorter than the sequence takes the
    window kernels, whose grid step holds a block's whole band (``chunk``
    there: a cap on the band's rows a step holds, default the kernels' VMEM
    budget; a shape they do not take RAISES); one that covers it is causal
    attention."""
    B, H, S, D = q.shape
    Dv = v.shape[-1]
    from deepspeed_tpu.ops.attention import (check_qkv_shapes,
                                             reference_attention)
    check_qkv_shapes(q, k, v)
    scale = float(scale) if scale is not None else 1.0 / float(np.sqrt(D))
    if interpret is None:
        interpret = _interpret_default()
    if window is not None:
        if not causal or window < 1:
            raise ValueError("a window is a causal band of >= 1 keys: "
                             f"causal={causal}, window={window}")
        assert v.shape[1] == k.shape[1] and H % k.shape[1] == 0, \
            (q.shape, k.shape)
        if window < S:
            return _flash_attention_window(q, k, v, scale, window, block_q,
                                           block_k, chunk, interpret)
    itemsize = jnp.dtype(q.dtype).itemsize
    # a q·k width that is not the value width is the chunked family's at
    # EVERY S: one path, and nothing of it falls to [S, S] scores
    unequal = Dv != D
    whole_row = chunk is None and not unequal \
        and S * D * itemsize <= _UNCHUNKED_ROW_BYTES
    block_q = _pick_block(S, block_q, interpret, whole_row)
    block_k = _pick_block(S, block_k, interpret, whole_row)
    Hkv = k.shape[1]
    assert v.shape[1] == Hkv and H % Hkv == 0, (q.shape, k.shape)

    def no_tiling(why):
        if unequal:
            raise ValueError(
                f"flash attention with a q·k width of {D} and a value width "
                f"of {Dv} runs in the chunked kernels alone, and {why}: "
                f"q {tuple(q.shape)}, v {tuple(v.shape)}")
        # reference_attention repeats reduced-head K/V itself
        return reference_attention(q, k, v, causal=causal, scale=scale)

    if not block_q or not block_k or S % block_q or S % block_k:
        return no_tiling(f"no block tiles S={S}")
    if chunk is not None:
        if S % chunk or chunk % block_q or chunk % block_k:
            raise ValueError(
                f"chunk={chunk} must divide S={S} and be a multiple of "
                f"block_q={block_q} and block_k={block_k}")
    if chunk is None and not whole_row:
        # whole-row residency stops fitting scoped VMEM — stream chunks
        chunk = _pick_chunk(S, D, Dv, itemsize, block_q, block_k)
        if not chunk:
            return no_tiling(f"no chunk within {_CHUNK_BYTES} bytes of K "
                             f"and V tiles S={S}")

    chunk = int(chunk) if chunk else 0
    _note_plan(S, D, q.dtype, scale, causal, block_q, block_k, chunk,
               value_dim=Dv if unequal else 0)
    qf = q.reshape(B * H, S, D)
    kf = k.reshape(B * k.shape[1], S, D)
    vf = v.reshape(B * v.shape[1], S, Dv)
    o = _flash_attention(qf, kf, vf, scale, causal, block_q, block_k, chunk,
                         bool(interpret), H, Hkv)
    return o.reshape(B, H, S, Dv)


def flash_attention_bse(q, k=None, v=None, *, heads, causal=False,
                        scale=None, block_q=None, block_k=None,
                        interpret=None):
    """Flash attention on the model's own layout: q, k, v [B, S, H*D] in,
    [B, S, H*D] out — or, with k and v None, ``q`` the fused projection
    [B, S, 3*H*D], whose thirds are read IN PLACE where they start on a
    lane block (H*D % 128 == 0; split here otherwise).

    Where the whole-row kernels take the shape (a row within
    ``_UNCHUNKED_ROW_BYTES``, 128 % D == 0 or D % 128 == 0) they address
    heads as 128-lane column blocks of these arrays, 128 // D heads a
    block, and no head-major copy of q, k, v, o or of their gradients
    exists. Every other shape is transposed into ``flash_attention``'s
    [B, H, S, D] and back. Which it was: the plan's log line and the gauge
    ``attention/flash_heads_per_block`` (0: head-major)."""
    from deepspeed_tpu.ops.attention import from_head_major, to_head_major
    operands = (q,) if k is None else (q, k, v)
    S = q.shape[1]
    _, _, E, plan = _columns(operands, heads)
    D = E // heads
    if interpret is None:
        interpret = _interpret_default()
    itemsize = jnp.dtype(q.dtype).itemsize
    blocks = [_pick_block(S, b, interpret, True) for b in (block_q, block_k)]
    # a column block is max(D, 128) lanes wide whatever D is: the row the
    # kernels hold whole may not outgrow what a padded D=64 row takes
    columns = plan and S * D * itemsize <= _UNCHUNKED_ROW_BYTES \
        and S * plan[2] * itemsize <= 2 * _UNCHUNKED_ROW_BYTES \
        and all(b and S % b == 0 for b in blocks)
    if k is None and not (columns and E % plan[2] == 0):
        operands = tuple(jnp.split(q, 3, axis=-1))
    if not columns:
        return from_head_major(flash_attention(
            *(to_head_major(t, heads) for t in operands), causal=causal,
            scale=scale, block_q=block_q, block_k=block_k,
            interpret=interpret))
    scale = float(scale) if scale is not None else 1.0 / float(np.sqrt(D))
    _note_plan(S, D, q.dtype, scale, causal, *blocks, 0, plan[0])
    return _flash_attention_cols(operands, heads, scale, causal, *blocks,
                                 bool(interpret))


def bwd_dq_slab_rows(S, D, Dv, itemsize):
    """Float32 rows of dq partials a causal backward over ``S`` rows leaves
    in HBM for every sequence row of a head, until ``_sum_dq_slabs`` has
    added them: the (block, chunk) pairs the chunked backward walks x the
    block's rows over S — 2.5 at S 16,384 in chunks of 4,096 — and 0 where
    the row is VMEM-resident whole or the plan is one chunk (dq leaves the
    kernel in the operands' dtype). What ``runtime/remat_budget.py`` counts
    of an attention branch in flight; the plan is ``flash_attention``'s."""
    if Dv == D and S * D * itemsize <= _UNCHUNKED_ROW_BYTES:
        return 0.0
    block = _pick_block(S, None, False, False)
    chunk = block and _pick_chunk(S, D, Dv, itemsize, block, block)
    if not chunk or chunk == S:
        return 0.0
    return len(_pair_walk(S, block, chunk, True, True)[0]) * block / S
