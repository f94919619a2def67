"""Rows back to their tokens — the segmented sum of a dropless expert layer
that holds a share of its experts (``moe/dropless.rows_to_tokens``).

``rows`` [M, H] are the held experts' output rows in expert order, ``tok``
[M] the token of each (``T``: a row of no token, past the rows held); token
t of the result is the float32 sum of the rows r with ``tok[r] == t``. The
rows are brought into token order once (a stable sort of the M keys and one
row gather, XLA ops under the scope ``rows_to_tokens``), and one Pallas kernel
walks them:

- the grid runs over (token block, row tile) pairs in order: a block of
  ``TOKEN_BLOCK`` tokens visits the tiles of ``ROW_TILE`` sorted rows that
  its contiguous run of rows touches (one tile where it has no row at all, so
  that every block is written). Which pair a grid step is arrives as
  scalar-prefetched arrays, as the grouped matmul's group metadata does; the
  grid is sized for the worst case (tiles + blocks), the steps past the last
  pair repeat it and do nothing;
- consecutive steps with the same tile or the same block keep it in VMEM, so
  every row tile up to the last row of a token is read from HBM ONCE, and
  none past it (``rows_walked``); every token block is written once;
- a step multiplies the 0/1 matrix ``[token of the block == token of the
  row]`` with the row tile on the MXU and accumulates in float32. A float32
  row is split into three bfloat16 terms that add up to it exactly, each
  multiplied on its own: a product with 0 or 1 is exact, so what reaches a
  token is the float32 sum of its rows and nothing is rounded to bfloat16 on
  the way. Rows past the last row of a token are zeroed by a select BEFORE
  the product (the grouped matmul leaves them undefined).

No bound on the rows a token has is needed. On other backends the same kernel
runs in Pallas interpret mode, as the grouped matmul does.
"""

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu

from deepspeed_tpu.telemetry.spans import annotate
from deepspeed_tpu.utils.logging import logger

# sorted rows a grid step reads, tokens an output block holds and columns one
# product inside a step spans (the sweep on a v5e: PERF.md Findings PR 36);
# a grid step takes at most _COLS columns, wider rows a second grid axis
ROW_TILE = 128
TOKEN_BLOCK = 128
_LANE_CHUNK = 512
_COLS = 2048


def _interpret_default():
    from deepspeed_tpu.utils.platform import is_tpu_backend
    return not is_tpu_backend()


def rows_walked(n_rows):
    """Rows the kernel reads for ``n_rows`` rows that have a token: whole row
    tiles up to the last of them (one tile where there is none)."""
    return jnp.maximum(-(-n_rows // ROW_TILE), 1) * ROW_TILE


def walk(seg, n_blocks, n_tiles):
    """The (token block, row tile) pair of every grid step, and [how many
    steps hold one, how many rows have a token]. ``seg`` [n_tiles *
    ROW_TILE]: the tokens of the sorted rows, ascending, ``n_blocks *
    TOKEN_BLOCK`` or more where a row has no token."""
    i32 = jnp.int32
    # the first sorted row of every token block, and one past the last
    # block's: n_blocks + 1 searches fused into their row sums
    bounds = jnp.searchsorted(
        seg, jnp.arange(n_blocks + 1, dtype=i32) * TOKEN_BLOCK, side="left",
        method="compare_all").astype(i32)
    # (a block with no row visits the tile its neighbour does, and none past
    # the last row of a token)
    first = jnp.minimum(bounds[:-1] // ROW_TILE,
                        jnp.maximum((bounds[-1] - 1) // ROW_TILE, 0))
    last = jnp.maximum((bounds[1:] - 1) // ROW_TILE, first)
    visits = last - first + 1
    ends = jnp.cumsum(visits)
    steps = n_tiles + n_blocks          # first[i + 1] >= last[i]
    block = jnp.repeat(jnp.arange(n_blocks, dtype=i32), visits,
                       total_repeat_length=steps)
    tile = first[block] + jnp.arange(steps, dtype=i32) \
        - (ends - visits)[block]
    return (block, jnp.minimum(tile, last[block]),
            jnp.stack([ends[-1], bounds[-1]]))


def _exact_terms(z):
    """Terms of ``z``'s dtype-or-narrower that the MXU multiplies exactly by
    0 / 1 and that add up to ``z``: a float32 array as three bfloat16 ones."""
    if z.dtype != jnp.float32:
        return [z]
    terms = []
    for _ in range(3):
        t = z.astype(jnp.bfloat16)
        terms.append(t)
        z = z - t.astype(jnp.float32)
    return terms


def _rows_to_tokens_kernel(block_ref, tile_ref, count_ref, seg_ref, z_ref,
                           o_ref, acc_ref):
    g = pl.program_id(1)
    block, tile = block_ref[g], tile_ref[g]
    rb, cols = z_ref.shape
    tb = o_ref.shape[0]
    opens = (g == 0) | (block_ref[jnp.maximum(g - 1, 0)] != block)

    @pl.when(g < count_ref[0])
    def _():
        tokens = block * tb + jax.lax.broadcasted_iota(jnp.int32, (tb, 1), 0)
        hit = (tokens == seg_ref[...]).astype(                 # [tb, rb]
            jnp.bfloat16 if z_ref.dtype == jnp.float32 else z_ref.dtype)
        held = tile * rb + jax.lax.broadcasted_iota(
            jnp.int32, (rb, 1), 0) < count_ref[1]
        for c in range(0, cols, _LANE_CHUNK):
            span = slice(c, min(c + _LANE_CHUNK, cols))
            z = jnp.where(held, z_ref[:, span], 0)
            acc = jnp.where(opens, 0.0, acc_ref[:, span])
            for term in _exact_terms(z):
                acc += jnp.dot(hit, term, preferred_element_type=jnp.float32)
            acc_ref[:, span] = acc
            o_ref[:, span] = acc.astype(o_ref.dtype)


_shapes_logged = set()


def _note_call(M, H, T, dtype, interpret):
    """Trace-time engagement record: once per distinct shape, a log line."""
    key = (M, H, T, jnp.dtype(dtype).name, interpret)
    if key not in _shapes_logged:
        _shapes_logged.add(key)
        logger.info(
            f"rows_to_tokens [{M}, {H}] {key[3]} -> [{T}, {H}]: Pallas "
            f"kernel over rows in token order, {ROW_TILE} rows a tile, "
            f"{TOKEN_BLOCK} tokens a block, float32 sums on the MXU"
            f"{' (interpreter)' if interpret else ''}")


def sum_rows_by_token(rows, tok, T, interpret=None):
    """[M, H] rows, [M] int32 tokens in [0, T] -> [T, H] in ``rows.dtype``:
    token t is the float32 sum of the rows r with ``tok[r] == t``; a row with
    ``tok[r] == T`` goes nowhere and may hold anything."""
    if interpret is None:
        interpret = _interpret_default()
    (M, H), T = rows.shape, int(T)
    _note_call(M, H, T, rows.dtype, interpret)
    n_tiles, n_blocks = -(-M // ROW_TILE), -(-T // TOKEN_BLOCK)
    Mp, Tp, Hp = n_tiles * ROW_TILE, n_blocks * TOKEN_BLOCK, -(-H // 128) * 128
    cols = next(c for c in range(min(Hp, _COLS), 0, -128) if Hp % c == 0)
    with annotate("rows_to_tokens"):
        tok = tok.astype(jnp.int32)
        # the sorted keys come with their rows (an argsort and a gather of
        # the keys by it: the gather of M scalars is ten times the sort)
        seg, by_tok = jax.lax.sort((tok, jnp.arange(M, dtype=jnp.int32)),
                                   num_keys=1, is_stable=True)
        # whole tiles: the rows added have no token and are never read
        seg = jnp.pad(jnp.where(seg < T, seg, Tp), (0, Mp - M),
                      constant_values=Tp)
        z = jnp.take(rows, jnp.pad(by_tok, (0, Mp - M)), axis=0, mode="clip")
        z = jnp.pad(z, ((0, 0), (0, Hp - H)))
        block, tile, counts = walk(seg, n_blocks, n_tiles)
        out = pl.pallas_call(
            _rows_to_tokens_kernel,
            out_shape=jax.ShapeDtypeStruct((Tp, Hp), rows.dtype),
            grid_spec=pltpu.PrefetchScalarGridSpec(
                num_scalar_prefetch=3,
                grid=(Hp // cols, n_tiles + n_blocks),
                in_specs=[
                    pl.BlockSpec((1, ROW_TILE),
                                 lambda h, g, block, tile, n: (0, tile[g])),
                    pl.BlockSpec((ROW_TILE, cols),
                                 lambda h, g, block, tile, n: (tile[g], h))],
                out_specs=pl.BlockSpec(
                    (TOKEN_BLOCK, cols),
                    lambda h, g, block, tile, n: (block[g], h)),
                scratch_shapes=[pltpu.VMEM((TOKEN_BLOCK, cols),
                                           jnp.float32)]),
            compiler_params=pltpu.CompilerParams(
                dimension_semantics=("parallel", "arbitrary")),
            interpret=interpret,
        )(block, tile, counts, seg.reshape(1, Mp), z)
        return out[:T, :H]
