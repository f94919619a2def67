"""Flash attention under the block-diffusion training mask.

A block-diffusion step (BD3-LMs, arXiv 2503.09573) runs every layer over
``2L`` rows: the ``L`` NOISED tokens of a sequence, then its ``L`` CLEAN
ones, both at positions ``0 .. L-1``. With ``blk(r) = (r mod L) //
block_length`` a query row ``r`` may see key row ``s`` where

    r noised, s noised, blk(s) == blk(r)      its own block, both directions
    r noised, s clean,  blk(s) <  blk(r)      the clean text before its block
    r clean,  s clean,  blk(s) <= blk(r)      block-causal

and nothing else: ``L^2 + L * block_length`` of the ``4 L^2`` pairs. The
kernels here are the chunked flash kernels of ``flash_attention.py`` (their
tile math is imported, not copied) over ANOTHER WALK: grid ``(B*H, pairs)``,
a pair one (query block, key chunk) that holds an allowed score, in the
order ``_bd_walk`` gives — a query block's pairs together for the forward,
a key chunk's together for the single-pass backward (each score tile formed
once for dq, dk and dv: five MXU products). The clean -> noised quarter and
both upper triangles are never visited; of the noised -> noised quarter only
the diagonal. With ``n = L / block`` the tiles walked are ``n^2 + 2n`` a
head (``tiles_walked``). K and V are ONE ``[B*Hkv, 2L, D]`` array each: the
clean half is read for both query halves through the index maps, grouped
query heads through ``_kv_row``; no ``[2L, 2L]`` array of any dtype exists.

What a grid step does is decided with numpy at trace time and handed to the
call as scalar-prefetch arrays: the key blocks of its chunk to take without
a mask, ``[lo, full)``, and the one on the diagonal, ``[full, hi)``, whose
mask is one of three rules on ``blk(query) - blk(key)`` (``== 0``, ``> 0``,
``>= 0``). A query block is as long as a whole number of diffusion blocks
(or a diffusion block a whole number of query blocks, and then no tile is
masked at all), so the block structure finer than a tile lives inside the
diagonal tile as the causal diagonal does in the flash kernels.

A noised query block takes its noised tile FIRST: every row sees itself
there, so the running maximum is real before a clean tile in which a row of
the sequence's first block sees nothing (its scores are ``NEG_INF``, its
probabilities exp(NEG_INF - m) = 0).

Off a TPU the interpreter runs the same kernels. The dense-mask oracle is
``ops/attention.reference_block_diffusion_attention``.
"""

import functools
import importlib
import math

import numpy as np
import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu

from deepspeed_tpu.telemetry.registry import default_registry
from deepspeed_tpu.telemetry.spans import annotate
from deepspeed_tpu.utils.logging import logger

# the module: the package's attribute of this name is the function
fa = importlib.import_module("deepspeed_tpu.ops.pallas.flash_attention")

# the rule of a grid step's diagonal tile, on d = blk(query) - blk(key):
# d == 0 (a noised query and the noised keys), d > 0 (a noised query and the
# clean keys), d >= 0 (a clean query and the clean keys)
_OWN, _BEFORE, _UP_TO = 0, 1, 2
# rows of a key chunk where the caller names none, widest first (each K + V
# pair of a grid step within ``flash_attention._CHUNK_BYTES``). Measured on a
# v5e at the SDAR cell's [32 / 4, 2 x 8192, 128] bf16, block length 4, tiles
# of 512 (tests/perf/bd_attention_bench.py, wall clock, forward / forward +
# backward a call; my chip runs, PR 60): chunks of 1,024 17.8 / 41.7 ms, 2,048
# 15.1 / 37.0, 4,096 14.3 / 35.1, 8,192 15.1 / 35.7; tiles of 256 (1.062 x
# the pairs where 512 compute 1.124 x) 25.0 / 53.8 at chunks of 2,048: a
# grid step's fixed cost, not its tile, is what these kernels pay
_CHUNK_ROWS = (4096, 2048, 1024, 512, 256, 128, 64, 32, 16, 8)

_plans_logged = set()


def allowed_pairs(L, block_length):
    """(query, key) pairs a head's mask allows over the 2L rows:
    ``L Bk + L (L - Bk) / 2 + L (L + Bk) / 2 = L^2 + L Bk``."""
    return L * L + L * block_length


def _tile_states(L, block_length, block, qi):
    """What query block ``qi`` (of ``2L / block``) takes of each key block:
    None, "full" or the rule of a masked tile."""
    n = L // block
    clean, i = divmod(qi, n)
    state = [None] * (2 * n)
    if block_length >= block:           # a tile is allowed whole or not
        m = block_length // block
        g = i // m
        if clean:
            state[n:n + (g + 1) * m] = ["full"] * ((g + 1) * m)
        else:
            state[g * m:(g + 1) * m] = ["full"] * m
            state[n:n + g * m] = ["full"] * (g * m)
        return state
    state[n:n + i] = ["full"] * i
    state[n + i] = _UP_TO if clean else _BEFORE
    if not clean:
        state[i] = _OWN
    return state


@functools.lru_cache(maxsize=None)
def _bd_walk(L, block_length, block, chunk, by_chunk):
    """The second grid dimension: one row a grid step of (query block, key
    chunk, lo, full, hi, rule, first, last) as eight int32 arrays. The
    forward's order keeps a query block's pairs together (its noised chunk
    first, then the clean chunks ascending), ``first`` / ``last`` marking
    the block's run; ``by_chunk`` the same pairs a key chunk's together, its
    query blocks ascending, the flags marking the chunk's run."""
    n, cb = L // block, chunk // block
    steps = []
    for qi in range(2 * n):
        state = _tile_states(L, block_length, block, qi)
        for c in range(2 * n // cb):
            tiles = state[c * cb:(c + 1) * cb]
            held = [j for j, s in enumerate(tiles) if s is not None]
            if not held:
                continue
            lo, hi = held[0], held[-1] + 1
            assert held == list(range(lo, hi)), (qi, c, tiles)
            masked = [j for j in held if tiles[j] != "full"]
            assert masked in ([], [hi - 1]), (qi, c, tiles)
            steps.append((qi, c, lo, hi - len(masked), hi,
                          tiles[hi - 1] if masked else _OWN))
    if by_chunk:
        steps.sort(key=lambda s: (s[1], s[0]))
    run = [s[1 if by_chunk else 0] for s in steps]
    first = [t == 0 or run[t - 1] != r for t, r in enumerate(run)]
    last = [t == len(run) - 1 or run[t + 1] != r for t, r in enumerate(run)]
    walk = tuple(np.ascontiguousarray(x, np.int32)
                 for x in (*zip(*steps), first, last))
    for x in walk:
        x.flags.writeable = False
    return walk


def tiles_walked(L, block_length, block, chunk=0):
    """Score tiles of ``block`` x ``block`` a head's walk computes in one
    pass (the forward's, and the single-pass backward's again):
    ``n^2 + 2n`` with ``n = L / block`` where a diffusion block is shorter
    than a tile."""
    _, _, lo, _, hi, *_ = _bd_walk(L, block_length, block, chunk or block,
                                   False)
    return int(np.sum(hi - lo))


def tile_overcompute(L, block_length, block):
    """Score elements the walked tiles compute over the pairs the mask
    allows, forward and backward alike: 1.124 at L 8,192 / block length 4
    with tiles of 512."""
    return tiles_walked(L, block_length, block) * block * block \
        / allowed_pairs(L, block_length)


def _block_diff(block, sub, query_is_row):
    """blk(query) - blk(key) over a diagonal tile, whose first row and column
    start a diffusion block of ``sub`` rows: built once a grid step."""
    r = jax.lax.broadcasted_iota(jnp.int32, (block, block), 0) // sub
    c = jax.lax.broadcasted_iota(jnp.int32, (block, block), 1) // sub
    return r - c if query_is_row else c - r


def _rule_mask(diff, rule):
    """The diagonal tile's mask under ``rule`` (a traced scalar): lowest and
    highest d allowed."""
    lo = jnp.where(rule == _BEFORE, 1, 0)
    hi = jnp.where(rule == _OWN, 0, 2 ** 30)
    return (diff >= lo) & (diff <= hi)


def _bd_fwd_kernel(qi_of, kc_of, lo_of, full_of, hi_of, rule_of, first_of,
                last_of, q_ref, k_ref, v_ref, o_ref, lse_ref, m_ref, l_ref,
                *, scale, block, sub):
    t = pl.program_id(1)
    fold = fa._scale_folds(scale)
    s_scale = None if fold else scale
    q = q_ref[0] * scale if fold else q_ref[0]
    diff = _block_diff(block, sub, True) if sub else None

    def tile(j, masked):
        rows = pl.ds(pl.multiple_of(j * block, block), block)
        return (k_ref[0, rows, :], v_ref[0, rows, :],
                _rule_mask(diff, rule_of[t]) if masked and sub else None)

    fa._fwd_walk(q, tile, [(lo_of[t], full_of[t], False),
                           (full_of[t], hi_of[t], True)],
                 o_ref.at[0], m_ref, l_ref, s_scale, first_of[t] == 1)
    fa._finish_chunked_fwd(o_ref, lse_ref, m_ref, l_ref, last_of[t] == 1)


def _bd_bwd_kernel(qi_of, kc_of, lo_of, full_of, hi_of, rule_of, first_of,
                last_of, q_ref, k_ref, v_ref, do_ref, lse_ref, delta_ref,
                dq_ref, dk_ref, dv_ref, dk_acc, dv_acc, *, scale, block,
                sub):
    """``flash_attention._bwd_kernel_chunked`` over this file's walk: the
    chunk's K and V stay in VMEM over its run of steps, dk and dv accumulate
    in float32 scratch and leave on the run's last step, a step's dq — its
    query block against THIS chunk's keys, float32, unscaled — leaves as
    block ``t`` of [BH, pairs, block, D] for ``_sum_dq``. The tile is held
    transposed, [key, query]."""
    t = pl.program_id(1)
    fold = fa._scale_folds(scale)
    s_scale = None if fold else scale
    q = q_ref[0] * scale if fold else q_ref[0]
    do = do_ref[0]
    lse = fa._stat_row(lse_ref, (0,), 0, block)
    delta = fa._stat_row(delta_ref, (0,), 0, block)
    diff = _block_diff(block, sub, False) if sub else None

    @pl.when(first_of[t] == 1)
    def _init():
        dk_acc[...] = jnp.zeros_like(dk_acc)
        dv_acc[...] = jnp.zeros_like(dv_acc)

    def body(j, dq, masked):
        rows = pl.ds(pl.multiple_of(j * block, block), block)
        k = k_ref[0, rows, :]
        mask = _rule_mask(diff, rule_of[t]) if masked and sub else None
        p, ds = fa._bwd_ds_block(k, v_ref[0, rows, :], lse, delta, q, do,
                                 mask, s_scale)
        dv_acc[rows, :] += jax.lax.dot(p, do,
                                       preferred_element_type=jnp.float32)
        dk_acc[rows, :] += jax.lax.dot(ds, q,
                                       preferred_element_type=jnp.float32)
        return dq + jax.lax.dot_general(ds, k, (((0,), (0,)), ((), ())),
                                        preferred_element_type=jnp.float32)

    dq_ref[0, 0] = fa._causal_split_loop(
        lo_of[t], full_of[t], hi_of[t], body, jnp.zeros(q.shape, jnp.float32))

    @pl.when(last_of[t] == 1)
    def _leave():
        dk = dk_acc[...] if fold else dk_acc[...] * scale
        dk_ref[0] = dk.astype(dk_ref.dtype)
        dv_ref[0] = dv_acc[...].astype(dv_ref.dtype)


def _of_block(b, t, qi_of, *_):
    return b, qi_of[t]


def _of_chunk(b, t, qi_of, kc_of, *_):
    return b, kc_of[t]


def _call(kernel, walk, BH, in_specs, out_specs, out_shape, scratch,
          interpret, vmem_limit=None):
    call = pl.pallas_call(
        kernel,
        grid_spec=pltpu.PrefetchScalarGridSpec(
            num_scalar_prefetch=len(walk), grid=(BH, len(walk[0])),
            in_specs=in_specs, out_specs=out_specs, scratch_shapes=scratch),
        out_shape=out_shape, interpret=interpret,
        compiler_params=pltpu.CompilerParams(vmem_limit_bytes=vmem_limit)
        if vmem_limit and not interpret else None)
    return functools.partial(call, *walk)


def _sub(block_length, block):
    """Rows of a diffusion block inside a tile; 0 where a tile lies inside
    ONE diffusion block and nothing in it is masked."""
    return block_length if block_length < block else 0


def _fwd(q, k, v, scale, block_length, block, chunk, interpret, heads,
         kv_heads):
    BH, S, D = q.shape
    kv = fa._kv_row(heads, kv_heads)
    out_specs, out_shape, scratch = fa._chunked_fwd_outputs(
        q, block, block, _of_block)
    call = _call(
        functools.partial(_bd_fwd_kernel, scale=scale, block=block,
                          sub=_sub(block_length, block)),
        _bd_walk(S // 2, block_length, block, chunk, False), BH,
        [fa._rows_spec(block, D, _of_block),
         fa._rows_spec(chunk, D, _of_chunk, kv),
         fa._rows_spec(chunk, D, _of_chunk, kv)],
        out_specs, out_shape, scratch, interpret)
    with annotate("bd_fwd"):
        o32, lse = call(q, k, v)
    return o32.astype(q.dtype), lse


def _sum_dq(parts, walk, scale, dtype):
    """dq [BH, 2L, D] from the backward's partials [BH, pairs, block, D]
    (float32, unscaled): pair ``t`` holds query block ``qi_of[t]`` against
    one key chunk, and a block's dq is the float32 sum of the pairs that
    hold it, times the scale, cast once. Blocks whose pairs lie side by side
    in the walk (a chunk's run ascends by query block) are taken as one
    slice of each."""
    BH, _, block, D = parts.shape
    qi_of = walk[0]
    held = [np.flatnonzero(qi_of == i) for i in range(int(qi_of.max()) + 1)]
    runs = []                   # [first block, blocks, the pairs' first steps]
    for i, steps in enumerate(held):
        if runs and len(steps) == len(runs[-1][2]) and np.array_equal(
                steps, runs[-1][2] + runs[-1][1]):
            runs[-1][1] += 1
        else:
            runs.append([i, 1, steps])
    rows = [functools.reduce(jnp.add, [parts[:, t:t + count] for t in steps])
            for _, count, steps in runs]
    return (fa._cat(rows, 1) * scale).astype(dtype).reshape(BH, -1, D)


def _bwd(q, k, v, o, lse, do, scale, block_length, block, chunk, interpret,
         heads, kv_heads):
    BH, S, D = q.shape
    kv = fa._kv_row(heads, kv_heads)
    delta = jnp.sum(do.astype(jnp.float32) * o.astype(jnp.float32),
                    axis=-1).reshape(lse.shape)
    piece = lse.shape[-1]
    walk = _bd_walk(S // 2, block_length, block, chunk, True)
    call = _call(
        functools.partial(_bd_bwd_kernel, scale=scale, block=block,
                          sub=_sub(block_length, block)),
        walk, BH,
        [fa._rows_spec(block, D, _of_block),
         fa._rows_spec(chunk, D, _of_chunk, kv),
         fa._rows_spec(chunk, D, _of_chunk, kv),
         fa._rows_spec(block, D, _of_block)]
        + [fa._stat_spec(block, piece, _of_block)] * 2,
        [pl.BlockSpec((1, 1, block, D), lambda b, t, *_: (b, t, 0, 0)),
         fa._rows_spec(chunk, D, _of_chunk),
         fa._rows_spec(chunk, D, _of_chunk)],
        [jax.ShapeDtypeStruct((BH, len(walk[0]), block, D), jnp.float32),
         jax.ShapeDtypeStruct((BH, S, D), k.dtype),
         jax.ShapeDtypeStruct((BH, S, D), v.dtype)],
        [pltpu.VMEM((chunk, D), jnp.float32)] * 2,
        interpret, fa._BWD_VMEM_BYTES)
    with annotate("bd_bwd"):
        dq, dk, dv = call(q, k, v, do, lse, delta)
    with annotate("bd_bwd_dq_sum"):
        dq = _sum_dq(dq, walk, scale, q.dtype)
    return dq, dk, dv


@functools.partial(jax.custom_vjp, nondiff_argnums=(3, 4, 5, 6, 7, 8, 9))
def _attention(q, k, v, scale, block_length, block, chunk, interpret, heads,
               kv_heads):
    return _fwd(q, k, v, scale, block_length, block, chunk, interpret, heads,
                kv_heads)[0]


def _attention_fwd(q, k, v, scale, block_length, block, chunk, interpret,
                   heads, kv_heads):
    o, lse = fa._name_residuals(*_fwd(q, k, v, scale, block_length, block,
                                      chunk, interpret, heads, kv_heads))
    return o, (q, k, v, o, lse)


def _attention_bwd(scale, block_length, block, chunk, interpret, heads,
                   kv_heads, residuals, do):
    q, k, v, o, lse = residuals
    fa._named["closed"] = True
    dq, dk, dv = _bwd(q, k, v, o, lse, do, scale, block_length, block, chunk,
                      interpret, heads, kv_heads)
    if heads != kv_heads:
        # dk and dv come back a QUERY head: summed over a KV head's group
        def sum_group(t):
            return t.reshape(-1, kv_heads, heads // kv_heads, *t.shape[1:]) \
                .sum(axis=2).astype(t.dtype).reshape(-1, *t.shape[1:])
        dk, dv = sum_group(dk), sum_group(dv)
    return dq, dk, dv


_attention.defvjp(_attention_fwd, _attention_bwd)


def pick_plan(L, block_length, D, itemsize, interpret, block=None,
              chunk=None):
    """(block, chunk) of a call, or None where nothing tiles: the widest
    tile up to 512 rows (64 in the interpreter) that divides L and is a
    whole number of diffusion blocks or a whole fraction of one; the widest
    chunk of ``_CHUNK_ROWS`` that divides L in whole tiles and keeps a grid
    step's K + V inside ``flash_attention._CHUNK_BYTES``."""
    def fits(b):
        return L % b == 0 and (b % block_length == 0
                               or block_length % b == 0)
    if block is None:
        top = 64 if interpret else 512
        block = next((b for b in (512, 256, 128, 64, 32, 16, 8)
                      if b <= top and fits(b)), None)
    if not block or not fits(block) or L % block_length:
        return None
    row = 2 * max(D, fa._LANES) * itemsize
    if chunk is None:
        chunk = next((c for c in _CHUNK_ROWS + (block,)
                      if c * row <= fa._CHUNK_BYTES and L % c == 0
                      and c % block == 0), None)
    if not chunk or L % chunk or chunk % block:
        return None
    return int(block), int(chunk)


def _note_plan(L, D, dtype, block_length, block, chunk):
    """The gauges ``attention/bd_tile_overcompute`` (``tile_overcompute``)
    and ``attention/bd_tiles_per_grid_step`` (score tiles over grid steps,
    forward and backward together), and once a plan a log line."""
    over = tile_overcompute(L, block_length, block)
    steps = len(_bd_walk(L, block_length, block, chunk, False)[0])
    tiles = tiles_walked(L, block_length, block, chunk)
    default_registry().gauge("attention/bd_tile_overcompute").set(over)
    default_registry().gauge("attention/bd_tiles_per_grid_step").set(
        tiles / steps)
    plan = (L, D, jnp.dtype(dtype).name, block_length, block, chunk)
    if plan not in _plans_logged:
        _plans_logged.add(plan)
        n = L // block
        logger.info(
            f"block-diffusion attention L={L} (2L={2 * L} rows) D={D} "
            f"{plan[2]} block_length={block_length}: tiles of {block}, key "
            f"chunks of {chunk}: {tiles} tiles a head a pass in {steps} grid "
            f"steps (dense {4 * n * n}), backward 5 products a tile, "
            f"computes {over:.3f} x the {allowed_pairs(L, block_length)} "
            "allowed pairs")


def block_diffusion_attention(q, k, v, block_length, scale=None, block=None,
                              chunk=None, interpret=None):
    """[B, H, 2L, D] q against [B, Hkv, 2L, D] k and v (noised rows, then
    clean rows) under the block-diffusion mask of ``block_length``; the
    output as q. Raises where no tile fits (L must be a whole number of
    diffusion blocks): a ``[2L, 2L]`` mask is never the fallback."""
    B, H, S, D = q.shape
    Hkv = k.shape[1]
    if S % 2 or k.shape != v.shape or k.shape[2:] != (S, D) or H % Hkv:
        raise ValueError("block-diffusion attention takes q [B, H, 2L, D] "
                         f"and k, v [B, Hkv, 2L, D]: q {tuple(q.shape)}, "
                         f"k {tuple(k.shape)}, v {tuple(v.shape)}")
    L = S // 2
    if interpret is None:
        interpret = fa._interpret_default()
    scale = float(scale) if scale is not None else 1.0 / math.sqrt(D)
    plan = pick_plan(L, int(block_length), D, jnp.dtype(q.dtype).itemsize,
                     interpret, block, chunk)
    if plan is None:
        raise ValueError(
            f"block-diffusion attention over L={L} with block_length="
            f"{block_length} (block={block}, chunk={chunk}): no tile "
            "divides L in whole diffusion blocks")
    _note_plan(L, D, q.dtype, int(block_length), *plan)
    o = _attention(q.reshape(B * H, S, D), k.reshape(B * Hkv, S, D),
                   v.reshape(B * Hkv, S, D), scale, int(block_length), *plan,
                   bool(interpret), H, Hkv)
    return o.reshape(B, H, S, D)
