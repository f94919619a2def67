"""Grouped matmul — the expert bank of a dropless mixture-of-experts layer.

``lhs`` [M, K] holds token rows SORTED by expert, ``group_sizes`` [G] says
how many consecutive rows belong to each expert and ``rhs`` [G, K, N] is the
stacked expert weight: row r of the result is ``lhs[r] @ rhs[g(r)]``. The
group sizes are DATA — they reach the kernel as a scalar-prefetched array
and the grid is sized for the worst case (one extra row tile per group), so
no routing pattern changes a shape and nothing recompiles. The sizes sum to
M (the dropless layer routes every row it hands over); rows past their sum
are never visited and come back undefined.

The kernels are jax 0.9.0's ``jax.experimental.pallas.ops.tpu.megablox``
(``gmm`` and its transposed twin ``tgmm``), called as shipped. What is this
repo's: the custom VJP (each of the three products under a scope of its own,
``moe_gmm`` / ``moe_gmm_dlhs`` / ``moe_gmm_drhs``, so the device plane names
the kernels and the benchmark's ``moe_gmm_*`` readers find them by prefix),
the tilings (caps under which each side takes the largest multiple of 128
that divides it: ``_divisor``; powers of two and 3 or 5 times one alike;
all three products swept on the chip, my chip runs, PR 27 and PR 38),
padding M up to the row tile, and padding a width that no multiple of 128
divides up to the next one (``lane_padded``: 1,856 -> 1,920, PR 40). On other backends the same
kernels run in Pallas interpret mode, as the flash kernels do.

Backward: ``dlhs = gmm(dout, rhs^T)`` (a grouped matmul against the
transposed weights, the transpose folded into the kernel's index map) and
``drhs[g] = lhs_g^T @ dout_g`` (``tgmm``: per-group outer products
accumulated over the group's rows; an empty group's gradient is zero).
"""

import functools
import importlib

import jax
import jax.numpy as jnp

from deepspeed_tpu.telemetry.spans import annotate

# the kernels' module (the package exports a function of the same name)
_mb = importlib.import_module(
    "jax.experimental.pallas.ops.tpu.megablox.gmm")

# (rows, contraction, columns) CAPS of the three products' tiles; each side
# takes the largest multiple of 128 under its cap that divides the problem
# (``_divisor``). At powers of two — the OLMoE, Qwen3-Next and Laguna cells:
# K, N of 2048 / 1024 / 512 — the caps give (256, 2048, 1024) forward and
# (512, 1024, 1024) backward: the forward tile is from a sweep on a v5e
# (PERF.md Findings PR 27: the whole contraction keeps a group's weight tile
# resident; 512 rows with it is refused for scoped VMEM). The caps themselves
# are from a sweep at widths that are NO powers of two (SmallThinker's
# experts, (K, N) = (2560, 768) and (768, 2560), a slab of 49,152 rows of
# which 24,576 are filled over 16 experts; device ms a call, share of the
# bf16 peak; tests/perf/gmm_tile_bench.py, my chip runs, PR 38), chosen
# among the caps that leave every power of two its tile:
#   forward  (2560, 768): (256, 2560, 768) 0.587 ms, 83.5 % — halving gave
#            (256, 512, 768) 0.823, a cap of 2048 (256, 1280, 768) 0.751;
#            (768, 2560): (256, 768, 1280) 0.652, 75.3 % — (256, 768, 512)
#            0.802, (256, 768, 640) 0.737. 512 rows are faster still at these
#            widths ((512, 1280, 768) 0.568, (512, 768, 1280) 0.593) and
#            refused at OLMoE's: the row cap stays.
#   dlhs     dout [M, N] x rhs^T: (512, 768, 1280) 0.591, 83.0 % and
#            (512, 1280, 768) 0.574, 85.5 % — caps of 1024 gave (512, 768,
#            640) 0.690 and (512, 640, 768) 0.612; 1,024 rows 0.73-0.74.
#   drhs     lhs^T x dout: (512, 1280, 768) 0.589, 83.3 % and (512, 768,
#            1280) 0.591, 83.0 % — caps of 1024 gave (512, 640, 768) 0.661
#            and (512, 768, 640) 0.666; 1,024 rows x 1,280 and a whole side
#            of 2,560 are refused for scoped VMEM.
# A width that NO multiple of 128 divides (Nemotron-3-Nano's experts, 1,856 =
# 29 x 64 against 2,688 = 21 x 128; a slab of 12,288 rows of which 6,144 are
# filled over 8 experts) is PADDED with zeros to the next multiple, 1,920
# (``lane_padded``; 3.4 % more tile work, exact: zero columns of up meet zero
# rows of down), not taken as one whole-dimension block of 1,856: device ms a
# call at the tiles ``_clip`` gives these caps, padded against whole
# (tests/perf/gmm_tile_bench.py --set nemotron, my chip run, PR 40; the share
# of the bf16 peak counts the flops of the 1,856 on both sides) —
#   up   (2688 -> 1,920 | 1,856): forward (256, 896, 640) 0.549, 56.6 % |
#        (256, 896, 1856) 0.632 and (256, 2688, 1856) 0.614; dlhs (512, 640,
#        896) 0.533 | (512, 1856, 896) 0.724, (256, ...) 0.619; drhs (512, 896,
#        640) 0.614 | (512, 896, 1856) 0.763, (256, ...) 0.662.
#   down (1,920 | 1,856 -> 2688): forward (256, 1920, 896) 0.428, 72.7 % |
#        (256, 1856, 896) 0.458; dlhs (512, 896, 640) 0.527 | (512, 896, 1856)
#        0.537, (256, ...) 0.456; drhs (512, 640, 896) 0.602 | (512, 1856, 896)
#        REFUSED for scoped VMEM, (256, 1856, 896) 0.538.
# Padded wins five of six at the committed caps (a ragged last lane tile costs
# more than the 64 zero lanes). The caps stay: 1,920 alone would take more
# from them — forward (256, 896, 1920) 0.425 and (256, 2688, 640) 0.441, dlhs
# (256, 1920, 896) 0.428 — but 256 rows backward and a whole side of 1,920 or
# 2,688 are slower or refused at the other cells' widths, and the six products
# are 2.6 % of that cell's step.
TILE_FWD = (256, 2560, 1280)
TILE_DLHS = (512, 1280, 1280)
TILE_DRHS = (512, 1280, 1280)


def _interpret_default():
    from deepspeed_tpu.utils.platform import is_tpu_backend
    return not is_tpu_backend()


def _divisor(cap, d):
    """The largest multiple of 128 no larger than ``cap`` that divides
    ``d``: 2,560 under 2,560 itself and under 1,280 that, 768 under 1,280
    itself, where halving from 2,048 and 1,024 stopped at 512 and 256. A
    power of two gets the largest power of two under the cap. Where no
    multiple of 128 divides (a test's sizes), ``cap`` halved until it
    does."""
    for t in range(min(cap, d) // 128 * 128, 0, -128):
        if d % t == 0:
            return t
    t = min(cap, d)
    while d % t:
        t //= 2
    return t


def lane_padded(d):
    """``d``, or — where ``d`` is at least 128 and no multiple of 128
    divides it (1,856 = 29 x 64) — the next multiple of 128 (1,920): the
    width a side is PADDED to, with zeros, before it is tiled. ``_divisor``
    would halve such a side down to a tile of 64 lanes or fewer (1,856 from
    a cap of 1,280: a tile of 2)."""
    return d if d < 128 or d % 128 == 0 else -(-d // 128) * 128


def _clip(tile, m, k, n):
    """``tile`` no larger than the problem, each side dividing its
    dimension (``_divisor``; the rows are padded to whole row tiles)."""
    return tuple(_divisor(t, d) for t, d in zip(tile, (m, k, n)))


def _fwd(lhs, rhs, group_sizes, interpret):
    m, k = lhs.shape
    with annotate("moe_gmm"):
        return _mb.gmm(lhs, rhs, group_sizes, lhs.dtype,
                       _clip(TILE_FWD, m, k, rhs.shape[2]),
                       interpret=interpret)


@functools.partial(jax.custom_vjp, nondiff_argnums=(3,))
def _gmm(lhs, rhs, group_sizes, interpret):
    return _fwd(lhs, rhs, group_sizes, interpret)


def _gmm_fwd(lhs, rhs, group_sizes, interpret):
    return _fwd(lhs, rhs, group_sizes, interpret), (lhs, rhs, group_sizes)


def _gmm_bwd(interpret, saved, dout):
    lhs, rhs, group_sizes = saved
    m, k = lhs.shape
    n = rhs.shape[2]
    dout = dout.astype(lhs.dtype)
    with annotate("moe_gmm_dlhs"):
        dlhs = _mb.gmm(dout, rhs, group_sizes, lhs.dtype,
                       _clip(TILE_DLHS, m, n, k), transpose_rhs=True,
                       interpret=interpret)
    with annotate("moe_gmm_drhs"):
        drhs = _mb.tgmm(lhs.swapaxes(0, 1), dout, group_sizes, rhs.dtype,
                        _clip(TILE_DRHS, m, k, n),
                        num_actual_groups=rhs.shape[0], interpret=interpret)
    return dlhs, drhs, None


_gmm.defvjp(_gmm_fwd, _gmm_bwd)


def grouped_matmul(lhs, rhs, group_sizes, interpret=None):
    """[M, K] x [G, K, N] -> [M, N], row r against the weight of the group
    it lies in. ``group_sizes`` int32 [G] summing to M (rows past the sum
    are undefined). Differentiable in ``lhs`` and ``rhs``."""
    if interpret is None:
        interpret = _interpret_default()
    m, k = lhs.shape
    n = rhs.shape[2]
    if (lane_padded(k), lane_padded(n)) != (k, n):
        # zero columns of lhs against zero rows of rhs add nothing; zero
        # columns of rhs are cut from the result. A caller with such a
        # width between two products pads its WEIGHTS once and calls with
        # whole widths (``moe/dropless.DroplessMoE``): nothing is copied
        # between its products then
        lhs = jnp.pad(lhs, ((0, 0), (0, lane_padded(k) - k)))
        rhs = jnp.pad(rhs, ((0, 0), (0, lane_padded(k) - k),
                            (0, lane_padded(n) - n)))
        return grouped_matmul(lhs, rhs, group_sizes, interpret)[:, :n]
    # whole row tiles; a problem smaller than one tile, whole sublanes
    unit = max(TILE_FWD[0], TILE_DLHS[0], TILE_DRHS[0])
    pad = (-m) % (unit if m >= unit else 8)
    if pad:
        lhs = jnp.pad(lhs, ((0, pad), (0, 0)))
    out = _gmm(lhs, rhs, group_sizes.astype(jnp.int32), interpret)
    return out[:m] if pad else out
