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
the tilings, and padding M up to the row tile. On other backends the same
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

# (rows, contraction, columns) tiles of the three products at the sizes the
# OLMoE cell runs (M 131072, K 2048 / 1024, N 1024 / 2048); each is clipped
# to the problem. The forward tile is from a sweep on a v5e (PERF.md Findings
# PR 27: the whole contraction keeps a group's weight tile resident; 512 rows
# with it is refused for scoped VMEM); the two backward tiles were not swept.
TILE_FWD = (256, 2048, 1024)
TILE_DLHS = (512, 1024, 1024)
TILE_DRHS = (512, 1024, 1024)


def _interpret_default():
    from deepspeed_tpu.utils.platform import is_tpu_backend
    return not is_tpu_backend()


def _clip(tile, m, k, n):
    """``tile`` no larger than the problem, each side dividing its
    dimension (tiles and padded sizes are powers of two times 8)."""
    out = []
    for t, d in zip(tile, (m, k, n)):
        t = min(t, d)
        while d % t:
            t //= 2
        out.append(t)
    return tuple(out)


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
    m = lhs.shape[0]
    # whole row tiles; a problem smaller than one tile, whole sublanes
    unit = max(TILE_FWD[0], TILE_DLHS[0], TILE_DRHS[0])
    pad = (-m) % (unit if m >= unit else 8)
    if pad:
        lhs = jnp.pad(lhs, ((0, pad), (0, 0)))
    out = _gmm(lhs, rhs, group_sizes.astype(jnp.int32), interpret)
    return out[:m] if pad else out
