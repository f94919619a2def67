"""The elementwise stages round a recurrent mixer's chunked scan as Pallas
TPU kernels, forward and backward: one pass over HBM each.

``ops/mixer_elementwise.py`` has the entries, the XLA forms and the rule
that picks a form. Two stages, each a ``jax.custom_vjp`` whose residuals are
its own inputs:

- **convolution + SiLU (+ per-head L2 norm)** (``conv_act_kernel``; kernels
  ``_mixer_conv_fwd_kernel`` / ``_mixer_conv_bwd_kernel``): a causal
  depthwise convolution of W taps over the columns ``[offset, offset + C)``
  of the projection's output [B, S, Ctot], read in place — the column offset
  is in the ``BlockSpec``'s index map, no slice is formed. A grid step owns
  a (row block, column block); the W - 1 rows before the block come from a
  second, 16-row block of the same array (zeros at a sequence's start).
  Inside, tiles of ``_ROWS`` rows are walked with the previous tile's last
  rows as the loop's carry. With ``l2_scale`` the kernel also normalises
  every head of 128 lanes (one vreg column: the reduction never leaves the
  tile) and scales it, so q and k leave as the scan reads them. The
  backward kernel walks the row blocks and tiles in REVERSE: it forms the
  pre-activation again, takes SiLU's (and the norm's) Jacobian, keeps the
  first rows of the later tile's cotangent (VMEM scratch across grid
  steps) for the anti-causal convolution that gives dx, and adds the taps'
  and the bias's cotangents, folded to 8 sublanes, into an output block
  that stays resident across the row axis.
- **gate + grouped RMS norm** (``gated_group_norm_kernel``; kernels
  ``_mixer_norm_fwd_kernel`` / ``_mixer_norm_bwd_kernel``): an RMS norm
  over lane groups of ``group`` columns with a SiLU gate ``z`` read by
  column offset from the projection's output, the gate BEFORE the norm
  (``norm(y silu(z)) w``) or AFTER it (``norm(y) w silu(z)``). Rows stay
  rows: a group is ``group / 128`` vreg columns of a row tile. The backward
  kernel gives dy, dz and, resident across the row axis, dw. A group WIDER
  than the widest column block (granite-4.0-h: one group of all 4,096
  channels) is its own column block, of fewer rows, and a row tile walks
  it in slabs TWICE: once for the row's sums (of squares; backward also of
  the cotangent times the value), once more for the results, the gate
  formed again from VMEM — a tile of 64 rows x 4,096 float32 columns is
  1 MB, sixteen times the vreg file.

Operands and results are the model's dtype in HBM; every product, sum,
rsqrt and sigmoid is float32 in VMEM. The taps', bias's and norm weight's
cotangents are float32.
"""

import collections
import functools

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu

from deepspeed_tpu.telemetry.spans import annotate

_F32 = jnp.float32
LANES = 128
# rows a tile of the kernels' inner loops. Measured at the two cells' shapes
# (my chip run, PR 41, tests/perf/mixer_elementwise_bench.py --sweep): 64
# against 32 takes the convolution with the L2 norm from 1.25 to 1.00 ms
# forward (the lane reductions of one head hide behind the next's) and
# moves the other kernels by under 2 % either way
_ROWS = 64
# rows of the block that carries a row block's history: one packed bf16 tile
_HALO = 16
# rows of history a tile keeps (one float32 vreg row): W - 1 <= _KEEP
_KEEP = 8
_ROW_BLOCKS = (1024, 512, 256, 128, 64)
_COLUMN_BLOCKS = (512, 256, 128)


class ConvPlan(collections.namedtuple(
        "ConvPlan", "B S C total offset W bias l2_scale eps bs bc")):
    """A convolution call's shapes: C columns at ``offset`` of ``total``,
    W taps, row blocks of bs, column blocks of bc."""


class NormPlan(collections.namedtuple(
        "NormPlan", "B S D total offset group gate_first eps bs bc")):
    """A norm call's shapes: D columns in groups of ``group``, the gate the
    D columns at ``offset`` of ``total``."""

    @property
    def slab(self):
        """Columns a row tile takes at once: a whole group, or the widest
        column block that divides a group wider than any."""
        return self.group if self.group <= _COLUMN_BLOCKS[0] \
            else column_block(self.group)


def row_block(S, limit=None):
    """The largest row block that divides S (None: no block does)."""
    return next((b for b in _ROW_BLOCKS
                 if S % b == 0 and (limit is None or b <= limit)), None)


def column_block(*widths):
    """The widest column block that divides every width and offset."""
    return next((b for b in _COLUMN_BLOCKS
                 if all(w % b == 0 for w in widths)), None)


def _sigmoid(x):
    """Through tanh: one transcendental and no division (whose Newton
    steps and special cases were a seventh of the backward kernel's vector
    operations); within 6e-8 of the logistic function everywhere."""
    return 0.5 * jnp.tanh(0.5 * x) + 0.5


def _fold(t):
    """[R, c] -> [8, c]: the sum of the tile's 8-row slabs (vreg adds)."""
    return functools.reduce(
        jnp.add, (t[r:r + 8] for r in range(0, t.shape[0], 8)))


def _slabs(width, group):
    return [slice(a, a + group) for a in range(0, width, group)]


def _taps(v, taps, bias, W, R):
    """The W shifted views of ``v`` ([_KEEP + R, c]: a tile under its
    history) and the pre-activation ``bias + sum_j taps[j] * view_j``."""
    views = [v[_KEEP - (W - 1) + j:_KEEP - (W - 1) + j + R]
             for j in range(W)]
    p = views[0] * taps[0:1]
    for j in range(1, W):
        p = p + views[j] * taps[j:j + 1]
    return views, p if bias is None else p + bias


def _history(halo_ref, first):
    """[_KEEP, c] float32: the rows before a row block, zeros where the
    block is a sequence's first."""
    rows = halo_ref[0].astype(_F32)[_HALO - _KEEP:]
    return jnp.where(first, 0.0, rows)


# ---------------------------------------------------------- convolution

def _mixer_conv_fwd_kernel(x_ref, halo_ref, taps_ref, *rest, plan):
    bias_ref, y_ref = rest if plan.bias else (None,) + rest
    W, R = plan.W, _ROWS
    taps = taps_ref[...]
    bias = None if bias_ref is None else bias_ref[...]

    def tile(t, hist):
        rows = pl.ds(pl.multiple_of(t * R, R), R)
        xf = x_ref[0, rows, :].astype(_F32)
        _, p = _taps(jnp.concatenate([hist, xf], axis=0), taps, bias, W, R)
        y = p * _sigmoid(p)
        if plan.l2_scale is None:
            y_ref[0, rows, :] = y.astype(y_ref.dtype)
        else:
            for head in _slabs(plan.bc, LANES):
                yh = y[:, head]
                r = jax.lax.rsqrt(jnp.sum(yh * yh, axis=1, keepdims=True)
                                  + plan.eps)
                y_ref[0, rows, head] = (yh * (r * plan.l2_scale)).astype(
                    y_ref.dtype)
        return xf[R - _KEEP:]

    jax.lax.fori_loop(0, plan.bs // R, tile,
                      _history(halo_ref, pl.program_id(2) == 0))


def _mixer_conv_bwd_kernel(x_ref, halo_ref, taps_ref, *rest, plan):
    bias_ref, dy_ref, dx_ref, dw_ref, later_ref = \
        rest if plan.bias else (None,) + rest
    W, R = plan.W, _ROWS
    tiles = plan.bs // R
    taps = taps_ref[...]
    bias = None if bias_ref is None else bias_ref[...]
    step = pl.program_id(2)          # row blocks are visited last first

    @pl.when(step == 0)
    def _():
        later_ref[...] = jnp.zeros_like(later_ref)
        dw_ref[...] = jnp.zeros_like(dw_ref)

    first = _history(halo_ref, step == pl.num_programs(2) - 1)

    def tile(n, later):
        t = tiles - 1 - n
        rows = pl.ds(pl.multiple_of(t * R, R), R)
        xf = x_ref[0, rows, :].astype(_F32)
        before = pl.ds(pl.multiple_of(jnp.maximum(t * R - _HALO, 0), _HALO),
                       _HALO)
        hist = jnp.where(t == 0, first, x_ref[0, before, :].astype(_F32)[
            _HALO - _KEEP:])
        views, p = _taps(jnp.concatenate([hist, xf], axis=0), taps, bias,
                         W, R)
        s = _sigmoid(p)
        dy = dy_ref[0, rows, :].astype(_F32)
        if plan.l2_scale is not None:
            y = p * s
            parts = []
            for head in _slabs(plan.bc, LANES):
                yh, gh = y[:, head], dy[:, head] * plan.l2_scale
                r = jax.lax.rsqrt(jnp.sum(yh * yh, axis=1, keepdims=True)
                                  + plan.eps)
                parts.append(r * (gh - yh * (r * r * jnp.sum(
                    gh * yh, axis=1, keepdims=True))))
            dy = jnp.concatenate(parts, axis=1)
        dp = dy * (s * (1.0 + p * (1.0 - s)))
        for j in range(W):
            dw_ref[0, 8 * j:8 * j + 8, :] += _fold(dp * views[j])
        if plan.bias:
            dw_ref[0, 8 * W:8 * W + 8, :] += _fold(dp)
        u = jnp.concatenate([dp, later], axis=0)        # [R + _KEEP, c]
        dx = u[W - 1:W - 1 + R] * taps[0:1]
        for j in range(1, W):
            dx = dx + u[W - 1 - j:W - 1 - j + R] * taps[j:j + 1]
        dx_ref[0, rows, :] = dx.astype(dx_ref.dtype)
        return dp[:_KEEP]

    later_ref[...] = jax.lax.fori_loop(0, tiles, tile, later_ref[...])


def _conv_specs(plan, reverse=False):
    """BlockSpecs of (the wide array's columns, its history rows, a [*, C]
    parameter, a [B, S, C] array, the parameters' cotangent) for a grid of
    (batch row, column block, row block)."""
    bs, bc = plan.bs, plan.bc
    nr, first = plan.S // bs, plan.offset // bc

    def at(i):
        return nr - 1 - i if reverse else i

    return (pl.BlockSpec((1, bs, bc), lambda b, j, i: (b, at(i), first + j)),
            pl.BlockSpec((1, _HALO, bc), lambda b, j, i: (
                b, jnp.maximum(at(i) * (bs // _HALO) - 1, 0), first + j)),
            lambda n: pl.BlockSpec((n, bc), lambda b, j, i: (0, j)),
            pl.BlockSpec((1, bs, bc), lambda b, j, i: (b, at(i), j)),
            pl.BlockSpec((1, 8 * (plan.W + 1), bc),
                         lambda b, j, i: (b, 0, j)))


def _call(kernel, plan, columns, interpret, **kw):
    """``pallas_call`` over (batch row, column block, row block), the row
    blocks innermost and in order."""
    how = {"interpret": True} if interpret else {
        "compiler_params": pltpu.CompilerParams(
            dimension_semantics=("parallel", "parallel", "arbitrary"),
            vmem_limit_bytes=64 * 2 ** 20)}
    return pl.pallas_call(
        kernel, grid=(plan.B, columns // plan.bc, plan.S // plan.bs),
        **how, **kw)


def _conv_inputs(x, taps, bias, plan, reverse=False):
    """(in_specs, operands) of the inputs both kernels share, the [B, S, C]
    spec and the parameters' cotangent's."""
    wide, halo, param, narrow, dw = _conv_specs(plan, reverse)
    specs = [wide, halo, param(plan.W)] + ([param(1)] if plan.bias else [])
    operands = (x, x, taps) + ((bias,) if plan.bias else ())
    return specs, operands, narrow, dw


def _conv_forward(x, taps, bias, plan, interpret):
    specs, operands, narrow, _ = _conv_inputs(x, taps, bias, plan)
    with annotate("mixer_conv_fwd"):
        return _call(
            functools.partial(_mixer_conv_fwd_kernel, plan=plan), plan,
            plan.C, interpret, in_specs=specs, out_specs=narrow,
            out_shape=jax.ShapeDtypeStruct((plan.B, plan.S, plan.C),
                                           x.dtype))(*operands)


def _conv_backward(x, taps, bias, dy, plan, interpret):
    specs, operands, narrow, dw = _conv_inputs(x, taps, bias, plan,
                                               reverse=True)
    W = plan.W
    with annotate("mixer_conv_bwd"):
        dx, dwb = _call(
            functools.partial(_mixer_conv_bwd_kernel, plan=plan), plan,
            plan.C, interpret, in_specs=specs + [narrow],
            out_specs=(narrow, dw),
            out_shape=(jax.ShapeDtypeStruct(dy.shape, x.dtype),
                       jax.ShapeDtypeStruct((plan.B, 8 * (W + 1), plan.C),
                                            _F32)),
            scratch_shapes=[pltpu.VMEM((_KEEP, plan.bc), _F32)])(
            *operands, dy)
        # the batch rows and the 8 sublanes of every tap's slab
        dwb = jnp.sum(dwb.reshape(plan.B, W + 1, 8, plan.C), axis=(0, 2))
        dx = jnp.pad(dx, ((0, 0), (0, 0), (
            plan.offset, plan.total - plan.offset - plan.C)))
    return dx, dwb[:W], dwb[W:] if plan.bias else None


@functools.lru_cache(maxsize=None)
def _conv_rule(plan, interpret):
    @jax.custom_vjp
    def rule(x, taps, bias):
        return _conv_forward(x, taps, bias, plan, interpret)

    def fwd(x, taps, bias):
        return _conv_forward(x, taps, bias, plan, interpret), (x, taps, bias)

    def bwd(res, dy):
        return _conv_backward(*res, dy, plan, interpret)

    rule.defvjp(fwd, bwd)
    return rule


def conv_takes(S, C, total, offset, W, l2_head=None, block_rows=None):
    """Whether the convolution kernels take the call: whole 128-lane column
    blocks at the offset, a row block that divides S, the taps' history
    inside one vreg row and, for the folded L2 norm, heads of 128 lanes
    (as they are or zero-padded to 128: zeros add nothing to a head's sum
    of squares). ``conv_refusal`` names the condition that refuses."""
    return conv_refusal(S, C, total, offset, W, l2_head, block_rows) is None


def conv_act_kernel(x, taps, bias=None, *, eps, offset=0, l2_scale=None,
                    interpret=False, block_rows=None):
    """SiLU of the causal depthwise convolution of ``x[..., offset:offset +
    C]`` (x [B, S, total]; taps [W, C], bias [C] or None, float32), with
    ``l2_scale`` every head of 128 columns L2-normalised and scaled;
    [B, S, C] in x's dtype."""
    B, S, total = x.shape
    W, C = taps.shape
    plan = ConvPlan(B, S, C, total, offset, W, bias is not None,
                    None if l2_scale is None else float(l2_scale),
                    float(eps), row_block(S, block_rows),
                    column_block(C, offset))
    return _conv_rule(plan, bool(interpret))(
        x, taps.astype(_F32),
        None if bias is None else bias.astype(_F32).reshape(1, C))


# ------------------------------------------------------ gate + group norm

def _gate(z):
    """silu(z) and its derivative."""
    s = _sigmoid(z)
    return z * s, s * (1.0 + z * (1.0 - s))


def _group_slabs(group, plan):
    """The column slices a row tile takes a group in: the group itself, or
    the slabs of a group wider than any column block."""
    return [slice(group.start + s.start, group.start + s.stop)
            for s in _slabs(plan.group, plan.slab)]


def _same(value, cols):
    del cols
    return value


def _mixer_norm_fwd_kernel(y_ref, z_ref, w_ref, o_ref, *, plan):
    R = _ROWS

    def gated(rows, cols):
        """(the value the norm reads, the gate) of a slab."""
        u = y_ref[0, rows, cols].astype(_F32)
        gate, _ = _gate(z_ref[0, rows, cols].astype(_F32))
        return (u * gate if plan.gate_first else u), gate

    def tile(t, carry):
        rows = pl.ds(pl.multiple_of(t * R, R), R)
        for group in _slabs(plan.bc, plan.group):
            slabs = _group_slabs(group, plan)
            load = functools.partial(gated, rows)
            if len(slabs) == 1:     # its values stay in vregs between passes
                load = functools.partial(_same, load(slabs[0]))
            squares = sum(jnp.sum(u * u, axis=1, keepdims=True)
                          for u, _ in map(load, slabs))
            r = jax.lax.rsqrt(squares / plan.group + plan.eps)
            for cols in slabs:
                u, gate = load(cols)
                o = u * r * w_ref[:, cols]
                if not plan.gate_first:
                    o = o * gate
                o_ref[0, rows, cols] = o.astype(o_ref.dtype)
        return carry

    jax.lax.fori_loop(0, plan.bs // R, tile, 0)


def _mixer_norm_bwd_kernel(y_ref, z_ref, w_ref, do_ref, dy_ref, dz_ref,
                           dw_ref, *, plan):
    R = _ROWS

    @pl.when(pl.program_id(2) == 0)
    def _():
        dw_ref[...] = jnp.zeros_like(dw_ref)

    def slab(rows, cols):
        """(y, the gate and its derivative, do, w, the value the norm
        reads, the cotangent of its result) of a slab."""
        y = y_ref[0, rows, cols].astype(_F32)
        do = do_ref[0, rows, cols].astype(_F32)
        gate, dgate = _gate(z_ref[0, rows, cols].astype(_F32))
        w = w_ref[:, cols]
        if plan.gate_first:
            return y, gate, dgate, do, w, y * gate, do * w
        return y, gate, dgate, do, w, y, do * w * gate

    def tile(t, carry):
        rows = pl.ds(pl.multiple_of(t * R, R), R)
        for group in _slabs(plan.bc, plan.group):
            slabs = _group_slabs(group, plan)
            load = functools.partial(slab, rows)
            if len(slabs) == 1:     # its values stay in vregs between passes
                load = functools.partial(_same, load(slabs[0]))
            # the row's sums over the whole group: u^2 and a u
            squares = dots = 0.0
            for *_, u, a in map(load, slabs):
                squares += jnp.sum(u * u, axis=1, keepdims=True)
                dots += jnp.sum(a * u, axis=1, keepdims=True)
            r = jax.lax.rsqrt(squares / plan.group + plan.eps)
            # mean(a n) r = mean(a u) r^2, n = u r the norm's result
            back = dots / plan.group * (r * r)
            for cols in slabs:
                y, gate, dgate, do, w, u, a = load(cols)
                n = u * r
                # a: the cotangent of n;  du = r (a - n mean(a n))
                du = r * a - n * back
                if plan.gate_first:
                    dy, dz, dw = du * gate, du * y * dgate, do * n
                else:
                    dy, dz, dw = du, do * w * n * dgate, do * n * gate
                dy_ref[0, rows, cols] = dy.astype(dy_ref.dtype)
                dz_ref[0, rows, cols] = dz.astype(dz_ref.dtype)
                dw_ref[0, :, cols] += _fold(dw)
        return carry

    jax.lax.fori_loop(0, plan.bs // R, tile, 0)


def _norm_specs(plan):
    """BlockSpecs of (a [B, S, D] array, the gate's columns of the wide
    array, the weight, the weight's cotangent)."""
    bs, bc = plan.bs, plan.bc
    first = plan.offset // bc
    return (pl.BlockSpec((1, bs, bc), lambda b, j, i: (b, i, j)),
            pl.BlockSpec((1, bs, bc), lambda b, j, i: (b, i, first + j)),
            pl.BlockSpec((1, bc), lambda b, j, i: (0, j)),
            pl.BlockSpec((1, 8, bc), lambda b, j, i: (b, 0, j)))


def _norm_forward(y, z, w, plan, interpret):
    narrow, gate, weight, _ = _norm_specs(plan)
    with annotate("mixer_norm_fwd"):
        return _call(
            functools.partial(_mixer_norm_fwd_kernel, plan=plan), plan,
            plan.D, interpret, in_specs=[narrow, gate, weight],
            out_specs=narrow,
            out_shape=jax.ShapeDtypeStruct(y.shape, y.dtype))(y, z, w)


def _norm_backward(y, z, w, do, plan, interpret):
    narrow, gate, weight, dw = _norm_specs(plan)
    with annotate("mixer_norm_bwd"):
        dy, dz, dwb = _call(
            functools.partial(_mixer_norm_bwd_kernel, plan=plan), plan,
            plan.D, interpret,
            in_specs=[narrow, gate, weight, narrow],
            out_specs=(narrow, narrow, dw),
            out_shape=(jax.ShapeDtypeStruct(y.shape, y.dtype),
                       jax.ShapeDtypeStruct(y.shape, z.dtype),
                       jax.ShapeDtypeStruct((plan.B, 8, plan.D), _F32)))(
            y, z, w, do)
        dz = jnp.pad(dz, ((0, 0), (0, 0), (
            plan.offset, plan.total - plan.offset - plan.D)))
        return dy, dz, jnp.sum(dwb, axis=(0, 1))[None]


@functools.lru_cache(maxsize=None)
def _norm_rule(plan, interpret):
    @jax.custom_vjp
    def rule(y, z, w):
        return _norm_forward(y, z, w, plan, interpret)

    def fwd(y, z, w):
        return _norm_forward(y, z, w, plan, interpret), (y, z, w)

    def bwd(res, do):
        return _norm_backward(*res, do, plan, interpret)

    rule.defvjp(fwd, bwd)
    return rule


# elements of a norm call's block at most: the widest column block at the
# longest row block, which a wider group's block keeps with fewer rows
_NORM_BLOCK = _ROW_BLOCKS[0] * _COLUMN_BLOCKS[0]


def norm_block(D, offset, group):
    """The widest column block of whole groups that divides D and the
    gate's offset; a group wider than every column block is its own block
    (None: no block does)."""
    if group > _COLUMN_BLOCKS[0]:
        return group if D % group == 0 and offset % group == 0 else None
    return next((b for b in _COLUMN_BLOCKS
                 if group and b % group == 0 and D % b == 0
                 and offset % b == 0), None)


def norm_row_block(S, bc, limit=None):
    """The row block of a norm call whose column block is ``bc``: the
    largest that divides S and keeps the block within ``_NORM_BLOCK``."""
    rows = _NORM_BLOCK // bc
    return row_block(S, rows if limit is None else min(rows, limit))


def norm_takes(S, D, total, offset, group, block_rows=None):
    """Whether the norm kernels take the call: groups of whole vreg
    columns, each inside one column block or a column block itself, whole
    column blocks at the gate's offset, a row block that divides S.
    ``norm_refusal`` names the condition that refuses a call. A head
    zero-padded to whole tiles is a group of its tiles, its mean taken
    over the channels it has by a rescaled eps and weight
    (``ops.mixer_elementwise.tile_group_norm``)."""
    return norm_refusal(S, D, total, offset, group, block_rows) is None


def gated_group_norm_kernel(y, z, w, *, group, eps, gate_first, offset=0,
                            interpret=False, block_rows=None):
    """RMS norm of y [B, S, D] over groups of ``group`` columns, weight w
    [D], with the SiLU gate ``z[..., offset:offset + D]`` (z [B, S, total])
    applied to y before the norm (``gate_first``) or to its result; y's
    dtype."""
    B, S, D = y.shape
    bc = norm_block(D, offset, group)
    plan = NormPlan(B, S, D, z.shape[2], offset, group, bool(gate_first),
                    float(eps), norm_row_block(S, bc, block_rows), bc)
    return _norm_rule(plan, bool(interpret))(
        y, z, w.astype(_F32).reshape(1, D))


def conv_refusal(S, C, total, offset, W, l2_head=None, block_rows=None):
    """The condition of ``conv_takes`` that refuses the call, in words
    (None: the kernels take it)."""
    if row_block(S, block_rows) is None:
        return f"no row block of {_ROW_BLOCKS} divides S = {S}"
    if column_block(C, offset) is None:
        return (f"{C} columns at offset {offset} are not whole 128-lane "
                f"column blocks")
    if not 1 <= W - 1 <= _KEEP:
        return f"{W} taps: the history is not 1 ... {_KEEP} rows"
    if offset + C > total:
        return f"columns {offset}:{offset + C} pass the array's {total}"
    if l2_head not in (None, LANES):
        return f"the L2 norm's heads are {l2_head} wide, not 128 lanes"
    return None


def norm_refusal(S, D, total, offset, group, block_rows=None):
    """The condition of ``norm_takes`` that refuses the call, in words
    (None: the kernels take it)."""
    if not group or group % LANES:
        return f"groups of {group} are not whole 128-lane vreg columns"
    bc = norm_block(D, offset, group)
    if bc is None:
        return (f"no column block of whole groups of {group} divides {D} "
                f"columns and the gate's offset {offset}")
    if offset + D > total:
        return f"columns {offset}:{offset + D} pass the array's {total}"
    if norm_row_block(S, bc, block_rows) is None:
        return f"no row block divides S = {S} at column blocks of {bc}"
    return None
