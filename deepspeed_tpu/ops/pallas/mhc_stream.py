"""The residual streams' mixers (``models/hyper_connections.py``) as Pallas
TPU kernels: every pass a branch makes over the stream reads it once in the
model's dtype, does its arithmetic in float32 in VMEM and writes its result
once.

``models/hyper_connections.py`` has the entries, the ``jnp`` forms (the
kernels' oracles), the rule that picks a form and the two ``custom_vjp``s
that string the passes together. The stream is ``[T, n C]`` (stream ``j`` the
columns ``[j C, (j + 1) C)``), a grid step owns a tile of WHOLE rows — a
token's coefficients are sums over all its ``n C`` columns — and walks it in
slabs of ``_ROWS`` rows x 128 lanes. The coefficients travel token-minor
(``[k, T]`` float32, a multiple of 8 rows); a kernel turns its tile of them
into rows (``[R, 128]``, a coefficient a lane) with one small transpose and
back. Four passes a branch, and two at a trunk's ends:

- ``mix`` (kernel ``_mhc_mix_kernel``): the sum of squares and the
  projection on ``phi`` (the MXU, operands in the stream's dtype, float32
  sums), ``Ht``, the sigmoids and Sinkhorn's rounds token-minor, and ``u =
  sum_j H_pre[j] X[j]`` from the tile already held. Out: ``u``, the
  coefficients ``[2n + n^2 .., T]`` and what the backward needs of the small
  math (the projection and the rms, 100 B a token).
- ``write`` (``_mhc_write_kernel``): ``X_new[i] = sum_j H_res[i, j] X[j] +
  H_post[i] y``.
- ``write_backward`` (``_mhc_write_bwd_kernel``): reads dX_new, X and y once;
  writes ``dy = sum_i H_post[i] dX_new[i]``, ``H_res^T dX_new`` (the stream's
  cotangent through ``write``) and the ``n^2 + n`` row sums ``<dX_new[i],
  X[j]>``, ``<dX_new[i], y>``.
- ``mix_backward`` (``_mhc_mix_bwd_kernel``): reads the cotangent that came
  in through ``write``, X and du once and walks the tile twice. First
  ``<du, X[j]>``, H_pre's cotangent through ``u``; then the small math's
  backward token-minor in VMEM — the sigmoids, Sinkhorn's rounds in reverse
  against a tape of the forward's, the gates and offsets; then ``dX = that
  cotangent + H_pre du + dproj phi^T + c X`` (``c`` the norm's term), with
  ``dphi^T`` and the gates' and offsets' sums accumulated in blocks resident
  across the row tiles.
- ``tile`` / ``sum_streams`` (``_mhc_tile_kernel``, ``_mhc_sum_kernel``):
  ``spread``'s copy of [T, C] into the n streams and ``merge``'s float32 sum
  of them; each is the other's backward.

**What a pass costs before it runs.** A model calls these passes at dozens
of sites (the Xing4.0 cell's step: twelve branches, forward, recomputed and
backward), and tracing a kernel's body, lowering it to a Mosaic module and
compiling that module are paid by the process that builds the program, which
no compile cache shortens. So (1) the six entries are ``jax.jit`` functions
of their arrays with the plan static: a pass is traced once a (pass, plan)
and lowered to ONE function of the module, which every site calls; and (2)
the bodies LOOP where they walk: over a stream's 128-lane column slabs
(``_columns``: ``SLABS_A_TURN`` slabs a turn of the loop, the accumulators
carried; ``mix``'s two walks alone stay written out, its comment says why)
and over Sinkhorn's rounds (the backward's tape of them in a VMEM scratch),
so that a body's jaxpr and its Mosaic module hold a turn's and a round's
arithmetic once, not 28 x 4 and 20 times over.
"""

import collections
import functools

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu

from deepspeed_tpu.ops.pallas.mixer_elementwise import LANES

_F32 = jnp.float32
# rows of a slab: one packed bf16 tile, two float32 vregs a 128-lane column
_ROWS = 16
# tokens a row tile (a grid step). Measured at the Xing4.0 cell's [4,096, 4 x
# 3,584] bf16 (builder's chip runs, PR 57, refused; tests/perf/
# mhc_stream_bench.py --sweep; ms a call at 128 / 256 / 512 rows): mix 0.229 /
# 0.231 / 0.234, write 0.401 / 0.402 / 0.408, write_backward 0.610 / 0.613 /
# out of VMEM, mix_backward 0.597 / 0.587 / out of VMEM, tile 0.226 / 0.224 /
# 0.222, sum_streams 0.207 / 0.207 / 0.208: every pass moves its bytes at
# 78-86 % of the HBM peak at 128 already, and the backward passes' whole-row
# blocks, twice buffered, fit no more than 256
ROW_TILE = 128
_VMEM_BYTES = 96 * 2 ** 20
# column slabs a turn of a pass's loop over a stream's columns (``_columns``);
# None, or a count of all a stream's slabs or more, is the walk written out
# whole, as PR 57 had every pass (28 slabs at the cell's 3,584 columns).
# Measured at the
# cell's shape (my chip runs, PR 58, tests/perf/mhc_stream_bench.py --sweep;
# ms a call alone at 1 / 2 / 4 / 7 / 14 / 28 slabs a turn): mix 0.378 / 0.277 /
# 0.241 / 0.231 / 0.230 / 0.231, write 0.404 / 0.401 / 0.399 / 0.400 / 0.400 /
# 0.400, write_backward 0.634 / 0.641 / 0.616 / 0.608 / 0.608 / 0.610,
# mix_backward 0.865 / 0.613 / 0.598 / 0.601 / 0.603 / 0.603, sum_streams
# 0.211 / 0.209 / 0.208 / 0.208 / 0.208 / 0.208: a loop loses nothing from 4
# or 7 slabs a turn on. ``mix`` is the exception and keeps its two walks
# written out: INSIDE the cell's step (where every pass but this one reads as
# it does alone) it took 0.2239 ms a call at 7 slabs a turn where PR 57's
# written-out walks took 0.2024 (my chip runs, PR 58: traced runs of both
# trees) — its walks are bound by the vector slots (6.0 and 7.6 bundles a
# slab of four streams written out, 7.7 and 7.7 in a loop of 7), with
# arithmetic for ~85 % of the time its bytes take. Its Sinkhorn rounds loop
SLABS_A_TURN = {"mix": None, "write": 4, "write_backward": 7,
                "mix_backward": 4, "sum_streams": 4}


class StreamPlan(collections.namedtuple(
        "StreamPlan", "T n C rows iters eps clamp")):
    """A mixer call's shapes: T tokens of n streams of C columns, in tiles of
    ``rows`` tokens; Sinkhorn's rounds, the norm's eps and the clamp."""

    @property
    def w(self):
        """Coefficients a token: ``2n + n^2``."""
        return 2 * self.n + self.n * self.n


def up(k, m):
    """k rounded up to a multiple of m."""
    return -(-k // m) * m


def takes(T, n, C):
    """Whether the kernels take the call: streams of whole 128-lane columns,
    a row tile that divides the tokens, a token's coefficients in one vreg
    row."""
    return (n > 1 and C % LANES == 0 and T % ROW_TILE == 0
            and 2 * n + n * n + 1 <= LANES)


def _columns(name, C, body, carry=()):
    """``carry = body(k, slabs, carry)`` over a stream's C columns in runs
    of ``slabs`` 128-lane slabs from column ``k``: a loop of
    ``SLABS_A_TURN[name]`` slabs a turn (Mosaic unrolls a loop whole or not
    at all: ``body`` writes a turn's slabs out) and one more call for what
    is left after the turns, or ONE call of all the slabs where a turn holds
    them all or the entry is None."""
    count = C // LANES
    turn = SLABS_A_TURN[name] or count
    if turn >= count:
        return body(0, count, carry)
    turns = count // turn
    carry = jax.lax.fori_loop(
        0, turns, lambda t, carry: body(t * (turn * LANES), turn, carry),
        carry)
    if count > turns * turn:
        carry = body(turns * turn * LANES, count - turns * turn, carry)
    return carry


def _run(ref, rows, k, slabs, at=0):
    """The view ``rows`` x (``slabs`` 128-lane slabs from column ``at + k``)
    of ``ref``: ``at`` static (``j C`` for stream j of the stream's array),
    ``k`` a Python int or a loop's index times whole slabs. ONE address a
    run: its slabs are static slices of the view (``_slab``)."""
    k = at + k
    if not isinstance(k, int):
        k = pl.multiple_of(k, LANES)
    return ref.at[rows, pl.ds(k, slabs * LANES)]


def _runs(ref, rows, k, slabs, plan):
    """``_run`` of each of the n streams of the stream's array ``ref``."""
    return [_run(ref, rows, k, slabs, j * plan.C) for j in range(plan.n)]


def _slab(s):
    """Slab s of a run's view: all its rows, 128 lanes."""
    return (slice(None), slice(s * LANES, (s + 1) * LANES))


def _f32(run, s):
    """Slab s of a run's view in float32."""
    return run[_slab(s)].astype(_F32)


def _to_rows(c):
    """[k, R] token-minor (k a multiple of 8) -> [R, 128]: coefficient m in
    lane m."""
    k, R = c.shape
    return jnp.concatenate([c, jnp.zeros((LANES - k, R), c.dtype)], axis=0).T


def _col(c, m):
    """Lane m of c [r, 128] in every lane."""
    return jnp.broadcast_to(c[:, m:m + 1], c.shape)


def _sum_to_lane(out, m, acc):
    """``out`` [r, 128] with the row sums of ``acc`` [r, 128] in lane m."""
    lane = jax.lax.broadcasted_iota(jnp.int32, out.shape, 1)
    return jnp.where(lane == m, jnp.sum(acc, axis=1, keepdims=True), out)


def _zeros(count):
    return tuple(jnp.zeros((_ROWS, LANES), _F32) for _ in range(count))


def _walk(plan, tile):
    """``tile(rows)`` for every slab row of a row tile."""
    def body(t, carry):
        tile(pl.ds(pl.multiple_of(t * _ROWS, _ROWS), _ROWS))
        return carry
    jax.lax.fori_loop(0, plan.rows // _ROWS, body, 0)


# ------------------------------------------------- coefficients + read

def _sinkhorn(m, iters, tape_ref=None):
    """``models/hyper_connections.sinkhorn`` on the matrix's rows — ``m[i]``
    [n, R] holds ``M[i, :]`` of every token — as a loop over the rounds.
    Into ``tape_ref`` [iters, 3n + 1, n, R], where there is one, every step
    for the way back: a round's matrix after the column step (slots 0 .. n),
    after the row step (n .. 2n), the column sums (2n) and the row sums
    (row 0 of 2n + 1 ..)."""
    n = len(m)

    def one(it, m):
        s = functools.reduce(jnp.add, m)
        y = [mi / s for mi in m]
        r = [jnp.sum(yi, axis=0, keepdims=True) for yi in y]
        z = tuple(yi / ri for yi, ri in zip(y, r))
        if tape_ref is not None:
            for i in range(n):
                tape_ref[it, i] = y[i]
                tape_ref[it, n + i] = z[i]
                tape_ref[it, 2 * n + 1 + i, 0:1, :] = r[i]
            tape_ref[it, 2 * n] = s
        return z

    return jax.lax.fori_loop(0, iters, one, tuple(m))


def _mhc_mix_kernel(x_ref, phi_ref, ab_ref, u_ref, coef_ref, small_ref,
                    sq_ref, pre_ref, *, plan):
    n, C, w = plan.n, plan.C, plan.w

    def squares(rows):
        # a stream an accumulator: four chains of adds, not one
        def run(k, slabs, acc):
            xs = _runs(x_ref, rows, k, slabs, plan)
            for s in range(slabs):
                vs = [_f32(x, s) for x in xs]
                acc = tuple(a + v * v for a, v in zip(acc, vs))
            return acc
        sq_ref[rows, :] = functools.reduce(
            jnp.add, _columns("mix", C, run, _zeros(n)))

    _walk(plan, squares)
    rms = jax.lax.rsqrt(jnp.sum(sq_ref[...], axis=1, keepdims=True)
                        / (n * C) + plan.eps)                     # [R, 1]
    proj = functools.reduce(jnp.add, (
        jnp.dot(x_ref[:, j * C:(j + 1) * C], phi_ref[j * C:(j + 1) * C, :],
                preferred_element_type=_F32) for j in range(n)))  # [R, 128]
    ht = proj * rms * ab_ref[0:1, :] + ab_ref[1:2, :]
    pre_ref[...] = jax.nn.sigmoid(ht)

    def read(rows):
        h = pre_ref[rows, :]
        pre = [_col(h, j) for j in range(n)]

        def run(k, slabs, carry):
            xs = _runs(x_ref, rows, k, slabs, plan)
            out = _run(u_ref, rows, k, slabs)
            for s in range(slabs):
                u = pre[0] * _f32(xs[0], s)
                for j in range(1, n):
                    u = u + pre[j] * _f32(xs[j], s)
                out[_slab(s)] = u.astype(out.dtype)
            return carry
        _columns("mix", C, run)

    _walk(plan, read)
    # the small math token-minor: a coefficient a row of [1, R]
    lane = jax.lax.broadcasted_iota(jnp.int32, proj.shape, 1)
    small_ref[...] = jnp.where(lane == w, rms, proj).T[:small_ref.shape[0]]
    ht = ht.T
    lo, hi = plan.clamp
    res = _sinkhorn(
        [jnp.exp(jnp.clip(ht[2 * n + i * n:2 * n + (i + 1) * n], lo, hi))
         for i in range(n)], plan.iters)
    coef_ref[...] = jnp.zeros_like(coef_ref)
    coef_ref[0:n, :] = jax.nn.sigmoid(ht[0:n])
    coef_ref[n:2 * n, :] = 2.0 * jax.nn.sigmoid(ht[n:2 * n])
    for i in range(n):
        coef_ref[2 * n + i * n:2 * n + (i + 1) * n, :] = res[i]


def _call(kernel, plan, interpret, sequential=False, **kw):
    """``pallas_call`` over the row tiles."""
    how = {"interpret": True} if interpret else {
        "compiler_params": pltpu.CompilerParams(
            dimension_semantics=("arbitrary" if sequential else "parallel",),
            vmem_limit_bytes=_VMEM_BYTES)}
    return pl.pallas_call(kernel, grid=(plan.T // plan.rows,), **how, **kw)


def _specs(plan):
    """BlockSpecs of (the stream [T, n C], a [T, C] array, a function of k
    for a token-minor [k, T] array, a function of the shape for an array
    every tile reads whole)."""
    R, n, C = plan.rows, plan.n, plan.C
    return (pl.BlockSpec((R, n * C), lambda i: (i, 0)),
            pl.BlockSpec((R, C), lambda i: (i, 0)),
            lambda k: pl.BlockSpec((k, R), lambda i: (0, i)),
            lambda *shape: pl.BlockSpec(shape, lambda i: (0,) * len(shape)))


def _entry(fn):
    """A pass as the callers have it: ``jax.jit`` with the plan static, so
    that its body is traced once a (pass, plan, shapes) and a program that
    calls it at many sites holds ONE function of it."""
    return jax.jit(fn, static_argnames=("plan", "interpret"))


@_entry
def mix(x, phi, ab, plan, interpret=False):
    """x [T, n C], phi [n C, 128] (x's dtype, the coefficients' columns
    first), ab [8, 128] float32 (row 0 the gates a coefficient, row 1 the
    offsets) ->
    (u [T, C], the coefficients [up(w, 8), T] — H_pre, H_post, H_res row by
    row — and [up(w + 1, 8), T]: the projection's w rows over the rms)."""
    T, n, C, w = plan.T, plan.n, plan.C, plan.w
    stream, narrow, minor, whole = _specs(plan)
    k, ks = up(w, 8), up(w + 1, 8)
    return _call(
        functools.partial(_mhc_mix_kernel, plan=plan), plan, interpret,
        in_specs=[stream, whole(n * C, LANES), whole(*ab.shape)],
        out_specs=(narrow, minor(k), minor(ks)),
        out_shape=(jax.ShapeDtypeStruct((T, C), x.dtype),
                   jax.ShapeDtypeStruct((k, T), _F32),
                   jax.ShapeDtypeStruct((ks, T), _F32)),
        scratch_shapes=[pltpu.VMEM((plan.rows, LANES), _F32),
                        pltpu.VMEM((plan.rows, LANES), _F32)])(x, phi, ab)


# --------------------------------------------------------------- write

def _coefficient_rows(c_ref, rows_ref, plan):
    """``write``'s coefficients of a row tile, a coefficient a lane, and how
    to read them: H_post[i] in lane i, H_res[i, j] in lane n + i n + j."""
    n = plan.n
    rows_ref[...] = _to_rows(c_ref[...])

    def at(rows):
        c = rows_ref[rows, :]
        return ([_col(c, i) for i in range(n)],
                [[_col(c, n + i * n + j) for j in range(n)]
                 for i in range(n)])
    return at


def _mhc_write_kernel(x_ref, y_ref, c_ref, o_ref, rows_ref, *, plan):
    n, C = plan.n, plan.C
    at = _coefficient_rows(c_ref, rows_ref, plan)

    def tile(rows):
        post, res = at(rows)

        def run(k, slabs, carry):
            xr = _runs(x_ref, rows, k, slabs, plan)
            yr = _run(y_ref, rows, k, slabs)
            out = _runs(o_ref, rows, k, slabs, plan)
            for s in range(slabs):
                xs, y = [_f32(x, s) for x in xr], _f32(yr, s)
                for i in range(n):
                    new = res[i][0] * xs[0]
                    for j in range(1, n):
                        new = new + res[i][j] * xs[j]
                    out[i][_slab(s)] = (new + post[i] * y).astype(
                        o_ref.dtype)
            return carry
        _columns("write", C, run)

    _walk(plan, tile)


@_entry
def write(x, y, c, plan, interpret=False):
    """x [T, n C], y [T, C], c [up(n + n^2, 8), T] float32 (H_post's rows,
    then H_res's) -> X_new [T, n C]."""
    stream, narrow, minor, _ = _specs(plan)
    return _call(
        functools.partial(_mhc_write_kernel, plan=plan), plan, interpret,
        in_specs=[stream, narrow, minor(c.shape[0])], out_specs=stream,
        out_shape=jax.ShapeDtypeStruct(x.shape, x.dtype),
        scratch_shapes=[pltpu.VMEM((plan.rows, LANES), _F32)])(x, y, c)


def _mhc_write_bwd_kernel(g_ref, x_ref, y_ref, c_ref, dx_ref, dy_ref, dc_ref,
                          rows_ref, sums_ref, *, plan):
    n, C = plan.n, plan.C
    at = _coefficient_rows(c_ref, rows_ref, plan)

    def tile(rows):
        post, res = at(rows)

        def products(k, slabs, acc):
            # acc[i (n + 1) + j]: <dX_new[i], X[j]>, j = n: <dX_new[i], y>
            right = _runs(x_ref, rows, k, slabs, plan) \
                + [_run(y_ref, rows, k, slabs)]
            gr = _runs(g_ref, rows, k, slabs, plan)
            for s in range(slabs):
                rs = [_f32(r, s) for r in right]
                gs = [_f32(g, s) for g in gr]
                acc = tuple(acc[i * (n + 1) + j] + gs[i] * rs[j]
                            for i in range(n) for j in range(n + 1))
            return acc

        acc = _columns("write_backward", C, products, _zeros(n * (n + 1)))
        sums = jnp.zeros((_ROWS, LANES), _F32)
        for i in range(n):
            sums = _sum_to_lane(sums, i, acc[i * (n + 1) + n])
            for j in range(n):
                sums = _sum_to_lane(sums, n + i * n + j,
                                    acc[i * (n + 1) + j])
        sums_ref[rows, :] = sums

        def back(k, slabs, carry):
            gr = _runs(g_ref, rows, k, slabs, plan)
            dyr = _run(dy_ref, rows, k, slabs)
            dxr = _runs(dx_ref, rows, k, slabs, plan)
            for s in range(slabs):
                gs = [_f32(g, s) for g in gr]
                dy = post[0] * gs[0]
                for i in range(1, n):
                    dy = dy + post[i] * gs[i]
                dyr[_slab(s)] = dy.astype(dy_ref.dtype)
                for j in range(n):
                    dx = res[0][j] * gs[0]
                    for i in range(1, n):
                        dx = dx + res[i][j] * gs[i]
                    dxr[j][_slab(s)] = dx.astype(dx_ref.dtype)
            return carry
        _columns("write_backward", C, back)

    _walk(plan, tile)
    dc_ref[...] = sums_ref[...].T[:dc_ref.shape[0]]


@_entry
def write_backward(g, x, y, c, plan, interpret=False):
    """dX_new, X [T, n C], y [T, C], ``write``'s c -> (``H_res^T dX_new``
    [T, n C] in x's dtype, dy [T, C] in y's, dc [c's shape]: the cotangents
    of H_post's and H_res's rows)."""
    stream, narrow, minor, _ = _specs(plan)
    k = c.shape[0]
    return _call(
        functools.partial(_mhc_write_bwd_kernel, plan=plan), plan, interpret,
        in_specs=[stream, stream, narrow, minor(k)],
        out_specs=(stream, narrow, minor(k)),
        out_shape=(jax.ShapeDtypeStruct(x.shape, x.dtype),
                   jax.ShapeDtypeStruct(y.shape, y.dtype),
                   jax.ShapeDtypeStruct(c.shape, _F32)),
        scratch_shapes=[pltpu.VMEM((plan.rows, LANES), _F32),
                        pltpu.VMEM((plan.rows, LANES), _F32)])(g, x, y, c)


# ----------------------------------------- coefficients + read, backward

def _sinkhorn_back(g, iters, tape_ref):
    """The cotangent of the matrix the rounds started from, from ``g``, that
    of their result, against ``_sinkhorn``'s tape, last round first: a step
    ``z = y / sum(y)`` gives ``dy = (dz - sum(dz z)) / sum(y)``, the sum over
    the axis the step normalised."""
    n = len(g)

    def one(t, g):
        it = iters - 1 - t
        s = tape_ref[it, 2 * n]
        g = [(gi - jnp.sum(gi * tape_ref[it, n + i], axis=0, keepdims=True))
             / tape_ref[it, 2 * n + 1 + i, 0:1, :] for i, gi in enumerate(g)]
        y = [tape_ref[it, i] for i in range(n)]
        dot = functools.reduce(jnp.add, (gi * yi for gi, yi in zip(g, y)))
        return tuple((gi - dot) / s for gi in g)

    return jax.lax.fori_loop(0, iters, one, tuple(g))


def _small_math_back(du_ref, x_ref, small_ref, dcoef_ref, ab_ref, c_ref,
                     dproj_ref, dab_ref, sums_ref, tape_ref, plan):
    """The first half of ``mix_backward``'s tile: ``<du, X[j]>`` (H_pre's
    cotangent through ``u``), then the small math again, token-minor, and
    its way back. Into ``c_ref`` [8.., R] H_pre's n rows and the norm's term
    a token, into ``dproj_ref`` [wp, R] the projection's cotangent, onto
    ``dab_ref`` [wp, 128] the gates' and the offsets' sums."""
    n, C, w = plan.n, plan.C, plan.w

    def tile(rows):
        def run(k, slabs, acc):
            dur = _run(du_ref, rows, k, slabs)
            xs = _runs(x_ref, rows, k, slabs, plan)
            for s in range(slabs):
                du = _f32(dur, s)
                acc = tuple(a + du * _f32(x, s) for a, x in zip(acc, xs))
            return acc
        acc = _columns("mix_backward", C, run, _zeros(n))
        sums = jnp.zeros((_ROWS, LANES), _F32)
        for j in range(n):
            sums = _sum_to_lane(sums, j, acc[j])
        sums_ref[rows, :] = sums

    _walk(plan, tile)
    ab = ab_ref[...].T                                    # [128, 8]
    proj, rms = small_ref[0:w, :], small_ref[w:w + 1, :]
    ht = proj * rms * ab[0:w, 0:1] + ab[0:w, 1:2]          # [w, R]
    pre = jax.nn.sigmoid(ht[0:n])
    half = jax.nn.sigmoid(ht[n:2 * n])
    lo, hi = plan.clamp
    raw = [ht[2 * n + i * n:2 * n + (i + 1) * n] for i in range(n)]
    start = [jnp.exp(jnp.clip(h, lo, hi)) for h in raw]
    _sinkhorn(start, plan.iters, tape_ref)
    back = _sinkhorn_back(
        [dcoef_ref[2 * n + i * n:2 * n + (i + 1) * n, :] for i in range(n)],
        plan.iters, tape_ref)
    dpre = dcoef_ref[0:n, :] + sums_ref[...].T[0:n]
    dht = [dpre * pre * (1.0 - pre),
           dcoef_ref[n:2 * n, :] * 2.0 * half * (1.0 - half)] + [
        jnp.where((h > lo) & (h < hi), g * e, 0.0)
        for g, e, h in zip(back, start, raw)]
    dproj_ref[...] = jnp.zeros_like(dproj_ref)
    lane = jax.lax.broadcasted_iota(jnp.int32, (n, LANES), 1)
    drms = jnp.zeros_like(rms)
    for at, d in zip(range(0, w, n), dht):
        p, a = proj[at:at + n], ab[at:at + n, 0:1]
        dproj_ref[at:at + n, :] = d * rms * a
        drms = drms + jnp.sum(d * p * a, axis=0, keepdims=True)
        # the gates' sums in lane 0 (a coefficient a row), the offsets' in 1
        dab_ref[at:at + n, :] += jnp.where(
            lane == 0, jnp.sum(d * p * rms, axis=1, keepdims=True),
            jnp.where(lane == 1, jnp.sum(d, axis=1, keepdims=True), 0.0))
    c_ref[...] = jnp.zeros_like(c_ref)
    c_ref[0:n, :] = pre
    # rms = (mean(v^2) + eps)^-1/2: d mean(v^2) = -rms^3 / 2 drms
    c_ref[n:n + 1, :] = -drms * rms * rms * rms / (n * C)


def _mhc_mix_bwd_kernel(g_ref, x_ref, du_ref, small_ref, dcoef_ref, ab_ref,
                        phit_ref, dx_ref, dphi_ref, dab_ref, c_ref, dproj_ref,
                        sums_ref, rows_ref, back_ref, tape_ref, *, plan):
    n, C = plan.n, plan.C

    @pl.when(pl.program_id(0) == 0)
    def _():
        dphi_ref[...] = jnp.zeros_like(dphi_ref)
        dab_ref[...] = jnp.zeros_like(dab_ref)

    _small_math_back(du_ref, x_ref, small_ref, dcoef_ref, ab_ref, c_ref,
                     dproj_ref, dab_ref, sums_ref, tape_ref, plan)
    rows_ref[...] = _to_rows(c_ref[...])
    dproj_t = dproj_ref[...]                                   # [wp, R]
    dproj = _to_rows(dproj_t).astype(x_ref.dtype)              # [R, 128]
    dproj_t = dproj_t.astype(x_ref.dtype)
    for j in range(n):
        cols = slice(j * C, (j + 1) * C)
        dphi_ref[:, cols] += jnp.dot(dproj_t, x_ref[:, cols],
                                     preferred_element_type=_F32)
        back_ref[...] = jnp.dot(dproj, phit_ref[:, cols],
                                preferred_element_type=_F32)   # [R, C]

        def tile(rows, j=j):
            c = rows_ref[rows, :]
            pre, norm = _col(c, j), _col(c, n)

            def run(k, slabs, carry):
                gr, xr, dxr = (_run(ref, rows, k, slabs, j * C)
                               for ref in (g_ref, x_ref, dx_ref))
                dur, backr = (_run(ref, rows, k, slabs)
                              for ref in (du_ref, back_ref))
                for s in range(slabs):
                    dx = _f32(gr, s) + pre * _f32(dur, s)
                    dx = dx + (backr[_slab(s)] + norm * _f32(xr, s))
                    dxr[_slab(s)] = dx.astype(dx_ref.dtype)
                return carry
            _columns("mix_backward", C, run)

        _walk(plan, tile)


@_entry
def mix_backward(g, x, du, small, dcoef, ab, phit, plan, interpret=False):
    """The stream's cotangent through ``mix``, and the parameters': g (what
    came in through the stream handed on to ``write``), X [T, n C], du
    [T, C], ``mix``'s small [up(w + 1, 8), T] and ab, dcoef [up(w, 8), T]
    (the cotangents of ``mix``'s coefficients, row by row), phit [128, n C]
    (x's dtype: ``mix``'s phi transposed) -> (dX [T, n C], dphi^T [up(w,
    16), n C] float32, [up(w, 16), 128] float32: a coefficient a row, its
    gate's cotangent in lane 0 and its offset's in lane 1)."""
    stream, narrow, minor, whole = _specs(plan)
    n, R = plan.n, plan.rows
    wp, nC = up(plan.w, 16), n * plan.C
    return _call(
        functools.partial(_mhc_mix_bwd_kernel, plan=plan), plan, interpret,
        sequential=True,
        in_specs=[stream, stream, narrow, minor(small.shape[0]),
                  minor(dcoef.shape[0]), whole(*ab.shape), whole(LANES, nC)],
        out_specs=(stream, whole(wp, nC), whole(wp, LANES)),
        out_shape=(jax.ShapeDtypeStruct(x.shape, x.dtype),
                   jax.ShapeDtypeStruct((wp, nC), _F32),
                   jax.ShapeDtypeStruct((wp, LANES), _F32)),
        scratch_shapes=[pltpu.VMEM((up(n + 1, 8), R), _F32),
                        pltpu.VMEM((wp, R), _F32),
                        pltpu.VMEM((R, LANES), _F32),
                        pltpu.VMEM((R, LANES), _F32),
                        pltpu.VMEM((R, plan.C), _F32),
                        pltpu.VMEM((max(plan.iters, 1), 3 * n + 1, n, R),
                                   _F32)])(
        g, x, du, small, dcoef, ab, phit)


# ------------------------------------------------------ a trunk's two ends

def _mhc_tile_kernel(x_ref, o_ref, *, plan):
    for j in range(plan.n):
        o_ref[:, j * plan.C:(j + 1) * plan.C] = x_ref[...]


@_entry
def tile(x, plan, interpret=False):
    """x [T, C] copied into the n streams: [T, n C]."""
    stream, narrow, _, _ = _specs(plan)
    return _call(
        functools.partial(_mhc_tile_kernel, plan=plan), plan, interpret,
        in_specs=[narrow], out_specs=stream,
        out_shape=jax.ShapeDtypeStruct((plan.T, plan.n * plan.C), x.dtype))(x)


def _mhc_sum_kernel(x_ref, o_ref, *, plan):
    n, C = plan.n, plan.C

    def rows_of(rows):
        def run(k, slabs, carry):
            xs = _runs(x_ref, rows, k, slabs, plan)
            out = _run(o_ref, rows, k, slabs)
            for s in range(slabs):
                total = _f32(xs[0], s)
                for j in range(1, n):
                    total = total + _f32(xs[j], s)
                out[_slab(s)] = total.astype(o_ref.dtype)
            return carry
        _columns("sum_streams", C, run)

    _walk(plan, rows_of)


@_entry
def sum_streams(x, plan, interpret=False):
    """The n streams of x [T, n C] summed in float32: [T, C], x's dtype."""
    stream, narrow, _, _ = _specs(plan)
    return _call(
        functools.partial(_mhc_sum_kernel, plan=plan), plan, interpret,
        in_specs=[stream], out_specs=narrow,
        out_shape=jax.ShapeDtypeStruct((plan.T, plan.C), x.dtype))(x)
