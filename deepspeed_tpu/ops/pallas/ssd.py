"""The chunked state-space scan (Mamba-2's SSD) as Pallas TPU kernels,
forward and backward.

``ops/ssd.py`` has the recurrence, the chunked form and its XLA oracle. Here
one program owns a (batch row, group, HEAD BLOCK: at most ``_BLOCK_HEADS``
of the group's heads) and walks that sequence's chunks in order with the
state of each of its heads in float32 VMEM scratch. Per chunk it forms
``C B^T`` once for the head block, and for every head the decay mask ``L``
[c, c] in VMEM from the chunk's gates; nothing of [c, c] size goes to HBM.
A group of 8 heads (Nemotron-3-Nano: 8 groups of 8) is ONE head block; a
group of all 64 heads (granite-4.0-h: ``mamba_n_groups 1``) is 8, each
reading the group's one B and C, so that a grid step's operand blocks are
the size they are at 8 heads whatever the group's width.

- **Layout.** x, y [B, S, H*P] and B, C [B, S, G*N] are the model's own
  arrays (a reshape of [B, S, H, P]); a program takes the lane columns of
  its head block and of its group's B and C. Heads narrower than a vreg's
  128 lanes sit SIDE BY SIDE:
  ``pack = 128 // P`` heads make one lane block (two at P = 64), the state
  of a block is [N, pack * P], and the three products that are not masked a
  head — ``C S``, ``B^T (dt x)``, and their transposes backward — run at
  full lane width for the heads of a block together; the masked product
  ``(L o C B^T) (dt x)`` runs once a head against the block with the other
  heads' lanes zeroed. The gates are laid out first, a token a lane:
  ``dt`` and ``a`` (the running sum of ``dt A`` inside each chunk) as
  [B, head blocks, n, heads a block, c] float32 (4 MB each at 16,384
  tokens, 64 heads); a chunk's tile is transposed in VMEM where a token a
  sublane is needed.
- **Roundings** are the XLA form's: state, gates and ``L`` float32; the
  masked ``C B^T``, ``dt x`` and the state cast to the inputs' dtype before
  their matmuls, float32 accumulation.
- **Backward.** The forward RULE also writes the state every chunk STARTS
  from, in float32 ([B, H / pack, n, N, pack * P]: 128 chunks x 64 heads x
  32 KB = 268 MB a layer at 16,384 tokens, 64 heads of 64 x 128), and
  nothing else, and names it and y ``scan_states`` (``scan_residuals.py``).
  A rematted block that does not keep the name runs the rule as its
  recomputation — its forward pass runs the primal call, which writes y
  alone — and the states are alive from a layer's recomputation to its
  backward pass; one that keeps it (``runtime/remat_budget.py``, where the
  bytes fit) runs the rule's kernel once, in its forward pass. The
  backward kernel walks the chunks in
  REVERSE with ``dS`` in VMEM, forms the chunk's masks again and writes dx,
  dB, dC (summed over the head block's heads in float32; where a group is
  several head blocks each writes its own float32 part, [B, head blocks a
  group, S, G*N], and the parts are added under ``ssd_scan_prep``), ddt, da
  and, accumulated over the chunks, a head's ``sum dy x`` for dD.

The XLA ops left round the kernels (the gates' re-layout and the running
sum) are traced under ``ssd_scan_prep``.
"""

import collections
import functools

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu

from deepspeed_tpu.ops.pallas.scan_residuals import named_forward
from deepspeed_tpu.telemetry.registry import default_registry
from deepspeed_tpu.telemetry.spans import annotate
from deepspeed_tpu.utils.logging import logger

# chunks a grid step
_BLOCK_CHUNKS = 8
# heads a grid step at most: one float32 tile's sublanes of the gates, and
# the operand blocks the plan was measured with (Nemotron's groups of 8)
_BLOCK_HEADS = 8
_NN, _NT, _TN = ((1,), (0,)), ((1,), (1,)), ((0,), (0,))
_F32 = jnp.float32


class _Plan(collections.namedtuple("_Plan", "B H G P N C cb nb pack hg")):
    """What a call's shapes decide: chunk C, cb chunks a grid step, nb grid
    steps a sequence, pack heads a lane block, hg heads a grid step (a
    group's, or a head block of them)."""

    @property
    def W(self):
        return self.pack * self.P        # lanes a block

    @property
    def blocks(self):
        return self.hg // self.pack      # lane blocks a grid step

    @property
    def head_blocks(self):
        return self.H // self.G // self.hg   # head blocks a group

    @property
    def programs(self):
        return self.G * self.head_blocks     # (group, head block) pairs


def _plan_for(B, S, H, G, P, N, C):
    n = -(-S // C)
    cb = next(c for c in (_BLOCK_CHUNKS, 4, 2, 1) if n % c == 0)
    heads = H // G
    pack = 2 if 2 * P == 128 and heads % 2 == 0 else 1
    # a group of up to ``_BLOCK_HEADS`` heads is one head block; a wider one
    # is cut into the widest blocks of whole lane blocks that divide it
    hg = heads if heads <= _BLOCK_HEADS else next(
        h for h in range(_BLOCK_HEADS, 0, -1)
        if heads % h == 0 and h % pack == 0)
    return _Plan(B, H, G, P, N, C, cb, n // cb, pack, hg)


def takes_kernel(H, P, G, N, chunk, tpu):
    """Whether the kernels take ``H`` heads of ``P`` channels in ``G``
    groups with a state of ``N``: on a TPU backend a lane block (a head, or
    two heads of 64) and the state must be whole vregs wide and the chunk
    square with a vreg's lanes (a group of any width is cut into head
    blocks, ``_plan_for``); the interpreter (any other backend) takes any
    shape whose heads divide into the groups."""
    if H % G:
        return False
    return not tpu or (
        N % 128 == 0 and chunk == 128
        and (P % 128 == 0 or (P == 64 and (H // G) % 2 == 0)))


def _dot(a, b, dims=_NN):
    return jax.lax.dot_general(a, b, (dims, ((), ())),
                               preferred_element_type=_F32)


def _columns(tile, C):
    """[hg, C] (a head a sublane, a token a lane) -> [C, C] whose column j
    is head j's tokens down the sublanes: the tile padded to a square and
    transposed."""
    hg = tile.shape[0]
    if hg < C:
        tile = jnp.concatenate([tile, jnp.zeros((C - hg, C), _F32)], axis=0)
    return tile.T


def _rows_of(columns, hg):
    """The inverse of ``_columns``: [C, C] -> [hg, C]."""
    return columns.T[:hg]


def _lanes(cols, k, plan):
    """[C, W] of block k: lane l holds column ``k * pack + l // P`` of
    ``cols`` [C, >= hg] (each head's column broadcast over its P lanes)."""
    shape = (cols.shape[0], plan.W)
    h = k * plan.pack
    first = jnp.broadcast_to(cols[:, h:h + 1], shape)
    if plan.pack == 1:
        return first
    lane = jax.lax.broadcasted_iota(jnp.int32, shape, 1)
    return jnp.where(lane < plan.P, first, cols[:, h + 1:h + 2])


def _head_mask(j, plan, rows):
    """[rows, W] bool: the lanes of the block's j-th head (None where a
    block is one head)."""
    if plan.pack == 1:
        return None
    lane = jax.lax.broadcasted_iota(jnp.int32, (rows, plan.W), 1)
    return (lane >= j * plan.P) & (lane < (j + 1) * plan.P)


def _only(t, mask):
    return t if mask is None else jnp.where(mask, t, jnp.zeros_like(t))


def _chunk_common(i, b_ref, c_ref, dt_ref, a_ref, plan):
    """What a chunk's heads share: its rows, B, C, ``C B^T`` (float32), the
    gates' tiles a token a lane and a token a sublane, and the causal
    mask."""
    C = plan.C
    rows = pl.ds(pl.multiple_of(i * C, C), C)
    Bm, Cm = b_ref[0, rows, :], c_ref[0, rows, :]
    a_rows = a_ref[0, 0, i]                                 # [hg, C]
    dt_cols = _columns(dt_ref[0, 0, i], C)                  # [C, C]
    a_cols = _columns(a_rows, C)
    row = jax.lax.broadcasted_iota(jnp.int32, (C, C), 0)
    col = jax.lax.broadcasted_iota(jnp.int32, (C, C), 1)
    return rows, Bm, Cm, _dot(Cm, Bm, _NT), a_rows, a_cols, dt_cols, \
        row >= col


def _decay(a_cols, a_rows, h, causal):
    """``L`` [C, C] float32 of the group's head h: ``exp(a_i - a_j)`` on and
    below the diagonal, 0 above (the exponent masked first: nothing above
    the diagonal is formed)."""
    return jnp.exp(jnp.where(causal, a_cols[:, h:h + 1] - a_rows[h:h + 1],
                             -jnp.inf))


def _block_gates(a_cols, dt_cols, k, plan):
    """(dt, exp(a), exp(a_last - a) [C, W]; exp(a_last) [1, W]) of block
    k, each head's value on its own lanes."""
    a = _lanes(a_cols, k, plan)
    a_last = a[plan.C - 1:plan.C]
    return _lanes(dt_cols, k, plan), jnp.exp(a), jnp.exp(a_last - a), \
        jnp.exp(a_last)


def _ssd_fwd_kernel(x_ref, b_ref, c_ref, dt_ref, a_ref, d_ref, y_ref, *rest,
                    plan, keep):
    st_ref, s_ref = rest if keep else (None,) + rest
    dtype = x_ref.dtype
    C, W = plan.C, plan.W

    @pl.when(pl.program_id(1) == 0)
    def _():
        s_ref[...] = jnp.zeros_like(s_ref)

    def chunk(i, carry):
        rows, Bm, Cm, CB, a_rows, a_cols, dt_cols, causal = _chunk_common(
            i, b_ref, c_ref, dt_ref, a_ref, plan)
        for k in range(plan.blocks):
            lanes = slice(k * W, (k + 1) * W)
            dt, eG, e2, dl = _block_gates(a_cols, dt_cols, k, plan)
            xf = x_ref[0, rows, lanes].astype(_F32)
            xdt = xf * dt
            xdt_b = xdt.astype(dtype)
            S = s_ref[k]                                    # [N, W]
            if keep:
                st_ref[0, k, i] = S
            y = _dot(Cm, S.astype(dtype)) * eG + xf * d_ref[:, lanes]
            for j in range(plan.pack):
                M = (CB * _decay(a_cols, a_rows, k * plan.pack + j,
                                 causal)).astype(dtype)
                y += _dot(M, _only(xdt_b, _head_mask(j, plan, C)))
            y_ref[0, rows, lanes] = y.astype(dtype)
            s_ref[k] = S * dl + _dot(Bm, (xdt * e2).astype(dtype), _TN)
        return carry

    jax.lax.fori_loop(0, plan.cb, chunk, 0)


def _ssd_bwd_kernel(x_ref, b_ref, c_ref, dt_ref, a_ref, d_ref, st_ref, dy_ref,
                    dx_ref, db_ref, dc_ref, ddt_ref, da_ref, dd_ref, ds_ref,
                    *, plan):
    dtype = x_ref.dtype
    C, W, hg = plan.C, plan.W, plan.hg

    @pl.when(pl.program_id(1) == 0)
    def _():
        ds_ref[...] = jnp.zeros_like(ds_ref)
        dd_ref[...] = jnp.zeros_like(dd_ref)

    lane_c = jax.lax.broadcasted_iota(jnp.int32, (C, C), 1)
    last = jax.lax.broadcasted_iota(jnp.int32, (C, W), 0) == C - 1

    def chunk(t, carry):
        i = plan.cb - 1 - t
        rows, Bm, Cm, CB, a_rows, a_cols, dt_cols, causal = _chunk_common(
            i, b_ref, c_ref, dt_ref, a_ref, plan)
        dB = jnp.zeros((C, plan.N), _F32)
        dC = jnp.zeros((C, plan.N), _F32)
        dCB = jnp.zeros((C, C), _F32)
        # column h: head h's cotangent a token a sublane; the part of da
        # that arrives a token a lane is collected in ``da_rows``
        ddt_cols = jnp.zeros((C, C), _F32)
        da_cols = jnp.zeros((C, C), _F32)
        da_rows = []
        for k in range(plan.blocks):
            lanes = slice(k * W, (k + 1) * W)
            dt, eG, e2, dl = _block_gates(a_cols, dt_cols, k, plan)
            xf = x_ref[0, rows, lanes].astype(_F32)
            xdt = xf * dt
            xdt_b = xdt.astype(dtype)
            xdd_b = (xdt * e2).astype(dtype)
            S = st_ref[0, k, i]                             # [N, W] float32
            Sb = S.astype(dtype)
            dS = ds_ref[k]
            dSb = dS.astype(dtype)
            dy = dy_ref[0, rows, lanes]
            dyf = dy.astype(_F32)
            dyeg_b = (dyf * eG).astype(dtype)
            # y = (L o CB)(dt x) + e^a (C S) + D x;  S' = dl S + B^T (e2 dt x)
            CS = _dot(Cm, Sb)
            BdS = _dot(Bm, dSb)
            dxdt = BdS * e2
            de2 = BdS * xdt * e2
            # da, a lane of its head's P: e^a's part less e2's; the last
            # token's a also carries dl and every e2 of the chunk
            da_l = dyf * CS * eG - de2
            da_l = da_l + jnp.where(last, jnp.sum(
                de2, axis=0, keepdims=True) + dl * jnp.sum(
                S * dS, axis=0, keepdims=True), 0.0)
            dB += _dot(xdd_b, dSb, _NT)
            dC += _dot(dyeg_b, Sb, _NT)
            ds_ref[k] = dS * dl + _dot(Cm, dyeg_b, _TN)
            dd_ref[0, :, lanes] += jnp.sum(dyf * xf, axis=0, keepdims=True)
            for j in range(plan.pack):
                h = k * plan.pack + j
                mask = _head_mask(j, plan, C)
                L = _decay(a_cols, a_rows, h, causal)
                M = CB * L
                dxdt += _dot(M.astype(dtype), _only(dy, mask), _TN)
                dM = _dot(_only(dy, mask), xdt_b, _NT)      # [C, C]
                dCB += dM * L
                Wm = dM * M
                da_rows.append(jnp.sum(Wm, axis=0, keepdims=True))
                da_h = jnp.sum(Wm, axis=1, keepdims=True) + jnp.sum(
                    _only(da_l, mask), axis=1, keepdims=True)
                da_cols = jnp.where(lane_c == h, da_h, da_cols)
            for j in range(plan.pack):
                h = k * plan.pack + j
                ddt_h = jnp.sum(_only(dxdt * xf, _head_mask(j, plan, C)),
                                axis=1, keepdims=True)
                ddt_cols = jnp.where(lane_c == h, ddt_h, ddt_cols)
            dx_ref[0, rows, lanes] = (dxdt * dt + dyf
                                      * d_ref[:, lanes]).astype(dtype)
        dCB_b = dCB.astype(dtype)
        # the inputs' dtype where the group is this one head block, else
        # this block's float32 part of the group's sum
        db_ref[0, 0, rows, :] = (dB + _dot(dCB_b, Cm, _TN)).astype(
            db_ref.dtype)
        dc_ref[0, 0, rows, :] = (dC + _dot(dCB_b, Bm)).astype(dc_ref.dtype)
        ddt_ref[0, 0, i] = _rows_of(ddt_cols, hg)
        da_ref[0, 0, i] = _rows_of(da_cols, hg) \
            - jnp.concatenate(da_rows, axis=0)
        return carry

    jax.lax.fori_loop(0, plan.cb, chunk, 0)


# ------------------------------------------------------------- the calls

def _specs(plan, reverse=False):
    """BlockSpecs of (x or y, B or C, a gate array, D, the kept states, the
    dD accumulator, dB or dC) for a grid of (batch row x group x head
    block, block of chunks). Program p of a batch row owns head block
    ``p % head_blocks`` of group ``p // head_blocks``: the heads' lane
    columns [p * hg * P, (p + 1) * hg * P) — a group's heads lie side by
    side — and the group's columns of B and C."""
    C, cb, nb, Q, HB = plan.C, plan.cb, plan.nb, plan.programs, \
        plan.head_blocks

    def at(n):
        return nb - 1 - n if reverse else n

    def rows(p, n):
        return p // Q, at(n), p % Q

    def heads(p, n):
        return p // Q, p % Q, at(n), 0, 0

    return (pl.BlockSpec((1, cb * C, plan.hg * plan.P), rows),
            pl.BlockSpec((1, cb * C, plan.N),
                         lambda p, n: (p // Q, at(n), p % Q // HB)),
            pl.BlockSpec((1, 1, cb, plan.hg, C), heads),
            pl.BlockSpec((1, plan.hg * plan.P), lambda p, n: (0, p % Q)),
            pl.BlockSpec((1, plan.blocks, cb, plan.N, plan.W), heads),
            pl.BlockSpec((1, 1, plan.hg * plan.P),
                         lambda p, n: (p // Q, 0, p % Q)),
            pl.BlockSpec((1, 1, cb * C, plan.N),
                         lambda p, n: (p // Q, p % HB, at(n), p % Q // HB)))


def _call(kernel, plan, interpret, **kw):
    """``pallas_call`` over the plan's grid with the states' VMEM scratch."""
    how = {"interpret": True} if interpret else {
        "compiler_params": pltpu.CompilerParams(
            dimension_semantics=("parallel", "arbitrary"),
            vmem_limit_bytes=64 * 2 ** 20)}
    return pl.pallas_call(
        kernel, grid=(plan.B * plan.programs, plan.nb),
        scratch_shapes=[pltpu.VMEM((plan.blocks, plan.N, plan.W), _F32)],
        **how, **kw)


def _states_shape(plan):
    return jax.ShapeDtypeStruct(
        (plan.B, plan.H // plan.pack, plan.nb * plan.cb, plan.N, plan.W),
        _F32)


def _forward(x, Bm, Cm, dt, a, D, plan, interpret, keep):
    """y, or with ``keep`` (y, every chunk's starting states)."""
    xy, bc, gate, d, states, _, _ = _specs(plan)
    shapes = (jax.ShapeDtypeStruct(x.shape, x.dtype), _states_shape(plan))
    with annotate("ssd_scan_fwd"):
        return _call(
            functools.partial(_ssd_fwd_kernel, plan=plan, keep=keep), plan,
            interpret, in_specs=[xy, bc, bc, gate, gate, d],
            out_specs=(xy, states) if keep else xy,
            out_shape=shapes if keep else shapes[0])(x, Bm, Cm, dt, a, D)


def _backward(x, Bm, Cm, dt, a, D, states, dy, plan, interpret):
    xy, bc, gate, d, st, dd, dbc = _specs(plan, reverse=True)
    like = lambda t, dtype=None: jax.ShapeDtypeStruct(  # noqa: E731
        t.shape, dtype or t.dtype)
    # a head block's part of dB / dC: float32 where a group has several
    parts = jax.ShapeDtypeStruct(
        (plan.B, plan.head_blocks) + Bm.shape[1:],
        Bm.dtype if plan.head_blocks == 1 else _F32)
    with annotate("ssd_scan_bwd"):
        dx, dB, dC, ddt, da, dDx = _call(
            functools.partial(_ssd_bwd_kernel, plan=plan), plan, interpret,
            in_specs=[xy, bc, bc, gate, gate, d, st, xy],
            out_specs=(xy, dbc, dbc, gate, gate, dd),
            out_shape=(like(x), parts, parts, like(dt), like(a),
                       jax.ShapeDtypeStruct((plan.B, 1, plan.H * plan.P),
                                            _F32)))(
            x, Bm, Cm, dt, a, D, states, dy)
    with annotate("ssd_scan_prep"):
        dD = jnp.sum(dDx, axis=0)       # over the batch rows; D is a lane
        dB, dC = (jnp.sum(t, axis=1).astype(Bm.dtype) for t in (dB, dC))
    return dx, dB, dC, ddt, da, dD


@functools.lru_cache(maxsize=None)
def _rule(plan, interpret):
    """The custom VJP for one plan: the primal call writes y only, the
    forward rule also the state every chunk starts from, the two under
    ``SCAN_NAME`` (``scan_residuals.named_forward``: what a rematted block
    that does not keep the name runs twice, and one that keeps it once)."""
    forward = named_forward(functools.partial(
        _forward, plan=plan, interpret=interpret))

    @jax.custom_vjp
    def rule(x, Bm, Cm, dt, a, D):
        return _forward(x, Bm, Cm, dt, a, D, plan, interpret, keep=False)

    def fwd(x, Bm, Cm, dt, a, D):
        y, states = forward(x, Bm, Cm, dt, a, D)
        return y, (x, Bm, Cm, dt, a, D, states)

    def bwd(res, dy):
        return _backward(*res, dy, plan, interpret)

    rule.defvjp(fwd, bwd)
    return rule


_plans_logged = set()


def _note_plan(plan, dtype, interpret):
    """Trace-time engagement record: the gauges
    ``ssm/ssd_kernel_heads_per_step`` and ``ssm/ssd_head_blocks_per_group``
    and, once per distinct shape, a log line."""
    default_registry().gauge("ssm/ssd_kernel_heads_per_step").set(plan.hg)
    default_registry().gauge("ssm/ssd_head_blocks_per_group").set(
        plan.head_blocks)
    key = (plan, jnp.dtype(dtype).name, interpret)
    if key not in _plans_logged:
        _plans_logged.add(key)
        logger.info(
            f"state-space scan S={plan.nb * plan.cb * plan.C} H={plan.H} "
            f"P={plan.P} G={plan.G} N={plan.N} {key[1]}: Pallas kernels on "
            f"[B, S, H*P] column blocks, chunk={plan.C}, {plan.hg} heads a "
            f"grid step ({plan.pack} a lane block, {plan.head_blocks} head "
            f"block(s) a group), {plan.cb} chunks a grid "
            f"step, a float32 state kept every chunk for the backward pass"
            f"{' (interpreter)' if interpret else ''}")


def gate_layout(t, plan):
    """[B, S, H] -> [B, H / hg, n, hg, C] float32 (a head block's heads
    together), S padded to whole chunks (with zeros: a padded token's
    ``dt`` is 0)."""
    B, S, H = t.shape
    n = plan.nb * plan.cb
    t = jnp.pad(t.astype(_F32), ((0, 0), (0, n * plan.C - S), (0, 0)))
    t = t.transpose(0, 2, 1).reshape(B, plan.programs, plan.hg, n, plan.C)
    return t.transpose(0, 1, 3, 2, 4)


def ssd_scan_kernel(x, dt, A, B, C, D, chunk, interpret):
    """``ops.ssd.ssd_scan`` on the kernels: the same arguments and result,
    any S."""
    Bt, S, H, P = x.shape
    G, N = B.shape[2:]
    plan = _plan_for(Bt, S, H, G, P, N, chunk)
    _note_plan(plan, x.dtype, interpret)
    padded = plan.nb * plan.cb * chunk
    with annotate("ssd_scan_prep"):
        if padded > S:
            x, B, C = (jnp.pad(t, ((0, 0), (0, padded - S), (0, 0), (0, 0)))
                       for t in (x, B, C))
        x = x.reshape(Bt, padded, H * P)
        B, C = (t.reshape(Bt, padded, G * N).astype(x.dtype) for t in (B, C))
        dt = gate_layout(dt, plan)
        a = jnp.cumsum(dt * A.astype(_F32).reshape(
            1, plan.programs, 1, plan.hg, 1), axis=-1)
        D = jnp.repeat(D.astype(_F32), P)[None]             # [1, H*P]
    y = _rule(plan, bool(interpret))(x, B, C, dt, a, D)
    with annotate("ssd_scan_prep"):
        return y.reshape(Bt, padded, H, P)[:, :S]


def kept_row_bytes(heads, head_dim, state, chunk, itemsize):
    """Bytes a token one layer's forward rule writes under ``SCAN_NAME``
    (what a rematted block that keeps the name holds from its forward pass
    to its backward): y in the inputs' dtype and a float32 state
    [state, head_dim] a head every ``chunk`` tokens — 402 MB a layer at
    16,384 tokens, 64 heads of 64 x 128, chunks of 128."""
    return heads * head_dim * itemsize + 4 * heads * state * head_dim // chunk
