"""The chunked gated delta rule as Pallas TPU kernels, forward and backward.

``ops/gated_delta.py`` has the rule and its XLA form. Here one program owns
a (batch row, group of value heads) and walks that sequence's chunks in
order with the state ``S`` [Dk, Dv] of each head in float32 VMEM scratch.
Per chunk it builds, IN VMEM from the chunk's q, k, v, G, beta tiles, what
the XLA form lays out in HBM for all chunks at once — the decay mask,
``L``, ``T = (I + L)^-1``, ``u``, ``w``, ``qg``, the masked ``q k^T`` and
``kd`` — runs the three state matmuls and writes ``o``. Nothing of
[C, C] or [C, D] size goes to HBM between those steps.

- **Layout.** q, k [B, S, Hk*Dk] and v, o [B, S, Hv*Dv] are the model's own
  arrays (a reshape of [B, S, H, D]); a program takes the lane columns of
  its key heads and of the ``rep`` value heads each of them serves, so no
  repeated, chunk-major or head-major copy exists. The gates are the only
  operands laid out first: ``G`` (the running sum of g inside each chunk)
  and beta as [B, Hv / hb, N, hb, C] float32, a token a lane (2 MB each at
  2 x 8192 tokens, 32 heads). A head's columns are whole 128-lane tiles:
  head sizes off that grid which whole tiles widen by at most a third
  (``lane_heads``: 96 x 192 -> 128 x 256) come ZERO-PADDED — by the layer,
  which lays q | k | v out once for this rule and the elementwise stages
  round it, or by ``ops.gated_delta.gated_delta_rule`` itself — and the
  zeros are exact (no lane of q.k, k k^T or the state's other rows and
  columns sees them); ``linear_attn/gdn_lane_overcompute`` prices the
  lanes, and other sizes fall to the XLA form with one log line.
- **The inverse** is float32 and exact in form: forward substitution inside
  the ``_SUB`` x ``_SUB`` diagonal blocks as rank-one updates on the VPU,
  then the doubling rounds of ``unit_lower_inverse`` (``X - X C_s X``) for
  the blocks above, on the half of the rows a round changes; their
  float32 products are three bf16 passes (``_dot_x3``).
- **Roundings** are the XLA form's: state, gates, decay and ``T`` float32;
  ``T``, ``u``, ``w``, ``v'`` and the masked ``q k^T`` cast to the inputs'
  dtype before their matmuls, float32 accumulation. One difference: beta
  multiplies ``k k^T`` after the matmul, in float32 (two value heads share
  one key head's ``k k^T`` and ``q k^T``), where the XLA form rounds
  ``beta k`` to the inputs' dtype first.
- **Backward.** The forward RULE also writes the state every chunk
  starts from, in the inputs' dtype ([B, Hv, N, Dk, Dv]: 268 MB a layer
  in bf16 at the sizes above), and every chunk's ``T`` in float32 (268 MB
  as padded tiles), and names them and o ``scan_states``
  (``scan_residuals.py``). A rematted block that does not keep the name
  runs the rule as its recomputation — its forward pass runs the primal
  call, which writes o alone — and the two are alive from a layer's
  recomputation to its backward pass; one that keeps it
  (``runtime/remat_budget.py``, where the bytes fit) runs the rule's
  kernel once, in its forward pass. The backward kernel walks the chunks
  in REVERSE with ``dS`` in VMEM: it builds the chunk's preparation again from the tiles and the
  kept ``T`` (it never inverts), takes the loop's cotangents, then the
  preparation's (``dT``, ``dL = -T^T dT T^T``, the decay's, the gates')
  while the tiles are resident, and writes dq, dk (summed over a key
  head's value heads in float32), dv, dG and dbeta.

The XLA ops left round the kernels (the gates' re-layout and the two
within-chunk cumulative sums) are traced under ``gdn_scan_prep``; the
kernels under ``gdn_scan_fwd`` / ``gdn_scan_bwd``.
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

# side of the diagonal blocks of L inverted by substitution on the VPU; the
# blocks above them merge by matmuls. Measured on a v5e, ms a forward call at
# 2 x 8192 tokens: 8 -> 11.2, 16 -> 9.6, 32 -> 10.0, 64 -> 12.9 (PERF.md, PR 32)
_SUB = 16
# chunks a grid step (4 / 8 / 16 measured alike: 11.21 / 11.22 / 11.07 ms)
_BLOCK_CHUNKS = 8
_NN, _NT, _TN = ((1,), (0,)), ((1,), (1,)), ((0,), (0,))
_F32 = jnp.float32
LANES = 128


class _Plan(collections.namedtuple("_Plan", "B Hk Hv Dk Dv C cb kg nb")):
    """What a call's shapes decide: chunk C, cb chunks a grid step, nb grid
    steps a sequence, kg key heads (hb value heads) a grid step."""

    @property
    def rep(self):
        return self.Hv // self.Hk

    @property
    def hb(self):
        return self.kg * self.rep


def _plan_for(B, S, Hk, Hv, Dk, Dv, C):
    n = -(-S // C)
    cb = next(c for c in (_BLOCK_CHUNKS, 4, 2, 1) if n % c == 0)
    return _Plan(B, Hk, Hv, Dk, Dv, C, cb, _heads_per_step(Hk, Hv), n // cb)


def lane_heads(Dk, Dv):
    """The head sizes the kernels run a head of ``Dk`` x ``Dv`` at: each
    rounded up to whole 128-lane tiles where that adds at most a third of
    its lanes (96 -> 128, 192 -> 256), else itself. Zero lanes are exact:
    they change neither a head's L2 norm nor q.k nor k k^T, the state's
    padded rows and columns stay zero, and o's padded lanes are zero."""
    def up(d):
        tiles = -(-d // LANES) * LANES
        return tiles if 3 * tiles <= 4 * d else d
    return up(Dk), up(Dv)


_refused = set()


def takes_kernel(Dk, Dv, tpu):
    """Whether the kernels take heads of ``Dk`` x ``Dv``: on a TPU backend
    a head must be lane-aligned column blocks of the model's arrays, as it
    is or zero-padded by ``lane_heads``' rule; the interpreter (any other
    backend) takes any shape. A refused shape is logged once, with the
    size that refused it."""
    odd = [f"{name} {d}" for name, d, lanes in zip(
        ("Dk", "Dv"), (Dk, Dv), lane_heads(Dk, Dv)) if lanes % LANES]
    if tpu and odd and (Dk, Dv) not in _refused:
        _refused.add((Dk, Dv))
        logger.info(
            f"gated delta rule heads of {Dk} x {Dv}: the XLA form ("
            + " and ".join(odd) + " is no multiple of 128 lanes and whole "
            "tiles would add more than a third)")
    return not (tpu and odd)


def _dot(a, b, dims=_NN):
    return jax.lax.dot_general(a, b, (dims, ((), ())),
                               preferred_element_type=_F32)


def _dot_x3(a, b, dims=_NN):
    """A float32 product as three bf16 passes: a = a_hi + a_lo, b = b_hi +
    b_lo, without the lo x lo term (relative error ~2^-16; HIGHEST makes
    six). Written out, so that the interpreter rounds as the chip does:
    the float32 tests hold the inverse to their limits WITH this split
    (PERF.md, PR 32)."""
    bf = jnp.bfloat16
    a_hi, b_hi = a.astype(bf), b.astype(bf)
    a_lo = (a - a_hi.astype(_F32)).astype(bf)
    b_lo = (b - b_hi.astype(_F32)).astype(bf)
    return (_dot(a_hi, b_hi, dims) + _dot(a_hi, b_lo, dims)
            + _dot(a_lo, b_hi, dims))


def _iotas(C):
    row = jax.lax.broadcasted_iota(jnp.int32, (C, C), 0)
    col = jax.lax.broadcasted_iota(jnp.int32, (C, C), 1)
    return row, col


def _to_col(vec, eye):
    """[1, C] -> [C, 1] without a transpose: one masked lane reduction."""
    return jnp.sum(jnp.where(eye, vec, 0.0), axis=1, keepdims=True)


def _to_row(vec, eye):
    """[C, 1] -> [1, C]: one masked sublane reduction."""
    return jnp.sum(jnp.where(eye, vec, 0.0), axis=0, keepdims=True)


def _total(x):
    """Sum of every element, as [1, 1]."""
    return jnp.sum(jnp.sum(x, axis=1, keepdims=True), axis=0, keepdims=True)


def _unit_lower_inverse(lower):
    """``(I + L)^-1`` [C, C] float32 for strictly lower ``L``: forward
    substitution in the diagonal blocks (``X <- X - l_j x_j^T`` for each
    column j of a block: row j of X is final by then), then the block
    rounds of ``ops.gated_delta.unit_lower_inverse``, ``X - X C_s X``, on
    the rows they change: the lower half of every 2s block."""
    C = lower.shape[0]
    sub = min(_SUB, C)
    lane = jax.lax.broadcasted_iota(jnp.int32, (sub, C), 1)
    line = jax.lax.broadcasted_iota(jnp.int32, (sub, C), 0)
    slabs = []                              # the rows of X in pieces of s
    for b in range(C // sub):
        l_b = lower[b * sub:(b + 1) * sub]                  # [sub, C]
        x_b = jnp.where(lane == line + b * sub, 1.0, 0.0).astype(_F32)
        for j in range(sub - 1):
            x_b = x_b - l_b[:, b * sub + j:b * sub + j + 1] * x_b[j:j + 1]
        slabs.append(x_b)
    s = sub
    while s < C:
        lane = jax.lax.broadcasted_iota(jnp.int32, (s, C), 1)
        odd = range(1, C // s, 2)           # the pieces a round changes
        x = jnp.concatenate(slabs, axis=0)
        low = jnp.concatenate([slabs[i] for i in odd], axis=0)
        c_low = jnp.concatenate([jnp.where(
            (lane >= (i - 1) * s) & (lane < i * s), lower[i * s:(i + 1) * s],
            0.0) for i in odd], axis=0)
        cx = _dot_x3(c_low, x)                              # [C / 2, C]
        zero = jnp.zeros((s, C), _F32)
        spread = jnp.concatenate(
            [zero if i % 2 == 0 else cx[i // 2 * s:(i // 2 + 1) * s]
             for i in range(C // s)], axis=0)
        y = _dot_x3(low, spread)                            # [C / 2, C]
        slabs = [jnp.concatenate(
            [slabs[i], slabs[i + 1] - y[i // 2 * s:(i // 2 + 1) * s]], axis=0)
            for i in range(0, C // s, 2)]
        s *= 2
    return slabs[0]


def _prepare(q, k, v, KK, QK, G_row, b_row, row, col, T=None):
    """A chunk's preparation for one value head, from its tiles: every
    array the loop and the backward pass read. ``KK`` = k k^T and ``QK`` =
    q k^T (float32) are the key head's, shared by its value heads; ``T``
    is taken where the forward rule kept it, else inverted here."""
    C = q.shape[0]
    dt = q.dtype
    eye = row == col
    G = _to_col(G_row, eye)                                 # [C, 1]
    beta = _to_col(b_row, eye)
    G_last = jnp.sum(jnp.where(col[:1] == C - 1, G_row, 0.0), axis=1,
                     keepdims=True)                         # [1, 1]
    # exp of a masked difference: nothing above the diagonal is formed
    decay = jnp.exp(jnp.where(row >= col, G - G_row, -jnp.inf))
    strict = row > col
    if T is None:
        T = _unit_lower_inverse(jnp.where(strict, beta * KK * decay, 0.0))
    eG = jnp.exp(G)
    e2 = jnp.exp(G_last - G)
    kb = (k.astype(_F32) * beta).astype(dt)
    p = dict(
        beta=beta, decay=decay, strict=strict, T=T, Tb=T.astype(dt),
        eG=eG, e2=e2, dl=jnp.exp(G_last), kb=kb,
        vb=(v.astype(_F32) * beta).astype(dt),
        kbg=(kb.astype(_F32) * eG).astype(dt),
        qg=(q.astype(_F32) * eG).astype(dt),
        attn=jnp.where(row >= col, QK * decay, 0.0).astype(dt),
        kd=(k.astype(_F32) * e2).astype(dt))
    p["u"] = _dot(p["Tb"], p["vb"]).astype(dt)
    p["w"] = _dot(p["Tb"], p["kbg"]).astype(dt)
    return p


def _key_head(q_ref, k_ref, i, a, plan):
    """(rows of chunk i, q, k, k k^T, q k^T) of the step's key head a."""
    rows = pl.ds(pl.multiple_of(i * plan.C, plan.C), plan.C)
    cols = slice(a * plan.Dk, (a + 1) * plan.Dk)
    q, k = q_ref[0, rows, cols], k_ref[0, rows, cols]
    return rows, q, k, _dot(k, k, _NT), _dot(q, k, _NT)


def _gdn_fwd_kernel(q_ref, k_ref, v_ref, g_ref, b_ref, o_ref, *rest, plan,
                    keep):
    st_ref, t_ref, s_ref = rest if keep else (None, None) + rest
    dt = q_ref.dtype
    C, Dv, rep = plan.C, plan.Dv, plan.rep

    @pl.when(pl.program_id(1) == 0)
    def _():
        s_ref[...] = jnp.zeros_like(s_ref)

    row, col = _iotas(C)

    def chunk(i, carry):
        gates, betas = g_ref[0, 0, i], b_ref[0, 0, i]       # [hb, C]
        for a in range(plan.kg):
            rows, q, k, KK, QK = _key_head(q_ref, k_ref, i, a, plan)
            for h in range(a * rep, (a + 1) * rep):
                v = v_ref[0, rows, h * Dv:(h + 1) * Dv]
                p = _prepare(q, k, v, KK, QK, gates[h:h + 1],
                             betas[h:h + 1], row, col)
                S = s_ref[h]
                Sb = S.astype(dt)
                if keep:
                    st_ref[0, h, i] = Sb
                    t_ref[0, h, i] = p["T"]
                v_new = (p["u"] - _dot(p["w"], Sb)).astype(dt)
                o = _dot(p["qg"], Sb) + _dot(p["attn"], v_new)
                o_ref[0, rows, h * Dv:(h + 1) * Dv] = o.astype(dt)
                s_ref[h] = S * p["dl"] + _dot(p["kd"], v_new, _TN)
        return carry

    jax.lax.fori_loop(0, plan.cb, chunk, 0)


def _gdn_bwd_kernel(q_ref, k_ref, v_ref, g_ref, b_ref, st_ref, t_ref, do_ref,
                    dq_ref, dk_ref, dv_ref, dg_ref, db_ref, ds_ref, *, plan):
    dt = q_ref.dtype
    C, Dk, Dv, rep = plan.C, plan.Dk, plan.Dv, plan.rep

    @pl.when(pl.program_id(1) == 0)
    def _():
        ds_ref[...] = jnp.zeros_like(ds_ref)

    row, col = _iotas(C)
    eye = row == col
    last = jax.lax.broadcasted_iota(jnp.int32, (C, 1), 0) == C - 1

    def chunk(t, carry):
        i = plan.cb - 1 - t
        gates, betas = g_ref[0, 0, i], b_ref[0, 0, i]       # [hb, C]
        for a in range(plan.kg):
            rows, q, k, KK, QK = _key_head(q_ref, k_ref, i, a, plan)
            qf, kf = q.astype(_F32), k.astype(_F32)
            dq = jnp.zeros((C, Dk), _F32)
            dk = jnp.zeros((C, Dk), _F32)
            for h in range(a * rep, (a + 1) * rep):
                v = v_ref[0, rows, h * Dv:(h + 1) * Dv]
                do = do_ref[0, rows, h * Dv:(h + 1) * Dv]
                p = _prepare(q, k, v, KK, QK, gates[h:h + 1],
                             betas[h:h + 1], row, col, t_ref[0, h, i])
                Sb = st_ref[0, h, i]                        # [Dk, Dv]
                dS = ds_ref[h]
                dSb = dS.astype(dt)
                v_new = (p["u"] - _dot(p["w"], Sb)).astype(dt)
                # the loop: v' = u - w S, o = qg S + A v', S' = dl S + kd^T v'
                dvn = _dot(p["attn"], do, _TN) + _dot(p["kd"], dSb)
                dvn_b = dvn.astype(dt)
                dattn = _dot(do, v_new, _NT)                # masked below
                dqg = _dot(do, Sb, _NT)
                dkd = _dot(v_new, dSb, _NT)
                ddl = _total(Sb.astype(_F32) * dS)
                dw = -_dot(dvn_b, Sb, _NT)
                dw_b = dw.astype(dt)
                ds_ref[h] = (_dot(p["qg"], do, _TN) + p["dl"] * dS
                             - _dot(p["w"], dvn_b, _TN))
                # the preparation: u = T (beta v), w = T (beta k e^G)
                dT = _dot(dvn_b, p["vb"], _NT) + _dot(dw_b, p["kbg"], _NT)
                dvb = _dot(p["Tb"], dvn_b, _TN)
                dkbg = _dot(p["Tb"], dw_b, _TN)
                dL = -_dot_x3(p["T"], _dot_x3(dT, p["T"], _NT), _TN)
                m1 = jnp.where(p["strict"], dL * p["decay"], 0.0)
                a1 = m1 * KK
                dQK = jnp.where(row >= col, dattn * p["decay"], 0.0)
                dM = a1 * p["beta"] + dQK * QK
                dKK_b = (m1 * p["beta"]).astype(dt)
                dQK_b = dQK.astype(dt)
                dk += (_dot(dKK_b, k) + _dot(dKK_b, k, _TN)
                       + _dot(dQK_b, q, _TN))
                dq += _dot(dQK_b, k)
                dv_ref[0, rows, h * Dv:(h + 1) * Dv] = (
                    dvb * p["beta"]).astype(dt)
                dkb = dkbg * p["eG"]
                dk += dkb * p["beta"] + dkd * p["e2"]
                dq += dqg * p["eG"]
                dbeta = (jnp.sum(a1, axis=1, keepdims=True)
                         + jnp.sum(dvb * v.astype(_F32), axis=1,
                                   keepdims=True)
                         + jnp.sum(dkb * kf, axis=1, keepdims=True))
                de2 = jnp.sum(dkd * kf, axis=1, keepdims=True) * p["e2"]
                dG = (jnp.sum(dM, axis=1, keepdims=True)
                      + p["eG"] * jnp.sum(
                          dkbg * p["kb"].astype(_F32) + dqg * qf, axis=1,
                          keepdims=True)
                      - de2
                      + jnp.where(last, _total(de2) + ddl * p["dl"], 0.0))
                dg_ref[0, 0, i, h:h + 1] = (
                    _to_row(dG, eye) - jnp.sum(dM, axis=0, keepdims=True))
                db_ref[0, 0, i, h:h + 1] = _to_row(dbeta, eye)
            dq_ref[0, rows, a * Dk:(a + 1) * Dk] = dq.astype(dt)
            dk_ref[0, rows, a * Dk:(a + 1) * Dk] = dk.astype(dt)
        return carry

    jax.lax.fori_loop(0, plan.cb, chunk, 0)


# ------------------------------------------------------------- the calls

def _specs(plan, reverse=False):
    """BlockSpecs of (q or k, v or o, a gate array, the kept states, the
    kept inverses) for a grid of (batch row x head group, block of
    chunks)."""
    B, Hk, Hv, Dk, Dv, C, cb, kg, nb = plan
    groups, hb = Hk // kg, plan.hb

    def at(n):
        return nb - 1 - n if reverse else n

    def rows(p, n):
        return p // groups, at(n), p % groups

    def heads(p, n):
        return p // groups, p % groups, at(n), 0, 0

    return (pl.BlockSpec((1, cb * C, kg * Dk), rows),
            pl.BlockSpec((1, cb * C, hb * Dv), rows),
            pl.BlockSpec((1, 1, cb, hb, C), heads),
            pl.BlockSpec((1, hb, cb, Dk, Dv), heads),
            pl.BlockSpec((1, hb, cb, C, C), heads))


def _call(kernel, plan, interpret, **kw):
    """``pallas_call`` over the plan's grid with the state's VMEM scratch."""
    how = {"interpret": True} if interpret else {
        "compiler_params": pltpu.CompilerParams(
            dimension_semantics=("parallel", "arbitrary"),
            vmem_limit_bytes=64 * 2 ** 20)}
    return pl.pallas_call(
        kernel, grid=(plan.B * plan.Hk // plan.kg, plan.nb),
        scratch_shapes=[pltpu.VMEM((plan.hb, plan.Dk, plan.Dv), _F32)],
        **how, **kw)


def _forward(q, k, v, G, beta, plan, interpret, keep):
    """o, or with ``keep`` (o, every chunk's starting state, every T)."""
    qk, vo, gate, states, inverses = _specs(plan)
    chunks = plan.nb * plan.cb
    shapes = (jax.ShapeDtypeStruct(v.shape, v.dtype),
              jax.ShapeDtypeStruct(
                  (plan.B, plan.Hv, chunks, plan.Dk, plan.Dv), v.dtype),
              jax.ShapeDtypeStruct(
                  (plan.B, plan.Hv, chunks, plan.C, plan.C), _F32))
    with annotate("gdn_scan_fwd"):
        return _call(
            functools.partial(_gdn_fwd_kernel, plan=plan, keep=keep), plan,
            interpret, in_specs=[qk, qk, vo, gate, gate],
            out_specs=(vo, states, inverses) if keep else vo,
            out_shape=shapes if keep else shapes[0])(q, k, v, G, beta)


def _backward(q, k, v, G, beta, states, inverses, do, plan, interpret):
    qk, vo, gate, st, inv = _specs(plan, reverse=True)
    like = lambda t, dtype=None: jax.ShapeDtypeStruct(  # noqa: E731
        t.shape, dtype or t.dtype)
    with annotate("gdn_scan_bwd"):
        return _call(
            functools.partial(_gdn_bwd_kernel, plan=plan), plan, interpret,
            in_specs=[qk, qk, vo, gate, gate, st, inv, vo],
            out_specs=(qk, qk, vo, gate, gate),
            out_shape=(like(q), like(k), like(v), like(G, _F32),
                       like(beta, _F32)))(q, k, v, G, beta, states,
                                          inverses, do)


@functools.lru_cache(maxsize=None)
def _rule(plan, interpret):
    """The custom VJP for one plan: the primal call writes o only, the
    forward rule also the state every chunk starts from and its T, the
    three under ``SCAN_NAME`` (``scan_residuals.named_forward``: what a
    rematted block that does not keep the name runs twice, and one that
    keeps it once)."""
    forward = named_forward(functools.partial(
        _forward, plan=plan, interpret=interpret))

    @jax.custom_vjp
    def rule(q, k, v, G, beta):
        return _forward(q, k, v, G, beta, plan, interpret, keep=False)

    def fwd(q, k, v, G, beta):
        o, states, inverses = forward(q, k, v, G, beta)
        return o, (q, k, v, G, beta, states, inverses)

    def bwd(res, do):
        return _backward(*res, do, plan, interpret)

    rule.defvjp(fwd, bwd)
    return rule


def _heads_per_step(Hk, Hv):
    """Key heads a grid step (each with the value heads it serves): the
    heads of a step are independent chains in one basic block and hide
    each other's latencies. Measured at 2 x 8192 tokens, 16 / 32 heads of
    128 (PERF.md, PR 32): two key heads (four value heads) 8.9 ms a
    forward call against 9.6 with one."""
    return 2 if Hk % 2 == 0 and Hv // Hk <= 2 else 1


_plans_logged = set()


def lane_count(Dk, Dv):
    """Lanes a token's q | k | v and a head's state take at ``Dk`` x
    ``Dv``: what ``linear_attn/gdn_lane_overcompute`` is a ratio of."""
    return 2 * Dk + Dv + Dk * Dv


def _note_plan(plan, dtype, interpret, heads=None):
    """Trace-time engagement record: the gauges
    ``linear_attn/gdn_kernel_heads_per_step``,
    ``linear_attn/gdn_states_kept_every`` and
    ``linear_attn/gdn_lane_overcompute`` (the lanes of q | k | v | state
    the kernels compute on over those of ``heads``, the model's own (Dk,
    Dv) where the operands came zero-padded to whole tiles; 1.0 where they
    are the plan's) and, once per distinct shape, a log line."""
    gauge = default_registry().gauge
    gauge("linear_attn/gdn_kernel_heads_per_step").set(plan.hb)
    gauge("linear_attn/gdn_states_kept_every").set(1)
    Dk, Dv = heads or (plan.Dk, plan.Dv)
    gauge("linear_attn/gdn_lane_overcompute").set(
        lane_count(plan.Dk, plan.Dv) / lane_count(Dk, Dv))
    key = (plan, jnp.dtype(dtype).name, interpret, Dk, Dv)
    if key not in _plans_logged:
        _plans_logged.add(key)
        logger.info(
            f"gated delta rule S={plan.nb * plan.cb * plan.C} Hk={plan.Hk} "
            f"Hv={plan.Hv} Dk={plan.Dk} Dv={plan.Dv} {key[1]}: Pallas "
            + (f"kernels on heads of {Dk} x {Dv} zero-padded to whole "
               f"tiles, " if (Dk, Dv) != (plan.Dk, plan.Dv) else "kernels ")
            + f"on [B, S, H*D] column blocks, chunk={plan.C}, "
            f"{plan.hb} value heads a grid step, {plan.cb} chunks a grid "
            f"step, a state kept every chunk for the backward pass"
            f"{' (interpreter)' if interpret else ''}")


def gate_layout(t, plan):
    """[B, S, Hv] -> [B, Hv / hb, N, hb, C] float32, S padded to whole
    chunks; head-major first, so that no array with a head or two as its
    minor dimension is formed."""
    B, S, Hv = t.shape
    n = plan.nb * plan.cb
    t = jnp.pad(t.astype(_F32), ((0, 0), (0, n * plan.C - S), (0, 0)))
    t = t.transpose(0, 2, 1).reshape(B, Hv // plan.hb, plan.hb, n, plan.C)
    return t.transpose(0, 1, 3, 2, 4)


def gated_delta_rule_kernel(q, k, v, g, beta, chunk, interpret, heads=None):
    """``ops.gated_delta.gated_delta_rule`` on the kernels: the same
    arguments and result, any S (a short last chunk is padded with tokens
    that write nothing). ``heads``: the model's (Dk, Dv) where the operands'
    heads came zero-padded (the lane gauge's denominator)."""
    B, S, Hv, Dv = v.shape
    Hk, Dk = k.shape[2:]
    plan = _plan_for(B, S, Hk, Hv, Dk, Dv, chunk)
    _note_plan(plan, v.dtype, interpret, heads)
    padded = plan.nb * plan.cb * chunk
    with annotate("gdn_scan_prep"):
        if padded > S:
            q, k, v = (jnp.pad(t, ((0, 0), (0, padded - S), (0, 0), (0, 0)))
                       for t in (q, k, v))
        q, k = (t.reshape(B, padded, Hk * Dk) for t in (q, k))
        v = v.reshape(B, padded, Hv * Dv)
        G = jnp.cumsum(gate_layout(g, plan), axis=-1)
        beta = gate_layout(beta, plan)
    o = _rule(plan, bool(interpret))(q, k, v, G, beta)
    with annotate("gdn_scan_prep"):
        return o.reshape(B, padded, Hv, Dv)[:, :S]


def kept_row_bytes(value_heads, key_dim, value_dim, chunk, itemsize):
    """Bytes a token one layer's forward rule writes under ``SCAN_NAME``
    (what a rematted block that keeps the name holds from its forward pass
    to its backward): o and a state [key_dim, value_dim] a value head every
    ``chunk`` tokens in the inputs' dtype, and a float32 ``T`` [chunk,
    chunk] a head and chunk as the 128-lane tiles it is stored in — 134 +
    268 + 268 MB a layer at 2 x 8192 tokens, 32 heads of 128 x 128, chunks
    of 64."""
    return value_heads * (itemsize * (value_dim + key_dim * value_dim // chunk)
                          + 4 * max(chunk, 128))
