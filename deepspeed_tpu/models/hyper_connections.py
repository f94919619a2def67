"""Manifold-constrained hyper-connections (mHC, arXiv 2512.24880): a
residual path of ``n`` streams that every branch reads through one learned
mix and writes back through another, the stream-to-stream mix a doubly
stochastic matrix.

Per token the stream is ``X in R^{n x C}``. Round a branch ``F`` (a mixer or
an FFN with its input norm):

    v      = flatten(X) in R^{nC};   v' = v / sqrt(mean(v^2) + eps)
    Ht_pre = a_pre (v' phi_pre) + b_pre          in R^n
    Ht_post= a_post (v' phi_post) + b_post       in R^n
    Ht_res = a_res mat(v' phi_res) + b_res       in R^{n x n}
    H_pre  = sigmoid(Ht_pre);   H_post = 2 sigmoid(Ht_post)
    M      = exp(clip(Ht_res, clamp_min, clamp_max)); ``sinkhorn_iters``
             rounds of: every column over its sum, then every row over its
             sum; H_res = M after the last
    u      = sum_j H_pre[j] X[j];   y = F(u)
    X_new[i] = sum_j H_res[i, j] X[j] + H_post[i] y

``phi = [phi_pre | phi_post | phi_res]`` is ONE matrix ``[nC, 2n + n^2]``,
``bias`` its ``2n + n^2`` offsets and ``gate`` the three scalars ``a``; the
norm carries no weight. The embedding is copied into the ``n`` streams
(``spread``) and the streams are summed after the last layer (``merge``).

**Layout.** The stream travels as ``[B, S, n C]`` — the ``[B, S, n, C]``
array flattened, stream ``j`` the columns ``[j C, (j + 1) C)`` — so that the
tiled minor dimensions are ``(S, n C)``: a stream axis of 4 in second-minor
place would be padded to a sublane tile (16 rows in bf16, four times the
bytes). ``v`` is then the carry as it is, and a stream is a column slice at
a multiple of 128 lanes. The coefficients are float32 and travel
TOKEN-MINOR (``[n, T]``, ``[n, n, T]``): 24 numbers a token in the lanes of
a ``[T, 24]`` array would fill 24 of 128, and Sinkhorn's 20 rounds run over
them.

**Two forms**, picked at trace time from the shapes (``_plan``): streams of
whole 128-lane columns, a multiple of 128 tokens and one device take
the Pallas kernels of ``ops/pallas/mhc_stream.py`` (the interpreter off a
TPU) — every pass over the stream reads it once in its own dtype, sums in
float32 in VMEM and writes once, forward and backward; anything else (the
tiny models of the tests and the CPU rehearsals among it, and an engine's
mesh of several devices: a Mosaic call is not partitioned) takes the ``jnp``
forms below, which are also the kernels' oracles. No option selects a form;
the trace-time gauges ``mhc/kernel_sites`` / ``mhc/xla_sites`` count the
calls of ``StreamMixer``, ``write``, ``spread`` and ``merge`` that took each,
one log line a distinct shape says which.

What the kernel form costs a program BEFORE it runs is paid once a (pass,
shapes), not once a site: ``ops/pallas/mhc_stream.py``'s entries are
``jax.jit`` functions with the plan static (a body traced once, ONE function
of the lowered module that every site calls), and the ``custom_vjp`` rules
and the ends' ``linear_call`` pairs below are built once a plan
(``functools.lru_cache``).

A branch is ``u, coeff, x = mix(mixer, x); y = F(u); x = write(x, y, ...)``:
``mix`` gives the coefficients AND ``u`` in one pass, and hands the stream
on, so that the cotangent of ``write``'s stream arrives at ``mix``'s own
backward rule and is added there, in VMEM, to what came through ``u`` and
the coefficients: dX leaves a branch as one array written by one pass. Each
entry differentiates correctly alone; ``mixer(x)``, ``read`` and ``write``
on the stream itself give the same numbers with XLA adding their dX.

Scopes: ``mhc_coeff`` (norm, projection, sigmoids, Sinkhorn — and, in the
kernel form, ``u`` with them forward and the pass that writes dX backward),
``mhc_read`` (``u`` in the ``jnp`` form; ``merge`` where the model puts it
there), ``mhc_write`` (``X_new`` and its backward; ``spread`` likewise) —
``benchmark/layer_metrics/mhc_stream_ms``.
"""

import functools

from typing import Any

import numpy as np
import jax
import jax.numpy as jnp
import flax.linen as nn
from jax.custom_derivatives import linear_call

from deepspeed_tpu.ops.attention import _device_axes
from deepspeed_tpu.ops.pallas import mhc_stream as kernels
from deepspeed_tpu.telemetry.registry import default_registry
from deepspeed_tpu.telemetry.spans import annotate
from deepspeed_tpu.utils.logging import logger
from deepspeed_tpu.utils.platform import is_tpu_backend

# what ``StreamMixer`` sows into ``stats``, and the gauge it is read under:
# the largest |row sum - 1| or |column sum - 1| of ``H_res`` — how doubly
# stochastic the rounds left it. A model lists it among its ``stat_maxima``
# (the engine then folds the LARGEST value sown in the step, not the mean)
STAT_GAUGES = {"mhc_res_sum_err": "mhc/res_sum_err"}


def spread(x, n):
    """``x`` [B, S, C] copied into ``n`` streams: [B, S, n C]."""
    plan = _plan(x, n, x.shape[-1])
    if plan is None:
        return jnp.tile(x, (1, 1, n))
    return _ends(plan, not is_tpu_backend())[0](
        x.reshape(plan.T, -1)).reshape(*x.shape[:-1], -1)


def merge(x, n):
    """The ``n`` streams of ``x`` [B, S, n C] summed: [B, S, C] (float32
    sum, the carry's dtype)."""
    plan = _plan(x, n, x.shape[-1] // n)
    if plan is None:
        parts = jnp.split(x.astype(jnp.float32), n, axis=-1)
        return sum(parts[1:], parts[0]).astype(x.dtype)
    return _ends(plan, not is_tpu_backend())[1](
        x.reshape(plan.T, -1)).reshape(*x.shape[:-1], -1)


def sinkhorn(m, iters):
    """``m`` [n, n, ...] positive: ``iters`` rounds of every column over its
    sum (axis 0 runs down a column), then every row over its sum."""
    for _ in range(iters):
        m = m / jnp.sum(m, axis=0, keepdims=True)
        m = m / jnp.sum(m, axis=1, keepdims=True)
    return m


def res_sum_err(h_res):
    """The largest |row sum - 1| or |column sum - 1| of ``h_res``
    [n, n, ...]."""
    return jnp.maximum(jnp.max(jnp.abs(jnp.sum(h_res, axis=0) - 1.0)),
                       jnp.max(jnp.abs(jnp.sum(h_res, axis=1) - 1.0)))


def _normal_round(mean, std):
    def init(key, shape, dtype=jnp.float32):
        return mean + std * jax.random.normal(key, shape, dtype)
    return init


_noted = set()


def _plan(x, n, C, iters=0, eps=0.0, clamp=(0.0, 0.0)):
    """The kernels' plan of a call on ``x`` [B, S, ...] for n streams of C
    columns, or None where the ``jnp`` form runs; a trace-time engagement
    record beside it: the form's site gauge and, once a distinct shape, a
    log line."""
    T = int(np.prod(x.shape[:-1]))
    takes = kernels.takes(T, n, C) and _device_axes(x.shape[0], 1)[0] is None
    # both gauges exist from the first call: a form that took no site reads 0
    sites = {form: default_registry().gauge(f"mhc/{form}_sites")
             for form in ("kernel", "xla")}
    took = sites["kernel" if takes else "xla"]
    took.set(took.value + 1)
    what = (takes, T, n, C, jnp.dtype(x.dtype).name)
    if what not in _noted:
        _noted.add(what)
        logger.info(
            f"residual streams [{T}, {n} x {C}] {what[-1]}: " + (
                "Pallas kernels, the stream read once a pass, float32 in "
                "VMEM" + ("" if is_tpu_backend() else " (interpreter)")
                if takes else "the jnp form (streams of no whole 128-lane "
                "columns, tokens no row tile divides, or a mesh of several "
                "devices)"))
    if not takes:
        return None
    return kernels.StreamPlan(T, n, C, kernels.ROW_TILE, int(iters),
                              float(eps), tuple(float(c) for c in clamp))


def _pad_rows(c, k):
    """[rows, T] under zeros, to k rows."""
    return jnp.pad(c, ((0, k - c.shape[0]), (0, 0)))


def _gates_and_offsets(gate, bias, n):
    """[8, 128] float32: row 0 the gate of every coefficient, row 1 its
    offset."""
    a = jnp.repeat(gate.astype(jnp.float32), np.array([n, n, n * n]))
    ab = jnp.stack([a, bias.astype(jnp.float32)])
    return jnp.pad(ab, ((0, 8 - 2), (0, kernels.LANES - ab.shape[1])))


def _mix_forward(x, phi, gate, bias, plan, interpret):
    """The kernel pass of ``mix``: (u, the coefficients' rows, the
    projection's rows over the rms)."""
    with annotate("mhc_coeff"):
        wide = jnp.pad(phi.astype(x.dtype),
                       ((0, 0), (0, kernels.LANES - plan.w)))
        return kernels.mix(x, wide, _gates_and_offsets(gate, bias, plan.n),
                           plan, interpret)


def _cut(coef, n):
    """The coefficients' rows -> (H_pre, H_post, H_res)."""
    return (coef[:n], coef[n:2 * n],
            coef[2 * n:2 * n + n * n].reshape(n, n, -1))


@functools.lru_cache(maxsize=None)
def _mix_rule(plan, interpret):
    n, w = plan.n, plan.w

    @jax.custom_vjp
    def rule(x, phi, gate, bias):
        u, coef, _ = _mix_forward(x, phi, gate, bias, plan, interpret)
        return (u, *_cut(coef, n), x)

    def fwd(x, phi, gate, bias):
        u, coef, small = _mix_forward(x, phi, gate, bias, plan, interpret)
        return (u, *_cut(coef, n), x), (x, phi, gate, bias, small)

    def bwd(res, cts):
        x, phi, gate, bias, small = res
        du, dpre, dpost, dres, g = cts
        with annotate("mhc_coeff"):
            dx, dphi_t, dab = kernels.mix_backward(
                g, x, du, small, _pad_rows(jnp.concatenate(
                    [dpre, dpost, dres.reshape(n * n, -1)]).astype(
                        jnp.float32), kernels.up(w, 8)),
                _gates_and_offsets(gate, bias, n),
                _pad_rows(phi.astype(x.dtype).T, kernels.LANES),
                plan, interpret)
            dgate = jnp.stack([jnp.sum(part) for part in jnp.split(
                dab[:w, 0], [n, 2 * n])])
            return (dx, dphi_t[:w].T.astype(phi.dtype),
                    dgate.astype(gate.dtype), dab[:w, 1].astype(bias.dtype))

    rule.defvjp(fwd, bwd)
    return rule


def _write_coefficients(h_post, h_res, n):
    return _pad_rows(jnp.concatenate(
        [h_post, h_res.reshape(n * n, -1)]).astype(jnp.float32),
        kernels.up(n + n * n, 8))


@functools.lru_cache(maxsize=None)
def _write_rule(plan, interpret):
    n = plan.n

    def forward(x, y, h_post, h_res):
        with annotate("mhc_write"):
            return kernels.write(x, y, _write_coefficients(h_post, h_res, n),
                                 plan, interpret)

    rule = jax.custom_vjp(forward)

    def fwd(x, y, h_post, h_res):
        return forward(x, y, h_post, h_res), (x, y, h_post, h_res)

    def bwd(res, g):
        x, y, h_post, h_res = res
        with annotate("mhc_write"):
            dx, dy, dc = kernels.write_backward(
                g, x, y, _write_coefficients(h_post, h_res, n),
                plan, interpret)
            return (dx, dy, dc[:n].astype(h_post.dtype),
                    dc[n:n + n * n].reshape(h_res.shape).astype(h_res.dtype))

    rule.defvjp(fwd, bwd)
    return rule


@functools.lru_cache(maxsize=None)
def _ends(plan, interpret):
    """(``spread``, ``merge``) on [T, ...] arrays: each linear, and each the
    other's transpose. ``linear_call`` and no ``custom_vjp``: outside a
    rematted block a ``custom_vjp``'s backward rule is traced under
    ``transpose(<the outermost scope>)/jvp(<model>)``, which the benchmark's
    ``scope_reduce.phase_of`` reads as the forward pass."""
    def tile(_, x):
        return kernels.tile(x, plan, interpret)

    def total(_, x):
        return kernels.sum_streams(x, plan, interpret)

    return (functools.partial(linear_call, tile, total, ()),
            functools.partial(linear_call, total, tile, ()))


class StreamMixer(nn.Module):
    """One branch's three coefficient sets from the stream it starts from.
    ``phi`` is drawn normal with ``phi_std``, the gates round ``gate_mean``
    with ``gate_std``, the offsets round zero with ``bias_std``: a trained
    checkpoint brings its own, a configuration says how its seeded weights
    are drawn."""
    n: int
    sinkhorn_iters: int = 20
    eps: float = 1e-6
    clamp: Any = (-30.0, 30.0)
    phi_std: float = 0.02
    gate_mean: float = 1.0
    gate_std: float = 0.0
    bias_std: float = 0.0
    param_dtype: Any = jnp.float32

    @nn.compact
    def __call__(self, x, fused=False):
        """``x`` [B, S, n C] -> (H_pre [n, T], H_post [n, T], H_res
        [n, n, T]), float32, T = B S. ``fused`` (``mix``'s call): (u, the
        three, the stream for the branch's ``write``) — ``u`` None where the
        ``jnp`` form ran, which reads it in a pass of its own."""
        n, width = self.n, 2 * self.n + self.n * self.n
        phi = self.param("phi", _normal_round(0.0, self.phi_std),
                         (x.shape[-1], width), self.param_dtype)
        gate = self.param("gate", _normal_round(self.gate_mean, self.gate_std),
                          (3,), self.param_dtype)
        bias = self.param("bias", _normal_round(0.0, self.bias_std),
                          (width,), self.param_dtype)
        plan = _plan(x, n, x.shape[-1] // n, self.sinkhorn_iters, self.eps,
                     self.clamp)
        u = None
        if plan is None:
            coeff = coefficients_jnp(x, phi, gate, bias, n, self.eps,
                                     self.clamp, self.sinkhorn_iters)
        else:
            u, *coeff, through = _mix_rule(plan, not is_tpu_backend())(
                x.reshape(plan.T, -1), phi, gate, bias)
            u = u.reshape(*x.shape[:-1], plan.C)
            x = through.reshape(x.shape)
        self.sow("stats", "mhc_res_sum_err",
                 jax.lax.stop_gradient(res_sum_err(coeff[2])))
        return (u, tuple(coeff), x) if fused else tuple(coeff)


def mix(mixer, x):
    """A branch's opening on the stream ``x`` [B, S, n C]: (``u = read(x,
    H_pre)`` [B, S, C], ``mixer(x)``'s coefficients, the stream for the
    branch's ``write``: ``x`` itself). In the kernel form one pass over the
    stream gives the coefficients and ``u``, and the cotangent of the stream
    handed on is added to the others' by that pass's own backward rule."""
    u, coeff, x = mixer(x, fused=True)
    return (read(x, coeff[0]) if u is None else u), coeff, x


def _streams(x, n):
    """[B, S, n C] -> n float32 [T, C] column slices."""
    return jnp.split(x.reshape(-1, x.shape[-1]).astype(jnp.float32), n,
                     axis=-1)


@annotate("mhc_coeff")
def coefficients_jnp(x, phi, gate, bias, n, eps, clamp, iters):
    """``StreamMixer`` as ``jnp`` ops: the path of the shapes the kernels do
    not take, and their oracle."""
    v = x.reshape(-1, x.shape[-1]).astype(jnp.float32)
    # v' phi = (v phi) / rms(v): the normed copy of the stream is never
    # written. A float32 product of the stream as it is: a TPU's default
    # float32 matmul rounds its operands to bf16 and sums in float32, which
    # is the bf16 stream's own precision
    rms = jax.lax.rsqrt(jnp.mean(jnp.square(v), axis=-1) + eps)       # [T]
    proj = jnp.dot(v, phi.astype(jnp.float32))                     # [T, w]
    a = jnp.repeat(gate.astype(jnp.float32), np.array([n, n, n * n]))
    ht = proj.T * rms[None, :] * a[:, None] \
        + bias.astype(jnp.float32)[:, None]                        # [w, T]
    return (jax.nn.sigmoid(ht[:n]), 2.0 * jax.nn.sigmoid(ht[n:2 * n]),
            sinkhorn(jnp.exp(jnp.clip(ht[2 * n:], *clamp)).reshape(n, n, -1),
                     iters))


@annotate("mhc_read")
def read(x, h_pre):
    """``u = sum_j H_pre[j] X[j]``: [B, S, n C] -> [B, S, C], the sum in
    float32 (``jnp`` ops at every shape: a branch that wants ``u`` from the
    coefficients' own pass over the stream takes ``mix``)."""
    n = h_pre.shape[0]
    u = sum(h_pre[j][:, None] * xj for j, xj in enumerate(_streams(x, n)))
    return u.astype(x.dtype).reshape(*x.shape[:-1], x.shape[-1] // n)


def write(x, y, h_post, h_res):
    """``X_new[i] = sum_j H_res[i, j] X[j] + H_post[i] y``: the stream
    [B, S, n C] and the branch's output [B, S, C] -> [B, S, n C], every
    stream's sum in float32."""
    n = h_post.shape[0]
    plan = _plan(x, n, x.shape[-1] // n)
    if plan is None:
        return write_jnp(x, y, h_post, h_res)
    return _write_rule(plan, not is_tpu_backend())(
        x.reshape(plan.T, -1), y.reshape(plan.T, -1), h_post,
        h_res).reshape(x.shape)


@annotate("mhc_write")
def write_jnp(x, y, h_post, h_res):
    """``write`` as ``jnp`` ops: the path of the shapes the kernels do not
    take, and their oracle."""
    n = h_post.shape[0]
    xs = _streams(x, n)
    yf = y.reshape(-1, y.shape[-1]).astype(jnp.float32)
    new = [sum(h_res[i, j][:, None] * xj for j, xj in enumerate(xs))
           + h_post[i][:, None] * yf for i in range(n)]
    return jnp.concatenate(new, axis=-1).astype(x.dtype).reshape(x.shape)
