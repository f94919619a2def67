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

Scopes: ``mhc_coeff`` (norm, projection, sigmoids, Sinkhorn), ``mhc_read``
(``u``), ``mhc_write`` (``X_new``) — ``benchmark/layer_metrics/mhc_stream_ms``.
"""

from typing import Any

import numpy as np
import jax
import jax.numpy as jnp
import flax.linen as nn

from deepspeed_tpu.telemetry.spans import annotate

# what ``StreamMixer`` sows into ``stats``, and the gauge it is read under:
# the largest |row sum - 1| or |column sum - 1| of ``H_res`` — how doubly
# stochastic the rounds left it. A model lists it among its ``stat_maxima``
# (the engine then folds the LARGEST value sown in the step, not the mean)
STAT_GAUGES = {"mhc_res_sum_err": "mhc/res_sum_err"}


def spread(x, n):
    """``x`` [B, S, C] copied into ``n`` streams: [B, S, n C]."""
    return jnp.tile(x, (1, 1, n))


def merge(x, n):
    """The ``n`` streams of ``x`` [B, S, n C] summed: [B, S, C] (float32
    sum, the carry's dtype)."""
    parts = jnp.split(x.astype(jnp.float32), n, axis=-1)
    return sum(parts[1:], parts[0]).astype(x.dtype)


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
    def __call__(self, x):
        """``x`` [B, S, n C] -> (H_pre [n, T], H_post [n, T], H_res
        [n, n, T]), float32, T = B S."""
        n, width = self.n, 2 * self.n + self.n * self.n
        phi = self.param("phi", _normal_round(0.0, self.phi_std),
                         (x.shape[-1], width), self.param_dtype)
        gate = self.param("gate", _normal_round(self.gate_mean, self.gate_std),
                          (3,), self.param_dtype)
        bias = self.param("bias", _normal_round(0.0, self.bias_std),
                          (width,), self.param_dtype)
        with annotate("mhc_coeff"):
            v = x.reshape(-1, x.shape[-1]).astype(jnp.float32)
            # v' phi = (v phi) / rms(v): the normed copy of the stream is
            # never written. A float32 product of the stream as it is: a
            # TPU's default float32 matmul rounds its operands to bf16 and
            # sums in float32, which is the bf16 stream's own precision
            rms = jax.lax.rsqrt(jnp.mean(jnp.square(v), axis=-1)
                                + self.eps)                        # [T]
            proj = jnp.dot(v, phi.astype(jnp.float32))             # [T, w]
            a = jnp.repeat(gate.astype(jnp.float32),
                           np.array([n, n, n * n]))
            ht = proj.T * rms[None, :] * a[:, None] \
                + bias.astype(jnp.float32)[:, None]                # [w, T]
            h_pre = jax.nn.sigmoid(ht[:n])
            h_post = 2.0 * jax.nn.sigmoid(ht[n:2 * n])
            h_res = sinkhorn(
                jnp.exp(jnp.clip(ht[2 * n:], *self.clamp)).reshape(n, n, -1),
                self.sinkhorn_iters)
        self.sow("stats", "mhc_res_sum_err",
                 jax.lax.stop_gradient(res_sum_err(h_res)))
        return h_pre, h_post, h_res


def _streams(x, n):
    """[B, S, n C] -> n float32 [T, C] column slices."""
    return jnp.split(x.reshape(-1, x.shape[-1]).astype(jnp.float32), n,
                     axis=-1)


@annotate("mhc_read")
def read(x, h_pre):
    """``u = sum_j H_pre[j] X[j]``: [B, S, n C] -> [B, S, C], the sum in
    float32."""
    n = h_pre.shape[0]
    u = sum(h_pre[j][:, None] * xj for j, xj in enumerate(_streams(x, n)))
    return u.astype(x.dtype).reshape(*x.shape[:-1], x.shape[-1] // n)


@annotate("mhc_write")
def write(x, y, h_post, h_res):
    """``X_new[i] = sum_j H_res[i, j] X[j] + H_post[i] y``: the stream
    [B, S, n C] and the branch's output [B, S, C] -> [B, S, n C], every
    stream's sum in float32."""
    n = h_post.shape[0]
    xs = _streams(x, n)
    yf = y.reshape(-1, y.shape[-1]).astype(jnp.float32)
    new = [sum(h_res[i, j][:, None] * xj for j, xj in enumerate(xs))
           + h_post[i][:, None] * yf for i in range(n)]
    return jnp.concatenate(new, axis=-1).astype(x.dtype).reshape(x.shape)
