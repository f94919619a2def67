"""The gated delta rule, chunked: the recurrence of a Gated DeltaNet layer.

A value head keeps a state ``S`` in R^(Dk x Dv) and reads it token by token
(Gated Delta Networks, arXiv:2412.06464):

    S <- exp(g_t) * S
    S <- S + k_t (beta_t * (v_t - S^T k_t))^T
    o_t = S^T q_t

``gated_delta_rule`` computes the same outputs in CHUNKS of ``CHUNK`` tokens
(the paper's WY / UT form, as HF's ``torch_chunk_gated_delta_rule`` has it).
Inside a chunk, with ``G`` the running sum of ``g``, ``D_ij = exp(G_i - G_j)``
and ``L`` the strictly lower part of ``(beta k) k^T * D``:

    T = (I + L)^-1               ``unit_lower_inverse``
    u = T (beta v),   w = T (beta k exp(G))

and from chunk to chunk, with ``S`` the state the chunk starts from:

    v' = u - w S
    o  = (q exp(G)) S + tril(q k^T * D) v'
    S <- exp(G_last) S + (k exp(G_last - G))^T v'

``gated_delta_rule`` decides at trace time, from the head sizes and the
backend (``ops.pallas.gated_delta.takes_kernel``), which of two forms of
that one algorithm runs:

- **the Pallas kernels** (``ops/pallas/gated_delta.py``) where both head
  sizes are multiples of 128 lanes on a TPU, as they come or zero-padded
  (``lane_heads``: where whole tiles add at most a third, 96 x 192 -> 128 x
  256), and in the interpreter on every other backend: a program a (batch
  row, key head and the value heads it serves) walks the chunks in order
  with ``S`` in VMEM and builds the whole preparation above per chunk in
  VMEM from the q, k,
  v, G, beta tiles, read as column blocks of the model's own [B, S, H*D]
  arrays. For the backward pass the forward rule keeps the state every
  chunk STARTS from, in the inputs' dtype, and the backward kernel walks
  the chunks in reverse, builds the preparation again and takes every
  cotangent in VMEM (``dL = -T^T dT T^T`` in float32);
- **the XLA form** below (``gated_delta_rule_xla``) for every other head
  size: everything but the three-line loop is computed for all chunks at
  once in batched matmuls; the loop is a ``lax.scan`` over the chunks,
  three small matmuls a step; states are kept every ``_GROUP`` chunks and a
  group's steps run again in the backward pass, which is JAX's own through
  the scan except for the inverse (``unit_lower_inverse``), whose
  cotangent is the closed form. It is also the kernels' second oracle,
  beside ``gated_delta_recurrence``.

What a Gated DeltaNet layer does to q, k, v before the rule (a causal
depthwise convolution, SiLU, and the L2 norm of every head of q and k) and
to o after it (a head's RMS norm and the gate) is
``ops/mixer_elementwise.py``: its kernels write q, k and v as the
[B, S, H*D] column blocks the rule's kernels read, normalised, and read o
as they write it — no copy or transpose stands between them.

No option selects a form. Which one took a call: the trace-time gauges
``linear_attn/gdn_kernel_heads_per_step`` (value heads a grid step; 0 = the
XLA form) and ``linear_attn/gdn_states_kept_every`` (chunks between kept
states), and the kernels' log line. The per-token recurrence is never run.
In both forms the state, the gates and ``T`` are float32 and the matmul
operands the inputs' dtype (bf16 in a training step) with float32
accumulation.

Measured on a v5e and written down in PERF.md (Findings, PR 31 for the XLA
form, PR 32 for the kernels): what each form and its pieces cost at
2 x 8192 tokens, 32 value heads of 128 x 128.
"""


import functools

import jax
import jax.numpy as jnp

from deepspeed_tpu.ops.attention import _device_axes
from deepspeed_tpu.telemetry.registry import default_registry
from deepspeed_tpu.telemetry.spans import annotate
from deepspeed_tpu.utils.platform import is_tpu_backend

CHUNK = 64          # tokens a chunk (a power of two: the inverse doubles)
_GROUP = 8          # chunks between two states kept for the backward pass
_HIGHEST = jax.lax.Precision.HIGHEST


def _mm(a, b, precision=None):
    return jnp.matmul(a, b, precision=precision,
                      preferred_element_type=jnp.float32)


@jax.custom_vjp
def unit_lower_inverse(lower):
    """``(I + L)^-1`` for strictly lower-triangular ``L`` [..., C, C]
    (float32, C a power of two), by block forward substitution: with ``X``
    the inverse of the block diagonal at block size s and ``C_s`` the lower
    left s x s blocks of the 2s blocks, ``X - X C_s X`` is the inverse at
    2s — exactly, since ``(C_s X)^2 = 0``. log2(C) steps of two batched
    C x C matmuls; as stable as forward substitution, whose operations it
    regroups (a Neumann product of powers of L is not: its terms grow
    where neighbouring keys are alike)."""
    C = lower.shape[-1]
    assert C & (C - 1) == 0, C
    row = jnp.arange(C)[:, None]
    col = jnp.arange(C)[None, :]
    x = jnp.broadcast_to(jnp.eye(C, dtype=lower.dtype), lower.shape)
    s = 1
    while s < C:
        corner = (row // (2 * s) == col // (2 * s)) \
            & (row % (2 * s) >= s) & (col % (2 * s) < s)
        c_s = jnp.where(corner, lower, 0.0)
        x = x - _mm(x, _mm(c_s, x, _HIGHEST), _HIGHEST)
        s *= 2
    return x


def _unit_lower_inverse_fwd(lower):
    x = unit_lower_inverse(lower)
    return x, x


def _unit_lower_inverse_bwd(x, dx):
    xt = jnp.swapaxes(x, -1, -2)
    return (-_mm(xt, _mm(dx, xt, _HIGHEST), _HIGHEST),)


unit_lower_inverse.defvjp(_unit_lower_inverse_fwd, _unit_lower_inverse_bwd)


def _chunks(t, n):
    """[B, S, H, ...] -> [N, B, H, C, ...]: chunk-major for the scan."""
    B, S, H = t.shape[:3]
    t = t.reshape(B, n, S // n, H, *t.shape[3:])
    return jnp.moveaxis(jnp.moveaxis(t, 1, 0), 3, 2)


def gated_delta_rule(q, k, v, g, beta, chunk=CHUNK, heads=None):
    """o [B, S, Hv, Dv] of the gated delta rule from a zero state.

    q, k [B, S, Hk, Dk] (k L2-normalised, q normalised and scaled, as the
    layer does before calling); v [B, S, Hv, Dv]; g [B, S, Hv] float32, the
    log of the decay (<= 0); beta [B, S, Hv] float32 in (0, 2): a token's
    transition ``I - beta k k^T`` has its eigenvalue along k in (-1, 1)
    (a layer without negative eigenvalues keeps beta under 1). Key head i
    serves value heads [i * Hv / Hk, (i + 1) * Hv / Hk). Any S: the tail
    of a last, short chunk is padded with tokens that write nothing.

    Which form runs is decided here from the operands' shapes and the
    backend (``ops.pallas.gated_delta.takes_kernel``): the Pallas kernels
    where the heads are lane-aligned on a TPU — as they come, or
    zero-padded here to whole tiles where ``lane_heads`` says so (96 x 192
    runs at 128 x 256; o's padded lanes are cut off again) — and in the
    interpreter on any other backend; ``gated_delta_rule_xla`` for other
    head sizes. ``heads``: the layer's own (Dk, Dv) where IT has laid q, k
    and v out zero-padded already (``models/qwen3_next.GatedDeltaNet``: one
    re-layout for the convolution, the rule and the norm), for the gauge
    ``linear_attn/gdn_lane_overcompute`` alone."""
    from deepspeed_tpu.ops.pallas import gated_delta as kernels
    tpu = is_tpu_backend()
    Dk, Dv = k.shape[-1], v.shape[-1]
    if not kernels.takes_kernel(Dk, Dv, tpu):
        return gated_delta_rule_xla(q, k, v, g, beta, chunk)
    Pk, Pv = kernels.lane_heads(Dk, Dv)
    if (Pk, Pv) != (Dk, Dv):
        with annotate("gdn_scan_prep"):
            q, k = (jnp.pad(t, ((0, 0),) * 3 + ((0, Pk - Dk),))
                    for t in (q, k))
            v = jnp.pad(v, ((0, 0),) * 3 + ((0, Pv - Dv),))
        heads = (Dk, Dv)
    rule = functools.partial(kernels.gated_delta_rule_kernel, chunk=chunk,
                             interpret=not tpu, heads=heads)
    mesh, batch_axes, model_axis = _device_axes(q.shape[0], k.shape[2])
    if mesh is None:
        o = rule(q, k, v, g, beta)
    else:
        spec = jax.sharding.PartitionSpec(batch_axes, None, model_axis)
        o = jax.shard_map(rule, mesh=mesh, in_specs=(spec,) * 5,
                          out_specs=spec, check_vma=False)(q, k, v, g, beta)
    return o[..., :Dv]


def gated_delta_rule_xla(q, k, v, g, beta, chunk=CHUNK, heads=None):
    """``gated_delta_rule`` as XLA ops: the preparation for all chunks at
    once in batched matmuls and a ``lax.scan`` over the chunks. The path
    of head sizes the kernels do not take, and their second oracle beside
    ``gated_delta_recurrence`` (``heads``, the kernels' gauge's, is taken
    so that one form stands in for the other and read by nothing)."""
    del heads
    default_registry().gauge("linear_attn/gdn_kernel_heads_per_step").set(0)
    default_registry().gauge("linear_attn/gdn_states_kept_every").set(_GROUP)
    B, S, Hv, Dv = v.shape
    dt = v.dtype
    rep = Hv // k.shape[2]
    pad = (-S) % chunk
    if pad:
        q, k, v, g, beta = (jnp.pad(t, ((0, 0), (0, pad)) + ((0, 0),) * (
            t.ndim - 2)) for t in (q, k, v, g, beta))
    n = (S + pad) // chunk
    f32 = jnp.float32

    with annotate("gdn_scan_prep"):
        if rep > 1:
            q = jnp.repeat(q, rep, axis=2)
            k = jnp.repeat(k, rep, axis=2)
        q, k, v = (_chunks(t, n) for t in (q, k, v))       # [N, B, H, C, D]
        g, beta = (_chunks(t.astype(f32), n) for t in (g, beta))  # [N,B,H,C]
        G = jnp.cumsum(g, axis=-1)
        row = jnp.arange(chunk)[:, None]
        col = jnp.arange(chunk)[None, :]
        # exp of a masked difference: nothing above the diagonal is formed
        decay = jnp.exp(jnp.where(row >= col,
                                  G[..., :, None] - G[..., None, :], -jnp.inf))
        kb = (k.astype(f32) * beta[..., None]).astype(dt)
        kt = jnp.swapaxes(k, -1, -2)
        lower = jnp.where(row > col, _mm(kb, kt) * decay, 0.0)
        T = unit_lower_inverse(lower).astype(dt)
        u = _mm(T, (v.astype(f32) * beta[..., None]).astype(dt)).astype(dt)
        eG = jnp.exp(G)[..., None]
        w = _mm(T, (kb.astype(f32) * eG).astype(dt)).astype(dt)
        qg = (q.astype(f32) * eG).astype(dt)
        attn = jnp.where(row >= col, _mm(q, kt) * decay, 0.0).astype(dt)
        G_last = G[..., -1:]
        kd = (k.astype(f32) * jnp.exp(G_last - G)[..., None]).astype(dt)
        d_last = jnp.exp(G_last)[..., None]                 # [N, B, H, 1, 1]

    def step(S_, xs):
        w_c, u_c, qg_c, attn_c, kd_c, dl = xs
        Sb = S_.astype(dt)
        v_new = (u_c - _mm(w_c, Sb)).astype(dt)
        o = _mm(qg_c, Sb) + _mm(attn_c, v_new)
        S_ = S_ * dl + _mm(jnp.swapaxes(kd_c, -1, -2), v_new)
        return S_, o.astype(dt)

    # the backward pass keeps the state at the start of every GROUP of
    # chunks and runs a group's steps again (one state a chunk would be
    # 512 MB a layer at 2 x 8192 tokens, 32 heads of 128 x 128)
    group = next(g for g in (_GROUP, 4, 2, 1) if n % g == 0)

    @jax.checkpoint
    def steps(S_, xs):
        return jax.lax.scan(step, S_, xs)

    with annotate("gdn_scan"):
        S0 = jnp.zeros((B, Hv, k.shape[-1], Dv), f32)
        xs = tuple(t.reshape(n // group, group, *t.shape[1:])
                   for t in (w, u, qg, attn, kd, d_last))
        _, o = jax.lax.scan(steps, S0, xs)
        o = o.reshape(n, *o.shape[2:])
    with annotate("gdn_scan_prep"):
        o = jnp.moveaxis(jnp.moveaxis(o, 2, 3), 0, 1)       # [B, N, C, H, Dv]
        return o.reshape(B, n * chunk, Hv, Dv)[:, :S]


def gated_delta_recurrence(q, k, v, g, beta):
    """The same outputs by the recurrence as written, token by token, in
    float32: what the tests hold ``gated_delta_rule`` to. Not a path of
    the program."""
    f32 = jnp.float32
    rep = v.shape[2] // k.shape[2]
    q, k = (jnp.repeat(t.astype(f32), rep, axis=2) for t in (q, k))
    v, g, beta = (t.astype(f32) for t in (v, g, beta))
    B, S, H, Dv = v.shape

    def step(S_, xs):
        q_t, k_t, v_t, g_t, b_t = xs                       # [B, H, ...]
        S_ = S_ * jnp.exp(g_t)[..., None, None]
        read = jnp.einsum("bhkv,bhk->bhv", S_, k_t, precision=_HIGHEST)
        delta = b_t[..., None] * (v_t - read)
        S_ = S_ + k_t[..., :, None] * delta[..., None, :]
        return S_, jnp.einsum("bhkv,bhk->bhv", S_, q_t, precision=_HIGHEST)

    xs = tuple(jnp.moveaxis(t, 1, 0) for t in (q, k, v, g, beta))
    _, o = jax.lax.scan(step, jnp.zeros((B, H, k.shape[-1], Dv), f32), xs)
    return jnp.moveaxis(o, 0, 1)
