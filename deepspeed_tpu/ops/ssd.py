"""The selective state-space scan of a Mamba-2 layer (SSD), chunked.

A head keeps a state ``h`` in R^(P x N) (P the head's channels, N the state
size) and reads it token by token (Transformers are SSMs, arXiv:2405.21060):

    h_t = exp(dt_t A) h_(t-1) + dt_t x_t (x) B_t
    y_t = h_t C_t + D x_t

with ``A < 0`` ONE scalar a head (so the decay is a scalar a head a token),
``dt_t > 0`` the token's step (the layer's ``softplus(dt + dt_bias)``), and
``B_t``, ``C_t`` in R^N shared by the ``H / G`` heads of a group. No delta
rule: nothing is read back before the write, so a chunk needs no triangular
inverse (``ops/gated_delta.py`` has that rule).

``ssd_scan`` computes the same outputs in CHUNKS of ``chunk`` tokens. Inside
a chunk, with ``a`` the running sum of ``dt A`` and ``L_ij = exp(a_i - a_j)``
for ``i >= j`` (0 above the diagonal; every exponent is <= 0, so a strong
decay underflows to zero and nothing overflows):

    y = (L o C B^T) (dt x)  +  exp(a) C S  +  D x
    S <- exp(a_last) S + B^T (exp(a_last - a) dt x)

``S`` [N, P] the state the chunk starts from. Which of two forms of that one
algorithm runs is decided at trace time from the shapes and the backend
(``ops.pallas.ssd.takes_kernel``):

- **the Pallas kernels** (``ops/pallas/ssd.py``, scopes ``ssd_scan_fwd`` /
  ``ssd_scan_bwd``): a program a (batch row, group) walks the chunks in
  order with the float32 states of the group's heads in VMEM; on every other
  backend the same kernels run in the interpreter;
- **the XLA form** below (``ssd_scan_xla``, scope ``ssd_scan``) for the
  shapes the kernels do not take: the intra-chunk products of all chunks at
  once, a ``lax.scan`` that carries the state across the chunks, and the
  inter-chunk term; its backward pass is JAX's own. It is also the kernels'
  second oracle beside ``ssd_recurrence``.

What a Mamba-2 layer does to x, B, C before the scan (a causal depthwise
convolution and SiLU) and to y after it (the gate and a grouped RMS norm)
is ``ops/mixer_elementwise.py``: its kernels write x, B and C as the
[B, S, H*P] / [B, S, G*N] column blocks the scan's kernels read, and read y
as they write it — no copy or transpose stands between them.

No option selects a form; the trace-time gauge ``ssm/ssd_kernel_heads_per_step``
(heads a grid step; 0 = the XLA form) and the kernels' log line say which
one took a call. State and gates are float32 in both; matmul operands are
the inputs' dtype with float32 accumulation.
"""

import functools

import jax
import jax.numpy as jnp

from deepspeed_tpu.ops.attention import _device_axes
from deepspeed_tpu.telemetry.registry import default_registry
from deepspeed_tpu.telemetry.spans import annotate
from deepspeed_tpu.utils.platform import is_tpu_backend

CHUNK = 128
_HIGHEST = jax.lax.Precision.HIGHEST
_F32 = jnp.float32


def ssd_scan(x, dt, A, B, C, D, chunk=CHUNK):
    """y [B, S, H, P] of the scan from a zero state.

    x [B, S, H, P]; dt [B, S, H] float32, positive (after the softplus);
    A [H] float32, negative; B, C [B, S, G, N] (group g serves heads
    [g * H / G, (g + 1) * H / G)); D [H]. Any S: the tail of a last, short
    chunk is padded with tokens whose ``dt`` is 0 — they neither decay nor
    write."""
    from deepspeed_tpu.ops.pallas import ssd as kernels
    tpu = is_tpu_backend()
    H, P = x.shape[2:]
    G, N = B.shape[2:]
    if not kernels.takes_kernel(H, P, G, N, chunk, tpu):
        return ssd_scan_xla(x, dt, A, B, C, D, chunk)
    scan = functools.partial(kernels.ssd_scan_kernel, chunk=chunk,
                             interpret=not tpu)
    mesh, batch_axes, _ = _device_axes(x.shape[0], H)
    if mesh is None:
        return scan(x, dt, A, B, C, D)
    rows = jax.sharding.PartitionSpec(batch_axes)
    whole = jax.sharding.PartitionSpec()
    return jax.shard_map(
        scan, mesh=mesh, in_specs=(rows, rows, whole, rows, rows, whole),
        out_specs=rows, check_vma=False)(x, dt, A, B, C, D)


def _chunked(t, n):
    """[B, S, ...] -> [B, n, S / n, ...]."""
    return t.reshape(t.shape[0], n, t.shape[1] // n, *t.shape[2:])


def ssd_scan_xla(x, dt, A, B, C, D, chunk=CHUNK):
    """``ssd_scan`` as XLA ops: the path of the shapes the kernels do not
    take, and their second oracle beside ``ssd_recurrence``."""
    default_registry().gauge("ssm/ssd_kernel_heads_per_step").set(0)
    Bt, S, H, P = x.shape
    G = B.shape[2]
    dtype = x.dtype
    pad = (-S) % chunk
    if pad:
        x, dt, B, C = (jnp.pad(t, ((0, 0), (0, pad)) + ((0, 0),) * (
            t.ndim - 2)) for t in (x, dt, B, C))
    n = (S + pad) // chunk
    with annotate("ssd_scan"):
        dt = dt.astype(_F32)
        xc, dtc, Bc, Cc = (_chunked(t, n) for t in (x, dt, B, C))
        a = jnp.cumsum(dtc * A.astype(_F32), axis=2)        # [B, n, c, H]
        a_last = a[:, :, -1:]
        xdt = xc.astype(_F32) * dtc[..., None]              # [B, n, c, H, P]
        row = jnp.arange(chunk)[:, None]
        col = jnp.arange(chunk)[None, :]
        ah = jnp.moveaxis(a, 3, 2)                          # [B, n, H, c]
        # exp of a masked difference: nothing above the diagonal is formed
        L = jnp.exp(jnp.where(row >= col,
                              ah[..., :, None] - ah[..., None, :], -jnp.inf))
        CB = jnp.einsum("bnigs,bnjgs->bngij", Cc, Bc,
                        preferred_element_type=_F32)
        M = (L.reshape(Bt, n, G, H // G, chunk, chunk)
             * CB[:, :, :, None]).reshape(Bt, n, H, chunk, chunk)
        y = jnp.einsum("bnhij,bnjhp->bnihp", M.astype(dtype),
                       xdt.astype(dtype), preferred_element_type=_F32)
        # what each chunk adds to the state, and the states the chunks
        # start from (float32, carried by a scan over the chunks)
        xdd = (xdt * jnp.exp(a_last - a)[..., None]).astype(dtype)
        xdd = xdd.reshape(Bt, n, chunk, G, H // G, P)
        adds = jnp.einsum("bnjgs,bnjghp->bnghsp", Bc, xdd,
                          preferred_element_type=_F32)
        adds = adds.reshape(Bt, n, H, -1, P)                # [B, n, H, N, P]
        keep = jnp.exp(a_last[:, :, 0])[..., None, None]    # [B, n, H, 1, 1]

        def step(state, xs):
            add, k = xs
            return state * k + add, state

        _, starts = jax.lax.scan(
            step, jnp.zeros(adds.shape[:1] + adds.shape[2:], _F32),
            (jnp.moveaxis(adds, 1, 0), jnp.moveaxis(keep, 1, 0)))
        starts = jnp.moveaxis(starts, 0, 1).astype(dtype)   # [B, n, H, N, P]
        starts = starts.reshape(Bt, n, G, H // G, -1, P)
        inter = jnp.einsum("bnigs,bnghsp->bnighp", Cc, starts,
                           preferred_element_type=_F32)
        y = y + inter.reshape(Bt, n, chunk, H, P) * jnp.exp(a)[..., None]
        y = y + xc.astype(_F32) * D.astype(_F32)[:, None]
        return y.astype(dtype).reshape(Bt, n * chunk, H, P)[:, :S]


def ssd_recurrence(x, dt, A, B, C, D):
    """The same outputs by the recurrence as written, token by token, in
    float32: what the tests hold ``ssd_scan`` to. Not a path of the
    program."""
    H = x.shape[2]
    rep = H // B.shape[2]
    x, dt, A, D = (t.astype(_F32) for t in (x, dt, A, D))
    B, C = (jnp.repeat(t.astype(_F32), rep, axis=2) for t in (B, C))

    def step(h, xs):
        x_t, dt_t, B_t, C_t = xs                            # [B, H, ...]
        h = h * jnp.exp(dt_t * A)[..., None, None] \
            + (dt_t[..., None] * x_t)[..., :, None] * B_t[..., None, :]
        y = jnp.einsum("bhpn,bhn->bhp", h, C_t, precision=_HIGHEST)
        return h, y + D[:, None] * x_t

    xs = tuple(jnp.moveaxis(t, 1, 0) for t in (x, dt, B, C))
    h0 = jnp.zeros((x.shape[0], H, x.shape[3], B.shape[3]), _F32)
    _, y = jax.lax.scan(step, h0, xs)
    return jnp.moveaxis(y, 0, 1)
