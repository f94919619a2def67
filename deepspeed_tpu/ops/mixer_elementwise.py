"""The elementwise stages round a recurrent mixer's chunked scan.

A Gated DeltaNet layer (``models/qwen3_next.py``) and a Mamba-2 mixer
(``models/nemotron_h.py``) wrap their scans (``ops/gated_delta.py``,
``ops/ssd.py``) in the same two memory-bound stages, with different
parameters:

- ``conv_act``: a causal depthwise convolution (W taps, optional bias) and
  SiLU over some columns of the input projection's output, cut into the
  column runs the scan takes as operands; a run may ask for the L2 norm of
  each of its heads and a scale (Qwen3-Next's q and k);
- ``gated_group_norm``: an RMS norm over groups of columns with a SiLU gate
  that is other columns of the same projection, the gate before the norm
  (Nemotron) or after it (Qwen3-Next).

Which of two forms runs is decided at trace time from the shapes
(``ops.pallas.mixer_elementwise.conv_takes`` / ``norm_takes``), as for the
scans:

- **the Pallas kernels** (``ops/pallas/mixer_elementwise.py``, scopes
  ``mixer_conv_fwd`` / ``mixer_conv_bwd`` / ``mixer_norm_fwd`` /
  ``mixer_norm_bwd``): every stage reads its operands from HBM once in the
  model's dtype, by column offset out of the projection's output, does its
  arithmetic in float32 in VMEM and writes its result once, forward and
  backward; off a TPU the same kernels run in the interpreter;
- **the XLA forms** below (``conv_act_xla``, ``gated_group_norm_xla``), for
  widths, offsets or groups that are no multiple of 128 lanes, a sequence
  no row block divides, and heads of another width than 128 under the L2
  norm (heads zero-padded to whole tiles are exact: ``tile_group_norm``).

No option selects a form. The trace-time gauges ``mixer/conv_kernel_sites``
/ ``mixer/norm_kernel_sites`` count the call sites traced through the
kernels and ``mixer/conv_xla_sites`` / ``mixer/norm_xla_sites`` those that
fell to the XLA forms; one log line a distinct shape says which.
"""

import functools

import jax
import jax.numpy as jnp

from deepspeed_tpu.ops.attention import _device_axes
from deepspeed_tpu.telemetry.registry import default_registry
from deepspeed_tpu.utils.logging import logger
from deepspeed_tpu.utils.platform import is_tpu_backend

_F32 = jnp.float32
L2_EPS = 1e-6


def causal_depthwise_conv(x, taps):
    """[B, S, C] through a causal depthwise convolution, ``taps`` [W, C]:
    ``y_t = sum_j taps[j] * x_(t - W + 1 + j)``, zeros before the start."""
    W = taps.shape[0]
    S = x.shape[1]
    xp = jnp.pad(x, ((0, 0), (W - 1, 0), (0, 0)))
    return sum(xp[:, j:j + S] * taps[j] for j in range(W))


def l2_normalise(x, eps=L2_EPS):
    """float32 ``x / sqrt(sum(x^2) + eps)`` over the last dimension."""
    xf = x.astype(_F32)
    return xf * jax.lax.rsqrt(jnp.sum(xf * xf, axis=-1, keepdims=True) + eps)


_noted = set()


def _note(stage, why, what):
    """Trace-time engagement record: the stage's site gauge and, once a
    distinct shape, a log line; ``why`` is the kernels' refusal, the
    condition that sent the call to the XLA form (None: the kernels took
    the call)."""
    # both gauges exist from the first call: a form that took no site reads 0
    sites = {form: default_registry().gauge(f"mixer/{stage}_{form}_sites")
             for form in ("kernel", "xla")}
    took = sites["xla" if why else "kernel"]
    took.set(took.value + 1)
    if (stage, why, what) not in _noted:
        _noted.add((stage, why, what))
        logger.info(
            f"mixer {stage} {what}: " + (
                f"the XLA form ({why})" if why else
                "Pallas kernels, one HBM pass forward and one backward, "
                "float32 in VMEM" + ("" if is_tpu_backend()
                                     else " (interpreter)")))


def _over_batch(fn, rows, *whole):
    """``fn(*rows, *whole)`` per device of the engine's mesh: the arrays of
    ``rows`` by their batch rows on the batch axes, the parameters whole
    (``ops.ssd.ssd_scan``'s wrapper)."""
    mesh, batch_axes, _ = _device_axes(rows[0].shape[0], 1)
    if mesh is None:
        return fn(*rows, *whole)
    by_row = jax.sharding.PartitionSpec(batch_axes)
    return jax.shard_map(
        fn, mesh=mesh, in_specs=(by_row,) * len(rows)
        + (jax.sharding.PartitionSpec(),) * len(whole), out_specs=by_row,
        check_vma=False)(*rows, *whole)


def conv_act(x, taps, bias=None, *, offset=0, runs=None, head_width=None):
    """SiLU of the causal depthwise convolution of ``x[..., offset:offset +
    C]`` (x [B, S, total], the projection's output; taps [W, C]; bias [C] or
    None), as a tuple of its column runs.

    ``runs``: ``((width, l2_scale), ...)``, widths summing to C; a run with
    an ``l2_scale`` (not None) has every head of ``head_width`` columns
    L2-normalised and multiplied by it. Default: one run, all C columns.
    Each result is [B, S, width] in x's dtype."""
    from deepspeed_tpu.ops.pallas import mixer_elementwise as kernels
    B, S, total = x.shape
    W, C = taps.shape
    runs = tuple(runs or ((C, None),))
    assert sum(width for width, _ in runs) == C, (runs, C)
    starts = [offset + sum(w for w, _ in runs[:n]) for n in range(len(runs))]
    why = next(filter(None, (kernels.conv_refusal(
        S, width, total, start, W, None if scale is None else head_width)
        for start, (width, scale) in zip(starts, runs))), None)
    _note("conv", why, f"[{B}, {S}, {total}] {jnp.dtype(x.dtype).name} "
          f"columns {offset}:{offset + C} W={W} runs={runs}")
    if why:
        return conv_act_xla(x, taps, bias, offset=offset, runs=runs,
                            head_width=head_width)
    interpret = not is_tpu_backend()

    def run(x, taps, bias):
        out, at = [], 0
        for start, (width, scale) in zip(starts, runs):
            out.append(kernels.conv_act_kernel(
                x, taps[:, at:at + width],
                None if bias is None else bias[at:at + width], offset=start,
                l2_scale=scale, eps=L2_EPS, interpret=interpret))
            at += width
        return tuple(out)

    return _over_batch(run, (x,), taps, bias)


def conv_act_xla(x, taps, bias=None, *, offset=0, runs=None,
                 head_width=None):
    """``conv_act`` as XLA ops, in x's dtype but for the float32 L2 norm:
    the path of the shapes the kernels do not take, and their oracle."""
    C = taps.shape[1]
    dtype = x.dtype
    y = causal_depthwise_conv(x[..., offset:offset + C], taps.astype(dtype))
    if bias is not None:
        y = y + bias.astype(dtype)
    y = jax.nn.silu(y)
    out, at = [], 0
    for width, scale in runs or ((C, None),):
        part = y[..., at:at + width]
        at += width
        if scale is not None:
            heads = part.reshape(*part.shape[:2], width // head_width,
                                 head_width)
            part = (l2_normalise(heads) * scale).astype(dtype).reshape(
                part.shape)
        out.append(part)
    return tuple(out)


def gated_group_norm(y, z, w, *, group, eps, gate_first, offset=0):
    """The RMS norm of y [B, S, D] over groups of ``group`` columns with
    the gate ``silu(z[..., offset:offset + D])`` (z [B, S, total], the
    projection's output) and the weight w — [D], or [group] for one weight
    shared by every group: ``norm(y * gate) * w`` with ``gate_first`` (the
    gate before the norm), else ``norm(y) * w * gate``. y's dtype."""
    from deepspeed_tpu.ops.pallas import mixer_elementwise as kernels
    B, S, D = y.shape
    why = kernels.norm_refusal(S, D, z.shape[2], offset, group)
    _note("norm", why, f"[{B}, {S}, {D}] {jnp.dtype(y.dtype).name} "
          f"group={group} gate {'before' if gate_first else 'after'} the "
          f"norm, at columns {offset}:{offset + D} of {z.shape[2]}")
    if why:
        return gated_group_norm_xla(y, z, w, group=group, eps=eps,
                                    gate_first=gate_first, offset=offset)
    kernel = functools.partial(
        kernels.gated_group_norm_kernel, group=group, eps=eps,
        gate_first=gate_first, offset=offset, interpret=not is_tpu_backend())
    return _over_batch(
        lambda y, z, w: kernel(y, z, jnp.tile(w.astype(_F32),
                                              D // w.shape[0])),
        (y, z), w)


def gated_group_norm_xla(y, z, w, *, group, eps, gate_first, offset=0):
    """``gated_group_norm`` as XLA ops in float32 (a group a ROW of a
    two-dimensional array: over [B, S, G, group] XLA lays the groups out
    ahead of the tokens and copies back, 268 MB a copy, three a layer: my
    chip run, PR 40): the path of the shapes the kernels do not take, and
    their oracle."""
    B, S, D = y.shape
    gate = jax.nn.silu(z[..., offset:offset + D].astype(_F32))
    yf = y.astype(_F32)
    if gate_first:
        yf = yf * gate
    yf = yf.reshape(B * S * (D // group), group)
    yf = yf * jax.lax.rsqrt(jnp.mean(yf * yf, axis=-1, keepdims=True) + eps)
    yf = yf.reshape(B, S, D) * jnp.tile(w.astype(_F32), D // w.shape[0])
    if not gate_first:
        yf = yf * gate
    return yf.astype(y.dtype)


def tile_group_norm(y, z, w, *, group, width, eps, offset=0):
    """``gated_group_norm`` with the gate after the norm for groups of
    ``group`` lanes that hold a head of ``width`` channels and zeros (a
    head zero-padded to whole tiles: ``ops.pallas.gated_delta.lane_heads``);
    w [width], one weight for every head. The mean of squares over ``width``
    channels is ``group / width`` times the one over the group, so the
    norm over the group with ``eps * width / group`` and the weight times
    ``sqrt(width / group)`` is the head's own norm, and the padded lanes
    leave as zeros (their weight is zero)."""
    w = jnp.pad(w.astype(_F32) * (width / group) ** 0.5, (0, group - width))
    return gated_group_norm(y, z, w, group=group, eps=eps * width / group,
                            gate_first=False, offset=offset)
