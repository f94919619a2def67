"""1-bit compressed collectives over ICI — rebuild of the reference's
compressed-communication backends (runtime/comm/nccl.py:47-186 `NcclBackend.
compressed_allreduce`, runtime/comm/mpi.py:14 `MpiBackend`, cupy bit packing
in runtime/compression/cupy.py:10).

The reference's algorithm (error-compensated 1-bit Adam, two-level error
feedback):

  1. worker compensates its buffer with its local worker_error,
     computes one fp32 scale = ||buf|| / sqrt(numel), packs sign bits,
     records the new worker_error = buf - scale*sign(buf);
  2. all_to_all: worker i receives everyone's sign-chunk i (+ allgather of
     the scales), decompresses and averages its chunk — the "server" role
     is sharded round-robin over workers;
  3. the server chunk is itself compensated (server_error), re-compressed
     to sign+scale, and allgathered back to every worker.

TPU-native mapping: the collectives are `jax.lax.all_to_all`/`all_gather`
over a named mesh axis inside `shard_map` (ICI within a slice, DCN across
slices — XLA routes by mesh position); cupy packbits becomes a vectorized
bit-pack to uint8 (×32 payload shrink vs fp32, ×8 vs the sign bytes). The
two error-feedback tensors are *per-device* state: worker_error is
[numel]-shaped on every worker, server_error is [numel/n]-shaped (one chunk
per worker).

Everything here is pure and jit-able; functions taking ``axis_name`` must
run inside `shard_map` (or `pmap`) that binds the axis.
"""

import functools

import numpy as np
import jax
import jax.numpy as jnp

from deepspeed_tpu.parallel import mesh as mesh_lib


_BIT_WEIGHTS = 2 ** np.arange(8, dtype=np.uint8)  # LSB-first packing


def pack_signs(x):
    """[N] float → [N/8] uint8 bitmap, bit j of byte i = (x[8i+j] >= 0).
    N must be a multiple of 8."""
    bits = (x >= 0).reshape(-1, 8).astype(jnp.uint8)
    return (bits * jnp.asarray(_BIT_WEIGHTS)).sum(axis=1).astype(jnp.uint8)


def unpack_signs(packed, dtype=jnp.float32):
    """[M] uint8 bitmap → [8M] ±1 values of `dtype`."""
    bits = jnp.bitwise_and(
        packed[:, None] >> jnp.arange(8, dtype=jnp.uint8)[None, :], 1)
    return (bits.astype(dtype) * 2.0 - 1.0).reshape(-1)


def _scale_of(x):
    # reference scale: ||x||_2 / sqrt(numel)  (nccl.py:66)
    return jnp.linalg.norm(x) / np.sqrt(x.size)


def compressed_allreduce(buf, worker_error, server_error, axis_name):
    """Error-compensated 1-bit mean-allreduce of ``buf`` over ``axis_name``.

    Must run inside shard_map binding ``axis_name``. ``buf`` is the local
    [numel] fp32 buffer (same shape on every device, numel divisible by
    8*axis_size); ``worker_error`` is [numel], ``server_error`` is
    [numel // axis_size], both per-device.

    Returns (result, new_worker_error, new_server_error): ``result`` is the
    approximate mean of ``buf`` over the axis, identical on all devices.
    """
    n = jax.lax.axis_size(axis_name)
    numel = buf.size
    assert numel % (8 * n) == 0, (
        f"1-bit buffer numel {numel} must divide by 8*axis={8 * n}")
    chunk = numel // n

    # -- worker side: compensate, compress ------------------------------
    compensated = buf + worker_error
    worker_scale = _scale_of(compensated)
    new_worker_error = compensated - worker_scale * jnp.sign(compensated)
    packed = pack_signs(compensated)                       # [numel/8] u8

    # -- exchange: chunk i of every worker → worker i -------------------
    # [n, chunk/8] rows; row i goes to worker i, rows arrive stacked by
    # source worker
    packed = packed.reshape(n, chunk // 8)
    recv = jax.lax.all_to_all(packed, axis_name, split_axis=0, concat_axis=0)
    scales = jax.lax.all_gather(worker_scale, axis_name)   # [n]

    # -- server side: decompress+average my chunk, re-compress ----------
    signs = unpack_signs(recv.reshape(-1)).reshape(n, chunk)
    avg = (signs * scales[:, None]).mean(axis=0)           # [chunk]
    server_comp = avg + server_error
    server_scale = _scale_of(server_comp)
    new_server_error = server_comp - server_scale * jnp.sign(server_comp)
    server_packed = pack_signs(server_comp)                # [chunk/8]

    # -- gather the servers' results back to everyone -------------------
    all_packed = jax.lax.all_gather(server_packed, axis_name)  # [n, chunk/8]
    all_scales = jax.lax.all_gather(server_scale, axis_name)   # [n]
    out = unpack_signs(all_packed.reshape(-1)).reshape(n, chunk) \
        * all_scales[:, None]
    return out.reshape(buf.shape), new_worker_error, new_server_error


def hierarchical_allreduce(buf, inter_axis, intra_axis):
    """Exact two-level mean-allreduce of ``buf`` (the uncompressed leg of
    the link-aware exchange, ISSUE 10): ring reduce-scatter over the fast
    ``intra_axis`` (each device ends with one chunk of the intra-group
    sum), one mean over the slow ``inter_axis`` of just that chunk (XLA
    picks the algorithm for the DCN-class hop), ring all-gather back over
    the fast axis. Must run inside shard_map binding both axes;
    ``buf.size`` must divide by the intra axis size. Matches a flat pmean
    over both axes to fp32 ring-order rounding."""
    from deepspeed_tpu.parallel import overlap
    k = jax.lax.axis_size(intra_axis)
    shard = overlap.ring_reduce_scatter(buf, intra_axis, k)
    shard = jax.lax.pmean(shard, inter_axis) * np.float32(1.0 / k)
    return overlap.ring_all_gather(shard, intra_axis, k).reshape(buf.shape)


def hierarchical_compressed_allreduce(buf, worker_error, server_error,
                                      inter_axis, intra_axis):
    """Link-aware 1-bit mean-allreduce (ISSUE 10): only the slow
    inter-host hop is compressed.

      1. ring reduce-scatter over the fast ``intra_axis`` (uncompressed —
         ICI-class links, compression would cost more than it saves) and
         fold in the intra mean: each device holds chunk ``intra_index``
         of its group's mean;
      2. the error-compensated 1-bit exchange (`compressed_allreduce`) of
         that chunk over the slow ``inter_axis`` — sign bits + one scale
         on the DCN-class wire, ~32x fewer payload bytes than fp32;
      3. ring all-gather over the fast axis to rebuild the full buffer.

    Per-device error state is chunk-shaped: ``worker_error``
    [numel/intra], ``server_error`` [numel/(intra*inter)]; ``buf.size``
    must divide by 8*inter*intra (pad via `padded_numel(numel,
    inter*intra)`). Returns (approx_mean, new_worker_error,
    new_server_error) — the result is identical on every device."""
    from deepspeed_tpu.parallel import overlap
    k = jax.lax.axis_size(intra_axis)
    shard = overlap.ring_reduce_scatter(buf, intra_axis, k) \
        * np.float32(1.0 / k)
    red, we2, se2 = compressed_allreduce(shard, worker_error, server_error,
                                         inter_axis)
    return (overlap.ring_all_gather(red, intra_axis, k).reshape(buf.shape),
            we2, se2)


def compressed_reduce_scatter_sum(buf, worker_error, axis_name):
    """Error-compensated 1-bit reduce-scatter-SUM of ``buf`` over
    ``axis_name`` (ISSUE 16): the worker half of `compressed_allreduce`
    with no server leg — the output stays scattered, so there is nothing
    to re-compress and gather back.

    ``buf`` is the local [numel] fp32 buffer laid out piece-major: chunk
    ``j`` (of ``numel // axis_size`` elements) is destined for axis peer
    ``j``. Each worker compensates with its persistent ``worker_error``
    ([numel], per-device), compresses to sign bits + one fp32 scale,
    all-to-alls the sign chunks, and returns the weighted SUM (not mean —
    the ZeRO-3 grad contract hands the caller fp32 sums, the 1/world
    scale is applied downstream) of its own chunk over all peers:

        chunk_sum[j] = sum_i  scale_i * sign(buf_i + err_i)[my chunk]

    Returns (chunk_sum [numel/n], new_worker_error [numel]). ``numel``
    must divide by 8*axis_size (pad via `padded_numel`). Slow-hop wire
    cost per device: (n-1)/n of numel/8 sign bytes + n-1 scale floats —
    vs (n-1)/n * numel * 4 bytes for the exact ring reduce-scatter."""
    n = jax.lax.axis_size(axis_name)
    numel = buf.size
    assert numel % (8 * n) == 0, (
        f"1-bit RS buffer numel {numel} must divide by 8*axis={8 * n}")
    chunk = numel // n

    compensated = buf + worker_error
    worker_scale = _scale_of(compensated)
    new_worker_error = compensated - worker_scale * jnp.sign(compensated)
    packed = pack_signs(compensated).reshape(n, chunk // 8)
    recv = jax.lax.all_to_all(packed, axis_name, split_axis=0, concat_axis=0)
    scales = jax.lax.all_gather(worker_scale, axis_name)   # [n]
    signs = unpack_signs(recv.reshape(-1)).reshape(n, chunk)
    chunk_sum = (signs * scales[:, None]).sum(axis=0)      # [chunk]
    return chunk_sum, new_worker_error


def padded_numel(numel, axis_size):
    """Smallest buffer size >= numel divisible by 8*axis_size."""
    q = 8 * axis_size
    return ((numel + q - 1) // q) * q


def tree_compressed_allreduce(tree, worker_errors, server_errors, axis_name):
    """Per-leaf compressed allreduce of a pytree (the reference fuses the
    whole momentum into one flat buffer per tensor, onebit/adam.py:191).
    Leaves are padded to the 8*axis_size quantum; error states carry the
    padded length."""
    n = jax.lax.axis_size(axis_name)

    def one(leaf, we, se):
        flat = leaf.reshape(-1).astype(jnp.float32)
        pn = padded_numel(flat.size, n)
        buf = jnp.zeros((pn,), jnp.float32).at[:flat.size].set(flat)
        out, we2, se2 = compressed_allreduce(buf, we, se, axis_name)
        return out[:flat.size].reshape(leaf.shape), we2, se2

    flat = jax.tree_util.tree_map(one, tree, worker_errors, server_errors)
    pick = lambda i: jax.tree_util.tree_map(  # noqa: E731
        lambda t: t[i], flat, is_leaf=lambda x: isinstance(x, tuple))
    return pick(0), pick(1), pick(2)


def init_error_states(params, axis_size):
    """(worker_errors, server_errors) zero trees for a param tree — worker
    [padded], server [padded/axis]."""
    def we(p):
        return jnp.zeros((padded_numel(p.size, axis_size),), jnp.float32)

    def se(p):
        return jnp.zeros((padded_numel(p.size, axis_size) // axis_size,),
                         jnp.float32)

    return (jax.tree_util.tree_map(we, params),
            jax.tree_util.tree_map(se, params))
