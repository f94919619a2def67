"""Bucketed gradient-sync scheduler — explicit comm/compute overlap.

The fused GSPMD train step expresses the ZeRO grad exchange as one implicit
constraint ("grad reduce-scatter → a sharding constraint", runtime/engine.py
docstring), which leaves XLA free to serialize the WHOLE gradient exchange
after backward. The reference DeepSpeed instead buckets gradients as they
are produced and overlaps each bucket's collective with the remaining
backward compute (`overlap_comm` + the IPG bucket machinery,
stage2.py:614-746). This module is the TPU-native rebuild of that
scheduler:

  * gradients flatten (in tree-leaf order) into fixed-size fp32 buckets
    (config knob ``zero_optimization.reduce_bucket_size``, reference
    constants.py ZERO_REDUCE_BUCKET_SIZE — element count, default 5e8);
  * each bucket's exchange is an EXPLICIT ring program over the data axis
    (`lax.ppermute` hops, like parallel/ring_attention.py): a ring
    reduce-scatter followed by a ring all-gather — an allreduce decomposed
    into 2(n-1) chunk hops whose only data dependency is the bucket's own
    leaves. XLA's latency-hiding scheduler can therefore float bucket k's
    hops over bucket k+1's backward compute and over other buckets' hops,
    where one monolithic post-hoc psum has nothing to overlap with;
  * ``mode="fused"`` keeps the bucket granularity but lets XLA pick the
    collective implementation per bucket (one `lax.psum` each) — the
    fallback when ppermute rings lose to the fused collective on a given
    interconnect (measure; see docs/perf_tuning.md).

Everything here is pure, jit-able, and must run INSIDE `shard_map` binding
the axis (the engine's explicit-comm train path, like parallel/compression).
Numerics: ring summation visits devices in ring order rather than the
reduction tree XLA picks for psum, so results match psum to fp32 rounding
(the numerics test pins this across bucket layouts).

The 1-bit path rides the same bucket stream: `bucketed_compressed_allreduce`
runs parallel/compression.py's error-compensated 1-bit exchange per bucket,
so a bucket is the unit of both overlap and compression.
"""

import dataclasses
from typing import List, Optional, Sequence, Tuple

import numpy as np
import jax
import jax.numpy as jnp


# ---------------------------------------------------------------------------
# bucket planning (host-side, static)
# ---------------------------------------------------------------------------

@dataclasses.dataclass(frozen=True)
class Bucket:
    """One bucket: a contiguous run of flattened leaves.

    ``leaf_ids`` indexes the flat leaf list; ``sizes`` are the flattened
    element counts; ``padded`` is the bucket's exchange length — total
    elements rounded up to a multiple of the axis size so the ring can
    chunk it evenly (the uneven LAST bucket differs from the rest)."""
    leaf_ids: Tuple[int, ...]
    sizes: Tuple[int, ...]
    padded: int

    @property
    def numel(self):
        return int(sum(self.sizes))


def plan_buckets(shapes: Sequence, bucket_elems: int,
                 axis_size: int) -> List[Bucket]:
    """Greedy whole-leaf packing of ``shapes`` (in order) into buckets of
    ~``bucket_elems`` elements (the reference's IPG bucket close condition,
    stage2.py `elements_in_ipg_bucket + param.numel() > reduce_bucket_size`).
    A leaf larger than the budget gets a bucket of its own; the last bucket
    is whatever is left over (usually uneven)."""
    bucket_elems = max(int(bucket_elems), 1)
    buckets: List[Bucket] = []
    ids: List[int] = []
    sizes: List[int] = []
    acc = 0
    n_leaves = 0
    for i, shape in enumerate(shapes):
        n = int(np.prod(shape or (1,)))
        n_leaves += 1
        if ids and acc + n > bucket_elems:
            buckets.append(_close_bucket(ids, sizes, axis_size))
            ids, sizes, acc = [], [], 0
        ids.append(i)
        sizes.append(n)
        acc += n
    if ids:
        buckets.append(_close_bucket(ids, sizes, axis_size))
    # flight-recorder breadcrumb (trace-time only — planning runs once
    # per compile, never per step): what the bucket stream looked like
    from deepspeed_tpu.telemetry.recorder import default_recorder
    default_recorder().record(
        "overlap_bucket_plan", buckets=len(buckets), leaves=n_leaves,
        elems=sum(b.numel for b in buckets),
        padded_elems=sum(b.padded for b in buckets), axis_size=axis_size,
        bucket_elems=bucket_elems)
    return buckets


def _close_bucket(ids, sizes, axis_size):
    total = int(sum(sizes))
    padded = ((total + axis_size - 1) // axis_size) * axis_size
    return Bucket(tuple(ids), tuple(sizes), padded)


# ---------------------------------------------------------------------------
# ring collectives (per-device local view; inside shard_map)
# ---------------------------------------------------------------------------

def _ring_hops(fn_body, n, unroll_limit=32):
    """n-1 ring hops, unrolled below ``unroll_limit`` so the latency-hiding
    scheduler sees independent ops it can interleave across buckets; a scan
    (sequential while loop) above it to bound HLO size on huge meshes."""
    return n <= unroll_limit


def ring_reduce_scatter(buf, axis_name: str, n: int) -> jax.Array:
    """[n*c] local buffer → [c] shard: this device ends with the sum over
    the axis of chunk ``axis_index``. Standard ring: the partial for chunk k
    is born on device (k+1) mod n and accumulates one local chunk per hop
    until it lands on device k after n-1 hops — c elements on the wire per
    hop per device."""
    assert buf.size % n == 0, (buf.size, n)
    c = buf.size // n
    if n == 1:
        return buf.reshape(c)
    chunks = buf.reshape(n, c)
    idx = jax.lax.axis_index(axis_name)
    perm = [(j, (j + 1) % n) for j in range(n)]
    carry = jnp.take(chunks, (idx - 1) % n, axis=0, mode="wrap")
    if _ring_hops(None, n):
        for s in range(1, n):
            carry = jax.lax.ppermute(carry, axis_name, perm)
            carry = carry + jnp.take(chunks, (idx - 1 - s) % n, axis=0,
                                     mode="wrap")
    else:
        def hop(carry, s):
            carry = jax.lax.ppermute(carry, axis_name, perm)
            return carry + jnp.take(chunks, (idx - 1 - s) % n, axis=0,
                                    mode="wrap"), None
        carry, _ = jax.lax.scan(hop, carry, jnp.arange(1, n))
    return carry


def ring_all_gather(shard, axis_name: str, n: int) -> jax.Array:
    """[c] shard (this device owns chunk ``axis_index``) → [n*c] full
    buffer, chunks in axis order; the reverse ring of ring_reduce_scatter."""
    if n == 1:
        return shard
    c = shard.size
    idx = jax.lax.axis_index(axis_name)
    perm = [(j, (j + 1) % n) for j in range(n)]
    out = jnp.zeros((n, c), shard.dtype)
    out = jax.lax.dynamic_update_index_in_dim(out, shard[None], idx, 0)
    carry = shard
    if _ring_hops(None, n):
        for s in range(1, n):
            carry = jax.lax.ppermute(carry, axis_name, perm)
            out = jax.lax.dynamic_update_index_in_dim(
                out, carry[None], (idx - s) % n, 0)
    else:
        def hop(acc, s):
            out, carry = acc
            carry = jax.lax.ppermute(carry, axis_name, perm)
            out = jax.lax.dynamic_update_index_in_dim(
                out, carry[None], (idx - s) % n, 0)
            return (out, carry), None
        (out, _), _ = jax.lax.scan(hop, (out, carry), jnp.arange(1, n))
    return out.reshape(-1)


# ---------------------------------------------------------------------------
# bucketed tree sync (inside shard_map)
# ---------------------------------------------------------------------------

def _pack_bucket(leaves, bucket: Bucket) -> jax.Array:
    parts = [leaves[i].reshape(-1).astype(jnp.float32)
             for i in bucket.leaf_ids]
    flat = parts[0] if len(parts) == 1 else jnp.concatenate(parts)
    if bucket.padded != bucket.numel:
        flat = jnp.zeros((bucket.padded,), jnp.float32).at[:flat.size].set(flat)
    return flat


def _unpack_bucket(flat, leaves, bucket: Bucket, out):
    off = 0
    for i, sz in zip(bucket.leaf_ids, bucket.sizes):
        leaf = leaves[i]
        out[i] = jax.lax.dynamic_slice_in_dim(flat, off, sz, 0) \
            .reshape(leaf.shape).astype(leaf.dtype)
        off += sz


def bucketed_allreduce(tree, axis_name: str, n: int, bucket_elems: int,
                       mode: str = "ring", mean: bool = True):
    """Sum (or mean) a gradient pytree over ``axis_name`` as a stream of
    per-bucket explicit collectives. Must run inside shard_map binding the
    axis with the tree per-device (unreduced local grads).

    mode="ring":  per bucket, ring reduce-scatter + ring all-gather
                  (2(n-1) chunk hops the scheduler can float over compute).
    mode="fused": per bucket, one `lax.psum` (XLA picks the algorithm) —
                  still bucketed, so buckets interleave with backward.
    """
    if mode not in ("ring", "fused"):
        raise ValueError(f"mode must be 'ring' or 'fused', got {mode!r}")
    leaves, treedef = jax.tree_util.tree_flatten(tree)
    if not leaves or n == 1:
        return tree
    buckets = plan_buckets([l.shape for l in leaves], bucket_elems, n)
    inv = np.float32(1.0 / n)
    out: List[Optional[jax.Array]] = [None] * len(leaves)
    for bucket in buckets:
        flat = _pack_bucket(leaves, bucket)
        if mode == "ring":
            shard = ring_reduce_scatter(flat, axis_name, n)
            flat = ring_all_gather(shard, axis_name, n)
        else:
            flat = jax.lax.psum(flat, axis_name)
        if mean:
            flat = flat * inv
        _unpack_bucket(flat, leaves, bucket, out)
    return jax.tree_util.tree_unflatten(treedef, out)


def bucketed_reduce_scatter(tree, axis_name: str, n: int, bucket_elems: int,
                            mean: bool = True):
    """Ring reduce-scatter only: returns the list of per-bucket [padded/n]
    fp32 shards (this device's chunk of each bucket) plus the bucket plan —
    the ZeRO-2 shape, for callers that update in flat shard space and
    all-gather params instead of grads. The allreduce above is RS∘AG of
    this."""
    leaves, _ = jax.tree_util.tree_flatten(tree)
    buckets = plan_buckets([l.shape for l in leaves], bucket_elems, n)
    shards = []
    inv = np.float32(1.0 / n)
    for bucket in buckets:
        flat = _pack_bucket(leaves, bucket)
        shard = ring_reduce_scatter(flat, axis_name, n)
        shards.append(shard * inv if mean else shard)
    return shards, buckets


def bucketed_compressed_allreduce(tree, worker_errors, server_errors,
                                  axis_name: str, n: int, bucket_elems: int):
    """1-bit error-compensated mean-allreduce riding the bucket stream:
    each bucket is one compression unit (sign-pack → all_to_all → server
    average → all_gather, parallel/compression.py) instead of one unit per
    LEAF (tree_compressed_allreduce) — fewer, larger collectives whose
    exchanges interleave exactly like the ring buckets.

    ``worker_errors``/``server_errors`` are lists aligned with the bucket
    plan of ``tree`` (see `compressed_error_states`). Returns
    (mean_tree, new_worker_errors, new_server_errors)."""
    from deepspeed_tpu.parallel import compression as comp
    leaves, treedef = jax.tree_util.tree_flatten(tree)
    buckets = plan_buckets([l.shape for l in leaves], bucket_elems, n)
    assert len(worker_errors) == len(buckets), \
        (len(worker_errors), len(buckets))
    out: List[Optional[jax.Array]] = [None] * len(leaves)
    new_we, new_se = [], []
    for bucket, we, se in zip(buckets, worker_errors, server_errors):
        flat = _pack_bucket(leaves, bucket)
        pn = comp.padded_numel(bucket.padded, n)
        if pn != flat.size:
            flat = jnp.zeros((pn,), jnp.float32).at[:flat.size].set(flat)
        red, we2, se2 = comp.compressed_allreduce(flat, we, se, axis_name)
        new_we.append(we2)
        new_se.append(se2)
        _unpack_bucket(red[:bucket.padded], leaves, bucket, out)
    return jax.tree_util.tree_unflatten(treedef, out), new_we, new_se


# ---------------------------------------------------------------------------
# hierarchical link-aware exchange (ISSUE 10): per-bucket compression
# policy over a slow/fast split of the data axis
# ---------------------------------------------------------------------------

@dataclasses.dataclass(frozen=True)
class HierarchyPlan:
    """Static link-aware comm plan for the bucketed exchange: the data
    axis split into ``inter`` slow-link groups (DCN-class,
    ``inter_axis``) of ``intra`` fast-link devices (ICI-class,
    ``intra_axis``), plus the per-bucket compression policy —
    ``"always"``/``"never"``, or ``"auto"``: compress only buckets whose
    fp32 payload clears ``min_bucket_bytes`` (small buckets pay more in
    scale overhead + pack/unpack than the sign bits save)."""
    inter_axis: str
    intra_axis: str
    inter: int
    intra: int
    compression: str = "auto"
    min_bucket_bytes: int = 1 << 16
    bucket_elems: int = int(5e8)

    @property
    def axes(self):
        return (self.inter_axis, self.intra_axis)

    @property
    def world(self):
        return self.inter * self.intra


def plan_bucket_compression(buckets, plan: HierarchyPlan):
    """Per-bucket compress/no-compress decision (host-side, static at
    trace time — the link assignment itself is the plan's axis split).
    Pure: the engine breadcrumbs the plan once per compile
    (`comm_hierarchy_plan`), since this runs from several callers
    (error-state init, the traced exchange, the wire model)."""
    if plan.compression == "always":
        return [True] * len(buckets)
    if plan.compression == "never":
        return [False] * len(buckets)
    return [b.padded * 4 >= plan.min_bucket_bytes for b in buckets]


def bucketed_hierarchical_mean(tree, plan: HierarchyPlan):
    """Exact two-level mean of a gradient pytree riding the bucket
    stream (the warmup-phase exchange of the hierarchical 1-bit path):
    per bucket, ring reduce-scatter over the fast axis → pmean of the
    chunk over the slow axis → ring all-gather. Must run inside
    shard_map binding both plan axes."""
    from deepspeed_tpu.parallel import compression as comp
    leaves, treedef = jax.tree_util.tree_flatten(tree)
    if not leaves:
        return tree
    buckets = plan_buckets([l.shape for l in leaves], plan.bucket_elems,
                           plan.world)
    out: List[Optional[jax.Array]] = [None] * len(leaves)
    for bucket in buckets:
        flat = _pack_bucket(leaves, bucket)
        flat = comp.hierarchical_allreduce(flat, plan.inter_axis,
                                           plan.intra_axis)
        _unpack_bucket(flat, leaves, bucket, out)
    return jax.tree_util.tree_unflatten(treedef, out)


def bucketed_hierarchical_compressed_allreduce(tree, worker_errors,
                                               server_errors,
                                               plan: HierarchyPlan):
    """Policy-driven link-aware mean-allreduce of a pytree over the
    bucket stream: buckets the policy compresses run the two-level 1-bit
    exchange (`compression.hierarchical_compressed_allreduce` — slow-axis
    sign bits with error feedback); the rest run the exact two-level
    mean. ``worker_errors``/``server_errors`` are per-bucket lists (None
    entries for uncompressed buckets — see `hierarchical_error_states`).
    Returns (mean_tree, new_worker_errors, new_server_errors)."""
    from deepspeed_tpu.parallel import compression as comp
    leaves, treedef = jax.tree_util.tree_flatten(tree)
    buckets = plan_buckets([l.shape for l in leaves], plan.bucket_elems,
                           plan.world)
    flags = plan_bucket_compression(buckets, plan)
    assert len(worker_errors) == len(buckets), \
        (len(worker_errors), len(buckets))
    out: List[Optional[jax.Array]] = [None] * len(leaves)
    new_we, new_se = [], []
    for bucket, flag, we, se in zip(buckets, flags, worker_errors,
                                    server_errors):
        flat = _pack_bucket(leaves, bucket)
        if flag:
            pn = comp.padded_numel(bucket.padded, plan.world)
            if pn != flat.size:
                flat = jnp.zeros((pn,), jnp.float32) \
                    .at[:flat.size].set(flat)
            red, we2, se2 = comp.hierarchical_compressed_allreduce(
                flat, we, se, plan.inter_axis, plan.intra_axis)
            red = red[:bucket.padded]
        else:
            red = comp.hierarchical_allreduce(flat, plan.inter_axis,
                                              plan.intra_axis)
            we2, se2 = we, se
        new_we.append(we2)
        new_se.append(se2)
        _unpack_bucket(red, leaves, bucket, out)
    return jax.tree_util.tree_unflatten(treedef, out), new_we, new_se


def hierarchical_error_states(params, plan: HierarchyPlan):
    """Zero error-feedback state aligned with the bucket plan AND the
    compression policy of ``params``: compressed buckets carry
    chunk-shaped worker [pn/intra] and server [pn/(intra*inter)] errors;
    uncompressed buckets carry None (nothing to compensate — the None
    rides the pytree as empty structure through the phase cond)."""
    from deepspeed_tpu.parallel import compression as comp
    leaves = jax.tree_util.tree_leaves(params)
    buckets = plan_buckets([l.shape for l in leaves], plan.bucket_elems,
                           plan.world)
    flags = plan_bucket_compression(buckets, plan)
    wes, ses = [], []
    for bucket, flag in zip(buckets, flags):
        if not flag:
            wes.append(None)
            ses.append(None)
            continue
        pn = comp.padded_numel(bucket.padded, plan.world)
        wes.append(jnp.zeros((pn // plan.intra,), jnp.float32))
        ses.append(jnp.zeros((pn // plan.world,), jnp.float32))
    return wes, ses


def hierarchy_wire_bytes(buckets, flags, plan: HierarchyPlan):
    """Trace-time bytes-on-wire cost model (per device, per step) for
    the hierarchical exchange — what the telemetry counters
    ``comm/bytes_on_wire/{intra,inter}`` advance by each step.

    Ring formulas: the fast-axis reduce-scatter + all-gather move
    2(k-1) fp32 chunks of pn/k elements per device; the slow-axis hop
    moves, uncompressed, a ring allreduce of the pn/k chunk
    (2·(ni-1)/ni·4 bytes/elem), or compressed, the packed sign bitmaps
    both ways (all_to_all + server all-gather, (ni-1)/ni·pn/(8k) bytes
    each) plus 2(ni-1) fp32 scales. ``inter_uncompressed`` is the
    would-have-been fp32 cost of the same slow hop — the compression
    denominator of the compression's reduction in bytes."""
    from deepspeed_tpu.parallel import compression as comp
    k, ni = plan.intra, plan.inter
    intra = inter = inter_unc = 0
    for bucket, flag in zip(buckets, flags):
        pn = comp.padded_numel(bucket.padded, plan.world) if flag \
            else bucket.padded
        c = pn // k
        intra += 2 * (k - 1) * c * 4
        unc = 2 * c * 4 * (ni - 1) // ni
        if flag:
            inter += 2 * (c // 8) * (ni - 1) // ni + 2 * (ni - 1) * 4
        else:
            inter += unc
        inter_unc += unc
    return {"intra": int(intra), "inter": int(inter),
            "inter_uncompressed": int(inter_unc)}


def compressed_error_states(params, axis_size: int, bucket_elems: int):
    """Zero error-feedback state aligned with the bucket plan of ``params``
    (worker [padded_numel], server [padded_numel/axis] per bucket)."""
    from deepspeed_tpu.parallel import compression as comp
    leaves = jax.tree_util.tree_leaves(params)
    buckets = plan_buckets([l.shape for l in leaves], bucket_elems,
                           axis_size)
    wes, ses = [], []
    for bucket in buckets:
        pn = comp.padded_numel(bucket.padded, axis_size)
        wes.append(jnp.zeros((pn,), jnp.float32))
        ses.append(jnp.zeros((pn // axis_size,), jnp.float32))
    return wes, ses
