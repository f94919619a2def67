"""Tile-granularity fused matmul+collective kernels (ISSUE 8).

The stage3_prefetch pipeline (parallel/prefetch.py) overlaps parameter
gathers with compute at LAYER granularity: layer i+1's packed shards
ride the ring while layer i computes, but layer i's own first GEMM
still waits on its full all-gather, and backward's per-layer grad
reduce-scatters serialize against the same ring. T3 (arxiv 2401.16677)
and the fused computation-collective work (arxiv 2305.06942) show the
remaining win comes from TILE granularity: decompose the ring
collective into its per-chunk hops and interleave them with the GEMM's
own k/m-loop, so each hop hides inside the matmul tile it feeds. This
module is that decomposition, three ways:

  * ``all_gather_matmul`` — ``y = x @ W_full`` where ``W`` rests as a
    ZeRO-3 shard: each ring step computes the GEMM tile over the chunk
    already on-device while the next chunk is in flight. When the
    shard cuts W's contraction dim the chunk GEMMs accumulate
    (``y += x[:, c] @ W_c``, fp32); when it cuts the output dim they
    assemble output column blocks. ``transpose_w`` serves the backward
    ``dx = dy @ W^T`` from the SAME resting shard — no transposed copy.
  * ``matmul_reduce_scatter`` — the param-grad transpose:
    ``dW_shard = RS_axis(lhs^T @ rhs)`` as a ring of partial-block
    GEMMs. Each step computes the [*, chunk] partial destined for one
    device and ring-shifts the running accumulation, so every device
    ends holding ONLY its reduced output shard — the full [K, N]
    gradient never materializes.
  * ``collective_matmul`` — the custom-VJP pairing of the two: forward
    all-gather+matmul, backward matmul+reduce-scatter for dW (shard-
    shaped, already SUMMED over the axis) and a transposed
    all-gather+matmul for dx.

Each op has two interchangeable lowerings, chosen per call:

  backend="fused"  one ``pallas_call`` per GEMM: grid (ring_step,
                   m_tile), the next chunk ppermutes via in-kernel
                   RDMA (``make_async_remote_copy`` + a neighbor
                   credit semaphore) while the current chunk's tiles
                   multiply. Interpret-mode runs on CPU for numerics;
                   The TPU compiler REFUSES both kernels on the
                   installed jax (tests/test_tpu_compile.py quotes it;
                   ROADMAP S2) — nothing selects this on a chip yet.
  backend="lax"    the decomposed-ring reference: the same chunk
                   schedule as ``lax.ppermute`` hops + per-chunk
                   ``dot_general`` tiles, valid on any mesh/dtype —
                   the fallback for shapes the kernel doesn't cover
                   and the CPU-proxy bench path.

Everything here is pure, jit-able, and must run INSIDE ``shard_map``
binding ``axis_name``. Ring schedules mirror parallel/overlap.py
(chunk k lands on device k), so layouts compose with the prefetch
pipeline's ring mode; numerics match a single ``jnp.einsum`` to fp32
partial-sum rounding (pinned by tests/test_fused_collective.py).
"""

import dataclasses
import functools
import threading
from typing import Optional, Tuple

import numpy as np
import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu

from deepspeed_tpu.utils.platform import is_tpu_backend

# ---------------------------------------------------------------------------
# config + trace-scoped context
# ---------------------------------------------------------------------------

@dataclasses.dataclass(frozen=True)
class RingHierarchy:
    """Two-level split of the collective axis for link-aware lowering
    (ISSUE 16): the flat ring of ``inter * intra`` devices becomes
    ``inter`` slow-link blocks (DCN-class, ``inter_axis``) of ``intra``
    fast-link devices (ICI-class, ``intra_axis``) each. Frozen/hashable
    so it can ride `CollectiveMatmulConfig` through the custom-VJP
    builder cache. Axis names must be bound by the enclosing shard_map
    (the `mesh.split_data_axis` view); the flat data axis name does NOT
    exist on that mesh, so a hierarchical call never touches it."""
    inter_axis: str
    intra_axis: str
    inter: int
    intra: int


@dataclasses.dataclass(frozen=True)
class CollectiveMatmulConfig:
    """Static per-train-fn configuration (hashable: keys custom-VJP
    builder caches and rides the trace-scoped gather context).

    ``backend``: "auto" (fused on TPU, lax elsewhere) | "fused" | "lax".
    ``tile_m``: requested m-tile of the fused kernel's grid (clamped to
    a divisor of the actual M).
    ``min_shard_bytes``: a weight qualifies for fused consumption only
    when its per-device shard is at least this large — below it the
    packed layer-gather of prefetch ring mode is cheaper than n chunk
    GEMMs.
    ``interpret``: force pallas interpret mode (None = auto: interpret
    everywhere except a real TPU backend).
    ``vmem_budget_bytes``: ceiling on the contracting kernel's chunk
    stash (it holds the FULL weight in VMEM — see _ag_matmul_fused);
    bigger weights take the lax ring under backend="auto".
    ``hierarchy``: optional two-level split — when set, both collective
    ops run the link-aware schedule (ONE inter-block hop per operand,
    the per-block ring over the fast axis; see _hier_ag_matmul) and the
    per-block intra rings run the lax decomposed ring regardless of
    ``backend`` (pallas remote DMA cannot address a two-named-axis
    env — see _sub_cfg)."""
    axis_name: str = "data"
    axis_size: int = 1
    backend: str = "auto"
    tile_m: int = 128
    min_shard_bytes: int = 1 << 16
    interpret: Optional[bool] = None
    vmem_budget_bytes: int = 8 << 20
    hierarchy: Optional[RingHierarchy] = None


class _CtxState(threading.local):
    def __init__(self):
        self.stack = []


_ctx_state = _CtxState()


class gather_scope:
    """Trace-scoped activation of fused gather+matmul consumption: while
    entered, models whose dense layers are collective-matmul-aware
    (models/gpt2.py CollectiveDense) treat a shard-shaped kernel in
    their param tree as a ZeRO-3 resting shard and feed it to
    ``collective_matmul`` instead of a materialized full weight. The
    prefetch pipeline enters it exactly around its per-layer body
    invocations (forward and backward-vjp traces) — like
    mesh_lib.layout_pins, this is a Python-call-scoped fact, reliable
    wherever jax re-traces the body. Re-entrant; innermost wins."""

    def __init__(self, cfg: Optional[CollectiveMatmulConfig]):
        self.cfg = cfg

    def __enter__(self):
        _ctx_state.stack.append(self.cfg)
        return self

    def __exit__(self, *exc):
        _ctx_state.stack.pop()
        return False


def gather_ctx() -> Optional[CollectiveMatmulConfig]:
    """The active fused-gather config, or None outside the prefetch
    pipeline's fused_matmul body traces."""
    stack = _ctx_state.stack
    return stack[-1] if stack else None


def infer_shard_dim(shard_shape, in_dim: int, features: int,
                    axis_size: int) -> Optional[int]:
    """Which dim of a [in_dim, features] weight a shard cuts: 0, 1, or
    None when ``shard_shape`` IS the full shape (not a shard). Raises
    on a shape that is neither — a wiring bug, not a fallback case."""
    if tuple(shard_shape) == (in_dim, features):
        return None
    if in_dim % axis_size == 0 and \
            tuple(shard_shape) == (in_dim // axis_size, features):
        return 0
    if features % axis_size == 0 and \
            tuple(shard_shape) == (in_dim, features // axis_size):
        return 1
    raise ValueError(
        f"kernel value of shape {tuple(shard_shape)} is neither the full "
        f"({in_dim}, {features}) weight nor its 1/{axis_size} shard on "
        f"either dim")


# ---------------------------------------------------------------------------
# shared ring arithmetic
# ---------------------------------------------------------------------------

def _ring_perm(n):
    return [(j, (j + 1) % n) for j in range(n)]


def _divisor_tile(m: int, requested: int) -> int:
    """Largest divisor of ``m`` that is <= requested (>=1): the fused
    kernels require the grid to tile M exactly."""
    t = max(1, min(int(requested), m))
    while m % t:
        t -= 1
    return t


def _breadcrumb(op, site, backend, **fields):
    # trace-time only (dispatch runs once per compile, never per step)
    from deepspeed_tpu.telemetry.recorder import default_recorder
    default_recorder().record("collective_matmul", op=op, site=site,
                              backend=backend, **fields)


# ---------------------------------------------------------------------------
# lax decomposed-ring reference path
# ---------------------------------------------------------------------------

def _ag_matmul_lax(x, w_shard, *, contracting, transpose_w, axis_name, n,
                   out_dtype, precision=None):
    """Decomposed-ring all-gather+matmul: chunk held at ring step s is
    chunk id (axis_index - s) mod n (the overlap.ring_all_gather
    schedule); its GEMM tile issues while the next hop is in flight —
    per-chunk dots with no data dependency between hop s+1 and tile s,
    so XLA's latency-hiding scheduler floats the ppermutes over the
    MXU work."""
    idx = jax.lax.axis_index(axis_name)
    perm = _ring_perm(n)
    m = x.shape[0]
    cdim = 1 if transpose_w else 0          # chunked dim of the dot's rhs
    dnums = (((1,), (cdim,)), ((), ()))
    chunk = w_shard
    if contracting:
        ck = w_shard.shape[1] if transpose_w else w_shard.shape[0]
        n_out = w_shard.shape[0] if transpose_w else w_shard.shape[1]
        acc = jnp.zeros((m, n_out), jnp.float32)
        for s in range(n):
            c = jax.lax.rem(idx - s + n, n)
            xs = jax.lax.dynamic_slice_in_dim(x, c * ck, ck, axis=1)
            acc = acc + jax.lax.dot_general(
                xs, chunk, dnums, preferred_element_type=jnp.float32,
                precision=precision)
            if s < n - 1:
                chunk = jax.lax.ppermute(chunk, axis_name, perm)
        return acc.astype(out_dtype)
    ck_out = w_shard.shape[0] if transpose_w else w_shard.shape[1]
    out = jnp.zeros((m, n * ck_out), out_dtype)
    for s in range(n):
        c = jax.lax.rem(idx - s + n, n)
        blk = jax.lax.dot_general(
            x, chunk, dnums, preferred_element_type=jnp.float32,
            precision=precision).astype(out_dtype)
        out = jax.lax.dynamic_update_slice_in_dim(out, blk, c * ck_out,
                                                  axis=1)
        if s < n - 1:
            chunk = jax.lax.ppermute(chunk, axis_name, perm)
    return out


def _mm_rs_lax(lhs, rhs, *, chunk_lhs, axis_name, n, precision=None):
    """Decomposed-ring matmul+reduce-scatter: the partial for chunk k
    is born on device (k+1) mod n as a chunk GEMM and accumulates one
    local partial per hop until it lands on device k — the
    overlap.ring_reduce_scatter schedule with the pack/GEMM fused, so
    the full [K, N] product never materializes. Returns this device's
    fp32 shard, SUMMED over the axis."""
    idx = jax.lax.axis_index(axis_name)
    perm = _ring_perm(n)
    ck = (lhs.shape[1] if chunk_lhs else rhs.shape[1]) // n
    dnums = (((0,), (0,)), ((), ()))

    def partial(c):
        if chunk_lhs:
            ls = jax.lax.dynamic_slice_in_dim(lhs, c * ck, ck, axis=1)
            return jax.lax.dot_general(
                ls, rhs, dnums, preferred_element_type=jnp.float32,
                precision=precision)
        rs = jax.lax.dynamic_slice_in_dim(rhs, c * ck, ck, axis=1)
        return jax.lax.dot_general(
            lhs, rs, dnums, preferred_element_type=jnp.float32,
            precision=precision)

    carry = partial(jax.lax.rem(idx - 1 + n, n))
    for s in range(1, n):
        carry = jax.lax.ppermute(carry, axis_name, perm)
        carry = carry + partial(jax.lax.rem(idx - 1 - s + 2 * n, n))
    return carry


# ---------------------------------------------------------------------------
# fused pallas kernels (ring RDMA inside the GEMM grid)
# ---------------------------------------------------------------------------
#
# Both kernels share the grid shape (ring_step s, m_tile i) and the
# neighbor-credit protocol that makes the 2-slot comm buffer race-free:
#
#   * a chunk ppermutes right (device i -> i+1) via make_async_remote_copy
#     into alternating slots (step s lives in slot s % 2);
#   * before sending into the right neighbor's slot, a device waits ONE
#     credit on a counting semaphore; the neighbor signals that credit
#     only after it has (a) finished every GEMM tile that read the slot
#     being recycled and (b) seen its own send out of that slot complete
#     (wait_send) — without (b), an in-flight send's source could be
#     overwritten by the incoming copy (the classic 2-slot WAR race);
#   * signals and waits are balanced exactly (n-2 of each), so the
#     scratch semaphores drain to zero by kernel exit;
#   * interpret mode SKIPS the credit exchange (a Python-level gate, not
#     a traced branch): the interpreter executes the remote copies
#     synchronously so the WAR race cannot occur, and its discharge
#     rules do not implement remote semaphore_signal. The credit path is
#     therefore Mosaic-only and so far unverified: the kernels do not
#     yet compile for a TPU (tests/test_tpu_compile.py, ROADMAP S2).

def _ag_matmul_fused(x, w_shard, *, contracting, transpose_w, axis_name,
                     n, tile_m, interpret, out_dtype, precision=None):
    m, k_x = x.shape
    ck_w = tuple(w_shard.shape)
    tile = _divisor_tile(m, tile_m)
    mt = m // tile
    cdim = 1 if transpose_w else 0
    dnums = (((1,), (cdim,)), ((), ()))
    idx = jax.lax.axis_index(axis_name)
    order = jax.lax.rem(idx - jnp.arange(n, dtype=jnp.int32) + n, n)

    if contracting:
        # Chunks CONTRACT (y += x[:, c] @ W_c): the output block must
        # accumulate across ring steps, so the grid runs (m_tile, step)
        # with steps INNERMOST — the out block stays VMEM-resident over
        # its consecutive revisits (the canonical pallas accumulation
        # pattern; an aliased HBM round-trip is NOT interpretable, jax
        # b/370563936). The ring completes during the first m-tile's
        # step sweep into a per-chunk stash (each slot written exactly
        # once — no credit protocol needed); later m-tiles replay the
        # chunk GEMMs from the stash. VMEM holds the full stashed W: the
        # dispatcher falls back to the lax ring when that exceeds the
        # configured budget.
        ck_x = ck_w[1] if transpose_w else ck_w[0]
        n_out = ck_w[0] if transpose_w else ck_w[1]
        out_shape = (m, n_out)

        def kernel(order_ref, x_ref, w_ref, o_ref, stash,
                   send_sem, recv_sem):
            i = pl.program_id(0)
            s = pl.program_id(1)
            my = jax.lax.axis_index(axis_name)
            right = jax.lax.rem(my + 1, n)

            def hop(step):
                return pltpu.make_async_remote_copy(
                    src_ref=stash.at[step], dst_ref=stash.at[step + 1],
                    send_sem=send_sem.at[step],
                    recv_sem=recv_sem.at[step + 1],
                    device_id=right,
                    device_id_type=pltpu.DeviceIdType.LOGICAL)

            @pl.when(i == 0)
            def _():
                @pl.when(s == 0)
                def _():
                    stash[0] = w_ref[:]

                @pl.when(s > 0)
                def _():
                    hop(s - 1).wait_recv()      # chunk for step s landed

                # drain send semaphores two steps behind (send s-1 is
                # usually still flying under step s's GEMM) plus the
                # final one at the last step — n-1 sends, n-1 waits
                @pl.when(s > 1)
                def _():
                    hop(s - 2).wait_send()

                # forward the chunk while its GEMM tile runs below
                @pl.when(s < n - 1)
                def _():
                    hop(s).start()

                @pl.when(s == n - 1)
                def _():
                    hop(n - 2).wait_send()

            tile_out = jax.lax.dot_general(
                x_ref[:], stash[s], dnums,
                preferred_element_type=jnp.float32, precision=precision)

            @pl.when(s == 0)
            def _():
                o_ref[:] = tile_out

            @pl.when(s > 0)
            def _():
                o_ref[:] = o_ref[:] + tile_out

        grid_spec = pltpu.PrefetchScalarGridSpec(
            num_scalar_prefetch=1,
            grid=(mt, n),
            in_specs=[
                pl.BlockSpec((tile, ck_x),
                             lambda i, s, order: (i, order[s])),
                pl.BlockSpec(ck_w, lambda i, s, order: (0, 0)),
            ],
            out_specs=pl.BlockSpec((tile, n_out),
                                   lambda i, s, order: (i, 0)),
            scratch_shapes=[
                pltpu.VMEM((n,) + ck_w, w_shard.dtype),
                pltpu.SemaphoreType.DMA((n,)),
                pltpu.SemaphoreType.DMA((n,)),
            ])
        y = pl.pallas_call(
            kernel,
            grid_spec=grid_spec,
            out_shape=jax.ShapeDtypeStruct(out_shape, jnp.float32),
            compiler_params=pltpu.CompilerParams(
                dimension_semantics=("arbitrary", "arbitrary"),
                collective_id=0),
            interpret=interpret)(order, x, w_shard)
        return y.astype(out_dtype)

    # Chunks produce OUTPUT COLUMN BLOCKS (y[:, c] = x @ W_c): no
    # accumulation, so the grid runs (step, m_tile) with the 2-slot
    # comm buffer + neighbor-credit protocol — maximum overlap (the
    # hop for step s+1 flies under ALL of step s's m-tiles) at 2-chunk
    # VMEM cost.
    ck_out = ck_w[0] if transpose_w else ck_w[1]
    out_shape = (m, n * ck_out)

    def kernel(order_ref, x_ref, w_ref, o_ref, comm, send_sem,
               recv_sem, credit_sem):
        s = pl.program_id(0)
        i = pl.program_id(1)
        last_i = pl.num_programs(1) - 1
        my = jax.lax.axis_index(axis_name)
        right = jax.lax.rem(my + 1, n)
        left = jax.lax.rem(my + n - 1, n)
        cur = jax.lax.rem(s, 2)
        nxt = jax.lax.rem(s + 1, 2)

        def hop(src_slot, dst_slot):
            return pltpu.make_async_remote_copy(
                src_ref=comm.at[src_slot], dst_ref=comm.at[dst_slot],
                send_sem=send_sem.at[src_slot],
                recv_sem=recv_sem.at[dst_slot],
                device_id=right,
                device_id_type=pltpu.DeviceIdType.LOGICAL)

        @pl.when(i == 0)
        def _():
            @pl.when(s == 0)
            def _():
                comm[0] = w_ref[:]

            @pl.when(s > 0)
            def _():
                hop(nxt, cur).wait_recv()   # chunk c(s) has landed

            @pl.when(s < n - 1)
            def _():
                if not interpret:
                    @pl.when(s > 0)
                    def _():
                        # right neighbor recycled the slot we target
                        pltpu.semaphore_wait(credit_sem, 1)
                hop(cur, nxt).start()

        o_ref[:] = jax.lax.dot_general(
            x_ref[:], comm[cur], dnums,
            preferred_element_type=jnp.float32,
            precision=precision).astype(o_ref.dtype)

        @pl.when(jnp.logical_and(i == last_i, s < n - 1))
        def _():
            hop(cur, nxt).wait_send()
            if not interpret:
                @pl.when(s < n - 2)
                def _():
                    pltpu.semaphore_signal(
                        credit_sem, 1, device_id=left,
                        device_id_type=pltpu.DeviceIdType.LOGICAL)

    grid_spec = pltpu.PrefetchScalarGridSpec(
        num_scalar_prefetch=1,
        grid=(n, mt),
        in_specs=[
            pl.BlockSpec((tile, k_x), lambda s, i, order: (i, 0)),
            pl.BlockSpec(ck_w, lambda s, i, order: (0, 0)),
        ],
        out_specs=pl.BlockSpec((tile, ck_out),
                               lambda s, i, order: (i, order[s])),
        scratch_shapes=[
            pltpu.VMEM((2,) + ck_w, w_shard.dtype),
            pltpu.SemaphoreType.DMA((2,)),
            pltpu.SemaphoreType.DMA((2,)),
            pltpu.SemaphoreType.REGULAR,
        ])
    y = pl.pallas_call(
        kernel,
        grid_spec=grid_spec,
        out_shape=jax.ShapeDtypeStruct(out_shape, out_dtype),
        compiler_params=pltpu.CompilerParams(
            dimension_semantics=("arbitrary", "arbitrary"),
            collective_id=0),
        interpret=interpret)(order, x, w_shard)
    return y


def _mm_rs_fused(lhs, rhs, *, chunk_lhs, axis_name, n, tile_m, interpret,
                 precision=None):
    m = lhs.shape[0]
    tile = _divisor_tile(m, tile_m)
    mt = m // tile
    ck = (lhs.shape[1] if chunk_lhs else rhs.shape[1]) // n
    if chunk_lhs:
        out_shape = (ck, rhs.shape[1])
    else:
        out_shape = (lhs.shape[1], ck)
    dnums = (((0,), (0,)), ((), ()))

    def kernel(order_ref, lhs_ref, rhs_ref, o_ref, acc, comm,
               send_sem, recv_sem, credit_sem):
        s = pl.program_id(0)
        i = pl.program_id(1)
        last_i = pl.num_programs(1) - 1
        my = jax.lax.axis_index(axis_name)
        right = jax.lax.rem(my + 1, n)
        left = jax.lax.rem(my + n - 1, n)
        cur = jax.lax.rem(s, 2)
        nxt = jax.lax.rem(s + 1, 2)

        def hop(src_slot, dst_slot):
            return pltpu.make_async_remote_copy(
                src_ref=comm.at[src_slot], dst_ref=comm.at[dst_slot],
                send_sem=send_sem.at[src_slot],
                recv_sem=recv_sem.at[dst_slot],
                device_id=right,
                device_id_type=pltpu.DeviceIdType.LOGICAL)

        part = jax.lax.dot_general(
            lhs_ref[:], rhs_ref[:], dnums,
            preferred_element_type=jnp.float32, precision=precision)

        @pl.when(i == 0)
        def _():
            acc[:] = part

        @pl.when(i > 0)
        def _():
            acc[:] = acc[:] + part

        # the carry hop for step s flew while this step's tiles above
        # were multiplying — combine and forward only at the tail
        @pl.when(i == last_i)
        def _():
            @pl.when(s == 0)
            def _():
                comm[0] = acc[:]

            @pl.when(jnp.logical_and(s > 0, s < n - 1))
            def _():
                hop(nxt, cur).wait_recv()
                comm[cur] = comm[cur] + acc[:]

            @pl.when(s < n - 1)
            def _():
                if not interpret:
                    @pl.when(s > 0)
                    def _():
                        pltpu.semaphore_wait(credit_sem, 1)
                hop(cur, nxt).start()
                # the carry is small (1/n of the gather bytes): waiting
                # the send here, inside the step tail, keeps the 2-slot
                # credit accounting simple at the cost of overlapping
                # only the RECV side of the carry hop with step s+1
                hop(cur, nxt).wait_send()
                if not interpret:
                    @pl.when(s < n - 2)
                    def _():
                        pltpu.semaphore_signal(
                            credit_sem, 1, device_id=left,
                            device_id_type=pltpu.DeviceIdType.LOGICAL)

            @pl.when(s == n - 1)
            def _():
                hop(nxt, cur).wait_recv()
                o_ref[:] = comm[cur] + acc[:]

    if chunk_lhs:
        in_specs = [
            pl.BlockSpec((tile, ck), lambda s, i, order: (i, order[s])),
            pl.BlockSpec((tile, rhs.shape[1]), lambda s, i, order: (i, 0)),
        ]
    else:
        in_specs = [
            pl.BlockSpec((tile, lhs.shape[1]), lambda s, i, order: (i, 0)),
            pl.BlockSpec((tile, ck), lambda s, i, order: (i, order[s])),
        ]
    grid_spec = pltpu.PrefetchScalarGridSpec(
        num_scalar_prefetch=1,
        grid=(n, mt),
        in_specs=in_specs,
        out_specs=pl.BlockSpec(out_shape, lambda s, i, order: (0, 0)),
        scratch_shapes=[
            pltpu.VMEM(out_shape, jnp.float32),          # acc
            pltpu.VMEM((2,) + out_shape, jnp.float32),   # ring carry
            pltpu.SemaphoreType.DMA((2,)),
            pltpu.SemaphoreType.DMA((2,)),
            pltpu.SemaphoreType.REGULAR,
        ])
    idx = jax.lax.axis_index(axis_name)
    order = jax.lax.rem(idx - 1 - jnp.arange(n, dtype=jnp.int32) + 2 * n, n)
    return pl.pallas_call(
        kernel,
        grid_spec=grid_spec,
        out_shape=jax.ShapeDtypeStruct(out_shape, jnp.float32),
        compiler_params=pltpu.CompilerParams(
            dimension_semantics=("arbitrary", "arbitrary"),
            collective_id=1),
        interpret=interpret)(order, lhs, rhs)


# ---------------------------------------------------------------------------
# two-level link-aware lowering (ISSUE 16)
# ---------------------------------------------------------------------------
#
# The flat ring pays every hop equally; on a multi-host slice ni of the
# n ring edges are DCN-class, so a shard of c bytes costs an AVERAGE of
# (n-1)·c·ni/n slow-link bytes per device. The two-level schedule pays
# the slow links exactly once per operand: one lax.all_gather of the
# RAW resting shard over the inter axis ((ni-1)·c slow bytes), then ni
# per-block invocations of the flat dispatch over the intra axis — the
# existing lax/pallas lowerings (and their backend="auto" feasibility
# gates) serve each block unchanged. Block b of the full weight is the
# contiguous run of intra-ring shards from inter group b, because the
# split mesh is row-major (data index = inter_index·intra +
# intra_index) — so per-block results concatenate (non-contracting) or
# accumulate (contracting) in natural order and numerics match the
# flat ring to fp32 partial-sum ordering.

def _sub_cfg(cfg: CollectiveMatmulConfig, h: RingHierarchy):
    # the per-block intra ring runs with BOTH split axes bound in the
    # shard_map axis env, and pallas remote DMA (dma_start with LOGICAL
    # device ids) refuses a >1-named-axis env in this jax version — so
    # the intra hop always takes the lax decomposed ring; "fused" under
    # a hierarchy means fused-at-the-flat-level only
    return dataclasses.replace(cfg, axis_name=h.intra_axis,
                               axis_size=h.intra, hierarchy=None,
                               backend="lax")


def _hier_ag_matmul(x2, w_shard, *, h, shard_dim, contracting,
                    transpose_w, cfg, out_dtype, precision, site):
    ni, k = h.inter, h.intra
    sub = _sub_cfg(cfg, h)
    # ONE slow hop: the ni same-intra-position shards; stacked[b] is the
    # intra-position-t shard of full-weight block b
    stacked = jax.lax.all_gather(w_shard, h.inter_axis)
    _breadcrumb("all_gather_matmul", site, "two_level", fallback=None,
                m=int(x2.shape[0]), shard_shape=tuple(w_shard.shape),
                shard_dim=int(shard_dim), transpose_w=bool(transpose_w),
                contracting=bool(contracting), inter=ni, intra=k)
    if contracting:
        ck = w_shard.shape[1] if transpose_w else w_shard.shape[0]
        acc = None
        for b in range(ni):
            xs = jax.lax.slice_in_dim(x2, b * k * ck, (b + 1) * k * ck,
                                      axis=1)
            y = all_gather_matmul(xs, stacked[b], shard_dim=shard_dim,
                                  axis_name=h.intra_axis, axis_size=k,
                                  transpose_w=transpose_w, cfg=sub,
                                  out_dtype=jnp.float32,
                                  precision=precision,
                                  site=site + f"/blk{b}")
            acc = y if acc is None else acc + y
        return acc.astype(out_dtype)
    blocks = [all_gather_matmul(x2, stacked[b], shard_dim=shard_dim,
                                axis_name=h.intra_axis, axis_size=k,
                                transpose_w=transpose_w, cfg=sub,
                                out_dtype=out_dtype, precision=precision,
                                site=site + f"/blk{b}")
              for b in range(ni)]
    return jnp.concatenate(blocks, axis=1)


def _hier_mm_rs(l2, r2, *, h, shard_dim, cfg, precision, site):
    from deepspeed_tpu.parallel import overlap
    ni, k = h.inter, h.intra
    sub = _sub_cfg(cfg, h)
    chunk_lhs = shard_dim == 0
    _breadcrumb("matmul_reduce_scatter", site, "two_level", fallback=None,
                m=int(l2.shape[0]), k=int(l2.shape[1]),
                nn=int(r2.shape[1]), shard_dim=int(shard_dim),
                inter=ni, intra=k)
    blk = (l2.shape[1] if chunk_lhs else r2.shape[1]) // ni
    parts = []
    for b in range(ni):
        if chunk_lhs:
            ls = jax.lax.slice_in_dim(l2, b * blk, (b + 1) * blk, axis=1)
            p = matmul_reduce_scatter(ls, r2, shard_dim=0,
                                      axis_name=h.intra_axis, axis_size=k,
                                      cfg=sub, precision=precision,
                                      site=site + f"/blk{b}")
        else:
            rs = jax.lax.slice_in_dim(r2, b * blk, (b + 1) * blk, axis=1)
            p = matmul_reduce_scatter(l2, rs, shard_dim=1,
                                      axis_name=h.intra_axis, axis_size=k,
                                      cfg=sub, precision=precision,
                                      site=site + f"/blk{b}")
        parts.append(p)
    piece_shape = parts[0].shape
    stack = jnp.stack([p.reshape(-1) for p in parts])   # [ni, piece]
    # exact fp32 slow hop: device's inter index keeps its own block's
    # piece, summed over the ni host groups
    out = overlap.ring_reduce_scatter(stack.reshape(-1), h.inter_axis, ni)
    return out.reshape(piece_shape)


# ---------------------------------------------------------------------------
# dispatch
# ---------------------------------------------------------------------------

def _resolve(cfg: Optional[CollectiveMatmulConfig]):
    cfg = cfg or CollectiveMatmulConfig()
    backend = cfg.backend
    if backend == "auto":
        backend = "fused" if is_tpu_backend() else "lax"
    if backend not in ("fused", "lax"):
        raise ValueError(f"collective_matmul backend must be 'auto', "
                         f"'fused' or 'lax', got {cfg.backend!r}")
    interpret = cfg.interpret
    if interpret is None:
        interpret = not is_tpu_backend()
    return cfg, backend, bool(interpret)


def _as_2d(x):
    return x.reshape(-1, x.shape[-1])


def _ag_auto_fallback(cfg, shard_shape, itemsize, contracting, n,
                      interpret):
    """Why backend="auto" must route this all-gather+matmul through the
    lax ring instead of the pallas kernel, or None when the kernel is
    feasible. Pure (host ints only) so the gates are unit-testable off
    a TPU."""
    full_w_bytes = int(np.prod(shard_shape)) * n * itemsize
    if contracting and full_w_bytes > cfg.vmem_budget_bytes:
        # the contracting kernel stashes the full gathered W in VMEM
        # (interpret-safe accumulation; see _ag_matmul_fused)
        return "vmem_budget"
    if not contracting and 2 * (full_w_bytes // n) > cfg.vmem_budget_bytes:
        # the non-contracting kernel's ring carry is 2 chunk-sized comm
        # slots (the (2,)+ck_w VMEM scratch in _ag_matmul_fused)
        return "vmem_budget"
    if not interpret and (shard_shape[-1] % 128 or shard_shape[0] % 128):
        # Mosaic lane alignment: BOTH shard dims appear as a block
        # minor somewhere across the fwd/bwd kernel family (e.g. a
        # dim-0 shard's ck is the x-block minor in the contracting
        # forward and the output-block minor in the transposed dx) —
        # unaligned minors lower poorly or not at all on real hardware
        return "lane_alignment"
    return None


def _rs_auto_fallback(cfg, k, nn, chunk_lhs, n, interpret):
    """matmul+reduce-scatter twin of ``_ag_auto_fallback``: acc + the
    2 carry slots are all fp32 shard-sized VMEM scratch."""
    shard_bytes = (k // n) * nn * 4 if chunk_lhs else k * (nn // n) * 4
    if 3 * shard_bytes > cfg.vmem_budget_bytes:
        return "vmem_budget"
    # block minors: the chunked operand's ck and the un-chunked minor
    minors = (k // n, nn) if chunk_lhs else (k, nn // n)
    if not interpret and any(m % 128 for m in minors):
        return "lane_alignment"
    return None


def all_gather_matmul(x, w_shard, *, shard_dim, axis_name, axis_size,
                      transpose_w=False, cfg=None, out_dtype=None,
                      precision=None, site="unsited"):
    """``x @ W_full`` (or ``x @ W_full^T``) where ``W`` rests as this
    device's 1/n shard cut on ``shard_dim`` — the all-gather decomposed
    into ring chunks interleaved with the GEMM tiles they feed. Must
    run inside shard_map binding ``axis_name``. ``x``: [..., K]; output
    [..., N]. fp32 accumulation, output in ``out_dtype`` (default
    ``x.dtype``)."""
    out_dtype = out_dtype or x.dtype
    n = int(axis_size)
    lead = x.shape[:-1]
    x2 = _as_2d(x)
    if n == 1:
        dnums = (((1,), (1 if transpose_w else 0,)), ((), ()))
        y = jax.lax.dot_general(
            x2, w_shard, dnums, preferred_element_type=jnp.float32,
            precision=precision).astype(out_dtype)
        return y.reshape(lead + (y.shape[-1],))
    contracting = (shard_dim == 0) != bool(transpose_w)
    if cfg is not None and cfg.hierarchy is not None:
        h = cfg.hierarchy
        assert h.inter * h.intra == n, (h, n)
        y = _hier_ag_matmul(x2, w_shard, h=h, shard_dim=shard_dim,
                            contracting=contracting,
                            transpose_w=transpose_w, cfg=cfg,
                            out_dtype=out_dtype, precision=precision,
                            site=site)
        return y.reshape(lead + (y.shape[-1],))
    cfg, backend, interpret = _resolve(cfg)
    fallback = None
    if backend == "fused" and cfg.backend == "auto":
        # feasibility gates for the auto-chosen kernel lowering; a
        # forced backend="fused" is trusted (and will fail loudly)
        fallback = _ag_auto_fallback(cfg, tuple(w_shard.shape),
                                     jnp.dtype(w_shard.dtype).itemsize,
                                     contracting, n, interpret)
        if fallback:
            backend = "lax"
    _breadcrumb("all_gather_matmul", site, backend, fallback=fallback,
                m=int(x2.shape[0]), shard_shape=tuple(w_shard.shape),
                shard_dim=int(shard_dim), transpose_w=bool(transpose_w),
                contracting=bool(contracting), axis_size=n)
    if backend == "fused":
        y = _ag_matmul_fused(x2, w_shard, contracting=contracting,
                             transpose_w=transpose_w, axis_name=axis_name,
                             n=n, tile_m=cfg.tile_m, interpret=interpret,
                             out_dtype=out_dtype, precision=precision)
    else:
        y = _ag_matmul_lax(x2, w_shard, contracting=contracting,
                           transpose_w=transpose_w, axis_name=axis_name,
                           n=n, out_dtype=out_dtype, precision=precision)
    return y.reshape(lead + (y.shape[-1],))


def matmul_reduce_scatter(lhs, rhs, *, shard_dim, axis_name, axis_size,
                          cfg=None, precision=None, site="unsited"):
    """This device's shard of ``sum_over_axis(lhs^T @ rhs)`` — the
    param-grad GEMM fused with its ring reduce-scatter, partial
    accumulations ring-shifting between chunk GEMMs so the full
    product never materializes. ``lhs``: [..., K]; ``rhs``: [..., N];
    returns fp32 [K/n, N] (shard_dim 0) or [K, N/n] (shard_dim 1),
    SUMMED (not meaned) over the axis. Must run inside shard_map."""
    n = int(axis_size)
    l2, r2 = _as_2d(lhs), _as_2d(rhs)
    if n == 1:
        return jax.lax.dot_general(
            l2, r2, (((0,), (0,)), ((), ())),
            preferred_element_type=jnp.float32, precision=precision)
    chunk_lhs = shard_dim == 0
    if cfg is not None and cfg.hierarchy is not None:
        h = cfg.hierarchy
        assert h.inter * h.intra == n, (h, n)
        return _hier_mm_rs(l2, r2, h=h, shard_dim=shard_dim, cfg=cfg,
                           precision=precision, site=site)
    cfg, backend, interpret = _resolve(cfg)
    fallback = None
    if backend == "fused" and cfg.backend == "auto":
        fallback = _rs_auto_fallback(cfg, int(l2.shape[1]),
                                     int(r2.shape[1]), chunk_lhs, n,
                                     interpret)
        if fallback:
            backend = "lax"
    _breadcrumb("matmul_reduce_scatter", site, backend, fallback=fallback,
                m=int(l2.shape[0]), k=int(l2.shape[1]), nn=int(r2.shape[1]),
                shard_dim=int(shard_dim), axis_size=n)
    if backend == "fused":
        return _mm_rs_fused(l2, r2, chunk_lhs=chunk_lhs,
                            axis_name=axis_name, n=n, tile_m=cfg.tile_m,
                            interpret=interpret, precision=precision)
    return _mm_rs_lax(l2, r2, chunk_lhs=chunk_lhs, axis_name=axis_name,
                      n=n, precision=precision)


# ---------------------------------------------------------------------------
# the fused dense op (custom VJP): forward AG+matmul, backward
# matmul+RS for dW and transposed AG+matmul for dx
# ---------------------------------------------------------------------------

@functools.lru_cache(maxsize=None)
def _collective_matmul_fn(shard_dim, axis_name, axis_size, cfg, site,
                          precision=None):
    @jax.custom_vjp
    def f(x, w_shard):
        return all_gather_matmul(x, w_shard, shard_dim=shard_dim,
                                 axis_name=axis_name, axis_size=axis_size,
                                 cfg=cfg, precision=precision, site=site)

    def fwd(x, w_shard):
        return f(x, w_shard), (x, w_shard)

    def bwd(res, dy):
        x, w_shard = res
        # dx = dy @ W^T from the SAME resting shard (no transposed copy)
        dx = all_gather_matmul(dy, w_shard, shard_dim=shard_dim,
                               axis_name=axis_name, axis_size=axis_size,
                               transpose_w=True, cfg=cfg,
                               out_dtype=x.dtype, precision=precision,
                               site=site + "/dx")
        # dW shard = RS_axis(x^T @ dy): already reduce-scattered and
        # SUMMED over the axis (the caller normalizes to a mean), the
        # contract parallel/prefetch.py's sharded-leaf grads follow
        dw = matmul_reduce_scatter(x, dy, shard_dim=shard_dim,
                                   axis_name=axis_name,
                                   axis_size=axis_size, cfg=cfg,
                                   precision=precision,
                                   site=site + "/dw")
        return dx.reshape(x.shape), dw.astype(w_shard.dtype)

    f.defvjp(fwd, bwd)
    return f


def collective_matmul(x, w_shard, *, shard_dim, axis_name, axis_size,
                      cfg=None, precision=None, site="unsited"):
    """Differentiable fused dense op over a ZeRO-3 resting shard: the
    forward gathers W through the GEMM it feeds; the backward routes
    dW through matmul+reduce-scatter (returning the shard-shaped SUM
    over the axis — NOT the full gradient) and dx through a transposed
    all-gather+matmul. The param-grad contract matches the prefetch
    pipeline's sharded leaves (caller scales by 1/n for the mean)."""
    cfg = cfg or CollectiveMatmulConfig(axis_name=axis_name,
                                        axis_size=axis_size)
    return _collective_matmul_fn(int(shard_dim), axis_name,
                                 int(axis_size), cfg, site,
                                 precision)(x, w_shard)
