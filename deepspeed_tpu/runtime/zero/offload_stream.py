"""TPU-native ZeRO-Offload: optimizer state in device-host DRAM, update
streamed on device.

The reference's ZeRO-Offload (stage2.py:747-925 + csrc/adam/cpu_adam.cpp:21)
moves gradients over PCIe to the host, runs a SIMD Adam on host cores, and
copies updated params back — the right architecture when the accelerator
host has fat cores and the grads already cross PCIe for the NCCL reduction.
On TPU neither holds: XLA exposes the host DRAM *as a device memory space*
(``memory_kind="pinned_host"``), so the TPU-native realization of the same
memory shape — fp32 master + Adam moments in host DRAM, zero HBM resident
optimizer state — keeps the *step on the device* and streams the state
through HBM in bounded chunks:

    master/m/v (pinned_host) --DMA--> HBM chunk --VPU update--> back to
    pinned_host; bf16 params out to HBM for the next forward.

One step therefore moves 2x the state bytes over the device's host link
(PCIe-class) instead of running the update in host SIMD: the VPU applies
it at HBM bandwidth and no host library or core count is on the critical
path.

HBM discipline (the analog of the reference's tiled pinned-buffer bounds,
swap_tensor/optimizer_utils.py): state is stored pre-chunked — leaves whose
fp32 bytes exceed ``unit_bytes`` are split along their leading (layer) dim
into separate pinned_host arrays — and chunks are packed into per-program
groups of ≤ ``unit_bytes`` fp32 state, so one program's HBM staging is one
group's worth. Gradient leaves stay whole in HBM; each program slices its
units' windows on-device and the LAST program touching a leaf takes it
donated, so gradient HBM frees progressively as updated params accumulate.

Used by the engine when ``offload_optimizer.device == "cpu"`` and the
backend exposes a pinned_host memory space; the numpy/SIMD
`HostOffloadOptimizer` (offload.py) remains the NVMe tier and the explicit
``stream: "host"`` fallback.
"""

import dataclasses
from typing import List

import numpy as np
import jax
import jax.numpy as jnp
from jax.sharding import NamedSharding, PartitionSpec

from deepspeed_tpu.utils.logging import logger


def host_memory_kind(device=None):
    """The backend's host-side memory kind for resting optimizer state:
    ``pinned_host`` where the platform has a distinct DMA-able host space
    (TPU), else the backend's default kind (the XLA CPU backend collapses
    memory spaces — host IS device memory, exposed only as
    ``unpinned_host`` — so the streamed tier runs there with no-op moves
    and identical semantics). None when the backend reports nothing."""
    try:
        dev = device or jax.devices()[0]
        kinds = {m.kind for m in dev.addressable_memories()}
    except Exception:
        return None
    if "pinned_host" in kinds:
        return "pinned_host"
    return _default_memory_kind(device) or next(iter(sorted(kinds)), None)


def _default_memory_kind(device=None):
    try:
        dev = device or jax.devices()[0]
        return dev.default_memory().kind
    except Exception:
        return None


def backend_supports_offload_stream(device=None) -> bool:
    """True when the streamed tier can place its state somewhere the
    backend names — every current backend; kept as a guard for exotic
    PJRT plugins that report no memories at all."""
    return host_memory_kind(device) is not None


@dataclasses.dataclass(frozen=True)
class _Unit:
    """One streamed window: rows [start, stop) of leaf ``leaf`` (the whole
    leaf when the leaf is small or has no splittable leading dim)."""
    leaf: int
    start: int
    stop: int          # 0/0 for unsplit leaves

    @property
    def split(self):
        return self.stop > 0


class StreamedOffloadOptimizer:
    """Adam/AdamW with fp32 master + moments resident in pinned_host.

    Interface mirrors HostOffloadOptimizer where the engine touches it
    (``step_count``, ``params_tree``, ``state_dict``, ``load_state_dict``);
    the step itself is ``step(grad_leaves, lr, grad_scale, out_dtype)`` →
    updated compute-dtype param leaves resting in device memory.
    """

    def __init__(self, params, optimizer, mesh, partitioner,
                 unit_bytes: int = 512 * 1024 * 1024):
        from deepspeed_tpu.ops.adam import FusedAdam
        from deepspeed_tpu.ops.lamb import FusedLamb
        if isinstance(optimizer, FusedLamb) or \
                not isinstance(optimizer, FusedAdam):
            raise ValueError(
                "streamed offload supports Adam/AdamW (per-element update); "
                f"got {type(optimizer).__name__} — the host runner handles "
                "LAMB (whole-leaf trust ratios)")
        self.optimizer = optimizer
        self.mesh = mesh
        self.zero = partitioner
        self.step_count = 0
        dev0 = mesh.devices.flat[0]
        self.host_memory_kind = host_memory_kind(dev0)
        self.device_memory_kind = _default_memory_kind(dev0) or "device"
        if self.host_memory_kind is None:
            raise ValueError(
                "streamed offload: backend reports no addressable "
                "memories; use the host runner (stream='host')")
        self._mdtype = jnp.bfloat16 \
            if getattr(optimizer, "moment_dtype", "fp32") == "bf16" \
            else jnp.float32

        leaves, self.treedef = jax.tree_util.tree_flatten(params)
        self.shapes = [tuple(l.shape) for l in leaves]
        n = len(leaves)

        # per-leaf specs: opt state lives in the ZeRO opt sharding; params
        # rest in the param sharding. Memory-kind moves keep the spec fixed
        # (host<->HBM is a pure DMA); spec moves happen in device space.
        opt_spec_tree = partitioner.opt_param_like_specs(params)
        self.opt_specs = jax.tree_util.tree_leaves(
            opt_spec_tree, is_leaf=lambda x: isinstance(x, PartitionSpec))
        param_spec_tree = partitioner.param_specs(params)
        self.param_specs = jax.tree_util.tree_leaves(
            param_spec_tree, is_leaf=lambda x: isinstance(x, PartitionSpec))
        assert len(self.opt_specs) == n and len(self.param_specs) == n
        self.param_memory_kind = partitioner.param_memory_kind \
            or self.device_memory_kind

        # split big leaves along dim0 into units of <= unit_bytes fp32
        self.units: List[_Unit] = []
        for i, shape in enumerate(self.shapes):
            nbytes = int(np.prod(shape or (1,))) * 4
            d0 = shape[0] if shape else 1
            if nbytes <= unit_bytes or d0 <= 1 or \
                    self._spec_shards_dim0(self.opt_specs[i]):
                if nbytes > 2 * unit_bytes:
                    logger.warning(
                        f"streamed offload: leaf {i} {shape} "
                        f"({nbytes >> 20} MiB fp32) cannot be split along "
                        f"dim0; it streams as one window")
                self.units.append(_Unit(i, 0, 0))
                continue
            k = -(-nbytes // unit_bytes)          # ceil
            rows = -(-d0 // k)
            for s in range(0, d0, rows):
                self.units.append(_Unit(i, s, min(s + rows, d0)))

        # pack units into per-program groups of <= unit_bytes fp32 state
        self.groups: List[List[_Unit]] = []
        cur, cur_b = [], 0
        for u in self.units:
            b = self._unit_elems(u) * 4
            if cur and cur_b + b > unit_bytes:
                self.groups.append(cur)
                cur, cur_b = [], 0
            cur.append(u)
            cur_b += b
        if cur:
            self.groups.append(cur)
        # the last group touching each leaf takes its gradient donated
        self._last_group_of_leaf = {}
        for gi, g in enumerate(self.groups):
            for u in g:
                self._last_group_of_leaf[u.leaf] = gi

        # state storage: per-unit pinned_host arrays
        self.master: List = [None] * len(self.units)
        self.m: List = [None] * len(self.units)
        self.v: List = [None] * len(self.units)
        for gi, group in enumerate(self.groups):
            place = jax.jit(
                lambda *ls, us=tuple(group): tuple(
                    jax.device_put(l.astype(jnp.float32), self._host_sh(u))
                    for l, u in zip(ls, us)))
            placed = place(*[self._slice_leaf(leaves[u.leaf], u)
                             for u in group])
            zeros = jax.jit(
                lambda us=tuple(group): tuple(
                    (jax.device_put(
                        jnp.zeros(self._unit_shape(u), self._mdtype),
                        self._host_sh(u)),
                     jax.device_put(
                        jnp.zeros(self._unit_shape(u), jnp.float32),
                        self._host_sh(u))) for u in us))
            for u, arr, (zm, zv) in zip(group, placed, zeros()):
                ui = self.units.index(u)
                self.master[ui] = arr
                self.m[ui], self.v[ui] = zm, zv
        self._unit_index = {u: i for i, u in enumerate(self.units)}
        self._group_fns = {}
        logger.info(
            f"StreamedOffloadOptimizer: {n} leaves -> {len(self.units)} "
            f"stream units in {len(self.groups)} programs; moments "
            f"{'bf16' if self._mdtype == jnp.bfloat16 else 'fp32'} + fp32 "
            f"master resident in {self.host_memory_kind}")

    # -- unit geometry -----------------------------------------------------
    @staticmethod
    def _spec_shards_dim0(spec):
        entries = tuple(spec)
        return bool(entries) and entries[0] is not None

    def _unit_shape(self, u: _Unit):
        shape = self.shapes[u.leaf]
        if not u.split:
            return shape
        return (u.stop - u.start,) + shape[1:]

    def _unit_elems(self, u: _Unit):
        return int(np.prod(self._unit_shape(u) or (1,)))

    @staticmethod
    def _slice_leaf(leaf, u: _Unit):
        if not u.split:
            return leaf
        return jax.lax.slice_in_dim(leaf, u.start, u.stop, axis=0)

    def _host_sh(self, u: _Unit):
        return NamedSharding(self.mesh, self.opt_specs[u.leaf],
                             memory_kind=self.host_memory_kind)

    def _stage_sh(self, u: _Unit):
        return NamedSharding(self.mesh, self.opt_specs[u.leaf],
                             memory_kind=self.device_memory_kind)

    # -- the step ----------------------------------------------------------
    def _build_group_fn(self, gi, out_dtype):
        """One jitted program per group: device_put each unit's host state
        into HBM, apply Adam on the unit's on-device gradient window, write
        state back to pinned_host and emit the compute-dtype param chunk.
        Host state args are donated (in-place update semantics); gradient
        leaves are donated only in their last group."""
        opt = self.optimizer
        beta1, beta2 = opt.betas
        eps, wd = opt.eps, opt.weight_decay
        adam_w, bias_c = opt.adam_w_mode, opt.bias_correction
        group = self.groups[gi]
        g_leaves = sorted({u.leaf for u in group})
        g_pos = {l: k for k, l in enumerate(g_leaves)}
        donate_leaves = tuple(
            k + 3 for k, l in enumerate(g_leaves)
            if self._last_group_of_leaf[l] == gi)
        mdtype = self._mdtype

        def group_step(masters, ms, vs, *rest):
            grads = rest[:len(g_leaves)]
            lr, coef, count = rest[len(g_leaves):]
            cf = count.astype(jnp.float32)
            bc1 = 1.0 - beta1 ** cf if bias_c else jnp.float32(1.0)
            bc2 = 1.0 - beta2 ** cf if bias_c else jnp.float32(1.0)
            outs_p, outs_w, outs_m, outs_v = [], [], [], []
            for master, m, v, u in zip(masters, ms, vs, group):
                ss = self._stage_sh(u)
                p32 = jax.device_put(master, ss)
                m32 = jax.device_put(m, ss).astype(jnp.float32)
                v32 = jax.device_put(v, ss)
                g = self._slice_leaf(grads[g_pos[u.leaf]], u)
                g32 = jax.lax.with_sharding_constraint(
                    g.astype(jnp.float32), ss) * coef
                if wd != 0.0 and not adam_w:
                    g32 = g32 + wd * p32
                m_new = beta1 * m32 + (1.0 - beta1) * g32
                v_new = beta2 * v32 + (1.0 - beta2) * (g32 * g32)
                upd = (m_new / bc1) / (jnp.sqrt(v_new / bc2) + eps)
                if wd != 0.0 and adam_w:
                    upd = upd + wd * p32
                p_new = p32 - lr * upd
                outs_p.append(p_new.astype(out_dtype))
                outs_w.append(jax.device_put(p_new, self._host_sh(u)))
                outs_m.append(jax.device_put(m_new.astype(mdtype),
                                             self._host_sh(u)))
                outs_v.append(jax.device_put(v_new, self._host_sh(u)))
            return (tuple(outs_p), tuple(outs_w),
                    tuple(outs_m), tuple(outs_v))

        return jax.jit(group_step,
                       donate_argnums=(0, 1, 2) + donate_leaves)

    def _assemble_leaf(self, leaf_idx, chunks, out_dtype):
        """Reassemble a leaf's param from its unit chunks and move it to
        the resting param sharding (spec move in device space, memory-kind
        move as a same-spec DMA when the pinned-host param tier is on)."""
        dev_sh = NamedSharding(self.mesh, self.param_specs[leaf_idx],
                               memory_kind=self.device_memory_kind)
        key = (leaf_idx, jnp.dtype(out_dtype).name, len(chunks))
        fn = self._group_fns.get(("asm", key))
        if fn is None:
            def assemble(*cs):
                x = cs[0] if len(cs) == 1 else jnp.concatenate(cs, axis=0)
                x = jax.lax.with_sharding_constraint(x, dev_sh)
                if self.param_memory_kind != self.device_memory_kind:
                    x = jax.device_put(x, NamedSharding(
                        self.mesh, self.param_specs[leaf_idx],
                        memory_kind=self.param_memory_kind))
                return x
            fn = self._group_fns[("asm", key)] = jax.jit(
                assemble, donate_argnums=tuple(range(len(chunks))))
        return fn(*chunks)

    def step(self, grad_leaves, lr: float, grad_scale: float = 1.0,
             out_dtype=jnp.bfloat16):
        """Stream-update every group; returns new param leaves (device,
        ``out_dtype``). Programs dispatch back-to-back — JAX dispatch is
        async, so one group's host reads overlap the previous group's tail
        writes on the full-duplex host link."""
        self.step_count += 1
        n = len(self.shapes)
        assert len(grad_leaves) == n, (len(grad_leaves), n)
        lr = jnp.float32(lr)
        coef = jnp.float32(grad_scale)
        count = jnp.int32(self.step_count)
        chunks = [[] for _ in range(n)]
        new_params: List = [None] * n
        for gi, group in enumerate(self.groups):
            key = (gi, jnp.dtype(out_dtype).name)
            fn = self._group_fns.get(key)
            if fn is None:
                fn = self._group_fns[key] = self._build_group_fn(
                    gi, out_dtype)
            g_leaves = sorted({u.leaf for u in group})
            uis = [self._unit_index[u] for u in group]
            ps, ws, ms, vs = fn(
                tuple(self.master[ui] for ui in uis),
                tuple(self.m[ui] for ui in uis),
                tuple(self.v[ui] for ui in uis),
                *[grad_leaves[l] for l in g_leaves],
                lr, coef, count)
            for j, (u, ui) in enumerate(zip(group, uis)):
                chunks[u.leaf].append(ps[j])
                self.master[ui] = ws[j]
                self.m[ui] = ms[j]
                self.v[ui] = vs[j]
            for l in g_leaves:
                if self._last_group_of_leaf[l] == gi:
                    new_params[l] = self._assemble_leaf(
                        l, chunks[l], out_dtype)
                    chunks[l] = None
        return new_params

    # -- checkpoint interface (HostOffloadOptimizer parity) ----------------
    def _gather_leaf(self, store, leaf_idx, dtype):
        parts = [np.asarray(store[self._unit_index[u]])
                 for u in self.units if u.leaf == leaf_idx]
        full = parts[0] if len(parts) == 1 else np.concatenate(parts, axis=0)
        return np.asarray(full, dtype)

    def params_tree(self):
        return jax.tree_util.tree_unflatten(
            self.treedef,
            [self._gather_leaf(self.master, i, np.float32)
             for i in range(len(self.shapes))])

    def state_dict(self):
        n = len(self.shapes)
        return {
            "step": self.step_count,
            "exp_avg": jax.tree_util.tree_unflatten(
                self.treedef,
                [self._gather_leaf(self.m, i, np.float32) for i in range(n)]),
            "exp_avg_sq": jax.tree_util.tree_unflatten(
                self.treedef,
                [self._gather_leaf(self.v, i, np.float32) for i in range(n)]),
        }

    def load_state_dict(self, sd):
        self.step_count = int(np.asarray(sd["step"]))
        m = jax.tree_util.tree_leaves(sd["exp_avg"])
        v = jax.tree_util.tree_leaves(sd["exp_avg_sq"])
        for ui, u in enumerate(self.units):
            # place through a jit: eager device_put from numpy ALIASES the
            # numpy buffer on the CPU backend, and the step's donation of
            # an externally-owned buffer aborts the runtime
            place = jax.jit(
                lambda a, b, u=u: (
                    jax.device_put(a.astype(self._mdtype), self._host_sh(u)),
                    jax.device_put(b.astype(jnp.float32), self._host_sh(u))))
            mw = self._slice_np(np.asarray(m[u.leaf]), u)
            vw = self._slice_np(np.asarray(v[u.leaf]), u)
            self.m[ui], self.v[ui] = place(mw, vw)

    @staticmethod
    def _slice_np(arr, u: _Unit):
        return arr if not u.split else arr[u.start:u.stop]
