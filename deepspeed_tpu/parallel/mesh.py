"""Device mesh construction — the TPU-native communication substrate.

Replaces the reference's process-group machinery (torch.distributed/NCCL init
in deepspeed/utils/distributed.py:12-142 and the group building in
deepspeed/runtime/pipe/topology.py:252-455). On TPU every collective is an
axis-scoped XLA op over a `jax.sharding.Mesh`; "creating a process group"
becomes naming a mesh axis.

Canonical axis order (outer→inner): ``('pipe', 'data', 'seq', 'model')`` —
pipe outermost so stages land on contiguous sub-slices (cheap DCN hops between
stages, fat ICI inside a stage for data/model collectives), matching the
reference's topology axis order ['pipe','data','model']
(pipe/topology.py:246).
"""

import dataclasses
from typing import Optional, Sequence

import numpy as np
import jax
from jax.sharding import (AxisType, Mesh, NamedSharding, PartitionSpec,
                          get_abstract_mesh)

# Mesh axis names. ZeRO shards over DATA_AXIS; tensor parallelism over
# MODEL_AXIS; pipeline stages over PIPE_AXIS; ring-attention/sequence
# parallelism over SEQ_AXIS; MoE experts over EXPERT_AXIS (a dedicated
# axis when MeshConfig.expert > 1, else experts alias onto data).
PIPE_AXIS = "pipe"
DATA_AXIS = "data"
SEQ_AXIS = "seq"
MODEL_AXIS = "model"
EXPERT_AXIS = "expert"

AXIS_ORDER = (PIPE_AXIS, DATA_AXIS, EXPERT_AXIS, SEQ_AXIS, MODEL_AXIS)

# The hierarchical comm split (ISSUE 10): the data axis factored at the
# host/process boundary into a slow DCN-class outer axis and a fast
# ICI-class inner axis. Only the explicit-comm train programs see these
# names (split_data_axis below); state at rest stays on DATA_AXIS.
DATA_INTER_AXIS = "data_inter"
DATA_INTRA_AXIS = "data_intra"


def in_manual_region() -> bool:
    """True when the current trace sits inside an explicit-comm region
    (shard_map Manual axes). Model layout pins must not apply there — the
    data is already device-local."""
    return any(t == AxisType.Manual for t in get_abstract_mesh().axis_types)


_current_mesh: Optional[Mesh] = None


def set_current_mesh(mesh: Optional[Mesh]):
    """Engine-scoped mesh registry: model code (e.g. ring attention inside
    SelfAttention) can discover the active mesh without threading it through
    flax module attributes."""
    global _current_mesh
    _current_mesh = mesh


def current_mesh() -> Optional[Mesh]:
    return _current_mesh


# pin scopes are PER-THREAD: two engines tracing concurrently from
# different threads must not cross-contaminate each other's pin state
# (the registries above stay process-global by design — a mesh is not
# thread-scoped, a trace is)
import threading

_pin_state = threading.local()


def _pins_disabled_count():
    return getattr(_pin_state, "disabled", 0)


def _get_pin_mesh():
    return getattr(_pin_state, "mesh", None)


class layout_pins:
    """Engine-scoped activation of the models' GSPMD layout pins
    (with_sharding_constraint on param/grad edges, e.g. the wpe slice and
    wte-scatter pins in models/gpt2.py). The pins must NOT read the
    ambient mesh registry: set_current_mesh outlives its engine, and a
    later single-device jit tracing the model with a constraint over a
    stale multi-device mesh crashes XLA's CPU compiler (the r4
    full-suite Fatal abort — order-dependent, invisible in isolation).
    Engines enter this around every jitted call with THEIR mesh; any
    trace outside an engine gets no pins. Re-entrant; inner-most wins.
    ``gather_edge`` is the engine's ZeRO-3 gather edge
    (runtime/zero/partition.GatherEdge) or None: it rides the same scope
    so the models' blocks pin their parameters with the same lifetime
    and the same off-switch as every other pin. ``remat_free_bytes``
    rides it too: what the engine says a chip has left for the names a
    rematted block keeps (``runtime/remat_budget.py``; 0: the base set)."""

    def __init__(self, mesh, gather_edge=None, remat_free_bytes=0):
        self.mesh = mesh
        self.gather_edge = gather_edge
        self.remat_free_bytes = remat_free_bytes
        self._prev = None

    def __enter__(self):
        self._prev = (_get_pin_mesh(), getattr(_pin_state, "edge", None),
                      pinned_remat_free_bytes())
        _pin_state.mesh = self.mesh
        _pin_state.edge = self.gather_edge
        _pin_state.remat_free_bytes = self.remat_free_bytes
        return self

    def __exit__(self, *exc):
        (_pin_state.mesh, _pin_state.edge,
         _pin_state.remat_free_bytes) = self._prev
        return False


def pinned_remat_free_bytes():
    """The pinned trace's free bytes for kept names; 0 outside an engine's
    trace."""
    return getattr(_pin_state, "remat_free_bytes", 0)


def pinned_mesh():
    """Mesh for model layout pins, or None outside an engine-pinned
    trace (or when pins are disabled for explicit-comm programs)."""
    if _pins_disabled_count() > 0:
        return None
    return _get_pin_mesh()


def pinned_gather_edge():
    """The pinned trace's ZeRO-3 gather edge, or None wherever
    ``pinned_mesh()`` is None, inside an explicit-comm (Manual axes)
    region, or when the engine has no leaf to gather."""
    if pinned_mesh() is None or in_manual_region():
        return None
    return getattr(_pin_state, "edge", None)


class no_layout_pins:
    """Context manager disabling the models' GSPMD layout pins
    (with_sharding_constraint on param/grad edges) while an engine traces
    an EXPLICIT-COMM program (shard_map, Manual axes). Inside shard_map
    the data is already device-local, so the pins are meaningless — and a
    NamedSharding built over the global (Auto-axis) mesh poisons avals in
    ways trace-context sniffing cannot reliably detect: custom_vjp
    backwards re-trace under whatever mesh context is live at transpose
    time (sometimes empty, sometimes the Auto mesh), so the ENGINE —
    which knows which kind of program it is building — is the only
    authoritative source. Re-entrant."""

    def __enter__(self):
        _pin_state.disabled = _pins_disabled_count() + 1
        return self

    def __exit__(self, *exc):
        _pin_state.disabled = _pins_disabled_count() - 1
        return False


def layout_pins_disabled() -> bool:
    return _pins_disabled_count() > 0


def init_distributed(coordinator_address: Optional[str] = None,
                     num_processes: Optional[int] = None,
                     process_id: Optional[int] = None,
                     auto_mpi_discovery: bool = True):
    """Multi-host initialization — parity with reference
    deepspeed/utils/distributed.py:12. Full resolution order (launcher env
    contract, generic env, MPI discovery) lives in utils/distributed.py;
    single-process is a no-op."""
    from deepspeed_tpu.utils.distributed import init_distributed as _init
    _init(coordinator_address=coordinator_address,
          num_processes=num_processes,
          process_id=process_id,
          auto_mpi_discovery=auto_mpi_discovery)


@dataclasses.dataclass
class MeshConfig:
    """Logical parallelism degrees. ``data=-1`` absorbs the remaining devices.

    The product pipe*data*seq*model must equal the device count (after -1
    resolution)."""
    data: int = -1
    model: int = 1
    pipe: int = 1
    seq: int = 1
    expert: int = 1

    def resolve(self, n_devices: int) -> "MeshConfig":
        explicit = self.model * self.pipe * self.seq * self.expert
        data = self.data
        if data == -1:
            assert n_devices % explicit == 0, (
                f"device count {n_devices} not divisible by "
                f"pipe*expert*seq*model={explicit}")
            data = n_devices // explicit
        total = data * explicit
        assert total == n_devices, (
            f"mesh {self.pipe}x{data}x{self.expert}x{self.seq}x"
            f"{self.model} != {n_devices} devices")
        return MeshConfig(data=data, model=self.model, pipe=self.pipe,
                          seq=self.seq, expert=self.expert)


def make_mesh(config: Optional[MeshConfig] = None,
              devices: Optional[Sequence] = None,
              axis_order: Sequence[str] = AXIS_ORDER) -> Mesh:
    """Build the global device mesh.

    ``jax.experimental.mesh_utils.create_device_mesh`` lines the logical
    mesh up with the physical ICI torus; a failure there raises. CPU
    devices have no topology, so test meshes are a plain reshape.
    """
    if devices is None:
        devices = jax.devices()
    config = (config or MeshConfig()).resolve(len(devices))
    shape = tuple({
        PIPE_AXIS: config.pipe,
        DATA_AXIS: config.data,
        EXPERT_AXIS: config.expert,
        SEQ_AXIS: config.seq,
        MODEL_AXIS: config.model,
    }[a] for a in axis_order)
    if devices[0].platform == "cpu":
        dev_array = np.asarray(devices).reshape(shape)  # sync-ok: host device list
    else:
        from jax.experimental import mesh_utils
        dev_array = mesh_utils.create_device_mesh(shape, devices=list(devices))
    return Mesh(dev_array, axis_names=tuple(axis_order))


def split_data_axis(mesh: Mesh, inter: int) -> Mesh:
    """Mesh with the data axis factored into ``(data_inter, data_intra)``
    — same devices in the same order (row-major split, so the ``intra``
    fast-axis neighbors are the devices that were contiguous along the
    original data axis: one host's local devices when the data axis is
    laid out host-major). Resharding an array between the two meshes is
    metadata-only — no device ever changes which elements it holds."""
    names = list(mesh.axis_names)
    di = names.index(DATA_AXIS)
    n = mesh.devices.shape[di]
    assert inter > 0 and n % inter == 0, (
        f"data axis {n} not divisible by inter={inter}")
    shape = list(mesh.devices.shape)
    shape[di:di + 1] = [inter, n // inter]
    names[di:di + 1] = [DATA_INTER_AXIS, DATA_INTRA_AXIS]
    return Mesh(mesh.devices.reshape(shape), tuple(names))


def single_device_mesh() -> Mesh:
    return Mesh(np.asarray(jax.devices()[:1]).reshape(  # sync-ok: host device list
        (1,) * len(AXIS_ORDER)), AXIS_ORDER)


def mesh_axis_size(mesh: Mesh, axis: str) -> int:
    return mesh.shape[axis] if axis in mesh.axis_names else 1


def dp_world_size(mesh: Optional[Mesh]) -> int:
    if mesh is None:
        return 1
    return mesh_axis_size(mesh, DATA_AXIS) * mesh_axis_size(mesh, EXPERT_AXIS)


def replicated(mesh: Mesh) -> NamedSharding:
    return NamedSharding(mesh, PartitionSpec())


def batch_sharding(mesh: Mesh) -> NamedSharding:
    """Input batches shard dim 0 over (data, expert) — dp_world_size counts
    both, so a dedicated expert axis carries its share of the batch instead
    of replicating non-MoE compute — and dim 1 over the seq axis when one
    exists."""
    dim0 = (DATA_AXIS, EXPERT_AXIS) \
        if mesh_axis_size(mesh, EXPERT_AXIS) > 1 else DATA_AXIS
    if mesh_axis_size(mesh, SEQ_AXIS) > 1:
        return NamedSharding(mesh, PartitionSpec(dim0, SEQ_AXIS))
    return NamedSharding(mesh, PartitionSpec(dim0))
