"""Cartesian process topology — API-parity rebuild of
deepspeed/runtime/pipe/topology.py:12-455.

On TPU the *communication* side of this file is obsolete — mesh axes replace
process groups (see mesh.py). What survives is the pure coordinate math:
rank ↔ (pipe, data, model) mapping used for checkpoint naming, stage
assignment and grid bookkeeping. `PipelineParallelGrid` keeps the reference's
accessor surface (get_stage_id, get_data_parallel_rank, …) but is backed by a
`jax.sharding.Mesh` when one is supplied.
"""

import dataclasses
import itertools
from collections import namedtuple

import numpy as np


class ProcessTopology:
    """Maps n-dim cartesian coordinates to linear ranks, axes major→minor.

    API parity with reference pipe/topology.py:12, but backed by a numpy
    rank grid the way `jax.sharding.Mesh` is backed by a devices ndarray:
    a coordinate lookup is an array index, a comm list is an axis slice,
    and a filter query is fancy indexing — no dict scans."""

    def __init__(self, axes, dims):
        self.axes = list(axes)
        self.dims = list(dims)
        self.ProcessCoord = namedtuple("ProcessCoord", axes)
        # C-order reshape gives the odometer rank numbering (last axis
        # fastest) that the reference's coordinate enumeration produced.
        self._grid = np.arange(int(np.prod(self.dims))).reshape(self.dims)

    def get_rank(self, **coord_kwargs):
        if set(coord_kwargs) != set(self.axes):
            raise ValueError("get_rank() does not support slices, use filter_match")
        idx = tuple(coord_kwargs[a] for a in self.axes)
        if any(not 0 <= i < d for i, d in zip(idx, self.dims)):
            raise AssertionError(f"key {coord_kwargs} invalid")
        return int(self._grid[idx])

    def get_axis_names(self):
        return self.axes

    def get_rank_repr(self, rank, omit_axes=("data", "pipe"), inner_sep="_", outer_sep="-"):
        """String used in checkpoint filenames (reference topology.py:87):
        e.g. mp_rank_00 style naming omits data/pipe axes."""
        coord = self.get_coord(rank)._asdict()
        return outer_sep.join(
            f"{ax}{inner_sep}{coord[ax]:02d}"
            for ax in self.axes if ax not in omit_axes)

    def get_dim(self, axis):
        if axis not in self.axes:
            return 0
        return self.dims[self.axes.index(axis)]

    def get_coord(self, rank):
        if not 0 <= rank < self._grid.size:
            raise ValueError(f"rank {rank} not found in topology.")
        return self.ProcessCoord(*map(int, np.unravel_index(rank, self.dims)))

    def get_axis_comm_lists(self, axis):
        """All groups of ranks that vary along ``axis`` with other coords
        fixed — the reference built process groups from these lists
        (topology.py:139). Here: move ``axis`` last and flatten the rest,
        so each row of the resulting matrix is one comm group."""
        if axis not in self.axes:
            return []
        rows = np.moveaxis(self._grid, self.axes.index(axis), -1)
        return rows.reshape(-1, self.get_dim(axis)).tolist()

    def filter_match(self, **filter_kwargs):
        """Ranks whose coords match all kwargs (reference topology.py:167),
        as a sorted list: index the grid with the fixed coordinates and
        flatten whatever remains. Unknown axis names raise (the dict-based
        original raised AttributeError); out-of-range values match nothing."""
        for axis, val in filter_kwargs.items():
            if axis not in self.axes:
                raise AttributeError(f"unknown topology axis {axis!r}; "
                                     f"have {self.axes}")
            if not 0 <= val < self.get_dim(axis):
                return []
        selector = tuple(
            filter_kwargs.get(a, slice(None)) for a in self.axes)
        return np.atleast_1d(self._grid[selector]).ravel().tolist()

    def get_axis_list(self, axis, idx):
        return self.filter_match(**{axis: idx})

    def world_size(self):
        return int(self._grid.size)

    @property
    def mapping(self):
        """coord→rank dict view (kept for repr/debug parity)."""
        return {self.get_coord(r): r for r in range(self.world_size())}

    def __str__(self):
        return str(self.mapping)


@dataclasses.dataclass(frozen=True)
class DataAxisHierarchy:
    """A two-level split of the mesh data axis for link-aware comm
    (ISSUE 10): ``inter`` slow-link groups (DCN-class hops between
    hosts/processes) of ``intra`` fast-link devices each (ICI-class hops
    inside a host). ``source`` records how the split was derived —
    ``"process"`` (real jax.distributed process boundaries) or
    ``"override"`` (the ``comm.hierarchy.slow_axis`` synthetic split for
    single-process testing)."""
    inter: int
    intra: int
    source: str


def data_axis_devices(mesh, data_axis="data"):
    """The device sequence along ``data_axis`` (other coordinates fixed
    at 0), in mesh order — the ordering the hierarchy split and the
    explicit ring programs both walk."""
    if data_axis not in mesh.axis_names:
        return []
    devs = np.moveaxis(mesh.devices,
                       list(mesh.axis_names).index(data_axis), 0)
    return list(devs.reshape(devs.shape[0], -1)[:, 0])


def derive_data_hierarchy(mesh, slow_axis=0, data_axis="data"):
    """Resolve the slow/fast split of ``mesh``'s data axis.

    ``slow_axis > 1`` forces a synthetic split into that many slow-link
    groups (single-process testing of the multi-host exchange — the
    config override); ``slow_axis`` 0 derives the split from the REAL
    process boundaries: the devices along the data axis must form
    contiguous, equal-sized, per-process blocks (what
    ``jax.distributed.initialize`` + a host-major mesh produce).

    Returns ``(DataAxisHierarchy, "")`` on success or ``(None, reason)``
    when no slow axis exists / the placement cannot be split — callers
    fall back loudly to the flat exchange."""
    n = mesh.shape.get(data_axis, 1) if hasattr(mesh, "shape") else 1
    if n <= 1:
        return None, f"data axis has size {n} (nothing to split)"
    if slow_axis and int(slow_axis) > 1:
        s = int(slow_axis)
        if n % s != 0:
            return None, (f"slow_axis override {s} does not divide the "
                          f"data axis size {n}")
        return DataAxisHierarchy(inter=s, intra=n // s,
                                 source="override"), ""
    procs = [getattr(d, "process_index", 0)
             for d in data_axis_devices(mesh, data_axis)]
    blocks = [(p, len(list(g))) for p, g in itertools.groupby(procs)]
    if len(blocks) <= 1:
        return None, ("single process on the data axis — no slow links "
                      "(set comm.hierarchy.slow_axis for a synthetic "
                      "split)")
    if len({p for p, _ in blocks}) != len(blocks):
        return None, ("process placement along the data axis is not "
                      "contiguous (a process's devices interleave with "
                      "another's)")
    if len({ln for _, ln in blocks}) != 1:
        return None, "uneven devices-per-process along the data axis"
    return DataAxisHierarchy(inter=len(blocks), intra=blocks[0][1],
                             source="process"), ""


# flat-fallback warning latch (ISSUE 16 satellite): callers of
# ``derive_data_hierarchy`` warn + drop a ``comm_hierarchy_fallback``
# breadcrumb when the split fails, and a caller that re-derives per
# step-build would flood the bounded flight-recorder ring with the same
# event. Latched process-wide per (axis, reason) — same shape as the
# router_block episode latch from the serving router.
_FALLBACK_LATCH = set()


def latch_fallback(axis, reason):
    """True exactly once per distinct (axis, reason) fallback; False on
    repeats. Callers gate their warning + breadcrumb on this."""
    key = (str(axis), str(reason))
    if key in _FALLBACK_LATCH:
        return False
    _FALLBACK_LATCH.add(key)
    return True


def _prime_factors(N):
    """Prime factorization in increasing order (reference topology.py:230)."""
    if N <= 0:
        raise ValueError("Factorization requires N > 0")
    primes = []
    while N % 2 == 0:
        primes.append(2)
        N //= 2
    p = 3
    while p * p <= N:
        while N % p == 0:
            primes.append(p)
            N //= p
        p += 2
    if N > 1:
        primes.append(N)
    return primes


class PipeDataParallelTopology(ProcessTopology):
    """Hybrid pipeline+data topology; DP innermost for intra-node allreduce
    bandwidth (reference topology.py:235)."""

    def __init__(self, num_pp, num_dp):
        super().__init__(axes=["pipe", "data"], dims=[num_pp, num_dp])


class PipeModelDataParallelTopology(ProcessTopology):
    """3D topology for DP×PP×TP (reference topology.py:246)."""

    def __init__(self, num_pp, num_mp, num_dp):
        super().__init__(axes=["pipe", "data", "model"], dims=[num_pp, num_dp, num_mp])


class PipelineParallelGrid:
    """Rank-bookkeeping for a hybrid grid — reference topology.py:252-455.

    The reference builds torch process groups here; on TPU the mesh axes carry
    the collectives, so this class only answers "who am I" queries. ``mesh``
    (optional) ties it to a real jax Mesh; ``process_id`` selects this
    process's coordinates (defaults to jax.process_index for multi-host)."""

    def __init__(self, topology=None, process_group=None, mesh=None,
                 world_size=None, global_rank=0):
        if topology is None:
            if mesh is not None:
                num_pp = mesh.shape.get("pipe", 1)
                num_mp = mesh.shape.get("model", 1)
                num_dp = (mesh.size // (num_pp * num_mp))
                topology = PipeModelDataParallelTopology(num_pp=num_pp,
                                                         num_mp=num_mp,
                                                         num_dp=num_dp)
            else:
                ws = world_size or 1
                topology = PipeDataParallelTopology(num_pp=1, num_dp=ws)
        self._topo = topology
        self.mesh = mesh
        self.global_rank = global_rank
        self.world_size = topology.world_size()

        self.data_parallel_size = max(self._topo.get_dim("data"), 1)
        self.pipe_parallel_size = max(self._topo.get_dim("pipe"), 1)
        self.model_parallel_size = max(self._topo.get_dim("model"), 1)
        assert self.world_size == (self.data_parallel_size * self.pipe_parallel_size
                                   * self.model_parallel_size)

        self.stage_id = self.get_stage_id()
        self.data_parallel_id = self.get_data_parallel_id()

        # p2p pair lists kept for schedule bookkeeping (reference
        # _build_p2p_groups topology.py:373); on TPU these become ppermute
        # source/dest index pairs over the pipe axis.
        self.p2p_matrix = self._build_p2p_pairs()

    def _build_p2p_pairs(self):
        pairs = []
        if self.pipe_parallel_size <= 1:
            return pairs
        for rank in range(self.world_size):
            coord = self._topo.get_coord(rank)
            stage = getattr(coord, "pipe", 0)
            next_stage = (stage + 1) % self.pipe_parallel_size
            kwargs = coord._asdict()
            kwargs["pipe"] = next_stage
            pairs.append((rank, self._topo.get_rank(**kwargs)))
        return pairs

    def get_stage_id(self):
        if "pipe" not in self._topo.get_axis_names():
            return 0
        return getattr(self._topo.get_coord(rank=self.global_rank), "pipe", 0)

    def get_data_parallel_id(self):
        if "data" not in self._topo.get_axis_names():
            return 0
        return getattr(self._topo.get_coord(rank=self.global_rank), "data", 0)

    # -- reference accessor surface (topology.py:395-455) ------------------
    def get_global_rank(self):
        return self.global_rank

    def get_pipe_parallel_rank(self):
        return self.get_stage_id()

    def get_pipe_parallel_world_size(self):
        return self.pipe_parallel_size

    def get_data_parallel_rank(self):
        return self.get_data_parallel_id()

    def get_data_parallel_world_size(self):
        return self.data_parallel_size

    def get_model_parallel_rank(self):
        if "model" not in self._topo.get_axis_names():
            return 0
        return getattr(self._topo.get_coord(rank=self.global_rank), "model", 0)

    def get_model_parallel_world_size(self):
        return self.model_parallel_size

    # mesh-era group accessors: return the axis name to use in collectives
    def get_pipe_parallel_group(self):
        return "pipe"

    def get_data_parallel_group(self):
        return "data"

    def get_model_parallel_group(self):
        return "model"

    def get_slice_parallel_group(self):
        # alias of model group, as in reference topology.py:455
        return "model"

    def topology(self):
        return self._topo

    def stage_to_global(self, stage_id, **kwargs):
        me = self._topo.get_coord(self.global_rank)
        transform = me._replace(pipe=stage_id, **kwargs)._asdict()
        return self._topo.get_rank(**transform)

    def is_first_stage(self):
        return self.stage_id == 0

    def is_last_stage(self):
        return self.stage_id == self.pipe_parallel_size - 1
