"""ZeRO stages 1/2/3 as GSPMD sharding rules.

The reference implements ZeRO as ~6,300 lines of imperative partition
bookkeeping (zero/stage1.py:57, stage2.py:68, stage3.py:581,
partition_parameters.py:450-545). On TPU the same *memory states* are
expressed declaratively and XLA inserts the collectives:

  stage 0: params, grads, optimizer state all replicated over the data axis
           (plain DP — grad psum).
  stage 1: optimizer state sharded over the data axis; grads replicated
           (all-reduce), each shard of the update computed locally, updated
           params all-gathered — exactly the reference's sub-partition
           scheme (stage1.py:305) with XLA choosing the bucketing.
  stage 2: + gradients sharded: the grad sharding constraint turns the
           backward all-reduce into reduce-scatter (+ all-gather of updated
           params) — the reference's IPG-bucket reduce-scatter
           (stage2.py:614-746).
  stage 3: + parameters sharded at rest. A layer's parameters reach the
           block's arithmetic through an explicit GATHER EDGE
           (`GatherEdge`, below): inside the rematted block each leaf
           that rests data-sharded is pinned to its resting spec with the
           data axis taken out, so the partitioner may choose only WHEN to
           all-gather the weight (the forward scan's body, and again the
           backward's recompute — the gathered copy is never a saved
           residual), never to keep the weight sharded and re-lay the
           activations instead. XLA schedules those gathers as async
           collectives under the neighbouring layer's compute — the
           reference's PartitionedParameterCoordinator prefetch
           (stage3.py:287-447). Nothing else is pinned: parameters
           outside the blocks (embeddings, final norm, head) and every
           explicit-comm (shard_map) step builder are left as they were.

Sharding choice per tensor: the largest dimension not already occupied by a
tensor-parallel axis, provided it divides by the data-axis size; otherwise
the tensor stays replicated (the analog of the reference's
`param_persistence_threshold` — small tensors aren't worth partitioning,
stage3.py constants ZERO_PARAM_PERSISTENCE_THRESHOLD). A layer-stacked leaf
(`[L, ...]` under one of `layer_stacked_prefixes`) is judged as the
reference judges it, one layer's parameter at a time: the threshold is
compared with `prod(shape[1:])`, and the layer dim is never the one sharded
(a scan slices it).
"""

from typing import Optional

import numpy as np
import jax
from jax.sharding import Mesh, NamedSharding, PartitionSpec

from deepspeed_tpu.parallel import mesh as mesh_lib


def plan_from_specs(leaves, specs, axis_name: str, n: int):
    """Per-leaf shard plan from a PartitionSpec tree: ``(dim, shard_size)``
    where ``dim`` (in the leaf's own coordinates) carries ``axis_name``,
    or None for leaves the spec leaves replicated over the axis — the
    contract of ``ZeroPartitioner.explicit_shard_plan``, usable on any
    params subtree."""
    plan = []
    for leaf, spec in zip(leaves, specs):
        entry = None
        for d, ax in enumerate(spec):
            axes = ax if isinstance(ax, tuple) else (ax,)
            if axis_name in axes:
                entry = (d, leaf.shape[d] // n)
                break
        plan.append(entry)
    return plan


def shard_spec_for_leaf(shape,
                        dp_size: int,
                        base_spec: Optional[PartitionSpec] = None,
                        min_size: int = 0,
                        axis_name: str = mesh_lib.DATA_AXIS,
                        layer_stacked: bool = False) -> PartitionSpec:
    """Extend ``base_spec`` (TP sharding) with a data-axis shard on the
    largest free, divisible dimension. Returns base_spec unchanged if no
    dimension qualifies or the tensor is below ``min_size`` elements.
    ``layer_stacked`` says dim 0 is the layer dim of a stacked leaf: it
    is never a candidate (a layer scan slices whole layers
    device-locally) and ``min_size`` is compared with ONE
    layer's elements, the unit the threshold was defined on."""
    base = tuple(base_spec) if base_spec is not None else ()
    base = base + (None,) * (len(shape) - len(base))
    first = 1 if layer_stacked else 0
    if dp_size <= 1 or \
            int(np.prod(shape[first:] or (1,))) < max(min_size, dp_size):
        return PartitionSpec(*base)
    # candidate dims: unsharded, divisible by dp, largest first
    candidates = sorted(
        (d for d in range(first, len(shape))
         if base[d] is None
         and shape[d] % dp_size == 0 and shape[d] >= dp_size),
        key=lambda d: shape[d], reverse=True)
    if not candidates:
        return PartitionSpec(*base)
    d = candidates[0]
    new = list(base)
    new[d] = axis_name
    return PartitionSpec(*new)


def _path_keys(path):
    """A tree path as a tuple of plain keys (dict key, index or attribute
    name), the form model code can rebuild from a module's scope path."""
    return tuple(
        getattr(p, "key", getattr(p, "idx", getattr(p, "name", None)))
        for p in path)


class GatherEdge:
    """The stage-3 gather edge: what a model's block needs in order to
    receive its parameters data-replicated (module docstring). ``specs``
    maps the path of every parameter leaf that rests data-sharded to its
    COMPUTE spec — the resting spec with the data axis taken out (tensor-
    and expert-parallel axes stay), and for a layer-stacked leaf without
    the layer dim, since the block sees one layer's slice. The engine
    hands the edge to the trace through ``mesh_lib.layout_pins``; model
    code calls it on a block's parameter subtree inside the remat."""

    def __init__(self, mesh, specs):
        self.mesh = mesh
        self.specs = specs
        # {block path: (leaves, gathered bytes a chip)} of the blocks the
        # current trace sent through the edge; the engine reads and
        # clears it after each compile (zero/gather_edge_* gauges)
        self.engaged = {}

    def __call__(self, path, tree):
        """Pin the leaves of ``tree`` (the parameter subtree at ``path``
        of the engine's params) that rest data-sharded to their compute
        spec; every other leaf is returned as it is."""
        path = tuple(path)
        leaves = nbytes = 0

        def pin(leaf_path, x):
            nonlocal leaves, nbytes
            spec = self.specs.get(path + _path_keys(leaf_path))
            if spec is None:
                return x
            sharding = NamedSharding(self.mesh, spec)
            leaves += 1
            nbytes += int(np.prod(sharding.shard_shape(x.shape))) \
                * x.dtype.itemsize
            return jax.lax.with_sharding_constraint(x, sharding)

        out = jax.tree_util.tree_map_with_path(pin, tree)
        if leaves:
            self.engaged[path] = (leaves, nbytes)
        return out


class ZeroPartitioner:
    """Produces NamedShardings for params / grads / optimizer state given the
    configured ZeRO stage. ``tp_specs`` is an optional pytree of
    PartitionSpec matching the params tree carrying tensor-parallel axes."""

    def __init__(self, mesh: Mesh, stage: int, tp_specs=None,
                 param_persistence_threshold: int = 0,
                 param_memory_kind=None, layer_stacked_prefixes=()):
        assert 0 <= stage <= 3
        self.mesh = mesh
        self.stage = stage
        self.tp_specs = tp_specs
        self.dp = mesh_lib.mesh_axis_size(mesh, mesh_lib.DATA_AXIS)
        self.min_size = int(param_persistence_threshold)
        # "pinned_host" = ZeRO-Offload/Infinity param tier: params rest in
        # host DRAM (reference offload_param, partitioned_param_swapper.py:36)
        # and stream to HBM inside the step via device_put
        self.param_memory_kind = param_memory_kind
        # top-level param-tree keys whose leaves are layer-stacked
        # ([L, ...]): judged one layer at a time (shard_spec_for_leaf's
        # ``layer_stacked``). The engine sets this from the model's
        # ``layer_stacked_subtree``
        self.layer_stacked_prefixes = tuple(layer_stacked_prefixes)

    # -- spec trees --------------------------------------------------------
    def _base_spec(self, path, leaf):
        if self.tp_specs is None:
            return None
        # tp_specs is a matching tree; fetch by path
        sub = self.tp_specs
        try:
            for key in _path_keys(path):
                sub = sub[key]
            return sub
        except (KeyError, TypeError, IndexError):
            return None

    def _layer_stacked(self, path):
        return bool(path) and \
            _path_keys(path[:1])[0] in self.layer_stacked_prefixes

    def _zero_spec(self, path, leaf):
        return shard_spec_for_leaf(leaf.shape, self.dp,
                                   self._base_spec(path, leaf),
                                   min_size=self.min_size,
                                   layer_stacked=self._layer_stacked(path))

    def _tp_only_spec(self, path, leaf):
        base = self._base_spec(path, leaf)
        base = tuple(base) if base is not None else ()
        base = base + (None,) * (len(leaf.shape) - len(base))
        return PartitionSpec(*base)

    def gather_edge(self, params) -> Optional[GatherEdge]:
        """The gather edge for this partitioning of ``params``, or None
        when no parameter rests data-sharded (stages 0-2, a data axis of
        one, or every leaf under the persistence threshold)."""
        if self.stage < 3 or self.dp <= 1:
            return None
        specs = {}
        for path, leaf in jax.tree_util.tree_flatten_with_path(params)[0]:
            rest = self._zero_spec(path, leaf)
            if mesh_lib.DATA_AXIS in jax.tree_util.tree_leaves(tuple(rest)):
                compute = tuple(self._tp_only_spec(path, leaf))
                if self._layer_stacked(path):
                    compute = compute[1:]
                specs[_path_keys(path)] = PartitionSpec(*compute)
        return GatherEdge(self.mesh, specs) if specs else None

    def param_specs(self, params):
        """Stage 3 shards params at rest; stages 0-2 keep them replicated
        (modulo TP axes)."""
        fn = self._zero_spec if self.stage >= 3 else self._tp_only_spec
        return jax.tree_util.tree_map_with_path(fn, params)

    def grad_specs(self, params):
        """Stage >=2: sharded grads (reduce-scatter); else same as params."""
        fn = self._zero_spec if self.stage >= 2 else self._tp_only_spec
        return jax.tree_util.tree_map_with_path(fn, params)

    def opt_param_like_specs(self, params):
        """Stage >=1: shard optimizer moments like stage-3 params."""
        fn = self._zero_spec if self.stage >= 1 else self._tp_only_spec
        return jax.tree_util.tree_map_with_path(fn, params)

    # -- sharding trees ----------------------------------------------------
    def _named(self, spec_tree):
        return jax.tree_util.tree_map(
            lambda s: NamedSharding(self.mesh, s), spec_tree,
            is_leaf=lambda x: isinstance(x, PartitionSpec))

    def param_shardings(self, params):
        """Resting shardings (host-memory-kind when the param offload tier
        is on)."""
        sh = self._named(self.param_specs(params))
        if self.param_memory_kind:
            sh = jax.tree_util.tree_map(
                lambda s: s.with_memory_kind(self.param_memory_kind), sh)
        return sh

    def device_param_shardings(self, params):
        """Compute-time shardings: always default (HBM) memory."""
        return self._named(self.param_specs(params))

    def grad_shardings(self, params):
        return self._named(self.grad_specs(params))

    def opt_state_shardings(self, opt_state, params, param_like_fields):
        """Build shardings for the optimizer-state dict: fields listed in
        ``param_like_fields`` mirror the param tree and get ZeRO specs;
        everything else (step counters, scalars) is replicated."""
        moment_shardings = self._named(self.opt_param_like_specs(params))
        out = {}
        for key, sub in opt_state.items():
            if key in param_like_fields:
                out[key] = moment_shardings
            else:
                out[key] = jax.tree_util.tree_map(
                    lambda _: NamedSharding(self.mesh, PartitionSpec()), sub)
        return out

    def explicit_shard_plan(self, params):
        """Per-leaf update ownership for the explicit-comm (shard_map)
        overlap train path: a list aligned with ``tree_leaves(params)`` of
        ``(dim, shard_size)`` — the data-axis dim the stage>=1 optimizer
        state shards over and the per-device extent — or ``None`` for
        leaves whose moments stay replicated (every device runs their full
        update redundantly, which is exact). Inside shard_map the owner
        device updates params[dim slice] with its local moment shard and
        the slices all-gather back (the stage-1/2 updated-param all-gather,
        stage2.py:~1470, made explicit)."""
        leaves = jax.tree_util.tree_leaves(params)
        spec_leaves = jax.tree_util.tree_leaves(
            self.opt_param_like_specs(params),
            is_leaf=lambda x: isinstance(x, PartitionSpec))
        return plan_from_specs(leaves, spec_leaves, mesh_lib.DATA_AXIS,
                               self.dp)

    def constrain_grads(self, grads):
        """Apply the stage>=2 reduce-scatter constraint inside the train step."""
        if self.stage < 2:
            return grads
        specs = self.grad_specs(grads)
        return jax.tree_util.tree_map(
            lambda g, s: jax.lax.with_sharding_constraint(
                g, NamedSharding(self.mesh, s)),
            grads, specs)
