"""Sharded parameter construction — rebuild of
deepspeed/runtime/zero/partition_parameters.py:183-261,265 (`zero.Init`) and
:1002 (`GatheredParameters`).

The reference monkey-patches ``nn.Module.__init__`` / ``torch.empty`` so
parameters are partitioned the moment they are constructed — required
because eager torch would otherwise materialize the full model on one GPU.
On TPU the same guarantee comes from jitting the *initializer* with sharded
output: each device materializes only its shard of each parameter; the full
tensor never exists anywhere. No monkey-patching, no ds_tensor bookkeeping.

    with zero.Init(mesh=mesh, zero_stage=3):
        params = zero.Init.current().init(model, rng, example_input)

or functionally::

    params = sharded_init(model, rng, example, mesh, stage=3)

`GatheredParameters(params)` yields the fully-replicated tree (the
reference's allgather context for e.g. weight export) and re-shards on exit.
"""

import contextlib
from typing import Optional

import jax
from jax.sharding import NamedSharding, PartitionSpec

from deepspeed_tpu.runtime.zero.partition import ZeroPartitioner
from deepspeed_tpu.parallel import mesh as mesh_lib
from deepspeed_tpu.telemetry.spans import span, watch_compiles


@span("startup/sharded_init")
def sharded_init(model, rng, example_input, mesh, stage=3, tp_specs=None,
                 param_persistence_threshold=0, layer_stacked_prefixes=()):
    """Initialize a flax model with every parameter born sharded.

    Two-phase: ``jax.eval_shape`` discovers shapes without allocating, the
    partitioner assigns specs, then the real init runs under jit with those
    specs as out_shardings — XLA emits per-device shard initialization only.
    """
    import jax.numpy as jnp
    watch_compiles()
    example_input = jnp.asarray(example_input)

    shapes = jax.eval_shape(lambda r, x: model.init(r, x), rng, example_input)
    params_shapes = shapes["params"] if "params" in shapes else shapes
    part = ZeroPartitioner(
        mesh, stage, tp_specs=tp_specs,
        param_persistence_threshold=param_persistence_threshold,
        layer_stacked_prefixes=layer_stacked_prefixes)
    shardings = part.param_shardings(params_shapes)

    @jax.jit
    def _init(r, x):
        variables = model.init(r, x)
        return variables["params"] if "params" in variables else variables

    with mesh:
        init_fn = jax.jit(
            lambda r, x: _init(r, x), out_shardings=shardings)
        params = init_fn(rng, example_input)
    return params, shardings


class Init:
    """Context-manager shell for API parity with ``deepspeed.zero.Init``
    (partition_parameters.py:265). Inside the context, `init()` builds
    sharded params; the context itself carries the mesh/stage config."""

    _current: Optional["Init"] = None

    def __init__(self, module=None, mesh=None, zero_stage=3, tp_specs=None,
                 remote_device=None, pin_memory=False, config=None,
                 param_persistence_threshold=0, enabled=True):
        self.mesh = mesh
        self.zero_stage = zero_stage if enabled else 0
        self.tp_specs = tp_specs
        self.param_persistence_threshold = param_persistence_threshold
        self.enabled = enabled
        # reference accepts a module to convert eagerly; we defer to init()
        self.module = module
        self.shardings = None

    @classmethod
    def current(cls):
        return cls._current

    def __enter__(self):
        Init._current = self
        return self

    def __exit__(self, *exc):
        Init._current = None
        return False

    def init(self, model, rng, example_input):
        if not self.enabled or self.mesh is None:
            variables = model.init(rng, example_input)
            return variables.get("params", variables)
        params, self.shardings = sharded_init(
            model, rng, example_input, self.mesh, stage=self.zero_stage,
            tp_specs=self.tp_specs,
            param_persistence_threshold=self.param_persistence_threshold)
        return params


@contextlib.contextmanager
def GatheredParameters(params, mesh=None, modifier_rank=None, fwd_module=None,
                       enabled=True):
    """Yield the fully-gathered (replicated) parameter tree — reference
    partition_parameters.py:1002. Mutations inside the context are NOT
    propagated back (functional world); callers re-shard explicitly with
    `jax.device_put` if they want to adopt edits."""
    if not enabled:
        yield params
        return
    gathered = jax.tree_util.tree_map(
        lambda p: jax.device_get(p) if hasattr(p, "sharding") else p, params)
    yield gathered
