"""deepspeed_tpu — a TPU-native large-model training framework.

A ground-up JAX/XLA/Pallas rebuild of the capabilities of 2021-era DeepSpeed
(reference: deepspeed/__init__.py:54 `initialize`, :203 `add_config_arguments`):
ZeRO-style partitioned data parallelism expressed as GSPMD sharding over a
`jax.sharding.Mesh`, pipeline + tensor + sequence parallelism over ICI,
host/NVMe offload through a native C++ async-IO tier, Pallas kernels for the
hot ops, and an engine/config/checkpoint stack mirroring the reference's user
API.

Typical use::

    import deepspeed_tpu as dstpu

    engine, _, loader, scheduler = dstpu.initialize(
        config="ds_config.json", model=model, training_data=data)
    for batch in loader:
        loss = engine.train_batch(batch)
"""

from deepspeed_tpu.version import __version__, git_hash, git_branch

from deepspeed_tpu.utils import logging as _logging

logger = _logging.logger

# The public surface resolves LAZILY (PEP 562): importing the bare
# package must not drag in jax — the stdlib-only tooling (the flight
# dump viewer `python -m deepspeed_tpu.telemetry.view`,
# ci/telemetry_gate.sh) runs on machines
# where jax does not exist, and tests/test_metric_names.py pins that
# with a poisoned-jax import. Everything below behaves exactly like
# the old eager imports: `dstpu.DeepSpeedEngine`, `dstpu.zero`,
# `from deepspeed_tpu import MeshConfig` all still work — the import
# just happens on first attribute access.
_LAZY_ATTRS = {
    "DeepSpeedConfig": ("deepspeed_tpu.config.config", "DeepSpeedConfig"),
    "DeepSpeedEngine": ("deepspeed_tpu.runtime.engine", "DeepSpeedEngine"),
    "add_tuning_arguments": ("deepspeed_tpu.runtime.lr_schedules",
                             "add_tuning_arguments"),
    "MeshConfig": ("deepspeed_tpu.parallel.mesh", "MeshConfig"),
    "make_mesh": ("deepspeed_tpu.parallel.mesh", "make_mesh"),
    "init_distributed": ("deepspeed_tpu.parallel.mesh",
                         "init_distributed"),
    "PipelineModule": ("deepspeed_tpu.runtime.pipe.module",
                       "PipelineModule"),
    "LayerSpec": ("deepspeed_tpu.runtime.pipe.module", "LayerSpec"),
    "TiedLayerSpec": ("deepspeed_tpu.runtime.pipe.module",
                      "TiedLayerSpec"),
    # subpackages the old root bound (eager imports made even
    # `deepspeed_tpu.config` / `.parallel` reachable as attributes)
    "config": ("deepspeed_tpu.config", None),
    "parallel": ("deepspeed_tpu.parallel", None),
    "utils": ("deepspeed_tpu.utils", None),
    "elasticity": ("deepspeed_tpu.elasticity", None),
    "module_inject": ("deepspeed_tpu.module_inject", None),
    "ops": ("deepspeed_tpu.ops", None),
    "models": ("deepspeed_tpu.models", None),
    "zero": ("deepspeed_tpu.runtime.zero", None),
    "runtime": ("deepspeed_tpu.runtime", None),
    "serving": ("deepspeed_tpu.serving", None),
    "telemetry": ("deepspeed_tpu.telemetry", None),
}

from deepspeed_tpu.utils.lazy import lazy_attrs  # noqa: E402

__getattr__, __dir__ = lazy_attrs(__name__, _LAZY_ATTRS)


def initialize(args=None,
               model=None,
               optimizer=None,
               model_parameters=None,
               training_data=None,
               lr_scheduler=None,
               mesh=None,
               mpu=None,
               dist_init_required=None,
               collate_fn=None,
               config=None,
               config_params=None,
               rng=None,
               loss_fn=None):
    """Initialize the engine — mirrors ``deepspeed.initialize``
    (reference deepspeed/__init__.py:54).

    Arguments:
        args: optional argparse namespace carrying ``deepspeed_config``.
        model: a flax ``nn.Module`` (or any object with ``.init``/``.apply``)
            or a :class:`~deepspeed_tpu.runtime.pipe.module.PipelineModule`.
        optimizer: optional pre-built optimizer (an optax-style gradient
            transform); overrides the config's optimizer section.
        model_parameters: optional pre-initialized parameter pytree; if
            omitted the engine initializes parameters from ``rng``.
        training_data: optional dataset (anything indexable / iterable).
        lr_scheduler: optional schedule fn ``step -> lr`` overriding config.
        mesh: optional ``jax.sharding.Mesh``; built from config if omitted.
        mpu: model-parallelism "unit" for parity with the reference
            (engine.py:636-641) — an object exposing axis sizes; superseded
            by ``mesh`` on TPU.
        config: path to a JSON config, a dict, or a DeepSpeedConfig.
        config_params: legacy alias for ``config``.
        rng: optional ``jax.random.PRNGKey`` used for parameter init.

    Returns:
        A tuple ``(engine, optimizer, training_dataloader, lr_scheduler)``
        exactly like the reference.
    """
    # start-up's timeline (telemetry/spans.py): every compile of the
    # process named from here on, and the whole of this call one span —
    # config parse, mesh, optimizer, and the state's placement when
    # ``model_parameters`` are handed in
    from deepspeed_tpu.telemetry.spans import span, watch_compiles
    watch_compiles()
    with span("startup/engine_init"):
        return _initialize(args, model, optimizer, model_parameters,
                           training_data, lr_scheduler, mesh, mpu,
                           collate_fn, config, config_params, rng, loss_fn)


def _initialize(args, model, optimizer, model_parameters, training_data,
                lr_scheduler, mesh, mpu, collate_fn, config, config_params,
                rng, loss_fn):
    # local imports: global-name lookup inside a function bypasses the
    # module-level lazy __getattr__, and initialize() is where the
    # heavy (jax-importing) machinery genuinely becomes necessary
    from deepspeed_tpu.config.config import DeepSpeedConfig
    from deepspeed_tpu.runtime.engine import DeepSpeedEngine
    from deepspeed_tpu.runtime.pipe.module import PipelineModule
    from deepspeed_tpu.runtime.pipe.engine import PipelineEngine

    if config is None:
        config = config_params
    if config is None and args is not None:
        config = getattr(args, "deepspeed_config", None)
    if config is None:
        raise ValueError(
            "DeepSpeed requires --deepspeed_config to specify configuration file")

    # ZeRO-Infinity segment-streamed engine: params + optimizer state
    # larger than HBM, streamed per layer-segment (offload_param
    # stream_segments > 0 — runtime/zero/infinity.py). Peek at the RAW
    # dict — a full DeepSpeedConfig parse here would validate the batch
    # triangle against the default world_size=1 and reject multi-chip
    # configs the engine itself parses correctly with the dp world size.
    if isinstance(config, DeepSpeedConfig):
        segs = getattr(config.zero_config.offload_param,
                       "stream_segments", 0)
    else:
        import json as _json
        raw = config if isinstance(config, dict) else _json.load(
            open(config))
        segs = int(raw.get("zero_optimization", {})
                   .get("offload_param", {}).get("stream_segments", 0))
    if segs:
        unsupported = {
            "optimizer": optimizer, "training_data": training_data,
            "lr_scheduler": lr_scheduler, "mpu": mpu,
            "collate_fn": collate_fn, "loss_fn": loss_fn}
        bad = [k for k, v in unsupported.items() if v is not None]
        if bad:
            raise ValueError(
                "offload_param.stream_segments selects the ZeRO-Infinity "
                f"segment-streamed engine, which does not accept {bad}; "
                "it builds its Adam/AdamW step and tied-LM loss from the "
                "config (runtime/zero/infinity.py)")
        from deepspeed_tpu.runtime.zero.infinity import InfinityEngine
        parsed = config if isinstance(config, DeepSpeedConfig) \
            else DeepSpeedConfig(config)
        engine = InfinityEngine.from_config(
            model, parsed, model_parameters=model_parameters,
            device=mesh.devices.flat[0] if mesh is not None else None)
        return engine, engine.optimizer, engine.training_dataloader, \
            engine.lr_scheduler

    engine_cls = DeepSpeedEngine
    if isinstance(model, PipelineModule):
        engine_cls = PipelineEngine

    engine = engine_cls(args=args,
                        model=model,
                        optimizer=optimizer,
                        model_parameters=model_parameters,
                        training_data=training_data,
                        lr_scheduler=lr_scheduler,
                        mesh=mesh,
                        mpu=mpu,
                        collate_fn=collate_fn,
                        config=config,
                        rng=rng,
                        loss_fn=loss_fn)

    return engine, engine.optimizer, engine.training_dataloader, engine.lr_scheduler


def add_config_arguments(parser):
    """Add ``--deepspeed``/``--deepspeed_config`` CLI flags — parity with
    reference deepspeed/__init__.py:160-201."""
    group = parser.add_argument_group("DeepSpeed", "DeepSpeed configurations")
    group.add_argument("--deepspeed",
                       default=False,
                       action="store_true",
                       help="Enable DeepSpeed (helper flag to ease transition)")
    group.add_argument("--deepspeed_config",
                       default=None,
                       type=str,
                       help="DeepSpeed json configuration file.")
    group.add_argument("--deepscale",
                       default=False,
                       action="store_true",
                       help="Deprecated alias of --deepspeed")
    group.add_argument("--deepscale_config",
                       default=None,
                       type=str,
                       help="Deprecated alias of --deepspeed_config")
    return parser
