"""DeepSpeedEngine — TPU-native rebuild of deepspeed/runtime/engine.py:102.

The reference engine wraps a mutable torch module and drives
forward/backward/step imperatively, hand-scheduling collectives. Here the
engine owns a functional **TrainState** (params / optimizer state / loss-scale
state) sharded over a `jax.sharding.Mesh`, and one jitted, donated
**train step** that fuses: micro-batch gradient accumulation (lax.scan over
the reference's GAS loop, engine.py:985-1092), ZeRO grad reduce-scatter
(stage2.py:614-746 → a sharding constraint), overflow check + dynamic loss
scaling (fp16/loss_scaler.py:79), global-norm clipping (runtime/utils.py
clip_grad_norm_), the optimizer update, and updated-param all-gather
(stage2.py:~1470 → param sharding constraint).

API parity: `train_batch`, `forward`/`backward`/`step` (emulated over the
functional core, same call pattern as the reference loop, engine.py:1005,
1077, 1234), `save_checkpoint`/`load_checkpoint` (engine.py:1562-1891),
`is_gradient_accumulation_boundary` (engine.py:975).
"""

import functools
import inspect
import os
import time
from typing import Any, Callable, Optional

import numpy as np
import jax
import jax.numpy as jnp
import flax.struct
from jax.sharding import NamedSharding, PartitionSpec

from deepspeed_tpu.config.config import DeepSpeedConfig
from deepspeed_tpu.config import constants as C
from deepspeed_tpu.parallel import mesh as mesh_lib
from deepspeed_tpu.runtime import precision as prec
from deepspeed_tpu.runtime.dataloader import DeepSpeedDataLoader, RepeatingLoader
from deepspeed_tpu.runtime.lr_schedules import get_lr_schedule, _Schedule
from deepspeed_tpu.runtime.zero.partition import ZeroPartitioner
from deepspeed_tpu.runtime.progressive_layer_drop import ProgressiveLayerDrop
from deepspeed_tpu.ops.adam import FusedAdam, Adam, DeepSpeedCPUAdam
from deepspeed_tpu.ops.lamb import FusedLamb
from deepspeed_tpu.ops.sgd import SGD
from deepspeed_tpu.ops.optimizer import TpuOptimizer, OptaxOptimizer
from deepspeed_tpu.utils.logging import logger, log_dist
from deepspeed_tpu.utils.timer import SynchronizedWallClockTimer, ThroughputTimer
from deepspeed_tpu.utils.memory import see_memory_usage
from deepspeed_tpu.telemetry.anomaly import Watchdog
from deepspeed_tpu.telemetry.recorder import default_recorder
from deepspeed_tpu.telemetry.registry import default_registry
from deepspeed_tpu.runtime.elastic import faults as _faults
from deepspeed_tpu.telemetry.spans import span as tel_span, annotate, \
    TraceWindow

FORWARD_MICRO_TIMER = "forward_microstep"
BACKWARD_MICRO_TIMER = "backward_microstep"
STEP_MICRO_TIMER = "step_microstep"
FORWARD_GLOBAL_TIMER = "forward"
BACKWARD_GLOBAL_TIMER = "backward"
STEP_GLOBAL_TIMER = "step"
# cost of one scalar readback of an already-computed value, measured by
# the instrumented mode and reported so phase times can be read net of it
FENCE_TIMER = "fence"


@flax.struct.dataclass
class TrainState:
    params: Any
    opt_state: Any
    scaler: Any
    global_step: jax.Array            # optimizer steps taken
    skipped_steps: jax.Array


def _global_norm(tree):
    leaves = jax.tree_util.tree_leaves(tree)
    if not leaves:
        return jnp.float32(0.0)
    return jnp.sqrt(sum(jnp.sum(jnp.square(l.astype(jnp.float32))) for l in leaves))


def _mean_by_name(collection, maxima=()):
    """{variable name: mean over every leaf sown under it} of a flax
    collection (a layer scan stacks a name's values, ``sow`` tuples them);
    of a name among ``maxima`` (a model's ``stat_maxima``) the LARGEST value
    sown, not the mean."""
    by_name = {}
    for path, leaf in jax.tree_util.tree_flatten_with_path(collection)[0]:
        name = [k.key for k in path if hasattr(k, "key")][-1]
        by_name.setdefault(name, []).append(
            jnp.max(leaf) if name in maxima else jnp.mean(leaf))
    return {n: jnp.max(jnp.stack(v)) if n in maxima else sum(v) / len(v)
            for n, v in sorted(by_name.items())}


def _tree_where(pred, a, b):
    return jax.tree_util.tree_map(
        lambda x, y: jnp.where(pred, x, y) if hasattr(x, "dtype") else x, a, b)


def _build_optimizer(name, params_dict):
    p = dict(params_dict or {})
    betas = tuple(p.pop("betas", (0.9, 0.999)))
    name = (name or "adam").lower()
    common = dict(lr=p.pop("lr", 1e-3), betas=betas, eps=p.pop("eps", 1e-8),
                  weight_decay=p.pop("weight_decay", 0.0))
    if name in (C.ADAM_OPTIMIZER, "fusedadam"):
        adam_w = p.pop("adam_w_mode", True)
        opt = FusedAdam(adam_w_mode=adam_w,
                        bias_correction=p.pop("bias_correction", True),
                        moment_dtype=p.pop("moment_dtype", "fp32"), **common)
    elif name == C.ADAMW_OPTIMIZER:
        opt = FusedAdam(adam_w_mode=True,
                        bias_correction=p.pop("bias_correction", True),
                        moment_dtype=p.pop("moment_dtype", "fp32"), **common)
    elif name == C.CPU_ADAM_OPTIMIZER:
        opt = DeepSpeedCPUAdam(adam_w_mode=p.pop("adam_w_mode", True),
                               bias_correction=p.pop("bias_correction", True),
                               moment_dtype=p.pop("moment_dtype", "fp32"),
                               **common)
    elif name in (C.LAMB_OPTIMIZER, "fusedlamb"):
        opt = FusedLamb(bias_correction=p.pop("bias_correction", True),
                        max_coeff=p.pop("max_coeff", 10.0),
                        min_coeff=p.pop("min_coeff", 0.01),
                        moment_dtype=p.pop("moment_dtype", "fp32"), **common)
    elif name == C.ONEBIT_ADAM_OPTIMIZER:
        from deepspeed_tpu.runtime.fp16.onebit.adam import OnebitAdam
        opt = OnebitAdam(freeze_step=p.pop("freeze_step", 100000), **common)
    elif name == C.ONEBIT_LAMB_OPTIMIZER:
        from deepspeed_tpu.runtime.fp16.onebit.lamb import OnebitLamb
        opt = OnebitLamb(freeze_step=p.pop("freeze_step", 100000), **common)
    elif name == C.SGD_OPTIMIZER:
        opt = SGD(lr=common["lr"], momentum=p.pop("momentum", 0.0),
                  weight_decay=common["weight_decay"],
                  nesterov=p.pop("nesterov", False))
    else:
        raise ValueError(f"Unknown optimizer type {name}")
    if p:
        # a key the chosen optimizer never reads must not vanish silently
        # (e.g. moment_dtype on an optimizer without half-storage support)
        logger.warning(f"optimizer '{name}' ignores config params: "
                       f"{sorted(p)}")
    return opt


class DeepSpeedEngine:
    """See module docstring. Construction mirrors the reference's
    `_configure_*` phases (engine.py:149-220)."""

    def __init__(self,
                 args=None,
                 model=None,
                 optimizer=None,
                 model_parameters=None,
                 training_data=None,
                 lr_scheduler=None,
                 mesh=None,
                 mpu=None,
                 collate_fn=None,
                 config=None,
                 rng=None,
                 loss_fn=None,
                 param_tp_specs=None,
                 dont_change_device=False):
        mesh_lib.init_distributed()

        self.module = model
        self.client_optimizer = optimizer
        self.client_lr_scheduler = lr_scheduler
        self.training_data = training_data
        self.collate_fn = collate_fn
        self.mpu = mpu
        self._loss_fn_user = loss_fn
        self._param_tp_specs = param_tp_specs

        # -- config + mesh (reference engine.py:566 + _set_distributed_vars)
        # peek only at the mesh section first — full validation needs the
        # mesh-derived dp world size (batch triangle, config.py:837)
        explicit_mesh = mesh is not None
        if mesh is None:
            from deepspeed_tpu.config.config import MeshConfigSection
            pd = (config._param_dict if isinstance(config, DeepSpeedConfig)
                  else DeepSpeedConfig.load_param_dict(config))
            mc = MeshConfigSection(pd)
            mesh = mesh_lib.make_mesh(mesh_lib.MeshConfig(
                data=mc.data, model=mc.model, pipe=mc.pipe, seq=mc.seq,
                expert=mc.expert))
        if mpu is not None:
            mesh = self._adopt_mpu(mpu, mesh, explicit_mesh)
        self.mesh = mesh
        mesh_lib.set_current_mesh(mesh)
        # pipeline modules re-layout their params for the 1F1B executor;
        # this must see the FINAL mesh (after distributed init + config
        # resolution) and precede any param/state initialization
        if hasattr(model, "lower_to_spmd") and \
                mesh_lib.mesh_axis_size(mesh, mesh_lib.PIPE_AXIS) > 1:
            model.lower_to_spmd(mesh)
        self.dp_world_size = mesh_lib.dp_world_size(mesh)
        self._config = DeepSpeedConfig(config, mpu=mpu,
                                       world_size=self.dp_world_size)

        self.precision = prec.PrecisionConfig.from_ds_config(self._config)
        param_offload = self._config.zero_config.offload_param
        self._param_offload_host = bool(param_offload.enabled)
        self._param_offload_nvme = False
        self._param_swapper = None
        self._params_parked = False
        self._parked_via_push = False
        if self._param_offload_host:
            from deepspeed_tpu.utils.platform import is_tpu_backend
            if param_offload.device == C.OFFLOAD_NVME_DEVICE:
                # ZeRO-Infinity parameter tier: params REST on NVMe and
                # stream disk -> bounded staging -> HBM around each step
                # (swap_tensor/PartitionedParamSwapper); they are NOT
                # pinned_host-resident
                if not param_offload.nvme_path:
                    raise ValueError(
                        "offload_param device=nvme requires nvme_path")
                self._param_offload_nvme = True
                self._param_offload_host = False
            elif not is_tpu_backend():
                # the CPU PJRT backend advertises pinned_host but aborts
                # executing programs that move between memory spaces — the
                # tier is a no-op off-TPU (host RAM is already "host")
                logger.warning("offload_param: non-TPU backend, params "
                               "stay in default memory")
                self._param_offload_host = False
        self.zero = ZeroPartitioner(
            mesh, self._config.zero_optimization_stage,
            tp_specs=param_tp_specs,
            param_persistence_threshold=(
                self._config.zero_config.param_persistence_threshold
                if self._config.zero_optimization_stage >= 3 else 0),
            param_memory_kind="pinned_host" if self._param_offload_host
            else None)

        # -- optimizer (reference _configure_optimizer engine.py:647)
        if optimizer is not None:
            if isinstance(optimizer, TpuOptimizer):
                self.optimizer = optimizer
            elif hasattr(optimizer, "init") and hasattr(optimizer, "update"):
                self.optimizer = OptaxOptimizer(optimizer)
            else:
                raise TypeError("optimizer must be a TpuOptimizer or optax transform")
        else:
            self.optimizer = _build_optimizer(self._config.optimizer_name,
                                              self._config.optimizer_params)

        # -- lr scheduler (reference _configure_lr_scheduler engine.py:494)
        if lr_scheduler is not None:
            self.lr_scheduler = lr_scheduler
        elif self._config.scheduler_name:
            self.lr_scheduler = get_lr_schedule(self._config.scheduler_name,
                                                self._config.scheduler_params,
                                                self.optimizer)
        else:
            self.lr_scheduler = None

        # -- progressive layer drop (reference engine.py:1018)
        self.progressive_layer_drop = None
        if self._config.pld_config.enabled:
            self.progressive_layer_drop = ProgressiveLayerDrop(
                theta=self._config.pld_config.theta,
                gamma=self._config.pld_config.gamma)

        # -- MoQ quantize-aware training + eigenvalue (reference
        # engine.py:761-791 _configure_quantization)
        self.quantizer = None
        self.eigenvalue = None
        qcfg = self._config.quantize_training_config
        if qcfg.enabled:
            from deepspeed_tpu.runtime.quantize import Quantizer
            self.quantizer = Quantizer(
                q_target_bits=qcfg.target_bits,
                q_start_bits=qcfg.start_bits,
                q_period=qcfg.quantize_period,
                q_offset=qcfg.schedule_offset,
                q_groups=qcfg.groups,
                q_mixed_fp16=qcfg.fp16_mixed_quantize,
                q_change_ratio=qcfg.quantize_change_ratio,
                q_type=qcfg.q_type,
                q_rounding=qcfg.q_rounding,
                q_verbose=qcfg.verbose,
                q_eigenvalue=qcfg.eigenvalue_enabled,
                use_quantizer_kernel=qcfg.quantizer_kernel,
                layer_num=qcfg.eigenvalue_layer_num)
            if qcfg.eigenvalue_enabled:
                from deepspeed_tpu.runtime.eigenvalue import Eigenvalue
                self.eigenvalue = Eigenvalue(
                    verbose=qcfg.eigenvalue_verbose,
                    max_iter=qcfg.eigenvalue_max_iter,
                    tol=qcfg.eigenvalue_tol,
                    stability=qcfg.eigenvalue_stability,
                    gas_boundary_resolution=(
                        qcfg.eigenvalue_gas_boundary_resolution),
                    layer_name=qcfg.eigenvalue_layer_name,
                    layer_num=max(qcfg.eigenvalue_layer_num, 1))

        # -- dataloader (reference deepspeed_io engine.py:928)
        self.training_dataloader = None
        if training_data is not None:
            self.training_dataloader = self.deepspeed_io(training_data)

        # -- timers / counters (reference engine.py:176-180)
        self.timers = SynchronizedWallClockTimer()
        self.tput_timer = ThroughputTimer(
            batch_size=self.train_micro_batch_size_per_gpu(),
            num_workers=self.dp_world_size,
            steps_per_output=self._config.steps_per_print)
        self.global_steps = 0
        self.micro_steps = 0
        self.skipped_steps = 0
        self.global_samples = 0
        self.scalar_history = []  # tensorboard-lite: list of (step, dict)

        # -- unified telemetry (deepspeed_tpu/telemetry): per-step
        # counters/histograms into the process-wide registry (sync-free),
        # window folds + exports at steps_per_print boundaries where the
        # existing loss readback is already the fence, and the
        # config-gated XLA trace window (profiling.trace_dir/trace_steps)
        self.telemetry = default_registry()
        self._trace_window = TraceWindow.from_config(
            self._config.profiling_config)
        self._tel_exporter = None      # lazy JSONL stream (monitor gate)
        self._tel_bridge = None        # lazy SummaryEventWriter bridge
        self._tel_window_t0 = None     # open measurement window start
        self._tel_window_step0 = 0
        self._tel_window_tokens = 0
        self._tel_flops_per_step = None  # lazily priced via cost analysis

        # -- flight recorder + anomaly watchdog (ISSUE 6): the recorder
        # is the process-wide event ring (monitor.flight_recorder sizes/
        # gates it); the watchdog (monitor.watchdog, opt-in) evaluates
        # NaN-loss / step-time / swap-stall rules ONLY at the
        # steps_per_print boundary and window folds — the fences this
        # engine already pays — and dumps the ring to JSONL on trigger
        mc = self._config.monitor_config
        self.flight_recorder = default_recorder().configure(
            enabled=mc.flight_recorder.enabled,
            capacity=mc.flight_recorder.capacity)
        self.watchdog = Watchdog.from_config(
            mc.watchdog, recorder=self.flight_recorder,
            registry=self.telemetry, source="train")

        # -- cluster telemetry plane (ISSUE 12): cross-rank aggregation
        # at the fences this engine already pays (the steps_per_print
        # loss readback; snapshot commit fences) — a ~7-float gloo
        # allgather folded on rank 0 into cluster/* skew gauges and the
        # watchdog's rank_straggler rule. Single-process it degenerates
        # to local gauges with no collective.
        self._cluster = None
        self._tel_last_step_s = None   # the just-closed window's mean
        self._tel_last_host_step_s = None  # rank-attributable component
        self._tel_window_dispatch_s = 0.0  # blocked-in-dispatch seconds
        self._tel_last_fence_ts = None
        if mc.cluster.enabled:
            from deepspeed_tpu.telemetry.cluster import ClusterAggregator
            self._cluster = ClusterAggregator(
                registry=self.telemetry, recorder=self.flight_recorder,
                watchdog=self.watchdog)
        # live /metrics + /healthz endpoint (monitor.serve_port, rank 0
        # only — that is where the cluster gauges fold; a bind failure
        # warns instead of killing training)
        self._metrics_server = None
        from deepspeed_tpu.utils.logging import _process_index
        if mc.serve_port and _process_index() == 0:
            from deepspeed_tpu.telemetry.serve import start_metrics_server
            self._metrics_server = start_metrics_server(
                mc.serve_port, host=mc.serve_host,
                registry=self.telemetry, watchdog=self.watchdog,
                fence_age_fn=lambda: self._tel_last_fence_ts)

        # -- elastic preemption tolerance (runtime/elastic, ISSUE 7):
        # periodic async snapshots through the swap tier's write-behind
        # aio handle, a SIGTERM hook with a grace budget, auto-resume
        # from the newest valid manifest. All gated on the `snapshot`
        # config block; the snapshotter itself is built lazily (it may
        # ride the param swapper's write handle, which exists only
        # after state init).
        self._snap_cfg = self._config.snapshot_config
        self._snapshotter = None
        self._preemption = None
        self.preempted = False
        self._auto_resumed = False
        if self._snap_cfg.enabled:
            from deepspeed_tpu.runtime.elastic.preemption import (
                PreemptionHandler)
            self._preemption = PreemptionHandler(
                signals=self._snap_cfg.signals,
                grace_s=self._snap_cfg.grace_secs,
                recorder=self.flight_recorder)

        # -- collective hang watchdog + heartbeat (runtime/elastic/hang,
        # ISSUE 15): a daemon thread riding the same blocked-in-dispatch
        # interval the train/host_step_s accounting measures — a
        # collective stalled past fault_tolerance.hang_deadline_s
        # becomes one latched rank_dead dump + a distinct EXIT_HANG
        # exit instead of an eternal hang; the thread also rewrites
        # this rank's heartbeat file for the launcher-level supervisor.
        # restart_epoch (stamped by the supervisor into child envs) is
        # breadcrumbed into the ring so view.py can stitch the
        # die → detect → shrink → resume timeline across epochs.
        self._hangdog = None
        self._fence_ref = None   # the last step's loss array: the
        #                          pre-boundary-collective fence target
        self._fenced_step = None  # step already fenced (once per step)
        self._restart_epoch = int(
            os.environ.get("DSTPU_RESTART_EPOCH", "0") or 0)
        if self._restart_epoch:
            self.flight_recorder.record(
                "restart_epoch", epoch=self._restart_epoch,
                world=jax.process_count())
        ftc = self._config.fault_tolerance_config
        if ftc.enabled:
            from deepspeed_tpu.runtime.elastic.hang import HangWatchdog
            hb_dir = ftc.heartbeat_dir \
                or os.environ.get("DSTPU_HEARTBEAT_DIR") or None
            self._hangdog = HangWatchdog(
                deadline_s=ftc.hang_deadline_s,
                poll_s=ftc.hang_poll_s or None,
                rank=_process_index(), world=jax.process_count(),
                watchdog=self.watchdog, recorder=self.flight_recorder,
                registry=self.telemetry, heartbeat_dir=hb_dir,
                heartbeat_interval_s=ftc.heartbeat_interval_s,
                restart_epoch=self._restart_epoch)

        # ZeRO-Offload: optimizer state + fp32 master on host (cpu) or NVMe
        self._offload_cfg = self._config.zero_config.offload_optimizer
        self._host_runner = None
        if self._offload_cfg.enabled:
            # fail at construction, not at the first train_batch: the host
            # tier only has SIMD steps for the Adam/LAMB families, and the
            # NVMe tier needs somewhere to put the moments
            from deepspeed_tpu.ops.lamb import FusedLamb
            if not isinstance(self.optimizer, (FusedAdam, FusedLamb)):
                raise ValueError(
                    "optimizer offload supports Adam/AdamW/LAMB optimizers "
                    f"only, got {type(self.optimizer).__name__}")
            if self._offload_cfg.device == C.OFFLOAD_NVME_DEVICE and \
                    not self._offload_cfg.nvme_path:
                raise ValueError(
                    "offload_optimizer device=nvme requires nvme_path")

        # BEFORE state init: the partitioner judges a layer-stacked leaf
        # one layer at a time and never shards its layer dim (the layer
        # scan slices whole layers device-locally)
        stacked = getattr(self.module, "layer_stacked_subtree", None)
        self.zero.layer_stacked_prefixes = (stacked,) if stacked else ()
        # the ZeRO-3 gather edge of the GSPMD step programs
        # (zero/partition.GatherEdge), built with the state shardings
        self._gather_edge = None

        self._rng = rng if rng is not None else jax.random.PRNGKey(self._config.seed)
        self.state: Optional[TrainState] = None
        self.state_shardings = None
        self._jit_train_batch = None
        self._jit_micro_grads = None
        self._jit_grads_finite = None
        self._jit_grad_norm = None
        self._jit_apply_grads = None
        self._jit_eval = None
        self._pending_grads = None
        self._pending_loss = None
        self._pending_micro = None
        self._accum_loss = None
        self._last_lr = None

        if model_parameters is not None:
            self._init_state(model_parameters)

        if self._config.flops_profiler_config.enabled:
            from deepspeed_tpu.profiling.flops_profiler import FlopsProfiler
            self.flops_profiler = FlopsProfiler(self)
        else:
            self.flops_profiler = None

        log_dist(f"DeepSpeedEngine initialized: mesh={dict(self.mesh.shape)} "
                 f"zero_stage={self.zero_optimization_stage()} "
                 f"precision={self.precision.compute_dtype.__name__}", ranks=[0])

    # ------------------------------------------------------------------
    # config accessors (parity with reference engine.py:270-470)
    # ------------------------------------------------------------------
    def train_batch_size(self):
        return self._config.train_batch_size

    def train_micro_batch_size_per_gpu(self):
        return self._config.train_micro_batch_size_per_gpu

    def gradient_accumulation_steps(self):
        return self._config.gradient_accumulation_steps

    def zero_optimization_stage(self):
        return self._config.zero_optimization_stage

    def zero_optimization(self):
        return self._config.zero_enabled

    def fp16_enabled(self):
        return self._config.fp16_enabled

    def bfloat16_enabled(self):
        return self._config.bf16_enabled

    def gradient_clipping(self):
        return self._config.gradient_clipping

    def steps_per_print(self):
        return self._config.steps_per_print

    def wall_clock_breakdown(self):
        return self._config.wall_clock_breakdown

    def dump_state(self):
        return self._config.dump_state

    def get_lr(self):
        if self._last_lr is not None:
            return [float(self._last_lr)]
        return [float(getattr(self.optimizer, "lr", 0.0))]

    def get_global_grad_norm(self):
        return getattr(self, "_last_grad_norm", None)

    @property
    def loss_scale(self):
        if self.state is None:
            return 1.0
        return float(jax.device_get(self.state.scaler["loss_scale"]))

    def is_gradient_accumulation_boundary(self):
        return (self.micro_steps + 1) % self.gradient_accumulation_steps() == 0

    def _compressed_comm_active(self):
        """True when the train step should use the 1-bit compressed
        collective path (reference onebit wiring: engine's own allreduce is
        disabled and the optimizer communicates compressed momentum,
        onebit/adam.py:92-104). Requires a pure-DP layout: the momentum
        collective assumes replicated params (ZeRO stage 0, no tp/sp/pp)."""
        cached = getattr(self, "_compressed_comm_cached", None)
        if cached is not None:
            return cached
        self._compressed_comm_cached = self._compute_compressed_comm()
        return self._compressed_comm_cached

    def _compute_compressed_comm(self):
        if not getattr(self.optimizer, "supports_compressed_comm", False):
            return False
        if self._offload_cfg.enabled or self._param_offload_host:
            return False
        dp = mesh_lib.mesh_axis_size(self.mesh, mesh_lib.DATA_AXIS)
        if dp <= 1:
            return False
        pure_dp = (self.zero_optimization_stage() == 0
                   and self._pure_dp_mesh())
        if not pure_dp:
            logger.warning(
                "1-bit optimizer requested with ZeRO stage "
                f"{self.zero_optimization_stage()} or a non-data mesh axis; "
                "compressed communication disabled (exact-comm fallback)")
            return False
        return True

    def _pure_dp_mesh(self):
        """True when only the data axis is live — the explicit-comm
        train paths shard_map the data axis alone, so every other mesh
        axis must be trivial (the one shared gate of the 1-bit / CSR /
        overlap dispatch)."""
        return all(mesh_lib.mesh_axis_size(self.mesh, a) == 1
                   for a in (mesh_lib.PIPE_AXIS, mesh_lib.SEQ_AXIS,
                             mesh_lib.MODEL_AXIS, mesh_lib.EXPERT_AXIS))

    def _comm_hierarchy(self):
        """Resolved slow/fast split of the data axis for the link-aware
        compressed exchange (ISSUE 10), cached. None = flat single-link
        exchange — either the hierarchy block is off, or no slow axis
        exists, in which case the fallback is LOUD (warning + flight
        breadcrumb): silently compressing the fast links would be the
        exact mistake this layer exists to avoid."""
        cached = getattr(self, "_comm_hier_cached", "unset")
        if cached != "unset":
            return cached
        hier = None
        hcfg = self._config.comm_config.hierarchy
        if hcfg.enabled and self._compressed_comm_active():
            from deepspeed_tpu.parallel import topology as topo
            hier, reason = topo.derive_data_hierarchy(
                self.mesh, slow_axis=hcfg.slow_axis)
            if hier is None:
                # latched per (axis, reason): elastic restarts and test
                # harnesses rebuild engines in one process, and the same
                # fallback repeating per rebuild buries the one
                # occurrence that matters (the router_block episode rule)
                if topo.latch_fallback(hcfg.slow_axis
                                       if hcfg.slow_axis else "auto",
                                       reason):
                    logger.warning(
                        f"comm.hierarchy enabled but no usable slow axis "
                        f"({reason}); falling back to the FLAT "
                        f"single-link schedule — every link pays the "
                        f"full exchange")
                    self.flight_recorder.record("comm_hierarchy_fallback",
                                                reason=reason)
            else:
                log_dist(
                    f"comm.hierarchy: data axis split {hier.inter}x"
                    f"{hier.intra} (source={hier.source}, "
                    f"compression={hcfg.compression})", ranks=[0])
        self._comm_hier_cached = hier
        return hier

    def _comm_plan(self):
        """The static overlap.HierarchyPlan for the hierarchical
        compressed exchange, or None (flat path)."""
        hier = self._comm_hierarchy()
        if hier is None:
            return None
        from deepspeed_tpu.parallel import overlap
        hcfg = self._config.comm_config.hierarchy
        return overlap.HierarchyPlan(
            inter_axis=mesh_lib.DATA_INTER_AXIS,
            intra_axis=mesh_lib.DATA_INTRA_AXIS,
            inter=hier.inter, intra=hier.intra,
            compression=hcfg.compression,
            min_bucket_bytes=hcfg.min_bucket_bytes,
            bucket_elems=self._config.zero_config.reduce_bucket_size)

    # ------------------------------------------------------------------
    # state init
    # ------------------------------------------------------------------
    def _example_from_batch(self, batch):
        def first_micro(x):
            arr = np.asarray(x)
            mb = self.train_micro_batch_size_per_gpu() * self.dp_world_size
            return arr[:mb] if arr.ndim > 0 and arr.shape[0] >= mb else arr
        return jax.tree_util.tree_map(first_micro, batch)

    def _model_inputs(self, batch):
        """Extract the positional model input from a batch pytree."""
        if isinstance(batch, dict):
            for key in ("input_ids", "inputs", "x"):
                if key in batch:
                    return batch[key]
            return next(iter(batch.values()))
        if isinstance(batch, (tuple, list)):
            return batch[0]
        return batch

    def _maybe_derive_tp_specs(self, x):
        """Auto-derive Megatron-style TP specs for known in-tree models when
        the mesh has a model axis (shape-only, via eval_shape)."""
        if self._param_tp_specs is not None:
            return
        # models may publish their own base specs (TP/pipe axes)
        if hasattr(self.module, "param_partition_specs"):
            try:
                shapes = jax.eval_shape(
                    lambda r, xx: self.module.init(r, xx), self._rng, x)
                self._param_tp_specs = self.module.param_partition_specs(shapes)
                self.zero.tp_specs = self._param_tp_specs
                return
            except Exception as e:
                logger.warning(f"model param_partition_specs failed: {e}")
        if mesh_lib.mesh_axis_size(self.mesh, mesh_lib.MODEL_AXIS) <= 1:
            return
        try:
            from deepspeed_tpu.models.sharding import tp_specs_for
            shapes = jax.eval_shape(
                lambda r, xx: self.module.init(r, xx), self._rng, x)
            specs = tp_specs_for(
                self.module, shapes["params"] if "params" in shapes
                else shapes)
            if specs is not None:
                self._param_tp_specs = specs
                self.zero.tp_specs = specs
                return
        except Exception as e:
            logger.warning(f"TP spec auto-derivation failed: {e}")
        logger.warning(
            f"mesh has model axis "
            f"{mesh_lib.mesh_axis_size(self.mesh, mesh_lib.MODEL_AXIS)} but "
            f"no tensor-parallel sharding rules are known for "
            f"{type(self.module).__name__}: parameters will be REPLICATED "
            f"across the model axis (TP is a no-op). Register rules via "
            f"deepspeed_tpu.models.sharding.register_tp_rules or expose "
            f"param_partition_specs on the model.")

    def _make_offload_runner(self, params):
        """Pick the offload tier: the device-streamed step (state in the
        accelerator host's pinned_host memory, update on device —
        offload_stream.py) when the backend supports it, the numpy/SIMD
        host runner (offload.py) for NVMe state, LAMB, non-pinned-host
        backends, or an explicit ``stream: "host"``."""
        from deepspeed_tpu.runtime.zero.offload import HostOffloadOptimizer
        cfg = self._offload_cfg
        want_stream = cfg.stream != "host" \
            and cfg.device == C.OFFLOAD_CPU_DEVICE \
            and not isinstance(self.optimizer, FusedLamb)
        if want_stream:
            from deepspeed_tpu.runtime.zero.offload_stream import (
                StreamedOffloadOptimizer, backend_supports_offload_stream)
            if backend_supports_offload_stream(self.mesh.devices.flat[0]):
                # TPU: state rests in pinned_host; CPU: memory spaces are
                # collapsed (unpinned_host only) so the moves are no-ops
                # but the tier runs with identical semantics
                return StreamedOffloadOptimizer(
                    params, self.optimizer, self.mesh, self.zero)
            if cfg.stream == "device":
                raise ValueError(
                    "offload_optimizer stream='device' requires a backend "
                    "with an addressable host memory space")
            logger.warning("offload: backend reports no addressable "
                           "memories; using the host runner")
        elif cfg.stream == "device":
            raise ValueError(
                "offload_optimizer stream='device' supports device='cpu' "
                "with Adam/AdamW only (NVMe state and LAMB run on the host "
                "runner)")
        return HostOffloadOptimizer(
            params, self.optimizer, cfg, self._config.aio_config)

    def _offload_streamed(self):
        from deepspeed_tpu.runtime.zero.offload_stream import (
            StreamedOffloadOptimizer)
        return isinstance(self._host_runner, StreamedOffloadOptimizer)

    def _init_state(self, params=None, example_batch=None):
        if params is None:
            x = jnp.asarray(self._model_inputs(example_batch))
            self._maybe_derive_tp_specs(x)
            params = self._init_params(x)

        if self._offload_cfg.enabled:
            # fp32 master + moments to host/NVMe; device keeps compute-dtype
            # params only (the ZeRO-Offload memory shape)
            self._host_runner = self._make_offload_runner(params)
            params = jax.tree_util.tree_map(
                lambda p: jnp.asarray(p, self.precision.compute_dtype)
                if jnp.issubdtype(jnp.asarray(p).dtype, jnp.floating) else
                jnp.asarray(p), params)
            opt_state = {}
        elif self._compressed_comm_active():
            opt_state = self.optimizer.init_compressed(
                params, mesh_lib.mesh_axis_size(self.mesh, mesh_lib.DATA_AXIS),
                comm=self._comm_plan())
        else:
            opt_state = self.optimizer.init(params)
        scaler = prec.init_scaler_state(self.precision)
        state = TrainState(params=params, opt_state=opt_state, scaler=scaler,
                           global_step=jnp.zeros((), jnp.int32),
                           skipped_steps=jnp.zeros((), jnp.int32))

        # shard the state onto the mesh per ZeRO stage
        self.state_shardings = self._build_state_shardings(state)
        self.state = jax.tree_util.tree_map(
            lambda x, s: jax.device_put(x, s), state, self.state_shardings)
        if self._param_offload_nvme:
            # the files themselves are first written by the post-step
            # _park_params — params are device-resident until then, so an
            # eager write here would be dead work the first park overwrites
            self._param_swapper = self._make_param_swapper()
        see_memory_usage("after engine state init",
                         force=self._config.memory_breakdown)

    def _make_param_swapper(self):
        """The NVMe param-tier swapper, wired to the offload_param
        pipeline knobs (pipeline_read/pipeline_write/buffer_count) and
        this engine's telemetry registry."""
        from deepspeed_tpu.runtime.swap_tensor import PartitionedParamSwapper
        pc = self._config.zero_config.offload_param
        return PartitionedParamSwapper(
            pc.nvme_path, self._config.aio_config,
            pipeline_read=pc.pipeline_read,
            pipeline_write=pc.pipeline_write,
            buffer_count=pc.buffer_count,
            registry=self.telemetry,
            fsync=pc.fsync)

    def _param_swap_order(self):
        """The per-layer swap schedule: the order param leaves stream
        disk→host→device at unpark, derived from the partitioner's
        layer-stacked prefixes (the model's ``layer_stacked_subtree``).
        First-consumed leaves first — outer (embedding-side) leaves, then
        the stacked transformer blocks the layer scan slices layer by
        layer — so the device assembles inputs in compute order while
        later groups are still on disk. Pure metadata: any
        permutation is correct; this one pipelines best."""
        order = getattr(self, "_param_swap_order_cache", None)
        if order is not None and len(order) == len(
                jax.tree_util.tree_leaves(self.state_shardings.params)):
            return order
        flat, _ = jax.tree_util.tree_flatten_with_path(
            self.state_shardings.params)
        stacked = set(self.zero.layer_stacked_prefixes or ())

        def head(path):
            if not path:
                return ""
            p = path[0]
            return str(getattr(p, "key", getattr(p, "name", p)))

        outer = [i for i, (p, _) in enumerate(flat) if head(p) not in stacked]
        inner = [i for i, (p, _) in enumerate(flat) if head(p) in stacked]
        # flatten order puts the block subtree ("h") before ln_f/wpe/wte;
        # reversing the outer list puts the embedding leaves first
        order = outer[::-1] + inner
        self._param_swap_order_cache = order
        return order

    # -- NVMe parameter residency (ZeRO-Infinity param tier) ---------------
    def _ensure_params_resident(self):
        """Parked params (resting on NVMe) stream back to the device before
        any computation that reads them — in swap-schedule order, through
        the pipelined read window (and the write-behind byte cache) when
        the offload_param pipeline knobs are on."""
        if not self._params_parked:
            return
        t0 = time.perf_counter()
        leaves = self._param_swapper.swap_in_device(
            jax.tree_util.tree_leaves(self.state_shardings.params),
            order=self._param_swap_order())
        self.telemetry.histogram("swap/unpark_s").observe(
            time.perf_counter() - t0)
        self.state = TrainState(
            params=jax.tree_util.tree_unflatten(
                jax.tree_util.tree_structure(self.state_shardings.params),
                leaves),
            opt_state=self.state.opt_state, scaler=self.state.scaler,
            global_step=self.state.global_step,
            skipped_steps=self.state.skipped_steps)
        self._params_parked = False

    def _park_params(self):
        """Write the (updated) device params back to NVMe and free their
        HBM — params rest on disk between steps, so at rest the chip holds
        no parameter bytes and host RAM holds only the bounded staging
        pool. With ``pipeline_write`` the disk writes run behind this call
        (swap-out of step N overlaps everything up to step N+1's unpark,
        whose drain fence guarantees no leaf is re-read mid-write); when
        the host optimizer already parked the updated leaves directly
        (``_parked_via_push``), only the stale device copies remain to
        free."""
        if self._param_swapper is None or self._params_parked:
            return
        t0 = time.perf_counter()
        leaves = jax.tree_util.tree_leaves(self.state.params)
        if getattr(self, "_parked_via_push", False):
            self._parked_via_push = False
        else:
            self._param_swapper.swap_out_device(leaves)
        for leaf in leaves:
            try:
                leaf.delete()
            except Exception:
                pass
        self._params_parked = True
        self.telemetry.histogram("swap/park_s").observe(
            time.perf_counter() - t0)

    # -- collective hang guard (runtime/elastic/hang, ISSUE 15) ------------
    # Every region that can block on a PEER process — the step dispatch
    # plus the boundary exchanges (cluster allgather, preemption
    # agreement) — is bracketed so the hang watchdog can tell "blocked
    # on a dead/stuck peer" from "idle between steps". Two attribute
    # stores per call; the first region of each kind is compile-exempt.

    def _guard_enter(self, kind, step=None):
        if self._hangdog is not None:
            self._hangdog.enter_dispatch(kind, step)

    def _guard_exit(self):
        if self._hangdog is not None:
            self._hangdog.exit_dispatch()

    def stop_fault_tolerance(self):
        """Stop the hang-watchdog daemon thread and remove this rank's
        heartbeat file. Engines have no general teardown hook, so a
        process that builds SEVERAL fault_tolerance-enabled engines
        (sequential jobs, test loops) should call this on each retired
        engine — otherwise every retired engine's thread keeps polling
        and rewriting the same heartbeat file. Called automatically
        when a preemption finalizes (the engine trains no further)."""
        if self._hangdog is not None:
            self._hangdog.stop()
            self._hangdog = None

    def _fence_step_program(self):
        """Multi-process only: block until the just-dispatched step
        program — and with it every IN-program cross-process collective
        — has completed, before any OUT-of-program collective runs at
        the boundary (the preemption agreement, the snapshot barriers).
        Two XLA programs' gloo ops interleave on the same TCP pair when
        the first is still in flight as the second dispatches (observed
        as ``gloo EnforceNotMet: op.preamble.length <= op.nbytes`` —
        one rank's boundary allgather recv met the peer's still-flowing
        step psum). The loss output alone is NOT a sufficient fence:
        output buffers become ready per-chain and the loss chain does
        not depend on the grad allreduce, so waiting on the loss can
        pass while the update collectives still flow — the fence waits
        on the UPDATED state leaves (each downstream of its own grad
        exchange) plus the loss. Leaves already parked/donated are
        skipped: their chains completed before the park could run. The
        wait itself is guarded — a peer that died mid-step parks us
        HERE, and the hang watchdog must see it. Latched per step:
        a boundary that is commit + agreement + cluster exchange at
        once fences the leaf tree exactly once."""
        if jax.process_count() == 1:
            return
        if self._fenced_step == self.global_steps:
            return
        self._fenced_step = self.global_steps
        self._guard_enter("fence", self.global_steps)
        try:
            leaves = [] if self.state is None else \
                jax.tree_util.tree_leaves(
                    (self.state.params, self.state.opt_state))
            if self._fence_ref is not None:
                leaves.append(self._fence_ref)
            for leaf in leaves:
                if getattr(leaf, "is_deleted", None) \
                        and leaf.is_deleted():
                    continue
                try:
                    jax.block_until_ready(leaf)  # sync-ok: boundary
                except Exception:                # fence
                    pass    # a just-donated buffer's chain is done
        finally:
            self._guard_exit()

    # -- elastic snapshots + preemption (runtime/elastic, ISSUE 7) ---------
    def _make_snapshotter(self):
        """The async snapshotter, on its OWN dedicated write-behind aio
        handle (the swap tier's write-handle pattern, not its handle:
        `aio_handle_wait` drains a whole handle, so literally sharing
        the park stream would make step N+1's unpark drain fence eat
        the snapshot writes after ~0 overlap — and charge them to
        swap/stall_s while ckpt/stall_s reads a structural 0)."""
        from deepspeed_tpu.runtime.elastic.snapshot import AsyncSnapshotter
        sc = self._snap_cfg
        return AsyncSnapshotter(
            sc.path, aio_config=self._config.aio_config,
            fsync=sc.fsync, keep=sc.keep, registry=self.telemetry,
            recorder=self.flight_recorder)

    def _snapshot_trees(self):
        """The {stem: pytree} payload of one snapshot — the same state
        save_checkpoint persists, but leaves already parked on NVMe
        become FileLeaf markers (bytes come off the swap files, or the
        write-behind staging cache for the most recent parks) instead of
        being re-serialized from the device."""
        from deepspeed_tpu.runtime.elastic.snapshot import FileLeaf
        state = self.state
        if self._host_runner is not None:
            # fp32 master + host moments, like save_checkpoint
            params = self._host_runner.params_tree()
            opt_state = self._host_runner.state_dict()
        elif self._params_parked and self._param_swapper is not None:
            sw = self._param_swapper
            if sw.has_pending_writes:
                # the files must be whole before FileLeaf reads them;
                # cache-backed leaves wouldn't need this, but the
                # uncached rest do and the fence drains the whole handle
                sw.drain_writes()
            flat, tdef = jax.tree_util.tree_flatten(
                self.state_shardings.params)
            leaves = []
            for i in range(len(flat)):
                shape, dtype = sw.meta[i]
                value, source = sw.staged_leaf(i)
                leaves.append(value if source == "cache"
                              else FileLeaf(value, shape, dtype))
            params = jax.tree_util.tree_unflatten(tdef, leaves)
            opt_state = state.opt_state
        else:
            params = state.params
            opt_state = state.opt_state
        return {
            "model_states": {"params": params},
            "optim_states": {
                "opt_state": opt_state,
                "scaler": state.scaler,
                "global_step": state.global_step,
                "skipped_steps": state.skipped_steps,
            },
        }

    def _begin_snapshot(self, tag=None):
        """Stage + submit one async snapshot (returns its tag). The
        disk writes overlap the following step; the next _elastic_step
        boundary is the commit point."""
        if self._snapshotter is None:
            self._snapshotter = self._make_snapshotter()
        if self._snapshotter.in_flight:
            self._snapshotter.finalize()
        tag = tag or f"global_step{self.global_steps}"
        meta = {
            "zero_stage": self.zero_optimization_stage(),
            "world_size": jax.process_count(),
            "dp_world_size": self.dp_world_size,
            "train_batch_size": self.train_batch_size(),
            "micro_batch": self.train_micro_batch_size_per_gpu(),
            "grad_accum": self.gradient_accumulation_steps(),
            "elastic": bool(self._config.elasticity_enabled),
        }
        self._snapshotter.begin(tag, self._snapshot_trees(),
                                extra=self._ckpt_extra(), meta=meta)
        return tag

    def _elastic_commit(self):
        """Commit point of the previous boundary's snapshot — runs
        BEFORE this step's ``_park_params`` so the drain fence waits
        only on writes that had a whole step to land (park and
        snapshot share one write handle when the NVMe tier is
        pipelined; fencing AFTER the park would synchronously eat the
        park the write-behind exists to hide, every post-boundary
        step). The measured stall feeds ckpt/stall_s and the
        watchdog's snapshot-stall rule."""
        if not self._snap_cfg.enabled:
            return
        if self._snapshotter is not None and self._snapshotter.in_flight:
            # the finalize path's _sync barriers (and the commit-fence
            # cluster exchange below) are OUT-of-program collectives:
            # the just-dispatched step program must be done first
            self._fence_step_program()
            _, stall = self._snapshotter.finalize()
            # stall observations happen ONLY at commit fences: feeding
            # zeros on the 99 in-between steps would pin the watchdog's
            # rolling median at 0 (factor never participates) and
            # re-arm its latch between commits (one dump per interval
            # instead of per episode)
            self.telemetry.histogram("ckpt/stall_s").observe(stall)
            if self.watchdog is not None:
                # host wall timer this method already kept — no fence
                self.watchdog.observe_ckpt_stall(
                    stall, step=self.global_steps)
            # ISSUE 12: the commit fence is the second aligned
            # aggregation point (snapshot begins happen at aligned
            # interval boundaries, so in_flight agrees across ranks) —
            # the fresh ckpt/stall_s observation rides the exchange
            if self._cluster is not None:
                # step_time_s is explicitly UNMEASURED here: the last
                # boundary's value is stale, and re-feeding it would
                # let one slow window satisfy the straggler rule's
                # K-CONSECUTIVE-fences debounce by itself (the rule
                # skips NaN ranks). This fence aggregates the fresh
                # ckpt stall; step-time skew belongs to boundaries.
                self._guard_enter("exchange", self.global_steps)
                try:
                    self._cluster.exchange_from_registry(
                        step=self.global_steps,
                        overrides={"step_time_s": None,
                                   "ckpt_stall_s": stall})
                finally:
                    self._guard_exit()
                self._tel_last_fence_ts = time.time()
                # NO window re-stamp here (unlike the boundary
                # exchange): this fence sits mid-window and moving t0
                # would shrink window_s under an unchanged step count,
                # corrupting train/step_time_s. The cost: the wait for
                # the slowest rank's arrival lands in this window —
                # once per snapshot interval, not per boundary.

    def _elastic_step(self):
        """Step-boundary elastic hook (after the park): the
        fault-injection point, preemption handling, and the periodic
        begin — whose commit rides the NEXT boundary's
        ``_elastic_commit``."""
        _faults.fire("step_end", step=self.global_steps, engine=self)
        sc = self._snap_cfg
        if not sc.enabled or self.preempted:
            return
        at_boundary = bool(sc.interval_steps) \
            and self.global_steps % sc.interval_steps == 0
        # multi-process: the snapshot path contains collective barriers
        # (ckpt._sync), so ranks must AGREE before entering it — a
        # per-rank signal flag would send ranks down mismatched barrier
        # sequences and deadlock. The agreement collective runs only at
        # interval boundaries (every rank reaches the same global_steps
        # in SPMD lockstep); single-process keeps the immediate
        # any-step preemption response.
        if jax.process_count() == 1:
            preempt_now = self._preemption is not None \
                and self._preemption.requested
        else:
            if at_boundary:
                # the agreement allgather + the snapshot path's _sync
                # barriers must not race the step program's own gloo
                # ops (see _fence_step_program)
                self._fence_step_program()
            preempt_now = at_boundary and self._preempt_agreed()
        if preempt_now:
            self._preempt_finalize()
        elif at_boundary:
            self._begin_snapshot()

    def _preempt_agreed(self):
        """Cross-process preemption agreement (multi-process only,
        called at aligned interval boundaries): any rank's pending
        signal preempts the whole job; ranks that never saw the signal
        adopt it; and EVERY rank restarts its grace clock at the
        agreement point — per-rank clocks started at arbitrary signal
        arrivals, and a diverged (or already-expired) budget check
        would send ranks down mismatched barrier sequences, or skip
        the final snapshot entirely whenever the signal landed more
        than grace_secs before a boundary. The commit protocol makes a
        past-deadline attempt harmless (a SIGKILL mid-commit leaves
        the previous snapshot intact), so attempting is always the
        better branch; the budget bounds the snapshot WORK from
        here."""
        pre = self._preemption
        if pre is None:
            return False
        from jax.experimental import multihost_utils
        self._guard_enter("exchange", self.global_steps)
        try:
            flags = multihost_utils.process_allgather(  # sync-ok: boundary
                np.asarray([pre.requested], np.float64))   # agreement
        finally:
            self._guard_exit()
        agreed = bool(np.any(flags))
        if agreed:
            if not pre.requested:
                pre.request("peer")
            if (pre.remaining() or 0) <= 0:
                logger.warning(
                    "preemption signal predates this boundary by more "
                    "than the grace budget; attempting the final "
                    "snapshot anyway (commit is atomic)")
            pre.restart_clock()
        return agreed

    def _preempt_finalize(self):
        """Final snapshot inside the grace budget, then mark the engine
        preempted. When the budget is already spent, the snapshot is
        abandoned rather than half committed — the previous committed
        one stays ``latest`` (the manifest is the commit point). In
        the multi-process shape _preempt_agreed restarted every rank's
        clock at the same boundary, so this check cannot diverge
        across ranks."""
        pre = self._preemption
        pre.poll_event()   # the signal handler deferred its ring event
        snapshotted = False
        tag = None
        if (pre.remaining() or 0) > 0:
            try:
                tag = self._begin_snapshot(
                    tag=f"global_step{self.global_steps}_final")
                self._snapshotter.finalize()
                snapshotted = True
            except _faults.SimulatedCrash:
                raise
            except Exception as e:
                logger.warning(f"preemption snapshot failed: {e}")
                try:
                    self._snapshotter.abort("preempt_grace")
                except Exception:
                    pass
        else:
            logger.warning("preemption grace budget already spent; "
                           "keeping the previous snapshot")
        self.preempted = True
        self.stop_fault_tolerance()   # no further training: retire the
        #                               watchdog thread + heartbeat
        self.flight_recorder.record(
            "preempt", step=self.global_steps, snapshotted=snapshotted,
            tag=tag, source=pre.source, remaining_s=pre.remaining())
        if self.watchdog is not None:
            self.watchdog.note_preempt(
                step=self.global_steps, snapshotted=snapshotted,
                grace_s=pre.grace_s, source=pre.source)

    def finalize_pending_snapshot(self):
        """Clean-shutdown hook: commit a snapshot still in flight (a
        run whose last step began one would otherwise leave an
        uncommitted ``.saving`` orphan — harmless, resume clears it,
        but the snapshot itself is lost). Returns the committed dir or
        None."""
        if self._snapshotter is not None and self._snapshotter.in_flight:
            path, _ = self._snapshotter.finalize()
            return path
        return None

    def _maybe_auto_resume(self):
        """Startup auto-resume (once): when the snapshot block is on
        and a valid manifest exists under snapshot.path, adopt the
        newest valid snapshot before the first step."""
        sc = self._snap_cfg
        if not sc.enabled or not sc.auto_resume or self._auto_resumed:
            return
        self._auto_resumed = True
        if self.global_steps:
            return   # an explicit load_checkpoint already positioned us
        from deepspeed_tpu.runtime.elastic.resume import elastic_resume
        res = elastic_resume(self, sc.path)
        if res is not None:
            log_dist(f"auto-resumed from snapshot tag={res[0]} at "
                     f"step={self.global_steps}", ranks=[0])

    # ------------------------------------------------------------------
    # loss
    # ------------------------------------------------------------------
    def _init_params(self, x):
        """Initialize params born-sharded when ZeRO-3 is on (the zero.Init
        path, partition_parameters.py:265 analog) so the full model never
        materializes on one device; eager init otherwise."""
        if self.zero_optimization_stage() >= 3:
            from deepspeed_tpu.runtime.zero.init import sharded_init
            params, _ = sharded_init(
                self.module, self._rng, x, self.mesh,
                stage=self.zero_optimization_stage(),
                tp_specs=self._param_tp_specs,
                param_persistence_threshold=(
                    self._config.zero_config.param_persistence_threshold),
                layer_stacked_prefixes=self.zero.layer_stacked_prefixes)
            return params
        variables = self.module.init(self._rng, x)
        return variables["params"] if "params" in variables else variables

    def _resolve_loss_fn(self) -> Callable:
        """The scalar loss: what the forward-only, explicit-comm and
        eigenvalue paths evaluate (``_loss_and_stats_fn``'s first value)."""
        fn = self._loss_and_stats_fn()

        def loss(params, batch, rng, keep_prob):
            return fn(params, batch, rng, keep_prob)[0]
        return loss

    def _loss_and_stats_fn(self) -> Callable:
        """(params, batch, rng, keep_prob) -> (loss, {name: scalar}): the
        objective and what the model sowed into its ``stats`` collection in
        the same forward pass ({} for a model that sows none, and for a
        user's loss function)."""
        if self._loss_fn_user is not None:
            fn = self._loss_fn_user
            n = len(inspect.signature(fn).parameters)

            def user_loss(params, batch, rng, keep_prob):
                args = (params, batch, rng, keep_prob)[:n]
                return fn(*args), {}
            return user_loss

        model = self.module
        accepts_keep_prob = False
        accepts_deterministic = False
        fused_loss = False
        try:
            sig = inspect.signature(type(model).__call__)
            accepts_keep_prob = "keep_prob" in sig.parameters
            accepts_deterministic = "deterministic" in sig.parameters
            # models with a fused head+loss path (chunked cross entropy —
            # no [B, S, V] buffer) take `labels` and return the scalar loss
            fused_loss = "labels" in sig.parameters and \
                getattr(getattr(model, "config", None), "loss_chunk", 0) > 0
        except (TypeError, ValueError):
            pass
        has_dropout = getattr(getattr(model, "config", None), "dropout", 0.0) > 0
        model_cfg = getattr(model, "config", None)
        uses_moe = getattr(model_cfg, "moe_experts", 0) and \
            getattr(model_cfg, "moe_experts", 0) > 0
        moe_aux_coeff = float(getattr(model_cfg, "moe_aux_coeff", 0.01))
        # a model that sows says so itself (``sown_collections``, a property
        # of the model): "losses" is added to the objective AS IT IS, "stats"
        # leaves the step beside the loss and is folded into gauges at a
        # steps_per_print boundary (_telemetry_model_stats); one that DRAWS in
        # training names its ``rng_streams``, each handed the step's key
        sown = tuple(getattr(model, "sown_collections", ()))
        stat_maxima = tuple(getattr(model, "stat_maxima", ()))
        rng_names = _rng_names(model, has_dropout)

        def apply_model(params, inputs, kwargs):
            """(output, auxiliary loss, stats) of the model; when it carries
            MoE blocks, collect the sown load-balancing losses so the router
            actually trains balanced (the aux term of Switch/GShard)."""
            if uses_moe:
                out, vs = model.apply({"params": params}, inputs,
                                      mutable=["losses"], **kwargs)
                aux = sum(jnp.sum(l) for l in jax.tree_util.tree_leaves(
                    vs.get("losses", {})))
                return out, moe_aux_coeff * aux, {}
            if sown:
                out, vs = model.apply({"params": params}, inputs,
                                      mutable=list(sown), **kwargs)
                aux = sum(jnp.sum(l) for l in jax.tree_util.tree_leaves(
                    vs.get("losses", {})))
                return out, aux, _mean_by_name(vs.get("stats", {}),
                                               stat_maxima)
            return model.apply({"params": params}, inputs, **kwargs), 0.0, {}

        def default_loss(params, batch, rng, keep_prob):
            from deepspeed_tpu.models.gpt2 import lm_loss
            kwargs = {}
            if accepts_keep_prob:
                kwargs["keep_prob"] = keep_prob
            if accepts_deterministic:
                kwargs["deterministic"] = not has_dropout
            if rng_names:
                kwargs["rngs"] = dict.fromkeys(rng_names, rng)
            if isinstance(batch, dict) and "input_ids" in batch:
                labels = batch.get("labels", batch["input_ids"])
                if fused_loss:
                    loss, aux, stats = apply_model(
                        params, batch["input_ids"],
                        {**kwargs, "labels": labels})
                    return loss + aux, stats
                logits, aux, stats = apply_model(params, batch["input_ids"],
                                                 kwargs)
                return lm_loss(logits, labels) + aux, stats
            if isinstance(batch, (tuple, list)) and len(batch) == 2:
                x, y = batch
                out, aux, stats = apply_model(params, x, kwargs)
                if jnp.issubdtype(jnp.asarray(y).dtype, jnp.integer):
                    logp = jax.nn.log_softmax(out.astype(jnp.float32), axis=-1)
                    ll = jnp.take_along_axis(logp, y[..., None], axis=-1)
                    return -ll.mean() + aux, stats
                return jnp.mean(jnp.square(out.astype(jnp.float32) -
                                           y.astype(jnp.float32))) + aux, stats
            # bare array → LM on itself
            if fused_loss:
                loss, aux, stats = apply_model(params, batch,
                                               {**kwargs, "labels": batch})
                return loss + aux, stats
            logits, aux, stats = apply_model(params, batch, kwargs)
            return lm_loss(logits, batch) + aux, stats

        return default_loss

    # ------------------------------------------------------------------
    # jitted step construction
    # ------------------------------------------------------------------
    def _lr_fn(self):
        sched = self.lr_scheduler
        base_lr = getattr(self.optimizer, "lr", 1e-3)
        if sched is None:
            return lambda step: jnp.float32(base_lr)
        if isinstance(sched, _Schedule):
            return lambda step: sched.lr_at(step).astype(jnp.float32)
        if callable(sched):
            return lambda step: jnp.asarray(sched(step), jnp.float32)
        return lambda step: jnp.float32(base_lr)

    def _keep_prob_fn(self):
        pld = self.progressive_layer_drop
        if pld is None:
            return lambda step: jnp.float32(1.0)
        return lambda step: pld.theta_at(step)

    def _apply_grads(self, state, grads, loss):
        """Unscale, clip, step, scaler update — one fused update.

        The loss-scale inverse and clip coefficient are folded into ONE
        scalar passed to the optimizer's gradient read (`grad_scale`), so
        the full gradient tree is never re-materialized for unscaling or
        clipping (the reference does both as separate tensor passes,
        fused_optimizer.py:194-246)."""
        cfg = self._config
        scale = state.scaler["loss_scale"]
        inv = 1.0 / scale
        finite = prec.grads_finite(grads) if self.precision.fp16 \
            else jnp.asarray(True)

        # one read-only pass: norm of the RAW (still loss-scaled) grads
        grad_norm = _global_norm(grads) * inv
        gscale = inv
        if cfg.gradient_clipping and cfg.gradient_clipping > 0:
            gscale = inv * jnp.minimum(
                1.0, cfg.gradient_clipping / (grad_norm + 1e-6))

        lr = self._lr_fn()(state.global_step)
        params = state.params
        if self._param_offload_host:
            # param offload tier: stream host-resident params to HBM for
            # the update (compute ops cannot mix memory spaces)
            params = jax.device_put(
                params, self.zero.device_param_shardings(params))
        if "grad_scale" in inspect.signature(
                self.optimizer.step).parameters:
            new_params, new_opt = self.optimizer.step(
                params, grads, state.opt_state, lr, grad_scale=gscale)
        else:
            grads = jax.tree_util.tree_map(
                lambda g: g.astype(jnp.float32) * gscale, grads)
            new_params, new_opt = self.optimizer.step(params, grads,
                                                      state.opt_state, lr)
        buffers = tuple(getattr(self.module, "buffer_leaves", ()))
        if buffers:
            # leaves the model holds as BUFFERS (a router's selection bias,
            # moved by a rule outside the loss): handed back as they came —
            # no gradient step, no weight decay
            new_params = jax.tree_util.tree_map_with_path(
                lambda path, new, old: old if getattr(
                    path[-1], "key", None) in buffers else new,
                new_params, params)
        # skip-on-overflow (reference fused_optimizer.py:194-246); done
        # before moving back so both branches live in device memory
        new_params = _tree_where(finite, new_params, params)
        new_opt = _tree_where(finite, new_opt, state.opt_state)
        if self._param_offload_host:
            new_params = jax.device_put(
                new_params, self.zero.param_shardings(new_params))
        else:
            # constrain updated params back to their resting sharding (the
            # stage-1/2 all-gather of updated partitions, stage2.py:~1470)
            new_params = jax.tree_util.tree_map(
                lambda p, s: jax.lax.with_sharding_constraint(p, s),
                new_params, self.zero.param_shardings(new_params))
        new_scaler = prec.update_scaler(state.scaler, self.precision, finite)
        return TrainState(
            params=new_params,
            opt_state=new_opt,
            scaler=new_scaler,
            global_step=state.global_step + finite.astype(jnp.int32),
            skipped_steps=state.skipped_steps + (~finite).astype(jnp.int32),
        ), {"loss": loss, "grad_norm": grad_norm, "lr": lr,
            "overflow": ~finite, "loss_scale": new_scaler["loss_scale"]}

    def _pinned(self, jitted):
        """Run a GSPMD-jitted engine program with the models' layout pins
        scoped to THIS engine's mesh (mesh_lib.layout_pins): the pins
        must never read the ambient registry — it outlives engines, and
        a trace in another context constraining to a stale foreign-device
        mesh crashes GSPMD. Python-call scoping survives however jax
        re-traces custom_vjp backwards. `lower` passes through for
        train_step_memory_stats."""
        mesh = self.mesh

        def traced(fn, *args, **kwargs):
            # (the pins' scope, and the fall-back of what rematted blocks keep)
            out = self._run_pinned(mesh, fn, args, kwargs)
            self._note_gather_edge()
            return out

        call = functools.partial(traced, jitted)
        call.lower = functools.partial(traced, jitted.lower)
        return call

    def _note_gather_edge(self):
        """Publish what the trace that just ran (if one did) sent through
        the ZeRO-3 gather edge: per layer, the leaves that arrived
        data-sharded and the bytes a chip holds of them once gathered.
        Gauges plus one flight-recorder breadcrumb per traced program;
        a cached call finds nothing engaged and returns."""
        edge = self._gather_edge
        if edge is None or not edge.engaged:
            return
        leaves, nbytes = max(edge.engaged.values())
        blocks = len(edge.engaged)
        edge.engaged.clear()
        self.telemetry.gauge("zero/gather_edge_leaves").set(leaves)
        self.telemetry.gauge("zero/gather_edge_bytes_per_layer").set(nbytes)
        self.flight_recorder.record(
            "zero_gather_edge", leaves=leaves, bytes_per_layer=nbytes,
            blocks=blocks)
        log_dist(f"zero stage 3: gather edge pins {leaves} sharded leaves "
                 f"a layer data-replicated inside the block "
                 f"({nbytes / 1e6:.1f} MB gathered a chip a layer)",
                 ranks=[0])

    def _build_jit_fns(self):
        # 0 until a trace sends a sharded leaf through the gather edge
        # (_note_gather_edge): one chip, stages 0-2, explicit-comm steps
        self.telemetry.gauge("zero/gather_edge_leaves").set(0)
        self.telemetry.gauge("zero/gather_edge_bytes_per_layer").set(0)
        loss_fn = self._resolve_loss_fn()
        loss_stats_fn = self._loss_and_stats_fn()
        gas = self.gradient_accumulation_steps()
        batch_sh = mesh_lib.batch_sharding(self.mesh)
        repl = NamedSharding(self.mesh, PartitionSpec())

        def accumulate_grads(state, batch, rng):
            if gas == 1:
                # no accumulation: skip the scan and the fp32 zero-buffer
                # init+add pass entirely (one full extra read/write of the
                # gradient tree per step otherwise)
                batch = jax.tree_util.tree_map(
                    lambda x: jax.lax.with_sharding_constraint(x, batch_sh),
                    batch)
                with annotate("ds_fwd_bwd"):
                    loss, grads, stats = self._micro_loss_and_grads(
                        state, batch, rng, loss_fn=loss_stats_fn)
                return grads, loss, stats
            # batch leading dim = gas * micro_global; scan over gas chunks
            def to_chunks(x):
                assert x.shape[0] % gas == 0, (
                    f"train_batch got leading dim {x.shape[0]} not divisible "
                    f"by gradient_accumulation_steps={gas}; pass a global "
                    f"batch of micro*gas samples or use forward/backward/step")
                return x.reshape((gas, x.shape[0] // gas) + x.shape[1:])
            chunked = jax.tree_util.tree_map(to_chunks, batch)
            rngs = jax.random.split(rng, gas)

            acc_dtype = jnp.bfloat16 \
                if self._config.grad_accum_dtype == "bf16" else jnp.float32

            def micro(acc, inp):
                micro_batch, r = inp
                micro_batch = jax.tree_util.tree_map(
                    lambda x: jax.lax.with_sharding_constraint(x, batch_sh),
                    micro_batch)
                with annotate("ds_fwd_bwd"):
                    loss, grads, stats = self._micro_loss_and_grads(
                        state, micro_batch, r, loss_fn=loss_stats_fn)
                acc_g, acc_l = acc
                acc_g = jax.tree_util.tree_map(
                    lambda a, g: a + g.astype(acc_dtype) / gas, acc_g, grads)
                return (acc_g, acc_l + loss / gas), stats

            zero_g = jax.tree_util.tree_map(
                lambda p: jnp.zeros(p.shape, acc_dtype), state.params)
            zero_g = self.zero.constrain_grads(zero_g)
            (grads, loss), stats = jax.lax.scan(
                micro, (zero_g, jnp.float32(0.0)), (chunked, rngs))
            return grads, loss, jax.tree_util.tree_map(
                lambda s: jnp.mean(s, axis=0), stats)

        def train_batch_fn(state, batch, rng):
            grads, loss, stats = accumulate_grads(state, batch, rng)
            with annotate("ds_optimizer"):
                new_state, metrics = self._apply_grads(state, grads, loss)
            if stats:
                metrics["model_stats"] = stats
            return new_state, metrics

        def grads_batch_fn(state, batch, rng):
            # offload path: grads stay on device; host applies the step.
            # finiteness + norm are computed here so the host only pulls two
            # scalars instead of re-scanning every leaf
            grads, loss, _ = accumulate_grads(state, batch, rng)
            finite = prec.grads_finite(grads) if self.precision.fp16 \
                else jnp.asarray(True)
            return grads, loss, finite, _global_norm(grads)

        self._jit_grads_batch = self._pinned(jax.jit(grads_batch_fn))

        def micro_grads_fn(state, batch, rng):
            batch = jax.tree_util.tree_map(
                lambda x: jax.lax.with_sharding_constraint(x, batch_sh), batch)
            loss, grads, _ = self._micro_loss_and_grads(
                state, batch, rng, loss_fn=loss_stats_fn)
            return loss, grads

        def apply_grads_fn(state, grads, loss):
            with annotate("ds_optimizer"):
                return self._apply_grads(state, grads, loss)

        self._jit_train_batch = self._pinned(
            jax.jit(train_batch_fn, donate_argnums=(0,)))
        self._jit_micro_grads = self._pinned(jax.jit(micro_grads_fn))
        self._jit_apply_grads = self._pinned(
            jax.jit(apply_grads_fn, donate_argnums=(0, 1)))

        def loss_batch_fn(state, batch, rng):
            # forward-only twin of accumulate_grads, for the
            # wall_clock_breakdown forward-phase measurement
            if gas == 1:
                b = jax.tree_util.tree_map(
                    lambda x: jax.lax.with_sharding_constraint(x, batch_sh),
                    batch)
                return self._micro_loss(state, b, rng, loss_fn=loss_fn)
            chunked = jax.tree_util.tree_map(
                lambda x: x.reshape((gas, x.shape[0] // gas) + x.shape[1:]),
                batch)
            rngs = jax.random.split(rng, gas)

            def micro(acc, inp):
                b, r = inp
                b = jax.tree_util.tree_map(
                    lambda x: jax.lax.with_sharding_constraint(x, batch_sh), b)
                return acc + self._micro_loss(state, b, r,
                                              loss_fn=loss_fn) / gas, None
            total, _ = jax.lax.scan(micro, jnp.float32(0.0), (chunked, rngs))
            return total
        self._jit_loss_batch = self._pinned(jax.jit(loss_batch_fn))
        if self._compressed_comm_active():
            self._jit_train_batch = self._build_compressed_train_fn(loss_fn)
        elif self._sparse_grad_active():
            self._jit_train_batch = self._build_sparse_train_fn(loss_fn)
        elif self._overlap_comm_active():
            self._jit_train_batch = self._build_overlap_train_fn(loss_fn)

        try:
            accepts_det = "deterministic" in inspect.signature(
                type(self.module).__call__).parameters
        except (TypeError, ValueError):
            accepts_det = False

        try:
            accepts_inference = "inference" in inspect.signature(
                self.module.apply).parameters
        except (TypeError, ValueError, AttributeError):
            accepts_inference = False

        def eval_fn(state, x):
            x = jax.lax.with_sharding_constraint(x, batch_sh)
            params = state.params
            if self._param_offload_host:
                params = jax.device_put(
                    params, self.zero.device_param_shardings(params))
            kwargs = {}
            if accepts_inference:
                # pipeline modules: run the forward-only InferenceSchedule
                # program instead of the differentiable 1F1B primal
                kwargs["inference"] = True
            if accepts_det:
                kwargs["deterministic"] = True
            return self.module.apply({"params": params}, x, **kwargs)
        self._jit_eval = self._pinned(jax.jit(eval_fn))
        self._last_lr = None

    def _local_grad_accumulator(self, loss_fn, axis):
        """Shared scaffold for the explicit-comm (shard_map) train paths
        (1-bit compressed, row-sparse): per-device rng folding and local
        gradient accumulation over gas microbatches — grads come back
        LOCAL to the data shard, in fp32, loss averaged locally."""
        gas = self.gradient_accumulation_steps()
        keep_fn = self._keep_prob_fn()

        def accumulate(state, batch, rng):
            tm = jax.tree_util.tree_map
            rng = jax.random.fold_in(rng, jax.lax.axis_index(axis))
            scale = state.scaler["loss_scale"]
            keep_prob = keep_fn(state.global_step)

            def micro_grads(micro, r):
                def scaled(p):
                    loss = loss_fn(p, micro, r, keep_prob)
                    return (loss * scale).astype(jnp.float32), loss
                return jax.grad(scaled, has_aux=True)(state.params)

            if gas == 1:
                grads, loss = micro_grads(batch, rng)
                grads = tm(lambda g: g.astype(jnp.float32), grads)
            else:
                chunked = tm(lambda x: x.reshape(
                    (gas, x.shape[0] // gas) + x.shape[1:]), batch)
                rngs = jax.random.split(rng, gas)

                def body(acc, inp):
                    micro, r = inp
                    g, l = micro_grads(micro, r)
                    acc_g, acc_l = acc
                    return (tm(lambda a, gg: a + gg.astype(jnp.float32)
                               / gas, acc_g, g), acc_l + l / gas), None
                zero_g = tm(lambda p: jnp.zeros(p.shape, jnp.float32),
                            state.params)
                (grads, loss), _ = jax.lax.scan(
                    body, (zero_g, jnp.float32(0.0)), (chunked, rngs))
            return grads, loss

        return accumulate

    @staticmethod
    def _finish_explicit_state(state, new_params, new_opt, finite,
                               precision):
        """Overflow-skip + scaler/counter epilogue shared by the explicit-
        comm train paths (mirrors _apply_grads' tail)."""
        new_params = _tree_where(finite, new_params, state.params)
        new_opt = _tree_where(finite, new_opt, state.opt_state)
        new_scaler = prec.update_scaler(state.scaler, precision, finite)
        return TrainState(
            params=new_params, opt_state=new_opt, scaler=new_scaler,
            global_step=state.global_step + finite.astype(jnp.int32),
            skipped_steps=state.skipped_steps + (~finite).astype(jnp.int32))

    def _build_compressed_train_fn(self, loss_fn):
        """shard_map train step for 1-bit optimizers: grads stay LOCAL to
        each data shard (no GSPMD psum), the optimizer's step_local runs the
        warmup pmean / compressed momentum collective itself (the
        reference's compressed_allreduce replacing the engine allreduce,
        comm/nccl.py:47). Params replicated; error-feedback state per-device
        with a leading [dp] axis.

        With comm.hierarchy resolved (ISSUE 10) the program shard_maps a
        data-axis-split view of the same mesh ((data_inter, data_intra) —
        metadata-only reshard) and the optimizer runs the link-aware
        bucketed exchange: fast-axis hops uncompressed, slow-axis hops
        sign-packed per the per-bucket policy."""
        plan = self._comm_plan()
        if plan is not None:
            mesh = mesh_lib.split_data_axis(self.mesh, plan.inter)
            axis = plan.axes
            self._install_comm_wire_model(plan)
        else:
            mesh = self.mesh
            axis = mesh_lib.DATA_AXIS
        cfg = self._config
        state = self.state
        lr_fn = self._lr_fn()
        opt = self.optimizer
        precision = self.precision
        accumulate = self._local_grad_accumulator(loss_fn, axis)
        spec_like = lambda tree, s: jax.tree_util.tree_map(  # noqa: E731
            lambda _: s, tree)

        opt_specs = {
            k: spec_like(v, PartitionSpec(axis))
            if k in ("worker_error", "server_error") else
            spec_like(v, PartitionSpec())
            for k, v in state.opt_state.items()}
        state_specs = TrainState(
            params=spec_like(state.params, PartitionSpec()),
            opt_state=opt_specs,
            scaler=spec_like(state.scaler, PartitionSpec()),
            global_step=PartitionSpec(),
            skipped_steps=PartitionSpec())

        def train_fn(state, batch, rng):
            batch_specs = spec_like(batch, PartitionSpec(axis))

            @functools.partial(
                jax.shard_map, mesh=mesh,
                in_specs=(state_specs, batch_specs, PartitionSpec()),
                out_specs=(state_specs, spec_like(
                    {"loss": 0, "grad_norm": 0, "lr": 0, "overflow": 0,
                     "loss_scale": 0}, PartitionSpec())),
                check_vma=False)
            def inner(state, batch, rng):
                tm = jax.tree_util.tree_map
                # per-device dropout streams over distinct data shards
                grads, loss = accumulate(state, batch, rng)
                scale = state.scaler["loss_scale"]

                inv = 1.0 / scale
                grads = tm(lambda g: g * inv, grads)
                loss = jax.lax.pmean(loss, axis)
                local_finite = prec.grads_finite(grads) if precision.fp16 \
                    else jnp.asarray(True)
                finite = jax.lax.pmin(
                    local_finite.astype(jnp.int32), axis) > 0
                # metrics-only norm: mean of the local-shard grad norms
                # (the exact global norm would need an uncompressed
                # collective, defeating the compression)
                grad_norm = jax.lax.pmean(_global_norm(grads), axis)

                opt_local = dict(state.opt_state)
                for key in ("worker_error", "server_error"):
                    opt_local[key] = tm(lambda x: x[0], opt_local[key])

                lr = lr_fn(state.global_step)
                clip = cfg.gradient_clipping or None
                new_params, new_opt = opt.step_local(
                    state.params, grads, opt_local, lr, axis, clip=clip,
                    comm=plan)

                for key in ("worker_error", "server_error"):
                    new_opt[key] = tm(lambda x: x[None], new_opt[key])

                new_state = self._finish_explicit_state(
                    state, new_params, new_opt, finite, precision)
                return new_state, {
                    "loss": loss, "grad_norm": grad_norm, "lr": lr,
                    "overflow": ~finite,
                    "loss_scale": new_state.scaler["loss_scale"]}

            return inner(state, batch, rng)

        return self._jit_explicit_comm(train_fn)

    def _jit_explicit_comm(self, train_fn):
        """jit an explicit-comm (shard_map) train program with the models'
        GSPMD layout pins disabled for its traces (see
        mesh_lib.no_layout_pins — inside shard_map the pins poison avals
        with foreign-mesh shardings). The wrapper keeps the jitted fn's
        `lower` (train_step_memory_stats uses it), entering the same
        pin-free mode so an explicit lowering doesn't re-poison."""
        jitted = jax.jit(train_fn, donate_argnums=(0,))

        def call(state, batch, rng):
            with mesh_lib.no_layout_pins():
                return jitted(state, batch, rng)

        def lower(*args, **kwargs):
            with mesh_lib.no_layout_pins():
                return jitted.lower(*args, **kwargs)
        call.lower = lower
        return call

    def _overlap_comm_active(self):
        """True when the train step should run the bucketed gradient-sync
        scheduler (parallel/overlap.py): the explicit-comm train path whose
        per-bucket ring reduce-scatter/all-gather XLA can float over
        backward compute — the reference's `overlap_comm` + IPG buckets
        (stage2.py:614-746). Requires a multi-device pure-DP data axis and
        an elementwise optimizer (the per-shard ZeRO update slices param
        tensors)."""
        cached = getattr(self, "_overlap_comm_cached", None)
        if cached is None:
            cached = self._overlap_comm_cached = self._compute_overlap_comm()
        return cached

    def _compute_overlap_comm(self):
        zc = self._config.zero_config
        if not zc.overlap_comm:
            return False
        if self._offload_cfg.enabled or self._param_offload_host or \
                self._param_offload_nvme:
            # overlap_comm keeps its offload meaning there: per-microbatch
            # d2h gradient streaming (_host_offload_step_overlapped)
            return False
        if self._compressed_comm_active() or self._sparse_grad_active():
            return False
        if mesh_lib.mesh_axis_size(self.mesh, mesh_lib.DATA_AXIS) <= 1:
            return False
        if not self._pure_dp_mesh():
            log_dist("overlap_comm: non-data mesh axes are live — the "
                     "explicit bucket scheduler shard_maps the data axis "
                     "only; falling back to the fused GSPMD exchange",
                     ranks=[0])
            return False
        if self.zero_optimization_stage() >= 3:
            log_dist("overlap_comm supports ZeRO stages 0-2 (stage 3 "
                     "shards params at rest, which the explicit path does "
                     "not re-gather); falling back to the fused GSPMD "
                     "exchange", ranks=[0])
            return False
        if not getattr(self.optimizer, "elementwise_update", False):
            log_dist(f"overlap_comm needs an elementwise optimizer "
                     f"(Adam/AdamW/SGD) — the per-shard ZeRO update slices "
                     f"tensors, which breaks per-tensor statistics of "
                     f"{type(self.optimizer).__name__}; falling back to "
                     f"the fused GSPMD exchange", ranks=[0])
            return False
        return True

    def _build_overlap_train_fn(self, loss_fn):
        """shard_map train step with the bucketed gradient-sync scheduler:
        grads stay LOCAL to each data shard through backward, then sync as
        a stream of per-bucket ring reduce-scatter + all-gather programs
        (parallel/overlap.py) instead of one implicit monolithic psum.
        ZeRO stage-1/2 semantics are explicit: optimizer moments keep their
        resting sharded layout (each device updates only its param slice)
        and updated slices all-gather back — stage2.py's partition update +
        param all-gather, with the exchange XLA can schedule early."""
        from deepspeed_tpu.parallel import overlap as overlap_lib
        mesh = self.mesh
        axis = mesh_lib.DATA_AXIS
        cfg = self._config
        zc = cfg.zero_config
        n = mesh_lib.mesh_axis_size(mesh, axis)
        lr_fn = self._lr_fn()
        opt = self.optimizer
        precision = self.precision
        accumulate = self._local_grad_accumulator(loss_fn, axis)
        bucket_elems = int(zc.reduce_bucket_size)
        mode = zc.overlap_reduce
        spec_like = lambda tree, s: jax.tree_util.tree_map(  # noqa: E731
            lambda _: s, tree)

        params = self.state.params
        plan = self.zero.explicit_shard_plan(params)
        moment_specs = self.zero.opt_param_like_specs(params)
        param_like = getattr(opt, "param_like_state_fields", ())
        opt_specs = {
            k: moment_specs if k in param_like else spec_like(
                v, PartitionSpec())
            for k, v in self.state.opt_state.items()}
        state_specs = TrainState(
            params=spec_like(params, PartitionSpec()),
            opt_state=opt_specs,
            scaler=spec_like(self.state.scaler, PartitionSpec()),
            global_step=PartitionSpec(),
            skipped_steps=PartitionSpec())
        takes_gscale = "grad_scale" in inspect.signature(opt.step).parameters

        def train_fn(state, batch, rng):
            batch_specs = spec_like(batch, PartitionSpec(axis))

            @functools.partial(
                jax.shard_map, mesh=mesh,
                in_specs=(state_specs, batch_specs, PartitionSpec()),
                out_specs=(state_specs, spec_like(
                    {"loss": 0, "grad_norm": 0, "lr": 0, "overflow": 0,
                     "loss_scale": 0}, PartitionSpec())),
                check_vma=False)
            def inner(state, batch, rng):
                tm = jax.tree_util.tree_map
                with annotate("ds_fwd_bwd"):
                    grads, loss = accumulate(state, batch, rng)
                # the bucket stream — mean-reduced full grads on every
                # device (identical across the axis afterwards)
                with annotate("ds_overlap_bucket_sync"):
                    grads = overlap_lib.bucketed_allreduce(
                        grads, axis, n, bucket_elems, mode=mode, mean=True)
                loss = jax.lax.pmean(loss, axis)
                scale = state.scaler["loss_scale"]
                inv = 1.0 / scale
                finite = prec.grads_finite(grads) if precision.fp16 \
                    else jnp.asarray(True)
                grad_norm = _global_norm(grads)
                gscale = inv
                if cfg.gradient_clipping and cfg.gradient_clipping > 0:
                    gscale = inv * jnp.minimum(
                        1.0, cfg.gradient_clipping /
                        (grad_norm * inv + 1e-6))
                lr = lr_fn(state.global_step)

                # per-shard ZeRO update: slice each leaf to the moment
                # shard this device owns, step, gather the slices back
                idx = jax.lax.axis_index(axis)
                p_leaves, tdef = jax.tree_util.tree_flatten(state.params)
                g_leaves = jax.tree_util.tree_leaves(grads)

                def shard_leaf(x, entry):
                    if entry is None:
                        return x
                    d, sz = entry
                    return jax.lax.dynamic_slice_in_dim(x, idx * sz, sz, d)

                p_loc = jax.tree_util.tree_unflatten(
                    tdef, [shard_leaf(x, e) for x, e in zip(p_leaves, plan)])
                g_loc = jax.tree_util.tree_unflatten(
                    tdef, [shard_leaf(x, e) for x, e in zip(g_leaves, plan)])
                with annotate("ds_optimizer"):
                    if takes_gscale:
                        new_p_loc, new_opt = opt.step(
                            p_loc, g_loc, state.opt_state, lr,
                            grad_scale=gscale)
                    else:
                        g_loc = tm(lambda g: g * gscale, g_loc)
                        new_p_loc, new_opt = opt.step(p_loc, g_loc,
                                                      state.opt_state, lr)

                def gather_leaf(x, entry):
                    if entry is None:
                        return x
                    d, _ = entry
                    return jax.lax.all_gather(x, axis, axis=d, tiled=True)

                with annotate("ds_param_allgather"):
                    new_params = jax.tree_util.tree_unflatten(
                        tdef, [gather_leaf(x, e) for x, e in
                               zip(jax.tree_util.tree_leaves(new_p_loc),
                                   plan)])
                new_state = self._finish_explicit_state(
                    state, new_params, new_opt, finite, precision)
                return new_state, {
                    "loss": loss, "grad_norm": grad_norm * inv, "lr": lr,
                    "overflow": ~finite,
                    "loss_scale": new_state.scaler["loss_scale"]}

            return inner(state, batch, rng)

        return self._jit_explicit_comm(train_fn)

    def _sparse_grad_active(self):
        """True when the train step should exchange embedding gradients
        row-compressed (reference sparse_gradients, engine.py:195-202 +
        the CSR bucket split at :1459-1515). Requires the explicit-comm
        layout (pure DP, replicated params) since GSPMD otherwise reduces
        gradients implicitly with no collective to replace."""
        if not self._config.sparse_gradients_enabled:
            return False
        if not self._pure_dp_mesh() or self.zero_optimization_stage() > 0 \
                or self._offload_cfg.enabled \
                or self._compressed_comm_active():
            log_dist("sparse_gradients requires a pure-DP mesh with ZeRO "
                     "stage 0 (explicit grad exchange); falling back to "
                     "dense reduction", ranks=[0])
            return False
        if not self._sparse_leaf_paths():
            log_dist(
                "sparse_gradients enabled but the model declares no "
                "sparse_grad_params — falling back to dense reduction. "
                "(The declaration is deliberate: a name heuristic would "
                "silently drop gradient for tied embeddings, whose head "
                "term is dense over the vocabulary.)", ranks=[0])
            return False
        return True

    def _sparse_leaf_paths(self):
        # strictly opt-in: models declare which leaves have row-sparse
        # gradients (GPT2LMHeadModel does, when untied)
        pats = getattr(self.module, "sparse_grad_params", ())
        return tuple(p.lower() for p in pats)

    def _build_sparse_train_fn(self, loss_fn):
        """shard_map train step exchanging embedding grads as compressed
        rows: per-shard grads stay local, dense leaves psum, sparse leaves
        go through CSRTensor compress → all_gather(rows) → scatter-add
        (runtime/csr_tensor.py). Numerically exact: the row budget is the
        shard's token count, and every token touches one row."""
        from deepspeed_tpu.runtime.csr_tensor import CSRTensor
        mesh = self.mesh
        axis = mesh_lib.DATA_AXIS
        cfg = self._config
        lr_fn = self._lr_fn()
        opt = self.optimizer
        precision = self.precision
        accumulate = self._local_grad_accumulator(loss_fn, axis)
        sparse_pats = self._sparse_leaf_paths()
        spec_like = lambda tree, s: jax.tree_util.tree_map(  # noqa: E731
            lambda _: s, tree)
        state_specs = TrainState(
            params=spec_like(self.state.params, PartitionSpec()),
            opt_state=spec_like(self.state.opt_state, PartitionSpec()),
            scaler=spec_like(self.state.scaler, PartitionSpec()),
            global_step=PartitionSpec(),
            skipped_steps=PartitionSpec())

        def is_sparse_path(path):
            name = "/".join(str(getattr(k, "key", k)) for k in path).lower()
            return any(p in name for p in sparse_pats)

        def local_tokens(batch):
            # the CSR row budget must cover the LARGEST token stream in the
            # batch (a smaller auxiliary id array must not shrink it — the
            # exchange would silently drop gradient rows)
            counts = [int(np.prod(leaf.shape))
                      for leaf in jax.tree_util.tree_leaves(batch)
                      if jnp.issubdtype(leaf.dtype, jnp.integer)
                      and leaf.ndim >= 2]
            return max(counts) if counts else None

        def train_fn(state, batch, rng):
            batch_specs = spec_like(batch, PartitionSpec(axis))

            @functools.partial(
                jax.shard_map, mesh=mesh,
                in_specs=(state_specs, batch_specs, PartitionSpec()),
                out_specs=(state_specs, spec_like(
                    {"loss": 0, "grad_norm": 0, "lr": 0, "overflow": 0,
                     "loss_scale": 0}, PartitionSpec())),
                check_vma=False)
            def inner(state, batch, rng):
                tm = jax.tree_util.tree_map
                grads, loss = accumulate(state, batch, rng)
                scale = state.scaler["loss_scale"]
                tokens = local_tokens(batch)

                def reduce_leaf(path, g):
                    if tokens is not None and g.ndim == 2 \
                            and is_sparse_path(path) and tokens < g.shape[0]:
                        # row-compressed exchange (reference CSR allreduce)
                        csr = CSRTensor.from_dense(g, tokens)
                        all_idx = jax.lax.all_gather(csr.indices, axis)
                        all_val = jax.lax.all_gather(csr.values, axis)
                        out = jnp.zeros_like(g)
                        return out.at[all_idx.reshape(-1)].add(
                            all_val.reshape(-1, g.shape[1]), mode="drop")
                    return jax.lax.psum(g, axis)

                grads = jax.tree_util.tree_map_with_path(reduce_leaf, grads)
                # loss_fn averaged over the LOCAL shard; the exchange above
                # sums shard gradients, so normalize to the global mean
                dp = mesh.shape[axis]
                grads = tm(lambda g: g / dp, grads)
                loss = jax.lax.pmean(loss, axis)
                finite = prec.grads_finite(grads) if precision.fp16 \
                    else jnp.asarray(True)
                grad_norm = _global_norm(grads)
                inv = 1.0 / scale
                gscale = inv
                if cfg.gradient_clipping and cfg.gradient_clipping > 0:
                    gscale = inv * jnp.minimum(
                        1.0, cfg.gradient_clipping / (grad_norm * inv + 1e-6))
                lr = lr_fn(state.global_step)
                if "grad_scale" in inspect.signature(opt.step).parameters:
                    new_params, new_opt = opt.step(
                        state.params, grads, state.opt_state, lr,
                        grad_scale=gscale)
                else:
                    grads = tm(lambda g: g * gscale, grads)
                    new_params, new_opt = opt.step(state.params, grads,
                                                   state.opt_state, lr)
                new_state = self._finish_explicit_state(
                    state, new_params, new_opt, finite, precision)
                return new_state, {
                    "loss": loss, "grad_norm": grad_norm * inv, "lr": lr,
                    "overflow": ~finite,
                    "loss_scale": new_state.scaler["loss_scale"]}

            return inner(state, batch, rng)

        return self._jit_explicit_comm(train_fn)

    def _micro_loss_and_grads(self, state, micro_batch, rng, loss_fn=None):
        """(loss, gradients, stats) of one micro batch. ``loss_fn`` returns
        (loss, stats) as ``_loss_and_stats_fn``'s does: stats is what the
        model sowed into "stats", {} for a model that sows none."""
        if loss_fn is None:
            loss_fn = self._loss_and_stats_fn()
        keep_prob = self._keep_prob_fn()(state.global_step)
        scale = state.scaler["loss_scale"]

        cast_bf16 = self._config.grad_dtype == "bf16"

        def scaled_loss(p):
            if cast_bf16:
                # one whole-tree fp32→bf16 cast INSIDE the differentiated
                # function: cotangents (incl. layer-scan grad stacks)
                # materialize in bf16, and the model reads half the param
                # bytes per pass. The reference fp16 engine's grads-in-fp16
                # semantics (engine.py:624 model.half()).
                p = jax.tree_util.tree_map(
                    lambda x: x.astype(jnp.bfloat16)
                    if x.dtype == jnp.float32 else x, p)
            loss, stats = loss_fn(p, micro_batch, rng, keep_prob)
            return (loss * scale).astype(jnp.float32), (loss, stats)

        params = state.params
        if self._param_offload_host:
            # stream the host-resident params into HBM for compute; grads
            # come out device-resident (the swap-in of the reference's
            # partitioned_param_swapper, done by XLA's h2d DMA)
            params = jax.device_put(
                params, self.zero.device_param_shardings(params))
        grads, (loss, stats) = jax.grad(scaled_loss, has_aux=True)(params)
        grads = self.zero.constrain_grads(grads)
        return loss, grads, stats

    def _micro_loss(self, state, micro_batch, rng, loss_fn=None):
        """Forward-only loss (no grad) — the wall_clock_breakdown forward
        phase. Mirrors _micro_loss_and_grads' param handling."""
        if loss_fn is None:
            loss_fn = self._resolve_loss_fn()
        keep_prob = self._keep_prob_fn()(state.global_step)
        params = state.params
        if self._param_offload_host:
            params = jax.device_put(
                params, self.zero.device_param_shardings(params))
        if self._config.grad_dtype == "bf16":
            params = jax.tree_util.tree_map(
                lambda x: x.astype(jnp.bfloat16)
                if x.dtype == jnp.float32 else x, params)
        return loss_fn(params, micro_batch, rng, keep_prob)

    def _globalize_batch(self, batch):
        """Multi-host: every process feeds the FULL global batch (the
        reference gives each rank a per-rank loader instead); jax extracts
        each process's addressable shards. Single-process: plain upload.

        make_array_from_process_local_data is the wrong tool here: with
        the default global_shape it treats each process's rows as that
        process's PRIVATE shard and stacks them — the global batch
        silently doubles with duplicated rows (mean losses hide that:
        mean of duplicates == mean, but any path sensitive to WHICH rows
        a device holds — per-device compressed-gradient pieces, sample
        accounting — diverges from the single-process run) — and with an
        explicit global_shape it verifies cross-process equality with a
        host-side gloo all-reduce, one more independent collective for
        the multi-device interleave flake (ROADMAP standing backlog) to
        race. make_array_from_callback slices the local copy per
        addressable device with no collective at all."""
        if jax.process_count() == 1:
            return jax.tree_util.tree_map(jnp.asarray, batch)
        sh = mesh_lib.batch_sharding(self.mesh)

        def globalize(x):
            x = np.asarray(x)
            return jax.make_array_from_callback(
                x.shape, sh, lambda idx, _x=x: _x[idx])

        return jax.tree_util.tree_map(globalize, batch)

    def _ensure_ready(self, batch):
        # one-time branches: start-up's spans (telemetry/spans.span) cost
        # the steady-state path nothing
        if self.state is None:
            with tel_span("startup/state_init", self.telemetry):
                self._init_state(
                    example_batch=self._example_from_batch(batch))
        if self._jit_train_batch is None:
            with tel_span("startup/build_fns", self.telemetry):
                self._build_jit_fns()
        self._maybe_auto_resume()

    def _next_rng(self):
        self._rng, sub = jax.random.split(self._rng)
        return sub

    # ------------------------------------------------------------------
    # public training API
    # ------------------------------------------------------------------
    def train_batch(self, batch=None, data_iter=None):
        """One full optimizer step over gas×micro samples.

        `batch` may carry the full global batch (leading dim
        micro*gas[*dp]) or a micro batch (then gas must be 1); alternatively
        pass `data_iter` to pull gas micro-batches, like the reference
        PipelineEngine.train_batch(data_iter) (pipe/engine.py:250)."""
        if batch is None:
            assert data_iter is not None, "need batch or data_iter"
            micro = [next(data_iter) for _ in range(self.gradient_accumulation_steps())]
            batch = jax.tree_util.tree_map(
                lambda *xs: np.concatenate(
                    [np.asarray(x) for x in xs]),  # sync-ok: host loader data
                *micro)
        # state init inspects host-side shapes; globalize only after
        self._ensure_ready(batch)
        self._ensure_params_resident()
        batch = self._globalize_batch(batch)
        if self.flops_profiler is not None:
            self.flops_profiler.maybe_profile(batch)

        step_idx = self.global_steps
        # events recorded during this step (spans, swap I/O) carry it
        self.flight_recorder.set_step(step_idx)
        if self._trace_window is not None:
            self._trace_window.on_step_begin(step_idx)
        self.tput_timer.start()
        # the span measures host-side DISPATCH of the step (async under
        # jit — no sync); device-true step time comes from the boundary
        # window fold below. The same interval feeds the cluster
        # plane's per-rank SELF time (ISSUE 12): time spent blocked
        # INSIDE the dispatch call is where a healthy rank absorbs a
        # straggler's delay (backends that execute cross-process
        # collectives synchronously block right here), so host_step_s
        # excludes it — what remains is rank-attributable host work.
        # ISSUE 15: the hang_in_collective fault point sits BEFORE the
        # dispatch guard — the injected rank models "stuck elsewhere"
        # (its own watchdog sees no dispatch, its heartbeat keeps
        # beating), while its PEERS block inside the collective below
        # and their guard converts the stall into EXIT_HANG.
        _faults.fire("collective_enter", step=step_idx, engine=self)
        _t_disp = time.perf_counter()
        self._guard_enter("step", step_idx)
        try:
            with tel_span("train/step_dispatch", self.telemetry):
                if self._host_runner is not None:
                    metrics = self._host_offload_step(batch)
                elif self.wall_clock_breakdown() and not (
                        self._compressed_comm_active()
                        or self._sparse_grad_active()
                        or self._overlap_comm_active()):
                    # (1-bit / CSR / overlap paths keep their fused
                    # shard_map programs — their comm scheduling lives
                    # inside the step and cannot be split into phase
                    # programs)
                    metrics = self._train_batch_instrumented(batch)
                else:
                    self.state, metrics = self._jit_train_batch(
                        self.state, batch, self._next_rng())
        finally:
            self._guard_exit()
        self._tel_window_dispatch_s += time.perf_counter() - _t_disp
        self._fence_ref = metrics["loss"]
        # device scalars, read only at a telemetry fold
        self._model_stats = metrics.pop("model_stats", None)
        self.tput_timer.stop()

        gas = self.gradient_accumulation_steps()
        self.micro_steps += gas
        self.global_steps += 1
        self.global_samples += self.train_batch_size()
        self._record_metrics(metrics)
        if hasattr(self.lr_scheduler, "step"):
            self.lr_scheduler.step()
        self._moq_boundary(batch, metrics)
        self._elastic_commit()
        self._park_params()
        self._elastic_step()
        loss = metrics["loss"]
        self._telemetry_step(batch, loss)
        if self._trace_window is not None:
            self._trace_window.on_step_end(
                step_idx,   # sync-ok: config-gated trace-window close
                fence=lambda: jax.block_until_ready(loss))
        if self.global_steps % self.steps_per_print() == 0:
            self._report_progress(loss)
        return loss

    @staticmethod
    def _adopt_mpu(mpu, mesh, explicit_mesh):
        """Map a Megatron-style mpu object onto the mesh (the reference
        adopts mpu groups for TP, engine.py:636-641) — or reject loudly.
        On TPU, tensor parallelism IS the mesh 'model' axis: an mpu that
        agrees with the mesh is redundant-but-welcome; one that disagrees
        would silently train with the wrong sharding, so it is an error.
        When the mesh came from config defaults (model=1), the mpu's TP
        degree is adopted by rebuilding the mesh with model=mp."""
        mp = None
        for name in ("get_model_parallel_world_size",
                     "get_tensor_model_parallel_world_size"):
            if hasattr(mpu, name):
                mp = int(getattr(mpu, name)())
                break
        if mp is None:
            raise ValueError(
                "initialize(mpu=...) requires an object exposing "
                "get_model_parallel_world_size(); on TPU, express tensor "
                "parallelism as the mesh 'model' axis instead "
                "(make_mesh(MeshConfig(model=N)))")
        mesh_mp = mesh_lib.mesh_axis_size(mesh, mesh_lib.MODEL_AXIS)
        if mesh_mp == mp:
            return mesh
        if explicit_mesh or mesh_mp != 1:
            raise ValueError(
                f"mpu reports model_parallel_world_size={mp} but the mesh "
                f"'model' axis is {mesh_mp}; make them agree (or drop the "
                f"mpu argument — the mesh axis alone defines TP here)")
        # config-default mesh: adopt the mpu's TP degree
        shape = dict(mesh.shape)
        log_dist(f"adopting mpu model_parallel_world_size={mp} as the mesh "
                 f"'model' axis", ranks=[0])
        return mesh_lib.make_mesh(
            mesh_lib.MeshConfig(data=-1, model=mp,
                                pipe=shape.get(mesh_lib.PIPE_AXIS, 1),
                                seq=shape.get(mesh_lib.SEQ_AXIS, 1),
                                expert=shape.get(mesh_lib.EXPERT_AXIS, 1)),
            devices=list(mesh.devices.flat))

    def _train_batch_instrumented(self, batch):
        """wall_clock_breakdown for the fused train path (reference wraps
        every phase with synchronized timers, engine.py:1028-1047): the step
        splits into forward-loss, fwd+bwd-grads and optimizer-apply
        programs with a data-dependent readback as the fence after each —
        the TPU analog of the reference's cuda.synchronize-per-phase.
        Numerics match the fused program; while the flag is on, throughput
        pays one extra forward and loses cross-phase fusion, exactly as the
        reference pays its per-phase synchronize — a measurement mode, not
        the production path. The backward phase is reported as (grads
        program − forward program) since XLA computes fwd+bwd fused."""
        rng = self._next_rng()
        t0 = t_fwd = time.monotonic()
        lval = self._jit_loss_batch(self.state, batch, rng)
        float(jax.device_get(lval))  # fence: the readback waits for the value
        fwd_s = time.monotonic() - t0

        t0 = t_bwd = time.monotonic()
        grads, loss, _, _ = self._jit_grads_batch(self.state, batch, rng)
        float(jax.device_get(loss))
        fwdbwd_s = time.monotonic() - t0

        t0 = t_opt = time.monotonic()
        self.state, metrics = self._jit_apply_grads(self.state, grads, loss)
        float(jax.device_get(metrics["grad_norm"]))
        step_s = time.monotonic() - t0

        # each phase fence pays one scalar device-to-host readback on top
        # of the wait, so phases are reported NET of it. metrics["lr"] is
        # already computed once the grad_norm fence returns, so reading it
        # measures the readback alone.
        t0 = t_fence = time.monotonic()
        float(jax.device_get(metrics["lr"]))
        fence_s = time.monotonic() - t0

        self.timers(FORWARD_GLOBAL_TIMER).elapsed_ += \
            max(fwd_s - fence_s, 0.0)
        # grads program = fwd+bwd fused; report bwd as its excess over fwd
        self.timers(BACKWARD_GLOBAL_TIMER).elapsed_ += \
            max(fwdbwd_s - fwd_s, 0.0)
        self.timers(STEP_GLOBAL_TIMER).elapsed_ += \
            max(step_s - fence_s, 0.0)
        self.timers(FENCE_TIMER).elapsed_ += fence_s

        # the instrumented phases are REAL device measurements (each one
        # fenced) — feed them to the span histograms so the telemetry
        # stream carries per-phase times whenever this mode is on
        # (t0_mono: the start of the PROGRAM the phase was timed in, on
        # span()'s clock; dur_s is the derived figure, so backward's
        # event is shorter than the fwd+bwd program it starts with)
        reg = self.telemetry
        for tag, dur, t0_mono in (
                ("train/forward", max(fwd_s - fence_s, 0.0), t_fwd),
                ("train/backward", max(fwdbwd_s - fwd_s, 0.0), t_bwd),
                ("train/optimizer", max(step_s - fence_s, 0.0), t_opt),
                ("train/fence", fence_s, t_fence)):
            reg.histogram(f"span/{tag}").observe(dur)
            self.flight_recorder.record("span", tag=tag, dur_s=dur,
                                        t0_mono=t0_mono)

        if self.global_steps % self.steps_per_print() == 0:
            # per-step means over the print interval (reference resets each
            # log; cumulative totals would read as ever-growing phase times)
            self.timers.log([FORWARD_GLOBAL_TIMER, BACKWARD_GLOBAL_TIMER,
                             STEP_GLOBAL_TIMER], reset=True,
                            normalizer=max(self.steps_per_print(), 1))
        return metrics

    def wall_clock_times(self, reset=False):
        """Per-phase seconds accumulated since the last reset/log by the
        instrumented path ({'forward', 'backward', 'step'}; offload engines
        report 'backward' as the fused fwd+bwd program and 'step' as the
        host optimizer). Empty unless wall_clock_breakdown is enabled."""
        out = {}
        for name in (FORWARD_GLOBAL_TIMER, BACKWARD_GLOBAL_TIMER,
                     STEP_GLOBAL_TIMER, FENCE_TIMER):
            if name in self.timers.timers:
                out[name] = self.timers(name).elapsed(reset=reset)
        return out

    def _host_offload_step(self, batch):
        """Device grads → host SIMD Adam (cpu/NVMe state) → device params.
        The ZeRO-Offload step (reference stage2.py:747-925 + cpu_adam).

        With ``zero_optimization.overlap_comm`` and gas > 1, gradients
        stream to the host per microbatch while the device computes the
        next one (the reference's reduction-stream overlap,
        stage2.py:679-746); otherwise the accumulation runs fused on
        device and only the final tree transfers."""
        gas = self.gradient_accumulation_steps()
        if gas > 1 and self._config.zero_config.overlap_comm \
                and not self._offload_streamed():
            # host-fold overlap only helps when the step runs on THIS host;
            # the streamed tier accumulates on device (the gas scan in
            # accumulate_grads) and never moves gradients off the device
            return self._host_offload_step_overlapped(batch, gas)
        wcb = self.wall_clock_breakdown()
        t0 = time.perf_counter()
        grads, loss, finite, scaled_norm = self._jit_grads_batch(
            self.state, batch, self._next_rng())
        if wcb:
            # phase accounting for offload (the flag must not silently
            # no-op here): 'backward' = the fused fwd+bwd device program,
            # 'step' = host transfer+SIMD+push
            float(jax.device_get(loss))
            self.timers(BACKWARD_GLOBAL_TIMER).elapsed_ += \
                time.perf_counter() - t0
            t0 = time.perf_counter()
        metrics = self._host_apply_grads(grads, loss, finite=finite,
                                         scaled_norm=scaled_norm)
        if wcb:
            self.timers(STEP_GLOBAL_TIMER).elapsed_ += \
                time.perf_counter() - t0
        return metrics

    def _host_offload_step_overlapped(self, batch, gas):
        """Per-micro dispatch: while the device computes micro k+1, micro
        k's gradient leaves copy d2h (`copy_to_host_async`) and fold into
        fp32 host accumulators; the final SIMD step + h2d push then run on
        the host tree via the streamed step. Device compute hides
        (gas-1)/gas of the transfer+accumulate time."""
        lead = jax.tree_util.tree_leaves(batch)[0].shape[0]
        assert lead % gas == 0, (
            f"train_batch got leading dim {lead} not divisible by "
            f"gradient_accumulation_steps={gas}")
        m = lead // gas
        inv_gas = np.float32(1.0 / gas)

        wcb = self.wall_clock_breakdown()
        t0 = time.perf_counter()
        acc = None
        losses = []
        pending = None

        def fold(leaves):
            nonlocal acc
            if acc is None:
                acc = [np.asarray(g, np.float32) * inv_gas for g in leaves]
            else:
                for i, g in enumerate(leaves):
                    acc[i] += np.asarray(g, np.float32) * inv_gas

        for k in range(gas):
            micro = jax.tree_util.tree_map(
                lambda x: x[k * m:(k + 1) * m], batch)
            loss_k, grads_k = self._jit_micro_grads(self.state, micro,
                                                    self._next_rng())
            losses.append(loss_k)
            leaves_k = jax.tree_util.tree_leaves(grads_k)
            for g in leaves_k:
                if hasattr(g, "copy_to_host_async"):
                    try:
                        g.copy_to_host_async()
                    except Exception:
                        pass
            if pending is not None:
                fold(pending)   # overlaps micro k's device compute
            pending = leaves_k
        fold(pending)
        loss = sum(float(jax.device_get(l)) for l in losses) / gas

        # norm on host (BLAS dot per leaf): serves clipping AND the fp16
        # finite check — inf/nan gradients make the norm non-finite
        scaled_norm = float(np.sqrt(sum(
            float(np.dot(a.ravel(), a.ravel())) for a in acc)))
        finite = bool(np.isfinite(scaled_norm)) if self.precision.fp16 \
            else True
        grads_tree = jax.tree_util.tree_unflatten(
            jax.tree_util.tree_structure(self.state.params), acc)
        if wcb:
            # 'backward' = device compute with the overlapped d2h+fold
            # (the losses device_get above fenced the last micro)
            self.timers(BACKWARD_GLOBAL_TIMER).elapsed_ += \
                time.perf_counter() - t0
            t0 = time.perf_counter()
        metrics = self._host_apply_grads(grads_tree, jnp.float32(loss),
                                         finite=finite,
                                         scaled_norm=scaled_norm)
        if wcb:
            self.timers(STEP_GLOBAL_TIMER).elapsed_ += \
                time.perf_counter() - t0
        return metrics

    def _host_apply_grads(self, grads, loss, finite=None, scaled_norm=None):
        """Shared offload update, pipelined: overflow/norm resolve from two
        device scalars, then the leaves stream d2h while earlier leaves run
        the SIMD step and updated leaves push h2d — the reference's
        overlapped offload step (stage2.py:747-925 + pipelined swapper),
        expressed with JAX async transfers (see
        HostOffloadOptimizer.step_streamed).

        ``finite``/``scaled_norm`` are device scalars when coming from the
        fused grads fn; the forward/backward/step path computes them here
        (also on device — the host never scans the gradient tree)."""
        fp16 = self.precision.fp16
        scale = float(jax.device_get(self.state.scaler["loss_scale"])) \
            if fp16 else 1.0

        # overflow-skip applies under fp16 only, matching _apply_grads —
        # bf16/fp32 runs step unconditionally like the device path. Resolve
        # the device finite scalar BEFORE transferring the gradient tree so
        # skipped steps don't pull the full model's grads just to drop them.
        if finite is not None:
            finite = bool(jax.device_get(finite))
        elif fp16:
            if self._jit_grads_finite is None:
                self._jit_grads_finite = jax.jit(prec.grads_finite)
            finite = bool(jax.device_get(self._jit_grads_finite(grads)))
        else:
            finite = True
        new_scaler = prec.update_scaler(self.state.scaler, self.precision,
                                        jnp.asarray(finite))
        step_now = int(jax.device_get(self.state.global_step))
        lr = float(jax.device_get(self._lr_fn()(jnp.asarray(step_now))))
        if not finite:
            self.state = TrainState(
                params=self.state.params, opt_state=self.state.opt_state,
                scaler=new_scaler, global_step=self.state.global_step,
                skipped_steps=self.state.skipped_steps + 1)
            return {"loss": loss, "grad_norm": jnp.float32(0.0),
                    "lr": jnp.float32(lr), "overflow": jnp.asarray(True),
                    "loss_scale": new_scaler["loss_scale"]}

        if scaled_norm is None:
            if self._jit_grad_norm is None:
                self._jit_grad_norm = jax.jit(_global_norm)
            scaled_norm = self._jit_grad_norm(grads)
        norm = float(jax.device_get(scaled_norm)) / scale

        # fold unscale + clip into one coefficient, consumed inside the
        # native step's gradient read — no host-side rescale pass
        coef = 1.0 / scale
        clip = self._config.gradient_clipping
        if clip and clip > 0 and norm > clip:
            coef *= clip / (norm + 1e-6)

        out_dtype = self.precision.compute_dtype
        if self._offload_streamed():
            # device-streamed tier: the update runs on the accelerator with
            # state in pinned_host — gradients never leave the device
            new_leaves = self._host_runner.step(
                jax.tree_util.tree_leaves(grads), lr, grad_scale=coef,
                out_dtype=out_dtype)
        elif self._param_swapper is not None \
                and self._param_swapper.pipeline_write \
                and self.quantizer is None:
            # (MoQ reads state.params at the step boundary, which this
            # shortcut leaves stale — quantizing engines keep the push)
            # pipelined NVMe park, host-optimizer shortcut: each leaf's
            # updated compute-dtype copy comes OUT of the SIMD step on the
            # host, so park it straight to the write-behind queue — no h2d
            # push + d2h re-read round trip. The device copies that fed
            # fwd+bwd are stale now; _park_params just frees them.
            swapper = self._param_swapper

            def park(i, host_arr):
                swapper.write_behind(i, host_arr)
                return None

            self._host_runner.step_streamed(
                jax.tree_util.tree_leaves(grads), lr, grad_scale=coef,
                push_fn=park, out_dtype=out_dtype)
            self._parked_via_push = True
            new_leaves = jax.tree_util.tree_leaves(self.state.params)
        else:
            shard_leaves = jax.tree_util.tree_leaves(
                self.state_shardings.params)
            # on the CPU backend device_put ALIASES host memory — the
            # runner's staging buffers are reused next step, so alias would
            # corrupt the live params; accelerator backends copy over the
            # wire
            aliases_host = self.mesh.devices.flat[0].platform == "cpu"

            def push(i, host_arr):
                # async dispatch: the h2d copy overlaps the remaining leaf
                # steps, and the next step's jit consumes the futures
                # directly
                if aliases_host:
                    host_arr = np.array(host_arr, copy=True)
                return jax.device_put(host_arr, shard_leaves[i])

            new_leaves = self._host_runner.step_streamed(
                jax.tree_util.tree_leaves(grads), lr, grad_scale=coef,
                push_fn=push, out_dtype=out_dtype)
        new_params = jax.tree_util.tree_unflatten(
            jax.tree_util.tree_structure(self.state.params), new_leaves)
        self.state = TrainState(
            params=new_params,
            opt_state=self.state.opt_state,
            scaler=new_scaler,
            global_step=self.state.global_step + 1,
            skipped_steps=self.state.skipped_steps)
        return {"loss": loss, "grad_norm": jnp.float32(norm),
                "lr": jnp.float32(lr), "overflow": jnp.asarray(False),
                "loss_scale": new_scaler["loss_scale"]}

    def forward(self, batch):
        """Parity shim: computes loss+grads for one micro batch and stashes
        them for `backward`/`step` (the reference runs fwd here and autograd
        later; under XLA fwd+bwd are one fused program)."""
        # state init inspects host-side shapes; globalize only after
        self._ensure_ready(batch)
        self._ensure_params_resident()
        batch = self._globalize_batch(batch)
        if self.wall_clock_breakdown():
            self.timers(FORWARD_MICRO_TIMER).start()
        # the micro program's loss reduction is a cross-process
        # collective on a dp mesh — guard it like the fused dispatch
        # (a dead peer parks this call forever otherwise, ISSUE 15).
        # Own kind: each jitted program gets its own first-occurrence
        # compile allowance
        self._guard_enter("micro", self.global_steps)
        try:
            loss, grads = self._jit_micro_grads(self.state, batch,
                                                self._next_rng())
        finally:
            self._guard_exit()
        if self.wall_clock_breakdown():
            self.timers(FORWARD_MICRO_TIMER).stop()
        self._pending_loss = loss
        self._pending_micro = (loss, grads)
        self._moq_batch = batch   # last micro batch, for eigenvalue at step()
        return loss

    __call__ = forward

    def backward(self, loss=None, allreduce_gradients=True):
        """Accumulate the stashed micro-grads (reference engine.py:1077)."""
        assert self._pending_micro is not None, "forward() must precede backward()"
        if self.wall_clock_breakdown():
            self.timers(BACKWARD_MICRO_TIMER).start()
        mloss, grads = self._pending_micro
        self._pending_micro = None
        gas = self.gradient_accumulation_steps()
        scaled = jax.tree_util.tree_map(
            lambda g: g.astype(jnp.float32) / gas, grads)
        if self._pending_grads is None:
            self._pending_grads = scaled
            self._accum_loss = mloss / gas
        else:
            self._pending_grads = jax.tree_util.tree_map(
                jnp.add, self._pending_grads, scaled)
            self._accum_loss = self._accum_loss + mloss / gas
        self.micro_steps += 1
        if self.wall_clock_breakdown():
            self.timers(BACKWARD_MICRO_TIMER).stop()
        return loss if loss is not None else mloss

    def step(self):
        """Optimizer step at GAS boundaries (reference engine.py:1234)."""
        if self.micro_steps % self.gradient_accumulation_steps() != 0:
            return  # not at boundary — reference also early-outs
        assert self._pending_grads is not None, "backward() must precede step()"
        self.flight_recorder.set_step(self.global_steps)
        if self.wall_clock_breakdown():
            self.timers(STEP_MICRO_TIMER).start()
        # own kind ("apply", not "step"): _jit_apply_grads compiles on
        # ITS first dispatch — sharing the fused path's kind would
        # spend the compile allowance on the wrong program
        self._guard_enter("apply", self.global_steps)
        try:
            if self._host_runner is not None:
                metrics = self._host_apply_grads(self._pending_grads,
                                                 self._accum_loss)
            else:
                self.state, metrics = self._jit_apply_grads(
                    self.state, self._pending_grads, self._accum_loss)
        finally:
            self._guard_exit()
        self._fence_ref = metrics["loss"]
        self._pending_grads = None
        self._accum_loss = None
        self.global_steps += 1
        self.global_samples += self.train_batch_size()
        self._record_metrics(metrics)
        if hasattr(self.lr_scheduler, "step"):
            self.lr_scheduler.step()
        if self.wall_clock_breakdown():
            self.timers(STEP_MICRO_TIMER).stop()
        self._moq_boundary(getattr(self, "_moq_batch", None), metrics)
        self._elastic_commit()
        self._park_params()
        self._elastic_step()
        self._telemetry_step(getattr(self, "_moq_batch", None),
                             metrics["loss"])
        if self.global_steps % self.steps_per_print() == 0:
            self._report_progress(metrics["loss"])

    def _moq_boundary(self, batch, metrics):
        """MoQ hook at every optimizer-step boundary (reference
        engine.py:1199-1206 quantizer call in _take_model_step +
        eigenvalue computation at :1250-1257)."""
        q = self.quantizer
        if q is None:
            return
        if self.global_steps < self._config.quantize_training_config.\
                schedule_offset:
            return
        eigenvalues = None
        if self.eigenvalue is not None and batch is not None and \
                q.any_precision_switch() and \
                self.global_steps % self.eigenvalue.gas_boundary_resolution \
                == 0:
            loss_fn = self._resolve_loss_fn()

            def params_loss(p):
                return loss_fn(p, batch, jax.random.PRNGKey(0),
                               jnp.float32(1.0))
            try:
                eigenvalues = self.eigenvalue.compute_layer_eigenvalues(
                    params_loss, self.state.params, self._next_rng())
            except Exception as e:  # curvature is advisory, never fatal
                logger.warning(f"eigenvalue computation failed: {e}")
        overflow = bool(jax.device_get(metrics.get("overflow", False)))
        new_params = q.quantize_tree(self.state.params, overflow=overflow,
                                     eigenvalues=eigenvalues,
                                     key=self._next_rng())
        self.state = TrainState(params=new_params,
                                opt_state=self.state.opt_state,
                                scaler=self.state.scaler,
                                global_step=self.state.global_step,
                                skipped_steps=self.state.skipped_steps)

    def eval_batch(self, batch):
        self._ensure_params_resident()
        # state init inspects host-side shapes; globalize only after
        self._ensure_ready(batch)
        batch = self._globalize_batch(batch)
        return self._jit_eval(self.state, self._model_inputs(batch))

    def zero_grad(self):
        self._pending_grads = None

    # ------------------------------------------------------------------
    # bookkeeping / reporting
    # ------------------------------------------------------------------
    def _record_metrics(self, metrics):
        self._last_lr = metrics["lr"]
        self._last_grad_norm = metrics["grad_norm"]
        if self._config.tensorboard_config.enabled:
            host = {k: float(jax.device_get(v)) for k, v in metrics.items()}
            self.scalar_history.append((self.global_steps, host))
            writer = self._summary_writer()
            if writer is not None:
                # reference tags, engine.py:1095-1105 / :1272-1298
                writer.add_scalar("Train/Samples/train_loss", host["loss"],
                                  self.global_samples)
                writer.add_scalar("Train/Samples/lr", host["lr"],
                                  self.global_samples)
                writer.add_scalar("Train/Samples/loss_scale",
                                  host["loss_scale"], self.global_samples)
                writer.add_scalar("Train/Samples/grad_norm",
                                  host["grad_norm"], self.global_samples)
                if self.global_steps % self.steps_per_print() == 0:
                    writer.flush()

    # ------------------------------------------------------------------
    # unified telemetry (deepspeed_tpu/telemetry)
    # ------------------------------------------------------------------
    def _install_comm_wire_model(self, plan):
        """Trace-time bytes-on-wire cost model for the hierarchical
        exchange (ISSUE 10): the bucket plan + per-bucket policy are
        static, so each phase's per-device, per-step wire bytes are one
        host-side dict computed once — the per-step telemetry just
        advances counters by it (sync-free)."""
        from deepspeed_tpu.parallel import overlap
        leaves = jax.tree_util.tree_leaves(self.state.params)
        buckets = overlap.plan_buckets([l.shape for l in leaves],
                                       plan.bucket_elems, plan.world)
        flags = overlap.plan_bucket_compression(buckets, plan)
        self.flight_recorder.record(
            "comm_hierarchy_plan", buckets=len(buckets),
            compressed=int(sum(flags)), inter=plan.inter,
            intra=plan.intra, policy=plan.compression,
            min_bucket_bytes=plan.min_bucket_bytes)
        self._comm_wire_model = {
            "warmup": overlap.hierarchy_wire_bytes(
                buckets, [False] * len(buckets), plan),
            "compressed": overlap.hierarchy_wire_bytes(buckets, flags,
                                                       plan),
        }
        self.comm_hierarchy = plan

    def _comm_wire_step(self):
        """Per-step comm accounting for the compressed train paths: the
        onebit_freeze ring event at the warmup→compressed transition,
        and (hierarchical path only) the ``comm/bytes_on_wire/*``
        counter advance from the trace-time cost model. Which phase ran
        is mirrored from the host counters — the optimizer's own count
        lives on device and reading it back would be a sync. fp16
        overflow skips lag the optimizer count behind global_steps;
        ``self.skipped_steps`` (the steps_per_print-boundary-synced
        mirror) corrects for them, so the mirror can misattribute at
        most the steps between an overflow and the next boundary.
        Returns the step's byte dict or None."""
        if not self._compressed_comm_active():
            return None
        freeze = int(getattr(self.optimizer, "freeze_step", 0) or 0)
        frozen = (self.global_steps - self.skipped_steps) > freeze
        model = getattr(self, "_comm_wire_model", None)
        if frozen and not getattr(self, "_onebit_freeze_recorded", False):
            self._onebit_freeze_recorded = True
            self.flight_recorder.record(
                "onebit_freeze", step=self.global_steps,
                freeze_step=freeze, hierarchical=model is not None)
        if model is None:
            return None
        w = model["compressed" if frozen else "warmup"]
        reg = self.telemetry
        reg.counter("comm/bytes_on_wire/intra").inc(w["intra"])
        reg.counter("comm/bytes_on_wire/inter").inc(w["inter"])
        reg.counter("comm/bytes_on_wire/inter_uncompressed").inc(
            w["inter_uncompressed"])
        reg.gauge("comm/bytes_per_step/intra").set(w["intra"])
        reg.gauge("comm/bytes_per_step/inter").set(w["inter"])
        reg.gauge("comm/bytes_per_step/inter_uncompressed").set(
            w["inter_uncompressed"])
        return w

    def _telemetry_step(self, batch, loss):
        """Per-step recording (sync-free) + the steps_per_print-boundary
        window fold. Between boundaries only host counters move; AT the
        boundary the loss readback — the same fence _report_progress
        pays right after — closes a wall-clock window whose mean is the
        honest per-step time (the SynchronizedWallClockTimer
        sync-per-read pattern, retired). The boundary readback is also
        where the watchdog's NaN/inf rule sees the loss — the one
        fence the anomaly layer is allowed to ride (ISSUE 6)."""
        reg = self.telemetry
        reg.counter("train/steps").inc()
        reg.counter("train/samples").inc(self.train_batch_size())
        tokens = 0
        if isinstance(batch, dict) and "input_ids" in batch:
            tokens = int(np.prod(batch["input_ids"].shape))
        if tokens:
            reg.counter("train/tokens").inc(tokens)
        self._tel_window_tokens += tokens
        # swap tier: host seconds this step actually BLOCKED on disk I/O
        # (the pipelined schedules shrink this toward zero while the
        # bytes_read/written counters keep moving — I/O hidden behind
        # compute). Host timers only, sync-free.
        stall = 0.0
        have_swap = self._param_swapper is not None
        if have_swap:
            stall += self._param_swapper.take_stall_s()
        opt_swapper = getattr(self._host_runner, "swapper", None)
        if opt_swapper is not None:
            have_swap = True
            stall += opt_swapper.take_stall_s()
        if have_swap:
            reg.histogram("swap/stall_s").observe(stall)
            if self.watchdog is not None:
                # host wall timer the swapper already kept — no fence
                self.watchdog.observe_swap_stall(
                    stall, step=self.global_steps)
        wire = self._comm_wire_step()
        self.flight_recorder.record(
            "step", step=self.global_steps, tokens=tokens,
            samples=self.train_batch_size(),
            **({"swap_stall_s": stall} if have_swap else {}),
            **({"comm_intra_bytes": wire["intra"],
                "comm_inter_bytes": wire["inter"]} if wire else {}))
        if self.global_steps % self.steps_per_print() != 0:
            return
        # per-rank SELF step time (ISSUE 12): host time this rank OWNS
        # per step — window wall time to ARRIVE at this fence
        # (pre-readback stamp) minus the seconds spent blocked inside
        # the step-dispatch calls. In synchronous SPMD every rank's
        # FENCED wall time converges to the slowest rank, and a
        # backend that executes cross-process collectives synchronously
        # parks the healthy rank inside dispatch — so only the
        # remainder (driver loop, park/unpark, swap stalls, GC pauses,
        # an injected sleep) is attributable to THIS rank. First
        # (compile) window dropped like the fenced one.
        t_arrive = time.perf_counter()
        steps_in_window = self.global_steps - self._tel_window_step0
        if steps_in_window > 0 and self._tel_window_t0 is not None \
                and self._tel_window_step0 > 0:
            self._tel_last_host_step_s = max(
                (t_arrive - self._tel_window_t0
                 - self._tel_window_dispatch_s), 0.0) / steps_in_window
            reg.histogram("train/host_step_s").observe(
                self._tel_last_host_step_s)
        lval = float(jax.device_get(loss))  # sync-ok: steps_per_print boundary
        self.flight_recorder.record("loss", step=self.global_steps,
                                    loss=lval)
        if self.watchdog is not None:
            self.watchdog.check_loss(lval, step=self.global_steps)
        self._telemetry_fold(batch)
        self._telemetry_export()
        # ISSUE 12: cross-rank aggregation rides the fence the loss
        # readback above already paid — every rank reaches this exact
        # boundary in SPMD lockstep, so the allgather is aligned. The
        # just-closed window's step time is threaded directly (the
        # process-wide registry may hold another engine's history).
        if self._cluster is not None:
            # the loss readback above is NOT a sufficient fence for the
            # exchange: the loss chain is independent of the grad
            # allreduces, so the step program's collectives can still
            # be in flight (see _fence_step_program)
            self._fence_step_program()
            self._guard_enter("exchange", self.global_steps)
            try:
                self._cluster.exchange_from_registry(
                    loss=lval, step=self.global_steps,
                    overrides={"step_time_s": self._cluster_step_value(),
                               "swap_stall_s": stall if have_swap
                               else None})
            finally:
                self._guard_exit()
            # re-open the window AFTER the exchange (same rule as the
            # fold's MFU-pricing re-stamp): the allgather blocks until
            # the SLOWEST rank arrives, and charging that wait to the
            # next window would hand every healthy rank the straggler's
            # time — the exact skew signal this plane exists to expose
            self._tel_window_dispatch_s = 0.0
            self._tel_window_t0 = time.perf_counter()
        self._tel_last_fence_ts = time.time()

    def _cluster_step_value(self):
        """The per-rank step time the cluster vector carries (ISSUE
        12): single-process the fenced window mean IS self time (no
        peer to wait on); multi-process the host-arrival component —
        the fenced figure converges to the slowest rank under the
        boundary collectives, which would blind the straggler rule."""
        if jax.process_count() == 1:
            return self._tel_last_step_s
        return self._tel_last_host_step_s

    def _telemetry_priced(self):
        """Whether the MFU cost analysis may be priced: an explicit
        ``lower().compile()`` re-traces the train fn outside the jit
        call cache (a real recompile when no persistent XLA cache is
        on), so it only happens — ONCE per engine — for engines whose
        config opted into a telemetry export, or on an explicit
        telemetry_flush()."""
        return self._config.monitor_config.enabled \
            or self._config.tensorboard_config.enabled

    def _telemetry_fold(self, batch=None, price_mfu=None):
        """Close the open measurement window (caller has fenced): one
        step-time observation (window mean), throughput gauges, MFU, and
        the memory gauges. Windows containing step 0 are dropped — they
        measure compile, not steady state."""
        reg = self.telemetry
        now = time.perf_counter()
        if self._tel_window_t0 is not None:
            steps = self.global_steps - self._tel_window_step0
            window_s = now - self._tel_window_t0
            if steps > 0 and window_s > 0 and self._tel_window_step0 > 0:
                step_s = window_s / steps
                self._tel_last_step_s = step_s
                reg.histogram("train/step_time_s").observe(step_s)
                self.flight_recorder.record(
                    "window", step=self.global_steps, steps=steps,
                    step_s=step_s)
                if self.watchdog is not None:
                    # outlier check on the already-fenced window mean
                    self.watchdog.observe_step_time(
                        step_s, step=self.global_steps)
                reg.gauge("train/samples_per_sec").set(
                    steps * self.train_batch_size() / window_s)
                if self._tel_window_tokens:
                    reg.gauge("train/tokens_per_sec").set(
                        self._tel_window_tokens / window_s)
                if price_mfu is None:
                    price_mfu = self._telemetry_priced()
                self._telemetry_mfu(batch, step_s, price=price_mfu)
        self._tel_window_step0 = self.global_steps
        self._tel_window_tokens = 0
        self._telemetry_memory_gauges()
        self._telemetry_model_stats()
        # open the next window AFTER the fold's own work (the one-time
        # MFU pricing retrace can take seconds — charging it to the
        # next window would corrupt its step-time observation)
        self._tel_window_dispatch_s = 0.0
        self._tel_window_t0 = time.perf_counter()

    def _telemetry_model_stats(self):
        """The last step's model statistics (what the model sowed into
        "stats": a MoE router's balance and auxiliary losses) as gauges,
        each under the name the model's ``stat_gauges`` gives its variable.
        The caller has fenced."""
        stats = getattr(self, "_model_stats", None)
        if not stats:
            return
        gauges = self.module.stat_gauges
        host = jax.device_get(stats)  # sync-ok: telemetry fold, caller fenced
        for name, value in host.items():
            self.telemetry.gauge(gauges[name]).set(float(value))

    def _telemetry_mfu(self, batch, step_s, price=False):
        """MFU as a first-class logged metric: flops/step from the
        COMPILED train step's XLA cost analysis (exact, fusion-aware)
        over the mesh's peak. Host-offload engines skip it: their step
        is not one compiled program."""
        if self._host_runner is not None or step_s <= 0:
            return
        if self._tel_flops_per_step is None and batch is not None and price:
            from deepspeed_tpu.profiling.flops_profiler import \
                compiled_step_flops
            self._tel_flops_per_step = compiled_step_flops(
                self._jit_train_batch, self.state, batch, self._rng)
        flops = self._tel_flops_per_step
        if not flops:
            return
        from deepspeed_tpu.profiling.flops_profiler import PEAK_BF16_FLOPS
        reg = self.telemetry
        # cost_analysis() of a partitioned module reports PER-DEVICE
        # flops (verified on an 8-device SPMD matmul: 2N^3/8, not
        # 2N^3): per-device flops over ONE device's peak IS the MFU
        # under uniform sharding; the flops gauge scales to the global
        # step figure
        ndev = int(self.mesh.devices.size)
        dev = self.mesh.devices.flat[0]
        reg.gauge("train/flops_per_step").set(flops * ndev)
        # MFU only against a recorded peak: a device kind outside the
        # table (every CPU mesh) gets the flops gauge and no train/mfu
        peak = PEAK_BF16_FLOPS.get(dev.device_kind)
        if peak:
            reg.gauge("train/mfu").set(flops / step_s / peak)

    def _telemetry_memory_gauges(self):
        """Satellite of the scalar stream: host RSS and, where the
        backend exposes it, per-device HBM (utils/memory.py)."""
        from deepspeed_tpu.utils import memory as memory_lib
        reg = self.telemetry
        for k, v in memory_lib.memory_metrics().items():
            reg.gauge(f"memory/{k}").set(v)

    def _telemetry_exporters(self):
        mc = self._config.monitor_config
        out = []
        if mc.enabled:
            if self._tel_exporter is None:
                from deepspeed_tpu.telemetry.registry import (
                    JsonlExporter, _process_rank)
                path = mc.jsonl_path or os.path.join(
                    mc.output_path,
                    f"telemetry_rank{_process_rank()}.jsonl")
                try:
                    self._tel_exporter = JsonlExporter(
                        path, self.telemetry,
                        max_bytes=int(mc.jsonl_max_mb * 2**20),
                        max_files=mc.jsonl_max_files)
                except OSError as e:
                    logger.warning(f"telemetry JSONL unavailable: {e}")
                    self._tel_exporter = False
            if self._tel_exporter:
                out.append(self._tel_exporter)
        if self._config.tensorboard_config.enabled:
            if self._tel_bridge is None:
                from deepspeed_tpu.telemetry.registry import SummaryBridge
                writer = self._summary_writer()
                self._tel_bridge = SummaryBridge(writer, self.telemetry) \
                    if writer is not None else False
            if self._tel_bridge:
                out.append(self._tel_bridge)
        return out

    def _telemetry_export(self):
        exporters = self._telemetry_exporters()
        if not exporters:
            return
        snap = self.telemetry.snapshot()
        for e in exporters:
            e.export(self.global_steps, snapshot=snap)

    def telemetry_snapshot(self):
        """The current registry snapshot (no fence, no fold)."""
        return self.telemetry.snapshot()

    def telemetry_flush(self, batch=None):
        """Fence, fold the open window, export, and return the
        snapshot — a programmatic steps_per_print boundary for harness /
        notebook use off the print cadence. Pass the current batch to
        (lazily) price MFU."""
        if self.state is not None:
            # fence on a DERIVED value: a device_get of global_step
            # itself would populate that array's host-side npy cache, and
            # a caller timing a later readback of it would measure ~0
            int(jax.device_get(self.state.global_step + 0))  # sync-ok: flush
        self._telemetry_fold(batch, price_mfu=batch is not None)
        self._telemetry_export()
        return self.telemetry.snapshot()

    def _summary_writer(self):
        if getattr(self, "_summary_writer_obj", None) is None:
            try:
                from deepspeed_tpu.utils.monitor import SummaryEventWriter
                tb = self._config.tensorboard_config
                self._summary_writer_obj = SummaryEventWriter(
                    tb.output_path, tb.job_name)
            except Exception as e:
                logger.warning(f"summary writer unavailable: {e}")
                self._summary_writer_obj = False
        return self._summary_writer_obj or None

    def _sync_skipped_steps(self):
        if self.state is not None:
            self.skipped_steps = int(jax.device_get(self.state.skipped_steps))

    def _report_progress(self, loss):
        lr = self.get_lr()
        self._sync_skipped_steps()
        log_dist(f"step={self.global_steps}, skipped={self.skipped_steps}, "
                 f"loss={float(jax.device_get(loss)):.6f}, lr={lr}, "
                 f"loss_scale={self.loss_scale}", ranks=[0])

    # ------------------------------------------------------------------
    # dataloader factory (reference deepspeed_io engine.py:928)
    # ------------------------------------------------------------------
    def deepspeed_io(self, dataset, batch_size=None, route="train",
                     data_sampler=None, collate_fn=None, num_local_io_workers=None):
        # each yielded batch is the *global* micro batch — GSPMD shards it
        # over the data axis (the reference instead gives each rank a
        # per-rank loader of micro_batch_size, dataloader.py:33)
        batch_size = batch_size or (self.train_micro_batch_size_per_gpu()
                                    * self.dp_world_size)
        return DeepSpeedDataLoader(
            dataset,
            batch_size=batch_size,
            data_parallel_world_size=1,   # GSPMD shards the global batch
            data_parallel_rank=0,
            collate_fn=collate_fn or self.collate_fn,
            seed=self._config.seed)

    # ------------------------------------------------------------------
    # checkpointing (reference engine.py:1562-1891)
    # ------------------------------------------------------------------
    def _ckpt_extra(self, client_state=None):
        """The counters + scheduler state every save carries — shared
        by the blocking save and the async snapshot path."""
        self._sync_skipped_steps()
        extra = {
            "global_steps": self.global_steps,
            "micro_steps": self.micro_steps,
            "global_samples": self.global_samples,
            "skipped_steps": self.skipped_steps,
            "client_state": client_state or {},
        }
        if isinstance(self.lr_scheduler, _Schedule):
            extra["lr_scheduler"] = self.lr_scheduler.state_dict()
        return extra

    def save_checkpoint(self, save_dir, tag=None, client_state=None,
                        save_latest=True):
        from deepspeed_tpu.runtime import checkpointing as ckpt
        assert self.state is not None, "no state to save"
        self._ensure_params_resident()
        tag = tag or f"global_step{self.global_steps}"
        extra = self._ckpt_extra(client_state)
        state = self.state
        if self._host_runner is not None:
            # persist fp32 master + host moments, not the bf16 device copy
            state = TrainState(params=self._host_runner.params_tree(),
                               opt_state=self._host_runner.state_dict(),
                               scaler=self.state.scaler,
                               global_step=self.state.global_step,
                               skipped_steps=self.state.skipped_steps)
        ckpt.save_checkpoint(save_dir, tag, state, extra,
                             save_latest=save_latest,
                             zero_stage=self.zero_optimization_stage())
        return True

    def lower_train_step(self, batch):
        """The jitted train step lowered for the live state and ``batch``
        (a ``jax.stages.Lowered``): ``.as_text()`` shows which kernels the
        program holds (``tpu_custom_call`` = a Pallas kernel),
        ``.compile()`` its memory and cost analysis. Call after at least
        one train_batch; compiling it then is an executable-cache hit."""
        assert self._jit_train_batch is not None and self.state is not None, \
            "run a train_batch first (this lowers the step that ran)"
        if self._host_runner is not None:
            raise NotImplementedError(
                "ZeRO-Offload engines split the step across device grads "
                "and a host optimizer; the on-device fused step this "
                "would lower is not the program that runs")
        return self._jit_train_batch.lower(
            self.state, self._globalize_batch(batch), self._rng)

    def train_step_memory_stats(self, batch):
        """Compiled-executable memory breakdown of the jitted train step
        (XLA buffer assignment — exact, not sampled; one program's
        budget, where device.memory_stats() reports the process). Returns
        bytes for arguments (resident state), temporaries (activations,
        remat workspaces), outputs, and the peak estimate the compiler
        budgeted. The SURVEY §7 'memory evidence' instrument."""
        ma = self.lower_train_step(batch).compile().memory_analysis()
        args = int(ma.argument_size_in_bytes)
        temp = int(ma.temp_size_in_bytes)
        out = int(ma.output_size_in_bytes)
        alias = int(ma.alias_size_in_bytes)
        return {
            "argument_bytes": args,
            "temp_bytes": temp,
            "output_bytes": out,
            "alias_bytes": alias,
            "generated_code_bytes": int(ma.generated_code_size_in_bytes),
            # donated state aliases outputs, so peak ≈ args + temps + code
            "peak_hbm_estimate_bytes": args + temp + max(out - alias, 0)
            + int(ma.generated_code_size_in_bytes),
        }

    def _ckpt_shardings(self, struct):
        """Target shardings for sharded checkpoint loading — derived from
        the ShapeDtypeStruct trees in the checkpoint index, so each process
        reads only the windows of its own shards."""
        try:
            param_sh = self.zero.param_shardings(struct["params"])
            opt_sh = self.zero.opt_state_shardings(
                struct.get("opt_state", {}), struct["params"],
                getattr(self.optimizer, "param_like_state_fields", ()))
        except Exception as e:
            logger.warning(f"sharded-load sharding derivation failed ({e}); "
                           f"assembling full arrays on host")
            return None
        repl = NamedSharding(self.mesh, PartitionSpec())
        out = {"params": param_sh, "opt_state": opt_sh,
               "scaler": jax.tree_util.tree_map(lambda _: repl,
                                                struct.get("scaler", {})),
               "global_step": repl, "skipped_steps": repl}
        return out

    def load_checkpoint(self, load_dir, tag=None, load_module_only=False,
                        load_optimizer_states=True, load_lr_scheduler_states=True):
        from deepspeed_tpu.runtime import checkpointing as ckpt
        # an explicit load expresses intent — auto-resume must never
        # clobber it afterwards (global_steps==0 is NOT a reliable
        # proxy: a step-0 save or module-only restore lands there too)
        self._auto_resumed = True
        # an in-flight snapshot captures PRE-load state and its staging
        # dir would be swept as an orphan by the elastic route below —
        # abandon it before adopting different state
        if self._snapshotter is not None and self._snapshotter.in_flight:
            self._snapshotter.abort("load_checkpoint")
        # elastic-snapshot directories (runtime/elastic, ISSUE 7) load
        # through the validating snapshot reader — with fallback to the
        # newest VALID generation when the pointed-at one is corrupt
        from deepspeed_tpu.runtime.elastic.snapshot import (
            has_snapshots, is_snapshot_dir)
        resolved = tag or ckpt.read_latest_tag(load_dir)
        # route by pointer/tag when one resolves; by SCAN when none
        # does (a crash before the first-ever `latest` write leaves a
        # committed snapshot with no pointer — resume's mtime walk
        # still finds it)
        if (resolved is not None and is_snapshot_dir(
                ckpt.resolve_ckpt_dir(load_dir, resolved))) \
                or (resolved is None and has_snapshots(load_dir)):
            from deepspeed_tpu.runtime.elastic.resume import elastic_resume
            res = elastic_resume(
                self, load_dir, tag=tag,
                load_module_only=load_module_only,
                load_optimizer_states=load_optimizer_states,
                load_lr_scheduler_states=load_lr_scheduler_states)
            if res is None:
                logger.warning(
                    f"no valid snapshot in {load_dir}, tag={tag}")
                return None, {}
            return res
        shardings_fn = None if self._offload_cfg.enabled \
            else self._ckpt_shardings
        # module-only restores substitute the live optimizer state below —
        # skip the (2x param bytes) opt_state shard reads entirely then
        want_opt = load_optimizer_states and not load_module_only
        loaded = ckpt.load_checkpoint(
            load_dir, tag, shardings_fn=shardings_fn,
            load_optimizer=want_opt or self.state is None)
        if loaded is None:
            logger.warning(f"Unable to find checkpoint in {load_dir}, tag={tag}")
            return None, {}
        state_tree, extra = loaded
        keep_live_opt = load_module_only or not load_optimizer_states
        self._adopt_ckpt_tree(state_tree, extra,
                              keep_live_opt=keep_live_opt,
                              load_lr=load_lr_scheduler_states)
        tag = tag or ckpt.read_latest_tag(load_dir)
        return tag, extra.get("client_state", {})

    def _adopt_ckpt_tree(self, state_tree, extra, keep_live_opt=False,
                         load_lr=True):
        """Adopt a loaded {params, opt_state, scaler, global_step,
        skipped_steps} tree + counter dict — shared by load_checkpoint
        and the elastic resume path (runtime/elastic/resume.py)."""
        if keep_live_opt and self.state is not None:
            # keep the live (possibly non-addressable) sharded opt_state
            # as-is — device_get would gather/fail on multi-host shards
            state_tree["opt_state"] = self.state.opt_state
        template = TrainState(
            params=state_tree["params"],
            opt_state=state_tree["opt_state"],
            scaler=state_tree["scaler"],
            global_step=jnp.asarray(state_tree["global_step"], jnp.int32),
            skipped_steps=jnp.asarray(state_tree["skipped_steps"], jnp.int32))
        if self._offload_cfg.enabled:
            self._adopt_loaded_state_offload(template)
        else:
            self._adopt_loaded_state(template)
        if self._param_offload_nvme:
            # un-park onto the LOADED params: the swap files still hold
            # pre-load weights, and a parked engine would otherwise swap
            # the stale copies back in on the next step (the next park
            # rewrites the files from the loaded weights). Also covers a
            # fresh engine restoring before any train_batch (no swapper
            # exists yet — the configured tier must not silently disable).
            if self._param_swapper is None:
                self._param_swapper = self._make_param_swapper()
            self._params_parked = False
        self.global_steps = extra.get("global_steps", 0)
        self.micro_steps = extra.get("micro_steps", 0)
        self.global_samples = extra.get("global_samples", 0)
        self.skipped_steps = extra.get("skipped_steps", 0)
        if load_lr and isinstance(self.lr_scheduler, _Schedule) \
                and "lr_scheduler" in extra:
            self.lr_scheduler.load_state_dict(extra["lr_scheduler"])

    def _adopt_loaded_state(self, template: TrainState):
        template = self._restore_error_lists(template)
        self.state_shardings = self._build_state_shardings(template)
        self.state = jax.tree_util.tree_map(
            lambda x, s: jax.device_put(jnp.asarray(x), s),
            template, self.state_shardings)

    def _restore_error_lists(self, template: TrainState):
        """The checkpoint serializer rebuilds every container as a dict
        (checkpointing._unflatten), so the hierarchical comm path's
        per-BUCKET error LISTS come back digit-keyed — and uncompressed
        buckets' None entries were dropped at save. Rebuild the lists
        against the plan's bucket count so the loaded residuals land in
        the positions the train program's per-bucket zip expects."""
        if not isinstance(template.opt_state, dict):
            return template
        plan = self._comm_plan()
        if plan is None:
            return self._restore_flat_error_trees(template)
        from deepspeed_tpu.parallel import overlap
        # canonical zero state for the CURRENT policy — the checkpoint
        # may have been written under a different compression/bucket
        # config, so loaded residuals only land where the shapes still
        # agree; anything else resets to zero (or drops) with a warning
        # instead of tripping a cryptic trace error on a None operand
        canon = dict(zip(
            ("worker_error", "server_error"),
            overlap.hierarchical_error_states(template.params, plan)))
        dp = mesh_lib.mesh_axis_size(self.mesh, mesh_lib.DATA_AXIS)
        opt_state, changed = dict(template.opt_state), False
        for key, zeros in canon.items():
            v = opt_state.get(key)
            if isinstance(v, list):
                continue        # live state kept as-is (keep_live_opt)
            loaded = v if isinstance(v, dict) \
                and all(k.isdigit() for k in v) else {}
            out = []
            for i, z in enumerate(zeros):
                lv = loaded.get(str(i))
                if z is None:
                    if lv is not None:
                        logger.warning(
                            f"{key}[{i}]: bucket is uncompressed under "
                            f"the current comm.hierarchy policy — "
                            f"checkpointed residual dropped")
                    out.append(None)
                elif lv is not None \
                        and tuple(np.shape(lv)) == (dp,) + z.shape:
                    out.append(lv)
                else:
                    if lv is not None:
                        logger.warning(
                            f"{key}[{i}]: checkpointed residual shape "
                            f"{np.shape(lv)} does not match the current "
                            f"plan ({(dp,) + z.shape}) — reset to zero")
                    out.append(jnp.zeros((dp,) + z.shape, z.dtype))
            opt_state[key] = out
            changed = True
        return template.replace(opt_state=opt_state) if changed \
            else template

    def _restore_flat_error_trees(self, template: TrainState):
        """The reverse policy flip: a checkpoint written by the
        HIERARCHICAL path (per-bucket error lists, digit-keyed after the
        round trip) resumed on the FLAT compressed path. The bucket-flat
        residuals have no per-leaf interpretation here — reset to zero
        per-leaf trees (warned) instead of handing
        tree_compressed_allreduce a digit-dict and crashing the trace."""
        if not self._compressed_comm_active():
            return template
        opt_state = template.opt_state

        def hier_format(v):
            # digit-keyed = a round-tripped per-bucket list; None/absent =
            # an all-None ("never"-policy) list the serializer dropped
            return v is None or (isinstance(v, dict) and v
                                 and all(s.isdigit() for s in v))
        needs = [k for k in ("worker_error", "server_error")
                 if opt_state and hier_format(opt_state.get(k))]
        if not needs:
            return template
        logger.warning(
            f"checkpoint carries hierarchical per-bucket error state "
            f"({needs}) but the engine runs the FLAT compressed "
            f"exchange — error feedback resets to zero")
        from deepspeed_tpu.parallel import compression as comp
        dp = mesh_lib.mesh_axis_size(self.mesh, mesh_lib.DATA_AXIS)
        we, se = comp.init_error_states(template.params, dp)
        bump = lambda t: jax.tree_util.tree_map(  # noqa: E731
            lambda x: jnp.zeros((dp,) + x.shape, x.dtype), t)
        opt_state = dict(opt_state)
        opt_state["worker_error"] = bump(we)
        opt_state["server_error"] = bump(se)
        return template.replace(opt_state=opt_state)

    def _build_state_shardings(self, state: TrainState) -> TrainState:
        """Shardings for a full TrainState per ZeRO stage + the
        compressed-comm special cases — shared by _init_state and the
        checkpoint/elastic adoption paths (which previously rebuilt a
        subset of this and mis-sharded the error-feedback state)."""
        params, opt_state, scaler = state.params, state.opt_state, \
            state.scaler
        param_sh = self.zero.param_shardings(params)
        self._gather_edge = self.zero.gather_edge(params)
        opt_sh = self.zero.opt_state_shardings(
            opt_state, params,
            getattr(self.optimizer, "param_like_state_fields", ()))
        state_mesh = self.mesh
        if self._compressed_comm_active():
            plan = self._comm_plan()
            if plan is not None:
                # hierarchical path (ISSUE 10): rest the whole TrainState
                # on the split-mesh view. The device layout is identical
                # (metadata-only), but the hierarchical train program's
                # shard_map shardings then match its inputs from step one
                # instead of forcing a second-step retrace when the first
                # output comes back on the split mesh.
                state_mesh = mesh_lib.split_data_axis(self.mesh, plan.inter)

                def resplit(s):
                    spec = tuple(
                        (plan.inter_axis, plan.intra_axis)
                        if p == mesh_lib.DATA_AXIS else p
                        for p in tuple(s.spec))
                    return NamedSharding(state_mesh, PartitionSpec(*spec))
                param_sh = jax.tree_util.tree_map(resplit, param_sh)
                opt_sh = jax.tree_util.tree_map(resplit, opt_sh)
            # per-device error-feedback state: leading [dp] axis sharded
            # over data so every worker keeps exactly its own error tensors
            err_sh = NamedSharding(
                state_mesh,
                PartitionSpec((plan.inter_axis, plan.intra_axis)
                              if plan is not None else mesh_lib.DATA_AXIS))
            for key in ("worker_error", "server_error"):
                if key in opt_state:
                    opt_sh[key] = jax.tree_util.tree_map(
                        lambda _: err_sh, opt_state[key])
        repl = NamedSharding(state_mesh, PartitionSpec())
        scaler_sh = jax.tree_util.tree_map(lambda _: repl, scaler)
        shardings = TrainState(params=param_sh, opt_state=opt_sh,
                               scaler=scaler_sh, global_step=repl,
                               skipped_steps=repl)
        from deepspeed_tpu.runtime import remat_budget
        self._remat_free_bytes = remat_budget.free_bytes(
            self.mesh.devices.flat[0].device_kind,
            self._held_bytes(state, shardings))
        return shardings

    # what a chip has left for the names a rematted block keeps beside its
    # base names (``runtime/remat_budget.py``), handed to every trace
    _remat_free_bytes = 0

    def _held_bytes(self, state, shardings):
        """Bytes ONE chip holds through a step beside its activations: its
        shard of the state, and of the parameters once more in the
        gradients' dtype and (where the step casts the tree) the compute
        dtype's. Exact on abstract state too: shapes and shardings alone."""
        def shard_bytes(x, sh, itemsize=None):
            return int(np.prod(sh.shard_shape(jnp.shape(x)))) * (
                itemsize or jnp.result_type(x).itemsize)
        bf16 = self._config.grad_dtype == "bf16"
        return sum(jax.tree_util.tree_leaves(jax.tree_util.tree_map(
            shard_bytes, state, shardings))) + sum(jax.tree_util.tree_leaves(
                jax.tree_util.tree_map(
                    lambda x, sh: shard_bytes(x, sh, 4 if bf16 else 8)
                    if jnp.issubdtype(jnp.result_type(x), jnp.floating)
                    else 0, state.params, shardings.params)))

    def _run_pinned(self, mesh, fn, args, kwargs):
        """``fn(*args, **kwargs)`` under the models' trace-time scope
        (``_pinned``). A step the compiler refuses for memory with names kept
        is built ONCE more with the base set: the estimate behind
        ``_remat_free_bytes`` was wrong, and a shape that trained before the
        rule must train with it."""
        def run():
            with mesh_lib.layout_pins(
                    mesh, gather_edge=self._gather_edge,
                    remat_free_bytes=self._remat_free_bytes):
                return fn(*args, **kwargs)
        try:
            return run()
        except Exception as e:  # boundary: the compiler's refusal
            words = str(e)
            if not self._remat_free_bytes or not (
                    "RESOURCE_EXHAUSTED" in words
                    or "Ran out of memory" in words):
                raise
            self._remat_free_bytes = 0
            self.telemetry.counter("remat/fell_back_to_base").inc()
            log_dist("the step did not fit with what its rematted blocks "
                     "kept: built again with their base names "
                     f"({words.splitlines()[0][:200]})", ranks=[0])
            jax.clear_caches()
            return run()

    def _adopt_loaded_state_offload(self, template: TrainState):
        self._host_runner = self._make_offload_runner(template.params)
        if template.opt_state:
            self._host_runner.load_state_dict(template.opt_state)
        device_params = jax.tree_util.tree_map(
            lambda p: np.asarray(p, self.precision.compute_dtype)
            if np.issubdtype(np.asarray(p).dtype, np.floating) else
            np.asarray(p), template.params)
        surrogate = TrainState(params=device_params, opt_state={},
                               scaler=template.scaler,
                               global_step=template.global_step,
                               skipped_steps=template.skipped_steps)
        self._adopt_loaded_state(surrogate)

    def save_fp16_model(self, save_dir, save_filename="mp_rank_00_model_states.npz"):
        """Gathered model weights only (reference engine.py:1955)."""
        from deepspeed_tpu.runtime import checkpointing as ckpt
        self._ensure_params_resident()
        os.makedirs(save_dir, exist_ok=True)
        ckpt.save_tree(os.path.join(save_dir, save_filename), self.state.params)


def _rng_names(model, has_dropout):
    """The rng streams a training forward is handed the step's key under (a
    micro-batch's own split under accumulation): ``dropout`` where the
    model's config has it, and what the model itself names in
    ``rng_streams`` (a block-diffusion step's ``diffusion``)."""
    return (("dropout",) if has_dropout else ()) \
        + tuple(getattr(model, "rng_streams", ()))
