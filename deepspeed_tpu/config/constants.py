"""Config keys + defaults — rebuild of deepspeed/runtime/constants.py (406 LoC)
and zero/constants.py. Key names are kept identical to the reference JSON
schema so existing DeepSpeed configs parse unchanged; TPU-specific aliases
(``*_per_chip``) are accepted alongside the reference's ``*_per_gpu``.
"""

#############################################
# Batch-size triangle (reference config.py:837)
#############################################
TRAIN_BATCH_SIZE = "train_batch_size"
TRAIN_BATCH_SIZE_DEFAULT = None

TRAIN_MICRO_BATCH_SIZE_PER_GPU = "train_micro_batch_size_per_gpu"
TRAIN_MICRO_BATCH_SIZE_PER_CHIP = "train_micro_batch_size_per_chip"
TRAIN_MICRO_BATCH_SIZE_PER_GPU_DEFAULT = None

GRADIENT_ACCUMULATION_STEPS = "gradient_accumulation_steps"
GRADIENT_ACCUMULATION_STEPS_DEFAULT = None

#############################################
# Optimizer / scheduler
#############################################
OPTIMIZER = "optimizer"
OPTIMIZER_TYPE_DEFAULT = None
OPTIMIZER_PARAMS = "params"
TYPE = "type"
LEGACY_FUSION = "legacy_fusion"
LEGACY_FUSION_DEFAULT = False

MAX_GRAD_NORM = "max_grad_norm"

SCHEDULER = "scheduler"
SCHEDULER_TYPE_DEFAULT = None
SCHEDULER_PARAMS = "params"

# optimizer names (reference engine.py:27-29 DEEPSPEED_OPTIMIZERS)
ADAM_OPTIMIZER = "adam"
ADAMW_OPTIMIZER = "adamw"
LAMB_OPTIMIZER = "lamb"
ONEBIT_ADAM_OPTIMIZER = "onebitadam"
ONEBIT_LAMB_OPTIMIZER = "onebitlamb"
CPU_ADAM_OPTIMIZER = "cpuadam"
SGD_OPTIMIZER = "sgd"
DEEPSPEED_OPTIMIZERS = [
    ADAM_OPTIMIZER, ADAMW_OPTIMIZER, LAMB_OPTIMIZER, ONEBIT_ADAM_OPTIMIZER,
    ONEBIT_LAMB_OPTIMIZER, CPU_ADAM_OPTIMIZER, SGD_OPTIMIZER
]

#############################################
# Precision (fp16 parity + TPU-native bf16)
#############################################
FP16 = "fp16"
FP16_ENABLED = "enabled"
FP16_ENABLED_DEFAULT = False
FP16_LOSS_SCALE = "loss_scale"
FP16_LOSS_SCALE_DEFAULT = 0
FP16_INITIAL_SCALE_POWER = "initial_scale_power"
FP16_INITIAL_SCALE_POWER_DEFAULT = 32
FP16_LOSS_SCALE_WINDOW = "loss_scale_window"
FP16_LOSS_SCALE_WINDOW_DEFAULT = 1000
FP16_HYSTERESIS = "hysteresis"
FP16_HYSTERESIS_DEFAULT = 2
FP16_MIN_LOSS_SCALE = "min_loss_scale"
FP16_MIN_LOSS_SCALE_DEFAULT = 1

BF16 = "bf16"
BFLOAT16 = "bfloat16"
BF16_ENABLED = "enabled"
BF16_ENABLED_DEFAULT = False

PRECISION = "precision"  # tpu-native: "bfloat16" | "float32" | "float16"

#############################################
# Gradient handling
#############################################
GRADIENT_CLIPPING = "gradient_clipping"
GRADIENT_CLIPPING_DEFAULT = 0.0

PRESCALE_GRADIENTS = "prescale_gradients"
PRESCALE_GRADIENTS_DEFAULT = False

GRADIENT_PREDIVIDE_FACTOR = "gradient_predivide_factor"
GRADIENT_PREDIVIDE_FACTOR_DEFAULT = 1.0

SPARSE_GRADIENTS = "sparse_gradients"
SPARSE_GRADIENTS_DEFAULT = False

ALLREDUCE_ALWAYS_FP32 = "fp32_allreduce"
ALLREDUCE_ALWAYS_FP32_DEFAULT = False

DISABLE_ALLGATHER = "disable_allgather"
DISABLE_ALLGATHER_DEFAULT = False

#############################################
# Steps / misc
#############################################
STEPS_PER_PRINT = "steps_per_print"
STEPS_PER_PRINT_DEFAULT = 10

WALL_CLOCK_BREAKDOWN = "wall_clock_breakdown"
WALL_CLOCK_BREAKDOWN_DEFAULT = False

MEMORY_BREAKDOWN = "memory_breakdown"
MEMORY_BREAKDOWN_DEFAULT = False

DUMP_STATE = "dump_state"
DUMP_STATE_DEFAULT = False

GRADIENT_NOISE_SCALE = "gradient_noise_scale"

SEED = "seed"
SEED_DEFAULT = 1234

#############################################
# Tensorboard (reference constants.py TENSORBOARD_*)
#############################################
TENSORBOARD = "tensorboard"
TENSORBOARD_ENABLED = "enabled"
TENSORBOARD_ENABLED_DEFAULT = False
TENSORBOARD_OUTPUT_PATH = "output_path"
TENSORBOARD_OUTPUT_PATH_DEFAULT = ""
TENSORBOARD_JOB_NAME = "job_name"
TENSORBOARD_JOB_NAME_DEFAULT = "DeepSpeedJobName"

#############################################
# ZeRO (reference zero/constants.py)
#############################################
ZERO_OPTIMIZATION = "zero_optimization"
ZERO_STAGE = "stage"
ZERO_STAGE_DEFAULT = 0
ZERO_REDUCE_BUCKET_SIZE = "reduce_bucket_size"
ZERO_REDUCE_BUCKET_SIZE_DEFAULT = 5e8
ZERO_ALLGATHER_BUCKET_SIZE = "allgather_bucket_size"
ZERO_ALLGATHER_BUCKET_SIZE_DEFAULT = 5e8
ZERO_OVERLAP_COMM = "overlap_comm"
ZERO_OVERLAP_COMM_DEFAULT = False
# TPU extension: collective implementation for the overlap_comm bucket
# stream — "ring" (explicit lax.ppermute ring reduce-scatter + all-gather
# per bucket, maximum scheduling freedom) or "fused" (one lax.psum per
# bucket; XLA picks the algorithm). See parallel/overlap.py.
ZERO_OVERLAP_REDUCE = "overlap_reduce"
ZERO_OVERLAP_REDUCE_DEFAULT = "ring"
ZERO_REDUCE_SCATTER = "reduce_scatter"
ZERO_REDUCE_SCATTER_DEFAULT = True
ZERO_CONTIGUOUS_GRADIENTS = "contiguous_gradients"
ZERO_CONTIGUOUS_GRADIENTS_DEFAULT = False
ZERO_ALLGATHER_PARTITIONS = "allgather_partitions"
ZERO_ALLGATHER_PARTITIONS_DEFAULT = True
ZERO_CPU_OFFLOAD = "cpu_offload"
ZERO_CPU_OFFLOAD_DEFAULT = False
ZERO_CPU_OFFLOAD_PARAMS = "cpu_offload_params"
ZERO_ELASTIC_CHECKPOINT = "elastic_checkpoint"
ZERO_ELASTIC_CHECKPOINT_DEFAULT = True
ZERO_LOAD_FROM_FP32_WEIGHTS = "load_from_fp32_weights"
ZERO_LOAD_FROM_FP32_WEIGHTS_DEFAULT = True

ZERO_OFFLOAD_PARAM = "offload_param"
ZERO_OFFLOAD_OPTIMIZER = "offload_optimizer"
OFFLOAD_DEVICE = "device"
OFFLOAD_CPU_DEVICE = "cpu"
OFFLOAD_NVME_DEVICE = "nvme"
OFFLOAD_NONE_DEVICE = "none"
OFFLOAD_NVME_PATH = "nvme_path"
OFFLOAD_BUFFER_COUNT = "buffer_count"
OFFLOAD_BUFFER_COUNT_DEFAULT = 5
OFFLOAD_BUFFER_SIZE = "buffer_size"
OFFLOAD_PIN_MEMORY = "pin_memory"
OFFLOAD_MAX_IN_CPU = "max_in_cpu"
# pipelined swap schedules (reference aio/pipelined_optimizer_swapper
# knobs): pipeline_read streams swap-in through a sliding window of
# buffer_count staging slots; pipeline_write parks leaves write-behind on
# a dedicated aio handle (drain-fenced before any re-read). Host staging
# is bounded at ~2 x buffer_count x largest-leaf bytes.
OFFLOAD_PIPELINE_READ = "pipeline_read"
OFFLOAD_PIPELINE_WRITE = "pipeline_write"
OFFLOAD_PIPELINE_READ_DEFAULT = False
OFFLOAD_PIPELINE_WRITE_DEFAULT = False
OFFLOAD_FAST_INIT = "fast_init"
# TPU extension (ISSUE 7 satellite): fsync-fenced durability for the
# write-behind aio path. Off by default — swap files are per-step
# scratch riding the guest page cache — but the drain fence becomes a
# real durability barrier when on, and elastic snapshots taken FROM the
# parked files require it for their commit fence to mean anything.
OFFLOAD_FSYNC = "fsync"
OFFLOAD_FSYNC_DEFAULT = False
# TPU extension: how the offloaded optimizer step executes (offload_stream.py)
OFFLOAD_STREAM = "stream"
OFFLOAD_STREAM_SEGMENTS = "stream_segments"

# stage-3 tuning knobs (reference zero/constants.py).
# stage3_prefetch_bucket_size and stage3_max_live_parameters are accepted
# for the reference's configs and have no effect: XLA schedules the
# gather edge's all-gathers
ZERO_PREFETCH_BUCKET_SIZE = "stage3_prefetch_bucket_size"
ZERO_PREFETCH_BUCKET_SIZE_DEFAULT = 5e7
# keys of the explicit layer-gather prefetch step, which is gone (the
# GSPMD step with zero/partition.GatherEdge is the one way stage 3
# gathers weights): refused by name, not dropped in silence
ZERO_REMOVED_KEYS = ("stage3_prefetch", "stage3_prefetch_gather",
                     "collective_matmul")
ZERO_PARAM_PERSISTENCE_THRESHOLD = "stage3_param_persistence_threshold"
ZERO_PARAM_PERSISTENCE_THRESHOLD_DEFAULT = 1e5
ZERO_MAX_LIVE_PARAMETERS = "stage3_max_live_parameters"
ZERO_MAX_LIVE_PARAMETERS_DEFAULT = 1e9
ZERO_MAX_REUSE_DISTANCE = "stage3_max_reuse_distance"
ZERO_MAX_REUSE_DISTANCE_DEFAULT = 1e9
ZERO_GATHER_FP16_WEIGHTS_ON_MODEL_SAVE = "stage3_gather_fp16_weights_on_model_save"
ZERO_GATHER_FP16_WEIGHTS_ON_MODEL_SAVE_DEFAULT = False

#############################################
# Activation checkpointing
# (reference activation_checkpointing/checkpointing.py:759-838)
#############################################
ACTIVATION_CHECKPOINTING = "activation_checkpointing"
ACT_CKPT_PARTITION_ACTIVATIONS = "partition_activations"
ACT_CKPT_CPU_CHECKPOINTING = "cpu_checkpointing"
ACT_CKPT_CONTIGUOUS_MEMORY_OPTIMIZATION = "contiguous_memory_optimization"
ACT_CKPT_NUMBER_CHECKPOINTS = "number_checkpoints"
ACT_CKPT_SYNCHRONIZE_CHECKPOINT_BOUNDARY = "synchronize_checkpoint_boundary"
ACT_CKPT_PROFILE = "profile"

#############################################
# Sparse attention (reference config.py:236-406)
#############################################
SPARSE_ATTENTION = "sparse_attention"
SPARSE_MODE = "mode"
SPARSE_MODE_DEFAULT = "fixed"
SPARSE_DENSE_MODE = "dense"
SPARSE_FIXED_MODE = "fixed"
SPARSE_VARIABLE_MODE = "variable"
SPARSE_BIGBIRD_MODE = "bigbird"
SPARSE_BSLONGFORMER_MODE = "bslongformer"
SPARSE_BLOCK = "block"
SPARSE_BLOCK_DEFAULT = 16
SPARSE_DIFFERENT_LAYOUT_PER_HEAD = "different_layout_per_head"
SPARSE_DIFFERENT_LAYOUT_PER_HEAD_DEFAULT = False
SPARSE_NUM_LOCAL_BLOCKS = "num_local_blocks"
SPARSE_NUM_LOCAL_BLOCKS_DEFAULT = 4
SPARSE_NUM_GLOBAL_BLOCKS = "num_global_blocks"
SPARSE_NUM_GLOBAL_BLOCKS_DEFAULT = 1
SPARSE_ATTENTION_TYPE = "attention"
SPARSE_ATTENTION_TYPE_DEFAULT = "bidirectional"
SPARSE_HORIZONTAL_GLOBAL_ATTENTION = "horizontal_global_attention"
SPARSE_HORIZONTAL_GLOBAL_ATTENTION_DEFAULT = False
SPARSE_NUM_DIFFERENT_GLOBAL_PATTERNS = "num_different_global_patterns"
SPARSE_NUM_DIFFERENT_GLOBAL_PATTERNS_DEFAULT = 1
SPARSE_NUM_RANDOM_BLOCKS = "num_random_blocks"
SPARSE_NUM_RANDOM_BLOCKS_DEFAULT = 0
SPARSE_LOCAL_WINDOW_BLOCKS = "local_window_blocks"
SPARSE_LOCAL_WINDOW_BLOCKS_DEFAULT = [4]
SPARSE_GLOBAL_BLOCK_INDICES = "global_block_indices"
SPARSE_GLOBAL_BLOCK_INDICES_DEFAULT = [0]
SPARSE_GLOBAL_BLOCK_END_INDICES = "global_block_end_indices"
SPARSE_GLOBAL_BLOCK_END_INDICES_DEFAULT = None
SPARSE_NUM_SLIDING_WINDOW_BLOCKS = "num_sliding_window_blocks"
SPARSE_NUM_SLIDING_WINDOW_BLOCKS_DEFAULT = 3

#############################################
# Gradient compression (1-bit) + MoQ quantize
#############################################
QUANTIZE_TRAINING = "quantize_training"
QUANTIZE_TRAINING_ENABLED = "enabled"
QUANTIZE_TRAINING_ENABLED_DEFAULT = False

#############################################
# Parallelism (tpu-native section; absent in reference where
# TP was delegated to the client's mpu — SURVEY §2.3)
#############################################
MESH = "mesh"
MESH_DATA = "data"
MESH_MODEL = "model"
MESH_PIPE = "pipe"
MESH_SEQ = "seq"
MESH_EXPERT = "expert"

# Hierarchical link-aware gradient communication (ISSUE 10): the
# ``comm.hierarchy`` block splits the data axis at the host/process
# boundary so the 1-bit compressed exchange pays sign bits only on the
# slow DCN-class hop. Presence of the hierarchy block enables it.
COMM = "comm"
COMM_HIERARCHY = "hierarchy"
COMM_HIERARCHY_ENABLED = "enabled"
COMM_HIERARCHY_ENABLED_DEFAULT = True
# 0 = auto: derive the slow-axis size from jax.distributed process
# boundaries; >1 = synthetic split into that many slow groups (the
# single-process testing override).
COMM_HIERARCHY_SLOW_AXIS = "slow_axis"
COMM_HIERARCHY_SLOW_AXIS_DEFAULT = 0
COMM_HIERARCHY_COMPRESSION = "compression"
COMM_HIERARCHY_COMPRESSION_DEFAULT = "auto"
COMM_HIERARCHY_COMPRESSION_MODES = ("auto", "always", "never")
COMM_HIERARCHY_MIN_BUCKET_BYTES = "min_bucket_bytes"
COMM_HIERARCHY_MIN_BUCKET_BYTES_DEFAULT = 1 << 16

PIPELINE = "pipeline"
PIPELINE_STAGES = "stages"
PIPELINE_PARTITION = "partition"
PIPELINE_SEED_LAYERS = "seed_layers"
PIPELINE_ACTIVATION_CHECKPOINT_INTERVAL = "activation_checkpoint_interval"

#############################################
# Elasticity (reference elasticity/constants.py)
#############################################
ELASTICITY = "elasticity"
ENABLED = "enabled"
ENABLED_DEFAULT = False
MAX_ACCEPTABLE_BATCH_SIZE = "max_train_batch_size"
MAX_ACCEPTABLE_BATCH_SIZE_DEFAULT = 2000
MICRO_BATCHES = "micro_batch_sizes"
MICRO_BATCHES_DEFAULT = [2, 4, 6]
MIN_GPUS = "min_gpus"
MIN_GPUS_DEFAULT = 1
MAX_GPUS = "max_gpus"
MAX_GPUS_DEFAULT = 10000
MIN_TIME = "min_time"
MIN_TIME_DEFAULT = 0
VERSION = "version"
VERSION_DEFAULT = 0.1
LATEST_ELASTICITY_VERSION = 0.1
IGNORE_NON_ELASTIC_BATCH_INFO = "ignore_non_elastic_batch_info"
IGNORE_NON_ELASTIC_BATCH_INFO_DEFAULT = False
PREFER_LARGER_BATCH = "prefer_larger_batch"
PREFER_LARGER_BATCH_DEFAULT = True

#############################################
# FLOPS profiler (reference profiling/constants.py)
#############################################
FLOPS_PROFILER = "flops_profiler"
FLOPS_PROFILER_ENABLED = "enabled"
FLOPS_PROFILER_ENABLED_DEFAULT = False
FLOPS_PROFILER_PROFILE_STEP = "profile_step"
FLOPS_PROFILER_PROFILE_STEP_DEFAULT = 1
FLOPS_PROFILER_MODULE_DEPTH = "module_depth"
FLOPS_PROFILER_MODULE_DEPTH_DEFAULT = -1
FLOPS_PROFILER_TOP_MODULES = "top_modules"
FLOPS_PROFILER_TOP_MODULES_DEFAULT = 3
FLOPS_PROFILER_DETAILED = "detailed"
FLOPS_PROFILER_DETAILED_DEFAULT = True

#############################################
# Telemetry monitor (unified metrics stream, deepspeed_tpu/telemetry —
# the role of the reference's monitor family tensorboard/csv/wandb):
# presence of the block + enabled turns on the per-steps_per_print
# registry export (JSONL stream + SummaryEventWriter bridge).
#############################################
MONITOR = "monitor"
MONITOR_ENABLED = "enabled"
MONITOR_ENABLED_DEFAULT = True       # presence of the block enables it
MONITOR_JSONL_PATH = "jsonl_path"
MONITOR_JSONL_PATH_DEFAULT = ""      # "" -> <output_path>/telemetry_rank{r}.jsonl
MONITOR_OUTPUT_PATH = "output_path"
MONITOR_OUTPUT_PATH_DEFAULT = "runs/telemetry"
# JSONL stream rotation (ISSUE 6 satellite): size-bounded so multi-hour
# runs can't grow one unbounded file. 0 MB disables rotation.
MONITOR_JSONL_MAX_MB = "jsonl_max_mb"
MONITOR_JSONL_MAX_MB_DEFAULT = 256
MONITOR_JSONL_MAX_FILES = "jsonl_max_files"
MONITOR_JSONL_MAX_FILES_DEFAULT = 4

#############################################
# Flight recorder + anomaly watchdog (monitor sub-blocks, ISSUE 6 —
# deepspeed_tpu/telemetry/recorder.py + anomaly.py). The recorder is a
# passive in-memory ring and defaults ON (recording is host-only and
# cheap); the watchdog writes dump FILES on anomaly and so gates on the
# presence of its block, like the monitor block itself.
#############################################
MONITOR_FLIGHT_RECORDER = "flight_recorder"
FLIGHT_RECORDER_ENABLED = "enabled"
FLIGHT_RECORDER_ENABLED_DEFAULT = True
FLIGHT_RECORDER_CAPACITY = "capacity"
FLIGHT_RECORDER_CAPACITY_DEFAULT = 4096

MONITOR_WATCHDOG = "watchdog"
WATCHDOG_ENABLED = "enabled"
WATCHDOG_ENABLED_DEFAULT = True      # presence of the block enables it
WATCHDOG_DUMP_DIR = "dump_dir"
WATCHDOG_DUMP_DIR_DEFAULT = "runs/flight"
WATCHDOG_BASELINE_WINDOW = "baseline_window"
WATCHDOG_BASELINE_WINDOW_DEFAULT = 64
WATCHDOG_MIN_SAMPLES = "min_samples"
WATCHDOG_MIN_SAMPLES_DEFAULT = 8
WATCHDOG_STEP_TIME_FACTOR = "step_time_factor"
WATCHDOG_STEP_TIME_FACTOR_DEFAULT = 3.0
WATCHDOG_SWAP_STALL_FACTOR = "swap_stall_factor"
WATCHDOG_SWAP_STALL_FACTOR_DEFAULT = 4.0
WATCHDOG_SWAP_STALL_MIN_S = "swap_stall_min_s"
WATCHDOG_SWAP_STALL_MIN_S_DEFAULT = 0.05
WATCHDOG_TTFT_FACTOR = "ttft_factor"
WATCHDOG_TTFT_FACTOR_DEFAULT = 4.0
WATCHDOG_TTFT_MIN_S = "ttft_min_s"
WATCHDOG_TTFT_MIN_S_DEFAULT = 1.0
WATCHDOG_CHECK_NAN = "check_nan"
WATCHDOG_CHECK_NAN_DEFAULT = True
WATCHDOG_MAX_DUMPS = "max_dumps"
WATCHDOG_MAX_DUMPS_DEFAULT = 0       # 0 = unlimited
# snapshot-stall rule (ISSUE 7): the async-snapshot commit fence is
# supposed to measure ~0 (writes had a whole step to land); a stall
# past factor x baseline (with an absolute floor) means the aio write
# stream fell behind training and snapshots are no longer free.
WATCHDOG_CKPT_STALL_FACTOR = "ckpt_stall_factor"
WATCHDOG_CKPT_STALL_FACTOR_DEFAULT = 4.0
WATCHDOG_CKPT_STALL_MIN_S = "ckpt_stall_min_s"
WATCHDOG_CKPT_STALL_MIN_S_DEFAULT = 0.25
# rank-straggler rule (ISSUE 12): at cluster fences, a rank whose
# step time exceeds straggler_factor x the median of the OTHER ranks
# for straggler_fences CONSECUTIVE fences trips one latched dump
# naming the rank. Leave-one-out median: with small worlds (2 ranks)
# a whole-cluster median would include the straggler itself and the
# ratio could never reach 2x.
WATCHDOG_STRAGGLER_FACTOR = "straggler_factor"
WATCHDOG_STRAGGLER_FACTOR_DEFAULT = 2.0
WATCHDOG_STRAGGLER_FENCES = "straggler_fences"
WATCHDOG_STRAGGLER_FENCES_DEFAULT = 3
WATCHDOG_STRAGGLER_MIN_S = "straggler_min_s"
WATCHDOG_STRAGGLER_MIN_S_DEFAULT = 0.05   # absolute floor: sub-50ms
# per-step host-time skew is dispatch noise, not a straggler

#############################################
# Cluster telemetry plane (monitor sub-block + serve_port, ISSUE 12 —
# deepspeed_tpu/telemetry/cluster.py + serve.py). The cross-rank
# aggregation is a small fp32 allgather at fences the engine already
# pays (the steps_per_print loss readback; snapshot commit fences) and
# defaults ON like the flight recorder (single-process it degenerates
# to local gauges, no collective). serve_port gates the live /metrics
# + /healthz http.server thread; 0 = off.
#############################################
MONITOR_CLUSTER = "cluster"
CLUSTER_ENABLED = "enabled"
CLUSTER_ENABLED_DEFAULT = True
MONITOR_SERVE_PORT = "serve_port"
MONITOR_SERVE_PORT_DEFAULT = 0       # 0 = no endpoint
MONITOR_SERVE_HOST = "serve_host"
MONITOR_SERVE_HOST_DEFAULT = "127.0.0.1"

#############################################
# Windowed SLO plane (monitor.slo sub-block, ISSUE 19 —
# deepspeed_tpu/telemetry/slo.py). Rolling time-bucketed quantiles +
# error-budget burn rate per serving ROLE, aggregated on rank 0 from
# the transport metrics vector and exported as slo/* gauges; the
# roles_signal() recommendation feeds role-aware autoscaling
# (serving.autoscale.scale_signal: "slo"). Default ON when the monitor
# block is present — the plane is a few host floats per tick.
#############################################
MONITOR_SLO = "slo"
SLO_ENABLED = "enabled"
SLO_ENABLED_DEFAULT = True
SLO_WINDOW_S = "window_s"
SLO_WINDOW_S_DEFAULT = 30.0
SLO_TARGETS = "targets"          # {metric: target seconds} overrides
SLO_BUDGET = "budget"            # error-budget fraction of the window
SLO_BUDGET_DEFAULT = 0.1
SLO_UP_BURN = "up_burn"          # burn rate >= this: role scales up
SLO_UP_BURN_DEFAULT = 2.0
SLO_DOWN_BURN = "down_burn"      # every burn <= this: role has slack
SLO_DOWN_BURN_DEFAULT = 0.25
SLO_MIN_SAMPLES = "min_samples"  # windowed samples before a signal
SLO_MIN_SAMPLES_DEFAULT = 8

#############################################
# Programmatic XLA trace window (profiling.trace_dir + trace_steps):
# wraps jax.profiler.start_trace/stop_trace around global steps
# [trace_steps[0], trace_steps[1]) so span annotations land in
# perfetto/xprof. Off unless trace_dir is set.
#############################################
PROFILING = "profiling"
PROFILING_TRACE_DIR = "trace_dir"
PROFILING_TRACE_DIR_DEFAULT = ""
PROFILING_TRACE_STEPS = "trace_steps"
PROFILING_TRACE_STEPS_DEFAULT = ()

#############################################
# Progressive layer drop (reference constants.py)
#############################################
# MoQ quantize-aware training (reference runtime/constants.py
# QUANTIZE_TRAINING section)
QUANTIZE_TRAINING = "quantize_training"
QUANTIZE_TRAINING_ENABLED = "enabled"
QUANTIZE_TRAINING_ENABLED_DEFAULT = False
QUANTIZE_BITS = "quantize_bits"
QUANTIZE_START_BITS = "start_bits"
QUANTIZE_START_BITS_DEFAULT = 16
QUANTIZE_TARGET_BITS = "target_bits"
QUANTIZE_TARGET_BITS_DEFAULT = 8
QUANTIZE_SCHEDULE = "quantize_schedule"
QUANTIZE_PERIOD = "quantize_period"
QUANTIZE_PERIOD_DEFAULT = 1000
QUANTIZE_SCHEDULE_OFFSET = "schedule_offset"
QUANTIZE_OFFSET_DEFAULT = 1000
QUANTIZE_GROUPS = "quantize_groups"
QUANTIZE_GROUPS_DEFAULT = 1
QUANTIZE_ALGO = "quantize_algo"
QUANTIZE_TYPE = "q_type"
QUANTIZE_SYMMETRIC = "symmetric"
QUANTIZE_ASYMMETRIC = "asymmetric"
QUANTIZE_ROUNDING = "rounding"
QUANTIZE_NEAREST_ROUNDING = "nearest"
QUANTIZE_STOCHASTIC_ROUNDING = "stochastic"
FP16_MIXED_QUANTIZE = "fp16_mixed_quantize"
FP16_MIXED_QUANTIZE_ENABLED = "enabled"
FP16_MIXED_QUANTIZE_ENABLED_DEFAULT = False
QUANTIZE_CHANGE_RATIO = "quantize_change_ratio"
QUANTIZE_CHANGE_RATIO_DEFAULT = 0.001
QUANTIZE_VERBOSE = "quantize_verbose"
QUANTIZE_VERBOSE_DEFAULT = False
QUANTIZER_KERNEL = "quantizer_kernel"
QUANTIZER_KERNEL_DEFAULT = True
QUANTIZE_EIGENVALUE = "eigenvalue"
QUANTIZE_EIGENVALUE_ENABLED = "enabled"
QUANTIZE_EIGENVALUE_ENABLED_DEFAULT = False
EIGENVALUE_VERBOSE = "verbose"
EIGENVALUE_VERBOSE_DEFAULT = False
EIGENVALUE_MAX_ITER = "max_iter"
EIGENVALUE_MAX_ITER_DEFAULT = 100
EIGENVALUE_TOL = "tol"
EIGENVALUE_TOL_DEFAULT = 1e-2
EIGENVALUE_STABILITY = "stability"
EIGENVALUE_STABILITY_DEFAULT = 1e-6
EIGENVALUE_GAS_BOUNDARY_RESOLUTION = "gas_boundary_resolution"
EIGENVALUE_GAS_BOUNDARY_RESOLUTION_DEFAULT = 1
EIGENVALUE_LAYER_NAME = "layer_name"
EIGENVALUE_LAYER_NAME_DEFAULT = "bert.encoder.layer"
EIGENVALUE_LAYER_NUM = "layer_num"
EIGENVALUE_LAYER_NUM_DEFAULT = 0

PROGRESSIVE_LAYER_DROP = "progressive_layer_drop"
PLD_ENABLED = "enabled"
PLD_ENABLED_DEFAULT = False
PLD_THETA = "theta"
PLD_THETA_DEFAULT = 0.5
PLD_GAMMA = "gamma"
PLD_GAMMA_DEFAULT = 0.001

#############################################
# Checkpoint / aio
#############################################
AIO = "aio"
AIO_BLOCK_SIZE = "block_size"
AIO_BLOCK_SIZE_DEFAULT = 1048576
AIO_QUEUE_DEPTH = "queue_depth"
AIO_QUEUE_DEPTH_DEFAULT = 8
AIO_THREAD_COUNT = "thread_count"
AIO_THREAD_COUNT_DEFAULT = 1
AIO_SINGLE_SUBMIT = "single_submit"
AIO_SINGLE_SUBMIT_DEFAULT = False
AIO_OVERLAP_EVENTS = "overlap_events"
AIO_OVERLAP_EVENTS_DEFAULT = True
# O_DIRECT swap I/O (ISSUE 20): bytes-on-device instead of
# bytes-into-page-cache; requires block_size % page == 0. Latches to
# buffered I/O (with one loud warning) on filesystems that reject it.
AIO_O_DIRECT = "o_direct"
AIO_O_DIRECT_DEFAULT = False

#############################################
# Elastic snapshots (runtime/elastic, ISSUE 7): periodic async
# checkpoints through the swap tier's write-behind aio handle, SIGTERM
# preemption handling with a grace budget, and auto-resume from the
# newest valid manifest. Presence of the block (plus a path) enables it.
#############################################
SNAPSHOT = "snapshot"
SNAPSHOT_ENABLED = "enabled"
SNAPSHOT_ENABLED_DEFAULT = True       # presence of the block enables it
SNAPSHOT_PATH = "path"
SNAPSHOT_PATH_DEFAULT = ""
SNAPSHOT_INTERVAL_STEPS = "interval_steps"
SNAPSHOT_INTERVAL_STEPS_DEFAULT = 100
SNAPSHOT_KEEP = "keep"                # committed snapshot generations
SNAPSHOT_KEEP_DEFAULT = 2
SNAPSHOT_FSYNC = "fsync"              # the commit fence durability
SNAPSHOT_FSYNC_DEFAULT = True
SNAPSHOT_AUTO_RESUME = "auto_resume"
SNAPSHOT_AUTO_RESUME_DEFAULT = True
SNAPSHOT_GRACE_SECS = "grace_secs"    # preemption grace budget
SNAPSHOT_GRACE_SECS_DEFAULT = 30.0
SNAPSHOT_SIGNALS = "signals"
SNAPSHOT_SIGNALS_DEFAULT = ("SIGTERM",)

#############################################
# Fault tolerance (runtime/elastic/{hang,supervisor}.py, ISSUE 15):
# the collective hang watchdog + per-rank heartbeat inside every
# worker, and the knobs the launcher-level supervisor exports into
# child environments (heartbeat dir, rendezvous retry). Presence of
# the block enables the in-process watchdog thread.
#############################################
FAULT_TOLERANCE = "fault_tolerance"
FT_ENABLED = "enabled"
FT_ENABLED_DEFAULT = True             # presence of the block enables it
FT_HANG_DEADLINE_S = "hang_deadline_s"    # blocked-in-collective limit
FT_HANG_DEADLINE_S_DEFAULT = 300.0
FT_HANG_POLL_S = "hang_poll_s"        # 0 → deadline/10, clamped
FT_HANG_POLL_S_DEFAULT = 0.0
FT_HEARTBEAT_DIR = "heartbeat_dir"    # "" → DSTPU_HEARTBEAT_DIR env
FT_HEARTBEAT_DIR_DEFAULT = ""
FT_HEARTBEAT_INTERVAL_S = "heartbeat_interval_s"
FT_HEARTBEAT_INTERVAL_S_DEFAULT = 1.0
FT_RENDEZVOUS_RETRIES = "rendezvous_retries"
FT_RENDEZVOUS_RETRIES_DEFAULT = 8
FT_RENDEZVOUS_BACKOFF_S = "rendezvous_backoff_s"
FT_RENDEZVOUS_BACKOFF_S_DEFAULT = 0.5

#############################################
# Serving (continuous batching + paged KV cache) [tpu]
#############################################
SERVING = "serving"
SERVING_ENABLED = "enabled"
SERVING_ENABLED_DEFAULT = True        # presence of the block enables it
SERVING_SLOTS = "slots"
SERVING_SLOTS_DEFAULT = 8
SERVING_PAGE_SIZE = "page_size"
SERVING_PAGE_SIZE_DEFAULT = 128
SERVING_MAX_PAGES_PER_SLOT = "max_pages_per_slot"
SERVING_MAX_PAGES_PER_SLOT_DEFAULT = 16
SERVING_NUM_BLOCKS = "num_blocks"
SERVING_NUM_BLOCKS_DEFAULT = 0        # 0 → slots * max_pages + 1 (trash)
SERVING_KV_CACHE_BITS = "kv_cache_bits"
SERVING_KV_CACHE_BITS_DEFAULT = 0
SERVING_QUANTIZE_BITS = "quantize_bits"
SERVING_QUANTIZE_BITS_DEFAULT = 0

# serving.prefix_cache — copy-on-write prefix page sharing (ISSUE 9):
# presence of the sub-block enables the refcounted prefix index over
# the paged allocator; repeat-prefix admissions alias resident pages
# read-only and prefill only their suffix
SERVING_PREFIX_CACHE = "prefix_cache"
SERVING_PREFIX_CACHE_ENABLED = "enabled"
SERVING_PREFIX_CACHE_ENABLED_DEFAULT = True   # presence enables
SERVING_PREFIX_CACHE_COW = "cow"
SERVING_PREFIX_CACHE_COW_DEFAULT = True       # share the partial page
#                                               via copy-on-write

# serving.speculative — drafter-based speculative decoding (ISSUE 9):
# presence enables; the drafter proposes `tokens` tokens per round and
# the target verifies the window in one multi-query paged-attention
# dispatch (greedy-only; outputs stay token-for-token identical)
SERVING_SPECULATIVE = "speculative"
SERVING_SPEC_ENABLED = "enabled"
SERVING_SPEC_ENABLED_DEFAULT = True           # presence enables
SERVING_SPEC_TOKENS = "tokens"
SERVING_SPEC_TOKENS_DEFAULT = 3               # drafts per verify round
SERVING_SPEC_DRAFTER = "drafter"
SERVING_SPEC_DRAFTER_DEFAULT = "ngram"        # "ngram" | "model"
SERVING_SPEC_NGRAM_MAX = "ngram_max"
SERVING_SPEC_NGRAM_MAX_DEFAULT = 3
SERVING_SPEC_NGRAM_MIN = "ngram_min"
SERVING_SPEC_NGRAM_MIN_DEFAULT = 1

# serving.elastic — preemption-tolerant serving (ISSUE 11): on SIGTERM
# the engine drains requests that fit the grace budget and snapshots
# the rest (per-slot request state + referenced K/V pages + the prefix
# index) through the elastic snapshot commit path; a restore rebuilds
# them on a different engine/replica count
SERVING_ELASTIC = "elastic"
SERVING_ELASTIC_ENABLED = "enabled"
SERVING_ELASTIC_ENABLED_DEFAULT = True        # presence enables
SERVING_ELASTIC_SNAPSHOT_PATH = "snapshot_path"
SERVING_ELASTIC_SNAPSHOT_PATH_DEFAULT = ""
SERVING_ELASTIC_GRACE_SECS = "grace_secs"     # preemption drain budget
SERVING_ELASTIC_GRACE_SECS_DEFAULT = 30.0
SERVING_ELASTIC_MAX_RETRIES = "max_retries"   # cross-replica requeue cap
SERVING_ELASTIC_MAX_RETRIES_DEFAULT = 3
SERVING_ELASTIC_BACKOFF_S = "backoff_s"       # requeue backoff base
SERVING_ELASTIC_BACKOFF_S_DEFAULT = 0.05      # (jittered, doubles/try)
SERVING_ELASTIC_INTERVAL_TICKS = "interval_ticks"
SERVING_ELASTIC_INTERVAL_TICKS_DEFAULT = 0    # 0 = snapshot only on
#                                               preemption / drain
SERVING_ELASTIC_KEEP = "keep"
SERVING_ELASTIC_KEEP_DEFAULT = 2
SERVING_ELASTIC_FSYNC = "fsync"
SERVING_ELASTIC_FSYNC_DEFAULT = True
SERVING_ELASTIC_SIGNALS = "signals"
SERVING_ELASTIC_SIGNALS_DEFAULT = ("SIGTERM",)

# serving.autoscale — replica-pool autoscaling (ISSUE 11): the
# ReplicaPool supervisor scales up on latched watchdog incidents
# (ttft_blowup / page_pool_exhausted trips) and scales down by
# draining an idle replica through the same snapshot path
SERVING_AUTOSCALE = "autoscale"
SERVING_AUTOSCALE_MIN_REPLICAS = "min_replicas"
SERVING_AUTOSCALE_MIN_REPLICAS_DEFAULT = 1
SERVING_AUTOSCALE_MAX_REPLICAS = "max_replicas"
SERVING_AUTOSCALE_MAX_REPLICAS_DEFAULT = 1
SERVING_AUTOSCALE_SCALE_SIGNAL = "scale_signal"
SERVING_AUTOSCALE_SCALE_SIGNAL_DEFAULT = "watchdog"
# "slo" (ISSUE 19): scale on the windowed per-role error-budget burn
# rate the SLO plane (telemetry/slo.py) exports as slo/* gauges
SERVING_AUTOSCALE_SCALE_SIGNAL_MODES = ("watchdog", "slo", "none")

# serving.disaggregation — prefill/decode role split (ISSUE 14):
# dedicated prefill-role engines admit + prefill, a page-handoff
# transport moves the request, decode-role engines adopt the pages
# and tick. decode_replicas 0 = colocated fallback (role="both").
SERVING_DISAGG = "disaggregation"
SERVING_DISAGG_ENABLED = "enabled"
SERVING_DISAGG_ENABLED_DEFAULT = True          # presence enables
SERVING_DISAGG_PREFILL_REPLICAS = "prefill_replicas"
SERVING_DISAGG_PREFILL_REPLICAS_DEFAULT = 1
SERVING_DISAGG_DECODE_REPLICAS = "decode_replicas"
SERVING_DISAGG_DECODE_REPLICAS_DEFAULT = 1
SERVING_DISAGG_DEDUPE_PAGES = "dedupe_pages"
SERVING_DISAGG_DEDUPE_PAGES_DEFAULT = True     # prefix-index re-share
SERVING_DISAGG_TRANSPORT = "transport"
SERVING_DISAGG_TRANSPORT_DEFAULT = "inproc"
SERVING_DISAGG_TRANSPORT_MODES = ("inproc", "process")  # ISSUE 17:
#   "process" = per-role PROCESS placement over the gloo fabric (rank
#   0 prefill+router, ranks >= 1 decode; serving/transport.py)
SERVING_DISAGG_ADDRESSING = "addressing"
SERVING_DISAGG_ADDRESSING_DEFAULT = "targeted"
SERVING_DISAGG_ADDRESSING_MODES = ("targeted", "broadcast")  # ISSUE 18:
#   "targeted" moves dst-addressed frames point-to-point (payload
#   crosses the wire once, any world size); "broadcast" is the PR-17
#   legacy all-rank allgather (O(world x payload), kept for A/B)
SERVING_DISAGG_PAYLOAD_TIMEOUT_S = "payload_timeout_s"
SERVING_DISAGG_PAYLOAD_TIMEOUT_S_DEFAULT = 60.0  # socket-leg deadline:
#   a dead peer fails LOUD into the supervisor's rank-death path

# serving.router — the SLO-aware multi-engine router over the role
# split (ISSUE 14): prefix-locality admission, decode-page
# reservations, live TTFT/queue-depth scoring
SERVING_ROUTER = "router"
SERVING_ROUTER_PREFIX_ROUTING = "prefix_routing"
SERVING_ROUTER_PREFIX_ROUTING_DEFAULT = True
SERVING_ROUTER_QUEUE_WEIGHT = "queue_weight"
SERVING_ROUTER_QUEUE_WEIGHT_DEFAULT = 1.0
SERVING_ROUTER_TTFT_WEIGHT = "ttft_weight"
SERVING_ROUTER_TTFT_WEIGHT_DEFAULT = 1.0
SERVING_ROUTER_TTFT_WINDOW = "ttft_window"
SERVING_ROUTER_TTFT_WINDOW_DEFAULT = 16
SERVING_ROUTER_MAX_HANDOFF_RETRIES = "max_handoff_retries"
SERVING_ROUTER_MAX_HANDOFF_RETRIES_DEFAULT = 3
SERVING_ROUTER_DECODE_TICK_CAP = "decode_tick_cap"
SERVING_ROUTER_DECODE_TICK_CAP_DEFAULT = 4
SERVING_ROUTER_MAX_INFLIGHT_PAGES = "max_inflight_pages"
SERVING_ROUTER_MAX_INFLIGHT_PAGES_DEFAULT = 0   # 0 = 2x decode pools
SERVING_ROUTER_MAX_INFLIGHT_PAGES_PER_RANK = "max_inflight_pages_per_rank"
SERVING_ROUTER_MAX_INFLIGHT_PAGES_PER_RANK_DEFAULT = 0  # ISSUE 18:
#   0 = the aggregate bound split evenly across decode ranks
SERVING_ROUTER_DECODE_SCHEDULE = "decode_schedule"
SERVING_ROUTER_DECODE_SCHEDULE_DEFAULT = "lpt"
SERVING_ROUTER_DECODE_SCHEDULE_MODES = ("lpt", "fifo")
