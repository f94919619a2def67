"""Unified telemetry: per-step metrics registry, async-safe spans and a
config-gated programmatic XLA trace window.

The reference DeepSpeed treats observability as a first-class subsystem
(TensorBoard scalars + wall-clock breakdown timers + the FLOPS profiler
wired into the engine loop); this package is the TPU rebuild of that
layer, with one discipline the reference's CUDA timers didn't need:
**nothing here forces a device sync in a hot loop**. Under jit the
dispatch is asynchronous, so spans record host wall time + a profiler
annotation only, and device-accurate accounting happens (a) at
``steps_per_print`` boundaries, where the engine's existing loss
readback is the fence, or (b) inside an XLA trace window where the
profiler timeline is the source of truth.

Layout:

- ``registry``: process-wide counters / gauges / histograms with
  snapshot/reset, plus three exporters — JSONL stream,
  ``SummaryEventWriter`` bridge, Prometheus text dump;
- ``spans``: ``span("tag")`` host-side context manager
  (``jax.profiler.TraceAnnotation`` + wall time), ``annotate("tag")``
  for trace-time ``jax.named_scope`` labels inside jitted train fns,
  and ``TraceWindow`` wrapping ``jax.profiler.start_trace/stop_trace``
  around a configured step range;
- ``recorder``: the flight recorder — a process-wide bounded ring of
  structured events (step/swap/serving lifecycle) for post-anomaly
  reconstruction (ISSUE 6);
- ``anomaly``: the watchdog — fence-point anomaly rules (NaN loss,
  step-time / swap-stall outliers, TTFT blowup, page-pool exhaustion)
  that write one-shot JSONL dumps of the ring;
- ``view``: ``python -m deepspeed_tpu.telemetry.view <dump.jsonl>``
  renders a dump as per-step phase tables + per-request timelines;
- ``cluster``: cross-rank aggregation (ISSUE 12) — a fixed fp32
  metrics vector allgathered at existing fences, folded on rank 0
  into ``cluster/*`` skew gauges + the ``rank_straggler`` rule;
- ``serve``: the live ``/metrics`` + ``/healthz`` http endpoint
  (``monitor.serve_port``), stdlib http.server in a daemon thread;
- ``slo``: the windowed per-role SLO plane (ISSUE 19) — rolling
  quantiles + error-budget burn rate per (role, metric), exported as
  ``slo/*`` gauges and distilled into the per-role scale
  recommendation autoscalers consume;
- ``perfetto``: Chrome trace-event export (ISSUE 19) — N per-rank
  dumps merged into one ``ui.perfetto.dev`` timeline with causal
  span ids and handoff flow arrows (``view --format perfetto``).
"""

from deepspeed_tpu.telemetry.registry import (     # noqa: F401
    Counter, Gauge, Histogram, MetricsRegistry, default_registry,
    JsonlExporter, SummaryBridge, prometheus_text)
from deepspeed_tpu.telemetry.spans import (        # noqa: F401
    span, annotate, TraceWindow)
from deepspeed_tpu.telemetry.recorder import (     # noqa: F401
    FlightRecorder, default_recorder)
from deepspeed_tpu.telemetry.anomaly import Watchdog  # noqa: F401

# cluster/serve resolve lazily (PEP 562, same trick as the package
# root): cluster.py imports numpy at module level, and the dump
# viewer's "pure stdlib, runs anywhere" contract covers machines
# without numpy too — an eager import here would put numpy on
# `python -m deepspeed_tpu.telemetry.view`'s import chain
# (tests/test_metric_names.py poisons BOTH jax and numpy to pin this).
_LAZY_ATTRS = {
    "ClusterAggregator": ("deepspeed_tpu.telemetry.cluster",
                          "ClusterAggregator"),
    "CLUSTER_METRICS": ("deepspeed_tpu.telemetry.cluster",
                        "CLUSTER_METRICS"),
    "cluster_metric_names": ("deepspeed_tpu.telemetry.cluster",
                             "cluster_metric_names"),
    "cluster": ("deepspeed_tpu.telemetry.cluster", None),
    "MetricsServer": ("deepspeed_tpu.telemetry.serve", "MetricsServer"),
    "start_metrics_server": ("deepspeed_tpu.telemetry.serve",
                             "start_metrics_server"),
    "serve": ("deepspeed_tpu.telemetry.serve", None),
    # stdlib-only modules, lazy anyway so `import deepspeed_tpu.
    # telemetry` stays exactly as cheap as before ISSUE 19
    "SloPlane": ("deepspeed_tpu.telemetry.slo", "SloPlane"),
    "slo_metric_names": ("deepspeed_tpu.telemetry.slo",
                         "slo_metric_names"),
    "roles_signal": ("deepspeed_tpu.telemetry.slo", "roles_signal"),
    "slo": ("deepspeed_tpu.telemetry.slo", None),
    "perfetto": ("deepspeed_tpu.telemetry.perfetto", None),
}

from deepspeed_tpu.utils.lazy import lazy_attrs  # noqa: E402

__getattr__, __dir__ = lazy_attrs(__name__, _LAZY_ATTRS)
