"""Process-wide metrics registry: counters, gauges, histograms.

Design rules (the sync-discipline contract, docs/observability.md):

- recording is host-only and cheap — a lock acquire plus a float store;
  callers in hot loops (the engine's per-step path, the serving
  scheduler tick) never pay a device sync to record;
- histograms keep a bounded reservoir (most-recent ``maxlen``
  observations) plus exact running count/sum/min/max, so percentiles
  are over recent behaviour while totals stay exact;
- everything is thread-safe: the serving scheduler and a training loop
  may record into the same registry concurrently.

Exporters are pull-based: they serialize a ``snapshot()`` — they never
hold the registry lock across I/O.
"""

import json
import math
import os
import threading
import time
from collections import deque


def _process_rank():
    """This process's rank for event tagging: the launcher's env, else
    the jax process index — via utils.logging._process_index, which
    asks WITHOUT initializing a backend (a bare jax.process_index()
    before jax.distributed.initialize would pin every host to rank 0
    and break the multi-host rendezvous)."""
    for var in ("RANK", "PMI_RANK", "SLURM_PROCID"):
        if os.environ.get(var):
            try:
                return int(os.environ[var])
            except ValueError:
                pass
    from deepspeed_tpu.utils.logging import _process_index
    return int(_process_index())


class Counter:
    """Monotonic float counter."""

    __slots__ = ("value", "_lock")

    def __init__(self, lock):
        self.value = 0.0
        self._lock = lock

    def inc(self, n=1.0):
        with self._lock:
            self.value += n


class Gauge:
    """Last-value-wins scalar; ``set_max`` keeps a high-water mark."""

    __slots__ = ("value", "_lock")

    def __init__(self, lock):
        self.value = 0.0
        self._lock = lock

    def set(self, v):
        with self._lock:
            self.value = float(v)   # sync-ok: contract — host scalars only

    def set_max(self, v):
        with self._lock:
            self.value = max(self.value, float(v))  # sync-ok: host scalars


class Histogram:
    """Bounded-reservoir histogram with exact count/sum/min/max."""

    __slots__ = ("count", "sum", "min", "max", "_values", "_lock")

    def __init__(self, lock, maxlen=1024):
        self.count = 0
        self.sum = 0.0
        self.min = math.inf
        self.max = -math.inf
        self._values = deque(maxlen=maxlen)
        self._lock = lock

    def observe(self, v):
        v = float(v)                # sync-ok: contract — host scalars only
        with self._lock:
            self.count += 1
            self.sum += v
            self.min = min(self.min, v)
            self.max = max(self.max, v)
            self._values.append(v)

    def values(self):
        """Copy of the bounded reservoir (most-recent observations) —
        cross-replica aggregation (ISSUE 12: ReplicaPool pool-level
        TTFT percentiles) merges raw reservoirs instead of averaging
        already-summarized percentiles."""
        with self._lock:
            return list(self._values)

    def summary(self):
        with self._lock:
            vals = sorted(self._values)
            count, total = self.count, self.sum
            lo, hi = self.min, self.max
            # inside the lock: a concurrent observe() between the copy
            # and this read would make 'last' inconsistent with the
            # rest of the snapshot (last > max)
            last = self._values[-1] if self._values else None
        if not vals:
            return {"count": 0, "sum": 0.0}

        def pct(q):
            return vals[min(len(vals) - 1,
                            max(0, int(round(q / 100.0 * (len(vals) - 1)))))]
        return {
            "count": count,
            "sum": total,
            "mean": total / max(count, 1),
            "min": lo,
            "max": hi,
            "p50": pct(50),
            "p90": pct(90),
            "p99": pct(99),
            "last": last,
        }


class MetricsRegistry:
    """Named metric store. Metric names are ``/``-separated paths
    (``train/step_time_s``, ``serving/ttft_s``); the first segment is
    the subsystem, which exporters may filter on."""

    def __init__(self):
        self._lock = threading.RLock()
        self._counters = {}
        self._gauges = {}
        self._histograms = {}

    def counter(self, name) -> Counter:
        with self._lock:
            c = self._counters.get(name)
            if c is None:
                c = self._counters[name] = Counter(self._lock)
            return c

    def gauge(self, name) -> Gauge:
        with self._lock:
            g = self._gauges.get(name)
            if g is None:
                g = self._gauges[name] = Gauge(self._lock)
            return g

    def histogram(self, name, maxlen=1024) -> Histogram:
        with self._lock:
            h = self._histograms.get(name)
            if h is None:
                h = self._histograms[name] = Histogram(self._lock, maxlen)
            return h

    def peek_gauge(self, name):
        """Current gauge value WITHOUT creating the gauge (None when it
        was never set) — per-fence readers (telemetry/cluster.py) must
        neither pollute the registry with empty metrics nor pay a full
        snapshot() to read three values."""
        with self._lock:
            g = self._gauges.get(name)
            return None if g is None else g.value

    def peek_histogram_last(self, name):
        """Most recent observation of a histogram, or None when absent
        or empty — same per-fence-reader rationale as peek_gauge."""
        with self._lock:
            h = self._histograms.get(name)
            if h is None or not h._values:
                return None
            return h._values[-1]

    def peek_histogram_count(self, name):
        """Lifetime observation count of a histogram WITHOUT creating
        it (0 when absent) — the SLO plane's new-tail cursor
        (telemetry/slo.py feed_counted) polls this at tick cadence."""
        with self._lock:
            h = self._histograms.get(name)
            return 0 if h is None else h.count

    def peek_histogram_values(self, name):
        """Reservoir copy WITHOUT creating the histogram ([] when
        absent) — cross-replica mergers (ReplicaPool.metrics_snapshot)
        must not seed idle replicas' registries with phantom
        zero-count metrics."""
        with self._lock:
            h = self._histograms.get(name)
            return [] if h is None else list(h._values)

    def snapshot(self, prefix=None):
        """One JSON-able dict of everything (optionally filtered to
        names starting with ``prefix``)."""
        with self._lock:
            counters = {k: c.value for k, c in self._counters.items()}
            gauges = {k: g.value for k, g in self._gauges.items()}
            hists = dict(self._histograms)
        if prefix:
            counters = {k: v for k, v in counters.items()
                        if k.startswith(prefix)}
            gauges = {k: v for k, v in gauges.items()
                      if k.startswith(prefix)}
            hists = {k: v for k, v in hists.items()
                     if k.startswith(prefix)}
        return {
            "counters": counters,
            "gauges": gauges,
            "histograms": {k: h.summary() for k, h in hists.items()},
        }

    def reset(self):
        """Drop every metric (snapshot-and-reset windows)."""
        with self._lock:
            self._counters.clear()
            self._gauges.clear()
            self._histograms.clear()


_default = MetricsRegistry()


def default_registry() -> MetricsRegistry:
    """The process-wide registry — the engine, spans, and serving
    default here so one JSONL stream carries every subsystem."""
    return _default


# ---------------------------------------------------------------- export

class JsonlExporter:
    """Appends one JSON line per export: wall-clock timestamp, rank,
    step, and the full snapshot — the multi-process-mergeable stream
    (each rank writes its own file; events self-identify).

    Size-bounded rotation (ISSUE 6 satellite): when ``max_bytes`` > 0
    and the file crosses it after an export, the stream rotates
    logrotate-style — ``path`` → ``path.1`` → … → ``path.{max_files-1}``
    and the oldest drops — so a multi-hour run holds at most
    ``max_files × max_bytes`` of scalar history on disk."""

    def __init__(self, path, registry=None, max_bytes=0, max_files=4):
        self.path = path
        self.registry = registry or default_registry()
        self.rank = _process_rank()
        self.max_bytes = int(max_bytes or 0)
        self.max_files = max(int(max_files), 1)
        d = os.path.dirname(path)
        if d:
            os.makedirs(d, exist_ok=True)
        self._fh = open(path, "a")

    def _rotate(self):
        self._fh.close()
        # shift path.{k} -> path.{k+1}, oldest falls off the end
        for k in range(self.max_files - 1, 0, -1):
            src = self.path if k == 1 else f"{self.path}.{k - 1}"
            dst = f"{self.path}.{k}"
            if os.path.exists(src):
                os.replace(src, dst)
        if self.max_files == 1:          # bounded to ONE file: truncate
            open(self.path, "w").close()
        self._fh = open(self.path, "a")

    def export(self, step=None, snapshot=None):
        snap = snapshot if snapshot is not None else self.registry.snapshot()
        self._fh.write(json.dumps({
            "ts": time.time(),
            "rank": self.rank,
            "step": step,
            "metrics": snap,
        }) + "\n")
        self._fh.flush()
        if self.max_bytes and self._fh.tell() >= self.max_bytes:
            self._rotate()

    def close(self):
        self._fh.close()


class SummaryBridge:
    """Bridges a snapshot into the existing ``SummaryEventWriter``
    (TensorBoard when available, JSONL events otherwise): counters and
    gauges as plain scalars, histograms as p50/p90/p99/mean scalars."""

    def __init__(self, writer, registry=None):
        self.writer = writer
        self.registry = registry or default_registry()

    def export(self, step, snapshot=None):
        snap = snapshot if snapshot is not None else self.registry.snapshot()
        w = self.writer
        for k, v in snap["counters"].items():
            w.add_scalar(k, v, step)
        for k, v in snap["gauges"].items():
            w.add_scalar(k, v, step)
        for k, s in snap["histograms"].items():
            if not s.get("count"):
                continue
            for stat in ("mean", "p50", "p90", "p99"):
                w.add_scalar(f"{k}/{stat}", s[stat], step)
        w.flush()


def _prom_name(name):
    out = []
    for ch in name:
        out.append(ch if ch.isalnum() or ch == "_" else "_")
    n = "".join(out)
    return ("_" + n) if n[:1].isdigit() else n


def _prom_escape_label(value):
    """Escape a label VALUE per the exposition format (backslash,
    double-quote and newline must be escaped inside the quotes) — real
    scrapers reject unescaped ones."""
    return (str(value).replace("\\", "\\\\").replace('"', '\\"')
            .replace("\n", "\\n"))


def _prom_escape_help(text):
    """HELP text escaping: backslash and newline only (HELP lines are
    unquoted)."""
    return str(text).replace("\\", "\\\\").replace("\n", "\\n")


def _prom_header(lines, prom_name, metric_name, kind):
    """``# HELP`` then ``# TYPE`` (the order scrapers expect) for one
    metric family. The help text carries the original ``/``-separated
    metric path — the name mangling is lossy, the HELP line is not."""
    lines.append(f"# HELP {prom_name} deepspeed_tpu metric "
                 f"{_prom_escape_help(metric_name)}")
    lines.append(f"# TYPE {prom_name} {kind}")


def prometheus_text(registry=None, snapshot=None):
    """Prometheus exposition-format text dump of a snapshot: counters
    as ``counter``, gauges as ``gauge``, histograms as ``summary``
    (quantiles + _sum/_count). Every family carries ``# HELP`` and
    ``# TYPE`` lines and label values are escaped, so real scrapers
    (prometheus, vmagent) parse the page cleanly (ISSUE 6 satellite)."""
    snap = snapshot if snapshot is not None else \
        (registry or default_registry()).snapshot()
    lines = []
    for k, v in sorted(snap["counters"].items()):
        n = _prom_name(k)
        _prom_header(lines, n, k, "counter")
        lines.append(f"{n} {v}")
    for k, v in sorted(snap["gauges"].items()):
        n = _prom_name(k)
        _prom_header(lines, n, k, "gauge")
        lines.append(f"{n} {v}")
    for k, s in sorted(snap["histograms"].items()):
        n = _prom_name(k)
        _prom_header(lines, n, k, "summary")
        for q, stat in (("0.5", "p50"), ("0.9", "p90"), ("0.99", "p99")):
            if stat in s:
                lines.append(
                    f'{n}{{quantile="{_prom_escape_label(q)}"}} {s[stat]}')
        lines.append(f"{n}_sum {s.get('sum', 0.0)}")
        lines.append(f"{n}_count {s.get('count', 0)}")
    return "\n".join(lines) + "\n"
