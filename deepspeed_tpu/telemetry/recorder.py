"""Flight recorder: a process-wide, bounded ring buffer of structured
events — the "what happened on THAT step/request" layer the aggregate
registry (registry.py) cannot answer.

The registry answers "how fast on average"; the recorder keeps the last
``capacity`` discrete events (step lifecycle with per-phase host
timings, swap-tier I/O, prefetch/overlap bucket plans, serving request
lifecycle) so that when something goes wrong — a NaN loss, a step-time
spike, a TTFT blowup — the watchdog (anomaly.py) can dump the recent
history to JSONL and ``python -m deepspeed_tpu.telemetry.view`` can
reconstruct the offending step or request.

Design rules (same sync-discipline contract as the registry):

- recording is host-only and cheap: one enabled-flag read, a dict
  build, a lock acquire, a deque append. Nothing here ever touches a
  device value — callers pass host scalars they already have;
- the ring is bounded (``deque(maxlen=capacity)``): a multi-day run
  holds the last ~capacity events and nothing more;
- everything is thread-safe: the serving scheduler, aio completion
  paths and a training loop may record concurrently;
- when disabled, ``record()`` is a single attribute read and return —
  the recorder-off cost in a hot loop is one branch.

Events are plain dicts: ``{"ts": wall_clock, "seq": monotonic_int,
"kind": str, ...payload}`` plus a ``"step"`` field injected from the
recorder's current training-step context when one is set. Kinds in use
(docs/observability.md has the full schema):

- ``span`` (tag, dur_s) — host phase timings from spans.span();
- ``step`` (step, tokens, swap_stall_s) / ``loss`` (step, loss) /
  ``window`` (step_s, steps) — engine step lifecycle;
- ``swap_out`` / ``swap_in`` / ``swap_drain`` — swap-tier I/O
  (runtime/swap_tensor/swapper.py);
- ``overlap_bucket_plan`` — trace-time bucket planning
  (parallel/overlap.py);
- ``admit`` / ``prefill`` / ``tick`` / ``finish`` / ``pool_exhausted``
  — serving request lifecycle (serving/engine.py);
- ``ckpt_begin`` / ``ckpt_commit`` / ``ckpt_abort`` / ``ckpt_corrupt``
  / ``preempt_signal`` / ``preempt`` / ``resume`` — elastic snapshot +
  preemption lifecycle (runtime/elastic, ISSUE 7);
- ``anomaly`` — appended by the watchdog after it dumps.
"""

import threading
import time
from collections import deque


class FlightRecorder:
    """Bounded, thread-safe ring of structured events."""

    def __init__(self, capacity=4096, enabled=True):
        self.enabled = bool(enabled)
        self._lock = threading.Lock()
        self._ring = deque(maxlen=max(int(capacity), 32))
        self._seq = 0
        self._step = None

    @property
    def capacity(self):
        return self._ring.maxlen

    def configure(self, enabled=None, capacity=None):
        """Reconfigure in place (the engine applies the
        ``monitor.flight_recorder`` block here). Shrinking/growing the
        capacity keeps the most recent events."""
        with self._lock:
            if enabled is not None:
                self.enabled = bool(enabled)
            if capacity is not None and int(capacity) != self._ring.maxlen:
                self._ring = deque(self._ring,
                                   maxlen=max(int(capacity), 32))
        return self

    def set_step(self, step):
        """Set the training-step context stamped onto subsequent events
        (a plain int store — benign under concurrent readers)."""
        self._step = int(step) if step is not None else None

    def record(self, kind, **fields):
        """Append one event. Host scalars only — never pass a device
        array (the sync-discipline contract; test_sync_guard pins the
        module). No-op when disabled."""
        if not self.enabled:
            return
        ev = {"ts": time.time(), "kind": kind}
        step = self._step
        if step is not None and "step" not in fields:
            ev["step"] = step
        ev.update(fields)
        with self._lock:
            self._seq += 1
            ev["seq"] = self._seq
            self._ring.append(ev)

    def events(self):
        """A consistent copy of the ring, oldest first."""
        with self._lock:
            return list(self._ring)

    def __len__(self):
        with self._lock:
            return len(self._ring)

    def clear(self):
        with self._lock:
            self._ring.clear()


_default = FlightRecorder()


def default_recorder() -> FlightRecorder:
    """The process-wide recorder — the engine, spans, swap tier and
    serving scheduler all default here so one ring carries every
    subsystem's recent history (what a post-anomaly dump needs)."""
    return _default
