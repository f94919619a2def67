"""Anomaly watchdog: fence-point rule evaluation + one-shot ring dumps.

The watchdog turns the flight recorder (recorder.py) into an incident
reporter: when a rule trips, it writes ONE JSONL dump of the ring —
the last ~capacity events leading up to the anomaly — and latches so a
persistent condition (a NaN loss that stays NaN, a saturated page pool)
produces exactly one dump, not one per step.

The cardinal rule, inherited from the telemetry sync discipline
(docs/observability.md): **the watchdog never forces a device sync.**
Every hook takes host scalars the caller already paid for at an
existing fence point:

- ``check_loss(v)`` — the engine's ``steps_per_print`` boundary, where
  the loss readback already happened (NaN/inf detection);
- ``observe_step_time(s)`` — the boundary window fold (outlier vs a
  rolling baseline);
- ``observe_swap_stall(s)`` — the per-step host stall timer the swap
  tier already keeps (outlier vs baseline, with an absolute floor);
- ``observe_ttft(s)`` / ``note_pool_exhausted()`` — the serving
  scheduler's admission sweep, whose prefill-logits readback is the
  TTFT measurement itself;
- ``observe_ckpt_stall(s)`` / ``note_ckpt_corrupt()`` /
  ``note_preempt()`` — the elastic snapshot layer (ISSUE 7): the
  commit-fence stall timer the engine already keeps, resume-time
  validation failures, and the preemption incident itself;
- ``note_rank_dead()`` / ``note_crash_loop()`` — the fault-tolerance
  plane (ISSUE 15): a rank's hard death or hung collective as
  observed by the hang watchdog (runtime/elastic/hang.py) or the
  launcher-level supervisor (runtime/elastic/supervisor.py), and the
  terminal exhausted-restart-budget incident.

Outlier rules keep a rolling baseline of recent NORMAL observations
(anomalous values never pollute their own baseline) and trip when a
value exceeds ``max(factor * baseline_mean, min_value)``; they re-arm
once a normal value is seen again. Dumps are numbered by a monotonic
``dump_id`` surfaced in ``snapshot()`` (and, for serving, in
``ContinuousBatcher.metrics_snapshot()``).
"""

import json
import math
import os
import threading
import time
from collections import deque

from deepspeed_tpu.telemetry.recorder import default_recorder
from deepspeed_tpu.telemetry.registry import default_registry
from deepspeed_tpu.utils.logging import logger

_PROVENANCE = None


def _provenance_doc():
    """Cached host/build stamp for dump headers (ISSUE 19 satellite):
    a dump read days later off a shared scratch dir must answer "which
    box, which sha, which restart epoch" without archaeology. The git
    subprocess runs ONCE per process, not per dump; an installed package
    with no checkout round it reads ``git_sha: "unknown"``."""
    global _PROVENANCE
    if _PROVENANCE is None:
        import platform
        import socket
        import subprocess
        try:
            sha = subprocess.check_output(
                ["git", "rev-parse", "--short", "HEAD"],
                cwd=os.path.dirname(os.path.abspath(__file__)),
                stderr=subprocess.DEVNULL).decode().strip()
        except Exception:
            sha = "unknown"
        _PROVENANCE = {"git_sha": sha,
                       "hostname": socket.gethostname(),
                       "cpu_count": os.cpu_count(),
                       "jax_version": "unknown",
                       "python_version": platform.python_version()}
    return _PROVENANCE


class RollingOutlierRule:
    """Trip when a value exceeds ``max(factor * rolling_median,
    min_value)``; latch until a normal value re-arms. Values observed
    while the baseline is still warming (< ``min_samples``) only feed
    the baseline — and the baseline statistic is the MEDIAN, so a
    single extreme warm-up observation (a compile-inflated first
    window, a cold-cache first read) cannot poison the threshold the
    way a mean would."""

    def __init__(self, name, factor=3.0, min_value=0.0, window=64,
                 min_samples=8):
        assert factor > 1.0, (name, factor)
        self.name = name
        self.factor = factor
        self.min_value = min_value
        self.min_samples = max(int(min_samples), 1)
        self._baseline = deque(maxlen=max(int(window), self.min_samples))
        self._tripped = False

    def _median(self):
        vals = sorted(self._baseline)
        n = len(vals)
        mid = n // 2
        return vals[mid] if n % 2 else (vals[mid - 1] + vals[mid]) / 2.0

    def threshold(self):
        """Current trip threshold, or None while warming."""
        if len(self._baseline) < self.min_samples:
            return None
        return max(self.factor * self._median(), self.min_value)

    def observe(self, v):
        """Returns a detail dict when this observation TRIPS the rule
        (first anomalous value after normal ones), else None."""
        thr = self.threshold()
        if thr is not None and v > thr:
            if self._tripped:
                return None              # latched: one dump per episode
            self._tripped = True
            return {"value": v, "threshold": thr,
                    "baseline_median": self._median(),
                    "baseline_n": len(self._baseline)}
        self._tripped = False
        self._baseline.append(v)
        return None


class StragglerRule:
    """Cluster rank-straggler rule (ISSUE 12): at each cluster fence it
    sees the per-rank step-time vector (``None``/NaN = rank did not
    measure this fence) and trips when one rank exceeds ``max(factor *
    median_of_the_OTHER_ranks, min_value)`` for ``fences`` CONSECUTIVE
    fences. The leave-one-out median matters at small world sizes: with
    2 ranks a whole-cluster median includes the straggler itself, so a
    rank 10x slower only reaches ~1.8x the median and a 2x factor would
    never fire. Latched per rank per episode — a persistently slow rank
    dumps once; a fence where it looks normal re-arms it."""

    def __init__(self, factor=2.0, min_value=0.0, fences=3):
        assert factor > 1.0, factor
        self.factor = factor
        self.min_value = min_value
        self.fences = max(int(fences), 1)
        self._streak = {}            # rank -> consecutive slow fences
        self._tripped = set()        # latched ranks

    def observe(self, per_rank):
        """``per_rank``: sequence of step-time seconds (None/NaN for
        unmeasured ranks). Returns a detail dict when the WORST newly
        over-threshold-for-K-fences rank trips, else None (other
        simultaneous stragglers latch silently this fence)."""
        vals = {r: float(v) for r, v in enumerate(per_rank)
                if v is not None and math.isfinite(v)}  # sync-ok: host
        if len(vals) < 2:
            # no comparison possible: CONSECUTIVE is broken for every
            # rank — freezing the streaks here would let slow fences
            # separated by arbitrary unmeasured gaps count as adjacent
            self._streak.clear()
            return None
        for r, _v in enumerate(per_rank):
            if r not in vals:
                # a rank that skipped measurement this fence breaks its
                # own consecutiveness (the latch stays: unmeasured is
                # not evidence of normality, only a normal fence re-arms)
                self._streak[r] = 0
        trips = []
        for r, v in vals.items():
            others = sorted(x for q, x in vals.items() if q != r)
            n = len(others)
            med = others[n // 2] if n % 2 \
                else (others[n // 2 - 1] + others[n // 2]) / 2.0
            thr = max(self.factor * med, self.min_value)
            if v > thr:
                self._streak[r] = self._streak.get(r, 0) + 1
                if self._streak[r] >= self.fences \
                        and r not in self._tripped:
                    trips.append({"rank": r, "value": v,
                                  "threshold": thr,
                                  "peer_median": med,
                                  "consecutive_fences": self._streak[r],
                                  "world": len(per_rank)})
            else:
                self._streak[r] = 0
                self._tripped.discard(r)
        if not trips:
            return None
        worst = max(trips, key=lambda t: t["value"])
        for t in trips:              # every qualifying rank latches,
            self._tripped.add(t["rank"])   # only the worst dumps
        return worst


class Watchdog:
    """Fence-point anomaly rules over the flight recorder, with
    one-shot JSONL dumps. One instance per subsystem (the engine builds
    one with ``source="train"``, the serving scheduler one with
    ``source="serving"``) — both share the process-wide recorder by
    default, so either's dump carries the full recent history."""

    def __init__(self, dump_dir, recorder=None, registry=None,
                 source="train", step_time_factor=3.0,
                 swap_stall_factor=4.0, swap_stall_min_s=0.05,
                 ttft_factor=4.0, ttft_min_s=1.0,
                 ckpt_stall_factor=4.0, ckpt_stall_min_s=0.25,
                 straggler_factor=2.0, straggler_fences=3,
                 straggler_min_s=0.0,
                 baseline_window=64, min_samples=8, check_nan=True,
                 max_dumps=0):
        self.dump_dir = dump_dir
        self.source = source
        self.recorder = recorder if recorder is not None \
            else default_recorder()
        self.registry = registry if registry is not None \
            else default_registry()
        self.check_nan = bool(check_nan)
        self.max_dumps = int(max_dumps)      # 0 = unlimited
        self.dump_id = 0
        self.last_anomaly = None
        self.trips = {}                      # rule name -> count
        self._lock = threading.Lock()
        self._nan_tripped = False
        self._pool_tripped = False
        self._ckpt_corrupt_tripped = False
        self._preempt_tripped = False
        self._rank_dead_tripped = False
        self._crash_loop_tripped = False
        self._rules = {
            "step_time_outlier": RollingOutlierRule(
                "step_time_outlier", factor=step_time_factor,
                window=baseline_window, min_samples=min_samples),
            "swap_stall_outlier": RollingOutlierRule(
                "swap_stall_outlier", factor=swap_stall_factor,
                min_value=swap_stall_min_s, window=baseline_window,
                min_samples=min_samples),
            "ttft_blowup": RollingOutlierRule(
                "ttft_blowup", factor=ttft_factor, min_value=ttft_min_s,
                window=baseline_window, min_samples=min_samples),
            # ISSUE 7: the async-snapshot commit fence is supposed to be
            # ~free (writes had a whole step to land); a stall past
            # factor x baseline means the aio write stream fell behind
            # training — snapshot-stall
            "ckpt_stall_outlier": RollingOutlierRule(
                "ckpt_stall_outlier", factor=ckpt_stall_factor,
                min_value=ckpt_stall_min_s, window=baseline_window,
                min_samples=min_samples),
        }
        # ISSUE 12: per-rank straggler detection over cluster fences —
        # fed by the ClusterAggregator's rank-0 fold, never by a new
        # collective of its own
        self._straggler = StragglerRule(
            factor=straggler_factor, min_value=straggler_min_s,
            fences=straggler_fences)

    @classmethod
    def from_config(cls, watchdog_cfg, recorder=None, registry=None,
                    source="train"):
        """None when the gate is off (no ``monitor.watchdog`` block)."""
        if not getattr(watchdog_cfg, "enabled", False):
            return None
        return cls(
            watchdog_cfg.dump_dir, recorder=recorder, registry=registry,
            source=source,
            step_time_factor=watchdog_cfg.step_time_factor,
            swap_stall_factor=watchdog_cfg.swap_stall_factor,
            swap_stall_min_s=watchdog_cfg.swap_stall_min_s,
            ttft_factor=watchdog_cfg.ttft_factor,
            ttft_min_s=watchdog_cfg.ttft_min_s,
            ckpt_stall_factor=watchdog_cfg.ckpt_stall_factor,
            ckpt_stall_min_s=watchdog_cfg.ckpt_stall_min_s,
            straggler_factor=getattr(watchdog_cfg, "straggler_factor",
                                     2.0),
            straggler_fences=getattr(watchdog_cfg, "straggler_fences", 3),
            straggler_min_s=getattr(watchdog_cfg, "straggler_min_s", 0.0),
            baseline_window=watchdog_cfg.baseline_window,
            min_samples=watchdog_cfg.min_samples,
            check_nan=watchdog_cfg.check_nan,
            max_dumps=watchdog_cfg.max_dumps)

    # ------------------------------------------------------------- hooks
    # Every hook takes HOST scalars its caller already read at an
    # existing fence — the watchdog itself never syncs.

    def check_loss(self, loss_value, step=None):
        """NaN/inf loss at the steps_per_print boundary readback.
        Latched: a loss that stays non-finite dumps once; a finite loss
        re-arms."""
        if not self.check_nan:
            return None
        if math.isfinite(loss_value):
            self._nan_tripped = False
            return None
        if self._nan_tripped:
            return None
        self._nan_tripped = True
        return self._trigger("nan_loss",
                             {"loss": repr(loss_value), "step": step})

    def observe_step_time(self, step_s, step=None):
        """Boundary-window mean step time vs the rolling baseline."""
        det = self._rules["step_time_outlier"].observe(step_s)
        if det is None:
            return None
        det["step"] = step
        return self._trigger("step_time_outlier", det)

    def observe_swap_stall(self, stall_s, step=None):
        """Per-step swap-tier blocked-on-I/O seconds vs baseline (with
        an absolute floor so a 1 ms -> 5 ms wiggle never dumps)."""
        det = self._rules["swap_stall_outlier"].observe(stall_s)
        if det is None:
            return None
        det["step"] = step
        return self._trigger("swap_stall_outlier", det)

    def observe_ttft(self, ttft_s, rid=None):
        """Serving time-to-first-token vs the rolling baseline."""
        det = self._rules["ttft_blowup"].observe(ttft_s)
        if det is None:
            return None
        det["rid"] = rid
        return self._trigger("ttft_blowup", det)

    def note_pool_exhausted(self, queue_depth=0, free_pages=0,
                            need_pages=0):
        """Admission blocked on page-pool pages. Latched per episode:
        one dump until an admission succeeds (``note_pool_ok``)."""
        if self._pool_tripped:
            return None
        self._pool_tripped = True
        return self._trigger("page_pool_exhausted",
                             {"queue_depth": queue_depth,
                              "free_pages": free_pages,
                              "need_pages": need_pages})

    def note_pool_ok(self):
        self._pool_tripped = False

    def observe_ckpt_stall(self, stall_s, step=None):
        """Host seconds the engine's step boundary blocked on the
        snapshot drain fence (ISSUE 7) vs the rolling baseline, with an
        absolute floor — the snapshot-stall rule."""
        det = self._rules["ckpt_stall_outlier"].observe(stall_s)
        if det is None:
            return None
        det["step"] = step
        return self._trigger("ckpt_stall_outlier", det)

    def observe_rank_step_times(self, per_rank, step=None):
        """Cluster rank-straggler check (ISSUE 12): ``per_rank`` is the
        per-rank step-time vector the ClusterAggregator allgathered at
        an EXISTING fence (the steps_per_print readback / a snapshot
        commit fence) and folded on rank 0 — host floats only, the
        collective already happened. Trips ``rank_straggler`` naming
        the offending rank after K consecutive slow fences."""
        det = self._straggler.observe(per_rank)
        if det is None:
            return None
        det["step"] = step
        return self._trigger("rank_straggler", det)

    def note_ckpt_corrupt(self, path, reason):
        """An elastic-resume candidate failed validation (torn
        manifest, rotted shard, missing rank). Latched per recovery
        episode: a multi-candidate fallback chain dumps ONCE; a
        successful load (``note_ckpt_ok``) re-arms."""
        if self._ckpt_corrupt_tripped:
            return None
        self._ckpt_corrupt_tripped = True
        return self._trigger("ckpt_corrupt",
                             {"dir": str(path), "reason": str(reason)})

    def note_ckpt_ok(self):
        self._ckpt_corrupt_tripped = False

    def note_preempt(self, step=None, snapshotted=None, grace_s=None,
                     source=None):
        """Preemption incident (ISSUE 7): one dump carrying the ring
        history leading up to the SIGTERM, stamped with whether the
        final snapshot committed inside the grace budget."""
        if self._preempt_tripped:
            return None
        self._preempt_tripped = True
        return self._trigger("preempt",
                             {"step": step, "snapshotted": snapshotted,
                              "grace_s": grace_s, "source": source})

    def note_preempt_ok(self):
        """Re-arm the preempt latch after an incident is fully handled
        (ISSUE 11: a replica-pool supervisor survives its replicas, so
        a SECOND kill later in the same process must dump again —
        unlike training, where one preemption ends the process)."""
        self._preempt_tripped = False

    def note_rank_dead(self, rank=None, reason=None, step=None,
                       exit_code=None, blocked_s=None, deadline_s=None,
                       restart_epoch=None, world=None):
        """A rank left the world uncleanly (ISSUE 15): a hard death the
        supervisor observed (SIGKILL/OOM/node loss, ``reason``
        carrying the exit classification), or — fired from INSIDE a
        surviving rank by the collective hang watchdog
        (runtime/elastic/hang.py) — a collective stalled past the hang
        deadline (``reason="collective_hang"``, ``blocked_s``). Latched
        per incident: one dump however many ranks die together (the
        supervisor's teardown makes the survivors exit nonzero too,
        and each of those must not re-dump); a successful restart
        re-arms it (``note_world_ok``)."""
        if self._rank_dead_tripped:
            return None
        self._rank_dead_tripped = True
        return self._trigger("rank_dead",
                             {"rank": rank, "reason": reason,
                              "step": step, "exit_code": exit_code,
                              "blocked_s": blocked_s,
                              "deadline_s": deadline_s,
                              "restart_epoch": restart_epoch,
                              "world": world})

    def note_world_ok(self):
        """Re-arm the rank-dead latch after the supervisor respawned a
        healthy world — the NEXT incident is a new episode and must
        dump again."""
        self._rank_dead_tripped = False

    def note_crash_loop(self, restarts=None, max_restarts=None,
                        world=None, last_reason=None):
        """The supervisor's restart budget is exhausted (ISSUE 15): a
        world that dies every epoch stopped being restarted. Latched
        and NEVER re-armed — the condition is terminal for this
        supervisor, so there is exactly one ``crash_loop`` dump per
        process however the exit path replays."""
        if self._crash_loop_tripped:
            return None
        self._crash_loop_tripped = True
        return self._trigger("crash_loop",
                             {"restarts": restarts,
                              "max_restarts": max_restarts,
                              "world": world,
                              "last_reason": last_reason})

    # -------------------------------------------------------------- dump

    def force_dump(self, reason="manual"):
        """Unconditional dump of the current ring (debug hook)."""
        return self._trigger(reason, {}, forced=True)

    def _trigger(self, rule, detail, forced=False):
        """Write one JSONL dump of the ring: a ``dump_header`` line then
        every ring event, oldest first. Returns the dump path (None if
        dumping failed or the dump budget is spent — the trip is still
        counted and surfaced)."""
        with self._lock:
            self.dump_id += 1
            dump_id = self.dump_id
            self.trips[rule] = self.trips.get(rule, 0) + 1
        events = self.recorder.events()
        info = {"kind": "dump_header", "rule": rule, "dump_id": dump_id,
                "source": self.source, "ts": time.time(),
                "detail": detail, "n_events": len(events),
                "recorder_capacity": self.recorder.capacity,
                # ISSUE 19 satellite: which box/sha/incarnation wrote
                # this dump — the Perfetto merger and any human reading
                # a days-old dump both need it in the header, not in
                # out-of-band notes
                "provenance": dict(_provenance_doc()),
                "restart_epoch": int(
                    os.environ.get("DSTPU_RESTART_EPOCH", "0") or 0)}
        self.last_anomaly = {"rule": rule, "dump_id": dump_id,
                             "ts": info["ts"], "detail": detail}
        reg = self.registry
        reg.counter("watchdog/dumps").inc()
        reg.counter(f"watchdog/trips/{rule}").inc()
        reg.gauge("watchdog/last_dump_id").set(dump_id)
        path = None
        if not self.max_dumps or dump_id <= self.max_dumps:
            try:
                os.makedirs(self.dump_dir, exist_ok=True)
                path = os.path.join(
                    self.dump_dir,
                    f"flight_{self.source}_{dump_id:04d}_{rule}.jsonl")
                with open(path, "w") as fh:
                    # default=repr: an exotic payload value (a tuple
                    # request id, a dtype) must degrade to its repr,
                    # never crash the fence point that triggered us
                    fh.write(json.dumps(info, default=repr) + "\n")
                    for ev in events:
                        fh.write(json.dumps(ev, default=repr) + "\n")
            except OSError as e:       # an unwritable dir must not kill
                logger.warning(f"watchdog dump failed: {e}")
                path = None
        self.last_anomaly["dump_path"] = path
        if not forced:
            logger.warning(
                f"[watchdog] {rule} tripped ({self.source}); "
                f"dump #{dump_id}: {path or '<not written>'}")
        # the anomaly marker lands in the ring AFTER the snapshot, so
        # the dump holds the pre-anomaly history and the NEXT dump shows
        # this one as an event
        self.recorder.record("anomaly", rule=rule, dump_id=dump_id,
                             dump_path=path, **{
                                 k: v for k, v in detail.items()
                                 if isinstance(v, (int, float, str,
                                                   type(None)))})
        return path

    def snapshot(self):
        """JSON-able watchdog state (serving embeds this in
        ``metrics_snapshot()``)."""
        return {"dump_id": self.dump_id,
                "last_anomaly": self.last_anomaly,
                "trips": dict(self.trips)}
