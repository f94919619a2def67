"""Replica-pool supervisor: N serving engines, cross-replica resume,
watchdog-driven autoscaling (ISSUE 11).

One :class:`ReplicaPool` runs N in-process
:class:`~deepspeed_tpu.serving.engine.ContinuousBatcher` replicas
(sharing one adapter's compiled programs — the long-lived-server shape
of serving) and owns the request ledger above them:

- **dispatch**: arrivals go to the least-loaded live replica;
- **recovery**: a replica that dies (an injected ``SimulatedCrash``
  unwinding out of its ``step()``, or :meth:`kill_replica`) is
  recovered from its last COMMITTED elastic snapshot — the snapshotted
  requests restore onto the least-loaded survivor
  (``elastic.restore_serving``: direct slot rebuilds + replay
  requeues), and anything the snapshot predates is re-served from the
  pool's own ledger. Every re-serve attempt is bounded
  (``max_retries``) with jittered exponential backoff (``backoff_s``)
  so a poisoned request cannot ping-pong across the pool forever.
  Greedy decoding makes every recovery path token-for-token lossless:
  replayed requests regenerate exactly the continuation the dead
  replica would have produced.
- **autoscale** (``scale_signal="watchdog"``): the PR 6 watchdog's
  LATCHED incident rules are the scale-up signal — new
  ``ttft_blowup`` / ``page_pool_exhausted`` trips on any replica add a
  replica (up to ``max_replicas``); a pool that stays overprovisioned
  for ``scale_down_idle_rounds`` consecutive rounds drains its
  least-loaded replica through the SAME snapshot path (preempt →
  drain-or-snapshot → restore onto survivors) down to
  ``min_replicas``. Both directions land a ``replica_scale`` ring
  event.

The pool is deliberately host-side and single-threaded: one round of
:meth:`step` steps every replica once, so the device work interleaves
exactly like the single-engine scheduler's and the fault points fire
at deterministic places (the property the recovery tests pin).
"""

import json
import os
import time
from collections import OrderedDict, deque
from typing import Any, Dict, List, Optional

import numpy as np

from deepspeed_tpu.runtime.elastic.faults import SimulatedCrash
from deepspeed_tpu.serving import elastic
from deepspeed_tpu.serving.engine import Request, ensure_trace_id
from deepspeed_tpu.telemetry.recorder import default_recorder
from deepspeed_tpu.utils.logging import logger


def _req_to_doc(req):
    """Pool-ledger doc for a request as SUBMITTED (no progress) — the
    fresh re-serve fallback when no snapshot covers it. Same schema as
    the snapshot's slot docs (ONE serializer, progress zeroed)."""
    return dict(elastic._req_doc(req), generated=[])


def save_ledger(path, docs) -> None:
    """Persist a ``{rid: submitted doc}`` ledger atomically (tmp +
    rename — a SIGKILL mid-write leaves the previous valid file, never
    a torn one). ISSUE 17: the supervisor-respawned router rank
    re-serves the UNFINISHED slice of this ledger; greedy replay from
    the submitted docs is token-lossless, the PR-11 pool-ledger
    recovery rule applied across a process death."""
    tmp = f"{path}.tmp.{os.getpid()}"
    with open(tmp, "w") as fh:
        json.dump({str(rid): doc for rid, doc in docs.items()}, fh)
        fh.flush()
        os.fsync(fh.fileno())
    os.replace(tmp, path)


def load_ledger(path):
    """The saved ``{rid: doc}`` map (string rids — the caller's docs
    carry the native rid in ``doc["rid"]``), or None when absent."""
    try:
        with open(path) as fh:
            return json.load(fh)
    except OSError:
        return None


def percentile_summary(vals):
    """count/mean/p50/p90/p99 of a raw host reservoir — ONE percentile
    rule shared by the pool's and the router's metrics_snapshot so the
    two aggregation documents can't drift."""
    if not vals:
        return {"count": 0}
    v = np.asarray(vals, np.float64)       # sync-ok: host reservoirs
    return {"count": int(v.size),
            "mean": float(v.mean()),                  # sync-ok: host
            "p50": float(np.percentile(v, 50)),       # sync-ok: host
            "p90": float(np.percentile(v, 90)),       # sync-ok: host
            "p99": float(np.percentile(v, 99))}       # sync-ok: host


def merged_reservoir(engines, name):
    """Concatenate one histogram's raw values across engines, counting
    a SHARED registry once (the merged-stream case)."""
    vals, seen = [], set()
    for cb in engines:
        if id(cb.metrics) in seen:
            continue
        seen.add(id(cb.metrics))
        vals += cb.metrics.peek_histogram_values(name)
    return vals


class ReplicaPool:
    """See module docstring. ``factory(replica_id)`` builds one
    batcher — give each replica its OWN elastic snapshot dir (e.g.
    ``snapshot_root/replica_<id>``) and its own watchdog; crash
    recovery needs the former, autoscaling the latter."""

    def __init__(self, factory, n_replicas=1, min_replicas=1,
                 max_replicas=None, scale_signal="watchdog",
                 max_retries=3, backoff_s=0.05,
                 scale_down_idle_rounds=40, recorder=None,
                 watchdog=None, seed=0, slo_registry=None):
        self.factory = factory
        # ISSUE 19: scale_signal="slo" reads the windowed slo/* gauge
        # plane from here (an exported registry — typically the rank-0
        # node's); None falls back to the first live replica's registry
        self.slo_registry = slo_registry
        self.min_replicas = int(min_replicas)
        self.max_replicas = int(max_replicas
                                if max_replicas is not None
                                else max(n_replicas, min_replicas))
        self.scale_signal = str(scale_signal)
        self.max_retries = int(max_retries)
        self.backoff_s = float(backoff_s)   # sync-ok: config scalar
        self.scale_down_idle_rounds = int(scale_down_idle_rounds)
        self.recorder = recorder if recorder is not None \
            else default_recorder()
        self.watchdog = watchdog
        self._rng = np.random.RandomState(seed)
        self._next_id = 0
        self.replicas: "OrderedDict[int, Any]" = OrderedDict()
        self._draining = set()          # replica ids scaling down
        self._trip_base: Dict[int, int] = {}
        self._assign: Dict[Any, int] = {}      # rid -> replica id
        self._ledger: Dict[Any, dict] = {}     # rid -> submitted doc
        self._attempts: Dict[Any, int] = {}
        self._resume_q = deque()        # (ready_time, doc) re-serves
        self.done: Dict[Any, Request] = {}
        self.lost: Dict[Any, dict] = {}
        self.parked_snapshots: List[str] = []
        self._idle_rounds = 0
        # latched when a replica parks from a NON-scale-down preemption
        # (a process-wide SIGTERM): the pool stops respawning — the
        # final snapshots on disk are the hand-off, not a restart
        self.shutdown = False
        self.stats = {"kills": 0, "preempts": 0, "recovered_direct": 0,
                      "recovered_requeued": 0, "resubmitted_fresh": 0,
                      "lost": 0, "scale_ups": 0, "scale_downs": 0,
                      "restore_s_total": 0.0}
        for _ in range(max(int(n_replicas), self.min_replicas)):
            self._spawn(reason="init", record=False)

    @classmethod
    def from_config(cls, factory, config, n_replicas=None, **kw):
        """Build from the ``serving.autoscale`` + ``serving.elastic``
        blocks of a DeepSpeed-style config (dict or json path)."""
        from deepspeed_tpu.serving import _serving_section
        sc = _serving_section(config)
        a, e = sc.autoscale, sc.elastic
        return cls(factory,
                   n_replicas=(a.min_replicas if n_replicas is None
                               else n_replicas),
                   min_replicas=a.min_replicas,
                   max_replicas=a.max_replicas,
                   scale_signal=a.scale_signal,
                   max_retries=e.max_retries if e.enabled
                   else kw.pop("max_retries", 3),
                   backoff_s=e.backoff_s if e.enabled
                   else kw.pop("backoff_s", 0.05),
                   **kw)

    # ---------------------------------------------------------- replicas

    def _spawn(self, reason="scale_up", record=True):
        rid = self._next_id
        self._next_id += 1
        cb = self.factory(rid)
        # ISSUE 12: ring events from this batcher self-identify — the
        # replicas share one process-wide recorder, and the stitched
        # per-trace timeline needs to know which replica emitted what
        cb.replica_id = rid
        self.replicas[rid] = cb
        wd = cb.watchdog
        self._trip_base[rid] = self._trips_of(wd)
        if record:
            self.stats["scale_ups"] += 1
            self.recorder.record("replica_scale", direction="up",
                                 replica=rid, reason=reason,
                                 replicas=len(self.replicas))
        return rid

    @staticmethod
    def _trips_of(wd):
        if wd is None:
            return 0
        return wd.trips.get("ttft_blowup", 0) \
            + wd.trips.get("page_pool_exhausted", 0)

    def _live(self):
        return [(rid, cb) for rid, cb in self.replicas.items()
                if rid not in self._draining]

    def _least_loaded(self, exclude=()):
        best, best_load = None, None
        for rid, cb in self._live():
            if rid in exclude:
                continue
            load = len(cb.queue) + sum(s.active for s in cb.slots)
            if best_load is None or load < best_load:
                best, best_load = rid, load
        return best

    @property
    def pending(self) -> int:
        n = len(self._resume_q)
        for _rid, cb in self.replicas.items():
            n += cb.pending
        return n

    # ----------------------------------------------------------- ledger

    def submit(self, request: Request) -> None:
        ensure_trace_id(request)   # before the ledger doc freezes it
        self._ledger[request.rid] = _req_to_doc(request)
        self._attempts.setdefault(request.rid, 0)
        self._dispatch(request)

    def _dispatch(self, request: Request) -> None:
        target = self._least_loaded()
        if target is None:
            # no live replica (whole-pool preemption): hold as a
            # resume doc so a later spawn can pick it up
            self._resume_q.append((0.0, _req_to_doc(request)))
            return
        self._assign[request.rid] = target
        self.replicas[target].submit(request)

    def _schedule_reserve(self, doc, immediate=False):
        """Queue one snapshot/ledger doc for re-serving, with bounded
        retries + jittered exponential backoff."""
        rid = doc["rid"]
        self._attempts[rid] = self._attempts.get(rid, 0) + 1
        if self._attempts[rid] > self.max_retries:
            self.stats["lost"] += 1
            self.lost[rid] = doc
            self.recorder.record("serving_requeue", rid=rid,
                                 trace=doc.get("trace_id"),
                                 outcome="dropped",
                                 attempts=self._attempts[rid])
            logger.warning(f"request {rid!r} dropped after "
                           f"{self._attempts[rid] - 1} recovery retries")
            return
        delay = 0.0
        if not immediate:
            delay = self.backoff_s * (2 ** (self._attempts[rid] - 1)) \
                * float(self._rng.uniform(0.5, 1.5))  # sync-ok: host rng
        self._resume_q.append((time.monotonic() + delay, doc))
        self.recorder.record("serving_requeue", rid=rid,
                             trace=doc.get("trace_id"),
                             outcome="scheduled",
                             attempts=self._attempts[rid],
                             backoff_s=delay,
                             committed=len(doc["generated"]))

    def _drain_resume_q(self):
        now = time.monotonic()
        later = deque()
        while self._resume_q:
            ready, doc = self._resume_q.popleft()
            if ready > now or self._least_loaded() is None:
                later.append((ready, doc))
                continue
            req = elastic.resume_request(doc)
            target = self._least_loaded()
            self._assign[doc["rid"]] = target
            self.replicas[target].submit(req)
            if doc["generated"]:
                self.stats["recovered_requeued"] += 1
            else:
                self.stats["resubmitted_fresh"] += 1
        self._resume_q = later

    # --------------------------------------------------------- recovery

    def kill_replica(self, replica_id, reason="killed") -> None:
        """Hard-kill one replica (the injected-fault stand-in for a
        dead process): its batcher is discarded WITHOUT a final
        snapshot — recovery runs from its last committed one."""
        assert replica_id in self.replicas, replica_id
        self.stats["kills"] += 1
        self.recorder.record("replica_kill", replica=replica_id,
                             reason=reason)
        if self.watchdog is not None:
            self.watchdog.note_preempt(source=f"replica_{replica_id}_"
                                       f"{reason}")
            self.watchdog.note_preempt_ok()   # a pool outlives its
            #                              replicas: re-arm for the next
        self._recover(replica_id, final_snapshot=False)

    def preempt_replica(self, replica_id, source="scale_down") -> None:
        """Graceful removal: request preemption on the replica's
        elastic controller; its next steps run the drain-or-snapshot
        path and the pool recovers the snapshot once it parks."""
        cb = self.replicas[replica_id]
        assert cb.elastic is not None, \
            "preempt_replica needs an elastic controller on the replica"
        self._draining.add(replica_id)
        cb.elastic.request_preemption(source)

    def _recover(self, replica_id, final_snapshot):
        cb = self.replicas.pop(replica_id)
        self._draining.discard(replica_id)
        self._trip_base.pop(replica_id, None)
        snap_dir = None
        if cb.elastic is not None:
            snap_dir = cb.elastic.last_snapshot_dir if final_snapshot \
                else None
            if snap_dir is None:
                snap_dir = cb.elastic.snapshot_dir
            # release, NOT close: restoring the signal table mid-chain
            # would drop every later-installed replica's handler (the
            # dead controller's own handler is a weakref pass-through)
            cb.elastic.release()
        assigned = {rid for rid, r in self._assign.items()
                    if r == replica_id and rid not in self.done}
        recovered = set()
        t0 = time.perf_counter()
        if snap_dir and os.path.isdir(snap_dir) and assigned:
            loaded = self._load_snapshot(snap_dir)
            if loaded is not None:
                host, kv = loaded
                # the snapshot may predate finishes the pool already
                # collected — and may cover rids later re-assigned
                # elsewhere; serve only what is still this replica's
                host = dict(host)
                host["slots"] = [d for d in host["slots"]
                                 if d["rid"] in assigned]
                host["queued"] = [d for d in host["queued"]
                                  if d["rid"] in assigned]
                target = self._least_loaded()
                if target is not None:
                    try:
                        res = elastic.restore_serving(
                            self.replicas[target], host, kv,
                            requeue_overflow=False)
                    except elastic.ServingRestoreError as e:
                        # e.g. a replay prompt outgrew the target's
                        # prompt-page budget: the snapshot can't land
                        # here — fall through to ledger re-serves
                        # (fresh replays always fit what submit once
                        # accepted) rather than crash the supervisor
                        logger.warning(
                            f"snapshot of replica {replica_id} not "
                            f"restorable onto replica {target}: {e}")
                        res = None
                    if res is not None:
                        for req in res["restored"]:
                            self._assign[req.rid] = target
                            recovered.add(req.rid)
                        self.stats["recovered_direct"] += \
                            len(res["restored"])
                        for doc in res["overflow"]:
                            recovered.add(doc["rid"])
                            self._schedule_reserve(doc, immediate=True)
        for rid in sorted(assigned - recovered, key=str):
            # no snapshot coverage: re-serve from the pool ledger
            self._schedule_reserve(self._ledger[rid])
        self.stats["restore_s_total"] += time.perf_counter() - t0

    def _load_snapshot(self, snap_dir):
        if elastic.is_snapshot_dir(snap_dir):
            try:
                return elastic.load_serving_snapshot(snap_dir)
            except elastic.SnapshotCorrupt as e:
                logger.warning(f"replica snapshot {snap_dir} invalid: "
                               f"{e}")
                return None
        loaded = elastic.load_latest_serving(snap_dir)
        if loaded is None:
            return None
        host, kv, _cand = loaded
        return host, kv

    # ------------------------------------------------------------- step

    def step(self, now: Optional[float] = None) -> List[Request]:
        """One pool round: due re-serves dispatch, every replica steps
        once (crashes and drain-completions recover inline), autoscale
        runs last. Returns requests finished this round."""
        # a supervisor maintains its floor: kills respawn up to
        # min_replicas — unless the pool itself is being preempted
        while not self.shutdown \
                and len(self.replicas) < self.min_replicas \
                and (self.pending or len(self.replicas) == 0):
            self._spawn(reason="min_replicas")
        self._drain_resume_q()
        finished = []
        for replica_id, cb in list(self.replicas.items()):
            if replica_id not in self.replicas:
                continue            # recovered away mid-round
            try:
                out = cb.step(now)
            except SimulatedCrash as e:
                self.stats["kills"] += 1
                self.recorder.record("replica_kill", replica=replica_id,
                                     reason=repr(e))
                if self.watchdog is not None:
                    self.watchdog.note_preempt(
                        source=f"replica_{replica_id}_crash")
                    self.watchdog.note_preempt_ok()
                self._recover(replica_id, final_snapshot=False)
                continue
            for req in out:
                self.done[req.rid] = req
                self._assign.pop(req.rid, None)
            finished.extend(out)
            if cb.preempted:
                # drain-or-snapshot finished (scale-down or SIGTERM):
                # recover its committed snapshot onto survivors
                self.stats["preempts"] += 1
                was_scaling = replica_id in self._draining
                if not was_scaling:
                    self.shutdown = True   # a real preemption, not our
                    #                        own scale-down: stop
                    #                        respawning
                self._recover(replica_id, final_snapshot=True)
                if cb.elastic is not None \
                        and cb.elastic.last_snapshot_dir \
                        and not self._live():
                    # whole-pool preemption: nothing to requeue onto —
                    # the snapshot on disk IS the hand-off
                    self.parked_snapshots.append(
                        cb.elastic.last_snapshot_dir)
                if was_scaling:
                    self.stats["scale_downs"] += 1
                    self.recorder.record(
                        "replica_scale", direction="down",
                        replica=replica_id, reason="idle",
                        replicas=len(self.replicas))
        self._autoscale()
        return finished

    def _autoscale(self):
        if self.scale_signal == "slo":
            self._autoscale_slo()
            return
        if self.scale_signal != "watchdog":
            return
        trips = 0
        for rid, cb in list(self.replicas.items()):
            t = self._trips_of(cb.watchdog)
            base = self._trip_base.get(rid, 0)
            if t > base:
                trips += t - base
            self._trip_base[rid] = t
        if trips and len(self.replicas) < self.max_replicas:
            self._idle_rounds = 0
            new = self._spawn(reason=f"watchdog_trips:{trips}")
            logger.info(f"replica pool scaled UP to "
                        f"{len(self.replicas)} (replica {new}; "
                        f"{trips} new watchdog trips)")
            return
        # scale-down hysteresis: the pool must look overprovisioned
        # (all pending work fits comfortably in n-1 replicas' slots)
        # for scale_down_idle_rounds consecutive rounds
        live = self._live()
        if len(live) <= self.min_replicas or self._draining:
            self._idle_rounds = 0
            return
        slots_per = [len(cb.slots) for _, cb in live]
        capacity_wo_one = sum(slots_per) - max(slots_per)
        if self.pending <= capacity_wo_one // 2:
            self._idle_rounds += 1
        else:
            self._idle_rounds = 0
        if self._idle_rounds >= self.scale_down_idle_rounds:
            self._idle_rounds = 0
            victim = self._least_loaded()
            if victim is not None:
                self.preempt_replica(victim, source="scale_down")

    def slo_recommendation(self):
        """The per-role ``{"prefill"|"decode": "up"|"down"|"hold"}``
        the windowed SLO plane (telemetry/slo.py) last exported —
        derived PURELY from ``slo/*`` gauges, never from the plane
        object (the consumer contract ISSUE 19 pins). Empty when no
        registry is reachable yet."""
        from deepspeed_tpu.telemetry.slo import roles_signal
        reg = self.slo_registry
        if reg is None:
            live = self._live()
            reg = live[0][1].metrics if live else None
        return roles_signal(reg) if reg is not None else {}

    def _autoscale_slo(self):
        """Burn-rate autoscaling (ISSUE 19): a role whose windowed
        error-budget burn crossed ``up_burn`` spawns immediately (the
        window IS the hysteresis — 30s of sustained violations, not
        one bad request); scale-down needs a "down" verdict, no "up"
        anywhere, and the same consecutive-round patience as the
        watchdog path (two hysteresis layers on the shrink side,
        because a wrong shrink costs a drain + restore)."""
        roles = self.slo_recommendation()
        if not roles:
            return
        hot = sorted(r for r, a in roles.items() if a == "up")
        if hot and len(self.replicas) < self.max_replicas:
            self._idle_rounds = 0
            new = self._spawn(reason="slo_burn:" + ",".join(hot))
            logger.info(f"replica pool scaled UP to "
                        f"{len(self.replicas)} (replica {new}; "
                        f"slo burn on {','.join(hot)})")
            return
        live = self._live()
        if len(live) <= self.min_replicas or self._draining or hot:
            self._idle_rounds = 0
            return
        if any(a == "down" for a in roles.values()):
            self._idle_rounds += 1
        else:
            self._idle_rounds = 0
        if self._idle_rounds >= self.scale_down_idle_rounds:
            self._idle_rounds = 0
            victim = self._least_loaded()
            if victim is not None:
                self.preempt_replica(victim, source="scale_down")

    # -------------------------------------------------------------- run

    def run(self, requests, respect_arrival_times=False,
            timeout_s=None) -> Dict[Any, Request]:
        """Serve every request to completion (or loss) across the pool
        — the multi-replica ``serve()``. Poisson arrival semantics
        match the single engine's: with ``respect_arrival_times`` a
        request becomes dispatchable at its ``arrival_time`` against a
        wall clock started on entry."""
        todo = deque(sorted(requests, key=lambda r: r.arrival_time))
        t0 = time.monotonic()
        if not respect_arrival_times:
            while todo:
                self.submit(todo.popleft())
        while True:
            now = time.monotonic() - t0
            while todo and (todo[0].arrival_time <= now):
                self.submit(todo.popleft())
            if not todo and not self.pending:
                break
            if timeout_s is not None and now > timeout_s:
                logger.warning(f"replica pool run timed out with "
                               f"{self.pending} pending")
                break
            if self.shutdown and not self.replicas:
                break   # whole pool preempted: the parked snapshots
                #         are the hand-off. (A mere crash of the last
                #         replica is NOT this — step() respawns to
                #         min_replicas and the pending work continues.)
            stepped = self.step(now if respect_arrival_times else None)
            if not stepped and not any(
                    any(s.active for s in cb.slots) or cb.queue
                    for cb in self.replicas.values()):
                time.sleep(0.002)   # waiting on arrivals / backoff
        return dict(self.done)

    def close(self):
        # release (not close) every controller: restoring chained
        # signal handlers out of install order corrupts the chain; the
        # leftover handlers are inert weakref pass-throughs
        for rid in list(self.replicas):
            cb = self.replicas.pop(rid)
            if cb.elastic is not None:
                cb.elastic.release()

    def snapshot_stats(self) -> Dict[str, Any]:
        return {
            "replicas": len(self.replicas),
            "draining": len(self._draining),
            "pending": self.pending,
            "done": len(self.done),
            "lost": len(self.lost),
            **self.stats,
        }

    def metrics_snapshot(self) -> Dict[str, Any]:
        """Pool-level aggregation of every replica's
        ``metrics_snapshot()`` (ISSUE 12): pool TTFT percentiles over
        the MERGED raw reservoirs (averaging per-replica percentiles
        would be wrong under skewed load), per-replica slot
        utilization / queue depth, and the pool's lost / retried /
        recovered counters — the document
        a disaggregated router would schedule on."""
        per_replica = {}
        active = slots = queued = 0
        # peek, don't histogram(): get-or-create would seed an idle
        # replica's registry with phantom empty metrics
        ttft = merged_reservoir(self.replicas.values(), "serving/ttft_s")
        waits = merged_reservoir(self.replicas.values(),
                                 "serving/admission_wait_s")
        for rid, cb in self.replicas.items():
            a = sum(s.active for s in cb.slots)
            active += a
            slots += len(cb.slots)
            queued += len(cb.queue)
            per_replica[rid] = {
                "active_slots": a,
                "slots": len(cb.slots),
                "slot_utilization": a / max(len(cb.slots), 1),
                "queue_depth": len(cb.queue),
                "draining": rid in self._draining,
                "decode_tokens": cb.stats["decode_tokens"],
                "dump_id": cb.watchdog.dump_id
                if cb.watchdog is not None else 0,
            }

        return {
            "replicas": len(self.replicas),
            "per_replica": per_replica,
            "pool_ttft_s": percentile_summary(ttft),
            "pool_admission_wait_s": percentile_summary(waits),
            "active_slots": active,
            "total_slots": slots,
            "slot_utilization": active / max(slots, 1),
            "queue_depth": queued,
            "pending": self.pending,
            "done": len(self.done),
            "lost": len(self.lost),
            "retried": sum(1 for a in self._attempts.values() if a > 0),
            **self.stats,
        }
