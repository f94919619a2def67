"""Disaggregated prefill/decode serving with an SLO-aware router
(ISSUE 14 tentpole).

Mixed traffic head-of-line blocks a colocated engine: its slots are
decode residency, so an arriving prompt waits for some long request to
FINISH before it can even prefill, and symmetrically a long prefill
dispatch sits between two
decode ticks of every in-flight request. The split:

- **prefill-role engines** (``ContinuousBatcher(role="prefill")``)
  admit prompts and run the page-bucketed prefill, nothing else. Their
  slots free the moment the produced pages are handed off, so prompt
  admission is never blocked on decode residency — TTFT collapses to
  router-queue + prefill time.
- a **page-handoff transport** moves the request: the wire format is
  ``elastic._req_doc`` (+ slot position) next to a device-side gather
  of the request's DATA pages (``PagedKVCache.gather_block_kv``). The
  in-process fast path keeps the gather on device and lands it with
  one scatter per pool component (``scatter_block_kv``) into blocks
  the decode engine's REFCOUNTED allocator handed out
  (``admit``/``admit_prefix``) — a cross-process transport only has to
  serialize the same (doc, component arrays) pair, so it is a drop-in
  (PAPERS.md 2408.13356: page movement is a transport concern, not an
  engine concern).
- **decode-role engines** adopt the pages (incref through the shared
  refcounted allocator path; a prefix-index dedupe hit re-shares
  resident pages instead of copying them) and continue token-for-token
  identically to a colocated run — they never execute a prefill
  program, so decode tick latency stops depending on prompt-arrival
  luck.

The :class:`DisaggRouter` schedules on three signals:

- **prefix locality**: a prompt routes to the prefill replica whose
  index already holds its prefix chain (``match_prefix`` probe — the
  hit skips the shared span's prefill compute there);
- **page-pool pressure**: the undelivered handoff KV is bounded
  (``max_inflight_pages``, default 2x the decode pools' allocatable
  total) — when exhausted decode pools leave a packet backlog at the
  bound, new prompts queue AT THE ROUTER, so an in-flight request can
  never hit ``pool_exhausted`` (delivery only takes pages when a slot
  freed them);
- **SLO**: otherwise prompts go to the prefill replica with the best
  live score (queue depth + recent-TTFT tail from the engines'
  ``metrics_snapshot()`` reservoirs), and packets land on the decode
  replica with the most free pages.

Colocated fallback: built with ``decode_replicas == 0`` (or
``serving.disaggregation.enabled: false`` through
:func:`deepspeed_tpu.serving.build_router`) every engine runs
``role="both"`` and the router degrades to an SLO dispatcher over N
colocated replicas — no handoff, pre-ISSUE-14 semantics per engine.

Recovery: a crash between extract and deliver (the ``serving_handoff``
fault point — the gathered bytes died with the transport) replays the
request from its wire doc: the committed stream becomes the admission
prompt, so greedy (and, with PR-14's persisted ``sample_key``, sampled)
decoding regenerates the identical continuation. Bounded by
``max_handoff_retries``. A crash INSIDE delivery (``serving_deliver``,
ISSUE 15 satellite — the decode pool already admitted the packet's
pages) additionally unwinds the admission in ``deliver_handoff``
before the same replay, so the pool never leaks the pages of a
half-delivered request.
"""

import time
from collections import deque
from typing import Any, Dict, List, Optional

import numpy as np

from deepspeed_tpu.runtime.elastic import faults
from deepspeed_tpu.serving import elastic
from deepspeed_tpu.serving.engine import Request, ensure_trace_id
from deepspeed_tpu.telemetry.recorder import default_recorder
from deepspeed_tpu.telemetry.registry import MetricsRegistry
from deepspeed_tpu.utils.logging import logger


def router_metric_names():
    """Every ``router/*`` metric the router can emit — pinned EXACTLY
    (both directions) against docs/observability.md by
    tests/test_metric_names.py, like the cluster namespace."""
    return (
        "router/queue_depth",        # prompts waiting at the router
        "router/inflight_packets",   # extracted, not yet delivered
        "router/inflight_pages",     # KV pages those packets hold
        "router/handoffs",           # delivered prefill→decode moves
        "router/handoff_requeues",   # transport-crash replays
        "router/decode_blocked",     # admissions deferred on pressure
        "router/prefix_routed",      # admissions routed by locality
        "router/slo_routed",         # admissions routed by SLO score
        "router/handoff_bytes_sent",  # wire bytes extracted/sent
        "router/handoff_bytes_recv",  # wire bytes delivered
        "router/handoff_wasted_bytes",  # wire bytes received unaddressed
    )


# ------------------------------------------------------------ transport

class HandoffPacket:
    """One request in flight between roles: the JSON-able wire doc
    (``elastic._req_doc`` + slot position + page counts) and the
    device-side gather of its data pages. ``req`` is the live Request
    object — the in-process fast path hands the same object across so
    submit-time identity (trace, timing bases) survives; a
    cross-process transport would rebuild it from ``doc``."""

    __slots__ = ("doc", "kv", "req")

    def __init__(self, doc, kv, req):
        self.doc = doc
        self.kv = kv
        self.req = req

    @property
    def rid(self):
        return self.doc["rid"]


def extract_handoff(pcb, slot_id: int) -> HandoffPacket:
    """Detach ``slot_id`` from a prefill-role engine as a packet: the
    wire doc captures the committed stream + position, the kv tuple is
    a device gather of the pages that hold real rows (``pos`` of them
    — the tail pages admission allocated for decode headroom carry no
    data and never travel). The slot's pages decref immediately; the
    gathered arrays are independent buffers."""
    cache = pcb.cache
    slot = pcb.slots[slot_id]
    req = slot.request
    pos = slot.pos
    n_data = cache.pages_needed(pos)
    pages = cache.slot_pages(slot_id)
    kv = cache.gather_block_kv(pages[:n_data])
    # t_sent: wall clock (time.time, comparable ACROSS processes —
    # monotonic bases aren't) stamped at extraction; the delivery side
    # observes serving/transport_s against it
    doc = dict(elastic._req_doc(req), pos=int(pos),
               last_tok=int(slot.last_tok), n_data_pages=int(n_data),
               t_sent=time.time())
    req_out, _pos, _last = pcb.export_slot(slot_id)
    # ISSUE 19: export_slot just minted the handoff span — ship it in
    # the wire doc so the RECEIVING rank's handoff_in / transport spans
    # parent onto it across the process boundary (the codec ignores
    # keys it doesn't know, so older peers are unaffected)
    doc["handoff_span"] = getattr(req_out, "_handoff_span", None)
    return HandoffPacket(doc, kv, req_out)


def deliver_handoff(dcb, packet: HandoffPacket,
                    dedupe: bool = True) -> Optional[int]:
    """Land a packet on a decode-role engine: allocate the request's
    full page set through the refcounted allocator (``admit_prefix``
    when the engine's prefix index is on — full prompt pages the index
    already holds are RE-SHARED with an incref instead of copied, the
    cross-request sharing a colocated prefix cache would have kept),
    scatter the transported bytes into the fresh blocks, register the
    prompt pages for future dedupe, and adopt the slot. Returns the
    slot id, or None (nothing allocated) when no free slot or the pool
    cannot cover the fresh pages — the router keeps the packet queued.
    """
    free = [i for i, s in enumerate(dcb.slots) if not s.active]
    if not free:
        return None
    slot_id = free[0]
    doc = packet.doc
    prompt_np = np.asarray(doc["prompt"], np.int32)  # sync-ok: wire doc
    total = len(prompt_np) + int(doc["max_new_tokens"]) \
        + len(doc["generated"]) - 1
    # capacity mirrors what a colocated admission of the ORIGINAL
    # request reserved: prompt + max_new rows (generated rows beyond
    # the first token are already appended — pos covers them)
    total = max(total, int(doc["pos"]) + 1)
    n_data = int(doc["n_data_pages"])
    shared = 0
    cache = dcb.cache
    plan = None
    if dedupe and dcb.prefix_cache:
        plan = cache.admit_prefix(slot_id, prompt_np, total, cow=False)
        if plan is None:
            return None
        pages = plan.pages
        shared = plan.start_pos // cache.spec.page_size
    else:
        pages = cache.admit(slot_id, total)
        if pages is None:
            return None
    # From here pages are ADMITTED (allocated/increffed into slot_id's
    # table): any failure before adoption completes must UNWIND the
    # admission — decref the pages and clear the slot — or the pool
    # leaks them until restart (the PR-14 review bug, ISSUE 15
    # satellite). The ``serving_deliver`` fault point models the
    # delivery side dying right inside that window. Prefix
    # registration happens only AFTER the scatter wrote the blocks, so
    # an unwound delivery can never leave index entries pointing at
    # never-written pages.
    t_land = time.monotonic()
    try:
        faults.fire("serving_deliver", rid=packet.rid, slot=slot_id)
        # one scatter per pool component writes the non-shared data
        # pages
        cache.scatter_block_kv(pages[shared:n_data], packet.kv,
                               src_offset=shared)
        if plan is not None:
            cache.register_prefix(slot_id, prompt_np, hashes=plan.hashes)
        req = packet.req if packet.req is not None \
            else elastic.resume_request(doc)
        # span parents off the wire (ISSUE 19): a rebuilt request lost
        # its in-process attributes — restore the handoff/encode span
        # ids the doc carried so adopt_request parents correctly
        if getattr(req, "_handoff_span", None) is None \
                and doc.get("handoff_span"):
            req._handoff_span = doc["handoff_span"]
        if getattr(req, "_encode_span", None) is None \
                and doc.get("encode_span"):
            req._encode_span = doc["encode_span"]
        dcb.adopt_request(slot_id, req, int(doc["pos"]),
                          int(doc["last_tok"]))
        # the landing segment of the transport: scatter + adopt on the
        # receiver, monotonic (single-process span)
        dcb.metrics.histogram("serving/transport_decode_s").observe(
            time.monotonic() - t_land)
        if doc.get("t_sent") is not None:
            # the wire/move segment of the handoff: extraction stamp to
            # adoption, wall clock so it survives the process boundary
            dcb.metrics.histogram("serving/transport_s").observe(
                max(time.time() - float(doc["t_sent"]), 0.0))  # sync-ok: wall clock
    except BaseException:
        cache.release(slot_id)
        slot = dcb.slots[slot_id]
        slot.request, slot.pos, slot.last_tok = None, -1, 0
        raise
    return slot_id


# --------------------------------------------------------------- router

class DisaggRouter:
    """See module docstring. Build directly from engine lists, or from
    a config through :func:`deepspeed_tpu.serving.build_router`."""

    def __init__(self, prefill_engines, decode_engines,
                 registry: Optional[MetricsRegistry] = None,
                 recorder=None, prefix_routing: bool = True,
                 dedupe_pages: bool = True, queue_weight: float = 1.0,
                 ttft_weight: float = 1.0, ttft_window: int = 16,
                 max_handoff_retries: int = 3, decode_tick_cap: int = 4,
                 max_inflight_pages: Optional[int] = None,
                 decode_schedule: str = "lpt"):
        assert prefill_engines, "need at least one prefill-role engine"
        self.prefill_engines = list(prefill_engines)
        self.decode_engines = list(decode_engines)
        self.colocated = not self.decode_engines
        for i, cb in enumerate(self.prefill_engines):
            if cb.replica_id is None:
                cb.replica_id = f"prefill{i}" if not self.colocated \
                    else f"colo{i}"
        for i, cb in enumerate(self.decode_engines):
            if cb.replica_id is None:
                cb.replica_id = f"decode{i}"
        if not self.colocated:
            for cb in self.prefill_engines:
                assert cb.role == "prefill", \
                    "disaggregated mode needs prefill-role engines"
            for cb in self.decode_engines:
                assert cb.role in ("decode", "both")
        self.metrics = registry if registry is not None \
            else MetricsRegistry()
        self.recorder = recorder if recorder is not None \
            else default_recorder()
        self.prefix_routing = bool(prefix_routing)
        self.dedupe_pages = bool(dedupe_pages)
        self.queue_weight = float(queue_weight)   # sync-ok: config
        self.ttft_weight = float(ttft_weight)     # sync-ok: config
        self.ttft_window = int(ttft_window)
        self.max_handoff_retries = int(max_handoff_retries)
        self.decode_tick_cap = int(decode_tick_cap)
        assert decode_schedule in ("lpt", "fifo"), decode_schedule
        self.decode_schedule = decode_schedule
        # decode-side backpressure: the KV pages held by extracted-but-
        # undelivered packets are device memory OUTSIDE every pool, so
        # they must be bounded — default 2x the decode pools' total
        # allocatable pages (an exhausted decode pool under a sustained
        # backlog queues prompts AT THE ROUTER, never mid-flight).
        # Reserving per-request pages instead would double-count: a
        # waiting packet claims no pool pages until a slot (and with it
        # its previous occupant's pages) frees.
        alloc = sum(cb.cache.num_blocks - 1 for cb in self.decode_engines)
        self.max_inflight_pages = int(max_inflight_pages) \
            if max_inflight_pages is not None else 2 * alloc
        self.queue: deque = deque()
        self._packets: deque = deque()
        # handoff-crash replay state lives in the PACKET's wire doc
        # (unlike ReplicaPool there is no whole-replica loss to
        # re-serve from a submit-time ledger)
        self._attempts: Dict[Any, int] = {}
        self._block_latched = False   # one decode_blocked per episode
        self.done: Dict[Any, Request] = {}
        self.lost: Dict[Any, dict] = {}
        self._host_rng = np.random.RandomState(0)
        self.stats = {"routed": 0, "prefix_routed": 0, "slo_routed": 0,
                      "handoffs": 0, "handoff_requeues": 0,
                      "decode_blocked": 0, "lost": 0}

    # ------------------------------------------------------------ intake

    def submit(self, request: Request) -> None:
        ensure_trace_id(request)
        if request.temperature and request.temperature > 0 \
                and request.sample_key is None:
            # stamped BEFORE the ledger doc freezes, so a handoff-crash
            # replay of a sampled request keeps its key (the engine's
            # own stamp would come too late for the router ledger)
            request.sample_key = int(
                self._host_rng.randint(0, 2 ** 31 - 1))  # sync-ok: host
        if not self.colocated:
            # feasibility: a request no decode pool could EVER hold
            # would orbit as an undeliverable packet forever
            S = int(np.asarray(request.prompt).shape[0])  # sync-ok: host
            need = self.decode_engines[0].cache.pages_needed(
                S + request.max_new_tokens)
            assert any(need <= cb.cache.num_blocks - 1
                       for cb in self.decode_engines), (
                f"request {request.rid!r} needs {need} pages but no "
                f"decode pool can hold that many")
        if getattr(request, "_t_arrived", None) is None:
            # TTFT/queue-wait reference = ROUTER entry (run() pre-stamps
            # wall-clock arrivals; the engine's own submit stamp would
            # start the clock only after routing)
            request._t_arrived = time.monotonic()
        self._attempts.setdefault(request.rid, 0)
        self.queue.append(request)
        self.metrics.gauge("router/queue_depth").set(len(self.queue))

    @property
    def pending(self) -> int:
        n = len(self.queue) + len(self._packets)
        for cb in self.prefill_engines + self.decode_engines:
            n += cb.pending
        return n

    # -------------------------------------------------------- scheduling

    def _ttft_tail(self, cb) -> float:
        vals = cb.metrics.peek_histogram_values("serving/ttft_s")
        if not vals:
            return 0.0
        tail = vals[-self.ttft_window:]
        return float(sum(tail) / len(tail))   # sync-ok: host reservoir

    def _route_prefill(self, prompt_np):
        """(engine index, reason): longest resident prefix chain wins
        (locality — the hit skips that span's prefill compute); ties
        and cold prompts go to the best live SLO score."""
        if self.prefix_routing and len(self.prefill_engines) >= 1:
            best, best_hit = None, 0
            for i, cb in enumerate(self.prefill_engines):
                if not cb.prefix_cache:
                    continue
                hit = cb.cache.match_prefix(prompt_np,
                                            cow=False).start_pos
                if hit > best_hit:
                    best, best_hit = i, hit
            if best is not None:
                return best, "prefix"
        scores = []
        for i, cb in enumerate(self.prefill_engines):
            load = len(cb.queue) + sum(s.active for s in cb.slots)
            scores.append(self.queue_weight * load
                          + self.ttft_weight * self._ttft_tail(cb))
        return int(np.argmin(scores)), "slo"   # sync-ok: host scores

    def _inflight_pages(self) -> int:
        """KV pages committed to the handoff pipeline but not yet
        absorbed by a decode pool: extracted packets' data pages PLUS
        the prompt pages of everything already routed into a prefill
        engine (queued or prefilling) — those become packets next
        sweep, so the backpressure gate must see them coming."""
        n = sum(p.doc["n_data_pages"] for p in self._packets)
        for pcb in self.prefill_engines:
            for r in pcb.queue:
                n += pcb.cache.pages_needed(
                    int(np.asarray(r.prompt).shape[0]))  # sync-ok: host
            for s in pcb.slots:
                if s.active:
                    n += pcb.cache.pages_needed(max(s.pos, 1))
        return n

    def _route_admissions(self, now):
        while self.queue:
            req = self.queue[0]
            if now is not None and req.arrival_time > now:
                break                  # FIFO against the arrival clock
            prompt_np = np.asarray(req.prompt, np.int32)  # sync-ok: host
            if not self.colocated:
                need = self.decode_engines[0].cache.pages_needed(
                    len(prompt_np))
                inflight = self._inflight_pages()
                if inflight + need > self.max_inflight_pages:
                    # decode-side backpressure: the undelivered handoff
                    # KV is at its bound — the decode pools cannot
                    # absorb more, so the prompt queues AT THE ROUTER
                    # (an admitted request can therefore never hit
                    # pool_exhausted mid-flight; waiting packets claim
                    # no pool pages, so reserving per-request pages
                    # here would double-count against the slots that
                    # will free them). LATCHED per episode — a blocked
                    # head request re-checks every round, and counting/
                    # recording each re-check would flood the bounded
                    # ring at tick rate under sustained pressure.
                    if not self._block_latched:
                        self._block_latched = True
                        self.stats["decode_blocked"] += 1
                        self.metrics.counter(
                            "router/decode_blocked").inc()
                        self.recorder.record(
                            "router_block", rid=req.rid,
                            trace=req.trace_id, need_pages=need,
                            inflight_pages=inflight,
                            queue_depth=len(self.queue))
                    break
            self._block_latched = False   # an admission re-arms
            pidx, reason = self._route_prefill(prompt_np)
            self.queue.popleft()
            self.stats["routed"] += 1
            self.stats[f"{reason}_routed"] += 1
            self.metrics.counter(f"router/{reason}_routed").inc()
            self.recorder.record(
                "router_route", rid=req.rid, trace=req.trace_id,
                engine=self.prefill_engines[pidx].replica_id,
                reason=reason)
            self.prefill_engines[pidx].submit(req)
        self.metrics.gauge("router/queue_depth").set(len(self.queue))

    # ----------------------------------------------------------- handoff

    def _requeue_lost_packet(self, packet, error) -> None:
        """The transport died between extract and deliver: the gathered
        bytes are gone, but the wire doc survives — replay the request
        through prefill (committed stream as prompt), bounded."""
        rid = packet.rid
        self.stats["handoff_requeues"] += 1
        self.metrics.counter("router/handoff_requeues").inc()
        self._attempts[rid] = self._attempts.get(rid, 0) + 1
        if self._attempts[rid] > self.max_handoff_retries:
            self.stats["lost"] += 1
            self.lost[rid] = packet.doc
            self.recorder.record(
                "serving_requeue", rid=rid,
                trace=packet.doc.get("trace_id"), outcome="dropped",
                attempts=self._attempts[rid])
            logger.warning(f"request {rid!r} dropped after "
                           f"{self._attempts[rid] - 1} handoff retries")
            return
        replay = elastic.resume_request(packet.doc)
        self.recorder.record(
            "serving_requeue", rid=rid,
            trace=packet.doc.get("trace_id"), outcome="scheduled",
            attempts=self._attempts[rid],
            committed=len(packet.doc["generated"]))
        logger.warning(f"handoff of {rid!r} failed ({error}); "
                       f"replaying from the committed stream")
        self.queue.appendleft(replay)

    def _sweep_handoffs(self) -> None:
        """Every active slot on a prefill-role engine is handoff-ready
        (its prefill ran at admission). Extract each into a packet;
        the ``serving_handoff`` fault point models the transport dying
        with the bytes in flight."""
        for pcb in self.prefill_engines:
            for slot_id, slot in enumerate(pcb.slots):
                if not slot.active:
                    continue
                packet = extract_handoff(pcb, slot_id)
                try:
                    faults.fire("serving_handoff", rid=packet.rid)
                except faults.SimulatedCrash as e:
                    self._requeue_lost_packet(packet, e)
                    continue
                # in-process, "bytes on the wire" = the payload the
                # gather materialized (data pages x per-block bytes);
                # the cross-process transport counts encoded frame
                # lengths instead and recv == sent holds either way
                self.metrics.counter("router/handoff_bytes_sent").inc(
                    packet.doc["n_data_pages"] * pcb.cache.page_nbytes)
                self._packets.append(packet)
        self._note_inflight()

    def _note_inflight(self):
        self.metrics.gauge("router/inflight_packets").set(
            len(self._packets))
        self.metrics.gauge("router/inflight_pages").set(
            self._inflight_pages())

    def _deliver_packets(self) -> None:
        if self.decode_schedule == "lpt" and len(self._packets) > 1:
            # longest-remaining-first: the router's scheduling freedom
            # — first tokens are already delivered, so reordering the
            # DECODE start order trades nothing on TTFT and the LPT
            # rule packs the slot makespan tighter (long decodes start
            # early instead of draining solo at the tail). Under a
            # sustained overload this favors long requests' completion;
            # decode_schedule="fifo" restores arrival order.
            self._packets = deque(sorted(
                self._packets, key=lambda p:
                -(p.doc["max_new_tokens"] - len(p.doc["generated"]))))
        still = deque()
        while self._packets:
            packet = self._packets.popleft()
            order = sorted(
                range(len(self.decode_engines)), key=lambda i:
                -self.decode_engines[i].cache.available_pages)
            slot = None
            crashed = None
            for di in order:
                # the serving_deliver fault point (ISSUE 15 satellite)
                # fires INSIDE delivery, after the decode pool admitted
                # the packet's pages — deliver_handoff unwinds the
                # admission before re-raising, so the pool cannot leak;
                # the router replays the request from its wire doc like
                # a transport crash (the gathered bytes are suspect)
                try:
                    slot = deliver_handoff(self.decode_engines[di],
                                           packet,
                                           dedupe=self.dedupe_pages)
                except faults.SimulatedCrash as e:
                    crashed = e
                    break
                if slot is not None:
                    self.stats["handoffs"] += 1
                    self.metrics.counter("router/handoffs").inc()
                    self.metrics.counter(
                        "router/handoff_bytes_recv").inc(
                        packet.doc["n_data_pages"]
                        * self.decode_engines[di].cache.page_nbytes)
                    break
            if crashed is not None:
                self._requeue_lost_packet(packet, crashed)
            elif slot is None:
                still.append(packet)   # waiting on a decode slot/pages
        self._packets = still
        self._note_inflight()

    # -------------------------------------------------------------- step

    def step(self, now: Optional[float] = None) -> List[Request]:
        """One router round: route due prompts, step the prefill
        engines (admission + prefill), sweep/deliver handoffs, then
        step the decode engines (ticks). Returns requests finished
        this round across every engine."""
        self._route_admissions(now)
        finished: List[Request] = []
        for pcb in self.prefill_engines:
            finished.extend(pcb.step())
        if not self.colocated:
            self._sweep_handoffs()
            self._deliver_packets()
        # short decode ticks only while PROMPT work is pending (router
        # queue / prefill engines) so prefills interleave; packets
        # waiting on a decode SLOT don't need short ticks — slots free
        # at finishes, which long ticks reach with less dispatch
        # overhead
        busy = (bool(self.queue) or any(
            cb.queue or any(s.active for s in cb.slots)
            for cb in self.prefill_engines)) if not self.colocated \
            else False
        for dcb in self.decode_engines:
            dcb.tick_step_cap = self.decode_tick_cap if busy else None
            if any(s.active for s in dcb.slots) or dcb.queue:
                finished.extend(dcb.step())
        if self._packets:
            # second chance: slots this round's ticks just freed take
            # waiting packets NOW instead of idling until next round
            self._deliver_packets()
        for req in finished:
            self.done[req.rid] = req
        return finished

    def run(self, requests, respect_arrival_times: bool = False,
            timeout_s: Optional[float] = None) -> Dict[Any, Request]:
        """Serve every request to completion (or loss) — the
        disaggregated ``serve()``. Arrival semantics match the single
        engine's: with ``respect_arrival_times`` a request becomes
        routable at its ``arrival_time`` against a wall clock started
        on entry (and TTFT is measured from that arrival)."""
        todo = deque(sorted(requests, key=lambda r: r.arrival_time))
        t0 = time.monotonic()
        if respect_arrival_times:
            for r in todo:
                r._t_arrived = t0 + r.arrival_time
        else:
            while todo:
                self.submit(todo.popleft())
        while True:
            now = time.monotonic() - t0
            while todo and todo[0].arrival_time <= now:
                self.submit(todo.popleft())
            if not todo and not self.pending:
                break
            if timeout_s is not None and now > timeout_s:
                logger.warning(f"router run timed out with "
                               f"{self.pending} pending")
                break
            stepped = self.step(now if respect_arrival_times else None)
            if not stepped and not any(
                    any(s.active for s in cb.slots) or cb.queue
                    for cb in self.prefill_engines
                    + self.decode_engines):
                time.sleep(0.002)      # waiting on arrivals
        return dict(self.done)

    # --------------------------------------------------------- telemetry

    def metrics_snapshot(self) -> Dict[str, Any]:
        """Router + per-role aggregation:
        merged TTFT/breakdown percentiles over the
        prefill engines' raw reservoirs, per-engine role rows, the
        reservation/queue state and the handoff counters."""
        from deepspeed_tpu.serving.replica_pool import (
            merged_reservoir as merged, percentile_summary as pct)
        pe = self.prefill_engines
        de = self.decode_engines
        per_engine = {}
        for cb in pe + de:
            per_engine[cb.replica_id] = {
                "role": cb.role,
                "active_slots": sum(s.active for s in cb.slots),
                "queue_depth": len(cb.queue),
                "page_pool_available": cb.cache.available_pages,
                "handoffs_out": cb.stats["handoffs_out"],
                "handoffs_in": cb.stats["handoffs_in"],
                "decode_tokens": cb.stats["decode_tokens"],
            }
        return {
            "mode": "colocated" if self.colocated else "disaggregated",
            "prefill_engines": len(pe),
            "decode_engines": len(de),
            "queue_depth": len(self.queue),
            "inflight_packets": len(self._packets),
            "inflight_pages": self._inflight_pages(),
            "ttft_s": pct(merged(pe, "serving/ttft_s")),
            "ttft_breakdown": {
                "queue_wait_s": pct(
                    merged(pe, "serving/ttft_queue_wait_s")),
                "prefill_s": pct(merged(pe, "serving/ttft_prefill_s")),
                "handoff_s": pct(merged(de, "serving/handoff_s")),
                "transport_s": pct(merged(de, "serving/transport_s")),
                # ISSUE 18: the transport term split into attributable
                # segments (encode at the sender, the aligned exchange,
                # scatter/adopt at the receiver); in-process delivery
                # observes only the landing segment
                "transport_encode_s": pct(
                    merged(pe, "serving/transport_encode_s")),
                "transport_collective_s": pct(
                    merged(pe + de, "serving/transport_collective_s")),
                "transport_decode_s": pct(
                    merged(de, "serving/transport_decode_s")),
                "first_decode_tick_s": pct(
                    merged(pe + de, "serving/first_decode_tick_s")),
            },
            "per_engine": per_engine,
            "done": len(self.done),
            # "lost" rides self.stats (kept in lockstep with the
            # self.lost dict by _requeue_lost_packet — one source)
            **self.stats,
        }

    def close(self) -> None:
        for cb in self.prefill_engines + self.decode_engines:
            if cb.elastic is not None:
                cb.elastic.release()
