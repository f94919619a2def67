"""Continuous-batching scheduler over the paged KV cache.

The static serving path (`models/gpt2_inference.generate`) runs one
batch per call: every request shares the prompt pass, pads to the
longest sequence, and the whole batch drains before any new request
starts. Here the batch is a set of SLOTS that requests flow through
independently:

- a request is admitted into any free slot the moment enough pool pages
  are free for ``prompt + max_new_tokens``; its prompt prefills into its
  own pages while other slots keep decoding;
- every scheduler step runs ONE compiled decode tick over all slots
  (idle slots masked by pos < 0); a slot that hits EOS/max_new frees its
  pages immediately and the next queued request takes it on the same
  step — the chip never waits for the slowest request in a gang.

The device work per step is one fixed-shape donated-pool program (plus
one bucketed prefill per admission), so any arrival pattern replays a
small fixed set of executables — the restructuring that turns mixed
traffic from serialized batches into interleaved independent work (the
fused computation-collective argument applied to prefill/decode).
"""

import dataclasses
import time
import uuid
from collections import deque
from typing import Any, Dict, List, Optional, Sequence

import numpy as np
import jax.numpy as jnp

from deepspeed_tpu.runtime.elastic import faults
from deepspeed_tpu.serving.paged_cache import (PagedKVCache,
                                               padded_prefill_inputs,
                                               pow2_page_bucket)
from deepspeed_tpu.telemetry.recorder import default_recorder
from deepspeed_tpu.telemetry.registry import MetricsRegistry
from deepspeed_tpu.telemetry.spans import new_span_id


@dataclasses.dataclass
class Request:
    """One generation request. ``arrival_time`` is seconds relative to
    the serve() clock (0 = already queued); requests become admissible
    only once arrived."""
    rid: Any
    prompt: Any                       # [S] int array-like
    max_new_tokens: int = 16
    eos_token_id: Optional[int] = None
    temperature: float = 0.0
    arrival_time: float = 0.0
    # request-scoped distributed tracing (ISSUE 12): stamped once at
    # first submit, carried through every lifecycle ring event and
    # across snapshot -> restore -> requeue replica handoffs, so
    # telemetry/view.py can stitch one cross-replica timeline per
    # request from N dump files. Never re-stamped: a replayed or
    # restored request keeps the identity it was born with.
    trace_id: Optional[str] = None
    # ISSUE 19: the request's ROOT span id, minted next to trace_id at
    # first submit and persisted through the same snapshot / restore /
    # handoff docs. Every lifecycle span (prefill, handoff, transport
    # legs, first decode tick) parents onto it — directly or through an
    # intermediate span — so N per-role dump files merge into ONE
    # causal tree per trace_id (telemetry/perfetto.py).
    span_id: Optional[str] = None
    # ISSUE 14: per-request sampling identity (temperature > 0 only).
    # Stamped once at first submit and persisted through snapshot /
    # restore / handoff docs; every sampled token's key is
    # fold_in(sample_key, global_token_index), so replays regenerate
    # the identical sampled stream instead of drawing fresh rng.
    sample_key: Optional[int] = None
    # filled by the engine:
    generated: List[int] = dataclasses.field(default_factory=list)
    finish_reason: Optional[str] = None

    def tokens(self) -> np.ndarray:
        return np.concatenate([          # sync-ok: host-side lists
            np.asarray(self.prompt, np.int32),
            np.asarray(self.generated, np.int32)])  # sync-ok: host


def ensure_trace_id(request) -> str:
    """Stamp a stable ``trace_id`` at first submit (idempotent — a
    restored/replayed request arrives with the one it was born with).
    ISSUE 19: the root ``span_id`` is minted here too, under the same
    never-re-stamped contract — it is the anchor every downstream
    lifecycle span parents onto."""
    if getattr(request, "trace_id", None) is None:
        request.trace_id = uuid.uuid4().hex[:16]
    if getattr(request, "span_id", None) is None:
        from deepspeed_tpu.telemetry.spans import new_span_id
        request.span_id = new_span_id()
    return request.trace_id


@dataclasses.dataclass
class _Slot:
    request: Optional[Request] = None
    pos: int = -1                     # rows already in cache; -1 = idle
    last_tok: int = 0                 # token to feed on the next tick

    @property
    def active(self) -> bool:
        return self.request is not None


class ContinuousBatcher:
    """Host-side slot scheduler around one adapter's compiled programs.

    Usage::

        engine = serving.build_engine(family="gpt2", model_config=cfg,
                                      params=params, config=ds_config)
        results = engine.serve([Request(0, prompt, max_new_tokens=32)])

    or incrementally: ``submit()`` then ``step()`` until it returns
    everything (each call runs at most one admission sweep + one tick).
    """

    def __init__(self, adapter,
                 registry: Optional[MetricsRegistry] = None,
                 recorder=None, watchdog=None, prefix_cache: bool = False,
                 prefix_cow: bool = True, drafter=None,
                 spec_tokens: int = 3, role: str = "both"):
        self.adapter = adapter
        self.spec = adapter.spec
        self.cache: PagedKVCache = adapter.make_cache()
        # ISSUE 14 (disaggregation): a "prefill"-role engine admits and
        # prefills but NEVER runs a decode program — its active slots
        # are handoff candidates the router exports; a "decode"-role
        # engine only receives handoffs (its queue stays empty). "both"
        # is the colocated engine every pre-disagg config builds.
        assert role in ("both", "prefill", "decode"), role
        self.role = role
        assert not (role == "prefill" and drafter is not None), \
            "a prefill-role engine never decodes — no drafter"
        # ISSUE 9 (a): copy-on-write prefix page sharing — admission
        # consults the refcounted prefix index before allocating, and a
        # hit skips both the pages AND the prefill compute for the
        # shared span (prefill_suffix starts at start_pos)
        self.prefix_cache = bool(prefix_cache)
        self.prefix_cow = bool(prefix_cow)
        if self.prefix_cache:
            self.cache.enable_prefix_sharing()
        # ISSUE 9 (b): speculative decoding — a drafter proposes
        # spec_tokens tokens per round and the target model verifies the
        # whole window in ONE multi-query paged-attention dispatch;
        # greedy accept/reject keeps outputs token-for-token identical
        # to the plain engine (verify is greedy-only: any active sampled
        # request falls the whole step back to the normal tick)
        self.drafter = drafter
        self.spec_tokens = int(spec_tokens)
        self.slots = [_Slot() for _ in range(self.spec.slots)]
        self.queue: deque = deque()
        # sampling is STATELESS per request (fold_in(sample_key, index)
        # — ISSUE 14); the only engine-held rng is the host stream that
        # stamps fresh requests' sample keys at submit
        self._host_rng = np.random.RandomState(0)
        self.last_logits = None       # [slots, V] of the latest tick
        self.stats = {"ticks": 0, "tick_steps": 0, "decode_tokens": 0,
                      "prefills": 0, "prefill_tokens": 0,
                      "spec_rounds": 0, "spec_proposed": 0,
                      "spec_accepted": 0, "prefix_tokens_shared": 0,
                      "prefix_tokens_prompt": 0, "prefix_pages_saved": 0,
                      "handoffs_out": 0, "handoffs_in": 0}
        # per-engine metrics registry (serving/* names) — pass the
        # process-wide default_registry() to merge into one JSONL
        # stream with a training engine. All recording is host-side;
        # the only device readbacks in this scheduler are the token /
        # logits consumptions it already cannot avoid.
        self.metrics = registry if registry is not None \
            else MetricsRegistry()
        # flight recorder (ISSUE 6): request lifecycle events — admit ->
        # prefill -> ticks -> EOS — land in the process-wide ring by
        # default; the optional watchdog (telemetry/anomaly.py)
        # evaluates TTFT-blowup / pool-exhaustion rules at the admission
        # sweep, the one place those values already exist as host
        # scalars (never a new device sync)
        self.recorder = recorder if recorder is not None \
            else default_recorder()
        self.watchdog = watchdog
        self._t_first_decode = None   # engine-lifetime tokens/sec base
        # ISSUE 11: elastic preemption tolerance — an
        # ElasticServingController (serving/elastic.py) attached here
        # runs the drain-or-snapshot policy at every tick end; while it
        # drains, _admitting gates new admissions off so the snapshot
        # set stops growing
        self.elastic = None
        self._admitting = True
        # ISSUE 12: a ReplicaPool stamps its replica id here so ring
        # events self-identify (replicas share the process-wide ring);
        # _t_last_step_ts feeds the /healthz fence age
        self.replica_id = None
        self._t_last_step_ts = None
        self.metrics_server = None
        # ISSUE 14: a router sets this while prompts/handoffs are
        # pending so a decode-role engine's multi-step ticks stay short
        # enough to interleave with prefill work on one host thread
        self.tick_step_cap = None

    def _record(self, kind, **fields):
        """Ring event with the replica identity stamped (ISSUE 12):
        cross-replica trace stitching needs to know which engine
        emitted what when N replicas share one recorder."""
        if self.replica_id is not None and "replica" not in fields:
            fields["replica"] = self.replica_id
        self.recorder.record(kind, **fields)

    @property
    def preempted(self) -> bool:
        """True once the elastic controller finished its
        drain-or-snapshot pass — serve() stops stepping and the
        leftover requests live in the committed snapshot."""
        return self.elastic is not None and self.elastic.preempted

    def attach_elastic(self, controller) -> None:
        self.elastic = controller

    # ----------------------------------------------------------- metrics

    def _note_pool(self) -> None:
        """Record page-pool occupancy (+ high-water mark) — called
        after admissions (the local peak) and after ticks (releases).
        Refcount-0 resident prefix-cache pages count as CACHED, not
        live — they free on demand under pool pressure."""
        alloc = self.cache.num_blocks - 1
        cached = self.cache.cached_pages
        used = alloc - self.cache.free_pages - cached
        m = self.metrics
        m.gauge("serving/page_pool_used_pages").set(used)
        m.gauge("serving/prefix_cache_pages").set(cached)
        occ = used / max(alloc, 1)
        m.gauge("serving/page_pool_occupancy").set(occ)
        m.gauge("serving/page_pool_occupancy_hwm").set_max(occ)

    def _note_first_decode_tick(self, req, now) -> None:
        """TTFT attribution tail (ISSUE 14): time from first-token
        delivery (prefill readback — or handoff completion on a decode
        engine) to the request's first committed decode-tick token.
        Observed once per request."""
        if getattr(req, "_first_tick_noted", False):
            return
        req._first_tick_noted = True
        base = getattr(req, "_t_handoff_done", None)
        if base is None:
            base = getattr(req, "_t_first_tok", None)
        if base is not None:
            self.metrics.histogram(
                "serving/first_decode_tick_s").observe(
                max(now - base, 0.0))

    def metrics_snapshot(self) -> Dict[str, Any]:
        """One JSON-able dict of the serving observables: queue depth,
        admission wait, time-to-first-token, per-tick decode latency,
        tokens/sec, slot utilization, page-pool occupancy (+ HWM), and
        the watchdog state — a monotonic ``dump_id`` plus the last
        anomaly (ISSUE 6 satellite; 0/None when no watchdog is
        attached)."""
        snap = self.metrics.snapshot()
        hists = snap["histograms"]
        gauges = snap["gauges"]
        now = time.monotonic()
        lifetime = (now - self._t_first_decode) \
            if self._t_first_decode is not None else 0.0
        alloc = self.cache.num_blocks - 1
        st = self.stats
        prompt_toks = st["prefix_tokens_prompt"]
        return {
            "role": self.role,
            "queue_depth": len(self.queue),
            "active_slots": sum(s.active for s in self.slots),
            "slots": len(self.slots),
            "page_pool": {
                "allocatable_pages": alloc,
                "used_pages": alloc - self.cache.free_pages
                - self.cache.cached_pages,
                "prefix_cached_pages": self.cache.cached_pages,
                "occupancy": gauges.get("serving/page_pool_occupancy", 0.0),
                "occupancy_hwm": gauges.get(
                    "serving/page_pool_occupancy_hwm", 0.0),
            },
            "prefix_cache": {
                "enabled": self.prefix_cache,
                # token-level hit rate: shared prompt tokens (skipped
                # prefill compute AND skipped page writes) over all
                # prompt tokens admitted
                "hit_rate": (st["prefix_tokens_shared"] / prompt_toks)
                if prompt_toks else 0.0,
                "pages_saved": st["prefix_pages_saved"],
                **({k: v for k, v in self.cache.prefix_stats.items()}
                   if self.prefix_cache else {}),
            },
            "speculative": {
                "enabled": self.drafter is not None,
                "rounds": st["spec_rounds"],
                "proposed": st["spec_proposed"],
                "accepted": st["spec_accepted"],
                "accept_rate": (st["spec_accepted"] / st["spec_proposed"])
                if st["spec_proposed"] else 0.0,
            },
            "admission_wait_s": hists.get("serving/admission_wait_s",
                                          {"count": 0}),
            "ttft_s": hists.get("serving/ttft_s", {"count": 0}),
            # TTFT attribution (ISSUE 14 satellite): the head-of-line
            # gap decomposed — queue-wait + prefill sum to ttft_s;
            # handoff + first-decode-tick are the post-first-token path
            # a disaggregated request additionally crosses
            "ttft_breakdown": {
                "queue_wait_s": hists.get("serving/ttft_queue_wait_s",
                                          {"count": 0}),
                "prefill_s": hists.get("serving/ttft_prefill_s",
                                       {"count": 0}),
                "handoff_s": hists.get("serving/handoff_s",
                                       {"count": 0}),
                "transport_s": hists.get("serving/transport_s",
                                         {"count": 0}),
                "transport_encode_s": hists.get(
                    "serving/transport_encode_s", {"count": 0}),
                "transport_collective_s": hists.get(
                    "serving/transport_collective_s", {"count": 0}),
                "transport_decode_s": hists.get(
                    "serving/transport_decode_s", {"count": 0}),
                "first_decode_tick_s": hists.get(
                    "serving/first_decode_tick_s", {"count": 0}),
            },
            "tick_latency_s": hists.get("serving/tick_latency_s",
                                        {"count": 0}),
            "decode_latency_per_token_s": hists.get(
                "serving/decode_latency_per_token_s", {"count": 0}),
            "slot_utilization": hists.get("serving/slot_utilization",
                                          {"count": 0}),
            "decode_tokens_per_sec": (self.stats["decode_tokens"] / lifetime)
            if lifetime > 0 else 0.0,
            "dump_id": self.watchdog.dump_id
            if self.watchdog is not None else 0,
            "last_anomaly": self.watchdog.last_anomaly
            if self.watchdog is not None else None,
            "watchdog": self.watchdog.snapshot()
            if self.watchdog is not None else None,
            **self.stats,
        }

    # ------------------------------------------------------------- queue

    def submit(self, request: Request) -> None:
        S = int(np.asarray(request.prompt).shape[0])  # sync-ok: host prompt
        assert S >= 1, "empty prompt"
        # prefill unconditionally samples the first token, so a zero
        # budget would still emit one — reject instead of over-serving
        assert request.max_new_tokens >= 1, (
            f"max_new_tokens must be >= 1, got {request.max_new_tokens}")
        total = S + request.max_new_tokens
        # every decoded position needs a real learned position — past
        # the model budget the wpe gather would clamp and silently
        # corrupt (same contract as the dense generate() paths)
        assert total <= self.adapter.max_prompt_len(), (
            f"prompt {S} + max_new_tokens {request.max_new_tokens} "
            f"exceeds the model's position budget "
            f"{self.adapter.max_prompt_len()}")
        cap = self.spec.max_tokens_per_slot()
        assert total <= cap, (
            f"prompt {S} + max_new_tokens {request.max_new_tokens} "
            f"exceeds the per-slot page capacity {cap} "
            f"(max_pages_per_slot {self.spec.max_pages_per_slot} x "
            f"page_size {self.spec.page_size})")
        # an oversubscribed pool (num_blocks set low) must still be able
        # to hold this request once everything else drains — otherwise
        # FIFO admission would wait on it forever
        assert self.cache.pages_needed(total) <= self.cache.num_blocks - 1, (
            f"request needs {self.cache.pages_needed(total)} pages but "
            f"the whole pool has {self.cache.num_blocks - 1} allocatable "
            f"blocks (serving.num_blocks)")
        # the prefill bucket pads the prompt to WHOLE pages, so the
        # prompt must fit the model's position budget in page units —
        # with a page size that doesn't divide it, the last partial
        # page is unusable for prompts (admission would otherwise
        # allocate pages and then crash inside prefill)
        max_prompt_pages = self.adapter.max_prompt_len() \
            // self.spec.page_size
        assert self.cache.pages_needed(S) <= max_prompt_pages, (
            f"prompt {S} needs {self.cache.pages_needed(S)} pages but "
            f"only {max_prompt_pages} whole pages of "
            f"{self.spec.page_size} fit the model's "
            f"{self.adapter.max_prompt_len()}-position budget")
        ensure_trace_id(request)
        if request.temperature and request.temperature > 0 \
                and request.sample_key is None:
            # per-request sampling identity (idempotent: a restored /
            # replayed request arrives with the key it was born with)
            request.sample_key = int(
                self._host_rng.randint(0, 2 ** 31 - 1))  # sync-ok: host
        request._t_submit = time.monotonic()
        self.queue.append(request)
        self.metrics.gauge("serving/queue_depth").set(len(self.queue))

    @property
    def pending(self) -> int:
        return len(self.queue) + sum(s.active for s in self.slots)

    # --------------------------------------------------------- admission

    def _bucket_count(self, need: int) -> int:
        """pow2_page_bucket against the position budget (the full
        prefill path buckets inside padded_prefill_inputs; the
        suffix/prefix prefill buckets here). submit() guarantees the
        prompt itself fits in whole pages, so the clamp only trims
        pad."""
        return pow2_page_bucket(
            need, self.adapter.max_prompt_len() // self.spec.page_size)

    @staticmethod
    def _sample_base(req) -> int:
        """Global token index of the request's FIRST not-yet-sampled
        token minus len(generated): tokens committed in previous
        incarnations (folded into a replay prompt) shift the sampling
        index so a restored request keeps drawing the same stream."""
        return int(getattr(req, "resumed_committed", 0) or 0)

    def _pick_token(self, logits: np.ndarray, req: Request) -> int:
        if req.temperature and req.temperature > 0:
            from deepspeed_tpu.serving.adapters import sample_token
            idx = self._sample_base(req) + len(req.generated)
            return sample_token(logits, req.sample_key or 0, idx,
                                req.temperature)
        return int(np.argmax(logits))

    def _admit(self, now: Optional[float]) -> List[Request]:
        finished = []
        free = [i for i, s in enumerate(self.slots) if not s.active]
        while free and self.queue:
            req = self.queue[0]
            if now is not None and req.arrival_time > now:
                break                 # FIFO: don't skip ahead of arrivals
            prompt_np = np.asarray(req.prompt, np.int32)  # sync-ok: host prompt
            S = int(prompt_np.shape[0])
            slot_id = free[0]
            plan = None
            if self.prefix_cache:
                plan = self.cache.admit_prefix(
                    slot_id, prompt_np, S + req.max_new_tokens,
                    cow=self.prefix_cow)
                pages = plan.pages if plan is not None else None
            else:
                pages = self.cache.admit(slot_id, S + req.max_new_tokens)
            if pages is None:
                # pool exhausted; retry next step. The watchdog rule is
                # latched per episode — one dump until pages free again
                need = self.cache.pages_needed(S + req.max_new_tokens)
                self._record(
                    "pool_exhausted", rid=req.rid,
                    trace=getattr(req, "trace_id", None), need_pages=need,
                    free_pages=self.cache.available_pages,
                    queue_depth=len(self.queue),
                    parent_span=getattr(req, "span_id", None))
                if self.watchdog is not None:
                    self.watchdog.note_pool_exhausted(
                        queue_depth=len(self.queue),
                        free_pages=self.cache.available_pages,
                        need_pages=need)
                break
            self.queue.popleft()
            free.pop(0)
            # fault point (ISSUE 11): pages are allocated, nothing is
            # prefilled yet — a replica dying HERE models the
            # mid-prefill crash the pool recovery tests drive
            faults.fire("serving_admit", rid=req.rid, slot=slot_id)
            t_admit = time.monotonic()
            # wait since the request became ADMISSIBLE (its arrival
            # under respect_arrival_times, its submit otherwise)
            t_ref = getattr(req, "_t_arrived", None)
            if t_ref is None:
                t_ref = getattr(req, "_t_submit", t_admit)
            wait_s = max(t_admit - t_ref, 0.0)
            self.metrics.histogram("serving/admission_wait_s").observe(
                wait_s)
            # TTFT attribution (ISSUE 14 satellite): queue-wait ends
            # here, the prefill component starts — the two sum to the
            # colocated ttft_s; handoff / first-decode-tick components
            # land later (zero on a colocated engine's TTFT)
            self.metrics.histogram("serving/ttft_queue_wait_s").observe(
                wait_s)
            t_pf0 = time.monotonic()
            start = plan.start_pos if plan is not None else 0
            # the admit event IS the request's root span (ISSUE 19):
            # span_id = the id minted at first submit, no parent — every
            # downstream lifecycle span in any rank's dump parents onto
            # it, so the merged export has zero orphans by construction
            self._record("admit", rid=req.rid, slot=slot_id,
                         trace=getattr(req, "trace_id", None),
                         pages=len(pages), wait_s=wait_s,
                         shared_tokens=start,
                         span_id=getattr(req, "span_id", None))
            if self.watchdog is not None:
                self.watchdog.note_pool_ok()   # re-arm the pool rule
            P = self.spec.page_size
            if plan is not None and plan.cow is not None:
                # COW: the matched rows of the partially-filled prefix
                # page are device-copied into this slot's own page; the
                # suffix prefill continues writing mid-page. (With
                # prefix_cow off the cache never matches partial pages,
                # so plan.cow is None by construction.)
                src, dst, _rows = plan.cow
                self.cache.pool = self.adapter.copy_block(
                    self.cache.pool, src, dst)
            if start > 0:
                # prefix hit: prefill ONLY the suffix — the shared
                # span's K/V is already resident through the page table
                suf_len = S - start
                n_pre = min(self._bucket_count(-(-start // P)),
                            self.spec.max_pages_per_slot)
                # same pow2 page bucket + zero-pad contract as the full
                # prefill (the page_vec is unused — prefill_suffix reads
                # through the slot's page-table row)
                ids, _ = padded_prefill_inputs(
                    prompt_np[start:], [], P,
                    self.adapter.max_prompt_len() // P)
                pool, logits = self.adapter.prefill_suffix(
                    self.cache.pool, jnp.asarray(ids), S, start, n_pre,
                    self.cache.page_table[slot_id])
                self.stats["prefill_tokens"] += suf_len
            else:
                ids, page_vec = padded_prefill_inputs(
                    prompt_np, pages, P,
                    self.adapter.max_prompt_len() // P)
                pool, logits = self.adapter.prefill(
                    self.cache.pool, jnp.asarray(ids),
                    jnp.asarray(S, jnp.int32), jnp.asarray(page_vec))
                self.stats["prefill_tokens"] += S
            self.cache.pool = pool
            self.stats["prefills"] += 1
            if self.prefix_cache:
                self.cache.register_prefix(
                    slot_id, prompt_np, hashes=plan.hashes)
                n_shared = start // P
                self.stats["prefix_tokens_shared"] += start
                self.stats["prefix_tokens_prompt"] += S
                self.stats["prefix_pages_saved"] += n_shared
                m = self.metrics
                m.counter("serving/prefix_tokens_shared").inc(start)
                m.counter("serving/prefix_tokens_prompt").inc(S)
                m.counter("serving/prefix_pages_saved").inc(n_shared)
            tok = self._pick_token(
                np.asarray(logits, np.float32),  # sync-ok: scheduler
                req)                             # consumes the sample
            req.generated.append(tok)
            # the prefill logits readback above IS first-token delivery
            t_tok = time.monotonic()
            ttft_s = max(t_tok - t_ref, 0.0)
            self.metrics.histogram("serving/ttft_s").observe(ttft_s)
            self.metrics.histogram("serving/ttft_prefill_s").observe(
                max(t_tok - t_pf0, 0.0))
            req._t_first_tok = t_tok   # base for the first-decode-tick
            #                            (and handoff) TTFT components
            self._record("prefill", rid=req.rid,
                         trace=getattr(req, "trace_id", None),
                         prompt_tokens=S, ttft_s=ttft_s,
                         prefill_s=max(t_tok - t_pf0, 0.0),
                         span_id=new_span_id(),
                         parent_span=getattr(req, "span_id", None))
            if self.watchdog is not None:
                # the readback above was the fence — the rule sees only
                # the host scalar it produced
                self.watchdog.observe_ttft(ttft_s, rid=req.rid)
            if self._t_first_decode is None:
                self._t_first_decode = time.monotonic()
            slot = self.slots[slot_id]
            slot.request, slot.pos, slot.last_tok = req, S, tok
            done = self._maybe_finish(slot_id)
            if done is not None:      # max_new_tokens == 1 / instant EOS
                finished.append(done)
                free.insert(0, slot_id)
            elif self.drafter is not None:
                # drafter mirrors the admission (its own prefill for a
                # ModelDrafter, host history for the n-gram fallback)
                self.drafter.admit(slot_id, prompt_np, tok,
                                   S + req.max_new_tokens)
        self.metrics.gauge("serving/queue_depth").set(len(self.queue))
        self._note_pool()
        return finished

    # -------------------------------------------------------------- tick

    def _maybe_finish(self, slot_id: int) -> Optional[Request]:
        slot = self.slots[slot_id]
        req = slot.request
        if req is None:
            return None
        if req.eos_token_id is not None \
                and req.generated[-1] == req.eos_token_id:
            req.finish_reason = "eos"
        elif len(req.generated) >= req.max_new_tokens:
            req.finish_reason = "length"
        else:
            return None
        # with prefix sharing this is a DECREF: shared pages stay
        # resident for other holders (or as refcount-0 prefix cache)
        self.cache.release(slot_id)
        if self.drafter is not None:
            self.drafter.release(slot_id)
        slot.request, slot.pos, slot.last_tok = None, -1, 0
        self._record("finish", rid=req.rid,
                     trace=getattr(req, "trace_id", None),
                     reason=req.finish_reason,
                     generated=len(req.generated),
                     span_id=new_span_id(),
                     parent_span=getattr(req, "span_id", None))
        return req

    # multi-step dispatch caps: a tick of K steps amortizes the host
    # dispatch over K tokens. K = min remaining budget is LOSSLESS (no
    # slot can finish or free pages before that many steps anyway);
    # EOS-capable requests cap K low so an early stop wastes at most
    # max_eos_tick_steps - 1 speculative steps (the appends stay inside
    # the slot's own admitted pages either way).
    max_tick_steps = 32
    max_eos_tick_steps = 4

    def _pick_tick_steps(self) -> int:
        if self.queue and any(not s.active for s in self.slots):
            return 1                  # admission pending — stay responsive
        active = [s.request for s in self.slots if s.active]
        rem = min(r.max_new_tokens - len(r.generated) for r in active)
        cap = self.max_eos_tick_steps if any(
            r.eos_token_id is not None for r in active) \
            else self.max_tick_steps
        if self.tick_step_cap:
            cap = min(cap, self.tick_step_cap)
        k = 1
        while k * 2 <= min(rem, cap):  # pow2 bucket → few compiles
            k *= 2
        return k

    def _tick(self) -> List[Request]:
        steps = self._pick_tick_steps()
        n_active = sum(s.active for s in self.slots)
        toks = np.array([s.last_tok for s in self.slots], np.int32)
        pos = np.array([s.pos if s.active else -1 for s in self.slots],
                       np.int32)
        temps = np.array(
            [s.request.temperature if s.active else 0.0
             for s in self.slots], np.float32)
        # per-slot stateless sampling identity: (request sample_key,
        # global index of the slot's next token) — engine rng state
        # plays no part, so restores/handoffs replay sampled streams
        seeds = np.array(
            [(s.request.sample_key or 0) if s.active else 0
             for s in self.slots], np.uint32)
        idxs = np.array(
            [(self._sample_base(s.request) + len(s.request.generated))
             if s.active else 0 for s in self.slots], np.int32)
        t0 = time.monotonic()
        pool, toks_seq, logits = self.adapter.tick(
            self.cache.pool, jnp.asarray(toks), jnp.asarray(pos),
            jnp.asarray(self.cache.page_table), jnp.asarray(seeds),
            jnp.asarray(idxs), jnp.asarray(temps), steps=steps)
        self.cache.pool = pool
        self.last_logits = logits
        toks_seq = np.asarray(toks_seq)  # sync-ok: scheduler consumes
        #                                  the sampled tokens [steps,slots]
        tick_s = time.monotonic() - t0   # real: the asarray fenced it
        self._record("tick", steps=steps, active=n_active,
                     tick_s=tick_s,
                     traces=[s.request.trace_id for s in self.slots
                             if s.active])
        m = self.metrics
        m.histogram("serving/tick_latency_s").observe(tick_s)
        m.histogram("serving/decode_latency_per_token_s").observe(
            tick_s / max(steps, 1))
        m.histogram("serving/slot_utilization").observe(
            n_active / max(len(self.slots), 1))
        self.stats["ticks"] += 1
        self.stats["tick_steps"] += steps
        finished = []
        tokens_before = self.stats["decode_tokens"]
        t_commit = time.monotonic()
        for i, slot in enumerate(self.slots):
            if not slot.active:
                continue
            self._note_first_decode_tick(slot.request, t_commit)
            for t in range(steps):
                self.stats["decode_tokens"] += 1
                tok = int(toks_seq[t, i])   # sync-ok: host array already
                slot.request.generated.append(tok)
                slot.pos += 1
                slot.last_tok = tok
                done = self._maybe_finish(i)
                if done is not None:
                    # steps past an EOS were speculative; their appends
                    # landed in pages this slot owned until right now
                    finished.append(done)
                    break
        m.counter("serving/decode_tokens").inc(
            self.stats["decode_tokens"] - tokens_before)
        if self.drafter is not None:
            # keep the drafter aligned with the committed stream: a
            # plain tick (sampled slot live / admission pending / 1-token
            # budget) commits tokens the drafter never saw, and a
            # ModelDrafter's KV cache would otherwise hold NO rows for
            # those positions — accept rate silently collapses for the
            # rest of the request. Survivors committed all `steps`
            # tokens (an early EOS releases the slot in the loop above).
            survivors = [i for i in range(len(self.slots))
                         if pos[i] >= 0 and self.slots[i].active]
            if survivors:
                feed = np.vstack([toks[None, :], toks_seq[:-1]])
                self.drafter.observe_plain(survivors, feed, toks_seq)
        self._note_pool()
        return finished

    # ------------------------------------------------------- speculative

    def _pick_verify_rows(self) -> int:
        """Verification window (feed token + drafts): exactly the
        configured window while every active request has budget for it
        (ONE compiled verify program in steady state), pow2-bucketed
        only when the min remaining budget clamps it (O(log) extra
        end-of-request programs) — the appended rows always land inside
        the slot's admitted pages either way."""
        active = [s.request for s in self.slots if s.active]
        rem = min(r.max_new_tokens - len(r.generated) for r in active)
        cap = min(self.spec_tokens + 1, self.max_tick_steps)
        if rem >= cap:
            return cap
        k = 1
        while k * 2 <= rem:
            k *= 2
        return k

    def _spec_tick(self, V: int, active: List[int]) -> List[Request]:
        """One speculative round: draft V-1 tokens per active slot,
        verify the whole window in ONE multi-query dispatch, commit the
        longest greedy-matching prefix (+ the correction token).
        Rollback of rejected drafts is a pointer move — the appended
        rows past the committed position are overwritten by the next
        round's appends and never read (per-slot pos masking)."""
        B = len(self.slots)
        drafts = self.drafter.draft(active, V - 1)        # [n_act, V-1]
        toks = np.zeros((B, V), np.int32)
        toks[:, 0] = [s.last_tok for s in self.slots]
        for row, i in zip(drafts, active):
            toks[i, 1:] = row
        pos = np.array([s.pos if s.active else -1 for s in self.slots],
                       np.int32)
        t0 = time.monotonic()
        pool, greedy, logits = self.adapter.verify(
            self.cache.pool, toks, pos, self.cache.page_table)
        self.cache.pool = pool
        greedy = np.asarray(greedy)   # sync-ok: scheduler consumes the
        #                               verified tokens [B, V]; fences
        #                               the dispatch, so tick_s is real.
        #                               logits stay on device — only one
        #                               row per slot feeds last_logits.
        tick_s = time.monotonic() - t0
        n_active = len(active)
        # fault point (ISSUE 11): the verify dispatch ran but NOTHING is
        # committed yet — a crash here models dying mid-spec-verify;
        # every slot's pos still points at its last committed token, so
        # a snapshot/restore sees only verified tokens
        faults.fire("serving_spec_verify", rows=V, active=n_active)
        self._record("spec_round", rows=V, active=n_active,
                     tick_s=tick_s,
                     traces=[self.slots[i].request.trace_id
                             for i in active])
        m = self.metrics
        m.histogram("serving/tick_latency_s").observe(tick_s)
        m.histogram("serving/slot_utilization").observe(
            n_active / max(B, 1))
        self.stats["ticks"] += 1
        self.stats["tick_steps"] += 1  # one dispatched model step/round
        self.stats["spec_rounds"] += 1
        # drafters that keep their own KV state (ModelDrafter) can only
        # fast-forward through rows they actually appended — the free
        # correction token is dropped in the all-accepted case
        aligned = getattr(self.drafter, "aligned", False)
        finished = []
        tokens_before = self.stats["decode_tokens"]
        last_row = np.zeros(B, np.int32)
        t_commit = time.monotonic()
        for i in active:
            slot = self.slots[i]
            self._note_first_decode_tick(slot.request, t_commit)
            g, d = greedy[i], toks[i]
            a = 0
            while a < V - 1 and d[a + 1] == g[a]:
                a += 1
            ncommit = a + 1
            if aligned:
                ncommit = min(ncommit, V - 1)
            committed = []
            for t in range(ncommit):
                tok = int(g[t])
                self.stats["decode_tokens"] += 1
                slot.request.generated.append(tok)
                slot.pos += 1
                slot.last_tok = tok
                committed.append(tok)
                done = self._maybe_finish(i)
                if done is not None:
                    finished.append(done)
                    break
            self.stats["spec_proposed"] += V - 1
            self.stats["spec_accepted"] += min(a, len(committed))
            last_row[i] = len(committed) - 1
            if slot.active:
                self.drafter.commit(i, committed, slot.pos,
                                    slot.last_tok)
        # device-side gather of each slot's last committed row — the
        # last_logits contract without hauling [B, V, vocab] to host
        self.last_logits = logits[jnp.arange(B), jnp.asarray(last_row)]
        n_committed = self.stats["decode_tokens"] - tokens_before
        m.counter("serving/decode_tokens").inc(n_committed)
        # per-token latency stays live under speculation: one dispatch
        # commits up to V tokens per slot
        m.histogram("serving/decode_latency_per_token_s").observe(
            tick_s / max(n_committed / max(n_active, 1), 1e-9))
        m.counter("serving/spec_proposed").inc(n_active * (V - 1))
        m.gauge("serving/spec_accept_rate").set(
            self.stats["spec_accepted"]
            / max(self.stats["spec_proposed"], 1))
        self._note_pool()
        return finished

    def _decode_step(self) -> List[Request]:
        """One decode dispatch: the speculative round when a drafter is
        attached and every active request is greedy, else the plain
        multi-step tick (speculative verify is greedy-only — sampling
        would need rejection-sampling verification to stay lossless)."""
        if self.drafter is None:
            return self._tick()
        active = [i for i, s in enumerate(self.slots) if s.active]
        if any(self.slots[i].request.temperature > 0 for i in active):
            return self._tick()
        if self.queue and any(not s.active for s in self.slots):
            return self._tick()       # admission pending: 1-step tick
        V = self._pick_verify_rows()
        if V < 2:
            return self._tick()
        return self._spec_tick(V, active)

    # ------------------------------------------------------------- abort

    def abort(self, request_id) -> Optional[Request]:
        """Abort one admitted-or-queued request (ISSUE 11 satellite):
        decref its pages NOW instead of leaking them until EOS, release
        the drafter's mirror state, emit a ``serving_abort`` ring event.
        Returns the request with ``finish_reason="aborted"`` (its
        committed ``generated`` tokens intact), or None when the id is
        unknown (already finished)."""
        for slot_id, slot in enumerate(self.slots):
            if slot.active and slot.request.rid == request_id:
                req = slot.request
                self.cache.release(slot_id)
                if self.drafter is not None:
                    self.drafter.release(slot_id)
                slot.request, slot.pos, slot.last_tok = None, -1, 0
                req.finish_reason = "aborted"
                self._record("serving_abort", rid=req.rid,
                             trace=getattr(req, "trace_id", None),
                             slot=slot_id, where="slot",
                             generated=len(req.generated))
                self._note_pool()
                return req
        for req in self.queue:
            if req.rid == request_id:
                self.queue.remove(req)
                req.finish_reason = "aborted"
                self._record("serving_abort", rid=req.rid,
                             trace=getattr(req, "trace_id", None),
                             slot=None, where="queue",
                             generated=0)
                self.metrics.gauge("serving/queue_depth").set(
                    len(self.queue))
                return req
        return None

    def drain(self) -> List[Request]:
        """Abort EVERY in-flight and queued request (shutdown /
        scale-down fence): after drain() the pool holds no live pages —
        only refcount-0 resident prefix cache, which
        ``sweep_prefix_cache()`` returns to the free list."""
        out = []
        for slot in list(self.slots):
            if slot.active:
                out.append(self.abort(slot.request.rid))
        while self.queue:
            out.append(self.abort(self.queue[0].rid))
        return out

    # ----------------------------------------------------------- handoff

    def export_slot(self, slot_id: int):
        """Detach an active slot for a prefill→decode page handoff
        (ISSUE 14): the request leaves WITHOUT a finish event and its
        pages decref NOW — the caller (serving/router.py) must already
        hold a device-side gather of the slot's data pages. Returns
        ``(request, pos, last_tok)``."""
        slot = self.slots[slot_id]
        req, pos, last_tok = slot.request, slot.pos, slot.last_tok
        assert req is not None, f"slot {slot_id} idle"
        self.cache.release(slot_id)
        slot.request, slot.pos, slot.last_tok = None, -1, 0
        self.stats["handoffs_out"] += 1
        self.metrics.counter("serving/handoffs_out").inc()
        # ISSUE 19: mint the HANDOFF span here — the transport legs
        # (encode on this rank, decode/adopt on the receiving rank)
        # parent onto it, and extract_handoff ships it in the wire doc
        # so the receiving rank's events can reference it
        req._handoff_span = new_span_id()
        self._record("handoff_out", rid=req.rid,
                     trace=getattr(req, "trace_id", None),
                     slot=slot_id, pos=pos,
                     generated=len(req.generated),
                     span_id=req._handoff_span,
                     parent_span=getattr(req, "span_id", None))
        self._note_pool()
        return req, pos, last_tok

    def adopt_request(self, slot_id: int, req: Request, pos: int,
                      last_tok: int) -> None:
        """Install an already-prefilled request into a free slot (the
        receiving half of a handoff / elastic restore): the caller has
        already mapped the request's pages into ``slot_id``'s page
        table (cache ``admit``/``admit_prefix`` + scatter) — this
        rebuilds the host slot state and realigns any drafter."""
        slot = self.slots[slot_id]
        assert slot.request is None, f"slot {slot_id} busy"
        slot.request, slot.pos, slot.last_tok = req, pos, last_tok
        if self.drafter is not None:
            prompt_np = np.asarray(req.prompt, np.int32)  # sync-ok: host
            self.drafter.restore_slot(
                slot_id, prompt_np, req.generated,
                len(prompt_np) + req.max_new_tokens)
        self.stats["handoffs_in"] += 1
        self.metrics.counter("serving/handoffs_in").inc()
        t_done = time.monotonic()
        # always the first-decode-tick base — a request rebuilt from a
        # cross-process wire doc arrives WITHOUT _t_first_tok (that
        # monotonic stamp died with the sending process) but its
        # first-tick latency on THIS engine is still well-defined
        req._t_handoff_done = t_done
        t_first = getattr(req, "_t_first_tok", None)
        if t_first is not None:
            self.metrics.histogram("serving/handoff_s").observe(
                max(t_done - t_first, 0.0))
        # parent preference (ISSUE 19): the transport ENCODE span when
        # the packet crossed the process fabric, else the handoff span
        # minted at export, else the request root — whichever leg this
        # packet actually traversed, the tree stays connected
        parent = (getattr(req, "_encode_span", None)
                  or getattr(req, "_handoff_span", None)
                  or getattr(req, "span_id", None))
        self._record("handoff_in", rid=req.rid,
                     trace=getattr(req, "trace_id", None),
                     slot=slot_id, pos=pos,
                     generated=len(req.generated),
                     span_id=new_span_id(), parent_span=parent)
        self._note_pool()

    def step(self, now: Optional[float] = None) -> List[Request]:
        """One scheduler iteration: admit whatever fits, then one decode
        tick (or speculative verify round) over the active slots.
        Returns requests finished this step (including any that finished
        at prefill with max_new_tokens=1). A prefill-role engine skips
        the decode dispatch — its active slots wait for the router's
        handoff sweep."""
        finished = self._admit(now) if self._admitting else []
        if self.role != "prefill" and any(s.active for s in self.slots):
            finished.extend(self._decode_step())
        # fault point + elastic policy (ISSUE 11): the tick boundary is
        # the only place slot state is consistent (no speculation in
        # flight), so SIGTERM handling, periodic snapshot begin/commit
        # and the drain-or-snapshot decision all live here
        faults.fire("serving_tick_end", tick=self.stats["ticks"],
                    pending=self.pending)
        self._t_last_step_ts = time.time()   # /healthz fence age
        if self.elastic is not None:
            self.elastic.on_tick_end()
        return finished

    # ------------------------------------------------------------- serve

    def serve(self, requests: Sequence[Request],
              respect_arrival_times: bool = False) -> Dict[Any, Request]:
        """Run the scheduler until every request completes. With
        ``respect_arrival_times`` the queue honours each request's
        ``arrival_time`` against a wall clock started on entry —
        the mode of a Poisson-arrival workload."""
        for r in sorted(requests, key=lambda r: r.arrival_time):
            self.submit(r)
        done: Dict[Any, Request] = {}
        t0 = time.monotonic()
        if respect_arrival_times:
            # TTFT/admission-wait reference: when arrivals are honoured
            # a request only becomes admissible at its arrival time
            for r in requests:
                r._t_arrived = t0 + r.arrival_time
        while self.pending and not self.preempted:
            now = (time.monotonic() - t0) if respect_arrival_times \
                else None
            if respect_arrival_times and not any(
                    s.active for s in self.slots) and self.queue:
                wait = self.queue[0].arrival_time - (
                    time.monotonic() - t0)
                if wait > 0:
                    time.sleep(min(wait, 0.05))
                    if self.elastic is not None:
                        # a SIGTERM landing while we idle between
                        # arrivals must not wait for the next tick —
                        # the queued (never-admitted) requests snapshot
                        # here exactly like at a tick boundary (idle:
                        # the sleep must not feed the tick-latency EMA)
                        self.elastic.on_tick_end(idle=True)
                    continue
            for req in self.step(now):
                done[req.rid] = req
        # requests that finished at admission time (max_new_tokens == 1
        # or instant EOS) are collected by step(); nothing else pending
        return done
