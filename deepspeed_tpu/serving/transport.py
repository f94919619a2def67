"""Cross-process KV page-handoff transport (ISSUE 17 tentpole).

PR 14's disaggregated split moves :class:`HandoffPacket`\\ s between
roles in-process only. This module is the real fabric: the packet's
``(wire doc, per-pool-component page arrays)`` pair — serializable by
design — crosses OS processes over the PR-10/15 gloo harness, so
prefill-role and decode-role engines can live on DIFFERENT hosts.

Three layers:

**Wire codec.** One self-delimiting frame per message::

    magic "DSHP" | version u16 | header_len u32 | header_crc u32
    | header JSON | component payloads (raw array bytes) ...

The header carries ``kind`` ("packet" / "done" / "nack"), ``src`` /
``dst`` ranks, the JSON wire doc, and per-component
``{dtype, shape, crc}`` metadata. Every byte is crc-checked (header and
each payload independently), the version word makes a field addition
LOUD instead of silently corrupting old packets or serving snapshots
(an unknown version raises :class:`WireFormatError`), and unknown
header keys are ignored so a same-version reader tolerates forward
extensions. Encoding is canonical (sorted keys, minimal separators):
re-encoding a decoded frame reproduces the identical bytes — the
golden-test property and the receiver-side cost model
(:func:`frame_nbytes`) both ride on it. Pure numpy + stdlib: the codec
never touches a jax backend.

**Aligned exchange.** The header leg keeps PR 17's fence discipline:
one fixed-width float allgather of ``[sizes, *metrics]`` every rank
calls at the same loop point (the ``ClusterAggregator`` fence), so the
exchange cannot deadlock; the collectives are SEQUENTIAL with one
device per process, the documented gloo-flake-stable recipe
(tests/test_multiprocess_dist). ISSUE 18 splits the PAYLOAD off that
fence: with ``addressing="targeted"`` (default) the header leg also
carries the per-destination traffic matrix, destination-addressed
frames (``dst >= 0`` — packets, done, nack) then move point-to-point
over :func:`~deepspeed_tpu.utils.distributed
.exchange_host_bytes_targeted`'s deterministic socket schedule, and
only dst<0 traffic rides the padded broadcast allgather — a KV payload
crosses the wire ONCE regardless of world size, where the PR-17
broadcast paid O(world x payload). ``addressing="broadcast"`` keeps
the legacy single-leg allgather; either way the bytes a rank received
WITHOUT being addressed (filtered frames + broadcast padding) land in
``router/handoff_wasted_bytes``, so the per-handoff wire cost is
assertable from counters alone.

**Role nodes.** Rank 0 runs :class:`PrefillNode` — the router lives on
the prefill rank: admission (bounded by ``max_inflight_pages`` fed
from the exchanged metrics), prefill engine steps, packet extraction
(``gather_block_kv``), LPT placement across EVERY decode rank (least
exchanged remaining-decode estimate, per-rank inflight-pages caps —
packets with no eligible rank queue HERE), "done"/"nack" intake,
bounded nack replay from the wire doc. Ranks >= 1 run :class:`DecodeNode`:
decode frames, land packets through
:func:`~deepspeed_tpu.serving.router.deliver_handoff` (the receiving
pool's prefix index re-shares resident full prompt pages — the
content-addressed dedupe survives the process boundary; a delivery
crash at the ``serving_deliver`` fault point unwinds the admission and
nacks), tick the decode engine, ship finished streams back.

:class:`LoopbackFabric` runs the same nodes and the same codec inside
ONE process (frames round-trip through encode/decode in memory, no
collectives) — the fast single-process sibling of the 2-real-process
acceptance tests.
"""

import json
import struct
import time
import zlib
from collections import deque
from typing import Any, Dict, List, Optional

import numpy as np

from deepspeed_tpu.utils.logging import logger

WIRE_MAGIC = b"DSHP"
WIRE_VERSION = 1
_HEAD = struct.Struct("<4sHII")   # magic, version, header_len, header_crc
FRAME_BASE_NBYTES = _HEAD.size

# phase-1 metrics-vector layout: one fp32 slot each, published by every
# rank at every exchange. Senders read the decode rows for backpressure
# (free pages/slots, cumulative absorbed pages); everyone reads rank
# 0's MV_STOP to leave the loop at the SAME aligned exchange.
MV_LEN = 8
MV_ROLE = 0            # 0 = prefill/router rank, 1 = decode rank
MV_FREE_PAGES = 1      # decode pool pages currently allocatable
MV_FREE_SLOTS = 2      # decode slots currently free
MV_ABSORBED_PAGES = 3  # cumulative data pages absorbed (delivered)
MV_DONE = 4            # cumulative requests finished on this rank
MV_STOP = 5            # rank 0 sets 1: drain done, leave after this tick
MV_REMAINING = 6       # est. remaining decode tokens (active + waiting)
#   — the LPT balancing signal the router minimizes over decode ranks
MV_TICK_S = 7          # most recent decode-tick latency on this rank
#   (ISSUE 19) — the per-ROLE decode-latency feed the rank-0 SLO plane
#   windows into slo/decode/* quantiles + burn rate; 0 = no tick yet


class WireFormatError(ValueError):
    """A frame failed validation: bad magic, unknown version, crc
    mismatch, or truncation. Deliberately LOUD — a silently-tolerated
    corrupt packet would scatter garbage KV into a decode pool."""


def _jsonable(o):
    if isinstance(o, np.integer):
        return int(o)
    if isinstance(o, np.floating):
        return float(o)  # sync-ok: numpy scalar, already host
    if isinstance(o, np.ndarray):
        return o.tolist()
    raise TypeError(f"not wire-serializable: {type(o)!r}")


def encode_frame(kind: str, doc: Optional[dict], comps=(),
                 src: int = 0, dst: int = -1) -> bytes:
    """One message → canonical frame bytes. ``comps`` are array-likes
    (a packet's per-pool-component page gathers); ``dst=-1``
    broadcasts. Canonical JSON (sorted keys, minimal separators) makes
    encoding deterministic: encode(decode(b)) == b."""
    # the serialization point: gathered pages must leave the device to
    # cross the process boundary as bytes
    arrs = [np.ascontiguousarray(np.asarray(c))  # sync-ok: wire encode
            for c in comps]
    meta = [{"dtype": a.dtype.str, "shape": list(a.shape),
             "crc": zlib.crc32(a.tobytes()) & 0xFFFFFFFF}
            for a in arrs]
    header = json.dumps(
        {"v": WIRE_VERSION, "kind": str(kind), "src": int(src),
         "dst": int(dst), "doc": doc, "comps": meta},
        sort_keys=True, separators=(",", ":"),
        default=_jsonable).encode()
    out = [_HEAD.pack(WIRE_MAGIC, WIRE_VERSION, len(header),
                      zlib.crc32(header) & 0xFFFFFFFF), header]
    out.extend(a.tobytes() for a in arrs)
    return b"".join(out)


def decode_frame(buf, offset: int = 0):
    """Decode one frame at ``offset``; returns ``(frame, next_offset)``
    where frame is ``{"kind", "src", "dst", "doc", "comps"}`` with
    comps a tuple of numpy arrays. Raises :class:`WireFormatError` on
    any validation failure."""
    view = memoryview(buf)
    if len(view) - offset < _HEAD.size:
        raise WireFormatError(
            f"truncated frame: {len(view) - offset} bytes < "
            f"{_HEAD.size}-byte fixed header")
    magic, ver, hlen, hcrc = _HEAD.unpack_from(view, offset)
    if magic != WIRE_MAGIC:
        raise WireFormatError(f"bad magic {magic!r} (want {WIRE_MAGIC!r})")
    if ver != WIRE_VERSION:
        # the versioned-header contract: a future field addition bumps
        # WIRE_VERSION, and an old reader REFUSES instead of
        # misparsing old packets/snapshots into silent corruption
        raise WireFormatError(
            f"wire version {ver} not supported (this codec speaks "
            f"{WIRE_VERSION}); refusing to guess at the layout")
    offset += _HEAD.size
    header = bytes(view[offset:offset + hlen])
    if len(header) != hlen:
        raise WireFormatError("truncated frame header")
    if zlib.crc32(header) & 0xFFFFFFFF != hcrc:
        raise WireFormatError("header crc mismatch")
    h = json.loads(header.decode())
    offset += hlen
    comps = []
    for m in h.get("comps", ()):
        dt = np.dtype(m["dtype"])
        n = int(np.prod(m["shape"], dtype=np.int64)) * dt.itemsize
        raw = bytes(view[offset:offset + n])
        if len(raw) != n:
            raise WireFormatError("truncated component payload")
        if zlib.crc32(raw) & 0xFFFFFFFF != int(m["crc"]):
            raise WireFormatError("component payload crc mismatch")
        comps.append(np.frombuffer(raw, dt).reshape(m["shape"]))
        offset += n
    return {"kind": h["kind"], "src": int(h.get("src", 0)),
            "dst": int(h.get("dst", -1)), "doc": h.get("doc"),
            "comps": tuple(comps)}, offset


def decode_frames(buf) -> List[dict]:
    """All frames in a buffer (frames are self-delimiting)."""
    out, offset = [], 0
    while offset < len(buf):
        frame, offset = decode_frame(buf, offset)
        out.append(frame)
    return out


def frame_nbytes(frame: dict) -> int:
    """Receiver-side cost model: the exact wire size of a decoded
    frame, recomputed from its CONTENT (canonical encoding makes this
    equal to the bytes that actually traveled) — what the
    ``router/handoff_bytes_recv`` counter observes, so the acceptance
    test can pin counters against packet sizes independently of the
    sender's arithmetic."""
    return len(encode_frame(frame["kind"], frame["doc"], frame["comps"],
                            frame["src"], frame["dst"]))


def payload_nbytes(comps) -> int:
    """Raw KV payload bytes of a component tuple (frame size minus
    header: ``n_data_pages * cache.page_nbytes`` for a packet)."""
    return sum(int(np.asarray(c).nbytes) for c in comps)  # sync-ok: nbytes only


def encode_packet(packet, src: int = 0, dst: int = -1) -> bytes:
    """A :class:`~deepspeed_tpu.serving.router.HandoffPacket` → one
    "packet" frame. The live ``req`` object does NOT travel — the
    receiver rebuilds it from the wire doc
    (``elastic.resume_request``), exactly the ``req=None`` path
    ``deliver_handoff`` already speaks."""
    return encode_frame("packet", packet.doc, packet.kv, src, dst)


def packet_from_frame(frame: dict):
    """The receiving half: a decoded "packet" frame → HandoffPacket
    with ``req=None`` (rebuild-from-doc delivery)."""
    from deepspeed_tpu.serving.router import HandoffPacket
    return HandoffPacket(dict(frame["doc"]), frame["comps"], None)


# ----------------------------------------------------------- endpoints

class LoopbackFabric:
    """Single-process fabric: endpoints exchange ENCODED frames through
    an in-memory inbox, so the codec and both node state machines run
    for real with no collectives — the fast sibling of the
    N-real-process path. Metrics rows update at each endpoint's
    exchange (last-written wins, like the aligned gather's snapshot).
    ``addressing="targeted"`` (default) routes each frame to its
    destination only, mirroring the socket payload leg;
    ``addressing="broadcast"`` copies every frame to every rank and
    lets receivers filter — the PR-17 wire shape, so the
    ``handoff_wasted_bytes`` accounting is testable without spawning
    processes."""

    def __init__(self, world: int, addressing: str = "targeted"):
        assert world >= 2, world
        assert addressing in ("targeted", "broadcast"), addressing
        self.world = int(world)
        self.addressing = addressing
        self._inbox = [deque() for _ in range(self.world)]
        self._metrics = np.zeros((self.world, MV_LEN), np.float32)

    def endpoint(self, rank: int) -> "LoopbackEndpoint":
        return LoopbackEndpoint(self, rank)


class LoopbackEndpoint:
    def __init__(self, fabric: LoopbackFabric, rank: int):
        assert 0 <= rank < fabric.world
        self.fabric = fabric
        self.rank = int(rank)
        self.world = fabric.world
        self._wasted = 0

    def take_wasted(self) -> int:
        """Bytes this endpoint received without being addressed since
        the last call — the ``router/handoff_wasted_bytes`` feed."""
        w, self._wasted = self._wasted, 0
        return w

    def exchange(self, out, metrics):
        fab = self.fabric
        fab._metrics[self.rank] = np.asarray(  # sync-ok: host metrics vec
            metrics, np.float32).reshape(MV_LEN)
        for dst, buf in out:
            for frame in decode_frames(buf):
                if fab.addressing == "broadcast" or dst < 0:
                    dsts = range(fab.world)
                else:
                    dsts = (int(dst),)
                for r in dsts:
                    if r != self.rank:
                        fab._inbox[r].append(frame)
        inbox = fab._inbox[self.rank]
        frames = []
        for _ in range(len(inbox)):
            frame = inbox.popleft()
            if frame["dst"] < 0 or frame["dst"] == self.rank:
                frames.append(frame)
            else:
                self._wasted += frame_nbytes(frame)
        return frames, fab._metrics.copy()


class ProcessEndpoint:
    """The real thing: frames + metrics cross processes through the
    aligned exchange (see module docstring). Every rank MUST call
    :meth:`exchange` at the same loop point every tick — the fence
    discipline is what makes the fabric deadlock-free. ``out`` is a
    list of ``(dst, frame bytes)``: with ``addressing="targeted"``
    dst>=0 frames ride the point-to-point payload leg (lazy
    :class:`~deepspeed_tpu.utils.distributed.PeerFabric`, created at
    the first exchange — an aligned point every rank reaches
    together); ``addressing="broadcast"`` is the PR-17 legacy
    single-allgather shape."""

    def __init__(self, addressing: str = "targeted",
                 payload_timeout_s: float = 60.0):
        import jax
        assert addressing in ("targeted", "broadcast"), addressing
        self.rank = int(jax.process_index())
        self.world = int(jax.process_count())
        self.addressing = addressing
        self.payload_timeout_s = float(payload_timeout_s)  # sync-ok: cfg
        self._fabric = None
        self._wasted = 0

    def take_wasted(self) -> int:
        w, self._wasted = self._wasted, 0
        return w

    def fabric_health(self) -> dict:
        """Targeted-fabric liveness for /healthz (ISSUE 19 satellite):
        the :class:`PeerFabric`'s per-peer connected flags +
        last-payload ages. Before the fabric's lazy construction (or
        under broadcast addressing, which has no point-to-point leg)
        the doc says so instead of faking peers."""
        if self._fabric is None:
            return {"fabric": {"built": False,
                               "addressing": self.addressing}}
        return {"fabric": dict(self._fabric.liveness(), built=True,
                               addressing=self.addressing)}

    def _filter(self, bufs, me, pad):
        """Broadcast-leg intake: keep frames addressed here (or to
        all), count everything else — mis-addressed frames and the
        padding peers forced onto this rank — as wasted wire bytes."""
        frames = []
        for r, buf in enumerate(bufs):
            if r == me:
                continue
            self._wasted += max(pad - len(buf), 0)
            for frame in decode_frames(buf):
                if frame["dst"] < 0 or frame["dst"] == me:
                    frames.append(frame)
                else:
                    self._wasted += frame_nbytes(frame)
        return frames

    def exchange(self, out, metrics):
        meta = np.asarray(metrics, np.float32).reshape(
            MV_LEN)   # sync-ok: metrics vector is host-built numpy
        if self.addressing == "broadcast":
            from deepspeed_tpu.utils.distributed import \
                allgather_host_bytes
            bufs, mat, me = allgather_host_bytes(
                b"".join(buf for _dst, buf in out),  # sync-ok: wire hop
                meta=meta)
            pad = max((len(b) for b in bufs), default=0)
            return self._filter(bufs, me, pad), mat
        from deepspeed_tpu.utils.distributed import (
            PeerFabric, exchange_host_bytes_targeted)
        if self._fabric is None:
            # collective construction (listener-address allgather) at
            # the first exchange — a point every rank reaches together
            self._fabric = PeerFabric(timeout_s=self.payload_timeout_s)
        bcast, by_dst = [], {}
        for dst, buf in out:
            if dst < 0:
                bcast.append(buf)
            else:
                assert dst != self.rank, "frame addressed to self"
                by_dst[int(dst)] = by_dst.get(int(dst), b"") + buf
        bufs, incoming, mat, me, pad = exchange_host_bytes_targeted(
            b"".join(bcast), by_dst, meta=meta,  # sync-ok: wire hop
            fabric=self._fabric)
        frames = self._filter(bufs, me, pad)
        for src in sorted(incoming):
            frames.extend(decode_frames(incoming[src]))
        return frames, mat


# ---------------------------------------------------------- role nodes

class DecodeNode:
    """Decode-role rank: land packets, tick the engine, ship "done"
    streams back to the router rank. ``on_tick(node)`` runs once per
    exchange loop (heartbeat files, fault hooks); ``on_absorb(node)``
    after each successful delivery (the SIGKILL-mid-stream fault test
    arms its kill there)."""

    def __init__(self, engine, endpoint, registry=None, recorder=None,
                 decode_ticks: int = 4, on_tick=None, on_absorb=None):
        from deepspeed_tpu.telemetry.recorder import default_recorder
        from deepspeed_tpu.telemetry.registry import MetricsRegistry
        assert engine.role in ("decode", "both"), engine.role
        self.engine = engine
        self.endpoint = endpoint
        self.metrics = registry if registry is not None \
            else MetricsRegistry()
        self.recorder = recorder if recorder is not None \
            else default_recorder()
        self.decode_ticks = int(decode_ticks)
        self.on_tick = on_tick
        self.on_absorb = on_absorb
        self._waiting: deque = deque()   # packets waiting on a slot
        self._outbox: List = []          # (dst, frame bytes) pairs
        self.absorbed_pages = 0
        self.done_count = 0
        self.stats = {"delivered": 0, "nacked": 0, "bytes_recv": 0,
                      "wasted_bytes": 0, "decode_busy_s": 0.0,
                      "slot_busy_ticks": 0, "slot_cap_ticks": 0}

    def _vec(self):
        cb = self.engine
        v = np.zeros(MV_LEN, np.float32)
        v[MV_ROLE] = 1.0
        v[MV_FREE_PAGES] = cb.cache.available_pages
        v[MV_FREE_SLOTS] = sum(not s.active for s in cb.slots)
        v[MV_ABSORBED_PAGES] = self.absorbed_pages
        v[MV_DONE] = self.done_count
        # remaining-decode estimate: tokens still owed by active slots
        # plus everything parked in the waiting queue — what the
        # router's LPT placement minimizes across decode ranks
        rem = 0
        for s in cb.slots:
            if s.active and s.request is not None:
                rem += max(int(s.request.max_new_tokens)
                           - len(s.request.generated), 0)
        for frame in self._waiting:
            doc = frame["doc"]
            rem += max(int(doc["max_new_tokens"])
                       - len(doc["generated"]), 0)
        v[MV_REMAINING] = rem
        # the SLO plane's decode-latency feed (ISSUE 19): the engine's
        # most recent tick latency, already a host scalar (the token
        # readback fenced it) — peek, never create, so an idle rank
        # publishes 0 instead of seeding a phantom histogram
        tick_s = cb.metrics.peek_histogram_last("serving/tick_latency_s")
        v[MV_TICK_S] = tick_s or 0.0
        return v

    def _note_wasted(self):
        take = getattr(self.endpoint, "take_wasted", None)
        if take is None:
            return
        wasted = int(take())
        if wasted:
            self.stats["wasted_bytes"] += wasted
            self.metrics.counter("router/handoff_wasted_bytes").inc(
                wasted)

    def _try_deliver(self, frame, out_bufs) -> bool:
        """True when the packet landed or was nacked (consumed);
        False = no slot/pages free yet, caller keeps it waiting."""
        from deepspeed_tpu.runtime.elastic import faults
        from deepspeed_tpu.serving.router import deliver_handoff
        packet = packet_from_frame(frame)
        try:
            slot = deliver_handoff(self.engine, packet,
                                   dedupe=self.engine.prefix_cache)
        except faults.SimulatedCrash as e:
            # admission already unwound inside deliver_handoff; the
            # gathered bytes are suspect — nack with the wire doc so
            # the router replays from the committed stream, bounded
            self.stats["nacked"] += 1
            out_bufs.append((frame["src"], encode_frame(
                "nack", dict(packet.doc, error=str(e)),
                src=self.endpoint.rank, dst=frame["src"])))
            return True
        if slot is None:
            return False
        self.stats["delivered"] += 1
        self.absorbed_pages += int(packet.doc["n_data_pages"])
        if self.on_absorb is not None:
            self.on_absorb(self)
        return True

    def tick(self):
        """One exchange / deliver / decode iteration; returns the
        exchanged metrics matrix (callers check ``mat[0, MV_STOP]``).
        :meth:`run` loops this, and the loopback tests drive it
        directly — same code path either way."""
        t_coll = time.monotonic()
        frames, mat = self.endpoint.exchange(self._outbox, self._vec())
        self.engine.metrics.histogram(
            "serving/transport_collective_s").observe(
            time.monotonic() - t_coll)
        self._outbox = []
        self._note_wasted()
        for frame in frames:
            if frame["kind"] != "packet":
                continue
            nb = frame_nbytes(frame)
            self.stats["bytes_recv"] += nb
            self.metrics.counter("router/handoff_bytes_recv").inc(nb)
            self._waiting.append(frame)
        # deliver in arrival order; stop at the first packet the
        # pool cannot take yet (later ones would jump the queue)
        while self._waiting:
            if not self._try_deliver(self._waiting[0], self._outbox):
                break
            self._waiting.popleft()
        cb = self.engine
        # busy time is THIS THREAD's CPU seconds, not wall clock and
        # not process CPU: on the shared-core harness several decode
        # ranks time-slice one core, so a wall clock bills each rank
        # for slices it spent descheduled, and process CPU bills the
        # XLA pool threads' post-collective spin-wait (which grows
        # with wall time, i.e. with world size). The scheduler thread
        # drives every decode step, so its own CPU measures the
        # per-rank capacity a one-host-per-rank deployment would see
        t_busy = time.thread_time()
        stepped = False
        for _tick in range(self.decode_ticks):
            active = sum(s.active for s in cb.slots)
            self.stats["slot_busy_ticks"] += active
            if not active:
                break
            stepped = True
            for req in cb.step():
                self.done_count += 1
                self._outbox.append((0, encode_frame(
                    "done",
                    {"rid": req.rid,
                     "tokens": [int(t) for t in req.tokens()],
                     "finish_reason": req.finish_reason,
                     "trace_id": getattr(req, "trace_id", None),
                     "span_id": getattr(req, "span_id", None),
                     "generated": len(req.generated)},
                    src=self.endpoint.rank, dst=0)))
        # slot-utilization denominator counts the FULL decode budget of
        # the tick (idle ticks show as low utilization,
        # honestly), busy time only what actually stepped
        self.stats["slot_cap_ticks"] += len(cb.slots) * self.decode_ticks
        if stepped:
            self.stats["decode_busy_s"] += time.thread_time() - t_busy
        if self.on_tick is not None:
            self.on_tick(self)
        return mat

    def run(self, max_ticks: int = 200000) -> dict:
        """Exchange/deliver/tick until rank 0 raises MV_STOP (seen by
        every rank at the same aligned exchange). Returns stats."""
        for _ in range(max_ticks):
            mat = self.tick()
            if mat[0, MV_STOP]:
                break
        return dict(self.stats, absorbed_pages=self.absorbed_pages,
                    done=self.done_count)


class PrefillNode:
    """Prefill-role rank 0 — the router lives here: admission gated by
    ``max_inflight_pages`` (extracted-but-unabsorbed KV, estimated
    from cumulative sent pages minus the decode ranks' exchanged
    ``MV_ABSORBED_PAGES``), prefill steps, extract/encode/send, and
    "done"/"nack" intake with bounded replay from the wire doc —
    the same recovery semantics as
    :meth:`DisaggRouter._requeue_lost_packet`."""

    def __init__(self, engines, endpoint, registry=None, recorder=None,
                 max_inflight_pages: Optional[int] = None,
                 max_inflight_pages_per_rank: Optional[int] = None,
                 max_handoff_retries: int = 3, on_tick=None,
                 on_done=None):
        from deepspeed_tpu.telemetry.recorder import default_recorder
        from deepspeed_tpu.telemetry.registry import MetricsRegistry
        assert engines, "need at least one prefill-role engine"
        for cb in engines:
            assert cb.role == "prefill", cb.role
        self.engines = list(engines)
        self.endpoint = endpoint
        self.metrics = registry if registry is not None \
            else MetricsRegistry()
        self.recorder = recorder if recorder is not None \
            else default_recorder()
        self.max_handoff_retries = int(max_handoff_retries)
        self.max_inflight_pages = None if max_inflight_pages is None \
            else int(max_inflight_pages)
        self.on_tick = on_tick
        self.on_done = on_done
        self.decode_ranks = [r for r in range(endpoint.world)
                             if r != endpoint.rank]
        # per-rank send-time backpressure: default = the aggregate
        # bound split evenly across decode ranks, so one slow rank
        # cannot monopolize the whole inflight budget
        if max_inflight_pages_per_rank is not None:
            self.max_inflight_pages_per_rank = int(
                max_inflight_pages_per_rank)
        elif self.max_inflight_pages is not None:
            self.max_inflight_pages_per_rank = max(
                self.max_inflight_pages // max(len(self.decode_ranks), 1),
                1)
        else:
            self.max_inflight_pages_per_rank = None
        self.queue: deque = deque()
        self._packets: deque = deque()     # extracted, not yet sent
        self._attempts: Dict[Any, int] = {}
        self._sent_pages = {r: 0 for r in self.decode_ranks}
        self._submitted = 0
        self._block_latched = False
        self._rank_blocked = {r: False for r in self.decode_ranks}
        self._host_rng = np.random.RandomState(0)
        self.done: Dict[Any, dict] = {}    # rid -> done doc
        self.lost: Dict[Any, dict] = {}
        self.stats = {"routed": 0, "handoffs": 0, "handoff_requeues": 0,
                      "decode_blocked": 0, "lost": 0, "bytes_sent": 0,
                      "wasted_bytes": 0, "slot_busy_ticks": 0,
                      "slot_cap_ticks": 0}
        # ISSUE 19: the rank-0 SLO plane (telemetry/slo.py), attached
        # by build_transport_node when monitor.slo asks for it. Fed +
        # exported once per aligned exchange — prefill-role TTFT
        # segments from the local registries, decode-role tick latency
        # from every decode rank's MV_TICK_S slot
        self.slo = None

    # ------------------------------------------------------------ intake

    def submit(self, request) -> None:
        from deepspeed_tpu.serving.engine import ensure_trace_id
        ensure_trace_id(request)
        if request.temperature and request.temperature > 0 \
                and getattr(request, "sample_key", None) is None:
            request.sample_key = int(
                self._host_rng.randint(0, 2 ** 31 - 1))  # sync-ok: host
        if getattr(request, "_t_arrived", None) is None:
            request._t_arrived = time.monotonic()
        self._attempts.setdefault(request.rid, 0)
        self._submitted += 1
        self.queue.append(request)
        self.metrics.gauge("router/queue_depth").set(len(self.queue))

    # -------------------------------------------------------- accounting

    def _inflight_pages(self, mat) -> int:
        """Pages committed to the handoff pipeline but not absorbed by
        a decode pool: on-the-wire sends minus the exchanged absorbed
        counters, extracted-unsent packets, and everything routed into
        a prefill engine (those become packets next sweep)."""
        n = sum(self._sent_pages[r]
                - int(mat[r, MV_ABSORBED_PAGES])
                for r in self.decode_ranks)
        n += sum(int(p.doc["n_data_pages"]) for p in self._packets)
        for pcb in self.engines:
            for r in pcb.queue:
                n += pcb.cache.pages_needed(
                    int(np.asarray(r.prompt).shape[0]))  # sync-ok: host
            for s in pcb.slots:
                if s.active:
                    n += pcb.cache.pages_needed(max(s.pos, 1))
        return n

    def _route_admissions(self, mat) -> None:
        while self.queue:
            req = self.queue[0]
            if self.max_inflight_pages is not None:
                need = self.engines[0].cache.pages_needed(
                    int(np.asarray(req.prompt).shape[0]))  # sync-ok
                inflight = self._inflight_pages(mat)
                if inflight + need > self.max_inflight_pages:
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
            self._block_latched = False
            self.queue.popleft()
            loads = [len(cb.queue) + sum(s.active for s in cb.slots)
                     for cb in self.engines]
            pidx = int(np.argmin(loads))   # sync-ok: host scores
            self.stats["routed"] += 1
            self.metrics.counter("router/slo_routed").inc()
            self.recorder.record(
                "router_route", rid=req.rid, trace=req.trace_id,
                engine=self.engines[pidx].replica_id, reason="slo")
            self.engines[pidx].submit(req)
        self.metrics.gauge("router/queue_depth").set(len(self.queue))

    # ----------------------------------------------------------- handoff

    def _requeue(self, doc, error) -> None:
        from deepspeed_tpu.serving import elastic
        rid = doc["rid"]
        self.stats["handoff_requeues"] += 1
        self.metrics.counter("router/handoff_requeues").inc()
        self._attempts[rid] = self._attempts.get(rid, 0) + 1
        if self._attempts[rid] > self.max_handoff_retries:
            self.stats["lost"] += 1
            self.lost[rid] = doc
            self.recorder.record(
                "serving_requeue", rid=rid, trace=doc.get("trace_id"),
                outcome="dropped", attempts=self._attempts[rid])
            logger.warning(f"request {rid!r} dropped after "
                           f"{self._attempts[rid] - 1} handoff retries")
            return
        replay = elastic.resume_request(doc)
        self.recorder.record(
            "serving_requeue", rid=rid, trace=doc.get("trace_id"),
            outcome="scheduled", attempts=self._attempts[rid],
            committed=len(doc["generated"]))
        logger.warning(f"cross-process handoff of {rid!r} failed "
                       f"({error}); replaying from the committed stream")
        self.queue.appendleft(replay)

    def _sweep_and_send(self, mat, out_bufs) -> None:
        from deepspeed_tpu.runtime.elastic import faults
        from deepspeed_tpu.serving.router import extract_handoff
        for pcb in self.engines:
            for slot_id, slot in enumerate(pcb.slots):
                if not slot.active:
                    continue
                packet = extract_handoff(pcb, slot_id)
                try:
                    faults.fire("serving_handoff", rid=packet.rid)
                except faults.SimulatedCrash as e:
                    self._requeue(packet.doc, e)
                    continue
                self._packets.append(packet)
        # LPT placement (ISSUE 18): longest-remaining packet first onto
        # the decode rank with the least estimated remaining work (the
        # exchanged MV_REMAINING plus its sent-but-unacknowledged pages
        # as the in-flight lag proxy), subject to the per-rank
        # inflight-pages cap. A rank with no free slot still accepts a
        # frame into its waiting queue (the pages stay counted as
        # inflight here until MV_ABSORBED_PAGES acknowledges them); a
        # packet NO rank can take stays queued HERE — per-rank
        # backpressure at the router — and each refusing rank latches
        # one decode_blocked per episode.
        def _rem(p):
            return max(int(p.doc["max_new_tokens"])
                       - len(p.doc["generated"]), 0)

        unabsorbed = {r: self._sent_pages[r]
                      - int(mat[r, MV_ABSORBED_PAGES])
                      for r in self.decode_ranks}
        load = {r: float(mat[r, MV_REMAINING]) + unabsorbed[r]
                for r in self.decode_ranks}   # sync-ok: mat is the
        #                                       host metrics matrix
        cap = self.max_inflight_pages_per_rank
        held: deque = deque()
        for packet in sorted(self._packets, key=_rem, reverse=True):
            need = int(packet.doc["n_data_pages"])
            if cap is None:
                eligible = self.decode_ranks
            else:
                # an oversized packet (need > cap) may still go to a
                # fully-acknowledged rank: the cap is backpressure,
                # not a validator, and holding it forever would wedge
                eligible = [r for r in self.decode_ranks
                            if unabsorbed[r] + need <= cap
                            or unabsorbed[r] == 0]
            if not eligible:
                for r in self.decode_ranks:
                    self._latch_rank_block(r, packet, unabsorbed[r])
                held.append(packet)
                continue
            dst = min(eligible, key=lambda r: (
                load[r], -float(mat[r, MV_FREE_PAGES]),
                r))   # sync-ok: host metrics matrix, no device read
            self._rank_blocked[dst] = False   # headroom proven: re-arm
            # ISSUE 19: the encode leg gets its own span, child of the
            # handoff span, SHIPPED IN THE DOC before encoding — the
            # receiving rank's handoff_in parents onto it, so the
            # cross-process hop is one connected edge in the merged tree
            from deepspeed_tpu.telemetry.spans import new_span_id
            enc_span = new_span_id()
            packet.doc["encode_span"] = enc_span
            t_enc = time.monotonic()
            buf = encode_frame("packet", packet.doc, packet.kv,
                               src=self.endpoint.rank, dst=dst)
            enc_s = time.monotonic() - t_enc
            self.engines[0].metrics.histogram(
                "serving/transport_encode_s").observe(enc_s)
            self.recorder.record(
                "transport_encode", rid=packet.doc["rid"],
                trace=packet.doc.get("trace_id"), dst=dst,
                nbytes=len(buf), dur_s=enc_s, span_id=enc_span,
                parent_span=packet.doc.get("handoff_span"))
            out_bufs.append((dst, buf))
            self._sent_pages[dst] += need
            unabsorbed[dst] += need
            load[dst] += _rem(packet)
            self.stats["handoffs"] += 1
            self.stats["bytes_sent"] += len(buf)
            self.metrics.counter("router/handoffs").inc()
            self.metrics.counter("router/handoff_bytes_sent").inc(
                len(buf))
        self._packets = held
        self.metrics.gauge("router/inflight_pages").set(
            self._inflight_pages(mat))

    def _latch_rank_block(self, rank, packet, unabsorbed) -> None:
        """One decode_blocked per REFUSING RANK per episode (the
        admission latch's per-rank sibling): a held packet re-checks
        every sweep, and counting each re-check would flood the
        bounded ring at tick rate under sustained pressure."""
        if self._rank_blocked[rank]:
            return
        self._rank_blocked[rank] = True
        self.stats["decode_blocked"] += 1
        self.metrics.counter("router/decode_blocked").inc()
        self.recorder.record(
            "router_block", rid=packet.doc["rid"],
            trace=packet.doc.get("trace_id"), rank=rank,
            need_pages=int(packet.doc["n_data_pages"]),
            inflight_pages=int(unabsorbed),
            queue_depth=len(self._packets))

    def _note_wasted(self) -> None:
        take = getattr(self.endpoint, "take_wasted", None)
        if take is None:
            return
        wasted = int(take())
        if wasted:
            self.stats["wasted_bytes"] += wasted
            self.metrics.counter("router/handoff_wasted_bytes").inc(
                wasted)

    # the prefill-role window sources: (slo metric, registry histogram)
    _SLO_FEEDS = (
        ("ttft_s", "serving/ttft_s"),
        ("queue_wait_s", "serving/ttft_queue_wait_s"),
        ("transport_s", "serving/transport_encode_s"),
        ("transport_s", "serving/transport_collective_s"),
    )

    def _feed_slo(self, mat) -> None:
        """One SLO-plane update per aligned exchange (ISSUE 19): new
        prefill-side histogram tails under role ``prefill``, each
        decode rank's exchanged tick latency under role ``decode``
        (a per-exchange SAMPLE of that rank's current latency — the
        cadence every other backpressure signal already rides), then
        re-export the ``slo/*`` gauges. Host floats only."""
        plane = self.slo
        if plane is None:
            return
        for cb in self.engines:
            reg = cb.metrics
            for metric, src in self._SLO_FEEDS:
                n = reg.peek_histogram_count(src)
                if n:
                    plane.feed_counted(
                        "prefill", metric,
                        reg.peek_histogram_values(src), n,
                        source=f"{cb.replica_id}:{src}")
        for r in self.decode_ranks:
            if mat[r, MV_ROLE] and mat[r, MV_TICK_S] > 0:
                plane.observe("decode", "tick_s",
                              float(mat[r, MV_TICK_S]))   # sync-ok: host metrics matrix
        plane.export(self.metrics)

    def _finish(self, doc) -> None:
        from deepspeed_tpu.telemetry.spans import new_span_id
        self.done[doc["rid"]] = doc
        # the router rank is the completion authority: its ring closes
        # every trace even when a decode rank's ring died with it —
        # the close parents straight onto the request ROOT (doc-borne),
        # never onto a decode-rank span that may not have been dumped
        self.recorder.record(
            "finish", rid=doc["rid"], trace=doc.get("trace_id"),
            reason=doc.get("finish_reason"),
            generated=doc.get("generated"),
            span_id=new_span_id(),
            parent_span=doc.get("span_id"))
        if self.on_done is not None:
            self.on_done(doc)

    # -------------------------------------------------------------- loop

    def serve(self, requests, max_ticks: int = 200000) -> Dict[Any, dict]:
        """Serve every request to completion (or bounded loss) across
        the fabric; returns ``{rid: done doc}`` with the FULL token
        stream per request. Finishes that never left the prefill rank
        (max_new_tokens == 1 / instant EOS) complete locally."""
        for r in requests:
            self.submit(r)
        out_bufs: List = []   # (dst, frame bytes) pairs
        mat = np.zeros((self.endpoint.world, MV_LEN), np.float32)
        for _ in range(max_ticks):
            self._route_admissions(mat)
            for pcb in self.engines:
                for req in pcb.step():
                    self._finish({
                        "rid": req.rid,
                        "tokens": [int(t) for t in req.tokens()],
                        "finish_reason": req.finish_reason,
                        "trace_id": getattr(req, "trace_id", None),
                        "span_id": getattr(req, "span_id", None),
                        "generated": len(req.generated)})
                # occupancy is sampled AFTER the step and BEFORE the
                # sweep extracts the active slots into packets — the
                # only point in the tick where prefill work is visible
                self.stats["slot_busy_ticks"] += sum(
                    s.active for s in pcb.slots)
                self.stats["slot_cap_ticks"] += len(pcb.slots)
            self._sweep_and_send(mat, out_bufs)
            t_coll = time.monotonic()
            frames, mat = self.endpoint.exchange(out_bufs, self._vec(0.0))
            self.engines[0].metrics.histogram(
                "serving/transport_collective_s").observe(
                time.monotonic() - t_coll)
            self._feed_slo(mat)
            self._note_wasted()
            out_bufs = []
            for frame in frames:
                if frame["kind"] == "done":
                    self._finish(frame["doc"])
                elif frame["kind"] == "nack":
                    self._requeue(frame["doc"],
                                  frame["doc"].get("error", "nack"))
            if self.on_tick is not None:
                self.on_tick(self)
            if len(self.done) + len(self.lost) >= self._submitted \
                    and not self.queue and not self._packets:
                break
        # one final aligned exchange raises MV_STOP: every decode rank
        # sees it at the same tick and leaves its loop — no straggler
        # ever blocks alone inside a collective
        self.endpoint.exchange([], self._vec(1.0))
        return dict(self.done)

    def _vec(self, stop: float):
        v = np.zeros(MV_LEN, np.float32)
        v[MV_ROLE] = 0.0
        v[MV_STOP] = stop
        v[MV_DONE] = len(self.done)
        return v
