"""The serving driver: one thread steps the engine and stamps its tokens.

``eng.step()`` is called back to back and every request's new tokens are
stamped with the time ``step`` returned — that is when a client could have
them; the engine keeps no per-token times of its own. A *feeder* (the
traffic kind's) decides what is submitted when: the closed-loop kind
submits a client's next request when its last one ends.

Phases on one clock (``time.monotonic``): programs warmed and outputs
checked (set-up), warm-up traffic of the same mix (set-up), the measured
window and, for a traced run, a slice of ``trace_s`` under the profiler
with the traffic still running. Every phase changes on a step's return.
Nothing is drained: what is still running when the window closes is cut
by the window. A request FAILS when the engine ends it for another reason
than its length, or with another number of tokens than it asked for, or
loses it (submitted, never ended, and neither queued nor in a slot).
"""

import time

import numpy as np

from benchmark import harness, stats, traffic
from benchmark.harness import span


class Req:
    """The harness's own record of one request."""
    __slots__ = ("rid", "want", "request", "submitted", "n", "ended_t",
                 "reason")

    def __init__(self, rid, want, request):
        self.rid, self.want, self.request = rid, want, request
        self.submitted = self.ended_t = self.reason = None
        self.n = 0

    @property
    def ok(self):
        """Ended as asked: by its length, with the tokens it asked for."""
        return self.ended_t is not None and self.reason == "length" \
            and len(self.request.generated) == self.want


class Tracker:
    def __init__(self):
        self.reqs = {}            # rid -> Req
        self.deliveries = []      # (time, tokens) of every step's output

    def stamp(self, eng, finished, t):
        """Credit tokens that appeared since the last step to time t, and
        note how each finished request ended."""
        delivered = 0
        live = [s.request for s in eng.slots if s.request is not None]
        for request in live + list(finished):
            rec = self.reqs.get(request.rid)
            if rec is None:
                continue
            n = len(request.generated)
            if n > rec.n:
                delivered += n - rec.n
                rec.n = n
        for request in finished:
            rec = self.reqs.get(request.rid)
            if rec is not None:
                rec.ended_t, rec.reason = t, request.finish_reason
        if delivered:
            self.deliveries.append((t, delivered))

    def lost(self, eng):
        """Requests that never ended and that the engine no longer holds."""
        held = {s.request.rid for s in eng.slots if s.request is not None}
        held |= {r.rid for r in eng.queue}
        return [r for r in self.reqs.values()
                if r.ended_t is None and r.rid not in held]


class Feeder:
    """What serving kinds' feeders share: the one way a request is handed
    to the engine and recorded. A kind adds ``start(t)`` and
    ``after_step(finished, t)``."""

    def __init__(self, eng, tracker):
        self.eng, self.tracker = eng, tracker

    def submit(self, request):
        rec = Req(request.rid, request.max_new_tokens, request)
        self.tracker.reqs[rec.rid] = rec
        with span("bench/submit"):
            self.eng.submit(request)
        rec.submitted = time.monotonic()


def warm_programs(eng, p, vocab):
    """Run every program the cell's traffic can reach, through the public
    API: one request per prefill bucket, the first long enough that its
    decode walks the tick step counts down from the largest (the engine
    takes the largest power of two within the remaining budget)."""
    import deepspeed_tpu.serving as serving
    P = eng.spec.page_size
    limit = min(eng.adapter.max_prompt_len(), eng.spec.max_tokens_per_slot())
    rng = np.random.default_rng(0)
    for i, pages in enumerate(p["prefill_page_buckets"]):
        new = 2 * max(p["tick_steps"]) if i == 0 else 1
        n = min((pages - 1) * P + max(1, P // 2), limit - new)
        prompt = rng.integers(0, vocab, size=n, dtype=np.int32)
        eng.serve([serving.Request(("warm", i), prompt, max_new_tokens=new)])


REGISTRY_HISTOGRAMS = (
    "serving/tick_latency_s", "serving/decode_latency_per_token_s",
    "serving/slot_utilization")


def build(ctx):
    """(engine, its registry, checks, detail): the engine built, every
    program warmed, the outputs held to the reference."""
    from deepspeed_tpu.telemetry.registry import MetricsRegistry
    p, config, family = ctx.traffic, ctx.config, ctx.family
    shapes = family.traffic_shapes(config, ctx.rehearse)
    scale = shapes["seq_scale"]
    vocab = min(p["token_below"], shapes["vocab_size"])
    registry = MetricsRegistry()
    with span("bench/build"):
        eng, params = family.build_serving(config, ctx.seed, ctx.rehearse,
                                           registry)
    harness.mark(ctx, "engine built, weights on the device")
    with span("bench/warm_programs"):
        warm_programs(eng, p, vocab)
    harness.mark(ctx, "programs warmed")
    with span("bench/reference"):
        prompts = traffic.sample_prompts(p, ctx.seed, p["check_prompts"],
                                         vocab, reserve=17, scale=scale)
        longest = p["prompt_tokens"].get("max") or p["prompt_tokens"]["value"]
        checks, detail = family.check_serving(
            config, eng, params, prompts, ctx.rehearse,
            pad_to=min(int(round(longest * scale)) + 16,
                       shapes["max_positions"]))
    harness.mark(ctx, "outputs checked against the reference")
    return eng, registry, checks, detail


def run(ctx, make_feeder):
    """Drive one serving cell. ``make_feeder(ctx, eng, tracker, shapes)``
    returns the kind's feeder; ``shapes`` is the family's
    ``traffic_shapes``."""
    p = ctx.traffic
    eng, registry, checks, detail = build(ctx)

    tracker = Tracker()
    record = harness.Record(**ctx.base)
    warmup_s = p["warmup_s"] * (0.2 if ctx.rehearse else 1.0)
    trace_s = p["trace_s"] if ctx.trace else 0.0
    feeder = make_feeder(ctx, eng, tracker,
                         ctx.family.traffic_shapes(ctx.config, ctx.rehearse))
    prof = harness.Profiler(ctx.tag) if ctx.trace else None

    # every phase changes on a step's return: window_open and t1 are edges
    t_warm = time.monotonic()
    window_open = t1 = t_trace = compiles0 = None
    phase = "warmup"
    feeder.start(t_warm)
    slice_steps = []
    while True:
        now = time.monotonic()
        if phase == "warmup" and now - t_warm >= warmup_s:
            # registry and counters start clean, the requests keep running
            phase, window_open = "window", now
            registry.reset()
            compiles0 = ctx.compiles.count
            record.setup_s = now - ctx.t_start
        elif phase == "window" and now - window_open >= ctx.seconds:
            t1 = now
            record.compiles_in_window = ctx.compiles.count - compiles0
            record.registry = {n: registry.peek_histogram_values(n)
                               for n in REGISTRY_HISTOGRAMS}
            record.extra["stats_at_close"] = dict(eng.stats)
            if prof is None:
                break
            phase = "trace"
            prof.start()
            t_trace = time.monotonic()
        elif phase == "trace" and now - t_trace >= trace_s:
            record.trace = prof.stop()
            break
        if not eng.pending:
            raise RuntimeError("the feeder left the engine without work")
        before = eng.stats["tick_steps"]
        with span("bench/eng_step"):
            finished = eng.step()
        t = time.monotonic()
        tracker.stamp(eng, finished, t)
        if phase == "trace":
            ends = [sl.pos for sl in eng.slots if sl.request is not None]
            ends += [len(r.prompt) + len(r.generated) - 1 for r in finished]
            slice_steps.append((eng.stats["tick_steps"] - before, ends))
        feeder.after_step(finished, t)
    lost = tracker.lost(eng)
    eng.drain()

    # judged: the requests in flight at some instant of the window
    window = [r for r in tracker.reqs.values() if r.submitted < t1
              and (r.ended_t is None or r.ended_t >= window_open)]
    ended = [r for r in window if r.ended_t is not None and r.ended_t < t1]
    bad = [r for r in ended if not r.ok] + [r for r in window if r in lost]
    record.window_s = t1 - window_open
    record.attempted, record.failed = len(window), len(bad)
    stamps, counts = zip(*tracker.deliveries) if tracker.deliveries \
        else ((), ())
    record.e2e["serve_tokens_per_s"] = stats.rate_in_window(
        stamps, counts, window_open, t1)
    record.samples["serve_tokens_per_s"] = [
        c for t, c in tracker.deliveries if window_open <= t < t1]
    contexts = [len(r.request.prompt) + r.n for r in window
                if r.ended_t is None]
    record.extra.update(
        requests_finished=sum(r.ok for r in ended),
        contexts_at_close_mean=sum(contexts) / max(1, len(contexts)),
        slice_steps=slice_steps, slots=eng.spec.slots,
        pool_shape=",".join(str(d) for d in eng.cache.pool[0].shape))
    if record.trace is not None:
        from benchmark import trace_reduce
        record.slice = trace_reduce.window_of(record.trace)
    record.memory_peak_bytes = harness.memory_peak_bytes(ctx.cell["chips"])
    checks["requests_ended_as_asked"] = not bad and bool(window)
    checks["no_compile_in_window"] = record.compiles_in_window == 0
    record.checks, record.detail = checks, detail
    return record
