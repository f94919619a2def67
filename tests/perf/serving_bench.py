"""Continuous batching vs static-batch serving throughput.

Workload: N requests with MIXED prompt lengths and mixed decode budgets,
arriving on a Poisson clock (exponential interarrivals at a rate that
keeps the queue saturated — the benchmark measures throughput, not an
idle arrival tail). Both systems serve the identical request trace:

- **continuous** (deepspeed_tpu/serving): slot scheduler + paged KV
  cache; a request admits the moment a slot and pages free up, so the
  chip never decodes padding for a finished request.
- **static baseline** (`models/gpt2_inference.generate`): requests gang
  into batches of ``slots`` in arrival order; every gang pads its
  prompts to the longest member and decodes the gang-max new-token
  budget before ANY member of the next gang starts — the cost model of
  the one-static-batch-per-call path. (Its outputs for the shorter
  members would additionally be wrong — right-padded prompts shift
  logits, the static path has no left-pad masking — so the baseline is
  charged only for its TIME, which is generous to it.)

Speedup = continuous requests/sec over static requests/sec; the mixed
decode budgets are where static batching bleeds (every short request
pays the gang's longest budget).

Run: ``python tests/perf/serving_bench.py`` (CPU ok; prints JSON).
"""

import json
import os
import sys
import time

import numpy as np

sys.path.insert(0, os.path.join(os.path.dirname(__file__), '..', '..'))


def _workload(rs, n_requests, prompt_lens, new_tokens, rate):
    """Poisson arrival trace over mixed lengths/budgets."""
    lens = rs.choice(prompt_lens, size=n_requests)
    news = rs.choice(new_tokens, size=n_requests)
    arrivals = np.cumsum(rs.exponential(1.0 / rate, size=n_requests))
    arrivals -= arrivals[0]            # first request is already queued
    return lens, news, arrivals


def run_serving_bench(n_requests=32, slots=4, seed=0,
                      prompt_lens=(8, 16, 32, 48),
                      new_tokens=(2, 4, 8, 96), rate=400.0,
                      page_size=32, max_pages_per_slot=5,
                      kv_cache_bits=0, model_cfg=None, params=None,
                      warm=True):
    """Returns {continuous: {...}, static: {...}, speedup_requests_per_sec}.

    ``model_cfg``/``params`` default to a small fp32 GPT-2 sized for CPU
    runs; pass a real config + converted params to measure on-chip."""
    import jax
    import jax.numpy as jnp
    from deepspeed_tpu.models.gpt2 import GPT2Config, GPT2LMHeadModel
    from deepspeed_tpu.models.gpt2_inference import generate
    import deepspeed_tpu.serving as serving

    rs = np.random.RandomState(seed)
    if model_cfg is None:
        # big enough that per-step MODEL compute (not interpret-mode /
        # dispatch constants) is what both systems spend their time on —
        # the regime the comparison is about
        model_cfg = GPT2Config(
            vocab_size=2048, n_positions=512, n_embd=256, n_layer=6,
            n_head=8, dtype=jnp.float32, param_dtype=jnp.float32,
            scan_layers=True)
    if params is None:
        params = jax.jit(GPT2LMHeadModel(model_cfg).init)(
            jax.random.PRNGKey(0), np.zeros((1, 8), np.int32))["params"]

    lens, news, arrivals = _workload(rs, n_requests, prompt_lens,
                                     new_tokens, rate)
    prompts = [rs.randint(0, model_cfg.vocab_size,
                          size=(s,)).astype(np.int32) for s in lens]
    total_new = int(news.sum())

    def make_requests():
        return [serving.Request(i, prompts[i], max_new_tokens=int(news[i]),
                                arrival_time=float(arrivals[i]))
                for i in range(n_requests)]

    # ONE adapter for every window: compiled tick/prefill programs live
    # on the adapter, so fresh engines per window (clean scheduler/pool
    # state) still replay warm executables — a long-lived server's
    # steady state, which is what the benchmark measures
    shared = serving.build_engine(
        "gpt2", model_cfg, params,
        config={"serving": {"slots": slots, "page_size": page_size,
                            "max_pages_per_slot": max_pages_per_slot,
                            "kv_cache_bits": kv_cache_bits}})

    # watchdog rides the measured engine (ISSUE 6): TTFT-blowup /
    # pool-exhaustion trips surface in the snapshot next to the TTFT
    # percentiles, so the bench record says whether the run was clean.
    # One dump SUBDIR per window: each window's Watchdog restarts its
    # dump_id at 1, so a shared dir would overwrite an earlier window's
    # incident with a later one's
    import tempfile
    from deepspeed_tpu.telemetry.anomaly import Watchdog
    wd_dump_dir = tempfile.mkdtemp(prefix="dstpu_flight_serving_")
    wd_window = [0]

    def run_continuous():
        wd_window[0] += 1
        eng = serving.ContinuousBatcher(
            shared.adapter,
            watchdog=Watchdog(
                os.path.join(wd_dump_dir, f"window{wd_window[0]}"),
                source="serving"))
        t0 = time.monotonic()
        res = eng.serve(make_requests(), respect_arrival_times=True)
        dt = time.monotonic() - t0
        assert len(res) == n_requests
        return dt, eng.stats, eng.metrics_snapshot()

    # one cache length for every static gang → one compiled decode_scan
    max_out = int(np.max(lens)) + int(news.max())
    max_out = min(model_cfg.n_positions, -(-max_out // 64) * 64)

    def run_static():
        # gangs in arrival order; a gang launches once its LAST member
        # has arrived (static batching gathers a full batch first)
        order = np.argsort(arrivals, kind="stable")
        t0 = time.monotonic()
        for g in range(0, n_requests, slots):
            gang = order[g:g + slots]
            gate = float(arrivals[gang].max())
            while time.monotonic() - t0 < gate:
                time.sleep(min(gate - (time.monotonic() - t0), 0.02))
            S = int(max(lens[i] for i in gang))
            batch = np.zeros((len(gang), S), np.int32)
            for row, i in enumerate(gang):
                batch[row, :lens[i]] = prompts[i]      # right-pad: the
                # static path's only option — and part of why it loses
            steps = int(max(news[i] for i in gang))
            toks = generate(model_cfg, params, batch, max_new_tokens=steps,
                            max_out_tokens=max_out)
            float(jax.device_get(toks[0, -1]))         # fence

        return time.monotonic() - t0

    if warm:
        # compile both systems outside the timed windows
        run_continuous()
        run_static()
    # best of three INTERLEAVED window pairs: the host shows ±15%
    # run-to-run noise and the comparison should report the scheduler,
    # not which system a descheduling blip landed on (same rule as
    # bench.py's 3-window MFU)
    dt_c, stats, telemetry = run_continuous()
    dt_s = run_static()
    for _ in range(2):
        dt_c2, stats2, telemetry2 = run_continuous()
        if dt_c2 < dt_c:
            dt_c, stats, telemetry = dt_c2, stats2, telemetry2
        dt_s = min(dt_s, run_static())

    out = {
        "workload": {
            "n_requests": n_requests, "slots": slots,
            "prompt_lens": list(map(int, prompt_lens)),
            "new_tokens": list(map(int, new_tokens)),
            "total_decode_tokens": total_new,
            "poisson_rate_per_s": rate,
        },
        "continuous": {
            "requests_per_sec": round(n_requests / dt_c, 2),
            "decode_tokens_per_sec": round(total_new / dt_c, 1),
            "wall_s": round(dt_c, 3),
            "tick_dispatches": stats["ticks"],
            "tick_steps": stats["tick_steps"],
            "mean_slot_occupancy": round(
                stats["decode_tokens"] / max(stats["tick_steps"], 1), 2),
            # the serving engine's own metrics (TTFT, admission wait,
            # tick latency, page-pool occupancy HWM — the winning
            # window's snapshot)
            "telemetry": telemetry,
        },
        "static": {
            "requests_per_sec": round(n_requests / dt_s, 2),
            "decode_tokens_per_sec": round(total_new / dt_s, 1),
            "wall_s": round(dt_s, 3),
        },
        "speedup_requests_per_sec": round(dt_s / dt_c, 2),
    }
    return out


def run_hot_prefix_bench(n_requests=16, slots=2, seed=0, sys_prompt_len=150,
                         unique_len=6, max_new=8, page_size=16,
                         max_pages_per_slot=16, model_cfg=None,
                         params=None):
    """Hot-prefix workload (ISSUE 9 satellite): N requests sharing an
    S-token system prompt (each with a short unique user suffix), served
    with the prefix cache OFF then ON. Records token-level
    prefix-hit-rate, pages-saved, and admission-to-first-token latency
    (TTFT — prefill is the dominant admission cost, and a prefix hit
    skips the shared span's compute entirely)."""
    import jax
    import jax.numpy as jnp
    from deepspeed_tpu.models.gpt2 import GPT2Config, GPT2LMHeadModel
    import deepspeed_tpu.serving as serving

    rs = np.random.RandomState(seed)
    if model_cfg is None:
        model_cfg = GPT2Config(
            vocab_size=2048, n_positions=512, n_embd=256, n_layer=6,
            n_head=8, dtype=jnp.float32, param_dtype=jnp.float32,
            scan_layers=True)
    if params is None:
        params = jax.jit(GPT2LMHeadModel(model_cfg).init)(
            jax.random.PRNGKey(0), np.zeros((1, 8), np.int32))["params"]
    sys_prompt = rs.randint(0, model_cfg.vocab_size,
                            size=(sys_prompt_len,)).astype(np.int32)
    prompts = [np.concatenate([sys_prompt, rs.randint(
        0, model_cfg.vocab_size, size=(unique_len,)).astype(np.int32)])
        for _ in range(n_requests)]

    def make_requests():
        return [serving.Request(i, prompts[i], max_new_tokens=max_new)
                for i in range(n_requests)]

    def run(prefix_on):
        sv = {"slots": slots, "page_size": page_size,
              "max_pages_per_slot": max_pages_per_slot}
        if prefix_on:
            sv["prefix_cache"] = {}
        eng = serving.build_engine("gpt2", model_cfg, params,
                                   config={"serving": sv})
        # warm the compiled programs: the SECOND identical-prompt
        # request drives the prefix-hit path (COW copy + suffix
        # prefill), so the measured window replays warm executables
        eng_warm = serving.ContinuousBatcher(eng.adapter,
                                             prefix_cache=prefix_on)
        eng_warm.serve([serving.Request("w", prompts[0],
                                        max_new_tokens=max_new)])
        if prefix_on:
            eng_warm.serve([serving.Request("w2", prompts[1],
                                            max_new_tokens=max_new)])
        eng = serving.ContinuousBatcher(eng.adapter,
                                        prefix_cache=prefix_on)
        t0 = time.monotonic()
        res = eng.serve(make_requests())
        dt = time.monotonic() - t0
        assert len(res) == n_requests
        snap = eng.metrics_snapshot()
        return dt, res, snap

    dt_off, res_off, snap_off = run(False)
    dt_on, res_on, snap_on = run(True)
    # prefix sharing must not change outputs
    mismatches = sum(
        res_on[i].tokens().tolist() != res_off[i].tokens().tolist()
        for i in range(n_requests))
    return {
        "workload": {
            "n_requests": n_requests, "slots": slots,
            "sys_prompt_len": sys_prompt_len, "unique_len": unique_len,
            "max_new_tokens": max_new, "page_size": page_size,
        },
        "prefix_hit_rate": round(
            snap_on["prefix_cache"]["hit_rate"], 4),
        "pages_saved": snap_on["prefix_cache"]["pages_saved"],
        "cow_hits": snap_on["prefix_cache"].get("cow_hits", 0),
        "evictions": snap_on["prefix_cache"].get("evictions", 0),
        "token_mismatches": mismatches,
        # admission-to-first-token latency: the prefill skip is the win
        "ttft_p50_s_off": snap_off["ttft_s"].get("p50"),
        "ttft_p50_s_on": snap_on["ttft_s"].get("p50"),
        "ttft_p99_s_off": snap_off["ttft_s"].get("p99"),
        "ttft_p99_s_on": snap_on["ttft_s"].get("p99"),
        "wall_s_off": round(dt_off, 3),
        "wall_s_on": round(dt_on, 3),
        "wall_speedup": round(dt_off / dt_on, 2) if dt_on > 0 else None,
    }


def run_spec_decode_bench(seed=0, prompt_len=32, max_new=96,
                          spec_tokens=3, page_size=16,
                          max_pages_per_slot=16, kv_cache_bits=0,
                          model_cfg=None, params=None, best_of=3):
    """Speculative-decode b1 throughput: ONE greedy request decoded by
    the plain engine vs the speculative engine (n-gram self-drafting, no
    second checkpoint). Outputs are asserted token-for-token identical;
    speedup = plain wall / spec wall. The n-gram drafter wins on
    repetitive continuations — greedy decode of a small model settles
    into loops, the same regime the multi-step tick's EOS cap already
    exploits — and the verify dispatch prices K tokens at ~one tick of
    host/dispatch overhead."""
    import jax
    import jax.numpy as jnp
    from deepspeed_tpu.models.gpt2 import GPT2Config, GPT2LMHeadModel
    import deepspeed_tpu.serving as serving

    rs = np.random.RandomState(seed)
    if model_cfg is None:
        model_cfg = GPT2Config(
            vocab_size=2048, n_positions=512, n_embd=256, n_layer=6,
            n_head=8, dtype=jnp.float32, param_dtype=jnp.float32,
            scan_layers=True)
    if params is None:
        params = jax.jit(GPT2LMHeadModel(model_cfg).init)(
            jax.random.PRNGKey(0), np.zeros((1, 8), np.int32))["params"]
    prompt = rs.randint(0, model_cfg.vocab_size,
                        size=(prompt_len,)).astype(np.int32)
    sv = {"slots": 1, "page_size": page_size,
          "max_pages_per_slot": max_pages_per_slot,
          "kv_cache_bits": kv_cache_bits}
    plain_proto = serving.build_engine("gpt2", model_cfg, params,
                                       config={"serving": sv})
    spec_proto = serving.build_engine(
        "gpt2", model_cfg, params,
        config={"serving": {**sv,
                            "speculative": {"tokens": spec_tokens}}})

    def run(proto, spec_on):
        from deepspeed_tpu.serving.drafter import NGramDrafter
        drafter = NGramDrafter(1) if spec_on else None
        eng = serving.ContinuousBatcher(proto.adapter, drafter=drafter,
                                        spec_tokens=spec_tokens)
        t0 = time.monotonic()
        res = eng.serve([serving.Request(0, prompt,
                                         max_new_tokens=max_new)])
        return time.monotonic() - t0, res[0].tokens(), \
            eng.metrics_snapshot()

    run(plain_proto, False)        # compile warmup
    run(spec_proto, True)
    dt_p, toks_p, _ = run(plain_proto, False)
    dt_s, toks_s, snap = run(spec_proto, True)
    for _ in range(best_of - 1):   # interleaved best-of windows (±15%
        dt_p = min(dt_p, run(plain_proto, False)[0])     # box noise)
        dt_s2, toks_s2, snap2 = run(spec_proto, True)
        if dt_s2 < dt_s:
            dt_s, snap = dt_s2, snap2
    identical = toks_p.tolist() == toks_s.tolist()
    return {
        "workload": {"prompt_len": prompt_len, "max_new": max_new,
                     "spec_tokens": spec_tokens, "b": 1,
                     "kv_cache_bits": kv_cache_bits},
        "tokens_identical": identical,
        "tok_per_s_plain": round(max_new / dt_p, 1),
        "tok_per_s_spec": round(max_new / dt_s, 1),
        "spec_decode_speedup": round(dt_p / dt_s, 2),
        "accept_rate": round(snap["speculative"]["accept_rate"], 3),
        "verify_rounds": snap["speculative"]["rounds"],
        "wall_s_plain": round(dt_p, 3),
        "wall_s_spec": round(dt_s, 3),
    }


def run_disagg_bench(n_requests=32, slots=4, seed=0,
                     prompt_lens=(8, 16, 32, 48),
                     new_tokens=(2, 4, 8, 96), rate=400.0,
                     page_size=32, max_pages_per_slot=5,
                     prefill_replicas=1, decode_replicas=1,
                     pool_factor=1, model_cfg=None, params=None,
                     warm=True, best_of=3):
    """Disaggregated prefill/decode vs the colocated engine
    (ISSUE 14): the SAME deterministic mixed-traffic workload (seeded
    lengths/budgets/arrivals — BENCH_r08's serving trace) served by

    - the colocated ``ContinuousBatcher`` (prefill competes with
      decode for slot residency: an arriving prompt waits for a long
      request to FINISH before it can prefill — the TTFT p99 vs p50
      head-of-line gap), and
    - a ``DisaggRouter`` over prefill-role + decode-role engines:
      every arrival prefills the moment a prefill slot frees (they
      free at handoff), so TTFT stops depending on decode residency.

    Every engine gets the SAME fully-provisioned pool
    (``pool_factor`` x slots x max_pages_per_slot + trash) so the
    comparison isolates the ROLE SPLIT, not pool size — this jax CPU
    backend implements no buffer donation, so every donated
    prefill/tick COPIES its pool and per-op cost grows linearly with
    num_blocks (a proxy artifact a real chip does not have; keep
    pool_factor=1 here). The disaggregation memory trade (KV of
    requests queued behind a decode slot) is carried OUTSIDE the pools
    by the in-flight packets, bounded by the router's
    ``max_inflight_pages``. Greedy outputs are asserted
    token-for-token identical across the handoff, and the leak fence
    (every pool drains to num_blocks - 1 after a sweep) must hold
    across every handoff the run performed."""
    import jax
    import jax.numpy as jnp
    from deepspeed_tpu.models.gpt2 import GPT2Config, GPT2LMHeadModel
    import deepspeed_tpu.serving as serving
    from deepspeed_tpu.serving.engine import ContinuousBatcher
    from deepspeed_tpu.serving.router import DisaggRouter

    rs = np.random.RandomState(seed)
    if model_cfg is None:
        model_cfg = GPT2Config(
            vocab_size=2048, n_positions=512, n_embd=256, n_layer=6,
            n_head=8, dtype=jnp.float32, param_dtype=jnp.float32,
            scan_layers=True)
    if params is None:
        params = jax.jit(GPT2LMHeadModel(model_cfg).init)(
            jax.random.PRNGKey(0), np.zeros((1, 8), np.int32))["params"]
    lens, news, arrivals = _workload(rs, n_requests, prompt_lens,
                                     new_tokens, rate)
    prompts = [rs.randint(0, model_cfg.vocab_size,
                          size=(s,)).astype(np.int32) for s in lens]
    total_new = int(news.sum())
    num_blocks = slots * max_pages_per_slot * pool_factor + 1

    def make_requests():
        return [serving.Request(i, prompts[i],
                                max_new_tokens=int(news[i]),
                                arrival_time=float(arrivals[i]))
                for i in range(n_requests)]

    # ONE adapter for every engine in every window (colocated AND both
    # roles): the compiled prefill/tick programs are shared, so each
    # window replays warm executables — the long-lived-server steady
    # state, and the disagg engines pay zero extra compile
    shared = serving.build_engine(
        "gpt2", model_cfg, params,
        config={"serving": {"slots": slots, "page_size": page_size,
                            "max_pages_per_slot": max_pages_per_slot,
                            "num_blocks": num_blocks}})
    adapter = shared.adapter

    def run_colocated():
        eng = ContinuousBatcher(adapter)
        t0 = time.monotonic()
        res = eng.serve(make_requests(), respect_arrival_times=True)
        dt = time.monotonic() - t0
        assert len(res) == n_requests
        return dt, res, eng.metrics_snapshot()

    def run_disagg():
        router = DisaggRouter(
            [ContinuousBatcher(adapter, role="prefill",
                               prefix_cache=True)
             for _ in range(prefill_replicas)],
            [ContinuousBatcher(adapter, role="decode",
                               prefix_cache=True)
             for _ in range(decode_replicas)])
        t0 = time.monotonic()
        res = router.run(make_requests(), respect_arrival_times=True)
        dt = time.monotonic() - t0
        assert len(res) == n_requests and not router.lost
        snap = router.metrics_snapshot()
        # leak fence: after the drained workload + a prefix sweep,
        # every engine's pool must hold its full allocatable count
        leak_ok = True
        for cb in router.prefill_engines + router.decode_engines:
            cb.cache.sweep_prefix_cache()
            leak_ok &= cb.cache.free_pages == cb.cache.num_blocks - 1
        return dt, res, snap, leak_ok

    if warm:
        run_colocated()
        run_disagg()
    dt_c, res_c, snap_c = run_colocated()
    dt_d, res_d, snap_d, leak_ok = run_disagg()
    # greedy outputs must be token-for-token identical across the
    # handoff — compared on the first measured pair
    mismatches = sum(
        res_d[i].tokens().tolist() != res_c[i].tokens().tolist()
        for i in range(n_requests))
    for _ in range(best_of - 1):   # interleaved best-of windows (±15%
        dt_c2, _res, snap_c2 = run_colocated()      # box noise)
        if dt_c2 < dt_c:
            dt_c, snap_c = dt_c2, snap_c2
        dt_d2, _res, snap_d2, leak2 = run_disagg()
        leak_ok &= leak2
        if dt_d2 < dt_d:
            dt_d, snap_d = dt_d2, snap_d2

    def bd(b):
        return {k: {kk: round(vv, 4) for kk, vv in v.items()
                    if isinstance(vv, float)}
                for k, v in b.items()}

    ttft_c = snap_c["ttft_s"]
    ttft_d = snap_d["ttft_s"]
    return {
        "workload": {
            "n_requests": n_requests, "slots": slots,
            "prompt_lens": list(map(int, prompt_lens)),
            "new_tokens": list(map(int, new_tokens)),
            "total_decode_tokens": total_new,
            "poisson_rate_per_s": rate, "seed": seed,
            "prefill_replicas": prefill_replicas,
            "decode_replicas": decode_replicas,
            "pool_blocks_per_engine": num_blocks,
        },
        "colocated": {
            "ttft_p50_s": ttft_c.get("p50"),
            "ttft_p99_s": ttft_c.get("p99"),
            "decode_tokens_per_sec": round(total_new / dt_c, 1),
            "wall_s": round(dt_c, 3),
            "ttft_breakdown": bd(snap_c["ttft_breakdown"]),
        },
        "disagg": {
            "ttft_p50_s": ttft_d.get("p50"),
            "ttft_p99_s": ttft_d.get("p99"),
            "decode_tokens_per_sec": round(total_new / dt_d, 1),
            "wall_s": round(dt_d, 3),
            "handoffs": snap_d["handoffs"],
            "handoff_requeues": snap_d["handoff_requeues"],
            "decode_blocked": snap_d["decode_blocked"],
            "prefix_routed": snap_d["prefix_routed"],
            "ttft_breakdown": bd(snap_d["ttft_breakdown"]),
        },
        # the gated headline (lower is better) + its attribution
        "ttft_p99_s_disagg": ttft_d.get("p99"),
        "ttft_p99_s_colocated": ttft_c.get("p99"),
        "disagg_ttft_p99_speedup": round(
            ttft_c.get("p99") / max(ttft_d.get("p99"), 1e-9), 2)
        if ttft_c.get("p99") else None,
        "decode_tok_s_ratio": round(
            (total_new / dt_d) / (total_new / dt_c), 3),
        "token_mismatches": mismatches,
        "leak_fence_ok": bool(leak_ok),
    }


def run_serving_elastic_bench(n_requests=16, slots=2, seed=0,
                              prompt_lens=(8, 16, 24),
                              max_new=24, rate=400.0, page_size=16,
                              max_pages_per_slot=8, model_cfg=None,
                              params=None):
    """Elastic-serving workload (ISSUE 11): a Poisson request trace
    served by a ReplicaPool that takes ONE injected hard replica kill
    and ONE graceful SIGTERM-style drain mid-flight, recovering both
    from committed elastic snapshots. Reports the recovered-request
    fraction (must be 1.0), the committed-token-loss count vs an
    uninterrupted reference (must be 0 — greedy replay regenerates the
    identical stream), and the mean per-recovery restore latency; a
    second mini-experiment measures TTFT p99 under a burst overload
    with autoscaling on vs off (watchdog-trip scale-up, 1 -> up to 3
    replicas)."""
    import tempfile
    import jax
    import jax.numpy as jnp
    from deepspeed_tpu.models.gpt2 import GPT2Config, GPT2LMHeadModel
    import deepspeed_tpu.serving as serving
    from deepspeed_tpu.serving.elastic import ElasticServingController
    from deepspeed_tpu.serving.replica_pool import ReplicaPool
    from deepspeed_tpu.telemetry.anomaly import Watchdog
    from deepspeed_tpu.telemetry.registry import MetricsRegistry

    rs = np.random.RandomState(seed)
    if model_cfg is None:
        # smaller than the throughput bench's sizing: this section
        # measures recovery plumbing, not model compute
        model_cfg = GPT2Config(
            vocab_size=512, n_positions=256, n_embd=128, n_layer=3,
            n_head=4, dtype=jnp.float32, param_dtype=jnp.float32,
            scan_layers=True)
    if params is None:
        params = jax.jit(GPT2LMHeadModel(model_cfg).init)(
            jax.random.PRNGKey(0), np.zeros((1, 8), np.int32))["params"]
    lens, news, arrivals = _workload(
        rs, n_requests, prompt_lens, [max_new], rate)
    prompts = [rs.randint(0, model_cfg.vocab_size,
                          size=(s,)).astype(np.int32) for s in lens]

    def make_requests():
        return [serving.Request(i, prompts[i],
                                max_new_tokens=int(news[i]))
                for i in range(n_requests)]

    proto = serving.build_engine(
        "gpt2", model_cfg, params,
        config={"serving": {"slots": slots, "page_size": page_size,
                            "max_pages_per_slot": max_pages_per_slot}})

    # uninterrupted greedy reference — the token-loss baseline
    ref_eng = serving.ContinuousBatcher(proto.adapter)
    ref = {rid: r.tokens().tolist()
           for rid, r in ref_eng.serve(make_requests()).items()}

    root = tempfile.mkdtemp(prefix="dstpu_serving_elastic_")
    wd_dir = os.path.join(root, "flight")

    def factory_for(registry, interval_ticks=2, wd_kw=None):
        def factory(rid):
            cb = serving.ContinuousBatcher(
                proto.adapter, registry=registry,
                watchdog=Watchdog(os.path.join(wd_dir, f"r{rid}"),
                                  source=f"serving_r{rid}",
                                  registry=registry,
                                  **(wd_kw or {})))
            cb.attach_elastic(ElasticServingController(
                cb, os.path.join(root, f"replica_{rid}"),
                grace_secs=30.0, interval_ticks=interval_ticks,
                fsync=False, install_signals=False))
            return cb
        return factory

    # --- fault leg: 3 replicas, one kill + one graceful drain -------
    # the Poisson trace is honored: requests become dispatchable at
    # their arrival times while the pool steps (rate is rescaled so
    # arrivals actually spread across the run instead of landing at
    # t=0 on this CPU proxy)
    reg = MetricsRegistry()
    pool = ReplicaPool(factory_for(reg), n_replicas=3, min_replicas=1,
                       max_replicas=3, scale_signal="none")
    todo = sorted(make_requests(), key=lambda r: r.arrival_time)
    for req, t_arr in zip(todo, arrivals * (rate / 25.0)):
        req.arrival_time = float(t_arr)
    t0 = time.monotonic()
    rounds = 0
    killed = drained = False
    while (todo or pool.pending) and rounds < 3000:
        now = time.monotonic() - t0
        while todo and todo[0].arrival_time <= now:
            pool.submit(todo.pop(0))
        if not pool.pending:
            time.sleep(0.002)      # waiting on arrivals, not a round
            continue
        pool.step()
        rounds += 1
        if rounds == 3 and pool.replicas:
            killed = True
            pool.kill_replica(next(iter(pool.replicas)), reason="bench")
        if rounds == 6 and len(pool.replicas) > 1:
            drained = True
            pool.preempt_replica(list(pool.replicas)[-1],
                                 source="bench_drain")
    wall = time.monotonic() - t0
    done = pool.done
    token_loss = sum(
        done[i].tokens().tolist() != ref[i]
        for i in range(n_requests) if i in done)
    missing = n_requests - len(done)
    st = pool.snapshot_stats()
    n_recoveries = st["kills"] + st["preempts"]
    # pool-level aggregation (ISSUE 12): merged-reservoir TTFT
    # percentiles + per-replica utilization — the document a
    # disaggregated router would schedule on
    pool_telemetry = pool.metrics_snapshot()
    pool.close()

    # --- autoscale leg: burst overload, watchdog signal on vs off ---
    def ttft_burst(signal):
        reg2 = MetricsRegistry()
        # a hair-trigger TTFT rule so queue buildup trips fast on the
        # CPU proxy (pool_exhausted trips fire regardless)
        p = ReplicaPool(
            factory_for(reg2, interval_ticks=0,
                        wd_kw=dict(ttft_factor=1.5, ttft_min_s=0.01,
                                   min_samples=4)),
            n_replicas=1, min_replicas=1, max_replicas=3,
            scale_signal=signal, scale_down_idle_rounds=10**9)
        burst = [serving.Request(f"b{i}", prompts[i % n_requests],
                                 max_new_tokens=max_new)
                 for i in range(2 * n_requests)]
        p.run(burst)
        snap = reg2.snapshot()
        ttft = snap["histograms"].get("serving/ttft_s", {})
        out = {"ttft_p50_s": ttft.get("p50"),
               "ttft_p99_s": ttft.get("p99"),
               "replicas_final": len(p.replicas),
               "scale_ups": p.stats["scale_ups"]}
        p.close()
        return out

    fixed = ttft_burst("none")
    auto = ttft_burst("watchdog")

    return {
        "workload": {"n_requests": n_requests, "slots": slots,
                     "replicas": 3, "max_new_tokens": max_new,
                     "prompt_lens": list(map(int, prompt_lens))},
        "faults_injected": int(killed) + int(drained),
        "recovered_fraction": round(len(done) / n_requests, 4),
        "committed_token_loss": int(token_loss) + int(missing),
        "requests_lost": len(pool.lost),
        "restore_latency_s": round(
            st["restore_s_total"] / max(n_recoveries, 1), 4),
        "recovered_direct": st["recovered_direct"],
        "recovered_requeued": st["recovered_requeued"],
        "resubmitted_fresh": st["resubmitted_fresh"],
        "wall_s": round(wall, 3),
        "ttft_p99_s_fixed": fixed["ttft_p99_s"],
        "ttft_p99_s_autoscale": auto["ttft_p99_s"],
        "autoscale": {"fixed": fixed, "watchdog": auto},
        "pool_telemetry": pool_telemetry,
    }


def run_disagg_xproc_bench(n_requests=32, max_new=6, timeout=420,
                           world=2, slots=2, tick_cap=0,
                           addressing="targeted"):
    """``transport: "process"`` over ``world`` REAL ranked OS
    processes (ISSUE 17/18): rank 0 = router + prefill engine
    (``PrefillNode``), every other rank one decode engine
    (``DecodeNode``), KV pages crossing as versioned wire frames —
    the header leg on the gloo fence, dst-addressed payloads
    point-to-point (``addressing: "targeted"``). Reuses the PR-10
    ``spawn_workers`` harness and tests/xproc_serving_worker.py — the
    same module the acceptance tests and the supervisor SIGKILL fault
    leg run — on the tiny deterministic model, so the section prices
    the TRANSPORT (frame encode → collective hop → decode → scatter →
    adopt), not a big model's compute.

    Headline: ``ttft_p99_s_disagg_xproc`` (TTFT is observed on the
    PREFILL engine at first-token delivery, so the cross-process
    placement can only show up in it through admission/handoff
    stalls); the decode ranks' ``transport_s`` summaries attribute
    the wire/move segment inside the breakdown, and the byte counters
    are re-derived on both sides of the boundary (``sent == recv``
    pins the codec). Greedy parity vs an in-process colocated run of
    the identical trace is asserted, as is the leak fence on EVERY
    pool.

    ISSUE 18 honesty additions: ``slot_util`` per role (busy/capacity
    decode ticks — idle ticks count in the denominator, so a
    queue-wait-bound TTFT tail shows as low utilization on the
    default 2-slot geometry instead of hiding behind the breakdown)
    and ``decode_tok_s_aggregate`` (the scale-out headline's
    numerator: each rank's slot occupancy × one saturated rank's
    decode rate calibrated on the quiet in-process reference run —
    occupancy is deterministic, so the projection sidesteps the
    one-core harness box where every per-rank clock prices
    time-slicing instead of capacity; see the inline comment at the
    computation)."""
    import pathlib
    import tempfile
    from tests.test_multiprocess_dist import spawn_workers
    from tests.xproc_serving_worker import (build_model, build_requests,
                                            serving_config)

    tmp = pathlib.Path(tempfile.mkdtemp(prefix="dstpu_xproc_bench_"))
    outs = spawn_workers(
        world,
        "import sys\n"
        "from tests.xproc_serving_worker import main\n"
        "main(['worker'] + sys.argv[1:])\n",
        tmp, script_args=(tmp / "out", n_requests, max_new, -1, slots,
                          0, addressing, tick_cap),
        timeout=timeout)
    met, res = {}, {}
    for out in outs:
        for line in out.splitlines():
            if line.startswith("MET "):
                doc = json.loads(line[4:])
                met[doc["rank"]] = doc
            elif line.startswith("RES "):
                _tag, rid, blob = line.split(" ", 2)
                res[int(rid)] = json.loads(blob)
    m0 = met[0]
    dmets = [met[r] for r in range(1, world)]

    # in-process colocated reference over the IDENTICAL trace: greedy
    # parity across the process boundary is the bench's correctness
    # fence, same as the acceptance test's
    import deepspeed_tpu.serving as serving
    sv = {k: v for k, v in serving_config(slots)["serving"].items()
          if k != "disaggregation"}
    cfg, params = build_model()
    eng = serving.build_engine("gpt2", cfg, params,
                               config={"serving": sv})
    ref = eng.serve(build_requests(n_requests, max_new))
    mismatches = sum(
        res[rid]["tokens"] != ref[rid].tokens().tolist()
        for rid in ref)

    sent = int(m0["counters"].get("router/handoff_bytes_sent", 0))
    recv = sum(int(m["counters"].get("router/handoff_bytes_recv", 0))
               for m in dmets)
    wasted = sum(int(m["stats"].get("wasted_bytes", 0))
                 for m in [m0] + dmets)
    payload = sum(int(m["absorbed_pages"]) for m in dmets) \
        * int(m0["page_nbytes"])
    fences = [f for m in [m0] + dmets for f in m["leak_fence"]]

    def pct(h):
        return {k: (round(h[k], 6) if isinstance(h.get(k), float)
                    else h.get(k))
                for k in ("count", "mean", "p50", "p99", "max")}

    def merged_pct(mets, key):
        # decode ranks each carry their own registry: merge the
        # samples' summaries coarsely (count-weighted mean, max of
        # tails) — good enough for a breakdown row
        hs = [m[key] for m in mets if m.get(key, {}).get("count")]
        if not hs:
            return {"count": 0}
        n = sum(h["count"] for h in hs)
        return {"count": n,
                "mean": round(sum(h["mean"] * h["count"]
                                  for h in hs) / n, 6),
                "p50": round(max(h["p50"] for h in hs), 6),
                "p99": round(max(h["p99"] for h in hs), 6),
                "max": round(max(h["max"] for h in hs), 6)}

    # scale-out numerator: on the one-core harness box every rank
    # time-slices the same CPU, so ANY per-rank clock — wall, process
    # CPU (bills XLA pool-thread spin), even the scheduler thread's
    # own CPU (XLA:CPU result sync busy-waits, so it stretches with
    # the peers' contention) — prices the box's interleaving, not
    # rank capacity. The honest per-rank observable is the
    # DETERMINISTIC slot occupancy each rank sustained; the quiet
    # in-process reference run above calibrates one saturated rank's
    # decode rate, and each rank's projected rate is occupancy × that
    # rate (decode steps are batch-padded to the slot count, so
    # per-tick cost is occupancy-independent). The calibration
    # constant cancels in the scale-out RATIO the gate pins — the
    # ratio is purely the balancer's occupancy split.
    tl = eng.metrics.histogram("serving/tick_latency_s").summary()
    su = eng.metrics.histogram("serving/slot_utilization").summary()
    tick_wall = float(tl.get("count", 0) or 0) * float(
        tl.get("mean", 0.0) or 0.0)
    sat_tok_s = (eng.stats["decode_tokens"]
                 / tick_wall / max(float(su.get("mean") or 0.0), 1e-9)
                 ) if tick_wall > 0 else 0.0
    tok_s = [round(float(m["slot_util"]) * sat_tok_s, 3)
             for m in dmets]

    ttft = m0["ttft_s"]
    return {
        "workload": {"world": world, "n_requests": n_requests,
                     "max_new_tokens": max_new, "slots": slots,
                     "transport": "process",
                     "addressing": addressing},
        "handoffs": m0["stats"]["handoffs"],
        "handoff_bytes_sent": sent,
        "handoff_bytes_recv": recv,
        "handoff_wasted_bytes": wasted,
        "kv_payload_bytes": payload,
        "wire_overhead_bytes": sent - payload,
        "payload_bytes_per_handoff": round(
            (payload + wasted) / max(m0["stats"]["handoffs"], 1), 1),
        "bytes_counters_equal": sent == recv,
        "ttft_p50_s": ttft.get("p50"),
        "ttft_breakdown": {
            "queue_wait_s": pct(m0["ttft_queue_wait_s"]),
            "prefill_s": pct(m0["ttft_prefill_s"]),
            # the wire/move segments (ISSUE 18 split): encode on the
            # router rank, collective on every rank, land on decode
            "transport_s": merged_pct(dmets, "transport_s"),
            "transport_encode_s": pct(m0["transport_encode_s"]),
            "transport_collective_s": merged_pct(
                [m0] + dmets, "transport_collective_s"),
            "transport_decode_s": merged_pct(dmets,
                                             "transport_decode_s"),
        },
        "slot_util": {
            "prefill": round(float(m0["slot_util"]), 4),
            "decode_per_rank": [round(float(m["slot_util"]), 4)
                                for m in dmets],
        },
        "decode_tok_s_per_rank": tok_s,
        "decode_tok_s_aggregate": round(sum(tok_s), 3),
        "decode_tok_s_calibration": round(sat_tok_s, 3),
        "delivered_per_rank": [m["stats"]["delivered"] for m in dmets],
        "ttft_p99_s_disagg_xproc": ttft.get("p99"),
        "token_mismatches": mismatches,
        "leak_fence_ok": all(f["free"] == f["want"] for f in fences),
    }


def run_disagg_scaleout_bench(n_requests=16, max_new=24, timeout=420):
    """ISSUE 18 scale-out headline: the SAME deterministic trace over
    world=2 (1 decode rank) and world=3 (2 decode ranks, LPT-balanced
    targeted transport). ``decode_scaleout_tok_s_ratio`` = world-3
    aggregate decode tok/s over world-2's, computed with ONE shared
    calibration so it reduces to the deterministic occupancy ratio —
    ≥ ~2× when the balancer keeps both ranks at the single-rank
    occupancy, gated ≥ 1.6× —
    with token parity and the leak fence asserted on every leg, and
    the per-handoff payload wire cost reported for both worlds (the
    targeted transport keeps it world-independent).

    Geometry note: both legs run the SAME saturation geometry —
    longer streams (``max_new=24``) than the TTFT leg's 6 and
    ``decode_tick_cap=1`` so each stream stays slot-resident across
    ~24 router sweeps instead of 6. At the TTFT leg's geometry the
    prefill rank's arrival rate sustains only ~1.6 concurrent decode
    streams, which one world-2 rank absorbs whole while two world-3
    ranks split it and idle half their slots; the longer residency
    lifts steady-state concurrency past 2 slots x 2 ranks so BOTH
    world-3 ranks hold near-single-rank occupancy (the reported
    ``slot_util`` is the honesty check). Per-rank rates are projected
    as occupancy × the calibrated saturated single-rank rate (decode
    steps are batch-padded, so per-tick cost is
    occupancy-independent): on the one-core harness box every direct
    per-rank clock prices the ranks' time-slicing of the shared core,
    while a real deployment runs one host per rank — and the
    calibration constant cancels in the gated ratio, which is exactly
    the occupancy the balancer + targeted transport sustained."""
    w2 = run_disagg_xproc_bench(n_requests, max_new, timeout, world=2,
                                tick_cap=1)
    w3 = run_disagg_xproc_bench(n_requests, max_new, timeout, world=3,
                                tick_cap=1)
    # the gated ratio divides out ONE shared calibration: it is the
    # pure occupancy ratio Σ util_w3 / Σ util_w2, so per-leg
    # calibration drift (box noise in each leg's quiet reference run)
    # cannot leak into the gate — the legs' absolute tok_s figures
    # keep their own calibration and are reported for scale only
    u2 = sum(w2["slot_util"]["decode_per_rank"])
    u3 = sum(w3["slot_util"]["decode_per_rank"])
    ratio = round(u3 / u2, 3) if u2 else 0.0
    return {
        "xproc_w2": w2,
        "xproc_w3": w3,
        "decode_scaleout_tok_s_ratio": ratio,
        "wire_cost_ratio_w3_over_w2": round(
            w3["payload_bytes_per_handoff"]
            / max(w2["payload_bytes_per_handoff"], 1e-9), 4),
        "token_parity_ok": w2["token_mismatches"] == 0
        and w3["token_mismatches"] == 0,
        "leak_fence_ok": w2["leak_fence_ok"] and w3["leak_fence_ok"],
    }


if __name__ == "__main__":
    import argparse
    ap = argparse.ArgumentParser()
    ap.add_argument("--mode", default="poisson",
                    choices=["poisson", "hot_prefix", "spec_decode",
                             "elastic", "disagg", "disagg_xproc",
                             "disagg_scaleout"])
    args = ap.parse_args()
    fn = {"poisson": run_serving_bench,
          "hot_prefix": run_hot_prefix_bench,
          "spec_decode": run_spec_decode_bench,
          "elastic": run_serving_elastic_bench,
          "disagg": run_disagg_bench,
          "disagg_xproc": run_disagg_xproc_bench,
          "disagg_scaleout": run_disagg_scaleout_bench}[args.mode]
    print(json.dumps(fn(), indent=1))
