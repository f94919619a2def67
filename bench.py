"""Benchmark — GPT-2 training MFU on the local TPU chip.

Prints up to TWO JSON lines — an insurance line with every number except
the long-running infinity6b case, then the authoritative final line
including it. THE LAST COMPLETE JSON LINE IS THE RESULT.
North star (BASELINE.json): GPT-2 ZeRO-3 at ≥45% MFU → vs_baseline = MFU/45.

Model flops per step use the standard 6·N·T (+ attention) accounting; peak
chip flops resolved from the device kind.

Sections + budgets (r5: the run hit the driver's wall clock, rc=124, and
the JSON tail was truncated mid-object): every optional section is gated
by a SectionRunner that (a) honours ``--sections a,b,c`` to run a subset,
(b) skips anything whose estimated cost no longer fits ``--budget``
seconds of global wall clock, and (c) records a section's exception as
``{"error": reason}`` in ``detail.sections_failed`` and goes on — so EVERY
run prints complete, parseable JSON lines — and then EXITS NON-ZERO: a
section that threw is a failed run, not a skip. Deselected and
over-budget sections are recorded in ``detail.sections_skipped``.
``--list-sections`` prints the names.

Device sections (``DEVICE_SECTIONS``) report accelerator metrics and fail
without a TPU. ``DSTPU_BENCH_ALLOW_CPU=1`` admits a CPU-only process for
the remaining sections alone, which count bytes, hits, parity and host or
disk times and say so in their output.
"""

import argparse
import json
import os
import sys
import time
import traceback

import numpy as np


class SectionRunner:
    """Gate + error-fence for bench sections. ``selected`` empty → all
    sections run (budget permitting); skips and failures are recorded with
    reasons, and any failure makes ``main`` exit non-zero."""

    def __init__(self, selected=(), budget_s=0.0, on_tpu=True):
        self.t0 = time.time()
        self.selected = tuple(s for s in selected if s)
        self.budget = float(budget_s or 0.0)
        self.on_tpu = on_tpu
        self.skipped = {}
        self.failed = {}

    def elapsed(self):
        return time.time() - self.t0

    def remaining(self):
        return max(0.0, self.budget - self.elapsed()) if self.budget \
            else float("inf")

    def want(self, name, est_s=60.0):
        if self.selected and name not in self.selected:
            self.skipped[name] = "deselected (--sections)"
            return False
        if self.budget and est_s > self.remaining():
            self.skipped[name] = (
                f"budget: {self.elapsed():.0f}s elapsed of "
                f"{self.budget:.0f}s, section estimate {est_s:.0f}s")
            return False
        return True

    def run(self, name, fn, est_s=60.0):
        """Run ``fn`` if selected + affordable; any outcome is a JSON-able
        value: {"skipped": reason} when gated, {"error": reason} when the
        section threw or is a device section on a process without a TPU."""
        if not self.want(name, est_s):
            return {"skipped": self.skipped[name]}
        try:
            if name in DEVICE_SECTIONS and not self.on_tpu:
                raise RuntimeError(
                    f"section {name!r} reports device metrics and needs a "
                    f"TPU; DSTPU_BENCH_ALLOW_CPU covers only the CPU-count "
                    f"sections")
            return fn()
        except Exception as e:    # noqa: BLE001 — boundary: record, go on
            traceback.print_exc()
            self.failed[name] = f"{type(e).__name__}: {str(e)[:300]}"
            return {"error": self.failed[name]}


BENCH_SECTIONS = ("bert", "train", "sparse", "decode", "llama7b", "moe",
                  "onebit_comm", "aio",
                  "nvme_param", "nvme_xl",
                  "elastic_ckpt", "fault_recovery", "serving",
                  "serving_prefix", "serving_spec", "serving_elastic",
                  "serving_disagg", "infinity6b")

#: sections whose numbers are accelerator metrics (MFU, tokens/s, kernel
#: speedups, the on-chip scale proof): no CPU route reaches them
DEVICE_SECTIONS = frozenset({"bert", "train", "sparse", "decode", "llama7b",
                             "moe", "infinity6b"})


# ---------------------------------------------------------------------------
# --compare: the CI regression gate (ISSUE 6). Diffs the headline
# metrics of two bench result documents and exits nonzero when any
# common metric regressed past the threshold. Handles both the
# bench-native result JSON and the driver-captured BENCH_rXX.json
# format ({"parsed": {metric, value, ...}}). This path never imports
# jax — it runs on artifact files anywhere.
# ---------------------------------------------------------------------------

def _load_doc(path):
    try:
        with open(path) as fh:
            return json.load(fh)
    except (OSError, ValueError) as e:
        raise SystemExit(f"--compare: cannot load {path}: {e}")


def provenance(jax_version=None):
    """Stamp for every result JSON (``meta.provenance``, ISSUE 12
    satellite): the ±25% box swing between identical-content runs keeps
    getting rediscovered by hand — a compare that shows two different
    hostnames/cpu_counts (or the same sha measured twice) answers "is
    this a regression or a different box" without archaeology. Pass
    ``jax_version`` from the caller that already imported jax; this
    function itself must stay importable jax-free (the --candidate
    compare path)."""
    import platform
    import socket
    import subprocess
    here = os.path.dirname(os.path.abspath(__file__))
    try:
        sha = subprocess.check_output(
            ["git", "rev-parse", "--short", "HEAD"], cwd=here,
            stderr=subprocess.DEVNULL).decode().strip()
    except Exception:
        sha = "unknown"
    return {
        "git_sha": sha,
        "hostname": socket.gethostname(),
        "cpu_count": os.cpu_count(),
        "jax_version": jax_version or "unknown",
        "python_version": platform.python_version(),
    }


def _doc_provenance(doc):
    """meta.provenance of a result document (driver-captured docs may
    carry it beside ``parsed``), or None."""
    for d in (doc, doc.get("parsed") if isinstance(doc.get("parsed"),
                                                   dict) else {}):
        if isinstance(d, dict):
            p = (d.get("meta") or {}).get("provenance")
            if isinstance(p, dict):
                return p
    return None


def _num(v):
    return isinstance(v, (int, float)) and not isinstance(v, bool)


def headline_metrics(doc):
    """Flatten a bench result document into ``{name: (value,
    direction)}`` where direction is +1 for higher-is-better and -1
    for lower-is-better. Sections that were skipped (or absent)
    contribute nothing — the gate compares only metrics BOTH runs
    measured."""
    parsed = doc.get("parsed")
    if isinstance(parsed, dict) and _num(parsed.get("value")):
        # driver-captured format: the parsed line IS a bench-native doc
        # (r01-r03 carry the full detail; r05 only the headline) —
        # recurse so whatever survived the tail capture gates
        return headline_metrics(parsed)
    out = {}
    if _num(doc.get("value")):
        out[doc.get("metric", "headline")] = (doc["value"], +1)
    d = doc.get("detail") or {}

    def grab(name, container, key, direction):
        v = container.get(key) if isinstance(container, dict) else None
        if _num(v):
            out[name] = (v, direction)

    grab("tokens_per_sec", d, "tokens_per_sec", +1)
    grab("samples_per_sec_per_chip", d, "samples_per_sec_per_chip", +1)
    grab("step_time_ms", d, "step_time_ms", -1)
    grab("bert_base_seq128_samples_per_sec", d,
         "bert_base_seq128_samples_per_sec", +1)
    dec = d.get("decode")
    if isinstance(dec, dict):
        for name, entry in sorted(dec.items()):
            if not isinstance(entry, dict):
                continue
            if name == "serving_continuous_batching":
                grab("serving.requests_per_sec", entry,
                     "requests_per_sec_continuous", +1)
                grab("serving.decode_tokens_per_sec", entry,
                     "decode_tokens_per_sec_continuous", +1)
                grab("serving.ttft_p99_s", entry, "ttft_p99_s", -1)
            elif name == "serving_hot_prefix":
                # ISSUE 9: repeat-prefix admissions must keep aliasing
                # resident pages (a drop means the prefix index broke)
                grab("serving.prefix_hit_rate", entry,
                     "prefix_hit_rate", +1)
            elif name == "serving_spec_decode":
                # ISSUE 9: batched verification must keep beating the
                # one-model-call-per-token decode loop at b1
                grab("serving.spec_decode_speedup", entry,
                     "spec_decode_speedup", +1)
            elif name == "serving_disagg":
                # ISSUE 14: the role split must keep beating colocated
                # head-of-line TTFT on the deterministic mixed trace
                grab("serving.disagg_ttft_p99", entry,
                     "ttft_p99_s_disagg", -1)
                # ISSUE 17: the 2-real-process transport leg's TTFT
                # tail (wire codec + collective hop in the handoff
                # path) — gate against BENCH_r16.json or newer
                grab("serving.disagg_xproc_ttft_p99", entry,
                     "ttft_p99_s_disagg_xproc", -1)
                # ISSUE 18: multi-decode scale-out — world-3 aggregate
                # decode tok/s over world-2's single decode rank must
                # keep >= 1.6x (LPT balancing holding both ranks near
                # single-rank occupancy); gate vs BENCH_r18 or newer
                grab("serving.decode_scaleout_tok_s_ratio", entry,
                     "decode_scaleout_tok_s_ratio", +1)
            elif name == "serving_elastic":
                # ISSUE 11: one replica kill + one graceful drain must
                # keep recovering EVERY request (greedy replay makes
                # recovery token-lossless, so 1.0 is the only pass);
                # token-loss/restore-latency ride the detail unguarded
                # (latency is box-noise-bound on the CPU harness)
                grab("serving.elastic_recovered_fraction", entry,
                     "recovered_fraction", +1)
            else:
                grab(f"decode.{name}.decode_tokens_per_sec", entry,
                     "decode_tokens_per_sec", +1)
    grab("moe.tokens_per_sec", d.get("moe"), "tokens_per_sec", +1)
    # ISSUE 10: the hierarchical exchange must keep the slow-hop
    # bytes-on-wire reduction (static cost-model ratio, >= 4x; a drop
    # means the per-bucket policy stopped compressing the slow axis)
    grab("onebit_comm.bytes_reduction", d.get("onebit_comm"),
         "bytes_reduction", +1)
    grab("nvme_param.steady_step_s", d.get("nvme_param_tier"),
         "steady_step_s", -1)
    # ISSUE 20: the honest NVMe path. max_params_b is the single-chip
    # scale proof under O_DIRECT streaming (must stay >= 10B); the
    # o_direct stall share is the page-cache-free swap cost the step
    # actually pays
    grab("nvme_xl.max_params_b", d.get("nvme_xl"), "max_params_b", +1)
    nv = d.get("nvme_param_tier")
    grab("nvme_param.o_direct_stall_share",
         nv.get("o_direct") if isinstance(nv, dict) else None,
         "stall_share_of_step", -1)
    grab("infinity.steady_step_s", d.get("infinity_6b"),
         "steady_step_s", -1)
    # elastic snapshots (ISSUE 7) stay OUT of the gated set on purpose:
    # step_s_async/blocking_save_s are ~0.2-0.4 s page-cache timings
    # with documented ±20% box noise — gating them at 5% makes CI
    # flaky with no real regression (the numbers live in the section
    # detail; the stable signals are ckpt_stall_s == 0 and
    # overhead_pct_at_interval_100 < 1)
    return out


def compare_docs(prior, candidate, threshold=0.05):
    """Structured diff of two result documents; ``regressions`` lists
    common metrics whose direction-signed change is worse than
    ``threshold`` (a fraction, e.g. 0.05 = 5%)."""
    pm, cm = headline_metrics(prior), headline_metrics(candidate)
    compared, regressions, improvements = {}, [], []
    for k in sorted(set(pm) & set(cm)):
        pv, direction = pm[k]
        cv, _ = cm[k]
        if pv == 0:
            continue
        delta = (cv / pv - 1.0) * direction    # > 0 means better
        compared[k] = {
            "prior": pv, "candidate": cv,
            "delta_pct": round(delta * 100, 2),
            "better": "higher" if direction > 0 else "lower",
        }
        if delta < -threshold:
            regressions.append(k)
        elif delta > threshold:
            improvements.append(k)
    return {
        "threshold_pct": round(threshold * 100, 2),
        "compared": compared,
        "regressions": regressions,
        "improvements": improvements,
        "only_in_prior": sorted(set(pm) - set(cm)),
        "only_in_candidate": sorted(set(cm) - set(pm)),
    }


def compare_and_report(prior_doc, candidate_doc, threshold):
    """Print the per-metric diff + a machine-readable summary line;
    return the process exit code (0 pass, 3 regression)."""
    rep = compare_docs(prior_doc, candidate_doc, threshold)
    # both sides' provenance up front: a "regression" measured on a
    # different hostname/cpu_count is the box, not the code
    for side, doc in (("prior", prior_doc), ("candidate", candidate_doc)):
        prov = _doc_provenance(doc)
        print(f"  {side} provenance: "
              + (json.dumps(prov, sort_keys=True) if prov
                 else "<none recorded>"))
    for k, row in rep["compared"].items():
        flag = "REGRESSION" if k in rep["regressions"] else (
            "improved" if k in rep["improvements"] else "ok")
        print(f"  {k}: {row['prior']} -> {row['candidate']} "
              f"({row['delta_pct']:+.2f}%, {row['better']}-is-better) "
              f"[{flag}]")
    print(json.dumps({"compare": rep}), flush=True)
    if not rep["compared"]:
        print("WARN: no common headline metrics to compare "
              "(gate passes vacuously)")
        return 0
    if rep["regressions"]:
        print(f"FAIL: {len(rep['regressions'])} metric(s) regressed "
              f"past {rep['threshold_pct']}%: "
              f"{', '.join(rep['regressions'])}")
        return 3
    print(f"PASS: no headline metric regressed past "
          f"{rep['threshold_pct']}% "
          f"({len(rep['compared'])} compared)")
    return 0


def peak_flops(device):
    """Single source of truth: profiling/flops_profiler.py (the engine's
    telemetry MFU gauge resolves the same table)."""
    from deepspeed_tpu.profiling.flops_profiler import peak_device_flops
    return peak_device_flops(device)


def model_flops_per_token(cfg):
    """6N + attention term (12·L·S·E per token) — canonical copy in
    profiling/flops_profiler.py, shared with the MFU tests."""
    from deepspeed_tpu.profiling import flops_profiler
    return flops_profiler.model_flops_per_token(cfg)


def main(argv=None):
    ap = argparse.ArgumentParser(description=__doc__.splitlines()[0])
    ap.add_argument("--sections", default="",
                    help="comma-separated subset of sections to run "
                         f"(default all): {','.join(BENCH_SECTIONS)}")
    ap.add_argument("--budget", type=float, default=None,
                    help="global wall-clock budget in seconds; sections "
                         "whose estimate no longer fits are skipped and "
                         "recorded (0 = unlimited; default: "
                         "$DSTPU_BENCH_BUDGET or 3000 — r5 ran unbounded, "
                         "hit the driver's wall clock at rc=124, and lost "
                         "the trailing sections to a SIGKILL instead of "
                         "an explicit skip)")
    ap.add_argument("--list-sections", action="store_true")
    ap.add_argument("--compare", metavar="PRIOR.json", default="",
                    help="regression gate: diff this run's headline "
                         "metrics against a prior result document "
                         "(bench-native JSON or a driver BENCH_rXX.json)"
                         " and exit nonzero past the threshold; with "
                         "--candidate, diff two files WITHOUT running "
                         "the bench (no jax import — CI-usable on "
                         "artifacts)")
    ap.add_argument("--candidate", metavar="CURRENT.json", default="",
                    help="candidate result file for --compare "
                         "(skips the bench run)")
    ap.add_argument("--regression-threshold", type=float, default=0.05,
                    help="fractional worsening that fails the gate "
                         "(default 0.05 = 5%%)")
    args = ap.parse_args(argv)
    if args.list_sections:
        print(json.dumps(list(BENCH_SECTIONS)))
        return 0
    if args.candidate and not args.compare:
        raise SystemExit("--candidate requires --compare PRIOR.json")
    if args.compare and args.candidate:
        # pure-file gate: no bench run, no jax import
        return compare_and_report(_load_doc(args.compare),
                                  _load_doc(args.candidate),
                                  args.regression_threshold)
    selected = [s.strip() for s in args.sections.split(",") if s.strip()]
    unknown = [s for s in selected if s not in BENCH_SECTIONS]
    if unknown:
        raise SystemExit(f"unknown sections {unknown}; "
                         f"choose from {list(BENCH_SECTIONS)}")
    if args.budget is None:
        # the default run gets a budget UNDER the driver's wall clock so
        # trailing sections record an explicit skip instead of the whole
        # process dying rc=124 mid-JSON
        args.budget = float(os.environ.get("DSTPU_BENCH_BUDGET", 3000))

    import jax
    import jax.numpy as jnp
    import deepspeed_tpu as dstpu
    from deepspeed_tpu.parallel.mesh import make_mesh, MeshConfig
    from deepspeed_tpu.utils.platform import (enable_compile_cache,
                                              is_tpu_backend)
    enable_compile_cache()

    on_tpu = is_tpu_backend()
    if not on_tpu and not os.environ.get("DSTPU_BENCH_ALLOW_CPU"):
        raise RuntimeError(
            f"bench.py needs a TPU and found backend "
            f"{jax.default_backend()!r} (DSTPU_BENCH_ALLOW_CPU=1 admits "
            f"the CPU-count sections only: "
            f"{sorted(set(BENCH_SECTIONS) - DEVICE_SECTIONS)})")
    runner = SectionRunner(selected, args.budget, on_tpu=on_tpu)

    dev = jax.devices()[0]

    # BERT headline first: its state must be freed before the 774M model
    # claims most of HBM
    bert_sps = runner.run(
        "bert", lambda: bench_bert(dstpu, make_mesh, MeshConfig, dev),
        est_s=180)
    jax.clear_caches()

    train = runner.run(
        "train", lambda: bench_train_gpt2(dstpu, make_mesh, MeshConfig,
                                          dev, jnp),
        est_s=600)
    jax.clear_caches()
    sparse = runner.run("sparse", lambda: bench_sparse_attention(jnp),
                        est_s=180)
    jax.clear_caches()
    decode = runner.run("decode", lambda: bench_decode(jnp), est_s=900)
    jax.clear_caches()
    # llama7b + serving ride the decode section of the JSON but are
    # gated INDEPENDENTLY through the runner, so selecting/skipping
    # either always records a reason even when decode itself skipped
    for bs in (1, 8):
        decode[f"llama7b_b{bs}_int8"] = runner.run(
            "llama7b", lambda bs=bs: bench_llama_decode(jnp, bs=bs),
            est_s=600)
        jax.clear_caches()
    decode["serving_continuous_batching"] = runner.run(
        "serving", bench_serving, est_s=600)
    jax.clear_caches()
    # ISSUE 9: prefix-sharing + speculative decoding ride the serving
    # section (same CPU-proxy model sizing) but gate independently
    decode["serving_hot_prefix"] = runner.run(
        "serving_prefix", bench_serving_hot_prefix, est_s=300)
    jax.clear_caches()
    decode["serving_spec_decode"] = runner.run(
        "serving_spec", bench_serving_spec_decode, est_s=300)
    jax.clear_caches()
    # ISSUE 11: elastic serving — replica kill + graceful drain
    # recovery and watchdog-driven autoscale under burst overload
    decode["serving_elastic"] = runner.run(
        "serving_elastic", bench_serving_elastic, est_s=420)
    jax.clear_caches()
    # ISSUE 14: disaggregated prefill/decode + SLO router vs the
    # colocated engine on the identical deterministic mixed trace
    decode["serving_disagg"] = runner.run(
        "serving_disagg", bench_serving_disagg, est_s=420)
    jax.clear_caches()
    moe = runner.run(
        "moe", lambda: bench_moe(dstpu, make_mesh, MeshConfig, dev),
        est_s=180)
    jax.clear_caches()
    onebit_comm = runner.run("onebit_comm", bench_onebit_comm, est_s=240)
    jax.clear_caches()

    # NVMe/disk tier throughput (reference's aio perf harness role,
    # csrc/aio/py_test): 128 MB write+read through the async-IO library,
    # median of 3 passes + cold first read (pinned methodology — see
    # quick_throughput) — sizes the ZeRO-Infinity swap tier
    def _aio():
        from tests.perf.aio_bench import quick_throughput
        return quick_throughput(mb=128)
    aio = runner.run("aio", _aio, est_s=120)
    nvme_param = runner.run(
        "nvme_param",
        lambda: bench_nvme_param_tier(dstpu, make_mesh, MeshConfig, dev),
        est_s=300)
    jax.clear_caches()
    # ISSUE 20: the O_DIRECT streaming scale proof — 10B+ params on one
    # chip with bounded host residency, measured against the page-cache-
    # free device numbers (plus a small-scale loss-parity leg)
    nvme_xl = runner.run(
        "nvme_xl",
        lambda: bench_nvme_xl(dstpu, make_mesh, MeshConfig, dev),
        est_s=600)
    jax.clear_caches()
    elastic_ckpt = runner.run(
        "elastic_ckpt",
        lambda: bench_elastic_ckpt(dstpu, make_mesh, MeshConfig, dev),
        est_s=240)
    jax.clear_caches()
    # ISSUE 15: supervisor MTTR — detect latency + restart-to-first-step
    # over real (stdlib) child processes; seconds, not minutes
    fault_recovery = runner.run("fault_recovery", bench_fault_recovery,
                                est_s=30)

    tdet = train if isinstance(train, dict) else {}
    skipped_train = "skipped" in tdet or "error" in tdet
    result = {
        "meta": {"provenance": provenance(jax_version=jax.__version__)},
        "metric": "gpt2_large_774m_zero3_mfu",
        "value": None if skipped_train else tdet["mfu_pct"],
        "unit": "%MFU",
        "vs_baseline": None if skipped_train
        else round(tdet["mfu_pct"] / 45.0, 3),
        "detail": {
            **({"train_skipped": tdet.get("skipped") or tdet.get("error")}
               if skipped_train
               else {k: v for k, v in tdet.items() if k != "mfu_pct"}),
            # fused-kernel BERT pretraining headline (reference: 272
            # samples/s @ seq128 on one V100, 2020-05-28 blog)
            "bert_base_seq128_samples_per_sec": bert_sps,
            # serving decode throughput (reference ships 6.5k LoC of
            # inference kernels because decode perf mattered; here the
            # fused inference layer + KV cache, models/gpt2_inference.py)
            "decode": decode,
            # block-sparse vs dense flash attention fwd+bwd (reference
            # claim: up to 6.1x + 10x longer sequences; 16k runs the
            # streaming kernel past the old S*D cap)
            "sparse_attention": sparse,
            # async-IO tier (io_uring or thread pool; cache-cold read)
            "aio_disk": aio,
            # ZeRO-Infinity parameter tier: params REST on NVMe between
            # steps (swap files + parked device arrays), streaming disk ->
            # staging -> HBM around each step.
            "nvme_param_tier": nvme_param,
            # O_DIRECT streaming scale proof (ISSUE 20): a 10B+ tiled
            # parameter set parks on disk and streams back through the
            # bounded staging window twice — first pass vs steady pass
            # at device bandwidth (no page-cache assist), host RSS
            # bounded by the window, small-scale loss parity vs the
            # in-memory engine
            "nvme_xl": nvme_xl,
            # elastic async snapshots (ISSUE 7): step-time overhead of
            # checkpointing every few steps through the write-behind aio
            # handle vs the blocking save stall it replaces
            "elastic_ckpt": elastic_ckpt,
            # fault-tolerant training supervisor (ISSUE 15): rank-death
            # detect latency + restart-to-first-step MTTR over real
            # child processes (stdlib workers — the machinery's cost,
            # not an engine compile)
            "fault_recovery": fault_recovery,
            # expert-parallel MoE training throughput (beyond-reference
            # component; routing einsums regress invisibly without it)
            "moe": moe,
            # hierarchical link-aware 1-bit gradient exchange (ISSUE
            # 10): slow-hop bytes-on-wire reduction + step times; on a
            # single-host harness the 8-virtual-device synthetic-split
            # proxy (the REAL process-boundary path is pinned by
            # tests/test_multiprocess_dist.py)
            "onebit_comm": onebit_comm,
            "sections_skipped": runner.skipped,
            "sections_failed": runner.failed,
        },
    }

    def short(r):
        # the driver records a bounded TAIL of stdout; the full result
        # line outgrew it in r4 and the headline number vanished. ALWAYS
        # end with a short headline-only line so the tail is
        # self-sufficient regardless of how much detail precedes it.
        return json.dumps({k: r[k] for k in
                           ("metric", "value", "unit", "vs_baseline")})

    # insurance line: the 6B case below can take many minutes; if the
    # run is cut mid-way, the LAST complete JSON line still carries every
    # other number. The later (authoritative) line replaces it.
    result["detail"]["sections_skipped"] = dict(runner.skipped)
    result["detail"]["sections_failed"] = dict(runner.failed)
    print(json.dumps(result), flush=True)
    print(short(result), flush=True)

    # the max-params-per-chip scale proof (ZeRO-Infinity, ≥6B on 16 GB)
    # — free every earlier section's device state first; the 6B case
    # needs nearly the whole chip
    jax.clear_caches()
    inf6b = runner.run("infinity6b",
                       lambda: bench_infinity_6b(dstpu, dev), est_s=1200)
    result["detail"]["infinity_6b"] = inf6b
    result["detail"]["max_params_per_chip_b"] = inf6b.get("params_b")
    result["detail"]["sections_skipped"] = dict(runner.skipped)
    result["detail"]["sections_failed"] = dict(runner.failed)
    print(json.dumps(result))
    print(short(result))

    rc = 0
    if args.compare:
        # the gate rides a full run: this run's result is the candidate
        rc = compare_and_report(_load_doc(args.compare), result,
                                args.regression_threshold)
    if runner.failed:
        print(f"FAIL: section(s) raised: {sorted(runner.failed)}",
              file=sys.stderr)
        return rc or 1
    return rc


def bench_train_gpt2(dstpu, make_mesh, MeshConfig, dev, jnp):
    """The headline section: GPT-2 large (774M) ZeRO-3 training MFU.
    Returns a dict whose ``mfu_pct`` is the bench metric; everything
    else lands in the result detail."""
    import jax
    from deepspeed_tpu.models.gpt2 import GPT2Config, GPT2LMHeadModel
    mesh = make_mesh(MeshConfig(data=1), devices=[dev])

    seq = 1024
    # GPT-2 large (774M), the largest dense config that trains in 16 GB.
    # Measured fastest recipe on v5e (see docs/perf_tuning.md): bs8
    # (8192-row matmuls feed the MXU at its efficiency knee), remat with
    # the dots_flash_fc_lean policy (keep mlp matmuls + flash residuals;
    # qkv and the attention projection recompute), fused chunked
    # head+loss (no [B,S,V] buffer), bf16 gradients + a bf16 Adam first
    # moment (fp32 update math; the second moment stays fp32 — a bf16
    # EMA freezes below its ulp).
    model_cfg = GPT2Config(vocab_size=50304, n_positions=seq, n_embd=1280,
                           n_layer=36, n_head=20, dtype=jnp.bfloat16,
                           scan_layers=True, remat=True,
                           remat_policy="dots_flash_fc_lean", loss_chunk=1024)
    batch_size = 8

    cfg = {
        "train_batch_size": batch_size,
        "gradient_accumulation_steps": 1,
        "zero_optimization": {"stage": 3},
        "bf16": {"enabled": True},
        "data_types": {"grad_dtype": "bf16"},
        "gradient_clipping": 1.0,
        "optimizer": {"type": "AdamW",
                      "params": {"lr": 1e-4, "weight_decay": 0.01,
                                 "moment_dtype": "bf16"}},
        "steps_per_print": 1000,
    }
    model = GPT2LMHeadModel(model_cfg)
    # telemetry: the engine records into the process-wide registry; a
    # fresh window here keeps earlier sections' train/* values out of
    # this section's snapshot
    from deepspeed_tpu.telemetry import default_registry
    default_registry().reset()
    engine, _, _, _ = dstpu.initialize(config=cfg, model=model, mesh=mesh)

    rng = np.random.RandomState(0)
    batch = {"input_ids": rng.randint(0, 50304, size=(batch_size, seq))
             .astype(np.int32)}

    # warmup (compile); the loss readback waits for the step that made it
    for _ in range(2):
        loss = engine.train_batch(batch)
    float(jax.device_get(loss))
    engine.telemetry_flush()   # open a steady-state telemetry window

    # three timed windows of 30 steps, each closed by ONE loss readback
    # (the fence); best wins
    iters = 30
    best = float("inf")
    for _ in range(3):
        t0 = time.perf_counter()
        for _ in range(iters):
            loss = engine.train_batch(batch)
        float(jax.device_get(loss))
        best = min(best, (time.perf_counter() - t0) / iters)
        # fold each timed window into the step-time histogram (the
        # fence above already paid the sync). The batch lets the first
        # fold price MFU from the compiled step's cost analysis —
        # between windows, outside every timed region.
        engine.telemetry_flush(batch)
    dt = best

    tokens_per_step = batch_size * seq
    flops_per_step = model_flops_per_token(model_cfg) * tokens_per_step
    achieved = flops_per_step / dt
    mfu = achieved / peak_flops(dev)
    samples_per_sec = batch_size / dt
    final_loss = float(jax.device_get(loss))
    # exact compiled-buffer memory breakdown (free: executable cache hit)
    mem = engine.train_step_memory_stats(batch)
    params_b = round(model_cfg.num_params() / 1e9, 3)

    # per-phase wall-clock breakdown (reference wall_clock_breakdown,
    # engine.py:1028-1047): the instrumented mode splits the fused program
    # into fwd / fwd+bwd / apply with data-dependent fences, so phase times
    # are real measurements — fwd+bwd don't sum to the fused step time
    # (which keeps cross-phase fusion and no fences)
    engine._config.wall_clock_breakdown = True
    engine.train_batch(batch)          # compiles the loss + apply programs
    engine.wall_clock_times(reset=True)
    for _ in range(3):
        engine.train_batch(batch)
    phase_ms = {k: round(v / 3 * 1000, 1)
                for k, v in engine.wall_clock_times().items()}
    engine._config.wall_clock_breakdown = False

    # unified-telemetry snapshot for the BENCH record: step-time
    # percentiles over the timed windows, per-phase span histograms
    # (fed by the instrumented runs above), and the engine's own MFU
    # gauge (flops from the compiled step's cost analysis, priced at
    # the first window fold). Snapshot, not flush: the instrumented
    # window must not fold into the steady-state step-time histogram.
    tel = engine.telemetry_snapshot()
    spans = {k.split("span/", 1)[1]: v
             for k, v in tel["histograms"].items() if k.startswith("span/")}
    telemetry = {
        "step_time_s": tel["histograms"].get("train/step_time_s", {}),
        "spans": spans,
        "mfu_engine_pct": round(tel["gauges"].get("train/mfu", 0.0) * 100,
                                2),
        "tokens_per_sec_engine": round(
            tel["gauges"].get("train/tokens_per_sec", 0.0), 1),
        "flops_per_step_cost_analysis": tel["gauges"].get(
            "train/flops_per_step", 0.0),
    }

    # free the ~8 GB of training state before later sections allocate
    # their params + KV caches (same ordering rule as the BERT section)
    del engine, model, loss
    import jax as _jax
    _jax.clear_caches()
    return {
        "mfu_pct": round(mfu * 100, 2),
        "samples_per_sec_per_chip": round(samples_per_sec, 2),
        "tokens_per_sec": round(tokens_per_step / dt, 1),
        "step_time_ms": round(dt * 1000, 2),
        "achieved_tflops": round(achieved / 1e12, 2),
        "device": getattr(dev, "device_kind", str(dev)),
        # loss after ~92 optimizer steps on ONE repeated batch — a
        # memorization sanity value, not a convergence claim (see r4
        # note: window growth tripled the steps before this read).
        "loss": final_loss,
        "loss_note": "after ~92 steps on one repeated batch",
        # SURVEY §7 memory evidence: exact XLA buffer assignment of
        # the train step. True peak is BELOW the sum of these two —
        # donated state buffers are reused for temporaries.
        "hbm_compiled_buffers_gb": {
            "state_and_batch": round(mem["argument_bytes"] / 2**30, 2),
            "activations_and_temps": round(mem["temp_bytes"] / 2**30, 2),
        },
        "dense_params_b": params_b,
        # instrumented-mode per-phase means, NET of the per-phase
        # readback fence (the 'fence' entry is the measured readback)
        "phase_breakdown_ms": phase_ms,
        # unified telemetry (ISSUE 4): per-phase span times, step-time
        # percentiles over the timed windows, and the engine's own MFU
        # gauge next to the bench's analytic headline
        "telemetry": telemetry,
    }


def _run_proxy_bench(script_relpath, devices=8, timeout=900):
    """Run a tests/perf bench script as an N-virtual-device CPU
    subprocess (XLA_FLAGS is read at interpreter start, so the parent
    process cannot widen its own device count) and parse its JSON
    output. The script prints one indented JSON object; log lines may
    precede it, so parse from the last bare "{" line onward."""
    import subprocess
    here = os.path.dirname(os.path.abspath(__file__))
    env = dict(os.environ)
    env["JAX_PLATFORMS"] = "cpu"
    # a CPU child keeps out of a cache directory placed for TPU programs
    env.pop("JAX_COMPILATION_CACHE_DIR", None)
    env["XLA_FLAGS"] = (env.get("XLA_FLAGS", "")
                        + f" --xla_force_host_platform_device_count"
                          f"={devices}")
    proc = subprocess.run(
        [sys.executable, os.path.join(here, *script_relpath.split("/"))],
        env=env, cwd=here, capture_output=True, text=True,
        timeout=timeout)
    if proc.returncode != 0:
        raise RuntimeError(f"proxy subprocess rc={proc.returncode}: "
                           f"{(proc.stderr or '')[-2000:]}")
    lines = (proc.stdout or "").splitlines()
    start = max(i for i, l in enumerate(lines) if l.strip() == "{")
    out = json.loads("\n".join(lines[start:]))
    return {"mesh": f"cpu_virtual_{devices}dev_step_time_proxy", **out}


def bench_onebit_comm():
    """Hierarchical link-aware 1-bit gradient exchange (ISSUE 10,
    tests/perf/onebit_comm_bench.py): flat compressed allreduce vs the
    two-level split (fast axis uncompressed, slow axis sign-packed) vs
    the exact two-level mean, one OneBitAdam engine each. Headline gate
    is ``bytes_reduction`` — modeled post-freeze slow-hop fp32 bytes
    over sign-packed bytes, exact because the bucket plan and policy
    are static (acceptance: >= 4x). Step times recorded for
    calibration; on the CPU proxy the links are memcpys, so wall-clock
    is not the portable claim — the wire-byte ledger is."""
    import jax
    if len(jax.devices()) >= 4 and len(jax.devices()) % 2 == 0:
        from tests.perf.onebit_comm_bench import run_onebit_comm_bench
        return {"mesh": "real", **run_onebit_comm_bench()}
    return _run_proxy_bench("tests/perf/onebit_comm_bench.py")


def bench_serving():
    """Continuous batching vs the static-batch path on a mixed-length
    Poisson workload (tests/perf/serving_bench.py): requests/sec +
    decode tokens/sec for both systems and the speedup. Uses the bench
    module's default model sizing (CPU-safe); the paged engine itself is
    exercised at GPT-2-large scale by the decode section's configs."""
    from tests.perf.serving_bench import run_serving_bench
    out = run_serving_bench()
    tel = out["continuous"].get("telemetry", {})
    return {
        "requests_per_sec_continuous":
            out["continuous"]["requests_per_sec"],
        "requests_per_sec_static": out["static"]["requests_per_sec"],
        "decode_tokens_per_sec_continuous":
            out["continuous"]["decode_tokens_per_sec"],
        "decode_tokens_per_sec_static":
            out["static"]["decode_tokens_per_sec"],
        "speedup_requests_per_sec": out["speedup_requests_per_sec"],
        "mean_slot_occupancy": out["continuous"]["mean_slot_occupancy"],
        # serving telemetry headline numbers + the full snapshot
        "ttft_p50_s": tel.get("ttft_s", {}).get("p50"),
        "ttft_p99_s": tel.get("ttft_s", {}).get("p99"),
        "page_pool_occupancy_hwm": tel.get(
            "page_pool", {}).get("occupancy_hwm"),
        # watchdog verdict next to the percentiles (ISSUE 6): nonzero
        # trips mean the winning window was NOT clean — read the dump
        "watchdog_trips": sum(
            ((tel.get("watchdog") or {}).get("trips") or {}).values()),
        "watchdog_dump_id": tel.get("dump_id", 0),
        "watchdog_last_anomaly": (tel.get("last_anomaly") or {}).get(
            "rule"),
        "telemetry": tel,
        "workload": out["workload"],
    }


def bench_serving_hot_prefix():
    """Hot-prefix serving workload (ISSUE 9): N requests sharing an
    S-token system prompt, prefix cache off vs on. The headline gate is
    ``prefix_hit_rate`` (token-level: shared prompt tokens whose pages
    AND prefill compute were skipped); pages-saved, COW hits and the
    TTFT shift ride along. ``token_mismatches`` must be 0 — sharing may
    never change outputs."""
    from tests.perf.serving_bench import run_hot_prefix_bench
    return run_hot_prefix_bench()


def bench_serving_spec_decode():
    """Speculative decoding at b1 (ISSUE 9): plain engine vs n-gram
    self-drafting + one-dispatch multi-query verification, greedy,
    outputs asserted token-for-token identical. Headline gate:
    ``spec_decode_speedup`` (tok/s ratio). The CPU proxy sits in the
    dispatch-bound regime the real chip's b1 decode also lives in
    (one model call per token was the earlier b1 wall)."""
    from tests.perf.serving_bench import run_spec_decode_bench
    return run_spec_decode_bench()


def bench_serving_elastic():
    """Elastic preemption-tolerant serving (ISSUE 11): a Poisson trace
    through a 3-replica pool taking one injected hard kill + one
    graceful drain, both recovered from committed elastic snapshots
    (headline gate: ``recovered_fraction`` must stay 1.0;
    ``committed_token_loss`` must be 0 — greedy replay regenerates the
    identical streams), plus TTFT p99 under a burst overload with the
    watchdog-trip autoscaler on vs off."""
    from tests.perf.serving_bench import run_serving_elastic_bench
    return run_serving_elastic_bench()


def bench_serving_disagg():
    """Disaggregated prefill/decode serving (ISSUE 14): the BENCH_r08
    mixed-traffic trace served colocated vs through the DisaggRouter
    (prefill-role + decode-role engines, in-process page-handoff
    transport). Headline gate: ``ttft_p99_s_disagg`` (lower is better
    — prompt admission decoupled from decode slot residency); the
    colocated leg, the attribution breakdown, token parity and the
    page-pool leak fence ride the detail.

    Since r16 the section grows a ``transport: "process"`` leg
    (ISSUE 17): the same roles split across 2 REAL ranked OS
    processes, KV pages moving as versioned wire frames through the
    gloo host-bytes collective. Its headline gate is
    ``ttft_p99_s_disagg_xproc``; byte counters, the transport_s
    attribution and the cross-process parity/leak fences ride the
    ``xproc`` detail.

    Since r18 the scale-out leg (ISSUE 18): the identical trace over
    world=3 (2 decode ranks, targeted addressed frames, LPT
    balancing). Headline gate: ``decode_scaleout_tok_s_ratio``
    (world-3 aggregate decode tok/s over world-2's single rank,
    higher is better, ~2x when the balancer holds per-rank occupancy);
    the per-handoff wire-cost figures for both worlds, slot
    utilization per role, and the per-rank delivery split ride the
    ``xproc``/``xproc_w3`` details. The scale-out legs run a
    saturation geometry (16 reqs x 24 new tokens) so both world-3
    decode ranks hold single-rank slot occupancy; the ``xproc`` TTFT
    leg keeps the BENCH_r16 geometry (32 x 6) so
    ``ttft_p99_s_disagg_xproc`` stays comparable across runs."""
    from tests.perf.serving_bench import (run_disagg_bench,
                                          run_disagg_scaleout_bench,
                                          run_disagg_xproc_bench)
    out = run_disagg_bench()
    out["xproc"] = xp = run_disagg_xproc_bench()
    sc = run_disagg_scaleout_bench()
    out["xproc_w2_scaleout"] = sc["xproc_w2"]
    out["xproc_w3"] = sc["xproc_w3"]
    out["ttft_p99_s_disagg_xproc"] = xp["ttft_p99_s_disagg_xproc"]
    out["decode_scaleout_tok_s_ratio"] = \
        sc["decode_scaleout_tok_s_ratio"]
    out["wire_cost_ratio_w3_over_w2"] = sc["wire_cost_ratio_w3_over_w2"]
    return out


def bench_fault_recovery():
    """Fault-tolerant training supervisor MTTR (ISSUE 15): one
    SIGKILLed rank in a 2-process world under the
    runtime/elastic/supervisor.py state machine, measured with stdlib
    workers so the section prices the RECOVERY machinery (detect →
    teardown → backoff → respawn → first step), not an engine compile
    — the end-to-end engine legs are pinned by the slow
    tests/test_fault_tolerance.py acceptance tests. Reported:
    ``detect_s`` (rank death → supervisor incident record) and
    ``restart_to_first_step_s`` (death → the restarted epoch's first
    step line, the MTTR minus the resumed engine's compile)."""
    import sys
    import tempfile
    import textwrap
    import time as _time
    from deepspeed_tpu.runtime.elastic.supervisor import Supervisor
    from deepspeed_tpu.telemetry.recorder import FlightRecorder

    d = tempfile.mkdtemp(prefix="fault_recovery_")
    worker = os.path.join(d, "worker.py")
    with open(worker, "w") as fh:
        fh.write(textwrap.dedent("""
            import os, signal, time
            rank = int(os.environ["DSTPU_PROCESS_ID"])
            epoch = int(os.environ["DSTPU_RESTART_EPOCH"])
            print(f"FIRST_STEP {time.time()}", flush=True)
            if epoch == 0 and rank == 1:
                time.sleep(0.3)
                print(f"DYING {time.time()}", flush=True)
                os.kill(os.getpid(), signal.SIGKILL)
            time.sleep(0.8)          # the rest of the "epoch"
        """))
    rec = FlightRecorder()
    sup = Supervisor([sys.executable, worker], 2,
                     heartbeat_dir=os.path.join(d, "hb"),
                     grace_kill_s=2.0, max_restarts=2,
                     backoff_base_s=0.2, backoff_max_s=0.5,
                     poll_s=0.05, recorder=rec)
    t0 = _time.time()
    rc = sup.run(deadline_s=60)
    wall_s = _time.time() - t0
    if rc != 0 or sup.restarts != 1:
        raise RuntimeError(f"unexpected supervision outcome rc={rc} "
                           f"restarts={sup.restarts}")

    import re
    def stamp(path, tag):
        m = re.search(rf"{tag} ([0-9.]+)", open(path).read())
        return float(m.group(1)) if m else None
    t_die = stamp(sup.log_paths[(0, 1)], "DYING")
    t_up = stamp(sup.log_paths[(1, 0)], "FIRST_STEP")
    t_detect = next(ev["ts"] for ev in rec.events()
                    if ev["kind"] == "rank_exit")
    t_respawn = next(ev["ts"] for ev in rec.events()
                     if ev["kind"] == "supervisor_spawn"
                     and ev.get("restart_epoch") == 1)
    return {
        "world": 2,
        "detect_s": round(t_detect - t_die, 4),
        "teardown_respawn_s": round(t_respawn - t_detect, 4),
        "restart_to_first_step_s": round(t_up - t_die, 4),
        "supervision_wall_s": round(wall_s, 3),
        "poll_s": sup.poll_s,
        "grace_kill_s": sup.grace_kill_s,
        "note": "stdlib workers: MTTR of the supervisor machinery; "
                "engine resume cost = compile + snapshot load, pinned "
                "by the slow acceptance tests",
    }


def bench_sparse_attention(jnp):
    """Block-sparse vs dense-flash attention, fwd+bwd (the reference's
    sparse-attention headline: up to 6.1x on GPT-2 and 10x longer
    sequences, 2020-09-09 blog). BigBird (1 random + 3 window + 1 global
    block) at each sequence's measured-best layout block size — the
    kernel is DMA-ISSUE bound (~1.4 us per tile copy) with the r5
    grouped-row fusion amortizing the issue cost across R fused q-block
    rows per union tile. r5 sweep (tests/perf/bs_sweep_r5.py, grouped):
    S=4096 -> 1.08x/0.93x/1.36x at block 128/256/512; S=16384 ->
    2.30x/2.62x/2.75x — both cases run block 512. Near-dense layouts
    auto-fall back to the masked-dense path (the calibrated crossover in
    sparse_self_attention._kernel_beats_dense)."""
    import time
    import jax
    from deepspeed_tpu.ops.sparse_attention import BigBirdSparsityConfig
    from deepspeed_tpu.ops.pallas.blocksparse import blocksparse_attention
    from deepspeed_tpu.ops.pallas.flash_attention import flash_attention

    out = {}
    H, D = 16, 64
    for S, B, block in ((4096, 4, 512), (16384, 1, 512)):
        cfg = BigBirdSparsityConfig(num_heads=1, block=block,
                                    num_random_blocks=1,
                                    num_sliding_window_blocks=3,
                                    num_global_blocks=1)
        np.random.seed(0)
        layout = cfg.make_layout(S)
        density = float(layout[0].mean())
        rng = jax.random.PRNGKey(0)
        q, k, v = (jax.random.normal(jax.random.fold_in(rng, i),
                                     (B, H, S, D), jnp.bfloat16) * 0.3
                   for i in range(3))

        def sp_loss(q, k, v):
            return jnp.sum(blocksparse_attention(
                q, k, v, layout, block).astype(jnp.float32) ** 2)

        def dn_loss(q, k, v):
            return jnp.sum(flash_attention(
                q, k, v, causal=False).astype(jnp.float32) ** 2)

        def timed(fn):
            g = jax.jit(jax.grad(fn, argnums=(0, 1, 2)))
            r = g(q, k, v)
            float(jax.device_get(r[0].astype(jnp.float32).sum()))  # fence
            t0 = time.perf_counter()
            for _ in range(5):
                r = g(q, k, v)
            float(jax.device_get(r[0].astype(jnp.float32).sum()))
            return (time.perf_counter() - t0) / 5

        sp = timed(sp_loss)
        dn = timed(dn_loss)
        out[f"S{S}"] = {"sparse_ms": round(sp * 1000, 2),
                        "dense_flash_ms": round(dn * 1000, 2),
                        "speedup": round(dn / sp, 2),
                        "layout_block": block,
                        "layout_density": round(density, 3)}
    out["crossover_note"] = (
        "kernel is DMA-issue bound; speedup ~ 1/active_block_count. "
        "Auto mode falls back to masked-dense when the calibrated "
        "estimate predicts the kernel loses (near-dense layouts)")
    return out


def bench_decode(jnp):
    """GPT-2 large KV-cache decode tokens/sec. b1 at 2k context is the
    latency case; b32 uses a 512 context because 36 layers of bf16 KV at
    2k x 32 is ~12 GB (~24 GB with the scan carry's double buffer — past a
    16 GB chip either way once params/activations are resident)."""
    import time
    import jax
    from deepspeed_tpu.models.gpt2 import GPT2Config, GPT2LMHeadModel
    from deepspeed_tpu.models.gpt2_inference import (
        generate, convert_gpt2_params, quantize_gpt2_inference_params)
    out = {}
    cases = (
        # latency case: scan decode (one dispatch for the whole loop)
        ("b1_ctx2048", 1, 2048, dict(scan_decode=True)),
        # latency case, int8 weights + int8 KV (head-major cache): the
        # serving recipe — weight reads and cache reads both halve
        ("b1_ctx2048_int8", 1, 2048,
         dict(scan_decode=True, quantize_bits=8, kv_cache_bits=8)),
        # throughput, bf16 cache: ~6 GB of KV can't afford the scan
        # carry's double buffer, so per-token step loop
        ("b32_ctx512", 32, 512, dict(scan_decode=False)),
        # throughput, int8 KV cache: the halved cache fits the scan path
        # — the two serving features composing (2.1x over the step loop)
        ("b32_ctx512_int8kv", 32, 512,
         dict(scan_decode=True, kv_cache_bits=8)),
    )
    for name, bs, ctx, kw in cases:
        cfg = GPT2Config(vocab_size=50304, n_positions=ctx, n_embd=1280,
                         n_layer=36, n_head=20, dtype=jnp.bfloat16,
                         param_dtype=jnp.bfloat16, scan_layers=True)
        rng = np.random.RandomState(0)
        prompt = rng.randint(0, 50304, size=(bs, ctx - 80)).astype(np.int32)
        params = jax.jit(GPT2LMHeadModel(cfg).init)(
            jax.random.PRNGKey(0), prompt[:, :8])["params"]
        if kw.get("quantize_bits"):
            params = quantize_gpt2_inference_params(
                convert_gpt2_params(params, cfg))

        def run(new):
            toks = generate(cfg, params, prompt, max_new_tokens=new,
                            max_out_tokens=ctx, **kw)
            return float(jax.device_get(toks[0, -1]))

        run(4)                      # compile both lengths before timing
        run(68)
        # best of three difference-method windows
        best_dt, t_short = float("inf"), 0.0
        for _ in range(3):
            t0 = time.perf_counter()
            run(4)
            t_s = time.perf_counter() - t0
            t0 = time.perf_counter()
            run(68)
            t_l = time.perf_counter() - t0
            # prompt pass and fixed overheads cancel in the difference
            if t_l - t_s < best_dt:
                best_dt, t_short = t_l - t_s, t_s
        decode_tps = bs * 64 / best_dt
        out[name] = {"decode_tokens_per_sec": round(decode_tps, 1),
                     "prompt_plus_4_tokens_s": round(t_short, 3)}
        del params, run   # run's closure pins params otherwise
        jax.clear_caches()
    return out


def bench_llama_decode(jnp, bs=1, ctx=2048):
    """LLaMA-7B int8 serving through the fused RMS/SwiGLU/stacked-kernel
    loop (models/llama_inference.py). Weights are random int8 codes —
    decode reads exactly the bytes a converted checkpoint would, without
    materializing 13.5 GB of bf16 first. ROOFLINE: 6.74B int8 params =
    6.7 GB of weight reads per tick, so b1 is bounded at ~120 tok/s on
    an 819 GB/s chip no matter the software; batching shares the weight
    read across rows (the b8 case)."""
    import time
    import jax
    from deepspeed_tpu.models.llama import llama_7b
    from deepspeed_tpu.models.llama_inference import (
        llama_fast_generate, random_int8_serving_params)
    cfg = llama_7b(dtype=jnp.bfloat16, param_dtype=jnp.bfloat16,
                   max_seq_len=ctx)
    sparams = random_int8_serving_params(cfg)
    rs = np.random.RandomState(0)
    prompt = rs.randint(0, cfg.vocab_size,
                        size=(bs, ctx - 80)).astype(np.int32)

    def run(new):
        toks = llama_fast_generate(cfg, sparams, prompt,
                                   max_new_tokens=new,
                                   max_out_tokens=ctx, kv_cache_bits=8)
        return float(jax.device_get(toks[0, -1]))

    run(4)
    run(68)
    best = float("inf")
    for _ in range(3):
        t0 = time.perf_counter()
        run(4)
        t_s = time.perf_counter() - t0
        t0 = time.perf_counter()
        run(68)
        t_l = time.perf_counter() - t0
        best = min(best, t_l - t_s)
    return {"decode_tokens_per_sec": round(bs * 64 / best, 1),
            "params_b": round(cfg.num_params() / 1e9, 2),
            "weight_read_bound_tok_s_b1": 122}


def bench_moe(dstpu, make_mesh, MeshConfig, dev, batch_size=8, seq=512):
    """Expert-parallel MoE GPT-2 training throughput on one chip —
    8 experts, top-1 routing (the beyond-reference MoE subsystem's only
    perf line; regressions in the routing einsums show here)."""
    import time
    import jax
    import jax.numpy as jnp
    from deepspeed_tpu.models.gpt2 import GPT2Config, GPT2LMHeadModel
    cfg_m = GPT2Config(vocab_size=50304, n_positions=seq, n_embd=512,
                       n_layer=8, n_head=8, dtype=jnp.bfloat16,
                       scan_layers=True, moe_experts=8, moe_k=1)
    cfg = {
        "train_batch_size": batch_size,
        "bf16": {"enabled": True},
        "optimizer": {"type": "AdamW", "params": {"lr": 1e-4}},
        "steps_per_print": 1000,
    }
    engine, _, _, _ = dstpu.initialize(
        config=cfg, model=GPT2LMHeadModel(cfg_m),
        mesh=make_mesh(MeshConfig(data=1), devices=[dev]))
    rng = np.random.RandomState(0)
    batch = {"input_ids": rng.randint(
        0, 50304, size=(batch_size, seq)).astype(np.int32)}
    for _ in range(2):
        loss = engine.train_batch(batch)
    float(jax.device_get(loss))
    iters = 8
    t0 = time.perf_counter()
    for _ in range(iters):
        loss = engine.train_batch(batch)
    final = float(jax.device_get(loss))
    dt = (time.perf_counter() - t0) / iters
    return {"samples_per_sec": round(batch_size / dt, 1),
            "tokens_per_sec": round(batch_size * seq / dt, 1),
            "experts": 8, "loss": round(final, 3)}


def tiled_gpt2_init(cfg, seed=0):
    """Fast tiled-random GPT-2 init: every stacked layer shares one
    random block (the canonical copy — bench + tests/perf harnesses
    import this). Loss still falls because per-layer gradients differ
    from step one; avoids minutes of gaussians per GB on 1-core hosts."""
    import jax
    from deepspeed_tpu.models.gpt2 import GPT2LMHeadModel
    shapes = jax.eval_shape(
        GPT2LMHeadModel(cfg).init, jax.random.PRNGKey(0),
        np.zeros((1, 8), np.int32))["params"]
    rs = np.random.RandomState(seed)

    def leaf(path, s):
        names = [str(getattr(p, "key", p)) for p in path]
        if s.ndim == 3:          # scan-stacked [L, ...]: tile one layer
            one = (rs.standard_normal(s.shape[1:]).astype(np.float32)
                   / np.sqrt(max(s.shape[-2], 1))
                   if names[-1] == "kernel"
                   else np.zeros(s.shape[1:], np.float32))
            a = np.broadcast_to(one, s.shape)
        elif names[-1] in ("wte", "wpe"):
            a = rs.standard_normal(s.shape).astype(np.float32) * 0.02
        elif names[-1] == "scale":
            a = np.ones(s.shape, np.float32)
        else:
            a = np.zeros(s.shape, np.float32)
        return a.astype(np.dtype(s.dtype))
    import jax.tree_util as jtu
    return jtu.tree_map_with_path(leaf, shapes)


def bench_infinity_6b(dstpu, dev, steps=3):
    """THE scale proof: a multi-billion-param GPT-2 trains on this one
    16 GB chip (ZeRO-Infinity, runtime/zero/infinity.py) — compute
    params resting on NVMe, fp32 master + Adam moments in pinned_host,
    per-segment streamed fwd/bwd/update. Reference claim this answers:
    40B on a 32 GB V100 (ZeRO-Infinity blog, 1.25 B/GB).

    Two proven sizes: 6.25B (61 GB pinned state, 0.39 B/GB) and 9.41B
    (94 GB pinned, 0.59 B/GB — measured: loss 11.77 -> 10.06, 18.6 s
    steps, flat RSS — an earlier phase's harness, not re-measured).
    6.25B runs by default; DSTPU_BENCH_FORCE_9B=1 selects the 9.41B
    configuration.

    Init is TILED-random (every layer shares one random block): the
    bench measures the streaming engine, not 6.25 s of gaussians per GB
    on a 1-core host; loss still falls because gradients differ per
    layer from step one."""
    import shutil
    import time
    import jax
    import jax.numpy as jnp
    from deepspeed_tpu.models.gpt2 import GPT2Config, GPT2LMHeadModel

    def rss_mb():
        with open("/proc/self/status") as f:
            for line in f:
                if line.startswith("VmRSS"):
                    return int(line.split()[1]) / 1024
        return 0.0

    big = os.environ.get("DSTPU_BENCH_FORCE_9B")
    E, L, H = (4608, 36, 36) if big else (4096, 30, 32)
    cfg_m = GPT2Config(vocab_size=50304, n_positions=1024, n_embd=E,
                       n_layer=L, n_head=H, dtype=jnp.bfloat16,
                       param_dtype=jnp.bfloat16, scan_layers=True,
                       remat=True, loss_chunk=2048)
    segments = 6
    t0 = time.time()
    params = tiled_gpt2_init(cfg_m)
    init_s = time.time() - t0

    nvme = os.path.join(os.path.dirname(os.path.abspath(__file__)),
                        ".bench_nvme_6b")
    shutil.rmtree(nvme, ignore_errors=True)
    os.makedirs(nvme, exist_ok=True)
    try:
        t0 = time.time()
        engine, _, _, _ = dstpu.initialize(
            config={
                "train_batch_size": 4,
                "zero_optimization": {
                    "stage": 3,
                    "offload_param": {"device": "nvme", "nvme_path": nvme,
                                      "stream_segments": segments},
                    "offload_optimizer": {"device": "cpu"}},
                "optimizer": {"type": "AdamW", "params": {"lr": 1e-4}},
            },
            model=GPT2LMHeadModel(cfg_m), model_parameters=params)
        del params
        setup_s = time.time() - t0
        rng = np.random.RandomState(0)
        batch = {"input_ids": rng.randint(
            0, 50304, size=(4, 1024)).astype(np.int32)}
        t0 = time.time()
        l0 = engine.train_batch(batch)
        compile_step_s = time.time() - t0
        rss0 = rss_mb()
        ts, losses = [], [l0]
        for _ in range(steps):
            t0 = time.time()
            losses.append(engine.train_batch(batch))
            ts.append(time.time() - t0)
        return {
            "params_b": round(cfg_m.num_params() / 1e9, 3),
            "params_on_disk_mb": round(
                engine.params_on_disk_bytes() / 2**20, 1),
            "steady_step_s": round(min(ts), 2),
            "first_loss": round(losses[0], 3),
            "last_loss": round(losses[-1], 3),
            "host_rss_growth_mb_over_steps": round(rss_mb() - rss0, 1),
            "init_s": round(init_s, 1), "setup_s": round(setup_s, 1),
            "first_step_incl_compile_s": round(compile_step_s, 1),
            "hbm_gb": 16, "params_per_hbm_gb": round(
                cfg_m.num_params() / 1e9 / 16, 3),
        }
    finally:
        shutil.rmtree(nvme, ignore_errors=True)


def bench_elastic_ckpt(dstpu, make_mesh, MeshConfig, dev):
    """Async-snapshot overhead (ISSUE 7 acceptance): steady-state step
    time of a small GPT-2 run (a) with no checkpointing, (b) with an
    async snapshot every ``interval`` (4) steps — deliberately tight so
    the per-snapshot cost is measurable above step noise; begin stages
    + submits on the write-behind aio handle, the commit fence rides
    the next step boundary — and (c) the measured blocking
    engine.save_checkpoint stall the async path replaces. Embeds the
    sync-free telemetry counters (ckpt/bytes_written, ckpt/stall_s)
    the engine kept."""
    import shutil
    import tempfile
    import time
    import jax.numpy as jnp
    from deepspeed_tpu.models.gpt2 import GPT2Config, GPT2LMHeadModel
    from deepspeed_tpu.telemetry import default_registry

    cfg_m = GPT2Config(vocab_size=2048, n_positions=128, n_embd=256,
                       n_layer=4, n_head=4, dtype=jnp.float32,
                       scan_layers=True)
    steps = 8
    interval = 4
    tmp = tempfile.mkdtemp(prefix="dstpu_elastic_ckpt_")
    rng = np.random.RandomState(0)
    batch = {"input_ids": rng.randint(0, 2048, size=(4, 128))
             .astype(np.int32)}

    def run(tagdir, snapshot=False, fsync=False, o_direct=False):
        cfg = {
            "train_batch_size": 4,
            "optimizer": {"type": "AdamW", "params": {"lr": 1e-4}},
            "steps_per_print": 1000,
            "aio": {"o_direct": bool(o_direct)},
        }
        if snapshot:
            cfg["snapshot"] = {"path": os.path.join(tmp, tagdir),
                               "interval_steps": interval, "keep": 2,
                               "fsync": fsync}
        default_registry().reset()
        engine, _, _, _ = dstpu.initialize(
            config=cfg, model=GPT2LMHeadModel(cfg_m),
            mesh=make_mesh(MeshConfig(data=1), devices=[dev]))
        engine.train_batch(batch)        # compile
        engine.telemetry.reset()
        ts = []
        for _ in range(steps):
            t0 = time.perf_counter()
            engine.train_batch(batch)
            ts.append(time.perf_counter() - t0)
        # commit the possibly in-flight final-step snapshot BEFORE the
        # teardown rmtree races its aio writes (and so both begun
        # snapshots have a measured commit fence)
        engine.finalize_pending_snapshot()
        snap = engine.telemetry.snapshot("ckpt/")
        if engine._preemption is not None:
            engine._preemption.restore()
        return engine, sum(ts) / len(ts), snap

    try:
        eb, base_s, _ = run("never")
        t0 = time.perf_counter()
        eb.save_checkpoint(os.path.join(tmp, "blocking"))
        blocking_s = time.perf_counter() - t0
        # fsync OFF is the apples-to-apples overhead number (the
        # blocking save above never fsyncs either); the fsync-fenced
        # variant prices the durability barrier separately
        ea, async_s, snap = run("snaps", snapshot=True, fsync=False)
        _, async_fsync_s, _ = run("snaps_fsync", snapshot=True,
                                  fsync=True)
        # fsync honesty (ISSUE 20): the fsync price above is a BUFFERED
        # price (per-fd data flush out of the page cache); under
        # O_DIRECT the data is on-device at the drain and the remaining
        # fsync is metadata-only — the delta between these two
        # fsync-fenced runs is what the page cache was hiding
        _, async_direct_fsync_s, _ = run("snaps_direct", snapshot=True,
                                         fsync=True, o_direct=True)
        stall = snap["histograms"].get("ckpt/stall_s", {})
        n_snaps = max(int(snap["counters"].get("ckpt/snapshots", 0)), 1)
        bytes_per = snap["counters"].get("ckpt/bytes_written", 0) / n_snaps
        return {
            "step_s_base": round(base_s, 3),
            "step_s_async_ckpt": round(async_s, 3),
            "async_overhead_pct": round((async_s / base_s - 1) * 100, 1),
            "per_snapshot_overhead_s": round(
                (async_s - base_s) * steps / n_snaps, 3),
            # the acceptance-criterion number: the bench snapshots every
            # `interval` steps to make the per-snapshot cost measurable;
            # at the production default cadence (interval_steps=100) the
            # same cost amortizes to this share of step time
            "overhead_pct_at_interval_100": round(
                max(async_s - base_s, 0) * steps / n_snaps
                / (100 * base_s) * 100, 2),
            "step_s_async_ckpt_fsync": round(async_fsync_s, 3),
            "step_s_async_ckpt_fsync_o_direct": round(
                async_direct_fsync_s, 3),
            # per-snapshot durability-barrier price, both modes: what
            # fsync adds over the unfenced async run, amortized per
            # snapshot (buffered pays a data flush; direct pays only
            # the dirent/metadata flush)
            "fsync_overhead_s_per_snapshot_buffered": round(
                max(async_fsync_s - async_s, 0) * steps / n_snaps, 3),
            "fsync_overhead_s_per_snapshot_o_direct": round(
                max(async_direct_fsync_s - async_s, 0) * steps
                / n_snaps, 3),
            "blocking_save_s": round(blocking_s, 3),
            "blocking_share_if_per_interval_pct": round(
                blocking_s / (interval * base_s) * 100, 1),
            "ckpt_mb_per_snapshot": round(bytes_per / 2**20, 1),
            "ckpt_stall_s_mean": round(stall.get("mean", 0.0), 4),
            "ckpt_stall_s_max": round(stall.get("max", 0.0), 4),
            "snapshot_interval_steps": interval,
            "snapshots_per_run": n_snaps,
            "note": "overhead = host staging (d2h+memcpy+crc32) of the "
                    "full state; the aio writes + commit fence overlap "
                    "the next step (ckpt_stall_s is what the fence "
                    "actually blocked). CPU-harness caveat: the 2-core "
                    "box charges the staging AND the overlapped disk "
                    "writes to the same cores as compute — on a TPU "
                    "host the step is device-bound and the staging "
                    "share shrinks by the step-time ratio.",
        }
    finally:
        shutil.rmtree(tmp, ignore_errors=True)


def bench_nvme_param_tier(dstpu, make_mesh, MeshConfig, dev):
    """offload_param device=nvme evidence, blocking vs PIPELINED (PR 5):
    a small GPT-2 trains with its params resting on disk between steps,
    once with the r5 blocking park/unpark and once with the pipelined
    swap schedule (pipeline_read + pipeline_write + write-behind cache).
    Reports both steady step times, loss-trajectory equality, the
    sync-free swap telemetry (stall seconds hidden vs exposed, phase
    times), and a swap-cycle microbench on the same parameter set that
    isolates the tier's own cost from the model arithmetic (on a
    CPU-only harness the step is compute-bound, so the cycle number is
    the tier's own figure)."""
    import glob
    import tempfile
    import time
    import jax
    import jax.numpy as jnp
    from deepspeed_tpu.models.gpt2 import GPT2Config, GPT2LMHeadModel
    from deepspeed_tpu.telemetry import default_registry

    def rss_mb():
        with open("/proc/self/status") as f:
            for line in f:
                if line.startswith("VmRSS"):
                    return int(line.split()[1]) / 1024
        return 0.0

    cfg_m = GPT2Config(vocab_size=8192, n_positions=256, n_embd=512,
                       n_layer=8, n_head=8, dtype=jnp.bfloat16,
                       scan_layers=True)
    steps = 3

    def train_run(pipelined, o_direct=False):
        tmp = tempfile.mkdtemp(prefix="dstpu_nvme_param_")
        off = {"device": "nvme", "nvme_path": tmp}
        if pipelined:
            off.update({"pipeline_read": True, "pipeline_write": True,
                        "buffer_count": 4})
        cfg = {
            "train_batch_size": 4,
            "zero_optimization": {
                "stage": 2, "offload_param": off,
                "offload_optimizer": {"device": "cpu"}},
            "bf16": {"enabled": True},
            "aio": {"o_direct": bool(o_direct)},
            "optimizer": {"type": "AdamW", "params": {"lr": 1e-4}},
            "steps_per_print": 1000,
        }
        try:
            default_registry().reset()
            engine, _, _, _ = dstpu.initialize(
                config=cfg, model=GPT2LMHeadModel(cfg_m),
                mesh=make_mesh(MeshConfig(data=1), devices=[dev]))
            rng = np.random.RandomState(0)
            batch = {"input_ids": rng.randint(0, 8192, size=(4, 256))
                     .astype(np.int32)}
            l0 = float(engine.train_batch(batch))
            engine.telemetry.reset()
            rss0 = rss_mb()
            ts = []
            for _ in range(steps):
                t0 = time.perf_counter()
                l1 = float(engine.train_batch(batch))
                ts.append(time.perf_counter() - t0)
            snap = engine.telemetry.snapshot("swap/")
            disk = sum(os.path.getsize(p) for p in glob.glob(
                tmp + "/param_swap_*/param_*.swp"))
            parked = all(leaf.is_deleted() for leaf in
                         jax.tree_util.tree_leaves(engine.state.params))
            hist = snap["histograms"]
            counters = snap["counters"]
            step_s = min(ts)
            stall_sum = hist.get("swap/stall_s", {}).get("sum", 0.0)
            stall_per_step = stall_sum / steps
            return {
                "steady_step_s": round(step_s, 3),
                "first_loss": l0, "last_loss": l1,
                "parked": bool(parked),
                "disk_mb": round(disk / 2**20, 1),
                "rss_growth_mb": round(rss_mb() - rss0, 1),
                "stall_s_per_step": round(stall_per_step, 3),
                # matching statistics: total stall over total wall of the
                # SAME steps (min-step denominators overstate the share
                # on a ±20%-noise harness)
                "stall_share_of_step": round(stall_sum / sum(ts), 3),
                "unpark_s": round(hist.get("swap/unpark_s", {})
                                  .get("mean", 0.0), 3),
                "park_s": round(hist.get("swap/park_s", {})
                                .get("mean", 0.0), 3),
                "bytes_read_mb_per_step": round(
                    counters.get("swap/bytes_read", 0) / steps / 2**20, 1),
                "cache_hit_mb_per_step": round(
                    counters.get("swap/cache_hit_bytes", 0) / steps
                    / 2**20, 1),
                "bytes_written_mb_per_step": round(
                    counters.get("swap/bytes_written", 0) / steps
                    / 2**20, 1),
                # device-side bandwidth gauges: set by the alignment
                # layer over DIRECT bytes only, so buffered runs report 0
                "device_read_mb_s": snap["gauges"].get(
                    "swap/device_read_mb_s", 0.0),
                "device_write_mb_s": snap["gauges"].get(
                    "swap/device_write_mb_s", 0.0),
            }
        finally:
            import shutil
            shutil.rmtree(tmp, ignore_errors=True)

    def swap_cycle_run(pipelined, leaves, shardings, compute_s,
                       cycles=5, buffer_count=4, aio_cfg=None):
        """The tier's own cost, isolated: park + [a fixed jitted compute
        burst standing in for the next step's fwd+bwd] + unpark, on the
        real param set. ``exposed_s`` = cycle time minus the burst — the
        swap seconds the step actually pays. Blocking pays write+read
        serially; the pipelined schedule write-behinds into the burst and
        serves the re-read from the pool cache + page-cache window."""
        from deepspeed_tpu.runtime.swap_tensor import PartitionedParamSwapper
        import shutil
        tmp = tempfile.mkdtemp(prefix="dstpu_nvme_cycle_")
        # burst sized to compute_s on this machine (jitted matmul chain)
        import jax.numpy as jnp2
        a = jnp2.asarray(np.random.RandomState(0)
                         .randn(1024, 1024).astype(np.float32))
        burst_fn = jax.jit(lambda x, n: jax.lax.fori_loop(
            0, n, lambda _, y: jnp2.tanh(y @ y) * 0.5 + y * 0.5, x))
        burst_fn(a, 1).block_until_ready()
        t0 = time.perf_counter()
        burst_fn(a, 8).block_until_ready()
        per8 = time.perf_counter() - t0
        n_iter = max(1, int(round(8 * compute_s / max(per8, 1e-6))))
        t0 = time.perf_counter()
        burst_fn(a, n_iter).block_until_ready()
        burst_s = time.perf_counter() - t0
        try:
            sw = PartitionedParamSwapper(
                tmp, aio_config=aio_cfg,
                pipeline_read=pipelined, pipeline_write=pipelined,
                buffer_count=buffer_count)
            sw.write_all(leaves)
            cur = sw.swap_in_device(shardings)
            t_first = None
            ts = []
            for c in range(cycles):
                t0 = time.perf_counter()
                sw.swap_out_device(cur)
                for leaf in cur:
                    leaf.delete()
                # the "next step's compute": write-behind I/O (aio
                # threads + kernel) runs while XLA owns the cores
                burst_fn(a, n_iter).block_until_ready()
                cur = sw.swap_in_device(shardings)
                dt = time.perf_counter() - t0
                if c == 0:
                    t_first = dt
                else:
                    ts.append(dt)
            sw.release()
            cycle = min(ts)
            return {"cycle_s": round(cycle, 3),
                    "burst_s": round(burst_s, 3),
                    "exposed_s": round(max(cycle - burst_s, 0.0), 3),
                    "first_cycle_s": round(t_first, 3)}
        finally:
            shutil.rmtree(tmp, ignore_errors=True)

    blocking = train_run(False)
    pipelined = train_run(True)
    # the honest mode (ISSUE 20): same pipelined schedule, swap
    # files opened O_DIRECT — bytes hit the device, not the page
    # cache, so these are the numbers the 2104.07857 claim is about
    direct = train_run(True, o_direct=True)
    losses_equal = (blocking["first_loss"] == pipelined["first_loss"]
                    and abs(blocking["last_loss"]
                            - pipelined["last_loss"]) < 1e-4)
    losses_equal_direct = (
        direct["first_loss"] == pipelined["first_loss"]
        and abs(direct["last_loss"] - pipelined["last_loss"]) < 1e-4)

    # microbench on the real leaf set (host-side init, no training)
    model = GPT2LMHeadModel(cfg_m)
    params = model.init(
        jax.random.PRNGKey(0),
        np.zeros((1, 8), np.int32))["params"]
    from jax.sharding import NamedSharding, PartitionSpec
    mesh = make_mesh(MeshConfig(data=1), devices=[dev])
    leaves = jax.tree_util.tree_leaves(params)
    shardings = [NamedSharding(mesh, PartitionSpec())] * len(leaves)
    cyc_b = swap_cycle_run(False, leaves, shardings, compute_s=0.4)
    cyc_p = swap_cycle_run(True, leaves, shardings, compute_s=0.4)
    # hot-set pool: buffer_count sized to the leaf count (the
    # reference's generously-sized pinned pool) — every re-read is a
    # cache hit and writes drain behind the next step's compute
    cyc_h = swap_cycle_run(True, leaves, shardings, compute_s=0.4,
                           buffer_count=len(leaves))
    from types import SimpleNamespace
    from deepspeed_tpu.ops.native.aio import o_direct_fallback_latched
    cyc_d = swap_cycle_run(
        True, leaves, shardings, compute_s=0.4,
        aio_cfg=SimpleNamespace(o_direct=True))

    return {
        "params_b": round(cfg_m.num_params() / 1e9, 4),
        "params_on_disk_mb": pipelined["disk_mb"],
        "params_parked_between_steps": bool(
            blocking["parked"] and pipelined["parked"]),
        # headline stays the r5-shape metric, now from the PIPELINED
        # tier; blocking_step_s is the same-harness baseline
        "steady_step_s": pipelined["steady_step_s"],
        "blocking_step_s": blocking["steady_step_s"],
        "step_speedup": round(blocking["steady_step_s"]
                              / pipelined["steady_step_s"], 3),
        "losses_equal_blocking_vs_pipelined": bool(losses_equal),
        "first_loss": pipelined["first_loss"],
        "last_loss": pipelined["last_loss"],
        # the tier's own cost, arithmetic excluded: one full
        # park+unpark of every leaf (write-behind + cache + sliding
        # read window vs the r5 sync loop)
        "swap_cycle": {
            "blocking_s": cyc_b["cycle_s"],
            "pipelined_s": cyc_p["cycle_s"],
            "hotset_pool_s": cyc_h["cycle_s"],
            "compute_burst_s": cyc_b["burst_s"],
            "blocking_exposed_s": cyc_b["exposed_s"],
            "pipelined_exposed_s": cyc_p["exposed_s"],
            "hotset_pool_exposed_s": cyc_h["exposed_s"],
            # swap seconds the step pays, arithmetic excluded
            "exposed_speedup": round(
                cyc_b["exposed_s"] / max(cyc_p["exposed_s"], 1e-9), 2),
            "hotset_exposed_speedup": round(
                cyc_b["exposed_s"] / max(cyc_h["exposed_s"], 1e-9), 2),
            "first_cycle_blocking_s": cyc_b["first_cycle_s"],
            "first_cycle_pipelined_s": cyc_p["first_cycle_s"],
        },
        # ISSUE 20: buffered-vs-direct on the identical schedule.
        # Buffered first reads were page-cache-warm (write_all just
        # populated the cache), so buffered first≈steady is a cache
        # artifact; O_DIRECT first≈steady is the honest version —
        # every pass pays the device, and the ratio should sit near
        # 1.0 because there is no cache to warm
        "o_direct": {
            "steady_step_s": direct["steady_step_s"],
            "step_s_delta_vs_buffered_pct": round(
                (direct["steady_step_s"]
                 / pipelined["steady_step_s"] - 1) * 100, 1),
            "losses_equal_vs_buffered": bool(losses_equal_direct),
            "stall_s_per_step": direct["stall_s_per_step"],
            "stall_share_of_step": direct["stall_share_of_step"],
            "device_read_mb_s": direct["device_read_mb_s"],
            "device_write_mb_s": direct["device_write_mb_s"],
            "cycle_s": cyc_d["cycle_s"],
            "exposed_s": cyc_d["exposed_s"],
            "first_cycle_s": cyc_d["first_cycle_s"],
            "first_vs_steady_cycle": round(
                cyc_d["first_cycle_s"] / max(cyc_d["cycle_s"],
                                             1e-9), 2),
            "fallback_latched": o_direct_fallback_latched(),
        },
        "swap_stall": {
            "blocking_s_per_step": blocking["stall_s_per_step"],
            "pipelined_s_per_step": pipelined["stall_s_per_step"],
            "blocking_share_of_step": blocking["stall_share_of_step"],
            "pipelined_share_of_step":
                pipelined["stall_share_of_step"],
        },
        "swap_phases": {
            "blocking": {k: blocking[k] for k in
                         ("unpark_s", "park_s",
                          "bytes_read_mb_per_step",
                          "cache_hit_mb_per_step",
                          "bytes_written_mb_per_step")},
            "pipelined": {k: pipelined[k] for k in
                          ("unpark_s", "park_s",
                           "bytes_read_mb_per_step",
                           "cache_hit_mb_per_step",
                           "bytes_written_mb_per_step")},
        },
        "host_rss_growth_mb_over_steps": pipelined["rss_growth_mb"],
        "compute_note": "CPU-only harness: the step is model-"
                        "arithmetic-bound (fwd+bwd ~9s, swap ~0.15s, "
                        "run-to-run step noise ~20%), AND the swap "
                        "files ride the guest page cache (no O_DIRECT"
                        "/per-step fsync), so the kernel already "
                        "write-behinds and every mode is memcpy-"
                        "bound — the pipelined schedule shows up as "
                        "the halved stall share, not a step multiple. "
                        "Not measured on the chip.",
    }


def bench_nvme_xl(dstpu, make_mesh, MeshConfig, dev):
    """ISSUE 20 acceptance: the 10B+ single-chip run on the honest
    (O_DIRECT) NVMe path. Two legs:

    - **parity**: a small GPT-2 trains with params in memory vs resting
      on NVMe through the O_DIRECT swap tier — identical host-optimizer
      math, so the loss trajectories must match exactly (the direct
      path changes WHERE bytes live, never what they are);
    - **scale**: a 10.6B-parameter tiled bf16 leaf set (GPT-2 shapes at
      n_embd=5120, 33 layers: qkv/proj/mlp_in/mlp_out per layer + a
      row-tiled embedding) parks to disk through a GENERATOR (host
      residency: one leaf), then streams back twice through
      ``swap_in_stream``'s bounded staging window with a host touch +
      sampled content check per leaf. Under O_DIRECT there is no page
      cache to warm, so pass 1 ≈ pass 2 (the buffered tier's 5x
      first-read cliff was a cache artifact), and host RSS stays at
      the staging window no matter the model size.

    Shrink knob: DSTPU_NVME_XL_LAYERS (default 33) scales the layer
    count for CI boxes without 25 GB of scratch disk."""
    import shutil
    import tempfile
    import time
    from types import SimpleNamespace
    import jax.numpy as jnp
    import ml_dtypes
    from deepspeed_tpu.models.gpt2 import GPT2Config, GPT2LMHeadModel
    from deepspeed_tpu.ops.native.aio import (
        aligned_empty, o_direct_fallback_latched)
    from deepspeed_tpu.runtime.swap_tensor import PartitionedParamSwapper
    from deepspeed_tpu.telemetry import default_registry

    def rss_mb():
        with open("/proc/self/status") as f:
            for line in f:
                if line.startswith("VmRSS"):
                    return int(line.split()[1]) / 1024
        return 0.0

    # ---- leg 1: small-scale loss parity, in-memory vs nvme+O_DIRECT --
    cfg_m = GPT2Config(vocab_size=2048, n_positions=128, n_embd=256,
                       n_layer=4, n_head=4, dtype=jnp.float32,
                       scan_layers=True)
    rng = np.random.RandomState(0)
    batch = {"input_ids": rng.randint(0, 2048, size=(4, 128))
             .astype(np.int32)}

    def parity_run(nvme, tmp):
        zo = {"stage": 2, "offload_optimizer": {"device": "cpu"}}
        if nvme:
            zo["offload_param"] = {
                "device": "nvme", "nvme_path": tmp,
                "pipeline_read": True, "pipeline_write": True,
                "buffer_count": 4}
        cfg = {
            "train_batch_size": 4,
            "zero_optimization": zo,
            "aio": {"o_direct": bool(nvme)},
            "optimizer": {"type": "AdamW", "params": {"lr": 1e-4}},
            "steps_per_print": 1000,
        }
        default_registry().reset()
        engine, _, _, _ = dstpu.initialize(
            config=cfg, model=GPT2LMHeadModel(cfg_m),
            mesh=make_mesh(MeshConfig(data=1), devices=[dev]))
        return [float(engine.train_batch(batch)) for _ in range(4)]

    tmp = tempfile.mkdtemp(prefix="dstpu_nvme_xl_")
    try:
        mem_losses = parity_run(False, tmp)
        nvme_losses = parity_run(True, tmp)
        parity = all(abs(a - b) < 1e-6
                     for a, b in zip(mem_losses, nvme_losses))

        # ---- leg 2: the 10B+ O_DIRECT stream -------------------------
        E = 5120
        L = int(os.environ.get("DSTPU_NVME_XL_LAYERS", 33))
        vocab = 50304
        dt = np.dtype(ml_dtypes.bfloat16)
        shapes = []
        for _ in range(L):
            shapes += [(E, 3 * E), (E, E), (E, 4 * E), (4 * E, E)]
        rows = vocab
        while rows > 0:                    # row-tiled embedding
            shapes.append((min(rows, E), E))
            rows -= min(rows, E)
        total_params = sum(int(np.prod(s)) for s in shapes)
        total_bytes = total_params * dt.itemsize
        free = shutil.disk_usage(tmp).free
        if free < total_bytes * 1.15:
            return {"skipped": f"needs {total_bytes / 2**30:.1f} GiB "
                               f"scratch, only {free / 2**30:.1f} free",
                    "parity_losses_equal": bool(parity)}

        max_nbytes = max(int(np.prod(s)) * dt.itemsize for s in shapes)
        # one reusable pattern buffer: every leaf is the pattern with
        # its index stamped into the first 8 bytes (cheap to generate,
        # cheap to verify by sample on the way back)
        pat = aligned_empty(max_nbytes)
        pat[:] = np.tile(
            np.frombuffer(np.random.RandomState(7).bytes(1 << 20),
                          np.uint8),
            max_nbytes // (1 << 20) + 1)[:max_nbytes]

        def leaf_bytes(i, nbytes):
            view = pat[:nbytes]
            view[:8] = np.frombuffer(
                np.int64(i).tobytes(), np.uint8)
            return view

        def gen():
            for i, s in enumerate(shapes):
                nb = int(np.prod(s)) * dt.itemsize
                yield leaf_bytes(i, nb).view(dt).reshape(s)

        sw = PartitionedParamSwapper(
            tmp, aio_config=SimpleNamespace(o_direct=True),
            pipeline_read=True, buffer_count=4)
        rss0 = rss_mb()
        t0 = time.perf_counter()
        sw.write_all(gen())
        write_s = time.perf_counter() - t0
        disk = sum(os.path.getsize(sw._path(i))
                   for i in range(len(shapes)))

        def stream_pass():
            t0 = time.perf_counter()
            touched = 0
            verified = 0
            for i, view in sw.swap_in_stream():
                raw = view.view(np.uint8).reshape(-1)
                touched += int(raw[-4096:].sum())   # the host "compute"
                stamp = int(np.frombuffer(raw[:8].tobytes(),
                                          np.int64)[0])
                off = 1 << 16
                ok = (stamp == i and np.array_equal(
                    raw[off:off + 4096], pat[off:off + 4096]))
                verified += int(ok)
            return time.perf_counter() - t0, verified, touched

        pass1_s, ok1, _ = stream_pass()
        pass2_s, ok2, _ = stream_pass()
        rss_peak_growth = rss_mb() - rss0
        sw.release()
        reg = default_registry()
        return {
            "max_params_b": round(total_params / 1e9, 2),
            "leaves": len(shapes),
            "layers": L,
            "dtype": str(dt),
            "disk_gb": round(disk / 2**30, 2),
            "write_s": round(write_s, 1),
            "write_mb_s": round(total_bytes / write_s / 2**20, 1),
            "first_pass_s": round(pass1_s, 1),
            "steady_pass_s": round(pass2_s, 1),
            "read_mb_s_first": round(total_bytes / pass1_s / 2**20, 1),
            "read_mb_s_steady": round(total_bytes / pass2_s / 2**20, 1),
            # ≈1.0 is the point: no page cache, no first-read cliff
            "first_vs_steady_pass": round(pass1_s / pass2_s, 2),
            "leaves_verified_pass1": ok1,
            "leaves_verified_pass2": ok2,
            "host_rss_growth_mb": round(rss_peak_growth, 1),
            "device_read_mb_s_gauge": reg.peek_gauge(
                "swap/device_read_mb_s"),
            "device_write_mb_s_gauge": reg.peek_gauge(
                "swap/device_write_mb_s"),
            "o_direct_fallback_latched": o_direct_fallback_latched(),
            "parity_losses_equal": bool(parity),
            "parity_losses_mem": mem_losses,
            "parity_losses_nvme": nvme_losses,
            "note": "host residency while streaming = the staging "
                    "window (buffer_count slots of the largest leaf), "
                    "not the model: the 10.6B bf16 set is ~20 GiB on "
                    "disk against a window under 1 GiB. On a "
                    "virtualized disk first_vs_steady_pass can exceed "
                    "1 even under O_DIRECT — the guest bypasses ITS "
                    "cache but the virtio host may still serve "
                    "re-reads; the nvme_param o_direct "
                    "first_vs_steady_cycle (fresh files per cycle) is "
                    "the cache-independence pin",
        }
    finally:
        shutil.rmtree(tmp, ignore_errors=True)


def bench_bert(dstpu, make_mesh, MeshConfig, dev, batch_size=128, seq=128):
    """BERT-base MLM pretraining step throughput (samples/sec)."""
    import time
    import jax
    import jax.numpy as jnp
    from deepspeed_tpu.models.bert import bert_base, BertForPreTraining, \
        pretraining_loss

    model_cfg = bert_base(dtype=jnp.bfloat16, scan_layers=True)
    model = BertForPreTraining(model_cfg)

    def loss_fn(params, batch):
        out = model.apply({"params": params}, batch["input_ids"],
                          batch["attention_mask"])
        return pretraining_loss(out, batch)

    cfg = {
        "train_batch_size": batch_size,
        "bf16": {"enabled": True},
        "optimizer": {"type": "Adam", "params": {"lr": 1e-4}},
        "steps_per_print": 1000,
    }
    engine, _, _, _ = dstpu.initialize(
        config=cfg, model=model, loss_fn=loss_fn,
        mesh=make_mesh(MeshConfig(data=1), devices=[dev]))
    rng = np.random.RandomState(0)
    labels = rng.randint(0, model_cfg.vocab_size,
                         size=(batch_size, seq)).astype(np.int32)
    mlm_labels = np.where(rng.rand(batch_size, seq) < 0.15, labels, -100) \
        .astype(np.int32)
    batch = {
        "input_ids": labels,
        "attention_mask": np.ones((batch_size, seq), np.int32),
        "mlm_labels": mlm_labels,
        "nsp_labels": rng.randint(0, 2, size=(batch_size,)).astype(np.int32),
    }
    for _ in range(2):
        loss = engine.train_batch(batch)
    float(jax.device_get(loss))
    iters = 12
    t0 = time.perf_counter()
    for _ in range(iters):
        loss = engine.train_batch(batch)
    float(jax.device_get(loss))
    dt = (time.perf_counter() - t0) / iters
    return round(batch_size / dt, 1)


if __name__ == "__main__":
    sys.exit(main())
