"""chip_smoke.py — the quickest proof that the system still starts on the chip.

Drives the main path once through the entry points a user calls, at the
full width AND depth of GPT-2 large (774M: n_embd 1280 x 36 layers x 20
heads, vocab 50304, seq 1024, bf16), weights random from ``--seed``:

* train phase — ``dstpu.initialize`` + ``engine.train_batch``: ZeRO stage 3,
  AdamW (bf16 first moment), bf16 grads, clip 1.0, global batch 8; 5 steps
  on one repeated batch, then ``save_checkpoint`` / ``load_checkpoint`` into
  a fresh engine and one more step (the README quickstart flow);
* serve phase — ``serving.build_engine`` + ``eng.serve``: bf16 paged cache,
  4 requests of 64-256 prompt tokens, 32 new tokens each, checked against a
  float32 ``model.apply`` re-forward and greedy ``gpt2_inference.generate``.

``--chips 4`` runs instead ONLY the path across chips and what it is compared
with: the same model under ``MeshConfig(data=4)`` ZeRO-3 against a one-device
mesh, same seed and batch, in this one process.

One process holds the chip: the phases run in sequence here and free their
device state in between; nothing is started as a child. Each phase prints one
JSON line; times on those lines are host wall clock around
``block_until_ready`` and seconds JAX spent tracing+lowering+compiling — named
as what they are, not metrics. The LAST line is the contract:

    {"ok": true, "device": {"platform": "tpu", "kind": "...", "count": 1}}

It is ``"ok": false`` with a non-zero exit when the first device is not a TPU,
when any check fails or when a phase raises. ``--rehearse-cpu`` shrinks the
model and runs the same control flow on the CPU (Pallas kernels interpreted) to
find wrong paths before chip time is spent; a rehearsal never prints
``"ok": true``.
"""

import argparse
import gc
import json
import os
import shutil
import sys
import tempfile
import time
import traceback

import numpy as np

# GPT-2 large at its published widths and full depth, and the rehearsal's
# shrunken stand-in
MODEL = dict(vocab_size=50304, n_positions=1024, n_embd=1280, n_layer=36,
             n_head=20, remat=True, remat_policy="dots_flash_fc_lean",
             loss_chunk=1024, scan_layers=True)
REHEARSAL_MODEL = dict(MODEL, vocab_size=512, n_positions=128, n_embd=64,
                       n_layer=2, n_head=2, loss_chunk=128)
BATCH = 8
TRAIN_STEPS = 5
CHIPS4_STEPS = 3
SERVING = {"slots": 4, "page_size": 16, "max_pages_per_slot": 20}
PROMPT_LENS = (64, 128, 200, 256)     # one request each, 64-256 tokens
NEW_TOKENS = 32

# Stated tolerances (bf16 has an 8-bit mantissa, eps = 2^-8 ~ 0.4%):
# first-token logits of the bf16 paged server vs the float32 re-forward —
# 36 layers of bf16 matmuls on O(1) logits; a wrong kernel is off by O(1)
LOGIT_ATOL = 0.08
# dp=4 vs one device, same seed and batch: per-sample math is the same, the
# bf16 gradient all-reduce and matmul tilings differ — on a loss of ~10.8
LOSS_ATOL_CHIPS4 = 0.03
# every device holds a quarter of params + optimizer state, give or take
# the small leaves ZeRO-3 keeps replicated
SPREAD_RTOL = 0.03


def model_config(rehearse):
    import jax.numpy as jnp
    from deepspeed_tpu.models.gpt2 import GPT2Config
    return GPT2Config(dtype=jnp.bfloat16,
                      **(REHEARSAL_MODEL if rehearse else MODEL))


def train_config(seed, rehearse):
    # ZeRO-3 keeps leaves under stage3_param_persistence_threshold (1e5
    # elements) replicated: 0.08% of GPT-2 large, but every leaf of the
    # rehearsal's tiny model — which therefore shards everything, or its
    # spread check would check nothing
    zero = {"stage": 3, "stage3_param_persistence_threshold": 0} \
        if rehearse else {"stage": 3}
    return {
        "train_batch_size": BATCH,
        "gradient_accumulation_steps": 1,
        "seed": seed,
        "zero_optimization": zero,
        "bf16": {"enabled": True},
        "data_types": {"grad_dtype": "bf16"},
        "gradient_clipping": 1.0,
        "optimizer": {"type": "AdamW",
                      "params": {"lr": 1e-4, "weight_decay": 0.01,
                                 "moment_dtype": "bf16"}},
        "steps_per_print": 1000,
    }


def train_batch_for(cfg, seed):
    rs = np.random.RandomState(seed)
    return {"input_ids": rs.randint(
        0, cfg.vocab_size, size=(BATCH, cfg.n_positions)).astype(np.int32)}


class CompileClock:
    """What JAX reports of its own compiling, read as deltas around a phase:
    ``compile_s`` is seconds inside the backend compile (the XLA/Mosaic
    compile, or the fetch on a persistent-cache hit), ``trace_lower_s`` the
    seconds tracing to jaxprs and lowering to MLIR (nested jits count in
    their parents too), plus persistent-cache hits and misses."""

    EVENTS = {"/jax/core/compile/backend_compile_duration": "compile_s",
              "/jax/core/compile/jaxpr_trace_duration": "trace_lower_s",
              "/jax/core/compile/jaxpr_to_mlir_module_duration":
                  "trace_lower_s",
              "/jax/compilation_cache/cache_hits": "compile_cache_hits",
              "/jax/compilation_cache/cache_misses": "compile_cache_misses"}

    def __init__(self):
        import jax.monitoring as mon
        self.totals = dict.fromkeys(self.EVENTS.values(), 0)
        mon.register_event_duration_secs_listener(self._on_event)
        mon.register_event_listener(self._on_event)

    def _on_event(self, event, secs=1, **_):
        if event in self.EVENTS:
            self.totals[self.EVENTS[event]] += secs

    def mark(self):
        return dict(self.totals)

    def since(self, mark):
        return {k: round(v - mark[k], 2) for k, v in self.totals.items()}


def device_info():
    import jax
    d = jax.devices()[0]
    return {"platform": d.platform, "kind": d.device_kind,
            "count": len(jax.devices())}


def emit(phase, t0, clock, mark, checks, **fields):
    """One JSON line for a phase; returns whether all its checks held."""
    import jax
    ok = all(checks.values())
    print(json.dumps({"phase": phase, "ok": ok, "checks": checks,
                      "wall_s": round(time.time() - t0, 2),
                      **clock.since(mark), **fields, **device_info(),
                      "jax": jax.__version__}), flush=True)
    return ok


def free_device_state():
    import jax
    gc.collect()
    jax.clear_caches()


def timed_steps(engine, batch, n):
    """n train steps; per-step loss and host wall seconds around
    block_until_ready."""
    import jax
    losses, secs = [], []
    for _ in range(n):
        t = time.time()
        loss = jax.block_until_ready(engine.train_batch(batch))
        secs.append(round(time.time() - t, 3))
        losses.append(float(loss))
    return losses, secs


def resident_bytes_per_device(state):
    """Bytes of the train state (params + optimizer) each device holds."""
    import jax
    per = {}
    for leaf in jax.tree_util.tree_leaves(state):
        for shard in leaf.addressable_shards:
            per[shard.device.id] = per.get(shard.device.id, 0) \
                + shard.data.nbytes
    return per


# ------------------------------------------------------------------ train

def train_phase(args, clock):
    import deepspeed_tpu as dstpu
    from deepspeed_tpu.models.gpt2 import GPT2LMHeadModel
    from deepspeed_tpu.parallel.mesh import make_mesh, MeshConfig
    import jax

    t0, mark = time.time(), clock.mark()
    cfg = model_config(args.rehearse_cpu)
    batch = train_batch_for(cfg, args.seed)
    mesh = make_mesh(MeshConfig(data=1), devices=jax.devices()[:1])

    def new_engine():
        engine, _, _, _ = dstpu.initialize(
            config=train_config(args.seed, args.rehearse_cpu), model=GPT2LMHeadModel(cfg),
            mesh=mesh)
        return engine

    engine = new_engine()
    losses, step_s = timed_steps(engine, batch, TRAIN_STEPS)
    first_engine = clock.since(mark)

    # the step that ran must hold the Pallas flash kernels, not the O(S^2)
    # reference attention; on CPU the model takes the reference by rule
    text = engine.lower_train_step(batch).as_text()
    kernels = sorted(k for k in ("_fwd_kernel", "_bwd_fused_kernel")
                     if f'kernel_name = "{k}"' in text)
    flash_ok = "tpu_custom_call" in text and len(kernels) == 2

    ckpt_dir = tempfile.mkdtemp(prefix="chip_smoke_ckpt_")
    try:
        t = time.time()
        engine.save_checkpoint(ckpt_dir)
        save_s = round(time.time() - t, 2)
        # the uninterrupted run's next step: what the resumed one must give
        want, _ = timed_steps(engine, batch, 1)
        del engine
        free_device_state()

        mark2 = clock.mark()
        engine = new_engine()
        t = time.time()
        tag, _ = engine.load_checkpoint(ckpt_dir)
        load_s = round(time.time() - t, 2)
        got, _ = timed_steps(engine, batch, 1)
        second_engine = clock.since(mark2)
    finally:
        shutil.rmtree(ckpt_dir, ignore_errors=True)
    resumed_step = engine.global_steps
    del engine
    free_device_state()

    checks = {
        "losses_finite": bool(np.all(np.isfinite(losses + want + got))),
        "loss_falls": losses[-1] < losses[0],
        "resumed_loss_continues_run": bool(
            tag is not None and resumed_step == TRAIN_STEPS + 1
            and np.isclose(got[0], want[0], rtol=1e-3, atol=0)),
    }
    if not args.rehearse_cpu:
        checks["flash_kernel_in_step"] = flash_ok
    return emit(
        "train", t0, clock, mark, checks,
        model={k: getattr(cfg, k) for k in (
            "n_embd", "n_layer", "n_head", "vocab_size", "n_positions")},
        zero_stage=3, batch=BATCH, losses=losses, step_wall_s=step_s,
        loss_after_resume=got[0], loss_uninterrupted=want[0],
        pallas_kernels_in_step=kernels, checkpoint_save_s=save_s,
        checkpoint_load_s=load_s, first_engine=first_engine,
        resumed_engine=second_engine)


# ------------------------------------------------------------------ serve

def serve_phase(args, clock):
    import dataclasses
    import jax
    import jax.numpy as jnp
    import deepspeed_tpu.serving as serving
    from deepspeed_tpu.models.gpt2 import GPT2LMHeadModel
    from deepspeed_tpu.models.gpt2_inference import generate
    from deepspeed_tpu.serving.paged_cache import (TRASH_BLOCK,
                                                   padded_prefill_inputs)

    t0, mark = time.time(), clock.mark()
    cfg = dataclasses.replace(model_config(args.rehearse_cpu), remat=False,
                              loss_chunk=0)
    lens = [min(n, cfg.n_positions - NEW_TOKENS) for n in PROMPT_LENS]
    rs = np.random.RandomState(args.seed + 1)
    prompts = [rs.randint(0, cfg.vocab_size, size=(n,)).astype(np.int32)
               for n in lens]
    model = GPT2LMHeadModel(cfg)
    params = jax.jit(model.init)(
        jax.random.PRNGKey(args.seed), jnp.zeros((1, 8), jnp.int32))["params"]

    eng = serving.build_engine("gpt2", cfg, params,
                               config={"serving": SERVING})
    t = time.time()
    done = eng.serve([serving.Request(i, p, max_new_tokens=NEW_TOKENS)
                      for i, p in enumerate(prompts)])
    serve_s = round(time.time() - t, 2)
    counts = [len(done[i].generated) if i in done else 0
              for i in range(len(prompts))]

    # float32 reference: a plain full re-forward, reference attention
    ref_model = GPT2LMHeadModel(dataclasses.replace(
        cfg, dtype=jnp.float32, use_flash=False))
    ref_logits = jax.jit(
        lambda p, ids: ref_model.apply({"params": p}, ids)[0, -1])

    # the logits that chose each first token: the adapter's prefill program
    # for that prompt again (same executable, same inputs), K/V to the trash
    # block so no live page is touched
    P = eng.spec.page_size
    first_diffs, first_match = [], []
    for i, prompt in enumerate(prompts):
        ids, pages = padded_prefill_inputs(
            prompt, [], P, eng.adapter.max_prompt_len() // P)
        assert set(pages.tolist()) == {TRASH_BLOCK}
        eng.cache.pool, got = eng.adapter.prefill(
            eng.cache.pool, jnp.asarray(ids),
            jnp.asarray(len(prompt), jnp.int32), jnp.asarray(pages))
        got = np.asarray(got, np.float32).reshape(-1)
        want = np.asarray(ref_logits(params, prompt[None]), np.float32)
        first_diffs.append(float(np.max(np.abs(got - want))))
        first_match.append(i in done
                           and int(np.argmax(got)) == done[i].generated[0])

    # request 0 against the static-cache greedy generate (SKILL.md contract:
    # identical tokens). bf16 rounding differs between the paged and the
    # static kernels, so a parting is held to the float32 reference: the two
    # choices must be a near-tie there, within the logit tolerance
    static = np.asarray(generate(cfg, params, prompts[0][None],
                                 max_new_tokens=NEW_TOKENS))[0, lens[0]:]
    paged = np.asarray(done[0].generated if 0 in done else [], np.int32)
    n = min(len(static), len(paged))
    apart = [j for j in range(n) if static[j] != paged[j]]
    vs_generate = {"identical": not apart and len(paged) == len(static)}
    explained = vs_generate["identical"]
    if apart:
        j = apart[0]
        ctx = np.concatenate([prompts[0], paged[:j]])[None]
        ref = np.asarray(ref_logits(params, ctx), np.float32)
        vs_generate.update(
            first_parting_at_new_token=j, paged_token=int(paged[j]),
            static_token=int(static[j]),
            fp32_reference_margin_between_them=float(
                abs(ref[paged[j]] - ref[static[j]])),
            fp32_reference_best_minus_each=[
                float(ref.max() - ref[paged[j]]),
                float(ref.max() - ref[static[j]])])
        explained = max(
            vs_generate["fp32_reference_best_minus_each"]) <= LOGIT_ATOL

    snapshot = eng.metrics_snapshot()
    del eng, params, done
    free_device_state()
    checks = {
        "all_requests_finished_full_length":
            counts == [NEW_TOKENS] * len(prompts),
        # prefill samples each request's first token, decode ticks the rest
        "decode_tokens_accounted": snapshot["decode_tokens"]
            == len(prompts) * (NEW_TOKENS - 1),
        "first_token_is_argmax_of_prefill_logits": all(first_match),
        "first_token_logits_match_fp32_reforward":
            max(first_diffs) <= LOGIT_ATOL,
        "request0_matches_generate_or_explained": explained,
    }
    return emit(
        "serve", t0, clock, mark, checks, prompt_tokens=lens,
        new_tokens=counts, serving=SERVING, kv_cache="bf16 paged",
        serve_wall_s=serve_s, logit_atol=LOGIT_ATOL,
        first_token_logit_max_abs_diff=first_diffs,
        request0_vs_generate=vs_generate,
        prefills=snapshot["prefills"], decode_ticks=snapshot["ticks"],
        decode_tokens=snapshot["decode_tokens"])


# ---------------------------------------------------------------- 4 chips

def chips4_phase(args, clock):
    import deepspeed_tpu as dstpu
    from deepspeed_tpu.models.gpt2 import GPT2LMHeadModel
    from deepspeed_tpu.parallel.mesh import make_mesh, MeshConfig
    import jax

    t0, mark = time.time(), clock.mark()
    cfg = model_config(args.rehearse_cpu)
    batch = train_batch_for(cfg, args.seed)
    devices = jax.devices()[:4]
    runs = {}
    for name, n in (("one_device", 1), ("dp4", 4)):
        m = clock.mark()
        mesh = make_mesh(MeshConfig(data=n), devices=devices[:n])
        engine, _, _, _ = dstpu.initialize(
            config=train_config(args.seed, args.rehearse_cpu), model=GPT2LMHeadModel(cfg),
            mesh=mesh)
        losses, step_s = timed_steps(engine, batch, CHIPS4_STEPS)
        runs[name] = {"losses": losses, "step_wall_s": step_s,
                      "resident_bytes": resident_bytes_per_device(
                          engine.state), **clock.since(m)}
        del engine
        free_device_state()

    one, dp4 = runs["one_device"], runs["dp4"]
    total = sum(one["resident_bytes"].values())
    quarter = total / 4
    diffs = [abs(a - b) for a, b in zip(one["losses"], dp4["losses"])]
    checks = {
        "losses_finite": bool(np.all(np.isfinite(
            one["losses"] + dp4["losses"]))),
        "dp4_losses_match_one_device": max(diffs) <= LOSS_ATOL_CHIPS4,
        "state_on_all_four_devices": len(dp4["resident_bytes"]) == 4,
        "every_device_holds_a_quarter": all(
            abs(b - quarter) <= SPREAD_RTOL * quarter
            for b in dp4["resident_bytes"].values())
        and len(dp4["resident_bytes"]) == 4,
    }
    return emit("chips4", t0, clock, mark, checks, zero_stage=3,
                batch=BATCH, loss_atol=LOSS_ATOL_CHIPS4,
                loss_abs_diffs=diffs, one_device_total_bytes=total,
                spread_rtol=SPREAD_RTOL, one_device=one, dp4=dp4)


# ------------------------------------------------------------------- main

def main(argv=None):
    ap = argparse.ArgumentParser(description=__doc__.splitlines()[0])
    ap.add_argument("--seed", type=int, default=0,
                    help="seed for weights, batch and prompts")
    ap.add_argument("--chips", type=int, choices=(1, 4), default=1,
                    help="4: run ONLY the dp=4 ZeRO-3 path and its "
                         "one-device comparison (needs four chips)")
    ap.add_argument("--rehearse-cpu", action="store_true",
                    help="tiny widths on the CPU backend: same control "
                         "flow, never a pass")
    args = ap.parse_args(argv)

    import jax
    from deepspeed_tpu.ops.native import builder
    from deepspeed_tpu.utils.platform import enable_compile_cache

    dev = device_info()
    need = "cpu" if args.rehearse_cpu else "tpu"
    if dev["platform"] != need or dev["count"] < args.chips:
        print(json.dumps({"ok": False, "device": dev,
                          "error": f"needs {args.chips} {need} device(s)"}),
              flush=True)
        return 1
    if args.chips == 4:
        dev["count"] = 4
    cache_dir = enable_compile_cache()
    print(json.dumps({
        "phase": "setup", "seed": args.seed, "chips": args.chips,
        "rehearsal": args.rehearse_cpu, "compile_cache_dir": cache_dir,
        "compile_cache_placed_by":
            "JAX_COMPILATION_CACHE_DIR" if os.environ.get(
                "JAX_COMPILATION_CACHE_DIR") else "code (fixed path)",
        "compile_cache_entries_at_start": len(os.listdir(cache_dir))
        if os.path.isdir(cache_dir) else 0,
        **dev, "jax": jax.__version__}), flush=True)

    clock = CompileClock()
    phases = [chips4_phase] if args.chips == 4 else [train_phase, serve_phase]
    ok = True
    for phase in phases:
        try:
            ok = phase(args, clock) and ok
        except Exception:      # boundary: report the failed phase, then fail
            traceback.print_exc()
            print(json.dumps({"phase": phase.__name__, "ok": False,
                              "error": traceback.format_exc(limit=1)
                              .strip().splitlines()[-1]}), flush=True)
            ok = False
            break
    print(json.dumps({"phase": "native_ops",
                      "loaded": builder.loaded_ops()}), flush=True)
    if args.rehearse_cpu:
        print(json.dumps({"ok": False, "rehearsal": True,
                          "rehearsal_checks_passed": ok, "device": dev}),
              flush=True)
        return 0 if ok else 1
    print(json.dumps({"ok": ok, "device": dev}), flush=True)
    return 0 if ok else 1


if __name__ == "__main__":
    sys.exit(main())
