"""Async-safe phase spans + the programmatic XLA trace window.

The retired anti-pattern: ``SynchronizedWallClockTimer`` syncs the
device on every ``start``/``stop`` read, which serializes dispatch
against execution when wrapped around hot-loop phases (the reference's
``cuda.synchronize`` habit, utils/timer.py). Spans here never sync:

- ``span("tag")`` (host side) records the host wall time of the block
  into ``span/{tag}`` and emits a ``jax.profiler.TraceAnnotation`` so
  the block shows on the host timeline of an XLA trace. Around a jitted
  call this measures **dispatch** time (async under jit) — real device
  time for the block comes from the trace window or from a
  ``steps_per_print``-boundary fence the caller already pays.
- ``annotate("tag")`` (trace time) is ``jax.named_scope``: ops traced
  under it carry the tag in their HLO metadata, so device-side phase
  attribution (forward / backward / bucket-sync)
  lands in perfetto/xprof without any runtime cost.
- ``TraceWindow`` wraps ``jax.profiler.start_trace/stop_trace`` around
  a configured step range (``profiling.trace_dir`` +
  ``profiling.trace_steps``) — the one place a deliberate fence happens
  (at stop, so the captured steps' device work is in the trace).

ISSUE 19 adds the **causal span-id layer** under the distributed trace
plane: ``new_span_id()`` mints process-unique ids (pid-scoped, so ids
minted on different ranks never collide when their dump files merge)
and serving lifecycle events carry ``span_id``/``parent_span`` fields
that ``telemetry/perfetto.py`` stitches into one parent/child tree per
``trace_id`` — prefill on rank 0, transport encode/collective, adopt +
per-tick decode on rank N, finish — even though every leg landed in a
different per-role dump file. Minting is stdlib + a lock; nothing here
touches jax (the jax-free viewer contract covers the exporter that
consumes these ids).

ISSUE 35 gives START-UP a timeline: the engine's one-time phases run
under ``span("startup/...")``, every ``span`` event carries its start on
``time.monotonic()`` (``t0_mono``), and ``watch_compiles()`` names,
times and counts every trace, lowering and compile-or-cache-fetch of the
process (one ``compile`` event a phase; ``compile/cache_misses``,
``compile/after_first_step``).
"""

import contextlib
import itertools
import os
import threading
import time

from deepspeed_tpu.telemetry.recorder import default_recorder
from deepspeed_tpu.telemetry.registry import default_registry
from deepspeed_tpu.utils.logging import logger


# ---------------------------------------------------------------- span ids
#
# A span id must be unique across EVERY process whose dump files end up
# merged in one Perfetto export (N ranks × supervisor restart epochs).
# uuid-per-span would work but costs an entropy read per serving event;
# a pid-prefixed counter is two orders cheaper and collision-free by
# construction: the pid names the process, the counter names the span.
# (Pid recycling across supervisor epochs is disambiguated by the
# startup-time nonce baked into the prefix.)

_span_counter = itertools.count(1)
_span_prefix = None
_span_lock = threading.Lock()


def new_span_id():
    """Mint a process-unique span id (``"<pid-hex><nonce>-<n>"``).
    Host-only and cheap — safe on the serving scheduler's per-request
    path. Thread-safe; ids from concurrent threads never collide."""
    global _span_prefix
    if _span_prefix is None:
        with _span_lock:
            if _span_prefix is None:
                _span_prefix = f"{os.getpid():x}{os.urandom(2).hex()}"
    return f"{_span_prefix}-{next(_span_counter)}"


def span_fields(span_id, parent_span=None):
    """The event-field convention of the trace plane: a dict to splat
    into a recorder event. ``parent_span=None`` marks a ROOT span —
    the exporter renders it as the request's top-level slice."""
    out = {"span_id": span_id}
    if parent_span is not None:
        out["parent_span"] = parent_span
    return out


def annotate(tag):
    """Trace-time scope: ops traced inside carry ``tag`` in HLO
    metadata (shows up in xprof/perfetto op names). Zero runtime cost —
    usable unconditionally inside jitted train fns.

    The tag becomes a path element of the ``op_name`` the COMPILED text
    gives each instruction (the device plane's event names carry no
    metadata; ``benchmark/scope_reduce.py`` joins the two by instruction
    name). The names below are the contract the benchmark reads — rename
    one and its metric goes blind:

    - ``ds_fwd_bwd`` (runtime/engine.py): inside it JAX's own path
      elements tell the direction — ``jvp(`` is ``train_fwd_ms``,
      ``transpose(jvp(`` is ``train_bwd_ms``, ``rematted_computation``
      is ``train_recompute_ms``;
    - ``ds_optimizer`` (runtime/engine.py): ``train_optimizer_ms``;
    - ``flash_fwd`` and its long-S variant ``flash_fwd_chunk``
      (ops/pallas/flash_attention.py, round the ``pallas_call`` itself):
      ``flash_fwd_roofline``;
    - ``flash_bwd`` and its long-S variant ``flash_bwd_chunk`` (same
      file; the single-pass chunked backward since PR 49, a
      ``flash_bwd_dq`` and a ``flash_bwd_dkv`` call before it):
      ``flash_bwd_roofline``; ``flash_bwd_dq_sum`` beside it, the XLA pass
      that adds that kernel's float32 dq slabs, scales and casts — no
      Pallas call, so in ``train_bwd_ms`` and the module's row and in no
      roofline;
    - ``ds_loss_head`` (``chunked_lm_loss``, ``lm_loss``, the tied-logits
      einsum): ``loss_head_ms``, every phase's rows together (the chunked
      head forms its gradient in its forward rule: its matmuls are
      ``train_fwd_ms``, its backward rule one scaling); ``ds_embed`` (the
      ``wte``/``wpe`` lookup): rows of the benchmark's detail table; both
      models/gpt2.py.

    - ``moe_gmm``, ``moe_gmm_dlhs``, ``moe_gmm_drhs``
      (ops/pallas/grouped_matmul.py, round each ``pallas_call``: forward,
      the rows' gradient, the expert weights' gradient):
      ``moe_gmm_roofline`` and ``moe_gmm_share`` (one tag, by prefix);
    - ``moe_router``, ``moe_dispatch``, ``moe_combine`` (moe/dropless.py:
      logits, softmax, top-k and the two losses; the sort and the row
      gather; weighting and the rows' way back): ``moe_dispatch_ms``;
      ``moe_router`` alone: ``moe_router_ms`` (in a block whose router
      reads the block's INPUT — ``DroplessMoE(x, router_x=...)``,
      models/smallthinker.py — its operations depend on nothing of the
      mixer and XLA may run them ahead of it; the scope's path stays
      ``.../mlp/moe_router``).
      ``rows_to_tokens`` (ops/pallas/rows_to_tokens.py: the held rows'
      sort, gather and ``pallas_call``) lies INSIDE ``moe_combine`` in the
      forward pass and ``moe_dispatch`` in the backward pass and is counted
      with them; it must not start with a kernel tag (``moe_gmm``,
      ``flash_``, ``gdn_scan``, ``ssd_scan``, ``swa_``: matched by prefix);
    - ``moe_act`` (moe/dropless.py: ``act(gate) * up``, silu, relu or
      relu^2, or ``act(up)`` alone for an ungated expert)
      and ``qk_norm`` (models/llama.py, the
      RMSNorms over the whole q and k projections): rows of the detail
      table.

    - ``gdn_scan_fwd`` and ``gdn_scan_bwd`` (ops/pallas/gated_delta.py,
      round the gated delta rule's two ``pallas_call``s), ``gdn_scan_prep``
      (the XLA ops left round them: the gates' re-layout and running
      sums) and, for head sizes the kernels do not take, ``gdn_scan`` with
      its all-chunks ``gdn_scan_prep`` (ops/gated_delta.py, the XLA
      form): ``gdn_scan_share`` and ``gdn_scan_roofline`` (one tag, by
      prefix);
    - ``gdn_conv``, ``gdn_gates``, ``gdn_out_norm`` (models/qwen3_next.py:
      the causal depthwise convolution, its SiLU and the L2 norms of q and
      k; beta and the decay; the gated RMSNorm of the output): with
      ``gdn_scan*`` and the module name ``linear_attn``, ``gdn_layer_ms``;
    - ``attn_gate`` (models/qwen3_next.py, models/laguna.py: the
      attention output times ``sigmoid(gate)``, element-wise there, one
      scalar a head here) and ``moe_shared`` (moe/dropless.py, the gated
      shared expert): rows of the detail table.

    - ``ssd_scan_fwd`` and ``ssd_scan_bwd`` (ops/pallas/ssd.py, round the
      state-space scan's two ``pallas_call``s), ``ssd_scan_prep`` (the XLA
      ops left round them: the gates' re-layout and running sum) and, for
      the shapes the kernels do not take, ``ssd_scan`` (ops/ssd.py, the XLA
      chunked form): ``ssd_scan_share`` and ``ssd_scan_roofline`` (one tag,
      by prefix);
    - ``ssm_conv``, ``ssm_gates``, ``ssm_norm`` (models/nemotron_h.py: the
      causal depthwise convolution with bias and its SiLU; the softplus of
      the steps and the decay; the gate and the grouped RMSNorm): with
      ``ssd_scan*`` and the module name ``mamba``, ``ssm_layer_ms``;
      ``ssm_norm`` alone (models/granite_hybrid.py runs the same mixer with
      ONE group of all channels), ``ssm_norm_roofline``;
    - ``shared_mlp`` (models/granite_hybrid.py: a flax module name, the
      dense SwiGLU of every layer; the model's four multipliers fold into
      the operations beside them and have no scope): ``dense_mlp_ms``;
    - ``mixer_conv_fwd``, ``mixer_conv_bwd``, ``mixer_norm_fwd``,
      ``mixer_norm_bwd`` (ops/pallas/mixer_elementwise.py, round the four
      ``pallas_call``s of the mixers' elementwise stages, INSIDE
      ``gdn_conv`` / ``ssm_conv`` and ``gdn_out_norm`` / ``ssm_norm``): no
      metric reads them by name — they start with no kernel tag, so their
      time stays under the module scope round them.

    - ``swa_fwd``, ``swa_bwd`` (ops/pallas/flash_attention.py, round the
      window kernels' two ``pallas_call``s): ``swa_attn_share`` and
      ``swa_*_roofline``; ``bd_fwd``, ``bd_bwd`` (ops/pallas/
      block_diffusion_attention.py, the same for the block-diffusion mask;
      ``bd_bwd_dq_sum`` the XLA sum of dq's partials): ``bd_attn_share``,
      ``bd_*_roofline``; ``bd_noise`` (models/llama.py): ``bd_noise_ms``;
    - ``dsa_indexer``, ``dsa_indexer_bwd``, ``dsa_select``, ``dsa_fwd``,
      ``dsa_bwd``, ``dsa_kl`` (ops/pallas/learned_sparse_attention.py, round
      the six ``pallas_call``s of a layer whose attention a learned indexer
      prunes; ``dsa_select_pin``, ``dsa_indexer_bwd_sum``,
      ``dsa_bwd_dq_sum`` the XLA passes beside them, each under its kernel's
      tag) and ``dsa_index_proj`` (models/llama.py: the indexer's three
      projections, its key's norm and their rotary): ``dsa_attn_share``,
      ``dsa_indexer_ms``, ``dsa_select_ms``, ``dsa_kl_ms`` and a
      ``dsa_*_roofline`` a kernel;
    - ``dense_mlp`` (laguna.py, deepseek_v3.py: the leading SwiGLU): a row.

    - ``mla_latent``, ``mla_expand``, ``mla_rope`` (models/deepseek_v3.py,
      inside the module ``mla_attn``: the down-projection to latent +
      rotated key and the latent's RMS norm — and, where queries are
      compressed, their down-projection and its norm; the up-projection into every
      head's key without position and value, and the kernels' K operand —
      the concat with the broadcast rotated key; the de-interleaving
      rotation of q_rope and of the shared key): ``mla_expand_ms``, and
      with ``flash_*`` and the module name ``mla_attn``, ``mla_layer_ms``.
    - ``mhc_coeff``, ``mhc_read``, ``mhc_write``
      (models/hyper_connections.py, in a block of several residual streams,
      beside its modules: the stream's norm, its projection onto the
      coefficients, the sigmoids and Sinkhorn's rounds; ``u = H_pre X`` and
      the streams' sum at a chain's end; ``X_new = H_res X + H_post y`` and
      the copy into the streams at its start; the kernel form gives ``u``
      in ``mhc_coeff``'s pass): ``mhc_stream_ms``, ``mhc_stream_roofline``;
    - ``mtp`` (models/deepseek_v3.py, round the whole multi-token-prediction
      module: the next token's embedding, the join, its block, its head norm
      and its pass through the shared head — which stays ``ds_loss_head``
      inside it, as its attention stays ``mla_*``): ``mtp_ms``, which reads
      the path element itself and not a row's tag.

    The flax module names ``attn``, ``mlp``, ``ln_1``, ``ln_2``, ``ln_f``
    (models/gpt2.py), ``attn``, ``mlp``, ``input_norm``,
    ``post_attn_norm``, ``norm`` (models/llama.py) and ``linear_attn``,
    ``attn``, ``mlp`` and the same three norms (models/qwen3_next.py;
    models/laguna.py has ``attn``, ``mlp`` and the three norms;
    models/deepseek_v3.py ``mla_attn``, ``mlp`` and the three norms) are the
    detail table's remaining tags.

    Beside the scopes, the flash kernels leave trace-time GAUGES in
    the registry: ``attention/flash_tile_overcompute`` (score elements
    the chosen loops compute over those the softmax needs: whether the
    strip walk engaged for a shape) and
    ``attention/flash_heads_per_block`` (heads a 128-lane column block of
    the model's own [B, S, H*D] operands, 0 for a head-major call), the
    chunked kernels a third, ``attention/flash_grid_steps_walked_share``
    (grid steps of their (block, chunk) pair lists over the rectangular
    grid's: 0.625 causal at S 16,384, 1.0 where nothing is masked), and a
    fourth, ``attention/flash_chunk_rows`` (sequence rows a grid step
    holds: 4,096 at head_dim 128 in bf16), every flash call
    ``attention/flash_bwd_products_per_tile`` (MXU products a score tile
    of its backward takes: 5, each tile computed once) and
    ``attention/flash_bwd_dq_slabs`` (float32 dq slabs a chunked backward
    leaves to be added, one a key chunk; 0 for a whole-row call), and
    the gated delta rule three, ``linear_attn/gdn_kernel_heads_per_step``
    (value heads a grid step of its kernels; 0: the XLA form took the
    call), ``linear_attn/gdn_states_kept_every`` (chunks between the
    states kept for the backward pass) and
    ``linear_attn/gdn_lane_overcompute`` (lanes of q | k | v | state the
    kernels compute on over the model's own heads': 1.77 where 96 x 192
    runs at 128 x 256), which ``gdn_lane_overcompute`` reads, as
    ``gdn_xla_sites`` reads the first with the mixer stages'
    ``mixer/*_xla_sites``. The window kernels leave ``attention/window_tile_overcompute``
    (score elements their tiles compute over those the band holds), which
    ``swa_tile_overcompute`` reads, ``attention/window_tiles_per_grid_step``
    and ``attention/window_bwd_tiles_per_grid_step`` (score tiles a grid
    step of the two calls, and of the backward's), which no metric reads;
    the block-diffusion kernels ``attention/bd_tile_overcompute`` (read by
    ``bd_tile_overcompute``) and ``attention/bd_tiles_per_grid_step``, and
    their model's step ``diffusion/masked_share`` and
    ``diffusion/weight_max`` (the largest 1 / t that met a masked row);
    the learned-sparse kernels ``attention/dsa_tile_overcompute`` (read by
    ``dsa_tile_overcompute``), their model's step
    ``attention/dsa_selected_share`` (``dsa_selected_share``) and
    ``attention/dsa_kl`` (the indexer's loss L_I, no metric), and
    ``remat/selection_pin_mb`` (what a rematted stack keeps of its layers'
    selections, no metric), ``remat/dsa_kl_grad_mb`` and
    ``remat/dsa_kl_grad_kept`` (what it would hold of the KL's gradient in
    the indexer's scores, and whether the bytes fit and it does: the
    recomputed forward then runs neither ``dsa_indexer`` nor ``dsa_kl``; no
    metric).
    A chunked call whose q·k width is not its value
    width (latent attention) leaves ``attention/mla_qk_dim`` and
    ``attention/mla_v_dim``, the two widths as the kernels saw them (192 /
    128 on the Kanana-2 cell; untouched by every equal-width call).
    Every flash VJP's forward rule
    leaves ``attention/flash_residual_mb`` (decimal MB of HBM the
    ``flash_o`` / ``flash_lse`` pairs one differentiation names take, a
    minor dimension counted in 128-lane tiles: what a remat policy that
    keeps the names holds, ~1,227 on the Laguna cell's step; whether
    remat DID keep them is the compiled step's to say — no forward
    attention scope under ``rematted_computation``,
    ``tests/hlo_text.rematted_forward_attention`` — and on the chip
    ``train_recompute_ms`` and the forward rooflines): no benchmark
    metric reads it."""
    import jax
    return jax.named_scope(tag)


@contextlib.contextmanager
def span(tag, registry=None, annotation=True, recorder=None):
    """Host-side phase span: wall time into ``span/{tag}`` plus a
    profiler TraceAnnotation, plus one ``span`` event in the flight
    recorder (the per-STEP record the histogram's aggregate view
    cannot reconstruct — recorder.py). NEVER syncs the device — around
    a jitted call this measures dispatch, by design (sync discipline,
    docs/observability.md). Async-safe: state lives on the stack, the
    registry/recorder lock per record; concurrent spans from other
    threads (e.g. the serving scheduler) interleave correctly.

    The event's ``ts`` is the recorder's wall clock at the span's END;
    ``t0_mono`` is its START on ``time.monotonic()`` — the clock a
    harness stamps its own phases with, so spans, ``compile`` events and
    such stamps lie on one axis (``dur_s`` is taken on the same clock).

    Start-up's one-time phases are spans too: ``startup/sharded_init``
    (runtime/zero/init.py), ``startup/engine_init`` (``initialize``,
    entry to return), ``startup/state_init`` and ``startup/build_fns``
    (the engine's first ``train_batch``); step 0's
    ``train/step_dispatch`` holds the step's trace, lowering and
    compile-or-fetch. ``benchmark/setup_reduce.py`` lays them on
    ``setup_s`` (``setup_engine_init_s``, ``setup_first_step_s``,
    ``setup_outside_program_s``)."""
    reg = registry or default_registry()
    rec = recorder if recorder is not None else default_recorder()
    ann = None
    if annotation:
        try:
            import jax
            ann = jax.profiler.TraceAnnotation(tag)
            ann.__enter__()
        except Exception:   # profiler backends are optional
            ann = None
    t0 = time.monotonic()
    try:
        yield
    finally:
        dt = time.monotonic() - t0
        if ann is not None:
            ann.__exit__(None, None, None)
        reg.histogram(f"span/{tag}").observe(dt)
        rec.record("span", tag=tag, dur_s=dt, t0_mono=t0)


# ------------------------------------------------------- compile events
#
# JAX announces every trace, lowering and backend compile on
# jax.monitoring with the function's name (jax/_src/dispatch.py,
# LogElapsedTimeContextManager), and the persistent cache its hits and
# misses (jax/_src/compiler.py). A backend "compile" that the cache
# answers is announced all the same: its event says ``cache: "hit"``.

_COMPILE_PHASES = {
    "/jax/core/compile/jaxpr_trace_duration": "trace",
    "/jax/core/compile/jaxpr_to_mlir_module_duration": "lower",
    "/jax/core/compile/backend_compile_duration": "backend",
}
_CACHE_VERDICTS = {
    "/jax/compilation_cache/cache_hits": "hit",
    "/jax/compilation_cache/cache_misses": "miss",
}
_FIRST_STEP_SPAN = "span/train/step_dispatch"
# An eager jnp call is a trace too, of microseconds, that finds its
# program compiled: a float32 reference run op by op makes thousands, and
# the ring holds 4,096 events. A trace this short leaves no event.
_SHORT_TRACE_S = 0.01

_watching_compiles = False
_compiled_late = set()      # function names already logged
# .verdict: what the persistent cache said inside the backend phase now
# running on this thread
_cache = threading.local()


def _on_compile_phase(event, start, end, fun_name=None, **_):
    phase = _COMPILE_PHASES.get(event)
    if phase is None or (phase == "trace" and end - start < _SHORT_TRACE_S):
        return
    # start/end are time.time() values: carry the start over to the
    # monotonic clock by its distance from now
    fields = dict(fun_name=fun_name, phase=phase, dur_s=end - start,
                  t0_mono=time.monotonic() - (time.time() - start))
    if phase != "backend":
        default_recorder().record("compile", **fields)
        return
    verdict, _cache.verdict = getattr(_cache, "verdict", None), None
    default_recorder().record("compile", cache=verdict, **fields)
    reg = default_registry()
    if reg.peek_histogram_count(_FIRST_STEP_SPAN):
        reg.counter("compile/after_first_step").inc()
        if fun_name not in _compiled_late:
            _compiled_late.add(fun_name)
            logger.info(f"[telemetry] {fun_name} compiled (or was fetched) "
                        f"after the first optimizer step had returned")


def _on_cache_event(event, **_):
    verdict = _CACHE_VERDICTS.get(event)
    if verdict is not None:
        _cache.verdict = verdict
        if verdict == "miss":
            default_registry().counter("compile/cache_misses").inc()


def watch_compiles():
    """Name and time every compile of this process from here on: one
    registration on ``jax.monitoring`` however often it is called
    (``sharded_init`` and ``initialize`` both call it; two engines in a
    process share it). Always on, like ``train/step_dispatch``: a
    callback of microseconds a compile phase, nothing a step. What the
    process compiled BEFORE the first of those two calls (a caller's
    ``jax.random.PRNGKey``, its example input) is not seen.

    Into the flight recorder: one ``compile`` event a phase JAX announces
    (a trace under 10 ms, an eager call finding its program, leaves
    none) with ``fun_name``, ``phase`` (``trace`` / ``lower`` /
    ``backend``; a jitted function called inside a trace has its own
    trace event inside the outer one's interval), ``dur_s``, ``t0_mono``
    (its start on ``time.monotonic()``, as a ``span`` event's) and, on a
    backend phase, ``cache`` (``hit``, ``miss``, or None where no
    persistent cache was asked; a fetch is announced as the backend
    phase it replaces). The ring is the timeline of the last 4,096
    events: ``benchmark/setup_reduce.py`` reads it for
    ``setup_compile_s``, ``setup_programs_compiled`` and
    ``setup_cache_misses``. Into ``default_registry()``, which does not
    forget: counter ``compile/cache_misses`` (JAX counts a miss when it
    WRITES the entry) — 0 says this restart was a warm one.

    A backend compile that ends once ``span/train/step_dispatch`` holds
    an observation — a first optimizer step has returned — also counts in
    ``compile/after_first_step`` and logs one line a function name: in
    steady state that is the silent loss to look for (a new shape, a
    weak type, a changed static argument). A program first NEEDED later
    shows there too and is no fault: an eval or checkpoint program, the
    throughput timer's first device sync on step 1 (``jit(<lambda>)`` of
    ``utils/timer._sync_device``: the one name every benchmark run
    logs). ``lower_train_step(...).compile()`` after a step, as the
    benchmark's traced run calls it to keep the step's text, is served
    by jit's own caches and announces nothing."""
    global _watching_compiles
    with _span_lock:
        if _watching_compiles:
            return
        import jax.monitoring as monitoring
        monitoring.register_event_time_span_listener(_on_compile_phase)
        monitoring.register_event_listener(_on_cache_event)
        _watching_compiles = True


class TraceWindow:
    """Config-gated programmatic profiler window: capture steps
    ``[start, stop)`` of the training loop into ``trace_dir`` (xprof
    format — open in perfetto / tensorboard-profile). Start/stop are
    engine ``global_steps`` values as seen BEFORE the step runs.

    The window stops with a caller-supplied fence so the traced steps'
    device work is actually inside the capture; that one sync is the
    point of the window and never happens unless tracing was on."""

    def __init__(self, trace_dir, start_step, stop_step, registry=None):
        assert stop_step > start_step >= 0, (start_step, stop_step)
        self.trace_dir = trace_dir
        self.start_step = int(start_step)
        self.stop_step = int(stop_step)
        self.active = False
        self.done = False
        self._registry = registry or default_registry()

    @classmethod
    def from_config(cls, profiling_cfg):
        """None when the gate is off (no trace_dir or no trace_steps)."""
        if not getattr(profiling_cfg, "trace_dir", None):
            return None
        steps = getattr(profiling_cfg, "trace_steps", None)
        if not steps:
            return None
        return cls(profiling_cfg.trace_dir, steps[0], steps[1])

    def on_step_begin(self, step):
        if self.done or self.active or step < self.start_step \
                or step >= self.stop_step:
            return
        import jax
        try:
            jax.profiler.start_trace(self.trace_dir)
        except Exception as e:   # a second live trace, unwritable dir …
            logger.warning(f"trace window failed to start: {e}")
            self.done = True
            return
        self.active = True
        # a run that ends before stop_step-1 (crash, short loop) must
        # still finalize the capture — a dangling live trace writes no
        # artifact and blocks every later start_trace in the process
        import atexit
        atexit.register(self.close)
        self._registry.counter("profiling/trace_windows").inc()
        logger.info(f"[telemetry] XLA trace started (steps "
                    f"[{self.start_step}, {self.stop_step}) -> "
                    f"{self.trace_dir})")

    def on_step_end(self, step, fence=None):
        """``step`` is the same pre-run index passed to on_step_begin;
        ``fence`` (e.g. a loss readback) runs before stop_trace so the
        final step's device work lands in the capture."""
        if not self.active or step < self.stop_step - 1:
            return
        if fence is not None:
            try:
                fence()   # sync-ok: trace-window close, config-gated
            except Exception:
                pass
        self.close()

    def close(self):
        """Finalize an active capture (idempotent; also the atexit
        safety net for runs shorter than the configured window)."""
        if not self.active:
            return
        import atexit
        import jax
        try:
            jax.profiler.stop_trace()
        except Exception as e:
            logger.warning(f"trace window failed to stop: {e}")
        self.active = False
        self.done = True
        atexit.unregister(self.close)
        logger.info(f"[telemetry] XLA trace written to {self.trace_dir}")
