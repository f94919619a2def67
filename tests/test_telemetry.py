"""Unified-telemetry tests (ISSUE 4): registry snapshot/reset semantics,
async-safe spans, exporters (JSONL / SummaryEventWriter bridge /
Prometheus), flops-profiler MFU math, the engine's per-step scalar
stream, and a CPU smoke of the programmatic XLA trace window."""

import json
import os
import threading

import numpy as np
import jax
import pytest

import deepspeed_tpu as dstpu
from deepspeed_tpu.telemetry import (
    MetricsRegistry, JsonlExporter, SummaryBridge, prometheus_text,
    span, TraceWindow, default_registry)
from tests.simple_model import SimpleModel, base_config


# --------------------------------------------------------------- registry

def test_registry_counter_gauge_histogram_semantics():
    r = MetricsRegistry()
    r.counter("a/steps").inc()
    r.counter("a/steps").inc(2)
    r.gauge("a/g").set(3.5)
    r.gauge("a/hwm").set_max(1.0)
    r.gauge("a/hwm").set_max(0.25)        # lower — HWM must hold
    for v in range(1, 101):
        r.histogram("a/h").observe(v / 100.0)
    snap = r.snapshot()
    assert snap["counters"]["a/steps"] == 3.0
    assert snap["gauges"]["a/g"] == 3.5
    assert snap["gauges"]["a/hwm"] == 1.0
    h = snap["histograms"]["a/h"]
    assert h["count"] == 100 and abs(h["sum"] - 50.5) < 1e-9
    assert h["min"] == 0.01 and h["max"] == 1.0
    assert abs(h["p50"] - 0.5) <= 0.02 and h["p99"] >= 0.98
    # the same name returns the same metric object
    assert r.counter("a/steps") is r.counter("a/steps")


def test_registry_snapshot_prefix_filter_and_reset():
    r = MetricsRegistry()
    r.counter("train/x").inc()
    r.counter("serving/y").inc()
    assert set(r.snapshot(prefix="serving/")["counters"]) == {"serving/y"}
    r.reset()
    snap = r.snapshot()
    assert not snap["counters"] and not snap["histograms"]


def test_histogram_reservoir_bounded_but_totals_exact():
    r = MetricsRegistry()
    h = r.histogram("h", maxlen=8)
    for v in range(100):
        h.observe(float(v))
    s = h.summary()
    assert s["count"] == 100 and s["sum"] == sum(range(100))
    assert s["p50"] >= 92       # percentiles over the RECENT reservoir


def test_spans_record_host_time_and_are_thread_safe():
    r = MetricsRegistry()

    def worker(tag, n):
        for _ in range(n):
            with span(tag, registry=r):
                pass

    threads = [threading.Thread(target=worker, args=(f"t{i}", 50))
               for i in range(4)]
    for t in threads:
        t.start()
    for t in threads:
        t.join()
    snap = r.snapshot()
    for i in range(4):
        assert snap["histograms"][f"span/t{i}"]["count"] == 50


# --------------------------------------------------------------- exporters

def test_jsonl_exporter_events_carry_ts_rank_step(tmp_path):
    r = MetricsRegistry()
    r.counter("c").inc(7)
    path = str(tmp_path / "m.jsonl")
    ex = JsonlExporter(path, r)
    ex.export(step=3)
    ex.export(step=4)
    ex.close()
    lines = [json.loads(l) for l in open(path)]
    assert len(lines) == 2
    assert lines[0]["step"] == 3 and lines[1]["step"] == 4
    assert lines[0]["ts"] > 0 and isinstance(lines[0]["rank"], int)
    assert lines[0]["metrics"]["counters"]["c"] == 7.0


def test_summary_bridge_and_jsonl_fallback_tagging(tmp_path, monkeypatch):
    import sys
    # force the JSONL fallback (and skip the ~15s torch import):
    # a None sys.modules entry makes the tensorboard import raise
    monkeypatch.setitem(sys.modules, "torch.utils.tensorboard", None)
    from deepspeed_tpu.utils.monitor import SummaryEventWriter
    r = MetricsRegistry()
    r.gauge("train/mfu").set(0.42)
    r.histogram("train/step_time_s").observe(0.1)
    w = SummaryEventWriter(str(tmp_path), "job")
    assert w._tb is None
    SummaryBridge(w, r).export(step=5)
    w.close()
    events = [json.loads(l)
              for l in open(os.path.join(w.log_dir, "events.jsonl"))]
    tags = {e["tag"] for e in events}
    assert "train/mfu" in tags and "train/step_time_s/p50" in tags
    # satellite: every fallback event self-identifies for merge
    for e in events:
        assert e["ts"] > 0 and isinstance(e["rank"], int)
        assert e["step"] == 5


def test_prometheus_text_dump():
    r = MetricsRegistry()
    r.counter("train/steps").inc(3)
    r.gauge("serving/queue_depth").set(2)
    r.histogram("train/step_time_s").observe(0.25)
    text = prometheus_text(r)
    assert "# TYPE train_steps counter\ntrain_steps 3.0" in text
    assert "# TYPE serving_queue_depth gauge" in text
    assert 'train_step_time_s{quantile="0.5"} 0.25' in text
    assert "train_step_time_s_count 1" in text


def test_prometheus_help_type_pairs_and_label_escaping():
    """ISSUE 6 satellite: every family carries a # HELP line right
    before its # TYPE line (the order scrapers expect), the HELP text
    preserves the original /-separated path, and label values escape
    backslash/quote/newline per the exposition format."""
    from deepspeed_tpu.telemetry.registry import _prom_escape_label
    r = MetricsRegistry()
    r.counter("train/steps").inc(3)
    r.histogram("serving/ttft_s").observe(0.5)
    lines = prometheus_text(r).splitlines()
    helps = [i for i, l in enumerate(lines) if l.startswith("# HELP ")]
    assert helps, lines
    for i in helps:
        name = lines[i].split()[2]
        assert lines[i + 1] == f"# TYPE {name} " \
            + lines[i + 1].split()[-1]
    # the lossy name mangling is recoverable from HELP
    assert any("# HELP train_steps" in l and "train/steps" in l
               for l in lines)
    # one HELP/TYPE per family even with quantile samples following
    assert sum(1 for l in lines if l.startswith("# TYPE serving_ttft_s "
                                                )) == 1
    assert _prom_escape_label('a"b\\c\nd') == 'a\\"b\\\\c\\nd'
    assert 'quantile="0.99"' in prometheus_text(r)


def test_jsonl_exporter_rotation_bounds_disk(tmp_path):
    """ISSUE 6 satellite: with max_bytes set the stream rotates
    logrotate-style and total files never exceed max_files — a
    multi-hour run cannot grow one unbounded file."""
    r = MetricsRegistry()
    r.counter("c").inc()
    path = str(tmp_path / "m.jsonl")
    ex = JsonlExporter(path, r, max_bytes=512, max_files=3)
    for step in range(60):
        ex.export(step=step)
    ex.close()
    files = sorted(os.listdir(tmp_path))
    assert "m.jsonl" in files
    assert "m.jsonl.1" in files and "m.jsonl.2" in files
    assert len(files) == 3                    # oldest fell off the end
    for f in files:
        p = os.path.join(str(tmp_path), f)
        assert os.path.getsize(p) <= 512 + 256   # one event of slack
        for line in open(p):
            assert json.loads(line)["metrics"]["counters"]["c"] == 1.0
    # rotation keeps the newest events in the live file
    last = [json.loads(l) for l in open(path)]
    assert last == [] or last[-1]["step"] == 59


def test_jsonl_exporter_rotation_off_by_default(tmp_path):
    r = MetricsRegistry()
    path = str(tmp_path / "m.jsonl")
    ex = JsonlExporter(path, r)
    for step in range(20):
        ex.export(step=step)
    ex.close()
    assert sorted(os.listdir(tmp_path)) == ["m.jsonl"]


# ------------------------------------------------- engine + trace window

def test_engine_scalar_stream_mfu_and_trace_window(tmp_path, monkeypatch):
    """One tiny engine exercises the whole integration: per-step
    counters, boundary window folds (step-time histogram, throughput
    gauges), flops priced from the compiled step's cost analysis (MFU
    only once the device kind has a recorded peak), memory gauges, the
    JSONL stream, and a 2-step XLA trace window."""
    default_registry().reset()
    jsonl = str(tmp_path / "tel.jsonl")
    cfg = base_config(steps_per_print=2)
    cfg["monitor"] = {"jsonl_path": jsonl}
    cfg["profiling"] = {"trace_dir": str(tmp_path / "trace"),
                        "trace_steps": [1, 3]}
    engine, _, _, _ = dstpu.initialize(config=cfg, model=SimpleModel())
    batch = (np.random.RandomState(0).randn(8, 8).astype(np.float32),
             np.zeros((8,), np.int32))
    for _ in range(6):
        engine.train_batch(batch)
    snap = engine.telemetry_flush(batch)

    assert snap["counters"]["train/steps"] == 6
    assert snap["counters"]["train/samples"] == 48
    # boundary folds at steps 2/4/6 — the first window (contains the
    # compile) is dropped, later ones observed
    assert snap["histograms"]["train/step_time_s"]["count"] >= 2
    assert snap["histograms"]["span/train/step_dispatch"]["count"] == 6
    assert snap["gauges"]["train/samples_per_sec"] > 0
    # flops priced (monitor gate on): exact, from cost analysis. The
    # CPU mesh's device kind has no recorded peak, so no MFU gauge
    assert snap["gauges"]["train/flops_per_step"] > 0
    assert "train/mfu" not in snap["gauges"]
    assert snap["gauges"]["memory/host_max_rss_mb"] > 0

    events = [json.loads(l) for l in open(jsonl)]
    assert len(events) >= 3
    assert {"ts", "rank", "step", "metrics"} <= set(events[0])

    # trace window: dir non-empty after the [1, 3) capture
    assert snap["counters"]["profiling/trace_windows"] == 1
    n_files = sum(len(fs) for _, _, fs in os.walk(tmp_path / "trace"))
    assert n_files > 0

    # a device kind that IS in the peak table gets the MFU gauge
    import jax
    from deepspeed_tpu.profiling import flops_profiler
    monkeypatch.setitem(flops_profiler.PEAK_BF16_FLOPS,
                        jax.devices()[0].device_kind, 1e12)
    for _ in range(2):
        engine.train_batch(batch)
    assert engine.telemetry_flush(batch)["gauges"]["train/mfu"] > 0


def test_train_step_scope_names_reach_the_compiled_text():
    """The ``annotate`` scopes the benchmark joins device events to
    (spans.annotate's list) survive a refactor: a scanned, rematted GPT-2
    step with the chunked loss head carries each in some ``op_name`` of
    its compiled text (the plain lowered text drops locations)."""
    import re
    from deepspeed_tpu.models.gpt2 import GPT2Config, GPT2LMHeadModel
    cfg = GPT2Config(vocab_size=256, n_positions=32, n_embd=32, n_layer=2,
                     n_head=2, scan_layers=True, remat=True,
                     remat_policy="dots_flash_fc_lean", loss_chunk=16)
    engine, _, _, _ = dstpu.initialize(config=base_config(),
                                       model=GPT2LMHeadModel(cfg))
    batch = {"input_ids": np.random.RandomState(0).randint(
        0, 256, (8, 32)).astype(np.int32)}
    engine.train_batch(batch)
    hlo = engine.lower_train_step(batch).compile().as_text()
    for scope in ("ds_optimizer", "ds_loss_head", "ds_embed",
                  "transpose(jvp(", "rematted_computation"):
        assert re.search(r'op_name="[^"]*' + re.escape(scope), hlo), scope


@pytest.mark.parametrize("n_embd,n_head,per_block,layout", [
    (256, 4, 2, "[B, S, H*D] column blocks of 2 heads"),    # head_dim 64
    (256, 2, 1, "[B, S, H*D] column blocks of 1 heads"),    # head_dim 128
    (192, 4, 0, "[B*H, S, D] head-major"),                  # head_dim 48
], ids=["head_dim64", "head_dim128", "head_dim48-head_major"])
def test_flash_heads_per_block_gauge_and_plan_line(n_embd, n_head, per_block,
                                                   layout):
    """``attention/flash_heads_per_block`` (ISSUE 30) says at trace time
    whether a GPT-2 forward took the column-block flash path — heads a
    128-lane block of the model's own [B, S, H*D] arrays — or went
    head-major (0), and the plan's log line names the layout. (The package
    logger does not propagate: a handler of its own, not caplog.)"""
    import importlib
    import logging
    import jax.numpy as jnp
    from deepspeed_tpu.models.gpt2 import GPT2Config, GPT2LMHeadModel
    from deepspeed_tpu.utils.logging import logger as dlog
    fa = importlib.import_module("deepspeed_tpu.ops.pallas.flash_attention")
    fa._plans_logged.clear()
    cfg = GPT2Config(vocab_size=256, n_positions=128, n_embd=n_embd,
                     n_layer=1, n_head=n_head, use_flash=True,
                     dtype=jnp.float32)
    model = GPT2LMHeadModel(cfg)
    ids = jax.ShapeDtypeStruct((2, 128), jnp.int32)
    records = []
    handler = logging.Handler()
    handler.emit = records.append
    dlog.addHandler(handler)
    try:
        params = jax.eval_shape(model.init, jax.random.PRNGKey(0), ids)
        default_registry().gauge("attention/flash_heads_per_block").set(-1)
        jax.eval_shape(model.apply, params, ids)
    finally:
        dlog.removeHandler(handler)
    assert default_registry().peek_gauge(
        "attention/flash_heads_per_block") == per_block
    plans = [r.getMessage() for r in records
             if r.getMessage().startswith("flash attention S=128")]
    assert plans and all(f"layout {layout}," in p for p in plans), plans


def test_moe_scope_names_and_gauges_reach_the_step():
    """ISSUE 27's names: a LLaMA model with experts and QK-norm carries
    ``moe_router`` / ``moe_dispatch`` / ``moe_gmm*`` / ``moe_combine`` /
    ``qk_norm`` in its compiled step's ``op_name``s, and the step's
    ``stats`` collection arrives as the ``moe/*`` gauges at a fold."""
    import re
    import jax.numpy as jnp
    from deepspeed_tpu.models.llama import LlamaForCausalLM, olmoe_1b_7b
    default_registry().reset()
    cfg = olmoe_1b_7b(vocab_size=256, hidden_size=32, intermediate_size=16,
                      n_layers=1, n_heads=2, max_seq_len=32, num_experts=4,
                      num_experts_per_tok=2, loss_chunk=16,
                      dtype=jnp.float32)
    engine, _, _, _ = dstpu.initialize(config=base_config(),
                                       model=LlamaForCausalLM(cfg))
    batch = {"input_ids": np.random.RandomState(0).randint(
        0, 256, (8, 32)).astype(np.int32)}
    engine.train_batch(batch)
    assert "moe/aux_loss" not in engine.telemetry_snapshot()["gauges"], \
        "read back before a fold"
    gauges = engine.telemetry_flush()["gauges"]
    assert {"moe/aux_loss", "moe/z_loss", "moe/rows_max_over_mean",
            "moe/dropped_rows"} <= set(gauges)
    assert gauges["moe/dropped_rows"] == 0
    hlo = engine.lower_train_step(batch).compile().as_text()
    for scope in ("moe_router", "moe_dispatch", "moe_gmm", "moe_gmm_dlhs",
                  "moe_gmm_drhs", "moe_combine", "qk_norm", "ds_embed",
                  "ds_loss_head"):
        assert re.search(r'op_name="[^"]*/' + scope + "/", hlo), scope


# ----------------------------------- start-up spans and compile events

STARTUP_TAGS = ("sharded_init", "engine_init", "state_init", "build_fns")


def _tiny_gpt2_engine():
    """ZeRO-3 with no parameters handed in: the first ``train_batch`` runs
    ``_init_state``, which makes the weights through ``sharded_init``."""
    from deepspeed_tpu.models.gpt2 import GPT2Config, GPT2LMHeadModel
    cfg = GPT2Config(vocab_size=256, n_positions=32, n_embd=32, n_layer=2,
                     n_head=2, scan_layers=True)
    engine, _, _, _ = dstpu.initialize(
        config=base_config(zero_optimization={"stage": 3}),
        model=GPT2LMHeadModel(cfg))
    batch = {"input_ids": np.random.RandomState(0).randint(
        0, 256, (8, 32)).astype(np.int32)}
    return engine, batch


def _events_since(seq, *kinds):
    from deepspeed_tpu.telemetry import default_recorder
    return [e for e in default_recorder().events()
            if e["seq"] > seq and e["kind"] in kinds]


def _last_seq():
    from deepspeed_tpu.telemetry import default_recorder
    events = default_recorder().events()
    return events[-1]["seq"] if events else 0


def _logged_while(call):
    """Messages the package logger (which does not propagate) emitted
    during ``call()``."""
    import logging
    from deepspeed_tpu.utils.logging import logger as dlog
    records = []
    handler = logging.Handler()
    handler.emit = records.append
    dlog.addHandler(handler)
    try:
        call()
    finally:
        dlog.removeHandler(handler)
    return [r.getMessage() for r in records]


def test_startup_spans_fire_once_each_on_the_harness_clock():
    """ISSUE 35: ``startup/{sharded_init,engine_init,state_init,build_fns}``
    are one-time branches — one observation each after ``initialize`` and two
    steps, none added by the second step — and every ``span`` and ``compile``
    event carries ``t0_mono``, its start on ``time.monotonic()``, so that
    set-up's spans, each step's ``train/step_dispatch`` and a harness's own
    stamps lie on one axis."""
    import time
    reg = default_registry()
    reg.reset()
    seq, t_before = _last_seq(), time.monotonic()
    engine, batch = _tiny_gpt2_engine()
    engine.train_batch(batch)
    counts = {t: reg.peek_histogram_count(f"span/startup/{t}")
              for t in STARTUP_TAGS}
    assert counts == dict.fromkeys(STARTUP_TAGS, 1)
    jax.block_until_ready(engine.train_batch(batch))
    t_after = time.monotonic()
    assert counts == {t: reg.peek_histogram_count(f"span/startup/{t}")
                      for t in STARTUP_TAGS}
    assert reg.peek_histogram_count("span/train/step_dispatch") == 2

    events = _events_since(seq, "span", "compile")
    assert {e["kind"] for e in events} == {"span", "compile"}
    for e in events:
        assert t_before <= e["t0_mono"], e
        assert e["t0_mono"] + e["dur_s"] <= t_after + 1e-3, e
    start = {e["tag"]: e["t0_mono"] for e in events if e["kind"] == "span"
             and e["tag"].startswith("startup/")}
    end = {e["tag"]: e["t0_mono"] + e["dur_s"] for e in events
           if e["kind"] == "span" and e["tag"].startswith("startup/")}
    assert set(start) == {"startup/" + t for t in STARTUP_TAGS}
    # in order: initialize returns, then the first train_batch makes the
    # state (the weights through sharded_init, inside it) and the step
    assert end["startup/engine_init"] <= start["startup/state_init"] \
        <= start["startup/sharded_init"] <= end["startup/sharded_init"] \
        <= end["startup/state_init"] <= start["startup/build_fns"]
    steps = [e for e in events if e.get("tag") == "train/step_dispatch"]
    assert [e["step"] for e in steps] == [0, 1]
    assert end["startup/build_fns"] <= steps[0]["t0_mono"]
    # step 0's dispatch holds the step's compile: its backend phase began
    # and ended inside the span
    inside = [e for e in events if e["kind"] == "compile"
              and e["phase"] == "backend"
              and "train_batch_fn" in e["fun_name"]]
    assert len(inside) == 1
    assert steps[0]["t0_mono"] <= inside[0]["t0_mono"] and \
        inside[0]["t0_mono"] + inside[0]["dur_s"] <= \
        steps[0]["t0_mono"] + steps[0]["dur_s"] + 1e-3


def test_watch_compiles_registers_once_and_names_each_phase():
    """Two engines in a process (``watch_compiles()`` twice) leave ONE
    listener a kind, and one call of a fresh jitted function is one program:
    a ``compile`` event a phase with the function's name and a start between
    the stamps taken round the call — twice registered it would be two. A
    trace under 10 ms (as a rule the jitted function called INSIDE the
    trace; on a loaded host it may take longer, and is then an event of its
    own inside the outer one's interval) leaves no event."""
    import time
    from jax._src import monitoring
    from deepspeed_tpu.telemetry import spans
    spans.watch_compiles()
    spans.watch_compiles()
    assert monitoring.get_event_time_span_listeners().count(
        spans._on_compile_phase) == 1
    assert monitoring.get_event_listeners().count(
        spans._on_cache_event) == 1

    @jax.jit
    def _probe_inner(x):
        return x + 1

    def _probe_outer(x):
        time.sleep(0.02)            # at trace time: a trace worth an event
        return _probe_inner(x) * 3

    seq, t0 = _last_seq(), time.monotonic()
    # (a numpy operand: ``jnp.ones`` would be a program of its own)
    jax.block_until_ready(jax.jit(_probe_outer)(np.ones((7,), np.float32)))
    t1 = time.monotonic()
    probes = [e for e in _events_since(seq, "compile")
              if "_probe" in (e["fun_name"] or "")]
    mine = [e for e in probes if "_probe_outer" in e["fun_name"]]
    assert sorted(e["phase"] for e in mine) == ["backend", "lower", "trace"]
    for e in mine:
        assert t0 <= e["t0_mono"] and e["t0_mono"] + e["dur_s"] <= t1 + 1e-3
        assert e["dur_s"] > 0
    assert next(e for e in mine if e["phase"] == "backend")["cache"] is None
    outer = next(e for e in mine if e["phase"] == "trace")
    for e in probes:
        if e not in mine:           # the inner function, traced slowly
            assert e["phase"] == "trace" and \
                spans._SHORT_TRACE_S <= e["dur_s"] <= outer["dur_s"]
    # a trace of microseconds (an eager call that finds its program) leaves
    # the ring to the programs: the listener fed by hand, on either side
    trace = "/jax/core/compile/jaxpr_trace_duration"
    seq, now = _last_seq(), time.time()
    spans._on_compile_phase(trace, now - 0.005, now, fun_name="_fed_short")
    spans._on_compile_phase(trace, now - 0.02, now, fun_name="_fed_long")
    spans._on_compile_phase("/jax/core/compile/jaxpr_to_mlir_module_duration",
                            now - 0.001, now, fun_name="_fed_short")
    assert [(e["fun_name"], e["phase"])
            for e in _events_since(seq, "compile")] == [
        ("_fed_long", "trace"), ("_fed_short", "lower")]


def test_a_compile_after_the_first_step_is_counted_and_named_once():
    """A backend compile once a first optimizer step has returned counts in
    ``compile/after_first_step`` and logs its function's name ONCE — the
    operator's sign of a recompile in steady state."""
    reg = default_registry()
    reg.reset()
    engine, batch = _tiny_gpt2_engine()
    jax.block_until_ready(engine.train_batch(batch))
    # everything up to here compiled BEFORE a first step had returned
    assert reg.counter("compile/after_first_step").value == 0

    def _late_probe(x):
        return x * 2 - 1

    late = jax.jit(_late_probe)
    lines = _logged_while(lambda: [late(np.ones((n,), np.float32))
                                   for n in (3, 5)])    # two programs
    assert reg.counter("compile/after_first_step").value == 2
    named = [m for m in lines if "_late_probe" in m]
    assert len(named) == 1 and "after the first optimizer step" in named[0]


def test_cache_verdicts_are_counted_from_jax_monitoring_events():
    """Hits and misses come from the events JAX's persistent cache records
    (fed by hand here: no dependence on a CPU cache): the backend phase they
    fell in carries the verdict, and ``compile/cache_misses`` keeps the
    misses past the ring's turnover."""
    import time
    from jax import monitoring
    from deepspeed_tpu.telemetry import spans
    spans.watch_compiles()
    reg = default_registry()
    reg.reset()
    seq = _last_seq()
    backend = "/jax/core/compile/backend_compile_duration"
    for verdict in ("cache_hits", "cache_misses", "cache_hits"):
        now = time.time()
        monitoring.record_event("/jax/compilation_cache/" + verdict)
        monitoring.record_event_time_span(backend, now, now + 1.0,
                                          fun_name="jit(fed_by_hand)")
    monitoring.record_event("/jax/compilation_cache/tasks_using_cache")
    assert reg.counter("compile/cache_misses").value == 1
    assert [e["cache"] for e in _events_since(seq, "compile")] == [
        "hit", "miss", "hit"]


def test_engine_without_gates_records_but_never_prices_or_exports():
    """No monitor/profiling config: counters still move (snapshot is
    always available) but no cost-analysis retrace, no exporter, no
    trace — the zero-config cost is bookkeeping only."""
    default_registry().reset()
    engine, _, _, _ = dstpu.initialize(config=base_config(),
                                       model=SimpleModel())
    batch = (np.random.RandomState(0).randn(8, 8).astype(np.float32),
             np.zeros((8,), np.int32))
    for _ in range(3):
        engine.train_batch(batch)
    assert engine._trace_window is None
    assert engine._telemetry_exporters() == []
    snap = engine.telemetry_snapshot()
    assert snap["counters"]["train/steps"] == 3
    assert engine._tel_flops_per_step is None      # never priced
    assert "train/mfu" not in snap["gauges"]


def test_config_gates_validation():
    from deepspeed_tpu.config.config import (DeepSpeedConfig,
                                             DeepSpeedConfigError)
    c = DeepSpeedConfig({"train_batch_size": 4})
    assert not c.monitor_config.enabled and not c.profiling_config.trace_dir
    with pytest.raises(DeepSpeedConfigError):
        DeepSpeedConfig({"train_batch_size": 4,
                         "profiling": {"trace_dir": "/tmp/x"}})
    with pytest.raises(DeepSpeedConfigError):
        DeepSpeedConfig({"train_batch_size": 4,
                         "profiling": {"trace_dir": "/tmp/x",
                                       "trace_steps": [3, 3]}})


def test_trace_window_unit():
    tw = TraceWindow.from_config(type("P", (), {
        "trace_dir": "", "trace_steps": ()})())
    assert tw is None
    tw = TraceWindow("/tmp/nonexistent_ok", 2, 4)
    assert not tw.active and not tw.done
    tw.on_step_end(5)          # never started — must be a no-op
    assert not tw.done


# --------------------------------------------------------------- serving

def test_serving_metrics_snapshot_mixed_workload():
    """Mixed prompt/budget workload through the tiny CPU serving
    engine: TTFT and admission wait per request, tick latency + slot
    utilization per tick, page-pool occupancy high-water mark."""
    import jax.numpy as jnp
    from deepspeed_tpu.models.gpt2 import GPT2Config, GPT2LMHeadModel
    import deepspeed_tpu.serving as serving
    cfg = GPT2Config(vocab_size=128, n_positions=64, n_embd=32,
                     n_layer=2, n_head=4, dtype=jnp.float32,
                     param_dtype=jnp.float32, scan_layers=True)
    params = jax.jit(GPT2LMHeadModel(cfg).init)(
        jax.random.PRNGKey(0), np.zeros((1, 8), np.int32))["params"]
    eng = serving.build_engine(
        "gpt2", cfg, params,
        config={"serving": {"slots": 2, "page_size": 16,
                            "max_pages_per_slot": 3}})
    rs = np.random.RandomState(0)
    shapes = [(8, 4), (20, 3), (5, 4), (16, 2)]   # (prompt, max_new)
    reqs = [serving.Request(i, rs.randint(0, 128, size=(s,))
                            .astype(np.int32), max_new_tokens=n)
            for i, (s, n) in enumerate(shapes)]
    done = eng.serve(reqs)
    assert len(done) == 4
    snap = eng.metrics_snapshot()
    assert snap["ttft_s"]["count"] == 4
    assert snap["admission_wait_s"]["count"] == 4
    assert snap["ttft_s"]["p50"] >= 0 and snap["ttft_s"]["max"] > 0
    assert 0 < snap["page_pool"]["occupancy_hwm"] <= 1
    assert snap["page_pool"]["used_pages"] == 0    # all released
    assert snap["tick_latency_s"]["count"] == snap["ticks"] > 0
    assert 0 < snap["slot_utilization"]["max"] <= 1
    # decode tokens exclude each request's prefill-sampled first token
    assert snap["decode_tokens"] == sum(n for _, n in shapes) - len(shapes)
    assert snap["decode_tokens_per_sec"] > 0
    assert snap["queue_depth"] == 0 and snap["active_slots"] == 0
