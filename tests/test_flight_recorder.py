"""Flight recorder + anomaly watchdog (ISSUE 6): bounded ring
semantics, fence-point rule evaluation, one-shot dumps for the three
injected anomalies (NaN loss through a real engine boundary, a seeded
swap-stall spike, a throttled-tick TTFT blowup through the serving
scheduler), and the dump viewer. All fast — the only engine compile is
the SimpleModel step the telemetry tests already pay."""

import json
import os
import threading
import time

import numpy as np
import jax.numpy as jnp
import pytest

import deepspeed_tpu as dstpu
from deepspeed_tpu.telemetry import view
from deepspeed_tpu.telemetry.anomaly import RollingOutlierRule, Watchdog
from deepspeed_tpu.telemetry.recorder import (FlightRecorder,
                                              default_recorder)
from tests.simple_model import SimpleModel, base_config


# --------------------------------------------------------------- recorder

def test_recorder_ring_is_bounded_and_ordered():
    rec = FlightRecorder(capacity=64)
    for i in range(200):
        rec.record("step", step=i)
    evs = rec.events()
    assert len(evs) == 64
    assert [e["step"] for e in evs] == list(range(136, 200))
    # seq is monotonic and survives the ring wrap
    assert [e["seq"] for e in evs] == list(range(137, 201))


def test_recorder_disabled_is_a_noop_and_configure_flips():
    rec = FlightRecorder(capacity=64, enabled=False)
    rec.record("x")
    assert len(rec) == 0
    rec.configure(enabled=True)
    rec.record("x")
    assert len(rec) == 1
    rec.configure(capacity=128)          # resize keeps events
    assert len(rec) == 1 and rec.capacity == 128


def test_recorder_step_context_stamps_events():
    rec = FlightRecorder()
    rec.set_step(7)
    rec.record("span", tag="t", dur_s=0.1)
    rec.record("loss", step=9, loss=1.0)   # explicit step wins
    evs = rec.events()
    assert evs[0]["step"] == 7 and evs[1]["step"] == 9


def test_recorder_thread_safety():
    rec = FlightRecorder(capacity=4096)

    def worker(k):
        for i in range(200):
            rec.record("t", worker=k, i=i)

    threads = [threading.Thread(target=worker, args=(k,))
               for k in range(4)]
    for t in threads:
        t.start()
    for t in threads:
        t.join()
    evs = rec.events()
    assert len(evs) == 800
    assert len({e["seq"] for e in evs}) == 800     # no lost updates


# ------------------------------------------------------------- rule logic

def test_rolling_outlier_rule_warmup_trip_latch_rearm():
    r = RollingOutlierRule("x", factor=3.0, min_samples=4, window=16)
    assert r.observe(100.0) is None      # warming: even a huge value
    for _ in range(4):
        assert r.observe(0.1) is None
    det = r.observe(10.0)
    assert det and det["value"] == 10.0 and det["threshold"] > 0
    assert r.observe(10.0) is None       # latched
    assert r.observe(0.1) is None        # re-arms (and feeds baseline)
    assert r.observe(10.0)               # trips again


def test_rolling_outlier_rule_absolute_floor():
    r = RollingOutlierRule("x", factor=3.0, min_value=0.05,
                           min_samples=2)
    r.observe(0.001)
    r.observe(0.001)
    assert r.observe(0.01) is None       # 10x baseline but under floor
    assert r.observe(0.2)                # over both


# ------------------------------------------------- watchdog + dump format

def _prefilled_recorder(n=40):
    rec = FlightRecorder(capacity=256)
    for i in range(n):
        rec.record("step", step=i, tokens=128, swap_stall_s=0.01)
    return rec


def _dump_files(d):
    return sorted(f for f in os.listdir(d) if f.startswith("flight_"))


def test_swap_stall_spike_produces_exactly_one_dump(tmp_path):
    """Satellite: a seeded swap-stall spike -> one dump with the last
    >= 32 ring events; repeated spikes in the same episode stay
    latched."""
    rec = _prefilled_recorder(40)
    w = Watchdog(str(tmp_path), recorder=rec, source="train",
                 min_samples=4)
    for _ in range(8):
        assert w.observe_swap_stall(0.01) is None
    path = w.observe_swap_stall(1.0)     # the seeded spike
    assert path and os.path.exists(path)
    assert w.observe_swap_stall(1.0) is None    # latched
    assert _dump_files(tmp_path) == [os.path.basename(path)]
    header, events, skipped = view.load_dump(path)
    assert skipped == 0
    assert header["rule"] == "swap_stall_outlier"
    assert header["dump_id"] == 1 and header["source"] == "train"
    assert header["detail"]["value"] == 1.0
    assert len(events) >= 32             # the last >=32 ring events
    assert events == rec.events()[:len(events)]  # pre-anomaly history
    assert w.snapshot()["trips"] == {"swap_stall_outlier": 1}


def test_step_time_outlier_and_dump_counters(tmp_path):
    from deepspeed_tpu.telemetry import MetricsRegistry
    reg = MetricsRegistry()
    w = Watchdog(str(tmp_path), recorder=_prefilled_recorder(),
                 registry=reg, min_samples=4)
    for _ in range(6):
        assert w.observe_step_time(0.1) is None
    assert w.observe_step_time(0.5)      # > 3x baseline
    snap = reg.snapshot()
    assert snap["counters"]["watchdog/dumps"] == 1
    assert snap["counters"]["watchdog/trips/step_time_outlier"] == 1
    assert snap["gauges"]["watchdog/last_dump_id"] == 1


def test_nan_latch_and_unwritable_dir_is_nonfatal(tmp_path):
    w = Watchdog(os.path.join(str(tmp_path), "no", "such", "dir"),
                 recorder=_prefilled_recorder())
    # makedirs creates it — use a FILE as the dir to force the failure
    blocker = tmp_path / "blocked"
    blocker.write_text("x")
    w2 = Watchdog(str(blocker), recorder=_prefilled_recorder())
    assert w2.check_loss(np.nan) is None          # dump failed...
    assert w2.dump_id == 1                        # ...trip still counted
    assert w2.check_loss(np.inf) is None          # latched
    assert w2.check_loss(1.0) is None             # finite re-arms
    assert w2.check_loss(np.nan) is None and w2.dump_id == 2
    assert w.check_loss(1.0) is None and w.dump_id == 0


# ----------------------------------------------- anomaly 1: NaN loss (e2e)

def test_forced_nan_loss_dumps_once_through_engine_boundary(tmp_path):
    """A real engine run: finite steps build >= 32 ring events, then a
    batch of infs drives the loss non-finite — the steps_per_print
    boundary readback (the fence the engine already pays) trips the
    watchdog exactly once, and the dump renders in the viewer."""
    default_recorder().clear()
    dump_dir = str(tmp_path / "flight")
    cfg = base_config(steps_per_print=1)
    cfg["monitor"] = {"enabled": False,
                      "flight_recorder": {"capacity": 512},
                      # step_time_factor raised way past CPU-harness
                      # jitter: THIS test is about the NaN rule, and a
                      # contended box can legitimately produce a 3x
                      # step-time outlier during warmup (observed flake)
                      "watchdog": {"dump_dir": dump_dir,
                                   "min_samples": 4,
                                   "step_time_factor": 100.0}}
    engine, _, _, _ = dstpu.initialize(config=cfg, model=SimpleModel())
    assert engine.watchdog is not None
    rs = np.random.RandomState(0)
    batch = (rs.randn(8, 8).astype(np.float32),
             rs.randint(0, 4, size=(8,)).astype(np.int32))
    for _ in range(12):
        engine.train_batch(batch)
    assert not (os.path.isdir(dump_dir) and _dump_files(dump_dir))
    bad = (np.full((8, 8), np.inf, np.float32), batch[1])
    for _ in range(3):                   # NaN persists: still ONE dump
        engine.train_batch(bad)
    files = _dump_files(dump_dir)
    assert len(files) == 1, files
    path = os.path.join(dump_dir, files[0])
    header, events, _ = view.load_dump(path)
    assert header["rule"] == "nan_loss"
    assert len(events) >= 32
    kinds = {e["kind"] for e in events}
    assert {"span", "step", "loss"} <= kinds
    # the engine's serving-style snapshot surfaces the trip
    assert engine.watchdog.dump_id == 1
    assert engine.watchdog.last_anomaly["rule"] == "nan_loss"
    # viewer renders the real dump
    out = _render_lines(path)
    assert "nan_loss" in out and "per-step phase attribution" in out


def _render_lines(path):
    return "\n".join(view.render(path, tail_events=4))


# ------------------------------------- anomaly 2+3: serving TTFT / pool

class _StubAdapter:
    """Host-only adapter: instant prefill/tick, so the scheduler (and
    only the scheduler) is under test. Matches the adapter protocol the
    ContinuousBatcher drives."""

    def __init__(self, spec):
        self.spec = spec

    def make_cache(self):
        from deepspeed_tpu.serving.paged_cache import PagedKVCache
        return PagedKVCache(self.spec)

    def max_prompt_len(self):
        return 4096

    def prefill(self, pool, ids, length, pages):
        return pool, np.zeros((16,), np.float32)

    def tick(self, pool, toks, pos, pt, seeds, idxs, temps, steps=1):
        return pool, np.ones((steps, self.spec.slots), np.int32), None


def _serving_engine(tmp_path, num_blocks=0, min_samples=4):
    from deepspeed_tpu.serving.paged_cache import PagedCacheSpec
    from deepspeed_tpu.serving.engine import ContinuousBatcher
    spec = PagedCacheSpec(n_layers=1, kv_heads=1, head_dim=4,
                          page_size=4, max_pages_per_slot=4, slots=2,
                          num_blocks=num_blocks, dtype=jnp.float32)
    rec = _prefilled_recorder(40)
    w = Watchdog(str(tmp_path), recorder=rec, source="serving",
                 min_samples=min_samples)
    return ContinuousBatcher(_StubAdapter(spec), recorder=rec,
                             watchdog=w), w, rec


def test_throttled_tick_ttft_blowup_dumps_once(tmp_path):
    """Baseline TTFTs from fast admissions, then one request whose
    admission was throttled (its clock started long before the
    scheduler got to it) — the TTFT rule trips exactly once at the
    admission sweep, and metrics_snapshot surfaces dump_id /
    last-anomaly."""
    from deepspeed_tpu.serving.engine import Request
    eng, w, _ = _serving_engine(tmp_path)
    for i in range(6):                   # fast-TTFT baseline
        eng.submit(Request(i, np.zeros((4,), np.int32),
                           max_new_tokens=2))
        while eng.pending:
            eng.step()
    snap = eng.metrics_snapshot()
    assert snap["dump_id"] == 0 and snap["last_anomaly"] is None
    late = Request("late", np.zeros((4,), np.int32), max_new_tokens=2)
    eng.submit(late)
    late._t_submit = time.monotonic() - 30.0   # throttled for 30 s
    while eng.pending:
        eng.step()
    files = _dump_files(tmp_path)
    assert len(files) == 1 and "ttft_blowup" in files[0]
    header, events, _ = view.load_dump(os.path.join(str(tmp_path),
                                                    files[0]))
    assert header["rule"] == "ttft_blowup"
    assert header["detail"]["rid"] == "late"
    assert len(events) >= 32
    snap = eng.metrics_snapshot()
    assert snap["dump_id"] == 1
    assert snap["last_anomaly"]["rule"] == "ttft_blowup"
    assert snap["watchdog"]["trips"] == {"ttft_blowup": 1}


def test_page_pool_exhaustion_dumps_once_and_rearms(tmp_path):
    """Two requests that cannot share the pool: the second's blocked
    admission trips page_pool_exhausted ONCE (latched across retries);
    after the pool frees and an admission succeeds the rule re-arms."""
    from deepspeed_tpu.serving.engine import Request
    eng, w, rec = _serving_engine(tmp_path, num_blocks=7)  # 6 usable
    eng.submit(Request(0, np.zeros((8,), np.int32), max_new_tokens=8))
    eng.submit(Request(1, np.zeros((8,), np.int32), max_new_tokens=8))
    done = {}
    for _ in range(40):
        for r in eng.step():
            done[r.rid] = r
        if not eng.pending:
            break
    assert set(done) == {0, 1}
    files = _dump_files(tmp_path)
    assert len(files) == 1 and "page_pool_exhausted" in files[0]
    assert not w._pool_tripped           # re-armed by the later admit
    kinds = [e["kind"] for e in rec.events()]
    assert "pool_exhausted" in kinds and "finish" in kinds
    # request lifecycle is in the ring: admit -> prefill -> finish
    admits = [e for e in rec.events() if e["kind"] == "admit"]
    assert {e["rid"] for e in admits} == {0, 1}


def test_serving_events_render_request_timelines(tmp_path):
    from deepspeed_tpu.serving.engine import Request
    eng, w, rec = _serving_engine(tmp_path)
    eng.submit(Request(3, np.zeros((4,), np.int32), max_new_tokens=3))
    while eng.pending:
        eng.step()
    path = w.force_dump("manual")
    out = _render_lines(path)
    assert "per-request timelines" in out
    assert "prompt_toks" in out and "length" in out   # finish reason


def test_recorder_disabled_engine_records_nothing(tmp_path):
    """monitor.flight_recorder.enabled=false: the hot-path record()
    calls all no-op (the recorder-off cost is one branch)."""
    default_recorder().clear()
    cfg = base_config(steps_per_print=1)
    cfg["monitor"] = {"enabled": False,
                      "flight_recorder": {"enabled": False}}
    engine, _, _, _ = dstpu.initialize(config=cfg, model=SimpleModel())
    assert engine.watchdog is None
    rs = np.random.RandomState(0)
    batch = (rs.randn(8, 8).astype(np.float32),
             rs.randint(0, 4, size=(8,)).astype(np.int32))
    for _ in range(3):
        engine.train_batch(batch)
    assert len(default_recorder()) == 0
    default_recorder().configure(enabled=True)   # undo for later tests


# ------------------------------------------------------------------ config

def test_monitor_subblock_validation():
    from deepspeed_tpu.config.config import (DeepSpeedConfig,
                                             DeepSpeedConfigError)
    c = DeepSpeedConfig({"train_batch_size": 4})
    mc = c.monitor_config
    assert mc.flight_recorder.enabled and mc.flight_recorder.capacity \
        == 4096
    assert not mc.watchdog.enabled
    c = DeepSpeedConfig({"train_batch_size": 4,
                         "monitor": {"enabled": False,
                                     "watchdog": {"dump_dir": "/tmp/x"}}})
    assert c.monitor_config.watchdog.enabled     # own gate, not monitor's
    assert not c.monitor_config.enabled
    with pytest.raises(DeepSpeedConfigError):
        DeepSpeedConfig({"train_batch_size": 4,
                         "monitor": {"flight_recorder": {"capacity": 8}}})
    with pytest.raises(DeepSpeedConfigError):
        DeepSpeedConfig({"train_batch_size": 4,
                         "monitor": {"watchdog":
                                     {"step_time_factor": 0.5}}})
    with pytest.raises(DeepSpeedConfigError):
        DeepSpeedConfig({"train_batch_size": 4,
                         "monitor": {"jsonl_max_files": 0}})


# ----------------------------------------------------------------- viewer

def test_view_cli_on_synthetic_dump_and_missing_file(tmp_path, capsys):
    path = str(tmp_path / "d.jsonl")
    t0 = 1000.0
    lines = [
        {"kind": "dump_header", "rule": "step_time_outlier",
         "dump_id": 2, "source": "train", "ts": t0, "n_events": 4,
         "detail": {"value": 0.9, "threshold": 0.3}},
        {"kind": "span", "tag": "train/step_dispatch", "dur_s": 0.01,
         "step": 5, "ts": t0, "seq": 1},
        {"kind": "step", "step": 5, "tokens": 1024,
         "swap_stall_s": 0.002, "ts": t0, "seq": 2},
        {"kind": "loss", "step": 5, "loss": 2.5, "ts": t0, "seq": 3},
        {"kind": "swap_in", "step": 5, "bytes_read": 2 ** 20,
         "cache_hit_bytes": 0, "leaves": 3, "ts": t0, "seq": 4},
        "this line is not json",
    ]
    with open(path, "w") as fh:
        for l in lines:
            fh.write((l if isinstance(l, str) else json.dumps(l))
                     + "\n")
    assert view.main([path, "--events", "2"]) == 0
    out = capsys.readouterr().out
    assert "step_time_outlier" in out
    assert "step_dispatch" in out and "2.5" in out
    assert "swap-tier I/O per step" in out
    assert "1 unparseable line(s) skipped" in out
    assert view.main([str(tmp_path / "missing.jsonl")]) == 2


def test_view_renders_comm_bytes_column_and_hierarchy_plan(tmp_path):
    """ISSUE 10 satellite: step events carrying the hierarchical comm
    cost model render a per-step comm-bytes column in the phase table;
    the onebit_freeze ring event marks the transition and the
    comm_hierarchy_plan breadcrumb shows up with the bucket plans."""
    import json
    path = tmp_path / "comm.jsonl"
    events = [
        {"kind": "comm_hierarchy_plan", "buckets": 1, "compressed": 1,
         "inter": 2, "intra": 4, "policy": "always"},
        {"kind": "step", "step": 1, "tokens": 128,
         "comm_intra_bytes": 2 * 2**20, "comm_inter_bytes": 1 * 2**20},
        {"kind": "onebit_freeze", "step": 2, "freeze_step": 1,
         "hierarchical": True},
        {"kind": "step", "step": 2, "tokens": 128,
         "comm_intra_bytes": 2 * 2**20, "comm_inter_bytes": 65536},
        {"kind": "loss", "step": 2, "loss": 1.5},
    ]
    path.write_text("\n".join(json.dumps(e) for e in events) + "\n")
    out = _render_lines(str(path))
    assert "comm_mb" in out
    assert "comm_phase" in out and "freeze" in out
    assert "comm_hierarchy_plan" in out
    # 3 MiB on step 1; the post-freeze step shrinks
    lines = [ln for ln in out.splitlines() if ln.strip().startswith("1 ")]
    assert any("3" in ln for ln in lines), out
