"""Distributed trace plane (ISSUE 19): causal span ids across the
prefill -> transport -> decode -> finish lifecycle, the Perfetto
exporter, and the dump-header provenance stamp.

The loopback legs run the REAL node state machines (the same ones the
2-process acceptance drives) in one process, so tier-1 pins the causal
tree — every ``parent_span`` in a complete dump set resolves to some
event's ``span_id``, zero orphans — without paying a process spawn.
"""

import json

import numpy as np
import jax
import jax.numpy as jnp
import pytest

import deepspeed_tpu.serving as serving
from deepspeed_tpu.serving.engine import ContinuousBatcher
from deepspeed_tpu.telemetry.perfetto import export, orphan_spans
from deepspeed_tpu.telemetry.recorder import default_recorder
from deepspeed_tpu.telemetry.spans import new_span_id


@pytest.fixture(autouse=True)
def _clean():
    default_recorder().configure(enabled=True, capacity=4096)
    default_recorder().clear()
    yield


@pytest.fixture(scope="module")
def gpt2_adapter():
    from deepspeed_tpu.models.gpt2 import GPT2Config, GPT2LMHeadModel
    cfg = GPT2Config(vocab_size=256, n_positions=128, n_embd=64,
                     n_layer=2, n_head=4, dtype=jnp.float32,
                     param_dtype=jnp.float32, scan_layers=True)
    params = jax.jit(GPT2LMHeadModel(cfg).init)(
        jax.random.PRNGKey(0), np.zeros((1, 8), np.int32))["params"]
    return serving.build_engine(
        "gpt2", cfg, params,
        config={"serving": {"slots": 2, "page_size": 8,
                            "max_pages_per_slot": 8}}).adapter


def _reqs(n, max_new=4, seed=0):
    rs = np.random.RandomState(seed)
    lens = rs.choice([5, 9, 14], n)
    return [serving.Request(
        i, rs.randint(0, 256, size=(int(lens[i]),)).astype(np.int32),
        max_new_tokens=max_new) for i in range(n)]


def _mk_loopback(adapter, world=2):
    from deepspeed_tpu.serving.transport import (DecodeNode,
                                                 LoopbackFabric,
                                                 PrefillNode)
    fab = LoopbackFabric(world, addressing="targeted")
    pnode = PrefillNode(
        [ContinuousBatcher(adapter, role="prefill")], fab.endpoint(0))
    dnodes = [DecodeNode(ContinuousBatcher(adapter, role="decode",
                                           prefix_cache=True),
                         fab.endpoint(r)) for r in range(1, world)]
    pnode.on_tick = lambda _n: [d.tick() for d in dnodes]
    return pnode, dnodes


# ------------------------------------------------------------ span ids


def test_span_ids_unique_and_process_prefixed():
    ids = [new_span_id() for _ in range(500)]
    assert len(set(ids)) == 500
    # one shared process prefix, monotone suffixes — merged dumps from
    # DIFFERENT processes cannot collide (prefix carries the pid +
    # a random nonce), ids within one process never repeat
    prefixes = {i.rsplit("-", 1)[0] for i in ids}
    assert len(prefixes) == 1


def test_ensure_trace_id_mints_root_span_once():
    from deepspeed_tpu.serving.engine import ensure_trace_id
    req = serving.Request(0, np.arange(5, dtype=np.int32),
                          max_new_tokens=2)
    ensure_trace_id(req)
    first = (req.trace_id, req.span_id)
    assert req.span_id is not None
    ensure_trace_id(req)
    assert (req.trace_id, req.span_id) == first


def test_span_id_rides_the_wire_doc():
    from deepspeed_tpu.serving import elastic
    from deepspeed_tpu.serving.engine import ensure_trace_id
    req = serving.Request(7, np.arange(9, dtype=np.int32),
                          max_new_tokens=3)
    ensure_trace_id(req)
    doc = elastic._req_doc(req)
    assert doc["span_id"] == req.span_id
    back = elastic.resume_request(json.loads(json.dumps(doc)))
    assert back.span_id == req.span_id
    assert back.trace_id == req.trace_id


# ------------------------------------------- causal tree, zero orphans


def test_loopback_causal_tree_zero_orphans(gpt2_adapter):
    """THE acceptance pin, loopback form: serve through the real
    handoff path and every handoff renders as one causal tree under
    its trace_id — every parent_span resolves, the chain admit(root)
    -> handoff_out -> transport_encode -> handoff_in is parented
    exactly, and finish parents on the root."""
    pnode, _dnodes = _mk_loopback(gpt2_adapter, world=3)
    done = pnode.serve(_reqs(8, max_new=4), max_ticks=5000)
    assert len(done) == 8 and not pnode.lost
    events = default_recorder().events()
    assert orphan_spans(events) == []

    by_id = {ev["span_id"]: ev for ev in events
             if ev.get("span_id") is not None}
    roots = {ev["rid"]: ev["span_id"] for ev in events
             if ev.get("kind") == "admit"
             and ev.get("span_id") is not None}
    assert len(roots) == 8
    # admit is the ROOT: no parent
    for ev in events:
        if ev.get("kind") == "admit":
            assert ev.get("parent_span") is None
    hops = 0
    for ev in events:
        kind = ev.get("kind")
        if kind == "handoff_out":
            assert ev["parent_span"] == roots[ev["rid"]], ev
        elif kind == "transport_encode":
            parent = by_id[ev["parent_span"]]
            assert parent["kind"] == "handoff_out", parent
        elif kind == "handoff_in":
            hops += 1
            # walk up: encode -> handoff_out -> root
            enc = by_id[ev["parent_span"]]
            assert enc["kind"] == "transport_encode"
            out = by_id[enc["parent_span"]]
            assert out["kind"] == "handoff_out"
            assert out["parent_span"] == roots[ev["rid"]]
        elif kind == "finish":
            assert ev["parent_span"] == roots[ev["rid"]], ev
    assert hops >= 8


def test_orphan_spans_flags_missing_parent():
    events = [
        {"kind": "admit", "span_id": "a-1", "rid": 0},
        {"kind": "handoff_out", "span_id": "a-2", "parent_span": "a-1",
         "rid": 0},
        {"kind": "handoff_in", "span_id": "b-1", "parent_span": "a-9",
         "rid": 0},
    ]
    bad = orphan_spans(events)
    assert [o["parent_span"] for o in bad] == ["a-9"]
    events.append({"kind": "transport_encode", "span_id": "a-9"})
    assert orphan_spans(events) == []


# ---------------------------------------------- ttft segments (sat. 4)


def test_loopback_ttft_segments_sum_to_ttft(gpt2_adapter):
    """Per-role TTFT attribution stays sound through the transport
    path: on the prefill role, queue_wait + prefill account for
    ttft_s (the only gap is the sub-ms admit bookkeeping between the
    two timers)."""
    pnode, _dnodes = _mk_loopback(gpt2_adapter, world=2)
    done = pnode.serve(_reqs(10, max_new=3, seed=2), max_ticks=5000)
    assert len(done) == 10
    reg = pnode.engines[0].metrics
    ttft = reg.peek_histogram_values("serving/ttft_s")
    qw = reg.peek_histogram_values("serving/ttft_queue_wait_s")
    pf = reg.peek_histogram_values("serving/ttft_prefill_s")
    assert len(ttft) == len(qw) == len(pf) == 10
    gap = sum(ttft) - (sum(qw) + sum(pf))
    assert 0.0 <= gap <= 0.05 + 0.02 * sum(ttft), \
        (sum(ttft), sum(qw), sum(pf))
    # per-request decomposition, paired by rid through the ring: the
    # admit event's wait_s + the prefill event's prefill_s account for
    # that request's ttft_s up to the admit-bookkeeping sliver
    waits = {ev["rid"]: ev["wait_s"]
             for ev in default_recorder().events()
             if ev.get("kind") == "admit"}
    n = 0
    for ev in default_recorder().events():
        if ev.get("kind") != "prefill":
            continue
        n += 1
        seg = waits[ev["rid"]] + ev["prefill_s"]
        assert seg <= ev["ttft_s"] + 1e-6, ev
        assert ev["ttft_s"] - seg <= 0.01 + 0.1 * ev["ttft_s"], ev
    assert n == 10


# --------------------------------------------------- perfetto exporter


def _golden_dumps(tmp_path):
    """Two synthetic per-rank dumps with fixed timestamps — the same
    shape the CI golden uses (ci/make_perfetto_golden.py)."""
    r0 = [
        {"kind": "dump_header", "rule": "worker_exit", "dump_id": 1,
         "source": "rank0e0", "ts": 100.0,
         "provenance": {"git_sha": "abc1234", "hostname": "hostA"},
         "restart_epoch": 0},
        {"ts": 100.0, "kind": "admit", "rid": 0, "trace": "t0",
         "replica": 0, "span_id": "p0-1", "seq": 1},
        {"ts": 100.2, "kind": "prefill", "rid": 0, "trace": "t0",
         "replica": 0, "prefill_s": 0.15, "span_id": "p0-2",
         "parent_span": "p0-1", "seq": 2},
        {"ts": 100.3, "kind": "handoff_out", "rid": 0, "trace": "t0",
         "replica": 0, "span_id": "p0-3", "parent_span": "p0-1",
         "seq": 3},
        {"ts": 100.31, "kind": "transport_encode", "rid": 0,
         "trace": "t0", "dst": 1, "nbytes": 4096, "dur_s": 0.01,
         "span_id": "p0-4", "parent_span": "p0-3", "seq": 4},
        {"ts": 100.9, "kind": "finish", "rid": 0, "trace": "t0",
         "replica": 0, "reason": "length", "span_id": "p0-5",
         "parent_span": "p0-1", "seq": 5},
    ]
    r1 = [
        {"kind": "dump_header", "rule": "worker_exit", "dump_id": 1,
         "source": "rank1e0", "ts": 100.0,
         "provenance": {"git_sha": "abc1234", "hostname": "hostA"},
         "restart_epoch": 0},
        {"ts": 100.4, "kind": "handoff_in", "rid": 0, "trace": "t0",
         "replica": 0, "span_id": "d1-1", "parent_span": "p0-4",
         "seq": 1},
        {"ts": 100.5, "kind": "tick", "steps": 1, "active": 1,
         "tick_s": 0.05, "replica": 0, "seq": 2},
    ]
    paths = []
    for name, evs in (("r0.jsonl", r0), ("r1.jsonl", r1)):
        p = tmp_path / name
        p.write_text("\n".join(json.dumps(e) for e in evs) + "\n")
        paths.append(str(p))
    return paths


def test_perfetto_export_processes_slices_and_flows(tmp_path):
    paths = _golden_dumps(tmp_path)
    doc = export(paths)
    evs = doc["traceEvents"]
    # ranks as processes, named with provenance
    pnames = {e["pid"]: e["args"]["name"] for e in evs
              if e["ph"] == "M" and e["name"] == "process_name"}
    assert pnames == {0: "rank 0 hostA abc1234",
                      1: "rank 1 hostA abc1234"}
    # duration events became complete slices with recorder-end
    # timestamps shifted back by their duration
    slices = {e["name"]: e for e in evs if e["ph"] == "X"}
    assert set(slices) == {"prefill", "transport_encode", "tick"}
    assert slices["prefill"]["dur"] == 150000.0
    assert slices["prefill"]["ts"] == pytest.approx(
        (100.2 - 0.15 - 100.0) * 1e6)
    # one flow arrow out of rank 0 into rank 1
    s = [e for e in evs if e["ph"] == "s"]
    f = [e for e in evs if e["ph"] == "f"]
    assert len(s) == len(f) == 1
    assert s[0]["id"] == f[0]["id"]
    assert s[0]["pid"] == 0 and f[0]["pid"] == 1
    # span identity rides in args
    admits = [e for e in evs if e["ph"] == "i" and e["name"] == "admit"]
    assert admits[0]["args"]["span_id"] == "p0-1"
    # zero orphans across the merged pair
    merged = []
    for p in paths:
        with open(p) as fh:
            merged += [json.loads(l) for l in fh if l.strip()]
    assert orphan_spans(
        [e for e in merged if e.get("kind") != "dump_header"]) == []


def test_perfetto_export_is_deterministic(tmp_path):
    from deepspeed_tpu.telemetry import perfetto
    paths = _golden_dumps(tmp_path)
    assert perfetto.dumps(export(paths)) == perfetto.dumps(export(paths))


def test_view_cli_perfetto_format(tmp_path):
    from deepspeed_tpu.telemetry import view
    paths = _golden_dumps(tmp_path)
    out = tmp_path / "trace.json"
    rc = view.main(paths + ["--format", "perfetto", "--out", str(out)])
    assert rc == 0
    doc = json.loads(out.read_text())
    assert doc["displayTimeUnit"] == "ms"
    assert any(e["ph"] == "X" for e in doc["traceEvents"])


# ------------------------------- transport SLO feed + fabric health


def test_loopback_slo_feed_exports_gauges(gpt2_adapter):
    """The transport-level wiring: a PrefillNode with an attached SLO
    plane feeds its own TTFT segments (role prefill) and the decode
    ranks' exchanged MV_TICK_S (role decode) once per exchange, and
    the windowed ``slo/*`` gauges land on the rank-0 registry."""
    from deepspeed_tpu.telemetry.slo import SloPlane
    pnode, _dnodes = _mk_loopback(gpt2_adapter, world=2)
    pnode.slo = SloPlane(min_samples=1)
    done = pnode.serve(_reqs(6, max_new=4, seed=3), max_ticks=5000)
    assert len(done) == 6
    reg = pnode.metrics
    assert reg.peek_gauge("slo/window_s") == pnode.slo.window_s
    assert reg.peek_gauge("slo/prefill/ttft_s/samples") >= 6
    assert reg.peek_gauge("slo/prefill/queue_wait_s/samples") >= 6
    assert reg.peek_gauge("slo/prefill/transport_s/samples") >= 6
    assert reg.peek_gauge("slo/decode/tick_s/samples") >= 1
    assert reg.peek_gauge("slo/prefill/ttft_s/burn_rate") is not None
    # and the recommendation derives purely from those gauges
    from deepspeed_tpu.telemetry.slo import roles_signal
    assert set(roles_signal(reg, min_samples=1)) == {"decode",
                                                     "prefill"}


def test_peer_fabric_liveness_doc():
    from deepspeed_tpu.utils.distributed import PeerFabric
    fab = object.__new__(PeerFabric)    # no collective construction
    fab.rank, fab.world = 0, 3
    fab._out, fab._in = {1: object()}, {}
    fab.last_send_ts, fab.last_recv_ts = {1: 0.0}, {}
    doc = fab.liveness()
    assert doc["rank"] == 0 and doc["world"] == 3
    assert set(doc["peers"]) == {"1", "2"}
    p1 = doc["peers"]["1"]
    assert p1["out_connected"] and not p1["in_connected"]
    assert p1["last_send_age_s"] > 0
    assert p1["last_recv_age_s"] is None
    assert doc["peers"]["2"] == {"out_connected": False,
                                 "in_connected": False,
                                 "last_send_age_s": None,
                                 "last_recv_age_s": None}


def test_healthz_reports_fabric_liveness():
    """Satellite 2 end-to-end: /healthz carries the targeted-fabric
    doc through the endpoint's ``fabric_health`` hook (pre-build here
    — the single-process shape; the per-peer form is pinned above)."""
    import urllib.request
    from deepspeed_tpu.serving.transport import ProcessEndpoint
    from deepspeed_tpu.telemetry.serve import MetricsServer
    ep = ProcessEndpoint(addressing="targeted")
    srv = MetricsServer(0, registry=None,
                        extra_health_fn=ep.fabric_health).start()
    try:
        with urllib.request.urlopen(
                f"http://127.0.0.1:{srv.port}/healthz") as r:
            doc = json.loads(r.read())
    finally:
        srv.stop()
    assert doc["ok"] is True
    assert doc["fabric"] == {"built": False, "addressing": "targeted"}


# ------------------------------------------- dump-header provenance


def test_watchdog_dump_header_carries_provenance(tmp_path, monkeypatch):
    from deepspeed_tpu.telemetry.anomaly import Watchdog
    from deepspeed_tpu.telemetry.recorder import FlightRecorder
    from deepspeed_tpu.telemetry.registry import MetricsRegistry
    monkeypatch.setenv("DSTPU_RESTART_EPOCH", "3")
    rec = FlightRecorder()
    rec.record("admit", rid=0)
    wd = Watchdog(str(tmp_path), recorder=rec,
                  registry=MetricsRegistry(), source="rank0e3")
    path = wd.force_dump("unit")
    with open(path) as fh:
        header = json.loads(fh.readline())
    assert header["kind"] == "dump_header"
    assert header["restart_epoch"] == 3
    prov = header["provenance"]
    assert set(prov) >= {"git_sha", "hostname", "python_version"}
    assert prov["hostname"]
