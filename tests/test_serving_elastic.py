"""Elastic preemption-tolerant serving (ISSUE 11).

Covers the drain-or-snapshot subsystem end to end:

- snapshot/restore token parity, same and DIFFERENT slot counts
  (direct slot rebuilds + replay requeues), prefix hit-rate preserved
  across restore;
- SIGTERM mid-serve through the real signal path: grace-budget drain
  vs immediate snapshot, and the mid-spec-tick rollback pin — no
  drafted-but-unverified token ever appears in a restored stream, for
  BOTH drafters;
- the two-rename commit crash window (previous snapshot survives);
- abort()/drain() page-leak fence;
- ReplicaPool: mid-prefill and mid-spec-verify replica crashes
  recovered from committed snapshots (token-lossless), bounded retry
  dropping a poisoned request, watchdog-trip scale-up + idle
  scale-down, one latched dump per injected fault;
- config validation for serving.elastic / serving.autoscale;
- the dump viewer's drain -> snapshot -> restore -> requeue timeline.
"""

import json
import os

import numpy as np
import jax
import jax.numpy as jnp
import pytest

import deepspeed_tpu.serving as serving
from deepspeed_tpu.config.config import (DeepSpeedConfigError,
                                         ServingConfig)
from deepspeed_tpu.runtime.elastic import faults
from deepspeed_tpu.serving import elastic
from deepspeed_tpu.serving.drafter import ModelDrafter, NGramDrafter
from deepspeed_tpu.serving.elastic import ElasticServingController
from deepspeed_tpu.serving.replica_pool import ReplicaPool
from deepspeed_tpu.telemetry.anomaly import Watchdog
from deepspeed_tpu.telemetry.recorder import default_recorder


@pytest.fixture(autouse=True)
def _clean():
    faults.clear()
    default_recorder().configure(enabled=True, capacity=4096)
    default_recorder().clear()
    yield
    faults.clear()


# ------------------------------------------------------ engine fixture

def _gpt2_cfg():
    from deepspeed_tpu.models.gpt2 import GPT2Config
    return GPT2Config(vocab_size=256, n_positions=128, n_embd=64,
                      n_layer=2, n_head=4, dtype=jnp.float32,
                      param_dtype=jnp.float32, scan_layers=True)


@pytest.fixture(scope="module")
def gpt2_el():
    """(cfg, params, make): batchers over shared per-geometry adapters
    (compiled programs live on the adapter — tier-1 budget)."""
    from deepspeed_tpu.models.gpt2 import GPT2LMHeadModel
    cfg = _gpt2_cfg()
    params = jax.jit(GPT2LMHeadModel(cfg).init)(
        jax.random.PRNGKey(0), np.zeros((1, 8), np.int32))["params"]
    adapters = {}

    def make(slots=2, **kw):
        sv = {"slots": slots, "page_size": 8, "max_pages_per_slot": 8}
        sv.update(kw.pop("serving", {}))
        key = tuple(sorted(sv.items()))
        if key not in adapters:
            adapters[key] = serving.build_engine(
                "gpt2", cfg, params, config={"serving": sv}).adapter
        return serving.ContinuousBatcher(adapters[key], **kw)

    return cfg, params, make


def _reqs(n=4, max_new=12, seed=0, eos=None):
    rs = np.random.RandomState(seed)
    lens = rs.choice([5, 9, 14, 21], n)
    return [serving.Request(
        i, rs.randint(0, 256, size=(int(lens[i]),)).astype(np.int32),
        max_new_tokens=max_new, eos_token_id=eos) for i in range(n)]


def _clone(reqs):
    return [serving.Request(r.rid, r.prompt,
                            max_new_tokens=r.max_new_tokens,
                            eos_token_id=r.eos_token_id,
                            temperature=r.temperature,
                            arrival_time=r.arrival_time) for r in reqs]


def _ref_streams(make, reqs, **kw):
    eng = make(**kw)
    return {rid: r.tokens().tolist()
            for rid, r in eng.serve(_clone(reqs)).items()}


def _drive(cb, done=None, max_rounds=500):
    done = {} if done is None else done
    rounds = 0
    while cb.pending and not cb.preempted and rounds < max_rounds:
        for r in cb.step():
            done[r.rid] = r
        rounds += 1
    return done


# ------------------------------------------------- config validation


def test_serving_elastic_config_validation():
    def cfg(el):
        return ServingConfig({"serving": {"elastic": el}})

    ok = cfg({"snapshot_path": "/tmp/x", "grace_secs": 5,
              "max_retries": 2, "backoff_s": 0.1,
              "interval_ticks": 4, "signals": "SIGTERM"})
    assert ok.elastic.enabled and ok.elastic.grace_secs == 5.0
    assert ok.elastic.signals == ("SIGTERM",)   # no per-char iteration
    assert not ServingConfig({"serving": {}}).elastic.enabled
    with pytest.raises(DeepSpeedConfigError):
        cfg("nvme:/path")                        # not a dict
    with pytest.raises(DeepSpeedConfigError):
        cfg({})                                  # enabled, no path
    with pytest.raises(DeepSpeedConfigError):
        cfg({"snapshot_path": "/tmp/x", "grace_secs": 0})
    with pytest.raises(DeepSpeedConfigError):
        cfg({"snapshot_path": "/tmp/x", "grace_secs": "soon"})
    with pytest.raises(DeepSpeedConfigError):
        cfg({"snapshot_path": "/tmp/x", "max_retries": -1})
    with pytest.raises(DeepSpeedConfigError):
        cfg({"snapshot_path": "/tmp/x", "backoff_s": -0.5})
    with pytest.raises(DeepSpeedConfigError):
        cfg({"snapshot_path": "/tmp/x", "interval_ticks": -2})
    with pytest.raises(DeepSpeedConfigError):
        cfg({"snapshot_path": "/tmp/x", "keep": 0})
    with pytest.raises(DeepSpeedConfigError):
        cfg({"snapshot_path": "/tmp/x", "signals": ["alarm"]})


def test_serving_autoscale_config_validation():
    def cfg(a):
        return ServingConfig({"serving": {"autoscale": a}})

    ok = cfg({"min_replicas": 2, "max_replicas": 4})
    assert ok.autoscale.min_replicas == 2
    assert ok.autoscale.scale_signal == "watchdog"
    with pytest.raises(DeepSpeedConfigError):
        cfg(["watchdog"])                        # not a dict
    with pytest.raises(DeepSpeedConfigError):
        cfg({"min_replicas": 0})
    with pytest.raises(DeepSpeedConfigError):
        cfg({"min_replicas": 3, "max_replicas": 2})
    with pytest.raises(DeepSpeedConfigError):
        cfg({"min_replicas": "a few"})
    with pytest.raises(DeepSpeedConfigError):
        cfg({"scale_signal": "vibes"})


# ------------------------------------------------------- abort / drain


def test_abort_and_drain_release_pages(gpt2_el):
    _cfg, _params, make = gpt2_el
    cb = make(slots=2)
    reqs = _reqs(4, max_new=16, seed=3)
    for r in reqs:
        cb.submit(r)
    for _ in range(2):
        cb.step()
    active_rid = next(s.request.rid for s in cb.slots if s.active)
    queued_rid = cb.queue[0].rid
    got = cb.abort(active_rid)
    assert got is not None and got.finish_reason == "aborted"
    assert got.generated                     # committed tokens intact
    got_q = cb.abort(queued_rid)
    assert got_q is not None and got_q.finish_reason == "aborted"
    assert cb.abort("nonsense") is None
    rest = cb.drain()
    assert all(r.finish_reason == "aborted" for r in rest)
    assert cb.pending == 0
    # the leak fence: every page back in the pool
    cb.cache.sweep_prefix_cache()
    assert cb.cache.free_pages == cb.cache.num_blocks - 1
    kinds = [e["kind"] for e in default_recorder().events()]
    assert kinds.count("serving_abort") == 2 + len(rest)


# ------------------------------------------- snapshot / restore parity


def test_snapshot_restore_different_slot_counts(gpt2_el, tmp_path):
    """Snapshot a 2-slot engine mid-flight, restore onto a 1-slot AND
    a 3-slot engine: direct slot rebuilds + replay requeues, greedy
    token-for-token parity with the uninterrupted run either way."""
    _cfg, _params, make = gpt2_el
    reqs = _reqs(4, max_new=12, seed=0)
    ref = _ref_streams(make, reqs, slots=2)

    from deepspeed_tpu.runtime.elastic.snapshot import AsyncSnapshotter
    cb = make(slots=2)
    done = {}
    for r in _clone(reqs):
        cb.submit(r)
    for _ in range(5):
        for r in cb.step():
            done[r.rid] = r
    snap = AsyncSnapshotter(str(tmp_path / "snaps"), fsync=False)
    path = elastic.snapshot_serving(cb, snap, "t1")
    host, kv = elastic.load_serving_snapshot(path)
    assert host["slots"] or host["queued"]

    for slots in (1, 3):
        target = make(slots=slots)
        out = elastic.restore_serving(target, host, kv)
        if slots == 1:
            assert len(out["restored"]) == 1 and out["requeued"]
        merged = dict(done)
        _drive(target, merged)
        for rid, toks in ref.items():
            assert merged[rid].tokens().tolist() == toks, \
                (slots, rid)


def test_restore_preserves_prefix_hit_rate(gpt2_el, tmp_path):
    """The prefix index survives the snapshot/restore hop: a restored
    engine keeps serving repeat-prefix admissions from resident pages
    (the acceptance criterion's hit-rate-preserved leg)."""
    _cfg, _params, make = gpt2_el
    rs = np.random.RandomState(7)
    shared = rs.randint(0, 256, size=(19,)).astype(np.int32)
    prompts = [np.concatenate([shared,
                               rs.randint(0, 256, size=(3,))
                               .astype(np.int32)]) for _ in range(3)]
    mk = (lambda i: serving.Request(i, prompts[i], max_new_tokens=6))
    cb = make(slots=2, prefix_cache=True)
    cb.serve([mk(0), mk(1)])
    assert cb.cache.prefix_stats["hit_pages"] > 0

    from deepspeed_tpu.runtime.elastic.snapshot import AsyncSnapshotter
    snap = AsyncSnapshotter(str(tmp_path / "snaps"), fsync=False)
    path = elastic.snapshot_serving(cb, snap, "t1")
    host, kv = elastic.load_serving_snapshot(path)
    assert host["prefix"]["full"]            # resident entries captured

    fresh = make(slots=2, prefix_cache=True)
    ref = {rid: r.tokens().tolist()
           for rid, r in make(slots=2).serve([mk(2)]).items()}
    out = elastic.restore_serving(fresh, host, kv)
    assert out["dropped_prefix_pages"] == 0
    before = fresh.cache.prefix_stats["hit_pages"]
    done = fresh.serve([mk(2)])
    assert fresh.cache.prefix_stats["hit_pages"] > before  # still hits
    assert done[2].tokens().tolist() == ref[2]   # and stays lossless


def test_sampled_snapshot_restore_is_deterministic(gpt2_el, tmp_path):
    """ISSUE 14 satellite (the PR-11 caveat fix): SAMPLED
    (temperature > 0) requests restore deterministically. The per-
    request sample_key + cumulative committed-token count persisted in
    the snapshot docs make every token's sampling key
    fold_in(sample_key, global_index) — so both the direct slot
    rebuild AND the replay requeue regenerate the uninterrupted run's
    exact sampled stream (previously they drew fresh rng)."""
    _cfg, _params, make = gpt2_el
    from deepspeed_tpu.runtime.elastic.snapshot import AsyncSnapshotter
    reqs = [serving.Request(r.rid, r.prompt, max_new_tokens=14,
                            temperature=0.8) for r in _reqs(4, seed=21)]
    ref = _ref_streams(make, reqs, slots=2)
    # sanity: the streams are actually sampled, not greedy
    greedy = _ref_streams(
        make, [serving.Request(r.rid, r.prompt, max_new_tokens=14)
               for r in reqs], slots=2)
    assert any(ref[i] != greedy[i] for i in ref)

    src = make(slots=2)
    done = {}
    for r in _clone(reqs):
        src.submit(r)
    for _ in range(4):
        for r in src.step():
            done[r.rid] = r
    snap = AsyncSnapshotter(str(tmp_path / "snaps"), fsync=False)
    path = elastic.snapshot_serving(src, snap, "t1")
    host, kv = elastic.load_serving_snapshot(path)
    assert host["slots"], "something must still be in flight"
    for doc in host["slots"] + host["queued"]:
        assert doc["sample_key"] is not None      # persisted identity
        assert doc["committed_total"] == len(doc["generated"])
    # 1-slot target: direct rebuild AND replay requeue paths both run
    target = make(slots=1)
    merged = dict(done)
    elastic.restore_serving(target, host, kv)
    _drive(target, merged)
    for rid, toks in ref.items():
        assert merged[rid].tokens().tolist() == toks, rid


# --------------------------------------------------- SIGTERM mid-serve


def _elastic_cb(make, tmp_path, grace_secs, name="s", interval_ticks=0,
                wd=None, **mk_kw):
    cb = make(**mk_kw)
    ctrl = ElasticServingController(
        cb, str(tmp_path / name), grace_secs=grace_secs,
        interval_ticks=interval_ticks, fsync=False, watchdog=wd)
    cb.attach_elastic(ctrl)
    return cb, ctrl


def test_sigterm_with_grace_drains_everything(gpt2_el, tmp_path):
    _cfg, _params, make = gpt2_el
    reqs = _reqs(2, max_new=10, seed=1)   # both fit the slots: pure
    ref = _ref_streams(make, reqs, slots=2)            # drain, no
    wd = Watchdog(str(tmp_path / "dumps"), source="serving")  # leftover
    cb, ctrl = _elastic_cb(make, tmp_path, grace_secs=3600.0, wd=wd,
                           interval_ticks=1)
    try:
        with faults.kill_at_serving_tick(1):
            done = cb.serve(_clone(reqs))
        assert cb.preempted
        assert {r: done[r].tokens().tolist() for r in done} == ref
        assert ctrl.last_snapshot_dir is None      # nothing left over
        evs = [e for e in default_recorder().events()
               if e["kind"] == "serving_drain"]
        assert len(evs) == 1 and evs[0]["drained"] == 2 \
            and evs[0]["left"] == 0
        assert wd.trips.get("preempt") == 1        # exactly one dump
        # a clean drain PRUNES stale periodic snapshots: recovery must
        # find nothing, or it would replay completed requests
        assert elastic.load_latest_serving(ctrl.snapshot_dir) is None
    finally:
        ctrl.close()


@pytest.mark.parametrize("drafter_kind", ["ngram", "model"])
def test_sigterm_mid_spec_tick_rolls_back_to_committed(
        gpt2_el, tmp_path, drafter_kind):
    """SIGTERM lands between speculative rounds: the snapshot must
    hold only COMMITTED (verified) tokens — every snapshotted stream
    is a strict prefix of the uninterrupted greedy run — and the
    restored engines (a DIFFERENT slot count) finish token-for-token
    identical. One latched preempt dump per injected fault."""
    _cfg, _params, make = gpt2_el
    reqs = _reqs(2, max_new=14, seed=2)
    ref = _ref_streams(make, reqs, slots=2)

    def mk_drafter(slots):
        if drafter_kind == "ngram":
            return NGramDrafter(slots)
        # same checkpoint as the target (the alignment contract is
        # what's under test); the drafter's slot count must match the
        # engine it serves
        return ModelDrafter(make(slots=slots).adapter)

    wd = Watchdog(str(tmp_path / "dumps"), source="serving")
    cb, ctrl = _elastic_cb(make, tmp_path, grace_secs=1e-3, wd=wd,
                           drafter=mk_drafter(2), spec_tokens=3)
    try:
        with faults.kill_at_serving_tick(2):
            done = cb.serve(_clone(reqs))
        assert cb.preempted and ctrl.last_snapshot_dir is not None
        assert wd.trips.get("preempt") == 1
        host, kv = elastic.load_serving_snapshot(ctrl.last_snapshot_dir)
        assert host["slots"]                 # something was in flight
        for sd in host["slots"]:
            stream = list(sd["prompt"]) + list(sd["generated"])
            full = ref[sd["rid"]]
            # committed-only: a drafted-but-unverified token would
            # break the prefix property against the greedy reference
            assert stream == full[:len(stream)]
            assert len(stream) < len(full)
        # restore on a DIFFERENT slot count with a fresh drafter
        target = make(slots=3, drafter=mk_drafter(3), spec_tokens=3)
        merged = {rid: r for rid, r in done.items()}
        elastic.restore_serving(target, host, kv)
        _drive(target, merged)
        for rid, toks in ref.items():
            assert merged[rid].tokens().tolist() == toks, rid
    finally:
        ctrl.close()


def test_periodic_snapshots_and_crash_between_renames(gpt2_el,
                                                      tmp_path):
    """interval_ticks commits snapshots while serving; a crash between
    the commit renames of a LATER snapshot leaves the previous
    generation loadable (the two-rename window, serving flavor)."""
    _cfg, _params, make = gpt2_el
    from deepspeed_tpu.runtime.elastic.snapshot import AsyncSnapshotter
    reqs = _reqs(3, max_new=16, seed=4)
    cb, ctrl = _elastic_cb(make, tmp_path, grace_secs=3600.0,
                           name="periodic", interval_ticks=2)
    try:
        for r in _clone(reqs):
            cb.submit(r)
        done = {}
        rounds = 0
        while cb.pending and ctrl.last_snapshot_dir is None \
                and rounds < 200:
            for r in cb.step():
                done[r.rid] = r
            rounds += 1
        assert ctrl.last_snapshot_dir is not None    # periodic commit
        first = ctrl.last_snapshot_dir
        host1, _kv1 = elastic.load_serving_snapshot(first)

        # a later snapshot dies between its two renames: the commit
        # never publishes, the first generation stays the newest valid
        snap = ctrl.snapshotter
        with faults.crash_between_renames():
            with pytest.raises(faults.SimulatedCrash):
                elastic.snapshot_serving(cb, snap, "doomed")
        got = elastic.load_latest_serving(str(tmp_path / "periodic"))
        assert got is not None
        host, _kv, cand = got
        assert os.path.basename(cand) == os.path.basename(first)
        assert [d["rid"] for d in host["slots"]] == \
            [d["rid"] for d in host1["slots"]]
    finally:
        ctrl.close()


def test_snapshot_tick_end_fires_and_viewer_renders(gpt2_el, tmp_path):
    """The serving elastic lifecycle renders as a timeline: drain ->
    snapshot -> restore -> requeue (+ abort) rows from a real event
    stream, through the stdlib-only viewer."""
    from deepspeed_tpu.telemetry import view
    _cfg, _params, make = gpt2_el
    reqs = _reqs(4, max_new=12, seed=5)
    cb, ctrl = _elastic_cb(make, tmp_path, grace_secs=1e-3, name="v")
    try:
        for r in _clone(reqs):
            cb.submit(r)
        cb.step()
        cb.abort(reqs[3].rid)
        ctrl.request_preemption("test")
        _drive(cb)
        assert cb.preempted and ctrl.last_snapshot_dir
        host, kv = elastic.load_serving_snapshot(ctrl.last_snapshot_dir)
        target = make(slots=1)
        elastic.restore_serving(target, host, kv)
    finally:
        ctrl.close()
    dump = tmp_path / "events.jsonl"
    with open(dump, "w") as fh:
        for ev in default_recorder().events():
            fh.write(json.dumps(ev, default=repr) + "\n")
    lines = "\n".join(view.render(str(dump)))
    for kind in ("serving_drain", "serving_snapshot", "serving_restore",
                 "serving_requeue", "serving_abort"):
        assert kind in lines, kind
    assert "drained" in lines and "requeued" in lines


# -------------------------------------------------------- replica pool


def _pool_factory(make, tmp_path, slots=2, interval_ticks=2, wd_dir=None,
                  registry=None, drafter_fn=None, **wd_kw):
    def factory(rid):
        kw = {}
        if drafter_fn is not None:
            kw["drafter"] = drafter_fn()
            kw["spec_tokens"] = 3
        wd = None
        if wd_dir is not None:
            wd = Watchdog(os.path.join(wd_dir, f"r{rid}"),
                          source=f"serving_r{rid}", registry=registry,
                          **wd_kw)
        cb = make(slots=slots, registry=registry, watchdog=wd, **kw)
        cb.attach_elastic(ElasticServingController(
            cb, str(tmp_path / f"replica_{rid}"), grace_secs=30.0,
            interval_ticks=interval_ticks, fsync=False,
            install_signals=False))
        return cb
    return factory


def _run_pool(pool, reqs, fault_round=None, fault=None, max_rounds=800):
    for r in reqs:
        pool.submit(r)
    rounds = 0
    while pool.pending and rounds < max_rounds:
        pool.step()
        rounds += 1
        if fault_round is not None and rounds == fault_round:
            fault(pool)
    return pool.done


def test_pool_recovers_mid_prefill_crash(gpt2_el, tmp_path):
    """A replica dying inside admission (pages allocated, prefill not
    dispatched) is recovered from its last committed snapshot; every
    request completes token-identical; the pool watchdog dumps exactly
    once per fault and re-arms for the next."""
    _cfg, _params, make = gpt2_el
    reqs = _reqs(6, max_new=16, seed=6)
    ref = _ref_streams(make, reqs, slots=2)
    wd = Watchdog(str(tmp_path / "pool_dumps"), source="pool")
    pool = ReplicaPool(_pool_factory(make, tmp_path), n_replicas=2,
                       min_replicas=1, max_replicas=2,
                       scale_signal="none", watchdog=wd)
    try:
        crash = faults.crash_replica_mid_prefill()   # exactly ONE
        armed = [False]                              # admission crashes

        def fault(_p):
            armed[0] = True
            crash.__enter__()

        done = _run_pool(pool, _clone(reqs), fault_round=2, fault=fault)
        if armed[0]:
            crash.__exit__(None, None, None)
        assert pool.stats["kills"] == 1
        assert len(done) == len(reqs) and not pool.lost
        for rid, toks in ref.items():
            assert done[rid].tokens().tolist() == toks, rid
        assert wd.trips.get("preempt") == pool.stats["kills"]
    finally:
        pool.close()


def test_pool_recovers_mid_spec_verify_crash(gpt2_el, tmp_path):
    """Mid-spec-verify death: the round's drafted tokens were never
    committed, so the snapshot-restored streams stay greedy-identical
    (the speculative flavor of the zero-committed-token-loss pin)."""
    _cfg, _params, make = gpt2_el
    reqs = _reqs(4, max_new=14, seed=8)
    ref = _ref_streams(make, reqs, slots=2)
    pool = ReplicaPool(
        _pool_factory(make, tmp_path, drafter_fn=lambda: NGramDrafter(2)),
        n_replicas=2, min_replicas=1, max_replicas=2,
        scale_signal="none")
    try:
        crash = faults.crash_replica_mid_spec_verify(at_round=1)

        def fault(_p):
            crash.__enter__()

        done = _run_pool(pool, _clone(reqs), fault_round=2, fault=fault)
        crash.__exit__(None, None, None)
        assert pool.stats["kills"] >= 1
        assert len(done) == len(reqs) and not pool.lost
        for rid, toks in ref.items():
            assert done[rid].tokens().tolist() == toks, rid
    finally:
        pool.close()


def test_pool_bounded_retry_drops_poisoned_request(gpt2_el, tmp_path):
    """A request that kills every replica that admits it is dropped
    after max_retries (bounded, backed-off) — the rest of the traffic
    completes; the pool respawns to min_replicas after each kill."""
    _cfg, _params, make = gpt2_el
    reqs = _reqs(3, max_new=8, seed=9)
    innocents, poison_req = reqs[:2], reqs[2]
    pool = ReplicaPool(_pool_factory(make, tmp_path, interval_ticks=0),
                       n_replicas=1, min_replicas=1, max_replicas=1,
                       scale_signal="none", max_retries=2,
                       backoff_s=0.0)
    try:
        done = _run_pool(pool, _clone(innocents))
        assert sorted(done) == sorted(r.rid for r in innocents)
        # every admission of the poisoned request kills its replica;
        # the pool respawns to min_replicas each time and gives up
        # after max_retries re-serves
        with faults.crash_replica_mid_prefill(match_rid=poison_req.rid,
                                              times=None):
            _run_pool(pool, _clone([poison_req]))
        assert poison_req.rid in pool.lost
        assert pool.stats["kills"] == 3        # initial + 2 retries
        assert poison_req.rid not in pool.done
    finally:
        pool.close()


def test_pool_autoscale_up_on_trips_and_down_when_idle(gpt2_el,
                                                       tmp_path):
    """Scale-up rides the latched watchdog rules (pool exhaustion /
    TTFT blowup trips); scale-down drains a replica through the
    snapshot path after the idle hysteresis — both bounded and both
    recorded as replica_scale events."""
    _cfg, _params, make = gpt2_el
    # 1 slot + tiny pool per replica: a burst saturates instantly
    factory = _pool_factory(make, tmp_path, slots=1, interval_ticks=0,
                            wd_dir=str(tmp_path / "wd"),
                            ttft_factor=1.5, ttft_min_s=0.0001,
                            min_samples=2)
    pool = ReplicaPool(factory, n_replicas=1, min_replicas=1,
                       max_replicas=3, scale_signal="watchdog",
                       scale_down_idle_rounds=3)
    try:
        reqs = _reqs(8, max_new=8, seed=10)
        done = _run_pool(pool, _clone(reqs))
        assert len(done) == len(reqs)
        assert pool.stats["scale_ups"] >= 1
        assert len(pool.replicas) <= 3
        # idle rounds after the burst: down to min_replicas
        for _ in range(40):
            pool.step()
            if len(pool.replicas) == 1 and not pool._draining:
                break
        assert len(pool.replicas) == 1
        assert pool.stats["scale_downs"] >= 1
        kinds = [(e["kind"], e.get("direction"))
                 for e in default_recorder().events()
                 if e["kind"] == "replica_scale"]
        assert ("replica_scale", "up") in kinds
        assert ("replica_scale", "down") in kinds
    finally:
        pool.close()


def test_build_engine_wires_elastic_from_config(gpt2_el, tmp_path):
    cfg, params, _make = gpt2_el
    eng = serving.build_engine(
        "gpt2", cfg, params,
        config={"serving": {
            "slots": 2, "page_size": 8, "max_pages_per_slot": 8,
            "elastic": {"snapshot_path": str(tmp_path / "s"),
                        "grace_secs": 5.0, "interval_ticks": 3,
                        "fsync": False}}})
    try:
        assert eng.elastic is not None
        assert eng.elastic.grace_secs == 5.0
        assert eng.elastic.interval_ticks == 3
        assert not eng.preempted
    finally:
        eng.elastic.close()


# ----------------------------------------- request tracing (ISSUE 12)


def test_trace_id_stitches_kill_restore_across_replica_dumps(
        gpt2_el, tmp_path):
    """The ISSUE 12 tracing proof: requests born on one replica keep
    their submit-time trace_id through kill -> snapshot-restore/requeue
    -> finish on a survivor, and telemetry/view.py stitches the single
    per-trace timeline out of TWO dump files (one taken at the kill,
    one at the end — overlapping ring contents, deduplicated) with
    zero orphaned events: every submitted trace appears, every
    timeline closes with a finish."""
    from deepspeed_tpu.telemetry import view

    _cfg, _params, make = gpt2_el
    reqs = _reqs(6, max_new=12, seed=12)
    ref = _ref_streams(make, reqs, slots=2)
    # the reference engine's own lifecycle events (with their own
    # trace ids) must not leak into the dumps under test
    default_recorder().clear()
    pool = ReplicaPool(_pool_factory(make, tmp_path, interval_ticks=1),
                       n_replicas=2, min_replicas=1, max_replicas=2,
                       scale_signal="none")
    wd = Watchdog(str(tmp_path / "trace_dumps"), source="pool")
    try:
        work = _clone(reqs)
        for r in work:
            pool.submit(r)
        # every request got a trace id AT SUBMIT, frozen in the ledger
        traces = {r.rid: r.trace_id for r in work}
        assert all(traces.values())
        assert len(set(traces.values())) == len(work)
        for rid, doc in pool._ledger.items():
            assert doc["trace_id"] == traces[rid]

        for _ in range(3):
            pool.step()
        victim = next(iter(pool.replicas))
        victims = {rid for rid, rep in pool._assign.items()
                   if rep == victim and rid not in pool.done}
        assert victims, "victim replica should hold requests"
        pool.kill_replica(victim, reason="trace_test")
        dump_a = wd.force_dump("mid_run")      # the at-the-kill dump

        rounds = 0
        while pool.pending and rounds < 800:
            pool.step()
            rounds += 1
        dump_b = wd.force_dump("end_of_run")   # the end-of-run dump
        done = pool.done
        assert len(done) == len(reqs) and not pool.lost

        # identity survived the handoff; streams are token-lossless
        for rid, r in done.items():
            assert r.trace_id == traces[rid], rid
            assert r.tokens().tolist() == ref[rid], rid

        # the viewer stitches the two dumps into per-trace timelines
        headers, events, _ = view.load_dumps([dump_a, dump_b])
        assert len(headers) == 2
        seqs = [e["seq"] for e in events if "seq" in e]
        assert len(seqs) == len(set(seqs)), "overlap not deduplicated"
        timelines = view.trace_timelines(events)
        # zero orphaned events: every stitched trace is one we
        # submitted, every submitted trace shows up and closes
        assert set(timelines) == set(traces.values())
        for rid, tid in traces.items():
            evs = timelines[tid]
            assert view._trace_outcome(evs).startswith("finished"), rid
            assert all(ev.get("trace") == tid or
                       tid in (ev.get("traces") or ()) for ev in evs)
        # at least one victim crossed replicas (direct restore lands
        # its finish on the survivor; requeues re-admit there)
        crossed = [rid for rid in victims
                   if len({ev["replica"] for ev in timelines[traces[rid]]
                           if ev.get("replica") is not None}) > 1]
        assert crossed, "no victim trace shows two replicas"
        text = "\n".join(view.render([dump_a, dump_b]))
        assert "request traces" in text
        assert f"trace {traces[crossed[0]]}" in text
    finally:
        pool.close()


def test_restored_and_replayed_requests_keep_their_trace_id(
        gpt2_el, tmp_path):
    """Unit-level pin of the persistence contract: capture -> restore
    rebuilds direct slots with the original trace_id, and the replay
    path (resume_request) carries it through the requeue prompt."""
    _cfg, _params, make = gpt2_el
    from deepspeed_tpu.runtime.elastic.snapshot import AsyncSnapshotter
    src = make(slots=2)
    # budget large enough that nothing finishes before the snapshot
    # (a finished request rightly never lands in one)
    reqs = _reqs(3, max_new=40, seed=13)
    for r in reqs:
        src.submit(r)
    src.step()
    snap = AsyncSnapshotter(str(tmp_path / "snap"), fsync=False)
    path = elastic.snapshot_serving(src, snap, "t0")
    host, kv = elastic.load_serving_snapshot(path)
    for doc in host["slots"] + host["queued"]:
        assert doc["trace_id"] is not None
    dst = make(slots=1)                 # forces the requeue path too
    res = elastic.restore_serving(dst, host, kv)
    by_rid = {r.rid: r for r in res["restored"] + res["requeued"]}
    for r in reqs:
        assert by_rid[r.rid].trace_id == r.trace_id, r.rid
    # a fresh doc with no trace stays None-safe
    doc = dict(elastic._req_doc(reqs[0]), trace_id=None)
    assert elastic.resume_request(doc).trace_id is None


def test_pool_metrics_snapshot_aggregates_replicas(gpt2_el, tmp_path):
    """ReplicaPool.metrics_snapshot(): pool TTFT percentiles over the
    replicas' merged raw reservoirs, per-replica utilization rows, and
    the lost/retried/recovered counters."""
    _cfg, _params, make = gpt2_el
    pool = ReplicaPool(_pool_factory(make, tmp_path, interval_ticks=0),
                       n_replicas=2, min_replicas=1, max_replicas=2,
                       scale_signal="none")
    try:
        reqs = _reqs(6, max_new=6, seed=14)
        done = _run_pool(pool, _clone(reqs))
        assert len(done) == len(reqs)
        snap = pool.metrics_snapshot()
        assert snap["replicas"] == 2
        assert set(snap["per_replica"]) == set(pool.replicas)
        for row in snap["per_replica"].values():
            assert 0.0 <= row["slot_utilization"] <= 1.0
        # merged reservoirs: every admission's TTFT observation counted
        assert snap["pool_ttft_s"]["count"] == len(reqs)
        assert snap["pool_ttft_s"]["p99"] >= snap["pool_ttft_s"]["p50"]
        assert snap["done"] == len(reqs)
        assert snap["lost"] == 0 and snap["retried"] == 0
        assert snap["slot_utilization"] == 0.0   # drained pool
    finally:
        pool.close()
