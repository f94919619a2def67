"""``setup_reduce``: set-up's timeline from the program's events (PR 35).

The reducer is held on synthetic event lists (what the flight recorder's
ring holds: ``span`` and ``compile`` events with their start on
``time.monotonic()``), the six readers on a record that carries an
attribution, and the whole path — the program's spans and compile events,
the reducer, the readers, the detail file — on one CPU rehearsal of
``gpt2l-train-1chip`` in a process of its own, as the driver runs a cell.
"""

import importlib
import json
import os
import subprocess
import sys

import pytest

from benchmark import harness, manifest, setup_reduce

BENCH = manifest.load()
METRICS = {"setup_engine_init_s": "engine_init_s",
           "setup_first_step_s": "first_step_s",
           "setup_outside_program_s": "outside_program_s",
           "setup_compile_s": "compile_s",
           "setup_programs_compiled": "programs_compiled",
           "setup_cache_misses": "cache_misses"}
T_START, T_OPEN = 1000.0, 1100.0            # a set-up of 100 s


def span(tag, t0, dur, **more):
    return {"kind": "span", "tag": tag, "t0_mono": T_START + t0,
            "dur_s": dur, **more}


def compiled(name, phase, t0, dur, cache=None):
    return {"kind": "compile", "fun_name": name, "phase": phase,
            "t0_mono": T_START + t0, "dur_s": dur, "cache": cache}


def numbered(events):
    return [dict(e, seq=i + 1) for i, e in enumerate(events)]


def a_run():
    """A benchmark's set-up as the ring would hold it: the weights, the
    engine, the reference's programs, the first step, three warm-up steps, a
    traced run's ``step_program`` — then the window's own steps."""
    return numbered([
        compiled("_init", "trace", 10.5, 1.0),
        compiled("jit(_init)", "lower", 11.5, 0.5),
        compiled("jit(_init)", "backend", 12.0, 2.0, "hit"),
        span("startup/sharded_init", 10.0, 5.0),
        compiled("jit(_identity)", "backend", 16.5, 0.25, "hit"),
        span("startup/engine_init", 16.0, 2.0),
        # the benchmark's reference: no span of the program's covers it;
        # a jitted function traced INSIDE its trace ends, and is recorded,
        # first: once in a union, twice in a plain sum
        compiled("inner", "trace", 20.5, 1.0),
        compiled("reference", "trace", 20.0, 3.0),
        # a fetch INSIDE the trace (a constant made eagerly), likewise
        compiled("jit(fill)", "backend", 21.0, 1.0, "hit"),
        compiled("jit(reference)", "lower", 23.0, 2.0),
        compiled("jit(reference)", "backend", 25.0, 15.0, "miss"),
        span("startup/build_fns", 50.0, 1.0),
        compiled("train_batch_fn", "trace", 51.5, 4.0),
        compiled("jit(train_batch_fn)", "lower", 55.5, 2.5),
        compiled("jit(train_batch_fn)", "backend", 58.0, 6.0, "miss"),
        span("train/step_dispatch", 51.25, 13.75, step=0),
        span("train/step_dispatch", 70.0, 0.5, step=1),
        span("train/step_dispatch", 72.0, 0.5, step=2),
        compiled("jit(train_batch_fn)", "lower", 80.0, 2.0),
        compiled("jit(train_batch_fn)", "backend", 82.0, 8.0, "hit"),
        # the window's: they begin at or after its opening
        span("train/step_dispatch", 100.0, 0.5, step=3),
        compiled("jit(late)", "backend", 101.0, 1.0, "miss"),
        span("train/step_dispatch", 102.0, 0.5, step=4),
    ])


def test_the_rows_add_up_to_setup_s_in_order_and_without_overlap():
    found = setup_reduce.attribution(a_run(), T_START, T_OPEN)
    rows = found["rows"]
    assert [r["row"] for r in rows] == list(setup_reduce.ROWS)
    assert abs(sum(r["seconds"] for r in rows) - 100.0) < 1e-3
    assert found["setup_s"] == 100.0
    assert rows[0]["start_s"] == 0.0 and rows[-1]["end_s"] == 100.0
    for before, after in zip(rows, rows[1:]):
        assert before["end_s"] == after["start_s"]
        assert before["start_s"] <= before["end_s"]
    assert [(r["start_s"], r["end_s"]) for r in rows] == [
        (0.0, 10.0), (10.0, 16.0), (16.0, 18.0), (18.0, 50.0),
        (50.0, 65.0), (65.0, 100.0)]
    # the seconds of the program's own spans inside each row
    assert [r["span_s"] for r in rows] == [0.0, 5.0, 2.0, 0.0, 14.75, 0.0]


def test_an_event_that_begins_at_the_windows_opening_or_later_is_left_out():
    found = setup_reduce.attribution(a_run(), T_START, T_OPEN)
    assert found["programs_compiled"] == 6          # not ``jit(late)``
    assert found["cache_misses"] == 2
    assert "jit(late)" not in [c["fun_name"]
                               for c in found["longest_compiles"]]
    assert found["first_step_s"] == 13.75           # step 0's, no other's
    # ... and with the opening drawn before the traced run's step_program
    early = setup_reduce.attribution(a_run(), T_START, T_START + 80.0)
    assert early["programs_compiled"] == 5
    assert abs(sum(r["seconds"] for r in early["rows"]) - 80.0) < 1e-3


def test_a_compile_nested_in_a_span_counts_once_in_its_row_and_once_in_all():
    found = setup_reduce.attribution(a_run(), T_START, T_OPEN)
    by_row = {r["row"]: r for r in found["rows"]}
    # sharded_init's three phases lie inside its span: 3.5 s, one program
    assert by_row["sharded_init"]["compile_s"] == 3.5
    assert by_row["sharded_init"]["programs"] == 1
    assert by_row["engine_init"]["compile_s"] == 0.25
    # the reference: trace 20-23 holds the fetch 21-22; lower 23-25;
    # backend 25-40: 20 s of the clock, where a plain sum gives 22
    assert by_row["reference"]["compile_s"] == 20.0
    assert (by_row["reference"]["programs"],
            by_row["reference"]["cache_misses"]) == (2, 1)
    assert by_row["first_step"]["compile_s"] == 12.5
    assert (by_row["first_step"]["programs"],
            by_row["first_step"]["cache_misses"]) == (1, 1)
    assert by_row["warmup_rest"]["compile_s"] == 10.0
    assert by_row["before_first_span"]["compile_s"] == 0.0
    # a cut by kind across the rows: every row's seconds once
    assert found["compile_s"] == 3.5 + 0.25 + 20.0 + 12.5 + 10.0
    # ... and by phase, each a union: the trace inside the trace counts
    # once, the fetch inside it under its own phase
    assert found["compile_phase_s"] == {
        "trace": 1.0 + 3.0 + 4.0, "lower": 0.5 + 2.0 + 2.5 + 2.0,
        "backend": 2.0 + 0.25 + 1.0 + 15.0 + 6.0 + 8.0}
    assert found["engine_init_s"] == 5.0 + 2.0 + 1.0
    assert found["outside_program_s"] == 100.0 - (8.0 + 13.75)
    assert found["longest_compiles"][0] == {
        "fun_name": "jit(reference)", "seconds": 15.0, "cache": "miss",
        "start_s": 25.0}
    assert len(found["longest_compiles"]) == 6


def test_sharded_init_inside_the_engines_own_span_is_counted_once():
    """An engine that makes its own weights runs ``sharded_init`` inside
    ``state_init``: the union takes the seconds once."""
    events = numbered([
        span("startup/engine_init", 5.0, 1.0),
        span("startup/sharded_init", 8.0, 3.0),
        span("startup/state_init", 7.0, 5.0),
        span("startup/build_fns", 12.0, 1.0),
        span("train/step_dispatch", 13.0, 4.0, step=0)])
    found = setup_reduce.attribution(events, T_START, T_START + 20.0)
    assert found["engine_init_s"] == 1.0 + 5.0 + 1.0
    assert found["outside_program_s"] == 20.0 - 11.0
    assert [(r["row"], r["seconds"]) for r in found["rows"]] == [
        ("before_first_span", 5.0), ("sharded_init", 0.0),
        ("engine_init", 1.0), ("reference", 1.0), ("first_step", 10.0),
        ("warmup_rest", 3.0)]


def test_what_cannot_be_told_is_none_and_never_zero_or_a_short_sum():
    run = a_run()
    # an engine built another way: no ``startup/engine_init``
    bare = numbered([e for e in run
                     if e.get("tag") != "startup/engine_init"])
    assert setup_reduce.attribution(bare, T_START, T_OPEN) is None
    # the parent of PR 35: spans without ``t0_mono``, no compile event
    old = numbered([{k: v for k, v in e.items() if k != "t0_mono"}
                    for e in run if e["kind"] == "span"])
    assert setup_reduce.attribution(old, T_START, T_OPEN) is None
    assert setup_reduce.attribution([], T_START, T_OPEN) is None
    # the ring has pushed its oldest events out
    assert setup_reduce.attribution(run[1:], T_START, T_OPEN) is None
    # no step before the window: that metric alone is None
    unstepped = numbered([e for e in run
                          if e.get("tag") != "train/step_dispatch"])
    found = setup_reduce.attribution(unstepped, T_START, T_OPEN)
    assert found["first_step_s"] is None
    assert found["engine_init_s"] == 8.0
    assert [r["row"] for r in found["rows"]] == list(setup_reduce.ROWS[:4])
    assert abs(sum(r["seconds"] for r in found["rows"]) - 100.0) < 1e-3


def _why_not(events):
    said = []
    assert setup_reduce.attribution(events, T_START, T_OPEN,
                                    say=said.append) is None
    return said


@pytest.mark.parametrize("events, why", [
    ([], "holds no event"),
    (a_run()[2:], "pushed out its first 2 events"),
    (numbered([e for e in a_run() if e.get("tag") != "startup/engine_init"]),
     "no startup/engine_init span")],
    ids=["empty", "ring_overflowed", "engine_built_another_way"])
def test_a_missing_attribution_says_which_case_it_was(events, why):
    """A traced line that lacks the six values explains itself."""
    said = _why_not(events)
    assert len(said) == 1 and why in said[0]


def test_a_run_without_attribution_logs_the_reason_once(capsys):
    record = harness.Record(setup_s=25.0)           # no window's samples
    assert setup_reduce.of(record) is None
    assert setup_reduce.metric(record, "compile_s") is None
    lines = [line for line in capsys.readouterr().err.splitlines()
             if "setup_attribution" in line]
    assert len(lines) == 1 and "no training window's samples" in lines[0]


def test_union_counts_an_overlap_once():
    assert setup_reduce.union_s([]) == 0.0
    assert setup_reduce.union_s([(0, 2), (1, 3), (5, 6), (5.5, 5.75)]) == 4.0


@pytest.mark.parametrize("metric", sorted(METRICS))
def test_each_reader_gives_its_number_of_the_attribution_or_none(metric):
    reader = importlib.import_module(f"benchmark.layer_metrics.{metric}")
    assert reader.MOVES == "setup_s"
    found = setup_reduce.attribution(a_run(), T_START, T_OPEN)
    record = harness.Record(setup_s=100.0)
    record.extra[setup_reduce.SLOT] = found
    assert reader.read(record) == found[METRICS[metric]]
    assert reader.read(record) is not None
    record.extra[setup_reduce.SLOT] = None          # nothing to read
    assert reader.read(record) is None


def test_the_window_opens_where_the_runs_own_samples_say():
    record = harness.Record(setup_s=25.0)
    record.samples = {"train_tokens_per_s": [530.5, 531.0],
                      "step_s": [0.5, 0.5]}
    assert setup_reduce.window_opening(record) == (505.0, 530.0)


def test_the_six_are_the_ones_setup_reduce_names():
    assert tuple(METRICS) == setup_reduce.METRICS
    assert set(METRICS) <= {m["name"] for m in BENCH["per_layer"]
                            if m["moves"] == "setup_s"}


@pytest.mark.parametrize("metric", sorted(METRICS))
def test_each_of_the_six_is_in_the_manifest_as_its_reader_states_it(metric):
    """Admitted in PR 37, by name: the entry is the reader's own words, and
    every cell that reports ``train_tokens_per_s`` (the window's opening is
    read from a training run's samples) is listed."""
    entry = next(m for m in BENCH["per_layer"] if m["name"] == metric)
    reader = manifest.metric_module(metric)
    assert (entry["moves"], entry["better"]) == ("setup_s", "lower")
    assert (entry["unit"], entry["source"], entry["layer"]) == \
        (reader.UNIT, reader.SOURCE, reader.LAYER)
    training = next(m for m in BENCH["end_to_end"]
                    if m["name"] == "train_tokens_per_s")["workloads"]
    assert training and set(training) <= set(entry["workloads"])


def test_a_rehearsal_lists_the_six_and_writes_the_timeline(tmp_path):
    """One process, one cell, as the driver runs it (``python -m
    benchmark.run ... --trace 1``; the ring and the registry are the
    process's own): the traced CPU rehearsal names the six metrics, and the
    detail file holds the timeline, whose rows add up."""
    seed = 3535
    env = dict(os.environ, JAX_PLATFORMS="cpu", PYTHONPATH=manifest.ROOT)
    env.pop("JAX_COMPILATION_CACHE_DIR", None)
    done = subprocess.run(
        [sys.executable, "-m", "benchmark.run", "--workload",
         "gpt2l-train-1chip", "--seed", str(seed), "--seconds", "1",
         "--trace", "1", "--rehearse-cpu"],
        cwd=manifest.ROOT, env=env, capture_output=True, text=True,
        timeout=600)
    assert done.returncode == 0, done.stderr[-2000:]
    line = json.loads(done.stdout.strip().splitlines()[-1])
    assert set(METRICS) <= set(line["rehearsal_metric_names"])
    assert line["metrics"] == {}
    with open(os.path.join(
            harness.OUT_DIR, f"gpt2l-train-1chip-seed{seed}-trace1.json")) as f:
        detail = json.load(f)
    found = detail["extra"][setup_reduce.SLOT]
    rows = found["rows"]
    assert [r["row"] for r in rows] == list(setup_reduce.ROWS)
    assert rows[0]["start_s"] == 0.0
    assert abs(rows[-1]["end_s"] - detail["setup_s"]) < 1e-3
    assert abs(sum(r["seconds"] for r in rows) - detail["setup_s"]) < 1e-3
    assert all(a["end_s"] == b["start_s"] for a, b in zip(rows, rows[1:]))
    # the engine was built and stepped under the program's spans, and the
    # step's program is among the longest compiles, by name
    assert found["engine_init_s"] > 0 and found["first_step_s"] > 0
    assert found["programs_compiled"] > 10 and found["cache_misses"] == 0
    assert any("train_batch_fn" in c["fun_name"]
               for c in found["longest_compiles"])
    assert 0 < found["compile_s"] < detail["setup_s"]
