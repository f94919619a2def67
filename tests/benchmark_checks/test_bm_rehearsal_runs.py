"""``--rehearse-cpu`` runs of every traffic kind, and a stand-in cell.

The rehearsal drives the whole control flow — build through the program's
entry points, reference check, warm-up, window, result line — at the tiny
sizes the configuration files carry. Its last line is well formed, holds no
metric value and is never ``correct: true``; the checks against the plain
float32 reference must still pass.
"""

import json
import os
import shutil
import subprocess
import sys

import pytest

from benchmark import manifest, run

CONTRACT_KEYS = {"correct", "attempted", "failed", "metrics", "device"}


def rehearse(capsys, cell, trace, seed=0, more=()):
    rc = run.main(["--workload", cell, "--seed", str(seed), "--seconds", "1.5",
                   "--trace", str(trace), "--rehearse-cpu", *more])
    out = capsys.readouterr().out.strip().splitlines()
    return rc, json.loads(out[-1])


@pytest.mark.parametrize("cell,trace,more,names", [
    ("gpt2l-train-1chip", 0, (), {"train_tokens_per_s", "setup_s"}),
    ("gpt2xl-train-zero3-4chip", 1, (),
     {"collectives_per_step", "train_compiles_in_window"}),
    # a candidate cell: its files are there, BENCHMARK.json does not list it
    ("gpt2l-serve-decode-sat", 0, ("--candidate",),
     {"serve_tokens_per_s", "setup_s"}),
    ("gpt2l-serve-decode-sat", 1, ("--candidate",),
     {"closedloop_slot_occupancy", "closedloop_tick_ms_per_token_p50",
      "closedloop_compiles_in_window"}),
])
def test_rehearsal_prints_a_well_formed_line_and_no_device_metric(
        capsys, cell, trace, more, names):
    rc, line = rehearse(capsys, cell, trace, more=more)
    assert rc == 0
    assert CONTRACT_KEYS <= set(line)
    assert line["metrics"] == {} and line["correct"] is False
    assert line["rehearsal"] is True and line["rehearsal_checks_passed"]
    assert line["device"]["platform"] == "cpu"
    assert "busy_s" not in line["device"] and "breakdown" not in line
    assert line["attempted"] > 0 and line["failed"] == 0
    if "serve" in cell:
        # closed loop: requests ended inside the window, as they asked
        assert 0 < line["requests_finished"] <= line["attempted"]
    # every metric the flow could compute on a CPU was computed; the device
    # trace's metrics found nothing to read and were left out
    assert names <= set(line["rehearsal_metric_names"])
    assert not [n for n in line["rehearsal_metric_names"]
                if "idle" in n or "roofline" in n or "mfu" in n]


def test_a_cell_without_its_tpu_devices_exits_nonzero_and_prints_nothing(
        capsys):
    rc = run.main(["--workload", "gpt2l-train-1chip", "--seconds", "1"])
    assert rc != 0
    assert capsys.readouterr().out == ""


def test_a_candidate_is_not_run_unless_asked_for():
    with pytest.raises(KeyError):
        run.main(["--workload", "gpt2l-serve-decode-sat", "--seconds", "1",
                  "--rehearse-cpu"])


STANDIN_METRIC = '''"""standin_steps: a later PR's metric, added as a file."""
NAME, UNIT, LAYER = "standin_steps", "count", "train step program"
MOVES, SOURCE = "train_tokens_per_s", "program_counter"


def read(record):
    return record.extra["steps"]
'''


def test_a_second_config_cell_and_metric_are_added_as_files_only(tmp_path):
    """What a later PR does: new files and new entries, no edit to a file
    that was there — the harness finds everything by name."""
    root = tmp_path / "checkout"
    shutil.copytree(manifest.HERE, root / "benchmark",
                    ignore=shutil.ignore_patterns("out", "__pycache__"))
    before = {p: p.read_bytes() for p in (root / "benchmark").rglob("*")
              if p.is_file()}
    bench = manifest.load()
    with open(os.path.join(manifest.HERE, "configs",
                           "gpt2-large-774m.json")) as f:
        config = json.load(f)
    config.update(name="standin-tiny", n_embd=64, n_layer=2, n_head=2,
                  n_positions=128, vocab_size=512)
    (root / "benchmark/configs/standin-tiny.json").write_text(
        json.dumps(config))
    with open(os.path.join(manifest.HERE, "workloads",
                           "gpt2l-train-1chip.json")) as f:
        traffic = json.load(f)
    traffic.update(name="standin-train", config="standin-tiny",
                   traffic="standin-b8", seq_len=128, batch_pool=2)
    (root / "benchmark/workloads/standin-train.json").write_text(
        json.dumps(traffic))
    (root / "benchmark/layer_metrics/standin_steps.py").write_text(
        STANDIN_METRIC)
    bench["configs"].append({
        "name": "standin-tiny", "source": "a test's stand-in",
        "file": "benchmark/configs/standin-tiny.json",
        "reduced": config["reduced"], "why": "stands for a later PR's model"})
    bench["workloads"].append({
        "name": "standin-train", "config": "standin-tiny",
        "traffic": "standin-b8", "chips": 1, "why": "a later PR's cell"})
    for m in bench["end_to_end"]:
        if m["name"] == "train_tokens_per_s":
            m["workloads"].append("standin-train")
    bench["per_layer"].append({
        "name": "standin_steps", "unit": "count", "better": "higher",
        "source": "program_counter", "layer": "train step program",
        "moves": "train_tokens_per_s", "workloads": ["standin-train"]})
    (root / "BENCHMARK.json").write_text(json.dumps(bench))

    env = dict(os.environ, JAX_PLATFORMS="cpu",
               PYTHONPATH=os.pathsep.join([str(root), manifest.ROOT]))
    check = subprocess.run(
        [sys.executable, "-c",
         "from benchmark import manifest; import json; "
         "print(json.dumps(manifest.problems(manifest.load())))"],
        cwd=root, env=env, capture_output=True, text=True, timeout=120)
    assert json.loads(check.stdout.strip().splitlines()[-1]) == [], \
        check.stderr[-2000:]
    proc = subprocess.run(
        [sys.executable, "-m", "benchmark.run", "--workload", "standin-train",
         "--seed", "3", "--seconds", "1", "--trace", "1", "--rehearse-cpu"],
        cwd=root, env=env, capture_output=True, text=True, timeout=600)
    assert proc.returncode == 0, proc.stderr[-3000:]
    line = json.loads(proc.stdout.strip().splitlines()[-1])
    assert line["rehearsal_checks_passed"] and line["metrics"] == {}
    assert "standin_steps" in line["rehearsal_metric_names"]
    after = {p: p.read_bytes() for p in before}
    assert after == before, "the run edited a file that was there"
