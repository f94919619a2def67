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
                if "idle" in n or "roofline" in n or "mfu" in n or "hbm" in n]


def test_the_step_programs_memory_is_the_compilers_own_and_the_engines():
    """``train_program_hbm_gb`` reads the ``memory_analysis()`` of the
    executable that ran: the fields the kind keeps are the ones the engine's
    ``train_step_memory_stats`` reports (the benchmark keeps a count of its
    own; this holds the two together at the rehearsal's size), and the reader
    gives the compiler's peak on a chip run and nothing on a rehearsal or an
    untraced run."""
    import jax
    from benchmark import harness, traffic
    from benchmark.kinds import train_steps
    from benchmark.layer_metrics import train_program_hbm_gb as reader
    bench = manifest.load()
    cell = manifest.cell_of(bench, "gpt2l-train-1chip")
    config, p = manifest.config_of(bench, cell), manifest.traffic_of(cell)
    family = manifest.family_module(config)
    shapes = family.traffic_shapes(config, True)
    batch = traffic.train_batches(p, 0, shapes["vocab_size"],
                                  shapes["seq_scale"])[0]
    engine, _ = family.build_train(config, p["global_batch"], 0,
                                   jax.devices()[:1], True)
    engine.train_batch({"input_ids": batch})
    text, memory = train_steps.step_program(engine, batch)
    stats = engine.train_step_memory_stats({"input_ids": batch})
    assert text.startswith("HloModule")
    assert {k: memory[k + "_size"] for k in (
        "argument", "temp", "output", "alias", "generated_code")} == {
        k: stats[k + "_bytes"] for k in (
            "argument", "temp", "output", "alias", "generated_code")}
    assert memory["argument_size"] > 0 and memory["peak_memory"] > 0
    assert stats["peak_hbm_estimate_bytes"] == sum(memory[k] for k in (
        "argument_size", "temp_size", "generated_code_size")) + max(
        memory["output_size"] - memory["alias_size"], 0)
    record = harness.Record(rehearse=False)
    assert reader.read(record) is None                  # an untraced run
    record.extra["step_program_memory"] = dict(memory,
                                               peak_memory=15_844_653_568)
    assert reader.read(record) == 15.844653568
    record.rehearse = True
    assert reader.read(record) is None                  # no device number


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

# A later PR's model of ANOTHER family, as a file: GPT-2's program under the
# key names the catalog's models use, with a kernel scope of its own.
STANDIN_FAMILY = '''"""The stand-in family: a configuration file that speaks ``hidden_size``."""
from benchmark.families import gpt2

WIDTH_KEYS = ("hidden_size", "num_attention_heads", "intermediate_size")
KERNEL_TAGS = gpt2.KERNEL_TAGS + ("standin_gmm",)
MODULE_TAGS = ("ds_loss_head", "ds_embed", "attn", "mlp")
KEYS = {"hidden_size": "n_embd", "num_hidden_layers": "n_layer",
        "num_attention_heads": "n_head", "vocab_size": "vocab_size",
        "max_position_embeddings": "n_positions",
        "rms_norm_eps": "layer_norm_epsilon"}


def _as_gpt2(config):
    def rename(d):
        return {KEYS.get(k, k): v for k, v in d.items()}
    return dict(rename(config), rehearse_cpu=rename(config["rehearse_cpu"]))


def sizes(config, rehearse):
    got = gpt2.sizes(_as_gpt2(config), rehearse)
    return {k: got[g] for k, g in KEYS.items()}


def __getattr__(name):
    """Every other member is GPT-2's, handed the file under GPT-2's names."""
    member = getattr(gpt2, name)
    return lambda config, *a, **kw: member(_as_gpt2(config), *a, **kw)
'''

STANDIN_ROOFLINE = '''"""standin_gmm_roofline: a new kernel's reader passes its OWN count."""
from benchmark import scope_reduce

NAME, UNIT, LAYER = "standin_gmm_roofline", "%", "expert kernels"
MOVES, SOURCE = "train_tokens_per_s", "device_trace"


def read(record):
    if record.peaks is None:
        return None
    needed = 2 * record.extra["tokens_per_step"] * record.config["hidden_size"]
    return scope_reduce.kernel_roofline(record, "standin_gmm", needed,
                                        record.peaks["hbm_bytes_per_s"])
'''

TO_STANDIN_KEYS = {"n_embd": "hidden_size", "n_layer": "num_hidden_layers",
                   "n_head": "num_attention_heads",
                   "n_positions": "max_position_embeddings",
                   "layer_norm_epsilon": "rms_norm_eps"}


def add_standin(root):
    """What a later ``model_config`` PR does to a checkout at ``root``: a
    family file, a configuration of that family, its cell, two readers and
    the entries that name them. New files and new entries only."""
    def renamed(d):
        return {TO_STANDIN_KEYS.get(k, k): v for k, v in d.items()
                if k != "n_ctx"}
    with open(root / "BENCHMARK.json") as f:
        bench = json.load(f)
    with open(root / "benchmark/configs/gpt2-large-774m.json") as f:
        config = renamed(json.load(f))
    config.update(name="standin-tiny", family="standin", hidden_size=64,
                  num_hidden_layers=2, num_attention_heads=2,
                  max_position_embeddings=128, vocab_size=512,
                  rehearse_cpu=renamed(config["rehearse_cpu"]))
    config["published"]["vocab_size"] = 512
    with open(root / "benchmark/workloads/gpt2l-train-1chip.json") as f:
        traffic = json.load(f)
    traffic.update(name="standin-train", config="standin-tiny",
                   traffic="standin-b8", seq_len=128, batch_pool=2)
    for path, text in (
            ("families/standin.py", STANDIN_FAMILY),
            ("configs/standin-tiny.json", json.dumps(config)),
            ("workloads/standin-train.json", json.dumps(traffic)),
            ("layer_metrics/standin_steps.py", STANDIN_METRIC),
            ("layer_metrics/standin_gmm_roofline.py", STANDIN_ROOFLINE)):
        assert not (root / "benchmark" / path).exists()
        (root / "benchmark" / path).write_text(text)
    bench["configs"].append({
        "name": "standin-tiny", "source": config["source"],
        "file": "benchmark/configs/standin-tiny.json",
        "reduced": config["reduced"], "why": "stands for a later PR's model"})
    bench["workloads"].append({
        "name": "standin-train", "config": "standin-tiny",
        "traffic": "standin-b8", "chips": 1, "why": "a later PR's cell"})
    for m in bench["end_to_end"] + bench["per_layer"]:
        # the designed path: a cell's name appended to an existing metric
        if m["name"] in ("train_tokens_per_s", "train_program_hbm_gb",
                         "train_fwd_ms", "flash_attn_share"):
            m["workloads"].append("standin-train")
    for name, unit, layer, source in (
            ("standin_steps", "count", "train step program",
             "program_counter"),
            ("standin_gmm_roofline", "%", "expert kernels", "device_trace")):
        bench["per_layer"].append({
            "name": name, "unit": unit, "better": "higher", "source": source,
            "layer": layer, "moves": "train_tokens_per_s",
            "workloads": ["standin-train"]})
    (root / "BENCHMARK.json").write_text(json.dumps(bench))


def test_a_second_config_cell_and_metric_are_added_as_files_only(tmp_path):
    """What a later PR does: new files and new entries, no edit to a file
    that was there — the harness finds everything by name, asks the family
    for every model key, and the rule tests hold the addition to its own
    files. The acceptance test of ISSUE 26."""
    root = tmp_path / "checkout"
    ignore = shutil.ignore_patterns("out", "__pycache__")
    shutil.copytree(manifest.HERE, root / "benchmark", ignore=ignore)
    # the whole directory of checks: the rule file runs each family's own
    # check over the manifest with a further cell appended
    checks = "tests/benchmark_checks"
    rules = checks + "/test_bm_manifest_rules.py"
    shutil.copytree(os.path.join(manifest.ROOT, checks), root / checks,
                    ignore=ignore)
    shutil.copy(os.path.join(manifest.ROOT, "BENCHMARK.json"), root)
    before = {p: p.read_bytes() for p in root.rglob("*")
              if p.is_file() and p.name != "BENCHMARK.json"}
    add_standin(root)

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
    # every rule test, on the checkout that now holds the other family
    held = subprocess.run(
        [sys.executable, "-m", "pytest", "-v", "-p", "no:cacheprovider",
         "-p", "no:xdist", "-p", "no:randomly", rules],
        cwd=root, env=env, capture_output=True, text=True, timeout=600)
    assert held.returncode == 0, held.stdout[-3000:] + held.stderr[-2000:]
    for case in ("keeps_its_published_widths[standin-tiny]",
                 "asks_of_a_family[standin]", "by_name[standin-train]",
                 "declares_the_same[standin_gmm_roofline]",
                 "breaks_no_rule_and_no_familys_check"):
        assert case + " PASSED" in held.stdout, case
    after = {p: p.read_bytes() for p in before}
    assert after == before, "the addition edited a file that was there"
