"""Model e2e regression tier — the reference's tests/model/ role
(Megatron_GPT2/run_func_test.py loss-curve assertions, BingBertSquad
test_e2e_squad.py): each examples/ script runs as a real subprocess with a
tiny config on CPU devices, and the printed loss curve must fall.

Marked with the same pattern as the rest of the suite (CPU devices forced in
the child env, not inherited state), ~1-2 min each.
"""

import os
import re
import subprocess
import sys

import pytest

REPO = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))


def _run_example(script, *args, devices=8, timeout=240):
    env = dict(os.environ)
    flags = re.sub(r"--xla_force_host_platform_device_count=\d+", "",
                   env.get("XLA_FLAGS", ""))
    env["XLA_FLAGS"] = (
        flags + f" --xla_force_host_platform_device_count={devices}").strip()
    env["JAX_PLATFORMS"] = "cpu"
    proc = subprocess.run(
        [sys.executable, os.path.join(REPO, "examples", script), *args],
        env=env, cwd=REPO, capture_output=True, text=True, timeout=timeout)
    assert proc.returncode == 0, (
        f"{script} failed rc={proc.returncode}\n--- stdout\n{proc.stdout}"
        f"\n--- stderr\n{proc.stderr[-3000:]}")
    return proc.stdout


def _losses(stdout, script):
    first = re.search(r"first loss: ([\d.]+)", stdout)
    final = re.search(r"final loss[^:]*: ([\d.]+)", stdout)
    assert first and final, f"{script} printed no loss curve:\n{stdout}"
    return float(first.group(1)), float(final.group(1))


def test_example_cifar10():
    out = _run_example("cifar10_train.py", "--steps", "20", devices=1)
    first, final = _losses(out, "cifar10")
    assert final < first, (first, final)


def test_example_gpt2_pretrain_zero2():
    out = _run_example("gpt2_pretrain.py", "--model", "tiny", "--steps", "8",
                       "--batch", "8", "--seq", "64", "--repeat-batch",
                       devices=2)
    first, final = _losses(out, "gpt2_pretrain")
    assert final < first, (first, final)


@pytest.mark.slow
def test_example_gpt2_pipeline():
    out = _run_example("gpt2_pipeline.py", "--steps", "8", "--pipe", "2",
                       "--data", "2", devices=4)
    first, final = _losses(out, "gpt2_pipeline")
    assert final < first, (first, final)


def test_example_bert_squad():
    out = _run_example("bert_squad_finetune.py", "--steps", "8",
                       "--seq", "64", "--repeat-batch", devices=1)
    first, final = _losses(out, "bert_squad")
    assert final < first, (first, final)


@pytest.mark.slow
def test_example_observability_demo(tmp_path):
    """The ISSUE-4 acceptance artifact end to end in a subprocess: a
    20-step run emits the JSONL snapshot stream, the scalar events, the
    Prometheus dump, and a non-empty XLA trace window."""
    out_dir = str(tmp_path / "tel")
    out = _run_example("observability_demo.py", "--out", out_dir,
                       "--steps", "12", devices=1)
    assert os.path.getsize(os.path.join(out_dir,
                                        "telemetry_rank0.jsonl")) > 0
    assert os.path.getsize(os.path.join(out_dir, "metrics.prom")) > 0
    assert any(files for _, _, files
               in os.walk(os.path.join(out_dir, "trace")))
    assert '"train/steps": 12.0' in out
    # flops priced; no MFU on a device kind without a recorded peak
    assert '"train/flops_per_step"' in out and '"train/mfu"' not in out
    assert '"train/step_time_s"' in out
    assert '"span/train/forward"' in out   # per-phase span times


def test_example_llama_pretrain():
    out = _run_example("llama_pretrain.py", "--steps", "8", "--batch", "8",
                       "--seq", "64", "--hidden", "128", "--layers", "2",
                       "--heads", "4", "--kv-heads", "2", "--repeat-batch",
                       devices=2)
    first, final = _losses(out, "llama_pretrain")
    assert final < first, (first, final)
