"""chip_smoke.py's contract, as far as a machine without a chip can hold it:
it fails without a TPU whatever the environment says, fails where the repo
is not beside it, and its rehearsal mode drives the same control flow at a
tiny size on the CPU — train, checkpoint round trip, serve, and the
four-device ZeRO-3 comparison — without ever printing a pass."""

import json
import os
import pathlib
import shutil
import subprocess
import sys

REPO = pathlib.Path(__file__).resolve().parent.parent


def run_smoke(*argv, cwd=REPO, devices=1, **env):
    base = {k: v for k, v in os.environ.items()
            if k not in ("PYTHONPATH", "XLA_FLAGS",
                         "JAX_DISABLE_MOST_OPTIMIZATIONS")}
    base.update(JAX_PLATFORMS="cpu",
                XLA_FLAGS=f"--xla_force_host_platform_device_count={devices}",
                **env)
    proc = subprocess.run(
        [sys.executable, "chip_smoke.py", *argv], cwd=cwd, env=base,
        capture_output=True, text=True, timeout=600)
    lines = [json.loads(l) for l in proc.stdout.splitlines()
             if l.startswith("{")]
    return proc, lines


def test_fails_without_a_tpu_whatever_the_environment_says():
    proc, lines = run_smoke(DSTPU_BENCH_ALLOW_CPU="1")
    assert proc.returncode != 0
    assert lines[-1]["ok"] is False
    assert lines[-1]["device"]["platform"] == "cpu"
    assert '"ok": true' not in proc.stdout


def test_fails_where_the_repo_is_not_beside_it(tmp_path):
    shutil.copy(REPO / "chip_smoke.py", tmp_path)
    proc, lines = run_smoke(cwd=tmp_path)
    assert proc.returncode != 0
    assert '"ok": true' not in proc.stdout
    assert "deepspeed_tpu" in proc.stderr         # the import that failed


def test_rehearsal_runs_train_and_serve_and_is_never_a_pass(tmp_path):
    proc, lines = run_smoke("--rehearse-cpu", "--seed", "3",
                            JAX_COMPILATION_CACHE_DIR=str(tmp_path))
    assert proc.returncode == 0, proc.stderr[-3000:]
    by_phase = {l["phase"]: l for l in lines if "phase" in l}
    # the cache went where the environment said, not into the checkout
    assert by_phase["setup"]["compile_cache_dir"] == str(tmp_path)
    assert by_phase["setup"]["compile_cache_placed_by"] \
        == "JAX_COMPILATION_CACHE_DIR"
    assert os.listdir(tmp_path)
    train, serve = by_phase["train"], by_phase["serve"]
    assert train["ok"] and all(train["checks"].values())
    assert len(train["losses"]) == 5 and train["losses"][-1] < train["losses"][0]
    assert train["loss_after_resume"] == train["loss_uninterrupted"]
    assert serve["ok"] and serve["new_tokens"] == [32] * 4
    assert serve["request0_vs_generate"]["identical"]
    assert "chips4" not in by_phase
    assert lines[-1] == {"ok": False, "rehearsal": True,
                         "rehearsal_checks_passed": True,
                         "device": {"platform": "cpu", "kind": "cpu",
                                    "count": 1}}


def test_rehearsal_of_the_four_chip_path_runs_only_that(tmp_path):
    proc, lines = run_smoke("--rehearse-cpu", "--chips", "4", devices=4,
                            JAX_COMPILATION_CACHE_DIR=str(tmp_path))
    assert proc.returncode == 0, proc.stderr[-3000:]
    phases = [l["phase"] for l in lines if "phase" in l]
    assert phases == ["setup", "chips4", "native_ops"]
    c4 = lines[1]
    assert c4["ok"] and all(c4["checks"].values())
    quarter = c4["one_device_total_bytes"] / 4
    assert len(c4["dp4"]["resident_bytes"]) == 4
    assert all(abs(b - quarter) < 0.03 * quarter
               for b in c4["dp4"]["resident_bytes"].values())
    assert lines[-1]["ok"] is False and lines[-1]["device"]["count"] == 4
