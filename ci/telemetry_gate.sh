#!/usr/bin/env bash
# Observability fast gate (ISSUE 12 satellite): the jax-free telemetry
# plumbing regressions — a viewer that grew a jax import, a perfetto
# export that drifted, a prometheus page real scrapers reject, metric
# names that rotted out of the docs — gate in <30 s without an
# accelerator. This script gates the instrumentation; measured speed is
# the benchmark's (python3 -m benchmark.run, PERF_LEDGER.jsonl).
#
# Usage:
#   ci/telemetry_gate.sh
#
# Exit nonzero on any failure.
set -eu

REPO_DIR=$(CDPATH= cd -- "$(dirname -- "$0")/.." && pwd)
cd "${REPO_DIR}"

echo "== [1/3] viewer import guard (poisoned jax + numpy stubs)"
python - <<'EOF'
import os, subprocess, sys, tempfile
d = tempfile.mkdtemp(prefix="poisoned_deps_")
for name in ("jax", "numpy"):
    with open(os.path.join(d, name + ".py"), "w") as fh:
        fh.write("raise ImportError('poisoned: the viewer must not "
                 "import " + name + "')\n")
env = dict(os.environ)
env["PYTHONPATH"] = d + os.pathsep + env.get("PYTHONPATH", "")
r = subprocess.run(
    [sys.executable, "-c", "import deepspeed_tpu.telemetry.view"],
    env=env, capture_output=True, text=True)
if r.returncode != 0:
    sys.stderr.write("viewer import chain pulled jax/numpy:\n" + r.stderr)
    sys.exit(1)
print("   ok (stdlib-only import chain)")
EOF

echo "== [2/3] perfetto export golden round-trip (poisoned stubs)"
# ISSUE 19: the exporter is deterministic and stdlib-only — render the
# checked-in 2-rank golden dumps via the CLI under poisoned jax/numpy
# and byte-diff against the golden JSON. Regenerate on purposeful
# schema changes with ci/make_perfetto_golden.py.
python - <<'EOF'
import filecmp, os, subprocess, sys, tempfile
d = tempfile.mkdtemp(prefix="poisoned_deps_")
for name in ("jax", "numpy"):
    with open(os.path.join(d, name + ".py"), "w") as fh:
        fh.write("raise ImportError('poisoned: the perfetto export "
                 "path must not import " + name + "')\n")
env = dict(os.environ)
env["PYTHONPATH"] = d + os.pathsep + env.get("PYTHONPATH", "")
out = os.path.join(d, "perfetto_out.json")
r = subprocess.run(
    [sys.executable, "-m", "deepspeed_tpu.telemetry.view",
     "ci/perfetto_golden_dump_rank0.jsonl",
     "ci/perfetto_golden_dump_rank1.jsonl",
     "--format", "perfetto", "--out", out],
    env=env, capture_output=True, text=True)
if r.returncode != 0:
    sys.stderr.write("perfetto export CLI failed:\n" + r.stderr)
    sys.exit(1)
if not filecmp.cmp(out, "ci/perfetto_golden.json", shallow=False):
    sys.stderr.write(
        "perfetto export drifted from ci/perfetto_golden.json — "
        "nondeterminism or an unannounced schema change; if the "
        "change is intentional, regenerate with "
        "ci/make_perfetto_golden.py\n")
    sys.exit(1)
print("   ok (byte-identical to golden, stdlib-only)")
EOF

echo "== [3/3] prometheus grammar + metric-name drift tests"
JAX_PLATFORMS=cpu python -m pytest tests/test_metric_names.py -q \
    -p no:cacheprovider -p no:randomly

echo "telemetry_gate: PASS"
