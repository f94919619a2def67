#!/usr/bin/env bash
# NVMe swap-tier fast gate (ISSUE 20 satellite): the O_DIRECT alignment
# layer, the buffered-fallback latch, and the swapper contracts that
# ride on them — gated in <10 s without an accelerator.
# Wire it next to ci/telemetry_gate.sh (instrumentation); this script
# gates the I/O-path CORRECTNESS of the swap tier.
#
# Usage:
#   ci/swap_gate.sh
#
# Exit nonzero on any failure.
set -eu

REPO_DIR=$(CDPATH= cd -- "$(dirname -- "$0")/.." && pwd)
cd "${REPO_DIR}"

echo "== [1/2] aio + swapper import guard (poisoned jax stub)"
# ops/native/aio.py promises jax-free importability (the swap tier must
# construct before — and survive without — an accelerator stack), and
# the swapper module keeps jax behind function-local imports. A jax
# import creeping into either module chain fails here, not in prod.
python - <<'EOF'
import os, subprocess, sys, tempfile
d = tempfile.mkdtemp(prefix="poisoned_deps_")
with open(os.path.join(d, "jax.py"), "w") as fh:
    fh.write("raise ImportError('poisoned: the swap tier must not "
             "import jax at module level')\n")
env = dict(os.environ)
env["PYTHONPATH"] = d + os.pathsep + env.get("PYTHONPATH", "")
r = subprocess.run(
    [sys.executable, "-c",
     "import deepspeed_tpu.ops.native.aio; "
     "import deepspeed_tpu.runtime.swap_tensor.swapper"],
    env=env, capture_output=True, text=True)
if r.returncode != 0:
    sys.stderr.write("swap-tier import chain pulled jax:\n" + r.stderr)
    sys.exit(1)
print("   ok (jax-free import chain)")
EOF

echo "== [2/2] O_DIRECT alignment / fallback / swapper contract tests"
# the snapshot case needs jax — the tier-1 run owns it; everything else
# in the file is accelerator-free and fast
JAX_PLATFORMS=cpu python -m pytest tests/test_o_direct.py -q \
    -k "not snapshot" -p no:cacheprovider -p no:randomly

echo "swap_gate: PASS"
