#!/usr/bin/env bash
# Fault-tolerance fast gate (ISSUE 15 satellite): the self-healing
# plane's regressions — a fault-injection point that stopped firing, a
# hang watchdog that no longer trips (or trips on the compile-exempt
# first region), a supervisor state machine that leaks orphans/stale
# heartbeats or loses the crash-loop bound, a rendezvous retry that
# started retrying config errors — gate in seconds without an engine
# compile or a 2-process rendezvous. Wire it next to
# ci/telemetry_gate.sh (instrumentation): this script gates the
# RECOVERY machinery. The
# slow 2-process acceptance legs (SIGKILL auto-recovery with the loss
# trajectory preserved; in-collective hang detection) live in
# tests/test_fault_tolerance.py -m slow and ride the full suite.
#
# Usage: ci/fault_gate.sh
# Exit nonzero on any failure.
set -eu

REPO_DIR=$(CDPATH= cd -- "$(dirname -- "$0")/.." && pwd)
cd "${REPO_DIR}"

echo "== [1/2] supervisor/hang import guard (no jax backend touch)"
# the supervisor runs in the LAUNCHER process; on a TPU-VM libtpu takes
# an exclusive per-process lock, so importing these modules must never
# initialize a jax backend (module import alone is tolerated)
python - <<'EOF'
import sys
import deepspeed_tpu.runtime.elastic.supervisor as sup
import deepspeed_tpu.runtime.elastic.hang as hang
from deepspeed_tpu.runtime.elastic import faults
assert hang.EXIT_HANG != sup.EXIT_CRASH_LOOP
jax = sys.modules.get("jax")
if jax is not None:
    # imported transitively is fine; an INITIALIZED backend is not
    backends = sys.modules.get("jax._src.xla_bridge")
    live = getattr(backends, "_backends", None) if backends else None
    assert not live, "supervisor import chain initialized a jax backend"
print("   ok (no backend initialized)")
EOF

echo "== [2/2] fast fault-tolerance tests (injection registry, hang"
echo "   watchdog, supervisor state machine, rendezvous retry, viewer)"
JAX_PLATFORMS=cpu python -m pytest tests/test_fault_tolerance.py -q \
    -m 'not slow' -p no:cacheprovider -p no:randomly

echo "fault_gate: PASS"
