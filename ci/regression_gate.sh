#!/usr/bin/env bash
# Bench regression gate (PR 6's `bench.py --compare`, runnable as ONE
# command in CI — ISSUE 7 satellite).
#
# Usage:
#   ci/regression_gate.sh PRIOR.json CANDIDATE.json [THRESHOLD]
#
#   PRIOR.json      the baseline result document — a bench-native JSON
#                   (what `python bench.py` prints as its last complete
#                   JSON line) or a driver-captured BENCH_rXX.json
#                   ({"parsed": {...}})
#   CANDIDATE.json  the result document under test, same formats
#   THRESHOLD       fractional worsening that fails the gate
#                   (default 0.05 = 5%)
#
# Exit codes:
#   0  no common headline metric regressed past the threshold
#   3  at least one metric regressed (bench.py's compare exit code)
#   2  usage / unreadable input
#
# The gated metric set is bench.py's headline_metrics(); since r09 it
# includes ``onebit_comm.bytes_reduction`` (ISSUE 10: the hierarchical
# exchange's slow-hop bytes-on-wire reduction, >= 4x — gate against
# BENCH_r09.json or newer to arm it), and since r10
# ``serving.elastic_recovered_fraction`` (ISSUE 11: every request
# survives one replica kill + one graceful drain, must stay 1.0) —
# gate against BENCH_r10.json or newer to arm that one. Since r16 it
# includes ``serving.disagg_xproc_ttft_p99`` (ISSUE 17: TTFT p99 of the
# disaggregated trace with the handoff crossing 2 REAL OS processes as
# versioned wire frames over the gloo host-bytes collective — gate
# against BENCH_r16.json or newer to arm it). Since r18 it includes
# ``serving.decode_scaleout_tok_s_ratio`` (ISSUE 18: world-3
# aggregate decode tok/s over world-2's single decode rank on the
# LPT-balanced targeted transport, >= 1.6x — gate against
# BENCH_r18.json or newer to arm it). Since r19 it includes
# ``nvme_xl.max_params_b`` (ISSUE 20: largest param count parked +
# twice re-streamed through the O_DIRECT NVMe tier on one chip, must
# stay >= 10B) and ``nvme_param.o_direct_stall_share`` (the O_DIRECT
# pipelined leg's exposed-stall share of the step — the honest-cache
# counterpart of the buffered stall gate) — gate against
# a record that carries both (none is left in the tree: the PR-20
# record was deleted with the harness it named; S1 re-records).
#
# The --candidate path never imports jax and finishes in <2 s, so this
# runs on artifact files on any CI box. Typical wiring:
#
#   python bench.py > bench_out.txt          # on the perf machine
#   tail -n 2 bench_out.txt | head -n 1 > candidate.json
#   ci/regression_gate.sh BENCH_r06.json candidate.json || exit $?
set -u

if [ "$#" -lt 2 ]; then
    echo "usage: $0 PRIOR.json CANDIDATE.json [THRESHOLD]" >&2
    exit 2
fi

PRIOR=$1
CANDIDATE=$2
THRESHOLD=${3:-0.05}
REPO_DIR=$(CDPATH= cd -- "$(dirname -- "$0")/.." && pwd)

exec python "${REPO_DIR}/bench.py" \
    --compare "${PRIOR}" \
    --candidate "${CANDIDATE}" \
    --regression-threshold "${THRESHOLD}"
