#!/usr/bin/env bash
# Round-program builder bench cell (ISSUE 11) ->
# bench_matrix/round_program.json
#
# Runs bench.py in its BENCH_ROUND_PROGRAM mode: per-engine dispatch and
# compile counts and per-round wall of the round loop compiled by
# engines/program.py (fedavg, ditto, dpsgd, subavg) beside fedfomo's own
# per-round jits. The counts (one dispatch a round, one compiled program
# a run) are the stable claim on this CPU harness.
set -euo pipefail
cd "$(dirname "$0")/.."
mkdir -p bench_matrix
env JAX_PLATFORMS="${JAX_PLATFORMS:-cpu}" \
    BENCH_ROUND_PROGRAM=1 \
    BENCH_MODEL="${BENCH_MODEL:-3dcnn_tiny}" \
    BENCH_SHAPE="${BENCH_SHAPE:-12,14,12}" \
    BENCH_BATCH="${BENCH_BATCH:-8}" \
    BENCH_LOCAL="${BENCH_LOCAL:-16}" \
    BENCH_RP_ROUNDS="${BENCH_RP_ROUNDS:-8}" \
    python bench.py | tee bench_matrix/round_program.json
