#!/usr/bin/env bash
# Round-program builder bench cell (ISSUE 11) ->
# bench_matrix/round_program.json
#
# Runs bench.py in its BENCH_ROUND_PROGRAM mode: per-engine dispatch
# counts and per-round wall for K=1 per-round loops vs K=4 fused windows
# compiled by engines/program.py — including the engines the builder put
# on the fused path for the first time (ditto, dpsgd, subavg) and the
# fedfomo fallback reference. The DISPATCH COUNTS and the
# one-compiled-program-per-window evidence are the stable claims on this
# CPU harness; the wall ratio scales with per-dispatch latency and is
# not measured on the current chip.
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
