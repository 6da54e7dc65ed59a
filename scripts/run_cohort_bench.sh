#!/usr/bin/env bash
# Cohort-sharding bench cell (ISSUE 6) -> bench_matrix/cohort_sharding.json
#
# Runs bench.py in its BENCH_COHORT_DEVICES mode: per-round wall time vs C
# for the sequential C-loop / the cohort-SHARDED program / the shipped
# vmapped round, the flagship 21-site fedavg+salientgrads cells, and
# salientgrads_mask_ms under the sharded phase-1 driver. Defaults
# provision an 8-VIRTUAL-device CPU mesh on this host — treat the SLOPES
# as the stable claim (the absolute sharded speedup is a TPU-session
# measurement); override
# BENCH_COHORT_VIRTUAL=0 and the shape/model knobs on a real chip.
set -euo pipefail
cd "$(dirname "$0")/.."
mkdir -p bench_matrix
env JAX_PLATFORMS="${JAX_PLATFORMS:-cpu}" \
    BENCH_COHORT_DEVICES="${BENCH_COHORT_DEVICES:-8}" \
    BENCH_COHORT_VIRTUAL="${BENCH_COHORT_VIRTUAL:-1}" \
    BENCH_MODEL="${BENCH_MODEL:-3dcnn_tiny}" \
    BENCH_SHAPE="${BENCH_SHAPE:-12,14,12}" \
    BENCH_BATCH="${BENCH_BATCH:-8}" \
    BENCH_LOCAL="${BENCH_LOCAL:-16}" \
    BENCH_REPS="${BENCH_REPS:-3}" \
    python bench.py | tee bench_matrix/cohort_sharding.json
