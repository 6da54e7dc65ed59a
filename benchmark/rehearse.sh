#!/bin/sh
# CPU rehearsal of every cell's control flow (benchmark/rehearse.py):
# no chip, no metric, tiny shapes. Run from the root of the checkout.
set -e
cells=$(python3 -c "import json; print(' '.join(w['name'] for w in json.load(open('BENCHMARK.json'))['workloads']))")
for cell in $cells; do
  JAX_PLATFORMS=cpu python3 -m benchmark.rehearse --workload "$cell" --trace 0
  JAX_PLATFORMS=cpu python3 -m benchmark.rehearse --workload "$cell" --trace 1
done
