#!/usr/bin/env bash
# Byzantine-robustness A/B (ISSUE 5 acceptance): 4-silo simulated
# federations (the engine CLI — the attack runs INSIDE the jitted round
# body via faults/adversary.py) on a hard low-signal synthetic cohort,
# 1 of 4 silos sign-flipping its upload delta from round 0:
#
#   clean          no fault, defense none        -> the attack-free AUC
#   attack_none    byz:1@0:sign_flip, no defense -> degraded (the flipped
#                  silo carries ~its sample weight against the honest
#                  sum; on seeds where it is the heaviest silo the
#                  weighted mean FOLLOWS the attacker below chance)
#   attack_trimmed byz + --defense trimmed_mean  -> recovered
#   attack_krum    byz + --defense krum          -> recovered
#
# Each cell runs SEEDS (default 3 7 11) end to end and the summary
# compares mean final AUC: attack_none must degrade by >= DEGRADE_MIN
# below clean, each defense must recover to within RECOVER_MARGIN of
# clean. Artifact: bench_matrix/byz_bench.json.
#
# The cohort uses --synthetic_signal 5 (vs the sigma-8 voxel noise;
# default 12): at the default the task saturates in ~2 effective
# rounds, so even a halved effective step learns it and the attack is
# invisible. Large local batches (32) + 2 local epochs keep the honest
# silos' deltas mutually consistent, so the order statistics discard
# the attacker — not honest signal.
set -uo pipefail
cd "$(dirname "$0")/.."

export JAX_PLATFORMS="${JAX_PLATFORMS:-cpu}"
PY=${PYTHON:-python}
ROUNDS=${BYZ_BENCH_ROUNDS:-16}
SEEDS=(${BYZ_BENCH_SEEDS:-3 7 11})
OUT=bench_matrix/byz_bench.json
mkdir -p bench_matrix /tmp/byz_bench

run_one() {
    local tag=$1 seed=$2; shift 2
    echo "== byz bench [$tag seed=$seed]: $* =="
    local log="/tmp/byz_bench/${tag}_s${seed}.log"
    if ! $PY -m neuroimagedisttraining_tpu \
        --dataset synthetic --model 3dcnn_tiny \
        --synthetic_num_subjects 192 --synthetic_shape 12 14 12 \
        --synthetic_signal 5 \
        --client_num_in_total 4 --frac 1.0 --comm_round "$ROUNDS" \
        --batch_size 32 --epochs 2 --lr 2e-3 \
        --frequency_of_the_test 99 --seed "$seed" "$@" > "$log" 2>&1
    then
        echo "FAIL($tag seed=$seed)"; tail -20 "$log"; return 1
    fi
    grep -a -o '^{.*}' "$log" | tail -1 \
        > "/tmp/byz_bench/${tag}_s${seed}.json"
}

ATK=(--fault_spec byz:1@0:sign_flip)
rc=0
for seed in "${SEEDS[@]}"; do
    run_one clean          "$seed"                                    || rc=1
    run_one attack_none    "$seed" "${ATK[@]}"                        || rc=1
    run_one attack_trimmed "$seed" "${ATK[@]}" --defense trimmed_mean \
                           --byz_f 1                                  || rc=1
    run_one attack_krum    "$seed" "${ATK[@]}" --defense krum \
                           --byz_f 1                                  || rc=1
done
[ $rc -ne 0 ] && exit $rc

$PY - "$OUT" "$ROUNDS" "${SEEDS[@]}" <<'EOF'
import json
import sys

out_path, rounds = sys.argv[1], int(sys.argv[2])
seeds = [int(s) for s in sys.argv[3:]]
DEGRADE_MIN = 0.10     # attack_none must lose >= this much mean AUC
RECOVER_MARGIN = 0.15  # defenses must land within this of clean

cells = {}
for tag in ("clean", "attack_none", "attack_trimmed", "attack_krum"):
    aucs = []
    for s in seeds:
        res = json.load(open(f"/tmp/byz_bench/{tag}_s{s}.json"))
        aucs.append(float(res["final_global"]["auc"]))
    cells[tag] = {"auc_by_seed": dict(zip(map(str, seeds), aucs)),
                  "mean_auc": sum(aucs) / len(aucs)}

clean = cells["clean"]["mean_auc"]
degrade = clean - cells["attack_none"]["mean_auc"]
summary = {
    "setup": {"silos": 4, "byzantine": 1, "attack": "byz:1@0:sign_flip",
              "rounds": rounds, "seeds": seeds, "model": "3dcnn_tiny",
              "dataset": "synthetic(signal=5, 192 subjects, 12x14x12)",
              "batch_size": 32, "epochs": 2, "lr": 2e-3},
    "cells": cells,
    "degrade_auc": round(degrade, 4),
    "degrade_min": DEGRADE_MIN,
    "recover_margin": RECOVER_MARGIN,
}
ok = degrade >= DEGRADE_MIN
print(f"attack degradation: clean {clean:.3f} -> "
      f"none {cells['attack_none']['mean_auc']:.3f} "
      f"(-{degrade:.3f}, need >= {DEGRADE_MIN}) -> "
      f"{'PASS' if ok else 'FAIL'}")
for tag in ("attack_trimmed", "attack_krum"):
    gap = clean - cells[tag]["mean_auc"]
    good = gap <= RECOVER_MARGIN
    cells[tag]["recovered"] = bool(good)
    print(f"{tag}: mean AUC {cells[tag]['mean_auc']:.3f} "
          f"(gap to clean {gap:+.3f}, margin {RECOVER_MARGIN}) -> "
          f"{'PASS' if good else 'FAIL'}")
    ok = ok and good
summary["pass"] = bool(ok)
json.dump(summary, open(out_path, "w"), indent=1, sort_keys=True)
print(f"artifact -> {out_path}")
sys.exit(0 if ok else 1)
EOF
