"""CPU rehearsal of the harness's control flow. Test-only.

Runs a cell's own argv through ``harness.run_cell`` at a tiny shape
(``3dcnn_tiny`` on 12x14x12 volumes, batch 4, every site an eighth of its
size) with the device check skipped, so that wrong arguments, meshes and
control flow are found at no chip time. It measures nothing: it prints the
checks and counts only, never a number under a device metric's name, and
cannot print the contract's last line.

    JAX_PLATFORMS=cpu python3 -m benchmark.rehearse --workload <name> [--trace 1]
"""

from __future__ import annotations

import time

T_PROCESS = time.perf_counter()

import argparse  # noqa: E402
import copy  # noqa: E402
import json  # noqa: E402
import sys  # noqa: E402

from benchmark import harness  # noqa: E402

TINY_SHAPE = [12, 14, 12]
TINY_BATCH = 4
SITE_DIVISOR = 8


def tiny(config: dict, traffic: dict) -> tuple[dict, dict]:
    config, traffic = copy.deepcopy(config), copy.deepcopy(traffic)
    argv = config["argv"]
    argv[argv.index("--model") + 1] = "3dcnn_tiny"
    argv[argv.index("--batch_size") + 1] = str(TINY_BATCH)
    config.update(input_shape=TINY_SHAPE, batch_size=TINY_BATCH)
    sizes = harness.site_sizes_of(config, traffic)
    traffic["site_sizes"] = [max(10, n // SITE_DIVISOR) for n in sizes]
    traffic["name"] = "rehearsal_" + traffic["name"]
    # a tiny model on a tiny cohort learns nothing in a few rounds
    traffic["correct"] = {"loss_round": 1, "train_loss_max": 1e9,
                          "final_auc_min": 0.0, "reason": "rehearsal"}
    return config, traffic


def main(argv: list[str] | None = None) -> int:
    ap = argparse.ArgumentParser(description=__doc__.split("\n\n")[0])
    ap.add_argument("--workload", required=True)
    ap.add_argument("--seed", type=int, default=1)
    ap.add_argument("--seconds", type=float, default=2.0)
    ap.add_argument("--trace", type=int, choices=(0, 1), default=0)
    args = ap.parse_args(argv)

    bench, cell, config, traffic = harness.load_cell(args.workload)
    if int(cell["chips"]) > 1:
        from neuroimagedisttraining_tpu.parallel.mesh import (
            provision_virtual_devices,
        )

        provision_virtual_devices(int(cell["chips"]))
    import jax

    if jax.devices()[0].platform != "cpu":
        raise SystemExit("the rehearsal is for the CPU; run benchmark.run "
                         "on the chip")
    config, traffic = tiny(config, traffic)
    run = harness.run_cell(
        cell, config, traffic,
        seed=args.seed, seconds=args.seconds, trace=bool(args.trace),
        t_process=T_PROCESS, peak=None, per_layer=bench["per_layer"],
        rehearsal=True)
    info = run["info"]
    print(json.dumps({
        "rehearsal": cell["name"], "platform": "cpu",
        "checks": {k: v["ok"] for k, v in info["checks"].items()},
        "window_rounds": run["attempted"], "failed_rounds": run["failed"],
        "samples_per_round": info["checks"]["samples"]["samples_per_round"],
        "programs": info["checks"]["programs"]["programs"],
        "readers_with_a_value": sorted(run["metrics"]),
    }, default=float), flush=True)
    # compile counts are real on any backend; the window must not compile
    ok = all(info["checks"][k]["ok"] for k in
             ("no_compile_in_window", "fallbacks", "samples", "learning"))
    print("REHEARSAL", "OK" if ok else "FAILED", cell["name"], flush=True)
    return 0 if ok else 1


if __name__ == "__main__":
    sys.exit(main())
