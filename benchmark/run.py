"""The benchmark's command: one run of one cell, one JSON line last.

    python3 -m benchmark.run --workload <name> --seed <n> --seconds <s> --trace <0|1>

Run from the root of a checkout, on a machine that holds the cell's chips.
It exits non-zero, and prints no result, unless JAX finds a TPU of a kind
that ``peaks.json`` lists and at least as many chips as the cell asks for.
See ``README.md`` for what a run does and what ``correct`` checks.
"""

from __future__ import annotations

import time

T_PROCESS = time.perf_counter()  # set-up is counted from here

import argparse  # noqa: E402
import json  # noqa: E402
import os  # noqa: E402
import sys  # noqa: E402

from benchmark import harness, trace_reduce  # noqa: E402


def device_or_die(chips: int) -> tuple[dict, dict]:
    """``(device, peak)``: what JAX reports, and its row of the table."""
    import jax

    devices = jax.devices()
    device = {"platform": devices[0].platform,
              "kind": devices[0].device_kind, "count": len(devices)}
    peaks = harness.read_json(os.path.join(harness.BENCH, "peaks.json"))
    if device["platform"] != "tpu":
        raise SystemExit(f"no TPU: {device}. The benchmark measures the "
                         "chip and has no CPU fallback.")
    if len(devices) < chips:
        raise SystemExit(f"the cell needs {chips} chips: {device}")
    if device["kind"] not in peaks:
        raise SystemExit(f"device kind {device['kind']!r} is not in "
                         "benchmark/peaks.json; add it with its source")
    return device, peaks[device["kind"]]


def main(argv: list[str] | None = None) -> int:
    ap = argparse.ArgumentParser(description=__doc__.split("\n\n")[0])
    ap.add_argument("--workload", required=True)
    ap.add_argument("--seed", type=int, required=True)
    ap.add_argument("--seconds", type=float, required=True)
    ap.add_argument("--trace", type=int, choices=(0, 1), default=0)
    args = ap.parse_args(argv)

    bench, cell, config, traffic = harness.load_cell(args.workload)
    device, peak = device_or_die(int(cell["chips"]))
    run = harness.run_cell(
        cell, config, traffic, seed=args.seed, seconds=args.seconds,
        trace=bool(args.trace), t_process=T_PROCESS, peak=peak,
        per_layer=bench["per_layer"])
    print(harness.summary_line(run["info"]), flush=True)

    device["memory_peak_bytes"] = int(run["peak_bytes"])
    line = {"correct": bool(run["correct"]), "attempted": run["attempted"],
            "failed": run["failed"], "metrics": run["metrics"],
            "device": device}
    if run["reduced"] is not None:
        device["busy_s"] = run["reduced"]["busy_s"]
        device["window_s"] = run["reduced"]["window_s"]
        line["breakdown"] = trace_reduce.breakdown(run["reduced"])
    print(json.dumps(line), flush=True)
    return 0


if __name__ == "__main__":
    sys.exit(main())
