"""Benchmark: federated training throughput of the flagship workload,
measured on the SHIPPED engine path.

Phase 1 — FedAvg rounds: times ``FedAvgEngine._round_jit`` (the exact
program ``engine.train()`` runs: gather sampled clients -> vmapped local SGD
-> weighted-mean aggregation) on AlexNet3D_Dropout over full-size
121x145x121 volumes in the flagship DEPLOYMENT layout: ONE client per
chip (the multi-chip design shards the client axis, one site per core),
batch 128, 512-sample resident shard. Batch 128 is the measured
single-chip sweet spot (round-3 sweep, PROFILE.md): it fills the MXU's
batch/sublane dimensions that the reference-canonical b16 leaves idle —
b16 measured 3.5% MFU in the same session window where b128 measured
10.0%. A V100 cannot hold b128 of this model's activations at all; using
HBM for large-batch compute is the point of the TPU-first design. The
reference-parity cell (4 clients x b16) stays measurable via
``BENCH_CLIENTS=4 BENCH_BATCH=16 BENCH_LOCAL=64`` and is recorded by
scripts/run_bench_matrix.sh.

Phase 2 — SalientGrads mask: times the one-shot federated SNIP mask
pipeline (per-client saliency scores -> mean -> global top-k), giving the
Pallas histogram-select kernel (ops/topk.py) real TPU executions, and
asserts its threshold equals the XLA fallback's on-device.

Reported extras: analytic GFLOP/sample (ops/flops.py), sustained TFLOP/s,
and MFU against the visible chip's bf16 peak (device-kind table; "mfu" is
null when the chip is unknown).

Wire-codec cell (ISSUE 3): encodes a real client upload from the shipped
round program with the cross-silo wire codec — fedavg delta+quant and
masked sparse+quant against the phase-2 SNIP mask — reporting frame
bytes vs the dense msgpack wire, encode/decode ms, and the overhead as a
fraction of the measured round wall time (acceptance: < 10%).

Phase 3 — one-round timings for every other engine program, now including
the flagship's steady-state MASKED round (salientgrads phase 2), ditto
(dual-track: ~2x compute/sample), fedprox, local, and turboaggregate
(with the MPC aggregation stage — device-jitted by default — also timed
alone).

``vs_baseline`` compares against the reference's single-V100 sequential
simulation. The reference publishes NO numbers (BASELINE.md), so the
denominator is an ANALYTIC {low=48, mid=64, high=96} samples/s bound
derived in BASELINE.md ("Derived V100 throughput bound": 22.36
GFLOP/sample x V100 fp32 roofline x assumed Conv3d MFU range);
``vs_baseline`` divides by mid and ``vs_baseline_range`` carries the
[value/high, value/low] spread. North star: >= 8x (BASELINE.json).

Prints exactly one JSON line:
  {"metric": ..., "value": N, "unit": ..., "vs_baseline": N, ...extras}

Env knobs: BENCH_BATCH (default 128), BENCH_CLIENTS (1), BENCH_LOCAL
(512), BENCH_ROUNDS (3), BENCH_REPS (3 — best-of-N timed repeats),
BENCH_SHAPE /
BENCH_MODEL (CPU smoke runs of the harness itself). The persistent
compile cache follows utils/compile_cache.py's one rule
(JAX_COMPILATION_CACHE_DIR, else <checkout>/.jax_cache).
"""

from __future__ import annotations

import json
import os
import time

# {low, mid, high} analytic V100 throughput bound — derivation with MFU
# assumptions in BASELINE.md ("Derived V100 throughput bound").
# vs_baseline divides by MID; vs_baseline_range spans [value/high, value/low].
V100_BASELINE_LOW = 48.0
V100_BASELINE_SAMPLES_PER_SEC = 64.0   # mid
V100_BASELINE_HIGH = 96.0


def _chip_peak_tflops() -> float | None:
    """Per-chip bf16 peak from the one device-kind table
    (obs/compute.py): None on the CPU (no honest peak), an error naming
    the kind on an accelerator the table does not list."""
    import jax

    from neuroimagedisttraining_tpu.obs.compute import peak_flops_estimate

    total = peak_flops_estimate()
    return total / len(jax.local_devices()) / 1e12 if total else None


def cohort_sharding_cell(n_devices: int) -> dict:
    """Cohort-sharding bench cell (ISSUE 6): per-round wall time vs C for
    the sequential C-loop (the reference's client-at-a-time simulation as
    ONE ``lax.map`` program), the cohort-SHARDED program
    (parallel/cohort.py), and the shipped vmapped unsharded round —
    plus the flagship 21-site fedavg + salientgrads cells and
    ``salientgrads_mask_ms`` under the sharded phase-1
    driver (PROFILE.md round 7 / ROADMAP item 4 reconciliation).

    Env: BENCH_COHORT_DEVICES=D arms this cell (main() then prints ONLY
    it); BENCH_COHORT_VIRTUAL=1 provisions D virtual CPU devices first
    (the committed bench_matrix/cohort_sharding.json artifact runs this
    way on the 2-core harness — treat the SLOPES as the stable claim
    there; the absolute speedup is a TPU-session measurement).
    BENCH_COHORT_CLIENTS overrides the C sweep."""
    if os.environ.get("BENCH_COHORT_VIRTUAL", "0") == "1":
        from neuroimagedisttraining_tpu.parallel.mesh import (
            provision_virtual_devices,
        )
        provision_virtual_devices(n_devices)

    import jax
    import jax.numpy as jnp
    import numpy as np

    from neuroimagedisttraining_tpu.config import (
        DataConfig, ExperimentConfig, FedConfig, OptimConfig,
    )
    from neuroimagedisttraining_tpu.core.trainer import LocalTrainer
    from neuroimagedisttraining_tpu.data.federate import FederatedData
    from neuroimagedisttraining_tpu.engines import create_engine
    from neuroimagedisttraining_tpu.models import create_model
    from neuroimagedisttraining_tpu.parallel.mesh import make_mesh
    from neuroimagedisttraining_tpu.utils.logging import ExperimentLogger

    D = n_devices
    batch = int(os.environ.get("BENCH_BATCH", 8))
    n_local = int(os.environ.get("BENCH_LOCAL", 16))
    reps = int(os.environ.get("BENCH_REPS", 3))
    shape = tuple(int(s) for s in
                  os.environ.get("BENCH_SHAPE", "12,14,12").split(","))
    model_name = os.environ.get("BENCH_MODEL", "3dcnn_tiny")
    c_env = os.environ.get("BENCH_COHORT_CLIENTS", "")
    Cs = ([int(c) for c in c_env.split(",")] if c_env
          else sorted({D, 2 * D, 21, 3 * D}))

    mesh = make_mesh(num_devices=D)
    log = ExperimentLogger("/tmp/nidt_bench", "synthetic", "cohort_cell",
                           console=False)

    def make_fed(C: int, pad_to: int | None, sharded: bool):
        P = C if pad_to is None else pad_to
        kx, ky = jax.random.split(jax.random.key(4))
        X = jax.random.randint(kx, (P, n_local) + shape, 0, 255,
                               dtype=jnp.int32).astype(jnp.uint8)
        y = jax.random.randint(ky, (P, n_local), 0, 2, dtype=jnp.int32)
        n = jnp.asarray([n_local] * C + [0] * (P - C), jnp.int32)
        fed = FederatedData(X_train=X, y_train=y, n_train=n,
                            X_test=X[:, :4], y_test=y[:, :4],
                            n_test=jnp.where(n > 0, 4, 0))
        if sharded:
            from neuroimagedisttraining_tpu.parallel.mesh import (
                shard_federation,
            )
            fed = shard_federation(fed, mesh)
        return fed

    def engine_for(C: int, mode: str, algorithm: str = "fedavg"):
        """mode: 'sharded' | 'sequential' (C-loop reference) |
        'vmapped' (the shipped unsharded default)."""
        pad = ((C + D - 1) // D) * D
        cfg = ExperimentConfig(
            model=model_name, num_classes=1, algorithm=algorithm,
            data=DataConfig(dataset="synthetic"),
            optim=OptimConfig(lr=1e-3, batch_size=batch, epochs=1),
            fed=FedConfig(client_num_in_total=C, comm_round=3,
                          frequency_of_the_test=10 ** 9,
                          client_mesh=D if mode != "vmapped" else 0),
            log_dir="/tmp/nidt_bench", tag=f"cohort-{mode}-{C}")
        trainer = LocalTrainer(create_model(model_name, num_classes=1),
                               cfg.optim, num_classes=1)
        use_mesh = None if mode == "vmapped" else mesh
        fed = make_fed(C, None if mode == "vmapped" else pad,
                       sharded=mode != "vmapped")
        eng = create_engine(algorithm, cfg, fed, trainer, mesh=use_mesh,
                            logger=log)
        eng._donate = False
        if mode == "sequential":
            eng._cohort_sequential = True
        return eng

    def bestof(fn):
        fn()  # compile + warm
        best = float("inf")
        for _ in range(reps):
            t0 = time.perf_counter()
            jax.block_until_ready(fn())
            best = min(best, time.perf_counter() - t0)
        return best

    cells: dict[str, dict] = {}
    for C in Cs:
        row: dict[str, float] = {}
        for mode in ("sequential", "sharded", "vmapped"):
            eng = engine_for(C, mode)
            gs = eng.init_global_state()
            sampled = eng.client_sampling(0)
            if mode == "vmapped":
                rngs = eng.per_client_rngs(0, sampled)
                fn = lambda e=eng, g=gs, s=sampled, r=rngs: e._round_jit(
                    g.params, g.batch_stats, e.data, jnp.asarray(s), r,
                    e.round_lr(0))
            else:
                ids, n_real = eng._cohort_pad(sampled)
                rngs = eng.per_client_rngs(0, ids)
                row["n_pad"] = len(ids)
                fn = lambda e=eng, g=gs, i=ids, r=rngs, nr=n_real: \
                    e._sharded_round_jit(nr)(
                        g.params, g.batch_stats, e.data, jnp.asarray(i),
                        r, e.round_lr(0))
            key = {"sequential": "sequential_loop_s",
                   "sharded": "sharded_s",
                   "vmapped": "vmapped_unsharded_s"}[mode]
            row[key] = round(bestof(fn), 4)
        row["speedup_vs_sequential_loop"] = round(
            row["sequential_loop_s"] / row["sharded_s"], 3)
        cells[str(C)] = row

    # slopes (s per client) from a least-squares fit over the C sweep —
    # the stable claim on a noisy shared host
    xs = np.asarray(Cs, np.float64)
    slope = {}
    for key in ("sequential_loop_s", "sharded_s", "vmapped_unsharded_s"):
        ys = np.asarray([cells[str(C)][key] for C in Cs])
        slope[key] = float(np.polyfit(xs, ys, 1)[0])
    slope["sharded_over_sequential"] = round(
        slope["sharded_s"] / max(slope["sequential_loop_s"], 1e-12), 4)
    slope = {k: round(v, 6) for k, v in slope.items()}

    # flagship 21-site salientgrads: sharded masked round + mask pipeline
    sg_sh = engine_for(21, "sharded", "salientgrads")
    sg_un = engine_for(21, "vmapped", "salientgrads")
    gs = sg_sh.init_global_state()
    mask_sync = lambda m: float(sum(jnp.sum(x)
                                    for x in jax.tree.leaves(m)))
    t_mask = {}
    for name, e in (("cohort_sharded", sg_sh), ("unsharded", sg_un)):
        e.generate_global_mask(gs.params, gs.batch_stats)  # compile+warm
        best = float("inf")
        for _ in range(reps):
            t0 = time.perf_counter()
            m, _ = e.generate_global_mask(gs.params, gs.batch_stats)
            mask_sync(m)
            best = min(best, time.perf_counter() - t0)
        t_mask[name] = round(best * 1e3, 1)
    masks, _ = sg_sh.generate_global_mask(gs.params, gs.batch_stats)
    per = sg_sh.broadcast_states(gs, sg_sh.num_clients)
    sampled = sg_sh.client_sampling(0)
    ids, n_real = sg_sh._cohort_pad(sampled)
    rngs = sg_sh.per_client_rngs(0, ids)
    sg_round_s = bestof(lambda: sg_sh._sharded_round_jit(n_real)(
        gs.params, gs.batch_stats, per.params, per.batch_stats,
        sg_sh.data, masks, jnp.asarray(ids), rngs, sg_sh.round_lr(0)))

    return {
        "metric": "cohort_sharding",
        "devices": D,
        "device_kind": getattr(jax.devices()[0], "device_kind",
                               "unknown"),
        "model": model_name, "shape": "x".join(map(str, shape)),
        "batch": batch, "n_local": n_local,
        "cells_per_round_s": cells,
        "slope_s_per_client": slope,
        "flagship_21_salientgrads": {
            "sharded_round_s": round(sg_round_s, 4),
            "mask_ms": t_mask,
        },
        "timing": f"best of {reps} repeats",
        "caveat": ("virtual-CPU-mesh numbers when BENCH_COHORT_VIRTUAL=1 "
                   "(2-core harness): the slope ratio is the stable "
                   "claim; the absolute sharded speedup is a "
                   "TPU-session measurement"),
    }


def obs_overhead_cell() -> dict:
    """Obs overhead guard (ISSUE 9, extended by ISSUE 14): the SAME
    smoke round loop timed with the telemetry plane disarmed (tracer
    off, registry disabled) and armed (tracer writing spans, registry
    enabled, stat_info published per round — a HARSHER cadence than the
    shipped driver, which publishes at eval boundaries only). Since
    ISSUE 14 every dispatch ALSO feeds the compute-plane profiler
    (obs/compute.py: two clock reads + a nidt_dispatch_ms observe per
    dispatch, an MFU boundary close per publish) — the armed leg
    exercises the full dispatch-boundary instrumentation, so this cell
    IS the profiler-armed overhead acceptance. Because instrumentation
    sits only at host dispatch boundaries, the per-round cost is a few
    microseconds against a multi-millisecond round — acceptance:
    overhead <= 2% (bench_matrix/obs_overhead.json).

    Env: BENCH_OBS_OVERHEAD=1 arms this cell (main() prints ONLY it);
    BENCH_OBS_ROUNDS / BENCH_REPS size the loop."""
    import jax
    import jax.numpy as jnp
    import numpy as np

    from neuroimagedisttraining_tpu.config import (
        DataConfig, ExperimentConfig, FedConfig, OptimConfig,
    )
    from neuroimagedisttraining_tpu.core.trainer import LocalTrainer
    from neuroimagedisttraining_tpu.data.federate import FederatedData
    from neuroimagedisttraining_tpu.engines import create_engine
    from neuroimagedisttraining_tpu.models import create_model
    from neuroimagedisttraining_tpu.obs import metrics as obs_metrics
    from neuroimagedisttraining_tpu.obs import trace as obs_trace
    from neuroimagedisttraining_tpu.utils.logging import ExperimentLogger

    batch = int(os.environ.get("BENCH_BATCH", 8))
    n_local = int(os.environ.get("BENCH_LOCAL", 16))
    n_clients = 4
    # floor of 1: zero rounds/reps would leave the timed legs undefined
    rounds = max(1, int(os.environ.get("BENCH_OBS_ROUNDS", 6)))
    reps = max(1, int(os.environ.get("BENCH_REPS", 5)))
    shape = tuple(int(s) for s in
                  os.environ.get("BENCH_SHAPE", "12,14,12").split(","))
    model_name = os.environ.get("BENCH_MODEL", "3dcnn_tiny")

    cfg = ExperimentConfig(
        model=model_name, num_classes=1, algorithm="fedavg",
        data=DataConfig(dataset="synthetic"),
        optim=OptimConfig(lr=1e-3, batch_size=batch, epochs=1),
        fed=FedConfig(client_num_in_total=n_clients, comm_round=rounds,
                      frequency_of_the_test=10 ** 9),
        log_dir="/tmp/nidt_bench", tag="obs-overhead")
    kx, ky = jax.random.split(jax.random.key(7))
    X = jax.random.randint(kx, (n_clients, n_local) + shape, 0, 255,
                           dtype=jnp.int32).astype(jnp.uint8)
    y = jax.random.randint(ky, (n_clients, n_local), 0, 2,
                           dtype=jnp.int32)
    n = jnp.full((n_clients,), n_local, jnp.int32)
    fed = FederatedData(X_train=X, y_train=y, n_train=n,
                        X_test=X[:, :4], y_test=y[:, :4],
                        n_test=jnp.full((n_clients,), 4, jnp.int32))
    trainer = LocalTrainer(create_model(model_name, num_classes=1),
                           cfg.optim, num_classes=1)
    log = ExperimentLogger("/tmp/nidt_bench", "synthetic",
                           "obs_overhead_cell", console=False)
    engine = create_engine("fedavg", cfg, fed, trainer, logger=log)
    engine._donate = False  # the legs replay one state through the jit
    gs = engine.init_global_state()
    sampled = jnp.asarray(engine.client_sampling(0))

    def run_rounds(armed: bool) -> float:
        p, b = gs.params, gs.batch_stats
        for r in range(rounds):
            rngs = engine.per_client_rngs(r, np.arange(n_clients))
            with obs_trace.span("round", round=r):
                p, b, loss, _ = engine._round_jit(
                    p, b, fed, sampled, rngs, engine.round_lr(r))
            if armed:
                # harsher-than-shipped publish cadence: every round
                engine.stat_info["sum_training_flops"] += 1.0
                engine.publish_stat_info(r)
        return float(loss)  # full sync closes the timed region

    def set_leg(armed: bool) -> None:
        if armed:
            obs_metrics.enable()
            obs_trace.arm("/tmp/nidt_bench/obs_overhead_trace.json",
                          tags={"bench": "obs_overhead"})
        else:
            obs_metrics.disable()
            obs_trace.disarm()

    run_rounds(False)  # compile + warm
    legs = {"disarmed": float("inf"), "armed": float("inf")}
    ratios = []
    # legs INTERLEAVED per repeat: the shared-box load drifts on the
    # seconds scale, and back-to-back leg blocks would alias that drift
    # into a fake (even negative) "overhead". The estimator is the
    # MEDIAN of per-repeat armed/disarmed ratios — each repeat's pair
    # runs temporally adjacent, so low-frequency drift cancels WITHIN
    # the pair, where a best-of-each-leg quotient compares two
    # different load windows and can swing past the ±2% bound on a
    # drifty box (measured: best-of quotients ranged −5.7%..+17.8% on
    # an idle sandbox while paired medians sit at the noise floor).
    for _ in range(reps):
        pair = {}
        for name, armed in (("disarmed", False), ("armed", True)):
            set_leg(armed)
            t0 = time.perf_counter()
            run_rounds(armed)
            pair[name] = time.perf_counter() - t0
            legs[name] = min(legs[name], pair[name])
        ratios.append(pair["armed"] / pair["disarmed"])
    obs_metrics.enable()
    obs_trace.disarm()
    overhead = float(np.median(ratios)) - 1.0
    return {
        "metric": "obs_overhead",
        "model": model_name, "shape": "x".join(map(str, shape)),
        "batch": batch, "clients": n_clients, "rounds_per_leg": rounds,
        "disarmed_s": round(legs["disarmed"], 4),
        "armed_s": round(legs["armed"], 4),
        "per_rep_ratios": [round(r, 4) for r in ratios],
        "overhead_frac": round(overhead, 4),
        "acceptance": "overhead_frac <= 0.02 (armed = span per round + "
                      "stat_info publish per round + tracer buffering + "
                      "the ISSUE 14 dispatch profiler: nidt_dispatch_ms "
                      "observe per dispatch, MFU boundary per publish)",
        "timing": f"median of {reps} paired-repeat ratios x {rounds} "
                  "rounds (legs best-of for reference)",
    }


def precision_cell() -> dict:
    """Precision/fused-update bench cell (ISSUE 10): the SAME shipped
    FedAvg round program timed under three train-step configurations —
    ``fp32`` (the legacy tree bitwise), ``bf16_mixed`` (bf16 compute +
    activations, f32 master weights — core/optim.py), and ``bf16_mixed``
    with the fused mask/clip/momentum/update tail
    (``--fused_update``, ops/fused_update.py) — plus a compile-time
    peak-memory estimate per leg (XLA's ``memory_analysis`` temp/argument
    bytes: the activation working set the remat policy trades against)
    and the parity numbers the tolerance pins state (bf16-vs-fp32 loss
    delta; fused-vs-unfused bitwise flag on this backend).

    Env: BENCH_PRECISION=1 arms this cell (main() prints ONLY it);
    BENCH_BATCH / BENCH_LOCAL / BENCH_SHAPE / BENCH_MODEL / BENCH_REMAT /
    BENCH_REPS size it. On the CPU harness the WALL numbers are smoke —
    the honest caveat rides the payload; the real fp32-vs-bf16 step
    ratio and the fused kernel's on-chip win are next-TPU-session
    measurements (scripts/run_precision_bench.sh is the entry point)."""
    import jax
    import jax.numpy as jnp
    import numpy as np

    from neuroimagedisttraining_tpu.config import (
        DataConfig, ExperimentConfig, FedConfig, OptimConfig,
    )
    from neuroimagedisttraining_tpu.core.optim import compute_dtype
    from neuroimagedisttraining_tpu.core.trainer import LocalTrainer
    from neuroimagedisttraining_tpu.data.federate import FederatedData
    from neuroimagedisttraining_tpu.engines import create_engine
    from neuroimagedisttraining_tpu.models import create_model
    from neuroimagedisttraining_tpu.utils.logging import ExperimentLogger

    batch = int(os.environ.get("BENCH_BATCH", 8))
    n_local = int(os.environ.get("BENCH_LOCAL", 16))
    n_clients = int(os.environ.get("BENCH_CLIENTS", 2))
    reps = max(1, int(os.environ.get("BENCH_REPS", 3)))
    shape = tuple(int(s) for s in
                  os.environ.get("BENCH_SHAPE", "12,14,12").split(","))
    model_name = os.environ.get("BENCH_MODEL", "3dcnn_tiny")
    remat_env = os.environ.get("BENCH_REMAT", "0")
    remat: bool | str | None = {"0": False, "1": True}.get(remat_env,
                                                           remat_env)
    steps = -(-n_local // batch)

    kx, ky = jax.random.split(jax.random.key(11))
    X = jax.random.randint(kx, (n_clients, n_local) + shape, 0, 255,
                           dtype=jnp.int32).astype(jnp.uint8)
    y = jax.random.randint(ky, (n_clients, n_local), 0, 2, dtype=jnp.int32)
    n = jnp.full((n_clients,), n_local, jnp.int32)
    fed = FederatedData(X_train=X, y_train=y, n_train=n,
                        X_test=X[:, :4], y_test=y[:, :4],
                        n_test=jnp.full((n_clients,), 4, jnp.int32))
    log = ExperimentLogger("/tmp/nidt_bench", "synthetic", "precision_cell",
                           console=False)

    LEGS = (("fp32", "fp32", False),
            ("bf16_mixed", "bf16_mixed", False),
            ("bf16_mixed_fused", "bf16_mixed", True),
            ("fp32_fused", "fp32", True))

    legs: dict[str, dict] = {}
    end_params: dict[str, object] = {}
    end_loss: dict[str, float] = {}
    for leg_name, precision, fused in LEGS:
        optim = OptimConfig(lr=1e-3, batch_size=batch, epochs=1,
                            precision=precision, fused_update=fused)
        cfg = ExperimentConfig(
            model=model_name, num_classes=1, algorithm="fedavg",
            data=DataConfig(dataset="synthetic"), optim=optim,
            fed=FedConfig(client_num_in_total=n_clients, comm_round=1,
                          frequency_of_the_test=10 ** 9),
            log_dir="/tmp/nidt_bench", tag=f"prec-{leg_name}")
        trainer = LocalTrainer(
            create_model(model_name, num_classes=1,
                         dtype=compute_dtype(precision), remat=remat),
            optim, num_classes=1)
        eng = create_engine("fedavg", cfg, fed, trainer, logger=log)
        eng._donate = False  # legs replay one state through the program
        gs = eng.init_global_state()
        sampled = jnp.asarray(eng.client_sampling(0))
        rngs = eng.per_client_rngs(0, np.arange(n_clients))
        lr = eng.round_lr(0)

        def run(e=eng, g=gs, s=sampled, r=rngs, lr=lr):
            out = e._round_jit(g.params, g.batch_stats, e.data, s, r, lr)
            jax.block_until_ready(out[0])
            return out

        out = run()  # compile + warm
        best = float("inf")
        for _ in range(reps):
            t0 = time.perf_counter()
            out = run()
            best = min(best, time.perf_counter() - t0)
        end_params[leg_name] = out[0]
        end_loss[leg_name] = float(out[2])

        # compile-time peak-memory estimate: XLA's own accounting of the
        # program's temp (activation working set) + argument bytes — the
        # number the remat policy trades against; device memory_stats()
        # replaces it with a MEASURED peak on TPU sessions
        mem = None
        try:
            compiled = eng._round_jit.lower(
                gs.params, gs.batch_stats, eng.data, sampled, rngs,
                lr).compile()
            ma = compiled.memory_analysis()
            mem = {
                "temp_bytes": int(getattr(ma, "temp_size_in_bytes", 0)),
                "argument_bytes": int(
                    getattr(ma, "argument_size_in_bytes", 0)),
                "output_bytes": int(
                    getattr(ma, "output_size_in_bytes", 0)),
            }
        except Exception:  # memory_analysis is backend-best-effort
            mem = None
        samples = n_clients * steps * batch
        legs[leg_name] = {
            "round_s": round(best, 4),
            "samples_per_sec": round(samples / best, 2),
            "memory_analysis": mem,
        }

    bitwise = lambda a, b: bool(all(
        np.array_equal(np.asarray(x), np.asarray(yv))
        for x, yv in zip(jax.tree.leaves(a), jax.tree.leaves(b))))
    max_delta = lambda a, b: float(max(
        float(jnp.max(jnp.abs(x - yv)))
        for x, yv in zip(jax.tree.leaves(a), jax.tree.leaves(b))))
    return {
        "metric": "precision_bench",
        "model": model_name, "shape": "x".join(map(str, shape)),
        "batch": batch, "clients": n_clients, "n_local": n_local,
        "remat": str(remat),
        "device_kind": getattr(jax.devices()[0], "device_kind", "unknown"),
        "legs": legs,
        "parity": {
            "fp32_fused_bitwise_equals_fp32": bitwise(
                end_params["fp32"], end_params["fp32_fused"]),
            "bf16_fused_bitwise_equals_bf16": bitwise(
                end_params["bf16_mixed"], end_params["bf16_mixed_fused"]),
            "bf16_vs_fp32_loss_abs_delta": round(
                abs(end_loss["bf16_mixed"] - end_loss["fp32"]), 6),
            "bf16_vs_fp32_param_max_abs_delta": round(max_delta(
                end_params["fp32"], end_params["bf16_mixed"]), 8),
        },
        "timing": f"best of {reps} repeats, one shipped FedAvg round",
        "caveat": ("CPU-harness smoke numbers when run off-TPU: the "
                   "parity columns and the memory_analysis estimates are "
                   "the stable claims; the fp32-vs-bf16 step ratio, the "
                   "fused kernel's HBM win, and the measured peak-HBM "
                   "are TPU-session measurements "
                   "(scripts/run_precision_bench.sh)"),
    }


def round_program_cell() -> dict:
    """Round-program builder bench cell (ISSUE 11): per-engine dispatch
    and compile counts and per-round wall of the round loop through
    engines/program.py (fedavg, ditto, dpsgd, subavg) beside an engine
    that drives its own per-round jits (fedfomo). The counts are exact
    (program.dispatches / program.built: one dispatch a round, one
    compiled program a run) and are the stable claim; the wall numbers
    are this CPU harness's, compile included.

    Env: BENCH_ROUND_PROGRAM=1 arms this cell (main() prints ONLY it);
    BENCH_RP_ROUNDS (default 8), BENCH_RP_ENGINES, BENCH_BATCH /
    BENCH_LOCAL / BENCH_SHAPE / BENCH_MODEL size it."""
    import time

    import jax

    from neuroimagedisttraining_tpu.config import (
        DataConfig, ExperimentConfig, FedConfig, OptimConfig,
    )
    from neuroimagedisttraining_tpu.core.trainer import LocalTrainer
    from neuroimagedisttraining_tpu.data.federate import federate_cohort
    from neuroimagedisttraining_tpu.data.synthetic import (
        generate_synthetic_abcd,
    )
    from neuroimagedisttraining_tpu.engines import create_engine
    from neuroimagedisttraining_tpu.models import create_model
    from neuroimagedisttraining_tpu.parallel.mesh import make_mesh
    from neuroimagedisttraining_tpu.utils.logging import ExperimentLogger

    batch = int(os.environ.get("BENCH_BATCH", 8))
    n_local = int(os.environ.get("BENCH_LOCAL", 16))
    rounds = int(os.environ.get("BENCH_RP_ROUNDS", 8))
    shape = tuple(int(s) for s in
                  os.environ.get("BENCH_SHAPE", "12,14,12").split(","))
    model_name = os.environ.get("BENCH_MODEL", "3dcnn_tiny")
    names = os.environ.get(
        "BENCH_RP_ENGINES", "fedavg,ditto,dpsgd,subavg,fedfomo").split(",")

    cohort = generate_synthetic_abcd(
        num_subjects=4 * n_local, shape=shape, num_sites=4, seed=0)

    def run(algorithm: str):
        cfg = ExperimentConfig(
            model=model_name, num_classes=1, algorithm=algorithm,
            data=DataConfig(dataset="synthetic", partition_method="site",
                            val_fraction=0.25 if algorithm == "fedfomo"
                            else 0.0),
            optim=OptimConfig(lr=1e-3, batch_size=batch, epochs=1),
            fed=FedConfig(client_num_in_total=4, comm_round=rounds,
                          frequency_of_the_test=10 ** 9),
            log_dir="/tmp/nidt_bench", tag=f"rp-{algorithm}")
        mesh = make_mesh()
        trainer = LocalTrainer(create_model(model_name, num_classes=1),
                               cfg.optim, num_classes=1)
        log = ExperimentLogger("/tmp/nidt_bench", "synthetic",
                               cfg.identity(), console=False)
        fed, _ = federate_cohort(
            cohort, partition_method="site", mesh=mesh,
            val_fraction=cfg.data.val_fraction)
        eng = create_engine(algorithm, cfg, fed, trainer, mesh=mesh,
                            logger=log)
        t0 = time.perf_counter()
        eng.train()
        wall = time.perf_counter() - t0
        prog = eng.program
        return {
            "wall_s": round(wall, 3),
            "wall_per_round_ms": round(1e3 * wall / rounds, 2),
            # engines without declared stages (fedfomo here) drive their
            # own per-round jits — the builder counters don't see them,
            # but their dispatch count IS one-per-round by construction
            "dispatches": (prog.dispatches if prog.stages is not None
                           else rounds),
            "programs_built": (prog.built if prog.stages is not None
                               else None),
        }

    engines = {algorithm: run(algorithm) for algorithm in names}
    return {
        "metric": "round_program",
        "model": model_name, "shape": "x".join(map(str, shape)),
        "batch": batch, "n_local": n_local, "rounds": rounds,
        "device_kind": getattr(jax.devices()[0], "device_kind",
                               "unknown"),
        "engines": engines,
        "notes": ("dispatches counts compiled-program invocations "
                  "(engines/program.py RoundProgram.dispatches; train "
                  "rounds only — eval/fine-tune jits are separate): "
                  "one a round, one compiled program a run, for every "
                  "engine whose stages are declared. CPU-harness wall "
                  "numbers INCLUDE compile; the counts are the stable "
                  "claim."),
    }


def main() -> None:
    if os.environ.get("BENCH_ROUND_PROGRAM", "0") == "1":
        # standalone cell (ISSUE 11): one JSON line, no flagship phases
        print(json.dumps(round_program_cell()))
        return
    if os.environ.get("BENCH_PRECISION", "0") == "1":
        # standalone cell (ISSUE 10): one JSON line, no flagship phases
        print(json.dumps(precision_cell()))
        return
    if os.environ.get("BENCH_OBS_OVERHEAD", "0") == "1":
        # standalone cell (ISSUE 9): one JSON line, no flagship phases
        print(json.dumps(obs_overhead_cell()))
        return
    cohort_devices = int(os.environ.get("BENCH_COHORT_DEVICES", "0"))
    if cohort_devices > 1:
        # standalone cell: provisions (optionally virtual) devices before
        # any backend touch, prints ONE JSON line, skips the flagship
        # phases (scripts/run_cohort_bench.sh -> bench_matrix/)
        print(json.dumps(cohort_sharding_cell(cohort_devices)))
        return

    import jax
    import jax.numpy as jnp
    import numpy as np

    from neuroimagedisttraining_tpu.config import (
        DataConfig, ExperimentConfig, FedConfig, OptimConfig, SparsityConfig,
    )
    from neuroimagedisttraining_tpu.core.trainer import LocalTrainer
    from neuroimagedisttraining_tpu.data.federate import FederatedData
    from neuroimagedisttraining_tpu.engines import create_engine
    from neuroimagedisttraining_tpu.models import create_model
    from neuroimagedisttraining_tpu.ops import flops as flops_ops
    from neuroimagedisttraining_tpu.ops.topk import kth_largest
    from neuroimagedisttraining_tpu.utils.compile_cache import (
        enable_compile_cache,
    )
    from neuroimagedisttraining_tpu.utils.logging import ExperimentLogger

    # reuse compiled round programs across bench invocations; warmup
    # already excludes compile from the timed region, so the cache only
    # speeds startup
    enable_compile_cache()

    batch = int(os.environ.get("BENCH_BATCH", 128))
    n_clients = int(os.environ.get("BENCH_CLIENTS", 1))
    n_rounds = int(os.environ.get("BENCH_ROUNDS", 3))
    n_local = int(os.environ.get("BENCH_LOCAL", 512))
    # BENCH_SHAPE="12,14,12" shrinks volumes for CPU smoke runs of the
    # bench harness itself; real numbers use the default ABCD shape
    shape = tuple(int(s) for s in
                  os.environ.get("BENCH_SHAPE", "121,145,121").split(","))
    epochs = 1
    steps = -(-n_local // batch)  # ceil: local steps per client per epoch

    cfg = ExperimentConfig(
        model="3DCNN", num_classes=1, algorithm="fedavg",
        data=DataConfig(dataset="synthetic"),
        optim=OptimConfig(lr=1e-3, batch_size=batch, epochs=epochs),
        fed=FedConfig(client_num_in_total=n_clients, comm_round=n_rounds,
                      frequency_of_the_test=10**9),
        sparsity=SparsityConfig(dense_ratio=0.5, itersnip_iterations=1),
        log_dir="/tmp/nidt_bench")

    # device-resident synthetic federation at real ABCD shapes
    kx, ky = jax.random.split(jax.random.key(2))
    X = jax.random.randint(kx, (n_clients, n_local) + shape, 0, 255,
                           dtype=jnp.int32).astype(jnp.uint8)
    y = jax.random.randint(ky, (n_clients, n_local), 0, 2, dtype=jnp.int32)
    n = jnp.full((n_clients,), n_local, jnp.int32)
    fed = FederatedData(X_train=X, y_train=y, n_train=n,
                        X_test=X[:, :8], y_test=y[:, :8],
                        n_test=jnp.full((n_clients,), 8, jnp.int32))

    remat_env = os.environ.get("BENCH_REMAT", "0")
    remat: bool | str = {"0": False, "1": True}.get(remat_env, remat_env)
    # BENCH_DTYPE: the flagship cell's historical default is bf16 compute
    # (the TPU-native posture since round 1); fp32 makes the cell the
    # precision bench's control leg. Recorded in the payload alongside
    # remat/fused_update so artifacts from different precision configs
    # are no longer indistinguishable (ISSUE 10 satellite).
    bench_dtype = os.environ.get("BENCH_DTYPE", "bfloat16")
    fused_env = os.environ.get("BENCH_FUSED", "0") == "1"
    if fused_env:
        import dataclasses as _dc

        cfg = _dc.replace(cfg, optim=_dc.replace(cfg.optim,
                                                 fused_update=True))
    _dtypes = {"bfloat16": jnp.bfloat16, "float32": jnp.float32}
    if bench_dtype not in _dtypes:
        raise SystemExit(f"BENCH_DTYPE={bench_dtype!r}: choose one of "
                         f"{sorted(_dtypes)}")
    model = create_model(os.environ.get("BENCH_MODEL", "3DCNN"),
                         num_classes=1, dtype=_dtypes[bench_dtype],
                         remat=remat)
    trainer = LocalTrainer(model, cfg.optim, num_classes=1)
    log = ExperimentLogger("/tmp/nidt_bench", "synthetic", cfg.identity(),
                           console=False)
    engine = create_engine("fedavg", cfg, fed, trainer, logger=log)

    gs = engine.init_global_state()
    params, bstats = gs.params, gs.batch_stats
    sampled = jnp.asarray(engine.client_sampling(0))

    def one_round(params, bstats, r):
        rngs = engine.per_client_rngs(r, np.arange(n_clients))
        return engine._round_jit(params, bstats, fed, sampled, rngs,
                                 engine.round_lr(r))

    # compile + warmup
    params, bstats, loss, _ = one_round(params, bstats, 0)
    jax.block_until_ready((params, bstats, loss))

    # best-of-N timed repeats (S1 replaces this with a median and
    # quartiles; spread on the current chip: not measured)
    reps = int(os.environ.get("BENCH_REPS", 3))
    samples = n_rounds * n_clients * epochs * steps * batch
    sps = 0.0
    for _ in range(reps):
        t0 = time.perf_counter()
        for r in range(n_rounds):
            params, bstats, loss, _ = one_round(params, bstats, r + 1)
        jax.block_until_ready((params, bstats, loss))
        sps = max(sps, samples / (time.perf_counter() - t0))

    # analytic cost + MFU
    sample_in = trainer._prep(jnp.zeros((1,) + shape, jnp.float32))
    flops_per_sample = flops_ops.count_training_flops_per_sample(
        model, params, sample_in, batch_stats=bstats)
    sustained = sps * flops_per_sample
    peak = _chip_peak_tflops()
    mfu = (sustained / (peak * 1e12)) if peak else None

    # ---- phase 2: SalientGrads mask pipeline + Pallas/XLA agreement ----
    # (phase-2/3 engines replay the SAME {params, bstats, per-client}
    # buffers through their round programs across timed repeats, so
    # donation is disabled on them — it affects memory residency, not
    # the round math being timed; the donated path is what the phase-1
    # loop above measures)
    sg = create_engine("salientgrads", cfg, fed, trainer, logger=log)
    sg._donate = False

    def _mask_sync(masks):
        # value-sync through EVERY mask leaf (the threshold alone completes
        # before the downstream per-leaf comparisons do)
        return float(sum(jnp.sum(m) for m in jax.tree.leaves(masks)))

    masks, thr = sg.generate_global_mask(params, bstats)  # compile + warmup
    _mask_sync(masks)
    t0 = time.perf_counter()
    masks, thr = sg.generate_global_mask(params, bstats)
    _mask_sync(masks)
    mask_ms = (time.perf_counter() - t0) * 1e3

    # ---- phase 3: one-round TPU timings for the remaining engine
    # programs (VERDICT r2 next-step #4: einsum-consensus, sort-based
    # percentile prune, pair-list fomo weights had no recorded numbers).
    # Best-of-REPS wall time for ONE round at the flagship shape.
    algo_round_s: dict[str, float] = {}
    if os.environ.get("BENCH_ALGO_PHASES", "1") != "0":
        import dataclasses

        from neuroimagedisttraining_tpu.utils import pytree as pt

        def _sync(*arrs):
            return sum(float(jnp.sum(a.astype(jnp.float32))
                             if hasattr(a, "astype") else 0.0)
                       for a in arrs)

        def _bestof(fn):
            fn()  # compile + warmup
            best = float("inf")
            for _ in range(reps):
                t0 = time.perf_counter()
                fn()
                best = min(best, time.perf_counter() - t0)
            return best

        C = n_clients
        rngs_all = engine.per_client_rngs(1, np.arange(C))
        lr = engine.round_lr(1)

        # DisPFL: masked einsum consensus + local train + fire/regrow
        dp = create_engine("dispfl", dataclasses.replace(
            cfg, algorithm="dispfl"), fed, trainer, logger=log)
        dp._donate = False
        m_local, _ = dp.init_masks_all(params)
        dper = dp.broadcast_states(
            gs.__class__(params=params, batch_stats=bstats, opt_state=None,
                         rng=None), C)
        dpp = jax.tree.map(jnp.multiply, dper.params, m_local)
        A_dp = jnp.asarray(dp.adjacency(1, dp.active_draw(1)))

        def dispfl_round():
            out = dp._round_jit(dpp, dper.batch_stats, m_local, m_local,
                                fed, A_dp, rngs_all, lr, jnp.float32(1),
                                {})
            _sync(out[-1], jax.tree.leaves(out[0])[0])

        algo_round_s["dispfl"] = _bestof(dispfl_round)

        # D-PSGD: gossip mixing-matrix consensus + local train
        dg = create_engine("dpsgd", dataclasses.replace(
            cfg, algorithm="dpsgd"), fed, trainer, logger=log)
        dg._donate = False
        M_mix = jnp.asarray(dg.mixing_matrix(1))

        def dpsgd_round():
            out = dg._round_jit(dper.params, dper.batch_stats, fed, M_mix,
                                rngs_all, lr, {})
            _sync(out[-1], jax.tree.leaves(out[0])[0])

        algo_round_s["dpsgd"] = _bestof(dpsgd_round)

        # SubAvg: masked train + per-client full-sort percentile prune +
        # overlap-count aggregation
        sa = create_engine("subavg", dataclasses.replace(
            cfg, algorithm="subavg"), fed, trainer, logger=log)
        sa._donate = False
        from neuroimagedisttraining_tpu.ops.masks import ones_mask

        sa_masks = sa.broadcast_states(ones_mask(params), C)

        def subavg_round():
            out = sa._round_jit(params, bstats, sa_masks, fed, sampled,
                                rngs_all[: len(sampled)], lr)
            _sync(out[3], jax.tree.leaves(out[0])[0])

        algo_round_s["subavg"] = _bestof(subavg_round)

        # FedFomo: local train + pair-list val-loss/distance weights +
        # delta aggregation (needs a val split)
        fed_val = dataclasses.replace(
            fed, X_val=fed.X_test, y_val=fed.y_test, n_val=fed.n_test)
        fo = create_engine("fedfomo", dataclasses.replace(
            cfg, algorithm="fedfomo"), fed_val, trainer, logger=log)
        fo._donate = False
        A_fo = np.zeros((C, C), np.float32)
        for c in range(fo.real_clients):
            A_fo[c, np.unique(fo.benefit_choose(1, c, np.ones(C)))] = 1.0
        pc_, pn_, _np = fo.pairs_from_adjacency(A_fo)
        W0 = jnp.full((C, C), 1.0 / C, jnp.float32)
        P0 = jnp.ones((C, C), jnp.float32)

        def fedfomo_round():
            out = fo._round_jit(dper.params, dper.batch_stats, W0, P0,
                                jnp.asarray(A_fo), jnp.asarray(pc_),
                                jnp.asarray(pn_), fed_val, rngs_all, lr)
            _sync(out[-1], jax.tree.leaves(out[0])[0])

        algo_round_s["fedfomo"] = _bestof(fedfomo_round)

        # SalientGrads phase-2 MASKED round — the flagship's steady-state
        # hot loop (per-step mask multiplies on top of the FedAvg shape);
        # masks come from the phase-2 pipeline above
        rngs_s = rngs_all[: len(sampled)]

        def salientgrads_round():
            out = sg._round_jit(params, bstats, dper.params,
                                dper.batch_stats, fed, masks, sampled,
                                rngs_s, lr)
            _sync(out[-2], jax.tree.leaves(out[0])[0])

        algo_round_s["salientgrads_masked"] = _bestof(salientgrads_round)

        # FedProx: the FedAvg round + per-step proximal pull toward the
        # incoming global (engines/fedprox.py; BASELINE.json configs[3])
        fp = create_engine("fedprox", dataclasses.replace(
            cfg, algorithm="fedprox"), fed, trainer, logger=log)
        fp._donate = False

        def fedprox_round():
            out = fp._round_jit(params, bstats, fed, sampled, rngs_s, lr)
            _sync(out[-2], jax.tree.leaves(out[0])[0])

        algo_round_s["fedprox"] = _bestof(fedprox_round)

        # Ditto: dual-track round (global step + proximal personal step —
        # ~2x the FedAvg compute per sample by construction)
        dt = create_engine("ditto", dataclasses.replace(
            cfg, algorithm="ditto"), fed, trainer, logger=log)
        dt._donate = False

        def ditto_round():
            out = dt._round_jit(params, bstats, dper.params,
                                dper.batch_stats, fed, sampled, rngs_s, lr)
            _sync(out[-1], jax.tree.leaves(out[0])[0])

        algo_round_s["ditto"] = _bestof(ditto_round)

        # Local-only: vmapped per-client training, no aggregation
        lo = create_engine("local", dataclasses.replace(
            cfg, algorithm="local"), fed, trainer, logger=log)
        lo._donate = False

        def local_round():
            out = lo._round_jit(dper.params, dper.batch_stats, fed,
                                rngs_all, lr)
            _sync(out[-1], jax.tree.leaves(out[0])[0])

        algo_round_s["local"] = _bestof(local_round)

        # TurboAggregate: jitted train stage + MPC aggregation (default
        # backend "device": the quantize -> share -> slot-major sum ->
        # dequantize pipeline as jitted uint32 mod-p ops on the VPU,
        # ops/mpc_device.py; VERDICT r4 weak #3); the MPC stage is also
        # timed alone
        ta = create_engine("turboaggregate", dataclasses.replace(
            cfg, algorithm="turboaggregate"), fed, trainer, logger=log)
        ta._donate = False

        def turbo_round():
            out = ta._round_jit(params, bstats, fed, sampled, rngs_s, lr)
            _sync(out[-2], jax.tree.leaves(out[0])[0])

        algo_round_s["turboaggregate"] = _bestof(turbo_round)
        weighted, _, _, _ = ta._train_only_jit(params, bstats, fed, sampled,
                                               rngs_s, lr)
        _sync(jax.tree.leaves(weighted)[0])
        jax.block_until_ready(ta.secure_aggregate(weighted, 0))  # warm
        t0 = time.perf_counter()
        jax.block_until_ready(ta.secure_aggregate(weighted, 1))
        turbo_mpc_ms = (time.perf_counter() - t0) * 1e3
    else:
        turbo_mpc_ms = None

    # ---- wire codec cell (ISSUE 3): bytes/round + encode/decode ms ----
    # Encodes a REAL client upload — one more shipped-engine round from
    # the current params (BENCH_CLIENTS=1 => the round output IS the
    # client's trained model) — as the cross-silo wire would ship it:
    # fedavg delta+quant (dense engine) and the flagship's masked
    # sparse+quant against the phase-2 SNIP mask (mask handoff, no
    # bitmap). Reports true frame bytes vs the dense msgpack wire and
    # host encode/decode wall time; overhead_frac relates encode+decode
    # to the measured round wall time (acceptance: < 10%).
    from neuroimagedisttraining_tpu.codec import (
        decode_update, encode_update, frame_nbytes, parse_wire_spec,
    )

    ref_host = {"params": jax.tree.map(np.asarray, params),
                "batch_stats": jax.tree.map(np.asarray, bstats)}
    p2, b2, loss2, _ = one_round(params, bstats, n_rounds + 1)
    float(loss2)
    upd_host = {"params": jax.tree.map(np.asarray, p2),
                "batch_stats": jax.tree.map(np.asarray, b2)}
    masks_host = {"params": jax.tree.map(np.asarray, masks),
                  "batch_stats": jax.tree.map(
                      lambda x: np.ones_like(np.asarray(x)),
                      bstats)}
    dense_bytes = frame_nbytes(upd_host)
    round_s = samples / (n_rounds * max(sps, 1e-9))  # one round's wall time
    codec_cell = {"dense_bytes": dense_bytes}
    for key, spec_str, m in (
            ("fedavg_delta_quant", "delta+quant", None),
            ("salientgrads_mask_sparse_quant", "delta+sparse+quant",
             masks_host)):
        spec = parse_wire_spec(spec_str)
        t0 = time.perf_counter()
        frame, _ = encode_update(spec, upd_host, reference=ref_host,
                                 masks=m, mask_on_wire=False)
        enc_s = time.perf_counter() - t0
        t0 = time.perf_counter()
        decode_update(frame, like=upd_host, reference=ref_host, masks=m)
        dec_s = time.perf_counter() - t0
        nbytes = frame_nbytes(frame)
        codec_cell[key] = {
            "bytes": nbytes,
            "reduction_x": round(dense_bytes / nbytes, 2),
            "encode_ms": round(enc_s * 1e3, 1),
            "decode_ms": round(dec_s * 1e3, 1),
            "overhead_frac_of_round": round((enc_s + dec_s) / round_s, 4),
        }

    scores = jax.random.uniform(jax.random.key(5), (1 << 22,))
    # the Pallas kernel exists on the TPU only: off-TPU both fields are
    # null (never the XLA path compared with itself)
    pallas_ok = topk_ms = None
    if jax.default_backend() == "tpu":
        thr_pallas = kth_largest(scores, 1 << 21, use_pallas=True)
        thr_xla = kth_largest(scores, 1 << 21, use_pallas=False)
        pallas_ok = bool(jnp.equal(thr_pallas, thr_xla))
        t0 = time.perf_counter()
        jax.block_until_ready(
            kth_largest(scores, 1 << 21, use_pallas=True))
        topk_ms = (time.perf_counter() - t0) * 1e3

    print(json.dumps({
        "metric": "abcd_fedavg_train_samples_per_sec",
        "value": round(sps, 2),
        "unit": f"samples/s ({os.environ.get('BENCH_MODEL', '3DCNN')} "
                f"{'x'.join(map(str, shape))}, b{batch}, "
                f"{n_clients} clients, shipped FedAvgEngine round program)",
        "vs_baseline": round(sps / V100_BASELINE_SAMPLES_PER_SEC, 3),
        "vs_baseline_range": [round(sps / V100_BASELINE_HIGH, 3),
                              round(sps / V100_BASELINE_LOW, 3)],
        "gflops_per_sample": round(flops_per_sample / 1e9, 2),
        "sustained_tflops": round(sustained / 1e12, 2),
        # precision provenance (ISSUE 10 satellite): artifacts from
        # different precision configs must be distinguishable
        "dtype": bench_dtype,
        "remat": str(remat),
        "fused_update": fused_env,
        "device_kind": getattr(jax.devices()[0], "device_kind", "unknown"),
        "peak_tflops_assumed": peak,
        "mfu": round(mfu, 4) if mfu is not None else None,
        "salientgrads_mask_ms": round(mask_ms, 1),
        "algo_round_s": {k: round(v, 3) for k, v in algo_round_s.items()}
        or None,
        "algo_round_samples_per_sec": {
            k: round(n_clients * epochs * steps * batch / v, 1)
            for k, v in algo_round_s.items()} or None,
        "turboaggregate_mpc_ms": (round(turbo_mpc_ms, 1)
                                  if turbo_mpc_ms is not None else None),
        "wire_codec": codec_cell,
        "pallas_topk_ms_4m": round(topk_ms, 1) if topk_ms else None,
        "pallas_threshold_matches_xla": pallas_ok,
        "timing": f"best of {reps} repeats",
    }))


if __name__ == "__main__":
    main()
