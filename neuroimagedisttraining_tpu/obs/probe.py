"""Declarative profile-session driver (ISSUE 14): PROFILE.md's hand-run
probe checklist as a probe MANIFEST, executed push-button into one
machine-readable artifact.

Every chip session so far re-ran a prose checklist (PROFILE.md
rounds 8/9: "run precision bench at flagship shape", "re-read mask_ms",
"sweep remat x batch") by hand and pasted numbers back into markdown.
This module makes the session a FUNCTION: each :class:`Probe` names one
config cell (precision x remat x fused x client_mesh x batch), the
driver runs it through the SHIPPED engine driver (``engine.train()`` —
the same round loop / sharded dispatch path production runs, not a
bench-only loop) with the
dispatch-boundary profiler armed (obs/compute.py), and the session
emits ``bench_matrix/profile_session.json``:

- per probe: wall, per-round ms, exact dispatch/compile counts
  (deterministic compile facts the bench gate pins with ``eq``),
  sustained TFLOP/s and — when the device peak is known — the MFU
  sample for the last boundary window;
- once per session: the XLA ``cost_analysis`` FLOPs of one lowered
  training step reconciled against the analytic ``ops/flops.py``
  counter (ratio RECORDED, neither side silently trusted) and the
  ``memory_analysis`` byte accounting;
- a live ``/metrics`` + ``/healthz`` self-scrape over real HTTP
  (``metrics_scrape_ok`` / ``healthz_compute_ok`` — the structural
  proof the gauges this PR promises actually serve).

``analysis/bench_gate.py`` gates the artifact: structural cells
(manifest fingerprint, dispatch counts, scrape booleans) exactly,
wall/TFLOPs at the drift-tolerant ratio tripwires every other wall
cell uses. Entry points::

    scripts/run_profile_session.sh                 # the push-button
    python -m neuroimagedisttraining_tpu.obs.probe --out X.json
    python -m neuroimagedisttraining_tpu ... --profile_session X.json

Env knobs (the bench.py convention): PROFILE_MODEL / PROFILE_SHAPE /
PROFILE_BATCH / PROFILE_LOCAL / PROFILE_CLIENTS / PROFILE_ROUNDS size
the cells (defaults are the CPU-harness smoke shape; the TPU session
exports the flagship shape — PROFILE.md round 10). A custom manifest
JSON (``--manifest``) replaces the default probe list; cells it names
ride the same driver.
"""

from __future__ import annotations

import argparse
import dataclasses
import json
import os
import sys
import time
from typing import Any

__all__ = ["Probe", "default_manifest", "load_manifest", "run_probe",
           "run_session", "session_ok", "main", "validate_cell_value",
           "remat_policy"]

#: config-cell keys a probe may set; anything else in a manifest cell
#: is a spelling error and fails loudly at load (declarative probes
#: must not silently ignore a knob)
CELL_KEYS = ("precision", "fused_update", "remat", "client_mesh",
             "batch")

#: legal remat spellings in a cell: the CLI policy strings plus the
#: historic manifest booleans (True -> full remat, False -> off)
REMAT_CELL_VALUES = ("none", "stem", "all")


def remat_policy(value) -> bool | str:
    """Map a cell's remat value onto ``LocalTrainer(remat=...)``: bools
    pass through, the CLI policy strings map {"none": off, "stem":
    stem-only, "all": full}."""
    if isinstance(value, bool):
        return value
    return {"none": False, "stem": "stem", "all": True}[value]


def validate_cell_value(key: str, value) -> None:
    """Per-axis domain check (ValueError on violation) — shared by the
    manifest loader and the autotuner's space generator (tune/space.py)
    so neither can propose a cell the driver would choke on."""
    def die(expect: str) -> None:
        raise ValueError(f"cell key {key}={value!r} out of domain: "
                         f"expected {expect}")

    if key == "precision":
        from neuroimagedisttraining_tpu.core.optim import PRECISIONS
        if value not in PRECISIONS:
            die(f"one of {PRECISIONS}")
    elif key == "fused_update":
        if not isinstance(value, bool):
            die("a bool")
    elif key == "remat":
        if not isinstance(value, bool) and value not in REMAT_CELL_VALUES:
            die(f"a bool or one of {REMAT_CELL_VALUES}")
    elif key == "client_mesh":
        if isinstance(value, bool) or not isinstance(value, int) or value < 0:
            die("an int >= 0")
    elif key == "batch":
        if isinstance(value, bool) or not isinstance(value, int) or value < 1:
            die("an int >= 1")
    else:
        raise ValueError(f"unknown cell key {key!r}; declarable keys: "
                         f"{CELL_KEYS}")


@dataclasses.dataclass(frozen=True)
class Probe:
    """One declared probe: a name and the config cell it pins. Cell
    values ride ``ExperimentConfig`` knobs verbatim; unset knobs keep
    the shipped defaults, so a probe IS a reproducible CLI spelling."""

    name: str
    cell: dict

    def __post_init__(self):
        bad = set(self.cell) - set(CELL_KEYS)
        if bad:
            raise ValueError(
                f"probe {self.name!r} names unknown cell keys "
                f"{sorted(bad)}; declarable keys: {CELL_KEYS}")
        for key, value in self.cell.items():
            try:
                validate_cell_value(key, value)
            except ValueError as e:
                raise ValueError(f"probe {self.name!r}: {e}") from None


def default_manifest(n_devices: int = 1) -> tuple[Probe, ...]:
    """PROFILE.md's queued probe list, declared (round-9 items 1/2/4):
    the precision step-ratio pair, the fused-update delta, the remat
    product, and — when a client mesh is available — the
    cohort-sharded dispatch. One cell each."""
    probes = [
        Probe("fp32_baseline", {"precision": "fp32"}),
        Probe("bf16", {"precision": "bf16_mixed"}),
        Probe("bf16_fused", {"precision": "bf16_mixed",
                             "fused_update": True}),
        Probe("bf16_remat", {"precision": "bf16_mixed", "remat": True}),
    ]
    if n_devices > 1:
        probes.append(Probe("cohort_sharded",
                            {"precision": "fp32",
                             "client_mesh": n_devices}))
    return tuple(probes)


def load_manifest(path: str) -> tuple[Probe, ...]:
    """A manifest file is a JSON list of ``{"name", "cell"}`` objects —
    the declarative form a future session edits instead of editing
    driver code."""
    with open(path) as f:
        doc = json.load(f)
    if not isinstance(doc, list) or not doc:
        raise ValueError(f"manifest {path}: expected a non-empty JSON "
                         "list of {name, cell} objects")
    return tuple(Probe(p["name"], dict(p.get("cell", {}))) for p in doc)


def _env_meta() -> dict:
    return {
        "model": os.environ.get("PROFILE_MODEL", "3dcnn_tiny"),
        "shape": tuple(int(s) for s in os.environ.get(
            "PROFILE_SHAPE", "12,14,12").split(",")),
        "batch": int(os.environ.get("PROFILE_BATCH", 8)),
        "n_local": int(os.environ.get("PROFILE_LOCAL", 16)),
        "clients": int(os.environ.get("PROFILE_CLIENTS", 4)),
        "rounds": int(os.environ.get("PROFILE_ROUNDS", 5)),
    }


def _make_fed(meta: dict):
    """Seeded synthetic federation at the session shape (the bench
    cells' construction — deterministic in the key, no disk)."""
    import jax
    import jax.numpy as jnp

    from neuroimagedisttraining_tpu.data.federate import FederatedData

    kx, ky = jax.random.split(jax.random.key(20))
    C, n_local = meta["clients"], meta["n_local"]
    shape = tuple(meta["shape"])
    X = jax.random.randint(kx, (C, n_local) + shape, 0, 255,
                           dtype=jnp.int32).astype(jnp.uint8)
    y = jax.random.randint(ky, (C, n_local), 0, 2, dtype=jnp.int32)
    n = jnp.full((C,), n_local, jnp.int32)
    return FederatedData(X_train=X, y_train=y, n_train=n,
                         X_test=X[:, :4], y_test=y[:, :4],
                         n_test=jnp.full((C,), 4, jnp.int32))


def run_probe(probe: Probe, meta: dict, fed, log) -> dict:
    """One probe through the SHIPPED driver: build the cell's engine,
    ``engine.train()``, read the exact dispatch/compile counts off its
    round program and the MFU/TFLOPs samples off the profiler."""
    import jax

    from neuroimagedisttraining_tpu.config import (
        DataConfig, ExperimentConfig, FedConfig, OptimConfig,
    )
    from neuroimagedisttraining_tpu.core.optim import compute_dtype
    from neuroimagedisttraining_tpu.core.trainer import LocalTrainer
    from neuroimagedisttraining_tpu.engines import create_engine
    from neuroimagedisttraining_tpu.models import create_model
    from neuroimagedisttraining_tpu.obs import compute as obs_compute
    from neuroimagedisttraining_tpu.parallel.mesh import make_mesh

    cell = dict(probe.cell)
    cm = int(cell.get("client_mesh", 0))
    if cm > 1 and len(jax.devices()) < cm:
        return {"config": cell, "ran": False,
                "skip_reason": f"client_mesh={cm} needs {cm} devices, "
                               f"{len(jax.devices())} visible "
                               "(--virtual_devices provisions them)"}
    precision = cell.get("precision", "fp32")
    optim = OptimConfig(lr=1e-3,
                        batch_size=int(cell.get("batch", meta["batch"])),
                        epochs=1, precision=precision,
                        fused_update=bool(cell.get("fused_update",
                                                   False)))
    cfg = ExperimentConfig(
        model=meta["model"], num_classes=1, algorithm="fedavg",
        data=DataConfig(dataset="synthetic"), optim=optim,
        fed=FedConfig(client_num_in_total=meta["clients"],
                      comm_round=meta["rounds"],
                      client_mesh=cm,
                      frequency_of_the_test=10 ** 9),
        log_dir="/tmp/nidt_profile", tag=f"probe-{probe.name}")
    trainer = LocalTrainer(
        create_model(meta["model"], num_classes=1,
                     dtype=compute_dtype(precision),
                     remat=remat_policy(cell.get("remat", False))),
        optim, num_classes=1)
    mesh = make_mesh(num_devices=cm) if cm > 1 else None
    engine = create_engine("fedavg", cfg, fed, trainer, logger=log,
                           mesh=mesh)
    # a probe never inherits its predecessor's MFU/TFLOPs samples: a
    # cell whose run closes no boundary must report None, not a stale
    # number in a committed artifact
    obs_compute.PROFILER.clear_samples()
    t0 = time.perf_counter()
    result = engine.train()
    wall = time.perf_counter() - t0
    prof = obs_compute.PROFILER.snapshot()
    hist = result.get("history") or [{}]
    return {
        "config": cell,
        "ran": True,
        "skip_reason": None,
        "wall_s": round(wall, 4),
        "round_ms": round(wall / meta["rounds"] * 1e3, 2),
        "dispatches": int(engine.program.dispatches),
        "compiles": int(engine.program.built),
        "sustained_tflops": prof.get("last_sustained_tflops"),
        "mfu": prof.get("last_mfu"),
        "train_loss_final": hist[-1].get("train_loss"),
    }


def _scrape(port: int) -> tuple[bool, bool]:
    """(metrics_scrape_ok, healthz_compute_ok): a REAL HTTP scrape of
    the live endpoint — the structural proof ``nidt_dispatch_ms`` /
    ``nidt_sustained_tflops``/``nidt_mfu`` and the ``/healthz`` compute
    block actually serve (the CI smoke the ISSUE names)."""
    from urllib.request import urlopen

    from neuroimagedisttraining_tpu.obs import names as obs_names

    try:
        body = urlopen(f"http://127.0.0.1:{port}/metrics",
                       timeout=5).read().decode()
        # _bucket is the Prometheus exposition suffix of the histogram
        metrics_ok = (obs_names.DISPATCH_MS + "_bucket" in body
                      and obs_names.COMPILES_TOTAL in body
                      and (obs_names.SUSTAINED_TFLOPS in body
                           or obs_names.MFU in body))
        health = json.loads(urlopen(
            f"http://127.0.0.1:{port}/healthz", timeout=5).read())
        comp = health.get("compute") or {}
        health_ok = (comp.get("dispatches", 0) > 0
                     and comp.get("compiles", 0) > 0)
        return bool(metrics_ok), bool(health_ok)
    except Exception:  # noqa: BLE001 — the artifact records the failure
        return False, False


def run_session(manifest: tuple[Probe, ...], out_path: str,
                trace_out: str = "") -> dict:
    """The whole session: arm the obs plane, run every probe through
    the shipped driver, reconcile the XLA/analytic cost models once,
    self-scrape the live endpoint, write the artifact."""
    import jax

    from neuroimagedisttraining_tpu.core.optim import compute_dtype
    from neuroimagedisttraining_tpu.core.trainer import LocalTrainer
    from neuroimagedisttraining_tpu.config import OptimConfig
    from neuroimagedisttraining_tpu.models import create_model
    from neuroimagedisttraining_tpu.obs import compute as obs_compute
    from neuroimagedisttraining_tpu.obs import trace as obs_trace
    from neuroimagedisttraining_tpu.obs.http import MetricsServer
    from neuroimagedisttraining_tpu.utils.logging import ExperimentLogger

    meta = _env_meta()
    log = ExperimentLogger("/tmp/nidt_profile", "synthetic",
                           "profile_session", console=False)
    if trace_out:
        obs_trace.arm(trace_out, tags={"session": "profile"})
    srv = MetricsServer(
        0, health_probe=lambda: {
            "compute": obs_compute.PROFILER.health()})
    fed = _make_fed(meta)
    probes: dict[str, dict] = {}
    completed = 0
    t0 = time.perf_counter()
    try:
        for probe in manifest:
            print(f"[profile] probe {probe.name}: {probe.cell}",
                  flush=True)
            try:
                probes[probe.name] = run_probe(probe, meta, fed, log)
            except Exception as e:  # noqa: BLE001 — one blown probe
                # (flagship OOM mid-TPU-session) must not lose the
                # completed probes' results: record, continue, and the
                # probes_completed < n_probes verdict fails the session
                probes[probe.name] = {
                    "config": dict(probe.cell), "ran": False,
                    "skip_reason": f"error: {type(e).__name__}: {e}"}
            if probes[probe.name]["ran"]:
                completed += 1
            else:
                print(f"[profile]   skipped: "
                      f"{probes[probe.name]['skip_reason']}", flush=True)

        # cost-model reconciliation, once per session at the session
        # shape (compile=True: the memory_analysis bytes ride the
        # artifact; the double compile is a session cost, never a
        # hot-path one)
        trainer = LocalTrainer(
            create_model(meta["model"], num_classes=1,
                         dtype=compute_dtype("fp32")),
            OptimConfig(lr=1e-3, batch_size=meta["batch"], epochs=1),
            num_classes=1)
        xla = obs_compute.analyze_train_step(
            trainer, tuple(meta["shape"]), meta["batch"], compile=True)
        metrics_ok, health_ok = _scrape(srv.port)
    finally:
        # the endpoint thread and the armed tracer must not outlive the
        # session, even when a probe or the reconciliation raises
        srv.close()
        if trace_out:
            obs_trace.dump()
            obs_trace.disarm()

    fingerprint = json.dumps({p.name: p.cell for p in manifest},
                             sort_keys=True)
    doc = {
        "metric": "profile_session",
        "meta": {
            **{k: (list(v) if isinstance(v, tuple) else v)
               for k, v in meta.items()},
            "device_kind": getattr(jax.devices()[0], "device_kind",
                                   "unknown"),
            "n_devices": len(jax.devices()),
            "peak_flops": obs_compute.peak_flops_estimate() or None,
            "jax": jax.__version__,
        },
        "probes": probes,
        "xla": {"train_step": xla},
        "session": {
            "n_probes": len(manifest),
            "probes_completed": completed,
            "structural_fingerprint": fingerprint,
            "metrics_scrape_ok": metrics_ok,
            "healthz_compute_ok": health_ok,
            "wall_s": round(time.perf_counter() - t0, 2),
        },
        "notes": (
            "Shipped-driver probes (engine.train()) with the dispatch-"
            "boundary profiler armed (obs/compute.py). Dispatch/compile "
            "counts and the scrape booleans are deterministic compile "
            "facts; wall and TFLOP/s cells drift with the box (the "
            "bench gate's 0.5/2.0 ratio tripwires apply); nidt_mfu "
            "publishes only where a device peak is known "
            "(NIDT_PEAK_FLOPS overrides). CPU-harness numbers are "
            "harness evidence — the flagship-shape TPU session exports "
            "PROFILE_MODEL/PROFILE_SHAPE/PROFILE_BATCH (PROFILE.md "
            "round 10)."),
    }
    d = os.path.dirname(out_path)
    if d:
        os.makedirs(d, exist_ok=True)
    with open(out_path, "w") as f:
        json.dump(doc, f, indent=1, sort_keys=True)
    print(f"[profile] session artifact: {out_path} "
          f"({completed}/{len(manifest)} probes, "
          f"scrape_ok={metrics_ok})", flush=True)
    return doc


def main(argv=None) -> int:
    ap = argparse.ArgumentParser(
        prog="python -m neuroimagedisttraining_tpu.obs.probe",
        description=__doc__.split("\n\n")[0])
    ap.add_argument("--out", type=str,
                    default="bench_matrix/profile_session.json",
                    help="artifact path (the committed cell lives at "
                         "bench_matrix/profile_session.json)")
    ap.add_argument("--manifest", type=str, default="",
                    help="JSON probe manifest replacing the default "
                         "list (a [{name, cell}] array)")
    ap.add_argument("--trace_out", type=str, default="",
                    help="also write the session's host-span Chrome "
                         "trace here")
    ap.add_argument("--virtual_devices", type=int, default=0,
                    help="provision N virtual CPU devices before the "
                         "first backend touch (arms the cohort_sharded "
                         "probe off-TPU)")
    args = ap.parse_args(argv)
    if args.virtual_devices:
        from neuroimagedisttraining_tpu.parallel.mesh import (
            provision_virtual_devices,
        )
        provision_virtual_devices(args.virtual_devices)
    import jax

    manifest = (load_manifest(args.manifest) if args.manifest
                else default_manifest(len(jax.devices())))
    doc = run_session(manifest, args.out, trace_out=args.trace_out)
    ok = session_ok(doc)
    return 0 if ok else 1


def session_ok(doc: dict) -> bool:
    """The push-button success contract: every declared probe ran AND
    both live-endpoint self-scrapes held (``/metrics`` samples and the
    ``/healthz`` compute block) — the exit-code mirror of the gate's
    structural cells, shared by this CLI and ``--profile_session``."""
    s = doc["session"]
    return bool(s["probes_completed"] == s["n_probes"]
                and s["metrics_scrape_ok"] and s["healthz_compute_ok"])


if __name__ == "__main__":
    sys.exit(main())
