"""Compute-plane observability: the dispatch-boundary profiler (ISSUE 14).

PRs 9/13 gave the CONTROL plane spans, a merged ``/metrics`` and a bench
regression gate; every COMPUTE-plane claim (the 0.25-MFU bf16 thesis,
the fused kernel's HBM win, the 7.7x cohort slope) still rested on
hand-timed ``device_get`` probes. This module instruments the dispatch
boundary itself — the host<->XLA seam Frostig et al. 2018 (PAPERS.md,
JAX/SysML) define as the only place host observability is meaningful
under asynchronous dispatch — with ZERO added device syncs:

- **per-dispatch wall** (``nidt_dispatch_ms{engine, program, phase}``
  histogram): ``time.perf_counter`` around each compiled-program
  invocation in ``engines/program.py``. Under async dispatch this
  measures the HOST side (trace + compile on the first call, enqueue
  thereafter) — the ``phase`` label carries the compile-vs-execute
  split, and a steady-state "execute" sample that suddenly reads
  compile-scale is itself the recompile signal.
- **recompile accounting** (``nidt_compiles_total{engine, program}``
  counter): every program build increments it — the same increment
  that feeds ``RoundProgram.built``, one measurement, not a second
  bookkeeping path (tests/test_program.py re-asserts the
  one-compiled-program-per-window pins through this counter). A
  rebuild of the SAME cache key mid-run (LRU thrash, a shape leak) is
  a recompile STORM: warning-logged (capped) and flight-recorded.
- **MFU / sustained-TFLOPs gauges** (``nidt_mfu{engine}``,
  ``nidt_sustained_tflops{engine}``): dispatched work is accumulated
  per dispatch as analytic training FLOPs (``ops/flops.py`` — exact
  for fixed shapes, free: one abstract ``eval_shape``) and divided by
  the wall between HOST BOUNDARIES (``publish_stat_info``, where the
  driver already blocks on device results) — never by enqueue time,
  which the async dispatch model makes meaningless, and never via an
  added sync. The MFU denominator is :func:`peak_flops_estimate`
  (device-kind table x local device count; ``NIDT_PEAK_FLOPS``
  overrides; unknown backends publish TFLOPs only).
- **XLA accounting reconciliation** (``nidt_xla_flops``,
  ``nidt_flops_parity_ratio``, ``nidt_hbm_peak_bytes{kind}``):
  :func:`analyze_train_step` AOT-lowers ONE training step at abstract
  shapes (``LocalTrainer.lower_train_step`` — nothing materialized,
  nothing executed), reads ``cost_analysis()`` FLOPs off the
  unoptimized HLO and reconciles them against the analytic counter;
  ``compile=True`` additionally compiles the step for
  ``memory_analysis()`` temp/argument/output bytes. Deliberately NOT
  on the hot path (the probe driver and the parity test call it).

The per-dispatch timing is always on, like the flight ring — two clock
reads and one histogram observe per dispatch is the whole cost, pinned
inside the ±2% ``obs_overhead`` acceptance (bench.py) — and the armed
vs disarmed round is bitwise-identical by construction: nothing here
touches a device buffer (tests/test_compute.py pins it).

``/healthz`` gains a ``compute`` block from :meth:`ComputeProfiler
.health` (last dispatch age, last MFU sample, compile/recompile
counts), so a WEDGED-dispatch federation (dispatch age grows, rounds
stall) is distinguishable from a merely slow one at the liveness probe.
"""

from __future__ import annotations

import logging
import os
import threading
import time
from typing import Any

from neuroimagedisttraining_tpu.obs import flight as obs_flight
from neuroimagedisttraining_tpu.obs import metrics as obs_metrics

__all__ = [
    "ComputeProfiler", "PROFILER", "note_compile", "note_dispatch",
    "boundary", "arm_model", "health", "compiles_total",
    "peak_flops_estimate", "analyze_train_step", "analytic_sample_flops",
]

log = logging.getLogger("neuroimagedisttraining_tpu.obs")

#: per-chip dense-matmul peaks (bf16/MXU for TPUs) by ``device_kind``
#: prefix — the MFU denominator. Per CHIP, multiplied by the local
#: device count at estimate time; ``NIDT_PEAK_FLOPS`` (total, flop/s)
#: overrides the table outright (and is the only route on CPU, where
#: no honest peak exists).
PEAK_FLOPS_BY_DEVICE_KIND: tuple[tuple[str, float], ...] = (
    ("TPU v2", 45e12),
    ("TPU v3", 123e12),
    ("TPU v4", 275e12),
    ("TPU v5 lite", 197e12),
    ("TPU v5e", 197e12),
    ("TPU v5p", 459e12),
    ("TPU v5", 459e12),
    ("TPU v6 lite", 918e12),
    ("TPU v6e", 918e12),
)

#: ``nidt_dispatch_ms`` buckets (milliseconds): sub-ms enqueues through
#: multi-minute flagship compiles
DISPATCH_MS_BUCKETS = (0.1, 0.25, 0.5, 1.0, 2.5, 5.0, 10.0, 25.0, 50.0,
                       100.0, 250.0, 500.0, 1000.0, 2500.0, 5000.0,
                       10000.0, 30000.0, 120000.0)

#: recompile warnings are capped per process (a storm should not also
#: be a log flood); the counter and flight ring keep the full count
_MAX_STORM_WARNINGS = 8


def peak_flops_estimate() -> float:
    """Total peak flop/s of the local devices for the MFU denominator:
    ``NIDT_PEAK_FLOPS`` env override (total, not per chip), else the
    device-kind table x local device count. The CPU has no honest peak
    and reads 0.0 by design (the MFU gauge stays unpublished and
    sustained TFLOPs carry the evidence); an ACCELERATOR whose kind is
    not in the table is an error that names the kind, never a silent
    "no MFU"."""
    env = os.environ.get("NIDT_PEAK_FLOPS", "")
    if env:
        try:
            return float(env)
        except ValueError:
            log.warning("NIDT_PEAK_FLOPS=%r is not a number; ignoring",
                        env)
    import jax

    devs = jax.local_devices()
    if devs[0].platform == "cpu":
        return 0.0
    kind = devs[0].device_kind
    for prefix, per_chip in PEAK_FLOPS_BY_DEVICE_KIND:
        if kind.startswith(prefix):
            return per_chip * len(devs)
    raise ValueError(
        f"no peak FLOP/s known for device_kind {kind!r}: add it to "
        "obs/compute.py PEAK_FLOPS_BY_DEVICE_KIND, or pass --peak_flops "
        "/ NIDT_PEAK_FLOPS (total flop/s across local devices)")


class ComputeProfiler:
    """Per-process dispatch-boundary accounting. One instance
    (:data:`PROFILER`) is fed by ``engines/program.py``'s dispatch
    wrappers and drained at engine host boundaries
    (``FederatedEngine.publish_stat_info``)."""

    def __init__(self):
        self._lock = threading.Lock()
        self.reset()

    def reset(self) -> None:
        """Back to cold state (tests; never called by shipped code)."""
        with getattr(self, "_lock", threading.Lock()):
            self._armed_engine: str | None = None
            self._flops_per_round = 0.0
            self._peak_flops = 0.0
            self._peak_override = 0.0
            self._total_compiles = 0
            self._total_recompiles = 0
            self._total_dispatches = 0
            self._storm_warnings = 0
            self._last_dispatch_mono: float | None = None
            self._last_compile_s: float | None = None
            self._boundary_mono: float | None = None
            self._rounds_pending = 0
            self._dispatch_s_pending = 0.0
            self._last_mfu: float | None = None
            self._last_tflops: float | None = None

    # ---------- arming (analytic FLOPs + peak) ----------

    def arm_model(self, engine: str, flops_per_round: float,
                  peak_flops: float | None = None) -> None:
        """Arm MFU accounting for ``engine``: ``flops_per_round`` is the
        analytic training-FLOPs estimate of ONE round at the nominal
        cohort (``FederatedEngine._arm_compute_profiler`` derives it
        from ``ops/flops.py``); ``peak_flops`` defaults to
        :func:`peak_flops_estimate`. Re-arming (a second engine in the
        same process) overwrites — the gauges are per-engine-labeled,
        the accumulator window is whoever armed last."""
        with self._lock:
            self._armed_engine = engine
            self._flops_per_round = float(flops_per_round)
            if peak_flops is not None:
                self._peak_flops = float(peak_flops)
            elif self._peak_override > 0:
                self._peak_flops = self._peak_override
            else:
                self._peak_flops = peak_flops_estimate()
            self._boundary_mono = time.monotonic()
            self._rounds_pending = 0
            self._dispatch_s_pending = 0.0
            # a fresh arm starts a fresh measurement: stale samples from
            # the PREVIOUS armed engine must not be read as this one's
            # (the probe driver snapshots after every probe — a probe
            # that never closes a boundary reports None, not its
            # predecessor's throughput)
            self._last_mfu = None
            self._last_tflops = None

    def clear_samples(self) -> None:
        """Drop the last MFU/TFLOPs samples without disarming — the
        probe driver calls this before each probe so a probe that never
        closes a boundary (arming failed, run too short) reports None
        instead of its predecessor's throughput."""
        with self._lock:
            self._last_mfu = None
            self._last_tflops = None

    def set_peak_flops(self, peak_flops: float) -> None:
        """CLI override (``--peak_flops``): sticks across later
        ``arm_model`` calls; 0 keeps the device-kind estimate."""
        if peak_flops and peak_flops > 0:
            with self._lock:
                self._peak_override = float(peak_flops)
                self._peak_flops = self._peak_override

    # ---------- the dispatch boundary (engines/program.py) ----------

    def note_compile(self, engine: str, program: str,
                     recompile: bool = False) -> None:
        """One program build. ``recompile=True`` marks a rebuild of the
        SAME cache key mid-run — the storm signal: counted, flight-
        recorded, warning-logged (capped). The counter increment IS the
        measurement ``RoundProgram.built`` mirrors (one bookkeeping
        path; tests/test_program.py pins them equal)."""
        obs_metrics.counter(
            "nidt_compiles_total",
            "compiled round-program builds by engine and program "
            "variant (engines/program.py); a variant compiling more "
            "than once mid-run is a recompile storm",
            labelnames=("engine", "program")).labels(
            engine=engine, program=program).inc()
        with self._lock:
            self._total_compiles += 1
            if recompile:
                self._total_recompiles += 1
                warn = self._storm_warnings < _MAX_STORM_WARNINGS
                self._storm_warnings += 1
                n = self._total_recompiles
        if recompile:
            # scrapeable storm evidence (ISSUE 15): the recompile-storm
            # anomaly rule (obs/rules.py) judges this counter — the
            # health() block alone is not a metric series a rule or a
            # Prometheus alert can watch
            obs_metrics.counter(
                "nidt_recompiles_total",
                "mid-run rebuilds of an already-built program variant "
                "(plan-cache thrash / shape leak — the recompile "
                "storm)",
                labelnames=("engine", "program")).labels(
                engine=engine, program=program).inc()
            obs_flight.record("recompile", engine=engine,
                              program=program, total=n)
            if warn:
                log.warning(
                    "compute: program %s/%s RECOMPILED mid-run "
                    "(recompile #%d this process) — a plan-cache "
                    "eviction or shape leak is paying a fresh XLA "
                    "compile on the hot path (nidt_compiles_total; "
                    "flight ring has the event)", engine, program, n)

    def note_dispatch(self, engine: str, program: str, dur_s: float,
                      rounds: int = 1, phase: str = "execute") -> None:
        """One compiled-program invocation: ``dur_s`` is host wall
        around the call (trace+compile on ``phase="compile"``, enqueue
        on ``"execute"`` — never device time, never a sync), ``rounds``
        the federated rounds the dispatch carries (K for fused
        windows) — the MFU numerator accumulates
        ``rounds * flops_per_round`` until the next boundary."""
        obs_metrics.histogram(
            "nidt_dispatch_ms",
            "host wall per compiled-program invocation at the dispatch "
            "boundary (obs/compute.py): trace+compile on "
            "phase=\"compile\", enqueue on phase=\"execute\" (async "
            "dispatch — device time lives on the XLA timeline)",
            labelnames=("engine", "program", "phase"),
            buckets=DISPATCH_MS_BUCKETS).labels(
            engine=engine, program=program, phase=phase).observe(
            dur_s * 1e3)
        with self._lock:
            self._total_dispatches += 1
            self._last_dispatch_mono = time.monotonic()
            if phase == "compile":
                self._last_compile_s = float(dur_s)
            if engine == self._armed_engine:
                self._rounds_pending += int(rounds)
                self._dispatch_s_pending += float(dur_s)

    def boundary(self, engine: str) -> float | None:
        """Close one boundary-to-boundary window and publish the
        derived gauges. Called from ``publish_stat_info`` — a host
        point where the driver ALREADY blocked on device results, so
        every dispatch accumulated since the last boundary has
        finished and ``flops / wall`` is an honest sustained rate.
        Returns the MFU sample (None when unarmed / unknown peak /
        empty window)."""
        now = time.monotonic()
        with self._lock:
            if engine != self._armed_engine or self._boundary_mono is None:
                return None
            wall = now - self._boundary_mono
            rounds = self._rounds_pending
            self._boundary_mono = now
            self._rounds_pending = 0
            self._dispatch_s_pending = 0.0
            if rounds <= 0 or wall <= 0 or self._flops_per_round <= 0:
                return None
            flops_s = rounds * self._flops_per_round / wall
            self._last_tflops = flops_s / 1e12
            mfu = (flops_s / self._peak_flops
                   if self._peak_flops > 0 else None)
            self._last_mfu = mfu
        obs_metrics.gauge(
            "nidt_sustained_tflops",
            "sustained analytic training TFLOP/s over the last host-"
            "boundary window (ops/flops.py numerator / synced wall)",
            labelnames=("engine",)).labels(engine=engine).set(
            self._last_tflops)
        if mfu is not None:
            obs_metrics.gauge(
                "nidt_mfu",
                "model FLOPs utilization over the last host-boundary "
                "window: analytic training FLOP/s over the device "
                "peak (obs/compute.peak_flops_estimate; "
                "NIDT_PEAK_FLOPS / --peak_flops override)",
                labelnames=("engine",)).labels(engine=engine).set(mfu)
        return mfu

    # ---------- liveness (the /healthz compute block) ----------

    def health(self) -> dict:
        """The ``/healthz`` ``compute`` block: a wedged-dispatch
        federation shows a growing ``last_dispatch_age_s`` with stalled
        dispatch/compile counts; a slow one keeps the age bounded."""
        with self._lock:
            age = (None if self._last_dispatch_mono is None
                   else round(time.monotonic() - self._last_dispatch_mono,
                              3))
            return {
                "last_dispatch_age_s": age,
                "dispatches": self._total_dispatches,
                "compiles": self._total_compiles,
                "recompiles": self._total_recompiles,
                "last_compile_s": self._last_compile_s,
                "last_mfu": self._last_mfu,
                "last_sustained_tflops": self._last_tflops,
                "peak_flops": self._peak_flops or None,
                "armed_engine": self._armed_engine,
            }

    def snapshot(self) -> dict:
        """Artifact-facing state (the profile-session driver records
        it per probe)."""
        h = self.health()
        h.pop("last_dispatch_age_s", None)
        return h


#: the process-global profiler every dispatch wrapper feeds
PROFILER = ComputeProfiler()

#: module-level conveniences (instrumentation-site spelling)
note_compile = PROFILER.note_compile
note_dispatch = PROFILER.note_dispatch
boundary = PROFILER.boundary
arm_model = PROFILER.arm_model
health = PROFILER.health


def compiles_total(engine: str | None = None,
                   program: str | None = None) -> float:
    """Sum of ``nidt_compiles_total`` cells matching the filters — the
    single-measurement read the compiled-programs-per-window pins use
    (tests/test_program.py)."""
    snap = obs_metrics.REGISTRY.snapshot().get("nidt_compiles_total")
    if not snap:
        return 0.0
    total = 0.0
    for cell in snap["values"]:
        lb = cell["labels"]
        if engine is not None and lb.get("engine") != engine:
            continue
        if program is not None and lb.get("program") != program:
            continue
        total += float(cell["value"])
    return total


# ---------------------------------------------------------------------------
# XLA cost/memory accounting (AOT — the probe driver and parity test)
# ---------------------------------------------------------------------------


def _flops_sample_struct(trainer, input_shape: tuple[int, ...]):
    """Abstract ``[1, *spatial(, C)]`` sample at the shape the model
    applies (mirrors ``LocalTrainer._prep``'s channel completion
    without touching a real array)."""
    import jax
    import jax.numpy as jnp

    shape = (1, *input_shape)
    rank = getattr(trainer.model, "input_rank", None)
    if rank is not None and len(shape) == rank - 1:
        shape = shape + (1,)
    return jax.ShapeDtypeStruct(shape, jnp.float32)


def analytic_sample_flops(trainer, input_shape: tuple[int, ...],
                          mask_density: dict | None = None) -> float:
    """Analytic training FLOPs per sample (``ops/flops.py``: 3x
    inference, exact for fixed shapes) — computed fully abstractly:
    params come from an ``eval_shape`` of the model init, so nothing is
    materialized even at the flagship 121x145x121 volume."""
    import jax
    import jax.numpy as jnp

    from neuroimagedisttraining_tpu.ops import flops as flops_ops

    cs = jax.eval_shape(
        trainer.init_client_state, jax.random.key(0),
        jax.ShapeDtypeStruct((1, *input_shape), jnp.float32))
    return flops_ops.count_training_flops_per_sample(
        trainer.model, cs.params, _flops_sample_struct(trainer,
                                                       input_shape),
        mask_density=mask_density)


def analyze_train_step(trainer, input_shape: tuple[int, ...],
                       batch_size: int, *, compile: bool = False,
                       publish: bool = True) -> dict:
    """XLA's own accounting of ONE training step, reconciled against
    the analytic counter. AOT and abstract: ``cost_analysis()`` reads
    the unoptimized HLO of ``LocalTrainer.lower_train_step`` (no
    params, no compile, no execution — safe at flagship shape on the
    CPU harness); ``compile=True`` additionally compiles the step and
    reads ``memory_analysis()`` temp/argument/output bytes (the
    working set the remat policy trades against — backend-best-effort,
    None where unsupported).

    Returns ``{"xla_flops", "analytic_flops", "parity_ratio",
    "batch_size", "memory"}`` and (``publish=True``) mirrors them as
    ``nidt_xla_flops`` / ``nidt_flops_parity_ratio`` /
    ``nidt_hbm_peak_bytes{kind}`` gauges. The discrepancy is RECORDED,
    not resolved: the analytic 3x-inference convention undercounts
    backward-pass transpose convs at flagship shape (~1.1x there) and
    overcounts dense-dominated tiny shapes (~0.9x) — the profile
    artifact carries the ratio so neither counter is silently
    trusted."""
    lowered = trainer.lower_train_step(input_shape, batch_size)
    xla_flops = None
    try:
        ca = lowered.cost_analysis()
        if isinstance(ca, (list, tuple)):
            ca = ca[0] if ca else {}
        xla_flops = float(ca.get("flops", 0.0)) or None
    except Exception as e:  # noqa: BLE001 — backend-best-effort surface
        log.info("compute: cost_analysis unavailable (%s)", e)
    analytic = analytic_sample_flops(trainer, input_shape) * batch_size
    ratio = (xla_flops / analytic
             if xla_flops and analytic > 0 else None)
    mem: dict[str, int] | None = None
    if compile:
        try:
            ma = lowered.compile().memory_analysis()
            mem = {
                "temp_bytes": int(getattr(ma, "temp_size_in_bytes", 0)),
                "argument_bytes": int(
                    getattr(ma, "argument_size_in_bytes", 0)),
                "output_bytes": int(
                    getattr(ma, "output_size_in_bytes", 0)),
            }
            mem["peak_bytes"] = (mem["temp_bytes"]
                                 + mem["argument_bytes"]
                                 + mem["output_bytes"])
        except Exception as e:  # noqa: BLE001 — backend-best-effort
            log.info("compute: memory_analysis unavailable (%s)", e)
            mem = None
    out = {
        "batch_size": int(batch_size),
        "xla_flops": xla_flops,
        "analytic_flops": analytic,
        "parity_ratio": round(ratio, 4) if ratio is not None else None,
        "memory": mem,
    }
    if publish:
        if xla_flops is not None:
            obs_metrics.gauge(
                "nidt_xla_flops",
                "XLA cost_analysis FLOPs of one lowered training step "
                "(obs/compute.analyze_train_step)").set(xla_flops)
        if ratio is not None:
            obs_metrics.gauge(
                "nidt_flops_parity_ratio",
                "XLA cost_analysis FLOPs over the analytic "
                "ops/flops.py count for one training step (the "
                "recorded-not-trusted reconciliation)").set(ratio)
        if mem is not None:
            g = obs_metrics.gauge(
                "nidt_hbm_peak_bytes",
                "XLA memory_analysis bytes of one compiled training "
                "step by kind (temp = activation working set, the "
                "number remat trades against)",
                labelnames=("kind",))
            for kind in ("temp_bytes", "argument_bytes",
                         "output_bytes", "peak_bytes"):
                g.labels(kind=kind.removesuffix("_bytes")).set(
                    mem[kind])
    return out
