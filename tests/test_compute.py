"""Compute-plane observability tests (ISSUE 14, obs/compute.py +
obs/probe.py).

Contracts:

(a) Cost-model parity: XLA ``cost_analysis()`` FLOPs of one lowered
    training step vs the analytic ``ops/flops.py`` counter at the
    FLAGSHIP AlexNet3D shape — fully abstract (nothing materialized,
    nothing compiled), pinned within the stated tolerance, discrepancy
    recorded rather than silently trusted either way.
(b) Dispatch accounting: every round-program invocation lands one
    ``nidt_dispatch_ms`` sample (compile-vs-execute phase split) and
    every build moves ``nidt_compiles_total`` in the SAME increment as
    ``program.built``; a rebuild of the same plan-cache key is a
    recompile — warning-logged and flight-recorded.
(c) Zero-sync / zero-perturbation: a profiler-armed round is BITWISE
    identical to a disarmed one (params and loss) — the profiler never
    touches a device buffer.
(d) MFU gauges: ``boundary()`` divides analytic FLOPs dispatched by
    synced boundary-to-boundary wall; ``nidt_mfu`` publishes only when
    a peak is known, ``nidt_sustained_tflops`` always.
(e) ``/healthz`` compute block: dispatch liveness over real HTTP.
(f) The declarative probe manifest validates its cells, and one probe
    runs end-to-end through the SHIPPED driver (the session smoke).
"""

import json
import logging
from urllib.request import urlopen

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from neuroimagedisttraining_tpu.config import (
    DataConfig, ExperimentConfig, FedConfig, OptimConfig,
)
from neuroimagedisttraining_tpu.core.trainer import LocalTrainer
from neuroimagedisttraining_tpu.data.federate import FederatedData
from neuroimagedisttraining_tpu.engines import create_engine
from neuroimagedisttraining_tpu.models import create_model
from neuroimagedisttraining_tpu.obs import compute as obs_compute
from neuroimagedisttraining_tpu.obs import flight as obs_flight
from neuroimagedisttraining_tpu.obs import metrics as obs_metrics
from neuroimagedisttraining_tpu.obs import probe as obs_probe
from neuroimagedisttraining_tpu.obs import trace as obs_trace
from neuroimagedisttraining_tpu.obs.http import MetricsServer
from neuroimagedisttraining_tpu.utils.logging import ExperimentLogger

FLAGSHIP_SHAPE = (121, 145, 121)


# ---------------------------------------------------------------------------
# (a) cost-model parity at the flagship shape
# ---------------------------------------------------------------------------


def test_flops_parity_flagship_alexnet3d():
    """XLA vs analytic FLOPs on the flagship AlexNet3D shape, abstract
    end to end on the CPU harness. Stated tolerance: the analytic
    3x-inference convention (the reference's, ops/flops.py) undercounts
    backward-pass transpose convs, so XLA reads ~1.1x at this shape —
    the pin brackets [0.8, 1.5] and the artifact carries the exact
    ratio."""
    trainer = LocalTrainer(create_model("3DCNN", num_classes=1),
                           OptimConfig(), num_classes=1)
    out = obs_compute.analyze_train_step(trainer, FLAGSHIP_SHAPE, 8,
                                         compile=False)
    assert out["xla_flops"] is not None and out["xla_flops"] > 0
    assert out["analytic_flops"] > 0
    assert out["parity_ratio"] is not None
    assert 0.8 <= out["parity_ratio"] <= 1.5, out
    # flagship-scale sanity: one step at b8 is tens of GFLOPs, not MFLOPs
    assert out["analytic_flops"] > 1e10
    # the reconciliation published as gauges (recorded, not trusted)
    snap = obs_metrics.REGISTRY.snapshot()
    assert "nidt_flops_parity_ratio" in snap
    assert "nidt_xla_flops" in snap


def test_analytic_flops_abstract_matches_concrete_callers():
    """The abstract path (eval_shape params) equals the number the
    engines' concrete-params call sites compute — the flops.py
    refactor (eval_shape args, not closure) changed nothing for them."""
    trainer = LocalTrainer(create_model("3dcnn_tiny", num_classes=1),
                           OptimConfig(), num_classes=1)
    shape = (12, 14, 12)
    abstract = obs_compute.analytic_sample_flops(trainer, shape)
    from neuroimagedisttraining_tpu.ops import flops as flops_ops

    cs = trainer.init_client_state(
        jax.random.key(0), jnp.zeros((1,) + shape, jnp.float32))
    concrete = flops_ops.count_training_flops_per_sample(
        trainer.model, cs.params,
        trainer._prep(jnp.zeros((1,) + shape, jnp.float32)))
    assert abstract == concrete


def test_lower_train_step_memory_analysis_smoke():
    """``compile=True`` adds the memory_analysis byte accounting on the
    tiny shape (backend-best-effort — assert the dict shape when the
    backend provides it)."""
    trainer = LocalTrainer(create_model("3dcnn_tiny", num_classes=1),
                           OptimConfig(), num_classes=1)
    out = obs_compute.analyze_train_step(trainer, (12, 14, 12), 4,
                                         compile=True)
    if out["memory"] is not None:
        assert set(out["memory"]) == {"temp_bytes", "argument_bytes",
                                      "output_bytes", "peak_bytes"}
        assert out["memory"]["peak_bytes"] >= out["memory"]["temp_bytes"]
        hbm = obs_metrics.REGISTRY.snapshot().get("nidt_hbm_peak_bytes")
        assert hbm is not None and len(hbm["values"]) >= 4


# ---------------------------------------------------------------------------
# engine harness (tiny, bench-cell construction)
# ---------------------------------------------------------------------------


def _tiny_engine(tmp_path, tag, rounds=2):
    cfg = ExperimentConfig(
        model="3dcnn_tiny", num_classes=1, algorithm="fedavg",
        data=DataConfig(dataset="synthetic"),
        optim=OptimConfig(lr=1e-3, batch_size=8, epochs=1),
        fed=FedConfig(client_num_in_total=2, comm_round=rounds,
                      frequency_of_the_test=10 ** 9),
        log_dir=str(tmp_path), tag=tag)
    kx, ky = jax.random.split(jax.random.key(3))
    X = jax.random.randint(kx, (2, 16, 12, 14, 12), 0, 255,
                           dtype=jnp.int32).astype(jnp.uint8)
    y = jax.random.randint(ky, (2, 16), 0, 2, dtype=jnp.int32)
    n = jnp.full((2,), 16, jnp.int32)
    fed = FederatedData(X_train=X, y_train=y, n_train=n,
                        X_test=X[:, :4], y_test=y[:, :4],
                        n_test=jnp.full((2,), 4, jnp.int32))
    trainer = LocalTrainer(create_model("3dcnn_tiny", num_classes=1),
                           cfg.optim, num_classes=1)
    log = ExperimentLogger(str(tmp_path), "synthetic", cfg.identity(),
                           console=False)
    eng = create_engine("fedavg", cfg, fed, trainer, logger=log)
    eng._donate = False  # tests replay state through the programs
    return eng


def _one_round(eng, params, bstats, r=0):
    sampled = jnp.asarray(eng.client_sampling(r))
    rngs = eng.per_client_rngs(r, np.arange(2))
    return eng._round_jit(params, bstats, eng.data, sampled, rngs,
                          eng.round_lr(r))


# ---------------------------------------------------------------------------
# (b) dispatch + compile accounting
# ---------------------------------------------------------------------------


def test_dispatch_histogram_and_compile_counter(tmp_path):
    eng = _tiny_engine(tmp_path, "acct")
    gs = eng.init_global_state()
    h0 = obs_compute.PROFILER.health()
    ctr0 = obs_compute.compiles_total(engine="fedavg", program="round")
    out = _one_round(eng, gs.params, gs.batch_stats)
    out = _one_round(eng, out[0], out[1], r=1)
    jax.block_until_ready(out[0])
    # counter moved with built — one measurement
    assert eng.program.built == 1
    assert obs_compute.compiles_total(engine="fedavg",
                                      program="round") - ctr0 == 1.0
    # two dispatches: one compile-phase, one execute-phase sample
    hist = obs_metrics.REGISTRY.snapshot()["nidt_dispatch_ms"]
    phases = {(v["labels"]["engine"], v["labels"]["phase"]):
              v["value"]["count"] for v in hist["values"]
              if v["labels"]["program"] == "round"}
    assert phases.get(("fedavg", "compile"), 0) >= 1
    assert phases.get(("fedavg", "execute"), 0) >= 1
    h1 = obs_compute.PROFILER.health()
    assert h1["dispatches"] >= h0["dispatches"] + 2
    assert h1["last_dispatch_age_s"] is not None
    assert h1["last_dispatch_age_s"] >= 0


def test_recompile_storm_warns_and_flight_records(tmp_path, caplog):
    eng = _tiny_engine(tmp_path, "storm")
    obs_flight.clear()
    prog = eng.program
    with caplog.at_level(logging.WARNING,
                         logger="neuroimagedisttraining_tpu.obs"):
        prog._note_build("round", ("round", None, None, False))
        prog._note_build("round", ("round", None, None, False))
    assert any("RECOMPILED" in r.message for r in caplog.records)
    kinds = [e["kind"] for e in obs_flight.events()]
    assert "recompile" in kinds
    rec = [e for e in obs_flight.events() if e["kind"] == "recompile"][0]
    assert rec["engine"] == "fedavg" and rec["program"] == "round"
    # distinct keys are specializations, not recompiles: no new warning
    n_warn = len([r for r in caplog.records if "RECOMPILED" in r.message])
    with caplog.at_level(logging.WARNING,
                         logger="neuroimagedisttraining_tpu.obs"):
        prog._note_build("round_sharded", ("round", 2, None, True))
    assert len([r for r in caplog.records
                if "RECOMPILED" in r.message]) == n_warn


# ---------------------------------------------------------------------------
# (c) armed == disarmed, bitwise
# ---------------------------------------------------------------------------


def test_profiler_armed_vs_disarmed_bitwise(tmp_path):
    """The acceptance pin: the profiler adds clock reads and registry
    mutations around the ENQUEUE — never a device touch — so the round
    is bitwise-identical armed vs disarmed (and the overhead rides the
    obs_overhead <= 2% cell, bench.py)."""
    eng_a = _tiny_engine(tmp_path, "armed")
    eng_d = _tiny_engine(tmp_path, "disarmed")
    gs_a = eng_a.init_global_state()
    gs_d = eng_d.init_global_state()
    obs_metrics.enable()
    obs_trace.arm(str(tmp_path / "t.json"))
    try:
        out_a = _one_round(eng_a, gs_a.params, gs_a.batch_stats)
        eng_a._flush_nonfinite(0)
    finally:
        obs_trace.disarm()
    obs_metrics.disable()
    try:
        out_d = _one_round(eng_d, gs_d.params, gs_d.batch_stats)
        eng_d._flush_nonfinite(0)
    finally:
        obs_metrics.enable()
    for a, d in zip(jax.tree.leaves(out_a[0]), jax.tree.leaves(out_d[0])):
        np.testing.assert_array_equal(np.asarray(a), np.asarray(d))
    assert float(out_a[2]) == float(out_d[2])


# ---------------------------------------------------------------------------
# (d) MFU / sustained-TFLOPs boundary math
# ---------------------------------------------------------------------------


def test_boundary_publishes_mfu_and_tflops():
    obs_compute.PROFILER.arm_model("unit", flops_per_round=2e9,
                                   peak_flops=1e12)
    obs_compute.note_dispatch("unit", "round", 0.001, rounds=3)
    mfu = obs_compute.boundary("unit")
    assert mfu is not None and 0 < mfu
    snap = obs_metrics.REGISTRY.snapshot()
    cells = {v["labels"]["engine"]: v["value"]
             for v in snap["nidt_mfu"]["values"]}
    assert cells["unit"] == pytest.approx(mfu)
    tf = {v["labels"]["engine"]: v["value"]
          for v in snap["nidt_sustained_tflops"]["values"]}
    # 3 rounds x 2 GFLOP over the measured wall; mfu = tflops*1e12/peak
    assert tf["unit"] * 1e12 / 1e12 == pytest.approx(mfu, rel=1e-6)
    h = obs_compute.PROFILER.health()
    assert h["last_mfu"] == pytest.approx(mfu)
    # empty window: no sample (no division by zero rounds)
    assert obs_compute.boundary("unit") is None
    # unarmed engines never publish
    assert obs_compute.boundary("someone-else") is None


def test_boundary_without_peak_publishes_tflops_only():
    obs_compute.PROFILER.arm_model("unit2", flops_per_round=1e9,
                                   peak_flops=0.0)
    obs_compute.note_dispatch("unit2", "round", 0.001, rounds=1)
    assert obs_compute.boundary("unit2") is None  # no peak -> no MFU
    h = obs_compute.PROFILER.health()
    assert h["last_sustained_tflops"] is not None
    assert h["last_mfu"] is None


def test_peak_flops_env_override(monkeypatch):
    monkeypatch.setenv("NIDT_PEAK_FLOPS", "123e12")
    assert obs_compute.peak_flops_estimate() == 123e12
    monkeypatch.setenv("NIDT_PEAK_FLOPS", "not-a-number")
    assert obs_compute.peak_flops_estimate() == 0.0  # cpu harness
    monkeypatch.delenv("NIDT_PEAK_FLOPS")
    assert obs_compute.peak_flops_estimate() == 0.0


@pytest.mark.parametrize("platform,kind,n,want", [
    ("cpu", "cpu", 8, 0.0),                    # no honest peak: by design
    ("tpu", "TPU v5 lite", 1, 197e12),
    ("tpu", "TPU v5 lite", 4, 4 * 197e12),
    ("tpu", "TPU v9 unheard-of", 1, None),     # an error naming the kind
])
def test_peak_flops_by_device_kind(monkeypatch, platform, kind, n, want):
    """A kind missing from the table is an error on an accelerator,
    never a silent 0.0 that unpublishes the MFU gauge."""
    import types

    monkeypatch.delenv("NIDT_PEAK_FLOPS", raising=False)
    devs = [types.SimpleNamespace(platform=platform, device_kind=kind)] * n
    monkeypatch.setattr(jax, "local_devices", lambda: devs)
    if want is None:
        with pytest.raises(ValueError, match="TPU v9 unheard-of"):
            obs_compute.peak_flops_estimate()
    else:
        assert obs_compute.peak_flops_estimate() == want


def test_set_peak_flops_override_sticks_across_arm():
    """--peak_flops must survive the engine's lazy arm_model (the CLI
    sets it before any dispatch)."""
    obs_compute.PROFILER.set_peak_flops(7e12)
    obs_compute.PROFILER.arm_model("unit3", flops_per_round=1e9)
    assert obs_compute.PROFILER.health()["peak_flops"] == 7e12


# ---------------------------------------------------------------------------
# (e) /healthz compute block over real HTTP
# ---------------------------------------------------------------------------


def test_healthz_compute_block_http(tmp_path):
    eng = _tiny_engine(tmp_path, "health")
    gs = eng.init_global_state()
    out = _one_round(eng, gs.params, gs.batch_stats)
    jax.block_until_ready(out[0])
    srv = MetricsServer(0, health_probe=lambda: {
        "compute": obs_compute.PROFILER.health()})
    try:
        doc = json.loads(urlopen(
            f"http://127.0.0.1:{srv.port}/healthz", timeout=5).read())
    finally:
        srv.close()
    assert doc["ok"] is True
    comp = doc["compute"]
    assert comp["dispatches"] >= 1
    assert comp["compiles"] >= 1
    assert comp["last_dispatch_age_s"] is not None
    assert "recompiles" in comp and "last_mfu" in comp


# ---------------------------------------------------------------------------
# (f) the declarative probe manifest + session driver
# ---------------------------------------------------------------------------


def test_probe_manifest_validates_cells(tmp_path):
    with pytest.raises(ValueError, match="unknown cell keys"):
        obs_probe.Probe("bad", {"not_a_knob": 1})
    man = tmp_path / "m.json"
    man.write_text(json.dumps(
        [{"name": "a", "cell": {"precision": "fp32"}}]))
    probes = obs_probe.load_manifest(str(man))
    assert probes[0].name == "a"
    assert probes[0].cell == {"precision": "fp32"}
    man.write_text("{}")
    with pytest.raises(ValueError, match="non-empty JSON list"):
        obs_probe.load_manifest(str(man))


def test_default_manifest_arms_sharded_probe_with_devices():
    names1 = [p.name for p in obs_probe.default_manifest(1)]
    names2 = [p.name for p in obs_probe.default_manifest(2)]
    assert "cohort_sharded" not in names1
    assert "cohort_sharded" in names2


def test_run_probe_shipped_driver(tmp_path, monkeypatch):
    """One probe through the SHIPPED driver (engine.train()) on the
    smoke shape: deterministic dispatch/compile counts + profiler
    samples in the cell (the tier-1 sibling of the slow full-session
    smoke)."""
    monkeypatch.setenv("PROFILE_ROUNDS", "2")
    meta = obs_probe._env_meta()
    fed = obs_probe._make_fed(meta)
    log = ExperimentLogger(str(tmp_path), "synthetic", "probe-t",
                           console=False)
    cell = obs_probe.run_probe(
        obs_probe.Probe("fp32_baseline", {"precision": "fp32"}),
        meta, fed, log)
    assert cell["ran"] is True
    assert cell["dispatches"] == 2  # one round program, two rounds
    assert cell["compiles"] == 1
    assert cell["wall_s"] > 0
    assert cell["sustained_tflops"] is not None


def test_run_probe_skips_unprovisionable_mesh(tmp_path, monkeypatch):
    monkeypatch.setenv("PROFILE_ROUNDS", "2")
    meta = obs_probe._env_meta()
    fed = obs_probe._make_fed(meta)
    log = ExperimentLogger(str(tmp_path), "synthetic", "probe-s",
                           console=False)
    cell = obs_probe.run_probe(
        obs_probe.Probe("cohort_sharded",
                        {"precision": "fp32", "client_mesh": 64}),
        meta, fed, log)
    assert cell["ran"] is False
    assert "64 devices" in cell["skip_reason"]


@pytest.mark.slow
def test_profile_session_end_to_end(tmp_path, monkeypatch):
    """The full push-button session on a 2-probe manifest: artifact
    schema, live /metrics self-scrape, healthz compute block, and the
    bench gate's spec paths all resolve against the fresh artifact."""
    monkeypatch.setenv("PROFILE_ROUNDS", "2")
    manifest = (
        obs_probe.Probe("fp32_baseline", {"precision": "fp32"}),
        obs_probe.Probe("bf16", {"precision": "bf16_mixed"}),
    )
    out = tmp_path / "profile_session.json"
    doc = obs_probe.run_session(manifest, str(out))
    assert out.exists()
    assert doc["session"]["probes_completed"] == 2
    assert doc["session"]["metrics_scrape_ok"] is True
    assert doc["session"]["healthz_compute_ok"] is True
    assert doc["xla"]["train_step"]["parity_ratio"] is not None
    # the gate resolves the fresh artifact's spec paths (self-diff:
    # fresh == committed == this artifact -> ratios 1.0, eq green)
    from neuroimagedisttraining_tpu.analysis import bench_gate

    res = bench_gate.gate(str(tmp_path), committed_dir=str(tmp_path),
                          artifacts=["profile_session.json"])
    assert res["verdict"] == "green", res
