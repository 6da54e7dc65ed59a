"""Training-health plane tests (ISSUE 15).

Four contracts:

(a) The in-dispatch stats leg is value-transparent: a round with
    ``--health_stats`` armed produces BITWISE-identical
    params/batch_stats to a disarmed round, at the same
    compiled-program/dispatch counts (no added device syncs) — and the
    armed leg composes with fused K-windows and cohort sharding at the
    same bitwise pins those planes carry.
(b) The anomaly-rule engine's full matrix: every comparator, window
    aggregation, severity, debounce path, label-subset selection
    (worker labels included), histogram p99 evaluation, NaN semantics,
    startup validation against the declared-name set, JSON manifests.
(c) The seeded divergence scenario: a 1-of-4 sign-flip silo fires the
    client-divergence rule (nidt_alert sample, flight ``alert`` event,
    critical health block, nonzero --health_gate exit) while the clean
    twin stays green; run_report joins both runs into artifacts that
    differ in the alert timeline.
(d) The health-rule-discipline lint family: metric-name literals
    outside obs/ are findings; constants and obs/-internal literals
    are clean.
"""

import json
import os

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from neuroimagedisttraining_tpu.analysis import lint_source
from neuroimagedisttraining_tpu.analysis.run_report import (
    build_report, read_metrics_jsonl, render_markdown,
)
from neuroimagedisttraining_tpu.analysis.run_report import (
    main as run_report_main,
)
from neuroimagedisttraining_tpu.config import (
    DataConfig, ExperimentConfig, FedConfig, OptimConfig,
)
from neuroimagedisttraining_tpu.core.trainer import LocalTrainer
from neuroimagedisttraining_tpu.data.federate import federate_cohort
from neuroimagedisttraining_tpu.data.synthetic import (
    generate_synthetic_abcd,
)
from neuroimagedisttraining_tpu.engines import create_engine
from neuroimagedisttraining_tpu.engines import program as round_program
from neuroimagedisttraining_tpu.models import create_model
from neuroimagedisttraining_tpu.obs import flight as obs_flight
from neuroimagedisttraining_tpu.obs import health as obs_health
from neuroimagedisttraining_tpu.obs import metrics as obs_metrics
from neuroimagedisttraining_tpu.obs import names as N
from neuroimagedisttraining_tpu.obs import rules as obs_rules
from neuroimagedisttraining_tpu.obs.rules import HealthRule, RuleEngine
from neuroimagedisttraining_tpu.parallel.mesh import make_mesh
from neuroimagedisttraining_tpu.utils.logging import ExperimentLogger


@pytest.fixture(scope="module")
def cohort64():
    """64 subjects over 4 sites: enough shared signal that honest site
    updates cohere (clean leave-one-out cosines land ~ +0.2..+0.4),
    which is what separates a sign-flip silo from ordinary non-IID
    noise."""
    return generate_synthetic_abcd(num_subjects=64, shape=(12, 14, 12),
                                   num_sites=4, seed=0)


def _engine(tmp_path, cohort, algorithm="fedavg", health=True,
            comm_round=2, freq=2, client_mesh=0, tag="h", seed=1024,
            metrics_out="", **fed_kw):
    cfg = ExperimentConfig(
        model="3dcnn_tiny", num_classes=1, algorithm=algorithm,
        seed=seed,
        data=DataConfig(dataset="synthetic", partition_method="site"),
        optim=OptimConfig(lr=1e-3, batch_size=8, epochs=1),
        fed=FedConfig(client_num_in_total=4, comm_round=comm_round,
                      frequency_of_the_test=freq, client_mesh=client_mesh,
                      **fed_kw),
        log_dir=str(tmp_path), tag=tag, health_stats=health,
        metrics_out=metrics_out)
    mesh = make_mesh()
    trainer = LocalTrainer(create_model(cfg.model, num_classes=1),
                           cfg.optim, num_classes=1)
    log = ExperimentLogger(str(tmp_path), "synthetic", cfg.identity(),
                           console=False)
    fed, _ = federate_cohort(cohort, partition_method="site", mesh=mesh)
    return create_engine(algorithm, cfg, fed, trainer, mesh=mesh,
                         logger=log)


def _bitwise(a, b):
    la, lb = jax.tree.leaves(a), jax.tree.leaves(b)
    assert len(la) == len(lb)
    for x, y in zip(la, lb):
        np.testing.assert_array_equal(np.asarray(x), np.asarray(y))


def _gauge_value(name, **labels):
    snap = obs_metrics.REGISTRY.snapshot().get(name)
    assert snap is not None, f"{name} not in registry"
    for cell in snap["values"]:
        if all(cell["labels"].get(k) == v for k, v in labels.items()):
            return cell["value"]
    raise AssertionError(f"{name}: no cell with {labels}: {snap}")


# ---------------------------------------------------------------------------
# (a) the in-dispatch stats leg
# ---------------------------------------------------------------------------


def test_update_stats_match_numpy_reference():
    """The traced stat math vs a straight numpy reimplementation —
    norms, leave-one-out cosine, dispersion, global norms."""
    rng = np.random.default_rng(3)
    C = 4
    up = {"params": {"w": jnp.asarray(rng.normal(size=(C, 5, 3)),
                                      jnp.float32)},
          "batch_stats": {}}
    ref = {"params": {"w": jnp.asarray(rng.normal(size=(5, 3)),
                                       jnp.float32)}, "batch_stats": {}}
    new = {"w": jnp.asarray(rng.normal(size=(5, 3)), jnp.float32)}
    w = jnp.asarray([3.0, 1.0, 2.0, 2.0], jnp.float32)
    out = round_program.health_update_stats(up, ref, new, w)

    d = (np.asarray(up["params"]["w"])
         - np.asarray(ref["params"]["w"])[None]).reshape(C, -1)
    agg = (np.asarray(new["w"])
           - np.asarray(ref["params"]["w"])).reshape(-1)
    norms = np.linalg.norm(d, axis=1)
    p = np.asarray(w) / np.sum(np.asarray(w))
    cos = np.empty(C)
    for i in range(C):
        loo = agg - p[i] * d[i]
        cos[i] = d[i] @ loo / (norms[i] * np.linalg.norm(loo))
    np.testing.assert_allclose(np.asarray(out["h_up_norms"]), norms,
                               rtol=1e-5)
    np.testing.assert_allclose(float(out["h_cos_min"]), cos.min(),
                               rtol=1e-4)
    np.testing.assert_allclose(float(out["h_cos_mean"]), cos.mean(),
                               rtol=1e-4)
    np.testing.assert_allclose(float(out["h_disp"]),
                               norms.max() / np.median(norms),
                               rtol=1e-5)
    np.testing.assert_allclose(float(out["h_agg_up"]),
                               np.linalg.norm(agg), rtol=1e-5)
    np.testing.assert_allclose(
        float(out["h_gnorm"]),
        np.linalg.norm(np.asarray(new["w"]).ravel()), rtol=1e-5)


def test_mask_health_stats():
    old = {"w": jnp.asarray([[1, 1, 1, 0], [1, 1, 0, 0]], jnp.float32)}
    new = {"w": jnp.asarray([[1, 1, 0, 0], [1, 0, 0, 0]], jnp.float32)}
    out = round_program.mask_health_stats(new, old)
    assert float(out["h_mask_density"]) == pytest.approx(3 / 8)
    assert float(out["h_mask_overlap"]) == pytest.approx(3 / 5)
    assert float(out["h_mask_churn"]) == pytest.approx(2 / 5)
    static = round_program.mask_health_stats(new, None)
    assert float(static["h_mask_overlap"]) == 1.0
    assert float(static["h_mask_churn"]) == 0.0


def test_armed_vs_disarmed_bitwise_same_counts(tmp_path, cohort64):
    """The acceptance pin: armed rounds are bitwise-identical to
    disarmed rounds at the SAME compiled-program and dispatch counts
    (the health leg adds outputs, never syncs or dispatches)."""
    off = _engine(tmp_path, cohort64, health=False, tag="off")
    on = _engine(tmp_path, cohort64, health=True, tag="on")
    r_off = off.train()
    r_on = on.train()
    _bitwise(r_off["params"], r_on["params"])
    _bitwise(r_off["batch_stats"], r_on["batch_stats"])
    assert [h["train_loss"] for h in r_off["history"]] == \
        [h["train_loss"] for h in r_on["history"]]
    assert on.program.built == off.program.built
    assert on.program.dispatches == off.program.dispatches
    # and the armed run actually published the health series
    assert _gauge_value(N.HEALTH_COSINE_MIN, engine="fedavg") is not None
    assert _gauge_value(N.HEALTH_ROUND, engine="fedavg") == 1.0


def test_four_rounds_queue_one_unstacked_entry_each(tmp_path, cohort64):
    """Four rounds between two host boundaries: armed, every dispatch
    queues ONE entry of that round's own statistics (scalars and [C]
    vectors, no leading round axis) without touching the carried state
    (bitwise the disarmed rounds'), and the flush publishes them round
    by round, indices reconstructed backward from the flush round."""
    def four_rounds(eng):
        gs = eng.init_global_state()
        p, b = gs.params, gs.batch_stats
        for r in range(4):
            sampled = eng.client_sampling(r)
            p, b, _, _ = eng._round_jit(
                p, b, eng.data, jnp.asarray(sampled),
                eng.per_client_rngs(r, sampled), eng.round_lr(r))
            yield p, b

    off = _engine(tmp_path, cohort64, health=False, comm_round=4,
                  freq=4, frac=0.5, tag="q0")
    on = _engine(tmp_path, cohort64, health=True, comm_round=4, freq=4,
                 frac=0.5, tag="q1")
    for r, (s_off, s_on) in enumerate(zip(four_rounds(off),
                                          four_rounds(on))):
        assert len(on._health_pending) == r + 1
        assert off._health_pending == []
    entry = on._health_pending[-1]
    assert set(entry) == set(on.program.health_names)
    assert entry["h_gnorm"].shape == ()
    assert entry["h_up_norms"].shape == (2,)  # this round's two clients
    _bitwise(s_off, s_on)
    on._flush_nonfinite(3)
    assert on._health_pending == []
    assert sorted(on._last_health_rows) == [0, 1, 2, 3]
    assert _gauge_value(N.HEALTH_ROUND, engine="fedavg") == 3.0


def test_sharded_with_health_armed(tmp_path, cohort64):
    """Cohort-sharding composition: arming the stats leg changes
    NOTHING on the sharded path (bitwise vs the disarmed sharded
    round), and the sharded-vs-sequential pin holds with health armed
    at the cohort plane's own tolerance (the ~1-ulp compile-context
    residue, tests/test_cohort.py — sharded is not bitwise vs
    sequential even without health)."""
    sh_off = _engine(tmp_path, cohort64, health=False, client_mesh=8,
                     tag="shoff").train()
    shr = _engine(tmp_path, cohort64, health=True, client_mesh=8,
                  tag="shr")
    sh_on = shr.train()
    _bitwise(sh_off["params"], sh_on["params"])
    _bitwise(sh_off["batch_stats"], sh_on["batch_stats"])
    seq = _engine(tmp_path, cohort64, health=True, client_mesh=8,
                  tag="seq")
    seq._cohort_sequential = True
    rs = seq.train()
    for x, y in zip(jax.tree.leaves(rs["params"]),
                    jax.tree.leaves(sh_on["params"])):
        np.testing.assert_allclose(np.asarray(x, np.float64),
                                   np.asarray(y, np.float64),
                                   rtol=1e-6, atol=1e-8)


def test_metrics_jsonl_sink_round_seq(tmp_path, cohort64):
    """ISSUE 15 satellite: one JSONL record per round with monotonic
    round/seq join keys, health gauges inside."""
    path = str(tmp_path / "m.jsonl")
    _engine(tmp_path, cohort64, health=True, comm_round=3, freq=1,
            tag="sink", metrics_out=path).train()
    recs = read_metrics_jsonl(path)
    assert [r["round"] for r in recs] == [0, 1, 2]
    assert [r["seq"] for r in recs] == [1, 2, 3]
    assert all(r["engine"] == "fedavg" for r in recs)
    snap = recs[-1]["metrics"]
    assert N.HEALTH_COSINE_MIN in snap
    assert N.STAT in snap


def test_subavg_mask_health_stats(tmp_path, cohort64):
    _engine(tmp_path, cohort64, algorithm="subavg", health=True,
            comm_round=1, freq=1, tag="sub").train()
    dens = _gauge_value(N.HEALTH_MASK_DENSITY, engine="subavg")
    churn = _gauge_value(N.HEALTH_MASK_CHURN, engine="subavg")
    assert 0.0 <= dens <= 1.0
    assert 0.0 <= churn <= 1.0


def test_mask_density_publishes_from_nnz_boundary(tmp_path, cohort64):
    """dispfl-style engines publish density from the existing
    warn_if_masks_collapsed nnz fetch (no new sync)."""
    eng = _engine(tmp_path, cohort64, health=False, tag="nnz")
    masks = {"w": jnp.ones((4, 10), jnp.float32).at[:, 5:].set(0.0)}
    nnz = eng.warn_if_masks_collapsed(masks, round_idx=7)
    assert (nnz == 5).all()
    assert _gauge_value(N.HEALTH_MASK_DENSITY,
                        engine="fedavg") == pytest.approx(0.5)


# ---------------------------------------------------------------------------
# (b) the rule-engine matrix
# ---------------------------------------------------------------------------


def _snap(value, metric=N.HEALTH_COSINE_MIN, kind="gauge", labels=None):
    return {metric: {"kind": kind, "help": "",
                     "values": [{"labels": labels or {},
                                 "value": value}]}}


def _rule(**kw):
    base = dict(name="r", metric=N.HEALTH_COSINE_MIN, op="<",
                threshold=-0.2)
    base.update(kw)
    return HealthRule(**base)


def test_rule_validation_matrix():
    with pytest.raises(ValueError, match="unknown metric"):
        RuleEngine([_rule(metric="nidt_not_a_thing")])
    with pytest.raises(ValueError, match="comparator"):
        RuleEngine([_rule(op="~")])
    with pytest.raises(ValueError, match="window"):
        RuleEngine([_rule(window="p50")])
    with pytest.raises(ValueError, match="severity"):
        RuleEngine([_rule(severity="fatal")])
    with pytest.raises(ValueError, match="aggregation"):
        RuleEngine([_rule(agg="median")])
    with pytest.raises(ValueError, match=">= 1"):
        RuleEngine([_rule(for_rounds=0)])
    with pytest.raises(ValueError, match="delta"):
        RuleEngine([_rule(window="delta", n=1)])
    with pytest.raises(ValueError, match="declared twice"):
        RuleEngine([_rule(), _rule()])
    # the error names the known-names list
    try:
        RuleEngine([_rule(metric="nidt_zzz")])
    except ValueError as e:
        assert N.HEALTH_COSINE_MIN in str(e)


@pytest.mark.parametrize("op,value,thr,fires", [
    (">", 2.0, 1.0, True), (">", 1.0, 1.0, False),
    (">=", 1.0, 1.0, True), ("<", 0.5, 1.0, True),
    ("<", 1.5, 1.0, False), ("<=", 1.0, 1.0, True),
    ("==", 3.0, 3.0, True), ("==", 3.1, 3.0, False),
    ("!=", 3.1, 3.0, True), ("!=", 3.0, 3.0, False),
])
def test_comparator_matrix(op, value, thr, fires):
    eng = RuleEngine([_rule(op=op, threshold=thr)])
    eng.observe(0, _snap(value))
    assert eng.health_block()["firing"] == ({"r": "warn"} if fires
                                            else {})


def test_nan_never_fires():
    for op in obs_rules.OPS:
        eng = RuleEngine([_rule(op=op, threshold=0.0)])
        eng.observe(0, _snap(float("nan")))
        assert eng.health_block()["status"] == "ok", op


def test_window_aggregations():
    vals = [1.0, 5.0, 3.0]
    for window, expect in (("last", 3.0), ("mean", 3.0), ("max", 5.0),
                           ("min", 1.0), ("delta", 2.0)):
        eng = RuleEngine([_rule(op="==", threshold=expect,
                                window=window, n=3)])
        for r, v in enumerate(vals):
            eng.observe(r, _snap(v))
        assert eng.health_block()["firing"], window


def test_debounce_for_rounds_and_clear():
    eng = RuleEngine([_rule(for_rounds=2)])
    eng.observe(0, _snap(-0.5))
    assert eng.health_block()["status"] == "ok"  # 1 of 2
    eng.observe(1, _snap(-0.5))
    assert eng.health_block()["status"] == "degraded"  # debounced fire
    eng.observe(2, _snap(0.5))
    assert eng.health_block()["status"] == "ok"  # cleared
    assert eng.health_block()["worst_status"] == "degraded"  # sticky
    v = eng.verdict()
    assert v["alerts_total"] == 1
    kinds = [e["kind"] for e in v["timeline"]]
    assert kinds == ["alert", "alert_clear"]
    assert [e["round"] for e in v["timeline"]] == [1, 2]


def test_missing_metric_resets_debounce():
    eng = RuleEngine([_rule(for_rounds=2)])
    eng.observe(0, _snap(-0.5))
    eng.observe(1, {})  # no samples: not an anomaly, debounce resets
    eng.observe(2, _snap(-0.5))
    assert eng.health_block()["status"] == "ok"


def test_severity_critical_and_rounds_dedupe():
    eng = RuleEngine([_rule(severity="critical")])
    eng.observe(3, _snap(-0.5))
    assert eng.health_block()["status"] == "critical"
    # re-observing an already-evaluated round is a no-op
    assert eng.observe(3, _snap(0.5)) == []
    assert eng.health_block()["status"] == "critical"
    assert eng.health_block()["rounds_evaluated"] == 1


def test_label_subset_match_fires_on_worker_series():
    eng = RuleEngine([_rule(metric=N.SELECTOR_CONNECTIONS, op=">",
                            threshold=10.0)])
    snap = _snap(50.0, metric=N.SELECTOR_CONNECTIONS,
                 labels={"worker": "2"})
    eng.observe(0, snap)
    assert eng.health_block()["firing"] == {"r": "warn"}


def test_cell_aggregations_across_labels():
    cells = [{"labels": {"engine": "a"}, "value": 1.0},
             {"labels": {"engine": "b"}, "value": 9.0}]
    snap = {N.HEALTH_DIVERGENCE: {"kind": "gauge", "help": "",
                                  "values": cells}}
    for agg, expect in (("max", 9.0), ("min", 1.0), ("sum", 10.0)):
        eng = RuleEngine([_rule(metric=N.HEALTH_DIVERGENCE, op="==",
                                threshold=expect, agg=agg)])
        eng.observe(0, snap)
        assert eng.health_block()["firing"], agg


def test_histogram_rules_evaluate_p99():
    cell = {"count": 100, "sum": 0.0,
            "buckets": {"1": 50, "2": 40, "4": 9, "8": 1, "+Inf": 0}}
    snap = {N.ASYNC_STALENESS: {"kind": "histogram", "help": "",
                                "values": [{"labels": {},
                                            "value": cell}]}}
    eng = RuleEngine([_rule(metric=N.ASYNC_STALENESS, op=">",
                            threshold=3.0)])
    eng.observe(0, snap)
    # p99 lands in the (2, 4] bucket, interpolated to 4.0 at the 99th
    assert eng.health_block()["firing"]


def test_alert_gauge_published_even_when_green():
    obs_metrics.REGISTRY.reset()
    eng = RuleEngine([_rule(name="quiet")])
    eng.observe(0, _snap(0.9))
    assert _gauge_value(N.ALERT, rule="quiet", severity="warn") == 0.0


def test_flight_ring_carries_alert_edges():
    obs_flight.clear()
    eng = RuleEngine([_rule(name="edgy")])
    eng.observe(0, _snap(-0.9))
    eng.observe(1, _snap(0.9))
    kinds = [(e["kind"], e.get("rule")) for e in obs_flight.events()
             if e["kind"].startswith("alert")]
    assert kinds == [("alert", "edgy"), ("alert_clear", "edgy")]


def test_load_rules_manifest(tmp_path):
    p = tmp_path / "rules.json"
    p.write_text(json.dumps([
        {"name": "m1", "metric": N.HEALTH_DIVERGENCE, "op": ">",
         "threshold": 5, "labels": {"engine": "fedavg"},
         "severity": "critical", "for_rounds": 2}]))
    rules = obs_rules.load_rules(str(p))
    assert rules[0].labels == (("engine", "fedavg"),)
    assert rules[0].for_rounds == 2
    p.write_text(json.dumps([{"name": "x", "metric": "nidt_zzz",
                              "op": ">", "threshold": 1}]))
    with pytest.raises(ValueError, match="unknown metric"):
        RuleEngine(obs_rules.load_rules(str(p)))
    p.write_text(json.dumps([{"metric": N.MFU}]))
    with pytest.raises(ValueError, match="missing required"):
        obs_rules.load_rules(str(p))
    p.write_text(json.dumps([{"name": "x", "metric": N.MFU, "op": ">",
                              "threshold": 1, "frobnicate": True}]))
    with pytest.raises(ValueError, match="unknown fields"):
        obs_rules.load_rules(str(p))
    p.write_text(json.dumps({"not": "a list"}))
    with pytest.raises(ValueError, match="JSON list"):
        obs_rules.load_rules(str(p))


def test_builtin_manifest_validates_and_budget_rules():
    base = obs_rules.builtin_rules()
    RuleEngine(base)  # every built-in name is declared
    names = {r.name for r in base}
    assert "client-divergence" in names
    assert "dp-budget-exceeded" not in names
    with_budget = obs_rules.builtin_rules(dp_epsilon_budget=4.0,
                                          comm_round=100)
    names_b = {r.name for r in with_budget}
    assert {"dp-budget-exceeded", "dp-burn-rate"} <= names_b
    burn = next(r for r in with_budget if r.name == "dp-burn-rate")
    assert burn.threshold == pytest.approx(2.0 * 4.0 / 100)


def test_example_manifest_action_bindings():
    """The shipped example manifest (scripts/health_rules.example.json)
    must load, validate, and carry reflex-action bindings whose names
    resolve in obs/actions.py BUILTIN_ACTIONS (ISSUE 20): the manifest
    is both operator documentation and the action-discipline lint's
    cross-file fixture."""
    from neuroimagedisttraining_tpu.obs import actions as obs_actions

    rules = obs_rules.load_rules(
        os.path.join(os.path.dirname(__file__), os.pardir, "scripts",
                     "health_rules.example.json"))
    RuleEngine(rules)  # metrics declared, actions resolve, no dupes
    bound = {r.name: r.action for r in rules if r.action}
    assert bound == {
        "update-blowup-rollback-example": "freeze_rollback",
        "divergence-quarantine-example": "quarantine_silo"}
    assert set(bound.values()) <= set(obs_actions.BUILTIN_ACTIONS)


def test_configure_manifest_overrides_builtin(tmp_path):
    p = tmp_path / "rules.json"
    p.write_text(json.dumps([
        {"name": "client-divergence", "metric": N.HEALTH_COSINE_MIN,
         "op": "<", "threshold": -0.9}]))
    try:
        eng = obs_rules.configure(manifest_path=str(p))
        rule = next(r for r in eng.rules
                    if r.name == "client-divergence")
        assert rule.threshold == -0.9
        assert obs_rules.active() is eng
    finally:
        obs_rules.disarm()
    assert obs_rules.active() is None
    assert obs_rules.observe_boundary(0) == []
    assert obs_rules.health_block() == {"status": "unarmed"}


# ---------------------------------------------------------------------------
# (c) the seeded divergence scenario + run report
# ---------------------------------------------------------------------------

_BYZ = "byz:1@0:sign_flip,byz:1@1:sign_flip,byz:1@2:sign_flip"


def test_sign_flip_fires_divergence_clean_twin_green(tmp_path,
                                                     cohort64):
    """Engine-level acceptance: the sign-flip run fires
    client-divergence (alert gauge, flight event, critical block); the
    clean twin stays ok on the same config."""
    obs_flight.clear()
    try:
        obs_rules.configure()
        _engine(tmp_path, cohort64, health=True, comm_round=1, freq=1,
                tag="clean").train()
        assert obs_rules.health_block()["status"] == "ok"
        assert _gauge_value(N.ALERT, rule="client-divergence",
                            severity="critical") == 0.0
        clean_verdict = obs_rules.active().verdict()
        assert clean_verdict["alerts_total"] == 0
    finally:
        obs_rules.disarm()
    try:
        obs_rules.configure()
        _engine(tmp_path, cohort64, health=True, comm_round=1, freq=1,
                tag="byz", fault_spec=_BYZ).train()
        block = obs_rules.health_block()
        assert block["status"] == "critical"
        assert block["firing"].get("client-divergence") == "critical"
        assert _gauge_value(N.ALERT, rule="client-divergence",
                            severity="critical") == 1.0
        verdict = obs_rules.active().verdict()
        assert verdict["alerts_total"] >= 1
        assert any(e["rule"] == "client-divergence"
                   for e in verdict["timeline"])
    finally:
        obs_rules.disarm()
    alerts = [e for e in obs_flight.events() if e["kind"] == "alert"]
    assert any(e["rule"] == "client-divergence" for e in alerts)


def test_cli_health_gate_end_to_end(tmp_path, cohort64):
    """The CLI acceptance criterion: --health_gate exits nonzero on the
    sign-flip run and 0 on the clean twin; both write gate-passing
    run_report artifacts whose alert timelines differ."""
    from neuroimagedisttraining_tpu.__main__ import main

    argv = ["--algorithm", "fedavg", "--dataset", "synthetic",
            "--model", "3dcnn_tiny", "--synthetic_num_subjects", "64",
            "--synthetic_shape", "12", "14", "12",
            "--client_num_in_total", "4", "--comm_round", "1",
            "--batch_size", "8", "--epochs", "1", "--lr", "1e-3",
            "--seed", "0", "--log_dir", str(tmp_path),
            "--health_stats", "--health_gate"]
    rc_clean = main(argv + ["--tag", "cli_clean", "--metrics_out",
                            str(tmp_path / "clean.jsonl")])
    assert rc_clean == 0
    rc_byz = main(argv + ["--tag", "cli_byz", "--metrics_out",
                          str(tmp_path / "byz.jsonl"),
                          "--fault_spec", "byz:1@0:sign_flip"])
    assert rc_byz != 0

    def verdict_path(tag):
        (p,) = [os.path.join(tmp_path, "synthetic", f)
                for f in os.listdir(tmp_path / "synthetic")
                if tag in f and f.endswith(".health.json")]
        return p

    reports = {}
    for tag, metrics in (("cli_clean", "clean.jsonl"),
                         ("cli_byz", "byz.jsonl")):
        out = tmp_path / ("report_" + tag)
        assert run_report_main([
            "--metrics", str(tmp_path / metrics),
            "--verdict", verdict_path(tag), "--out", str(out)]) == 0
        reports[tag] = json.load(open(out / "run_report.json"))
        assert (out / "run_report.md").exists()
    clean, byz = reports["cli_clean"], reports["cli_byz"]
    assert clean["summary"]["schema_ok"] and byz["summary"]["schema_ok"]
    assert clean["summary"]["worst_status"] == "ok"
    assert byz["summary"]["worst_status"] == "critical"
    assert clean["alerts"] == []
    assert any(e["rule"] == "client-divergence" for e in byz["alerts"])


def test_run_report_build_join():
    recs = [
        {"round": 0, "seq": 1, "metrics": {
            N.EXP_METRIC: {"kind": "gauge", "help": "", "values": [
                {"labels": {"key": "train_loss"}, "value": 0.9}]},
            N.HEALTH_COSINE_MIN: {"kind": "gauge", "help": "",
                                  "values": [{"labels":
                                              {"engine": "fedavg"},
                                              "value": 0.3}]}}},
        {"round": 1, "seq": 2, "metrics": {
            N.DP_EPSILON: {"kind": "gauge", "help": "", "values": [
                {"labels": {"source": "weak_dp"}, "value": 1.5}]},
            N.DP_EPSILON_PER_ROUND: {
                "kind": "gauge", "help": "", "values": [
                    {"labels": {"source": "weak_dp"}, "value": 0.2}]},
            N.FALLBACK_TOTAL: {"kind": "counter", "help": "",
                               "values": [{"labels": {
                                   "plane": "sharding",
                                   "engine": "fedavg",
                                   "reason": "no-sharded-body"},
                                   "value": 1.0}]}}},
    ]
    verdict = {"status": "ok", "worst_status": "degraded",
               "alerts_total": 1,
               "timeline": [{"kind": "alert", "rule": "x",
                             "severity": "warn", "round": 1,
                             "value": 2.0}]}
    flight = {"capacity": 8, "evicted": 0, "events": [
        {"kind": "alert", "rule": "x", "severity": "warn", "round": 1},
        {"kind": "accept", "client": 2}]}
    rep = build_report(recs, flight, verdict)
    assert rep["summary"]["rounds"] == 2
    assert rep["summary"]["worst_status"] == "degraded"
    assert rep["rounds"][0]["train_loss"] == 0.9
    assert rep["rounds"][0]["cos_min"] == 0.3
    assert rep["epsilon_ledger"]["sources"]["weak_dp"] == {
        "epsilon": 1.5, "epsilon_per_round": 0.2}
    assert rep["dispatch"]["fallbacks"][0]["reason"] == "no-sharded-body"
    # the flight alert deduped against the verdict's (same key)
    assert len(rep["alerts"]) == 1
    md = render_markdown(rep)
    assert "## Alert timeline" in md and "`x`" in md


# ---------------------------------------------------------------------------
# (d) /healthz blocks + the lint family
# ---------------------------------------------------------------------------


def test_fallback_block_shape():
    round_program.report_fallback("fedavg", "no-sharded-body")
    block = obs_health.fallback_block()
    assert block["total"] >= 1
    assert block["by_plane"].get("sharding", 0) >= 1
    rows = [r for r in block["announcements"]
            if r["reason"] == "no-sharded-body"]
    assert rows and rows[0]["engine"] == "fedavg"


def test_health_metric_literal_lint_fires_outside_obs():
    findings = lint_source(
        'from neuroimagedisttraining_tpu.obs import metrics as m\n'
        'g = m.gauge("nidt_health_cosine_min", "h")\n',
        path="neuroimagedisttraining_tpu/engines/whatever.py")
    ids = [f.rule for f in findings]
    assert "health-metric-literal" in ids


def test_health_metric_literal_lint_clean_cases():
    # prose mentioning a metric is not a full-match literal
    assert not lint_source(
        'x = "the nidt_mfu gauge"\n',
        path="neuroimagedisttraining_tpu/engines/whatever.py")
    # the constant spelling is the blessed one
    assert not lint_source(
        'from neuroimagedisttraining_tpu.obs import names as n\n'
        'name = n.MFU + "_bucket"\n',
        path="neuroimagedisttraining_tpu/engines/whatever.py")
    # obs/ is the declaration side — exempt
    assert not lint_source(
        'g = ("nidt_mfu",)\n',
        path="neuroimagedisttraining_tpu/obs/compute.py")


def test_declared_set_covers_builtin_rules_and_health_names():
    for r in obs_rules.builtin_rules(dp_epsilon_budget=1.0):
        assert r.metric in N.DECLARED
    for name in (N.HEALTH_COSINE_MIN, N.ALERT, N.RECOMPILES_TOTAL,
                 N.DP_EPSILON_PER_ROUND):
        assert name in N.DECLARED
