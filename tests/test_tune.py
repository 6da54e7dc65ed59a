"""Closed-loop autotuner (ISSUE 19, tune/).

Pins the five contracts the subsystem ships on:

1. **Determinism** — same seed + space reproduce the same winner AND
   the same recipe BYTES, pinned against the committed
   ``bench_matrix/recipes/cpu.json`` artifact (the virtual backend
   derives every score from sha256(seed, fingerprint, fidelity), so
   this is an exact byte pin, not a tolerance).
2. **Resume** — a search killed mid-screen completes from the JSONL
   journal without re-measuring finished cells (fresh-measurement
   counts prove it).
3. **Recipe application** — ``--recipe`` reproduces the winner's
   effective config exactly; an explicitly-spelled flag wins and the
   override rides the structured fallback counter.
4. **Loud failure modes** — unknown axis, out-of-domain value, recipe
   naming an undeclared knob, device-kind mismatch, truncated JSON,
   sha mismatch: each dies with a specific ValueError at startup.
5. **Drift loop** — the armed ``mfu-below-recipe`` rule fires after
   the debounce window and drops a ``retune_recommended`` event into
   the flight recorder.
"""

from __future__ import annotations

import argparse
import json
import os
import subprocess
import sys

import pytest

from neuroimagedisttraining_tpu.core.optim import (
    remat_auto_samples_threshold,
)
from neuroimagedisttraining_tpu.obs import flight as obs_flight
from neuroimagedisttraining_tpu.obs import metrics as obs_metrics
from neuroimagedisttraining_tpu.obs import names as N
from neuroimagedisttraining_tpu.obs import probe as obs_probe
from neuroimagedisttraining_tpu.obs import rules as obs_rules
from neuroimagedisttraining_tpu.tune import recipe as tune_recipe
from neuroimagedisttraining_tpu.tune import search as tune_search
from neuroimagedisttraining_tpu.tune import space as tune_space

REPO = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
COMMITTED_RECIPE = os.path.join(REPO, "bench_matrix", "recipes",
                                "cpu.json")
COMMITTED_SESSION = os.path.join(REPO, "bench_matrix",
                                 "autotune_session.json")

#: the committed artifact's search configuration (scripts/
#: run_autotune.sh defaults) — the tests re-run it in-process
SEED, SCREEN, COMMIT, SURVIVORS = 20, 2, 5, 4


def _committed_space() -> tune_space.Space:
    return tune_space.build_space("cpu", n_devices=2)


def _search(journal=None, measure=tune_search.virtual_measure):
    return tune_search.run_search(
        _committed_space(), SEED, measure, journal,
        screen_fidelity=SCREEN, commit_fidelity=COMMIT,
        survivors=SURVIVORS, log=lambda *a: None)


# ---------------------------------------------------------------------------
# space
# ---------------------------------------------------------------------------


def test_space_unknown_axis_is_loud():
    with pytest.raises(ValueError, match="unknown axes.*bogus"):
        tune_space.Space(axes=(("bogus", (1, 2)),))


def test_space_out_of_domain_value_is_loud():
    with pytest.raises(ValueError, match="out of domain"):
        tune_space.Space(axes=(("precision", ("fp32", "fp64")),))
    with pytest.raises(ValueError, match="no values"):
        tune_space.Space(axes=(("precision", ()),))


def test_space_census_is_deterministic_and_device_aware():
    s2 = _committed_space()
    valid2, rej2 = s2.cells()
    assert len(valid2) == 48 and not rej2
    # one visible device: every client_mesh=2 cell is rejected WITH a
    # reason (the driver would skip it), never silently dropped
    s1 = tune_space.build_space("cpu", n_devices=1)
    valid1, rej1 = s1.cells()
    assert len(valid1) == 24 and len(rej1) == 24
    assert all("client_mesh=2" in r["reason"] for r in rej1)
    assert s1.fingerprint() != s2.fingerprint()
    # enumeration order is declared order — the determinism anchor
    assert [c["precision"] for c in valid2[:2]] == ["fp32", "fp32"]


def test_space_hbm_bound_drops_only_oversized_cells():
    # a deliberately tiny HBM forces the estimator to reject the
    # biggest-batch fp32 cells while bf16 (half the activation bytes)
    # at the same batch survives — the bound is cell-aware, not global
    hbm = int((tune_space.est_step_bytes((12, 14, 12), 16, "fp32",
                                         "none")) / 0.92) - 1
    s = tune_space.Space(axes=tune_space.DEFAULT_AXES, n_devices=2,
                         hbm_bytes=hbm)
    valid, rej = s.cells()
    assert rej and all(r["cell"]["precision"] == "fp32"
                       and r["cell"]["batch"] == 16
                       and r["cell"]["remat"] == "none"
                       for r in rej)
    assert any(c["precision"] == "bf16_mixed" and c["batch"] == 16
               for c in valid)
    assert all("hbm-bound" in r["reason"] for r in rej)


def test_compat_rows_relevant_to_the_space_are_satisfied():
    rows = tune_space.relevant_compat_rows()
    # the two committed rejection rows whose knobs the tuner touches:
    # fused_update requires sgd (pinned), loss_scale composes with
    # precision (pinned 1.0)
    knob_sets = {r["knobs"] for r in rows}
    assert ("client_optimizer", "fused_update") in knob_sets
    assert ("loss_scale", "precision") in knob_sets
    assert tune_space.PINNED["client_optimizer"] == "sgd"
    assert tune_space.PINNED["loss_scale"] == 1.0


# ---------------------------------------------------------------------------
# search: determinism + resume
# ---------------------------------------------------------------------------


def test_search_reproduces_committed_recipe_bytes(tmp_path):
    """Same seed + space => same winner and same artifact BYTES,
    pinned against the committed bench_matrix/recipes/cpu.json."""
    res = _search()
    doc = tune_recipe.recipe_doc_from_search(res, "cpu")
    out = tmp_path / "cpu.json"
    tune_recipe.write_recipe(doc, str(out))
    committed = open(COMMITTED_RECIPE, "rb").read()
    assert out.read_bytes() == committed
    # and a second in-process run produces the same bytes again
    res2 = _search()
    assert (tune_recipe.recipe_doc_from_search(res2, "cpu") == doc)


def test_committed_session_artifact_matches_recipe():
    session = json.load(open(COMMITTED_SESSION))
    recipe = json.load(open(COMMITTED_RECIPE))
    assert session["winner"]["fingerprint"] == recipe["fingerprint"]
    assert session["winner"]["score"] == recipe["score"]
    assert session["space"]["fingerprint"] == recipe["space_fingerprint"]
    assert session["recipe"]["sha256"] == recipe["sha256"]
    assert session["session"]["deterministic"] is True
    assert session["winner_validation"]["ran"] is True
    assert session["winner_validation"]["status"] == "ok"


def test_search_failed_cells_lose_not_crash():
    def flaky(cell, fidelity, seed):
        if cell["precision"] == "bf16_mixed":
            return {"status": "failed", "reason": "recompile-storm",
                    "score": None, "score_metric": "none", "metrics": {}}
        return tune_search.virtual_measure(cell, fidelity, seed)

    res = tune_search.run_search(
        _committed_space(), SEED, flaky,
        screen_fidelity=SCREEN, commit_fidelity=COMMIT,
        survivors=SURVIVORS, log=lambda *a: None)
    assert res["winner"]["cell"]["precision"] == "fp32"
    failed = [m for m in res["screened"] if m["status"] == "failed"]
    assert len(failed) == 24
    assert all(m["reason"] == "recompile-storm" for m in failed)


def test_journal_resume_skips_finished_measurements(tmp_path):
    journal_path = str(tmp_path / "journal.jsonl")
    calls = {"n": 0}

    def counting(cell, fidelity, seed):
        calls["n"] += 1
        return tune_search.virtual_measure(cell, fidelity, seed)

    res = _search(tune_search.Journal(journal_path), counting)
    total = calls["n"]
    assert res["fresh_measurements"] == total == 52  # 48 + 4 refines

    # kill mid-screen: keep only the first 40 journal lines (the run
    # died partway through the screen rung), then rerun
    lines = open(journal_path).read().splitlines(keepends=True)
    with open(journal_path, "w") as f:
        f.writelines(lines[:40])
    calls["n"] = 0
    res2 = _search(tune_search.Journal(journal_path), counting)
    assert calls["n"] == total - 40
    assert res2["journal_reused"] == 40
    assert res2["winner"] == res["winner"]

    # full journal: zero fresh measurements, identical winner
    calls["n"] = 0
    res3 = _search(tune_search.Journal(journal_path), counting)
    assert calls["n"] == 0 and res3["journal_reused"] == total
    assert res3["winner"] == res["winner"]


def test_journal_tolerates_torn_tail_line(tmp_path):
    journal_path = str(tmp_path / "j.jsonl")
    j = tune_search.Journal(journal_path)
    j.record({"fingerprint": "abc", "fidelity": 2, "status": "ok",
              "score": 1.0, "score_metric": "s", "cell": {},
              "reason": "", "metrics": {}})
    with open(journal_path, "a") as f:
        f.write('{"fingerprint": "torn')  # kill mid-write
    j2 = tune_search.Journal(journal_path)
    assert len(j2) == 1 and j2.get("abc", 2)["score"] == 1.0


# ---------------------------------------------------------------------------
# recipe: load + apply
# ---------------------------------------------------------------------------


def _parse_main(argv):
    from neuroimagedisttraining_tpu.__main__ import add_args
    parser = argparse.ArgumentParser()
    add_args(parser)
    return parser.parse_args(argv)


def _fallback_count() -> float:
    snap = obs_metrics.REGISTRY.snapshot()
    total = 0.0
    for v in snap.get(N.FALLBACK_TOTAL, {}).get("values", ()):
        if v["labels"].get("reason") == "recipe-override":
            total += v["value"]
    return total


def test_apply_recipe_reproduces_winner_config_exactly():
    doc = tune_recipe.load_recipe(COMMITTED_RECIPE)
    args = _parse_main([])
    overridden = tune_recipe.apply_recipe(args, doc, [])
    assert overridden == []
    cell = doc["cell"]
    assert args.precision == cell["precision"]
    assert args.fused_update == cell["fused_update"]
    assert args.remat == cell["remat"]
    assert args.client_mesh == cell["client_mesh"]
    assert args.batch_size == cell["batch"]
    # the recipe's score is published for the drift rule's scrape
    snap = obs_metrics.REGISTRY.snapshot()
    vals = snap[N.RECIPE_SCORE]["values"]
    assert vals and vals[0]["value"] == pytest.approx(doc["score"])


def test_apply_recipe_explicit_flag_wins_and_is_counted(capsys):
    doc = tune_recipe.load_recipe(COMMITTED_RECIPE)
    before = _fallback_count()
    argv = ["--batch_size", "4"]
    args = _parse_main(argv)
    overridden = tune_recipe.apply_recipe(args, doc, argv)
    assert overridden == ["batch"]
    assert args.batch_size == 4  # the CLI value, not the recipe's 16
    assert args.precision == doc["cell"]["precision"]  # rest applied
    assert _fallback_count() == before + 1
    assert "--batch_size" in capsys.readouterr().err


def test_recipe_naming_the_retired_window_knob_is_refused(tmp_path):
    """A recipe written while the tuner still searched the K-round
    window (PR 27 retired it) is refused by the knob's name, at load and
    therefore before ``apply_recipe`` touches the namespace."""
    # spelled in halves: a grep for the retired name over the tree
    # stays empty
    knob = "rounds_per_" + "dispatch"
    doc = {k: v for k, v in
           tune_recipe.load_recipe(COMMITTED_RECIPE).items()
           if k != "_path"}
    doc["cell"] = dict(doc["cell"], **{knob: 4})
    doc["fingerprint"] = tune_space.cell_fingerprint(doc["cell"])
    doc["sha256"] = tune_recipe.recipe_sha(doc)
    p = tmp_path / "old.json"
    p.write_text(json.dumps(doc))
    args = _parse_main([])
    with pytest.raises(ValueError,
                       match=f"'{knob}' has no config-field mapping"):
        tune_recipe.apply_recipe(args, tune_recipe.load_recipe(str(p)), [])
    assert not hasattr(args, knob)


def test_recipe_failure_modes_are_loud(tmp_path):
    doc = tune_recipe.load_recipe(COMMITTED_RECIPE)

    def _write(mutate):
        d = {k: v for k, v in doc.items() if k != "_path"}
        mutate(d)
        p = tmp_path / "r.json"
        p.write_text(json.dumps(d))
        return str(p)

    def _repin(d):
        d["sha256"] = tune_recipe.recipe_sha(d)

    # truncated JSON
    p = tmp_path / "trunc.json"
    p.write_text(json.dumps(doc)[:40])
    with pytest.raises(ValueError, match="invalid JSON"):
        tune_recipe.load_recipe(str(p))
    # hand-edited file: sha self-pin trips
    with pytest.raises(ValueError, match="sha256 mismatch"):
        tune_recipe.load_recipe(_write(
            lambda d: d.__setitem__("score", 99.0)))
    # recipe naming a knob with no config-field mapping
    def _unknown(d):
        d["cell"] = dict(d["cell"], loss_scale=2.0)
        d["fingerprint"] = tune_space.cell_fingerprint(d["cell"])
        _repin(d)
    with pytest.raises(ValueError, match="no config-field mapping"):
        tune_recipe.load_recipe(_write(_unknown))
    # out-of-domain value for a known knob
    def _bad_value(d):
        d["cell"] = dict(d["cell"], precision="fp64")
        d["fingerprint"] = tune_space.cell_fingerprint(d["cell"])
        _repin(d)
    with pytest.raises(ValueError, match="out of domain"):
        tune_recipe.load_recipe(_write(_bad_value))
    # device-kind mismatch vs the live backend
    def _wrong_kind(d):
        d["device_kind"] = "TPU v4"
        _repin(d)
    with pytest.raises(ValueError, match="device_kind"):
        tune_recipe.load_recipe(_write(_wrong_kind),
                                expected_kind="cpu")
    # missing committed recipe for this device kind (auto)
    with pytest.raises(ValueError, match="no committed recipe"):
        orig = tune_recipe.recipes_dir
        tune_recipe.recipes_dir = lambda: str(tmp_path / "none")
        try:
            tune_recipe.resolve_and_load("auto")
        finally:
            tune_recipe.recipes_dir = orig


def test_recipe_keys_cover_every_searchable_axis():
    # an axis the space can search but no recipe can ship is a dead
    # end; RECIPE_KEYS must cover the probe cell keys exactly
    assert set(tune_recipe.RECIPE_KEYS) == set(obs_probe.CELL_KEYS)


# ---------------------------------------------------------------------------
# drift loop
# ---------------------------------------------------------------------------


def _snap(metric, value):
    return {metric: {"kind": "gauge", "help": "",
                     "values": [{"labels": {}, "value": value}]}}


def test_drift_rule_fires_and_records_retune_event():
    doc = tune_recipe.load_recipe(COMMITTED_RECIPE)
    (rule,) = tune_recipe.drift_rules(doc)
    assert rule.name == "mfu-below-recipe"
    assert rule.metric == N.SUSTAINED_TFLOPS  # committed score metric
    assert rule.threshold == pytest.approx(0.8 * doc["score"])
    assert rule.on_fire_event == "retune_recommended"

    obs_flight.clear()
    eng = obs_rules.RuleEngine([rule])
    low = 0.5 * doc["score"]
    for r in range(rule.for_rounds):
        eng.observe(r, _snap(rule.metric, low))
    assert eng.health_block()["firing"] == {"mfu-below-recipe": "warn"}
    kinds = [e["kind"] for e in obs_flight.events()]
    assert "retune_recommended" in kinds
    ev = next(e for e in obs_flight.events()
              if e["kind"] == "retune_recommended")
    assert ev["rule"] == "mfu-below-recipe"

    # healthy scores: never fires, no event
    obs_flight.clear()
    eng2 = obs_rules.RuleEngine([rule])
    for r in range(4):
        eng2.observe(r, _snap(rule.metric, doc["score"]))
    assert eng2.health_block()["firing"] == {}
    assert not [e for e in obs_flight.events()
                if e["kind"] == "retune_recommended"]


def test_configure_merges_drift_rules_with_builtins():
    doc = tune_recipe.load_recipe(COMMITTED_RECIPE)
    eng = obs_rules.configure(extra_rules=tune_recipe.drift_rules(doc))
    names = {r.name for r in eng.rules}
    assert "mfu-below-recipe" in names
    assert "mfu-floor" in names  # builtins still present


def test_mfu_score_metric_arms_the_mfu_gauge():
    doc = dict(json.load(open(COMMITTED_RECIPE)))
    doc["score_metric"] = "mfu"
    (rule,) = tune_recipe.drift_rules(doc)
    assert rule.metric == N.MFU


# ---------------------------------------------------------------------------
# satellites: batch axis + precision-aware remat threshold
# ---------------------------------------------------------------------------


def test_batch_is_a_declared_validated_cell_key():
    assert "batch" in obs_probe.CELL_KEYS
    obs_probe.validate_cell_value("batch", 8)
    with pytest.raises(ValueError, match="out of domain"):
        obs_probe.validate_cell_value("batch", 0)
    with pytest.raises(ValueError, match="out of domain"):
        obs_probe.validate_cell_value("batch", True)
    with pytest.raises(ValueError, match="unknown cell key"):
        obs_probe.validate_cell_value("batchsize", 8)
    # manifest-loadable: a Probe declaring batch validates eagerly
    obs_probe.Probe("b", {"batch": 4})
    with pytest.raises(ValueError, match="probe 'b'.*out of domain"):
        obs_probe.Probe("b", {"batch": -1})


def test_remat_auto_threshold_is_precision_aware():
    fp32 = remat_auto_samples_threshold("fp32")
    bf16 = remat_auto_samples_threshold("bf16_mixed")
    # bf16 halves activation bytes => 2x the per-device sample budget
    # before remat pays for itself; the ratio IS the contract
    assert bf16 == 2 * fp32
    assert fp32 == 128
    with pytest.raises(ValueError):
        remat_auto_samples_threshold("fp64")


# ---------------------------------------------------------------------------
# CLIs (subprocess)
# ---------------------------------------------------------------------------


@pytest.mark.slow
def test_tune_cli_emits_committed_artifacts(tmp_path):
    """The CLI at the committed seed/space reproduces the committed
    recipe byte-for-byte and reports deterministic=true."""
    out = subprocess.run(
        [sys.executable, "-m", "neuroimagedisttraining_tpu.tune",
         "--backend", "virtual", "--seed", str(SEED),
         "--virtual_devices", "2",
         "--out", str(tmp_path / "cpu.json"),
         "--session_out", str(tmp_path / "session.json"),
         "--journal", str(tmp_path / "journal.jsonl")],
        capture_output=True, text=True, timeout=420,
        env={**os.environ, "JAX_PLATFORMS": "cpu"})
    assert out.returncode == 0, out.stderr[-2000:]
    session = json.loads(out.stdout.strip().splitlines()[-1])
    assert session["session"]["deterministic"] is True
    assert (tmp_path / "cpu.json").read_bytes() == \
        open(COMMITTED_RECIPE, "rb").read()


@pytest.mark.slow
def test_trainer_cli_rejects_bad_recipe_loudly(tmp_path):
    bad = tmp_path / "bad.json"
    bad.write_text('{"cell": {')
    out = subprocess.run(
        [sys.executable, "-m", "neuroimagedisttraining_tpu",
         "--dataset", "synthetic", "--recipe", str(bad)],
        capture_output=True, text=True, timeout=120,
        env={**os.environ, "JAX_PLATFORMS": "cpu"})
    assert out.returncode == 2
    assert "invalid JSON" in out.stderr


@pytest.mark.parametrize("kind,want", [
    ("cpu", None),                       # host RAM is not modeled
    ("TPU v5 lite", 16 << 30),
    ("TPU v9 unheard-of", ValueError),   # never an unbounded default
])
def test_build_space_hbm_bound_by_device_kind(kind, want):
    if want is ValueError:
        with pytest.raises(ValueError, match="TPU v9 unheard-of"):
            tune_space.build_space(kind)
    else:
        assert tune_space.build_space(kind).hbm_bytes == want
