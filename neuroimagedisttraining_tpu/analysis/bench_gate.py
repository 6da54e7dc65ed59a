"""Bench regression gate (ISSUE 13): diff fresh bench cells against the
committed ``bench_matrix/`` artifacts with per-cell thresholds.

ROADMAP item 2's "regenerated BENCH_MATRIX" session needs to trust its
own numbers: every committed artifact carries wall-clock cells measured
on a shared, drifty box, and until now the only way to know whether a
fresh run regressed was reading JSON by eye. This gate makes the
comparison mechanical and the verdict machine-readable:

- ``SPECS`` names, per artifact, the cells that matter and HOW each is
  judged — structural booleans exactly (``true``), wall-clock numbers
  as loose ratios vs the committed value (``ratio_min``/``ratio_max``,
  tolerances sized for this box's documented 2x run-to-run drift:
  regression tripwires, not noise detectors), and absolute contracts
  (``abs_max``, e.g. the obs-overhead <= 2% acceptance).
- missing FRESH artifacts are SKIPPED, not red (a session regenerates
  the cells it touched, not the whole matrix); ``--strict`` upgrades
  skips to failures for full-matrix regeneration sessions.
- the verdict is one JSON object (``--json`` to also write it) and the
  exit code follows the nidtlint convention: 0 green, 1 red, 2 usage
  error.

Entry points::

    python -m neuroimagedisttraining_tpu.analysis.bench_gate \
        --fresh /tmp/fresh_bench [--committed bench_matrix]

    scripts/bench_diff.py --produce ingest   # regenerate a quick
        # ingest cell into a fresh dir, then gate it

With no ``--fresh`` the gate self-diffs the committed directory — every
ratio is exactly 1.0, which verifies the spec paths still match the
artifacts (the schema-drift canary) without claiming fresh evidence.

Dependency-free (stdlib json only), like the rest of ``analysis/``.
"""

from __future__ import annotations

import argparse
import dataclasses
import json
import os
import sys
from typing import Any

__all__ = ["Check", "SPECS", "extract", "gate", "main"]


@dataclasses.dataclass(frozen=True)
class Check:
    """One gated cell: a dotted ``path`` into the artifact JSON and the
    judgment ``kind``:

    - ``true``      — fresh value must be truthy (committed ignored)
    - ``ratio_min`` — fresh / committed >= threshold (higher-better)
    - ``ratio_max`` — fresh / committed <= threshold (lower-better)
    - ``abs_max``   — fresh <= threshold (absolute contract)
    - ``eq``        — fresh == committed exactly (deterministic cells)
    """

    path: str
    kind: str
    threshold: float | None = None
    note: str = ""


#: per-artifact cell specs. Ratio thresholds are deliberately loose
#: (0.5 / 2.0): the box's wall numbers drift ~2x run to run (documented
#: in the artifacts' own notes), so the gate trips on order-of-change
#: regressions — a broken fast path, a serialized fleet — not on load.
SPECS: dict[str, tuple[Check, ...]] = {
    "ingest_bench.json": (
        Check("summary.audits_green", "true",
              note="cross-process accounting audits"),
        Check("async.uploads_per_s_sustained", "ratio_min", 0.5,
              "single-process selector baseline"),
        Check("ingest_w1.uploads_per_s_sustained", "ratio_min", 0.5,
              "sharded plane, 1 worker"),
        Check("ingest_w2.uploads_per_s_sustained", "ratio_min", 0.5,
              "sharded plane, 2 workers (the knee on this box)"),
        Check("ingest_w4.uploads_per_s_sustained", "ratio_min", 0.5,
              "sharded plane, 4 workers (headline cell)"),
    ),
    "async_bench.json": (
        Check("async.frames_reconciled", "true",
              note="zero-lost/zero-double-counted accounting"),
        Check("async.uploads_per_s", "ratio_min", 0.5,
              "buffered-server sustained throughput"),
        Check("summary.p99_advance_ratio", "ratio_min", 0.5,
              "sync-vs-async p99 version-advance advantage"),
    ),
    "obs_overhead.json": (
        Check("overhead_frac", "abs_max", 0.02,
              "armed-vs-disarmed telemetry overhead acceptance"),
    ),
    "wire_bench.json": (
        Check("masked_sparse_quant.pass", "true"),
        Check("masked_sparse_quant.bytes_reduction_x", "ratio_min", 0.5,
              "masked sparse+quant wire reduction"),
        Check("fedavg_delta_quant.pass", "true"),
        Check("fedavg_delta_quant.bytes_reduction_x", "ratio_min", 0.5,
              "delta+quant wire reduction"),
    ),
    "secure_bench.json": (
        Check("cells.secure_quant.bytes_recv", "ratio_max", 1.5,
              "secure-quant server-received bytes (deterministic frame "
              "sizes; 1.5x headroom for protocol chatter)"),
        Check("cells.secure_dense.bytes_recv", "ratio_max", 1.5),
    ),
    "byz_bench.json": (
        Check("pass", "true", note="defense-recovery acceptance"),
        Check("cells.clean.mean_auc", "ratio_min", 0.8,
              "clean-run AUC (seeded, should be near-deterministic)"),
    ),
    "round_program.json": (
        Check("engines.fedavg.dispatches", "eq",
              note="dispatch counts are deterministic compile facts"),
        Check("engines.fedavg.programs_built", "eq"),
        Check("engines.ditto.dispatches", "eq"),
        Check("engines.dpsgd.dispatches", "eq"),
        Check("engines.subavg.dispatches", "eq"),
    ),
    "cohort_sharding.json": (
        Check("slope_s_per_client.sharded_over_sequential", "ratio_max",
              2.0, "sharded-vs-sequential per-client slope"),
    ),
    "precision_bench.json": (
        Check("parity.fp32_fused_bitwise_equals_fp32", "true"),
        Check("parity.bf16_fused_bitwise_equals_bf16", "true"),
        Check("parity.bf16_vs_fp32_loss_abs_delta", "abs_max", 2e-3,
              "bf16 loss tolerance pin"),
    ),
    # profile session (ISSUE 14, obs/probe.py): structural cells exact —
    # the probe manifest fingerprint, the deterministic dispatch/compile
    # counts, the live-scrape booleans — and every wall/TFLOPs cell at
    # the standard drift-tolerant ratio tripwires. The XLA-vs-analytic
    # FLOPs reconciliation is deterministic on a fixed backend, so its
    # ratio band is tight (same-box schema canary, not a wall cell).
    # The eq cells are deterministic AT THE COMMITTED CONFIG (counts
    # follow PROFILE_ROUNDS, the fingerprint follows devices/manifest):
    # a config-changing regeneration — the flagship TPU recipe replacing
    # the CPU smoke baseline — legitimately differs, and
    # scripts/run_profile_session.sh detects the meta mismatch and
    # treats the verdict as informational while a SAME-config red
    # blocks the install (the round_program.json eq cells carry the
    # same config-pinned contract).
    # training-health exemplar (ISSUE 15, scripts/run_health_report.sh):
    # the seeded sign-flip divergence run vs its clean twin through the
    # shipped CLI + analysis/run_report.py. Every cell is a
    # deterministic verdict fact at the committed config (seeded tiny
    # run, rule edges are debounced booleans), so the checks are exact
    # — a regeneration that stops firing the divergence rule, or starts
    # firing on the clean twin, is a broken health plane, not drift.
    "health_report.json": (
        Check("contrast.timelines_differ", "true",
              note="byz vs clean alert timelines visibly differ "
                   "(the acceptance criterion verbatim)"),
        Check("clean.summary.schema_ok", "true"),
        Check("byz.summary.schema_ok", "true"),
        Check("contrast.clean_worst", "eq",
              note="clean twin stays ok for the whole run"),
        Check("contrast.byz_worst", "eq",
              note="sign-flip run's worst status (critical)"),
        Check("contrast.clean_alerts", "eq"),
        Check("contrast.byz_alerts", "eq",
              note="alert count at the committed seed/config"),
        Check("byz.summary.rounds", "eq",
              note="metrics JSONL rounds joined (the round/seq keys)"),
    ),
    # serving plane (ISSUE 17, scripts/run_serve_bench.sh): the
    # loadgen serve fleet (1k open-loop clients) against a 2-worker
    # SO_REUSEPORT serving cell on a real ditto bundle. Structural
    # cells exact — the shutdown accounting, the one-program-per-
    # (model, bucket) compile pin, the per-site routing distinctness —
    # and the wall cells (requests/s, client p99) at the standard
    # drift-tolerant ratio tripwires.
    "serve_bench.json": (
        Check("summary.audits_green", "true",
              note="client-side exactness + root/bye verdict "
                   "reconciliation (zero unaccounted requests)"),
        Check("serve.compile_pin_ok", "true",
              note="ONE compiled program per (model, bucket); zero "
                   "recompiles (the tripwire counter)"),
        Check("serve.routing.distinct_site_models", "true",
              note="two sites observed two DIFFERENT personalized "
                   "bundle digests"),
        Check("serve.merged_metrics.has_serve_latency", "true",
              note="merged scrape carries nidt_serve_latency_ms "
                   "samples"),
        Check("serve.merged_metrics.has_rtt_samples", "true",
              note="client-observed nidt_client_rtt_ms published "
                   "through the shared fleet path"),
        Check("serve.serve_workers", "eq",
              note="the committed cell is the 2-worker config"),
        Check("serve.requests_per_s", "ratio_min", 0.5,
              "client-confirmed serving throughput"),
        Check("serve.rtt_ms_p99", "ratio_max", 2.0,
              "client-observed p99 RTT tripwire (box drift "
              "tolerated)"),
    ),
    # hierarchical aggregation tier (ISSUE 18,
    # scripts/run_region_bench.sh): a 2-region x 2-worker tree under the
    # committed ingest_bench load (1k clients) plus the downlink
    # delta-sync A/B (same fleet, delta on vs off). Structural cells
    # exact — the audits, the shm-beats-pipe A/B, the tree-vs-committed-
    # single-root floor, the >=3x delta-bytes pin (all computed as
    # booleans by the bench itself so the gate re-judges fresh runs,
    # not just the committed one) — and the absolute throughput cell at
    # the standard drift-tolerant ratio tripwire.
    "region_bench.json": (
        Check("summary.audits_green", "true",
              note="every cell's received/accepted accounting exact + "
                   "frames reconciled through the region tier"),
        Check("summary.tree_at_least_committed_single_root", "true",
              note="the 2x2 tree sustains >= the committed single-root "
                   "best (ingest_bench ingest_w*)"),
        Check("summary.shm_beats_pipe", "true",
              note="shared-memory partial hand-off beats the pickled "
                   "pipe on mean per-export latency"),
        Check("summary.delta_sync_3x", "true",
              note=">=3x fewer bytes per changed-version sync reply "
                   "(delta vs dense, decoded bitwise-equal)"),
        Check("summary.delta_errors", "abs_max", 0,
              "zero base-mismatch delta replies ever shipped"),
        Check("summary.regions", "eq",
              note="the committed cell is the 2-region tree"),
        Check("summary.workers_per_region", "eq"),
        Check("summary.tree_uploads_per_s_sustained", "ratio_min", 0.5,
              "tree sustained throughput tripwire (box drift "
              "tolerated)"),
        Check("summary.delta_sync_bytes_ratio", "ratio_min", 0.5,
              "dense/delta sync-bytes ratio (codec regression "
              "tripwire)"),
    ),
    "profile_session.json": (
        Check("session.structural_fingerprint", "eq",
              note="the declared probe manifest (structural cells)"),
        Check("session.probes_completed", "eq",
              note="every declared probe ran (skips are structural)"),
        Check("session.metrics_scrape_ok", "true",
              note="live /metrics served nidt_dispatch_ms + "
                   "nidt_mfu/nidt_sustained_tflops samples"),
        Check("session.healthz_compute_ok", "true",
              note="/healthz compute block (dispatch liveness)"),
        Check("probes.fp32_baseline.dispatches", "eq",
              note="dispatch counts are deterministic compile facts"),
        Check("probes.fp32_baseline.compiles", "eq"),
        Check("probes.fp32_baseline.round_ms", "ratio_max", 2.0,
              "per-round wall tripwire (box drift tolerated)"),
        Check("probes.bf16.round_ms", "ratio_max", 2.0),
        Check("probes.fp32_baseline.sustained_tflops", "ratio_min", 0.5,
              "sustained analytic TFLOP/s over the last boundary "
              "window (the MFU numerator)"),
        # the MFU ratio cells are ACTIVE but judge only when the
        # committed side carries a number: mfu is null off-chip (no
        # device peak), the committed cell is the CPU baseline, and a
        # null committed value SKIPS a ratio check (the self-diff
        # canary in tests/test_bench_gate.py pins exactly this — only
        # .mfu cells may skip). The first TPU-session regeneration
        # flips them to judging with zero spec edits.
        Check("probes.fp32_baseline.mfu", "ratio_min", 0.5,
              "model FLOPs utilization (judged once the committed "
              "artifact was measured where the device peak is known)"),
        Check("probes.bf16.mfu", "ratio_min", 0.5),
        Check("xla.train_step.parity_ratio", "ratio_min", 0.9,
              "XLA cost_analysis vs analytic ops/flops.py FLOPs — "
              "deterministic on a fixed backend"),
        Check("xla.train_step.parity_ratio", "ratio_max", 1.1),
    ),
    # autotuner session (ISSUE 19, scripts/run_autotune.sh): the seeded
    # successive-halving search over the declared space through the
    # virtual backend, plus one REAL-driver run of the winner. Every
    # cell is a deterministic search fact at the committed seed/space —
    # the byte-determinism self-check, the winner identity, the space
    # census — so the checks are exact; a regeneration that changes the
    # winner changed the space/seed/cost model, not the weather.
    "autotune_session.json": (
        Check("session.deterministic", "true",
              note="same seed + space reproduced the same recipe "
                   "BYTES twice (in-memory rerun self-check)"),
        Check("winner.fingerprint", "eq",
              note="winner identity at the committed seed/space"),
        Check("winner.score", "eq",
              note="committed-window score (virtual backend: seeded, "
                   "exact)"),
        Check("space.fingerprint", "eq",
              note="the declared space (axes + device context + "
                   "pinned knobs)"),
        Check("space.n_cells", "eq",
              note="valid-cell census after the validity predicates"),
        Check("winner_validation.ran", "true",
              note="the winner ran once through the REAL probe "
                   "driver after emission"),
        Check("winner_validation.status", "eq",
              note="and survived it (committed cell says 'ok')"),
    ),
    # the committed per-hardware recipe itself (tune/recipe.py): the
    # artifact --recipe auto loads on this box. Identity cells exact —
    # the sha256 self-pin covers every other byte.
    "recipes/cpu.json": (
        Check("device_kind", "eq",
              note="the recipe file matches its directory slot"),
        Check("fingerprint", "eq",
              note="winning-cell identity"),
        Check("score", "eq"),
        Check("space_fingerprint", "eq"),
        Check("sha256", "eq",
              note="the self-pin: any other drift shows here"),
    ),
}

#: default committed-artifact directory (repo-relative)
DEFAULT_COMMITTED = "bench_matrix"


def extract(doc: Any, dotted: str) -> Any:
    """Walk ``a.b.c`` through nested dicts; None when any hop is
    missing (missing != zero — the caller distinguishes skip from
    fail)."""
    cur = doc
    for part in dotted.split("."):
        if not isinstance(cur, dict) or part not in cur:
            return None
        cur = cur[part]
    return cur


def _judge(check: Check, fresh: Any, committed: Any) -> tuple[bool, str]:
    """(ok, detail) for one cell; raises nothing — malformed values
    read as failures with the reason in ``detail``."""
    k = check.kind
    if k == "true":
        return bool(fresh), f"fresh={fresh!r}"
    if k == "abs_max":
        try:
            ok = float(fresh) <= float(check.threshold)
        except (TypeError, ValueError):
            return False, f"non-numeric fresh value {fresh!r}"
        return ok, f"fresh={fresh} <= {check.threshold}"
    if k == "eq":
        return fresh == committed, f"fresh={fresh!r} vs {committed!r}"
    # ratio kinds need both numbers
    try:
        f, c = float(fresh), float(committed)
    except (TypeError, ValueError):
        return False, (f"non-numeric value (fresh={fresh!r}, "
                       f"committed={committed!r})")
    if c == 0:
        return False, "committed value is 0 — ratio undefined"
    ratio = f / c
    if k == "ratio_min":
        return ratio >= float(check.threshold), (
            f"fresh/committed={ratio:.3f} >= {check.threshold}")
    if k == "ratio_max":
        return ratio <= float(check.threshold), (
            f"fresh/committed={ratio:.3f} <= {check.threshold}")
    return False, f"unknown check kind {k!r}"


def gate(fresh_dir: str | None, committed_dir: str = DEFAULT_COMMITTED,
         artifacts: list[str] | None = None,
         strict: bool = False) -> dict:
    """Run the gate; returns the machine-readable verdict document.

    ``fresh_dir=None`` self-diffs the committed artifacts (spec-path
    canary). ``artifacts`` filters to the named files. ``strict``
    turns missing fresh artifacts/paths into failures."""
    self_diff = fresh_dir is None
    fdir = committed_dir if self_diff else fresh_dir
    wanted = set(artifacts) if artifacts else None
    unknown = (wanted or set()) - set(SPECS)
    if unknown:
        raise ValueError(
            f"unknown artifacts {sorted(unknown)}; gated artifacts are "
            f"{sorted(SPECS)}")
    cells: list[dict] = []
    skipped: list[dict] = []

    def _load(path: str):
        try:
            with open(path) as f:
                return json.load(f)
        except (OSError, ValueError):
            return None

    for name in sorted(SPECS):
        if wanted is not None and name not in wanted:
            continue
        fresh_doc = _load(os.path.join(fdir, name))
        committed_doc = _load(os.path.join(committed_dir, name))
        if fresh_doc is None:
            skipped.append({"artifact": name,
                            "reason": "no fresh artifact"})
            continue
        if committed_doc is None:
            skipped.append({"artifact": name,
                            "reason": "no committed artifact"})
            continue
        for check in SPECS[name]:
            fv = extract(fresh_doc, check.path)
            cv = extract(committed_doc, check.path)
            row = {"artifact": name, "path": check.path,
                   "kind": check.kind, "threshold": check.threshold,
                   "fresh": fv, "committed": cv, "note": check.note}
            if fv is None:
                # a quick session regenerates SOME cells — absent ones
                # skip (e.g. a fresh ingest_bench with only the w2 cell)
                skipped.append({**row, "reason": "path missing in "
                                                 "fresh artifact"})
                continue
            if cv is None and check.kind in ("ratio_min", "ratio_max",
                                             "eq"):
                skipped.append({**row, "reason": "path missing in "
                                                 "committed artifact"})
                continue
            ok, detail = _judge(check, fv, cv)
            cells.append({**row, "ok": ok, "detail": detail})
    red = [c for c in cells if not c["ok"]]
    if strict and skipped:
        red = red + [{"ok": False, **s} for s in skipped]
    verdict = ("red" if red else ("green" if cells else "empty"))
    return {
        "verdict": verdict,
        "self_diff": self_diff,
        "fresh_dir": fdir,
        "committed_dir": committed_dir,
        "checked": len(cells),
        "failed": len(red),
        "skipped": len(skipped),
        "cells": cells,
        "skips": skipped,
    }


def main(argv=None) -> int:
    ap = argparse.ArgumentParser(
        prog="python -m neuroimagedisttraining_tpu.analysis.bench_gate",
        description=__doc__.split("\n\n")[0])
    ap.add_argument("--fresh", type=str, default=None,
                    help="directory of freshly produced bench_matrix "
                         "artifacts; omitted = self-diff the committed "
                         "dir (spec-path canary, trivially green)")
    ap.add_argument("--committed", type=str, default=DEFAULT_COMMITTED,
                    help="committed artifact directory (default "
                         "bench_matrix/)")
    ap.add_argument("--artifact", action="append", default=None,
                    help="gate only this artifact file name "
                         "(repeatable); default: every spec'd artifact")
    ap.add_argument("--strict", action="store_true",
                    help="missing fresh artifacts/paths fail instead "
                         "of skipping (full-matrix regeneration runs)")
    ap.add_argument("--json", type=str, default="",
                    help="also write the verdict document here")
    ap.add_argument("--quiet", action="store_true",
                    help="print only the one-line verdict summary, not "
                         "the full document")
    try:
        args = ap.parse_args(argv)
        res = gate(args.fresh, committed_dir=args.committed,
                   artifacts=args.artifact, strict=args.strict)
    except ValueError as e:
        print(f"bench_gate: {e}", file=sys.stderr)
        return 2
    if args.json:
        d = os.path.dirname(args.json)
        if d:
            os.makedirs(d, exist_ok=True)
        with open(args.json, "w") as f:
            json.dump(res, f, indent=1, sort_keys=True)
    if args.quiet:
        print(json.dumps({k: res[k] for k in
                          ("verdict", "checked", "failed", "skipped",
                           "self_diff")}))
    else:
        print(json.dumps(res, indent=1, default=str))
    return 0 if res["verdict"] != "red" else 1


if __name__ == "__main__":
    sys.exit(main())
