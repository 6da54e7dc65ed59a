"""Tests of the scope readers (CPU; no device number is produced here).

    python -m pytest benchmark/test_scopes.py -q -p no:cacheprovider
"""

from __future__ import annotations

import json
import os
import re

import pytest

from benchmark import harness, readers, round_spans, scopes, trace_reduce

BENCH = os.path.dirname(os.path.abspath(__file__))
DATA = os.path.join(BENCH, "testdata")
RECORDED = os.path.join(DATA, "tiny_v5e.xplane.pb")


@pytest.fixture(scope="module")
def hand():
    doc = trace_reduce.load_json(os.path.join(DATA, "hand_scoped.json"))
    return trace_reduce.reduce(doc), doc["meta"]


# ---------- the xplane's own op metadata ----------

def test_recorded_xplane_carries_the_ops_paths_flops_and_bytes():
    meta = scopes.xplane_op_meta(RECORDED)
    (fusion,) = [v for k, v in meta.items() if k.startswith("%fusion.1 = ")]
    assert fusion["tf_op"] == "jit(f)/conv_general_dilated:"
    assert scopes.scope_path(fusion["tf_op"]) \
        == "/jit(f)/conv_general_dilated/"
    assert fusion["flops"] == 983040
    assert fusion["bytes_accessed"] == 638976
    assert fusion["hlo_category"] == "loop fusion"
    # the keys are the op names trace_reduce loads: the join is exact
    ops = {e[0] for dev in trace_reduce.load_xplane(RECORDED)[
        "devices"].values() for e in dev["ops"]}
    assert ops <= set(meta)
    (conv,) = [v for k, v in meta.items()
               if k.startswith("%convolution_select_fusion = ")]
    assert conv["flops"] == 78200832


def test_the_recorded_trace_has_no_scope_of_the_program():
    """``jit(f)/conv_general_dilated`` holds no scope of the table: all of
    it is other_scoped but the ops without a path."""
    reduced = trace_reduce.reduce(trace_reduce.load_xplane(RECORDED),
                                  "bench:slice")
    table = scopes.build(reduced["ops_s"], scopes.xplane_op_meta(RECORDED))
    phase = table["share_pct"]["phase"]
    assert set(phase) == {scopes.OTHER, scopes.UNSCOPED}
    assert sum(phase.values()) == pytest.approx(100.0)
    assert table["busy_s"] == pytest.approx(sum(reduced["ops_s"].values()))


# ---------- the hand-made scoped trace ----------

def test_phase_shares_by_hand(hand):
    """10 ms of self time: gather 0.5, batch_prep 1.0, forward 2.0 + 0.5,
    backward 1.0 + 1.5 + 1.0, remat 0.5 (by instruction name, although its
    path says forward), optimizer 0.5, aggregate 0.5, eval 0.5, unscoped
    0.5; the while's 8 ms are its children's."""
    reduced, meta = hand
    table = scopes.build(reduced["ops_s"], meta)
    assert table["busy_s"] == pytest.approx(10e-3)
    assert table["share_pct"]["phase"] == pytest.approx({
        "backward": 35.0, "forward": 25.0, "batch_prep": 10.0,
        "gather": 5.0, "remat": 5.0, "optimizer": 5.0, "aggregate": 5.0,
        "eval": 5.0, "unscoped": 5.0})
    assert sum(table["share_pct"]["phase"].values()) == pytest.approx(100)
    assert table["ops"] == 13 and table["ops_with_path"] == 12


def test_stage_and_pool_shares_by_hand(hand):
    """stem = conv 2.0 + its pool forward 0.5 and backward 1.5 = 40%; f1 =
    backward 1.0 + the remat op 0.5; head 1.0; the evaluation's pass
    through the stem is outside the stage partition."""
    reduced, meta = hand
    share = scopes.build(reduced["ops_s"], meta)["share_pct"]
    assert share["stage"] == pytest.approx({
        "stem": 40.0, "f1": 15.0, "head": 10.0, "none": 30.0,
        "unscoped": 5.0})
    assert share["pool"] == pytest.approx({
        "pool": 20.0, "none": 75.0, "unscoped": 5.0})


def test_scopes_carry_flops_and_bytes(hand):
    reduced, meta = hand
    rows = {r[0]: r[1:] for r in
            scopes.build(reduced["ops_s"], meta)["scopes"]}
    stem = [k for k in rows if k.endswith("jvp(M)/stem/f0/conv")]
    assert len(stem) == 1
    assert rows[stem[0]] == pytest.approx([2e-3, 4000, 256])
    assert rows[scopes.UNSCOPED][0] == pytest.approx(0.5e-3)


def test_the_primitive_named_gather_is_not_the_scope():
    rules = scopes.load_rules()["phase"]
    assert scopes.classify(
        rules, "jit(r)/local_train/update/jit(_take)/gather:", "%g = x") \
        == "optimizer"
    assert scopes.classify(rules, "jit(r)/gather/jit(_take)/gather:",
                           "%g = x") == "gather"
    assert scopes.classify(rules, "jit(r)/jit(_take)/gather:", "%g = x") \
        == scopes.OTHER
    assert scopes.classify(rules, "", "%copy.1 = x") == scopes.UNSCOPED


# ---------- the same paths from HLO text ----------

HLO = """HloModule jit_round_fn, entry_computation_layout={()->f32[]}

%fused_computation (p: f32[8]) -> f32[8] {
  %p = f32[8]{0} parameter(0)
  ROOT %multiply.3 = f32[8]{0} multiply(f32[8]{0} %p, f32[8]{0} %p), metadata={op_name="jit(round_fn)/local_train/update/mul" source_file="x.py" source_line=3}
}

ENTRY %main () -> f32[8] {
  %fusion.1 = f32[8]{0} fusion(f32[8]{0} %c), kind=kLoop, calls=%fused_computation, metadata={op_name="jit(round_fn)/local_train/update/mul" source_file="x.py" source_line=3}
  ROOT %copy.2 = f32[8]{0} copy(f32[8]{0} %fusion.1)
}
"""


def test_live_join_finds_an_op_by_name_and_prefers_the_closer_text():
    live = scopes.hlo_op_meta(HLO, "jit_round_fn")
    assert live["fusion.1"][0][2] == "jit(round_fn)/local_train/update/mul"
    assert live["copy.2"][0][2] == ""
    other = scopes.hlo_op_meta(
        '  %fusion.1 = f32[4]{0} fusion(f32[4]{0} %c), kind=kLoop, '
        'calls=%f, metadata={op_name="jit(eval_all)/eval/add"}\n',
        "jit_eval_all")
    for name, rows in other.items():
        live.setdefault(name, []).extend(rows)
    traced = ("%fusion.1 = f32[8]{0} fusion(f32[8]{0} %c), kind=kLoop, "
              "calls=%fused_computation")
    meta = scopes.join_live([traced, "%copy.2 = f32[8]{0} copy(...)",
                             "%absent.9 = f32[] add(...)"], live)
    assert meta[traced] == {"tf_op": "jit(round_fn)/local_train/update/mul"}
    # a module that did not run in the trace is passed over, whatever its
    # text; one that is the only holder of the name is still taken
    ran = scopes.join_live([traced, "%copy.2 = f32[8]{0} copy(...)"], live,
                           ran={"jit_eval_all"})
    assert ran[traced] == {"tf_op": "jit(eval_all)/eval/add"}
    assert ran["%copy.2 = f32[8]{0} copy(...)"] == {"tf_op": ""}
    assert meta["%copy.2 = f32[8]{0} copy(...)"] == {"tf_op": ""}
    assert "%absent.9 = f32[] add(...)" not in meta


# ---------- the rules against the program's table of names ----------

def test_every_scope_of_the_program_has_a_class():
    from neuroimagedisttraining_tpu.obs import names

    doc = harness.read_json(scopes.RULES)
    listed = {s for k, v in doc["scope_names"].items() if k != "what"
              for s in v}
    assert listed == set(names.DEVICE_SCOPES)
    rules = scopes.load_rules()
    for cls, members in doc["scope_names"].items():
        if cls == "what":
            continue
        part = "phase" if cls in {r[0] for r in rules["phase"]} else "stage"
        for scope in members:
            got = scopes.classify(rules[part], f"jit(f)/{scope}/mul:",
                                  "%x = y")
            assert got == cls, (scope, got)


def test_reduce_is_unchanged_on_the_old_files():
    """``trace_reduce.reduce`` is what PR 22 left: the scope table is built
    from its ``ops_s`` and adds no key to it."""
    keys = {"window_s", "devices", "busy_s", "busy_s_max", "busy_s_min",
            "idle_share", "per_device", "ops_s", "modules_s",
            "idle_gaps_s", "host_spans_s", "between_main_idle_ms"}
    hand_trace = trace_reduce.reduce(trace_reduce.load_json(
        os.path.join(DATA, "hand_trace.json")), "bench:slice",
        harness.GAP_SPANS)
    recorded = trace_reduce.reduce(trace_reduce.load_xplane(RECORDED),
                                   "bench:slice")
    assert set(hand_trace) == keys and set(recorded) == keys
    assert hand_trace["busy_s"] == pytest.approx(9.6e-3)
    assert recorded["busy_s"] == pytest.approx(6.9559e-05)


# ---------- the readers ----------

def test_share_readers_read_the_table(hand):
    reduced, meta = hand
    ctx = {"trace": reduced, "scopes": scopes.build(reduced["ops_s"], meta)}
    bench = harness.read_json(os.path.join(harness.ROOT, "BENCHMARK.json"))
    got = {}
    for m in bench["per_layer"]:
        spec = harness.read_json(os.path.join(BENCH, "metrics",
                                              m["name"] + ".json"))
        if spec["reader"].get("partition"):
            got[m["name"]] = readers.read(spec["reader"], ctx, m["name"])
            assert m["unit"] == "%" and m["source"] == "device_trace"
    assert got == pytest.approx({
        "fwd_time_share_pct": 25.0, "bwd_time_share_pct": 35.0,
        "stem_time_share_pct": 40.0, "pool_time_share_pct": 20.0,
        "optimizer_time_share_pct": 5.0, "remat_time_share_pct": 5.0,
        "input_time_share_pct": 15.0, "aggregate_time_share_pct": 5.0,
        "unscoped_time_share_pct": 5.0})
    assert scopes.read({"partition": "phase", "classes": ["eval"]},
                       {"trace": None}) is None


def test_round_span_readers():
    from neuroimagedisttraining_tpu.obs import trace as obs_trace

    tracer = obs_trace.TRACER
    tracer.arm()
    try:
        t0 = tracer.epoch_ns / 1e9
        for r, (real, run) in enumerate(((49, 96), (49, 96))):
            b = t0 + r
            tracer.record_interval("round", b, b + 0.9, round=r)
            tracer.record_interval("dispatch_program", b + 0.01, b + 0.02,
                                   round=r, steps_real=real, steps_run=run)
            tracer.record_interval("eval_sync", b + 0.1, b + 0.8, round=r)
            tracer.record_interval("round_flush_sync", b + 0.8, b + 0.85,
                                   round=r)
        ctx = {"window": (t0 - 1.0, t0 + 5.0)}
        assert round_spans.round_host_busy_ms({}, ctx) \
            == pytest.approx(150.0)
        assert round_spans.padded_step_share_counted_pct({}, ctx) \
            == pytest.approx(100 * (1 - 49 / 96))
        # a program without the counts (the parent) gives nothing
        assert round_spans.padded_step_share_counted_pct(
            {}, {"window": (t0 + 10, t0 + 11)}) is None
        assert round_spans.round_host_busy_ms(
            {}, {"window": (t0 + 10, t0 + 11)}) is None
    finally:
        tracer.disarm()
