"""Set-up measured from inside the program (ISSUE 35).

While the span tracer is armed, and only then, ``jax.monitoring`` listeners
turn JAX's trace / lower / compile / cache-fetch events into spans on the
tracer's clock (obs/trace.py ``_JaxBridge``); ``train_init`` and
``final_pass`` cover what a ``train()`` does outside its rounds. Disarmed,
the process has no listener of ours and builds exactly what the parent
built: the counts in ``PARENT_BUILDS`` were read on the parent's code
(commit cb0cad1) with ``count_builds``, and PR 34 was refused for moving
what they guard.
"""

import gc
import json
import os
import subprocess
import sys
import time

import jax
import jax.numpy as jnp
import pytest
from jax import monitoring
from jax._src import monitoring as jax_monitoring

from benchmark import setup_spans
from neuroimagedisttraining_tpu.engines import base as engines_base
from neuroimagedisttraining_tpu.obs import names
from neuroimagedisttraining_tpu.obs import trace as obs_trace
from tests.test_scopes import _engine

ENGINES = ("fedavg", "salientgrads")
BUILD_EVENTS = tuple(obs_trace.JAX_SPAN_EVENTS)
#: the registries' readers are not part of jax.monitoring's public face
LISTENER_GETTERS = (jax_monitoring.get_event_time_span_listeners,
                    jax_monitoring.get_event_duration_listeners,
                    jax_monitoring.get_event_listeners)


def _listeners() -> list[list]:
    return [get() for get in LISTENER_GETTERS]


def _end(e: dict) -> float:
    return e["ts"] + e["dur"]


# ---------- (a) the listeners exist between arm() and disarm() only ------

def test_arm_adds_the_bridge_and_disarm_removes_exactly_it():
    t = obs_trace.SpanTracer()
    before = _listeners()
    t.arm()
    try:
        added = [[cb for cb in now if cb not in was]
                 for now, was in zip(_listeners(), before)]
        assert [len(a) for a in added] == [1, 1, 1]
        assert all(cb.__self__ is t._jax_bridge for (cb,) in added)
        t.arm()  # armed again: the same bridge, nothing registered twice
        assert [len(now) - len(was)
                for now, was in zip(_listeners(), before)] == [1, 1, 1]
    finally:
        t.disarm()
    assert _listeners() == before
    assert t._jax_bridge is None
    t.disarm()  # twice is harmless
    assert _listeners() == before


def test_disarm_survives_a_cleared_registry():
    t = obs_trace.SpanTracer()
    kept = _listeners()
    t.arm()
    monitoring.clear_event_listeners()
    try:
        t.disarm()
        assert _listeners() == [[], [], []]
    finally:
        for cbs, register in zip(kept, (
                monitoring.register_event_time_span_listener,
                monitoring.register_event_duration_secs_listener,
                monitoring.register_event_listener)):
            for cb in cbs:
                register(cb)
    assert _listeners() == kept


def test_a_tracer_dropped_while_armed_takes_its_listeners_along():
    """Tests arm tracers of their own and let them go: the registry must
    not keep them, their buffers and their listeners alive."""
    before = _listeners()
    t = obs_trace.SpanTracer()
    t.arm()
    assert [len(now) - len(was)
            for now, was in zip(_listeners(), before)] == [1, 1, 1]
    del t
    gc.collect()
    assert _listeners() == before


def test_a_process_that_never_arms_has_no_listener():
    """The package imported, an engine module loaded, a program jitted: the
    three registries are as JAX made them."""
    code = (
        "import jax, jax.numpy as jnp\n"
        "from jax._src import monitoring as m\n"
        "import neuroimagedisttraining_tpu.engines.fedavg\n"
        "import neuroimagedisttraining_tpu.engines.salientgrads\n"
        "from neuroimagedisttraining_tpu.obs import trace\n"
        "jax.jit(lambda x: x + 1)(jnp.ones(3)).block_until_ready()\n"
        "with trace.span('round', round=0): pass\n"
        "assert not trace.TRACER.armed and trace.TRACER._jax_bridge is None\n"
        "print(len(m.get_event_time_span_listeners()),\n"
        "      len(m.get_event_duration_listeners()),\n"
        "      len(m.get_event_listeners()))\n")
    out = subprocess.run([sys.executable, "-c", code], check=True,
                         capture_output=True, text=True, timeout=300)
    assert out.stdout.split() == ["0", "0", "0"]


def test_arm_before_jax_is_imported_installs_nothing():
    code = (
        "import sys\n"
        "from neuroimagedisttraining_tpu.obs import trace\n"
        "assert 'jax' not in sys.modules\n"
        "trace.arm()\n"
        "assert trace.TRACER._jax_bridge is None\n"
        "assert 'jax' not in sys.modules\n"
        "trace.disarm()\n"
        "print('ok')\n")
    out = subprocess.run([sys.executable, "-c", code], check=True,
                         capture_output=True, text=True, timeout=300)
    assert out.stdout.split() == ["ok"]


# ---------- (b) a program's build, as spans of the span that called it ------

@pytest.fixture()
def built_in_a_round():
    """A jitted function first called inside a ``round`` span, armed."""
    def setup_spans_probe(x):
        return jnp.tanh(x) * 3.0

    t = obs_trace.SpanTracer()
    t.arm()
    try:
        t0 = time.perf_counter_ns()
        with t.span(names.SPAN_ROUND, round=7):
            jax.jit(setup_spans_probe)(jnp.ones(5)).block_until_ready()
        t1 = time.perf_counter_ns()
        events = t.events()
    finally:
        t.disarm()
    return t, events, (t0, t1)


@pytest.mark.parametrize("name,program", [
    (names.SPAN_JAX_TRACE, "setup_spans_probe"),
    (names.SPAN_JAX_LOWER, "jit(setup_spans_probe)"),
    (names.SPAN_JAX_COMPILE, "jit(setup_spans_probe)")])
def test_build_events_become_spans_of_the_round(built_in_a_round, name,
                                                program):
    t, events, (t0, t1) = built_in_a_round
    (round_span,) = [e for e in events if e["name"] == names.SPAN_ROUND]
    (e,) = [e for e in events
            if e["name"] == name and e["args"]["program"] == program]
    assert e["ph"] == "X" and e["args"]["round"] == 7
    assert set(e["args"]) <= set(names.ARGS_BY_SPAN[name]) | {"round"}
    assert e["tid"] == round_span["tid"]
    # JAX stamped it on time.time(); through the arm instant's pair of
    # clock reads it lies inside the span that was open, to 5 ms
    slack_us = 5e3
    assert round_span["ts"] - slack_us <= e["ts"]
    assert _end(e) <= _end(round_span) + slack_us
    assert (t0 - t.epoch_ns) / 1e3 - slack_us <= e["ts"]
    assert _end(e) <= (t1 - t.epoch_ns) / 1e3 + slack_us
    assert 0 <= e["dur"] <= (t1 - t0) / 1e3 + slack_us


def test_build_spans_come_in_build_order(built_in_a_round):
    _, events, _ = built_in_a_round
    mine = [e for e in events if e["name"] in names.JAX_BUILD_SPANS
            and "setup_spans_probe" in e["args"]["program"]]
    assert [e["name"] for e in sorted(mine, key=lambda e: e["ts"])] == [
        names.SPAN_JAX_TRACE, names.SPAN_JAX_LOWER, names.SPAN_JAX_COMPILE]


def test_cache_events_name_the_compile_that_follows():
    """The persistent cache's events carry no program: the bridge holds
    them on the compiling thread until the compile event names them."""
    t = obs_trace.SpanTracer()
    t.arm()
    try:
        now = time.time()
        with t.span(names.SPAN_ROUND, round=3):
            monitoring.record_event("/jax/compilation_cache/cache_hits")
            monitoring.record_event_duration_secs(
                obs_trace.JAX_CACHE_FETCH_EVENT, 0.25)
            monitoring.record_event_time_span(
                BUILD_EVENTS[2], now - 0.5, now, fun_name="jit_p")
            monitoring.record_event("/jax/compilation_cache/cache_misses")
            monitoring.record_event_time_span(
                BUILD_EVENTS[2], now - 0.1, now, fun_name="jit_q")
        monitoring.record_event_time_span(
            BUILD_EVENTS[2], now - 0.1, now, fun_name="jit_r")
        monitoring.record_event_time_span(
            "/jax/some/other_event", now - 0.1, now, fun_name="jit_s")
        events = [e for e in t.events()
                  if e["name"] in names.JAX_BUILD_SPANS]
    finally:
        t.disarm()
    fetch, p, q, r = events
    assert (fetch["name"], fetch["args"]) == (
        names.SPAN_JAX_CACHE_FETCH, {"program": "jit_p", "round": 3})
    assert fetch["dur"] == pytest.approx(0.25e6)
    assert p["args"] == {"program": "jit_p", "round": 3, "cache": "hit"}
    assert p["dur"] == pytest.approx(0.5e6, abs=1.0)
    # the fetch is reported from inside the compile event: it lies in it
    assert p["ts"] - 5e3 <= fetch["ts"] and _end(fetch) <= _end(p) + 5e3
    assert q["args"] == {"program": "jit_q", "round": 3, "cache": "miss"}
    assert r["args"] == {"program": "jit_r"}  # no cache event, no round


# ---------- (c) a train(): train_init, the rounds, final_pass ----------

@pytest.fixture(params=ENGINES)
def armed_train(request, tmp_path, synthetic_cohort):
    engine = _engine(tmp_path, synthetic_cohort, request.param,
                     comm_round=2)
    obs_trace.arm()
    try:
        t0 = time.perf_counter_ns()
        engine.train()
        t1 = time.perf_counter_ns()
        events = [e for e in obs_trace.TRACER.events() if e["ph"] == "X"]
        epoch = obs_trace.TRACER.epoch_ns
    finally:
        obs_trace.disarm()
    return request.param, events, ((t0 - epoch) / 1e3, (t1 - epoch) / 1e3)


@pytest.mark.parametrize("name", [names.SPAN_TRAIN_INIT, names.SPAN_ROUND,
                                  names.SPAN_FINAL_PASS])
def test_a_train_is_tiled_by_init_rounds_and_final_pass(armed_train, name):
    _, events, (t0, t1) = armed_train
    tiles = [e for e in events if e["name"] in (
        names.SPAN_TRAIN_INIT, names.SPAN_ROUND, names.SPAN_FINAL_PASS)]
    assert [e["name"] for e in tiles] == [
        names.SPAN_TRAIN_INIT, names.SPAN_ROUND, names.SPAN_ROUND,
        names.SPAN_FINAL_PASS]  # recorded as they close: in that order
    assert [e["args"].get("round") for e in tiles] == [None, 0, 1, None]
    for a, b in zip(tiles, tiles[1:]):
        assert _end(a) <= b["ts"]  # none overlaps the next
    # together they cover the call to within 50 ms (what lies between
    # them is a return, a loop header and fedavg's last flush)
    assert t0 <= tiles[0]["ts"] and _end(tiles[-1]) <= t1
    covered = sum(e["dur"] for e in tiles)
    assert (t1 - t0) - covered < 50e3
    assert sum(e["name"] == name for e in tiles) == (
        2 if name == names.SPAN_ROUND else 1)


def test_mask_phase_lies_inside_train_init(armed_train):
    engine, events, _ = armed_train
    phases = [e for e in events if e["name"] == names.SPAN_MASK_PHASE]
    if engine == "fedavg":
        assert phases == []
        return
    (phase,) = phases
    (init,) = [e for e in events if e["name"] == names.SPAN_TRAIN_INIT]
    assert init["ts"] <= phase["ts"] and _end(phase) <= _end(init)
    assert phase["tid"] == init["tid"] and phase["dur"] < init["dur"]


def test_every_build_span_of_a_round_lies_inside_it(armed_train):
    """One clock: what JAX stamped on ``time.time()`` during a round lies
    inside that round's span, and carries its id."""
    _, events, _ = armed_train
    rounds = {e["args"]["round"]: e for e in events
              if e["name"] == names.SPAN_ROUND}
    builds = [e for e in events if e["name"] in names.JAX_BUILD_SPANS]
    assert len(builds) > 10  # a first train() builds its programs
    in_round = [e for e in builds if "round" in e["args"]]
    assert in_round  # round 0 holds the round program's build
    for e in in_round:
        r = rounds[e["args"]["round"]]
        assert r["ts"] - 5e3 <= e["ts"] and _end(e) <= _end(r) + 5e3, e
    for e in builds:
        assert set(e["args"]) <= set(names.ARGS_BY_SPAN[e["name"]]) | {
            "round"}
    # the round program itself is named, and was built in round 0
    assert any(e["args"]["program"] == "jit(round_fn)"
               and e["args"]["round"] == 0 for e in in_round
               if e["name"] == names.SPAN_JAX_LOWER)


# ---------- (d) tracing off: the parent's builds, to the event ----------

#: two ``train()`` calls of one engine in a process of their own, as the
#: benchmark's harness makes them (warm-up, then the measured call),
#: tracing off: what a listener of the test's counts in the first call, read
#: on the PARENT's code (cb0cad1) with this same function. A wrapper that
#: changes a jitted callable's identity, a second initialisation or a hook
#: shows here before it shows as seconds on the chip (PR 34: +5.1 s and
#: +6.3 s of set-up, refused). In a process that has run other programs
#: the eager initialisation finds some of its operations built, so the
#: counts are those of a fresh process.
PARENT_BUILDS = {
    "fedavg": {"trace": 1731, "lower": 70, "compile": 70, "inits": 1,
               "built": 1},
    "salientgrads": {"trace": 1775, "lower": 125, "compile": 125,
                     "inits": 1, "built": 1},
}
#: what the second call may still trace (it lowers and compiles nothing):
#: the parent's too
PARENT_SECOND_TRACES = {"fedavg": 3, "salientgrads": 50}


def count_builds(case: str) -> dict:
    """Run in a fresh process (``two_trains_disarmed``): the build events,
    ``init_global_state`` calls and ``RoundProgram.built`` of two
    ``train()`` calls, and the listeners the calls left registered."""
    import pathlib
    import tempfile

    from neuroimagedisttraining_tpu.data.synthetic import (
        generate_synthetic_abcd,
    )

    short = dict(zip(BUILD_EVENTS, ("trace", "lower", "compile")))
    counts = dict.fromkeys(short.values(), 0)
    inits = []
    inner = engines_base.FederatedEngine.init_global_state

    def init_global_state(self):
        inits.append(1)
        return inner(self)

    def count(event, start_time, end_time, **_):
        if event in short:
            counts[short[event]] += 1

    def take(engine, built_before):
        out = {**counts, "inits": len(inits),
               "built": engine.program.built - built_before}
        inits.clear()
        counts.update(dict.fromkeys(counts, 0))
        return out

    engines_base.FederatedEngine.init_global_state = init_global_state
    cohort = generate_synthetic_abcd(num_subjects=96, shape=(12, 14, 12),
                                     num_sites=4, seed=0)
    with tempfile.TemporaryDirectory() as tmp:
        engine = _engine(pathlib.Path(tmp), cohort, case, comm_round=2)
        before = _listeners()
        monitoring.register_event_time_span_listener(count)
        engine.train()
        first = take(engine, 0)
        engine.train()
        second = take(engine, first["built"])
        monitoring.unregister_event_time_span_listener(count)
    return {"first": first, "second": second,
            "armed": obs_trace.TRACER.armed,
            "listeners_left": [len(now) - len(was) for now, was in
                               zip(_listeners(), before)]}


@pytest.fixture(scope="module", params=ENGINES)
def two_trains_disarmed(request):
    code = ("import json, tests.conftest\n"
            "from tests.test_setup_spans import count_builds\n"
            f"print(json.dumps(count_builds({request.param!r})))\n")
    out = subprocess.run([sys.executable, "-c", code], check=True,
                         capture_output=True, text=True, timeout=600)
    return request.param, json.loads(out.stdout.strip().splitlines()[-1])


@pytest.mark.parametrize("what", ["trace", "lower", "compile", "inits",
                                  "built"])
def test_disarmed_builds_what_the_parent_built(two_trains_disarmed, what):
    engine, counted = two_trains_disarmed
    assert counted["first"][what] == PARENT_BUILDS[engine][what], counted


@pytest.mark.parametrize("what", ["lower", "compile", "built"])
def test_disarmed_second_train_builds_nothing(two_trains_disarmed, what):
    engine, counted = two_trains_disarmed
    assert counted["second"][what] == 0, counted
    assert counted["second"]["inits"] == 1  # eager, at every call
    assert counted["second"]["trace"] == PARENT_SECOND_TRACES[engine]


def test_disarmed_train_registers_no_listener(two_trains_disarmed):
    _, counted = two_trains_disarmed
    assert counted["listeners_left"] == [0, 0, 0]
    assert counted["armed"] is False


# ---------- (e) the benchmark's readers, on a hand-made event list ----------

def _x(name, t0_s, t1_s, tid=1, **args):
    return {"name": name, "ph": "X", "ts": t0_s * 1e6,
            "dur": (t1_s - t0_s) * 1e6, "pid": 1, "tid": tid, "args": args}


#: a set-up of 100 s (arm at 0, the measured call at 100) and a measured
#: call of three rounds
HAND_MADE = [
    # warm-up train(): train_init 1..11, rounds 11..41, final_pass 41..61
    _x("jax_trace", 2.0, 6.0, program="init"),
    _x("jax_trace", 3.0, 4.0, program="inner"),      # nested: not added
    _x("jax_trace", 3.5, 3.75, program="innermost"),  # nested twice
    _x("jax_trace", 3.0, 5.0, tid=2, program="other_thread"),
    _x("jax_lower", 6.0, 8.0, program="jit(init)"),
    _x("jax_cache_fetch", 8.0, 8.5, program="jit(init)"),
    _x("jax_compile", 8.0, 9.0, program="jit(init)", cache="hit"),
    _x("train_init", 1.0, 11.0),
    _x("jax_trace", 11.5, 13.5, program="round_fn", round=0),
    _x("jax_lower", 13.5, 16.5, program="jit(round_fn)", round=0),
    _x("jax_compile", 16.5, 20.5, program="jit(round_fn)", round=0,
       cache="miss"),
    _x("eval_sync", 22.0, 23.0, round=0),
    _x("round", 11.0, 25.0, round=0),
    _x("round", 25.0, 33.0, round=1),
    _x("round", 33.0, 41.0, round=2),
    _x("jax_lower", 42.0, 43.0, program="jit(init)"),  # the same name again
    _x("final_pass", 41.0, 61.0),
    # the harness's round_lr warm-up: top-level spans outside a train()
    _x("jax_trace", 70.0, 70.5, program="pow"),
    _x("jax_lower", 70.5, 71.0, program="jit(pow)"),
    _x("jax_compile", 71.0, 72.0, program="jit(pow)"),
    # a span straddling the measured call's start: left out of set-up
    _x("jax_lower", 99.5, 100.5, program="jit(straddler)"),
    # the measured call
    _x("train_init", 100.0, 105.0),
    _x("jax_trace", 105.5, 106.0, program="round_fn", round=0),
    _x("round", 105.0, 110.0, round=0),
    _x("jax_lower", 111.0, 111.5, program="jit(late)", round=1),
    # jit's own cache missed, the jaxpr was found again: 30 us, every round
    _x("jax_trace", 112.0, 112.00003, program="_threefry_fold_in", round=1),
    _x("round", 110.0, 115.0, round=1),
    _x("jax_cache_fetch", 118.0, 118.25, program="jit(late)", round=2),
    _x("jax_compile", 117.0, 118.5, program="jit(late)", round=2,
       cache="hit"),
    _x("round", 115.0, 120.0, round=2),
    _x("final_pass", 120.0, 130.0),
]

HAND_MADE_READS = {
    "setup_trace_s": 4.0 + 2.0 + 2.0 + 0.5,   # the outermost on each thread
    "setup_lower_s": 2.0 + 3.0 + 1.0 + 0.5,
    "setup_cache_fetch_s": 0.5,
    "setup_programs": 4,
    "setup_train_init_s": 10.0,
    "setup_final_pass_s": 20.0,
    "setup_rounds_s": 14.0 + 8.0 + 8.0,
    # 100 s less train_init 10, rounds 30, final_pass 20, the round_lr
    # warm-up's three spans 2: the straddler covers nothing of it
    "setup_unspanned_s": 100.0 - 62.0,
    "window_build_events": 3,  # rounds 1 and 2 of the measured call
}


@pytest.fixture()
def hand_made_ctx(monkeypatch):
    t = obs_trace.SpanTracer()
    t.arm()
    t.disarm()
    t._events = [dict(e) for e in HAND_MADE]
    monkeypatch.setattr(obs_trace, "TRACER", t)
    epoch = t.epoch_ns / 1e9
    return {"call": (epoch + 100.0, epoch + 130.0),
            "window": (epoch + 110.0, epoch + 120.0)}


@pytest.mark.parametrize("metric", sorted(HAND_MADE_READS))
def test_reader_on_a_hand_made_event_list(hand_made_ctx, metric):
    # the reader's own parameters, as harness.run_cell hands them over
    spec = json.load(open(os.path.join(
        os.path.dirname(setup_spans.__file__), "metrics",
        metric + ".json")))["reader"]
    got = getattr(setup_spans, metric)(spec, hand_made_ctx)
    assert got == pytest.approx(HAND_MADE_READS[metric], abs=1e-6)


def test_window_build_events_counts_every_trace_when_told_to(hand_made_ctx):
    assert setup_spans.window_build_events({}, hand_made_ctx) == 4.0


@pytest.mark.parametrize("metric", sorted(HAND_MADE_READS))
def test_reader_gives_none_without_spans(monkeypatch, metric):
    """The parent's program: rounds and evaluations, none of the spans
    this PR adds."""
    t = obs_trace.SpanTracer()
    t.arm()
    t.disarm()
    t._events = [dict(e) for e in HAND_MADE
                 if e["name"] in ("round", "eval_sync")]
    monkeypatch.setattr(obs_trace, "TRACER", t)
    epoch = t.epoch_ns / 1e9
    ctx = {"call": (epoch + 100.0, epoch + 130.0),
           "window": (epoch + 110.0, epoch + 120.0)}
    assert getattr(setup_spans, metric)({}, ctx) is None


def test_programs_table_names_the_largest_first(hand_made_ctx):
    table = setup_spans.programs_table(hand_made_ctx)
    assert [row["program"] for row in table] == [
        "jit(round_fn)", "jit(init)", "jit(pow)"]  # lowered ones only
    assert table[0] == {"program": "jit(round_fn)", "lowerings": 1,
                        "trace_s": pytest.approx(2.0),
                        "lower_s": pytest.approx(3.0),
                        "compile_s": pytest.approx(4.0),
                        "cache_fetch_s": 0.0, "cache": "miss"}
    # the outermost trace only, and both lowerings of the name
    assert table[1]["trace_s"] == pytest.approx(4.0)
    assert table[1]["lowerings"] == 2
    assert table[1]["lower_s"] == pytest.approx(3.0)
    assert table[1]["cache_fetch_s"] == pytest.approx(0.5)
    in_window = setup_spans.window_build_table(hand_made_ctx, 1e-3)
    assert [(r["span"], r["program"], r["round"]) for r in in_window] == [
        ("jax_lower", "jit(late)", 1), ("jax_compile", "jit(late)", 2),
        ("jax_cache_fetch", "jit(late)", 2)]
    assert [r["seconds"] for r in in_window] == pytest.approx(
        [0.5, 1.5, 0.25])
