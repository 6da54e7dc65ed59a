"""Readers that split ``setup_s`` by the program's own spans (PR 35).

While the span tracer is armed (``harness.run_cell`` arms it in a traced
run, after the engine is built), the program records JAX's build events as
spans on the tracer's clock (``neuroimagedisttraining_tpu/obs/trace.py``
``_JaxBridge``: ``jax_trace`` / ``jax_lower`` / ``jax_compile`` /
``jax_cache_fetch``, each with ``program=<fun_name>``) and, around what a
``train()`` does outside its rounds, ``train_init`` and ``final_pass``
(``engines/fedavg.py``, ``salientgrads.py``).

The set-up interval is from the tracer's arm instant (``TRACER.epoch_ns``)
to ``ctx["call"][0]``, the instant the measured ``train()`` is called: it
holds the whole warm-up ``train()`` and the harness's ``round_lr`` warm-up.
What lies before it (imports, device, cohort file, ``build_engine``) is
outside the tracer: the run's JSON has ``cohort.seconds`` and
``build_engine_s``. A span belongs to the interval when it lies inside it
whole. A program without these spans, as the parent of PR 35 is, gives
``None`` for every metric here.

``setup_programs`` also publishes the whole split and each lowered
program's seconds by stage, largest first, as one ``[setup]`` line on standard error
and one file under ``benchmark/out/setup/``; ``window_build_events`` does
the same (``[window_build]``) with the program, the round and the seconds
of whatever was built inside the window (the run's own JSON is the
harness's to write).
"""

from __future__ import annotations

import json
import os
import sys
import time

BENCH = os.path.dirname(os.path.abspath(__file__))

TRACE, LOWER, COMPILE, FETCH = (
    "jax_trace", "jax_lower", "jax_compile", "jax_cache_fetch")
BUILD = (TRACE, LOWER, COMPILE, FETCH)
TRAIN_INIT, FINAL_PASS, ROUND = "train_init", "final_pass", "round"


def tracer_events() -> list[dict]:
    """The tracer's complete ("X") events with ``t0``/``t1`` in
    ``time.perf_counter`` seconds, in starting order."""
    from neuroimagedisttraining_tpu.obs import trace as obs_trace

    epoch = obs_trace.TRACER.epoch_ns / 1e9
    out = [{**e, "t0": epoch + e["ts"] / 1e6,
            "t1": epoch + (e["ts"] + e["dur"]) / 1e6}
           for e in obs_trace.TRACER.events() if e.get("ph") == "X"]
    return sorted(out, key=lambda e: (e["t0"], -e["t1"]))


def setup_interval(ctx: dict) -> tuple[float, float]:
    from neuroimagedisttraining_tpu.obs import trace as obs_trace

    return obs_trace.TRACER.epoch_ns / 1e9, ctx["call"][0]


def setup_events(ctx: dict) -> list[dict] | None:
    """The events inside the set-up interval; ``None`` where the program
    bridges no build event and spans no ``train_init`` (the parent)."""
    events = tracer_events()
    if not any(e["name"] in BUILD or e["name"] == TRAIN_INIT
               for e in events):
        return None
    s0, s1 = setup_interval(ctx)
    return [e for e in events if e["t0"] >= s0 and e["t1"] <= s1]


def outermost(events: list[dict]) -> list[dict]:
    """Of events in starting order, those not inside an earlier one on the
    same thread."""
    kept: list[dict] = []
    end_of: dict = {}
    for e in events:
        if e["t1"] <= end_of.get(e["tid"], float("-inf")):
            continue
        end_of[e["tid"]] = e["t1"]
        kept.append(e)
    return kept


def _seconds(events) -> float:
    return sum(e["t1"] - e["t0"] for e in events)


def _named(events: list[dict] | None, name: str) -> list[dict] | None:
    return None if events is None else [e for e in events
                                        if e["name"] == name]


def _sum_of(ctx: dict, name: str):
    rows = _named(setup_events(ctx), name)
    return None if rows is None else _seconds(rows)


def _driver_sum_of(ctx: dict, name: str):
    """A driver span's seconds; ``None`` unless the program spans
    ``train_init`` (a parent with ``round`` spans alone reads nothing)."""
    events = setup_events(ctx)
    if not _named(events, TRAIN_INIT):
        return None
    return _seconds(_named(events, name))


def setup_trace_s(spec: dict, ctx: dict):
    """Seconds in ``jax_trace`` spans, the outermost only: a trace inside
    another on the same thread is an inner ``jit`` being traced."""
    rows = _named(setup_events(ctx), TRACE)
    return None if rows is None else _seconds(outermost(rows))


def setup_lower_s(spec: dict, ctx: dict):
    return _sum_of(ctx, LOWER)


def setup_cache_fetch_s(spec: dict, ctx: dict):
    return _sum_of(ctx, FETCH)


def setup_train_init_s(spec: dict, ctx: dict):
    return _driver_sum_of(ctx, TRAIN_INIT)


def setup_final_pass_s(spec: dict, ctx: dict):
    return _driver_sum_of(ctx, FINAL_PASS)


def setup_rounds_s(spec: dict, ctx: dict):
    return _driver_sum_of(ctx, ROUND)


def setup_unspanned_s(spec: dict, ctx: dict):
    """The set-up interval less the union of the spans inside it on the
    driver's thread (the one ``train_init`` was recorded on)."""
    events = setup_events(ctx)
    inits = _named(events, TRAIN_INIT)
    if not inits:
        return None
    s0, s1 = setup_interval(ctx)
    top = outermost([e for e in events if e["tid"] == inits[0]["tid"]])
    return (s1 - s0) - _seconds(top)


def programs_table(ctx: dict) -> list[dict]:
    """One row a distinct ``program`` lowered in the set-up interval, with
    its lowerings and its seconds by stage, the most seconds first. JAX
    names a trace by the function (``round_fn``) and what follows by the
    module (``jit(round_fn)``): an outermost trace is booked to the row of
    the module of its name."""
    events = setup_events(ctx) or []
    rows: dict[str, dict] = {}
    keys = {TRACE: "trace_s", LOWER: "lower_s", COMPILE: "compile_s",
            FETCH: "cache_fetch_s"}
    traces = outermost(_named(events, TRACE))
    for e in traces + [e for e in events if e["name"] in keys
                       and e["name"] != TRACE]:
        program = e["args"].get("program", "")
        if e["name"] == TRACE:
            program = f"jit({program})"
        row = rows.setdefault(program, {
            "lowerings": 0, **dict.fromkeys(keys.values(), 0.0),
            "cache": None})
        row["lowerings"] += e["name"] == LOWER
        row[keys[e["name"]]] += e["t1"] - e["t0"]
        row["cache"] = e["args"].get("cache", row["cache"])
    table = [{"program": p, **row} for p, row in rows.items()
             if row["lowerings"]]
    return sorted(table, key=lambda r: -(
        r["trace_s"] + r["lower_s"] + r["compile_s"]))


def setup_programs(spec: dict, ctx: dict):
    """Programs lowered before the window: the ``jax_lower`` spans."""
    rows = _named(setup_events(ctx), LOWER)
    if rows is None:
        return None
    s0, s1 = setup_interval(ctx)
    split = {name: fn({}, ctx) for name, fn in (
        ("setup_trace_s", setup_trace_s), ("setup_lower_s", setup_lower_s),
        ("setup_cache_fetch_s", setup_cache_fetch_s),
        ("setup_train_init_s", setup_train_init_s),
        ("setup_final_pass_s", setup_final_pass_s),
        ("setup_rounds_s", setup_rounds_s),
        ("setup_unspanned_s", setup_unspanned_s))}
    events = setup_events(ctx)
    _publish("setup", {
        "interval_s": s1 - s0, **split, "setup_programs": len(rows),
        "jax_compile_s": _seconds(_named(events, COMPILE)),
        "events": {name: len(_named(events, name)) for name in BUILD},
        "programs": programs_table(ctx)})
    return float(len(rows))


def window_build_table(ctx: dict, min_trace_s: float = 0.0) -> list[dict]:
    """The build spans that start inside a ``round`` span of the measured
    call, rounds 1 and later: which span, program, round and seconds. A
    ``jax_trace`` shorter than ``min_trace_s`` is left out: JAX fires its
    trace event on a miss of ``jit``'s own cache even where it then finds
    the jaxpr again (tens of microseconds; ``jax.random.fold_in`` called
    eagerly does so once a round, on the parent too)."""
    c0, c1 = ctx["call"]
    events = tracer_events()
    rounds = [e for e in events if e["name"] == ROUND and e["t0"] >= c0
              and e["t1"] <= c1 and e["args"].get("round", 0) >= 1]
    return [{"span": e["name"], "program": e["args"].get("program", ""),
             "round": r["args"]["round"], "seconds": e["t1"] - e["t0"]}
            for r in rounds for e in events
            if e["name"] in BUILD and e["tid"] == r["tid"]
            and r["t0"] <= e["t0"] < r["t1"]
            and (e["name"] != TRACE or e["t1"] - e["t0"] >= min_trace_s)]


def window_build_events(spec: dict, ctx: dict):
    """0 in a sound run: nothing is traced, lowered, compiled or fetched
    in a round of the measured call after its first."""
    if not any(e["name"] in BUILD for e in tracer_events()):
        return None
    table = window_build_table(ctx, float(spec.get("min_trace_s", 0.0)))
    if table:
        _publish("window_build", {"events": table})
    return float(len(table))


def _publish(kind: str, table: dict) -> None:
    line = json.dumps(table)
    print(f"[{kind}] " + line, file=sys.stderr, flush=True)
    out = os.path.join(BENCH, "out", "setup")
    os.makedirs(out, exist_ok=True)
    with open(os.path.join(
            out, f"{kind}_{time.strftime('%Y%m%dT%H%M%S')}_{os.getpid()}"
            ".json"), "w") as f:
        f.write(line)
