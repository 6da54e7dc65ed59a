"""Readers of the round driver's own spans and counts.

The program records host spans around every stage of a round
(``neuroimagedisttraining_tpu/obs/trace.py``; names in ``obs/names.py``):
``round`` covers one whole loop iteration, its children share its ``round``
id, and the spans whose name ends in ``_sync`` are the only ones that wait
for the device. ``dispatch_program`` carries what the dispatched program
trains as host integers (``samples_real``, ``steps_real``, ``steps_run``).
A traced run arms the tracer (``harness.run_cell``); these readers take the
events of the measured window from it, on the harness's own clock
(``time.perf_counter``). A program without such a span or count, as the
parent of PR 23 is, gives ``None``.
"""

from __future__ import annotations

import statistics

ROUND = "round"
DISPATCH = "dispatch_program"
SYNC_SUFFIX = "_sync"


def window_events(ctx: dict) -> list[dict]:
    """The tracer's complete ("X") events inside the host-clock window,
    with ``t0``/``t1`` in ``time.perf_counter`` seconds."""
    from neuroimagedisttraining_tpu.obs import trace as obs_trace

    tracer = obs_trace.TRACER
    epoch = tracer.epoch_ns / 1e9
    w0, w1 = ctx["window"]
    out = []
    for e in tracer.events():
        if e.get("ph") != "X":
            continue
        t0 = epoch + e["ts"] / 1e6
        t1 = t0 + e["dur"] / 1e6
        if t0 >= w0 and t1 <= w1:
            out.append({**e, "t0": t0, "t1": t1})
    return out


def round_host_busy_ms(spec: dict, ctx: dict):
    """Median over the window's rounds of the ``round`` span's duration
    less the ``*_sync`` spans inside it: what the host does in a round
    while it is not waiting for the device."""
    events = window_events(ctx)
    syncs = [e for e in events if e["name"].endswith(SYNC_SUFFIX)]
    busy = []
    for r in events:
        if r["name"] != ROUND:
            continue
        waited = sum(s["t1"] - s["t0"] for s in syncs
                     if s["tid"] == r["tid"] and s["t0"] >= r["t0"]
                     and s["t1"] <= r["t1"])
        busy.append((r["t1"] - r["t0"]) - waited)
    return 1e3 * statistics.median(busy) if busy else None


def padded_step_share_counted_pct(spec: dict, ctx: dict):
    """``1 - steps_real / steps_run`` over the window's dispatches, from
    the counts on ``dispatch_program``."""
    rows = [e["args"] for e in window_events(ctx)
            if e["name"] == DISPATCH and e["args"].get("steps_run")]
    if not rows:
        return None
    real = sum(a["steps_real"] for a in rows)
    return 100.0 * (1.0 - real / sum(a["steps_run"] for a in rows))
