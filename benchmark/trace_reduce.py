"""Reduce a profiler trace to the benchmark's device numbers.

Input: the ``.xplane.pb`` that ``jax.profiler`` writes, read with
``jax.profiler.ProfileData`` (nothing but JAX), or the same content as a
small JSON file, which is what the hand-made test data is kept as. Both
become one neutral structure::

    {"devices": {"<plane name>": {"ops": [[name, start_ns, dur_ns], ...],
                                  "modules": [[name, start_ns, dur_ns], ...]}},
     "host": [[name, start_ns, dur_ns], ...]}

``ops`` is the device's "XLA Ops" line, ``modules`` its "XLA Modules" line
(one event per executed program), ``host`` every named host event that is
not a Python-tracer frame (``jax.profiler.TraceAnnotation`` spans, which is
what ``obs.trace.arm(annotate=True)`` and the harness open, and the
runtime's own).

Output of :func:`reduce`: per device the union of the op intervals (busy),
the idle share, self time by op (a ``while`` loop's time is its body's, not
counted twice), and every idle gap attributed to the host span that covers
it. All times are inside one window: the host span named ``window_span``
when the trace has it, else the extent of the device events.
"""

from __future__ import annotations

import json
import re
import statistics

OPS_LINE = "XLA Ops"
MODULES_LINE = "XLA Modules"
DEVICE_PLANE = re.compile(r"^/device:(TPU|GPU):\d+$")
HOST_PLANE = re.compile(r"^/host:")
#: idle gaps shorter than this are between two ops of one program and say
#: nothing about the host
MIN_GAP_NS = 20_000
TOP = 10
#: "jit_round_fn(1290209712970534945)" -> "jit_round_fn"
MODULE_RUN_ID = re.compile(r"\(\d+\)$")
#: an op's name in a TPU trace is its whole HLO instruction:
#: "%fusion.480 = bf16[16,59,71,59,256]{...} fusion(...), kind=kOutput, ..."
HLO_SHAPE = re.compile(r"\w+\[[\d,]*\]")
HLO_OPCODE = re.compile(r" ([a-z][a-z0-9\-]*)\(")  # layouts say T( and S(
HLO_KIND = re.compile(r"kind=(\w+)")
HLO_TARGET = re.compile(r'custom_call_target="([^"]+)"')


class NoDeviceOps(ValueError):
    """The trace holds no operation on any device."""


# ---------- loading ----------

def load_xplane(path: str) -> dict:
    from jax.profiler import ProfileData

    data = ProfileData.from_file(path)
    trace: dict = {"devices": {}, "host": []}
    for plane in data.planes:
        if DEVICE_PLANE.match(plane.name):
            dev = {"ops": [], "modules": []}
            for line in plane.lines:
                key = {OPS_LINE: "ops", MODULES_LINE: "modules"}.get(
                    line.name)
                if key:
                    dev[key] = [[e.name, int(e.start_ns),
                                 int(e.duration_ns)] for e in line.events]
            trace["devices"][plane.name] = dev
        elif HOST_PLANE.match(plane.name):
            for line in plane.lines:
                for e in line.events:
                    if e.name and not e.name.startswith("$"):
                        trace["host"].append([e.name, int(e.start_ns),
                                              int(e.duration_ns)])
    return trace


def load_json(path: str) -> dict:
    with open(path) as f:
        return json.load(f)


# ---------- interval arithmetic ----------

def union(intervals: list[tuple[int, int]]) -> list[tuple[int, int]]:
    """Merge ``(start, end)`` intervals into disjoint sorted ones."""
    merged: list[list[int]] = []
    for s, e in sorted(intervals):
        if merged and s <= merged[-1][1]:
            merged[-1][1] = max(merged[-1][1], e)
        else:
            merged.append([s, e])
    return [(s, e) for s, e in merged]


def clip_each(events: list[list], t0: int, t1: int
              ) -> list[tuple[int, int]]:
    """Every event's interval clipped to the window, one for one (empty
    ones are ``(t, t)``)."""
    return [(min(max(s, t0), t1), max(min(s + d, t1), t0))
            for _, s, d in events]


def clip(intervals, t0: int, t1: int) -> list[tuple[int, int]]:
    return [(max(s, t0), min(e, t1)) for s, e in intervals
            if min(e, t1) > max(s, t0)]


def total(intervals) -> int:
    return sum(e - s for s, e in intervals)


def gaps(busy: list[tuple[int, int]], t0: int, t1: int
         ) -> list[tuple[int, int]]:
    """The complement of disjoint sorted ``busy`` inside ``[t0, t1]``."""
    out, at = [], t0
    for s, e in busy:
        if s > at:
            out.append((at, s))
        at = max(at, e)
    if t1 > at:
        out.append((at, t1))
    return out


def self_times(events: list[list], t0: int, t1: int) -> dict[str, int]:
    """Self time by op name inside the window: an event's duration minus
    the part its children (events nested inside it on the same line)
    cover. Events are clipped to the window first."""
    evs = sorted(((max(s, t0), min(s + d, t1), n) for n, s, d in events
                  if min(s + d, t1) > max(s, t0)),
                 key=lambda e: (e[0], -e[1]))
    out: dict[str, int] = {}
    stack: list[list] = []  # [end, name, self_ns]

    def close(upto: int) -> None:
        while stack and stack[-1][0] <= upto:
            _, name, self_ns = stack.pop()
            out[name] = out.get(name, 0) + self_ns

    for s, e, n in evs:
        close(s)
        if stack:
            e = min(e, stack[-1][0])  # a child never outlives its parent
            stack[-1][2] -= e - s
        stack.append([e, n, e - s])
    close(t1 + 1)
    return out


def covering_span(gap: tuple[int, int], host: list[list]) -> str:
    """The shortest host span that covers at least half of the gap."""
    g0, g1 = gap
    best, best_dur = "unspanned", None
    for name, s, d in host:
        overlap = min(s + d, g1) - max(s, g0)
        if overlap * 2 >= g1 - g0 and (best_dur is None or d < best_dur):
            best, best_dur = name, d
    return best


# ---------- the reduction ----------

def window_of(trace: dict, window_span: str | None) -> tuple[int, int]:
    if window_span:
        spans = [(s, s + d) for n, s, d in trace["host"] if n == window_span]
        if spans:
            return min(s for s, _ in spans), max(e for _, e in spans)
    edges = [(s, s + d) for dev in trace["devices"].values()
             for _, s, d in dev["ops"]]
    if not edges:
        raise NoDeviceOps("the trace holds no device operation")
    return min(s for s, _ in edges), max(e for _, e in edges)


def dominant_module(modules: list[list], t0: int, t1: int) -> str | None:
    by: dict[str, int] = {}
    for n, s, d in modules:
        if s >= t0 and s + d <= t1:
            by[n] = by.get(n, 0) + d
    return max(by, key=by.get) if by else None


def reduce(trace: dict, window_span: str | None = None,
           host_prefixes: tuple[str, ...] | None = None) -> dict:
    """See the module docstring. ``host_prefixes``: if given, only host
    events whose name starts with one of them are candidates for a gap's
    attribution (the harness passes its own and the program's span names,
    so that a gap is named by a layer and not by a runtime thread)."""
    if not any(dev["ops"] for dev in trace["devices"].values()):
        raise NoDeviceOps("the trace holds no device operation")
    t0, t1 = window_of(trace, window_span)
    host = [h for h in trace["host"]
            if h[1] < t1 and h[1] + h[2] > t0 and h[0] != window_span
            and (host_prefixes is None or h[0].startswith(host_prefixes))]
    per_device, ops_total, gap_rows, between = {}, {}, [], []
    modules_total: dict[str, int] = {}
    for name, dev in sorted(trace["devices"].items()):
        busy = clip(union([(s, s + d) for _, s, d in dev["ops"]]), t0, t1)
        busy_ns = total(busy)
        per_device[name] = {"busy_s": busy_ns / 1e9,
                            "idle_share": 1.0 - busy_ns / (t1 - t0)}
        own = self_times(dev["ops"], t0, t1)
        per_device[name]["ops_s"] = {k: v / 1e9 for k, v in own.items()}
        for op, ns in own.items():
            ops_total[op] = ops_total.get(op, 0) + ns
        for g in gaps(busy, t0, t1):
            if g[1] - g[0] >= MIN_GAP_NS:
                gap_rows.append((covering_span(g, host), g[1] - g[0]))
        # idle time between consecutive executions of the program that
        # takes most of the device's time (the round program)
        for (m0, m1), n in zip(clip_each(dev["modules"], t0, t1),
                               (m[0] for m in dev["modules"])):
            if m1 > m0:
                key = MODULE_RUN_ID.sub("", n)
                modules_total[key] = modules_total.get(key, 0) + m1 - m0
        main = dominant_module(dev["modules"], t0, t1)
        runs = sorted((s, s + d) for n, s, d in dev["modules"]
                      if n == main and s >= t0 and s + d <= t1)
        for (_, e0), (s1, _) in zip(runs, runs[1:]):
            between.append((s1 - e0) - total(clip(busy, e0, s1)))
        per_device[name].update(main_module=main, main_module_runs=len(runs))
    n_dev = max(1, len(per_device))
    busy_all = [d["busy_s"] for d in per_device.values()]
    gap_by: dict[str, int] = {}
    for label, ns in gap_rows:
        gap_by[label] = gap_by.get(label, 0) + ns
    host_by: dict[str, int] = {}
    for n, s, d in host:
        host_by[n] = host_by.get(n, 0) + min(s + d, t1) - max(s, t0)
    return {
        "window_s": (t1 - t0) / 1e9,
        "devices": len(per_device),
        "busy_s": sum(busy_all) / n_dev,
        "busy_s_max": max(busy_all, default=0.0),
        "busy_s_min": min(busy_all, default=0.0),
        "idle_share": 1.0 - sum(busy_all) / n_dev / ((t1 - t0) / 1e9),
        "per_device": per_device,
        # self seconds by op, averaged over devices
        "ops_s": {k: v / 1e9 / n_dev for k, v in ops_total.items()},
        # seconds inside each executed program, averaged over devices
        "modules_s": {k: v / 1e9 / n_dev for k, v in modules_total.items()},
        "idle_gaps_s": {k: v / 1e9 / n_dev for k, v in gap_by.items()},
        "host_spans_s": {k: v / 1e9 for k, v in host_by.items()},
        "between_main_idle_ms": (statistics.median(between) / 1e6
                                 if between else None),
    }


def op_share(reduced: dict, pattern: str, worst_chip: bool = False
             ) -> float | None:
    """Share of device busy (self) time in ops whose name matches: over
    all devices together, or on the device where it is largest."""
    rx = re.compile(pattern)

    def share(ops_s: dict) -> float | None:
        all_s = sum(ops_s.values())
        if not all_s:
            return None
        return sum(v for k, v in ops_s.items() if rx.search(k)) / all_s

    if not worst_chip:
        return share(reduced["ops_s"])
    shares = [share(d["ops_s"]) for d in reduced["per_device"].values()]
    shares = [x for x in shares if x is not None]
    return max(shares) if shares else None


def short_op(text: str) -> str:
    """A readable label of an op: name, opcode (with a fusion's kind or a
    custom call's target) and output shape, in place of the whole HLO
    instruction the trace prints as the op's name."""
    name, sep, rest = text.partition(" = ")
    shape, opcode = HLO_SHAPE.search(rest), HLO_OPCODE.search(" " + rest)
    if not (sep and shape and opcode):
        return text[:120]
    extra = HLO_KIND.search(rest) or HLO_TARGET.search(rest)
    return (f"{name} {opcode.group(1)}"
            f"{':' + extra.group(1) if extra else ''} -> {shape.group(0)}")


def breakdown(reduced: dict) -> dict:
    """The contract's ``breakdown``: at most ``TOP`` rows each."""
    def top(d, label=str):
        return [[label(k), v] for k, v in
                sorted(d.items(), key=lambda kv: -kv[1])[:TOP]]

    return {"device_ops": top(reduced["ops_s"], short_op),
            "idle_gaps": top(reduced["idle_gaps_s"])}
