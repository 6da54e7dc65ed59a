"""Readers of per-layer metrics: a few generic kinds, chosen by data.

``benchmark/metrics/<name>.json`` holds ``{"reader": {"kind": ..., ...}}``.
``read(spec, ctx)`` returns the value, or ``None`` when there is nothing to
read in this run (no trace, no streamed feed, no such span): the harness
then leaves the metric out of the line. A metric that needs a new kind
brings ``benchmark/metrics/<name>.py`` with ``read(spec, ctx)`` of its own
and says ``{"kind": "module"}``.

``ctx`` is what one run knows (see ``harness.run_cell``): the harness
spans, the window on the host clock, the set-up counters, the reduced
device trace, the device peaks, counts from shapes, the feed's totals.
"""

from __future__ import annotations

import importlib.util
import os
import re
import statistics

from benchmark import trace_reduce

GIB = float(1 << 30)


def setup_counter(spec, ctx):
    """A counter of the set-up phase (``jax.monitoring``)."""
    return float(ctx["setup"][spec["key"]])


def span_median_ms(spec, ctx):
    """Median duration of a harness span inside the measured call."""
    rows = ctx["spans"].durations(spec["name"], *ctx["call"])
    return 1e3 * statistics.median(rows) if rows else None


def trace_value(spec, ctx):
    """A number of the reduced trace, scaled; ``per_round`` divides by the
    rounds in the traced slice."""
    tr = ctx["trace"]
    if tr is None or tr.get(spec["key"]) is None:
        return None
    value = float(tr[spec["key"]]) * float(spec.get("scale", 1.0))
    return value / tr["rounds"] if spec.get("per_round") else value


def trace_imbalance_pct(spec, ctx):
    """1 - least busy chip / busiest chip."""
    tr = ctx["trace"]
    if tr is None or not tr["busy_s_max"]:
        return None
    return 100.0 * (1.0 - tr["busy_s_min"] / tr["busy_s_max"])


def trace_op_share_pct(spec, ctx):
    """Share of device busy time in ops whose name matches ``pattern``."""
    if ctx["trace"] is None:
        return None
    share = trace_reduce.op_share(ctx["trace"], spec["pattern"],
                                  bool(spec.get("worst_chip")))
    return None if share is None else 100.0 * share


def trace_module_share_pct(spec, ctx):
    """Share of device busy time inside executed programs whose name
    matches ``pattern`` (the trace's "XLA Modules" line)."""
    tr = ctx["trace"]
    if tr is None or not tr["busy_s"]:
        return None
    rx = re.compile(spec["pattern"])
    return 100.0 * sum(v for k, v in tr["modules_s"].items()
                       if rx.search(k)) / tr["busy_s"]


def memory_peak_gib(spec, ctx):
    """The peak on the fullest chip (``harness.memory_peak``)."""
    return ctx["peak_bytes"] / GIB if ctx["peak_bytes"] else None


def count_pct(spec, ctx):
    """A share the harness counted from shapes."""
    return 100.0 * float(ctx["counts"][spec["key"]])


def flops_util_pct(spec, ctx):
    """Analytic training FLOPs of the real samples over what the chips
    could do: in the window's wall time (``over: wall``), or in the time
    the device was busy in the traced slice (``over: busy``)."""
    if ctx["peak"] is None or ctx["flops_per_sample"] is None:
        return None
    capacity = ctx["chips"] * ctx["peak"]["bf16_flops_per_s"]
    if spec["over"] == "wall":
        rate = ctx["samples_per_s"]
    else:
        tr = ctx["trace"]
        if tr is None or not tr["busy_s"]:
            return None
        rate = tr["real_samples"] / tr["busy_s"]
    return 100.0 * rate * ctx["flops_per_sample"] / capacity


def transfer_rate_gbps(spec, ctx):
    """``bytes`` over a stage's milliseconds, of the streamed feed's
    ``transfer_stats`` inside the window."""
    tr = ctx["transfer"]
    if not tr or not tr.get(spec["ms"]):
        return None
    return tr["bytes"] / 1e9 / (tr[spec["ms"]] / 1e3)


KINDS = {f.__name__: f for f in (
    setup_counter, span_median_ms, trace_value,
    trace_imbalance_pct, trace_op_share_pct, trace_module_share_pct,
    memory_peak_gib, count_pct, flops_util_pct, transfer_rate_gbps)}


def read(spec: dict, ctx: dict, name: str | None = None):
    if spec["kind"] == "module":
        path = os.path.join(os.path.dirname(os.path.abspath(__file__)),
                            "metrics", f"{name}.py")
        mod_spec = importlib.util.spec_from_file_location(
            "benchmark_metric_" + name.replace("-", "_").replace(".", "_"),
            path)
        module = importlib.util.module_from_spec(mod_spec)
        mod_spec.loader.exec_module(module)
        return module.read(spec, ctx)
    if spec["kind"] not in KINDS:
        raise ValueError(f"unknown reader kind {spec['kind']!r}; have "
                         f"{sorted(KINDS)} and 'module'")
    return KINDS[spec["kind"]](spec, ctx)
