"""``held_overflow_calls`` (PR 30): the calls of Nemotron-H's held expert
layer that passed the held runs' buffer, read from the round driver's
``round_log`` span. A program without the counter (the parent of PR 30,
a CNN, OLMoE) reads ``None`` and the line leaves the metric out.
"""

import json
import os

import pytest

from benchmark import harness
from benchmark.metrics.held_overflow_calls import read

NAME = "held_overflow_calls"
SPEC = harness.read_json(os.path.join(harness.BENCH, "metrics",
                                      NAME + ".json"))["reader"]


@pytest.fixture
def tracer():
    from neuroimagedisttraining_tpu.obs import trace as obs_trace

    obs_trace.TRACER.arm()
    yield obs_trace.TRACER
    obs_trace.TRACER.disarm()


def _span(tracer, t0, t1, **args):
    epoch = tracer.epoch_ns / 1e9
    tracer.record_interval("round_log", epoch + t0, epoch + t1, **args)


def _window(tracer, w0, w1):
    epoch = tracer.epoch_ns / 1e9
    return (epoch + w0, epoch + w1)


@pytest.mark.parametrize("counts,median", [
    ((96, 0, 0, 96), 0.0), ((96, 0, 4, 96), 2.0), ((0, 96, 96, 0), 96.0)])
def test_the_median_of_the_windows_rounds(tracer, counts, median):
    """The rounds whose ``round_log`` starts inside the window (the
    second and third), whatever the first and the last read."""
    for r, calls in enumerate(counts):
        _span(tracer, 10.4 + 10 * r, 10.6 + 10 * r, round=r,
              held_overflow_calls=calls, held_capacity_rows=7680)
    assert SPEC == {"kind": "module", "span": "round_log", "arg": NAME}
    assert read(SPEC, {"window": _window(tracer, 15.0, 35.0)}) == median


def test_a_program_without_the_counter_reads_none(tracer):
    _span(tracer, 10.4, 10.6, round=0, tokens_routed=100, rows_held=6)
    assert read(SPEC, {"window": _window(tracer, 0.0, 99.0)}) is None
    # and a window that holds no round_log at all
    assert read(SPEC, {"window": _window(tracer, 50.0, 99.0)}) is None


def test_the_metric_is_the_last_of_the_benchmarks_and_the_cells_alone():
    with open(os.path.join(harness.ROOT, "BENCHMARK.json")) as f:
        bench = json.load(f)
    entry = bench["per_layer"][-1]
    assert entry == {
        "name": NAME, "unit": "count", "better": "lower",
        "source": "program_counter", "layer": "local step",
        "moves": "train_samples_per_s",
        "workloads": ["nemotronh.fedavg_fold3"]}
    assert [m["name"] for m in bench["per_layer"]].count(NAME) == 1


def test_the_round_driver_writes_what_the_reader_reads():
    """The argument's name on the span is the program's own
    (engines/fedavg.py), where the program has it."""
    fedavg = pytest.importorskip("neuroimagedisttraining_tpu.engines.fedavg")
    if not hasattr(fedavg, "HELD_OVERFLOW_CALLS"):
        pytest.skip("a program from before the counter")
    assert fedavg.HELD_OVERFLOW_CALLS == SPEC["arg"]
    import numpy as np

    args = fedavg.expert_load(np.ones(128), (0, 8), np.int32(3), 7680)
    assert args[NAME] == 3 and args["held_capacity_rows"] == 7680
