"""The OLMoE cell's own readers (PR 25): what a window of one round gives.

A traced run of ``olmoe.fedavg_fold`` has a host-clock window of one round
(``min_rounds`` 4, two traced rounds and the profiler's lead round), and
the harness stamps a round's end inside that round's ``round_log`` span:
the counter reader has to take the span that STARTS in the window.
"""

import pytest

from benchmark import olmoe_scopes


@pytest.fixture
def tracer():
    from neuroimagedisttraining_tpu.obs import trace as obs_trace

    obs_trace.TRACER.arm()
    yield obs_trace.TRACER
    obs_trace.TRACER.disarm()


def _log_span(tracer, t0, t1, **args):
    """A ``round_log`` span ``[t0, t1]`` seconds after the tracer's
    epoch, on the ``time.perf_counter`` clock."""
    epoch = tracer.epoch_ns / 1e9
    tracer.record_interval("round_log", epoch + t0, epoch + t1, **args)


def _window(tracer, w0, w1):
    epoch = tracer.epoch_ns / 1e9
    return {"window": (epoch + w0, epoch + w1)}


SPEC = {"span": "round_log", "arg": "expert_load_max_over_mean"}


def test_span_arg_median_reads_a_one_round_window(tracer):
    # rounds end (the harness's stamps) at 10.5, 20.5, 30.5, each inside
    # its round_log span
    for r, load in enumerate((3.0, 2.0, 5.0)):
        _log_span(tracer, 10.4 + 10 * r, 10.6 + 10 * r,
                  expert_load_max_over_mean=load, round=r)
    one_round = _window(tracer, 10.5, 20.5)
    assert olmoe_scopes.span_arg_median(SPEC, one_round) == 2.0
    two_rounds = _window(tracer, 10.5, 30.5)
    assert olmoe_scopes.span_arg_median(SPEC, two_rounds) == 3.5


def test_span_arg_median_is_none_without_the_counter(tracer):
    _log_span(tracer, 10.4, 10.6, round=0)  # a CNN's round_log: no counter
    assert olmoe_scopes.span_arg_median(
        SPEC, _window(tracer, 0.0, 99.0)) is None
