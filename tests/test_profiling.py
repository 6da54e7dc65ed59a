"""Profiler hooks + failure context (SURVEY §5.1/§5.3 aux subsystems)."""

import logging
import os

import jax
import jax.numpy as jnp
import pytest

from neuroimagedisttraining_tpu.utils.profiling import (
    failure_context, profile_trace,
)


@pytest.mark.slow  # tier-1 window (PR 7): heavy twin/artifact test, core pin covered by a lighter tier-1 sibling
def test_profile_trace_writes_artifacts(tmp_path):
    d = str(tmp_path / "trace")
    with profile_trace(d):
        with jax.profiler.TraceAnnotation("toy-span"):
            x = jnp.arange(128.0)
            (x * 2).block_until_ready()
    found = [f for _, _, fs in os.walk(d) for f in fs]
    assert found, "profiler produced no trace files"


def test_profile_trace_noop_when_disabled(tmp_path):
    with profile_trace("", enabled=False):
        pass  # must not raise or create anything


def test_failure_context_logs_and_tears_down(caplog):
    torn = []
    with pytest.raises(RuntimeError):
        with caplog.at_level(logging.ERROR):
            with failure_context(teardown=lambda: torn.append(1),
                                 name="boom-test"):
                raise RuntimeError("boom")
    assert torn == [1]
    assert any("boom-test" in r.message for r in caplog.records)
