"""Profiling hooks + failure context.

SURVEY §5.1: the reference has no timeline profiler, only hook-based FLOPs
counting; the TPU equivalent it prescribes is ``jax.profiler`` traces (+ the
analytic FLOPs model in ops/flops.py). ``profile_trace`` wraps any span in a
TensorBoard-loadable trace capture (XLA ops, HBM, ICI); the CLI exposes it
as ``--profile_dir``.

SURVEY §5.3 / §2.7: the reference's failure handling is the
``raise_MPI_error`` context manager — log traceback, then
``MPI.COMM_WORLD.Abort()`` (fedml_api/utils/context.py:9-18).
``failure_context`` is the equivalent for our runtime: log, run the
registered teardown (e.g. a comm manager's stop, or
``jax.distributed.shutdown`` in multi-host mode), re-raise.
"""

from __future__ import annotations

import contextlib
import logging
import traceback
from typing import Callable


@contextlib.contextmanager
def profile_trace(log_dir: str | None, enabled: bool = True):
    """Capture a jax.profiler trace of the enclosed span into ``log_dir``
    (viewable in TensorBoard / XProf). No-op when disabled or dir empty."""
    if not (enabled and log_dir):
        yield
        return
    import jax

    with jax.profiler.trace(log_dir):
        yield


@contextlib.contextmanager
def failure_context(logger: logging.Logger | None = None,
                    teardown: Callable[[], None] | None = None,
                    name: str = "run"):
    """Log-then-teardown-then-reraise (raise_MPI_error parity,
    context.py:9-18 — minus the unsound process Abort: teardown is
    caller-supplied and the exception propagates)."""
    log = logger or logging.getLogger("neuroimagedisttraining_tpu")
    try:
        yield
    except Exception as exc:
        log.error("FATAL in %s:\n%s", name, traceback.format_exc())
        # flight-recorder post-mortem (obs/flight.py, ISSUE 9): the last
        # N control-plane decisions, dumped BEFORE teardown can destroy
        # more state; dumping must never mask the original exception
        try:
            from neuroimagedisttraining_tpu.obs import flight

            flight.record("failure", name=name,
                          error=f"{type(exc).__name__}: {exc}")
            out = flight.dump(reason=f"failure_context: {name}")
            if out:
                log.error("flight recorder dumped to %s", out)
            else:
                # no dump path configured (e.g. a silo rank): the
                # recorded decisions must not vanish — log the tail
                evs = flight.events()
                if evs:
                    log.error("no flight dump path configured; last "
                              "%d of %d flight events: %s",
                              min(20, len(evs)), len(evs), evs[-20:])
        except Exception:  # noqa: BLE001 — best-effort post-mortem
            pass
        if teardown is not None:
            try:
                teardown()
            except Exception:
                log.error("teardown after failure also failed:\n%s",
                          traceback.format_exc())
        raise
