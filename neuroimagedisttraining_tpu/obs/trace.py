"""Span tracer: host-side timeline, Chrome trace-event JSON out.

Frostig et al. 2018 (PAPERS.md, JAX/SysML): under asynchronous dispatch
the host thread races ahead of the accelerator, so host observability is
only meaningful at the host<->XLA seams the dispatch model defines — a
span here measures HOST time between dispatch boundaries (enqueue a
round program, block on an eval result), never device time, and must
never ADD a sync to read a clock. The complementary device timeline is
``jax.profiler`` (``--profile_dir``); the adapter below opens a matching
``jax.profiler.TraceAnnotation`` per span so the two line up in one
XProf/Perfetto view.

Design constraints (ISSUE 9):

- dependency-free: stdlib only; jax is imported lazily and only when the
  caller armed the annotation adapter.
- thread-safe: every server handler thread / selector loop / engine
  driver appends to one per-process buffer under a lock; events carry
  the OS thread id so Perfetto lays threads out as separate tracks.
- nestable: spans are ordinary context managers; Chrome "X" (complete)
  events nest by time containment per thread, so no explicit parent
  bookkeeping is needed (``tests/test_obs.py`` pins containment). One
  identifier is handed down: a span opened inside another on the same
  thread takes the outer span's ``round`` argument unless it names its
  own, so the spans of one round share its id without every call site
  being passed it (``SpanTracer.INHERITED``).
- off-by-default cheap: disarmed, ``span()`` returns a shared no-op
  context manager — no allocation, no clock read, one attribute test.

Output: ``{"traceEvents": [...], "displayTimeUnit": "ms"}`` with "X"
events ``{name, ph, ts, dur, pid, tid, args}`` (ts/dur in microseconds
since arm time, monotonic clock) — the Chrome trace-event format
Perfetto and ``chrome://tracing`` load directly. ``nidtClockAnchor``
holds the arm instant on both clocks (``perf_counter_ns`` and
``time_ns``, read back to back): a ``--trace_out`` file and a
``--profile_dir`` trace of one run, whose "Task Environment" plane
carries ``profile_start_time`` in unix nanoseconds, lie on one axis
through it.

JAX's own build events ride on the same axis (ISSUE 35): between
``arm()`` and ``disarm()``, and only then, ``jax.monitoring`` listeners
turn every trace, lowering, backend compile and persistent-cache fetch
into one "X" event (``jax_trace`` / ``jax_lower`` / ``jax_compile`` /
``jax_cache_fetch``, ``program=<fun_name>``, ``cache="hit"|"miss"`` on a
compile whose cache event preceded it, ``round`` from the compiling
thread's open spans). JAX stamps them on ``time.time()``; the clock
anchor above puts them on the tracer's. The round driver's ``train_init``
/ ``mask_phase`` / ``final_pass`` spans (engines/fedavg.py,
salientgrads.py) cover what a ``train()`` does outside its rounds, so an
operator's ``--trace_out`` file says where set-up went. Disarmed there is
no listener, no wrapper and no hook: the bridge is installed by ``arm()``
when ``jax`` is already imported, and this module never imports it at
module level.
"""

from __future__ import annotations

import json
import os
import sys
import threading
import time
import weakref
from typing import Any

from neuroimagedisttraining_tpu.obs import names as obs_names

__all__ = ["SpanTracer", "TRACER", "span", "instant", "flow", "arm",
           "disarm", "dump", "make_trace_ctx", "flow_id_of"]

#: flow-event phases (Chrome trace-event format): start / step / end —
#: Perfetto draws an arrow chain through the slices that enclose them
FLOW_PHASES = ("s", "t", "f")


def make_trace_ctx(rank: int, seq: int) -> dict:
    """Wire trace context (ISSUE 13): the Dapper lesson is that per-hop
    telemetry without PROPAGATED context cannot answer "where did this
    upload's latency go" — so the client stamps one of these on every
    upload frame (``distributed.message.ARG_TRACE_CTX``) and every hop
    (worker admission, root merge/aggregate) emits a flow event carrying
    the same id, turning one upload into a causally-linked Perfetto
    track. ``trace_id`` is unique per (sender, upload); ``span_id``
    names the sender's originating span."""
    return {"trace_id": (int(rank) << 24) | (int(seq) & 0xFFFFFF),
            "span_id": int(rank)}


def flow_id_of(ctx) -> int | None:
    """The Perfetto flow id of a wire trace context; None for a missing
    or malformed context (a version-skewed client must never crash a
    telemetry path)."""
    if not isinstance(ctx, dict):
        return None
    tid = ctx.get("trace_id")
    if isinstance(tid, bool) or not hasattr(tid, "__index__"):
        return None  # ints only (msgpack may hand back numpy scalars)
    return int(tid)


class _NullSpan:
    """Shared no-op context manager — the disarmed fast path."""

    __slots__ = ()

    def __enter__(self):
        return self

    def __exit__(self, *exc):
        return False


_NULL = _NullSpan()


class _Span:
    """One live span: records a Chrome "X" event on exit; optionally
    holds a matching ``jax.profiler.TraceAnnotation`` open for its
    lifetime (the host<->XLA alignment adapter)."""

    __slots__ = ("_tracer", "name", "args", "_t0", "_ann")

    def __init__(self, tracer: "SpanTracer", name: str, args: dict):
        self._tracer = tracer
        self.name = name
        self.args = args
        self._t0 = 0
        self._ann = None

    def __enter__(self):
        t = self._tracer
        t._inherit(self.args).append(self)
        if t._annotate:
            try:
                import jax

                self._ann = jax.profiler.TraceAnnotation(self.name)
                self._ann.__enter__()
            except Exception:  # noqa: BLE001 — tracing must never be
                # the thing that kills a run (no jax, profiler torn down)
                self._ann = None
        self._t0 = time.perf_counter_ns()
        return self

    def __exit__(self, *exc):
        t1 = time.perf_counter_ns()
        self._tracer._open_spans().pop()
        if self._ann is not None:
            try:
                self._ann.__exit__(*exc)
            except Exception:  # noqa: BLE001 — see __enter__
                pass
        self._tracer._record(self.name, self._t0, t1, self.args)
        return False


#: jax.monitoring time-span events (``start_time``, ``end_time`` on
#: ``time.time()``, ``fun_name``) -> the span each becomes
JAX_SPAN_EVENTS = {
    "/jax/core/compile/jaxpr_trace_duration": obs_names.SPAN_JAX_TRACE,
    "/jax/core/compile/jaxpr_to_mlir_module_duration":
        obs_names.SPAN_JAX_LOWER,
    "/jax/core/compile/backend_compile_duration":
        obs_names.SPAN_JAX_COMPILE,
}
#: the persistent cache's events, recorded by JAX from inside its
#: backend-compile event, without the program's name: held on the
#: compiling thread until that event closes and names them
JAX_CACHE_FETCH_EVENT = "/jax/compilation_cache/cache_retrieval_time_sec"
JAX_CACHE_EVENTS = {"/jax/compilation_cache/cache_hits": "hit",
                    "/jax/compilation_cache/cache_misses": "miss"}


class _JaxBridge:
    """The ``jax.monitoring`` listeners of one armed tracer. JAX calls
    a listener on the thread that traces / lowers / compiles, after the
    work, with clock reads it already took: each becomes one complete
    event through ``SpanTracer._record``, nothing is timed twice. The
    registry holds the bridge and the bridge only a weak reference to
    its tracer: a tracer dropped while armed takes its listeners along."""

    def __init__(self, tracer: "SpanTracer", monitoring):
        self._tracer = weakref.ref(tracer)
        self._monitoring = monitoring
        self._local = threading.local()  # the compile in flight's cache
        monitoring.register_event_time_span_listener(self.time_span)
        monitoring.register_event_duration_secs_listener(self.duration)
        monitoring.register_event_listener(self.event)
        self._finalizer = weakref.finalize(tracer, self.remove)
        self._finalizer.atexit = False

    def remove(self) -> None:
        m = self._monitoring
        self._finalizer.detach()
        for unregister, listener in (
                (m.unregister_event_time_span_listener, self.time_span),
                (m.unregister_event_duration_listener, self.duration),
                (m.unregister_event_listener, self.event)):
            try:
                unregister(listener)
            except (AssertionError, ValueError):
                # somebody's clear_event_listeners() was here first
                pass

    def event(self, event: str, **_: Any) -> None:
        outcome = JAX_CACHE_EVENTS.get(event)
        if outcome is not None:
            self._local.cache = outcome

    def duration(self, event: str, duration_secs: float, **_: Any) -> None:
        if event == JAX_CACHE_FETCH_EVENT:
            # a duration without a span: it ends now
            t1 = time.perf_counter_ns()
            self._local.fetch = (t1 - int(duration_secs * 1e9), t1)

    def time_span(self, event: str, start_time: float, end_time: float,
                  **kwargs: Any) -> None:
        name = JAX_SPAN_EVENTS.get(event)
        t = self._tracer()
        if name is None or t is None:
            return
        args = {"program": str(kwargs.get("fun_name", ""))}
        t._inherit(args)
        if name == obs_names.SPAN_JAX_COMPILE:
            pending = vars(self._local)  # this thread's
            fetch = pending.pop("fetch", None)
            if fetch is not None:
                t._record(obs_names.SPAN_JAX_CACHE_FETCH, *fetch,
                          dict(args))
            if "cache" in pending:
                args["cache"] = pending.pop("cache")
        t._record(name, t.from_unix_s(start_time), t.from_unix_s(end_time),
                  args)


class SpanTracer:
    """Per-process span buffer. Arm with an output path; every
    ``span()`` between arm and ``dump()`` lands in the trace. Tracer-
    level ``tags`` (rank, role, ...) merge into every event's args —
    the per-process key the multi-silo timeline is joined on."""

    #: event-buffer cap (~80 MB of dicts at ~300 B/event): a multi-hour
    #: armed run must not grow host memory without bound — events past
    #: the cap are DROPPED and counted (bounded-buffer honesty, the
    #: flight ring's rule), keeping the PREFIX of the run, which is
    #: what a Perfetto session of a long run gets opened on anyway
    DEFAULT_MAX_EVENTS = 1 << 18
    #: arguments a span hands down to the spans opened inside it on the
    #: same thread: the identifier the spans of one round share
    INHERITED = ("round",)

    def __init__(self):
        self._lock = threading.Lock()
        self._local = threading.local()
        self._events: list[dict] = []
        self._armed = False
        self._annotate = False
        self._path: str | None = None
        self._tags: dict[str, Any] = {}
        self._epoch_ns = time.perf_counter_ns()
        self._epoch_unix_ns = time.time_ns()
        self._max_events = self.DEFAULT_MAX_EVENTS
        self._dropped = 0
        self._jax_bridge: _JaxBridge | None = None

    # ---- lifecycle ----

    @property
    def armed(self) -> bool:
        return self._armed

    @property
    def epoch_ns(self) -> int:
        """The ``perf_counter_ns`` instant event timestamps are relative
        to — the rebase anchor the cross-process merge
        (``obs/fanin.py``) aligns worker timelines with."""
        return self._epoch_ns

    def from_unix_s(self, unix_s: float) -> int:
        """A ``time.time()`` reading on the tracer's clock
        (``perf_counter_ns``), through the pair read back to back at
        ``arm()``: the ``nidtClockAnchor`` of a dump."""
        return self._epoch_ns + int(unix_s * 1e9) - self._epoch_unix_ns

    def arm(self, path: str | None = None, *, annotate: bool = False,
            tags: dict | None = None,
            max_events: int | None = None) -> None:
        """Start recording. ``annotate=True`` additionally opens a
        ``jax.profiler.TraceAnnotation`` per span (use with
        ``--profile_dir`` so host spans appear on the XLA timeline);
        ``tags`` ride in every event's args; ``max_events`` caps the
        buffer (default ``DEFAULT_MAX_EVENTS``; excess events are
        dropped and counted in the dump's ``nidtDroppedEvents``). Where
        ``jax`` is already imported, JAX's build events are bridged into
        the buffer until ``disarm()`` (``_JaxBridge``)."""
        jax = sys.modules.get("jax")
        with self._lock:
            self._path = path
            self._annotate = bool(annotate)
            self._tags = dict(tags or {})
            self._epoch_ns = time.perf_counter_ns()
            self._epoch_unix_ns = time.time_ns()
            self._events.clear()
            self._max_events = (self.DEFAULT_MAX_EVENTS
                                if max_events is None
                                else int(max_events))
            self._dropped = 0
            self._armed = True
            if self._jax_bridge is None and jax is not None:
                self._jax_bridge = _JaxBridge(self, jax.monitoring)

    def disarm(self) -> None:
        with self._lock:
            self._armed = False
            self._annotate = False
            bridge, self._jax_bridge = self._jax_bridge, None
        if bridge is not None:
            bridge.remove()

    # ---- recording ----

    def _open_spans(self) -> list:
        """This thread's stack of live spans (armed path only)."""
        try:
            return self._local.stack
        except AttributeError:
            stack = self._local.stack = []
            return stack

    def _inherit(self, args: dict) -> list:
        """Hand ``args`` the ``INHERITED`` arguments of the span open on
        this thread, where it does not name its own; returns the stack."""
        stack = self._open_spans()
        if stack:
            outer = stack[-1].args
            for key in self.INHERITED:
                if key in outer and key not in args:
                    args[key] = outer[key]
        return stack

    def span(self, name: str, **args: Any):
        """Context manager for one host span. Disarmed: a shared no-op
        (no allocation, no clock read)."""
        if not self._armed:
            return _NULL
        return _Span(self, name, args)

    def instant(self, name: str, **args: Any) -> None:
        """Zero-duration marker (Chrome "i" instant event)."""
        if not self._armed:
            return
        ts = (time.perf_counter_ns() - self._epoch_ns) / 1e3
        with self._lock:
            if not self._armed:
                return
            if len(self._events) >= self._max_events:
                self._dropped += 1
                return
            self._events.append({
                "name": name, "ph": "i", "s": "t",
                "ts": ts, "pid": os.getpid(),
                "tid": threading.get_ident(),
                "args": {**self._tags, **args}})

    def _record(self, name: str, t0_ns: int, t1_ns: int,
                args: dict) -> None:
        ev = {
            "name": name, "ph": "X",
            "ts": (t0_ns - self._epoch_ns) / 1e3,
            "dur": (t1_ns - t0_ns) / 1e3,
            "pid": os.getpid(),
            "tid": threading.get_ident(),
            "args": {**self._tags, **args},
        }
        with self._lock:
            if not self._armed:
                return
            if len(self._events) >= self._max_events:
                self._dropped += 1
                return
            self._events.append(ev)

    def record_interval(self, name: str, t0_s: float, t1_s: float,
                        **args: Any) -> None:
        """One span from two ``time.perf_counter`` readings the caller
        already took (the streamed feed times its stages for
        ``transfer_stats``; the same reads become its spans, no second
        timer). Disarmed: one attribute test."""
        if self._armed:
            self._record(name, int(t0_s * 1e9), int(t1_s * 1e9), args)

    def flow(self, name: str, flow_id: int, phase: str,
             **args: Any) -> None:
        """One flow event (ISSUE 13): ``phase`` is "s" (start), "t"
        (step) or "f" (end). Perfetto binds each to the "X" slice
        enclosing its timestamp on that (pid, tid) and draws the arrow
        chain through slices sharing ``flow_id`` — emit INSIDE a live
        span. Flow ends carry ``bp: "e"`` (bind to enclosing slice)."""
        if not self._armed:
            return
        if phase not in FLOW_PHASES:
            raise ValueError(f"flow phase must be one of {FLOW_PHASES}, "
                             f"got {phase!r}")
        ts = (time.perf_counter_ns() - self._epoch_ns) / 1e3
        ev = {"name": name, "ph": phase, "cat": "flow",
              "id": int(flow_id), "ts": ts, "pid": os.getpid(),
              "tid": threading.get_ident(),
              "args": {**self._tags, **args}}
        if phase == "f":
            ev["bp"] = "e"
        with self._lock:
            if not self._armed:
                return
            if len(self._events) >= self._max_events:
                self._dropped += 1
                return
            self._events.append(ev)

    # ---- output ----

    def events(self) -> list[dict]:
        with self._lock:
            return list(self._events)

    def events_from(self, start: int) -> tuple[list[dict], int]:
        """Incremental read for periodic shipping (obs/fanin.py):
        events recorded since index ``start`` plus the new watermark.
        ``arm()`` clears the buffer, so shippers must reset their
        watermark when they re-arm."""
        with self._lock:
            evs = list(self._events[start:])
            return evs, start + len(evs)

    def dump(self, path: str | None = None) -> str | None:
        """Write the Chrome trace JSON; returns the path written (None
        when no path was armed or given, OR when the write failed —
        every caller dumps from a ``finally``, and an unwritable
        ``--trace_out`` must neither mask the run's real exception nor
        fail a successful run at exit; flight.dump keeps the same
        contract). Safe to call repeatedly — the buffer is kept, so a
        mid-run dump is a prefix of the final."""
        with self._lock:
            out = path or self._path
            if not out:
                return None
            doc = {"traceEvents": list(self._events),
                   "displayTimeUnit": "ms",
                   "nidtClockAnchor": {
                       "perf_counter_ns": self._epoch_ns,
                       "time_ns": self._epoch_unix_ns}}
            if self._dropped:
                # Perfetto ignores unknown top-level keys; the count
                # keeps a truncated long run honest
                doc["nidtDroppedEvents"] = self._dropped
        try:
            d = os.path.dirname(out)
            if d:
                os.makedirs(d, exist_ok=True)
            with open(out, "w") as f:
                json.dump(doc, f)
        except OSError:
            return None
        return out


#: the process-global tracer every instrumentation site records into
TRACER = SpanTracer()

#: module-level conveniences (the instrumentation-site spelling:
#: ``from neuroimagedisttraining_tpu.obs import trace`` then
#: ``with trace.span("eval", round=r): ...``)
span = TRACER.span
instant = TRACER.instant
flow = TRACER.flow
arm = TRACER.arm
disarm = TRACER.disarm
dump = TRACER.dump
