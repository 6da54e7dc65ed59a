"""obs/: the unified telemetry plane (ISSUE 9).

Covers the span tracer (nesting containment, thread safety, Chrome
trace-event schema, disarmed no-op), the metrics registry (Prometheus
exposition scraped from a LIVE in-process endpoint, histogram bucket
math, idempotent registration, JSONL sink, disable switch), the flight
recorder (bounded ring, dump schema, failure_context and upload-audit
dump triggers), the ExperimentLogger handler-leak regression, and the
legacy-surface parity pins: registry values == ``byte_stats()`` /
``upload_stats`` / ``stat_info`` on live smoke federations (no double
counting — the counters increment in lockstep with the legacy dicts,
not from a second measurement).
"""

import json
import logging
import re
import threading
import time
import urllib.request

import numpy as np
import pytest

from neuroimagedisttraining_tpu.distributed import message as M
from neuroimagedisttraining_tpu.obs import flight as obs_flight
from neuroimagedisttraining_tpu.obs import metrics as obs_metrics
from neuroimagedisttraining_tpu.obs import trace as obs_trace
from neuroimagedisttraining_tpu.obs.flight import FlightRecorder
from neuroimagedisttraining_tpu.obs.http import MetricsServer
from neuroimagedisttraining_tpu.obs.metrics import MetricsRegistry
from neuroimagedisttraining_tpu.obs.trace import SpanTracer


# ------------------------------------------------ span tracer


def test_span_nesting_containment(tmp_path):
    t = SpanTracer()
    t.arm(str(tmp_path / "t.json"), tags={"rank": 0})
    with t.span("outer", round=3):
        with t.span("inner"):
            pass
    doc = json.load(open(t.dump()))
    evs = doc["traceEvents"]
    outer = next(e for e in evs if e["name"] == "outer")
    inner = next(e for e in evs if e["name"] == "inner")
    # Chrome "X" events nest by time containment per tid — the property
    # Perfetto renders as parent/child
    assert outer["tid"] == inner["tid"]
    assert outer["ts"] <= inner["ts"]
    assert inner["ts"] + inner["dur"] <= outer["ts"] + outer["dur"]
    assert outer["args"] == {"rank": 0, "round": 3}


def test_span_thread_safety(tmp_path):
    t = SpanTracer()
    t.arm(str(tmp_path / "t.json"))
    N, MSPANS = 8, 50
    barrier = threading.Barrier(N)  # all alive together -> distinct
    # OS thread idents (a finished thread's ident is reusable)

    def worker(i):
        barrier.wait()
        for j in range(MSPANS):
            with t.span("w", thread=i, j=j):
                pass

    threads = [threading.Thread(target=worker, args=(i,))
               for i in range(N)]
    for th in threads:
        th.start()
    for th in threads:
        th.join()
    evs = t.events()
    assert len(evs) == N * MSPANS
    # every event intact (no torn/interleaved records) and thread ids
    # distinguish the tracks
    assert {e["args"]["thread"] for e in evs} == set(range(N))
    assert len({e["tid"] for e in evs}) == N
    json.load(open(t.dump()))  # parses


def test_tracer_disarmed_is_free_noop():
    t = SpanTracer()
    s1 = t.span("a", x=1)
    s2 = t.span("b")
    # disarmed: the SAME shared no-op object — no per-span allocation
    assert s1 is s2
    with s1:
        pass
    t.instant("never")
    assert t.events() == []
    assert t.dump() is None  # no path armed


def test_tracer_buffer_bounded(tmp_path):
    """A multi-hour armed run must not grow host memory without bound:
    events past the cap are dropped and counted in the dump."""
    t = SpanTracer()
    t.arm(str(tmp_path / "t.json"), max_events=5)
    for i in range(9):
        with t.span("s", i=i):
            pass
    assert len(t.events()) == 5
    doc = json.load(open(t.dump()))
    assert len(doc["traceEvents"]) == 5
    assert doc["nidtDroppedEvents"] == 4


def test_chrome_trace_event_schema(tmp_path):
    t = SpanTracer()
    t.arm(str(tmp_path / "t.json"), tags={"role": "server"})
    with t.span("round", round=0):
        pass
    t.instant("mark", k="v")
    doc = json.load(open(t.dump()))
    assert set(doc) == {"traceEvents", "displayTimeUnit",
                        "nidtClockAnchor"}
    # the arm instant on both clocks, read back to back
    anchor = doc["nidtClockAnchor"]
    assert anchor["perf_counter_ns"] == t.epoch_ns
    assert abs(anchor["time_ns"] - time.time_ns()) < 60e9
    for e in doc["traceEvents"]:
        assert e["ph"] in ("X", "i")
        assert isinstance(e["name"], str)
        assert isinstance(e["ts"], float) and e["ts"] >= 0
        assert isinstance(e["pid"], int) and isinstance(e["tid"], int)
        assert e["args"]["role"] == "server"
        if e["ph"] == "X":
            assert isinstance(e["dur"], float) and e["dur"] >= 0


@pytest.mark.parametrize("name", ["jax_trace", "jax_lower", "jax_compile"])
def test_jax_build_events_land_in_the_dump(tmp_path, name):
    """ISSUE 35: armed, JAX's own build events are "X" events of the same
    schema and tags as the spans around them; disarmed again, a build
    records nothing."""
    import jax
    import jax.numpy as jnp

    t = SpanTracer()
    t.arm(str(tmp_path / "t.json"), tags={"role": "server"})
    with t.span("round", round=4):
        jax.jit(lambda x: x * 2 + 1)(jnp.ones(3)).block_until_ready()
    t.disarm()
    n = len(t.events())
    jax.jit(lambda x: x * 3 - 1)(jnp.ones(3)).block_until_ready()
    assert len(t.events()) == n
    doc = json.load(open(t.dump()))
    mine = [e for e in doc["traceEvents"] if e["name"] == name]
    assert mine
    for e in mine:
        assert e["ph"] == "X" and e["args"]["role"] == "server"
        assert e["args"]["round"] == 4
        assert isinstance(e["args"]["program"], str) and e["args"]["program"]
        assert isinstance(e["ts"], float) and isinstance(e["dur"], float)
        assert e["dur"] >= 0


def test_jax_build_events_respect_the_buffer_cap():
    import jax
    import jax.numpy as jnp

    t = SpanTracer()
    t.arm(max_events=2)
    try:
        jax.jit(lambda x: x * 5 + 2)(jnp.ones(3)).block_until_ready()
    finally:
        t.disarm()
    assert len(t.events()) == 2  # trace, lower; the compile was dropped
    assert t._dropped >= 1


# ------------------------------------------------ metrics registry


def test_registry_idempotent_and_conflicts():
    reg = MetricsRegistry()
    c1 = reg.counter("x_total", "h", labelnames=("a",))
    c2 = reg.counter("x_total", "other help ignored", labelnames=("a",))
    assert c1 is c2
    with pytest.raises(ValueError, match="already registered as"):
        reg.gauge("x_total")
    with pytest.raises(ValueError, match="labels"):
        reg.counter("x_total", labelnames=("b",))
    with pytest.raises(ValueError, match="cannot decrease"):
        c1.inc(-1, a="1")
    with pytest.raises(ValueError, match="takes labels"):
        c1.inc(1)  # missing label
    # a histogram re-registered with DIFFERENT buckets must raise —
    # silently keeping the first spec would collapse the second
    # caller's range into +Inf with no signal
    reg.histogram("h", buckets=(1, 2))
    reg.histogram("h", buckets=(2, 1))  # same set, order-insensitive
    with pytest.raises(ValueError, match="buckets"):
        reg.histogram("h", buckets=(1, 10, 100))


def test_histogram_bucket_math():
    reg = MetricsRegistry()
    h = reg.histogram("lat", "h", buckets=(1.0, 2.0, 5.0))
    for v in (0.5, 1.0, 1.5, 2.0, 4.9, 100.0):
        h.observe(v)
    snap = reg.snapshot()["lat"]["values"][0]["value"]
    # le semantics: a value ON the bound lands IN that bucket
    assert snap["buckets"] == {"1": 2, "2": 2, "5": 1, "+Inf": 1}
    assert snap["count"] == 6
    assert snap["sum"] == pytest.approx(109.9)
    text = reg.prometheus_text()
    # exposition is CUMULATIVE per Prometheus histogram semantics
    assert 'lat_bucket{le="1"} 2' in text
    assert 'lat_bucket{le="2"} 4' in text
    assert 'lat_bucket{le="5"} 5' in text
    assert 'lat_bucket{le="+Inf"} 6' in text
    assert "lat_count 6" in text


def test_registry_disable_enable_switch():
    reg = MetricsRegistry()
    c = reg.counter("c_total")
    g = reg.gauge("g")
    h = reg.histogram("h", buckets=(1,))
    reg.disable()
    c.inc()
    g.set(5)
    h.observe(0.5)
    assert c.get() == 0 and g.get() == 0
    assert reg.snapshot()["h"]["values"] == []
    reg.enable()
    c.inc(2)
    assert c.get() == 2


def test_jsonl_sink(tmp_path):
    reg = MetricsRegistry()
    reg.counter("c_total").inc(3)
    p = str(tmp_path / "m.jsonl")
    reg.dump_jsonl(p, phase="a")
    reg.counter("c_total").inc()
    reg.dump_jsonl(p, phase="b")
    lines = [json.loads(ln) for ln in open(p)]
    assert len(lines) == 2
    assert lines[0]["phase"] == "a"
    assert lines[0]["metrics"]["c_total"]["values"][0]["value"] == 3
    assert lines[1]["metrics"]["c_total"]["values"][0]["value"] == 4


def test_nonfinite_values_render_canonically(tmp_path):
    """A NaN train_loss is reachable (losses diverge — that is why the
    non-finite guards exist): the exposition must use the canonical
    NaN/+Inf tokens, and the JSONL sink must stay strict-JSON."""
    reg = MetricsRegistry()
    reg.gauge("g_nan").set(float("nan"))
    reg.gauge("g_inf").set(float("inf"))
    text = reg.prometheus_text()
    assert "g_nan NaN" in text  # not repr()'s lowercase 'nan'
    assert "g_inf +Inf" in text  # not 'inf'
    p = str(tmp_path / "m.jsonl")
    reg.dump_jsonl(p)

    def _reject(tok):
        raise ValueError(f"bare {tok} token in JSONL")

    rec = json.loads(open(p).read(), parse_constant=_reject)
    assert rec["metrics"]["g_nan"]["values"][0]["value"] == "NaN"
    assert rec["metrics"]["g_inf"]["values"][0]["value"] == "+Inf"


_SAMPLE_RE = re.compile(
    r"^[a-zA-Z_:][a-zA-Z0-9_:]*(\{[^}]*\})? "
    r"(-?\d+(\.\d+)?([eE][+-]?\d+)?|\+Inf|-Inf|NaN)$")


def test_prometheus_exposition_live_scrape():
    """Scrape a LIVE in-process /metrics endpoint and validate the text
    exposition format line by line (+ /healthz and 404 routing)."""
    reg = MetricsRegistry()
    reg.counter("up_total", "uploads", labelnames=("outcome",)).inc(
        7, outcome='we"ird\nlabel')
    reg.gauge("occ", "occupancy").set(3)
    reg.histogram("tau", "staleness", buckets=(0, 1, 4)).observe(2)
    srv = MetricsServer(0, registry=reg,
                        health_probe=lambda: {"round": 5})
    try:
        base = f"http://127.0.0.1:{srv.port}"
        resp = urllib.request.urlopen(f"{base}/metrics")
        assert resp.headers["Content-Type"].startswith(
            "text/plain; version=0.0.4")
        body = resp.read().decode()
        for line in body.strip().splitlines():
            if line.startswith("#"):
                assert re.match(r"^# (HELP|TYPE) [a-zA-Z_:]", line)
            else:
                assert _SAMPLE_RE.match(line), line
        assert 'outcome="we\\"ird\\nlabel"' in body  # label escaping
        assert "occ 3" in body
        hz = json.loads(urllib.request.urlopen(f"{base}/healthz").read())
        assert hz["ok"] is True and hz["round"] == 5
        with pytest.raises(urllib.error.HTTPError):
            urllib.request.urlopen(f"{base}/nope")
    finally:
        srv.close()


# ------------------------------------------------ flight recorder


def test_flight_ring_bounded_and_dump_schema(tmp_path):
    fr = FlightRecorder(capacity=4)
    for i in range(7):
        fr.record("ev", i=i)
    assert [e["i"] for e in fr.events()] == [3, 4, 5, 6]
    out = fr.dump(str(tmp_path / "f.json"), reason="test")
    doc = json.load(open(out))
    assert doc["reason"] == "test"
    assert doc["capacity"] == 4 and doc["evicted"] == 3
    assert [e["i"] for e in doc["events"]] == [3, 4, 5, 6]
    for e in doc["events"]:
        assert e["kind"] == "ev"
        assert e["t_mono"] > 0 and e["t_wall"] > 0
    # resize keeps the newest events
    fr.configure(capacity=2)
    assert [e["i"] for e in fr.events()] == [5, 6]
    assert fr.dump() is None  # no path configured -> no dump


def test_failure_context_dumps_flight(tmp_path):
    from neuroimagedisttraining_tpu.utils.profiling import failure_context

    path = str(tmp_path / "flight.json")
    obs_flight.configure(capacity=64, path=path)
    try:
        with pytest.raises(RuntimeError, match="boom"):
            with failure_context(name="obs-test"):
                obs_flight.record("before_failure", x=1)
                raise RuntimeError("boom")
        doc = json.load(open(path))
        kinds = [e["kind"] for e in doc["events"]]
        assert "before_failure" in kinds and "failure" in kinds
        fail = next(e for e in doc["events"] if e["kind"] == "failure")
        assert fail["name"] == "obs-test"
        assert "RuntimeError: boom" in fail["error"]
    finally:
        obs_flight.configure(path="")
        obs_flight.clear()


# ------------------------------------------------ async-server parity


class _CaptureComm:
    """Minimal BaseCommManager stand-in (test_asyncfl.py idiom)."""

    def __init__(self):
        self.sent = []

    def send_message(self, msg, **kw):
        self.sent.append(msg)

    def add_observer(self, obs):
        pass

    def remove_observer(self, obs):
        pass

    def handle_receive_message(self):
        pass

    def stop_receive_message(self):
        pass

    def byte_stats(self):
        return {}


def _tree(seed, scale=1.0):
    rng = np.random.default_rng(seed)
    return {"params": {"w": (scale * rng.standard_normal(12)
                             ).astype(np.float32)}}


def _upload(sender, tree, n, version, seq=None):
    msg = M.Message(M.MSG_TYPE_C2S_SEND_MODEL, sender, 0)
    msg.add(M.ARG_MODEL_PARAMS, tree)
    msg.add(M.ARG_NUM_SAMPLES, float(n))
    msg.add(M.ARG_ROUND_IDX, int(version))
    if seq is not None:
        msg.add(M.ARG_UPLOAD_SEQ, int(seq))
    return msg


def _metric_value(snap, name, **labels):
    for v in snap[name]["values"]:
        if v["labels"] == {k: str(val) for k, val in labels.items()}:
            return v["value"]
    return None


def test_async_upload_stats_mirror_registry_exactly():
    """Every upload_stats bump goes through ONE helper that also bumps
    the registry counter — the audit dict and a /metrics scrape can
    never disagree (no double counting, no second measurement)."""
    from neuroimagedisttraining_tpu.asyncfl.server import (
        BufferedFedAvgServer,
    )

    obs_metrics.reset()
    srv = BufferedFedAvgServer(_tree(0), 10, 3, buffer_k=2,
                               max_staleness=1, comm=_CaptureComm())
    srv._on_model(_upload(1, _tree(1), 4.0, version=0, seq=0))
    srv._on_model(_upload(2, _tree(2), 5.0, version=0, seq=0))  # -> agg
    assert srv.round_idx == 1
    # duplicate (same seq), future tag, and an accepted stale upload
    srv._on_model(_upload(1, _tree(1), 4.0, version=0, seq=0))
    srv._on_model(_upload(1, _tree(3), 4.0, version=7, seq=1))
    srv._on_model(_upload(3, _tree(4), 6.0, version=0, seq=0))  # tau=1
    stats = dict(srv.upload_stats)
    assert stats["received"] == 5 and stats["dropped_duplicate"] == 1 \
        and stats["dropped_future"] == 1
    snap = obs_metrics.snapshot()
    for key, want in stats.items():
        got = _metric_value(snap, "nidt_async_uploads_total",
                            outcome=key)
        assert (got or 0) == want, (key, got, want)
    # staleness histogram saw exactly the accepted taus (0, 0, 1)
    tau = _metric_value(snap, "nidt_async_staleness")
    assert tau["count"] == stats["accepted"] == 3
    assert tau["buckets"]["0"] == 2 and tau["buckets"]["1"] == 1
    # buffer occupancy gauge tracks the live buffer
    assert _metric_value(snap, "nidt_async_buffer_occupancy") \
        == len(srv._buffer) == 1
    audit = srv.upload_audit()
    assert audit["received_accounted"] and audit["accepted_accounted"]


def test_upload_audit_failure_dumps_flight(tmp_path):
    from neuroimagedisttraining_tpu.asyncfl.server import (
        BufferedFedAvgServer,
    )

    obs_metrics.reset()
    path = str(tmp_path / "audit_flight.json")
    obs_flight.configure(capacity=64, path=path)
    try:
        srv = BufferedFedAvgServer(_tree(0), 10, 2, buffer_k=2,
                                   comm=_CaptureComm())
        srv._on_model(_upload(1, _tree(1), 4.0, version=0, seq=0))
        # simulate the accounting bug the audit exists to catch
        srv.upload_stats["received"] += 1
        audit = srv.upload_audit()
        assert not audit["received_accounted"]
        doc = json.load(open(path))
        kinds = [e["kind"] for e in doc["events"]]
        assert "audit_failure" in kinds
        assert "accept" in kinds  # the decisions leading up to it
    finally:
        obs_flight.configure(path="")
        obs_flight.clear()


# ------------------------------------------------ comm byte parity


def test_socket_byte_stats_mirror_registry(tmp_path):
    from neuroimagedisttraining_tpu.distributed.comm import (
        SocketCommManager,
    )
    from neuroimagedisttraining_tpu.distributed.ports import (
        free_port_block,
    )

    obs_metrics.reset()
    port = free_port_block(4)
    a = SocketCommManager(0, 2, base_port=port)
    b = SocketCommManager(1, 2, base_port=port)
    try:
        msg = M.Message("ping", 0, 1)
        msg.add("x", 123)
        a.send_message(msg)
        got = b._q.get(timeout=10)
        assert got.get("x") == 123
        snap = obs_metrics.snapshot()
        sa, sb = a.byte_stats(), b.byte_stats()
        assert sa["bytes_sent"] > 0
        assert _metric_value(snap, "nidt_comm_bytes_sent_total",
                             rank=0) == sa["bytes_sent"]
        assert _metric_value(snap, "nidt_comm_frames_sent_total",
                             rank=0) == sa["frames_sent"] == 1
        assert _metric_value(snap, "nidt_comm_bytes_recv_total",
                             rank=1) == sb["bytes_recv"]
        assert sa["bytes_sent"] == sb["bytes_recv"]
    finally:
        a.stop_receive_message()
        b.stop_receive_message()


# ------------------------------------------------ ExperimentLogger


def test_experiment_logger_handler_leak_fixed(tmp_path):
    """Regression (ISSUE 9 satellite): constructing twice with the same
    identity used to stack duplicate handlers on the name-cached logger
    and duplicate every line."""
    from neuroimagedisttraining_tpu.utils.logging import ExperimentLogger

    lg1 = ExperimentLogger(str(tmp_path), "synthetic", "leak_test")
    lg1.info("first line")
    lg2 = ExperimentLogger(str(tmp_path), "synthetic", "leak_test")
    underlying = logging.getLogger("nidt.exp.leak_test")
    # exactly one FileHandler + one StreamHandler, not 2 + 2
    assert len(underlying.handlers) == 2
    lg2.info("second line")
    lg2.close()
    text = open(lg2.log_path).read()
    assert text.count("second line") == 1


def test_logger_metrics_route_through_registry(tmp_path):
    from neuroimagedisttraining_tpu.utils.logging import ExperimentLogger

    obs_metrics.reset()
    lg = ExperimentLogger(str(tmp_path), "synthetic", "route_test",
                          console=False)
    lg.metrics(4, train_loss=1.5, nested={"acc": 0.75}, note="text")
    lg.close()
    snap = obs_metrics.snapshot()
    assert _metric_value(snap, "nidt_exp_metric",
                         key="train_loss") == 1.5
    assert _metric_value(snap, "nidt_exp_metric",
                         key="nested_acc") == 0.75
    assert _metric_value(snap, "nidt_exp_round") == 4
    # non-numeric values stay JSONL-only
    assert _metric_value(snap, "nidt_exp_metric", key="note") is None
    rec = json.loads(open(lg.jsonl_path).read().strip())
    assert rec["note"] == "text" and rec["round"] == 4


# ------------------------------------------------ engine smoke parity


def _build_engine(tmp_path, synthetic_cohort):
    from neuroimagedisttraining_tpu.config import (
        DataConfig, ExperimentConfig, FedConfig, OptimConfig,
    )
    from neuroimagedisttraining_tpu.core.trainer import LocalTrainer
    from neuroimagedisttraining_tpu.data.federate import federate_cohort
    from neuroimagedisttraining_tpu.engines import create_engine
    from neuroimagedisttraining_tpu.models import create_model
    from neuroimagedisttraining_tpu.parallel.mesh import make_mesh
    from neuroimagedisttraining_tpu.utils.logging import ExperimentLogger

    cfg = ExperimentConfig(
        model="3dcnn_tiny", num_classes=1, algorithm="fedavg",
        data=DataConfig(dataset="synthetic", partition_method="site"),
        optim=OptimConfig(lr=1e-3, batch_size=8, epochs=1),
        fed=FedConfig(client_num_in_total=4, comm_round=2,
                      frequency_of_the_test=1, ci=True),
        log_dir=str(tmp_path))
    mesh = make_mesh()
    fed, _ = federate_cohort(synthetic_cohort, partition_method="site",
                             mesh=mesh)
    trainer = LocalTrainer(create_model(cfg.model, num_classes=1),
                           cfg.optim, num_classes=1)
    log = ExperimentLogger(str(tmp_path), "synthetic", cfg.identity(),
                           console=False)
    return cfg, create_engine("fedavg", cfg, fed, trainer, mesh=mesh,
                              logger=log)


def test_engine_publish_stat_info_parity(tmp_path, synthetic_cohort):
    """Tier-1 pin of the publish path itself (the full-train smoke is
    the slow twin below): whatever the accumulators hold at a host
    boundary, the nidt_stat gauges equal it after publish."""
    obs_metrics.reset()
    _, engine = _build_engine(tmp_path, synthetic_cohort)
    engine.stat_info["sum_comm_bytes"] = 12345.0
    engine.stat_info["nonfinite_uploads"] = 2.0
    engine.publish_stat_info(3)
    snap = obs_metrics.snapshot()
    for k, v in engine.stat_info.items():
        if isinstance(v, (int, float)) and not isinstance(v, bool):
            assert _metric_value(snap, "nidt_stat", key=k) == float(v), k
    assert _metric_value(snap, "nidt_engine_round") == 3


@pytest.mark.slow  # tier-1 window (PR 9): full-train smoke twin; the
# publish-path parity pin above stays tier-1
def test_engine_stat_info_publishes_to_registry(tmp_path,
                                                synthetic_cohort):
    """Smoke federation: after train(), the registry's nidt_stat gauges
    equal the legacy stat_info accumulators (single source, gauge
    semantics — no double counting), and the round-metric gauges carry
    the last eval."""
    obs_metrics.reset()
    cfg, engine = _build_engine(tmp_path, synthetic_cohort)
    result = engine.train()
    assert np.isfinite(result["history"][-1]["train_loss"])
    snap = obs_metrics.snapshot()
    for k, v in engine.stat_info.items():
        if isinstance(v, (int, float)) and not isinstance(v, bool):
            assert _metric_value(snap, "nidt_stat", key=k) == float(v), k
    # ExperimentLogger.metrics routed the eval series through too
    assert _metric_value(snap, "nidt_exp_metric", key="train_loss") \
        is not None
    assert _metric_value(snap, "nidt_engine_round") == cfg.fed.comm_round - 1
