"""One run of one cell: set-up, window, checks.

``run_cell`` builds the engine exactly as ``__main__.main`` does (its own
``add_args`` / ``config_from_args`` / ``run_mesh`` / ``build_experiment``),
calls ``engine.train()`` once to compile and warm every program, and once
more for the measured window. Nothing here names a cell, a configuration
or a traffic mix: those are the JSON files this module is handed.

From the program it takes the system under test and, from outside, three
seams: ``engine.log.metrics`` (the end of every round, with the round's
losses), the engine's evaluation / mask / feed methods (harness spans),
and the compiled-program boundary of ``engines/program.py`` (the shapes a
round program was called with, so that the compiled text can be read).
"""

from __future__ import annotations

import argparse
import contextlib
import dataclasses
import glob
import importlib.util
import json
import math
import os
import shutil
import statistics
import time

from benchmark import cohort, flops, readers, trace_reduce

BENCH = os.path.dirname(os.path.abspath(__file__))
ROOT = os.path.dirname(BENCH)
CACHE_DIR = os.path.join(BENCH, ".cache")
OUT_DIR = os.path.join(BENCH, "out")

KERNEL_MARK = "tpu_custom_call"
COLLECTIVES = ("all-reduce", "all-gather", "reduce-scatter",
               "collective-permute", "all-to-all")
SLICE_SPAN = "bench:slice"
#: engine methods the harness times from outside, where the engine has
#: them: (attribute path, span name, wait for the result on the device)
SPAN_SITES = (
    ("eval_global", "eval_global", False),
    ("eval_global_stream", "eval_global", False),
    ("eval_personalized", "eval_personalized", False),
    ("eval_personalized_stream", "eval_personalized", False),
    ("generate_global_mask", "mask_pipeline", True),
    ("stream.get_train", "feed_wait", False),
)
#: host spans a gap of the device may be named by: the harness's own and
#: the program's (obs/trace.py)
GAP_SPANS = ("bench:", "round", "dispatch", "eval_", "window")
REFERENCE_BATCH = 8
REFERENCE_ROWS_MAX = 1024


# ---------- files ----------

def read_json(path: str) -> dict:
    with open(path) as f:
        return json.load(f)


def load_cell(name: str) -> tuple[dict, dict, dict, dict]:
    """``(benchmark, cell, config, traffic)`` for a workload name of
    ``BENCHMARK.json``."""
    bench = read_json(os.path.join(ROOT, "BENCHMARK.json"))
    cells = {w["name"]: w for w in bench["workloads"]}
    if name not in cells:
        raise SystemExit(f"unknown workload {name!r}; have {sorted(cells)}")
    cell = cells[name]
    files = {c["name"]: c["file"] for c in bench["configs"]}
    config = read_json(os.path.join(ROOT, files[cell["config"]]))
    traffic = read_json(os.path.join(BENCH, "traffic",
                                     cell["traffic"] + ".json"))
    return bench, cell, config, traffic


def load_reference(config: dict):
    path = os.path.join(BENCH, "reference", config["reference"])
    spec = importlib.util.spec_from_file_location(
        "benchmark_reference_" + config["name"].replace("-", "_"), path)
    module = importlib.util.module_from_spec(spec)
    spec.loader.exec_module(module)
    return module


def site_sizes_of(config: dict, traffic: dict) -> list[int]:
    if "site_sizes" in traffic:
        return [int(n) for n in traffic["site_sizes"]]
    return [int(traffic["subjects_per_site"])] * int(config[traffic["sites"]])


def correct_bands(config: dict, traffic: dict) -> dict:
    """The cell's bands: the traffic file's, unless the configuration file
    brings its own for this traffic mix (so that a new configuration can
    join an existing mix without editing it)."""
    return config.get("correct", {}).get(traffic["name"],
                                         traffic["correct"])


# ---------- clocks ----------

class CompileClock:
    """Backend-compile seconds and persistent-cache hits and misses, from
    ``jax.monitoring`` (copied from ``chip_smoke.CompileClock``)."""

    def __init__(self):
        from jax import monitoring

        self.secs, self.compiles, self.hits, self.misses = 0.0, 0, 0, 0
        monitoring.register_event_duration_secs_listener(self._duration)
        monitoring.register_event_listener(self._event)

    def _duration(self, event, secs, **_):
        if event == "/jax/core/compile/backend_compile_duration":
            self.secs += secs
            self.compiles += 1

    def _event(self, event, **_):
        if event == "/jax/compilation_cache/cache_hits":
            self.hits += 1
        elif event == "/jax/compilation_cache/cache_misses":
            self.misses += 1

    def snapshot(self) -> dict:
        return {"compile_s": self.secs, "compiles": self.compiles,
                "cache_hits": self.hits, "cache_misses": self.misses}


def delta(after: dict, before: dict) -> dict:
    return {k: after[k] - before[k] for k in after}


class Spans:
    """Harness spans: ``(name, start, end)`` on ``time.perf_counter``,
    recorded around calls into the engine from outside. With
    ``annotate`` each also opens a ``jax.profiler.TraceAnnotation``
    (``bench:<name>``), which puts it on the profiler's clock."""

    def __init__(self, annotate: bool):
        self.rows: list[tuple[str, float, float]] = []
        self.annotate = annotate

    @contextlib.contextmanager
    def span(self, name: str):
        ann = None
        if self.annotate:
            import jax

            ann = jax.profiler.TraceAnnotation("bench:" + name)
            ann.__enter__()
        t0 = time.perf_counter()
        try:
            yield
        finally:
            self.rows.append((name, t0, time.perf_counter()))
            if ann is not None:
                ann.__exit__(None, None, None)

    def wrap(self, owner, attr: str, name: str, sync: bool) -> None:
        fn = getattr(owner, attr)

        def timed(*args, **kwargs):
            with self.span(name):
                out = fn(*args, **kwargs)
                if sync:
                    import jax

                    jax.block_until_ready(out)
                return out

        setattr(owner, attr, timed)

    def durations(self, name: str, t0: float, t1: float) -> list[float]:
        return [e - s for n, s, e in self.rows
                if n == name and s >= t0 and e <= t1]


class ProgramSpy:
    """Sits at the compiled-program boundary (``RoundProgram.
    _count_dispatches``): remembers the abstract arguments each jitted
    program was first called with, so that its compiled text and memory
    can be read afterwards, outside the window."""

    def __init__(self, program, spans: Spans):
        self.seen: dict[str, tuple] = {}
        inner = program._count_dispatches
        spy = self

        def count_dispatches(jitted, label="round", **kwargs):
            def call(*args):
                if label not in spy.seen:
                    spy.seen[label] = (jitted, _abstract(args))
                with spans.span("dispatch:" + label):
                    return jitted(*args)

            call.lower = jitted.lower
            return inner(call, label=label, **kwargs)

        program._count_dispatches = count_dispatches

    def compiled(self) -> dict[str, object]:
        return {label: jitted.lower(*args).compile()
                for label, (jitted, args) in self.seen.items()}


def _abstract(args):
    import jax

    def one(x):
        if not isinstance(x, jax.Array):
            return x
        # an uncommitted array (fresh from init) goes where jit puts it
        return jax.ShapeDtypeStruct(
            x.shape, x.dtype,
            sharding=x.sharding if x.committed else None)

    return jax.tree.map(one, args)


class RoundLog:
    """The end of every round, by ``engine.log.metrics``: the host reads
    the round's loss there, so the device has finished the round."""

    def __init__(self, engine, on_round_end=None):
        self.rows: list[dict] = []
        self.on_round_end = on_round_end
        inner = engine.log.metrics

        def metrics(round_idx, **values):
            inner(round_idx, **values)
            row = {"round": int(round_idx), "t": time.perf_counter(),
                   **values}
            self.rows.append(row)
            if self.on_round_end is not None:
                self.on_round_end(row)

        engine.log.metrics = metrics

    def take(self) -> list[dict]:
        rows, self.rows = self.rows, []
        return rows


# ---------- the engine ----------

def cell_argv(config: dict, traffic: dict, cohort_path: str, sites: int,
              seed: int, rounds: int, log_dir: str) -> list[str]:
    return [*config["argv"], *traffic["argv"],
            "--dataset", "abcd_h5", "--data_dir", cohort_path,
            "--partition_method", "site",
            "--client_num_in_total", str(sites), "--frac", "1.0",
            "--frequency_of_the_test", "1",
            "--seed", str(seed), "--comm_round", str(rounds),
            "--log_dir", log_dir]


def build_engine(argv: list[str]):
    """The engine ``main(argv)`` would build: same parser, config and mesh
    rule (copied from ``chip_smoke.hold_engine``)."""
    from neuroimagedisttraining_tpu.__main__ import (
        add_args, build_experiment, config_from_args, run_mesh,
    )

    args = add_args(argparse.ArgumentParser()).parse_args(argv)
    cfg = config_from_args(args)
    return build_experiment(cfg, streaming=args.streaming,
                            mesh=run_mesh(cfg, args.streaming),
                            console=False)


def set_rounds(engine, rounds: int) -> None:
    engine.cfg = dataclasses.replace(
        engine.cfg, fed=dataclasses.replace(engine.cfg.fed,
                                            comm_round=rounds))


def test_rows(engine):
    """Per client, the test split's rows ``(X uint8 [n, D, H, W], y [n])``
    as the engine holds them (resident arrays, or the streamed source)."""
    import numpy as np

    if engine.stream is not None:
        st = engine.stream
        for c in range(st.num_clients):
            idx = np.sort(st.test_map[c])
            if len(idx):
                yield np.asarray(st.X[idx]), st.y[idx]
        return
    data = engine.data
    n = np.asarray(data.n_test)
    for c in range(len(n)):
        if n[c]:
            yield (np.asarray(data.X_test[c, :n[c]]),
                   np.asarray(data.y_test[c, :n[c]]))


# ---------- checks ----------

def forward_check(engine, reference, config: dict) -> dict:
    """(a) The engine's evaluation loss at the initial weights against the
    plain float32 reference on the same rows: per client the mean loss,
    then the mean over clients, as ``engine.eval_global`` reports it."""
    import jax
    import numpy as np

    from benchmark.reference import ops as ref_ops

    gs = engine.init_global_state()
    evaluate = (engine.eval_global_stream if engine.stream is not None
                else engine.eval_global)
    got = float(evaluate(gs.params, gs.batch_stats)["loss"])

    @jax.jit
    def losses(params, stats, x, y):
        with jax.default_matmul_precision("highest"):
            return ref_ops.bce_with_logits(
                reference.forward(params, stats, x), y)

    per_client, rows = [], 0
    for X, y in test_rows(engine):
        rows += len(y)
        if rows > REFERENCE_ROWS_MAX:
            raise RuntimeError(
                f"the test split has more than {REFERENCE_ROWS_MAX} rows: "
                "the reference would not cover what eval_global does")
        pad = (-len(y)) % REFERENCE_BATCH
        Xp = np.concatenate([X, np.zeros((pad,) + X.shape[1:], X.dtype)])
        yp = np.concatenate([y, np.zeros((pad,), y.dtype)])
        out = [np.asarray(losses(gs.params, gs.batch_stats,
                                 Xp[i:i + REFERENCE_BATCH],
                                 yp[i:i + REFERENCE_BATCH]))
               for i in range(0, len(yp), REFERENCE_BATCH)]
        per_client.append(float(np.mean(np.concatenate(out)[:len(y)])))
    want = float(np.mean(per_client))
    rel = abs(got - want) / max(abs(want), 1e-12)
    tol = float(config["forward_check"]["rel_tol"])
    return {"ok": math.isfinite(got) and rel <= tol, "engine_loss": got,
            "reference_loss": want, "rel_diff": rel, "rel_tol": tol,
            "rows": rows}


def finite_losses(row: dict) -> bool:
    return all(math.isfinite(float(row[k])) for k in ("train_loss", "loss")
               if k in row)


def learning_check(rounds: list[dict], final: dict, bands: dict) -> dict:
    """(b) Every round's losses finite, the training loss at a fixed round
    under its band, the final global test AUC over its floor."""
    by_round = {r["round"]: r for r in rounds}
    at = int(bands["loss_round"])
    finite = all(finite_losses(r) for r in rounds)
    loss_at = (float(by_round[at]["train_loss"]) if at in by_round
               else float("nan"))
    auc = float(final["auc"])
    return {"ok": bool(finite and loss_at <= bands["train_loss_max"]
                       and auc >= bands["final_auc_min"]),
            "finite": finite, "loss_round": at, "train_loss": loss_at,
            "train_loss_max": bands["train_loss_max"], "final_auc": auc,
            "final_auc_min": bands["final_auc_min"]}


def fallbacks() -> dict:
    from neuroimagedisttraining_tpu.obs.health import fallback_block

    return {f"{r['plane']}/{r['engine']}/{r['reason']}": r["count"]
            for r in fallback_block()["announcements"]}


def mask_check(engine, result: dict, on_tpu: bool) -> dict:
    """SalientGrads: the mask's density, masked weights exactly zero in the
    final global model, and the Pallas threshold against the XLA one on the
    model's real score vector (copied from ``chip_smoke``)."""
    import jax
    import jax.numpy as jnp

    from neuroimagedisttraining_tpu.ops import snip as snip_ops
    from neuroimagedisttraining_tpu.ops.topk import kth_largest

    density = float(result["mask_density"])
    leaked = sum(int(jnp.sum((p != 0) & (m == 0))) for p, m in zip(
        jax.tree.leaves(result["params"]), jax.tree.leaves(result["masks"])))
    out = {"density": density, "masked_nonzero": leaked,
           "ok": 0.5 <= density <= 0.501 and leaked == 0}
    if on_tpu:
        gs = engine.init_global_state()
        flat = snip_ops.flat_weight_scores(
            engine.global_scores(gs.params, gs.batch_stats))
        flat = jax.device_put(flat / jnp.sum(flat), jax.devices()[0])
        k = max(1, int(flat.size * engine.cfg.sparsity.dense_ratio))
        pallas = float(kth_largest(flat, k, use_pallas=True))
        xla = float(kth_largest(flat, k, use_pallas=False))
        out.update(threshold_pallas=pallas, threshold_xla=xla,
                   ok=out["ok"] and pallas == xla and math.isfinite(pallas))
    return out


def program_check(spy: ProgramSpy, expect: dict, on_tpu: bool) -> dict:
    """(c) What the compiled round programs contain, and their bytes."""
    out: dict = {"programs": {}, "ok": True}
    for label, compiled in spy.compiled().items():
        text = compiled.as_text()
        mem = compiled.memory_analysis()
        row = {KERNEL_MARK: KERNEL_MARK in text,
               "collectives": [c for c in COLLECTIVES if c in text],
               "temp_bytes": getattr(mem, "temp_size_in_bytes", None),
               "argument_bytes": getattr(mem, "argument_size_in_bytes",
                                         None)}
        out["programs"][label] = row
        if on_tpu and row[KERNEL_MARK] != bool(expect["tpu_custom_call"]):
            out["ok"] = False
        if bool(row["collectives"]) != bool(expect["collective"]):
            out["ok"] = False
    if not out["programs"]:
        out["ok"] = False
    return out


# ---------- one run ----------

def run_cell(cell: dict, config: dict, traffic: dict, *, seed: int,
             seconds: float, trace: bool, t_process: float,
             peak: dict | None, per_layer: list[dict],
             rehearsal: bool = False) -> dict:
    """Run one cell once. ``peak`` is the device's row of ``peaks.json``
    (``None`` only in a rehearsal, which then reports no device number).
    ``per_layer`` are the ``BENCHMARK.json`` entries to read with
    ``--trace 1``."""
    import jax
    import numpy as np

    from neuroimagedisttraining_tpu.utils.compile_cache import (
        enable_compile_cache,
    )

    clock = CompileClock()
    cache_dir = enable_compile_cache()
    on_tpu = jax.devices()[0].platform == "tpu"
    info: dict = {"cell": cell["name"], "seed": seed, "seconds": seconds,
                  "trace": trace, "compile_cache_dir": cache_dir,
                  "jax": jax.__version__}

    # ----- set-up: cohort, engine, warm-up -----
    shape = tuple(config["input_shape"])
    sizes = site_sizes_of(config, traffic)
    n_train, n_test = cohort.split_counts(sizes)
    t0 = time.perf_counter()
    cohort_path, written = cohort.ensure_cohort(
        CACHE_DIR, traffic["name"], sizes, shape, seed)
    info["cohort"] = {"path": os.path.relpath(cohort_path, ROOT),
                      "written": written, "subjects": sum(sizes),
                      "seconds": time.perf_counter() - t0}

    out_dir = os.path.join(
        OUT_DIR, ("rehearsal_" if rehearsal else "") + cell["name"])
    os.makedirs(out_dir, exist_ok=True)
    win = traffic["window"]
    warm_rounds = int(win["warmup_rounds"])
    argv = cell_argv(config, traffic, cohort_path, len(sizes), seed,
                     warm_rounds, os.path.join(out_dir, "log"))
    info["argv"] = argv
    t0 = time.perf_counter()
    engine = build_engine(argv)
    info["build_engine_s"] = time.perf_counter() - t0
    if trace:
        from neuroimagedisttraining_tpu.obs import trace as obs_trace

        obs_trace.arm(annotate=True)
    spans = Spans(annotate=trace)
    for path, name, sync in SPAN_SITES:
        owner = engine
        *parents, attr = path.split(".")
        for p in parents:
            owner = getattr(owner, p, None)
        if owner is not None and hasattr(owner, attr):
            spans.wrap(owner, attr, name, sync)
    spy = ProgramSpy(engine.program, spans)
    rounds_log = RoundLog(engine)

    epochs = int(config["epochs"])
    batch = int(config["batch_size"])
    samples_per_round = epochs * sum(n_train)

    t0 = time.perf_counter()
    engine.train()
    warm = rounds_log.take()
    ends = [r["t"] for r in warm if r["round"] >= 0]
    round_s = ends[-1] - ends[-2]
    info["warmup"] = {"seconds": time.perf_counter() - t0,
                      "rounds": warm_rounds, "steady_round_s": round_s,
                      "train_loss": [float(r["train_loss"]) for r in warm
                                     if r["round"] >= 0]}
    k_trace = int(win["trace_rounds"]) if trace else 0
    # the profiler is started one round before the slice it reduces:
    # starting it can stall the device once (1.1 s idle in one traced run
    # of five, PR 22), and that is the profiler's cost, not the program's
    lead = 1 if k_trace else 0
    n_window = max(int(win["min_rounds"]), int(seconds / round_s),
                   k_trace + lead + 1)
    set_rounds(engine, n_window + 1)  # round 0 is outside the window
    # core/optim.py::round_lr raises the decay to a Python int, which jax
    # compiles once per exponent: warm every round index the window uses
    jax.block_until_ready([engine.round_lr(r)
                           for r in range(-1, n_window + 2)])
    setup = clock.snapshot()
    t_window_call = time.perf_counter()
    setup_s = t_window_call - t_process

    # ----- window -----
    state = {"profile_dir": None, "transfer": {}}
    slice_from = n_window - k_trace  # the slice is the last k rounds
    host_to = slice_from - lead      # the host-clock window's last round

    def on_round_end(row):
        r = row["round"]
        if engine.stream is not None and r in (0, host_to, n_window):
            state["transfer"][r] = dict(engine.stream.transfer_stats)
        if not k_trace:
            return
        if r == host_to:
            state["profile_dir"] = os.path.join(out_dir, "profile")
            start_profile(state["profile_dir"])
        elif r == slice_from:
            state["slice"] = jax.profiler.TraceAnnotation(SLICE_SPAN)
            state["slice"].__enter__()
        elif r == n_window:
            state["slice"].__exit__(None, None, None)
            jax.profiler.stop_trace()

    rounds_log.on_round_end = on_round_end
    before = clock.snapshot()
    result = engine.train()
    rows = rounds_log.take()
    t_train_end = time.perf_counter()
    end_of = {r["round"]: r["t"] for r in rows}
    # the host-clock window: round 1 .. the last round before the profiler
    # starts (all of them in an untraced run)
    w0, w1 = end_of[0], end_of[host_to]
    in_window = delta(clock.snapshot(), before)
    # compilations after round 0 of this call would be inside the window
    measured = [r for r in rows if 1 <= r["round"] <= host_to]
    wall = w1 - w0
    samples = samples_per_round * len(measured)
    samples_per_s = samples / wall
    failed = sum(1 for r in measured if not finite_losses(r))
    stats = [d.memory_stats() or {} for d in jax.local_devices()]
    info["window"] = {
        "rounds": len(measured), "wall_s": wall, "samples": samples,
        "round_s": [b["t"] - a["t"] for a, b in zip(rows, rows[1:])
                    if 1 <= b["round"] <= host_to],
        "train_loss": [float(r["train_loss"]) for r in rows
                       if r["round"] >= 0],
        "test_auc": [float(r["auc"]) for r in rows
                     if r["round"] >= 0 and "auc" in r],
        "after_window_s": t_train_end - end_of[n_window],
        "compile_events_in_call": in_window}

    # ----- checks (outside the window) -----
    t0 = time.perf_counter()
    checks: dict = {}
    checks["learning"] = learning_check(
        [r for r in rows if r["round"] >= 0], result["final_global"],
        correct_bands(config, traffic))
    checks["no_compile_in_window"] = {
        "ok": in_window["compiles"] == 0 and in_window["cache_misses"] == 0
        and in_window["cache_hits"] == 0, **in_window}
    fb = fallbacks()
    checks["fallbacks"] = {"ok": not fb, "nidt_fallback_total": fb}
    engine_rows = [int(n) for n in np.asarray(engine._n_train_host) if n > 0]
    checks["samples"] = {"ok": engine_rows == n_train,
                         "samples_per_round": samples_per_round,
                         "engine_train_rows": engine_rows}
    expect = traffic["expect"]
    checks["programs"] = program_check(spy, expect, on_tpu)
    if expect["mask"]:
        checks["mask"] = mask_check(engine, result, on_tpu)
    reference = None if rehearsal else load_reference(config)
    if reference is not None:
        checks["forward"] = forward_check(engine, reference, config)
    info["checks"] = checks
    info["checks_s"] = time.perf_counter() - t0
    peak_bytes, info["memory_stats_fullest"] = memory_peak(stats)
    correct = all(c["ok"] for c in checks.values())

    # ----- metrics -----
    steps_real = sum(math.ceil(n / batch) for n in n_train)
    rows_trained = int(engine.num_clients)  # mesh pad rows included
    steps_padded = rows_trained * math.ceil(max(n_train) / batch)
    ctx = {
        "chips": int(cell["chips"]), "peak": peak, "spans": spans,
        "window": (w0, w1), "call": (t_window_call, t_train_end),
        "wall_s": wall, "samples_per_s": samples_per_s,
        "samples_per_round": samples_per_round,
        "setup": setup,
        "peak_bytes": peak_bytes,
        "counts": {"padded_step_share": 1.0 - steps_real / steps_padded},
        "transfer": None, "trace": None, "flops_per_sample": None,
    }
    if engine.stream is not None:
        tr = state["transfer"]
        ctx["transfer"] = delta(tr[host_to], tr[0])
        info["window"]["transfer"] = ctx["transfer"]
    if reference is not None:
        gs = jax.eval_shape(engine.init_global_state)
        tape = flops.record_tape(reference.forward, gs.params,
                                 gs.batch_stats, shape)
        ctx["flops_per_sample"] = flops.training_flops_per_sample(tape)
        info["flops_per_sample"] = ctx["flops_per_sample"]
    reduced = None
    if trace:
        (pb,) = glob.glob(os.path.join(state["profile_dir"], "plugins",
                                       "profile", "*", "*.xplane.pb"))
        try:
            reduced = trace_reduce.reduce(trace_reduce.load_xplane(pb),
                                          SLICE_SPAN, GAP_SPANS)
        except trace_reduce.NoDeviceOps:
            if not rehearsal:  # a CPU trace has no device plane
                raise
    if reduced is not None:
        reduced["rounds"] = k_trace
        reduced["real_samples"] = samples_per_round * k_trace
        ctx["trace"] = reduced
        info["trace_reduced"] = {k: v for k, v in reduced.items()
                                 if k != "per_device"}
        shutil.rmtree(state["profile_dir"], ignore_errors=True)
        info["trace_reduced"]["per_device"] = {
            d: {k: v for k, v in row.items() if k != "ops_s"}
            for d, row in reduced["per_device"].items()}

    metrics: dict = {}
    if trace:
        for entry in per_layer:
            if cell["name"] not in entry.get("workloads", [cell["name"]]):
                continue
            spec = read_json(os.path.join(BENCH, "metrics",
                                          entry["name"] + ".json"))
            value = readers.read(spec["reader"], ctx, entry["name"])
            if value is not None:
                metrics[entry["name"]] = {"value": value,
                                          "unit": entry["unit"]}
    else:
        metrics["train_samples_per_s"] = {"value": samples_per_s,
                                          "unit": "samples/s"}
        metrics["setup_s"] = {"value": setup_s, "unit": "s"}
    info["setup"] = {"setup_s": setup_s, **ctx["setup"]}
    info["total_s"] = time.perf_counter() - t_process
    with open(os.path.join(out_dir, f"run_seed{seed}_trace{int(trace)}.json"),
              "w") as f:
        json.dump(info, f, indent=1, default=float)
    return {"correct": correct, "attempted": len(measured),
            "failed": failed, "metrics": metrics,
            "peak_bytes": peak_bytes, "reduced": reduced, "info": info}


def memory_peak(stats: list[dict]) -> tuple[int, dict]:
    """Peak bytes on the fullest chip, from the runtime's own counters. The
    TPU runtime counts the arrays a process holds (``peak_bytes_in_use``)
    apart from the block it reserves for a running program's temporaries
    (``peak_bytes_reserved``): beside a round program with 12.97 GiB of
    temporaries the first alone read 1.48 GiB (my chip run, PR 22). The
    peak is their sum; a backend without the second reports the first."""
    def peak(s: dict) -> int:
        return (s.get("peak_bytes_in_use", 0)
                + s.get("peak_bytes_reserved", 0))

    fullest = max(stats, key=peak, default={})
    return peak(fullest), fullest


def start_profile(log_dir: str) -> None:
    """Device and host-annotation tracing, without the Python call tracer
    (it slows the host and fills the trace with frames)."""
    import jax

    shutil.rmtree(log_dir, ignore_errors=True)
    options = jax.profiler.ProfileOptions()
    options.python_tracer_level = 0
    options.host_tracer_level = 2
    jax.profiler.start_trace(log_dir, profiler_options=options)


def summary_line(info: dict) -> str:
    w, s = info["window"], info["setup"]
    return (f"[bench] {info['cell']} seed={info['seed']} "
            f"setup={s['setup_s']:.1f}s (compile {s['compile_s']:.1f}s, "
            f"{s['cache_hits']} hits, {s['cache_misses']} misses) "
            f"window={w['wall_s']:.2f}s rounds={w['rounds']} "
            f"round_median={statistics.median(w['round_s']):.3f}s "
            f"checks={ {k: v['ok'] for k, v in info['checks'].items()} } "
            f"total={info['total_s']:.1f}s")

