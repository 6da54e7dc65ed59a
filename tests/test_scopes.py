"""The program names its own work: device scopes on every stage of the
round (``jax.named_scope``, obs/names.py SCOPE_*) and host spans over the
whole loop iteration (obs/trace.py, SPAN_*).

Scopes are compile-time metadata: the compiled round program must hold
them, and must be operation for operation the program it is without them.
Spans are armed by ``obs.trace.arm``: one ``round`` span an iteration whose
children share its id, and nothing at all while disarmed."""

import contextlib
import re

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from neuroimagedisttraining_tpu.config import (
    DataConfig, ExperimentConfig, FedConfig, OptimConfig,
)
from neuroimagedisttraining_tpu.core.trainer import LocalTrainer, scan_steps
from neuroimagedisttraining_tpu.data.federate import federate_cohort
from neuroimagedisttraining_tpu.engines import create_engine
from neuroimagedisttraining_tpu.models import create_model
from neuroimagedisttraining_tpu.obs import names
from neuroimagedisttraining_tpu.obs import trace as obs_trace
from neuroimagedisttraining_tpu.parallel.mesh import make_mesh
from neuroimagedisttraining_tpu.utils.logging import ExperimentLogger

#: engine cases: (algorithm, OptimConfig extras, scopes its stages add)
CASES = {
    "fedavg": ("fedavg", {}, ()),
    "fedavg_fused_update": ("fedavg", {"fused_update": True}, ()),
    "salientgrads": ("salientgrads", {},
                     (names.SCOPE_STATE_UPDATE, names.SCOPE_MASK_APPLY)),
}
#: scopes every declared round of the tiny 3D CNN holds
COMMON = (names.SCOPE_GATHER, names.SCOPE_LOCAL_TRAIN,
          names.SCOPE_BATCH_PREP, names.SCOPE_FWD_BWD, names.SCOPE_CLIP,
          names.SCOPE_UPDATE, names.SCOPE_STEM, names.SCOPE_POOL0,
          names.SCOPE_POOL1, names.SCOPE_HEAD, names.SCOPE_AGGREGATE)
EPOCHS, BATCH = 2, 8


def _engine(tmp_path, cohort, case: str, comm_round: int = 2):
    algorithm, optim_kw, _ = CASES[case]
    cfg = ExperimentConfig(
        model="3dcnn_tiny", num_classes=1, algorithm=algorithm,
        data=DataConfig(dataset="synthetic", partition_method="site"),
        optim=OptimConfig(lr=1e-3, batch_size=BATCH, epochs=EPOCHS,
                          **optim_kw),
        fed=FedConfig(client_num_in_total=4, comm_round=comm_round,
                      frequency_of_the_test=1),
        log_dir=str(tmp_path))
    mesh = make_mesh()
    fed, _ = federate_cohort(cohort, partition_method="site", mesh=mesh)
    trainer = LocalTrainer(create_model(cfg.model, num_classes=1),
                           cfg.optim, num_classes=1)
    log = ExperimentLogger(str(tmp_path), "synthetic", cfg.identity(),
                           console=False)
    return create_engine(algorithm, cfg, fed, trainer, mesh=mesh,
                         logger=log)


def _round_program_text(engine) -> str:
    """Run the engine and return the compiled text of its round program,
    lowered again from the shapes it was first called with."""
    seen = {}
    inner = engine.program._count_dispatches

    def count_dispatches(jitted, label="round", **kwargs):
        def call(*args):
            if label not in seen:
                seen[label] = (jitted, jax.tree.map(
                    lambda x: jax.ShapeDtypeStruct(x.shape, x.dtype)
                    if isinstance(x, jax.Array) else x, args))
            return jitted(*args)

        call.lower = jitted.lower
        return inner(call, label=label, **kwargs)

    engine.program._count_dispatches = count_dispatches
    engine.train()
    (jitted, args), = seen.values()
    return jitted.lower(*args).compile().as_text()


def _op_names(text: str) -> set[str]:
    return {"/" + n + "/" for n in re.findall(r'op_name="([^"]+)"', text)}


@pytest.fixture(scope="module")
def scoped_text(tmp_path_factory, synthetic_cohort):
    cache = {}

    def get(case):
        if case not in cache:
            cache[case] = _round_program_text(_engine(
                tmp_path_factory.mktemp(case), synthetic_cohort, case, 1))
        return cache[case]

    return get


@pytest.mark.parametrize("case", sorted(CASES))
def test_round_program_holds_every_declared_scope(scoped_text, case):
    paths = _op_names(scoped_text(case))
    for scope in COMMON + CASES[case][2]:
        assert any(f"/{scope}/" in p for p in paths), scope
    # forward and backward are both under the stem, and JAX marks which
    assert any("/jvp(Tiny3DCNN)/stem/" in p for p in paths)
    assert any("/transpose(jvp(Tiny3DCNN))/stem/" in p for p in paths)
    assert any("/stem/pool0/" in p and "transpose(" in p for p in paths)
    # the optimizer tail sits inside the local step's scan, under one name
    assert any(re.search(r"/local_train/.*/update/", p) for p in paths)
    # the model's own modules keep flax's names, under no new prefix
    assert any(re.search(r"\)/f1/conv/", p) for p in paths)


def _instructions(text: str) -> list[str]:
    """The compiled module's instructions, in order, without what a scope
    may change: their metadata (and the tables of file names and stack
    frames that follow the module)."""
    return [re.sub(r", metadata=\{[^}]*\}", "", line)
            for line in text.splitlines()
            if re.match(r"\s*(ROOT )?%?[\w.\-]+ = ", line)]


@pytest.mark.parametrize("case", sorted(CASES))
def test_scopes_change_metadata_only(scoped_text, tmp_path, monkeypatch,
                                     synthetic_cohort, case):
    """The same program built with every named scope a no-op (the
    program's and flax's) is instruction for instruction the scoped one:
    what makes tracing free on the device."""
    with monkeypatch.context() as m:
        m.setattr(jax, "named_scope",
                  lambda name: contextlib.nullcontext())
        bare = _round_program_text(
            _engine(tmp_path, synthetic_cohort, case, 1))
    assert not any("/stem/" in p or "/f1/" in p for p in _op_names(bare))
    scoped, bare = _instructions(scoped_text(case)), _instructions(bare)
    assert len(scoped) == len(bare)
    assert scoped == bare


PARAM_PATHS = {
    "3DCNN": [
        *(f"batch_stats/f{i}/bn/{s}" for i in range(5)
          for s in ("mean", "var")),
        *(f"params/f{i}/{m}" for i in range(5)
          for m in ("bn/bias", "bn/scale", "conv/bias", "conv/kernel")),
        "params/fc1/bias", "params/fc1/kernel",
        "params/fc2/bias", "params/fc2/kernel"],
    "resnet3d": [
        "batch_stats/bn1/mean", "batch_stats/bn1/var",
        *(f"batch_stats/layer{i}_0/{bn}/{s}" for i in (1, 2, 3)
          for bn in (("bn1", "bn2") if i == 1 else ("bn1", "bn2", "ds_bn"))
          for s in ("mean", "var")),
        "params/bn1/bias", "params/bn1/scale", "params/conv1/kernel",
        "params/fc/bias", "params/fc/kernel",
        "params/fc2/bias", "params/fc2/kernel",
        *(f"params/layer{i}_0/{leaf}" for i in (1, 2, 3) for leaf in (
            "bn1/bias", "bn1/scale", "bn2/bias", "bn2/scale",
            "conv1/kernel", "conv2/kernel",
            *(("ds_bn/bias", "ds_bn/scale", "ds_conv/kernel")
              if i > 1 else ())))],
}


@pytest.mark.parametrize("model,shape", [("3DCNN", (1, 69, 69, 69, 1)),
                                         ("resnet3d", (1, 33, 33, 33, 1))])
def test_scopes_rename_no_parameter(model, shape):
    """Checkpoints and PARITY files address parameters by these paths (the
    list is the parent's, PR 22): a scope is not a module."""
    m = create_model(model, num_classes=1)
    variables = jax.eval_shape(lambda: m.init(
        {"params": jax.random.key(0), "dropout": jax.random.key(1)},
        jnp.zeros(shape), train=False))
    paths = ["/".join(k.key for k in path) for path, _ in
             jax.tree_util.tree_flatten_with_path(variables)[0]]
    assert paths == PARAM_PATHS[model]


# ---------- host spans ----------

def _children(events, parent):
    return [e for e in events if e is not parent and e["tid"] == parent["tid"]
            and e["ts"] >= parent["ts"]
            and e["ts"] + e["dur"] <= parent["ts"] + parent["dur"]]


@pytest.mark.parametrize("case", ["fedavg", "salientgrads"])
def test_one_armed_run_spans_every_iteration(tmp_path, synthetic_cohort,
                                             case):
    engine = _engine(tmp_path, synthetic_cohort, case, comm_round=2)
    obs_trace.arm()
    try:
        engine.train()
        events = [e for e in obs_trace.TRACER.events() if e["ph"] == "X"]
    finally:
        obs_trace.disarm()
    rounds = [e for e in events if e["name"] == names.SPAN_ROUND]
    assert [e["args"]["round"] for e in rounds] == [0, 1]
    n_train = np.asarray(engine._n_train_host)
    for r in rounds:
        inside = _children(events, r)
        # every span of the iteration carries the iteration's id
        assert inside and all(e["args"]["round"] == r["args"]["round"]
                              for e in inside)
        # the stages of names.ROUND_CHILD_SPANS tile the iteration: in
        # loop order, one after the other, none inside another
        stages = sorted((e for e in inside
                         if e["name"] in names.ROUND_CHILD_SPANS),
                        key=lambda e: e["ts"])
        assert {e["name"] for e in stages} >= {
            names.SPAN_ROUND_PROLOGUE, names.SPAN_DISPATCH_PROGRAM,
            names.SPAN_EVAL_DISPATCH, names.SPAN_EVAL_SYNC,
            names.SPAN_ROUND_FLUSH, names.SPAN_ROUND_LOG,
            names.SPAN_ROUND_CHECKPOINT}
        order = [names.ROUND_CHILD_SPANS.index(e["name"]) for e in stages]
        assert stages[0]["name"] == names.SPAN_ROUND_PROLOGUE
        assert stages[-1]["name"] == names.SPAN_ROUND_CHECKPOINT
        assert order[:2] == [0, 1] and order[-3:] == [4, 5, 6]
        for a, b in zip(stages, stages[1:]):
            assert a["ts"] + a["dur"] <= b["ts"]
        # what is not a stage is inside one, and waits for the device or
        # is JAX's own build of a program first called there (ISSUE 35)
        for e in inside:
            if e["name"] not in names.ROUND_CHILD_SPANS:
                assert e["name"].endswith("_sync") \
                    or e["name"] in names.JAX_BUILD_SPANS
        # the counts of the dispatched round
        (d,) = [e for e in stages
                if e["name"] == names.SPAN_DISPATCH_PROGRAM]
        a = d["args"]
        assert a["samples_real"] == EPOCHS * int(n_train.sum())
        assert a["steps_real"] == EPOCHS * int(
            np.ceil(n_train / BATCH).sum())
        # four sampled rows (no mesh padding on the unsharded path), each
        # walking the largest site's steps
        assert a["steps_run"] == 4 * scan_steps(
            EPOCHS, BATCH, engine._max_samples())
        assert 0 < a["steps_real"] <= a["steps_run"]
    # the wait for the device is eval_sync's, not eval_dispatch's: the
    # sync of a round's first evaluation waits for the whole round
    first_sync = next(e for e in events
                      if e["name"] == names.SPAN_EVAL_SYNC)
    first_dispatch = next(e for e in events
                          if e["name"] == names.SPAN_EVAL_DISPATCH
                          and e["args"]["round"] == 1)
    assert first_sync["args"]["program"] == "eval_global"
    assert first_dispatch["dur"] >= 0
    # every span carries what obs/names.py's table says it carries;
    # evaluation says where _per_client placed its rows (no client
    # mesh armed and no fold here: the four sites' rows, padded to the
    # eight devices by the data layer, stacked under one vmap)
    for name in (names.SPAN_DISPATCH_PROGRAM, names.SPAN_EVAL_DISPATCH):
        for e in events:
            if e["name"] == name:
                assert set(names.ARGS_BY_SPAN[name]) <= set(e["args"]), e
    a = first_dispatch["args"]
    assert (a["program"], a["split"]) == ("eval_global", "test")
    assert (a["placement"], a["rows"], a["rows_a_chip"]) == (
        "stacked", 8, 8)


@pytest.mark.parametrize("case", ["fedavg", "salientgrads"])
def test_round_program_is_traced_once(tmp_path, synthetic_cohort, case):
    """The initial state is placed where the rounds leave their outputs
    (engines/base.py init_global_state), so the first dispatch has the
    signature of every later one: one trace, one compile, three rounds."""
    engine = _engine(tmp_path, synthetic_cohort, case, comm_round=3)
    engine.train()
    (prog,) = engine._round_prog_cache.values()
    assert engine.program.dispatches == 3
    assert prog.jit._cache_size() == 1


def test_disarmed_loop_records_nothing(tmp_path, synthetic_cohort):
    assert not obs_trace.TRACER.armed
    before = len(obs_trace.TRACER.events())
    null = obs_trace.span(names.SPAN_ROUND, round=0)
    assert obs_trace.span(names.SPAN_EVAL_SYNC) is null  # the shared no-op
    engine = _engine(tmp_path, synthetic_cohort, "fedavg", comm_round=1)
    engine.train()
    assert len(obs_trace.TRACER.events()) == before
    assert engine._dispatch_counts == {}


def test_dump_carries_one_clock_anchor(tmp_path):
    import json
    import time

    t = obs_trace.SpanTracer()
    t.arm(str(tmp_path / "t.json"))
    t.record_interval(names.SPAN_FEED_GATHER, 1.0, 1.5, bytes=8)
    doc = json.load(open(t.dump()))
    anchor = doc["nidtClockAnchor"]
    assert anchor["perf_counter_ns"] == t.epoch_ns
    # both clocks were read back to back at arm
    skew = (time.time_ns() - anchor["time_ns"]) \
        - (time.perf_counter_ns() - anchor["perf_counter_ns"])
    assert abs(skew) < 50e6
    (e,) = doc["traceEvents"]
    assert e["name"] == names.SPAN_FEED_GATHER
    assert e["dur"] == pytest.approx(0.5e6)
    assert e["ts"] == pytest.approx((1.0e9 - t.epoch_ns) / 1e3)


def test_names_table_is_complete():
    """Every scope and span constant is unique, and the children of a
    round are named so that benchmark/harness.py GAP_SPANS admits them."""
    assert len(names.DEVICE_SCOPES) == 21
    # a layout marker inside SCOPE_STEM, in neither table: no class reads it
    assert names.SCOPE_STEM_MERGED not in (names.DEVICE_SCOPES
                                           | names.MODEL_SCOPES)
    spans = [v for k, v in vars(names).items() if k.startswith("SPAN_")]
    assert len(spans) == len(set(spans))
    for s in names.ROUND_CHILD_SPANS:
        assert s.startswith(("round", "dispatch", "eval_"))
    assert set(names.ARGS_BY_SPAN) <= set(spans)


def test_model_scopes_table():
    """MODEL_SCOPES (the stages of the sparse-expert block, PR 25, of the
    hybrid trunk, PR 29, of compressed convolutional attention, PR 31, of
    EVA attention with its dense feed-forward, PR 38, of latent
    attention of the reconstructing kind, PR 40, and of gated attention of
    two kinds under QK norms, PR 44)
    is a second table, disjoint from DEVICE_SCOPES (which
    benchmark/scopes.json pins); every constant is used at least once in
    models/, none is spelled as a literal there, and the benchmark's six
    rules files name each between them: the OLMoE block's five in
    olmoe_scopes.json, the hybrid trunk's eleven in nemotronh_scopes.json,
    ZAYA1's layer's eight (three of them new) in zaya_scopes.json,
    EvaByte's layer's five (four of them new) in evabyte_scopes.json,
    Moonlight's layer's nine (two of them new) in moonlight_scopes.json,
    Trinity-Mini's layers' eleven (four of them new) in
    trinity_scopes.json."""
    import glob
    import json
    import os

    consts = {k: v for k, v in vars(names).items()
              if k.startswith("SCOPE_") and v in names.MODEL_SCOPES}
    assert len(consts) == len(names.MODEL_SCOPES) == 24
    assert not names.MODEL_SCOPES & names.DEVICE_SCOPES
    root = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
    sources = {p: open(p).read() for p in glob.glob(os.path.join(
        root, "neuroimagedisttraining_tpu", "models", "*.py"))}
    for const, value in consts.items():
        assert any(f"obs_names.{const}" in s for s in sources.values()), const
        for path, s in sources.items():
            assert not re.search(rf"""["']{value}["']""", s), (path, value)
    named = {}
    for file, partition in (("olmoe_scopes.json", "block"),
                            ("nemotronh_scopes.json", "trunk"),
                            ("zaya_scopes.json", "layer"),
                            ("evabyte_scopes.json", "layer"),
                            ("moonlight_scopes.json", "layer"),
                            ("trinity_scopes.json", "layer")):
        rules = json.load(open(os.path.join(
            root, "benchmark", "metrics", file)))
        named[file] = {s for k, v in rules["scope_names"].items()
                       if k != "what" for s in v}
        classes = {r["class"] for r in rules[partition]}
        assert set(rules["scope_names"]) - {"what"} <= classes
    expert_layer = {names.SCOPE_ROUTER, names.SCOPE_DISPATCH,
                    names.SCOPE_EXPERTS, names.SCOPE_COMBINE}
    assert named["olmoe_scopes.json"] == expert_layer | {names.SCOPE_ATTN}
    cca = {names.SCOPE_CCA_PROJ, names.SCOPE_CCA_CONV, names.SCOPE_CCA_MIX}
    assert named["zaya_scopes.json"] == \
        expert_layer | cca | {names.SCOPE_ATTN}
    eva = {names.SCOPE_EVA_POOL, names.SCOPE_EVA_LOCAL,
           names.SCOPE_EVA_REMOTE, names.SCOPE_MLP}
    assert named["evabyte_scopes.json"] == eva | {names.SCOPE_ATTN}
    mla = {names.SCOPE_MLA_LATENT, names.SCOPE_MLA_CORE}
    assert named["moonlight_scopes.json"] == expert_layer | mla | {
        names.SCOPE_ATTN, names.SCOPE_MLP, names.SCOPE_SHARED_EXPERT}
    gated = {names.SCOPE_SWA_CORE, names.SCOPE_FULL_CORE,
             names.SCOPE_QK_NORM, names.SCOPE_ATTN_GATE}
    assert named["trinity_scopes.json"] == expert_layer | gated | {
        names.SCOPE_ATTN, names.SCOPE_MLP, names.SCOPE_SHARED_EXPERT}
    assert named["nemotronh_scopes.json"] == \
        set(names.MODEL_SCOPES) - cca - eva - mla - gated
    assert set().union(*named.values()) == set(names.MODEL_SCOPES)
