"""The EvaByte cell's own readers and files (PR 38).

The layer partition on op paths recorded from the program (forward,
rematerialised and ``transpose(...)``), and on the paths of a small model
lowered here; the counting functions against numbers worked by hand; the
roofline arithmetic on a hand-made table; the evaluation counter on
hand-made ``eval_dispatch`` spans; every reader returning ``None`` where a
program has no such scope or counter (the parent, a CNN, the other
trunks); and the configuration file against the catalog row it was copied
from. Nothing here pins where in ``per_layer`` the entries stand.
"""

import importlib
import json
import os
import re

import pytest

from benchmark import evabyte_scopes, harness, scopes

PEAK = {"bf16_flops_per_s": 197e12, "hbm_bytes_per_s": 819e9}
CELL = "evabyte.fedavg_fold3_s10"
SHARE_METRICS = ("eva_time_share_pct", "eva_local_time_share_pct",
                 "eva_remote_time_share_pct", "eva_pool_time_share_pct",
                 "dense_mlp_time_share_pct", "eva_proj_time_share_pct",
                 "unit_norm_time_share_pct")
NEW_METRICS = SHARE_METRICS + ("eva_roofline_pct", "dense_mlp_roofline_pct",
                               "eval_rows_run_share_pct")
T, HEADS, HD, LAYERS = 4864, 8, 128, 4
LOCAL, REMOTE = 4_491_648, 458_752


@pytest.fixture
def tracer():
    from neuroimagedisttraining_tpu.obs import trace as obs_trace

    obs_trace.TRACER.arm()
    yield obs_trace.TRACER
    obs_trace.TRACER.disarm()


@pytest.fixture(scope="module")
def reference():
    return harness.load_reference({"reference": "evabyte-abcd.py",
                                   "name": "evabyte-abcd"})


def _spec(name):
    return harness.read_json(os.path.join(
        harness.BENCH, "metrics", name + ".json"))["reader"]


def _read(name, ctx):
    return importlib.import_module("benchmark.metrics." + name).read(
        _spec(name), ctx)


def _span(tracer, name, t0, t1, **args):
    epoch = tracer.epoch_ns / 1e9
    tracer.record_interval(name, epoch + t0, epoch + t1, **args)


def _window(tracer, w0, w1):
    epoch = tracer.epoch_ns / 1e9
    return (epoch + w0, epoch + w1)


_FWD = "jit(round_fn)/local_train/fwd_bwd/jvp(EvaByte3D)/"
_BWD = ("jit(round_fn)/local_train/fwd_bwd/transpose(jvp(EvaByte3D))/"
        "jvp(EvaByte3D)/checkpoint/")
#: op path, as the program's lowering records it -> the class the layer
#: partition must give it
PATHS = {
    _FWD + "layers_0/attn/eva/eva_pool/bjtad,ad->bjta/dot_general":
        "eva_pool",
    _BWD + "layers_0/attn/eva/eva_pool/bjtad,ad->bjta/add_any": "eva_pool",
    _BWD + "rematted_computation/layers_0/attn/eva/eva_pool/add":
        "eva_pool",
    _FWD + "layers_0/attn/eva/eva_local/baqk,bkad->bqad/dot_general":
        "eva_local",
    _BWD + "layers_0/attn/eva/eva_local/baqk,bkad->bqad/dot_general":
        "eva_local",
    _BWD + "rematted_computation/layers_3/attn/eva/eva_local/exp":
        "eva_local",
    _FWD + "layers_1/attn/eva/eva_remote/baqj,bjad->bqad/dot_general":
        "eva_remote",
    _BWD + "layers_1/attn/eva/eva_remote/baqj,bjad->bqad/dot_general":
        "eva_remote",
    _BWD + "rematted_computation/layers_1/attn/eva/eva_remote/add":
        "eva_remote",
    # what is left of attn: the projections, rotary, W_o
    _FWD + "layers_0/attn/eva/q_proj/dot_general": "attn",
    _BWD + "layers_0/attn/eva/o_proj/transpose": "attn",
    _FWD + "layers_2/attn/eva/mul": "attn",
    _FWD + "layers_0/mlp/ffn/gate_proj/dot_general": "mlp",
    _BWD + "layers_0/mlp/ffn/down_proj/dot_general": "mlp",
    _BWD + "rematted_computation/layers_0/mlp/ffn/jit(silu)/add": "mlp",
    _FWD + "layers_0/attn_norm/div": "norm",
    _BWD + "layers_0/mlp_norm/mul": "norm",
    _FWD + "stem/patch_embed/dot_general": "stem",
    _FWD + "head/final_norm/mul": "head",
    "jit(round_fn)/local_train/update/add": "optimizer",
    "jit(eval_all)/eval/EvaByte3D/layers_0/attn/eva/eva_local/exp": "eval",
    "jit(round_fn)/aggregate/add": "aggregate",
    "jit(round_fn)/local_train/batch_prep/convert_element_type": "input",
}


@pytest.mark.parametrize("path", sorted(PATHS))
def test_layer_partition_classifies_by_the_programs_scopes(path):
    rules = scopes.load_rules(evabyte_scopes.RULES)
    table = scopes.build({"%op = f32[] x()": 1.0},
                         {"%op = f32[] x()": {"tf_op": path}}, rules=rules)
    assert table["share_pct"]["layer"] == {PATHS[path]: 100.0}


def test_every_matrix_product_of_a_lowered_step_has_a_class():
    """The small model's gradient, lowered here: every ``dot_general`` of a
    layer lands in one of EVA's three classes, ``attn`` or ``mlp``, forward
    and ``transpose(...)`` alike, and each of the five is met both ways."""
    jax = pytest.importorskip("jax")
    import jax.numpy as jnp

    from neuroimagedisttraining_tpu.models.evabyte3d import EvaByte3D, Widths

    model = EvaByte3D(widths=Widths(
        layers=2, hidden_size=32, heads=2, head_dim=16, intermediate_size=48,
        window_size=16, chunk_size=4, patch=4))
    x = jnp.zeros((2, 12, 14, 12, 1))  # 36 tokens: windows of 16, 16, 4
    params = model.init(jax.random.key(0), x)
    text = jax.jit(jax.grad(lambda p: jnp.sum(model.apply(p, x)))).lower(
        params).as_text(debug_info=True)
    paths = set(re.findall(r'"(jit\([^"]*)"', text))
    rules = scopes.load_rules(evabyte_scopes.RULES)["layer"]
    met = set()
    for path in paths:
        if "/layers_" in path and path.endswith("dot_general"):
            cls = scopes.classify(rules, path + ":dot_general", "%op")
            assert cls in ("eva_pool", "eva_local", "eva_remote", "attn",
                           "mlp"), path
            met.add((cls, "transpose(" in path))
    assert met == {(c, back) for c in ("eva_pool", "eva_local", "eva_remote",
                                       "attn", "mlp")
                   for back in (False, True)}


def test_the_counting_functions_by_hand(reference):
    """4,864 tokens in windows of 2,048: two whole ones and one of 768.
    Causal pairs 2 x 2048 x 2049 / 2 + 768 x 769 / 2 = 4,491,648; summary
    pairs 2048 x 128 + 768 x 256 = 458,752. At 4 x 128 operations a pair, 8
    heads, 4 layers: 81.11 GFLOP a sample forward."""
    tape = reference.published_tape()
    assert 2 * 2048 * 2049 // 2 + 768 * 769 // 2 == LOCAL
    assert 2048 * 128 + 768 * 256 == REMOTE
    assert reference.eva_pairs(tape) == (LOCAL, REMOTE)
    forward = 4 * HD * (LOCAL + REMOTE) * HEADS * LAYERS
    assert reference.eva_flops_per_sample(tape) == forward
    assert forward == pytest.approx(81.11e9, rel=1e-4)
    per_pass = (4 * T + 4 * (T // 16)) * HEADS * HD * 2 * LAYERS
    assert reference.eva_bytes_per_sample(tape) == per_pass
    flops, nbytes = evabyte_scopes.eva_work(reference, tape, 48)
    assert flops == 3 * forward * 48 and nbytes == 3 * per_pass * 48
    mlp = 3 * 2 * 4096 * 11008 * T * LAYERS
    assert reference.mlp_flops_per_sample(tape) == mlp
    assert evabyte_scopes.mlp_work(reference, tape, 48) == 3 * mlp * 48


def _ctx_with_table(seconds_by_class, **more):
    busy = sum(seconds_by_class.values())
    table = {"busy_s": busy, "share_pct": {"layer": {
        c: 100.0 * s / busy for c, s in seconds_by_class.items()}}}
    return {evabyte_scopes.KEY: table, "peak": PEAK, "chips": 1, **more}


def test_shares_sum_their_classes():
    ctx = _ctx_with_table({"eva_pool": 0.25, "eva_local": 1.0,
                           "eva_remote": 0.25, "attn": 1.0, "mlp": 6.0,
                           "norm": 0.5, "optimizer": 1.0})
    assert _read("eva_time_share_pct", ctx) == pytest.approx(15.0)
    assert _read("eva_local_time_share_pct", ctx) == pytest.approx(10.0)
    assert _read("eva_remote_time_share_pct", ctx) == pytest.approx(2.5)
    assert _read("eva_pool_time_share_pct", ctx) == pytest.approx(2.5)
    assert _read("dense_mlp_time_share_pct", ctx) == pytest.approx(60.0)
    # what is left of attn (projections, rotary, W_o), and the pre-norms
    assert _read("eva_proj_time_share_pct", ctx) == pytest.approx(10.0)
    assert _read("unit_norm_time_share_pct", ctx) == pytest.approx(5.0)


def test_eva_roofline_is_the_flop_side():
    """Two traced rounds of 48 samples: operations 3 x 81.11 GFLOP x 96 =
    23.36 TFLOP -> 118.6 ms at 197e12; bytes 3 x 169.3 MB x 96 = 48.8 GB ->
    59.5 ms at 819e9. Scopes that took ten times 118.6 ms are at 10%."""
    flop_s = 3 * 4 * HD * (LOCAL + REMOTE) * HEADS * LAYERS * 96 / 197e12
    byte_s = 3 * (4 * T + 4 * 304) * HEADS * HD * 2 * LAYERS * 96 / 819e9
    assert flop_s == pytest.approx(0.1186, rel=1e-3)
    assert byte_s == pytest.approx(0.0595, rel=2e-3)
    ctx = _ctx_with_table({"eva_pool": 0.1 * flop_s, "eva_local": 9 * flop_s,
                           "eva_remote": 0.9 * flop_s, "mlp": 5.0},
                          trace={"real_samples": 96, "rounds": 2})
    assert _read("eva_roofline_pct", ctx) == pytest.approx(10.0, rel=1e-6)


def test_mlp_roofline_counts_three_passes():
    """96 samples: 3 x 5.263 TFLOP x 96 = 1,516 TFLOP -> 7.69 s at the
    peak; a scope that took 4 / 3 of that reads 75."""
    flop_s = 3 * 3 * 2 * 4096 * 11008 * T * LAYERS * 96 / 197e12
    assert flop_s == pytest.approx(7.695, rel=1e-3)
    ctx = _ctx_with_table({"mlp": flop_s * 4 / 3, "eva_local": 1.0},
                          trace={"real_samples": 96, "rounds": 2})
    assert _read("dense_mlp_roofline_pct", ctx) == pytest.approx(75.0)


def test_evaluation_rows_read_the_spans_that_start_in_the_window(tracer):
    """Three sites of 2 test rows in batches of 4: 12 rows run for 6 real,
    200%; the spans before and after the window (at the old batch of 32)
    are not read."""
    _span(tracer, "eval_dispatch", 1.0, 1.1, rows=3, rows_run=96,
          rows_real=6)
    for t in (10.0, 20.0):
        _span(tracer, "eval_dispatch", t, t + 0.1, rows=3, rows_run=12,
              rows_real=6)
    _span(tracer, "eval_dispatch", 30.0, 30.1, rows=3, rows_run=96,
          rows_real=6)
    ctx = {"window": _window(tracer, 5.0, 25.0)}
    assert _read("eval_rows_run_share_pct", ctx) == pytest.approx(200.0)


@pytest.mark.parametrize("name", NEW_METRICS)
def test_a_program_without_the_scopes_or_counters_reads_none(tracer, name):
    """The parent of PR 38, a CNN, the other trunks: a trace whose ops
    carry none of EVA's scopes (though they may carry ``attn``), an
    ``eval_dispatch`` span with ``rows`` and no ``rows_run``."""
    _span(tracer, "eval_dispatch", 10.4, 10.6, rows=3, rows_a_chip=3,
          placement="folded")
    ctx = _ctx_with_table({"optimizer": 1.0, "stem": 2.0, "attn": 0.5,
                           "none": 3.0},
                          trace={"real_samples": 384, "rounds": 1},
                          window=_window(tracer, 0.0, 99.0))
    assert _read(name, ctx) is None
    # and with no trace at all
    assert _read(name, {"trace": None, "peak": PEAK, "chips": 1,
                        "window": _window(tracer, 0.0, 99.0)}) is None


def test_the_round_driver_writes_what_the_readers_read():
    """The arguments' names on the spans are the program's own
    (obs/names.py), where the program has them."""
    names = pytest.importorskip("neuroimagedisttraining_tpu.obs.names")
    if "rows_run" not in names.ARGS_BY_SPAN[names.SPAN_EVAL_DISPATCH]:
        pytest.skip("a program from before the evaluation-batch rule")
    assert names.SPAN_EVAL_DISPATCH == evabyte_scopes.EVAL_DISPATCH
    assert "rows_real" in names.ARGS_BY_SPAN[names.SPAN_EVAL_DISPATCH]
    rules = harness.read_json(evabyte_scopes.RULES)
    mine = {s for k, v in rules["scope_names"].items() if k != "what"
            for s in v}
    assert mine <= set(names.MODEL_SCOPES)
    assert set(evabyte_scopes.OWN) <= mine


def test_configuration_file_holds_the_catalog_rows_config():
    """Every key of the catalog row's ``config`` is in the file under the
    same name with the same value, but the ``reduced`` ones; the published
    counts and the deployment stand beside them."""
    doc = harness.read_json(os.path.join(
        harness.BENCH, "configs", "evabyte-abcd.json"))
    published = {
        "hidden_size": 4096, "intermediate_size": 11008, "head_dim": 128,
        "window_size": 2048, "chunk_size": 16, "rope_theta": 100000,
        "rms_norm_eps": 1e-5, "init_std": 0.01275, "num_pred_heads": 8,
        "attention_class": "eva", "model_type": "evabyte",
        "norm_add_unit_offset": True, "fp32_skip_add": True,
        "attention_bias": False, "hidden_act": "silu",
        "max_position_embeddings": 32768}
    for key, value in published.items():
        assert doc[key] == value, key
    assert doc["reduced"] == ["num_hidden_layers", "num_attention_heads",
                              "num_key_value_heads", "vocab_size", "cohort"]
    assert doc["num_hidden_layers"] == 4
    assert doc["num_attention_heads"] == doc["num_key_value_heads"] \
        in (8, 4)
    assert "vocab_size" not in doc
    assert doc["published"]["num_hidden_layers"] == 32
    assert doc["published"]["num_attention_heads"] == 32
    assert doc["published"]["num_key_value_heads"] == 32
    assert doc["published"]["vocab_size"] == 320
    assert "Four chips share each layer" in doc["deployment"]
    assert "holds heads 0-7" in doc["deployment"]
    assert "NOT built" in doc["deployment"]
    assert set(doc["reduced"]) == set(doc["reduced_notes"])
    assert "fedavg_fold3_s10" in doc["correct"]
    for key, value in doc["assumed"].items():
        assert "Source:" in value, key
    catalog = "/opt/skills/guides/model-configs/architectures.jsonl"
    if os.path.exists(catalog):
        (row,) = [r for r in map(json.loads, open(catalog))
                  if r["name"] == "EvaByte"]
        assert row["source_url"] in doc["source"]
        for key, value in row["config"].items():
            if key not in doc["reduced"]:
                assert doc[key] == value, key


def test_the_cell_and_its_metrics_are_in_the_index():
    bench = harness.read_json(os.path.join(harness.ROOT, "BENCHMARK.json"))
    (w,) = [w for w in bench["workloads"] if w["name"] == CELL]
    assert w["chips"] == 1 and w["config"] == "evabyte-abcd"
    assert w["traffic"] == "fedavg_fold3_s10"
    by_name = {m["name"]: m for m in bench["per_layer"]}
    for name in NEW_METRICS:
        m = by_name[name]
        assert CELL in m["workloads"]
        assert m["moves"] == "train_samples_per_s"
        assert os.path.exists(os.path.join(harness.BENCH, "metrics",
                                           name + ".json"))
        assert callable(importlib.import_module(
            "benchmark.metrics." + name).read)
    _, _, config, traffic = harness.load_cell(CELL)
    assert harness.site_sizes_of(config, traffic) == [10, 10, 10]
    bands = harness.correct_bands(config, traffic)
    assert bands is config["correct"]["fedavg_fold3_s10"]  # its own
    assert not traffic["expect"]["tpu_custom_call"]  # no kernel: plain XLA
    # the model name is in the registry, and says what a row costs
    from neuroimagedisttraining_tpu.models import create_model

    argv = config["argv"]
    model = create_model(argv[argv.index("--model") + 1])
    assert model.row_tokens(tuple(config["input_shape"])) == T
    assert model.widths.heads == config["num_attention_heads"]
    assert model.widths.layers == config["num_hidden_layers"]
