"""The Trinity-Mini cell's own readers and files (PR 44).

The layer partition on op paths as the program's lowering records them
(forward, rematerialised and ``transpose(...)``), and on the paths of a
small model lowered here; the counting function against numbers worked by
hand; the roofline arithmetic on a hand-made table; the routing counter on
hand-made ``round_log`` spans; every reader returning ``None`` where a
program has no such scope or counter (the parent, a CNN, the other
trunks); the configuration file against the catalog row it was copied
from. It asks ``in``, never ``[-1]``, of ``per_layer`` and ``workloads``:
nothing here pins where in either the entries stand.
"""

import importlib
import json
import os
import re

import pytest

from benchmark import harness, scopes, trinity_scopes

PEAK = {"bf16_flops_per_s": 197e12, "hbm_bytes_per_s": 819e9}
CELL = "trinity.fedavg_fold3_s10k"
SHARE_METRICS = {
    "trinity_attn_time_share_pct": 60.0, "swa_core_time_share_pct": 20.0,
    "full_core_time_share_pct": 7.5, "attn_gate_time_share_pct": 7.5,
    "sandwich_norm_time_share_pct": 2.5, "trinity_moe_time_share_pct": 12.5,
    "trinity_shared_time_share_pct": 5.0,
    "trinity_dense_ffn_time_share_pct": 10.0}
NEW_METRICS = tuple(SHARE_METRICS) + (
    "swa_core_roofline_pct", "full_core_roofline_pct",
    "trinity_expert_matmul_roofline_pct", "trinity_rows_held_share_pct")
T, W, HEADS, KV, D = 4864, 2048, 32, 4, 128
SWA_PAIRS, FULL_PAIRS = 7_865_344, 11_831_680
SLIDING, FULL = "sliding_attention", "full_attention"


@pytest.fixture
def tracer():
    from neuroimagedisttraining_tpu.obs import trace as obs_trace

    obs_trace.TRACER.arm()
    yield obs_trace.TRACER
    obs_trace.TRACER.disarm()


@pytest.fixture(scope="module")
def reference():
    return harness.load_reference({"reference": "trinity-abcd.py",
                                   "name": "trinity-abcd"})


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


_FWD = "jit(round_fn)/local_train/fwd_bwd/jvp(Trinity3D)/"
_BWD = ("jit(round_fn)/local_train/fwd_bwd/transpose(jvp(Trinity3D))/"
        "jvp(Trinity3D)/checkpoint/")
#: op path, as the program's lowering records it -> the class the layer
#: partition must give it
PATHS = {
    # the kernels' calls, and the plain form's blocks
    _FWD + "layers_0/attn/self_attn/swa_core/attention_forward": "swa_core",
    _BWD + "layers_1/attn/self_attn/swa_core/rematted_computation/"
    "attention_forward": "swa_core",
    _BWD + "layers_4/attn/self_attn/swa_core/attention_backward": "swa_core",
    _FWD + "layers_3/attn/self_attn/swa_core/checkpoint/bqgrd,bkgd->bgrqk/"
    "dot_general": "swa_core",
    _BWD + "layers_3/attn/self_attn/swa_core/checkpoint/rematted_computation/"
    "exp": "swa_core",
    _FWD + "layers_2/attn/self_attn/full_core/attention_forward": "full_core",
    _BWD + "layers_2/attn/self_attn/full_core/attention_backward": "full_core",
    _BWD + "layers_2/attn/self_attn/full_core/checkpoint/bgrqk,bkgd->bqgrd/"
    "dot_general": "full_core",
    _FWD + "layers_0/attn/self_attn/qk_norm/q_norm/mul": "qk_norm",
    _BWD + "layers_2/attn/self_attn/qk_norm/k_norm/rsqrt": "qk_norm",
    _FWD + "layers_1/attn/self_attn/attn_gate/gate_proj/dot_general":
        "attn_gate",
    _FWD + "layers_1/attn/self_attn/attn_gate/logistic": "attn_gate",
    _BWD + "layers_4/attn/self_attn/attn_gate/mul": "attn_gate",
    # what is left of attn: W_q, W_k, W_v, the rotary embedding, W_o
    _FWD + "layers_0/attn/self_attn/q_proj/dot_general": "attn",
    _BWD + "layers_0/attn/self_attn/k_proj/dot_general": "attn",
    _BWD + "layers_2/attn/self_attn/o_proj/dot_general": "attn",
    _FWD + "layers_1/attn/self_attn/mul": "attn",
    _FWD + "layers_0/mlp/ffn/gate_proj/dot_general": "mlp",
    _BWD + "layers_0/mlp/ffn/down_proj/dot_general": "mlp",
    _FWD + "layers_1/shared_expert/shared/up_proj/dot_general":
        "shared_expert",
    _BWD + "layers_4/shared_expert/shared/down_proj/dot_general":
        "shared_expert",
    _FWD + "layers_1/moe/router/dot_general": "router",
    _BWD + "layers_1/moe/router/transpose": "router",
    _FWD + "router/scatter-add": "router",  # the count of choices
    _FWD + "layers_1/moe/dispatch/sort": "dispatch",
    _FWD + "layers_1/moe/while/body/dispatch/gather": "dispatch",
    _FWD + "layers_1/moe/while/body/experts/gmm": "experts",
    _BWD + "layers_1/moe/while/body/experts/tgmm": "experts",
    _FWD + "layers_1/moe/while/body/combine/scatter-add": "combine",
    _BWD + "layers_2/moe/while/body/combine/mul": "combine",
    _FWD + "layers_0/attn_norm/mul": "norm",
    _FWD + "layers_0/attn_post_norm/mul": "norm",
    _BWD + "layers_1/mlp_norm/mul": "norm",
    _BWD + "layers_3/mlp_post_norm/rsqrt": "norm",
    _FWD + "stem/patch_embed/dot_general": "stem",
    _FWD + "head/final_norm/mul": "head",
    "jit(round_fn)/local_train/update/add": "optimizer",
    "jit(eval_all)/eval/Trinity3D/layers_0/attn/self_attn/swa_core/exp": "eval",
    "jit(round_fn)/aggregate/add": "aggregate",
    "jit(round_fn)/local_train/batch_prep/convert_element_type": "input",
}


@pytest.mark.parametrize("path", sorted(PATHS))
def test_layer_partition_classifies_by_the_programs_scopes(path):
    rules = scopes.load_rules(trinity_scopes.RULES)
    table = scopes.build({"%op = f32[] x()": 1.0},
                         {"%op = f32[] x()": {"tf_op": path}}, rules=rules)
    assert table["share_pct"]["layer"] == {PATHS[path]: 100.0}


def test_every_matrix_product_of_a_lowered_step_has_a_class():
    """The small model's gradient, lowered here: every ``dot_general`` of a
    layer lands in a class of its stage, forward and ``transpose(...)``
    alike, each met both ways; the cores by the layer's kind; and no norm
    of the four lies inside ``attn``."""
    jax = pytest.importorskip("jax")
    import jax.numpy as jnp

    trinity3d = pytest.importorskip(
        "neuroimagedisttraining_tpu.models.trinity3d")
    kinds = (trinity3d.SLIDING, trinity3d.FULL, trinity3d.SLIDING)
    model = trinity3d.Trinity3D(widths=trinity3d.Widths(
        layer_types=kinds, dense_layers=1, hidden_size=32, heads=4,
        kv_heads=2, head_dim=8, sliding_window=16, intermediate_size=48,
        num_experts=16, held=(0, 4), experts_per_token=4, expert_width=16,
        block=16, patch=4))
    x = jnp.zeros((2, 12, 14, 12, 1))  # 36 tokens: blocks of 16, 16, 4

    def loss(p):
        logits, aux = model.apply(p, x)
        return jnp.sum(logits) + aux["loss"]

    params = model.init(jax.random.key(0), x)
    text = jax.jit(jax.grad(loss)).lower(params).as_text(debug_info=True)
    paths = set(re.findall(r'"(jit\([^"]*)"', text))
    rules = scopes.load_rules(trinity_scopes.RULES)["layer"]
    stages = ("swa_core", "full_core", "attn_gate", "attn", "mlp",
              "shared_expert", "router")
    met = set()
    for path in paths:
        cls = scopes.classify(rules, path + ":op", "%op")
        if re.search(r"/(attn|mlp)(_post)?_norm/", path) \
                and "/layers_" in path:
            assert cls == "norm", path
        if re.search(r"/(q|k)_norm/", path):
            assert cls == "qk_norm", path
        if "/layers_" in path and path.endswith("dot_general"):
            # off the TPU the held experts multiply by dot_general too
            assert cls in stages + ("experts",), path
            if cls != "experts":
                met.add((cls, "transpose(" in path))
        for i, kind in enumerate(kinds):
            if f"/layers_{i}/" in path and "_core/" in path:
                assert cls == ("swa_core" if kind == trinity3d.SLIDING
                               else "full_core"), path
    assert met == {(c, back) for c in stages for back in (False, True)}
    held = {scopes.classify(rules, p + ":op", "%op") for p in paths
            if "/moe/while/body/" in p}
    assert {"dispatch", "experts", "combine"} <= held


def test_the_counting_function_by_hand(reference):
    """4,864 tokens: the triangle has 4864 x 4865 / 2 = 11,831,680 pairs a
    head; inside a window of 2,048 the first 2,048 queries read 2048 x
    2049 / 2 = 2,098,176 and the other 2,816 read 2,048 each: 7,865,344
    (66.5%). At 2 x (128 + 128) = 512 operations a pair, 32 query heads:
    four sliding layers 515.5 GFLOP a sample forward, the full one 193.8.
    q and o 32 x 128, k and v 4 x 128, bf16: 89.65 MB a layer and pass."""
    tape = reference.published_tape()
    assert trinity_scopes.window_pairs(T, None) == FULL_PAIRS \
        == T * (T + 1) // 2
    assert trinity_scopes.window_pairs(T, W) == SWA_PAIRS \
        == W * (W + 1) // 2 + (T - W) * W
    assert trinity_scopes.window_pairs(640, W) == 640 * 641 // 2
    assert trinity_scopes.window_pairs(T, 1) == T  # the query's own key
    assert reference.core_pairs(tape, SLIDING) == SWA_PAIRS
    assert reference.core_pairs(tape, FULL) == FULL_PAIRS
    assert reference.ops.window_pairs(T, W) == SWA_PAIRS
    per_pass = T * (2 * HEADS * D + 2 * KV * D) * 2
    assert per_pass == 89_653_248
    for kind, pairs, layers in ((SLIDING, SWA_PAIRS, 4),
                                (FULL, FULL_PAIRS, 1)):
        forward = 512 * pairs * HEADS * layers
        assert reference.core_flops_per_sample(tape, kind) == forward
        assert reference.core_bytes_per_sample(tape, kind) == \
            per_pass * layers
        flops, nbytes = trinity_scopes.core_work(reference, tape, kind, 48)
        assert flops == 3 * forward * 48
        assert nbytes == 3 * per_pass * layers * 48
    assert 512 * SWA_PAIRS * HEADS * 4 == pytest.approx(515.46e9, rel=1e-4)
    assert 512 * FULL_PAIRS * HEADS == pytest.approx(193.85e9, rel=1e-4)
    # a tape that counted the triangle in a sliding layer is refused
    wrong = [dict(r, out_spatial=(FULL_PAIRS,))
             if r["name"].endswith("/attn/scores") else r for r in tape]
    with pytest.raises(ValueError, match="does not count the pairs"):
        trinity_scopes.core_work(reference, wrong, SLIDING, 1)
    # a held expert's row: gate-and-up 2048 x 2048, down 1024 x 2048
    assert reference.expert_layers(tape) == 4
    assert reference.expert_flops_per_row(tape) == \
        2 * (2048 * 2048 + 1024 * 2048)
    rows = 9728.0
    assert reference.expert_bytes_per_step(tape, rows) == 3 * (
        16 * (2048 * 2048 + 1024 * 2048) * 2
        + rows * (2048 + 2048 + 1024 + 2048) * 2)
    # the whole sample: 8.74 TFLOP for training, as ISSUE 44 expects
    from benchmark import flops as bench_flops

    assert bench_flops.training_flops_per_sample(tape) == pytest.approx(
        8.74e12, rel=1e-3)


def _ctx_with_table(seconds_by_class, **more):
    busy = sum(seconds_by_class.values())
    table = {"busy_s": busy, "share_pct": {"layer": {
        c: 100.0 * s / busy for c, s in seconds_by_class.items()}}}
    return {trinity_scopes.KEY: table, "peak": PEAK, "chips": 1, **more}


@pytest.mark.parametrize("name", sorted(SHARE_METRICS))
def test_shares_sum_their_classes(name):
    ctx = _ctx_with_table({
        "swa_core": 4.0, "full_core": 1.5, "qk_norm": 0.5, "attn_gate": 1.0,
        "attn": 5.0, "mlp": 2.0, "shared_expert": 1.0, "router": 0.5,
        "dispatch": 0.5, "experts": 1.0, "combine": 0.5, "norm": 0.5,
        "optimizer": 1.5, "stem": 0.5})
    assert _read(name, ctx) == pytest.approx(SHARE_METRICS[name])


@pytest.mark.parametrize("name, cls, kind, pairs, layers", [
    ("swa_core_roofline_pct", "swa_core", SLIDING, SWA_PAIRS, 4),
    ("full_core_roofline_pct", "full_core", FULL, FULL_PAIRS, 1)])
def test_core_rooflines_are_the_flop_side(name, cls, kind, pairs, layers):
    """Two traced rounds of 48 samples: the sliding layers' operations 3 x
    515.5 GFLOP x 96 = 148.5 TFLOP -> 0.754 s at 197e12, their bytes 3 x
    358.6 MB x 96 = 103 GB -> 0.126 s at 819e9; the full layer's 0.283 s
    and 0.032 s. A scope that took four times its least is at 25%."""
    flop_s = 3 * 512 * pairs * HEADS * layers * 96 / 197e12
    byte_s = 3 * 89_653_248 * layers * 96 / 819e9
    assert flop_s > 5 * byte_s
    assert flop_s == pytest.approx(0.7536 if layers == 4 else 0.2834,
                                   rel=1e-3)
    ctx = _ctx_with_table({cls: 4 * flop_s, "mlp": 5.0},
                          trace={"real_samples": 96, "rounds": 2})
    assert _read(name, ctx) == pytest.approx(25.0, rel=1e-6)
    # the other kind's scope alone: this one has no seconds to read
    other = "full_core" if cls == "swa_core" else "swa_core"
    ctx = _ctx_with_table({other: 1.0}, trace={"real_samples": 96,
                                                "rounds": 2})
    assert _read(name, ctx) is None


def test_expert_roofline_counts_the_rows_that_landed(tracer):
    """Two traced rounds of 24 steps: 48 steps x 4 layers x 9,728 rows =
    1,867,776 rows held; operations x 12,582,912 x 3 = 70.5 TFLOP -> 0.358
    s at the peak; the weights' and rows' bytes take 0.225 s. ``experts``
    at twice 0.358 s reads 50."""
    rows = 48 * 4 * 9728
    for t in (10.0, 20.0):
        _span(tracer, "round_log", t, t + 0.1, tokens_routed=rows * 4,
              rows_held=rows // 2)
        _span(tracer, "dispatch_program", t - 5, t - 4, steps_real=24)
    flop_s = 3 * 2 * (2048 * 2048 + 1024 * 2048) * rows / 197e12
    assert flop_s == pytest.approx(0.3579, rel=1e-3)
    ctx = _ctx_with_table({"experts": 2 * flop_s, "swa_core": 5.0},
                          trace={"real_samples": 96, "rounds": 2})
    assert _read("trinity_expert_matmul_roofline_pct", ctx) == \
        pytest.approx(50.0, rel=1e-6)


def test_rows_held_share_reads_the_spans_that_start_in_the_window(tracer):
    _span(tracer, "round_log", 1.0, 1.1, tokens_routed=1000, rows_held=500)
    for t, held in ((10.0, 120), (20.0, 130), (22.0, 125)):
        _span(tracer, "round_log", t, t + 0.1, tokens_routed=1000,
              rows_held=held)
    ctx = _ctx_with_table({"full_core": 1.0},
                          window=_window(tracer, 5.0, 25.0))
    assert _read("trinity_rows_held_share_pct", ctx) == pytest.approx(12.5)


@pytest.mark.parametrize("name", NEW_METRICS)
def test_a_program_without_the_scopes_or_counters_reads_none(tracer, name):
    """The parent of PR 44, a CNN, the other trunks: a trace whose ops
    carry neither attention core's scope (though they may carry ``attn``,
    ``mlp``, ``experts``, ``norm`` and the routing counters, as the other
    held-expert trunks do)."""
    for t in (10.0, 20.0):
        _span(tracer, "round_log", t, t + 0.1, tokens_routed=1000,
              rows_held=470)
        _span(tracer, "dispatch_program", t - 5, t - 4, steps_real=24)
    ctx = _ctx_with_table({"optimizer": 1.0, "stem": 2.0, "attn": 0.5,
                           "mlp": 1.0, "experts": 1.0, "router": 0.2,
                           "shared_expert": 0.3, "norm": 0.1, "none": 3.0},
                          trace={"real_samples": 384, "rounds": 2},
                          window=_window(tracer, 0.0, 99.0))
    assert _read(name, ctx) is None
    # and with no trace at all
    assert _read(name, {"trace": None, "peak": PEAK, "chips": 1,
                        "window": _window(tracer, 0.0, 99.0)}) is None


def test_the_program_names_what_the_rules_read():
    names = pytest.importorskip("neuroimagedisttraining_tpu.obs.names")
    if not hasattr(names, "SCOPE_SWA_CORE"):
        pytest.skip("a program from before the windowed attention")
    rules = harness.read_json(trinity_scopes.RULES)
    mine = {s for k, v in rules["scope_names"].items() if k != "what"
            for s in v}
    assert mine <= set(names.MODEL_SCOPES)
    assert {names.SCOPE_SWA_CORE, names.SCOPE_FULL_CORE,
            names.SCOPE_QK_NORM, names.SCOPE_ATTN_GATE} <= mine
    assert set(trinity_scopes.OWN) <= mine
    assert names.SPAN_ROUND_LOG == trinity_scopes.ROUND_LOG
    assert names.SPAN_DISPATCH_PROGRAM == trinity_scopes.DISPATCH
    assert "steps_real" in names.ARGS_BY_SPAN[names.SPAN_DISPATCH_PROGRAM]
    from neuroimagedisttraining_tpu.engines.fedavg import expert_load

    assert {"tokens_routed", "rows_held"} <= set(
        expert_load([1.0] * 128, (0, 16)))


def test_configuration_file_holds_the_catalog_rows_config():
    """Every key of the catalog row's ``config`` is in the file under the
    same name with the same value, but the ``reduced`` ones; the published
    counts and the deployment stand beside them."""
    doc = harness.read_json(os.path.join(
        harness.BENCH, "configs", "trinity-abcd.json"))
    published = {
        "hidden_size": 2048, "num_attention_heads": 32,
        "num_key_value_heads": 4, "head_dim": 128, "sliding_window": 2048,
        "intermediate_size": 6144, "moe_intermediate_size": 1024,
        "num_shared_experts": 1, "num_experts_per_tok": 8,
        "route_scale": 2.826, "route_norm": True, "score_func": "sigmoid",
        "rope_theta": 10000, "rope_scaling": None, "rms_norm_eps": 1e-5,
        "global_attn_every_n_layers": 4, "load_balance_coeff": 0.001,
        "mup_enabled": True, "n_group": 1, "topk_group": 1,
        "model_type": "afmoe", "max_position_embeddings": 131072}
    for key, value in published.items():
        assert doc[key] == value, key
    assert doc["reduced"] == ["num_hidden_layers", "num_dense_layers",
                              "layer_types", "num_experts", "vocab_size",
                              "cohort"]
    assert doc["num_hidden_layers"] == 5 and doc["num_dense_layers"] == 1
    assert doc["layer_types"] == [SLIDING, SLIDING, FULL, SLIDING, SLIDING]
    # three sliding layers to one full among the expert layers
    assert doc["layer_types"][1:].count(SLIDING) == 3
    assert doc["num_experts"] in (16, 8)
    assert "vocab_size" not in doc
    assert doc["published"]["num_hidden_layers"] == 32
    assert doc["published"]["num_experts"] == 128
    assert doc["published"]["vocab_size"] == 200192
    assert "Eight chips share each layer" in doc["deployment"]
    assert "608 rows" in doc["deployment"] and "4,864" in doc["deployment"]
    assert "NOT built" in doc["deployment"]
    assert set(doc["reduced"]) == set(doc["reduced_notes"])
    assert CELL.split(".")[1] in doc["correct"]
    for key, value in doc["assumed"].items():
        assert "Source:" in value, key
    catalog = "/opt/skills/guides/model-configs/architectures.jsonl"
    if os.path.exists(catalog):
        (row,) = [r for r in map(json.loads, open(catalog))
                  if r["name"] == "Trinity-Mini"]
        assert row["source_url"] in doc["source"]
        assert row["config"]["layer_types"][1:6] == doc["layer_types"]
        for key, value in row["config"].items():
            if key not in doc["reduced"]:
                assert doc[key] == value, key


def test_the_cell_and_its_metrics_are_in_the_index():
    bench = harness.read_json(os.path.join(harness.ROOT, "BENCHMARK.json"))
    (w,) = [w for w in bench["workloads"] if w["name"] == CELL]
    assert w["chips"] == 1 and w["config"] == "trinity-abcd"
    assert w["traffic"] == "fedavg_fold3_s10k" and len(w["why"]) <= 200
    for said in ("4,864 tokens", "77,824 slots", "window 2,048",
                 "32 heads on 4", "16 of 128", "8x"):
        assert said in w["why"], said
    (c,) = [c for c in bench["configs"] if c["name"] == "trinity-abcd"]
    for said in ("Trinity-Mini/blob/main/config.json", "afmoe", "catalog"):
        assert said in c["source"], said
    assert len(c["source"]) <= 200 and len(c["why"]) <= 200
    by_name = {m["name"]: m for m in bench["per_layer"]}
    assert len(NEW_METRICS) == 12
    for name in NEW_METRICS:
        m = by_name[name]
        assert CELL in m["workloads"]
        assert m["moves"] == "train_samples_per_s" and m["unit"] == "%"
        assert os.path.exists(os.path.join(harness.BENCH, "metrics",
                                           name + ".json"))
        assert callable(importlib.import_module(
            "benchmark.metrics." + name).read)
    # 9 cells of 24, one of them on four chips
    assert len(bench["workloads"]) == 9
    assert sum(w["chips"] == 4 for w in bench["workloads"]) == 1
    _, _, config, traffic = harness.load_cell(CELL)
    assert harness.site_sizes_of(config, traffic) == [10, 10, 10]
    bands = harness.correct_bands(config, traffic)
    assert bands is config["correct"]["fedavg_fold3_s10k"]  # its own
    assert traffic["expect"]["tpu_custom_call"]  # megablox.gmm, attention
    # the model name is in the registry, and says what a row costs
    models = pytest.importorskip("neuroimagedisttraining_tpu.models")
    argv = config["argv"]
    try:
        model = models.create_model(argv[argv.index("--model") + 1])
    except ValueError:
        pytest.skip("a program from before the model")
    assert model.row_tokens(tuple(config["input_shape"])) == T
    assert len(model.widths.layer_types) == config["num_hidden_layers"]
    assert list(model.widths.layer_types) == config["layer_types"]
    assert model.widths.dense_layers == config["num_dense_layers"]
    assert model.held_experts == (0, config["num_experts"])
    assert model.widths.num_experts == config["published"]["num_experts"]
    assert model.widths.sliding_window == config["sliding_window"]
