"""The Moonlight cell's own readers and files (PR 40).

The layer partition on op paths recorded from the program (forward,
rematerialised and ``transpose(...)``), and on the paths of a small model
lowered here; the counting functions against numbers worked by hand; the
roofline arithmetic on a hand-made table; the routing counter on hand-made
``round_log`` spans; every reader returning ``None`` where a program has no
such scope or counter (the parent, a CNN, the other trunks); the
configuration file against the catalog row it was copied from; and the
traffic twin against the file it was copied from. Nothing here pins where
in ``per_layer`` the entries stand.
"""

import importlib
import json
import os
import re

import pytest

from benchmark import harness, moonlight_scopes, scopes

PEAK = {"bf16_flops_per_s": 197e12, "hbm_bytes_per_s": 819e9}
CELL = "moonlight.fedavg_fold3_s10k"
SHARE_METRICS = ("mla_time_share_pct", "mla_latent_time_share_pct",
                 "mla_core_time_share_pct", "moon_moe_time_share_pct",
                 "moon_shared_time_share_pct",
                 "moon_dense_ffn_time_share_pct")
NEW_METRICS = SHARE_METRICS + ("mla_core_roofline_pct",
                               "moon_expert_matmul_roofline_pct",
                               "moon_rows_held_share_pct")
T, HEADS, DN, DR, DV, LAYERS = 4864, 16, 128, 64, 128, 6
PAIRS = 11_831_680


@pytest.fixture
def tracer():
    from neuroimagedisttraining_tpu.obs import trace as obs_trace

    obs_trace.TRACER.arm()
    yield obs_trace.TRACER
    obs_trace.TRACER.disarm()


@pytest.fixture(scope="module")
def reference():
    return harness.load_reference({"reference": "moonlight-abcd.py",
                                   "name": "moonlight-abcd"})


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


_FWD = "jit(round_fn)/local_train/fwd_bwd/jvp(Moonlight3D)/"
_BWD = ("jit(round_fn)/local_train/fwd_bwd/transpose(jvp(Moonlight3D))/"
        "jvp(Moonlight3D)/checkpoint/")
_CORE = "attn/mla/mla_core/checkpoint/"
#: op path, as the program's lowering records it -> the class the layer
#: partition must give it
PATHS = {
    _FWD + "layers_0/" + _CORE + "bqad,bkad->baqk/dot_general": "mla_core",
    _FWD + "layers_3/attn/mla/mla_core/broadcast_in_dim": "mla_core",
    _BWD + "layers_0/" + _CORE + "baqk,bkad->bqad/dot_general": "mla_core",
    _BWD + "layers_5/" + _CORE
    + "rematted_computation/bqad,bkad->baqk/dot_general": "mla_core",
    _BWD + "layers_1/" + _CORE + "rematted_computation/exp": "mla_core",
    _FWD + "layers_0/attn/mla/mla_latent/kv_a_proj/dot_general":
        "mla_latent",
    _FWD + "layers_2/attn/mla/mla_latent/kv_norm/mul": "mla_latent",
    _BWD + "layers_0/attn/mla/mla_latent/kv_b_proj/dot_general":
        "mla_latent",
    _BWD + "layers_4/attn/mla/mla_latent/add_any": "mla_latent",
    # what is left of attn: W_q, its rotary embedding, W_o
    _FWD + "layers_0/attn/mla/q_proj/dot_general": "attn",
    _BWD + "layers_0/attn/mla/o_proj/dot_general": "attn",
    _FWD + "layers_2/attn/mla/mul": "attn",
    _FWD + "layers_0/mlp/ffn/gate_proj/dot_general": "mlp",
    _BWD + "layers_0/mlp/ffn/down_proj/dot_general": "mlp",
    _BWD + "layers_0/mlp/ffn/jit(silu)/add": "mlp",
    _FWD + "layers_1/shared_expert/shared/up_proj/dot_general":
        "shared_expert",
    _BWD + "layers_5/shared_expert/shared/down_proj/dot_general":
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
    _BWD + "layers_1/mlp_norm/mul": "norm",
    _FWD + "stem/patch_embed/dot_general": "stem",
    _FWD + "head/final_norm/mul": "head",
    "jit(round_fn)/local_train/update/add": "optimizer",
    "jit(eval_all)/eval/Moonlight3D/layers_0/" + _CORE + "exp": "eval",
    "jit(round_fn)/aggregate/add": "aggregate",
    "jit(round_fn)/local_train/batch_prep/convert_element_type": "input",
}


@pytest.mark.parametrize("path", sorted(PATHS))
def test_layer_partition_classifies_by_the_programs_scopes(path):
    rules = scopes.load_rules(moonlight_scopes.RULES)
    table = scopes.build({"%op = f32[] x()": 1.0},
                         {"%op = f32[] x()": {"tf_op": path}}, rules=rules)
    assert table["share_pct"]["layer"] == {PATHS[path]: 100.0}


def test_every_matrix_product_of_a_lowered_step_has_a_class():
    """The small model's gradient, lowered here: every ``dot_general`` of a
    layer lands in a class of its stage, forward and ``transpose(...)``
    alike, each met both ways; and no pre-norm lies inside ``attn``."""
    jax = pytest.importorskip("jax")
    import jax.numpy as jnp

    from neuroimagedisttraining_tpu.models.moonlight3d import (
        Moonlight3D, Widths,
    )

    model = Moonlight3D(widths=Widths(
        dense_layers=1, expert_layers=2, hidden_size=32, heads=2,
        qk_nope_head_dim=8, qk_rope_head_dim=8, v_head_dim=8,
        kv_lora_rank=16, intermediate_size=48, num_experts=16, held=(0, 4),
        experts_per_token=4, expert_width=16, block=16, patch=4))
    x = jnp.zeros((2, 12, 14, 12, 1))  # 36 tokens: blocks of 16, 16, 4

    def loss(p):
        logits, aux = model.apply(p, x)
        return jnp.sum(logits) + aux["loss"]

    params = model.init(jax.random.key(0), x)
    text = jax.jit(jax.grad(loss)).lower(params).as_text(debug_info=True)
    paths = set(re.findall(r'"(jit\([^"]*)"', text))
    rules = scopes.load_rules(moonlight_scopes.RULES)["layer"]
    stages = ("mla_latent", "mla_core", "attn", "mlp", "shared_expert",
              "router")
    met = set()
    for path in paths:
        cls = scopes.classify(rules, path + ":op", "%op")
        if "_norm/" in path and "kv_norm" not in path and "/layers_" in path:
            assert cls == "norm", path
        if "/layers_" in path and path.endswith("dot_general"):
            # off the TPU the held experts multiply by dot_general too
            assert cls in stages + ("experts",), path
            if cls != "experts":
                met.add((cls, "transpose(" in path))
    assert met == {(c, back) for c in stages for back in (False, True)}
    # the held experts' sublayer off the TPU multiplies by ragged_dot
    held = {scopes.classify(rules, p + ":op", "%op") for p in paths
            if "/moe/while/body/" in p}
    assert {"dispatch", "experts", "combine"} <= held


def test_the_counting_functions_by_hand(reference):
    """4,864 tokens: 4864 x 4865 / 2 = 11,831,680 causal pairs a head. At
    2 x (192 + 128) operations a pair, 16 heads, 6 layers: 726.9 GFLOP a
    sample forward. q 16 x 192, the heads' keys 16 x 128, one rotary key
    64, v and o 16 x 128 each, bf16: 541.7 MB a pass over the 6 layers."""
    tape = reference.published_tape()
    assert T * (T + 1) // 2 == PAIRS == moonlight_scopes.causal_pairs(T)
    assert reference.core_pairs(tape) == PAIRS
    forward = 2 * (DN + DR + DV) * PAIRS * HEADS * LAYERS
    assert reference.core_flops_per_sample(tape) == forward
    assert forward == pytest.approx(726.94e9, rel=1e-4)
    per_pass = T * (HEADS * (DN + DR) + HEADS * DN + DR
                    + 2 * HEADS * DV) * 2 * LAYERS
    assert reference.core_bytes_per_sample(tape) == per_pass
    flops, nbytes = moonlight_scopes.core_work(reference, tape, 48)
    assert flops == 3 * forward * 48 and nbytes == 3 * per_pass * 48
    # a held expert's row: gate-and-up 2048 x 2816, down 1408 x 2048
    assert reference.expert_layers(tape) == 5
    assert reference.expert_flops_per_row(tape) == \
        2 * (2048 * 2816 + 1408 * 2048)
    rows = 7296.0
    assert reference.expert_bytes_per_step(tape, rows) == 3 * (
        8 * (2048 * 2816 + 1408 * 2048) * 2
        + rows * (2048 + 2816 + 1408 + 2048) * 2)
    # the whole sample: 10.13 TFLOP for training, as ISSUE 40 expects
    from benchmark import flops as bench_flops

    assert bench_flops.training_flops_per_sample(tape) == pytest.approx(
        10.13e12, rel=1e-3)


def _ctx_with_table(seconds_by_class, **more):
    busy = sum(seconds_by_class.values())
    table = {"busy_s": busy, "share_pct": {"layer": {
        c: 100.0 * s / busy for c, s in seconds_by_class.items()}}}
    return {moonlight_scopes.KEY: table, "peak": PEAK, "chips": 1, **more}


def test_shares_sum_their_classes():
    ctx = _ctx_with_table({"mla_latent": 0.5, "mla_core": 4.0, "attn": 1.0,
                           "mlp": 1.5, "shared_expert": 1.0, "router": 0.25,
                           "dispatch": 0.25, "experts": 0.5, "combine": 0.25,
                           "norm": 0.25, "optimizer": 0.5})
    assert _read("mla_time_share_pct", ctx) == pytest.approx(55.0)
    assert _read("mla_latent_time_share_pct", ctx) == pytest.approx(5.0)
    assert _read("mla_core_time_share_pct", ctx) == pytest.approx(40.0)
    assert _read("moon_moe_time_share_pct", ctx) == pytest.approx(12.5)
    assert _read("moon_shared_time_share_pct", ctx) == pytest.approx(10.0)
    assert _read("moon_dense_ffn_time_share_pct", ctx) == pytest.approx(15.0)


def test_core_roofline_is_the_flop_side():
    """Two traced rounds of 48 samples: operations 3 x 726.9 GFLOP x 96 =
    209.4 TFLOP -> 1.063 s at 197e12; bytes 3 x 541.7 MB x 96 = 156 GB ->
    0.190 s at 819e9. A scope that took ten times 1.063 s is at 10%."""
    flop_s = 3 * 2 * (DN + DR + DV) * PAIRS * HEADS * LAYERS * 96 / 197e12
    byte_s = 3 * 541_655_040 * 96 / 819e9
    assert flop_s == pytest.approx(1.0627, rel=1e-3)
    assert byte_s == pytest.approx(0.1905, rel=2e-3)
    ctx = _ctx_with_table({"mla_core": 10 * flop_s, "mlp": 5.0},
                          trace={"real_samples": 96, "rounds": 2})
    assert _read("mla_core_roofline_pct", ctx) == pytest.approx(10.0,
                                                                rel=1e-6)


def test_expert_roofline_counts_the_rows_that_landed(tracer):
    """Two traced rounds of 24 steps: 48 steps x 5 layers x 7,296 rows =
    1,751,040 rows held; operations x 17,301,504 x 3 = 90.9 TFLOP -> 0.461
    s at the peak; the weights' and rows' bytes take 0.228 s. ``experts``
    at twice 0.461 s reads 50."""
    rows = 48 * 5 * 7296
    for t in (10.0, 20.0):
        _span(tracer, "round_log", t, t + 0.1, tokens_routed=rows * 4,
              rows_held=rows // 2)
        _span(tracer, "dispatch_program", t - 5, t - 4, steps_real=24)
    flop_s = 3 * 2 * (2048 * 2816 + 1408 * 2048) * rows / 197e12
    assert flop_s == pytest.approx(0.4614, rel=1e-3)
    ctx = _ctx_with_table({"experts": 2 * flop_s, "mla_core": 5.0},
                          trace={"real_samples": 96, "rounds": 2})
    assert _read("moon_expert_matmul_roofline_pct", ctx) == pytest.approx(
        50.0, rel=1e-6)


def test_rows_held_share_reads_the_spans_that_start_in_the_window(tracer):
    _span(tracer, "round_log", 1.0, 1.1, tokens_routed=1000, rows_held=500)
    for t, held in ((10.0, 120), (20.0, 130), (22.0, 125)):
        _span(tracer, "round_log", t, t + 0.1, tokens_routed=1000,
              rows_held=held)
    ctx = _ctx_with_table({"mla_core": 1.0},
                          window=_window(tracer, 5.0, 25.0))
    assert _read("moon_rows_held_share_pct", ctx) == pytest.approx(12.5)


@pytest.mark.parametrize("name", NEW_METRICS)
def test_a_program_without_the_scopes_or_counters_reads_none(tracer, name):
    """The parent of PR 40, a CNN, the other trunks: a trace whose ops
    carry none of the latent attention's scopes (though they may carry
    ``attn``, ``mlp``, ``experts`` and the routing counters, as the other
    held-expert trunks do)."""
    for t in (10.0, 20.0):
        _span(tracer, "round_log", t, t + 0.1, tokens_routed=1000,
              rows_held=470)
        _span(tracer, "dispatch_program", t - 5, t - 4, steps_real=24)
    ctx = _ctx_with_table({"optimizer": 1.0, "stem": 2.0, "attn": 0.5,
                           "mlp": 1.0, "experts": 1.0, "router": 0.2,
                           "shared_expert": 0.3, "none": 3.0},
                          trace={"real_samples": 384, "rounds": 2},
                          window=_window(tracer, 0.0, 99.0))
    assert _read(name, ctx) is None
    # and with no trace at all
    assert _read(name, {"trace": None, "peak": PEAK, "chips": 1,
                        "window": _window(tracer, 0.0, 99.0)}) is None


def test_the_program_names_what_the_rules_read():
    names = pytest.importorskip("neuroimagedisttraining_tpu.obs.names")
    if not hasattr(names, "SCOPE_MLA_CORE"):
        pytest.skip("a program from before the latent attention")
    rules = harness.read_json(moonlight_scopes.RULES)
    mine = {s for k, v in rules["scope_names"].items() if k != "what"
            for s in v}
    assert mine <= set(names.MODEL_SCOPES)
    assert {names.SCOPE_MLA_CORE, names.SCOPE_MLA_LATENT} <= mine
    assert set(moonlight_scopes.OWN) <= mine
    assert names.SPAN_ROUND_LOG == moonlight_scopes.ROUND_LOG
    assert names.SPAN_DISPATCH_PROGRAM == moonlight_scopes.DISPATCH
    assert "steps_real" in names.ARGS_BY_SPAN[names.SPAN_DISPATCH_PROGRAM]
    from neuroimagedisttraining_tpu.engines.fedavg import expert_load

    assert {"tokens_routed", "rows_held"} <= set(
        expert_load([1.0] * 64, (0, 8)))


def test_configuration_file_holds_the_catalog_rows_config():
    """Every key of the catalog row's ``config`` is in the file under the
    same name with the same value, but the ``reduced`` ones; the published
    counts and the deployment stand beside them."""
    doc = harness.read_json(os.path.join(
        harness.BENCH, "configs", "moonlight-abcd.json"))
    published = {
        "hidden_size": 2048, "num_attention_heads": 16,
        "num_key_value_heads": 16, "qk_nope_head_dim": 128,
        "qk_rope_head_dim": 64, "v_head_dim": 128, "kv_lora_rank": 512,
        "q_lora_rank": None, "intermediate_size": 11264,
        "moe_intermediate_size": 1408, "n_shared_experts": 2,
        "num_experts_per_tok": 6, "routed_scaling_factor": 2.446,
        "rope_theta": 50000, "rms_norm_eps": 1e-5,
        "first_k_dense_replace": 1, "scoring_func": "sigmoid",
        "seq_aux": True, "norm_topk_prob": True, "n_group": 1,
        "topk_group": 1, "topk_method": "noaux_tc",
        "model_type": "deepseek_v3", "max_position_embeddings": 8192}
    for key, value in published.items():
        assert doc[key] == value, key
    assert doc["reduced"] == ["num_hidden_layers", "n_routed_experts",
                              "vocab_size", "cohort"]
    assert doc["num_hidden_layers"] in (6, 5)  # 1 + 5, or 1 + 4
    assert doc["n_routed_experts"] == 8
    assert "vocab_size" not in doc
    assert doc["published"] == {"num_hidden_layers": 27,
                                "n_routed_experts": 64,
                                "vocab_size": 163840}
    assert "Eight chips share each layer" in doc["deployment"]
    assert "912 rows" in doc["deployment"] and "7,296" in doc["deployment"]
    assert "NOT built" in doc["deployment"]
    assert set(doc["reduced"]) == set(doc["reduced_notes"])
    assert CELL.split(".")[1] in doc["correct"]
    for key, value in doc["assumed"].items():
        assert "Source:" in value, key
    catalog = "/opt/skills/guides/model-configs/architectures.jsonl"
    if os.path.exists(catalog):
        (row,) = [r for r in map(json.loads, open(catalog))
                  if r["name"] == "Moonlight-16B-A3B"]
        assert row["source_url"] in doc["source"]
        for key, value in row["config"].items():
            if key not in doc["reduced"]:
                assert doc[key] == value, key


def test_the_traffic_twin_differs_where_it_says():
    """``fedavg_fold3_s10k`` is ``fedavg_fold3_s10`` with a kernel
    expected in the compiled round: its name, what it says of itself, that
    expectation and the placeholder bands' reason differ, nothing else."""
    twin, first = (harness.read_json(os.path.join(
        harness.BENCH, "traffic", name + ".json"))
        for name in ("fedavg_fold3_s10k", "fedavg_fold3_s10"))
    assert twin["name"] == "fedavg_fold3_s10k"
    assert twin["expect"]["tpu_custom_call"] is True
    assert first["expect"]["tpu_custom_call"] is False
    for doc in (twin, first):
        doc.pop("name"), doc.pop("what")
        doc["expect"].pop("tpu_custom_call")
        doc["correct"].pop("reason")
    assert twin == first


def test_the_cell_and_its_metrics_are_in_the_index():
    bench = harness.read_json(os.path.join(harness.ROOT, "BENCHMARK.json"))
    (w,) = [w for w in bench["workloads"] if w["name"] == CELL]
    assert w["chips"] == 1 and w["config"] == "moonlight-abcd"
    assert w["traffic"] == "fedavg_fold3_s10k"
    for said in ("9,728 tokens", "58,368 slots", "8 of 64", "8x"):
        assert said in w["why"], said
    by_name = {m["name"]: m for m in bench["per_layer"]}
    for name in NEW_METRICS:
        m = by_name[name]
        assert m["workloads"] == [CELL]
        assert m["moves"] == "train_samples_per_s" and m["unit"] == "%"
        assert os.path.exists(os.path.join(harness.BENCH, "metrics",
                                           name + ".json"))
        assert callable(importlib.import_module(
            "benchmark.metrics." + name).read)
    # evaluation's counter stays the one cell's that brought it
    assert by_name["eval_rows_run_share_pct"]["workloads"] == [
        "evabyte.fedavg_fold3_s10"]
    _, _, config, traffic = harness.load_cell(CELL)
    assert harness.site_sizes_of(config, traffic) == [10, 10, 10]
    bands = harness.correct_bands(config, traffic)
    assert bands is config["correct"]["fedavg_fold3_s10k"]  # its own
    assert traffic["expect"]["tpu_custom_call"]  # megablox.gmm
    # the model name is in the registry, and says what a row costs
    from neuroimagedisttraining_tpu.models import create_model

    argv = config["argv"]
    model = create_model(argv[argv.index("--model") + 1])
    assert model.row_tokens(tuple(config["input_shape"])) == T
    assert model.widths.dense_layers + model.widths.expert_layers == \
        config["num_hidden_layers"]
    assert model.held_experts == (0, config["n_routed_experts"])
    assert model.widths.num_experts == \
        config["published"]["n_routed_experts"]
