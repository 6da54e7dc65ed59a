"""The ZAYA1 cell's own readers and files (PR 31).

The layer partition on hand-made op paths, the counter readers on
hand-made ``round_log`` spans, the roofline arithmetic on a hand-made
table, every reader returning ``None`` where a program has no such scope
or counter (the parent, a CNN, OLMoE, Nemotron-H), and the configuration
file against the catalog row it was copied from.
"""

import importlib
import json
import os

import pytest

from benchmark import harness, scopes, zaya_scopes

PEAK = {"bf16_flops_per_s": 197e12, "hbm_bytes_per_s": 819e9}
CELL = "zaya.fedavg_fold3"
SHARE_METRICS = ("cca_time_share_pct", "cca_mix_time_share_pct",
                 "zaya_router_time_share_pct", "zaya_moe_time_share_pct")
ROW_METRICS = ("zaya_rows_held_share_pct", "zaya_rows_skipped_share_pct")
NEW_METRICS = SHARE_METRICS + ROW_METRICS + (
    "cca_mix_roofline_pct", "zaya_expert_matmul_roofline_pct")


@pytest.fixture
def tracer():
    from neuroimagedisttraining_tpu.obs import trace as obs_trace

    obs_trace.TRACER.arm()
    yield obs_trace.TRACER
    obs_trace.TRACER.disarm()


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


#: op path -> the class the layer partition must give it
PATHS = {
    "jit(round)/local_train/fwd_bwd/jvp(Zaya3D)/layers_0/cca/cca_proj/"
    "q_proj/dot_general": "cca_proj",
    "jit(round)/local_train/fwd_bwd/transpose(jvp(Zaya3D))/layers_0/cca/"
    "cca_conv/bthc,hcd->bthd/dot_general": "cca_conv",
    "jit(round)/fwd_bwd/rematted_computation/layers_2/cca/cca_mix/rsqrt":
        "cca_mix",
    "jit(round)/fwd_bwd/jvp(Z)/layers_2/cca/attn/o_proj/dot_general":
        "attn",
    "jit(round)/fwd_bwd/jvp(Z)/layers_1/moe/router/down/dot_general":
        "router",
    # the router's own RMSNorm is the router's, not a pre-norm
    "jit(round)/fwd_bwd/jvp(Z)/layers_1/moe/router/norm/rsqrt": "router",
    "jit(round)/fwd_bwd/jvp(Z)/layers_1/moe/dispatch/sort": "dispatch",
    "jit(round)/fwd_bwd/jvp(Z)/layers_1/moe/experts/while/body/gmm":
        "experts",
    "jit(round)/fwd_bwd/jvp(Z)/layers_1/moe/combine/scatter-add":
        "combine",
    "jit(round)/fwd_bwd/jvp(Z)/layers_1/attn_merge/mul": "merge",
    "jit(round)/fwd_bwd/jvp(Z)/layers_1/moe_merge/add": "merge",
    "jit(round)/fwd_bwd/jvp(Z)/layers_3/attn_norm/mul": "norm",
    "jit(round)/fwd_bwd/jvp(Z)/layers_3/moe_norm/mul": "norm",
    "jit(round)/fwd_bwd/jvp(Z)/stem/patch_embed/dot": "stem",
    "jit(round)/fwd_bwd/jvp(Z)/head/final_norm/mul": "head",
    "jit(round)/local_train/update/add": "optimizer",
    "jit(eval)/eval/Z/layers_0/cca/cca_mix/mul": "eval",
    "jit(round)/aggregate/add": "aggregate",
    "jit(round)/local_train/batch_prep/take": "input",
}


@pytest.mark.parametrize("path", sorted(PATHS))
def test_layer_partition_classifies_by_the_programs_scopes(path):
    rules = scopes.load_rules(zaya_scopes.RULES)
    table = scopes.build({"%op = f32[] x()": 1.0},
                         {"%op = f32[] x()": {"tf_op": path}}, rules=rules)
    assert table["share_pct"]["layer"] == {PATHS[path]: 100.0}


def _ctx_with_table(seconds_by_class, **more):
    busy = sum(seconds_by_class.values())
    table = {"busy_s": busy, "share_pct": {"layer": {
        c: 100.0 * s / busy for c, s in seconds_by_class.items()}}}
    return {zaya_scopes.KEY: table, "peak": PEAK, "chips": 1, **more}


def test_shares_sum_their_classes():
    ctx = _ctx_with_table({"cca_proj": 1.5, "cca_conv": 0.5, "cca_mix": 1.0,
                           "attn": 1.0, "router": 0.5, "dispatch": 0.5,
                           "experts": 2.5, "combine": 0.5, "merge": 0.5,
                           "optimizer": 1.5})
    assert _read("cca_time_share_pct", ctx) == pytest.approx(40.0)
    assert _read("cca_mix_time_share_pct", ctx) == pytest.approx(15.0)
    assert _read("zaya_router_time_share_pct", ctx) == pytest.approx(5.0)
    assert _read("zaya_moe_time_share_pct", ctx) == pytest.approx(35.0)


def test_cca_mix_roofline_is_the_bytes_side():
    """One round of 384 samples (24 steps): bytes 3 passes x 384 x 640
    tokens x 11,776 elements x 2 B x 5 layers = 86.8 GB -> 106.0 ms at 819
    GB/s; operations 3 x 2.11 GFLOP x 384 = 2.43 TFLOP -> 12.4 ms. Scopes
    that took 1.06 s are at 10% of the (bytes) roofline."""
    nbytes = 3 * 384 * 640 * (9 * 1280 + 2 * 128) * 2 * 5
    ctx = _ctx_with_table({"cca_conv": 0.4, "cca_mix": nbytes / 819e9 / 0.1
                           - 0.4, "attn": 1.0},
                          trace={"real_samples": 384, "rounds": 1})
    assert nbytes / 819e9 == pytest.approx(0.1060, rel=1e-3)
    assert _read("cca_mix_roofline_pct", ctx) == pytest.approx(10.0,
                                                               rel=1e-6)


def test_expert_roofline_counts_the_rows_that_landed(tracer):
    """Two traced rounds of 24 steps, 5 layers, 4,800 rows a step and
    layer: 1,152,000 rows. Operations 3 x 3 x 2 x 2048^2 x 1,152,000 =
    87.0 TFLOP -> 441.5 ms; bytes 240 step-layers x 3 passes x (201.3 MB of
    weights + 4,800 x 10,240 x 2 B) = 215.7 GB -> 263 ms: the FLOP side
    binds. Rounds before the slice are not counted."""
    for r, rows in enumerate((999_999, 576_000, 576_000)):
        _span(tracer, "dispatch_program", 10 * r, 10 * r + 1, steps_real=24)
        _span(tracer, "round_log", 10 * r + 8, 10 * r + 9, rows_held=rows,
              rows_skipped=70_000, tokens_routed=1_228_800)
    flop_s = 3 * 3 * 2 * 2048 ** 2 * 1_152_000 / 197e12
    ctx = _ctx_with_table({"experts": 10 * flop_s, "cca_mix": 1.0},
                          trace={"real_samples": 768, "rounds": 2})
    assert flop_s == pytest.approx(0.4415, rel=1e-3)
    assert _read("zaya_expert_matmul_roofline_pct", ctx) == pytest.approx(
        10.0, rel=1e-6)
    # a tenth of the rows (480 a step and layer): the held weights' bytes
    # bind: 240 x 3 x (8 x 3 x 2048^2 x 2 B + 480 x 10,240 x 2 B) = 152.0
    # GB -> 185.6 ms, against 44 ms of operations
    tracer.disarm(), tracer.arm()
    for r in range(2):
        _span(tracer, "dispatch_program", 10 * r, 10 * r + 1, steps_real=24)
        _span(tracer, "round_log", 10 * r + 8, 10 * r + 9, rows_held=57_600)
    nbytes = 240 * 3 * (8 * 3 * 2048 ** 2 * 2 + 480 * 10240 * 2)
    assert _read("zaya_expert_matmul_roofline_pct", ctx) == pytest.approx(
        100.0 * nbytes / 819e9 / (10 * flop_s), rel=1e-6)


@pytest.mark.parametrize("name, want", zip(ROW_METRICS, (46.875, 6.25)))
def test_row_shares_read_the_rounds_counters(tracer, name, want):
    """The median over the rounds whose ``round_log`` starts inside the
    window (the second and third)."""
    for r, (held, skipped) in enumerate(
            ((1, 1), (115_200, 15_360), (115_200, 15_360), (9, 9))):
        _span(tracer, "round_log", 10.4 + 10 * r, 10.6 + 10 * r,
              rows_held=held, rows_skipped=skipped, tokens_routed=245_760,
              round=r)
    assert _read(name, {"window": _window(tracer, 15.0, 35.0)}) == \
        pytest.approx(want)


@pytest.mark.parametrize("name", NEW_METRICS)
def test_a_program_without_the_scopes_or_counters_reads_none(tracer, name):
    """The parent of PR 31, a CNN, OLMoE, Nemotron-H: a trace whose ops
    carry none of the compressed attention's scopes (though they may
    carry ``router`` or ``experts``), a ``round_log`` with ``rows_held``
    and no ``rows_skipped``."""
    _span(tracer, "round_log", 10.4, 10.6, tokens_routed=100, rows_held=6,
          round=0)
    _span(tracer, "dispatch_program", 1.0, 2.0, steps_real=24)
    ctx = _ctx_with_table({"optimizer": 1.0, "stem": 2.0, "experts": 1.0,
                           "router": 0.5, "attn": 0.5},
                          trace={"real_samples": 384, "rounds": 1},
                          window=_window(tracer, 0.0, 99.0))
    assert _read(name, ctx) is None
    # and with no trace at all
    assert _read(name, {"trace": None, "peak": PEAK, "chips": 1,
                        "window": _window(tracer, 0.0, 99.0)}) is None


def test_the_round_driver_writes_what_the_readers_read():
    """The arguments' names on the span are the program's own
    (engines/fedavg.py), where the program has them."""
    fedavg = pytest.importorskip("neuroimagedisttraining_tpu.engines.fedavg")
    import inspect

    import numpy as np

    if "skip" not in inspect.signature(fedavg.expert_load).parameters:
        pytest.skip("a program from before the skip output")
    args = fedavg.expert_load(np.arange(17.0), (0, 8), np.int32(0), 9728,
                              skip=16)
    for name in ROW_METRICS:
        assert _spec(name)["arg"] in args
    assert args["rows_held"] == 28 and args["rows_skipped"] == 16


def test_configuration_file_holds_the_catalog_rows_config():
    """Every key of the catalog row's ``config`` is in the file under the
    same name with the same value, but the ``reduced`` ones; the published
    counts and the deployment stand beside them."""
    doc = harness.read_json(os.path.join(
        harness.BENCH, "configs", "zaya1-abcd.json"))
    published = {
        "hidden_size": 2048, "num_attention_heads": 8,
        "num_key_value_heads": 2, "head_dim": 128, "cca_time0": 2,
        "cca_time1": 2, "partial_rotary_factor": 0.5,
        "moe_intermediate_size": 2048, "num_experts_per_tok": 1,
        "router_hidden_size": 256, "rms_norm_eps": 1e-5,
        "attention_bias": False, "hidden_act": "silu",
        "model_type": "zaya", "sliding_window": None,
        "tie_word_embeddings": True, "max_position_embeddings": 131072}
    for key, value in published.items():
        assert doc[key] == value, key
    assert doc["rope_parameters"]["hybrid"] == {
        "partial_rotary_factor": 0.5, "rope_theta": 5000000,
        "rope_type": "default"}
    assert doc["reduced"] == ["num_hidden_layers", "layer_types",
                              "num_experts", "vocab_size", "cohort"]
    assert doc["num_hidden_layers"] == 5 and doc["num_experts"] == 8
    assert doc["layer_types"] == ["hybrid"] * 5
    assert "vocab_size" not in doc
    assert doc["published"]["num_hidden_layers"] == 40
    assert doc["published"]["num_experts"] == 16
    assert doc["published"]["vocab_size"] == 262272
    assert "Two chips share each layer" in doc["deployment"]
    assert "holds experts 0-7" in doc["deployment"]
    assert "NOT built" in doc["deployment"]
    assert set(doc["reduced"]) == set(doc["reduced_notes"])
    assert "fedavg_fold3" in doc["correct"]
    catalog = "/opt/skills/guides/model-configs/architectures.jsonl"
    if os.path.exists(catalog):
        (row,) = [r for r in map(json.loads, open(catalog))
                  if r["name"] == "ZAYA1-8B"]
        assert row["source_url"] in doc["source"]
        for key, value in row["config"].items():
            if key not in doc["reduced"]:
                assert doc[key] == value, key


def test_the_cell_and_its_metrics_are_in_the_index():
    bench = harness.read_json(os.path.join(harness.ROOT, "BENCHMARK.json"))
    (w,) = [w for w in bench["workloads"] if w["name"] == CELL]
    assert w == bench["workloads"][-1]
    assert w["chips"] == 1 and w["config"] == "zaya1-abcd"
    assert w["traffic"] == "fedavg_fold3"
    mine = [m for m in bench["per_layer"] if m.get("workloads") == [CELL]]
    assert {m["name"] for m in mine} == set(NEW_METRICS)
    assert mine == bench["per_layer"][-len(mine):]  # added at the end
    for m in mine:
        assert m["moves"] == "train_samples_per_s"
        assert m["layer"] == "local step"
    _, _, config, traffic = harness.load_cell(CELL)
    assert harness.site_sizes_of(config, traffic) == [80, 80, 80]
    bands = harness.correct_bands(config, traffic)
    assert bands is config["correct"]["fedavg_fold3"]  # its own, not the mix's
    # the model name is in the registry
    from neuroimagedisttraining_tpu.models import create_model

    argv = config["argv"]
    model = create_model(argv[argv.index("--model") + 1])
    assert model.returns_aux and model.held_experts == (0, 8)
