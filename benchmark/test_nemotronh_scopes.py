"""The Nemotron-H cell's own readers and files (PR 29).

The trunk partition on hand-made op paths, the counter readers on
hand-made ``round_log`` spans, the roofline arithmetic on a hand-made
table, every reader returning ``None`` where a program has no such scope
or counter (the parent, a CNN, OLMoE), and the configuration file against
the catalog row it was copied from.
"""

import json
import os

import pytest

from benchmark import harness, nemotronh_scopes, scopes

PEAK = {"bf16_flops_per_s": 197e12, "hbm_bytes_per_s": 819e9}
SHARE_METRICS = ("ssm_time_share_pct", "ssd_time_share_pct",
                 "held_moe_time_share_pct", "held_dispatch_time_share_pct",
                 "gqa_time_share_pct")
LOAD_METRICS = ("held_load_max_over_mean", "routed_load_max_over_mean")
NEW_METRICS = SHARE_METRICS + LOAD_METRICS + (
    "ssd_roofline_pct", "held_expert_matmul_roofline_pct",
    "held_rows_share_pct")


@pytest.fixture
def tracer():
    from neuroimagedisttraining_tpu.obs import trace as obs_trace

    obs_trace.TRACER.arm()
    yield obs_trace.TRACER
    obs_trace.TRACER.disarm()


def _spec(name):
    return harness.read_json(os.path.join(
        harness.BENCH, "metrics", name + ".json"))["reader"]


def _span(tracer, name, t0, t1, **args):
    epoch = tracer.epoch_ns / 1e9
    tracer.record_interval(name, epoch + t0, epoch + t1, **args)


def _window(tracer, w0, w1):
    epoch = tracer.epoch_ns / 1e9
    return (epoch + w0, epoch + w1)


#: op path -> the class the trunk partition must give it
PATHS = {
    "jit(round)/local_train/fwd_bwd/jvp(NemotronH3D)/layers_0/mixer/ssd/dot":
        "ssd",
    "jit(round)/local_train/fwd_bwd/transpose(jvp(NemotronH3D))/layers_0/"
    "mixer/ssm_in_proj/in_proj/dot_general": "ssm_in_proj",
    "jit(round)/fwd_bwd/rematted_computation/layers_2/mixer/ssm_conv/mul":
        "ssm_conv",
    "jit(round)/fwd_bwd/jvp(N)/layers_2/mixer/ssm_gate_norm/rsqrt":
        "ssm_gate_norm",
    "jit(round)/fwd_bwd/jvp(N)/layers_2/mixer/ssm_out_proj/out_proj/dot":
        "ssm_out_proj",
    "jit(round)/fwd_bwd/jvp(N)/layers_1/mixer/router/top_k": "router",
    "jit(round)/fwd_bwd/jvp(N)/layers_1/mixer/dispatch/sort": "dispatch",
    "jit(round)/fwd_bwd/jvp(N)/layers_1/mixer/experts/gmm": "experts",
    "jit(round)/fwd_bwd/jvp(N)/layers_1/mixer/combine/dot": "combine",
    "jit(round)/fwd_bwd/jvp(N)/layers_1/shared/shared_expert/up/dot":
        "shared_expert",
    "jit(round)/fwd_bwd/jvp(N)/layers_5/mixer/attn/q_proj/dot": "attn",
    "jit(round)/fwd_bwd/jvp(N)/layers_5/norm/mul": "norm",
    "jit(round)/fwd_bwd/jvp(N)/stem/patch_embed/dot": "stem",
    "jit(round)/local_train/update/add": "optimizer",
    "jit(eval)/eval/N/layers_0/mixer/ssd/dot": "eval",
    "jit(round)/aggregate/add": "aggregate",
}


@pytest.mark.parametrize("path", sorted(PATHS))
def test_trunk_partition_classifies_by_the_programs_scopes(path):
    rules = scopes.load_rules(nemotronh_scopes.RULES)
    table = scopes.build({"%op = f32[] x()": 1.0},
                         {"%op = f32[] x()": {"tf_op": path}}, rules=rules)
    assert table["share_pct"]["trunk"] == {PATHS[path]: 100.0}


def _ctx_with_table(seconds_by_class, **more):
    busy = sum(seconds_by_class.values())
    table = {"busy_s": busy, "share_pct": {"trunk": {
        c: 100.0 * s / busy for c, s in seconds_by_class.items()}}}
    return {nemotronh_scopes.KEY: table, "peak": PEAK, "chips": 1, **more}


def test_shares_sum_their_classes():
    ctx = _ctx_with_table({"ssd": 2.0, "ssm_in_proj": 1.0, "ssm_conv": 0.5,
                           "ssm_gate_norm": 0.25, "ssm_out_proj": 0.25,
                           "experts": 1.0, "router": 0.5, "dispatch": 1.5,
                           "combine": 0.5, "shared_expert": 0.5,
                           "attn": 1.0, "optimizer": 1.0})
    read = lambda name: nemotronh_scopes.share_pct(_spec(name), ctx)
    assert read("ssm_time_share_pct") == pytest.approx(40.0)
    assert read("ssd_time_share_pct") == pytest.approx(20.0)
    assert read("held_moe_time_share_pct") == pytest.approx(40.0)
    assert read("held_dispatch_time_share_pct") == pytest.approx(20.0)
    assert read("gqa_time_share_pct") == pytest.approx(10.0)


def test_ssd_roofline_is_the_bytes_side():
    """One round of 384 samples: 24 steps of the four M layers' scans.
    Bytes 24 x 2.548 GB = 61.2 GB -> 74.7 ms at 819 GB/s; operations 3 x
    7.06 GFLOP x 384 = 8.13 TFLOP -> 41.3 ms. A scope that took 0.747 s
    is at 10% of the (bytes) roofline."""
    ctx = _ctx_with_table({"ssd": 0.7466, "attn": 1.0},
                          trace={"real_samples": 384, "rounds": 1})
    got = nemotronh_scopes.ssd_roofline_pct(_spec("ssd_roofline_pct"), ctx)
    assert got == pytest.approx(10.0, rel=2e-3)


def test_held_expert_roofline_counts_the_rows_that_landed(tracer):
    """Two traced rounds of 24 steps, 4 expert layers, the uniform 3,840
    rows a step and layer: 737,280 rows. Operations 3 x 19,955,712 x
    737,280 = 44.1 TFLOP -> 224 ms; bytes 192 x 0.6883 GB = 132 GB ->
    161 ms: the FLOP side binds. Rounds before the slice are not
    counted."""
    for r, rows in enumerate((999_999, 368_640, 368_640)):
        _span(tracer, "dispatch_program", 10 * r, 10 * r + 1, steps_real=24)
        _span(tracer, "round_log", 10 * r + 8, 10 * r + 9, rows_held=rows,
              tokens_routed=5_898_240)
    ctx = _ctx_with_table({"experts": 2.24, "attn": 1.0},
                          trace={"real_samples": 768, "rounds": 2})
    got = nemotronh_scopes.held_expert_matmul_roofline_pct(
        _spec("held_expert_matmul_roofline_pct"), ctx)
    assert got == pytest.approx(10.0, rel=5e-3)
    # a tenth of the rows (384 a step and layer): the held weights' bytes
    # bind: 192 step-layers x 3 passes x (2 x 8 x 2688 x 1856 x 2 B of
    # weights + 384 rows x 2 x (2688 + 1856) x 2 B) = 96 GB -> 117 ms,
    # against 22 ms of operations
    tracer.disarm(), tracer.arm()
    for r in range(2):
        _span(tracer, "dispatch_program", 10 * r, 10 * r + 1, steps_real=24)
        _span(tracer, "round_log", 10 * r + 8, 10 * r + 9, rows_held=36_864)
    few = nemotronh_scopes.held_expert_matmul_roofline_pct(
        _spec("held_expert_matmul_roofline_pct"), ctx)
    nbytes = 192 * 3 * (2 * 8 * 2688 * 1856 * 2 + 384 * 2 * 4544 * 2)
    assert few == pytest.approx(100.0 * nbytes / 819e9 / 2.24, rel=1e-6)


def test_held_rows_share_reads_the_rounds_counters(tracer):
    for r, rows in enumerate((100, 368_640, 400_000)):
        _span(tracer, "round_log", 10.4 + 10 * r, 10.6 + 10 * r,
              rows_held=rows, tokens_routed=5_898_240, round=r)
    ctx = {"window": _window(tracer, 10.5, 20.5)}
    assert nemotronh_scopes.held_rows_share_pct({}, ctx) == \
        pytest.approx(6.25)


@pytest.mark.parametrize("name, arg", zip(LOAD_METRICS, (
    "held_load_max_over_mean", "expert_load_max_over_mean")))
def test_load_metrics_read_the_rounds_own_argument(tracer, name, arg):
    """The median over the rounds whose ``round_log`` starts inside the
    window, of the one argument the metric's file names."""
    import importlib

    for r, load in enumerate((9.0, 1.25, 1.75, 9.0)):
        _span(tracer, "round_log", 10.4 + 10 * r, 10.6 + 10 * r,
              **{arg: load, "round": r})
    read = importlib.import_module("benchmark.metrics." + name).read
    spec = _spec(name)
    assert spec["arg"] == arg
    assert read(spec, {"window": _window(tracer, 15.0, 35.0)}) == \
        pytest.approx(1.5)


@pytest.mark.parametrize("name", NEW_METRICS)
def test_a_program_without_the_scopes_or_counters_reads_none(tracer, name):
    """The parent of PR 29, a CNN, OLMoE: a trace whose ops carry none of
    the trunk's classes, a ``round_log`` without ``rows_held``."""
    _span(tracer, "round_log", 10.4, 10.6, tokens_routed=100, round=0)
    _span(tracer, "dispatch_program", 1.0, 2.0, steps_real=24)
    ctx = _ctx_with_table({"optimizer": 1.0, "stem": 2.0, "experts": 0.0},
                          trace={"real_samples": 384, "rounds": 1},
                          window=_window(tracer, 0.0, 99.0))
    import importlib

    read = importlib.import_module("benchmark.metrics." + name).read
    assert read(_spec(name), ctx) is None
    # and with no trace at all
    assert read(_spec(name), {"trace": None, "peak": PEAK, "chips": 1,
                              "window": _window(tracer, 0.0, 99.0)}) is None


def test_configuration_file_holds_the_catalog_rows_config():
    """Every key of the catalog row's ``config`` is in the file under the
    same name with the same value, but the ``reduced`` ones; the published
    counts and the deployment stand beside them."""
    doc = harness.read_json(os.path.join(
        harness.BENCH, "configs", "nemotronh-abcd.json"))
    published = {
        "hidden_size": 2688, "mamba_num_heads": 64, "mamba_head_dim": 64,
        "ssm_state_size": 128, "n_groups": 8, "conv_kernel": 4,
        "chunk_size": 128, "num_attention_heads": 32,
        "num_key_value_heads": 2, "head_dim": 128,
        "moe_intermediate_size": 1856, "intermediate_size": 1856,
        "moe_shared_expert_intermediate_size": 3712,
        "num_experts_per_tok": 6, "routed_scaling_factor": 2.5,
        "n_shared_experts": 1, "norm_topk_prob": True, "expand": 2,
        "layer_norm_epsilon": 1e-5, "mlp_hidden_act": "relu2",
        "model_type": "nemotron_h", "time_step_limit": [0, None],
        "hybrid_override_pattern":
            "MEMEM*EMEMEM*EMEMEM*EMEMEM*EMEMEM*EMEMEMEM*EMEMEMEME"}
    for key, value in published.items():
        assert doc[key] == value, key
    assert doc["reduced"] == ["num_hidden_layers", "n_routed_experts",
                              "vocab_size", "cohort"]
    assert doc["num_hidden_layers"] == 9 and doc["n_routed_experts"] == 8
    assert "vocab_size" not in doc
    assert doc["published"] == {
        "num_hidden_layers": 52, "n_routed_experts": 128,
        "vocab_size": 131072, "hybrid_override_pattern_run": "MEMEM*EME"}
    assert doc["hybrid_override_pattern"][:9] == "MEMEM*EME"
    assert "16 chips share each layer" in doc["deployment"]
    assert "NOT built" in doc["deployment"]
    assert set(doc["reduced"]) == set(doc["reduced_notes"])
    catalog = "/opt/skills/guides/model-configs/architectures.jsonl"
    if os.path.exists(catalog):
        (row,) = [r for r in map(json.loads, open(catalog))
                  if r["source_url"] in doc["source"]]
        for key, value in row["config"].items():
            if key not in doc["reduced"]:
                assert doc[key] == value, key


def test_the_cell_and_its_metrics_are_in_the_index():
    bench = harness.read_json(os.path.join(harness.ROOT, "BENCHMARK.json"))
    cell = "nemotronh.fedavg_fold3"
    (w,) = [w for w in bench["workloads"] if w["name"] == cell]
    assert w["chips"] == 1 and w["config"] == "nemotronh-abcd"
    mine = {m["name"]: m for m in bench["per_layer"]
            if m.get("workloads") == [cell]}
    assert set(mine) == set(NEW_METRICS)
    for m in mine.values():
        assert m["moves"] == "train_samples_per_s"
    _, _, config, traffic = harness.load_cell(cell)
    assert harness.site_sizes_of(config, traffic) == [80, 80, 80]
    # the model name is in the registry
    from neuroimagedisttraining_tpu.models import create_model

    argv = config["argv"]
    assert create_model(argv[argv.index("--model") + 1]).returns_aux
