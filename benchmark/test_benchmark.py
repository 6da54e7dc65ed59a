"""Tests of the yardstick itself. CPU, seconds: ``python -m pytest benchmark/``.

Not part of ``tests/`` (the program's suite): these guard the benchmark's
own arithmetic, so that a number it prints means what PERF.md says.
"""

import json
import math
import os
import re

import numpy as np
import pytest

from benchmark import cohort, flops, harness, readers, trace_reduce

BENCH = os.path.dirname(os.path.abspath(__file__))
NAME = re.compile(r"^[A-Za-z0-9_][A-Za-z0-9_.\-]{0,63}$")
SHAPE = (121, 145, 121)
MS = 1e-3


@pytest.fixture(scope="module")
def bench():
    return harness.read_json(os.path.join(harness.ROOT, "BENCHMARK.json"))


# ---------- trace reduction, against values worked out by hand ----------

@pytest.fixture(scope="module")
def reduced():
    trace = trace_reduce.load_json(os.path.join(BENCH, "testdata",
                                                "hand_trace.json"))
    return trace_reduce.reduce(trace, "bench:slice", harness.GAP_SPANS)


def test_busy_is_the_union_of_op_intervals(reduced):
    # window = the bench:slice span, 1..16 ms. Device 0: fusion.0 clipped
    # to 0.2, while 4.0 + all-gather 0.5, eval 1.0, round again 4.5 = 10.2.
    # Device 1: (3.0 + 1.5) x 2 = 9.0. Nested ops are not counted twice.
    assert reduced["window_s"] == pytest.approx(15 * MS)
    per = reduced["per_device"]
    assert per["/device:TPU:0"]["busy_s"] == pytest.approx(10.2 * MS)
    assert per["/device:TPU:1"]["busy_s"] == pytest.approx(9.0 * MS)
    assert reduced["busy_s"] == pytest.approx(9.6 * MS)
    assert reduced["idle_share"] == pytest.approx(1 - 9.6 / 15)
    assert per["/device:TPU:0"]["idle_share"] == pytest.approx(1 - 10.2 / 15)


def test_self_time_by_op(reduced):
    # a while loop's own time is its duration less its body's ops;
    # seconds are averaged over the two devices and sum to mean busy
    ops = reduced["ops_s"]
    assert ops["convolution.2"] == pytest.approx(4.0 * MS)
    assert ops["while.1"] == pytest.approx(2.0 * MS)
    assert ops["fusion.3"] == pytest.approx(1.0 * MS)
    assert ops["all-gather.4"] == pytest.approx(2.0 * MS)
    assert ops["fusion.5"] == pytest.approx(0.5 * MS)
    assert ops["fusion.0"] == pytest.approx(0.1 * MS)
    assert sum(ops.values()) == pytest.approx(reduced["busy_s"])


def test_op_shares(reduced):
    assert trace_reduce.op_share(reduced, "^convolution") == \
        pytest.approx(8.0 / 19.2)
    # worst chip: device 1 spends 3.0 of its 9.0 busy ms in all-gather
    assert trace_reduce.op_share(reduced, "all-gather", worst_chip=True) \
        == pytest.approx(3.0 / 9.0)


def test_gaps_are_named_by_the_shortest_covering_span(reduced):
    # device 0: 1.2-1.5 nothing; 6-8 eval_global (shorter than feed_wait);
    # 9-11 dispatch_program (1.2 of 2.0 covered); 15.5-16 nothing.
    # device 1: 1-1.5 nothing; 6-11 feed_wait (4.4 of 5.0); 15.5-16
    # nothing. The runtime thread's event is not a candidate (prefixes).
    gaps = reduced["idle_gaps_s"]
    assert gaps["bench:eval_global"] == pytest.approx(1.0 * MS)
    assert gaps["dispatch_program"] == pytest.approx(1.0 * MS)
    assert gaps["bench:feed_wait"] == pytest.approx(2.5 * MS)
    assert gaps["unspanned"] == pytest.approx(0.9 * MS)
    assert sum(gaps.values()) == pytest.approx(
        reduced["window_s"] - reduced["busy_s"])


def test_idle_between_round_programs(reduced):
    # device 0: 6 -> 11 ms less the eval op's 1 ms = 4; device 1: 5
    assert reduced["between_main_idle_ms"] == pytest.approx(4.5)
    assert reduced["per_device"]["/device:TPU:0"]["main_module"] == \
        "jit_round_fn"


def test_breakdown_and_readers(reduced):
    b = trace_reduce.breakdown(reduced)
    assert b["device_ops"][0][0] == "convolution.2"
    assert b["idle_gaps"][0][0] == "bench:feed_wait"
    assert len(b["device_ops"]) <= 10 and len(b["idle_gaps"]) <= 10
    ctx = {"trace": dict(reduced, rounds=2, real_samples=100),
           "chips": 2, "peak": {"bf16_flops_per_s": 1e12},
           "flops_per_sample": 1e9, "samples_per_s": 500.0}
    assert readers.read({"kind": "trace_imbalance_pct"}, ctx) == \
        pytest.approx(100 * (1 - 9.0 / 10.2))
    assert readers.read({"kind": "trace_value", "key": "busy_s",
                         "scale": 1000.0, "per_round": True}, ctx) == \
        pytest.approx(4.8)
    # 100 samples x 1e9 FLOPs in 9.6 ms of busy time on 2 chips of 1e12
    assert readers.read({"kind": "flops_util_pct", "over": "busy"}, ctx) \
        == pytest.approx(100 * 100e9 / 9.6e-3 / 2e12)
    assert readers.read({"kind": "flops_util_pct", "over": "wall"}, ctx) \
        == pytest.approx(100 * 500e9 / 2e12)
    assert readers.read({"kind": "trace_value", "key": "busy_s"},
                        dict(ctx, trace=None)) is None


def test_short_op_labels():
    text = ("%multiply_reduce_fusion.102 = (f32[4]{0:T(128)S(1)}, "
            "bf16[5,5,5,1,4,64]{5,3,4,2,1,0:T(2,128)(2,1)S(1)}) "
            "fusion(bf16[4,16,121,145,121,1]{1,5,0,4,3,2:T(2,128)(2,1)} "
            "%copy.789), kind=kOutput, calls=%fused_computation.234")
    assert trace_reduce.short_op(text) == \
        "%multiply_reduce_fusion.102 fusion:kOutput -> f32[4]"
    assert trace_reduce.short_op(
        '%closed_call.3 = f32[4,8,128]{2,1,0} custom-call(f32[4,8,128]{2,1,0}'
        ' %x), custom_call_target="tpu_custom_call"') == \
        "%closed_call.3 custom-call:tpu_custom_call -> f32[4,8,128]"
    assert trace_reduce.short_op("fusion.5") == "fusion.5"


def test_module_seconds(reduced):
    # the eval program runs 1 ms on device 0 only: 0.5 ms a device
    assert reduced["modules_s"]["jit_eval_all"] == pytest.approx(0.5 * MS)
    assert reduced["modules_s"]["jit_round_fn"] == pytest.approx(9.0 * MS)
    ctx = {"trace": reduced}
    assert readers.read({"kind": "trace_module_share_pct",
                         "pattern": "eval"}, ctx) == \
        pytest.approx(100 * 0.5 / 9.6)


def test_a_trace_without_device_ops_is_refused():
    with pytest.raises(trace_reduce.NoDeviceOps):
        trace_reduce.reduce({"devices": {}, "host": [["x", 0, 10]]})


def test_recorded_xplane_loads():
    """A small trace recorded on the v5e (PR 22): the loader finds the
    device plane, its ops line and the harness's host annotation."""
    path = os.path.join(BENCH, "testdata", "tiny_v5e.xplane.pb")
    trace = trace_reduce.load_xplane(path)
    assert list(trace["devices"]) == ["/device:TPU:0"]
    assert trace["devices"]["/device:TPU:0"]["ops"]
    assert trace["devices"]["/device:TPU:0"]["modules"]
    r = trace_reduce.reduce(trace, "bench:slice", harness.GAP_SPANS)
    assert 0 < r["busy_s"] < r["window_s"]
    assert "bench:step" in r["host_spans_s"]


# ---------- FLOPs: the copy starts equal to the program's ----------

@pytest.mark.parametrize("config, model, gflop", [
    ("alexnet3d-abcd", "3DCNN", 22.36), ("resnet3d-abcd", "resnet3d", 84.66)])
def test_flops_agree_with_the_program(config, model, gflop):
    import jax
    import jax.numpy as jnp

    from neuroimagedisttraining_tpu.models import create_model
    from neuroimagedisttraining_tpu.ops import flops as program_flops

    cfg = harness.read_json(os.path.join(BENCH, "configs", config + ".json"))
    net = create_model(model, num_classes=1, remat=False)
    x = jax.ShapeDtypeStruct((1,) + SHAPE + (1,), jnp.float32)
    v = jax.eval_shape(lambda: net.init(
        {"params": jax.random.key(0), "dropout": jax.random.key(1)},
        jnp.zeros(x.shape), train=False))
    tape = flops.record_tape(harness.load_reference(cfg).forward,
                             v["params"], v["batch_stats"], SHAPE)
    mine = flops.training_flops_per_sample(tape)
    theirs = program_flops.count_training_flops_per_sample(
        net, v["params"], x, batch_stats=v["batch_stats"])
    assert mine == theirs
    assert mine / 1e9 == pytest.approx(gflop, abs=0.005)


@pytest.mark.parametrize("config, model", [
    ("alexnet3d-abcd", "3DCNN"), ("resnet3d-abcd", "resnet3d")])
def test_reference_forward_matches_the_model_in_float32(config, model):
    """At a small volume on the CPU, where float32 is float32: the plain
    reference and the program's flax model give the same logits."""
    import jax
    import jax.numpy as jnp

    from neuroimagedisttraining_tpu.models import create_model, primary_logits

    cfg = harness.read_json(os.path.join(BENCH, "configs", config + ".json"))
    small = (69, 77, 69)
    net = create_model(model, num_classes=1, remat=False)
    v = net.init({"params": jax.random.key(0), "dropout": jax.random.key(1)},
                 jnp.zeros((1,) + small + (1,)), train=False)
    stats = jax.tree.map(lambda a: a + 0.3 * jax.random.uniform(
        jax.random.key(5), a.shape), v["batch_stats"])
    x = jax.random.randint(jax.random.key(2), (2,) + small, 0, 255) \
        .astype(jnp.uint8)
    want = primary_logits(net.apply(
        {"params": v["params"], "batch_stats": stats},
        x.astype(jnp.float32)[..., None], train=False))
    got = harness.load_reference(cfg).forward(v["params"], stats, x)
    np.testing.assert_allclose(np.asarray(got), np.asarray(want), rtol=1e-4)


# ---------- cohort ----------

def test_cohort_is_a_function_of_the_seed(tmp_path):
    sizes, shape = [7, 5], (6, 7, 6)
    a = [x.copy() for x, _ in cohort.generate(sizes, shape, 3)]
    b = [x.copy() for x, _ in cohort.generate(sizes, shape, 3)]
    c = [x.copy() for x, _ in cohort.generate(sizes, shape, 4)]
    assert all(np.array_equal(p, q) for p, q in zip(a, b))
    assert not all(np.array_equal(p, q) for p, q in zip(a, c))
    path, written = cohort.ensure_cohort(str(tmp_path), "t", sizes, shape, 3)
    assert written
    assert cohort.ensure_cohort(str(tmp_path), "t", sizes, shape, 3) == \
        (path, False)
    other, _ = cohort.ensure_cohort(str(tmp_path), "t", sizes, shape, 4)
    assert os.listdir(tmp_path) == [os.path.basename(other)]  # one kept
    import h5py

    with h5py.File(other) as f:
        assert f["X"].shape == (12,) + shape and f["X"].dtype == np.uint8
        assert np.bincount(np.asarray(f["site"])).tolist() == sizes
        assert sorted(np.bincount(np.asarray(f["y"])[:7]).tolist()) == [3, 4]


def test_split_counts_are_the_programs_site_partition():
    from neuroimagedisttraining_tpu.data.partition import site_partition

    sizes = harness.read_json(os.path.join(
        BENCH, "traffic", "sites8_mesh4.json"))["site_sizes"]
    train_map, test_map, _ = site_partition(cohort.site_labels(sizes))
    train, test = cohort.split_counts(sizes)
    assert [len(train_map[c]) for c in range(len(sizes))] == train
    assert [len(test_map[c]) for c in range(len(sizes))] == test
    # 49 real steps an epoch of 8 client rows x 12 (the largest site's)
    steps = sum(math.ceil(n / 16) for n in train)
    assert (steps, math.ceil(max(train) / 16)) == (49, 12)
    assert 100 * (1 - steps / (8 * 12)) == pytest.approx(48.96, abs=0.005)


# ---------- BENCHMARK.json and the files it names ----------

def test_every_cell_and_metric_has_its_files(bench):
    configs = {c["name"]: c for c in bench["configs"]}
    for c in configs.values():
        doc = harness.read_json(os.path.join(harness.ROOT, c["file"]))
        assert doc["name"] == c["name"] and doc["reduced"] == c["reduced"]
        assert os.path.exists(os.path.join(BENCH, "reference",
                                           doc["reference"]))
    for w in bench["workloads"]:
        assert NAME.match(w["name"]) and len(w["why"]) <= 200
        traffic = harness.read_json(os.path.join(
            BENCH, "traffic", w["traffic"] + ".json"))
        assert traffic["chips"] == w["chips"] and w["config"] in configs
    e2e = {m["name"] for m in bench["end_to_end"]}
    cells = {w["name"] for w in bench["workloads"]}
    for m in bench["per_layer"]:
        spec = harness.read_json(os.path.join(BENCH, "metrics",
                                              m["name"] + ".json"))
        assert spec["reader"]["kind"] in set(readers.KINDS) | {"module"}
        assert m["moves"] in e2e and set(m.get("workloads", [])) <= cells
        if "pattern" in spec["reader"]:
            re.compile(spec["reader"]["pattern"])


def test_peaks_name_their_source():
    peaks = harness.read_json(os.path.join(BENCH, "peaks.json"))
    assert "TPU v5e" in peaks["_source"]
    assert peaks["TPU v5 lite"]["bf16_flops_per_s"] == 197e12
    assert peaks["TPU v5 lite"]["hbm_bytes_per_s"] == 819e9
