"""HDF5 ingestion + host-streaming data path.

The streaming feed must be an exact drop-in: a streamed FedAvg run sees
bitwise-identical inputs to the device-resident run, so its metrics are
identical (VERDICT r1 missing #2 acceptance)."""

import jax
import numpy as np
import pytest

from neuroimagedisttraining_tpu.config import (
    DataConfig, ExperimentConfig, FedConfig, OptimConfig,
)
from neuroimagedisttraining_tpu.core.trainer import LocalTrainer
from neuroimagedisttraining_tpu.data import partition as P
from neuroimagedisttraining_tpu.data.federate import federate_cohort
from neuroimagedisttraining_tpu.data.hdf5 import fetch_rows, load_abcd_hdf5
from neuroimagedisttraining_tpu.data.stream import StreamingFederation
from neuroimagedisttraining_tpu.data.synthetic import write_synthetic_hdf5
from neuroimagedisttraining_tpu.engines import create_engine
from neuroimagedisttraining_tpu.models import create_model
from neuroimagedisttraining_tpu.utils.logging import ExperimentLogger


@pytest.fixture(scope="module")
def h5_cohort(tmp_path_factory):
    path = str(tmp_path_factory.mktemp("h5") / "cohort.h5")
    data = write_synthetic_hdf5(path, num_subjects=48, shape=(12, 14, 12),
                                num_sites=4, seed=0)
    return path, data


def test_load_abcd_hdf5_lazy_and_eager(h5_cohort):
    path, data = h5_cohort
    lazy = load_abcd_hdf5(path, lazy=True)
    assert lazy["file"] is not None
    np.testing.assert_array_equal(lazy["y"], data["y"])
    np.testing.assert_array_equal(lazy["site"], data["site"])
    # X is a lazy handle, row-sliceable
    np.testing.assert_array_equal(np.asarray(lazy["X"][3]), data["X"][3])
    lazy["file"].close()
    eager = load_abcd_hdf5(path, lazy=False)
    assert isinstance(eager["X"], np.ndarray)
    np.testing.assert_array_equal(eager["X"], data["X"])


def test_load_abcd_hdf5_missing_key(tmp_path):
    import h5py

    path = str(tmp_path / "bad.h5")
    with h5py.File(path, "w") as f:
        f.create_dataset("X", data=np.zeros((2, 3, 3, 3), np.uint8))
        f.create_dataset("y", data=np.zeros(2, np.int8))
    with pytest.raises(KeyError, match="site"):
        load_abcd_hdf5(path)


def test_fetch_rows_unsorted_and_duplicate_indices(h5_cohort):
    path, data = h5_cohort
    lazy = load_abcd_hdf5(path, lazy=True)
    idx = np.array([7, 2, 2, 41, 0, 7])
    got = fetch_rows(lazy["X"], idx)
    np.testing.assert_array_equal(got, data["X"][idx])
    lazy["file"].close()


def _assert_final_metrics(a, b):
    """Final-eval parity with float32-ulp slack on the mean losses: the
    resident and streamed paths of these engines run STRUCTURALLY
    different programs (one fused round vs consensus/agg + chunked
    blocks), and buffer donation (ISSUE 4) changes XLA's in-place fusion
    layout, which can reassociate the scalar loss reductions by an ulp.
    Count-based metrics (acc/auc) must still match exactly."""
    assert set(a) == set(b)
    for k in sorted(a):
        if k == "loss":
            np.testing.assert_allclose(b[k], a[k], rtol=1e-6)
        else:
            assert a[k] == b[k], (k, a, b)


def _run_algo(algo, cohort_or_stream, streaming: bool, tmp_path, tag,
              mesh=None, val_fraction=0.0, **cfg_extra):
    cfg = ExperimentConfig(
        model="3dcnn_tiny", num_classes=1, algorithm=algo,
        data=DataConfig(dataset="synthetic", partition_method="site",
                        val_fraction=val_fraction),
        optim=OptimConfig(lr=1e-2, batch_size=4, epochs=1),
        fed=FedConfig(client_num_in_total=4, comm_round=3, frac=0.5,
                      frequency_of_the_test=1),
        log_dir=str(tmp_path), tag=tag, **cfg_extra)
    trainer = LocalTrainer(create_model(cfg.model, num_classes=1), cfg.optim,
                           num_classes=1)
    log = ExperimentLogger(str(tmp_path), "synthetic", cfg.identity(),
                           console=False)
    if streaming:
        engine = create_engine(algo, cfg, None, trainer, mesh=mesh,
                               logger=log, stream=cohort_or_stream)
    else:
        fed, _ = federate_cohort(cohort_or_stream, partition_method="site",
                                 mesh=mesh, val_fraction=val_fraction)
        engine = create_engine(algo, cfg, fed, trainer, mesh=mesh,
                               logger=log)
    return engine.train()


def _run_fedavg(cohort_or_stream, streaming: bool, tmp_path, tag):
    return _run_algo("fedavg", cohort_or_stream, streaming, tmp_path, tag)


def test_streaming_fedavg_identical_to_resident(h5_cohort, tmp_path):
    path, data = h5_cohort
    # device-resident run straight from the in-memory cohort
    res = _run_fedavg(data, streaming=False, tmp_path=tmp_path, tag="res")
    # streaming run from the HDF5 file with the same partition maps
    lazy = load_abcd_hdf5(path, lazy=True)
    train_map, test_map, _ = P.site_partition(lazy["site"], seed=42)
    stream = StreamingFederation(lazy["X"], lazy["y"], train_map, test_map)
    try:
        st = _run_fedavg(stream, streaming=True, tmp_path=tmp_path,
                         tag="st")
    finally:
        stream.close()
        lazy["file"].close()

    # identical inputs -> identical round losses and metrics
    for r_res, r_st in zip(res["history"], st["history"]):
        assert r_res["train_loss"] == r_st["train_loss"], (r_res, r_st)
        assert r_res["acc"] == r_st["acc"]
        assert r_res["auc"] == r_st["auc"]
    assert res["final_global"] == st["final_global"]
    assert res["final_personal"]["acc"] == st["final_personal"]["acc"]


def test_streaming_salientgrads_identical_to_resident(h5_cohort, tmp_path):
    """The FLAGSHIP algorithm streams: phase-1 SNIP scores accumulate over
    streamed client chunks, phase-2 masked rounds stream the sampled
    clients' shards — bitwise equal to the device-resident run
    (VERDICT r2 next-step #1 acceptance)."""
    path, data = h5_cohort
    res = _run_algo("salientgrads", data, streaming=False,
                    tmp_path=tmp_path, tag="sgres")
    lazy = load_abcd_hdf5(path, lazy=True)
    train_map, test_map, _ = P.site_partition(lazy["site"], seed=42)
    stream = StreamingFederation(lazy["X"], lazy["y"], train_map, test_map)
    try:
        st = _run_algo("salientgrads", stream, streaming=True,
                       tmp_path=tmp_path, tag="sgst")
    finally:
        stream.close()
        lazy["file"].close()

    # identical mask...
    assert st["mask_density"] == res["mask_density"]
    for a, b in zip(jax.tree.leaves(res["masks"]),
                    jax.tree.leaves(st["masks"])):
        np.testing.assert_array_equal(np.asarray(a), np.asarray(b))
    # ...identical rounds, metrics, and personal models
    for r_res, r_st in zip(res["history"], st["history"]):
        assert r_res["train_loss"] == r_st["train_loss"], (r_res, r_st)
        assert r_res["acc"] == r_st["acc"]
        assert r_res["auc"] == r_st["auc"]
        assert r_res["personal_acc"] == r_st["personal_acc"]
    assert res["final_global"] == st["final_global"]
    assert res["final_personal"] == st["final_personal"]


def _open_stream(path):
    lazy = load_abcd_hdf5(path, lazy=True)
    train_map, test_map, _ = P.site_partition(lazy["site"], seed=42)
    return lazy, StreamingFederation(lazy["X"], lazy["y"], train_map,
                                     test_map)


@pytest.mark.slow  # tier-1 870s window (PR 11, the PR 2/7 precedent): per-engine streamed==resident e2e twins ride the full suite; the fedavg/salientgrads/local siblings + the streamed machinery tests keep tier-1 coverage
def test_streaming_subavg_identical_to_resident(h5_cohort, tmp_path):
    """Sub-FedAvg streams its sampled clients' shards per round; personal
    masks stay resident. Streamed == resident bitwise."""
    path, data = h5_cohort
    res = _run_algo("subavg", data, streaming=False, tmp_path=tmp_path,
                    tag="sares")
    lazy, stream = _open_stream(path)
    try:
        st = _run_algo("subavg", stream, streaming=True, tmp_path=tmp_path,
                       tag="sast")
    finally:
        stream.close()
        lazy["file"].close()
    for r_res, r_st in zip(res["history"], st["history"]):
        assert r_res["train_loss"] == r_st["train_loss"], (r_res, r_st)
        assert r_res["personal_acc"] == r_st["personal_acc"]
    assert res["final_personal"] == st["final_personal"]
    np.testing.assert_array_equal(res["client_densities"],
                                  st["client_densities"])


@pytest.mark.slow  # tier-1 870s window (PR 11, the PR 2/7 precedent): per-engine streamed==resident e2e twins ride the full suite; the fedavg/salientgrads/local siblings + the streamed machinery tests keep tier-1 coverage
def test_streaming_dispfl_identical_to_resident(h5_cohort, tmp_path):
    """DisPFL trains every client per round, so the streamed round chunks
    local training (chunk=2 < 4 clients exercises real chunking); the
    consensus einsum runs on resident state. Streamed == resident."""
    path, data = h5_cohort
    res = _run_algo("dispfl", data, streaming=False, tmp_path=tmp_path,
                    tag="dpres")
    lazy, stream = _open_stream(path)
    try:
        st = _run_algo("dispfl", stream, streaming=True, tmp_path=tmp_path,
                       tag="dpst", stream_chunk_clients=2)
    finally:
        stream.close()
        lazy["file"].close()
    for r_res, r_st in zip(res["history"], st["history"]):
        # the scalar loss DIAGNOSTIC is reduced inside the fused resident
        # program but in a separate program when chunked — XLA may
        # reassociate that one reduce, so allow ulp-level slack there; the
        # STATE comparisons below stay exact
        np.testing.assert_allclose(r_st["train_loss"], r_res["train_loss"],
                                   rtol=1e-6)
        assert r_res["personal_acc"] == r_st["personal_acc"]
        assert r_res["mask_change"] == r_st["mask_change"]
    assert res["final_personal"] == st["final_personal"]
    np.testing.assert_array_equal(res["mask_dis_matrix"],
                                  st["mask_dis_matrix"])


def test_streaming_salientgrads_chunked_phase1(h5_cohort, tmp_path):
    """Phase-1 SNIP accumulation over chunk=2 < 4 clients (two chunks)
    still reproduces the resident global mask and rounds."""
    path, data = h5_cohort
    res = _run_algo("salientgrads", data, streaming=False,
                    tmp_path=tmp_path, tag="sgres2")
    lazy, stream = _open_stream(path)
    try:
        st = _run_algo("salientgrads", stream, streaming=True,
                       tmp_path=tmp_path, tag="sgst2",
                       stream_chunk_clients=2)
    finally:
        stream.close()
        lazy["file"].close()
    assert st["mask_density"] == res["mask_density"]
    for a, b in zip(jax.tree.leaves(res["masks"]),
                    jax.tree.leaves(st["masks"])):
        np.testing.assert_array_equal(np.asarray(a), np.asarray(b))
    for r_res, r_st in zip(res["history"], st["history"]):
        assert r_res["train_loss"] == r_st["train_loss"], (r_res, r_st)
    assert res["final_global"] == st["final_global"]


@pytest.mark.slow  # tier-1 870s window (PR 11, the PR 2/7 precedent): per-engine streamed==resident e2e twins ride the full suite; the fedavg/salientgrads/local siblings + the streamed machinery tests keep tier-1 coverage
def test_streaming_ditto_identical_to_resident(h5_cohort, tmp_path):
    """Ditto's two tracks only consume sampled clients' shards — the
    streamed round is shape-identical to resident, so bitwise equal."""
    path, data = h5_cohort
    res = _run_algo("ditto", data, streaming=False, tmp_path=tmp_path,
                    tag="dtres")
    lazy, stream = _open_stream(path)
    try:
        st = _run_algo("ditto", stream, streaming=True, tmp_path=tmp_path,
                       tag="dtst")
    finally:
        stream.close()
        lazy["file"].close()
    for r_res, r_st in zip(res["history"], st["history"]):
        assert r_res["train_loss"] == r_st["train_loss"], (r_res, r_st)
        assert r_res["personal_acc"] == r_st["personal_acc"]
        assert r_res["global_acc"] == r_st["global_acc"]
    assert res["final_personal"] == st["final_personal"]


def test_streaming_local_identical_to_resident(h5_cohort, tmp_path):
    """Local-only streams client chunks (chunk=2 < 4 exercises real
    chunking); per-client training is independent so state is exact."""
    path, data = h5_cohort
    res = _run_algo("local", data, streaming=False, tmp_path=tmp_path,
                    tag="lores")
    lazy, stream = _open_stream(path)
    try:
        st = _run_algo("local", stream, streaming=True, tmp_path=tmp_path,
                       tag="lost", stream_chunk_clients=2)
    finally:
        stream.close()
        lazy["file"].close()
    for r_res, r_st in zip(res["history"], st["history"]):
        np.testing.assert_allclose(r_st["train_loss"], r_res["train_loss"],
                                   rtol=1e-6)  # chunked scalar reduce
        assert r_res["acc"] == r_st["acc"]
    assert res["final_personal"] == st["final_personal"]


@pytest.mark.slow  # tier-1 870s window (PR 11, the PR 2/7 precedent): per-engine streamed==resident e2e twins ride the full suite; the fedavg/salientgrads/local siblings + the streamed machinery tests keep tier-1 coverage
def test_streaming_dpsgd_identical_to_resident(h5_cohort, tmp_path):
    """D-PSGD: state-only gossip consensus + chunked local training."""
    path, data = h5_cohort
    res = _run_algo("dpsgd", data, streaming=False, tmp_path=tmp_path,
                    tag="dgres")
    lazy, stream = _open_stream(path)
    try:
        st = _run_algo("dpsgd", stream, streaming=True, tmp_path=tmp_path,
                       tag="dgst", stream_chunk_clients=2)
    finally:
        stream.close()
        lazy["file"].close()
    for r_res, r_st in zip(res["history"], st["history"]):
        np.testing.assert_allclose(r_st["train_loss"], r_res["train_loss"],
                                   rtol=1e-6)
        assert r_res["personal_acc"] == r_st["personal_acc"]
        assert r_res["global_acc"] == r_st["global_acc"]
    _assert_final_metrics(res["final_global"], st["final_global"])


@pytest.mark.slow  # tier-1 870s window (PR 11, the PR 2/7 precedent): per-engine streamed==resident e2e twins ride the full suite; the fedavg/salientgrads/local siblings + the streamed machinery tests keep tier-1 coverage
def test_streaming_turboaggregate_identical_to_resident(h5_cohort,
                                                        tmp_path):
    """TurboAggregate inherits FedAvg's streamed loop; the MPC stage is
    host-side and rng-independent either way — bitwise equal."""
    path, data = h5_cohort
    res = _run_algo("turboaggregate", data, streaming=False,
                    tmp_path=tmp_path, tag="tares")
    lazy, stream = _open_stream(path)
    try:
        st = _run_algo("turboaggregate", stream, streaming=True,
                       tmp_path=tmp_path, tag="tast")
    finally:
        stream.close()
        lazy["file"].close()
    for r_res, r_st in zip(res["history"], st["history"]):
        assert r_res["train_loss"] == r_st["train_loss"], (r_res, r_st)
        assert r_res["acc"] == r_st["acc"]
    assert res["final_global"] == st["final_global"]


@pytest.mark.slow  # tier-1 870s window (PR 11, the PR 2/7 precedent): per-engine streamed==resident e2e twins ride the full suite; the fedavg/salientgrads/local siblings + the streamed machinery tests keep tier-1 coverage
def test_streaming_fedfomo_identical_to_resident(h5_cohort, tmp_path):
    """FedFomo — the last engine onto the streaming list (VERDICT r3
    next-step #5): train shards chunk through stream_map_train_chunks
    (chunk=2 < 4 exercises real chunking), the val_fraction-small val
    shards are fetched resident once, and the pair-list evaluation gathers
    from resident per-client models. Streamed == resident."""
    from neuroimagedisttraining_tpu.data.federate import carve_val_split

    path, data = h5_cohort
    res = _run_algo("fedfomo", data, streaming=False, tmp_path=tmp_path,
                    tag="ffres", val_fraction=0.25)
    lazy = load_abcd_hdf5(path, lazy=True)
    train_map, test_map, _ = P.site_partition(lazy["site"], seed=42)
    # same carve the resident federate_cohort(val_fraction=0.25) applies
    val_map, train_map = carve_val_split(train_map, 0.25, seed=42)
    stream = StreamingFederation(lazy["X"], lazy["y"], train_map, test_map,
                                 val_map=val_map)
    try:
        st = _run_algo("fedfomo", stream, streaming=True, tmp_path=tmp_path,
                       tag="ffst", val_fraction=0.25,
                       stream_chunk_clients=2)
    finally:
        stream.close()
        lazy["file"].close()
    for r_res, r_st in zip(res["history"], st["history"]):
        # chunked scalar loss reduce may reassociate (same slack as the
        # dispfl/local streamed tests); state comparisons are exact
        np.testing.assert_allclose(r_st["train_loss"], r_res["train_loss"],
                                   rtol=1e-6)
        assert r_res["personal_acc"] == r_st["personal_acc"]
    _assert_final_metrics(res["final_personal"], st["final_personal"])
    # fomo weights divide ulp-scale val-loss gaps by small parameter
    # distances, so the resident-vs-streamed codegen difference donation
    # introduces (see _assert_final_metrics) is AMPLIFIED here — the
    # matrices agree to ~1e-5 relative, not bitwise
    np.testing.assert_allclose(np.asarray(res["weights"]),
                               np.asarray(st["weights"]),
                               rtol=5e-5, atol=5e-5)
    np.testing.assert_allclose(np.asarray(res["p_choose"]),
                               np.asarray(st["p_choose"]),
                               rtol=5e-5, atol=5e-5)


def test_streaming_fedfomo_requires_val_map(h5_cohort, tmp_path):
    """A StreamingFederation built without a val split must be refused
    with a clear error (FedFomo's pair evals need val shards)."""
    path, data = h5_cohort
    lazy = load_abcd_hdf5(path, lazy=True)
    train_map, test_map, _ = P.site_partition(lazy["site"], seed=42)
    stream = StreamingFederation(lazy["X"], lazy["y"], train_map, test_map)
    try:
        with pytest.raises(ValueError, match="requires a val split"):
            _run_algo("fedfomo", stream, streaming=True,
                      tmp_path=tmp_path, tag="rej")
    finally:
        stream.close()
        lazy["file"].close()


def test_stream_transfer_stats_and_two_level_put(h5_cohort):
    """The reader thread does fetch AND device_put (VERDICT r3 weak #2):
    transfer_stats accumulates both stages, prefetched get_train returns
    already-transferred arrays, and with a two-level (silos, clients) mesh
    the round buffer shards over BOTH axes silo-major (VERDICT r3
    next-step #10)."""
    from neuroimagedisttraining_tpu.parallel.hierarchical import (
        make_two_level_mesh,
    )

    path, data = h5_cohort
    lazy = load_abcd_hdf5(path, lazy=True)
    train_map, test_map, _ = P.site_partition(lazy["site"], seed=42)
    mesh = make_two_level_mesh(2, 2)  # 4 clients over 2 silos x 2 cores
    stream = StreamingFederation(lazy["X"], lazy["y"], train_map, test_map,
                                 mesh=mesh)
    try:
        stream.prefetch_train(np.arange(4))
        Xs, ys, ns = stream.get_train(np.arange(4))
        assert stream.transfer_stats["fetches"] == 1
        assert stream.transfer_stats["host_gather_ms"] > 0
        assert stream.transfer_stats["device_put_ms"] > 0
        assert stream.transfer_stats["bytes"] == (
            np.asarray(Xs).nbytes + np.asarray(ys).nbytes
            + np.asarray(ns).nbytes)
        # obs gauge parity (ISSUE 10 satellite): every registry series
        # equals the legacy dict entry, no double counting
        from neuroimagedisttraining_tpu.obs import metrics as obs_metrics

        snap = obs_metrics.snapshot()["nidt_stream_transfer"]["values"]
        got = {v["labels"]["key"]: v["value"] for v in snap}
        for k, v in stream.transfer_stats.items():
            assert got[k] == float(v), (k, got[k], v)
        # sharded over all 4 mesh devices, one client per device,
        # silo-major placement = mesh device order
        assert len(Xs.sharding.device_set) == 4
        assert not Xs.sharding.is_fully_replicated
        assert {s.data.shape[0] for s in Xs.addressable_shards} == {1}
        mesh_order = [d.id for d in mesh.devices.reshape(-1)]
        shard_dev = sorted((s.index[0].start, s.device.id)
                           for s in Xs.addressable_shards)
        assert [d for _, d in shard_dev] == mesh_order
        # the silo-first two-level reduction accepts this layout directly
        from neuroimagedisttraining_tpu.parallel.hierarchical import (
            silo_then_global_mean,
        )
        from neuroimagedisttraining_tpu.utils.pytree import (
            tree_weighted_mean,
        )

        w = ns.astype(np.float32)
        got = silo_then_global_mean({"x": Xs.astype(np.float32)}, w, mesh)
        want = tree_weighted_mean({"x": Xs.astype(np.float32)}, w)
        np.testing.assert_allclose(np.asarray(got["x"]),
                                   np.asarray(want["x"]), rtol=1e-6)
    finally:
        stream.close()
        lazy["file"].close()


def test_streaming_checkpoint_resume(h5_cohort, tmp_path):
    """Checkpoint/resume also works in streaming mode: kill back to the
    round-0 checkpoint, resume, final metrics equal the uninterrupted run."""
    import os

    from neuroimagedisttraining_tpu.utils import checkpoint as ckpt

    path, data = h5_cohort
    ck = str(tmp_path / "ck")

    def run():
        lazy = load_abcd_hdf5(path, lazy=True)
        train_map, test_map, _ = P.site_partition(lazy["site"], seed=42)
        stream = StreamingFederation(lazy["X"], lazy["y"], train_map,
                                     test_map)
        cfg = ExperimentConfig(
            model="3dcnn_tiny", num_classes=1, algorithm="fedavg",
            data=DataConfig(dataset="synthetic", partition_method="site"),
            optim=OptimConfig(lr=1e-2, batch_size=4, epochs=1),
            fed=FedConfig(client_num_in_total=4, comm_round=2,
                          frequency_of_the_test=1),
            checkpoint_dir=ck, checkpoint_every=1,
            log_dir=str(tmp_path), tag="stck")
        trainer = LocalTrainer(create_model(cfg.model, num_classes=1),
                               cfg.optim, num_classes=1)
        log = ExperimentLogger(str(tmp_path), "synthetic", cfg.identity(),
                               console=False)
        engine = create_engine("fedavg", cfg, None, trainer, mesh=None,
                               logger=log, stream=stream)
        try:
            return engine.train()
        finally:
            stream.close()
            lazy["file"].close()

    full = run()
    assert ckpt.list_checkpoints(ck) == [0, 1]
    os.unlink(os.path.join(ck, "ckpt_00000001.msgpack"))  # kill after r0
    resumed = run()
    assert resumed["final_global"] == full["final_global"]
    assert len(resumed["history"]) == 2


def test_streaming_sharded_over_client_mesh(h5_cohort, tmp_path):
    """Sharded streaming: the round's host-fetched buffers are device_put
    SHARDED over a 1-D client mesh (the full-scale deployment path:
    host-stream a > HBM cohort INTO a multi-chip federation). Metrics
    match the unsharded streamed run; cross-device reduction may
    reassociate, so the comparison is allclose not bitwise."""
    from neuroimagedisttraining_tpu.parallel.mesh import make_mesh

    path, data = h5_cohort
    lazy, stream_plain = _open_stream(path)
    try:
        st = _run_algo("fedavg", stream_plain, streaming=True,
                       tmp_path=tmp_path, tag="shpl")
    finally:
        stream_plain.close()
        lazy["file"].close()

    mesh = make_mesh(shape=(2,))  # frac 0.5 of 4 clients = 2 sampled: tiles
    lazy2 = load_abcd_hdf5(path, lazy=True)
    train_map, test_map, _ = P.site_partition(lazy2["site"], seed=42)
    stream_sh = StreamingFederation(lazy2["X"], lazy2["y"], train_map,
                                    test_map, mesh=mesh)
    try:
        # the feed really shards: one round's buffer spans both devices
        Xs, _, _ = stream_sh.get_train(np.array([0, 1]))
        assert len(Xs.sharding.device_set) == 2
        st_sh = _run_algo("fedavg", stream_sh, streaming=True,
                          tmp_path=tmp_path, tag="shme", mesh=mesh)
    finally:
        stream_sh.close()
        lazy2["file"].close()

    for r_a, r_b in zip(st["history"], st_sh["history"]):
        np.testing.assert_allclose(r_b["train_loss"], r_a["train_loss"],
                                   rtol=2e-5)
        np.testing.assert_allclose(r_b["acc"], r_a["acc"], atol=1e-6)
    np.testing.assert_allclose(st_sh["final_global"]["loss"],
                               st["final_global"]["loss"], rtol=2e-5)


def test_streaming_salientgrads_checkpoint_resume(h5_cohort, tmp_path):
    """Flagship streaming + checkpoint/resume: kill back to the round-0
    checkpoint, resume (phase-1 masks restored, NOT recomputed), final
    metrics equal the uninterrupted run."""
    import os

    from neuroimagedisttraining_tpu.utils import checkpoint as ckpt

    path, data = h5_cohort
    ck = str(tmp_path / "sgck")

    def run():
        lazy, stream = _open_stream(path)
        try:
            return _run_algo("salientgrads", stream, streaming=True,
                             tmp_path=tmp_path, tag="sgck",
                             checkpoint_dir=ck, checkpoint_every=1)
        finally:
            stream.close()
            lazy["file"].close()

    full = run()
    assert ckpt.list_checkpoints(ck) == [0, 1, 2]
    os.unlink(os.path.join(ck, "ckpt_00000002.msgpack"))
    os.unlink(os.path.join(ck, "ckpt_00000001.msgpack"))  # kill after r0
    resumed = run()
    assert resumed["final_global"] == full["final_global"]
    assert resumed["final_personal"] == full["final_personal"]
    assert resumed["mask_density"] == full["mask_density"]


def test_streaming_fedavg_prefetch_one_train_fetch_a_round(h5_cohort,
                                                           tmp_path):
    """The one round loop's feed: over the streamed run every round's
    train shards are fetched exactly ONCE, on the reader thread (the
    prefetch queued behind the previous round was the one served: a
    mismatched key would fetch again, on the driver's thread), and the
    streamed run's final model is the resident run's, bitwise."""
    import threading

    path, data = h5_cohort
    res = _run_fedavg(data, streaming=False, tmp_path=tmp_path, tag="pres")
    lazy = load_abcd_hdf5(path, lazy=True)
    train_map, test_map, _ = P.site_partition(lazy["site"], seed=42)
    stream = StreamingFederation(lazy["X"], lazy["y"], train_map, test_map)
    calls = []
    inner = stream._fetch_put

    def spy(client_ids, split, n_real=None):
        calls.append((split, len(client_ids), threading.current_thread()
                      is threading.main_thread()))
        return inner(client_ids, split, n_real)

    stream._fetch_put = spy
    try:
        st = _run_fedavg(stream, streaming=True, tmp_path=tmp_path,
                         tag="pst")
        stream.sync()
        assert stream.transfer_stats["fetches"] == len(calls)
    finally:
        stream.close()
        lazy["file"].close()
    # a round samples 2 of the 4 clients (frac 0.5); the final pass
    # streams the cohort in chunks of its own size
    rounds = len(res["history"])
    per_round = [on_main for split, n, on_main in calls
                 if split == "train" and n == 2]
    assert per_round == [False] * rounds == [False] * len(st["history"])
    for a, b in zip(jax.tree.leaves(res["params"]),
                    jax.tree.leaves(st["params"])):
        np.testing.assert_array_equal(np.asarray(a), np.asarray(b))


def test_streaming_double_buffer_prefetch(h5_cohort):
    path, data = h5_cohort
    lazy = load_abcd_hdf5(path, lazy=True)
    train_map, test_map, _ = P.site_partition(lazy["site"], seed=42)
    stream = StreamingFederation(lazy["X"], lazy["y"], train_map, test_map)
    try:
        stream.prefetch_train(np.array([0, 2]))
        X1, y1, n1 = stream.get_train(np.array([0, 2]))     # hits prefetch
        X2, y2, n2 = stream.get_train(np.array([0, 2]))     # cold read
        np.testing.assert_array_equal(np.asarray(X1), np.asarray(X2))
        np.testing.assert_array_equal(np.asarray(n1), np.asarray(n2))
        # mismatched prefetch is ignored, not served stale
        stream.prefetch_train(np.array([1]))
        X3, _, n3 = stream.get_train(np.array([3]))
        assert int(np.asarray(n3)[0]) == len(train_map[3])
    finally:
        stream.close()
        lazy["file"].close()
