"""CLI harness: flag mapping, experiment wiring, and a subprocess smoke."""

import json
import subprocess
import sys

import numpy as np

from neuroimagedisttraining_tpu.__main__ import add_args, config_from_args


def _parse(argv):
    import argparse

    return add_args(argparse.ArgumentParser()).parse_args(argv)


def test_flag_mapping_reference_names():
    args = _parse([
        "--algorithm", "salientgrads", "--model", "3DCNN",
        "--dataset", "ABCD", "--partition_method", "dir",
        "--partition_alpha", "0.3", "--batch_size", "16", "--lr", "0.01",
        "--lr_decay", "0.998", "--wd", "5e-4", "--epochs", "2",
        "--client_num_in_total", "21", "--frac", "0.5",
        "--comm_round", "200", "--dense_ratio", "0.2",
        "--itersnip_iteration", "20", "--stratified_sampling",
        "--each_prune_ratio", "0.2", "--lamda", "0.75", "--seed", "7",
        "--mpc_n_shares", "5", "--mpc_frac_bits", "20",
        "--stream_chunk_clients", "2",
    ])
    cfg = config_from_args(args)
    assert cfg.algorithm == "salientgrads"
    assert cfg.data.partition_method == "dir"
    assert cfg.optim.batch_size == 16 and cfg.optim.lr_decay == 0.998
    assert cfg.fed.client_num_in_total == 21 and cfg.fed.frac == 0.5
    assert cfg.fed.client_num_per_round == 10  # int(21 * 0.5)
    assert cfg.sparsity.dense_ratio == 0.2
    assert cfg.sparsity.itersnip_iterations == 20
    assert cfg.sparsity.stratified_sampling is True
    assert cfg.sparsity.each_prune_ratio == 0.2
    assert cfg.fed.lamda == 0.75
    assert cfg.fed.mpc_n_shares == 5 and cfg.fed.mpc_frac_bits == 20
    assert cfg.stream_chunk_clients == 2
    assert cfg.seed == 7
    assert "salientgrads" in cfg.identity() and "seed7" in cfg.identity()


def test_snip_mask_off_switch():
    # the reference's `--snip_mask type=bool` bug makes ANY string truthy
    # (main_sailentgrads.py:125); our explicit off switch must actually work
    assert config_from_args(_parse([])).sparsity.snip_mask is True
    assert config_from_args(
        _parse(["--no_snip_mask"])).sparsity.snip_mask is False


def test_cli_subprocess_end_to_end(tmp_path):
    """One shell command reproduces a FedAvg experiment (VERDICT r1 #6)."""
    out = subprocess.run(
        [sys.executable, "-m", "neuroimagedisttraining_tpu",
         "--algorithm", "fedavg", "--dataset", "synthetic",
         "--model", "3dcnn_tiny", "--synthetic_num_subjects", "32",
         "--synthetic_shape", "12", "14", "12",
         "--client_num_in_total", "4", "--comm_round", "1",
         "--batch_size", "4", "--epochs", "1", "--virtual_devices", "4",
         "--log_dir", str(tmp_path)],
        capture_output=True, text=True, timeout=420)
    assert out.returncode == 0, out.stderr[-2000:]
    result = json.loads(out.stdout.strip().splitlines()[-1])
    assert "final_global" in result and "identity" in result
    assert np.isfinite(result["final_global"]["loss"])
    # file logging under LOG/<dataset>/<identity> (main_sailentgrads.py:184)
    logs = list(tmp_path.glob("synthetic/*.log"))
    assert logs, list(tmp_path.rglob("*"))
    # stat_info persisted at end of training (reference stat pickle,
    # subavg_api.py:218-220)
    stats = list(tmp_path.glob("synthetic/*.stats.json"))
    assert stats, list(tmp_path.rglob("*"))
    blob = json.loads(stats[0].read_text())
    assert "sum_training_flops" in blob and "global_test_acc" in blob


def test_cli_unknown_dataset_errors(tmp_path):
    import pytest

    from neuroimagedisttraining_tpu.__main__ import build_experiment

    cfg = config_from_args(_parse(["--dataset", "imagenet",
                                   "--log_dir", str(tmp_path)]))
    with pytest.raises(ValueError, match="no loader"):
        build_experiment(cfg, console=False)


def test_streaming_fedfomo_requires_val_split(tmp_path):
    """All nine algorithms stream; fedfomo's remaining precondition is a
    val split (its pair-list eval keeps the val_fraction-small shards
    resident), so --streaming without --val_fraction must fail with the
    specific guard in engines/fedfomo.py, not a generic streaming error."""
    import pytest

    from neuroimagedisttraining_tpu.__main__ import build_experiment
    from neuroimagedisttraining_tpu.data.synthetic import write_synthetic_hdf5

    path = str(tmp_path / "c.h5")
    write_synthetic_hdf5(path, num_subjects=16, shape=(8, 8, 8),
                         num_sites=2, seed=0)
    cfg = config_from_args(_parse([
        "--algorithm", "fedfomo", "--dataset", "abcd_h5",
        "--data_dir", path, "--log_dir", str(tmp_path)]))
    with pytest.raises(ValueError,
                       match="streaming requires a val split"):
        build_experiment(cfg, streaming=True, console=False)


def test_two_level_mesh_composes_with_streaming(tmp_path):
    """--streaming --mesh_shape S C now COMPOSES (VERDICT r3 next-step
    #10): round buffers shard over the two-level (silos, clients) mesh
    silo-major, preserving the silo-first aggregation routing."""
    from neuroimagedisttraining_tpu.__main__ import build_experiment
    from neuroimagedisttraining_tpu.data.synthetic import (
        write_synthetic_hdf5,
    )
    from neuroimagedisttraining_tpu.parallel.hierarchical import (
        is_two_level,
    )

    path = str(tmp_path / "c.h5")
    write_synthetic_hdf5(path, num_subjects=64, shape=(12, 14, 12),
                         num_sites=8, seed=0)
    cfg = config_from_args(_parse([
        "--algorithm", "fedavg", "--dataset", "abcd_h5",
        "--data_dir", path, "--client_num_in_total", "8",
        "--mesh_shape", "2", "4", "--log_dir", str(tmp_path)]))
    from neuroimagedisttraining_tpu.parallel.mesh import make_mesh

    mesh = make_mesh(shape=(2, 4))
    engine = build_experiment(cfg, streaming=True, mesh=mesh,
                              console=False)
    try:
        assert engine.stream is not None and engine.stream.mesh is mesh
        assert is_two_level(engine.stream.mesh)
        Xs, _, _ = engine.stream.get_train(engine.client_sampling(0))
        # sharded across all 8 devices of the (2, 4) grid, one client each
        assert len(Xs.sharding.device_set) == 8
        assert {s.data.shape[0] for s in Xs.addressable_shards} == {1}
    finally:
        engine.stream.close()


def test_streaming_mesh_pads_nontiling_sample_count(tmp_path):
    """A sampled set that does not tile the mesh (the north-star shape:
    frac-sampling vs a fixed device grid) streams via stream_sampling's
    zero-weight padding instead of erroring (VERDICT r4 #2)."""
    import jax

    from neuroimagedisttraining_tpu.__main__ import build_experiment
    from neuroimagedisttraining_tpu.data.synthetic import write_synthetic_hdf5
    from neuroimagedisttraining_tpu.parallel.mesh import make_mesh

    path = str(tmp_path / "c.h5")
    write_synthetic_hdf5(path, num_subjects=32, shape=(12, 14, 12),
                         num_sites=4, seed=0)
    mesh = make_mesh(shape=(2,))
    cfg = config_from_args(_parse([
        "--algorithm", "fedavg", "--dataset", "abcd_h5",
        "--model", "3dcnn_tiny",
        "--data_dir", path, "--client_num_in_total", "4",
        "--frac", "0.75",  # 3 sampled clients, 2-device mesh: no tile
        "--comm_round", "1", "--batch_size", "4", "--epochs", "1",
        "--log_dir", str(tmp_path)]))
    engine = build_experiment(cfg, streaming=True, mesh=mesh, console=False)
    try:
        fed_ids, n_real = engine.stream_sampling(0)
        assert n_real == 3 and len(fed_ids) == 4  # padded to tile 2 devs
        Xs, ys, ns = engine.stream.get_train(fed_ids, n_real)
        assert len(Xs.sharding.device_set) == 2
        assert int(jax.device_get(ns)[-1]) == 0  # pad client weighs 0
        result = engine.train()
        assert np.isfinite(result["final_global"]["loss"])
    finally:
        engine.stream.close()


# ---------------------------------------------------------------------------
# chip_smoke.py: never a result without a TPU
# ---------------------------------------------------------------------------

import pytest  # noqa: E402


@pytest.mark.parametrize("where", ["in_checkout", "alone"])
def test_chip_smoke_fails_without_a_tpu(tmp_path, where):
    """``chip_smoke.py`` must exit non-zero and print no ``"ok": true``
    when JAX finds no accelerator (it fails at its device phase, it does
    not carry on over the CPU), and in a directory that holds the script
    and nothing else of the repo (it is the program's smoke, not a
    program of its own)."""
    import os
    import shutil

    repo = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
    script = os.path.join(repo, "chip_smoke.py")
    cwd = repo
    if where == "alone":
        script = shutil.copy(script, tmp_path / "chip_smoke.py")
        cwd = str(tmp_path)
    out = subprocess.run(
        [sys.executable, script, "--out", str(tmp_path / "out")],
        capture_output=True, text=True, timeout=300, cwd=cwd,
        env={"PATH": "/usr/bin:/bin", "JAX_PLATFORMS": "cpu"})
    assert out.returncode not in (0, 2, 3), out.returncode
    assert '"ok"' not in out.stdout
    if where == "in_checkout":
        assert "no TPU" in out.stderr
        assert not (tmp_path / "out").exists()  # failed before any work
    else:
        assert "neuroimagedisttraining_tpu" in out.stderr  # ImportError
