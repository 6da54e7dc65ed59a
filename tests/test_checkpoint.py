"""Checkpoint/resume: round-granular save/restore with bitwise-identical
replay (SURVEY §5.4 rebuild requirement — the reference lost 3-day runs at
the SLURM time limit)."""

import jax
import jax.numpy as jnp
import numpy as np

from neuroimagedisttraining_tpu.utils import checkpoint as ckpt
import pytest


def test_roundtrip_arrays_and_keys(tmp_path):
    state = {
        "params": {"w": jnp.arange(6, dtype=jnp.float32).reshape(2, 3)},
        "rng": jax.random.key(42),
        "history": [{"round": 0, "loss": 0.5}],
        "round_float": 3.25,
    }
    ckpt.save_checkpoint(str(tmp_path), 7, state)
    r, got = ckpt.load_checkpoint(str(tmp_path))
    assert r == 7
    np.testing.assert_array_equal(np.asarray(got["params"]["w"]),
                                  np.asarray(state["params"]["w"]))
    # PRNG key survives the trip and generates the same stream
    a = jax.random.uniform(state["rng"], (4,))
    b = jax.random.uniform(got["rng"], (4,))
    np.testing.assert_array_equal(np.asarray(a), np.asarray(b))
    assert got["history"] == [{"round": 0, "loss": 0.5}]
    assert got["round_float"] == 3.25


def test_prune_keeps_newest(tmp_path):
    for r in range(6):
        ckpt.save_checkpoint(str(tmp_path), r, {"x": jnp.zeros(1)}, keep=2)
    assert ckpt.list_checkpoints(str(tmp_path)) == [4, 5]


def test_load_missing_returns_none(tmp_path):
    assert ckpt.load_checkpoint(str(tmp_path / "nope")) is None


def _engine_with_ckpt(tmp_path, cohort, ckpt_dir, comm_round, algorithm):
    from neuroimagedisttraining_tpu.config import (
        DataConfig, ExperimentConfig, FedConfig, OptimConfig,
    )
    from neuroimagedisttraining_tpu.core.trainer import LocalTrainer
    from neuroimagedisttraining_tpu.data.federate import federate_cohort
    from neuroimagedisttraining_tpu.engines import create_engine
    from neuroimagedisttraining_tpu.models import create_model
    from neuroimagedisttraining_tpu.parallel.mesh import make_mesh
    from neuroimagedisttraining_tpu.utils.logging import ExperimentLogger

    cfg = ExperimentConfig(
        model="3dcnn_tiny", num_classes=1, algorithm=algorithm,
        data=DataConfig(dataset="synthetic", partition_method="site"),
        optim=OptimConfig(lr=5e-4, batch_size=8, epochs=1),
        fed=FedConfig(client_num_in_total=4, comm_round=comm_round),
        checkpoint_dir=ckpt_dir, checkpoint_every=2 if ckpt_dir else 0,
        log_dir=str(tmp_path),
    )
    mesh = make_mesh()
    fed, _ = federate_cohort(cohort, partition_method="site", mesh=mesh)
    model = create_model(cfg.model, num_classes=1)
    trainer = LocalTrainer(model, cfg.optim, num_classes=1)
    log = ExperimentLogger(str(tmp_path), "synthetic", cfg.identity(),
                           console=False)
    return create_engine(algorithm, cfg, fed, trainer, mesh=mesh, logger=log)


def _kill_after_round(ckpt_dir, keep_round):
    """Simulate a mid-run kill: drop every checkpoint after ``keep_round``
    so resume starts from it (schedules like DisPFL's fire-mask cosine
    anneal depend on comm_round, so the interrupted and control runs must
    share ONE comm_round — we run to completion then forget the tail)."""
    import os

    for r in ckpt.list_checkpoints(ckpt_dir):
        if r != keep_round:
            os.unlink(os.path.join(ckpt_dir, f"ckpt_{r:08d}.msgpack"))


@pytest.mark.parametrize("algorithm,keys", [
    ("fedavg", ("params", "batch_stats")),
    ("salientgrads", ("params", "batch_stats", "masks")),
    ("ditto", ("params", "personal_params")),
    ("subavg", ("params", "batch_stats", "mask_pers")),
    ("dpsgd", ("personal_params", "global_params")),
    ("local", ("personal_params", "personal_batch_stats")),
], ids=["fedavg", "salientgrads", "ditto", "subavg", "dpsgd", "local"])
def test_resume_bitwise_identical(tmp_path, synthetic_cohort, algorithm,
                                  keys):
    """Run 4 rounds checkpointed, 'kill' back to the round-1 checkpoint,
    restore into a fresh engine and finish rounds 2-3: every engine on
    the one round loop ends on BITWISE the uninterrupted run's state,
    with the same history (the restored rounds + the replayed ones)."""
    ckpt_dir = str(tmp_path / "ck")
    eng_a = _engine_with_ckpt(tmp_path, synthetic_cohort, ckpt_dir, 4,
                              algorithm)
    res_a = eng_a.train()
    assert ckpt.list_checkpoints(ckpt_dir) == [1, 3]
    _kill_after_round(ckpt_dir, 1)
    eng_b = _engine_with_ckpt(tmp_path, synthetic_cohort, ckpt_dir, 4,
                              algorithm)
    res_b = eng_b.train()
    assert len(res_b["history"]) == 4  # restored history + replayed rounds
    assert res_b["history"] == res_a["history"]
    for key in keys:
        for leaf_b, leaf_a in zip(jax.tree.leaves(res_b[key]),
                                  jax.tree.leaves(res_a[key])):
            np.testing.assert_array_equal(np.asarray(leaf_b),
                                          np.asarray(leaf_a))


@pytest.mark.slow
def test_resume_bitwise_identical_dispfl(tmp_path, synthetic_cohort):
    """Same bitwise-resume contract for the most stateful engine (personal
    params + evolving masks)."""
    ckpt_dir = str(tmp_path / "ck2")
    eng_a = _engine_with_ckpt(tmp_path, synthetic_cohort, ckpt_dir, 4,
                              "dispfl")
    res_a = eng_a.train()
    _kill_after_round(ckpt_dir, 1)
    eng_b = _engine_with_ckpt(tmp_path, synthetic_cohort, ckpt_dir, 4,
                              "dispfl")
    res_b = eng_b.train()
    for lb, la in zip(jax.tree.leaves(res_b["personal_params"]),
                      jax.tree.leaves(res_a["personal_params"])):
        np.testing.assert_array_equal(np.asarray(lb), np.asarray(la))
    for lb, la in zip(jax.tree.leaves(res_b["masks"]),
                      jax.tree.leaves(res_a["masks"])):
        np.testing.assert_array_equal(np.asarray(lb), np.asarray(la))
