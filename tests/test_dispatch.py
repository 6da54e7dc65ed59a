"""Round-driver dispatch tests (ISSUE 4): buffer donation + fused windows.

Three contracts:

(a) Donation is value-transparent: a round program with ``donate_argnums``
    produces bitwise-identical outputs to the same program without it
    (donation changes buffer residency, never math) — fedavg, the
    salientgrads flagship, and ditto's dual-track round.
(b) The fused multi-round driver (``--rounds_per_dispatch K``) is
    bitwise-identical to the sequential loop: params, batch_stats and the
    logged metrics of a K-fused run equal the K=1 run for
    fedavg/fedprox/salientgrads at K in {1, 2, 4}, including a frac < 1
    sampled config and a checkpoint-resume that lands mid-window.
(c) Engines/modes that cross the host each round fall back to one round
    per dispatch WITH a logged reason (streaming, fedfomo, the
    distributed CLI) — and still train.
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


def _engine(tmp_path, cohort, algorithm="fedavg", K=1, comm_round=4,
            freq=4, donate=True, tag="d", val_fraction=0.0, stream=False,
            checkpoint_dir="", checkpoint_every=0, **fed_kw):
    cfg = ExperimentConfig(
        model="3dcnn_tiny", num_classes=1, algorithm=algorithm,
        data=DataConfig(dataset="synthetic", partition_method="site",
                        val_fraction=val_fraction),
        optim=OptimConfig(lr=1e-3, batch_size=8, epochs=1),
        fed=FedConfig(client_num_in_total=4, comm_round=comm_round,
                      frequency_of_the_test=freq, rounds_per_dispatch=K,
                      **fed_kw),
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
# (b) K-fused scan == K sequential dispatches, bitwise
# ---------------------------------------------------------------------------

@pytest.mark.slow  # tier-1 870s window (PR 11, the PR 2/7 precedent): heavy twin rides the full suite; a lighter tier-1 sibling keeps the pin
def test_fused_driver_bitwise_equal_sequential_fedavg(tmp_path,
                                                      synthetic_cohort):
    """The full driver end to end: a K=4 fedavg run — windows planned
    around the eval cadence, hooks at boundaries — equals the K=1 run in
    params, batch_stats, metrics history, and final eval, bitwise.
    frac=0.5 keeps the per-round ``np.random.seed(round_idx)`` sampling
    contract load-bearing (different cohort each round); comm_round=4
    with eval every 4 rounds exercises a 1-round hooked window, a fused
    interior window, and the final boundary."""
    base = _engine(tmp_path, synthetic_cohort, "fedavg", K=1, frac=0.5,
                   tag="k1").train()
    fused = _engine(tmp_path, synthetic_cohort, "fedavg", K=4, frac=0.5,
                    tag="k4").train()
    _assert_trees_bitwise(base["params"], fused["params"])
    _assert_trees_bitwise(base["batch_stats"], fused["batch_stats"])
    assert base["history"] == fused["history"]
    assert base["final_global"] == fused["final_global"]


@pytest.mark.parametrize("algorithm", [
    "fedavg",
    # fedprox shares FedAvg's program shape (a prox op on top) — its
    # variant rides the full suite; tier-1 keeps the two distinct shapes
    pytest.param("fedprox", marks=pytest.mark.slow),
    pytest.param("salientgrads", marks=pytest.mark.slow),  # tier-1 window (PR 7): fedavg twin stays
])
def test_fused_program_bitwise_equal_sequential(tmp_path, synthetic_cohort,
                                                algorithm):
    """Program-level K sweep, every K in {1, 2, 4}: 4 rounds dispatched
    as four K=1 singles, two K=2 windows, and one K=4 window must yield
    bitwise-identical state and per-round losses (frac=0.5: the
    host-precomputed per-round sampling is load-bearing). Cheaper than
    full trains — the driver integration is pinned end-to-end by
    test_fused_driver_bitwise_equal_sequential_fedavg and the resume
    test below."""
    def init_state(eng):
        gs = eng.init_global_state()
        if algorithm == "salientgrads":
            masks, _ = eng.generate_global_mask(gs.params, gs.batch_stats)
            per = eng.broadcast_states(gs, eng.num_clients)
            return [gs.params, gs.batch_stats, per.params,
                    per.batch_stats], masks
        return [gs.params, gs.batch_stats], None

    # sequential reference: 4 single-round dispatches
    seq = _engine(tmp_path, synthetic_cohort, algorithm, K=1, frac=0.5,
                  tag="pseq")
    state, masks = init_state(seq)
    seq_losses = []
    for r in range(4):
        sampled = seq.client_sampling(r)
        rngs = seq.per_client_rngs(r, sampled)
        if algorithm == "salientgrads":
            out = seq._round_jit(*state[:2], *state[2:], seq.data, masks,
                                 jnp.asarray(sampled), rngs, seq.round_lr(r))
            state, loss = list(out[:4]), out[4]
        else:
            out = seq._round_jit(*state, seq.data, jnp.asarray(sampled),
                                 rngs, seq.round_lr(r))
            state, loss = list(out[:2]), out[2]
        seq_losses.append(float(loss))

    # fused: two K=2 windows, then (fresh state) one K=4 window — one
    # engine for both partitions (its jit caches persist; the state is
    # re-derived per partition because donation consumes it)
    fz = _engine(tmp_path, synthetic_cohort, algorithm, K=4, frac=0.5,
                 tag="pf")
    for windows in ([(0, 2), (2, 2)], [(0, 4)]):
        fstate, fmasks = init_state(fz)
        flosses = []
        for r0, k in windows:
            if algorithm == "salientgrads":
                (*fstate, _, loss, kk) = fz._run_fused_window(
                    *fstate, fmasks, r0, k)
            else:
                (*fstate, loss, kk) = fz._run_fused_window(*fstate, r0, k)
            assert kk == k
            flosses.append(float(loss))
        assert flosses == [seq_losses[r0 + k - 1] for r0, k in windows]
        _assert_trees_bitwise(state, list(fstate))


def test_fused_window_planner_respects_hooks(tmp_path, synthetic_cohort):
    """Window lengths: hook rounds (eval cadence, checkpoints, the final
    round) always land on a window boundary, never inside one."""
    eng = _engine(tmp_path, synthetic_cohort, K=4, comm_round=10, freq=3,
                  tag="plan")
    # eval rounds: 0, 3, 6, 9 (freq=3) + last (9)
    assert eng._dispatch_window(0) == 1        # round 0 is hooked
    assert eng._dispatch_window(1) == 3        # [1, 2, 3] — 3 hooked, ends
    assert eng._dispatch_window(4) == 3        # [4, 5, 6]
    assert eng._dispatch_window(7) == 3        # [7, 8, 9]
    ck = _engine(tmp_path, synthetic_cohort, K=4, comm_round=10,
                 freq=10 ** 9, checkpoint_dir=str(tmp_path / "ck"),
                 checkpoint_every=2, tag="plan2")
    # round 0 is ALWAYS an eval round (0 % freq == 0 — same as the
    # sequential loop); checkpoints land after rounds 1, 3, 5, ...
    assert ck._dispatch_window(0) == 1
    assert ck._dispatch_window(1) == 1         # ckpt after round 1
    assert ck._dispatch_window(2) == 2         # [2, 3] — ckpt after 3
    free = _engine(tmp_path, synthetic_cohort, K=4, comm_round=10,
                   freq=10 ** 9, tag="plan3")
    assert free._dispatch_window(1) == 4       # nothing hooked: full K


@pytest.mark.slow
def test_fused_resume_mid_window_bitwise(tmp_path, synthetic_cohort):
    """A checkpoint-resume landing mid-window (start round not aligned to
    K) must reproduce the uninterrupted sequential run bitwise: windows
    re-plan from the resume round. (Full-suite tier: tier-1 covers the
    restored-state-into-donated-round path via test_checkpoint's K=1
    resume pins and the fused driver via the tests above; this is the
    composition of the two.)"""
    full = _engine(tmp_path, synthetic_cohort, "fedavg", K=1, comm_round=4,
                   freq=10 ** 9, tag="full").train()
    ck = str(tmp_path / "ck_resume")
    # partial K=4 run: rounds 0-1, checkpoint at round 1
    _engine(tmp_path, synthetic_cohort, "fedavg", K=4, comm_round=2,
            freq=10 ** 9, checkpoint_dir=ck, checkpoint_every=2,
            tag="part").train()
    # resume at round 2 — mid-window w.r.t. a K=4 alignment from round 0
    resumed = _engine(tmp_path, synthetic_cohort, "fedavg", K=4,
                      comm_round=4, freq=10 ** 9, checkpoint_dir=ck,
                      checkpoint_every=2, tag="res").train()
    _assert_trees_bitwise(full["params"], resumed["params"])
    _assert_trees_bitwise(full["batch_stats"], resumed["batch_stats"])


# ---------------------------------------------------------------------------
# (c) fallback-to-K=1 paths log and run
# ---------------------------------------------------------------------------

def _log_text(eng) -> str:
    with open(eng.log.log_path) as f:
        return f.read()


def test_streaming_falls_back_with_logged_reason(tmp_path,
                                                 synthetic_cohort):
    """Engines WITHOUT a fused streamed window body (ISSUE 10:
    ``supports_fused_streaming`` — salientgrads here) still collapse to
    K=1 under --streaming with the logged streaming reason; the fedavg
    family now fuses streamed windows instead (pinned below)."""
    eng = _engine(tmp_path, synthetic_cohort, "salientgrads", K=4,
                  comm_round=1, freq=1, stream=True, tag="stfall")
    try:
        assert "dispatching one round at a time" in _log_text(eng)
        assert "streaming" in _log_text(eng)
        result = eng.train()
        assert np.isfinite(result["history"][-1]["train_loss"])
    finally:
        eng.stream.close()


@pytest.mark.slow  # tier-1 870s window (PR 11, the PR 2/7 precedent): heavy twin rides the full suite; a lighter tier-1 sibling keeps the pin
def test_streaming_fedavg_fused_window_bitwise(tmp_path, synthetic_cohort):
    """The fused STREAMED driver (ISSUE 10): a K=4 streamed fedavg run —
    whole-window shard stacks prefetched, one lax.scan dispatch per
    window — equals the K=1 streamed loop bitwise in params,
    batch_stats, and metrics history (frac=0.5 keeps the per-round
    sampling contract load-bearing)."""
    base = _engine(tmp_path, synthetic_cohort, "fedavg", K=1, comm_round=4,
                   freq=4, frac=0.5, stream=True, tag="swk1")
    fused = _engine(tmp_path, synthetic_cohort, "fedavg", K=4, comm_round=4,
                    freq=4, frac=0.5, stream=True, tag="swk4")
    try:
        assert fused.fused_fallback_reason() is None
        r1 = base.train()
        r4 = fused.train()
    finally:
        base.stream.close()
        fused.stream.close()
    _assert_trees_bitwise(r1["params"], r4["params"])
    _assert_trees_bitwise(r1["batch_stats"], r4["batch_stats"])
    assert r1["history"] == r4["history"]


def test_fedfomo_falls_back_with_logged_reason(tmp_path, synthetic_cohort):
    eng = _engine(tmp_path, synthetic_cohort, "fedfomo", K=4, comm_round=1,
                  freq=1, val_fraction=0.25, tag="fomofall")
    assert "dispatching one round at a time" in _log_text(eng)
    result = eng.train()
    assert np.isfinite(result["history"][-1]["train_loss"])


def test_wire_codec_falls_back_with_logged_reason(tmp_path,
                                                  synthetic_cohort):
    eng = _engine(tmp_path, synthetic_cohort, "fedavg", K=4, comm_round=1,
                  freq=1, wire_codec="delta+quant", tag="codecfall")
    assert "dispatching one round at a time" in _log_text(eng)
    assert "wire_codec" in _log_text(eng)


def test_distributed_cli_logs_dispatch_collapse(capsys):
    """The cross-silo runner accepts --rounds_per_dispatch for config
    parity and announces the per-round collapse before doing anything
    else (here the run is then stopped by an unrelated usage error, so
    no sockets are opened)."""
    from neuroimagedisttraining_tpu.distributed import run as drun

    assert drun.dispatch_fallback_note(1) is None
    note = drun.dispatch_fallback_note(3)
    assert "one round at a time" in note
    with pytest.raises(SystemExit):
        drun.main(["--role", "aggregator", "--num_clients", "1",
                   "--rounds_per_dispatch", "3"])
    assert "one round at a time" in capsys.readouterr().out


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
