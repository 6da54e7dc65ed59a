"""The held runs' buffer where it is passed (PR 30), on the CPU.

PR 29 built the buffer without a way on when its rows do not fit, and
withdrew it: the trainer's evaluation pads a batch with zero volumes,
whose tokens all take the same experts, and the real rows behind them
were dropped in a program that counts nothing. Here the program computes
such a call in as many windows of the buffer as its rows need (ops/moe.py
``held_expert_rows``): a training round whose every step passes the
buffer says so and trains to the losses of the full sort, and an
evaluation batch whose filler passes it gives the real rows the
reference's logits; and what every round logs of the held rows. The
layer itself, window against full sort, is tests/test_nemotronh3d.py,
whose small size this borrows.
"""

import dataclasses

import jax
import jax.numpy as jnp
import numpy as np

from neuroimagedisttraining_tpu.config import OptimConfig
from neuroimagedisttraining_tpu.core.trainer import LocalTrainer
from neuroimagedisttraining_tpu.models.nemotronh3d import (
    PATTERN, HeldExperts, NemotronH3D,
)
from neuroimagedisttraining_tpu.ops import moe
from tests.test_nemotronh3d import (  # noqa: F401 -- ref is a fixture
    CFG, COUNT, E, F32_ATOL, F32_RTOL, SHAPE, SMALL, _batch, _state,
    _trainer, ref,
)


def _folded_train(tmp_path, tag, plant=None):
    """Two rounds of the folded ``train()`` through FedAvg's declared
    round, tracer armed: ``(engine, round_log arguments, history)``.
    ``plant`` edits the freshly initialised parameters."""
    from neuroimagedisttraining_tpu.config import (
        DataConfig, ExperimentConfig, FedConfig,
    )
    from neuroimagedisttraining_tpu.data.federate import federate_cohort
    from neuroimagedisttraining_tpu.data.synthetic import (
        generate_synthetic_abcd,
    )
    from neuroimagedisttraining_tpu.engines import create_engine
    from neuroimagedisttraining_tpu.obs import names as obs_names
    from neuroimagedisttraining_tpu.obs import trace as obs_trace
    from neuroimagedisttraining_tpu.utils.logging import ExperimentLogger

    cohort = generate_synthetic_abcd(num_subjects=24, shape=SHAPE,
                                     num_sites=2, seed=0)
    cohort["site"] = np.repeat(np.arange(2), (16, 8)).astype(
        cohort["site"].dtype)
    cfg = ExperimentConfig(
        model="nemotronh3d", num_classes=1, algorithm="fedavg",
        data=DataConfig(dataset="synthetic", partition_method="site"),
        optim=OptimConfig(lr=1e-2, batch_size=4, epochs=1),
        fed=FedConfig(client_num_in_total=2, comm_round=2),
        log_dir=str(tmp_path), tag=tag)
    tr = LocalTrainer(NemotronH3D(widths=SMALL), cfg.optim, 1)
    if plant is not None:
        init = tr.init_client_state
        tr.init_client_state = lambda *a: (
            lambda cs: cs.replace(params=plant(cs.params)))(init(*a))
    fed, _ = federate_cohort(cohort, partition_method="site", mesh=None)
    eng = create_engine("fedavg", cfg, fed, tr, mesh=None,
                        logger=ExperimentLogger(
                            str(tmp_path), "synthetic", cfg.identity(),
                            console=False))
    eng._fold_budget_bytes = 1
    obs_trace.arm()
    try:
        history = eng.train()["history"]
        logs = [e["args"] for e in obs_trace.TRACER.events()
                if e["ph"] == "X"
                and e["name"] == obs_names.SPAN_ROUND_LOG]
    finally:
        obs_trace.disarm()
    assert eng.program.placement == "folded"
    assert [a["round"] for a in logs] == [0, 1]
    return eng, logs, history


def test_folded_train_logs_the_held_rows_every_round(tmp_path):
    """Every round's ``round_log`` span carries ``tokens_routed`` over
    all 32 experts and the four expert layers, ``rows_held`` over the two
    held, both load ratios, the rows of a training step's buffer and the
    calls that passed it."""
    from neuroimagedisttraining_tpu.engines.fedavg import expert_load

    eng, logs, _ = _folded_train(tmp_path, "held")
    real_steps = int(np.ceil(np.asarray(eng.data.n_train) / 4).sum())
    for a in logs:
        assert a["tokens_routed"] == real_steps * 4 * 3 * (4 * 8)
        assert 0 < a["rows_held"] < a["tokens_routed"]
        assert a["held_load_max_over_mean"] >= 1.0
        assert a["expert_load_max_over_mean"] >= 1.0
        # a step routes 4 volumes x 8 tokens x 3 slots: twice 96 x 2 / 32
        assert a["held_capacity_rows"] == 12
        assert 0 <= a["held_overflow_calls"] <= real_steps * 4
        assert "attn_kernel_calls" not in a  # no such output of this model
    # by hand: 4 experts, the middle two held
    load = expert_load(np.asarray([10, 30, 10, 50]), (1, 2))
    assert load["tokens_routed"] == 100 and load["rows_held"] == 40
    assert load["held_load_max_over_mean"] == 1.5
    assert load["expert_load_max_over_mean"] == 2.0
    assert "held_overflow_calls" not in load
    load = expert_load(np.asarray([1, 2]), overflow_calls=np.int32(3),
                       capacity=512)
    assert "rows_held" not in load
    assert load["held_overflow_calls"] == 3
    assert load["held_capacity_rows"] == 512
    assert "attn_kernel_calls" not in load
    # Moonlight's third output: the attention calls that ran as the kernel
    load = expert_load(np.asarray([1, 2]), (0, 1), np.int32(0), 512,
                       kernel_calls=np.int32(144))
    assert load["attn_kernel_calls"] == 144
    assert "attn_outputs_kept" not in load
    # and its fourth: those of them whose outputs the layer kept
    load = expert_load(np.asarray([1, 2]), (0, 1), np.int32(0), 512,
                       kernel_calls=np.int32(144),
                       outputs_kept=np.int32(144))
    assert (load["attn_kernel_calls"], load["attn_outputs_kept"]) == (144,
                                                                      144)
    assert load["held_overflow_calls"] == 0 and load["rows_held"] == 1


def _all_alike(params):
    """Every token of every expert layer to the held experts 6 and 7 and
    the unheld 9 (``test_no_row_is_lost_when_every_token_comes_here``)."""
    params = jax.tree.map(lambda a: a, params)
    for i, kind in enumerate(PATTERN):
        if kind == "E":
            # a buffer each: the round program donates its carry
            params[f"layers_{i}"]["mixer"]["router"] = jnp.full(
                (64, E), -1.0).at[:, 6].set(0.5).at[:, 7].set(0.4).at[
                    :, 9].set(0.3)
            params[f"layers_{i}"]["norm"]["weight"] = jnp.ones((64,))
    return params


def test_a_round_whose_rows_pass_the_buffer_is_counted_and_exact(
        tmp_path, monkeypatch):
    """The fault PR 29's bounded buffer had, made observable: a planted
    routing sends every token of every step to the held experts, far
    past the buffer; the round says so (``held_overflow_calls`` counts
    the four expert layers of every real step) and trains to the losses
    of the program that has the full sort alone."""
    eng, logs, history = _folded_train(tmp_path / "either", "passed",
                                       plant=_all_alike)
    real_steps = int(np.ceil(np.asarray(eng.data.n_train) / 4).sum())
    assert logs[0]["held_overflow_calls"] == real_steps * 4
    assert logs[0]["rows_held"] > 4 * real_steps * logs[0][
        "held_capacity_rows"]
    monkeypatch.setattr(moe, "held_capacity", lambda *a: None)
    _, logs_full, history_full = _folded_train(tmp_path / "full", "passed",
                                               plant=_all_alike)
    assert logs_full[0]["held_overflow_calls"] == 0
    assert logs_full[0]["held_capacity_rows"] == 0
    np.testing.assert_allclose(
        [h["train_loss"] for h in history],
        [h["train_loss"] for h in history_full], rtol=1e-6)
    np.testing.assert_allclose(
        [h["acc"] for h in history], [h["acc"] for h in history_full])


def test_evaluation_filler_that_passes_the_buffer_drops_no_row(
        ref, monkeypatch):
    """``LocalTrainer.evaluate`` fills its last batch with ZERO volumes
    (here 27 of the 32 rows asked for; left to itself it leaves at most
    one fewer than its batches, core/trainer.py ``eval_batches``),
    whose tokens are all alike and take the same experts; the share held
    here is made to include the one they take first, so the filler alone
    passes the buffer (27 volumes x 8 tokens against 96 rows). The five
    real rows' logits are the reference's and those of the program that
    has the full sort alone: a buffer that dropped what does not fit
    (PR 29's, 1.64e-3 on the chip) would have dropped theirs."""
    tr = _trainer()
    cs = _state(tr)
    _, inter = tr.model.apply(
        {"params": cs.params}, tr._prep(jnp.zeros((1,) + SHAPE, jnp.uint8)),
        capture_intermediates=lambda m, _: isinstance(m, HeldExperts))
    chosen = jax.tree.leaves(inter["intermediates"],
                             is_leaf=lambda t: isinstance(t, tuple))[0][0][1]
    first = int(np.bincount(np.asarray(chosen).ravel()).argmax()) // 2 * 2
    held = (first, COUNT)
    tr = _trainer(widths=dataclasses.replace(SMALL, held=held))
    x, y = _batch(3)
    x, y = jnp.concatenate([x, x[:1] // 2]), jnp.concatenate([y, y[:1]])
    padded = jnp.pad(x, [(0, 27), (0, 0), (0, 0), (0, 0)])
    out = tr.model.apply({"params": cs.params}, tr._prep(padded))
    assert int(out[1]["held_overflow_calls"]) > 0
    evaluate = lambda: jax.jit(lambda p, x, y: tr.evaluate(
        p, {}, x, y, jnp.ones((5,)), batch_size=32))(cs.params, x, y)
    with jax.default_matmul_precision("highest"):
        got = evaluate()
        want, _ = ref.trunk(cs.params, x, cfg={**CFG, "held": held})
        monkeypatch.setattr(moe, "held_capacity", lambda *a: None)
        full = evaluate()
    assert got["scores"].shape == (5,)
    np.testing.assert_allclose(got["scores"], want[:, 0], rtol=F32_RTOL,
                               atol=F32_ATOL)
    np.testing.assert_allclose(got["scores"], full["scores"],
                               rtol=F32_RTOL, atol=F32_ATOL)
    assert float(got["test_total"]) == 5.0
