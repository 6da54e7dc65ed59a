"""The FOLDED placement (PR 25): clients in time, the aggregate folded.

A round program whose stacked client states exceed the device's memory
budget runs its clients one after another in a ``lax.scan`` with a single
client state alive, each upload folded into a running weighted sum
(engines/program.py ``_fold_body``). These tests pin it against the
stacked round on ``3dcnn_tiny`` with three clients of unequal size: the
same clients, the same rngs, the same data; only the placement differs.

Tolerance: the stacked tail computes ``sum_i (w_i / W) x_i`` and the fold
``(sum_i w_i x_i) / W`` in float32, and ``vmap`` lowers a client's
convolutions batched where the fold runs them alone, so the two differ by
float32 summation order and nothing else: ``rtol`` 2e-5 on values of
order 0.01-1 (``atol`` 2e-6 for the entries near zero). A wrong weight, a
dropped client or a stale state is off by orders of magnitude more.
"""

import dataclasses

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from neuroimagedisttraining_tpu.config import (
    DataConfig, ExperimentConfig, FedConfig, OptimConfig,
)
from neuroimagedisttraining_tpu.core.trainer import LocalTrainer
from neuroimagedisttraining_tpu.data.federate import federate_cohort
from neuroimagedisttraining_tpu.data.synthetic import generate_synthetic_abcd
from neuroimagedisttraining_tpu.engines import create_engine
from neuroimagedisttraining_tpu.engines import program as round_program
from neuroimagedisttraining_tpu.models import create_model
from neuroimagedisttraining_tpu.obs import trace as obs_trace
from neuroimagedisttraining_tpu.utils.logging import ExperimentLogger

RTOL, ATOL = 2e-5, 2e-6
SITES = (40, 24, 12)  # unequal: the weights and the padded steps differ


@pytest.fixture(scope="module")
def cohort3():
    cohort = generate_synthetic_abcd(num_subjects=sum(SITES),
                                     shape=(12, 14, 12), num_sites=3,
                                     seed=3)
    # sites of unequal size, in order
    cohort["site"] = np.repeat(np.arange(3), SITES).astype(
        cohort["site"].dtype)
    return cohort


def _engine(tmp_path, cohort, *, budget, tag, algorithm="fedavg",
            comm_round=2, model="3dcnn_tiny", batch_size=8, clients=3,
            epochs=2, **fed_kw):
    cfg = ExperimentConfig(
        model=model, num_classes=1, algorithm=algorithm,
        data=DataConfig(dataset="synthetic", partition_method="site"),
        optim=OptimConfig(lr=1e-2, batch_size=batch_size, epochs=epochs),
        fed=FedConfig(client_num_in_total=clients, comm_round=comm_round,
                      frequency_of_the_test=1, **fed_kw),
        log_dir=str(tmp_path), tag=tag)
    trainer = LocalTrainer(create_model(cfg.model, num_classes=1),
                           cfg.optim, num_classes=1)
    log = ExperimentLogger(str(tmp_path), "synthetic", cfg.identity(),
                           console=False)
    fed, _ = federate_cohort(cohort, partition_method="site", mesh=None)
    eng = create_engine(algorithm, cfg, fed, trainer, mesh=None,
                        logger=log)
    eng._fold_budget_bytes = budget
    return eng


def _close(a, b):
    la, lb = jax.tree.leaves(a), jax.tree.leaves(b)
    assert len(la) == len(lb)
    for x, y in zip(la, lb):
        np.testing.assert_allclose(np.asarray(x, np.float64),
                                   np.asarray(y, np.float64),
                                   rtol=RTOL, atol=ATOL)


def _one_round(eng, params=None, bstats=None, poison=None):
    gs = eng.init_global_state()
    params = gs.params if params is None else params
    bstats = gs.batch_stats if bstats is None else bstats
    if poison is not None:
        # a client whose data makes its upload non-finite
        X = np.asarray(eng.data.X_train).astype(np.float32)
        eng.data = dataclasses.replace(
            eng.data, X_train=jnp.asarray(X).at[poison].set(jnp.inf))
    sampled = eng.client_sampling(0)
    rngs = eng.per_client_rngs(0, sampled)
    return eng._round_jit(params, bstats, eng.data, jnp.asarray(sampled),
                          rngs, eng.round_lr(0))


def test_placement_follows_the_budget(tmp_path, cohort3):
    """The fold is chosen when the budget is under the stacked states,
    and not otherwise; with no budget reported (the CPU) the stacked
    path stands."""
    big = _engine(tmp_path, cohort3, budget=1 << 40, tag="big")
    assert big.program.placement == round_program.STACKED
    assert not big.folded
    small = _engine(tmp_path, cohort3, budget=1, tag="small")
    assert small.program.placement == round_program.FOLDED
    none = _engine(tmp_path, cohort3, budget=None, tag="none")
    assert none.fold_budget_bytes() is None  # CPU: no bytes_limit
    assert none.program.placement == round_program.STACKED
    # the rule itself: rows x (2 x params + opt_state) against the budget
    cs = jax.eval_shape(big.trainer.init_client_state, jax.random.key(0),
                        big.sample_input())
    need = round_program.stacked_state_bytes(cs.params, cs.opt_state, 3)
    p = round_program.tree_bytes(cs.params)
    assert need == 3 * (2 * p + round_program.tree_bytes(cs.opt_state))
    at = _engine(tmp_path, cohort3, budget=need, tag="at")
    assert at.program.placement == round_program.STACKED
    under = _engine(tmp_path, cohort3, budget=need - 1, tag="under")
    assert under.program.placement == round_program.FOLDED


def _small_evabyte3d(name, num_classes=1):
    """EvaByte's layer at a small size (models/evabyte3d.py): 12 x 14 x 12
    volumes in patches of 4 are 36 tokens, two windows of 16 and one of
    4, so a folded client computes summaries read across both edges."""
    from neuroimagedisttraining_tpu.models.evabyte3d import EvaByte3D, Widths

    return EvaByte3D(num_classes=num_classes, widths=Widths(
        layers=2, hidden_size=32, heads=2, head_dim=16,
        intermediate_size=48, window_size=16, chunk_size=4, patch=4))


def _small_moonlight3d(name, num_classes=1):
    """Moonlight's layer at a small size (models/moonlight3d.py): 12 x 14
    x 12 volumes in patches of 4 are 36 tokens, two query blocks of 16 and
    one of 4; a dense layer, then two expert layers that hold 4 of 16
    experts (the stacked round runs their held rows under ``vmap``)."""
    from neuroimagedisttraining_tpu.models.moonlight3d import (
        Moonlight3D, Widths,
    )

    return Moonlight3D(num_classes=num_classes, widths=Widths(
        dense_layers=1, expert_layers=2, hidden_size=32, heads=2,
        qk_nope_head_dim=8, qk_rope_head_dim=8, v_head_dim=8,
        kv_lora_rank=16, intermediate_size=48, num_experts=16, held=(0, 4),
        experts_per_token=4, expert_width=16, block=16, patch=4))


def _small_trinity3d(name, num_classes=1):
    """Trinity-Mini's layers at a small size (models/trinity3d.py): 12 x
    14 x 12 volumes in patches of 4 are 36 tokens, two windows of 16 and
    4 more, two query blocks of 16 and one of 4; a dense sliding layer,
    then a full and a sliding expert layer that hold 4 of 16 experts."""
    from neuroimagedisttraining_tpu.models.trinity3d import (
        FULL, SLIDING, Trinity3D, Widths,
    )

    return Trinity3D(num_classes=num_classes, widths=Widths(
        layer_types=(SLIDING, FULL, SLIDING), dense_layers=1, hidden_size=32,
        heads=4, kv_heads=2, head_dim=8, sliding_window=16,
        intermediate_size=48, num_experts=16, held=(0, 4),
        experts_per_token=4, expert_width=16, block=16, patch=4))


SMALL_MODELS = {"evabyte3d_small": _small_evabyte3d,
                "moonlight3d_small": _small_moonlight3d,
                "trinity3d_small": _small_trinity3d}


@pytest.mark.parametrize("model", ["3dcnn_tiny", *sorted(SMALL_MODELS)])
def test_folded_round_equals_stacked_round(tmp_path, cohort3, monkeypatch,
                                           model):
    """New global parameters and batch statistics equal to float32
    summation order; the round's loss and n_bad equal."""
    if model in SMALL_MODELS:
        monkeypatch.setitem(globals(), "create_model", SMALL_MODELS[model])
    st = _one_round(_engine(tmp_path, cohort3, budget=1 << 40, tag="s",
                            model=model))
    fo = _one_round(_engine(tmp_path, cohort3, budget=1, tag="f",
                            model=model))
    _close(st[0], fo[0])
    _close(st[1], fo[1])
    np.testing.assert_allclose(float(st[2]), float(fo[2]), rtol=RTOL)
    assert int(st[3]) == int(fo[3]) == 0
    # the round moved the model (the comparison is not of two no-ops)
    gs = _engine(tmp_path, cohort3, budget=None, tag="g",
                 model=model).init_global_state()
    moved = max(float(jnp.max(jnp.abs(a - b))) for a, b in zip(
        jax.tree.leaves(gs.params), jax.tree.leaves(fo[0])))
    assert moved > 1e-4


def test_flagship_stem_stacked_equals_folded(tmp_path):
    """The flagship ``3DCNN`` at the smallest volume it takes (69^3), two
    clients of unequal size: stacked, the stem block computes in the
    client-merged layout (ops/stemconv.py: a grouped convolution, norm,
    relu and pool on 2 x 64 channels, each client's weight gradient a
    re-expressed contraction on its window of the merged ``g``); folded,
    a row runs alone and takes the plain composition. One round of each
    agrees to float32 summation order: at this size a gradient is a sum
    of 72 thousand terms a sample through the norm's cancelling backward,
    and the parent's two placements already differed by 2.3e-6 in f1's
    kernel, so the band is five times the tiny model's (a wrong client's
    channels or window would be off by the values themselves); f0's
    convolution bias, whose gradient is zero in exact arithmetic (the
    norm subtracts the mean), is noise in both. The stacked program
    names its merged ops inside ``stem/f0``, the folded one names
    none."""
    from neuroimagedisttraining_tpu.obs import names as obs_names

    sites = (4, 2)  # two steps and one: the stacked row pads a step
    cohort = generate_synthetic_abcd(num_subjects=sum(sites),
                                     shape=(69, 69, 69), num_sites=2, seed=5)
    cohort["site"] = np.repeat(np.arange(2), sites).astype(
        cohort["site"].dtype)
    kw = dict(model="3DCNN", batch_size=2, clients=2, epochs=1)
    stacked = _engine(tmp_path, cohort, budget=1 << 40, tag="s", **kw)
    folded = _engine(tmp_path, cohort, budget=1, tag="f", **kw)
    st, fo = _one_round(stacked), _one_round(folded)
    params = [jax.tree.map(lambda x: x, t[0]) for t in (st, fo)]
    np.testing.assert_allclose(
        *(p["f0"]["conv"].pop("bias") for p in params), atol=1e-5)
    for a, b in zip(jax.tree.leaves((params[0], st[1])),
                    jax.tree.leaves((params[1], fo[1]))):
        np.testing.assert_allclose(np.asarray(a), np.asarray(b),
                                   rtol=5 * RTOL, atol=5 * ATOL)
    np.testing.assert_allclose(float(st[2]), float(fo[2]), rtol=RTOL)
    assert int(st[3]) == int(fo[3]) == 0

    def op_names(eng):
        gs = eng.init_global_state()
        sampled = eng.client_sampling(0)
        text = eng._round_jit.lower(
            gs.params, gs.batch_stats, eng.data, jnp.asarray(sampled),
            eng.per_client_rngs(0, sampled), eng.round_lr(0)
        ).as_text(debug_info=True)
        return text

    merged = (f"/{obs_names.SCOPE_STEM}/f0/"
              f"{obs_names.SCOPE_STEM_MERGED}/")
    assert merged in op_names(stacked)
    assert f"/{obs_names.SCOPE_STEM_MERGED}/" not in op_names(folded)


def test_nonfinite_client_dropped_in_both(tmp_path, cohort3):
    """A client whose upload is non-finite adds nothing and counts in
    n_bad, in both placements; the survivors' mean is the same."""
    st = _one_round(_engine(tmp_path, cohort3, budget=1 << 40, tag="s"),
                    poison=1)
    fo = _one_round(_engine(tmp_path, cohort3, budget=1, tag="f"),
                    poison=1)
    assert int(st[3]) == int(fo[3]) == 1
    assert all(bool(jnp.all(jnp.isfinite(x)))
               for x in jax.tree.leaves(fo[0]))
    _close(st[0], fo[0])
    np.testing.assert_allclose(float(st[2]), float(fo[2]), rtol=RTOL)


def test_clip_defense_folds_per_client(tmp_path, cohort3):
    """A clip-family defense acts per client and folds: same result."""
    kw = dict(defense_type="norm_diff_clipping", norm_bound=0.05)
    st = _one_round(_engine(tmp_path, cohort3, budget=1 << 40, tag="s",
                            **kw))
    fo = _one_round(_engine(tmp_path, cohort3, budget=1, tag="f", **kw))
    _close(st[0], fo[0])


@pytest.mark.parametrize("key,kw", [
    ("fold-order-statistic-defense", dict(defense_type="median")),
    ("fold-byz-attack-plan", dict(fault_spec="byz:1@0:sign_flip")),
    ("fold-codec-error-feedback", dict(wire_codec="delta+sparse+quant")),
    ("fold-secure-quant", dict(secure_quant=True, secure_quant_field_bits=32)),
])
def test_refused_combinations_raise_their_reason(tmp_path, cohort3, key,
                                                 kw):
    """What needs the whole upload stack at once refuses at program
    build with its REASONS message; the same configuration builds when
    the stack fits."""
    eng = _engine(tmp_path, cohort3, budget=1, tag="r" + key[5:9], **kw)
    assert eng.program.fold_refusal_key() == key
    with pytest.raises(ValueError) as e:
        eng.program.placement
    assert round_program.reason(key) in str(e.value)
    ok = _engine(tmp_path, cohort3, budget=1 << 40, tag="k" + key[5:9],
                 **kw)
    assert ok.program.placement == round_program.STACKED


def test_health_stats_refuse_the_fold(tmp_path, cohort3):
    eng = _engine(tmp_path, cohort3, budget=1, tag="h")
    eng.cfg = dataclasses.replace(eng.cfg, health_stats=True)
    assert eng.program.fold_refusal_key() == "fold-health-stats"
    with pytest.raises(ValueError, match="health_stats"):
        eng.program.placement


def test_undeclared_engine_keeps_the_stacked_program(tmp_path, cohort3):
    """An engine whose stages do not declare the fold keeps its stacked
    program, with a counted fallback reason."""
    from neuroimagedisttraining_tpu.obs import metrics as obs_metrics
    from neuroimagedisttraining_tpu.obs import names as obs_names

    counter = obs_metrics.counter(
        obs_names.FALLBACK_TOTAL, labelnames=("plane", "engine", "reason"))
    labels = dict(plane="fold", engine="ditto", reason="fold-not-declared")
    before = counter.get(**labels)
    eng = _engine(tmp_path, cohort3, budget=1, tag="d", algorithm="ditto")
    assert not eng.program.stages.folds
    assert eng.program.placement == round_program.STACKED
    assert counter.get(**labels) == before + 1.0


def test_train_end_to_end_matches_stacked(tmp_path, cohort3):
    """engine.train() folded: rounds, evaluation (clients scanned, not
    vmapped), the final fine-tune pass (fine-tuned, evaluated and
    discarded one at a time) give the stacked run's metrics; the folded
    run returns no stack of personalized states; the dispatch span
    carries the placement, and ``steps_run`` what that placement
    executes."""
    obs_trace.arm()
    try:
        st = _engine(tmp_path, cohort3, budget=1 << 40, tag="s").train()
        n0 = len(obs_trace.TRACER.events())
        fo = _engine(tmp_path, cohort3, budget=1, tag="f").train()
        events = obs_trace.TRACER.events()
    finally:
        obs_trace.disarm()
    assert fo["personal"] is None and st["personal"] is not None
    _close(st["params"], fo["params"])
    for a, b in zip(st["history"], fo["history"]):
        for k in ("train_loss", "loss", "acc", "auc"):
            np.testing.assert_allclose(a[k], b[k], rtol=1e-4, atol=1e-6)
    for part in ("final_global", "final_personal"):
        for k in ("loss", "acc", "auc"):
            np.testing.assert_allclose(st[part][k], fo[part][k],
                                       rtol=1e-4, atol=1e-6)
    dispatch = [e["args"] for e in events
                if e.get("name") == "dispatch_program"
                and e["args"].get("steps_run")]
    assert {a["placement"] for a in dispatch[:2]} == {"stacked"}
    assert {a["placement"] for a in dispatch[2:]} == {"folded"}
    assert n0 > 0
    assert dispatch[0]["samples_real"] == dispatch[2]["samples_real"]
    assert dispatch[0]["steps_real"] == dispatch[2]["steps_real"]
    # stacked, every row walks the loop's whole length; folded, a row
    # runs alone and executes its own real steps and no other
    st0, fo0 = dispatch[0], dispatch[2]
    assert st0["steps_skipped"] == 0 and st0["steps_run"] > st0["steps_real"]
    assert fo0["steps_run"] == fo0["steps_real"]
    assert fo0["steps_run"] + fo0["steps_skipped"] == st0["steps_run"]
    for a in (st0, fo0):
        assert a["chip_steps_max"] == a["chip_steps_mean"] == a["steps_run"]
