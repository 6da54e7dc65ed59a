"""Mixed-precision + fused-update contract (ISSUE 10).

Pins, in contract order:

- config validation dies at startup (unknown precision, loss_scale under
  fp32, fused_update off the SGD chain);
- ``ops/fused_update.fused_sgd_step`` is BITWISE-equal to the unfused
  optax chain (clip -> wd -> momentum -> -lr update -> mask) across the
  stage on/off matrix, and the Pallas kernel (interpreter mode on this
  CPU tier) matches the XLA fallback within tolerance;
- the plain fp32 path is bitwise-unchanged with the fused flag on, at
  engine-round granularity, for the dense (fedavg) and masked
  (salientgrads) flagship shapes — masks and metrics identical;
- bf16_mixed keeps f32 MASTER weights, reproduces the fp32 metrics
  within the stated tolerance on the fp32-safe tiny model, and the
  fixed loss-scale constant is exact: scale 1024 == scale 1 bitwise
  (power-of-two scaling of an f32 loss);
- bf16_mixed composes with the fused K-window driver (bitwise vs the
  sequential loop) and with checkpoint resume landing mid-window
  (extends tests/test_dispatch.py's resume pin): restored master
  weights are float32 and the resumed run equals the unbroken run
  bitwise.
"""

import dataclasses

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from neuroimagedisttraining_tpu.config import (
    DataConfig, ExperimentConfig, FedConfig, OptimConfig,
)
from neuroimagedisttraining_tpu.core.optim import (
    compute_dtype, make_local_optimizer, validate_precision,
)
from neuroimagedisttraining_tpu.core.trainer import LocalTrainer
from neuroimagedisttraining_tpu.data.federate import federate_cohort
from neuroimagedisttraining_tpu.engines import create_engine
from neuroimagedisttraining_tpu.models import create_model
from neuroimagedisttraining_tpu.ops import fused_update as fu
from neuroimagedisttraining_tpu.utils.logging import ExperimentLogger


def _bitwise(a, b):
    la, lb = jax.tree.leaves(a), jax.tree.leaves(b)
    assert len(la) == len(lb)
    for x, y in zip(la, lb):
        np.testing.assert_array_equal(np.asarray(x), np.asarray(y))


def _engine(tmp_path, cohort, algorithm="fedavg", precision="fp32",
            fused=False, loss_scale=1.0, comm_round=2,
            freq=10 ** 9, tag="p", checkpoint_dir="", checkpoint_every=0,
            **fed_kw):
    optim = OptimConfig(lr=1e-3, batch_size=8, epochs=1,
                        precision=precision, loss_scale=loss_scale,
                        fused_update=fused)
    cfg = ExperimentConfig(
        model="3dcnn_tiny", num_classes=1, algorithm=algorithm,
        data=DataConfig(dataset="synthetic", partition_method="site"),
        optim=optim,
        fed=FedConfig(client_num_in_total=4, comm_round=comm_round,
                      frequency_of_the_test=freq, **fed_kw),
        checkpoint_dir=checkpoint_dir, checkpoint_every=checkpoint_every,
        log_dir=str(tmp_path), tag=tag)
    trainer = LocalTrainer(
        create_model(cfg.model, num_classes=1,
                     dtype=compute_dtype(precision)),
        optim, num_classes=1)
    fed, _ = federate_cohort(cohort, partition_method="site")
    log = ExperimentLogger(str(tmp_path), "synthetic", cfg.identity(),
                           console=False)
    return create_engine(algorithm, cfg, fed, trainer, logger=log)


# ---------------------------------------------------------------------------
# config validation
# ---------------------------------------------------------------------------

def test_precision_validation_rejects_bad_configs():
    with pytest.raises(ValueError, match="unknown precision"):
        validate_precision(OptimConfig(precision="fp16"))
    with pytest.raises(ValueError, match="bf16_mixed"):
        validate_precision(OptimConfig(loss_scale=128.0))
    with pytest.raises(ValueError, match="positive finite"):
        validate_precision(OptimConfig(precision="bf16_mixed",
                                       loss_scale=0.0))
    with pytest.raises(ValueError, match="fused"):
        validate_precision(OptimConfig(client_optimizer="adam",
                                       fused_update=True))
    # the trainer enforces the same contract at build
    with pytest.raises(ValueError, match="bf16_mixed"):
        LocalTrainer(create_model("3dcnn_tiny", num_classes=1),
                     OptimConfig(loss_scale=2.0), num_classes=1)
    assert compute_dtype("bf16_mixed") == jnp.bfloat16
    assert compute_dtype("fp32") == jnp.float32


# ---------------------------------------------------------------------------
# fused step vs the optax chain
# ---------------------------------------------------------------------------

@pytest.mark.parametrize("clip,wd,mom", [
    (10.0, 5e-4, 0.9),     # the flagship chain, clip triggered below
    (1e-3, 5e-4, 0.9),     # clip rescale branch taken
    (0.0, 0.0, 0.9),       # momentum only
    (10.0, 0.0, 0.0),      # clip only (no trace state)
])
def test_fused_step_bitwise_equals_optax_chain(clip, wd, mom):
    key = jax.random.key(0)
    params = {"w": jax.random.normal(key, (37, 129)),
              "b": jax.random.normal(jax.random.fold_in(key, 1), (129,))}
    grads = jax.tree.map(lambda x: x * 0.1 + 0.3, params)
    mask = {"w": (jax.random.uniform(jax.random.fold_in(key, 2),
                                     (37, 129)) > 0.5).astype(jnp.float32),
            "b": jnp.ones((129,))}
    cfg = OptimConfig(grad_clip=clip, wd=wd, momentum=mom)
    opt = make_local_optimizer(cfg)
    opt_f = make_local_optimizer(dataclasses.replace(cfg,
                                                     fused_update=True))
    assert opt_f.fused_apply is not None
    st = opt.init(params)
    lr = jnp.float32(0.01)

    @jax.jit
    def unfused(p, s):
        updates, s2 = opt.update(grads, s, p, lr)
        p = jax.tree.map(jnp.add, p, updates)
        return jax.tree.map(jnp.multiply, p, mask), s2

    @jax.jit
    def fused(p, s):
        return opt_f.fused_apply(grads, s, p, lr, mask)

    _bitwise(unfused(params, st), fused(params, st))
    # dense (mask=None) variant
    @jax.jit
    def unfused_dense(p, s):
        updates, s2 = opt.update(grads, s, p, lr)
        return jax.tree.map(jnp.add, p, updates), s2

    @jax.jit
    def fused_dense(p, s):
        return opt_f.fused_apply(grads, s, p, lr, None)

    _bitwise(unfused_dense(params, st), fused_dense(params, st))


@pytest.mark.parametrize("clip,wd,mom,masked", [
    (10.0, 5e-4, 0.9, True),
    (1e-3, 0.0, 0.0, False),
])
def test_fused_kernel_interpret_matches_fallback(clip, wd, mom, masked):
    """The Pallas kernel (interpreter mode on this CPU tier — the
    blocking/padding plumbing under test) matches the XLA fallback
    within tolerance; on-TPU bit-equality is the bench's pin
    (bench_matrix/precision_bench.json on a chip session)."""
    key = jax.random.key(7)
    # a deliberately lane-unaligned leaf exercises the padding path
    params = {"w": jax.random.normal(key, (13, 57)),
              "b": jax.random.normal(jax.random.fold_in(key, 3), (5,))}
    grads = jax.tree.map(lambda x: x * 0.3 + 0.1, params)
    trace = jax.tree.map(jnp.ones_like, params) if mom > 0 else None
    mask = (jax.tree.map(
        lambda x: (x > 0).astype(jnp.float32), params) if masked else None)
    lr = jnp.float32(0.05)
    p_i, t_i = fu.fused_sgd_step(params, grads, trace, mask, clip=clip,
                                 wd=wd, momentum=mom, lr=lr,
                                 use_pallas=False, interpret=True)
    p_x, t_x = fu.fused_sgd_step(params, grads, trace, mask, clip=clip,
                                 wd=wd, momentum=mom, lr=lr,
                                 use_pallas=False)
    for a, b in zip(jax.tree.leaves(p_i), jax.tree.leaves(p_x)):
        np.testing.assert_allclose(np.asarray(a), np.asarray(b),
                                   rtol=1e-6, atol=1e-7)
    if mom > 0:
        for a, b in zip(jax.tree.leaves(t_i), jax.tree.leaves(t_x)):
            np.testing.assert_allclose(np.asarray(a), np.asarray(b),
                                       rtol=1e-6, atol=1e-7)


# ---------------------------------------------------------------------------
# engine rounds: fused on/off, fp32 bitwise; masked engine identical
# ---------------------------------------------------------------------------

def _one_round(eng):
    gs = eng.init_global_state()
    sampled = eng.client_sampling(0)
    rngs = eng.per_client_rngs(0, sampled)
    lr = eng.round_lr(0)
    if eng.name == "salientgrads":
        masks, _ = eng.generate_global_mask(gs.params, gs.batch_stats)
        per = eng.broadcast_states(gs, eng.num_clients)
        out = eng._round_jit(gs.params, gs.batch_stats, per.params,
                             per.batch_stats, eng.data, masks,
                             jnp.asarray(sampled), rngs, lr)
        return out[:2] + (masks,)
    out = eng._round_jit(gs.params, gs.batch_stats, eng.data,
                         jnp.asarray(sampled), rngs, lr)
    return out[:2]


@pytest.mark.parametrize("algorithm", ["fedavg", "salientgrads"])
def test_fused_round_bitwise_equals_unfused_fp32(tmp_path, synthetic_cohort,
                                                 algorithm):
    """The acceptance pin: fp32 + fused_update is bitwise the fp32 tree,
    dense and masked — identical params, batch_stats, and (masked) the
    identical mask."""
    out_u = _one_round(_engine(tmp_path, synthetic_cohort, algorithm,
                               fused=False, tag="uf"))
    out_f = _one_round(_engine(tmp_path, synthetic_cohort, algorithm,
                               fused=True, tag="fu"))
    _bitwise(out_u, out_f)


# ---------------------------------------------------------------------------
# bf16_mixed: master weights, tolerance, loss-scale exactness
# ---------------------------------------------------------------------------

def test_bf16_mixed_masters_f32_and_metrics_within_tolerance(
        tmp_path, synthetic_cohort):
    """bf16_mixed on the fp32-safe tiny model reproduces the fp32 round
    within the STATED tolerance — end-round loss within 2e-3 absolute,
    master weights within 5e-3 — and every master-weight leaf stays
    float32 (what checkpoints and aggregation see)."""
    eng32 = _engine(tmp_path, synthetic_cohort, tag="f32")
    eng16 = _engine(tmp_path, synthetic_cohort, precision="bf16_mixed",
                    tag="b16")
    gs32, gs16 = eng32.init_global_state(), eng16.init_global_state()
    _bitwise(gs32.params, gs16.params)  # identical f32 init
    s = eng32.client_sampling(0)
    r = eng32.per_client_rngs(0, s)
    p32, b32, l32, _ = eng32._round_jit(gs32.params, gs32.batch_stats,
                                        eng32.data, jnp.asarray(s), r,
                                        eng32.round_lr(0))
    p16, b16, l16, _ = eng16._round_jit(gs16.params, gs16.batch_stats,
                                        eng16.data, jnp.asarray(s), r,
                                        eng16.round_lr(0))
    for leaf in jax.tree.leaves(p16):
        assert leaf.dtype == jnp.float32
    for leaf in jax.tree.leaves(b16):
        assert leaf.dtype == jnp.float32
    assert abs(float(l16) - float(l32)) < 2e-3
    for a, b in zip(jax.tree.leaves(p32), jax.tree.leaves(p16)):
        np.testing.assert_allclose(np.asarray(a), np.asarray(b), atol=5e-3)


def test_loss_scale_pin_power_of_two_is_exact(tmp_path, synthetic_cohort):
    """The fixed loss-scale contract: scale 1024 (power of two — exact
    f32 multiply/divide) reproduces scale 1 BITWISE under bf16_mixed."""
    e1 = _engine(tmp_path, synthetic_cohort, precision="bf16_mixed",
                 loss_scale=1.0, tag="s1")
    e2 = _engine(tmp_path, synthetic_cohort, precision="bf16_mixed",
                 loss_scale=1024.0, tag="s1024")
    _bitwise(_one_round(e1), _one_round(e2))


# ---------------------------------------------------------------------------
# composition: the round driver + checkpoint resume under bf16_mixed
# ---------------------------------------------------------------------------

def test_bf16_driver_equals_rounds_by_hand(tmp_path, synthetic_cohort):
    """bf16_mixed under the driver: four rounds of ``train()`` equal the
    same four rounds dispatched by hand bitwise — same pin as
    test_dispatch's, at the new precision (frac<1 keeps per-round
    sampling load-bearing) — and are one compiled program."""
    drv = _engine(tmp_path, synthetic_cohort, precision="bf16_mixed",
                  comm_round=4, freq=4, frac=0.5, tag="bdrv")
    res = drv.train()
    assert drv.program.dispatches == 4 and drv.program.built == 1
    seq = _engine(tmp_path, synthetic_cohort, precision="bf16_mixed",
                  comm_round=4, freq=4, frac=0.5, tag="bseq")
    gs = seq.init_global_state()
    p, b, losses = gs.params, gs.batch_stats, []
    for r in range(4):
        sampled = seq.client_sampling(r)
        p, b, loss, _ = seq._round_jit(
            p, b, seq.data, jnp.asarray(sampled),
            seq.per_client_rngs(r, sampled), seq.round_lr(r))
        losses.append(float(loss))
    _bitwise(res["params"], p)
    _bitwise(res["batch_stats"], b)
    assert [h["train_loss"] for h in res["history"]] == \
        [losses[0], losses[3]]


def test_bf16_checkpoint_resume_bitwise(tmp_path, synthetic_cohort):
    """Checkpoint round-trip under bf16_mixed (ISSUE 10 satellite): the
    saved state IS the f32 master weights (restored bitwise, dtype
    float32), and a resume from the round-1 checkpoint reproduces the
    unbroken run bitwise."""
    from neuroimagedisttraining_tpu.utils import checkpoint as ckpt

    full = _engine(tmp_path, synthetic_cohort, precision="bf16_mixed",
                   comm_round=4, tag="cfull").train()
    ck = str(tmp_path / "ck_bf16")
    part = _engine(tmp_path, synthetic_cohort, precision="bf16_mixed",
                   comm_round=2, checkpoint_dir=ck,
                   checkpoint_every=2, tag="cpart").train()
    # the checkpoint carries f32 master weights bitwise
    r, state = ckpt.load_checkpoint(ck)
    assert r == 1
    for leaf in jax.tree.leaves(state["params"]):
        assert np.asarray(leaf).dtype == np.float32
    _bitwise(state["params"], part["params"])
    resumed = _engine(tmp_path, synthetic_cohort, precision="bf16_mixed",
                      comm_round=4, checkpoint_dir=ck,
                      checkpoint_every=2, tag="cres").train()
    _bitwise(full["params"], resumed["params"])
    _bitwise(full["batch_stats"], resumed["batch_stats"])
