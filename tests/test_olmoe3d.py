"""``--model olmoe3d`` against its plain reference (PR 25), on the CPU.

The program (models/olmoe3d.py, ops/moe.py: sort by expert, grouped
matmul over data-dependent group sizes, un-sort, combine) against
``benchmark/reference/olmoe-abcd.py`` (every expert for every token,
masked), on seeded random weights at a small size: hidden 64, 4 heads of
16, 8 experts of width 32, top-2, a 16x16x16 volume in 8x8x8 patches
(8 tokens a volume). The chip comparison at the published widths is the
builder's (PERF.md).
"""

import importlib.util
import os
import re

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from neuroimagedisttraining_tpu.config import OptimConfig
from neuroimagedisttraining_tpu.core.trainer import LocalTrainer
from neuroimagedisttraining_tpu.models import create_model, primary_logits
from neuroimagedisttraining_tpu.models.olmoe3d import OLMoE3D, SparseExperts

ROOT = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
SMALL = dict(hidden_size=64, num_heads=4, num_experts=8,
             experts_per_token=2, expert_width=32, patch=8)
CFG = {"num_heads": 4, "experts_per_token": 2, "rms_eps": 1e-5,
       "rope_theta": 10000.0, "patch": 8, "aux_coef": 0.01}
B, SHAPE = 6, (16, 16, 16)

#: float32, program against reference: the same products summed in another
#: order (a grouped matmul over sorted rows against a masked sum over all
#: experts; XLA's reduction trees). Values are of order 0.01-1, float32
#: carries 1.2e-7 a product and the longest contraction is 512 deep.
#: Nothing else may differ: a reference computed in bfloat16 is off by
#: 1e-3 and fails this by two orders of magnitude (asserted below).
F32_RTOL, F32_ATOL = 2e-5, 1e-6
#: bf16_mixed (bf16 operands and activations; float32 master weights,
#: norm statistics, router, softmax, accumulation and read-out) against the
#: float32 reference: 8 mantissa bits, 4e-3 a rounding, averaged down over
#: 64-512-deep contractions on the way to a logit of order 0.2. Measured
#: over the three seeds here: logits 2.6e-4 to 4.9e-4 absolute, the loss
#: 0.7e-4 to 1.9e-4 relative, gradients 0.8-2.4% (patch embedding) and
#: 0.2-0.3% (head) relative L2. The bounds are five times the largest seen
#: (three times for the gradients). The nearest precision below, float8
#: e4m3 operands (3 mantissa bits), is off by 1.4e-2 to 1.9e-2 in the
#: logits and fails the bound by a factor of five (asserted below).
BF16_LOGIT_ATOL = 2.5e-3
BF16_LOSS_RTOL = 1e-3
BF16_GRAD_REL_L2 = 0.08


def _load(name, path):
    spec = importlib.util.spec_from_file_location(name, path)
    mod = importlib.util.module_from_spec(spec)
    spec.loader.exec_module(mod)
    return mod


@pytest.fixture(scope="module")
def ref():
    return _load("ref_olmoe", os.path.join(
        ROOT, "benchmark", "reference", "olmoe-abcd.py"))


def _batch(seed):
    r = np.random.RandomState(seed)
    x = r.randint(0, 256, (B,) + SHAPE).astype(np.uint8)
    y = r.randint(0, 2, (B,)).astype(np.int32)
    return jnp.asarray(x), jnp.asarray(y)


def _trainer(dtype=jnp.float32, precision="fp32"):
    model = OLMoE3D(dtype=dtype, **SMALL)
    return LocalTrainer(model, OptimConfig(precision=precision), 1)


def _state(tr, seed=0):
    cs = tr.init_client_state(jax.random.key(seed),
                              jnp.zeros((1,) + SHAPE, jnp.float32))
    # norm weights away from 1 and a wider router, so that every term of
    # every gradient is exercised and the routing is not near-uniform
    r = np.random.RandomState(seed + 100)
    def jitter(path, x):
        name = jax.tree_util.keystr(path)
        if "norm" in name:
            return x + jnp.asarray(r.uniform(-0.3, 0.3, x.shape), x.dtype)
        if "router" in name:
            return x * 20.0
        if "patch_embed" in name and "kernel" in name:
            return x * 5.0
        return x
    return cs.replace(
        params=jax.tree_util.tree_map_with_path(jitter, cs.params))


def _program(tr, cs, x, y):
    """(logits, task loss, grads, expert_tokens, experts [N, k])."""
    out, inter = tr.model.apply(
        {"params": cs.params}, tr._prep(x), train=True,
        capture_intermediates=lambda m, _: isinstance(m, SparseExperts))
    experts = jax.tree.leaves(
        inter["intermediates"], is_leaf=lambda t: isinstance(t, tuple))[0][0][2]
    loss, grads, _, _ = tr.loss_and_grad(cs, x, y)
    return primary_logits(out), loss, grads, out[1], experts


def _rel_l2(a, b):
    a, b = np.asarray(a, np.float64), np.asarray(b, np.float64)
    return float(np.linalg.norm(a - b) / max(np.linalg.norm(b), 1e-30))


@pytest.mark.parametrize("seed", [0, 1, 2])
def test_float32_logits_loss_and_every_gradient(ref, seed):
    tr = _trainer()
    cs, (x, y) = _state(tr, seed), _batch(seed)
    logits, loss, grads, aux, _ = _program(tr, cs, x, y)
    with jax.default_matmul_precision("highest"):
        want = ref.forward(cs.params, {}, x, cfg=CFG)
        (total, (task, aux_ref)), g_ref = jax.value_and_grad(
            ref.training_loss, has_aux=True)(cs.params, {}, x, y, cfg=CFG)
        low = ref.forward(cs.params, {}, x, cfg=CFG,
                          q=ref.ops.rounded(jnp.bfloat16))
    np.testing.assert_allclose(logits, want, rtol=F32_RTOL, atol=F32_ATOL)
    # the reported loss is the task loss; the gradient is of task + aux
    np.testing.assert_allclose(float(loss), float(task), rtol=F32_RTOL)
    np.testing.assert_allclose(float(aux["loss"]), float(aux_ref),
                               rtol=F32_RTOL)
    assert float(aux_ref) > 0.0
    flat = jax.tree_util.tree_flatten_with_path(grads)[0]
    flat_ref = jax.tree.leaves(g_ref)
    assert len(flat) == len(flat_ref) == 16
    for (path, g), gr in zip(flat, flat_ref):
        assert float(jnp.max(jnp.abs(gr))) > 0, jax.tree_util.keystr(path)
        np.testing.assert_allclose(
            g, gr, rtol=F32_RTOL * 10,
            atol=F32_ATOL * float(jnp.max(jnp.abs(gr))) * 20,
            err_msg=jax.tree_util.keystr(path))
    # the tolerance is about precision: a bfloat16 reference fails it
    with pytest.raises(AssertionError):
        np.testing.assert_allclose(low, want, rtol=F32_RTOL, atol=F32_ATOL)


@pytest.mark.parametrize("seed", [0, 1, 2])
def test_bf16_mixed_against_the_float32_reference(ref, seed):
    tr = _trainer(jnp.bfloat16, "bf16_mixed")
    cs, (x, y) = _state(_trainer(), seed), _batch(seed)
    logits, loss, grads, _, _ = _program(tr, cs, x, y)
    with jax.default_matmul_precision("highest"):
        want = ref.forward(cs.params, {}, x, cfg=CFG)
        (_, (task, _)), g_ref = jax.value_and_grad(
            ref.training_loss, has_aux=True)(cs.params, {}, x, y, cfg=CFG)
        fp8 = ref.forward(cs.params, {}, x, cfg=CFG,
                          q=ref.ops.rounded(jnp.float8_e4m3fn))
    assert float(jnp.max(jnp.abs(logits - want))) <= BF16_LOGIT_ATOL
    np.testing.assert_allclose(float(loss), float(task),
                               rtol=BF16_LOSS_RTOL)
    for name in ("patch_embed", "head"):
        assert _rel_l2(jax.tree.leaves(grads[name])[0],
                       jax.tree.leaves(g_ref[name])[0]) <= BF16_GRAD_REL_L2
    # one precision below the stated one is NOT inside the tolerance
    assert float(jnp.max(jnp.abs(fp8 - want))) > BF16_LOGIT_ATOL


@pytest.mark.parametrize("seed", [0, 1, 2])
def test_routing_agrees_and_counts_match(ref, seed):
    """Float32: every token's k experts equal the reference's, and the
    model's ``expert_tokens`` is a bincount of the reference's choices."""
    tr = _trainer()
    cs, (x, y) = _state(tr, seed), _batch(seed)
    _, _, _, aux, experts = _program(tr, cs, x, y)
    with jax.default_matmul_precision("highest"):
        _, _, e_ref = ref.trunk(cs.params, x, cfg=CFG)
    assert experts.shape == e_ref.shape == (B * 8, 2)
    np.testing.assert_array_equal(np.sort(experts, -1), np.sort(e_ref, -1))
    np.testing.assert_array_equal(
        aux["expert_tokens"], np.bincount(np.asarray(e_ref).ravel(),
                                          minlength=8))
    assert len(np.unique(np.asarray(e_ref))) > 2  # the routing is spread


def test_dropless_when_every_token_goes_to_one_expert(ref):
    """A router forced to send every token to expert 3 (and its second
    slot to expert 5): the per-expert counts sum to k*T, nothing is
    dropped, and the output is still the reference's."""
    tr = _trainer()
    cs, (x, y) = _state(tr), _batch(7)
    router = jnp.zeros((64, 8)).at[:, 3].set(0.5).at[:, 5].set(0.25)
    params = jax.tree.map(lambda a: a, cs.params)
    params["layers_0"]["moe"]["router"] = router
    # all-positive normalised inputs: column sums decide the choice
    params["layers_0"]["mlp_norm"]["weight"] = jnp.ones((64,))
    cs = cs.replace(params=params)
    out = tr.model.apply({"params": params}, tr._prep(x), train=True)
    tokens = np.asarray(out[1]["expert_tokens"])
    T = B * 8
    assert tokens.sum() == 2 * T
    with jax.default_matmul_precision("highest"):
        want, _, e_ref = ref.trunk(params, x, cfg=CFG)
    np.testing.assert_array_equal(
        tokens, np.bincount(np.asarray(e_ref).ravel(), minlength=8))
    assert tokens.max() >= T // 2  # one expert holds most of the rows
    np.testing.assert_allclose(out[0], want, rtol=F32_RTOL, atol=F32_ATOL)


def test_local_train_counts_real_steps_only():
    """``local_train`` of a model with an auxiliary output: the reported
    loss is the task loss, and ``expert_tokens`` sums over the client's real steps (a
    padded step adds nothing): steps x k x tokens a step."""
    tr = _trainer()
    cs, (x, y) = _state(tr), _batch(3)
    X = jnp.concatenate([x, x])  # 12 rows, 5 of them valid
    Y = jnp.concatenate([y, y])
    cs, loss, tokens = tr.local_train(
        cs, X, Y, jnp.int32(5), 0.01, epochs=2, batch_size=2,
        max_samples=12)
    real_steps = 2 * 3  # ceil(5 / 2) an epoch, of 6 the scan walks
    assert int(tokens.sum()) == real_steps * 2 * (2 * 8)
    assert np.isfinite(float(loss))


def test_published_widths_and_work(ref):
    """``create_model("olmoe3d")`` is the published block: the parameter
    shapes, 427,964,416 parameters, and the tape's 98.5 GFLOP forward."""
    from benchmark import flops

    model = create_model("olmoe3d", 1)
    shapes = jax.eval_shape(
        lambda: model.init(jax.random.key(0),
                           jnp.zeros((1, 121, 145, 121, 1))))["params"]
    moe = shapes["layers_0"]["moe"]
    assert moe["gate"].shape == moe["up"].shape == (64, 2048, 1024)
    assert moe["down"].shape == (64, 1024, 2048)
    assert moe["router"].shape == (2048, 64)
    assert shapes["patch_embed"]["kernel"].shape == (4096, 2048)
    assert shapes["head"]["kernel"].shape == (2048, 1)
    n = sum(int(np.prod(x.shape)) for x in jax.tree.leaves(shapes))
    assert n == 427_964_416
    tape = flops.record_tape(ref.forward, shapes, {}, (121, 145, 121))
    assert tape == ref.published_tape()
    assert abs(flops.forward_flops(tape) / 1e9 - 98.48) < 0.01
    assert abs(flops.training_flops_per_sample(tape) / 1e9 - 295.45) < 0.01
    assert ref.expert_flops_per_sample(tape) == 3 * 2 * 8 * 640 * 2048 * 1024


def _instructions(text: str) -> list[str]:
    """A compiled module's instructions, in order, metadata apart (as
    tests/test_scopes.py reads them)."""
    return [re.sub(r", metadata=\{[^}]*\}", "", line)
            for line in text.splitlines()
            if re.match(r"\s*(ROOT )?%?[\w.\-]+ = ", line)]


def test_logits_only_model_traces_the_parents_step():
    """The auxiliary route costs a model that returns logits alone
    nothing: ``loss_and_grad`` and ``eval_grad`` lower to the program the
    parent's bodies (copied here verbatim) lower to, metadata apart."""
    tr = LocalTrainer(create_model("3dcnn_tiny", 1),
                      OptimConfig(precision="bf16_mixed", loss_scale=8.0),
                      1)
    assert not tr.has_aux
    x = jnp.zeros((4, 12, 14, 12), jnp.uint8)
    y = jnp.zeros((4,), jnp.int32)
    cs = tr.init_client_state(jax.random.key(0),
                              jnp.zeros((1, 12, 14, 12), jnp.float32))

    def parent_loss_and_grad(cs, x, y):
        rng, drng = jax.random.split(cs.rng)

        def f(params):
            out, bstats = tr._apply(params, cs.batch_stats, tr._prep(x),
                                    train=True, dropout_rng=drng)
            return tr._scaled(tr.loss(primary_logits(out), y)), bstats

        (loss, bstats), grads = jax.value_and_grad(
            f, has_aux=True)(cs.params)
        loss, grads = tr._unscaled(loss, grads)
        return loss, grads, bstats, rng

    def parent_eval_grad(params, batch_stats, x, y):
        def f(p):
            out, _ = tr._apply(p, batch_stats, tr._prep(x), train=False)
            return tr._scaled(tr.loss(primary_logits(out), y))

        grads = jax.grad(f)(params)
        return jax.tree.map(lambda g: g / tr._loss_scale, grads)

    def text(fn, *args):
        return _instructions(jax.jit(fn).lower(*args).compile().as_text())

    ours = text(tr.loss_and_grad, cs, x, y)
    assert len(ours) > 100
    assert ours == text(parent_loss_and_grad, cs, x, y)
    assert text(tr.eval_grad, cs.params, cs.batch_stats, x, y) == \
        text(parent_eval_grad, cs.params, cs.batch_stats, x, y)


def test_fedavg_round_reports_expert_load(tmp_path):
    """The small model through FedAvg's declared round in both
    placements: the round program returns ``expert_tokens`` summed over
    the round's real steps, and the folded round equals the stacked."""
    from neuroimagedisttraining_tpu.config import (
        DataConfig, ExperimentConfig, FedConfig,
    )
    from neuroimagedisttraining_tpu.data.federate import federate_cohort
    from neuroimagedisttraining_tpu.data.synthetic import (
        generate_synthetic_abcd,
    )
    from neuroimagedisttraining_tpu.engines import create_engine
    from neuroimagedisttraining_tpu.engines.fedavg import expert_load
    from neuroimagedisttraining_tpu.utils.logging import ExperimentLogger

    cohort = generate_synthetic_abcd(num_subjects=30, shape=SHAPE,
                                     num_sites=2, seed=0)
    cohort["site"] = np.repeat(np.arange(2), (20, 10)).astype(
        cohort["site"].dtype)
    outs = {}
    for tag, budget in (("stacked", 1 << 40), ("folded", 1)):
        cfg = ExperimentConfig(
            model="olmoe3d", num_classes=1, algorithm="fedavg",
            data=DataConfig(dataset="synthetic", partition_method="site"),
            optim=OptimConfig(lr=1e-2, batch_size=4, epochs=1),
            fed=FedConfig(client_num_in_total=2, comm_round=1),
            log_dir=str(tmp_path), tag=tag)
        tr = LocalTrainer(OLMoE3D(**SMALL), cfg.optim, 1)
        fed, _ = federate_cohort(cohort, partition_method="site", mesh=None)
        eng = create_engine("fedavg", cfg, fed, tr, mesh=None,
                            logger=ExperimentLogger(
                                str(tmp_path), "synthetic", cfg.identity(),
                                console=False))
        eng._fold_budget_bytes = budget
        assert eng.program.placement == tag
        gs = eng.init_global_state()
        sampled = eng.client_sampling(0)
        outs[tag] = eng._round_jit(
            gs.params, gs.batch_stats, eng.data, jnp.asarray(sampled),
            eng.per_client_rngs(0, sampled), eng.round_lr(0))
        n = np.asarray(eng.data.n_train)
    assert len(outs["folded"]) == 5  # params, stats, loss, n_bad, tokens
    tokens = np.asarray(outs["folded"][4])
    real_steps = int(np.ceil(n / 4).sum())
    assert tokens.shape == (8,)
    assert tokens.sum() == real_steps * 2 * (4 * 8)
    np.testing.assert_array_equal(tokens, outs["stacked"][4])
    for a, b in zip(jax.tree.leaves(outs["stacked"][0]),
                    jax.tree.leaves(outs["folded"][0])):
        np.testing.assert_allclose(a, b, rtol=1e-4, atol=1e-6)
    load = expert_load(tokens)
    assert load["tokens_routed"] == tokens.sum()
    assert load["expert_load_max_over_mean"] >= 1.0 \
        >= load["expert_load_min_over_mean"]


def test_folded_train_logs_expert_load_every_round(tmp_path):
    """Three rounds of the folded ``train()``, tracer armed: every
    round's ``round_log`` span carries that round's expert-load
    counters (the round program's ``expert_tokens`` output read where
    the loss is read), each accounting for the round's real steps."""
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

    cohort = generate_synthetic_abcd(num_subjects=30, shape=SHAPE,
                                     num_sites=2, seed=0)
    cohort["site"] = np.repeat(np.arange(2), (20, 10)).astype(
        cohort["site"].dtype)
    cfg = ExperimentConfig(
        model="olmoe3d", num_classes=1, algorithm="fedavg",
        data=DataConfig(dataset="synthetic", partition_method="site"),
        optim=OptimConfig(lr=1e-2, batch_size=4, epochs=1),
        fed=FedConfig(client_num_in_total=2, comm_round=3),
        log_dir=str(tmp_path), tag="fold3")
    tr = LocalTrainer(OLMoE3D(**SMALL), cfg.optim, 1)
    fed, _ = federate_cohort(cohort, partition_method="site", mesh=None)
    eng = create_engine("fedavg", cfg, fed, tr, mesh=None,
                        logger=ExperimentLogger(
                            str(tmp_path), "synthetic", cfg.identity(),
                            console=False))
    eng._fold_budget_bytes = 1
    obs_trace.arm()
    try:
        eng.train()
        logs = [e for e in obs_trace.TRACER.events()
                if e["ph"] == "X"
                and e["name"] == obs_names.SPAN_ROUND_LOG]
    finally:
        obs_trace.disarm()
    assert eng.program.placement == "folded"
    assert eng.program.dispatches == 3 and eng.program.built == 1
    assert [e["args"]["round"] for e in logs] == [0, 1, 2]
    real_steps = int(np.ceil(np.asarray(eng.data.n_train) / 4).sum())
    for e in logs:
        assert e["args"]["tokens_routed"] == real_steps * 2 * (4 * 8)
        assert e["args"]["expert_load_max_over_mean"] >= 1.0 \
            >= e["args"]["expert_load_min_over_mean"]


def test_olmoe3d_lowers_to_the_parents_text(monkeypatch):
    """PR 29 put two routers behind ``route``, a ``held`` window into
    ``grouped_matmul`` and the tiles behind ``gmm_tiling``, and moved
    ``RMSNorm``, ``patches`` and the read-out to models/tokens3d.py. The
    OLMoE block must not notice: its training step compiles to the program
    it compiles to with the parent's ``route`` and ``grouped_matmul``
    (copied here verbatim) in their place, metadata apart; and on a TPU
    the kernel is called as the parent called it (all 64 groups, tiles
    (512, 1024, 1024), no ``group_offset``)."""
    from neuroimagedisttraining_tpu.ops import moe

    def parent_route(logits, k):
        probs = jax.nn.softmax(logits.astype(jnp.float32), axis=-1)
        weights, experts = jax.lax.top_k(probs, k)
        return probs, weights, experts

    def parent_grouped_matmul(xs, w, group_sizes):
        return jax.lax.ragged_dot(xs, w, group_sizes)

    tr = _trainer()
    cs, (x, y) = _state(tr), _batch(0)

    def text():
        return _instructions(
            jax.jit(tr.loss_and_grad).lower(cs, x, y).compile().as_text())

    ours = text()
    assert len(ours) > 100
    with monkeypatch.context() as m:
        m.setattr(moe, "route", parent_route)
        m.setattr(moe, "grouped_matmul", parent_grouped_matmul)
        assert text() == ours

    from jax.experimental.pallas.ops.tpu.megablox import ops as megablox

    calls = []
    monkeypatch.setattr(jax, "default_backend", lambda: "tpu")
    monkeypatch.setattr(
        megablox, "gmm", lambda *a: calls.append(a) or jnp.zeros(
            (a[0].shape[0], a[1].shape[2]), a[3]))
    xs = jnp.zeros((81920, 2048), jnp.bfloat16)
    w = jnp.zeros((64, 2048, 1024), jnp.bfloat16)
    sizes = jnp.zeros((64,), jnp.int32)
    moe.grouped_matmul(xs, w, sizes)
    ((a_xs, a_w, a_sizes, dtype, tiling),) = calls  # five operands, no sixth
    assert a_xs is xs and a_w is w and a_sizes is sizes
    assert dtype == jnp.bfloat16 and tiling == (512, 1024, 1024)
