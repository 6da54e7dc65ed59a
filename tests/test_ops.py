"""Sparsity ops tests: top-k selection vs numpy, ERK sparsities, mask init
exact counts, fire/regrow semantics, SNIP identity, FLOPs counter."""

import functools
import re
from typing import Any, NamedTuple

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from neuroimagedisttraining_tpu.config import OptimConfig
from neuroimagedisttraining_tpu.core.trainer import LocalTrainer
from neuroimagedisttraining_tpu.models import Tiny3DCNN
from neuroimagedisttraining_tpu.ops import flops as F
from neuroimagedisttraining_tpu.ops import masks as M
from neuroimagedisttraining_tpu.ops import snip as S
from neuroimagedisttraining_tpu.ops.topk import kth_largest


def test_kth_largest_matches_numpy():
    rng = np.random.default_rng(0)
    x = jnp.asarray(rng.normal(size=100_003).astype(np.float32))
    for k in (1, 7, 1000, 50_000, 100_003):
        got = float(kth_largest(x, k))
        want = float(np.sort(np.asarray(x))[::-1][k - 1])
        assert got == pytest.approx(want, rel=1e-6), k
        # mask semantics: >= threshold keeps at least k
        assert int(np.sum(np.asarray(x) >= got)) >= k


def test_kth_largest_with_duplicates():
    x = jnp.asarray(np.array([1.0, 2.0, 2.0, 2.0, 3.0], np.float32))
    assert float(kth_largest(x, 2)) == 2.0
    assert float(kth_largest(x, 4)) == 2.0
    assert float(kth_largest(x, 5)) == 1.0


def test_kth_largest_nan_input_yields_nan_not_garbage():
    """VERDICT r4 #7: a single NaN score (one client's diverged loss) must
    not silently produce a wrong-but-finite threshold."""
    rng = np.random.default_rng(0)
    x = rng.normal(size=1000).astype(np.float32)
    x[137] = np.nan
    assert np.isnan(float(kth_largest(jnp.asarray(x), 10)))
    x[137] = np.inf
    assert np.isnan(float(kth_largest(jnp.asarray(x), 10)))


def test_mask_from_scores_raises_on_nonfinite():
    _, _, cs = _toy_trainer()
    rng = np.random.default_rng(0)
    scores = jax.tree.map(
        lambda p: jnp.asarray(np.abs(rng.normal(size=p.shape)), jnp.float32),
        cs.params)
    # poison ONE maskable leaf with a single NaN
    k = scores["f0"]["conv"]["kernel"]
    scores["f0"]["conv"]["kernel"] = k.at[(0,) * k.ndim].set(jnp.nan)
    with pytest.raises(FloatingPointError, match="non-finite"):
        S.mask_from_scores(scores, keep_ratio=0.3)


def test_mask_from_scores_raises_on_all_zero():
    """Degenerate phase-1 probe (zero gradients everywhere) must get its
    own diagnostic, not the non-finite one."""
    _, _, cs = _toy_trainer()
    scores = jax.tree.map(jnp.zeros_like, cs.params)
    with pytest.raises(FloatingPointError, match="identically zero"):
        S.mask_from_scores(scores, keep_ratio=0.3)


def _toy_trainer():
    model = Tiny3DCNN(num_classes=1)
    trainer = LocalTrainer(model, OptimConfig(batch_size=4), num_classes=1)
    cs = trainer.init_client_state(jax.random.key(0),
                                   jnp.zeros((1, 12, 12, 12, 1)))
    return model, trainer, cs


def test_erk_sparsities_hit_target_density():
    _, _, cs = _toy_trainer()
    for dr in (0.5, 0.2):
        sp = M.calculate_sparsities(cs.params, "ERK", dense_ratio=dr)
        shapes = {k: v for k, v in sp.items()}
        assert shapes  # found maskable kernels
        total = kept = 0
        flat = jax.tree_util.tree_leaves_with_path(cs.params)
        for path, leaf in flat:
            name = "/".join(str(getattr(p, "key", p)) for p in path)
            if name in sp:
                total += leaf.size
                kept += leaf.size * (1 - sp[name])
        assert kept / total == pytest.approx(dr, rel=0.05)
        assert all(0.0 <= s < 1.0 for s in sp.values())


def test_uniform_sparsities():
    _, _, cs = _toy_trainer()
    sp = M.calculate_sparsities(cs.params, "uniform", dense_ratio=0.3)
    assert all(s == pytest.approx(0.7) for s in sp.values())


def test_init_masks_exact_counts_and_ones_elsewhere():
    _, _, cs = _toy_trainer()
    sp = M.calculate_sparsities(cs.params, "uniform", dense_ratio=0.5)
    masks = M.init_masks(jax.random.key(1), cs.params, sp)
    flat = jax.tree_util.tree_leaves_with_path(masks)
    for path, m in flat:
        name = "/".join(str(getattr(p, "key", p)) for p in path)
        if name in sp:
            assert int(jnp.sum(m)) == int((1 - sp[name]) * m.size)
        else:
            assert bool(jnp.all(m == 1))


def test_fire_and_regrow_roundtrip_preserves_nnz():
    _, _, cs = _toy_trainer()
    sp = M.calculate_sparsities(cs.params, "uniform", dense_ratio=0.5)
    masks = M.init_masks(jax.random.key(1), cs.params, sp)
    grads = jax.tree.map(
        lambda p: jnp.asarray(
            np.random.default_rng(3).normal(size=p.shape), jnp.float32),
        cs.params)
    fired, num_remove = M.fire_mask(masks, cs.params, round_idx=0,
                                    comm_round=10, anneal_factor=0.5)
    # fire drops exactly num_remove per layer
    flat_m = jax.tree_util.tree_leaves_with_path(masks)
    for path, m in flat_m:
        name = "/".join(str(getattr(p, "key", p)) for p in path)
        if name in num_remove:
            before = int(jnp.sum(m))
            after = int(jnp.sum(M._by_name(fired, name)))
            assert before - after == int(num_remove[name])
    regrown = M.regrow_mask(fired, num_remove, grads)
    for path, m in flat_m:
        name = "/".join(str(getattr(p, "key", p)) for p in path)
        if name in num_remove:
            assert int(jnp.sum(M._by_name(regrown, name))) == int(jnp.sum(m))


def test_snip_score_equals_w_times_grad():
    _, trainer, cs = _toy_trainer()
    rng = np.random.default_rng(0)
    x = jnp.asarray(rng.normal(size=(4, 12, 12, 12)), jnp.float32)
    y = jnp.asarray(rng.integers(0, 2, size=4), jnp.int32)
    scores = S.snip_scores(trainer, cs, x, y)
    _, grads, _, _ = trainer.loss_and_grad(cs, x, y)
    w = cs.params["f0"]["conv"]["kernel"]
    g = grads["f0"]["conv"]["kernel"]
    np.testing.assert_allclose(np.asarray(scores["f0"]["conv"]["kernel"]),
                               np.abs(np.asarray(w) * np.asarray(g)),
                               rtol=1e-5)
    # bias leaves get zero scores
    assert bool(jnp.all(scores["f0"]["conv"]["bias"] == 0))


def test_mask_from_scores_keep_ratio():
    _, trainer, cs = _toy_trainer()
    rng = np.random.default_rng(0)
    scores = jax.tree.map(
        lambda p: jnp.asarray(np.abs(rng.normal(size=p.shape)), jnp.float32),
        cs.params)
    masks, thr = S.mask_from_scores(scores, keep_ratio=0.3)
    total = kept = 0
    flat = jax.tree_util.tree_leaves_with_path(masks)
    for path, m in flat:
        name = "/".join(str(getattr(p, "key", p)) for p in path)
        if M.is_weight_kernel(name, m):
            total += m.size
            kept += int(jnp.sum(m))
        else:
            assert bool(jnp.all(m == 1))
    assert kept == pytest.approx(0.3 * total, rel=0.01)


def test_flops_counter_conv_and_dense():
    model, trainer, cs = _toy_trainer()
    x = jnp.zeros((1, 12, 12, 12, 1))
    dense_flops = F.count_inference_flops(model, cs.params, x)
    # hand count (12^3 input): conv f0 VALID -> 10^3 spatial, kernel
    # 3^3*1*8=216 MACs/pos -> 2*216*1000; pool2 -> 5^3; conv f1 -> 3^3,
    # kernel 3^3*8*16=3456 -> 2*3456*27; pool2 -> 1^3, flatten 16;
    # fc1: 2*16*32; fc2: 2*32*1
    want = (2 * 216 * 1000) + (2 * 3456 * 27) + (2 * 16 * 32) + (2 * 32 * 1)
    assert dense_flops == pytest.approx(want, rel=1e-6)
    # sparsity-aware: half density halves kernel MACs
    dens = {k: 0.5 for k in F.densities_from_masks(
        jax.tree.map(jnp.ones_like, cs.params))}
    sparse_flops = F.count_inference_flops(model, cs.params, x,
                                           mask_density=dens)
    assert sparse_flops == pytest.approx(dense_flops / 2, rel=1e-6)
    assert F.count_training_flops_per_sample(model, cs.params, x) == \
        pytest.approx(3 * dense_flops)


def test_prep_channel_dim_gated_on_input_rank():
    """ADVICE r1: a 4-D [B,H,W,C] batch into a 2D model must NOT grow a
    5th dim; a 4-D [B,D,H,W] batch into a 3D model must."""
    from neuroimagedisttraining_tpu.models import CNNCifar

    t3 = LocalTrainer(Tiny3DCNN(num_classes=1), OptimConfig(), num_classes=1)
    assert t3._prep(jnp.zeros((2, 12, 12, 12))).shape == (2, 12, 12, 12, 1)
    assert t3._prep(jnp.zeros((2, 12, 12, 12, 1))).shape == (2, 12, 12, 12, 1)
    t2 = LocalTrainer(CNNCifar(num_classes=10), OptimConfig(), num_classes=10)
    assert t2._prep(jnp.zeros((2, 32, 32, 3))).shape == (2, 32, 32, 3)


def test_stratified_indices_balance_classes():
    y = jnp.asarray([0] * 90 + [1] * 10 + [0] * 28, jnp.int32)  # 28 padding
    idx = S._stratified_indices(jax.random.key(0), y, n_valid=100,
                                batch_size=2000)
    labels = np.asarray(y)[np.asarray(idx)]
    assert np.all(np.asarray(idx) < 100)          # never samples padding
    assert 0.4 < labels.mean() < 0.6              # ~50/50 despite 90/10 data


def test_kth_largest_rejects_bad_nbins():
    x = jnp.arange(512, dtype=jnp.float32)
    with pytest.raises(AssertionError):
        kth_largest(x, 5, nbins=100)


def test_fast_maxpool_matches_xla_fwd_and_bwd():
    """ops/pooling.py scatter-free non-overlapping max-pool backward ==
    XLA SelectAndScatter reference, fwd bitwise + bwd to f32 tolerance
    (ties are measure-zero on continuous inputs; see module docstring)."""
    import flax.linen as nn

    from neuroimagedisttraining_tpu.ops.pooling import max_pool_3d_nonoverlap

    x = jax.random.normal(jax.random.key(7), (2, 7, 9, 7, 3))
    np.testing.assert_array_equal(
        np.asarray(max_pool_3d_nonoverlap(x, 3)),
        np.asarray(nn.max_pool(x, (3, 3, 3), (3, 3, 3), "VALID")))

    def loss(pool):
        return lambda x: jnp.sum(pool(x) ** 2)

    g_fast = jax.grad(loss(lambda x: max_pool_3d_nonoverlap(x, 3)))(x)
    g_ref = jax.grad(loss(
        lambda x: nn.max_pool(x, (3, 3, 3), (3, 3, 3), "VALID")))(x)
    np.testing.assert_allclose(np.asarray(g_fast), np.asarray(g_ref),
                               atol=1e-6)


def _stem_grads(conv, x, w):
    """(y, dx, dw) of ``sum(sin(conv(x, w)))`` — a cotangent that differs
    at every output element."""
    y, vjp = jax.vjp(conv, x, w)
    return (y,) + vjp(jnp.cos(y.astype(jnp.float32)).astype(y.dtype))


def _stem_case(shape, c_out, dtype, seed=5):
    kx, kw = jax.random.split(jax.random.key(seed))
    x = jax.random.normal(kx, shape + (1,), jnp.float32).astype(dtype)
    w = (0.2 * jax.random.normal(kw, (5, 5, 5, 1, c_out))).astype(dtype)
    return x, w


def _rel(got, want):
    got, want = (np.asarray(a, np.float32) for a in (got, want))
    return float(np.max(np.abs(got - want)) / max(np.max(np.abs(want)), 1e-30))


@pytest.mark.parametrize("case", [
    "f32", "bf16", "even_extents", "one_window", "batch1_odd_channels",
    "vmap_distinct_kernels", "vmap_shared_input", "lax_map", "remat"])
def test_stemconv_grads_match_plain_conv(case):
    """``ops.stemconv``'s weight gradient (the stem's kernel gradient as a
    2-D convolution that contracts over (od, oh, (ow, n))) against
    ``jax.vjp`` of the plain 3-D convolution, called directly and through
    ``stem_conv3d``: unbatched (XLA's own form), under ``vmap`` with a
    kernel a client (the re-expressed form, a client at a time), under
    ``lax.map`` (``cohort_map``'s per-row loop) and inside
    ``jax.checkpoint``; the
    output and ``dx`` are the plain (transposed) convolution's. bf16
    operands, as ``bf16_mixed`` runs them, accumulate in f32 in both."""
    from neuroimagedisttraining_tpu.ops import stemconv as SC

    dtype = jnp.bfloat16 if case == "bf16" else jnp.float32
    tol = 2e-2 if case == "bf16" else 1e-4
    shape = {"even_extents": (2, 12, 14, 16),   # W even: equal parity halves,
             "one_window": (3, 5, 5, 5),        # ... one window more; 1x1x1
             "batch1_odd_channels": (1, 9, 7, 11)}.get(case, (2, 13, 15, 11))
    c_out = 3 if case == "batch1_odd_channels" else 8
    x, w = _stem_case(shape, c_out, dtype)
    xs = jnp.stack([x, 2.0 * x, x[::-1]])
    ws = jnp.stack([w, -w, 0.5 * w + 0.1])

    def both(run):
        return run(SC.stem_conv3d), run(SC._conv)

    if case == "vmap_distinct_kernels":   # the engines' client axis
        got, want = both(lambda f: jax.vmap(
            lambda a, b: _stem_grads(f, a, b))(xs, ws))
    elif case == "vmap_shared_input":     # only the cotangent is batched
        got, want = both(lambda f: jax.vmap(
            lambda b: _stem_grads(f, x, b))(ws))
    elif case == "lax_map":
        got, want = both(lambda f: jax.lax.map(
            lambda t: _stem_grads(f, *t), (xs, ws)))
    elif case == "remat":                 # --remat stem wraps the block
        got = jax.vmap(lambda a, b: _stem_grads(
            jax.checkpoint(SC.stem_conv3d), a, b))(xs, ws)
        want = jax.vmap(lambda a, b: _stem_grads(SC._conv, a, b))(xs, ws)
    else:
        got = jax.jit(lambda a, b: _stem_grads(SC.stem_conv3d, a, b))(x, w)
        want = _stem_grads(SC._conv, x, w)
        # the re-expressed contraction itself, on the same cotangent
        cot = jnp.cos(want[0].astype(jnp.float32)).astype(dtype)
        lanes = jax.jit(SC._dw_lanes)(x, cot)
        assert lanes.shape == w.shape and lanes.dtype == w.dtype
        assert _rel(lanes, want[2]) < tol, (case, _rel(lanes, want[2]))
    for name, g, r in zip(("y", "dx", "dw"), got, want):
        assert g.shape == r.shape and g.dtype == r.dtype, name
        assert _rel(g, r) < tol, (case, name, _rel(g, r))


def test_stemconv_form_follows_the_client_axis():
    """Which contraction runs is decided by what ``custom_vmap`` sees:
    unbatched, the backward holds the plain 3-D kernel gradient (the
    cohort-sharded round's program stays what autodiff gives); under
    ``vmap`` it holds the 2-D one whose batch is ``W_out * N``."""
    from neuroimagedisttraining_tpu.ops import stemconv as SC

    x, w = _stem_case((2, 13, 15, 11), 8, jnp.float32)

    def dw_of(conv):
        return lambda x, w: jax.grad(
            lambda w_: jnp.sum(conv(x, w_) ** 2))(w)

    def dw_forms(fn, *args):
        """(plain 3-D kernel gradients, 2-D re-expressed ones) lowered."""
        text = jax.jit(fn).lower(*args).as_text()
        convs = [ln for ln in text.splitlines() if "stablehlo.convolution" in ln]
        return (sum("[f, 0, 1, 2, b]x[i, 0, 1, 2, o]" in ln for ln in convs),
                sum("[0, 1, b, f]x[0, 1, i, o]" in ln for ln in convs))

    def program(conv):
        """Compiled instructions of the unbatched gradient, less metadata."""
        text = jax.jit(dw_of(conv)).lower(x, w).compile().as_text()
        return [re.sub(r", metadata=\{[^}]*\}", "", ln)
                for ln in text.splitlines()
                if re.match(r"\s+(ROOT )?%|ENTRY", ln)]

    dw = dw_of(SC.stem_conv3d)
    assert dw_forms(dw, x, w) == (1, 0)
    assert program(SC.stem_conv3d) == program(SC._conv)
    assert dw_forms(jax.vmap(dw), jnp.stack([x, x]), jnp.stack([w, w])) \
        == (0, 1)
    assert dw_forms(lambda a, b: jax.lax.map(lambda t: dw(*t), (a, b)),
                    x[None], w[None]) == (1, 0)


@pytest.mark.parametrize("remat", [False, True], ids=["plain", "nn_remat"])
def test_stem_block_keeps_the_conv_parameters(remat):
    """The stem block's parameter tree and output are ``nn.Conv``'s: the
    same ``conv/kernel [5,5,5,1,C]`` + ``conv/bias``, so checkpoints,
    SalientGrads' masks and the benchmark's float32 reference see one
    tensor; and its gradient through ``nn.remat`` equals the plain one."""
    import flax.linen as nn

    from neuroimagedisttraining_tpu.models.neuro3d import (
        ConvBNReLU3D,
        RematConvBNReLU3D,
    )

    x, _ = _stem_case((2, 13, 15, 13), 8, jnp.float32)
    blk = (RematConvBNReLU3D if remat else ConvBNReLU3D)(
        features=8, kernel=5, stride=2, pad=0)
    variables = blk.init(jax.random.key(6), x, False)
    conv = variables["params"]["conv"]
    assert set(conv) == {"kernel", "bias"}
    assert conv["kernel"].shape == (5, 5, 5, 1, 8)

    class Ref(nn.Module):  # the block as it was: nn.Conv + the same norm
        @nn.compact
        def __call__(self, x, train):
            x = nn.Conv(8, (5, 5, 5), strides=(2, 2, 2), padding="VALID",
                        name="conv")(x)
            x = nn.BatchNorm(use_running_average=not train, momentum=0.9,
                             epsilon=1e-5, name="bn")(x)
            return nn.relu(x)

    def loss(mod):
        def f(params):
            out, _ = mod.apply({**variables, "params": params}, x, True,
                               mutable=["batch_stats"])
            return jnp.sum(out ** 2)
        return f

    np.testing.assert_allclose(
        np.asarray(blk.apply(variables, x, False)),
        np.asarray(Ref().apply(variables, x, False)), atol=1e-5)
    got = jax.grad(loss(blk))(variables["params"])
    want = jax.grad(loss(Ref()))(variables["params"])
    # one scale for the tree: the conv bias's gradient is zero in exact
    # arithmetic (the norm subtracts the mean) and rounding noise in both
    scale = max(float(jnp.max(jnp.abs(r))) for r in jax.tree.leaves(want))
    for g, r in zip(jax.tree.leaves(got), jax.tree.leaves(want)):
        np.testing.assert_allclose(np.asarray(g), np.asarray(r),
                                   atol=1e-4 * scale)


@pytest.mark.parametrize("k,s,pad,c_in", [
    (5, 2, 0, 1), (5, 2, 0, 2), (5, 2, 1, 1), (5, 1, 0, 1), (3, 2, 0, 1),
    (3, 1, 0, 1)])
def test_stem_routing_is_decided_by_shape(k, s, pad, c_in):
    """``ConvBNReLU3D`` takes the re-expressed stem exactly when it can
    see ``(kernel, stride, pad, C_in) == (5, 2, 0, 1)``: no flag, no
    model name (Tiny3DCNN's k3 s1 stem keeps ``nn.Conv``)."""
    from neuroimagedisttraining_tpu.models.neuro3d import ConvBNReLU3D

    x = jnp.zeros((1, 9, 9, 9, c_in))
    blk = ConvBNReLU3D(features=4, kernel=k, stride=s, pad=pad)
    variables = blk.init(jax.random.key(0), x, False)
    text = str(jax.make_jaxpr(lambda v: blk.apply(v, x, False))(variables))
    assert ("custom_vjp_call" in text) == ((k, s, pad, c_in) == (5, 2, 0, 1))
    assert variables["params"]["conv"]["kernel"].shape == (k, k, k, c_in, 4)


class _StemGeometry(NamedTuple):
    """A first stage's static fields, as a model spells them."""

    kernel: int
    stride: int
    pad: int
    use_bias: bool
    norm_dtype: Any          # None: the compute dtype
    pool: tuple              # (window, stride, pad)

    def block(self, x, kernel, bias, *rest, train):
        from neuroimagedisttraining_tpu.ops import stemconv as SC

        return SC.stem_block(
            x, kernel, bias if self.use_bias else None, *rest, train=train,
            stride=self.stride, pad=self.pad, pool=self.pool,
            norm_dtype=self.norm_dtype)


#: AlexNet3D's f0 + pool0 and ResNet3D's conv1 / bn1 / pool0
_STEMS = {
    "alexnet": _StemGeometry(5, 2, 0, True, None, (3, 3, 0)),
    "resnet": _StemGeometry(3, 2, 3, False, jnp.float32, (3, 2, 1)),
}


class _PlainStem:
    """The stage as the models spelled it before ``stem_block``: flax's
    own ``nn.Conv`` + ``nn.BatchNorm`` + relu + ``nn.max_pool``. A stage
    without a bias ignores the one it is handed."""

    def __init__(self, features, geo, dtype):
        import flax.linen as nn

        window, stride, pad = geo.pool

        class Plain(nn.Module):
            @nn.compact
            def __call__(self, x, train):
                x = nn.Conv(features, (geo.kernel,) * 3,
                            strides=(geo.stride,) * 3,
                            padding=[(geo.pad, geo.pad)] * 3,
                            use_bias=geo.use_bias, dtype=dtype,
                            name="conv")(x)
                x = nn.BatchNorm(use_running_average=not train, momentum=0.9,
                                 epsilon=1e-5, dtype=geo.norm_dtype or dtype,
                                 name="bn")(x)
                return nn.max_pool(nn.relu(x), (window,) * 3,
                                   strides=(stride,) * 3,
                                   padding=[(pad, pad)] * 3)

        self.module = Plain()
        self.use_bias = geo.use_bias

    def __call__(self, x, kernel, bias, scale, offset, mean, var, *, train):
        conv = {"kernel": kernel, "bias": bias} if self.use_bias \
            else {"kernel": kernel}
        variables = {"params": {"conv": conv,
                                "bn": {"scale": scale, "bias": offset}},
                     "batch_stats": {"bn": {"mean": mean, "var": var}}}
        if not train:
            return self.module.apply(variables, x, False), mean, var
        out, new = self.module.apply(variables, x, True,
                                     mutable=["batch_stats"])
        # the batch statistics, out of the running update
        bn = new["batch_stats"]["bn"]
        return (out, (bn["mean"] - 0.9 * mean) / 0.1,
                (bn["var"] - 0.9 * var) / 0.1)


def _stem_block_case(clients, features, shape=(2, 17, 19, 21),
                     dtype=jnp.float32, seed=11, kernel=5):
    """Operands of ``clients`` stem blocks, a leading client axis on each:
    17 x 19 x 21 voxels leave 7 x 8 x 9 after the k5 convolution, so two
    of the k3 s3 pool's three extents have tail voxels outside the last
    window (11 x 12 x 13 after k3 s2 pad 3, 6 x 6 x 7 after its pool)."""
    ks = jax.random.split(jax.random.key(seed), 7)
    lead = (clients,)
    x = jax.random.normal(ks[0], lead + shape + (1,), jnp.float32)
    return (x.astype(dtype),
            0.2 * jax.random.normal(
                ks[1], lead + (kernel,) * 3 + (1, features)),
            0.1 * jax.random.normal(ks[2], lead + (features,)),
            1.0 + 0.1 * jax.random.normal(ks[3], lead + (features,)),
            0.1 * jax.random.normal(ks[4], lead + (features,)),
            0.1 * jax.random.normal(ks[5], lead + (features,)),
            1.0 + 0.1 * jax.random.uniform(ks[6], lead + (features,)))


def _stem_block_grads(block, train):
    """``(pooled, mean, var), (dkernel, dbias, dscale, doffset)`` of
    ``sum(sin(pooled)) + sum(cos(3 mean)) + sum(var^2)``: a cotangent
    that differs at every element, on each of the three results."""
    def loss(x, *params):
        out = block(x, *params, train=train)
        return (jnp.sum(jnp.sin(out[0].astype(jnp.float32)))
                + jnp.sum(jnp.cos(3.0 * out[1])) + jnp.sum(out[2] ** 2)), out

    def run(*args):
        (_, out), grads = jax.value_and_grad(
            loss, argnums=(1, 2, 3, 4), has_aux=True)(*args)
        return out, grads
    return run


def _tree_rel(got, want):
    """Largest error of a tuple of arrays over its largest entry: one
    scale for the parameters' gradients, because the conv bias's is zero
    in exact arithmetic (the norm subtracts the mean) and noise in both."""
    scale = max(float(np.max(np.abs(np.asarray(w, np.float32))))
                for w in want)
    return max(float(np.max(np.abs(np.asarray(g, np.float32)
                                   - np.asarray(w, np.float32))))
               for g, w in zip(got, want)) / scale


_STEM_BLOCK_CASES = [
    (f"c{c}-f{f}-{'train' if t else 'eval'}", c, f, t)
    for c in (1, 2, 3, 4) for f in (8, 64) for t in (True, False)]
#: the second geometry (ResNet3D's stage): 2 clients x 64 channels are
#: one 128-lane window of ``g``, 3 x 64 clamp the last
_RESNET_BLOCK_CASES = [
    (f"resnet-c{c}-f{f}-{'train' if t else 'eval'}", c, f, t)
    for c in (1, 2, 3) for f in (8, 64) for t in (True, False)] + [
    ("resnet-even_extent", 2, 8, True), ("resnet-even_extent", 2, 8, False),
    ("resnet-shared_parameters", 2, 8, False),
    ("resnet-shared_input", 2, 8, True), ("resnet-remat", 2, 8, True),
    ("resnet-lax_map", 2, 8, True), ("resnet-unbatched", 1, 8, True),
    ("resnet-unbatched", 1, 8, False), ("resnet-bf16", 2, 64, True),
    ("resnet-bf16", 3, 64, False), ("resnet-bf16_even_extent", 2, 64, True),
    ("resnet-bf16_unbatched", 1, 64, True),
    ("resnet-bf16_unbatched", 1, 64, False)]


@pytest.mark.parametrize("case,clients,features,train", _STEM_BLOCK_CASES + [
    ("even_extent", 2, 8, True), ("one_window", 3, 8, True),
    ("shared_parameters", 3, 8, False), ("shared_input", 2, 8, True),
    ("remat", 2, 8, True), ("lax_map", 2, 8, True),
    ("unbatched", 1, 8, True), ("bf16", 4, 64, True),
    ("bf16_unbatched", 1, 64, True)] + _RESNET_BLOCK_CASES,
    ids=lambda v: v if isinstance(v, str) else None)
def test_stem_block_matches_plain_composition(case, clients, features, train):
    """``ops.stemconv.stem_block`` against flax's ``nn.Conv`` +
    ``nn.BatchNorm`` + relu + ``nn.max_pool``, a client at a time, in the
    two geometries the models have (``_STEMS``; a ``resnet-`` case is k3
    stride 2 pad 3 without a bias, a float32 norm whatever the input's
    dtype, an overlapping k3 s2 pad 1 pool): the
    pooled output, the batch statistics and the gradients of kernel, bias,
    scale and offset, under a client-axis ``vmap`` (the merged layout) of
    1 to 4 clients and 8 or 64 channels (64: two clients share a 128-lane
    window of ``g``; 3 x 64: the last window is clamped), training and
    evaluating; with an even extent, a single pool window, parameters the
    ``vmap`` does not batch (evaluation of one model on every client's
    volumes), a shared input, ``jax.checkpoint``, and unbatched (directly
    and in ``lax.map``: the plain composition). float32 to 1e-4 of the
    largest entry: the sums run in another order. In bfloat16 the plain
    composition's own kernel gradient is a fifth off the float32 one at
    these shapes (a rounded activation flips a pool window's arg-max, and
    the norm's backward cancels), so the bound there is the plain
    composition's own distance from float32, not a constant."""
    geo = _STEMS["resnet" if case.startswith("resnet-") else "alexnet"]
    case = case.removeprefix("resnet-")
    half = case.startswith("bf16")
    shape = {"even_extent": (2, 18, 20, 16), "bf16_even_extent": (2, 18, 20, 16),
             "one_window": (3, 9, 9, 11)}.get(case, (2, 17, 19, 21))
    args = _stem_block_case(clients, features, shape,
                            jnp.bfloat16 if half else jnp.float32,
                            kernel=geo.kernel)
    mine = geo.block

    def plain(dtype):
        return _PlainStem(features, geo, dtype)

    got_fn = _stem_block_grads(mine, train)
    want_fn = _stem_block_grads(plain(args[0].dtype), train)
    axes = 0
    if case == "shared_parameters":
        axes = (0,) + (None,) * 6
        args = args[:1] + tuple(a[0] for a in args[1:])
    elif case == "shared_input":
        axes = (None,) + (0,) * 6
        args = (args[0][0],) + args[1:]
    if case.endswith("unbatched"):
        args = tuple(a[0] for a in args)
        got, want = jax.jit(got_fn)(*args), want_fn(*args)
    elif case == "lax_map":
        got = jax.jit(lambda *a: jax.lax.map(lambda t: got_fn(*t), a))(*args)
        want = jax.vmap(want_fn)(*args)
    elif case == "remat":
        got = jax.jit(jax.vmap(_stem_block_grads(
            lambda *a, train: jax.checkpoint(
                functools.partial(mine, train=train))(*a), train)))(*args)
        want = jax.vmap(want_fn)(*args)
    else:
        got = jax.jit(jax.vmap(got_fn, in_axes=axes))(*args)
        want = jax.vmap(want_fn, in_axes=axes)(*args)
    for name, g, w in zip(("pooled", "mean", "var"), got[0], want[0]):
        assert g.shape == w.shape and g.dtype == w.dtype, (case, name)
        assert _rel(g, w) < (2e-2 if half else 1e-4), (case, name, _rel(g, w))
    for g, w in zip(got[1], want[1]):
        assert g.shape == w.shape and g.dtype == w.dtype == jnp.float32
    if not half:
        assert _tree_rel(got[1], want[1]) < 1e-4, (
            case, _tree_rel(got[1], want[1]))
        return
    exact_args = (args[0].astype(jnp.float32),) + args[1:]
    exact_fn = _stem_block_grads(plain(jnp.float32), train)
    exact = (exact_fn if case == "bf16_unbatched"
             else jax.vmap(exact_fn))(*exact_args)[1]
    assert _tree_rel(got[1], exact) < max(
        2e-2, 1.25 * _tree_rel(want[1], exact)), (
        case, _tree_rel(got[1], exact), _tree_rel(want[1], exact))


@pytest.mark.parametrize("stem", sorted(_STEMS))
def test_stem_block_form_follows_the_client_axis(stem):
    """Which layout the stage computes in is decided by what
    ``custom_vmap`` sees, in either geometry. Unbatched (directly and in ``lax.map``: the mesh
    cell's rows) the compiled program holds the plain composition's
    convolutions, reductions, pool and pool backward, one for one, and no
    op carries the ``merged`` scope; under ``vmap`` the ops carry it, the forward is one
    ``feature_group_count = C`` convolution, and no tensor of the compiled
    program, forward or backward, has the pre-pool extent with the clients
    and the channels as axes of their own (``[..., C, F]`` or ``[C, ...,
    F]``): the split comes after the pool."""
    import collections

    from neuroimagedisttraining_tpu.obs import names
    from neuroimagedisttraining_tpu.ops import stemconv as SC

    clients, features, geo = 2, 8, _STEMS[stem]
    conv = SC._Window(geo.kernel, geo.stride, geo.pad)
    stack = _stem_block_case(clients, features, kernel=geo.kernel)
    one = tuple(a[0] for a in stack)

    def grads_of(block):
        def scoped(*a, train):  # as the model calls it: inside "stem"
            with jax.named_scope(names.SCOPE_STEM):
                return block(*a, train=train)
        return _stem_block_grads(scoped, True)

    def operations(fn, *args):
        """(opcode, result type) of the compiled program's convolutions,
        reductions, pool and pool backward: what costs. (The statistics'
        chain rule is spelled by hand in ``_block_vjp``, so a few
        per-channel vector ops differ from autodiff's spelling.)"""
        text = jax.jit(fn).lower(*args).compile().as_text()
        found = (re.match(r"\s+(?:ROOT )?%[\w.\-]+ = (\S+) ([\w\-]+)\(", ln)
                 for ln in text.splitlines())
        return collections.Counter(
            (m.group(2), m.group(1)) for m in found
            if m and m.group(2) in ("convolution", "reduce", "reduce-window",
                                    "select-and-scatter"))

    def plain_block(x, kernel, bias, *rest, train):
        stage = SC._Stage(train, conv, SC._Window(*geo.pool),
                          jnp.dtype(geo.norm_dtype or x.dtype))
        if not geo.use_bias:
            bias = jnp.zeros_like(bias)
        return SC._block(x, kernel, bias, *rest, stage=stage)[0]

    mine, plain = grads_of(geo.block), grads_of(plain_block)
    assert operations(mine, *one) == operations(plain, *one)
    assert len(operations(mine, *one)) >= 4

    def pool_backward_paths(fn, *args):
        """Paths of the pool's backward ops: the benchmark's rules spell a
        scope ``/pool0/``, and one entered inside an inner ``jax.vjp``
        would read ``transpose(jvp(pool0))``."""
        text = jax.jit(fn).lower(*args).compile().as_text()
        return [m.group(1) for m in re.finditer(
            r'op_name="([^"]*/select_and_scatter[^"]*)"', text)]

    for paths in (pool_backward_paths(mine, *one),
                  pool_backward_paths(jax.vmap(mine), *stack)):
        assert paths and all(f"/{names.SCOPE_POOL0}/" in p for p in paths)

    def lowered(fn, *args):
        return jax.jit(fn).lower(*args).as_text(debug_info=True)

    def looped(*a):
        return jax.lax.map(lambda t: mine(*t), a)

    scope = f"/{names.SCOPE_STEM_MERGED}/"
    assert scope not in lowered(mine, *one)
    assert scope not in lowered(looped, *stack)
    batched = lowered(jax.vmap(mine), *stack)
    assert scope in batched

    forward = functools.partial(geo.block, train=True)

    convs = [ln for ln in lowered(jax.vmap(forward), *stack).splitlines()
             if "stablehlo.convolution" in ln]
    assert len(convs) == 1 and f"feature_group_count = {clients}" in convs[0]
    # The two activations the backward reads cross from the forward in the
    # clients-first shape a batched value must have, and the backward
    # undoes it at once: the COMPILED program holds neither split shape.
    n, (od, oh, ow) = stack[0].shape[1], map(conv.out, stack[0].shape[2:5])
    split = (f"[{n},{od},{oh},{ow},{clients},{features}]",
             f"[{clients},{n},{od},{oh},{ow},{features}]")
    compiled = jax.jit(jax.vmap(mine)).lower(*stack).compile().as_text()
    assert f"[{n},{od},{oh},{ow},{clients * features}]" in compiled
    assert not any(t in compiled for t in split)
    # ... which the plain composition under the same vmap does have
    compiled = jax.jit(jax.vmap(plain)).lower(*stack).compile().as_text()
    assert any(t in compiled for t in split)


def test_fast_maxpool_tie_gradient_is_conserved():
    """Equal-split tie rule: a window of identical values (the post-ReLU
    all-zeros case) distributes the window's gradient, conserving total
    mass — sum(dx) == sum(g) regardless of tie count."""
    from neuroimagedisttraining_tpu.ops.pooling import max_pool_3d_nonoverlap

    x = jnp.zeros((1, 6, 6, 6, 2))  # every 3x3x3 window fully tied
    g = jax.grad(lambda x: jnp.sum(max_pool_3d_nonoverlap(x, 3) *
                                   jnp.arange(16.0).reshape(1, 2, 2, 2, 2)))(x)
    np.testing.assert_allclose(float(jnp.sum(g)), float(jnp.sum(jnp.arange(16.0))),
                               rtol=1e-6)
    # each element of a fully-tied window gets 1/27 of that window's grad
    np.testing.assert_allclose(np.asarray(g[0, :3, :3, :3, 0]),
                               np.full((3, 3, 3), 0.0), atol=1e-7)
    np.testing.assert_allclose(np.asarray(g[0, :3, :3, :3, 1]),
                               np.full((3, 3, 3), 1.0 / 27), rtol=1e-6)


@pytest.mark.parametrize("layout", ["replicated", "sharded"])
def test_mask_from_scores_ranks_on_one_device(monkeypatch, layout):
    """Scores that live on a whole mesh (what a sharded phase-1 hands
    over) are ranked on ONE device — a Pallas kernel cannot lower in a
    program that spans several — and the mask equals the single-device
    one."""
    from jax.sharding import NamedSharding, PartitionSpec

    from neuroimagedisttraining_tpu.parallel.mesh import make_mesh

    mesh = make_mesh()
    spec = PartitionSpec() if layout == "replicated" \
        else PartitionSpec(mesh.axis_names[0])
    scores = {"conv": {"kernel": jax.random.uniform(
        jax.random.key(3), (8 * 40, 16))}}
    want, want_thr = S.mask_from_scores(scores, keep_ratio=0.3)
    placed = jax.device_put(scores, NamedSharding(mesh, spec))
    seen = []
    real = S.kth_largest
    monkeypatch.setattr(
        S, "kth_largest",
        lambda x, k: (seen.append(len(x.sharding.device_set)),
                      real(x, k))[1])
    got, thr = S.mask_from_scores(placed, keep_ratio=0.3)
    assert seen == [1]
    assert float(thr) == float(want_thr)
    np.testing.assert_array_equal(np.asarray(got["conv"]["kernel"]),
                                  np.asarray(want["conv"]["kernel"]))
