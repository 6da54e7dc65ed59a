"""Sparsity ops tests: top-k selection vs numpy, ERK sparsities, mask init
exact counts, fire/regrow semantics, SNIP identity, FLOPs counter."""

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


def test_stemconv_pallas_dw_matches_xla():
    """ops/stemconv.py split-K weight-gradient == XLA kernel-grad
    (interpret mode exercises the real kernel grid incl. the ragged-K
    tail; shapes sized so R > one 8192 block)."""
    from neuroimagedisttraining_tpu.ops import stemconv as SC

    kx, kg = jax.random.split(jax.random.key(3))
    x = jax.random.normal(kx, (4, 29, 31, 29, 1), jnp.float32)
    w = jax.random.normal(kg, (5, 5, 5, 1, 64), jnp.float32)
    g = jax.random.normal(jax.random.key(4), SC._conv(x, w).shape,
                          jnp.float32)
    dw_ref = np.asarray(SC._dw_reference(x, g))
    dw_pal = np.asarray(SC._dw_pallas(x, g, interpret=True))
    err = np.max(np.abs(dw_pal - dw_ref)) / np.max(np.abs(dw_ref))
    assert err < 2e-2, err  # bf16 products, f32 accumulation


def test_stemconv_custom_vjp_grads(monkeypatch):
    """stem_conv3d's custom VJP returns the same (dx, dw) as plain XLA
    autodiff (the CPU fallback path IS autodiff for dw; dx always the
    transposed conv), and the NIDT_FAST_STEM=1 module keeps the nn.Conv
    param tree."""
    from neuroimagedisttraining_tpu.models.neuro3d import ConvBNReLU3D
    from neuroimagedisttraining_tpu.ops import stemconv as SC

    kx, kw = jax.random.split(jax.random.key(5))
    x = jax.random.normal(kx, (2, 13, 15, 13, 1), jnp.float32)
    w = jax.random.normal(kw, (5, 5, 5, 1, 8), jnp.float32)

    def loss(f):
        return lambda x, w: jnp.sum(f(x, w) ** 2)

    gx, gw = jax.grad(loss(SC.stem_conv3d), argnums=(0, 1))(x, w)
    rx, rw = jax.grad(loss(SC._conv), argnums=(0, 1))(x, w)
    np.testing.assert_allclose(np.asarray(gx), np.asarray(rx), atol=1e-4)
    np.testing.assert_allclose(np.asarray(gw), np.asarray(rw), atol=1e-4)

    blk = ConvBNReLU3D(features=8, kernel=5, stride=2, pad=0)
    monkeypatch.setenv("NIDT_FAST_STEM", "1")
    params = blk.init(jax.random.key(6), x, train=False)
    assert set(params["params"]["conv"]) == {"kernel", "bias"}
    out_fast = blk.apply(params, x, train=False)  # env read at apply time
    monkeypatch.delenv("NIDT_FAST_STEM")
    out_ref = blk.apply(params, x, train=False)
    np.testing.assert_allclose(np.asarray(out_fast), np.asarray(out_ref),
                               atol=1e-5)


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
