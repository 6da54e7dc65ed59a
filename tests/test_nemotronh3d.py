"""``--model nemotronh3d`` against its plain reference (PR 29), on the CPU.

The program (models/nemotronh3d.py: a trunk built from a pattern string;
ops/ssd.py: the chunked scan; ops/moe.py: sigmoid routing and an expert
layer that holds a share of its experts) against
``benchmark/reference/nemotronh-abcd.py`` (token-by-token recurrence, a
loop over the held experts), on seeded random weights at a small size:
the published nine-layer pattern, hidden 64, 4 state-space heads of 8 in
2 groups of state 16, chunks of 4 (8 tokens a volume: two chunks), 32
experts of width 24 of which 2 are held, 3 a token, a shared expert of
48, 4 query heads over 2 key/value heads of 16. The chip comparison at
the published widths is the builder's (PERF.md).
"""

import importlib.util
import os

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from neuroimagedisttraining_tpu.config import OptimConfig
from neuroimagedisttraining_tpu.core.trainer import LocalTrainer
from neuroimagedisttraining_tpu.models import create_model, primary_logits
from neuroimagedisttraining_tpu.models.nemotronh3d import (
    PATTERN, HeldExperts, NemotronH3D, Widths,
)
from neuroimagedisttraining_tpu.models.tokens3d import relu2
from neuroimagedisttraining_tpu.ops import moe

ROOT = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
E, COUNT = 32, 2
SMALL = Widths(hidden_size=64, mamba_num_heads=4, mamba_head_dim=8,
               n_groups=2, ssm_state_size=16, chunk_size=4, num_experts=E,
               held=(6, COUNT), experts_per_token=3, expert_width=24,
               shared_expert_width=48, num_heads=4, num_kv_heads=2,
               head_dim=16, patch=8)
CFG = {"mamba_num_heads": 4, "mamba_head_dim": 8, "n_groups": 2,
       "ssm_state_size": 16, "num_heads": 4, "num_kv_heads": 2,
       "head_dim": 16, "experts_per_token": 3, "held": (6, COUNT),
       "routed_scaling_factor": 2.5, "rms_eps": 1e-5, "patch": 8}
B, SHAPE = 4, (16, 16, 16)

#: float32, program against reference: the same products summed in another
#: order (the chunked scan against the recurrence; a grouped matmul over
#: sorted rows against a masked loop; XLA's reduction trees) through nine
#: layers. Values are of order 0.01-1 and float32 carries 1.2e-7 a
#: product. Nothing else may differ: a reference computed with bfloat16
#: operands is off by 1e-3 and fails this (asserted below).
F32_RTOL, F32_ATOL = 5e-5, 2e-6
#: bf16_mixed against the float32 reference: 8 mantissa bits, 4e-3 a
#: rounding, through nine layers to a logit of order 0.1. Measured over
#: five seeds (the three here among them): logits 0.2e-3 to 2.1e-3
#: absolute. Float8 e4m3 operands in the reference, the nearest precision
#: below, are off by 6.1e-3 to 2.0e-2 over the same seeds. The bound lies
#: between the two readings, twice the largest of the first and two thirds
#: of the smallest of the second, which fails it (asserted below).
BF16_LOGIT_ATOL = 4e-3


def _load(name, path):
    spec = importlib.util.spec_from_file_location(name, path)
    mod = importlib.util.module_from_spec(spec)
    spec.loader.exec_module(mod)
    return mod


@pytest.fixture(scope="module")
def ref():
    return _load("ref_nemotronh", os.path.join(
        ROOT, "benchmark", "reference", "nemotronh-abcd.py"))


def _batch(seed):
    r = np.random.RandomState(seed)
    x = r.randint(0, 256, (B,) + SHAPE).astype(np.uint8)
    y = r.randint(0, 2, (B,)).astype(np.int32)
    return jnp.asarray(x), jnp.asarray(y)


def _trainer(dtype=jnp.float32, precision="fp32", widths=SMALL, **kw):
    model = NemotronH3D(dtype=dtype, widths=widths, **kw)
    return LocalTrainer(model, OptimConfig(precision=precision), 1)


def _state(tr, seed=0):
    cs = tr.init_client_state(jax.random.key(seed),
                              jnp.zeros((1,) + SHAPE, jnp.float32))
    # norm weights away from 1, a wider router and larger projections, so
    # that every term of every gradient is exercised, the routing is not
    # near-uniform and the held experts see rows
    r = np.random.RandomState(seed + 100)

    def jitter(path, x):
        name = jax.tree_util.keystr(path)
        if "norm" in name or "'D'" in name:
            return x + jnp.asarray(r.uniform(-0.3, 0.3, x.shape), x.dtype)
        if "router" in name:
            return x * 20.0
        if "patch_embed" in name and "kernel" in name:
            return x * 5.0
        if "conv_bias" in name:
            return x + jnp.asarray(r.uniform(-0.2, 0.2, x.shape), x.dtype)
        if name.endswith("['kernel']") or "'up'" in name or "'down'" in name:
            return x * 3.0
        return x
    return cs.replace(
        params=jax.tree_util.tree_map_with_path(jitter, cs.params))


def _program(tr, cs, x, y):
    """(logits, task loss, grads, aux, experts [L_E * N, k])."""
    out, inter = tr.model.apply(
        {"params": cs.params}, tr._prep(x), train=True,
        capture_intermediates=lambda m, _: isinstance(m, HeldExperts))
    leaves = jax.tree.leaves(inter["intermediates"],
                             is_leaf=lambda t: isinstance(t, tuple))
    experts = jnp.concatenate([leaf[0][1] for leaf in leaves])
    loss, grads, _, _ = tr.loss_and_grad(cs, x, y)
    return primary_logits(out), loss, grads, out[1], experts


@pytest.mark.parametrize("seed", [0, 1, 2])
def test_float32_logits_loss_and_every_gradient(ref, seed):
    tr = _trainer(remat_layers=False)
    cs, (x, y) = _state(tr, seed), _batch(seed)
    logits, loss, grads, aux, _ = _program(tr, cs, x, y)
    with jax.default_matmul_precision("highest"):
        want = ref.forward(cs.params, {}, x, cfg=CFG)
        task, g_ref = jax.value_and_grad(ref.training_loss)(
            cs.params, {}, x, y, cfg=CFG)
        low = ref.forward(cs.params, {}, x, cfg=CFG,
                          q=ref.ops.rounded(jnp.bfloat16))
    np.testing.assert_allclose(logits, want, rtol=F32_RTOL, atol=F32_ATOL)
    np.testing.assert_allclose(float(loss), float(task), rtol=F32_RTOL)
    assert float(aux["loss"]) == 0.0
    flat = jax.tree_util.tree_flatten_with_path(grads)[0]
    flat_ref = jax.tree.leaves(g_ref)
    # 4 M x 9 + 4 E x 6 + 1 * x 5 + patch embedding 2, final norm, head
    assert len(flat) == len(flat_ref) == 36 + 24 + 5 + 4
    for (path, g), gr in zip(flat, flat_ref):
        assert float(jnp.max(jnp.abs(gr))) > 0, jax.tree_util.keystr(path)
        np.testing.assert_allclose(
            g, gr, rtol=F32_RTOL * 10,
            atol=F32_ATOL * float(jnp.max(jnp.abs(gr))) * 20,
            err_msg=jax.tree_util.keystr(path))
    # the tolerance is about precision: a bfloat16 reference fails it
    with pytest.raises(AssertionError):
        np.testing.assert_allclose(low, want, rtol=F32_RTOL, atol=F32_ATOL)


def test_rematerialised_layers_give_the_same_logits_and_gradients():
    """``remat_layers`` (the model's default) changes what is kept, not
    what is computed."""
    cs, (x, y) = _state(_trainer()), _batch(5)
    outs = []
    for remat in (False, True):
        tr = _trainer(remat_layers=remat)
        loss, grads, _, _ = jax.jit(tr.loss_and_grad)(cs, x, y)
        outs.append((loss, grads))
    np.testing.assert_allclose(outs[0][0], outs[1][0], rtol=1e-6)
    for a, b_ in zip(jax.tree.leaves(outs[0][1]), jax.tree.leaves(outs[1][1])):
        np.testing.assert_allclose(a, b_, rtol=1e-4, atol=1e-7)


@pytest.mark.parametrize("seed", [0, 1, 2])
def test_bf16_mixed_against_the_float32_reference(ref, seed):
    tr = _trainer(jnp.bfloat16, "bf16_mixed")
    cs, (x, y) = _state(_trainer(), seed), _batch(seed)
    logits, loss, _, _, _ = _program(tr, cs, x, y)
    with jax.default_matmul_precision("highest"):
        want = ref.forward(cs.params, {}, x, cfg=CFG)
        fp8 = ref.forward(cs.params, {}, x, cfg=CFG,
                          q=ref.ops.rounded(jnp.float8_e4m3fn))
    assert np.isfinite(float(loss))
    assert float(jnp.max(jnp.abs(logits - want))) <= BF16_LOGIT_ATOL
    # one precision below the stated one is NOT inside the tolerance
    assert float(jnp.max(jnp.abs(fp8 - want))) > BF16_LOGIT_ATOL


@pytest.mark.parametrize("seed", [0, 1, 2])
def test_routing_agrees_and_counts_match(ref, seed):
    """Float32: every token's k experts equal the reference's in every
    expert layer, and ``expert_tokens`` is a bincount over all ``E`` of the
    reference's choices: the router keeps its published width whatever
    is held."""
    tr = _trainer()
    cs, (x, y) = _state(tr, seed), _batch(seed)
    _, _, _, aux, experts = _program(tr, cs, x, y)
    with jax.default_matmul_precision("highest"):
        _, e_ref = ref.trunk(cs.params, x, cfg=CFG)
    assert experts.shape == e_ref.shape == (4 * B * 8, 3)
    np.testing.assert_array_equal(np.sort(experts, -1), np.sort(e_ref, -1))
    counts = np.bincount(np.asarray(e_ref).ravel(), minlength=E)
    np.testing.assert_array_equal(aux["expert_tokens"], counts)
    assert counts.sum() == 4 * B * 8 * 3
    assert len(np.unique(np.asarray(e_ref))) > 8  # the routing is spread
    assert counts[6:8].sum() > 0  # and the held experts see rows


def _expert_layer(ref, seed, k=3):
    """One expert layer's operands at the small size: tokens, the router,
    ALL ``E`` experts' weights, the shared expert's."""
    r = np.random.RandomState(seed)
    f = lambda *s: jnp.asarray(r.randn(*s), jnp.float32)
    return {"m": f(2, 8, 64), "router": f(64, E) * 0.5,
            "up": f(E, 64, 24) * 0.2, "down": f(E, 24, 64) * 0.2,
            "shared": {"up": {"kernel": f(64, 48) * 0.2},
                       "down": {"kernel": f(48, 64) * 0.2}}}


#: (seed, shares whose rows pass their buffer of 6): at seed 2 the 48
#: assignments spread so that every share's rows fit (5 at most); at seeds
#: 0 and 1 one share receives 7 and takes a second window of its buffer
@pytest.mark.parametrize("seed,passes", [(0, 1), (1, 1), (2, 0)])
def test_the_sixteen_shares_add_up_to_the_uncut_layer(ref, seed, passes):
    """The routed parts that all 16 ``held`` windows of 2 experts give,
    plus the shared expert counted once, equal the uncut reference layer
    (all 32 experts held). Each share is the PROGRAM's expert layer, told
    which experts it holds and given their weights alone; it says which
    of its two paths computed it, and that follows the rows it received
    against its buffer's and nothing else."""
    t = _expert_layer(ref, seed)
    cfg = {**CFG, "held": (0, E)}
    with jax.default_matmul_precision("highest"):
        whole, _ = ref.experts(
            t["m"], {k: t[k] for k in ("router", "up", "down")},
            t["shared"], cfg, ref.ops.exact, None, "layer")
        shared = ref.ops.relu2_mlp(t["m"], t["shared"]["up"]["kernel"],
                                   t["shared"]["down"]["kernel"])
    capacity = moe.held_capacity(2 * 8 * 3, COUNT, E)
    assert capacity == 6  # twice the uniform share of 48 x 2 / 32
    total, rows, passed_shares = shared, 0, 0
    for first in range(0, E, COUNT):
        layer = HeldExperts(E, (first, COUNT), 3, 24, 2.5, 0.02)
        part, chosen, passed = layer.apply({"params": {
            "router": t["router"], "up": t["up"][first:first + COUNT],
            "down": t["down"][first:first + COUNT]}}, t["m"])
        held = (chosen >= first) & (chosen < first + COUNT)
        rows += int(held.sum())
        assert int(passed) == int(int(held.sum()) > capacity)
        passed_shares += int(passed)
        # a share whose experts nobody chose adds exactly nothing
        assert bool(held.any()) or float(jnp.max(jnp.abs(part))) == 0.0
        total = total + part
        # and the reference, given the same share, gives the same part
        with jax.default_matmul_precision("highest"):
            part_ref, _ = ref.experts(
                t["m"], {"router": t["router"],
                         "up": t["up"][first:first + COUNT],
                         "down": t["down"][first:first + COUNT]},
                t["shared"], {**CFG, "held": (first, COUNT)},
                ref.ops.exact, None, "layer")
        np.testing.assert_allclose(part, part_ref - shared, rtol=1e-4,
                                   atol=1e-5)
    assert rows == 2 * 8 * 3  # every assignment landed on exactly one share
    assert passed_shares == passes
    np.testing.assert_allclose(total, whole, rtol=1e-4, atol=1e-5)


def test_sigmoid_routing_by_hand():
    """Scores ``sigmoid(logits)``; the bias changes the CHOICE and not the
    weight; weights are renormalised over the chosen and scaled."""
    logits = jnp.log(jnp.asarray([[0.8, 0.6, 0.5, 0.2]]) /
                     (1 - jnp.asarray([[0.8, 0.6, 0.5, 0.2]])))
    s, w, e = moe.route(logits, 2, scoring="sigmoid", scale=2.5)
    np.testing.assert_allclose(s, [[0.8, 0.6, 0.5, 0.2]], rtol=1e-6)
    np.testing.assert_array_equal(e, [[0, 1]])
    np.testing.assert_allclose(w, [[2.5 * 0.8 / 1.4, 2.5 * 0.6 / 1.4]],
                               rtol=1e-6)
    bias = jnp.asarray([0.0, 0.0, 0.0, 0.5])  # lifts expert 3 over 1 and 2
    _, wb, eb = moe.route(logits, 2, scoring="sigmoid", bias=bias, scale=2.5)
    np.testing.assert_array_equal(eb, [[0, 3]])
    # the weight is the SCORE's share (0.2), not the biased 0.7
    np.testing.assert_allclose(wb, [[2.5 * 0.8 / 1.0, 2.5 * 0.2 / 1.0]],
                               rtol=1e-6)
    # the softmax router is what it was
    p, ws, es = moe.route(logits, 2)
    np.testing.assert_allclose(p, jax.nn.softmax(logits), rtol=1e-6)
    np.testing.assert_allclose(ws, jnp.take_along_axis(p, es, -1))
    with pytest.raises(ValueError, match="unknown scoring"):
        moe.route(logits, 2, scoring="tanh")


@pytest.mark.parametrize("planted,passed", [
    ({6: 0.5, 7: 0.4, 9: 0.3}, 4), ({9: 0.5, 10: 0.4}, 0)],
    ids=["here", "elsewhere"])
def test_no_row_is_lost_when_every_token_comes_here(ref, planted, passed):
    """A planted router whose three largest columns are the held experts 6
    and 7 and the unheld 9 sends about half of all tokens here, five
    times the uniform share of 3 x 2 / 32 of the assignments: they pass
    the buffer (twice the uniform share) in each of the four expert
    layers, which says so and takes as many windows as they need; nothing
    is dropped and the output is still the reference's. With two unheld
    columns planted instead, a token's third choice falls where a small
    drawn term puts it, a few land here, the held runs are moved alone,
    and the output is the reference's as well."""
    tr = _trainer()
    cs, (x, y) = _state(tr), _batch(7)
    params = jax.tree.map(lambda a: a, cs.params)
    router = -1.0 + 0.05 * jnp.asarray(
        np.random.RandomState(5).randn(64, E), jnp.float32)
    for column, value in planted.items():
        router = router.at[:, column].set(value)
    for i, kind in enumerate(PATTERN):
        if kind == "E":
            params[f"layers_{i}"]["mixer"]["router"] = router
            # all-positive normalised inputs: column sums decide the choice
            params[f"layers_{i}"]["norm"]["weight"] = jnp.ones((64,))
    out = tr.model.apply({"params": params}, tr._prep(x), train=True)
    tokens = np.asarray(out[1]["expert_tokens"])
    T = B * 8
    with jax.default_matmul_precision("highest"):
        want, e_ref = ref.trunk(params, x, cfg=CFG)
    np.testing.assert_array_equal(
        tokens, np.bincount(np.asarray(e_ref).ravel(), minlength=E))
    assert tokens.sum() == 4 * 3 * T
    uniform = 4 * 3 * T * COUNT / E
    assert int(out[1]["held_overflow_calls"]) == passed
    if passed:
        assert tokens[6:8].sum() >= 4 * uniform
    else:
        assert 0 < tokens[6:8].sum() <= 2 * uniform
    np.testing.assert_allclose(out[0], want, rtol=F32_RTOL, atol=F32_ATOL)


def _held_part_by_hand(x, weights, experts, up, down, first):
    """What the held experts add to each token, every token through
    every held expert and a mask: no sort, no buffer."""
    y = jnp.zeros_like(x)
    for i in range(up.shape[0]):
        w = jnp.sum(jnp.where(experts == first + i, weights, 0.0), axis=1)
        y = y + w[:, None] * (relu2(x @ up[i]) @ down[i])
    return y


#: 64 tokens x 3 slots = 192 rows, 2 of 32 experts held: a buffer of 24.
#: (label, first held expert, rows planted on the held experts, passed).
#: The other rows go to experts 0-2 (32 tokens) and 8-10 (32 tokens), so
#: the held runs start in the middle of the sort unless they end it
PLANTED = [("none_here", 6, 0, 0), ("a_few", 6, 5, 0),
           ("buffer_full", 6, 24, 0), ("one_row_over", 6, 25, 1),
           ("every_token", 6, 64, 1), ("run_ends_the_sort", 30, 5, 0),
           ("run_ends_the_sort_full", 30, 24, 0),
           ("run_ends_the_sort_over", 30, 25, 1)]


@pytest.mark.parametrize("label,first,n,passed", PLANTED,
                         ids=[c[0] for c in PLANTED])
def test_held_runs_window_by_window_by_hand(label, first, n, passed):
    """``held_expert_rows`` on planted routings, forward and every
    gradient, against the masked loop: a buffer exactly full is still one
    window, one row more is two, every token here is three, and each is
    the dropless answer; a held window that ends the layer (``first + count
    == E``) has its run at the end of the sort, where the buffer's window
    passes the last row and must not be moved back into another expert's
    rows."""
    T, k, H, W = 64, 3, 16, 24
    r = np.random.RandomState(n)
    f = lambda *shape: jnp.asarray(r.randn(*shape), jnp.float32)
    x, up, down = f(T, H), f(COUNT, H, W) * 0.3, f(COUNT, W, H) * 0.3
    weights = jnp.asarray(r.uniform(0.1, 1.0, (T, k)), jnp.float32)
    experts = np.where(np.arange(T)[:, None] < 32, [0, 1, 2], [8, 9, 10])
    experts[:n, 0] = first + np.arange(n) % COUNT
    experts = jnp.asarray(experts, jnp.int32)
    assert moe.held_capacity(T * k, COUNT, E) == 24
    assert int(moe.rows_held(experts, first, COUNT).sum()) == n

    def program(x, weights, up, down):
        return moe.held_expert_rows(x, weights, experts, up, down, E, first,
                                    relu2)

    def by_hand(x, weights, up, down):
        return _held_part_by_hand(x, weights, experts, up, down, first)

    with jax.default_matmul_precision("highest"):
        got, over = jax.jit(program)(x, weights, up, down)
        want = by_hand(x, weights, up, down)
        tangent = f(T, H)
        grads = jax.jit(jax.grad(
            lambda *a: jnp.sum(program(*a)[0] * tangent),
            argnums=(0, 1, 2, 3)))(x, weights, up, down)
        grads_want = jax.grad(lambda *a: jnp.sum(by_hand(*a) * tangent),
                              argnums=(0, 1, 2, 3))(x, weights, up, down)
    assert int(over) == passed
    np.testing.assert_allclose(got, want, rtol=1e-5, atol=1e-5)
    for g, w, name in zip(grads, grads_want, ("x", "weights", "up", "down")):
        np.testing.assert_allclose(g, w, rtol=1e-4, atol=1e-5, err_msg=name)


@pytest.mark.parametrize("seed", [0, 1])
def test_the_layer_by_either_path_has_the_same_gradients(monkeypatch, seed):
    """The expert layer at 512 tokens, where the drawn routing's held rows
    (about 96 of 1,536) fit the buffer of 192: its output and the
    gradient of every leaf (the tokens, the router through the weights,
    both matrices) equal those of the same layer traced with the full
    sort alone."""
    r = np.random.RandomState(seed)
    f = lambda *shape: jnp.asarray(r.randn(*shape), jnp.float32)
    m, tangent = f(4, 128, 64), f(4, 128, 64)
    params = {"router": f(64, E) * 0.5, "up": f(COUNT, 64, 24) * 0.2,
              "down": f(COUNT, 24, 64) * 0.2}
    layer = HeldExperts(E, (6, COUNT), 3, 24, 2.5, 0.02)

    def run():
        def loss(params, m):
            y, _, passed = layer.apply({"params": params}, m)
            return jnp.sum(y * tangent), (y, passed)
        with jax.default_matmul_precision("highest"):
            return jax.jit(jax.value_and_grad(loss, argnums=(0, 1),
                                              has_aux=True))(params, m)

    (_, (y, passed)), grads = run()
    assert moe.held_capacity(512 * 3, COUNT, E) == 192 and int(passed) == 0
    monkeypatch.setattr(moe, "held_capacity", lambda *a: None)
    (_, (y_full, _)), grads_full = run()
    np.testing.assert_allclose(y, y_full, rtol=1e-5, atol=1e-5)
    assert float(jnp.max(jnp.abs(grads[0]["router"]))) > 0
    # the same float32 products summed in another order: 1e-6 of a leaf's
    # largest entry
    jax.tree.map(lambda a, b: np.testing.assert_allclose(
        a, b, rtol=1e-4, atol=1e-6 * float(jnp.max(jnp.abs(b)))),
        grads, grads_full)


def test_held_window_of_the_grouped_matmul():
    """``grouped_matmul`` with fewer weights than groups: the rows of the
    held experts are multiplied, every other row comes out zero, wherever
    the window starts; a window that leaves the layer is refused."""
    r = np.random.RandomState(0)
    M, K, N, first, count = 40, 8, 5, 3, 2
    ids = np.sort(r.randint(0, 8, M))
    sizes = jnp.asarray(np.bincount(ids, minlength=8), jnp.int32)
    xs = jnp.asarray(r.randn(M, K), jnp.float32)
    w = jnp.asarray(r.randn(8, K, N), jnp.float32)
    full = moe.grouped_matmul(xs, w, sizes)
    for first in (0, 3, 6):
        got = moe.grouped_matmul(xs, w[first:first + count], sizes, first)
        inside = (ids >= first) & (ids < first + count)
        np.testing.assert_allclose(got[inside], full[inside], rtol=1e-6)
        assert float(jnp.max(jnp.abs(got[~inside]))) == 0.0
    with pytest.raises(ValueError, match="not among"):
        moe.grouped_matmul(xs, w[:2], sizes, 7)


@pytest.mark.parametrize("m,k,n,rows,tile", [
    (81920, 2048, 1024, 512, 1024), (81920, 1024, 2048, 512, 1024),  # OLMoE
    (5120, 2048, 1024, 512, 1024),   # its one-volume initialisation
    (61440, 2688, 1856, 512, 384), (61440, 1856, 2688, 512, 384),
    (3840, 2688, 1856, 256, 384),    # Nemotron-H's one-volume initialisation
    (384, 512, 512, 128, 512),
    # Moonlight (PR 47): 1408 = 11 x 128 has no wider divisor, and the
    # least-padding rule gave it tiles of 128 and its neighbour 256
    (14848, 2048, 2816, 512, 1024), (14848, 2816, 2048, 512, 1024),
    (14848, 1408, 2048, 512, 512), (14848, 2048, 1408, 512, 512),
    (29184, 2048, 2816, 512, 1024),  # evaluation at 4 rows, and the
    (29184, 1408, 2048, 512, 512),   # one-volume initialisation's full sort
    (19456, 2048, 2048, 512, 1024), (19456, 1024, 2048, 512, 1024),  # Trinity
    (512, 136, 1024, 512, 256)])     # every tile pads 136 by 88% or more
def test_gmm_tiles_follow_the_operand_shapes(m, k, n, rows, tile):
    assert moe.gmm_tiling(m, k, n) == (rows, tile, tile)


def test_gmm_tile_is_the_widest_that_pads_within_a_tenth():
    """Over every ``(k, n)`` of multiples of 8 from 128 to 4096: the tile
    is a multiple of 128 lanes and at most 1024, it pads neither
    dimension by more than a tenth wherever any tile can do that, and no
    wider tile can."""
    def pads_within_a_tenth(t, k, n):
        return all(10 * -(-d // t) * t <= 11 * d for d in (k, n))

    tiles = range(128, 1025, 128)
    for k in range(128, 4097, 8):
        for n in range(128, 4097, 8):
            _, t, tn = moe.gmm_tiling(512, k, n)
            assert t == tn and t in tiles, (k, n, t)
            fit = [u for u in tiles if pads_within_a_tenth(u, k, n)]
            if fit:
                assert t == fit[-1], (k, n, t)


def test_gmm_kernel_at_a_ragged_wide_tile():
    """``megablox.gmm`` in Pallas' interpreter at a tile that divides
    neither matrix dimension (384 x 640 at 256: what 1408 x 2048 at 512
    and 2048 x 2816 at 1024 are on the chip), three groups that do not
    fill the rows: forward, ``dx`` and ``dW`` are ``jax.lax.ragged_dot``'s
    over the rows the groups reach. The rows past them are whatever the
    memory held (``ops/moe.py`` ``_window_experts`` masks them)."""
    from jax.experimental.pallas.ops.tpu.megablox import ops as megablox

    r = np.random.RandomState(0)
    M, K, N, tile = 256, 384, 640, 256
    sizes = jnp.asarray([70, 100, 40], jnp.int32)
    reach = int(jnp.sum(sizes))
    xs = jnp.asarray(r.randn(M, K), jnp.float32)
    w = jnp.asarray(r.randn(3, K, N) / np.sqrt(K), jnp.float32)
    g = jnp.asarray(r.randn(M, N), jnp.float32)
    valid = (jnp.arange(M) < reach)[:, None]

    def kernel(xs, w):
        ys = megablox.gmm(xs, w, sizes, jnp.float32, (128, tile, tile),
                          None, None, False, True)  # interpret
        return jnp.where(valid, ys, 0)

    def plain(xs, w):
        rest = jnp.zeros((1,) + w.shape[1:], w.dtype)
        ys = jax.lax.ragged_dot(xs, jnp.concatenate([w, rest]),
                                jnp.append(sizes, M - reach))
        return jnp.where(valid, ys, 0)

    y, transpose = jax.vjp(kernel, xs, w)
    y0, transpose0 = jax.vjp(plain, xs, w)
    (dx, dw), (dx0, dw0) = transpose(g), transpose0(g)
    # float32 sums of 384, 640 and up to 100 products in another order
    np.testing.assert_allclose(y, y0, rtol=1e-5, atol=2e-5)
    np.testing.assert_allclose(dx[:reach], dx0[:reach], rtol=1e-5, atol=2e-5)
    np.testing.assert_allclose(dw, dw0, rtol=1e-5, atol=2e-4)


@pytest.mark.parametrize("m,k,n,words", [
    (61440, 64, 1024, "no megablox tile fits"),
    (61440, 2688, 100, "no megablox tile fits"),
    (61440, 2050, 1024, "no megablox tile fits"),
    (3800, 2688, 1856, "not a multiple of any")])
def test_gmm_refuses_a_shape_no_tile_fits(m, k, n, words):
    with pytest.raises(ValueError, match=words):
        moe.gmm_tiling(m, k, n)


def test_published_widths_and_work(ref):
    """``create_model("nemotronh3d")`` is the published trunk cut to this
    chip: the parameter shapes, 589,897,984 parameters, and the tape."""
    from benchmark import flops

    model = create_model("nemotronh3d", 1, remat="stem")  # --remat: ignored
    assert model.remat_layers and model.held_experts == (0, 8)
    assert model.widths.pattern == ref.PATTERN == "MEMEM*EME"
    shapes = jax.eval_shape(
        lambda: model.init(jax.random.key(0),
                           jnp.zeros((1, 121, 145, 121, 1))))["params"]
    count = lambda t: sum(int(np.prod(x.shape)) for x in jax.tree.leaves(t))
    assert count(shapes["layers_0"]) == 38_744_896  # M
    assert count(shapes["layers_1"]) == 100_125_312  # E, 8 held
    assert count(shapes["layers_5"]) == 23_399_040  # *
    m = shapes["layers_0"]["mixer"]
    assert m["in_proj"]["kernel"].shape == (2688, 10304)
    assert m["conv_kernel"].shape == (4, 6144)
    e = shapes["layers_1"]["mixer"]
    assert e["router"].shape == (2688, 128)
    assert e["up"].shape == (8, 2688, 1856)
    assert e["down"].shape == (8, 1856, 2688)
    assert shapes["layers_1"]["shared"]["up"]["kernel"].shape == (2688, 3712)
    a = shapes["layers_5"]["mixer"]
    assert a["q_proj"]["kernel"].shape == (2688, 4096)
    assert a["k_proj"]["kernel"].shape == a["v_proj"]["kernel"].shape \
        == (2688, 256)
    assert count(shapes) == 589_897_984
    tape = flops.record_tape(ref.forward, shapes, {}, (121, 145, 121))
    assert tape == ref.published_tape()
    assert abs(flops.forward_flops(tape) / 1e9 - 374.17) < 0.01
    assert abs(flops.training_flops_per_sample(tape) / 1e12 - 1.1225) < 1e-4
    # two matrices of 2688 x 1856 a row, four expert layers
    assert ref.expert_flops_per_row(tape) == 2 * 2 * 2688 * 1856
    assert ref.expert_layers(tape) == 4
    assert abs(ref.ssd_flops_per_sample(tape) / 1e9 - 7.06) < 0.01


def test_initialisation_of_the_state_space_layer():
    """``dt`` starts in ``[time_step_min, time_step_max]`` through the
    inverse softplus, ``A = -(1..H)``, ``D = 1``."""
    tr = _trainer()
    m = tr.init_client_state(
        jax.random.key(0), jnp.zeros((1,) + SHAPE, jnp.float32)
    ).params["layers_0"]["mixer"]
    dt = jax.nn.softplus(m["dt_bias"])
    assert float(dt.min()) >= 1e-3 * 0.999 and float(dt.max()) <= 0.1 * 1.001
    np.testing.assert_allclose(jnp.exp(m["A_log"]), np.arange(1, 5),
                               rtol=1e-6)
    np.testing.assert_array_equal(m["D"], np.ones(4))


def test_an_unknown_kind_in_the_pattern_is_refused():
    tr = _trainer(widths=Widths(**{**SMALL.__dict__, "pattern": "MX"}))
    with pytest.raises(ValueError, match="unknown layer kind 'X'"):
        tr.init_client_state(jax.random.key(0),
                             jnp.zeros((1,) + SHAPE, jnp.float32))


def test_a_second_eager_initialisation_compiles_nothing():
    """The trainer initialises its model eagerly, and the engine does so
    at every ``train()``: a scan body defined inside ``ssd_chunked``
    compiled the scan anew each time (four compilations inside the
    benchmark's measured window; my chip run, PR 29), as a layer
    rematerialised while initialising would. After the first
    initialisation a second compiles nothing."""
    from jax import monitoring

    compiles = []
    monitoring.register_event_duration_secs_listener(
        lambda event, secs, **_: compiles.append(event) if event ==
        "/jax/core/compile/backend_compile_duration" else None)
    tr = _trainer()
    init = lambda: tr.init_client_state(
        jax.random.key(0), jnp.zeros((1,) + SHAPE, jnp.float32))
    init()
    before = len(compiles)
    init()
    assert len(compiles) == before
