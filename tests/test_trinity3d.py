"""``--model trinity3d`` against its plain reference (PR 44), on the CPU.

The program (models/trinity3d.py: grouped-query attention with per-head QK
norms and a sigmoid gate on its output, over a sliding window in some
layers and the whole causal triangle in others, the rotary embedding in
the sliding ones alone; four norms a layer; a leading dense layer;
sigmoid-routed experts of which this chip holds a share, beside a shared
expert) against ``benchmark/reference/trinity-abcd.py`` (one dense mask a
layer kind, the key heads repeated over their groups, a loop over the held
experts), on seeded random weights at a small size: 1 dense + 4 expert
layers of kinds s, s, f, s, s, hidden 64, 8 heads of 16 on 2 key heads, a
window of 32, feed-forward 96, 16 experts of width 24 with 4 a token and 4
held, blocks of 32 queries, patch 4. Volumes of 16 x 4k x 4 voxels are ``T
= 4 k`` tokens, of 12 x 4k x 4 ``3 k``: 24 (inside one window), 33 (one key
past it), 76 (2.4 windows). The chip comparison at the published widths is the builder's
(PERF.md).
"""

import dataclasses
import os

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from neuroimagedisttraining_tpu.models import create_model, tokens3d
from neuroimagedisttraining_tpu.models.trinity3d import (
    FULL, SLIDING, GatedAttention, HeldExperts, Layer, Trinity3D, Widths,
)
from neuroimagedisttraining_tpu.ops import attention
from tests.test_moonlight3d import _all_eqns, _jitter, _load

ROOT = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
HEADS, KV, D, W, E, K, BLOCK = 8, 2, 16, 32, 16, 4, 32
KINDS = (SLIDING, SLIDING, FULL, SLIDING, SLIDING)
SMALL = Widths(layer_types=KINDS, dense_layers=1, hidden_size=64,
               heads=HEADS, kv_heads=KV, head_dim=D, sliding_window=W,
               intermediate_size=96, num_experts=E, held=(0, 4),
               experts_per_token=K, expert_width=24, block=BLOCK, patch=4)
CFG = {"heads": HEADS, "kv_heads": KV, "experts_per_token": K,
       "held": (0, 4), "route_scale": 2.826, "rope_theta": 1e4,
       "rms_eps": 1e-5, "patch": 4, "sliding_window": W,
       "layer_types": KINDS}
B = 3
LENGTHS = (24, 33, 76)

#: float32, program against reference: the same products summed in another
#: order (a block of queries against one row of the dense mask, grouped
#: heads against repeated keys, a grouped matmul over sorted rows against a
#: masked loop, XLA's reduction trees) through five layers of four norms.
#: A reference whose scores are rounded to bfloat16, or which leaves the
#: window out, fails it (asserted below).
F32_RTOL, F32_ATOL = 5e-5, 2e-6


@pytest.fixture(scope="module")
def ref():
    return _load("ref_trinity", os.path.join(
        ROOT, "benchmark", "reference", "trinity-abcd.py"))


def _shape(tokens):
    """4 x tokens / 4 x 1 patches of 4^3, or 3 x tokens / 3 x 1."""
    if tokens % 4 == 0:
        return (16, tokens, 4)
    return (12, 4 * tokens // 3, 4)


def _batch(seed, tokens, rows=B):
    r = np.random.RandomState(seed)
    x = r.randint(0, 256, (rows,) + _shape(tokens)).astype(np.uint8)
    y = r.randint(0, 2, (rows,)).astype(np.int32)
    return jnp.asarray(x), jnp.asarray(y)


def _params(model, seed, tokens):
    x = jnp.zeros((1,) + _shape(tokens) + (1,))
    return _jitter(model.init(jax.random.key(seed), x)["params"], seed)


def _close(a, b, rtol=F32_RTOL, atol=F32_ATOL):
    np.testing.assert_allclose(np.asarray(a, np.float64),
                               np.asarray(b, np.float64), rtol=rtol,
                               atol=atol)


def _apply(model, params, x):
    return model.apply({"params": params}, x.astype(jnp.float32)[..., None])


# ---------- (a) the model against the reference ----------

@pytest.mark.parametrize("tokens", LENGTHS)
def test_float32_logits_loss_and_every_gradient(ref, tokens):
    model = Trinity3D(widths=SMALL)
    params = _params(model, tokens, tokens)
    x, y = _batch(tokens, tokens)

    def loss(p):
        logits, aux = _apply(model, p, x)
        return (jnp.mean(ref.ops.bce_with_logits(logits, y)) + aux["loss"],
                (logits, aux))

    (got_loss, (got, aux)), got_grads = jax.jit(jax.value_and_grad(
        loss, has_aux=True))(params)

    @jax.jit
    def reference(p):
        with jax.default_matmul_precision("highest"):
            return (ref.forward(p, {}, x, cfg=CFG),
                    ref.trunk(p, x, cfg=CFG)[1],
                    jax.value_and_grad(lambda p: ref.training_loss(
                        p, {}, x, y, cfg=CFG))(p),
                    ref.forward(p, {}, x, cfg=CFG,
                                q_scores=ref.ops.rounded(jnp.bfloat16)),
                    ref.forward(p, {}, x,
                                cfg={**CFG, "sliding_window": None}))

    want, chosen, (want_loss, want_grads), low, no_window = reference(params)
    _close(got, want)
    _close(got_loss, want_loss)
    assert float(aux["loss"]) == 0.0  # no auxiliary term
    # the routing, over all 16 outputs whatever is held
    np.testing.assert_array_equal(
        aux["expert_tokens"],
        np.bincount(np.asarray(chosen).ravel(), minlength=E))
    assert int(aux["expert_tokens"].sum()) == 4 * B * tokens * K
    assert int(aux["held_overflow_calls"]) == 0
    assert int(aux["attn_kernel_calls"]) == 0  # off the TPU: the plain form
    assert int(aux["attn_outputs_kept"]) == 0  # no kernel, no outputs kept
    flat = jax.tree_util.tree_flatten_with_path(got_grads)[0]
    flat_ref = jax.tree.leaves(want_grads)
    # attention 7 + four norms a layer; layer 0's feed-forward 3; an expert
    # layer's router, up, down and shared 3; patch embedding 2, norm, head
    assert len(flat) == len(flat_ref) == 5 * 11 + 3 + 4 * 6 + 4
    for (path, g), gr in zip(flat, flat_ref):
        name = jax.tree_util.keystr(path)
        top = float(jnp.max(jnp.abs(gr)))
        assert top > 0, name
        _close(g, gr, rtol=F32_RTOL * 10, atol=F32_ATOL * top * 20)
    # the tolerance is about precision: bfloat16 scores fail it
    with pytest.raises(AssertionError):
        _close(low, want)
    # and about the window: inside one window there is none to leave out
    if tokens <= W:
        np.testing.assert_array_equal(no_window, want)
    else:
        with pytest.raises(AssertionError):
            _close(no_window, want)


def test_rematerialised_layers_give_the_same_tree_logits_and_gradients():
    plain = Trinity3D(widths=SMALL, remat_layers=False)
    remat = Trinity3D(widths=SMALL)
    x, y = _batch(5, 76)
    a, b_ = (m.init(jax.random.key(3),
                    jnp.zeros((1,) + _shape(76) + (1,)))["params"]
             for m in (plain, remat))
    assert jax.tree.structure(a) == jax.tree.structure(b_)
    jax.tree.map(np.testing.assert_array_equal, a, b_)
    params = _jitter(a, 5)

    def grads(model):
        def loss(p):
            logits, _ = _apply(model, p, x)
            return jnp.sum(logits * (2.0 * y[:, None] - 1))
        return jax.jit(jax.value_and_grad(loss))(params)

    (la, ga), (lb, gb) = grads(plain), grads(remat)
    _close(la, lb, rtol=1e-6)
    for g, h in zip(jax.tree.leaves(ga), jax.tree.leaves(gb)):
        _close(g, h, rtol=1e-4, atol=1e-7)


def test_bf16_mixed_keeps_scores_softmax_qk_norms_and_router_in_float32():
    """The compute dtype reaches the projections, the gate and the
    experts, never the scores, their softmax, the QK norms' statistics or
    the router: in the traced step every exponential over a block of
    scores, every ``rsqrt`` and the routers' logistics are float32."""
    model = Trinity3D(widths=SMALL, dtype=jnp.bfloat16)
    params = _params(Trinity3D(widths=SMALL), 0, 76)
    x, _ = _batch(0, 76)
    text = jax.make_jaxpr(lambda p: _apply(model, p, x))(params)
    assert text.out_avals[0].dtype == jnp.float32
    eqns = list(_all_eqns(text.jaxpr))
    exps = [e for e in eqns if e.primitive.name == "exp"]
    gates = [e for e in eqns if e.primitive.name == "logistic"
             and e.outvars[0].aval.shape[-1] == E]
    assert len(exps) >= 5 * 3 and len(gates) == 4  # blocks, routers
    roots = [e for e in eqns if e.primitive.name == "rsqrt"]
    # four norms and two QK norms a layer, the volume's and the final one
    assert len(roots) == 5 * 6 + 2
    for e in exps + gates + roots:
        assert e.outvars[0].aval.dtype == jnp.float32, e
    # contractions over the 16 score dimensions of a grouped head
    # (einsum hands dot_general the keys first)
    scores = [e for e in eqns if e.primitive.name == "dot_general"
              and e.outvars[0].aval.ndim == 5
              and e.invars[0].aval.shape[-1] == e.invars[1].aval.shape[-1]
              == D]
    assert len(scores) >= 5 * 3
    assert all(e.outvars[0].aval.dtype == jnp.float32 for e in scores)
    # the output gate's sigmoid is the compute dtype's, over all heads
    wide = [e for e in eqns if e.primitive.name == "logistic"
            and e.outvars[0].aval.shape[-1] == HEADS * D]
    assert len(wide) == 5
    assert all(e.outvars[0].aval.dtype == jnp.bfloat16 for e in wide)


# ---------- (b) the window, on the attention entry's plain forms ----------

def _qkv(seed, tokens, rows=2):
    keys = jax.random.split(jax.random.key(seed), 3)
    return (jax.random.normal(keys[0], (rows, tokens, KV, HEADS // KV, D)),
            jax.random.normal(keys[1], (rows, tokens, KV, D)),
            jax.random.normal(keys[2], (rows, tokens, KV, D)))


def _entry(block=BLOCK, window=W):
    return jax.jit(lambda q, k, v: attention.causal_attention(
        q, k, v, block, jnp.float32, window=window))


@pytest.mark.parametrize("block", [BLOCK, 76])  # blocked, one block
@pytest.mark.parametrize("t", [0, 5, 40, 70])
def test_a_change_at_token_t_moves_the_rows_of_its_window_alone(t, block):
    """``k``, ``v`` at token ``t`` reach the queries ``t .. t + W - 1``:
    every row before ``t`` and from ``t + W`` on is bitwise alone."""
    q, k, v = _qkv(t, 76)
    f = _entry(block)
    before = f(q, k, v)
    after = f(q, k.at[:, t].add(1.0), v.at[:, t].add(1.0))
    np.testing.assert_array_equal(before[:, :t], after[:, :t])
    np.testing.assert_array_equal(before[:, t + W:], after[:, t + W:])
    moved = np.abs(np.asarray(before - after)).reshape(2, 76, HEADS, D)
    assert (moved[:, t:t + W].max(-1) > 0).all()
    # without the window the change reaches every later row
    full = _entry(block, None)
    moved = np.abs(np.asarray(full(q, k, v) - full(
        q, k.at[:, t].add(1.0), v.at[:, t].add(1.0))))
    assert (moved.reshape(2, 76, HEADS, D)[:, t:].max(-1) > 0).all()


@pytest.mark.parametrize("tokens", [24, 32, 76])
def test_a_window_the_sequence_never_fills_is_no_window(tokens):
    q, k, v = _qkv(tokens, tokens)
    want = _entry(window=None)(q, k, v)
    for window in (tokens, tokens + 1, 10 * tokens):
        np.testing.assert_array_equal(_entry(window=window)(q, k, v), want)
    if tokens > W:
        assert float(jnp.max(jnp.abs(_entry()(q, k, v) - want))) > 1e-3
    else:
        np.testing.assert_array_equal(_entry()(q, k, v), want)


@pytest.mark.parametrize("window", [W, 20, 45])
@pytest.mark.parametrize("block", [8, 16, 32, 24, 30, 50])
def test_the_blocked_form_equals_the_one_block_form(block, window):
    """For block sizes that do and do not divide the window, and windows
    that do and do not divide the sequence: values and every gradient."""
    q, k, v = _qkv(block + window, 76)
    one = lambda *a: attention.causal_gq_attention(*a, jnp.float32, window)
    blocked = lambda *a: attention.blocked_causal_attention(
        *a, block, jnp.float32, window)
    f = lambda fn: jax.jit(jax.value_and_grad(
        lambda *a: jnp.sum(jnp.sin(fn(*a))), argnums=(0, 1, 2)))(q, k, v)
    with jax.default_matmul_precision("highest"):
        got = jax.jit(blocked)(q, k, v)
        want = jax.jit(one)(q, k, v)
        (_, g_got), (_, g_want) = f(blocked), f(one)
    _close(got, want)
    for g, h in zip(g_got, g_want):
        _close(g, h, rtol=F32_RTOL * 10,
               atol=F32_ATOL * float(jnp.max(jnp.abs(h))) * 20)


def test_the_one_block_form_is_the_dense_mask_by_hand():
    q, k, v = _qkv(1, 76)
    i, t = np.arange(76)[:, None], np.arange(76)[None]
    mask = (t <= i) & (i - t < W)
    assert mask.sum() == W * (W + 1) // 2 + (76 - W) * W
    s = jnp.einsum("bqgrd,bkgd->bgrqk", q, k,
                   precision="highest") / np.sqrt(D)
    p = jax.nn.softmax(jnp.where(mask, s, -jnp.inf), axis=-1)
    want = jnp.einsum("bgrqk,bkgd->bqgrd", p, v,
                      precision="highest").reshape(2, 76, -1)
    with jax.default_matmul_precision("highest"):
        _close(attention.causal_gq_attention(q, k, v, jnp.float32, W), want)


# ---------- (c) position: the sliding layers alone ----------

def _attn(sliding, w=SMALL, seed=0):
    layer = GatedAttention(w, sliding)
    x = jax.random.normal(jax.random.key(seed), (2, 76, 64))
    params = _jitter(GatedAttention(SMALL, sliding).init(
        jax.random.key(0), x)["params"], seed, 0.2)
    return layer, params, x


@pytest.mark.parametrize("sliding", [False, True])
def test_the_full_layers_read_no_position_and_the_sliding_ones_do(sliding):
    layer, params, x = _attn(sliding)
    other = GatedAttention(dataclasses.replace(SMALL, rope_theta=5e2),
                           sliding)
    f = lambda m: jax.jit(lambda x: m.apply({"params": params}, x)[0])(x)
    if sliding:
        assert float(jnp.max(jnp.abs(f(layer) - f(other)))) > 1e-3
    else:
        np.testing.assert_array_equal(f(layer), f(other))


@pytest.mark.parametrize("sliding", [False, True])
@pytest.mark.parametrize("t", [5, 40, 70])
def test_a_change_at_token_t_leaves_the_outputs_before_t_bitwise_alone(
        t, sliding):
    layer, params, x = _attn(sliding, seed=t)
    f = jax.jit(lambda x: layer.apply({"params": params}, x)[0])
    before, after = f(x), f(x.at[:, t].add(1.0))
    np.testing.assert_array_equal(before[:, :t], after[:, :t])
    moved = np.abs(np.asarray(before - after)).max(-1)
    assert (moved[:, t:t + W] > 0).all()
    if sliding:  # the layer is attention alone: nothing passes the window
        np.testing.assert_array_equal(before[:, t + W:], after[:, t + W:])
    else:
        assert (moved[:, t:] > 0).all()


# ---------- (d) the gate and the norms ----------

@pytest.mark.parametrize("sliding", [False, True])
def test_a_zero_gate_halves_the_attentions_contribution_exactly(sliding):
    """``Wg = 0``: ``sigmoid(0) = 1/2`` on every head's output, and a
    factor of two passes through ``Wo`` exactly."""
    layer, params, x = _attn(sliding)
    c = SMALL
    zero = {**params, "gate_proj": {"kernel": jnp.zeros_like(
        params["gate_proj"]["kernel"])}}
    got = jax.jit(lambda x: layer.apply({"params": zero}, x)[0])(x)

    @jax.jit
    def ungated(x):
        norm = lambda n, a: tokens3d.RMSNorm().apply(
            {"params": params[n]}, a)
        lin = lambda n: x @ params[n]["kernel"]
        q = norm("q_norm", lin("q_proj").reshape(2, 76, HEADS, D))
        k = norm("k_norm", lin("k_proj").reshape(2, 76, KV, D))
        v = lin("v_proj").reshape(2, 76, KV, D)
        if sliding:
            cos, sin = tokens3d.rope_tables(76, D, c.rope_theta)
            q, k = (tokens3d.apply_rope(a, cos, sin) for a in (q, k))
        out = attention.causal_attention(
            q.reshape(2, 76, KV, HEADS // KV, D), k, v, BLOCK, jnp.float32,
            window=W if sliding else None)
        return out @ params["o_proj"]["kernel"]

    want = ungated(x)
    assert float(jnp.max(jnp.abs(want))) > 0.1
    _close(got, 0.5 * want, rtol=1e-6, atol=1e-7)
    # and the gate is live: the jittered Wg gives another output
    live = jax.jit(lambda x: layer.apply({"params": params}, x)[0])(x)
    assert float(jnp.max(jnp.abs(live - got))) > 1e-3


def test_qk_norms_make_the_scores_blind_to_a_heads_scale():
    """A head's columns of ``Wq`` (or ``Wk``) times a constant: the
    per-head norm takes it out again, up to its eps."""
    layer, params, x = _attn(True)
    by_head = jnp.repeat(jnp.asarray([4.0, 0.25] * (HEADS // 2)), D)
    by_kv = jnp.repeat(jnp.asarray([8.0, 0.5]), D)
    scaled = {**params,
              "q_proj": {"kernel": params["q_proj"]["kernel"] * by_head},
              "k_proj": {"kernel": params["k_proj"]["kernel"] * by_kv}}
    f = lambda p: jax.jit(lambda x: layer.apply({"params": p}, x)[0])(x)
    _close(f(scaled), f(params), rtol=1e-3, atol=1e-4)
    # the values have no norm: their scale passes through
    louder = {**params,
              "v_proj": {"kernel": params["v_proj"]["kernel"] * 2.0}}
    _close(f(louder), 2.0 * f(params), rtol=1e-5, atol=1e-6)


def test_the_four_norms_are_where_the_equations_put_them(ref):
    """One expert layer against the reference's: the post-norms act on a
    sub-layer's output before the residual sum."""
    h = jax.random.normal(jax.random.key(0), (2, 40, 64))
    p = _jitter(Layer(False, True, SMALL).init(jax.random.key(0), h)[
        "params"], 0, 0.2)
    assert {"attn_norm", "attn_post_norm", "mlp_norm",
            "mlp_post_norm"} <= set(p)
    got = jax.jit(lambda h: Layer(False, True, SMALL).apply(
        {"params": p}, h)[0])(h)
    with jax.default_matmul_precision("highest"):
        want, _ = ref.layer(h, p, True, CFG, ref.ops.exact, ref.ops.exact,
                            ref.ops.exact, False, None, "layer")
    _close(got, want, rtol=1e-4, atol=1e-5)
    # a post-norm's weight scales its sub-layer's contribution alone
    doubled = {**p, "attn_post_norm": {"weight":
                                       2.0 * p["attn_post_norm"]["weight"]}}
    x = tokens3d.RMSNorm().apply({"params": p["attn_norm"]}, h)
    y = GatedAttention(SMALL, True).apply({"params": p["self_attn"]}, x)[0]
    post = tokens3d.RMSNorm().apply({"params": p["attn_post_norm"]}, y)
    after = lambda q: _after_attention(SMALL, q, h, True)
    _close(after(doubled) - after(p), post, rtol=1e-4, atol=1e-5)


def _after_attention(w, p, h, sliding):
    """``h + N_2(attention(N_1(h)))`` by the program's own modules."""
    x = tokens3d.RMSNorm().apply({"params": p["attn_norm"]}, h)
    y = GatedAttention(w, sliding).apply({"params": p["self_attn"]}, x)[0]
    return h + tokens3d.RMSNorm().apply({"params": p["attn_post_norm"]}, y)


# ---------- (e) the share of experts ----------

@pytest.mark.parametrize("sliding", [False, True])
def test_the_four_shares_add_up_to_the_uncut_layer(ref, sliding):
    """The routed parts of the four shares of experts (0-3, 4-7, 8-11,
    12-15), with attention, the shared expert, the norms' input and the
    residual counted once, equal the uncut reference's ``m`` (all 16
    experts held) BEFORE ``N_4``: the post-norm is not linear, so the
    shares are summed before it, and the whole layer is then the
    reference's ``h + N_4(m)``. Each share is the PROGRAM's layer told
    which experts it holds and given their weights alone; the router,
    which every chip computes alike, gives every share the same choices."""
    r = np.random.RandomState(int(sliding))
    h = jnp.asarray(r.randn(2, 40, 64), jnp.float32)
    whole_w = dataclasses.replace(SMALL, held=(0, E))
    p = _jitter(Layer(False, sliding, whole_w).init(jax.random.key(1), h)[
        "params"], int(sliding), 0.2)
    exact = ref.ops.exact

    @jax.jit
    def reference(p):
        with jax.default_matmul_precision("highest"):
            cfg = {**CFG, "held": (0, E)}
            mid = ref.after_attention(h, p, sliding, cfg, exact, exact,
                                      False, None, "layer")
            m, chosen = ref.feed_forward(mid, p, cfg, exact, exact, None,
                                         "layer")
            return m, chosen, ref.layer(h, p, sliding, cfg, exact, exact,
                                        exact, False, None, "layer")[0]

    whole_m, chosen, whole = reference(p)
    mid = _after_attention(SMALL, p, h, sliding)
    u = tokens3d.RMSNorm().apply({"params": p["mlp_norm"]}, mid)
    total, rows = None, 0
    for first in range(0, E, 4):
        w = dataclasses.replace(SMALL, held=(first, 4))
        share = {**p, "moe": {"router": p["moe"]["router"],
                              "up": p["moe"]["up"][first:first + 4],
                              "down": p["moe"]["down"][first:first + 4]}}

        @jax.jit
        def program(share):
            return (Layer(False, sliding, w).apply({"params": share}, h),
                    HeldExperts(w).apply({"params": share["moe"]}, u))

        (out, experts, passed, kernels), (routed, _, _) = program(share)
        np.testing.assert_array_equal(experts, chosen)
        held = (experts >= first) & (experts < first + 4)
        rows += int(held.sum())
        assert int(passed) == 0 and int(kernels) == 0  # the plain form
        # a token none of whose choices is held here gets nothing
        none = ~held.any(-1)
        assert float(jnp.max(jnp.abs(routed.reshape(-1, 64)[none]),
                             initial=0.0)) == 0.0
        # and the reference, given the same share, gives the same layer
        with jax.default_matmul_precision("highest"):
            part_ref, _ = ref.layer(
                h, share, sliding, {**CFG, "held": (first, 4)}, exact,
                exact, exact, False, None, "layer")
        _close(out, part_ref, rtol=1e-4, atol=1e-5)
        total = routed if total is None else total + routed
    assert rows == 2 * 40 * K  # every slot landed on exactly one share
    # what every chip computes alike, once: the shared expert on u
    shared = tokens3d.GatedMLP(64, 24, 0.02).apply(
        {"params": p["shared"]}, u)
    _close(total + shared, whole_m, rtol=1e-4, atol=1e-5)
    post = tokens3d.RMSNorm().apply({"params": p["mlp_post_norm"]},
                                    total + shared)
    _close(mid + post, whole, rtol=1e-4, atol=1e-5)


# ---------- the trainer's protocol ----------

def test_the_model_declares_what_the_trainer_reads():
    from neuroimagedisttraining_tpu.config import OptimConfig
    from neuroimagedisttraining_tpu.core.trainer import LocalTrainer

    model = create_model("trinity3d")
    w = model.widths
    assert (w.hidden_size, w.heads, w.kv_heads, w.head_dim,
            w.sliding_window) == (2048, 32, 4, 128, 2048)
    assert (w.intermediate_size, w.expert_width, w.num_experts,
            w.experts_per_token, w.shared_experts) == (6144, 1024, 128, 8, 1)
    assert (w.route_scale, w.rope_theta, w.rms_eps) == (2.826, 1e4, 1e-5)
    assert w.layer_types == KINDS and w.dense_layers == 1
    # three sliding layers to one full among the expert layers
    assert w.layer_types[1:].count(SLIDING) == 3
    assert (w.held, w.patch) == ((0, 16), 8)
    assert model.returns_aux and model.remat_layers
    assert model.aux_counters == ("expert_tokens", "held_overflow_calls",
                                  "attn_kernel_calls", "attn_outputs_kept")
    assert model.held_experts == (0, 16)
    assert model.row_tokens((121, 145, 121)) == 4864
    assert LocalTrainer(model, OptimConfig(), 1).eval_batch_rows(
        (121, 145, 121)) == 4
    # 2 x 4,864 tokens x 8 slots = 77,824; twice the uniform eighth
    assert model.held_capacity_rows((2, 121, 145, 121, 1)) == 77824 // 4
    shapes = jax.eval_shape(
        model.init, jax.random.key(0), jnp.zeros((1, 121, 145, 121, 1)))
    count = lambda t: sum(int(np.prod(a.shape)) for a in jax.tree.leaves(t))
    sizes = {k: count(v) for k, v in shapes["params"].items()}
    assert count(shapes["params"]["layers_0"]["self_attn"]) == 27_263_232
    assert sizes["layers_0"] == 65_020_160
    assert sizes["layers_1"] == 134_488_320
    assert sum(sizes.values()) == 604_028_160


def test_the_published_shapes_take_the_kernels_on_a_tpu(monkeypatch):
    """4,864 tokens of 32 heads of 128 on 4, a window of 2,048 = 8 blocks
    of 256: both kinds of layer; not the eager initialisation, not off
    the TPU, not a window of a fraction of a block."""
    monkeypatch.setattr(jax, "default_backend", lambda: "tpu")
    takes = lambda window, kernel=True, T=4864: attention.takes_kernel(
        T, 128, 0, 128, kernel, window, 8)
    assert takes(2048) and takes(None)
    assert not takes(2048, kernel=False)
    assert not takes(2000)
    assert takes(2000, T=1024)  # never closes: no window
    monkeypatch.setattr(jax, "default_backend", lambda: "cpu")
    assert not takes(2048)


def _tiling_trunks():
    """Both kernel trunks at the smallest widths the kernels' blocks tile,
    three layers each: ``name -> (model, tokens, (heads, value width))``."""
    from tests import test_moonlight3d as moonlight

    return {
        "moonlight3d": (moonlight.Moonlight3D(widths=dataclasses.replace(
            moonlight.SMALL, heads=2, qk_nope_head_dim=128,
            qk_rope_head_dim=64, v_head_dim=128, block=128)), 256, (2, 128)),
        "trinity3d": (Trinity3D(widths=dataclasses.replace(
            SMALL, layer_types=(SLIDING, FULL, SLIDING), heads=4,
            kv_heads=2, head_dim=128, sliding_window=128, block=128)), 384,
            (4, 128)),
    }


def test_through_the_interpreted_kernel_the_model_is_the_plain_forms(
        monkeypatch):
    """The small trunk at widths the kernel's blocks tile (384 tokens:
    three blocks of 128, a window of one block, heads of 128 in groups of
    two), its attention through the kernels' own bodies in Pallas'
    interpreter (the choice answered as a TPU answers it), against the
    same trunk on the plain form: logits, the counters and every
    gradient, under ``nn.remat`` as the trainer runs it."""
    import functools

    model, tokens, _ = _tiling_trunks()["trinity3d"]
    x, y = _batch(7, tokens, rows=2)
    params = _jitter(model.init(jax.random.key(7), jnp.zeros(
        (1,) + _shape(tokens) + (1,)))["params"], 7)

    def run(p):
        def loss(p):
            logits, aux = _apply(model, p, x)
            return jnp.sum(logits * (2.0 * y[:, None] - 1)), (logits, aux)
        return jax.jit(jax.value_and_grad(loss, has_aux=True))(p)

    (_, (want, want_aux)), want_grads = run(params)
    assert int(want_aux["attn_kernel_calls"]) == 0
    assert int(want_aux["attn_outputs_kept"]) == 0
    real = attention.attention_kernel
    monkeypatch.setattr(attention, "takes_kernel",
                        lambda T, dk, ds, dv, kernel, *a: kernel
                        and attention.kernel_tiles(T, dk, ds, dv, *a))
    monkeypatch.setattr(attention, "attention_kernel",
                        functools.partial(real, interpret=True))
    (_, (got, aux)), got_grads = run(params)
    assert int(aux["attn_kernel_calls"]) == 3
    assert int(aux["attn_outputs_kept"]) == 3
    _close(got, want)
    np.testing.assert_array_equal(aux["expert_tokens"],
                                  want_aux["expert_tokens"])
    for (path, g), h in zip(
            jax.tree_util.tree_flatten_with_path(got_grads)[0],
            jax.tree.leaves(want_grads)):
        top = float(jnp.max(jnp.abs(h)))
        _close(g, h, rtol=F32_RTOL * 10, atol=F32_ATOL * max(top, 1e-30)
               * 20)


# ---------- what a rematerialised layer keeps (PR 45) ----------

@pytest.mark.parametrize("name", ["moonlight3d", "trinity3d"])
def test_a_rematerialised_layer_keeps_the_forward_kernels_outputs(
        monkeypatch, capsys, name):
    """``tokens3d.layer_stack``'s policy keeps, by name, the attention
    kernel's ``o`` and log-sum-exp of every layer (ops/attention.py
    ``KEPT``) and nothing else of a layer but its input: the gradient
    step's forward kernel is traced once a layer, none of them inside a
    rematerialised body (``remat2``), where ``nn.remat`` with no policy
    traces it a second time there; and since the kept ``o`` IS the array
    the first forward wrote, the loss and every gradient leaf are
    bitwise what full rematerialisation gives (the kernels' own bodies,
    through Pallas' interpreter)."""
    import functools

    model, tokens, got_o = _tiling_trunks()[name]
    x, y = _batch(11, tokens, rows=2)
    params = _jitter(model.init(jax.random.key(11), jnp.zeros(
        (1,) + _shape(tokens) + (1,)))["params"], 11)
    monkeypatch.setattr(attention, "takes_kernel",
                        lambda T, dk, ds, dv, kernel, *a: kernel
                        and attention.kernel_tiles(T, dk, ds, dv, *a))
    monkeypatch.setattr(attention, "attention_kernel", functools.partial(
        attention.attention_kernel, interpret=True))

    def loss(p):
        logits, aux = _apply(model, p, x)
        return jnp.sum(logits * (2.0 * y[:, None] - 1)) + aux["loss"]

    def forwards(traced, inside_remat=False):
        """The ``attention_forward`` calls of a jaxpr: (all, those inside
        a rematerialised body)."""
        found = []
        for e in traced.eqns:
            if (e.primitive.name == "pallas_call"
                    and e.params["name"] == "attention_forward"):
                found.append(inside_remat)
            for sub in jax.core.jaxprs_in_params(e.params):
                found += forwards(sub, inside_remat
                                  or e.primitive.name == "remat2")
        return found

    # a function of its own a trace: jit's cache would answer the second
    # with the first's jaxpr
    step = lambda: jax.value_and_grad(lambda p: loss(p))
    kept = forwards(jax.make_jaxpr(step())(params).jaxpr)
    assert kept == [False] * 3
    # what the step keeps of ops/attention.py's: the two a layer, and no
    # operand of the kernel (``o`` is read on, by W_o, and so reaches the
    # list behind the barrier JAX puts on such a residual's producer)
    jax.ad_checkpoint.print_saved_residuals(loss, params)
    ours = [line for line in capsys.readouterr().out.splitlines()
            if "ops/attention.py" in line]
    assert len(ours) == 2 * 3
    assert sum(f"named '{attention.KEPT[1]}'" in line
               for line in ours) == 3
    heads, dv = got_o
    assert sum(line.startswith(f"f32[2,{tokens},{heads * dv}] output of "
                               "reduce_precision") for line in ours) == 3
    got, got_grads = jax.jit(step())(params)

    # full rematerialisation: the policy that keeps nothing
    monkeypatch.setattr(jax.checkpoint_policies, "save_only_these_names",
                        lambda *names: None)
    full = forwards(jax.make_jaxpr(step())(params).jaxpr)
    assert sorted(full) == [False] * 3 + [True] * 3
    want, want_grads = jax.jit(step())(params)
    np.testing.assert_array_equal(got, want)
    assert jax.tree.structure(got_grads) == jax.tree.structure(want_grads)
    for (path, g), h in zip(
            jax.tree_util.tree_flatten_with_path(got_grads)[0],
            jax.tree.leaves(want_grads)):
        np.testing.assert_array_equal(g, h, err_msg=str(path))
