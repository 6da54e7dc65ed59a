"""``--model moonlight3d`` against its plain reference (PR 40), on the CPU.

The program (models/moonlight3d.py: latent attention whose keys and values
are rebuilt from a latent beside one shared rotary key, a block of queries
at a time; a leading dense layer; sigmoid-routed experts of which this
chip holds a share, beside a shared expert; a sequence-wise balance loss)
against ``benchmark/reference/moonlight-abcd.py`` (one dense causal mask a
head, a loop over the held experts), on seeded random weights at a small
size: 1 + 2 layers, hidden 64, 4 heads of 16 + 8 score and 16 value
dimensions, latent 32, feed-forward 96, 16 experts of width 24 with 4 a
token and 4 held, blocks of 32 queries, patch 4. Volumes of 16 x 4k x 4
voxels are ``T = 4 k`` tokens: 24 (one block), 64 (two whole blocks), 76
(two whole blocks and one of 12). The chip comparison at the published
widths is the builder's (PERF.md).
"""

import dataclasses
import importlib.util
import os

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from neuroimagedisttraining_tpu.models import create_model, tokens3d
from neuroimagedisttraining_tpu.models.moonlight3d import (
    HeldGatedExperts, LatentAttention, Layer, Moonlight3D, Widths, mla_core,
)
from neuroimagedisttraining_tpu.ops import attention, moe

ROOT = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
HEADS, DN, DR, DV, E, K, BLOCK = 4, 16, 8, 16, 16, 4, 32
SMALL = Widths(dense_layers=1, expert_layers=2, hidden_size=64, heads=HEADS,
               qk_nope_head_dim=DN, qk_rope_head_dim=DR, v_head_dim=DV,
               kv_lora_rank=32, intermediate_size=96, num_experts=E,
               held=(0, 4), experts_per_token=K, expert_width=24,
               block=BLOCK, patch=4)
CFG = {"heads": HEADS, "qk_nope_head_dim": DN, "experts_per_token": K,
       "held": (0, 4), "routed_scaling_factor": 2.446, "aux_alpha": 0.001,
       "rope_theta": 5e4, "rms_eps": 1e-5, "patch": 4}
B = 3
LENGTHS = (24, 64, 76)

#: float32, program against reference: the same products summed in another
#: order (a block of queries against one row of the dense mask, one
#: contraction over dn + dr against the sum of two, a grouped matmul over
#: sorted rows against a masked loop, XLA's reduction trees) through three
#: layers. Values are of order 0.01-1 and float32 carries 1.2e-7 a
#: product. A reference whose scores are rounded to bfloat16 is off by
#: 1e-3 and fails it (asserted below).
F32_RTOL, F32_ATOL = 5e-5, 2e-6


def _load(name, path):
    spec = importlib.util.spec_from_file_location(name, path)
    mod = importlib.util.module_from_spec(spec)
    spec.loader.exec_module(mod)
    return mod


@pytest.fixture(scope="module")
def ref():
    return _load("ref_moonlight", os.path.join(
        ROOT, "benchmark", "reference", "moonlight-abcd.py"))


def _shape(tokens):
    return (16, tokens, 4)  # 4 x tokens / 4 x 1 patches of 4^3


def _batch(seed, tokens, rows=B):
    r = np.random.RandomState(seed)
    x = r.randint(0, 256, (rows,) + _shape(tokens)).astype(np.uint8)
    y = r.randint(0, 2, (rows,)).astype(np.int32)
    return jnp.asarray(x), jnp.asarray(y)


def _jitter(params, seed, scale=0.15):
    """Seeded weights away from their start: norm weights of order 1 +-
    0.15, projections large enough that scores and routing differ."""
    leaves, tree = jax.tree.flatten(params)
    keys = jax.random.split(jax.random.key(seed + 100), len(leaves))
    return jax.tree.unflatten(tree, [
        a + scale * jax.random.normal(k, a.shape) for a, k in
        zip(leaves, keys)])


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
def test_float32_logits_loss_balance_and_every_gradient(ref, tokens):
    model = Moonlight3D(widths=SMALL)
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
                    ref.loss_terms(p, {}, x, y, cfg=CFG),
                    jax.value_and_grad(lambda p: ref.training_loss(
                        p, {}, x, y, cfg=CFG))(p),
                    ref.forward(p, {}, x, cfg=CFG,
                                q_scores=ref.ops.rounded(jnp.bfloat16)))

    (want, chosen, (want_task, want_balance), (want_loss, want_grads),
     low) = reference(params)
    _close(got, want)
    _close(got_loss, want_loss)
    _close(aux["loss"], want_balance)
    # L is a live term: of the order of alpha x the expert layers
    assert 0.5 * 2e-3 < float(aux["loss"]) < 2.0 * 2e-3
    _close(want_loss, want_task + want_balance, rtol=1e-6)
    # the routing, over all 16 outputs whatever is held
    np.testing.assert_array_equal(
        aux["expert_tokens"],
        np.bincount(np.asarray(chosen).ravel(), minlength=E))
    assert int(aux["expert_tokens"].sum()) == 2 * B * tokens * K
    assert int(aux["held_overflow_calls"]) == 0
    assert int(aux["attn_kernel_calls"]) == 0  # off the TPU: the XLA form
    assert int(aux["attn_outputs_kept"]) == 0  # no kernel, no outputs kept
    flat = jax.tree_util.tree_flatten_with_path(got_grads)[0]
    flat_ref = jax.tree.leaves(want_grads)
    # attention 5 + two norms a layer; layer 0's feed-forward 3; an expert
    # layer's router, up, down and shared 3; patch embedding 2, norm, head
    assert len(flat) == len(flat_ref) == 3 * 7 + 3 + 2 * 6 + 4
    for (path, g), gr in zip(flat, flat_ref):
        name = jax.tree_util.keystr(path)
        top = float(jnp.max(jnp.abs(gr)))
        assert top > 0, name
        _close(g, gr, rtol=F32_RTOL * 10, atol=F32_ATOL * top * 20)
    # the tolerance is about precision: bfloat16 scores fail it
    with pytest.raises(AssertionError):
        _close(low, want)


def test_rematerialised_layers_give_the_same_tree_logits_and_gradients():
    """``remat_layers`` (the model's default) changes what is kept, not
    what is computed, and the tree is the eager one's."""
    plain = Moonlight3D(widths=SMALL, remat_layers=False)
    remat = Moonlight3D(widths=SMALL)
    x, y = _batch(5, 76)
    a, b_ = (m.init(jax.random.key(3),
                    jnp.zeros((1,) + _shape(76) + (1,)))["params"]
             for m in (plain, remat))
    assert jax.tree.structure(a) == jax.tree.structure(b_)
    jax.tree.map(np.testing.assert_array_equal, a, b_)
    params = _jitter(a, 5)

    def grads(model):
        def loss(p):
            logits, aux = _apply(model, p, x)
            return jnp.sum(logits * (2.0 * y[:, None] - 1)) + aux["loss"]
        return jax.jit(jax.value_and_grad(loss))(params)

    (la, ga), (lb, gb) = grads(plain), grads(remat)
    _close(la, lb, rtol=1e-6)
    for g, h in zip(jax.tree.leaves(ga), jax.tree.leaves(gb)):
        _close(g, h, rtol=1e-4, atol=1e-7)


def test_bf16_mixed_keeps_scores_softmax_and_router_in_float32():
    """The compute dtype reaches the projections and the experts, never
    the scores, their softmax, the router or the balance loss: in the
    traced step every exponential and logistic is float32."""
    model = Moonlight3D(widths=SMALL, dtype=jnp.bfloat16)
    params = _params(Moonlight3D(widths=SMALL), 0, 76)
    x, _ = _batch(0, 76)
    text = jax.make_jaxpr(lambda p: _apply(model, p, x))(params)
    logits, aux = text.out_avals[0], text.out_avals[1:]
    assert logits.dtype == jnp.float32
    assert [a.dtype for a in aux].count(jnp.float32) == 1  # aux["loss"]
    eqns = list(_all_eqns(text.jaxpr))
    exps = [e for e in eqns if e.primitive.name == "exp"]
    # the router's sigmoid is over the 16 outputs (SiLU's, over an
    # expert's width, is the compute dtype's)
    gates = [e for e in eqns if e.primitive.name == "logistic"
             and e.outvars[0].aval.shape[-1] == E]
    assert len(exps) >= 3 * 3 and len(gates) == 2  # blocks, routers
    for e in exps + gates:
        assert e.outvars[0].aval.dtype == jnp.float32, e
    # contractions over the 24 score dimensions of a head
    scores = [e for e in eqns if e.primitive.name == "dot_general"
              and e.outvars[0].aval.shape[:2] == (B, HEADS)
              and e.invars[0].aval.shape[-1] == DN + DR]
    assert len(scores) >= 3 * 3
    assert all(e.outvars[0].aval.dtype == jnp.float32 for e in scores)


def _all_eqns(jaxpr):
    for e in jaxpr.eqns:
        yield e
        for sub in jax.core.jaxprs_in_params(e.params):
            yield from _all_eqns(sub)


def attention_kernel_bodies(traced, layers: int):
    """The attention's ``pallas_call``s of a traced gradient step (a layer:
    forward and backward; the rematerialised layer keeps the forward's
    outputs and does not run it again, PR 45) and the equations inside
    their bodies, held to the stated precision: every exponential,
    logarithm, maximum, sum and product's result float32, every product's
    operands the compute dtype's (bfloat16). Also used by
    tests/test_tpu_compile.py at the published size."""
    calls = [e for e in _all_eqns(traced.jaxpr)
             if e.primitive.name == "pallas_call"
             and (e.params["name"] or "").startswith("attention_")]
    assert sorted(e.params["name"] for e in calls) == (
        ["attention_backward"] * layers + ["attention_forward"] * layers)
    inside = [e for call in calls for e in _all_eqns(call.params["jaxpr"])]
    by = lambda *names: [e for e in inside if e.primitive.name in names]
    # a forward: the running maximum's and the tile's, in its loop and on
    # its diagonal; a backward: the tile's, twice
    assert len(by("exp")) >= layers * (4 + 2)
    assert len(by("log")) == layers
    for e in by("exp", "log", "reduce_max", "reduce_sum", "max",
                "dot_general"):
        # (a windowed kernel's loop bounds are integer maxima)
        assert e.outvars[0].aval.dtype == jnp.float32 or jnp.issubdtype(
            e.outvars[0].aval.dtype, jnp.integer), e
    assert {v.aval.dtype for e in by("dot_general") for v in e.invars} == {
        jnp.dtype(jnp.bfloat16)}
    return calls, inside


# ---------- (b) the blocked attention ----------

def _qkv(seed, tokens, rows=2):
    keys = jax.random.split(jax.random.key(seed), 5)
    shape = lambda d, heads=HEADS: (rows, tokens, heads, d)
    return (jax.random.normal(keys[0], shape(DN)),
            jax.random.normal(keys[1], shape(DR)),
            jax.random.normal(keys[2], shape(DN)),
            jax.random.normal(keys[3], shape(DR, 1)),
            jax.random.normal(keys[4], shape(DV)))


def _dense_masked(q, k, v):
    """One dense masked block, score and value widths apart."""
    T = q.shape[1]
    s = jnp.einsum("bqad,bkad->baqk", q, k,
                   precision="highest") / np.sqrt(q.shape[-1])
    s = jnp.where(jnp.tril(jnp.ones((T, T), bool)), s, -jnp.inf)
    out = jnp.einsum("baqk,bkad->bqad", jax.nn.softmax(s, axis=-1), v,
                     precision="highest")
    return out.reshape(*out.shape[:2], -1)


@pytest.mark.parametrize("tokens", LENGTHS)
def test_blocked_attention_equals_one_dense_masked_block(tokens):
    """24-wide scores, 16-wide values; forward and every gradient."""
    qn, qr, kn, kr, v = _qkv(tokens, tokens)
    q = jnp.concatenate([qn, qr], -1)
    k = jnp.concatenate([kn, jnp.broadcast_to(kr, qr.shape)], -1)
    assert q.shape[-1] == 24 and v.shape[-1] == 16
    blocked = lambda *a: attention.blocked_causal_attention(
        *a, BLOCK, jnp.float32)
    with jax.default_matmul_precision("highest"):
        got = jax.jit(blocked)(q, k, v)
        core = jax.jit(lambda *a: mla_core(*a, BLOCK, jnp.float32)[0])(
            qn, qr, kn, kr, v)
        want = _dense_masked(q, k, v)
        # the entry's plain form: one block of scores up to BLOCK tokens
        entry = got if tokens > BLOCK else jax.jit(
            attention.causal_gq_attention, static_argnums=3)(
                q, k, v, jnp.float32)
        f = lambda fn: jax.jit(jax.grad(
            lambda *a: jnp.sum(jnp.sin(fn(*a))), argnums=(0, 1, 2)))(q, k, v)
        g_got, g_want = f(blocked), f(_dense_masked)
    assert got.shape == (2, tokens, HEADS * DV)
    _close(got, want)
    _close(entry, want)
    np.testing.assert_array_equal(core, entry)
    for g, h in zip(g_got, g_want):
        _close(g, h, rtol=F32_RTOL * 10,
               atol=F32_ATOL * float(jnp.max(jnp.abs(h))) * 20)


@pytest.mark.parametrize("t", [5, 40, 70])  # one in each block of T = 76
def test_one_rotary_key_serves_every_head(t):
    """Changing ``kr`` at token ``t`` moves every head's rows ``i >= t``
    and none before."""
    qn, qr, kn, kr, v = _qkv(t, 76)
    core = jax.jit(lambda kr: mla_core(qn, qr, kn, kr, v, BLOCK,
                                       jnp.float32)[0])
    before, after = core(kr), core(kr.at[:, t].add(1.0))
    np.testing.assert_array_equal(before[:, :t], after[:, :t])
    moved = np.abs(np.asarray(before - after)).reshape(2, 76, HEADS, DV)
    assert (moved[:, t:].max(-1) > 0).all()


# ---------- (c) causality ----------

@pytest.mark.parametrize("t", [5, 40, 70])
def test_a_change_at_token_t_leaves_the_outputs_before_t_bitwise_alone(t):
    layer = LatentAttention(SMALL)
    x = jax.random.normal(jax.random.key(t), (2, 76, 64))
    params = _jitter(layer.init(jax.random.key(0), x)["params"], t, 0.2)
    f = jax.jit(lambda x: layer.apply({"params": params}, x)[0])
    before, after = f(x), f(x.at[:, t].add(1.0))
    np.testing.assert_array_equal(before[:, :t], after[:, :t])
    moved = np.abs(np.asarray(before - after)).max(-1)
    assert (moved[:, t:] > 0).all()


# ---------- (d) the share of experts ----------

@pytest.mark.parametrize("seed", [0, 1])
def test_the_four_shares_add_up_to_the_uncut_layer(ref, seed):
    """The routed parts of the four shares of experts (0-3, 4-7, 8-11,
    12-15), with attention, the shared experts and the residual counted
    once, equal the uncut reference layer (all 16 experts held). Each
    share is the PROGRAM's layer told which experts it holds and given
    their weights alone; the router, which every chip computes alike,
    gives every share the same choices."""
    r = np.random.RandomState(seed)
    h = jnp.asarray(r.randn(2, 40, 64), jnp.float32)
    whole_w = dataclasses.replace(SMALL, held=(0, E))
    p = _jitter(Layer(False, whole_w).init(jax.random.key(seed), h)[
        "params"], seed, 0.2)
    def reference(p, held):
        with jax.default_matmul_precision("highest"):
            return ref.layer(h, p, {**CFG, "held": held}, ref.ops.exact,
                             ref.ops.exact, ref.ops.exact, None, "layer")

    whole, chosen, _ = jax.jit(lambda p: reference(p, (0, E)))(p)
    total, rows = None, 0
    for first in range(0, E, 4):
        w = dataclasses.replace(SMALL, held=(first, 4))
        share = {**p, "moe": {"router": p["moe"]["router"],
                              "up": p["moe"]["up"][first:first + 4],
                              "down": p["moe"]["down"][first:first + 4]}}
        @jax.jit
        def program(share):
            u = tokens3d.RMSNorm().apply({"params": p["mlp_norm"]},
                                         _after_attention(w, p, h))
            return (Layer(False, w).apply({"params": share}, h),
                    HeldGatedExperts(w).apply({"params": share["moe"]},
                                              u)[0])

        (out, experts, passed, _, kernels), routed = program(share)
        np.testing.assert_array_equal(experts, chosen)
        held = (experts >= first) & (experts < first + 4)
        rows += int(held.sum())
        assert int(passed) == 0 and int(kernels) == 0  # the XLA form
        # a token none of whose choices is held here gets nothing
        none = ~held.any(-1)
        assert float(jnp.max(jnp.abs(routed.reshape(-1, 64)[none]),
                             initial=0.0)) == 0.0
        # and the reference, given the same share, gives the same layer
        part_ref, _, _ = jax.jit(
            lambda p, first=first: reference(p, (first, 4)))(share)
        _close(out, part_ref, rtol=1e-4, atol=1e-5)
        # the first share brings what every chip computes alike, once
        total = out if total is None else total + routed
    assert rows == 2 * 40 * K  # every slot landed on exactly one share
    _close(total, whole, rtol=1e-4, atol=1e-5)


def _after_attention(w, p, h):
    """``h + attention(N_1(h))`` by the program's own modules."""
    x = tokens3d.RMSNorm().apply({"params": p["attn_norm"]}, h)
    return h + LatentAttention(w).apply({"params": p["mla"]}, x)[0]


# ---------- (e) the balance loss is per sequence ----------

def test_balance_loss_is_per_sequence_and_reaches_the_router_alone(ref):
    r = np.random.RandomState(0)
    logits = jnp.asarray(r.randn(2, 40, E) * 2.0, jnp.float32)
    bias = jnp.asarray(r.randn(E) * 0.3, jnp.float32)

    def terms(z, b):
        s, _, e = moe.route(z.reshape(-1, E), K, scoring="sigmoid", bias=b)
        return s.reshape(z.shape), e.reshape(*z.shape[:2], K)

    s, e = terms(logits, bias)
    both = moe.sequence_balance_loss(s, e, E)
    assert both.shape == (2,)
    for row in range(2):
        alone = moe.sequence_balance_loss(s[row:row + 1], e[row:row + 1], E)
        np.testing.assert_array_equal(alone[0], both[row])
        _close(both[row], ref.ops.sequence_balance(s[row], e[row], E))
        # by hand
        f = np.bincount(np.asarray(e[row]).ravel(), minlength=E) \
            * E / (K * 40)
        P = np.mean(np.asarray(s[row]) / np.asarray(s[row]).sum(-1,
                                                               keepdims=True), 0)
        _close(both[row], np.sum(f * P), rtol=1e-5)
    # uniform routing reads 1; these rows are uneven, each its own way
    assert abs(float(both[0]) - float(both[1])) > 1e-3
    # not the batch-wise term of the same scores and choices
    batchwise = moe.load_balancing_loss(
        (s / s.sum(-1, keepdims=True)).reshape(-1, E), e.reshape(-1, K), E)
    assert abs(float(batchwise) - float(both.mean())) > 1e-4

    def L(z, b):
        s, e = terms(z, b)
        return jnp.mean(moe.sequence_balance_loss(s, e, E))

    gz, gb = jax.grad(L, argnums=(0, 1))(logits, bias)
    assert float(jnp.max(jnp.abs(gz))) > 0
    assert float(jnp.max(jnp.abs(gb))) == 0.0  # nothing through b
    # nothing through C either: the gradient is that of sum_e f_e P_e with
    # f held as a constant
    f = jnp.sum(jax.nn.one_hot(e, E), axis=(1, 2)) * (E / (K * 40))

    def fixed(z):
        s = jax.nn.sigmoid(z)
        P = jnp.mean(s / jnp.sum(s, -1, keepdims=True), axis=1)
        return jnp.mean(jnp.sum(f * P, -1))
    _close(gz, jax.grad(fixed)(logits), rtol=1e-5, atol=1e-9)


def test_the_models_balance_gradient_reaches_the_routers(ref):
    """Through the model: with the task loss left out, ``aux['loss']``
    alone moves both expert layers' ``Wr``."""
    model = Moonlight3D(widths=SMALL)
    params = _params(model, 1, 64)
    x, _ = _batch(1, 64, rows=2)
    g = jax.jit(jax.grad(lambda p: _apply(model, p, x)[1]["loss"]))(params)
    top = lambda i, name: float(jnp.max(jnp.abs(
        g[f"layers_{i}"]["moe"][name])))
    assert top(1, "router") > 0 and top(2, "router") > 0
    # the last layer's experts feed no later router; the first's feed one
    assert top(2, "up") == 0.0 and top(2, "down") == 0.0
    assert top(1, "up") > 0
    assert float(jnp.max(jnp.abs(g["head"]["kernel"]))) == 0.0
    # a batch of two: the mean of the two rows' own L
    balance = jax.jit(lambda x: _apply(model, params, x)[1]["loss"])
    both = balance(x)
    rows = [balance(x[i:i + 1]) for i in (0, 1)]
    _close(both, (rows[0] + rows[1]) / 2, rtol=1e-5)


# ---------- the trainer's protocol ----------

def test_the_model_declares_what_the_trainer_reads():
    from neuroimagedisttraining_tpu.config import OptimConfig
    from neuroimagedisttraining_tpu.core.trainer import LocalTrainer

    model = create_model("moonlight3d")
    w = model.widths
    assert (w.hidden_size, w.heads, w.qk_nope_head_dim, w.qk_rope_head_dim,
            w.v_head_dim, w.kv_lora_rank) == (2048, 16, 128, 64, 128, 512)
    assert (w.intermediate_size, w.expert_width, w.num_experts,
            w.experts_per_token, w.shared_experts) == (11264, 1408, 64, 6, 2)
    assert (w.dense_layers, w.expert_layers, w.held, w.patch) == (
        1, 5, (0, 8), 8)
    assert model.returns_aux and model.remat_layers
    assert model.aux_counters == ("expert_tokens", "held_overflow_calls",
                                  "attn_kernel_calls", "attn_outputs_kept")
    assert model.held_experts == (0, 8)
    assert model.row_tokens((121, 145, 121)) == 4864
    assert LocalTrainer(model, OptimConfig(), 1).eval_batch_rows(
        (121, 145, 121)) == 4
    # 2 x 4,864 tokens x 6 slots = 58,368; twice the uniform eighth
    assert model.held_capacity_rows((2, 121, 145, 121, 1)) == 58368 // 4
    shapes = jax.eval_shape(
        model.init, jax.random.key(0), jnp.zeros((1, 121, 145, 121, 1)))
    sizes = {k: sum(int(np.prod(a.shape)) for a in jax.tree.leaves(v))
             for k, v in shapes["params"].items()}
    mla = sum(int(np.prod(a.shape)) for a in jax.tree.leaves(
        shapes["params"]["layers_0"]["mla"]))
    assert mla == 13_763_072
    assert sizes["layers_0"] == 82_973_184
    assert sizes["layers_1"] == 100_405_760
    assert sum(sizes.values()) == 585_001_984 + 512 * 2048 + 2048 * 3



# ---------- the attention kernel's counter (PR 42) ----------

@pytest.mark.parametrize("kernel", [False, True])
def test_folded_train_logs_the_attention_kernels_calls(tmp_path, monkeypatch,
                                                       kernel):
    """Every round's ``round_log`` span carries ``attn_kernel_calls``
    beside the routing counters: the attention calls of the round's real
    steps that ran as the kernel, and ``attn_outputs_kept``, those of them
    whose outputs the layer kept for its backward pass (PR 45: all of
    them). Both 0 here as it stands (off the TPU the XLA form runs); with
    the choice answered as a TPU answers it at widths the blocks tile (and
    the kernel stood in for, the small widths tile no block), three layers
    a real step, none from the eager initialisation or the evaluation."""
    from neuroimagedisttraining_tpu.config import (
        DataConfig, ExperimentConfig, FedConfig, OptimConfig,
    )
    from neuroimagedisttraining_tpu.core.trainer import LocalTrainer
    from neuroimagedisttraining_tpu.data.federate import federate_cohort
    from neuroimagedisttraining_tpu.data.synthetic import (
        generate_synthetic_abcd,
    )
    from neuroimagedisttraining_tpu.engines import create_engine
    from neuroimagedisttraining_tpu.obs import names as obs_names
    from neuroimagedisttraining_tpu.obs import trace as obs_trace
    from neuroimagedisttraining_tpu.ops import attention
    from neuroimagedisttraining_tpu.utils.logging import ExperimentLogger

    eager = []
    if kernel:
        monkeypatch.setattr(attention, "takes_kernel",
                            lambda T, dk, ds, dv, kernel, *a: kernel)
        monkeypatch.setattr(
            attention, "attention_kernel",
            lambda q, k, v, qs, ks, window=None: eager.append(
                not isinstance(q, jax.core.Tracer))
            or attention.blocked_causal_attention(
                jnp.concatenate([q, qs], -1),
                jnp.concatenate([k, jnp.broadcast_to(ks, qs.shape)], -1),
                v, BLOCK, q.dtype))
    cohort = generate_synthetic_abcd(num_subjects=24, shape=_shape(24),
                                     num_sites=2, seed=0)
    cohort["site"] = np.repeat(np.arange(2), (16, 8)).astype(
        cohort["site"].dtype)
    cfg = ExperimentConfig(
        model="moonlight3d", num_classes=1, algorithm="fedavg",
        data=DataConfig(dataset="synthetic", partition_method="site"),
        optim=OptimConfig(lr=1e-2, batch_size=4, epochs=1),
        fed=FedConfig(client_num_in_total=2, comm_round=2),
        log_dir=str(tmp_path), tag=f"kernel{kernel}")
    tr = LocalTrainer(Moonlight3D(widths=SMALL), cfg.optim, 1)
    fed, _ = federate_cohort(cohort, partition_method="site", mesh=None)
    eng = create_engine("fedavg", cfg, fed, tr, mesh=None,
                        logger=ExperimentLogger(
                            str(tmp_path), "synthetic", cfg.identity(),
                            console=False))
    eng._fold_budget_bytes = 1
    obs_trace.arm()
    try:
        eng.train()
        logs = [e["args"] for e in obs_trace.TRACER.events()
                if e["ph"] == "X" and e["name"] == obs_names.SPAN_ROUND_LOG]
    finally:
        obs_trace.disarm()
    assert [a["round"] for a in logs] == [0, 1]
    real_steps = int(np.ceil(np.asarray(eng.data.n_train) / 4).sum())
    for a in logs:
        assert a["attn_kernel_calls"] == (real_steps * 3 if kernel else 0)
        assert a["attn_outputs_kept"] == a["attn_kernel_calls"]
        assert a["held_overflow_calls"] >= 0 and a["rows_held"] > 0
        assert a["tokens_routed"] == real_steps * 4 * 24 * K * 2
    assert not any(eager)  # the eager initialisation says kernel=False


def test_on_a_tpu_the_kernels_bodies_keep_scores_and_softmax_float32(
        monkeypatch):
    """Where the scores live since PR 42: at widths the kernel's blocks
    tile, traced as a TPU traces it, every layer's attention is two
    ``pallas_call``s of the gradient step (forward and backward: the
    rematerialised layer keeps the forward's outputs, PR 45) and no block
    of scores is left outside them; inside
    their bodies every exponential, logarithm, maximum and sum is float32
    and every product accumulates in float32 under ``bf16_mixed``:
    bfloat16 enters a product as an operand and leaves a body as an
    output, nowhere else."""
    # dense layers alone: the experts' grouped matmul asks the platform
    # too, and no megablox tile fits the small widths
    tile = dataclasses.replace(SMALL, dense_layers=3, expert_layers=0,
                               heads=2, qk_nope_head_dim=128,
                               qk_rope_head_dim=64, v_head_dim=128)
    monkeypatch.setattr(jax, "default_backend", lambda: "tpu")
    model = Moonlight3D(widths=tile, dtype=jnp.bfloat16)
    x = jnp.zeros((2, 16, 128, 4, 1))  # 128 tokens: one block
    params = jax.eval_shape(
        Moonlight3D(widths=tile).init, jax.random.key(0), x[:1])["params"]

    def loss(p):
        logits, aux = model.apply({"params": p}, x)
        return jnp.sum(logits) + aux["loss"], aux

    traced = jax.make_jaxpr(jax.grad(loss, has_aux=True))(params)
    _, inside = attention_kernel_bodies(traced, layers=3)
    # no block of scores [B, heads, queries, keys] is left in XLA
    outside = [e for e in _all_eqns(traced.jaxpr)
               if not any(e is i for i in inside)]
    assert not [e for e in outside if e.primitive.name in ("exp",
                                                           "dot_general")
                and e.outvars[0].aval.shape[:2] == (2, 2)]


def test_through_the_interpreted_kernel_the_model_is_the_xla_forms(
        monkeypatch):
    """The whole small trunk at widths the kernel's blocks tile (256
    tokens: two blocks), its attention through the kernels' own bodies in
    Pallas' interpreter (the choice answered as a TPU answers it), against
    the same trunk on the XLA form: logits, the balance loss, the counters
    and every gradient, under ``nn.remat`` as the trainer runs it."""
    import functools

    from neuroimagedisttraining_tpu.ops import attention

    tile = dataclasses.replace(SMALL, heads=2, qk_nope_head_dim=128,
                               qk_rope_head_dim=64, v_head_dim=128,
                               block=128)
    model = Moonlight3D(widths=tile)
    x, y = _batch(7, 256, rows=2)
    params = _jitter(model.init(jax.random.key(7), jnp.zeros(
        (1,) + _shape(256) + (1,)))["params"], 7)

    def run(p):
        def loss(p):
            logits, aux = _apply(model, p, x)
            return (jnp.sum(logits * (2.0 * y[:, None] - 1)) + aux["loss"],
                    (logits, aux))
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
    _close(aux["loss"], want_aux["loss"])
    np.testing.assert_array_equal(aux["expert_tokens"],
                                  want_aux["expert_tokens"])
    for (path, g), h in zip(
            jax.tree_util.tree_flatten_with_path(got_grads)[0],
            jax.tree.leaves(want_grads)):
        top = float(jnp.max(jnp.abs(h)))
        _close(g, h, rtol=F32_RTOL * 10, atol=F32_ATOL * max(top, 1e-30)
               * 20)
