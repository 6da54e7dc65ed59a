"""ops/attention.py: the causal attention kernels against the XLA form
(PR 42), on the CPU.

Pallas' interpreter runs the kernels' own bodies here: the same grid,
blocks, loops over key and query blocks, running maximum and sum, carried
``dq`` buffer and sum over heads the chip runs. Shapes are the smallest the
blocks tile: heads of Moonlight's published 128 score + 64 shared rotary
and 128 value dimensions (a score is 192 wide where its value is 128), a
few heads, one to three blocks of 256 tokens (the chip's) or of 128.

What the cell's ``correct`` cannot see (PERF.md section 7, item 17a) is
held here: with scores of standard deviation 16 a kernel whose scores were
bfloat16 anywhere would miss the float32 tolerance by orders of magnitude,
and the control below shows that the tolerance resolves it.

Since PR 44 the same cases run over the kernels' other forms (``VARIANTS``):
heads of 128 score dimensions without a shared part, under a sliding
window of two blocks, grouped four query heads to a key/value head (the
head map in the index maps; ``dk``, ``dv`` summed over the group in the
backward), and both at once (Trinity-Mini's sliding layers,
models/trinity3d.py).
"""

import functools
import re

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from neuroimagedisttraining_tpu.ops import attention

DN, DR, DV, BLOCK = 128, 64, 128, 128
#: (tokens, heads): the block is the largest of 256 and 128 that tiles
CASES = {"one_block_of_256": (256, 2), "two_blocks_of_256": (512, 2),
         "three_blocks_of_256": (768, 2), "three_blocks_of_128": (384, 3),
         "one_block_of_128": (128, 2)}
#: float32, kernel against XLA form: the same products summed in another
#: order (a running maximum over tiles against one softmax a block)
TOL = 1e-5
#: (a shared rotary part, the window in blocks, query heads a key/value
#: head). ``shared`` is Moonlight's layer, the cases as PR 42 wrote them;
#: the others have heads of 128 score dimensions alone
VARIANTS = {"shared": (True, None, 1), "alone": (False, None, 1),
            "window": (False, 2, 1), "grouped": (False, None, 4),
            "window_grouped": (False, 2, 4)}
#: every case on Moonlight's form; the others where a window of two
#: blocks closes (three blocks)
PAIRS = [(c, "shared") for c in sorted(CASES)] + [
    (c, v) for c in ("three_blocks_of_128", "three_blocks_of_256")
    for v in sorted(VARIANTS) if v != "shared"]


def _window(tokens, variant):
    blocks = VARIANTS[variant][1]
    return None if blocks is None else blocks * attention._block_of(tokens)


def _operands(tokens, heads, seed=0, score_std=1.0, rows=2,
              variant="shared"):
    """``qn, qr, kn, kr, v``, float32; the scaled scores have standard
    deviation ``score_std`` (unit operands give 1: 192 products over
    192^1/2). Without a shared part ``qr`` and ``kr`` are ``None``;
    grouped, ``qn`` is ``[rows, tokens, Hkv, G, DN]`` on ``Hkv`` = 2
    key/value heads (one at 768 tokens: the interpreter's time).

    Above 1 the queries and keys are drawn ON A GRID (multiples of 1/4
    and 1/8): their products and any sum of 192 of them are then exact in
    float32 in whatever order, so the two forms' scores are the same
    numbers and what is compared is what happens to them afterwards. Off
    the grid two float32 forms are 5e-5 apart at a standard deviation of
    16 by the scores' own rounding alone (a score of 80 is known to
    7.6e-6, and a probability follows it), which would hide a tolerance
    of 1e-5 behind the operands' noise."""
    keys = jax.random.split(jax.random.key(seed), 5)
    shared, _, groups = VARIANTS[variant]
    if groups > 1:
        heads = 2 if tokens < 768 else 1
    shape = lambda d, a=heads: (rows, tokens, a, d)

    def draw(key, shape, by=1.0, step=None):
        x = by * jax.random.normal(key, shape)
        return x if step is None else jnp.round(x / step) * step

    q_step, k_step = (None, None) if score_std == 1.0 else (0.25, 0.125)
    q_shape = shape(DN) if groups == 1 else (rows, tokens, heads, groups, DN)
    return (draw(keys[0], q_shape, score_std, q_step),
            draw(keys[1], shape(DR), score_std, q_step) if shared else None,
            draw(keys[2], shape(DN), step=k_step),
            draw(keys[3], shape(DR, 1), step=k_step) if shared else None,
            draw(keys[4], shape(DV)))


def _joined(qn, qr, kn, kr):
    """The XLA form's operands: the shared key repeated beside each
    head's own (the heads' own alone where there is no shared part)."""
    if qr is None:
        return qn, kn
    return (jnp.concatenate([qn, qr], -1),
            jnp.concatenate([kn, jnp.broadcast_to(kr, qr.shape)], -1))


def xla_form(qn, qr, kn, kr, v, dtype=jnp.float32, window=None):
    q, k = _joined(qn, qr, kn, kr)
    return attention.blocked_causal_attention(q, k, v, BLOCK, dtype, window)


def kernel(qn, qr, kn, kr, v, window=None):
    return attention.attention_kernel(qn, kn, v, qr, kr, window=window,
                                      interpret=True)


def dense(qn, qr, kn, kr, v, rounded=None, window=None):
    """One dense masked block; ``rounded``: the scores through that dtype
    and back (the control). Grouped heads: the keys and values repeated
    over their groups."""
    q, k = _joined(qn, qr, kn, kr)
    if q.ndim == 5:
        k, v = (jnp.repeat(a, q.shape[3], axis=2) for a in (k, v))
        q = q.reshape(*q.shape[:2], -1, q.shape[-1])
    T = q.shape[1]
    s = jnp.einsum("bqad,bkad->baqk", q, k) / np.sqrt(q.shape[-1])
    if rounded is not None:
        s = s.astype(rounded).astype(jnp.float32)
    seen = jnp.tril(jnp.ones((T, T), bool))
    if window is not None:
        seen &= ~jnp.tril(jnp.ones((T, T), bool), -window)
    s = jnp.where(seen, s, -jnp.inf)
    out = jnp.einsum("baqk,bkad->bqad", jax.nn.softmax(s, axis=-1), v)
    return out.reshape(*out.shape[:2], -1)


def _forms(tokens, variant):
    """``(kernel, xla_form, dense)`` under the variant's window."""
    w = _window(tokens, variant)
    return tuple(functools.partial(f, window=w)
                 for f in (kernel, xla_form, dense))


def _max_diff(a, b):
    return float(jnp.max(jnp.abs(a.astype(jnp.float32)
                                 - b.astype(jnp.float32))))


# ---------- (a) forward, (b) its control ----------

@pytest.mark.parametrize("score_std", [1.0, 16.0])
@pytest.mark.parametrize("case, variant", PAIRS)
def test_forward_is_the_xla_forms_to_float32(case, variant, score_std):
    """Scores of standard deviation 16 put weight on their low bits: a
    softmax over them is near one-hot, and what it selects follows
    differences of a few parts in a thousand."""
    tokens, heads = CASES[case]
    args = _operands(tokens, heads, score_std=score_std, variant=variant)
    kernel_, xla_form_, dense_ = _forms(tokens, variant)
    got, want = jax.jit(kernel_)(*args), jax.jit(xla_form_)(*args)
    assert got.shape == want.shape == (
        2, tokens, int(np.prod(args[0].shape[2:-1])) * DV)
    assert got.dtype == jnp.float32
    assert DV != DN + DR
    assert float(jnp.max(jnp.abs(want))) > 1
    np.testing.assert_allclose(got, want, rtol=TOL, atol=TOL)
    if _window(tokens, variant) is not None:
        # the window is live: the whole triangle gives other values
        assert _max_diff(want, jax.jit(xla_form)(*args)) > 0.1


@pytest.mark.parametrize("case, variant", PAIRS)
def test_bfloat16_scores_miss_that_tolerance_tenfold(case, variant):
    """The control: the dense reference agrees with both forms at the
    tolerance; with its scores rounded to bfloat16 it misses by more than
    ten times. So the tolerance resolves what ``correct`` cannot."""
    tokens, heads = CASES[case]
    args = _operands(tokens, heads, score_std=16.0, variant=variant)
    kernel_, xla_form_, dense_ = _forms(tokens, variant)
    want = jax.jit(xla_form_)(*args)
    np.testing.assert_allclose(jax.jit(dense_)(*args), want, rtol=TOL,
                               atol=TOL)
    low = jax.jit(lambda *a: dense_(*a, rounded=jnp.bfloat16))(*args)
    excess = jnp.abs(low - want) - TOL * jnp.abs(want)
    assert float(jnp.max(excess)) > 10 * TOL
    assert _max_diff(jax.jit(kernel_)(*args), want) < _max_diff(low,
                                                                want) / 100


def test_without_a_shared_part_it_is_plain_causal_attention():
    """The shared key is optional (ROADMAP D17: other callers have none)."""
    qn, _, kn, _, v = _operands(384, 2, score_std=4.0)
    f = lambda fn: jax.jit(jax.value_and_grad(
        lambda *a: jnp.sum(jnp.sin(fn(*a))), argnums=(0, 1, 2)))(qn, kn, v)
    (got, g_got), (want, g_want) = (
        f(lambda q, k, v: attention.attention_kernel(q, k, v,
                                                     interpret=True)),
        f(lambda q, k, v: attention.blocked_causal_attention(
            q, k, v, BLOCK, jnp.float32)))
    np.testing.assert_allclose(got, want, rtol=TOL)
    for g, h in zip(g_got, g_want):
        np.testing.assert_allclose(g, h, rtol=TOL * 10, atol=TOL * float(
            jnp.max(jnp.abs(h))))


# ---------- (c) gradients ----------

@pytest.mark.parametrize("wrap", ["plain", "checkpoint"])
@pytest.mark.parametrize("score_std", [1.0, 16.0])
@pytest.mark.parametrize("case, variant", PAIRS)
def test_gradients_are_autodiffs_of_the_xla_form(case, variant, score_std,
                                                 wrap):
    """The backward kernel (probabilities remade from the log-sum-exp,
    ``dq`` gathered over the key blocks, the shared key's cotangent over
    the heads, a key/value head's ``dk`` and ``dv`` over its group of
    query heads) against autodiff of the XLA form, for every operand; also
    as the trunk runs it, under ``jax.checkpoint`` (``nn.remat``)."""
    tokens, heads = CASES[case]
    args = _operands(tokens, heads, seed=1, score_std=score_std,
                     variant=variant)
    kernel_, xla_form_, _ = _forms(tokens, variant)
    w = jax.random.normal(jax.random.key(2), (
        2, tokens, int(np.prod(args[0].shape[2:-1])) * DV))
    fn = jax.checkpoint(kernel_) if wrap == "checkpoint" else kernel_
    given = tuple(i for i, a in enumerate(args) if a is not None)
    grads = lambda f: jax.jit(jax.grad(
        lambda *a: jnp.sum(f(*a) * w), argnums=given))(*args)
    names = [("qn", "qr", "kn", "kr", "v")[i] for i in given]
    for name, g, r in zip(names, grads(fn), grads(xla_form_)):
        assert g.shape == r.shape and np.isfinite(np.asarray(g)).all(), name
        top = float(jnp.max(jnp.abs(r)))
        assert top > 0, name
        np.testing.assert_allclose(g, r, rtol=TOL * 10, atol=TOL * top,
                                   err_msg=name)


def _kernel_names(jaxpr) -> list[str]:
    """The kernels of a printed jaxpr (not the names ``KEPT`` gives the
    forward's outputs, which print as ``name=attention_o``)."""
    return re.findall(r"name=(attention_(?:forward|backward))\b", str(jaxpr))


@pytest.mark.parametrize("keeps", [False, True], ids=["full", "keeps"])
def test_under_remat_a_layer_holds_the_three_kernels_once_each(keeps):
    """The forward, the rematerialised forward, the backward: nothing is
    traced a second time, and no residual is ``[T, T]``-shaped. Under a
    policy that keeps the forward's two outputs by name
    (``attention.KEPT``; models/tokens3d.py ``layer_stack``, PR 45) the
    rematerialised forward is not traced either; under none the names are
    identities."""
    tokens, heads = CASES["three_blocks_of_256"]
    assert tokens not in (heads * DN, heads * DV, heads * 2 * DR)
    args = _operands(tokens, heads)
    layer = jax.checkpoint(
        lambda *a: kernel(*a),  # a function a case: jit's trace cache
        policy=jax.checkpoint_policies.save_only_these_names(
            *attention.KEPT) if keeps else None)
    jaxpr = jax.make_jaxpr(jax.grad(lambda *a: jnp.sum(layer(*a)),
                                    argnums=(0, 1, 2, 3, 4)))(*args)
    assert sorted(_kernel_names(jaxpr)) == [
        "attention_backward"] + ["attention_forward"] * (1 if keeps else 2)
    square = [v.aval.shape for e in jaxpr.jaxpr.eqns for v in e.outvars
              if list(v.aval.shape).count(tokens) > 1]
    assert square == []


# ---------- (d) causality and the shared key ----------

@pytest.mark.parametrize("variant", sorted(VARIANTS))
@pytest.mark.parametrize("t", [5, 128, 300])  # one in each block of 384
def test_a_change_at_token_t_leaves_the_outputs_before_t_bitwise_alone(
        t, variant):
    """And under a window of ``W`` keys every row from ``t + W`` on: a key
    and its value reach the queries ``t .. t + W - 1`` and no other."""
    args = _operands(384, 2, seed=t, variant=variant)
    window = _window(384, variant)
    f = jax.jit(_forms(384, variant)[0])
    before = f(*args)
    # keys and values alone under a window: a changed query moves its row
    change = lambda i, a: a if a is None or (window and i < 2) \
        else a.at[:, t].add(1.0)
    after = f(*(change(i, a) for i, a in enumerate(args)))
    np.testing.assert_array_equal(before[:, :t], after[:, :t])
    moved = np.abs(np.asarray(before - after)).reshape(2, 384, -1, DV)
    reach = 384 if window is None else t + window
    assert (moved[:, t:reach].max(-1) > 0).all()
    np.testing.assert_array_equal(before[:, reach:], after[:, reach:])
    assert window is None or t != 5 or reach < 384  # the window closes


@pytest.mark.parametrize("variant", ["window", "window_grouped"])
def test_a_windowed_program_visits_the_windows_blocks_and_no_more(
        monkeypatch, variant):
    """A counting stub in place of the kernels' loop (``attention._loop``):
    five blocks of 128 under a window of two. A forward program at query
    block ``i`` makes ``min(i, 1)`` whole trips and one more, under the
    edge's mask, where block ``i - 2`` exists: with the diagonal's tile,
    ``window / block + 1`` = 3 key blocks and no more, where the whole
    triangle takes ``i + 1``. The backward's sweep over query blocks ends
    where the window does."""
    trips = []
    real = attention._loop

    def counting(lo, hi, body, carry):
        jax.debug.callback(
            lambda lo, hi: trips.append(max(int(hi) - int(lo), 0)), lo, hi)
        return real(lo, hi, body, carry)

    monkeypatch.setattr(attention, "_loop", counting)
    args = _operands(640, 1, variant=variant, rows=1)
    heads = int(np.prod(args[0].shape[2:-1]))

    def counted(fn):
        """The trips of every loop ``fn`` runs, sorted."""
        trips.clear()
        jax.block_until_ready(jax.jit(fn)(*args))
        jax.effects_barrier()
        return sorted(trips)

    run = lambda window: counted(lambda *a: kernel(*a, window=window))
    back = lambda window: counted(jax.grad(
        lambda q, *a: jnp.sum(kernel(q, *a, window=window))))
    # whole trips [0, 1, 1, 1, 1] and edge trips [0, 0, 1, 1, 1] a head
    forward = [0, 1, 1, 1, 1, 0, 0, 1, 1, 1]
    assert run(256) == sorted(forward * heads)
    assert run(None) == sorted([0, 1, 2, 3, 4] * heads)
    # key block j: whole trips over j + 1 .. min(j + 2, 5) - 1, the edge
    # where block j + 2 exists
    assert back(256) == sorted((forward + [1, 1, 1, 1, 0, 1, 1, 1, 0, 0])
                               * heads)
    assert back(None) == sorted(([0, 1, 2, 3, 4] + [4, 3, 2, 1, 0]) * heads)


@pytest.mark.parametrize("t", [5, 128, 300])
def test_one_shared_key_serves_every_head(t):
    """``kr`` alone, at token ``t``: every head's rows from ``t`` on move
    and none before; its gradient is the sum of the heads' own."""
    qn, qr, kn, kr, v = _operands(384, 3, seed=t)
    f = jax.jit(lambda kr: kernel(qn, qr, kn, kr, v))
    before, after = f(kr), f(kr.at[:, t].add(1.0))
    np.testing.assert_array_equal(before[:, :t], after[:, :t])
    moved = np.abs(np.asarray(before - after)).reshape(2, 384, 3, DV)
    assert (moved[:, t:].max(-1) > 0).all()
    # a key of its own for every head, each a copy of the shared one
    own = jnp.broadcast_to(kr, qr.shape)
    g_own = jax.jit(jax.grad(lambda k: jnp.sum(jnp.sin(xla_form_own(
        qn, qr, kn, k, v)))))(own)
    g = jax.jit(jax.grad(lambda k: jnp.sum(jnp.sin(kernel(
        qn, qr, kn, k, v)))))(kr)
    np.testing.assert_allclose(g, g_own.sum(axis=2, keepdims=True),
                               rtol=1e-4, atol=1e-5)


def xla_form_own(qn, qr, kn, kr_own, v):
    """The XLA form with a rotary key a head, ``[B, T, A, dr]``."""
    return attention.blocked_causal_attention(
        jnp.concatenate([qn, qr], -1), jnp.concatenate([kn, kr_own], -1),
        v, BLOCK, jnp.float32)


# ---------- (e) bfloat16 operands ----------

def test_with_bfloat16_operands_it_is_within_the_xla_forms_own_error():
    """bf16 operands, float32 scores, softmax and accumulation, in both:
    the kernel is no further from the float32 answer than the XLA form
    is (only the order of the sums is another)."""
    tokens, heads = CASES["three_blocks_of_128"]
    args = _operands(tokens, heads, score_std=4.0)
    low = tuple(a.astype(jnp.bfloat16) for a in args)
    want = jax.jit(xla_form)(*args)
    plain = jax.jit(lambda *a: xla_form(*a, dtype=jnp.bfloat16))(*low)
    got = jax.jit(kernel)(*low)
    assert got.dtype == plain.dtype == jnp.bfloat16
    plain_err = _max_diff(plain, want)
    assert 1e-3 < plain_err < 0.5
    assert _max_diff(got, want) <= 1.5 * plain_err
    grads = lambda f, a: jax.jit(jax.grad(
        lambda *a: jnp.sum(f(*a).astype(jnp.float32) ** 2),
        argnums=tuple(range(5))))(*a)
    g_true = grads(xla_form, args)
    g_plain = grads(lambda *a: xla_form(*a, dtype=jnp.bfloat16), low)
    g_kernel = grads(kernel, low)
    norm = lambda a: float(jnp.linalg.norm(a.astype(jnp.float32).ravel()))
    for name, k, p, t in zip(("qn", "qr", "kn", "kr", "v"), g_kernel,
                             g_plain, g_true):
        assert k.dtype == jnp.bfloat16, name
        plain_err = norm(p.astype(jnp.float32) - t) / norm(t)
        assert 1e-4 < plain_err < 0.1, name
        assert norm(k.astype(jnp.float32) - t) / norm(t) \
            <= 1.5 * plain_err, name


# ---------- (f) which form runs ----------

#: (backend, tokens, dn, dr, dv, kernel=, takes the kernel): the published
#: layer on a TPU does; the same off it, an eager caller, and shapes the
#: blocks cannot tile do not
ROUTES = {
    "published_on_tpu": ("tpu", 4864, 128, 64, 128, True, True),
    "one_block_on_tpu": ("tpu", 128, 128, 64, 128, True, True),
    "no_shared_part_on_tpu": ("tpu", 640, 128, 0, 128, True, True),
    "published_on_cpu": ("cpu", 4864, 128, 64, 128, True, False),
    "eager_caller_on_tpu": ("tpu", 4864, 128, 64, 128, False, False),
    "tokens_of_the_cpu_tests": ("tpu", 76, 128, 64, 128, True, False),
    "tokens_no_whole_blocks": ("tpu", 4800, 128, 64, 128, True, False),
    "score_width_of_64": ("tpu", 256, 64, 64, 128, True, False),
    "value_width_of_192": ("tpu", 256, 128, 64, 192, True, False),
    "shared_width_of_48": ("tpu", 256, 128, 48, 128, True, False),
    "the_small_widths": ("tpu", 64, 16, 8, 16, True, False),
    "a_sequence_past_vector_memory": ("tpu", 8192, 128, 64, 128, True,
                                      False),
    # (..., window, query heads a key/value head): Trinity-Mini's two
    # kinds of layer; a window of whole blocks only
    "sliding_grouped_on_tpu": ("tpu", 4864, 128, 0, 128, True, True, 2048,
                               8),
    "full_grouped_on_tpu": ("tpu", 4864, 128, 0, 128, True, True, None, 8),
    "sliding_grouped_on_cpu": ("cpu", 4864, 128, 0, 128, True, False, 2048,
                               8),
    "a_window_never_filled": ("tpu", 640, 128, 0, 128, True, True, 2048, 8),
    "a_window_of_no_whole_blocks": ("tpu", 4864, 128, 0, 128, True, False,
                                    2000, 8),
}


@pytest.mark.parametrize("route", sorted(ROUTES))
def test_which_form_runs_follows_platform_and_shapes(monkeypatch, route):
    backend, T, dn, dr, dv, kernel_, wanted, *more = ROUTES[route]
    window, groups = more or (None, 1)
    took = []
    monkeypatch.setattr(jax, "default_backend", lambda: backend)
    monkeypatch.setattr(
        attention, "attention_kernel",
        lambda q, k, v, *a, **kw: took.append(q.shape) or jnp.zeros(
            (*q.shape[:2], int(np.prod(q.shape[2:-1])) * v.shape[-1]),
            v.dtype))
    s = lambda a, d: jax.ShapeDtypeStruct((2, T, a, d), jnp.bfloat16)
    shared = dict(q_shared=s(4, dr), k_shared=s(1, dr)) if dr else {}
    kv = 4 // min(groups, 4)
    q = s(4, dn) if groups == 1 else jax.ShapeDtypeStruct(
        (2, T, kv, groups, dn), jnp.bfloat16)

    def call(q, k, v, shared):
        return attention.causal_attention(q, k, v, 32, jnp.bfloat16,
                                          kernel=kernel_, window=window,
                                          **shared)

    y = jax.eval_shape(call, q, s(kv, dn), s(kv, dv), shared)
    heads = 4 if groups == 1 else kv * groups
    assert (y.shape, y.dtype) == ((2, T, heads * dv), jnp.bfloat16)
    assert took == ([q.shape] if wanted else [])
    assert attention.takes_kernel(T, dn, dr, dv, kernel_, window,
                                  groups) == wanted
    assert attention.kernel_tiles(
        T, dn, dr, dv, attention._closes(window, T), groups) == (
        wanted or route in ("published_on_cpu", "eager_caller_on_tpu",
                            "sliding_grouped_on_cpu"))
    if groups > 1:  # no caller has grouped heads and a shared part
        assert not attention.kernel_tiles(T, dn, 64, dv, None, groups)


@pytest.mark.parametrize("tokens, window", [
    (76, None), (130, None),
    (384, 2 * 128 + 44)])  # two blocks and a non-multiple
def test_refused_shapes_give_the_xla_forms_values(monkeypatch, tokens,
                                                  window):
    """On a TPU, a sequence the blocks cannot tile, or a window of no
    whole number of them: the XLA form's values, bit for bit; and the
    kernel itself says why it will not run."""
    monkeypatch.setattr(jax, "default_backend", lambda: "tpu")
    qn, qr, kn, kr, v = args = _operands(tokens, 2, score_std=4.0)
    got = jax.jit(lambda *a: attention.causal_attention(
        a[0], a[2], a[4], BLOCK, jnp.float32, q_shared=a[1],
        k_shared=a[3], window=window))(*args)
    np.testing.assert_array_equal(got, jax.jit(
        lambda *a: xla_form(*a, window=window))(*args))
    if window is not None:
        assert _max_diff(got, jax.jit(xla_form)(*args)) > 1e-3
    with pytest.raises(ValueError, match="no blocks"):
        attention.attention_kernel(qn, kn, v, qr, kr, window=window,
                                   interpret=True)
