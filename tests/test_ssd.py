"""``ops/ssd.py``: the chunked state-space scan against the recurrence it
computes, token by token (``benchmark/reference/nemotronh_ops.py``
``selective_scan``), forward and ``jax.grad``, in float32 on the CPU.

Sizes are small and shaped like the published layer: heads in groups that
share ``B`` and ``C``, a state wider than a head, ``dt`` and ``A`` drawn as
the layer initialises them (``dt`` in [1e-3, 1e-1], ``A = -(1..H)``), so
that decays across a chunk run from nearly 1 to nearly 0.
"""

import importlib.util
import os

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from neuroimagedisttraining_tpu.ops import ssd

_spec = importlib.util.spec_from_file_location(
    "ref_nemotronh_ops", os.path.join(
        os.path.dirname(os.path.dirname(os.path.abspath(__file__))),
        "benchmark", "reference", "nemotronh_ops.py"))
_ref = importlib.util.module_from_spec(_spec)
_spec.loader.exec_module(_ref)
#: the recurrence itself, token by token, float32: the benchmark's plain
#: reference (a ``lax.scan`` over the tokens)
recurrent = _ref.selective_scan

#: float32, the same sums in another order (chunked against sequential):
#: values of order 1, 1.2e-7 a product, the longest chain 3 chunks x 8
#: tokens with decays below 1. A bfloat16 run of the chunked form is off
#: by 1e-2 (asserted below): the bound is about precision, not slack.
RTOL, ATOL = 2e-5, 2e-6
b, H, P, G, N = 2, 4, 8, 2, 16
#: (tokens, chunk, D): several chunks, one chunk, a sequence shorter than a
#: chunk, and the skip term off and on
CASES = {"three_chunks": (24, 8, 0.0), "three_chunks_D": (24, 8, 1.0),
         "one_chunk": (8, 8, 0.0), "one_chunk_D": (8, 8, 0.7),
         "shorter_than_a_chunk": (6, 128, 0.5),
         "five_chunks_D": (40, 8, 1.3)}


def _inputs(T, D, seed=0):
    r = np.random.RandomState(seed)
    f = lambda *s: jnp.asarray(r.randn(*s), jnp.float32)
    dt = jnp.asarray(np.exp(r.uniform(np.log(1e-3), np.log(1e-1),
                                      (b, T, H))), jnp.float32)
    A = -jnp.arange(1, H + 1, dtype=jnp.float32)
    return (f(b, T, H, P), dt, A, f(b, T, G, N), f(b, T, G, N),
            jnp.full((H,), D, jnp.float32))


@pytest.mark.parametrize("case", sorted(CASES))
def test_chunked_forward_is_the_recurrence(case):
    T, chunk, D = CASES[case]
    args = _inputs(T, D)
    got = ssd.ssd_chunked(*args, chunk)
    want = recurrent(*args)
    assert got.shape == want.shape == (b, T, H, P)
    np.testing.assert_allclose(got, want, rtol=RTOL, atol=ATOL)
    if D:  # the skip term is really there
        off = ssd.ssd_chunked(*args[:5], jnp.zeros((H,)), chunk)
        assert float(jnp.max(jnp.abs(off - want))) > 0.1


@pytest.mark.parametrize("case", sorted(CASES))
def test_chunked_gradients_are_the_recurrences(case):
    """``jax.grad`` of a scalar of ``y`` with respect to every operand:
    ``x``, ``dt``, ``A``, ``B``, ``C``, ``D``."""
    T, chunk, D = CASES[case]
    args = _inputs(T, D, seed=1)
    w = jnp.asarray(np.random.RandomState(2).randn(b, T, H, P), jnp.float32)
    g = jax.grad(lambda *a: jnp.sum(ssd.ssd_chunked(*a, chunk) * w),
                 argnums=tuple(range(6)))(*args)
    g_ref = jax.grad(lambda *a: jnp.sum(recurrent(*a) * w),
                     argnums=tuple(range(6)))(*args)
    for name, a, r in zip(("x", "dt", "A", "B", "C", "D"), g, g_ref):
        assert np.isfinite(np.asarray(a)).all(), name
        scale = float(jnp.max(jnp.abs(r)))
        assert scale > 0, name
        np.testing.assert_allclose(a, r, rtol=RTOL * 10, atol=ATOL * scale
                                   * 10, err_msg=name)


def test_bfloat16_operands_fail_the_float32_bound():
    T, chunk, D = CASES["three_chunks_D"]
    args = _inputs(T, D)
    want = recurrent(*args)
    x, dt, A, B, C, Dv = args
    low = ssd.ssd_chunked(x.astype(jnp.bfloat16), dt, A,
                          B.astype(jnp.bfloat16), C.astype(jnp.bfloat16),
                          Dv, chunk)
    assert low.dtype == jnp.bfloat16
    err = float(jnp.max(jnp.abs(low.astype(jnp.float32) - want)))
    assert 1e-3 < err < 0.5


def test_refuses_a_length_that_is_no_whole_number_of_chunks():
    with pytest.raises(ValueError, match="whole chunks"):
        ssd.ssd_chunked(*_inputs(20, 0.0), 8)


# ---------- the kernels (ops/ssd.py ``ssd_kernel``), interpreted ----------
#
# Pallas' interpreter runs the kernels' own bodies on the CPU: the same
# grid, blocks, carried scratch and reverse sweep the chip runs, in
# float32. Shapes are the smallest the blocks tile (chunks and a state of
# 128, whole lane tiles of heads).

#: (tokens, D, heads, channels a head, groups): two groups of eight heads,
#: four to a lane tile; heads of the published layer's 64 channels, two to
#: a lane tile; a head that is a whole lane tile; one chunk and several
KERNEL_CASES = {"three_chunks": (384, 0.0, 16, 32, 2),
                "three_chunks_D": (384, 1.0, 16, 32, 2),
                "one_chunk": (128, 0.0, 16, 32, 2),
                "one_chunk_D": (128, 0.7, 16, 32, 2),
                "two_chunks_heads_of_64_D": (256, 1.3, 16, 64, 2),
                "two_chunks_a_head_a_tile_D": (256, 0.5, 8, 128, 1)}
KQ, KN = 128, 128


def _kernel_inputs(T, D, heads, P, G, seed=0, batch=2):
    """As :func:`_inputs`: ``dt`` in [1e-3, 1e-1] and ``A = -(1..H)``, so
    that decays across a chunk of 128 run from 0.88 to e^-100."""
    r = np.random.RandomState(seed)
    f = lambda *s: jnp.asarray(r.randn(*s), jnp.float32)
    dt = jnp.asarray(np.exp(r.uniform(np.log(1e-3), np.log(1e-1),
                                      (batch, T, heads))), jnp.float32)
    A = -jnp.arange(1, heads + 1, dtype=jnp.float32)
    return (f(batch, T, heads, P), dt, A, f(batch, T, G, KN),
            f(batch, T, G, KN), jnp.full((heads,), D, jnp.float32))


@pytest.mark.parametrize("case", sorted(KERNEL_CASES))
def test_kernel_forward_is_the_recurrence(case):
    T, D, heads, P, G = KERNEL_CASES[case]
    args = _kernel_inputs(T, D, heads, P, G)
    got = ssd.ssd_kernel(*args, KQ, interpret=True)
    want = recurrent(*args)
    assert got.shape == want.shape == (2, T, heads, P)
    # sums of 128 x 128 products of order 1: the absolute bound follows
    # the values' scale, as the gradients' does above
    scale = float(jnp.max(jnp.abs(want)))
    assert scale > 1
    np.testing.assert_allclose(got, want, rtol=RTOL, atol=ATOL * scale)
    if D:
        off = ssd.ssd_kernel(*args[:5], jnp.zeros((heads,)), KQ,
                             interpret=True)
        assert float(jnp.max(jnp.abs(off - want))) > 0.1


@pytest.mark.parametrize("case", sorted(KERNEL_CASES))
def test_kernel_gradients_are_the_recurrences(case):
    """The backward kernel (a reverse sweep over the chunks carrying the
    state's cotangent) against autodiff of the token-by-token recurrence,
    for every operand."""
    T, D, heads, P, G = KERNEL_CASES[case]
    args = _kernel_inputs(T, D, heads, P, G, seed=1)
    w = jnp.asarray(np.random.RandomState(2).randn(2, T, heads, P),
                    jnp.float32)
    g = jax.grad(lambda *a: jnp.sum(ssd.ssd_kernel(*a, KQ, interpret=True)
                                    * w), argnums=tuple(range(6)))(*args)
    g_ref = jax.grad(lambda *a: jnp.sum(recurrent(*a) * w),
                     argnums=tuple(range(6)))(*args)
    for name, a, r in zip(("x", "dt", "A", "B", "C", "D"), g, g_ref):
        assert np.isfinite(np.asarray(a)).all(), name
        scale = float(jnp.max(jnp.abs(r)))
        assert scale > 0, name
        np.testing.assert_allclose(a, r, rtol=RTOL * 10, atol=ATOL * scale
                                   * 10, err_msg=name)


def test_kernel_with_bfloat16_operands_is_within_the_plain_forms_error():
    """bf16 operands, float32 decays and accumulation, in both: the two
    differ from each other by no more than the plain form differs from
    the float32 recurrence (only the order of the sums is another)."""
    T, D, heads, P, G = KERNEL_CASES["two_chunks_heads_of_64_D"]
    args = _kernel_inputs(T, D, heads, P, G)
    want = recurrent(*args)
    x, dt, A, B, C, Dv = args
    low = (x.astype(jnp.bfloat16), dt, A, B.astype(jnp.bfloat16),
           C.astype(jnp.bfloat16), Dv)
    plain = ssd.ssd_chunked(*low, KQ).astype(jnp.float32)
    got = ssd.ssd_kernel(*low, KQ, interpret=True)
    assert got.dtype == jnp.bfloat16
    plain_err = float(jnp.max(jnp.abs(plain - want)))
    assert 1e-3 < plain_err < 0.5
    assert float(jnp.max(jnp.abs(got.astype(jnp.float32) - plain))) \
        <= plain_err
    grads = lambda f: jax.grad(
        lambda *a: jnp.sum(f(*a).astype(jnp.float32) ** 2),
        argnums=(0, 1, 3, 4))(*low)
    g_plain = grads(lambda *a: ssd.ssd_chunked(*a, KQ))
    g_kernel = grads(lambda *a: ssd.ssd_kernel(*a, KQ, interpret=True))
    g_true = jax.grad(lambda *a: jnp.sum(recurrent(*a) ** 2),
                      argnums=(0, 1, 3, 4))(*args)
    norm = lambda a: float(jnp.linalg.norm(a.astype(jnp.float32).ravel()))
    for name, k, p, t in zip(("x", "dt", "B", "C"), g_kernel, g_plain,
                             g_true):
        plain_err = norm(p.astype(jnp.float32) - t) / norm(t)
        assert 1e-4 < plain_err < 0.1, name
        assert norm(k.astype(jnp.float32) - t) / norm(t) \
            <= 1.5 * plain_err, name


#: (backend, tokens, chunk, heads, channels, groups, state, kernel=, takes
#: the kernel): the published layer on a TPU does; the same off it, an
#: eager caller, and shapes the blocks cannot tile do not
ROUTES = {
    "published_on_tpu": ("tpu", 640, 128, 64, 64, 8, 128, True, True),
    "one_chunk_on_tpu": ("tpu", 128, 128, 16, 32, 2, 128, True, True),
    "published_on_cpu": ("cpu", 640, 128, 64, 64, 8, 128, True, False),
    "eager_caller_on_tpu": ("tpu", 640, 128, 64, 64, 8, 128, False, False),
    "chunk_of_8": ("tpu", 24, 8, 4, 8, 2, 16, True, False),
    "shorter_than_a_chunk": ("tpu", 6, 128, 8, 32, 1, 128, True, False),
    "state_of_64": ("tpu", 256, 128, 16, 32, 2, 64, True, False),
    "heads_of_96": ("tpu", 256, 128, 8, 96, 1, 128, True, False),
    "groups_of_4_heads": ("tpu", 256, 128, 8, 64, 2, 128, True, False),
}


@pytest.mark.parametrize("route", sorted(ROUTES))
def test_which_form_runs_follows_platform_and_shapes(monkeypatch, route):
    backend, T, chunk, heads, P, G, N, kernel, wanted = ROUTES[route]
    took = []
    monkeypatch.setattr(jax, "default_backend", lambda: backend)
    monkeypatch.setattr(
        ssd, "ssd_kernel",
        lambda x, *a, **k: took.append(a[-1]) or jnp.zeros_like(x))
    s = jax.ShapeDtypeStruct
    f32 = jnp.float32
    y = jax.eval_shape(
        lambda *a: ssd.ssd_chunked(*a, chunk, kernel=kernel),
        s((2, T, heads, P), jnp.bfloat16), s((2, T, heads), f32),
        s((heads,), f32), s((2, T, G, N), jnp.bfloat16),
        s((2, T, G, N), jnp.bfloat16), s((heads,), f32))
    assert (y.shape, y.dtype) == ((2, T, heads, P), jnp.bfloat16)
    assert took == ([min(chunk, T)] if wanted else [])
    assert ssd.kernel_tiles(min(chunk, T), heads // G, P, N) == (
        wanted or route in ("published_on_cpu", "eager_caller_on_tpu"))


def _kernel_names(jaxpr) -> list[str]:
    import re

    return re.findall(r"name=(ssd_\w+)", str(jaxpr))


def test_under_remat_a_layer_holds_the_three_kernels_once_each():
    """As the trunk runs it (``nn.remat`` is ``jax.checkpoint``): the
    forward, the rematerialised forward that also writes the chunks'
    start states, the backward; nothing is traced a second time."""
    T, D, heads, P, G = KERNEL_CASES["three_chunks_D"]
    args = _kernel_inputs(T, D, heads, P, G)
    layer = jax.checkpoint(lambda *a: ssd.ssd_kernel(*a, KQ, interpret=True))
    jaxpr = jax.make_jaxpr(jax.grad(lambda *a: jnp.sum(layer(*a)),
                                    argnums=(0, 1, 2, 3, 4, 5)))(*args)
    assert sorted(_kernel_names(jaxpr)) == ["ssd_backward", "ssd_forward",
                                            "ssd_forward"]


def test_under_a_scan_over_clients_the_kernels_are_traced_once():
    """The folded round is a ``lax.scan`` over clients: one forward and
    one backward kernel in its body whatever the number of clients, and
    the same gradients as client by client."""
    T, D, heads, P, G = KERNEL_CASES["one_chunk_D"]
    clients = [_kernel_inputs(T, D, heads, P, G, seed=s, batch=1)
               for s in range(3)]
    stacked = jax.tree.map(lambda *a: jnp.stack(a), *clients)
    loss = lambda a: jnp.sum(ssd.ssd_kernel(*a, KQ, interpret=True) ** 2)

    def fold(stacked):
        return jax.lax.scan(
            lambda total, a: (total + loss(a), None), 0.0, stacked)[0]

    jaxpr = jax.make_jaxpr(jax.grad(fold))(stacked)
    assert sorted(_kernel_names(jaxpr)) == ["ssd_backward", "ssd_forward"]
    got = jax.grad(fold)(stacked)
    for i, client in enumerate(clients):
        want = jax.grad(loss)(client)
        for name, a, r in zip(("x", "dt", "A", "B", "C", "D"), got, want):
            np.testing.assert_allclose(
                a[i], r, rtol=RTOL, err_msg=name,
                atol=ATOL * float(jnp.max(jnp.abs(r))))
