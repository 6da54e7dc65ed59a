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
