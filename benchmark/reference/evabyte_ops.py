"""Plain float32 building blocks of the EvaByte reference (PR 38).

Beside ``olmoe_ops.py`` and in its spirit (``linear``, ``patches``,
``rope``, ``read_out``, ``bce_with_logits``, ``rounded`` and the tape's
``_record`` are taken from it): straightforward ``jax.numpy``, nothing
imported from the program, every matrix product at ``Precision.HIGHEST``,
each op that does useful work recorded on ``tape`` in one of the two kinds
``flops.py`` knows.

What is recorded for the layers that are new here:

- EVA attention as ``(heads, head_dim)`` at the (query, key) pairs a head
  really has, once for the scores and once for the values: the causal
  pairs inside the windows (``/local_scores``, ``/local_values``) and the
  (query, summary) pairs across them (``/remote_scores``,
  ``/remote_values``). The mask is counted, not assumed: ``pairs`` sums it;
- the feed-forward's three matrices as linear layers at ``T``.

The pooling of a chunk (a ``phi . k`` score, a softmax over 16, two weighted
sums: 6 d operations a token and head, beside 4 d a PAIR and a thousand
pairs a token), the norms, rotary, softmax and SiLU are recorded as
nothing: utilization is of the matrix work.

``q`` is the rounding applied to both operands of every matrix product
(identity in the reference proper), as in ``olmoe_ops.py``; ``q_scores``
the rounding of the attention scores before their softmax.
"""

from __future__ import annotations

import importlib.util
import os

import jax
import jax.numpy as jnp
import numpy as np

_spec = importlib.util.spec_from_file_location(
    "benchmark_reference_olmoe_ops",
    os.path.join(os.path.dirname(os.path.abspath(__file__)), "olmoe_ops.py"))
base = importlib.util.module_from_spec(_spec)
_spec.loader.exec_module(base)

HIGHEST, F32 = base.HIGHEST, base.F32
exact, rounded, _record = base.exact, base.rounded, base._record
linear, patches, rope = base.linear, base.patches, base.rope
read_out, bce_with_logits = base.read_out, base.bce_with_logits


def unit_rms_norm(x, g, eps):
    """``(1 + g) x / sqrt(mean(x^2) + eps)`` (``norm_add_unit_offset``)."""
    var = jnp.mean(jnp.square(x), axis=-1, keepdims=True)
    return (1.0 + g.astype(F32)) * (x * jax.lax.rsqrt(var + eps))


def eva_mask(T: int, window: int, chunk: int) -> np.ndarray:
    """``[T, T + T // chunk]`` bool: what query ``i`` reads. Key ``t``
    where ``t <= i`` in ``i``'s own window; the summary of chunk ``j``
    where the chunk lies in a window before ``i``'s."""
    i = np.arange(T)[:, None]
    t = np.arange(T)[None]
    j = np.arange(T // chunk)[None]
    local = (t <= i) & (t // window == i // window)
    remote = (j * chunk) // window < i // window
    return np.concatenate([local, remote], axis=1)


def eva_attention(q_, k_, v_, phi, mu, window, chunk, *, q=exact,
                  q_scores=exact, tape=None, name=""):
    """EVA over prepared heads, from ONE dense mask a head: ``q_, k_, v_
    [B, T, A, d]`` after rotary, ``phi, mu [A, d]`` -> ``[B, T, A * d]``.
    Every chunk gets its summary key ``sum_t al_t k_t + mu`` and value
    ``sum_t al_t v_t`` (``al`` the softmax over the chunk's tokens of
    ``d^-1/2 phi . k_t``); the summaries are appended to the keys and
    values, and one softmax runs over what the mask lets a query read."""
    B, T, A, d = q_.shape
    J = T // chunk  # a partial last chunk lies in the last window: unread
    scale = 1.0 / jnp.sqrt(F32(d))
    kc = k_[:, :J * chunk].reshape(B, J, chunk, A, d)
    vc = v_[:, :J * chunk].reshape(B, J, chunk, A, d)
    al = jax.nn.softmax(jnp.einsum("bjtad,ad->bjta", q(kc),
                                   q(phi.astype(F32)), precision=HIGHEST)
                        * scale, axis=2)
    ks = jnp.einsum("bjta,bjtad->bjad", al, kc, precision=HIGHEST) \
        + mu.astype(F32)
    vs = jnp.einsum("bjta,bjtad->bjad", al, vc, precision=HIGHEST)
    mask = eva_mask(T, window, chunk)
    for part, pairs in (("local", mask[:, :T].sum()),
                        ("remote", mask[:, T:].sum())):
        _record(tape, f"{name}/{part}_scores", "conv", (A, d), (pairs,))
        _record(tape, f"{name}/{part}_values", "conv", (A, d), (pairs,))
    keys = jnp.concatenate([k_, ks], axis=1)
    values = jnp.concatenate([v_, vs], axis=1)
    s = jnp.einsum("bqad,bkad->baqk", q(q_), q(keys),
                   precision=HIGHEST) * scale
    p = jax.nn.softmax(jnp.where(mask[None, None], q_scores(s), -jnp.inf),
                       axis=-1)
    out = jnp.einsum("baqk,bkad->bqad", q(p), q(values), precision=HIGHEST)
    return out.reshape(B, T, A * d)


def gated_mlp(x, p, *, q=exact, tape=None, name=""):
    """``(silu(x W_gate) * (x W_up)) W_down``."""
    lin = lambda a, n: linear(a, p[n]["kernel"], q=q, tape=tape,
                              name=f"{name}/{n}")
    return lin(jax.nn.silu(lin(x, "gate_proj")) * lin(x, "up_proj"),
               "down_proj")
