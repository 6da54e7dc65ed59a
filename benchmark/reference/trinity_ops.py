"""Plain float32 building blocks of the Trinity-Mini reference (PR 44).

Beside ``moonlight_ops.py`` and in its spirit (``linear``, ``rms_norm``,
``patches``, ``rope``, ``read_out``, ``bce_with_logits``, ``rounded``, the
tape's ``_record``, ``sigmoid_route``, ``gated_mlp`` and
``held_gated_experts`` are taken from it and from the files it names):
straightforward ``jax.numpy``, nothing imported from the program, every
matrix product at ``Precision.HIGHEST``, each op that does useful work
recorded on ``tape`` in one of the two kinds ``flops.py`` knows.

What is recorded for the layer that is new here: grouped attention's
(query, key) pairs a head, once for the scores and once for the values as
``(heads, d)`` at ``pairs`` positions (``/scores``, ``/values``), where
``pairs`` is what the layer's own dense mask holds: ``T (T + 1) / 2`` in a
full layer, ``sum_i min(i + 1, W)`` under a window of ``W`` keys
(:func:`window_pairs`: 11,831,680 and 7,865,344 at ``T`` = 4,864, ``W`` =
2,048); its five projections (``Wq``, ``Wk``, ``Wv``, ``Wg``, ``Wo``) as
linear layers at ``T``. The held experts at the UNIFORM share of the
routing, as ``moonlight_ops.py`` says (8 x 16 / 128 = 1 assignment a
token). Norms (the per-head ones too), rotary, softmax, sigmoid, SiLU and
the router's top-k are recorded as nothing: utilization is of the matrix
work.

``q`` is the rounding applied to both operands of every matrix product
(identity in the reference proper); ``q_scores`` the rounding of the
attention scores before their softmax; ``q_router`` that of the router's
two operands.
"""

from __future__ import annotations

import importlib.util
import os

import jax
import jax.numpy as jnp


def _sibling(name: str):
    spec = importlib.util.spec_from_file_location(
        "benchmark_reference_" + name,
        os.path.join(os.path.dirname(os.path.abspath(__file__)),
                     name + ".py"))
    module = importlib.util.module_from_spec(spec)
    spec.loader.exec_module(module)
    return module


base = _sibling("moonlight_ops")
HIGHEST, F32 = base.HIGHEST, base.F32
exact, rounded, _record = base.exact, base.rounded, base._record
linear, rms_norm, patches, rope = (base.linear, base.rms_norm, base.patches,
                                   base.rope)
read_out, bce_with_logits = base.read_out, base.bce_with_logits
sigmoid_route, gated_mlp = base.sigmoid_route, base.gated_mlp
held_gated_experts = base.held_gated_experts


def window_pairs(tokens: int, window: int | None) -> int:
    """(query, key) pairs of one head over a causal sequence in which a
    query reads itself and the ``window - 1`` tokens before it (``None``:
    every earlier token)."""
    if window is None or window >= tokens:
        return tokens * (tokens + 1) // 2
    return window * (window + 1) // 2 + (tokens - window) * window


def grouped_attention(q_, k_, v_, window, *, q=exact, q_scores=exact,
                      remat=False, tape=None, name=""):
    """``s = d^-1/2 q_a . k_g`` under ONE dense mask (``t <= i``, and ``i -
    t < window`` with it), softmax, values: ``q_ [B, T, A, d]``, ``k_, v_
    [B, T, Hkv, d]``, query head ``a`` reading key/value head ``a // (A /
    Hkv)`` (the key heads repeated over their groups) -> ``[B, T, A *
    d]``. A key/value head's group of query heads at a time (``lax.map``),
    so that a row's dense scores are alive a group at a time (0.76 GB at
    4,864 tokens and 8 heads); ``remat`` rematerialises each group in a
    gradient. The values are those of all heads at once."""
    B, T, A, d = q_.shape
    Hkv = k_.shape[2]
    G = A // Hkv
    pairs = (window_pairs(T, window),)
    _record(tape, name + "/scores", "conv", (A, d), pairs)
    _record(tape, name + "/values", "conv", (A, d), pairs)
    i, t = jnp.arange(T)[:, None], jnp.arange(T)[None]
    mask = t <= i
    if window is not None:
        mask = mask & (i - t < window)

    def group(qkv):
        qg, kg, vg = qkv  # [B, T, G, d], [B, T, d], [B, T, d]
        kr = jnp.repeat(kg[:, :, None], G, axis=2)
        vr = jnp.repeat(vg[:, :, None], G, axis=2)
        s = jnp.einsum("bqad,bkad->baqk", q(qg), q(kr), precision=HIGHEST)
        s = q_scores(s / jnp.sqrt(F32(d)))
        p = jax.nn.softmax(jnp.where(mask[None, None], s, -jnp.inf), axis=-1)
        return jnp.einsum("baqk,bkad->bqad", q(p), q(vr), precision=HIGHEST)

    out = jax.lax.map(
        jax.checkpoint(group) if remat else group,
        (jnp.moveaxis(q_.reshape(B, T, Hkv, G, d), 2, 0),
         jnp.moveaxis(k_, 2, 0), jnp.moveaxis(v_, 2, 0)))
    return jnp.moveaxis(out, 0, 2).reshape(B, T, A * d)
