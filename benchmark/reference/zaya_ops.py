"""Plain float32 building blocks of the ZAYA1 reference (PR 31).

Beside ``olmoe_ops.py`` and in its spirit (``linear``, ``rms_norm``,
``patches``, ``read_out``, ``bce_with_logits``, ``rounded`` and the tape's
``_record`` are taken from it): straightforward ``jax.numpy``, nothing
imported from the program, every matrix product at ``Precision.HIGHEST``,
each op that does useful work recorded on ``tape`` in one of the two
kinds ``flops.py`` knows (``conv``: ``2 x prod(kernel_shape) x
prod(out_spatial)``; ``dense``).

What is recorded for the layers that are new here:

- the depthwise convolution as ``(taps, channels)`` at ``T`` positions, the
  per-head grouped convolution as ``(taps, heads, head_dim, head_dim)`` at
  ``T``;
- grouped-query attention as ``(query heads, head_dim)`` at ``T (T + 1) /
  2`` (query, key) pairs, once for the scores and once for the values;
- the router's four matrices as linear layers at ``T``;
- the held experts at the UNIFORM share of the routing: the tape is traced
  abstractly and cannot see the routing, so it counts ``held / outputs``
  assignments a token (8 / 17: the router has 17 outputs, one a token) for
  each of the two matrices, ``kernel_shape (in, out)`` at ``T x held /
  outputs`` positions. How far a run's routing is from that is the cell's
  ``zaya_rows_held_share_pct``.

Norms, the q-k mean, the L2 norm, rotary, softmax, GELU, the arg-max and
the residual scaling are recorded as nothing: utilization is of the
matrix work.

``q`` is the rounding applied to both operands of every matrix product
(identity in the reference proper), as in ``olmoe_ops.py``. The router is
float32 by the architecture's definition and is never rounded.
"""

from __future__ import annotations

import importlib.util
import os

import jax
import jax.numpy as jnp
from jax import lax

_spec = importlib.util.spec_from_file_location(
    "benchmark_reference_olmoe_ops",
    os.path.join(os.path.dirname(os.path.abspath(__file__)), "olmoe_ops.py"))
base = importlib.util.module_from_spec(_spec)
_spec.loader.exec_module(base)

HIGHEST, F32 = base.HIGHEST, base.F32
exact, rounded, _record = base.exact, base.rounded, base._record
linear, rms_norm, patches = base.linear, base.rms_norm, base.patches
read_out, bce_with_logits = base.read_out, base.bce_with_logits


def previous(x):
    """``x[t-1]`` at token ``t`` (axis 1), zero at ``t = 0``."""
    return jnp.concatenate([jnp.zeros_like(x[:, :1]), x[:, :-1]], axis=1)


def depthwise_conv2(u, kernel, bias, *, q=exact, tape=None, name=""):
    """``c[t] = kernel[0] * u[t-1] + kernel[1] * u[t] + bias`` a channel:
    ``u [B, T, C]``, ``kernel [2, C]``."""
    _record(tape, name, "conv", kernel.shape, (u.shape[1],))
    k = q(kernel.astype(F32))
    return k[0] * previous(q(u)) + k[1] * q(u) + bias.astype(F32)


def grouped_conv2(c, kernel, bias, *, q=exact, tape=None, name=""):
    """``y[t, h] = c[t-1, h] K[0, h] + c[t, h] K[1, h] + bias[h]``, a
    ``[d, d]`` matrix a head and tap: ``c [B, T, heads, d]``, ``kernel [2,
    heads, d, d]``, ``bias [heads, d]``."""
    _record(tape, name, "conv", kernel.shape, (c.shape[1],))
    k = q(kernel.astype(F32))
    tap = lambda x, j: jnp.einsum("bthc,hcd->bthd", q(x), k[j],
                                  precision=HIGHEST)
    return tap(previous(c), 0) + tap(c, 1) + bias.astype(F32)


def unit_rows(x, scale):
    """``scale x / |x|_2`` over the last axis; a zero row stays zero."""
    sq = jnp.sum(jnp.square(x), axis=-1, keepdims=True)
    return x * (scale / jnp.sqrt(jnp.maximum(sq, 1e-24)))


def partial_rope(x, theta, rotary):
    """Rotate-half rotary embedding on the first ``rotary`` of each head's
    dimensions, positions 0..T-1: ``x [B, T, heads, d]``."""
    T = x.shape[1]
    half = rotary // 2
    freq = theta ** (-jnp.arange(half, dtype=F32) * 2.0 / rotary)
    ang = (jnp.arange(T, dtype=F32)[:, None] * freq[None])[None, :, None, :]
    a, b, rest = x[..., :half], x[..., half:rotary], x[..., rotary:]
    return jnp.concatenate([a * jnp.cos(ang) - b * jnp.sin(ang),
                            b * jnp.cos(ang) + a * jnp.sin(ang), rest],
                           axis=-1)


def gq_attention(q_, k_, v_, *, q=exact, tape=None, name=""):
    """Causal grouped-query attention on prepared heads: ``q_ [B, T, Hq,
    d]``, ``k_, v_ [B, T, Hkv, d]`` -> ``[B, T, Hq * d]``; query head ``i``
    reads key/value head ``i // (Hq / Hkv)``."""
    B, T, Hq, d = q_.shape
    rep = Hq // k_.shape[2]
    pairs = (T * (T + 1) // 2,)
    _record(tape, name + "/scores", "conv", (Hq, d), pairs)
    _record(tape, name + "/values", "conv", (Hq, d), pairs)
    k_, v_ = jnp.repeat(k_, rep, axis=2), jnp.repeat(v_, rep, axis=2)
    s = jnp.einsum("bqhd,bkhd->bhqk", q(q_), q(k_),
                   precision=HIGHEST) / jnp.sqrt(F32(d))
    s = jnp.where(jnp.tril(jnp.ones((T, T), bool))[None, None], s, -jnp.inf)
    p = jax.nn.softmax(s, axis=-1)
    out = jnp.einsum("bhqk,bkhd->bqhd", q(p), q(v_), precision=HIGHEST)
    return out.reshape(B, T, Hq * d)


def gelu(x):
    """The exact GELU, ``x Phi(x)``."""
    return 0.5 * x * (1.0 + lax.erf(x / jnp.sqrt(F32(2.0))))


def mlp_route(m, r_prev, p, eps, bias=None, *, tape=None, name=""):
    """ZAYA's router, float32 and never rounded: ``m [N, d]``, the previous
    layer's router state ``r_prev [N, R]`` (``None`` in the first layer)
    -> ``(r [N, R], probs [N, E + 1], weight [N], choice [N])``: the
    arg-max of ``probs + bias`` and the probability of the chosen output
    as it is."""
    mm = lambda x, layer: jnp.matmul(x, layer["kernel"].astype(F32),
                                     precision=HIGHEST)
    for part in ("down", "fc1", "fc2", "fc3"):
        _record(tape, f"{name}/{part}", "conv", p[part]["kernel"].shape,
                (m.shape[0],))
    r = mm(m, p["down"]) + p["down"]["bias"]
    if r_prev is not None:
        r = r + p["depth_gain"] * r_prev
    z = rms_norm(r, p["norm"]["weight"], eps)
    z = gelu(mm(z, p["fc1"]) + p["fc1"]["bias"])
    z = gelu(mm(z, p["fc2"]) + p["fc2"]["bias"])
    probs = jax.nn.softmax(mm(z, p["fc3"]), axis=-1)
    choice = jnp.argmax(
        lax.stop_gradient(probs) + (0.0 if bias is None else bias), axis=-1)
    weight = jnp.take_along_axis(probs, choice[:, None], axis=-1)[:, 0]
    return r, probs, weight, choice


def held_gated_experts(m, weight, choice, up, down, held, outputs, *,
                       q=exact, tape=None, name=""):
    """``w (silu(m Wg_e) * (m Wu_e)) Wd_e`` for the tokens whose choice
    ``e`` is one of the held experts, the plain way: a loop over the held
    ids, each computed for EVERY token and masked by that token's weight
    for it (zero where it was not chosen). ``up [count, d, 2 W]`` holds gate
    and up side by side for the experts ``first .. first + count - 1``. What
    another chip's expert would add, and the skip output, are left out."""
    first, count = held
    W = down.shape[1]
    share = count / outputs  # assignments a token, uniform routing
    for part, w in (("up", up), ("down", down)):
        _record(tape, f"{name}/{part}", "conv", w.shape[1:],
                (m.shape[0] * share,), num_experts=int(count))
    out = jnp.zeros_like(m)
    for i in range(count):
        w_i = jnp.where(choice == first + i, weight, 0.0)
        u = jnp.matmul(q(m), q(up[i].astype(F32)), precision=HIGHEST)
        h = jax.nn.silu(u[:, :W]) * u[:, W:]
        out = out + w_i[:, None] * jnp.matmul(
            q(h), q(down[i].astype(F32)), precision=HIGHEST)
    return out


def residual_scale(h, y, p):
    return (p["stream_gain"] * h + p["stream_offset"]) \
        + (p["out_gain"] * y + p["out_offset"])
