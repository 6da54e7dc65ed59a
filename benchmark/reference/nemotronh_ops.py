"""Plain float32 building blocks of the Nemotron-H reference (PR 29).

Beside ``olmoe_ops.py`` and in its spirit (``linear``, ``rms_norm``,
``patches``, ``read_out``, ``bce_with_logits``, ``rounded`` and the tape's
``_record`` are taken from it): straightforward ``jax.numpy``, nothing
imported from the program, every matrix product at ``Precision.HIGHEST``,
each op that does useful work recorded on ``tape`` in one of the two
kinds ``flops.py`` knows (``conv``: ``2 x prod(kernel_shape) x
prod(out_spatial)``; ``dense``).

What is recorded for the layers that are new here:

- the state-space recurrence: per token and head the rank-one state update
  ``dt x B^T`` and the read ``S C``, each ``kernel_shape (heads, head_dim,
  state)`` at ``T`` positions (2 x 2 x 64 x 64 x 128 = 2.1 MFLOP a token;
  the chunked form the program runs needs more products for the same
  result, and those are not useful work); the depthwise conv as
  ``(kernel, channels)`` at ``T``;
- grouped-query attention as ``(query heads, head_dim)`` at ``T (T + 1) /
  2`` (query, key) pairs, once for the scores and once for the values;
- the held experts at the UNIFORM share: the tape is traced abstractly and
  cannot see the routing, so it counts ``k x held / E`` assignments a token
  (6 x 8 / 128 = 0.375) for each of the two matrices, as ``kernel_shape
  (in, out)`` at ``T x k x held / E`` positions. How far a run's routing
  is from that is the cell's ``held_rows_share_pct``.

Norms, the gate, softmax, the router's top-k and the sort are recorded as
nothing: utilization is of the matrix work.

``q`` is the rounding applied to both operands of every matrix product
(identity in the reference proper), as in ``olmoe_ops.py``. The scan's
running state is an accumulator and is never rounded; its inputs are.
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
#: the recurrence is differentiated through segments of this many tokens,
#: each recomputed in the backward pass, so that a gradient at the
#: published widths fits the chip (the mathematics is untouched)
SCAN_SEGMENT = 128


def softplus(x):
    return jnp.maximum(x, 0.0) + jnp.log1p(jnp.exp(-jnp.abs(x)))


def silu(x):
    return x * jax.nn.sigmoid(x)


def relu2(x):
    return jnp.square(jnp.maximum(x, 0.0))


def causal_conv1d(x, kernel, bias, *, tape=None, name=""):
    """Depthwise, causal: ``y_t = b + sum_j kernel[j] x_{t - (K-1) + j}``
    with zeros before the sequence. ``x [B, T, C]``, ``kernel [K, C]``."""
    K, T = kernel.shape[0], x.shape[1]
    _record(tape, name, "conv", kernel.shape, (T,))
    padded = jnp.pad(x, ((0, 0), (K - 1, 0), (0, 0)))
    y = bias.astype(F32)
    for j in range(K):
        y = y + padded[:, j:j + T] * kernel[j].astype(F32)
    return y


def selective_scan(x, dt, A, B, C, D, *, q=exact, tape=None, name=""):
    """The state-space recurrence TOKEN BY TOKEN (not the chunked form):

        S_t = exp(dt_t A) S_{t-1} + dt_t x_t B_t^T,  S_0 = 0
        y_t = S_t C_t + D x_t

    ``x [b, T, H, P]``, ``dt [b, T, H]``, ``A, D [H]``, ``B, C [b, T, G,
    N]``; head ``h`` uses group ``h // (H / G)``."""
    b, T, H, P = x.shape
    G, N = B.shape[-2:]
    _record(tape, name + "/update", "conv", (H, P, N), (T,))
    _record(tape, name + "/read", "conv", (H, P, N), (T,))
    expand = lambda m: jnp.repeat(q(m), H // G, axis=2)
    seq = jax.tree.map(lambda a: a.swapaxes(0, 1),
                       (q(x * dt[..., None]), x, dt, expand(B), expand(C)))

    def step(S, t):
        xdt_t, x_t, dt_t, B_t, C_t = t
        S = S * jnp.exp(dt_t * A)[..., None, None] + jnp.einsum(
            "bhp,bhn->bhpn", xdt_t, B_t, precision=HIGHEST)
        y = jnp.einsum("bhpn,bhn->bhp", S, C_t, precision=HIGHEST)
        return S, y + D[:, None] * x_t

    @jax.checkpoint
    def segment(S, part):
        return lax.scan(step, S, part)

    S, ys, at = jnp.zeros((b, H, P, N), F32), [], 0
    while at < T:
        part = jax.tree.map(lambda a: a[at:at + SCAN_SEGMENT], seq)
        S, y = segment(S, part)
        ys.append(y)
        at += SCAN_SEGMENT
    return jnp.concatenate(ys).swapaxes(0, 1)


def gated_group_norm(y, z, weight, groups, eps):
    """``weight * RMSNorm_grouped(y * silu(z))``: the statistics over
    each of ``groups`` equal slices of the last axis."""
    g = y * silu(z)
    shape = g.shape
    g = g.reshape(shape[:-1] + (groups, shape[-1] // groups))
    g = g * lax.rsqrt(jnp.mean(jnp.square(g), axis=-1, keepdims=True) + eps)
    return weight.astype(F32) * g.reshape(shape)


def gq_attention(q_, k_, v_, *, q=exact, tape=None, name=""):
    """Causal grouped-query attention, no positions: ``q_ [B, T, Hq, d]``,
    ``k_, v_ [B, T, Hkv, d]`` -> ``[B, T, Hq * d]``; query head ``h`` reads
    key/value head ``h // (Hq / Hkv)``."""
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


def sigmoid_route(m, router, k, scaling, bias=None, *, tape=None, name=""):
    """Float32, never rounded: ``s = sigmoid(m W_r)``; the top ``k`` of
    ``s + bias``; weights ``s_sel / (sum s_sel + 1e-20) x scaling``.
    ``(scores [N, E], weights [N, k], experts [N, k])``."""
    _record(tape, name, "conv", router.shape, (m.shape[0],))
    s = jax.nn.sigmoid(jnp.matmul(m, router.astype(F32), precision=HIGHEST))
    _, experts = lax.top_k(s if bias is None else s + bias, k)
    chosen = jnp.take_along_axis(s, experts, axis=-1)
    weights = chosen / (jnp.sum(chosen, axis=-1, keepdims=True) + 1e-20)
    return s, weights * scaling, experts


def held_experts(m, weights, experts, up, down, held, num_experts, *,
                 q=exact, tape=None, name=""):
    """``sum_{j: e_j held} w_j relu(m W_up,ej)^2 W_down,ej`` the plain way:
    a loop over the held expert ids, each computed for EVERY token and
    masked by that token's weight for it (zero where it was not chosen).
    ``up [count, d, W]`` holds experts ``first .. first + count - 1``.
    What an expert outside the window would add is left out."""
    N, k = experts.shape
    first, count = held
    share = k * count / num_experts  # assignments a token, uniform routing
    for part, w in (("up", up), ("down", down)):
        _record(tape, f"{name}/{part}", "conv", w.shape[1:], (N * share,),
                num_experts=int(count))
    out = jnp.zeros_like(m)
    for i in range(count):
        w_i = jnp.sum(jnp.where(experts == first + i, weights, 0.0), axis=-1)
        h = relu2(jnp.matmul(q(m), q(up[i].astype(F32)), precision=HIGHEST))
        out = out + w_i[:, None] * jnp.matmul(
            q(h), q(down[i].astype(F32)), precision=HIGHEST)
    return out


def relu2_mlp(m, up, down, *, q=exact, tape=None, name=""):
    """The shared expert: ``relu(m W_up)^2 W_down`` on every token."""
    h = relu2(linear(m, up, q=q, tape=tape, name=name + "/up"))
    return linear(h, down, q=q, tape=tape, name=name + "/down")
