"""Plain float32 building blocks of the OLMoE reference (PR 25).

Beside ``ops.py`` (which this PR may not edit), in the same spirit:
straightforward ``jax.numpy``, nothing imported from the program, every
matrix product at ``Precision.HIGHEST``. Each op that does useful work
appends a record to ``tape`` in one of the two kinds ``flops.py`` knows:

- ``conv``: ``2 x prod(kernel_shape) x prod(out_spatial)`` operations, used
  for "a kernel applied at N positions": a ``[in, out]`` projection over
  ``T`` tokens is ``kernel_shape (in, out)``, ``out_spatial (T,)``; causal
  attention is ``kernel_shape (heads, head_dim)`` at ``T (T + 1) / 2``
  (query, key) positions, once for the scores and once for the values;
  the experts are ``kernel_shape (k, in, out)``: the ``k`` ACTIVE experts
  of a token, not the ``E`` this reference computes.
- ``dense``: ``2 x prod(kernel_shape)``, the read-out of one position.

Norms, softmax, RoPE, the router's top-k and the sort are recorded as
nothing: utilization is of the matrix work.

``q`` is the rounding applied to both operands of every matrix product
(identity in the reference proper). The tests and PERF.md pass a
bfloat16 or float8 rounding to show that a tolerance fails a forward
computed in a lower precision.
"""

from __future__ import annotations

import jax
import jax.numpy as jnp
from jax import lax

HIGHEST = lax.Precision.HIGHEST
F32 = jnp.float32


def exact(a):
    return a


def rounded(dtype):
    """Operands rounded to ``dtype`` and back: a product's inputs in a
    lower precision, its accumulation still float32."""
    return lambda a: a.astype(dtype).astype(F32)


def _record(tape, name, kind, kernel_shape, out_spatial=None, **more):
    if tape is not None:
        row = {"name": name, "kind": kind,
               "kernel_shape": tuple(int(n) for n in kernel_shape), **more}
        if out_spatial is not None:
            row["out_spatial"] = tuple(int(n) for n in out_spatial)
        tape.append(row)


def linear(x, kernel, bias=None, *, q=exact, tape=None, name=""):
    """``x [B, T, in] @ kernel [in, out]``: one kernel at ``T`` positions."""
    _record(tape, name, "conv", kernel.shape, x.shape[1:-1])
    out = jnp.matmul(q(x), q(kernel.astype(F32)), precision=HIGHEST)
    return out if bias is None else out + bias.astype(F32)


def rms_norm(x, weight, eps):
    var = jnp.mean(jnp.square(x), axis=-1, keepdims=True)
    return weight.astype(F32) * (x * lax.rsqrt(var + eps))


def patches(x_uint8, patch, eps):
    """uint8 ``[B, D, H, W]`` -> float32 ``[B, tokens, patch^3]``: each
    volume standardised over its own voxels (zero mean, unit variance;
    ``eps`` under the root as in a norm), zero-padded up to a multiple of
    ``patch``, raster order D, H, W over patches and d, h, w inside one."""
    x = x_uint8.astype(F32)
    x = x - jnp.mean(x, axis=(1, 2, 3), keepdims=True)
    x = x * lax.rsqrt(jnp.mean(jnp.square(x), axis=(1, 2, 3), keepdims=True)
                      + eps)
    x = jnp.pad(x, [(0, 0)] + [(0, (-n) % patch) for n in x.shape[1:]])
    B, D, H, W = x.shape
    x = x.reshape(B, D // patch, patch, H // patch, patch, W // patch, patch)
    x = x.transpose(0, 1, 3, 5, 2, 4, 6)
    return x.reshape(B, -1, patch ** 3)


def rope(x, theta):
    """``x [B, T, heads, d]``, positions 0..T-1, rotate-half."""
    T, d = x.shape[1], x.shape[-1]
    inv = 1.0 / (theta ** (jnp.arange(0, d, 2, dtype=F32) / d))
    ang = jnp.arange(T, dtype=F32)[:, None] * inv[None]
    ang = jnp.concatenate([ang, ang], axis=-1)[None, :, None, :]
    rot = jnp.concatenate([-x[..., d // 2:], x[..., :d // 2]], axis=-1)
    return x * jnp.cos(ang) + rot * jnp.sin(ang)


def causal_attention(q_, k_, v_, *, q=exact, tape=None, name=""):
    """``[B, T, heads, d]`` each -> ``[B, T, heads * d]``."""
    B, T, heads, d = q_.shape
    pairs = (T * (T + 1) // 2,)
    _record(tape, name + "/scores", "conv", (heads, d), pairs)
    _record(tape, name + "/values", "conv", (heads, d), pairs)
    s = jnp.einsum("bqhd,bkhd->bhqk", q(q_), q(k_),
                   precision=HIGHEST) / jnp.sqrt(F32(d))
    s = jnp.where(jnp.tril(jnp.ones((T, T), bool))[None, None], s,
                  -jnp.inf)
    p = jax.nn.softmax(s, axis=-1)
    out = jnp.einsum("bhqk,bkhd->bqhd", q(p), q(v_), precision=HIGHEST)
    return out.reshape(B, T, heads * d)


def route(m, router, k, *, tape=None, name=""):
    """Float32 router: ``(probs [N, E], weights [N, k], experts [N, k])``,
    the weights NOT renormalised. Never rounded: the architecture's
    router is float32 whatever the compute precision."""
    _record(tape, name, "conv", router.shape, (m.shape[0],))
    probs = jax.nn.softmax(
        jnp.matmul(m, router.astype(F32), precision=HIGHEST), axis=-1)
    weights, experts = lax.top_k(probs, k)
    return probs, weights, experts


def dense_experts(m, weights, experts, gate, up, down, *, group=8,
                  q=exact, tape=None, name=""):
    """``sum_j w_j down_ej(silu(gate_ej(m)) * up_ej(m))`` the plain way:
    EVERY expert computed for every token and masked by the top-k
    weights, ``group`` experts at a time (each group rematerialised in
    the backward pass, so that a gradient at the published widths fits
    the chip). ``m [N, H]``; the tape counts the ``k`` active experts."""
    N, k = experts.shape
    E = gate.shape[0]
    for part, w in (("gate", gate), ("up", up), ("down", down)):
        _record(tape, f"{name}/{part}", "conv", (k,) + tuple(w.shape[1:]),
                (N,), num_experts=int(E))
    # [N, E]: a token's weight for each expert, zero where not chosen
    mask = jnp.sum(jax.nn.one_hot(experts, E, dtype=F32)
                   * weights[..., None], axis=1)

    @jax.checkpoint
    def some(m, mask_g, gate_g, up_g, down_g):
        h = jax.nn.silu(jnp.einsum("nh,ehw->enw", q(m), q(gate_g),
                                   precision=HIGHEST))
        h = h * jnp.einsum("nh,ehw->enw", q(m), q(up_g), precision=HIGHEST)
        y = jnp.einsum("enw,ewh->enh", q(h), q(down_g), precision=HIGHEST)
        return jnp.einsum("enh,ne->nh", y, mask_g, precision=HIGHEST)

    out = jnp.zeros_like(m)
    for g in range(0, E, group):
        sl = slice(g, g + group)
        out = out + some(m, mask[:, sl], gate[sl].astype(F32),
                         up[sl].astype(F32), down[sl].astype(F32))
    return out


def load_balancing(probs, experts, num_experts):
    """``E x sum_e f_e P_e``: ``f_e`` the slots routed to ``e`` per token
    (they sum to k), ``P_e`` the mean probability of ``e``."""
    f = jnp.sum(jax.nn.one_hot(experts, num_experts, dtype=F32),
                axis=(0, 1)) / probs.shape[0]
    return num_experts * jnp.sum(f * jnp.mean(probs, axis=0))


def read_out(x, kernel, *, q=exact, tape=None, name=""):
    """The pooled state's ``[B, in] @ [in, out]``: one kernel, once a
    sample."""
    _record(tape, name, "dense", kernel.shape)
    return jnp.matmul(q(x), q(kernel.astype(F32)), precision=HIGHEST)


def bce_with_logits(logits, labels):
    z = logits.reshape(-1).astype(F32)
    y = labels.reshape(-1).astype(F32)
    return jnp.maximum(z, 0.0) - z * y + jnp.log1p(jnp.exp(-jnp.abs(z)))
