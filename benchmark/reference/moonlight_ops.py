"""Plain float32 building blocks of the Moonlight reference (PR 40).

Beside ``olmoe_ops.py`` and in its spirit (``linear``, ``rms_norm``,
``patches``, ``rope``, ``read_out``, ``bce_with_logits``, ``rounded`` and
the tape's ``_record`` are taken from it; ``sigmoid_route`` from
``nemotronh_ops.py``, ``gated_mlp`` from ``evabyte_ops.py``):
straightforward ``jax.numpy``, nothing imported from the program, every
matrix product at ``Precision.HIGHEST``, each op that does useful work
recorded on ``tape`` in one of the two kinds ``flops.py`` knows.

What is recorded for the layers that are new here:

- latent attention's causal pairs, ``T (T + 1) / 2`` a head, once for the
  scores as ``(heads, dn + dr)`` and once for the values as ``(heads,
  dv)``: the score is wider than its value (``/scores``, ``/values``);
  its four projections as linear layers at ``T``;
- the held experts at the UNIFORM share of the routing: the tape is
  traced abstractly and cannot see the routing, so it counts ``k x held /
  E`` assignments a token (6 x 8 / 64 = 0.75) for each of the two
  matrices (gate and up side by side, then down), ``kernel_shape (in,
  out)`` at ``T x k x held / E`` positions. How far a run's routing is
  from that is the cell's ``moon_rows_held_share_pct``.

Norms, rotary, softmax, SiLU, the router's top-k and the balance loss are
recorded as nothing: utilization is of the matrix work.

``q`` is the rounding applied to both operands of every matrix product
(identity in the reference proper), as in ``olmoe_ops.py``; ``q_scores``
the rounding of the attention scores before their softmax; ``q_router``
that of the router's two operands (the router is float32 by the
architecture's definition: ``q`` never reaches it).
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


base = _sibling("olmoe_ops")
HIGHEST, F32 = base.HIGHEST, base.F32
exact, rounded, _record = base.exact, base.rounded, base._record
linear, rms_norm, patches, rope = (base.linear, base.rms_norm, base.patches,
                                   base.rope)
read_out, bce_with_logits = base.read_out, base.bce_with_logits
sigmoid_route = _sibling("nemotronh_ops").sigmoid_route
gated_mlp = _sibling("evabyte_ops").gated_mlp


def latent_keys_values(x, p, heads, dn, eps, theta, *, q=exact, tape=None,
                       name=""):
    """``(kn [B, T, A, dn], kr [B, T, 1, dr], v [B, T, A, dv])``: the
    normed latent ``c`` and ONE rotary key a token from ``x Wdkv``, the
    heads' keys without position and their values from ``c Wukv``."""
    B, T, _ = x.shape
    rank = p["kv_norm"]["weight"].shape[0]
    down = linear(x, p["kv_a_proj"]["kernel"], q=q, tape=tape,
                  name=name + "/kv_a_proj")
    latent = rms_norm(down[..., :rank], p["kv_norm"]["weight"], eps)
    kr = rope(down[..., rank:].reshape(B, T, 1, -1), theta)
    up = linear(latent, p["kv_b_proj"]["kernel"], q=q, tape=tape,
                name=name + "/kv_b_proj").reshape(B, T, heads, -1)
    return up[..., :dn], kr, up[..., dn:]


def latent_attention(qn, qr, kn, kr, v, *, q=exact, q_scores=exact,
                     tape=None, name=""):
    """``s = (dn + dr)^-1/2 (qn . kn + qr . kr)`` under ONE dense causal
    mask a head, softmax, values: ``qn, kn [B, T, A, dn]``, ``qr [B, T, A,
    dr]``, ``kr [B, T, 1, dr]`` (read by every head), ``v [B, T, A, dv]``
    -> ``[B, T, A * dv]``."""
    B, T, A, dn = qn.shape
    dr, dv = qr.shape[-1], v.shape[-1]
    pairs = (T * (T + 1) // 2,)
    _record(tape, name + "/scores", "conv", (A, dn + dr), pairs)
    _record(tape, name + "/values", "conv", (A, dv), pairs)
    s = jnp.einsum("bqad,bkad->baqk", q(qn), q(kn), precision=HIGHEST) \
        + jnp.einsum("bqad,bkd->baqk", q(qr), q(kr[:, :, 0]),
                     precision=HIGHEST)
    s = q_scores(s / jnp.sqrt(F32(dn + dr)))
    s = jnp.where(jnp.tril(jnp.ones((T, T), bool))[None, None], s, -jnp.inf)
    p = jax.nn.softmax(s, axis=-1)
    out = jnp.einsum("baqk,bkad->bqad", q(p), q(v), precision=HIGHEST)
    return out.reshape(B, T, A * dv)


def held_gated_experts(m, weights, experts, up, down, held, num_experts, *,
                       q=exact, tape=None, name=""):
    """``sum_{j: e_j held} g_j (silu(m Wg_ej) * (m Wu_ej)) Wd_ej`` the
    plain way: a loop over the held expert ids, each computed for EVERY
    token and multiplied by that token's weight for it, a 0/1 selection of
    its ``k`` weights (zero where it was not chosen). ``up [count, H, 2 W]``
    holds gate and up side by side for the experts ``first .. first + count
    - 1``. What an expert outside the window would add is left out."""
    N, k = experts.shape
    first, count = held
    W = down.shape[1]
    share = k * count / num_experts  # assignments a token, uniform routing
    for part, w in (("up", up), ("down", down)):
        _record(tape, f"{name}/{part}", "conv", w.shape[1:], (N * share,),
                num_experts=int(count))
    out = jnp.zeros_like(m)
    for i in range(count):
        g_i = jnp.sum(jnp.where(experts == first + i, weights, 0.0), axis=-1)
        u = jnp.matmul(q(m), q(up[i].astype(F32)), precision=HIGHEST)
        h = jax.nn.silu(u[:, :W]) * u[:, W:]
        out = out + g_i[:, None] * jnp.matmul(
            q(h), q(down[i].astype(F32)), precision=HIGHEST)
    return out


def sequence_balance(scores, experts, num_experts):
    """One sequence's ``sum_e f_e P_e``: ``scores [T, E]``, ``experts [T,
    k]``; ``f_e = E / (k T) #{t : e chosen at t}``, ``P_e = mean_t (s_e,t /
    sum_j s_j,t)``. Unweighted."""
    T, k = experts.shape
    f = jnp.sum(jax.nn.one_hot(experts, num_experts, dtype=F32),
                axis=(0, 1)) * (num_experts / (k * T))
    norm = scores / jnp.sum(scores, axis=-1, keepdims=True)
    return jnp.sum(f * jnp.mean(norm, axis=0))
