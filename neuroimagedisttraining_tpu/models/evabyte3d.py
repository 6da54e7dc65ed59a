"""EvaByte's layer over 3D patch tokens (``--model evabyte3d``).

Added here, not ported: the reference repository has no such model. The
layer is ``EvaByte``'s (the public ``config.json``, ``attention_class``
``eva``; EVA: Zheng, Yuan, Wang, Kong, "Efficient Attention via Control
Variates", arXiv:2302.04542, in the deterministic, causal, windowed form
the released modeling code computes), every width as published: hidden
4096, heads of ``d`` = 128, a SiLU-gated feed-forward of width 11008,
windows of ``W`` = 2048 tokens, chunks of ``c`` = 16. Four of its 32
layers; the stream ``h`` is float32 (``fp32_skip_add``); no bias:

    N(x)   = (1 + g) * x / sqrt(mean(x^2) + 1e-5)      unit offset, g starts at 0
    x      = N_1(h)
    q_a = rope(x Wq_a)   k_a = rope(x Wk_a)   v_a = x Wv_a      a held head a; theta 1e5
    window(t) = t // W   chunk(t) = t // c   chunk j lies in window j // (W / c)

    eva_pool    al_t  = softmax over the c tokens t of chunk j of (d^-1/2 phi_a . k_a,t)
                ks_a,j = sum_t al_t k_a,t + mu_a       vs_a,j = sum_t al_t v_a,t
    eva_local   s_i,t = d^-1/2 q_a,i . k_a,t     for t <= i with window(t) = window(i)
    eva_remote  r_i,j = d^-1/2 q_a,i . ks_a,j    for chunks j of the windows before i's
                Z_i   = sum_t exp(s_i,t) + sum_j exp(r_i,j)      one softmax over both, float32
                o_a,i = (sum_t exp(s_i,t) v_a,t + sum_j exp(r_i,j) vs_a,j) / Z_i
    h = h + sum_a o_a Wo_a
    h = h + (silu(N_2(h) W_gate) * (N_2(h) W_up)) W_down

A query reads the keys of its own window exactly and every earlier window
through one summary key and value a chunk; a chunk's summary becomes
visible one window later, never inside its own window, so a sequence of
at most ``W`` tokens is plain causal attention.

**How it is computed** (:func:`eva_attention`): one window after another,
each a ``[L, L]`` block of scores (``L`` = ``W``, the last window what is
left: 2048, 2048, 768 of this cell's 4,864 tokens) beside its ``[L, 128 x
windows before]`` block of summary scores. The two are merged by their
common maximum, not concatenated: ``exp(s - m)`` and ``exp(r - m)`` each
multiply their own values and share one ``Z``. Scores, exponentials and
``Z`` are float32; the probabilities meet the values in the compute dtype,
as in the other trunks (ops/attention.py ``causal_gq_attention``). Only
the whole windows before the last are pooled: no query reads the last
window's summaries.

**The layer holds ``heads`` of the 32 heads** (8: four chips share each
layer by tensor parallelism over heads): their columns of W_q, W_k, W_v,
their rows of W_o, their ``phi`` and ``mu``. What the other heads would
add to the stream is left out, and nothing stands in for the other chips
or their all-reduce. The norms and the feed-forward are whole.

What is NOT built: the byte embedding and the 8 multi-byte prediction
heads (replaced as in the other trunks, models/tokens3d.py), generation
and its cache. What ``config.json`` does not give is listed, with where
each was taken from, in benchmark/configs/evabyte-abcd.json (``assumed``).

The model returns logits alone (no auxiliary output). Every layer is
rematerialised (``remat_layers``, the model's own declaration).

Device scopes (obs/names.py MODEL_SCOPES): ``attn`` (the projections, the
rotary embedding, W_o) with ``eva_pool``, ``eva_local``, ``eva_remote``
inside it; ``mlp``; ``stem``, ``head``.
"""

from __future__ import annotations

import dataclasses
import math
from typing import Any

import flax.linen as nn
import jax
import jax.numpy as jnp

from neuroimagedisttraining_tpu.models import tokens3d
from neuroimagedisttraining_tpu.models.tokens3d import RMSNorm
from neuroimagedisttraining_tpu.obs import names as obs_names

Dtype = Any
_scope = jax.named_scope


@dataclasses.dataclass(frozen=True)
class Widths:
    """The trunk's sizes; the defaults are the published widths and this
    chip's share (the CPU tests pass a small size)."""

    layers: int = 4
    hidden_size: int = 4096
    heads: int = 8  # held here, of the published 32
    head_dim: int = 128
    intermediate_size: int = 11008
    window_size: int = 2048
    chunk_size: int = 16
    rope_theta: float = 1e5
    init_std: float = 0.01275
    patch: int = 8
    rms_eps: float = 1e-5


def _phi_mu_init(head_dim: int):
    """``clip(normal, -1, 1) x d^-1/2`` (``assumed``: the configuration
    file says from where)."""
    def init(key, shape, dtype=jnp.float32):
        return jnp.clip(jax.random.normal(key, shape, dtype), -1.0, 1.0) \
            / math.sqrt(head_dim)
    return init


def eva_pool(k, v, phi, mu, chunk: int):
    """The chunks' summaries: ``k, v [B, S, A, d]`` with ``S`` a multiple of
    ``chunk``, ``phi, mu [A, d]`` -> ``(ks, vs) [B, S / chunk, A, d]`` in
    ``k``'s dtype; the pooling softmax and both sums in float32."""
    B, S, A, d = k.shape
    f32 = jnp.float32
    kc = k.reshape(B, S // chunk, chunk, A, d)
    vc = v.reshape(B, S // chunk, chunk, A, d)
    logits = jnp.einsum("bjtad,ad->bjta", kc, phi.astype(k.dtype),
                        preferred_element_type=f32) / math.sqrt(d)
    al = jax.nn.softmax(logits, axis=2)[..., None]
    ks = jnp.sum(al * kc, axis=2, dtype=f32) + mu
    vs = jnp.sum(al * vc, axis=2, dtype=f32)
    return ks.astype(k.dtype), vs.astype(v.dtype)


def eva_attention(q, k, v, phi, mu, window: int, chunk: int, dtype):
    """``q, k, v [B, T, A, d]`` after the rotary embedding, ``phi, mu [A,
    d]`` -> ``[B, T, A * d]`` (the equations are in the module's
    docstring)."""
    B, T, A, d = q.shape
    f32 = jnp.float32
    scale = 1.0 / math.sqrt(d)
    whole = (-(-T // window) - 1) * window  # the windows before the last
    if whole:
        with _scope(obs_names.SCOPE_EVA_POOL):
            ks, vs = eva_pool(k[:, :whole], v[:, :whole], phi, mu, chunk)
    outs = []
    for start in range(0, T, window):
        L = min(window, T - start)
        qw = q[:, start:start + L]
        with _scope(obs_names.SCOPE_EVA_LOCAL):
            s = jnp.einsum("bqad,bkad->baqk", qw, k[:, start:start + L],
                           preferred_element_type=f32) * scale
            s = jnp.where(jnp.tril(jnp.ones((L, L), bool)), s, -jnp.inf)
            m = jnp.max(s, axis=-1, keepdims=True)
        seen = start // chunk  # summaries of the windows before this one
        if seen:
            with _scope(obs_names.SCOPE_EVA_REMOTE):
                r = jnp.einsum("bqad,bjad->baqj", qw, ks[:, :seen],
                               preferred_element_type=f32) * scale
                m = jnp.maximum(m, jnp.max(r, axis=-1, keepdims=True))
        # one softmax over both: a common maximum (a constant of the
        # quotient, so no gradient goes through it) and a common Z
        m = jax.lax.stop_gradient(m)
        with _scope(obs_names.SCOPE_EVA_LOCAL):
            p = jnp.exp(s - m)
            z = jnp.sum(p, axis=-1)
            o = jnp.einsum("baqk,bkad->bqad", p.astype(dtype),
                           v[:, start:start + L],
                           preferred_element_type=f32)
        if seen:
            with _scope(obs_names.SCOPE_EVA_REMOTE):
                p = jnp.exp(r - m)
                z = z + jnp.sum(p, axis=-1)
                o = o + jnp.einsum("baqj,bjad->bqad", p.astype(dtype),
                                   vs[:, :seen], preferred_element_type=f32)
        with _scope(obs_names.SCOPE_EVA_LOCAL):
            outs.append((o / z.transpose(0, 2, 1)[..., None]).astype(dtype))
    return jnp.concatenate(outs, axis=1).reshape(B, T, A * d)


class EvaAttention(nn.Module):
    """The held heads' attention: ``x [B, T, hidden]`` -> ``[B, T,
    hidden]``, their part of the layer's attention output."""

    w: Widths
    dtype: Dtype = jnp.float32

    @nn.compact
    def __call__(self, x):
        c = self.w
        B, T, H = x.shape
        A, d = c.heads, c.head_dim
        init = tokens3d.normal(c.init_std)
        dense = lambda n, name: nn.Dense(n, use_bias=False, dtype=self.dtype,
                                         kernel_init=init, name=name)
        heads = lambda t: t.reshape(B, T, A, d)
        cos, sin = tokens3d.rope_tables(T, d, c.rope_theta)
        q = tokens3d.apply_rope(heads(dense(A * d, "q_proj")(x)), cos, sin)
        k = tokens3d.apply_rope(heads(dense(A * d, "k_proj")(x)), cos, sin)
        v = heads(dense(A * d, "v_proj")(x))
        phi = self.param("phi", _phi_mu_init(d), (A, d), jnp.float32)
        mu = self.param("mu", _phi_mu_init(d), (A, d), jnp.float32)
        out = eva_attention(q, k, v, phi, mu, c.window_size, c.chunk_size,
                            self.dtype)
        return dense(H, "o_proj")(out)


def GatedMLP(w: Widths, dtype: Dtype = jnp.float32, **module):
    """The feed-forward at this trunk's widths (models/tokens3d.py
    ``GatedMLP``)."""
    return tokens3d.GatedMLP(w.hidden_size, w.intermediate_size, w.init_std,
                             dtype, **module)


class Layer(nn.Module):
    """One layer, attention then feed-forward, on the float32 stream."""

    w: Widths
    dtype: Dtype = jnp.float32

    @nn.compact
    def __call__(self, h):
        c = self.w
        norm = lambda name: RMSNorm(c.rms_eps, self.dtype, unit_offset=True,
                                    name=name)
        f32 = jnp.float32
        x = norm("attn_norm")(h)
        with _scope(obs_names.SCOPE_ATTN):
            y = EvaAttention(c, self.dtype, name="eva")(x)
        h = h + y.astype(f32)  # nidt: allow[precision-upcast] -- the residual stream is float32 by the architecture's definition (fp32_skip_add)
        x = norm("mlp_norm")(h)
        with _scope(obs_names.SCOPE_MLP):
            y = GatedMLP(c, self.dtype, name="ffn")(x)
        return h + y.astype(f32)  # nidt: allow[precision-upcast] -- the same


class EvaByte3D(nn.Module):
    """The trunk over 3D patch tokens: ``widths.layers`` layers."""

    num_classes: int = 1
    dtype: Dtype = jnp.float32
    widths: Widths = Widths()
    remat_layers: bool = True

    input_rank = 5  # [B, D, H, W, C]

    @nn.compact
    def __call__(self, x, train: bool = False):
        c = self.widths
        init = tokens3d.normal(c.init_std)
        h = tokens3d.patch_embed(x, c.hidden_size, c.patch, c.rms_eps,
                                 self.dtype, init)
        h = h.astype(jnp.float32)  # nidt: allow[precision-upcast] -- the residual stream is float32 (fp32_skip_add)
        (h,), _ = tokens3d.layer_stack(self, Layer,
                                       [(c, self.dtype)] * c.layers, (h,))
        return tokens3d.pooled_logits(h, self.num_classes, c.rms_eps, init,
                                      unit_offset=True)

    def row_tokens(self, row_shape) -> int:
        return tokens3d.row_tokens(row_shape, self.widths.patch)
