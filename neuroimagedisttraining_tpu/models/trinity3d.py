"""Trinity-Mini's layers over 3D patch tokens (``--model trinity3d``).

Added here, not ported: the reference repository has no such model. The
layers are ``Trinity-Mini``'s (arcee-ai; the public ``config.json``,
``model_type`` ``afmoe``; what it does not show is the public
``modeling_afmoe.py`` as ISSUE 44 writes it), every width as published:
hidden ``H`` = 2048, 32 query heads ``a`` on 4 key/value heads ``g = a //
8``, head size 128, a sliding window ``W`` = 2048 in three layers of four
and full attention in the fourth, a leading dense feed-forward of width
6144, then 128 experts of width 1024, 8 a token, beside one shared expert.
The second of the two leading dense layers and the four expert layers that
follow it (published layers 1-5: sliding, sliding, full, sliding, sliding);
``N`` is RMSNorm with a plain weight, eps 1e-5, its statistics in float32;
no bias:

    x       = N_1(h)
    q_a     = Nq(x Wq_a)   k_g = Nk(x Wk_g)   v_g = x Wv_g     Wq [H, 32 x 128], Wk, Wv [H, 4 x 128]
                                                               Nq, Nk over the head's 128, weight [128]
    sliding   q_a, k_g = rope(q_a), rope(k_g)                  theta 10000, the whole head; full: no position
    s_a,i,t = 128^-1/2 q_a,i . k_g,t     float32;   sliding: i - W < t <= i     full: t <= i
    o_a,i   = sum_t softmax_t(s_a,i,.) v_g,t
    y       = (concat_a(o_a) * sigmoid(x Wg)) Wo               Wg [H, 32 x 128], Wo [32 x 128, H]
    h       = h + N_2(y)

    u       = N_3(h)
    dense     m = (silu(u Wgate) * (u Wup)) Wdown              width 6144
    expert    s   = sigmoid(u Wr) in R^128, float32
              C   = top-8 of (s + b)     b the expert_bias: zeros
              g_e = 2.826 s_e / (sum over C of s + 1e-20)
              m   = sum over e in C and HELD of g_e E_e(u) + S(u)
              E_e, S: the gated form at width 1024; S on every token
    h       = h + N_4(m)

Two kinds of layer differ in what they attend to, and only the sliding
kind reads a position: the full layers see the order of the tokens through
the causal mask alone. There is no auxiliary loss (the published recipe
balances the experts by moving ``b`` outside the gradient):
``aux["loss"]`` is zero, as models/nemotronh3d.py's.

**How the attention is computed** (ops/attention.py ``causal_attention``,
grouped ``q [B, T, 4, 8, 128]``, ``window`` 2048 in the sliding layers):
exact over the window or the whole sequence, no pair outside either is
computed. On a TPU, at widths its blocks tile (the published ones: 4,864
tokens = 19 blocks of 256, a window of 8), one Pallas kernel a pass whose
index maps send query head ``a`` to key/value head ``a // 8``; everywhere
else (the CPU tests, the small widths, the eager initialisation) its plain
forms. ``aux["attn_kernel_calls"]`` counts the layers whose attention took
the kernel.

**The expert layer holds experts 0-15 of the 128** (``held``; ops/moe.py
``held_expert_rows``): eight chips share each layer by expert
parallelism. The router keeps its 128 outputs and 8 a token; a slot routed
to another chip's expert adds nothing here, and nothing stands in for the
other chips or their exchange. Gate and up are one ``[count, 2048, 2048]``
matrix, side by side. Attention, the shared expert, the norms, the router
and the leading layer are whole.

What is NOT built: the token embedding and LM head (replaced as in the
other trunks, models/tokens3d.py; ``mup_enabled``'s one effect in the
forward pass, the embedding times ``H^1/2``, goes with it), generation and
a cache that keeps a window's keys in some layers and all in others, the
update of ``b``, the experts' exchange. What ``config.json`` does not give
is listed, with where each was taken from, in
benchmark/configs/trinity-abcd.json (``assumed``).

The model returns ``(logits, aux)``: ``aux["loss"]`` zero,
``aux["expert_tokens"]`` the slots routed to each of the 128 experts,
summed over the expert layers, ``aux["held_overflow_calls"]`` the layers
whose held rows passed the buffer in this call,
``aux["attn_kernel_calls"]`` the layers whose attention ran as the kernel,
``aux["attn_outputs_kept"]`` those of them whose forward kernel the
backward pass does not run again. Every layer is rematerialised
(``remat_layers``, the model's own declaration) but for the attention
kernel's two outputs, which it keeps (models/tokens3d.py ``layer_stack``:
81.0 MB a layer and step for 5.0-6.6 ms of forward kernel).

Device scopes (obs/names.py MODEL_SCOPES): ``attn`` (W_q, W_k, W_v, the
rotary embedding, W_o) with ``qk_norm``, ``swa_core`` or ``full_core``, and
``attn_gate`` inside it; ``mlp`` (the leading layer); ``router``,
``dispatch``, ``experts``, ``combine``, ``shared_expert``; ``stem``,
``head``.
"""

from __future__ import annotations

import dataclasses
from typing import Any

import flax.linen as nn
import jax
import jax.numpy as jnp

from neuroimagedisttraining_tpu.models import tokens3d
from neuroimagedisttraining_tpu.models.tokens3d import GatedMLP, RMSNorm
from neuroimagedisttraining_tpu.obs import names as obs_names
from neuroimagedisttraining_tpu.ops import attention

Dtype = Any
_scope = jax.named_scope
INIT_STD = 0.02
SLIDING, FULL = "sliding_attention", "full_attention"


@dataclasses.dataclass(frozen=True)
class Widths:
    """The trunk's sizes; the defaults are the published widths and this
    chip's share (the CPU tests pass a small size)."""

    #: what each layer attends to: published layers 1-5 of the 32
    layer_types: tuple[str, ...] = (SLIDING, SLIDING, FULL, SLIDING, SLIDING)
    dense_layers: int = 1  # num_dense_layers: the leading ones count once
    hidden_size: int = 2048
    heads: int = 32
    kv_heads: int = 4
    head_dim: int = 128
    sliding_window: int = 2048
    intermediate_size: int = 6144
    num_experts: int = 128
    held: tuple[int, int] = (0, 16)  # one of 8 chips' experts of a layer
    experts_per_token: int = 8
    expert_width: int = 1024
    shared_experts: int = 1
    route_scale: float = 2.826
    rope_theta: float = 1e4
    block: int = 512  # queries a block of the plain form's scores (no width)
    patch: int = 8
    rms_eps: float = 1e-5


def _dense(n, name, dtype):
    return nn.Dense(n, use_bias=False, dtype=dtype, name=name,
                    kernel_init=tokens3d.normal(INIT_STD))


def attention_core(q, k, v, w: Widths, sliding: bool, dtype,
                   kernel: bool = True):
    """Scores, softmax and values: grouped ``q [B, T, Hkv, G, d]``, ``k, v
    [B, T, Hkv, d]`` -> ``([B, T, heads * d], took)``, over the window in
    a ``sliding`` layer and the whole causal triangle in a full one, each
    under its own scope. ``took``: whether this call ran as the kernel
    (ops/attention.py)."""
    window = w.sliding_window if sliding else None
    took = attention.takes_kernel(q.shape[1], q.shape[-1], 0, v.shape[-1],
                                  kernel, window, q.shape[3])
    with _scope(obs_names.SCOPE_SWA_CORE if sliding
                else obs_names.SCOPE_FULL_CORE):
        return attention.causal_attention(
            q, k, v, w.block, dtype, kernel=took, window=window), took


class GatedAttention(nn.Module):
    """Grouped-query attention with per-head QK norms and a sigmoid gate
    on the heads' output: ``x [B, T, H]`` -> ``([B, T, H], took)`` (the
    equations are in the module's docstring). ``sliding``: the rotary
    embedding and the window; else neither."""

    w: Widths
    sliding: bool
    dtype: Dtype = jnp.float32

    @nn.compact
    def __call__(self, x):
        c = self.w
        B, T, H = x.shape
        A, Hkv, d = c.heads, c.kv_heads, c.head_dim
        q = _dense(A * d, "q_proj", self.dtype)(x).reshape(B, T, A, d)
        k = _dense(Hkv * d, "k_proj", self.dtype)(x).reshape(B, T, Hkv, d)
        v = _dense(Hkv * d, "v_proj", self.dtype)(x).reshape(B, T, Hkv, d)
        with _scope(obs_names.SCOPE_QK_NORM):
            q = RMSNorm(c.rms_eps, self.dtype, name="q_norm")(q)
            k = RMSNorm(c.rms_eps, self.dtype, name="k_norm")(k)
        if self.sliding:
            cos, sin = tokens3d.rope_tables(T, d, c.rope_theta)
            q = tokens3d.apply_rope(q, cos, sin)
            k = tokens3d.apply_rope(k, cos, sin)
        # the trainer initialises eagerly (tokens3d.layer_stack)
        out, took = attention_core(
            q.reshape(B, T, Hkv, A // Hkv, d), k, v, c, self.sliding,
            self.dtype, kernel=not self.is_initializing())
        with _scope(obs_names.SCOPE_ATTN_GATE):
            out = out * nn.sigmoid(_dense(A * d, "gate_proj", self.dtype)(x))
        return _dense(H, "o_proj", self.dtype)(out), took


class HeldExperts(nn.Module):
    """The routed part of an expert layer for the experts this chip
    holds: ``u [B, T, H]`` -> ``(y [B, T, H], experts [B*T, k],
    passed)``. Routes over all ``num_experts`` by sigmoid scores;
    ``passed`` is 1 where this call's held rows passed the buffer and took
    more than one window of it (ops/moe.py ``held_expert_rows``: the
    dropless answer either way)."""

    w: Widths
    dtype: Dtype = jnp.float32

    @nn.compact
    def __call__(self, u):
        c = self.w
        B, T, H = u.shape
        x = u.reshape(B * T, H)
        # expert_bias: a buffer the published recipe moves outside the
        # gradient; zeros, so no bias is handed on
        _, weights, experts = tokens3d.linear_router(
            self, x, c.num_experts, c.experts_per_token, INIT_STD,
            scoring="sigmoid", scale=c.route_scale)
        y, passed = tokens3d.held_expert_body(
            self, x, weights, experts, c.num_experts, c.held,
            c.expert_width, gated=True, stds=(INIT_STD, INIT_STD))
        return y.reshape(B, T, H), experts, passed


class Layer(nn.Module):
    """One layer, attention then feed-forward, a norm before and after
    each: ``h -> (h, experts, passed, kernels)``. ``dense``: the leading
    layer's whole feed-forward (an empty ``[0, k]`` of choices and 0
    beside it, so that every layer returns the same structure under
    ``nn.remat``); else the held experts beside the shared one.
    ``sliding``: what the attention reads. ``kernels``: 1 where the
    attention ran as the kernel."""

    dense: bool
    sliding: bool
    w: Widths
    dtype: Dtype = jnp.float32

    @nn.compact
    def __call__(self, h):
        c, dtype = self.w, self.dtype
        norm = lambda name: RMSNorm(c.rms_eps, dtype, name=name)
        x = norm("attn_norm")(h)
        with _scope(obs_names.SCOPE_ATTN):
            y, took = GatedAttention(c, self.sliding, dtype,
                                     name="self_attn")(x)
        kernels = jnp.full((), took, jnp.int32)
        h = h + norm("attn_post_norm")(y)
        u = norm("mlp_norm")(h)
        if self.dense:
            with _scope(obs_names.SCOPE_MLP):
                m = GatedMLP(c.hidden_size, c.intermediate_size, INIT_STD,
                             dtype, name="ffn")(u)
            experts = jnp.zeros((0, c.experts_per_token), jnp.int32)
            passed = jnp.zeros((), jnp.int32)
        else:
            m, experts, passed = HeldExperts(c, dtype, name="moe")(u)
            with _scope(obs_names.SCOPE_SHARED_EXPERT):
                m = m + GatedMLP(c.hidden_size,
                                 c.shared_experts * c.expert_width, INIT_STD,
                                 dtype, name="shared")(u)
        return h + norm("mlp_post_norm")(m), experts, passed, kernels


class Trinity3D(nn.Module):
    """The trunk over 3D patch tokens: a layer a ``widths.layer_types``
    entry, the first ``widths.dense_layers`` of them dense."""

    num_classes: int = 1
    dtype: Dtype = jnp.float32
    widths: Widths = Widths()
    remat_layers: bool = True

    input_rank = 5  # [B, D, H, W, C]
    returns_aux = True  # (logits, {"loss", *aux_counters})
    aux_counters = ("expert_tokens", "held_overflow_calls",
                    "attn_kernel_calls", "attn_outputs_kept")

    @property
    def held_experts(self) -> tuple[int, int]:
        return self.widths.held

    def row_tokens(self, row_shape) -> int:
        return tokens3d.row_tokens(row_shape, self.widths.patch)

    def held_capacity_rows(self, batch_shape) -> int | None:
        c = self.widths
        return tokens3d.held_capacity_rows(
            batch_shape, c.patch, c.experts_per_token, c.held, c.num_experts)

    @nn.compact
    def __call__(self, x, train: bool = False):
        c = self.widths
        init = tokens3d.normal(INIT_STD)
        h = tokens3d.patch_embed(x, c.hidden_size, c.patch, c.rms_eps,
                                 self.dtype, init)
        (h,), (chosen, passed, kernels) = tokens3d.layer_stack(
            self, Layer, [(i < c.dense_layers, kind == SLIDING, c, self.dtype)
                          for i, kind in enumerate(c.layer_types)], (h,))
        logits = tokens3d.pooled_logits(h, self.num_classes, c.rms_eps, init)
        with _scope(obs_names.SCOPE_ROUTER):
            kernels = sum(kernels)
        return logits, tokens3d.held_aux(
            jnp.zeros((), jnp.float32), chosen, passed, c.num_experts,
            **tokens3d.attention_counters(kernels))
