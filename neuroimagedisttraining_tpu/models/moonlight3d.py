"""Moonlight's layer over 3D patch tokens (``--model moonlight3d``).

Added here, not ported: the reference repository has no such model. The
layer is ``Moonlight-16B-A3B``'s (moonshotai; the public ``config.json``,
``model_type`` ``deepseek_v3``; Moonlight arXiv:2502.16982, its attention
DeepSeek-V2's multi-head latent attention arXiv:2405.04434, its expert
layer DeepSeek-V3's arXiv:2412.19437), every width as published: hidden
``H`` = 2048, 16 heads ``a`` with ``dn`` = 128 score dimensions without
position, ``dr`` = 64 rotary ones and ``dv`` = 128 value dimensions, a
latent of ``r`` = 512, a leading dense feed-forward of width 11264, then
64 experts of width 1408, 6 a token, beside two shared experts. The
leading dense layer and five of the 26 expert layers; ``N`` is RMSNorm
with a plain weight, eps 1e-5, its statistics in float32; no bias:

    x          = N_1(h)
    q_a        = x Wq_a                      Wq [H, 16 x 192] (no low-rank query)
    qn_a, qr_a = q_a[:128], rope(q_a[128:])  rotary over the 64, theta 50000
    [c, kr]    = x Wdkv                      Wdkv [H, 512 + 64]
    c          = N_kv(c)                     the latent; kr' = rope(kr): ONE
                                             rotary key a token, read by every head
    [kn_a,v_a] = c Wukv_a                    Wukv [512, 16 x (128 + 128)]
    s_a,i,t    = 192^-1/2 (qn_a,i . kn_a,t + qr_a,i . kr'_t)     t <= i, float32
    o_a,i      = sum_t softmax_t(s_a,i,.) v_a,t
    h          = h + concat_a(o_a) Wo        Wo [16 x 128, H]

    layer 0    h = h + (silu(u Wg) * (u Wu)) Wd                  u = N_2(h), width 11264
    layers 1-5 s   = sigmoid(u Wr) in R^64, float32              u = N_2(h)
               C   = top-6 of (s + b)        b the e_score_correction_bias: zeros
               g_e = 2.446 s_e / (sum over C of s + 1e-20)
               h   = h + sum over e in C and HELD of g_e E_e(u) + S(u)
               E_e = the gated form above at width 1408; S the same at 2 x 1408
    aux        for each sequence f_e = 64 / (6 T) #{t : e in C_t},
               P_e = mean_t (s_e,t / sum_j s_j,t), L = alpha sum_e f_e P_e;
               the mean over the batch's sequences, summed over the five layers

Keys and values are REBUILT from the latent by one up-projection (not
kept in it, as models/zaya3d.py's are), the rotary key bypasses the
latent and is shared by all heads, and a score is 192 wide where its
value is 128.

**How the attention is computed** (ops/attention.py
``causal_attention``): ``kn`` and ``v`` are rebuilt once a layer; exact and
causal over the whole sequence, no ``[T, T]`` scores of all heads, no pair
above the diagonal's blocks. On a TPU, at widths its blocks tile (the
published ones), one Pallas kernel a pass: a tile of float32 scores lives
and dies in vector memory, every head reads the ONE rotary key from its
``[T, dr]`` array, and the backward pass remakes the probabilities from
the rows' log-sum-exp. Everywhere else (the CPU tests, the small widths,
the eager initialisation) its ``blocked_causal_attention``: the shared
key repeated beside each head's ``kn``, the scores a block of ``block``
queries at a time against the keys up to the block's end, each block
rematerialised in the backward pass. ``aux["attn_kernel_calls"]`` counts
the layers whose attention took the kernel.

**The expert layer holds experts 0-7 of the 64** (``held``; ops/moe.py
``held_expert_rows``): eight chips share each layer by expert
parallelism. The router keeps its 64 outputs and 6 a token; a slot routed
to another chip's expert adds nothing here, and nothing stands in for the
other chips or their exchange. Gate and up are one ``[count, 2048, 2816]``
matrix, side by side. Attention, the shared experts (one gated MLP of
width 2816, as the public code computes ``n_shared_experts x
moe_intermediate_size``), the norms and the leading layer are whole.

What is NOT built: the token embedding and LM head (replaced as in the
other trunks, models/tokens3d.py), generation and the cache of latents,
the absorbed form of the up-projections that serving uses, the update of
``b`` (a training recipe ``config.json`` does not give: none is handed to
the router). What ``config.json`` does not give is listed, with where
each was taken from, in benchmark/configs/moonlight-abcd.json
(``assumed``).

The model returns ``(logits, aux)``: ``aux["loss"]`` is the weighted
``L`` (filler rows of a padded batch count as sequences, as
models/olmoe3d.py's term counts their tokens), ``aux["expert_tokens"]``
the slots routed to each of the 64 experts, summed over the expert
layers, ``aux["held_overflow_calls"]`` the layers whose held rows passed
the buffer in this call, ``aux["attn_kernel_calls"]`` the layers whose
attention ran as the kernel, ``aux["attn_outputs_kept"]`` those of them
whose forward kernel the backward pass does not run again. Every layer is
rematerialised (``remat_layers``, the model's own declaration) but for the
attention kernel's two outputs, which it keeps (models/tokens3d.py
``layer_stack``: 40.5 MB a layer and step for 3.87 ms of forward kernel).

Device scopes (obs/names.py MODEL_SCOPES): ``attn`` (W_q, its rotary,
W_o) with ``mla_latent`` and ``mla_core`` inside it; ``mlp`` (layer 0);
``router``, ``dispatch``, ``experts``, ``combine``, ``shared_expert``;
``stem``, ``head``.
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
from neuroimagedisttraining_tpu.ops import attention, moe

Dtype = Any
_scope = jax.named_scope
INIT_STD = 0.02


@dataclasses.dataclass(frozen=True)
class Widths:
    """The trunk's sizes; the defaults are the published widths and this
    chip's share (the CPU tests pass a small size)."""

    dense_layers: int = 1   # first_k_dense_replace
    expert_layers: int = 5  # of the published 26
    hidden_size: int = 2048
    heads: int = 16
    qk_nope_head_dim: int = 128
    qk_rope_head_dim: int = 64
    v_head_dim: int = 128
    kv_lora_rank: int = 512
    intermediate_size: int = 11264
    num_experts: int = 64
    held: tuple[int, int] = (0, 8)  # one of 8 chips' experts of a layer
    experts_per_token: int = 6
    expert_width: int = 1408
    shared_experts: int = 2
    routed_scaling_factor: float = 2.446
    aux_alpha: float = 0.001
    rope_theta: float = 5e4
    block: int = 512  # queries a block of the XLA form's scores (no width)
    patch: int = 8
    rms_eps: float = 1e-5


def _dense(n, name, dtype):
    return nn.Dense(n, use_bias=False, dtype=dtype, name=name,
                    kernel_init=tokens3d.normal(INIT_STD))


def mla_core(qn, qr, kn, kr, v, block: int, dtype, kernel: bool = True):
    """Scores, softmax and values: ``qn, kn [B, T, A, dn]``, ``qr [B, T,
    A, dr]`` and the ONE rotary key a token ``kr [B, T, 1, dr]`` (both
    after the rotary embedding), ``v [B, T, A, dv]`` -> ``([B, T, A * dv],
    took)``. A head's score is ``(dn + dr)^-1/2 (qn . kn + qr . kr)``.
    ``took``: whether this call ran as the kernel (ops/attention.py: on a
    TPU, at widths its blocks tile, unless the caller says
    ``kernel=False``); else ``block`` queries at a time in plain XLA."""
    took = attention.takes_kernel(qn.shape[1], qn.shape[-1], qr.shape[-1],
                                  v.shape[-1], kernel)
    with _scope(obs_names.SCOPE_MLA_CORE):
        return attention.causal_attention(
            qn, kn, v, block, dtype, q_shared=qr, k_shared=kr,
            kernel=took), took


class LatentAttention(nn.Module):
    """Multi-head latent attention: ``x [B, T, H]`` -> ``([B, T, H],
    took)`` (the equations are in the module's docstring; ``took`` is
    :func:`mla_core`'s)."""

    w: Widths
    dtype: Dtype = jnp.float32

    @nn.compact
    def __call__(self, x):
        c = self.w
        B, T, H = x.shape
        A, dn, dr, dv = (c.heads, c.qk_nope_head_dim, c.qk_rope_head_dim,
                         c.v_head_dim)
        cos, sin = tokens3d.rope_tables(T, dr, c.rope_theta)
        q = _dense(A * (dn + dr), "q_proj", self.dtype)(x).reshape(
            B, T, A, dn + dr)
        qn, qr = q[..., :dn], tokens3d.apply_rope(q[..., dn:], cos, sin)
        with _scope(obs_names.SCOPE_MLA_LATENT):
            down = _dense(c.kv_lora_rank + dr, "kv_a_proj", self.dtype)(x)
            latent = RMSNorm(c.rms_eps, self.dtype, name="kv_norm")(
                down[..., :c.kv_lora_rank])
            kr = tokens3d.apply_rope(
                down[..., c.kv_lora_rank:].reshape(B, T, 1, dr), cos, sin)
            up = _dense(A * (dn + dv), "kv_b_proj", self.dtype)(
                latent).reshape(B, T, A, dn + dv)
            kn, v = up[..., :dn], up[..., dn:]
        # the trainer initialises eagerly (tokens3d.layer_stack)
        out, took = mla_core(qn, qr, kn, kr, v, c.block, self.dtype,
                             kernel=not self.is_initializing())
        return _dense(H, "o_proj", self.dtype)(out), took


class HeldGatedExperts(nn.Module):
    """The routed part of an expert layer for the experts this chip
    holds: ``u [B, T, H]`` -> ``(y [B, T, H], experts [B*T, k], passed,
    balance)``. Routes over all ``num_experts`` by sigmoid scores;
    ``passed`` is 1 where this call's held rows passed the buffer and took
    more than one window of it (ops/moe.py ``held_expert_rows``: the
    dropless answer either way); ``balance`` is the sequence-wise balance
    loss, the mean over the batch's sequences, unweighted."""

    w: Widths
    dtype: Dtype = jnp.float32

    @nn.compact
    def __call__(self, u):
        c = self.w
        B, T, H = u.shape
        E, k = c.num_experts, c.experts_per_token
        x = u.reshape(B * T, H)
        # e_score_correction_bias: a buffer the published recipe moves
        # outside the gradient; zeros, so no bias is handed on
        scores, weights, experts = tokens3d.linear_router(
            self, x, E, k, INIT_STD, scoring="sigmoid",
            scale=c.routed_scaling_factor)
        with _scope(obs_names.SCOPE_ROUTER):
            balance = jnp.mean(moe.sequence_balance_loss(
                scores.reshape(B, T, E), experts.reshape(B, T, k), E))
        y, passed = tokens3d.held_expert_body(
            self, x, weights, experts, E, c.held, c.expert_width,
            gated=True, stds=(INIT_STD, INIT_STD))
        return y.reshape(B, T, H), experts, passed, balance


class Layer(nn.Module):
    """One layer, attention then feed-forward: ``h -> (h, experts,
    passed, balance, kernels)``. ``dense``: the leading layer's whole
    feed-forward (an empty ``[0, k]`` of choices, 0 and 0.0 beside it, so
    that every layer returns the same structure under ``nn.remat``); else
    the held experts beside the shared ones. ``kernels``: 1 where the
    attention ran as the kernel."""

    dense: bool
    w: Widths
    dtype: Dtype = jnp.float32

    @nn.compact
    def __call__(self, h):
        c, dtype = self.w, self.dtype
        norm = lambda name: RMSNorm(c.rms_eps, dtype, name=name)
        x = norm("attn_norm")(h)
        with _scope(obs_names.SCOPE_ATTN):
            y, took = LatentAttention(c, dtype, name="mla")(x)
        kernels = jnp.full((), took, jnp.int32)
        h = h + y
        u = norm("mlp_norm")(h)
        if self.dense:
            with _scope(obs_names.SCOPE_MLP):
                y = GatedMLP(c.hidden_size, c.intermediate_size, INIT_STD,
                             dtype, name="ffn")(u)
            return (h + y, jnp.zeros((0, c.experts_per_token), jnp.int32),
                    jnp.zeros((), jnp.int32), jnp.zeros((), jnp.float32),
                    kernels)
        y, experts, passed, balance = HeldGatedExperts(c, dtype,
                                                       name="moe")(u)
        with _scope(obs_names.SCOPE_SHARED_EXPERT):
            y = y + GatedMLP(c.hidden_size,
                             c.shared_experts * c.expert_width, INIT_STD,
                             dtype, name="shared")(u)
        return h + y, experts, passed, balance, kernels


class Moonlight3D(nn.Module):
    """The trunk over 3D patch tokens: ``widths.dense_layers`` dense
    layers, then ``widths.expert_layers`` expert layers."""

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
        kinds = [True] * c.dense_layers + [False] * c.expert_layers
        (h,), (chosen, passed, balance, kernels) = tokens3d.layer_stack(
            self, Layer, [(dense, c, self.dtype) for dense in kinds], (h,))
        logits = tokens3d.pooled_logits(h, self.num_classes, c.rms_eps, init)
        with _scope(obs_names.SCOPE_ROUTER):
            loss, kernels = c.aux_alpha * sum(balance), sum(kernels)
        return logits, tokens3d.held_aux(
            loss, chosen, passed, c.num_experts,
            **tokens3d.attention_counters(kernels))
