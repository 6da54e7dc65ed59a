"""OLMoE's sparse-expert block over 3D patch tokens (``--model olmoe3d``).

Added here, not ported: the reference repository has no transformer. The
block is ``OLMoE-1B-7B-0125-Instruct``'s (allenai; the public
``config.json`` and modelling code), every width as published: hidden
2048, 16 heads of 128 (plain multi-head attention), RMSNorm eps 1e-5 with
a weight, RMSNorm over the whole 2048-wide query and key projections
before RoPE (theta 10000, rotate-half), 64 SiLU-gated experts of width
1024, 8 per token, no shared expert, router weights NOT renormalised, no
bias anywhere, load-balancing auxiliary loss with coefficient 0.01.

What is this system's own is how the trunk meets a volume (ROADMAP R4;
models/tokens3d.py, shared with models/nemotronh3d.py):

    x uint8 [B,121,145,121] -> (x - mean) / std of the volume, zero-pad
                               to [B,128,160,128]
    tokens = patches(16x16x16, raster order D,H,W) @ W_pe + b_pe   [B,640,2048]
    h = tokens; per layer: h += attn(norm(h)); h += moe(norm(h))
    logit = mean_t(norm(h)) @ W_head                                [B, classes]

as vision-language models feed a decoder (``inputs_embeds``) and as
embedding models read one (the mean of the final hidden states). Token
embedding and LM head are replaced; positions are 1-D RoPE over the
raster order. Why the volume is standardised and the read-out pooled, and
not x/255 and the last position as ISSUE 25 first had it: an RMSNorm-first
trunk keeps a token's direction and drops its length, x/255 makes every
token the same direction (the grey level times the embedding's column sum)
and the class signal of this system's volumes is an amplitude; and the last
patch is nearly all padding (benchmark/configs/olmoe-abcd.json, ``assumed``).

The model returns ``(logits, aux)``: ``aux["loss"]`` is the auxiliary
term already weighted (core/trainer.py adds it to the task loss inside
the grad function), ``aux["expert_tokens"]`` the integer count of slots
routed to each expert (summed over layers), from which the round driver
reports expert load.

Device scopes (obs/names.py MODEL_SCOPES): the patch embedding is the
``stem``, the read-out the ``head``; ``attn``, ``router``, ``dispatch``,
``experts``, ``combine`` name the block's stages.
"""

from __future__ import annotations

from typing import Any

import flax.linen as nn
import jax
import jax.numpy as jnp

from neuroimagedisttraining_tpu.models import tokens3d
from neuroimagedisttraining_tpu.models.tokens3d import (
    RMSNorm, apply_rope, rope_tables,
)
from neuroimagedisttraining_tpu.obs import names as obs_names
from neuroimagedisttraining_tpu.ops import attention, moe

Dtype = Any
_scope = jax.named_scope
INIT_STD = 0.02  # OLMoE's, every matrix
_init = tokens3d.normal(INIT_STD)


class Attention(nn.Module):
    """Causal multi-head attention with QK-norm and RoPE, no bias."""

    num_heads: int
    rope_theta: float
    eps: float
    dtype: Dtype = jnp.float32

    @nn.compact
    def __call__(self, a):
        B, T, H = a.shape
        d = H // self.num_heads
        dense = lambda name: nn.Dense(H, use_bias=False, dtype=self.dtype,
                                      kernel_init=_init, name=name)
        q = RMSNorm(self.eps, self.dtype, name="q_norm")(dense("q_proj")(a))
        k = RMSNorm(self.eps, self.dtype, name="k_norm")(dense("k_proj")(a))
        v = dense("v_proj")(a)
        heads = lambda t: t.reshape(B, T, self.num_heads, d)
        cos, sin = rope_tables(T, d, self.rope_theta)
        q = apply_rope(heads(q), cos, sin)
        k = apply_rope(heads(k), cos, sin)
        # one block of scores (640 tokens); the kernel for such heads is
        # a measured change (ROADMAP D17)
        return dense("o_proj")(attention.causal_attention(
            q, k, heads(v), T, self.dtype, kernel=False))


class SparseExperts(nn.Module):
    """Router + dropless top-k experts (ops/moe.py). Returns the mixed
    output and the router's ``(probs, experts)`` for the auxiliary loss
    and the load counter."""

    num_experts: int
    experts_per_token: int
    expert_width: int
    dtype: Dtype = jnp.float32

    @nn.compact
    def __call__(self, m):
        B, T, H = m.shape
        E, W = self.num_experts, self.expert_width
        x = m.reshape(B * T, H)
        probs, weights, experts = tokens3d.linear_router(
            self, x, E, self.experts_per_token, INIT_STD)
        gate = self.param("gate", _init, (E, H, W), jnp.float32)
        up = self.param("up", _init, (E, H, W), jnp.float32)
        down = self.param("down", _init, (E, W, H), jnp.float32)
        with _scope(obs_names.SCOPE_DISPATCH):
            plan = moe.dispatch_plan(experts, E)
            xs = moe.gather_slots(x, plan)
        with _scope(obs_names.SCOPE_EXPERTS):
            g = moe.grouped_matmul(xs, gate.astype(self.dtype),
                                   plan.group_sizes)
            u = moe.grouped_matmul(xs, up.astype(self.dtype),
                                   plan.group_sizes)
            ys = moe.grouped_matmul(nn.silu(g) * u, down.astype(self.dtype),
                                    plan.group_sizes)
        with _scope(obs_names.SCOPE_COMBINE):
            y = moe.combine_slots(ys, weights, plan).astype(self.dtype)
        return y.reshape(B, T, H), probs, experts


class Block(nn.Module):
    """Pre-norm residual block: attention, then the sparse experts."""

    num_heads: int
    num_experts: int
    experts_per_token: int
    expert_width: int
    rms_eps: float
    rope_theta: float
    dtype: Dtype = jnp.float32

    @nn.compact
    def __call__(self, h):
        norm = lambda name: RMSNorm(self.rms_eps, self.dtype, name=name)
        h = h + Attention(self.num_heads, self.rope_theta, self.rms_eps,
                          self.dtype, name=obs_names.SCOPE_ATTN)(
                              norm("attn_norm")(h))
        y, probs, experts = SparseExperts(
            self.num_experts, self.experts_per_token, self.expert_width,
            self.dtype, name="moe")(norm("mlp_norm")(h))
        return h + y, probs, experts


class OLMoE3D(nn.Module):
    """The trunk over 3D patch tokens; defaults are the published widths
    (the CPU tests pass a small size)."""

    num_classes: int = 1
    dtype: Dtype = jnp.float32
    hidden_size: int = 2048
    num_heads: int = 16
    num_experts: int = 64
    experts_per_token: int = 8
    expert_width: int = 1024
    depth: int = 1
    patch: int = 16
    rms_eps: float = 1e-5
    rope_theta: float = 10000.0
    aux_coef: float = 0.01
    remat: bool = False

    input_rank = 5  # [B, D, H, W, C]
    returns_aux = True  # (logits, {"loss", "expert_tokens"})
    aux_counters = ("expert_tokens",)  # summed over a round's real steps

    @nn.compact
    def __call__(self, x, train: bool = False):
        h = tokens3d.patch_embed(x, self.hidden_size, self.patch,
                                 self.rms_eps, self.dtype, _init)
        block = nn.remat(Block) if self.remat else Block
        probs, experts = [], []
        for i in range(self.depth):
            h, p, e = block(
                self.num_heads, self.num_experts, self.experts_per_token,
                self.expert_width, self.rms_eps, self.rope_theta,
                self.dtype, name=f"layers_{i}")(h)
            probs.append(p)
            experts.append(e)
        logits = tokens3d.pooled_logits(h, self.num_classes, self.rms_eps,
                                        _init)
        # over every layer's rows at once, as the public
        # load_balancing_loss_func concatenates them
        probs = jnp.concatenate(probs)
        experts = jnp.concatenate(experts)
        with _scope(obs_names.SCOPE_ROUTER):
            aux = {
                "loss": self.aux_coef * moe.load_balancing_loss(
                    probs, experts, self.num_experts),
                "expert_tokens": jnp.bincount(
                    experts.reshape(-1),
                    length=self.num_experts).astype(jnp.int32),
            }
        return logits, aux
