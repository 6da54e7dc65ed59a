"""What the token trunks share (models/olmoe3d.py, models/nemotronh3d.py,
models/zaya3d.py, models/evabyte3d.py): how a decoder trunk meets a volume,
and how its one logit is read.

    x uint8 [B,121,145,121] -> (x - mean) / std of the volume, zero-pad
                               to a multiple of the patch
    tokens = patches(P^3, raster order D,H,W) @ W_pe + b_pe    (the ``stem``)
    ... the trunk's layers ...
    logit = mean_t(RMSNorm(h)) @ W_head, in float32            (the ``head``)

as vision-language models feed a decoder (``inputs_embeds``) and as
embedding models read one (the mean of the final hidden states). Why the
volume is standardised and the read-out pooled is in
benchmark/configs/olmoe-abcd.json (``assumed``). The helpers create their
flax modules in the calling ``@nn.compact`` method, under the names the
trunks' parameter trees have always had (``patch_embed``, ``final_norm``,
``head``). Beside them, what more than one trunk computes the same way:
the rotary tables (for a whole head, or for its first part) and the
causal depthwise convolution over the token axis.
"""

from __future__ import annotations

import math
from typing import Any

import flax.linen as nn
import jax
import jax.numpy as jnp

from neuroimagedisttraining_tpu.obs import names as obs_names

Dtype = Any
_scope = jax.named_scope


class RMSNorm(nn.Module):
    """``weight * x / sqrt(mean(x^2) + eps)``, the statistics in float32
    as the public code computes them. With ``unit_offset`` the gain is ``1
    + weight`` and ``weight`` starts at zero (``norm_add_unit_offset``)."""

    eps: float = 1e-5
    dtype: Dtype = jnp.float32
    unit_offset: bool = False

    @nn.compact
    def __call__(self, x):
        if self.unit_offset:
            weight = 1.0 + self.param("weight", nn.initializers.zeros,
                                      (x.shape[-1],), jnp.float32)
        else:
            weight = self.param("weight", nn.initializers.ones,
                                (x.shape[-1],), jnp.float32)
        x32 = x.astype(jnp.float32)  # nidt: allow[precision-upcast] -- norm statistics in float32 (OlmoeRMSNorm)
        var = jnp.mean(jnp.square(x32), axis=-1, keepdims=True)
        y = (x32 * jax.lax.rsqrt(var + self.eps)).astype(self.dtype)
        return weight.astype(self.dtype) * y


def rope_tables(positions: int, rotary_dim: int, theta: float):
    """``(cos, sin)`` ``[positions, rotary_dim]`` in float32: frequencies
    ``theta^(-2i/d)`` repeated over both halves (rotate-half form)."""
    inv = 1.0 / (theta ** (jnp.arange(0, rotary_dim, 2, dtype=jnp.float32)
                           / rotary_dim))
    ang = jnp.arange(positions, dtype=jnp.float32)[:, None] * inv[None]
    ang = jnp.concatenate([ang, ang], axis=-1)
    return jnp.cos(ang), jnp.sin(ang)


def apply_rope(x, cos, sin):
    """``x [B, T, heads, d]``: ``x * cos + rotate_half(x) * sin`` over the
    first ``cos.shape[-1]`` of each head's ``d`` dimensions, the rest as
    they are (``partial_rotary_factor``; a table as wide as the head
    rotates all of it)."""
    rotary = cos.shape[-1]
    if rotary < x.shape[-1]:
        return jnp.concatenate(
            [apply_rope(x[..., :rotary], cos, sin), x[..., rotary:]],
            axis=-1)
    half = x.shape[-1] // 2
    rot = jnp.concatenate([-x[..., half:], x[..., :half]], axis=-1)
    c = cos[None, :, None, :].astype(x.dtype)
    s = sin[None, :, None, :].astype(x.dtype)
    return x * c + rot * s


def causal_depthwise_conv(x, kernel):
    """``y[t] = sum_j kernel[j] * x[t - (K-1) + j]`` a channel, zeros
    before the sequence: ``x [B, T, C]``, ``kernel [K, C]`` (tap ``K-1``
    reads this token, tap 0 the one ``K-1`` positions back), computed in
    ``x``'s dtype. No bias: the caller adds its own."""
    K, T = kernel.shape[0], x.shape[1]
    padded = jnp.pad(x, ((0, 0), (K - 1, 0), (0, 0)))
    return sum(padded[:, j:j + T] * kernel[j].astype(x.dtype)
               for j in range(K))


def causal_gq_attention(q, k, v, dtype):
    """Causal softmax attention over grouped heads: ``q [B, T, Hkv, G,
    d]`` (query head ``g * G + r`` reads key/value head ``g``), ``k, v
    [B, T, Hkv, d]`` -> ``[B, T, Hkv * G * d]``; scores and softmax in
    float32, scaled by ``d^-1/2``."""
    B, T, Hkv, G, d = q.shape
    scores = jnp.einsum("bqgrd,bkgd->bgrqk", q, k,
                        preferred_element_type=jnp.float32)
    scores = scores / jnp.sqrt(jnp.float32(d))
    causal = jnp.tril(jnp.ones((T, T), bool))
    scores = jnp.where(causal, scores, -jnp.inf)
    p = jax.nn.softmax(scores, axis=-1).astype(dtype)
    out = jnp.einsum("bgrqk,bkgd->bqgrd", p, v)
    return out.reshape(B, T, Hkv * G * d)


def token_count(batch_shape, patch: int) -> int:
    """Tokens in a batch ``[B, D, H, W, ...]`` of volumes cut into
    ``patch``-cubes (each axis padded up to a multiple)."""
    return batch_shape[0] * math.prod(-(-n // patch)
                                      for n in batch_shape[1:4])


def patches(x, patch: int, eps: float, dtype):
    """``[B, D, H, W, 1]`` raw intensities -> ``[B, tokens, patch^3]``:
    each volume standardised over its own voxels (zero mean, unit
    variance, in float32), zero-padded (the mean) up to a multiple of
    the patch, raster order D, H, W (and d, h, w inside a patch)."""
    P = patch
    x = x[..., 0].astype(jnp.float32)  # nidt: allow[precision-upcast] -- the volume's statistics in float32, like a norm's
    x = x - jnp.mean(x, axis=(1, 2, 3), keepdims=True)
    var = jnp.mean(jnp.square(x), axis=(1, 2, 3), keepdims=True)
    x = (x * jax.lax.rsqrt(var + eps)).astype(dtype)
    pads = [(0, 0)] + [(0, (-n) % P) for n in x.shape[1:]]
    x = jnp.pad(x, pads)
    B, D, H, W = x.shape
    x = x.reshape(B, D // P, P, H // P, P, W // P, P)
    x = x.transpose(0, 1, 3, 5, 2, 4, 6)
    return x.reshape(B, (D // P) * (H // P) * (W // P), P ** 3)


def patch_embed(x, hidden_size: int, patch: int, eps: float, dtype, init):
    """The ``stem``: one linear patch embedding with a bias."""
    with _scope(obs_names.SCOPE_STEM):
        return nn.Dense(hidden_size, dtype=dtype, kernel_init=init,
                        name="patch_embed")(patches(x, patch, eps, dtype))


def pooled_logits(h, num_classes: int, eps: float, init,
                  unit_offset: bool = False):
    """The ``head``: float32 whatever the compute dtype (hidden x
    classes, no cost; a bf16 logit of order 1 is 0.4% coarse)."""
    with _scope(obs_names.SCOPE_HEAD):
        pooled = jnp.mean(RMSNorm(eps, jnp.float32, unit_offset,
                                  name="final_norm")(h), axis=1)
        return nn.Dense(num_classes, use_bias=False, dtype=jnp.float32,
                        kernel_init=init,
                        precision=jax.lax.Precision.HIGHEST,
                        name="head")(pooled)


def blocked_causal_attention(q, k, v, block: int, dtype):
    """Causal softmax attention, exact over the whole sequence, a block
    of queries at a time: ``q, k [B, T, A, dk]``, ``v [B, T, A, dv]`` (the
    score width and the value width apart) -> ``[B, T, A * dv]``; scores
    and softmax in float32, scaled by ``dk^-1/2``.

    Beside :func:`causal_gq_attention`, for sequences whose ``[T, T]``
    scores of all heads do not fit: queries ``start .. start + block - 1``
    read the keys ``0 .. start + block - 1`` and no later one, so no pair
    above the diagonal's blocks is computed and a ``[B, A, block, start +
    block]`` block of scores is the largest that is ever alive (a Python
    loop over static extents; the last block is what is left). Each block
    is rematerialised in the backward pass (``jax.checkpoint``): only
    ``q``, ``k``, ``v`` are kept, not the causal triangle of
    probabilities (1.5 GB a layer in float32 at 2 x 16 heads x 4,864
    tokens). No key is dropped and nothing is summarised."""
    T, dk = q.shape[1], q.shape[-1]
    scale = 1.0 / math.sqrt(dk)

    def rows_from(start):
        def rows(qb, kb, vb):
            s = jnp.einsum("bqad,bkad->baqk", qb, kb,
                           preferred_element_type=jnp.float32) * scale
            seen = (start + jnp.arange(qb.shape[1]))[:, None] \
                >= jnp.arange(kb.shape[1])[None]
            p = jax.nn.softmax(jnp.where(seen, s, -jnp.inf), axis=-1)
            return jnp.einsum("baqk,bkad->bqad", p.astype(dtype), vb)
        return jax.checkpoint(rows)

    outs = []
    for start in range(0, T, block):
        end = min(start + block, T)
        outs.append(rows_from(start)(q[:, start:end], k[:, :end],
                                     v[:, :end]))
    out = jnp.concatenate(outs, axis=1)
    return out.reshape(out.shape[0], T, -1)
