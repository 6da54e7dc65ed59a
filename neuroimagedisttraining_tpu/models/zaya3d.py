"""ZAYA1's layer over 3D patch tokens (``--model zaya3d``).

Added here, not ported: the reference repository has no such model. The
layer is ``ZAYA1-8B``'s (Zyphra; the public ``config.json``, Compressed
Convolutional Attention arXiv:2510.04476, the ZAYA1 report
arXiv:2511.17127), every width as published: d = 2048, 8 query heads over
2 key/value heads of 128 (G = 4 query heads a group), 16 experts of width
2048, one a token, a router MLP of width R = 256. Five of its 40 layers,
each two sublayers, everything causal, eps 1e-5, no bias in attention;
``a[t-1]`` is zero at t = 0:

    a = RMSNorm(h)
    CCA   q~ = a W_q [1024]    k~ = a W_k [256]    u = [q~ ; k~]   (10 heads of 128)
          c0[t] = kappa0[0] * u[t-1] + kappa0[1] * u[t] + b0        depthwise
          c1[t] = c0[t-1] K1[0] + c0[t] K1[1] + b1      K1[j]: 10 blocks of 128 x 128
          [q_c ; k_c] = c1
          m_q[t, i] = (q~[t, i] + k~[t, i // G]) / 2;  m_k[t, g] = mean_{i in g} m_q[t, i]
          q = q_c + m_q       k = k_c + m_k
          v[t] = [a[t] W_v1 ; a[t-1] W_v2]      value head 1 reads the PREVIOUS token
          q^ = sqrt(128) q / |q|_2    k^ = tau_g sqrt(128) k / |k|_2     float32
          rotary on the first 64 of each head's 128 (theta 5e6), q^ and k^
          o = softmax(q^ k^T / sqrt(128) + causal) v     head i reads key/value head i // G
          y = o W_o [1024 -> d]
          h = (s1 * h + t1) + (s2 * y + t2)                       residual scaling
    a = RMSNorm(h)
    MoE   r = a W_dn + b_dn [R];  r = r + gamma * r_{l-1};  r_l = r     handed to layer l+1
          z = gelu(gelu(RMSNorm_R(r) W_1 + b_1) W_2 + b_2) W_3   [17], float32
          p = softmax(z);  e = argmax(stop_gradient(p) + bias);  w = p[e]
          y = w * (silu(a Wg_e) * (a Wu_e)) Wd_e      if e < 16 and e is held here
          y = 0                                       if e = 16 (the token skips the
                                                      layer) or e is another chip's
          h = (s3 * h + t3) + (s4 * y + t4)

**A layer carries two streams**, ``(h, r) -> (h, r)``: the router of
layer ``l`` reads the router state of layer ``l - 1`` (exponential depth
averaging), which the first layer receives as zeros. The whole q/k/v/o
path of the attention lives in latents narrower than the residual stream
(1024 and 256 of 2048), and its memory-bound mixing steps (two
convolutions, a mean, a shift, a norm, rotary) sit between MXU-bound
projections.

**The expert layer holds experts 0-7 of the 16** (``held``; ops/moe.py
``held_expert_rows``): two chips share each layer by expert parallelism.
The router keeps its 17 outputs; a row routed to an expert of the other
chip, or to output 16, adds nothing here, and nothing stands in for the
other chip or its exchange. Gate and up are one ``[count, 2048, 4096]``
matrix, side by side. The balancing bias is a buffer of zeros that the
published recipe moves outside the gradient, so none is handed to the
router (``ops/moe.py route`` takes one).

What is NOT built: the token embedding and tied head (replaced as in the
other trunks, models/tokens3d.py), the 74B sibling's windowed layers,
generation. What ``config.json`` does not give is listed, with where each
was taken from, in benchmark/configs/zaya1-abcd.json (``assumed``).

The model returns ``(logits, aux)``: ``aux["loss"]`` is 0,
``aux["expert_tokens"]`` counts the tokens sent to each of the 17 outputs,
summed over the layers, and ``aux["held_overflow_calls"]`` the layers
whose held rows passed the buffer in this call. Every layer is
rematerialised (``remat_layers``, the model's own declaration).

Device scopes (obs/names.py MODEL_SCOPES): ``cca_proj``, ``cca_conv``,
``cca_mix``, ``attn``; ``router``, ``dispatch``, ``experts``, ``combine``;
``stem``, ``head``.
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
from neuroimagedisttraining_tpu.ops import attention, moe

Dtype = Any
_scope = jax.named_scope
_normal = tokens3d.normal
HIGHEST = jax.lax.Precision.HIGHEST


def _conv_init(fan_in):
    """Uniform in +-1/sqrt(fan_in), a convolution's usual start."""
    bound = 1.0 / math.sqrt(fan_in)

    def init(key, shape, dtype=jnp.float32):
        return jax.random.uniform(key, shape, dtype, -bound, bound)
    return init


def previous_token(x):
    """``x[t-1]`` at position ``t`` along axis 1, zeros at ``t = 0``."""
    return jnp.pad(x[:, :-1], ((0, 0), (1, 0)) + ((0, 0),) * (x.ndim - 2))


@dataclasses.dataclass(frozen=True)
class Widths:
    """The trunk's sizes; the defaults are the published widths and this
    chip's share (the CPU tests pass a small size)."""

    layers: int = 5
    hidden_size: int = 2048
    num_heads: int = 8
    num_kv_heads: int = 2
    head_dim: int = 128
    cca_time0: int = 2  # the depthwise conv's kernel
    cca_time1: int = 2  # the per-head grouped conv's
    partial_rotary_factor: float = 0.5
    rope_theta: float = 5e6
    num_experts: int = 16
    held: tuple[int, int] = (0, 8)  # one of 2 chips' experts of a layer
    expert_width: int = 2048
    router_hidden_size: int = 256
    patch: int = 16
    rms_eps: float = 1e-5


class CCAttention(nn.Module):
    """Compressed convolutional attention: ``a [B, T, d]`` -> ``[B, T,
    d]`` (the equations are in the module's docstring)."""

    w: Widths
    out_std: float
    dtype: Dtype = jnp.float32

    @nn.compact
    def __call__(self, a):
        c = self.w
        B, T, d = a.shape
        Hq, Hkv, hd = c.num_heads, c.num_kv_heads, c.head_dim
        G, heads = Hq // Hkv, Hq + Hkv
        f32 = jnp.float32
        dense = lambda n, name, std=0.02: nn.Dense(
            n, use_bias=False, dtype=self.dtype, kernel_init=_normal(std),
            name=name)
        with _scope(obs_names.SCOPE_CCA_PROJ):
            q_lat = dense(Hq * hd, "q_proj")(a)
            k_lat = dense(Hkv * hd, "k_proj")(a)
            v_now = dense(hd, "v_proj_now")(a)
            v_prev = dense(hd, "v_proj_prev")(a)
        with _scope(obs_names.SCOPE_CCA_CONV):
            K0, K1 = c.cca_time0, c.cca_time1
            kappa0 = self.param("conv0_kernel", _conv_init(K0),
                                (K0, heads * hd), f32)
            b0 = self.param("conv0_bias", nn.initializers.zeros,
                            (heads * hd,), f32)
            kappa1 = self.param("conv1_kernel", _conv_init(K1 * hd),
                                (K1, heads, hd, hd), f32)
            b1 = self.param("conv1_bias", nn.initializers.zeros,
                            (heads, hd), f32)
            u = jnp.concatenate([q_lat, k_lat], axis=-1)
            c0 = tokens3d.causal_depthwise_conv(u, kappa0) \
                + b0.astype(self.dtype)
            # causal, a head at a time: tap j reads the token K1-1-j back
            taps = jnp.pad(c0.reshape(B, T, heads, hd),
                           ((0, 0), (K1 - 1, 0), (0, 0), (0, 0)))
            c1 = sum(jnp.einsum("bthc,hcd->bthd", taps[:, j:j + T],
                                kappa1[j].astype(self.dtype))
                     for j in range(K1)) + b1.astype(self.dtype)
        with _scope(obs_names.SCOPE_CCA_MIX):
            q_heads = q_lat.reshape(B, T, Hkv, G, hd)
            m_q = (q_heads + k_lat.reshape(B, T, Hkv, 1, hd)) / 2
            q = c1[:, :, :Hq].reshape(B, T, Hkv, G, hd) + m_q
            k = c1[:, :, Hq:] + jnp.mean(m_q, axis=3)
            v = jnp.stack([v_now, previous_token(v_prev)], axis=2)
            tau = self.param("temperature", nn.initializers.ones, (Hkv,),
                             f32)

            def unit(x):
                # float32; a zero row (a filler volume's tokens at the
                # initial weights) stays zero, forward and backward
                x = x.astype(f32)  # nidt: allow[precision-upcast] -- the L2 norm's statistics in float32, like a norm's
                sq = jnp.sum(jnp.square(x), axis=-1, keepdims=True)
                return x * (math.sqrt(hd)
                            * jax.lax.rsqrt(jnp.maximum(sq, 1e-24)))

            cos, sin = tokens3d.rope_tables(
                T, int(hd * c.partial_rotary_factor), c.rope_theta)
            q = tokens3d.apply_rope(unit(q).reshape(B, T, Hq, hd), cos, sin)
            k = tokens3d.apply_rope(unit(k) * tau[:, None], cos, sin)
            q = q.reshape(B, T, Hkv, G, hd).astype(self.dtype)
            k = k.astype(self.dtype)
        with _scope(obs_names.SCOPE_ATTN):
            # one block of scores (640 tokens); the kernel for grouped
            # heads is a measured change (ROADMAP D17)
            return dense(d, "o_proj", self.out_std)(
                attention.causal_attention(q, k, v, T, self.dtype,
                                           kernel=False))


class ZayaRouter(nn.Module):
    """The router MLP with its depth state, in float32 whatever the
    compute dtype: ``(a [N, d], r_prev [N, R])`` -> ``(r [N, R], weights
    [N, 1], experts [N, 1])`` over ``num_experts + 1`` outputs, the last
    of which is no expert."""

    num_experts: int
    hidden: int
    eps: float

    @nn.compact
    def __call__(self, a, r_prev):
        f32 = jnp.float32
        # orthogonal matrices, the first hidden layer at a gain that keeps
        # GELU in its linear range: every output's logit is then an
        # equal-norm projection of the same normalised state, and the
        # initial routing is balanced to within sampling, as the published
        # balancing keeps a trained router's. A normal(0.02) draw starts
        # +-45% an expert from uniform, differently for every seed, and
        # the rows that land on the half held here with it (PERF.md, PR 31)
        dense = lambda n, name, gain=1.0, bias=True: nn.Dense(
            n, use_bias=bias, dtype=f32, precision=HIGHEST, name=name,
            kernel_init=nn.initializers.orthogonal(scale=gain))
        gelu = lambda x: jax.nn.gelu(x, approximate=False)
        # the module is named as its stage is (HeldGatedExperts), so
        # flax puts everything here under the scope ``router``
        gamma = self.param("depth_gain", nn.initializers.ones,
                           (self.hidden,), f32)
        r = dense(self.hidden, "down")(a.astype(f32)) + gamma * r_prev  # nidt: allow[precision-upcast] -- the router is float32 by the architecture's definition
        z = RMSNorm(self.eps, f32, name="norm")(r)
        z = gelu(dense(self.hidden, "fc1", gain=0.02)(z))
        z = gelu(dense(self.hidden, "fc2")(z))
        z = dense(self.num_experts + 1, "fc3", bias=False)(z)
        # the balancing bias is a buffer of zeros (module docstring):
        # none is handed on, and the choice is the arg-max of p
        _, weights, experts = moe.route(z, 1)
        return r, weights, experts


class HeldGatedExperts(nn.Module):
    """The expert sublayer's routed part for the experts this chip
    holds: ``(a [B, T, d], r_prev [B*T, R])`` -> ``(y [B, T, d], r,
    experts [B*T, 1], passed)``. ``passed`` is 1 where this call's held
    rows passed the buffer and took more than one window of it
    (ops/moe.py ``held_expert_rows``: the dropless answer either way)."""

    w: Widths
    out_std: float
    dtype: Dtype = jnp.float32

    @nn.compact
    def __call__(self, a, r_prev):
        c = self.w
        B, T, d = a.shape
        x = a.reshape(B * T, d)
        r, weights, experts = ZayaRouter(
            c.num_experts, c.router_hidden_size, c.rms_eps,
            name=obs_names.SCOPE_ROUTER)(x, r_prev)
        # the skip output is an expert that no chip holds
        y, passed = tokens3d.held_expert_body(
            self, x, weights, experts, c.num_experts + 1, c.held,
            c.expert_width, gated=True, stds=(0.02, self.out_std))
        return y.reshape(B, T, d), r, experts, passed


class ResidualScale(nn.Module):
    """``(s_h * h + t_h) + (s_y * y + t_y)``: a learned gain and offset a
    channel on the stream and on the sublayer's output (gains 1, offsets
    0 at the start: the plain residual sum)."""

    dtype: Dtype = jnp.float32

    @nn.compact
    def __call__(self, h, y):
        d = h.shape[-1]
        vec = lambda name, init: self.param(name, init, (d,),
                                            jnp.float32).astype(self.dtype)
        ones, zeros = nn.initializers.ones, nn.initializers.zeros
        return (vec("stream_gain", ones) * h + vec("stream_offset", zeros)) \
            + (vec("out_gain", ones) * y + vec("out_offset", zeros))


class Layer(nn.Module):
    """One layer, attention then experts: ``(h, r_prev) -> (h, r,
    experts, passed)``, the router state handed on beside the stream."""

    w: Widths
    dtype: Dtype = jnp.float32

    @nn.compact
    def __call__(self, h, r_prev):
        c, dtype = self.w, self.dtype
        # every projection back into the stream starts 1/sqrt(2 x layers)
        # smaller (two sublayers a layer)
        out_std = 0.02 / math.sqrt(2 * c.layers)
        y = CCAttention(c, out_std, dtype, name="cca")(
            RMSNorm(c.rms_eps, dtype, name="attn_norm")(h))
        h = ResidualScale(dtype, name="attn_merge")(h, y)
        y, r, experts, passed = HeldGatedExperts(c, out_std, dtype,
                                                 name="moe")(
            RMSNorm(c.rms_eps, dtype, name="moe_norm")(h), r_prev)
        return ResidualScale(dtype, name="moe_merge")(h, y), r, experts, \
            passed


class Zaya3D(nn.Module):
    """The trunk over 3D patch tokens: ``widths.layers`` layers."""

    num_classes: int = 1
    dtype: Dtype = jnp.float32
    widths: Widths = Widths()
    remat_layers: bool = True

    input_rank = 5  # [B, D, H, W, C]
    returns_aux = True  # (logits, {"loss", *aux_counters})
    aux_counters = ("expert_tokens", "held_overflow_calls")

    @property
    def held_experts(self) -> tuple[int, int]:
        return self.widths.held

    @property
    def skip_output(self) -> int:
        """The router output that is no expert: the round driver counts
        ``rows_skipped`` there."""
        return self.widths.num_experts

    def held_capacity_rows(self, batch_shape) -> int | None:
        c = self.widths
        return tokens3d.held_capacity_rows(batch_shape, c.patch, 1, c.held,
                                           c.num_experts + 1)

    @nn.compact
    def __call__(self, x, train: bool = False):
        c = self.widths
        h = tokens3d.patch_embed(x, c.hidden_size, c.patch, c.rms_eps,
                                 self.dtype, _normal(0.02))
        r = jnp.zeros((h.shape[0] * h.shape[1], c.router_hidden_size),
                      jnp.float32)
        (h, r), (chosen, passed) = tokens3d.layer_stack(
            self, Layer, [(c, self.dtype)] * c.layers, (h, r))
        logits = tokens3d.pooled_logits(h, self.num_classes, c.rms_eps,
                                        _normal(0.02))
        return logits, tokens3d.held_aux(jnp.zeros((), jnp.float32), chosen,
                                         passed, c.num_experts + 1)
