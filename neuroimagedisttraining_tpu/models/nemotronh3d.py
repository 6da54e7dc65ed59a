"""Nemotron-H's hybrid trunk over 3D patch tokens (``--model nemotronh3d``).

Added here, not ported: the reference repository has no such model. The
trunk is the ``nemotron_h`` tower of ``Nemotron-Labs-TwoTower-30B-A3B-
Base-BF16`` (nvidia; the public ``config.json`` and the modelling code's
layer equations), every width as published. It is built from a PATTERN
STRING (``hybrid_override_pattern``), one pre-norm mixer a layer,

    h = h + mixer_l(RMSNorm_l(h))          no second sub-block

of three kinds (d = 2688, eps 1e-5, no bias but the conv's):

``M``  Mamba-2. ``z, xBC, dt = split(u W_in)`` (4096, 6144, 64);
       ``xBC = silu(causal depthwise conv1d(xBC, kernel 4) + b)``;
       ``x, B, C = split(xBC)`` (4096 = 64 heads of 64, and 8 groups of
       state 128 each for B and C; head h uses group h // 8);
       ``dt = softplus(dt + dt_bias)``; ``A = -exp(A_log)``; the scan of
       ops/ssd.py in chunks of 128; ``y = RMSNorm_grouped(y * silu(z))``
       over 8 groups of 512 with a weight; ``out = y W_out``.
``E``  128 sigmoid-routed experts, 6 a token, beside one shared expert:
       ``s = sigmoid(x W_r)`` in float32; the top 6 of ``s + b`` (``b``
       the ``e_score_correction_bias`` buffer: zeros, not trained);
       ``w = s_sel / (sum s_sel + 1e-20) x 2.5``; an expert is
       ``relu(x W_up)^2 W_down``, width 1856, no gate; the shared expert
       the same at width 3712 on every token.
``*``  bias-free grouped-query attention: 32 query heads, 2 key/value
       heads, head 128, causal, scale 128^-1/2, no rotary embedding
       (position comes through the ``M`` layers).

**The expert layer holds a share of its experts** (``held``: the first
expert and how many; ops/moe.py ``held_expert_rows``): it routes over
all 128 and computes the part of the result that its own experts give
for the rows routed to them, dropping none: it gathers, multiplies and
adds back those rows alone, a buffer of twice the uniform share at a
time (once, unless the routing sends more here than the buffer holds).
What the absent experts would add is left out, as on one chip of a
deployment that divides each layer over 16 by expert parallelism. The
router, the shared expert, the mixers and every norm are whole.

What is NOT built: the row's second, denoiser tower (adaLN,
bidirectional in-block attention, cross-tower conditioning) and
generation by block diffusion. This system trains a classifier; nothing
stands in for them (benchmark/configs/nemotronh-abcd.json).

How the trunk meets a volume is models/tokens3d.py, shared with
models/olmoe3d.py. The model returns ``(logits, aux)`` like it:
``aux["loss"]`` is 0 (the published balancing is the bias update, a
training recipe the config does not give), ``aux["expert_tokens"]``
counts the slots routed to each of the 128 experts, summed over the
``E`` layers, and ``aux["held_overflow_calls"]`` the ``E`` layers whose
held rows passed the buffer in this call.

Nine layers of three kinds at 10,240 tokens a step do not keep their
activations beside a 590 M-parameter training state: every layer is
rematerialised (``remat_layers``, the model's own declaration; the
``--remat`` policy is the 3D CNN family's and does not reach here).

Device scopes (obs/names.py MODEL_SCOPES): ``ssm_in_proj``, ``ssm_conv``,
``ssd``, ``ssm_gate_norm``, ``ssm_out_proj``; ``router``, ``dispatch``,
``experts``, ``combine``, ``shared_expert``; ``attn``; ``stem``, ``head``.
"""

from __future__ import annotations

import dataclasses
import math
from typing import Any

import flax.linen as nn
import jax
import jax.numpy as jnp

from neuroimagedisttraining_tpu.models import tokens3d
from neuroimagedisttraining_tpu.models.tokens3d import RMSNorm, relu2
from neuroimagedisttraining_tpu.obs import names as obs_names
from neuroimagedisttraining_tpu.ops import attention, ssd

Dtype = Any
_scope = jax.named_scope
_normal = tokens3d.normal
#: the first nine layers of the published 52-layer pattern
PATTERN = "MEMEM*EME"
KINDS = "ME*"  # Mamba-2, expert layer, attention


def _dt_bias_init(dt_min, dt_max, dt_floor):
    """``dt`` log-uniform in ``[dt_min, dt_max]``, floored, through the
    inverse of softplus (the Mamba-2 initialisation)."""
    def init(key, shape, dtype=jnp.float32):
        u = jax.random.uniform(key, shape, jnp.float32)
        dt = jnp.exp(u * (math.log(dt_max) - math.log(dt_min))
                     + math.log(dt_min))
        dt = jnp.maximum(dt, dt_floor)
        return (dt + jnp.log(-jnp.expm1(-dt))).astype(dtype)
    return init


_EXACT = jax.lax.Precision.HIGHEST


def _a_log_init(key, shape, dtype=jnp.float32):
    return jnp.log(jnp.arange(1, shape[0] + 1, dtype=jnp.float32)).astype(
        dtype)


def _conv_init(key, shape, dtype=jnp.float32):
    """Uniform in +-1/sqrt(kernel), a depthwise conv's fan-in."""
    bound = 1.0 / math.sqrt(shape[0])
    return jax.random.uniform(key, shape, dtype, -bound, bound)


class Mamba2Mixer(nn.Module):
    """The ``M`` layer's mixer (the equations are in the module's
    docstring). ``a [B, T, d]`` -> ``[B, T, d]``."""

    num_heads: int
    head_dim: int
    n_groups: int
    state_size: int
    conv_kernel: int
    chunk_size: int
    eps: float
    out_std: float
    dt_limits: tuple[float, float, float]  # time_step_min, max, floor
    dtype: Dtype = jnp.float32

    @nn.compact
    def __call__(self, a):
        B, T, d = a.shape
        H, P, G, N = (self.num_heads, self.head_dim, self.n_groups,
                      self.state_size)
        inner, bc = H * P, G * N
        conv_dim = inner + 2 * bc
        f32 = jnp.float32
        with _scope(obs_names.SCOPE_SSM_IN_PROJ):
            proj = nn.Dense(inner + conv_dim + H, use_bias=False,
                            dtype=self.dtype, kernel_init=_normal(0.02),
                            name="in_proj")(a)
            z, xBC, dt = jnp.split(proj, [inner, inner + conv_dim], axis=-1)
        with _scope(obs_names.SCOPE_SSM_CONV):
            kernel = self.param("conv_kernel", _conv_init,
                                (self.conv_kernel, conv_dim), f32)
            bias = self.param("conv_bias", nn.initializers.zeros,
                              (conv_dim,), f32)
            xBC = nn.silu(tokens3d.causal_depthwise_conv(xBC, kernel)
                          + bias.astype(self.dtype))
            x, Bm, Cm = jnp.split(xBC, [inner, inner + bc], axis=-1)
        with _scope(obs_names.SCOPE_SSD):
            dt_bias = self.param("dt_bias", _dt_bias_init(*self.dt_limits),
                                 (H,), f32)
            A_log = self.param("A_log", _a_log_init, (H,), f32)
            D = self.param("D", nn.initializers.ones, (H,), f32)
            # float32: the step and the decay rate (time_step_limit is
            # (0, inf): nothing to clip)
            dt = jax.nn.softplus(dt.astype(f32) + dt_bias)  # nidt: allow[precision-upcast] -- the scan's step size, float32 like its decays (ops/ssd.py)
            # the trainer initialises eagerly (tokens3d.layer_stack)
            y = ssd.ssd_chunked(
                x.reshape(B, T, H, P), dt, -jnp.exp(A_log),
                Bm.reshape(B, T, G, N), Cm.reshape(B, T, G, N), D,
                self.chunk_size, kernel=not self.is_initializing())
        with _scope(obs_names.SCOPE_SSM_GATE_NORM):
            weight = self.param("gate_norm", nn.initializers.ones,
                                (inner,), f32)
            g = (y.reshape(B, T, inner) * nn.silu(z)).astype(f32)  # nidt: allow[precision-upcast] -- norm statistics in float32, as RMSNorm's
            # a group's mean square, and its root back over the group's
            # channels, as products with the groups' 0/1 membership at
            # HIGHEST (exact in float32: a factor of 1 keeps all three
            # bf16 parts of the other operand). A reshape to [B, T, G,
            # inner // G] puts G on the sublanes, and XLA re-tiles all of
            # g for it, there and back, in the forward, the rematerialised
            # and the backward pass: 6.9 ms a layer and step for 2.7 (my
            # chip run, PR 32)
            member = (jnp.arange(inner)[:, None] // (inner // G)
                      == jnp.arange(G)).astype(f32)
            mean_sq = jnp.einsum("btc,cg->btg", jnp.square(g), member,
                                 precision=_EXACT) / (inner // G)
            g = g * jnp.einsum("btg,cg->btc",
                               jax.lax.rsqrt(mean_sq + self.eps), member,
                               precision=_EXACT)
            y = weight.astype(self.dtype) * g.astype(self.dtype)
        with _scope(obs_names.SCOPE_SSM_OUT_PROJ):
            return nn.Dense(d, use_bias=False, dtype=self.dtype,
                            kernel_init=_normal(self.out_std),
                            name="out_proj")(y)


class HeldExperts(nn.Module):
    """The routed part of the ``E`` layer for the experts this chip
    holds: ``(y [B, T, d], experts [B*T, k], passed)``. Routes over all
    ``num_experts``; the weights hold ``held[1]`` of them, from expert
    ``held[0]``; ``passed`` is 1 where this call's held rows passed the
    buffer and took more than one window of it (ops/moe.py
    ``held_expert_rows``: the dropless answer either way)."""

    num_experts: int
    held: tuple[int, int]
    experts_per_token: int
    expert_width: int
    scaling: float
    out_std: float
    dtype: Dtype = jnp.float32

    @nn.compact
    def __call__(self, m):
        B, T, d = m.shape
        E = self.num_experts
        x = m.reshape(B * T, d)
        # e_score_correction_bias: a buffer the published recipe moves
        # outside the gradient; zeros, so no bias is handed on
        _, weights, experts = tokens3d.linear_router(
            self, x, E, self.experts_per_token, 0.02, scoring="sigmoid",
            scale=self.scaling)
        y, passed = tokens3d.held_expert_body(
            self, x, weights, experts, E, self.held, self.expert_width,
            gated=False, stds=(0.02, self.out_std))
        return y.reshape(B, T, d), experts, passed


class SharedExpert(nn.Module):
    """``relu(x W_up)^2 W_down`` on every token, no gate, no bias."""

    width: int
    out_std: float
    dtype: Dtype = jnp.float32

    @nn.compact
    def __call__(self, m):
        with _scope(obs_names.SCOPE_SHARED_EXPERT):
            dense = lambda n, name, std: nn.Dense(
                n, use_bias=False, dtype=self.dtype, kernel_init=_normal(std),
                name=name)
            return dense(m.shape[-1], "down", self.out_std)(
                relu2(dense(self.width, "up", 0.02)(m)))


class GQAttention(nn.Module):
    """Causal grouped-query attention, no bias, no rotary embedding."""

    num_heads: int
    num_kv_heads: int
    head_dim: int
    out_std: float
    dtype: Dtype = jnp.float32

    @nn.compact
    def __call__(self, a):
        B, T, d = a.shape
        Hq, Hkv, hd = self.num_heads, self.num_kv_heads, self.head_dim
        dense = lambda n, name, std=0.02: nn.Dense(
            n, use_bias=False, dtype=self.dtype, kernel_init=_normal(std),
            name=name)
        with _scope(obs_names.SCOPE_ATTN):
            # query head h reads key/value head h // (Hq / Hkv)
            q = dense(Hq * hd, "q_proj")(a).reshape(B, T, Hkv, Hq // Hkv, hd)
            k = dense(Hkv * hd, "k_proj")(a).reshape(B, T, Hkv, hd)
            v = dense(Hkv * hd, "v_proj")(a).reshape(B, T, Hkv, hd)
            # one block of scores (640 tokens); the kernel for grouped
            # heads is a measured change (ROADMAP D17)
            return dense(d, "o_proj", self.out_std)(
                attention.causal_attention(q, k, v, T, self.dtype,
                                           kernel=False))


@dataclasses.dataclass(frozen=True)
class Widths:
    """The trunk's sizes; the defaults are the published widths and this
    chip's share (the CPU tests pass a small size)."""

    pattern: str = PATTERN
    hidden_size: int = 2688
    # M
    mamba_num_heads: int = 64
    mamba_head_dim: int = 64
    n_groups: int = 8
    ssm_state_size: int = 128
    conv_kernel: int = 4
    chunk_size: int = 128
    time_step: tuple[float, float, float] = (0.001, 0.1, 1e-4)
    # E
    num_experts: int = 128
    held: tuple[int, int] = (0, 8)  # one of 16 chips' experts of a layer
    experts_per_token: int = 6
    expert_width: int = 1856
    shared_expert_width: int = 3712
    routed_scaling_factor: float = 2.5
    # *
    num_heads: int = 32
    num_kv_heads: int = 2
    head_dim: int = 128
    patch: int = 16
    rms_eps: float = 1e-5


class Layer(nn.Module):
    """One pre-norm layer of kind ``kind``: ``(h + mixer(norm(h)),
    experts, passed)``, ``experts`` the ``E`` layer's choices
    ``[B*T, k]`` and ``passed`` its buffer's verdict (an empty ``[0, k]``
    and 0 for the other kinds, so that every layer returns the same
    structure under ``nn.remat``)."""

    kind: str
    w: Widths
    dtype: Dtype = jnp.float32

    @nn.compact
    def __call__(self, h):
        c, dtype = self.w, self.dtype
        # rescale_prenorm_residual: every projection back into the
        # residual stream starts 1/sqrt(layers) smaller
        out_std = 0.02 / math.sqrt(len(c.pattern))
        a = RMSNorm(c.rms_eps, dtype, name="norm")(h)
        experts = jnp.zeros((0, c.experts_per_token), jnp.int32)
        passed = jnp.zeros((), jnp.int32)
        if self.kind == "M":
            y = Mamba2Mixer(
                c.mamba_num_heads, c.mamba_head_dim, c.n_groups,
                c.ssm_state_size, c.conv_kernel, c.chunk_size, c.rms_eps,
                out_std, c.time_step, dtype, name="mixer")(a)
        elif self.kind == "E":
            y, experts, passed = HeldExperts(
                c.num_experts, c.held, c.experts_per_token, c.expert_width,
                c.routed_scaling_factor, out_std, dtype, name="mixer")(a)
            y = y + SharedExpert(c.shared_expert_width, out_std, dtype,
                                 name="shared")(a)
        elif self.kind == "*":
            y = GQAttention(c.num_heads, c.num_kv_heads, c.head_dim, out_std,
                            dtype, name="mixer")(a)
        else:
            raise ValueError(f"unknown layer kind {self.kind!r} in the "
                             f"pattern {c.pattern!r}; have {sorted(KINDS)}")
        return h + y, experts, passed


class NemotronH3D(nn.Module):
    """The trunk over 3D patch tokens, built from ``widths.pattern``."""

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

    def held_capacity_rows(self, batch_shape) -> int | None:
        c = self.widths
        return tokens3d.held_capacity_rows(
            batch_shape, c.patch, c.experts_per_token, c.held, c.num_experts)

    @nn.compact
    def __call__(self, x, train: bool = False):
        c = self.widths
        h = tokens3d.patch_embed(x, c.hidden_size, c.patch, c.rms_eps,
                                 self.dtype, _normal(0.02))
        (h,), (chosen, passed) = tokens3d.layer_stack(
            self, Layer, [(kind, c, self.dtype) for kind in c.pattern], (h,))
        logits = tokens3d.pooled_logits(h, self.num_classes, c.rms_eps,
                                        _normal(0.02))
        return logits, tokens3d.held_aux(jnp.zeros((), jnp.float32), chosen,
                                         passed, c.num_experts)
