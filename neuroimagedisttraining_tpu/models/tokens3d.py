"""What the token trunks share (models/olmoe3d.py, nemotronh3d.py,
zaya3d.py, evabyte3d.py, moonlight3d.py, trinity3d.py): how a decoder trunk meets a
volume, how its one logit is read, and what more than one of them computes
the same way between the two. No trunk file imports another: each brings
its attention, its router and its ``Widths``, and takes the rest from here
and from ``ops/`` (exact causal attention: ops/attention.py).

    x uint8 [B,121,145,121] -> (x - mean) / std of the volume, zero-pad
                               to a multiple of the patch
    tokens = patches(P^3, raster order D,H,W) @ W_pe + b_pe    (the ``stem``)
    ... the trunk's layers ...                                 (``layer_stack``)
    logit = mean_t(RMSNorm(h)) @ W_head, in float32            (the ``head``)

as vision-language models feed a decoder (``inputs_embeds``) and as
embedding models read one (the mean of the final hidden states). Why the
volume is standardised and the read-out pooled is in
benchmark/configs/olmoe-abcd.json (``assumed``). The helpers create their
flax parameters and modules in the calling ``@nn.compact`` method, under
the names the trunks' parameter trees have always had (``patch_embed``,
``final_norm``, ``head``, ``layers_{i}``, ``up``, ``down``).

A trunk that holds a share of its experts says so to core/trainer.py and
engines/fedavg.py, which read by name: ``held_experts`` ``(first,
count)``, over which the round driver counts ``rows_held``;
``held_capacity_rows(batch_shape)``; ``aux_counters``, the integer
entries of its auxiliary dict (``held_aux``), summed over a round's real
steps. ``row_tokens(row_shape)`` is what a row of an evaluation batch
costs, for a trunk whose rows cost more than the default allows.
"""

from __future__ import annotations

import math
from typing import Any

import flax.linen as nn
import jax
import jax.numpy as jnp

from neuroimagedisttraining_tpu.obs import names as obs_names
from neuroimagedisttraining_tpu.ops import attention, moe

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


def row_tokens(row_shape, patch: int) -> int:
    """Tokens of one volume ``[D, H, W, ...]`` (core/trainer.py
    ``eval_batch_rows``: the cap under which ``eval_batches`` balances a
    client's rows)."""
    return token_count((1, *row_shape), patch)


def normal(std: float):
    return nn.initializers.normal(stddev=std)


def relu2(x):
    return jnp.square(nn.relu(x))


def swiglu(width: int):
    """``silu(gate) * up`` of a ``[rows, 2 * width]`` product whose first
    ``width`` columns are the gate's."""
    return lambda u: nn.silu(u[:, :width]) * u[:, width:]


class GatedMLP(nn.Module):
    """``(silu(x W_gate) * (x W_up)) W_down`` at ``width``, no bias."""

    hidden: int
    width: int
    init_std: float
    dtype: Dtype = jnp.float32

    @nn.compact
    def __call__(self, x):
        dense = lambda n, name: nn.Dense(
            n, use_bias=False, dtype=self.dtype, name=name,
            kernel_init=normal(self.init_std))
        gated = nn.silu(dense(self.width, "gate_proj")(x)) \
            * dense(self.width, "up_proj")(x)
        return dense(self.hidden, "down_proj")(gated)


def linear_router(module, x, outputs: int, k: int, std: float, **route):
    """``moe.route(x W_r, k, **route)`` -> ``(scores, weights, experts)``:
    one matrix ``[d, outputs]``, named as its stage is, in float32
    whatever the compute dtype (the architectures say so: bf16 logits
    flip near-tied experts, and the router is 0.2% of the FLOPs)."""
    w_router = module.param(obs_names.SCOPE_ROUTER, normal(std),
                            (x.shape[-1], outputs), jnp.float32)
    with _scope(obs_names.SCOPE_ROUTER):
        logits = jnp.dot(x.astype(jnp.float32), w_router,  # nidt: allow[precision-upcast] -- see above
                         precision=jax.lax.Precision.HIGHEST)
        return moe.route(logits, k, **route)


def held_expert_body(module, x, weights, experts, outputs: int, held,
                     width: int, gated: bool, stds):
    """An expert layer's routed part for the ``held = (first, count)``
    experts this chip holds, after the trunk's own router over
    ``outputs`` outputs: ``x [rows, d]``, ``weights, experts [rows, k]``
    -> ``(y [rows, d], passed)`` (ops/moe.py ``held_expert_rows``).
    Declares ``up [count, d, width]`` and ``down [count, width, d]`` in
    ``module`` at the standard deviations ``stds``; ``gated``: gate and
    up side by side in ``up [count, d, 2 * width]`` (:func:`swiglu`),
    else :func:`relu2`."""
    first, count = held
    d = x.shape[-1]
    up = module.param("up", normal(stds[0]),
                      (count, d, 2 * width if gated else width), jnp.float32)
    down = module.param("down", normal(stds[1]), (count, width, d),
                        jnp.float32)
    # no buffer while initialising: the trainer initialises eagerly, and
    # an eager loop compiles anew on every call (as layer_stack's remat)
    return moe.held_expert_rows(
        x, weights, experts, up, down, outputs, first,
        swiglu(width) if gated else relu2,
        buffer=not module.is_initializing())


def held_capacity_rows(batch_shape, patch: int, slots_per_token: int,
                       held, outputs: int) -> int | None:
    """The rows of the held runs' buffer for a batch ``[B, D, H, W, ...]``
    of volumes (ops/moe.py ``held_capacity``), ``None`` where such a
    batch is computed by the full sort alone."""
    return moe.held_capacity(
        slots_per_token * token_count(batch_shape, patch), held[1], outputs)


def held_aux(loss, chosen, passed, outputs: int, **counters) -> dict:
    """``loss`` as the trunk weighted it, ``expert_tokens`` the slots
    routed to each of the ``outputs`` router outputs (``chosen``: ``[rows,
    k]`` a layer), ``held_overflow_calls`` the layers whose held rows
    ``passed`` the buffer in this call, and the trunk's own ``counters``."""
    with _scope(obs_names.SCOPE_ROUTER):
        return {
            "loss": loss,
            "expert_tokens": jnp.bincount(
                jnp.concatenate(chosen).reshape(-1),
                length=outputs).astype(jnp.int32),
            "held_overflow_calls": sum(passed),
            **counters,
        }


def layer_stack(module, layer_cls, layer_args, carry):
    """Layer ``i`` is ``layer_cls(*layer_args[i], name="layers_{i}")``,
    called on the tuple ``carry`` (the stream, and what else a layer
    hands the next) and returning it anew, alone or followed by outputs
    of its own -> ``(carry, outputs)``, a list over the layers for each
    of those. Rematerialised where ``module.remat_layers`` says so, but
    not while initialising: the trainer initialises eagerly, and a
    rematerialised layer run eagerly compiles its body anew on every
    call (four compilations inside the benchmark's measured window, my
    chip run, PR 29); the parameter tree is the same.

    A rematerialised layer keeps its input and, by name, the attention
    kernel's two outputs (ops/attention.py ``KEPT``: ``o`` and the rows'
    log-sum-exp, 40.5 MB a Moonlight layer and step, 81.0 MB a Trinity-Mini
    one); the backward pass computes everything else again. Those two are
    all the backward kernel needs that the layer's second forward would
    otherwise run the forward kernel for (3.9-6.6 ms a layer, PR 45), so a
    layer and step run it once. The names exist only where
    ``causal_attention`` took the kernel: a trunk on the plain forms
    (``kernel=False``, off the TPU, models/evabyte3d.py's own attention)
    traces none, the policy then keeps nothing, and its step is the
    program ``nn.remat(layer_cls)`` alone compiles to
    (tests/test_tpu_compile.py ``PARENT_STEPS``)."""
    remat = module.remat_layers and not module.is_initializing()
    layer = nn.remat(
        layer_cls,
        policy=jax.checkpoint_policies.save_only_these_names(*attention.KEPT),
    ) if remat else layer_cls
    outputs = []
    for i, args in enumerate(layer_args):
        out = layer(*args, name=f"layers_{i}")(*carry)
        out = out if isinstance(out, tuple) else (out,)
        carry = out[:len(carry)]
        outputs.append(out[len(carry):])
    return carry, [list(column) for column in zip(*outputs)]


def attention_counters(kernel_calls) -> dict:
    """A trunk's two attention counters from ``kernel_calls``, its layers'
    attention calls that ran as the kernel: ``attn_kernel_calls`` itself,
    and ``attn_outputs_kept``, those among them whose forward kernel the
    backward pass does not run again. Under :func:`layer_stack` that is
    every one (a rematerialised layer keeps the kernel's outputs, any
    other keeps all its residuals), and none on the plain forms, which
    have no kernel to keep the outputs of."""
    return {"attn_kernel_calls": kernel_calls,
            "attn_outputs_kept": kernel_calls}
