"""Plain float32 building blocks of the reference forward passes.

Straightforward ``jax.numpy`` / ``jax.lax``: no flax module, no kernel, no
rematerialisation, nothing imported from the program. Channels-last
(NDHWC), as the program stores its weights. Every matrix product runs at
``Precision.HIGHEST``: on a TPU a float32 convolution otherwise runs in
bf16 passes, and the reference would then be no reference.

Each op that has weights appends one record to ``tape`` (when given), from
which ``benchmark/flops.py`` counts operations: the count comes from the
shapes the reference itself saw, not from the program's model.
"""

from __future__ import annotations

import jax
import jax.numpy as jnp
from jax import lax

HIGHEST = lax.Precision.HIGHEST
BN_EPS = 1e-5
_DIMS = ("NDHWC", "DHWIO", "NDHWC")


def conv3d(x, kernel, bias=None, *, stride=1, pad=0, tape=None, name=""):
    out = lax.conv_general_dilated(
        x, kernel.astype(jnp.float32), (stride,) * 3, [(pad, pad)] * 3,
        dimension_numbers=_DIMS, precision=HIGHEST)
    if bias is not None:
        out = out + bias.astype(jnp.float32)
    if tape is not None:
        tape.append({"name": name, "kind": "conv",
                     "kernel_shape": tuple(kernel.shape),
                     "out_spatial": tuple(out.shape[1:-1])})
    return out


def dense(x, kernel, bias, *, tape=None, name=""):
    if tape is not None:
        tape.append({"name": name, "kind": "dense",
                     "kernel_shape": tuple(kernel.shape)})
    return jnp.matmul(x, kernel.astype(jnp.float32),
                      precision=HIGHEST) + bias.astype(jnp.float32)


def batch_norm_eval(x, p, stats):
    """Inference batch norm over the running statistics."""
    inv = lax.rsqrt(stats["var"].astype(jnp.float32) + BN_EPS)
    return ((x - stats["mean"].astype(jnp.float32)) * inv
            * p["scale"].astype(jnp.float32) + p["bias"].astype(jnp.float32))


def _window(x, k, s, pad, init, op):
    return lax.reduce_window(
        x, init, op, (1, k, k, k, 1), (1, s, s, s, 1),
        [(0, 0)] + [(pad, pad)] * 3 + [(0, 0)])


def max_pool(x, k, s, pad=0):
    """Floor-mode max pooling; padding never wins (-inf)."""
    return _window(x, k, s, pad, -jnp.inf, lax.max)


def avg_pool(x, k, s):
    return _window(x, k, s, 0, 0.0, lax.add) / float(k ** 3)


def bce_with_logits(logits, labels):
    """Per-row binary cross-entropy of one logit, the stable form."""
    z = logits.reshape(-1).astype(jnp.float32)
    y = labels.reshape(-1).astype(jnp.float32)
    return jnp.maximum(z, 0.0) - z * y + jnp.log1p(jnp.exp(-jnp.abs(z)))


def prep(x_uint8):
    """uint8 volume ``[B, D, H, W]`` -> float32 ``[B, D, H, W, 1]``: the raw
    cast of the reference trainer (my_model_trainer.py:197-198)."""
    return x_uint8.astype(jnp.float32)[..., None]
