"""Alternative weight-gradient for the C_in=1 stride-2 stem conv
(Pallas split-K; opt-in via ``NIDT_FAST_STEM=1``).

The flagship 3D CNNs open with ``Conv3d(1, 64, kernel_size=5, stride=2)``
(salient_models.py:147), and its kernel-gradient — a contraction of ~4M
patch rows onto a tiny 125x64 output — dominates the whole training
step: per-stage bisection puts stage f0's fwd+bwd at ~44 ms of a ~40 ms
full-model step, i.e. everything after the stem is free (PROFILE.md
round 2). Every XLA formulation measured lands 13-40 ms (conv emitter,
im2col+dot, k-split batched dot, parity-decomposed convs), far from the
shape's compute cost.

This module is the Pallas alternative. It is OFF by default: its one
measurement (PROFILE.md round 2, 80-96 ms against XLA's 13-40) was
taken on a chip that delivered ~75-200 GB/s of HBM bandwidth (nominal
v5e is 819), where the extra patch materialization made it NET SLOWER
despite the clean MXU contraction. Not measured on the current chip:
the split puts ~2.2 GB of traffic behind a canonical [128, K]x[K, 64]
MXU stream, so measure before enabling (ROADMAP D3).

Design (see ``_dw_pallas``): XLA builds one contiguous patch row per
tap from stride-2 parity sub-volumes, stacked to [128, R]; Pallas runs
the [128, R] x [R, C] contraction as a split-K grid of canonical MXU
dots with per-block f32 partials (no program_id, no cross-step
accumulation — composes with the engines' client-axis ``vmap``); a
ragged K tail falls to a tiny XLA dot.

``stem_conv3d`` wraps forward (plain XLA conv — fine on MXU) and this
backward in a ``custom_vjp``; dx falls back to the standard transposed
conv (dead-code-eliminated in training, where the input is data). On
non-TPU backends the whole op falls back to XLA autodiff. Gradient
products run in the training compute dtype (bf16 models -> bf16 dW,
matching XLA's own bf16 kernel-grad; f32 models keep f32).
"""

from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
from jax import lax

_DN = ("NDHWC", "DHWIO", "NDHWC")
_K = 5       # kernel size per spatial dim
_S = 2       # stride
_KB = 3      # parity-block taps per dim (ceil(K/S))
_P = 8       # parities (S^3)


def _conv(x: jax.Array, w: jax.Array) -> jax.Array:
    return lax.conv_general_dilated(x, w, (_S,) * 3, "VALID",
                                    dimension_numbers=_DN)


_BLK = 8192   # split-K block columns per grid step
_MROWS = 128  # tap rows padded to one MXU/lane tile


def _dw_kernel(p_ref, g_ref, out_ref):
    """One split-K block: out = P_blk @ g_blk, canonical [M,K]x[K,N] MXU
    orientation, f32 accumulate."""
    out_ref[0] = lax.dot_general(
        p_ref[...], g_ref[...], (((1,), (0,)), ((), ())),
        preferred_element_type=jnp.float32)


def _dw_pallas(x: jax.Array, g: jax.Array,
               interpret: bool = False) -> jax.Array:
    """dW [5,5,5,1,C] for y = conv3d(x, W, stride 2, VALID).

    Build: 8 parity sub-volumes of x (stride-2 slices), then one
    CONTIGUOUS row per tap — ``P[t] = flatten(x_par[p][block slice])`` —
    stacked to [128, R] (125 real taps + zero rows). Pure block copies;
    no conv emitter, no interleaving. Pallas then grids a split-K
    [128, blk] x [blk, C] MXU matmul over R with per-block f32 partials
    (summed by XLA); the ragged tail of R is a tiny XLA dot. Per-block
    partial outputs keep the kernel free of program_id/accumulation, so
    it composes with the engines' client-axis vmap."""
    import jax.experimental.pallas as pl
    from jax.experimental.pallas import tpu as pltpu

    od, oh, ow = g.shape[1:4]
    c_out = g.shape[4]
    # products run in the training compute dtype: bf16 models get bf16
    # dW (matching XLA's own bf16 kernel-grad); f32 models keep f32
    cdtype = (x.dtype if x.dtype in (jnp.float32, jnp.bfloat16)
              else jnp.bfloat16)
    xb = x[..., 0].astype(cdtype)
    rows = []
    for kd in range(_K):
        for kh in range(_K):
            for kw in range(_K):
                par = xb[:, kd % _S::_S, kh % _S::_S, kw % _S::_S]
                sl = par[:, kd // _S:kd // _S + od,
                         kh // _S:kh // _S + oh,
                         kw // _S:kw // _S + ow]
                rows.append(sl.reshape(-1))
    r = rows[0].shape[0]
    taps = len(rows)                                     # 125
    p2 = jnp.stack(
        rows + [jnp.zeros((r,), cdtype)] * (_MROWS - taps))
    g2 = g.astype(cdtype).reshape(-1, c_out)             # [R, C]

    nblk = r // _BLK
    rmain = nblk * _BLK
    if nblk == 0:  # tiny inputs (tests): the ragged-tail dot covers all of R
        dw = lax.dot_general(p2[:taps], g2, (((1,), (0,)), ((), ())),
                             preferred_element_type=jnp.float32)
        return dw.reshape(_K, _K, _K, 1, c_out)
    part = pl.pallas_call(
        _dw_kernel,
        out_shape=jax.ShapeDtypeStruct((nblk, _MROWS, c_out), jnp.float32),
        grid=(nblk,),
        in_specs=[pl.BlockSpec((_MROWS, _BLK), lambda i: (0, i)),
                  pl.BlockSpec((_BLK, c_out), lambda i: (i, 0))],
        out_specs=pl.BlockSpec((1, _MROWS, c_out), lambda i: (i, 0, 0)),
        compiler_params=pltpu.CompilerParams(
            dimension_semantics=("arbitrary",)),
        interpret=interpret,
    )(p2[:, :rmain], g2[:rmain])

    dw = jnp.sum(part, axis=0)[:taps]                    # [125, C]
    if rmain < r:                                        # ragged K tail
        dw = dw + lax.dot_general(
            p2[:taps, rmain:], g2[rmain:], (((1,), (0,)), ((), ())),
            preferred_element_type=jnp.float32)
    return dw.reshape(_K, _K, _K, 1, c_out)


@jax.custom_vjp
def stem_conv3d(x: jax.Array, w: jax.Array) -> jax.Array:
    """``conv3d(x, w, stride 2, VALID)`` for single-channel NDHWC input
    with a Pallas weight-gradient on TPU (XLA autodiff elsewhere)."""
    return _conv(x, w)


def _fwd(x, w):
    return _conv(x, w), (x, w)


def _bwd(res, g):
    x, w = res
    # dx via the standard transposed conv — XLA DCEs it when the input is
    # training data (nothing consumes the cotangent)
    _, vjp = jax.vjp(lambda x_: _conv(x_, w), x)
    (dx,) = vjp(g)
    if jax.default_backend() == "tpu":
        dw = _dw_pallas(x, g).astype(w.dtype)
    else:
        _, vjp_w = jax.vjp(lambda w_: _conv(x, w_), w)
        (dw,) = vjp_w(g)
    return dx, dw


stem_conv3d.defvjp(_fwd, _bwd)


@functools.partial(jax.jit, static_argnames=())
def _dw_reference(x, g):
    """XLA kernel-grad (for tests): dW of sum(conv * g)."""
    _, vjp_w = jax.vjp(lambda w_: _conv(x, w_),
                       jnp.zeros((_K, _K, _K, 1, g.shape[-1]), x.dtype))
    (dw,) = vjp_w(g)
    return dw
