"""Weight gradient of the stem convolution, on the MXU when a client
axis is batched.

The flagship 3D CNNs open with ``Conv3d(1, C, kernel_size=5, stride=2)``
(salient_models.py:147). XLA lowers its kernel gradient as a convolution
that contracts over the batch alone, 16 of the MXU's 128 rows at the
reference batch; under the engines' client-axis ``vmap`` the grouped
form of it cost 18.1 ms a client step on a v5e (and 2.3 ms more for a
padded copy of ``x``) against 7.4 ms unbatched: a third of the FedAvg
cell's device time (PERF.md, PR 24).

The output column and the sample are independent of each other in this
convolution, so both can be the contracted batch:

    x'[d, h, kw, (ow, n)] = x[n, d, h, 2 ow + kw]          (2.5x the input)
    dW[kd, kh, kw, c] = sum_{od, oh, (ow, n)} x'[2 od + kd, 2 oh + kh, kw, (ow, n)]
                                              * g[n, od, oh, ow, c]

which is XLA's own kernel gradient of a k5 x5, stride-2 convolution over
(D, H) with five input channels and a batch of ``W_out * N`` (944 at the
flagship's shape): one contraction 944 deep that reads ``g`` in the tiles
it already lies in (1.9 ms a client), no patch matrix, no Pallas, no
``tpu_custom_call``.

Which form runs is decided by what the code can see. ``stem_conv3d`` is a
``custom_vjp`` whose forward and input gradient are the plain
convolution; its weight gradient is a ``custom_vmap``. Called unbatched
(``cohort_map``'s ``shard_map`` + ``lax.map``, a single client) it is XLA's
own kernel gradient, which fuses the norm's backward into the contraction
and never writes ``g``: the compiled program is what plain autodiff gives
(the re-expressed form, which has to have ``g`` written out and builds
``x'``, measured 2.8 ms a step slower there). Batched, each client's
contraction is the re-expressed one, one client after another: a client's
``g`` is one strided copy out of the stack (XLA keeps the client axis
between ``ow`` and ``n``, so a grouped form cannot merge ``(ow, n)``
without relaying all of ``g`` out twice: 22.8 ms a 4-client step against
12.4 for the copies). 8.4 ms a client step all told, ``g`` written out
included. The parameter stays ``[5, 5, 5, 1, C]`` throughout.
"""

from __future__ import annotations

import jax
import jax.numpy as jnp
from jax import lax

_DN = ("NDHWC", "DHWIO", "NDHWC")
_K = 5       # kernel size per spatial dim
_S = 2       # stride
_LANES = 128
#: x' [D, H, kw, B] (batch kw, feature B) * g' [od, oh, B, C] -> [kd, kh, kw, C]
_DN_DW = lax.ConvDimensionNumbers(lhs_spec=(2, 3, 0, 1),
                                  rhs_spec=(3, 2, 0, 1),
                                  out_spec=(2, 3, 0, 1))


def _conv(x: jax.Array, w: jax.Array) -> jax.Array:
    return lax.conv_general_dilated(x, w, (_S,) * 3, "VALID",
                                    dimension_numbers=_DN)


def _to_lanes(x: jax.Array) -> jax.Array:
    """``[N, D, H, W, 1] -> x' [D, H, 5, W_out * N]``. One stride-2
    split of W; every tap is then a contiguous slice."""
    n, _, _, w = x.shape[:4]
    ow = (w - _K) // _S + 1
    xt = jnp.transpose(x[..., 0], (1, 2, 3, 0))               # [D, H, W, N]
    par = [xt[:, :, p::_S].reshape(xt.shape[:2] + (-1,)) for p in range(_S)]
    return jnp.stack([par[kw % _S][:, :, (kw // _S) * n:(kw // _S + ow) * n]
                      for kw in range(_K)], axis=2)


def _dw_lanes(x: jax.Array, g: jax.Array) -> jax.Array:
    """dW ``[5, 5, 5, 1, C]`` of ``conv3d(x, W, stride 2, VALID)`` as one
    contraction over ``(od, oh, (ow, n))``; f32 accumulation."""
    n, od, oh, ow, c = g.shape
    gp = jnp.transpose(g, (1, 2, 3, 0, 4)).reshape(od, oh, ow * n, c)
    # With at least 128 output channels XLA reads g' channel-minor, the
    # tiles g already lies in; with 64 it wants (ow, n) minor and relays
    # all of g out for that. The pad fuses into the contraction's operand
    # (no bytes, no further MXU passes: 64 channels fill half a pass), and
    # the barrier keeps the simplifier from slicing it away again.
    gp = jnp.pad(gp, ((0, 0),) * 3 + ((0, -c % _LANES),))
    dw = lax.optimization_barrier(lax.conv_general_dilated(
        _to_lanes(x), gp, (1, 1), "VALID", rhs_dilation=(_S, _S),
        dimension_numbers=_DN_DW, preferred_element_type=jnp.float32))
    # an even extent leaves one more window position than the kernel has
    return dw[:_K, :_K, :, None, :c].astype(x.dtype)


@jax.custom_batching.custom_vmap
def _dw(x: jax.Array, g: jax.Array) -> jax.Array:
    """Unbatched: XLA's own kernel gradient (module docstring)."""
    kernel = jnp.zeros((_K, _K, _K, 1, g.shape[-1]), x.dtype)
    _, vjp = jax.vjp(lambda w: _conv(x, w), kernel)
    return vjp(g)[0]


@_dw.def_vmap
def _dw_batched(axis_size, in_batched, x, g):
    x, g = (a if b else jnp.broadcast_to(a, (axis_size,) + a.shape)
            for a, b in zip((x, g), in_batched))
    return lax.map(lambda t: _dw_lanes(*t), (x, g)), True


@jax.custom_vjp
def stem_conv3d(x: jax.Array, w: jax.Array) -> jax.Array:
    """``conv3d(x, w, stride 2, VALID)`` for ``x [N, D, H, W, 1]`` and
    ``w [5, 5, 5, 1, C]`` of one dtype, NDHWC."""
    return _conv(x, w)


def _fwd(x, w):
    return _conv(x, w), (x, w)


def _bwd(res, g):
    x, w = res
    # XLA removes dx when the input is data (nothing reads its cotangent)
    _, vjp_x = jax.vjp(lambda x_: _conv(x_, w), x)
    return vjp_x(g)[0], _dw(x, g)


stem_conv3d.defvjp(_fwd, _bwd)
