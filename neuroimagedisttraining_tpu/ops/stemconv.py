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
contraction is the re-expressed one, one client after another. The
parameter stays ``[5, 5, 5, 1, C]`` throughout.

``stem_block`` (PR 37) is the whole first stage behind the same
construction: convolution + bias, batch norm, relu and the stage's max
pool. ``vmap`` over clients turns the convolution into a grouped one
whose output holds the clients' channels side by side,
``[N, od, oh, ow, clients x C]``: 4 x 64 channels fill 256 lanes. Left to
itself the program then splits that axis back into ``[..., 4, 64]`` and
every later tensor of the stage, forward and backward, pads 64 channels to
a 128-lane tile: five 2 GB activations a step written at twice their
size. Batched, ``stem_block`` stays in the convolution's layout instead: a
merged channel's statistics over ``(N, D, H, W)`` ARE that client's
channel's, norm, relu and pool never mix channels, and the client axis is
split off once, after the pool, at 1/27 of the size (behind an
``optimization_barrier``: without it XLA hoists the split above the
pool). Backward, the pool's, relu's and norm's cotangents stay merged,
and each client's weight gradient reads ``g`` where it lies: the merged
``g`` viewed as ``[od, oh, (ow, n), clients x C]`` is the channel-minor
operand the contraction above wants, and a client's contraction takes
the 128-lane window that holds its channels (with 64 channels the
neighbour's ride in the half of the MXU pass that ``_dw_lanes`` pads with
zeros, and are sliced off the result): no transpose of ``g``, no pad, no
copy of a client's ``g`` out of a stack. Nothing the forward computed is
computed again: it hands the backward the two activations that reads.
Unbatched it is the plain composition with XLA's own kernel gradient: the
compiled program holds what plain autodiff's holds (the statistics' chain
rule is spelled by hand, a few per-channel vector ops apart).
"""

from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
from jax import lax

from neuroimagedisttraining_tpu.obs import names as obs_names

_DN = ("NDHWC", "DHWIO", "NDHWC")
_K = 5       # kernel size per spatial dim
_S = 2       # stride
_LANES = 128
#: x' [D, H, kw, B] (batch kw, feature B) * g' [od, oh, B, C] -> [kd, kh, kw, C]
_DN_DW = lax.ConvDimensionNumbers(lhs_spec=(2, 3, 0, 1),
                                  rhs_spec=(3, 2, 0, 1),
                                  out_spec=(2, 3, 0, 1))


def _conv(x: jax.Array, w: jax.Array, groups: int = 1) -> jax.Array:
    return lax.conv_general_dilated(x, w, (_S,) * 3, "VALID",
                                    dimension_numbers=_DN,
                                    feature_group_count=groups)


def _to_lanes(x: jax.Array) -> jax.Array:
    """``[N, D, H, W, 1] -> x' [D, H, 5, W_out * N]``. One stride-2
    split of W; every tap is then a contiguous slice."""
    n, _, _, w = x.shape[:4]
    ow = (w - _K) // _S + 1
    xt = jnp.transpose(x[..., 0], (1, 2, 3, 0))               # [D, H, W, N]
    par = [xt[:, :, p::_S].reshape(xt.shape[:2] + (-1,)) for p in range(_S)]
    return jnp.stack([par[kw % _S][:, :, (kw // _S) * n:(kw // _S + ow) * n]
                      for kw in range(_K)], axis=2)


def _rows(g: jax.Array) -> jax.Array:
    """``g [N, od, oh, ow, C] -> g' [od, oh, (ow, n), C]``, channel-minor."""
    n, od, oh, ow, c = g.shape
    return jnp.transpose(g, (1, 2, 3, 0, 4)).reshape(od, oh, ow * n, c)


def _contract(x: jax.Array, gp: jax.Array) -> jax.Array:
    """``x' * g' -> [5, 5, 5, C]`` in float32: one contraction over
    ``(od, oh, (ow, n))``. The barrier keeps the simplifier from slicing
    ``g'``'s channels down to the ones the caller keeps."""
    dw = lax.optimization_barrier(lax.conv_general_dilated(
        _to_lanes(x), gp, (1, 1), "VALID", rhs_dilation=(_S, _S),
        dimension_numbers=_DN_DW, preferred_element_type=jnp.float32))
    # an even extent leaves one more window position than the kernel has
    return dw[:_K, :_K]


def _dw_lanes(x: jax.Array, g: jax.Array) -> jax.Array:
    """dW ``[5, 5, 5, 1, C]`` of ``conv3d(x, W, stride 2, VALID)`` as one
    contraction over ``(od, oh, (ow, n))``; f32 accumulation."""
    c = g.shape[-1]
    # With at least 128 output channels XLA reads g' channel-minor, the
    # tiles g already lies in; with 64 it wants (ow, n) minor and relays
    # all of g out for that. The pad fuses into the contraction's operand
    # (no bytes, no further MXU passes: 64 channels fill half a pass).
    gp = jnp.pad(_rows(g), ((0, 0),) * 3 + ((0, -c % _LANES),))
    return _contract(x, gp)[:, :, :, None, :c].astype(x.dtype)


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


# --------------------------------------------------------------------------
# the whole stage: convolution + bias, batch norm, relu, max pool
# --------------------------------------------------------------------------
_EPS = 1e-5  # nn.BatchNorm's epsilon in models/neuro3d.py


def _batch_stats(y):
    """``nn.BatchNorm``'s statistics of ``y`` over ``(N, D, H, W)``:
    float32, the one-pass variance floored at zero."""
    yf = y.astype(jnp.promote_types(y.dtype, jnp.float32))
    mean = jnp.mean(yf, (0, 1, 2, 3))
    return mean, jnp.maximum(
        0.0, jnp.mean(lax.square(yf), (0, 1, 2, 3)) - lax.square(mean))


def _norm_relu(y, scale, offset, mean, var):
    """``nn.BatchNorm(dtype=y.dtype, epsilon=1e-5)``'s normalisation by
    the statistics it is given, then relu, channels last."""
    z = (y - mean) * (lax.rsqrt(var + _EPS) * scale) + offset
    return jax.nn.relu(z.astype(y.dtype))


def _max_pool(a, k: int):
    return lax.reduce_window(a, -jnp.inf, lax.max, (1, k, k, k, 1),
                             (1, k, k, k, 1), "VALID")


def _conv_bias(x, kernel, bias, groups: int = 1):
    return _conv(x, kernel.astype(x.dtype), groups) + bias.astype(x.dtype)


def _block(x, kernel, bias, scale, offset, mean, var, *, train, pool,
           groups: int = 1):
    """The stage on ``groups`` clients' channels side by side (one
    client: the plain composition), ``-> (pooled, mean, var), (y, a)``:
    its results, and the two activations its backward reads (convolution
    + bias, and the pool's input)."""
    y = _conv_bias(x, kernel, bias, groups)
    if train:
        mean, var = _batch_stats(y)
    a = _norm_relu(y, scale, offset, mean, var)
    with jax.named_scope(obs_names.SCOPE_POOL0):
        return (_max_pool(a, pool), mean, var), (y, a)


def _block_vjp(y, a, scale, offset, mean, var, cts, *, train, pool):
    """``-> g, (dscale, doffset, dmean, dvar)``: the pool's, relu's and
    norm's backward down to ``g``, the cotangent of convolution + bias, in
    whichever layout ``y`` and ``a`` are in; nothing the forward computed
    is computed again. ``mean`` / ``var`` are the statistics the forward
    normalised by; training, their cotangents (the norm's own and the
    caller's for the returned statistics) go back into ``g`` by the chain
    rule of :func:`_batch_stats`, and the running ones get none. The
    pool's scope stands AROUND its ``jax.vjp``: entered under it, it would
    be named ``transpose(jvp(pool0))`` and no rule of the benchmark would
    know it."""
    with jax.named_scope(obs_names.SCOPE_POOL0):
        da, = jax.vjp(lambda a_: _max_pool(a_, pool), a)[1](cts[0])
    g, dscale, doffset, dmean, dvar = jax.vjp(
        _norm_relu, y, scale, offset, mean, var)[1](da)
    dmean, dvar = dmean + cts[1], dvar + cts[2]
    if train:
        # mean = E[y], var = max(0, E[y^2] - mean^2)
        dsquare = jnp.where(var > 0, dvar, 0.0)
        count = y.size // y.shape[-1]
        g = g + ((dmean - 2.0 * mean * dsquare + 2.0 * dsquare * y)
                 / count).astype(y.dtype)
        dmean, dvar = jnp.zeros_like(dmean), jnp.zeros_like(dvar)
    return g, (dscale, doffset, dmean, dvar)


def _merge(v, lead: int = 0):
    """``[C, *lead dims, F] -> [*lead dims, C * F]``: the clients'
    channels side by side, as the grouped convolution lays them."""
    v = jnp.moveaxis(v, 0, lead)
    return v.reshape(v.shape[:lead] + (-1,))


def _split(v, clients: int):
    """``[*lead dims, C * F] -> [C, *lead dims, F]``."""
    v = v.reshape(v.shape[:-1] + (clients, -1))
    return jnp.moveaxis(v, -2, 0)


def _stacked(tree, batched, axis_size: int):
    """An operand the ``vmap`` does not batch is every client's."""
    leaves, treedef = jax.tree.flatten(tree)
    return treedef.unflatten(
        v if b else jnp.broadcast_to(v, (axis_size,) + v.shape)
        for v, b in zip(leaves, jax.tree.leaves(batched)))


def _merged_args(x, kernel, *vectors):
    """Client-stacked operands in the merged layout: ``x [N, D, H, W, C]``,
    kernel ``[5, 5, 5, 1, C * F]``, the per-channel vectors ``[C * F]``."""
    return (_merge(x[..., 0], 4), _merge(kernel, 4),
            *(_merge(v) for v in vectors))


def _window(clients: int, features: int) -> int:
    """Channels a client's contraction reads of the merged ``g``: a whole
    lane tile where the client's ``features`` lie inside one, its own
    channels otherwise."""
    whole = _LANES % features == 0 and clients * features >= _LANES
    return _LANES if whole else features


def _dw_merged(xs, g):
    """Each client's dW ``[C, 5, 5, 5, 1, F]`` from the merged ``g [N, od,
    oh, ow, C * F]``, one client after another."""
    clients = xs.shape[0]
    features = g.shape[-1] // clients
    gp = _rows(g)
    width = _window(clients, features)

    def one(t):
        c, x = t
        first = jnp.minimum(c * features // width * width,
                            clients * features - width)
        dw = _contract(x, lax.dynamic_slice_in_dim(gp, first, width, 3))
        return lax.dynamic_slice_in_dim(dw, c * features - first, features,
                                        3)

    dw = lax.map(one, (jnp.arange(clients), xs))
    return dw[:, :, :, :, None].astype(xs.dtype)


@functools.lru_cache(maxsize=None)
def _stem_block(train: bool, pool: int):
    """``stem_block`` for one static ``(train, pool)``: a ``custom_vjp``
    whose forward and backward are ``custom_vmap``s. The forward hands the
    backward the two activations it reads; merged, they cross in the
    clients-first shape a batched value has to have, and the backward's
    first act undoes the forward's last: the compiler cancels the pair."""
    block = functools.partial(_block, train=train, pool=pool)
    block_vjp = functools.partial(_block_vjp, train=train, pool=pool)

    @jax.custom_batching.custom_vmap
    def forward(x, kernel, bias, scale, offset, mean, var):
        return block(x, kernel, bias, scale, offset, mean, var)

    @forward.def_vmap
    def forward_merged(axis_size, in_batched, *args):
        with jax.named_scope(obs_names.SCOPE_STEM_MERGED):
            merged = _merged_args(*_stacked(args, in_batched, axis_size))
            (out, mean, var), (y, a) = block(*merged, groups=axis_size)
            # the split belongs after the pool, at 1/27 of the size
            out = lax.optimization_barrier(out)
            outs = jax.tree.map(lambda v: _split(v, axis_size),
                                ((out, mean, var), (y, a)))
            return outs, jax.tree.map(lambda _: True, outs)

    @jax.custom_batching.custom_vmap
    def backward(res, cts):
        (x, kernel, bias, scale, offset, _, _), (y, a), mean, var = res
        g, dvectors = block_vjp(y, a, scale, offset, mean, var, cts)
        # XLA's own kernel gradient, with the norm's backward fused in
        return (*jax.vjp(_conv_bias, x, kernel, bias)[1](g), *dvectors)

    @backward.def_vmap
    def backward_merged(axis_size, in_batched, res, cts):
        with jax.named_scope(obs_names.SCOPE_STEM_MERGED):
            (args, acts, *stats), cts = (
                _stacked(t, b, axis_size)
                for t, b in zip((res, cts), in_batched))
            x, kernel, _, scale, offset, _, _ = _merged_args(*args)
            y, a = (_merge(v, 4) for v in acts)
            mean, var = (_merge(v) for v in stats)
            cts = (lax.optimization_barrier(_merge(cts[0], 4)),
                   _merge(cts[1]), _merge(cts[2]))
            g, dvectors = block_vjp(y, a, scale, offset, mean, var, cts)
            dbias = jnp.sum(g, (0, 1, 2, 3), dtype=jnp.float32)
            # XLA removes dx when the input is data
            dx = jax.vjp(lambda x_: _conv(x_, kernel.astype(x.dtype),
                                          axis_size), x)[1](g)[0]
            dkernel = _dw_merged(args[0], g)
            grads = (_split(dx, axis_size), dkernel.astype(args[1].dtype),
                     *(_split(d.astype(v.dtype), axis_size)
                       for d, v in zip((dbias, *dvectors), args[2:])))
            return grads, (True,) * len(grads)

    @jax.custom_vjp
    def stem(x, kernel, bias, scale, offset, mean, var):
        return forward(x, kernel, bias, scale, offset, mean, var)[0]

    def stem_fwd(*args):
        outs, acts = forward(*args)
        return outs, (args, acts, outs[1], outs[2])

    stem.defvjp(stem_fwd, backward)
    return stem


def stem_block(x, kernel, bias, scale, offset, mean, var, *, train: bool,
               pool: int):
    """The 3D CNNs' first stage, ``-> (pooled activation, batch mean,
    batch var)``: ``conv3d(x, kernel, stride 2, VALID) + bias`` for ``x
    [N, D, H, W, 1]`` in the compute dtype and ``kernel [5, 5, 5, 1, F]``,
    batch norm (float32 statistics over ``(N, D, H, W)`` when ``train``,
    else the running ``mean`` / ``var``, which are then returned as they
    came), relu, and a ``pool``^3 max pool of stride ``pool``, VALID.
    Under a client-axis ``vmap`` the stage computes in the grouped
    convolution's layout (module docstring)."""
    return _stem_block(bool(train), int(pool))(x, kernel, bias, scale,
                                               offset, mean, var)
