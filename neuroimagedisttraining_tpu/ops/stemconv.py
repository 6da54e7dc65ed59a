"""The 3D CNNs' first stage under a client-axis ``vmap``: the stem
convolution's weight gradient on the MXU, and the whole stage in the
grouped convolution's client-merged layout.

Two geometries open the models, both on a one-channel volume: the
flagship family's ``Conv3d(1, 64, kernel_size=5, stride=2)`` + bias, batch
norm in the compute dtype, relu, ``MaxPool3d(3, 3)`` (salient_models.py:
147-150), and ResNet3D's ``Conv3d(1, 64, kernel_size=3, stride=2,
padding=3, bias=False)``, float32 batch norm, relu, ``MaxPool3d(3, 2, 1)``
(``ResNet_l3``, salient_models.py:84-139). ``stem_conv3d`` is the first's
convolution alone (PR 24); ``stem_block`` is the stage, its geometry an
argument (PR 37, PR 39). What the geometry is follows the calling module's own fields;
which form runs follows the client axis, as ``custom_vmap`` sees it. No
flag chooses either.

The k5 stride-2 convolution first. XLA lowers its kernel gradient as a
convolution that contracts over the batch alone, 16 of the MXU's 128 rows
at the reference batch; under the engines' client-axis ``vmap`` the grouped
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
``tpu_custom_call``. With another kernel size ``k``, stride ``s`` and
zero padding (ResNet3D's 3, 2, 3: ``x`` is padded first, 68 MB a client)
it is the same contraction with ``k`` taps and ``s`` in place of 2
(``x'`` 1.5x the padded input, ``W_out * N`` = 1,008).

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
construction: convolution (+ bias), batch norm, relu and the stage's max
pool, in either geometry (PR 39: kernel size, stride, padding, bias or
none, the norm's output dtype and the pool's window, stride and padding
are static arguments of one rule). ``vmap`` over clients turns the
convolution into a grouped one whose output holds the clients' channels
side by side, ``[N, od, oh, ow, clients x C]``: the flagship's 4 x 64
channels fill 256 lanes, ResNet3D's 2 x 64 exactly one 128-lane tile. Left
to itself the program then splits that axis back into ``[..., 4, 64]`` and
every later tensor of the stage, forward and backward, pads 64 channels to
a 128-lane tile: five 2 GB activations a step written at twice their
size (in ResNet3D, whose norm is float32, 2.44 GB each written as 4.88,
and the compiler rematerialised one of them for want of room). Batched,
``stem_block`` stays in the convolution's layout instead: a merged
channel's statistics over ``(N, D, H, W)`` ARE that client's channel's,
norm, relu and pool never mix channels (a ``reduce_window`` padded with
``-inf`` no more than a VALID one), and the client axis is split off once,
after the pool, at 1/27 of the size (1/8 after ResNet3D's stride-2 pool;
behind an ``optimization_barrier``: without it XLA hoists the split above
the pool). Backward, the pool's, relu's and norm's cotangents stay merged,
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
from typing import Any, NamedTuple

import jax
import jax.numpy as jnp
from jax import lax

from neuroimagedisttraining_tpu.obs import names as obs_names

_DN = ("NDHWC", "DHWIO", "NDHWC")
_LANES = 128


class _Window(NamedTuple):
    """A cubic window and how it moves: a convolution's kernel or a
    pool's, the same on the three spatial dims."""

    size: int
    stride: int
    pad: int = 0

    @property
    def strides(self):
        return (self.stride,) * 3

    @property
    def padding(self):
        return ((self.pad, self.pad),) * 3

    def out(self, extent: int) -> int:
        return (extent + 2 * self.pad - self.size) // self.stride + 1


_STEM = _Window(5, 2)  # the flagship's stem: k5, stride 2, VALID
#: x' [D, H, kw, B] (batch kw, feature B) * g' [od, oh, B, C] -> [kd, kh, kw, C]
_DN_DW = lax.ConvDimensionNumbers(lhs_spec=(2, 3, 0, 1),
                                  rhs_spec=(3, 2, 0, 1),
                                  out_spec=(2, 3, 0, 1))


def _conv(x: jax.Array, w: jax.Array, at: _Window = _STEM,
          groups: int = 1) -> jax.Array:
    return lax.conv_general_dilated(x, w, at.strides, at.padding,
                                    dimension_numbers=_DN,
                                    feature_group_count=groups)


def _to_lanes(x: jax.Array, at: _Window) -> jax.Array:
    """``[N, D, H, W, 1] -> x' [D, H, k, W_out * N]`` (D, H, W with the
    convolution's zero padding: the one-channel input is the cheap tensor
    to pad). One split of W by the stride; every tap is then a contiguous
    slice."""
    k, s = at.size, at.stride
    n, ow = x.shape[0], at.out(x.shape[3])
    x = x[..., 0]
    if at.pad:
        x = jnp.pad(x, ((0, 0),) + at.padding)
    xt = jnp.transpose(x, (1, 2, 3, 0))                       # [D, H, W, N]
    par = [xt[:, :, p::s].reshape(xt.shape[:2] + (-1,)) for p in range(s)]
    return jnp.stack([par[kw % s][:, :, (kw // s) * n:(kw // s + ow) * n]
                      for kw in range(k)], axis=2)


def _rows(g: jax.Array) -> jax.Array:
    """``g [N, od, oh, ow, C] -> g' [od, oh, (ow, n), C]``, channel-minor."""
    n, od, oh, ow, c = g.shape
    return jnp.transpose(g, (1, 2, 3, 0, 4)).reshape(od, oh, ow * n, c)


def _contract(x: jax.Array, gp: jax.Array, at: _Window) -> jax.Array:
    """``x' * g' -> [k, k, k, C]`` in float32: one contraction over
    ``(od, oh, (ow, n))``. The barrier keeps the simplifier from slicing
    ``g'``'s channels down to the ones the caller keeps."""
    dw = lax.optimization_barrier(lax.conv_general_dilated(
        _to_lanes(x, at), gp, (1, 1), "VALID",
        rhs_dilation=(at.stride,) * 2, dimension_numbers=_DN_DW,
        preferred_element_type=jnp.float32))
    # the extent's remainder by the stride leaves window positions beyond
    # the kernel's
    return dw[:at.size, :at.size]


def _dw_lanes(x: jax.Array, g: jax.Array, at: _Window = _STEM) -> jax.Array:
    """dW ``[k, k, k, 1, C]`` of ``conv3d(x, W)`` with ``at``'s stride and
    padding as one contraction over ``(od, oh, (ow, n))``; f32
    accumulation."""
    c = g.shape[-1]
    # With at least 128 output channels XLA reads g' channel-minor, the
    # tiles g already lies in; with 64 it wants (ow, n) minor and relays
    # all of g out for that. The pad fuses into the contraction's operand
    # (no bytes, no further MXU passes: 64 channels fill half a pass).
    gp = jnp.pad(_rows(g), ((0, 0),) * 3 + ((0, -c % _LANES),))
    return _contract(x, gp, at)[:, :, :, None, :c].astype(x.dtype)


@jax.custom_batching.custom_vmap
def _dw(x: jax.Array, g: jax.Array) -> jax.Array:
    """Unbatched: XLA's own kernel gradient (module docstring)."""
    kernel = jnp.zeros((_STEM.size,) * 3 + (1, g.shape[-1]), x.dtype)
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
# the whole stage: convolution (+ bias), batch norm, relu, max pool
# --------------------------------------------------------------------------
_EPS = 1e-5  # nn.BatchNorm's epsilon in models/neuro3d.py


class _Stage(NamedTuple):
    """What is static of a first stage: read off the model's own fields
    by the caller of :func:`stem_block`, never chosen."""

    train: bool
    conv: _Window    # the convolution's kernel, stride and zero padding
    pool: _Window    # the max pool's window, stride and -inf padding
    norm_dtype: Any  # of the norm's output: relu, pool and cotangents


def _batch_stats(y):
    """``nn.BatchNorm``'s statistics of ``y`` over ``(N, D, H, W)``:
    float32, the one-pass variance floored at zero."""
    yf = y.astype(jnp.promote_types(y.dtype, jnp.float32))
    mean = jnp.mean(yf, (0, 1, 2, 3))
    return mean, jnp.maximum(
        0.0, jnp.mean(lax.square(yf), (0, 1, 2, 3)) - lax.square(mean))


def _norm_relu(y, scale, offset, mean, var, dtype):
    """``nn.BatchNorm(dtype=dtype, epsilon=1e-5)``'s normalisation by the
    statistics it is given, then relu, channels last."""
    z = (y - mean) * (lax.rsqrt(var + _EPS) * scale) + offset
    return jax.nn.relu(z.astype(dtype))


def _max_pool(a, at: _Window):
    return lax.reduce_window(
        a, -jnp.inf, lax.max, (1,) + (at.size,) * 3 + (1,),
        (1,) + at.strides + (1,), ((0, 0),) + at.padding + ((0, 0),))


def _conv_bias(x, kernel, bias, at: _Window = _STEM, groups: int = 1):
    return _conv(x, kernel.astype(x.dtype), at, groups) + bias.astype(x.dtype)


def _block(x, kernel, bias, scale, offset, mean, var, *, stage: _Stage,
           groups: int = 1):
    """The stage on ``groups`` clients' channels side by side (one
    client: the plain composition), ``-> (pooled, mean, var), (y, a)``:
    its results, and the two activations its backward reads (convolution
    + bias, and the pool's input)."""
    y = _conv_bias(x, kernel, bias, stage.conv, groups)
    if stage.train:
        mean, var = _batch_stats(y)
    a = _norm_relu(y, scale, offset, mean, var, stage.norm_dtype)
    with jax.named_scope(obs_names.SCOPE_POOL0):
        return (_max_pool(a, stage.pool), mean, var), (y, a)


def _block_vjp(y, a, scale, offset, mean, var, cts, *, stage: _Stage):
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
        da, = jax.vjp(lambda a_: _max_pool(a_, stage.pool), a)[1](cts[0])
    g, dscale, doffset, dmean, dvar = jax.vjp(
        functools.partial(_norm_relu, dtype=stage.norm_dtype),
        y, scale, offset, mean, var)[1](da)
    dmean, dvar = dmean + cts[1], dvar + cts[2]
    if stage.train:
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
    kernel ``[k, k, k, 1, C * F]``, the per-channel vectors ``[C * F]``."""
    return (_merge(x[..., 0], 4), _merge(kernel, 4),
            *(_merge(v) for v in vectors))


def _window(clients: int, features: int) -> int:
    """Channels a client's contraction reads of the merged ``g``: a whole
    lane tile where the client's ``features`` lie inside one, its own
    channels otherwise."""
    whole = _LANES % features == 0 and clients * features >= _LANES
    return _LANES if whole else features


def _dw_merged(xs, g, at: _Window):
    """Each client's dW ``[C, k, k, k, 1, F]`` from the merged ``g [N, od,
    oh, ow, C * F]``, one client after another."""
    clients = xs.shape[0]
    features = g.shape[-1] // clients
    gp = _rows(g)
    width = _window(clients, features)

    def one(t):
        c, x = t
        first = jnp.minimum(c * features // width * width,
                            clients * features - width)
        dw = _contract(x, lax.dynamic_slice_in_dim(gp, first, width, 3), at)
        return lax.dynamic_slice_in_dim(dw, c * features - first, features,
                                        3)

    dw = lax.map(one, (jnp.arange(clients), xs))
    return dw[:, :, :, :, None].astype(xs.dtype)


@functools.lru_cache(maxsize=None)
def _stem_block(stage: _Stage):
    """``stem_block`` for one static ``stage``: a ``custom_vjp`` whose
    forward and backward are ``custom_vmap``s. The forward hands the
    backward the two activations it reads; merged, they cross in the
    clients-first shape a batched value has to have, and the backward's
    first act undoes the forward's last: the compiler cancels the pair."""
    block = functools.partial(_block, stage=stage)
    block_vjp = functools.partial(_block_vjp, stage=stage)

    @jax.custom_batching.custom_vmap
    def forward(x, kernel, bias, scale, offset, mean, var):
        return block(x, kernel, bias, scale, offset, mean, var)

    @forward.def_vmap
    def forward_merged(axis_size, in_batched, *args):
        with jax.named_scope(obs_names.SCOPE_STEM_MERGED):
            merged = _merged_args(*_stacked(args, in_batched, axis_size))
            (out, mean, var), (y, a) = block(*merged, groups=axis_size)
            # the split belongs after the pool, at a fraction of the size
            out = lax.optimization_barrier(out)
            outs = jax.tree.map(lambda v: _split(v, axis_size),
                                ((out, mean, var), (y, a)))
            return outs, jax.tree.map(lambda _: True, outs)

    @jax.custom_batching.custom_vmap
    def backward(res, cts):
        (x, kernel, bias, scale, offset, _, _), (y, a), mean, var = res
        g, dvectors = block_vjp(y, a, scale, offset, mean, var, cts)
        # XLA's own kernel gradient, with the norm's backward fused in
        conv = functools.partial(_conv_bias, at=stage.conv)
        return (*jax.vjp(conv, x, kernel, bias)[1](g), *dvectors)

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
                                          stage.conv, axis_size), x)[1](g)[0]
            dkernel = _dw_merged(args[0], g, stage.conv)
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
               stride: int, pad: int, pool, norm_dtype=None):
    """A 3D CNN's first stage, ``-> (pooled activation, batch mean, batch
    var)``: ``conv3d(x, kernel, stride, pad) + bias`` for ``x [N, D, H, W,
    1]`` in the compute dtype and ``kernel [k, k, k, 1, F]`` (``bias``
    None: a convolution without one), batch norm (float32 statistics over
    ``(N, D, H, W)`` when ``train``, else the running ``mean`` / ``var``,
    which are then returned as they came) whose output, and with it relu,
    the pool and their cotangents, is ``norm_dtype`` (None: ``x``'s),
    relu, and a max pool: ``pool`` is its ``(window, stride, pad)``, or
    one integer for a VALID pool whose stride is its window. The two
    geometries the models have: AlexNet3D's k5 / stride 2 / pad 0 / bias /
    norm in the compute dtype / pool 3, and ResNet3D's k3 / stride 2 / pad
    3 / no bias / float32 norm / pool ``(3, 2, 1)``; any other is as
    right. Under a client-axis ``vmap`` the stage computes in
    the grouped convolution's layout (module docstring): which form runs
    follows the client axis, what it computes follows these arguments."""
    if bias is None:
        # a constant the compiler folds; its cotangent is never read
        bias = jnp.zeros(kernel.shape[-1:], kernel.dtype)
    pool = _Window(*pool) if isinstance(pool, tuple) else _Window(pool, pool)
    stage = _Stage(bool(train), _Window(kernel.shape[0], stride, pad), pool,
                   jnp.dtype(x.dtype if norm_dtype is None else norm_dtype))
    return _stem_block(stage)(x, kernel, bias, scale, offset, mean, var)
