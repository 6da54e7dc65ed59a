"""3D neuroimaging CNNs (the models that matter for ABCD).

Layer-for-layer parity with the reference's torch definitions
(fedml_api/model/cv/salient_models.py:142-191 AlexNet3D_Dropout,
194-246 AlexNet3D_Deeper_Dropout, 248-297 AlexNet3D_Dropout_Regression,
84-139 ResNet_l3, 13-81 BasicBlock/Bottleneck), re-designed for TPU:

- **NDHWC layout** (channels-last) so XLA tiles Conv3D onto the MXU.
- ``dtype`` controls compute precision (bfloat16 on TPU); params stay f32.
- The flatten→Linear boundary is shape-inferred rather than hard-coded
  (the reference hard-codes 256 / 512 / 9216 input features, which silently
  assumes the 121x145x121 ABCD volume; salient_models.py:99,171,227).

Pooling uses VALID windows with floor semantics, matching torch's default
floor_mode MaxPool3d/AvgPool3d.
"""

from __future__ import annotations

import os
from typing import Any, Sequence

import flax.linen as nn
import jax
import jax.numpy as jnp

from neuroimagedisttraining_tpu.obs import names as obs_names
from neuroimagedisttraining_tpu.ops.pooling import max_pool_3d_nonoverlap
from neuroimagedisttraining_tpu.ops.stemconv import stem_block, stem_conv3d

Dtype = Any

# Device scopes (obs/names.py): flax names its own modules on every op
# (f1/conv, layer1_0/bn1, fc1); the pools, the flatten and the head's
# dropout sit outside any module, and the first stage is named "stem" in
# both families so that one metric reads it. Scopes are metadata only:
# no module is renamed and the parameter tree is untouched.
_scope = jax.named_scope


def _pool(x, kind: str, k: int, s: int):
    if kind == "max":
        if s == k and os.environ.get("NIDT_FAST_POOL") == "1":
            # opt-in scatter-free backward for the reference's
            # non-overlapping pools: ~4% faster step but carries extra
            # residual memory — see ops/pooling.py for the measured
            # trade-off and why it is not the default
            return max_pool_3d_nonoverlap(x, k)
        return nn.max_pool(x, (k,) * 3, strides=(s,) * 3)
    return nn.avg_pool(x, (k,) * 3, strides=(s,) * 3)


class _StemConv(nn.Module):
    """``nn.Conv(features, (window,) * 3, use_bias=use_bias)``'s parameter
    tree on a single-channel input (kernel ``[window, window, window, 1,
    features]``, a bias or none; same initializers), for a convolution
    that ``ops.stemconv`` computes: called, the k5 / stride-2 / VALID one
    through ``stem_conv3d``; ``_stem_stage`` reads the parameters and
    hands them to ``stem_block`` with the geometry."""

    features: int
    window: int = 5
    use_bias: bool = True
    dtype: Dtype = jnp.float32

    def setup(self):
        self.kernel = self.param("kernel", nn.initializers.lecun_normal(),
                                 (self.window,) * 3 + (1, self.features),
                                 jnp.float32)
        self.bias = self.param("bias", nn.initializers.zeros,
                               (self.features,),
                               jnp.float32) if self.use_bias else None

    def __call__(self, x):
        y = stem_conv3d(x.astype(self.dtype), self.kernel.astype(self.dtype))
        return y + self.bias.astype(self.dtype)


class _StemNorm(nn.Module):
    """``nn.BatchNorm``'s "bn" tree for a block that ``ops.stemconv.
    stem_block`` computes: ``scale`` and ``bias`` parameters,
    ``batch_stats`` ``mean`` and ``var``, the same initialisers and the
    same running update."""

    features: int
    momentum: float = 0.9

    def setup(self):
        shape = (self.features,)
        self.mean = self.variable("batch_stats", "mean", jnp.zeros, shape,
                                  jnp.float32)
        self.var = self.variable("batch_stats", "var", jnp.ones, shape,
                                 jnp.float32)
        self.scale = self.param("scale", nn.initializers.ones, shape,
                                jnp.float32)
        self.bias = self.param("bias", nn.initializers.zeros, shape,
                               jnp.float32)

    def update(self, mean, var):
        if not self.is_initializing():
            for running, batch in ((self.mean, mean), (self.var, var)):
                running.value = (self.momentum * running.value
                                 + (1 - self.momentum) * batch)


def _stem_stage(x, conv: _StemConv, bn: _StemNorm, train: bool, *,
               stride: int, pad: int, pool, norm_dtype: Dtype):
    """A first stage (convolution of the one-channel volume, batch norm,
    relu, the max pool that closes it) as one function, which keeps the
    clients' channels side by side under a client-axis ``vmap``
    (``ops/stemconv.py`` ``stem_block``). The geometry is the calling
    model's own fields, handed on: ``conv``'s window and bias, ``stride``,
    ``pad``, ``pool`` (``stem_block``'s), the norm's output dtype. ``conv``
    and ``bn`` hold the trees ``nn.Conv`` and ``nn.BatchNorm`` would
    declare under their names."""
    if conv.is_mutable_collection("intermediates"):
        # ops/flops.py counts a convolution from its module's captured
        # output, which this route never materialises
        conv.sow("intermediates", "__call__", jnp.zeros(
            (x.shape[0],
             *((e + 2 * pad - conv.window) // stride + 1
               for e in x.shape[1:4]), conv.features), conv.dtype))
    x, mean, var = stem_block(
        x.astype(conv.dtype), conv.kernel, conv.bias, bn.scale, bn.bias,
        bn.mean.value, bn.var.value, train=train, stride=stride, pad=pad,
        pool=pool, norm_dtype=norm_dtype)
    if train:
        bn.update(mean, var)
    return x


class ConvBNReLU3D(nn.Module):
    """Conv3d + BatchNorm3d + ReLU block (salient_models.py:147-149 pattern).

    BatchNorm runs in the block's compute dtype (bf16 on TPU) with f32
    params/stats — keeping the huge early-stage activations half-width so
    the pool backward (select-and-scatter) doesn't blow HBM."""
    features: int
    kernel: int = 3
    stride: int = 1
    pad: int = 0
    dtype: Dtype = jnp.float32
    norm: str = "batch"  # "batch" | "group" (3D GroupNorm option — parity
    # with the functional GroupNorm3d, group_normalization.py:7-118)
    pool: int = 0  # a max pool of this window and stride closes the block

    @nn.compact
    def __call__(self, x, train: bool = False):
        if x.shape[-1] == 1 and self.norm == "batch" and self.pool:
            # what the block can see of a first stage: one input channel,
            # batch norm, a pool that closes it. Whatever its geometry,
            # it goes through _stem_stage; same "conv" and "bn" trees
            return _stem_stage(
                x, _StemConv(self.features, self.kernel, dtype=self.dtype,
                             name="conv"),
                _StemNorm(self.features, name="bn"), train,
                stride=self.stride, pad=self.pad, pool=self.pool,
                norm_dtype=self.dtype)
        if (self.kernel, self.stride, self.pad, x.shape[-1]) == (5, 2, 0, 1):
            # the C_in = 1 stride-2 stem: XLA's own lowering leaves the MXU
            # nearly empty (ops/stemconv.py); same "conv" parameters
            x = _StemConv(self.features, dtype=self.dtype, name="conv")(x)
        else:
            x = nn.Conv(self.features, (self.kernel,) * 3,
                        strides=(self.stride,) * 3,
                        padding=[(self.pad, self.pad)] * 3, dtype=self.dtype,
                        name="conv")(x)
        if self.norm == "group":
            x = nn.GroupNorm(num_groups=min(32, self.features),
                             dtype=self.dtype, name="gn")(x)
        else:
            x = nn.BatchNorm(use_running_average=not train, momentum=0.9,
                             epsilon=1e-5, dtype=self.dtype, name="bn")(x)
        x = nn.relu(x)
        if self.pool:
            with _scope(obs_names.SCOPE_POOL0):
                x = _pool(x, "max", self.pool, self.pool)
        return x


# Rematerialized block: the backward pass recomputes conv/bn activations
# instead of keeping all five feature stages live (HBM is the bottleneck for
# 121^3 volumes; trades ~1.3x FLOPs for ~4x activation memory).
RematConvBNReLU3D = nn.remat(ConvBNReLU3D, static_argnums=(2,))


class AlexNet3D_Dropout(nn.Module):
    """5-conv 3D AlexNet with dropout head; the ABCD flagship (``--model 3DCNN``,
    num_classes=1 + BCE). Parity: salient_models.py:142-191."""
    input_rank = 5  # input ndim incl. batch+channel (unannotated: not a flax field)
    num_classes: int = 2
    dtype: Dtype = jnp.float32
    # Rematerialization policy (HBM vs FLOPs trade; measured on TPU v5e,
    # PROFILE.md): False = none — fastest (+21% over remat) but only fits
    # ~64 samples in flight per chip (e.g. b16 x 4 vmapped clients);
    # "stem" = f0+f1 only (the large activations; costs the same as True
    # since f0's recompute IS the remat tax, but needs less HBM); True =
    # all stages. The harness picks automatically from the federation
    # shape (--remat auto, __main__.build_experiment).
    remat: bool | str = "stem"
    norm: str = "batch"  # "group" => GN3D variant (no running stats)

    def _blk(self, stage: int):
        if self.remat is True or (self.remat == "stem" and stage <= 1):
            return RematConvBNReLU3D
        return ConvBNReLU3D

    @nn.compact
    def __call__(self, x, train: bool = False):
        with _scope(obs_names.SCOPE_BATCH_PREP):
            x = x.astype(self.dtype)
        with _scope(obs_names.SCOPE_STEM):
            x = self._blk(0)(64, kernel=5, stride=2, pad=0, dtype=self.dtype,
                             norm=self.norm, pool=3, name="f0")(x, train)
        x = self._blk(1)(128, kernel=3, stride=1, pad=0, dtype=self.dtype,
                         norm=self.norm, name="f1")(x, train)
        with _scope(obs_names.SCOPE_POOL1):
            x = _pool(x, "max", 3, 3)
        x = self._blk(2)(192, kernel=3, pad=1, dtype=self.dtype,
                         norm=self.norm, name="f2")(x, train)
        x = self._blk(3)(192, kernel=3, pad=1, dtype=self.dtype,
                         norm=self.norm, name="f3")(x, train)
        x = self._blk(4)(128, kernel=3, pad=1, dtype=self.dtype,
                         norm=self.norm, name="f4")(x, train)
        with _scope(obs_names.SCOPE_POOL2):
            x = _pool(x, "max", 3, 3)
        with _scope(obs_names.SCOPE_HEAD):
            x = x.reshape((x.shape[0], -1))
            x = nn.Dropout(0.5, deterministic=not train)(x)
            x = nn.relu(nn.Dense(64, dtype=self.dtype, name="fc1")(x))
            x = nn.Dropout(0.5, deterministic=not train)(x)
            x = nn.Dense(self.num_classes, dtype=self.dtype, name="fc2")(x)
            return x.astype(jnp.float32)


class AlexNet3D_Deeper_Dropout(nn.Module):
    """6-conv, 512-dim-flatten variant; returns ``[x, x]`` like the reference
    (salient_models.py:194-246)."""
    input_rank = 5  # input ndim incl. batch+channel (unannotated: not a flax field)
    num_classes: int = 2
    dtype: Dtype = jnp.float32
    remat: bool | str = "stem"  # same policy semantics as AlexNet3D_Dropout

    def _blk(self, stage: int):
        if self.remat is True or (self.remat == "stem" and stage <= 1):
            return RematConvBNReLU3D
        return ConvBNReLU3D

    @nn.compact
    def __call__(self, x, train: bool = False):
        with _scope(obs_names.SCOPE_BATCH_PREP):
            x = x.astype(self.dtype)
        with _scope(obs_names.SCOPE_STEM):
            x = self._blk(0)(64, kernel=5, stride=2, pad=0, dtype=self.dtype,
                             pool=3, name="f0")(x, train)
        x = self._blk(1)(128, kernel=3, stride=1, pad=0, dtype=self.dtype, name="f1")(x, train)
        with _scope(obs_names.SCOPE_POOL1):
            x = _pool(x, "max", 3, 3)
        x = self._blk(2)(192, kernel=3, pad=1, dtype=self.dtype, name="f2")(x, train)
        x = self._blk(3)(384, kernel=3, pad=1, dtype=self.dtype, name="f3")(x, train)
        x = self._blk(4)(256, kernel=3, pad=1, dtype=self.dtype, name="f4")(x, train)
        x = self._blk(5)(256, kernel=3, pad=1, dtype=self.dtype, name="f5")(x, train)
        with _scope(obs_names.SCOPE_POOL2):
            x = _pool(x, "max", 3, 3)
        with _scope(obs_names.SCOPE_HEAD):
            x = x.reshape((x.shape[0], -1))
            x = nn.Dropout(0.5, deterministic=not train)(x)
            x = nn.relu(nn.Dense(64, dtype=self.dtype, name="fc1")(x))
            x = nn.Dropout(0.5, deterministic=not train)(x)
            x = nn.Dense(self.num_classes, dtype=self.dtype, name="fc2")(x)
            x = x.astype(jnp.float32)
        return x, x


class AlexNet3D_Dropout_Regression(nn.Module):
    """Regression head; returns ``(pred.squeeze(), feature_map)``
    (salient_models.py:248-297)."""
    input_rank = 5  # input ndim incl. batch+channel (unannotated: not a flax field)
    num_classes: int = 1
    dtype: Dtype = jnp.float32
    remat: bool | str = "stem"  # same policy semantics as AlexNet3D_Dropout

    def _blk(self, stage: int):
        if self.remat is True or (self.remat == "stem" and stage <= 1):
            return RematConvBNReLU3D
        return ConvBNReLU3D

    @nn.compact
    def __call__(self, x, train: bool = False):
        with _scope(obs_names.SCOPE_BATCH_PREP):
            x = x.astype(self.dtype)
        with _scope(obs_names.SCOPE_STEM):
            x = self._blk(0)(64, kernel=5, stride=2, pad=0, dtype=self.dtype,
                             pool=3, name="f0")(x, train)
        x = self._blk(1)(128, kernel=3, stride=1, pad=0, dtype=self.dtype, name="f1")(x, train)
        with _scope(obs_names.SCOPE_POOL1):
            x = _pool(x, "max", 3, 3)
        x = self._blk(2)(192, kernel=3, pad=1, dtype=self.dtype, name="f2")(x, train)
        x = self._blk(3)(192, kernel=3, pad=1, dtype=self.dtype, name="f3")(x, train)
        x = self._blk(4)(128, kernel=3, pad=1, dtype=self.dtype, name="f4")(x, train)
        with _scope(obs_names.SCOPE_POOL2):
            xp = _pool(x, "max", 3, 3)
        with _scope(obs_names.SCOPE_HEAD):
            x = xp.reshape((xp.shape[0], -1))
            x = nn.Dropout(0.5, deterministic=not train)(x)
            x = nn.relu(nn.Dense(64, dtype=self.dtype, name="fc1")(x))
            x = nn.Dropout(0.5, deterministic=not train)(x)
            x = nn.Dense(self.num_classes, dtype=self.dtype, name="fc2")(x)
            return (jnp.squeeze(x.astype(jnp.float32)),
                    xp.astype(jnp.float32))


class Tiny3DCNN(nn.Module):
    """Small 2-conv 3D CNN for CI/tests on small synthetic volumes — the
    structural miniature of AlexNet3D_Dropout (conv-BN-relu-pool x2 + MLP
    head). Not in the reference zoo; serves its ``--ci`` fast-path role
    (sailentgrads_api.py:260-265) with real Conv3D+BN+Dropout semantics."""
    input_rank = 5  # input ndim incl. batch+channel (unannotated: not a flax field)
    num_classes: int = 1
    width: int = 8
    dtype: Dtype = jnp.float32

    @nn.compact
    def __call__(self, x, train: bool = False):
        with _scope(obs_names.SCOPE_BATCH_PREP):
            x = x.astype(self.dtype)
        with _scope(obs_names.SCOPE_STEM):
            x = ConvBNReLU3D(self.width, kernel=3, dtype=self.dtype, name="f0")(x, train)
            with _scope(obs_names.SCOPE_POOL0):
                x = _pool(x, "max", 2, 2)
        x = ConvBNReLU3D(self.width * 2, kernel=3, dtype=self.dtype, name="f1")(x, train)
        with _scope(obs_names.SCOPE_POOL1):
            x = _pool(x, "max", 2, 2)
        with _scope(obs_names.SCOPE_HEAD):
            x = x.reshape((x.shape[0], -1))
            x = nn.Dropout(0.5, deterministic=not train)(x)
            x = nn.relu(nn.Dense(32, dtype=self.dtype, name="fc1")(x))
            x = nn.Dense(self.num_classes, dtype=self.dtype, name="fc2")(x)
            return x.astype(jnp.float32)


class BasicBlock3D(nn.Module):
    """3D residual basic block (salient_models.py:13-42)."""
    planes: int
    stride: int = 1
    downsample: bool = False
    dtype: Dtype = jnp.float32
    expansion: int = 1

    @nn.compact
    def __call__(self, x, train: bool = False):
        residual = x
        out = nn.Conv(self.planes, (3,) * 3, strides=(self.stride,) * 3,
                      padding=[(1, 1)] * 3, use_bias=False, dtype=self.dtype,
                      name="conv1")(x)
        out = nn.BatchNorm(use_running_average=not train, momentum=0.9,
                           dtype=jnp.float32, name="bn1")(out)
        out = nn.relu(out)
        out = nn.Conv(self.planes, (3,) * 3, padding=[(1, 1)] * 3,
                      use_bias=False, dtype=self.dtype, name="conv2")(out)
        out = nn.BatchNorm(use_running_average=not train, momentum=0.9,
                           dtype=jnp.float32, name="bn2")(out)
        if self.downsample:
            residual = nn.Conv(self.planes * self.expansion, (1,) * 3,
                               strides=(self.stride,) * 3, use_bias=False,
                               dtype=self.dtype, name="ds_conv")(x)
            residual = nn.BatchNorm(use_running_average=not train, momentum=0.9,
                                    dtype=jnp.float32, name="ds_bn")(residual)
        return nn.relu(out + residual)


class Bottleneck3D(nn.Module):
    """3D bottleneck block, expansion 4 (salient_models.py:45-81)."""
    planes: int
    stride: int = 1
    downsample: bool = False
    dtype: Dtype = jnp.float32
    expansion: int = 4

    @nn.compact
    def __call__(self, x, train: bool = False):
        residual = x

        def bn(name):
            return nn.BatchNorm(use_running_average=not train, momentum=0.9,
                                dtype=jnp.float32, name=name)

        out = nn.relu(bn("bn1")(nn.Conv(self.planes, (1,) * 3, use_bias=False,
                                        dtype=self.dtype, name="conv1")(x)))
        out = nn.relu(bn("bn2")(nn.Conv(self.planes, (3,) * 3,
                                        strides=(self.stride,) * 3,
                                        padding=[(1, 1)] * 3, use_bias=False,
                                        dtype=self.dtype, name="conv2")(out)))
        out = bn("bn3")(nn.Conv(self.planes * 4, (1,) * 3, use_bias=False,
                                dtype=self.dtype, name="conv3")(out))
        if self.downsample:
            residual = nn.Conv(self.planes * self.expansion, (1,) * 3,
                               strides=(self.stride,) * 3, use_bias=False,
                               dtype=self.dtype, name="ds_conv")(x)
            residual = nn.BatchNorm(use_running_average=not train, momentum=0.9,
                                    dtype=jnp.float32, name="ds_bn")(residual)
        return nn.relu(out + residual)


class ResNet3D_l3(nn.Module):
    """3-stage 3D ResNet; returns ``(logits, penultimate)``
    (salient_models.py:84-139). ``block`` is "basic" or "bottleneck"."""
    input_rank = 5  # input ndim incl. batch+channel (unannotated: not a flax field)
    layers: Sequence[int] = (1, 1, 1)
    num_classes: int = 2
    block: str = "basic"
    dtype: Dtype = jnp.float32

    @nn.compact
    def __call__(self, x, train: bool = False):
        with _scope(obs_names.SCOPE_BATCH_PREP):
            x = x.astype(self.dtype)
        blk = BasicBlock3D if self.block == "basic" else Bottleneck3D
        expansion = 1 if self.block == "basic" else 4
        with _scope(obs_names.SCOPE_STEM):
            # nn.Conv(64, k3, stride 2, pad 3, no bias) "conv1", a float32
            # nn.BatchNorm "bn1", relu, max pool k3 s2 pad 1 (pool0)
            x = _stem_stage(
                x, _StemConv(64, 3, use_bias=False, dtype=self.dtype,
                             name="conv1"),
                _StemNorm(64, name="bn1"), train, stride=2, pad=3,
                pool=(3, 2, 1), norm_dtype=jnp.float32)
        inplanes = 64
        for stage, (planes, blocks) in enumerate(zip((64, 128, 256), self.layers)):
            stride = 1 if stage == 0 else 2
            for i in range(blocks):
                s = stride if i == 0 else 1
                ds = i == 0 and (s != 1 or inplanes != planes * expansion)
                x = blk(planes, stride=s, downsample=ds, dtype=self.dtype,
                        name=f"layer{stage + 1}_{i}")(x, train)
                inplanes = planes * expansion
        with _scope(obs_names.SCOPE_POOL1):
            x = _pool(x, "avg", 3, 3)
        with _scope(obs_names.SCOPE_HEAD):
            x = x.reshape((x.shape[0], -1))
            x1 = nn.Dense(512, dtype=self.dtype, name="fc")(x)
            x = nn.Dense(self.num_classes, dtype=self.dtype, name="fc2")(x1)
            return x.astype(jnp.float32), x1.astype(jnp.float32)
