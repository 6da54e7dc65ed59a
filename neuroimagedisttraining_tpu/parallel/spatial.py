"""Spatial (voxel) sharding: the context-parallelism analog for volumes.

The reference has no sequence models, so ring attention / sequence
parallelism has no direct counterpart (SURVEY §5.7); its scaling axes are
clients and volume size. This module supplies the volume-size axis: a 3D
convolution whose DEPTH dimension is sharded across a mesh axis, with halo
exchange over ICI (`lax.ppermute` inside `shard_map`) — structurally the
same neighbor-exchange pattern ring attention uses for KV blocks, applied
to conv receptive fields. With it, a volume too large for one chip's HBM
(or a future higher-resolution cohort) can be partitioned D-wise across
the mesh while every shard computes only its local rows.

Scope: stride-1 'SAME' convolutions (the shape-preserving f2/f3/f4 stages
of AlexNet3D). Strided stems and pools mix shard boundaries with stride
phase and are left to XLA's own SPMD partitioner when whole-model spatial
sharding is wanted; this module is the hand-rolled building block + parity
proof (tests/test_spatial.py: matches the unsharded conv to float32
accumulation tolerance (1e-5) on an 8-device CPU mesh).
"""

from __future__ import annotations

import jax
import jax.numpy as jnp
from jax import lax, shard_map
from jax.sharding import Mesh, PartitionSpec as P

SPACE_AXIS = "space"


def make_space_mesh(num_devices: int | None = None) -> Mesh:
    from neuroimagedisttraining_tpu.parallel.mesh import make_mesh

    return make_mesh(num_devices=num_devices, axis_name=SPACE_AXIS)


def _halo_exchange(x: jax.Array, halo: int, axis_name: str) -> jax.Array:
    """Concatenate each shard's D-block with ``halo`` rows from both
    neighbors (zeros at the global volume edges).

    x: [B, D_local, H, W, C] (one shard's rows). Ring ppermutes move the
    boundary rows over ICI; the first/last shards mask their missing
    neighbor with zero padding — exactly 'SAME' conv semantics.
    """
    if halo == 0:  # 1-wide depth kernel: nothing to exchange (x[:, -0:]
        return x   # would select the WHOLE block, doubling the depth)
    n = lax.axis_size(axis_name)
    idx = lax.axis_index(axis_name)
    # receive the LAST `halo` rows of the left neighbor (shift right)
    from_left = lax.ppermute(x[:, -halo:], axis_name,
                             perm=[(i, (i + 1) % n) for i in range(n)])
    # receive the FIRST `halo` rows of the right neighbor (shift left)
    from_right = lax.ppermute(x[:, :halo], axis_name,
                              perm=[(i, (i - 1) % n) for i in range(n)])
    from_left = jnp.where(idx == 0, jnp.zeros_like(from_left), from_left)
    from_right = jnp.where(idx == n - 1, jnp.zeros_like(from_right),
                           from_right)
    return jnp.concatenate([from_left, x, from_right], axis=1)


def spatial_sharded_conv3d(x: jax.Array, kernel: jax.Array, mesh: Mesh,
                           bias: jax.Array | None = None) -> jax.Array:
    """Stride-1 'SAME' Conv3D with the depth axis sharded over ``mesh``.

    x: [B, D, H, W, Cin] with D divisible by the mesh size; kernel:
    [kd, kh, kw, Cin, Cout] with odd kd. Returns [B, D, H, W, Cout],
    matching the unsharded lax conv to f32 accumulation tolerance.
    """
    kd, kh, kw = kernel.shape[:3]
    assert kd % 2 == 1 and kh % 2 == 1 and kw % 2 == 1, (
        "all kernel dims must be odd for SAME semantics")
    halo = kd // 2
    n = mesh.devices.size
    assert x.shape[1] % n == 0, (
        f"depth {x.shape[1]} not divisible by mesh size {n}")
    assert x.shape[1] // n >= halo, (
        "each shard must hold at least `halo` rows")

    def block(xb, kb, bb):
        xx = _halo_exchange(xb, halo, SPACE_AXIS)
        out = lax.conv_general_dilated(
            xx, kb, window_strides=(1, 1, 1),
            padding=[(0, 0), (kh // 2, kh // 2), (kw // 2, kw // 2)],
            dimension_numbers=("NDHWC", "DHWIO", "NDHWC"))
        if bb is not None:
            out = out + bb
        return out

    spec_x = P(None, SPACE_AXIS)            # shard D, replicate the rest
    spec_k = P()
    fn = shard_map(block, mesh=mesh,
                   in_specs=(spec_x, spec_k, spec_k),
                   out_specs=spec_x)
    return fn(x, kernel, bias)
