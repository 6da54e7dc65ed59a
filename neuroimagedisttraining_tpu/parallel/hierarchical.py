"""Two-level (silo -> global) federated aggregation over a 2-D mesh.

The reference's cross-silo scale-out path re-partitions the pooled cohort
into many equal client shards (``load_partition_data_abcd_rescale``,
ABCD/data_loader.py:216-315; BASELINE.json's 256-client cross-silo
config). On a TPU pod that federation has a natural two-level shape:

    mesh ("silos", "clients"): silo = one host (DCN between silos),
    clients = that host's cores (ICI within a silo).

Aggregation then decomposes into a silo-local weighted reduction (rides
ICI) followed by a cross-silo reduction of ONE pytree per silo (rides
DCN) — the bandwidth-correct layout: the narrow inter-host links carry
`num_silos` model-sized messages instead of `num_clients`.

The decomposition is also a semantic capability the flat mean cannot
express: ``silo_then_global_mean(..., norm_bound=...)`` applies the
reference's Byzantine norm-diff clipping (robust_aggregation.py:38-49)
to each SILO AGGREGATE before the global mean — the cross-silo trust
model (silos are administrative domains; a hostile silo is bounded as a
unit no matter how many clients it claims to contain).

With no clipping the result is bit-comparable to the flat
``tree_weighted_mean`` over all clients (same sums, same division),
pinned by tests/test_sharding.py on a 2x4 virtual mesh.
"""

from __future__ import annotations

from typing import Any

import jax
import jax.numpy as jnp
from jax import shard_map
from jax.sharding import Mesh, PartitionSpec as P

from neuroimagedisttraining_tpu.core.robust import norm_diff_clip
from neuroimagedisttraining_tpu.parallel.mesh import CLIENT_AXIS, SILO_AXIS

PyTree = Any


def make_two_level_mesh(num_silos: int, clients_per_silo: int,
                        devices=None) -> Mesh:
    """2-D mesh [silos, clients]; on a real pod pass a devices array whose
    first axis groups devices by host so the silo axis maps onto DCN."""
    from neuroimagedisttraining_tpu.parallel.mesh import make_mesh

    return make_mesh(devices=devices,
                     shape=(num_silos, clients_per_silo))


def is_two_level(mesh: Mesh | None) -> bool:
    return mesh is not None and SILO_AXIS in mesh.axis_names


def silo_then_global_mean(stacked: PyTree, weights: jax.Array, mesh: Mesh,
                          global_params: PyTree | None = None,
                          norm_bound: float | None = None) -> PyTree:
    """Weighted mean of client-stacked ``stacked`` ([C, ...], C sharded over
    both mesh axes) computed silo-locally first, then across silos.

    ``norm_bound`` (with ``global_params``) clips each silo's aggregate to
    within ``norm_bound`` of the previous global params before the
    cross-silo mean — norm-diff clipping at silo granularity.
    """
    spec = P((SILO_AXIS, CLIENT_AXIS))

    def agg(stacked, weights, *maybe_global):
        # silo-local weighted sum over this device's clients + ICI psum
        wsum = jax.tree.map(
            lambda x: jax.lax.psum(
                jnp.tensordot(weights, x.astype(jnp.float32), axes=(0, 0)),
                CLIENT_AXIS),
            stacked)
        wtot = jax.lax.psum(jnp.sum(weights.astype(jnp.float32)),
                            CLIENT_AXIS)
        if norm_bound is not None:
            silo_mean = jax.tree.map(lambda s: s / jnp.maximum(wtot, 1e-9),
                                     wsum)
            clipped = norm_diff_clip(silo_mean, maybe_global[0], norm_bound)
            wsum = jax.tree.map(lambda c: c * wtot, clipped)
        # cross-silo (DCN) reduction of one aggregate per silo; cast each
        # leaf back to its input dtype so the two-level path matches the
        # flat tree_weighted_mean for non-f32 leaves
        gsum = jax.tree.map(lambda s: jax.lax.psum(s, SILO_AXIS), wsum)
        gtot = jax.lax.psum(wtot, SILO_AXIS)
        return jax.tree.map(
            lambda s, x: (s / jnp.maximum(gtot, 1e-9)).astype(x.dtype),
            gsum, stacked)

    args = (stacked, weights)
    in_specs = (jax.tree.map(lambda _: spec, stacked), spec)
    if norm_bound is not None:
        assert global_params is not None, "clipping needs global_params"
        args += (global_params,)
        in_specs += (jax.tree.map(lambda _: P(), global_params),)
    out_specs = jax.tree.map(lambda _: P(), stacked)
    return shard_map(agg, mesh=mesh, in_specs=in_specs,
                     out_specs=out_specs)(*args)
