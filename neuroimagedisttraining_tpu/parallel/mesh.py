"""Device mesh + sharding layout for federated simulation.

The reference simulates clients with a sequential Python loop on one GPU
(sailentgrads_api.py:126-138). Here the client axis IS a mesh axis: stacked
client pytrees (``[C, ...]``) are sharded over ``Mesh(axis="clients")`` so
each TPU core trains ``C/ndev`` clients in parallel inside one jitted round
program, and cross-client reductions (FedAvg, score means, gossip) lower to
XLA collectives over ICI (SURVEY.md §2.10, BASELINE.json north star).

On multi-host slices the same mesh spans all devices; host-local data feeding
uses ``jax.make_array_from_process_local_data`` (data layer) and collectives
ride ICI/DCN as laid out by XLA.
"""

from __future__ import annotations

from typing import Any

import jax
import numpy as np
from jax.sharding import Mesh, NamedSharding, PartitionSpec as P

PyTree = Any

CLIENT_AXIS = "clients"
SILO_AXIS = "silos"  # outer axis of a two-level (host, core) mesh


def provision_virtual_devices(n: int) -> bool:
    """Provision ``n`` virtual CPU devices for mesh simulation (SURVEY.md §4:
    fake-device meshes stand in for multi-node without a cluster).

    Pins ``jax_platforms=cpu`` and ``jax_num_cpu_devices=n`` through the
    config API, which only takes effect before the first backend touch.
    Returns False (and changes nothing) when a backend already exists —
    the caller must then live with whatever devices it has."""
    try:
        jax.config.update("jax_num_cpu_devices", n)
    except RuntimeError:  # jax's own "backends are initialized" refusal
        return False
    jax.config.update("jax_platforms", "cpu")
    return True


def make_mesh(num_devices: int | None = None, devices=None,
              axis_name: str = CLIENT_AXIS,
              shape: tuple[int, ...] = ()) -> Mesh:
    """1-D mesh over all (or the first N) visible devices; a 2-entry
    ``shape`` (e.g. ``--mesh_shape 2 4``) builds the two-level
    ``(silos, clients)`` mesh instead — silo reductions ride ICI, cross-
    silo traffic rides DCN (parallel/hierarchical.py)."""
    if devices is None:
        devices = jax.devices()
    if shape and (len(shape) > 2 or any(s < 1 for s in shape)):
        raise ValueError(
            f"--mesh_shape must be 1 or 2 positive integers, got {shape}")
    if len(shape) == 2:
        need = shape[0] * shape[1]
        if len(devices) < need:
            raise ValueError(
                f"--mesh_shape {shape} needs {need} devices, "
                f"have {len(devices)}")
        grid = np.asarray(devices[:need]).reshape(shape)
        return Mesh(grid, (SILO_AXIS, CLIENT_AXIS))
    if shape:
        num_devices = shape[0]
    if num_devices is not None:
        if len(devices) < num_devices:
            raise ValueError(
                f"mesh needs {num_devices} devices, have {len(devices)}")
        devices = devices[:num_devices]
    return Mesh(np.asarray(devices), (axis_name,))


def client_sharding(mesh: Mesh) -> NamedSharding:
    """Leading (client) axis sharded over EVERY mesh axis — on a two-level
    mesh clients split across silos x cores — rest replicated."""
    return NamedSharding(mesh, P(tuple(mesh.axis_names)))


def replicated_sharding(mesh: Mesh) -> NamedSharding:
    return NamedSharding(mesh, P())


def pad_to_multiple(n: int, d: int) -> int:
    return ((n + d - 1) // d) * d


def shard_federation(tree: PyTree, mesh: Mesh) -> PyTree:
    """Device-put a stacked client pytree with its leading axis sharded over
    the mesh's client axis. Leading dim must be a multiple of the mesh size
    (pad clients with zero-weight shards first if needed)."""
    sh = client_sharding(mesh)
    return jax.tree.map(lambda x: jax.device_put(x, sh), tree)
