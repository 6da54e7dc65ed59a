"""Sparse gossip consensus: ``lax.ppermute`` rings and routed all-to-all.

The decentralized engines' consensus is ``einsum("cj,j...->c...", M, x)`` —
an all-gather that materializes the full C-stacked model per device and is
the scaling wall at the 100-client north star. Two sparse lowerings
replace it whenever the round's mixing matrix allows:

1. CIRCULANT (``circulant_plan`` / ``gossip_apply``): the ring /
   Watts-Strogatz k-lattice topologies the reference ships
   (fedml_core/distributed/topology/symmetric_topology_manager.py:21-52,
   dpsgd_api.py:116-139 cs="ring") give ``M[c, j] = base[(j - c) mod C]``,
   so the consensus is a handful of weighted client-axis rotations, each a
   ``lax.ppermute`` of a |k|-row slice. Per-device traffic O(k_max *
   model), independent of C. The rotation offsets are part of the compiled
   program — fine, because ring plans are round-invariant.

2. GENERAL SPARSE (``sparse_plan`` / ``gossip_apply_sparse``): the
   reference's DisPFL default and dpsgd ``cs="random"`` draw a NEW
   k-regular random adjacency every round (dispfl_api.py:200,
   dpsgd_api.py:116-139), so any lowering whose communication pattern is
   baked into the program would retrace per round. The TPU-native answer
   is a capped ``lax.all_to_all`` with TRACED routing tables: each device
   sends, per destination, just the (deduplicated) client rows that
   destination's clients actually reference, padded to a static per-pair
   cap ``m``; receivers reassemble their neighbor rows by a local gather.
   The routing tables (send indices, gather indices, weights) are runtime
   OPERANDS, so one compiled program serves every round whose size bucket
   matches — per-device traffic O(D * m * model) with
   ``m ~ B * (k+1) / D`` rows (B = clients per device), vs the einsum's
   O(C * model), and peak memory O(D * m) instead of the gathered
   O(C) stack. ``sparse_plan`` returns None when the pattern is dense
   enough that the einsum is no better (m would equal B).

Plan detection runs on the host per round (cheap: O(C^2) compares /
O(C * k) bucketing); engines fall back to the dense einsum whenever
neither structure applies — behavior is identical either way, only the
lowering differs.
"""

from __future__ import annotations

import dataclasses

import jax
import jax.numpy as jnp
import numpy as np
from jax import shard_map

from neuroimagedisttraining_tpu.parallel.mesh import CLIENT_AXIS

#: plan entry: (signed client-axis offset, mixing weight)
Plan = tuple[tuple[int, float], ...]


def circulant_plan(M: np.ndarray, tol: float = 0.0) -> Plan | None:
    """``((offset, weight), ...)`` when ``M`` is circulant, else None.

    Offsets are signed (shortest direction around the ring) and sorted, so
    equal matrices always produce the same (hashable) plan — engines key
    their jit caches on it."""
    M = np.asarray(M)
    C = M.shape[0]
    if M.ndim != 2 or M.shape[1] != C or C == 0:
        return None
    base = M[0]
    for i in range(1, C):
        if not (np.abs(M[i] - np.roll(base, i)) <= tol).all():
            return None
    plan = []
    for j in np.flatnonzero(base):
        k = int(j) if j <= C // 2 else int(j) - C
        plan.append((k, float(base[j])))
    return tuple(sorted(plan))


def plan_fits_mesh(plan: Plan, mesh, num_clients: int) -> bool:
    """A plan lowers to single-hop ppermutes iff the mesh is the 1-D
    client mesh, the client axis tiles it, and every offset stays within
    one device block."""
    if mesh is None or plan is None:
        return False
    if tuple(mesh.axis_names) != (CLIENT_AXIS,):
        return False
    D = mesh.devices.size
    if D < 2 or num_clients % D != 0:
        return False
    block = num_clients // D
    return all(abs(k) <= block for k, _ in plan)


def _rolled(blk: jax.Array, k: int, D: int) -> jax.Array:
    """This device's rows of the client-axis rotation
    ``rolled[i] = x[(i + k) mod C]``: a |k|-row ppermute from the
    neighboring device plus a local slice-concat."""
    if k == 0:
        return blk
    B = blk.shape[0]
    if k > 0:
        # rows [k:] are local; the tail comes from the NEXT device's head
        recv = jax.lax.ppermute(blk[:k], CLIENT_AXIS,
                                [((d + 1) % D, d) for d in range(D)])
        return jnp.concatenate([blk[k:], recv], axis=0)
    kk = -k
    # rows [:B-kk] are local (shifted); the head comes from the PREVIOUS
    # device's tail
    recv = jax.lax.ppermute(blk[B - kk:], CLIENT_AXIS,
                            [((d - 1) % D, d) for d in range(D)])
    return jnp.concatenate([recv, blk[:B - kk]], axis=0)


def gossip_apply(tree, plan: Plan, mesh):
    """Circulant consensus of a client-stacked pytree via ppermute shifts.

    Equivalent to ``einsum("cj,j...->c...", M, x)`` (float32 accumulate,
    cast back) for the circulant ``M`` that produced ``plan``, but lowers
    to collective-permutes of |k|-row slices instead of an all-to-all."""
    from jax.sharding import PartitionSpec

    if plan is None:
        # None is the "not circulant" sentinel from circulant_plan — the
        # caller should have taken the dense einsum path; silently gossiping
        # nothing here would return an all-zero consensus for a matrix that
        # is NOT all-zero
        raise ValueError(
            "gossip_apply(plan=None): None means 'not circulant, use the "
            "dense einsum path'; only an actual Plan tuple is accepted")
    if not jax.tree.leaves(tree):  # e.g. batch_stats of a GroupNorm model
        return tree
    if plan == ():
        # an all-zero matrix is (trivially) circulant and yields an empty
        # plan; the consensus it defines is identically zero — match the
        # einsum path instead of tripping over an empty accumulation
        return jax.tree.map(jnp.zeros_like, tree)
    D = mesh.devices.size
    specs = jax.tree.map(
        lambda x: PartitionSpec(CLIENT_AXIS, *([None] * (x.ndim - 1))),
        tree)

    def block_fn(blk_tree):
        def one(blk):
            b32 = blk.astype(jnp.float32)
            acc = None
            for k, w in plan:
                term = w * _rolled(b32, k, D)
                acc = term if acc is None else acc + term
            return acc.astype(blk.dtype)

        return jax.tree.map(one, blk_tree)

    return shard_map(block_fn, mesh=mesh, in_specs=(specs,),
                     out_specs=specs)(tree)


def make_plan(M: np.ndarray, mesh, num_clients: int):
    """``(plan, plan_arrays)`` for a round's mixing/adjacency matrix — the
    shared circulant -> sparse -> dense cascade used by the decentralized
    engines: a hashable circulant Plan tuple (ppermute shifts) when the
    matrix is circulant and tiles the mesh, a SparseSpec + traced routing
    arrays (routed all_to_all) for sparse patterns, else ``(None, {})``
    for the dense einsum."""
    plan = circulant_plan(M)
    if plan_fits_mesh(plan, mesh, num_clients):
        return plan, {}
    sp = sparse_plan(M, mesh, num_clients)
    if sp is not None:
        return sp
    return None, {}


# ---------- general sparse (per-round random) topologies ----------


@dataclasses.dataclass(frozen=True)
class SparseSpec:
    """Static (hashable, jit-cache-keying) part of a sparse gossip plan.

    ``m`` is bucketed to quarters of B so a config's rounds share a
    handful of compiled programs even though the random topology (and
    therefore the traced routing tables) changes every round; ``n_max``
    is the per-round max row support, which is constant for a fixed
    (k, activity) config."""
    D: int       # devices on the 1-D client mesh
    B: int       # clients per device (C // D)
    m: int       # padded per-(src, dst) slot count for the all_to_all
    n_max: int   # padded per-client neighbor count for the local gather


def _bucket(n: int, q: int) -> int:
    """Round n up to the next multiple of q (n >= 1)."""
    n = max(n, 1)
    return ((n + q - 1) // q) * q


def sparse_plan(M: np.ndarray, mesh, num_clients: int
                ) -> tuple[SparseSpec, dict[str, np.ndarray]] | None:
    """Routing plan for an arbitrary sparse mixing matrix on the 1-D
    client mesh, or None when the einsum is no worse (pattern dense
    enough that some device pair would exchange its full block).

    Returns ``(spec, arrays)``:
    - ``arrays["send_idx"]`` [D, D, m] int32 — device s's slot for
      destination d holds LOCAL row indices (deduplicated, ascending),
      padded with 0 (padding rows are sent but never gathered).
    - ``arrays["gather_idx"]`` [C, n_max] int32 — per client, positions
      into the receiver's pool = concat(all-to-all result [D*m], local
      block [B]), neighbor terms in ascending global-j order (matching
      the einsum's reduction order), padded with 0.
    - ``arrays["gather_w"]`` [C, n_max] float32 — matching weights,
      padding 0.
    """
    M = np.asarray(M)
    C = M.shape[0]
    if M.ndim != 2 or M.shape[1] != C or C == 0:
        return None
    if mesh is None or tuple(mesh.axis_names) != (CLIENT_AXIS,):
        return None
    D = mesh.devices.size
    if D < 2 or num_clients % D != 0 or C != num_clients:
        return None
    B = C // D

    rows = [np.flatnonzero(M[c]) for c in range(C)]
    n_actual = max((len(r) for r in rows), default=0)
    # send sets: per ordered device pair (s != d), the deduplicated local
    # rows of s referenced by any client of d
    need: list[list[set]] = [[set() for _ in range(D)] for _ in range(D)]
    for c in range(C):
        d = c // B
        for j in rows[c]:
            s = int(j) // B
            if s != d:
                need[s][d].add(int(j) - s * B)
    m_actual = max((len(need[s][d]) for s in range(D) for d in range(D)),
                   default=0)
    # bucket to quarters of B (bounded program count per config); the plan
    # only pays off when the padded per-pair slots stay strictly below a
    # full block — at m == B the all_to_all moves the all-gather volume
    # (that covers B == 1 too: one-client-per-device random gossip has no
    # sparse win, every row is a full block)
    m = _bucket(m_actual, max(1, B // 4))
    if m >= B:
        return None
    n_max = min(max(n_actual, 1), C)

    send_idx = np.zeros((D, D, m), np.int32)
    slot: dict[tuple[int, int, int], int] = {}
    for s in range(D):
        for d in range(D):
            for i, r in enumerate(sorted(need[s][d])):
                send_idx[s, d, i] = r
                slot[(s, d, r)] = i
    gather_idx = np.zeros((C, n_max), np.int32)
    gather_w = np.zeros((C, n_max), np.float32)
    for c in range(C):
        d = c // B
        for i, j in enumerate(rows[c]):  # ascending j == einsum order
            s = int(j) // B
            if s == d:
                gather_idx[c, i] = D * m + (int(j) - d * B)
            else:
                gather_idx[c, i] = s * m + slot[(s, d, int(j) - s * B)]
            gather_w[c, i] = M[c, j]
    spec = SparseSpec(D=D, B=B, m=m, n_max=n_max)
    return spec, {"send_idx": send_idx, "gather_idx": gather_idx,
                  "gather_w": gather_w}


def gossip_apply_sparse(tree, spec: SparseSpec, arrays, mesh):
    """Sparse consensus of a client-stacked pytree via one routed
    ``lax.all_to_all`` + local gathers.

    Equivalent to ``einsum("cj,j...->c...", M, x)`` (float32 accumulate in
    ascending-j order, cast back) for the ``M`` that produced the plan;
    per-device traffic D*m rows instead of the einsum's C-row all-gather.
    ``arrays`` are traced operands — one compiled program per SparseSpec
    bucket, reused across rounds of changing random topologies."""
    from jax.sharding import PartitionSpec

    if not jax.tree.leaves(tree):  # e.g. batch_stats of a GroupNorm model
        return tree
    D, B, m, n_max = spec.D, spec.B, spec.m, spec.n_max
    specs = jax.tree.map(
        lambda x: PartitionSpec(CLIENT_AXIS, *([None] * (x.ndim - 1))),
        tree)
    vec = PartitionSpec(CLIENT_AXIS)

    def block_fn(blk_tree, send_blk, gidx_blk, gw_blk):
        # send_blk [1, D, m]; gidx_blk/gw_blk [B, n_max]
        def one(blk):
            b32 = blk.astype(jnp.float32)
            S = b32[send_blk[0]]                         # [D, m, ...]
            R = jax.lax.all_to_all(S, CLIENT_AXIS, 0, 0, tiled=True)
            pool = jnp.concatenate(
                [R.reshape((D * m,) + b32.shape[1:]), b32], axis=0)
            G = pool[gidx_blk]                           # [B, n_max, ...]
            w = gw_blk.reshape((B, n_max) + (1,) * (b32.ndim - 1))
            return jnp.sum(w * G, axis=1).astype(blk.dtype)

        return jax.tree.map(one, blk_tree)

    return shard_map(
        block_fn, mesh=mesh,
        in_specs=(specs, vec, vec, vec), out_specs=specs,
    )(tree, jnp.asarray(arrays["send_idx"]),
      jnp.asarray(arrays["gather_idx"]), jnp.asarray(arrays["gather_w"]))
