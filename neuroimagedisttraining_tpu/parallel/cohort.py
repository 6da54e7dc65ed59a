"""Cohort sharding: one dispatched program trains every sampled client.

The flagship workload is 21 acquisition-site clients, but until ISSUE 6
the round driver ran the whole ``[C, ...]`` client stack on one device:
the federation's DATA was mesh-sharded (data/federate.py), yet the jitted
round program's vmapped local-training stage carried no placement
contract, so XLA was free to (and on the measured configs did) execute
all C clients' local SGD serially on one device — round time linear in C.
This module supplies the missing placement contract (ROADMAP item 2, the
SysML-2018 compile-once/dispatch-once premise in PAPERS.md):

- :func:`cohort_map` wraps the per-client training block in ``shard_map``
  over the mesh's client axis with EXPLICIT in/out specs: each device
  trains its ``C/D`` client shard, then the trained stacks are
  all-gathered back to replicated full stacks. Evaluation takes the same
  road (PR 28, ``FederatedEngine._per_client``): each device scores the
  client rows it holds, one unbatched row at a time, the global model
  closed over (replicated) or the personalized stack cut by row, and
  what is all-gathered is four scalars a client. The ``vmap`` it
  replaced left the partitioning to GSPMD, which rebuilt the whole
  stem activation of every row on every chip.
- :func:`pad_cohort` pads a sampled set that does not tile the mesh
  (21 sites on 8 devices -> 24 rows) with zero-weight pad rows, and
  :func:`pad_row_weights` is THE one place pad-row weights are zeroed
  (nidtlint's ``mesh-pad-weights`` rule rejects ad-hoc reconstructions).
- :func:`deal_rows` orders the padded set so that the contiguous blocks
  ``shard_map`` cuts are balanced in SGD steps (PR 26): a row trained
  here runs alone, so it stops at its own last step
  (``LocalTrainer.rows_alone``; the engine's ``_cohort_map`` enters it
  around both the mesh loop and the sequential reference), a chip's share
  of a round is the sum of its rows' real steps, and the round ends when
  the busiest chip reaches the all-gather.

Numerical contract (tests/test_cohort.py), stated with the precision
the measurements force:

- vs the UNPARTITIONED sequential C-loop (:func:`sequential_map` in a
  plain jit) AND the shipped vmapped round: a FedAvg round's training
  losses from identical state are BITWISE-equal — the proof that batch
  selection, masking, weighting, and every semantic choice is
  identical (the masked salientgrads round's mean loss sits exactly 1
  float32 ulp off: the per-step mask multiply adds one more fusion
  seam; on jax 0.9.0 so does the 4-site cohort padded to 8 rows, whose
  per-client losses are bitwise-equal while the partitioned module
  orders the 4-term weighted mean differently) — and trained params/batch stats agree to ~1 ulp of their own
  magnitude. The residue is an XLA compile-context artifact, not a
  semantic one (different modules tile a handful of reductions
  differently); over multi-round windows it feeds back through
  training and surfaces as ~1e-6-level relative drift.
- MESH-WIDTH INDEPENDENCE to the same ~1 ulp: a full sharded
  ``train()`` on a 2-device mesh matches the 8-device run through
  different pad counts (21 real sites -> 22 vs 24 rows) and per-device
  work lists. Exactly-bitwise equality holds only between runs whose
  COMPILED MODULE is identical; a K=4 fused window IS bitwise-equal to
  four single sharded dispatches (pinned).

Three design decisions exist to keep those pins maximal — the third is
a hard CORRECTNESS requirement, not a preference:

- Per-client training runs UNBATCHED, ``lax.map``-looped within each
  device's shard (:func:`sequential_map` is the same loop on one
  device). It does NOT run as vmapped client lanes: XLA tiles a batched
  client contraction by its total width, so a client's trained values
  differ at 1e-3 level between a 3-lane device block and a 21-lane
  unsharded vmap — vmap lanes are not width-stable; unbatched
  per-client programs are.
- The aggregation is NOT a ``psum`` of per-device partial weighted
  sums: partial sums reorder the float reduction. Instead the trained
  stacks are all-gathered to every device and the engine's existing
  aggregation/defense/codec tail runs unchanged on replicated full
  stacks — identical operations on identical values. The gather moves
  the same bytes per device a reduce-scatter + broadcast pair would;
  what it gives up is only the redundant (cheap, model-sized)
  reduction arithmetic per device.
- RANDOM-SORT OPS MUST BE HOISTED OUT OF THE PARTITION. Measured on
  jax 0.4.x CPU SPMD (not re-checked on 0.9.0): an argsort-lowered
  ``jax.random.permutation`` computed INSIDE a shard_map partition and
  CONSUMED by the training scan silently yields different batch
  selections than the same code unpartitioned — while OBSERVING the
  permutation (returning it as an output) makes it correct, the
  signature of a fusion miscompilation. The bisection that found it:
  per-client losses diverged at 1e-0 level with identical inputs,
  identical observable indices, across every gather mode,
  ``optimization_barrier`` placement, and XLA runtime flag — and went
  to ZERO the moment the permutations were computed outside the
  ``shard_map`` and passed in. Hence ``LocalTrainer.local_train``'s
  ``perms=`` parameter + the round-program builder's perm hoist
  (``engines/program.py``: ``hoisted_epoch_perms`` /
  ``RoundCtx.client_map``) for the rounds, and
  ``ops.snip.iter_snip_batch_indices`` for phase-1's IterSNIP draws;
  the non-hoistable ``batch_order=replacement`` (i.i.d. per-step
  randint draws — same in-partition lowering family, same measured
  wrongness) falls back to the unsharded round with a logged reason.
  The rule is held where the partition is entered:
  ``FederatedEngine._cohort_map`` tells the trainer its rows sit in a
  partition (``LocalTrainer.rows_alone(partitioned=True)``), and a
  ``local_train`` traced there without ``perms`` raises. Evaluation
  draws nothing random and sorts nothing (``binary_auc`` is the
  pairwise form), so it needs no hoist.

The compute-dominant stage (per-client Conv3D local training, ~99% of
round FLOPs) therefore runs ``ceil(C/D)`` sequential clients per device
instead of ``C`` — flat in C up to the device count, the flagship
deployment's one-site-per-core layout.

Pad-row semantics: pad ids prefer the federation's zero-sample padding
clients (rows ``[real_clients, num_clients)`` — ``n_train == 0``), then
repeat the last sampled id; either way :func:`pad_row_weights` zeroes
their sample counts before local training, so a pad row runs NO step at
all (its loop bound is zero) and weighs nothing, and the engine round
bodies drop the pad rows after the gather — the aggregation/defense tail
never sees them (the robust aggregators additionally ignore zero-weight
rows, so even an unsliced consumer is safe).

The dealt order, and where it is undone: the host computes the deal per
round from integers it already holds (``FederatedEngine._cohort_deal``:
``ceil(n / batch)`` of the padded ids, pads at zero) and hands the round
program the ids in dealt order (so ``X_train[idx]`` is gathered as
dealt, the rngs, folded from client ids, follow, and the hoisted
permutations are derived from both) together with the deal itself, an
operand and never a constant: another sampled set, or another round of a
fused window, is another index array and no recompile. After the
all-gather, one ``take`` over model-sized leaves
(``RoundProgram._body``: ``argsort(deal)[:n_real]``) puts the trained
stacks back in the sampler's order and drops the pad rows, so the
attack / codec / defense / aggregate / update tail sees the rows it
always saw in the order it saw them and the weighted sum is the same sum:
a dealt round equals the undealt one bitwise (tests/test_rows_alone.py).
Phase 1 of SalientGrads, the final fine-tune pass
(``cohort_local_stage``) and evaluation go through :func:`cohort_map` in
the data's own order (every evaluated row walks the same number of
eval batches, so there is nothing to deal); the streamed sharded feed keeps the sampler's order too (cohort
sharding does not arm under streaming: its rows are batched, and batched
rows all walk the longest row's steps wherever they sit).
"""

from __future__ import annotations

from typing import Any

import jax
import jax.numpy as jnp
import numpy as np
from jax import shard_map
from jax.sharding import Mesh, PartitionSpec as P

from neuroimagedisttraining_tpu.obs import names as obs_names

PyTree = Any


def pad_cohort(sampled: np.ndarray, real_clients: int, num_clients: int,
               n_devices: int) -> tuple[np.ndarray, int]:
    """``(padded_ids, n_real)``: the sampled set padded to tile an
    ``n_devices``-wide client mesh. Pad entries prefer the federation's
    zero-sample padding clients (rows ``[real_clients, num_clients)``),
    then repeat the last sampled id (its pad rows are zero-weighted by
    position via :func:`pad_row_weights`, never by sample count). The
    shared pad rule of the streamed feed (``stream_sampling``) and the
    cohort-sharded resident round."""
    sampled = np.asarray(sampled)
    if len(sampled) == 0:
        raise ValueError("pad_cohort got an empty sampled set — no client "
                         "to pad the mesh tile from (configuration error)")
    pad = (-len(sampled)) % n_devices
    if pad == 0:
        return sampled, len(sampled)
    pool = np.arange(real_clients, num_clients)
    fill = np.concatenate([pool, np.full(max(0, pad - len(pool)),
                                         sampled[-1])])[:pad]
    return np.concatenate([sampled, fill]).astype(sampled.dtype), \
        len(sampled)


def pad_row_weights(ns: jax.Array, n_real: int,
                    deal: jax.Array | None = None) -> jax.Array:
    """Zero the per-client sample counts of mesh-pad rows (position >=
    ``n_real`` of the padded set; ``deal`` gives each row's position
    there when the rows arrive dealt, :func:`deal_rows`). THE shared
    helper for pad-row zero-weight construction: a pad entry may
    DUPLICATE a real client id (``pad_cohort`` repeats the last sampled
    id once the zero-sample pool runs dry), so gathering ``n_train`` rows
    is not enough — the position mask is what guarantees a pad row runs
    no step and weighs nothing. nidtlint's ``mesh-pad-weights`` rule
    keeps every call site on this function."""
    pos = jnp.arange(ns.shape[0]) if deal is None else deal
    return jnp.where(pos < n_real, ns, jnp.zeros_like(ns))


def deal_rows(steps: np.ndarray, n_devices: int) -> np.ndarray:
    """Deal the rows of a mesh-tiling set to the chips by their step
    counts: ``order`` with ``order[k]`` the row (position in ``steps``)
    that goes to dealt position ``k``, so that the contiguous blocks of
    ``len(steps) / n_devices`` rows ``shard_map`` cuts carry balanced
    sums. Longest first, each next row to the chip with the smallest sum
    that still has a free slot. A chip's share of a round is the sum of
    its rows' real steps once a row stops at its own last step
    (``LocalTrainer.rows_alone``), and the round ends when the busiest
    chip does. The sampler's order is kept (the identity) where dealing
    makes the busiest chip no lighter: equal sites, one row a chip."""
    steps = np.asarray(steps)
    rows = len(steps) // n_devices
    sums = np.zeros(n_devices, np.int64)
    dealt: list[list[int]] = [[] for _ in range(n_devices)]
    for r in np.argsort(-steps, kind="stable"):
        d = min((d for d in range(n_devices) if len(dealt[d]) < rows),
                key=lambda d: (sums[d], d))
        dealt[d].append(int(r))
        sums[d] += int(steps[r])
    as_sampled = steps.reshape(n_devices, rows).sum(axis=1).max()
    if sums.max() >= as_sampled:
        return np.arange(len(steps))
    return np.asarray([r for d in dealt for r in sorted(d)])


def sequential_map(fn, *stacked: PyTree) -> PyTree:
    """The sequential C-loop as ONE dispatched program: ``lax.map`` of
    the UNBATCHED per-client ``fn`` over the stacks' leading client axis
    — the reference's client-at-a-time simulation
    (sailentgrads_api.py:126-138) expressed as a single XLA while loop.
    :func:`cohort_map` runs D of these loops in parallel, one per mesh
    device; because both paths execute the identical unbatched
    per-client program, the sharded round matches this loop to ~1 ulp
    with bitwise first-round losses (module docstring) — which no
    vmap-lane formulation can promise."""
    return jax.lax.map(lambda args: fn(*args), tuple(stacked))


def _gather_replicated(x: jax.Array, axis_name: str) -> jax.Array:
    """All-gather one leaf's per-device client blocks back into the full
    replicated ``[C, ...]`` stack. Typed PRNG-key arrays (the trained
    ``ClientState.rng`` leaves) gather through their uint32 key data —
    collectives do not accept extended dtypes."""
    if jnp.issubdtype(x.dtype, jax.dtypes.prng_key):
        data = jax.lax.all_gather(jax.random.key_data(x), axis_name,
                                  axis=0, tiled=True)
        return jax.random.wrap_key_data(data)
    return jax.lax.all_gather(x, axis_name, axis=0, tiled=True)


def cohort_map(mesh: Mesh, fn, *stacked: PyTree) -> PyTree:
    """Map the unbatched per-client ``fn`` over the leading client axis
    of the ``stacked`` pytrees with that axis SHARDED over ``mesh``'s
    (single) client axis: each device runs :func:`sequential_map`'s
    client loop over its ``C/D`` block, and the outputs are all-gathered
    back to replicated full ``[C, ...]`` stacks — ~1-ulp-equal (with
    bitwise losses from identical state) to
    ``sequential_map(fn, *stacked)`` and across mesh widths (the module
    docstring explains why the loop, and not vmap lanes, is what makes
    those pins possible, and where exact bitwise equality holds).

    ``fn`` may close over replicated (unbatched) state — the round's
    incoming global params, the SNIP mask, FedProx's proximal reference;
    ``shard_map`` lifts closed-over values as replicated. The leading
    axis must tile the mesh (:func:`pad_cohort`); anything else is a
    caller bug and fails loudly here."""
    if len(mesh.axis_names) != 1:
        raise ValueError(
            f"cohort_map shards over a 1-D client mesh; got axes "
            f"{mesh.axis_names} (two-level meshes route aggregation "
            "silo-first instead — parallel/hierarchical.py)")
    axis = mesh.axis_names[0]
    D = mesh.devices.size
    C = jax.tree.leaves(stacked[0])[0].shape[0]
    if C % D != 0:
        raise ValueError(
            f"cohort_map: client axis ({C}) does not tile the {D}-device "
            "mesh — pad the sampled set with pad_cohort first")

    def block(*blocks):
        out = sequential_map(fn, *blocks)
        with jax.named_scope(obs_names.SCOPE_COHORT_GATHER):
            return jax.tree.map(lambda x: _gather_replicated(x, axis),
                                out)

    in_specs = tuple(P(axis) for _ in stacked)
    # out_specs P(): the all-gather leaves every output replicated, but
    # the varying-manual-axes checker cannot see through a tiled
    # all_gather, so it is off (the gather IS the replication proof).
    return shard_map(block, mesh=mesh, in_specs=in_specs, out_specs=P(),
                     check_vma=False)(*stacked)
