"""Dropless sparse-expert dispatch: route, sort, grouped matmul, combine.

The expert layer of a top-k mixture (models/olmoe3d.py) computes, for
each of ``T`` tokens, ``k`` of ``E`` experts and nothing else. There is
no capacity factor and no dropped token (OLMoE trains dropless): the
``k * T`` (token, slot) pairs are sorted by expert, every expert
multiplies the contiguous run of rows routed to it, and the rows are
un-sorted and summed back per token under the router's weights.

    probs, weights, experts = route(logits, k)        # float32, always
    plan = dispatch_plan(experts, E)                   # the sort
    xs = gather_slots(x, plan)                         # [k*T, H]
    ys = grouped_matmul(xs, w, plan.group_sizes)       # [k*T, N]
    y = combine_slots(ys, weights, plan)               # [T, N]

The group sizes are data, so the grouped matmul is a ragged
contraction. Two candidates were measured on the v5e inside the real
training step (PERF.md, PR 25): ``jax.lax.ragged_dot`` (XLA's own, a
Mosaic kernel on a TPU) and the Pallas ``megablox.gmm`` kernel, which
shipped (:func:`grouped_matmul`). Nothing here is ever placed under a
client-axis ``vmap`` on the chip: the round program runs such a model
one client at a time (engines/program.py, the folded placement).
"""

from __future__ import annotations

from typing import NamedTuple

import jax
import jax.numpy as jnp


class DispatchPlan(NamedTuple):
    """The sort of ``k * T`` slots by expert. ``order[j]`` is the flat
    slot (``token * k + slot``) that lands in sorted row ``j``;
    ``inverse`` undoes it; ``group_sizes[e]`` rows belong to expert
    ``e`` (they sum to ``k * T``: nothing is dropped)."""

    order: jax.Array
    inverse: jax.Array
    group_sizes: jax.Array
    k: int


def route(logits: jax.Array, k: int):
    """Softmax over the experts in float32 whatever the compute dtype,
    then the top ``k``: ``(probs [T, E], weights [T, k], experts [T, k])``.
    The weights are the chosen probabilities as they are, NOT
    renormalised over the k (OLMoE's ``norm_topk_prob`` false)."""
    probs = jax.nn.softmax(logits.astype(jnp.float32), axis=-1)  # nidt: allow[precision-upcast] -- the router is float32 by the architecture's definition (a bf16 softmax flips near-tied experts)
    weights, experts = jax.lax.top_k(probs, k)
    return probs, weights, experts


def dispatch_plan(experts: jax.Array, num_experts: int) -> DispatchPlan:
    """Sort the flat slots by expert (stable: a token's rows keep their
    slot order inside an expert's run) and count each expert's rows."""
    k = int(experts.shape[-1])
    flat = experts.reshape(-1)
    order = jnp.argsort(flat, stable=True)
    inverse = jnp.zeros_like(order).at[order].set(
        jnp.arange(order.shape[0], dtype=order.dtype))
    group_sizes = jnp.bincount(flat, length=num_experts).astype(jnp.int32)
    return DispatchPlan(order, inverse, group_sizes, k)


@jax.custom_vjp
def permute_rows(x: jax.Array, perm: jax.Array, inverse: jax.Array):
    """``y[j] = x[perm[j]]`` for a PERMUTATION ``perm`` with inverse
    ``inverse``. Its transpose is the gather by ``inverse``; autodiff
    does not know ``perm`` is one-to-one and emits a scatter-add, which
    on the v5e cost 9.1 ms a step for one ``[81920, 2048]`` operand
    where a gather costs 2.8 (my chip runs, PR 25)."""
    return jnp.take(x, perm, axis=0)


def _permute_fwd(x, perm, inverse):
    return jnp.take(x, perm, axis=0), inverse


def _permute_bwd(inverse, g):
    return jnp.take(g, inverse, axis=0), None, None


permute_rows.defvjp(_permute_fwd, _permute_bwd)


def gather_slots(x: jax.Array, plan: DispatchPlan) -> jax.Array:
    """Token rows in expert order: ``[T, H] -> [k*T, H]``: each row
    repeated for its ``k`` slots, then permuted (the backward pass is
    the inverse permutation and a sum over the ``k``)."""
    return permute_rows(jnp.repeat(x, plan.k, axis=0), plan.order,
                        plan.inverse)


#: megablox tile sizes (rows, contraction, columns), from the chip runs
#: of PR 25 at the published widths (81,920 rows x 2048 x 1024, 64
#: groups). The real training step took 87.3 ms with (512, 1024, 1024),
#: 88.7 with (256, 1024, 1024), 94.2 with (512, 512, 512), 97.7 with
#: ``jax.lax.ragged_dot``; wider tiles do not fit VMEM. The kernel visits
#: a row tile once for every group that has rows in it, so its time
#: follows the routing: alone, one forward call reads 2.00 ms when every
#: group boundary falls on a tile boundary and 2.74 when none does.
GMM_TILING = (512, 1024, 1024)


def grouped_matmul(xs: jax.Array, w: jax.Array,
                   group_sizes: jax.Array) -> jax.Array:
    """``ys[j] = xs[j] @ w[expert of row j]`` for rows sorted by expert:
    ``xs [M, K]``, ``w [E, K, N]``, ``group_sizes [E]`` -> ``[M, N]``.
    Only the routed rows are multiplied: ``2 * M * K * N`` operations,
    not ``E`` times that.

    On a TPU this is the Pallas ``megablox.gmm`` kernel (with its own
    ``custom_vjp``: ``gmm`` against the transposed weights for ``dx``,
    ``tgmm`` for ``dW``). Its one constraint is a row count that is a
    multiple of the row tile; 8 slots x 640 tokens a volume are ten
    tiles, so every batch of the published model meets it, and another
    shape is refused here, not routed to a slower kernel nobody
    measured. Off the TPU (the CPU tests) it is XLA's
    ``jax.lax.ragged_dot``."""
    if jax.default_backend() != "tpu":
        return jax.lax.ragged_dot(xs, w, group_sizes)
    from jax.experimental.pallas.ops.tpu.megablox import ops as megablox

    if xs.shape[0] % GMM_TILING[0]:
        raise ValueError(
            f"grouped_matmul: {xs.shape[0]} rows are not a multiple of "
            f"the kernel's row tile {GMM_TILING[0]}")
    return megablox.gmm(xs, w, group_sizes, xs.dtype, GMM_TILING)


def combine_slots(ys: jax.Array, weights: jax.Array,
                  plan: DispatchPlan) -> jax.Array:
    """Un-sort the expert outputs and sum each token's ``k`` rows under
    the router's weights: ``[k*T, N] -> [T, N]``, accumulated in
    float32."""
    T = weights.shape[0]
    per_slot = permute_rows(ys, plan.inverse, plan.order).reshape(
        T, plan.k, -1)
    return jnp.einsum("tkn,tk->tn", per_slot, weights.astype(ys.dtype),
                      preferred_element_type=jnp.float32)


def load_balancing_loss(probs: jax.Array, experts: jax.Array,
                        num_experts: int) -> jax.Array:
    """``E * sum_e f_e * P_e`` as ``load_balancing_loss_func`` of the
    public OLMoE modelling code has it: ``f_e`` the tokens routed to
    expert ``e`` over the ``k`` slots, per token (the ``f_e`` sum to
    ``k``), ``P_e`` the mean router probability of ``e``. Unweighted:
    the model multiplies by its coefficient."""
    T = probs.shape[0]
    counts = jnp.bincount(experts.reshape(-1), length=num_experts)
    f = counts.astype(jnp.float32) / T  # nidt: allow[precision-upcast] -- an auxiliary loss term: float32 like every loss
    return num_experts * jnp.sum(f * jnp.mean(probs, axis=0))
