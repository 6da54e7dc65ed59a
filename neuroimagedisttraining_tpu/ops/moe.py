"""Dropless sparse-expert dispatch: route, sort, grouped matmul, combine.

The expert layer of a top-k mixture (models/olmoe3d.py,
models/nemotronh3d.py) computes, for each of ``T`` tokens, ``k`` of ``E``
experts and nothing else. There is no capacity factor and no dropped
token: the ``k * T`` (token, slot) pairs are sorted by expert, every
expert multiplies the contiguous run of rows routed to it, and the rows
are un-sorted and summed back per token under the router's weights.

    probs, weights, experts = route(logits, k)        # float32, always
    plan = dispatch_plan(experts, E)                   # the sort
    xs = gather_slots(x, plan)                         # [k*T, H]
    ys = grouped_matmul(xs, w, plan.group_sizes)       # [k*T, N]
    y = combine_slots(ys, weights, plan)               # [T, N]

Two routers stand behind :func:`route`'s one signature: OLMoE's softmax
top-k, and the sigmoid scores with a selection bias, renormalised top-k
weights and a scaling factor of the DeepSeek-V3 line (Nemotron-H).

A layer may hold a SHARE of its experts (expert parallelism: this chip's
``count`` of the layer's ``E``, starting at expert ``first``). It still
routes over all ``E`` and sorts every slot; ``grouped_matmul`` is then
handed the held experts' weights ``[count, K, N]`` beside the full
``group_sizes [E]`` and multiplies only the runs of the held experts,
wherever they start in the sort. No row routed to a held expert can be
dropped, because there is no buffer to overflow; the rows of the absent
experts come out zero, as the partial result one chip of the deployment
contributes.

The alternative, gathering the held runs alone into a buffer of a static
row bound, was built and measured too (PERF.md, PR 29): 47% more samples
a second in Nemotron-H's cell, and not shipped, because a bound can be
passed where nothing counts it: the trainer's evaluation pads a batch
with zero volumes, whose 10,240 identical tokens all take the same six
experts, and where one of those is held here the real rows behind them
were dropped in silence.

The group sizes are data, so the grouped matmul is a ragged
contraction. Two candidates were measured on the v5e inside the real
training step (PERF.md, PR 25): ``jax.lax.ragged_dot`` (XLA's own, a
Mosaic kernel on a TPU) and the Pallas ``megablox.gmm`` kernel, which
shipped (:func:`grouped_matmul`), its tiles chosen from the operand
shapes (:func:`gmm_tiling`). Nothing here is ever placed under a
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


def route(logits: jax.Array, k: int, *, scoring: str = "softmax",
          bias: jax.Array | None = None, scale: float = 1.0):
    """The router, in float32 whatever the compute dtype:
    ``(scores [T, E], weights [T, k], experts [T, k])``.

    ``scoring="softmax"`` (OLMoE): softmax over the experts, then the top
    ``k``; the weights are the chosen probabilities as they are, NOT
    renormalised over the k (``norm_topk_prob`` false).

    ``scoring="sigmoid"`` (Nemotron-H, after DeepSeek-V3): scores
    ``sigmoid(logits)``; the top ``k`` of ``scores + bias`` (the
    ``e_score_correction_bias`` buffer: it moves the CHOICE and never the
    weight); weights ``scores_chosen / (sum + 1e-20) * scale``
    (``norm_topk_prob`` true, ``routed_scaling_factor``)."""
    logits = logits.astype(jnp.float32)  # nidt: allow[precision-upcast] -- the router is float32 by the architecture's definition (a bf16 softmax flips near-tied experts)
    if scoring == "softmax":
        probs = jax.nn.softmax(logits, axis=-1)
        weights, experts = jax.lax.top_k(probs, k)
        return probs, weights, experts
    if scoring != "sigmoid":
        raise ValueError(f"route: unknown scoring {scoring!r}")
    scores = jax.nn.sigmoid(logits)
    _, experts = jax.lax.top_k(scores if bias is None else scores + bias, k)
    chosen = jnp.take_along_axis(scores, experts, axis=-1)
    weights = chosen / (jnp.sum(chosen, axis=-1, keepdims=True) + 1e-20)
    return scores, weights * scale, experts


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


#: the row tiles, widest first, and the widest tile of the two matrix dimensions, of
#: ``megablox.gmm``: from the chip runs of PR 25 at OLMoE's widths
#: (81,920 rows x 2048 x 1024, 64 groups). The real training step took
#: 87.3 ms with (512, 1024, 1024), 88.7 with (256, 1024, 1024), 94.2 with
#: (512, 512, 512), 97.7 with ``jax.lax.ragged_dot``; wider tiles do not
#: fit VMEM. The kernel visits a row tile once for every group that has
#: rows in it, so its time follows the routing: alone, one forward call
#: reads 2.00 ms when every group boundary falls on a tile boundary and
#: 2.74 when none does.
GMM_ROW_TILES = (512, 256, 128)
GMM_TILE_MAX = 1024
_LANES = 128


def gmm_tiling(m: int, k: int, n: int) -> tuple[int, int, int]:
    """``(rows, contraction, columns)`` tiles of ``megablox.gmm`` for a
    ``[m, k] x [E, k, n]`` product, from the operand shapes alone.

    The row tile is the widest of :data:`GMM_ROW_TILES` that divides the
    row count, the kernel's one constraint on it: 512 for every training
    and evaluation batch of both models; 256 for the 3,840 slots of the
    single volume Nemotron-H is initialised on (6 slots x 640 tokens).

    One tile ``t`` serves both matrix dimensions, because the kernel's
    own backward pass (``dx`` against the transposed weights) swaps them
    under the same tuple. ``t`` is the multiple of 128 lanes, at most
    :data:`GMM_TILE_MAX`, that pads the two dimensions least (the kernel
    masks a ragged last tile and still computes it whole), the widest on
    a tie: 1024 for OLMoE's 2048 x 1024 (the measured choice above), 384
    for Nemotron-H's 2688 x 1856 (2688 = 7 x 384; 1856 = 29 x 64 has no
    128-multiple divisor, 5 x 384 pads it by 3.4%)."""
    rows = next((t for t in GMM_ROW_TILES if m % t == 0), None)
    if rows is None:
        raise ValueError(
            f"grouped_matmul: {m} rows are not a multiple of any of the "
            f"kernel's row tiles {GMM_ROW_TILES}")
    if min(k, n) < _LANES or k % 8 or n % 8:
        raise ValueError(
            f"grouped_matmul: no megablox tile fits a {k} x {n} weight "
            f"(each dimension has to be a multiple of 8 and at least "
            f"{_LANES})")

    def padded(t):
        return -(-k // t) * t / k + -(-n // t) * t / n

    t = min(range(_LANES, GMM_TILE_MAX + 1, _LANES),
            key=lambda t: (padded(t), -t))
    return rows, t, t


def grouped_matmul(xs: jax.Array, w: jax.Array, group_sizes: jax.Array,
                   first: int = 0) -> jax.Array:
    """``ys[j] = xs[j] @ w[expert of row j - first]`` for rows sorted by
    expert: ``xs [M, K]``, ``w [count, K, N]``, ``group_sizes [E]`` ->
    ``[M, N]``. Only the routed rows are multiplied: ``2 * rows * K * N``
    operations, not ``E`` times that.

    ``w`` holds the experts ``first .. first + count - 1`` of the ``E``
    that ``group_sizes`` counts (all of them by default). The rows of an
    expert outside that window come out zero and cost nothing: the
    kernel starts at the first held expert's run and stops after the
    last's.

    On a TPU this is the Pallas ``megablox.gmm`` kernel (with its own
    ``custom_vjp``: ``gmm`` against the transposed weights for ``dx``,
    ``tgmm`` for ``dW``), the window its ``group_offset``, the tiles
    :func:`gmm_tiling`'s; a shape it has no tiles for is refused there,
    not routed to a slower kernel nobody measured. Off the
    TPU (the CPU tests) it is XLA's ``jax.lax.ragged_dot``, the window
    made of one zero matrix before and one after the held weights."""
    count, E = w.shape[0], group_sizes.shape[0]
    if not 0 <= first <= E - count:
        raise ValueError(f"grouped_matmul: experts {first}..{first + count}"
                         f" are not among the layer's {E}")
    if jax.default_backend() != "tpu":
        if count == E:
            return jax.lax.ragged_dot(xs, w, group_sizes)
        held = group_sizes[first:first + count]
        before = jnp.sum(group_sizes[:first])
        sizes = jnp.concatenate([
            before[None], held,
            (xs.shape[0] - before - jnp.sum(held))[None]]).astype(jnp.int32)
        zero = jnp.zeros((1,) + w.shape[1:], w.dtype)
        return jax.lax.ragged_dot(xs, jnp.concatenate([zero, w, zero]),
                                  sizes)
    from jax.experimental.pallas.ops.tpu.megablox import ops as megablox

    tiling = gmm_tiling(xs.shape[0], w.shape[1], w.shape[2])
    if count == E:
        return megablox.gmm(xs, w, group_sizes, xs.dtype, tiling)
    return megablox.gmm(xs, w, group_sizes, xs.dtype, tiling,
                        jnp.int32(first))


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
