"""Dropless sparse-expert dispatch: route, sort, grouped matmul, combine.

The expert layer of a top-k mixture (models/olmoe3d.py, nemotronh3d.py,
zaya3d.py) computes, for each of ``T`` tokens, ``k`` of ``E`` experts and
nothing else. There is no capacity factor and no dropped token: the
``k * T`` (token, slot) pairs are sorted by expert, every expert
multiplies the contiguous run of rows routed to it, and the rows are
un-sorted and summed back per token under the router's weights.

    probs, weights, experts = route(logits, k)        # float32, always
    plan = dispatch_plan(experts, E)                   # the sort
    xs = gather_slots(x, plan)                         # [k*T, H]
    ys = grouped_matmul(xs, w, plan.group_sizes)       # [k*T, N]
    y = combine_slots(ys, weights, plan)               # [T, N]

or, for a layer of two matrices an expert that holds some of the ``E``,

    y = held_expert_rows(x, weights, experts, up, down, E, first, act)

Two scorings stand behind :func:`route`'s one signature: the softmax
top-k (OLMoE; ZAYA's router MLP hands it its 17 logits, the last of which
is no expert: a token sent there skips the layer, and since no chip holds
it the held layer below adds nothing for it), and the sigmoid scores with
renormalised top-k weights and a scaling factor of the DeepSeek-V3 line
(Nemotron-H); either takes a selection bias.

A layer may hold a SHARE of its experts (expert parallelism: this chip's
``count`` of the layer's ``E``, starting at expert ``first``). It still
routes over all ``E``; what it computes is the part of the result that
its own experts give for the rows routed to them, the partial result one
chip of the deployment contributes (:func:`held_expert_rows`). In the
sort those rows are one contiguous run, about ``count / E`` of the
``k * T``, and the layer moves that run alone, as a chip of the
deployment receives only its own experts' rows: the run's window of the
sort is taken into a buffer of a static row count ``C``
(:func:`held_capacity`: twice the uniform share), the ``C`` token rows
are gathered, multiplied under the held experts' own ``group_sizes
[count]`` and added back into their tokens' rows in float32. The buffer
is the unit of work and not a capacity factor: a routing that sends here
more rows than it holds is computed in as many windows of ``C`` rows as
the run needs, by a loop whose bound the program reads from the routing
it already has (one window where the run fits, which is every training
step measured; the forward pass and, behind a ``custom_vjp`` whose
residuals are the layer's inputs, the backward pass each have the loop).
No row routed to a held expert is dropped, on any input, and no row of
an absent expert is ever moved. The trainer's evaluation, whose filler
volumes are zero and send their 10,240 identical tokens to the same six
experts, is where more than one window runs (PR 29 built the buffer
without the loop, and withdrew it there: real rows were dropped in a
program that counts nothing); in training such a call is counted
(``held_overflow_calls`` on the round driver's ``round_log`` span).

Where nothing is to be gained (every expert held, or a buffer as long as
the sort) and where a call runs eagerly (a model's initialisation) the
held part is computed by the sort of every slot: ``grouped_matmul`` is
handed the held experts' weights ``[count, K, N]`` beside the full
``group_sizes [E]`` and multiplies only the runs of the held experts,
wherever they start in the sort; the rows of the absent experts come out
zero. That was the whole layer until PR 30, at 38% of Nemotron-H's step
for 1% of its useful FLOPs (PERF.md, sections 5 and 6).

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

import functools
from typing import Callable, NamedTuple

import jax
import jax.numpy as jnp

from neuroimagedisttraining_tpu.obs import names as obs_names

_scope = jax.named_scope


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
    renormalised over the k (``norm_topk_prob`` false). With a ``bias``
    (ZAYA's balancing buffer) the choice is the top ``k`` of
    ``stop_gradient(probs) + bias`` and the weights are still the chosen
    probabilities: the bias moves the CHOICE and never the weight.

    ``scoring="sigmoid"`` (Nemotron-H, after DeepSeek-V3): scores
    ``sigmoid(logits)``; the top ``k`` of ``scores + bias`` (the
    ``e_score_correction_bias`` buffer: it moves the CHOICE and never the
    weight); weights ``scores_chosen / (sum + 1e-20) * scale``
    (``norm_topk_prob`` true, ``routed_scaling_factor``)."""
    logits = logits.astype(jnp.float32)  # nidt: allow[precision-upcast] -- the router is float32 by the architecture's definition (a bf16 softmax flips near-tied experts)
    if scoring == "softmax":
        probs = jax.nn.softmax(logits, axis=-1)
        if bias is None:
            weights, experts = jax.lax.top_k(probs, k)
            return probs, weights, experts
        _, experts = jax.lax.top_k(jax.lax.stop_gradient(probs) + bias, k)
        return probs, jnp.take_along_axis(probs, experts, axis=-1), experts
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
#:
#: From the chip runs of PR 46 and PR 47 at Moonlight's widths (a
#: 14,848-row buffer, 8 groups, 2048 x 2816 "up" and 1408 x 2048 "down";
#: the real training step, forward and backward, by the matrix tiles of
#: (up, down); the eight kernels of one layer in ms: forward up + down,
#: the backward's recomputation up + down, dx down + up, dW up + down; PR
#: 46's call, whose change was refused unmeasured and asked again as PR
#: 47, which read the first and third rows again: 323.4 and 284.4 ms,
#: 13.10 and 5.19):
#:
#:   (256, 128)   323.1 ms a step   1.50+1.76  1.51+1.55  1.90+1.31  1.65+1.92 = 13.09
#:   (512, 512)   288.4             0.93+0.46  1.01+0.48  0.48+0.93  1.01+0.51 =  5.80
#:   (1024, 512)  284.7             0.79+0.46  0.80+0.48  0.48+0.83  0.84+0.51 =  5.19
#:   (1024, 768)  285.2             0.79+0.47  0.79+0.53  0.48+0.83  0.84+0.50 =  5.23
#:
#: (256, 128) is what the least-padding rule chose: a grid step of 17-67
#: MFLOP costs what it costs whatever it multiplies. A tile that pads a
#: dimension by a tenth loses less than that (2816 -> 3072, 1408 -> 1536).
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
    under the same tuple. ``t`` is the WIDEST multiple of 128 lanes, at
    most :data:`GMM_TILE_MAX`, that pads neither dimension by more than a
    tenth (the kernel masks a ragged last tile and still computes it
    whole, so padding is work; a narrow tile is grid steps, each with its
    own cost whatever it multiplies): 1024 for OLMoE's 2048 x 1024 (the
    measured choice above), for ZAYA1's 2048 x 4096 and 2048 x 2048 and
    for Trinity-Mini's 2048 x 2048 and 1024 x 2048; 384 for Nemotron-H's
    2688 x 1856 (2688 = 7 x 384, 5 x 384 pads 1856 by 3.4%, and every
    wider tile pads one of the two by 14% or more); 1024 for Moonlight's
    2048 x 2816 and 512 for its 1408 x 2048 (9.1% more columns each: 1408
    = 11 x 128 has no wider divisor, and the tiles that pad them least,
    256 and 128, are about 23,000 grid steps a layer and step of 17-67
    MFLOP each: 13.1 ms of kernels for 5.2, the second table above
    :data:`GMM_ROW_TILES`). Where no tile pads both within a tenth (136 x
    1024), the tile that pads them least, the widest on a tie."""
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

    def padded(d, t):
        return -(-d // t) * t

    tiles = range(_LANES, GMM_TILE_MAX + 1, _LANES)
    within_a_tenth = [t for t in tiles if all(
        10 * padded(d, t) <= 11 * d for d in (k, n))]
    t = within_a_tenth[-1] if within_a_tenth else min(
        tiles, key=lambda t: (padded(k, t) / k + padded(n, t) / n, -t))
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


#: the held runs' buffer over the uniform share ``k * T * count / E``.
#: Twice: at the initial weights Nemotron-H's 8 held of 128 receive 5.8 to
#: 6.4% of the rows where uniform is 6.25% (PERF.md, PR 29), so training
#: never comes near it, and the buffer still moves an eighth of the rows
#: the full sort does. What passes it takes a second window, so the
#: factor trades time only, never a row.
HELD_BUFFER_OVER_UNIFORM = 2


def held_capacity(rows: int, count: int, num_experts: int) -> int | None:
    """The static row count ``C`` of the buffer a layer that holds
    ``count`` of ``num_experts`` experts gathers its runs into, of the
    ``rows = k * T`` it routes: :data:`HELD_BUFFER_OVER_UNIFORM` times
    the uniform share, rounded up to the kernel's widest row tile where
    the kernel runs (7,680 of the training step's 61,440 rows at 8 of
    128; 15,360 of evaluation's 122,880). ``None`` where there is no
    share to move alone: every expert is held (OLMoE), or the buffer
    would be no smaller than the sort. From shapes alone, so which
    paths a program holds is decided when it is traced."""
    if count == num_experts:
        return None
    tile = GMM_ROW_TILES[0] if jax.default_backend() == "tpu" else 1
    share = HELD_BUFFER_OVER_UNIFORM * rows * count
    capacity = -(-share // (num_experts * tile)) * tile
    return capacity if capacity < rows else None


def rows_held(experts: jax.Array, first: int, count: int) -> jax.Array:
    """``int32 [count]``: the (token, slot) pairs of ``experts [T, k]``
    routed to each of the experts ``first .. first + count - 1``."""
    held = first + jnp.arange(count, dtype=experts.dtype)
    return jnp.sum(experts.reshape(-1, 1) == held, axis=0, dtype=jnp.int32)


def _full_sort_rows(x, weights, experts, up, down, num_experts, first, act):
    """The held experts' part by the sort of every slot: no buffer, so
    any routing fits; the rows of the absent experts are gathered,
    zeroed and un-sorted with the rest."""
    with _scope(obs_names.SCOPE_DISPATCH):
        plan = dispatch_plan(experts, num_experts)
        xs = gather_slots(x, plan)
    with _scope(obs_names.SCOPE_EXPERTS):
        u = grouped_matmul(xs, up, plan.group_sizes, first)
        ys = grouped_matmul(act(u), down, plan.group_sizes, first)
    with _scope(obs_names.SCOPE_COMBINE):
        return combine_slots(ys, weights, plan).astype(x.dtype)


class _HeldRuns(NamedTuple):
    """Where the held experts' rows are in the sort of ``k * T`` slots:
    ``order`` as :class:`DispatchPlan` has it, the sorted row ``begin``
    at which the first held expert's run starts, and the runs' lengths
    ``sizes [count]`` (one after another from ``begin``)."""

    order: jax.Array
    begin: jax.Array
    sizes: jax.Array


def _held_runs(experts, first, count):
    flat = experts.reshape(-1)
    return _HeldRuns(jnp.argsort(flat, stable=True),
                     jnp.sum(flat < first, dtype=jnp.int32),
                     rows_held(experts, first, count))


def _window(x, weights, runs: _HeldRuns, capacity: int, i):
    """Window ``i`` of the held runs, ``capacity`` rows of them:
    ``(slot, token, valid, sizes, xs, w)``: the flat slot and the token
    of each buffer row, which rows of the buffer the runs reach, how many
    of the window's rows belong to each held expert, and the rows' tokens
    ``x[token]`` and weights (rounded to the compute dtype as
    ``combine_slots`` rounds them, in float32).

    The window is read by index, not sliced: a slice that would pass the
    end of the sort is moved back by XLA, into another expert's rows."""
    ends = jnp.cumsum(runs.sizes)
    w0 = i * capacity
    sizes = jnp.clip(jnp.minimum(ends, w0 + capacity)
                     - jnp.maximum(ends - runs.sizes, w0), 0, None)
    j = w0 + jnp.arange(capacity, dtype=jnp.int32)
    valid = j < ends[-1]
    last = runs.order.shape[0] - 1
    slot = jnp.where(
        valid, jnp.take(runs.order, jnp.minimum(runs.begin + j, last)), 0)
    token = slot // weights.shape[1]
    w = jnp.take(weights.reshape(-1), slot).astype(x.dtype)
    return (slot, token, valid, sizes.astype(jnp.int32),
            jnp.take(x, token, axis=0), w.astype(jnp.float32))  # nidt: allow[precision-upcast] -- the combine multiplies and accumulates in float32, as combine_slots does


def _window_experts(xs, up, down, sizes, valid, act):
    """The buffer's rows through the held experts. A row past the runs
    is masked on both sides of the matmuls: the kernel neither reads nor
    writes it, so it comes out as whatever the memory held, forward and
    in ``dx``."""
    xs = jnp.where(valid[:, None], xs, 0)
    ys = grouped_matmul(act(grouped_matmul(xs, up, sizes)), down, sizes)
    return jnp.where(valid[:, None], ys, 0)


def _windows(runs: _HeldRuns, capacity: int):
    """How many windows of ``capacity`` rows the held runs take: one,
    unless the routing sends here more than the buffer holds, or
    nothing."""
    return -(-jnp.sum(runs.sizes) // capacity)


@functools.partial(jax.custom_vjp, nondiff_argnums=(5, 6, 7))
def _held_run_rows(x, weights, experts, up, down, first, capacity, act):
    """The held experts' part from the held runs alone: window by window
    of ``capacity`` rows, the window's token rows gathered, multiplied
    under the held experts' own group sizes and added into their tokens'
    rows in float32: a loop to a traced bound, one iteration where the
    runs fit the buffer, so every routing has the dropless answer and
    none moves the absent experts' rows."""
    with _scope(obs_names.SCOPE_DISPATCH):
        runs = _held_runs(experts, first, up.shape[0])

    def add_window(i, y):
        with _scope(obs_names.SCOPE_DISPATCH):
            _, token, valid, sizes, xs, w = _window(x, weights, runs,
                                                    capacity, i)
        with _scope(obs_names.SCOPE_EXPERTS):
            ys = _window_experts(xs, up, down, sizes, valid, act)
        with _scope(obs_names.SCOPE_COMBINE):
            return y.at[token].add(ys.astype(jnp.float32) * w[:, None])  # nidt: allow[precision-upcast] -- see _window

    # the first window is an iteration like the rest, not a copy of the
    # body before the loop: a step's program is 164 MiB of code on the
    # chip, a quarter of it these kernels, and every program that trains
    # or evaluates holds them (PERF.md, PR 30: the copies did not fit)
    y = jax.lax.fori_loop(
        0, _windows(runs, capacity), add_window,
        jnp.zeros((x.shape[0], down.shape[2]), jnp.float32))
    return y.astype(x.dtype)


def _held_run_rows_fwd(x, weights, experts, up, down, *static):
    return (_held_run_rows(x, weights, experts, up, down, *static),
            (x, weights, experts, up, down))


def _held_run_rows_bwd(first, capacity, act, inputs, g):
    """Window by window again: each window's rows recomputed from the
    layer's inputs (the only residuals: the layer is rematerialised
    anyway, models/nemotronh3d.py) and transposed, the tokens' and the
    weights' cotangents added in float32 where the rows came from.
    Autodiff cannot transpose a loop to a traced bound, and would keep a
    window's intermediates for each of a static one."""
    x, weights, experts, up, down = inputs
    with _scope(obs_names.SCOPE_DISPATCH):
        runs = _held_runs(experts, first, up.shape[0])

    def add_window(i, sums):
        dx, dw, dup, ddown = sums
        with _scope(obs_names.SCOPE_DISPATCH):
            slot, token, valid, sizes, xs, w = _window(x, weights, runs,
                                                       capacity, i)
        # the scopes around the inner transposition, none inside it: a
        # scope entered under ``jax.vjp`` is named ``transpose(jvp(..))``
        # and the benchmark's rules would not know it
        with _scope(obs_names.SCOPE_EXPERTS):
            ys, transpose = jax.vjp(
                lambda *a: _window_experts(*a, sizes, valid, act),
                xs, up, down)
        with _scope(obs_names.SCOPE_COMBINE):
            g_rows = jnp.take(g, token, axis=0).astype(jnp.float32)  # nidt: allow[precision-upcast] -- the cotangent of a float32 sum
            dws = jnp.sum(g_rows * ys.astype(jnp.float32), axis=1)  # nidt: allow[precision-upcast] -- the same
            dys = (g_rows * w[:, None]).astype(ys.dtype)
        with _scope(obs_names.SCOPE_EXPERTS):
            dxs, dup_i, ddown_i = transpose(dys)
            dup, ddown = dup + dup_i, ddown + ddown_i
        with _scope(obs_names.SCOPE_DISPATCH):
            dx = dx.at[token].add(dxs.astype(jnp.float32))  # nidt: allow[precision-upcast] -- a sum over a token's rows: float32 like the combine's
            dw = dw.at[slot].add(dws)
        return dx, dw, dup, ddown

    # the matrices' cotangents are summed and leave in the compute dtype,
    # as the kernel's own do: the cast to the master weights' float32 is
    # the caller's, and XLA is free to leave it to the step's end
    dx, dw, dup, ddown = jax.lax.fori_loop(
        0, _windows(runs, capacity), add_window,
        (jnp.zeros(x.shape, jnp.float32),
         jnp.zeros(weights.size, jnp.float32), jnp.zeros_like(up),
         jnp.zeros_like(down)))
    return (dx.astype(x.dtype),
            dw.reshape(weights.shape).astype(weights.dtype), None, dup,
            ddown)


_held_run_rows.defvjp(_held_run_rows_fwd, _held_run_rows_bwd)


def held_expert_rows(x: jax.Array, weights: jax.Array, experts: jax.Array,
                     up: jax.Array, down: jax.Array, num_experts: int,
                     first: int, act: Callable,
                     buffer: bool = True) -> tuple[jax.Array, jax.Array]:
    """What the experts ``first .. first + count - 1`` of ``num_experts``
    add to each token, ``sum_slots weight * act(x @ up[e]) @ down[e]``
    over the token's slots routed to one of them: ``x [T, H]``, the
    router's ``weights`` and ``experts [T, k]``, ``up [count, H, W]``,
    ``down [count, W, N]`` (cast to ``x``'s dtype here) -> ``(y [T, N],
    passed int32)``, ``y`` summed in float32 and returned in ``x``'s
    dtype.

    Where a share is held (:func:`held_capacity` gives a buffer) the held
    runs alone are moved and multiplied, a buffer of rows at a time:
    once where they fit it, ``passed`` 0; in as many windows as they
    need where they do not, ``passed`` 1. Where every expert is held, or
    the buffer would not be smaller than the sort, the full sort of
    every slot is what is traced; so it is with ``buffer=False``, for a
    call that runs eagerly (a model's initialisation: an eager loop
    compiles anew on every call). Under a client-axis ``vmap`` the loop
    runs to the longest row's bound (never on the chip: such a model is
    folded)."""
    capacity = held_capacity(experts.size, up.shape[0], num_experts) \
        if buffer else None
    with _scope(obs_names.SCOPE_EXPERTS):  # where the casts always were
        up, down = up.astype(x.dtype), down.astype(x.dtype)
    if capacity is None:
        return (_full_sort_rows(x, weights, experts, up, down, num_experts,
                                first, act), jnp.zeros((), jnp.int32))
    y = _held_run_rows(x, weights, experts, up, down, first, capacity, act)
    held = jnp.sum(rows_held(experts, first, up.shape[0]))
    return y, (held > capacity).astype(jnp.int32)


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


def sequence_balance_loss(scores: jax.Array, experts: jax.Array,
                          num_experts: int) -> jax.Array:
    """The sequence-wise balance loss of the DeepSeek-V3 line
    (``seq_aux``): ``scores [B, T, E]`` (the router's sigmoid scores,
    float32), ``experts [B, T, k]`` -> ``[B]``, for each sequence

        f_e = E / (k T) #{t : e in C_t}      P_e = mean_t (s_e,t / sum_j s_j,t)
        L   = sum_e f_e P_e

    (1 at uniform routing). Beside :func:`load_balancing_loss`, which is
    one term over every row of the batch and over softmax probabilities.
    ``f`` is a count: the gradient reaches the router through ``P`` alone,
    never through the choice or a selection bias. Unweighted: the model
    multiplies by its coefficient and takes the mean over sequences."""
    T, k = experts.shape[1], experts.shape[2]
    chosen = jax.nn.one_hot(experts, num_experts, dtype=jnp.float32)
    f = jnp.sum(chosen, axis=(1, 2)) * (num_experts / (k * T))
    norm = scores / jnp.sum(scores, axis=-1, keepdims=True)
    return jnp.sum(f * jnp.mean(norm, axis=1), axis=-1)
