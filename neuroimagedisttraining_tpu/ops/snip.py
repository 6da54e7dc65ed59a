"""SNIP saliency scoring + global mask construction (SalientGrads core).

The reference computes per-weight saliency by monkey-patching every
Conv3d/Linear with a multiplicative ``weight_mask`` parameter and taking
``|dL/d mask|`` at mask=1 (snip.py:21-74). Since the patched forward is
``conv(x, w * mask)``, the chain rule gives ``dL/d mask = w ⊙ dL/d(w*mask)``,
so at mask=1 the score is exactly ``|w ⊙ grad_w L|`` — one ``jax.grad``
call, no model surgery.

Mask construction (snip.py:80-116): concat+normalize all scores by their
global sum, threshold at the k-th largest normalized score
(k = keep_ratio * total), binary masks for conv/linear kernels, ones for
everything else. The k-th value comes from the Pallas histogram-select
kernel (ops/topk.py).

Cross-client averaging (snip.py:120-140 ``get_mean_snip_scores``) is a plain
mean over the stacked client axis — under the mesh this is one ICI
all-reduce.
"""

from __future__ import annotations

from typing import TYPE_CHECKING, Any

import jax
import jax.numpy as jnp
import numpy as np

from neuroimagedisttraining_tpu.obs import names as obs_names
from neuroimagedisttraining_tpu.ops.masks import is_weight_kernel
from neuroimagedisttraining_tpu.ops.topk import kth_largest
from neuroimagedisttraining_tpu.utils.pytree import (
    tree_by_name as _get,
    tree_map_with_path_names,
)

if TYPE_CHECKING:  # annotations only: ops/ imports nothing above it
    from neuroimagedisttraining_tpu.core.trainer import (
        ClientState, LocalTrainer,
    )

PyTree = Any


def snip_scores(trainer: LocalTrainer, cs: ClientState, x: jax.Array,
                y: jax.Array) -> PyTree:
    """|w ⊙ grad_w L| on one minibatch, zeros for non-maskable leaves."""
    _, grads, _, _ = trainer.loss_and_grad(cs, x, y)
    return tree_map_with_path_names(
        lambda name, g: jnp.abs(_get(cs.params, name) * g)
        if is_weight_kernel(name, g) else jnp.zeros_like(g),
        grads)


def _stratified_indices(rng: jax.Array, y: jax.Array, n_valid,
                        batch_size: int) -> jax.Array:
    """Label-balanced batch draw: each class contributes with equal expected
    frequency — the intent of the reference's StratifiedKFold batch sampler
    for IterSNIP (client.py:36-46), expressed as weighted sampling so it jits
    with static shapes."""
    valid = jnp.arange(y.shape[0]) < n_valid
    # per-sample weight = 1 / (count of its own label among valid samples),
    # computed via an equality matrix so it works for any label set without
    # a static class count (clients hold <= a few thousand samples, so the
    # O(n^2) compare is negligible)
    eq = (y[None, :] == y[:, None]) & valid[None, :]
    cnt = jnp.sum(eq, axis=1)
    w = jnp.where(valid, 1.0 / jnp.maximum(cnt, 1), 0.0)
    p = w / jnp.maximum(jnp.sum(w), 1e-12)
    return jax.random.choice(rng, y.shape[0], (batch_size,), replace=True,
                             p=p)


def iter_snip_batch_indices(rng: jax.Array, iterations: int,
                            batch_size: int, n_valid) -> jax.Array:
    """[iterations, batch_size] of the batch indices ``iter_snip_scores``
    would draw from ``rng`` (its ``cs.rng``) — the hoisted form the
    cohort-sharded phase-1 computes OUTSIDE its ``shard_map`` and passes
    via ``idx_stack=``: in-partition RNG draws consumed by a scan are
    the SPMD miscompile class measured on jax 0.4.x the round's perms hoist
    exists for (parallel/cohort.py). Must mirror ``one_iter``'s splits
    exactly."""
    rngs = jax.random.split(rng, iterations)

    def one(r):
        brng, _ = jax.random.split(r)
        return jax.random.randint(brng, (batch_size,), 0,
                                  jnp.maximum(n_valid, 1))

    return jax.vmap(one)(rngs)


def iter_snip_scores(trainer: LocalTrainer, cs: ClientState, X: jax.Array,
                     y: jax.Array, n_valid, iterations: int,
                     batch_size: int, stratified: bool = False,
                     idx_stack: jax.Array | None = None) -> PyTree:
    """IterSNIP: mean saliency over ``iterations`` minibatches
    (client.py:30-53 + snip.py:143-164). Batches are drawn uniformly from
    the client's valid range, or label-balanced when ``stratified``
    (reference ``stratified_sampling`` flag). ``idx_stack``: precomputed
    batch indices (:func:`iter_snip_batch_indices`, cohort-sharded
    phase-1) — the dropout rng stream is identical either way (the split
    that would feed the draw is still consumed)."""
    def one_iter(carry, xs):
        if idx_stack is None:
            brng, srng = jax.random.split(xs)
            if stratified:
                idx = _stratified_indices(brng, y, n_valid, batch_size)
            else:
                idx = jax.random.randint(brng, (batch_size,), 0,
                                         jnp.maximum(n_valid, 1))
        else:
            rng, idx = xs
            _, srng = jax.random.split(rng)
        # fresh dropout rng per iteration so IterSNIP iterations don't share
        # one dropout mask
        s = snip_scores(trainer, cs.replace(rng=srng),
                        jnp.take(X, idx, axis=0), jnp.take(y, idx, axis=0))
        return jax.tree.map(jnp.add, carry, s), None

    zero = jax.tree.map(jnp.zeros_like, cs.params)
    rngs = jax.random.split(cs.rng, iterations)
    xs = rngs if idx_stack is None else (rngs, idx_stack)
    total, _ = jax.lax.scan(one_iter, zero, xs)
    return jax.tree.map(lambda t: t / iterations, total)


def mean_scores(stacked_scores: PyTree) -> PyTree:
    """Server-side mean of per-client score pytrees (snip.py:120-140); with a
    client-sharded leading axis this lowers to an all-reduce."""
    return jax.tree.map(lambda s: jnp.mean(s, axis=0), stacked_scores)


def flat_weight_scores(scores: PyTree) -> jax.Array:
    """The maskable (weight-kernel) leaves of a score pytree as ONE flat
    vector in tree order — the global cross-layer ranking's input."""
    flat_parts = []

    def collect(name, s):
        if is_weight_kernel(name, s):
            flat_parts.append(s.reshape(-1))
        return s

    tree_map_with_path_names(collect, scores)
    return jnp.concatenate(flat_parts)


def _on_one_device(x: jax.Array) -> jax.Array:
    """``x`` on a single device. The global top-k is one serial selection
    over a ~10 MB vector: nothing to partition, and its Pallas counting
    kernel cannot lower in a program that spans several devices (jax:
    "Mosaic kernels cannot be automatically partitioned")."""
    if x.is_fully_replicated:  # one device, or a copy on each
        return x.addressable_data(0)
    return jax.device_put(x, x.addressable_shards[0].device)


@jax.named_scope(obs_names.SCOPE_TOPK_MASK)
def mask_from_scores(scores: PyTree, keep_ratio: float) -> tuple[PyTree, jax.Array]:
    """Normalize scores by global sum, keep the top ``keep_ratio`` fraction
    globally (cross-layer), ones for non-maskable leaves (snip.py:80-116)."""
    all_scores = flat_weight_scores(scores)
    total_elems = all_scores.size
    norm = jnp.sum(all_scores)
    # count non-finite entries on the RAW scores: after the /norm below a
    # single NaN poisons every element and the count would read as "all"
    bad = jnp.sum(~jnp.isfinite(all_scores))
    all_scores = all_scores / norm
    k = max(1, int(total_elems * keep_ratio))
    threshold = kth_largest(_on_one_device(all_scores), k)
    # Fail LOUDLY on non-finite saliency (e.g. one client's phase-1 loss
    # diverged): the histogram top-k would otherwise return a garbage
    # threshold and the run would continue with a silently-wrong global
    # mask. (The reference would crash inside torch.topk; silence is
    # worse.) This runs eagerly — generate_global_mask calls it outside
    # jit — and the three diagnostics sync in ONE batched device fetch
    # (ISSUE 4 / VERDICT r5 #5): the old per-check bool()/int() pulls
    # were 3-5 separate host syncs back to back, each blocking on the
    # full score pipeline; all quantities are computed
    # first (garbage-tolerant — a non-finite norm just yields a
    # non-finite threshold we are about to refuse) and fetched together.
    norm_h, bad_h, thr_h = jax.device_get((norm, bad, threshold))
    if not np.isfinite(norm_h):
        raise FloatingPointError(
            f"SNIP saliency scores contain {int(bad_h)} non-finite "
            "entries (or their sum overflows): refusing to build the "
            "global mask. Check the phase-1 loss of each client for "
            "divergence.")
    if norm_h == 0:
        # all-zero saliency (e.g. dead activations or a zero-initialized
        # head): normalizing would give 0/0 = NaN everywhere — distinct
        # failure, distinct diagnostic
        raise FloatingPointError(
            "SNIP saliency scores are identically zero: no signal to rank "
            "— the phase-1 gradient probe produced zero gradients for "
            "every maskable weight (dead activations? zero init?).")
    if not np.isfinite(thr_h):
        raise FloatingPointError(
            f"global top-k threshold is non-finite ({int(bad_h)} "
            "non-finite raw saliency scores): refusing to build "
            "the global mask. Check the phase-1 loss of each client for "
            "divergence.")

    # the fetched f32 scalar, not the one-device array: the scores may
    # live on a whole mesh, and an uncommitted scalar joins either
    threshold = jnp.float32(thr_h)

    def build(name, s):
        if is_weight_kernel(name, s):
            return ((s / norm) >= threshold).astype(jnp.float32)
        return jnp.ones_like(s)

    return tree_map_with_path_names(build, scores), threshold

