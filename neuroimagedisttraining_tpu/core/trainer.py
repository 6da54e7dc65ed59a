"""The local trainer: jitted client-side SGD and evaluation.

Replaces the reference's per-algorithm ``MyModelTrainer`` torch classes
(e.g. fedml_api/standalone/sailentgrads/my_model_trainer.py:201-236 train,
239-274 test) with pure functions designed to be ``vmap``-ed over a leading
client axis and sharded over a TPU mesh:

- ``local_train``: E local epochs of minibatch SGD via ``lax.scan`` —
  BCE/CE loss, global-norm grad clip 10, torch-parity SGD momentum + weight
  decay, per-round lr, optional post-step sparse-mask reapply
  (``param *= mask``, my_model_trainer.py:228-231).
- Per-client *step counts* are preserved under vmap: every client scans the
  same static number of steps, but steps beyond ``ceil(n_i/B)`` per epoch are
  masked no-ops, so small clients do exactly as many updates as the
  reference's DataLoader would give them. A row that runs alone (a
  placement's ``LocalTrainer.rows_alone``) executes those updates and no
  masked one: same state, same loss, same rng stream.
- ``evaluate``: full-cohort chunked eval returning correct/loss/total plus
  raw scores for AUC (metrics dict parity: my_model_trainer.py:245-274).

Data lives on device as padded per-client arrays (uint8 voxels cast raw to
float32, matching my_model_trainer.py:197-198's ``torch.tensor(X_batch,
dtype=float32)`` with no rescale).
"""

from __future__ import annotations

import contextlib
import math
from typing import Any

import flax.struct
import jax
import jax.numpy as jnp

from neuroimagedisttraining_tpu.config import OptimConfig
from neuroimagedisttraining_tpu.core.losses import make_loss, predictions
from neuroimagedisttraining_tpu.core.optim import (
    make_local_optimizer, validate_precision,
)
from neuroimagedisttraining_tpu.models import aux_outputs, primary_logits
from neuroimagedisttraining_tpu.obs import names as obs_names

PyTree = Any
#: an evaluation batch holds at most as many rows as have their tokens fit
#: the budget (32 rows of 640 tokens, the batch every trunk before PR 38
#: evaluated at), and never more than the 32 rows a CNN evaluates at
EVAL_TOKEN_BUDGET = 20480
EVAL_BATCH_MAX = 32


def epoch_permutations(rng: jax.Array, epochs: int, max_samples: int,
                       n_valid) -> jax.Array:
    """[epochs, max_samples] of per-epoch uniform permutations of the
    VALID rows (indices < ``n_valid``) with every padded row sorted last.

    Static-shape analog of the reference DataLoader's per-epoch shuffle
    (my_model_trainer.py:213): sort per-row uniforms, with padded rows
    pinned to a sentinel above the uniform range so positions
    ``[0, n_valid)`` of each row are a uniform permutation of the valid
    indices."""
    keys = jax.random.split(rng, epochs)
    u = jax.vmap(lambda k: jax.random.uniform(k, (max_samples,)))(keys)
    u = jnp.where(jnp.arange(max_samples) < n_valid, u, 2.0)
    return jnp.argsort(u, axis=-1)


def epoch_perms_for(rng: jax.Array, epochs: int, max_samples: int,
                    n_valid) -> jax.Array:
    """The epoch permutations ``local_train`` would derive from ``rng``
    (its ``cs.rng`` at entry) — the hoisted form the cohort-sharded round
    computes OUTSIDE its ``shard_map`` and passes via ``perms=``: the
    argsort-lowered permutation miscompiles inside a shard_map partition
    on this toolchain (see ``local_train``'s docstring and
    parallel/cohort.py). Must mirror local_train's split exactly."""
    _, prng = jax.random.split(rng)
    return epoch_permutations(prng, epochs, max_samples, n_valid)


def scan_steps(epochs: int, batch_size: int, max_samples: int) -> int:
    """The LENGTH of ``local_train``'s step loop for one client row, real
    or padded: ``ceil(max_samples / batch_size)`` iterations an epoch,
    whatever the row holds, because the rng stream and the per-step loss
    vector are laid out over it. It is the work done only where rows are
    batched (``vmap``): there a row with fewer samples computes the
    surplus and a ``where`` discards it, and the round driver's
    ``steps_run`` (obs/names.py SPAN_DISPATCH_PROGRAM) is this times the
    rows. A row that runs alone (:meth:`LocalTrainer.rows_alone`)
    executes its own ``epochs * ceil(n / batch_size)`` of them and no
    other; ``steps_skipped`` counts the rest."""
    return epochs * max(1, math.ceil(max_samples / batch_size))


def shuffle_batch_indices(perms: jax.Array, t, steps_per_epoch: int,
                          batch_size: int, n_valid):
    """Row indices + validity weights for scan step ``t`` when walking the
    per-epoch permutations in ``batch_size`` strides.

    The final batch of an epoch may run past ``n_valid``; those positions
    wrap to the epoch's start so every gathered row is a real sample, and
    their weight is 0 so the loss/grad is the mean over the true partial
    batch — exactly the reference's smaller last DataLoader batch."""
    e = t // steps_per_epoch
    pos = (t % steps_per_epoch) * batch_size + jnp.arange(batch_size)
    idx = perms[e][pos % jnp.maximum(n_valid, 1)]
    w = (pos < n_valid).astype(jnp.float32)  # nidt: allow[precision-upcast] -- loss weights are a blessed f32 loss site (the loss itself is f32 by contract)
    return idx, w


@flax.struct.dataclass
class ClientState:
    """All trainable state of one client; with a leading client axis this is
    the whole federation."""
    params: PyTree
    batch_stats: PyTree
    opt_state: PyTree
    rng: jax.Array


class LocalTrainer:
    """Functional trainer bound to one model + optimizer config."""

    def __init__(self, model, optim: OptimConfig, num_classes: int):
        self.model = model
        self.optim_cfg = optim
        self.num_classes = num_classes
        self.loss = make_loss(num_classes)
        # precision contract (ISSUE 10, core/optim.py): validated here so
        # a bad precision/loss_scale/fused_update combination dies at
        # trainer build, not at first trace. The model's compute dtype is
        # chosen where the model is built (build_experiment passes
        # compute_dtype(optim.precision)); the trainer owns the fixed
        # loss-scale constant — a static multiply of the f32 loss before
        # grad and an f32 divide of the grads after, skipped entirely at
        # scale 1.0 so the default path stays bitwise-unchanged.
        validate_precision(optim)
        self._loss_scale = float(optim.loss_scale)
        self.opt = make_local_optimizer(optim)
        # Full input ndim (batch + spatial + channel) the model expects;
        # drives channel-dim completion in _prep. Declared per model family
        # so a 4-D [B,H,W,C] CIFAR batch is never mistaken for an
        # unchanneled volumetric one.
        self._input_rank = getattr(model, "input_rank", None)
        #: the model returns ``(logits, aux)`` with a weighted auxiliary
        #: loss and integer expert counts (models/olmoe3d.py); the task
        #: loss of such a model gains ``aux["loss"]`` inside the grad
        #: function. A model without the declaration traces exactly the
        #: program it traced before the declaration existed.
        self.has_aux = bool(getattr(model, "returns_aux", False))
        #: the integer entries of that dict (the model's
        #: ``aux_counters``), each summed over a client's real steps
        #: and returned after the loss, in this order
        self.aux_counters = tuple(model.aux_counters) if self.has_aux \
            else ()
        #: set by :meth:`rows_alone` while a placement traces rows
        #: unbatched (and, second, inside a ``shard_map`` partition);
        #: ``local_train`` reads both at trace time
        self._rows_alone = False
        self._partitioned = False

    @contextlib.contextmanager
    def rows_alone(self, partitioned: bool = False):
        """For the duration of a trace, ``local_train`` is applied to one
        client row at a time (``lax.map`` / ``lax.scan`` over clients, a
        ``shard_map`` block's loop, a single folded client) and never
        under a client-axis ``vmap``: its step predicate is then a
        scalar, and the row stops at its own last step. Entered where a
        placement is realised (``RoundCtx.client_map``,
        ``FederatedEngine._cohort_map`` / ``_per_client``), never by a
        ``local_train`` call site. Under ``vmap`` the loop it selects
        would still be right (JAX batches a ``while`` into selects), only
        slower than the batched form.

        ``partitioned``: the rows' loop is a ``shard_map`` block's
        (``FederatedEngine._cohort_map``). A random sort drawn inside a
        partition miscompiles (parallel/cohort.py), so a ``local_train``
        traced there must be handed its hoisted ``perms`` and refuses to
        draw its own batches."""
        was = self._rows_alone, self._partitioned
        self._rows_alone, self._partitioned = True, partitioned
        try:
            yield
        finally:
            self._rows_alone, self._partitioned = was

    # ---------- init ----------

    def init_client_state(self, rng: jax.Array, sample_x: jax.Array) -> ClientState:
        prng, drng, srng = jax.random.split(rng, 3)
        variables = self.model.init({"params": prng, "dropout": drng},
                                    self._prep(sample_x), train=False)
        params = variables["params"]
        bstats = variables.get("batch_stats", {})
        return ClientState(params=params, batch_stats=bstats,
                           opt_state=self.opt.init(params), rng=srng)

    def _prep(self, x: jax.Array) -> jax.Array:
        """uint8 -> float32 raw cast; add trailing channel dim when the input
        is exactly one rank short of the model's declared ``input_rank``
        (reference ``unsqueeze(1)``, my_model_trainer.py:216 — ours is
        channels-last)."""
        with jax.named_scope(obs_names.SCOPE_BATCH_PREP):
            x = x.astype(jnp.float32)  # nidt: allow[precision-upcast] -- reference raw-cast parity (my_model_trainer.py:197-198): the uint8 input-quantization boundary, models re-cast to compute dtype
            if self._input_rank is not None \
                    and x.ndim == self._input_rank - 1:
                x = x[..., None]  # e.g. [B,D,H,W] -> [B,D,H,W,1]
        return x

    def _apply(self, params, batch_stats, x, train: bool, dropout_rng=None):
        variables = {"params": params}
        has_bn = bool(jax.tree.leaves(batch_stats))
        if has_bn:
            variables["batch_stats"] = batch_stats
        rngs = {"dropout": dropout_rng} if (train and dropout_rng is not None) else None
        if train and has_bn:
            out, mut = self.model.apply(variables, x, train=True, rngs=rngs,
                                        mutable=["batch_stats"])
            return out, mut["batch_stats"]
        out = self.model.apply(variables, x, train=train, rngs=rngs)
        return out, batch_stats

    # ---------- training ----------

    def _scaled(self, loss):
        """Loss-scale multiply inside the grad function (bf16_mixed
        static scaling); a literal no-op at the pinned scale 1.0."""
        return loss * self._loss_scale if self._loss_scale != 1.0 else loss

    def _unscaled(self, loss, grads):
        """Invert the loss scale on the f32 loss/grads outside the grad
        function; a literal no-op at scale 1.0 (bitwise-f32 contract)."""
        if self._loss_scale == 1.0:
            return loss, grads
        inv = self._loss_scale
        return loss / inv, jax.tree.map(lambda g: g / inv, grads)

    def _objective(self, out, y, weights=None):
        """``(scaled objective, task loss, aux)`` of one batch's model
        output: the task loss plus the model's own weighted auxiliary
        term (before the loss scale), the task loss alone for the
        report, and the auxiliary dict (None for a logits-only model,
        whose objective IS the scaled task loss)."""
        loss = self.loss(primary_logits(out), y, weights=weights)
        aux = aux_outputs(out) if self.has_aux else None
        if aux is None:
            return self._scaled(loss), loss, None
        return self._scaled(loss + aux["loss"]), loss, aux

    def loss_and_grad(self, cs: ClientState, x, y):
        """One batch's (loss, grads, new batch_stats); used directly by SNIP
        scoring and gradient probes as well as by ``local_train``."""
        rng, drng = jax.random.split(cs.rng)

        def f(params):
            out, bstats = self._apply(params, cs.batch_stats, self._prep(x),
                                      train=True, dropout_rng=drng)
            obj, task, aux = self._objective(out, y)
            return obj, (bstats, task, aux)

        with jax.named_scope(obs_names.SCOPE_FWD_BWD):
            (loss, (bstats, task, aux)), grads = jax.value_and_grad(
                f, has_aux=True)(cs.params)
            loss, grads = self._unscaled(loss, grads)
        # the reported loss stays the task loss (for a logits-only model
        # the objective is the task loss, read as it always was)
        return (loss if aux is None else task), grads, bstats, rng

    def local_train(self, cs: ClientState, X, y, n_valid, lr, epochs: int,
                    batch_size: int, max_samples: int,
                    mask: PyTree | None = None,
                    prox_lamda: float | None = None,
                    prox_ref: PyTree | None = None,
                    perms: jax.Array | None = None):
        """E epochs of local SGD on device-resident (padded) client data.

        Returns ``(new_state, mean_loss)``. ``n_valid`` is the client's true
        sample count; steps beyond its per-epoch quota are masked no-ops so
        vmapped clients keep reference-parity update counts. Inside
        :meth:`rows_alone` (the row is unbatched: its predicate is a
        scalar) the surplus iterations are not executed at all, with the
        same trained state, mean loss, ``expert_tokens`` and ``cs.rng``.

        Batch selection follows ``optim.batch_order``: ``"shuffle"``
        (default) walks a fresh per-epoch permutation in ``batch_size``
        strides with a weighted partial final batch — the reference
        DataLoader's semantics (my_model_trainer.py:213) under static
        shapes. Loss and gradients of the partial batch are EXACTLY the
        reference's smaller-batch mean (torch-pinned in
        tests/test_torch_parity.py); the one residual deviation is
        BatchNorm models, whose partial-batch activation statistics see
        the wrapped filler rows (real samples, zero loss weight) that a
        genuinely smaller torch batch would not contain.
        ``"replacement"`` draws i.i.d. uniform batches.

        A model that declares an auxiliary output (``has_aux``) trains on
        task loss + ``aux["loss"]``; the returned mean loss stays the task
        loss, and such a model's call returns its ``aux_counters`` after
        it (``expert_tokens``; Nemotron-H's ``held_overflow_calls``
        too), each summed over the client's REAL steps (masked padded
        steps add nothing).

        ``prox_lamda``/``prox_ref``: Ditto's personalized proximal pull,
        applied after each optimizer step: ``w -= lr * lamda * (w - ref)``
        (ditto/my_model_trainer.py:63-64).

        ``perms``: precomputed epoch permutations (what
        :func:`epoch_perms_for` derives from the SAME ``cs.rng``) — the
        cohort-sharded round (parallel/cohort.py) computes them OUTSIDE
        its ``shard_map`` and passes them in, because the argsort-based
        permutation lowering MISCOMPILES inside a shard_map partition on
        this toolchain (jax 0.4.x CPU SPMD: the consumed permutation
        silently differs from the observable one — caught by the cohort
        bitwise pins). The rng stream is identical either way: the split
        that would have fed the permutation is still consumed.
        """
        if self._partitioned and perms is None:
            raise ValueError(
                "local_train traced inside a shard_map partition without "
                "hoisted permutations: its batch draws (an argsort-"
                "lowered permutation, or per-step randint) miscompile "
                "there (parallel/cohort.py) — hoist them "
                "(engines/program.py hoisted_epoch_perms) and pass "
                "perms=")
        total = scan_steps(epochs, batch_size, max_samples)
        steps_per_epoch = total // epochs
        my_steps = jnp.ceil(n_valid / batch_size).astype(jnp.int32)
        shuffle = self.optim_cfg.batch_order == "shuffle"
        if shuffle:
            # reference DataLoader semantics: each epoch walks a fresh
            # permutation of the client's rows in batch_size strides
            rng0, prng = jax.random.split(cs.rng)
            cs = cs.replace(rng=rng0)
            if perms is None:
                perms = epoch_permutations(prng, epochs, max_samples,
                                           n_valid)

        def advance(state, t, brng, drng):
            """Iteration ``t`` computed: its batch, forward, backward and
            the optimizer tail. ``(params, batch_stats, opt_state, out)``
            with ``out`` the step's loss, or ``(loss, *counters)`` of a
            model with an auxiliary output."""
            with jax.named_scope(obs_names.SCOPE_BATCH_PREP):
                if shuffle:
                    idx, wb = shuffle_batch_indices(
                        perms, t, steps_per_epoch, batch_size, n_valid)
                else:
                    idx = jax.random.randint(brng, (batch_size,), 0,
                                             jnp.maximum(n_valid, 1))
                    wb = None
                xb = jnp.take(X, idx, axis=0)
                yb = jnp.take(y, idx, axis=0)

            def f(params):
                out, bstats = self._apply(params, state.batch_stats,
                                          self._prep(xb), train=True,
                                          dropout_rng=drng)
                obj, task, aux = self._objective(out, yb, weights=wb)
                return obj, (bstats, task, aux)

            with jax.named_scope(obs_names.SCOPE_FWD_BWD):
                (loss, (bstats, task, aux)), grads = jax.value_and_grad(
                    f, has_aux=True)(state.params)
                loss, grads = self._unscaled(loss, grads)
                if aux is not None:
                    loss = task
            # the optimizer tail carries one name on both paths (the
            # global-norm clip inside it is SCOPE_CLIP: core/optim.py,
            # ops/fused_update.py), so a trace reads it fused or not
            with jax.named_scope(obs_names.SCOPE_UPDATE):
                if self.opt.fused_apply is not None:
                    # fused clip+wd+momentum+update+mask tail in one pass
                    # (ops/fused_update.py; bit-parity with the chain
                    # below)
                    params, opt_state = self.opt.fused_apply(
                        grads, state.opt_state, state.params, lr, mask)
                else:
                    updates, opt_state = self.opt.update(
                        grads, state.opt_state, state.params, lr)
                    params = jax.tree.map(jnp.add, state.params, updates)
                    if mask is not None:
                        with jax.named_scope(obs_names.SCOPE_MASK_APPLY):
                            params = jax.tree.map(jnp.multiply, params,
                                                  mask)
                if prox_lamda is not None:
                    params = jax.tree.map(
                        lambda w, ref: w - lr * prox_lamda * (w - ref),
                        params, prox_ref)
            out = (loss, *(aux[name] for name in self.aux_counters)) \
                if self.has_aux else loss
            return params, bstats, opt_state, out

        def step(carry, t):
            # rows batched over a client axis: every row computes every
            # iteration, and a surplus one keeps its old state
            state = carry
            rng, brng, drng = jax.random.split(state.rng, 3)
            params, bstats, opt_state, out = advance(state, t, brng, drng)
            with jax.named_scope(obs_names.SCOPE_UPDATE):
                active = (t % steps_per_epoch) < my_steps

                def keep(new, old):
                    return jax.tree.map(
                        lambda a, b: jnp.where(active, a, b), new, old)

                new_state = ClientState(
                    params=keep(params, state.params),
                    batch_stats=keep(bstats, state.batch_stats),
                    opt_state=keep(opt_state, state.opt_state),
                    rng=rng)
                if not self.has_aux:
                    return new_state, jnp.where(active, out, 0.0)
                loss, *counters = out
                return new_state, (
                    jnp.where(active, loss, 0.0),
                    *(jnp.where(active, c, jnp.zeros_like(c))
                      for c in counters))

        def real_steps(cs):
            """The row runs alone: its ``epochs * my_steps`` real
            iterations in a loop to that traced bound, each writing its
            state with nothing selected against the old one; a surplus
            iteration runs nothing. The keys are today's stream, split
            ahead for the whole loop length (a skipped iteration's
            split is still consumed: ``RoundCtx.rng_after_local_train``
            replays it and the trained ``cs.rng`` is an output), and
            each iteration's ``out`` lands at its own ``t`` of a
            zero-filled ``[total]`` vector, so the sums below add what
            the batched form adds."""

            def keys_of(rng, _):
                rng, brng, drng = jax.random.split(rng, 3)
                return rng, (brng, drng)

            rng_end, (brngs, drngs) = jax.lax.scan(keys_of, cs.rng, None,
                                                   length=total)
            out0 = jax.eval_shape(
                lambda: advance(cs, 0, brngs[0], drngs[0])[3])
            outs0 = jax.tree.map(
                lambda o: jnp.zeros((total, *o.shape), o.dtype), out0)

            per = jnp.maximum(my_steps, 1)  # the loop is empty at 0

            def body(j, carry):
                state, outs = carry
                t = (j // per) * steps_per_epoch + j % per
                params, bstats, opt_state, out = advance(
                    state, t, brngs[t], drngs[t])
                state = ClientState(params=params, batch_stats=bstats,
                                    opt_state=opt_state, rng=state.rng)
                return state, jax.tree.map(
                    lambda v, o: v.at[t].set(o), outs, out)

            cs, outs = jax.lax.fori_loop(0, epochs * my_steps, body,
                                         (cs, outs0))
            return cs.replace(rng=rng_end), outs

        if self._rows_alone:
            cs, outs = real_steps(cs)
        else:
            cs, outs = jax.lax.scan(step, cs, jnp.arange(total))
        denom = jnp.maximum(epochs * my_steps, 1)
        if not self.has_aux:
            return cs, jnp.sum(outs) / denom
        losses, *counters = outs
        return (cs, jnp.sum(losses) / denom,
                *(jnp.sum(c, axis=0) for c in counters))

    def lower_train_step(self, input_shape: tuple[int, ...],
                         batch_size: int):
        """AOT-lower ONE training step (``loss_and_grad``: forward +
        backward + BN update) at fully ABSTRACT shapes — params come
        from an ``eval_shape`` of the model init, the batch is a
        ``ShapeDtypeStruct``, so nothing is materialized, compiled or
        executed even at the flagship 121x145x121 volume on the CPU
        harness. The returned ``jax.stages.Lowered`` is the XLA
        accounting surface: ``cost_analysis()`` reads FLOPs off the
        unoptimized HLO, ``.compile().memory_analysis()`` adds the
        temp/argument byte accounting (obs/compute.analyze_train_step
        reconciles both against the analytic ops/flops.py counter)."""
        x1 = jax.ShapeDtypeStruct((1, *input_shape), jnp.float32)
        cs = jax.eval_shape(self.init_client_state, jax.random.key(0),
                            x1)
        xs = jax.ShapeDtypeStruct((batch_size, *input_shape),
                                  jnp.float32)
        ys = jax.ShapeDtypeStruct((batch_size,), jnp.int32)

        def step(cs, x, y):
            loss, grads, bstats, _ = self.loss_and_grad(cs, x, y)
            return loss, grads, bstats

        return jax.jit(step).lower(cs, xs, ys)

    def eval_grad(self, params: PyTree, batch_stats: PyTree, x, y) -> PyTree:
        """One-batch DENSE gradient probe in eval mode (no dropout, BN in
        inference mode) — DisPFL's ``screen_gradients``
        (DisPFL/my_model_trainer.py:165-188, model.eval() + one batch)."""
        def f(p):
            out, _ = self._apply(p, batch_stats, self._prep(x), train=False)
            return self._objective(out, y)[0]

        grads = jax.grad(f)(params)
        if self._loss_scale != 1.0:
            grads = jax.tree.map(lambda g: g / self._loss_scale, grads)
        return grads

    # ---------- evaluation ----------

    def eval_batch_rows(self, row_shape) -> int:
        """The most rows an evaluation batch may hold for rows of
        ``row_shape`` (``[D, H, W, ...]``): as many as fit the token
        budget the trunks evaluate at, never more than
        ``EVAL_BATCH_MAX``. A model whose rows cost more than the
        budget's 32nd part says what a row costs (``row_tokens``:
        models/evabyte3d.py); any other (a CNN, a 640-token trunk) gets
        ``EVAL_BATCH_MAX``."""
        row_tokens = getattr(self.model, "row_tokens", None)
        tokens = row_tokens(row_shape) if row_tokens is not None else 1
        return min(EVAL_BATCH_MAX, max(1, EVAL_TOKEN_BUDGET // tokens))

    def eval_batches(self, row_shape, rows: int) -> tuple[int, int]:
        """``(batches, batch)`` that :meth:`evaluate` walks for ``rows``
        rows of ``row_shape``: the fewest batches :meth:`eval_batch_rows`
        allows, at the one width that tiles the rows with the least
        filler (2 rows under a cap of 4 run as 1 x 2, 44 under 32 as
        2 x 22: at most ``batches - 1`` filler rows, none where the rows
        tile the cap). The engines' ``rows_run`` counter is computed from
        it too (engines/base.py ``_eval_span_args``)."""
        batches = max(1, -(-rows // self.eval_batch_rows(row_shape)))
        return batches, max(1, -(-rows // batches))

    @jax.named_scope(obs_names.SCOPE_EVAL)
    def evaluate(self, params, batch_stats, X, y, valid,
                 batch_size: int | None = None):
        """Chunked full-set eval: ``batch_size`` rows a batch where one
        is given, else the balanced width of :meth:`eval_batches` for the
        static row count of ``X``. Filler rows are zero volumes that
        ``valid`` masks out of the sums. Returns dict with
        ``test_correct``, ``test_loss`` (sum), ``test_total`` and raw
        ``scores`` for AUC."""
        n = X.shape[0]
        if batch_size is None:
            nb, batch_size = self.eval_batches(X.shape[1:], n)
        else:
            nb = max(1, math.ceil(n / batch_size))
        pad = nb * batch_size - n
        Xp = jnp.pad(X, [(0, pad)] + [(0, 0)] * (X.ndim - 1))
        yp = jnp.pad(y, (0, pad))
        vp = jnp.pad(valid.astype(jnp.float32), (0, pad))

        def chunk(_, i):
            sl = lambda a: jax.lax.dynamic_slice_in_dim(a, i * batch_size,
                                                        batch_size, 0)
            xb, yb, vb = sl(Xp), sl(yp), sl(vp)
            out, _ = self._apply(params, batch_stats, self._prep(xb),
                                 train=False)
            logits = primary_logits(out)
            preds = predictions(logits, self.num_classes)
            correct = jnp.sum((preds == yb.astype(jnp.int32)) * vb)
            loss = self.loss(logits, yb, weights=vb) * jnp.sum(vb)
            score = (logits.reshape(batch_size, -1)[:, 0]
                     if self.num_classes == 1
                     else jax.nn.log_softmax(logits)[:, -1])
            return None, (correct, loss, jnp.sum(vb), score)

        _, (corrects, losses, totals, scores) = jax.lax.scan(
            chunk, None, jnp.arange(nb))
        return {
            "test_correct": jnp.sum(corrects),
            "test_loss": jnp.sum(losses),
            "test_total": jnp.sum(totals),
            "scores": scores.reshape(-1)[:n],
        }
