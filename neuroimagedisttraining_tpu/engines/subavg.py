"""Sub-FedAvg: per-client iterative magnitude pruning with an accept-test,
mask-overlap-count averaging (fedml_api/standalone/subavg/).

Behavior parity (subavg_api.py:43-92, subavg/client.py:36-64,
subavg/my_model_trainer.py:48-82):

- Initial masks are all-ones (my_model_trainer.py:28-40); every client
  maintains a personal mask that only ever loses entries.
- Per round, sampled clients receive ``w_global * mask_c`` and train with
  masked gradients (``param.grad *= mask``, my_model_trainer.py:66-68; with
  pruned weights starting at zero this equals our post-step re-mask).
- Prune candidates: ``fake_prune`` percentile masks computed after the FIRST
  epoch (m1) and after the LAST epoch (m2) (my_model_trainer.py:76-79);
  with epochs==1, m1 == m2 and pruning never triggers — reference parity.
- Accept-test (client.py:50-58): prune only if
  (a) hamming-fraction(m1, m2) > ``dist_thresh``,
  (b) pre-train density of the client model > ``dense_ratio`` (floor), and
  (c) accuracy of the m2-pruned trained model on the client's TRAINING data
      (local_test(..., False)) > ``acc_thresh``.
  On accept: weights *= m2 and the personal mask becomes m2.
- Aggregation (subavg_api.py:123-140): per weight, ``count`` = number of
  sampled clients whose OLD mask keeps it; server value becomes
  ``sum_i w_i / count`` where count > 0, and keeps its previous value where
  no sampled client keeps the weight (the reference's non-finite guard).
- Personalized model of client c = ``w_global * mask_c``
  (_local_test_on_all_clients, subavg_api.py:150-170).

The round is DECLARED through the round-program builder
(engines/program.py, ISSUE 11): the per-client prune/accept composite is
the train stage, the overlap-count average is a CUSTOM aggregate stage
(it replaces the weighted mean — order-statistic defenses have nothing
to select over a count-quotient), and the personal-mask scatter is the
update stage. The builder supplies ``--client_mesh`` cohort sharding of
the per-client composite — the
two-call epoch split hoists BOTH calls' permutations out of the
partition (ctx.rng_after_local_train replays the rng chain).
"""

from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
import numpy as np

from neuroimagedisttraining_tpu.core.losses import binary_auc
from neuroimagedisttraining_tpu.core.trainer import ClientState
from neuroimagedisttraining_tpu.engines import program as round_program
from neuroimagedisttraining_tpu.engines.base import FederatedEngine
from neuroimagedisttraining_tpu.obs import health as obs_health
from neuroimagedisttraining_tpu.obs import names as obs_names
from neuroimagedisttraining_tpu.ops import flops as flops_ops
from neuroimagedisttraining_tpu.ops import prune as P
from neuroimagedisttraining_tpu.ops.masks import ones_mask
from neuroimagedisttraining_tpu.utils import pytree as pt


class SubFedAvgEngine(FederatedEngine):
    name = "subavg"
    # Streaming (cohort > HBM): the round only consumes the SAMPLED clients'
    # data shards (same shape as FedAvg's streaming round); per-client masks
    # and the global model stay device-resident.
    supports_streaming = True
    supports_cohort_sharding = True  # the per-client prune/accept
    # composite runs as unbatched loops under the --client_mesh shard_map
    #: current per-client personal masks, tracked for the codec handoff
    _mask_pers = None

    def wire_masks(self):
        """Mask handoff (codec/): the per-client personal masks, stacked
        [C, ...]. They evolve by pruning (monotone entry loss) on
        accepted rounds, so a cross-silo deployment ships the bitmap
        frame with the surviving values (as DisPFL)."""
        return self._mask_pers

    # ---------- the declared round (engines/program.py) ----------

    def round_stages(self):
        return round_program.RoundStages(
            carry=("params", "batch_stats", "mask_pers"),
            train=self._train_stage,
            aggregate=self._aggregate_stage,
            update=self._update_stage,
            outputs=("loss", "mean_dist", "n_accept", "up_nnz"),
            health=self._health_stage,
            health_outputs=obs_health.MASK_STAT_NAMES,
        )

    def _health_stage(self, ctx, tr, new_carry) -> dict:
        """Mask-health leg (ISSUE 15, armed under ``--health_stats``):
        density of the sampled cohort's ACCEPTED masks plus their
        round-over-round overlap/churn vs the masks the cohort entered
        the round with — the in-dispatch mirror of
        ``warn_if_masks_collapsed``'s post-hoc nnz fetch."""
        return round_program.mask_health_stats(tr.extra["new_m"],
                                               tr.extra["Ms"])

    def _train_stage(self, ctx) -> round_program.TrainOut:
        """The per-client composite: masked epoch-1 train -> fake_prune
        m1 -> masked tail epochs -> fake_prune m2 -> accept-test. On the
        sharded path both ``local_train`` calls' epoch permutations are
        hoisted out of the partition: the tail call's entry rngs are the
        chain ``local_train`` leaves after epoch 1, replayed outside the
        shard_map (ctx.rng_after_local_train)."""
        trainer = self.trainer
        o = self.cfg.optim
        s = self.cfg.sparsity
        params = ctx.carry["params"]
        bstats = ctx.carry["batch_stats"]
        Xs, ys, ns = ctx.Xs, ctx.ys, ctx.ns
        lr = ctx.lr
        max_samples = self._max_samples()
        epochs_tail = max(o.epochs - 1, 0)
        Ms = pt.tree_stack_index(ctx.carry["mask_pers"], ctx.sampled_idx)

        def per_client(m, rng, Xc, yc, nc, perms1_c=None, perms2_c=None):
            w_per = jax.tree.map(jnp.multiply, params, m)
            dense = P.density_all_leaves(w_per)
            cs_c = ClientState(params=w_per, batch_stats=bstats,
                               opt_state=trainer.opt.init(w_per),
                               rng=rng)
            # epoch 1, then fake_prune -> m1
            cs_c, loss1 = trainer.local_train(
                cs_c, Xc, yc, nc, lr, epochs=1, batch_size=o.batch_size,
                max_samples=max_samples, mask=m, perms=perms1_c)
            m1 = P.fake_prune(s.each_prune_ratio, cs_c.params, m)
            # remaining epochs, then fake_prune -> m2
            if epochs_tail:
                cs_c, loss2 = trainer.local_train(
                    cs_c, Xc, yc, nc, lr, epochs=epochs_tail,
                    batch_size=o.batch_size, max_samples=max_samples,
                    mask=m, perms=perms2_c)
                loss = (loss1 + epochs_tail * loss2) / o.epochs
            else:
                loss = loss1
            m2 = P.fake_prune(s.each_prune_ratio, cs_c.params, m)
            dist = P.mask_distance_mean(m1, m2)

            # accept-test: acc of the m2-pruned model on TRAIN data
            pruned = jax.tree.map(jnp.multiply, cs_c.params, m2)
            valid = jnp.arange(Xc.shape[0]) < nc
            metrics = trainer.evaluate(pruned, cs_c.batch_stats, Xc, yc,
                                       valid)
            acc = metrics["test_correct"] / jnp.maximum(
                metrics["test_total"], 1.0)
            accept = ((dist > s.dist_thresh)
                      & (dense > s.dense_ratio)
                      & (acc > s.acc_thresh))
            sel = lambda a, b: jax.tree.map(
                lambda x, y: jnp.where(accept, x, y), a, b)
            new_params = sel(pruned, cs_c.params)
            new_mask = sel(m2, m)
            return (new_params, cs_c.batch_stats, new_mask, loss, dist,
                    accept)

        hoisted = [lambda: ctx.local_perms(ctx.rngs, ns, 1)]
        if epochs_tail:
            hoisted.append(lambda: ctx.local_perms(
                ctx.rng_after_local_train(ctx.rngs, 1), ns, epochs_tail))
        (new_p, new_b, new_m, losses, dists, accepts) = ctx.client_map(
            per_client, Ms, ctx.rngs, Xs, ys, ns, hoisted=tuple(hoisted))
        return round_program.TrainOut(
            losses=losses,
            upload={"params": new_p, "batch_stats": new_b},
            extra={"Ms": Ms, "new_m": new_m, "dists": dists,
                   "accepts": accepts})

    def _aggregate_stage(self, ctx, upload, w, tr):
        """Overlap-count aggregation against the OLD masks
        (subavg_api.py:123-140) — a custom aggregate stage: per weight,
        ``count`` = sampled clients whose old mask keeps it, server
        value = sum/count where count > 0, previous value elsewhere.
        Mesh-tiling pad entries (ns == 0, possibly duplicate ids from
        stream_sampling) contribute nothing."""
        params = ctx.carry["params"]
        Ms, new_m = tr.extra["Ms"], tr.extra["new_m"]
        new_p, new_b = upload["params"], upload["batch_stats"]
        real = (ctx.ns > 0).astype(jnp.float32)
        rb = lambda x: real.reshape((-1,) + (1,) * (x.ndim - 1))
        count = jax.tree.map(lambda m: jnp.sum(m * rb(m), axis=0), Ms)
        summed = jax.tree.map(
            lambda p: jnp.sum(p.astype(jnp.float32) * rb(p), axis=0),
            new_p)
        agg = jax.tree.map(
            lambda sm, ct, old: jnp.where(ct > 0, sm
                                          / jnp.maximum(ct, 1.0), old),
            summed, count, params)
        n_real = jnp.maximum(jnp.sum(real), 1.0)
        new_bstats = jax.tree.map(
            lambda b: jnp.sum(b.astype(jnp.float32) * rb(b), axis=0)
            / n_real, new_b)
        mean_loss = jnp.sum(tr.losses * real) / n_real
        # per-sampled-client nnz of the NEW masks: the true uplink volume
        # (reference nonzero-comm metric, model_trainer.py:49-53)
        up_nnz = jax.vmap(lambda m: sum(
            jnp.sum(x) for x in jax.tree.leaves(m)))(new_m)
        return ({"params": agg, "batch_stats": new_bstats},
                {"loss": mean_loss,
                 "mean_dist": jnp.sum(tr.extra["dists"] * real) / n_real,
                 "n_accept": jnp.sum(tr.extra["accepts"] * real),
                 "up_nnz": jnp.sum(up_nnz * real)})

    def _update_stage(self, ctx, tr, new_carry) -> dict:
        """Scatter updated personal masks back; pad entries are dropped,
        never written (base.scatter_sampled_rows)."""
        mask_pers = self.scatter_sampled_rows(
            ctx.carry["mask_pers"], tr.extra["new_m"], ctx.sampled_idx,
            ctx.ns > 0)
        return {"mask_pers": mask_pers}

    # ---------- legacy-signature program adapters ----------

    @functools.cached_property
    def _round_jit(self):
        prog = self.program.round_jit()

        def round_call(params, bstats, mask_pers, data, sampled_idx,
                       rngs, lr):
            return prog((params, bstats, mask_pers), data, (),
                        sampled_idx, rngs, lr)

        return round_call

    def _sharded_round_jit(self, n_real: int):
        prog = self.program.round_jit(n_real=n_real)

        def sharded_round_call(params, bstats, mask_pers, data,
                               sampled_idx, rngs, lr, deal=None):
            return prog((params, bstats, mask_pers), data, (),
                        sampled_idx, rngs, lr, None, None, None, deal)

        return sharded_round_call

    @functools.cached_property
    def _round_stream_jit(self):
        prog = self.program.stream_jit()

        def stream_round_call(params, bstats, mask_pers, Xs, ys, ns,
                              sampled_idx, rngs, lr):
            return prog((params, bstats, mask_pers), (), Xs, ys, ns,
                        sampled_idx, rngs, lr)

        return stream_round_call

    # ---------- personalized (masked-global) evaluation ----------

    @functools.cached_property
    def _eval_masked_global_jit(self):
        """Personalized eval: client c evaluates w_global * mask_c
        (subavg_api.py:150-170)."""
        trainer = self.trainer

        def eval_all(params, bstats, mask_pers, X, y, n):
            def per_client(m, Xc, yc, nc):
                p = jax.tree.map(jnp.multiply, params, m)
                valid = jnp.arange(Xc.shape[0]) < nc
                mt = trainer.evaluate(p, bstats, Xc, yc, valid)
                auc = binary_auc(mt["scores"], yc, valid)
                return mt["test_correct"], mt["test_loss"], mt["test_total"], auc

            with jax.named_scope(obs_names.SCOPE_EVAL):
                return self._per_client(per_client, mask_pers, X, y, n)

        return jax.jit(eval_all)

    def eval_masked_global(self, params, bstats, mask_pers) -> dict:
        if self.stream is not None:
            return self.eval_masked_global_stream(params, bstats, mask_pers)
        X, y, n = self.data.X_test, self.data.y_test, self.data.n_test
        if self.cfg.fed.ci:
            X, y, n = X[:1], y[:1], n[:1]
            mask_pers = pt.tree_stack_index(mask_pers, slice(0, 1))
        out = self._eval_masked_global_jit(params, bstats, mask_pers, X, y, n)
        return self._summarize(*out, n=n)

    def eval_masked_global_stream(self, params, bstats, mask_pers) -> dict:
        """Streamed variant: test shards arrive in client chunks; each
        chunk's personal masks are gathered from the resident stack."""
        chunk = self._eval_chunk_size()
        parts, ns = [], []
        for ch in self.stream.eval_chunks(chunk, "test"):
            m = pt.tree_stack_index(mask_pers, ch.padded_ids)
            out = self._eval_masked_global_jit(params, bstats, m, ch.X,
                                               ch.y, ch.n)
            parts.append(tuple(np.asarray(o)[: len(ch.ids)] for o in out))
            ns.append(np.asarray(jax.device_get(ch.n))[: len(ch.ids)])
            if self.cfg.fed.ci:
                break
        cat = [np.concatenate([p[i] for p in parts]) for i in range(4)]
        n_all = np.concatenate(ns)
        if self.cfg.fed.ci:
            cat, n_all = [c[:1] for c in cat], n_all[:1]
        return self._summarize(*cat, n=n_all)

    # ---------- driver ----------

    def train(self):
        cfg = self.cfg
        gs = self.init_global_state()
        params, bstats = gs.params, gs.batch_stats
        mask_pers = self.broadcast_states(ones_mask(params),
                                          self.num_clients)
        flops_per_sample = flops_ops.count_training_flops_per_sample(
            self.trainer.model, params,
            self.trainer._prep(self.sample_input()), batch_stats=bstats)
        n_params = pt.tree_size(params)

        history = []
        start, restored = self.restore_checkpoint()
        if restored is not None:
            params, bstats = restored["params"], restored["batch_stats"]
            mask_pers, history = restored["mask_pers"], restored["history"]
        if self.stream is not None:
            self.stream.prefetch_train(*self.stream_sampling(start))
        for round_idx in range(start, cfg.fed.comm_round):
            sampled = self.client_sampling(round_idx)
            self.log.info("################ round %d: clients %s",
                          round_idx, sampled.tolist())
            if self.stream is not None:
                fed_ids, n_real = self.stream_sampling(round_idx, sampled)
                rngs = self.per_client_rngs(round_idx, fed_ids)
                Xs, ys, ns = self.stream.get_train(fed_ids, n_real)
                if round_idx + 1 < cfg.fed.comm_round:
                    self.stream.prefetch_train(
                        *self.stream_sampling(round_idx + 1))
                (params, bstats, mask_pers, loss, mean_dist, n_accept,
                 up_nnz) = self._round_stream_jit(
                    params, bstats, mask_pers, Xs, ys, ns,
                    jnp.asarray(fed_ids), rngs, self.round_lr(round_idx))
            else:
                # cohort sharding (ISSUE 6): the sharded program gathers
                # the mesh-padded set; the accounting stays on the REAL
                # sampled set
                ids, round_prog = self._cohort_round_prog(sampled)
                rngs = self.per_client_rngs(round_idx, ids)
                (params, bstats, mask_pers, loss, mean_dist, n_accept,
                 up_nnz) = round_prog(
                    params, bstats, mask_pers, self.data,
                    jnp.asarray(ids), rngs, self.round_lr(round_idx))
            # host-side stat accounting. down: the dense w_global per
            # sampled client; up: the pruned client models' TRUE nonzero
            # count (reference nonzero-comm metric,
            # model_trainer.py:49-53) — computed inside the round
            # program, so the device pull is one scalar per round
            n_samples = float(np.sum(self._n_train_host[sampled]))
            self.stat_info["sum_training_flops"] += (
                flops_per_sample * cfg.optim.epochs * n_samples)
            self.stat_info["sum_comm_params"] += (
                n_params * len(sampled) + float(up_nnz))
            self._mask_pers = mask_pers
            # NaN-poisoned-mask diagnosability (ADVICE r5): a NaN in the
            # trained params poisons fake_prune's percentile into an
            # all-False m2; if the accept-test then fires, the client's
            # personal mask collapses — make it visible immediately
            self.warn_if_masks_collapsed(mask_pers, round_idx)
            if round_idx % cfg.fed.frequency_of_the_test == 0 \
                    or round_idx == cfg.fed.comm_round - 1:
                mp = self.eval_masked_global(params, bstats, mask_pers)
                # the shared OBS/health boundary (engines/base.py): the
                # eval above already synced, so the queued in-dispatch
                # health stats drain here (subavg has no n_bad output —
                # the flush is its health/stat boundary, not a
                # non-finite one)
                self._flush_nonfinite(round_idx)
                self.stat_info["person_test_acc"].append(mp["acc"])
                self.log.metrics(round_idx, train_loss=loss,
                                 personal=mp,
                                 mean_mask_dist=float(mean_dist),
                                 prunes_accepted=int(n_accept))
                history.append({"round": round_idx,
                                "train_loss": float(loss),
                                "personal_acc": mp["acc"],
                                "mean_mask_dist": float(mean_dist),
                                "prunes_accepted": int(n_accept)})
            self.maybe_checkpoint(round_idx, {
                "params": params, "batch_stats": bstats,
                "mask_pers": mask_pers, "history": history})
        self._flush_nonfinite(cfg.fed.comm_round - 1)
        m_person = self.eval_masked_global(params, bstats, mask_pers)
        self.log.metrics(-1, personal=m_person)
        densities = np.asarray(jax.device_get(jax.vmap(
            P.density_all_leaves)(jax.vmap(
                lambda m: jax.tree.map(jnp.multiply, params, m))(mask_pers))))
        return {"params": params, "batch_stats": bstats,
                "mask_pers": mask_pers, "history": history,
                "final_personal": m_person,
                "client_densities": densities[: self.real_clients]}
