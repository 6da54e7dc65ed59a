"""SalientGrads: one-shot federated SNIP mask + masked-sparse FedAvg.

The flagship algorithm (fedml_api/standalone/sailentgrads/sailentgrads_api.py).
Behavior parity:

- PHASE 1 (once, before training): every client computes SNIP saliency
  scores on its own data (IterSNIP over ``itersnip_iteration`` batches,
  client.py:30-53); the server averages score dicts (snip.py:120-140) and
  builds ONE global cross-layer top-(dense_ratio) binary mask
  (snip.py:80-116). Dense escape hatch: ``snip_mask=False`` -> all-ones
  masks (sailentgrads_api.py:94-100).
- PHASE 2 (rounds): sampled clients train from the global model with
  post-step re-masking ``param *= mask`` (my_model_trainer.py:228-231);
  sample-weighted FedAvg over the sampled set (sailentgrads_api.py:212-227);
  each client's personal model is its most recent local-train result
  (sailentgrads_api.py:128-136); global + personal eval every round.

TPU-native: phase 1 is one jitted program — per-client scores vmapped over
the client-sharded mesh, the score mean is an ICI all-reduce, and the global
top-k threshold runs the Pallas histogram-select kernel. Phase 2 rounds are
the same single-program SPMD shape as FedAvg.
"""

from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
import numpy as np

from neuroimagedisttraining_tpu.core import robust
from neuroimagedisttraining_tpu.core.trainer import ClientState
from neuroimagedisttraining_tpu.engines import program as round_program
from neuroimagedisttraining_tpu.engines.base import FederatedEngine
from neuroimagedisttraining_tpu.obs import health as obs_health
from neuroimagedisttraining_tpu.obs import names as obs_names
from neuroimagedisttraining_tpu.obs import trace as obs_trace
from neuroimagedisttraining_tpu.ops import flops as flops_ops
from neuroimagedisttraining_tpu.ops import snip as snip_ops
from neuroimagedisttraining_tpu.ops.masks import mask_density, ones_mask
from neuroimagedisttraining_tpu.utils import pytree as pt


class SalientGradsEngine(FederatedEngine):
    name = "salientgrads"
    # Streaming mode (cohort > HBM): per-client DATA streams per round /
    # per phase-1 chunk; the per-client personal STATE (params + batch
    # stats) and the global mask stay device-resident — the reference's
    # per-batch lazy HDF5 fetch (my_model_trainer.py:185-199) done at
    # round granularity, same as FedAvg's streaming path.
    supports_streaming = True
    supports_wire_codec = True  # masked roundtrip inside _round_body
    supports_secure_quant = True  # masked uploads still aggregate
    # through the builder's default tail — the field fold replaces it
    supports_byz_faults = True  # uploads route through faults/adversary
    supports_cohort_sharding = True  # phase-1 scores and the phase-2
    # round's local-train stage shard over the --client_mesh (ISSUE 6)
    supported_defenses = robust.DEFENSES
    #: the phase-1 global mask once generated (wire_masks handoff)
    _wire_masks = None

    def wire_masks(self):
        """Mask handoff (codec/): the phase-1 global SNIP mask — static
        across rounds and owned by BOTH endpoints (the server computed
        and broadcast it), so the wire codec packs uploads against it
        with no bitmap frame."""
        return self._wire_masks

    # ---------- phase 1: the global mask ----------

    @jax.named_scope(obs_names.SCOPE_SNIP_SCORES)
    def _scores_body(self, params, bstats, Xs, ys, ns, rngs):
        """Weighted SNIP-score SUM over a block of clients + the block's
        client-weight sum — shared by the resident one-shot program and
        the streamed per-chunk program."""
        trainer = self.trainer
        s = self.cfg.sparsity
        o = self.cfg.optim
        K = Xs.shape[0]
        cs = ClientState(
            params=jax.tree.map(
                lambda x: jnp.broadcast_to(x, (K,) + x.shape), params),
            batch_stats=jax.tree.map(
                lambda x: jnp.broadcast_to(x, (K,) + x.shape), bstats),
            opt_state=jax.tree.map(
                lambda x: jnp.broadcast_to(x, (K,) + x.shape),
                trainer.opt.init(params)),
            rng=rngs,
        )

        def per_client(cs_c, Xc, yc, nc, idx_c=None):
            sc = snip_ops.iter_snip_scores(
                trainer, cs_c, Xc, yc, nc,
                iterations=s.itersnip_iterations, batch_size=o.batch_size,
                stratified=s.stratified_sampling, idx_stack=idx_c)
            # zero-weight padding clients contribute nothing
            w = (nc > 0).astype(jnp.float32)
            return jax.tree.map(lambda t: t * w, sc), w

        # phase-1 scoring shards per-client over the cohort mesh when
        # armed (the resident cohort tiles the mesh by construction —
        # the data layer pads num_clients); the weighted SUM runs on the
        # all-gathered replicated stacks, so scores — and the global
        # mask/threshold — match the sequential pipeline's to ~1 ulp
        # (tests/test_cohort.py pins the emitted masks identical on its
        # seed). Like the round's epoch permutations, IterSNIP's batch
        # draws are HOISTED out of the partition (in-partition RNG draws
        # consumed by a scan are the measured miscompile class —
        # parallel/cohort.py); the STRATIFIED sampler's choice-based
        # draw has no hoisted form yet, so it keeps the unsharded path
        if self._cohort_on and K % self.mesh.devices.size == 0 \
                and not s.stratified_sampling:
            idxs = jax.vmap(
                lambda r, n: snip_ops.iter_snip_batch_indices(
                    r, s.itersnip_iterations, o.batch_size, n))(cs.rng, ns)
            per, w = self._cohort_map(per_client, cs, Xs, ys, ns, idxs)
        else:
            per, w = jax.vmap(per_client)(cs, Xs, ys, ns)
        return (jax.tree.map(lambda t: jnp.sum(t, axis=0), per),
                jnp.sum(w))

    @functools.cached_property
    def _scores_jit(self):
        def scores_fn(params, bstats, data, rngs):
            ssum, wsum = self._scores_body(params, bstats, data.X_train,
                                           data.y_train, data.n_train, rngs)
            # mean over REAL clients (snip.py get_mean_snip_scores)
            denom = jnp.maximum(wsum, 1.0)
            return jax.tree.map(lambda t: t / denom, ssum)

        return jax.jit(scores_fn)

    @functools.cached_property
    def _chunk_scores_jit(self):
        return jax.jit(self._scores_body)

    def _scores_streaming(self, params, bstats):
        """Phase-1 SNIP scores over a >HBM cohort: stream train shards in
        client chunks; only the (param-sized) score accumulator stays on
        device. Matches my_model_trainer.py:185-199's lazy per-batch fetch
        at chunk granularity."""
        chunk = self._eval_chunk_size()
        acc, wtot = None, None
        for ch in self.stream.eval_chunks(chunk, "train"):
            rngs = self.per_client_rngs(-1, ch.padded_ids)
            ssum, wsum = self._chunk_scores_jit(params, bstats, ch.X, ch.y,
                                                ch.n, rngs)
            if acc is None:
                acc, wtot = ssum, wsum
            else:
                acc = pt.tree_add(acc, ssum)
                wtot = wtot + wsum
        denom = jnp.maximum(wtot, 1.0)
        return jax.tree.map(lambda t: t / denom, acc)

    def global_scores(self, params, bstats):
        """Phase-1 scores: the per-client SNIP saliencies averaged over
        the real clients (resident or streamed cohort)."""
        if self.stream is not None:
            return self._scores_streaming(params, bstats)
        rngs = self.per_client_rngs(-1, np.arange(self.num_clients))
        return self._scores_jit(params, bstats, self.data, rngs)

    def generate_global_mask(self, params, bstats):
        """Phase-1 pipeline (sailentgrads_api.py:47-66)."""
        masks, thr = snip_ops.mask_from_scores(
            self.global_scores(params, bstats),
            keep_ratio=self.cfg.sparsity.dense_ratio)
        if not self.cfg.sparsity.snip_mask:
            masks = ones_mask(params)  # dense escape hatch
        return masks, thr

    # ---------- phase 2: masked rounds ----------

    # ---------- the declared round (engines/program.py) ----------

    def round_stages(self):
        """The masked round as a declaration: FedAvg's carry plus the
        persistent per-client personal stacks, the phase-1 mask as a
        loop constant, and an update stage scattering each sampled
        client's HONEST local result (pre-attack/codec — the attack is
        on the wire payload, not the silo's own state). The builder's
        codec stage packs uploads against the mask (``codec_masks``
        handoff: top-k sparse by construction, bitmap-free)."""
        return round_program.RoundStages(
            carry=("params", "batch_stats", "per_params", "per_bstats"),
            train=self._train_stage,
            update=self._update_stage,
            consts=("masks",),
            supports_attack=True,
            codec_masks=self._codec_masks,
            health=self._health_stage,
            health_outputs=obs_health.MASK_STAT_NAMES,
        )

    def _health_stage(self, ctx, tr, new_carry) -> dict:
        """Mask-health leg (ISSUE 15, armed under ``--health_stats``):
        the phase-1 mask is a loop CONSTANT, so density is the whole
        story (overlap pins at 1 — which is itself the signal: a
        salientgrads run whose overlap moved would mean the const mask
        was rebuilt mid-run)."""
        return round_program.mask_health_stats(ctx.consts["masks"],
                                               None)

    def _train_stage(self, ctx) -> round_program.TrainOut:
        """Masked local-train stage (post-step re-mask ``param *= mask``,
        my_model_trainer.py:228-231): vmapped, or unbatched per-client
        loops under the client mesh with the mask riding as a closed-over
        replicated constant (ctx.client_map; perms hoisted —
        parallel/cohort.py)."""
        trainer = self.trainer
        o = self.cfg.optim
        params = ctx.carry["params"]
        bstats = ctx.carry["batch_stats"]
        masks = ctx.consts["masks"]
        Xs, ys, ns = ctx.Xs, ctx.ys, ctx.ns
        lr = ctx.lr
        S = Xs.shape[0]
        max_samples = self._max_samples()
        cs = ClientState(
            params=jax.tree.map(
                lambda x: jnp.broadcast_to(x, (S,) + x.shape), params),
            batch_stats=jax.tree.map(
                lambda x: jnp.broadcast_to(x, (S,) + x.shape), bstats),
            opt_state=jax.tree.map(
                lambda x: jnp.broadcast_to(x, (S,) + x.shape),
                trainer.opt.init(params)),
            rng=ctx.rngs,
        )

        def local(cs_c, Xc, yc, nc, perms_c=None):
            return trainer.local_train(
                cs_c, Xc, yc, nc, lr, epochs=o.epochs,
                batch_size=o.batch_size, max_samples=max_samples,
                mask=masks, perms=perms_c)

        cs, losses = ctx.client_map(
            local, cs, Xs, ys, ns,
            hoisted=(lambda: ctx.local_perms(ctx.rngs, ns, o.epochs),))
        return round_program.TrainOut(
            losses=losses,
            upload={"params": cs.params, "batch_stats": cs.batch_stats},
            state=cs)

    def _codec_masks(self, ctx) -> dict:
        """Mask handoff to the builder's codec stage: the phase-1 global
        mask over params, all-ones over the (never-pruned) batch stats —
        the exact tree a cross-silo silo encodes (distributed/run.py)."""
        return {"params": ctx.consts["masks"],
                "batch_stats": jax.tree.map(jnp.ones_like,
                                            ctx.carry["batch_stats"])}

    def _update_stage(self, ctx, tr, new_carry) -> dict:
        """Personal models <- this round's local results; pad entries
        (mesh tiling / streamed feed) are dropped, never written
        (base.scatter_sampled_rows)."""
        real = ctx.ns > 0
        per_params = self.scatter_sampled_rows(
            ctx.carry["per_params"], tr.state.params, ctx.sampled_idx,
            real)
        per_bstats = self.scatter_sampled_rows(
            ctx.carry["per_bstats"], tr.state.batch_stats,
            ctx.sampled_idx, real)
        return {"per_params": per_params, "per_bstats": per_bstats}

    # ---------- legacy-signature program adapters ----------

    @functools.cached_property
    def _round_jit(self):
        prog = self.program.round_jit()

        def round_call(params, bstats, per_params, per_bstats, data,
                       masks, sampled_idx, rngs, lr, byz=None):
            return prog((params, bstats, per_params, per_bstats), data,
                        (masks,), sampled_idx, rngs, lr, None, byz)

        return round_call

    def _sharded_round_jit(self, n_real: int):
        """The cohort-sharded masked round (ISSUE 6): ``_round_jit``'s
        signature and donation contract, with ``sampled_idx``/``rngs``
        covering the MESH-PADDED sampled set and the builder sharding
        the local-train stage over the client mesh (``n_real`` static)."""
        prog = self.program.round_jit(n_real=n_real)

        def sharded_round_call(params, bstats, per_params, per_bstats,
                               data, masks, sampled_idx, rngs, lr,
                               byz=None, deal=None):
            return prog((params, bstats, per_params, per_bstats), data,
                        (masks,), sampled_idx, rngs, lr, None, byz,
                        None, deal)

        return sharded_round_call

    @functools.cached_property
    def _round_stream_jit(self):
        prog = self.program.stream_jit()

        def stream_round_call(params, bstats, per_params, per_bstats,
                              Xs, ys, ns, masks, sampled_idx, rngs, lr,
                              byz=None):
            return prog((params, bstats, per_params, per_bstats),
                        (masks,), Xs, ys, ns, sampled_idx, rngs, lr,
                        None, byz)

        return stream_round_call

    def _round_iteration(self, round_idx: int, state: tuple, masks,
                         history, acct: tuple) -> tuple:
        """One iteration of the round loop (resident or streamed): host
        prologue, the dispatch of one round, the host-side accounting
        and the boundary hooks (eval cadence + checkpoint). ``state`` is
        ``(params, bstats, per_params, per_bstats)``; returns the new
        one. The caller's ``round`` span covers the whole iteration; the
        stages here are its children and take their round id from it
        (obs/names.py)."""
        cfg = self.cfg
        flops_per_sample, comm_params_per_client = acct
        with obs_trace.span(obs_names.SPAN_ROUND_PROLOGUE):
            sampled = self.client_sampling(round_idx)
            self.log.info("################ round %d: clients %s",
                          round_idx, sampled.tolist())
            lr = self.round_lr(round_idx)
            if self.stream is not None:
                ids, n_real = self.stream_sampling(round_idx, sampled)
                byz = self._byz_round_plan(round_idx, ids)
                Xs, ys, ns = self.stream.get_train(ids, n_real)
                if round_idx + 1 < cfg.fed.comm_round:
                    # overlap next round's host read with this round
                    self.stream.prefetch_train(
                        *self.stream_sampling(round_idx + 1))
            else:
                # cohort sharding (ISSUE 6): padded gather ids for the
                # sharded program; byz plan and byte accounting stay on
                # the REAL sampled set (the body slices pads off)
                ids, round_prog = self._cohort_round_prog(sampled)
                byz = self._byz_round_plan(round_idx, sampled)
                if self.wire_spec is not None:
                    with obs_trace.span(obs_names.SPAN_CODEC_SYNC):
                        ref_host = jax.tree.map(
                            np.asarray, {"params": state[0],
                                         "batch_stats": state[1]})
            rngs = self.per_client_rngs(round_idx, ids)
            idx = jnp.asarray(ids)
            self._note_round_counts(sampled, len(ids))
        if self.stream is not None:
            *state, loss, n_bad = self._round_stream_jit(
                *state, Xs, ys, ns, masks, idx, rngs, lr, byz)
        elif self.wire_spec is not None:
            *state, loss, n_bad, u0 = round_prog(
                *state, self.data, masks, idx, rngs, lr, byz)
            with obs_trace.span(obs_names.SPAN_CODEC_SYNC):
                masks_host = {
                    "params": jax.tree.map(np.asarray, masks),
                    "batch_stats": jax.tree.map(
                        np.ones_like, ref_host["batch_stats"])}
                self.account_wire_bytes(
                    jax.tree.map(np.asarray, u0), ref_host,
                    masks_host=masks_host, n_uploads=len(sampled))
        else:
            *state, loss, n_bad = round_prog(
                *state, self.data, masks, idx, rngs, lr, byz)
        self._note_nonfinite(n_bad)
        # host-side accounting (host data only — no device sync)
        n_samples = float(np.sum(self._n_train_host[sampled]))
        self.stat_info["sum_training_flops"] += (
            flops_per_sample * cfg.optim.epochs * n_samples)
        self.stat_info["sum_comm_params"] += (
            comm_params_per_client * len(sampled))
        params, bstats, per_params, per_bstats = state
        if round_idx % cfg.fed.frequency_of_the_test == 0 \
                or round_idx == cfg.fed.comm_round - 1:
            m = self._eval_g(params, bstats)
            mp = self._eval_p(per_params, per_bstats)
            with obs_trace.span(obs_names.SPAN_ROUND_FLUSH):
                self._flush_nonfinite(round_idx)
            with obs_trace.span(obs_names.SPAN_ROUND_LOG):
                self.stat_info["global_test_acc"].append(m["acc"])
                self.stat_info["person_test_acc"].append(mp["acc"])
                self.log.metrics(round_idx, train_loss=loss, **m,
                                 personal_acc=mp["acc"])
                history.append({"round": round_idx,
                                "train_loss": float(loss), **m,
                                "personal_acc": mp["acc"]})
        with obs_trace.span(obs_names.SPAN_ROUND_CHECKPOINT):
            self.maybe_checkpoint(round_idx, {
                "params": params, "batch_stats": bstats,
                "per_params": per_params, "per_bstats": per_bstats,
                "masks": masks, "history": history})
        return tuple(state)

    def train(self):
        cfg = self.cfg
        # train_init / mask_phase / final_pass: what this call does
        # outside its rounds (obs/names.py); disarmed, the shared no-op
        with obs_trace.span(obs_names.SPAN_TRAIN_INIT):
            gs = self.init_global_state()
            params, bstats = gs.params, gs.batch_stats

            start, restored = self.restore_checkpoint()
            if restored is not None:
                masks = restored["masks"]  # phase 1 not recomputed on resume
            else:
                with obs_trace.span(obs_names.SPAN_MASK_PHASE):
                    masks, thr = self.generate_global_mask(params, bstats)
            density = float(mask_density(masks))
            # mask handoff: the wire codec (and any cross-silo deployment
            # of this engine) packs uploads against this mask — both
            # endpoints own it, phase 1 computed it server-side and
            # broadcast it
            self._wire_masks = masks
            self.log.info("global SNIP mask density = %.4f (target %.4f)",
                          density, cfg.sparsity.dense_ratio)
            self.stat_info["mask_density"] = density
            if cfg.sparsity.save_masks:
                self.stat_info["final_masks"] = jax.tree.map(np.asarray,
                                                             masks)

            # flops/comm accounting (reference stat_info parity)
            dens_map = flops_ops.densities_from_masks(masks)
            flops_per_sample = flops_ops.count_training_flops_per_sample(
                self.trainer.model, params,
                self.trainer._prep(self.sample_input()),
                mask_density=dens_map, batch_stats=bstats)
            # communicated parameters per client per round = nonzero mask
            # entries (masks are ones on non-maskable leaves), matching
            # the reference's nonzero-parameter comm metric
            # (model_trainer.py:49-53)
            comm_params_per_client = float(sum(
                float(jnp.sum(m)) for m in jax.tree.leaves(masks)))

            per = self.broadcast_states(
                ClientState(params=params, batch_stats=bstats,
                            opt_state=self.trainer.opt.init(params),
                            rng=gs.rng), self.num_clients)
            per_params, per_bstats = per.params, per.batch_stats

            history = []
            if restored is not None:
                params, bstats = restored["params"], restored["batch_stats"]
                per_params, per_bstats = (restored["per_params"],
                                          restored["per_bstats"])
                history = restored["history"]
            if self.stream is not None:
                self.stream.prefetch_train(*self.stream_sampling(start))
            state = (params, bstats, per_params, per_bstats)
        for round_idx in range(start, cfg.fed.comm_round):
            # one span for the whole iteration, sampling to checkpoint;
            # its children carry the same round id (obs/names.py)
            with obs_trace.span(obs_names.SPAN_ROUND, round=round_idx):
                state = self._round_iteration(
                    round_idx, state, masks, history,
                    (flops_per_sample, comm_params_per_client))
        with obs_trace.span(obs_names.SPAN_FINAL_PASS):
            params, bstats, per_params, per_bstats = state
            self._flush_nonfinite(cfg.fed.comm_round - 1)
            m_global = self._eval_g(params, bstats)
            m_person = self._eval_p(per_params, per_bstats)
            self.log.metrics(-1, global_=m_global, personal=m_person)
        return {"params": params, "batch_stats": bstats, "masks": masks,
                "mask_density": density, "history": history,
                "final_global": m_global, "final_personal": m_person}
