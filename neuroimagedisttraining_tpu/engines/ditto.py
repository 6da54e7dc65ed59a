"""Ditto: personalized FL with a proximal personal track.

Behavior parity with fedml_api/standalone/ditto/ditto_api.py:40-78 +
ditto/my_model_trainer.py:38-68:

- Global track: sampled clients train the global model normally for
  ``epochs`` epochs; sample-weighted FedAvg.
- Personal track: each sampled client also trains its PERSISTENT personal
  model for ``local_epochs`` epochs, pulling toward the round's incoming
  global model after every step: ``w -= lr * lamda * (w - w_global)``
  (my_model_trainer.py:63-64).
- Evaluation reports the personal models (ditto_api.py:74-78).

Both tracks run inside one jitted round program, DECLARED through the
round-program builder (engines/program.py, ISSUE 11): the builder
supplies ``--client_mesh`` cohort sharding of both training tracks,
buffer donation, the Byzantine attack plan + non-finite guard +
``--defense`` dispatch on the global track's uploads (the personal track
keeps each client's honest local result), all as config knobs — none of
which this engine had before the builder.
"""

from __future__ import annotations

import functools

import jax
import jax.numpy as jnp

from neuroimagedisttraining_tpu.core import robust
from neuroimagedisttraining_tpu.core.trainer import ClientState
from neuroimagedisttraining_tpu.engines import program as round_program
from neuroimagedisttraining_tpu.engines.base import FederatedEngine


class DittoEngine(FederatedEngine):
    name = "ditto"
    # Streaming (cohort > HBM): both tracks only consume the SAMPLED
    # clients' shards, so the streamed round has FedAvg's shape — data per
    # round on device, persistent personal state resident.
    supports_streaming = True
    supports_secure_quant = True  # default aggregate tail on the
    # global track — the secure fold protects exactly that upload
    supports_byz_faults = True  # the builder's attack stage hits the
    # global-track upload; the personal track stays honest
    supports_cohort_sharding = True  # both tracks run as unbatched
    # per-client loops under the --client_mesh shard_map
    supported_defenses = robust.DEFENSES

    # ---------- the declared round (engines/program.py) ----------

    def round_stages(self):
        return round_program.RoundStages(
            carry=("params", "batch_stats", "per_params", "per_bstats"),
            train=self._train_stage,
            update=self._update_stage,
            supports_attack=True,
        )

    def _train_stage(self, ctx) -> round_program.TrainOut:
        """Both tracks. Global: the incoming global model broadcast over
        the cohort, trained ``epochs`` epochs (its trained states are
        the round's upload). Personal: each sampled client's persistent
        model, trained ``local_epochs`` epochs with the proximal pull
        toward the round's incoming global model."""
        trainer = self.trainer
        o = self.cfg.optim
        f = self.cfg.fed
        params = ctx.carry["params"]
        bstats = ctx.carry["batch_stats"]
        Xs, ys, ns = ctx.Xs, ctx.ys, ctx.ns
        lr = ctx.lr
        S = Xs.shape[0]
        max_samples = self._max_samples()
        lamda = float(f.lamda)  # nidt: allow[trace-host-sync] -- cfg.fed.lamda is a static Python scalar bound at trace time, not a tracer

        def bcast(t):
            return jax.tree.map(
                lambda x: jnp.broadcast_to(x, (S,) + x.shape), t)

        # -- global track --
        cs = ClientState(params=bcast(params), batch_stats=bcast(bstats),
                         opt_state=bcast(trainer.opt.init(params)),
                         rng=ctx.rngs)

        def global_local(cs_c, Xc, yc, nc, perms_c=None):
            return trainer.local_train(
                cs_c, Xc, yc, nc, lr, epochs=o.epochs,
                batch_size=o.batch_size, max_samples=max_samples,
                perms=perms_c)

        cs, losses = ctx.client_map(
            global_local, cs, Xs, ys, ns,
            hoisted=(lambda: ctx.local_perms(ctx.rngs, ns, o.epochs),))

        # -- personal track (persistent, proximal to incoming global) --
        pp = jax.tree.map(lambda t: jnp.take(t, ctx.sampled_idx, axis=0),
                          ctx.carry["per_params"])
        pb = jax.tree.map(lambda t: jnp.take(t, ctx.sampled_idx, axis=0),
                          ctx.carry["per_bstats"])
        rngs2 = jax.vmap(lambda r: jax.random.fold_in(r, 1))(ctx.rngs)

        def personal_local(p, b, rng, Xc, yc, nc, perms_c=None):
            cs_p = ClientState(params=p, batch_stats=b,
                               opt_state=trainer.opt.init(p), rng=rng)
            cs_p, _ = trainer.local_train(
                cs_p, Xc, yc, nc, lr, epochs=f.local_epochs,
                batch_size=o.batch_size, max_samples=max_samples,
                prox_lamda=lamda, prox_ref=params, perms=perms_c)
            return cs_p.params, cs_p.batch_stats

        new_pp, new_pb = ctx.client_map(
            personal_local, pp, pb, rngs2, Xs, ys, ns,
            hoisted=(lambda: ctx.local_perms(rngs2, ns, f.local_epochs),))
        return round_program.TrainOut(
            losses=losses,
            upload={"params": cs.params, "batch_stats": cs.batch_stats},
            state=cs,
            extra={"pp": new_pp, "pb": new_pb})

    def _update_stage(self, ctx, tr, new_carry) -> dict:
        """Scatter the personal track back into the persistent per-client
        stacks; pad entries from stream_sampling / mesh tiling are
        dropped, never written (base.scatter_sampled_rows)."""
        real = ctx.ns > 0
        per_params = self.scatter_sampled_rows(
            ctx.carry["per_params"], tr.extra["pp"], ctx.sampled_idx,
            real)
        per_bstats = self.scatter_sampled_rows(
            ctx.carry["per_bstats"], tr.extra["pb"], ctx.sampled_idx,
            real)
        return {"per_params": per_params, "per_bstats": per_bstats}

    # ---------- legacy-signature program adapters ----------

    @functools.cached_property
    def _round_jit(self):
        prog = self.program.round_jit()

        def round_call(params, bstats, per_params, per_bstats, data,
                       sampled_idx, rngs, lr, byz=None):
            return prog((params, bstats, per_params, per_bstats), data,
                        (), sampled_idx, rngs, lr, None, byz)

        return round_call

    def _sharded_round_jit(self, n_real: int):
        prog = self.program.round_jit(n_real=n_real)

        def sharded_round_call(params, bstats, per_params, per_bstats,
                               data, sampled_idx, rngs, lr, byz=None,
                               deal=None):
            return prog((params, bstats, per_params, per_bstats), data,
                        (), sampled_idx, rngs, lr, None, byz, None, deal)

        return sharded_round_call

    @functools.cached_property
    def _round_stream_jit(self):
        prog = self.program.stream_jit()

        def stream_round_call(params, bstats, per_params, per_bstats,
                              Xs, ys, ns, sampled_idx, rngs, lr,
                              byz=None):
            return prog((params, bstats, per_params, per_bstats), (),
                        Xs, ys, ns, sampled_idx, rngs, lr, None, byz)

        return stream_round_call

    def train(self):
        cfg = self.cfg
        gs = self.init_global_state()
        params, bstats = gs.params, gs.batch_stats
        per = self.broadcast_states(
            ClientState(params=params, batch_stats=bstats, opt_state=None,
                        rng=None), self.num_clients)
        per_params, per_bstats = per.params, per.batch_stats
        history = []
        start, restored = self.restore_checkpoint()
        if restored is not None:
            params, bstats = restored["params"], restored["batch_stats"]
            per_params, per_bstats = (restored["per_params"],
                                      restored["per_bstats"])
            history = restored["history"]
        if self.stream is not None:
            self.stream.prefetch_train(*self.stream_sampling(start))
        for round_idx in range(start, cfg.fed.comm_round):
            if self.stream is not None:
                sampled = self.client_sampling(round_idx)
                fed_ids, n_real = self.stream_sampling(round_idx, sampled)
                rngs = self.per_client_rngs(round_idx, fed_ids)
                byz = self._byz_round_plan(round_idx, fed_ids)
                Xs, ys, ns = self.stream.get_train(fed_ids, n_real)
                if round_idx + 1 < cfg.fed.comm_round:
                    self.stream.prefetch_train(
                        *self.stream_sampling(round_idx + 1))
                (params, bstats, per_params, per_bstats, loss,
                 n_bad) = self._round_stream_jit(
                    params, bstats, per_params, per_bstats, Xs, ys, ns,
                    jnp.asarray(fed_ids), rngs, self.round_lr(round_idx),
                    byz)
                self._note_nonfinite(n_bad)
            else:
                sampled = self.client_sampling(round_idx)
                self.log.info("################ round %d: clients %s",
                              round_idx, sampled.tolist())
                # cohort sharding (ISSUE 6): the sharded program gathers
                # the mesh-padded set (and takes rngs for it); the byz
                # plan stays on the REAL sampled set (the builder slices
                # pads off before the attack/defense/scatter tail)
                ids, round_prog = self._cohort_round_prog(sampled)
                rngs = self.per_client_rngs(round_idx, ids)
                byz = self._byz_round_plan(round_idx, sampled)
                (params, bstats, per_params, per_bstats, loss,
                 n_bad) = round_prog(
                    params, bstats, per_params, per_bstats, self.data,
                    jnp.asarray(ids), rngs, self.round_lr(round_idx),
                    byz)
                self._note_nonfinite(n_bad)
            if round_idx % cfg.fed.frequency_of_the_test == 0 \
                    or round_idx == cfg.fed.comm_round - 1:
                m = self._eval_p(per_params, per_bstats)
                mg = self._eval_g(params, bstats)
                self._flush_nonfinite(round_idx)
                self.stat_info["person_test_acc"].append(m["acc"])
                self.log.metrics(round_idx, train_loss=loss,
                                 personal=m, global_=mg)
                history.append({"round": round_idx,
                                "train_loss": float(loss),
                                "personal_acc": m["acc"],
                                "global_acc": mg["acc"]})
            self.maybe_checkpoint(round_idx, {
                "params": params, "batch_stats": bstats,
                "per_params": per_params, "per_bstats": per_bstats,
                "history": history})
        self._flush_nonfinite(cfg.fed.comm_round - 1)
        m = self._eval_p(per_params, per_bstats)
        return {"params": params, "personal_params": per_params,
                "history": history, "final_personal": m}
