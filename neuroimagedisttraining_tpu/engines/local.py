"""Local-only baseline: every client trains its own model forever, no
communication (fedml_api/standalone/local/local_api.py:51-80).

The whole federation's persistent states live as one stacked pytree; every
round is one vmapped/sharded jitted program over ALL clients. The optimizer
is re-created each round (reference builds a fresh torch SGD per call).

DECLARED through the round-program builder (engines/program.py, ROADMAP
item 1(a)): the carry is the per-client state stacks, the train stage is
the vmapped/sharded local pass, and a custom aggregate stage simply
promotes the trained stacks to next round's carry (there is no server
aggregation in a local-only run). The declaration is what buys the
engine ``--client_mesh`` cohort sharding (tests/test_program.py)."""

from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
import numpy as np

from neuroimagedisttraining_tpu.core.trainer import ClientState
from neuroimagedisttraining_tpu.engines import program as round_program
from neuroimagedisttraining_tpu.engines.base import FederatedEngine


class LocalEngine(FederatedEngine):
    name = "local"
    # Streaming (cohort > HBM): clients are fully independent, so the
    # streamed round trains client CHUNKS against host-fetched shards and
    # concatenates the resident per-client state back (same chunked shape
    # as DisPFL's streamed round, minus any consensus).
    supports_streaming = True
    supports_cohort_sharding = True  # the train stage (every client,
    # every round) shards over the --client_mesh like dpsgd's; there is
    # no aggregation tail to replicate

    # ---------- the declared round (engines/program.py) ----------

    def round_stages(self):
        return round_program.RoundStages(
            carry=("per_params", "per_bstats"),
            train=self._train_stage,
            aggregate=self._aggregate_stage,
            outputs=("loss",),
            gathers_cohort=False,
        )

    def _train_stage(self, ctx) -> round_program.TrainOut:
        """Every client trains its own persistent model — vmapped, or
        sharded over the client mesh (perms hoisted out of the
        partition, parallel/cohort.py)."""
        trainer = self.trainer
        o = self.cfg.optim
        max_samples = self._max_samples()
        lr = ctx.lr

        def local(p, b, rng, Xc, yc, nc, perms_c=None):
            cs = ClientState(params=p, batch_stats=b,
                             opt_state=trainer.opt.init(p), rng=rng)
            cs, loss = trainer.local_train(
                cs, Xc, yc, nc, lr, epochs=o.epochs,
                batch_size=o.batch_size, max_samples=max_samples,
                perms=perms_c)
            return cs.params, cs.batch_stats, loss

        new_p, new_b, losses = ctx.client_map(
            local, ctx.carry["per_params"], ctx.carry["per_bstats"],
            ctx.rngs, ctx.Xs, ctx.ys, ctx.ns,
            hoisted=(lambda: ctx.local_perms(ctx.rngs, ctx.ns,
                                             o.epochs),))
        return round_program.TrainOut(
            losses=losses, extra={"new_p": new_p, "new_b": new_b})

    def _aggregate_stage(self, ctx, upload, w, tr):
        """No server aggregation: the trained stacks ARE next round's
        carry; the round's scalar is the sample-weighted mean loss
        (bitwise the legacy ``_round_jit``'s)."""
        mean_loss = jnp.sum(tr.losses * w) / jnp.maximum(jnp.sum(w),
                                                         1e-9)
        return ({"per_params": tr.extra["new_p"],
                 "per_bstats": tr.extra["new_b"]},
                {"loss": mean_loss})

    # ---------- legacy-signature program adapters ----------

    @functools.cached_property
    def _round_jit(self):
        prog = self.program.round_jit(sharded=self._cohort_on)

        def round_call(per_params, per_bstats, data, rngs, lr):
            return prog((per_params, per_bstats), data, (), None, rngs,
                        lr)

        return round_call

    @functools.cached_property
    def _block_jit(self):
        # the streamed chunk program consumes gathered per-chunk copies
        # (stream_map_train_chunks builds them fresh each chunk)
        trainer = self.trainer
        o = self.cfg.optim
        max_samples = self._max_samples()

        def block(per_params, per_bstats, rngs, X, y, n, lr):
            def local(p, b, rng, Xc, yc, nc):
                cs = ClientState(params=p, batch_stats=b,
                                 opt_state=trainer.opt.init(p), rng=rng)
                cs, loss = trainer.local_train(
                    cs, Xc, yc, nc, lr, epochs=o.epochs,
                    batch_size=o.batch_size, max_samples=max_samples)
                return cs.params, cs.batch_stats, loss

            return jax.vmap(local)(per_params, per_bstats, rngs, X, y, n)

        return jax.jit(block, donate_argnums=self._donate_argnums(0, 1))

    def _round_streaming(self, per_params, per_bstats, rngs, lr):
        (new_p, new_b), losses = self.stream_map_train_chunks(
            self._block_jit, (per_params, per_bstats), rngs, lr)
        w = jnp.asarray(self._n_train_host, jnp.float32)
        mean_loss = jnp.sum(losses * w) / jnp.maximum(jnp.sum(w), 1e-9)
        return new_p, new_b, mean_loss

    def train(self):
        cfg = self.cfg
        gs = self.init_global_state()
        per = self.broadcast_states(
            ClientState(params=gs.params, batch_stats=gs.batch_stats,
                        opt_state=None, rng=None), self.num_clients)
        per_params, per_bstats = per.params, per.batch_stats
        history = []
        start, restored = self.restore_checkpoint()
        if restored is not None:
            per_params, per_bstats = (restored["per_params"],
                                      restored["per_bstats"])
            history = restored["history"]
        for round_idx in range(start, cfg.fed.comm_round):
            rngs = self.per_client_rngs(round_idx,
                                        np.arange(self.num_clients))
            if self.stream is not None:
                per_params, per_bstats, loss = self._round_streaming(
                    per_params, per_bstats, rngs,
                    self.round_lr(round_idx))
            else:
                per_params, per_bstats, loss = self._round_jit(
                    per_params, per_bstats, self.data, rngs,
                    self.round_lr(round_idx))
            if round_idx % cfg.fed.frequency_of_the_test == 0 \
                    or round_idx == cfg.fed.comm_round - 1:
                m = self._eval_p(per_params, per_bstats)
                # the shared OBS/health boundary (engines/base.py) —
                # the eval above already synced
                self._flush_nonfinite(round_idx)
                self.stat_info["person_test_acc"].append(m["acc"])
                self.log.metrics(round_idx, train_loss=loss, **m)
                history.append({"round": round_idx,
                                "train_loss": float(loss), **m})
            self.maybe_checkpoint(round_idx, {
                "per_params": per_params, "per_bstats": per_bstats,
                "history": history})
        m = self._eval_p(per_params, per_bstats)
        self.log.metrics(-1, personal=m)
        return {"personal_params": per_params,
                "personal_batch_stats": per_bstats, "history": history,
                "final_personal": m}
