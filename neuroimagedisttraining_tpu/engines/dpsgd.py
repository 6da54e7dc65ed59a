"""D-PSGD: decentralized gossip SGD (fedml_api/standalone/dpsgd/dpsgd_api.py).

Behavior parity (dpsgd_api.py:41-139):
- Per round, every client picks neighbors by the ``cs`` selector: "random"
  (seeded np.random.seed(round_idx + client), resampled while it contains
  self, then self appended), "ring" (left/right), or "full" (everyone).
- Consensus: uniform average over {neighbors ∪ self} of LAST round's
  personal models (dpsgd_api.py:169-178), then local training from the
  consensus point.
- ``w_global`` = plain mean of all personal models, used for global eval
  (dpsgd_api.py:161-167).
- Every 100 rounds a fine-tune-from-global evaluation pass
  (dpsgd_api.py:89-101).

TPU-native: neighbor choices become one row-stochastic mixing matrix
``M[C,C]`` per round. For ``cs="ring"`` at full activity the matrix is
CIRCULANT and the consensus lowers to ``lax.ppermute`` shifts of 1-row
slices between neighboring devices (parallel/gossip.py) — per-device
traffic O(model), independent of C. For ``cs="random"`` (a fresh
k-regular draw every round) the consensus lowers to a routed, capped
``lax.all_to_all`` whose routing tables are traced operands
(parallel/gossip.py::sparse_plan) — per-device traffic O(D * m * model),
m ~ B(k+1)/D rows, one compiled program per size bucket. Only when
neither structure applies (dense patterns) does it fall back to the
``einsum('cj,j...->c...')`` all-gather.

The round is DECLARED through the round-program builder
(engines/program.py, ISSUE 11): consensus + local training is the train
stage (the mixing matrix and the sparse plan's routing arrays are
``per_round`` operands; the hashable plan spec keys the compiled
program), the all-real mean over trained stacks is a custom aggregate
stage, and ``w_global`` is an epilogue, computed from the round's new
stacks. The builder supplies ``--client_mesh`` sharding of the
local-train stage (the gossip consensus itself already runs mesh
collectives).
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
from neuroimagedisttraining_tpu.parallel.gossip import (
    SparseSpec, gossip_apply, gossip_apply_sparse, make_plan,
)

#: fold_in tag separating the DP noise stream from the training stream
#: (both derive from the same config-seeded per-client round key)
_DP_STREAM = 0x0D9


def benefit_choose(round_idx: int, cur_clnt: int, total: int,
                   per_round: int, cs: str) -> np.ndarray:
    """Neighbor selection, reference parity (dpsgd_api.py:116-139)."""
    if total == per_round:
        return np.arange(total)
    if cs == "random":
        num = min(per_round, total)
        np.random.seed(round_idx + cur_clnt)  # nidt: allow[determinism-global-random] -- reference-parity shim (dpsgd_api.py:116-139)
        idx = np.random.choice(range(total), num, replace=False)  # nidt: allow[determinism-global-random] -- reference-parity shim (dpsgd_api.py:116-139)
        while cur_clnt in idx:
            idx = np.random.choice(range(total), num, replace=False)  # nidt: allow[determinism-global-random] -- reference-parity shim (dpsgd_api.py:116-139)
        return idx
    if cs == "ring":
        return np.asarray([(cur_clnt - 1) % total, (cur_clnt + 1) % total])
    if cs == "full":
        return np.delete(np.arange(total), cur_clnt)
    raise ValueError(f"unknown cs {cs!r}")


class DPSGDEngine(FederatedEngine):
    name = "dpsgd"
    #: round-level DP (--dp_clip/--dp_sigma, privacy/ ISSUE 8): in a
    #: decentralized federation every client REVEALS its personal model
    #: to its gossip neighbors each round — there is no trusted server
    #: to defend at, so the only privacy boundary is the client's own
    #: upload. When armed, each client's post-training delta vs its
    #: consensus point is clipped to dp_clip and noised with
    #: N(0, (dp_sigma * dp_clip)^2) INSIDE the jitted round, before
    #: anything leaves the per-client row (neighbors, w_global, and
    #: eval all consume the noised models); the RDP accountant reports
    #: the running per-silo (epsilon, dp_delta) in stat_info
    #: (record_privacy: q = 1 full participation, z = dp_sigma).
    supports_dp = True

    def mixing_matrix(self, round_idx: int) -> np.ndarray:
        """Row c = uniform weights over {neighbors(c) ∪ c} among real
        clients; padding clients keep themselves."""
        C = self.num_clients
        total = self.real_clients
        per_round = min(self.cfg.fed.client_num_per_round, total)
        M = np.zeros((C, C), np.float32)
        for c in range(total):
            nei = benefit_choose(round_idx, c, total, per_round,
                                 self.cfg.fed.cs)
            if total != per_round:
                nei = np.append(nei, c)
            nei = np.unique(nei)
            M[c, nei] = 1.0 / len(nei)
        for c in range(total, C):
            M[c, c] = 1.0
        return M

    # Streaming (cohort > HBM): like DisPFL, every client trains each
    # round, so the streamed round runs the state-only gossip consensus
    # first and then local-trains client CHUNKS against host-fetched
    # shards.
    supports_streaming = True
    supports_cohort_sharding = True  # the local-train stage (every
    # client, every round) shards over the --client_mesh; the consensus
    # already runs mesh collectives (parallel/gossip.py)

    def _consensus(self, per_params, per_bstats, M, plan_arrays=None, *,
                   plan=None):
        """Gossip consensus over last round's models: ppermute ring shifts
        when the round's matrix is circulant and tiles the mesh (Plan
        tuple), a routed all_to_all for per-round sparse random topologies
        (SparseSpec + traced ``plan_arrays``), else one all-gather matmul
        against the mixing matrix."""
        if isinstance(plan, SparseSpec):
            mix = lambda t: gossip_apply_sparse(t, plan, plan_arrays,
                                                self.mesh)
        elif plan is not None:
            mix = lambda t: gossip_apply(t, plan, self.mesh)
        else:
            mix = lambda t: jax.tree.map(
                lambda x: jnp.einsum("cj,j...->c...", M, x), t)
        return mix(per_params), mix(per_bstats)

    def gossip_plan(self, M_np: np.ndarray):
        """``(plan, plan_arrays)`` for this round's matrix: a hashable
        circulant Plan tuple (ppermute shifts, round-invariant ring
        topologies), a SparseSpec + routing arrays (routed all_to_all,
        per-round random topologies — the spec keys the jit cache, the
        arrays are traced operands), or (None, {}) for the dense einsum.
        Detection cost: O(C^2) host compares / O(C*k) bucketing per
        round."""
        return make_plan(M_np, self.mesh, self.num_clients)

    # ---------- the declared round (engines/program.py) ----------

    def round_stages(self):
        return round_program.RoundStages(
            carry=("per_params", "per_bstats"),
            train=self._train_stage,
            aggregate=self._aggregate_stage,
            epilogue=self._epilogue_stage,
            outputs=("loss",),
            per_round=("M", "plan_arrays"),
            gathers_cohort=False,
        )

    def _train_stage(self, ctx) -> round_program.TrainOut:
        """Consensus over last round's models (per-round mixing matrix /
        routed plan arrays), then every client trains from its consensus
        point — vmapped, or sharded over the client mesh (the full
        cohort tiles it by construction: the data layer pads
        num_clients; perms hoisted out of the partition)."""
        o = self.cfg.optim
        Xs, ys, ns = ctx.Xs, ctx.ys, ctx.ns
        mixed_p, mixed_b = self._consensus(
            ctx.carry["per_params"], ctx.carry["per_bstats"],
            ctx.per_round["M"], ctx.per_round["plan_arrays"],
            plan=ctx.static)
        new_p, new_b, losses = ctx.client_map(
            self._dp_local_fn(ctx.lr), mixed_p, mixed_b, ctx.rngs, Xs,
            ys, ns,
            hoisted=(lambda: ctx.local_perms(ctx.rngs, ns, o.epochs),))
        return round_program.TrainOut(
            losses=losses, extra={"new_p": new_p, "new_b": new_b})

    def _aggregate_stage(self, ctx, upload, w, tr):
        """No server aggregation in a decentralized round: the trained
        stacks ARE next round's carry; the round's scalar is the mean
        loss over real clients."""
        real = (ctx.ns > 0).astype(jnp.float32)
        denom = jnp.maximum(jnp.sum(real), 1.0)
        mean_loss = jnp.sum(tr.losses * real) / denom
        return ({"per_params": tr.extra["new_p"],
                 "per_bstats": tr.extra["new_b"]},
                {"loss": mean_loss})

    @staticmethod
    def _global_mean(new_p, new_b, n_train):
        real = (n_train > 0).astype(jnp.float32)
        denom = jnp.maximum(jnp.sum(real), 1.0)
        gmean = lambda t: jax.tree.map(
            lambda x: jnp.einsum(
                "c,c...->...", real / denom, x.astype(jnp.float32)
            ).astype(x.dtype), t)
        return gmean(new_p), gmean(new_b), real, denom

    def _epilogue_stage(self, eng, carry, data) -> tuple:
        """``w_global`` — the plain mean of all personal models
        (dpsgd_api.py:161-167), from the round's new stacks."""
        wp, wb, _, _ = self._global_mean(carry["per_params"],
                                         carry["per_bstats"],
                                         data.n_train)
        return (wp, wb)

    # ---------- legacy-signature program adapters ----------

    def _round_jit_for(self, plan):
        prog = self.program.round_jit(static_key=plan,
                                      sharded=self._cohort_on)

        def round_call(per_params, per_bstats, data, M, rngs, lr,
                       plan_arrays):
            return prog((per_params, per_bstats), data, (), None, rngs,
                        lr, None, None, (M, plan_arrays))

        def lower(per_params, per_bstats, data, M, rngs, lr,
                  plan_arrays):
            # legacy-signature .lower passthrough (compile-text pins,
            # tests/test_gossip.py)
            return prog.jit.lower((per_params, per_bstats), data, (),
                                  None, rngs, lr, None, None,
                                  (M, plan_arrays))

        round_call.jit = prog.jit
        round_call.lower = lower
        return round_call

    @property
    def _round_jit(self):
        return self._round_jit_for(None)

    # ---------- streaming round (chunked; outside the program) ----------

    def _consensus_jit_for(self, plan):
        # donation: the streamed round never rereads the pre-consensus
        # stacks once mixed
        return self._plan_cached(
            "_consensus_jit_cache", plan,
            lambda: jax.jit(functools.partial(self._consensus, plan=plan),
                            donate_argnums=self._donate_argnums(0, 1)))

    @property
    def _consensus_jit(self):
        return self._consensus_jit_for(None)

    def _dp_local_fn(self, lr):
        """The per-client train + DP-boundary closure shared by the
        resident train stage and the streamed block — the DP transform
        lives ONCE. Clip the update delta vs THIS client's consensus
        point (its round input ``p`` — the model its neighbors already
        hold), then Gaussian noise at sigma = dp_sigma * dp_clip from
        the config-folded key. batch_stats are never clipped/noised
        (structural parity with the weak_dp is_weight_param
        exclusion)."""
        trainer = self.trainer
        o = self.cfg.optim
        f = self.cfg.fed
        max_samples = self._max_samples()
        dp_on = f.dp_sigma > 0 or f.dp_clip > 0

        def local(p, b, rng, Xc, yc, nc, perms_c=None):
            cs = ClientState(params=p, batch_stats=b,
                             opt_state=trainer.opt.init(p), rng=rng)
            cs, loss = trainer.local_train(
                cs, Xc, yc, nc, lr, epochs=o.epochs,
                batch_size=o.batch_size, max_samples=max_samples,
                perms=perms_c)
            out_p = cs.params
            if dp_on:
                out_p = robust.norm_diff_clip(out_p, p, f.dp_clip)
                if f.dp_sigma > 0:
                    out_p = robust.add_weak_dp_noise(
                        out_p, jax.random.fold_in(rng, _DP_STREAM),
                        f.dp_sigma * f.dp_clip)
            return out_p, cs.batch_stats, loss

        return local

    def _local_block(self, mixed_p, mixed_b, rngs, X, y, n, lr):
        """The streamed per-chunk training block (the resident path's
        local stage lives in ``_train_stage``)."""
        return jax.vmap(self._dp_local_fn(lr))(mixed_p, mixed_b, rngs,
                                               X, y, n)

    @functools.cached_property
    def _block_jit(self):
        # consumes the consensus output chunks (gathered fresh per chunk)
        return jax.jit(self._local_block,
                       donate_argnums=self._donate_argnums(0, 1))

    @functools.cached_property
    def _tail_jit(self):
        def tail(new_p, new_b, losses, n_train):
            w_global_p, w_global_b, real, denom = self._global_mean(
                new_p, new_b, n_train)
            mean_loss = jnp.sum(losses * real) / denom
            return w_global_p, w_global_b, mean_loss

        return jax.jit(tail)

    def _round_streaming(self, per_params, per_bstats, M, rngs, lr,
                         plan=None, plan_arrays=None):
        mixed_p, mixed_b = self._consensus_jit_for(plan)(
            per_params, per_bstats, M, plan_arrays or {})
        (new_p, new_b), losses = self.stream_map_train_chunks(
            self._block_jit, (mixed_p, mixed_b), rngs, lr)
        w_global_p, w_global_b, mean_loss = self._tail_jit(
            new_p, new_b, losses, jnp.asarray(self._n_train_host))
        return new_p, new_b, w_global_p, w_global_b, mean_loss

    @functools.cached_property
    def _finetune_jit(self):
        """Every-100-rounds fine-tune-from-global evaluation pass
        (dpsgd_api.py:89-101): each client trains one round from w_global;
        the fine-tuned models are evaluated then DISCARDED (w_per_tmp)."""
        trainer = self.trainer
        o = self.cfg.optim
        max_samples = int(self.data.X_train.shape[1])

        def ft(params, bstats, data, rngs, lr):
            def local(rng, Xc, yc, nc):
                cs = ClientState(
                    params=params, batch_stats=bstats,
                    opt_state=trainer.opt.init(params), rng=rng)
                cs, _ = trainer.local_train(
                    cs, Xc, yc, nc, lr, epochs=o.epochs,
                    batch_size=o.batch_size, max_samples=max_samples)
                return cs.params, cs.batch_stats

            p, b = jax.vmap(local)(rngs, data.X_train, data.y_train,
                                   data.n_train)
            return p, b

        return jax.jit(ft)

    def train(self):
        cfg = self.cfg
        gs = self.init_global_state()
        per = self.broadcast_states(
            ClientState(params=gs.params, batch_stats=gs.batch_stats,
                        opt_state=None, rng=None), self.num_clients)
        per_params, per_bstats = per.params, per.batch_stats
        g_params, g_bstats = gs.params, gs.batch_stats
        history = []
        start, restored = self.restore_checkpoint()
        if restored is not None:
            per_params, per_bstats = (restored["per_params"],
                                      restored["per_bstats"])
            g_params, g_bstats = (restored["g_params"],
                                  restored["g_bstats"])
            history = restored["history"]
        for round_idx in range(start, cfg.fed.comm_round):
            M_np = self.mixing_matrix(round_idx)
            plan, plan_arrays = self.gossip_plan(M_np)
            M = jnp.asarray(M_np)
            rngs = self.per_client_rngs(round_idx,
                                        np.arange(self.num_clients))
            if self.stream is not None:
                per_params, per_bstats, g_params, g_bstats, loss = \
                    self._round_streaming(per_params, per_bstats, M,
                                          rngs, self.round_lr(round_idx),
                                          plan=plan,
                                          plan_arrays=plan_arrays)
            else:
                per_params, per_bstats, g_params, g_bstats, loss = \
                    self._round_jit_for(plan)(
                        per_params, per_bstats, self.data, M, rngs,
                        self.round_lr(round_idx), plan_arrays)
            if round_idx % cfg.fed.frequency_of_the_test == 0 \
                    or round_idx == cfg.fed.comm_round - 1:
                # the shared OBS/health boundary: record_privacy runs
                # first inside the flush (the historic dpsgd call), and
                # the stat/DP/health gauges + rule evaluation publish
                # at this already-synced point (engines/base.py)
                self._flush_nonfinite(round_idx)
                mg = self._eval_g(g_params, g_bstats)
                mp = self._eval_p(per_params, per_bstats)
                self.stat_info["global_test_acc"].append(mg["acc"])
                self.log.metrics(round_idx, train_loss=loss, global_=mg,
                                 personal=mp)
                history.append({"round": round_idx,
                                "train_loss": float(loss),
                                "global_acc": mg["acc"],
                                "personal_acc": mp["acc"]})
            if round_idx % 100 == 99 and self.stream is not None \
                    and not getattr(self, "_warned_ft_skip", False):
                self._warned_ft_skip = True
                self.log.info(
                    "streaming run: skipping the every-100-rounds "
                    "fine-tune DIAGNOSTIC pass (its models are evaluated "
                    "then discarded; no training state depends on it)")
            if round_idx % 100 == 99 and self.stream is None:
                # fine-tune pass: lr uses round=-1 (client.train(..., -1),
                # dpsgd_api.py:97 -> lr * decay^-1). Streaming runs skip
                # this DIAGNOSTIC pass (the fine-tuned models are
                # evaluated then discarded, dpsgd_api.py:101 w_per_tmp —
                # no training state depends on it); the per-round metrics
                # above stream fine.
                ft_rngs = self.per_client_rngs(-1,
                                               np.arange(self.num_clients))
                ft_p, ft_b = self._finetune_jit(g_params, g_bstats, self.data,
                                                ft_rngs, self.round_lr(-1))
                mft = self.eval_personalized(ClientState(
                    params=ft_p, batch_stats=ft_b, opt_state=None, rng=None))
                self.log.metrics(-1, finetune_after_round=round_idx,
                                 finetune_personal=mft)
            self.maybe_checkpoint(round_idx, {
                "per_params": per_params, "per_bstats": per_bstats,
                "g_params": g_params, "g_bstats": g_bstats,
                "history": history})
        return {"personal_params": per_params, "global_params": g_params,
                "history": history,
                "final_global": self._eval_g(g_params, g_bstats)}
