"""FedAvg: classical federated averaging, ABCD-adapted.

Behavior parity with fedml_api/standalone/fedavg/fedavg_api.py:40-117:
per round {seeded client sampling -> per-client local SGD from the global
model -> sample-count-weighted average}, evaluation on all clients each
``frequency_of_the_test`` rounds, and a final extra fine-tune pass over all
clients after the last aggregation (fedavg_api.py:79-88).

TPU-native design: one round = ONE jitted SPMD program. Sampled clients'
data shards are gathered along the client-sharded mesh axis, local training
runs vmapped (one client per core via the mesh), and the weighted average is
a cross-shard reduction lowered to an ICI all-reduce — there is no per-client
host round-trip of state dicts.
"""

from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
import numpy as np

from neuroimagedisttraining_tpu.core import robust
from neuroimagedisttraining_tpu.core.losses import binary_auc
from neuroimagedisttraining_tpu.core.trainer import ClientState
from neuroimagedisttraining_tpu.engines import program as round_program
from neuroimagedisttraining_tpu.engines.base import FederatedEngine
from neuroimagedisttraining_tpu.obs import names as obs_names
from neuroimagedisttraining_tpu.obs import trace as obs_trace
from neuroimagedisttraining_tpu.utils import pytree as pt


#: the round program's first extra output for a model that declares an
#: auxiliary output (core/trainer.py ``aux_counters``): int32 [experts],
#: the slots routed to each expert over the round's real steps
EXPERT_TOKENS = "expert_tokens"
#: Nemotron-H's second: the expert-layer calls of those steps whose held
#: rows passed the buffer (ops/moe.py ``held_expert_rows``)
HELD_OVERFLOW_CALLS = "held_overflow_calls"
#: Moonlight's third: the attention calls of those steps that ran as the
#: kernel (ops/attention.py ``causal_attention``; 0 on its XLA form)
ATTN_KERNEL_CALLS = "attn_kernel_calls"
#: and its fourth: those among them whose forward kernel the backward pass
#: did not run again, because the layer's rematerialisation kept the
#: kernel's outputs (models/tokens3d.py ``layer_stack``)
ATTN_OUTPUTS_KEPT = "attn_outputs_kept"


def expert_load(expert_tokens, held: tuple[int, int] | None = None,
                overflow_calls=None, capacity: int | None = None,
                skip: int | None = None, kernel_calls=None,
                outputs_kept=None) -> dict:
    """The round's expert-load counters from the round program's
    ``expert_tokens`` output, as host numbers for the ``round_log``
    span: slots routed, and the busiest and the idlest expert's load
    over the mean (1.0 = perfectly balanced). For a model that holds a
    share of its experts (``held_experts``: the first and how many;
    models/nemotronh3d.py) also ``rows_held``, the assignments that
    landed on the held experts (the rows its grouped matmuls multiply),
    and the busiest held expert over the held mean; and, from its second
    output, ``held_overflow_calls``, the calls whose held rows passed
    the buffer and took more than one window of it, beside
    ``held_capacity_rows`` (``capacity``: the rows of a training step's
    buffer; 0 where a step has none). For a router one of whose outputs
    is no expert (``skip``: its index; models/zaya3d.py) ``rows_skipped``,
    the tokens sent there, which count as routed and are left out of the
    experts' load. For a model whose attention is a kernel where the
    platform and the shapes allow (``kernel_calls``: its third output;
    models/moonlight3d.py) ``attn_kernel_calls``, the calls that took
    it, and ``attn_outputs_kept`` (``outputs_kept``: its fourth), those
    of them whose outputs the layer kept for its backward pass."""
    tokens = np.asarray(expert_tokens, np.float64)
    out = {"tokens_routed": int(tokens.sum())}
    if skip is not None:
        out["rows_skipped"] = int(tokens[skip])
    experts = tokens if skip is None else np.delete(tokens, skip)
    mean = max(float(experts.mean()), 1e-12)
    out.update(expert_load_max_over_mean=float(experts.max()) / mean,
               expert_load_min_over_mean=float(experts.min()) / mean)
    if held is not None:
        here = tokens[held[0]:held[0] + held[1]]
        out["rows_held"] = int(here.sum())
        out["held_load_max_over_mean"] = float(here.max()) / max(
            float(here.mean()), 1e-12)
    if overflow_calls is not None:
        out[HELD_OVERFLOW_CALLS] = int(overflow_calls)
        out["held_capacity_rows"] = capacity or 0
    if kernel_calls is not None:
        out[ATTN_KERNEL_CALLS] = int(kernel_calls)
    if outputs_kept is not None:
        out[ATTN_OUTPUTS_KEPT] = int(outputs_kept)
    return out


class FedAvgEngine(FederatedEngine):
    name = "fedavg"
    supports_streaming = True
    supports_wire_codec = True  # the declared round runs the codec
    # roundtrip (builder codec stage, engines/program.py)
    supports_secure_quant = True  # the declared round routes the
    # builder's default aggregate tail, which --secure_quant swaps for
    # the jitted GF(p) fold (program.secure_quant_aggregate)
    supports_byz_faults = True  # uploads route through the builder's
    # attack stage when the schedule carries byz: value faults
    supports_cohort_sharding = True  # the declared local-train stage
    # runs under the --client_mesh shard_map (ISSUE 6)
    supported_defenses = robust.DEFENSES

    def _prox_kwargs(self, global_params) -> dict:
        """Extra ``local_train`` kwargs tying the local objective to the
        round's incoming global model; FedProx overrides."""
        return {}

    # ---------- the declared round (engines/program.py) ----------

    def round_stages(self):
        """FedAvg is the builder's simplest declaration: carry the
        global model, train the sampled cohort, and let the builder run
        the attack -> codec (with EF) -> sanitize -> defend -> aggregate
        tail. The compiled programs are bitwise-equal to the pre-builder
        hand-written paths (tests/test_dispatch.py, test_cohort.py).
        The stage works on a one-client stack and routes the default
        tail, so it declares the folded placement; a model with an
        auxiliary output adds its counters to the outputs."""
        outputs = ("loss", "n_bad") + self.trainer.aux_counters
        return round_program.RoundStages(
            carry=("params", "batch_stats"),
            train=self._train_stage,
            outputs=outputs,
            uses_ef=True,
            supports_attack=True,
            folds=True,
        )

    def _train_stage(self, ctx) -> round_program.TrainOut:
        """Local-train stage: broadcast the round's incoming global model
        over the cohort and run each client's local SGD — vmapped, or as
        unbatched per-client loops under the client mesh when the program
        was built sharded (ctx.client_map; epoch permutations hoisted out
        of the partition — parallel/cohort.py)."""
        trainer = self.trainer
        o = self.cfg.optim
        params = ctx.carry["params"]
        bstats = ctx.carry["batch_stats"]
        Xs, ys, ns = ctx.Xs, ctx.ys, ctx.ns
        lr = ctx.lr
        S = Xs.shape[0]
        max_samples = self._max_samples()
        prox = self._prox_kwargs(params)
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
                perms=perms_c, **prox)

        cs, losses, *counters = ctx.client_map(
            local, cs, Xs, ys, ns,
            hoisted=(lambda: ctx.local_perms(ctx.rngs, ns, o.epochs),))
        return round_program.TrainOut(
            losses=losses,
            upload={"params": cs.params, "batch_stats": cs.batch_stats},
            state=cs, extra=dict(zip(trainer.aux_counters, counters)))

    @functools.cached_property
    def _held_capacity_rows(self) -> int | None:
        """The rows of the buffer a training batch's held runs are
        gathered into, for a model that has one."""
        capacity = getattr(self.trainer.model, "held_capacity_rows", None)
        return capacity and capacity((self.cfg.optim.batch_size,
                                      *self.sample_input().shape[1:]))

    def _expert_load(self, counters) -> dict:
        """:func:`expert_load` of one round's auxiliary outputs, with
        what the model says of its held share."""
        named = dict(zip(self.trainer.aux_counters, counters))
        return expert_load(
            named[EXPERT_TOKENS],
            getattr(self.trainer.model, "held_experts", None),
            named.get(HELD_OVERFLOW_CALLS), self._held_capacity_rows,
            getattr(self.trainer.model, "skip_output", None),
            named.get(ATTN_KERNEL_CALLS), named.get(ATTN_OUTPUTS_KEPT))

    # ---------- legacy-signature program adapters ----------
    # The builder's compiled programs take structured (carry, data,
    # consts, ...) arguments; these adapters keep the historic per-engine
    # call shapes the drivers and the bitwise-parity tests use.

    @functools.cached_property
    def _round_jit(self):
        prog = self.program.round_jit()

        def round_call(params, bstats, data, sampled_idx, rngs, lr,
                       efs=None, byz=None):
            return prog((params, bstats), data, (), sampled_idx, rngs,
                        lr, efs, byz)

        def lower(params, bstats, data, sampled_idx, rngs, lr,
                  efs=None, byz=None):
            # legacy-signature .lower passthrough (compile pins)
            return prog.jit.lower((params, bstats), data, (),
                                  sampled_idx, rngs, lr, efs, byz)

        round_call.jit = prog.jit
        round_call.lower = lower
        return round_call

    def _sharded_round_jit(self, n_real: int):
        """The cohort-sharded round program (ISSUE 6): same signature and
        donation contract as ``_round_jit``, but ``sampled_idx``/``rngs``
        cover the MESH-PADDED sampled set and the builder shards the
        local-training stage over the client mesh (``n_real`` static —
        fault-schedule cohort shrinkage re-specializes via the plan
        cache). ``deal``: the order the padded set arrives in
        (``_cohort_round_prog`` binds it; None is the sampler's)."""
        prog = self.program.round_jit(n_real=n_real)

        def sharded_round_call(params, bstats, data, sampled_idx, rngs,
                               lr, efs=None, byz=None, deal=None):
            return prog((params, bstats), data, (), sampled_idx, rngs,
                        lr, efs, byz, None, deal)

        return sharded_round_call

    @functools.cached_property
    def _round_stream_jit(self):
        prog = self.program.stream_jit()

        def stream_round_call(params, bstats, Xs, ys, ns, rngs, lr,
                              efs=None, byz=None):
            return prog((params, bstats), (), Xs, ys, ns, None, rngs,
                        lr, efs, byz)

        return stream_round_call

    def _stream_prefetch_for(self, round_idx: int) -> None:
        """Queue the streamed feed for round ``round_idx`` behind the
        current round's compute (``get_train`` re-derives the identical
        ids: sampling is deterministic in the round index)."""
        if round_idx < self.cfg.fed.comm_round:
            self.stream.prefetch_train(*self.stream_sampling(round_idx))

    def _finetune_body(self, params, bstats, X, y, n, rngs, lr):
        """Per-client fine-tune from the aggregated model over a block of
        clients (fedavg_api.py:79-88) — produces personalized models."""
        trainer = self.trainer
        o = self.cfg.optim
        C = X.shape[0]
        max_samples = self._max_samples()
        cs = ClientState(
            params=jax.tree.map(
                lambda x: jnp.broadcast_to(x, (C,) + x.shape), params),
            batch_stats=jax.tree.map(
                lambda x: jnp.broadcast_to(x, (C,) + x.shape), bstats),
            opt_state=jax.tree.map(
                lambda x: jnp.broadcast_to(x, (C,) + x.shape),
                trainer.opt.init(params)),
            rng=rngs,
        )

        def local(cs_c, Xc, yc, nc, perms_c=None):
            return trainer.local_train(
                cs_c, Xc, yc, nc, lr, epochs=o.epochs,
                batch_size=o.batch_size, max_samples=max_samples,
                perms=perms_c)

        # the final fine-tune trains EVERY client — the heaviest single
        # program of the run — so it rides the cohort-sharded mesh too
        # when armed (the full cohort already tiles the mesh: the data
        # layer pads num_clients to a device multiple; permutations
        # hoisted out of the shard_map like the round's —
        # program.cohort_local_stage)
        if self._cohort_on and C % self.mesh.devices.size == 0:
            cs, *_ = round_program.cohort_local_stage(self, local, cs,
                                                      X, y, n)
        else:
            cs, *_ = jax.vmap(local)(cs, X, y, n)
        return cs

    @functools.cached_property
    def _finetune_jit(self):
        def ft(params, bstats, data, rngs, lr):
            return self._finetune_body(params, bstats, data.X_train,
                                       data.y_train, data.n_train, rngs, lr)

        return jax.jit(ft)

    @functools.cached_property
    def _finetune_eval_jit(self):
        """The final pass over a block of clients whose personalized
        models are not kept (a streamed chunk, or the whole cohort in
        the FOLDED placement): each client is fine-tuned from the
        aggregated model and evaluated on its own test rows, under
        ``_per_client``, so in the folded placement a client's state is
        discarded before the next starts (stacked, they would be one
        model state per client). Reached only streamed or folded, and
        cohort sharding arms under neither (``RoundProgram.placement``,
        ``cohort_fallback_key``), so ``_per_client`` never takes its
        sharded arm here: ``local_train`` draws its own permutations
        below, and inside a ``shard_map`` partition it refuses to
        (``LocalTrainer.rows_alone``; tests/test_cohort.py holds the
        refusal). Returns the four per-client metric arrays
        ``_eval_personal_jit`` returns."""
        trainer = self.trainer
        o = self.cfg.optim
        max_samples = self._max_samples()

        def ft_eval(params, bstats, Xtr, ytr, ntr, Xte, yte, nte, rngs, lr):
            opt0 = trainer.opt.init(params)

            def one(Xc, yc, nc, Xt, yt, nt, rng):
                cs, *_ = trainer.local_train(
                    ClientState(params=params, batch_stats=bstats,
                                opt_state=opt0, rng=rng),
                    Xc, yc, nc, lr, epochs=o.epochs,
                    batch_size=o.batch_size, max_samples=max_samples)
                valid = jnp.arange(Xt.shape[0]) < nt
                with jax.named_scope(obs_names.SCOPE_EVAL):
                    m = trainer.evaluate(cs.params, cs.batch_stats, Xt,
                                         yt, valid)
                return (m["test_correct"], m["test_loss"],
                        m["test_total"], binary_auc(m["scores"], yt, valid))

            return self._per_client(one, Xtr, ytr, ntr, Xte, yte, nte,
                                    rngs)

        return jax.jit(ft_eval)

    def _round_iteration(self, round_idx: int, params, bstats, history):
        """One iteration of the round loop, resident or streamed: the
        host prologue, the dispatch of one round, and the boundary
        hooks. Returns ``(next_round_idx, params, bstats, history)``.
        Each stage is a host span (obs/names.py) that takes its round id
        from the caller's ``round`` span, which covers the whole
        iteration; only the ``*_sync`` spans and ``feed_wait`` wait for
        anything."""
        cfg = self.cfg
        streaming = self.stream is not None
        codec_on = self.wire_spec is not None and not streaming
        with obs_trace.span(obs_names.SPAN_ROUND_PROLOGUE):
            # elastic compute plane (ISSUE 20): a scheduled device loss
            # shrinks the mesh mid-run; resume from the donation-safe
            # checkpoint when one exists, else continue on the live
            # state over the survivors
            pre = self._maybe_preempt(round_idx)
            if pre is not None:
                if pre[1] is not None:
                    round_idx, restored = pre
                    params, bstats = (restored["params"],
                                      restored["batch_stats"])
                    history = restored["history"]
                else:
                    # no checkpoint: continue on the live state over the
                    # survivors — off the evicted devices first
                    params = self._regather_live(params)
                    bstats = self._regather_live(bstats)
                if streaming:
                    # the prefetched shards targeted the pre-preemption
                    # round; re-key the feed (a key mismatch would
                    # degrade to a fresh fetch anyway)
                    self._stream_prefetch_for(round_idx)
                if pre[1] is not None:
                    return round_idx, params, bstats, history
            if streaming:
                lr = self.round_lr(round_idx)
                ids, n_real = self.stream_sampling(round_idx)
                self.log.info("################ round %d (stream): "
                              "clients %s", round_idx,
                              ids[:n_real].tolist())
                Xs, ys, ns = self.stream.get_train(ids, n_real)
                # overlap the next round's host read with this round's
                # compute
                self._stream_prefetch_for(round_idx + 1)
                rngs = self.per_client_rngs(round_idx, ids)
                byz = self._byz_round_plan(round_idx, ids)
                self._note_round_counts(ids[:n_real], len(ids))
            else:
                lr = self.round_lr(round_idx)
                sampled = self.client_sampling(round_idx)
                self.log.info("################ round %d: clients %s",
                              round_idx, sampled.tolist())
                # cohort sharding (ISSUE 6): the sharded program gathers
                # the mesh-padded set (and takes rngs for it); the EF
                # rows, byz plan, and byte accounting stay on the REAL
                # sampled set — the body slices pads off before that tail
                ids, round_prog = self._cohort_round_prog(sampled)
                rngs = self.per_client_rngs(round_idx, ids)
                byz = self._byz_round_plan(round_idx, sampled)
                idx = jnp.asarray(ids)
                if codec_on:
                    # downlink reference snapshot BEFORE dispatch: the
                    # round donates {params, bstats} and the sampled EF
                    # rows, so nothing may read them after the call
                    with obs_trace.span(obs_names.SPAN_CODEC_SYNC):
                        ref_host = jax.tree.map(np.asarray,
                                                {"params": params,
                                                 "batch_stats": bstats})
                    efs = (pt.tree_stack_index(self._wire_ef,
                                               np.asarray(sampled))
                           if self.wire_spec.needs_ef else None)
                self._note_round_counts(sampled, len(ids))
        if streaming:
            # efs/byz stay default-bound (None) when there is no plan:
            # subclasses override the round jits with efs-free signatures
            # (turboaggregate), and an argument filled from its default
            # is never donated
            tail = () if byz is None else (None, byz)
            params, bstats, loss, n_bad, *counters = \
                self._round_stream_jit(params, bstats, Xs, ys, ns,
                                       rngs, lr, *tail)
        elif codec_on:
            (params, bstats, loss, n_bad, *counters, new_efs,
             u0) = round_prog(params, bstats, self.data, idx, rngs,
                              lr, efs, byz)
            if new_efs is not None:
                real = jnp.asarray(self._n_train_host[sampled] > 0)
                self._wire_ef = self.scatter_sampled_rows(
                    self._wire_ef, new_efs, jnp.asarray(sampled),
                    real)
            with obs_trace.span(obs_names.SPAN_CODEC_SYNC):
                self.account_wire_bytes(
                    jax.tree.map(np.asarray, u0), ref_host, None,
                    len(sampled))
        else:
            # byz plans only reach engines whose round accepts them
            # (supports_byz_faults gates at startup)
            tail = () if byz is None else (None, byz)
            params, bstats, loss, n_bad, *counters = round_prog(
                params, bstats, self.data, idx, rngs, lr, *tail)
        self._note_nonfinite(n_bad)
        if round_idx % cfg.fed.frequency_of_the_test == 0 \
                or round_idx == cfg.fed.comm_round - 1:
            m = self._eval_g(params, bstats)
            with obs_trace.span(obs_names.SPAN_ROUND_FLUSH):
                self._flush_nonfinite(round_idx)
                # the rule evaluation inside the flush may have fired
                # freeze_rollback; consume it (or pin healthy state) at
                # this host boundary, never mid-dispatch
                params, bstats = self._reflex_boundary(round_idx, params,
                                                       bstats)
            with obs_trace.span(obs_names.SPAN_ROUND_LOG) as log_span:
                self.stat_info["global_test_acc"].append(m["acc"])
                self.log.metrics(round_idx, train_loss=loss, **m)
                if counters and obs_trace.TRACER.armed:
                    # read where the round's loss is read: the round has
                    # finished (eval_sync waited for it), so no new sync
                    log_span.args.update(self._expert_load(counters))
                history.append({"round": round_idx,
                                "train_loss": float(loss), **m})
        with obs_trace.span(obs_names.SPAN_ROUND_CHECKPOINT):
            self.maybe_checkpoint(round_idx, {
                "params": params, "batch_stats": bstats,
                "history": history})
        return round_idx + 1, params, bstats, history

    def _round_loop(self, start: int, params, bstats, history):
        """Rounds ``start .. comm_round - 1``: one ``round`` span (the
        whole iteration, sampling to checkpoint) around each
        ``_round_iteration``, whose children carry the same round id."""
        cfg = self.cfg
        round_idx = start
        while round_idx < cfg.fed.comm_round:
            with obs_trace.span(obs_names.SPAN_ROUND, round=round_idx):
                round_idx, params, bstats, history = \
                    self._round_iteration(round_idx, params, bstats,
                                          history)
        self._flush_nonfinite(cfg.fed.comm_round - 1)
        return params, bstats, history

    def train(self):
        if self.stream is not None:
            return self._train_streaming()
        cfg = self.cfg
        # train_init / final_pass: what this call does outside its rounds
        # (obs/names.py); disarmed, the shared no-op
        with obs_trace.span(obs_names.SPAN_TRAIN_INIT):
            self._register_reflexes()
            start, restored = self.restore_checkpoint()
            if restored is not None:
                params, bstats = (restored["params"],
                                  restored["batch_stats"])
                history = restored["history"]
            else:
                gs = self.init_global_state()
                params, bstats = gs.params, gs.batch_stats
                history = []
            if self.wire_spec is not None and self.wire_spec.needs_ef:
                # per-client error-feedback accumulators over the FULL
                # upload payload (params + batch_stats — what the wire
                # encodes), threaded across rounds: rows for the sampled
                # set ride into the jitted round and the updated rows
                # scatter back (pads dropped)
                self._wire_ef = jax.tree.map(
                    lambda x: jnp.zeros((self.num_clients,) + x.shape,
                                        jnp.float32),
                    {"params": params, "batch_stats": bstats})
        params, bstats, history = self._round_loop(start, params, bstats,
                                                   history)
        with obs_trace.span(obs_names.SPAN_FINAL_PASS):
            # final fine-tune pass -> personalized models + final eval at
            # "-1"
            rngs = self.per_client_rngs(cfg.fed.comm_round,
                                        np.arange(self.num_clients))
            # reference passes round=-1 for this pass (fedavg_api.py:85),
            # so the fine-tune lr is lr * decay^-1, not the decayed
            # end-of-training lr
            if self.folded:
                # fine-tuned, evaluated and discarded a client at a time,
                # as the streamed pass does per chunk: no stack of
                # personalized states ("personal" is None there too)
                d = self.data
                per_states = None
                with obs_trace.span(
                        obs_names.SPAN_EVAL_DISPATCH,
                        program="finetune_eval", split="test",
                        **self._eval_span_args(d.X_test, "test")):
                    out = self._finetune_eval_jit(
                        params, bstats, d.X_train, d.y_train, d.n_train,
                        d.X_test, d.y_test, d.n_test, rngs,
                        self.round_lr(-1))
                m_global = self.eval_global(params, bstats)
                with obs_trace.span(obs_names.SPAN_EVAL_SYNC,
                                    program="finetune_eval"):
                    ci = slice(0, 1) if cfg.fed.ci else slice(None)
                    m_person = self._summarize(*(o[ci] for o in out),
                                               n=d.n_test[ci])
            else:
                per_states = self._finetune_jit(params, bstats, self.data,
                                                rngs, self.round_lr(-1))
                m_global = self.eval_global(params, bstats)
                m_person = self.eval_personalized(per_states)
            self.stat_info["person_test_acc"].append(m_person["acc"])
            self.log.metrics(-1, global_=m_global, personal=m_person)
        return {"params": params, "batch_stats": bstats,
                "personal": per_states, "history": history,
                "final_global": m_global, "final_personal": m_person}

    # ---------- streaming mode (cohort > HBM) ----------

    def _train_streaming(self):
        """Same round loop, but only the sampled clients' shards live on
        device each round (double-buffered host reads), and evaluation +
        the final fine-tune pass stream the cohort in client chunks."""
        cfg = self.cfg
        with obs_trace.span(obs_names.SPAN_TRAIN_INIT):
            self._register_reflexes()
            start, restored = self.restore_checkpoint()
            if restored is not None:
                params, bstats = (restored["params"],
                                  restored["batch_stats"])
                history = restored["history"]
            else:
                gs = self.init_global_state()
                params, bstats = gs.params, gs.batch_stats
                history = []
            self._stream_prefetch_for(start)
        params, bstats, history = self._round_loop(start, params, bstats,
                                                   history)
        with obs_trace.span(obs_names.SPAN_FINAL_PASS):
            # final fine-tune: chunked over client blocks; personalized
            # models are evaluated per block then discarded (they'd
            # exceed HBM)
            chunk = self._eval_chunk_size()
            ft_lr = self.round_lr(-1)
            per_parts, per_ns = [], []
            test_iter = self.stream.eval_chunks(chunk, "test")
            for ch in self.stream.eval_chunks(chunk, "train"):
                if self.cfg.fed.ci and per_parts:
                    break  # CI escape hatch: first chunk only
                rngs = self.per_client_rngs(cfg.fed.comm_round,
                                            ch.padded_ids)
                che = next(test_iter)
                assert np.array_equal(ch.ids, che.ids)
                out = self._finetune_eval_jit(
                    params, bstats, ch.X, ch.y, ch.n, che.X, che.y, che.n,
                    rngs, ft_lr)
                per_parts.append(tuple(np.asarray(o)[: len(ch.ids)]
                                       for o in out))
                per_ns.append(
                    np.asarray(jax.device_get(che.n))[: len(ch.ids)])
            cat = [np.concatenate([p[i] for p in per_parts])
                   for i in range(4)]
            n_cat = np.concatenate(per_ns)
            if self.cfg.fed.ci:  # client 0 only, as the resident CI path
                cat, n_cat = [c[:1] for c in cat], n_cat[:1]
            m_person = self._summarize(*cat, n=n_cat)
            m_global = self.eval_global_stream(params, bstats)
            self.stat_info["person_test_acc"].append(m_person["acc"])
            self.log.metrics(-1, global_=m_global, personal=m_person)
        return {"params": params, "batch_stats": bstats,
                "personal": None, "history": history,
                "final_global": m_global, "final_personal": m_person}
