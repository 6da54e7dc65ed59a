"""TurboAggregate: FedAvg with secure (secret-shared) aggregation.

The reference's TurboAggregate is a vanilla-FedAvg scaffold
(TA_trainer.py:38-97 — TA_topology_vanilla is an explicit stub) plus a
standalone finite-field MPC toolkit (mpc_function.py:4-275). Here the
toolkit (ops/mpc.py) is actually WIRED into the round: each sampled client's
weighted model is fixed-point-quantized into GF(p), split into additive
secret shares (Gen_Additive_SS semantics), the server accumulates each share
SLOT across all clients and only combines slots at the very end, and the
aggregate is dequantized — the server never sees an individual client's
update in the clear (every pre-final intermediate is uniformly-random
masked; tests/test_mpc.py asserts it). Exactness: the share sum equals the plain
weighted sum mod p, so the only deviation from FedAvg is fixed-point
rounding (2^-frac_bits per parameter, default 2^-16).

Local training is the same one-program SPMD round as FedAvg. The MPC stage
runs on the accelerator by default (ops/mpc_device.py: the quantize /
share / slot-accumulate pipeline as jitted uint32 mod-p ops — no host
round-trip); ``mpc_backend="host"`` keeps the numpy path that models the
client<->server communication boundary (which the multi-aggregator
cross-silo deployment exercises over real processes).
"""

from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
import numpy as np

from neuroimagedisttraining_tpu.core import robust
from neuroimagedisttraining_tpu.core.trainer import ClientState
from neuroimagedisttraining_tpu.engines.fedavg import FedAvgEngine
from neuroimagedisttraining_tpu.ops import mpc


class TurboAggregateEngine(FedAvgEngine):
    name = "turboaggregate"
    # Streaming (cohort > HBM): the train-only stage consumes just the
    # sampled clients' shards (FedAvg's streaming shape); the MPC stage
    # follows mpc_backend (device-jitted by default). The streamed round
    # loop itself is inherited from FedAvgEngine._train_streaming via
    # _round_stream_jit below.
    supports_streaming = True
    # Inherits FedAvgEngine's train loop but NOT its codec branch: its
    # round replaces the plain aggregation with the MPC share pipeline,
    # whose GF(p) field embedding the codec's delta/top-k/quant stages
    # would corrupt (same incompatibility as cross_silo's
    # SecureFedAvgServer, and the inherited codec call path would pass
    # this engine's 6-arg round program 7 args anyway).
    supports_wire_codec = False
    # Byzantine simulation + order-statistic defenses are likewise OUT:
    # secure aggregation is a linear sum over additive shares — the
    # server never observes individual updates, so trimmed-mean/Krum
    # style order statistics have nothing to select over (the same
    # tension ARCHITECTURE.md's Byzantine-robustness section documents
    # for cross_silo's SecureFedAvgServer). Clipping still composes:
    # each silo clips its OWN update before sharing it.
    supports_byz_faults = False
    # Cohort sharding (ISSUE 6) is likewise out: the round crosses the
    # host for the MPC share pipeline every round (the client<->server
    # boundary is the point), and this engine overrides the round
    # programs the sharded driver would dispatch — --client_mesh falls
    # back to the unsharded round with the logged reason below.
    supports_cohort_sharding = False
    supported_defenses = robust.CLIP_DEFENSES

    def round_stages(self):
        # no declared stages: the round is a host-driven two-stage
        # dispatch (train program -> MPC share/aggregate program with a
        # per-round host-side mask seed), which the builder cannot
        # express — the override below names the table reason
        return None

    def cohort_fallback_key(self) -> str | None:
        return "mpc-host-boundary"

    def _train_only_body(self, params, bstats, Xs, ys, ns, rngs, lr):
        """Local training WITHOUT the in-program aggregation: returns the
        stacked client params (pre-weighted by n_c / sum n) for the MPC
        stage, plus the plain-averaged batch_stats (BN stats are not secret-
        shared — parity with robust aggregation's is_weight_param exclusion)."""
        trainer = self.trainer
        o = self.cfg.optim
        max_samples = self._max_samples()
        S = Xs.shape[0]
        cs = ClientState(
            params=jax.tree.map(
                lambda x: jnp.broadcast_to(x, (S,) + x.shape), params),
            batch_stats=jax.tree.map(
                lambda x: jnp.broadcast_to(x, (S,) + x.shape), bstats),
            opt_state=jax.tree.map(
                lambda x: jnp.broadcast_to(x, (S,) + x.shape),
                trainer.opt.init(params)),
            rng=rngs,
        )

        def local(cs_c, Xc, yc, nc):
            return trainer.local_train(
                cs_c, Xc, yc, nc, lr, epochs=o.epochs,
                batch_size=o.batch_size, max_samples=max_samples)

        cs, losses = jax.vmap(local)(cs, Xs, ys, ns)
        w = ns.astype(jnp.float32)
        # non-finite upload guard (ISSUE 5 satellite): a NaN client
        # would poison the GF(p) quantization AND the plain bstats mean;
        # its row becomes the broadcast reference at weight 0
        upload = {"params": cs.params, "batch_stats": cs.batch_stats}
        ref = {"params": params, "batch_stats": bstats}
        finite = robust.finite_per_client(upload)
        upload = robust.replace_nonfinite_clients(upload, ref, finite)
        n_bad = jnp.sum(~finite).astype(jnp.int32)
        w = w * finite.astype(jnp.float32)
        wn = w / jnp.maximum(jnp.sum(w), 1e-12)
        # robust defenses apply BEFORE weighting/sharing, same stage as
        # FedAvgEngine._round_body (clipping composes with secure agg:
        # each silo clips its own update before secret-sharing it)
        f = self.cfg.fed
        client_params = robust.defend_stacked(
            upload["params"], params, defense=f.defense_type,
            norm_bound=f.norm_bound, stddev=f.stddev, rngs=cs.rng)
        weighted = jax.tree.map(
            lambda x: x.astype(jnp.float32)
            * wn.reshape((-1,) + (1,) * (x.ndim - 1)), client_params)
        # batch_stats are not secret-shared; route them through the
        # silo-aware aggregate so the non-MPC half of the round keeps the
        # two-level ICI/DCN layout (params cross the host MPC boundary
        # regardless — that boundary IS the cross-silo link)
        new_bstats = self.aggregate(upload["batch_stats"], w)
        safe_losses = jnp.where(jnp.isfinite(losses), losses, 0.0)
        mean_loss = jnp.sum(safe_losses * w) / jnp.maximum(jnp.sum(w),
                                                           1e-9)
        return weighted, new_bstats, mean_loss, n_bad

    @functools.cached_property
    def _train_only_jit(self):
        def round_fn(params, bstats, data, sampled_idx, rngs, lr):
            Xs = jnp.take(data.X_train, sampled_idx, axis=0)
            ys = jnp.take(data.y_train, sampled_idx, axis=0)
            ns = jnp.take(data.n_train, sampled_idx, axis=0)
            return self._train_only_body(params, bstats, Xs, ys, ns, rngs,
                                         lr)

        # donation: bstats only — the [S, ...]-stacked ``weighted`` output
        # has no input of matching shape, so donating ``params`` would be
        # an unusable donation (ignored with a warning), and the wrapper
        # below never rereads either input after dispatch
        return jax.jit(round_fn, donate_argnums=self._donate_argnums(1))

    @functools.cached_property
    def _train_only_stream_jit(self):
        return jax.jit(self._train_only_body,
                       donate_argnums=self._donate_argnums(1))

    @functools.cached_property
    def _secure_agg_jit(self):
        from neuroimagedisttraining_tpu.ops import mpc_device

        f = self.cfg.fed

        def agg(weighted, key):
            return mpc_device.secure_aggregate_tree(
                weighted, key, f.mpc_n_shares, frac_bits=f.mpc_frac_bits)

        return jax.jit(agg)

    def secure_aggregate(self, weighted_stacked, call_idx: int):
        """Additive-share aggregation over GF(p): quantize each client's
        weighted update, share it ``mpc_n_shares`` ways, accumulate
        slot-major (share slot j across ALL clients before combining any
        slots), reconstruct. No server-side intermediate equals an
        individual client's quantized update (tested in tests/test_mpc.py
        for both backends).

        Default backend "device" runs the whole pipeline as jitted uint32
        mod-p ops on the accelerator (ops/mpc_device.py) — no host
        round-trip, round time ~FedAvg's (VERDICT r4 weak #3). Backend
        "host" keeps the numpy toolkit path that models the
        client<->server boundary (and is what the multi-aggregator
        cross-silo deployment exercises over real processes).

        The share randomness cancels EXACTLY in the sum (additive shares by
        construction), so the aggregate is independent of ``call_idx``/rng —
        the seed only decorrelates the masking material across calls."""
        f = self.cfg.fed
        if f.mpc_backend == "device":
            key = jax.random.fold_in(
                jax.random.key(self.cfg.seed * 7919 + 1), call_idx)
            return self._secure_agg_jit(weighted_stacked, key)
        if f.mpc_backend != "host":
            raise ValueError(f"unknown mpc_backend {f.mpc_backend!r} "
                             "(device | host)")
        rng = np.random.default_rng(self.cfg.seed * 7919 + call_idx)
        leaves, treedef = jax.tree.flatten(weighted_stacked)
        # ONE batched device_get for the whole tree: every copy_to_host
        # is issued before any blocks, so the per-leaf transfer round
        # trips overlap instead of serializing with the MPC compute
        # (~16 leaves, one host sync instead of 16). The rng draw
        # order (per leaf, per client) is unchanged, so the aggregate is
        # bitwise-identical to the per-leaf formulation.
        host = [np.asarray(x) for x in jax.device_get(leaves)]  # [S, ...] each
        agg = [mpc.secure_sum(arr, n_shares=f.mpc_n_shares,
                              frac_bits=f.mpc_frac_bits, rng=rng)
               .astype(np.float32) for arr in host]
        out = jax.device_put(agg)  # one batched upload
        return jax.tree.unflatten(treedef, out)

    # mask-material seed counter; the aggregate itself is rng-independent
    # (see secure_aggregate), so resume determinism of the training result
    # is unaffected. Instance assignment (+= 1) shadows the class default.
    _mpc_calls = 0

    @functools.cached_property
    def _round_jit(self):
        """FedAvg's round program signature, with the aggregation swapped
        for the MPC path (two jitted stages on the default device backend;
        a host callback between them on mpc_backend='host')."""
        train_only = self._train_only_jit

        def round_fn(params, bstats, data, sampled_idx, rngs, lr):
            weighted, new_bstats, loss, n_bad = train_only(
                params, bstats, data, sampled_idx, rngs, lr)
            new_params = self.secure_aggregate(weighted, self._mpc_calls)
            self._mpc_calls += 1
            return new_params, new_bstats, loss, n_bad

        return round_fn  # wrapper (not one jit): tracks _mpc_calls and
        # dispatches the MPC stage per mpc_backend

    @functools.cached_property
    def _round_stream_jit(self):
        """Streamed counterpart consumed by the inherited
        FedAvgEngine._train_streaming loop: jitted train-only stage on the
        host-fetched shards, then the host-side MPC aggregation."""
        train_only = self._train_only_stream_jit

        def round_fn(params, bstats, Xs, ys, ns, rngs, lr):
            weighted, new_bstats, loss, n_bad = train_only(
                params, bstats, Xs, ys, ns, rngs, lr)
            new_params = self.secure_aggregate(weighted, self._mpc_calls)
            self._mpc_calls += 1
            return new_params, new_bstats, loss, n_bad

        return round_fn
