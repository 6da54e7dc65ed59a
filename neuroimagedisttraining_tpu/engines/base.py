"""Shared federated-simulation substrate for all algorithm engines.

Every reference engine has the same shape (SURVEY.md §2.4): constructor takes
the dataset + trainer, ``.train()`` runs ``comm_round`` rounds of
{sample clients -> local train -> aggregate -> evaluate}. Here that shape is
factored once: subclasses provide jitted round programs; this base provides
model/state initialization, reference-parity client sampling
(np.random.seed(round_idx), fedavg_api.py:92-100), full-cohort evaluation
(global + personalized, sailentgrads_api.py:231-285), metrics logging, and
the ``stat_info`` accumulators (sailentgrads_api.py:334-346).
"""

from __future__ import annotations

import dataclasses
import functools
from typing import Any

import jax
import jax.numpy as jnp
import numpy as np

from neuroimagedisttraining_tpu.codec import wire as codec_wire
from neuroimagedisttraining_tpu.config import ExperimentConfig
from neuroimagedisttraining_tpu.core import robust
from neuroimagedisttraining_tpu.core.losses import binary_auc
from neuroimagedisttraining_tpu.core.trainer import (
    ClientState, LocalTrainer, scan_steps,
)
from neuroimagedisttraining_tpu.core.optim import round_lr
from neuroimagedisttraining_tpu.data.federate import FederatedData
from neuroimagedisttraining_tpu.faults import adversary
from neuroimagedisttraining_tpu.faults.schedule import (
    FaultSchedule, parse_fault_spec,
)
from neuroimagedisttraining_tpu.engines import program as round_program
from neuroimagedisttraining_tpu.obs import actions as obs_actions
from neuroimagedisttraining_tpu.obs import compute as obs_compute
from neuroimagedisttraining_tpu.obs import flight as obs_flight
from neuroimagedisttraining_tpu.obs import health as obs_health
from neuroimagedisttraining_tpu.obs import metrics as obs_metrics
from neuroimagedisttraining_tpu.obs import names as obs_names
from neuroimagedisttraining_tpu.obs import rules as obs_rules
from neuroimagedisttraining_tpu.obs import trace as obs_trace
from neuroimagedisttraining_tpu.parallel import cohort
from neuroimagedisttraining_tpu.parallel.mesh import (
    client_sharding, make_mesh, replicated_sharding,
)
from neuroimagedisttraining_tpu.utils import checkpoint as ckpt
from neuroimagedisttraining_tpu.utils.logging import ExperimentLogger, get_logger
from neuroimagedisttraining_tpu.utils import pytree as pt

PyTree = Any


class FederatedEngine:
    """Base class: owns config, trainer, data, mesh, logging, eval."""

    name = "base"
    supports_streaming = False  # engines opt in (need all-client state
    # resident otherwise)
    #: engines whose round program applies the wire codec's lossy
    #: roundtrip to client uploads before aggregation (codec/, ISSUE 3);
    #: others must reject --wire_codec loudly instead of silently
    #: training dense while reporting encoded-bytes accounting of 0
    supports_wire_codec = False
    #: engines whose round program routes client uploads through
    #: faults/adversary.py when the fault schedule carries ``byz:``
    #: value faults (ISSUE 5); others must reject such a spec loudly
    #: instead of silently simulating an attack-free federation
    supports_byz_faults = False
    #: defenses this engine's round program can realize; anything else
    #: in --defense fails at STARTUP, never mid-round (ISSUE 5
    #: satellite). Base engines aggregate with a plain weighted mean and
    #: support no defense at all.
    supported_defenses: tuple = ("none",)
    #: engines whose round body can run its local-training stage under
    #: the cohort-sharded client mesh (``--client_mesh``, ISSUE 6,
    #: parallel/cohort.py); others fall back to the unsharded round with
    #: a logged reason
    supports_cohort_sharding = False
    #: engines whose round program realizes the --dp_clip/--dp_sigma
    #: round-level DP transform (clip each client's update delta, add
    #: Gaussian noise from config-folded jax keys — privacy/, ISSUE 8);
    #: others must reject the flags loudly instead of silently training
    #: without the noise the accountant would be charging for
    supports_dp = False
    #: engines whose declared round routes the builder's DEFAULT
    #: sanitize/defend/aggregate tail — exactly the engines where
    #: ``--secure_quant`` can swap that tail for the in-process secure
    #: QUANTIZED aggregation stage (ROADMAP 1(b),
    #: program.secure_quant_aggregate); engines with a custom aggregate
    #: stage (or none) have no server fold for the field algebra to
    #: protect and must reject the flag loudly
    supports_secure_quant = False

    def __init__(self, cfg: ExperimentConfig, fed_data: FederatedData | None,
                 trainer: LocalTrainer, mesh=None,
                 logger: ExperimentLogger | None = None, stream=None):
        """``fed_data``: device-resident federation, or None when running in
        streaming mode with a ``StreamingFederation`` (cohort > HBM)."""
        self.cfg = cfg
        self.data = fed_data
        self.stream = stream
        self.trainer = trainer
        self.mesh = mesh
        self.log = logger or ExperimentLogger(cfg.log_dir, cfg.data.dataset,
                                              cfg.identity())
        self._console = get_logger()
        # reflex plane (ISSUE 20, obs/actions.py): engine-side state the
        # registered action handlers mutate. Initialized EARLY — the
        # ctor below may build round programs, and the builder's
        # aggregate tail reads ``active_defense()`` at trace time.
        # Quarantine windows are (from_round, until_round) pairs keyed
        # by client: a pure function of the round index, so
        # ``record_privacy``'s cohort re-derivation replays exactly the
        # cohorts training used (windows only ever start AFTER the
        # round that fired them).
        self._quarantine_windows: dict[int, list[tuple[int, int]]] = {}
        self._sampled_by_round: dict[int, np.ndarray] = {}
        self._last_health_rows: dict[int, dict] = {}
        self._defense_override: str | None = None
        self._healthy_pin: dict | None = None
        self._pending_rollback: dict | None = None
        self._preempts_done: set[int] = set()
        if stream is not None and not self.supports_streaming:
            from neuroimagedisttraining_tpu.engines import ENGINES
            ok = sorted({c.name for c in ENGINES.values()
                         if c.supports_streaming})
            raise ValueError(
                f"algorithm {self.name!r} does not support --streaming "
                "(its round needs every client's DATA device-resident, not "
                f"just its state); streaming currently supports: {ok}")
        if fed_data is not None:
            self.num_clients = int(fed_data.num_clients)  # incl. mesh padding
            source = fed_data
        elif stream is not None:
            self.num_clients = int(stream.num_clients)
            source = stream
        else:
            raise ValueError("need fed_data or stream")
        # every split's real rows a client, read to the host once
        self._n_host = {
            split: np.asarray(getattr(source, f"n_{split}"))
            for split in ("train", "test", "val")
            if getattr(source, f"n_{split}", None) is not None}
        self._n_train_host = self._n_host["train"]
        self.real_clients = int(np.sum(self._n_train_host > 0))
        # deterministic fault injection (faults/): the SAME seeded
        # schedule that drives the multiprocess federation filters the
        # simulated round's cohort, so one config seed replays one fault
        # trace in both worlds (engine client index c == rank c + 1)
        spec = (parse_fault_spec(cfg.fed.fault_spec)
                if cfg.fed.fault_spec else None)
        self.fault_schedule = (FaultSchedule(spec, cfg.seed)
                               if spec is not None and spec.any_faults
                               else None)
        if spec is not None and spec.any_value_faults \
                and not self.supports_byz_faults:
            from neuroimagedisttraining_tpu.engines import ENGINES
            ok = sorted({c.name for c in ENGINES.values()
                         if c.supports_byz_faults})
            raise ValueError(
                f"algorithm {self.name!r} does not simulate byz: value "
                "faults (its round program does not route client "
                "uploads through faults/adversary.py, so the spec "
                f"would silently run attack-free); supported: {ok}")
        # defense validation at STARTUP (ISSUE 5 satellite): an unknown
        # --defense name, or one this engine's round cannot realize,
        # must fail here — not as a trace error mid-round
        robust.validate_defense(cfg.fed.defense_type)
        if cfg.fed.defense_type not in self.supported_defenses:
            raise ValueError(
                f"algorithm {self.name!r} does not support --defense "
                f"{cfg.fed.defense_type!r}; this engine supports: "
                f"{', '.join(self.supported_defenses)}")
        if cfg.fed.defense_type in robust.ROBUST_AGGREGATORS:
            # surface breakdown-point violations (2f >= n, n < f+3)
            # before any data loads rather than at first-trace time
            robust._check_f(cfg.fed.client_num_per_round,
                            cfg.fed.byz_f, cfg.fed.defense_type)
        # round-level DP (--dp_clip/--dp_sigma, privacy/ ISSUE 8) fails
        # at STARTUP on engines whose round never applies the transform:
        # an unapplied noise config with a running accountant would
        # report epsilon for privacy nobody got
        if cfg.fed.dp_sigma < 0 or cfg.fed.dp_clip < 0:
            raise ValueError(
                f"dp_sigma/dp_clip must be >= 0 (got "
                f"{cfg.fed.dp_sigma}/{cfg.fed.dp_clip})")
        if cfg.fed.dp_sigma > 0 and cfg.fed.dp_clip <= 0:
            raise ValueError(
                "--dp_sigma needs --dp_clip > 0: the clip bound IS the "
                "sensitivity the noise multiplier is stated against "
                "(privacy/accountant.py)")
        if (cfg.fed.dp_sigma > 0 or cfg.fed.dp_clip > 0) \
                and not self.supports_dp:
            from neuroimagedisttraining_tpu.engines import ENGINES
            ok = sorted({c.name for c in ENGINES.values()
                         if c.supports_dp})
            raise ValueError(
                f"algorithm {self.name!r} does not apply the "
                "--dp_clip/--dp_sigma round-level DP transform (its "
                "round program would train un-noised while the "
                f"accountant reported epsilon); supported: {ok}")
        #: privacy ledger (privacy/accountant.py): per-round RDP of the
        #: armed noise path — weak_dp defense (subsampled cohorts) or
        #: the engine DP transform (full participation) — recorded
        #: through ``record_privacy`` at host boundaries
        self._dp_rdp = None
        self._dp_recorded_through = -1
        # wire codec (codec/, ISSUE 3): the lossy value transform the
        # cross-silo wire would apply to this engine's uploads, run
        # in-sim before aggregation so round metrics reflect the encoded
        # deployment; engines that own pruning masks hand them to the
        # codec via wire_masks() (mask handoff)
        self.wire_spec = codec_wire.parse_wire_spec(
            cfg.fed.wire_codec, cfg.fed.wire_topk_ratio)
        if self.wire_spec is not None and not self.supports_wire_codec:
            from neuroimagedisttraining_tpu.engines import ENGINES
            ok = sorted({c.name for c in ENGINES.values()
                         if c.supports_wire_codec})
            raise ValueError(
                f"algorithm {self.name!r} does not simulate --wire_codec "
                "(its round program does not pass client uploads through "
                "the codec roundtrip, so the flag would silently train "
                f"dense); supported: {ok}. Masked engines still expose "
                "wire_masks() for the cross-silo plane "
                "(distributed/run.py), where the codec runs for real.")
        if self.wire_spec is not None and stream is not None:
            raise ValueError(
                "--wire_codec currently simulates the encoded wire on "
                "the device-resident path only; streaming rounds "
                "(--streaming) keep the dense in-mesh aggregation — the "
                "real encoded transport lives in distributed/run.py")
        # in-process secure QUANTIZED aggregation (privacy/, ROADMAP
        # 1(b)): --secure_quant swaps the builder's sanitize/defend/
        # aggregate tail for the jitted GF(p) integer-weight fold
        # (program.secure_quant_aggregate) — bitwise the host
        # SlotAccumulator fold at the same (p, frac_bits, weights).
        # Every incompatibility fails HERE (startup), never mid-round.
        self.sq_spec = None
        self.sq_weight_shift = 0
        if cfg.fed.secure_quant:
            from neuroimagedisttraining_tpu.privacy import (
                QuantSpec, check_headroom,
            )
            from neuroimagedisttraining_tpu.privacy.secure_quant import (
                WEIGHT_FRAC_BITS, weighted_fold_capacity,
            )

            if not self.supports_secure_quant:
                from neuroimagedisttraining_tpu.engines import ENGINES
                ok = sorted({c.name for c in ENGINES.values()
                             if c.supports_secure_quant})
                raise ValueError(
                    f"algorithm {self.name!r} does not simulate "
                    "--secure_quant: its round has no default "
                    "server-side aggregation tail for the field fold to "
                    f"replace; supported: {ok}. The encoded secure wire "
                    "itself lives on the cross-silo/async planes "
                    "(distributed/run.py)")
            if self.wire_spec is not None:
                raise ValueError(
                    "--secure_quant does not compose with --wire_codec: "
                    "the codec's float stages would corrupt the GF(p) "
                    "residue embedding (field-element frames, not model "
                    "floats) — ARCHITECTURE.md 'Privacy plane'")
            if cfg.fed.defense_type in robust.ROBUST_AGGREGATORS:
                raise ValueError(
                    f"--defense {cfg.fed.defense_type} does not compose "
                    "with --secure_quant (no per-client plaintext to "
                    "select over); the clip family (norm_diff_clipping, "
                    "weak_dp) composes CLIENT-side pre-quantize — "
                    "ARCHITECTURE.md 'Privacy plane'")
            spec = QuantSpec.from_bits(cfg.fed.secure_quant_field_bits,
                                       cfg.fed.secure_quant_frac_bits)
            check_headroom(spec, cfg.fed.client_num_per_round)
            # the one-phase integer-weight fold (the async server's and
            # the sharded ingest plane's algebra): pick the largest
            # STATIC weight shift whose worst-case mass keeps the
            # aggregate inside the field's centered range — per-round
            # weights then fold exactly for the whole run
            cap = weighted_fold_capacity(spec)
            cohort = max(1, int(cfg.fed.client_num_per_round))
            shift = None
            for s in range(WEIGHT_FRAC_BITS, -1, -1):
                if cohort * (1 << s) < cap:
                    shift = s
                    break
            if shift is None:
                raise ValueError(
                    f"--secure_quant field too small for the in-process "
                    f"integer-weight fold: a {cohort}-client cohort "
                    f"exceeds the {cfg.fed.secure_quant_field_bits}-bit "
                    f"field's capacity of {cap:.1f} weight units — pass "
                    "--secure_quant_field_bits 32 (the same requirement "
                    "as the buffered one-phase path; ARCHITECTURE.md "
                    "'Privacy plane')")
            self.sq_spec = spec
            self.sq_weight_shift = int(shift)
            # materialize the static per-leaf scales NOW, outside any
            # trace: a lazy first touch would run the jitted model init
            # inside the round trace (tracer leaves -> leaf_scales'
            # host max() raises TracerArrayConversionError)
            _ = self.sq_scales
        self.stat_info: dict[str, Any] = {
            "sum_comm_params": 0.0, "sum_training_flops": 0.0,
            "sum_comm_bytes": 0.0, "sum_comm_bytes_dense": 0.0,
            "nonfinite_uploads": 0.0,
            "global_test_acc": [], "person_test_acc": [],
            "final_masks": [],
        }
        self._dense_upload_nbytes: int | None = None
        #: device-side non-finite-upload counts queued per round; synced
        #: in one batched device_get at host boundaries (_flush_nonfinite)
        self._nonfinite_pending: list = []
        #: in-dispatch training-health stats queued per dispatch (ISSUE
        #: 15): one ``{stat: device array}`` entry a round, which the
        #: builder's dispatch wrapper appends; drained in the SAME
        #: batched device_get as the non-finite counts — never a
        #: per-round sync
        self._health_pending: list = []
        #: host integers of the round about to be dispatched
        #: (``_note_round_counts``), taken as arguments by the next
        #: ``dispatch_program`` span; empty while the tracer is disarmed
        self._dispatch_counts: dict = {}
        #: monotonic sequence / round watermark of the metrics JSONL
        #: sink (ISSUE 15 satellite: every record carries a round +
        #: seq so run_report joins series without timestamp heuristics)
        self._metrics_seq = 0
        self._metrics_last_round: int | None = None
        # cohort sharding (--client_mesh, ISSUE 6): hard config errors
        # fail here; engines/modes whose rounds cannot shard announce the
        # unsharded fallback ONCE, up front
        self._cohort_on = False
        cm = int(cfg.fed.client_mesh)
        if cm > 0:
            if mesh is None:
                raise ValueError(
                    f"--client_mesh {cm} requested but no device mesh was "
                    "constructed — build the engine with a mesh (the CLIs "
                    "do this automatically; tests: make_mesh())")
            if cm != mesh.devices.size:
                raise ValueError(
                    f"--client_mesh {cm} does not match the constructed "
                    f"{mesh.devices.size}-device mesh; pass a matching "
                    "--client_mesh / --mesh_shape / --virtual_devices "
                    "combination (the sampled-client axis shards over "
                    "EVERY mesh device)")
            key = self.program.cohort_fallback_key()
            if key is None:
                self._cohort_on = True
                self.log.info(
                    "client_mesh=%d: cohort sharding armed — the sampled-"
                    "client axis of every round program shards over the "
                    "%d-device mesh (pad rows zero-weighted, aggregation "
                    "on all-gathered stacks; parallel/cohort.py)",
                    cm, mesh.devices.size)
            else:
                # announced ONCE, up front, AND counted: the structured
                # nidt_fallback_total{plane,engine,reason} counter makes
                # fast-path coverage scrapeable (engines/program.py)
                self.log.info(
                    "client_mesh=%d requested; running the unsharded "
                    "round program: %s", cm,
                    round_program.report_fallback(self.name, key))
        # Mosaic kernels cannot be partitioned automatically (jax refuses
        # the lowering: "wrap the call in a shard_map"), so on a TPU mesh
        # of several devices the fused Pallas tail lowers only inside
        # the cohort-sharded round's shard_map. Refuse the combination
        # here, with the resolution named, not deep in the first trace.
        n_mesh = 1 if mesh is None else mesh.devices.size
        if (cfg.optim.fused_update and n_mesh > 1 and not self._cohort_on
                and jax.default_backend() == "tpu"):
            raise ValueError(
                f"--fused_update on a {n_mesh}-device TPU mesh needs the "
                f"cohort-sharded round (--client_mesh {n_mesh}): its "
                "Pallas kernel cannot be partitioned by GSPMD. Add "
                f"--client_mesh {n_mesh}, or drop --fused_update, or pin "
                "one device with --mesh_shape 1")

    # ---------- state init ----------

    def sample_input(self) -> jax.Array:
        if self.data is not None:
            shape = self.data.X_train.shape[2:]
        else:
            shape = self.stream.sample_shape
        return jnp.zeros((1,) + tuple(shape), jnp.float32)

    def init_global_state(self) -> ClientState:
        """The initial global state, placed where the round programs
        leave their outputs: replicated over the mesh and committed. An
        uncommitted initial state gave the first dispatch another
        signature than every later one, so the round program was traced,
        lowered and compiled twice a run (the dispatch rows of a run on
        the v5e: 9.6 s, then 7.1-8.0 s, then 1 ms; PERF.md, PR 23)."""
        rng = jax.random.key(self.cfg.seed)
        cs = self.trainer.init_client_state(rng, self.sample_input())
        if self.mesh is None:
            return cs
        return jax.device_put(
            cs, jax.sharding.NamedSharding(self.mesh,
                                           jax.sharding.PartitionSpec()))

    def broadcast_states(self, cs: ClientState, n: int) -> ClientState:
        """Replicate one state across a leading client axis of size n."""
        return jax.tree.map(
            lambda x: jnp.broadcast_to(x, (n,) + x.shape).copy()
            if hasattr(x, "shape") else x, cs)

    def per_client_rngs(self, round_idx: int, idx: np.ndarray) -> jax.Array:
        # +1 so the pre-training phase (round_idx=-1, SNIP scoring) folds a
        # valid uint32
        base = jax.random.fold_in(jax.random.key(self.cfg.seed + 17),
                                  round_idx + 1)
        return jax.vmap(lambda i: jax.random.fold_in(base, i))(
            jnp.asarray(idx, jnp.uint32))

    # ---------- sampling (reference parity) ----------

    def client_sampling(self, round_idx: int) -> np.ndarray:
        """np.random.seed(round_idx); choice without replacement
        (fedavg_api.py:92-100). Sampling is over REAL clients only; mesh
        padding clients never train."""
        total = self.real_clients
        per_round = min(self.cfg.fed.client_num_per_round, total)
        if total == per_round:
            sampled = np.arange(total)
        else:
            # nidt: allow[determinism-global-random] -- reference-parity
            # sampling shim: MUST replay the legacy global stream
            # (fedavg_api.py:92-100) to keep client cohorts bit-identical
            np.random.seed(round_idx)  # nidt: allow[determinism-global-random] -- reference-parity shim (fedavg_api.py:92-100)
            sampled = np.sort(np.random.choice(range(total), per_round,  # nidt: allow[determinism-global-random] -- reference-parity shim (fedavg_api.py:92-100)
                                               replace=False))
        if self.fault_schedule is not None:
            # crashed clients drop out of the cohort; the weighted
            # aggregation over the survivor set re-weights by sample
            # count exactly as a frac-sampled round would
            sampled = self.fault_schedule.survivors(round_idx, sampled)
        if self._quarantine_windows:
            # reflex quarantine (ISSUE 20): clients inside an active
            # window drop out of the cohort, same re-weighting as a
            # crash. If every sampled client is quarantined the filter
            # is skipped — an empty round has no reference semantics
            # (the survivors() rule).
            keep = np.asarray(
                [not self._is_quarantined(int(c), round_idx)
                 for c in np.asarray(sampled)], bool)
            if keep.any():
                sampled = np.asarray(sampled)[keep]
        self._stash_bounded(self._sampled_by_round, int(round_idx),
                            np.asarray(sampled))
        if len(sampled) == 0:
            # ADVICE r5: an empty cohort used to surface as a bare
            # IndexError from stream_sampling's ``sampled[-1]`` pad fill
            # (or as shape-0 gathers in the resident round) — fail with
            # the configuration that caused it instead
            raise ValueError(
                f"round {round_idx}: the sampled client set is empty — "
                f"client_num_per_round={per_round} and the fault "
                f"schedule ({self.cfg.fed.fault_spec!r}) left no "
                "survivors; raise --frac / --client_num_in_total or "
                "reduce the crash coverage in --fault_spec")
        return sampled

    def stream_sampling(self, round_idx: int,
                        sampled: np.ndarray | None = None
                        ) -> tuple[np.ndarray, int]:
        """``(padded_ids, n_real)`` for the streamed sharded feed: the
        round's sampled set padded to tile the mesh (the north-star config
        — 100 clients, frac 0.1 — samples 10 clients onto an 8-device
        grid). Pad entries prefer mesh-padding clients (rows
        [real_clients, num_clients), n_train == 0) and then repeat the
        last sampled id; either way the feed zeroes their fetched sample
        counts (``n_real``), so pads train as masked no-ops and weigh 0 in
        aggregation. The streamed feed keeps the sampler's order: its
        rows are batched over the mesh (``vmap`` under GSPMD; cohort
        sharding does not arm under streaming), every batched row walks
        the longest row's steps wherever it sits, and so there is
        nothing for ``cohort.deal_rows`` to balance here. Engines that scatter per-client state by sampled id
        must route through ``scatter_sampled_rows`` (pad entries dropped).
        Pass ``sampled`` when the round's set was already computed."""
        if sampled is None:
            sampled = self.client_sampling(round_idx)
        if len(sampled) == 0:
            raise ValueError(
                f"round {round_idx}: stream_sampling got an empty "
                "sampled set — no clients to pad the mesh tile from "
                "(see client_sampling: fault schedules can empty the "
                "cohort; this is a configuration error, not a crash)")
        if self.mesh is None:
            return sampled, len(sampled)
        return cohort.pad_cohort(sampled, self.real_clients,
                                 self.num_clients, self.mesh.devices.size)

    def scatter_sampled_rows(self, all_tree, new_tree, sampled_idx, real):
        """Write the sampled clients' new rows into the [C, ...] stacked
        state. Pad entries (``real`` False — stream_sampling's mesh-tiling
        pads, possibly DUPLICATE ids of a real client) are redirected to
        an out-of-range index and dropped (``mode="drop"``), so no pad
        write can land on — let alone clobber, via scatter's last-wins
        duplicate resolution — a real client's freshly trained row."""
        idx = jnp.where(real, sampled_idx, self.num_clients)
        return jax.tree.map(
            lambda allp, newp: allp.at[idx].set(newp, mode="drop"),
            all_tree, new_tree)

    # ---------- evaluation ----------

    @functools.cached_property
    def _eval_global_jit(self):
        trainer = self.trainer

        def eval_all(params, bstats, X, y, n):
            def per_client(Xc, yc, nc):
                valid = jnp.arange(Xc.shape[0]) < nc
                m = trainer.evaluate(params, bstats, Xc, yc, valid)
                auc = binary_auc(m["scores"], yc, valid)
                return m["test_correct"], m["test_loss"], m["test_total"], auc

            with jax.named_scope(obs_names.SCOPE_EVAL):
                return self._per_client(per_client, X, y, n)

        return jax.jit(eval_all)

    @functools.cached_property
    def _eval_personal_jit(self):
        trainer = self.trainer

        def eval_all(params, bstats, X, y, n):
            def per_client(p, b, Xc, yc, nc):
                valid = jnp.arange(Xc.shape[0]) < nc
                m = trainer.evaluate(p, b, Xc, yc, valid)
                auc = binary_auc(m["scores"], yc, valid)
                return m["test_correct"], m["test_loss"], m["test_total"], auc

            with jax.named_scope(obs_names.SCOPE_EVAL):
                return self._per_client(per_client, params, bstats, X, y,
                                        n)

        return jax.jit(eval_all)

    def _summarize(self, correct, loss, total, auc, n) -> dict[str, float]:
        """Average of per-client ratios over clients with data — parity with
        the reference's mean-over-clients metric (sailentgrads_api.py:266-285)."""
        correct, loss, total, auc, n = map(np.asarray,
                                           (correct, loss, total, auc, n))
        mask = n > 0
        if not np.any(mask):  # e.g. CI mode and client 0 has no test data
            return {"acc": 0.0, "loss": 0.0, "auc": 0.0, "acc_pooled": 0.0}
        accs = correct[mask] / np.maximum(total[mask], 1)
        losses = loss[mask] / np.maximum(total[mask], 1)
        return {
            "acc": float(np.mean(accs)),
            "loss": float(np.mean(losses)),
            "auc": float(np.mean(auc[mask])),
            "acc_pooled": float(correct[mask].sum() / max(total[mask].sum(), 1)),
        }

    def eval_global(self, params, bstats, split: str = "test") -> dict[str, float]:
        X = getattr(self.data, f"X_{split}")
        y = getattr(self.data, f"y_{split}")
        n = getattr(self.data, f"n_{split}")
        if self.cfg.fed.ci:  # CI escape hatch: client 0 only
            X, y, n = X[:1], y[:1], n[:1]
        # two host spans, because they are two costs: the enqueue of the
        # eval program, and the blocking read of its result, which waits
        # for everything dispatched before it (the round itself)
        with obs_trace.span(obs_names.SPAN_EVAL_DISPATCH,
                            program="eval_global", split=split,
                            **self._eval_span_args(X, split)):
            out = self._eval_global_jit(params, bstats, X, y, n)
        with obs_trace.span(obs_names.SPAN_EVAL_SYNC,
                            program="eval_global"):
            return self._summarize(*out,
                                   n=n if not self.cfg.fed.ci else n[:1])

    def eval_personalized(self, states: ClientState, split: str = "test"
                          ) -> dict[str, float]:
        X = getattr(self.data, f"X_{split}")
        y = getattr(self.data, f"y_{split}")
        n = getattr(self.data, f"n_{split}")
        params, bstats = states.params, states.batch_stats
        if self.cfg.fed.ci:  # CI escape hatch gates BOTH eval paths
            # (ref sailentgrads_api.py:260-265)
            X, y, n = X[:1], y[:1], n[:1]
            params = pt.tree_stack_index(params, slice(0, 1))
            bstats = pt.tree_stack_index(bstats, slice(0, 1))
        with obs_trace.span(obs_names.SPAN_EVAL_DISPATCH,
                            program="eval_personalized", split=split,
                            **self._eval_span_args(X, split)):
            out = self._eval_personal_jit(params, bstats, X, y, n)
        with obs_trace.span(obs_names.SPAN_EVAL_SYNC,
                            program="eval_personalized"):
            return self._summarize(*out, n=n)

    # ---------- checkpoint / resume (SURVEY §5.4 rebuild requirement) ----------

    def _ckpt_active(self) -> bool:
        return bool(self.cfg.checkpoint_dir) and self.cfg.checkpoint_every > 0

    def maybe_checkpoint(self, round_idx: int, state: dict) -> None:
        """Save engine round state after ``round_idx`` completed, every
        ``checkpoint_every`` rounds (and always on the last round). All
        per-round randomness derives from the round index (per_client_rngs,
        client_sampling), so {state, round} is a complete resume point."""
        if not self._ckpt_active():
            return
        last = round_idx == self.cfg.fed.comm_round - 1
        if (round_idx + 1) % self.cfg.checkpoint_every == 0 or last:
            state = dict(state)
            state["stat_info"] = {
                k: v for k, v in self.stat_info.items()
                if isinstance(v, (int, float, list))}
            ckpt.save_checkpoint(self.cfg.checkpoint_dir, round_idx, state)
            self.log.info("checkpoint saved: round %d -> %s", round_idx,
                          self.cfg.checkpoint_dir)

    def restore_checkpoint(self) -> tuple[int, dict | None]:
        """Returns (start_round, state|None): the round to resume AT and the
        restored state of the last completed round."""
        if not self._ckpt_active():
            return 0, None
        loaded = ckpt.load_checkpoint(self.cfg.checkpoint_dir)
        if loaded is None:
            return 0, None
        round_idx, state = loaded
        self.stat_info.update(state.pop("stat_info", {}))
        # restored leaves arrive as host numpy; COPY them into
        # runtime-owned device buffers before they reach a round program.
        # The round programs donate their state arguments (ISSUE 4), and
        # handing numpy memory into a donated position is memory-unsafe:
        # the numpy->device conversion (device_put included) can borrow
        # the numpy buffer zero-copy on CPU, after which the donation
        # lets XLA write outputs into — and then free — memory that
        # numpy still owns (silently corrupt resumes, eventually heap
        # corruption; caught by tests/test_dispatch.py's resume pin).
        # ``jnp.array`` always copies from numpy, yielding an owned
        # buffer the donation may consume.
        state = {k: jax.tree.map(
            lambda x: jnp.array(x) if isinstance(x, np.ndarray) else x, v)
            for k, v in state.items()}
        self.log.info("resuming from checkpoint: round %d", round_idx + 1)
        return round_idx + 1, state

    # ---------- buffer donation (ISSUE 4) ----------

    #: Every round/consensus program donates the state pytrees it
    #: consumes (per-client stacks, broadcast params, EF accumulators),
    #: so XLA reuses their buffers for the matching outputs instead of
    #: double-buffering input and output state. The driver contract:
    #: NOTHING may read a donated argument after the dispatch (the
    #: runtime deletes the buffers; nidtlint's donation-discipline rules
    #: check the callers lexically). Tests/benches that replay the same
    #: buffers through one program twice set ``_donate = False`` BEFORE
    #: the program's first access (the jits are built lazily and read
    #: this flag at build time).
    _donate = True

    def _donate_argnums(self, *nums: int) -> tuple[int, ...]:
        """``donate_argnums`` for a round/consensus program; ``()`` when
        donation is disabled on this engine instance."""
        return tuple(nums) if self._donate else ()

    # ---------- the declared round program (ISSUE 11) ----------

    @functools.cached_property
    def sq_scales(self) -> dict:
        """Static per-leaf power-of-two scales for the in-process
        secure-quant stage, derived ONCE from the seed-deterministic
        init model (privacy.leaf_scales — BatchNorm raw-moment leaves
        would otherwise saturate the small field). Static for the run —
        per-round reference scales would force a host boundary; the
        fixed-scale contract is
        the async one-phase protocol's (frames fold unscaled against a
        startup bound there; scaled against the init here)."""
        from neuroimagedisttraining_tpu.privacy import leaf_scales

        gs = self.init_global_state()
        ref = {"params": jax.tree.map(np.asarray, gs.params),
               "batch_stats": jax.tree.map(np.asarray, gs.batch_stats)}
        return leaf_scales(ref)

    @functools.cached_property
    def program(self) -> "round_program.RoundProgram":
        """The engine's compiled round-program builder
        (engines/program.py): every sharded/folded/donated dispatch
        variant and the fallback reporting. Built from the
        engine's :meth:`round_stages` declaration (None for engines that
        keep hand-driven per-round loops — they still get the unified
        fallback reporting)."""
        return round_program.RoundProgram(self, self.round_stages())

    def round_stages(self):
        """The engine's declared round stages
        (:class:`engines.program.RoundStages`), or None when the engine
        has no declarable round body (host-side state between rounds).
        Declaring stages is what puts an engine on the sharded/folded/
        donated fast path — the builder owns the machinery."""
        return None

    # ---------- cohort sharding (--client_mesh, ISSUE 6) ----------

    def cohort_fallback_key(self) -> str | None:
        """REASONS key for why this engine runs the unsharded round even
        when ``--client_mesh`` asks for the cohort-sharded client mesh.
        The base answer covers every engine without declared stages (or
        whose stages cannot shard); engines with a structurally
        different sharding story (dispfl/turbo) override with their
        table key. Mode checks (mesh shape, streaming, batch order) live
        in the program builder."""
        return "no-sharded-body"

    def cohort_fallback_reason(self) -> str | None:
        """The logged message for the program's cohort fallback key
        (None when the sharded path arms)."""
        key = self.program.cohort_fallback_key()
        return None if key is None else round_program.reason(key)

    def _cohort_pad(self, sampled: np.ndarray) -> tuple[np.ndarray, int]:
        """``(padded_ids, n_real)`` for a cohort-sharded resident round:
        the sampled set padded to tile the client mesh (the shared
        ``pad_cohort`` rule — zero-sample pool first, then repeat)."""
        return cohort.pad_cohort(np.asarray(sampled), self.real_clients,
                                 self.num_clients, self.mesh.devices.size)

    def _cohort_deal(self, ids: np.ndarray, n_real: int
                     ) -> tuple[np.ndarray, np.ndarray]:
        """``(deal, chip_steps)``: this round's deal of the mesh-padded
        set ``ids`` to the chips (``cohort.deal_rows`` over ``ceil(n /
        batch)``, pad rows at zero steps), an index array computed per
        round from host integers, an operand of the sharded round program
        and never a constant of it; and the steps an epoch each chip then
        holds."""
        n = self._n_train_host[np.asarray(ids)].copy()
        n[n_real:] = 0  # by position: a pad may repeat a real client's id
        steps = np.ceil(n / self.cfg.optim.batch_size).astype(np.int64)
        chips = self.mesh.devices.size
        deal = cohort.deal_rows(steps, chips)
        return deal, steps[deal].reshape(chips, -1).sum(axis=1)

    def _cohort_round_prog(self, sampled: np.ndarray):
        """``(gather_ids, round_prog)`` for one resident round: the
        mesh-padded id set in DEALT order (``_cohort_deal``; the caller
        folds its rngs from these ids, so they follow) + the sharded
        round program with the deal bound, which undoes it after the
        all-gather, when cohort sharding is armed; the sampled set + the
        unsharded ``_round_jit`` otherwise (shared by the fedavg-family
        and salientgrads drivers)."""
        if self._cohort_on:
            ids, n_real = self._cohort_pad(sampled)
            deal, _ = self._cohort_deal(ids, n_real)
            return ids[deal], functools.partial(
                self._sharded_round_jit(n_real), deal=jnp.asarray(deal))
        return sampled, self._round_jit

    #: when True, the sharded round programs lower their local-training
    #: stage to the SEQUENTIAL C-loop on one device instead of the
    #: mesh-sharded loops — the bitwise reference tests/test_cohort.py
    #: and the bench's slope baseline pin the sharded path against (set
    #: BEFORE the first program access; the jits read it at build time)
    _cohort_sequential = False

    #: bytes one round's stacked client states may take before the round
    #: program folds its clients (engines/program.py ``placement``).
    #: None asks the device; a test sets a number BEFORE the first
    #: program access to force either placement on the CPU.
    _fold_budget_bytes: int | None = None

    def fold_budget_bytes(self) -> int | None:
        """The device's ``bytes_limit`` less the resident cohort, or None
        where the device reports no limit (the CPU): the stacked
        placement then stands, as it always did."""
        if self._fold_budget_bytes is not None:
            return int(self._fold_budget_bytes)
        device = (jax.devices()[0] if self.mesh is None
                  else self.mesh.devices.flat[0])
        limit = (device.memory_stats() or {}).get("bytes_limit")
        if not limit:
            return None
        resident = (round_program.tree_bytes(self.data)
                    if self.data is not None else 0)
        n_dev = 1 if self.mesh is None else int(self.mesh.devices.size)
        return int(limit) - resident // n_dev

    @property
    def folded(self) -> bool:
        """Clients run one after another (the round program's FOLDED
        placement): evaluation and the final fine-tune pass then loop
        over clients too, and never hold a state per client."""
        return self.program.placement == round_program.FOLDED

    def _rows_placement(self, rows: int) -> tuple[str, int]:
        """``(placement, rows_a_chip)`` of ``rows`` client rows handed to
        :meth:`_per_client`: the round program's placement, except that
        rows which do not tile the client mesh (the ``--ci`` single row)
        stay stacked. ``rows_a_chip`` is what one chip's loop walks when
        the rows are sharded, every row otherwise."""
        placement = self.program.placement
        if placement != round_program.SHARDED:
            return placement, rows
        chips = int(self.mesh.devices.size)
        if rows % chips:
            return round_program.STACKED, rows
        return placement, rows // chips

    def _eval_span_args(self, X, split: str, ids=None) -> dict:
        """Where an evaluation program about to be enqueued places the
        client rows of ``X [clients, rows, ...]`` (clients ``ids`` of
        ``split``; by default the first ``X.shape[0]``), as arguments of
        its ``eval_dispatch`` span (obs/names.py ``ARGS_BY_SPAN``), and
        how many sample rows its loops compute (``rows_run``: every
        client's rows as the batches ``LocalTrainer.eval_batches`` gives
        ``evaluate`` for them, the one rule both read) for the
        ``rows_real`` there are. Host integers, no device read; a no-op
        while the tracer is disarmed."""
        if not obs_trace.TRACER.armed:
            return {}
        rows = X.shape[0]
        placement, rows_a_chip = self._rows_placement(rows)
        batches, batch = self.trainer.eval_batches(X.shape[2:], X.shape[1])
        n = self._n_host[split]
        return {"placement": placement, "rows": rows,
                "rows_a_chip": rows_a_chip,
                "rows_run": rows * batches * batch,
                "rows_real": int(n[:rows].sum() if ids is None
                                 else n[ids].sum())}

    def _per_client(self, fn, *stacked):
        """``fn`` over the client axis outside the round program, placed
        as the round program places its clients (``_rows_placement``):
        ``vmap`` when stacked; one client after another when the round
        folds; and where the cohort is sharded over the client mesh,
        each chip's loop over the rows it holds (``_cohort_map``:
        ``fn``'s stacked operands are cut by row, what it closes over is
        replicated, and only its per-client outputs are all-gathered, so
        evaluation moves four scalars a client and no activation).
        Folded or sharded a row runs alone
        (``LocalTrainer.rows_alone``)."""
        rows = jax.tree.leaves(stacked[0])[0].shape[0]
        placement, _ = self._rows_placement(rows)
        if placement == round_program.FOLDED:
            with self.trainer.rows_alone():
                return cohort.sequential_map(fn, *stacked)
        if placement == round_program.SHARDED:
            return self._cohort_map(fn, *stacked)
        return jax.vmap(fn)(*stacked)

    def _cohort_map(self, fn, *stacked):
        """The round body's local-training stage on the sharded path:
        the unbatched per-client loop, shard_mapped over the client mesh
        and all-gathered back to replicated full stacks — or the same
        loop on one device when ``_cohort_sequential`` asks for the
        sequential reference (~1-ulp-equal with bitwise first-round
        losses — the full contract in parallel/cohort.py). Either way a
        row runs alone, and the trainer is told so while this traces
        (``LocalTrainer.rows_alone``): both take the same unbatched
        step, which stops at the row's own last one, and both hold
        ``fn`` to the partition's rule that a ``local_train`` in it is
        handed hoisted permutations (``_per_client`` sends evaluation
        here, which draws nothing)."""
        with self.trainer.rows_alone(partitioned=True):
            if self._cohort_sequential:
                return cohort.sequential_map(fn, *stacked)
            return cohort.cohort_map(self.mesh, fn, *stacked)

    # ---------- Byzantine value faults (faults/adversary.py, ISSUE 5) ----------

    def _byz_on(self) -> bool:
        """True iff the fault schedule can corrupt upload VALUES — the
        round programs then route client uploads through the adversary
        transform (an all-honest round rides an identity plan, which
        ``apply_attack`` passes through bitwise)."""
        return (self.fault_schedule is not None
                and self.fault_schedule.spec.any_value_faults)

    def _byz_round_plan(self, round_idx: int, sampled: np.ndarray):
        """One round's attack plan over the sampled cohort (engine
        client index c == cross-silo rank c + 1, the faults/ contract):
        ``(mult[C], std[C], nonfinite[C], keys[C])`` device arrays, or
        None when the schedule has no value faults at all."""
        if not self._byz_on():
            return None
        ranks = np.asarray(sampled) + 1
        mult, std, nan = adversary.plan_arrays(self.fault_schedule,
                                               round_idx, ranks)
        byzantine = np.flatnonzero((mult != 1.0) | (std != 0.0) | nan)
        if byzantine.size:
            self.log.info(
                "round %d: clients %s upload BYZANTINE values (%s)",
                round_idx, np.asarray(sampled)[byzantine].tolist(),
                [self.fault_schedule.byzantine_kind(round_idx,
                                                    int(r))
                 for r in ranks[byzantine]])
        keys = adversary.attack_keys(self.cfg.seed, round_idx, ranks)
        return (jnp.asarray(mult), jnp.asarray(std), jnp.asarray(nan),
                keys)

    # NOTE: the shared sanitize -> defend -> aggregate round tail lives
    # in engines/program.py (``sanitize_defend_aggregate``) — it is a
    # builder-owned stage, applied to every engine whose declared round
    # has no custom aggregate stage (ISSUE 11).

    # ---------- privacy accounting (privacy/, ISSUE 8) ----------

    def record_privacy(self, round_idx: int) -> None:
        """Charge the RDP ledger for every round completed through
        ``round_idx`` and publish the running (epsilon, delta) in
        ``stat_info`` — one entry PER ROUND (the weak_dp observability
        the defense never had: the clip bound and sigma it actually
        applied were invisible). Pure host numpy, called from
        ``_flush_nonfinite``'s host boundaries (and the dpsgd driver),
        never inside a trace.

        Two armed sources, mutually exclusive by construction (weak_dp
        is a server-side defense, dp_clip/dp_sigma a client-side
        transform dpsgd owns):

        - ``defense_type == "weak_dp"``: per round, a subsampled
          Gaussian at q = cohort/total with the effective multiplier
          over the round's ACTUAL sample-count weights
          (``weak_dp_noise_multiplier``) — cohorts re-derived from the
          deterministic sampling contract, so accounting replays
          exactly.
        - ``dp_sigma > 0`` (dpsgd): full participation (q = 1, every
          silo reveals its noised model to neighbors every round) at
          noise multiplier ``dp_sigma``.
        """
        from neuroimagedisttraining_tpu.privacy import accountant as acct

        f = self.cfg.fed
        weak = f.defense_type == "weak_dp"
        dp = f.dp_sigma > 0
        if not (weak or dp) or round_idx <= self._dp_recorded_through:
            return
        if weak and (f.stddev <= 0 or f.norm_bound <= 0):
            # degenerate-but-runnable ablation (no noise / no clip
            # sensitivity): warn once, never die at an eval boundary —
            # the same guard cross_silo._note_weak_dp keeps
            if not getattr(self, "_warned_dp_disabled", False):
                self._warned_dp_disabled = True
                self.log.warning(
                    "weak_dp with stddev=%s/norm_bound=%s adds no "
                    "accountable noise — epsilon is infinite; the "
                    "accountant records nothing", f.stddev, f.norm_bound)
            return
        key = "weak_dp" if weak else "dp"
        stats = self.stat_info.setdefault(key, {
            "norm_bound": f.norm_bound if weak else f.dp_clip,
            "stddev": f.stddev if weak else f.dp_sigma * f.dp_clip,
            "delta": f.dp_delta, "noise_multiplier_per_round": [],
            "epsilon_per_round": [], "epsilon": 0.0})
        if self._dp_rdp is None:
            self._dp_rdp = np.zeros(len(acct.DEFAULT_ORDERS), np.float64)
        for r in range(self._dp_recorded_through + 1, round_idx + 1):
            if weak:
                sampled = self.client_sampling(r)
                w = self._n_train_host[np.asarray(sampled)]
                q = len(sampled) / max(1, self.real_clients)
                z = acct.weak_dp_noise_multiplier(f.stddev, f.norm_bound,
                                                  w)
            else:
                q, z = 1.0, f.dp_sigma
            self._dp_rdp = self._dp_rdp + acct.rdp_gaussian(q, z)
            eps = acct.rdp_to_epsilon(self._dp_rdp,
                                      delta=f.dp_delta)[0]
            stats["noise_multiplier_per_round"].append(round(z, 6))
            stats["epsilon_per_round"].append(round(eps, 4))
        stats["epsilon"] = stats["epsilon_per_round"][-1]
        # per-silo report: under the sampling model every silo's loss is
        # identical (the subsampling is the amplifier), so the per-silo
        # map is uniform — the cross-silo server's ledger (which sees
        # deterministic survivor sets, no amplification) is the
        # per-silo-varying counterpart (cross_silo.dp_report)
        stats["epsilon_per_silo"] = {
            int(c): stats["epsilon"] for c in range(self.real_clients)}
        self._dp_recorded_through = round_idx

    # ---------- counts at the dispatch boundary (obs/trace.py) ----------

    def _note_round_counts(self, sampled, rows: int) -> None:
        """What the next dispatched program trains, as host integers the
        driver already holds (no device read): ``samples_real`` and
        ``steps_real`` over the round's ``sampled`` clients;
        ``steps_run``, the steps the program's loops execute for its
        ``rows`` client rows under its placement (stacked: every row walks
        core/trainer.py ``scan_steps``, padded rows and masked steps
        included; rows that run alone, sharded or folded: the real steps
        and no other); ``steps_skipped``, the surplus iterations of that
        loop length not executed; and ``chip_steps_max`` /
        ``chip_steps_mean``, the busiest chip's and the mean chip's
        share of ``steps_run`` as the rows are dealt
        (``_cohort_deal``; equal on one chip). They ride on the
        ``dispatch_program`` span, so a trace reads the padded share and
        the deal where the work is dispatched. A no-op while the tracer
        is disarmed."""
        if not obs_trace.TRACER.armed:
            return
        o = self.cfg.optim
        placement = self.program.placement
        n = self._n_train_host[np.asarray(sampled)]
        real = int(o.epochs * np.ceil(n / o.batch_size).sum())
        walked = int(rows * scan_steps(
            o.epochs, o.batch_size, self._max_samples()))
        run = walked if placement == round_program.STACKED else real
        busiest, chips = run, 1
        if placement == round_program.SHARDED:
            chips = int(self.mesh.devices.size)
            busiest = int(o.epochs * self._cohort_deal(
                *self._cohort_pad(sampled))[1].max())
        self._dispatch_counts = {
            "samples_real": int(o.epochs * n.sum()),
            "steps_real": real,
            "steps_run": run,
            "steps_skipped": walked - run,
            "chip_steps_max": busiest,
            "chip_steps_mean": run / chips,
            "placement": placement}

    # ---------- non-finite upload guard (ISSUE 5 satellite) ----------

    def _note_nonfinite(self, n_bad) -> None:
        """Queue a round's device-side count of rejected non-finite
        client uploads. Deliberately NOT synced here: a per-round
        ``device_get`` would serialize every dispatch; the queue drains
        in one batched transfer at the next host boundary."""
        self._nonfinite_pending.append(n_bad)

    # ---------- training-health plane (obs/health.py, ISSUE 15) ----------

    def _note_health(self, stats: dict) -> None:
        """Queue one round's health-stats pytree (device arrays — the
        builder's dispatch wrapper calls this, never a driver). Drained
        at ``_flush_nonfinite`` in the same batched device_get as the
        non-finite counts."""
        self._health_pending.append(stats)

    def _drain_health(self, host_vals: list, round_idx: int) -> None:
        """Publish the drained health stats round by round. Dispatches
        between two host boundaries cover CONTIGUOUS rounds ending at
        the flush round (the drivers' loop invariant), so the round
        index of every entry is reconstructed backward from
        ``round_idx`` — no per-dispatch round plumbing through the
        legacy adapters. Each published round also lands one metrics
        JSONL record and one rule-engine boundary evaluation."""
        for r, row in enumerate(host_vals,
                                round_idx - len(host_vals) + 1):
            obs_health.publish_round_stats(self.name, r, row)
            # stash the host row BEFORE the boundary evaluation: a
            # divergence alert fired at this round must be able to
            # attribute the offender from its h_cos vector (the reflex
            # quarantine handler, ISSUE 20)
            self._stash_bounded(self._last_health_rows, int(r), dict(row))
            if r < round_idx:
                # the flush round itself dumps/evaluates in
                # publish_stat_info, AFTER the stat/DP gauges of this
                # boundary are set
                self._dump_metrics_jsonl(r)
                obs_rules.observe_boundary(r)

    def _dump_metrics_jsonl(self, round_idx: int) -> None:
        """One metrics JSONL record per round (``--metrics_out``), each
        carrying the monotonic ``round`` + ``seq`` join keys
        (run_report joins series on them, never on timestamps).
        Re-flushing an already-recorded round is a no-op — boundaries
        and end-of-run paths may land on the same round."""
        path = getattr(self.cfg, "metrics_out", "")
        if not path:
            return
        if self._metrics_last_round is not None \
                and round_idx <= self._metrics_last_round:
            return
        self._metrics_seq += 1
        self._metrics_last_round = int(round_idx)
        obs_metrics.REGISTRY.dump_jsonl(
            path, round=int(round_idx), seq=self._metrics_seq,
            engine=self.name)

    def _flush_nonfinite(self, round_idx: int) -> None:
        """Drain the queued counts (one batched device_get) and emit the
        counted warning when any upload was rejected. Call at host-sync
        boundaries — eval rounds and end of training — where the driver
        already blocks on device results.

        Doubles as the privacy-ledger boundary: every driver that can
        arm weak_dp already calls this at exactly the host-sync points
        where per-round accounting should publish, so the accountant
        records here instead of asking each engine for a second hook —
        and as the OBS boundary (ISSUE 9): the stat_info accumulators
        publish into the metrics registry here, where the driver already
        blocks on device results, never from inside a dispatch. The
        training-health stats the round programs queued (ISSUE 15) ride
        the SAME batched device_get — armed health adds zero sync
        points to a run."""
        self.record_privacy(round_idx)
        if self._nonfinite_pending or self._health_pending:
            with obs_trace.span(obs_names.SPAN_FLUSH_SYNC):
                counts, health_vals = jax.device_get(
                    (self._nonfinite_pending, self._health_pending))
            self._nonfinite_pending.clear()
            self._health_pending = []
            total = int(sum(np.sum(np.asarray(c)) for c in counts))
            if total:
                self.stat_info["nonfinite_uploads"] += total
                self.log.warning(
                    "rounds <= %d: rejected %d non-finite (NaN/Inf) "
                    "client upload(s) before aggregation — the "
                    "offending clients were zero-weighted for their "
                    "rounds (%d rejected so far this run)", round_idx,
                    total, int(self.stat_info["nonfinite_uploads"]))
            if health_vals:
                self._drain_health(health_vals, round_idx)
        self.publish_stat_info(round_idx)

    def publish_stat_info(self, round_idx: int) -> None:
        """Publish the scalar ``stat_info`` accumulators (and the armed
        privacy ledger's running epsilon) into the obs metrics registry
        — gauge semantics, value == the legacy dict entry by
        construction (the no-double-counting pin in tests/test_obs.py).
        Host-boundary only: the callers are ``_flush_nonfinite`` and
        run-end paths, both already synced."""
        g = obs_metrics.gauge(
            obs_names.STAT, "engine stat_info accumulators "
            "(engines/base.py), one series per key",
            labelnames=("key",))
        for k, v in self.stat_info.items():
            if isinstance(v, (int, float)) and not isinstance(v, bool):
                g.labels(key=k).set(float(v))
        for src in ("weak_dp", "dp"):
            d = self.stat_info.get(src)
            if isinstance(d, dict) and d.get("epsilon_per_round"):
                obs_metrics.gauge(
                    obs_names.DP_EPSILON,
                    "running (epsilon, delta) privacy cost of the armed "
                    "noise path (privacy/accountant.py)",
                    labelnames=("source",)).labels(source=src).set(
                    float(d["epsilon"]))
                # epsilon burn RATE (ISSUE 15 satellite): what the last
                # accounted round cost — the built-in dp-burn-rate rule
                # and the run report's epsilon ledger read this next to
                # the running total
                per = d["epsilon_per_round"]
                burn = (per[-1] - per[-2]) if len(per) > 1 else per[-1]
                obs_metrics.gauge(
                    obs_names.DP_EPSILON_PER_ROUND,
                    "epsilon spent by the last accounted round (the "
                    "budget burn rate --dp_epsilon_budget is judged "
                    "against)",
                    labelnames=("source",)).labels(source=src).set(
                    float(burn))
        obs_metrics.gauge(
            obs_names.ENGINE_ROUND,
            "last round index flushed at an engine host boundary",
        ).set(int(round_idx))
        # compute-plane boundary (ISSUE 14): this is a host point where
        # the driver ALREADY blocked on device results, so the profiler
        # can close its MFU window (flops dispatched since the last
        # boundary / synced wall) without adding any sync
        obs_compute.PROFILER.boundary(self.name)
        # training-health boundary (ISSUE 15): one metrics JSONL record
        # + one rule-engine evaluation per boundary round — both no-ops
        # when the drained health stats already covered this round (or
        # when the sink / rule engine is unarmed)
        self._dump_metrics_jsonl(round_idx)
        obs_rules.observe_boundary(round_idx)

    # ---------- reflex plane (obs/actions.py, ISSUE 20) ----------

    #: bound on the per-round host stashes the reflex handlers read
    #: (sampled cohorts, drained health rows): old rounds evict oldest-
    #: first — a handler only ever looks a few boundaries back
    _REFLEX_STASH_CAP = 64

    #: rounds an engine-side reflex quarantine lasts. The cross-silo
    #: control plane has an operator knob (--quarantine_rounds); the
    #: in-process reflex uses one fixed conservative window — the alert
    #: that fired it re-fires if the divergence survives the window
    _REFLEX_QUARANTINE_ROUNDS = 5

    #: the escalation ladder (ISSUE 20): each rung strictly stronger.
    #: Deliberately short — weak_dp and the order statistics beyond
    #: trimmed_mean change the privacy/accuracy contract in ways a
    #: reflex must not decide on its own
    _DEFENSE_LADDER = ("none", "norm_diff_clipping", "trimmed_mean")

    @staticmethod
    def _stash_bounded(d: dict, key: int, value) -> None:
        d[key] = value
        while len(d) > FederatedEngine._REFLEX_STASH_CAP:
            d.pop(min(d))

    def _is_quarantined(self, client: int, round_idx: int) -> bool:
        return any(a <= round_idx < b
                   for a, b in self._quarantine_windows.get(client, ()))

    def active_defense(self) -> str:
        """The defense the round programs realize RIGHT NOW: the config
        literal unless the reflex plane escalated it. The builder's
        sanitize/defend/aggregate tail reads this at TRACE time
        (engines/program.py), so escalation invalidates the compiled
        programs and the next dispatch re-traces through here."""
        return self._defense_override or self.cfg.fed.defense_type

    def _invalidate_round_programs(self) -> None:
        """Drop every compiled round program and plan cache so the next
        dispatch re-traces/re-plans against the CURRENT engine state
        (escalated defense, shrunken mesh). The caches are lazy
        cached-properties / plan dicts in ``__dict__`` — popping them
        is the whole invalidation."""
        for name in ("program", "_round_jit", "_round_stream_jit",
                     "_round_prog_cache"):
            self.__dict__.pop(name, None)

    def _register_reflexes(self) -> None:
        """Register this engine's realizations of the reflex actions on
        the armed action bus — a no-op when none is armed (tests and
        library callers run engines without the CLI). Called at
        ``train()`` start; registration is latest-wins, so repeated
        trains re-arm cleanly."""
        bus = obs_actions.active()
        if bus is None:
            return
        bus.register("quarantine_silo", self._act_quarantine)
        bus.register("escalate_defense", self._act_escalate_defense)
        bus.register("freeze_rollback", self._act_freeze_rollback)

    def _act_quarantine(self, *, rule: str, round_idx: int | None,
                        value=None) -> dict:
        """quarantine_silo: attribute the divergence alert to the
        sampled client with the most negative leave-one-out cosine
        (the stashed ``h_cos`` row of the firing round) and open a
        quarantine window starting NEXT round. Concurrency is capped at
        the configured Byzantine budget — the same breakdown-point
        honesty the cross-silo strike machinery keeps."""
        r = -1 if round_idx is None else int(round_idx)
        sampled = self._sampled_by_round.get(r)
        row = self._last_health_rows.get(r)
        cos = None if row is None else row.get("h_cos")
        if sampled is None or cos is None:
            return {"status": "skipped",
                    "reason": "no per-client cosine row for the round "
                              "(--health_stats off, or pre-health "
                              "boundary)"}
        cos = np.ravel(np.asarray(cos))
        n = min(len(sampled), cos.size)
        if n == 0:
            return {"status": "skipped", "reason": "empty cohort"}
        offender = int(np.asarray(sampled)[int(np.argmin(cos[:n]))])
        if self._is_quarantined(offender, r + 1):
            return {"status": "skipped", "client": offender,
                    "reason": "offender already quarantined"}
        cap = max(1, int(self.cfg.fed.byz_f))
        active_now = sum(1 for c in self._quarantine_windows
                        if self._is_quarantined(c, r + 1))
        if active_now >= cap:
            return {"status": "skipped",
                    "reason": f"quarantine cap {cap} (byz_f) reached"}
        until = r + 1 + self._REFLEX_QUARANTINE_ROUNDS
        self._quarantine_windows.setdefault(offender, []).append(
            (r + 1, until))
        self.log.warning(
            "reflex: client %d quarantined rounds [%d, %d) (rule %s, "
            "min leave-one-out cosine %.3f)", offender, r + 1, until,
            rule, float(cos[:n].min()))
        return {"client": offender, "from_round": r + 1, "until": until,
                "cos": float(cos[:n].min())}

    def _act_escalate_defense(self, *, rule: str,
                              round_idx: int | None,
                              value=None) -> dict:
        """escalate_defense: step the ladder one rung and re-plan the
        round programs. Anything infeasible — an operator-chosen
        defense outside the ladder, an engine without the rung, a
        cohort below the rung's breakdown point, secure_quant's
        no-plaintext tail — is a SKIPPED dispatch with the reason in
        the action log, never an exception."""
        cur = self.active_defense()
        ladder = self._DEFENSE_LADDER
        if self.cfg.fed.secure_quant:
            return {"status": "skipped",
                    "reason": "secure_quant rounds have no plaintext "
                              "defend tail to escalate"}
        if cur not in ladder:
            return {"status": "skipped",
                    "reason": f"operator defense {cur!r} is outside "
                              "the escalation ladder"}
        if cur == ladder[-1]:
            return {"status": "skipped",
                    "reason": f"already at the top rung {cur!r}"}
        nxt = ladder[ladder.index(cur) + 1]
        if nxt not in self.supported_defenses:
            return {"status": "skipped",
                    "reason": f"engine {self.name!r} does not support "
                              f"{nxt!r}"}
        if nxt in robust.ROBUST_AGGREGATORS:
            try:
                robust._check_f(self.cfg.fed.client_num_per_round,
                                self.cfg.fed.byz_f, nxt)
            except ValueError as e:
                return {"status": "skipped", "reason": str(e)}
        self._defense_override = nxt
        self._invalidate_round_programs()
        self.log.warning(
            "reflex: defense escalated %s -> %s (rule %s); round "
            "programs invalidated for re-trace", cur, nxt, rule)
        return {"from": cur, "to": nxt}

    def _act_freeze_rollback(self, *, rule: str,
                             round_idx: int | None,
                             value=None) -> dict:
        """freeze_rollback: schedule a restore of the last healthy
        pinned state; the driver consumes it at the NEXT host boundary
        (``_reflex_boundary``) — never mid-dispatch, so the donation
        contract is untouched."""
        if self._healthy_pin is None:
            return {"status": "skipped",
                    "reason": "no healthy pinned state yet"}
        self._pending_rollback = {
            "rule": rule,
            "round": -1 if round_idx is None else int(round_idx)}
        return {"pin_round": int(self._healthy_pin["round"])}

    def _reflex_boundary(self, round_idx: int, params, bstats):
        """The drivers' per-boundary reflex hook, called right after
        ``_flush_nonfinite`` (whose rule evaluation may have scheduled
        a rollback): consume a pending freeze-and-rollback, else pin
        the current state as 'last healthy' while the rule engine
        reads ok. Pin and restore both take fresh ``jnp.array`` copies
        — the round programs donate their state arguments, so the pin
        must own buffers no dispatch can consume, and the restored
        arrays must be consumable without killing the pin."""
        pend = self._pending_rollback
        if pend is not None:
            self._pending_rollback = None
            pin = self._healthy_pin
            if pin is not None:
                params = jax.tree.map(jnp.array, pin["params"])
                bstats = jax.tree.map(jnp.array, pin["batch_stats"])
                if getattr(self, "_wire_ef", None) is not None:
                    # codec-EF reset invariant (ARCHITECTURE.md "Reflex
                    # plane"): the accumulated error was measured
                    # against states the rollback just discarded —
                    # replaying it would re-inject the divergence the
                    # rollback removed
                    self._wire_ef = jax.tree.map(jnp.zeros_like,
                                                 self._wire_ef)
                obs_flight.record("rollback", rule=pend.get("rule"),
                                  round=int(round_idx),
                                  pin_round=int(pin["round"]))
                self.log.warning(
                    "reflex: rolled back to the healthy state of round "
                    "%d at boundary %d (rule %s); codec EF reset",
                    pin["round"], round_idx, pend.get("rule"))
            return params, bstats
        bus = obs_actions.active()
        if bus is not None and bus.mode == "on":
            rules_eng = obs_rules.active()
            if rules_eng is None or rules_eng.status() == "ok":
                self._healthy_pin = {
                    "round": int(round_idx),
                    "params": jax.tree.map(jnp.array, params),
                    "batch_stats": jax.tree.map(jnp.array, bstats)}
        return params, bstats

    @staticmethod
    def _regather_live(tree):
        """Host-gather a live pytree off the pre-preemption devices and
        re-place it as fresh uncommitted buffers. The no-checkpoint
        resume path keeps training on the live state — but that state
        is committed to the OLD mesh's devices, and the re-planned
        programs shard over the survivors only."""
        return jax.tree.map(lambda x: jnp.array(np.asarray(x)), tree)

    def _maybe_preempt(self, round_idx: int):
        """Elastic compute plane (ISSUE 20): consume any scheduled
        ``preempt:NDEV@ROUND`` whose round has arrived, shrink the
        training mesh to the NDEV survivors, re-plan every compiled
        program, and return ``(resume_round, restored_state | None)``
        from the last donation-safe checkpoint. Returns None when
        nothing fired. Deliberately NOT gated by ``--actions``: an
        explicitly injected device loss is an event, not a reflex policy
        — the armed bus records it with the device-loss event as
        provenance either way."""
        if self.fault_schedule is None:
            return None
        hits = [(at, nd) for (at, nd)
                in self.fault_schedule.spec.preempts
                if at <= round_idx and at not in self._preempts_done]
        if not hits:
            return None
        at, ndev = hits[0]
        self._preempts_done.add(at)
        old = self.mesh.devices.size if self.mesh is not None else 0
        if self.mesh is None or not 0 < ndev < old:
            obs_actions.record_action(
                "shrink_mesh", rule="device-loss",
                round_idx=round_idx, status="skipped",
                detail={"reason": ("no mesh to shrink"
                                   if self.mesh is None else
                                   f"{ndev} survivors do not shrink "
                                   f"the {old}-device mesh"),
                        "scheduled_round": int(at)})
            return None
        self.mesh = make_mesh(num_devices=ndev)
        if int(self.cfg.fed.client_mesh) > 0:
            # keep the client_mesh == mesh-size startup invariant so
            # the re-planned programs shard over exactly the survivors
            self.cfg = dataclasses.replace(
                self.cfg, fed=dataclasses.replace(self.cfg.fed,
                                                  client_mesh=ndev))
        self._invalidate_round_programs()
        if self.data is not None:
            # the federation was device_put with the OLD mesh's client
            # sharding at federate time (data/federate.py); arrays still
            # committed to evicted devices would poison every re-planned
            # dispatch ("incompatible devices"). Host-gather and re-place
            # over the survivors — client-sharded while the padded client
            # count still divides them, replicated otherwise (the round
            # programs re-shard internally either way).
            sh = (client_sharding(self.mesh)
                  if self.data.num_clients % ndev == 0
                  else replicated_sharding(self.mesh))
            self.data = jax.tree.map(
                lambda x: jax.device_put(np.asarray(x), sh), self.data)
        if self._cohort_on:
            # the shrunken mesh may or may not still shard (mode checks
            # re-run against the new plan)
            self._cohort_on = self.program.cohort_fallback_key() is None
        start, restored = self.restore_checkpoint()
        if restored is not None and getattr(self, "_wire_ef", None) is not None:
            # match a fresh-process resume exactly: EF accumulators are
            # not checkpointed, so a from-checkpoint replay starts them
            # at zero — the elastic resume must too, or the pinned
            # replay parity breaks
            self._wire_ef = jax.tree.map(jnp.zeros_like, self._wire_ef)
        self.log.warning(
            "preemption at round %d (scheduled @%d): mesh shrunk "
            "%d -> %d devices; resuming from %s", round_idx, at, old,
            ndev, (f"checkpoint round {start}" if restored is not None
                   else "live state (no checkpoint configured)"))
        obs_actions.record_action(
            "shrink_mesh", rule="device-loss", round_idx=round_idx,
            detail={"devices_before": int(old),
                    "devices_after": int(ndev),
                    "scheduled_round": int(at),
                    "resume_round": (int(start) if restored is not None
                                     else int(round_idx))})
        return start, restored

    # ---------- compute-plane profiler (obs/compute.py, ISSUE 14) ----------

    #: lazily armed on the first dispatch (engines/program.py wrapper):
    #: one abstract eval_shape derives the analytic FLOPs-per-round the
    #: MFU gauges divide by — no device work, no params materialized
    _compute_armed = False

    def _arm_compute_profiler(self) -> None:
        """Arm the dispatch-boundary profiler's MFU accounting for this
        engine: analytic training FLOPs of one NOMINAL round (per-sample
        FLOPs x expected sampled sample mass x local epochs). The cohort
        estimate is the sampling contract's expectation — exact under
        full participation / equal-sized synthetic clients (the bench
        and profile-session configs), an estimate under frac sampling
        or fault schedules (MFU is a utilization gauge, not a parity
        pin; obs/compute.py documents the contract). Models the
        analytic counter cannot walk (no captured conv intermediates)
        disarm with a logged reason instead of failing a dispatch."""
        if self._compute_armed:
            return
        self._compute_armed = True
        try:
            if self.data is not None:
                shape = tuple(self.data.X_train.shape[2:])
            else:
                shape = tuple(self.stream.sample_shape)
            per_sample = obs_compute.analytic_sample_flops(self.trainer,
                                                           shape)
            total_n = float(np.sum(self._n_train_host))
            cohort_frac = (min(self.cfg.fed.client_num_per_round,
                               self.real_clients)
                           / max(1, self.real_clients))
            flops_per_round = (per_sample * total_n * cohort_frac
                               * max(1, self.cfg.optim.epochs))
            obs_compute.arm_model(self.name, flops_per_round)
        except Exception as e:  # noqa: BLE001 — MFU is best-effort
            # telemetry; an uncountable model must never fail a dispatch
            self.log.info(
                "compute profiler: analytic FLOPs unavailable for this "
                "model (%s) — nidt_mfu/nidt_sustained_tflops stay "
                "unpublished; dispatch/compile accounting is unaffected",
                e)

    # ---------- helpers ----------

    #: cap on per-instance plan-keyed jit caches: a topology whose
    #: circulant weights vary per round must not accumulate one compiled
    #: executable per distinct plan for the engine's lifetime
    _JIT_CACHE_CAP = 4

    def _plan_cached(self, cache_name: str, key, build):
        """Per-instance plan-keyed cache with LRU eviction past
        ``_JIT_CACHE_CAP`` (a class-level lru_cache would store ``self``
        and pin discarded engines' device-resident data)."""
        cache = self.__dict__.setdefault(cache_name, {})
        if key in cache:
            cache[key] = cache.pop(key)  # refresh recency (true LRU)
            return cache[key]
        if len(cache) >= self._JIT_CACHE_CAP:
            cache.pop(next(iter(cache)))
        cache[key] = build()
        return cache[key]

    def _max_samples(self) -> int:
        """Static per-client sample-axis pad (same in streamed and
        resident layouts, so round programs compile once)."""
        return (self.stream.nmax_train if self.stream is not None
                else int(self.data.X_train.shape[1]))

    def _eval_g(self, params, bstats) -> dict[str, float]:
        """Global-model eval, dispatched on the data residency mode."""
        if self.stream is not None:
            return self.eval_global_stream(params, bstats)
        return self.eval_global(params, bstats)

    def _eval_p(self, per_params, per_bstats) -> dict[str, float]:
        """Personalized eval over stacked per-client state, dispatched on
        the data residency mode."""
        if self.stream is not None:
            return self.eval_personalized_stream(per_params, per_bstats)
        return self.eval_personalized(ClientState(
            params=per_params, batch_stats=per_bstats, opt_state=None,
            rng=None))

    def round_lr(self, round_idx: int):
        return round_lr(self.cfg.optim, round_idx)

    def weights_for(self, sampled: np.ndarray) -> jax.Array:
        """FedAvg weights = per-client sample counts of the sampled set
        (fedavg_api.py:102-117)."""
        n = jnp.asarray(self._n_train_host[np.asarray(sampled)])
        return n.astype(jnp.float32)

    # ---------- wire codec (codec/, ISSUE 3) ----------

    def wire_masks(self):
        """Mask handoff: the pruning/saliency mask this engine would hand
        the wire codec so uploads pack mask-sparse — a params-congruent
        pytree (or a client-stacked one for per-client masks), or None
        for dense engines (the codec's top-k stage applies instead).
        Base engines own no mask."""
        return None

    def account_wire_bytes(self, upload_host, reference_host,
                           masks_host=None, n_uploads: int = 1) -> int:
        """Accumulate the round's uplink byte accounting from ONE
        representative encoded upload (uploads share sizes up to zlib
        noise): ``sum_comm_bytes`` gets the encoded frame size x
        ``n_uploads``, ``sum_comm_bytes_dense`` the dense msgpack size
        the legacy wire would have shipped. Host-side numpy — call it
        OUTSIDE jit with device_get'd trees. Re-encoding every round
        (rather than caching one frame size) is deliberate: zlib output
        varies with the round's residual entropy, and the measured host
        cost (~150 ms for the 2.6 M-param flagship) is < 1 % of its
        round wall time. Returns the frame size."""
        frame, _ = codec_wire.encode_update(
            self.wire_spec, upload_host, reference=reference_host,
            masks=masks_host, mask_on_wire=False)
        nbytes = codec_wire.frame_nbytes(frame)
        if self._dense_upload_nbytes is None:
            self._dense_upload_nbytes = codec_wire.frame_nbytes(
                jax.tree.map(np.asarray, upload_host))
        self.stat_info["sum_comm_bytes"] += float(nbytes * n_uploads)
        self.stat_info["sum_comm_bytes_dense"] += float(
            self._dense_upload_nbytes * n_uploads)
        return nbytes

    @functools.cached_property
    def _mask_nnz_jit(self):
        def nnz(masks_stacked):
            return jax.vmap(lambda m: sum(
                jnp.sum(x > 0) for x in jax.tree.leaves(m)))(masks_stacked)

        return jax.jit(nnz)

    def warn_if_masks_collapsed(self, masks_stacked, round_idx: int
                                ) -> np.ndarray:
        """Post-round diagnosability for the jitted mask-evolution paths
        (ADVICE r5): an all-False evolved mask — the footprint of a NaN
        poisoning fire/regrow's magnitude ranks — must be VISIBLE, not a
        silent collapse of the comm metrics. Returns per-client nnz.

        Doubles as the mask-health boundary for engines whose masks
        evolve OUTSIDE a declared round body (dispfl's chunked host
        driver, ISSUE 15): the nnz fetch this call already makes IS the
        density measurement, so ``nidt_health_mask_density`` publishes
        here with no added sync."""
        nnz = np.asarray(jax.device_get(
            self._mask_nnz_jit(masks_stacked)))[: self.real_clients]
        per_client = sum(
            float(np.prod(x.shape[1:]))
            for x in jax.tree.leaves(masks_stacked))
        if per_client > 0 and nnz.size:
            obs_health.publish_mask_density(
                self.name, round_idx,
                float(np.mean(nnz) / per_client))
        if (nnz == 0).any():
            dead = np.flatnonzero(nnz == 0).tolist()
            self.log.warning(
                "round %d: clients %s evolved an EMPTY mask (0 surviving "
                "weights) — a NaN in params/gradients poisons the "
                "fire/regrow magnitude ranks into all-False; check the "
                "local losses of these clients for divergence",
                round_idx, dead)
        return nnz

    def aggregate(self, stacked, weights: jax.Array):
        """Weighted mean of a client-stacked pytree. On a two-level
        (silos, clients) mesh (``--mesh_shape S C``) the reduction is
        routed silo-first: ICI within each silo, ONE aggregate per silo
        across DCN (parallel/hierarchical.py) — same result as the flat
        mean, bandwidth-correct layout. Falls back to the flat mean when
        the stacked axis doesn't tile the mesh (e.g. frac-sampled subsets
        smaller than the device grid)."""
        from neuroimagedisttraining_tpu.parallel.hierarchical import (
            is_two_level, silo_then_global_mean,
        )

        leaves = jax.tree.leaves(stacked)
        if not leaves:  # e.g. batch_stats of a GroupNorm model
            return stacked
        if is_two_level(self.mesh):
            if leaves[0].shape[0] % self.mesh.devices.size == 0:
                return silo_then_global_mean(stacked, weights, self.mesh)
            if not getattr(self, "_warned_flat_fallback", False):
                self._warned_flat_fallback = True
                self.log.info(
                    "two-level mesh: sampled-client axis (%d) does not "
                    "tile the %d-device grid; falling back to the FLAT "
                    "weighted mean (same result, but aggregation will NOT "
                    "be routed silo-first over ICI/DCN). Choose frac so "
                    "client_num_per_round is a multiple of the device "
                    "count to keep the two-level routing.",
                    leaves[0].shape[0], self.mesh.devices.size)
        return pt.tree_weighted_mean(stacked, weights)

    # ---------- streamed evaluation (cohort > HBM) ----------

    def _eval_chunk_size(self) -> int:
        if self.cfg.stream_chunk_clients > 0:
            return self.cfg.stream_chunk_clients
        return self.mesh.devices.size if self.mesh is not None else 4

    def eval_global_stream(self, params, bstats, split: str = "test"
                           ) -> dict[str, float]:
        """Full-cohort eval of one model, streaming client chunks through
        the same jitted per-chunk program as the resident path — metric
        parity by construction."""
        parts: list[tuple] = []
        ns: list[np.ndarray] = []
        for ch in self.stream.eval_chunks(self._eval_chunk_size(), split):
            with obs_trace.span(obs_names.SPAN_EVAL_DISPATCH,
                                program="eval_global", split=split,
                                **self._eval_span_args(ch.X, split, ch.ids)):
                out = self._eval_global_jit(params, bstats, ch.X, ch.y,
                                            ch.n)
            with obs_trace.span(obs_names.SPAN_EVAL_SYNC,
                                program="eval_global"):
                parts.append(tuple(np.asarray(o)[: len(ch.ids)]
                                   for o in out))
                ns.append(np.asarray(jax.device_get(ch.n))[: len(ch.ids)])
            if self.cfg.fed.ci:
                break
        cat = [np.concatenate([p[i] for p in parts]) for i in range(4)]
        n_all = np.concatenate(ns)
        if self.cfg.fed.ci:
            cat = [c[:1] for c in cat]
            n_all = n_all[:1]
        return self._summarize(*cat, n=n_all)

    def stream_map_train_chunks(self, block_fn, state_trees: tuple, rngs,
                                *args):
        """Run a vmapped per-client block over host-streamed TRAIN chunks
        and concatenate the per-client outputs back into [C, ...] stacks
        (the shared chunk loop of DisPFL/D-PSGD/Local streamed rounds).

        ``block_fn(*state_chunks, rng_chunk, X, y, n, *args)`` must return
        ``(*out_trees, per_client_aux_vector)``; outputs beyond the real
        clients in the final padded chunk are dropped."""
        chunk = self._eval_chunk_size()
        parts: list[list] | None = None
        aux_parts: list = []
        for ch in self.stream.eval_chunks(chunk, "train"):
            take = lambda t: pt.tree_stack_index(t, ch.padded_ids)
            *trees, aux = block_fn(*(take(t) for t in state_trees),
                                   rngs[ch.padded_ids], ch.X, ch.y, ch.n,
                                   *args)
            keep = len(ch.ids)
            if parts is None:
                parts = [[] for _ in trees]
            for lst, t in zip(parts, trees):
                lst.append(jax.tree.map(lambda x: x[:keep], t))
            aux_parts.append(aux[:keep])
        cat = lambda ps: jax.tree.map(
            lambda *xs: jnp.concatenate(xs, axis=0), *ps)
        return tuple(cat(ps) for ps in parts), jnp.concatenate(aux_parts)

    def eval_personalized_stream(self, per_params, per_bstats,
                                 split: str = "test") -> dict[str, float]:
        """Personalized eval when only the STATE is device-resident: stream
        the cohort's eval shards in client chunks and gather each chunk's
        rows out of the stacked per-client state. Per-client metrics are
        independent, so chunked results match the resident vmap bitwise."""
        chunk = self._eval_chunk_size()
        parts: list[tuple] = []
        ns: list[np.ndarray] = []
        for ch in self.stream.eval_chunks(chunk, split):
            p = pt.tree_stack_index(per_params, ch.padded_ids)
            b = pt.tree_stack_index(per_bstats, ch.padded_ids)
            with obs_trace.span(obs_names.SPAN_EVAL_DISPATCH,
                                program="eval_personalized", split=split,
                                **self._eval_span_args(ch.X, split, ch.ids)):
                out = self._eval_personal_jit(p, b, ch.X, ch.y, ch.n)
            with obs_trace.span(obs_names.SPAN_EVAL_SYNC,
                                program="eval_personalized"):
                parts.append(tuple(np.asarray(o)[: len(ch.ids)]
                                   for o in out))
                ns.append(np.asarray(jax.device_get(ch.n))[: len(ch.ids)])
            if self.cfg.fed.ci:
                break
        cat = [np.concatenate([p[i] for p in parts]) for i in range(4)]
        n_all = np.concatenate(ns)
        if self.cfg.fed.ci:  # client 0 only, matching the resident CI path
            cat = [c[:1] for c in cat]
            n_all = n_all[:1]
        return self._summarize(*cat, n=n_all)

    def train(self) -> dict[str, Any]:
        raise NotImplementedError
