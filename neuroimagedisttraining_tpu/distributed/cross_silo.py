"""Cross-silo federated orchestration over the socket control plane.

The capability SURVEY §2.3 requires: a server/client message loop carrying
the reference protocol {register -> init/broadcast params -> local train ->
upload update -> aggregate -> sync or finish} (client_manager.py /
server_manager.py semantics), as runnable processes. Within one silo the
bulk compute path is still the jitted SPMD round program; this layer
coordinates *between* silos (separate hosts/processes), where the
reference's MPI/gRPC runtime would have lived — model payloads ride the
msgpack codec, and each silo trains with its own jitted LocalTrainer round.

``FedAvgServer.run()`` drives ``comm_round`` rounds; each
``FedAvgClientProc`` owns a ``train_fn(params, round_idx) -> (params,
num_samples)`` — silos are free to implement it with any engine. Weighted
aggregation happens on the server in float32 numpy (parity:
fedavg_api.py:102-117).

Multi-host TPU pods: use ``init_multihost`` (jax.distributed) so each silo
process joins one global JAX runtime and bulk tensors can instead ride DCN
collectives; the socket plane then only carries control messages.
"""

from __future__ import annotations

import logging
import os
import threading
import time
from typing import Callable

import numpy as np
import jax
import jax.numpy as jnp

from neuroimagedisttraining_tpu.codec import wire as codec
from neuroimagedisttraining_tpu.distributed import message as M
from neuroimagedisttraining_tpu.distributed.managers import (
    ClientManager, ServerManager,
)
from neuroimagedisttraining_tpu.obs import flight as obs_flight
from neuroimagedisttraining_tpu.obs import metrics as obs_metrics
from neuroimagedisttraining_tpu.obs import trace as obs_trace
from neuroimagedisttraining_tpu.utils.pytree import tree_weighted_mean
from neuroimagedisttraining_tpu.obs import names as obs_names
from neuroimagedisttraining_tpu.obs import rules as obs_rules

log = logging.getLogger("neuroimagedisttraining_tpu.cross_silo")

_weighted_mean_jit = None


def survivor_weighted_mean(trees: list, ns: list[float]):
    """Sample-count-weighted mean over whatever subset of clients
    reported — THE jitted engine aggregation (utils/pytree
    ``tree_weighted_mean``, the op ``FederatedEngine.aggregate`` lowers
    to for frac-sampled rounds), so a deadline-truncated cross-silo
    round is bitwise-identical to an engine round over the same survivor
    set (pinned in tests/test_faults.py)."""
    global _weighted_mean_jit
    if _weighted_mean_jit is None:
        _weighted_mean_jit = jax.jit(tree_weighted_mean)
    stacked = jax.tree.map(
        lambda *xs: jnp.stack([jnp.asarray(x) for x in xs]), *trees)
    out = _weighted_mean_jit(stacked, jnp.asarray(ns, jnp.float32))
    return jax.tree.map(lambda x: np.asarray(x), out)


#: one compiled defended-aggregation program per (defense, f, iters,
#: bound) config — the server aggregates with the SAME jitted
#: core/robust.py dispatch the simulated engines trace into their round
#: bodies, so a cross-silo defended round matches an engine round over
#: the same survivor set
_defended_jit_cache: dict = {}


def survivor_defended_mean(trees: list, ns: list[float], reference, *,
                           defense: str, byz_f: int = 1,
                           geomed_iters: int = 8, norm_bound: float = 5.0,
                           stddev: float = 0.0, rngs=None):
    """Defended aggregation over whatever subset of clients reported:
    ``--defense`` dispatches through ``robust.aggregate_with_defense``
    (clip family per client then the weighted mean; order-statistic
    family replaces the mean). ``reference`` is the round's broadcast
    model — the clip/sanitize baseline the engines use. ``weak_dp``
    additionally needs ``rngs`` ([C] stacked per-client PRNG keys, one
    per reporting silo) and a noise ``stddev``."""
    from neuroimagedisttraining_tpu.core import robust

    key = (defense, int(byz_f), int(geomed_iters), float(norm_bound),
           float(stddev))
    fn = _defended_jit_cache.get(key)
    if fn is None:
        if defense == "weak_dp":
            def agg(stacked, w, ref, rngs):
                return robust.aggregate_with_defense(
                    stacked, ref, w, defense=defense,
                    norm_bound=norm_bound, stddev=stddev, rngs=rngs,
                    byz_f=byz_f, geomed_iters=geomed_iters)
        else:
            def agg(stacked, w, ref):
                return robust.aggregate_with_defense(
                    stacked, ref, w, defense=defense,
                    norm_bound=norm_bound, byz_f=byz_f,
                    geomed_iters=geomed_iters)

        fn = _defended_jit_cache[key] = jax.jit(agg)
    stacked = jax.tree.map(
        lambda *xs: jnp.stack([jnp.asarray(x) for x in xs]), *trees)
    args = (stacked, jnp.asarray(ns, jnp.float32),
            jax.tree.map(jnp.asarray, reference))
    if defense == "weak_dp":
        if rngs is None:
            raise ValueError("weak_dp needs per-client rngs")
        args = args + (rngs,)
    out = fn(*args)
    return jax.tree.map(lambda x: np.asarray(x), out)


def tree_all_finite(tree) -> bool:
    """Host-side: every leaf of ``tree`` is NaN/Inf-free. The server's
    hard gate on decoded uploads — one non-finite frame folded into the
    weighted mean poisons the aggregate for every honest silo."""
    return all(np.isfinite(np.asarray(x, np.float64)).all()
               for x in jax.tree.leaves(tree))


def update_outlier_flags(trees: list, reference, *,
                         norm_mult: float = 4.0,
                         cos_thresh: float = -0.5):
    """Per-silo anomaly flags over one round's decoded uploads: silo i is
    flagged when its update delta (vs the round's broadcast
    ``reference``) has norm > ``norm_mult`` x the cohort median, or
    cosine < ``cos_thresh`` against the mean delta of the OTHER silos
    (a sign-flipped upload scores ~-1 there; leave-one-out keeps a big
    attacker from dragging the comparison direction toward itself).
    Host numpy float64 — this is control-plane scoring over a handful of
    silos, not the jitted aggregation. Returns ``(flags, norms)``."""
    vecs = [np.concatenate([
        (np.asarray(a, np.float64) - np.asarray(b, np.float64)).ravel()
        for a, b in zip(jax.tree.leaves(t), jax.tree.leaves(reference))])
        for t in trees]
    V = np.stack(vecs)
    norms = np.linalg.norm(V, axis=1)
    med = float(np.median(norms))
    total = V.sum(axis=0)
    n = len(trees)
    flags = []
    for i in range(n):
        flag = med > 0 and norms[i] > norm_mult * med
        if not flag and n >= 3 and norms[i] > 0:
            others = (total - V[i]) / (n - 1)
            o_norm = np.linalg.norm(others)
            if o_norm > 0:
                cos = float(V[i] @ others) / (norms[i] * o_norm)
                flag = cos < cos_thresh
        flags.append(bool(flag))
    return flags, norms


def init_multihost(coordinator_address: str, num_processes: int,
                   process_id: int) -> None:
    """Join this process to a multi-host JAX runtime (DCN collectives).
    Thin wrapper so silos opt in with one call; requires all processes to
    call it before any backend touch.

    On real TPU pods this makes every host's chips part of one global mesh
    (libtpu handles cross-host wiring) so the client axis spans hosts and
    aggregation rides ICI/DCN. On the CPU backend two processes cluster
    too (Gloo collectives), which is how the hook is tested without a
    pod (tests/test_distributed.py::
    test_init_multihost_two_processes_cluster)."""
    jax.distributed.initialize(coordinator_address=coordinator_address,
                               num_processes=num_processes,
                               process_id=process_id)


def _to_numpy_tree(tree):
    return jax.tree.map(lambda x: np.asarray(x), tree)


class FedAvgServer(ServerManager):
    """Rank 0. Aggregates client updates sample-weighted per round.

    Fault tolerance (all opt-in; defaults reproduce the strict
    wait-for-everyone protocol):

    - ``round_deadline`` > 0 arms a per-round timer. When it fires with
      at least ``quorum`` uploads, the server aggregates over the
      survivors with sample-count re-weighting (the same jitted
      ``tree_weighted_mean`` the engines use for frac-sampled rounds)
      and marks the missing clients suspect; with fewer than ``quorum``
      it re-arms and keeps waiting — quorum is a hard floor, never
      silently lowered.
    - uploads are tagged with ``round_idx``: stale uploads (a straggler
      finishing after the deadline aggregated without it) and duplicate
      frames (a chaotic transport re-delivering) can never double-count.
    - ``heartbeat_timeout`` > 0 starts a monitor that marks clients
      suspect once their heartbeat goes stale — a crashed client is
      flagged within ~``timeout + timeout/4`` even mid-round.
    - a suspect client that re-registers is shipped the current round's
      model directly (late rejoin) and leaves the suspect set; a fresh
      upload or heartbeat also clears suspicion.

    Wire codec (ISSUE 3): uploads may arrive as tagged codec frames
    (codec/wire.py) instead of dense pytrees; ``_on_model`` decodes them
    BEFORE the weighted aggregation, against ``self.params`` — the
    round's broadcast model, which the round-tag accept gate guarantees
    is the delta reference the sender used. The DOWNLINK sync stays
    dense by design: a late-rejoining or deadline-skipped client has no
    agreed delta reference, and a dense broadcast means the reference
    chain can never desync under chaos (drops/dups/restarts).
    ``wire_masks`` is the engine mask handoff for shared-mask frames —
    the same pruning mask the encoding silos hold (e.g. SalientGrads'
    phase-1 global mask), letting them ship surviving values with no
    bitmap at all.

    Byzantine robustness (ISSUE 5):

    - decoded uploads that carry NaN/Inf are HARD-REJECTED before they
      can touch the aggregation (counted in ``byz_stats``, the sender
      treated like any other straggler by the deadline/quorum path) —
      this guard is unconditional, independent of ``defense``.
    - ``defense`` selects the aggregation rule (core/robust.py): the
      clip family transforms per silo before the weighted mean; the
      order-statistic family (trimmed_mean/median/krum/multi_krum/
      geometric_median) replaces the mean and tolerates up to ``byz_f``
      arbitrary silos. Validated at construction — an unknown name can
      never surface mid-round. ``defense="none"`` keeps the exact
      ``survivor_weighted_mean`` path (the engine-parity pin).
    - ``quarantine_rounds`` > 0 arms server-side DETECTION: every
      aggregation scores the survivors' update deltas (norm vs the
      cohort median, cosine vs the leave-one-out mean —
      ``update_outlier_flags``); flagged silos accrue strikes (one
      clean round forgives one strike), and ``outlier_threshold``
      strikes quarantine the silo for ``quarantine_rounds`` rounds —
      its uploads are dropped at accept time and it leaves the
      round-completion expected set, the same exclusion path the PR 2
      heartbeat-suspicion machinery uses for corpses. At most ``byz_f``
      silos are quarantined at once (the defense's own threat budget);
      the first sync after a silo's window ends carries
      ``ARG_EF_RESET``, clearing the silo's codec error-feedback stack
      (the EF mass it accumulated against dropped frames corresponds to
      nothing the server ever aggregated).
    """

    def __init__(self, init_params, comm_round: int, num_clients: int,
                 world_size: int | None = None, round_deadline: float = 0.0,
                 quorum: int = 0, heartbeat_timeout: float = 0.0,
                 wire_masks=None, defense: str = "none", byz_f: int = 1,
                 geomed_iters: int = 8, norm_bound: float = 5.0,
                 stddev: float = 0.05, defense_seed: int = 0,
                 quarantine_rounds: int = 0, outlier_threshold: int = 2,
                 dp_delta: float = 1e-5, **kw):
        from neuroimagedisttraining_tpu.core import robust

        super().__init__(rank=0, world_size=world_size or num_clients + 1,
                         **kw)
        # defense config fails loudly HERE (startup), never mid-round
        self.defense = robust.validate_defense(defense)
        self.byz_f = int(byz_f)
        self.geomed_iters = int(geomed_iters)
        self.norm_bound = float(norm_bound)
        self.stddev = float(stddev)
        #: weak_dp noise stream root: per-round keys fold_in from here so
        #: the noise is deterministic given (defense_seed, round, silo)
        self.defense_seed = int(defense_seed)
        if self.defense in robust.ROBUST_AGGREGATORS:
            robust._check_f(num_clients, self.byz_f, self.defense)
        self.quarantine_rounds = int(quarantine_rounds)
        self.outlier_threshold = int(outlier_threshold)
        #: value-anomaly strike counters (suspicion for BAD VALUES, the
        #: analogue of the heartbeat suspicion set for dead silos)
        self._strikes: dict[int, int] = {}
        #: client -> first round index AFTER its quarantine window
        self._quarantine_until: dict[int, int] = {}
        #: silos owed an ARG_EF_RESET on their next post-window sync
        self._ef_reset_pending: set[int] = set()
        self.byz_stats = {"nonfinite_rejected": 0, "outlier_flags": 0,
                          "quarantines": []}
        #: weak_dp RDP ledger (privacy/accountant.py): per-silo Renyi
        #: moments accumulated on every weak_dp aggregation the silo's
        #: upload entered, converted to (epsilon, dp_delta) at report
        #: time. Host numpy under _rlock — never touches a trace.
        self.dp_delta = float(dp_delta)
        self._dp_rdp: dict[int, np.ndarray] = {}
        self._dp_round_info: dict | None = None
        self.params = _to_numpy_tree(init_params)
        self.wire_masks = (_to_numpy_tree(wire_masks)
                           if wire_masks is not None else None)
        self.comm_round = comm_round
        self.num_clients = num_clients
        self.round_deadline = float(round_deadline)
        self.quorum = int(quorum) if quorum > 0 else num_clients
        self.heartbeat_timeout = float(heartbeat_timeout)
        self.round_idx = 0
        self._registered: set[int] = set()
        self._updates: dict[int, tuple] = {}
        #: silos whose THIS-round upload was hard-rejected (non-finite):
        #: they have reported — there is nothing to wait for — so they
        #: leave the round's expected set (without this, a NaN-uploading
        #: silo with fresh heartbeats deadlocks a no-deadline federation:
        #: its frame bounces but the round keeps waiting for it forever)
        self._rejected_round: set[int] = set()
        self.history: list[dict] = []
        self._done = threading.Event()
        #: guards all round state: handlers run on the dispatch thread,
        #: the deadline timer and heartbeat monitor on their own threads
        self._rlock = threading.Lock()
        self._started = False
        self._suspect: set[int] = set()
        self._last_beat: dict[int, float] = {}
        self._timer: threading.Timer | None = None
        #: bumped on every arm/cancel: a fired callback that was blocked
        #: on the lock while the round (or secure phase) moved on must
        #: become a no-op — round_idx alone cannot distinguish the
        #: secure A->B transition within one round
        self._deadline_gen = 0
        # ---- obs plane (ISSUE 9): every metric below publishes from
        # the server's existing accept/aggregate handlers (dispatch and
        # timer threads, under _rlock) — control-plane host code only,
        # never a trace. The flight recorder gets every control-plane
        # DECISION (drop/strike/quarantine/deadline/rejoin/ef-reset);
        # the registry gets the numbers a scrape wants live.
        self._obs_uploads = obs_metrics.counter(
            obs_names.SYNC_UPLOADS,
            "sync-server upload admission verdicts",
            labelnames=("outcome",))
        self._obs_round_wall = obs_metrics.histogram(
            obs_names.SYNC_ROUND_WALL,
            "wall time from a round's sync broadcast to its completion",
            buckets=(0.1, 0.25, 0.5, 1.0, 2.5, 5.0, 10.0, 30.0, 60.0,
                     120.0, 300.0))
        self._obs_quorum_wait = obs_metrics.histogram(
            obs_names.SYNC_QUORUM_WAIT,
            "wall time from a round's FIRST accepted upload to its "
            "aggregation (how long the earliest silo waited on the "
            "barrier)",
            buckets=(0.05, 0.1, 0.25, 0.5, 1.0, 2.5, 5.0, 10.0, 30.0,
                     60.0))
        self._obs_round_gauge = obs_metrics.gauge(
            obs_names.SERVER_ROUND, "current server round/version index")
        self._obs_suspects = obs_metrics.gauge(
            obs_names.SERVER_SUSPECTS, "clients currently marked suspect")
        self._obs_strikes = obs_metrics.counter(
            obs_names.BYZ_STRIKES, "value-anomaly strikes issued")
        self._obs_quarantines = obs_metrics.counter(
            obs_names.BYZ_QUARANTINES, "silo quarantines entered")
        #: wall anchors for round_wall / quorum_wait (monotonic; None
        #: until the first broadcast / first upload of the round)
        self._round_t0: float | None = None
        self._first_upload_t: float | None = None

    @property
    def fault_tolerant(self) -> bool:
        return self.round_deadline > 0 or self.heartbeat_timeout > 0

    def suspect_clients(self) -> set[int]:
        with self._rlock:
            return set(self._suspect)

    # ---- Byzantine detection / quarantine (ISSUE 5) ----

    def _quarantined_now(self) -> set[int]:
        """Under ``_rlock``: silos inside an active quarantine window."""
        return {c for c, until in self._quarantine_until.items()
                if self.round_idx < until}

    def quarantined_clients(self) -> set[int]:
        with self._rlock:
            return self._quarantined_now()

    def _strike(self, c: int, why: str) -> None:
        """Under ``_rlock``: one value-anomaly strike against silo
        ``c``; at ``outlier_threshold`` strikes the silo is quarantined
        — unless the byz_f budget of concurrent quarantines is already
        spent (quarantining more silos than the threat model's f would
        let a clever attacker starve the federation of honest silos)."""
        self._strikes[c] = self._strikes.get(c, 0) + 1
        self.byz_stats["outlier_flags"] += 1
        self._obs_strikes.inc()
        obs_flight.record("strike", client=c, count=self._strikes[c],
                          threshold=self.outlier_threshold, why=why,
                          round=self.round_idx)
        log.warning("server: value-anomaly strike %d/%d against silo %d "
                    "(%s)", self._strikes[c], self.outlier_threshold, c,
                    why)
        if self._strikes[c] < self.outlier_threshold:
            return
        if len(self._quarantined_now()) >= max(1, self.byz_f):
            log.warning("server: silo %d hit the strike threshold but "
                        "the quarantine budget (byz_f=%d) is spent",
                        c, self.byz_f)
            return
        until = self.round_idx + 1 + self.quarantine_rounds
        self._quarantine_until[c] = until
        self._strikes[c] = 0
        self._ef_reset_pending.add(c)
        self._obs_quarantines.inc()
        obs_flight.record("quarantine", client=c,
                          from_round=self.round_idx + 1,
                          until_round=until)
        self.byz_stats["quarantines"].append(
            {"client": c, "from_round": self.round_idx + 1,
             "until_round": until})
        log.warning("server: QUARANTINED silo %d for rounds [%d, %d) — "
                    "its uploads are excluded from aggregation; its "
                    "first post-window sync will carry ef_reset", c,
                    self.round_idx + 1, until)

    # ---- weak_dp accounting (privacy/, ISSUE 8) ----

    def _note_weak_dp(self, senders: list[int],
                      ws: list[float]) -> dict | None:
        """Under ``_rlock``: charge one weak_dp round to every silo whose
        upload entered this aggregation. The mechanism per round is a
        full-participation (q=1) Gaussian with effective multiplier
        ``weak_dp_noise_multiplier`` over the ACTUAL round weights; RDP
        composes additively per silo, so deadline-truncated rounds
        charge only the survivors. Returns the round's observability
        record (clip bound, sigma, z, per-silo epsilon) for history — or
        None when the configured geometry provides no DP to account
        (stddev/norm_bound <= 0, a valid no-noise ablation: warn once,
        never die mid-aggregation on a dispatch/timer thread)."""
        from neuroimagedisttraining_tpu.privacy import accountant as acct

        if self.stddev <= 0 or self.norm_bound <= 0:
            if not getattr(self, "_warned_dp_disabled", False):
                self._warned_dp_disabled = True
                log.warning(
                    "weak_dp with stddev=%s/norm_bound=%s adds no "
                    "accountable noise — epsilon is infinite; the RDP "
                    "ledger records nothing", self.stddev,
                    self.norm_bound)
            return None
        try:
            z = acct.weak_dp_noise_multiplier(self.stddev,
                                              self.norm_bound, ws)
        except ValueError as e:
            # degenerate round weights (all-zero survivors, a NaN n the
            # admission gates let through): skip the charge with a
            # warning — this runs on dispatch/timer threads, where an
            # escape would hang the federation
            log.warning("weak_dp ledger: skipping round %d charge "
                        "(%s)", self.round_idx, e)
            return None
        step = acct.rdp_gaussian(1.0, z)
        eps = {}
        eps_gauge = obs_metrics.gauge(
            obs_names.DP_EPSILON_SILO,
            "running weak_dp epsilon per silo (server RDP ledger, "
            "privacy/accountant.py)", labelnames=("silo",))
        # burn RATE alongside the running total (ISSUE 15 satellite):
        # what THIS round cost each silo — the series a budget
        # burn-rate rule can watch; label scheme matches the engine
        # ledger's source-labeled registration (engines/base.py)
        burn_gauge = obs_metrics.gauge(
            obs_names.DP_EPSILON_PER_ROUND,
            "epsilon spent by the last accounted round (the budget "
            "burn rate --dp_epsilon_budget is judged against)",
            labelnames=("source",))
        for c in senders:
            prev = self._dp_rdp.get(c)
            prev_eps = (acct.rdp_to_epsilon(prev,
                                            delta=self.dp_delta)[0]
                        if prev is not None else 0.0)
            self._dp_rdp[c] = self._dp_rdp.get(c, 0.0) + step
            eps[c] = acct.rdp_to_epsilon(self._dp_rdp[c],
                                         delta=self.dp_delta)[0]
            eps_gauge.labels(silo=c).set(float(eps[c]))
            burn_gauge.labels(source=f"silo{c}").set(
                float(eps[c] - prev_eps))
        return {"norm_bound": self.norm_bound, "stddev": self.stddev,
                "noise_multiplier": round(z, 6), "delta": self.dp_delta,
                "epsilon_per_silo": {c: round(e, 4)
                                     for c, e in eps.items()}}

    def dp_report(self) -> dict | None:
        """Run-end per-silo (epsilon, delta) from the weak_dp ledger, or
        None when the defense never charged a round."""
        from neuroimagedisttraining_tpu.privacy import accountant as acct

        with self._rlock:
            if not self._dp_rdp:
                return None
            return {"defense": "weak_dp", "delta": self.dp_delta,
                    "norm_bound": self.norm_bound, "stddev": self.stddev,
                    "epsilon_per_silo": {
                        c: round(acct.rdp_to_epsilon(
                            rdp, delta=self.dp_delta)[0], 4)
                        for c, rdp in sorted(self._dp_rdp.items())}}

    def _score_survivors(self, senders: list[int], trees: list) -> None:
        """Under ``_rlock``: norm/cosine outlier scoring over this
        round's accepted uploads -> strikes. A silo that scores clean
        this round is forgiven one prior strike (transient turbulence —
        a bad batch, an lr spike — should not accumulate forever)."""
        if self.quarantine_rounds <= 0 or len(senders) < 3:
            return
        flags, norms = update_outlier_flags(trees, self.params)
        for c, flag, nrm in zip(senders, flags, norms):
            if flag:
                self._strike(c, f"update-delta outlier, |u|={nrm:.3g} "
                                f"round {self.round_idx}")
            elif self._strikes.get(c, 0) > 0:
                self._strikes[c] -= 1

    def run(self) -> None:
        if self.heartbeat_timeout > 0:
            threading.Thread(target=self._monitor_loop, daemon=True).start()
        super().run()

    # ---- handlers ----

    def register_message_receive_handlers(self) -> None:
        self.register_message_receive_handler(
            M.MSG_TYPE_C2S_REGISTER, self._on_register)
        self.register_message_receive_handler(
            M.MSG_TYPE_C2S_SEND_MODEL, self._on_model)
        self.register_message_receive_handler(
            M.MSG_TYPE_C2S_HEARTBEAT, self._on_heartbeat)

    def _on_register(self, msg: M.Message) -> None:
        with self._rlock:
            c = msg.sender_id
            self._registered.add(c)
            self._suspect.discard(c)
            self._last_beat[c] = time.monotonic()
            if not self._started:
                if len(self._registered) == self.num_clients:
                    self._started = True
                    self._broadcast_sync(M.MSG_TYPE_S2C_INIT_CONFIG)
            else:
                # late rejoin: ship the CURRENT round state directly so a
                # restarted silo re-enters without waiting a full round
                obs_flight.record("rejoin", client=c,
                                  round=self.round_idx)
                log.info("server: client %d re-registered; shipping "
                         "round %d state", c, self.round_idx)
                self._send_sync_to(M.MSG_TYPE_S2C_SYNC_MODEL, c)

    def _on_heartbeat(self, msg: M.Message) -> None:
        with self._rlock:
            self._last_beat[msg.sender_id] = time.monotonic()
            self._suspect.discard(msg.sender_id)

    def _accept_update(self, msg: M.Message) -> bool:
        """Round-tag + duplicate gate (call under ``_rlock``): True iff
        this upload belongs to the current round and is the sender's
        first. Stale rounds and re-delivered frames never double-count."""
        r = msg.get(M.ARG_ROUND_IDX)
        if r is not None and int(r) != self.round_idx:
            self._obs_uploads.inc(outcome="stale")
            obs_flight.record("drop_stale", client=msg.sender_id,
                              tagged_round=int(r), round=self.round_idx)
            log.warning("server: dropping stale upload from %d "
                        "(round %s, current %d)", msg.sender_id, r,
                        self.round_idx)
            return False
        if msg.sender_id in self._updates:
            self._obs_uploads.inc(outcome="duplicate")
            obs_flight.record("drop_duplicate", client=msg.sender_id,
                              round=self.round_idx)
            log.warning("server: dropping duplicate upload from %d "
                        "(round %d)", msg.sender_id, self.round_idx)
            return False
        if msg.sender_id in self._quarantined_now():
            self._obs_uploads.inc(outcome="quarantined")
            obs_flight.record("drop_quarantined", client=msg.sender_id,
                              round=self.round_idx)
            log.warning("server: dropping upload from QUARANTINED silo "
                        "%d (round %d; window ends at round %d)",
                        msg.sender_id, self.round_idx,
                        self._quarantine_until[msg.sender_id])
            return False
        return True

    def _on_model(self, msg: M.Message) -> None:
        with self._rlock:
            if self._done.is_set() or not self._accept_update(msg):
                return
            # decode BEFORE aggregation: self.params is still the round's
            # broadcast model here (it only advances in
            # _aggregate_and_advance), so it IS the sender's delta
            # reference; the accept gate above already rejected any frame
            # from another round. Dense uploads pass through untouched.
            try:
                decoded = codec.decode_update(msg.get(M.ARG_MODEL_PARAMS),
                                              like=self.params,
                                              reference=self.params,
                                              masks=self.wire_masks)
            except Exception as e:  # noqa: BLE001 — an undecodable frame
                # (version skew, mask-config mismatch, zlib.error /
                # msgpack OutOfData from bit rot the transport let
                # through) is a DROPPED upload, not a dead dispatch
                # thread — the deadline/quorum machinery treats the
                # sender like any other straggler. Narrow catches here
                # would let a malformed body kill server.run() (the
                # dispatch loop has no guard of its own).
                self._obs_uploads.inc(outcome="undecodable")
                obs_flight.record("drop_undecodable",
                                  client=msg.sender_id,
                                  round=self.round_idx, error=str(e))
                log.warning("server: dropping undecodable upload from %d "
                            "(round %d): %s", msg.sender_id,
                            self.round_idx, e)
                return
            # non-finite hard gate (unconditional, before any defense):
            # one NaN/Inf frame folded into the mean poisons every silo.
            # The sender is treated like a straggler by deadline/quorum,
            # and the rejection counts as a value-anomaly strike — a
            # silo shipping NaNs every round earns its quarantine.
            if not tree_all_finite(decoded):
                self.byz_stats["nonfinite_rejected"] += 1
                self._obs_uploads.inc(outcome="nonfinite")
                obs_flight.record("reject_nonfinite",
                                  client=msg.sender_id,
                                  round=self.round_idx)
                log.warning("server: REJECTING non-finite (NaN/Inf) "
                            "upload from silo %d (round %d; %d rejected "
                            "so far)", msg.sender_id, self.round_idx,
                            self.byz_stats["nonfinite_rejected"])
                if self.quarantine_rounds > 0:
                    self._strike(msg.sender_id, "non-finite upload")
                # the silo HAS reported — nothing left to wait for this
                # round; drop it from the expected set so a no-deadline
                # federation cannot deadlock on its bounced frame
                self._rejected_round.add(msg.sender_id)
                self._maybe_complete()
                return
            if not self._updates:
                self._first_upload_t = time.monotonic()
            self._updates[msg.sender_id] = (
                decoded, float(msg.get(M.ARG_NUM_SAMPLES)))
            self._obs_uploads.inc(outcome="accepted")
            self._last_beat[msg.sender_id] = time.monotonic()
            self._suspect.discard(msg.sender_id)
            self._maybe_complete()

    def _maybe_complete(self) -> None:
        """Under ``_rlock``: aggregate as soon as every non-suspect,
        non-quarantined client has reported (and the quorum floor holds)
        — suspects are picked up by the deadline path if they resurface;
        quarantined silos' uploads are dropped at accept time, so
        waiting for them would deadlock the round."""
        expected = (set(range(1, self.num_clients + 1)) - self._suspect
                    - self._quarantined_now() - self._rejected_round)
        have = set(self._updates)
        if not have and not expected and self._rejected_round:
            # every live silo reported and EVERY upload bounced at the
            # non-finite gate: nothing to aggregate and nobody left to
            # wait for — advance with the global model unchanged (the
            # rejected silos train again from the next sync) instead of
            # hanging the federation on its own rejection set (with no
            # deadline nothing else fires: the rejected silos keep
            # heartbeating, so the suspicion monitor never will)
            log.warning("server: round %d has ZERO accepted uploads "
                        "(%d rejected as non-finite) - rebroadcasting "
                        "the unchanged global model", self.round_idx,
                        len(self._rejected_round))
            if self._timer is not None:
                self._timer.cancel()
            self._rejected_round.clear()
            self._complete_round(0, survivors=[])
            return
        if not have or not expected <= have or len(have) < min(
                self.quorum, self._effective_cohort()):
            return
        self._aggregate_and_advance()

    def _effective_cohort(self) -> int:
        """Under ``_rlock``: cohort size the quorum floor applies to —
        quarantined silos can never report, and hard-rejected uploads
        never will be accepted this round, so holding the floor at
        ``num_clients`` would hang a small federation whose quorum was
        sized for the full cohort."""
        return max(1, self.num_clients - len(self._quarantined_now()
                                             | self._rejected_round))

    def _aggregate_and_advance(self) -> None:
        """Under ``_rlock``: defended aggregation over whoever reported.
        ``defense="none"`` keeps the exact jitted
        ``survivor_weighted_mean`` (fedavg_api.py:102-117 semantics, the
        engine-parity pin in tests/test_faults.py); any other defense
        dispatches through the SAME core/robust.py program the simulated
        engines trace into their round bodies. Outlier scoring runs
        FIRST, so a silo quarantined this round is excluded from this
        very aggregation."""
        from neuroimagedisttraining_tpu.core import robust

        if self._timer is not None:
            self._timer.cancel()
        senders = sorted(self._updates)
        trees = [self._updates[s][0] for s in senders]
        self._score_survivors(senders, trees)
        q = self._quarantined_now()
        if q & set(senders):
            senders = [s for s in senders if s not in q]
            trees = [self._updates[s][0] for s in senders]
        ws = [self._updates[s][1] for s in senders]
        # deadline truncation can shrink the survivor set below the
        # aggregator's breakdown requirement; an undefended round beats
        # a dead server — the SAME feasibility rule the engines resolve
        # at trace time (core/robust.py::effective_defense)
        defense = robust.effective_defense(
            self.defense, len(senders), self.byz_f, warn=log.warning)
        if defense == "none":
            self.params = survivor_weighted_mean(trees, ws)
        else:
            rngs = None
            if defense == "weak_dp":
                # deterministic per-(seed, round, silo) noise keys, the
                # same fold_in discipline the attack/engine streams use
                base = jax.random.fold_in(
                    jax.random.key(self.defense_seed), self.round_idx)
                rngs = jax.vmap(
                    lambda s: jax.random.fold_in(base, s))(
                    jnp.asarray(senders, jnp.uint32))
                self._dp_round_info = self._note_weak_dp(senders, ws)
            self.params = survivor_defended_mean(
                trees, ws, self.params, defense=defense,
                byz_f=self.byz_f, geomed_iters=self.geomed_iters,
                norm_bound=self.norm_bound, stddev=self.stddev,
                rngs=rngs)
        self._updates.clear()
        self._rejected_round.clear()
        self._complete_round(len(senders), survivors=senders)

    # ---- deadline / heartbeat machinery ----

    def _arm_deadline(self) -> None:
        if self.round_deadline <= 0 or self._done.is_set():
            return
        if self._timer is not None:
            self._timer.cancel()
        self._deadline_gen += 1
        self._timer = threading.Timer(
            self.round_deadline, self._on_deadline,
            args=(self.round_idx, self._deadline_gen))
        self._timer.daemon = True
        self._timer.start()

    def _deadline_stale(self, round_for: int, gen: int) -> bool:
        """Under ``_rlock``: True iff this callback belongs to a window
        that was superseded while the callback waited for the lock."""
        return (self._done.is_set() or self.round_idx != round_for
                or gen != self._deadline_gen)

    def _mark_missing_suspect(self, have: set[int]) -> None:
        """Under ``_rlock``: clients that missed the deadline become
        suspect — unless their heartbeat is still fresh (a straggler,
        not a corpse; it may catch up next round) or they are
        quarantined (their uploads were dropped by design)."""
        for c in (set(range(1, self.num_clients + 1)) - have
                  - self._quarantined_now()):
            if self._beat_stale(c):
                log.warning("server: marking client %d suspect "
                            "(missed round %d deadline)", c, self.round_idx)
                self._suspect.add(c)
                obs_flight.record("suspect", client=c,
                                  round=self.round_idx,
                                  why="missed deadline")
        self._obs_suspects.set(len(self._suspect))

    def _beat_stale(self, c: int) -> bool:
        if self.heartbeat_timeout <= 0:
            return True  # no liveness signal configured: missing == dead
        last = self._last_beat.get(c)
        return last is None or (time.monotonic() - last
                                > self.heartbeat_timeout)

    def _on_deadline(self, round_for: int, gen: int) -> None:
        with self._rlock:
            if self._deadline_stale(round_for, gen):
                return
            obs_flight.record("deadline", round=round_for,
                              have=len(self._updates),
                              quorum=min(self.quorum, self.num_clients))
            if self._updates and len(self._updates) >= min(
                    self.quorum, self.num_clients):
                self._mark_missing_suspect(set(self._updates))
                log.warning("server: round %d deadline - aggregating %d/%d "
                            "survivors", round_for, len(self._updates),
                            self.num_clients)
                self._aggregate_and_advance()
            else:
                self._arm_deadline()  # below quorum: keep waiting

    def _monitor_loop(self) -> None:
        poll = max(0.05, self.heartbeat_timeout / 4)
        while not self._done.wait(poll):
            now = time.monotonic()
            with self._rlock:
                if self._done.is_set():
                    return
                for c, last in list(self._last_beat.items()):
                    if (now - last > self.heartbeat_timeout
                            and c not in self._suspect):
                        log.warning("server: heartbeat from client %d "
                                    "stale (%.2fs) - marking suspect",
                                    c, now - last)
                        self._suspect.add(c)
                        obs_flight.record(
                            "suspect", client=c, round=self.round_idx,
                            why=f"heartbeat stale {now - last:.2f}s")
                        self._obs_suspects.set(len(self._suspect))
                if self._started:
                    # a new suspect may have been the only missing
                    # uploader — the round can complete right now
                    self._maybe_complete()

    def _complete_round(self, n_clients: int,
                        survivors: list[int] | None = None) -> None:
        """Shared end-of-round transition: record history, advance, then
        either finish the federation or broadcast the next sync."""
        entry = {"round": self.round_idx, "clients": n_clients}
        now = time.monotonic()
        if self._round_t0 is not None:
            self._obs_round_wall.observe(now - self._round_t0)
        if self._first_upload_t is not None:
            self._obs_quorum_wait.observe(now - self._first_upload_t)
        self._first_upload_t = None
        obs_flight.record("round_complete", round=self.round_idx,
                          clients=n_clients,
                          survivors=list(survivors or []))
        if survivors is not None:
            entry["survivors"] = list(survivors)
        if self._dp_round_info is not None:
            # weak_dp observability (ISSUE 8 satellite): the clip bound,
            # sigma, and running per-silo epsilon this round applied
            entry["weak_dp"] = self._dp_round_info
            self._dp_round_info = None
        if self._suspect:
            entry["suspects"] = sorted(self._suspect)
        q = self._quarantined_now()
        if q:
            entry["quarantined"] = sorted(q)
        self.history.append(entry)
        self.round_idx += 1
        self._obs_round_gauge.set(self.round_idx)
        self._obs_suspects.set(len(self._suspect))
        # training-health boundary (ISSUE 15): every completed round is
        # a host boundary — the armed anomaly rules must see ONE
        # evaluation per round (debounce/window semantics are
        # round-indexed), not whatever cadence a /healthz poller
        # happens to scrape at; unarmed processes no-op
        obs_rules.observe_boundary(self.round_idx)
        if self.round_idx >= self.comm_round:
            if self._timer is not None:
                self._timer.cancel()
            self._broadcast_finish()
            self._done.set()
            self.finish()
        else:
            self._broadcast_sync(M.MSG_TYPE_S2C_SYNC_MODEL)

    # ---- sends ----

    def _send_tolerant(self, msg: M.Message) -> None:
        """In fault-tolerant mode a broadcast target may be dead — use a
        short retry budget and fold failures into suspicion instead of
        crashing the dispatch/timer thread. Legacy mode keeps the strict
        raise-on-unreachable contract.

        NOTE: these sends run under ``_rlock`` (the callers are round
        transitions). A dead same-host peer refuses instantly, so the
        lock hold is sub-second; a WAN peer whose packets are BLACKHOLED
        (no RST) can pin the lock for up to retries x the 10 s connect
        timeout — an accepted tradeoff until broadcasts move to a
        dedicated sender thread."""
        if not self.fault_tolerant:
            self.send_message(msg)
            return
        try:
            try:
                self.com_manager.send_message(msg, retries=3,
                                              retry_delay=0.05)
            except TypeError:  # transport without retry knobs (broker)
                self.com_manager.send_message(msg)
        except (ConnectionError, OSError) as e:
            log.warning("server: client %d unreachable (%s) - marking "
                        "suspect", msg.receiver_id, e)
            self._suspect.add(msg.receiver_id)

    def _send_sync_to(self, msg_type: str, c: int) -> None:
        msg = M.Message(msg_type, 0, c)
        msg.add(M.ARG_MODEL_PARAMS, self.params)
        msg.add(M.ARG_ROUND_IDX, self.round_idx)
        msg.add(M.ARG_CLIENT_INDEX, c - 1)
        if (c in self._ef_reset_pending
                and c not in self._quarantined_now()):
            # first sync after the quarantine window: the silo's codec
            # error-feedback accumulated against frames this server
            # DROPPED — that mass corresponds to nothing aggregated, so
            # re-injecting it would smear stale quarantine-era residuals
            # into honest post-window uploads
            msg.add(M.ARG_EF_RESET, True)
            self._ef_reset_pending.discard(c)
            obs_flight.record("ef_reset", client=c, round=self.round_idx)
            log.info("server: silo %d quarantine window over - sync "
                     "carries ef_reset", c)
        self._send_tolerant(msg)

    def _broadcast_sync(self, msg_type: str) -> None:
        for c in range(1, self.num_clients + 1):
            self._send_sync_to(msg_type, c)
        self._round_t0 = time.monotonic()  # round-wall anchor (obs)
        self._arm_deadline()

    def _broadcast_finish(self) -> None:
        for c in range(1, self.num_clients + 1):
            self._send_tolerant(M.Message(M.MSG_TYPE_S2C_FINISH, 0, c))


class SecureFedAvgServer(FedAvgServer):
    """Secure-aggregation server: clients upload additive SHARE SLOTS of
    their weight-scaled quantized update instead of plaintext params
    (engine parity: TurboAggregateEngine.secure_aggregate; ref
    turboaggregate/mpc_function.py:214-224 Gen_Additive_SS). The round is
    two-phase: clients first report their sample counts in the clear
    (metadata the plain protocol exposes anyway); the server replies with
    each client's NORMALIZED FedAvg weight w_c = n_c / sum n, and clients
    then share ``quantize(w_c * params)`` — with w_c <= 1 the field values
    stay within the fixed-point range regardless of cohort size. The
    server folds each arriving share set into per-slot accumulators
    (slot-major, mod p) and combines slots only once every weighted
    client has reported — or once the deadline+quorum path truncates the
    cohort, in which case the dropped clients' shares were never folded
    (atomic discard) and the dequantized sum is re-weighted over the
    survivors. Either way no stored server-side intermediate equals an
    individual client's update.

    Trust model: with ``n_aggregators == 0`` (the paper's single-
    aggregator degenerate case) each client's n_shares slots transit THIS
    server, which is trusted not to combine one client's slots before
    folding them into the accumulators. With ``n_aggregators == K > 0``
    the grouped deployment the reference's TurboAggregate describes
    (TA_trainer.py:38-85) runs for real: clients send slot j to
    aggregator-j's OS process (``SlotAggregatorProc``), each aggregator
    folds ITS slot across all clients and forwards one cross-client
    total, and this server only ever sees K totals — no single node holds
    enough to reconstruct any client (server included).

    Secure QUANTIZED mode (``quant_spec`` — privacy/secure_quant.py,
    ISSUE 8): phase B uploads become field-element frames in a small
    GF(p) (one wire-dtype residue per parameter + seed-expanded mask
    slots) instead of int64 share stacks, folded slot-major by a
    ``SlotAccumulator`` with the same atomic-discard dropout semantics
    — and bitwise-equal to the plain quantized ``tree_weighted_mean``
    over the survivor set. Quant mode lifts the clip-family defense
    rejection (each silo clips/noises its OWN update pre-share, and the
    weak_dp ledger charges here); order statistics, quarantine, the
    codec, and the grouped aggregator deployment remain out — the full
    matrix lives in ARCHITECTURE.md "Privacy plane"."""

    def __init__(self, init_params, comm_round: int, num_clients: int,
                 frac_bits: int = 16, n_aggregators: int = 0,
                 record_trace: bool = False, quant_spec=None, **kw):
        from neuroimagedisttraining_tpu.core import robust

        defense = kw.get("defense", "none")
        if quant_spec is None and (defense != "none"
                                   or kw.get("quarantine_rounds", 0)):
            # secure-DENSE aggregation is a LINEAR sum over additive
            # shares: the server never observes an individual silo's
            # update, so there is nothing for an order-statistic defense
            # to select over, nothing for the outlier scorer to score,
            # and even clipping would have to run client-side (each silo
            # clips its own update BEFORE sharing — the
            # TurboAggregateEngine composition). The QUANTIZED path
            # (--secure_quant) realizes exactly that composition for the
            # clip family; the full matrix lives in ARCHITECTURE.md
            # "Privacy plane".
            raise ValueError(
                "SecureFedAvgServer supports neither --defense nor "
                "quarantine in dense mode: additive-share aggregation "
                "never reveals per-silo updates to defend over. The "
                "clip-family defenses compose with --secure_quant "
                "(enforced CLIENT-side, pre-share); see ARCHITECTURE.md "
                "'Privacy plane'")
        if quant_spec is not None and (
                defense in robust.ROBUST_AGGREGATORS
                or kw.get("quarantine_rounds", 0)):
            raise ValueError(
                "secure_quant supports neither order-statistic defenses "
                "nor quarantine: the server still only ever sees masked "
                "field elements — there are no per-silo updates to "
                "select over or score. Clip-family defenses "
                "(norm_diff_clipping, weak_dp) run client-side, "
                "pre-share; see ARCHITECTURE.md 'Privacy plane'")
        if kw.get("wire_masks") is not None:
            # Secure aggregation stays structurally DENSE: each upload
            # is masked GF(p) material. Sparsification would leak the
            # client's mask support — the very structure the masking
            # hides — and the codec's float stages would destroy the
            # share algebra. Bandwidth comes from --secure_quant's small
            # field + seed-expanded masks instead (privacy/).
            raise ValueError(
                "SecureFedAvgServer is incompatible with the wire codec "
                "(shares are uniform field elements; encoding them would "
                "break the share algebra or leak mask support — use "
                "--secure_quant for the compressed secure wire)")
        if quant_spec is not None and n_aggregators:
            raise ValueError(
                "secure_quant does not compose with --n_aggregators: its "
                "mask slots ride as PRG seeds, and any node holding a "
                "client's seeds can expand every non-data slot — the "
                "grouped deployment's no-single-node property would be "
                "void. Use the dense --secure protocol for grouped "
                "aggregation (see ARCHITECTURE.md 'Privacy plane')")
        super().__init__(init_params, comm_round, num_clients,
                         world_size=num_clients + 1 + n_aggregators, **kw)
        self.quant_spec = quant_spec
        if quant_spec is not None:
            from neuroimagedisttraining_tpu.privacy import check_headroom

            # accumulator + aggregate-range headroom vs p and the cohort
            # fails HERE (startup), never as silent field wraparound
            check_headroom(quant_spec, num_clients)
        self.frac_bits = frac_bits
        self.n_aggregators = n_aggregators
        #: secure-quant slot accumulator (one per round, lazily built)
        self._sq_acc = None
        #: when record_trace, every post-fold slot-accumulator state
        self.sq_trace: list = [] if record_trace else None
        self._slot_acc: dict | None = None
        self._n_by_client: dict[int, float] = {}
        self._slot_totals: dict[int, dict] = {}
        #: phase within the round: "A" collecting sample counts, "B"
        #: collecting share uploads (deadline behavior differs per phase)
        self._phase = "A"
        #: normalized weight sent to each phase-A reporter this round —
        #: kept so a phase-B dropout can be re-weighted post-dequantize
        self._weights_sent: dict[int, float] = {}
        #: clients whose complete share set was folded this round; a
        #: client is in the aggregate iff it is here — shares from a
        #: dropped client are discarded atomically (its single upload
        #: message either folds whole or, when stale/duplicate, not at
        #: all — there is no partial slot fold)
        self._folded: set[int] = set()
        #: when record_trace, every aggregator total this server saw —
        #: model-sized per round, so tests-only
        self.record_trace = record_trace
        self.received_totals: list = []

    def register_message_receive_handlers(self) -> None:
        super().register_message_receive_handlers()
        self.register_message_receive_handler(
            M.MSG_TYPE_C2S_NUM_SAMPLES, self._on_num_samples)
        self.register_message_receive_handler(
            M.MSG_TYPE_A2S_SLOT_TOTAL, self._on_slot_total)

    # ---- phase A: sample counts -> normalized weights ----

    def _on_num_samples(self, msg: M.Message) -> None:
        with self._rlock:
            r = msg.get(M.ARG_ROUND_IDX)
            if ((r is not None and int(r) != self.round_idx)
                    or self._phase != "A"
                    or msg.sender_id in self._n_by_client):
                log.warning("server: dropping stale/duplicate sample "
                            "count from %d", msg.sender_id)
                return
            self._n_by_client[msg.sender_id] = float(
                msg.get(M.ARG_NUM_SAMPLES))
            self._last_beat[msg.sender_id] = time.monotonic()
            self._suspect.discard(msg.sender_id)
            self._maybe_complete()

    def _send_agg_weights(self) -> None:
        """Under ``_rlock``: close phase A — normalize weights over the
        reporters and open phase B with a fresh deadline window."""
        total = max(sum(self._n_by_client.values()), 1e-12)
        self._weights_sent = {c: n / total
                              for c, n in self._n_by_client.items()}
        for c, w in self._weights_sent.items():
            out = M.Message(M.MSG_TYPE_S2C_AGG_WEIGHTS, 0, c)
            out.add(M.ARG_AGG_WEIGHT, w)
            out.add(M.ARG_ROUND_IDX, self.round_idx)
            self._send_tolerant(out)
        self._n_by_client.clear()
        self._phase = "B"
        self._arm_deadline()

    # ---- phase B: slot-major share accumulation ----

    def _on_model(self, msg: M.Message) -> None:
        with self._rlock:
            if self._done.is_set():
                return
            r = msg.get(M.ARG_ROUND_IDX)
            if (self._phase != "B"
                    or (r is not None and int(r) != self.round_idx)
                    or msg.sender_id in self._folded
                    or msg.sender_id not in self._weights_sent):
                log.warning("server: dropping stale/duplicate/unweighted "
                            "share upload from %d (round %s, current %d)",
                            msg.sender_id, r, self.round_idx)
                return
            self._fold_shares(msg)
            self._last_beat[msg.sender_id] = time.monotonic()
            self._suspect.discard(msg.sender_id)
            self._maybe_complete()

    def _maybe_complete(self) -> None:
        """Under ``_rlock``: phase-aware early completion — advance as
        soon as every non-suspect expected peer has reported (quorum
        floor still holds). Called from the upload handlers and from the
        heartbeat monitor when suspicion changes."""
        floor = min(self.quorum, self.num_clients)
        if self._phase == "A":
            expected = set(range(1, self.num_clients + 1)) - self._suspect
            have = set(self._n_by_client)
            if have and expected <= have and len(have) >= floor:
                self._send_agg_weights()
        else:
            expected = set(self._weights_sent) - self._suspect
            if (self._folded and expected <= self._folded
                    and len(self._folded) >= floor):
                self._finalize_secure()

    def _fold_shares(self, msg: M.Message) -> None:
        from neuroimagedisttraining_tpu.ops import mpc

        if self.quant_spec is not None:
            from neuroimagedisttraining_tpu.privacy import SlotAccumulator

            if self._sq_acc is None:
                # like=self.params locks the expected leaf structure, so
                # a structurally skewed frame (version-skewed silo) is
                # rejected BEFORE any accumulator mutation — the fold
                # stays atomic even for the round's first frame
                self._sq_acc = SlotAccumulator(self.quant_spec,
                                               trace=self.sq_trace,
                                               like=self.params)
            try:
                # atomic: the frame folds whole or not at all (Bonawitz
                # discard — a validation failure leaves the accumulators
                # untouched and the sender a straggler for the
                # deadline/quorum machinery, like an undecodable codec
                # frame on the plain server)
                self._sq_acc.fold(msg.get(M.ARG_MODEL_PARAMS))
            except (ValueError, KeyError, TypeError) as e:
                log.warning("server: dropping invalid secure-quant frame "
                            "from %d (round %d): %s", msg.sender_id,
                            self.round_idx, e)
                return
            self._folded.add(msg.sender_id)
            return
        shares_tree = msg.get(M.ARG_MODEL_PARAMS)  # leaves: [n_shares, ...]
        if self._slot_acc is None:
            self._slot_acc = jax.tree.map(
                lambda s: np.asarray(s, np.int64) % mpc.P_DEFAULT,
                shares_tree)
        else:
            self._slot_acc = jax.tree.map(
                lambda acc, s: (acc + np.asarray(s, np.int64))
                % mpc.P_DEFAULT, self._slot_acc, shares_tree)
        self._folded.add(msg.sender_id)

    def _finalize_secure(self) -> None:
        """Under ``_rlock``: combine slots and dequantize. When every
        phase-A reporter folded, the slot total IS the weighted mean
        (weights sum to 1 client-side). When a reporter dropped between
        phases, the survivors' weights sum to W < 1 — re-weight by 1/W
        post-dequantize so the aggregate stays a true weighted mean over
        the survivor set (Bonawitz-style dropout tolerance)."""
        from neuroimagedisttraining_tpu.ops import mpc

        if self._timer is not None:
            self._timer.cancel()
        w_sum = sum(self._weights_sent.get(c, 0.0) for c in self._folded)
        rescale = (1.0 / w_sum
                   if self._folded != set(self._weights_sent) and w_sum > 0
                   else 1.0)
        if self.quant_spec is not None:
            from neuroimagedisttraining_tpu.privacy.secure_quant import (
                leaf_scales,
            )

            # self.params is still THE round's broadcast reference here
            # (it only advances below), so these scales are the very
            # ones every uploading client derived from its sync
            self.params = self._sq_acc.finalize(
                like=self.params, rescale=rescale,
                scales=leaf_scales(self.params))
            self._sq_acc = None
        else:
            self.params = jax.tree.map(
                lambda slots, old: (rescale * mpc.dequantize(
                    np.mod(slots.sum(axis=0), mpc.P_DEFAULT),
                    frac_bits=self.frac_bits)).astype(
                        np.asarray(old).dtype),
                self._slot_acc, self.params)
            self._slot_acc = None
        survivors = sorted(self._folded)
        if self.quant_spec is not None and self.defense == "weak_dp" \
                and survivors:
            # the noise was added CLIENT-side (pre-share), but its
            # geometry is config — the server still owns the ledger and
            # the per-silo epsilon report
            self._dp_round_info = self._note_weak_dp(
                survivors, [self._weights_sent.get(c, 0.0)
                            for c in survivors])
        self._folded = set()
        self._weights_sent = {}
        self._phase = "A"
        self._complete_round(len(survivors), survivors=survivors)

    def _on_deadline(self, round_for: int, gen: int) -> None:
        with self._rlock:
            if self._deadline_stale(round_for, gen):
                return
            floor = min(self.quorum, self.num_clients)
            if self._phase == "A":
                if self._n_by_client and len(self._n_by_client) >= floor:
                    self._mark_missing_suspect(set(self._n_by_client))
                    log.warning("server: round %d phase-A deadline - "
                                "weighting %d/%d reporters", round_for,
                                len(self._n_by_client), self.num_clients)
                    self._send_agg_weights()
                else:
                    self._arm_deadline()
            else:
                if self._folded and len(self._folded) >= floor:
                    self._mark_missing_suspect(set(self._folded))
                    log.warning("server: round %d phase-B deadline - "
                                "aggregating %d/%d survivors", round_for,
                                len(self._folded), self.num_clients)
                    self._finalize_secure()
                else:
                    self._arm_deadline()

    # ---- phase B': aggregator slot totals (n_aggregators > 0) ----
    # NOTE: the grouped deployment needs ALL K slot totals to
    # reconstruct (one missing slot destroys the additive sharing), so
    # deadline/quorum applies to the degenerate single-server mode only;
    # with aggregators a dropped client stalls the aggregators' fold —
    # a documented limitation, not silently wrong math.

    def _on_slot_total(self, msg: M.Message) -> None:
        from neuroimagedisttraining_tpu.ops import mpc

        with self._rlock:
            total = msg.get(M.ARG_MODEL_PARAMS)
            if self.record_trace:
                self.received_totals.append(total)
            self._slot_totals[int(msg.get(M.ARG_SLOT_INDEX))] = total
            if len(self._slot_totals) < self.n_aggregators:
                return
            totals = [self._slot_totals[j]
                      for j in sorted(self._slot_totals)]
            self.params = jax.tree.map(
                lambda old, *slots: mpc.dequantize(
                    np.mod(sum(np.asarray(s, np.int64) for s in slots),
                           mpc.P_DEFAULT),
                    frac_bits=self.frac_bits).astype(
                        np.asarray(old).dtype),
                self.params, *totals)
            self._slot_totals.clear()
            # close the round's phase state so the next round's sample
            # counts pass the phase-A gate
            self._weights_sent = {}
            self._folded = set()
            self._phase = "A"
            self._complete_round(self.num_clients)

    def _broadcast_finish(self) -> None:
        super()._broadcast_finish()
        for j in range(self.n_aggregators):
            self.send_message(M.Message(M.MSG_TYPE_S2C_FINISH, 0,
                                        self.num_clients + 1 + j))


class SlotAggregatorProc(ClientManager):
    """Aggregator j (rank ``num_clients + 1 + j``): receives ONLY slot j
    of every client's additive sharing per round, folds the slots mod p
    across clients, and forwards the single cross-client total to the
    server — TurboAggregate's grouped aggregation
    (turboaggregate/TA_trainer.py:38-85): one share slot reveals nothing
    about a client (it is uniform in GF(p)), and the forwarded total only
    reveals the cross-client sum of that slot."""

    def __init__(self, slot_index: int, num_clients: int,
                 n_aggregators: int, record_trace: bool = False, **kw):
        super().__init__(rank=num_clients + 1 + slot_index,
                         world_size=num_clients + 1 + n_aggregators, **kw)
        self.slot_index = slot_index
        self.num_clients = num_clients
        self._acc = None
        self._clients_in = 0
        #: when record_trace, every share received keyed by sender rank —
        #: model-sized per client per round, so tests-only (they assert
        #: what this process COULD learn); senders are always counted
        self.record_trace = record_trace
        self.received: dict[int, list] = {}

    def register_message_receive_handlers(self) -> None:
        self.register_message_receive_handler(
            M.MSG_TYPE_C2A_SEND_SLOT, self._on_slot)
        self.register_message_receive_handler(
            M.MSG_TYPE_S2C_FINISH, lambda msg: self.finish())

    def _on_slot(self, msg: M.Message) -> None:
        from neuroimagedisttraining_tpu.ops import mpc

        slot = msg.get(M.ARG_MODEL_PARAMS)
        lst = self.received.setdefault(msg.sender_id, [])
        if self.record_trace:
            lst.append(slot)
        if self._acc is None:
            self._acc = jax.tree.map(
                lambda s: np.asarray(s, np.int64) % mpc.P_DEFAULT, slot)
        else:
            self._acc = jax.tree.map(
                lambda a, s: (a + np.asarray(s, np.int64)) % mpc.P_DEFAULT,
                self._acc, slot)
        self._clients_in += 1
        if self._clients_in < self.num_clients:
            return
        out = M.Message(M.MSG_TYPE_A2S_SLOT_TOTAL, self.rank, 0)
        out.add(M.ARG_MODEL_PARAMS, self._acc)
        out.add(M.ARG_SLOT_INDEX, self.slot_index)
        self.send_message(out)
        self._acc = None
        self._clients_in = 0


class FedAvgClientProc(ClientManager):
    """Rank >= 1. Trains via the injected ``train_fn`` on every sync.

    ``heartbeat_interval`` > 0 starts a liveness thread beating to the
    server every interval — the signal the server's suspicion machinery
    (``heartbeat_timeout``) consumes. Uploads echo the sync's round
    index so the server can reject stale/duplicate frames.

    ``wire_codec`` encodes every model upload (codec/wire.py): delta vs
    the sync just received, mask-sparse against ``wire_masks`` (shipped
    bitmap-free — the server holds the same mask via its own
    ``wire_masks``, the engine mask handoff), or top-k sparse with this
    silo's persistent error-feedback accumulator ``_wire_ef`` threaded
    across rounds (dropped mass and quantization error re-enter the next
    round's residual, EF-SGD semantics). A dropped upload loses one
    round's kept mass like any dense upload would; the EF state itself
    never desyncs because it lives entirely on this sender. A sync
    carrying ``ARG_EF_RESET`` (the server's post-quarantine signal)
    clears the accumulator before this round trains.

    ``fault_schedule`` + ``seed`` (ISSUE 5): when the schedule carries
    ``byz:`` value faults, this silo transforms its OWN upload through
    ``faults/adversary.attack_update`` before any encoding — the
    attacker controls what its silo encodes, the server defends on what
    it decodes. The transform is the same jax math the simulated
    engines vmap over their client axis, keyed by (seed, round, rank),
    so one seed produces one attack trace in both federations."""

    #: monotone upload counter (ARG_UPLOAD_SEQ, class-level default so
    #: partially-constructed test doubles inherit it): lets the async
    #: buffered server (asyncfl/) distinguish a transport-duplicated
    #: frame from an honest repeat contribution; the sync server
    #: ignores it (round-tag dedup)
    _upload_seq = 0

    def __init__(self, rank: int, num_clients: int,
                 train_fn: Callable, world_size: int | None = None,
                 heartbeat_interval: float = 0.0, wire_codec: str = "none",
                 wire_masks=None, wire_topk_ratio: float = 0.25,
                 fault_schedule=None, seed: int = 0,
                 sync_delta: bool = False, **kw):
        super().__init__(rank=rank, world_size=world_size or num_clients + 1,
                         **kw)
        self.num_clients = num_clients
        self.train_fn = train_fn
        self.heartbeat_interval = float(heartbeat_interval)
        self.final_params = None
        self._hb_stop = threading.Event()
        self._wire_spec = codec.parse_wire_spec(wire_codec, wire_topk_ratio)
        self.wire_masks = (_to_numpy_tree(wire_masks)
                           if wire_masks is not None else None)
        self._wire_ef = None  # per-silo error-feedback accumulator
        #: last full model body received, reused when a cached-sync
        #: reply (version unchanged; asyncfl/ingest.py) omits the body
        self._last_sync_params = None
        #: opt into lossless delta sync bodies (ISSUE 18): changed-
        #: version replies may then ship the byte delta against the
        #: version named in ``_last_sync_version`` instead of the tree
        self.sync_delta = bool(sync_delta)
        self._last_sync_version = -1
        #: value-fault schedule (None, or a FaultSchedule whose spec may
        #: schedule THIS rank to upload Byzantine values)
        self.fault_schedule = fault_schedule
        self.seed = int(seed)

    def register_message_receive_handlers(self) -> None:
        self.register_message_receive_handler(
            M.MSG_TYPE_S2C_INIT_CONFIG, self._on_sync)
        self.register_message_receive_handler(
            M.MSG_TYPE_S2C_SYNC_MODEL, self._on_sync)
        self.register_message_receive_handler(
            M.MSG_TYPE_S2C_FINISH, self._on_finish)

    def run(self) -> None:
        self.register_message_receive_handlers()
        reg = M.Message(M.MSG_TYPE_C2S_REGISTER, self.rank, 0)
        # exactly-once dedup (ISSUE 18): this process lifetime IS the
        # incarnation — a restarted silo gets a fresh one (fresh seq
        # space), a reconnecting one keeps it, so a post-migration
        # ingest worker installs the root's accepted-seq floor for this
        # incarnation before replying
        reg.add(M.ARG_CLIENT_INCARNATION, os.getpid())
        if self.sync_delta:
            reg.add(M.ARG_SYNC_DELTA_OK, True)
        # the server process may still be initializing (model build + jit
        # compile) when this silo is ready — give the FIRST contact a
        # generous retry window on transports that support it (capped
        # exponential backoff: ~0.25s ramping to 2s, ~5 min total)
        try:
            self.com_manager.send_message(reg, retries=150,
                                          retry_delay=0.25)
        except TypeError:  # transport without retry knobs (e.g. broker)
            self.com_manager.send_message(reg)
        if self.heartbeat_interval > 0:
            threading.Thread(target=self._heartbeat_loop,
                             daemon=True).start()
        self.com_manager.handle_receive_message()
        self._hb_stop.set()  # loop exited (finish or simulated crash)

    def _heartbeat_loop(self) -> None:
        while not self._hb_stop.wait(self.heartbeat_interval):
            beat = M.Message(M.MSG_TYPE_C2S_HEARTBEAT, self.rank, 0)
            try:
                try:
                    self.com_manager.send_message(beat, retries=1)
                except TypeError:
                    self.com_manager.send_message(beat)
            except Exception:  # noqa: BLE001 — liveness is best-effort;
                # a missed beat (server busy/gone) must not kill the loop
                pass

    def _resolve_sync_params(self, msg: M.Message, round_idx: int):
        """The cached-sync contract (sharded ingest plane,
        asyncfl/ingest.py): an upload answered at an UNCHANGED version
        omits the model body — this silo already holds that exact tree
        from its previous sync. A body-less sync before any full sync
        is a protocol error (the ingest worker always ships the full
        model on register and on every version change); returns None
        for that dropped-sync case.

        Delta bodies (ISSUE 18, ``sync_delta`` opted in): a changed-
        version reply may carry the lossless byte delta against the
        version this silo last synced; it decodes against the held
        base bitwise. A delta naming any OTHER base is a protocol
        error handled LOUDLY (drop, never apply to a wrong base)."""
        params = msg.get(M.ARG_MODEL_PARAMS)
        if params is None:
            if self._last_sync_params is None:
                log.error("silo %d: body-less sync at version %d with no "
                          "cached model - dropping the sync", self.rank,
                          round_idx)
                return None
            return self._last_sync_params
        if codec.is_sync_delta_frame(params):
            base_v = int(params.get("base", -1))
            if (self._last_sync_params is None
                    or base_v != self._last_sync_version):
                log.error(
                    "silo %d: sync delta at version %d names base %d "
                    "but this silo holds %d - dropping the sync",
                    self.rank, round_idx, base_v,
                    self._last_sync_version)
                return None
            params = codec.decode_sync_delta(params,
                                             self._last_sync_params)
        self._last_sync_params = params
        self._last_sync_version = int(round_idx)
        return params

    def _on_sync(self, msg: M.Message) -> None:
        round_idx = int(msg.get(M.ARG_ROUND_IDX))
        params = self._resolve_sync_params(msg, round_idx)
        if params is None:
            return
        if msg.get(M.ARG_EF_RESET):
            log.info("silo %d: server requested ef_reset (round %d) - "
                     "clearing the codec error-feedback accumulator",
                     self.rank, round_idx)
            self._wire_ef = None
        new_params, n = self.train_fn(params, round_idx)
        payload = _to_numpy_tree(new_params)
        if self.fault_schedule is not None:
            # value-fault hook BEFORE encoding: a Byzantine silo encodes
            # its attacked update like any honest payload (the defense
            # runs server-side on the decoded frame)
            from neuroimagedisttraining_tpu.faults import adversary

            payload = adversary.attack_update(
                self.fault_schedule, self.seed, round_idx, self.rank,
                payload, _to_numpy_tree(params))
        if self._wire_spec is not None:
            # the delta reference is the sync we JUST trained from — the
            # server holds the identical tree for this round tag
            upload_finite = tree_all_finite(payload)
            payload, ef_next = codec.encode_update(
                self._wire_spec, payload,
                reference=_to_numpy_tree(params),
                masks=self.wire_masks, ef=self._wire_ef,
                mask_on_wire=False)
            # a non-finite upload bounces at the server's hard gate, and
            # absorbing its NaN residual would park NaN in the EF stack
            # FOREVER (every later encode consumes it — a one-round
            # value fault becomes permanent rejection). The consumed EF
            # corresponds to a frame that was never aggregated, so drop
            # the stack — the same invariant as the server's
            # post-quarantine ARG_EF_RESET.
            self._wire_ef = ef_next if upload_finite else None
        out = M.Message(M.MSG_TYPE_C2S_SEND_MODEL, self.rank, 0)
        out.add(M.ARG_MODEL_PARAMS, payload)
        out.add(M.ARG_NUM_SAMPLES, float(n))
        out.add(M.ARG_ROUND_IDX, round_idx)
        out.add(M.ARG_UPLOAD_SEQ, self._upload_seq)
        # wire trace context (ISSUE 13): the client originates the flow
        # — every downstream hop (worker admission, root aggregate)
        # links its events to this id, so one upload reads as a
        # causally-connected track in the merged trace
        ctx = obs_trace.make_trace_ctx(self.rank, self._upload_seq)
        out.add(M.ARG_TRACE_CTX, ctx)
        self._upload_seq += 1
        if obs_trace.TRACER.armed:
            with obs_trace.span("client_upload", round=round_idx):
                obs_trace.flow("upload", obs_trace.flow_id_of(ctx), "s",
                               round=round_idx)
                self.send_message(out)
        else:
            self.send_message(out)

    def _on_finish(self, msg: M.Message) -> None:
        self.final_params = None  # server holds the aggregate
        self._hb_stop.set()
        self.finish()


class SecureFedAvgClientProc(FedAvgClientProc):
    """Client for ``SecureFedAvgServer``: after local training it reports
    ``n_c`` in the clear, waits for its normalized weight w_c, then
    uploads additive shares of ``quantize(w_c * params)``. w_c <= 1 keeps
    the fixed-point embedding exact (|x| * 2^frac_bits < p/2) for any
    cohort size; the server reconstructs only the weighted mean."""

    def __init__(self, rank: int, num_clients: int, train_fn: Callable,
                 n_shares: int = 3, frac_bits: int = 16, mpc_seed: int = 0,
                 n_aggregators: int = 0, quant_spec=None,
                 one_phase: bool = False, defense: str = "none",
                 norm_bound: float = 5.0, stddev: float = 0.05,
                 defense_seed: int = 0, **kw):
        from neuroimagedisttraining_tpu.core import robust

        if n_aggregators and n_aggregators != n_shares:
            raise ValueError(
                f"n_aggregators ({n_aggregators}) must equal n_shares "
                f"({n_shares}): slot j routes to aggregator j")
        if n_aggregators and quant_spec is not None:
            raise ValueError(
                "secure_quant does not compose with --n_aggregators "
                "(seed-expanded mask slots; see SecureFedAvgServer)")
        if kw.get("wire_codec", "none") != "none" or \
                kw.get("wire_masks") is not None:
            raise ValueError(
                "SecureFedAvgClientProc is incompatible with the wire "
                "codec: secure uploads must ride the wire as field "
                "elements (see SecureFedAvgServer — encoding breaks the "
                "GF(p) share algebra or leaks mask support; "
                "--secure_quant IS the compressed secure wire)")
        sched = kw.get("fault_schedule")
        if sched is not None and sched.spec.any_value_faults:
            raise ValueError(
                "byz: value faults cannot be simulated under --secure: "
                "the secure client's upload path shares BEFORE any "
                "value hook could run, and the server has no plaintext "
                "updates to defend — the attack would go both "
                "uninjected and undefended (see ARCHITECTURE.md)")
        if one_phase and quant_spec is None:
            raise ValueError(
                "one_phase (the async buffered protocol) requires a "
                "quant_spec: the dense two-phase weight exchange IS a "
                "round barrier (see asyncfl/server.py)")
        if defense != "none":
            robust.validate_defense(defense)
            if quant_spec is None or defense not in robust.CLIP_DEFENSES:
                raise ValueError(
                    f"client-side defense {defense!r} composes only with "
                    "secure_quant and only for the clip family "
                    "(norm_diff_clipping, weak_dp) — each silo clips/"
                    "noises its OWN update before sharing; see "
                    "ARCHITECTURE.md 'Privacy plane'")
        super().__init__(rank, num_clients, train_fn,
                         world_size=num_clients + 1 + n_aggregators, **kw)
        self.n_shares = n_shares
        self.frac_bits = frac_bits
        self.n_aggregators = n_aggregators
        self.quant_spec = quant_spec
        self.one_phase = bool(one_phase)
        self.defense = defense
        self.norm_bound = float(norm_bound)
        self.stddev = float(stddev)
        self.defense_seed = int(defense_seed)
        self._rng = np.random.default_rng(mpc_seed * 7919 + rank)
        self._trained = None  # params awaiting the weight reply
        self._sync_ref = None  # the sync tree (client-side clip baseline)

    def register_message_receive_handlers(self) -> None:
        super().register_message_receive_handlers()
        self.register_message_receive_handler(
            M.MSG_TYPE_S2C_AGG_WEIGHTS, self._on_weights)

    def _client_side_defense(self, trained, round_idx: int):
        """Clip-family enforcement at the only place secure aggregation
        allows it — the silo's own update, BEFORE quantize/share (the
        TurboAggregateEngine composition). THE core/robust.py transforms
        run verbatim (``norm_diff_clip``, then ``add_weak_dp_noise``
        from a jax key folded from (defense_seed, round, rank) — the
        config-threaded stream discipline nidtlint's dp-key-discipline
        rule enforces), so a secure-quant silo applies bit-for-bit the
        defense a plain server would have."""
        if self.defense == "none" or self._sync_ref is None:
            return trained
        from neuroimagedisttraining_tpu.core import robust

        ref = jax.tree.map(lambda x: jnp.asarray(x, jnp.float32),
                           self._sync_ref)
        out = robust.norm_diff_clip(
            jax.tree.map(lambda x: jnp.asarray(x, jnp.float32), trained),
            ref, self.norm_bound)
        if self.defense == "weak_dp":
            key = jax.random.fold_in(jax.random.fold_in(
                jax.random.key(self.defense_seed), round_idx), self.rank)
            out = robust.add_weak_dp_noise(out, key, self.stddev)
        return _to_numpy_tree(out)

    def _sq_upload(self, payload, round_idx, weight: float) -> None:
        """Encode one secure-quant field-element frame and ship it (the
        one upload message of this round — folds whole or not at all).
        Per-leaf scales derive from the sync reference — the identical
        tree the server holds for this round tag, so both ends compute
        the identical scales with nothing extra on the wire."""
        from neuroimagedisttraining_tpu.privacy import encode_secure_quant
        from neuroimagedisttraining_tpu.privacy.secure_quant import (
            leaf_scales,
        )

        frame = encode_secure_quant(payload, weight, self.quant_spec,
                                    self._rng,
                                    scales=leaf_scales(self._sync_ref))
        out = M.Message(M.MSG_TYPE_C2S_SEND_MODEL, self.rank, 0)
        out.add(M.ARG_MODEL_PARAMS, frame)
        if round_idx is not None:
            out.add(M.ARG_ROUND_IDX, int(round_idx))
        out.add(M.ARG_UPLOAD_SEQ, self._upload_seq)
        self._upload_seq += 1
        self.send_message(out)

    def _on_sync(self, msg: M.Message) -> None:
        round_idx = int(msg.get(M.ARG_ROUND_IDX))
        params = self._resolve_sync_params(msg, round_idx)
        if params is None:  # dropped cached-sync protocol error
            return
        new_params, n = self.train_fn(params, round_idx)
        self._sync_ref = _to_numpy_tree(params)
        trained = self._client_side_defense(_to_numpy_tree(new_params),
                                            round_idx)
        if self.one_phase:
            # async buffered protocol: no phase-A weight exchange (it IS
            # a round barrier) — ship the UNWEIGHTED quantized update +
            # n in the clear; the server folds integer-scaled staleness
            # weights inside the field (asyncfl/server.py)
            from neuroimagedisttraining_tpu.privacy import (
                encode_secure_quant,
            )

            frame = encode_secure_quant(trained, 1.0, self.quant_spec,
                                        self._rng)
            out = M.Message(M.MSG_TYPE_C2S_SEND_MODEL, self.rank, 0)
            out.add(M.ARG_MODEL_PARAMS, frame)
            out.add(M.ARG_NUM_SAMPLES, float(n))
            out.add(M.ARG_ROUND_IDX, round_idx)
            out.add(M.ARG_UPLOAD_SEQ, self._upload_seq)
            self._upload_seq += 1
            self.send_message(out)
            return
        self._trained = trained
        out = M.Message(M.MSG_TYPE_C2S_NUM_SAMPLES, self.rank, 0)
        out.add(M.ARG_NUM_SAMPLES, float(n))
        out.add(M.ARG_ROUND_IDX, round_idx)
        self.send_message(out)

    def _on_weights(self, msg: M.Message) -> None:
        from neuroimagedisttraining_tpu.ops import mpc

        round_idx = msg.get(M.ARG_ROUND_IDX)
        w = float(msg.get(M.ARG_AGG_WEIGHT))
        if self.quant_spec is not None:
            payload, self._trained = self._trained, None
            self._sq_upload(payload, round_idx, w)
            return
        shares_tree = jax.tree.map(
            lambda x: mpc.additive_shares(
                mpc.quantize(w * np.asarray(x, np.float64),
                             frac_bits=self.frac_bits),
                self.n_shares, rng=self._rng),
            self._trained)
        self._trained = None
        if self.n_aggregators:
            # slot j -> aggregator j (rank num_clients+1+j): no single
            # node ever holds two of this client's slots
            for j in range(self.n_aggregators):
                out = M.Message(M.MSG_TYPE_C2A_SEND_SLOT, self.rank,
                                self.num_clients + 1 + j)
                out.add(M.ARG_MODEL_PARAMS,
                        jax.tree.map(lambda s: s[j], shares_tree))
                out.add(M.ARG_SLOT_INDEX, j)
                if round_idx is not None:
                    out.add(M.ARG_ROUND_IDX, int(round_idx))
                self.send_message(out)
            return
        out = M.Message(M.MSG_TYPE_C2S_SEND_MODEL, self.rank, 0)
        out.add(M.ARG_MODEL_PARAMS, shares_tree)
        if round_idx is not None:
            out.add(M.ARG_ROUND_IDX, int(round_idx))
        self.send_message(out)
