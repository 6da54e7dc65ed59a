"""Declarative round-program builder (ISSUE 11, ROADMAP item 1).

Every federated round in this tree has the same skeleton:

    sample -> [local-train] -> [attack] -> [codec] -> sanitize ->
    defend -> aggregate -> [update persistent state] -> privacy-account

but until this module each engine hand-rolled the skeleton into its own
``_round_jit`` / ``_sharded_round_jit`` bodies, so the fast-path
machinery built over ISSUEs 4-10 — ``--client_mesh`` cohort sharding,
buffer donation, Byzantine defenses, the wire codec — reached only the
engines that had copied the machinery in (fedavg/fedprox/salientgrads).

This module inverts the ownership. An engine DECLARES its round as a
:class:`RoundStages` value — which pytrees it carries between rounds,
its local-training stage, optionally a custom aggregation and a
persistent-state update stage — and :class:`RoundProgram` compiles the
declaration into the exact jitted round bodies the hand-written paths
produced, with the orthogonal knobs applied by the BUILDER:

- buffer donation of the carried state (+ codec EF rows) on every
  compiled program (ISSUE 4 contract, donation-discipline lint);
- ``--client_mesh`` cohort sharding of the local-train stage with the
  epoch-permutation hoist the toolchain requires (ISSUE 6,
  parallel/cohort.py — in-partition argsort miscompiles);
- the Byzantine attack plan + non-finite guard + ``--defense`` dispatch
  (ISSUE 5) and the wire codec's lossy roundtrip with error feedback
  (ISSUE 3) on engines whose stages opt in.

fedavg/fedprox/salientgrads ride the builder with BITWISE parity against
their pre-builder paths (the regression oracle: tests/test_dispatch.py,
test_cohort.py, test_byzantine.py pins are unchanged); ditto, dpsgd and
subavg are expressed as stage declarations and gain cohort sharding
for the first time (tests/test_program.py). A dispatch holds ONE round:
the driver loops call ``round_jit`` (or ``stream_jit``) once a round.

Fallback reporting is unified here too: :data:`REASONS` is the single
source of truth for every "falls back with a logged reason" site, and
:func:`report_fallback` increments the structured
``nidt_fallback_total{plane, engine, reason}`` counter in the obs
registry alongside the log line — fast-path coverage is scrapeable, not
grep-able.
"""

from __future__ import annotations

import dataclasses
import functools
import time
from typing import Any, Callable

import jax
import jax.numpy as jnp
import numpy as np

from neuroimagedisttraining_tpu.core import robust
from neuroimagedisttraining_tpu.faults import adversary
from neuroimagedisttraining_tpu.obs import compute as obs_compute
from neuroimagedisttraining_tpu.obs import health as obs_health
from neuroimagedisttraining_tpu.obs import metrics as obs_metrics
from neuroimagedisttraining_tpu.obs import names as obs_names
from neuroimagedisttraining_tpu.obs import trace as obs_trace
from neuroimagedisttraining_tpu.parallel import cohort

PyTree = Any

# ---------------------------------------------------------------------------
# fallback reason table — the single source of truth (ISSUE 11 satellite)
# ---------------------------------------------------------------------------

#: reason key -> (plane, message). Every "falls back with a logged
#: reason" site in the tree resolves its message HERE; engines override
#: ``*_fallback_key`` hooks with keys from this table, never ad-hoc
#: strings (tests/test_program.py asserts no orphaned or unknown keys).
REASONS: dict[str, tuple[str, str]] = {
    # -- cohort sharding (plane "sharding") --
    "no-sharded-body": ("sharding", (
        "engine has no cohort-sharded round body (its round crosses the "
        "host or exchanges per-client state outside the declared-stage "
        "shape)")),
    "two-level-mesh": ("sharding", (
        "two-level (silos, clients) mesh routes aggregation silo-first "
        "(parallel/hierarchical.py); cohort sharding arms on 1-D client "
        "meshes")),
    "one-device": ("sharding", (
        "only one device visible — the unsharded round IS the "
        "single-device program")),
    "streaming-sharded-feed": ("sharding", (
        "streaming rounds host-stage each round's shards; the streamed "
        "feed already device_puts them client-sharded over the mesh")),
    "batch-order-replacement": ("sharding", (
        "batch_order=replacement draws per-step randint batches inside "
        "the shard_map partition, where the partitioned RNG+gather "
        "lowering miscompiles on this toolchain (measured, "
        "parallel/cohort.py); the shuffle path hoists its permutations "
        "out of the partition — i.i.d. per-step draws cannot be "
        "hoisted")),
    "gossip-mesh-collectives": ("sharding", (
        "dispfl's decentralized round already runs client-sharded "
        "gossip collectives on the mesh (parallel/gossip.py); "
        "--client_mesh adds nothing")),
    "mpc-host-boundary": ("sharding", (
        "turboaggregate's round crosses the host at the MPC share "
        "boundary every round (quantize/share/aggregate models the "
        "client<->server link); no sharded round body")),
    "cohort-not-tiling": ("sharding", (
        "the full client axis does not tile the client mesh (the data "
        "layer pads resident cohorts to a device multiple; this one is "
        "not)")),
    # -- the distributed transport (distributed/run.py startup notes) --
    "distributed-no-client-axis": ("sharding", (
        "the distributed transport has no in-process client axis to "
        "shard (each rank trains its own silo) — flag accepted for "
        "config parity with the main CLI only")),
    # -- clients in time, the aggregate folded (plane "fold", PR 25) --
    # The folded placement holds ONE client's state and folds each
    # upload into a running weighted sum, so whatever needs the whole
    # upload stack at once cannot run there. A model whose stack does
    # not fit has no stacked program to fall back to: the "fold-*"
    # refusals below RAISE at program build with their message;
    # "fold-not-declared" alone is a fallback (the engine keeps the
    # stacked program and the device decides).
    "fold-not-declared": ("fold", (
        "the stacked client states exceed the device's memory budget, "
        "but this engine's round stages do not declare the folded "
        "placement (RoundStages.folds): the stacked round program is "
        "kept")),
    "fold-order-statistic-defense": ("fold", (
        "order-statistic defenses (trimmed_mean, median, krum, "
        "multi_krum, geometric_median) select over every client's "
        "upload at once; the folded round holds one upload at a time. "
        "Use a clip-family defense (norm_diff_clipping, weak_dp), which "
        "acts per client")),
    "fold-byz-attack-plan": ("fold", (
        "the Byzantine attack plan is applied to the stacked upload "
        "payload (faults/adversary.py apply_attack_stacked); the folded "
        "round never materializes that stack")),
    "fold-codec-error-feedback": ("fold", (
        "--wire_codec runs its lossy roundtrip, its per-client error "
        "feedback rows and the byte-accounting sample over the stacked "
        "uploads; the folded round holds one upload at a time")),
    "fold-health-stats": ("fold", (
        "--health_stats measures per-client update norms and "
        "leave-one-out cosines over the upload stack; the folded round "
        "has no stack to measure")),
    "fold-secure-quant": ("fold", (
        "--secure_quant normalises its integer fold weights by the "
        "cohort's largest and sums field residues over the stacked "
        "uploads; the folded round holds one upload at a time")),
    # -- autotuner recipes (plane "recipe", tune/recipe.py) --
    "recipe-override": ("recipe", (
        "an explicit CLI flag overrides the loaded recipe's value for "
        "this knob (--recipe applies as config DEFAULTS; flags the "
        "operator spells win)")),
}


def reason(key: str) -> str:
    """The logged message for a fallback ``key`` (KeyError on unknown
    keys — an engine naming a reason outside the table is a bug)."""
    return REASONS[key][1]


def report_fallback(engine_name: str, key: str) -> str:
    """Count one structured fallback announcement and return its message.
    The caller owns the log line (each site keeps its historic wording
    around the message); the counter is the scrapeable half:
    ``nidt_fallback_total{plane, engine, reason}``."""
    plane, msg = REASONS[key]
    obs_metrics.counter(
        obs_names.FALLBACK_TOTAL,
        "fast-path fallback announcements by plane (cohort sharding / "
        "fold / recipe), engine, and reason key (engines/program.py "
        "REASONS)",
        labelnames=("plane", "engine", "reason"),
    ).labels(plane=plane, engine=engine_name, reason=key).inc()
    return msg


# ---------------------------------------------------------------------------
# placement of the per-client work (ISSUE 6, PR 25)
# ---------------------------------------------------------------------------

#: how a round program places its clients: ``stacked`` (state broadcast
#: over a client axis and ``vmap``ped), ``sharded`` (the client axis
#: over the ``--client_mesh`` devices, ``lax.map`` within each), or
#: ``folded`` (clients one after another in a ``lax.scan``, a single
#: client state alive, the weighted sum of the uploads as the carry)
STACKED, SHARDED, FOLDED = "stacked", "sharded", "folded"


def tree_bytes(tree) -> int:
    """Bytes of a pytree of arrays or ``ShapeDtypeStruct``s."""
    return sum(int(np.prod(x.shape)) * jnp.dtype(x.dtype).itemsize
               for x in jax.tree.leaves(tree)
               if hasattr(x, "shape") and hasattr(x, "dtype"))


def stacked_state_bytes(params, opt_state, rows: int) -> int:
    """What ``rows`` stacked clients hold beside the global model: each
    its parameters, its optimizer state, and its row of the upload stack
    (``rows x (2 x params + opt_state)``). Gradients and activations are
    left out: they are the compiler's temporaries, and one client's are
    there in every placement."""
    return rows * (2 * tree_bytes(params) + tree_bytes(opt_state))


# ---------------------------------------------------------------------------
# stage declarations
# ---------------------------------------------------------------------------


@dataclasses.dataclass
class TrainOut:
    """What an engine's local-train stage hands the downstream stages.

    Every array field is CLIENT-STACKED along axis 0 over the round's
    cohort; on the cohort-sharded path the builder statically slices the
    mesh-pad rows off all of them before the attack/codec/defense tail.

    - ``upload``: the ``{"params", "batch_stats"}`` payload the clients
      would put on the wire (what attack/codec/sanitize/defend consume),
      or None when the engine's custom aggregate stage consumes ``extra``
      directly.
    - ``losses``: per-client training losses ``[C]``.
    - ``state``: the trained per-client state (``ClientState``) — its
      ``rng`` leaves seed the weak_dp defense, and update stages scatter
      from its params/batch_stats (the client's HONEST local result,
      pre-attack/codec by design).
    - ``extra``: engine-private client-stacked auxiliaries for the
      aggregate/update stages.
    """

    losses: jax.Array
    upload: dict | None = None
    state: Any = None
    extra: dict = dataclasses.field(default_factory=dict)


@dataclasses.dataclass(frozen=True)
class RoundStages:
    """An engine's declared round: the builder compiles this (and only
    this) into every dispatch variant — single-round, cohort-sharded,
    streamed — with donation and the attack/codec/defense stages applied
    by the builder.

    ``carry``: names of the device pytrees carried round to round, in
    program-argument (and return) order; all are donated.
    ``consts``: loop-constant operands after the federation data (e.g.
    salientgrads' phase-1 mask).
    ``per_round``: per-round operand names beyond the builder-owned
    sampling/rng/lr (e.g. dpsgd's mixing matrix).
    ``train``: the local-train stage, ``(ctx: RoundCtx) -> TrainOut``.
    ``aggregate``: custom aggregation stage
    ``(ctx, upload, w, tr) -> (new_carry: dict, outs: dict)``; None
    routes through the builder's sanitize -> defend -> weighted-mean
    tail (:func:`sanitize_defend_aggregate`).
    ``update``: persistent per-client state stage
    ``(ctx, tr, new_carry) -> dict`` of carry updates (scatters).
    ``epilogue``: outputs derived from the new carry
    ``(eng, carry: dict, data) -> tuple`` (e.g. dpsgd's ``w_global``).
    ``outputs``: names of the per-round scalar outputs.
    ``gathers_cohort``: the builder gathers the sampled clients' shards
    from the federation data by ``sampled_idx`` (False: the train stage
    consumes the full data, dpsgd-style).
    ``uses_ef``: the program takes (and donates) wire-codec
    error-feedback rows and returns the updated rows + the
    byte-accounting sample ``u0``.
    ``supports_attack``: the program takes the [C]-planned Byzantine
    attack and applies it to ``upload`` before codec/defense.
    ``codec_masks``: ``(ctx) -> masks_full`` handed to the codec
    roundtrip (salientgrads' phase-1 mask handoff), or None.
    ``health``: engine-private health-stats stage for the in-dispatch
    training-health leg (ISSUE 15), ``(ctx, tr, new_carry) -> dict`` of
    scalar stats named by ``health_outputs`` (the masked engines emit
    ``obs/health.py MASK_STAT_NAMES``); traced with the round, emitted
    only when ``--health_stats`` arms the leg.
    ``folds``: the round may run in the FOLDED placement (clients in
    time, the builder's default aggregate folded into the client loop;
    :meth:`RoundProgram._fold_body`). Only a declaration whose train
    stage works on a one-client stack and which routes the default
    sanitize/defend/aggregate tail (no custom ``aggregate`` / ``update``
    stage) may declare it.
    A declared output the aggregate stage does not produce is the sum
    over clients of the train stage's ``extra`` entry of that name (the
    expert-load counter of a sparse-expert model).
    """

    carry: tuple[str, ...]
    train: Callable
    aggregate: Callable | None = None
    update: Callable | None = None
    epilogue: Callable | None = None
    outputs: tuple[str, ...] = ("loss", "n_bad")
    consts: tuple[str, ...] = ()
    per_round: tuple[str, ...] = ()
    gathers_cohort: bool = True
    uses_ef: bool = False
    supports_attack: bool = False
    codec_masks: Callable | None = None
    health: Callable | None = None
    health_outputs: tuple[str, ...] = ()
    folds: bool = False


class RoundCtx:
    """Everything a stage sees about the round being traced. Built by
    the program body; stages read operands off it and use
    :meth:`client_map` for their per-client loops so the builder decides
    vmap vs the cohort-sharded mesh loop."""

    def __init__(self, eng, stages: RoundStages, carry: dict, data,
                 consts: dict, Xs, ys, ns, sampled_idx, rngs, lr,
                 per_round: dict, static_key, n_real, sharded: bool,
                 folded: bool = False):
        self.eng = eng
        self.stages = stages
        self.carry = carry
        self.data = data
        self.consts = consts
        self.Xs, self.ys, self.ns = Xs, ys, ns
        self.sampled_idx = sampled_idx
        self.rngs = rngs
        self.lr = lr
        self.per_round = per_round
        self.static = static_key
        self.n_real = n_real
        self.sharded = sharded
        self.folded = folded

    # -- the local-train placement contract (ISSUE 6) --

    def client_map(self, fn, *stacked, hoisted: tuple = ()):
        """Run the unbatched per-client ``fn`` over the client-stacked
        operands: plain ``vmap`` on the unsharded path (bitwise-identical
        to the pre-builder engines), the cohort-sharded mesh loop
        (``FederatedEngine._cohort_map`` -> parallel/cohort.py) when this
        program was built sharded. ``hoisted`` are thunks producing extra
        client-stacked operands passed ONLY on the sharded path — the
        epoch-permutation hoist that keeps argsort-lowered RNG out of the
        shard_map partition (the measured miscompile,
        parallel/cohort.py); ``fn`` takes them as trailing defaulted
        params. In the FOLDED placement the operands are a one-client
        stack and ``fn`` is applied to that client unbatched (no
        ``vmap``: a grouped matmul under a client axis is what the
        placement exists to avoid). Sharded or folded, a row runs alone,
        and the trainer is told so for the duration of the trace
        (``LocalTrainer.rows_alone``: the row stops at its own last
        step; here for the fold, in ``_cohort_map`` for the mesh)."""
        if self.folded:
            with self.eng.trainer.rows_alone():
                out = fn(*jax.tree.map(lambda x: x[0], stacked))
            return jax.tree.map(lambda x: x[None], out)
        if self.sharded:
            extra = tuple(h() for h in hoisted)
            return self.eng._cohort_map(fn, *stacked, *extra)
        return jax.vmap(fn)(*stacked)

    def local_perms(self, rngs, ns, epochs: int):
        """Hoisted per-client epoch permutations for a sharded
        local-train stage: exactly what each client's ``local_train``
        would derive from ``rngs`` (core/trainer.py ``epoch_perms_for``),
        computed OUTSIDE the shard_map partition."""
        return hoisted_epoch_perms(self.eng, rngs, ns, epochs)

    def rng_after_local_train(self, rngs, epochs: int):
        """The per-client rng values ``local_train`` leaves in
        ``cs.rng`` after ``epochs`` epochs — the entry rngs of a SECOND
        ``local_train`` call in the same per-client stage (subavg's
        epoch-1 / tail split), replayed outside the partition so the
        tail call's permutations can be hoisted too. Mirrors
        ``local_train``'s stream exactly: one (rng0, perm) split at
        entry, then one 3-way split per scan step."""
        from neuroimagedisttraining_tpu.core.trainer import scan_steps

        steps = scan_steps(epochs, self.eng.cfg.optim.batch_size,
                           self.eng._max_samples())

        def chain(rng):
            r0, _ = jax.random.split(rng)

            def step(r, _):
                return jax.random.split(r, 3)[0], None

            r, _ = jax.lax.scan(step, r0, None, length=steps)
            return r

        return jax.vmap(chain)(rngs)

    @property
    def upload_ref(self) -> dict:
        """The broadcast reference the attack/codec/sanitize stages
        compare uploads against: the round's incoming global model."""
        return {"params": self.carry["params"],
                "batch_stats": self.carry["batch_stats"]}


# ---------------------------------------------------------------------------
# builder-owned stages
# ---------------------------------------------------------------------------


def hoisted_epoch_perms(eng, rngs, ns, epochs: int):
    """The per-client epoch permutations ``local_train`` would derive
    from ``rngs``, vmapped over the cohort — computed OUTSIDE a
    shard_map partition (the argsort-lowered permutation MISCOMPILES
    inside one on this toolchain; parallel/cohort.py documents the
    measurement) and passed in via ``perms=``. The rng stream is
    identical either way."""
    from neuroimagedisttraining_tpu.core.trainer import epoch_perms_for

    ms = eng._max_samples()
    return jax.vmap(
        lambda r, n: epoch_perms_for(r, epochs, ms, n))(rngs, ns)


def cohort_local_stage(eng, fn, cs, Xs, ys, ns):
    """A hoisted-perms cohort-sharded local stage for driver code
    OUTSIDE the round program (fedavg's final fine-tune pass): hoist the
    epoch permutations from ``cs.rng``, then run the per-client loop
    under the client mesh. Cohort sharding only arms under
    ``batch_order=shuffle`` (the program's mode checks), so hoistable
    perms always exist here."""
    perms = hoisted_epoch_perms(eng, cs.rng, ns, eng.cfg.optim.epochs)
    return eng._cohort_map(fn, cs, Xs, ys, ns, perms)


def _sanitize(upload, ref):
    """``(upload with every non-finite client's row swapped for ref,
    finite [C])``: step 1 of the default tail, stacked or folded."""
    finite = robust.finite_per_client(upload)
    return robust.replace_nonfinite_clients(upload, ref, finite), finite


def _clip_defend(eng, upload, ref, defense, rngs):
    """The clip family's per-client transform of the uploaded params
    (batch_stats are never clipped): step 2 of the default tail where
    no order statistic replaces the mean."""
    f = eng.cfg.fed
    return robust.defend_stacked(
        upload["params"], ref["params"], defense=defense,
        norm_bound=f.norm_bound, stddev=f.stddev, rngs=rngs)


def _weighted_loss(losses, w):
    """The round's training loss: the clients' mean losses under the
    aggregation weights, a non-finite loss counted as zero."""
    safe = jnp.where(jnp.isfinite(losses), losses, 0.0)
    return jnp.sum(safe * w) / jnp.maximum(jnp.sum(w), 1e-9)


def sanitize_defend_aggregate(eng, upload, ref, w, losses, rngs=None):
    """The shared tail of a defended round body (trace-safe; the builder
    runs it for every engine without a custom aggregate stage):

    1. non-finite upload guard (runs with or without ``--defense``): a
       single NaN/Inf client would poison ``tree_weighted_mean``, so its
       row is swapped for the broadcast ``ref`` and zero-weighted (the
       count comes back as ``n_bad``);
    2. defense dispatch (core/robust.py): order-statistic defenses
       consume the whole upload payload (a Byzantine silo poisons its
       batch_stats too) and replace the weighted mean; the clip family
       transforms params per client (batch_stats are never clipped —
       structural parity with ``is_weight_param``,
       robust_aggregation.py:28-29) then reduces with the engine's
       silo-aware ``aggregate``. A cohort too small for the configured
       aggregator (fault-schedule shrinkage) falls back to the plain
       mean with a warning — resolved at trace time, the cohort axis is
       static.

    ``upload``/``ref`` are ``{"params", "batch_stats"}`` dicts (stacked /
    unstacked); ``rngs`` are the per-client keys weak_dp noise draws
    from. Returns ``(new_params, new_bstats, mean_loss, n_bad)``."""
    f = eng.cfg.fed
    upload, finite = _sanitize(upload, ref)
    n_bad = jnp.sum(~finite).astype(jnp.int32)
    w = w * finite.astype(jnp.float32)
    C = int(jax.tree.leaves(upload)[0].shape[0])
    # the engine's ACTIVE defense, not the config literal: the reflex
    # plane's escalate_defense handler can raise it mid-run (ISSUE 20),
    # after which the invalidated round programs re-trace through here
    defense = robust.effective_defense(eng.active_defense(), C, f.byz_f,
                                       warn=eng.log.warning)
    if defense in robust.ROBUST_AGGREGATORS:
        agg = robust.robust_aggregate(
            upload, w, defense=defense, byz_f=f.byz_f,
            geomed_iters=f.geomed_iters)
        new_params, new_bstats = agg["params"], agg["batch_stats"]
    else:
        client_params = _clip_defend(eng, upload, ref, defense, rngs)
        new_params = eng.aggregate(client_params, w)
        new_bstats = eng.aggregate(upload["batch_stats"], w)
    return new_params, new_bstats, _weighted_loss(losses, w), n_bad


def sq_integer_weights(w, shift: int):
    """The per-round integer fold weights of the in-process secure-quant
    stage: ``max(rint(w / max(w) * 2^shift), 1)``. Every operation is a
    single correctly-rounded f32 op (or exact: max, rint, the power-of-
    two multiply), so the identical numpy formula over the same f32
    weights reproduces these integers EXACTLY — the bridge the bitwise
    host-fold pin crosses (tests/test_program.py). Ratios are preserved
    to ~2^-shift relative; an admitted client never folds at zero."""
    wn = w.astype(jnp.float32) / jnp.max(w.astype(jnp.float32))
    return jnp.maximum(jnp.rint(wn * jnp.float32(1 << shift)),
                       jnp.float32(1.0)).astype(jnp.uint32)


def secure_quant_aggregate(eng, upload, ref, w, losses, rngs=None):
    """The in-process secure QUANTIZED aggregation stage (ROADMAP item
    1(b)): ``--secure_quant`` swaps the builder's sanitize/defend/
    aggregate tail for the jitted one-phase GF(p) fold — the CODEC-
    family emulation of privacy/secure_quant.py inside the round body,
    so simulated runs train on exactly the numbers the encoded secure
    wire would deliver.

    Per leaf: scale (static ``sq_scales`` from the init model), quantize
    into the field (ops/mpc_device.quantize_device — bitwise
    ``mpc.quantize32``), multiply by the integer fold weight INSIDE the
    field (shift-add mulmod: products of residues never materialize, so
    uint32 suffices for p < 2^31 with x64 disabled), residue-sum over
    clients, dequantize, undo the scale, divide by the integer mass.
    Every step is exact field/integer arithmetic or one correctly-
    rounded f32 op, so the aggregate is BITWISE what the host fold — a
    ``SlotAccumulator`` over ``encode_secure_quant`` frames at the same
    ``(p, frac_bits, scales, weights)`` — produces (pinned in
    tests/test_program.py; masks cancel exactly mod p, which is why the
    mask-free device fold can BE the parity reference).

    The privacy-plane matrix applies: clip-family defenses run
    CLIENT-side pre-quantize (``SecureFedAvgClientProc`` precedent);
    order statistics were rejected at startup; there is no server-side
    non-finite gate — a NaN quantizes to the neutral zero residue (its
    weight still enters the mass, exactly like the real protocol, and
    ``n_bad`` reports the count without changing the fold)."""
    from neuroimagedisttraining_tpu.codec.wire import (
        _named_leaves, _rebuild_like,
    )
    from neuroimagedisttraining_tpu.ops import mpc_device

    f = eng.cfg.fed
    spec, scales = eng.sq_spec, eng.sq_scales
    shift = int(eng.sq_weight_shift)
    p, fb = int(spec.p), int(spec.frac_bits)
    pp = jnp.uint32(p)
    if f.defense_type != "none":
        # client-side clip family (the ctor admitted nothing else)
        upload = dict(upload, params=robust.defend_stacked(
            upload["params"], ref["params"], defense=f.defense_type,
            norm_bound=f.norm_bound, stddev=f.stddev, rngs=rngs))
    finite = robust.finite_per_client(upload)
    n_bad = jnp.sum(~finite).astype(jnp.int32)
    wi = sq_integer_weights(w, shift)
    # integer mass < cohort * 2^shift < the startup capacity bound,
    # well inside f32's 2^24 exact-integer range
    denom = jnp.sum(wi).astype(jnp.float32)
    C = int(jax.tree.leaves(upload)[0].shape[0])
    out = {}
    for name, x in _named_leaves(upload):
        s_leaf = jnp.float32(scales.get(name, 1.0))
        q = mpc_device.quantize_device(
            x.astype(jnp.float32) / s_leaf, p=p, frac_bits=fb)
        # (wi_c * q_c) mod p by shift-add doubling: wi < 2^(shift+1), so
        # shift+1 conditional field-adds — addmod keeps everything < p,
        # no uint32 wrap for any admissible field
        wib = wi.reshape((-1,) + (1,) * (q.ndim - 1))
        acc = jnp.zeros_like(q)
        cur = q
        for b in range(shift + 1):
            bit = ((wib >> b) & jnp.uint32(1)) > 0
            acc = jnp.where(bit, mpc_device._addmod(acc, cur, pp), acc)
            cur = mpc_device._addmod(cur, cur, pp)
        # ascending client order, like secure_sum_device — mod-p adds
        # are exact, so the order is convention, not a numerics choice
        total = jax.lax.fori_loop(
            1, C, lambda c, t: mpc_device._addmod(t, acc[c], pp),
            acc[0])
        deq = mpc_device.dequantize_device(total, p=p,
                                           frac_bits=fb) * s_leaf
        out[name] = (deq / denom).astype(x.dtype)
    agg = _rebuild_like(ref, out)
    safe_losses = jnp.where(jnp.isfinite(losses), losses, 0.0)
    mean_loss = jnp.sum(safe_losses * w) / jnp.maximum(jnp.sum(w), 1e-9)
    return agg["params"], agg["batch_stats"], mean_loss, n_bad


def health_update_stats(upload, ref, new_params, w) -> dict:
    """The builder's default in-dispatch training-health leg (ISSUE
    15): per-client update L2 norms vs the round's broadcast params,
    cosine similarity of each client update to the aggregated update,
    update-norm dispersion, and the global param / aggregate-update
    norms — all pure jnp on values the round body already holds, traced
    with the round like any other output. Names/semantics:
    ``obs/health.py UPDATE_STAT_NAMES`` (the host-side publisher);
    batch_stats are running moments, not an optimization direction, so
    the geometry is measured on params only.

    ``upload`` is the post-attack/post-codec payload the aggregation
    consumed — the wire's truth, which is exactly what a divergence
    rule should judge.

    The cosine is LEAVE-ONE-OUT: client i scores against the aggregate
    minus its own weighted contribution. Against the raw aggregate, a
    sign-flipping silo's own mass flips its cosine back toward +1
    (measured: +0.09 for a 1/3-weight flipped client whose honest twin
    reads -0.5), burying exactly the signal the divergence rule exists
    for. The subtraction is exact for the weighted-mean tail and an
    approximation under robust defenses — a diagnostic, not a parity
    surface. Everything reduces to per-client dot products, so the
    leave-one-out costs nothing extra."""
    up = [x.astype(jnp.float32) for x in jax.tree.leaves(upload["params"])]
    rf = [x.astype(jnp.float32) for x in jax.tree.leaves(ref["params"])]
    nw = [x.astype(jnp.float32) for x in jax.tree.leaves(new_params)]
    C = int(up[0].shape[0]) if up else 1
    sq = jnp.zeros((C,), jnp.float32)
    dots = jnp.zeros((C,), jnp.float32)
    agg_sq = jnp.float32(0.0)
    gsq = jnp.float32(0.0)
    for u, r, n in zip(up, rf, nw):
        du = (u - r[None]).reshape(C, -1)
        da = (n - r).reshape(-1)
        sq = sq + jnp.sum(du * du, axis=1)
        dots = dots + du @ da
        agg_sq = agg_sq + jnp.sum(da * da)
        gsq = gsq + jnp.sum(n.reshape(-1) ** 2)
    norms = jnp.sqrt(sq)
    agg_norm = jnp.sqrt(agg_sq)
    wf = w.astype(jnp.float32)
    p = wf / jnp.maximum(jnp.sum(wf), jnp.float32(1e-12))
    # leave-one-out: loo_i = agg - p_i * d_i (direction of everyone
    # else's mass; the (W - w_i)/W scale cancels in the cosine)
    loo_dot = dots - p * sq
    loo_sq = jnp.maximum(agg_sq - 2.0 * p * dots + p * p * sq,
                         jnp.float32(0.0))
    cos = loo_dot / jnp.maximum(norms * jnp.sqrt(loo_sq),
                                jnp.float32(1e-12))
    med = jnp.median(norms)
    return {
        "h_up_norms": norms,
        "h_up_max": jnp.max(norms),
        "h_up_med": med,
        "h_cos_min": jnp.min(cos),
        "h_cos_mean": jnp.mean(cos),
        "h_disp": jnp.max(norms) / jnp.maximum(med, jnp.float32(1e-12)),
        "h_gnorm": jnp.sqrt(gsq),
        "h_agg_up": agg_norm,
        # the full [C] leave-one-out cosine vector rides out too (no
        # gauge — the reflex plane's quarantine handler attributes a
        # divergence alert to the offending SAMPLED client with it;
        # engines/base.py _register_reflexes, ISSUE 20)
        "h_cos": cos,
    }


def mask_health_stats(new_masks, old_masks) -> dict:
    """Mask-health stats (``obs/health.py MASK_STAT_NAMES``) for a
    masked engine's ``RoundStages.health`` hook: mean kept fraction,
    round-over-round kept-weight overlap, and churn — computed over
    congruent mask pytrees (client-stacked or global) inside the round
    body. ``old_masks=None`` (a static mask) reads as overlap 1."""
    kept = jnp.float32(0.0)
    total = 0.0
    both = jnp.float32(0.0)
    was = jnp.float32(0.0)
    old_leaves = (jax.tree.leaves(old_masks) if old_masks is not None
                  else None)
    for i, m in enumerate(jax.tree.leaves(new_masks)):
        mb = m > 0
        kept = kept + jnp.sum(mb)
        total += float(np.prod(m.shape))
        if old_leaves is not None:
            ob = old_leaves[i] > 0
            both = both + jnp.sum(mb & ob)
            was = was + jnp.sum(ob)
    density = kept / jnp.float32(max(total, 1.0))
    if old_masks is None:
        overlap = jnp.float32(1.0)
    else:
        overlap = both / jnp.maximum(was, jnp.float32(1.0))
    return {"h_mask_density": density, "h_mask_overlap": overlap,
            "h_mask_churn": jnp.float32(1.0) - overlap}


def _codec_stage(eng, stages: RoundStages, ctx: RoundCtx, upload, efs):
    """The wire codec's lossy roundtrip over the whole upload payload
    (codec/device.py) — delta vs the round's broadcast reference,
    optional top-k with per-client error feedback (``uses_ef`` engines),
    mask handoff for engines that own one (``codec_masks``),
    quantization. Returns ``(decoded_upload, new_efs, u0)`` where ``u0``
    is client 0's decoded upload for the host-side byte accounting."""
    from neuroimagedisttraining_tpu.codec import device as codec_dev

    spec = eng.wire_spec
    ref = ctx.upload_ref
    masks_full = stages.codec_masks(ctx) if stages.codec_masks else None
    new_efs = None
    if stages.uses_ef and spec.needs_ef:
        dec, new_efs = jax.vmap(
            lambda u, e: codec_dev.lossy_roundtrip(
                spec, u, reference=ref, ef=e))(upload, efs)
        # a non-finite upload row (byz nonfinite attack, diverged
        # optimizer) would park NaN in the EF stack FOREVER — EF =
        # u - decode(u) is NaN, and every later encode consumes it, so
        # the guard would zero-weight the client for the rest of the
        # run. Zero those rows so the value fault stays transient (the
        # engine-side mirror of the server's post-quarantine
        # ARG_EF_RESET invariant).
        fin = robust.finite_per_client(upload)
        new_efs = jax.tree.map(
            lambda e: jnp.where(
                fin.reshape((-1,) + (1,) * (e.ndim - 1)),
                e, jnp.zeros_like(e)), new_efs)
    else:
        dec, _ = jax.vmap(
            lambda u: codec_dev.lossy_roundtrip(
                spec, u, reference=ref, masks=masks_full))(upload)
    u0 = jax.tree.map(lambda x: x[0], dec)
    return dec, new_efs, u0


# ---------------------------------------------------------------------------
# the program builder
# ---------------------------------------------------------------------------


class RoundProgram:
    """Compiles an engine's :class:`RoundStages` declaration into every
    dispatch variant (``round_jit``, ``stream_jit``; each under the
    stacked, sharded or folded placement) and owns the fallback
    reporting. One instance per engine (``FederatedEngine.program``);
    compiled programs are cached on the ENGINE (``_round_prog_cache``).
    A dispatch holds one round.

    ``built`` counts program compilations (cache misses); ``dispatches``
    counts compiled-program invocations (bench.py ``round_program``
    cell).
    """

    def __init__(self, eng, stages: RoundStages | None):
        if stages is not None and stages.uses_ef \
                and stages.codec_masks is not None:
            # lossy_roundtrip tracks EF only when masks are absent
            # (codec/device.py: the mask handoff REPLACES top-k error
            # feedback), so a declaration naming both would silently
            # drop one of them inside _codec_stage
            raise ValueError(
                f"{type(eng).__name__} declares both uses_ef and "
                "codec_masks: the codec's mask handoff replaces error "
                "feedback — declare one")
        self.eng = eng
        self.stages = stages
        if stages is not None and (stages.health is None) \
                != (not stages.health_outputs):
            raise ValueError(
                f"{type(eng).__name__}: RoundStages.health and "
                "health_outputs must be declared together (the hook's "
                "returned stat names ARE the flattened-output order)")
        #: the in-dispatch training-health leg (ISSUE 15): stat names
        #: appended after the declared outputs (and the EF tail) when
        #: --health_stats arms it; the dispatch wrapper strips them
        #: back off and queues the device values, so every legacy
        #: driver/adapter arity is untouched
        self.health_names: tuple[str, ...] = ()
        if stages is not None and getattr(eng.cfg, "health_stats",
                                          False):
            self.health_names = obs_health.stat_names_for(
                stages.carry, stages.health_outputs)
        self.built = 0
        self.dispatches = 0
        #: builds per exact plan-cache key — a key building TWICE is a
        #: recompile (LRU thrash / shape leak), the storm the compute
        #: profiler warns about (obs/compute.py)
        self._build_counts: dict[tuple, int] = {}

    # ---------- fallback reporting ----------

    def cohort_fallback_key(self) -> str | None:
        """Why the engine runs unsharded even when ``--client_mesh``
        asks for the cohort-sharded mesh — a :data:`REASONS` key, or
        None when the sharded path arms (mode checks shared by every
        capable engine)."""
        eng = self.eng
        if self.stages is None or not eng.supports_cohort_sharding:
            return eng.cohort_fallback_key()
        if eng.mesh is not None and len(eng.mesh.axis_names) != 1:
            return "two-level-mesh"
        if eng.mesh is not None and eng.mesh.devices.size == 1:
            return "one-device"
        if eng.stream is not None:
            return "streaming-sharded-feed"
        if eng.cfg.optim.batch_order != "shuffle":
            return "batch-order-replacement"
        if not self.stages.gathers_cohort \
                and eng.num_clients % eng.mesh.devices.size != 0:
            return "cohort-not-tiling"
        return None

    # ---------- placement: stacked, sharded or folded (PR 25) ----------

    def _stack_fits(self) -> bool:
        """The stacked client states of one round against the engine's
        budget (``FederatedEngine.fold_budget_bytes``: what the device
        reports, less the resident cohort). Decided from shapes alone:
        nothing is placed on the device to ask."""
        eng = self.eng
        budget = eng.fold_budget_bytes()
        if budget is None:
            return True
        cs = jax.eval_shape(eng.trainer.init_client_state,
                            jax.random.key(0), eng.sample_input())
        n_dev = 1 if eng.mesh is None else int(eng.mesh.devices.size)
        rows = -(-int(eng.cfg.fed.client_num_per_round) // n_dev)
        return stacked_state_bytes(cs.params, cs.opt_state, rows) <= budget

    def fold_refusal_key(self) -> str | None:
        """What the configuration asks for that needs every upload at
        once — a :data:`REASONS` key the folded program is REFUSED with
        (there is no stacked program to fall back to: it does not fit),
        or None."""
        eng = self.eng
        if eng.active_defense() in robust.ROBUST_AGGREGATORS:
            return "fold-order-statistic-defense"
        if eng._byz_on():
            return "fold-byz-attack-plan"
        if eng.wire_spec is not None:
            return "fold-codec-error-feedback"
        if self.health_names:
            return "fold-health-stats"
        if getattr(eng, "sq_spec", None) is not None:
            return "fold-secure-quant"
        return None

    @functools.cached_property
    def placement(self) -> str:
        """Where this engine's round programs place their clients,
        decided once, at the first program build, by what the program
        can observe (bytes), never by a flag: SHARDED when
        ``--client_mesh`` armed, FOLDED when the stacked client states
        exceed the budget and the stages declare the fold (refused with
        its reason when the configuration needs the whole stack),
        STACKED otherwise."""
        eng = self.eng
        if eng._cohort_on:
            return SHARDED
        if self.stages is None or self._stack_fits():
            return STACKED
        if not self.stages.folds:
            eng.log.info(
                "stacked client states exceed the device budget; %s",
                report_fallback(eng.name, "fold-not-declared"))
            return STACKED
        key = self.fold_refusal_key()
        if key is not None:
            raise ValueError(
                f"{eng.name}: the round must fold its clients (their "
                "stacked states exceed the device's memory budget), and "
                f"cannot: {reason(key)}")
        eng.log.info(
            "stacked client states exceed the device budget: clients run "
            "one after another, each upload folded into the running "
            "weighted sum (placement %s)", FOLDED)
        return FOLDED

    # ---------- the round body, composed from the declared stages ----------

    def _gather(self, data, idx):
        with jax.named_scope(obs_names.SCOPE_GATHER):
            if self.placement == FOLDED:
                # the client loop takes one client's rows at a time
                return None, None, jnp.take(data.n_train, idx, axis=0)
            Xs = jnp.take(data.X_train, idx, axis=0)
            ys = jnp.take(data.y_train, idx, axis=0)
            ns = jnp.take(data.n_train, idx, axis=0)
        return Xs, ys, ns

    def _body(self, carry_vals: tuple, data, const_vals: tuple, Xs, ys,
              ns, idx, rngs, lr, efs, byz, per_round_vals, static_key,
              n_real, sharded: bool, deal=None):
        """One round: the declared stages in builder order, each under
        its device scope (obs/names.py SCOPE_*: compile-time metadata a
        profiler trace reads back; the one place every engine built on
        the builder gets them). ``deal`` (cohort sharding): the rows
        arrive dealt to the chips by step count, ``deal[k]`` the place
        of row ``k`` in the sampler's padded set; the train stage runs
        on them as dealt and everything after it sees the sampler's
        order. Returns ``(new_carry: dict, outs: dict, efs_tail:
        tuple)``."""
        scope = jax.named_scope
        eng, st = self.eng, self.stages
        carry = dict(zip(st.carry, carry_vals))
        consts = dict(zip(st.consts, const_vals))
        per_round = dict(zip(st.per_round, per_round_vals or ()))
        if self.placement == FOLDED:
            new_carry, outs = self._fold_body(
                carry, data, consts, Xs, ys, ns, idx, rngs, lr, per_round,
                static_key, byz)
            return new_carry, outs, ()
        if n_real is not None:
            ns = cohort.pad_row_weights(ns, n_real, deal)
        ctx = RoundCtx(eng, st, carry, data, consts, Xs, ys, ns, idx,
                       rngs, lr, per_round, static_key, n_real, sharded)
        with scope(obs_names.SCOPE_LOCAL_TRAIN):
            tr = st.train(ctx)
        S = int(tr.losses.shape[0])
        if deal is not None or (n_real is not None and n_real < S):
            # the real rows, in the sampler's order, before the
            # attack/codec/defense/aggregate/update tail: a static slice
            # drops the mesh-pad rows, or, where the rows were dealt, one
            # take over model-sized leaves undoes the deal and drops
            # them. The tail executes the identical operations on the
            # identical rows the sequential C-loop executes
            # (parallel/cohort.py contract)
            real = (slice(n_real) if deal is None
                    else jnp.argsort(deal)[:n_real])
            sl = lambda t: jax.tree.map(lambda x: x[real], t)
            tr = TrainOut(losses=sl(tr.losses),
                          upload=sl(tr.upload) if tr.upload is not None
                          else None,
                          state=sl(tr.state) if tr.state is not None
                          else None,
                          extra=sl(tr.extra))
            ns = ns[real]
            ctx.ns = ns
            if idx is not None:
                ctx.sampled_idx = idx[real]
        w = ns.astype(jnp.float32)
        upload = tr.upload
        new_efs = u0 = None
        if byz is not None:
            if not st.supports_attack:
                # trace-time consistency check: the ctor's
                # supports_byz_faults gate should make this unreachable,
                # but the declaration is the builder's contract — a plan
                # reaching stages that never declared the attack stage
                # is a bug, not a silent skip
                raise ValueError(
                    f"{type(eng).__name__}: byz attack plan reached a "
                    "RoundStages declaration without supports_attack")
            # the attack hits the WHOLE upload payload (params + batch
            # stats — what the wire ships) before any encoding; honest
            # clients ride the plan's identity rows bitwise-untouched
            mult, std, nonfinite, keys = byz
            with scope(obs_names.SCOPE_ATTACK):
                upload = adversary.apply_attack_stacked(
                    upload, ctx.upload_ref, mult, std, nonfinite, keys)
        if eng.wire_spec is not None:
            with scope(obs_names.SCOPE_CODEC):
                upload, new_efs, u0 = _codec_stage(eng, st, ctx, upload,
                                                   efs)
        with scope(obs_names.SCOPE_AGGREGATE):
            if st.aggregate is not None:
                new_carry, outs = st.aggregate(ctx, upload, w, tr)
            else:
                rng_leaf = tr.state.rng if tr.state is not None else None
                # --secure_quant: the field fold REPLACES the default
                # tail (the in-process codec-family stage, ROADMAP 1(b))
                tail = (secure_quant_aggregate
                        if getattr(eng, "sq_spec", None) is not None
                        else sanitize_defend_aggregate)
                new_params, new_bstats, mean_loss, n_bad = tail(
                    eng, upload, ctx.upload_ref, w, tr.losses,
                    rngs=rng_leaf)
                new_carry = {"params": new_params,
                             "batch_stats": new_bstats}
                outs = {"loss": mean_loss, "n_bad": n_bad}
            for name in st.outputs:
                if name not in outs:  # a counter of the train stage
                    outs[name] = jnp.sum(tr.extra[name], axis=0)
        if st.update is not None:
            with scope(obs_names.SCOPE_STATE_UPDATE):
                new_carry.update(st.update(ctx, tr, new_carry))
        missing = set(st.carry) - set(new_carry)
        assert not missing, f"stages left carry entries unset: {missing}"
        if self.health_names:
            # the in-dispatch training-health leg (ISSUE 15): pure jnp
            # over values this body already computed, traced with the
            # round and returned as trailing outputs — no host touch,
            # no extra dispatch, and the carry math above is untouched
            # (the armed-vs-disarmed bitwise pin, tests/test_health.py)
            hs: dict = {}
            if obs_health.UPDATE_STAT_NAMES[0] in self.health_names:
                measured = upload
                if measured is None and tr.state is not None:
                    measured = {"params": tr.state.params,
                                "batch_stats": tr.state.batch_stats}
                if measured is None:
                    raise ValueError(
                        f"{type(eng).__name__}: health stats need an "
                        "upload payload (or TrainOut.state) to measure "
                        "— the declared train stage returned neither")
                hs.update(health_update_stats(
                    measured, ctx.upload_ref, new_carry["params"], w))
            if st.health is not None:
                hs.update(st.health(ctx, tr, new_carry))
            missing_h = set(self.health_names) - set(hs)
            assert not missing_h, \
                f"health stage left stats unset: {missing_h}"
            outs = dict(outs, **{n: hs[n] for n in self.health_names})
        efs_tail = ()
        if eng.wire_spec is not None:
            efs_tail = (new_efs, u0) if st.uses_ef else (u0,)
        return new_carry, outs, efs_tail

    def _fold_body(self, carry: dict, data, consts: dict, Xs, ys, ns, idx,
                   rngs, lr, per_round: dict, static_key, byz):
        """One round in the FOLDED placement: the declared train stage
        runs for one sampled client at a time inside a ``lax.scan``, each
        from the broadcast global state, and the train and aggregate
        stages fuse. The carry is what the builder's default tail
        (:func:`sanitize_defend_aggregate`) would reduce the upload stack
        to: the running ``sum_i w_i * upload_i`` (parameters and batch
        statistics, float32), ``sum_i w_i``, and the count of non-finite
        clients; a client's upload is sanitized and (clip family only)
        defended alone, inside the loop, then dropped. A bad client adds
        nothing and counts in ``n_bad``. The result is the stacked
        path's weighted mean up to float32 summation order (``sum w x /
        sum w`` for ``sum (w / sum w) x``; tests/test_round_fold.py).
        Scopes are the stacked body's, so a trace reads both alike.
        Resident data is gathered a client at a time inside the loop
        (``Xs`` None); streamed shards arrive stacked and are scanned."""
        scope = jax.named_scope
        eng, st = self.eng, self.stages
        if byz is not None:
            raise ValueError(reason("fold-byz-attack-plan"))
        defense = eng.active_defense()
        if defense in robust.ROBUST_AGGREGATORS:
            # the reflex plane can escalate the defense mid-run: the
            # re-traced program refuses here, as the first build would
            raise ValueError(reason("fold-order-statistic-defense"))
        ref = {"params": carry["params"],
               "batch_stats": carry["batch_stats"]}
        f32 = jnp.float32

        def one_client(acc, row):
            if Xs is None:
                with scope(obs_names.SCOPE_GATHER):
                    Xc = jnp.take(data.X_train, row["i"], axis=0)
                    yc = jnp.take(data.y_train, row["i"], axis=0)
            else:
                Xc, yc = row["X"], row["y"]
            one = lambda x: None if x is None else x[None]
            ctx = RoundCtx(eng, st, carry, data, consts, Xc[None], yc[None],
                           row["n"][None], one(row.get("i")),
                           row["rng"][None], lr, per_round, static_key,
                           None, False, folded=True)
            with scope(obs_names.SCOPE_LOCAL_TRAIN):
                tr = st.train(ctx)
            with scope(obs_names.SCOPE_AGGREGATE):
                upload, finite = _sanitize(tr.upload, ref)
                params = _clip_defend(
                    eng, upload, ref, defense,
                    tr.state.rng if tr.state is not None else None)
                w = row["n"].astype(f32) * finite[0].astype(f32)
                add = lambda a, x: a + w * x[0].astype(f32)
                acc = {
                    "params": jax.tree.map(add, acc["params"], params),
                    "batch_stats": jax.tree.map(add, acc["batch_stats"],
                                                upload["batch_stats"]),
                    "w": acc["w"] + w,
                    "n_bad": acc["n_bad"] + (~finite[0]).astype(jnp.int32),
                }
            extra = {k: v[0] for k, v in tr.extra.items()
                     if k in st.outputs}
            return acc, (tr.losses[0], w, extra)

        rows = {"n": ns, "rng": rngs}
        if idx is not None:
            rows["i"] = idx
        if Xs is not None:
            rows.update(X=Xs, y=ys)
        zeros = lambda t: jax.tree.map(
            lambda x: jnp.zeros(x.shape, f32), t)
        acc0 = {"params": zeros(ref["params"]),
                "batch_stats": zeros(ref["batch_stats"]),
                "w": f32(0.0), "n_bad": jnp.int32(0)}
        acc, (losses, w, extra) = jax.lax.scan(one_client, acc0, rows)
        with scope(obs_names.SCOPE_AGGREGATE):
            total = jnp.maximum(acc["w"], 1e-12)
            mean = lambda a, r: (a / total).astype(r.dtype)
            new_carry = {
                "params": jax.tree.map(mean, acc["params"],
                                       ref["params"]),
                "batch_stats": jax.tree.map(mean, acc["batch_stats"],
                                            ref["batch_stats"])}
            outs = {"loss": _weighted_loss(losses, w),
                    "n_bad": acc["n_bad"]}
            outs.update({k: jnp.sum(v, axis=0) for k, v in extra.items()})
        return new_carry, outs

    def _epilogue(self, carry: dict, data) -> tuple:
        st = self.stages
        if st.epilogue is None:
            return ()
        with jax.named_scope(obs_names.SCOPE_EPILOGUE):
            return tuple(st.epilogue(self.eng, carry, data))

    def _flat(self, new_carry: dict, epi: tuple, outs: dict,
              efs_tail: tuple) -> tuple:
        st = self.stages
        # health stats ride LAST (after the EF tail) so the dispatch
        # wrapper can strip a fixed-length suffix without knowing the
        # program variant's tail shape
        return (*(new_carry[n] for n in st.carry), *epi,
                *(outs[o] for o in st.outputs), *efs_tail,
                *(outs[h] for h in self.health_names))

    def _note_build(self, label: str, key: tuple) -> None:
        """One program compilation: ``built`` and the scrapeable
        ``nidt_compiles_total{engine, program}`` counter move TOGETHER
        (one measurement — tests/test_program.py pins them equal). A
        rebuild of the same exact plan-cache ``key`` is a recompile
        (warning-logged + flight-recorded by the profiler)."""
        _ = self.placement  # decided (or refused) before anything traces
        self.built += 1
        n = self._build_counts[key] = self._build_counts.get(key, 0) + 1
        obs_compute.note_compile(self.eng.name, label, recompile=n > 1)

    def _count_dispatches(self, jitted, label: str = "round",
                          rounds: int = 1):
        """Wrap a compiled program so invocations count toward
        ``dispatches`` (the bench's per-engine dispatch evidence) and
        feed the dispatch-boundary profiler (obs/compute.py): host wall
        around the call — compile-dominated on the first invocation (jit
        compiles at first call), enqueue thereafter — plus ``rounds``
        toward the MFU numerator. No sync is added anywhere: the clock
        brackets the ENQUEUE, and MFU divides by boundary-to-boundary
        wall where the driver already blocked. ``.jit``/``.lower``
        expose the underlying executable for compile-text tests.

        When the training-health leg is armed, the program's trailing
        ``health_names`` outputs are stripped HERE and queued on the
        engine as device arrays (``_note_health`` — drained in the
        batched ``device_get`` at the next host boundary, never synced
        per dispatch), so every legacy driver/adapter sees its historic
        arity."""
        state = {"first": True}
        eng = self.eng
        health_names = self.health_names

        def dispatch(*args):
            self.dispatches += 1
            eng._arm_compute_profiler()
            # one span per dispatch (disarmed: a shared no-op) — under
            # --profile_dir the span opens a jax.profiler
            # TraceAnnotation, so this exact program invocation is the
            # shared ruler between the host and XLA timelines. The
            # driver's counts for this dispatch (round, samples_real,
            # steps_real, steps_run, steps_skipped, chip_steps_max,
            # chip_steps_mean: base._note_round_counts) ride on it
            counts = eng._dispatch_counts
            if counts:
                eng._dispatch_counts = {}
            with obs_trace.span(obs_names.SPAN_DISPATCH_PROGRAM,
                                program=label, engine=eng.name,
                                rounds=rounds, **counts):
                t0 = time.perf_counter()
                out = jitted(*args)
                dur = time.perf_counter() - t0
            obs_compute.note_dispatch(
                eng.name, label, dur, rounds=rounds,
                phase="compile" if state["first"] else "execute")
            state["first"] = False
            if health_names:
                n_h = len(health_names)
                eng._note_health(dict(zip(health_names, out[-n_h:])))
                out = out[:-n_h]
            return out

        dispatch.jit = jitted
        dispatch.lower = jitted.lower
        return dispatch

    # ---------- compiled variants ----------

    def round_jit(self, n_real: int | None = None, static_key=None,
                  sharded: bool | None = None):
        """The single-round program:
        ``f(carry, data, consts, idx, rngs, lr, efs=None, byz=None,
        per_round=None, deal=None)``. ``carry`` (argnum 0) and ``efs``
        (argnum 6) are donated; ``n_real`` marks the cohort-sharded
        variant over the mesh-padded sampled set (static —
        fault-schedule cohort shrinkage re-specializes via the plan
        cache), whose ``idx`` and ``rngs`` are in the order of that
        round's ``deal`` (an operand: another deal is no recompile)."""
        shard = sharded if sharded is not None else (n_real is not None)
        key = ("round", n_real, static_key, shard)
        label = "round_sharded" if shard else "round"

        def build():
            self._note_build(label, key)

            def round_fn(carry, data, consts, idx, rngs, lr, efs=None,
                         byz=None, per_round=None, deal=None):
                if self.stages.gathers_cohort:
                    Xs, ys, ns = self._gather(data, idx)
                else:
                    Xs, ys, ns = data.X_train, data.y_train, data.n_train
                new_carry, outs, efs_tail = self._body(
                    carry, data, consts, Xs, ys, ns, idx, rngs, lr, efs,
                    byz, per_round, static_key, n_real, shard, deal)
                epi = self._epilogue(new_carry, data)
                return self._flat(new_carry, epi, outs, efs_tail)

            return self._count_dispatches(jax.jit(
                round_fn,
                donate_argnums=self.eng._donate_argnums(0, 6)),
                label=label)

        return self.eng._plan_cached("_round_prog_cache", key, build)

    def stream_jit(self):
        """The streamed single-round program: shards arrive pre-gathered
        (data/stream.py feeds the sampled clients' padded arrays), the
        federation data never enters the program. It has no resident
        data to hand an epilogue stage, so a declaration with one is
        refused (an engine that needs both keeps its streaming outside
        the builder: dpsgd's chunked ``_round_streaming``)."""
        if self.stages is not None and self.stages.epilogue is not None:
            raise ValueError(
                f"{type(self.eng).__name__} declares an epilogue stage "
                "and streams through the builder: the streamed round "
                "program has no resident data for the epilogue")

        def build():
            self._note_build("stream", ("stream",))

            def stream_round_fn(carry, consts, Xs, ys, ns, idx, rngs, lr,
                                efs=None, byz=None):
                new_carry, outs, efs_tail = self._body(
                    carry, None, consts, Xs, ys, ns, idx, rngs, lr, efs,
                    byz, None, None, None, False)
                epi = self._epilogue(new_carry, None)
                return self._flat(new_carry, epi, outs, efs_tail)

            return self._count_dispatches(jax.jit(
                stream_round_fn,
                donate_argnums=self.eng._donate_argnums(0)),
                label="stream")

        return self.eng._plan_cached("_round_prog_cache", ("stream",),
                                     build)
