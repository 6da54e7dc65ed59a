"""Runnable cross-silo federation: one OS process per silo, real sockets.

The reference's distributed runtime was vestigial library code with no
entry point (SURVEY §2.3); this module makes ours drivable::

    # terminal 1 — the aggregation server (rank 0)
    python -m neuroimagedisttraining_tpu.distributed.run --role server \
        --num_clients 2 --comm_round 5 --model 3dcnn_tiny \
        --dataset synthetic --base_port 29500

    # terminals 2..N+1 — one trainer process per silo (ranks 1..N)
    python -m neuroimagedisttraining_tpu.distributed.run --role client \
        --rank 1 --num_clients 2 --comm_round 5 --model 3dcnn_tiny \
        --dataset synthetic --base_port 29500

Across machines, pass every rank's address once to all processes:
``--hosts 0=10.0.0.1,1=10.0.0.2,2=10.0.0.3`` (each rank listens on
``base_port + rank``). ``--secure`` swaps in the TurboAggregate
additive-share protocol (SecureFedAvgServer/ClientProc): clients upload
share slots of their weighted quantized updates and the server
reconstructs only the aggregate. Add ``--n_aggregators K`` (= K extra
processes with ``--role aggregator --slot_index j``, ranks
num_clients+1+j) for the grouped deployment: slot j rides to aggregator
j, each aggregator forwards only its cross-client slot total, and no
single node — server included — can reconstruct any client::

    # grouped secure aggregation: server + N silos + K aggregators
    python -m ...distributed.run --role aggregator --slot_index 0 \
        --num_clients 2 --n_aggregators 3 --secure ...

Each client trains its own site shard with the real jitted LocalTrainer
(silo k holds site ``(k-1) mod num_sites``); the server runs the
register -> broadcast -> train -> upload -> aggregate -> finish protocol
(cross_silo.py) and prints one JSON line with the final round count and
aggregate param norm.

Fault tolerance (ISSUE 2): ``--transport broker`` swaps the socket plane
for the pub/sub broker (hosted by the server process);
``--fault_spec "crash:3@1,drop:0.1,..."`` wraps each client's transport
in the seeded FaultyCommManager (faults/) so chaos replays bit-identically
from ``--seed``; ``--round_deadline``/``--quorum`` let the server
aggregate survivor subsets instead of hanging on a dead silo, and
``--heartbeat_interval``/``--heartbeat_timeout`` drive the suspicion
machinery. ``scripts/run_chaos_smoke.sh`` exercises the kill-k scenario
end-to-end on both transports.

Wire codec (ISSUE 3): ``--wire_codec delta+sparse+quant`` makes every
silo upload a tagged codec frame (codec/) — delta vs the round's sync,
sparse packing, int8/bf16 quantization — which the server decodes before
aggregation; ``--wire_mask_density 0.5`` additionally emulates the
masked-engine deployment (every rank derives the same seeded mask, silos
train masked, frames ship bitmap-free). ``scripts/run_wire_bench.sh``
A/Bs the bytes-on-wire against the dense format using the transports'
byte counters. This is the cross-silo deployment shape: bulk per-silo
compute on each silo's own accelerator(s), small model payloads on the
control plane (on a TPU pod, prefer --multihost_coordinator on the main
CLI so bulk tensors ride ICI/DCN collectives instead).
"""

from __future__ import annotations

import argparse
import json
import sys

import numpy as np


def cohort_fallback_note(n: int) -> str | None:
    """Why ``--client_mesh`` (ISSUE 6) has nothing to shard on the
    distributed transport (printed once at startup; None when n <= 0).
    The cohort-sharded round maps an IN-PROCESS ``[C, ...]`` client
    stack onto a device mesh; here each rank is one silo training only
    its own cohort — the client axis is the set of OS processes."""
    if n <= 0:
        return None
    from neuroimagedisttraining_tpu.engines import program as round_program

    return (f"client_mesh={n} requested; "
            + round_program.report_fallback(
                "distributed", "distributed-no-client-axis"))


def _parse_hosts(spec: str) -> dict[int, str] | None:
    if not spec:
        return None
    out = {}
    for part in spec.split(","):
        r, ip = part.split("=")
        out[int(r)] = ip
    return out


def _build_shard(args, rank: int):
    """(X, y, n) numpy shard for silo ``rank`` + input sample shape."""
    from neuroimagedisttraining_tpu.data import partition as P

    if args.dataset == "synthetic":
        from neuroimagedisttraining_tpu.data.synthetic import (
            generate_synthetic_abcd,
        )

        cohort = generate_synthetic_abcd(
            num_subjects=args.synthetic_num_subjects,
            shape=tuple(args.synthetic_shape),
            num_sites=max(2, args.num_clients), seed=args.seed,
            signal=args.synthetic_signal)
    else:
        from neuroimagedisttraining_tpu.data.hdf5 import load_abcd_hdf5

        cohort = load_abcd_hdf5(args.data_dir, lazy=False)
    train_map, _, _ = P.site_partition(cohort["site"], seed=42)
    site = (rank - 1) % len(train_map)
    idx = train_map[site]
    X = np.asarray(cohort["X"])[idx]
    y = np.asarray(cohort["y"])[idx]
    return X, y, len(idx)


def _optim_from_args(args):
    """One OptimConfig for every silo-side trainer in this process —
    including the mixed-precision train-step contract (ISSUE 10), so a
    cross-silo silo trains at the same precision the simulated engines
    would (fp32 master weights on the wire either way)."""
    from neuroimagedisttraining_tpu.config import OptimConfig

    return OptimConfig(lr=args.lr, lr_decay=args.lr_decay,
                       batch_size=args.batch_size, epochs=args.epochs,
                       precision=args.precision,
                       loss_scale=args.loss_scale,
                       fused_update=args.fused_update)


def _create_model_from_args(args):
    """Model build honoring the precision contract (compute dtype from
    --precision; master weights stay f32) and the --remat policy ("auto"
    defers to the model family's default — the single-silo runner has no
    federation shape to pick from)."""
    from neuroimagedisttraining_tpu.core.optim import compute_dtype
    from neuroimagedisttraining_tpu.models import create_model

    remat = {"auto": None, "none": False, "stem": "stem",
             "all": True}[args.remat]
    return create_model(args.model, num_classes=args.num_classes,
                        dtype=compute_dtype(args.precision), remat=remat)


def _seed_init_state(args):
    """``(trainer, init ClientState)`` — every rank derives the identical
    model from ``--seed``, so init broadcast, delta references, and wire
    masks agree across processes with no extra exchange."""
    import jax
    import jax.numpy as jnp

    from neuroimagedisttraining_tpu.core.trainer import LocalTrainer

    trainer = LocalTrainer(
        _create_model_from_args(args),
        _optim_from_args(args), num_classes=args.num_classes)
    if args.dataset == "synthetic":
        shape = (1,) + tuple(args.synthetic_shape)
    else:
        from neuroimagedisttraining_tpu.data.hdf5 import load_abcd_hdf5

        X0 = load_abcd_hdf5(args.data_dir, lazy=True)
        shape = (1,) + tuple(X0["X"].shape[1:])
        X0["file"].close()
    gs = trainer.init_client_state(jax.random.key(args.seed),
                                   jnp.zeros(shape, jnp.float32))
    return trainer, gs


def _build_wire_masks(args, gs=None):
    """Deterministic shared pruning mask for ``--wire_mask_density``: the
    masked-engine deployment shape (SalientGrads ships its phase-1 global
    mask to every silo) emulated with a seeded uniform mask every rank
    derives identically — the codec's mask handoff then packs uploads
    bitmap-free (codec/wire.py shared-mask mode). Pass ``gs`` when the
    caller already derived the seed-deterministic init state (the server
    does — no second model build/jit)."""
    import jax
    import jax.numpy as jnp

    from neuroimagedisttraining_tpu.ops import masks as Mk

    if gs is None:
        _, gs = _seed_init_state(args)
    sp = Mk.calculate_sparsities(gs.params, "uniform",
                                 dense_ratio=args.wire_mask_density)
    pm = Mk.init_masks(jax.random.key(args.seed + 97), gs.params, sp)
    tree = {"params": pm,
            "batch_stats": jax.tree.map(jnp.ones_like, gs.batch_stats)}
    return jax.tree.map(np.asarray, tree)


def _make_train_fn(args):
    """``(train_fn, wire_masks)``: the silo-local training closure —
    jitted LocalTrainer epochs on this silo's shard (fedavg
    my_model_trainer semantics, round-decayed lr) — plus the shared wire
    mask when ``--wire_mask_density`` is set, derived from THIS
    trainer's seed-deterministic init (one model build per client, not
    two). With a mask the silo trains MASKED (post-step re-mask, the
    SalientGrads/DisPFL client shape) so its uploads are sparse by
    construction — the deployment the codec's mask-sparse stage packs."""
    import jax
    import jax.numpy as jnp

    from neuroimagedisttraining_tpu.core.trainer import ClientState, LocalTrainer

    X, y, n = _build_shard(args, args.rank)
    optim = _optim_from_args(args)
    trainer = LocalTrainer(_create_model_from_args(args),
                           optim, num_classes=args.num_classes)
    wire_masks = None
    if args.wire_mask_density > 0:
        # derive the shared mask from THIS trainer's init state (the
        # seed-deterministic params every rank agrees on) instead of a
        # second model build + jitted init inside _build_wire_masks
        gs = trainer.init_client_state(
            jax.random.key(args.seed),
            jnp.zeros((1,) + X.shape[1:], jnp.float32))
        wire_masks = _build_wire_masks(args, gs)
    Xd, yd = jnp.asarray(X), jnp.asarray(y)
    mask_d = (jax.tree.map(jnp.asarray, wire_masks["params"])
              if wire_masks is not None else None)

    @jax.jit
    def step(params, bstats, rng, lr):
        cs = ClientState(params=params, batch_stats=bstats,
                         opt_state=trainer.opt.init(params), rng=rng)
        cs, loss = trainer.local_train(
            cs, Xd, yd, n, lr, epochs=optim.epochs,
            batch_size=optim.batch_size, max_samples=Xd.shape[0],
            mask=mask_d)
        return cs.params, cs.batch_stats, loss

    def train_fn(params_np, round_idx):
        # server ships {params, batch_stats}; silo trains and ships back
        params = jax.tree.map(jnp.asarray, params_np["params"])
        bstats = jax.tree.map(jnp.asarray, params_np["batch_stats"])
        rng = jax.random.fold_in(jax.random.key(args.seed + 17 + args.rank),
                                 round_idx)
        lr = jnp.float32(args.lr) * jnp.float32(args.lr_decay) ** round_idx
        p, b, loss = step(params, bstats, rng, lr)
        print(f"[silo {args.rank}] round {round_idx}: "
              f"loss={float(loss):.4f} (n={n})", flush=True)
        return {"params": jax.tree.map(np.asarray, p),
                "batch_stats": jax.tree.map(np.asarray, b)}, float(n)

    return train_fn, wire_masks


def _make_comm(args, rank: int, host_map):
    """Build the rank's transport per ``--transport``; client ranks are
    wrapped in ``FaultyCommManager`` when ``--fault_spec`` is given (the
    transports' own code is untouched). Returns ``(comm, broker)`` —
    ``comm`` may be None (socket, no faults: the manager builds its
    default), ``broker`` is the in-process daemon on the server rank."""
    import time

    comm = None
    broker = None
    world_size = args.num_clients + 1 + args.n_aggregators
    if args.transport == "broker":
        from neuroimagedisttraining_tpu.distributed.broker import (
            BrokerCommManager, MessageBroker,
        )

        port = args.broker_port or args.base_port
        if rank == 0:
            broker = MessageBroker(host="0.0.0.0", port=port)
            comm = BrokerCommManager("127.0.0.1", broker.port, client_id=0,
                                     client_num=args.num_clients)
        else:
            host = (host_map or {}).get(0, "127.0.0.1")
            # the server process hosts the broker daemon — back off while
            # it boots (model build + jit compile precede the broker)
            delay, deadline = 0.25, time.monotonic() + 300
            while True:
                try:
                    comm = BrokerCommManager(host, port, client_id=rank,
                                             client_num=args.num_clients)
                    break
                except OSError:
                    if time.monotonic() > deadline:
                        raise
                    time.sleep(delay)
                    delay = min(2.0, delay * 2)
    elif args.fault_spec and rank != 0:
        from neuroimagedisttraining_tpu.distributed.comm import (
            SocketCommManager,
        )

        comm = SocketCommManager(rank, world_size, host_map=host_map,
                                 base_port=args.base_port)
    if args.fault_spec and rank != 0 and comm is not None:
        from neuroimagedisttraining_tpu.faults import (
            FaultSchedule, FaultyCommManager, parse_fault_spec,
        )

        comm = FaultyCommManager(
            comm, FaultSchedule(parse_fault_spec(args.fault_spec),
                                args.seed), rank)
    return comm, broker


def main(argv=None) -> int:
    ap = argparse.ArgumentParser(
        prog="neuroimagedisttraining_tpu.distributed.run",
        description=__doc__.split("\n\n")[0])
    ap.add_argument("--role", required=True,
                    choices=("server", "client", "aggregator"))
    ap.add_argument("--rank", type=int, default=0,
                    help="client rank 1..num_clients (server is 0); "
                         "aggregator j is rank num_clients+1+j")
    ap.add_argument("--slot_index", type=int, default=0,
                    help="aggregator role: which share slot this process "
                         "aggregates (0..n_aggregators-1)")
    ap.add_argument("--n_aggregators", type=int, default=0,
                    help="secure mode: route share slot j to a distinct "
                         "aggregator process instead of the server "
                         "(TurboAggregate grouped aggregation); must equal "
                         "--mpc_n_shares; 0 = single-server degenerate "
                         "mode")
    ap.add_argument("--num_clients", type=int, required=True)
    ap.add_argument("--comm_round", type=int, default=5)  # nidt: allow[flag-config-cross-cli-drift] -- smoke-scale default; the multiprocess runner ships tiny CPU-safe cells
    ap.add_argument("--base_port", type=int, default=29500)
    ap.add_argument("--hosts", type=str, default="",
                    help="rank=ip,... (default: all localhost)")
    ap.add_argument("--transport", type=str, default="socket",
                    choices=("socket", "broker"),
                    help="control-plane transport: point-to-point TCP "
                         "(every rank listens on base_port+rank) or the "
                         "in-repo pub/sub broker (MQTT topic scheme; the "
                         "server process hosts the broker daemon)")
    ap.add_argument("--broker_port", type=int, default=0,
                    help="broker transport: the broker daemon's port "
                         "(0 = base_port); clients connect to rank 0's "
                         "host at this port")
    ap.add_argument("--fault_spec", type=str, default="",
                    help="deterministic chaos schedule applied to client "
                         "ranks via FaultyCommManager: 'crash:RANK@ROUND,"
                         "crash_prob:P,straggle:P:MAX_S,drop:P,dup:P,"
                         "disconnect:P,byz:RANK@ROUND:KIND,"
                         "byz_prob:P[:KIND]' — replays identically from "
                         "--seed on every rank; byz silos upload "
                         "KIND-corrupted values (sign_flip | scale:K | "
                         "gauss:STD | nonfinite, faults/adversary.py) "
                         "transformed BEFORE the wire codec")
    ap.add_argument("--defense", "--defense_type", dest="defense",
                    type=str, default="none",
                    help="server aggregation defense (core/robust.py): "
                         "none | norm_diff_clipping | weak_dp | "
                         "trimmed_mean | median | krum | multi_krum | "
                         "geometric_median — the order-statistic family "
                         "replaces the weighted mean and tolerates up "
                         "to --byz_f Byzantine silos; validated at "
                         "startup on every rank")
    ap.add_argument("--byz_f", type=int, default=1,
                    help="assumed Byzantine silo count f for the order-"
                         "statistic defenses (trim depth per side / "
                         "Krum neighborhood; krum needs num_clients >= "
                         "f+3, trimmed_mean/median need 2f < n) and the "
                         "quarantine budget (at most f silos "
                         "quarantined at once)")
    ap.add_argument("--geomed_iters", type=int, default=8,
                    help="geometric_median: fixed Weiszfeld iterations")
    ap.add_argument("--norm_bound", type=float, default=5.0,
                    help="clip threshold for norm_diff_clipping/weak_dp")
    ap.add_argument("--stddev", type=float, default=0.05,
                    help="weak_dp per-client Gaussian noise stddev "
                         "(keys derive from --seed per round/silo)")
    ap.add_argument("--quarantine_rounds", type=int, default=0,
                    help="server: > 0 arms Byzantine DETECTION — "
                         "update-norm/cosine outlier scoring feeds "
                         "strike counters, and --outlier_threshold "
                         "strikes quarantine a silo for this many "
                         "rounds (uploads dropped, codec error-"
                         "feedback reset on release); 0 = off")
    ap.add_argument("--outlier_threshold", type=int, default=2,
                    help="value-anomaly strikes before a silo is "
                         "quarantined (clean rounds forgive one strike "
                         "each)")
    ap.add_argument("--async_server", action="store_true",
                    help="server runs the FedBuff-style buffered "
                         "asynchronous control plane (asyncfl/): the "
                         "selector comm core holds every connection in "
                         "one event loop, uploads are accepted "
                         "continuously and aggregated every --buffer_k "
                         "arrivals with (1+tau)^-alpha staleness "
                         "weighting, broadcasts are version-tagged, and "
                         "there is NO round barrier (comm_round counts "
                         "aggregations). Clients run unchanged")
    ap.add_argument("--buffer_k", type=int, default=0,
                    help="async server: aggregate every K accepted "
                         "uploads (0 = num_clients)")
    ap.add_argument("--staleness_alpha", type=float, default=0.5,
                    help="async server: polynomial staleness exponent; "
                         "an upload tau versions stale weighs "
                         "n * (1+tau)^-alpha")
    ap.add_argument("--ingest_workers", type=int, default=0,
                    help="async server: shard the ingest plane across N "
                         "selector worker PROCESSES on one SO_REUSEPORT "
                         "port (asyncfl/ingest.py) — each worker runs "
                         "the admission gates and folds accepted "
                         "uploads into an exact int64 partial "
                         "aggregate; the root merges partials in "
                         "worker-id order, bitwise-equal to the "
                         "single-process fold. 0 = the single-process "
                         "BufferedFedAvgServer")
    ap.add_argument("--regions", type=int, default=0,
                    help="async server: interpose N regional "
                         "sub-aggregator PROCESSES between the ingest "
                         "workers and the root (asyncfl/region.py) — "
                         "each region owns --ingest_workers workers on "
                         "the shared SO_REUSEPORT port, folds their "
                         "partials locally and ships ONE merged partial "
                         "upstream per flush interval; the root merges "
                         "region partials in region-id order, "
                         "bitwise-equal to the flat fold. 0 = flat root")
    ap.add_argument("--ingest_shm", action="store_true",
                    help="ingest workers hand partials to their parent "
                         "over double-buffered shared-memory slabs "
                         "instead of the pickled pipe (same-host "
                         "fast path; the pipe remains the cross-host "
                         "fallback)")
    ap.add_argument("--sync_delta", action="store_true",
                    help="changed-version sync replies to opted-in "
                         "clients ship the lossless byte delta against "
                         "the client's last-synced version from the "
                         "broadcast ring (dense fallback when the base "
                         "left the ring)")
    ap.add_argument("--max_staleness", type=int, default=20,
                    help="async server: uploads staler than this many "
                         "versions are dropped at admission (with a "
                         "logged reason); also bounds the ring of "
                         "historical params kept as codec delta "
                         "references")
    ap.add_argument("--round_deadline", type=float, default=0.0,
                    help="server: per-round deadline seconds; when it "
                         "fires with >= --quorum uploads the round "
                         "aggregates over the survivors (sample-count "
                         "re-weighted) instead of hanging forever")
    ap.add_argument("--quorum", type=int, default=0,
                    help="min uploads for a deadline aggregation "
                         "(0 = simple majority when --round_deadline is "
                         "set, else all clients)")
    ap.add_argument("--heartbeat_interval", type=float, default=0.0,
                    help="clients: liveness beat period seconds "
                         "(0 = no heartbeats)")
    ap.add_argument("--heartbeat_timeout", type=float, default=0.0,
                    help="server: mark a client suspect once its "
                         "heartbeat is older than this (0 = off)")
    ap.add_argument("--wire_codec", type=str, default="none",
                    help="model-update wire codec (codec/): stages "
                         "joined by '+', e.g. none | delta | sparse | "
                         "quant | delta+sparse+quant (quant16 = bf16). "
                         "Uploads ride as tagged frames the server "
                         "decodes before aggregation; the downlink sync "
                         "stays dense (reference-chain safety)")
    ap.add_argument("--wire_topk_ratio", type=float, default=0.25,
                    help="sparse stage without masks: keep fraction for "
                         "magnitude top-k (error-feedback accumulated "
                         "per silo)")
    ap.add_argument("--wire_mask_density", type=float, default=0.0,
                    help="> 0 emulates a masked engine deployment: every "
                         "rank derives the same seeded pruning mask at "
                         "this density, silos train masked, and the "
                         "codec's sparse stage packs uploads bitmap-free "
                         "(mask handoff). 0 = dense training")
    ap.add_argument("--secure", action="store_true",
                    help="TurboAggregate additive-share aggregation over "
                         "the control plane (dense int64 share slots)")
    ap.add_argument("--secure_quant", action="store_true",
                    help="secure QUANTIZED aggregation "
                         "(privacy/secure_quant.py): uploads ride as "
                         "field-element frames in a small GF(p) — one "
                         "uintN residue per parameter plus seed-expanded "
                         "mask slots — so secure aggregation costs a "
                         "FRACTION of the dense wire instead of 6x it. "
                         "Implies the secure protocol; composes with "
                         "clip-family --defense (enforced client-side) "
                         "and with --async_server (one-phase, integer-"
                         "scaled staleness weights); see ARCHITECTURE.md "
                         "'Privacy plane' for the full matrix")
    ap.add_argument("--secure_quant_field_bits", type=int, default=16,
                    choices=(8, 16, 32),
                    help="secure_quant field width: p = largest prime "
                         "below 2^bits; the wire ships one uintN residue "
                         "per parameter (16 -> uint16)")
    ap.add_argument("--secure_quant_frac_bits", type=int, default=10,
                    help="secure_quant fixed-point fraction bits; the "
                         "aggregate headroom vs p and the cohort is "
                         "validated at startup")
    ap.add_argument("--dp_delta", type=float, default=1e-5,
                    help="target delta for the weak_dp RDP accountant's "
                         "(epsilon, delta) report (privacy/accountant.py)")
    ap.add_argument("--mpc_n_shares", type=int, default=3)
    ap.add_argument("--mpc_frac_bits", type=int, default=16)
    ap.add_argument("--model", type=str, default="3dcnn_tiny")  # nidt: allow[flag-config-cross-cli-drift] -- smoke-scale default; the multiprocess runner ships tiny CPU-safe cells
    ap.add_argument("--num_classes", type=int, default=1)
    ap.add_argument("--dataset", type=str, default="synthetic",
                    choices=("synthetic", "abcd_h5"))  # nidt: allow[flag-config-cross-cli-drift] -- smoke default + the only datasets the socket runner feeds
    ap.add_argument("--data_dir", type=str, default="")  # nidt: allow[flag-config-cross-cli-drift] -- smoke-scale default; the multiprocess runner ships tiny CPU-safe cells
    ap.add_argument("--synthetic_num_subjects", type=int, default=64)  # nidt: allow[flag-config-cross-cli-drift] -- smoke-scale default; the multiprocess runner ships tiny CPU-safe cells
    ap.add_argument("--synthetic_shape", type=int, nargs=3,
                    default=[12, 14, 12])  # nidt: allow[flag-config-cross-cli-drift] -- smoke-scale default; the multiprocess runner ships tiny CPU-safe cells
    ap.add_argument("--synthetic_signal", type=float, default=12.0)
    ap.add_argument("--batch_size", type=int, default=8)  # nidt: allow[flag-config-cross-cli-drift] -- smoke-scale default; the multiprocess runner ships tiny CPU-safe cells
    ap.add_argument("--epochs", type=int, default=1)  # nidt: allow[flag-config-cross-cli-drift] -- smoke-scale default; the multiprocess runner ships tiny CPU-safe cells
    ap.add_argument("--lr", type=float, default=0.01)
    ap.add_argument("--lr_decay", type=float, default=0.998)
    # mixed-precision train step (ISSUE 10) — mirrors the simulated
    # CLI's contract; the wire always carries fp32 master weights
    ap.add_argument("--precision", type=str, default="fp32",
                    choices=("fp32", "bf16_mixed"),
                    help="silo train-step compute dtype; master weights "
                         "(what the wire/codec/secure planes ship) stay "
                         "float32 either way (core/optim.py)")
    ap.add_argument("--loss_scale", type=float, default=1.0,
                    help="fixed loss-scale constant (bf16_mixed only; "
                         "1.0 = off)")
    ap.add_argument("--fused_update", action="store_true",
                    help="fused SGD clip/momentum/update/mask tail "
                         "(ops/fused_update.py; XLA fallback off-TPU)")
    ap.add_argument("--remat", type=str, default="auto",  # nidt: allow[flag-config-cross-cli-drift] -- choices enforced here only; the simulated CLI validates via models/
                    choices=("auto", "none", "stem", "all"),
                    help="3D-model rematerialization policy (auto = "
                         "model-family default; PROFILE.md)")
    ap.add_argument("--seed", type=int, default=1024)
    ap.add_argument("--force_cpu", action="store_true",
                    help="pin JAX to the CPU backend (several silo "
                         "processes on one machine: a chip belongs to "
                         "one process at a time)")
    # observability (obs/, ISSUE 9)
    ap.add_argument("--metrics_port", type=int, default=0,
                    help="serve /metrics (Prometheus text) + /healthz "
                         "on this port for the server rank's metrics "
                         "registry (obs/http.py); 0 = off. NOTE: the "
                         "endpoint is unauthenticated and the metrics "
                         "include control-plane state (per-silo DP "
                         "epsilon, upload verdicts) — bind scope via "
                         "--metrics_host")
    ap.add_argument("--metrics_host", type=str, default="0.0.0.0",
                    help="interface the metrics endpoint binds "
                         "(default all interfaces, the Prometheus-"
                         "exporter convention; pass 127.0.0.1 on "
                         "shared hosts)")
    ap.add_argument("--peak_flops", type=float, default=0.0,
                    help="device peak flop/s for the nidt_mfu gauge's "
                         "denominator on SILO ranks (obs/compute.py; "
                         "0 = device-kind estimate / NIDT_PEAK_FLOPS). "
                         "The server rank's /healthz carries the "
                         "compute block either way — a wedged-dispatch "
                         "silo federation is distinguishable from a "
                         "slow one at the liveness probe")
    ap.add_argument("--trace_out", type=str, default="",
                    help="write this process's host-span timeline as "
                         "Chrome trace-event JSON (obs/trace.py, "
                         "Perfetto-loadable) at exit; give each rank "
                         "its own path. Under --ingest_workers N the "
                         "BARE path is the MERGED federation trace "
                         "(root + clock-aligned worker timelines + "
                         "upload flow links, obs/fanin.py) — the "
                         "primary artifact; worker processes write "
                         ".wN-suffixed local secondaries instead of "
                         "clobbering one file")
    ap.add_argument("--flight_events", type=int, default=256,
                    help="flight-recorder ring capacity (obs/flight.py) "
                         "— the last N control-plane decisions kept for "
                         "the post-mortem dump")
    ap.add_argument("--flight_out", type=str, default="",
                    help="flight-recorder dump path: written at end of "
                         "run on the server rank and on any fatal "
                         "failure (failure_context); empty = dumps off")
    # training-health plane (obs/rules.py, ISSUE 15) — server rank only
    ap.add_argument("--health_rules", type=str, default="",
                    help="JSON anomaly-rule manifest extending the "
                         "built-in set (obs/rules.py) on the SERVER "
                         "rank; unknown metric names fail at startup "
                         "against the declared-name list (obs/names.py)")
    ap.add_argument("--health_gate", action="store_true",
                    help="server rank exits nonzero when the run's "
                         "WORST health status was not ok (any anomaly "
                         "rule fired); the machine-readable verdict "
                         "rides the end-of-run JSON either way")
    ap.add_argument("--dp_epsilon_budget", type=float, default=0.0,
                    help="epsilon budget the built-in DP health rules "
                         "judge against (dp-budget-exceeded / "
                         "dp-burn-rate); 0 = no budget rules")
    ap.add_argument("--actions", type=str, default="dry_run",
                    choices=("off", "dry_run", "on"),
                    help="reflex plane (obs/actions.py, ISSUE 20) on "
                         "the SERVER rank: what a firing health rule's "
                         "declared action DOES. off = rules only "
                         "observe; dry_run (default) = would-fire "
                         "dispatches are logged/flight-recorded with "
                         "rule provenance but nothing changes; on = "
                         "actions apply (quarantine the struck silo "
                         "through the strike machinery, escalate the "
                         "defense ladder, halve the async buffer_k and "
                         "raise staleness_alpha)")
    ap.add_argument("--client_mesh", type=int, default=0,
                    help="accepted for config parity with the main CLI; "
                         "each cross-silo rank trains only its own silo, "
                         "so there is no in-process client axis to shard "
                         "(cohort sharding lives in the simulated "
                         "engines, parallel/cohort.py)")
    ap.add_argument("--recipe", type=str, default="",
                    help="autotuner recipe (tune/recipe.py): a "
                         "bench_matrix/recipes/<device_kind>.json path, "
                         "or 'auto' for the committed recipe matching "
                         "this rank's device kind. Applies as config "
                         "DEFAULTS (flags spelled here win, override "
                         "logged); the server rank arms the "
                         "mfu-below-recipe drift rule")
    args = ap.parse_args(argv)
    if args.force_cpu:
        # provision BEFORE any backend touch: --recipe auto resolves
        # the live device kind through jax.devices()
        from neuroimagedisttraining_tpu.parallel.mesh import (
            provision_virtual_devices,
        )
        provision_virtual_devices(1)
    recipe_doc = None
    if args.recipe:
        from neuroimagedisttraining_tpu.tune import recipe as tune_recipe

        try:
            recipe_doc = tune_recipe.resolve_and_load(args.recipe)
            tune_recipe.apply_recipe(
                args, recipe_doc,
                argv if argv is not None else sys.argv[1:])
        except (OSError, ValueError) as e:
            ap.error(f"--recipe: {e}")
    if args.dp_epsilon_budget < 0:
        ap.error(f"--dp_epsilon_budget must be >= 0 (got "
                 f"{args.dp_epsilon_budget})")
    if args.health_rules:
        # manifest errors (bad JSON, unknown metric names, bad
        # comparators) die at argparse on every rank that was handed
        # the flag — never as a silently-never-firing rule mid-run
        from neuroimagedisttraining_tpu.obs import names as obs_names
        from neuroimagedisttraining_tpu.obs import rules as obs_rules

        try:
            for r in obs_rules.load_rules(args.health_rules):
                r.validate(obs_names.DECLARED)
        except (OSError, ValueError, TypeError) as e:
            ap.error(f"--health_rules: {e}")
    if args.peak_flops > 0:
        # arm the MFU denominator on every rank (silo ranks dispatch
        # the training programs; the server rank's /healthz compute
        # block reports its own dispatch liveness either way)
        from neuroimagedisttraining_tpu.obs import compute as obs_compute

        obs_compute.PROFILER.set_peak_flops(args.peak_flops)
    quant_spec = None
    if args.secure_quant:
        args.secure = True  # the quantized path IS the secure protocol
        from neuroimagedisttraining_tpu.privacy import (
            QuantSpec, check_headroom,
        )

        try:
            # field-geometry headroom (aggregate range vs p, int64
            # accumulators vs the cohort) fails HERE, at argparse on
            # every rank — never as silent field wraparound mid-round
            quant_spec = QuantSpec.from_bits(
                args.secure_quant_field_bits,
                args.secure_quant_frac_bits, args.mpc_n_shares)
            check_headroom(quant_spec, args.num_clients)
        except ValueError as e:
            ap.error(str(e))
    if args.client_mesh > 0:
        print(f"[cohort] {cohort_fallback_note(args.client_mesh)}",
              flush=True)
    if args.role == "aggregator":
        if args.n_aggregators <= 0:
            ap.error("--role aggregator requires --n_aggregators > 0 "
                     "(same value on every rank)")
        if not 0 <= args.slot_index < args.n_aggregators:
            ap.error(f"--slot_index ({args.slot_index}) must be in "
                     f"[0, {args.n_aggregators})")
    if args.n_aggregators > 0:
        # fail fast on EVERY rank: mismatched flags would otherwise leave
        # aggregator processes blocked forever (no slot, no FINISH)
        if not args.secure:
            ap.error("--n_aggregators requires --secure")
        if args.n_aggregators != args.mpc_n_shares:
            ap.error(f"--n_aggregators ({args.n_aggregators}) must equal "
                     f"--mpc_n_shares ({args.mpc_n_shares}): slot j "
                     "routes to aggregator j")
    if args.transport == "broker" and args.n_aggregators > 0:
        ap.error("--transport broker routes messages through the MQTT "
                 "topic scheme (server <-> client only); the grouped "
                 "multi-aggregator deployment needs --transport socket")
    if args.secure and (args.wire_codec != "none"
                        or args.wire_mask_density > 0):
        ap.error("--secure uploads must ride the wire as field elements: "
                 "the codec would break the GF(p) share algebra or leak "
                 "mask support. The COMPRESSED secure wire is "
                 "--secure_quant (small-field frames, "
                 "privacy/secure_quant.py) — drop --wire_codec/"
                 "--wire_mask_density and add --secure_quant")
    if args.secure_quant and args.n_aggregators > 0:
        ap.error("--secure_quant does not compose with --n_aggregators: "
                 "mask slots ride as PRG seeds, and any node holding a "
                 "client's seeds can expand every non-data slot — use "
                 "the dense --secure protocol for the grouped "
                 "deployment (see ARCHITECTURE.md 'Privacy plane')")
    if not 0.0 <= args.wire_mask_density < 1.0:
        ap.error(f"--wire_mask_density ({args.wire_mask_density}) must "
                 "be in [0, 1)")
    try:
        # fail fast on EVERY rank: only clients parse the spec at
        # runtime, and a typo'd spec crashing the clients would leave
        # the server blocked forever in the registration barrier
        from neuroimagedisttraining_tpu.codec import parse_wire_spec

        parse_wire_spec(args.wire_codec, args.wire_topk_ratio)
    except ValueError as e:
        ap.error(str(e))
    # Byzantine config (ISSUE 5) fails fast on EVERY rank too: a typo'd
    # --defense or byz: directive must die at startup, not mid-round
    try:
        from neuroimagedisttraining_tpu.core import robust
        from neuroimagedisttraining_tpu.faults import parse_fault_spec

        robust.validate_defense(args.defense)
        if args.defense in robust.ROBUST_AGGREGATORS:
            robust._check_f(args.num_clients, args.byz_f, args.defense)
        fault_spec = (parse_fault_spec(args.fault_spec)
                      if args.fault_spec else None)
    except ValueError as e:
        ap.error(str(e))
    if fault_spec is not None and fault_spec.rejoins:
        # fail at startup, not silently mid-run: the chaos wrapper
        # models a crash by latching and stopping the client PROCESS's
        # dispatch — nothing remains to revive at the rejoin round
        ap.error("--fault_spec rejoin: is not supported by the "
                 "multiprocess runner (a crashed client process cannot "
                 "revive itself; FaultyCommManager latches the crash). "
                 "Model rejoin by launching a replacement client "
                 "process (the server's late re-register path), or use "
                 "the asyncfl load harness (asyncfl/loadgen.py) whose "
                 "simulated clients honor rejoin deterministically")
    if args.secure:
        if args.quarantine_rounds > 0:
            ap.error("secure aggregation is incompatible with "
                     "--quarantine_rounds: the outlier scorer has no "
                     "per-silo plaintext to score (see ARCHITECTURE.md "
                     "'Privacy plane')")
        if args.defense != "none" and not args.secure_quant:
            ap.error("--secure (dense) is incompatible with --defense: "
                     "additive-share aggregation never reveals per-silo "
                     "updates to defend over. The clip-family defenses "
                     "(norm_diff_clipping, weak_dp) compose with "
                     "--secure_quant, enforced CLIENT-side pre-share — "
                     "add --secure_quant (see ARCHITECTURE.md 'Privacy "
                     "plane')")
        if args.secure_quant and args.defense in robust.ROBUST_AGGREGATORS:
            ap.error(f"--defense {args.defense} is incompatible with "
                     "secure aggregation (quantized included): order "
                     "statistics have no per-silo plaintext to select "
                     "over; only the clip family composes (client-side) "
                     "— see ARCHITECTURE.md 'Privacy plane'")
        if fault_spec is not None and fault_spec.any_value_faults:
            ap.error("--secure cannot simulate byz: value faults (the "
                     "share algebra hides the very values the attack "
                     "would corrupt; see cross_silo)")
    if args.async_server:
        # async incompatibilities fail at STARTUP on every rank, like
        # the secure/codec rejections — never mid-run
        if args.secure and not args.secure_quant:
            ap.error("--async_server is incompatible with dense "
                     "--secure: the two-phase secure weight exchange "
                     "(every client's normalized weight depends on every "
                     "other phase-A reporter) IS a round barrier — "
                     "exactly what the buffered asynchronous protocol "
                     "removes. --secure_quant composes: its one-phase "
                     "frames need no weight exchange (staleness weights "
                     "fold inside the field; see asyncfl/server.py)")
        if args.transport == "broker":
            ap.error("--async_server pairs with the selector socket "
                     "core (asyncfl/loop.py); the broker daemon is a "
                     "thread-per-connection transport with its own "
                     "scaling story — use --transport socket")
        if args.round_deadline > 0 or args.quorum > 0:
            ap.error("--async_server has no round barrier: "
                     "--round_deadline/--quorum do not apply (uploads "
                     "aggregate every --buffer_k arrivals; staleness is "
                     "bounded by --max_staleness instead)")
        if args.buffer_k < 0 or args.max_staleness < 0 \
                or args.staleness_alpha < 0:
            ap.error("--buffer_k/--max_staleness/--staleness_alpha "
                     "must be >= 0")
        if quant_spec is not None:
            from neuroimagedisttraining_tpu.privacy import secure_quant \
                as _sq

            k_cap = min(args.buffer_k or args.num_clients,
                        args.num_clients)
            if _sq.weighted_fold_capacity(quant_spec) <= k_cap:
                ap.error(
                    "--async_server --secure_quant folds integer-scaled "
                    "staleness weights inside the field, which needs "
                    "headroom the "
                    f"{args.secure_quant_field_bits}-bit field lacks "
                    f"for a {k_cap}-upload buffer — pass "
                    "--secure_quant_field_bits 32")
    if args.ingest_workers:
        if args.ingest_workers < 0:
            ap.error("--ingest_workers must be >= 0")
        if not args.async_server:
            ap.error("--ingest_workers shards the ASYNC ingest plane "
                     "(asyncfl/ingest.py) — add --async_server")
        if args.defense != "none" or args.quarantine_rounds:
            ap.error("--ingest_workers supports neither server-side "
                     "defenses nor quarantine: workers fold uploads "
                     "into partial aggregates, so the root never sees "
                     "per-client updates to select over or score "
                     "(matrix precedent: the buffered secure path). "
                     "Use the single-process plane (--ingest_workers 0) "
                     "or client-side clipping")
    if args.regions:
        if args.regions < 0:
            ap.error("--regions must be >= 0")
        if not args.ingest_workers:
            ap.error("--regions interposes regional sub-aggregators in "
                     "the SHARDED ingest plane — pass --ingest_workers "
                     "N (workers per region) too")
    if (args.ingest_shm or args.sync_delta) and not args.ingest_workers:
        ap.error("--ingest_shm/--sync_delta are sharded-ingest-plane "
                 "transports (asyncfl/ingest.py) — add "
                 "--ingest_workers N")
    if args.round_deadline > 0 and args.quorum == 0:
        args.quorum = args.num_clients // 2 + 1  # simple majority
    if args.heartbeat_timeout > 0 and not (
            0 < args.heartbeat_interval < args.heartbeat_timeout):
        # beats slower than the timeout would mark every HEALTHY client
        # suspect mid-round and silently truncate aggregates
        ap.error("--heartbeat_timeout requires 0 < --heartbeat_interval "
                 f"< timeout (got interval={args.heartbeat_interval}, "
                 f"timeout={args.heartbeat_timeout})")
    from neuroimagedisttraining_tpu.utils.compile_cache import (
        enable_compile_cache,
    )
    enable_compile_cache()
    # observability plane (obs/, ISSUE 9): flight ring + span tracer are
    # per-process; the /metrics endpoint starts on the server rank below
    from neuroimagedisttraining_tpu.obs import flight as obs_flight
    from neuroimagedisttraining_tpu.obs import trace as obs_trace

    # the dump PATH arms on the server rank only: silo ranks record into
    # their own rings (on a fatal failure failure_context logs the
    # ring's tail when no dump path is set), but a crashing silo
    # sharing one --flight_out arg list must never clobber the server's
    # post-mortem file
    obs_flight.configure(capacity=args.flight_events,
                         path=args.flight_out
                         if args.role == "server" else "")
    if args.trace_out:
        obs_trace.arm(args.trace_out,
                      tags={"role": args.role, "rank": args.rank})
    host_map = _parse_hosts(args.hosts)
    # (--force_cpu provisioning happens right after parse_args: the
    # --recipe auto resolution touches the backend)

    from neuroimagedisttraining_tpu.distributed.cross_silo import (
        FedAvgClientProc, FedAvgServer, SecureFedAvgClientProc,
        SecureFedAvgServer, SlotAggregatorProc,
    )

    if args.role == "aggregator":
        agg = SlotAggregatorProc(args.slot_index, args.num_clients,
                                 args.n_aggregators,
                                 base_port=args.base_port,
                                 host_map=host_map)
        print(f"[aggregator {args.slot_index}] rank {agg.rank} "
              f"aggregating slot {args.slot_index}", flush=True)
        agg.run()
        print(json.dumps({"role": "aggregator",
                          "slot_index": args.slot_index,
                          "clients_seen": len(agg.received)}), flush=True)
        return 0

    if args.role == "server":
        import jax

        # seed-deterministic init: every process derives the same model;
        # the wire mask (when configured) derives from the SAME state —
        # one model build, one jitted init
        _, gs = _seed_init_state(args)
        wire_masks = (_build_wire_masks(args, gs)
                      if args.wire_mask_density > 0 else None)
        init = {"params": jax.tree.map(np.asarray, gs.params),
                "batch_stats": jax.tree.map(np.asarray, gs.batch_stats)}
        cls = SecureFedAvgServer if args.secure else FedAvgServer
        if args.secure:
            kw = {"frac_bits": args.mpc_frac_bits,
                  "n_aggregators": args.n_aggregators,
                  "quant_spec": quant_spec}
            if args.secure_quant and args.defense != "none":
                # clip-family defense under secure_quant is enforced
                # CLIENT-side; the server keeps the geometry so the
                # weak_dp accountant can charge the ledger it reports
                kw.update(defense=args.defense,
                          norm_bound=args.norm_bound,
                          stddev=args.stddev, defense_seed=args.seed,
                          dp_delta=args.dp_delta)
        else:
            kw = {"wire_masks": wire_masks,
                  "defense": args.defense, "byz_f": args.byz_f,
                  "geomed_iters": args.geomed_iters,
                  "norm_bound": args.norm_bound,
                  "stddev": args.stddev, "defense_seed": args.seed,
                  "quarantine_rounds": args.quarantine_rounds,
                  "outlier_threshold": args.outlier_threshold,
                  "dp_delta": args.dp_delta}
        if args.async_server:
            from neuroimagedisttraining_tpu.asyncfl import (
                BufferedFedAvgServer,
            )

            if args.secure_quant:
                # the buffered server speaks one-phase secure_quant
                # natively; the dense-secure kw set does not apply
                kw = {"secure_quant": quant_spec,
                      "defense": args.defense,
                      "norm_bound": args.norm_bound,
                      "stddev": args.stddev, "defense_seed": args.seed,
                      "dp_delta": args.dp_delta}
            if args.ingest_workers:
                ikw = dict(
                    buffer_k=args.buffer_k,
                    staleness_alpha=args.staleness_alpha,
                    max_staleness=args.max_staleness,
                    base_port=args.base_port, host_map=host_map,
                    heartbeat_timeout=args.heartbeat_timeout,
                    trace_out=args.trace_out,
                    flight_out=args.flight_out,
                    use_shm=args.ingest_shm,
                    sync_delta=args.sync_delta, **kw)
                if args.regions:
                    from neuroimagedisttraining_tpu.asyncfl.region import (
                        HierarchicalIngestServer,
                    )

                    server = HierarchicalIngestServer(
                        init, args.comm_round, args.num_clients,
                        regions=args.regions,
                        workers_per_region=args.ingest_workers, **ikw)
                    topo = (f"{args.regions} regions x "
                            f"{args.ingest_workers} workers "
                            f"(hierarchical tier)")
                else:
                    from neuroimagedisttraining_tpu.asyncfl.ingest import (
                        ShardedIngestServer,
                    )

                    server = ShardedIngestServer(
                        init, args.comm_round, args.num_clients,
                        ingest_workers=args.ingest_workers, **ikw)
                    topo = (f"{args.ingest_workers} selector workers")
                print(f"[server] sharded ingest plane on port "
                      f"{args.base_port}: {topo} (SO_REUSEPORT), "
                      f"buffer_k={server.buffer_k}, staleness_alpha="
                      f"{args.staleness_alpha}, max_staleness="
                      f"{args.max_staleness}"
                      + (", shm partial hand-off" if args.ingest_shm
                         else "")
                      + (", delta sync" if args.sync_delta else ""),
                      flush=True)
            else:
                server = BufferedFedAvgServer(
                    init, args.comm_round, args.num_clients,
                    buffer_k=args.buffer_k,
                    staleness_alpha=args.staleness_alpha,
                    max_staleness=args.max_staleness,
                    base_port=args.base_port, host_map=host_map,
                    heartbeat_timeout=args.heartbeat_timeout, **kw)
                print(f"[server] asyncfl selector control plane on "
                      f"port {args.base_port}; buffer_k="
                      f"{server.buffer_k}, staleness_alpha="
                      f"{args.staleness_alpha}, max_staleness="
                      f"{args.max_staleness}", flush=True)
            broker = None
        else:
            comm, broker = _make_comm(args, 0, host_map)
            server = cls(init, args.comm_round, args.num_clients,
                         base_port=args.base_port, host_map=host_map,
                         comm=comm, round_deadline=args.round_deadline,
                         quorum=args.quorum,
                         heartbeat_timeout=args.heartbeat_timeout, **kw)
            print(f"[server] {args.transport} control plane on port "
                  f"{args.broker_port or args.base_port}; waiting for "
                  f"{args.num_clients} silos", flush=True)
        from neuroimagedisttraining_tpu.obs.http import (
            start_metrics_server,
        )
        from neuroimagedisttraining_tpu.utils.profiling import (
            failure_context,
        )

        # anomaly-rule engine on the server rank (obs/rules.py, ISSUE
        # 15): built-ins parameterized by this federation's knobs +
        # the --health_rules manifest; evaluated at every version
        # advance (asyncfl) and at each liveness probe, reported in
        # /healthz and gated at exit
        from neuroimagedisttraining_tpu.obs import health as obs_health
        from neuroimagedisttraining_tpu.obs import rules as obs_rules

        extra_rules = ()
        if recipe_doc is not None:
            from neuroimagedisttraining_tpu.tune import (
                recipe as tune_recipe,
            )
            extra_rules = tune_recipe.drift_rules(recipe_doc)
        hrules = obs_rules.configure(
            manifest_path=args.health_rules,
            dp_epsilon_budget=args.dp_epsilon_budget,
            comm_round=args.comm_round,
            max_staleness=args.max_staleness,
            extra_rules=extra_rules)
        # reflex plane (obs/actions.py, ISSUE 20): the control plane's
        # realizations of the reflex actions, registered on the LOCAL
        # bus handle (disarm precedes the result-JSON write, exactly
        # like ``hrules``). freeze_rollback/shrink_mesh have no
        # control-plane realization — a rule binding them here logs an
        # honest 'unhandled' dispatch instead of silently vanishing.
        from neuroimagedisttraining_tpu.obs import (
            actions as obs_actions,
        )

        bus = obs_actions.configure(args.actions)

        # LOCKING: rules evaluate (and therefore dispatch actions)
        # synchronously at the servers' own boundaries — cross_silo's
        # round completion and asyncfl's version advance — which run
        # UNDER ``server._rlock`` (a non-reentrant Lock). The handlers
        # below therefore never acquire it: they execute on the thread
        # that already holds it, so every mutation is serialized with
        # the aggregation state they touch. (The end-of-run boundary
        # evaluation happens after the control plane quiesced.)
        def _act_quarantine(*, rule, round_idx, value=None):
            # ride the PR 5 strike machinery's state: quarantine the
            # most-struck non-quarantined silo, same byz_f budget and
            # post-window ARG_EF_RESET debt the strike path keeps
            cand = {c: n for c, n in server._strikes.items()
                    if n > 0 and c not in server._quarantined_now()}
            if not cand:
                return {"status": "skipped",
                        "reason": "no struck silo to attribute the "
                                  "alert to"}
            if len(server._quarantined_now()) >= max(1, server.byz_f):
                return {"status": "skipped",
                        "reason": f"quarantine budget (byz_f="
                                  f"{server.byz_f}) spent"}
            c = max(cand, key=lambda k: (cand[k], -k))
            until = (server.round_idx + 1
                     + max(1, server.quarantine_rounds))
            server._quarantine_until[c] = until
            server._strikes[c] = 0
            server._ef_reset_pending.add(c)
            server.byz_stats["quarantines"].append(
                {"client": c, "from_round": server.round_idx + 1,
                 "until_round": until})
            return {"client": c, "from_round": server.round_idx + 1,
                    "until": until, "strikes": cand[c]}

        def _act_escalate(*, rule, round_idx, value=None):
            from neuroimagedisttraining_tpu.core import robust
            ladder = ("none", "norm_diff_clipping", "trimmed_mean")
            if args.secure or args.secure_quant:
                return {"status": "skipped",
                        "reason": "secure planes clip client-side; no "
                                  "server defend tail to escalate"}
            cur = server.defense
            if cur not in ladder:
                return {"status": "skipped",
                        "reason": f"operator defense {cur!r} is "
                                  "outside the escalation ladder"}
            if cur == ladder[-1]:
                return {"status": "skipped",
                        "reason": f"already at the top rung {cur!r}"}
            nxt = ladder[ladder.index(cur) + 1]
            if nxt in robust.ROBUST_AGGREGATORS:
                try:
                    robust._check_f(args.num_clients, server.byz_f,
                                    nxt)
                except ValueError as e:
                    return {"status": "skipped", "reason": str(e)}
            server.defense = nxt
            return {"from": cur, "to": nxt}

        bus.register("quarantine_silo", _act_quarantine)
        bus.register("escalate_defense", _act_escalate)
        if args.async_server:
            def _act_adapt_buffer(*, rule, round_idx, value=None):
                # staleness runaway => aggregate more eagerly (halve
                # the trigger) and discount stale arrivals harder
                old_k = server.buffer_k
                old_a = server.staleness_alpha
                new_k = max(1, (old_k + 1) // 2)
                new_a = min(old_a + 0.25, 2.0)
                if new_k == old_k and new_a == old_a:
                    return {"status": "skipped",
                            "reason": "buffer_k at its floor and "
                                      "staleness_alpha at its cap"}
                server.buffer_k = new_k
                server.staleness_alpha = new_a
                return {"buffer_k": [old_k, new_k],
                        "staleness_alpha": [old_a, new_a]}

            bus.register("adapt_buffer", _act_adapt_buffer)

        def _health() -> dict:
            # scrape-thread probe with a BOUNDED lock wait: _rlock is
            # held across whole aggregations (first-round XLA compile
            # included), and a k8s-style liveness probe with a 1-2s
            # timeout must never conclude "dead" because the server is
            # busy doing its job — a timed-out acquire reports busy,
            # which IS a liveness signal
            from neuroimagedisttraining_tpu.obs import (
                compute as obs_compute,
            )

            if not server._rlock.acquire(timeout=0.2):
                # the compute block rides even the busy report: its
                # profiler state is lock-free w.r.t. _rlock, and a
                # wedged dispatch is exactly when the probe matters
                return {"busy": True,
                        "compute": obs_compute.PROFILER.health(),
                        "health": obs_rules.health_block(),
                        # action log is bus-internal state, lock-free
                        # w.r.t. _rlock — it rides the busy report too
                        "actions": bus.actions_block()}
            try:
                # rules evaluate once per completed round at the
                # servers' own boundaries (cross_silo round completion /
                # asyncfl version advance); the probe only REPORTS
                h = {"round": int(server.round_idx),
                     "registered": len(server._registered),
                     "suspects": len(server._suspect),
                     # compute block (ISSUE 14): last dispatch age /
                     # MFU sample / recompile count — distinguishes a
                     # WEDGED-dispatch federation (age grows, counts
                     # stall) from a slow one at the liveness probe
                     "compute": obs_compute.PROFILER.health(),
                     # fast-path coverage (ISSUE 15 satellite): the
                     # fallback totals next to the compute block — a
                     # silently-degraded run reads differently from a
                     # healthy one right at the probe
                     "fallbacks": obs_health.fallback_block(
                         server.fanin.merged_snapshot()
                         if args.ingest_workers else None),
                     "health": obs_rules.health_block(),
                     # the last reflex dispatches, rule provenance
                     # included (ISSUE 20)
                     "actions": bus.actions_block()}
                if args.async_server:
                    h["buffered"] = (server._pending()
                                     if args.ingest_workers
                                     else len(server._buffer))
            finally:
                server._rlock.release()
            return h

        msrv = start_metrics_server(args.metrics_port,
                                    health_probe=_health,
                                    # sharded plane: serve the MERGED
                                    # view — root samples + worker-
                                    # labeled samples + snapshot-
                                    # staleness gauges (obs/fanin.py)
                                    registry=(server.metrics_view()
                                              if args.ingest_workers
                                              else None),
                                    host=args.metrics_host)
        if msrv is not None:
            print(f"[server] obs: /metrics + /healthz on port "
                  f"{msrv.port}"
                  + (" (merged across ingest workers)"
                     if args.ingest_workers else ""), flush=True)
        clean_exit = False
        try:
            # failure_context dumps the flight ring before re-raising —
            # a chaos run that dies leaves its post-mortem
            with failure_context(name="cross-silo server"):
                server.run()
            clean_exit = True
        finally:
            if args.ingest_workers:
                # the sharded root writes the MERGED artifacts at the
                # bare paths itself (ShardedIngestServer.dump_obs,
                # idempotent) — the per-process dumps below would
                # clobber them with root-only views
                pass
            else:
                if args.flight_out and clean_exit:
                    # on failure the failure_context dump IS the
                    # artifact — re-dumping here would relabel the
                    # crash post-mortem as a normal end of run
                    obs_flight.dump(reason="end of run")
                if args.trace_out:
                    obs_trace.dump()
            if msrv is not None:
                msrv.close()
            if not clean_exit:
                # crash path: the rule engine's lifetime is the run's
                # (the success path disarms after the final boundary
                # evaluation below)
                obs_rules.disarm()
                obs_actions.disarm()
        if broker is not None:
            broker.stop()
        norm = float(np.sqrt(sum(
            float(np.sum(np.asarray(v, np.float64) ** 2))
            for v in jax.tree.leaves(server.params))))
        stats = server.com_manager.byte_stats()
        extra = {}
        if args.async_server:
            extra = {"async_server": True,
                     # live server values, not the flags: adapt_buffer
                     # (ISSUE 20) may have changed them mid-run
                     "buffer_k": server.buffer_k,
                     "staleness_alpha": server.staleness_alpha,
                     "max_staleness": args.max_staleness,
                     "upload_audit": server.upload_audit(),
                     "staleness_taus": sorted({
                         t for h in server.history
                         for t in h.get("taus", ())})}
            if args.ingest_workers:
                extra["ingest_workers"] = args.ingest_workers
                if args.regions:
                    extra["regions"] = args.regions
                if args.ingest_shm or args.sync_delta:
                    extra["worker_xstats"] = server.worker_xstats()
                # workers own the client sockets: the wire accounting
                # lives with them, not the root's placeholder comm
                stats = server.worker_byte_stats()
        dp = server.dp_report()
        if dp is not None:
            # run-end privacy audit: per-silo (epsilon, delta) from the
            # weak_dp RDP ledger (privacy/accountant.py)
            extra["dp"] = dp
        # end-of-run health verdict (ISSUE 15): one final boundary
        # evaluation at the last completed version, then the
        # machine-readable verdict rides the result JSON (run_report
        # joins it); --health_gate turns a non-ok WORST status into a
        # nonzero exit
        if args.async_server:
            server._observe_health_boundary()
        else:
            obs_rules.observe_boundary(int(server.round_idx))
        health_verdict = hrules.verdict()
        obs_rules.disarm()
        obs_actions.disarm()  # local ``bus`` handle still readable
        extra["health"] = {
            k: health_verdict[k]
            for k in ("status", "worst_status", "alerts_total",
                      "rounds_evaluated")}
        extra["health_timeline"] = health_verdict["timeline"]
        # the reflex action log (timestamp-free: twin seeded chaos runs
        # produce byte-identical blocks) rides the result JSON
        extra["actions"] = bus.actions_block()
        print(json.dumps({"rounds_completed": len(server.history),
                          "clients": args.num_clients,
                          "secure": bool(args.secure),
                          "secure_quant": bool(args.secure_quant),
                          "transport": args.transport,
                          "wire_codec": args.wire_codec,
                          "wire_mask_density": args.wire_mask_density,
                          "suspects": sorted(server.suspect_clients()),
                          "defense": getattr(server, "defense", "none"),
                          "quarantined": sorted(
                              server.quarantined_clients()),
                          "byz_stats": server.byz_stats,
                          "final_param_norm": round(norm, 6),
                          **extra, **stats}), flush=True)
        if args.health_gate and health_verdict["worst_status"] != "ok":
            # stderr: the last stdout line stays the result JSON the
            # bench/smoke scripts parse
            print(f"[health] gate FAILED: worst status "
                  f"{health_verdict['worst_status']!r} "
                  f"({health_verdict['alerts_total']} alert(s))",
                  file=sys.stderr, flush=True)
            return 1
        return 0

    train_fn, wire_masks = _make_train_fn(args)
    cls = SecureFedAvgClientProc if args.secure else FedAvgClientProc
    if args.secure:
        kw = {"n_shares": args.mpc_n_shares,
              "frac_bits": args.mpc_frac_bits, "mpc_seed": args.seed,
              "n_aggregators": args.n_aggregators,
              "quant_spec": quant_spec,
              # async buffered plane: one-phase frames (no weight
              # exchange); clip-family defenses are enforced HERE, on
              # this silo's own update, pre-share
              "one_phase": bool(args.async_server)}
        if args.secure_quant and args.defense != "none":
            kw.update(defense=args.defense, norm_bound=args.norm_bound,
                      stddev=args.stddev, defense_seed=args.seed)
    else:
        kw = {"wire_codec": args.wire_codec,
              "wire_masks": wire_masks,
              "wire_topk_ratio": args.wire_topk_ratio,
              "sync_delta": args.sync_delta}
    if not args.secure and fault_spec is not None \
            and fault_spec.any_value_faults:
        # value faults live in the CLIENT, not the transport wrapper:
        # the silo attacks its own upload (faults/adversary.py) before
        # any encoding, keyed by the shared (seed, round, rank) schedule
        from neuroimagedisttraining_tpu.faults import FaultSchedule
        kw["fault_schedule"] = FaultSchedule(fault_spec, args.seed)
        kw["seed"] = args.seed
    comm, _ = _make_comm(args, args.rank, host_map)
    client = cls(args.rank, args.num_clients, train_fn,
                 base_port=args.base_port, host_map=host_map, comm=comm,
                 heartbeat_interval=args.heartbeat_interval, **kw)
    print(f"[silo {args.rank}] joining server", flush=True)
    from neuroimagedisttraining_tpu.utils.profiling import failure_context

    try:
        with failure_context(name=f"silo {args.rank}"):
            client.run()
    finally:
        if args.trace_out:
            obs_trace.dump()
    return 0


if __name__ == "__main__":
    sys.exit(main())
