"""Experiment harness: ``python -m neuroimagedisttraining_tpu ...``.

Replaces the reference's per-algorithm ``main_<algo>.py`` entry points
(fedml_experiments/standalone/sailentgrads/main_sailentgrads.py:31-281)
with ONE CLI: ``--algorithm`` selects the engine, the flag surface keeps
the reference's names and defaults (add_args, main_sailentgrads.py:31-127;
Ditto lamda/local_epochs main_ditto.py:79,101; SubAvg
each_prune_ratio/dist_thresh/acc_thresh main_subavg.py:105-108), and the
run follows the reference harness contract: deterministic seeding
(main_sailentgrads.py:264-268), experiment-identity string, file logging
under ``LOG/<dataset>/`` (main_sailentgrads.py:184-192), then
``engine.train()``.

Example (fast smoke):
    python -m neuroimagedisttraining_tpu --algorithm fedavg \
        --dataset synthetic --model 3dcnn_tiny --synthetic_num_subjects 32 \
        --synthetic_shape 12 14 12 --client_num_in_total 4 --comm_round 2 \
        --batch_size 4 --epochs 1
"""

from __future__ import annotations

import argparse
import json
import random
import sys

import numpy as np

from neuroimagedisttraining_tpu.config import (
    DataConfig, ExperimentConfig, FedConfig, OptimConfig, SparsityConfig,
)


def add_args(parser: argparse.ArgumentParser) -> argparse.ArgumentParser:
    # reference flag surface (main_sailentgrads.py:31-127)
    parser.add_argument("--algorithm", type=str, default="fedavg",
                        help="fedavg | fedprox | salientgrads | dispfl | "
                             "subavg | fedfomo | dpsgd | ditto | local | "
                             "turboaggregate")
    parser.add_argument("--model", type=str, default="3DCNN")
    parser.add_argument("--dataset", type=str, default="ABCD",
                        help="ABCD | abcd_h5 | synthetic | cifar10 | "
                             "cifar100 | tiny")
    parser.add_argument("--data_dir", type=str, default="./data",
                        help="for ABCD/abcd_h5: path to the X/y/site HDF5")
    parser.add_argument("--partition_method", type=str, default="site",
                        help="site | dir | n_cls | my_part | homo | hetero "
                             "| rescale")
    parser.add_argument("--partition_alpha", type=float, default=0.3)
    parser.add_argument("--batch_size", type=int, default=16)
    parser.add_argument("--client_optimizer", type=str, default="sgd")
    parser.add_argument("--lr", type=float, default=0.01)
    parser.add_argument("--lr_decay", type=float, default=0.998)
    parser.add_argument("--wd", type=float, default=5e-4)
    parser.add_argument("--momentum", type=float, default=0.9)
    parser.add_argument("--grad_clip", type=float, default=10.0,
                        help="global-norm gradient clip (<= 0 disables); "
                             "torch clip_grad_norm_ parity")
    parser.add_argument("--epochs", type=int, default=2)
    parser.add_argument("--batch_order", type=str, default="shuffle",
                        choices=["shuffle", "replacement"],
                        help="minibatch selection: per-epoch shuffled "
                             "strides (reference DataLoader semantics) or "
                             "i.i.d. draws with replacement")
    parser.add_argument("--client_num_in_total", type=int, default=21)
    parser.add_argument("--frac", type=float, default=1.0)
    parser.add_argument("--comm_round", type=int, default=200)
    parser.add_argument("--frequency_of_the_test", type=int, default=1)
    parser.add_argument("--ci", type=int, default=0)
    parser.add_argument("--seed", type=int, default=1024)
    parser.add_argument("--seed_split", type=int, default=42,
                        help="per-site 80/20 train/val split seed "
                             "(independent of --seed so reshuffling "
                             "training noise keeps the split fixed)")
    parser.add_argument("--cs", type=str, default="random")
    parser.add_argument("--neighbor_num", type=int, default=5,
                        help="gossip fan-out when --cs random")
    parser.add_argument("--active", type=float, default=1.0)
    parser.add_argument("--fault_spec", type=str, default="",
                        help="deterministic fault schedule (faults/): "
                             "'crash:RANK@ROUND,crash_prob:P,"
                             "straggle:P:MAX_S,drop:P,dup:P,disconnect:P,"
                             "byz:RANK@ROUND:KIND,byz_prob:P[:KIND]' "
                             "— crashed clients leave the sampled cohort "
                             "(survivor-reweighted rounds); byz clients "
                             "upload KIND-corrupted values (sign_flip | "
                             "scale:K | gauss:STD | nonfinite, "
                             "faults/adversary.py); the same seed "
                             "drives the multiprocess federation")
    parser.add_argument("--wire_codec", type=str, default="none",
                        help="model-update wire codec (codec/): '+'-"
                             "joined stages from {delta, sparse, quant, "
                             "quant16}, e.g. delta+sparse+quant; the "
                             "simulated round applies the codec's lossy "
                             "transform to client updates before "
                             "aggregation (jitted) and accounts encoded "
                             "vs dense bytes in stat_info — parity with "
                             "what distributed.run ships on real "
                             "sockets")
    parser.add_argument("--wire_topk_ratio", type=float, default=0.25,
                        help="wire codec sparse stage for dense engines: "
                             "magnitude top-k keep fraction (per-client "
                             "error feedback re-injects dropped mass "
                             "next round); masked engines use their own "
                             "mask instead")
    parser.add_argument("--round_deadline", type=float, default=0.0,
                        help="cross-silo per-round deadline seconds "
                             "(distributed.run); recorded in the config "
                             "for parity with the multiprocess runner")
    parser.add_argument("--quorum", type=int, default=0,
                        help="min survivor uploads for a deadline round "
                             "to aggregate (0 = all clients)")
    parser.add_argument("--heartbeat_interval", type=float, default=0.0,
                        help="cross-silo clients: liveness beat period "
                             "seconds (0 = off); recorded in the config "
                             "for parity with distributed.run")
    parser.add_argument("--heartbeat_timeout", type=float, default=0.0,
                        help="cross-silo server: mark clients suspect "
                             "once their heartbeat is older than this "
                             "(0 = off)")
    parser.add_argument("--async_server", action="store_true",
                        help="cross-silo server runs the FedBuff-style "
                             "buffered asynchronous control plane "
                             "(asyncfl/, distributed.run): uploads "
                             "aggregate every --buffer_k arrivals with "
                             "staleness weighting instead of a round "
                             "barrier; recorded in the config for "
                             "parity with the multiprocess runner")
    parser.add_argument("--buffer_k", type=int, default=0,
                        help="async server: aggregate every K accepted "
                             "uploads (0 = cohort size, which with zero "
                             "staleness reproduces the synchronous "
                             "server bitwise)")
    parser.add_argument("--staleness_alpha", type=float, default=0.5,
                        help="async server: polynomial staleness weight "
                             "(1 + tau)^-alpha on upload sample counts "
                             "(0 disables down-weighting)")
    parser.add_argument("--max_staleness", type=int, default=20,
                        help="async server: drop uploads based on a "
                             "version more than this many aggregations "
                             "old (also bounds the codec delta-"
                             "reference ring)")
    parser.add_argument("--tag", type=str, default="exp")
    parser.add_argument("--num_classes", type=int, default=1)
    # sparsity family
    parser.add_argument("--dense_ratio", type=float, default=0.5)
    parser.add_argument("--anneal_factor", type=float, default=0.5)
    parser.add_argument("--erk_power_scale", type=float, default=1.0)
    parser.add_argument("--uniform", action="store_true")
    parser.add_argument("--static", action="store_true")
    parser.add_argument("--dis_gradient_check", action="store_true")
    parser.add_argument("--different_initial", action="store_true")
    parser.add_argument("--diff_spa", action="store_true")
    parser.add_argument("--save_masks", action="store_true")
    # SalientGrads (note: the reference's `--snip_mask type=bool` makes any
    # string truthy, main_sailentgrads.py:125; we use an explicit off switch)
    parser.add_argument("--no_snip_mask", action="store_true",
                        help="dense escape hatch (snip_mask=False)")
    parser.add_argument("--itersnip_iteration", type=int, default=1)
    parser.add_argument("--stratified_sampling", action="store_true")
    # Ditto (main_ditto.py:79,101)
    parser.add_argument("--lamda", type=float, default=0.5)
    parser.add_argument("--local_epochs", type=int, default=1)
    # Sub-FedAvg (main_subavg.py:105-108)
    parser.add_argument("--each_prune_ratio", type=float, default=0.1)
    parser.add_argument("--dist_thresh", type=float, default=0.001)
    parser.add_argument("--acc_thresh", type=float, default=0.5)
    # FedFomo
    parser.add_argument("--fomo_m", type=int, default=5)
    parser.add_argument("--val_fraction", type=float, default=0.0)
    # robust aggregation (RobustAggregator args, robust_aggregation.py:32-36)
    parser.add_argument("--mpc_n_shares", type=int, default=3,
                        help="TurboAggregate: additive shares per client "
                             "update")
    parser.add_argument("--mpc_frac_bits", type=int, default=16,
                        help="TurboAggregate: fixed-point fraction bits "
                             "for GF(p) quantization")
    parser.add_argument("--mpc_backend", type=str, default="device",
                        choices=("device", "host"),
                        help="TurboAggregate MPC stage: 'device' (jitted "
                             "uint32 mod-p on the accelerator, default) | "
                             "'host' (numpy path modeling the "
                             "client<->server boundary)")
    # privacy plane (privacy/, ISSUE 8)
    parser.add_argument("--secure_quant", action="store_true",
                        help="secure QUANTIZED aggregation "
                             "(privacy/secure_quant.py): the simulated "
                             "round aggregates through the jitted GF(p) "
                             "integer-weight fold (the builder's codec-"
                             "family stage, engines/program.py — bitwise "
                             "the host SlotAccumulator fold), so round "
                             "metrics reflect exactly what the encoded "
                             "secure wire would deliver; the wire itself "
                             "lives on the cross-silo/async planes "
                             "(distributed.run). Needs "
                             "--secure_quant_field_bits 32 (the one-"
                             "phase capacity bound)")
    parser.add_argument("--secure_quant_field_bits", type=int, default=16,
                        choices=(8, 16, 32),
                        help="secure_quant field width: p = largest prime "
                             "below 2^bits (the wire ships one uintN "
                             "residue per parameter)")
    parser.add_argument("--secure_quant_frac_bits", type=int, default=10,
                        help="secure_quant fixed-point fraction bits; the "
                             "aggregate range value_bound * 2^frac_bits "
                             "must stay inside p/2 (checked at startup)")
    parser.add_argument("--dp_clip", type=float, default=0.0,
                        help="dpsgd round-level DP: clip each client's "
                             "update delta (vs its consensus point) to "
                             "this L2 bound before it reaches any "
                             "neighbor (0 = off)")
    parser.add_argument("--dp_sigma", type=float, default=0.0,
                        help="dpsgd round-level DP: Gaussian noise "
                             "multiplier — noise stddev is dp_sigma * "
                             "dp_clip, drawn inside the jitted round "
                             "from config-folded jax keys; the RDP "
                             "accountant (privacy/accountant.py) reports "
                             "the running per-silo (epsilon, dp_delta) "
                             "in stat_info (0 = off; requires --dp_clip)")
    parser.add_argument("--dp_delta", type=float, default=1e-5,
                        help="target delta for the RDP -> (epsilon, "
                             "delta) conversion (dpsgd DP and the "
                             "weak_dp defense accountant)")
    parser.add_argument("--defense_type", "--defense", dest="defense_type",
                        type=str, default="none",
                        help="none | norm_diff_clipping | weak_dp | "
                             "trimmed_mean | median | krum | multi_krum | "
                             "geometric_median — the clip family applies "
                             "per client before the weighted mean "
                             "(reference RobustAggregator parity); the "
                             "order-statistic family (core/robust.py, "
                             "ISSUE 5) replaces the mean and tolerates "
                             "up to --byz_f Byzantine clients. Runs "
                             "inside the jitted round body")
    parser.add_argument("--norm_bound", type=float, default=5.0)
    parser.add_argument("--stddev", type=float, default=0.05)
    parser.add_argument("--byz_f", type=int, default=1,
                        help="assumed Byzantine client count f for the "
                             "order-statistic defenses: trim depth per "
                             "side (trimmed_mean), Krum neighborhood "
                             "(sampled cohort must be >= f + 3; "
                             "trimmed_mean/median need 2f < n)")
    parser.add_argument("--geomed_iters", type=int, default=8,
                        help="geometric_median: fixed Weiszfeld "
                             "iteration count (trace-static)")
    # 3D-model rematerialization policy (PROFILE.md)
    parser.add_argument("--remat", type=str, default="auto",
                        help="auto | none | stem | all")
    # mixed-precision train step (ISSUE 10, core/optim.py)
    parser.add_argument("--precision", type=str, default="fp32",
                        choices=("fp32", "bf16_mixed"),
                        help="train-step compute dtype: fp32 (bitwise-"
                             "identical to the legacy tree) | bf16_mixed "
                             "(bf16 compute/activations, fp32 MASTER "
                             "weights + momentum + loss; checkpoints and "
                             "every aggregation/codec/secure plane see "
                             "only the fp32 master weights)")
    parser.add_argument("--loss_scale", type=float, default=1.0,
                        help="fixed loss-scale constant for bf16_mixed "
                             "(static scaling: loss * S before grad, "
                             "f32 grads / S after); 1.0 = off — the "
                             "pinned default, since bf16 keeps f32's "
                             "exponent range. Rejected under fp32")
    parser.add_argument("--fused_update", action="store_true",
                        help="fuse the SGD tail (global-norm clip + "
                             "weight decay + momentum + lr update + "
                             "sparse-mask re-apply) into one Pallas "
                             "pass over the params "
                             "(ops/fused_update.py; XLA fallback off-"
                             "TPU, bit-parity with the unfused chain "
                             "pinned). SGD only")
    # synthetic data knobs (tests / demos without the private cohort)
    parser.add_argument("--synthetic_num_subjects", type=int, default=256)
    parser.add_argument("--synthetic_shape", type=int, nargs=3,
                        default=[121, 145, 121])
    parser.add_argument("--synthetic_signal", type=float, default=12.0,
                        help="class-signal amplitude of the synthetic "
                             "cohort (vs sigma-8 voxel noise); lower = "
                             "harder task")
    # infra
    parser.add_argument("--log_dir", type=str, default="LOG")
    parser.add_argument("--streaming", action="store_true",
                        help="host-stream the cohort per round instead of "
                             "keeping it device-resident (cohorts > HBM); "
                             "supported by all ten algorithms (fedfomo "
                             "additionally needs --val_fraction > 0: its "
                             "small val shards stay resident)")
    parser.add_argument("--stream_chunk_clients", type=int, default=0,
                        help="clients per host-fetched chunk in streaming "
                             "eval / SNIP scoring / chunked DisPFL rounds "
                             "(0 = auto)")
    parser.add_argument("--checkpoint_dir", type=str, default="")
    parser.add_argument("--checkpoint_every", type=int, default=0)
    parser.add_argument("--multihost_coordinator", type=str, default="",
                        help="host:port of process 0; joins this process "
                             "to a multi-host JAX runtime (TPU pod) before "
                             "mesh construction (jax.distributed)")
    parser.add_argument("--process_id", type=int, default=0,
                        help="this process's rank in the multi-host "
                             "runtime")
    parser.add_argument("--num_processes", type=int, default=1,
                        help="total processes in the multi-host runtime")
    parser.add_argument("--virtual_devices", type=int, default=0,
                        help="provision N virtual CPU devices (mesh "
                             "simulation without TPU hardware)")
    parser.add_argument("--mesh_shape", type=int, nargs="*", default=[],
                        help="device mesh layout: one value = first-N 1-D "
                             "clients mesh; two values (silos cores) = "
                             "two-level cross-silo mesh (silo aggregation "
                             "on ICI, cross-silo on DCN)")
    parser.add_argument("--profile_dir", type=str, default="",
                        help="capture a jax.profiler trace of training "
                             "into this dir (TensorBoard-loadable)")
    # observability plane (obs/, ISSUE 9)
    parser.add_argument("--trace_out", type=str, default="",
                        help="write the run's host-span timeline "
                             "(round/eval spans at dispatch "
                             "boundaries) as Chrome trace-event JSON, "
                             "Perfetto-loadable (obs/trace.py); with "
                             "--profile_dir each span also opens a "
                             "jax.profiler.TraceAnnotation so host "
                             "spans line up with the XLA timeline. "
                             "Multi-process planes (distributed/run.py "
                             "--ingest_workers) treat the bare path as "
                             "the MERGED trace and suffix per-process "
                             "secondaries .wN (obs/fanin.py)")
    parser.add_argument("--metrics_port", type=int, default=0,
                        help="serve /metrics (Prometheus text "
                             "exposition of the obs registry: stat_info "
                             "accumulators, round metrics, DP epsilon) "
                             "+ /healthz during training (obs/http.py); "
                             "0 = off. The endpoint is unauthenticated "
                             "— bind scope via --metrics_host")
    parser.add_argument("--metrics_host", type=str, default="0.0.0.0",
                        help="interface the metrics endpoint binds "
                             "(default all interfaces; pass 127.0.0.1 "
                             "on shared hosts)")
    parser.add_argument("--profile_session", type=str, default="",
                        help="run the declarative profile session "
                             "(obs/probe.py: PROFILE.md's probe "
                             "checklist as a manifest through the "
                             "shipped driver with the dispatch-boundary "
                             "profiler armed) and write the machine-"
                             "readable artifact here instead of "
                             "training; PROFILE_MODEL/PROFILE_SHAPE/"
                             "PROFILE_BATCH env size the cells and "
                             "--profile_manifest replaces the probe "
                             "list. scripts/run_profile_session.sh is "
                             "the push-button wrapper")
    parser.add_argument("--profile_manifest", type=str, default="",
                        help="JSON probe manifest for "
                             "--profile_session (a [{name, cell}] "
                             "array; default: obs/probe.py's declared "
                             "list)")
    parser.add_argument("--peak_flops", type=float, default=0.0,
                        help="device peak flop/s for the nidt_mfu "
                             "gauge's denominator (total across local "
                             "devices); 0 = the obs/compute.py device-"
                             "kind estimate (NIDT_PEAK_FLOPS env also "
                             "overrides; unknown backends publish "
                             "sustained TFLOP/s only)")
    parser.add_argument("--flight_events", type=int, default=256,
                        help="flight-recorder ring capacity "
                             "(obs/flight.py); the ring dumps to "
                             "LOG/<dataset>/<identity>.flight.json on "
                             "any fatal failure (failure_context)")
    # training-health plane (obs/health.py + obs/rules.py, ISSUE 15)
    parser.add_argument("--health_stats", action="store_true",
                        help="arm the in-dispatch federation-"
                             "statistics leg on every declared round "
                             "program (engines/program.py): per-client "
                             "update L2 norms, cosine-to-aggregate, "
                             "update-norm dispersion, global param/"
                             "update norms and mask health, computed "
                             "INSIDE the jitted round and fetched only "
                             "in the existing batched host-boundary "
                             "device_get — armed rounds are bitwise-"
                             "identical to disarmed ones, published as "
                             "nidt_health_* on /metrics")
    parser.add_argument("--health_rules", type=str, default="",
                        help="JSON manifest of anomaly rules "
                             "(obs/rules.py: metric selector, window, "
                             "comparator, threshold, severity, "
                             "for_rounds debounce) extending the "
                             "built-in set (same-named rules "
                             "override); unknown metric names fail at "
                             "startup against the declared-name list "
                             "(obs/names.py)")
    parser.add_argument("--health_gate", action="store_true",
                        help="exit nonzero when the run's WORST health "
                             "status was not ok (any anomaly rule "
                             "fired), after writing the machine-"
                             "readable verdict to "
                             "LOG/<dataset>/<identity>.health.json — "
                             "the CI spelling of 'this run trained "
                             "healthily'")
    parser.add_argument("--metrics_out", type=str, default="",
                        help="append one metrics-registry JSONL record "
                             "per round at the engine host boundary, "
                             "each with monotonic round/seq join keys "
                             "(obs/metrics.py dump_jsonl) — the sink "
                             "analysis/run_report.py joins with the "
                             "flight dump and health verdict")
    parser.add_argument("--actions", type=str, default="dry_run",
                        choices=("off", "dry_run", "on"),
                        help="reflex plane (obs/actions.py, ISSUE 20): "
                             "what a firing health rule's declared "
                             "action DOES. off = rules only observe; "
                             "dry_run (default) = every would-fire "
                             "dispatch is logged and flight-recorded "
                             "with its rule as provenance but nothing "
                             "changes; on = actions apply (quarantine "
                             "the diverging silo, escalate the "
                             "defense ladder, adapt the async buffer, "
                             "freeze-and-rollback to the last healthy "
                             "state)")
    parser.add_argument("--dp_epsilon_budget", type=float, default=0.0,
                        help="epsilon budget the built-in DP health "
                             "rules judge against (obs/rules.py): "
                             "dp-budget-exceeded fires critical once "
                             "the running epsilon crosses it, "
                             "dp-burn-rate warns when a round burns "
                             "over 2x the uniform budget/comm_round "
                             "rate; 0 = no budget rules")
    parser.add_argument("--client_mesh", type=int, default=0,
                        help="shard the sampled-client axis of every "
                             "jitted round program over a client mesh of "
                             "exactly N devices (parallel/cohort.py): "
                             "per-device local training on client "
                             "shards, aggregation on all-gathered "
                             "stacks, bitwise-equal to the unsharded "
                             "round; non-tiling cohorts (21 sites on 8 "
                             "devices) pad with zero-weight rows. "
                             "Engines/modes without a declared sharded "
                             "round body (engines/program.py) fall back "
                             "with a logged + counted reason "
                             "(nidt_fallback_total on /metrics). "
                             "Combine with --virtual_devices N to "
                             "simulate without TPU hardware")
    parser.add_argument("--recipe", type=str, default="",
                        help="apply a committed autotune recipe "
                             "(tune/recipe.py) as config DEFAULTS "
                             "before any conflict check: a path to "
                             "bench_matrix/recipes/<device_kind>.json, "
                             "or 'auto' to resolve the committed recipe "
                             "for the visible device kind at startup. "
                             "Explicit CLI flags win over recipe values "
                             "(each override is logged + counted via "
                             "nidt_fallback_total{plane='recipe'}); a "
                             "truncated/tampered/mismatched recipe dies "
                             "at argparse. Loading a recipe also arms "
                             "the mfu-below-recipe drift rule "
                             "(obs/rules.py) against the recipe's "
                             "recorded score")
    return parser


def config_from_args(args: argparse.Namespace) -> ExperimentConfig:
    return ExperimentConfig(
        model=args.model, num_classes=args.num_classes,
        algorithm=args.algorithm, seed=args.seed, tag=args.tag,
        mesh_shape=tuple(args.mesh_shape),
        data=DataConfig(
            dataset=args.dataset.lower(), data_dir=args.data_dir,
            partition_method=args.partition_method,
            partition_alpha=args.partition_alpha,
            synthetic_num_subjects=args.synthetic_num_subjects,
            synthetic_shape=tuple(args.synthetic_shape),
            synthetic_signal=args.synthetic_signal,
            val_fraction=args.val_fraction,
            seed_split=args.seed_split),
        optim=OptimConfig(
            client_optimizer=args.client_optimizer, lr=args.lr,
            lr_decay=args.lr_decay, wd=args.wd, momentum=args.momentum,
            grad_clip=args.grad_clip,
            batch_size=args.batch_size, epochs=args.epochs,
            batch_order=args.batch_order,
            precision=args.precision, loss_scale=args.loss_scale,
            fused_update=args.fused_update),
        fed=FedConfig(
            client_num_in_total=args.client_num_in_total, frac=args.frac,
            comm_round=args.comm_round, cs=args.cs, active=args.active,
            neighbor_num=args.neighbor_num,
            fault_spec=args.fault_spec,
            wire_codec=args.wire_codec,
            wire_topk_ratio=args.wire_topk_ratio,
            round_deadline=args.round_deadline, quorum=args.quorum,
            heartbeat_interval=args.heartbeat_interval,
            heartbeat_timeout=args.heartbeat_timeout,
            async_server=args.async_server, buffer_k=args.buffer_k,
            staleness_alpha=args.staleness_alpha,
            max_staleness=args.max_staleness,
            lamda=args.lamda, local_epochs=args.local_epochs,
            fomo_m=args.fomo_m, mpc_n_shares=args.mpc_n_shares,
            mpc_frac_bits=args.mpc_frac_bits, mpc_backend=args.mpc_backend,
            secure_quant=args.secure_quant,
            secure_quant_field_bits=args.secure_quant_field_bits,
            secure_quant_frac_bits=args.secure_quant_frac_bits,
            dp_clip=args.dp_clip, dp_sigma=args.dp_sigma,
            dp_delta=args.dp_delta,
            dp_epsilon_budget=args.dp_epsilon_budget,
            defense_type=args.defense_type,
            norm_bound=args.norm_bound, stddev=args.stddev,
            byz_f=args.byz_f, geomed_iters=args.geomed_iters,
            client_mesh=args.client_mesh,
            frequency_of_the_test=args.frequency_of_the_test,
            ci=bool(args.ci)),
        sparsity=SparsityConfig(
            dense_ratio=args.dense_ratio, anneal_factor=args.anneal_factor,
            erk_power_scale=args.erk_power_scale, uniform=args.uniform,
            static=args.static, dis_gradient_check=args.dis_gradient_check,
            different_initial=args.different_initial, diff_spa=args.diff_spa,
            snip_mask=not args.no_snip_mask,
            itersnip_iterations=args.itersnip_iteration,
            stratified_sampling=args.stratified_sampling,
            each_prune_ratio=args.each_prune_ratio,
            dist_thresh=args.dist_thresh, acc_thresh=args.acc_thresh,
            save_masks=args.save_masks),
        checkpoint_dir=args.checkpoint_dir,
        checkpoint_every=args.checkpoint_every,
        remat=args.remat,
        recipe=args.recipe,
        stream_chunk_clients=args.stream_chunk_clients,
        log_dir=args.log_dir,
        trace_out=args.trace_out, metrics_port=args.metrics_port,
        flight_events=args.flight_events,
        health_stats=args.health_stats, health_rules=args.health_rules,
        health_gate=args.health_gate, metrics_out=args.metrics_out,
        actions=args.actions)


def run_mesh(cfg: ExperimentConfig, streaming: bool = False):
    """The device mesh a run gets. It applies to both residency modes:
    under --streaming each round's sampled-client buffers are device_put
    sharded over the client axis — on a two-level (silos, clients) mesh
    the axis maps over BOTH mesh axes silo-major (data/stream.py::_put),
    so the engine's silo-first aggregation routing is preserved while
    the cohort streams from host."""
    from neuroimagedisttraining_tpu.parallel.mesh import make_mesh

    if streaming and not cfg.mesh_shape and not cfg.fed.client_mesh:
        return None  # plain single-device streaming feed
    if cfg.fed.client_mesh > 0 and not cfg.mesh_shape:
        # --client_mesh N builds the 1-D N-device client mesh it shards
        # over (an explicit --mesh_shape wins and must agree — the
        # engine validates the sizes at startup)
        return make_mesh(num_devices=cfg.fed.client_mesh)
    return make_mesh(shape=cfg.mesh_shape)  # default: every visible device


def build_experiment(cfg: ExperimentConfig, streaming: bool = False,
                     mesh=None, console: bool = True):
    """Data dispatch (load_data, main_sailentgrads.py:130-160) + model +
    trainer + engine wiring. Returns the ready engine."""
    from neuroimagedisttraining_tpu.core.trainer import LocalTrainer
    from neuroimagedisttraining_tpu.data import partition as P
    from neuroimagedisttraining_tpu.data.federate import federate_cohort
    from neuroimagedisttraining_tpu.data.hdf5 import load_abcd_hdf5
    from neuroimagedisttraining_tpu.data.stream import StreamingFederation
    from neuroimagedisttraining_tpu.data.synthetic import generate_synthetic_abcd
    from neuroimagedisttraining_tpu.engines import create_engine
    from neuroimagedisttraining_tpu.models import create_model
    from neuroimagedisttraining_tpu.utils.logging import ExperimentLogger

    d = cfg.data
    dataset = d.dataset.lower()
    log = ExperimentLogger(cfg.log_dir, dataset, cfg.identity(),
                           console=console)
    log.info("config: %s", cfg.to_json())

    stream = None
    cohort = None
    if dataset in ("abcd", "abcd_h5"):
        cohort = load_abcd_hdf5(d.data_dir, lazy=streaming)
    elif dataset == "synthetic":
        cohort = generate_synthetic_abcd(
            num_subjects=d.synthetic_num_subjects,
            shape=d.synthetic_shape,
            signal=d.synthetic_signal,
            num_sites=max(4, cfg.fed.client_num_in_total // 4),
            seed=cfg.seed)
    elif dataset in ("cifar10", "cifar100", "tiny", "synthetic_vision"):
        if streaming:
            raise ValueError("streaming mode is for ABCD-scale cohorts")
        from neuroimagedisttraining_tpu.data.vision import federate_vision
        method = d.partition_method if d.partition_method != "site" else "dir"
        fed, info = federate_vision(
            "cifar10" if dataset == "synthetic_vision" else dataset,
            d.data_dir, method, d.partition_alpha,
            cfg.fed.client_num_in_total, mesh=mesh,
            val_fraction=d.val_fraction, seed=cfg.seed,
            synthetic=dataset == "synthetic_vision",
            num_classes=cfg.num_classes if cfg.num_classes > 1 else None)
        log.info("partition: %s", json.dumps(info.get("train_counts")))
    else:
        raise ValueError(
            f"dataset {dataset!r} has no loader (have: abcd/abcd_h5/"
            "synthetic/cifar10/cifar100/tiny/synthetic_vision)")

    if cohort is None:
        pass  # vision federation already built above
    elif streaming:
        if d.partition_method != "site":
            raise ValueError("streaming mode currently partitions by site")
        from neuroimagedisttraining_tpu.data.federate import DATA_SPLIT_SEED

        # same split seed as federate_cohort's resident path: a streamed
        # run must see the SAME train/test/val rows as a resident one
        train_map, test_map, _ = P.site_partition(cohort["site"],
                                                  seed=DATA_SPLIT_SEED)
        # NOTE: a sampled-set size that does not tile the mesh (e.g. the
        # north-star 100 clients at frac 0.1 on 8 devices) is handled by
        # the engines' stream_sampling padding (zero-weight pad clients),
        # so no tiling restriction applies to --frac
        if mesh is not None and cfg.stream_chunk_clients > 0 and \
                cfg.stream_chunk_clients % mesh.devices.size != 0:
            raise ValueError(
                f"--stream_chunk_clients ({cfg.stream_chunk_clients}) must "
                f"be a multiple of the {mesh.devices.size}-device mesh so "
                "each streamed chunk's NamedSharding device_put tiles the "
                "client axis (otherwise XLA rejects the put mid-run)")
        val_map = None
        if d.val_fraction > 0:
            from neuroimagedisttraining_tpu.data.federate import (
                carve_val_split,
            )

            val_map, train_map = carve_val_split(train_map, d.val_fraction,
                                                 seed=DATA_SPLIT_SEED)
        stream = StreamingFederation(cohort["X"], cohort["y"], train_map,
                                     test_map, mesh=mesh, val_map=val_map)
        fed = None
    else:
        fed, info = federate_cohort(
            cohort, partition_method=d.partition_method,
            client_number=cfg.fed.client_num_in_total,
            alpha=d.partition_alpha, mesh=mesh,
            val_fraction=d.val_fraction)
        log.info("partition: %s", json.dumps(info.get("train_counts")))

    # remat policy for the 3D family (PROFILE.md): no-remat is faster
    # (b128 x 1 client/core measured 768 vs 611 samples/s against stem
    # remat, round 3) and up to ~128 full-size fp32 samples fit in
    # flight per chip without it; above that use stem remat (f0+f1 —
    # same speed as full remat, less HBM). The cutoff is precision-
    # aware (core/optim.py REMAT_AUTO_SAMPLES): bf16_mixed stores
    # activations at half the bytes, so the same headroom carries 2x
    # the samples before recompute pays for itself.
    remat: bool | str | None
    if cfg.remat == "auto":
        import jax

        from neuroimagedisttraining_tpu.core.optim import (
            remat_auto_samples_threshold,
        )

        n_dev = max(1, len(jax.devices()) if mesh is None
                    else mesh.devices.size)
        per_dev = -(-cfg.fed.client_num_per_round // n_dev)
        threshold = remat_auto_samples_threshold(cfg.optim.precision)
        remat = (False if per_dev * cfg.optim.batch_size <= threshold
                 else "stem")
    else:
        remat = {"none": False, "stem": "stem", "all": True}[cfg.remat]
    # precision contract (ISSUE 10): the model's flax dtype IS the
    # compute precision; master weights stay f32 (flax param_dtype
    # default), so every plane outside the jitted step — aggregation,
    # codec, secure, checkpoints — sees float32 regardless
    from neuroimagedisttraining_tpu.core.optim import compute_dtype

    model = create_model(cfg.model, num_classes=cfg.num_classes, remat=remat,
                         dtype=compute_dtype(cfg.optim.precision))
    trainer = LocalTrainer(model, cfg.optim, num_classes=cfg.num_classes)
    return create_engine(cfg.algorithm, cfg, fed, trainer, mesh=mesh,
                         logger=log, stream=stream)


def main(argv: list[str] | None = None) -> int:
    parser = add_args(argparse.ArgumentParser(
        prog="neuroimagedisttraining_tpu"))
    args = parser.parse_args(argv)

    # virtual devices provision BEFORE any backend touch — including
    # the --recipe auto device-kind resolution just below
    if args.virtual_devices:
        from neuroimagedisttraining_tpu.parallel.mesh import (
            provision_virtual_devices,
        )
        provision_virtual_devices(args.virtual_devices)

    # autotune recipe (ISSUE 19, tune/recipe.py): applied as config
    # DEFAULTS before the conflict checks below, so a recipe knob that
    # conflicts with an explicit flag dies at argparse exactly like a
    # hand-spelled config; explicit flags win with a logged + counted
    # override (nidt_fallback_total{plane="recipe"})
    recipe_doc = None
    if args.recipe:
        from neuroimagedisttraining_tpu.tune import recipe as tune_recipe

        try:
            recipe_doc = tune_recipe.resolve_and_load(args.recipe)
            tune_recipe.apply_recipe(
                args, recipe_doc,
                argv if argv is not None else sys.argv[1:])
        except (OSError, ValueError) as e:
            parser.error(f"--recipe: {e}")

    # privacy-plane flag conflicts die AT ARGPARSE with the resolution
    # named (ISSUE 8 satellite) — the engine constructors reject these
    # too, but only after the data/model build, deep in a stack trace
    if args.algorithm.lower() == "turboaggregate":
        from neuroimagedisttraining_tpu.core import robust

        if args.wire_codec not in ("", "none"):
            parser.error(
                "--wire_codec does not compose with the secure "
                "turboaggregate engine (the codec's float stages would "
                "corrupt the GF(p) share embedding). The compressed "
                "secure wire is --secure_quant on the cross-silo runner "
                "(distributed.run); see ARCHITECTURE.md 'Privacy plane'")
        if args.defense_type in robust.ROBUST_AGGREGATORS:
            parser.error(
                f"--defense {args.defense_type} does not compose with "
                "secure aggregation (no per-client plaintext to select "
                "over); the clip family (norm_diff_clipping, weak_dp) "
                "composes client-side — see ARCHITECTURE.md 'Privacy "
                "plane'")
    if args.dp_sigma > 0 and args.dp_clip <= 0:
        parser.error("--dp_sigma needs --dp_clip > 0 (the clip bound is "
                     "the sensitivity the noise multiplier is stated "
                     "against)")
    # health-plane config dies AT ARGPARSE (ISSUE 15 satellite): a
    # negative budget or a broken/unknown-metric rule manifest must
    # fail here, never as a silently-never-firing rule mid-run
    if args.dp_epsilon_budget < 0:
        parser.error(f"--dp_epsilon_budget must be >= 0 (got "
                     f"{args.dp_epsilon_budget})")
    if args.dp_epsilon_budget > 0 and args.dp_sigma <= 0 \
            and args.defense_type != "weak_dp":
        parser.error(
            "--dp_epsilon_budget needs an armed noise path to budget "
            "(--dp_sigma/--dp_clip on a DP engine, or --defense "
            "weak_dp): without one the accountant records nothing and "
            "the budget rules can never fire")
    if args.health_rules:
        from neuroimagedisttraining_tpu.obs import names as obs_names
        from neuroimagedisttraining_tpu.obs import rules as obs_rules

        try:
            for r in obs_rules.load_rules(args.health_rules):
                # full validation (unknown metric names included), not
                # just the schema — a typo'd rule must die HERE with
                # the known-names list, not as a traceback after the
                # data/model build
                r.validate(obs_names.DECLARED)
        except (OSError, ValueError, TypeError) as e:
            parser.error(f"--health_rules: {e}")
    # precision-contract conflicts die AT ARGPARSE with the resolution
    # named (core/optim.validate_precision re-checks at trainer build)
    if args.loss_scale != 1.0 and args.precision != "bf16_mixed":
        parser.error(
            f"--loss_scale {args.loss_scale} needs --precision "
            "bf16_mixed: under fp32 the scale/unscale pair would only "
            "perturb rounding and break the bitwise-f32 contract")
    if args.fused_update and args.client_optimizer != "sgd":
        parser.error(
            "--fused_update fuses the SGD clip/momentum/update tail "
            f"(ops/fused_update.py); --client_optimizer "
            f"{args.client_optimizer} has no fused kernel and would "
            "silently train un-fused")
    if args.dp_sigma > 0 or args.dp_clip > 0:
        # one source of truth: the same supports_dp attribute the
        # engine ctor gates on (an engine gaining the transform later
        # must not stay rejected here)
        from neuroimagedisttraining_tpu.engines import ENGINES

        cls = ENGINES.get(args.algorithm.lower())
        if cls is None or not cls.supports_dp:
            ok = sorted({c.name for c in ENGINES.values()
                         if c.supports_dp})
            parser.error(
                f"--dp_clip/--dp_sigma need an engine with the round-"
                f"level DP transform; algorithm {args.algorithm!r} "
                f"would train un-noised while the accountant reported "
                f"epsilon (supported: {ok})")
    if args.secure_quant:
        # privacy-plane conflicts die AT ARGPARSE with the resolution
        # named (the engine ctor re-checks, but only after the
        # data/model build, deep in a stack trace)
        from neuroimagedisttraining_tpu.core import robust
        from neuroimagedisttraining_tpu.engines import ENGINES

        cls = ENGINES.get(args.algorithm.lower())
        if cls is None or not cls.supports_secure_quant:
            ok = sorted({c.name for c in ENGINES.values()
                         if c.supports_secure_quant})
            parser.error(
                f"--secure_quant needs an engine whose round routes the "
                f"builder's default aggregation tail; algorithm "
                f"{args.algorithm!r} has no server fold for the field "
                f"algebra to replace (supported: {ok})")
        if args.wire_codec not in ("", "none"):
            parser.error(
                "--secure_quant does not compose with --wire_codec "
                "(the codec's float stages would corrupt the GF(p) "
                "residue embedding); see ARCHITECTURE.md 'Privacy "
                "plane'")
        if args.defense_type in robust.ROBUST_AGGREGATORS:
            parser.error(
                f"--defense {args.defense_type} does not compose with "
                "--secure_quant (no per-client plaintext to select "
                "over); the clip family (norm_diff_clipping, weak_dp) "
                "composes client-side — see ARCHITECTURE.md 'Privacy "
                "plane'")
        # field-geometry headroom fails at argparse here exactly like
        # distributed.run's startup check — misconfigured frac/field
        # bits must never surface as silent field wraparound
        from neuroimagedisttraining_tpu.privacy import (
            QuantSpec, check_headroom,
        )

        try:
            check_headroom(
                QuantSpec.from_bits(args.secure_quant_field_bits,
                                    args.secure_quant_frac_bits,
                                    args.mpc_n_shares),
                args.client_num_in_total)
        except ValueError as e:
            parser.error(str(e))

    if args.profile_session:
        # push-button profile session (ISSUE 14, obs/probe.py): the
        # declarative probe manifest through the shipped driver with
        # the dispatch-boundary profiler armed — replaces PROFILE.md's
        # hand-run checklist; normal training is skipped
        import jax

        from neuroimagedisttraining_tpu.obs import compute as obs_compute
        from neuroimagedisttraining_tpu.obs import probe as obs_probe

        if args.peak_flops > 0:
            obs_compute.PROFILER.set_peak_flops(args.peak_flops)
        manifest = (obs_probe.load_manifest(args.profile_manifest)
                    if args.profile_manifest
                    else obs_probe.default_manifest(len(jax.devices())))
        doc = obs_probe.run_session(manifest, args.profile_session,
                                    trace_out=args.trace_out)
        return 0 if obs_probe.session_ok(doc) else 1

    if args.multihost_coordinator:
        # join the pod-wide JAX runtime BEFORE any backend touch so the
        # mesh below spans every host's chips (SURVEY §2.9 DCN row; see
        # README "Multi-host TPU pods" for the per-host launch recipe)
        from neuroimagedisttraining_tpu.distributed.cross_silo import (
            init_multihost,
        )
        init_multihost(args.multihost_coordinator, args.num_processes,
                       args.process_id)

    from neuroimagedisttraining_tpu.utils.compile_cache import (
        enable_compile_cache,
    )
    enable_compile_cache()

    # deterministic seeding (main_sailentgrads.py:264-268)
    random.seed(args.seed)
    np.random.seed(args.seed)  # nidt: allow[determinism-global-random] -- reference-parity entry seeding (main_sailentgrads.py:264-268), single-threaded startup

    # vision datasets imply their class counts unless overridden
    _vision_classes = {"cifar10": 10, "synthetic_vision": 10,
                       "cifar100": 100, "tiny": 200}
    if args.num_classes == 1 and args.dataset.lower() in _vision_classes:
        args.num_classes = _vision_classes[args.dataset.lower()]

    cfg = config_from_args(args)
    engine = build_experiment(cfg, streaming=args.streaming,
                              mesh=run_mesh(cfg, args.streaming))
    from neuroimagedisttraining_tpu.utils.profiling import (
        failure_context, profile_trace,
    )
    # observability plane (obs/, ISSUE 9): span tracer (annotating the
    # XLA timeline when --profile_dir is also set), live /metrics
    # endpoint, and the flight recorder's failure-dump destination —
    # all host-side, armed only when asked for
    import os

    from neuroimagedisttraining_tpu.obs import flight as obs_flight
    from neuroimagedisttraining_tpu.obs import trace as obs_trace
    from neuroimagedisttraining_tpu.obs.http import start_metrics_server

    obs_flight.configure(
        capacity=cfg.flight_events,
        path=os.path.join(engine.log.dir,
                          cfg.identity() + ".flight.json"))
    if cfg.trace_out:
        obs_trace.arm(cfg.trace_out,
                      annotate=bool(args.profile_dir),
                      tags={"algorithm": cfg.algorithm,
                            "seed": cfg.seed})
    # compute-plane gauges (obs/compute.py, ISSUE 14): the dispatch
    # profiler is always on; --peak_flops arms the MFU denominator and
    # /healthz carries the compute block (wedged vs slow dispatch)
    from neuroimagedisttraining_tpu.obs import compute as obs_compute
    from neuroimagedisttraining_tpu.obs import health as obs_health
    from neuroimagedisttraining_tpu.obs import rules as obs_rules

    if args.peak_flops > 0:
        obs_compute.PROFILER.set_peak_flops(args.peak_flops)
    # anomaly-rule engine (obs/rules.py, ISSUE 15): the built-in
    # manifest parameterized by this run's budget/schedule, extended by
    # --health_rules; evaluated at every engine host boundary
    # (publish_stat_info) and reported on /healthz
    # a loaded recipe arms its drift rule (mfu-below-recipe): live MFU
    # sagging under the recipe's recorded score flight-records
    # retune_recommended (tune/recipe.py drift_rules)
    extra_rules = ()
    if recipe_doc is not None:
        from neuroimagedisttraining_tpu.tune import recipe as tune_recipe

        extra_rules = tune_recipe.drift_rules(recipe_doc)
    hrules = obs_rules.configure(
        manifest_path=args.health_rules,
        dp_epsilon_budget=cfg.fed.dp_epsilon_budget,
        comm_round=cfg.fed.comm_round,
        max_staleness=cfg.fed.max_staleness,
        extra_rules=extra_rules)
    # reflex plane (obs/actions.py, ISSUE 20): arm the action bus the
    # firing rules dispatch into; the engine registers its handlers at
    # train() start. LOCAL handle — disarm() precedes the verdict
    # write, exactly like ``hrules``.
    from neuroimagedisttraining_tpu.obs import actions as obs_actions

    bus = obs_actions.configure(cfg.actions)
    msrv = start_metrics_server(
        cfg.metrics_port, host=args.metrics_host,
        health_probe=lambda: {
            "compute": obs_compute.PROFILER.health(),
            # fast-path coverage next to the compute block (ISSUE 15
            # satellite): a run silently degraded to K=1 unsharded
            # reads differently from a healthy one at the probe
            "fallbacks": obs_health.fallback_block(),
            "health": obs_rules.health_block(),
            # the last reflex dispatches, rule provenance included
            "actions": bus.actions_block()})
    try:
        with failure_context(name=cfg.identity()), \
                profile_trace(args.profile_dir,
                              enabled=bool(args.profile_dir)):
            result = engine.train()
    finally:
        # the rule engine's lifetime is the run's — disarm on EVERY
        # exit path (tests drive several runs per process; a stale
        # engine must not keep evaluating later runs' boundaries
        # against this run's state). The local ``hrules`` handle below
        # still reads the verdict after disarming.
        obs_rules.disarm()
        obs_actions.disarm()  # local ``bus`` handle outlives disarm too
        if cfg.trace_out:
            out = obs_trace.dump()
            if out:
                print(f"[obs] host-span trace written to {out} "
                      "(load in Perfetto / chrome://tracing)",
                      flush=True)
            # the jax.monitoring bridge lives from arm() to disarm()
            obs_trace.disarm()
        if msrv is not None:
            msrv.close()

    # persist the stat accumulators (the reference pickles stat_info at end
    # of training and crashed when the results dir was missing,
    # subavg_api.py:218-220 / subavg/error3437295.err — the logger already
    # created its dir, which is the single source of truth for the layout)
    from neuroimagedisttraining_tpu.utils.logging import _jsonable

    stats_path = os.path.join(engine.log.dir, cfg.identity() + ".stats.json")
    with open(stats_path, "w") as f:
        json.dump(_jsonable({k: v for k, v in engine.stat_info.items()
                             if not k.startswith("final_masks")}),
                  f, default=str)

    # end-of-run health verdict (ISSUE 15): always written (the run
    # report joins it); --health_gate additionally turns a non-ok WORST
    # status into a nonzero exit — a run that diverged and recovered
    # still failed its gate
    verdict = hrules.verdict()
    # the reflex action log rides in the verdict (and from there into
    # run_report): deliberately timestamp-free, so twin seeded chaos
    # runs produce byte-identical blocks (the replayability contract)
    verdict["actions"] = bus.actions_block()
    verdict_path = os.path.join(engine.log.dir,
                                cfg.identity() + ".health.json")
    with open(verdict_path, "w") as f:
        json.dump(verdict, f, indent=1, default=str)

    final = {k: v for k, v in result.items()
             if k in ("final_global", "final_personal", "mask_density")}
    # ONE result line (the last stdout line IS the machine-readable
    # result — tests/test_cli.py's contract); the health summary rides
    # inside it rather than as a second line
    print(json.dumps({
        "identity": cfg.identity(), **final,
        "health": {k: verdict[k] for k in
                   ("status", "worst_status", "alerts_total",
                    "rounds_evaluated")},
        "health_verdict_path": verdict_path}, default=float))
    if args.health_gate and verdict["worst_status"] != "ok":
        # stderr: the LAST stdout line must stay the machine-readable
        # result (tests/test_cli.py's contract)
        print(f"[health] gate FAILED: worst status "
              f"{verdict['worst_status']!r} "
              f"({verdict['alerts_total']} alert(s); see "
              f"{verdict_path})", file=sys.stderr, flush=True)
        return 1
    return 0


if __name__ == "__main__":
    sys.exit(main())
