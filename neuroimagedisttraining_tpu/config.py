"""Configuration dataclasses.

Externalizes the reference's per-entry-point argparse flag sets
(reference: fedml_experiments/standalone/sailentgrads/main_sailentgrads.py:31-127,
main_ditto.py:79,101, main_subavg.py:105-108) into typed, serializable config
objects shared by every algorithm engine. Defaults preserve the reference's
canonical ABCD configuration: 3DCNN model, ABCD dataset, 21 site-clients,
batch 16, 200 communication rounds, SGD lr 0.01 with 0.998/round decay,
weight decay 5e-4, gradient clip 10 (main_sailentgrads.py:61-99;
my_model_trainer.py:209,224).
"""

from __future__ import annotations

import dataclasses
import json
from dataclasses import dataclass, field
from typing import Any


@dataclass(frozen=True)
class OptimConfig:
    """Local-optimizer configuration (reference flags: lr, lr_decay, wd,
    momentum, batch_size, epochs, client_optimizer)."""

    client_optimizer: str = "sgd"  # "sgd" | "adam"
    lr: float = 0.01
    lr_decay: float = 0.998        # per-round exponential: lr * lr_decay**round
    wd: float = 5e-4
    momentum: float = 0.9
    batch_size: int = 16
    epochs: int = 2                # local epochs per round
    grad_clip: float = 10.0        # torch clip_grad_norm_ parity (my_model_trainer.py:224)
    # "shuffle": walk a fresh per-epoch permutation in batch_size strides
    # (reference DataLoader semantics, my_model_trainer.py:213);
    # "replacement": i.i.d. uniform draws per step (rounds 1-3 behavior)
    batch_order: str = "shuffle"
    # Mixed-precision train-step contract (ISSUE 10, core/optim.py):
    # "fp32" — everything float32, bitwise-identical to the pre-ISSUE-10
    # tree; "bf16_mixed" — bf16 compute + activations (the model's
    # flax ``dtype``), fp32 MASTER weights / momentum / loss (flax
    # ``param_dtype`` stays float32, models cast logits back to f32).
    # The FedAvg/codec/secure/checkpoint planes only ever see the fp32
    # master weights — bf16 exists strictly inside the jitted step.
    precision: str = "fp32"       # fp32 | bf16_mixed
    # Fixed loss-scale constant for bf16_mixed (Frostig et al.'s static
    # scaling; bf16's f32-sized exponent rarely needs it, so 1.0 is the
    # pinned default — scale S is mathematically a no-op: loss * S
    # before grad, grads / S after, both in fp32). Must be 1.0 under
    # fp32 (any other value would break the bitwise-unchanged pin).
    loss_scale: float = 1.0
    # Fused mask-apply + clip + momentum + SGD-update tail
    # (ops/fused_update.py): one Pallas pass over params instead of the
    # unfused chain's per-stage HBM round-trips; XLA fallback off-TPU,
    # bit-parity with the optax chain pinned. SGD only.
    fused_update: bool = False


@dataclass(frozen=True)
class DataConfig:
    """Dataset + partitioning configuration."""

    dataset: str = "abcd"          # abcd | cifar10 | cifar100 | tiny | synthetic
    data_dir: str = "./data"
    partition_method: str = "site"  # site | dir | n_cls | my_part | homo | hetero | rescale
    partition_alpha: float = 0.3
    # Synthetic-ABCD knobs (tests / benchmarks without the private cohort).
    synthetic_num_subjects: int = 256
    synthetic_shape: tuple[int, int, int] = (121, 145, 121)
    synthetic_signal: float = 12.0  # class-blob amplitude vs the fixed
    # sigma-8 voxel noise — lower it for harder tasks (run_byz_bench.sh
    # uses a low-signal cohort so a Byzantine slowdown is visible in AUC)
    seed_split: int = 42           # per-site 80/20 split seed (ABCD/data_loader.py:82-86)
    val_fraction: float = 0.0      # >0 adds per-client validation split (FedFomo 9-tuple)


@dataclass(frozen=True)
class SparsityConfig:
    """Sparse-training configuration shared by SalientGrads / DisPFL / SubAvg
    (reference flags: dense_ratio, anneal_factor, erk_power_scale, uniform,
    static, dis_gradient_check, snip_mask, itersnip_iteration,
    stratified_sampling, each_prune_ratio, dist_thresh, acc_thresh)."""

    dense_ratio: float = 0.5
    anneal_factor: float = 0.5
    erk_power_scale: float = 1.0
    uniform: bool = False          # uniform layer sparsity instead of ERK
    static: bool = False           # no mask evolution (DisPFL)
    dis_gradient_check: bool = False
    different_initial: bool = False  # per-client distinct initial masks (DisPFL)
    diff_spa: bool = False         # per-client density cycle 0.2..1.0 (DisPFL)
    snip_mask: bool = True         # SalientGrads dense escape hatch when False
    itersnip_iterations: int = 1
    stratified_sampling: bool = False
    # Sub-FedAvg
    each_prune_ratio: float = 0.1
    dist_thresh: float = 0.001
    acc_thresh: float = 0.5
    save_masks: bool = False


@dataclass(frozen=True)
class FedConfig:
    """Federation topology + schedule (reference flags: client_num_in_total,
    frac, comm_round, cs, active; Ditto lamda/local_epochs)."""

    client_num_in_total: int = 21
    frac: float = 1.0              # fraction of clients sampled per round
    comm_round: int = 200
    cs: str = "random"             # neighbor/topology selector: random | ring | full
    active: float = 1.0            # Bernoulli client-activity (fault injection, DisPFL)
    neighbor_num: int = 5          # gossip fan-out when cs == "random"
    # Ditto
    lamda: float = 0.5
    local_epochs: int = 1
    # FedFomo
    fomo_m: int = 5                # number of models requested per round
    # Robust aggregation (fedml_core/robustness/robust_aggregation.py:32-55;
    # the reference constructs RobustAggregator(args) from defense_type /
    # norm_bound / stddev flags). Byzantine-robust aggregators (ISSUE 5,
    # core/robust.py): trimmed_mean | median | krum | multi_krum |
    # geometric_median replace the weighted mean with an order statistic
    # tolerating up to byz_f arbitrary (value-faulty) clients.
    defense_type: str = "none"     # none | norm_diff_clipping | weak_dp |
    # trimmed_mean | median | krum | multi_krum | geometric_median
    norm_bound: float = 5.0        # clip threshold for the update-norm diff
    stddev: float = 0.05           # weak-DP Gaussian noise stddev
    byz_f: int = 1                 # assumed Byzantine count f: trim depth
    # per side (trimmed_mean), Krum's score neighborhood (needs the
    # sampled cohort n >= f + 3; trimmed_mean/median need 2f < n)
    geomed_iters: int = 8          # fixed Weiszfeld iterations
    # (geometric_median; trace-static so the round stays one program)
    # TurboAggregate secure aggregation (additive shares over GF(p))
    mpc_n_shares: int = 3          # shares per client update (paper: one
    # per neighbor group)
    mpc_frac_bits: int = 16        # fixed-point fraction bits for GF(p)
    # quantization
    # "device": the quantize/share/accumulate pipeline runs as jitted
    # uint32 mod-p ops on the TPU's VPU, fused with the round (no host
    # round-trip); "host": the numpy path that models the client<->server
    # communication boundary (the multi-aggregator cross-silo deployment
    # always uses the host toolkit — it crosses real process boundaries)
    mpc_backend: str = "device"
    # Secure QUANTIZED aggregation (privacy/secure_quant.py, ISSUE 8):
    # uploads become field-element frames in GF(p) for the largest prime
    # below 2^field_bits — one wire-dtype residue per parameter plus
    # seed-expanded mask slots, vs the dense secure protocol's n_shares
    # int64 stacks. These fields mirror distributed/run.py's
    # --secure_quant* flags (the encoded secure wire lives on the
    # cross-silo/async control planes; the simulated engines' jitted
    # counterpart is ops/mpc_device.py at this same (p, frac_bits)).
    secure_quant: bool = False
    secure_quant_field_bits: int = 16
    secure_quant_frac_bits: int = 10
    # Round-level differential privacy for the dpsgd engine (privacy/
    # accountant.py, ISSUE 8): every client's post-training update delta
    # vs its consensus point is clipped to dp_clip and noised with
    # N(0, (dp_sigma * dp_clip)^2) INSIDE the jitted round (keys folded
    # from the config seed), and the RDP accountant reports the running
    # per-silo (epsilon, dp_delta) in stat_info. 0 disables; dp_sigma>0
    # requires dp_clip>0 (the clip IS the sensitivity bound).
    dp_clip: float = 0.0
    dp_sigma: float = 0.0
    dp_delta: float = 1e-5
    # Epsilon budget for the built-in DP health rules (obs/rules.py,
    # ISSUE 15): > 0 arms dp-budget-exceeded (critical once the running
    # epsilon crosses it) and dp-burn-rate (warn when a round burns
    # over 2x the uniform budget/comm_round rate). Purely a verdict
    # threshold — the accountant itself never stops at a budget.
    dp_epsilon_budget: float = 0.0
    # Deterministic fault injection + tolerance (faults/, ISSUE 2).
    # fault_spec grammar: "crash:RANK@ROUND,crash_prob:P,straggle:P:MAX_S,
    # drop:P,dup:P,disconnect:P,byz:RANK@ROUND:KIND,preempt:NDEV@ROUND"
    # (faults/schedule.parse_fault_spec); one config seed replays the
    # identical fault trace in the simulated engines AND the
    # multiprocess federation. preempt: is the elastic-plane device loss
    # (ISSUE 20): the engine shrinks client_mesh to NDEV survivors and
    # resumes from the last checkpoint instead of dying.
    fault_spec: str = ""
    # Model-update wire codec (codec/, ISSUE 3): stages joined by '+'
    # from {delta, sparse, quant, quant16} or "none" (dense wire). In
    # the simulated engines the codec's lossy value transform is applied
    # to client updates BEFORE aggregation (jitted, codec/device.py) so
    # an in-process run aggregates exactly what a cross-silo federation
    # shipping encoded frames would; bytes ride stat_info
    # ("sum_comm_bytes" encoded vs "sum_comm_bytes_dense").
    wire_codec: str = "none"
    wire_topk_ratio: float = 0.25  # top-k keep fraction for dense engines
    round_deadline: float = 0.0    # s; >0 arms the cross-silo per-round deadline
    quorum: int = 0                # min uploads to aggregate at deadline; 0 = all
    # Async buffered control plane (ISSUE 7, asyncfl/): the cross-silo
    # server becomes a FedBuff-style buffered aggregator — uploads
    # accepted continuously, aggregated every buffer_k arrivals with
    # polynomial staleness weighting (1 + tau)^-staleness_alpha, and
    # uploads staler than max_staleness versions dropped at admission.
    # The simulated in-process engines stay round-synchronous (the
    # buffer is a control-plane construct); these fields mirror
    # distributed/run.py's flags like round_deadline/quorum do.
    async_server: bool = False
    buffer_k: int = 0              # aggregate every K uploads; 0 = cohort size
    staleness_alpha: float = 0.5   # FedBuff polynomial staleness exponent
    max_staleness: int = 20        # admission bound (and codec-ref ring depth)
    heartbeat_interval: float = 0.0  # s; >0 makes silo clients beat liveness
    heartbeat_timeout: float = 0.0   # s; >0 marks silent clients suspect
    # Cohort sharding (ISSUE 6, parallel/cohort.py): when > 0, the
    # sampled-client axis of every jitted round program shards over a
    # client mesh of exactly this many devices (one shard_map per round:
    # per-device local training on the client shards, trained stacks
    # all-gathered, aggregation/defense/codec tail on replicated full
    # stacks — bitwise-equal to the unsharded round). Sampled sets that
    # do not tile the mesh (the flagship 21 sites on 8 devices) pad with
    # zero-weight rows. Engines whose rounds cross the host or exchange
    # per-client state outside the fedavg/salientgrads shape — and the
    # streaming/two-level-mesh/single-device modes — fall back to the
    # unsharded round with a logged reason; a mismatch with the
    # constructed mesh size is a startup error.
    client_mesh: int = 0
    # Evaluation cadence
    frequency_of_the_test: int = 1
    ci: bool = False               # CI mode: evaluate client 0 only

    @property
    def client_num_per_round(self) -> int:
        # parity: main_sailentgrads.py:234
        return max(1, int(self.client_num_in_total * self.frac))


@dataclass(frozen=True)
class ExperimentConfig:
    """Top-level experiment config = the reference's full flag surface."""

    model: str = "3DCNN"           # 3DCNN | 3DCNN_deeper | 3DCNN_regression | resnet3d | resnet18 | ...
    num_classes: int = 1           # 1 => BCE-with-logits (ABCD sex), >1 => CE
    algorithm: str = "fedavg"
    seed: int = 1024
    tag: str = "exp"
    data: DataConfig = field(default_factory=DataConfig)
    optim: OptimConfig = field(default_factory=OptimConfig)
    fed: FedConfig = field(default_factory=FedConfig)
    sparsity: SparsityConfig = field(default_factory=SparsityConfig)
    # TPU execution. Compute dtype is ``optim.precision`` (the old
    # param_dtype/compute_dtype strings were dead config — nothing
    # consumed them; the precision contract in core/optim.py replaces
    # them with a single validated knob).
    mesh_shape: tuple[int, ...] = ()   # () => all visible devices on one "clients" axis
    remat: str = "auto"            # auto | none | stem | all — 3D-model
    # rematerialization policy (PROFILE.md); auto picks from samples
    # in flight per device (build_experiment)
    # Autotune recipe applied at startup (tune/recipe.py, ISSUE 19):
    # path to a committed bench_matrix/recipes/<device_kind>.json or
    # "auto" (resolve by visible device kind); "" = none. Recorded so a
    # run's config names the recipe that defaulted its knobs.
    recipe: str = ""
    checkpoint_dir: str = ""
    checkpoint_every: int = 0          # rounds; 0 disables
    log_dir: str = "LOG"
    # Observability (obs/, ISSUE 9). All off-by-default-cheap; none of
    # these may ever add a host sync or clock read inside a jitted body
    # (the obs-discipline lint family enforces it).
    trace_out: str = ""            # Chrome trace-event JSON path; ""=off
    metrics_port: int = 0          # /metrics + /healthz port; 0 = off
    flight_events: int = 256       # flight-recorder ring capacity
    # Training-health plane (ISSUE 15). health_stats arms the
    # in-dispatch federation-statistics leg on every declared round
    # program (engines/program.py -> obs/health.py): per-client update
    # norms, cosine-to-aggregate, dispersion, global norms, mask health
    # — computed inside the jitted round, fetched only in the existing
    # batched host-boundary device_get (armed-vs-disarmed rounds are
    # BITWISE identical; zero added syncs). health_rules names a JSON
    # manifest extending the built-in anomaly rules (obs/rules.py);
    # health_gate makes the CLI exit nonzero when the run's worst
    # health status was not "ok". metrics_out appends one registry
    # JSONL record per round (with monotonic round/seq join keys) for
    # analysis/run_report.py.
    health_stats: bool = False
    health_rules: str = ""
    health_gate: bool = False
    metrics_out: str = ""
    # Reflex plane (ISSUE 20, obs/actions.py): what a firing rule's
    # declared action is allowed to DO — "off" (no dispatch), "dry_run"
    # (log what WOULD fire; the default, so nothing changes behavior
    # silently), "on" (registered handlers run: quarantine, defense
    # escalation, buffer adaptation, freeze-and-rollback).
    actions: str = "dry_run"
    # streaming mode: clients per host-fetched chunk for streamed eval /
    # phase-1 scoring / chunked DisPFL rounds; 0 = auto (mesh size or 4)
    stream_chunk_clients: int = 0

    def identity(self) -> str:
        """Experiment-identity string encoding the config, mirroring the
        reference's identity-string construction (main_sailentgrads.py:202-242)."""
        d, o, f, s = self.data, self.optim, self.fed, self.sparsity
        parts = [
            self.algorithm, d.dataset, self.model,
            f"c{f.client_num_in_total}", f"frac{f.frac}", f"r{f.comm_round}",
            f"e{o.epochs}", f"b{o.batch_size}", f"lr{o.lr}", f"dec{o.lr_decay}",
            f"wd{o.wd}", f"part-{d.partition_method}{d.partition_alpha}",
            f"dr{s.dense_ratio}", f"seed{self.seed}", self.tag,
        ]
        return "_".join(str(p) for p in parts)

    def to_json(self) -> str:
        return json.dumps(dataclasses.asdict(self), default=str, sort_keys=True)

    @staticmethod
    def from_dict(d: dict[str, Any]) -> "ExperimentConfig":
        def sub(cls, key):
            v = d.get(key, {})
            if isinstance(v, cls):
                return v
            fields = {f.name for f in dataclasses.fields(cls)}
            return cls(**{k: tuple(x) if isinstance(x, list) else x
                          for k, x in v.items() if k in fields})

        top = {k: v for k, v in d.items()
               if k in {f.name for f in dataclasses.fields(ExperimentConfig)}
               and k not in ("data", "optim", "fed", "sparsity")}
        if "mesh_shape" in top and isinstance(top["mesh_shape"], list):
            top["mesh_shape"] = tuple(top["mesh_shape"])
        return ExperimentConfig(
            data=sub(DataConfig, "data"), optim=sub(OptimConfig, "optim"),
            fed=sub(FedConfig, "fed"), sparsity=sub(SparsityConfig, "sparsity"),
            **top,
        )
