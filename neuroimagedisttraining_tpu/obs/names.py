"""The declared metric-name table: every ``nidt_*`` series, one home.

ISSUE 15 (health-rule-discipline): the anomaly-rule engine
(obs/rules.py) turns metric names into VERDICTS, so a typo'd name in a
rule manifest must fail at startup against a known-names list — which
only works if the list actually covers every name the tree publishes.
This module is that list. Each metric name is declared here ONCE as a
constant; instrumentation sites outside ``obs/`` spell the constant,
never the string (nidtlint ``health-metric-literal`` fences the
literal spelling), so a name cannot drift out of the declared set
without the lint catching it.

Registration (kind, labels, help) stays at the instrumentation site —
this table owns NAMES, not schemas: the registry's idempotent
``counter/gauge/histogram`` calls already police kind/label collisions
per process, and centralizing help strings here would put the
documentation a package away from the measurement.
"""

from __future__ import annotations

# -- control-plane transports (distributed/comm.py) --
COMM_BYTES_SENT = "nidt_comm_bytes_sent_total"
COMM_BYTES_RECV = "nidt_comm_bytes_recv_total"
COMM_FRAMES_SENT = "nidt_comm_frames_sent_total"
COMM_FRAMES_RECV = "nidt_comm_frames_recv_total"

# -- synchronous cross-silo server (distributed/cross_silo.py) --
SYNC_UPLOADS = "nidt_sync_uploads_total"
SYNC_ROUND_WALL = "nidt_sync_round_wall_seconds"
SYNC_QUORUM_WAIT = "nidt_sync_quorum_wait_seconds"
SERVER_ROUND = "nidt_server_round"
SERVER_SUSPECTS = "nidt_server_suspects"
BYZ_STRIKES = "nidt_byz_strikes_total"
BYZ_QUARANTINES = "nidt_byz_quarantines_total"
DP_EPSILON_SILO = "nidt_dp_epsilon_silo"

# -- async buffered server (asyncfl/server.py) --
ASYNC_UPLOADS = "nidt_async_uploads_total"
ASYNC_STALENESS = "nidt_async_staleness"
ASYNC_BUFFER_OCCUPANCY = "nidt_async_buffer_occupancy"
ASYNC_BUFFER_K_EFF = "nidt_async_buffer_k_eff"

# -- selector socket core (asyncfl/loop.py) --
SELECTOR_CONNECTIONS = "nidt_selector_connections"
SELECTOR_WRITE_QUEUE = "nidt_selector_write_queue_frames"
BACKPRESSURE_STALLS = "nidt_backpressure_stalls_total"

# -- sharded ingest plane (asyncfl/ingest.py) --
INGEST_HEARTBEATS_SUPPRESSED = "nidt_ingest_heartbeats_suppressed"
INGEST_PENDING_UPLOADS = "nidt_ingest_pending_uploads"
INGEST_WORKERS_LIVE = "nidt_ingest_workers_live"
INGEST_PARTIALS = "nidt_ingest_partials_total"
INGEST_WORKER_UPLOADS = "nidt_ingest_worker_uploads_total"

# -- hierarchical aggregation tier (asyncfl/region.py, ISSUE 18) --
REGION_STALENESS = "nidt_region_staleness"
REGION_PARTIAL_AGE = "nidt_region_partial_age_s"

# -- telemetry fan-in (obs/fanin.py) --
UPLOAD_STAGE_MS = "nidt_upload_stage_ms"
CLIENT_RTT_MS = "nidt_client_rtt_ms"
OBS_WORKER_SNAPSHOT_AGE = "nidt_obs_worker_snapshot_age_s"
OBS_WORKER_ALIVE = "nidt_obs_worker_alive"

# -- compute-plane profiler (obs/compute.py) --
COMPILES_TOTAL = "nidt_compiles_total"
RECOMPILES_TOTAL = "nidt_recompiles_total"
DISPATCH_MS = "nidt_dispatch_ms"
SUSTAINED_TFLOPS = "nidt_sustained_tflops"
MFU = "nidt_mfu"
XLA_FLOPS = "nidt_xla_flops"
FLOPS_PARITY_RATIO = "nidt_flops_parity_ratio"
HBM_PEAK_BYTES = "nidt_hbm_peak_bytes"

# -- engine host boundaries (engines/base.py, engines/program.py) --
STAT = "nidt_stat"
DP_EPSILON = "nidt_dp_epsilon"
DP_EPSILON_PER_ROUND = "nidt_dp_epsilon_per_round"
ENGINE_ROUND = "nidt_engine_round"
FALLBACK_TOTAL = "nidt_fallback_total"

# -- experiment metrics (utils/logging.py) --
EXP_METRIC = "nidt_exp_metric"
EXP_ROUND = "nidt_exp_round"

# -- streamed feed (data/stream.py) --
STREAM_TRANSFER = "nidt_stream_transfer"

# -- training-health plane (ISSUE 15: obs/health.py publishes, the
#    stats are computed inside the round body by engines/program.py) --
HEALTH_UPDATE_NORM = "nidt_health_update_norm"
HEALTH_UPDATE_NORM_MAX = "nidt_health_update_norm_max"
HEALTH_UPDATE_NORM_MED = "nidt_health_update_norm_med"
HEALTH_COSINE_MIN = "nidt_health_cosine_min"
HEALTH_COSINE_MEAN = "nidt_health_cosine_mean"
HEALTH_DIVERGENCE = "nidt_health_divergence"
HEALTH_PARAM_NORM = "nidt_health_param_norm"
HEALTH_AGG_UPDATE_NORM = "nidt_health_agg_update_norm"
HEALTH_MASK_DENSITY = "nidt_health_mask_density"
HEALTH_MASK_OVERLAP = "nidt_health_mask_overlap"
HEALTH_MASK_CHURN = "nidt_health_mask_churn"
HEALTH_ROUND = "nidt_health_round"

# -- serving plane (serve/engine.py, serve/worker.py, serve/server.py) --
SERVE_LATENCY_MS = "nidt_serve_latency_ms"
SERVE_BATCH_OCCUPANCY = "nidt_serve_batch_occupancy"
SERVE_QUEUE_DEPTH = "nidt_serve_queue_depth"
SERVE_REQUESTS = "nidt_serve_requests_total"
SERVE_WORKERS_LIVE = "nidt_serve_workers_live"
SERVE_WORKER_REQUESTS = "nidt_serve_worker_requests_total"

# -- anomaly-rule engine (obs/rules.py) --
ALERT = "nidt_alert"

# -- reflex plane (obs/actions.py, ISSUE 20): rule->action dispatches
#    by action name and outcome status (applied / dry_run / unhandled /
#    skipped / error) --
ACTIONS_TOTAL = "nidt_actions_total"

# -- autotuner recipes (tune/recipe.py): the loaded recipe's recorded
#    score, published so the mfu-below-recipe drift rule's threshold is
#    scrapeable next to the live nidt_mfu it is compared against --
RECIPE_SCORE = "nidt_recipe_score"

# ---------------------------------------------------------------------------
# host span names (obs/trace.py) of the round driver and the streamed
# feed. Each child of ROUND starts with a prefix benchmark/harness.py's
# GAP_SPANS admits ("round", "dispatch", "eval_"), so a device-idle gap
# is named by the layer whose span covers it; a span that blocks on the
# device ends in "_sync" (round_host_busy_ms subtracts exactly those).
# ---------------------------------------------------------------------------
SPAN_ROUND = "round"                      # one whole loop iteration
SPAN_ROUND_PROLOGUE = "round_prologue"    # sampling, rngs, byz plan, lr
SPAN_DISPATCH_PROGRAM = "dispatch_program"  # the enqueue of one program
SPAN_EVAL_DISPATCH = "eval_dispatch"      # the enqueue of an eval jit
SPAN_EVAL_SYNC = "eval_sync"              # the blocking _summarize read
SPAN_CODEC_SYNC = "round_codec_sync"      # --wire_codec host snapshots
SPAN_ROUND_FLUSH = "round_flush"          # _flush_nonfinite + reflexes
SPAN_FLUSH_SYNC = "round_flush_sync"      # its batched device_get
SPAN_ROUND_LOG = "round_log"              # log.metrics, history
SPAN_ROUND_CHECKPOINT = "round_checkpoint"
SPAN_FEED_WAIT = "feed_wait"              # driver waits on the reader
SPAN_FEED_GATHER = "feed_gather"          # reader thread: host gather
SPAN_FEED_PUT = "feed_put"                # reader thread: device_put
# what a train() does outside its rounds (ISSUE 35): restore /
# init_global_state / accumulators up to the first round, SalientGrads'
# phase 1 inside it, and fine-tune + final evaluations + the -1 log row
SPAN_TRAIN_INIT = "train_init"
SPAN_MASK_PHASE = "mask_phase"
SPAN_FINAL_PASS = "final_pass"
# JAX's own build events, bridged from jax.monitoring while the tracer
# is armed (obs/trace.py _JaxBridge): one span a trace / lowering /
# backend compile / persistent-cache fetch, on the compiling thread
SPAN_JAX_TRACE = "jax_trace"
SPAN_JAX_LOWER = "jax_lower"
SPAN_JAX_COMPILE = "jax_compile"
SPAN_JAX_CACHE_FETCH = "jax_cache_fetch"

#: the bridged build spans: they may lie anywhere a program is first
#: called, inside a stage of a round too
JAX_BUILD_SPANS: tuple[str, ...] = (
    SPAN_JAX_TRACE, SPAN_JAX_LOWER, SPAN_JAX_COMPILE, SPAN_JAX_CACHE_FETCH)

#: the children a resident round's iteration is tiled by, in loop order
ROUND_CHILD_SPANS: tuple[str, ...] = (
    SPAN_ROUND_PROLOGUE, SPAN_DISPATCH_PROGRAM, SPAN_EVAL_DISPATCH,
    SPAN_EVAL_SYNC, SPAN_ROUND_FLUSH, SPAN_ROUND_LOG,
    SPAN_ROUND_CHECKPOINT)

#: the counters a span carries as arguments, beside the ``round`` id it
#: takes from the iteration's span: host integers and names the driver
#: already holds where the work is enqueued (no device read), written
#: only while the tracer is armed. ``dispatch_program``: what the round
#: program trains and where (``FederatedEngine._note_round_counts``).
#: ``eval_dispatch``: which evaluation program, and where
#: ``FederatedEngine._per_client`` places its client rows: ``placement``
#: ``stacked`` (one ``vmap``) / ``sharded`` (each chip loops over the rows
#: it holds) / ``folded`` (one row after another), ``rows`` handed to the
#: program, and ``rows_a_chip``, what one chip's loop walks when sharded
#: (every row otherwise); ``rows_run``, the SAMPLE rows its loops compute
#: (each client's rows as the balanced batches under the cap,
#: core/trainer.py ``eval_batches``) for the ``rows_real`` samples there
#: are. The ``jax_*`` spans: ``program`` is JAX's
#: ``fun_name`` (the traced function; ``jit(<name>)`` from lowering on),
#: ``cache`` the persistent cache's answer where it gave one.
ARGS_BY_SPAN: dict[str, tuple[str, ...]] = {
    SPAN_DISPATCH_PROGRAM: (
        "program", "engine", "rounds", "samples_real", "steps_real",
        "steps_run", "steps_skipped", "chip_steps_max", "chip_steps_mean",
        "placement"),
    SPAN_EVAL_DISPATCH: (
        "program", "split", "placement", "rows", "rows_a_chip", "rows_run",
        "rows_real"),
    SPAN_JAX_TRACE: ("program",),
    SPAN_JAX_LOWER: ("program",),
    SPAN_JAX_COMPILE: ("program", "cache"),
    SPAN_JAX_CACHE_FETCH: ("program",),
}

# ---------------------------------------------------------------------------
# device scope names (jax.named_scope): compile-time metadata on every
# op traced inside, read back from a profiler trace's op metadata
# (benchmark/scopes.py classifies by them; benchmark/scopes.json is
# checked against this table). Flax names its own modules (f1..f4,
# layer1_0, conv, bn, fc1); these cover what is not a module.
# ---------------------------------------------------------------------------
# round program (engines/program.py)
SCOPE_GATHER = "gather"
SCOPE_LOCAL_TRAIN = "local_train"
SCOPE_ATTACK = "attack"
SCOPE_CODEC = "codec"
SCOPE_AGGREGATE = "aggregate"
SCOPE_STATE_UPDATE = "state_update"
SCOPE_EPILOGUE = "epilogue"
# local step (core/trainer.py)
SCOPE_BATCH_PREP = "batch_prep"
SCOPE_FWD_BWD = "fwd_bwd"
SCOPE_CLIP = "clip"
SCOPE_UPDATE = "update"
SCOPE_MASK_APPLY = "mask_apply"
# local step, model (models/neuro3d.py)
SCOPE_STEM = "stem"    # first stage + its pool, both model families
SCOPE_POOL0 = "pool0"  # the stem's pool, nested inside SCOPE_STEM
SCOPE_POOL1 = "pool1"
SCOPE_POOL2 = "pool2"
SCOPE_HEAD = "head"
# evaluation, mask pipeline, collectives
SCOPE_EVAL = "eval"
SCOPE_SNIP_SCORES = "snip_scores"
SCOPE_TOPK_MASK = "topk_mask"
SCOPE_COHORT_GATHER = "cohort_gather"

#: every device scope of the table above
DEVICE_SCOPES: frozenset[str] = frozenset(
    v for k, v in list(globals().items()) if k.startswith("SCOPE_"))

# ---------------------------------------------------------------------------
# device scopes of the sparse-expert transformer block (models/olmoe3d.py,
# PR 25). A second table: benchmark/scopes.json's scope_names is pinned to
# DEVICE_SCOPES above and its classes know nothing of these, so in the
# existing partitions the block's ops read as forward / backward (phase)
# and none (stage); benchmark/metrics/olmoe_scopes.json gives them classes
# of their own. The patch embedding reuses SCOPE_STEM, the read-out
# SCOPE_HEAD. The next benchmark PR folds both tables into one.
# ---------------------------------------------------------------------------
SCOPE_ATTN = "attn"          # norm'd q/k/v, RoPE, causal softmax, o_proj
SCOPE_ROUTER = "router"      # float32 logits, softmax, top-k, aux loss
SCOPE_DISPATCH = "dispatch"  # sort of the k*T slots by expert + gather
SCOPE_EXPERTS = "experts"    # the three grouped matmuls and the SiLU gate
SCOPE_COMBINE = "combine"    # un-sort and the weighted sum over k

# the hybrid trunk's further stages (models/nemotronh3d.py, PR 29;
# benchmark/metrics/nemotronh_scopes.json): the Mamba-2 mixer in five,
# and the expert layer's shared expert. Its GQA layer reuses SCOPE_ATTN,
# its held experts the four expert scopes above.
SCOPE_SSM_IN_PROJ = "ssm_in_proj"      # u W_in and the split into z, xBC, dt
SCOPE_SSM_CONV = "ssm_conv"            # causal depthwise conv, SiLU, split
SCOPE_SSD = "ssd"                      # softplus(dt), the chunked scan (ops/ssd.py)
SCOPE_SSM_GATE_NORM = "ssm_gate_norm"  # y * silu(z), grouped RMSNorm
SCOPE_SSM_OUT_PROJ = "ssm_out_proj"    # y W_out
SCOPE_SHARED_EXPERT = "shared_expert"  # the relu^2 MLP every token takes

# the compressed convolutional attention's stages before its softmax
# (models/zaya3d.py, PR 31; benchmark/metrics/zaya_scopes.json). Scores,
# softmax, values and W_o reuse SCOPE_ATTN, the router MLP SCOPE_ROUTER,
# the held experts the three expert scopes above.
SCOPE_CCA_PROJ = "cca_proj"  # a W_q, a W_k, a W_v1, a W_v2 into the latents
SCOPE_CCA_CONV = "cca_conv"  # the depthwise and the per-head grouped conv
SCOPE_CCA_MIX = "cca_mix"    # q-k mean, value shift, L2 norm + temperature, rotary

# EVA attention's three stages and the dense gated feed-forward
# (models/evabyte3d.py, PR 38; benchmark/metrics/evabyte_scopes.json). The
# three lie inside SCOPE_ATTN, which keeps the projections, the rotary
# embedding and W_o.
SCOPE_EVA_POOL = "eva_pool"      # the chunks' summary keys and values
SCOPE_EVA_LOCAL = "eva_local"    # scores, exp and values inside a window; 1 / Z
SCOPE_EVA_REMOTE = "eva_remote"  # the same against earlier windows' summaries
SCOPE_MLP = "mlp"                # gate, up, SiLU, down

# latent attention of the reconstructing kind (models/moonlight3d.py, PR
# 40; benchmark/metrics/moonlight_scopes.json). Both lie inside SCOPE_ATTN,
# which keeps W_q, its rotary embedding and W_o. The leading dense layer's
# feed-forward reuses SCOPE_MLP, the two shared experts
# SCOPE_SHARED_EXPERT, the held experts the four expert scopes above.
SCOPE_MLA_LATENT = "mla_latent"  # W_dkv, the latent's norm, the shared key's rotary, W_ukv
SCOPE_MLA_CORE = "mla_core"      # scores, softmax and values of every query block

# attention of two kinds in one trunk, with per-head QK norms and a gated
# output (models/trinity3d.py, PR 44; benchmark/metrics/trinity_scopes.json).
# All four lie inside SCOPE_ATTN, which keeps W_q, W_k, W_v, the rotary
# embedding and W_o. The leading dense layer's feed-forward reuses
# SCOPE_MLP, the shared expert SCOPE_SHARED_EXPERT, the held experts the
# four expert scopes above.
SCOPE_SWA_CORE = "swa_core"    # scores, softmax and values of a sliding-window layer
SCOPE_FULL_CORE = "full_core"  # the same of a full-attention layer
SCOPE_QK_NORM = "qk_norm"      # the per-head RMS norms of q and k
SCOPE_ATTN_GATE = "attn_gate"  # W_g, the sigmoid and its product with the heads' output

#: the model scopes of the six tables above (disjoint from DEVICE_SCOPES)
MODEL_SCOPES: frozenset[str] = frozenset(
    (SCOPE_ATTN, SCOPE_ROUTER, SCOPE_DISPATCH, SCOPE_EXPERTS,
     SCOPE_COMBINE, SCOPE_SSM_IN_PROJ, SCOPE_SSM_CONV, SCOPE_SSD,
     SCOPE_SSM_GATE_NORM, SCOPE_SSM_OUT_PROJ, SCOPE_SHARED_EXPERT,
     SCOPE_CCA_PROJ, SCOPE_CCA_CONV, SCOPE_CCA_MIX,
     SCOPE_EVA_POOL, SCOPE_EVA_LOCAL, SCOPE_EVA_REMOTE, SCOPE_MLP,
     SCOPE_MLA_LATENT, SCOPE_MLA_CORE,
     SCOPE_SWA_CORE, SCOPE_FULL_CORE, SCOPE_QK_NORM, SCOPE_ATTN_GATE))

# A layout marker, not a stage: ops/stemconv.py's stem block names the ops
# of its batched rule (the client-merged lanes a client-axis ``vmap``
# selects, PR 37) with it, inside SCOPE_STEM. It belongs to neither table:
# no class reads it, the ``[scopes]`` table of a traced run shows it as
# ``.../stem/f0/merged/...`` rows where that form was traced.
SCOPE_STEM_MERGED = "merged"

#: every declared metric name — the set obs/rules.py validates rule
#: manifests against at startup (unknown names fail with this list)
DECLARED: frozenset[str] = frozenset(
    v for v in list(globals().values())
    if isinstance(v, str) and v.startswith("nidt_"))
