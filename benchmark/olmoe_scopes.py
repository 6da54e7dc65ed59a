"""Device time by the sparse-expert block's own scopes (PR 25).

The block names its stages from inside (``obs/names.py`` MODEL_SCOPES:
``attn``, ``router``, ``dispatch``, ``experts``, ``combine``; the patch
embedding under ``stem``, the read-out under ``head``). ``scopes.json``'s
partitions know nothing of them and may not be edited by the PR that
adds them, so they get a rules file of their own,
``metrics/olmoe_scopes.json``, applied through ``scopes.build(...,
rules=...)`` to the same self-seconds and the same op paths: one more
partition, ``block``, of the same busy time. Evaluation is a class of
its own there, so every other class is the local step's, forward and
backward together.

A program without these scopes (a CNN, the parent of PR 25) has no op
in any of the classes: the readers then return ``None`` and the line
leaves the metric out.
"""

from __future__ import annotations

import os
import statistics

from benchmark import harness, scopes

RULES = os.path.join(scopes.BENCH, "metrics", "olmoe_scopes.json")
PARTITION = "block"
KEY = "olmoe_scopes"


def table_of(ctx: dict) -> dict | None:
    """The run's table under the block's rules, built once a run."""
    if KEY not in ctx:
        tr = ctx.get("trace")
        if tr is None or not tr.get("ops_s"):
            ctx[KEY] = None
        else:
            ctx[KEY] = scopes.build(
                tr["ops_s"],
                scopes.join_live(tr["ops_s"], scopes.live_op_meta(),
                                 set(tr["modules_s"])),
                rules=scopes.load_rules(RULES))
            scopes._publish({"rules": os.path.basename(RULES),
                             **ctx[KEY]})
    return ctx[KEY]


def share_pct(spec: dict, ctx: dict):
    """Share of device busy time in ``spec["classes"]`` of the block
    partition, in percent; ``None`` without a trace or where no op
    carries any of those scopes."""
    table = table_of(ctx)
    if not table or not table["share_pct"]:
        return None
    shares = table["share_pct"][PARTITION]
    value = sum(shares.get(c, 0.0) for c in spec["classes"])
    return value if value > 0.0 else None


def class_seconds(spec: dict, ctx: dict):
    share = share_pct(spec, ctx)
    if share is None:
        return None
    return share / 100.0 * table_of(ctx)["busy_s"]


def expert_matmul_roofline_pct(spec: dict, ctx: dict):
    """The grouped matmuls' useful operations in the traced slice over
    what the chip could do in the seconds it spent under ``experts``.

    Operations: the tape's expert records (``reference/olmoe-abcd.py``
    ``expert_flops_per_sample``: 2 x 8 active experts x 3 matrices x
    2048 x 1024 x 640 tokens = 64.4 GFLOP a sample forward) x 3 for a
    training step x the slice's real samples. At batch 16 a step is
    3.09 TFLOP: 15.7 ms at 197e12.
    Bytes (``expert_bytes_per_step``): three passes, each reading every
    expert's bf16 weights once (0.81 GB) and moving the slot
    activations (81,920 x (2048 + 1024) x 2 B x 3 matrices = 1.51 GB):
    6.95 GB a step, 8.5 ms at 819e9 B/s. The step is compute-bound
    (intensity 445 FLOP/B against the chip's 241), so the roofline is
    the FLOP one and this is a share of it. The seconds include the
    SiLU gate and the weights' float32 -> bf16 casts, which run under
    the same scope: the share cannot pass 100.
    """
    if ctx.get("peak") is None or ctx.get("trace") is None:
        return None
    seconds = class_seconds(spec, ctx)
    if not seconds:
        return None
    reference = harness.load_reference(
        {"reference": spec["reference"], "name": spec["reference"]})
    per_sample = 3.0 * reference.expert_flops_per_sample(
        reference.published_tape())
    flops = per_sample * ctx["trace"]["real_samples"]
    return 100.0 * flops / (seconds * ctx["chips"]
                            * ctx["peak"]["bf16_flops_per_s"])


def span_arg_median(spec: dict, ctx: dict):
    """Median over the window's rounds of a number the round driver put
    on one of its spans (``obs/names.py``); ``None`` where no span of
    the window carries it. The harness stamps a round's end inside that
    round's ``round_log`` span, so the window cuts that span at both of
    its ends: a span is a window's own when it STARTS inside the window
    (``round_spans.window_events`` wants it whole, and in a window of
    one round, as a traced run of this cell has, finds none)."""
    from neuroimagedisttraining_tpu.obs import trace as obs_trace

    tracer = obs_trace.TRACER
    epoch = tracer.epoch_ns / 1e9
    w0, w1 = ctx["window"]
    rows = [e["args"][spec["arg"]] for e in tracer.events()
            if e.get("ph") == "X" and e["name"] == spec["span"]
            and spec["arg"] in e.get("args", {})
            and w0 <= epoch + e["ts"] / 1e6 <= w1]
    return float(statistics.median(rows)) if rows else None
