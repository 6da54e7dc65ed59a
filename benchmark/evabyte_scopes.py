"""Device time by EvaByte's layer's own scopes, and its counters (PR 38).

``models/evabyte3d.py`` names its stages from inside (``obs/names.py``
MODEL_SCOPES): EVA attention in three (``eva_pool``, ``eva_local``,
``eva_remote``) inside ``attn``, which keeps the projections, the rotary
embedding and W_o; the feed-forward under ``mlp``. As for the three trunks
before it (``olmoe_scopes.py``, ``nemotronh_scopes.py``, ``zaya_scopes.py``,
whose functions this module uses and does not edit), the classes live in a
rules file of their own, ``metrics/evabyte_scopes.json``: one more
partition, ``layer``, of the same busy time through ``scopes.build(...,
rules=...)``.

The round driver puts on its ``eval_dispatch`` spans how many sample rows
the evaluation program computes (``rows_run``) for the ``rows_real`` there
are (``engines/base.py`` ``_eval_span_args``).

A program without these scopes or counters (a CNN, the other trunks, the
parent of PR 38) has nothing in any of them: every reader then returns
``None`` and the line leaves the metric out.
"""

from __future__ import annotations

import os

from benchmark import nemotronh_scopes, scopes

RULES = os.path.join(scopes.BENCH, "metrics", "evabyte_scopes.json")
PARTITION = "layer"
KEY = "evabyte_scopes"
EVAL_DISPATCH = "eval_dispatch"
#: classes only this model's rules give a program: a table without them is
#: another model's (its ``attn`` is not this layer's)
OWN = ("eva_local",)


def table_of(ctx: dict) -> dict | None:
    """The run's table under the layer's rules, built once a run."""
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
    """Share of device busy time in ``spec["classes"]`` of the layer
    partition, in percent; ``None`` without a trace, or where no op
    carries EVA's scope (another model's program)."""
    table = table_of(ctx)
    if not table or not table["share_pct"]:
        return None
    shares = table["share_pct"][PARTITION]
    if not any(shares.get(c, 0.0) > 0.0 for c in OWN):
        return None
    value = sum(shares.get(c, 0.0) for c in spec["classes"])
    return value if value > 0.0 else None


def class_seconds(spec: dict, ctx: dict):
    share = share_pct(spec, ctx)
    if share is None:
        return None
    return share / 100.0 * table_of(ctx)["busy_s"]


# ---------- the work, from the reference's shapes ----------

def eva_work(reference, tape, samples: float) -> tuple[float, float]:
    """``(operations, bytes)`` the least a TRAINING pass over ``samples``
    samples asks of EVA, every layer and held head: the mask's pairs at ``4
    d`` operations a pair forward and twice that backward; q, k, v, o and
    the chunks' summaries read and written once a pass, three passes. A
    later kernel is held to the same count: it comes from the reference's
    tape, not from the program."""
    return (3.0 * reference.eva_flops_per_sample(tape) * samples,
            3.0 * reference.eva_bytes_per_sample(tape) * samples)


def mlp_work(reference, tape, samples: float) -> float:
    """Operations of the feed-forward's three matrices over a training
    pass of ``samples`` samples, every layer (forward x 3)."""
    return 3.0 * reference.mlp_flops_per_sample(tape) * samples


def eva_roofline_pct(spec: dict, ctx: dict):
    """EVA (scopes ``eva_pool`` + ``eva_local`` + ``eva_remote``) against
    the chip's roofline.

    Operations: a head of a 4,864-token volume has 4,491,648 causal
    in-window pairs and 458,752 (query, summary) pairs; at 4 x 128
    operations a pair, 8 heads and 4 layers that is 81.1 GFLOP a sample
    forward, 0.243 TFLOP for training: 1.24 ms at 197e12. Bytes: q, k, v
    read and o written once (4 x 4,864 x 1,024 bf16 elements) and the 304
    chunks' summaries written and read (4 x 304 x 1,024), 4 layers, three
    passes: 0.51 GB, 0.62 ms at 819e9 B/s. Intensity 479 FLOP/B against the
    chip's 241: the FLOP side bounds it. The seconds hold what plain XLA
    really does: the ``[L, L]`` float32 scores written to HBM and read
    back, forward, rematerialised and backward, the masked half of every
    window's block computed like the causal half, and the exponentials. The
    share says how many times the least that is; it cannot pass 100."""
    if ctx.get("peak") is None or ctx.get("trace") is None:
        return None
    seconds = class_seconds(spec, ctx)
    if not seconds:
        return None
    reference, tape = nemotronh_scopes._reference(spec)
    flops, nbytes = eva_work(reference, tape, ctx["trace"]["real_samples"])
    return nemotronh_scopes._roofline_pct(flops, nbytes, seconds, ctx)


def dense_mlp_roofline_pct(spec: dict, ctx: dict):
    """The feed-forward's three matrices (scope ``mlp``) against the bf16
    peak: 3 x 2 x 4096 x 11008 x 4,864 tokens x 4 layers = 5.26 TFLOP a
    sample forward, 15.8 TFLOP for training, 80 ms at 197e12; its bytes
    (three bf16 matrices of 90 MB and the activations, 1.4 GB a pass and
    layer at 9,728 tokens) are under a tenth of that in time, so the
    roofline is the FLOP one. The seconds hold the rematerialised gate and
    up products (11 products run for the 9 the count has), SiLU and the
    product with the gate, and the weights' float32 -> bf16 casts."""
    if ctx.get("peak") is None or ctx.get("trace") is None:
        return None
    seconds = class_seconds(spec, ctx)
    if not seconds:
        return None
    reference, tape = nemotronh_scopes._reference(spec)
    flops = mlp_work(reference, tape, ctx["trace"]["real_samples"])
    return nemotronh_scopes._roofline_pct(flops, 0.0, seconds, ctx)


def eval_rows_run_share_pct(spec: dict, ctx: dict):
    """``rows_run / rows_real`` in percent over the ``eval_dispatch`` spans
    that START inside the window (``olmoe_scopes.span_arg_median`` says why
    the start decides); ``None`` where none carries the counts (the parent
    of PR 38) or no tracer was armed."""
    from neuroimagedisttraining_tpu.obs import trace as obs_trace

    tracer = obs_trace.TRACER
    epoch = tracer.epoch_ns / 1e9
    w0, w1 = ctx["window"]
    rows = [e["args"] for e in tracer.events()
            if e.get("ph") == "X" and e["name"] == EVAL_DISPATCH
            and e.get("args", {}).get("rows_real")
            and w0 <= epoch + e["ts"] / 1e6 <= w1]
    if not rows:
        return None
    return 100.0 * sum(a["rows_run"] for a in rows) \
        / sum(a["rows_real"] for a in rows)
