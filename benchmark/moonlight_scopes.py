"""Device time by Moonlight's layer's own scopes, and its counters (PR 40).

``models/moonlight3d.py`` names its stages from inside (``obs/names.py``
MODEL_SCOPES): latent attention in two (``mla_latent``, ``mla_core``)
inside ``attn``, which keeps W_q, its rotary embedding and W_o; the
leading layer's feed-forward under ``mlp``; the expert sublayer in five
(``router``, ``dispatch``, ``experts``, ``combine``, ``shared_expert``).
As for the four trunks before it (``olmoe_scopes.py``,
``nemotronh_scopes.py``, ``zaya_scopes.py``, ``evabyte_scopes.py``, whose
functions this module uses and does not edit), the classes live in a rules
file of their own, ``metrics/moonlight_scopes.json``: one more partition,
``layer``, of the same busy time through ``scopes.build(..., rules=...)``.

The round driver puts the round's routing on its ``round_log`` span
(``engines/fedavg.py`` ``expert_load``): ``tokens_routed`` (slots over all
64 experts and the five expert layers) and ``rows_held`` (those that
landed on the experts held here: the rows the grouped matmuls multiply).

A program without these scopes or counters (a CNN, the other trunks, the
parent of PR 40) has nothing in any of them: every reader then returns
``None`` and the line leaves the metric out.
"""

from __future__ import annotations

import os

from benchmark import nemotronh_scopes, scopes

RULES = os.path.join(scopes.BENCH, "metrics", "moonlight_scopes.json")
PARTITION = "layer"
KEY = "moonlight_scopes"
ROUND_LOG, DISPATCH = "round_log", "dispatch_program"
#: classes only this model's rules give a program: a table without them is
#: another model's (its ``attn`` or ``experts`` are not this layer's)
OWN = ("mla_core",)


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
    carries the latent attention's scope (another model's program)."""
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

def causal_pairs(tokens: int) -> int:
    """(query, key) pairs of one head over a causal sequence: a query
    reads itself and every earlier token. 11,831,680 at 4,864."""
    return tokens * (tokens + 1) // 2


def core_work(reference, tape, samples: float) -> tuple[float, float]:
    """``(operations, bytes)`` the least a TRAINING pass over ``samples``
    samples asks of scores, softmax and values, every layer and head: the
    tape's causal pairs (:func:`causal_pairs` of its token count) at ``2 x
    (192 + 128)`` operations a pair and head forward, three times that a
    step; q, the heads' keys, the one shared rotary key, v and o read and
    written once a pass, three passes. A later kernel is held to the same
    count: it comes from the reference's tape, not from the program."""
    (tokens,) = next(r["out_spatial"] for r in tape
                     if r["name"].endswith("/mla/q_proj"))
    if reference.core_pairs(tape) != causal_pairs(tokens):
        raise ValueError("the reference's tape does not count the causal "
                         f"pairs of {tokens} tokens")
    return (3.0 * reference.core_flops_per_sample(tape) * samples,
            3.0 * reference.core_bytes_per_sample(tape) * samples)


def mla_core_roofline_pct(spec: dict, ctx: dict):
    """Scores, softmax and values (scope ``mla_core``) against the chip's
    roofline.

    Operations: a head of a 4,864-token volume has 11,831,680 causal
    pairs; at 2 x (192 + 128) = 640 operations a pair, 16 heads and 6
    layers that is 0.727 TFLOP a sample forward, 2.18 TFLOP for training:
    11.1 ms at 197e12. Bytes: q ``[4864, 16 x 192]``, the heads' keys
    ``[4864, 16 x 128]``, the shared rotary key ``[4864, 64]``, v and o
    ``[4864, 16 x 128]`` in bf16, 6 layers, three passes: 0.54 GB a
    sample, 0.66 ms at 819e9 B/s. Intensity 4,000 FLOP/B against the
    chip's 241: the FLOP side bounds it. The seconds hold what plain XLA
    really does: each block's float32 scores written to HBM and read back
    (forward, the layer's rematerialised forward, the block's own
    rematerialisation and backward), the masked part of every diagonal
    block (13.0 M pairs computed for the 11.8 M counted) and the
    exponentials. The share says how many times the least that is; it
    cannot pass 100."""
    if ctx.get("peak") is None or ctx.get("trace") is None:
        return None
    seconds = class_seconds(spec, ctx)
    if not seconds:
        return None
    reference, tape = nemotronh_scopes._reference(spec)
    flops, nbytes = core_work(reference, tape, ctx["trace"]["real_samples"])
    return nemotronh_scopes._roofline_pct(flops, nbytes, seconds, ctx)


def expert_matmul_roofline_pct(spec: dict, ctx: dict):
    """The grouped matmuls over the rows that REALLY landed on the 8 held
    experts, against the chip's roofline, over the seconds under
    ``experts`` (as ``zaya_scopes.expert_matmul_roofline_pct``, under this
    layer's rules).

    Operations: ``rows_held`` of the slice's rounds (the round driver's
    counter, all five expert layers) x (2048 x 2816 + 1408 x 2048) x 2 x
    3 for training. Bytes (``expert_bytes_per_step``): three passes a
    step and layer, each reading the 8 held experts' bf16 weights (0.14
    GB) and moving the landed rows in and out. At the uniform share
    (7,296 rows a step and layer) a step and layer is 0.38 TFLOP (1.9 ms
    at 197e12) and 0.78 GB (0.95 ms): the FLOP side bounds it; below about
    3,400 rows a step and layer the weights' bytes would. The reader takes
    the larger. The seconds also hold the SiLU gate, the float32 -> bf16
    weight casts and the buffer's rows past the runs (14,848 rows are
    multiplied for the 7,296 that land at the uniform share), so the
    share cannot pass 100."""
    if ctx.get("peak") is None or ctx.get("trace") is None:
        return None
    seconds = class_seconds(spec, ctx)
    rows = nemotronh_scopes.slice_sum(ctx, ROUND_LOG, "rows_held")
    steps = nemotronh_scopes.slice_sum(ctx, DISPATCH, "steps_real")
    if not seconds or not rows or not steps:
        return None
    reference, tape = nemotronh_scopes._reference(spec)
    layers = reference.expert_layers(tape)
    nbytes = steps * layers * reference.expert_bytes_per_step(
        tape, rows / (steps * layers))
    return nemotronh_scopes._roofline_pct(
        3.0 * reference.expert_flops_per_row(tape) * rows, nbytes, seconds,
        ctx)


def rows_held_share_pct(spec: dict, ctx: dict):
    """Median over the window's rounds of ``rows_held`` over
    ``tokens_routed`` in percent (12.5 at uniform routing: 8 of 64);
    ``None`` where the run's program is not this model's (no traced
    table says so: the counters alone are the other held-expert trunks'
    too) or no ``round_log`` span of the window carries them."""
    if share_pct({"classes": list(OWN)}, ctx) is None:
        return None
    return nemotronh_scopes.held_rows_share_pct(spec, ctx)
