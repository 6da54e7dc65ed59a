"""Device time by Trinity-Mini's layers' own scopes, and their counters
(PR 44).

``models/trinity3d.py`` names its stages from inside (``obs/names.py``
MODEL_SCOPES): the attention in five (``qk_norm``, ``swa_core`` or
``full_core`` by the layer's kind, ``attn_gate``, and what is left of
``attn``: W_q, W_k, W_v, the rotary embedding, W_o); the leading layer's
feed-forward under ``mlp``; the expert sublayer in five (``router``,
``dispatch``, ``experts``, ``combine``, ``shared_expert``); the four norms
a layer are flax modules outside all of them (class ``norm``). As for the
five trunks before it (``moonlight_scopes.py`` and the files it names,
whose functions this module uses and does not edit), the classes live in a
rules file of their own, ``metrics/trinity_scopes.json``: one more
partition, ``layer``, of the same busy time through ``scopes.build(...,
rules=...)``.

The round driver puts the round's routing on its ``round_log`` span
(``engines/fedavg.py`` ``expert_load``): ``tokens_routed`` (slots over all
128 experts and the four expert layers) and ``rows_held`` (those that
landed on the experts held here: the rows the grouped matmuls multiply).

A program without these scopes or counters (a CNN, the other trunks, the
parent of PR 44) has nothing in any of them: every reader then returns
``None`` and the line leaves the metric out.
"""

from __future__ import annotations

import os

from benchmark import nemotronh_scopes, scopes

RULES = os.path.join(scopes.BENCH, "metrics", "trinity_scopes.json")
PARTITION = "layer"
KEY = "trinity_scopes"
ROUND_LOG, DISPATCH = "round_log", "dispatch_program"
#: classes only this model's rules give a program: a table without either
#: is another model's (its ``attn`` or ``experts`` are not these layers')
OWN = ("swa_core", "full_core")
#: forward operations of one (query, key) pair and query head: the score's
#: 128 products and the value's 128, an add each
PAIR_FLOPS = 2 * (128 + 128)


def table_of(ctx: dict) -> dict | None:
    """The run's table under the layers' rules, built once a run."""
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
    carries either attention core's scope (another model's program)."""
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

def window_pairs(tokens: int, window: int | None) -> int:
    """(query, key) pairs of one head over a causal sequence in which a
    query reads itself and the ``window - 1`` tokens before it (``None``:
    every earlier token): 7,865,344 at 4,864 tokens under 2,048 and
    11,831,680 without."""
    if window is None or window >= tokens:
        return tokens * (tokens + 1) // 2
    return window * (window + 1) // 2 + (tokens - window) * window


def core_work(reference, tape, kind: str,
              samples: float) -> tuple[float, float]:
    """``(operations, bytes)`` the least a TRAINING pass over ``samples``
    samples asks of scores, softmax and values in the layers of ``kind``
    (``sliding_attention`` or ``full_attention``), every query head: the
    tape's pairs of that kind (:func:`window_pairs` of its token count and
    the published window) at ``PAIR_FLOPS`` operations a pair and query
    head forward, three times that a step; q and o ``[T, 32 x 128]``, k
    and v ``[T, 4 x 128]`` moved once a pass, three passes. THE ONE
    COUNTING FUNCTION: whatever implements the core (the kernels, the
    plain blocks, a triangle computed whole and masked) is held to this
    count, which comes from the reference's tape and not from the
    program."""
    cfg = reference.PUBLISHED
    records = reference.core_layers(tape, cfg)[kind]
    by_name = {r["name"]: r for r in tape}
    window = cfg["sliding_window"] if kind == reference.SLIDING else None
    flops = 0.0
    for r in records:
        (tokens,) = by_name[r["name"][:-len("scores")]
                            + "q_proj"]["out_spatial"]
        heads, width = r["kernel_shape"]
        (pairs,) = r["out_spatial"]
        if pairs != window_pairs(tokens, window) or 4 * width != PAIR_FLOPS:
            raise ValueError(
                f"the reference's tape does not count the pairs of {tokens} "
                f"tokens under a window of {window} at heads of 128")
        flops += PAIR_FLOPS * pairs * heads
    return (3.0 * flops * samples,
            3.0 * reference.core_bytes_per_sample(tape, kind, cfg) * samples)


def core_roofline_pct(spec: dict, ctx: dict):
    """Scores, softmax and values of the layers of ``spec["layer_kind"]``
    (scope ``swa_core`` or ``full_core``) against the chip's roofline.

    Operations: a head of a 4,864-token volume has 7,865,344 pairs inside
    a window of 2,048 and 11,831,680 without; at 512 operations a pair, 32
    query heads: 0.129 TFLOP a sample forward for a sliding layer (0.515
    for the four), 0.194 for the full one; three times that a step: 1.546
    and 0.582 TFLOP a sample, 7.85 and 2.95 ms at 197e12. Bytes: q and o
    ``[4864, 4096]``, k and v ``[4864, 512]`` in bf16, three passes: 1.08
    GB and 0.27 GB a sample, 1.31 and 0.33 ms at 819e9 B/s. The FLOP side
    bounds both (1,400 and 2,200 FLOP/B against the chip's 241). The
    seconds hold what the program really does: the masked halves of the
    diagonal's and the window's edge tiles (135 and 190 tiles of 256 x 256
    computed for the 120.0 and 180.5 counted), the exponentials and the
    running maximum beside the products, and in the plain form every
    block's float32 scores written to HBM and read back. The share says
    how many times the least that is; it cannot pass 100."""
    if ctx.get("peak") is None or ctx.get("trace") is None:
        return None
    seconds = class_seconds(spec, ctx)
    if not seconds:
        return None
    reference, tape = nemotronh_scopes._reference(spec)
    flops, nbytes = core_work(reference, tape, spec["layer_kind"],
                              ctx["trace"]["real_samples"])
    return nemotronh_scopes._roofline_pct(flops, nbytes, seconds, ctx)


def expert_matmul_roofline_pct(spec: dict, ctx: dict):
    """The grouped matmuls over the rows that REALLY landed on the 16 held
    experts, against the chip's roofline, over the seconds under
    ``experts`` (as ``moonlight_scopes.expert_matmul_roofline_pct``, under
    these layers' rules).

    Operations: ``rows_held`` of the slice's rounds (the round driver's
    counter, all four expert layers) x (2048 x 2048 + 1024 x 2048) x 2 x
    3 for training. Bytes (``expert_bytes_per_step``): three passes a
    step and layer, each reading the 16 held experts' bf16 weights (0.20
    GB) and moving the landed rows in and out. At the uniform share (9,728
    rows a step and layer) a step and layer is 0.37 TFLOP (1.86 ms at
    197e12) and 0.96 GB (1.17 ms): the FLOP side bounds it; below about
    7,700 rows a step and layer the weights' bytes would. The reader
    takes the larger. The seconds also hold the SiLU gate, the float32 ->
    bf16 weight casts and the buffer's rows past the runs (19,456 rows are
    multiplied for the 9,728 that land at the uniform share), so the
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
    ``tokens_routed`` in percent (12.5 at uniform routing: 16 of 128);
    ``None`` where the run's program is not this model's (no traced
    table says so: the counters alone are the other held-expert trunks'
    too) or no ``round_log`` span of the window carries them."""
    if share_pct({"classes": list(OWN)}, ctx) is None:
        return None
    return nemotronh_scopes.held_rows_share_pct(spec, ctx)
