"""Device time by ZAYA1's layer's own scopes, and its counters (PR 31).

``models/zaya3d.py`` names its stages from inside (``obs/names.py``
MODEL_SCOPES): compressed convolutional attention in four (``cca_proj``,
``cca_conv``, ``cca_mix``, ``attn``), the expert sublayer in four
(``router``, ``dispatch``, ``experts``, ``combine``). As for the two
trunks before it (``olmoe_scopes.py``, ``nemotronh_scopes.py``, whose
functions this module uses and does not edit), the classes live in a rules
file of their own, ``metrics/zaya_scopes.json``: one more partition,
``layer``, of the same busy time through ``scopes.build(..., rules=...)``.

The round driver puts the round's routing on its ``round_log`` span
(``engines/fedavg.py`` ``expert_load``): ``tokens_routed`` (over the 17
router outputs and the five layers), ``rows_held`` (those that landed on
the experts held here: the rows the grouped matmuls multiply) and
``rows_skipped`` (those sent to output 16, which is no expert).

A program without these scopes or counters (a CNN, OLMoE, Nemotron-H, the
parent of PR 31) has nothing in any of them: every reader then returns
``None`` and the line leaves the metric out.
"""

from __future__ import annotations

import os

from benchmark import nemotronh_scopes, olmoe_scopes, scopes

RULES = os.path.join(scopes.BENCH, "metrics", "zaya_scopes.json")
PARTITION = "layer"
KEY = "zaya_scopes"
ROUND_LOG, DISPATCH = "round_log", "dispatch_program"
#: classes only this model's rules give a program: a table without them is
#: another model's (its ``attn`` or ``experts`` are not this layer's)
OWN = ("cca_proj", "cca_conv", "cca_mix")


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
    carries a scope of the compressed attention (another model's
    program)."""
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


def cca_mix_roofline_pct(spec: dict, ctx: dict):
    """The memory-bound part of the compressed attention (scopes
    ``cca_conv`` + ``cca_mix``: the depthwise and the grouped convolution,
    the q-k mean, the value shift, the L2 norm with its temperature,
    rotary) against the chip's roofline.

    Bytes (``reference/zaya1-abcd.py`` ``cca_mix_bytes_per_step``): each of
    the five stages reads the latents it needs and writes its result once,
    three passes for training: 9 x 1280 + 2 x 128 = 11,776 bf16 elements a
    token and pass, 10,240 tokens, 5 layers: 3.62 GB a step, 4.4 ms at
    819e9 B/s. Operations (``cca_mix_flops_per_sample``: the two
    convolutions, 0.66 MFLOP a token) x 3: 0.10 TFLOP a step, 0.5 ms at
    197e12: intensity 28 FLOP/B against the chip's 241, so the BYTES side
    bounds it and this is a share of the bandwidth roofline. The seconds
    hold whatever the compiler really moves (float32 copies for the norm,
    the padded taps, layout changes between the einsum's head-major form
    and the flat latents), so the share says how many times the least that
    is; it cannot pass 100."""
    if ctx.get("peak") is None or ctx.get("trace") is None:
        return None
    seconds = class_seconds(spec, ctx)
    if not seconds:
        return None
    reference, tape = nemotronh_scopes._reference(spec)
    samples = ctx["trace"]["real_samples"]
    return nemotronh_scopes._roofline_pct(
        3.0 * reference.cca_mix_flops_per_sample(tape) * samples,
        reference.cca_mix_bytes_per_step(tape, samples), seconds, ctx)


def expert_matmul_roofline_pct(spec: dict, ctx: dict):
    """The grouped matmuls over the rows that REALLY landed on the 8 held
    experts, against the chip's roofline, over the seconds under
    ``experts``.

    Operations: ``rows_held`` of the slice's rounds (the round driver's
    counter, all five layers) x 3 matrices of 2048 x 2048 x 2 x 3 for
    training. Bytes (``expert_bytes_per_step``): three passes a step and
    layer, each reading the 8 held experts' bf16 weights (0.20 GB) and
    moving the landed rows in and out. At the uniform share (4,819 rows a
    step and layer) a step is 1.82 TFLOP (9.2 ms at 197e12) and 4.50 GB
    (5.5 ms): the FLOP side bounds it; below about 2,400 rows a step and
    layer the weights' bytes would. The reader takes the larger. The
    seconds also hold the SiLU gate, the float32 -> bf16 weight casts and
    the loop's zero-filled sums, so the share cannot pass 100."""
    if ctx.get("peak") is None or ctx.get("trace") is None:
        return None
    seconds = class_seconds(spec, ctx)
    rows = nemotronh_scopes.slice_sum(ctx, ROUND_LOG, "rows_held")
    steps = nemotronh_scopes.slice_sum(ctx, DISPATCH, "steps_real")
    if not seconds or not rows or not steps:
        return None
    reference, tape = nemotronh_scopes._reference(spec)
    layers = reference.layers(tape)
    nbytes = steps * layers * reference.expert_bytes_per_step(
        tape, rows / (steps * layers))
    return nemotronh_scopes._roofline_pct(
        3.0 * reference.expert_flops_per_row(tape) * rows, nbytes, seconds,
        ctx)


def rows_share_pct(spec: dict, ctx: dict):
    """Median over the window's rounds of ``spec["arg"]`` (``rows_held``,
    ``rows_skipped``) over ``tokens_routed``, in percent; ``None`` where no
    ``round_log`` span of the window carries ``rows_skipped`` (a router
    with no skip output: another model)."""
    median = lambda arg: olmoe_scopes.span_arg_median(
        {"span": ROUND_LOG, "arg": arg}, ctx)
    rows, routed = median(spec["arg"]), median("tokens_routed")
    if rows is None or not routed or median("rows_skipped") is None:
        return None
    return 100.0 * rows / routed
