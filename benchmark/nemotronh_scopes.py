"""Device time by the hybrid trunk's own scopes, and its counters (PR 29).

``models/nemotronh3d.py`` names its stages from inside (``obs/names.py``
MODEL_SCOPES): the state-space mixer in five (``ssm_in_proj``,
``ssm_conv``, ``ssd``, ``ssm_gate_norm``, ``ssm_out_proj``), the expert
layer in five (``router``, ``dispatch``, ``experts``, ``combine``,
``shared_expert``), the GQA layer under ``attn``. As for the OLMoE block
(``olmoe_scopes.py``, whose functions this module uses and does not edit),
the classes live in a rules file of their own,
``metrics/nemotronh_scopes.json``: one more partition, ``trunk``, of the
same busy time through ``scopes.build(..., rules=...)``.

The round driver puts the round's routing on its ``round_log`` span
(``engines/fedavg.py`` ``expert_load``): ``tokens_routed`` (assignments
over all 128 experts and the four expert layers) and ``rows_held`` (those
that landed on the experts held here: the rows the grouped matmuls
multiply).

A program without these scopes or counters (a CNN, OLMoE, the parent of
PR 29) has nothing in any of them: every reader then returns ``None`` and
the line leaves the metric out.
"""

from __future__ import annotations

import os

from benchmark import harness, olmoe_scopes, scopes

RULES = os.path.join(scopes.BENCH, "metrics", "nemotronh_scopes.json")
PARTITION = "trunk"
KEY = "nemotronh_scopes"
ROUND_LOG, DISPATCH = "round_log", "dispatch_program"


def table_of(ctx: dict) -> dict | None:
    """The run's table under the trunk's rules, built once a run."""
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
    """Share of device busy time in ``spec["classes"]`` of the trunk
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


def slice_sum(ctx: dict, span: str, arg: str):
    """Sum of a span argument over the traced slice's rounds: the last
    ``trace["rounds"]`` spans of that name that carry it (the slice is the
    last rounds of the measured call; nothing trains after it). ``None``
    where no span carries the argument."""
    from neuroimagedisttraining_tpu.obs import trace as obs_trace

    rows = [e["args"][arg] for e in obs_trace.TRACER.events()
            if e.get("ph") == "X" and e["name"] == span
            and arg in e.get("args", {})]
    k = int(ctx["trace"]["rounds"])
    return float(sum(rows[-k:])) if len(rows) >= k else None


def _reference(spec: dict):
    reference = harness.load_reference(
        {"reference": spec["reference"], "name": spec["reference"]})
    return reference, reference.published_tape()


def _roofline_pct(flops: float, nbytes: float, seconds: float, ctx: dict):
    """The least time the chip could take (the larger of operations over
    its peak and bytes over its bandwidth) over the seconds it took."""
    peak = ctx["peak"]
    least = max(flops / peak["bf16_flops_per_s"],
                nbytes / peak["hbm_bytes_per_s"]) / ctx["chips"]
    return 100.0 * least / seconds


def ssd_roofline_pct(spec: dict, ctx: dict):
    """The chunked scan alone (scope ``ssd``: softplus(dt), the four
    contractions a chunk, the state scan) against the chip's roofline.

    Operations and bytes from ``reference/nemotronh-abcd.py``
    (``ssd_flops_per_sample``: the causal half of ``C B^T`` and of its
    product with ``x`` a chunk, the chunk states' update and read;
    ``ssd_bytes_per_step``: three passes over ``x``, ``B``, ``C``, ``dt``,
    ``y``), x 3 for training, x the slice's real samples. At the published
    widths a step of 16 is 4 layers x 16 x 3 x 1.76 GFLOP = 0.34 TFLOP (1.7
    ms at 197e12) and 2.55 GB (3.1 ms at 819e9 B/s): intensity 133 FLOP/B
    against the chip's 241, so the BYTES side bounds it and this is a share
    of the bandwidth roofline. The plain-XLA scan materialises the decay
    matrices ``[b, chunks, 128, 128, heads]`` in float32 and runs the full
    128 x 128 products, so it moves and computes several times the least:
    the share says how many."""
    if ctx.get("peak") is None or ctx.get("trace") is None:
        return None
    seconds = class_seconds(spec, ctx)
    if not seconds:
        return None
    reference, tape = _reference(spec)
    samples = ctx["trace"]["real_samples"]
    return _roofline_pct(
        3.0 * reference.ssd_flops_per_sample(tape) * samples,
        reference.ssd_bytes_per_step(tape, samples), seconds, ctx)


def held_expert_matmul_roofline_pct(spec: dict, ctx: dict):
    """The grouped matmuls over the rows that REALLY landed on the held
    experts, against the chip's roofline, over the seconds under
    ``experts``.

    Operations: ``rows_held`` of the slice's rounds (the round driver's
    counter, all four expert layers) x 2 matrices x 2 x 2688 x 1856 x 3
    for training. Bytes (``expert_bytes_per_step``): three passes a step
    and layer, each reading the 8 held experts' bf16 weights (0.16 GB) and
    moving the landed rows in and out. At the uniform share (3,840 rows a
    step and layer) a step is 0.92 TFLOP (4.7 ms at 197e12) and 2.75 GB
    (3.4 ms): the FLOP side bounds it, by little; below about 2,500 rows a
    step and layer the weights' bytes do. The reader takes the larger, so the share
    is of whichever roofline binds at the routing the run had. The seconds
    also hold the relu^2, the weight casts and the kernel's zeroing of the
    55,600 rows that belong to absent experts, so the share cannot pass
    100."""
    if ctx.get("peak") is None or ctx.get("trace") is None:
        return None
    seconds = class_seconds(spec, ctx)
    rows = slice_sum(ctx, ROUND_LOG, "rows_held")
    steps = slice_sum(ctx, DISPATCH, "steps_real")
    if not seconds or not rows or not steps:
        return None
    reference, tape = _reference(spec)
    layers = reference.expert_layers(tape)
    nbytes = steps * layers * reference.expert_bytes_per_step(
        tape, rows / (steps * layers))
    return _roofline_pct(3.0 * reference.expert_flops_per_row(tape) * rows,
                         nbytes, seconds, ctx)


def held_rows_share_pct(spec: dict, ctx: dict):
    """Median over the window's rounds of the assignments that landed on
    the held experts, over all of them, in percent (6.25 at uniform
    routing: 8 of 128)."""
    median = lambda arg: olmoe_scopes.span_arg_median(
        {"span": ROUND_LOG, "arg": arg}, ctx)
    held, routed = median("rows_held"), median("tokens_routed")
    if held is None or not routed:
        return None
    return 100.0 * held / routed
