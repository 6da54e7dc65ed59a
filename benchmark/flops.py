"""The benchmark's own count of a model's floating-point operations.

The same arithmetic as the program's ``ops/flops.py`` (a convolution is
2 x MACs per output position x positions, a dense layer 2 x in x out,
training is 3 x the forward pass: forward plus about twice that for the
backward), kept here so that no later PR can move the numerator of a
utilization. It counts the dense model: a sparse mask, padded steps and
rematerialised work are not counted, so utilization is of *useful* work.

The shapes come from the tape that a configuration's plain reference
forward (``benchmark/reference/<config>.py``) records while it is traced
abstractly: nothing is executed.
"""

from __future__ import annotations

import math

TRAINING_FACTOR = 3.0  # forward + backward (2x), the reference's convention


def layer_flops(record: dict) -> float:
    """Operations of one forward pass through one recorded layer, for one
    sample."""
    macs = float(math.prod(record["kernel_shape"]))
    if record["kind"] == "conv":  # kernel [*k, Cin, Cout]
        return 2.0 * macs * float(math.prod(record["out_spatial"]))
    if record["kind"] == "dense":  # kernel [in, out]
        return 2.0 * macs
    raise ValueError(f"unknown layer kind {record['kind']!r}")


def forward_flops(tape: list[dict]) -> float:
    return sum(layer_flops(r) for r in tape)


def training_flops_per_sample(tape: list[dict]) -> float:
    return TRAINING_FACTOR * forward_flops(tape)


def record_tape(forward, params, batch_stats, input_shape) -> list[dict]:
    """Trace ``forward`` abstractly at one sample of ``input_shape`` and
    return the layers it recorded. ``params`` / ``batch_stats`` may be
    arrays or ``ShapeDtypeStruct``s."""
    import jax
    import jax.numpy as jnp

    tape: list[dict] = []
    x = jax.ShapeDtypeStruct((1,) + tuple(input_shape), jnp.uint8)
    jax.eval_shape(lambda p, s, v: forward(p, s, v, tape), params,
                   batch_stats, x)
    return tape
