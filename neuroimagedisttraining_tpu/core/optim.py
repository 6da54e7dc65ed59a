"""Local optimizers with torch-parity semantics + the precision contract.

The reference builds ``torch.optim.SGD(lr=args.lr * args.lr_decay**round,
momentum, weight_decay)`` fresh each round and clips gradients to global-norm
10 before the step (my_model_trainer.py:209, 224-225). Torch SGD applies lr
AFTER the momentum accumulation: ``buf = m*buf + (g + wd*p); p -= lr*buf``.
We reproduce that exactly by running the optax chain at unit lr and scaling
the final update by the per-round lr — so lr can be a traced scalar argument
of the jitted round program instead of a fresh optimizer object.

Precision contract (ISSUE 10): ``OptimConfig.precision`` picks the train
step's COMPUTE dtype only. Under ``bf16_mixed`` the flax modules run conv /
matmul / norm in bfloat16 (``dtype=bf16``) while every parameter, momentum
buffer, and the loss stay float32 — flax's ``param_dtype`` default keeps
master weights f32 and casts per-apply, the models cast logits back to f32,
and the optimizer below therefore always sees f32 grads against f32 params.
Everything outside the jitted step (FedAvg aggregation, the wire codec,
secure aggregation, checkpoints) sees ONLY the f32 master weights. A fixed
``loss_scale`` constant (static scaling — scale the loss before grad, divide
the f32 grads after) is available for underflow-prone models; it is pinned
to 1.0 under fp32 so the plain-f32 path stays bitwise-identical.

``fused_update=True`` routes the SGD tail (global-norm clip -> weight decay
-> momentum -> lr-scaled update -> mask re-apply) through the fused kernel
in ops/fused_update.py — one HBM pass instead of one per stage — with the
optax chain's exact arithmetic (bit-parity pinned in tests/test_precision).
"""

from __future__ import annotations

from typing import NamedTuple

import jax
import jax.numpy as jnp
import optax

from neuroimagedisttraining_tpu.config import OptimConfig
from neuroimagedisttraining_tpu.obs import names as obs_names

#: legal ``OptimConfig.precision`` values, in contract order
PRECISIONS = ("fp32", "bf16_mixed")

#: ``--remat auto`` activation budget by precision: the max samples in
#: flight per device before stem remat arms. The fp32 cutoff (128) is
#: the measured activation-bytes knee on the harness box; under
#: bf16_mixed the conv/matmul activations are stored in bfloat16 —
#: half the bytes per sample — so the same HBM headroom carries 2x the
#: samples before recompute pays for itself (ISSUE 19 satellite; the
#: ratio is pinned in tests/test_tune.py).
REMAT_AUTO_SAMPLES = {"fp32": 128, "bf16_mixed": 256}


def remat_auto_samples_threshold(precision: str) -> int:
    """Samples-in-flight-per-device cutoff above which ``--remat auto``
    resolves to stem remat, for this precision policy."""
    validate_precision_name(precision)
    return REMAT_AUTO_SAMPLES[precision]


def compute_dtype(precision: str):
    """The flax module ``dtype`` a precision policy compiles to (master
    weights stay float32 either way — flax ``param_dtype`` default)."""
    validate_precision_name(precision)
    return jnp.bfloat16 if precision == "bf16_mixed" else jnp.float32


def validate_precision_name(precision: str) -> None:
    if precision not in PRECISIONS:
        raise ValueError(
            f"unknown precision {precision!r}; choose one of {PRECISIONS}")


def validate_precision(cfg: OptimConfig) -> None:
    """The whole-config precision contract, enforced at trainer build so a
    bad combination dies at startup, not at first trace:

    - ``precision`` must be a known policy;
    - ``loss_scale`` must be positive and finite (it divides gradients);
    - ``loss_scale != 1`` requires ``bf16_mixed`` — under fp32 the scale
      pair would perturb rounding and silently break the bitwise-
      unchanged-fp32 pin the whole plan rests on;
    - ``fused_update`` exists for the SGD chain only (the adam path has
      no fused kernel; training un-fused while the flag claimed fusion
      would corrupt any bench comparing the two)."""
    import math

    validate_precision_name(cfg.precision)
    scale = float(cfg.loss_scale)
    if not (scale > 0 and math.isfinite(scale)):
        raise ValueError(f"loss_scale must be a positive finite constant "
                         f"(got {cfg.loss_scale!r})")
    if scale != 1.0 and cfg.precision != "bf16_mixed":
        raise ValueError(
            f"loss_scale={cfg.loss_scale} needs precision=bf16_mixed: "
            "under fp32 the scale/unscale pair would only perturb "
            "rounding and break the bitwise-f32 contract")
    if cfg.fused_update and cfg.client_optimizer != "sgd":
        raise ValueError(
            "--fused_update fuses the SGD clip/momentum/update tail "
            f"(ops/fused_update.py); client_optimizer="
            f"{cfg.client_optimizer!r} has no fused kernel and would "
            "silently train un-fused")


class LocalOptimizer(NamedTuple):
    init: object   # params -> opt_state
    update: object  # (grads, opt_state, params, lr) -> (updates, opt_state)
    #: fused one-pass apply (ops/fused_update.py), or None when the
    #: config keeps the unfused optax chain:
    #: (grads, opt_state, params, lr, mask|None) -> (params, opt_state)
    fused_apply: object | None = None


def _clip(max_norm: float) -> optax.GradientTransformation:
    """``optax.clip_by_global_norm`` (or the identity) under the device
    scope SCOPE_CLIP: same init, same update, a name on its ops."""
    tx = (optax.clip_by_global_norm(max_norm) if max_norm > 0
          else optax.identity())

    def update(updates, state, params=None):
        with jax.named_scope(obs_names.SCOPE_CLIP):
            return tx.update(updates, state, params)

    return optax.GradientTransformation(tx.init, update)


def make_local_optimizer(cfg: OptimConfig) -> LocalOptimizer:
    if cfg.client_optimizer == "sgd":
        tx = optax.chain(
            _clip(cfg.grad_clip),
            optax.add_decayed_weights(cfg.wd) if cfg.wd > 0 else optax.identity(),
            optax.trace(decay=cfg.momentum) if cfg.momentum > 0
            else optax.identity(),
        )
    elif cfg.client_optimizer == "adam":
        tx = optax.chain(
            _clip(cfg.grad_clip),
            optax.scale_by_adam(),
            optax.add_decayed_weights(cfg.wd) if cfg.wd > 0 else optax.identity(),
        )
    else:
        raise ValueError(f"unknown client_optimizer {cfg.client_optimizer!r}")

    def init(params):
        return tx.init(params)

    def update(grads, opt_state, params, lr):
        updates, opt_state = tx.update(grads, opt_state, params)
        updates = jax.tree.map(lambda u: -lr * u, updates)
        return updates, opt_state

    fused_apply = None
    if cfg.fused_update and cfg.client_optimizer == "sgd":
        from neuroimagedisttraining_tpu.ops import fused_update as fu

        has_trace = cfg.momentum > 0

        def fused_apply(grads, opt_state, params, lr, mask=None):
            # the chain state is always a 3-tuple (identity substitutes
            # keep the arity); slot 2 is the TraceState when momentum>0
            trace = opt_state[2].trace if has_trace else None
            new_params, new_trace = fu.fused_sgd_step(
                params, grads, trace, mask, clip=cfg.grad_clip,
                wd=cfg.wd, momentum=cfg.momentum, lr=lr)
            if has_trace:
                opt_state = (opt_state[0], opt_state[1],
                             optax.TraceState(trace=new_trace))
            return new_params, opt_state

    return LocalOptimizer(init=init, update=update, fused_apply=fused_apply)


def round_lr(cfg: OptimConfig, round_idx) -> jax.Array:
    """Per-round exponential decay: lr * lr_decay**round
    (my_model_trainer.py:209)."""
    return jnp.asarray(cfg.lr, jnp.float32) * (
        jnp.asarray(cfg.lr_decay, jnp.float32) ** round_idx)
