"""Fused masked-SGD tail: clip + weight-decay + momentum + update + mask
in ONE pass over the parameters (Pallas on TPU, XLA fallback elsewhere).

The unfused optax chain the engines run per training step
(core/optim.py: ``clip_by_global_norm -> add_decayed_weights -> trace``,
then ``params += -lr * updates`` and the masked engines' ``params *=
mask``) materializes a full params-sized intermediate in HBM per stage —
five reads + four writes of the 2.6M-param flagship tree per step, and
the masked-grad intermediate exists only to be multiplied and thrown
away. This module computes the identical arithmetic as one elementwise
kernel per leaf: read {param, grad, momentum, mask}, write {param,
momentum}. The global-norm reduction stays a separate (unavoidable)
pass, shared with the unfused path via ``optax.global_norm``.

Parity contract (tests/test_precision.py):

- the XLA fallback reproduces the optax chain BITWISE — same ops in the
  same order (``lax.select(trigger, g, (g / gnorm) * clip)``,
  ``g + wd*p``, ``u + momentum*t``, ``p + (-lr)*u``, ``p * mask``), so
  masked engines produce identical masks/metrics with the fused path on
  or off;
- the Pallas kernel is pinned bit-equal to the fallback on TPU (the
  same elementwise f32 ops on the VPU); on CPU the kernel runs in
  interpreter mode under a tolerance pin (the interpreter's math is the
  fallback's — the pin guards the padding/blocking plumbing).

Template: ops/topk.py (block conventions). Scalars
ride a (1, 128) f32 operand mapped to every grid step — lr is a traced
per-round scalar, the clip trigger and global norm are per-step values;
clip/wd/momentum are config constants baked as static flags so a
disabled stage costs nothing (and a wd=0 model avoids the ``g + 0*p``
rewrite of signed zeros the unfused identity stage never performs).
"""

from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
import optax

from neuroimagedisttraining_tpu.obs import names as obs_names

_LANES = 128
_MAX_BLOCK_ROWS = 512   # [512, 128] f32 block = 256 KiB VMEM per operand


def _round_up(n: int, m: int) -> int:
    return ((n + m - 1) // m) * m


# ---------- kernel ----------

def _make_kernel(has_clip: bool, has_wd: bool, has_trace: bool,
                 has_mask: bool):
    """Kernel factory: the stage set is static per config, so a disabled
    stage is absent from the compiled kernel entirely."""

    def kernel(*refs):
        refs = list(refs)
        p_ref = refs.pop(0)
        g_ref = refs.pop(0)
        t_ref = refs.pop(0) if has_trace else None
        m_ref = refs.pop(0) if has_mask else None
        s_ref = refs.pop(0)
        p_out = refs.pop(0)
        t_out = refs.pop(0) if has_trace else None

        p = p_ref[...]
        g = g_ref[...]
        if has_clip:
            ok = s_ref[0, 0]       # 1.0 when gnorm < clip (no rescale)
            gnorm = s_ref[0, 1]
            clip = s_ref[0, 2]
            g = jnp.where(ok > 0.5, g, (g / gnorm) * clip)
        if has_wd:
            g = g + s_ref[0, 3] * p
        if has_trace:
            g = g + s_ref[0, 4] * t_ref[...]
            t_out[...] = g
        p_new = p + (-s_ref[0, 5]) * g
        if has_mask:
            p_new = p_new * m_ref[...]
        p_out[...] = p_new

    return kernel


def _leaf_pallas(p, g, t, m, scalars, has_clip: bool, has_wd: bool,
                 interpret: bool = False):
    """One leaf through the fused kernel: flatten -> pad to [R, 128]
    blocks -> grid over row blocks -> unpad. Returns (p_new, t_new|None).
    Zero padding is inert through every stage (0/gnorm*clip = 0,
    0 + wd*0 = 0, ...) and sliced off regardless."""
    import jax.experimental.pallas as pl
    from jax.experimental.pallas import tpu as pltpu

    has_trace, has_mask = t is not None, m is not None
    n = p.size
    rows = _round_up(max(1, -(-n // _LANES)), 8)
    block_rows = min(_MAX_BLOCK_ROWS, rows)
    rows = _round_up(rows, block_rows)
    grid = rows // block_rows

    def pad2d(x):
        flat = x.astype(jnp.float32).reshape(-1)
        flat = jnp.concatenate(
            [flat, jnp.zeros((rows * _LANES - n,), jnp.float32)])
        return flat.reshape(rows, _LANES)

    operands = [pad2d(p), pad2d(g)]
    if has_trace:
        operands.append(pad2d(t))
    if has_mask:
        operands.append(pad2d(m))
    operands.append(scalars)

    blk = pl.BlockSpec((block_rows, _LANES), lambda i: (i, 0))
    in_specs = [blk] * (2 + has_trace + has_mask) + [
        pl.BlockSpec((1, _LANES), lambda i: (0, 0))]
    out_shape = [jax.ShapeDtypeStruct((rows, _LANES), jnp.float32)]
    out_specs = [blk]
    if has_trace:
        out_shape.append(jax.ShapeDtypeStruct((rows, _LANES), jnp.float32))
        out_specs.append(blk)

    out = pl.pallas_call(
        _make_kernel(has_clip, has_wd, has_trace, has_mask),
        out_shape=tuple(out_shape),
        grid=(grid,),
        in_specs=in_specs,
        out_specs=tuple(out_specs),
        compiler_params=pltpu.CompilerParams(
            dimension_semantics=("arbitrary",)),
        interpret=interpret,
    )(*operands)

    unpad = lambda x: x.reshape(-1)[:n].reshape(p.shape)
    p_new = unpad(out[0])
    t_new = unpad(out[1]) if has_trace else None
    return p_new, t_new


# ---------- XLA fallback (the bitwise reference) ----------

def _leaf_xla(p, g, t, m, ok, gnorm, clip: float, wd: float,
              momentum: float, lr):
    """The optax chain's exact per-leaf arithmetic, fused lexically (XLA
    fuses it into one loop on CPU/GPU): this IS the reference the Pallas
    kernel is pinned against, and it is bitwise-equal to the unfused
    ``make_local_optimizer`` path by construction (same ops, same
    order — clipping.clip_by_global_norm / transform.trace /
    add_decayed_weights, optax 0.2.x)."""
    if clip > 0:
        g = jax.lax.select(ok, g, (g / gnorm.astype(g.dtype)) * clip)
    if wd > 0:
        g = g + wd * p
    if momentum > 0:
        g = g + momentum * t
    t_new = g if momentum > 0 else None
    p_new = jnp.add(p, -lr * g)
    if m is not None:
        p_new = jnp.multiply(p_new, m)
    return p_new, t_new


# ---------- public API ----------

def fused_sgd_step(params, grads, trace, mask, *, clip: float, wd: float,
                   momentum: float, lr, use_pallas: bool | None = None,
                   interpret: bool = False):
    """One fused SGD step over a whole pytree.

    ``trace`` is the momentum tree (None when momentum == 0); ``mask``
    the sparse-training mask tree (None for dense engines). ``lr`` may
    be a traced scalar (the per-round decayed lr). Returns
    ``(new_params, new_trace|None)`` — float32 master weights in, f32
    out, exactly like the unfused chain.
    """
    if use_pallas is None:
        use_pallas = jax.default_backend() == "tpu"
    ok = gnorm = None
    if clip > 0:
        with jax.named_scope(obs_names.SCOPE_CLIP):
            gnorm = optax.global_norm(grads)      # the shared reduction
            ok = jnp.squeeze(gnorm < clip)

    leaves_p, treedef = jax.tree.flatten(params)
    leaves_g = treedef.flatten_up_to(grads)
    leaves_t = (treedef.flatten_up_to(trace) if trace is not None
                else [None] * len(leaves_p))
    leaves_m = (treedef.flatten_up_to(mask) if mask is not None
                else [None] * len(leaves_p))

    if use_pallas or interpret:
        # [ok, gnorm, clip, wd, momentum, lr] + lane padding; a single
        # (1, 128) f32 operand broadcast to every grid step
        svals = jnp.stack([
            jnp.where(ok, 1.0, 0.0) if ok is not None else jnp.float32(1),
            (gnorm if gnorm is not None else jnp.float32(1))
            .astype(jnp.float32),
            jnp.float32(clip), jnp.float32(wd), jnp.float32(momentum),
            jnp.asarray(lr, jnp.float32)])
        scalars = jnp.zeros((1, _LANES), jnp.float32).at[0, :6].set(svals)
        step = functools.partial(_leaf_pallas, scalars=scalars,
                                 has_clip=clip > 0, has_wd=wd > 0,
                                 interpret=interpret)
    else:
        step = functools.partial(_leaf_xla, ok=ok, gnorm=gnorm, clip=clip,
                                 wd=wd, momentum=momentum, lr=lr)

    out = [step(p, g, t, m) for p, g, t, m in
           zip(leaves_p, leaves_g, leaves_t, leaves_m)]
    new_params = jax.tree.unflatten(treedef, [o[0] for o in out])
    new_trace = (jax.tree.unflatten(treedef, [o[1] for o in out])
                 if trace is not None else None)
    return new_params, new_trace
