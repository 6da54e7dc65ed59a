"""Global top-k threshold selection over flattened saliency vectors.

Replaces the reference's ``torch.topk(all_scores, k)[..., -1]`` global
threshold (snip.py:91-98) — which materializes a full sorted copy of the
score vector (2,568,064 elements for this repo's AlexNet3D) — with a
multi-round histogram-select:
each round counts ``x >= t`` for a ladder of thresholds and narrows the
bracket containing the k-th largest value. With 4 rounds x 512 bins the
bracket shrinks by 512^4 ≈ 7e10 > 2^32, i.e. to float32 resolution: the
returned threshold is the exact k-th largest float.

The counting pass is the hot part and runs as a Pallas TPU kernel
(`_count_ge_pallas`): the score vector streams HBM->VMEM in [rows, 128]
blocks; each block compares against the threshold ladder in 128-wide chunks
on the VPU and accumulates partial counts into a VMEM accumulator mapped to
the same output block across the whole grid. Non-TPU backends (tests) use an
XLA fallback with identical semantics.
"""

from __future__ import annotations

import functools

import jax
import jax.numpy as jnp

from neuroimagedisttraining_tpu.obs import names as obs_names

_BLOCK_ROWS = 256          # x block = [256, 128] floats = 128 KiB VMEM
_LANES = 128
_BIN_CHUNK = 128


def _count_ge_kernel(x_ref, thr_ref, out_ref):
    import jax.experimental.pallas as pl

    @pl.when(pl.program_id(0) == 0)
    def _init():
        out_ref[:] = jnp.zeros_like(out_ref)

    x = x_ref[:]                      # [R, 128]
    nbins = out_ref.shape[1]

    def body(j, _):
        sl = pl.dslice(j * _BIN_CHUNK, _BIN_CHUNK)
        thr_chunk = thr_ref[0, sl]                               # [C]
        cmp = x[:, :, None] >= thr_chunk[None, None, :]          # [R,128,C]
        partial = jnp.sum(cmp.astype(jnp.float32), axis=(0, 1))  # [C]  # nidt: allow[precision-upcast] -- histogram COUNTS accumulate in f32 on the VPU (exactness of the bracket, not an activation)
        out_ref[0, sl] = out_ref[0, sl] + partial
        return 0

    jax.lax.fori_loop(0, nbins // _BIN_CHUNK, body, 0)


def _count_ge_pallas(x2d: jax.Array, thresholds: jax.Array) -> jax.Array:
    import jax.experimental.pallas as pl
    from jax.experimental.pallas import tpu as pltpu

    rows = x2d.shape[0]
    nbins = thresholds.shape[0]
    grid = rows // _BLOCK_ROWS
    out = pl.pallas_call(
        _count_ge_kernel,
        out_shape=jax.ShapeDtypeStruct((1, nbins), jnp.float32),
        grid=(grid,),
        in_specs=[
            pl.BlockSpec((_BLOCK_ROWS, _LANES), lambda i: (i, 0)),
            pl.BlockSpec((1, nbins), lambda i: (0, 0)),
        ],
        out_specs=pl.BlockSpec((1, nbins), lambda i: (0, 0)),
        compiler_params=pltpu.CompilerParams(
            dimension_semantics=("arbitrary",)),
    )(x2d, thresholds[None, :])
    return out[0]


def _count_ge_xla(x2d: jax.Array, thresholds: jax.Array) -> jax.Array:
    def chunk_counts(thr_chunk):
        return jnp.sum((x2d[None, :, :] >= thr_chunk[:, None, None])
                       .astype(jnp.float32), axis=(1, 2))  # nidt: allow[precision-upcast] -- histogram counts in f32, XLA fallback mirrors the kernel bitwise

    chunks = thresholds.reshape(-1, _BIN_CHUNK // 2)
    return jax.lax.map(chunk_counts, chunks).reshape(-1)


def _pad_to_blocks(x: jax.Array) -> jax.Array:
    n = x.shape[0]
    per_block = _BLOCK_ROWS * _LANES
    padded = ((n + per_block - 1) // per_block) * per_block
    fill = jnp.finfo(jnp.float32).min
    return jnp.concatenate(
        [x.astype(jnp.float32),  # nidt: allow[precision-upcast] -- saliency scores compare in exact f32: the k-th-largest bracket is defined on the f32 value lattice
         jnp.full((padded - n,), fill, jnp.float32)]).reshape(-1, _LANES)


@functools.partial(jax.jit, static_argnames=("k", "rounds", "nbins",
                                             "use_pallas"))
@jax.named_scope(obs_names.SCOPE_TOPK_MASK)
def kth_largest(x: jax.Array, k: int, rounds: int = 4, nbins: int = 512,
                use_pallas: bool | None = None) -> jax.Array:
    """Exact (to float32 resolution) k-th largest value of a 1-D vector.

    A mask ``x >= kth_largest(x, k)`` keeps >= k entries (ties included) —
    the same semantics as the reference's ``>= acceptable_score``
    (snip.py:96-98).

    Non-finite contract: the histogram bracket assumes every comparison
    ``x >= t`` is meaningful; a single NaN (or a +/-inf min/max bracket)
    would otherwise silently converge to a garbage threshold — worse than
    the reference, whose ``torch.topk`` would at least surface the NaN in
    the returned value. So non-finite input yields a NaN threshold
    (which poisons any ``>=`` mask to all-False *visibly*, and which
    eager callers — ops/snip.py:mask_from_scores — turn into a raised
    error before any mask is built).
    """
    assert x.ndim == 1
    assert nbins % _BIN_CHUNK == 0, (
        f"nbins ({nbins}) must be a multiple of {_BIN_CHUNK}: the Pallas "
        "kernel floor-divides the bin ladder into chunks and would silently "
        "drop remainder bins")
    if use_pallas is None:
        use_pallas = jax.default_backend() == "tpu"
    count_ge = _count_ge_pallas if use_pallas else _count_ge_xla
    x2d = _pad_to_blocks(x)
    lo = jnp.min(x).astype(jnp.float32)  # nidt: allow[precision-upcast] -- f32 bracket endpoints: the threshold IS an f32 value by contract
    hi = jnp.max(x).astype(jnp.float32)  # nidt: allow[precision-upcast] -- f32 bracket endpoints: the threshold IS an f32 value by contract

    def round_fn(carry, _):
        lo, hi = carry
        thr = jnp.linspace(lo, hi, nbins)
        counts = count_ge(x2d, thr)
        # counts is non-increasing in the threshold, except for sub-float32
        # linspace wiggle in the final rounds — so take the longest TRUE
        # prefix of (count >= k), not the total count of TRUEs.
        prefix = jnp.cumprod((counts >= k).astype(jnp.int32))
        j = jnp.maximum(jnp.sum(prefix) - 1, 0)
        new_lo = thr[j]
        new_hi = jnp.where(j + 1 < nbins, thr[jnp.minimum(j + 1, nbins - 1)],
                           hi)
        return (new_lo, new_hi), None

    (lo, hi), _ = jax.lax.scan(round_fn, (lo, hi), None, length=rounds)
    ok = jnp.all(jnp.isfinite(x))
    return jnp.where(ok, lo, jnp.float32(jnp.nan))  # nidt: allow[precision-upcast] -- the NaN-poison sentinel is an f32 threshold by contract


def topk_threshold_mask(x: jax.Array, k: int, **kw) -> tuple[jax.Array, jax.Array]:
    thr = kth_largest(x, k, **kw)
    return (x >= thr), thr
