"""Mamba-2's state-space scan in its chunked form (state-space duality).

The layer (models/nemotronh3d.py ``Mamba2Mixer``) defines, per head ``h``
with ``P`` channels and a state of ``N`` columns, the recurrence

    S_t = exp(dt_t A) S_{t-1} + dt_t x_t B_t^T        S_0 = 0, S [P, N]
    y_t = S_t C_t + D x_t

with a scalar decay a head (``A < 0``), ``dt > 0`` a token and head, and
``B_t, C_t [N]`` shared by the ``H / G`` heads of a group. Token by token
that is ``T`` dependent steps of rank-one updates: no matrix unit is ever
busy. The chunked form (Dao & Gu 2024, "Transformers are SSMs", the
``ssd_minimal`` listing) computes the same ``y`` from four batched
contractions a chunk of ``Q`` tokens:

    cum_i   = sum_{j <= i} dt_j A                      (inside the chunk)
    intra   y_i += sum_{j <= i} (C_i . B_j) exp(cum_i - cum_j) dt_j x_j
    states  S^c  = sum_j exp(cum_last - cum_j) dt_j x_j B_j^T
    scan    S_in^{c+1} = exp(cum_last^c) S_in^c + S^c  (T / Q steps)
    inter   y_i += exp(cum_i) (S_in^c C_i)

Decays and cumulative sums are float32 whatever the compute dtype (an
exponent of a sum of 128 terms: bf16 would lose the small ``dt``); the
contractions take operands in the compute dtype and accumulate in
float32. The backward pass is autodiff's of the same four contractions,
so it is chunked too: nothing of length ``T`` is ever scanned, and the
only sequential part is the ``T / Q``-step state scan (5 steps at 640
tokens and the published chunk of 128).

Plain XLA, no kernel: benchmark/metrics/ssd_roofline_pct.json says how
far from the chip's roofline that leaves it.
"""

from __future__ import annotations

import jax
import jax.numpy as jnp


def _carry_state(s_in, chunk_c):
    """One step of the scan over chunks: the state chunk ``c`` starts
    from goes out, the state it ends with goes on. At module level: a
    body defined inside :func:`ssd_chunked` is a new function on every
    call, and an eager call (the trainer initialises its model eagerly)
    then compiles the scan anew each time (four compilations inside the
    benchmark's measured window; my chip run, PR 29)."""
    decay_c, s_c = chunk_c
    return s_in * decay_c[..., None, None] + s_c, s_in


def ssd_chunked(x: jax.Array, dt: jax.Array, A: jax.Array, B: jax.Array,
                C: jax.Array, D: jax.Array, chunk: int) -> jax.Array:
    """``x [b, T, H, P]``, ``dt [b, T, H]`` (after softplus, float32),
    ``A [H]`` (negative, float32), ``B, C [b, T, G, N]``, ``D [H]`` ->
    ``y [b, T, H, P]`` in ``x``'s dtype. ``T`` has to be a multiple of
    ``chunk`` (or shorter than one: then it is one chunk)."""
    b, T, H, P = x.shape
    G, N = B.shape[-2:]
    Q = min(chunk, T)
    if T % Q or H % G:
        raise ValueError(f"ssd_chunked: {T} tokens are not whole chunks of "
                         f"{Q}, or {H} heads not whole groups of {G}")
    nc, R = T // Q, H // G
    f32, dtype = jnp.float32, x.dtype
    # [b, c, q, g, r, ...]: head h is group h // R, member h % R
    xc = x.reshape(b, nc, Q, G, R, P)
    Bc = B.reshape(b, nc, Q, G, N)
    Cc = C.reshape(b, nc, Q, G, N)
    dtc = dt.astype(f32).reshape(b, nc, Q, G, R)
    cum = jnp.cumsum(dtc * A.astype(f32).reshape(G, R), axis=2)
    xdt = (xc * dtc[..., None]).astype(dtype)  # dt_j x_j

    # intra-chunk: (C_i . B_j) exp(cum_i - cum_j), j <= i
    cb = jnp.einsum("bcign,bcjgn->bcgij", Cc, Bc,
                    preferred_element_type=f32)
    seg = cum[:, :, :, None] - cum[:, :, None]  # [b, c, i, j, g, r]
    causal = jnp.tril(jnp.ones((Q, Q), bool))[None, None, :, :, None, None]
    # masked BEFORE the exponent: above the diagonal the difference is
    # positive and exp would overflow where its gradient is then 0 * inf
    decay = jnp.exp(jnp.where(causal, seg, -jnp.inf))
    mix = (cb.transpose(0, 1, 3, 4, 2)[..., None] * decay).astype(dtype)
    y = jnp.einsum("bcijgr,bcjgrp->bcigrp", mix, xdt,
                   preferred_element_type=f32)

    # chunk states, then the scan over chunks
    last = cum[:, :, -1]  # [b, c, g, r]
    to_end = jnp.exp(last[:, :, None] - cum)  # [b, c, q, g, r]
    states = jnp.einsum("bcjgrp,bcjgn->bcgrpn",
                        (xdt * to_end[..., None]).astype(dtype), Bc,
                        preferred_element_type=f32)

    _, s_in = jax.lax.scan(
        _carry_state, jnp.zeros_like(states[:, 0]),
        (jnp.exp(last).swapaxes(0, 1), states.swapaxes(0, 1)))
    s_in = s_in.swapaxes(0, 1)  # the state each chunk STARTS from

    # state to output
    y = y + jnp.exp(cum)[..., None] * jnp.einsum(
        "bcign,bcgrpn->bcigrp", Cc, s_in.astype(dtype),
        preferred_element_type=f32)
    y = y + D.astype(f32).reshape(G, R)[..., None] * xc
    return y.reshape(b, T, H, P).astype(dtype)
