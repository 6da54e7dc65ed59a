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
float32. The backward pass is chunked too: nothing of length ``T`` is
ever scanned, and the only sequential part is the ``T / Q``-step state
scan (5 steps at 640 tokens and the published chunk of 128).

What runs where. :func:`ssd_chunked` is the one entry. On a TPU, for
shapes :func:`kernel_tiles` passes (the published layer does), it is
:func:`ssd_kernel`: one Pallas kernel forward and one backward, in which
the ``[Q, Q]`` tiles (``C B^T``, the masked exponent of ``cum_i - cum_j``,
their product ``mix``) live in vector memory; the plain form writes them
to HBM for every chunk and head, 335 MB of float32 decays a call at the
published widths where the operation's own operands are 212 MB. The
backward pass is a ``custom_vjp``: a kernel that sweeps the chunks in
reverse carrying the state's cotangent, residuals the inputs and the
state each chunk starts from. Off the TPU (the CPU tests), for other
shapes and for an eager caller it is the plain ``jax.numpy`` form with
autodiff's backward. Same equations and precisions in both; only the
order of the sums differs. benchmark/metrics/ssd_roofline_pct.json says
how far from the chip's roofline the scope is.
"""

from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu

_LANES = 128


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
                C: jax.Array, D: jax.Array, chunk: int, *,
                kernel: bool = True) -> jax.Array:
    """``x [b, T, H, P]``, ``dt [b, T, H]`` (after softplus, float32),
    ``A [H]`` (negative, float32), ``B, C [b, T, G, N]``, ``D [H]`` ->
    ``y [b, T, H, P]`` in ``x``'s dtype. ``T`` has to be a multiple of
    ``chunk`` (or shorter than one: then it is one chunk).

    On a TPU, for shapes :func:`kernel_tiles` passes, this is
    :func:`ssd_kernel`; everywhere else, and for a caller that says
    ``kernel=False`` (an EAGER call: a kernel is compiled anew on every
    one, the plain form's operations are cached one by one), the plain
    form below."""
    b, T, H, P = x.shape
    G, N = B.shape[-2:]
    Q = min(chunk, T)
    if T % Q or H % G:
        raise ValueError(f"ssd_chunked: {T} tokens are not whole chunks of "
                         f"{Q}, or {H} heads not whole groups of {G}")
    nc, R = T // Q, H // G
    if (kernel and jax.default_backend() == "tpu"
            and kernel_tiles(Q, R, P, N)):
        return ssd_kernel(x, dt, A, B, C, D, Q)
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


# ---------- the same four contractions as one kernel a pass ----------
#
# A program is one chunk of one group, of a few volumes: the chunk axis is
# the grid's last and sequential, and the state a chunk starts from waits
# in a scratch buffer, TRANSPOSED and for the group's R heads side by side:
# ``St [N, R * P]``. That layout makes the chunk states (``B^T @ xw``) and
# the state read-out (``C @ St``) one product each over all R * P columns;
# only ``mix @ xdt`` is a product a head, taken against the head's whole
# lane tile of 128 columns (the other heads' lanes of the result are
# dropped), so no slice ever starts off a lane-tile boundary. Per-head
# factors come in as ``[R, Q]`` rows and are transposed to columns here.
# What binds the kernels is not the matrix unit or the exponent but the
# crossbar (a lane broadcast of a ``[Q, 1]`` column costs as much as six
# vector operations a register) and the 64 vector registers, of which one
# ``[128, 128]`` float32 tile takes 16 (compiled for a v5e, PR 32: the
# forward's bundles are 55% busy in every unit). Hence the shape of the
# code below: one broadcast a head, everything else derived from it.


def kernel_tiles(Q: int, R: int, P: int, N: int) -> bool:
    """Whether the kernel's blocks tile a chunk of ``Q`` tokens for groups
    of ``R`` heads of ``P`` channels and a state of ``N`` columns: the
    decay tile is ``[Q, Q]`` and the state ``[N, R * P]``, so ``Q``, ``N``
    and ``R * P`` are whole lane tiles, a head is a whole fraction of one,
    and a group's per-head factors ``[R, Q]`` are whole sublane tiles (the
    published layer: 128, 8 x 64, 128)."""
    return (Q % _LANES == 0 and N % _LANES == 0 and (R * P) % _LANES == 0
            and _LANES % P == 0 and R % 8 == 0 and 2 * R <= _LANES)


def _by_head(parts, P: int):
    """One ``[q, 128]`` array from a lane tile's heads' ``[q, 128]`` (or
    ``[q, 1]``) arrays: head ``k``'s own on lanes ``k P .. (k+1) P``."""
    lane = jax.lax.broadcasted_iota(jnp.int32, (1, _LANES), 1)
    out = parts[0]
    for k in range(1, len(parts)):
        out = jnp.where(lane >= k * P, parts[k], out)
    return out


def _spread(cols, t: int, P: int):
    """Per-head columns ``cols [q, R]`` over lane tile ``t``'s heads:
    ``[q, 128]``, head ``t * (128 // P) + k`` on lanes ``k P .. (k+1) P``.
    A lane broadcast a head, the crossbar's work (the kernels' scarcest
    unit): what is a function of ``cum`` is spread by :func:`_cum_tile`
    instead, from the broadcasts the decay tiles need anyway."""
    hp = _LANES // P
    return jnp.broadcast_to(
        _by_head([cols[:, r:r + 1] for r in range(t * hp, (t + 1) * hp)], P),
        (cols.shape[0], _LANES))


def _own_lanes(k: int, P: int, a):
    """``a [q, 128]`` with the lanes of every head but the tile's ``k``-th
    zeroed."""
    if P == _LANES:
        return a
    lane = jax.lax.broadcasted_iota(jnp.int32, (1, _LANES), 1)
    return jnp.where((lane >= k * P) & (lane < (k + 1) * P), a, 0.0)


def _dot(a, b, contract=((1,), (0,))):
    """``a @ b`` by default; ``contract`` names the contracted axis of each
    (``((1,), (1,))`` is ``a @ b.T``)."""
    return jax.lax.dot_general(a, b, (contract, ((), ())),
                               preferred_element_type=jnp.float32)


_NT = ((1,), (1,))


def _cum_i(acol, r: int):
    """Head ``r``'s ``cum_i`` down the sublanes, the same on every lane:
    ``[Q, Q]``. A lane broadcast, the crossbar's work (the kernels'
    scarcest unit), so everything that is a function of ``cum_i`` is made
    from this one tile (the compiler merges the repeated calls)."""
    Q = acol.shape[0]
    return jnp.broadcast_to(acol[:, r:r + 1], (Q, Q))


def _decay(acol, arow, r: int, transposed: bool = False):
    """``exp(cum_i - cum_j)`` for ``j <= i`` and 0 above the diagonal,
    head ``r``: masked BEFORE the exponent, as the plain form. Or the same
    tile TRANSPOSED (``[j, i]``): rows and lanes change roles, so it costs
    an exponent and no transposition."""
    Q = acol.shape[0]
    row, col = (jax.lax.broadcasted_iota(jnp.int32, (Q, Q), d)
                for d in (0, 1))
    seg = _cum_i(acol, r) - arow[r:r + 1, :]
    if transposed:
        return jnp.exp(jnp.where(row <= col, -seg, -jnp.inf))
    return jnp.exp(jnp.where(row >= col, seg, -jnp.inf))


def _cum_tile(acol, t: int, P: int):
    """``cum_i`` over lane tile ``t``, each head's on its own ``P`` lanes:
    ``[Q, 128]``, from the broadcasts the decay tiles need anyway."""
    hp = _LANES // P
    return _by_head([_cum_i(acol, t * hp + k)[:, :_LANES]
                     for k in range(hp)], P)


def _forward_kernel(P, x_ref, b_ref, c_ref, dt_ref, cum_ref, d_ref, y_ref,
                    *rest):
    """One chunk of one group, for the block's volumes: ``y`` out, the
    state carried on in ``state`` (the last of ``rest``); with a second
    output, the state the chunk STARTS from goes out too (the backward
    pass reads it). The volumes are independent and unrolled side by side:
    the scheduler fills one's waits with another's work (1,408 bundles a
    volume alone, 1,038 each four at a time, compiled for a v5e; 0.81 ->
    0.48 ms a call at the published widths, my chip run, PR 32; the
    backward gains 4% from two and keeps one)."""
    state, s_in_ref = rest[-1], (rest[0] if len(rest) == 2 else None)

    @pl.when(pl.program_id(2) == 0)
    def _():
        state[...] = jnp.zeros_like(state)

    for v in range(x_ref.shape[0]):
        _forward_volume(P, v, x_ref, b_ref, c_ref, dt_ref, cum_ref, d_ref,
                        y_ref, s_in_ref, state)


def _forward_volume(P, v, x_ref, b_ref, c_ref, dt_ref, cum_ref, d_ref, y_ref,
                    s_in_ref, state):
    f32, dtype = jnp.float32, x_ref.dtype
    Q = x_ref.shape[1]
    heads = range(_LANES // P)
    Bm, Cm = b_ref[v], c_ref[v]
    BmT = Bm.T
    arow = cum_ref[v, 0]  # [R, Q]: head r's cum_j along the lanes
    acol, dtcol = arow.T, dt_ref[v, 0].T  # [Q, R]: cum_i down the sublanes
    last = acol[Q - 1:Q, :]  # [1, R]
    cb = _dot(Cm, Bm, _NT)  # [Q, Q], once for the group's heads
    for t in range(x_ref.shape[2] // _LANES):
        tile = slice(t * _LANES, (t + 1) * _LANES)
        xt = x_ref[v, :, tile]
        xdt = (xt * _spread(dtcol, t, P)).astype(dtype)  # dt_j x_j
        # a head's mix against the whole lane tile: _by_head drops the
        # other heads' lanes of the product, at no more passes of the
        # matrix unit than the head's own P columns would take
        intra = _by_head([_dot((cb * _decay(
            acol, arow, t * len(heads) + k)).astype(dtype), xdt)
            for k in heads], P)
        s_in = state[v, :, tile]
        cum, last_t = _cum_tile(acol, t, P), _spread(last, t, P)
        y = (intra + jnp.exp(cum) * _dot(Cm, s_in.astype(dtype))
             + d_ref[0, :, tile] * xt.astype(f32))
        y_ref[v, :, tile] = y.astype(dtype)
        if s_in_ref is not None:
            s_in_ref[v, 0, 0, :, tile] = s_in
        xw = (xdt.astype(f32) * jnp.exp(last_t - cum)).astype(dtype)
        state[v, :, tile] = jnp.exp(last_t) * s_in + _dot(BmT, xw)


def _backward_kernel(P, x_ref, b_ref, c_ref, dt_ref, cum_ref, d_ref, s_in_ref,
                     dy_ref, dx_ref, db_ref, dc_ref, ddt_ref, dcum_ref,
                     dD_ref, dstate, cols, wide):
    """The chunk's cotangents, chunks in REVERSE order: ``dstate`` carries
    the cotangent of the state the chunk ends with (zero after the last
    chunk: nothing reads the final state). ``dcum`` is gathered in two
    parts, a column a head (``i``'s side of ``cum_i - cum_j``, the
    read-out's and the chunk state's exponents) and a row a head (``j``'s
    side); the columns of ``ddt`` and ``dcum`` wait in ``cols [Q, 128]``
    (head ``r``'s on lanes ``r`` and ``R + r``) and are transposed to rows
    once; the operands of the products over all ``R * P`` columns (``dB``,
    ``dC``, the state's cotangent) wait in ``wide [2, Q, R * P]``; ``dD`` a
    lane, summed over this volume's and group's chunks."""
    f32, dtype = jnp.float32, x_ref.dtype
    R, Q = cum_ref.shape[2:]
    heads = range(_LANES // P)
    tiles = [slice(q, q + _LANES) for q in range(0, x_ref.shape[2], _LANES)]

    @pl.when(pl.program_id(2) == 0)
    def _():
        dstate[...] = jnp.zeros_like(dstate)
        dD_ref[...] = jnp.zeros_like(dD_ref)

    Bm, Cm = b_ref[0], c_ref[0]
    arow = cum_ref[0, 0]
    acol, dtcol = arow.T, dt_ref[0, 0].T
    last = acol[Q - 1:Q, :]
    cb, cb_t = _dot(Cm, Bm, _NT), _dot(Bm, Cm, _NT)
    is_last = jax.lax.broadcasted_iota(jnp.int32, (Q, 1), 0) == Q - 1
    lane = jax.lax.broadcasted_iota(jnp.int32, (1, _LANES), 1)
    dcb = jnp.zeros((Q, Q), f32)
    for t, tile in enumerate(tiles):
        xt = x_ref[0, :, tile].astype(f32)
        g = dy_ref[0, :, tile].astype(f32)
        dt_t, last_t = _spread(dtcol, t, P), _spread(last, t, P)
        xdt = (x_ref[0, :, tile] * dt_t).astype(dtype)
        cum = _cum_tile(acol, t, P)
        into, to_end, carry_t = (jnp.exp(cum), jnp.exp(last_t - cum),
                                 jnp.exp(last_t))
        s_in, ds_next = s_in_ref[0, 0, 0, :, tile], dstate[:, tile]
        # state to output, y_i += exp(cum_i) (C_i . St), and the chunk
        # state, St^c = B^T @ xw
        y_inter = into * _dot(Cm, s_in.astype(dtype))
        wide[0, :, tile] = (g * into).astype(dtype)  # dz
        wide[1, :, tile] = (xdt.astype(f32) * to_end).astype(dtype)  # xw
        dxdt = _dot(Bm, ds_next.astype(dtype)) * to_end
        # what hangs on cum_i a lane: the read-out's exponent, less the
        # chunk state's; what hangs on cum_last: the chunk state's and the
        # carry's exponents (St_in^{c+1} = exp(cum_last) St_in^c + St^c)
        d_to_end = dxdt * xdt.astype(f32)
        on_i = g * y_inter - d_to_end
        on_last = (jnp.sum(d_to_end, axis=0, keepdims=True) + carry_t
                   * jnp.sum(ds_next * s_in, axis=0, keepdims=True))
        # intra-chunk, a head at a time
        both = jnp.zeros((Q, _LANES), f32)
        for k in heads:
            r = t * len(heads) + k
            own = functools.partial(_own_lanes, k, P)  # the head's lanes
            gk = own(g).astype(dtype)
            dxdt += _dot((cb_t * _decay(acol, arow, r, transposed=True))
                         .astype(dtype), gk)
            # mix = cb * decay: d(cb) and d(cum_i - cum_j) share d(mix) decay
            on_cb = _dot(gk, xdt, _NT) * _decay(acol, arow, r)
            dcb += on_cb
            dseg = on_cb * cb
            dcum_ref[0, 0, r:r + 1, :] = -jnp.sum(dseg, axis=0,
                                                  keepdims=True)
            # one lane reduction for all that hangs on cum_i: the decay
            # tile's rows (its lane tiles folded first) and the lanes' part
            folded = own(on_i) + sum(dseg[:, q:q + _LANES]
                                     for q in range(0, Q, _LANES))
            on_cum = jnp.sum(folded, axis=1, keepdims=True) + jnp.where(
                is_last, jnp.sum(own(on_last), axis=1, keepdims=True), 0.0)
            on_dt = jnp.sum(own(dxdt * xt), axis=1, keepdims=True)
            both = jnp.where(lane == r, on_dt, jnp.where(lane == R + r,
                                                         on_cum, both))
        first = t * len(heads)  # the tile's heads: lanes first .. r
        mine = ((lane >= first) & (lane <= r)) | (
            (lane >= R + first) & (lane <= R + r))
        cols[...] = jnp.where(mine, both, cols[...])
        dx_ref[0, :, tile] = (dxdt * dt_t + d_ref[0, :, tile] * g).astype(
            dtype)
        dD_ref[0, 0, :, tile] += jnp.sum(g * xt, axis=0, keepdims=True)
    dcb_b = dcb.astype(dtype)
    dz, xw = wide[0], wide[1]
    dc_ref[0] = (_dot(dz, s_in_ref[0, 0, 0].astype(dtype), _NT)
                 + _dot(dcb_b, Bm)).astype(dc_ref.dtype)
    db_ref[0] = (_dot(xw, dstate[...].astype(dtype), _NT)
                 + _dot(dcb_b.T, Cm)).astype(db_ref.dtype)
    CmT = Cm.T
    for t, tile in enumerate(tiles):
        dstate[:, tile] = _dot(CmT, dz[:, tile]) + jnp.exp(_spread(
            last, t, P)) * dstate[:, tile]
    rows = cols[...].T
    ddt_ref[0, 0] = rows[:R]
    dcum_ref[0, 0] += rows[R:2 * R]


def _specs(Q: int, RP: int, N: int, R: int, nc: int, reverse: bool, VB=1):
    """Block specs of what both kernels read, on the grid ``(b, G, nc)``:
    ``x`` / ``y`` as ``[b, T, G * R * P]``, ``B`` / ``C`` as ``[b, T, G *
    N]`` (the arrays as the mixer holds them), the per-head factors as
    ``[b, G, R, T]`` rows (``T`` on the lanes: ``R`` there would be
    padded sixteen-fold in HBM; a kernel transposes its ``[R, Q]`` block),
    ``D`` a lane, the chunk-start states ``[b, G, nc, N, R * P]``."""
    at = (lambda c: nc - 1 - c) if reverse else (lambda c: c)
    spec = lambda block, index: pl.BlockSpec(block, index,
                                             memory_space=pltpu.VMEM)
    return dict(
        x=spec((VB, Q, RP), lambda i, g, c: (i, at(c), g)),
        bc=spec((VB, Q, N), lambda i, g, c: (i, at(c), g)),
        rows=spec((VB, 1, R, Q), lambda i, g, c: (i, g, 0, at(c))),
        D=spec((1, 1, RP), lambda i, g, c: (g, 0, 0)),
        s_in=spec((VB, 1, 1, N, RP), lambda i, g, c: (i, g, at(c), 0, 0)),
        dD=spec((1, 1, 1, RP), lambda i, g, c: (i, g, 0, 0)))


_GRID_ORDER = pltpu.CompilerParams(
    dimension_semantics=("parallel", "parallel", "arbitrary"))


def _forward(Q, P, interpret, emit_states, x, B, C, dt, cum, D):
    """The forward kernel over ``(b, G, T / Q)``: ``y``, and with
    ``emit_states`` the state each chunk starts from, float32."""
    (b, T, _), (G, _, RP), R = x.shape, D.shape, cum.shape[2]
    N, nc = B.shape[-1] // G, T // Q
    VB = next(v for v in (4, 2, 1) if b % v == 0)  # volumes a program
    s = _specs(Q, RP, N, R, nc, reverse=False, VB=VB)
    out_shape = [jax.ShapeDtypeStruct(x.shape, x.dtype)]
    out_specs = [s["x"]]
    if emit_states:
        out_shape.append(jax.ShapeDtypeStruct((b, G, nc, N, RP),
                                              jnp.float32))
        out_specs.append(s["s_in"])
    return pl.pallas_call(
        functools.partial(_forward_kernel, P),
        grid=(b // VB, G, nc),
        in_specs=[s["x"], s["bc"], s["bc"], s["rows"], s["rows"], s["D"]],
        out_specs=out_specs, out_shape=out_shape,
        scratch_shapes=[pltpu.VMEM((VB, N, RP), jnp.float32)],
        compiler_params=_GRID_ORDER, interpret=interpret,
        name="ssd_forward",
    )(x, B, C, dt, cum, D)


@functools.partial(jax.custom_vjp, nondiff_argnums=(0, 1, 2))
def _scan(Q, P, interpret, x, B, C, dt, cum, D):
    """``y [b, T, G * R * P]`` from ``x`` (the same shape), ``B, C [b, T,
    G * N]``, ``dt, cum [b, G, R, T]`` float32 (``cum`` the cumulative sum
    of ``dt A`` inside each chunk of ``Q``) and ``D [G, 1, R * P]``."""
    return _forward(Q, P, interpret, False, x, B, C, dt, cum, D)[0]


def _scan_fwd(Q, P, interpret, x, B, C, dt, cum, D):
    y, s_in = _forward(Q, P, interpret, True, x, B, C, dt, cum, D)
    return y, (x, B, C, dt, cum, D, s_in)


def _scan_bwd(Q, P, interpret, residuals, dy):
    x, B, C, dt, cum, D, s_in = residuals
    (b, T, _), (G, _, RP), R = x.shape, D.shape, cum.shape[2]
    N, nc = B.shape[-1] // G, T // Q
    s = _specs(Q, RP, N, R, nc, reverse=True)
    f32 = jnp.float32
    dx, dB, dC, ddt, dcum, dD = pl.pallas_call(
        functools.partial(_backward_kernel, P),
        grid=(b, G, nc),
        in_specs=[s["x"], s["bc"], s["bc"], s["rows"], s["rows"], s["D"],
                  s["s_in"], s["x"]],
        out_specs=[s["x"], s["bc"], s["bc"], s["rows"], s["rows"], s["dD"]],
        out_shape=[jax.ShapeDtypeStruct(a.shape, a.dtype)
                   for a in (x, B, C, dt, cum)]
        + [jax.ShapeDtypeStruct((b, G, 1, RP), f32)],
        scratch_shapes=[pltpu.VMEM((N, RP), f32),
                        pltpu.VMEM((Q, _LANES), f32),
                        pltpu.VMEM((2, Q, RP), x.dtype)],
        compiler_params=_GRID_ORDER, interpret=interpret,
        name="ssd_backward",
    )(x, B, C, dt, cum, D, s_in, dy)
    return dx, dB, dC, ddt, dcum, jnp.sum(dD, axis=0)


_scan.defvjp(_scan_fwd, _scan_bwd)


def ssd_kernel(x: jax.Array, dt: jax.Array, A: jax.Array, B: jax.Array,
               C: jax.Array, D: jax.Array, chunk: int, *,
               interpret: bool = False) -> jax.Array:
    """:func:`ssd_chunked` through the kernels, for shapes
    :func:`kernel_tiles` passes. What is a function of ``dt`` and ``A``
    alone (the cumulative sums, 1 / P of ``x``'s elements) stays in XLA
    and float32, so its gradient is autodiff's; ``interpret`` runs the
    kernels in Pallas' interpreter (the CPU tests)."""
    b, T, H, P = x.shape
    G, N = B.shape[-2:]
    Q, R = min(chunk, T), H // G
    if T % Q or H % G or not kernel_tiles(Q, R, P, N):
        raise ValueError(f"ssd_kernel: no blocks for chunks of {Q} of {T} "
                         f"tokens, {G} groups of {R} heads of {P}, state {N}")
    f32 = jnp.float32
    dtg = dt.astype(f32).reshape(b, T, G, R).transpose(0, 2, 3, 1)
    # the sums inside a chunk as a product with a triangle of ones: exact
    # in float32 at HIGHEST (a factor of 1 loses nothing of the operand's
    # three bf16 parts; the sum is the matrix unit's float32), where
    # jnp.cumsum is a reduce-window of 1 ms a call in the step (12 a step,
    # as much as the kernels; my chip run, PR 32)
    cum = jnp.einsum(
        "bgrcj,ji->bgrci",
        (dtg * A.astype(f32).reshape(G, R, 1)).reshape(b, G, R, T // Q, Q),
        jnp.triu(jnp.ones((Q, Q), f32)),
        precision=jax.lax.Precision.HIGHEST).reshape(b, G, R, T)
    y = _scan(Q, P, interpret, x.reshape(b, T, H * P),
              B.reshape(b, T, G * N), C.reshape(b, T, G * N), dtg, cum,
              jnp.repeat(D.astype(f32), P).reshape(G, 1, R * P))
    return y.reshape(b, T, H, P)
