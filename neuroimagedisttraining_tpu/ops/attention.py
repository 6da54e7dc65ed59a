"""Causal softmax attention whose scores never leave the chip.

    s_a,i,t = (dk + ds)^-1/2 (q_a,i . k_a,t + qs_a,i . ks_t)    t <= i, float32
    o_a,i   = sum_t softmax_t(s_a,i,.) v_a,t

and, under a sliding ``window`` of ``W`` keys, ``i - W < t <= i`` (the
query's own key among the ``W``: Trinity-Mini's sliding layers,
models/trinity3d.py); ``window=None`` is the whole triangle.

for heads ``a`` with ``dk`` score dimensions of their own and ``dv`` value
dimensions (the two apart), and optionally ``ds`` more score dimensions
whose KEY is one a token, shared by every head (``qs [B, T, A, ds]``
against ``ks [B, T, 1, ds]``: Moonlight's rotary key,
models/moonlight3d.py). Exact over the whole sequence or the window: no
key inside either is dropped, nothing is summarised, and no pair outside
either is computed.

What runs where. :func:`causal_attention` is the one entry, for every
trunk's exact causal attention (models/olmoe3d.py, nemotronh3d.py,
zaya3d.py, moonlight3d.py, trinity3d.py), with the heads alone (``q [B, T, A, dk]``) or
grouped over their key/value heads (``q [B, T, Hkv, G, dk]``: query head
``g * G + r`` reads key/value head ``g``). On a TPU, for shapes
:func:`kernel_tiles` passes (the published Moonlight and Trinity-Mini
layers do) and a
caller that does not say ``kernel=False``, it is :func:`attention_kernel`:
one Pallas kernel forward and one backward, in which a ``[block, block]``
tile of scores is made, exponentiated and multiplied into the values
inside vector memory. Everywhere else it is one of the two plain forms
beside it here: :func:`causal_gq_attention`, one ``[T, T]`` block of
scores, where the sequence is no longer than ``block``; beyond,
:func:`blocked_causal_attention`, a block of queries at a time under
``jax.checkpoint``, which writes every block's float32 scores to HBM and
reads them back, in the forward, the layer's rematerialised forward, the
block's own and twice in the backward: 254.9 ms of a 520 ms step at 8.7%
of the roofline (PERF.md, PR 40), where the kernels took 95.5 of 360 at
23.2% (PR 42: a forward, the rematerialised layer's second forward and a
backward a layer) and take one forward and one backward a layer since PR
45 (``KEPT``, below). The plain forms take the shared part concatenated
(``[q, qs]`` and ``[k, ks repeated a head]``) and autodiff's backward.

**Forward** (grid: volume, head, block of queries). A head's keys and
values wait in vector memory whole (3.7 MB at 4,864 tokens, fetched once a
head, not once a block of queries); the program loops over the key blocks
below its diagonal under a running maximum and sum, then takes the
diagonal's block under the causal mask: no block above the diagonal is
computed, and the mask costs nothing below it. Under a window of ``w``
blocks the loop starts at block ``i - w + 1`` and block ``i - w`` is
taken once under the window's edge mask (``col > row``): ``w + 1`` key
blocks a program and no more. Grouped heads are a head map in the index
maps: query head ``a`` fetches key/value head ``a // G`` (once a group:
consecutive programs ask for the same block). It writes ``o`` and the
rows' log-sum-exp ``[B, A, T]``, float32.

**Backward** (a ``custom_vjp``; grid: volume, head, block of keys). The
residuals are the operands, ``o`` and the log-sum-exp: nothing ``[T, T]``.
The last two carry names (``KEPT``) by which a rematerialised layer keeps
them, so that its second forward does not run the forward kernel for them
again (models/tokens3d.py ``layer_stack``); under no such policy the names
are identities.
One sweep: a program holds its block of keys and values, loops over the
query blocks from its diagonal down, remakes each tile of probabilities
from the log-sum-exp (TRANSPOSED, keys down the sublanes: the rows'
statistics then lie along the lanes as they are stored, and ``dk``, ``dv``
are plain products), and adds the tile's part of ``dq`` to a float32
buffer of the head's queries in vector memory, written out after the
head's last block. The shared key's cotangent is summed over the heads in
the kernel, in float32; so are a key/value head's ``dk`` and ``dv`` over
its group of query heads (float32 outputs that wait in vector memory
whole while the group's programs run, cast outside). Under a window the
sweep over query blocks ends at block ``j + w``, taken under the edge
mask.

**Precision**, as the plain form has it and no narrower at any step:
operands in the compute dtype, products accumulated in float32; the scale,
the mask, the maximum, the exponential, the sum and the accumulators
float32; the probabilities cast to the compute dtype only to meet ``v``,
their cotangent only to meet ``q`` and ``k``. Float32 operands stay float32
throughout. What differs is the order of the sums, and that a tile's
probabilities are normalised after the product with ``v`` (by the row's
whole sum) and not before it.

The shared part rides a lane tile of its own: ``qs`` and ``ks`` are
zero-padded to 128 columns outside the kernel (the matrix unit contracts
128 rows at a time either way), so no slice inside it starts off a tile's
boundary, and the padding's gradient is autodiff's.
"""

from __future__ import annotations

import functools
import math

import jax
import jax.numpy as jnp
from jax.ad_checkpoint import checkpoint_name
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu

from neuroimagedisttraining_tpu.ops.ssd import _NT, _dot  # a @ b, a @ b.T

_LANES = 128
#: what a masked score reads: finite, so that no row's running maximum is
#: ever ``-inf`` and no exponent ``inf - inf``
_MASKED = -1e30
#: queries a forward program, keys a backward program and both sides of a
#: tile of scores: the largest of these the sequence is whole blocks of
#: (PERF.md, PR 42: how 256 was picked)
_BLOCKS = (256, 128)
#: the forward kernel's two outputs as a rematerialisation policy can ask
#: for them (``jax.checkpoint_policies.save_only_these_names(*KEPT)``:
#: models/tokens3d.py ``layer_stack``): ``o`` and the rows' log-sum-exp are
#: the backward kernel's only residuals that are not the layer's own
#: operands, so a layer that keeps them does not run the forward kernel
#: again. Names no policy asks for are identities.
KEPT = ("attention_o", "attention_lse")
#: a head's operands wait in vector memory whole: 23 MB in the backward at
#: 4,864 tokens, above the compiler's default allowance of 16
_VMEM_LIMIT = 64 * 2 ** 20


def kernel_tiles(T: int, dk: int, ds: int, dv: int, window: int | None = None,
                 groups: int = 1) -> bool:
    """Whether the kernels' blocks tile ``T`` tokens of heads with ``dk``
    score and ``dv`` value dimensions and ``ds`` shared ones: the sequence
    whole blocks of whole lane tiles (a tile of scores is ``[block,
    block]``), ``dk`` and ``dv`` whole lane tiles, ``ds`` nothing or a
    whole fraction of one (the published Moonlight layer: 4,864 = 19 x
    256; 128, 64, 128); a ``window`` whole blocks (Trinity-Mini's 2,048 =
    8 x 256); heads in ``groups`` of more than one only without a shared
    part (no caller has both); and a head's whole sequence fits vector
    memory beside the tiles (the backward holds ``q``, the padded shared
    part and ``do`` twice, ``dq`` twice and once more in float32, and for
    grouped heads the group's ``dk`` and ``dv`` twice in float32: 7 KB a
    token of these widths at float32 operands, 34 MB at 4,864)."""
    shared = _LANES if ds else 0
    resident = T * 4 * (2 * (dk + shared + dv) + 3 * (dk + shared)
                        + 2 * shared + (2 * (dk + dv) if groups > 1 else 0))
    return (T % _BLOCKS[-1] == 0 and dk % _LANES == 0 and dv % _LANES == 0
            and (ds == 0 or (_LANES % ds == 0 and groups == 1))
            and (window is None or window % _block_of(T) == 0)
            and resident <= _VMEM_LIMIT * 3 // 4)


def takes_kernel(T: int, dk: int, ds: int, dv: int, kernel: bool,
                 window: int | None = None, groups: int = 1) -> bool:
    """Whether :func:`causal_attention` runs the kernels for such shapes:
    on a TPU, where the blocks tile, for a caller that did not say
    ``kernel=False``."""
    return (kernel and jax.default_backend() == "tpu"
            and kernel_tiles(T, dk, ds, dv, _closes(window, T), groups))


def _closes(window: int | None, T: int) -> int | None:
    """The window, or ``None`` where it never closes over ``T`` tokens."""
    return None if window is None or window >= T else int(window)


def causal_attention(q, k, v, block: int, dtype, *, q_shared=None,
                     k_shared=None, kernel: bool = True,
                     window: int | None = None):
    """``q [B, T, A, dk]`` or grouped ``[B, T, Hkv, G, dk]``, ``k [B, T,
    A | Hkv, dk]``, ``v [B, T, A | Hkv, dv]`` and optionally ``q_shared
    [B, T, A, ds]`` with ``k_shared [B, T, 1, ds]`` -> ``[B, T, heads *
    dv]``, the probabilities cast to ``dtype`` to meet ``v``. With a
    ``window`` (static) query ``i`` reads the keys ``i - window < t <= i``:
    ``window`` keys with its own; ``None``, or a window the sequence never
    fills, is the whole causal triangle.

    On a TPU, for shapes :func:`kernel_tiles` passes, this is
    :func:`attention_kernel`, unless the caller says ``kernel=False`` (an
    EAGER call: a kernel is compiled anew on every one; the three trunks
    whose cells were measured on the plain form: ROADMAP D17). Everywhere
    else one block of scores where ``T <= block``, and ``block`` queries a
    block beyond."""
    ds = 0 if q_shared is None else q_shared.shape[-1]
    window = _closes(window, q.shape[1])
    groups = q.shape[3] if q.ndim == 5 else 1
    if takes_kernel(q.shape[1], q.shape[-1], ds, v.shape[-1], kernel,
                    window, groups):
        return attention_kernel(q, k, v, q_shared, k_shared, window=window)
    if ds:
        q = jnp.concatenate([q, q_shared], axis=-1)
        k = jnp.concatenate(
            [k, jnp.broadcast_to(k_shared, k.shape[:-1] + (ds,))], axis=-1)
    if q.shape[1] <= block:
        return causal_gq_attention(q, k, v, dtype, window)
    return blocked_causal_attention(q, k, v, block, dtype, window)


# ---------- the plain forms ----------


def _spellings(q):
    """The two products' einsums, ``(scores, values)``: heads ``h`` alone
    for ``q [B, T, A, d]``, grouped ``gr`` for ``q [B, T, Hkv, G, d]``
    (the letters the trunks' own lines had: a product's spelling is its
    name in a trace)."""
    h = "gr" if q.ndim == 5 else "h"
    return f"bq{h}d,bk{h[0]}d->b{h}qk", f"b{h}qk,bk{h[0]}d->bq{h}d"


def causal_gq_attention(q, k, v, dtype, window: int | None = None):
    """Causal softmax attention as ONE block of scores: ``q [B, T, A,
    d]``, or over grouped heads ``[B, T, Hkv, G, d]`` (query head ``g * G
    + r`` reads key/value head ``g``), ``k [B, T, A | Hkv, d]``, ``v [B,
    T, A | Hkv, dv]`` -> ``[B, T, heads * dv]``; scores and softmax in
    float32, scaled by ``d^-1/2``; with a ``window``, the keys ``i -
    window < t <= i`` alone."""
    B, T, d = q.shape[0], q.shape[1], q.shape[-1]
    to_scores, to_values = _spellings(q)
    scores = jnp.einsum(to_scores, q, k, preferred_element_type=jnp.float32)
    scores = scores / jnp.sqrt(jnp.float32(d))
    causal = jnp.tril(jnp.ones((T, T), bool))
    if window is not None:
        causal = causal & ~jnp.tril(jnp.ones((T, T), bool), -window)
    if q.ndim == 4:
        # OLMoE's spelling: without the reshape its step is the same
        # program numbered otherwise, and tests/test_tpu_compile.py
        # PARENT_STEPS holds the numbers too (ROADMAP D17)
        causal = causal[None, None]
    scores = jnp.where(causal, scores, -jnp.inf)
    p = jax.nn.softmax(scores, axis=-1).astype(dtype)
    return jnp.einsum(to_values, p, v).reshape(B, T, -1)


def blocked_causal_attention(q, k, v, block: int, dtype,
                             window: int | None = None):
    """Causal softmax attention, exact over the whole sequence, a block
    of queries at a time: the operands of :func:`causal_gq_attention`
    (the score width and the value width apart) -> ``[B, T, heads *
    dv]``; scores and softmax in float32, scaled by ``dk^-1/2``.

    For sequences whose ``[T, T]`` scores of all heads do not fit: queries
    ``start .. start + block - 1`` read the keys ``0 .. start + block - 1``
    and no later one, so no pair above the diagonal's blocks is computed
    and a ``[B, A, block, start + block]`` block of scores is the largest
    that is ever alive (a Python loop over static extents; the last block
    is what is left). Each block is rematerialised in the backward pass
    (``jax.checkpoint``): only ``q``, ``k``, ``v`` are kept, not the
    causal triangle of probabilities (1.5 GB a layer in float32 at 2 x 16
    heads x 4,864 tokens). No key is dropped and nothing is summarised.

    With a ``window`` a block reads the keys from ``max(0, start + 1 -
    window)`` on and no earlier one: no pair before the window's first
    block of keys is computed either."""
    T, dk = q.shape[1], q.shape[-1]
    scale = 1.0 / math.sqrt(dk)
    to_scores, to_values = _spellings(q)

    def rows_from(start, first):
        def rows(qb, kb, vb):
            s = jnp.einsum(to_scores, qb, kb,
                           preferred_element_type=jnp.float32) * scale
            # positions of the block's queries, counted from its first key
            at = (start - first + jnp.arange(qb.shape[1]))[:, None]
            seen = at >= jnp.arange(kb.shape[1])[None]
            if window is not None:
                seen &= at - jnp.arange(kb.shape[1])[None] < window
            p = jax.nn.softmax(jnp.where(seen, s, -jnp.inf), axis=-1)
            return jnp.einsum(to_values, p.astype(dtype), vb)
        return jax.checkpoint(rows)

    outs = []
    for start in range(0, T, block):
        end = min(start + block, T)
        first = 0 if window is None else max(0, start + 1 - window)
        outs.append(rows_from(start, first)(
            q[:, start:end], k[:, first:end], v[:, first:end]))
    out = jnp.concatenate(outs, axis=1)
    return out.reshape(out.shape[0], T, -1)


# ---------- the kernels ----------
#
# Arrays reach them as the model holds them, ``[B, T, A * d]`` split into
# ``[B, T / block, block, A * d]`` (no copy): a head is a lane tile (or
# several) of the last axis, a block an index of the second. What a program
# loops over is indexed on that second, untiled axis.


def _visible(block: int, transposed: bool = False, edge: bool = False):
    """``[block, block]`` of a diagonal tile: whether the row's query sees
    the column's key (``transposed``: keys down the rows). ``edge``: of
    the tile a whole window of blocks before the diagonal's, where query
    ``r`` still sees the keys ``c > r`` (the window holds ``window`` keys
    with the query's own)."""
    row, col = (jax.lax.broadcasted_iota(jnp.int32, (block, block), d)
                for d in (0, 1))
    if edge:
        return (row > col) if transposed else (col > row)
    return (col >= row) if transposed else (row >= col)


#: the kernels' loops over blocks (tests/test_attention_kernel.py counts a
#: program's trips through a stub in its place)
_loop = jax.lax.fori_loop


def _forward_kernel(scale, shared, w, *refs):
    """One block of queries of one head against the head's keys up to the
    block's end; with a window of ``w`` blocks, from the block ``w``
    before its own on."""
    if shared:
        q_ref, qs_ref, k_ref, ks_ref, v_ref, o_ref, lse_ref = refs
    else:
        q_ref, k_ref, v_ref, o_ref, lse_ref = refs
    f32 = jnp.float32
    block = q_ref.shape[0]
    i = pl.program_id(2)
    q = q_ref[...]
    qs = qs_ref[...] if shared else None

    def scores(j):
        s = _dot(q, k_ref[j], _NT)
        if shared:
            s = s + _dot(qs, ks_ref[j], _NT)
        return s * scale

    def add(carry, s, j):
        m, l, acc = carry
        m_new = jnp.maximum(m, jnp.max(s, axis=1, keepdims=True))
        alpha = jnp.exp(m - m_new)
        p = jnp.exp(s - m_new)
        l = alpha * l + jnp.sum(p, axis=1, keepdims=True)
        acc = alpha * acc + _dot(p.astype(v_ref.dtype), v_ref[j])
        return m_new, l, acc

    carry = (jnp.full((block, 1), _MASKED, f32), jnp.zeros((block, 1), f32),
             jnp.zeros((block, v_ref.shape[-1]), f32))
    whole = lambda j, c: add(c, scores(j), j)
    if w is None:
        carry = _loop(0, i, whole, carry)
    else:
        carry = _loop(jnp.maximum(i - w + 1, 0), i, whole, carry)
        # the window's edge tile, under its mask: one trip where the
        # window has closed (block i - w exists), none before
        edge = i - w
        carry = _loop(
            jnp.maximum(edge, 0), jnp.where(edge >= 0, edge + 1, 0),
            lambda j, c: add(c, jnp.where(_visible(block, edge=True),
                                          scores(j), _MASKED), j), carry)
    # the diagonal's tile, under the mask
    m, l, acc = add(carry, jnp.where(_visible(block), scores(i), _MASKED), i)
    o_ref[...] = (acc / l).astype(o_ref.dtype)
    lse_ref[...] = _row(m + jnp.log(l))


def _row(column):
    """``[n, 1]`` down the sublanes -> ``[1, n]`` along the lanes: the
    diagonal of the column spread over ``n`` lanes."""
    n = column.shape[0]
    row, col = (jax.lax.broadcasted_iota(jnp.int32, (n, n), d)
                for d in (0, 1))
    return jnp.sum(jnp.where(row == col, column, 0.0), axis=0,
                   keepdims=True)


def _backward_kernel(scale, shared, w, groups, *refs):
    """One block of keys of one head against the head's queries from the
    block's start on (with a window of ``w`` blocks, up to the block ``w``
    after its own): ``dk``, ``dv`` whole, its part of every ``dq``. Query
    heads in ``groups`` of more than one read one key/value head: its
    ``dk``, ``dv`` gather over the group in float32, the arrays waiting
    whole as the shared key's cotangent does."""
    if shared:
        (q_ref, qs_ref, k_ref, ks_ref, v_ref, do_ref, lse_ref, delta_ref,
         dq_ref, dqs_ref, dk_ref, dks_ref, dv_ref, dq_acc, dqs_acc) = refs
    else:
        (q_ref, k_ref, v_ref, do_ref, lse_ref, delta_ref,
         dq_ref, dk_ref, dv_ref, dq_acc) = refs
    f32, dtype = jnp.float32, k_ref.dtype
    blocks, block = q_ref.shape[:2]
    a, j = pl.program_id(1), pl.program_id(2)

    @pl.when(j == 0)
    def _():
        dq_acc[...] = jnp.zeros_like(dq_acc)
        if shared:
            dqs_acc[...] = jnp.zeros_like(dqs_acc)

    k, v = k_ref[...], v_ref[...]
    ks = ks_ref[...] if shared else None

    def tile(i, carry, visible=None):
        """Query block ``i``: the transposed tile ``[keys, queries]``."""
        q, do = q_ref[i], do_ref[i]
        s = _dot(k, q, _NT)
        if shared:
            qs = qs_ref[i]
            s = s + _dot(ks, qs, _NT)
        p = jnp.exp(s * scale - lse_ref[i])
        if visible is not None:
            p = jnp.where(visible, p, 0.0)
        ds = (p * (_dot(v, do, _NT) - delta_ref[i]) * scale).astype(dtype)
        ds_t = ds.T
        dq_acc[i] += _dot(ds_t, k)
        out = [carry[0] + _dot(ds, q), carry[1] + _dot(p.astype(dtype), do)]
        if shared:
            dqs_acc[i] += _dot(ds_t, ks)
            out.append(carry[2] + _dot(ds, qs))
        return tuple(out)

    widths = (k.shape[-1], v.shape[-1]) + ((_LANES,) if shared else ())
    carry = tuple(jnp.zeros((block, w), f32) for w in widths)
    # the diagonal's tile, under the mask
    carry = tile(j, carry, _visible(block, transposed=True))
    if w is None:
        carry = _loop(j + 1, blocks, tile, carry)
    else:
        edge = j + w
        carry = _loop(j + 1, jnp.minimum(edge, blocks), tile, carry)
        # the window's edge tile: one trip where block j + w exists
        carry = _loop(
            jnp.minimum(edge, blocks), jnp.minimum(edge + 1, blocks),
            lambda i, c: tile(i, c, _visible(block, transposed=True,
                                             edge=True)), carry)
    if groups == 1:
        dk_ref[...] = carry[0].astype(dk_ref.dtype)
        dv_ref[...] = carry[1].astype(dv_ref.dtype)
    else:
        # over the group's query heads, in order: the arrays wait whole

        @pl.when(a % groups == 0)
        def _():
            dk_ref[j] = carry[0]
            dv_ref[j] = carry[1]

        @pl.when(a % groups > 0)
        def _():
            dk_ref[j] += carry[0]
            dv_ref[j] += carry[1]
    if shared:
        # the one key a token, over the heads: the array waits whole

        @pl.when(a == 0)
        def _():
            dks_ref[j] = carry[2]

        @pl.when(a > 0)
        def _():
            dks_ref[j] += carry[2]

    @pl.when(j == pl.num_programs(2) - 1)
    def _():
        dq_ref[...] = dq_acc[...].astype(dq_ref.dtype)
        if shared:
            dqs_ref[...] = dqs_acc[...].astype(dqs_ref.dtype)


def _spec(block, index):
    return pl.BlockSpec(block, index, memory_space=pltpu.VMEM)


def _reshaped(x, shape):
    """An array, or the description of an output, in another shape."""
    if isinstance(x, jax.ShapeDtypeStruct):
        return jax.ShapeDtypeStruct(shape, x.dtype)
    return x.reshape(shape)


def _specs(T: int, block: int, dk: int, dv: int, groups: int = 1):
    """Block specs on a grid ``(B, A, T / block)``, each with a function
    that splits its array to match: ``one`` block of tokens at the grid's
    last index and a head's ``whole`` sequence, of arrays ``[B, T / block,
    block, A * d]``, for a head's ``dk`` score (``q``), ``dv`` value
    (``v``) or 128 padded shared (``qs``) columns, the key every head
    shares (``ks``, ``[B, T, 128]``) and the rows' statistics (``stat``,
    ``[B, A, T]`` as ``[B, A, T / block, 1, block]``). ``k`` and ``kv``
    are the key/value head's ``dk`` and ``dv`` columns of arrays ``[B, T /
    block, block, A / groups * d]``: THE HEAD MAP, query head ``a`` reads
    key/value head ``a // groups`` (``q`` and ``v`` themselves where the
    heads stand alone)."""
    n = T // block

    def tokens(spec):
        return lambda x: (_reshaped(x, (x.shape[0], n, block, x.shape[-1])),
                          spec)

    def stat(spec):
        return lambda x: (_reshaped(x, (*x.shape[:2], n, 1, block)), spec)

    def one(d, head=lambda a: a):
        return tokens(_spec((None, None, block, d),
                            lambda b, a, i: (b, i, 0, head(a))))

    def whole(d, head=lambda a: a):
        return tokens(_spec((None, n, block, d),
                            lambda b, a, i: (b, 0, 0, head(a))))

    first = lambda a: 0  # the one key every head reads
    kv_head = (lambda a: a) if groups == 1 else (lambda a: a // groups)
    return dict(
        one={"q": one(dk), "qs": one(_LANES), "v": one(dv),
             "ks": one(_LANES, first),
             "k": one(dk, kv_head), "kv": one(dv, kv_head),
             "stat": stat(_spec((None, None, None, 1, block),
                                lambda b, a, i: (b, a, i, 0, 0)))},
        whole={"q": whole(dk), "qs": whole(_LANES), "v": whole(dv),
               "ks": whole(_LANES, first),
               "k": whole(dk, kv_head), "kv": whole(dv, kv_head),
               "stat": stat(_spec((None, None, n, 1, block),
                                  lambda b, a, i: (b, a, 0, 0, 0)))})


def _block_of(T: int) -> int:
    return next(b for b in _BLOCKS if T % b == 0)


def _params(*semantics):
    return pltpu.CompilerParams(dimension_semantics=semantics,
                                vmem_limit_bytes=_VMEM_LIMIT)


def _forward(A, ds, interpret, window, groups, q, qs, k, ks, v):
    """``o [B, T, A * dv]`` and the rows' log-sum-exp ``[B, A, T]``."""
    B, T = q.shape[:2]
    dk, shared = q.shape[-1] // A, qs is not None
    dv = v.shape[-1] * groups // A
    block = _block_of(T)
    s = _specs(T, block, dk, dv, groups)
    one, whole = s["one"], s["whole"]
    operands = [one["q"](q), one["qs"](qs), whole["k"](k), whole["ks"](ks),
                whole["kv"](v)] if shared else [
        one["q"](q), whole["k"](k), whole["kv"](v)]
    outs = [one["v"](jax.ShapeDtypeStruct((B, T, A * dv), v.dtype)),
            one["stat"](jax.ShapeDtypeStruct((B, A, T), jnp.float32))]
    o, lse = pl.pallas_call(
        functools.partial(_forward_kernel, 1.0 / math.sqrt(dk + ds), shared,
                          None if window is None else window // block),
        grid=(B, A, T // block),
        in_specs=[spec for _, spec in operands],
        out_specs=[spec for _, spec in outs],
        out_shape=[x for x, _ in outs],
        compiler_params=_params("parallel", "parallel", "parallel"),
        interpret=interpret, name="attention_forward",
    )(*(x for x, _ in operands))
    return o.reshape(B, T, A * dv), lse.reshape(B, A, T)


@functools.partial(jax.custom_vjp, nondiff_argnums=(0, 1, 2, 3, 4))
def _attend(A, ds, interpret, window, groups, q, qs, k, ks, v):
    """``o [B, T, A * dv]`` from ``q [B, T, A * dk]``, ``k [B, T, A /
    groups * dk]``, ``v [B, T, A / groups * dv]`` and, or ``None``, ``qs
    [B, T, A * 128]`` and ``ks [B, T, 128]`` whose first ``ds`` columns
    count (the rest are zero); ``window`` tokens, whole blocks, or
    ``None``."""
    return _forward(A, ds, interpret, window, groups, q, qs, k, ks, v)[0]


def _attend_fwd(A, ds, interpret, window, groups, q, qs, k, ks, v):
    o, lse = map(checkpoint_name, _forward(
        A, ds, interpret, window, groups, q, qs, k, ks, v), KEPT)
    return o, (q, qs, k, ks, v, o, lse)


def _attend_bwd(A, ds, interpret, window, groups, residuals, do):
    q, qs, k, ks, v, o, lse = residuals
    B, T = q.shape[:2]
    dk, shared = q.shape[-1] // A, qs is not None
    dv = v.shape[-1] * groups // A
    block = _block_of(T)
    f32 = jnp.float32
    # sum_t p dp a row: what the softmax's backward takes off every dp
    delta = jnp.sum((o.astype(f32) * do.astype(f32)).reshape(B, T, A, dv),
                    axis=-1).transpose(0, 2, 1)
    s = _specs(T, block, dk, dv, groups)
    one, whole = s["one"], s["whole"]
    like = lambda x, dtype=None: jax.ShapeDtypeStruct(x.shape,
                                                      dtype or x.dtype)
    if shared:
        operands = [whole["q"](q), whole["qs"](qs), one["k"](k),
                    one["ks"](ks), one["kv"](v), whole["v"](do),
                    whole["stat"](lse), whole["stat"](delta)]
        # the shared key's cotangent gathers over the heads in float32
        outs = [whole["q"](like(q)), whole["qs"](like(qs)),
                one["k"](like(k)), whole["ks"](like(ks, f32)),
                one["kv"](like(v))]
    else:
        operands = [whole["q"](q), one["k"](k), one["kv"](v),
                    whole["v"](do), whole["stat"](lse),
                    whole["stat"](delta)]
        # a group's dk, dv gather over its query heads in float32
        outs = [whole["q"](like(q))] + (
            [one["k"](like(k)), one["kv"](like(v))] if groups == 1 else
            [whole["k"](like(k, f32)), whole["kv"](like(v, f32))])
    scratch = [pltpu.VMEM((T // block, block, dk), f32)]
    if shared:
        scratch.append(pltpu.VMEM((T // block, block, _LANES), f32))
    grads = pl.pallas_call(
        functools.partial(_backward_kernel, 1.0 / math.sqrt(dk + ds), shared,
                          None if window is None else window // block,
                          groups),
        grid=(B, A, T // block),
        in_specs=[spec for _, spec in operands],
        out_specs=[spec for _, spec in outs],
        out_shape=[x for x, _ in outs],
        scratch_shapes=scratch,
        # a head's dq gathers over its key blocks, the shared key's
        # cotangent over the heads, a group's dk and dv over its query
        # heads: both axes in order
        compiler_params=_params("parallel", "arbitrary", "arbitrary"),
        interpret=interpret, name="attention_backward",
    )(*(x for x, _ in operands))
    grads = [g.reshape(B, T, g.shape[-1]) for g in grads]
    if not shared:
        return (grads[0], None, grads[1].astype(k.dtype), None,
                grads[2].astype(v.dtype))
    grads[3] = grads[3].astype(ks.dtype)
    return tuple(grads)


_attend.defvjp(_attend_fwd, _attend_bwd)


def attention_kernel(q, k, v, q_shared=None, k_shared=None, *,
                     window: int | None = None, interpret: bool = False):
    """:func:`causal_attention` through the kernels, for shapes
    :func:`kernel_tiles` passes (``q`` alone or grouped, as there);
    ``interpret`` runs them in Pallas' interpreter (the CPU tests)."""
    B, T, dk = q.shape[0], q.shape[1], q.shape[-1]
    groups = q.shape[3] if q.ndim == 5 else 1
    A, dv = q.shape[2] * groups, v.shape[-1]
    ds = 0 if q_shared is None else q_shared.shape[-1]
    window = _closes(window, T)
    if not kernel_tiles(T, dk, ds, dv, window, groups):
        raise ValueError(f"attention_kernel: no blocks for {T} tokens of "
                         f"heads of {dk} + {ds} score and {dv} value "
                         f"dimensions, a window of {window}, groups of "
                         f"{groups}")
    qs = ks = None
    if ds:
        pad = [(0, 0)] * 3 + [(0, _LANES - ds)]
        qs = jnp.pad(q_shared, pad).reshape(B, T, A * _LANES)
        ks = jnp.pad(k_shared, pad).reshape(B, T, _LANES)
    return _attend(A, ds, interpret, window, groups, q.reshape(B, T, A * dk),
                   qs, k.reshape(B, T, -1), ks, v.reshape(B, T, -1))
