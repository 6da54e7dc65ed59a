"""EvaByte's layer over 3D patch tokens: forward, loss and work, float32.

Written from the public description of ``EvaByte`` (``config.json``,
``attention_class`` ``eva``; EVA: Zheng, Yuan, Wang, Kong, "Efficient
Attention via Control Variates", arXiv:2302.04542, in the deterministic,
causal, windowed form the released modeling code computes: the exact set is
the query's window up to itself, the partition the earlier windows' chunks,
the sampled ``omega`` replaced by a learned ``phi`` and a learned offset
``mu`` on the key summary). Hidden 4096, heads of d = 128, feed-forward
11008, windows of W = 2048, chunks of c = 16, eps 1e-5, no bias:

    N(x) = (1 + g) x / sqrt(mean(x^2) + eps)
    x = N_1(h);  q_a = rope(x Wq_a)  k_a = rope(x Wk_a)  v_a = x Wv_a     theta 1e5
    al_t = softmax over chunk j's tokens of (d^-1/2 phi_a . k_a,t)
    ks_a,j = sum_t al_t k_a,t + mu_a      vs_a,j = sum_t al_t v_a,t
    query i reads key t where t <= i and t // W = i // W, and summary j
    where (j c) // W < i // W, under one softmax of d^-1/2 q . key
    h = h + sum_a o_a Wo_a
    h = h + (silu(N_2(h) W_gate) * (N_2(h) W_up)) W_down

computed from ONE dense ``[T, T + T / c]`` mask a head (``evabyte_ops.py``
``eva_mask``): no windows as a batch axis, no kernels.

Fed as this system feeds a trunk (``assumed`` in the configuration file):
tokens are 8^3 patches of the volume standardised over its own voxels,
zero-padded, through one linear patch embedding; the logit is one bias-free
linear on the mean over positions of the final-norm states.

Departures from the published description, each by need:

- the parameter tree's names and layouts are the system's under test
  (``patch_embed``, ``layers_i/{attn_norm, eva/{q_proj, k_proj, v_proj,
  o_proj, phi, mu}, mlp_norm, ffn/{gate_proj, up_proj, down_proj}}``,
  ``final_norm``, ``head``; ``[in, out]`` kernels);
- **the head share**: the heads are read off the parameters' shapes (``phi
  [A, d]``): with 8 of the 32 heads' columns of W_q, W_k, W_v and rows of
  W_o (one of 4 chips that share each layer by tensor parallelism) what the
  other 24 would add to the stream is left out, in the program alike; with
  all 32 it is the uncut layer (tests/test_evabyte3d.py adds the four
  shares up to it);
- the byte embedding, the 8 multi-byte prediction heads and generation are
  not built.

``forward`` maps over rows, so that a batch's scores are alive one row at a
time (0.8 GB a row and layer at 4,864 tokens). ``remat=True`` rematerialises
each layer in a gradient: at the published widths a row's four layers of
scores do not fit the chip beside the parameters otherwise
(benchmark/evabyte_check.py); the values are the same.

The tape counts useful work only (``evabyte_ops.py`` says how each new
layer is recorded): at the published widths 6.02 TFLOP forward, 18.05
TFLOP a training sample.
"""

import importlib.util
import os

_spec = importlib.util.spec_from_file_location(
    "benchmark_reference_evabyte_ops",
    os.path.join(os.path.dirname(os.path.abspath(__file__)),
                 "evabyte_ops.py"))
ops = importlib.util.module_from_spec(_spec)
_spec.loader.exec_module(ops)

#: what the parameter shapes do not say (config.json; ``patch`` is
#: `assumed`: the configuration file says why)
PUBLISHED = {"window_size": 2048, "chunk_size": 16, "rope_theta": 1e5,
             "rms_eps": 1e-5, "patch": 8}
LAYERS = 4
EVA_RECORDS = ("/local_scores", "/local_values", "/remote_scores",
               "/remote_values")
MLP_RECORDS = ("/ffn/gate_proj", "/ffn/up_proj", "/ffn/down_proj")


def attention(x, p, cfg, q, q_scores, tape, name):
    """The held heads' part of the attention output: ``x [B, T, hidden] ->
    [B, T, hidden]``."""
    B, T, _ = x.shape
    A, d = p["phi"].shape
    proj = lambda n: ops.linear(x, p[n]["kernel"], q=q, tape=tape,
                                name=f"{name}/{n}").reshape(B, T, A, d)
    ctx = ops.eva_attention(
        ops.rope(proj("q_proj"), cfg["rope_theta"]),
        ops.rope(proj("k_proj"), cfg["rope_theta"]), proj("v_proj"),
        p["phi"], p["mu"], cfg["window_size"], cfg["chunk_size"], q=q,
        q_scores=q_scores, tape=tape, name=name)
    return ops.linear(ctx, p["o_proj"]["kernel"], q=q, tape=tape,
                      name=name + "/o_proj")


def layer(h, p, cfg, q, q_scores, q_stream, tape, name):
    eps = cfg["rms_eps"]
    h = q_stream(h + attention(
        ops.unit_rms_norm(h, p["attn_norm"]["weight"], eps), p["eva"], cfg,
        q, q_scores, tape, name + "/eva"))
    return q_stream(h + ops.gated_mlp(
        ops.unit_rms_norm(h, p["mlp_norm"]["weight"], eps), p["ffn"], q=q,
        tape=tape, name=name + "/ffn"))


def trunk(params, x_uint8, tape=None, *, cfg=PUBLISHED, q=ops.exact,
          q_scores=ops.exact, q_stream=ops.exact, remat=False):
    """Logits ``[B, classes]`` of a batch computed together. ``q_scores``
    and ``q_stream`` round the attention scores and the residual stream
    (identity in the reference proper: both are float32 by the
    architecture's definition)."""
    import jax
    import jax.numpy as jnp

    eps = cfg["rms_eps"]
    h = q_stream(ops.linear(
        ops.patches(x_uint8, cfg["patch"], eps),
        params["patch_embed"]["kernel"], params["patch_embed"]["bias"], q=q,
        tape=tape, name="patch_embed"))
    for i in range(sum(1 for k in params if k.startswith("layers_"))):
        one = lambda h, p, i=i: layer(h, p, cfg, q, q_scores, q_stream,
                                      tape, f"layers_{i}")
        h = (jax.checkpoint(one) if remat else one)(h, params[f"layers_{i}"])
    pooled = jnp.mean(ops.unit_rms_norm(h, params["final_norm"]["weight"],
                                        eps), axis=1)
    return ops.read_out(pooled, params["head"]["kernel"], q=q, tape=tape,
                        name="head")


def forward(params, batch_stats, x_uint8, tape=None, **kw):
    """``x_uint8`` ``[B, D, H, W]`` -> logits ``[B, num_classes]``, a row
    at a time."""
    import jax

    if tape is not None:  # traced abstractly at one row: record it
        return trunk(params, x_uint8, tape, **kw)
    return jax.lax.map(lambda x: trunk(params, x[None], **kw)[0], x_uint8)


def training_loss(params, batch_stats, x_uint8, y, **kw):
    """Mean BCE of the batch; there is no auxiliary term. ``jax.grad`` of
    it is the reference gradient."""
    import jax.numpy as jnp

    return jnp.mean(ops.bce_with_logits(
        forward(params, batch_stats, x_uint8, **kw), y))


# ---------- the new stages' operations and bytes ----------

def eva_pairs(tape) -> tuple[int, int]:
    """``(local, remote)`` (query, key) pairs of one sequence and head, as
    the first layer's mask counted them."""
    first = lambda part: next(r["out_spatial"][0] for r in tape
                              if r["name"].endswith(part))
    return first("/local_scores"), first("/remote_scores")


def eva_flops_per_sample(tape) -> float:
    """Forward operations of scores and values over every pair, head and
    layer of one sample: 4 d a pair."""
    return sum(2.0 * r["kernel_shape"][0] * r["kernel_shape"][1]
               * r["out_spatial"][0] for r in tape
               if r["name"].endswith(EVA_RECORDS))


def eva_bytes_per_sample(tape, act_bytes: int = 2) -> float:
    """The least one pass moves for the attention of one sample, over
    every layer: q, k, v read and o written once (``4 T A d``), the chunks'
    summaries written and read once (``2 x 2 (T / c) A d``): the scores
    never leave the chip."""
    total = 0.0
    for r in tape:
        if r["name"].endswith("/eva/q_proj"):
            (T,) = r["out_spatial"]
            heads_d = r["kernel_shape"][1]
            chunks = T // PUBLISHED["chunk_size"]
            total += (4 * T + 4 * chunks) * heads_d * act_bytes
    return total


def mlp_flops_per_sample(tape) -> float:
    """Forward operations of the feed-forward's three matrices for one
    sample, over every layer."""
    import math

    return sum(2.0 * math.prod(r["kernel_shape"]) * r["out_spatial"][0]
               for r in tape if r["name"].endswith(MLP_RECORDS))


def published_tape():
    """The tape of one sample at the published widths, this chip's 8 heads
    and the cell's volume, traced abstractly (nothing runs)."""
    import jax
    import jax.numpy as jnp

    d, P, F, A, hd = 4096, 8, 11008, 8, 128
    f = lambda *s: jax.ShapeDtypeStruct(s, jnp.float32)
    dense = lambda i, o: {"kernel": f(i, o)}
    one = {
        "attn_norm": {"weight": f(d)}, "mlp_norm": {"weight": f(d)},
        "eva": {"q_proj": dense(d, A * hd), "k_proj": dense(d, A * hd),
                "v_proj": dense(d, A * hd), "o_proj": dense(A * hd, d),
                "phi": f(A, hd), "mu": f(A, hd)},
        "ffn": {"gate_proj": dense(d, F), "up_proj": dense(d, F),
                "down_proj": dense(F, d)}}
    params = {"patch_embed": {"kernel": f(P ** 3, d), "bias": f(d)},
              "final_norm": {"weight": f(d)}, "head": dense(d, 1)}
    for i in range(LAYERS):
        params[f"layers_{i}"] = one
    tape: list = []
    x = jax.ShapeDtypeStruct((1, 121, 145, 121), jnp.uint8)
    jax.eval_shape(lambda p, v: forward(p, {}, v, tape), params, x)
    return tape
