"""Trinity-Mini's layers over 3D patch tokens: forward, loss and work,
float32.

Written from the public description of ``Trinity-Mini`` (arcee-ai;
``config.json``, ``model_type`` ``afmoe``; what it does not show is the
public ``modeling_afmoe.py`` as ISSUE 44 writes it). Hidden 2048, 32 query
heads on 4 key/value heads of 128, a window of 2,048 in the sliding
layers, a leading dense feed-forward of 6144, then experts of 1024 (128
routed, 8 a token) beside one shared; eps 1e-5, no bias:

    x = N_1(h);  q_a = Nq(x Wq_a);  k_g = Nk(x Wk_g);  v_g = x Wv_g         g = a // 8
    sliding: q_a, k_g = rope(q_a), rope(k_g)   theta 1e4;    full: no position
    s_a,i,t = 128^-1/2 q_a,i . k_g,t;   sliding: i - W < t <= i;   full: t <= i;   o = softmax(s) v
    h = h + N_2((concat_a(o_a) * sigmoid(x Wg)) Wo)
    u = N_3(h)
    dense:   m = (silu(u Wgate) * (u Wup)) Wdown
    expert:  s = sigmoid(u Wr);  C = top-8 of s (+ b = 0);  g_e = 2.826 s_e / (sum_C s + 1e-20)
             m = sum_{e in C, held} g_e E_e(u) + S(u)
    h = h + N_4(m)

computed from ONE dense mask a layer kind (``trinity_ops.py``
``grouped_attention``), the key heads repeated over their groups, the
experts a loop over the held ids with a 0/1 selection: no blocks of
queries, no kernels.

Fed as this system feeds a trunk (``assumed`` in the configuration file):
tokens are 8^3 patches of the volume standardised over its own voxels,
zero-padded, through one linear patch embedding; the logit is one bias-free
linear on the mean over positions of the final-norm states.

Departures from the published description, each by need:

- the parameter tree's names and layouts are the system's under test
  (``patch_embed``, ``layers_i/{attn_norm, self_attn/{q_proj, k_proj, v_proj,
  q_norm, k_norm, gate_proj, o_proj}, attn_post_norm, mlp_norm, ffn |
  moe/{router, up, down} + shared, mlp_post_norm}``, ``final_norm``,
  ``head``; ``[in, out]`` kernels, ``[count, in, out]`` expert stacks with
  gate and up side by side); dense or expert is read from the names a
  layer holds, what it attends to from ``cfg["layer_types"]``;
- **the expert share**: ``cfg["held"] = (first, count)`` of the router's
  128 experts have weights here (16: one of 8 chips that share each layer
  by expert parallelism); what the others would add is left out, in the
  program alike. ``held = (0, E)`` with all the weights is the uncut layer
  (tests/test_trinity3d.py adds the shares up to it);
- ``expert_bias`` is zeros (its update is a training recipe outside the
  gradient), and there is no auxiliary loss;
- the token embedding (and ``mup_enabled``'s scaling of it), the LM head,
  generation and the cache are not built.

``forward`` maps over rows, and the attention over key/value heads, so that
a batch's scores are alive one row and group at a time (0.76 GB at 4,864
tokens). ``remat=True`` rematerialises each layer and each group in a
gradient, so that one at the published widths fits the chip
(benchmark/trinity_check.py); the values are the same.
``cfg["sliding_window"] = None`` is the control without the window: every
layer reads the whole causal triangle, the sliding ones still under their
rotary embedding.

The tape counts useful work only (``trinity_ops.py`` says how each new
layer is recorded): at the published widths 2.91 TFLOP forward, 8.74 TFLOP
a training sample, the held experts at the uniform share of the routing.
"""

import importlib.util
import math
import os

_spec = importlib.util.spec_from_file_location(
    "benchmark_reference_trinity_ops",
    os.path.join(os.path.dirname(os.path.abspath(__file__)),
                 "trinity_ops.py"))
ops = importlib.util.module_from_spec(_spec)
_spec.loader.exec_module(ops)

SLIDING, FULL = "sliding_attention", "full_attention"
#: what the parameter shapes do not say (config.json; ``patch`` is
#: `assumed`: the configuration file says why). ``layer_types``: the
#: published layers 1-5.
PUBLISHED = {
    "heads": 32, "kv_heads": 4, "experts_per_token": 8, "held": (0, 16),
    "route_scale": 2.826, "rope_theta": 1e4, "rms_eps": 1e-5, "patch": 8,
    "sliding_window": 2048,
    "layer_types": (SLIDING, SLIDING, FULL, SLIDING, SLIDING)}
LAYERS = len(PUBLISHED["layer_types"])
CORE_RECORDS = ("/attn/scores", "/attn/values")
EXPERT_RECORDS = ("/moe/up", "/moe/down")


def attention(x, p, sliding, cfg, q, q_scores, remat, tape, name):
    B, T, _ = x.shape
    A, Hkv, eps = cfg["heads"], cfg["kv_heads"], cfg["rms_eps"]
    lin = lambda n: ops.linear(x, p[n]["kernel"], q=q, tape=tape,
                               name=f"{name}/{n}")
    q_ = ops.rms_norm(lin("q_proj").reshape(B, T, A, -1),
                      p["q_norm"]["weight"], eps)
    k_ = ops.rms_norm(lin("k_proj").reshape(B, T, Hkv, -1),
                      p["k_norm"]["weight"], eps)
    v_ = lin("v_proj").reshape(B, T, Hkv, -1)
    if sliding:
        q_, k_ = (ops.rope(q_, cfg["rope_theta"]),
                  ops.rope(k_, cfg["rope_theta"]))
    ctx = ops.grouped_attention(
        q_, k_, v_, cfg["sliding_window"] if sliding else None, q=q,
        q_scores=q_scores, remat=remat, tape=tape, name=name)
    import jax

    return ops.linear(ctx * jax.nn.sigmoid(lin("gate_proj")),
                      p["o_proj"]["kernel"], q=q, tape=tape,
                      name=name + "/o_proj")


def experts(u, p, shared, cfg, q, q_router, tape, name):
    """``(m [B, T, H], chosen [B*T, k])``: the held experts' part beside
    the shared expert, before the post-norm."""
    B, T, H = u.shape
    m = u.reshape(B * T, H)
    E = p["router"].shape[-1]
    _, g, e = ops.sigmoid_route(
        q_router(m), q_router(p["router"].astype(ops.F32)),
        cfg["experts_per_token"], cfg["route_scale"], tape=tape,
        name=name + "/router")
    y = ops.held_gated_experts(m, g, e, p["up"], p["down"], cfg["held"], E,
                               q=q, tape=tape, name=name + "/moe")
    return y.reshape(B, T, H) + ops.gated_mlp(u, shared, q=q, tape=tape,
                                              name=name + "/shared"), e


def feed_forward(h, p, cfg, q, q_router, tape, name):
    """``(m, chosen or None)``: the sub-layer's output BEFORE its
    post-norm (``N_4`` is not linear: the shares of experts add up here)."""
    u = ops.rms_norm(h, p["mlp_norm"]["weight"], cfg["rms_eps"])
    if "ffn" in p:
        return ops.gated_mlp(u, p["ffn"], q=q, tape=tape,
                             name=name + "/ffn"), None
    return experts(u, p["moe"], p["shared"], cfg, q, q_router, tape, name)


def after_attention(h, p, sliding, cfg, q, q_scores, remat, tape, name):
    eps = cfg["rms_eps"]
    y = attention(ops.rms_norm(h, p["attn_norm"]["weight"], eps), p["self_attn"],
                  sliding, cfg, q, q_scores, remat, tape, name + "/attn")
    return h + ops.rms_norm(y, p["attn_post_norm"]["weight"], eps)


def layer(h, p, sliding, cfg, q, q_scores, q_router, remat, tape, name):
    """``(h, chosen or None)`` of one layer."""
    h = after_attention(h, p, sliding, cfg, q, q_scores, remat, tape, name)
    m, e = feed_forward(h, p, cfg, q, q_router, tape, name)
    return h + ops.rms_norm(m, p["mlp_post_norm"]["weight"],
                            cfg["rms_eps"]), e


def trunk(params, x_uint8, tape=None, *, cfg=PUBLISHED, q=ops.exact,
          q_scores=ops.exact, q_router=ops.exact, remat=False):
    """``(logits [B, classes], chosen [L_E, N, k])`` of a batch computed
    together: ``N = B x tokens``, ``L_E`` the expert layers. ``q_scores``
    and ``q_router`` round the attention scores and the router's operands
    (identity in the reference proper: both are float32 by the
    architecture's definition)."""
    import jax
    import jax.numpy as jnp

    eps = cfg["rms_eps"]
    h = ops.linear(ops.patches(x_uint8, cfg["patch"], eps),
                   params["patch_embed"]["kernel"],
                   params["patch_embed"]["bias"], q=q, tape=tape,
                   name="patch_embed")
    chosen = []
    for i, kind in enumerate(cfg["layer_types"]):
        one = lambda h, p, i=i, kind=kind: layer(
            h, p, kind == SLIDING, cfg, q, q_scores, q_router, remat, tape,
            f"layers_{i}")
        h, e = (jax.checkpoint(one) if remat else one)(
            h, params[f"layers_{i}"])
        if e is not None:
            chosen.append(e)
    pooled = jnp.mean(ops.rms_norm(h, params["final_norm"]["weight"], eps),
                      axis=1)
    logits = ops.read_out(pooled, params["head"]["kernel"], q=q, tape=tape,
                          name="head")
    return logits, jnp.stack(chosen)


def forward(params, batch_stats, x_uint8, tape=None, **kw):
    """``x_uint8`` ``[B, D, H, W]`` -> logits ``[B, num_classes]``, a row
    at a time."""
    import jax

    if tape is not None:  # traced abstractly at one row: record it
        return trunk(params, x_uint8, tape, **kw)[0]
    return jax.lax.map(lambda x: trunk(params, x[None], **kw)[0][0], x_uint8)


def training_loss(params, batch_stats, x_uint8, y, **kw):
    """Mean BCE of the batch (there is no auxiliary term). ``jax.grad`` of
    it is the reference gradient."""
    import jax.numpy as jnp

    return jnp.mean(ops.bce_with_logits(
        forward(params, batch_stats, x_uint8, **kw), y))


# ---------- the new stages' operations and bytes ----------

def core_layers(tape, cfg=PUBLISHED) -> dict:
    """``{kind: [the tape's /attn/scores record of each layer of that
    kind]}``, ``kind`` one of ``cfg["layer_types"]``."""
    by_kind: dict = {}
    scores = [r for r in tape if r["name"].endswith("/attn/scores")]
    for kind, r in zip(cfg["layer_types"], scores):
        by_kind.setdefault(kind, []).append(r)
    return by_kind


def core_pairs(tape, kind: str, cfg=PUBLISHED) -> int:
    """(query, key) pairs of one sequence and head in a layer of ``kind``,
    as that layer's mask counted them."""
    return core_layers(tape, cfg)[kind][0]["out_spatial"][0]


def core_flops_per_sample(tape, kind: str, cfg=PUBLISHED) -> float:
    """Forward operations of scores and values over every pair, query head
    and layer of ``kind`` of one sample: ``2 (d + d)`` a pair."""
    return sum(2.0 * 2.0 * math.prod(r["kernel_shape"]) * r["out_spatial"][0]
               for r in core_layers(tape, cfg)[kind])


def core_bytes_per_sample(tape, kind: str, cfg=PUBLISHED,
                          act_bytes: int = 2) -> float:
    """The least one pass moves for the scores and values of one sample,
    over the layers of ``kind``: q ``[T, A d]`` read, k and v ``[T, Hkv
    d]`` read and o ``[T, A d]`` written once: the scores never leave the
    chip."""
    by_name = {r["name"]: r for r in tape}
    total = 0.0
    for r in core_layers(tape, cfg)[kind]:
        of = lambda part: by_name[r["name"][:-len("scores")] + part]
        (T,) = of("q_proj")["out_spatial"]
        wide = of("q_proj")["kernel_shape"][1]
        narrow = of("k_proj")["kernel_shape"][1]
        total += T * (2 * wide + 2 * narrow) * act_bytes
    return total


def expert_layers(tape) -> int:
    return sum(1 for r in tape if r["name"].endswith("/moe/up"))


def expert_flops_per_row(tape) -> float:
    """Forward operations of ONE (token, slot) row through a held expert's
    two matrices (gate and up side by side, then down): the roofline reader
    multiplies by the rows that really landed."""
    rows = [r for r in tape if r["name"].endswith(EXPERT_RECORDS)]
    return sum(2.0 * math.prod(r["kernel_shape"]) for r in rows) \
        / expert_layers(tape)


def expert_bytes_per_step(tape, rows: float, weight_bytes: int = 2,
                          act_bytes: int = 2) -> float:
    """The least a training step's three passes move for the grouped
    matmuls of ONE expert layer whose held experts took ``rows`` rows: the
    held experts' weights once a pass, the rows in and out."""
    total = 0.0
    for r in tape:
        if r["name"].endswith(EXPERT_RECORDS):
            n_in, n_out = r["kernel_shape"]
            total += 3.0 * (r["num_experts"] * n_in * n_out * weight_bytes
                            + rows * (n_in + n_out) * act_bytes)
    return total / expert_layers(tape)


def published_tape():
    """The tape of one sample at the published widths, this chip's 16
    experts and the cell's volume, traced abstractly (nothing runs)."""
    import jax
    import jax.numpy as jnp

    H, P, F, W, A, Hkv, d = 2048, 8, 6144, 1024, 32, 4, 128
    f = lambda *s: jax.ShapeDtypeStruct(s, jnp.float32)
    norm = lambda n=H: {"weight": f(n)}
    dense = lambda i, o: {"kernel": f(i, o)}
    mlp = lambda w: {"gate_proj": dense(H, w), "up_proj": dense(H, w),
                     "down_proj": dense(w, H)}
    common = {
        "attn_norm": norm(), "attn_post_norm": norm(), "mlp_norm": norm(),
        "mlp_post_norm": norm(),
        "self_attn": {"q_proj": dense(H, A * d), "k_proj": dense(H, Hkv * d),
                 "v_proj": dense(H, Hkv * d), "q_norm": norm(d),
                 "k_norm": norm(d), "gate_proj": dense(H, A * d),
                 "o_proj": dense(A * d, H)}}
    expert = {**common, "shared": mlp(W),
              "moe": {"router": f(H, 128), "up": f(16, H, 2 * W),
                      "down": f(16, W, H)}}
    params = {"patch_embed": {"kernel": f(P ** 3, H), "bias": f(H)},
              "final_norm": norm(), "head": dense(H, 1),
              "layers_0": {**common, "ffn": mlp(F)}}
    for i in range(1, LAYERS):
        params[f"layers_{i}"] = expert
    tape: list = []
    x = jax.ShapeDtypeStruct((1, 121, 145, 121), jnp.uint8)
    jax.eval_shape(lambda p, v: forward(p, {}, v, tape), params, x)
    return tape
