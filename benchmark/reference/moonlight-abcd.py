"""Moonlight's layer over 3D patch tokens: forward, loss and work, float32.

Written from the public description of ``Moonlight-16B-A3B`` (moonshotai;
``config.json``, ``model_type`` ``deepseek_v3``; arXiv:2502.16982, its
attention DeepSeek-V2's MLA arXiv:2405.04434, its expert layer DeepSeek-
V3's arXiv:2412.19437). Hidden 2048, 16 heads of dn = 128 / dr = 64 / dv =
128, latent 512, a leading dense feed-forward of 11264, then experts of
1408 (64 routed, 6 a token) beside two shared ones; eps 1e-5, no bias:

    x = N_1(h);  q_a = x Wq_a;  qn_a, qr_a = q_a[:128], rope(q_a[128:])     theta 5e4
    [c, kr] = x Wdkv;  c = N_kv(c);  kr' = rope(kr)       ONE rotary key a token
    [kn_a, v_a] = c Wukv_a
    s_a,i,t = 192^-1/2 (qn_a,i . kn_a,t + qr_a,i . kr'_t), t <= i;  o = softmax(s) v
    h = h + concat_a(o_a) Wo
    layer 0:    h = h + (silu(u Wg) * (u Wu)) Wd,  u = N_2(h)
    layers 1..: s = sigmoid(u Wr);  C = top-6 of s (+ b = 0);  g_e = 2.446 s_e / (sum_C s + 1e-20)
                h = h + sum_{e in C, held} g_e E_e(u) + S(u)
    L = alpha x sum over expert layers of sum_e f_e P_e, a sequence

computed from ONE dense causal mask a head (``moonlight_ops.py``
``latent_attention``), the experts a loop over the held ids with a 0/1
selection: no blocks, no kernels.

Fed as this system feeds a trunk (``assumed`` in the configuration file):
tokens are 8^3 patches of the volume standardised over its own voxels,
zero-padded, through one linear patch embedding; the logit is one bias-free
linear on the mean over positions of the final-norm states.

Departures from the published description, each by need:

- the parameter tree's names and layouts are the system's under test
  (``patch_embed``, ``layers_i/{attn_norm, mla/{q_proj, kv_a_proj,
  kv_norm, kv_b_proj, o_proj}, mlp_norm, ffn | moe/{router, up, down} +
  shared}``, ``final_norm``, ``head``; ``[in, out]`` kernels, ``[count,
  in, out]`` expert stacks with gate and up side by side); a layer's kind
  is read from the names it holds;
- **the expert share**: ``cfg["held"] = (first, count)`` of the router's
  64 experts have weights here (8: one of 8 chips that share each layer
  by expert parallelism); what the others would add is left out, in the
  program alike. ``held = (0, E)`` with all the weights is the uncut layer
  (tests/test_moonlight3d.py adds the shares up to it);
- ``e_score_correction_bias`` is zeros (its update is a training recipe
  ``config.json`` does not give);
- the token embedding, the LM head, generation and the latent cache are
  not built.

``forward`` maps over rows, so that a batch's scores are alive one row at a
time (1.5 GB a row and layer at 4,864 tokens). ``remat=True``
rematerialises each layer in a gradient, so that one at the published
widths fits the chip (benchmark/moonlight_check.py); the values are the
same.

The tape counts useful work only (``moonlight_ops.py`` says how each new
layer is recorded): at the published widths 3.38 TFLOP forward, 10.13 TFLOP
a training sample, the held experts at the uniform share of the routing.
"""

import importlib.util
import math
import os

_spec = importlib.util.spec_from_file_location(
    "benchmark_reference_moonlight_ops",
    os.path.join(os.path.dirname(os.path.abspath(__file__)),
                 "moonlight_ops.py"))
ops = importlib.util.module_from_spec(_spec)
_spec.loader.exec_module(ops)

#: what the parameter shapes do not say (config.json; ``patch`` and
#: ``aux_alpha`` are `assumed`: the configuration file says why)
PUBLISHED = {
    "heads": 16, "qk_nope_head_dim": 128, "experts_per_token": 6,
    "held": (0, 8), "routed_scaling_factor": 2.446, "aux_alpha": 0.001,
    "rope_theta": 5e4, "rms_eps": 1e-5, "patch": 8}
LAYERS = 6  # the leading dense layer and five expert layers
CORE_RECORDS = ("/mla/scores", "/mla/values")
EXPERT_RECORDS = ("/moe/up", "/moe/down")


def attention(x, p, cfg, q, q_scores, tape, name):
    B, T, _ = x.shape
    A, dn = cfg["heads"], cfg["qk_nope_head_dim"]
    q_ = ops.linear(x, p["q_proj"]["kernel"], q=q, tape=tape,
                    name=name + "/q_proj").reshape(B, T, A, -1)
    kn, kr, v = ops.latent_keys_values(
        x, p, A, dn, cfg["rms_eps"], cfg["rope_theta"], q=q, tape=tape,
        name=name)
    ctx = ops.latent_attention(
        q_[..., :dn], ops.rope(q_[..., dn:], cfg["rope_theta"]), kn, kr, v,
        q=q, q_scores=q_scores, tape=tape, name=name)
    return ops.linear(ctx, p["o_proj"]["kernel"], q=q, tape=tape,
                      name=name + "/o_proj")


def experts(u, p, shared, cfg, q, q_router, tape, name):
    """``(out [B, T, H], chosen [B*T, k], balance)``: the held experts'
    part beside the shared experts, and the mean over the batch's sequences
    of the unweighted balance loss."""
    import jax.numpy as jnp

    B, T, H = u.shape
    m = u.reshape(B * T, H)
    E = p["router"].shape[-1]
    s, g, e = ops.sigmoid_route(
        q_router(m), q_router(p["router"].astype(ops.F32)),
        cfg["experts_per_token"], cfg["routed_scaling_factor"], tape=tape,
        name=name + "/router")
    y = ops.held_gated_experts(m, g, e, p["up"], p["down"], cfg["held"], E,
                               q=q, tape=tape, name=name + "/moe")
    y = y.reshape(B, T, H) + ops.gated_mlp(u, shared, q=q, tape=tape,
                                           name=name + "/shared")
    balance = jnp.mean(jnp.stack([
        ops.sequence_balance(s_b, e_b, E) for s_b, e_b in zip(
            s.reshape(B, T, E), e.reshape(B, T, -1))]))
    return y, e, balance


def layer(h, p, cfg, q, q_scores, q_router, tape, name):
    """``(h, chosen or None, balance)`` of one layer."""
    eps = cfg["rms_eps"]
    h = h + attention(ops.rms_norm(h, p["attn_norm"]["weight"], eps),
                      p["mla"], cfg, q, q_scores, tape, name + "/mla")
    u = ops.rms_norm(h, p["mlp_norm"]["weight"], eps)
    if "ffn" in p:
        return h + ops.gated_mlp(u, p["ffn"], q=q, tape=tape,
                                 name=name + "/ffn"), None, 0.0
    y, e, balance = experts(u, p["moe"], p["shared"], cfg, q, q_router, tape,
                            name)
    return h + y, e, balance


def trunk(params, x_uint8, tape=None, *, cfg=PUBLISHED, q=ops.exact,
          q_scores=ops.exact, q_router=ops.exact, remat=False):
    """``(logits [B, classes], chosen [L_E, N, k], L)`` of a batch computed
    together: ``N = B x tokens``, ``L_E`` the expert layers, ``L`` the
    weighted balance loss (the mean over the batch's sequences, summed over
    the expert layers). ``q_scores`` and ``q_router`` round the attention
    scores and the router's operands (identity in the reference proper:
    both are float32 by the architecture's definition)."""
    import jax
    import jax.numpy as jnp

    eps = cfg["rms_eps"]
    h = ops.linear(ops.patches(x_uint8, cfg["patch"], eps),
                   params["patch_embed"]["kernel"],
                   params["patch_embed"]["bias"], q=q, tape=tape,
                   name="patch_embed")
    chosen, total = [], 0.0
    for i in range(sum(1 for k in params if k.startswith("layers_"))):
        one = lambda h, p, i=i: layer(h, p, cfg, q, q_scores, q_router, tape,
                                      f"layers_{i}")
        h, e, balance = (jax.checkpoint(one) if remat else one)(
            h, params[f"layers_{i}"])
        total = total + balance
        if e is not None:
            chosen.append(e)
    pooled = jnp.mean(ops.rms_norm(h, params["final_norm"]["weight"], eps),
                      axis=1)
    logits = ops.read_out(pooled, params["head"]["kernel"], q=q, tape=tape,
                          name="head")
    return logits, jnp.stack(chosen), cfg["aux_alpha"] * total


def forward(params, batch_stats, x_uint8, tape=None, **kw):
    """``x_uint8`` ``[B, D, H, W]`` -> logits ``[B, num_classes]``, a row
    at a time."""
    import jax

    if tape is not None:  # traced abstractly at one row: record it
        return trunk(params, x_uint8, tape, **kw)[0]
    return jax.lax.map(lambda x: trunk(params, x[None], **kw)[0][0], x_uint8)


def loss_terms(params, batch_stats, x_uint8, y, **kw):
    """``(mean BCE of the batch, L)``, a row at a time: ``L`` is per
    sequence, so the batch's is the mean of its rows' own."""
    import jax
    import jax.numpy as jnp

    def row(x):
        logits, _, balance = trunk(params, x[None], **kw)
        return logits[0], balance
    logits, balance = jax.lax.map(row, x_uint8)
    return jnp.mean(ops.bce_with_logits(logits, y)), jnp.mean(balance)


def training_loss(params, batch_stats, x_uint8, y, **kw):
    """Mean BCE of the batch + ``L``. ``jax.grad`` of it is the reference
    gradient."""
    return sum(loss_terms(params, batch_stats, x_uint8, y, **kw))


# ---------- the new stages' operations and bytes ----------

def core_pairs(tape) -> int:
    """Causal (query, key) pairs of one sequence and head, as the first
    layer's mask counted them."""
    return next(r["out_spatial"][0] for r in tape
                if r["name"].endswith("/mla/scores"))


def core_flops_per_sample(tape) -> float:
    """Forward operations of scores and values over every causal pair,
    head and layer of one sample: ``2 (dn + dr + dv)`` a pair."""
    return sum(2.0 * math.prod(r["kernel_shape"]) * r["out_spatial"][0]
               for r in tape if r["name"].endswith(CORE_RECORDS))


def core_bytes_per_sample(tape, act_bytes: int = 2) -> float:
    """The least one pass moves for the scores and values of one sample,
    over every layer: q ``[T, A (dn + dr)]``, the heads' keys ``[T, A dn]``
    and the shared rotary key ``[T, dr]`` read, v ``[T, A dv]`` read and o
    written once: the scores never leave the chip."""
    by_name = {r["name"]: r for r in tape}
    total = 0.0
    for name in by_name:
        if not name.endswith("/mla/scores"):
            continue
        of = lambda part: by_name[name[:-len("scores")] + part]
        A, dk = of("scores")["kernel_shape"]
        dv = of("values")["kernel_shape"][1]
        rank = of("kv_b_proj")["kernel_shape"][0]
        dr = of("kv_a_proj")["kernel_shape"][1] - rank
        (T,) = of("q_proj")["out_spatial"]
        total += T * (A * dk + A * (dk - dr) + dr + 2 * A * dv) * act_bytes
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
    """The tape of one sample at the published widths, this chip's 8
    experts and the cell's volume, traced abstractly (nothing runs)."""
    import jax
    import jax.numpy as jnp

    H, P, F, W, A = 2048, 8, 11264, 1408, 16
    f = lambda *s: jax.ShapeDtypeStruct(s, jnp.float32)
    norm = lambda n=H: {"weight": f(n)}
    dense = lambda i, o: {"kernel": f(i, o)}
    mlp = lambda w: {"gate_proj": dense(H, w), "up_proj": dense(H, w),
                     "down_proj": dense(w, H)}
    common = {
        "attn_norm": norm(), "mlp_norm": norm(),
        "mla": {"q_proj": dense(H, A * 192), "kv_a_proj": dense(H, 576),
                "kv_norm": norm(512), "kv_b_proj": dense(512, A * 256),
                "o_proj": dense(A * 128, H)}}
    expert = {**common, "shared": mlp(2 * W),
              "moe": {"router": f(H, 64), "up": f(8, H, 2 * W),
                      "down": f(8, W, H)}}
    params = {"patch_embed": {"kernel": f(P ** 3, H), "bias": f(H)},
              "final_norm": norm(), "head": dense(H, 1),
              "layers_0": {**common, "ffn": mlp(F)}}
    for i in range(1, LAYERS):
        params[f"layers_{i}"] = expert
    tape: list = []
    x = jax.ShapeDtypeStruct((1, 121, 145, 121), jnp.uint8)
    jax.eval_shape(lambda p, v: forward(p, {}, v, tape), params, x)
    return tape
