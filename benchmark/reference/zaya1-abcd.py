"""ZAYA1's layer over 3D patch tokens: forward, loss and work, float32.

Written from the public description of ``ZAYA1-8B`` (Zyphra;
``config.json``; Compressed Convolutional Attention, arXiv:2510.04476,
section 3; the ZAYA1 report, arXiv:2511.17127: the router and the residual
scaling). d = 2048, 8 query heads over 2 key/value heads of 128 (G = 4),
16 experts of width 2048, one a token, router width 256, eps 1e-5;
``a[t-1]`` is zero at t = 0; everything causal:

    a = RMSNorm(h)
    CCA   q~ = a W_q [1024]    k~ = a W_k [256]    u = [q~ ; k~]
          c0[t] = kappa0[0] * u[t-1] + kappa0[1] * u[t] + b0
          c1[t] = c0[t-1] K1[0] + c0[t] K1[1] + b1        10 blocks of 128 x 128
          m_q[t, i] = (q~[t, i] + k~[t, i // G]) / 2;  m_k[t, g] = mean_{i in g} m_q[t, i]
          q = q_c + m_q;  k = k_c + m_k;  v[t] = [a[t] W_v1 ; a[t-1] W_v2]
          q^ = sqrt(128) q / |q|;  k^ = tau_g sqrt(128) k / |k|
          rotary on the first 64 of 128 (theta 5e6);  o = softmax(q^ k^T / sqrt(128) + causal) v
          h = (s1 * h + t1) + (s2 * (o W_o) + t2)
    a = RMSNorm(h)
    MoE   r = a W_dn + b_dn;  r = r + gamma * r_{l-1} (l > 0);  r_l = r
          z = gelu(gelu(RMSNorm(r) W_1 + b_1) W_2 + b_2) W_3      [17]
          p = softmax(z);  e = argmax(stop_gradient(p) + bias);  w = p[e]
          y = w (silu(a Wg_e) * (a Wu_e)) Wd_e  if e is held here, else 0
          h = (s3 * h + t3) + (s4 * y + t4)

The convolutions are written with an explicit shift by one token, the
experts as a loop over the held ids.

Fed as this system feeds a trunk (``assumed`` in the configuration file):
tokens are 16^3 patches of the volume standardised over its own voxels,
zero-padded, through one linear patch embedding; the logit is one
bias-free linear on the mean over positions of the final-norm states.

Departures from the published description, each by need:

- the parameter tree's names and layouts are the system's under test
  (``patch_embed``, ``layers_i/{attn_norm, cca/..., attn_merge, moe_norm,
  moe/{router/..., up, down}, moe_merge}``, ``final_norm``, ``head``;
  ``[in, out]`` kernels, ``[count, in, out]`` expert stacks, gate and up
  side by side in ``up``);
- **the expert share**: ``cfg["held"] = (first, count)`` of the 16 experts
  have weights here (8: one of 2 chips that share each layer by expert
  parallelism); what the others would add is left out, in the program
  alike. ``held = (0, 16)`` with all the weights is the uncut layer
  (tests/test_zaya3d.py adds the two shares up to it);
- the balancing bias is zeros and no auxiliary loss is added (the
  published balancing moves that bias outside the gradient: a training
  recipe ``config.json`` does not give);
- the token embedding and tied head, the 74B sibling's windowed layers and
  generation are not built.

The tape counts useful work only (``zaya_ops.py`` says how each new layer
is recorded): at the published widths 92.70 GFLOP forward, 0.278 TFLOP a
training sample, the held experts at the uniform share 8 / 17.
"""

import importlib.util
import math
import os

_spec = importlib.util.spec_from_file_location(
    "benchmark_reference_zaya_ops",
    os.path.join(os.path.dirname(os.path.abspath(__file__)), "zaya_ops.py"))
ops = importlib.util.module_from_spec(_spec)
_spec.loader.exec_module(ops)

#: what the parameter shapes do not say (config.json; ``patch`` is
#: `assumed`: the configuration file says why)
PUBLISHED = {"num_heads": 8, "num_kv_heads": 2, "head_dim": 128,
             "rotary_dim": 64, "rope_theta": 5e6, "held": (0, 8),
             "rms_eps": 1e-5, "patch": 16}
LAYERS = 5
EXPERT_RECORDS = ("/moe/up", "/moe/down")
#: records under the scopes cca_conv and cca_mix read and write the
#: latents; these name the matrix work among them
CONV_RECORDS = ("/cca/conv0", "/cca/conv1")


def cca(a, p, cfg, q, tape, name):
    """Compressed convolutional attention: ``a [B, T, d] -> [B, T, d]``."""
    import jax.numpy as jnp

    B, T, _ = a.shape
    Hq, Hkv, hd = cfg["num_heads"], cfg["num_kv_heads"], cfg["head_dim"]
    G = Hq // Hkv
    proj = lambda n: ops.linear(a, p[n]["kernel"], q=q, tape=tape,
                                name=f"{name}/{n}")
    q_lat, k_lat = proj("q_proj"), proj("k_proj")
    u = jnp.concatenate([q_lat, k_lat], axis=-1)
    c0 = ops.depthwise_conv2(u, p["conv0_kernel"], p["conv0_bias"], q=q,
                             tape=tape, name=name + "/conv0")
    c1 = ops.grouped_conv2(c0.reshape(B, T, Hq + Hkv, hd),
                           p["conv1_kernel"], p["conv1_bias"], q=q,
                           tape=tape, name=name + "/conv1")
    q_heads = q_lat.reshape(B, T, Hq, hd)
    k_heads = k_lat.reshape(B, T, Hkv, hd)
    m_q = (q_heads + jnp.repeat(k_heads, G, axis=2)) / 2.0
    m_k = jnp.mean(m_q.reshape(B, T, Hkv, G, hd), axis=3)
    scale = math.sqrt(hd)
    q_hat = ops.unit_rows(c1[:, :, :Hq] + m_q, scale)
    k_hat = ops.unit_rows(c1[:, :, Hq:] + m_k, scale) \
        * p["temperature"][:, None]
    v = jnp.stack([proj("v_proj_now"), ops.previous(proj("v_proj_prev"))],
                  axis=2)
    ctx = ops.gq_attention(
        ops.partial_rope(q_hat, cfg["rope_theta"], cfg["rotary_dim"]),
        ops.partial_rope(k_hat, cfg["rope_theta"], cfg["rotary_dim"]), v,
        q=q, tape=tape, name=name + "/attn")
    return ops.linear(ctx, p["o_proj"]["kernel"], q=q, tape=tape,
                      name=name + "/o_proj")


def experts(a, r_prev, p, cfg, q, tape, name, bias=None):
    """``(out [B, T, d], r [B*T, R], choice [B*T])``: the held experts'
    part of the expert sublayer and the router state handed on."""
    B, T, d = a.shape
    m = a.reshape(B * T, d)
    outputs = p["router"]["fc3"]["kernel"].shape[-1]
    r, _, weight, choice = ops.mlp_route(m, r_prev, p["router"],
                                         cfg["rms_eps"], bias, tape=tape,
                                         name=name + "/router")
    y = ops.held_gated_experts(m, weight, choice, p["up"], p["down"],
                               cfg["held"], outputs, q=q, tape=tape,
                               name=name)
    return y.reshape(B, T, d), r, choice


def trunk(params, x_uint8, tape=None, *, cfg=PUBLISHED, q=ops.exact):
    """``(logits [B, classes], choices [L, N])`` with ``N = B x tokens``
    and ``L`` the layers."""
    import jax.numpy as jnp

    eps = cfg["rms_eps"]
    h = ops.linear(ops.patches(x_uint8, cfg["patch"], eps),
                   params["patch_embed"]["kernel"],
                   params["patch_embed"]["bias"], q=q, tape=tape,
                   name="patch_embed")
    r, chosen = None, []
    for i in range(sum(1 for k in params if k.startswith("layers_"))):
        p, name = params[f"layers_{i}"], f"layers_{i}"
        y = cca(ops.rms_norm(h, p["attn_norm"]["weight"], eps), p["cca"],
                cfg, q, tape, name + "/cca")
        h = ops.residual_scale(h, y, p["attn_merge"])
        y, r, e = experts(ops.rms_norm(h, p["moe_norm"]["weight"], eps), r,
                          p["moe"], cfg, q, tape, name + "/moe")
        h = ops.residual_scale(h, y, p["moe_merge"])
        chosen.append(e)
    pooled = jnp.mean(ops.rms_norm(h, params["final_norm"]["weight"], eps),
                      axis=1)
    logits = ops.read_out(pooled, params["head"]["kernel"], q=q, tape=tape,
                          name="head")
    return logits, jnp.stack(chosen)


def forward(params, batch_stats, x_uint8, tape=None, *, cfg=PUBLISHED,
            q=ops.exact):
    """``x_uint8`` ``[B, D, H, W]`` -> logits ``[B, num_classes]``."""
    return trunk(params, x_uint8, tape, cfg=cfg, q=q)[0]


def training_loss(params, batch_stats, x_uint8, y, *, cfg=PUBLISHED,
                  q=ops.exact):
    """Mean BCE of the batch; there is no auxiliary term. ``jax.grad`` of
    it is the reference gradient."""
    import jax.numpy as jnp

    return jnp.mean(ops.bce_with_logits(
        forward(params, batch_stats, x_uint8, cfg=cfg, q=q), y))


# ---------- the new stages' operations and bytes ----------

def layers(tape) -> int:
    return sum(1 for r in tape if r["name"].endswith("/moe/up"))


def expert_flops_per_row(tape) -> float:
    """Forward operations of ONE routed token through a held expert's two
    matrices (gate and up side by side, then down): the roofline reader
    multiplies by the rows that really landed."""
    rows = [r for r in tape if r["name"].endswith(EXPERT_RECORDS)]
    return sum(2.0 * math.prod(r["kernel_shape"]) for r in rows) \
        / layers(tape)


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
    return total / layers(tape)


def cca_mix_flops_per_sample(tape) -> float:
    """Forward operations of the two convolutions for one sample, over
    every layer (the mean, the norm and rotary are not matrix work)."""
    return sum(2.0 * math.prod(r["kernel_shape"])
               * math.prod(r["out_spatial"]) for r in tape
               if r["name"].endswith(CONV_RECORDS))


def cca_mix_bytes_per_step(tape, batch: int, act_bytes: int = 2) -> float:
    """The least a training step moves under ``cca_conv`` + ``cca_mix``
    for ``batch`` samples, over every layer: each stage reads the latents
    it needs and writes its result once, forward, and reads and writes
    them again with their cotangents backward (three passes). A token's
    latents are ``C = (Hq + Hkv) x head_dim`` channels (1280):

        depthwise conv   reads u [C], writes c0 [C]
        grouped conv     reads c0 [C], writes c1 [C] (and 0.66 MB of kernel)
        mean + sum       reads u, c1 [2 C], writes q, k [C]
        value shift      reads and writes one value head [2 x head_dim]
        norm + rotary    reads q, k [C], writes q^, k^ [C]

    9 C + 2 head_dim elements a token and pass."""
    total = 0.0
    for r in tape:
        if r["name"].endswith("/cca/conv1"):
            _, heads, hd, _ = r["kernel_shape"]
            (T,) = r["out_spatial"]
            total += 3.0 * batch * T * (9 * heads * hd + 2 * hd) * act_bytes
    return total


def published_tape():
    """The tape of one sample at the published widths and the cell's
    volume, traced abstractly (nothing runs)."""
    import jax
    import jax.numpy as jnp

    d, P, R, W, heads, hd = 2048, 16, 256, 2048, 10, 128
    f = lambda *s: jax.ShapeDtypeStruct(s, jnp.float32)
    norm = lambda n=d: {"weight": f(n)}
    dense = lambda i, o: {"kernel": f(i, o)}
    biased = lambda i, o: {"kernel": f(i, o), "bias": f(o)}
    merge = {k: f(d) for k in ("stream_gain", "stream_offset", "out_gain",
                               "out_offset")}
    layer = {
        "attn_norm": norm(), "moe_norm": norm(), "attn_merge": merge,
        "moe_merge": merge,
        "cca": {"q_proj": dense(d, 1024), "k_proj": dense(d, 256),
                "v_proj_now": dense(d, hd), "v_proj_prev": dense(d, hd),
                "conv0_kernel": f(2, heads * hd), "conv0_bias": f(heads * hd),
                "conv1_kernel": f(2, heads, hd, hd),
                "conv1_bias": f(heads, hd), "temperature": f(2),
                "o_proj": dense(1024, d)},
        "moe": {"router": {"down": biased(d, R), "depth_gain": f(R),
                           "norm": norm(R), "fc1": biased(R, R),
                           "fc2": biased(R, R), "fc3": dense(R, 17)},
                "up": f(8, d, 2 * W), "down": f(8, W, d)}}
    params = {"patch_embed": biased(P ** 3, d), "final_norm": norm(),
              "head": dense(d, 1)}
    for i in range(LAYERS):
        params[f"layers_{i}"] = layer
    tape: list = []
    x = jax.ShapeDtypeStruct((1, 121, 145, 121), jnp.uint8)
    jax.eval_shape(lambda p, v: forward(p, {}, v, tape), params, x)
    return tape
