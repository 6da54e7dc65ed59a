"""Nemotron-H's hybrid trunk over 3D patch tokens: forward, loss and work,
float32.

Written from the public description of ``Nemotron-Labs-TwoTower-30B-A3B-
Base-BF16``'s ``nemotron_h`` tower (nvidia; ``config.json`` and the
modelling code's layer equations; d = 2688, eps 1e-5, no bias but the
conv's). One pre-norm mixer a layer, ``h = h + mixer(RMSNorm(h))``:

    M   z, xBC, dt = split(u W_in)                      (4096, 6144, 64)
        xBC = silu(causal depthwise conv1d(xBC, 4) + b)
        x, B, C = split(xBC)                            (64 x 64, 8 x 128, 8 x 128)
        dt = softplus(dt + dt_bias);  A = -exp(A_log)
        S_t = exp(dt_t A) S_{t-1} + dt_t x_t B_t^T;  y_t = S_t C_t + D x_t
        out = (weight * RMSNorm_groups-of-512(y * silu(z))) W_out
    E   s = sigmoid_f32(m W_r); top 6 of s + b (b zeros); w = s_sel / (sum + 1e-20) x 2.5
        out = shared(m) + sum_{e selected and held} w_e relu(m W_up,e)^2 W_down,e
        shared(m) = relu(m W_su)^2 W_sd                  (width 3712)
    *   32 query heads over 2 key/value heads of 128, causal, / sqrt(128), no positions

The state-space layer is the TOKEN-BY-TOKEN recurrence (``lax.scan`` over
the tokens; the program runs the chunked form, which is what is under
test), the experts a loop over the held ids.

Fed as this system feeds a trunk (``assumed`` in the configuration file):
tokens are 16^3 patches of the volume standardised over its own voxels,
zero-padded, through one linear patch embedding; the logit is one
bias-free linear on the mean over positions of the final-norm states.

Departures from the published description, each by need:

- the parameter tree's names and layouts are the system's under test
  (``patch_embed``, ``layers_i/{norm, mixer/..., shared/...}``,
  ``final_norm``, ``head``; ``[in, out]`` kernels, ``[count, in, out]``
  expert stacks, the conv kernel ``[4, channels]``); a layer's kind is read
  from the names its mixer holds;
- **the expert share**: ``cfg["held"] = (first, count)`` of the router's
  ``E`` experts have weights here (8 of 128: one of 16 chips that share
  each layer by expert parallelism); what the others would add is left out,
  in the program alike. ``held = (0, E)`` with all the weights is the
  uncut layer (tests/test_nemotronh3d.py adds the 16 shares up to it);
- ``e_score_correction_bias`` is zeros and no auxiliary loss is added (the
  published balancing moves that bias outside the gradient: a training
  recipe ``config.json`` does not give);
- no rotary embedding in ``*`` (the modelling code applies none);
- the denoiser tower and block diffusion of the TwoTower release are not
  built: ``config.json`` describes one tower, and this system generates
  nothing.

The tape counts useful work only (``nemotronh_ops.py`` says how each new
layer is recorded): at the published widths 374 GFLOP forward, 1.12 TFLOP
a training sample, the held experts at the uniform share of the routing.
"""

import importlib.util
import math
import os

_spec = importlib.util.spec_from_file_location(
    "benchmark_reference_nemotronh_ops",
    os.path.join(os.path.dirname(os.path.abspath(__file__)),
                 "nemotronh_ops.py"))
ops = importlib.util.module_from_spec(_spec)
_spec.loader.exec_module(ops)

#: what the parameter shapes do not say (config.json; ``patch`` is
#: `assumed`: the configuration file says why)
PUBLISHED = {
    "mamba_num_heads": 64, "mamba_head_dim": 64, "n_groups": 8,
    "ssm_state_size": 128, "num_heads": 32, "num_kv_heads": 2,
    "head_dim": 128, "experts_per_token": 6, "held": (0, 8),
    "routed_scaling_factor": 2.5, "rms_eps": 1e-5, "patch": 16}
PATTERN = "MEMEM*EME"  # hybrid_override_pattern[:9]
SSD_CHUNK, SSD_GROUPS = 128, 8  # chunk_size, n_groups (config.json)
SSD_RECORDS = ("/ssd/update", "/ssd/read")
EXPERT_RECORDS = ("/mixer/up", "/mixer/down")


def mamba(a, p, cfg, q, tape, name):
    H, P = cfg["mamba_num_heads"], cfg["mamba_head_dim"]
    G, N = cfg["n_groups"], cfg["ssm_state_size"]
    B, T, _ = a.shape
    inner, bc = H * P, G * N
    proj = ops.linear(a, p["in_proj"]["kernel"], q=q, tape=tape,
                      name=name + "/in_proj")
    z, xBC, dt = (proj[..., :inner], proj[..., inner:2 * inner + 2 * bc],
                  proj[..., 2 * inner + 2 * bc:])
    xBC = ops.silu(ops.causal_conv1d(xBC, p["conv_kernel"], p["conv_bias"],
                                     tape=tape, name=name + "/conv"))
    x, Bm, Cm = (xBC[..., :inner], xBC[..., inner:inner + bc],
                 xBC[..., inner + bc:])
    import jax.numpy as jnp

    y = ops.selective_scan(
        x.reshape(B, T, H, P), ops.softplus(dt + p["dt_bias"]),
        -jnp.exp(p["A_log"]), Bm.reshape(B, T, G, N), Cm.reshape(B, T, G, N),
        p["D"], q=q, tape=tape, name=name + "/ssd")
    y = ops.gated_group_norm(y.reshape(B, T, inner), z, p["gate_norm"], G,
                             cfg["rms_eps"])
    return ops.linear(y, p["out_proj"]["kernel"], q=q, tape=tape,
                      name=name + "/out_proj")


def experts(a, p, shared, cfg, q, tape, name):
    """``(out [B, T, d], chosen [B*T, k])``."""
    B, T, d = a.shape
    m = a.reshape(B * T, d)
    E = p["router"].shape[-1]
    _, w, e = ops.sigmoid_route(m, p["router"], cfg["experts_per_token"],
                                cfg["routed_scaling_factor"], tape=tape,
                                name=name + "/router")
    y = ops.held_experts(m, w, e, p["up"], p["down"], cfg["held"], E, q=q,
                         tape=tape, name=name + "/mixer")
    y = y.reshape(B, T, d) + ops.relu2_mlp(
        a, shared["up"]["kernel"], shared["down"]["kernel"], q=q, tape=tape,
        name=name + "/shared")
    return y, e


def attention(a, p, cfg, q, tape, name):
    B, T, _ = a.shape
    hd = cfg["head_dim"]
    proj = lambda n: ops.linear(a, p[n]["kernel"], q=q, tape=tape,
                                name=f"{name}/{n}")
    ctx = ops.gq_attention(
        proj("q_proj").reshape(B, T, cfg["num_heads"], hd),
        proj("k_proj").reshape(B, T, cfg["num_kv_heads"], hd),
        proj("v_proj").reshape(B, T, cfg["num_kv_heads"], hd),
        q=q, tape=tape, name=name + "/attn")
    return ops.linear(ctx, p["o_proj"]["kernel"], q=q, tape=tape,
                      name=name + "/o_proj")


def trunk(params, x_uint8, tape=None, *, cfg=PUBLISHED, q=ops.exact):
    """``(logits [B, classes], experts [L_E * N, k])`` with ``N = B x
    tokens`` and ``L_E`` the expert layers."""
    import jax.numpy as jnp

    eps = cfg["rms_eps"]
    h = ops.linear(ops.patches(x_uint8, cfg["patch"], eps),
                   params["patch_embed"]["kernel"],
                   params["patch_embed"]["bias"], q=q, tape=tape,
                   name="patch_embed")
    chosen = []
    for i in range(sum(1 for k in params if k.startswith("layers_"))):
        p, name = params[f"layers_{i}"], f"layers_{i}"
        a = ops.rms_norm(h, p["norm"]["weight"], eps)
        mixer = p["mixer"]
        if "in_proj" in mixer:
            y = mamba(a, mixer, cfg, q, tape, name)
        elif "router" in mixer:
            y, e = experts(a, mixer, p["shared"], cfg, q, tape, name)
            chosen.append(e)
        else:
            y = attention(a, mixer, cfg, q, tape, name)
        h = h + y
    pooled = jnp.mean(ops.rms_norm(h, params["final_norm"]["weight"], eps),
                      axis=1)
    logits = ops.read_out(pooled, params["head"]["kernel"], q=q, tape=tape,
                          name="head")
    return logits, jnp.concatenate(chosen)


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


# ---------- the new kernels' operations and bytes ----------

def ssd_flops_per_sample(tape) -> float:
    """Forward operations of the CHUNKED scan (what the program's kernel
    has to do; the tape's own ``ssd`` records are the recurrence's 2.1
    MFLOP a token) for one sample, over every ``M`` layer, from the tape's
    shapes: per chunk of ``Q`` tokens the causal half of ``C B^T`` (``G x
    N`` deep) and of its product with ``x`` (``H x P`` wide), and per token
    the chunk state's update and read (``H x P x N`` each)."""
    total = 0.0
    for r in tape:
        if not r["name"].endswith("/ssd/update"):
            continue
        H, P, N = r["kernel_shape"]
        (T,) = r["out_spatial"]
        Q = min(SSD_CHUNK, T)
        pairs = (T // Q) * Q * (Q + 1) // 2
        total += 2.0 * pairs * (SSD_GROUPS * N + H * P) \
            + 2.0 * 2.0 * T * H * P * N
    return total


def ssd_bytes_per_step(tape, batch: int, act_bytes: int = 2) -> float:
    """The least a training step's scan moves for ``batch`` samples, over
    every ``M`` layer: forward reads ``x``, ``B``, ``C`` (compute dtype)
    and ``dt`` (float32) and writes ``y``; backward reads those and ``dy``
    and writes the four gradients: three passes over ``x``-sized and
    ``B/C``-sized operands."""
    total = 0.0
    for r in tape:
        if not r["name"].endswith("/ssd/update"):
            continue
        H, P, N = r["kernel_shape"]
        (T,) = r["out_spatial"]
        one = T * (2 * H * P * act_bytes + 2 * SSD_GROUPS * N * act_bytes
                   + H * 4)
        total += 3.0 * one * batch
    return total


def expert_flops_per_row(tape) -> float:
    """Forward operations of ONE (token, slot) row through a held expert's
    two matrices, over the tape's expert records divided by their rows:
    the roofline reader multiplies by the rows that really landed."""
    rows = [r for r in tape if r["name"].endswith(EXPERT_RECORDS)]
    layers = len(rows) / 2
    return sum(2.0 * math.prod(r["kernel_shape"]) for r in rows) / layers


def expert_layers(tape) -> int:
    return sum(1 for r in tape if r["name"].endswith("/mixer/up"))


def expert_bytes_per_step(tape, rows: float, weight_bytes: int = 2,
                          act_bytes: int = 2) -> float:
    """The least a training step's three passes move for the grouped
    matmuls of ONE expert layer whose held experts took ``rows`` rows:
    the held experts' weights once a pass, the rows in and out."""
    total = 0.0
    for r in tape:
        if r["name"].endswith(EXPERT_RECORDS):
            n_in, n_out = r["kernel_shape"]
            total += 3.0 * (r["num_experts"] * n_in * n_out * weight_bytes
                            + rows * (n_in + n_out) * act_bytes)
    return total / expert_layers(tape)


def published_tape():
    """The tape of one sample at the published widths and the cell's
    volume, traced abstractly (nothing runs)."""
    import jax
    import jax.numpy as jnp

    d, P = 2688, 16
    f = lambda *s: jax.ShapeDtypeStruct(s, jnp.float32)
    norm = {"weight": f(d)}
    dense = lambda i, o: {"kernel": f(i, o)}
    mixers = {
        "M": {"mixer": {"in_proj": dense(d, 10304), "conv_kernel": f(4, 6144),
                        "conv_bias": f(6144), "dt_bias": f(64),
                        "A_log": f(64), "D": f(64), "gate_norm": f(4096),
                        "out_proj": dense(4096, d)}},
        "E": {"mixer": {"router": f(d, 128), "up": f(8, d, 1856),
                        "down": f(8, 1856, d)},
              "shared": {"up": dense(d, 3712), "down": dense(3712, d)}},
        "*": {"mixer": {"q_proj": dense(d, 4096), "k_proj": dense(d, 256),
                        "v_proj": dense(d, 256), "o_proj": dense(4096, d)}}}
    params = {"patch_embed": {"kernel": f(P ** 3, d), "bias": f(d)},
              "final_norm": norm, "head": dense(d, 1)}
    for i, kind in enumerate(PATTERN):
        params[f"layers_{i}"] = {"norm": norm, **mixers[kind]}
    tape: list = []
    x = jax.ShapeDtypeStruct((1, 121, 145, 121), jnp.uint8)
    jax.eval_shape(lambda p, v: forward(p, {}, v, tape), params, x)
    return tape
