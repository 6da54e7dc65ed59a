"""OLMoE's block over 3D patch tokens: forward, loss and work, float32.

Written from the public description of ``OLMoE-1B-7B-0125-Instruct``
(allenai; ``config.json`` and the modelling code's layer equations):

    per layer:  a = RMSNorm(h);  q, k, v = a Wq, a Wk, a Wv     (no bias)
                q, k = RMSNorm_hidden(q), RMSNorm_hidden(k)      (whole projection, before the head split)
                q, k = RoPE(q, k)                                (theta 10000, rotate-half, positions 0..T-1)
                h = h + softmax(causal(q k^T / sqrt(d))) v Wo
                m = RMSNorm(h);  p = softmax_f32(m Wr);  (w_j, e_j) = top_k(p)     (w not renormalised)
                h = h + sum_j w_j down_ej(silu(gate_ej(m)) * up_ej(m))
    aux = coef x E x sum_e f_e P_e          (load_balancing_loss_func: all layers' rows together)

and fed as this system feeds it (models/olmoe3d.py; each is `assumed` in
the configuration file): tokens are 16^3 patches of the volume standardised
over its own voxels, zero-padded, through one linear patch embedding; the
logit is one bias-free linear on the mean over positions of the final-norm
hidden states. Departures forced by
taking the weights of the system under test: the parameter tree's names
(``patch_embed``, ``layers_i/{attn_norm, attn/{q,k,v,o}_proj, attn/{q,k}_norm,
mlp_norm, moe/{router, gate, up, down}}``, ``final_norm``, ``head``) and the
``[E, in, out]`` layout of the expert stacks.

Every expert is computed for every token and masked by the top-k weights
(``olmoe_ops.dense_experts``): the program's sort and grouped matmul are
what is under test. The tape counts useful work only: the k ACTIVE experts
a token, causal attention as T(T+1)/2 pairs. At the published widths that
is 98.5 GFLOP forward, 295 GFLOP a training sample.
"""

import importlib.util
import os

_spec = importlib.util.spec_from_file_location(
    "benchmark_reference_olmoe_ops",
    os.path.join(os.path.dirname(os.path.abspath(__file__)), "olmoe_ops.py"))
ops = importlib.util.module_from_spec(_spec)
_spec.loader.exec_module(ops)

#: what the parameter shapes do not say (config.json; `assumed` for patch
#: and aux_coef: the configuration file says why)
PUBLISHED = {"num_heads": 16, "experts_per_token": 8, "rms_eps": 1e-5,
             "rope_theta": 10000.0, "patch": 16, "aux_coef": 0.01}
EXPERT_RECORDS = ("/moe/gate", "/moe/up", "/moe/down")


def trunk(params, x_uint8, tape=None, *, cfg=PUBLISHED, q=ops.exact):
    """``(logits [B, classes], probs [L*N, E], experts [L*N, k])`` with
    ``N = B x tokens``."""
    eps, heads = cfg["rms_eps"], cfg["num_heads"]
    h = ops.linear(ops.patches(x_uint8, cfg["patch"], eps),
                   params["patch_embed"]["kernel"],
                   params["patch_embed"]["bias"], q=q, tape=tape,
                   name="patch_embed")
    B, T, H = h.shape
    probs, experts = [], []
    for i in range(sum(1 for k in params if k.startswith("layers_"))):
        p, name = params[f"layers_{i}"], f"layers_{i}"
        a = ops.rms_norm(h, p["attn_norm"]["weight"], eps)
        at = p["attn"]
        proj = lambda n: ops.linear(a, at[n]["kernel"], q=q, tape=tape,
                                    name=f"{name}/attn/{n}")
        split = lambda t: t.reshape(B, T, heads, H // heads)
        qh = ops.rms_norm(proj("q_proj"), at["q_norm"]["weight"], eps)
        kh = ops.rms_norm(proj("k_proj"), at["k_norm"]["weight"], eps)
        ctx = ops.causal_attention(
            ops.rope(split(qh), cfg["rope_theta"]),
            ops.rope(split(kh), cfg["rope_theta"]), split(proj("v_proj")),
            q=q, tape=tape, name=f"{name}/attn")
        h = h + ops.linear(ctx, at["o_proj"]["kernel"], q=q, tape=tape,
                           name=f"{name}/attn/o_proj")
        moe = p["moe"]
        m = ops.rms_norm(h, p["mlp_norm"]["weight"], eps).reshape(B * T, H)
        pr, w, e = ops.route(m, moe["router"], cfg["experts_per_token"],
                             tape=tape, name=f"{name}/moe/router")
        y = ops.dense_experts(m, w, e, moe["gate"], moe["up"], moe["down"],
                              q=q, tape=tape, name=f"{name}/moe")
        h = h + y.reshape(B, T, H)
        probs.append(pr)
        experts.append(e)
    import jax.numpy as jnp

    pooled = jnp.mean(ops.rms_norm(h, params["final_norm"]["weight"], eps),
                      axis=1)
    logits = ops.read_out(pooled, params["head"]["kernel"], q=q, tape=tape,
                          name="head")

    return logits, jnp.concatenate(probs), jnp.concatenate(experts)


def forward(params, batch_stats, x_uint8, tape=None, *, cfg=PUBLISHED,
            q=ops.exact):
    """``x_uint8`` ``[B, D, H, W]`` -> logits ``[B, num_classes]``."""
    return trunk(params, x_uint8, tape, cfg=cfg, q=q)[0]


def training_loss(params, batch_stats, x_uint8, y, *, cfg=PUBLISHED,
                  q=ops.exact):
    """``(task + aux, (task, aux))``: mean BCE of the batch plus the
    weighted load-balancing term; ``jax.grad`` of the first is the
    reference gradient."""
    import jax.numpy as jnp

    logits, probs, experts = trunk(params, x_uint8, cfg=cfg, q=q)
    task = jnp.mean(ops.bce_with_logits(logits, y))
    E = params["layers_0"]["moe"]["router"].shape[-1]
    aux = cfg["aux_coef"] * ops.load_balancing(probs, experts, E)
    return task + aux, (task, aux)


# ---------- the grouped matmul's operations and bytes ----------

def expert_flops_per_sample(tape) -> float:
    """Forward operations of the routed experts for one sample, from the
    tape's expert records (k active experts a token, three matrices)."""
    import math

    return sum(2.0 * math.prod(r["kernel_shape"])
               * math.prod(r["out_spatial"]) for r in tape
               if r["name"].endswith(EXPERT_RECORDS))


def expert_bytes_per_step(tape, batch: int, weight_bytes: int = 2,
                          act_bytes: int = 2) -> float:
    """The least a training step's three passes (forward, dx, dW) move
    for the grouped matmuls of ``batch`` samples: every expert's
    weights once a pass (all E: at 1,280 tokens an expert every expert
    is hit), and the slot activations in and out."""
    total = 0.0
    for r in tape:
        if not r["name"].endswith(EXPERT_RECORDS):
            continue
        k, n_in, n_out = r["kernel_shape"]
        slots = k * r["out_spatial"][0] * batch
        total += 3.0 * (r["num_experts"] * n_in * n_out * weight_bytes
                        + slots * (n_in + n_out) * act_bytes)
    return total


def published_tape():
    """The tape of one sample at the published widths and the cell's
    volume, traced abstractly (nothing runs): what the roofline reader
    takes the grouped matmuls' operations from."""
    import jax
    import jax.numpy as jnp

    H, E, W, P = 2048, 64, 1024, 16
    f = lambda *s: jax.ShapeDtypeStruct(s, jnp.float32)
    norm = {"weight": f(H)}
    params = {
        "patch_embed": {"kernel": f(P ** 3, H), "bias": f(H)},
        "layers_0": {
            "attn_norm": norm, "mlp_norm": norm,
            "attn": {**{n: {"kernel": f(H, H)} for n in
                        ("q_proj", "k_proj", "v_proj", "o_proj")},
                     "q_norm": norm, "k_norm": norm},
            "moe": {"router": f(H, E), "gate": f(E, H, W),
                    "up": f(E, H, W), "down": f(E, W, H)}},
        "final_norm": norm, "head": {"kernel": f(H, 1)}}
    tape: list = []
    x = jax.ShapeDtypeStruct((1, 121, 145, 121), jnp.uint8)
    jax.eval_shape(lambda p, v: forward(p, {}, v, tape), params, x)
    return tape
