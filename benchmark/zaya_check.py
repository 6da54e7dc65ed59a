"""On-chip comparison of ``--model zaya3d`` with its reference (PR 31).

The builder's check at the published widths, outside any timed window,
``nemotronh_check.py``'s twin; PERF.md section 6 quotes what it prints. Not
a metric reader and not run by ``benchmark.run``:

    chiprun -- python3 -m benchmark.zaya_check [--seed N] [phases...]

Phases (default: all but ``control`` and ``faults``), each on the cell's
own engine, cohort and initial weights (``benchmark/harness.py``):

- ``logits``: 12 seeded volumes, program (``bf16_mixed``) against the
  float32 reference (computed in blocks of 4 rows): per-row absolute and
  relative difference, the share of the tokens' routing choices that agree
  over the five layers, the rows that landed on the held experts and on
  the skip output, and the same for the reference with bfloat16 and float8
  operands.
- ``grads``: one batch of 16: task loss, and the relative L2 distance from
  ``jax.grad`` of the reference's loss (in blocks of 4 rows, averaged) of
  the gradients of the first two layers' leaves OUTSIDE the expert
  sublayer (the attention's four projections, both convolution kernels,
  the temperature, W_o, a residual gain), of its ROUTER (W_dn, the second
  layer's depth gain, W_3) and INSIDE it (the busiest held expert's two
  matrices), ``W_pe`` and ``W_head``.
- ``forward``: ``harness.forward_check`` itself, three times a lower
  precision: the program against the float32 reference (the cell's own
  check); the program against the reference with every matmul operand
  rounded (bfloat16, float8 e4m3); and that rounded reference IN THE
  PROGRAM'S PLACE against the float32 one (a stand-in engine whose
  ``eval_global`` answers with the loss the harness itself just computed
  from the rounded reference). The last is the control of
  ``forward_check.rel_tol``: the float8 one has to come out ``"ok":
  false``.
- ``control``: the ``forward`` phase's float8 readings over ``--seeds``, a
  fresh cohort, initial weights and engine a seed.
- ``faults``: the cell's job with a planted fault, through
  ``harness.learning_check`` under the configuration's bands: ``lr0``
  (``--lr 0``, an optimizer that updates nothing), ``lrtenth`` (``--lr
  0.001``, a tenth of the step), ``momentum05`` (``--momentum 0.5``: a
  fifth of the step the configuration's 0.9 builds up); ``none`` is the
  job as it is. Every round's training loss is printed, and the norm of
  the parameters' change over the job relative to the initial parameters'
  norm. ``--faults`` picks among them.

Everything goes to standard output as ``[zaya_check] key json`` lines and
to ``chiprun_out/zaya_check.json``.
"""

from __future__ import annotations

import argparse
import gc
import json
import os
import types

import numpy as np

CELL = "zaya.fedavg_fold3"
OUT = os.path.join("chiprun_out", "zaya_check.json")
PHASES = ("logits", "grads", "forward")
BLOCK = 4  # rows a reference call
FAULTS = ("none", "lr0", "lrtenth", "momentum05")


def main(argv=None) -> None:
    import jax
    import jax.numpy as jnp

    from benchmark import cohort, harness

    ap = argparse.ArgumentParser()
    ap.add_argument("phases", nargs="*", default=list(PHASES))
    ap.add_argument("--seed", type=int, default=2147483659)
    ap.add_argument("--seeds", type=int, nargs="*", default=[],
                    help="the control phase's seeds")
    ap.add_argument("--faults", nargs="*", default=list(FAULTS),
                    choices=FAULTS)
    args = ap.parse_args(argv)
    from neuroimagedisttraining_tpu.utils.compile_cache import (
        enable_compile_cache,
    )

    enable_compile_cache()
    os.makedirs(os.path.dirname(OUT), exist_ok=True)
    res: dict = {}

    def note(key, value):
        res[key] = value
        print("[zaya_check]", key, json.dumps(value, default=float),
              flush=True)
        with open(OUT, "w") as f:
            json.dump(res, f, indent=1, default=float)

    _, _, config, traffic = harness.load_cell(CELL)
    sizes = harness.site_sizes_of(config, traffic)

    def build(rounds, seed=args.seed, **flags):
        path, _ = cohort.ensure_cohort(
            harness.CACHE_DIR, traffic["name"], sizes,
            tuple(config["input_shape"]), seed)
        a = harness.cell_argv(config, traffic, path, len(sizes), seed,
                              rounds, os.path.join("chiprun_out", "log"))
        for k, v in flags.items():
            a[a.index("--" + k) + 1] = str(v)
        return harness.build_engine(a)

    engine = build(2)
    tr, d = engine.trainer, engine.data
    ref = harness.load_reference(config)
    gs = engine.init_global_state()
    first, count = tr.model.held_experts
    note("device", {"kind": jax.devices()[0].device_kind, "seed": args.seed,
                    "placement": engine.program.placement,
                    "held": [first, count]})

    def ref_trunk(q, X):
        @jax.jit
        def f(params, x):
            with jax.default_matmul_precision("highest"):
                return ref.trunk(params, x, q=q)
        outs = [f(gs.params, X[i:i + BLOCK])
                for i in range(0, len(X), BLOCK)]
        # choices are [layers, block's tokens]
        return (np.concatenate([np.asarray(o[0], np.float64) for o in outs]),
                np.concatenate([np.asarray(o[1]) for o in outs], axis=1))

    skip = tr.model.skip_output
    lower = (("bf16", jnp.bfloat16), ("fp8_e4m3", jnp.float8_e4m3fn))
    same = lambda a, b: float(np.mean(a == b))
    held = lambda e: int(((e >= first) & (e < first + count)).sum())

    if "logits" in args.phases:
        from neuroimagedisttraining_tpu.models.zaya3d import HeldGatedExperts

        X12 = jnp.concatenate([d.X_test[c, :4] for c in range(len(sizes))])

        @jax.jit
        def program(params, x):
            out, inter = tr.model.apply(
                {"params": params}, tr._prep(x), train=False,
                capture_intermediates=lambda m, _: isinstance(
                    m, HeldGatedExperts))
            leaves = jax.tree.leaves(
                inter["intermediates"],
                is_leaf=lambda t: isinstance(t, tuple))
            return out[0], jnp.stack([leaf[0][2][:, 0] for leaf in leaves])

        got, chosen = program(gs.params, X12)
        got, chosen = np.asarray(got, np.float64).ravel(), np.asarray(chosen)
        want, want_chosen = ref_trunk(ref.ops.exact, X12)
        want = want.ravel()
        note("logits", {
            "program": got.tolist(), "reference": want.tolist(),
            "abs_diff_max": float(np.abs(got - want).max()),
            "rel_diff_max": float((np.abs(got - want)
                                   / np.maximum(np.abs(want), 1e-12)).max()),
            "routing_agreement": same(chosen, want_chosen),
            "rows_held_program": held(chosen),
            "rows_held_reference": held(want_chosen),
            "rows_skipped_program": int((chosen == skip).sum()),
            "rows_skipped_reference": int((want_chosen == skip).sum()),
            "assignments": int(want_chosen.size)})
        for name, dt in lower:
            ql, qe = ref_trunk(ref.ops.rounded(dt), X12)
            note(f"logits_reference_{name}", {
                "abs_diff_max": float(np.abs(ql.ravel() - want).max()),
                "routing_agreement": same(qe, want_chosen)})

    if "grads" in args.phases:
        xb, yb = d.X_train[0, :16], d.y_train[0, :16]
        loss, grads, _, _ = jax.jit(tr.loss_and_grad)(gs, xb, yb)
        names = ("patch_embed", "layers_0", "layers_1", "head")

        @jax.jit
        def ref_grad(part, params, x, y):
            with jax.default_matmul_precision("highest"):
                return jax.value_and_grad(
                    lambda p: ref.training_loss({**params, **p}, {}, x, y))(
                        part)

        part = {n: gs.params[n] for n in names}
        task, g_ref = 0.0, None
        for i in range(0, 16, BLOCK):
            t, g = ref_grad(part, gs.params, xb[i:i + BLOCK],
                            yb[i:i + BLOCK])
            task += float(t) * BLOCK / 16
            g = jax.tree.map(lambda a: a * (BLOCK / 16), g)
            g_ref = g if g_ref is None else jax.tree.map(jnp.add, g_ref, g)
        _, chosen = ref_trunk(ref.ops.exact, xb[:BLOCK])
        e0 = int(np.bincount(chosen[1].ravel(), minlength=skip + 1)
                 [first:first + count].argmax())

        def rel(path, index=None):
            a, b = grads, g_ref
            for k in path:
                a, b = a[k], b[k]
            if index is not None:
                a, b = a[index], b[index]
            a, b = np.asarray(a, np.float64), np.asarray(b, np.float64)
            return float(np.linalg.norm(a - b) / np.linalg.norm(b))

        A0, A1 = ("layers_0", "cca"), ("layers_1", "cca")
        R0, R1 = (("layers_0", "moe", "router"),
                  ("layers_1", "moe", "router"))
        note("grads", {
            "task_loss_program": float(loss), "task_loss_reference": task,
            "busiest_held_expert": first + e0, "rel_l2": {
                "W_pe": rel(("patch_embed", "kernel")),
                "outside": {
                    "q_proj": rel(A0 + ("q_proj", "kernel")),
                    "k_proj": rel(A0 + ("k_proj", "kernel")),
                    "v_proj_now": rel(A0 + ("v_proj_now", "kernel")),
                    "v_proj_prev": rel(A0 + ("v_proj_prev", "kernel")),
                    "conv0_kernel": rel(A0 + ("conv0_kernel",)),
                    "conv1_kernel": rel(A0 + ("conv1_kernel",)),
                    "temperature": rel(A0 + ("temperature",)),
                    "o_proj": rel(A0 + ("o_proj", "kernel")),
                    "q_proj_1": rel(A1 + ("q_proj", "kernel")),
                    "o_proj_1": rel(A1 + ("o_proj", "kernel")),
                    "attn_out_gain": rel(("layers_0", "attn_merge",
                                          "out_gain")),
                    "moe_out_gain_1": rel(("layers_1", "moe_merge",
                                           "out_gain"))},
                "router": {
                    "down": rel(R0 + ("down", "kernel")),
                    "fc3": rel(R0 + ("fc3", "kernel")),
                    "down_1": rel(R1 + ("down", "kernel")),
                    "depth_gain_1": rel(R1 + ("depth_gain",)),
                    "fc3_1": rel(R1 + ("fc3", "kernel"))},
                "inside": {
                    "up_1": rel(("layers_1", "moe", "up"), e0),
                    "down_1": rel(("layers_1", "moe", "down"), e0)},
                "W_head": rel(("head", "kernel"))}})
        del grads, g_ref

    def forward_readings(engine, precisions):
        """``harness.forward_check``'s verdicts: the program against the
        float32 reference, and for each lower precision the program against
        the rounded reference and the rounded reference in the program's
        place against the float32 one."""
        out = {"program": harness.forward_check(engine, ref, config)}
        for name, dt in precisions:
            low = types.SimpleNamespace(
                forward=lambda p, st, x, q=ref.ops.rounded(dt):
                    ref.forward(p, st, x, q=q))
            against = harness.forward_check(engine, low, config)
            stand_in = types.SimpleNamespace(
                stream=None, data=engine.data,
                init_global_state=engine.init_global_state,
                eval_global=lambda p, st, loss=against["reference_loss"]:
                    {"loss": loss})
            out[name] = {
                "program_against_it": against,
                "in_the_programs_place": harness.forward_check(
                    stand_in, ref, config)}
        return out

    if "forward" in args.phases:
        note("forward", forward_readings(engine, lower))

    if "control" in args.phases:
        for seed in args.seeds:
            eng = engine if seed == args.seed else build(2, seed=seed)
            note(f"control_{seed}", forward_readings(eng, lower[1:]))
            del eng
            gc.collect()

    if "faults" in args.phases:
        start = jax.tree.map(np.asarray, gs.params)
        del engine, tr, d, gs
        gc.collect()
        bands = harness.correct_bands(config, traffic)
        rounds = int(bands["loss_round"]) + 1
        norm = lambda t: float(np.sqrt(sum(
            float(np.sum(np.square(np.asarray(a, np.float64))))
            for a in jax.tree.leaves(t))))

        planted = {"none": {}, "lr0": {"lr": 0}, "lrtenth": {"lr": 0.001},
                   "momentum05": {"momentum": 0.5}}
        for label in args.faults:
            eng = build(rounds, **planted[label])
            log = harness.RoundLog(eng)
            out = eng.train()
            rows = [r for r in log.take() if r["round"] >= 0]
            moved = jax.tree.map(lambda a, b: np.asarray(a) - b,
                                 out["params"], start)
            note("faults_" + label, {
                "learning": harness.learning_check(
                    rows, out["final_global"], bands),
                "train_loss": [float(r["train_loss"]) for r in rows],
                "auc": [float(r["auc"]) for r in rows],
                "param_change_rel_norm": norm(moved) / norm(start)})
            del eng, log, out, moved
            gc.collect()

if __name__ == "__main__":
    main()
