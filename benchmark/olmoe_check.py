"""On-chip comparison of ``--model olmoe3d`` with its reference (PR 25).

The builder's check at the published widths, outside any timed window;
PERF.md section 6 quotes what it prints. Not a metric reader and not run
by ``benchmark.run``:

    chiprun -- python3 -m benchmark.olmoe_check [--seed N] [phases...]

Phases (default: all but ``faults``), each on the cell's own engine,
cohort and initial weights (``benchmark/harness.py``):

- ``logits``: 16 seeded volumes, program (``bf16_mixed``) against the
  float32 reference: per-row absolute and relative difference, the share
  of (token, slot) routing choices that agree, and the same for the
  reference with bfloat16 and float8 operands.
- ``grads``: one batch of 16: task loss, and the relative L2 distance of
  the gradients of ``W_pe``, ``Wq``, ``Wr``, the busiest expert's three
  matrices and ``W_head`` from ``jax.grad`` of the reference's loss.
- ``forward``: the harness's own ``forward`` check on the whole test
  split, and what a bfloat16- and a float8-operand reference read there
  (the second has to lie outside the configuration's tolerance).
- ``faults``: the cell's job as it is and with a planted fault,
  ``--lr 0`` (an optimizer that updates nothing), to show that the
  traffic file's ``correct`` bands pass the first and refuse the second.

Everything goes to standard output as ``[olmoe_check] key json`` lines
and to ``chiprun_out/olmoe_check.json``.
"""

from __future__ import annotations

import argparse
import json
import os

import numpy as np

CELL = "olmoe.fedavg_fold"
OUT = os.path.join("chiprun_out", "olmoe_check.json")
PHASES = ("logits", "grads", "forward")


def main(argv=None) -> None:
    import jax
    import jax.numpy as jnp

    from benchmark import cohort, harness

    ap = argparse.ArgumentParser()
    ap.add_argument("phases", nargs="*", default=list(PHASES))
    ap.add_argument("--seed", type=int, default=2147483659)
    args = ap.parse_args(argv)
    os.makedirs(os.path.dirname(OUT), exist_ok=True)
    res: dict = {}

    def note(key, value):
        res[key] = value
        print("[olmoe_check]", key, json.dumps(value, default=float),
              flush=True)
        with open(OUT, "w") as f:
            json.dump(res, f, indent=1, default=float)

    _, _, config, traffic = harness.load_cell(CELL)
    sizes = harness.site_sizes_of(config, traffic)
    path, _ = cohort.ensure_cohort(
        harness.CACHE_DIR, traffic["name"], sizes,
        tuple(config["input_shape"]), args.seed)

    def build(rounds, **flags):
        a = harness.cell_argv(config, traffic, path, len(sizes), args.seed,
                              rounds, os.path.join("chiprun_out", "log"))
        for k, v in flags.items():
            a[a.index("--" + k) + 1] = str(v)
        return harness.build_engine(a)

    engine = build(2)
    tr, d = engine.trainer, engine.data
    ref = harness.load_reference(config)
    gs = engine.init_global_state()
    note("device", {"kind": jax.devices()[0].device_kind, "seed": args.seed,
                    "placement": engine.program.placement})

    def ref_trunk(q, X):
        @jax.jit
        def f(params, x):
            with jax.default_matmul_precision("highest"):
                logits, _, experts = ref.trunk(params, x, q=q)
            return logits, experts
        outs = [f(gs.params, X[i:i + 8]) for i in range(0, len(X), 8)]
        return (np.concatenate([np.asarray(o[0], np.float64) for o in outs]),
                np.concatenate([np.asarray(o[1]) for o in outs]))

    lower = (("bf16", jnp.bfloat16), ("fp8_e4m3", jnp.float8_e4m3fn))
    same = lambda a, b: float(np.mean(np.sort(a, -1) == np.sort(b, -1)))

    if "logits" in args.phases:
        from neuroimagedisttraining_tpu.models.olmoe3d import SparseExperts

        X16 = jnp.concatenate([d.X_test[c, :4] for c in range(len(sizes))])

        @jax.jit
        def program(params, x):
            out, inter = tr.model.apply(
                {"params": params}, tr._prep(x), train=False,
                capture_intermediates=lambda m, _: isinstance(
                    m, SparseExperts))
            chosen = jax.tree.leaves(
                inter["intermediates"],
                is_leaf=lambda t: isinstance(t, tuple))[0][0][2]
            return out[0], chosen

        got, chosen = program(gs.params, X16)
        got = np.asarray(got, np.float64).ravel()
        want, want_chosen = ref_trunk(ref.ops.exact, X16)
        want = want.ravel()
        note("logits", {
            "program": got.tolist(), "reference": want.tolist(),
            "abs_diff_max": float(np.abs(got - want).max()),
            "rel_diff_max": float((np.abs(got - want)
                                   / np.maximum(np.abs(want), 1e-12)).max()),
            "routing_agreement": same(np.asarray(chosen), want_chosen)})
        for name, dt in lower:
            ql, qe = ref_trunk(ref.ops.rounded(dt), X16)
            note(f"logits_reference_{name}", {
                "abs_diff_max": float(np.abs(ql.ravel() - want).max()),
                "routing_agreement": same(qe, want_chosen)})

    if "grads" in args.phases:
        xb, yb = d.X_train[0, :16], d.y_train[0, :16]
        loss, grads, _, _ = jax.jit(tr.loss_and_grad)(gs, xb, yb)

        @jax.jit
        def ref_grad(params, x, y):
            with jax.default_matmul_precision("highest"):
                return jax.value_and_grad(ref.training_loss, has_aux=True)(
                    params, {}, x, y)

        (_, (task, aux)), g_ref = ref_grad(gs.params, xb, yb)
        _, chosen = ref_trunk(ref.ops.exact, xb[:8])
        e0 = int(np.bincount(chosen.ravel(), minlength=64).argmax())

        def rel(path, index=None):
            a, b = grads, g_ref
            for k in path:
                a, b = a[k], b[k]
            if index is not None:
                a, b = a[index], b[index]
            a, b = np.asarray(a, np.float64), np.asarray(b, np.float64)
            return float(np.linalg.norm(a - b) / np.linalg.norm(b))

        moe = ("layers_0", "moe")
        note("grads", {
            "task_loss_program": float(loss),
            "task_loss_reference": float(task), "aux_reference": float(aux),
            "busiest_expert": e0, "rel_l2": {
                "W_pe": rel(("patch_embed", "kernel")),
                "Wq": rel(("layers_0", "attn", "q_proj", "kernel")),
                "Wr": rel(moe + ("router",)),
                "gate": rel(moe + ("gate",), e0),
                "up": rel(moe + ("up",), e0),
                "down": rel(moe + ("down",), e0),
                "W_head": rel(("head", "kernel"))}})
        del grads, g_ref

    if "forward" in args.phases:
        from benchmark.reference import ops as ref_ops

        note("forward", harness.forward_check(engine, ref, config))

        def eval_loss(q):
            @jax.jit
            def losses(params, x, y):
                with jax.default_matmul_precision("highest"):
                    return ref_ops.bce_with_logits(
                        ref.forward(params, {}, x, q=q), y)
            per = []
            for X, y in harness.test_rows(engine):
                rows = [np.asarray(losses(gs.params, X[i:i + 8], y[i:i + 8]))
                        for i in range(0, len(y), 8)]
                per.append(float(np.mean(np.concatenate(rows))))
            return float(np.mean(per))

        base = eval_loss(ref.ops.exact)
        note("forward_reference_eval_loss", base)
        for name, dt in lower:
            note(f"forward_reference_{name}_rel_diff",
                 abs(eval_loss(ref.ops.rounded(dt)) - base) / base)

    if "faults" in args.phases:
        del engine, tr, d, gs
        bands = harness.correct_bands(config, traffic)
        rounds = int(bands["loss_round"]) + 2

        def job(label, **flags):
            eng = build(rounds, **flags)
            log = harness.RoundLog(eng)
            out = eng.train()
            rows = [r for r in log.take() if r["round"] >= 0]
            note("faults_" + label, {
                "learning": harness.learning_check(
                    rows, out["final_global"], bands),
                "train_loss": [float(r["train_loss"]) for r in rows],
                "auc": [float(r["auc"]) for r in rows]})

        job("none")
        job("lr0", lr=0)


if __name__ == "__main__":
    main()
