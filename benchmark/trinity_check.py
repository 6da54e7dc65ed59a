"""On-chip comparison of ``--model trinity3d`` with its reference (PR 44).

The builder's check at the published widths and the cell's sizes, outside
any timed window, ``moonlight_check.py``'s twin; PERF.md section 6 quotes
what it prints. Not a metric reader and not run by ``benchmark.run``:

    chiprun -- python3 -m benchmark.trinity_check [--seed N] [phases...]

Phases (default: all but ``control`` and ``faults``), each on the cell's
own engine, cohort and initial weights (``benchmark/harness.py``):

- ``logits``: the six test volumes of the three sites through the TIMED
  path's evaluation (``LocalTrainer.evaluate``: ``bf16_mixed``) against
  the float32 reference a row at a time: per-row absolute difference, held
  to ``LOGIT_ATOL``; the same reading for the reference computed in each
  lower precision (``lower_precisions``): the float8-operand one has to
  fail it; for the reference WITHOUT THE WINDOW (every layer reads the
  whole causal triangle, ``no_window``), which has to fail it too; and, on
  one training batch, how many of the program's routing choices the
  float32 reference makes too, a layer, with the slots that landed on the
  held experts.
- ``grads``: one batch of 2: the loss beside the reference's (there is
  no auxiliary term), and for every layer, the patch embedding and the head the L2 norm of the
  program's gradient beside the reference's (``jax.grad`` of its loss, a
  row at a time with each layer rematerialised, averaged) and their
  relative L2 distance, leaf by leaf; for the first two layers and the
  patch embedding, the same distance of each lower-precision reference's
  gradient from the float32 one.
- ``forward``: ``harness.forward_check`` itself: the program against the
  float32 reference (the cell's own check), and each lower-precision
  reference IN THE PROGRAM'S PLACE against the float32 one (a stand-in
  engine whose ``eval_global`` answers with the loss the harness itself
  just computed from it). The float8 one is the control of
  ``forward_check.rel_tol``: it has to come out ``"ok": false``.
- ``control``: the ``forward`` phase over ``--seeds``, a fresh cohort,
  initial weights and engine a seed.
- ``faults``: the cell's job with a planted fault, through
  ``harness.learning_check`` under the configuration's bands: ``lr0``
  (``--lr 0``), ``lrtenth`` (``--lr 0.001``), ``momentum05`` (``--momentum
  0.5``); ``none`` is the job as it is. Every round's training loss and
  AUC is printed, the routing counters a round (the tracer is armed for
  them), and the norm of
  the parameters' change over the job relative to the initial parameters'
  norm. ``--faults`` picks among them, ``--seeds`` runs them on several
  cohorts.

Everything goes to standard output as ``[trinity_check] key json`` lines
and to ``chiprun_out/trinity_check.json``.
"""

from __future__ import annotations

import argparse
import gc
import json
import os
import types

import numpy as np

CELL = "trinity.fedavg_fold3_s10k"
OUT = os.path.join("chiprun_out", "trinity_check.json")
PHASES = ("logits", "grads", "forward")
FAULTS = ("none", "lr0", "lrtenth", "momentum05")
#: a row's logit, program against float32 reference, absolute: between
#: the program's largest reading and the smallest of the two controls'
#: that have to fail it (float8 operands; no window). The readings are in
#: the configuration file's ``forward_check.reason`` (my chip run, PR 44,
#: seed 2147483833, logits of -1.09 to 0.52): the program 3.0e-4 to 5.5e-3,
#: float8 operands up to 8.2e-2, no window 2.0e-2 to 2.7e-1 on every row.
LOGIT_ATOL = 2e-2


def lower_precisions(ops):
    """The reference's keyword arguments for each precision below the one
    the configuration states (bf16 operands; float32 scores, softmax and
    router): the stated precision's float32 parts in bfloat16, and the
    operands in the nearest precision below bfloat16."""
    import jax.numpy as jnp

    bf16 = ops.rounded(jnp.bfloat16)
    return (
        ("bf16_operands", {"q": bf16}),  # the stated precision: no control
        ("bf16_scores_and_router", {"q": bf16, "q_scores": bf16,
                                    "q_router": bf16}),
        ("fp8_e4m3_operands", {"q": ops.rounded(jnp.float8_e4m3fn)}),
    )


def no_window(ref) -> dict:
    """The reference's keyword arguments for the control without the
    window: float32, every layer reading the whole causal triangle (the
    sliding ones still under their rotary embedding)."""
    return {"cfg": {**ref.PUBLISHED, "sliding_window": None}}


def main(argv=None) -> None:
    import jax
    import jax.numpy as jnp

    from benchmark import cohort, harness

    ap = argparse.ArgumentParser()
    ap.add_argument("phases", nargs="*", default=list(PHASES))
    ap.add_argument("--seed", type=int, default=2147483777)
    ap.add_argument("--seeds", type=int, nargs="*", default=[],
                    help="the control phase's seeds, and the faults'")
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
        print("[trinity_check]", key, json.dumps(value, default=float),
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

    ref = harness.load_reference(config)
    lower = lower_precisions(ref.ops) + (("no_window", no_window(ref)),)
    engine = tr = d = gs = None
    if set(args.phases) & {"logits", "grads", "forward", "control"}:
        engine = build(2)
        tr, d = engine.trainer, engine.data
        gs = engine.init_global_state()
        note("device", {"kind": jax.devices()[0].device_kind,
                        "seed": args.seed,
                        "placement": engine.program.placement,
                        "held_experts": tr.model.held_experts,
                        "eval_batch_rows": tr.eval_batch_rows(
                            tuple(config["input_shape"]))})

    norm = lambda t: float(np.sqrt(sum(
        float(np.sum(np.square(np.asarray(a, np.float64))))
        for a in jax.tree.leaves(t))))
    batch = int(config["batch_size"])

    def ref_logits(X, **kw):
        @jax.jit
        def f(params, x):
            with jax.default_matmul_precision("highest"):
                return ref.forward(params, {}, x, **kw)
        return np.asarray(f(gs.params, X), np.float64).ravel()

    if "logits" in args.phases:
        n_test = np.asarray(d.n_test)
        rows = [(c, i) for c in range(len(sizes)) for i in range(n_test[c])]
        X = jnp.stack([d.X_test[c, i] for c, i in rows])
        y = jnp.stack([d.y_test[c, i] for c, i in rows])
        got = np.asarray(jax.jit(tr.evaluate)(
            gs.params, gs.batch_stats, X, y,
            jnp.ones(len(rows), bool))["scores"], np.float64)
        want = ref_logits(X)
        diff = np.abs(got - want)
        note("logits", {
            "program": got.tolist(), "reference": want.tolist(),
            "abs_diff": diff.tolist(), "abs_diff_max": float(diff.max()),
            "atol": LOGIT_ATOL, "ok": bool(diff.max() <= LOGIT_ATOL)})
        for name, kw in lower:
            low = np.abs(ref_logits(X, **kw) - want)
            note(f"logits_reference_{name}", {
                "abs_diff": low.tolist(), "abs_diff_max": float(low.max()),
                "ok": bool(low.max() <= LOGIT_ATOL)})
        # the routing of one training batch: the program's choices (the
        # model's own call, bf16_mixed) against the float32 reference's
        from neuroimagedisttraining_tpu.models.trinity3d import (
            HeldExperts as HeldGatedExperts,
        )

        xb = d.X_train[0, :batch]

        @jax.jit
        def choices(params, x):
            _, inter = tr.model.apply(
                {"params": params}, tr._prep(x), train=True,
                capture_intermediates=lambda m, _: isinstance(
                    m, HeldGatedExperts))
            leaves = jax.tree.leaves(inter["intermediates"],
                                     is_leaf=lambda t: isinstance(t, tuple))
            return jnp.stack([leaf[0][1] for leaf in leaves])

        @jax.jit
        def ref_choices(params, x):
            with jax.default_matmul_precision("highest"):
                return ref.trunk(params, x)[1]

        mine = np.sort(np.asarray(choices(gs.params, xb)), axis=-1)
        theirs = np.sort(np.concatenate(
            [np.asarray(ref_choices(gs.params, xb[i:i + 1]))
             for i in range(batch)], axis=1), axis=-1)
        first, count = tr.model.held_experts
        note("routing", {
            "tokens_with_the_same_eight_share": np.mean(
                np.all(mine == theirs, axis=-1), axis=1).tolist(),
            "held_slots_program": np.sum(
                (mine >= first) & (mine < first + count),
                axis=(1, 2)).tolist(),
            "held_slots_reference": np.sum(
                (theirs >= first) & (theirs < first + count),
                axis=(1, 2)).tolist(),
            "slots_a_layer": int(mine.shape[1] * mine.shape[2]),
            "held_capacity_rows": tr.model.held_capacity_rows(
                (batch, *config["input_shape"], 1))})

    if "grads" in args.phases:
        xb, yb = d.X_train[0, :batch], d.y_train[0, :batch]
        loss, grads, _, _ = jax.jit(tr.loss_and_grad)(gs, xb, yb)
        grads = jax.tree.map(np.asarray, grads)  # to the host: 2.3 GB

        def ref_grad(**kw):
            @jax.jit
            def f(part, params, x, y):
                with jax.default_matmul_precision("highest"):
                    return jax.value_and_grad(
                        lambda p: ref.training_loss(
                            {**params, **p}, {}, x, y, remat=True, **kw))(
                                part)
            return f

        def batch_grad(f, part):
            """``(loss with L, gradient)`` of the batch, a row at a time."""
            total, g_ref = 0.0, None
            for i in range(batch):
                t, g = f(part, gs.params, xb[i:i + 1], yb[i:i + 1])
                total += float(t) / batch
                g = jax.tree.map(lambda a: np.asarray(a) / batch, g)
                g_ref = g if g_ref is None else jax.tree.map(np.add, g_ref, g)
            return total, g_ref

        def rel_l2(got, want):
            got = dict(jax.tree_util.tree_flatten_with_path(got)[0])
            return {jax.tree_util.keystr(path): float(
                np.linalg.norm(np.asarray(got[path], np.float64) - b)
                / max(np.linalg.norm(b), 1e-30))
                for path, b in jax.tree_util.tree_flatten_with_path(want)[0]}

        exact = ref_grad()
        layers = [f"layers_{i}" for i in range(ref.LAYERS)]
        out, low_out, total = {}, {}, 0.0
        for names in ([layers[0], "patch_embed"], *[[n] for n in layers[1:-1]],
                      [layers[-1], "head", "final_norm"]):
            part = {n: gs.params[n] for n in names}
            t, g_ref = batch_grad(exact, part)
            if names[0] in layers[:2]:
                total = t
                # the lower precisions' gradients against the float32 one,
                # in the leading dense layer and the first expert layer
                for label, kw in lower_precisions(ref.ops):
                    _, g_low = batch_grad(ref_grad(**kw), part)
                    low_out.setdefault(label, {}).update(
                        {n: rel_l2(g_low[n], g_ref[n]) for n in names})
                    del g_low
            for n in names:
                out[n] = {"norm_program": norm(grads[n]),
                          "norm_reference": norm(g_ref[n]),
                          "rel_l2": rel_l2(grads[n], g_ref[n])}
            del g_ref
        note("grads_reference_lower", low_out)
        note("grads", {
            "loss_program": float(loss), "loss_reference": total,
            "by_part": out})
        del grads

    def forward_readings(engine):
        """``harness.forward_check``'s verdicts: the program against the
        float32 reference, and each lower-precision reference in the
        program's place against the float32 one."""
        out = {"program": harness.forward_check(engine, ref, config)}
        for name, kw in lower:
            low = types.SimpleNamespace(
                forward=lambda p, st, x, kw=kw: ref.forward(p, st, x, **kw))
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
        note("forward", forward_readings(engine))

    if "control" in args.phases:
        for seed in args.seeds:
            eng = engine if seed == args.seed else build(2, seed=seed)
            note(f"control_{seed}", forward_readings(eng))
            del eng
            gc.collect()

    if "faults" in args.phases:
        engine = tr = d = gs = None
        gc.collect()
        from neuroimagedisttraining_tpu.obs import trace as obs_trace

        bands = harness.correct_bands(config, traffic)
        rounds = int(bands["loss_round"]) + 1
        planted = {"none": {}, "lr0": {"lr": 0}, "lrtenth": {"lr": 0.001},
                   "momentum05": {"momentum": 0.5}}
        for seed in args.seeds or [args.seed]:
            start = None
            for label in args.faults:
                eng = build(rounds, seed=seed, **planted[label])
                if start is None:  # the seed's initial weights, once
                    start = jax.tree.map(np.asarray,
                                         eng.init_global_state().params)
                n_test = np.asarray(eng.data.n_test)
                y_test = np.asarray(eng.data.y_test)
                log = harness.RoundLog(eng)
                # the round driver puts the routing on its round_log spans
                # while the tracer is armed (engines/fedavg.py)
                obs_trace.arm()
                out = eng.train()
                routing = [e["args"] for e in obs_trace.TRACER.events()
                           if e.get("ph") == "X" and e["name"] == "round_log"
                           and "tokens_routed" in e.get("args", {})]
                obs_trace.TRACER.disarm()
                rows = [r for r in log.take() if r["round"] >= 0]
                moved = jax.tree.map(lambda a, b: np.asarray(a) - b,
                                     out["params"], start)
                note(f"faults_{label}_{seed}", {
                    "learning": harness.learning_check(
                        rows, out["final_global"], bands),
                    "sites_of_both_classes": sum(
                        len(set(y_test[c, :n_test[c]].tolist())) > 1
                        for c in range(len(n_test))),
                    "train_loss": [float(r["train_loss"]) for r in rows],
                    "test_loss": [float(r["loss"]) for r in rows],
                    "auc": [float(r["auc"]) for r in rows],
                    "routing_by_round": [{k: a[k] for k in (
                        "tokens_routed", "rows_held",
                        "expert_load_max_over_mean",
                        "held_load_max_over_mean", "held_overflow_calls",
                        "held_capacity_rows", "attn_kernel_calls")
                        if k in a} for a in routing],
                    "param_change_rel_norm": norm(moved) / norm(start)})
                del eng, log, out, moved
                gc.collect()


if __name__ == "__main__":
    main()
