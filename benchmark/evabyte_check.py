"""On-chip comparison of ``--model evabyte3d`` with its reference (PR 38).

The builder's check at the published widths and the cell's sizes, outside
any timed window, ``zaya_check.py``'s twin; PERF.md section 6 quotes what it
prints. Not a metric reader and not run by ``benchmark.run``:

    chiprun -- python3 -m benchmark.evabyte_check [--seed N] [phases...]

Phases (default: all but ``probe``, ``control`` and ``faults``), each but
``probe`` on the cell's own engine, cohort and initial weights
(``benchmark/harness.py``):

- ``logits``: the six test volumes of the three sites through the TIMED
  path's evaluation (``LocalTrainer.evaluate``: ``bf16_mixed``, batches of
  4 rows) against the float32 reference a row at a time: per-row absolute
  difference, held to ``LOGIT_ATOL``; and the same reading for the
  reference computed in each lower precision (``lower_precisions``). The
  float8-operand one has to fail it. The one whose scores and stream are
  bfloat16 reads as the stated precision does (a logit is a mean over
  4,864 positions; the configuration file's ``forward_check.reason`` has
  the numbers): it is printed, and no tolerance that admits the program
  refuses it.
- ``grads``: one batch of 2: task loss, and for every layer, the patch
  embedding and the head the L2 norm of the program's gradient beside the
  reference's (``jax.grad`` of its loss, a row at a time with each layer
  rematerialised, averaged) and their relative L2 distance, leaf by leaf;
  and, for the first layer and the patch embedding, the same distance of
  each lower-precision reference's gradient from the float32 one. At the
  initial weights the scores are of order 1, and bfloat16 scores then add
  what bfloat16 operands already add: the readings are printed, and
  ``probe`` is the comparison that separates them.
- ``probe`` (:func:`probe`): the TIMED path's own functions at the step's
  shapes, ``evabyte3d.eva_attention`` (forward and its gradients) and one
  ``evabyte3d.Layer``, against the float32 reference position by position
  (relative L2 over un-pooled tensors, no mean over positions), on inputs
  at which the stated float32 parts carry weight: operands exact in
  bfloat16, scores of standard deviation ``PROBE_SCORE_STD`` (a trained
  layer's, not the initial weights' 0.7), a stream ``PROBE_STREAM_RMS``
  times a layer's contribution (as at depth). Held to ``PROBE_REL_L2_MAX``;
  the reference with bfloat16 scores, and with a bfloat16 stream, has to
  fail it (by one of its tensors, not by each: ``phi`` and ``mu`` hardly
  pass through the scores). ``--seeds`` runs it on several draws. No
  engine, no cohort: three draws take 92 s, cold. A PR that changes how
  the scores are computed (ROADMAP S11) runs it.
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
  0.5``), ``halfbatch`` (every step's loss, and so its gradient, over the
  first of its 2 rows); ``none`` is the job as it is. Every round's
  training loss and AUC is printed, and the norm of the parameters' change
  over the job relative to the initial parameters' norm. ``--faults``
  picks among them, ``--seeds`` runs them on several cohorts.

Everything goes to standard output as ``[evabyte_check] key json`` lines
and to ``chiprun_out/evabyte_check.json``.
"""

from __future__ import annotations

import argparse
import gc
import json
import os
import types

import numpy as np

CELL = "evabyte.fedavg_fold3_s10"
OUT = os.path.join("chiprun_out", "evabyte_check.json")
PHASES = ("logits", "grads", "forward")
FAULTS = ("none", "lr0", "lrtenth", "momentum05", "halfbatch")
#: a row's logit, program against float32 reference, absolute. The reason
#: and both readings are in the configuration file (``forward_check``).
LOGIT_ATOL = 1e-3

#: the probe's inputs: the standard deviation of the attention scores (an
#: 8-bit score is 0.06 coarse at |s| = 16: a test of sensitivity, not a
#: picture of a trained layer), and the stream's root mean square beside a
#: layer's contribution of about 0.5
PROBE_SCORE_STD = 16.0
PROBE_STREAM_RMS = 64.0
#: relative L2, program against float32 reference, of what the probe
#: compares: attention's output, its gradients, and what a layer adds to
#: the stream. Both readings of each on the v5e at the step's shapes, three
#: draws (my chip runs, PR 38), program / reference with bfloat16 scores
#: (or stream): out 0.146% / 1.83-1.84%; dq, dk 0.89-0.93% / 4.51-4.61%; dv
#: 0.225% / 1.83-1.84%; dphi, dmu 0.45-0.69% / 0.91-1.60%; added 0.456% /
#: 20.7%. Each limit lies between its two readings: 3.1 times over the
#: program's and 4.1 under the control's (forward), 2.2 and 2.3 (dq, dk),
#: 5.9 and 7.7 (added). dphi and dmu hardly pass through the scores: the
#: control fails by the other tensors.
PROBE_REL_L2_MAX = {"forward": 0.0045, "gradient": 0.02, "added": 0.027}
PROBE_LIMIT_OF = {"out": "forward", "dq": "gradient", "dk": "gradient",
                  "dv": "gradient", "dphi": "gradient", "dmu": "gradient",
                  "added": "added"}


def probe(ref, widths, tokens: int, batch: int, seed: int, dtype) -> dict:
    """The timed path's attention and layer against the float32 reference,
    position by position, and the reference with bfloat16 scores or a
    bfloat16 stream against the same. ``widths`` is the program's
    (``evabyte3d.Widths``), ``dtype`` its compute dtype."""
    import jax
    import jax.numpy as jnp

    from neuroimagedisttraining_tpu.models import evabyte3d

    f32, ops = jnp.float32, ref.ops
    A, d, H = widths.heads, widths.head_dim, widths.hidden_size
    W, c = widths.window_size, widths.chunk_size
    cfg = {**ref.PUBLISHED, "window_size": W, "chunk_size": c,
           "rope_theta": widths.rope_theta, "rms_eps": widths.rms_eps}
    bf16 = ops.rounded(jnp.bfloat16)
    keys = iter(jax.random.split(jax.random.key(seed % (2 ** 31)), 16))
    draw = lambda shape, std=1.0: bf16(
        std * jax.random.normal(next(keys), shape, f32))
    rel = lambda a, b: float(
        jnp.linalg.norm((a.astype(f32) - b).ravel())
        / jnp.maximum(jnp.linalg.norm(b.ravel()), 1e-30))

    # ----- attention: operands exact in bfloat16, so a product's float32
    # sum is the same on both sides and what is left is the scores' path
    heads = (batch, tokens, A, d)
    q, k = (draw(heads, PROBE_SCORE_STD ** 0.5) for _ in range(2))
    v, g = draw(heads), draw((batch, tokens, A * d))
    phi, mu = (bf16(jnp.clip(draw((A, d)), -1, 1) / d ** 0.5)
               for _ in range(2))

    @jax.jit
    def mine(q, k, v, phi, mu, g):
        low = lambda t: t.astype(dtype)
        out, vjp = jax.vjp(
            lambda q, k, v, phi, mu: evabyte3d.eva_attention(
                q, k, v, phi, mu, W, c, dtype),
            low(q), low(k), low(v), phi, mu)
        return (out, *vjp(low(g)))

    def reference(q_scores):
        @jax.jit
        def one(q, k, v, phi, mu, g):
            out, vjp = jax.vjp(
                lambda *a: ops.eva_attention(*a, W, c, q_scores=q_scores),
                q, k, v, phi, mu)
            return (out, *vjp(g))
        rows = [one(q[i:i + 1], k[i:i + 1], v[i:i + 1], phi, mu,
                    g[i:i + 1]) for i in range(batch)]
        return [jnp.concatenate([r[j] for r in rows]) for j in range(4)] \
            + [sum(r[j] for r in rows) for j in (4, 5)]

    names = ("out", "dq", "dk", "dv", "dphi", "dmu")
    want = reference(ops.exact)
    out = {"attention": {
        "program": dict(zip(names, map(rel, mine(q, k, v, phi, mu, g),
                                       want))),
        "reference_bf16_scores": dict(zip(names, map(
            rel, reference(bf16), want)))}}
    del want

    # ----- the stream: one layer on a stream far larger than what the
    # layer adds to it; what is compared is what the layer adds
    layer = evabyte3d.Layer(widths, dtype)
    params = jax.jit(layer.init)(next(keys), jnp.zeros((1, c, H), f32))
    h = PROBE_STREAM_RMS * jax.random.normal(next(keys),
                                             (batch, tokens, H), f32)
    added = jax.jit(lambda p, h: layer.apply(p, h) - h)(params, h)

    def reference_added(q_stream):
        one = jax.jit(lambda p, h: ref.layer(
            h, p, cfg, ops.exact, ops.exact, q_stream, None, "") - h)
        return jnp.concatenate([one(params["params"], h[i:i + 1])
                                for i in range(batch)])

    want = reference_added(ops.exact)
    out["stream"] = {
        "program": {"added": rel(added, want)},
        "reference_bf16_stream": {"added": rel(reference_added(bf16),
                                               want)},
        "stream_rms_over_added_rms": float(
            jnp.sqrt(jnp.mean(h ** 2) / jnp.mean(want ** 2)))}
    over = lambda readings: sorted(
        n for n, x in readings.items()
        if not x <= PROBE_REL_L2_MAX[PROBE_LIMIT_OF[n]])
    for part in out.values():
        control = next(k for k in part if k.startswith("reference_"))
        part["program_over"] = over(part["program"])
        part["control_over"] = over(part[control])
        part["ok"] = not part["program_over"]
        part["control_fails"] = bool(part["control_over"])
    out["rel_l2_max"] = PROBE_REL_L2_MAX
    return out


def lower_precisions(ops):
    """The reference's keyword arguments for each precision below the one
    the configuration states (bf16 operands; float32 scores, softmax and
    stream): the stated precision's float32 parts in bfloat16, and the
    operands in the nearest precision below bfloat16."""
    import jax.numpy as jnp

    bf16 = ops.rounded(jnp.bfloat16)
    return (
        ("bf16_operands", {"q": bf16}),  # the stated precision: no control
        ("bf16_scores_and_stream", {"q": bf16, "q_scores": bf16,
                                    "q_stream": bf16}),
        ("fp8_e4m3_operands", {"q": ops.rounded(jnp.float8_e4m3fn)}),
    )


def main(argv=None) -> None:
    import jax
    import jax.numpy as jnp

    from benchmark import cohort, harness

    ap = argparse.ArgumentParser()
    ap.add_argument("phases", nargs="*", default=list(PHASES))
    ap.add_argument("--seed", type=int, default=2147483659)
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
        print("[evabyte_check]", key, json.dumps(value, default=float),
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
    lower = lower_precisions(ref.ops)
    if "probe" in args.phases:
        from neuroimagedisttraining_tpu.models import evabyte3d

        tokens = ref.published_tape()[0]["out_spatial"][0]
        for seed in args.seeds or [args.seed]:
            note(f"probe_{seed}", {
                "tokens": tokens, "batch": int(config["batch_size"]),
                "score_std": PROBE_SCORE_STD,
                "stream_rms": PROBE_STREAM_RMS,
                **probe(ref, evabyte3d.Widths(), tokens,
                        int(config["batch_size"]), seed, jnp.bfloat16)})
            gc.collect()

    engine = tr = d = gs = None
    if set(args.phases) & {"logits", "grads", "forward", "control"}:
        engine = build(2)
        tr, d = engine.trainer, engine.data
        gs = engine.init_global_state()
        note("device", {"kind": jax.devices()[0].device_kind,
                        "seed": args.seed,
                        "placement": engine.program.placement,
                        "heads": tr.model.widths.heads,
                        "eval_batch_rows": tr.eval_batch_rows(
                            tuple(config["input_shape"]))})

    norm = lambda t: float(np.sqrt(sum(
        float(np.sum(np.square(np.asarray(a, np.float64))))
        for a in jax.tree.leaves(t))))

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

    if "grads" in args.phases:
        batch = int(config["batch_size"])
        xb, yb = d.X_train[0, :batch], d.y_train[0, :batch]
        loss, grads, _, _ = jax.jit(tr.loss_and_grad)(gs, xb, yb)
        grads = jax.tree.map(np.asarray, grads)  # to the host: 2.4 GB

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
            """``(task loss, gradient)`` of the batch, a row at a time."""
            task, g_ref = 0.0, None
            for i in range(batch):
                t, g = f(part, gs.params, xb[i:i + 1], yb[i:i + 1])
                task += float(t) / batch
                g = jax.tree.map(lambda a: np.asarray(a) / batch, g)
                g_ref = g if g_ref is None else jax.tree.map(np.add, g_ref, g)
            return task, g_ref

        def rel_l2(got, want):
            got = dict(jax.tree_util.tree_flatten_with_path(got)[0])
            return {jax.tree_util.keystr(path): float(
                np.linalg.norm(np.asarray(got[path], np.float64) - b)
                / max(np.linalg.norm(b), 1e-30))
                for path, b in jax.tree_util.tree_flatten_with_path(want)[0]}

        exact = ref_grad()
        layers = [f"layers_{i}" for i in range(ref.LAYERS)]
        out, low_out, task = {}, {}, 0.0
        for names in ([layers[0], "patch_embed"], *[[n] for n in layers[1:-1]],
                      [layers[-1], "head", "final_norm"]):
            part = {n: gs.params[n] for n in names}
            t, g_ref = batch_grad(exact, part)
            if names[0] == layers[0]:
                task = t
                # the lower precisions' gradients against the float32 one,
                # where the program's distance is largest (the first
                # layer's q and k projections and mu)
                for label, kw in lower:
                    _, g_low = batch_grad(ref_grad(**kw), part)
                    low_out[label] = {n: rel_l2(g_low[n], g_ref[n])
                                      for n in names}
                    del g_low
            for n in names:
                out[n] = {"norm_program": norm(grads[n]),
                          "norm_reference": norm(g_ref[n]),
                          "rel_l2": rel_l2(grads[n], g_ref[n])}
            del g_ref
        note("grads_reference_lower", low_out)
        note("grads", {"task_loss_program": float(loss),
                       "task_loss_reference": task, "by_part": out})
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
        bands = harness.correct_bands(config, traffic)
        rounds = int(bands["loss_round"]) + 1

        def half_batch(trainer):
            """Plant: every step's loss, and so its gradient, is the mean
            over the first half of the batch's rows."""
            objective = trainer._objective

            def planted(out, y, weights=None):
                n = y.shape[0]
                half = (jnp.arange(n) < n // 2).astype(jnp.float32)
                return objective(
                    out, y, half if weights is None else weights * half)
            trainer._objective = planted

        planted = {"none": ({}, None), "lr0": ({"lr": 0}, None),
                   "lrtenth": ({"lr": 0.001}, None),
                   "momentum05": ({"momentum": 0.5}, None),
                   "halfbatch": ({}, half_batch)}
        for seed in args.seeds or [args.seed]:
            start = None
            for label in args.faults:
                flags, plant = planted[label]
                eng = build(rounds, seed=seed, **flags)
                if start is None:  # the seed's initial weights, once
                    start = jax.tree.map(np.asarray,
                                         eng.init_global_state().params)
                if plant is not None:
                    plant(eng.trainer)
                n_test = np.asarray(eng.data.n_test)
                y_test = np.asarray(eng.data.y_test)
                log = harness.RoundLog(eng)
                out = eng.train()
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
                    "param_change_rel_norm": norm(moved) / norm(start)})
                del eng, log, out, moved
                gc.collect()


if __name__ == "__main__":
    main()
