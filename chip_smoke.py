#!/usr/bin/env python3
"""Chip smoke: the federated round on a TPU through the normal entry point.

One process, one chip. Drives ``neuroimagedisttraining_tpu.__main__.main``
(the CLI a user calls) at the full width of the flagship: model ``3DCNN``
(AlexNet3D_Dropout) on 121x145x121 volumes at batch 16, data made from
``--seed``. Phases, each printing one JSON line as it finishes; the first
failed assertion ends the script with a traceback and a non-zero exit:

1. device        fail unless ``jax.devices()[0].platform == "tpu"``
2. fedavg        4 clients, bf16_mixed + the fused Pallas SGD tail
3. salientgrads  the README flagship command at the same small cohort;
                 mask density, and Pallas == XLA threshold on the model's
                 real score vector
4. streaming     an HDF5 cohort streamed from the host (native gather)
5. kernels       ``tpu_custom_call`` in the compiled mask program and in
                 the compiled fused-update round
6. fallbacks     ``nidt_fallback_total`` by reason

``--chips 4`` runs the multi-chip path and what it is compared with, and
no other phase: the same fedavg run on one device (``--mesh_shape 1``),
on the default 4-device mesh (client axis sharded, vmapped round under
GSPMD) and under ``--client_mesh 4`` (shard_map), which must agree.

The last stdout line is ``{"ok": true, "device": {...}}``. These are a
smoke run's seconds, not benchmark numbers.
"""

from __future__ import annotations

import argparse
import contextlib
import gc
import glob
import importlib.metadata
import json
import math
import os
import shutil
import sys
import time

MODEL = "3DCNN"
SHAPE = (121, 145, 121)
CLIENTS = 4
BATCH = 16
SUBJECTS = 128           # ~32 per client before the 80/20 split
STREAM_SUBJECTS = 96
KERNEL_MARK = "tpu_custom_call"
COLLECTIVES = ("all-reduce", "all-gather", "reduce-scatter",
               "collective-permute", "all-to-all")
#: --chips 4 agreement bounds. Differently partitioned programs fuse
#: differently, so bf16 activations round at different points: on the
#: chip the three layouts' first-round losses sat 0.05-0.2% apart, and at
#: the default lr 0.01 the drift grew to 1.4-2.1% by round 2 (PR 21 chip
#: run). The comparison therefore trains at a tamer lr, where a bound of
#: a few percent separates rounding drift from a wrong sharding.
MULTICHIP_LR = 0.001
LOSS_RTOL = 3e-2
UPDATE_REL_L2 = 0.5
UPDATE_COSINE = 0.9


def emit(**record) -> None:
    print(json.dumps(record, default=float), flush=True)


class CompileClock:
    """Seconds JAX spent in backend compilation (or fetching from the
    persistent cache), and the cache's hit/miss counts, since ``take``."""

    def __init__(self):
        from jax import monitoring

        self.secs, self.hits, self.misses = 0.0, 0, 0
        monitoring.register_event_duration_secs_listener(self._duration)
        monitoring.register_event_listener(self._event)

    def _duration(self, event, secs, **_):
        if event == "/jax/core/compile/backend_compile_duration":
            self.secs += secs

    def _event(self, event, **_):
        if event == "/jax/compilation_cache/cache_hits":
            self.hits += 1
        elif event == "/jax/compilation_cache/cache_misses":
            self.misses += 1

    def take(self) -> dict:
        out = {"compile_seconds": round(self.secs, 2),
               "cache_hits": self.hits, "cache_misses": self.misses}
        self.secs, self.hits, self.misses = 0.0, 0, 0
        return out


def run_phase(name: str, clock: CompileClock, fn, *args) -> dict:
    gc.collect()  # drop the last phase's engine (and its device buffers)
    clock.take()
    t0 = time.perf_counter()
    info = fn(*args)
    emit(phase=name, seconds=round(time.perf_counter() - t0, 2),
         **clock.take(), **info)
    return info


# ---------- the entry point, as a user calls it ----------

class _LastLine:
    """stdout tee that remembers the last non-empty line written."""

    def __init__(self, stream):
        self.stream, self.last, self._buf = stream, "", ""

    def write(self, text):
        self.stream.write(text)
        self._buf += text
        *lines, self._buf = self._buf.split("\n")
        for line in lines:
            if line.strip():
                self.last = line
        return len(text)

    def flush(self):
        self.stream.flush()


def run_main(argv: list[str]) -> dict:
    """``main(argv)`` -> its result line; asserts the return code."""
    from neuroimagedisttraining_tpu.__main__ import main

    tee = _LastLine(sys.stdout)
    with contextlib.redirect_stdout(tee):
        rc = main(argv)
    assert rc == 0, f"main() returned {rc} for {argv}"
    return json.loads(tee.last)


def common_argv(out: str, name: str, seed: int) -> list[str]:
    return ["--model", MODEL, "--client_num_in_total", str(CLIENTS),
            "--batch_size", str(BATCH), "--seed", str(seed),
            "--log_dir", os.path.join(out, name)]


def synthetic_argv() -> list[str]:
    return ["--dataset", "synthetic",
            "--synthetic_shape", *map(str, SHAPE),
            "--synthetic_num_subjects", str(SUBJECTS)]


def fedavg_argv(out: str, name: str, seed: int, rounds: int,
                fused: bool = True) -> list[str]:
    return ["--algorithm", "fedavg", *synthetic_argv(),
            "--epochs", "1", "--comm_round", str(rounds),
            "--precision", "bf16_mixed",
            *(["--fused_update"] if fused else []),
            *common_argv(out, name, seed)]


def round_losses(out: str, name: str, rounds: int) -> list[float]:
    """Every round's training loss from the metrics JSONL under the log
    directory; asserts one finite value per round."""
    (path,) = glob.glob(os.path.join(out, name, "*", "*.metrics.jsonl"))
    with open(path) as f:
        rows = [json.loads(line) for line in f]
    losses = [r["train_loss"] for r in rows if r["round"] >= 0]
    assert len(losses) == rounds, (path, losses)
    assert all(math.isfinite(x) for x in losses), losses
    return losses


def hold_engine(argv: list[str]):
    """The engine ``main(argv)`` would build (same parser, config and
    mesh rule) for the checks that need the compiled programs."""
    from neuroimagedisttraining_tpu.__main__ import (
        add_args, build_experiment, config_from_args, run_mesh,
    )

    args = add_args(argparse.ArgumentParser()).parse_args(argv)
    cfg = config_from_args(args)
    return build_experiment(cfg, streaming=args.streaming,
                            mesh=run_mesh(cfg, args.streaming),
                            console=False)


def kernel_in(compiled_text: str) -> bool:
    """A Mosaic (Pallas TPU) kernel is in the compiled program: the XLA
    branch of a kernel-or-XLA choice leaves no such custom call."""
    return KERNEL_MARK in compiled_text


def compiled_round(engine):
    """Lower and compile the round program ``engine.train()`` dispatches
    (the cohort-sharded variant under --client_mesh)."""
    import jax.numpy as jnp
    import numpy as np

    gs = engine.init_global_state()
    ids, n_real, deal = engine.client_sampling(0), None, None
    if engine.cfg.fed.client_mesh:
        ids, n_real = engine._cohort_pad(ids)
        deal, _ = engine._cohort_deal(ids, n_real)
        ids, deal = ids[deal], jnp.asarray(deal)
    rngs = engine.per_client_rngs(0, np.asarray(ids))
    prog = engine.program.round_jit(n_real=n_real)
    return prog.jit.lower(
        (gs.params, gs.batch_stats), engine.data, (), jnp.asarray(ids),
        rngs, engine.round_lr(0), None, None, None, deal).compile()


# ---------- phases ----------

def phase_device(chips: int) -> dict:
    import jax
    import jaxlib

    from neuroimagedisttraining_tpu.utils.compile_cache import (
        enable_compile_cache,
    )

    cache_dir = enable_compile_cache()
    devices = jax.devices()
    info = {"platform": devices[0].platform,
            "kind": devices[0].device_kind, "count": len(devices),
            "jax": jax.__version__, "jaxlib": jaxlib.__version__,
            "libtpu": importlib.metadata.version("libtpu"),
            "compile_cache_dir": cache_dir}
    assert info["platform"] == "tpu", f"no TPU: {info}"
    assert len(devices) >= chips, f"need {chips} chips: {info}"
    return info


def phase_fedavg(out: str, seed: int) -> dict:
    rounds = 3
    result = run_main(fedavg_argv(out, "fedavg", seed, rounds))
    losses = round_losses(out, "fedavg", rounds)
    assert math.isfinite(result["final_global"]["loss"]), result
    assert losses[-1] != losses[0], losses
    return {"round_losses": losses, "final_loss": losses[-1],
            "final_global": result["final_global"],
            "asserted": "main()==0; finite loss every round; last != first"}


def salientgrads_argv(out: str, name: str, seed: int) -> list[str]:
    # README "flagship configuration" (2 local epochs, unfused SGD tail)
    # plus --precision bf16_mixed: at the CLI's fp32 default the vmapped
    # 4-client x batch-16 round needs 27 GB of the chip's 15.75 GB (the
    # TPU compiler refuses it, with or without --remat), bf16 needs 12.7
    return ["--algorithm", "salientgrads", *synthetic_argv(),
            "--comm_round", "2", "--dense_ratio", "0.5",
            "--precision", "bf16_mixed",
            *common_argv(out, name, seed)]


def phase_salientgrads(out: str, seed: int, held: dict) -> dict:
    import jax.numpy as jnp

    from neuroimagedisttraining_tpu.ops import snip as snip_ops
    from neuroimagedisttraining_tpu.ops.topk import kth_largest

    result = run_main(salientgrads_argv(out, "salientgrads", seed))
    losses = round_losses(out, "salientgrads", 2)
    # the mask keeps every score >= the k-th largest: ties can only add
    density = result["mask_density"]
    assert 0.5 <= density <= 0.5 + 1e-3, density

    # the Pallas threshold against the XLA one, on the real score vector
    engine = hold_engine(salientgrads_argv(out, "salientgrads_held", seed))
    gs = engine.init_global_state()
    flat = snip_ops.flat_weight_scores(
        engine.global_scores(gs.params, gs.batch_stats))
    flat = flat / jnp.sum(flat)
    k = max(1, int(flat.size * 0.5))
    thr_pallas = kth_largest(flat, k, use_pallas=True)
    thr_xla = kth_largest(flat, k, use_pallas=False)
    assert bool(jnp.isfinite(thr_pallas)), thr_pallas
    assert bool(thr_pallas == thr_xla), (thr_pallas, thr_xla)
    held["scores"], held["k"] = flat, k
    return {"round_losses": losses, "final_loss": losses[-1],
            "mask_density": density, "scores": int(flat.size),
            "threshold": float(thr_pallas),
            "asserted": "main()==0; density in [0.5, 0.501]; finite "
                        "loss every round; pallas threshold == xla "
                        "threshold on the real score vector"}


def phase_streaming(out: str, seed: int) -> dict:
    from neuroimagedisttraining_tpu.data.synthetic import (
        write_synthetic_hdf5,
    )
    from neuroimagedisttraining_tpu.utils import native

    h5 = os.path.join(out, "cohort.h5")
    write_synthetic_hdf5(h5, num_subjects=STREAM_SUBJECTS, shape=SHAPE,
                         num_sites=CLIENTS, seed=seed)
    result = run_main(
        ["--algorithm", "fedavg", "--dataset", "abcd_h5", "--data_dir", h5,
         "--streaming", "--epochs", "1", "--comm_round", "2",
         "--precision", "bf16_mixed", "--fused_update",
         *common_argv(out, "streaming", seed)])
    losses = round_losses(out, "streaming", 2)
    cohort_bytes = os.path.getsize(h5)
    os.unlink(h5)  # chiprun_out/ brings back logs, not the cohort
    assert math.isfinite(result["final_global"]["loss"]), result
    assert native.load() is not None, "native gather library did not load"
    return {"round_losses": losses, "final_loss": losses[-1],
            "cohort_bytes": cohort_bytes,
            "asserted": "main()==0; finite loss every round; "
                        "utils.native.load() returned the library"}


def fused_update_on_device(params) -> dict:
    """The Pallas tail against its XLA reference on the device, over
    every leaf of the real parameter tree (masked, clipped)."""
    import jax
    import jax.numpy as jnp
    import numpy as np

    from neuroimagedisttraining_tpu.ops.fused_update import fused_sgd_step

    leaves, treedef = jax.tree.flatten(params)

    def like(seed, scale):
        keys = jax.random.split(jax.random.key(seed), len(leaves))
        return jax.tree.unflatten(treedef, [
            scale * jax.random.normal(k, x.shape, jnp.float32)
            for k, x in zip(keys, leaves)])

    p, g, t = like(0, 1.0), like(1, 3.0), like(2, 0.1)  # |g| > clip
    m = jax.tree.map(lambda x: (x > 0).astype(jnp.float32), like(3, 1.0))

    def step(use_pallas):
        return jax.jit(lambda p, g, t, m: fused_sgd_step(
            p, g, t, m, clip=10.0, wd=5e-4, momentum=0.9, lr=0.01,
            use_pallas=use_pallas))(p, g, t, m)

    pallas, xla = jax.tree.leaves(step(True)), jax.tree.leaves(step(False))
    for a, b in zip(pallas, xla):
        np.testing.assert_allclose(np.asarray(a), np.asarray(b),
                                   rtol=1e-6, atol=1e-7)
    return {"leaves": len(pallas) // 2,
            "bitwise_equal": all(bool(jnp.array_equal(a, b))
                                 for a, b in zip(pallas, xla))}


def phase_kernels(out: str, seed: int, held: dict) -> dict:
    from neuroimagedisttraining_tpu.ops.topk import kth_largest

    # the mask program exactly as mask_from_scores calls it (backend
    # default, no use_pallas argument)
    mask_text = kth_largest.lower(held["scores"], held["k"]) \
        .compile().as_text()
    assert kernel_in(mask_text), "mask program took the XLA branch"
    engine = hold_engine(fedavg_argv(out, "fedavg_held", seed, 3))
    compiled = compiled_round(engine)
    assert kernel_in(compiled.as_text()), \
        "fused-update round took the XLA branch"
    mem = compiled.memory_analysis()
    return {"fused_update_pallas_vs_xla": fused_update_on_device(
                engine.init_global_state().params),
            "round_program_bytes": {
                "temp": mem.temp_size_in_bytes,
                "argument": mem.argument_size_in_bytes,
                "output": mem.output_size_in_bytes},
            "asserted": f"{KERNEL_MARK} in the compiled mask program and "
                        "in the compiled fused-update round; Pallas fused "
                        "update == XLA reference (rtol 1e-6) on every "
                        "flagship leaf"}


def phase_fallbacks() -> dict:
    from neuroimagedisttraining_tpu.obs.health import fallback_block

    rows = fallback_block()["announcements"]
    return {"nidt_fallback_total": {
        f"{r['plane']}/{r['engine']}/{r['reason']}": r["count"]
        for r in rows}}


def phase_multichip(out: str, seed: int) -> dict:
    """One device, GSPMD over four, shard_map over four: same seed, same
    process; losses and the saved global model must agree.

    The fused Pallas tail rides the one-device and the shard_map runs; the
    GSPMD run trains unfused, because jax cannot partition a Mosaic kernel
    automatically — and asking for it anyway must be refused at start-up
    with the resolution named, not fail inside the first trace."""
    import jax
    import numpy as np

    from neuroimagedisttraining_tpu.utils.checkpoint import load_checkpoint

    rounds = 2
    layouts = {  # name -> (fused, extra argv)
        "one_device": (True, ["--mesh_shape", "1"]),
        "gspmd_4": (False, []),
        "client_mesh_4": (True, ["--client_mesh", "4"])}

    def argv(name, log_name):
        fused, extra = layouts[name]
        return [*fedavg_argv(out, log_name, seed, rounds, fused),
                "--lr", str(MULTICHIP_LR), *extra]

    def flat(params):
        return np.concatenate([np.ravel(np.asarray(x, np.float64))
                               for x in jax.tree.leaves(params)])

    losses, models, info = {}, {}, {}
    for name in layouts:
        ckpt = os.path.join(out, name, "ckpt")
        run_main([*argv(name, name), "--checkpoint_dir", ckpt,
                  "--checkpoint_every", str(rounds)])
        losses[name] = round_losses(out, name, rounds)
        _, state = load_checkpoint(ckpt)
        models[name] = flat(state["params"])
        shutil.rmtree(ckpt)
        gc.collect()

    # the federation really lives on four devices, and the compiled round
    # really talks across them
    for name in ("gspmd_4", "client_mesh_4"):
        engine = hold_engine(argv(name, name + "_held"))
        init = flat(engine.init_global_state().params)  # same seed: the
        placed = len(engine.data.X_train.sharding.device_set)  # runs' own
        assert placed == 4, f"{name}: X_train on {placed} device(s)"
        text = compiled_round(engine).as_text()
        found = [c for c in COLLECTIVES if c in text]
        assert found, f"{name}: no cross-device collective in the round"
        assert kernel_in(text) == layouts[name][0], name
        info[name] = {"x_train_devices": placed, "collectives": found,
                      KERNEL_MARK: layouts[name][0]}
        del engine
        gc.collect()
    fused_gspmd_is_refused(fedavg_argv(out, "gspmd_4_fused", seed, rounds))
    info["mask_on_4_devices"] = mask_on_the_mesh(state["params"])

    # agreement, measured on the update (model - init): the parameters'
    # own norm is mostly the initialisation and would hide a difference
    ref_update = models["one_device"] - init
    for name in ("gspmd_4", "client_mesh_4"):
        update = models[name] - init
        info[name].update(
            loss_max_rel_diff=float(np.max(np.abs(
                np.array(losses[name]) / np.array(losses["one_device"])
                - 1))),
            update_rel_l2_diff=float(np.linalg.norm(update - ref_update)
                                     / np.linalg.norm(ref_update)),
            update_cosine=float(update @ ref_update / np.linalg.norm(update)
                                / np.linalg.norm(ref_update)))
    emit(phase="multichip_measured", round_losses=losses, **info)
    for name in ("gspmd_4", "client_mesh_4"):
        assert info[name]["loss_max_rel_diff"] <= LOSS_RTOL, name
        assert info[name]["update_rel_l2_diff"] <= UPDATE_REL_L2, name
        assert info[name]["update_cosine"] >= UPDATE_COSINE, name
    return {"asserted": f"every round's loss agrees (rtol {LOSS_RTOL}) and "
                        "the saved global model's update agrees (rel L2 <= "
                        f"{UPDATE_REL_L2}, cosine >= {UPDATE_COSINE}) "
                        "across one device, GSPMD x4 and --client_mesh 4; "
                        "X_train on 4 devices; a collective in each "
                        f"compiled 4-device round; {KERNEL_MARK} in the "
                        "shard_map round; fused GSPMD refused at start-up; "
                        "global top-k mask built from scores replicated "
                        "over the mesh"}


def fused_gspmd_is_refused(argv: list[str]) -> None:
    try:
        hold_engine(argv)
    except ValueError as e:
        assert "--client_mesh 4" in str(e), e
    else:
        raise AssertionError("--fused_update on the GSPMD mesh was not "
                             "refused at start-up")


def mask_on_the_mesh(params) -> dict:
    """The SalientGrads mask pipeline on a score tree (shaped like the
    model's parameters) that lives on all four devices, as a multi-chip
    phase 1 hands it over: the Pallas top-k must run on one device, and
    the mask must come back at the density."""
    import jax
    import jax.numpy as jnp
    from jax.sharding import NamedSharding, PartitionSpec

    from neuroimagedisttraining_tpu.ops import snip as snip_ops
    from neuroimagedisttraining_tpu.parallel.mesh import make_mesh

    leaves, treedef = jax.tree.flatten(params)
    keys = jax.random.split(jax.random.key(7), len(leaves))
    scores = jax.device_put(
        jax.tree.unflatten(treedef, [jax.random.uniform(k, x.shape)
                                     for k, x in zip(keys, leaves)]),
        NamedSharding(make_mesh(), PartitionSpec()))
    flat = snip_ops.flat_weight_scores(scores)
    assert len(flat.sharding.device_set) == 4, flat.sharding
    masks, thr = snip_ops.mask_from_scores(scores, keep_ratio=0.5)
    kept = snip_ops.flat_weight_scores(masks)
    density = float(jnp.mean(kept))
    assert 0.5 <= density <= 0.5 + 1e-3, density
    return {"scores": int(flat.size), "density": density,
            "threshold": float(thr)}


def main(argv: list[str] | None = None) -> int:
    ap = argparse.ArgumentParser(description=__doc__.split("\n\n")[0])
    ap.add_argument("--chips", type=int, default=1, choices=(1, 4),
                    help="4 = the multi-chip comparison and nothing else")
    ap.add_argument("--seed", type=int, default=1024)
    ap.add_argument("--out", default=os.path.join(
        os.path.dirname(os.path.abspath(__file__)), "chiprun_out", "smoke"),
        help="scratch directory (logs, HDF5 cohort, checkpoints); "
             "emptied first")
    args = ap.parse_args(argv)

    clock = CompileClock()  # imports JAX: this process now holds the chip
    device = run_phase("device", clock, phase_device, args.chips)
    shutil.rmtree(args.out, ignore_errors=True)
    os.makedirs(args.out)
    if args.chips == 4:
        run_phase("multichip", clock, phase_multichip, args.out, args.seed)
    else:
        held: dict = {}
        run_phase("fedavg", clock, phase_fedavg, args.out, args.seed)
        run_phase("salientgrads", clock, phase_salientgrads, args.out,
                  args.seed, held)
        run_phase("streaming", clock, phase_streaming, args.out, args.seed)
        run_phase("kernels", clock, phase_kernels, args.out, args.seed,
                  held)
    run_phase("fallbacks", clock, phase_fallbacks)
    emit(ok=True, device={"platform": device["platform"],
                          "kind": device["kind"],
                          "count": device["count"]})
    return 0


if __name__ == "__main__":
    sys.exit(main())
