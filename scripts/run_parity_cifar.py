"""End-to-end test-metric parity: this framework vs a minimal torch
reference loop (BASELINE.json "test-metric parity" clause; VERDICT r2
next-step #3).

Both sides train federated averaging on the SAME CIFAR-shaped cohort with
the SAME n_cls partition, the SAME initial weights (converted from the flax
init), and the same optimizer semantics (SGD momentum 0.9, wd 5e-4, global
grad-norm clip 10, per-round lr decay — my_model_trainer.py:209,224-225):

- framework side: the shipped FedAvgEngine round program (one jitted SPMD
  program per round);
- torch side: an independent reimplementation of the reference's round loop
  semantics (fedavg_api.py:40-117: sample -> per-client local epochs from
  the global model -> sample-count-weighted average), written against
  torch.nn like the reference's trainers. It is NOT a copy of the reference
  (no HDF5, no CUDA, argparse-free); file:line citations mark which
  semantics each block mirrors.

Both sides walk a fresh per-epoch shuffle of each client shard in
batch-size strides (reference DataLoader semantics, my_model_trainer.py:213
— the framework's default batch_order="shuffle" since round 4; the exact
scan-vs-torch step parity given one permutation is pinned by
tests/test_torch_parity.py::test_local_train_shuffle_matches_torch_epoch_walk).
The two runs draw different permutations (independent RNG streams), so the
comparison is statistical: same semantics, same expected curve, small
tolerance on the converged level.

CIFAR-10 itself cannot be downloaded in this environment (zero egress), so
the cohort is the package's class-separable synthetic CIFAR-shaped dataset
(data/vision.py synthetic_vision_cohort) — the comparison exercises the
full public CIFAR code path (same loaders, partitioners, model) with both
frameworks consuming identical arrays.

Usage:  python scripts/run_parity_cifar.py [--rounds 25] [--out PARITY]
Emits:  PARITY.json (curves + verdict) and prints a summary table.
"""

from __future__ import annotations

import argparse
import json
import os
import sys
import time

import numpy as np

sys.path.insert(0, os.path.dirname(os.path.dirname(os.path.abspath(__file__))))

# the parity claim is about f32 math, so pin JAX to the CPU backend before
# any backend touch (TPU matmuls default to bf16-reduced precision, which
# is exactly the class of difference this experiment must NOT contain)
from neuroimagedisttraining_tpu.parallel.mesh import provision_virtual_devices  # noqa: E402

provision_virtual_devices(1)

# ---------------------------------------------------------------- config

DEF = dict(
    num_train=2000, num_test=500, hw=32, data_seed=3,
    clients=10, alpha=2, partition="n_cls", seed=1024,
    lr=0.01, lr_decay=0.998, wd=5e-4, momentum=0.9,
    batch_size=32, epochs=1, rounds=40,  # protocol round cap
    tolerance=0.05,   # |final mean-over-clients acc delta| bound
)


def build_cohort(p):
    from neuroimagedisttraining_tpu.data import partition as P
    from neuroimagedisttraining_tpu.data.vision import (
        proportional_test_split, synthetic_vision_cohort, vision_partition,
    )

    Xtr, ytr, Xte, yte = synthetic_vision_cohort(
        num_train=p["num_train"], num_test=p["num_test"], hw=p["hw"],
        seed=p["data_seed"])
    train_map = vision_partition(ytr, p["clients"], p["alpha"],
                                 p["partition"], seed=p["seed"],
                                 num_classes=10)
    stats = P.record_data_stats(ytr, train_map)
    test_map = proportional_test_split(yte, stats, p["clients"],
                                       seed=p["seed"], num_classes=10)
    return Xtr, ytr, Xte, yte, train_map, test_map


# ---------------------------------------------------------------- framework side

def run_framework(p, Xtr, ytr, Xte, yte, train_map, test_map, tmp="/tmp"):
    from neuroimagedisttraining_tpu.config import (
        DataConfig, ExperimentConfig, FedConfig, OptimConfig, SparsityConfig,
    )
    from neuroimagedisttraining_tpu.core.trainer import LocalTrainer
    from neuroimagedisttraining_tpu.data.federate import build_federated_data
    from neuroimagedisttraining_tpu.engines import create_engine
    from neuroimagedisttraining_tpu.models import create_model
    from neuroimagedisttraining_tpu.utils.logging import ExperimentLogger

    algo = p.get("algorithm", "fedavg")
    model_name = p.get("model", "cnn_cifar10")
    cfg = ExperimentConfig(
        model=model_name, num_classes=10, algorithm=algo,
        seed=p["seed"], tag="parity",
        data=DataConfig(dataset="synthetic_vision",
                        partition_method=p["partition"],
                        partition_alpha=p["alpha"]),
        optim=OptimConfig(lr=p["lr"], lr_decay=p["lr_decay"], wd=p["wd"],
                          momentum=p["momentum"],
                          batch_size=p["batch_size"], epochs=p["epochs"]),
        fed=FedConfig(client_num_in_total=p["clients"], frac=1.0,
                      comm_round=p["rounds"], frequency_of_the_test=1),
        sparsity=SparsityConfig(
            dense_ratio=p.get("dense_ratio", 0.5),
            itersnip_iterations=p.get("itersnip_iterations", 1)),
        log_dir=tmp)
    fed = build_federated_data(Xtr, ytr, train_map, test_map, mesh=None,
                               X_eval=Xte, y_eval=yte)
    trainer = LocalTrainer(create_model(model_name, num_classes=10),
                           cfg.optim, num_classes=10)
    log = ExperimentLogger(tmp, "synthetic_vision", cfg.identity(),
                           console=False)
    engine = create_engine(algo, cfg, fed, trainer, mesh=None,
                           logger=log)
    init_params = engine.init_global_state()  # same seed the run re-inits with
    t0 = time.time()
    res = engine.train()
    curve = [{"round": h["round"], "acc": h["acc"],
              "acc_pooled": h["acc_pooled"], "loss": h["loss"]}
             for h in res["history"]]
    return init_params, curve, time.time() - t0, res


# ---------------------------------------------------------------- torch side

def _flax_to_torch_state(params):
    """Convert the flax CNNCifar init into a torch state dict.

    Layout notes: flax Conv kernels are HWIO -> torch OIHW; flax Dense
    kernels are (in, out) -> torch (out, in); fc1 consumes the flattened
    conv feature map, which flax flattens H,W,C-major (models/
    vision2d.py:83) but torch flattens C,H,W-major, so fc1's input rows
    are permuted accordingly."""
    import torch

    p = {k: np.asarray(v) for k, v in {
        "conv1.k": params["conv1"]["kernel"],
        "conv1.b": params["conv1"]["bias"],
        "conv2.k": params["conv2"]["kernel"],
        "conv2.b": params["conv2"]["bias"],
        "fc1.k": params["fc1"]["kernel"],
        "fc1.b": params["fc1"]["bias"],
        "fc2.k": params["fc2"]["kernel"],
        "fc2.b": params["fc2"]["bias"],
        "fc3.k": params["fc3"]["kernel"],
        "fc3.b": params["fc3"]["bias"],
    }.items()}
    # fc1 rows: flax order (h, w, c) -> torch order (c, h, w)
    fc1 = p["fc1.k"].reshape(5, 5, 64, 384).transpose(2, 0, 1, 3)
    fc1 = fc1.reshape(5 * 5 * 64, 384)
    sd = {
        "conv1.weight": p["conv1.k"].transpose(3, 2, 0, 1),
        "conv1.bias": p["conv1.b"],
        "conv2.weight": p["conv2.k"].transpose(3, 2, 0, 1),
        "conv2.bias": p["conv2.b"],
        "fc1.weight": fc1.T, "fc1.bias": p["fc1.b"],
        "fc2.weight": p["fc2.k"].T, "fc2.bias": p["fc2.b"],
        "fc3.weight": p["fc3.k"].T, "fc3.bias": p["fc3.b"],
    }
    return {k: torch.tensor(np.ascontiguousarray(v), dtype=torch.float32)
            for k, v in sd.items()}


def _flax_to_torch_state_bn(init_state):
    """CNNCifarBN init (params + batch_stats) -> torch state dict. Same
    layout transposes as ``_flax_to_torch_state``; BN scale/bias map to
    weight/bias and batch_stats mean/var to running_mean/running_var."""
    import torch

    params, bstats = init_state.params, init_state.batch_stats
    base = _flax_to_torch_state(params)
    for i in (1, 2):
        bn = params[f"bn{i}"]
        st = bstats[f"bn{i}"]
        base[f"bn{i}.weight"] = torch.tensor(np.asarray(bn["scale"]))
        base[f"bn{i}.bias"] = torch.tensor(np.asarray(bn["bias"]))
        base[f"bn{i}.running_mean"] = torch.tensor(np.asarray(st["mean"]))
        base[f"bn{i}.running_var"] = torch.tensor(np.asarray(st["var"]))
        base[f"bn{i}.num_batches_tracked"] = torch.tensor(0,
                                                          dtype=torch.int64)
    return base


def _torch_cnn_cifar_bn():
    """Torch twin of the flax CNNCifarBN (models/vision2d.py) with
    torch BatchNorm2d defaults — the reference's BN-in-FL semantics:
    running stats live in the state dict and are averaged by the
    state-dict FedAvg like every other key (fedavg_api.py:102-117).
    Shared by the parity run and the partial-batch probe so the two can
    never diverge."""
    import torch
    import torch.nn as nn

    class CNNCifarBN(nn.Module):
        def __init__(self):
            super().__init__()
            self.conv1 = nn.Conv2d(3, 64, 5)
            self.bn1 = nn.BatchNorm2d(64)
            self.conv2 = nn.Conv2d(64, 64, 5)
            self.bn2 = nn.BatchNorm2d(64)
            self.fc1 = nn.Linear(5 * 5 * 64, 384)
            self.fc2 = nn.Linear(384, 192)
            self.fc3 = nn.Linear(192, 10)

        def forward(self, x):
            pool = nn.functional.max_pool2d
            x = pool(torch.relu(self.bn1(self.conv1(x))), 2, 2)
            x = pool(torch.relu(self.bn2(self.conv2(x))), 2, 2)
            x = x.reshape(x.shape[0], -1)
            x = torch.relu(self.fc1(x))
            x = torch.relu(self.fc2(x))
            return self.fc3(x)

    return CNNCifarBN()


_MASKABLE = ("conv1.weight", "conv2.weight", "fc1.weight", "fc2.weight",
             "fc3.weight")


def _torch_fwd_masked(sd, masks, x):
    """CNNCifar forward from a raw state dict with multiplicative weight
    masks — the functional equivalent of the reference's monkey-patched
    ``w * weight_mask`` forwards (snip.py:9-16)."""
    import torch
    import torch.nn.functional as F

    h = F.max_pool2d(torch.relu(F.conv2d(
        x, sd["conv1.weight"] * masks["conv1.weight"], sd["conv1.bias"])), 2, 2)
    h = F.max_pool2d(torch.relu(F.conv2d(
        h, sd["conv2.weight"] * masks["conv2.weight"], sd["conv2.bias"])), 2, 2)
    h = h.reshape(h.shape[0], -1)
    h = torch.relu(F.linear(
        h, sd["fc1.weight"] * masks["fc1.weight"], sd["fc1.bias"]))
    h = torch.relu(F.linear(
        h, sd["fc2.weight"] * masks["fc2.weight"], sd["fc2.bias"]))
    return F.linear(h, sd["fc3.weight"] * masks["fc3.weight"], sd["fc3.bias"])


def torch_snip_masks(p, init_sd, Xtr, ytr, train_map):
    """Independent torch SNIP phase 1 (snip.py:21-116 + client.py:30-53):
    per-client IterSNIP |dL/d weight_mask| at mask=1, client mean, concat +
    normalize by the global sum, keep the top dense_ratio fraction."""
    import torch
    import torch.nn as nn

    X_t = torch.tensor(Xtr.transpose(0, 3, 1, 2))
    y_t = torch.tensor(ytr.astype(np.int64))
    loss_fn = nn.CrossEntropyLoss()
    sd = {k: v.clone() for k, v in init_sd.items()}
    I = p.get("itersnip_iterations", 1)
    client_means = []
    for c in range(p["clients"]):
        idx = np.asarray(train_map[c])
        if len(idx) == 0:
            continue
        rs = np.random.RandomState(p["seed"] * 977 + c)
        acc = {k: torch.zeros_like(sd[k]) for k in _MASKABLE}
        for _ in range(I):
            # reference IterSNIP draws the first batch of a fresh shuffle
            # per iteration (client.py:46-49 next(iter(loader)))
            b = rs.permutation(idx)[: p["batch_size"]]
            masks = {k: torch.ones_like(sd[k], requires_grad=True)
                     for k in _MASKABLE}
            loss = loss_fn(_torch_fwd_masked(sd, masks, X_t[b]), y_t[b])
            loss.backward()
            for k in _MASKABLE:
                acc[k] += masks[k].grad.abs()
        client_means.append({k: v / I for k, v in acc.items()})
    # server mean over clients (snip.py:120-140)
    mean = {k: sum(cm[k] for cm in client_means) / len(client_means)
            for k in _MASKABLE}
    # global top-k mask (snip.py:80-116)
    all_scores = torch.cat([mean[k].flatten() for k in _MASKABLE])
    norm = torch.sum(all_scores)
    k_keep = int(len(all_scores) * p.get("dense_ratio", 0.5))
    thr = torch.topk(all_scores / norm, k_keep, sorted=True)[0][-1]
    return {k: ((mean[k] / norm) >= thr).float() for k in _MASKABLE}


def run_torch(p, init_params, Xtr, ytr, Xte, yte, train_map, test_map,
              masks=None):
    """Reference-semantics FedAvg loop in torch (fedavg_api.py:40-117);
    with ``masks``, the SalientGrads masked variant (post-step
    ``param *= mask`` per batch, my_model_trainer.py:228-231)."""
    import torch
    import torch.nn as nn

    torch.manual_seed(p["seed"])
    torch.set_num_threads(max(1, __import__("os").cpu_count() or 1))

    class CNNCifar(nn.Module):
        # layer parity with the reference cnn_cifar10.py:12-52 and the
        # package's flax CNNCifar (models/vision2d.py:67-87)
        def __init__(self):
            super().__init__()
            self.conv1 = nn.Conv2d(3, 64, 5)
            self.conv2 = nn.Conv2d(64, 64, 5)
            self.fc1 = nn.Linear(5 * 5 * 64, 384)
            self.fc2 = nn.Linear(384, 192)
            self.fc3 = nn.Linear(192, 10)

        def forward(self, x):
            pool = nn.functional.max_pool2d
            x = pool(torch.relu(self.conv1(x)), 2, 2)
            x = pool(torch.relu(self.conv2(x)), 2, 2)
            x = x.reshape(x.shape[0], -1)
            x = torch.relu(self.fc1(x))
            x = torch.relu(self.fc2(x))
            return self.fc3(x)

    use_bn = p.get("model", "cnn_cifar10") == "cnn_cifar10_bn"
    model = _torch_cnn_cifar_bn() if use_bn else CNNCifar()
    model.load_state_dict(_flax_to_torch_state_bn(init_params) if use_bn
                          else _flax_to_torch_state(init_params.params))
    global_sd = {k: v.clone() for k, v in model.state_dict().items()}

    # init-conversion check: torch and flax produce the same logits on a
    # probe batch, so the two runs truly start from the SAME function
    from neuroimagedisttraining_tpu.models import create_model
    import jax.numpy as jnp

    probe = Xtr[:8]
    fx_vars = {"params": init_params.params}
    if use_bn:
        fx_vars["batch_stats"] = init_params.batch_stats
    fx = create_model(p.get("model", "cnn_cifar10"), num_classes=10).apply(
        fx_vars, jnp.asarray(probe), train=False)
    model.eval()
    with torch.no_grad():
        th = model(torch.tensor(probe.transpose(0, 3, 1, 2))).numpy()
    np.testing.assert_allclose(th, np.asarray(fx), atol=2e-4)

    X_t = torch.tensor(Xtr.transpose(0, 3, 1, 2))  # NHWC -> NCHW
    y_t = torch.tensor(ytr.astype(np.int64))
    Xe_t = torch.tensor(Xte.transpose(0, 3, 1, 2))
    ye_t = torch.tensor(yte.astype(np.int64))
    loss_fn = nn.CrossEntropyLoss()

    def eval_mean_acc(sd):
        model.load_state_dict(sd)
        model.eval()
        accs, correct_all, total_all = [], 0, 0
        with torch.no_grad():
            for c in range(p["clients"]):
                idx = np.asarray(test_map[c])
                if len(idx) == 0:
                    continue
                logits = model(Xe_t[idx])
                pred = logits.argmax(dim=1)
                correct = int((pred == ye_t[idx]).sum())
                accs.append(correct / len(idx))
                correct_all += correct
                total_all += len(idx)
        return float(np.mean(accs)), correct_all / max(total_all, 1)

    curve = []
    t0 = time.time()
    for round_idx in range(p["rounds"]):
        lr = p["lr"] * p["lr_decay"] ** round_idx  # my_model_trainer.py:209
        # client sampling parity (fedavg_api.py:92-100); frac=1 -> all
        sampled = np.arange(p["clients"])
        updates, weights = [], []
        for c in sampled:
            idx = np.asarray(train_map[c])
            if len(idx) == 0:
                continue
            model.load_state_dict(global_sd)  # set_model_params deepcopy
            model.train()
            opt = torch.optim.SGD(model.parameters(), lr=lr,
                                  momentum=p["momentum"],
                                  weight_decay=p["wd"])
            rs = np.random.RandomState(p["seed"] * 131 + round_idx * 17 + c)
            for _ in range(p["epochs"]):
                order = rs.permutation(idx)
                for s in range(0, len(order), p["batch_size"]):
                    b = order[s: s + p["batch_size"]]
                    opt.zero_grad()
                    loss = loss_fn(model(X_t[b]), y_t[b])
                    loss.backward()
                    # clip_grad_norm(10) parity, my_model_trainer.py:224
                    torch.nn.utils.clip_grad_norm_(model.parameters(), 10.0)
                    opt.step()
                    if masks is not None:
                        # post-step re-mask per batch (my_model_trainer.py
                        # :228-231 under args.snip_mask)
                        with torch.no_grad():
                            for name, param in model.named_parameters():
                                if name in masks:
                                    param.data *= masks[name]
            updates.append({k: v.detach().clone()
                            for k, v in model.state_dict().items()})
            weights.append(float(len(idx)))
        # sample-weighted FedAvg (fedavg_api.py:102-117) — EVERY state
        # dict key, BN running stats included (the reference's implicit
        # BN-in-FL semantics); integer buffers (num_batches_tracked) are
        # cast back like load_state_dict's copy_ would
        w = np.asarray(weights) / np.sum(weights)
        global_sd = {
            k: sum(wi * upd[k].float() for wi, upd in
                   zip(w, updates)).to(global_sd[k].dtype)
            for k in global_sd}
        acc, pooled = eval_mean_acc(global_sd)
        curve.append({"round": round_idx, "acc": acc, "acc_pooled": pooled})
    return curve, time.time() - t0


# ---------------------------------------------------------------- masks

def _flax_masks_to_torch(masks):
    """Framework mask pytree -> torch weight-name dict, with the same layout
    transposes as ``_flax_to_torch_state`` (HWIO->OIHW; fc1 rows hwc->chw)."""
    m = {k: np.asarray(masks[k]["kernel"]) for k in
         ("conv1", "conv2", "fc1", "fc2", "fc3")}
    fc1 = m["fc1"].reshape(5, 5, 64, 384).transpose(2, 0, 1, 3)
    return {
        "conv1.weight": m["conv1"].transpose(3, 2, 0, 1),
        "conv2.weight": m["conv2"].transpose(3, 2, 0, 1),
        "fc1.weight": fc1.reshape(5 * 5 * 64, 384).T,
        "fc2.weight": m["fc2"].T,
        "fc3.weight": m["fc3"].T,
    }


def compare_masks(fw_masks, th_masks):
    """Per-layer + overall agreement and densities of the two masks."""
    per_layer, agree_n, total_n, fw_nnz, th_nnz = {}, 0, 0, 0, 0
    for k in _MASKABLE:
        fw = np.asarray(fw_masks[k]) > 0.5
        th = np.asarray(th_masks[k].numpy()) > 0.5
        per_layer[k] = {
            "agreement": float(np.mean(fw == th)),
            "density_framework": float(fw.mean()),
            "density_torch": float(th.mean()),
        }
        agree_n += int(np.sum(fw == th))
        total_n += fw.size
        fw_nnz += int(fw.sum())
        th_nnz += int(th.sum())
    return {
        "overall_agreement": agree_n / total_n,
        "density_framework": fw_nnz / total_n,
        "density_torch": th_nnz / total_n,
        "per_layer": per_layer,
    }


# ------------------------------------------------------- parity protocol

def protocol_verdict(jx_curve, th_curve, tolerance, eps=0.06, k=10):
    """PRE-COMMITTED stopping + comparison rule (VERDICT r4 weak #5 /
    next-step #8): the stop round is the FIRST round >= 2k at which BOTH
    curves' trailing-k std < eps — a plateau — or the run's round cap
    (--rounds) if no round qualifies. The verdict compares the trailing-k
    means AT THE STOP ROUND against the tolerance. Every seed gets the
    same rule; there is no per-seed window choice. (eps=0.06 was fixed
    from the round-4 artifacts BEFORE any round-5 run: converged curves
    on this cohort oscillate with trailing-10 std 0.04-0.05, mid-climb
    curves read 0.1-0.17.)"""
    fw = np.array([r["acc"] for r in jx_curve])
    th = np.array([r["acc"] for r in th_curve])
    R = len(fw)
    k = min(k, R)  # short (smoke) runs: window = whole curve, labeled so
    stop, plateaued = R, False
    for r in range(2 * k, R + 1):
        if fw[r - k:r].std() < eps and th[r - k:r].std() < eps:
            stop, plateaued = r, True
            break
    m_fw = float(fw[stop - k:stop].mean())
    m_th = float(th[stop - k:stop].mean())
    delta = abs(m_fw - m_th)
    return {
        "protocol": {"eps": eps, "k": k, "rule":
                     "first round with both trailing-k stds < eps, else "
                     "the round cap; compare trailing-k means there"},
        "stop_round": stop, "plateaued": plateaued,
        "trailing_fw": m_fw, "trailing_th": m_th, "delta": delta,
        "std_fw_at_stop": float(fw[stop - k:stop].std()),
        "std_th_at_stop": float(th[stop - k:stop].std()),
        "parity": bool(delta <= tolerance),
    }


# ------------------------------------------- BN partial-batch probe

def bn_partial_batch_probe(p, init_params, Xtr, ytr, train_map):
    """Measured size of the documented partial-batch BN deviation
    (core/trainer.py: the static-shape scan's final batch wraps filler
    rows that are VISIBLE to BN batch statistics, where torch's
    DataLoader would see a genuinely smaller batch). One client, one
    epoch, THE SAME permutation on both sides — the only semantic
    differences left are the BN batch-stat population (wrapped rows vs
    smaller batch) and flax's biased vs torch's unbiased running-var
    update. Returns max-abs deltas of the post-epoch BN running stats and
    params."""
    import torch
    import jax
    import jax.numpy as jnp

    from neuroimagedisttraining_tpu.config import OptimConfig
    from neuroimagedisttraining_tpu.core.trainer import (
        LocalTrainer, epoch_permutations, shuffle_batch_indices,
    )
    from neuroimagedisttraining_tpu.models import create_model

    idx = np.asarray(train_map[0])
    n = len(idx)
    b = p["batch_size"]
    nmax = max(len(np.asarray(v)) for v in train_map.values())
    X = np.zeros((nmax,) + Xtr.shape[1:], np.float32)
    y = np.zeros((nmax,), np.int32)
    X[:n], y[:n] = Xtr[idx], ytr[idx]

    cfg = OptimConfig(lr=p["lr"], momentum=p["momentum"], wd=p["wd"],
                      grad_clip=10.0, batch_size=b, epochs=1,
                      batch_order="shuffle")
    trainer = LocalTrainer(create_model("cnn_cifar10_bn", num_classes=10),
                           cfg, num_classes=10)
    cs = init_params
    new_cs, _ = trainer.local_train(cs, jnp.asarray(X), jnp.asarray(y),
                                    jnp.int32(n), jnp.float32(p["lr"]),
                                    epochs=1, batch_size=b,
                                    max_samples=nmax)

    # reconstruct the trainer's own permutation and walk it in torch
    prng = jax.random.split(cs.rng)[1]
    perms = epoch_permutations(prng, 1, nmax, n)
    steps = -(-nmax // b)
    sd = _flax_to_torch_state_bn(cs)
    model = _torch_cnn_cifar_bn()
    model.load_state_dict(sd)
    model.train()
    opt = torch.optim.SGD(model.parameters(), lr=p["lr"],
                          momentum=p["momentum"], weight_decay=p["wd"])
    X_t = torch.tensor(X.transpose(0, 3, 1, 2))
    y_t = torch.tensor(y.astype(np.int64))
    loss_fn = torch.nn.CrossEntropyLoss()
    for t in range(steps):
        bidx, wmask = shuffle_batch_indices(perms, t, steps, b, n)
        keep = np.asarray(bidx)[np.asarray(wmask) > 0]
        if len(keep) == 0:
            continue  # masked no-op step beyond the client's quota
        opt.zero_grad()
        loss = loss_fn(model(X_t[keep]), y_t[keep])
        loss.backward()
        torch.nn.utils.clip_grad_norm_(model.parameters(), 10.0)
        opt.step()
    out_sd = model.state_dict()

    def _d(a, bt):
        return float(np.abs(np.asarray(a) - bt.detach().numpy()).max())

    bs = new_cs.batch_stats
    return {
        "client": 0, "n": n, "batch_size": b, "nmax_pad": nmax,
        "partial_batch_rows": int(n % b) if n % b else b,
        "running_mean_max_abs_delta": max(
            _d(bs["bn1"]["mean"], out_sd["bn1.running_mean"]),
            _d(bs["bn2"]["mean"], out_sd["bn2.running_mean"])),
        "running_var_max_abs_delta": max(
            _d(bs["bn1"]["var"], out_sd["bn1.running_var"]),
            _d(bs["bn2"]["var"], out_sd["bn2.running_var"])),
        "param_max_abs_delta": max(
            _d(new_cs.params["conv1"]["kernel"],
               out_sd["conv1.weight"].permute(2, 3, 1, 0)),
            _d(new_cs.params["fc3"]["kernel"], out_sd["fc3.weight"].T)),
    }


# ---------------------------------------------------------------- main

def main():
    ap = argparse.ArgumentParser()
    ap.add_argument("--rounds", type=int, default=DEF["rounds"])
    ap.add_argument("--algorithm", type=str, default="fedavg",
                    choices=["fedavg", "salientgrads"])
    ap.add_argument("--seed", type=int, default=DEF["seed"])
    ap.add_argument("--itersnip_iterations", type=int, default=10,
                    help="SNIP batches per client (salientgrads mode); "
                         "more batches -> more stable scores -> higher "
                         "expected cross-implementation mask agreement")
    ap.add_argument("--model", type=str, default="cnn_cifar10",
                    choices=["cnn_cifar10", "cnn_cifar10_bn"],
                    help="cnn_cifar10_bn runs the BatchNorm federated-"
                         "parity experiment (VERDICT r4 missing #2)")
    ap.add_argument("--num_train", type=int, default=DEF["num_train"],
                    help="cohort size override (smoke tests)")
    ap.add_argument("--num_test", type=int, default=DEF["num_test"])
    ap.add_argument("--out", type=str, default="PARITY")
    args = ap.parse_args()
    if args.model == "cnn_cifar10_bn" and args.algorithm != "fedavg":
        ap.error("--model cnn_cifar10_bn currently pairs with fedavg "
                 "(the BN parity experiment)")
    p = dict(DEF, rounds=args.rounds, algorithm=args.algorithm,
             seed=args.seed, itersnip_iterations=args.itersnip_iterations,
             dense_ratio=0.5, model=args.model,
             num_train=args.num_train, num_test=args.num_test)

    Xtr, ytr, Xte, yte, train_map, test_map = build_cohort(p)
    print(f"cohort: {len(ytr)} train / {len(yte)} test, "
          f"{p['clients']} clients (n_cls alpha={p['alpha']}), "
          f"algorithm={p['algorithm']}, seed={p['seed']}")

    init_params, jx_curve, jx_s, res = run_framework(
        p, Xtr, ytr, Xte, yte, train_map, test_map)
    print(f"framework run: {jx_s:.1f}s, final acc={jx_curve[-1]['acc']:.4f}")

    bn_probe = None
    if p["model"] == "cnn_cifar10_bn":
        bn_probe = bn_partial_batch_probe(p, init_params, Xtr, ytr,
                                          train_map)
        print(f"BN partial-batch probe: {json.dumps(bn_probe)}")

    mask_report = None
    th_masks = None
    if p["algorithm"] == "salientgrads":
        init_sd = _flax_to_torch_state(init_params.params)
        th_masks = torch_snip_masks(p, init_sd, Xtr, ytr, train_map)
        mask_report = compare_masks(_flax_masks_to_torch(res["masks"]),
                                    th_masks)
        print(f"mask agreement: {mask_report['overall_agreement']:.4f} "
              f"(density fw {mask_report['density_framework']:.4f} / "
              f"torch {mask_report['density_torch']:.4f})")

    th_curve, th_s = run_torch(p, init_params, Xtr, ytr, Xte, yte,
                               train_map, test_map, masks=th_masks)
    print(f"torch run:     {th_s:.1f}s, final acc={th_curve[-1]['acc']:.4f}")

    # Verdict metric: TRAILING-5-ROUND mean accuracy. Both learners
    # oscillate +-0.1 between adjacent rounds at this lr/momentum on the
    # small cohort (visible in both curves), so a single final-round
    # snapshot is dominated by that noise; the trailing mean is the
    # converged-level comparison. The raw final-round delta is reported
    # alongside for transparency.
    k = min(5, len(jx_curve))
    trail_fw = float(np.mean([r["acc"] for r in jx_curve[-k:]]))
    trail_th = float(np.mean([r["acc"] for r in th_curve[-k:]]))
    delta = abs(trail_fw - trail_th)
    ok = delta <= p["tolerance"]
    # trailing-10 rides along for noise diagnosis: when both learners
    # oscillate +-0.1-0.3 mid-convergence (hard partitions), the 5-round
    # window can catch the two sides at opposite phases; the 10-round
    # window says whether a trailing-5 excursion is phase noise
    k10 = min(10, len(jx_curve))
    trail10_fw = float(np.mean([r["acc"] for r in jx_curve[-k10:]]))
    trail10_th = float(np.mean([r["acc"] for r in th_curve[-k10:]]))
    # the PRE-COMMITTED protocol verdict (plateau-or-cap stop, trailing-10
    # comparison) — the headline verdict; trailing-5/10-at-final-round
    # ride along for continuity with the round-4 artifacts
    proto = protocol_verdict(jx_curve, th_curve, p["tolerance"])
    result = {
        "config": p, "mask_report": mask_report,
        "framework_curve": jx_curve, "torch_curve": th_curve,
        "final_acc_framework": jx_curve[-1]["acc"],
        "final_acc_torch": th_curve[-1]["acc"],
        "final_round_delta": abs(jx_curve[-1]["acc"] - th_curve[-1]["acc"]),
        "trailing5_acc_framework": trail_fw,
        "trailing5_acc_torch": trail_th,
        "trailing5_delta": delta,
        "trailing10_acc_framework": trail10_fw,
        "trailing10_acc_torch": trail10_th,
        "trailing10_delta": abs(trail10_fw - trail10_th),
        "tolerance": p["tolerance"], "parity": ok,
        "protocol_verdict": proto,
        "bn_partial_batch_probe": bn_probe,
        "framework_seconds": jx_s, "torch_seconds": th_s,
    }
    with open(args.out + ".json", "w") as f:
        json.dump(result, f, indent=1)
    print(f"\nround  framework_acc  torch_acc")
    for a, b in zip(jx_curve, th_curve):
        print(f"{a['round']:5d}  {a['acc']:.4f}         {b['acc']:.4f}")
    print(f"\ntrailing-5 mean acc: framework {trail_fw:.4f} vs torch "
          f"{trail_th:.4f}; delta = {delta:.4f} "
          f"(tolerance {p['tolerance']}) "
          f"-> {'PARITY OK' if ok else 'PARITY FAIL'}")
    print(f"protocol verdict (pre-committed): stop_round="
          f"{proto['stop_round']} plateaued={proto['plateaued']} "
          f"trailing-10 {proto['trailing_fw']:.4f} vs "
          f"{proto['trailing_th']:.4f}, delta={proto['delta']:.4f} -> "
          f"{'PARITY OK' if proto['parity'] else 'PARITY FAIL'}")
    return 0 if proto["parity"] else 1


if __name__ == "__main__":
    raise SystemExit(main())
