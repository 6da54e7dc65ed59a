"""Cohort sharding tests (ISSUE 6): one dispatched program, all sites.

The contract (parallel/cohort.py, stated with the precision the
measurements force):

(a) vs the sequential C-loop (the same unbatched per-client loop in an
    unpartitioned program): a FedAvg round's training losses from
    identical state are BITWISE equal — the proof that batch selection,
    masking, weighting, every semantic choice is identical (the masked
    salientgrads round's mean loss sits exactly 1 float32 ulp off: the
    mask multiply adds a fusion seam) — and trained state
    agrees to ~1 ulp of its own magnitude (an XLA compile-context
    tiling artifact — measured, documented in parallel/cohort.py — NOT
    a semantic divergence; the SEMANTIC divergence partitioned compiles
    DO produce, the in-partition random-sort miscompile, is hoisted
    away by design and would resurface here as 1e-0-level loss
    divergence if it regressed).
(b) MESH-WIDTH INDEPENDENCE to the same ~1 ulp through different pad
    counts (21 real sites pad to 22 rows on 2 devices, 24 on 8).
(c) Round after round, each with its own deal, is ONE compiled program;
    the Byzantine attack/defense tail and the wire codec's EF stacks
    compose on the sharded path under (a)/(b).
(d) Engines/modes without a sharded round body fall back to the
    unsharded round with a logged reason; config mismatches fail loudly
    at startup.
"""

import re

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from neuroimagedisttraining_tpu.config import (
    DataConfig, ExperimentConfig, FedConfig, OptimConfig,
)
from neuroimagedisttraining_tpu.core.losses import binary_auc
from neuroimagedisttraining_tpu.core.trainer import LocalTrainer
from neuroimagedisttraining_tpu.data import partition as P
from neuroimagedisttraining_tpu.data.federate import federate_cohort
from neuroimagedisttraining_tpu.data.stream import StreamingFederation
from neuroimagedisttraining_tpu.data.synthetic import generate_synthetic_abcd
from neuroimagedisttraining_tpu.engines import create_engine
from neuroimagedisttraining_tpu.models import create_model
from neuroimagedisttraining_tpu.obs import names as obs_names
from neuroimagedisttraining_tpu.obs import trace as obs_trace
from neuroimagedisttraining_tpu.parallel import cohort
from neuroimagedisttraining_tpu.parallel.mesh import make_mesh
from neuroimagedisttraining_tpu.utils.logging import ExperimentLogger

#: bounds for the measured ~1-ulp compile-context residue between
#: partitioned and unpartitioned programs (parallel/cohort.py); relative
#: 1e-6 ≈ 8 float32 ulps of headroom on each leaf's own magnitude (BN
#: running vars sit near 1e2, params near 1e0), atol covers near-zero
#: entries — both far below any training-relevant scale
ULP_RTOL = 1e-6
ULP_ATOL = 1e-6
#: one float32 ulp of headroom for a round's MEAN loss where the
#: partitioned module orders the weighted mean's reduction differently
#: (the salientgrads round; on jax 0.9.0 also the 4-site cohort padded
#: to 8 rows, whose per-client losses were checked bitwise-equal while
#: the 4-term weighted mean sat exactly 0x1p-24 relative off)
LOSS_ULP_RTOL = 3e-7


@pytest.fixture(scope="module")
def cohort21():
    """The flagship pad case: 21 real acquisition sites (seed-picked so
    every site survives the 80/20 split), padding to 24 rows on the
    8-device mesh and 22 on a 2-device mesh."""
    return generate_synthetic_abcd(num_subjects=84, shape=(12, 14, 12),
                                   num_sites=21, seed=5)


def _engine(tmp_path, cohort_data, algorithm="fedavg", client_mesh=8,
            n_dev=None, seq=False, C=21, comm_round=2, freq=2, tag="c",
            stream=False, val_fraction=0.0, mesh=None, fused_update=False,
            **fed_kw):
    cfg = ExperimentConfig(
        model="3dcnn_tiny", num_classes=1, algorithm=algorithm,
        data=DataConfig(dataset="synthetic", partition_method="site",
                        val_fraction=val_fraction),
        optim=OptimConfig(lr=1e-3, batch_size=8, epochs=1,
                          fused_update=fused_update),
        fed=FedConfig(client_num_in_total=C, comm_round=comm_round,
                      frequency_of_the_test=freq, client_mesh=client_mesh,
                      **fed_kw),
        log_dir=str(tmp_path), tag=tag)
    if mesh is None:
        mesh = make_mesh(num_devices=n_dev)
    elif mesh is False:  # no mesh at all: the one-chip engines
        mesh = None
    trainer = LocalTrainer(create_model(cfg.model, num_classes=1),
                           cfg.optim, num_classes=1)
    log = ExperimentLogger(str(tmp_path), "synthetic", cfg.identity(),
                           console=False)
    if stream:
        train_map, test_map, _ = P.site_partition(cohort_data["site"],
                                                  seed=42)
        feed = StreamingFederation(np.asarray(cohort_data["X"]),
                                   np.asarray(cohort_data["y"]),
                                   train_map, test_map, mesh=mesh)
        eng = create_engine(algorithm, cfg, None, trainer, mesh=mesh,
                            logger=log, stream=feed)
    else:
        fed, _ = federate_cohort(cohort_data, partition_method="site",
                                 mesh=mesh, val_fraction=val_fraction)
        eng = create_engine(algorithm, cfg, fed, trainer, mesh=mesh,
                            logger=log)
    eng._donate = False
    if seq:
        # the sequential C-loop reference: same padded program shape,
        # local stage lowered as ONE unpartitioned per-client loop
        eng._cohort_sequential = True
    return eng


def _assert_trees_bitwise(a, b):
    la, lb = jax.tree.leaves(a), jax.tree.leaves(b)
    assert len(la) == len(lb)
    for x, y in zip(la, lb):
        np.testing.assert_array_equal(np.asarray(x), np.asarray(y))


def _assert_trees_ulp(a, b, rtol=ULP_RTOL, atol=ULP_ATOL):
    la, lb = jax.tree.leaves(a), jax.tree.leaves(b)
    assert len(la) == len(lb)
    for x, y in zip(la, lb):
        np.testing.assert_allclose(np.asarray(x, np.float64),
                                   np.asarray(y, np.float64),
                                   rtol=rtol, atol=atol)


def _log_text(eng) -> str:
    with open(eng.log.log_path) as f:
        return f.read()


# ---------------------------------------------------------------------------
# pad helpers (the shared rule the mesh-pad-weights lint enforces)
# ---------------------------------------------------------------------------

def test_pad_cohort_rules():
    # tiling: untouched
    ids, n = cohort.pad_cohort(np.arange(8), 8, 8, 8)
    assert n == 8 and np.array_equal(ids, np.arange(8))
    # non-tiling with a zero-sample pool: pool rows first
    ids, n = cohort.pad_cohort(np.arange(21), 21, 24, 8)
    assert n == 21 and len(ids) == 24
    assert ids[21:].tolist() == [21, 22, 23]
    # pool exhausted: repeat the last sampled id (the DUPLICATE case the
    # position mask exists for)
    ids, n = cohort.pad_cohort(np.array([0, 1, 2]), 3, 3, 2)
    assert n == 3 and ids.tolist() == [0, 1, 2, 2]
    with pytest.raises(ValueError, match="empty sampled set"):
        cohort.pad_cohort(np.array([], dtype=int), 3, 3, 2)


def test_pad_row_weights_zero_by_position():
    ns = jnp.asarray([5, 3, 7, 7], jnp.int32)  # row 3 duplicates row 2
    out = np.asarray(cohort.pad_row_weights(ns, 3))
    assert out.tolist() == [5, 3, 7, 0]  # position, not sample count


def test_cohort_map_rejects_non_tiling_and_two_level():
    mesh = make_mesh()
    with pytest.raises(ValueError, match="does not tile"):
        cohort.cohort_map(mesh, lambda x: x, jnp.zeros((21, 2)))
    mesh2 = make_mesh(shape=(2, 4))
    with pytest.raises(ValueError, match="1-D client mesh"):
        cohort.cohort_map(mesh2, lambda x: x, jnp.zeros((8, 2)))


# ---------------------------------------------------------------------------
# (b) sharded round vs the sequential C-loop (program level)
# ---------------------------------------------------------------------------

def _one_sharded_round(eng, round_idx=0, efs=None, masks=None):
    gs = eng.init_global_state()
    sampled = eng.client_sampling(round_idx)
    # the program train() dispatches: the mesh-padded set dealt to the
    # chips by step count, the deal bound as an operand
    ids, round_prog = eng._cohort_round_prog(sampled)
    n_real = len(sampled)
    rngs = eng.per_client_rngs(round_idx, ids)
    byz = eng._byz_round_plan(round_idx, sampled)
    lr = eng.round_lr(round_idx)
    if eng.name == "salientgrads":
        if masks is None:
            masks, _ = eng.generate_global_mask(gs.params,
                                                gs.batch_stats)
        per = eng.broadcast_states(gs, eng.num_clients)
        out = round_prog(
            gs.params, gs.batch_stats, per.params, per.batch_stats,
            eng.data, masks, jnp.asarray(ids), rngs, lr, byz)
        return out
    if efs is not None:
        efs = jax.tree.map(
            lambda x: jnp.zeros((n_real,) + x.shape, jnp.float32),
            {"params": gs.params, "batch_stats": gs.batch_stats})
    out = round_prog(
        gs.params, gs.batch_stats, eng.data, jnp.asarray(ids), rngs, lr,
        efs, byz)
    return out


@pytest.mark.parametrize("algorithm", [
    "fedavg",
    pytest.param("salientgrads", marks=pytest.mark.slow),  # tier-1 window (PR 7): fedavg twin stays; salientgrads keeps the 1-ulp mask pin in the slow suite
])
def test_sharded_round_vs_sequential_loop(tmp_path, cohort21, algorithm):
    """The non-tiling flagship case (21 sites -> 24 rows on 8 devices):
    per-round loss bitwise, state within the 1-ulp compile-context
    residue of the sequential C-loop. Salientgrads rounds run on ONE
    shared phase-1 mask (the mask pipelines are cross-checked in
    test_salientgrads_sharded_mask below): its own sharded scores carry
    the same 1-ulp residue, so a mask threshold from the sharded
    pipeline sits an ulp off the sequential one's — with the mask held
    fixed, the round itself is exactly as tight as FedAvg's."""
    eng_sh = _engine(tmp_path, cohort21, algorithm, tag="sh")
    eng_sq = _engine(tmp_path, cohort21, algorithm, seq=True, tag="sq")
    masks = None
    if algorithm == "salientgrads":
        gs = eng_sq.init_global_state()
        masks, _ = eng_sq.generate_global_mask(gs.params, gs.batch_stats)
    out_sh = _one_sharded_round(eng_sh, masks=masks)
    out_sq = _one_sharded_round(eng_sq, masks=masks)
    loss_i = 4 if algorithm == "salientgrads" else 2
    if algorithm == "fedavg":
        # bitwise: the semantic proof (identical batch selection/
        # masking/weighting on both paths)
        np.testing.assert_array_equal(np.asarray(out_sh[loss_i]),
                                      np.asarray(out_sq[loss_i]))
    else:
        # the per-step mask multiply adds one more fusion seam, which
        # tiles a loss reduction differently — measured at exactly 1
        # float32 ulp on this seed (0x1p-24 relative); anything larger
        # would be the miscompile class the hoist guards against
        np.testing.assert_allclose(float(out_sh[loss_i]),
                                   float(out_sq[loss_i]),
                                   rtol=LOSS_ULP_RTOL)
    _assert_trees_ulp(out_sh, out_sq)


def test_salientgrads_sharded_mask(tmp_path, cohort21):
    """Phase-1 under the sharded driver: scores carry the 1-ulp SPMD
    residue, so the top-k threshold may sit an ulp off the sequential
    pipeline's — but on this seed no score lands inside that window and
    the emitted MASKS are identical (density is pinned either way)."""
    eng_sh = _engine(tmp_path, cohort21, "salientgrads", tag="msh")
    eng_sq = _engine(tmp_path, cohort21, "salientgrads", seq=True,
                     tag="msq")
    gs = eng_sh.init_global_state()
    mk_sh, thr_sh = eng_sh.generate_global_mask(gs.params, gs.batch_stats)
    gs2 = eng_sq.init_global_state()
    mk_sq, thr_sq = eng_sq.generate_global_mask(gs2.params,
                                                gs2.batch_stats)
    np.testing.assert_allclose(float(thr_sh), float(thr_sq), rtol=1e-6)
    _assert_trees_bitwise(mk_sh, mk_sq)


def test_sharded_round_byz_defense_composes(tmp_path, synthetic_cohort):
    """Attack + sanitize + defend tail on the sharded path: the byz plan
    covers the REAL sampled set (pads sliced off before the tail)."""
    kw = dict(algorithm="fedavg", C=4, tag="byz",
              fault_spec="byz:3@0:sign_flip", defense_type="trimmed_mean",
              byz_f=1)
    out_sh = _one_sharded_round(_engine(tmp_path, synthetic_cohort, **kw))
    out_sq = _one_sharded_round(
        _engine(tmp_path, synthetic_cohort, seq=True, **kw))
    np.testing.assert_allclose(float(out_sh[2]), float(out_sq[2]),
                               rtol=LOSS_ULP_RTOL)
    _assert_trees_ulp(out_sh, out_sq)


def test_sharded_round_wire_codec_ef_composes(tmp_path, synthetic_cohort):
    """The codec roundtrip + per-client EF stacks ride the sharded round:
    EF rows are sized for the REAL sampled set and the decoded uploads /
    new EF rows match the sequential loop's within the ulp residue."""
    kw = dict(algorithm="fedavg", C=4, tag="ef",
              wire_codec="delta+sparse+quant")
    out_sh = _one_sharded_round(
        _engine(tmp_path, synthetic_cohort, **kw), efs=True)
    out_sq = _one_sharded_round(
        _engine(tmp_path, synthetic_cohort, seq=True, **kw), efs=True)
    assert len(out_sh) == 6  # params, bstats, loss, n_bad, new_efs, u0
    np.testing.assert_allclose(float(out_sh[2]), float(out_sq[2]),
                               rtol=LOSS_ULP_RTOL)
    _assert_trees_ulp(out_sh, out_sq)


# ---------------------------------------------------------------------------
# (a) mesh-width independence (incl. pad-count change)
# ---------------------------------------------------------------------------

def _assert_history_close(h1, h2, rtol=1e-4):
    assert len(h1) == len(h2)
    for a, b in zip(h1, h2):
        assert a.keys() == b.keys()
        for k in a:
            np.testing.assert_allclose(a[k], b[k], rtol=rtol, atol=1e-7)


@pytest.mark.slow
def test_sharded_train_mesh_width_independent(tmp_path, cohort21):
    """A full sharded fedavg train() — rounds, eval cadence, final
    fine-tune — matches across a 2-device and an 8-device client mesh to
    the ~1-ulp compile-context residue, although the 21 real sites pad
    to 22 rows on one and 24 on the other and every device's work list
    differs (different padded shapes = different compiled modules, so
    exactly-bitwise is out of reach by construction — the SEMANTIC
    equality shows as bitwise-equal round-1 losses in the
    vs-sequential pins above; parallel/cohort.py)."""
    r8 = _engine(tmp_path, cohort21, "fedavg", client_mesh=8, n_dev=8,
                 tag="w8").train()
    r2 = _engine(tmp_path, cohort21, "fedavg", client_mesh=2, n_dev=2,
                 tag="w2").train()
    _assert_trees_ulp(r8["params"], r2["params"], rtol=1e-5, atol=1e-6)
    _assert_trees_ulp(r8["batch_stats"], r2["batch_stats"], rtol=1e-5,
                      atol=1e-6)
    _assert_history_close(r8["history"], r2["history"])


@pytest.mark.slow
def test_sharded_train_mesh_width_independent_salientgrads(tmp_path,
                                                           cohort21):
    """The flagship end to end (phase-1 sharded scores -> mask -> masked
    sharded rounds -> personal stacks): 2- vs 8-device meshes within the
    ulp residue, and the phase-1 MASK itself identical."""
    r8 = _engine(tmp_path, cohort21, "salientgrads", client_mesh=8,
                 n_dev=8, tag="sw8").train()
    r2 = _engine(tmp_path, cohort21, "salientgrads", client_mesh=2,
                 n_dev=2, tag="sw2").train()
    _assert_trees_bitwise(r8["masks"], r2["masks"])
    _assert_trees_ulp(r8["params"], r2["params"], rtol=1e-5, atol=1e-6)
    _assert_history_close(r8["history"], r2["history"])


# ---------------------------------------------------------------------------
# (c) four sharded rounds, four deals, one program
# ---------------------------------------------------------------------------

def test_sharded_rounds_one_program_across_deals(tmp_path, cohort21):
    """Four sharded rounds on a 4-device mesh, each with its own sampled
    set (frac=0.5: 10 clients padded to 12 rows) and its own deal, are
    four dispatches of ONE compiled program (the deal is an operand),
    and their losses follow the stacked single-device run's: round 0
    from identical state to the loss ulp, the later rounds to the float
    noise the ~1-ulp state residue feeds back through training."""
    eng = _engine(tmp_path, cohort21, "fedavg", client_mesh=4, n_dev=4,
                  comm_round=4, freq=4, frac=0.5, tag="d4")
    ref = _engine(tmp_path, cohort21, "fedavg", client_mesh=0, n_dev=1,
                  comm_round=4, freq=4, frac=0.5, tag="d1")
    assert eng._cohort_on and not ref._cohort_on
    gs, gr = eng.init_global_state(), ref.init_global_state()
    p, b, rp, rb = gs.params, gs.batch_stats, gr.params, gr.batch_stats
    deals, losses, ref_losses = [], [], []
    for r in range(4):
        sampled = eng.client_sampling(r)
        deals.append(tuple(eng._cohort_deal(
            *eng._cohort_pad(sampled))[0].tolist()))
        ids, round_prog = eng._cohort_round_prog(sampled)
        p, b, loss, _ = round_prog(
            p, b, eng.data, jnp.asarray(ids),
            eng.per_client_rngs(r, ids), eng.round_lr(r))
        losses.append(float(loss))
        rp, rb, rloss, _ = ref._round_jit(
            rp, rb, ref.data, jnp.asarray(sampled),
            ref.per_client_rngs(r, sampled), ref.round_lr(r))
        ref_losses.append(float(rloss))
    assert len(set(deals)) > 1  # the rounds were dealt differently
    assert eng.program.dispatches == 4
    assert eng.program.built == 1
    np.testing.assert_allclose(losses[0], ref_losses[0],
                               rtol=LOSS_ULP_RTOL)
    np.testing.assert_allclose(losses, ref_losses, rtol=1e-4)


# ---------------------------------------------------------------------------
# (e) evaluation: each client row on the chip that holds it (PR 28)
# ---------------------------------------------------------------------------

#: the tolerance the evaluation tests of the other placements use
#: (tests/test_round_fold.py: stacked against folded metrics): a client
#: batched with others under ``vmap`` is another program than the client
#: alone, float32 summation order and nothing else
EVAL_RTOL, EVAL_ATOL = 1e-4, 1e-6

_COLLECTIVE = re.compile(
    r"= (.*?) (all-reduce|all-gather|all-to-all|collective-permute|"
    r"collective-broadcast|reduce-scatter)(-start)?\(")


def _eval_operands(eng, which):
    """``(jit, operands)`` of one evaluation program over the whole
    resident cohort: the global model, or a stack of personalized ones
    that differ by row (a row read from the wrong chip would score
    another model's numbers)."""
    gs = eng.init_global_state()
    d = eng.data
    if which == "global":
        return eng._eval_global_jit, (gs.params, gs.batch_stats,
                                      d.X_test, d.y_test, d.n_test)
    per = eng.broadcast_states(gs, eng.num_clients)
    tilt = 1.0 + 0.05 * jnp.arange(eng.num_clients, dtype=jnp.float32)
    params = jax.tree.map(
        lambda x: x * tilt.reshape((-1,) + (1,) * (x.ndim - 1)),
        per.params)
    return eng._eval_personal_jit, (params, per.batch_stats,
                                    d.X_test, d.y_test, d.n_test)


def _eval_engines(tmp_path, request, rows):
    """Sharded over 4 chips, the sequential reference, and the stacked
    ``vmap`` on the same mesh, over 8 sites (2 rows a chip) or the 21
    sites the data layer pads to 24 rows (6 a chip, 3 of them pads)."""
    data, C = {8: ("synthetic_cohort8", 8), 24: ("cohort21", 21)}[rows]
    data = request.getfixturevalue(data)
    kw = dict(C=C, n_dev=4)
    return (_engine(tmp_path, data, client_mesh=4, tag="esh", **kw),
            _engine(tmp_path, data, client_mesh=4, seq=True, tag="esq",
                    **kw),
            _engine(tmp_path, data, client_mesh=0, tag="evm", **kw))


@pytest.mark.parametrize("rows", [8, 24])
@pytest.mark.parametrize("which", ["global", "personalized"])
def test_sharded_evaluation_on_the_rows_chips(tmp_path, request, which,
                                              rows):
    """Where the cohort is sharded, evaluation runs through the cohort's
    ``shard_map``: per client ``(correct, loss, total, auc)`` BITWISE the
    sequential loop's (measured on this seed, 8 rows and 24, both models:
    evaluation is one forward per batch and no training feeds a residue
    back) and the stacked ``vmap``'s within the tolerance the other
    placements' evaluation tests use; ``_summarize``'s numbers follow;
    and the compiled program moves nothing between chips but the
    per-client scalars: no all-reduce at all (the stacked form's rebuilt
    stem activation was one), no collective over more than ``rows``
    elements."""
    sh, sq, vm = _eval_engines(tmp_path, request, rows)
    assert sh.num_clients == rows
    assert sh._rows_placement(rows) == ("sharded", rows // 4)
    assert vm._rows_placement(rows) == ("stacked", rows)
    progs = {name: _eval_operands(eng, which)
             for name, eng in (("sh", sh), ("sq", sq), ("vm", vm))}
    outs = {name: [np.asarray(o) for o in jit(*ops)]
            for name, (jit, ops) in progs.items()}
    n = np.asarray(sh.data.n_test)
    assert (n[sh.real_clients:] == 0).all()
    for a, b in zip(outs["sh"], outs["sq"]):
        np.testing.assert_array_equal(a, b)
    for a, b in zip(outs["sh"], outs["vm"]):
        np.testing.assert_allclose(a, b, rtol=EVAL_RTOL, atol=EVAL_ATOL)
    # pad rows score nothing, and _summarize drops them
    assert (outs["sh"][2][sh.real_clients:] == 0).all()
    if which == "personalized":
        # the rows are told apart: a model per row, a score per model
        assert len(set(outs["sh"][1][:sh.real_clients].tolist())) > 1
    summ = {k: e._summarize(*outs[k], n=n)
            for k, e in (("sh", sh), ("sq", sq), ("vm", vm))}
    assert summ["sh"] == summ["sq"]
    for k in ("acc", "loss", "auc", "acc_pooled"):
        np.testing.assert_allclose(summ["sh"][k], summ["vm"][k],
                                   rtol=EVAL_RTOL, atol=EVAL_ATOL)
    jit, ops = progs["sh"]
    text = jit.lower(*ops).compile().as_text()
    assert "all-reduce" not in text
    moved = [(m.group(2), m.group(1)) for m in _COLLECTIVE.finditer(text)]
    assert moved and {op for op, _ in moved} == {"all-gather"}
    for _, result in moved:
        for dims in re.findall(r"\w+\[([\d,]*)\]", result):
            assert int(np.prod([int(x) for x in dims.split(",") if x]
                               or [1])) <= rows, result
    # the stacked form on the same mesh is what this arm replaced
    jit, ops = progs["vm"]
    assert "shard_map" not in jit.lower(*ops).as_text()


def test_ci_single_row_evaluates_stacked(tmp_path, synthetic_cohort8):
    """``--ci`` hands evaluation client 0's row alone: one row does not
    tile the mesh, so it stays the ``vmap`` it was (no ``shard_map`` in
    the lowered program), scores what row 0 of the whole cohort scores,
    and the ``eval_dispatch`` span says so."""
    ci = _engine(tmp_path, synthetic_cohort8, C=8, n_dev=4, client_mesh=4,
                 tag="ci", ci=True)
    full = _engine(tmp_path, synthetic_cohort8, C=8, n_dev=4,
                   client_mesh=4, tag="cif")
    assert ci.program.placement == "sharded"
    assert ci._rows_placement(1) == ("stacked", 1)
    gs = ci.init_global_state()
    obs_trace.arm()
    try:
        m_ci = ci.eval_global(gs.params, gs.batch_stats)
        m_full = full.eval_global(gs.params, gs.batch_stats)
        spans = [e["args"] for e in obs_trace.TRACER.events()
                 if e.get("name") == obs_names.SPAN_EVAL_DISPATCH]
    finally:
        obs_trace.disarm()
    assert [(a["placement"], a["rows"], a["rows_a_chip"])
            for a in spans[-2:]] == [("stacked", 1, 1), ("sharded", 8, 2)]
    d = ci.data
    one = (d.X_test[:1], d.y_test[:1], d.n_test[:1])
    assert "shard_map" not in ci._eval_global_jit.lower(
        gs.params, gs.batch_stats, *one).as_text()
    row0 = [np.asarray(o)[:1] for o in full._eval_global_jit(
        gs.params, gs.batch_stats, d.X_test, d.y_test, d.n_test)]
    want = full._summarize(*row0, n=np.asarray(d.n_test)[:1])
    for k in ("acc", "loss", "auc", "acc_pooled"):
        np.testing.assert_allclose(m_ci[k], want[k], rtol=EVAL_RTOL,
                                   atol=EVAL_ATOL)
    assert m_full["loss"] != m_ci["loss"]


def _subjaxprs(jaxpr):
    for eqn in jaxpr.eqns:
        for v in eqn.params.values():
            for j in (v if isinstance(v, (tuple, list)) else (v,)):
                j = getattr(j, "jaxpr", j)
                if hasattr(j, "eqns"):
                    yield eqn, j


def _evaluation_loops(jaxpr, depth=0):
    """``(depth, length, batch width)`` of every ``scan`` in a traced
    program that stacks an output a row: the width is the minor extent
    of its output of the highest rank (``evaluate``'s ``scores``, ``[nb,
    batch]`` with the client axis between them under ``vmap``)."""
    found = []
    for eqn, sub in _subjaxprs(jaxpr):
        found += _evaluation_loops(sub, depth + 1)
        if eqn.primitive.name == "scan":
            widest = max((v.aval for v in eqn.outvars),
                         key=lambda a: a.ndim)
            if widest.ndim >= 2:
                found.append((depth, eqn.params["length"],
                              widest.shape[-1]))
    return found


@pytest.mark.parametrize("placement", ["stacked", "folded", "sharded"])
def test_rows_run_counts_the_program_that_is_traced(tmp_path, placement):
    """``rows_run`` on the ``eval_dispatch`` span and the evaluation
    program come from one rule (core/trainer.py ``eval_batches``): under
    each placement the counter is the client rows x the length x the
    width of the innermost loop that ``eval_global`` traces, with sites
    of 44, 8, 4 and 2 test rows stacked to 44 (2 batches of 22)."""
    sizes = (220, 40, 20, 10)
    data = generate_synthetic_abcd(num_subjects=sum(sizes),
                                   shape=(12, 14, 12), num_sites=4, seed=3)
    data["site"] = np.repeat(np.arange(4), sizes).astype(np.int16)
    if placement == "sharded":
        eng = _engine(tmp_path, data, C=4, n_dev=4, client_mesh=4, tag="rr")
    else:
        eng = _engine(tmp_path, data, C=4, mesh=False, client_mesh=0,
                      tag="rr")
        if placement == "folded":
            eng._fold_budget_bytes = 1
    assert eng.program.placement == placement
    d = eng.data
    assert np.asarray(d.n_test).tolist() == [44, 8, 4, 2]
    rows, width = d.X_test.shape[:2]
    assert (rows, width) == (4, 44)
    obs_trace.arm()
    try:
        args = eng._eval_span_args(d.X_test, "test")
    finally:
        obs_trace.disarm()
    assert args["placement"] == placement
    assert args["rows_real"] == 58
    gs = eng.init_global_state()
    jaxpr = jax.make_jaxpr(eng._eval_global_jit)(
        gs.params, gs.batch_stats, d.X_test, d.y_test, d.n_test)
    ((_, length, batch),) = _evaluation_loops(jaxpr.jaxpr)
    assert (length, batch) == eng.trainer.eval_batches(
        d.X_test.shape[2:], width) == (2, 22)
    assert args["rows_run"] == rows * length * batch == 176


def _eval_all_as_it_was(eng, which, folded):
    """The evaluation program of the two placements this PR leaves
    alone, written out as the parent had it: ``vmap`` over the rows, or
    ``sequential_map`` with the rows alone when the round folds."""
    trainer = eng.trainer

    def eval_all(params, bstats, X, y, n):
        def score(p, b, Xc, yc, nc):
            valid = jnp.arange(Xc.shape[0]) < nc
            m = trainer.evaluate(p, b, Xc, yc, valid)
            auc = binary_auc(m["scores"], yc, valid)
            return m["test_correct"], m["test_loss"], m["test_total"], auc

        if which == "global":
            fn = lambda Xc, yc, nc: score(params, bstats, Xc, yc, nc)
            stacked = (X, y, n)
        else:
            fn, stacked = score, (params, bstats, X, y, n)
        if folded:
            with trainer.rows_alone():
                return cohort.sequential_map(fn, *stacked)
        return jax.vmap(fn)(*stacked)

    return jax.jit(eval_all)


@pytest.mark.parametrize("which", ["global", "personalized"])
@pytest.mark.parametrize("placement", ["stacked", "folded"])
def test_unsharded_evaluation_programs_unchanged(tmp_path,
                                                 synthetic_cohort,
                                                 placement, which):
    """With no mesh, and with the fold forced, ``_per_client`` lowers the
    program it lowered before it had a sharded arm: the text of the
    engine's jit against the parent's form written out by hand (lowered
    text carries no source locations, so equal programs are equal
    strings)."""
    eng = _engine(tmp_path, synthetic_cohort, C=4, client_mesh=0,
                  mesh=False, tag=f"un-{placement}")
    if placement == "folded":
        eng._fold_budget_bytes = 1
    assert eng.program.placement == placement
    jit, ops = _eval_operands(eng, which)
    was = _eval_all_as_it_was(eng, which, placement == "folded")
    assert jit.lower(*ops).as_text() == was.lower(*ops).as_text()
    assert "shard_map" not in jit.lower(*ops).as_text()


def test_local_train_in_the_sharded_arm_needs_hoisted_perms(
        tmp_path, synthetic_cohort8):
    """``fedavg._finetune_eval_jit`` trains inside ``_per_client`` and
    draws its own permutations; it is reached only streamed or folded,
    where cohort sharding never arms. Were it ever traced in the sharded
    arm, the in-partition random sort (the measured miscompile,
    parallel/cohort.py) must not run: ``local_train`` refuses."""
    eng = _engine(tmp_path, synthetic_cohort8, C=8, n_dev=4,
                  client_mesh=4, tag="ft")
    gs = eng.init_global_state()
    d = eng.data
    rngs = eng.per_client_rngs(0, np.arange(eng.num_clients))
    with pytest.raises(ValueError, match="without hoisted permutations"):
        eng._finetune_eval_jit.lower(
            gs.params, gs.batch_stats, d.X_train, d.y_train, d.n_train,
            d.X_test, d.y_test, d.n_test, rngs, eng.round_lr(0))
    # the trainer is left as it was found
    assert not eng.trainer._rows_alone and not eng.trainer._partitioned


# ---------------------------------------------------------------------------
# (d) fallbacks with logged reasons + loud config errors
# ---------------------------------------------------------------------------

@pytest.mark.parametrize("algorithm,needle", [
    ("fedfomo", "no cohort-sharded round body"),
    ("dispfl", "gossip collectives"),
    # local declared its round on the builder (ROADMAP 1(a)) and now
    # ARMS cohort sharding — its positive pins live in tests/
    # test_program.py (arm assertion + sharded==sequential-C-loop)
    ("turboaggregate", "MPC share boundary"),
])
def test_engines_without_sharded_round_fall_back(tmp_path,
                                                 synthetic_cohort,
                                                 algorithm, needle):
    eng = _engine(tmp_path, synthetic_cohort, algorithm, C=4,
                  tag=f"fb-{algorithm}",
                  val_fraction=0.25 if algorithm == "fedfomo" else 0.0)
    assert not eng._cohort_on
    text = _log_text(eng)
    assert "running the unsharded round program" in text
    assert needle in text


def test_replacement_batch_order_falls_back(tmp_path, synthetic_cohort):
    """batch_order=replacement draws per-step randint batches INSIDE the
    shard_map partition — the in-partition RNG lowering this toolchain
    miscompiles (parallel/cohort.py; the shuffle path hoists its
    permutations out, i.i.d. draws cannot be hoisted) — so --client_mesh
    collapses to the unsharded round with the logged reason."""
    cohort_data = synthetic_cohort
    cfg = ExperimentConfig(
        model="3dcnn_tiny", algorithm="fedavg",
        data=DataConfig(dataset="synthetic"),
        optim=OptimConfig(lr=1e-3, batch_size=8, epochs=1,
                          batch_order="replacement"),
        fed=FedConfig(client_num_in_total=4, comm_round=1, client_mesh=8),
        log_dir=str(tmp_path), tag="rep")
    mesh = make_mesh()
    fed, _ = federate_cohort(cohort_data, partition_method="site",
                             mesh=mesh)
    trainer = LocalTrainer(create_model(cfg.model, num_classes=1),
                           cfg.optim, num_classes=1)
    log = ExperimentLogger(str(tmp_path), "synthetic", cfg.identity(),
                           console=False)
    eng = create_engine("fedavg", cfg, fed, trainer, mesh=mesh, logger=log)
    assert not eng._cohort_on
    assert "replacement" in _log_text(eng)


def test_streaming_falls_back_with_logged_reason(tmp_path,
                                                 synthetic_cohort):
    eng = _engine(tmp_path, synthetic_cohort, "fedavg", C=4, stream=True,
                  tag="fbstream")
    try:
        assert not eng._cohort_on
        assert "streamed feed" in _log_text(eng)
    finally:
        eng.stream.close()


def test_two_level_mesh_falls_back_with_logged_reason(tmp_path,
                                                      synthetic_cohort):
    eng = _engine(tmp_path, synthetic_cohort, "fedavg", C=4,
                  mesh=make_mesh(shape=(2, 4)), tag="fb2l")
    assert not eng._cohort_on
    assert "silo-first" in _log_text(eng)


def test_single_device_mesh_falls_back(tmp_path, synthetic_cohort):
    eng = _engine(tmp_path, synthetic_cohort, "fedavg", C=4,
                  client_mesh=1, n_dev=1, tag="fb1")
    assert not eng._cohort_on
    assert "only one device" in _log_text(eng)


def test_client_mesh_size_mismatch_raises(tmp_path, synthetic_cohort):
    with pytest.raises(ValueError, match="does not match"):
        _engine(tmp_path, synthetic_cohort, "fedavg", C=4, client_mesh=4,
                n_dev=8, tag="mm")


def test_client_mesh_without_mesh_raises(tmp_path, synthetic_cohort):
    cfg = ExperimentConfig(
        model="3dcnn_tiny", algorithm="fedavg",
        data=DataConfig(dataset="synthetic"),
        optim=OptimConfig(lr=1e-3, batch_size=8, epochs=1),
        fed=FedConfig(client_num_in_total=4, comm_round=1, client_mesh=8),
        log_dir=str(tmp_path), tag="nm")
    fed, _ = federate_cohort(synthetic_cohort, partition_method="site",
                             mesh=None)
    trainer = LocalTrainer(create_model(cfg.model, num_classes=1),
                           cfg.optim, num_classes=1)
    log = ExperimentLogger(str(tmp_path), "synthetic", cfg.identity(),
                           console=False)
    with pytest.raises(ValueError, match="no device mesh"):
        create_engine("fedavg", cfg, fed, trainer, mesh=None, logger=log)


def test_distributed_cli_cohort_note(capsys):
    from neuroimagedisttraining_tpu.distributed import run as drun

    assert drun.cohort_fallback_note(0) is None
    assert "no in-process client axis" in drun.cohort_fallback_note(8)
    with pytest.raises(SystemExit):
        drun.main(["--role", "aggregator", "--num_clients", "1",
                   "--client_mesh", "8"])
    assert "no in-process client axis" in capsys.readouterr().out


def test_armed_engine_logs_and_flags(tmp_path, cohort21):
    eng = _engine(tmp_path, cohort21, "fedavg", tag="armed")
    assert eng._cohort_on
    assert "cohort sharding armed" in _log_text(eng)


@pytest.mark.parametrize("client_mesh,backend,refused", [
    (0, "tpu", True),    # GSPMD round over 8 devices: Mosaic cannot lower
    (8, "tpu", False),   # the cohort-sharded round's shard_map can
    (0, "cpu", False),   # off-TPU the fused tail is plain XLA
])
def test_fused_update_on_a_tpu_mesh_needs_the_sharded_round(
        tmp_path, synthetic_cohort8, monkeypatch, client_mesh, backend,
        refused):
    """jax refuses a Pallas kernel in a program GSPMD partitions over
    several devices ("wrap the call in a shard_map"), so the fused
    update on a multi-device TPU mesh is rejected at engine start with
    the resolution named — found by compiling the 4-chip round for a
    described v5e:2x2 (PR 21)."""
    monkeypatch.setattr(jax, "default_backend", lambda: backend)
    kw = dict(C=8, client_mesh=client_mesh, fused_update=True, tag="fu")
    if refused:
        with pytest.raises(ValueError, match="--client_mesh 8"):
            _engine(tmp_path, synthetic_cohort8, **kw)
    else:
        _engine(tmp_path, synthetic_cohort8, **kw)
