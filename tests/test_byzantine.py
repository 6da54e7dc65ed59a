"""Byzantine value faults + robust aggregation (ISSUE 5): the ``byz:``
fault grammar and adversary transforms, the order-statistic aggregators'
breakdown points, the engines' non-finite upload guard, fused-dispatch
bitwise parity with a defense enabled, and the cross-silo server's
detection/quarantine control plane."""

import threading

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from neuroimagedisttraining_tpu.core import robust
from neuroimagedisttraining_tpu.distributed.cross_silo import (
    FedAvgClientProc,
    FedAvgServer,
    SecureFedAvgServer,
    survivor_defended_mean,
    tree_all_finite,
    update_outlier_flags,
)
from neuroimagedisttraining_tpu.distributed.ports import free_port_block
from neuroimagedisttraining_tpu.faults import (
    FaultSchedule,
    adversary,
    parse_byz_kind,
    parse_fault_spec,
)
from neuroimagedisttraining_tpu.utils import pytree as pt


# ------------------------------------------------- byz grammar + schedule


def test_parse_byz_spec_grammar():
    spec = parse_fault_spec("byz:1@0:sign_flip,byz:3@2:scale:10,"
                            "byz_prob:0.25:gauss:0.5,crash:2@1")
    assert spec.byz == ((1, 0, "sign_flip"), (3, 2, "scale:10.0"))
    assert spec.byz_prob == 0.25
    assert spec.byz_kind == "gauss:0.5"
    assert spec.crashes == ((2, 1),)
    assert spec.any_faults and spec.any_value_faults
    # omission-only specs carry no value faults
    assert not parse_fault_spec("crash:2@1,drop:0.5").any_value_faults
    assert parse_byz_kind("nonfinite") == "nonfinite"
    assert parse_byz_kind("scale: -4 ") == "scale:-4.0"


def test_parse_byz_spec_malformed_fails_loudly():
    for bad in ("byz:1@0", "byz:1@0:evil", "byz:1@0:scale",
                "byz:1@0:gauss:-1", "byz:1@0:sign_flip:2",
                "byz_prob:1.5", "byz_prob:0.2:bogus"):
        with pytest.raises(ValueError):
            parse_fault_spec(bad)


def test_byz_schedule_deterministic_and_permanent():
    spec = parse_fault_spec("byz:2@1:sign_flip,byz_prob:0.3:scale:5")
    a = FaultSchedule(spec, seed=7)
    b = FaultSchedule(spec, seed=7)
    got = [[a.byzantine_kind(r, c) for c in range(1, 5)] for r in range(6)]
    assert got == [[b.byzantine_kind(r, c) for c in range(1, 5)]
                   for r in range(6)]
    # the deterministic directive is permanent from its round on and
    # wins over the probabilistic draw
    assert a.byzantine_kind(0, 2) in (None, "scale:5.0")
    for r in range(1, 6):
        assert a.byzantine_kind(r, 2) == "sign_flip"
    # a different seed redraws the transient stream
    c = FaultSchedule(spec, seed=8)
    trans = [(r, k) for r in range(20) for k in (1, 3, 4)]
    assert [a.byzantine_kind(r, k) for r, k in trans] != \
        [c.byzantine_kind(r, k) for r, k in trans]


# ------------------------------------------------- adversary transforms


def _toy_tree(rng, scale=1.0):
    return {"w": np.asarray(rng.normal(size=(4, 3)) * scale, np.float32),
            "b": np.asarray(rng.normal(size=(5,)) * scale, np.float32)}


def test_adversary_kinds_math():
    rng = np.random.default_rng(0)
    ref = _toy_tree(rng)
    u = {k: v + np.float32(0.5) for k, v in ref.items()}
    sched = FaultSchedule(parse_fault_spec("byz:1@0:sign_flip"), seed=0)

    flip = adversary.attack_update(sched, 0, 0, 1, u, ref)
    for k in ref:
        # sign_flip: ref - (u - ref)
        np.testing.assert_allclose(flip[k], ref[k] - (u[k] - ref[k]),
                                   rtol=1e-6)
    sched = FaultSchedule(parse_fault_spec("byz:1@0:scale:-10"), seed=0)
    sc = adversary.attack_update(sched, 0, 0, 1, u, ref)
    for k in ref:
        np.testing.assert_allclose(sc[k], ref[k] - 10 * (u[k] - ref[k]),
                                   rtol=1e-5)
    sched = FaultSchedule(parse_fault_spec("byz:1@0:nonfinite"), seed=0)
    bad = adversary.attack_update(sched, 0, 0, 1, u, ref)
    assert all(np.isnan(v).all() for v in bad.values())
    # honest rank / pre-attack round: the upload passes through BITWISE
    sched = FaultSchedule(parse_fault_spec("byz:1@3:sign_flip"), seed=0)
    for (r, c) in ((0, 1), (3, 2)):
        out = adversary.attack_update(sched, 0, r, c, u, ref)
        for k in ref:
            np.testing.assert_array_equal(out[k], u[k])


def test_adversary_stacked_matches_per_client_path():
    """The engines' vmapped plan path and the cross-silo client's eager
    ``attack_update`` inject bitwise-identical values — gauss noise
    included (one seed, one attack trace in both federations)."""
    rng = np.random.default_rng(1)
    ref = _toy_tree(rng)
    ups = [_toy_tree(rng) for _ in range(4)]
    sched = FaultSchedule(
        parse_fault_spec("byz:2@0:gauss:0.3,byz:4@0:sign_flip"), seed=5)
    ranks = np.arange(1, 5)
    mult, std, nan = adversary.plan_arrays(sched, 0, ranks)
    np.testing.assert_array_equal(mult, np.float32([1, 1, 1, -1]))
    np.testing.assert_array_equal(std, np.float32([0, 0.3, 0, 0]))
    keys = adversary.attack_keys(5, 0, ranks)
    stacked = jax.tree.map(lambda *xs: jnp.stack(xs), *ups)
    got = adversary.apply_attack_stacked(stacked, ref, jnp.asarray(mult),
                                         jnp.asarray(std),
                                         jnp.asarray(nan), keys)
    for i, u in enumerate(ups):
        want = adversary.attack_update(sched, 5, 0, i + 1, u, ref)
        for k in ref:
            np.testing.assert_array_equal(
                np.asarray(got[k][i]), np.asarray(want[k]))


# ------------------------------------------------- robust aggregators


def _stack(trees):
    return jax.tree.map(lambda *xs: jnp.stack([jnp.asarray(x)
                                               for x in xs]), *trees)


def test_trimmed_mean_discards_planted_outliers():
    honest = [{"w": jnp.full((3,), float(v))} for v in (1.0, 2.0, 3.0)]
    byz = [{"w": jnp.full((3,), 1e6)}, {"w": jnp.full((3,), -1e6)}]
    stacked = _stack(honest + byz)
    w = jnp.ones((5,), jnp.float32)
    out = robust.trimmed_mean(stacked, w, f=2)  # 2f < n = 5
    np.testing.assert_allclose(np.asarray(out["w"]), 2.0, rtol=1e-6)
    # weighted: surviving coordinates renormalize the sample weights
    w2 = jnp.asarray([1.0, 3.0, 1.0, 7.0, 7.0], jnp.float32)
    out2 = robust.trimmed_mean(stacked, w2, f=2)
    np.testing.assert_allclose(np.asarray(out2["w"]), 2.0, rtol=1e-6)


def test_trimmed_mean_zero_weight_rows_never_vote():
    """Zero-weight rows (non-finite uploads sanitized to the broadcast
    reference, streaming mesh pads) are not client updates: they must
    not occupy trim slots — a kept window holding ONLY zero-weight rows
    used to 0/eps-collapse the coordinate to 0.0."""
    # C=3, f=1: honest at 1 and 3 (w>0), a sanitized reference row at 2
    # (w=0) — the old positional trim kept exactly the w=0 row
    stacked = _stack([{"w": jnp.full((2,), 1.0)},
                      {"w": jnp.full((2,), 2.0)},
                      {"w": jnp.full((2,), 3.0)}])
    w = jnp.asarray([1.0, 0.0, 1.0], jnp.float32)
    out = robust.trimmed_mean(stacked, w, f=1)
    np.testing.assert_allclose(np.asarray(out["w"]), 2.0, rtol=1e-6)
    # a voting cohort deep enough to really trim still sheds the outlier
    stacked5 = _stack([{"w": jnp.full((2,), v)}
                       for v in (1.0, 2.0, 3.0, 1e6, 2.0)])
    w5 = jnp.asarray([1.0, 1.0, 1.0, 1.0, 0.0], jnp.float32)
    out5 = robust.trimmed_mean(stacked5, w5, f=1)
    np.testing.assert_allclose(np.asarray(out5["w"]), 2.5, rtol=1e-6)
    # pathological all-zero cohort degrades to the uniform trimmed mean
    out0 = robust.trimmed_mean(stacked, jnp.zeros((3,), jnp.float32), f=1)
    np.testing.assert_allclose(np.asarray(out0["w"]), 2.0, rtol=1e-6)
    # the weighted median shares the fallback (masking EVERY row past
    # the voting window used to return +inf and destroy the model)
    med0 = robust.coordinate_median(stacked, jnp.zeros((3,), jnp.float32))
    np.testing.assert_allclose(np.asarray(med0["w"]), 2.0, rtol=1e-6)


def test_krum_mechanical_floor_vs_blanchard_bound():
    """n >= f+3 is the mechanical floor (selection defined); the
    provable Blanchard guarantee needs n >= 2f+3 — in the gap the
    defense runs but ``effective_defense`` warns that f colluding
    attackers can win the selection."""
    calls = []

    def warn(msg, *a):
        calls.append(msg % a if a else msg)

    assert robust.effective_defense("krum", 4, 1, warn=warn) == "krum"
    assert any("2f+3" in c for c in calls)
    calls.clear()
    assert robust.effective_defense("krum", 5, 1, warn=warn) == "krum"
    assert not calls  # at/above the provable bound: silent
    assert robust.effective_defense("krum", 3, 1, warn=warn) == "none"
    assert calls  # below the mechanical floor: falls back with warning


def test_coordinate_median_breakdown():
    honest = [{"w": jnp.asarray([1.0, 5.0])}, {"w": jnp.asarray([2.0, 6.0])},
              {"w": jnp.asarray([3.0, 7.0])}]
    byz = [{"w": jnp.asarray([1e8, -1e8])}]
    out = robust.coordinate_median(_stack(honest + byz))
    got = np.asarray(out["w"])
    assert 1.0 <= got[0] <= 3.0 and 5.0 <= got[1] <= 7.0


def test_krum_selects_honest_cluster():
    rng = np.random.default_rng(3)
    honest = [{"w": jnp.asarray(rng.normal(size=(6,)) * 0.1 + 1.0,
                                jnp.float32)} for _ in range(4)]
    byz = [{"w": jnp.full((6,), -50.0)}]
    stacked = _stack(honest + byz)
    w = jnp.ones((5,), jnp.float32)
    sel = robust.krum_select(stacked, w, f=1, m=1)
    assert int(sel[0]) < 4  # never the planted outlier
    out = robust.krum(stacked, w, f=1)
    assert abs(float(np.asarray(out["w"]).mean()) - 1.0) < 0.5
    multi = robust.krum(stacked, w, f=1, multi=True)
    assert abs(float(np.asarray(multi["w"]).mean()) - 1.0) < 0.5
    # zero-weight rows (sanitized non-finite uploads) leave the selection
    w0 = jnp.asarray([0.0, 1.0, 1.0, 1.0, 1.0], jnp.float32)
    sel0 = robust.krum_select(stacked, w0, f=1, m=4)
    assert 0 not in set(np.asarray(sel0).tolist())


def test_geometric_median_resists_outlier():
    honest = [{"w": jnp.full((4,), float(v))} for v in (0.9, 1.0, 1.1)]
    byz = [{"w": jnp.full((4,), 1e5)}]
    out = robust.geometric_median(_stack(honest + byz),
                                  jnp.ones((4,), jnp.float32), iters=32)
    got = float(np.asarray(out["w"]).mean())
    assert 0.8 < got < 1.3  # the mean would sit at ~25000


def test_breakdown_point_checks_fail_loudly():
    with pytest.raises(ValueError):
        robust._check_f(4, 2, "trimmed_mean")  # 2f >= n
    with pytest.raises(ValueError):
        robust._check_f(3, 1, "krum")          # n < f + 3
    with pytest.raises(ValueError):
        robust._check_f(4, -1, "median")
    assert robust._check_f(5, 2, "median") == 2
    with pytest.raises(ValueError):
        robust.validate_defense("bogus_defense")
    with pytest.raises(ValueError):
        robust.robust_aggregate(_stack([{"w": jnp.ones(2)}] * 4),
                                jnp.ones((4,)), defense="weak_dp", byz_f=1)


def test_aggregate_with_defense_dispatch():
    """One entry point: the clip family clips-then-means; the order-
    statistic family ignores the mean entirely."""
    rng = np.random.default_rng(4)
    ref = {k: jnp.asarray(v) for k, v in _toy_tree(rng).items()}
    honest = [jax.tree.map(
        lambda x: x + jnp.float32(0.01) * (i + 1), ref) for i in range(3)]
    byz = [jax.tree.map(lambda x: x + jnp.float32(1e4), ref)]
    stacked = _stack(honest + byz)
    w = jnp.ones((4,), jnp.float32)
    mean = robust.aggregate_with_defense(stacked, ref, w, defense="none")
    for a, b in zip(jax.tree.leaves(mean),
                    jax.tree.leaves(pt.tree_weighted_mean(stacked, w))):
        np.testing.assert_array_equal(np.asarray(a), np.asarray(b))
    trimmed = robust.aggregate_with_defense(stacked, ref, w,
                                            defense="trimmed_mean",
                                            byz_f=1)
    err = float(pt.tree_norm(pt.tree_sub(trimmed, ref)))
    assert err < 1.0  # the undefended mean would sit ~2500 away
    clipped = robust.aggregate_with_defense(
        stacked, ref, w, defense="norm_diff_clipping", norm_bound=0.5)
    assert float(pt.tree_norm(pt.tree_sub(clipped, ref))) <= 0.5 + 1e-4


def test_finite_per_client_and_replacement():
    ref = {"w": jnp.ones((2, 2), jnp.float32), "b": jnp.zeros(3)}
    rows = [jax.tree.map(lambda x: x * (i + 1), ref) for i in range(3)]
    rows[1] = {"w": jnp.full((2, 2), jnp.nan), "b": jnp.zeros(3)}
    stacked = _stack(rows)
    finite = robust.finite_per_client(stacked)
    np.testing.assert_array_equal(np.asarray(finite), [True, False, True])
    fixed = robust.replace_nonfinite_clients(stacked, ref, finite)
    np.testing.assert_array_equal(np.asarray(fixed["w"][1]),
                                  np.asarray(ref["w"]))
    np.testing.assert_array_equal(np.asarray(fixed["w"][0]),
                                  np.asarray(stacked["w"][0]))
    assert tree_all_finite(fixed)
    assert not tree_all_finite(stacked)


# ------------------------------------------------- engine integration


@pytest.mark.slow  # tier-1 window (PR 7): single-engine behavioral e2e, engine keeps dispatch/stream/cohort coverage
def test_engine_nonfinite_guard_independent_of_defense(tmp_path,
                                                       synthetic_cohort):
    """A silo uploading NaN every round must not poison the aggregate —
    with --defense none. The guard zero-weights the row and emits the
    counted warning (ISSUE 5 satellite)."""
    from tests.test_fedavg import _make_engine

    engine = _make_engine(tmp_path, synthetic_cohort, comm_round=2,
                          fault_spec="byz:1@0:nonfinite")
    result = engine.train()
    assert all(np.isfinite(np.asarray(x)).all()
               for x in jax.tree.leaves(result["params"]))
    assert np.isfinite(result["history"][-1]["train_loss"])
    # one rejection per round, counted into stat_info
    assert engine.stat_info["nonfinite_uploads"] >= 2


def test_wire_codec_ef_resets_for_nonfinite_uploads(tmp_path,
                                                    synthetic_cohort):
    """A non-finite upload must not park NaN in the codec error-feedback
    stack: EF = u - decode(u) of a NaN row is NaN, every later encode
    consumes it, and a one-round value fault would zero-weight the
    client FOREVER. The round zeroes those EF rows instead."""
    from tests.test_fedavg import _make_engine

    e = _make_engine(tmp_path, synthetic_cohort,
                     fault_spec="byz:1@0:nonfinite",
                     wire_codec="delta+sparse+quant")
    e._donate = False
    gs = e.init_global_state()
    sampled = e.client_sampling(0)
    rngs = e.per_client_rngs(0, np.asarray(sampled))
    byz = e._byz_round_plan(0, np.asarray(sampled))
    assert byz is not None
    efs = jax.tree.map(
        lambda x: jnp.zeros((len(sampled),) + x.shape, jnp.float32),
        {"params": gs.params, "batch_stats": gs.batch_stats})
    new_params, _, _, _, new_efs, _ = e._round_jit(
        gs.params, gs.batch_stats, e.data, jnp.asarray(sampled), rngs,
        jnp.float32(2e-3), efs, byz)
    # byz rank 1 == engine client 0 (the faults/ contract)
    atk = int(np.flatnonzero(np.asarray(sampled) == 0)[0])
    hon = [i for i in range(len(sampled)) if i != atk]
    for leaf in jax.tree.leaves(new_efs):
        a = np.asarray(leaf)
        assert np.isfinite(a).all()  # the NaN residual never lands
        assert not np.any(a[atk])    # the attacked row is exactly zero
    # honest rows carry real lossy-roundtrip residuals
    assert sum(float(np.abs(np.asarray(leaf)[hon]).sum())
               for leaf in jax.tree.leaves(new_efs)) > 0.0
    assert all(np.isfinite(np.asarray(x)).all()
               for x in jax.tree.leaves(new_params))


def test_client_ef_dropped_after_nonfinite_upload():
    """Cross-silo mirror of the engine EF reset: a client whose upload
    goes non-finite (its frame bounces at the server's hard gate) drops
    the consumed EF stack instead of absorbing the NaN residual, so the
    next honest round encodes from a clean accumulator."""
    from neuroimagedisttraining_tpu.codec import parse_wire_spec
    from neuroimagedisttraining_tpu.distributed import message as M

    c = FedAvgClientProc.__new__(FedAvgClientProc)
    c.rank = 1
    c.seed = 0
    c.fault_schedule = None
    c._wire_spec = parse_wire_spec("delta+sparse+quant")
    c.wire_masks = None
    c._wire_ef = None
    sent = []
    c.send_message = sent.append
    ref = {"w": np.zeros((4, 4), np.float32)}
    outs = iter([({"w": np.full((4, 4), np.nan, np.float32)}, 8.0),
                 ({"w": np.full((4, 4), 0.5, np.float32)}, 8.0)])
    c.train_fn = lambda params, r: next(outs)

    def sync(r):
        m = M.Message(M.MSG_TYPE_S2C_SYNC_MODEL, 0, 1)
        m.add(M.ARG_MODEL_PARAMS, ref)
        m.add(M.ARG_ROUND_IDX, r)
        c._on_sync(m)

    sync(0)  # NaN upload: the consumed EF must be dropped, not parked
    assert c._wire_ef is None
    sync(1)  # honest round: EF threads again, finite
    assert c._wire_ef is not None
    assert all(np.isfinite(np.asarray(x)).all()
               for x in jax.tree.leaves(c._wire_ef))
    assert len(sent) == 2


def test_engine_rejects_unknown_or_unsupported_defense(tmp_path,
                                                       synthetic_cohort):
    from tests.test_fedavg import _make_engine

    with pytest.raises(ValueError, match="unknown defense"):
        _make_engine(tmp_path, synthetic_cohort,
                     defense_type="krumm")  # typo fails at startup
    # local's round has no defended aggregation path (no declared
    # aggregate stage routes through the builder's defense dispatch —
    # ditto gained one in ISSUE 11): loud, at startup
    with pytest.raises(ValueError, match="does not support"):
        _make_engine(tmp_path, synthetic_cohort, algorithm="local",
                     defense_type="trimmed_mean")
    # breakdown point vs the sampled cohort: krum needs n >= f + 3
    with pytest.raises(ValueError, match="f \\+ 3"):
        _make_engine(tmp_path, synthetic_cohort, defense_type="krum",
                     byz_f=2)


def test_engine_without_byz_support_rejects_value_faults(tmp_path,
                                                         synthetic_cohort):
    from tests.test_fedavg import _make_engine

    # local never puts uploads on a wire — no attack surface, and no
    # builder attack stage to route them through (ditto gained byz
    # support with its stage declaration, ISSUE 11)
    with pytest.raises(ValueError, match="byz"):
        _make_engine(tmp_path, synthetic_cohort, algorithm="local",
                     fault_spec="byz:1@0:sign_flip")
    # omission faults keep working everywhere
    e = _make_engine(tmp_path, synthetic_cohort, algorithm="local",
                     fault_spec="crash:1@1")
    assert e.fault_schedule is not None


@pytest.mark.slow
def test_fedavg_defense_recovers_under_sign_flip(tmp_path,
                                                 synthetic_cohort):
    """Engine-level measured contract: 1-of-4 sign-flip degrades the
    undefended round drift; trimmed_mean pulls the aggregate back toward
    the honest mean (the byz_bench.json claim at CI scale)."""
    from tests.test_fedavg import _make_engine

    def drift(defense, spec):
        e = _make_engine(tmp_path, synthetic_cohort, comm_round=2,
                         fault_spec=spec, defense_type=defense, byz_f=1)
        e._donate = False
        gs = e.init_global_state()
        sampled = jnp.asarray(e.client_sampling(0))
        rngs = e.per_client_rngs(0, np.asarray(sampled))
        byz = e._byz_round_plan(0, np.asarray(sampled))
        if byz is not None:
            p, _, _, _ = e._round_jit(gs.params, gs.batch_stats, e.data,
                                      sampled, rngs, jnp.float32(2e-3),
                                      None, byz)
        else:
            p, _, _, _ = e._round_jit(gs.params, gs.batch_stats, e.data,
                                      sampled, rngs, jnp.float32(2e-3))
        return p, gs

    p_clean, gs = drift("none", "")
    p_atk, _ = drift("none", "byz:1@0:scale:30")
    p_def, _ = drift("trimmed_mean", "byz:1@0:scale:30")
    err_atk = float(pt.tree_norm(pt.tree_sub(p_atk, p_clean)))
    err_def = float(pt.tree_norm(pt.tree_sub(p_def, p_clean)))
    assert err_atk > 5 * err_def  # the defense recovers most of the gap


@pytest.mark.slow
@pytest.mark.parametrize("algorithm", ["fedavg", "salientgrads"])
def test_sequential_rounds_one_program_with_defense(
        tmp_path, synthetic_cohort, algorithm):
    """Four driver rounds with a Byzantine schedule AND a defense
    enabled are four dispatches of one compiled program (the attack
    plan is an operand, the defense is traced in), deterministic run to
    run, for fedavg and salientgrads."""
    from tests.test_engines import _engine

    def run():
        e = _engine(tmp_path, synthetic_cohort, algorithm, comm_round=4,
                    fault_spec="byz:1@0:sign_flip",
                    defense_type="trimmed_mean", byz_f=1)
        e._donate = False
        res = e.train()
        assert e.program.dispatches == 4
        assert e.program.built == 1
        return res

    a, b = run(), run()
    for x, y in zip(jax.tree.leaves(a["params"]),
                    jax.tree.leaves(b["params"])):
        np.testing.assert_array_equal(np.asarray(x), np.asarray(y))
    assert all(np.isfinite(h["train_loss"]) for h in a["history"])


# ------------------------------------------------- cross-silo control plane


def test_update_outlier_flags_scoring():
    rng = np.random.default_rng(6)
    ref = _toy_tree(rng)
    honest = [{k: v + rng.normal(size=v.shape).astype(np.float32) * 0.01
               + np.float32(0.1)
               for k, v in ref.items()} for _ in range(3)]
    flipped = {k: ref[k] - (honest[0][k] - ref[k]) for k in ref}
    huge = {k: v + np.float32(50.0) for k, v in ref.items()}
    flags, norms = update_outlier_flags(honest + [flipped], ref)
    assert flags == [False, False, False, True]   # cosine catches the flip
    flags2, _ = update_outlier_flags(honest + [huge], ref)
    assert flags2 == [False, False, False, True]  # norm catches the blowup
    flags3, _ = update_outlier_flags(honest, ref)
    assert flags3 == [False, False, False]


def _toy_train(rank, lr=0.5):
    def fn(params, round_idx):
        p = {k: np.asarray(v, np.float32) for k, v in params.items()}
        p["w"] = p["w"] + np.float32(lr) * (np.float32(rank) - p["w"])
        return p, 10.0 * rank
    return fn


def _aligned_train(rank, lr=0.1):
    """Near-parallel honest updates (real silos training on similar
    cohorts): every client steps the same direction with a tiny
    per-rank wobble, so the outlier scorer has no false positives."""
    def fn(params, round_idx):
        p = {k: np.asarray(v, np.float32) for k, v in params.items()}
        p["w"] = p["w"] + np.float32(lr) * (np.float32(1.0 + 0.01 * rank)
                                            - 0.1 * p["w"])
        return p, 10.0
    return fn


def _make_client(rank, num_clients, bp, *, spec=None, seed=0, hb=0.0,
                 train=None):
    sched = (FaultSchedule(parse_fault_spec(spec), seed) if spec else None)
    return FedAvgClientProc(rank, num_clients, train or _toy_train(rank),
                            base_port=bp, fault_schedule=sched, seed=seed,
                            heartbeat_interval=hb)


def _run_federation(server, clients, timeout=90):
    threads = [threading.Thread(target=m.run, daemon=True)
               for m in [server] + clients]
    for t in threads:
        t.start()
    assert server._done.wait(timeout=timeout), "byz protocol stalled"
    for t in threads:
        t.join(timeout=15)


def test_server_defended_round_matches_engine_dispatch():
    """In-thread 4-silo federation, silo 1 sign-flips from round 0, the
    server aggregates with trimmed_mean: the final model is bitwise-
    equal to a host replay through the SAME jitted core/robust.py
    dispatch (survivor_defended_mean) over the same uploads."""
    num_clients, rounds = 4, 2
    bp = free_port_block(num_clients + 2)
    init = {"w": np.zeros(3, np.float32)}
    spec, seed = "byz:1@0:sign_flip", 11
    server = FedAvgServer(init, rounds, num_clients, base_port=bp,
                          defense="trimmed_mean", byz_f=1)
    clients = [_make_client(c, num_clients, bp, spec=spec, seed=seed)
               for c in range(1, num_clients + 1)]
    _run_federation(server, clients)
    assert len(server.history) == rounds

    sched = FaultSchedule(parse_fault_spec(spec), seed)
    params = init
    for r in range(rounds):
        outs = {c: _toy_train(c)(params, r)
                for c in range(1, num_clients + 1)}
        trees, ns = [], []
        for c in sorted(outs):
            u, n = outs[c]
            trees.append(adversary.attack_update(sched, seed, r, c, u,
                                                 params))
            ns.append(n)
        params = survivor_defended_mean(trees, ns, params,
                                        defense="trimmed_mean", byz_f=1)
    np.testing.assert_array_equal(server.params["w"], params["w"])


def test_server_quarantines_nonfinite_uploader():
    """Silo 2 uploads NaN every round: the server hard-rejects each
    frame (counted), strikes it, quarantines it at the threshold, keeps
    completing rounds over the honest silos, and schedules the post-
    window ef_reset."""
    num_clients, rounds = 4, 4
    bp = free_port_block(num_clients + 2)
    init = {"w": np.zeros(3, np.float32)}
    server = FedAvgServer(init, rounds, num_clients, base_port=bp,
                          round_deadline=1.5, quorum=2,
                          heartbeat_timeout=30.0,
                          quarantine_rounds=2, outlier_threshold=2)
    # heartbeats keep the rejected silo EXPECTED (alive straggler, not
    # corpse) so the strike counter — not the suspicion set — is what
    # eventually excludes it; honest trains are aligned so the outlier
    # scorer never false-positives into the byz_f=1 quarantine budget
    clients = [_make_client(c, num_clients, bp, spec="byz:2@0:nonfinite",
                            seed=3, hb=0.3, train=_aligned_train(c))
               for c in range(1, num_clients + 1)]
    _run_federation(server, clients, timeout=120)
    assert len(server.history) == rounds
    assert server.byz_stats["nonfinite_rejected"] >= 2
    qs = server.byz_stats["quarantines"]
    assert qs and qs[0]["client"] == 2
    q_from = qs[0]["from_round"]
    for e in server.history:
        if q_from <= e["round"] < qs[0]["until_round"]:
            assert 2 in e.get("quarantined", [])
            assert 2 not in e["survivors"]
    # the model never saw a NaN
    assert tree_all_finite(server.params)
    # the post-window sync owes silo 2 an EF reset (delivered on the
    # next sync after the window — here training may end first, so the
    # pending marker is the observable)
    assert 2 in server._ef_reset_pending or rounds >= qs[0]["until_round"]


def test_server_all_rejected_round_advances_without_deadline():
    """Every live silo's upload bounces at the non-finite gate in the
    same round of a NO-deadline federation: with heartbeats fresh the
    suspicion monitor never fires and no timer exists, so the server
    must advance with the global model unchanged instead of waiting
    forever on its own rejection set."""
    num_clients, rounds = 2, 3
    bp = free_port_block(num_clients + 2)
    init = {"w": np.asarray([1.0, 2.0, 3.0], np.float32)}
    server = FedAvgServer(init, rounds, num_clients, base_port=bp,
                          quorum=1, heartbeat_timeout=30.0)
    spec = "byz:1@0:nonfinite,byz:2@0:nonfinite"
    clients = [_make_client(c, num_clients, bp, spec=spec, hb=0.3)
               for c in range(1, num_clients + 1)]
    _run_federation(server, clients, timeout=60)
    assert len(server.history) == rounds
    assert all(e["clients"] == 0 for e in server.history)
    assert server.byz_stats["nonfinite_rejected"] == num_clients * rounds
    # nothing was ever aggregated: the model is bitwise the init
    np.testing.assert_array_equal(server.params["w"], init["w"])


def test_secure_server_rejects_defense_and_quarantine():
    init = {"w": np.zeros(3, np.float32)}
    bp = free_port_block(4)
    with pytest.raises(ValueError, match="neither"):
        SecureFedAvgServer(init, 1, 2, base_port=bp,
                           defense="trimmed_mean")
    with pytest.raises(ValueError, match="neither"):
        SecureFedAvgServer(init, 1, 2, base_port=bp, quarantine_rounds=2)


def test_server_unknown_defense_fails_at_construction():
    init = {"w": np.zeros(3, np.float32)}
    with pytest.raises(ValueError, match="unknown defense"):
        FedAvgServer(init, 1, 4, base_port=free_port_block(6),
                     defense="trimmed")
    with pytest.raises(ValueError, match="f \\+ 3"):
        FedAvgServer(init, 1, 4, base_port=free_port_block(6),
                     defense="krum", byz_f=2)


@pytest.mark.slow
def test_multiprocess_byzantine_one_of_four(tmp_path):
    """Real OS-process federation (distributed/run.py CLI): 4 silos
    train the tiny 3D CNN, silo 1 sign-flips every round, the server
    defends with trimmed_mean + quarantine armed. All rounds complete
    and the final model is finite."""
    import json
    import subprocess
    import sys

    bp = free_port_block(16)
    common = ["--num_clients", "4", "--comm_round", "3",
              "--model", "3dcnn_tiny", "--dataset", "synthetic",
              "--synthetic_num_subjects", "24",
              "--synthetic_shape", "12", "14", "12",
              "--batch_size", "4", "--base_port", str(bp), "--force_cpu",
              "--fault_spec", "byz:1@0:sign_flip",
              "--defense", "trimmed_mean", "--byz_f", "1",
              "--quarantine_rounds", "2", "--outlier_threshold", "2",
              "--round_deadline", "60", "--quorum", "2"]
    cmd = [sys.executable, "-m",
           "neuroimagedisttraining_tpu.distributed.run"]
    server = subprocess.Popen(cmd + ["--role", "server"] + common,
                              stdout=subprocess.PIPE,
                              stderr=subprocess.STDOUT, text=True)
    procs = [subprocess.Popen(cmd + ["--role", "client", "--rank",
                                     str(r)] + common,
                              stdout=subprocess.DEVNULL,
                              stderr=subprocess.DEVNULL)
             for r in range(1, 5)]
    try:
        out, _ = server.communicate(timeout=600)
    finally:
        for p in procs:
            p.kill()
    assert server.returncode == 0, out
    res = json.loads([ln for ln in out.splitlines()
                      if ln.startswith("{")][-1])
    assert res["rounds_completed"] == 3
    assert res["defense"] == "trimmed_mean"
    assert np.isfinite(res["final_param_norm"])
