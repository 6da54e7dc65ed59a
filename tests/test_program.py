"""Round-program builder tests (ISSUE 11, engines/program.py).

Contracts:

(a) The declared engines (ditto / dpsgd / subavg / local) dispatch one
    round at a time through ONE compiled program: four rounds by hand
    are four ``dispatches`` and one ``built``, and the driver's
    ``train()`` lands on the same carried state BITWISE.
    fedavg/fedprox/salientgrads keep the same pin in
    tests/test_dispatch.py.
(b) The same engines gain ``--client_mesh`` cohort sharding: the
    sharded round from identical state matches the sequential C-loop
    (losses bitwise, state to the ~1-ulp compile-context residue —
    parallel/cohort.py contract, same bounds as tests/test_cohort.py).
(c) Fallback reporting is unified: every reason is a key of
    ``program.REASONS`` and each announcement increments the
    structured ``nidt_fallback_total{plane, engine, reason}`` counter
    (value-pinned).
"""

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from neuroimagedisttraining_tpu.config import (
    DataConfig, ExperimentConfig, FedConfig, OptimConfig,
)
from neuroimagedisttraining_tpu.core.trainer import LocalTrainer
from neuroimagedisttraining_tpu.data.federate import federate_cohort
from neuroimagedisttraining_tpu.engines import ENGINES, create_engine
from neuroimagedisttraining_tpu.engines import program as round_program
from neuroimagedisttraining_tpu.models import create_model
from neuroimagedisttraining_tpu.obs import compute as obs_compute
from neuroimagedisttraining_tpu.obs import metrics as obs_metrics
from neuroimagedisttraining_tpu.parallel.mesh import make_mesh
from neuroimagedisttraining_tpu.utils.logging import ExperimentLogger

ULP_RTOL = 1e-6
ULP_ATOL = 1e-6


def _engine(tmp_path, cohort, algorithm="ditto", comm_round=4,
            freq=4, tag="p", epochs=1, client_mesh=0, seq=False,
            donate=True, val_fraction=0.0, **fed_kw):
    cfg = ExperimentConfig(
        model="3dcnn_tiny", num_classes=1, algorithm=algorithm,
        data=DataConfig(dataset="synthetic", partition_method="site",
                        val_fraction=val_fraction),
        optim=OptimConfig(lr=1e-3, batch_size=8, epochs=epochs),
        fed=FedConfig(client_num_in_total=4, comm_round=comm_round,
                      frequency_of_the_test=freq,
                      client_mesh=client_mesh, **fed_kw),
        log_dir=str(tmp_path), tag=tag)
    mesh = make_mesh()
    trainer = LocalTrainer(create_model(cfg.model, num_classes=1),
                           cfg.optim, num_classes=1)
    log = ExperimentLogger(str(tmp_path), "synthetic", cfg.identity(),
                           console=False)
    fed, _ = federate_cohort(cohort, partition_method="site", mesh=mesh,
                             val_fraction=val_fraction)
    eng = create_engine(algorithm, cfg, fed, trainer, mesh=mesh,
                        logger=log)
    eng._donate = donate
    if seq:
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


# ---------------------------------------------------------------------------
# per-engine sequential references / initial carries
# ---------------------------------------------------------------------------

def _init_carry(eng):
    gs = eng.init_global_state()
    if eng.name == "local":
        per = eng.broadcast_states(gs, eng.num_clients)
        return (per.params, per.batch_stats)
    if eng.name in ("ditto", "salientgrads"):
        per = eng.broadcast_states(gs, eng.num_clients)
        return (gs.params, gs.batch_stats, per.params, per.batch_stats)
    if eng.name == "subavg":
        from neuroimagedisttraining_tpu.ops.masks import ones_mask

        masks = eng.broadcast_states(ones_mask(gs.params),
                                     eng.num_clients)
        return (gs.params, gs.batch_stats, masks)
    if eng.name == "dpsgd":
        per = eng.broadcast_states(gs, eng.num_clients)
        return (per.params, per.batch_stats)
    return (gs.params, gs.batch_stats)


def _one_round(eng, carry, r):
    """One dispatch through the engine's legacy round adapter;
    returns (new_carry, loss)."""
    lr = eng.round_lr(r)
    if eng.name == "dpsgd":
        M_np = eng.mixing_matrix(r)
        plan, plan_arrays = eng.gossip_plan(M_np)
        rngs = eng.per_client_rngs(r, np.arange(eng.num_clients))
        out = eng._round_jit_for(plan)(*carry, eng.data,
                                       jnp.asarray(M_np), rngs, lr,
                                       plan_arrays)
        return out[:2], out[4]
    if eng.name == "local":
        rngs = eng.per_client_rngs(r, np.arange(eng.num_clients))
        out = eng._round_jit(*carry, eng.data, rngs, lr)
        return out[:2], out[2]
    sampled = eng.client_sampling(r)
    rngs = eng.per_client_rngs(r, sampled)
    n = len(carry)
    out = eng._round_jit(*carry, eng.data, jnp.asarray(sampled),
                         rngs, lr)
    return out[:n], out[n]


# ---------------------------------------------------------------------------
# (a) four rounds, four dispatches, one compiled program; train() == by hand
# ---------------------------------------------------------------------------

# tier-1 window budget (PR 2/7/9 precedent): the heavy bitwise pins ride
# the full suite; tier-1 keeps the cheap fallback/counter/reason pins
# below, tests/test_dispatch.py's fedavg case of this pin and
# tests/test_checkpoint.py's resume pins of the same engines
@pytest.mark.parametrize("algorithm,fed_kw,keys", [
    pytest.param("ditto", {"frac": 0.5},
                 {"params": 0, "personal_params": 2},
                 marks=pytest.mark.slow),
    pytest.param("subavg", {"frac": 0.5}, {"params": 0, "mask_pers": 2},
                 marks=pytest.mark.slow),
    pytest.param("dpsgd", {"cs": "ring", "frac": 0.5},
                 {"personal_params": 0}, marks=pytest.mark.slow),
    pytest.param("dpsgd", {"cs": "random", "frac": 0.5},
                 {"personal_params": 0}, marks=pytest.mark.slow),
    # ROADMAP 1(a): the local engine's trivial carry on the builder
    pytest.param("local", {}, {"personal_params": 0},
                 marks=pytest.mark.slow),
    # ROADMAP 1(b): the secure-quant codec-family stage in the round
    pytest.param("fedavg", {"frac": 0.5, "secure_quant": True,
                            "secure_quant_field_bits": 32},
                 {"params": 0}, marks=pytest.mark.slow),
], ids=["ditto", "subavg", "dpsgd-ring", "dpsgd-random", "local",
        "fedavg-secure_quant"])
def test_sequential_rounds_one_program(tmp_path, synthetic_cohort,
                                       algorithm, fed_kw, keys):
    """Four rounds dispatched by hand are four invocations of ONE
    compiled program (``program.built`` / ``program.dispatches``, and
    the scrapeable ``nidt_compiles_total`` moving in the same
    increment), and the driver's ``train()`` reaches the same carried
    state bitwise, with the same per-round losses where it logs them."""
    seq = _engine(tmp_path, synthetic_cohort, algorithm,
                  tag=f"sq-{algorithm}-{len(fed_kw)}", **fed_kw)
    carry = _init_carry(seq)
    built0 = seq.program.built
    ctr0 = obs_compute.compiles_total(engine=algorithm)
    losses = []
    for r in range(4):
        carry, loss = _one_round(seq, carry, r)
        losses.append(float(loss))
    assert seq.program.dispatches == 4
    # dpsgd's random topology keys its program by the round's plan spec
    n_built = seq.program.built - built0
    assert n_built == 1 or fed_kw.get("cs") == "random"
    assert obs_compute.compiles_total(engine=algorithm) - ctr0 == n_built

    drv = _engine(tmp_path, synthetic_cohort, algorithm,
                  tag=f"dr-{algorithm}-{len(fed_kw)}", **fed_kw)
    res = drv.train()
    assert drv.program.dispatches == 4
    assert drv.program.built == n_built
    for key, i in keys.items():
        _assert_trees_bitwise(res[key], carry[i])
    assert [h["round"] for h in res["history"]] == [0, 3]
    assert [h["train_loss"] for h in res["history"]] == \
        [losses[0], losses[3]]


# ---------------------------------------------------------------------------
# (b) cohort sharding for the newly-declared engines
# ---------------------------------------------------------------------------

def _one_sharded_round(eng, r=0):
    carry = _init_carry(eng)
    lr = eng.round_lr(r)
    if eng.name == "local":
        # no sampling: the full (mesh-padded) cohort trains; _round_jit
        # IS the sharded program when _cohort_on
        rngs = eng.per_client_rngs(r, np.arange(eng.num_clients))
        return eng._round_jit(*carry, eng.data, rngs, lr)
    if eng.name == "dpsgd":
        M_np = eng.mixing_matrix(r)
        plan, plan_arrays = eng.gossip_plan(M_np)
        rngs = eng.per_client_rngs(r, np.arange(eng.num_clients))
        return eng._round_jit_for(plan)(*carry, eng.data,
                                        jnp.asarray(M_np), rngs, lr,
                                        plan_arrays)
    sampled = eng.client_sampling(r)
    ids, round_prog = eng._cohort_round_prog(sampled)  # dealt, as train()
    rngs = eng.per_client_rngs(r, ids)
    return round_prog(*carry, eng.data, jnp.asarray(ids), rngs, lr)


@pytest.mark.parametrize("algorithm,loss_i,epochs", [
    pytest.param("ditto", 4, 1, marks=pytest.mark.slow),
    pytest.param("subavg", 3, 2, marks=pytest.mark.slow),
    pytest.param("dpsgd", 4, 1, marks=pytest.mark.slow),
    pytest.param("local", 2, 1, marks=pytest.mark.slow),
])
def test_sharded_round_vs_sequential_loop(tmp_path, synthetic_cohort,
                                          algorithm, loss_i, epochs):
    """The sharded round vs the sequential C-loop reference
    (_cohort_sequential): per-client work identical by construction, so
    the round loss is bitwise (ditto/dpsgd; subavg's two-phase masked
    composite is allowed the same 1-ulp seam as salientgrads' masked
    round) and state agrees to the ~1-ulp compile-context residue
    (parallel/cohort.py). subavg runs epochs=2 so the hoisted two-call
    permutation chain (epoch-1 + tail) is load-bearing; an rng-replay
    drift would show as 1e-0-level loss divergence."""
    eng_sh = _engine(tmp_path, synthetic_cohort, algorithm,
                     client_mesh=8, epochs=epochs, donate=False,
                     tag=f"sh{algorithm}")
    eng_sq = _engine(tmp_path, synthetic_cohort, algorithm,
                     client_mesh=8, epochs=epochs, donate=False,
                     seq=True, tag=f"sq{algorithm}")
    assert eng_sh._cohort_on and eng_sq._cohort_on
    out_sh = _one_sharded_round(eng_sh)
    out_sq = _one_sharded_round(eng_sq)
    if algorithm == "subavg":
        np.testing.assert_allclose(float(out_sh[loss_i]),
                                   float(out_sq[loss_i]), rtol=3e-7)
    else:
        np.testing.assert_array_equal(np.asarray(out_sh[loss_i]),
                                      np.asarray(out_sq[loss_i]))
    _assert_trees_ulp(out_sh, out_sq)


# ---------------------------------------------------------------------------
# (c) unified fallback reporting
# ---------------------------------------------------------------------------

def test_reason_table_has_no_orphans(tmp_path, synthetic_cohort):
    """Single source of truth: every engine's fallback keys resolve in
    REASONS, and the table has no plane a retired driver left behind
    (the lint rule round-program-reason rejects ad-hoc strings)."""
    seen = set()
    for name, cls in ENGINES.items():
        if name in ("sailentgrads", "sub-fedavg"):  # registry aliases
            continue
        kw = {"val_fraction": 0.25} if name == "fedfomo" else {}
        eng = _engine(tmp_path, synthetic_cohort, name, tag=f"rt-{name}",
                      **kw)
        ckey = eng.program.cohort_fallback_key()
        if ckey is not None:
            assert ckey in round_program.REASONS, (name, ckey)
            assert round_program.REASONS[ckey][0] == "sharding"
            seen.add(ckey)
    assert seen  # this mesh-free matrix announces at least one
    assert {plane for plane, _ in round_program.REASONS.values()} == \
        {"sharding", "fold", "recipe"}
    assert len(round_program.REASONS) == 16


def test_fallback_counter_value_pinned(tmp_path, synthetic_cohort):
    """Every announced fallback increments
    nidt_fallback_total{plane, engine, reason} — scrapeable, not
    grep-able. Constructing a --client_mesh fedfomo engine announces
    exactly one sharding fallback with the table key."""
    c = obs_metrics.counter(
        "nidt_fallback_total", labelnames=("plane", "engine", "reason"))
    # local DECLARES its round (ROADMAP 1(a)) and ARMS sharding on the
    # mesh-padded cohort, so the undeclared fedfomo carries this pin
    sh_labels = dict(plane="sharding", engine="fedfomo",
                     reason="no-sharded-body")
    before_sh = c.get(**sh_labels)
    eng = _engine(tmp_path, synthetic_cohort, "fedfomo",
                  client_mesh=8, val_fraction=0.25, tag="ctr2")
    assert not eng._cohort_on
    assert c.get(**sh_labels) == before_sh + 1.0
    # the declared local engine arms instead of announcing
    eng_l = _engine(tmp_path, synthetic_cohort, "local",
                    client_mesh=8, tag="ctr3")
    assert eng_l._cohort_on


# ---------------------------------------------------------------------------
# (d) --secure_quant as an in-process CODEC-family stage (ROADMAP 1(b))
# ---------------------------------------------------------------------------


def _sq_host_fold(upload, ref, w, spec, scales, shift):
    """THE reference the jitted stage is pinned against: integer fold
    weights from the identical f32 formula, ``encode_secure_quant``
    frames folded through a ``SlotAccumulator`` (privacy/secure_quant's
    host fold — masks cancel exactly mod p), finalized and divided by
    the integer mass in f32."""
    from neuroimagedisttraining_tpu.privacy import (
        SlotAccumulator, encode_secure_quant,
    )

    w = np.asarray(w, np.float32)
    wn = w / np.float32(np.max(w))
    wi = np.maximum(np.rint(wn * np.float32(1 << shift)),
                    np.float32(1.0)).astype(np.int64)
    denom = np.float32(wi.sum())
    acc = SlotAccumulator(spec, like=ref)
    C = int(wi.size)
    for c in range(C):
        u_c = jax.tree.map(lambda t: np.asarray(t)[c], upload)
        frame = encode_secure_quant(u_c, 1.0, spec,
                                    np.random.default_rng(1000 + c),
                                    scales=scales)
        acc.fold(frame, weight_int=int(wi[c]))
    host = acc.finalize(like=ref, rescale=1.0, scales=scales)
    return jax.tree.map(
        lambda t: (np.asarray(t, np.float32) / denom).astype(t.dtype),
        host)


def test_secure_quant_stage_bitwise_vs_host_fold():
    """The satellite's core pin: the jitted in-process secure-quant
    stage (program.secure_quant_aggregate) produces BITWISE the
    aggregate of privacy.secure_quant's host fold — SlotAccumulator
    over encode_secure_quant frames at the same (p, frac_bits, scales,
    integer weights). Exact field/integer algebra plus single
    correctly-rounded f32 ops on both sides is what makes the equality
    exact, not approximate. Includes a BatchNorm-magnitude leaf (the
    leaf_scales path) and a NaN row (quantizes to the neutral zero
    residue on both sides)."""
    import types

    from neuroimagedisttraining_tpu.privacy import QuantSpec, leaf_scales

    rng = np.random.default_rng(7)
    C = 5
    upload = {
        "params": {
            "k": (3.0 * rng.standard_normal((C, 3, 4))).astype(
                np.float32),
            "b": rng.standard_normal((C, 7)).astype(np.float32)},
        "batch_stats": {
            "m": (40.0 * rng.standard_normal((C, 6))).astype(
                np.float32)}}
    upload["params"]["b"][2, 3] = np.nan  # neutral zero residue
    ref = {
        "params": {"k": rng.standard_normal((3, 4)).astype(np.float32),
                   "b": rng.standard_normal(7).astype(np.float32)},
        "batch_stats": {
            "m": (50.0 * rng.standard_normal(6)).astype(np.float32)}}
    w = np.asarray([8.0, 11.0, 9.0, 12.0, 10.0], np.float32)
    losses = np.asarray([0.5, 0.6, 0.4, 0.7, 0.55], np.float32)
    spec = QuantSpec.from_bits(32, 10, 3)
    scales = leaf_scales(ref)
    shift = 6
    eng = types.SimpleNamespace(
        cfg=types.SimpleNamespace(
            fed=types.SimpleNamespace(defense_type="none")),
        sq_spec=spec, sq_scales=scales, sq_weight_shift=shift)
    params, bstats, mean_loss, n_bad = jax.jit(
        lambda u, rf, ww, ls: round_program.secure_quant_aggregate(
            eng, u, rf, ww, ls))(upload, ref, jnp.asarray(w),
                                 jnp.asarray(losses))
    host = _sq_host_fold(upload, ref, w, spec, scales, shift)
    _assert_trees_bitwise({"params": params, "batch_stats": bstats},
                          host)
    assert int(n_bad) == 1  # counted, not gated — protocol-faithful


def test_secure_quant_engine_round_near_plain(tmp_path,
                                              synthetic_cohort):
    """Wiring sanity: a fedavg round with --secure_quant armed agrees
    with the plain round to quantization error (the per-leaf scale's
    2^-frac_bits lattice), not more — the stage replaced the tail, it
    did not corrupt it. The four-round driver pin rides the slow
    matrix above."""
    pl = _engine(tmp_path, synthetic_cohort, "fedavg", frac=0.5,
                 tag="sqp")
    sq = _engine(tmp_path, synthetic_cohort, "fedavg", frac=0.5,
                 secure_quant=True, secure_quant_field_bits=32,
                 tag="sqs")
    assert sq.sq_spec is not None and sq.sq_weight_shift >= 1
    pcarry, _ = _one_round(pl, _init_carry(pl), 0)
    scarry, _ = _one_round(sq, _init_carry(sq), 0)
    for a, b in zip(jax.tree.leaves(scarry), jax.tree.leaves(pcarry)):
        np.testing.assert_allclose(np.asarray(a, np.float64),
                                   np.asarray(b, np.float64),
                                   atol=0.05, rtol=0)


def test_secure_quant_startup_rejections(tmp_path, synthetic_cohort):
    """The privacy-plane matrix fails at STARTUP, never mid-round:
    engines without the default tail, the wire codec, order-statistic
    defenses, and a too-small field are all named errors."""
    with pytest.raises(ValueError, match="does not simulate"):
        _engine(tmp_path, synthetic_cohort, "dpsgd", secure_quant=True,
                secure_quant_field_bits=32, tag="sjd")
    with pytest.raises(ValueError, match="wire_codec"):
        _engine(tmp_path, synthetic_cohort, "fedavg", secure_quant=True,
                secure_quant_field_bits=32, wire_codec="delta+quant",
                tag="sjw")
    with pytest.raises(ValueError, match="clip family"):
        _engine(tmp_path, synthetic_cohort, "fedavg", secure_quant=True,
                secure_quant_field_bits=32, defense_type="trimmed_mean",
                tag="sjt")
    with pytest.raises(ValueError, match="field_bits 32"):
        _engine(tmp_path, synthetic_cohort, "fedavg", secure_quant=True,
                secure_quant_field_bits=16, tag="sjf")


