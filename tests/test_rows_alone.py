"""A client row that runs alone stops at its own last step, and rows are
dealt to chips by their step counts (ISSUE 26).

(a) ``LocalTrainer.local_train`` under ``rows_alone()`` (the loop to the
    row's own traced bound) against the batched ``where`` form on the same
    row: the mean loss BITWISE (each real step's loss lands at its own
    index of the same zero-filled vector, so the sum is the same sum),
    the trained state to the compile-context residue tests/test_cohort.py
    states (a different module tiles a handful of reductions differently;
    on this CPU it reads 0), the trained ``cs.rng`` bitwise and equal to
    ``RoundCtx.rng_after_local_train`` (a skipped iteration still consumes
    its split).
(b) ``cohort.deal_rows`` as cases.
(c) a dealt sharded round on a 4-device mesh against the same compiled
    program handed the identity deal: every output BITWISE.
(d) the stacked placement's local-train loop is the parent commit's.
"""

import collections
import hashlib
import types

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from neuroimagedisttraining_tpu.config import (
    DataConfig, ExperimentConfig, FedConfig, OptimConfig,
)
from neuroimagedisttraining_tpu.core.trainer import (
    LocalTrainer, scan_steps,
)
from neuroimagedisttraining_tpu.data.federate import federate_cohort
from neuroimagedisttraining_tpu.engines import create_engine
from neuroimagedisttraining_tpu.engines import program as round_program
from neuroimagedisttraining_tpu.models import OLMoE3D, create_model
from neuroimagedisttraining_tpu.parallel import cohort
from neuroimagedisttraining_tpu.parallel.mesh import make_mesh
from neuroimagedisttraining_tpu.utils.logging import ExperimentLogger

#: the compile-context residue between two modules that run the same
#: per-client program, as tests/test_cohort.py states it
ULP_RTOL = 1e-6
ULP_ATOL = 1e-6
BATCH, EPOCHS, MAX_SAMPLES = 4, 2, 12  # 3 iterations an epoch, 6 in all
#: a full row, a 2-step row, a partial last batch, a mesh-pad row
ROWS = {"full": 12, "two_steps": 8, "partial_last_batch": 6, "pad_row": 0}
SMALL_OLMOE = dict(hidden_size=64, num_heads=4, num_experts=8,
                   experts_per_token=2, expert_width=32, patch=8)


def _trainer(has_aux: bool):
    model = (OLMoE3D(**SMALL_OLMOE) if has_aux
             else create_model("3dcnn_tiny", num_classes=1))
    shape = (16, 16, 16) if has_aux else (12, 14, 12)
    tr = LocalTrainer(model, OptimConfig(lr=1e-2, batch_size=BATCH,
                                         epochs=EPOCHS), 1)
    assert tr.has_aux == has_aux
    return tr, shape


@pytest.fixture(scope="module", params=[False, True],
                ids=["logits_only", "has_aux"])
def both_forms(request):
    """``(trainer, state, X, y, where_form, alone_form)``: one row's
    ``local_train`` jitted twice, batched form and alone."""
    tr, shape = _trainer(request.param)
    r = np.random.RandomState(3)
    X = jnp.asarray(r.randint(0, 256, (MAX_SAMPLES,) + shape), jnp.uint8)
    y = jnp.asarray(r.randint(0, 2, (MAX_SAMPLES,)), jnp.int32)
    cs = tr.init_client_state(jax.random.key(1),
                              jnp.zeros((1,) + shape, jnp.float32))

    def train(cs, n):
        return tr.local_train(cs, X, y, n, jnp.float32(1e-2), epochs=EPOCHS,
                              batch_size=BATCH, max_samples=MAX_SAMPLES)

    def alone(cs, n):
        with tr.rows_alone():
            return train(cs, n)

    return tr, cs, jax.jit(train), jax.jit(alone)


@pytest.mark.parametrize("row", list(ROWS))
def test_row_alone_is_the_where_form(both_forms, row):
    tr, cs, where_form, alone_form = both_forms
    n = jnp.int32(ROWS[row])
    cs_w, loss_w, *tok_w = where_form(cs, n)
    cs_a, loss_a, *tok_a = alone_form(cs, n)
    np.testing.assert_array_equal(np.asarray(loss_w), np.asarray(loss_a))
    for a, b in zip(tok_w, tok_a):  # integers: exact in any order
        np.testing.assert_array_equal(np.asarray(a), np.asarray(b))
    state = lambda c: (c.params, c.batch_stats, c.opt_state)
    for a, b in zip(jax.tree.leaves(state(cs_w)),
                    jax.tree.leaves(state(cs_a))):
        np.testing.assert_allclose(np.asarray(a), np.asarray(b),
                                   rtol=ULP_RTOL, atol=ULP_ATOL)
    if ROWS[row] == 0:
        # a pad row runs no step at all: its state is the state it got
        for a, b in zip(jax.tree.leaves(state(cs)),
                        jax.tree.leaves(state(cs_a))):
            np.testing.assert_array_equal(np.asarray(a), np.asarray(b))
        assert float(loss_a) == 0.0
    key = lambda k: np.asarray(jax.random.key_data(k))
    np.testing.assert_array_equal(key(cs_w.rng), key(cs_a.rng))
    # the replay subavg's two-call stage hoists its tail permutations by
    eng = types.SimpleNamespace(
        cfg=types.SimpleNamespace(optim=tr.optim_cfg),
        _max_samples=lambda: MAX_SAMPLES)
    ctx = round_program.RoundCtx(eng, None, {}, None, {}, None, None, None,
                                 None, None, None, {}, None, None, False)
    replay = ctx.rng_after_local_train(cs.rng[None], EPOCHS)
    np.testing.assert_array_equal(key(replay[0]), key(cs_a.rng))


def test_alone_loop_runs_to_the_rows_own_bound():
    """The alone form is a ``while`` to a traced bound with no ``select``
    over the carried state in it; the batched form is today's scan."""
    tr, shape = _trainer(False)
    cs = jax.eval_shape(tr.init_client_state, jax.random.key(0),
                        jnp.zeros((1,) + shape, jnp.float32))
    X = jax.ShapeDtypeStruct((MAX_SAMPLES,) + shape, jnp.uint8)
    y = jax.ShapeDtypeStruct((MAX_SAMPLES,), jnp.int32)

    def train(cs, X, y, n):
        return tr.local_train(cs, X, y, n, 1e-2, epochs=EPOCHS,
                              batch_size=BATCH, max_samples=MAX_SAMPLES)

    def alone(*a):
        with tr.rows_alone():
            return train(*a)

    n = jax.ShapeDtypeStruct((), jnp.int32)
    jaxpr_w = jax.make_jaxpr(train)(cs, X, y, n).jaxpr
    jaxpr_a = jax.make_jaxpr(alone)(cs, X, y, n).jaxpr
    hist_w, hist_a = _histogram(jaxpr_w), _histogram(jaxpr_a)
    assert hist_w["while"] == 0 and hist_w["cond"] == 0
    assert hist_a["while"] == 1 and hist_a["cond"] == 0
    # the selects over arrays (index arithmetic selects scalars): the
    # batched form has one more for every array leaf of the state
    leaves = sum(x.ndim > 0 for x in jax.tree.leaves(
        (cs.params, cs.batch_stats, cs.opt_state)))
    assert leaves > 0
    assert (_histogram(jaxpr_w, arrays_only=True)["select_n"]
            == _histogram(jaxpr_a, arrays_only=True)["select_n"] + leaves)


# ---------------------------------------------------------------------------
# (b) the deal
# ---------------------------------------------------------------------------

def _chip_sums(steps, order, chips):
    return np.asarray(steps)[order].reshape(chips, -1).sum(axis=1).tolist()


#: ISSUE 22's 21-site table at batch 16, the 80% training split
SITES21 = [716, 683, 650, 622, 600, 580, 560, 540, 520, 500, 480, 460, 440,
           420, 400, 380, 360, 340, 320, 300, 280]


@pytest.mark.parametrize("steps,chips,want_sums,identity", [
    # the mesh cell: 180/141/116/90/77/52/39/26 subjects at batch 16
    ([12, 9, 8, 6, 5, 4, 3, 2], 4, [14, 12, 12, 11], False),
    # equal sites keep the sampler's order
    ([6] * 8, 4, [12, 12, 12, 12], True),
    # one row a chip: nothing to deal
    ([9, 3, 7, 1], 4, [9, 3, 7, 1], True),
    # a chip's slot limit: the seven short rows cannot all avoid chip 0
    ([10, 1, 1, 1, 1, 1, 1, 1], 4, [11, 2, 2, 2], True),
    # already balanced in the sampler's order
    ([5, 1, 4, 2, 3, 3, 3, 3], 4, [6, 6, 6, 6], True),
    ([int(np.ceil(n / 16)) for n in SITES21] + [0] * 3, 4, None, False),
    ([int(np.ceil(n / 16)) for n in SITES21] + [0] * 3, 8, None, False),
], ids=["mesh_cell", "equal_sites", "one_row_a_chip", "slot_limit",
        "balanced_already", "sites21_on_4", "sites21_on_8"])
def test_deal_rows(steps, chips, want_sums, identity):
    order = cohort.deal_rows(np.asarray(steps), chips)
    # a permutation of the whole padded set, pad rows (zero steps)
    # included: every chip keeps exactly its C / D slots
    assert sorted(order.tolist()) == list(range(len(steps)))
    assert (order.tolist() == list(range(len(steps)))) == identity
    sums = _chip_sums(steps, order, chips)
    if want_sums is not None:
        assert sums == want_sums
    as_sampled = _chip_sums(steps, np.arange(len(steps)), chips)
    assert max(sums) <= max(as_sampled)
    if not identity:
        assert max(sums) < max(as_sampled)
        # longest-first dealing is within 4/3 of the floor
        assert max(sums) <= -(-4 * max(-(-sum(steps) // chips),
                                        max(steps)) // 3)


# ---------------------------------------------------------------------------
# (c) dealt == undealt, (d) the stacked program is the parent's
# ---------------------------------------------------------------------------

@pytest.fixture(scope="module")
def skewed_cohort():
    """8 sites of 60/45/40/30/25/20/15/10 subjects: at batch 4 the 80%
    splits are 12/9/8/6/5/4/3/2 steps an epoch, the mesh cell's skew."""
    sizes = [60, 45, 40, 30, 25, 20, 15, 10]
    r = np.random.RandomState(11)
    n = sum(sizes)
    return {"X": r.randint(0, 256, (n, 12, 14, 12)).astype(np.uint8),
            "y": r.randint(0, 2, (n,)).astype(np.int8),
            "site": np.repeat(np.arange(len(sizes)), sizes).astype(np.int16)}


def _engine(tmp_path, data, algorithm, tag, client_mesh=4):
    cfg = ExperimentConfig(
        model="3dcnn_tiny", num_classes=1, algorithm=algorithm,
        data=DataConfig(dataset="synthetic", partition_method="site"),
        optim=OptimConfig(lr=1e-3, batch_size=4, epochs=2),
        fed=FedConfig(client_num_in_total=8, comm_round=1,
                      client_mesh=client_mesh),
        log_dir=str(tmp_path), tag=tag)
    mesh = make_mesh(num_devices=client_mesh) if client_mesh else None
    trainer = LocalTrainer(create_model(cfg.model, num_classes=1),
                           cfg.optim, num_classes=1)
    fed, _ = federate_cohort(data, partition_method="site", mesh=mesh)
    eng = create_engine(algorithm, cfg, fed, trainer, mesh=mesh,
                        logger=ExperimentLogger(
                            str(tmp_path), "synthetic", cfg.identity(),
                            console=False))
    eng._donate = False
    return eng


def _round(eng, masks=None):
    gs = eng.init_global_state()
    sampled = eng.client_sampling(0)
    ids, prog = eng._cohort_round_prog(sampled)
    rngs = eng.per_client_rngs(0, ids)
    if eng.name != "salientgrads":
        return ids, prog(gs.params, gs.batch_stats, eng.data,
                         jnp.asarray(ids), rngs, eng.round_lr(0))
    per = eng.broadcast_states(gs, eng.num_clients)
    return ids, prog(gs.params, gs.batch_stats, per.params,
                     per.batch_stats, eng.data, masks, jnp.asarray(ids),
                     rngs, eng.round_lr(0))


@pytest.mark.parametrize("algorithm", ["fedavg", "salientgrads"])
def test_dealt_round_equals_undealt(tmp_path, skewed_cohort, monkeypatch,
                                    algorithm):
    """The dealt sharded round against the round in the sampler's order:
    the SAME compiled program (the deal is an operand), every row the
    same unbatched per-client program on whichever chip it lands, the
    trained stacks back in the sampler's order before the aggregate: so
    every output is bitwise, with no ulp of room. SalientGrads' phase 1
    never goes through the deal: one mask serves both."""
    eng = _engine(tmp_path, skewed_cohort, algorithm, "deal")
    masks = None
    if algorithm == "salientgrads":
        gs = eng.init_global_state()
        masks, _ = eng.generate_global_mask(gs.params, gs.batch_stats)
        masks2, _ = eng.generate_global_mask(gs.params, gs.batch_stats)
        for a, b in zip(jax.tree.leaves(masks), jax.tree.leaves(masks2)):
            np.testing.assert_array_equal(np.asarray(a), np.asarray(b))
    ids_dealt, out_dealt = _round(eng, masks)
    assert ids_dealt.tolist() == [0, 7, 1, 6, 2, 5, 3, 4]
    monkeypatch.setattr(cohort, "deal_rows",
                        lambda steps, chips: np.arange(len(steps)))
    ids_plain, out_plain = _round(eng, masks)
    assert ids_plain.tolist() == list(range(8))
    assert eng.program.built == 1  # another deal is no recompile
    for a, b in zip(jax.tree.leaves(out_dealt), jax.tree.leaves(out_plain)):
        np.testing.assert_array_equal(np.asarray(a), np.asarray(b))


def test_dealt_counts_on_the_dispatch_span(tmp_path, skewed_cohort):
    """``steps_run`` is what the placement executes; ``chip_steps_*`` the
    dealt load (the mesh cell's 28 and 24.5, at this batch size)."""
    from neuroimagedisttraining_tpu.obs import trace as obs_trace

    eng = _engine(tmp_path, skewed_cohort, "fedavg", "cnt")
    obs_trace.arm()
    try:
        sampled = eng.client_sampling(0)
        eng._note_round_counts(sampled, 8)
        got = dict(eng._dispatch_counts)
    finally:
        obs_trace.disarm()
        eng._dispatch_counts = {}
    assert got["placement"] == "sharded"
    assert got["steps_real"] == got["steps_run"] == 98
    assert got["steps_skipped"] == 8 * scan_steps(2, 4, 48) - 98 == 94
    assert got["chip_steps_max"] == 28
    assert got["chip_steps_mean"] == 24.5


def _nested(eqn):
    """The jaxprs an equation holds (a scan's body, a jit's callee ...)."""
    for v in eqn.params.values():
        for sub in (v if isinstance(v, (list, tuple)) else (v,)):
            inner = getattr(sub, "jaxpr", sub)
            if hasattr(inner, "eqns"):
                yield inner


def _histogram(jaxpr, into=None, arrays_only=False):
    """Primitive counts of a jaxpr and everything nested in it
    (``arrays_only``: equations whose first output has a dimension)."""
    into = collections.Counter() if into is None else into
    for eqn in jaxpr.eqns:
        if not arrays_only or eqn.outvars[0].aval.ndim > 0:
            into[eqn.primitive.name] += 1
        for inner in _nested(eqn):
            _histogram(inner, into, arrays_only)
    return into


def _scans(jaxpr, out):
    for eqn in jaxpr.eqns:
        if eqn.primitive.name == "scan":
            out.append(eqn)
        for inner in _nested(eqn):
            _scans(inner, out)
    return out


#: the local-train scan body of the stacked FedAvg round below, traced at
#: the parent commit (7a23d62, jax 0.9.0) by this file's own helpers with
#: the parent's package on the path: the ``where`` form. Its primitive
#: counts, and the sha256 of its printed jaxpr (the same equations in the
#: same order). A PR that changes the step on purpose records both again
#: (the failing assertion prints them).
STACKED_STEP_AT_PARENT = {
    'abs': 2, 'add': 73, 'add_any': 7, 'and': 4, 'bitcast_convert_type': 1,
    'broadcast_in_dim': 122, 'concatenate': 1, 'conv_general_dilated': 5,
    'convert_element_type': 9, 'custom_jvp_call': 5, 'div': 29,
    'dot_general': 6, 'eq': 15, 'exp': 6, 'gather': 4, 'gt': 3, 'iota': 1,
    'jit': 54, 'log1p': 2, 'lt': 14, 'max': 10, 'mul': 106, 'ne': 10,
    'neg': 30, 'or': 1, 'random_bits': 1, 'random_fold_in': 1,
    'random_split': 1, 'reduce_sum': 32, 'reduce_window_max': 2, 'rem': 4,
    'reshape': 45, 'rev': 1, 'rsqrt': 2, 'select_and_scatter_add': 2,
    'select_n': 71, 'shift_right_logical': 1, 'sign': 2, 'slice': 3,
    'sqrt': 1, 'square': 4, 'squeeze': 4, 'sub': 15, 'transpose': 9}
STACKED_STEP_SHA256_AT_PARENT = (
    "b774a6d4c15b2f2ca406998cf95da1af2a952aaf75f47c18c3c22b48417e7e3b")


def test_stacked_round_program_is_the_parents(tmp_path, skewed_cohort):
    """The one-chip cells train under the client-axis ``vmap``: their
    local-train loop keeps the ``where`` form, equation for equation what
    the parent commit traced (no ``cond``, no ``while``, a select for
    every leaf of the carried state and one for the loss), so they cannot
    drift through the code a row that runs alone takes."""
    eng = _engine(tmp_path, skewed_cohort, "fedavg", "stk", client_mesh=0)
    assert eng.program.placement == round_program.STACKED
    gs = eng.init_global_state()
    sampled = eng.client_sampling(0)
    jaxpr = jax.make_jaxpr(eng._round_jit.jit)(
        (gs.params, gs.batch_stats), eng.data, (), jnp.asarray(sampled),
        eng.per_client_rngs(0, sampled), eng.round_lr(0), None, None)
    total = scan_steps(2, 4, eng._max_samples())
    steps = [s for s in _scans(jaxpr.jaxpr, [])
             if s.params["length"] == total
             and _histogram(s.params["jaxpr"].jaxpr)["conv_general_dilated"]]
    assert len(steps) == 1
    hist = _histogram(steps[0].params["jaxpr"].jaxpr)
    assert hist["cond"] == 0 and hist["while"] == 0
    assert dict(hist) == STACKED_STEP_AT_PARENT
    body = str(steps[0].params["jaxpr"])
    assert (hashlib.sha256(body.encode()).hexdigest()
            == STACKED_STEP_SHA256_AT_PARENT), body
