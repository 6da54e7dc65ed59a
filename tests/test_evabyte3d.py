"""``--model evabyte3d`` against its plain reference (PR 38), on the CPU.

The program (models/evabyte3d.py: EVA attention a window at a time, the
summaries' scores merged by a common maximum; a dense gated feed-forward;
unit-offset norms; a float32 stream) against
``benchmark/reference/evabyte-abcd.py`` (one dense ``[T, T + T / c]`` mask a
head), on seeded random weights at a small size: two layers, hidden 64, 4
heads of 16, feed-forward 96, windows of 32 tokens, chunks of 4, patch 4.
Volumes of 16 x 4k x 4 voxels are ``T = 4 k`` tokens: 24 (one window), 64
(two whole windows), 76 (two whole windows and one of 12). The chip
comparison at the published widths is the builder's (PERF.md).
"""

import dataclasses
import importlib.util
import os

import flax.linen as nn
import jax
import jax.numpy as jnp
import numpy as np
import pytest

from neuroimagedisttraining_tpu.config import OptimConfig
from neuroimagedisttraining_tpu.core.trainer import LocalTrainer
from neuroimagedisttraining_tpu.models import create_model
from neuroimagedisttraining_tpu.models.evabyte3d import (
    EvaAttention, EvaByte3D, GatedMLP, Widths, eva_attention,
)
from neuroimagedisttraining_tpu.models.tokens3d import RMSNorm
from neuroimagedisttraining_tpu.ops import attention

ROOT = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
HEADS, HD, WINDOW, CHUNK = 4, 16, 32, 4
SMALL = Widths(layers=2, hidden_size=64, heads=HEADS, head_dim=HD,
               intermediate_size=96, window_size=WINDOW, chunk_size=CHUNK,
               patch=4)
CFG = {"window_size": WINDOW, "chunk_size": CHUNK, "rope_theta": 1e5,
       "rms_eps": 1e-5, "patch": 4}
B = 3
LENGTHS = (24, 64, 76)

#: float32, program against reference: the same products summed in another
#: order (a window's block against one row of the dense mask, the
#: summaries' exponentials added to the window's against one softmax over
#: both, XLA's reduction trees) through two layers. Values are of order
#: 0.01-1 and float32 carries 1.2e-7 a product. A reference whose scores
#: are rounded to bfloat16 is off by 1e-3 and fails it (asserted below).
F32_RTOL, F32_ATOL = 5e-5, 2e-6


def _load(name, path):
    spec = importlib.util.spec_from_file_location(name, path)
    mod = importlib.util.module_from_spec(spec)
    spec.loader.exec_module(mod)
    return mod


@pytest.fixture(scope="module")
def ref():
    return _load("ref_evabyte", os.path.join(
        ROOT, "benchmark", "reference", "evabyte-abcd.py"))


def _shape(tokens):
    return (16, tokens, 4)  # 4 x tokens / 4 x 1 patches of 4^3


def _batch(seed, tokens):
    r = np.random.RandomState(seed)
    x = r.randint(0, 256, (B,) + _shape(tokens)).astype(np.uint8)
    y = r.randint(0, 2, (B,)).astype(np.int32)
    return jnp.asarray(x), jnp.asarray(y)


def _params(model, seed, tokens):
    """Seeded weights away from their start: norm offsets, ``phi`` and
    ``mu`` of order 0.1, projections large enough that scores differ."""
    x = jnp.zeros((1,) + _shape(tokens) + (1,))
    params = model.init(jax.random.key(seed), x)["params"]
    leaves, tree = jax.tree.flatten(params)
    keys = jax.random.split(jax.random.key(seed + 100), len(leaves))
    return jax.tree.unflatten(tree, [
        a + 0.15 * jax.random.normal(k, a.shape) for a, k in
        zip(leaves, keys)])


def _close(a, b, rtol=F32_RTOL, atol=F32_ATOL):
    np.testing.assert_allclose(np.asarray(a, np.float64),
                               np.asarray(b, np.float64), rtol=rtol,
                               atol=atol)


def _qkv(seed, tokens, heads=HEADS):
    keys = jax.random.split(jax.random.key(seed), 5)
    q, k, v = (jax.random.normal(kk, (2, tokens, heads, HD))
               for kk in keys[:3])
    phi, mu = (0.3 * jax.random.normal(kk, (heads, HD)) for kk in keys[3:])
    return q, k, v, phi, mu


# ---------- (a) the model against the reference ----------

@pytest.mark.parametrize("tokens", LENGTHS)
def test_float32_logits_loss_and_every_gradient(ref, tokens):
    model = EvaByte3D(widths=SMALL)
    params = _params(model, tokens, tokens)
    x, y = _batch(tokens, tokens)

    def loss(p):
        logits = model.apply({"params": p},
                             x.astype(jnp.float32)[..., None])
        return jnp.mean(ref.ops.bce_with_logits(logits, y)), logits

    (got_loss, got), got_grads = jax.value_and_grad(loss, has_aux=True)(
        params)
    want = ref.forward(params, {}, x, cfg=CFG)
    want_loss, want_grads = jax.value_and_grad(
        lambda p: ref.training_loss(p, {}, x, y, cfg=CFG))(params)
    _close(got, want)
    _close(got_loss, want_loss)
    flat = lambda t: jax.tree_util.tree_flatten_with_path(t)[0]
    assert len(flat(got_grads)) == len(flat(want_grads)) == 4 + 11 * 2
    for (path, g), (_, w) in zip(flat(got_grads), flat(want_grads)):
        scale = float(jnp.max(jnp.abs(w)))
        # phi and mu too, where a window is remote
        assert scale > 0 or (tokens <= WINDOW
                             and path[-1].key in ("phi", "mu")), path
        np.testing.assert_allclose(
            np.asarray(g), np.asarray(w), rtol=2e-4, atol=2e-5 * scale,
            err_msg=jax.tree_util.keystr(path))
    # a reference whose scores are bfloat16 is no reference
    low = ref.forward(params, {}, x, cfg=CFG,
                      q_scores=ref.ops.rounded(jnp.bfloat16))
    assert float(jnp.max(jnp.abs(low - want))) > 10 * F32_ATOL \
        + F32_RTOL * float(jnp.max(jnp.abs(want)))


def test_one_window_leaves_phi_and_mu_without_a_gradient(ref):
    """At ``T <= W`` nothing is remote: the summaries are not computed,
    and ``phi`` and ``mu`` receive exactly zero."""
    model = EvaByte3D(widths=SMALL)
    params = _params(model, 5, 24)
    x, _ = _batch(5, 24)
    g = jax.grad(lambda p: jnp.sum(model.apply(
        {"params": p}, x.astype(jnp.float32)[..., None])))(params)
    for i in range(SMALL.layers):
        eva = g[f"layers_{i}"]["eva"]
        assert not np.any(np.asarray(eva["phi"]))
        assert not np.any(np.asarray(eva["mu"]))


def test_rematerialised_layers_give_the_same_tree_logits_and_gradients():
    x, _ = _batch(1, 76)
    x = x.astype(jnp.float32)[..., None]
    plain = EvaByte3D(widths=SMALL, remat_layers=False)
    remat = EvaByte3D(widths=SMALL)
    params = _params(plain, 1, 76)
    assert jax.tree.structure(remat.init(jax.random.key(0), x)["params"]) \
        == jax.tree.structure(params)
    f = lambda m: jax.value_and_grad(
        lambda p: jnp.sum(m.apply({"params": p}, x)))(params)
    (a, ga), (b, gb) = f(plain), f(remat)
    _close(a, b)
    jax.tree.map(_close, ga, gb)


# ---------- (b) one window is plain causal attention ----------

@pytest.mark.parametrize("tokens", [24, 32])
def test_a_sequence_inside_one_window_is_causal_attention(tokens):
    q, k, v, phi, mu = _qkv(2, tokens)
    got = eva_attention(q, k, v, phi, mu, WINDOW, CHUNK, jnp.float32)
    want = attention.causal_gq_attention(q[:, :, :, None], k, v,
                                         jnp.float32)
    _close(got, want, rtol=1e-5, atol=1e-6)


# ---------- (c) causality ----------

@pytest.mark.parametrize("t", [5, 40, 70])  # one in each window
def test_a_change_at_token_t_leaves_the_outputs_before_t_alone(t):
    """Bitwise: a later token reaches no earlier query, neither as a key of
    its window nor through its chunk's summary, which no query of its own
    window reads (tokens 4-7 share token 5's chunk and token 4 comes
    first)."""
    q, k, v, phi, mu = _qkv(3, 76)
    bump = lambda a: a.at[:, t].add(1.0)
    a = eva_attention(q, k, v, phi, mu, WINDOW, CHUNK, jnp.float32)
    b = eva_attention(bump(q), bump(k), bump(v), phi, mu, WINDOW, CHUNK,
                      jnp.float32)
    a, b = np.asarray(a), np.asarray(b)
    assert np.array_equal(a[:, :t], b[:, :t])
    assert not np.array_equal(a[:, t], b[:, t])
    # and its summary is read from the next window on, by every query there
    edge = (t // WINDOW + 1) * WINDOW
    if edge < 76:
        assert np.all(np.any(a[:, edge:] != b[:, edge:], axis=-1))


def test_a_summary_is_read_one_window_later_and_not_before():
    """Changing ``phi`` or ``mu`` moves no output of the first window and
    every output after it."""
    q, k, v, phi, mu = _qkv(4, 76)
    a = np.asarray(eva_attention(q, k, v, phi, mu, WINDOW, CHUNK,
                                 jnp.float32))
    for other in ((phi + 0.5, mu), (phi, mu + 0.5)):
        b = np.asarray(eva_attention(q, k, v, *other, WINDOW, CHUNK,
                                     jnp.float32))
        assert np.array_equal(a[:, :WINDOW], b[:, :WINDOW])
        assert np.all(np.any(a[:, WINDOW:] != b[:, WINDOW:], axis=-1))


def test_pairs_the_reference_mask_counts(ref):
    """What a head reads, counted from the reference's dense mask: the
    causal pairs inside the windows and the (query, summary) pairs across
    them (benchmark/evabyte_scopes.py's roofline reads the same counts off
    the reference's tape)."""
    pairs = lambda T, W, c: (
        int(ref.ops.eva_mask(T, W, c)[:, :T].sum()),
        int(ref.ops.eva_mask(T, W, c)[:, T:].sum()))
    assert pairs(4864, 2048, 16) == (4_491_648, 458_752)
    assert pairs(76, WINDOW, CHUNK) == (
        2 * (32 * 33 // 2) + 12 * 13 // 2, 32 * 8 + 12 * 16)
    assert pairs(24, WINDOW, CHUNK)[1] == 0


def test_the_probe_tells_bfloat16_scores_and_stream_from_the_stated(ref):
    """``benchmark/evabyte_check.py``'s ``probe`` (the builder's check on
    the chip, at the step's shapes) at a size the CPU runs: the program in
    ``bfloat16`` compute with float32 scores, softmax and stream stays
    under every limit; the reference with bfloat16 scores, and with a
    bfloat16 stream, is over one."""
    from benchmark import evabyte_check

    widths = Widths(layers=1, hidden_size=256, heads=2, head_dim=128,
                    intermediate_size=688, window_size=256, chunk_size=16)
    got = evabyte_check.probe(ref, widths, 608, 2, 0, jnp.bfloat16)
    for part in ("attention", "stream"):
        assert got[part]["ok"], got[part]
        assert got[part]["control_fails"], got[part]
    assert {"dq", "dk", "out"} <= set(got["attention"]["control_over"])


# ---------- (d) the share of heads ----------

@pytest.mark.parametrize("seed", [0, 1])
def test_the_four_shares_add_up_to_the_uncut_layer(ref, seed):
    """One head a share (the small size's 0-7, 8-15, 16-23, 24-31): each
    share's program, given its columns of W_q, W_k, W_v, its rows of W_o
    and its ``phi`` and ``mu``, computes its part of the attention output;
    the parts and the stream, counted once, are the uncut reference's
    stream after attention, and the whole feed-forward on top of it the
    uncut reference's layer."""
    whole = EvaByte3D(widths=SMALL)
    p = _params(whole, seed, 76)["layers_0"]
    h = jax.random.normal(jax.random.key(seed), (2, 76, 64))
    norm = lambda name, a: RMSNorm(1e-5, unit_offset=True).apply(
        {"params": p[name]}, a)
    x = norm("attn_norm", h)
    one = dataclasses.replace(SMALL, heads=1)
    parts = []
    for a in range(HEADS):
        cols = slice(a * HD, (a + 1) * HD)
        share = {**{n: {"kernel": p["eva"][n]["kernel"][:, cols]}
                    for n in ("q_proj", "k_proj", "v_proj")},
                 "o_proj": {"kernel": p["eva"]["o_proj"]["kernel"][cols]},
                 "phi": p["eva"]["phi"][a:a + 1],
                 "mu": p["eva"]["mu"][a:a + 1]}
        parts.append(EvaAttention(one).apply({"params": share}, x))
        # a share is what the reference computes from the same share
        _close(parts[-1], ref.attention(x, share, CFG, ref.ops.exact,
                                        ref.ops.exact, None, ""))
    after_attention = h + sum(parts)
    _close(after_attention, h + ref.attention(
        x, p["eva"], CFG, ref.ops.exact, ref.ops.exact, None, ""))
    got = after_attention + GatedMLP(SMALL).apply(
        {"params": p["ffn"]}, norm("mlp_norm", after_attention))
    _close(got, ref.layer(h, p, CFG, ref.ops.exact, ref.ops.exact,
                          ref.ops.exact, None, ""))
    # the parts are not small beside the whole
    assert float(jnp.max(jnp.abs(parts[0]))) > 1e-2


# ---------- (e) evaluation's batch follows what a row costs ----------

class _Plain(nn.Module):
    """A model that says nothing of what a row costs: the cap is 32."""

    input_rank = 5

    @nn.compact
    def __call__(self, x, train: bool = False):
        return nn.Dense(1)(x.reshape(x.shape[0], -1))


class _Costly(_Plain):
    """A model that declares a row a quarter of the evaluation budget."""

    def row_tokens(self, row_shape):
        return 5120


def _scan_lengths(jaxpr):
    return [e.params["length"] for e in jaxpr.eqns
            if e.primitive.name == "scan"]


def _scan_batches(jaxpr):
    """``(length, batch width)`` of each scan: the width is the minor
    extent of the one output a row, the stacked ``scores``."""
    return [(e.params["length"], v.aval.shape[-1])
            for e in jaxpr.eqns if e.primitive.name == "scan"
            for v in e.outvars if v.aval.ndim == 2]


def test_evaluation_batches_follow_the_rows_cost():
    tr = LocalTrainer(_Costly(), OptimConfig(), 1)
    r = np.random.RandomState(0)
    X = jnp.asarray(r.randint(0, 256, (6, 4, 4, 4)).astype(np.uint8))
    y = jnp.asarray(r.randint(0, 2, (6,)).astype(np.int32))
    valid = jnp.arange(6) < 5
    cs = tr.init_client_state(jax.random.key(0),
                              jnp.zeros((1, 4, 4, 4), jnp.float32))
    assert tr.eval_batch_rows(X.shape[1:]) == 4
    run = lambda **kw: tr.evaluate(cs.params, cs.batch_stats, X, y, valid,
                                   **kw)
    # two batches of 4: 8 rows computed, not 32
    jaxpr = jax.make_jaxpr(run)().jaxpr
    assert _scan_lengths(jaxpr) == [2]
    assert _scan_lengths(jax.make_jaxpr(
        lambda: run(batch_size=32))().jaxpr) == [1]
    got, want = run(), run(batch_size=32)
    assert got["scores"].shape == want["scores"].shape == (6,)
    assert float(got["test_total"]) == float(want["test_total"]) == 5.0
    for k in ("test_correct", "test_loss", "scores"):
        _close(got[k], want[k], rtol=1e-6, atol=1e-6)


@pytest.mark.parametrize("rows,cap,batches,batch", [
    (2, 4, 1, 2),      # evabyte's and moonlight's cells: a site's 2 rows
    (16, 32, 1, 16),   # nemotronh's and zaya's
    (24, 32, 1, 24),   # olmoe's and the one-chip CNN cells'
    (44, 32, 2, 22),   # the mesh cell's largest site
    (6, 4, 2, 3),
    (1, 32, 1, 1),
    (32, 32, 1, 32),   # rows that tile the cap: the program of before
    (64, 32, 2, 32),
    (8, 4, 2, 4),
])
def test_evaluation_batches_follow_the_rows_there_are(rows, cap, batches,
                                                      batch):
    """Where no ``batch_size`` is given ``evaluate`` walks the fewest
    batches the cap allows at the one width that tiles the static row
    count with the least filler (``eval_batches``); the sums and scores
    are those of a run at 32 rows a batch, under a ``valid`` mask that
    cuts inside the last batch."""
    tr = LocalTrainer({4: _Costly, 32: _Plain}[cap](), OptimConfig(), 1)
    r = np.random.RandomState(rows)
    X = jnp.asarray(r.randint(0, 256, (rows, 4, 4, 4)).astype(np.uint8))
    y = jnp.asarray(r.randint(0, 2, (rows,)).astype(np.int32))
    real = max(1, rows - 1)
    valid = jnp.arange(rows) < real
    cs = tr.init_client_state(jax.random.key(0),
                              jnp.zeros((1, 4, 4, 4), jnp.float32))
    assert tr.eval_batch_rows(X.shape[1:]) == cap
    assert tr.eval_batches(X.shape[1:], rows) == (batches, batch)
    run = lambda **kw: tr.evaluate(cs.params, cs.batch_stats, X, y, valid,
                                   **kw)
    jaxpr = jax.make_jaxpr(run)()
    assert _scan_batches(jaxpr.jaxpr) == [(batches, batch)]
    if batch == cap:
        assert str(jaxpr) == str(jax.make_jaxpr(
            lambda: run(batch_size=cap))())
    got, want = run(), run(batch_size=32)
    assert got["scores"].shape == want["scores"].shape == (rows,)
    assert float(got["test_total"]) == float(want["test_total"]) == real
    for k in ("test_correct", "test_loss", "scores"):
        _close(got[k], want[k], rtol=1e-6, atol=1e-6)


@pytest.mark.parametrize("model,shape,rows", [
    ("3dcnn_tiny", (12, 14, 12), 32),   # a CNN row counts as one token
    ("3dcnn", (121, 145, 121), 32),
    ("olmoe3d", (121, 145, 121), 32),   # 640 tokens a row
    ("nemotronh3d", (121, 145, 121), 32),
    ("zaya3d", (121, 145, 121), 32),
    ("evabyte3d", (121, 145, 121), 4),  # 4,864 tokens a row
])
def test_evaluation_batch_of_every_benchmark_model(model, shape, rows):
    tr = LocalTrainer(create_model(model, 1), OptimConfig(), 1)
    assert tr.eval_batch_rows(shape) == rows


# ---------- the model the CLI builds ----------

def test_published_widths_and_work(ref):
    """``create_model("evabyte3d")`` is the published layer cut to this
    chip: the parameter shapes, the parameter count, the tape."""
    from benchmark import flops

    model = create_model("evabyte3d", 1, remat="stem")  # --remat: ignored
    assert model.remat_layers and model.widths.layers == ref.LAYERS == 4
    assert not getattr(model, "returns_aux", False)
    shapes = jax.eval_shape(
        lambda: model.init(jax.random.key(0),
                           jnp.zeros((1, 121, 145, 121, 1))))["params"]
    count = lambda t: sum(int(np.prod(x.shape)) for x in jax.tree.leaves(t))
    layer = shapes["layers_0"]
    assert count(layer["eva"]) == 4 * 4096 * 1024 + 2 * 8 * 128
    assert count(layer["ffn"]) == 3 * 4096 * 11008 == 135_266_304
    assert count(layer) == 152_053_760
    assert layer["eva"]["q_proj"]["kernel"].shape == (4096, 1024)
    assert layer["eva"]["o_proj"]["kernel"].shape == (1024, 4096)
    assert layer["eva"]["phi"].shape == layer["eva"]["mu"].shape == (8, 128)
    assert layer["ffn"]["gate_proj"]["kernel"].shape == (4096, 11008)
    assert shapes["patch_embed"]["kernel"].shape == (512, 4096)
    assert count(shapes) == 4 * 152_053_760 + 512 * 4096 + 3 * 4096
    assert model.row_tokens((121, 145, 121)) == 4864
    tape = flops.record_tape(ref.forward, shapes, {}, (121, 145, 121))
    assert tape == ref.published_tape()
    assert ref.eva_pairs(tape) == (4_491_648, 458_752)
    assert abs(flops.forward_flops(tape) / 1e12 - 6.018) < 1e-3
    assert abs(flops.training_flops_per_sample(tape) / 1e12 - 18.05) < 5e-3
    assert abs(3 * ref.eva_flops_per_sample(tape) / 1e12 - 0.2433) < 1e-4


def test_a_second_eager_initialisation_compiles_nothing():
    """The trainer initialises its model eagerly, at every ``train()``:
    after the first initialisation a second compiles nothing
    (tests/test_nemotronh3d.py says why that matters)."""
    from jax import monitoring

    compiles = []
    monitoring.register_event_duration_secs_listener(
        lambda event, secs, **_: compiles.append(event) if event ==
        "/jax/core/compile/backend_compile_duration" else None)
    tr = LocalTrainer(EvaByte3D(widths=SMALL), OptimConfig(), 1)
    init = lambda: tr.init_client_state(
        jax.random.key(0), jnp.zeros((1,) + _shape(76), jnp.float32))
    init()
    before = len(compiles)
    init()
    assert len(compiles) == before


def test_a_folded_job_puts_its_counters_on_the_spans(tmp_path):
    """The small model through FedAvg's folded round, tracer armed:
    ``eval_dispatch`` carries the sample rows its loops compute for the
    real ones: the test rows a site of 36 tokens run as one batch of
    as many rows as the larger site has."""
    from neuroimagedisttraining_tpu.config import (
        DataConfig, ExperimentConfig, FedConfig,
    )
    from neuroimagedisttraining_tpu.data.federate import federate_cohort
    from neuroimagedisttraining_tpu.data.synthetic import (
        generate_synthetic_abcd,
    )
    from neuroimagedisttraining_tpu.engines import create_engine
    from neuroimagedisttraining_tpu.obs import names as obs_names
    from neuroimagedisttraining_tpu.obs import trace as obs_trace
    from neuroimagedisttraining_tpu.utils.logging import ExperimentLogger

    widths = dataclasses.replace(SMALL, window_size=16)
    cohort = generate_synthetic_abcd(num_subjects=30, shape=(12, 14, 12),
                                     num_sites=2, seed=0)
    cfg = ExperimentConfig(
        model="evabyte3d", num_classes=1, algorithm="fedavg",
        data=DataConfig(dataset="synthetic", partition_method="site"),
        optim=OptimConfig(lr=1e-2, batch_size=4, epochs=1),
        fed=FedConfig(client_num_in_total=2, comm_round=1,
                      frequency_of_the_test=1),
        log_dir=str(tmp_path), tag="spans")
    fed, _ = federate_cohort(cohort, partition_method="site", mesh=None)
    eng = create_engine(
        "fedavg", cfg, fed,
        LocalTrainer(EvaByte3D(widths=widths), cfg.optim, 1), mesh=None,
        logger=ExperimentLogger(str(tmp_path), "synthetic", cfg.identity(),
                                console=False))
    eng._fold_budget_bytes = 1
    obs_trace.arm()
    try:
        out = eng.train()
        events = [e for e in obs_trace.TRACER.events() if e["ph"] == "X"]
    finally:
        obs_trace.disarm()
    assert eng.program.placement == "folded"
    assert np.isfinite(out["final_global"]["loss"])
    (dispatch,) = [e["args"] for e in events
                   if e["name"] == obs_names.SPAN_DISPATCH_PROGRAM
                   and e["args"]["program"] == "round"]
    assert dispatch["placement"] == "folded"
    n_test = np.asarray(eng.data.n_test)
    rows = int(eng.data.X_test.shape[1])
    assert rows <= 32
    for e in events:
        if e["name"] == obs_names.SPAN_EVAL_DISPATCH:
            assert e["args"]["rows_real"] == int(n_test.sum())
            assert e["args"]["rows_run"] == 2 * rows
