"""The main path's kernels compile for the chip, asked of the TPU
compiler without a chip (on-chip-measurement guide, section 2, step 3).

The installed libtpu compiles for a DESCRIBED ``v5e:2x2`` topology with
no device attached, so these run in the CPU sandbox and refuse what the
chip's compiler would refuse: a slice off the tiling, too much VMEM, a
leaf shape the blocking cannot pad. Interpret-mode tests cannot see any of
that. A compile that passes is not a chip run — ``chip_smoke.py`` is.

Shapes are the flagship's own: the AlexNet3D (``3DCNN``) parameter tree at
121x145x121 and its 2,568,064-element saliency vector. Skipped where the
topology cannot be described (no libtpu, or another process holds it).
"""

import os

os.environ.setdefault("TPU_LOG_DIR", "disabled")  # else libtpu logs to /tmp

import jax
import jax.numpy as jnp
import pytest
from jax.sharding import SingleDeviceSharding

from neuroimagedisttraining_tpu.models import create_model
from neuroimagedisttraining_tpu.ops import fused_update as fu
from neuroimagedisttraining_tpu.ops.stemconv import stem_block, stem_conv3d
from neuroimagedisttraining_tpu.ops.topk import kth_largest
from neuroimagedisttraining_tpu.utils.pytree import tree_map_with_path_names

SHAPE = (121, 145, 121)
KERNEL_MARK = "tpu_custom_call"


@pytest.fixture(scope="module")
def chip():
    """Sharding on one described v5e chip. The persistent cache is off
    around these compiles: an entry written without a chip cannot be
    read back without one, and the next run would warn and recompile."""
    from jax.experimental import topologies
    from jax.experimental.compilation_cache import compilation_cache

    try:
        topo = topologies.get_topology_desc(platform="tpu",
                                            topology_name="v5e:2x2")
    except Exception as e:  # noqa: BLE001 — any failure to describe = skip
        pytest.skip(f"cannot describe a v5e:2x2 topology here: {e}")
    was_on = jax.config.jax_enable_compilation_cache
    jax.config.update("jax_enable_compilation_cache", False)
    compilation_cache.reset_cache()
    yield SingleDeviceSharding(topo.devices[0])
    jax.config.update("jax_enable_compilation_cache", was_on)
    compilation_cache.reset_cache()


@pytest.fixture(scope="module")
def flagship_params():
    model = create_model("3DCNN", num_classes=1)
    return jax.eval_shape(
        lambda: model.init(jax.random.key(0),
                           jnp.zeros((1,) + SHAPE + (1,)), train=False)
    )["params"]


def _on(chip, shape, dtype=jnp.float32):
    return jax.ShapeDtypeStruct(shape, dtype, sharding=chip)


def test_topk_compiles_at_the_flagship_score_length(chip, flagship_params):
    from neuroimagedisttraining_tpu.ops.masks import is_weight_kernel

    sizes = []
    tree_map_with_path_names(
        lambda name, s: sizes.append(s.size)
        if is_weight_kernel(name, s) else None, flagship_params)
    n = sum(sizes)
    assert n == 2_568_064  # the real score vector, not a round number
    text = kth_largest.lower(_on(chip, (n,)), n // 2, use_pallas=True) \
        .compile().as_text()
    assert KERNEL_MARK in text


@pytest.mark.parametrize("masked", [False, True], ids=["dense", "masked"])
@pytest.mark.parametrize("clip", [0.0, 10.0], ids=["noclip", "clip"])
def test_fused_update_compiles_over_every_flagship_leaf(chip,
                                                        flagship_params,
                                                        masked, clip):
    """Every leaf shape of the real tree — [5,5,5,1,64] and
    [3,3,3,192,192] kernels down to the 64-element and 1-element biases —
    through the Pallas tail, one ``tpu_custom_call`` per leaf."""
    tree = jax.tree.map(lambda s: _on(chip, s.shape), flagship_params)

    def step(p, g, t, m, lr):
        return fu.fused_sgd_step(p, g, t, m, clip=clip, wd=5e-4,
                                 momentum=0.9, lr=lr, use_pallas=True)

    text = jax.jit(step).lower(tree, tree, tree, tree if masked else None,
                               _on(chip, ())).compile().as_text()
    assert text.count(KERNEL_MARK) >= len(jax.tree.leaves(tree))


@pytest.mark.parametrize("clients", [0, 2], ids=["unbatched", "vmap2"])
def test_stem_dw_compiles_at_full_volume(chip, clients):
    """The stem's weight gradient (``ops/stemconv.py``) at the real volume
    and channel widths, unbatched (``cohort_map``'s per-row loop: XLA's
    own form) and under the engines' client-axis ``vmap`` (the
    re-expressed contraction, a client at a time): plain XLA (a Mosaic call would fail the
    mesh cell's ``program_check`` and GSPMD refuses one), an MXU
    convolution, and no patch matrix: the temporaries are ``x'`` (2.5x
    the input; the tiling pads its five taps to sixteen rows) and the
    pieces it is built from. The retired split-K form stacked 125 tap
    rows, 63 MB a sample. Batch 2 keeps the compile short; the program is
    the same at any batch."""
    d, h, w = ((s - 5) // 2 + 1 for s in SHAPE)
    lead = (clients,) if clients else ()
    x = _on(chip, lead + (2,) + SHAPE + (1,), jnp.bfloat16)
    k = _on(chip, lead + (5, 5, 5, 1, 64), jnp.bfloat16)
    g = _on(chip, lead + (2, d, h, w, 64), jnp.bfloat16)

    def dw(x, k, g):
        return jax.vjp(stem_conv3d, x, k)[1](g)[1]

    compiled = jax.jit(jax.vmap(dw) if clients else dw).lower(x, k, g) \
        .compile()
    text = compiled.as_text()
    assert KERNEL_MARK not in text
    assert " convolution(" in text
    # XLA's own form contracts over a 59x71x59 window; the re-expressed
    # one has (ow, n) in its batch and no third window extent of 59
    assert ("window={size=59x71x59 " in text) == (not clients)
    if clients:
        # measured 157 MiB: one client's x' (69 MiB as tiled), the five tap
        # slices it is built from, one client's g copied out of the stack;
        # a client's patch rows alone would be 126 MiB. (XLA's own form,
        # unbatched, takes 1,157 MiB for its padded copy of x.)
        assert compiled.memory_analysis().temp_size_in_bytes < 200 * 2 ** 20


#: (clients under ``vmap``, stage): the flagship's f0 + pool0 at 4 clients
#: and ResNet3D's conv1 / bn1 / pool0 at 2, each beside its unbatched form
STEM_BLOCK_CASES = [(0, "alexnet"), (4, "alexnet"), (0, "resnet"),
                    (2, "resnet")]


@pytest.mark.parametrize("clients,stage", STEM_BLOCK_CASES, ids=[
    "unbatched", "vmap4", "resnet-unbatched", "resnet-vmap2"])
def test_stem_block_compiles_merged_at_full_volume(chip, clients, stage):
    """The whole first stage (``ops/stemconv.py`` ``stem_block``: f0's
    convolution, norm, relu, pool0), forward and backward, at the
    flagship cell's shape: 4 clients of ``bf16[16, 121, 145, 121, 1]``
    under ``vmap``. The program stays in the grouped convolution's
    layout: no instruction's result has the clients and the 64 channels
    as axes of their own at the pre-pool extent (each such tensor pads 64
    lanes to 128: 4.05 GB for 2.02), and the temporaries come to under 7
    GiB (5.87 measured; the plain composition under the same ``vmap``
    takes 11.44). Unbatched (a mesh row) it is the plain composition's
    program: 3.84 GiB, as before the function existed.

    ResNet3D's stage (k3 stride 2 pad 3, no bias, a float32 norm, pool k3
    s2 pad 1; PR 39) at its cell's shape, 2 clients: 2 x 64 channels are
    one 128-lane tile, the activations after the norm are float32 as the
    configuration states (2.44 GB each, where the split form wrote 4.88),
    ``g`` is read as ``bf16[63, 75, 1008, 128]``: 5.98 GiB of temporaries
    measured, the plain composition under the same ``vmap`` 14.22.
    Unbatched it is the plain composition's program, 6.99 GiB in both."""
    resnet = stage == "resnet"
    k, pad, features = (3, 3, 64) if resnet else (5, 0, 64)
    d, h, w = ((s + 2 * pad - k) // 2 + 1 for s in SHAPE)
    lead = (clients,) if clients else ()
    args = (_on(chip, lead + (16,) + SHAPE + (1,), jnp.bfloat16),
            _on(chip, lead + (k, k, k, 1, features))) + tuple(
                _on(chip, lead + (features,)) for _ in range(5))

    def loss(x, kernel, bias, *rest):
        if resnet:
            out, mean, var = stem_block(
                x, kernel, None, *rest, train=True, stride=2, pad=3,
                pool=(3, 2, 1), norm_dtype=jnp.float32)
        else:
            out, mean, var = stem_block(x, kernel, bias, *rest, train=True,
                                        stride=2, pad=0, pool=3)
        return jnp.sum(jnp.sin(out.astype(jnp.float32))), (mean, var)

    step = jax.value_and_grad(loss, argnums=(1, 2, 3, 4), has_aux=True)
    compiled = jax.jit(jax.vmap(step) if clients else step).lower(*args) \
        .compile()
    text = compiled.as_text()
    assert KERNEL_MARK not in text
    temp = compiled.memory_analysis().temp_size_in_bytes / 2 ** 30
    if resnet and not clients:
        assert 6.8 < temp < 7.2, temp
        assert f"f32[16,{d},{h},{w},64]" in text
        return
    if resnet:
        assert f"f32[16,{d},{h},{w},128]" in text   # float32 after the norm
        assert f"bf16[{d},{h},{w * 16},128]" in text
        for split in (f"[16,{d},{h},{w},2,64]", f"[2,16,{d},{h},{w},64]"):
            assert split not in text, split
        assert ".remat" not in text
        assert temp < 6.5, temp
        return
    if not clients:
        assert 3.7 < temp < 4.0, temp
        assert f"[16,{d},{h},{w},64]" in text
        return
    assert f"[16,{d},{h},{w},256]" in text          # the merged activations
    assert f"[{d},{h},{w * 16},256]" in text        # g, read where it lies
    for split in (f"[16,{d},{h},{w},4,64]", f"[4,16,{d},{h},{w},64]"):
        assert split not in text, split
    assert temp < 7.0, temp


#: (rows, held experts' first, label): Nemotron-H's training batch (120 row
#: tiles of 512), its one-volume initialisation (15 tiles of 256) and a
#: share that starts in the middle of the layer's 128 experts
GMM_CASES = [(61440, 0, "batch16"), (3840, 0, "one_volume"),
             (61440, 56, "mid_window")]


@pytest.mark.parametrize("rows,first,label", GMM_CASES,
                         ids=[c[2] for c in GMM_CASES])
def test_held_grouped_matmul_compiles_at_the_published_widths(
        chip, monkeypatch, rows, first, label):
    """``ops/moe.py`` ``grouped_matmul`` over a share of the experts, at
    Nemotron-H's widths (2688 x 1856: no multiple of 1024, 1856 no
    multiple of 128), forward and both gradients: ``megablox.gmm`` with a
    ``group_offset``, the tiles :func:`gmm_tiling` chose (384: a ragged
    last tile of 1856 that the kernel masks): the first matrix's forward
    kernel and ``gmm`` / ``tgmm`` for both matrices' gradients (the second
    matrix's forward output is not needed for the gradient of a sum)."""
    from neuroimagedisttraining_tpu.ops import moe

    monkeypatch.setattr(jax, "default_backend", lambda: "tpu")
    xs = _on(chip, (rows, 2688), jnp.bfloat16)
    up = _on(chip, (8, 2688, 1856), jnp.bfloat16)
    down = _on(chip, (8, 1856, 2688), jnp.bfloat16)
    sizes = _on(chip, (128,), jnp.int32)

    def loss(xs, up, down, sizes):
        u = moe.grouped_matmul(xs, up, sizes, first)
        y = moe.grouped_matmul(jnp.square(jax.nn.relu(u)), down, sizes,
                               first)
        return jnp.sum(y.astype(jnp.float32))

    text = jax.jit(jax.grad(loss, argnums=(0, 1, 2))).lower(
        xs, up, down, sizes).compile().as_text()
    assert text.count(KERNEL_MARK) == 5


@pytest.mark.parametrize("train", [True, False],
                         ids=["step_b16", "evaluation_b16"])
def test_held_runs_buffer_compiles_at_the_published_widths(chip,
                                                           monkeypatch,
                                                           train):
    """``ops/moe.py`` ``held_expert_rows`` for Nemotron-H's training step
    (10,240 tokens x 6 slots, 8 of 128 experts held, 2688 x 1856, bf16
    beside float32 master weights), value and every gradient, and for a
    site's 16 test rows, which evaluation runs as one batch of those
    61,440 slots since PR 41, value alone (one loop, 2 kernels): a buffer of
    7,680 rows, which ``gmm_tiling`` gives the widest row tile; one loop
    over windows forward and one backward, ``megablox.gmm`` with no
    ``group_offset`` over ``[7680, .]`` in their bodies and nowhere else
    (as many kernels as the rematerialised full sort: a step's program is
    164 MiB of code on the chip, and a second copy of these did not fit);
    no array
    of all 61,440 rows anywhere, and a third of the full sort's
    temporaries."""
    from neuroimagedisttraining_tpu.models.tokens3d import relu2
    from neuroimagedisttraining_tpu.ops import moe

    monkeypatch.setattr(jax, "default_backend", lambda: "tpu")
    T, k, E, count = 10240, 6, 128, 8
    capacity = moe.held_capacity(T * k, count, E)
    assert capacity == 7680
    assert moe.gmm_tiling(capacity, 2688, 1856) == (512, 384, 384)
    operands = (_on(chip, (T, 2688), jnp.bfloat16), _on(chip, (T, k)),
                _on(chip, (count, 2688, 1856)),
                _on(chip, (count, 1856, 2688)))
    experts = _on(chip, (T, k), jnp.int32)

    def compiled(rows):
        def loss(x, weights, up, down, experts):
            y = rows(x, weights, experts, up, down, E, 0, relu2)
            return jnp.sum(jnp.sin(y.astype(jnp.float32)))
        if train:
            loss = jax.value_and_grad(loss, argnums=(0, 1, 2, 3))
        return jax.jit(loss).lower(*operands, experts).compile()

    windows = compiled(lambda *a: moe.held_expert_rows(*a)[0])
    full = compiled(moe._full_sort_rows)
    text = windows.as_text()
    assert " conditional(" not in text
    # a window forward: 2 kernels; backward: the 2 again and gmm / tgmm
    # for both matrices
    assert text.count(KERNEL_MARK) == (2 + 6 if train else 2)
    assert f"bf16[{capacity},2688]" in text
    assert f"[{T * k},2688]" not in text
    assert f"[{T * k},2688]" in full.as_text()
    assert 3 * windows.memory_analysis().temp_size_in_bytes \
        <= full.memory_analysis().temp_size_in_bytes


@pytest.mark.parametrize("rows,count,experts,buffered", [
    # a training step and a site's 16 test rows; 32 test rows
    (61440, 8, 128, True), (122880, 8, 128, True),
    (81920, 64, 64, False),   # OLMoE: every expert held
    (3840, 64, 128, False),   # a buffer as long as the sort
    (256, 8, 128, False)], ids=["train", "eval", "all_held", "half_held",
                                "tiny"])
def test_which_paths_a_held_layer_traces_follows_shapes(monkeypatch, rows,
                                                        count, experts,
                                                        buffered):
    """Where every expert is held, or the buffer would be no smaller than
    the sort, the layer's program holds no loop over windows: the full
    sort is what is traced, and ``olmoe3d``'s step stays what it was."""
    from neuroimagedisttraining_tpu.models.tokens3d import relu2
    from neuroimagedisttraining_tpu.ops import moe

    monkeypatch.setattr(jax, "default_backend", lambda: "tpu")
    capacity = moe.held_capacity(rows, count, experts)
    assert (capacity is not None) == buffered
    if buffered:
        assert capacity == 2 * rows * count // experts
        assert capacity % moe.GMM_ROW_TILES[0] == 0
    monkeypatch.undo()  # trace the layer with the CPU's grouped matmul
    T, k = rows // 8, 8
    s = jax.ShapeDtypeStruct
    jaxpr = jax.make_jaxpr(
        lambda x, w, e, up, down: moe.held_expert_rows(
            x, w, e, up, down, experts, 0, relu2))(
        s((T, 16), jnp.float32), s((T, k), jnp.float32),
        s((T, k), jnp.int32), s((count, 16, 8), jnp.float32),
        s((count, 8, 16), jnp.float32))
    assert (" while[" in str(jaxpr)) == (
        moe.held_capacity(rows, count, experts) is not None)


@pytest.mark.parametrize("batch,train",
                         [(16, True), (32, False), (16, False)],
                         ids=["step_b16", "evaluation_b32",
                              "evaluation_b16"])
def test_ssd_kernels_compile_at_the_published_widths(chip, monkeypatch,
                                                     batch, train):
    """``ops/ssd.py`` ``ssd_chunked`` on a TPU at Nemotron-H's mixer (640
    tokens in chunks of 128, 8 groups of 8 heads of 64, state 128, bf16
    beside float32 ``dt``): a training step's batch with every gradient
    (the forward kernel that also writes the chunks' start states, and the
    backward kernel), evaluation's batch forward at the cap's 32 rows and
    at the cell's 16 test rows a site. No ``[b, 5, 128, 128,
    ...]`` array: the decay tiles never leave the kernels."""
    from neuroimagedisttraining_tpu.ops import ssd

    monkeypatch.setattr(jax, "default_backend", lambda: "tpu")
    T, H, P, G, N = 640, 64, 64, 8, 128
    assert ssd.kernel_tiles(128, H // G, P, N)
    operands = (_on(chip, (batch, T, H, P), jnp.bfloat16),
                _on(chip, (batch, T, H)), _on(chip, (H,)),
                _on(chip, (batch, T, G, N), jnp.bfloat16),
                _on(chip, (batch, T, G, N), jnp.bfloat16), _on(chip, (H,)))
    scan = lambda *a: ssd.ssd_chunked(*a, 128)
    if train:
        scan = jax.grad(lambda *a: jnp.sum(jnp.sin(ssd.ssd_chunked(
            *a, 128).astype(jnp.float32))), argnums=tuple(range(6)))
    compiled = jax.jit(scan).lower(*operands).compile()
    text = compiled.as_text()
    assert text.count(KERNEL_MARK) == (2 if train else 1)
    assert f"[{batch},5,128,128," not in text
    # the plain form's temporaries at b16 are 2.3 GiB forward alone
    assert compiled.memory_analysis().temp_size_in_bytes < 0.7 * 2 ** 30


# ---------- whole training steps of the token trunks ----------

_STEPS: dict = {}  # a step compiles in a minute: once a name


def _compiled_step(chip, monkeypatch, name, batch=16, clients=0):
    """One training step (``LocalTrainer.loss_and_grad``: ``batch`` of the
    full volume, ``bf16_mixed``, the cells' optimizer) of ``--model name``
    at its published widths, compiled for the described chip from shapes
    alone; ``clients``: under the stacked placement's client-axis
    ``vmap``."""
    from neuroimagedisttraining_tpu.config import OptimConfig
    from neuroimagedisttraining_tpu.core.trainer import LocalTrainer

    if (name, clients) in _STEPS:
        return _STEPS[name, clients]
    trainer = LocalTrainer(
        create_model(name, 1, dtype=jnp.bfloat16),
        OptimConfig(precision="bf16_mixed", lr=0.01, momentum=0.9, wd=5e-4,
                    grad_clip=10.0, batch_size=batch), 1)
    lead = (clients,) if clients else ()
    state = jax.eval_shape(trainer.init_client_state, jax.random.key(0),
                           jnp.zeros((1,) + SHAPE, jnp.float32))
    state = jax.tree.map(lambda a: _on(chip, lead + a.shape, a.dtype), state)
    monkeypatch.setattr(jax, "default_backend", lambda: "tpu")
    step = trainer.loss_and_grad
    _STEPS[name, clients] = jax.jit(jax.vmap(step) if clients else step) \
        .lower(state, _on(chip, lead + (batch,) + SHAPE, jnp.uint8),
               _on(chip, lead + (batch,), jnp.int32)).compile()
    return _STEPS[name, clients]


def _traced_step(monkeypatch, name, batch=2):
    """``--model name``'s training step as a TPU traces it (a jaxpr: the
    bodies of its ``pallas_call``s are equations there, where the compiled
    text holds them serialized)."""
    from neuroimagedisttraining_tpu.config import OptimConfig
    from neuroimagedisttraining_tpu.core.trainer import LocalTrainer

    trainer = LocalTrainer(
        create_model(name, 1, dtype=jnp.bfloat16),
        OptimConfig(precision="bf16_mixed", lr=0.01, momentum=0.9, wd=5e-4,
                    grad_clip=10.0, batch_size=batch), 1)
    state = jax.eval_shape(trainer.init_client_state, jax.random.key(0),
                           jnp.zeros((1,) + SHAPE, jnp.float32))
    monkeypatch.setattr(jax, "default_backend", lambda: "tpu")
    return jax.make_jaxpr(trainer.loss_and_grad)(
        state, jax.ShapeDtypeStruct((batch,) + SHAPE, jnp.uint8),
        jax.ShapeDtypeStruct((batch,), jnp.int32))


def _program_of(compiled) -> tuple[int, int, str]:
    """``(instructions, Mosaic kernels, sha256)`` of a compiled program's
    instructions in order, without what a checkout's path or a line
    number changes: the ``metadata`` and, in a kernel's call, the
    serialized body (``backend_config``), which carries both."""
    import hashlib
    import re

    text = compiled.as_text()
    lines = [re.sub(r"backend_config=.*$", "backend_config=MASKED",
                    re.sub(r", metadata=\{[^}]*\}", "", line))
             for line in text.splitlines()
             if re.match(r"\s*(ROOT )?%?[\w.\-]+ = ", line)]
    return (len(lines), text.count(KERNEL_MARK),
            hashlib.sha256("\n".join(lines).encode()).hexdigest())


#: what the PARENT of PR 31 (ab41654) compiles ``olmoe3d``'s step to, and
#: what PR 32 compiles ``nemotronh3d``'s to (a scratch
#: script that imports the parent's checkout, the same shapes, the same
#: masking): PR 31 moved the rotary tables and the causal depthwise
#: convolution to models/tokens3d.py, gave ``route`` a bias under the
#: softmax and ``expert_load`` a skip output, and neither trunk's program
#: may notice. A PR that means to change one of them brings its new row.
PARENT_STEPS = {
    "olmoe3d": (
        3759, 9,
        "9d00652a34bff7c0712b1289620349954ed3c0258c148a39889f404aeabffcb5"),
    # PR 32's own row: the scan's kernels in the four M layers (the parent
    # of PR 32 read 24151 instructions, 32 kernels, 71c3a658...)
    "nemotronh3d": (
        20542, 44,
        "d07dfedba13a53cb752af3189894fb8c6fec08fbe96fda615febbd4179073996"),
    # PR 43 (what five trunks copied lives once in models/tokens3d.py and
    # ops/attention.py): the three other trunks as PR 42 (79b67d3)
    # compiles them, taken from a checkout of that commit before any edit
    "zaya3d": (
        39862, 50,
        "51f60dd88521497d6127654ffdabc170c0146b4d9da4af827d12f41d2d293e74"),
    "evabyte3d": (
        12945, 0,
        "6891b4bc7ec36f48e9f1aec2d8f9e7e71c7d82bf8cbbfea97a64566aa03b98af"),
    # PR 45's own rows: a rematerialised layer keeps the attention kernel's
    # outputs (models/tokens3d.py ``layer_stack``), so the two trunks on the
    # kernels lose a forward kernel a layer (the parent, a1da0d2: 26,621
    # instructions, 58 kernels, 9882b303... and 29,042, 55, 1c12a723...)
    # and gain the counter ``attn_outputs_kept``; the four rows above, the
    # same ``layer_stack`` with no named value to keep, hold untouched
    "trinity3d": (
        29142, 50,
        "6d613ea964e08936e7505e92d11269808dcaf50385abad727c6d0ed7e5ddccb3"),
    # PR 47's own row: ``gmm_tiling`` gives Moonlight's 2048 x 2816 and
    # 1408 x 2048 the widest tile that pads them by at most a tenth (1024
    # and 512 for 256 and 128; the parent, 21495ac, PR 45's row: 26,625
    # instructions, 52 kernels, e4549d7f...); every other trunk's products
    # keep their tiles and the five other rows hold untouched
    "moonlight3d": (
        26686, 52,
        "651618fb7cca21bde3a2ab86d3a0bae2f8e14848ceeba259bc2ae18b9a359aa3"),
}
#: the cells' batch where it is not 16 (``_STEPS`` holds one step a name:
#: the tests below compile these three at 2 as well)
STEP_BATCH = {"evabyte3d": 2, "moonlight3d": 2, "trinity3d": 2}


@pytest.mark.parametrize("name", sorted(PARENT_STEPS))
def test_training_step_compiles_to_the_parents_program(chip, monkeypatch,
                                                       name):
    compiled = _compiled_step(chip, monkeypatch, name,
                              batch=STEP_BATCH.get(name, 16))
    assert _program_of(compiled) == PARENT_STEPS[name]


def test_nemotronh3d_step_holds_no_decay_matrix_and_fits(chip, monkeypatch):
    """PR 32: the scan's kernels in the four ``M`` layers (forward,
    rematerialised forward, backward: 12 beside the expert layers' 32), no
    ``[b, chunks, 128, 128, ...]`` decay or mix array in any dtype, and
    code and temporaries under the parent's 168.4 MiB + 2.481 GiB (the
    cell fits the chip by tens of MB: PERF.md section 7, item 13d)."""
    compiled = _compiled_step(chip, monkeypatch, "nemotronh3d")
    text, mem = compiled.as_text(), compiled.memory_analysis()
    assert text.count(KERNEL_MARK) == 32 + 4 * 3
    assert "f32[16,5,128,128," not in text
    assert "bf16[16,5,128,128," not in text
    assert mem.temp_size_in_bytes < 2.481 * 2 ** 30
    assert mem.generated_code_size_in_bytes < 180 * 2 ** 20


def test_zaya3d_training_step_fits_at_the_published_widths(chip,
                                                           monkeypatch):
    """``--model zaya3d``'s step at 543 M parameters: the held runs'
    buffer of 9,728 rows in every layer (forward, rematerialised forward
    and backward: 10 kernels a layer), no array of all 10,240 sorted rows
    of a layer's width, and code + temporaries that leave room for the
    folded round's 10.1 GiB of state (PERF.md, PR 31: 283.6 MiB and 2.87
    GiB; the chip then held ZAYA_PEAK GiB at its peak)."""
    compiled = _compiled_step(chip, monkeypatch, "zaya3d")
    text, mem = compiled.as_text(), compiled.memory_analysis()
    assert text.count(KERNEL_MARK) == 5 * 10
    assert "bf16[9728,2048]" in text and "bf16[9728,4096]" in text
    assert mem.temp_size_in_bytes < 3.0 * 2 ** 30
    assert mem.generated_code_size_in_bytes < 300 * 2 ** 20


def test_evabyte3d_training_step_fits_at_the_published_widths(chip,
                                                              monkeypatch):
    """``--model evabyte3d``'s step at 610 M parameters and the cell's
    batch of 2 x 4,864 tokens: plain XLA (no kernel), the scores a window
    at a time (a ``[2048, 2048]`` block a head, never ``[4864, 4864]``),
    the summaries' scores beside them (``[768, 256]`` in the last window),
    and code + temporaries that leave room for the folded round's 11.4 GiB
    of state (PERF.md, PR 38: 106.8 MiB and 1.918 GiB; the chip then held
    14.92 GiB at its peak)."""
    compiled = _compiled_step(chip, monkeypatch, "evabyte3d", batch=2)
    text, mem = compiled.as_text(), compiled.memory_analysis()
    assert text.count(KERNEL_MARK) == 0
    assert "f32[2,8,2048,2048]" in text and "f32[2,8,768,256]" in text
    assert "[2,8,4864,4864]" not in text
    assert mem.temp_size_in_bytes < 2.0 * 2 ** 30
    assert mem.generated_code_size_in_bytes < 120 * 2 ** 20


def test_evabyte3d_step_keeps_scores_and_softmax_float32(chip, monkeypatch):
    """The configuration states float32 scores and softmax under
    ``bf16_mixed``, and no limit of the benchmark's ``correct`` can tell
    bfloat16 scores from them at the initial weights (PERF.md section 7,
    item 16): the program the cell times says it itself. Every exponential
    over an attention block (``[2, 8, L, ...]``: the windows' scores and
    the summaries') is float32, forward, rematerialised and backward, and
    so is every such block a product writes; bfloat16 appears there only
    as the probabilities cast to meet the values. A kernel that keeps the
    scores on the chip (ROADMAP S11) has no such instruction to show: it
    answers to ``benchmark/evabyte_check.py``'s ``probe``."""
    import re

    text = _compiled_step(chip, monkeypatch, "evabyte3d",
                          batch=2).as_text()
    block = re.compile(r" = (\w+)\[2,8,(2048|768),(2048|768|128|256)\]\S* "
                       r"(exponential|convolution|dot)\(")
    found = [m.groups() for m in map(block.search, text.splitlines()) if m]
    exps = [f for f in found if f[3] == "exponential"]
    assert len(exps) >= 3 and {f[0] for f in exps} == {"f32"}
    products = [f for f in found if f[3] != "exponential"]
    assert products and {f[0] for f in products} == {"f32"}


def test_moonlight3d_training_step_fits_at_the_published_widths(chip,
                                                                monkeypatch):
    """``--model moonlight3d``'s step at 586 M parameters and the cell's
    batch of 2 x 4,864 tokens: the held runs' buffer of 14,848 rows in
    every expert layer (forward, rematerialised forward and backward: 8
    kernels a layer), the attention's kernels in all six layers (forward
    and backward, TWO a layer: ops/attention.py, PR 42; the rematerialised
    layer keeps the forward's ``o`` and log-sum-exp and does not run it
    again, PR 45), every one under the scope ``mla_core``; no float32
    block of scores ``[2, 16, queries, keys]`` of any extent is left in
    the program (the XLA form held ``[2, 16, 512, 4608]`` and nine more),
    never ``[4864, 4864]``; and code + temporaries under what the XLA form
    took (PERF.md, PR 40: 334.8 MiB and 1.792 GiB; 225.9 MiB and 1.648 GiB
    with the kernels; 227.9 MiB and 1.647 GiB with their outputs kept,
    rehearsal compile, PR 45: the six layers' 243 MB do not raise the
    step's peak; 248.3 MiB and 1.650 GiB with the expert products in
    tiles of 1024 and 512, PR 47: the same 52 kernels, each unrolled over
    wider tiles), beside the folded round's 10.9 GiB of state: the chip
    then held 13.952 GiB at its peak for the parent's 13.897, PERF.md
    section 6."""
    import re

    compiled = _compiled_step(chip, monkeypatch, "moonlight3d", batch=2)
    text, mem = compiled.as_text(), compiled.memory_analysis()
    assert text.count(KERNEL_MARK) == 5 * 8 + 6 * 2
    calls = [line for line in text.splitlines() if KERNEL_MARK in line]
    attention = [c for c in calls if "/attention_" in c]
    assert len(attention) == 6 * 2
    assert sum("/attention_forward" in c for c in attention) == 6
    assert all("/mla_core/attention_" in c for c in attention)
    assert "bf16[14848,2048]" in text and "bf16[14848,2816]" in text
    # (the kept log-sum-exp, a number a query and head, waits as its 19
    # blocks of 256: rows' statistics, no block of scores)
    assert set(re.findall(r"f32\[2,16,\d+,\d+\]", text)) <= {
        "f32[2,16,19,256]"}
    assert "[2,16,4864,4864]" not in text
    assert mem.temp_size_in_bytes < 1.7 * 2 ** 30
    assert mem.generated_code_size_in_bytes < 260 * 2 ** 20


def test_moonlight3d_step_keeps_scores_softmax_and_router_float32(
        chip, monkeypatch):
    """The configuration states float32 scores, softmax and router under
    ``bf16_mixed``: the program the cell times says so itself. Nothing over
    the router's ``[9728, 64]`` scores is bfloat16, and its product is
    float32. The attention's scores live inside the kernels since PR 42,
    where the compiled text does not look: in the step as traced for the
    chip, inside the bodies of its 12 ``pallas_call``s, every exponential,
    logarithm, maximum and sum is float32 and every product accumulates in
    float32 (bfloat16 there is an operand of a product or an output); and
    no exponential over a ``[2, 16, ...]`` block is left outside them."""
    import re

    text = _compiled_step(chip, monkeypatch, "moonlight3d",
                          batch=2).as_text()
    assert "bf16[9728,64]" not in text
    assert re.search(r" = f32\[9728,64\]\S* convolution\(", text)
    assert re.search(r" = f32\[9728,64\]\S* exponential\(", text)
    assert not re.search(r" = \w+\[2,16,\d+,\d+\]\S* exponential\(", text)

    from tests.test_moonlight3d import attention_kernel_bodies

    attention_kernel_bodies(_traced_step(monkeypatch, "moonlight3d"),
                            layers=6)


def test_trinity3d_training_step_fits_at_the_published_widths(chip,
                                                              monkeypatch):
    """``--model trinity3d``'s step at 604 M parameters and the cell's
    batch of 2 x 4,864 tokens: the held runs' buffer of 19,456 rows in
    every expert layer (forward, rematerialised forward and backward: 10
    kernels a layer), the attention's kernels in all five layers (forward
    and backward, TWO a layer: ops/attention.py with the window and the
    head map, PR 44; the rematerialised layer keeps the forward's ``o``
    and log-sum-exp and does not run it again, PR 45), eight under
    ``swa_core`` and two under ``full_core``; grouped ``k`` and ``v`` ``[2,
    4864, 512]`` reach the kernels as they are (none repeated over its
    group to ``[..., 4096]`` outside them: ``dk``, ``dv`` come back float32
    and are cast); no float32 block of scores is left in the program; and
    code + temporaries (281.9 MiB and 1.8055 GiB = 1,938,650,624 bytes,
    rehearsal compile, PR 45: the parent's 281.0 MiB and 1.655 GiB plus
    0.15 GiB of the five layers' 0.38 GiB of kept outputs, the rest lies
    under the peak the step already had) that leave room for the folded
    round's 11.25 GiB of state: the chip held 14.917 GiB at its
    peak of 15.75 (PERF.md, PR 45)."""
    import re

    compiled = _compiled_step(chip, monkeypatch, "trinity3d", batch=2)
    text, mem = compiled.as_text(), compiled.memory_analysis()
    assert text.count(KERNEL_MARK) == 4 * 10 + 5 * 2
    calls = [line for line in text.splitlines() if KERNEL_MARK in line]
    attention = [c for c in calls if "/attention_" in c]
    assert len(attention) == 5 * 2
    assert sum("/swa_core/attention_" in c for c in attention) == 4 * 2
    assert sum("/full_core/attention_" in c for c in attention) == 2
    backward = [c for c in attention if "/attention_backward" in c]
    assert len(backward) == 5
    assert all("f32[2,19,256,512]" in c.split(" custom-call(")[0]
               for c in backward)  # dk, dv of the four key/value heads
    assert "bf16[19456,2048]" in text
    # (the kept log-sum-exp, a number a query and head, waits as its 19
    # blocks of 256: rows' statistics, no block of scores)
    assert set(m.group() for m in re.finditer(
        r"f32\[2,(32|4,8),\d+,\d+\]", text)) <= {"f32[2,32,19,256]"}
    assert "4864,4864]" not in text
    assert mem.temp_size_in_bytes < 1.85 * 2 ** 30
    assert mem.generated_code_size_in_bytes < 290 * 2 ** 20


def test_trinity3d_step_keeps_scores_softmax_qk_norms_and_router_float32(
        chip, monkeypatch):
    """The configuration states float32 scores, softmax, QK norm
    statistics and router under ``bf16_mixed``: the program the cell times
    says so itself. Nothing over the router's ``[9728, 128]`` scores is
    bfloat16, and its product is float32; the QK norms' reciprocal roots
    (one a token and head: ``[2, 4864, 32]`` and ``[2, 4864, 4]``)
    are float32; the attention's scores live inside the kernels: in the
    step as traced for the chip, inside the bodies of its 10
    ``pallas_call``s, every exponential, logarithm, maximum and sum is
    float32 and every product accumulates in float32; and no exponential
    over a block of scores is left outside them."""
    import re

    text = _compiled_step(chip, monkeypatch, "trinity3d",
                          batch=2).as_text()
    assert "bf16[9728,128]" not in text
    assert re.search(r" = f32\[9728,128\]\S* convolution\(", text)
    assert re.search(r" = f32\[9728,128\]\S* exponential\(", text)
    assert not re.search(r" = \w+\[2,(32|4,8),\d+,\d+\]\S* exponential\(",
                         text)
    roots = re.findall(r" = (\w+)\[2,4864,(?:32|4)\]\S* rsqrt\(", text)
    assert roots and set(roots) == {"f32"}

    from tests.test_moonlight3d import attention_kernel_bodies

    attention_kernel_bodies(_traced_step(monkeypatch, "trinity3d"), layers=5)


def test_resnet3d_vmapped_step_stays_client_merged(chip, monkeypatch):
    """``resnet3d.fedavg_resident``'s training step: 2 clients of batch 16
    under ``vmap``. The first stage stays in the grouped convolution's
    128-lane layout (PR 39): no instruction has a pre-pool activation
    split into ``[..., 2, 64]`` or ``[2, ...]`` (64 lanes padded to 128:
    4.88 GB written for 2.44), none is a ``.remat`` copy, and the step's
    temporaries are 6.12 GiB where the parent's were 14.36 of the chip's
    15.75. Unbatched (a mesh row) the step takes what the parent's took,
    6.99 GiB: the plain composition."""
    compiled = _compiled_step(chip, monkeypatch, "resnet3d", clients=2)
    text = compiled.as_text()
    assert "f32[16,63,75,63,128]" in text
    for split in ("[16,63,75,63,2,64]", "[2,16,63,75,63,64]"):
        assert split not in text, split
    assert ".remat" not in text
    assert compiled.memory_analysis().temp_size_in_bytes < 6.6 * 2 ** 30
    alone = _compiled_step(chip, monkeypatch, "resnet3d")
    assert "f32[16,63,75,63,64]" in alone.as_text()
    assert 6.8 < alone.memory_analysis().temp_size_in_bytes / 2 ** 30 < 7.2
