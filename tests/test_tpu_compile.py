"""The main path's Pallas kernels compile for the chip, asked of the TPU
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
from neuroimagedisttraining_tpu.ops.stemconv import _dw_pallas
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


def test_stem_dw_compiles_at_full_volume(chip):
    """The opt-in stem weight-gradient (NIDT_FAST_STEM=1) at the real
    volume and channel widths. Batch 1: the program is the same 125-tap
    patch build and split-K grid at any batch, and batch 16 takes the
    compiler ~90 s (PR 21's rehearsal compiled it once)."""
    d, h, w = ((s - 5) // 2 + 1 for s in SHAPE)
    text = jax.jit(_dw_pallas).lower(
        _on(chip, (1,) + SHAPE + (1,), jnp.bfloat16),
        _on(chip, (1, d, h, w, 64), jnp.bfloat16)).compile().as_text()
    assert KERNEL_MARK in text
