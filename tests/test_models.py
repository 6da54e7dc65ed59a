"""Model zoo shape/semantics tests.

Validates layer parity facts derived from the reference: AlexNet3D_Dropout's
flatten width is 256 on the real 121x145x121 ABCD volume
(salient_models.py:171 Linear(256, 64)), CNN_OriginalFedAvg matches the
FedAvg-paper parameter count (cnn.py:13-28), etc.
"""

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from neuroimagedisttraining_tpu.models import (
    AlexNet3D_Dropout,
    AlexNet3D_Deeper_Dropout,
    AlexNet3D_Dropout_Regression,
    ResNet3D_l3,
    CNN_OriginalFedAvg,
    create_model,
    primary_logits,
)
from neuroimagedisttraining_tpu.utils.pytree import tree_size


def _init_and_apply(model, x, train=False):
    rngs = {"params": jax.random.key(0), "dropout": jax.random.key(1)}
    variables = model.init(rngs, x, train=False)
    out, mutated = model.apply(
        variables, x, train=train,
        rngs={"dropout": jax.random.key(2)} if train else None,
        mutable=["batch_stats"] if train else [],
    )
    return variables, out, mutated


def _shapes_only(model, x_shape):
    """Initialize abstractly (no FLOPs) — full ABCD volumes are too slow for
    real CPU conv3d in unit tests."""
    x = jax.ShapeDtypeStruct(x_shape, jnp.float32)
    rngs = {"params": jax.random.key(0), "dropout": jax.random.key(1)}
    return jax.eval_shape(lambda: model.init(rngs, jnp.zeros(x_shape),
                                             train=False))


def test_alexnet3d_flatten_width_matches_reference_on_abcd_shape():
    # Reference hard-codes Linear(256, 64) after flatten (salient_models.py:171);
    # check our pool/conv arithmetic reproduces 256 features on 121x145x121.
    variables = _shapes_only(AlexNet3D_Dropout(num_classes=1),
                             (1, 121, 145, 121, 1))
    assert variables["params"]["fc1"]["kernel"].shape[0] == 256


def test_alexnet3d_train_mode_updates_batch_stats():
    model = AlexNet3D_Dropout(num_classes=1)
    x = jnp.asarray(np.random.default_rng(0).normal(size=(2, 69, 69, 69, 1)),
                    jnp.float32)
    variables, out, mutated = _init_and_apply(model, x, train=True)
    assert out.shape == (2, 1)
    old = variables["batch_stats"]["f0"]["bn"]["mean"]
    new = mutated["batch_stats"]["f0"]["bn"]["mean"]
    assert not np.allclose(np.asarray(old), np.asarray(new))


def test_alexnet3d_deeper_flatten_width_512():
    # flatten width 512 parity (salient_models.py:227 Linear(512, 64))
    variables = _shapes_only(AlexNet3D_Deeper_Dropout(num_classes=2),
                             (1, 121, 145, 121, 1))
    assert variables["params"]["fc1"]["kernel"].shape[0] == 512


def test_alexnet3d_regression_returns_pred_and_features():
    model = AlexNet3D_Dropout_Regression(num_classes=1)
    x = jnp.zeros((3, 69, 69, 69, 1))
    _, out, _ = _init_and_apply(model, x)
    pred, feat = out
    assert pred.shape == (3,)
    assert feat.ndim == 5


def test_resnet3d_l3_runs():
    model = ResNet3D_l3(layers=(1, 1, 1), num_classes=2)
    x = jnp.zeros((1, 49, 57, 49, 1))
    _, out, _ = _init_and_apply(model, x)
    logits, penult = out
    assert logits.shape == (1, 2)
    assert penult.shape == (1, 512)


def test_cnn_original_fedavg_param_count():
    model = CNN_OriginalFedAvg(only_digits=True)
    x = jnp.zeros((1, 28, 28))
    variables, out, _ = _init_and_apply(model, x)
    # 1,663,370 params reported in the FedAvg paper (cnn.py:13-40).
    assert tree_size(variables["params"]) == 1_663_370
    assert out.shape == (1, 10)


@pytest.mark.parametrize("name,shape,nc", [
    ("resnet18", (1, 32, 32, 3), 10),
    ("tiny_resnet18", (1, 64, 64, 3), 200),
    ("resnet18_ip", (1, 32, 32, 3), 10),
    ("vgg11", (1, 32, 32, 3), 10),
    ("cnn_cifar10", (1, 32, 32, 3), 10),
    ("cnn_cifar10_bn", (1, 32, 32, 3), 10),
    ("cnn_cifar100", (1, 32, 32, 3), 100),
    ("lenet5", (1, 28, 28, 1), 10),
    ("lenet5_cifar", (1, 32, 32, 3), 10),
    ("cnn_dropout", (1, 28, 28, 1), 10),
])
def test_registry_models_forward(name, shape, nc):
    model = create_model(name, num_classes=nc)
    x = jnp.zeros(shape)
    _, out, _ = _init_and_apply(model, x)
    assert primary_logits(out).shape == (shape[0], nc)


#: the flagship family's trees at 69^3 under ``key(0)``, as the tree stood
#: before the stem became one function (PR 37; parent 9b24476): leaves and
#: a crc32 over every leaf's path, shape, dtype and bytes in path order;
#: ResNet3D's as it stood before its first stage did (PR 39; parent d4e0186)
PARENT_TREES = {
    "3DCNN": {"params": (24, 1306946193), "batch_stats": (10, 3328936775)},
    "3dcnn_deeper": {"params": (28, 1810821921),
                     "batch_stats": (12, 557897556)},
    "3dcnn_regression": {"params": (24, 1306946193),
                         "batch_stats": (10, 3328936775)},
    "resnet3d": {"params": (31, 1424005596),
                 "batch_stats": (18, 4201344682)},
}
#: the stem block's own leaves, spelled out
PARENT_F0 = {
    "params/f0/conv/kernel": ((5, 5, 5, 1, 64), "float32"),
    "params/f0/conv/bias": ((64,), "float32"),
    "params/f0/bn/scale": ((64,), "float32"),
    "params/f0/bn/bias": ((64,), "float32"),
    "batch_stats/f0/bn/mean": ((64,), "float32"),
    "batch_stats/f0/bn/var": ((64,), "float32"),
}
#: ResNet3D's first stage: ``nn.Conv(use_bias=False)`` "conv1", "bn1"
PARENT_CONV1 = {
    "params/conv1/kernel": ((3, 3, 3, 1, 64), "float32"),
    "params/bn1/scale": ((64,), "float32"),
    "params/bn1/bias": ((64,), "float32"),
    "batch_stats/bn1/mean": ((64,), "float32"),
    "batch_stats/bn1/var": ((64,), "float32"),
}


def _flagship_variables(name):
    model = create_model(name, num_classes=1)
    return model, model.init(
        {"params": jax.random.key(0), "dropout": jax.random.key(1)},
        jnp.zeros((1, 69, 69, 69, 1)), train=False)


@pytest.mark.parametrize("name", sorted(PARENT_TREES))
def test_flagship_trees_are_the_parents(name):
    """``stem_block`` computes f0 and its pool; the model's parameter and
    ``batch_stats`` trees keep the parent's names, shapes, dtypes and, for
    a fixed key, values: checkpoints, SalientGrads' masks and the
    benchmark's float32 reference see the tree they saw."""
    import zlib

    from flax.traverse_util import flatten_dict

    _, variables = _flagship_variables(name)
    assert set(variables) == {"params", "batch_stats"}
    flat = flatten_dict(variables, sep="/")
    stem = PARENT_CONV1 if name == "resnet3d" else PARENT_F0
    for path, (shape, dtype) in stem.items():
        assert (flat[path].shape, str(flat[path].dtype)) == (shape, dtype)
    modules = {p.split("/")[1] for p in stem}   # f0; conv1 and bn1
    assert {p for p in flat if p.split("/")[1] in modules} == set(stem)
    for col, (leaves, want) in PARENT_TREES[name].items():
        crc, table = 0, []
        for path, leaf in sorted(flatten_dict(variables[col],
                                              sep="/").items()):
            crc = zlib.crc32(f"{path}{leaf.shape}{leaf.dtype}".encode()
                             + np.asarray(leaf).tobytes(), crc)
            table.append((path, leaf.shape, str(leaf.dtype)))
        assert (len(table), crc) == (leaves, want), (col, table)


@pytest.mark.parametrize("name", ["3DCNN", "resnet3d"])
def test_parents_checkpoint_restores_into_the_flagship(tmp_path, name):
    """A checkpoint holding the parent's tree (built here from the pinned
    names, not from the model) is read back, takes the model's variables'
    structure leaf for leaf, and the model runs on it, evaluating and
    training; the running statistics move by the parent's rule (momentum
    0.9). ResNet3D's first stage (PR 39) as the flagship's (PR 37)."""
    from flax import serialization
    from flax.traverse_util import flatten_dict, unflatten_dict

    from neuroimagedisttraining_tpu.utils.checkpoint import (
        load_checkpoint,
        save_checkpoint,
    )

    model, variables = _flagship_variables(name)
    flat = flatten_dict(jax.tree.map(np.asarray, dict(variables)), sep="/")
    assert set(PARENT_CONV1 if name == "resnet3d" else PARENT_F0) <= set(flat)
    rng = np.random.default_rng(0)
    written = {p: (v + 0.01 * rng.standard_normal(v.shape)).astype(v.dtype)
               for p, v in flat.items()}
    save_checkpoint(str(tmp_path), 3, {"variables": unflatten_dict(
        written, sep="/")})
    rnd, state = load_checkpoint(str(tmp_path))
    assert rnd == 3
    restored = serialization.from_state_dict(variables, state["variables"])
    for p, v in flatten_dict(restored, sep="/").items():
        np.testing.assert_array_equal(np.asarray(v), written[p])
    x = jnp.asarray(rng.standard_normal((2, 69, 69, 69, 1)), jnp.float32)
    assert primary_logits(
        model.apply(restored, x, train=False)).shape == (2, 1)
    _, new = model.apply(restored, x, train=True, mutable=["batch_stats"],
                         rngs={"dropout": jax.random.key(2)})
    # stem_block's batch statistics, through the parent's running update
    from neuroimagedisttraining_tpu.ops import stemconv
    if name == "resnet3d":
        y = stemconv._conv(x, restored["params"]["conv1"]["kernel"],
                           stemconv._Window(3, 2, 3))
        stats = lambda tree: tree["batch_stats"]["bn1"]
    else:
        f0 = restored["params"]["f0"]["conv"]
        y = stemconv._conv_bias(x, f0["kernel"], f0["bias"])
        stats = lambda tree: tree["batch_stats"]["f0"]["bn"]
    mean = jnp.mean(y, (0, 1, 2, 3))
    for which, batch in (("mean", mean),
                         ("var", jnp.mean(y * y, (0, 1, 2, 3)) - mean ** 2)):
        np.testing.assert_allclose(
            np.asarray(stats(new)[which]),
            np.asarray(0.9 * stats(restored)[which] + 0.1 * batch),
            rtol=1e-5, atol=1e-6)


@pytest.mark.parametrize("name,parents", [
    ("3DCNN", 7_452_031_488.0), ("resnet3d", 28_219_390_080.0)])
def test_flagship_flops_count_sees_the_stem_convolution(name, parents):
    """``ops/flops.py`` counts a convolution from its module's captured
    output; ``stem_block`` never materialises f0's (nor ResNet3D's
    ``conv1``'s), so the stage declares its shape where the counter looks:
    the count is the parent's."""
    from neuroimagedisttraining_tpu.ops import flops

    model = create_model(name, num_classes=1)
    x = jnp.zeros((1, 121, 145, 121, 1))
    variables = jax.eval_shape(lambda: model.init(
        {"params": jax.random.key(0), "dropout": jax.random.key(1)}, x,
        train=False))
    assert flops.count_inference_flops(
        model, variables["params"], x,
        batch_stats=variables["batch_stats"]) == parents
    # and the forward pass proper sows nothing
    assert set(jax.eval_shape(
        lambda v: model.apply(v, x, train=True, mutable=["batch_stats"],
                              rngs={"dropout": jax.random.key(2)})[1],
        variables)) == {"batch_stats"}


def test_norm_variants_have_no_running_stats():
    """GN-3D and resnet_ip variants must carry NO batch_stats collection —
    GroupNorm is stat-free and IP-norm never tracks (resnet_ip semantics,
    track_running_stats=False). The 3D variant is shape-checked lazily at
    the real ABCD shape (the full AlexNet3D stack needs >= ~41^3 inputs)."""
    import jax

    # resnet18_ip: real forward at CIFAR shape
    model = create_model("resnet18_ip", num_classes=2)
    variables, out, _ = _init_and_apply(model, jnp.zeros((1, 32, 32, 3)))
    assert primary_logits(out).shape == (1, 2)
    assert not jax.tree.leaves(dict(variables).get("batch_stats", {}))

    # 3dcnn_gn: eval_shape at ABCD scale (no compute)
    m3 = create_model("3dcnn_gn", num_classes=2)
    variables = jax.eval_shape(
        lambda: m3.init({"params": jax.random.key(0),
                         "dropout": jax.random.key(1)},
                        jnp.zeros((1, 121, 145, 121, 1)), train=False))
    assert not jax.tree.leaves(dict(variables).get("batch_stats", {}))
    # GN params exist where BN params would have been
    assert "gn" in variables["params"]["f0"]


def test_lenet5_flatten_matches_caffe_5x5_to_4x4():
    # lenet5.py:18 hard-codes 50*4*4; verify our VALID conv/pool arithmetic.
    model = create_model("lenet5", num_classes=10)
    x = jnp.zeros((1, 28, 28, 1))
    variables, _, _ = _init_and_apply(model, x)
    assert variables["params"]["fc3"]["kernel"].shape[0] == 50 * 4 * 4


# ---------- the arrows between ops/, models/ and core/ (PR 43) ----------

_PKG = "neuroimagedisttraining_tpu"


def _imports(path):
    """``(module, inside a function, under TYPE_CHECKING)`` for every
    import statement of a file, from its syntax alone (nothing is
    imported); ``from pkg.a import b`` counts as ``pkg.a.b`` too."""
    import ast

    found = []

    def walk(node, local, typing_only):
        for child in ast.iter_child_nodes(node):
            if isinstance(child, ast.Import):
                found.extend((a.name, local, typing_only)
                             for a in child.names)
            elif isinstance(child, ast.ImportFrom):
                assert child.level == 0, f"{path}: a relative import"
                found.append((child.module, local, typing_only))
                found.extend((f"{child.module}.{a.name}", local, typing_only)
                             for a in child.names)
            elif isinstance(child, ast.If) and "TYPE_CHECKING" in ast.dump(
                    child.test):
                walk(child, local, True)
            else:
                walk(child, local or isinstance(
                    child, (ast.FunctionDef, ast.AsyncFunctionDef,
                            ast.Lambda)), typing_only)

    with open(path) as f:
        walk(ast.parse(f.read()), False, False)
    return found


def import_violations(package_dir):
    """What points the wrong way under ``package_dir``, a rule each:
    ``ops/`` is a leaf, no trunk file imports another, and no model
    file hides an ``ops`` import inside a function."""
    import glob
    import os

    bad = {"ops_is_a_leaf": [], "no_model_imports_a_model": [],
           "no_local_ops_import_in_models": []}
    files = lambda sub: sorted(glob.glob(os.path.join(package_dir, sub,
                                                      "*.py")))
    for path in files("ops"):
        for module, _, typing_only in _imports(path):
            if not typing_only and module.startswith(
                    (f"{_PKG}.models", f"{_PKG}.core", f"{_PKG}.engines")):
                bad["ops_is_a_leaf"].append((path, module))
    trunks = {os.path.basename(p)[:-3] for p in files("models")
              if p.endswith("3d.py")} - {"tokens3d"}
    for path in files("models"):
        own = os.path.basename(path)[:-3]
        for module, local, _ in _imports(path):
            parts = module.split(".")
            if own != "__init__" and parts[:2] == [_PKG, "models"] \
                    and len(parts) > 2 and parts[2] in trunks - {own}:
                bad["no_model_imports_a_model"].append((path, module))
            if local and parts[:2] == [_PKG, "ops"]:
                bad["no_local_ops_import_in_models"].append((path, module))
    return bad


@pytest.mark.parametrize("rule", ["ops_is_a_leaf",
                                  "no_model_imports_a_model",
                                  "no_local_ops_import_in_models"])
def test_imports_point_one_way(rule):
    """ARCHITECTURE.md's layer map, held: ``ops`` below ``models`` below
    ``core``. At PR 42 this found ``ops/snip.py`` -> ``core.trainer``,
    ``ops/attention.py`` -> ``models.tokens3d``, ``moonlight3d`` ->
    ``evabyte3d`` and ``zaya3d``, and fourteen ``ops`` imports inside
    functions of five model files (eleven of them marked ``# ops imports
    models``)."""
    import os

    import neuroimagedisttraining_tpu

    package_dir = os.path.dirname(neuroimagedisttraining_tpu.__file__)
    assert import_violations(package_dir)[rule] == []
