"""Model zoo + registry.

``create_model`` mirrors the reference harness dispatch
(fedml_experiments/standalone/sailentgrads/main_sailentgrads.py:164-178:
``--model 3DCNN`` -> ``AlexNet3D_Dropout(num_classes=1)``), extended with
every model family the reference zoo contains.

Also covered, though vestigial in the reference (constructed by no
main_*.py entry point, SURVEY.md §2.5): the meta/mask models
(``models/meta.py`` — CNNCifarMeta + MetaNet hypernetwork,
cnn_meta.py:17-176) and the DARTS NAS suite (``models/darts.py`` —
search supernet, GDAS, exact-autodiff bilevel architect, genotype
derivation, fixed-genotype evaluation net).

Explicitly SKIPPED:

- ``batchnorm_utils`` sync-BN helpers: torch-DDP-specific; cross-replica
  BN on TPU would be an axis-name mean inside shard_map, unused by every
  reference experiment.
- ``resnet_meta.py``/``resnet_meta_2.py``: the same mask-hypernetwork
  pattern as cnn_meta applied to a ResNet trunk; the pattern is covered
  by models/meta.py (MetaNet is trunk-agnostic), the specific trunks are
  dead code even upstream.

The reference's ``resnet_ip`` per-batch-BN personalization variant IS
covered: ``--model resnet18_ip`` (norm="ipbn", resnet2d._Norm).
"""

from __future__ import annotations

import jax.numpy as jnp

from neuroimagedisttraining_tpu.models.neuro3d import (  # noqa: F401
    AlexNet3D_Dropout,
    AlexNet3D_Deeper_Dropout,
    AlexNet3D_Dropout_Regression,
    BasicBlock3D,
    Bottleneck3D,
    ResNet3D_l3,
    Tiny3DCNN,
)
from neuroimagedisttraining_tpu.models.nemotronh3d import (  # noqa: F401
    NemotronH3D,
)
from neuroimagedisttraining_tpu.models.olmoe3d import OLMoE3D  # noqa: F401
from neuroimagedisttraining_tpu.models.zaya3d import Zaya3D  # noqa: F401
from neuroimagedisttraining_tpu.models.resnet2d import (  # noqa: F401
    ResNet18,
    customized_resnet18,
    original_resnet18,
    tiny_resnet18,
)
from neuroimagedisttraining_tpu.models.darts import (  # noqa: F401
    DARTS_V1,
    DARTS_V2,
    DartsNetwork,
    DartsSearch,
    DartsSearchNet,
    DartsTrainer,
    FedNAS_V1,
    Genotype,
    PRIMITIVES,
    derive_genotype,
)
from neuroimagedisttraining_tpu.models.meta import (  # noqa: F401
    CNNCifarMeta,
    MetaNet,
    ResNetMeta,
)
from neuroimagedisttraining_tpu.models.vision2d import (  # noqa: F401
    VGG,
    vgg11,
    vgg16,
    CNNCifar,
    CNNCifarBN,
    CNN_OriginalFedAvg,
    CNN_DropOut,
    LeNet5,
    LeNet5_cifar,
)


def create_model(name: str, num_classes: int = 1, dtype=jnp.float32,
                 remat: bool | str | None = None):
    """Build a model by its reference CLI name. ``remat`` (None = model
    default) applies to the 3D family: False | "stem" | True — see
    AlexNet3D_Dropout.remat and PROFILE.md."""
    name = name.lower()
    rkw = {} if remat is None else {"remat": remat}
    if name in ("3dcnn", "alexnet3d", "alexnet3d_dropout"):
        return AlexNet3D_Dropout(num_classes=num_classes, dtype=dtype, **rkw)
    if name in ("3dcnn_gn", "alexnet3d_dropout_gn"):
        return AlexNet3D_Dropout(num_classes=num_classes, dtype=dtype,
                                 norm="group", **rkw)
    if name in ("3dcnn_deeper", "alexnet3d_deeper_dropout"):
        return AlexNet3D_Deeper_Dropout(num_classes=num_classes, dtype=dtype,
                                        **rkw)
    if name in ("3dcnn_regression", "alexnet3d_dropout_regression"):
        return AlexNet3D_Dropout_Regression(num_classes=num_classes,
                                            dtype=dtype, **rkw)
    if name in ("3dcnn_tiny", "tiny3dcnn"):
        return Tiny3DCNN(num_classes=num_classes, dtype=dtype)
    if name in ("resnet3d", "resnet_l3", "resnet3d_l3"):
        return ResNet3D_l3(num_classes=num_classes, dtype=dtype)
    if name == "olmoe3d":
        # added here, not ported (models/olmoe3d.py): OLMoE-1B-7B's
        # sparse-expert block at its published widths over 3D patch tokens
        return OLMoE3D(num_classes=num_classes, dtype=dtype,
                       remat=remat is True)
    if name == "nemotronh3d":
        # added here, not ported (models/nemotronh3d.py): the first nine
        # layers of Nemotron-H's hybrid pattern at the published widths,
        # 8 of each expert layer's 128 experts held. Its layers are
        # rematerialised by the model's own declaration (remat_layers):
        # --remat is the 3D CNN family's policy and does not reach it
        return NemotronH3D(num_classes=num_classes, dtype=dtype)
    if name == "zaya3d":
        # added here, not ported (models/zaya3d.py): five of ZAYA1-8B's
        # layers at the published widths, experts 0-7 of each layer's 16
        # held; rematerialised by its own declaration, like nemotronh3d
        return Zaya3D(num_classes=num_classes, dtype=dtype)
    if name == "evabyte3d":
        # added here, not ported (models/evabyte3d.py): four of EvaByte's
        # layers at the published widths, 8 of each layer's 32 heads held;
        # rematerialised by its own declaration, like nemotronh3d
        from neuroimagedisttraining_tpu.models.evabyte3d import EvaByte3D

        return EvaByte3D(num_classes=num_classes, dtype=dtype)
    if name == "moonlight3d":
        # added here, not ported (models/moonlight3d.py): Moonlight-16B-
        # A3B's leading dense layer and five of its expert layers at the
        # published widths, experts 0-7 of each layer's 64 held;
        # rematerialised by its own declaration, like nemotronh3d
        from neuroimagedisttraining_tpu.models.moonlight3d import Moonlight3D

        return Moonlight3D(num_classes=num_classes, dtype=dtype)
    if name == "trinity3d":
        # added here, not ported (models/trinity3d.py): Trinity-Mini's
        # leading dense layer and four of its expert layers at the
        # published widths (three of sliding-window attention, one of
        # full), experts 0-15 of each layer's 128 held; rematerialised by
        # its own declaration, like nemotronh3d
        from neuroimagedisttraining_tpu.models.trinity3d import Trinity3D

        return Trinity3D(num_classes=num_classes, dtype=dtype)
    if name in ("resnet18", "customized_resnet18"):
        return customized_resnet18(num_classes=num_classes, dtype=dtype)
    if name == "original_resnet18":
        return original_resnet18(num_classes=num_classes, dtype=dtype)
    if name == "tiny_resnet18":
        return tiny_resnet18(num_classes=num_classes, dtype=dtype)
    if name in ("resnet18_ip", "resnet_ip"):
        return ResNet18(num_classes=num_classes, norm="ipbn", dtype=dtype)
    if name == "vgg11":
        return vgg11(num_classes=num_classes, dtype=dtype)
    if name == "vgg16":
        return vgg16(num_classes=num_classes, dtype=dtype)
    if name in ("cnn_cifar10", "cnn_cifar100", "simple-cnn"):
        return CNNCifar(num_classes=num_classes, dtype=dtype)
    if name in ("cnn_cifar10_bn", "cnn_cifar100_bn"):
        return CNNCifarBN(num_classes=num_classes, dtype=dtype)
    if name in ("cnn", "cnn_originalfedavg"):
        return CNN_OriginalFedAvg(only_digits=num_classes <= 10, dtype=dtype)
    if name in ("cnn_dropout", "femnist-cnn"):
        return CNN_DropOut(only_digits=num_classes <= 10, dtype=dtype)
    if name == "lenet5":
        return LeNet5(num_classes=num_classes, dtype=dtype)
    if name == "lenet5_cifar":
        return LeNet5_cifar(num_classes=num_classes, dtype=dtype)
    if name == "darts_search":
        return DartsSearchNet(num_classes=num_classes, dtype=dtype)
    if name in ("darts", "darts_v2"):
        return DartsNetwork(genotype=DARTS_V2, num_classes=num_classes,
                            dtype=dtype)
    if name == "fednas_v1":
        return DartsNetwork(genotype=FedNAS_V1, num_classes=num_classes,
                            dtype=dtype)
    if name in ("cnn_cifar10_meta", "cnn_meta"):
        return CNNCifarMeta(num_classes=num_classes, dtype=dtype)
    if name in ("resnet_meta", "resnet20_meta"):
        return ResNetMeta(num_classes=num_classes, dtype=dtype)
    raise ValueError(f"unknown model: {name!r}")


def primary_logits(out):
    """Some reference models return ``[logits, aux]`` (salient_models.py:139,
    246, 297); normalize to the logits tensor."""
    if isinstance(out, (tuple, list)):
        return out[0]
    return out


def aux_outputs(out) -> dict | None:
    """The auxiliary dict of a model that declares one (``returns_aux``:
    ``(logits, {"loss": weighted scalar, "expert_tokens": int32 [E],
    ...})``, the integer entries named by the model's ``aux_counters``;
    models/olmoe3d.py, models/nemotronh3d.py, models/zaya3d.py), or None:
    the reference models' second output is a feature tensor, never a
    dict."""
    if isinstance(out, (tuple, list)) and len(out) == 2 \
            and isinstance(out[1], dict):
        return out[1]
    return None
