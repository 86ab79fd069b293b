"""Model zoo: name-based registry (reference models/__init__.py:1-7,
utils.py:114-118 introspect ``--model`` choices from the module and
instantiate via getattr).

All models are flax.linen Modules in NHWC layout (TPU-native). Batch-norm-free
defaults (plain convs / Fixup / LayerNorm) are preserved from the reference —
they are load-bearing for federated correctness (no cross-client BN leakage).
"""

from commefficient_tpu.models.resnet9 import ResNet9
from commefficient_tpu.models.fixup_resnet9 import FixupResNet9
from commefficient_tpu.models.fixup_resnet18 import FixupResNet18, ResNet18
from commefficient_tpu.models.fixup_resnet50 import FixupResNet50
from commefficient_tpu.models.resnets import (
    ResNetTV, resnet18, resnet34, resnet50, resnet101, resnet152,
    resnext50_32x4d, resnext101_32x8d, wide_resnet50_2, wide_resnet101_2,
    ResNet101LN, ResNet50LN)
from commefficient_tpu.models.toy import ToyLinear, TinyMLP
# the language models are built by training/gpt2.py from their own configs
# (``--model gpt2``, ``nemotron_h``, ``ouro``); the registry below is
# training/cv.py's
from commefficient_tpu.models.nemotron_h import NemotronH, NemotronHConfig
from commefficient_tpu.models.ouro import Ouro, OuroConfig

MODEL_REGISTRY = {
    "ResNet9": ResNet9,
    "FixupResNet9": FixupResNet9,
    "FixupResNet18": FixupResNet18,
    "FixupResNet50": FixupResNet50,
    "ResNet18": ResNet18,
    "ResNet34": resnet34,
    "ResNet50": resnet50,
    "ResNet101": resnet101,
    "ResNet152": resnet152,
    "ResNeXt50": resnext50_32x4d,
    "ResNeXt101": resnext101_32x8d,
    "WideResNet50": wide_resnet50_2,
    "WideResNet101": wide_resnet101_2,
    "ResNet101LN": ResNet101LN,
    "ResNet50LN": ResNet50LN,
    "ToyLinear": ToyLinear,
    "TinyMLP": TinyMLP,
}


def get_model(name: str, **kwargs):
    if name not in MODEL_REGISTRY:
        raise ValueError(f"unknown model {name!r}; choices: "
                         f"{sorted(MODEL_REGISTRY)}")
    return MODEL_REGISTRY[name](**kwargs)


__all__ = ["MODEL_REGISTRY", "get_model", "ResNet9", "FixupResNet9",
           "FixupResNet18", "FixupResNet50", "ResNet18", "ResNetTV",
           "resnet18", "resnet34", "resnet50", "resnet101", "resnet152",
           "resnext50_32x4d", "resnext101_32x8d", "wide_resnet50_2",
           "wide_resnet101_2", "ResNet101LN", "ResNet50LN",
           "ToyLinear", "TinyMLP", "NemotronH", "NemotronHConfig", "Ouro",
           "OuroConfig"]
