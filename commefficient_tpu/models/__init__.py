"""Model zoo and name registry.

The reference selects models by reflected class name
(``getattr(models, args.model)``, cv_train.py:363; choices enumerated from
``dir(models)``, utils.py:114-118). Same surface here: every public model
name resolves through ``get_model``; ``MODEL_NAMES`` drives the CLI choices.
"""

from commefficient_tpu.models.resnet9 import ResNet9, FixupResNet9
from commefficient_tpu.models.resnet18 import ResNet18, FixupResNet18
from commefficient_tpu.models.fixup_resnet import (
    FixupResNet50,
    FixupResNetImageNet,
)
from commefficient_tpu.models.resnets import (
    ResNet101LN,
    resnet18,
    resnet34,
    resnet50,
    resnet101,
    resnet152,
    resnext50_32x4d,
    resnext101_32x8d,
    wide_resnet50_2,
    wide_resnet101_2,
)
from commefficient_tpu.models.joyai import JoyAIConfig, JoyAILM
from commefficient_tpu.models.laguna import LagunaConfig, LagunaLM

_REGISTRY = {
    "ResNet9": ResNet9,
    "FixupResNet9": FixupResNet9,
    "ResNet18": ResNet18,
    "FixupResNet18": FixupResNet18,
    "FixupResNet50": FixupResNet50,
    "ResNet101LN": ResNet101LN,
    "resnet18": resnet18,
    "resnet34": resnet34,
    "resnet50": resnet50,
    "resnet101": resnet101,
    "resnet152": resnet152,
    "resnext50_32x4d": resnext50_32x4d,
    "resnext101_32x8d": resnext101_32x8d,
    "wide_resnet50_2": wide_resnet50_2,
    "wide_resnet101_2": wide_resnet101_2,
    # language models: built from a config.json by gpt2_train
    # (--model laguna|joyai --model_checkpoint <config.json>), not by
    # cv_train
    "laguna": LagunaLM,
    "joyai": JoyAILM,
}

MODEL_NAMES = sorted(_REGISTRY)


def get_model(name: str):
    """Look up a model constructor by its reference-compatible name."""
    try:
        return _REGISTRY[name]
    except KeyError:
        raise ValueError(
            f"unknown model {name!r}; choices: {MODEL_NAMES}") from None


__all__ = ["get_model", "MODEL_NAMES", "LagunaConfig", "LagunaLM",
           "JoyAIConfig", "JoyAILM"] + [
    n for n in _REGISTRY if n not in ("laguna", "joyai")]
