"""Model registry with reference-compatible selection semantics.

Parity with ``util.select_model`` (/root/reference/util.py:256-273):
``'res'`` → ResNet-50 on cifar10 / ResNet-18 on cifar100+ (the reference's
depth policy), ``'VGG'`` → VGG-16, ``'wrn'`` → WideResNet-28-10,
``'mlp'`` → 784-500-500 MLP.  Fixes quirk Q6 (SURVEY.md §2.7): the reference
driver hard-codes ``num_class=100`` regardless of dataset (train_mpi.py:84);
here the class count is derived from the dataset unless overridden.

Also registers explicit names the reference cannot express: ``resnet20``
(BASELINE.json's model), ``resnet32/44/56/110``, ``vgg11/13/19``, and the
sparse decoders for next-token training whose sizes come as ``sizes={...}``:
``mellum2`` (``models/mellum2.py``), ``keye_vl2``, whose attention reads a
learned choice of keys (``models/keye_vl2.py``), ``qwen3_next``, three
layers of linear attention to one of gated softmax attention
(``models/qwen3_next.py``), and ``sdar``, trained by block diffusion over a
noisy and a clean copy of every row (``models/sdar.py``).
"""

from __future__ import annotations

from typing import Any, Tuple

import flax.linen as nn

from .keye_vl2 import KeyeVL2
from .mellum2 import Mellum2
from .mlp import MLP
from .qwen3_next import Qwen3Next
from .resnet import ResNet, ResNetImageNet
from .sdar import Sdar
from .vgg import VGG
from .wrn import WideResNet

__all__ = ["select_model", "dataset_num_classes", "dataset_input_shape", "available_models"]

DATASET_CLASSES = {
    "cifar10": 10,
    "cifar100": 100,
    "imagenet": 1000,
    "emnist": 47,
    "digits": 10,
    "synthetic": 10,
    "synthetic_image": 10,
}

DATASET_SHAPES = {
    "cifar10": (32, 32, 3),
    "cifar100": (32, 32, 3),
    "imagenet": (224, 224, 3),
    "emnist": (28, 28, 1),
    "digits": (8, 8, 1),
    "synthetic": (28, 28, 1),
    "synthetic_image": (32, 32, 3),
}


TOKEN_MODELS = {"mellum2": Mellum2, "keye_vl2": KeyeVL2,
                "qwen3_next": Qwen3Next, "sdar": Sdar}


def dataset_num_classes(dataset: str) -> int:
    if dataset not in DATASET_CLASSES:
        raise KeyError(f"unknown dataset '{dataset}'; have {sorted(DATASET_CLASSES)}")
    return DATASET_CLASSES[dataset]


def dataset_input_shape(dataset: str) -> Tuple[int, ...]:
    return DATASET_SHAPES[dataset]


def select_model(
    name: str,
    dataset: str = "cifar10",
    num_classes: int | None = None,
    dtype: Any = None,
    **overrides,
) -> nn.Module:
    """Build a model by registry name.

    Reference aliases ('res', 'VGG', 'wrn', 'mlp') follow util.py:256-273
    selection policy; explicit names ('resnet20', 'vgg16', ...) set the depth
    directly.
    """
    kw = dict(overrides)
    if dtype is not None:
        kw["dtype"] = dtype

    lname = name.lower()
    if lname in TOKEN_MODELS:
        # a token model: its sizes come as ``sizes={...}`` (the vocabulary
        # it holds among them), not from the data set's name
        if "sizes" not in kw:
            raise ValueError(f"model '{lname}' needs sizes={{...}} "
                             "(TrainConfig.model_kwargs)")
        return TOKEN_MODELS[lname](**kw)
    classes = num_classes if num_classes is not None else dataset_num_classes(dataset)
    if name == "res":  # reference depth policy (util.py:258-265)
        if dataset == "imagenet":  # torchvision resnet18 path (util.py:262)
            return ResNetImageNet(depth=18, num_classes=classes, **kw)
        depth = 50 if dataset == "cifar10" else 18
        return ResNet(depth=depth, num_classes=classes, **kw)
    if lname.startswith("resnet"):
        depth = int(lname[len("resnet"):])
        # imagenet gets the 4-stage 7x7-stem layout, CIFAR the 3-stage one
        if dataset == "imagenet":
            return ResNetImageNet(depth=depth, num_classes=classes, **kw)
        return ResNet(depth=depth, num_classes=classes, **kw)
    if name == "VGG" or lname == "vgg":
        return VGG(depth=16, num_classes=classes, **kw)
    if lname.startswith("vgg"):
        return VGG(depth=int(lname[len("vgg"):]), num_classes=classes, **kw)
    if lname == "wrn":
        return WideResNet(depth=28, widen_factor=10, num_classes=classes, **kw)
    if lname.startswith("wrn-"):
        depth, widen = lname[len("wrn-"):].split("-")
        return WideResNet(depth=int(depth), widen_factor=int(widen),
                          num_classes=classes, **kw)
    if lname == "mlp":
        return MLP(num_classes=classes, **kw)
    raise KeyError(f"unknown model '{name}'; have {available_models()}")


def available_models():
    return ["res", "resnet<depth>", "VGG", "vgg<depth>", "wrn", "wrn-<d>-<k>", "mlp",
            *TOKEN_MODELS]
