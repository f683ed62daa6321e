"""Flax model zoo: CIFAR ResNets (incl. ResNet-20), VGG-BN, WideResNet, MLP,
and two sparse decoders (Mellum2, KeyeVL2) for next-token training."""

from .keye_vl2 import KeyeVL2
from .mellum2 import Mellum2
from .mlp import MLP
from .registry import (
    available_models,
    dataset_input_shape,
    dataset_num_classes,
    select_model,
)
from .resnet import ResNet, ResNetImageNet, resnet_config, resnet_imagenet_config
from .vgg import VGG, vgg_config
from .wrn import WideResNet

__all__ = [
    "KeyeVL2",
    "MLP",
    "Mellum2",
    "ResNet",
    "ResNetImageNet",
    "VGG",
    "resnet_imagenet_config",
    "WideResNet",
    "available_models",
    "dataset_input_shape",
    "dataset_num_classes",
    "resnet_config",
    "select_model",
    "vgg_config",
]
