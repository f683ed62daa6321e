"""Flax model zoo: CIFAR ResNets (incl. ResNet-20), VGG-BN, WideResNet, MLP,
and a sparse decoder (Mellum2) for next-token training."""

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
