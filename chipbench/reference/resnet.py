"""CIFAR ResNet-(6n+2) (He et al. 2016, section 4.2): 3x3 stem of 16
channels with BN-ReLU, three stages of 16/32/64 channels with n basic blocks
each (conv-BN-ReLU-conv-BN, add, ReLU; a 1x1 conv + BN shortcut where the
shape changes), global average pool and a linear head."""

import jax.numpy as jnp

from .layers import batch_norm, relu


def forward(p, stats, x, sizes, ops):
    conv, dot = ops.conv, ops.dot
    n = (sizes["depth"] - 2) // 6
    new = {}

    def bn(h, name):
        y, upd = batch_norm(h, p, stats, name, 0.9)
        new.update(upd)
        return y

    h = relu(bn(conv(x, p, "stem", 1, 1), "stem_bn"))
    for stage, (planes, stride) in enumerate(
            zip(sizes["channels"], (1, 2, 2))):
        for b in range(n):
            s = stride if b == 0 else 1
            blk = f"stage{stage}_block{b}"
            out = relu(bn(conv(h, p, blk + "/conv1", s, 1), blk + "/bn1"))
            out = bn(conv(out, p, blk + "/conv2", 1, 1), blk + "/bn2")
            if s != 1 or h.shape[-1] != planes:
                h = bn(conv(h, p, blk + "/shortcut_conv", s, 0),
                       blk + "/shortcut_bn")
            h = relu(out + h)
    return dot(jnp.mean(h, axis=(1, 2)), p, "head"), new


def forward_macs(sizes) -> int:
    """Multiply-accumulates of one image's forward pass: convolutions and
    the head, from the shapes alone."""
    n = (sizes["depth"] - 2) // 6
    hw, cin = sizes["input_shape"][0], sizes["input_shape"][2]
    total = hw * hw * 9 * cin * 16
    cin = 16
    for planes, stride in zip(sizes["channels"], (1, 2, 2)):
        for b in range(n):
            s = stride if b == 0 else 1
            out = hw // s
            total += out * out * 9 * cin * planes  # conv1, strided
            total += out * out * 9 * planes * planes  # conv2
            if s != 1 or cin != planes:
                total += out * out * cin * planes  # 1x1 shortcut
            hw, cin = out, planes
    return total + cin * sizes["num_classes"]
