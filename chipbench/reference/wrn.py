"""WideResNet-d-k (Zagoruyko & Komodakis 2016): pre-activation wide basic
blocks BN-ReLU-conv3x3-BN-ReLU-conv3x3 plus a 1x1-conv shortcut where the
shape changes, stages of 16k/32k/64k channels with (d-4)/6 blocks each, a
final BN-ReLU, global average pool and a linear head.  Dropout 0."""

import jax.numpy as jnp

from .layers import batch_norm, relu


def forward(p, stats, x, sizes, ops):
    conv, dot = ops.conv, ops.dot
    n = (sizes["depth"] - 4) // 6
    k = sizes["widen_factor"]
    new = {}

    def bn(h, name, momentum=0.9):
        y, upd = batch_norm(h, p, stats, name, momentum)
        new.update(upd)
        return y

    h = conv(x, p, "stem", 1, 1)
    for stage, (planes, stride) in enumerate(
            zip((16 * k, 32 * k, 64 * k), (1, 2, 2))):
        for b in range(n):
            s = stride if b == 0 else 1
            blk = f"stage{stage}_block{b}"
            out = conv(relu(bn(h, blk + "/bn1")), p, blk + "/conv1", 1, 1)
            out = conv(relu(bn(out, blk + "/bn2")), p, blk + "/conv2", s, 1)
            if s != 1 or h.shape[-1] != planes:
                h = conv(h, p, blk + "/shortcut_conv", s, 0)
            h = out + h
    # the final norm's running statistics move fast (torch momentum 0.9)
    h = relu(bn(h, "final_bn", momentum=0.1))
    return dot(jnp.mean(h, axis=(1, 2)), p, "head"), new


def forward_macs(sizes) -> int:
    """Multiply-accumulates of one image's forward pass: convolutions and
    the head, from the shapes alone."""
    n = (sizes["depth"] - 4) // 6
    k = sizes["widen_factor"]
    hw, cin = sizes["input_shape"][0], sizes["input_shape"][2]
    total = hw * hw * 9 * cin * 16
    cin = 16
    for planes, stride in zip((16 * k, 32 * k, 64 * k), (1, 2, 2)):
        for b in range(n):
            s = stride if b == 0 else 1
            total += hw * hw * 9 * cin * planes  # conv1 at the input size
            out = hw // s
            total += out * out * 9 * planes * planes  # conv2, strided
            if s != 1 or cin != planes:
                total += out * out * cin * planes  # 1x1 shortcut
            hw, cin = out, planes
    return total + cin * sizes["num_classes"]
