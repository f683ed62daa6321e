"""CIFAR-style ResNets in Flax (NHWC, TPU-native).

Capability parity with the reference model zoo
(/root/reference/models/resnet.py:89-122): 3 stages of 16/32/64 planes,
3×3 stem, 8×8 average pool, single linear head; named depths
{18, 34, 50, 101, 152} use the reference's (block, num_blocks) table
(resnet.py:21-32, first three entries of each list — the fourth is unused in
the 3-stage layout).  Additionally supports the classic CIFAR family
{20, 32, 44, 56, 110} with (depth−2)/6 basic blocks per stage — the
"ResNet-20" named by BASELINE.json that the reference zoo cannot express.

TPU notes: convolutions carry bias like the reference (bias=True); BatchNorm
statistics are **per virtual worker** — the module is vmapped over the worker
axis by the trainer, so no cross-worker stat syncing can occur (SURVEY.md §7
"BatchNorm under decentralized DP").

The packed form (``ResNet.packed_apply``, PERF.md section 6, PR 30): a
16-channel activation fills an eighth of the TPU's 128 lanes, and under the
trainer's ``vmap`` the compiler lays such a model out with the batch or a
half-empty channel dimension in the lanes, every activation stored two to
four times padded.  P workers side by side in the channel dimension are one
ResNet P times as wide whose convolution kernels are block diagonal: the
same modules, the same parameter and ``batch_stats`` trees (leaves
``[P, ...]``), lanes full.  Batch norm is per channel, so every worker
keeps its own statistics; the zero blocks multiply zeros.
"""

from __future__ import annotations

from typing import Any, Callable, Sequence, Tuple

import flax.linen as nn
import jax
import jax.numpy as jnp

__all__ = ["ResNet", "ResNetImageNet", "resnet_config", "resnet_imagenet_config"]

#: planes of the three stages of the CIFAR layout; the stem has the first
STAGE_PLANES = (16, 32, 64)


def resnet_config(depth: int) -> Tuple[str, Sequence[int]]:
    """(block_kind, blocks_per_stage) for a named depth."""
    reference = {
        18: ("basic", (2, 2, 2)),
        34: ("basic", (3, 4, 6)),
        50: ("bottleneck", (3, 4, 6)),
        101: ("bottleneck", (3, 4, 23)),
        152: ("bottleneck", (3, 8, 36)),
    }
    if depth in reference:
        return reference[depth]
    if depth >= 8 and (depth - 2) % 6 == 0:  # classic CIFAR ResNet-6n+2
        n = (depth - 2) // 6  # n=1 gives ResNet-8, the smallest of the family
        return "basic", (n, n, n)
    raise ValueError(
        f"unsupported ResNet depth {depth}: need one of {sorted(reference)} or 6n+2"
    )


def _remat_block(block: Callable) -> Callable:
    """Block-level rematerialization: the backward pass recomputes each
    residual block's interior instead of keeping it live, so activation
    memory drops from every-conv-output to block boundaries only (the
    TPU-first FLOPs-for-HBM trade; at 256 folded workers × batch 32 the
    un-rematted vmapped backward over-allocates v5e HBM — r4 finding).
    ``train`` (arg index 2 counting the module) is a trace-time constant.
    """
    return nn.remat(block, static_argnums=(2,))


class BasicBlock(nn.Module):
    planes: int
    stride: int = 1
    dtype: Any = jnp.float32

    @nn.compact
    def __call__(self, x, train: bool):
        conv = lambda f, s, n: nn.Conv(
            f, (3, 3), strides=(s, s), padding=1, use_bias=True, dtype=self.dtype, name=n
        )
        bn = lambda n: nn.BatchNorm(use_running_average=not train, momentum=0.9,
                                    dtype=self.dtype, name=n)
        out = nn.relu(bn("bn1")(conv(self.planes, self.stride, "conv1")(x)))
        out = bn("bn2")(conv(self.planes, 1, "conv2")(out))
        if self.stride != 1 or x.shape[-1] != self.planes:
            x = nn.Conv(self.planes, (1, 1), strides=(self.stride, self.stride),
                        use_bias=True, dtype=self.dtype, name="shortcut_conv")(x)
            x = nn.BatchNorm(use_running_average=not train, momentum=0.9,
                             dtype=self.dtype, name="shortcut_bn")(x)
        return nn.relu(out + x)


class Bottleneck(nn.Module):
    planes: int
    stride: int = 1
    dtype: Any = jnp.float32
    expansion: int = 4

    @nn.compact
    def __call__(self, x, train: bool):
        bn = lambda n: nn.BatchNorm(use_running_average=not train, momentum=0.9,
                                    dtype=self.dtype, name=n)
        out = nn.relu(bn("bn1")(nn.Conv(self.planes, (1, 1), use_bias=True,
                                        dtype=self.dtype, name="conv1")(x)))
        out = nn.relu(bn("bn2")(nn.Conv(self.planes, (3, 3),
                                        strides=(self.stride, self.stride), padding=1,
                                        use_bias=True, dtype=self.dtype, name="conv2")(out)))
        out = bn("bn3")(nn.Conv(self.planes * self.expansion, (1, 1), use_bias=True,
                                dtype=self.dtype, name="conv3")(out))
        want = self.planes * self.expansion
        if self.stride != 1 or x.shape[-1] != want:
            x = nn.Conv(want, (1, 1), strides=(self.stride, self.stride), use_bias=True,
                        dtype=self.dtype, name="shortcut_conv")(x)
            x = nn.BatchNorm(use_running_average=not train, momentum=0.9,
                             dtype=self.dtype, name="shortcut_bn")(x)
        return nn.relu(out + x)


class ResNet(nn.Module):
    """3-stage CIFAR ResNet; input NHWC (e.g. [B, 32, 32, 3]), output logits."""

    depth: int = 20
    num_classes: int = 10
    dtype: Any = jnp.float32
    remat: bool = False

    #: the narrowest width (the stem and stage 0): workers side by side
    #: fill the lanes when P x this width reaches 128
    pack_width = STAGE_PLANES[0]

    @nn.compact
    def __call__(self, x, train: bool = True):
        x = _cifar_trunk(x, train, self.depth, self.dtype, self.remat)
        return nn.Dense(self.num_classes, dtype=jnp.float32, name="head")(x)

    def packed_apply(self, params, batch_stats, x):
        """The training-mode forward of P workers as one network P times as
        wide: ``params`` and ``batch_stats`` are this model's own trees with
        leaves ``[P, ...]``, ``x`` is ``[P, B, H, W, C]``.  Returns
        ``(logits [P, B, classes], new batch_stats)`` with leaves ``[P, ...]``:
        what ``vmap`` of ``apply(..., train=True, mutable=["batch_stats"])``
        over the P workers returns, to float32 summation order.

        Workers are not isolated from each other here: a zero block times a
        non-finite activation is NaN, so one worker's overflow reaches the
        others of its pack.  Callers that quarantine workers keep ``vmap``.
        """
        workers, batch, height, width, channels = x.shape
        net = _PackedTrunk(depth=self.depth, dtype=self.dtype, workers=workers,
                           parent=None)
        trunk = lambda tree: _pack_variables(
            {k: v for k, v in tree.items() if k != "head"})
        features, mutated = net.apply(
            {"params": trunk(params), "batch_stats": trunk(batch_stats)},
            jnp.moveaxis(x, 0, 3).reshape(batch, height, width,
                                          workers * channels),
            mutable=["batch_stats"])
        # each worker's own head over its own channels, as nn.Dense in float32
        features = features.reshape(batch, workers, -1).astype(jnp.float32)
        head = params["head"]
        logits = jnp.einsum("bpc,pck->pbk", features, head["kernel"])
        return (logits + head["bias"][:, None],
                jax.tree.map(lambda a: a.reshape(workers, -1),
                             mutated["batch_stats"]))


def _cifar_trunk(x, train: bool, depth: int, dtype, remat: bool, workers: int = 1):
    """Stem, three stages and the global average pool of the CIFAR layout,
    as submodules of the module whose ``__call__`` is running; ``workers``
    multiplies every width (the packed form)."""
    kind, blocks = resnet_config(depth)
    block: Callable = BasicBlock if kind == "basic" else Bottleneck
    if remat:
        block = _remat_block(block)
    x = nn.Conv(STAGE_PLANES[0] * workers, (3, 3), padding=1, use_bias=True,
                dtype=dtype, name="stem")(x)
    x = nn.relu(nn.BatchNorm(use_running_average=not train, momentum=0.9,
                             dtype=dtype, name="stem_bn")(x))
    for stage, (planes, stride) in enumerate(zip(STAGE_PLANES, (1, 2, 2))):
        for b in range(blocks[stage]):
            x = block(planes=planes * workers, stride=stride if b == 0 else 1,
                      dtype=dtype, name=f"stage{stage}_block{b}")(x, train)
    return jnp.mean(x, axis=(1, 2))  # global average over the 8x8 map


class _PackedTrunk(nn.Module):
    """The trunks of ``workers`` ResNets as one: ``[B, H, W, workers x C]``
    (worker major in the channels) to the pooled features ``[B, workers x
    C']``, in training mode.  Applied to ``_pack_variables`` of the
    workers' own trees, never initialised."""

    depth: int
    dtype: Any
    workers: int

    @nn.compact
    def __call__(self, x):
        return _cifar_trunk(x, True, self.depth, self.dtype, False, self.workers)


def _pack_variables(tree):
    """The trunk's parameters or statistics, leaves ``[P, ...]``, as
    ``_PackedTrunk``'s: a convolution kernel ``[P, kh, kw, Cin, Cout]``
    becomes the block-diagonal ``[kh, kw, P Cin, P Cout]`` (a ``where``, not
    a product with the identity: exact forward, and the gradient of a
    worker's block is a selection), per-channel vectors are laid end to
    end."""
    def leaf(a):
        if a.ndim == 2:
            return a.reshape(-1)
        workers, kh, kw, cin, cout = a.shape
        own = jnp.eye(workers, dtype=bool)[None, None, :, None, :, None]
        blocks = jnp.moveaxis(a, 0, 2)[:, :, :, :, None, :]  # [kh, kw, P, Cin, 1, Cout]
        return jnp.where(own, blocks, jnp.zeros((), a.dtype)).reshape(
            kh, kw, workers * cin, workers * cout)

    return jax.tree.map(leaf, tree)


def resnet_imagenet_config(depth: int) -> Tuple[str, Sequence[int]]:
    """(block_kind, blocks_per_stage) for the 4-stage ImageNet layout."""
    table = {
        18: ("basic", (2, 2, 2, 2)),
        34: ("basic", (3, 4, 6, 3)),
        50: ("bottleneck", (3, 4, 6, 3)),
        101: ("bottleneck", (3, 4, 23, 3)),
        152: ("bottleneck", (3, 8, 36, 3)),
    }
    if depth not in table:
        raise ValueError(f"unsupported ImageNet ResNet depth {depth}: need {sorted(table)}")
    return table[depth]


class ResNetImageNet(nn.Module):
    """4-stage ImageNet ResNet (7×7/2 stem + 3×3/2 max pool, 64/128/256/512
    planes, global average pool) — the layout the reference reaches through
    ``torchvision.models.resnet18()`` for its imagenet config
    (/root/reference/util.py:262-265).  Input NHWC, e.g. ``[B, 224, 224, 3]``.
    """

    depth: int = 18
    num_classes: int = 1000
    dtype: Any = jnp.float32
    remat: bool = False

    @nn.compact
    def __call__(self, x, train: bool = True):
        kind, blocks = resnet_imagenet_config(self.depth)
        block: Callable = BasicBlock if kind == "basic" else Bottleneck
        if self.remat:
            block = _remat_block(block)
        x = nn.Conv(64, (7, 7), strides=(2, 2), padding=3, use_bias=True,
                    dtype=self.dtype, name="stem")(x)
        x = nn.relu(nn.BatchNorm(use_running_average=not train, momentum=0.9,
                                 dtype=self.dtype, name="stem_bn")(x))
        x = nn.max_pool(x, (3, 3), strides=(2, 2), padding=((1, 1), (1, 1)))
        for stage, planes in enumerate((64, 128, 256, 512)):
            for b in range(blocks[stage]):
                stride = 2 if (stage > 0 and b == 0) else 1
                x = block(planes=planes, stride=stride, dtype=self.dtype,
                          name=f"stage{stage}_block{b}")(x, train)
        x = jnp.mean(x, axis=(1, 2))
        return nn.Dense(self.num_classes, dtype=jnp.float32, name="head")(x)
