#!/usr/bin/env python
"""Regenerate the committed miniature real-format dataset fixtures.

These are byte-faithful miniatures of the exact on-disk formats the
reference's torchvision loaders consume (/root/reference/util.py:117-149,
223-251) — the canonical ``cifar-10-batches-py`` pickle layout (as unpacked
from ``cifar-10-python.tar.gz``) and the EMNIST/MNIST ``idx[13]-ubyte.gz``
pairs — shrunk to 20 examples per file so they can live in the repo (no
network egress here; a user with the real archives runs the identical
``python -m matcha_tpu.data.build_npz`` command on them).

Deterministic: fixed seed, so regenerating never dirties the tree.
"""

import gzip
import os
import pickle
import struct

import numpy as np

HERE = os.path.dirname(os.path.abspath(__file__))
ROWS = 20  # per batch file


def make_cifar10(root: str) -> None:
    src = os.path.join(root, "cifar-10-batches-py")
    os.makedirs(src, exist_ok=True)
    rng = np.random.default_rng(42)

    def batch(path):
        with open(path, "wb") as f:
            pickle.dump({
                b"data": rng.integers(0, 256, size=(ROWS, 3072), dtype=np.uint8),
                b"labels": rng.integers(0, 10, size=ROWS).tolist(),
            }, f)

    for i in range(1, 6):
        batch(os.path.join(src, f"data_batch_{i}"))
    batch(os.path.join(src, "test_batch"))


def make_emnist(root: str) -> None:
    rng = np.random.default_rng(43)

    def write_idx(path, arr):
        magic = struct.pack(">I", (0x08 << 8) | arr.ndim)
        dims = b"".join(struct.pack(">I", s) for s in arr.shape)
        with open(path, "wb") as raw:
            # mtime=0: reproducible bytes across regenerations
            with gzip.GzipFile(fileobj=raw, mode="wb", mtime=0) as f:
                f.write(magic + dims + arr.tobytes())

    write_idx(os.path.join(root, "emnist-balanced-train-images-idx3-ubyte.gz"),
              rng.integers(0, 256, size=(ROWS, 28, 28), dtype=np.uint8))
    write_idx(os.path.join(root, "emnist-balanced-train-labels-idx1-ubyte.gz"),
              rng.integers(0, 47, size=ROWS, dtype=np.uint8))
    write_idx(os.path.join(root, "emnist-balanced-test-images-idx3-ubyte.gz"),
              rng.integers(0, 256, size=(ROWS, 28, 28), dtype=np.uint8))
    write_idx(os.path.join(root, "emnist-balanced-test-labels-idx1-ubyte.gz"),
              rng.integers(0, 47, size=ROWS, dtype=np.uint8))


if __name__ == "__main__":
    make_cifar10(HERE)
    make_emnist(HERE)
    print(f"fixtures written under {HERE}")
