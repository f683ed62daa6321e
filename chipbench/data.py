"""The traffic generator: a CIFAR-shaped data set drawn from the seed.

uint8 pixels and integer labels in the ``.npz`` layout the program's
``load_npz`` reads.  Each class has a coarse 8x8 colour pattern of its own
under per-pixel noise, so the loss can fall and no two rows are alike.
"""

import numpy as np


def make_dataset(seed: int, n_train: int, n_test: int, classes: int,
                 shape=(32, 32, 3)) -> dict:
    rng = np.random.default_rng([int(seed), 0xC1FA])
    h, w, c = shape
    coarse = rng.integers(0, 256, (classes, h // 4, w // 4, c))
    templates = np.repeat(np.repeat(coarse, 4, axis=1), 4, axis=2)

    def split(n):
        # every class appears, so the program's class count (the largest
        # label + 1) is the configuration's whatever the seed
        y = np.concatenate([np.arange(classes), rng.integers(
            0, classes, max(n - classes, 0))])[:n].astype(np.int32)
        rng.shuffle(y)
        noise = rng.integers(-80, 81, (n, h, w, c), dtype=np.int16)
        x = templates[y].astype(np.int16) // 2 + 64 + noise
        return np.clip(x, 0, 255).astype(np.uint8), y

    x_train, y_train = split(n_train)
    x_test, y_test = split(n_test)
    return {"x_train": x_train, "y_train": y_train,
            "x_test": x_test, "y_test": y_test}
