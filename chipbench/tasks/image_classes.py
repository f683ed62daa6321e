"""Image classification: a CIFAR-shaped data set drawn from the seed, one
class label a row.

``make`` writes uint8 pixels ``x_*[n, H, W, C]`` and int32 labels ``y_*[n]``
in the ``.npz`` layout the program's ``load_npz`` reads
(``sizes["input_shape"]``, ``sizes["num_classes"]``).  Each class has a
coarse colour pattern of its own under per-pixel noise, so the loss can fall
and no two rows are alike.  ``prepare`` normalises the pixels by the
configuration's ``input_mean`` and ``input_std``; ``loss`` is the mean
softmax cross-entropy of ``outputs[B, classes]``.  A sample is one image.
"""

import jax
import jax.numpy as jnp
import numpy as np


def make(seed: int, n_train: int, n_test: int, config) -> dict:
    classes = config["sizes"]["num_classes"]
    h, w, c = config["sizes"]["input_shape"]
    rng = np.random.default_rng([int(seed), 0xC1FA])
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


def prepare(x_raw, y_raw, config):
    mean = np.asarray(config["input_mean"], np.float32)
    std = np.asarray(config["input_std"], np.float32)
    return (x_raw.astype(jnp.float32) / 255.0 - mean) / std, y_raw


def loss(outputs, targets):
    logp = outputs - jax.scipy.special.logsumexp(
        outputs, axis=-1, keepdims=True)
    nll = -jnp.take_along_axis(logp, targets[:, None], axis=-1)[:, 0]
    return jnp.mean(nll)
