"""The matrix products an architecture is handed, and the layers the two
convolutional ones are made of, in train mode."""

import collections

import jax.numpy as jnp
from jax import lax

BN_EPS = 1e-5

# What ``forward`` gets as ``ops``.  ``precision`` is for the products an
# architecture writes itself: ``jnp.einsum(..., precision=ops.precision)``.
Ops = collections.namedtuple("Ops", "conv dot precision")


def make_ops(precision) -> Ops:
    """``conv`` and ``dot`` (each adds the layer's bias) of float32 operands
    at one MXU precision, and that precision."""

    def conv(x, p, name, stride, pad):
        y = lax.conv_general_dilated(
            x, p[name + "/kernel"], (stride, stride), ((pad, pad), (pad, pad)),
            dimension_numbers=("NHWC", "HWIO", "NHWC"), precision=precision)
        return y + p[name + "/bias"]

    def dot(x, p, name):
        return jnp.dot(x, p[name + "/kernel"], precision=precision) \
            + p[name + "/bias"]

    return Ops(conv, dot, precision)


def batch_norm(x, p, stats, name, momentum):
    """Train-mode batch norm over one worker's batch: normalize by the
    batch's own mean and biased variance; the running statistics move by
    ``1 - momentum`` toward them.  Returns (y, new running mean, var)."""
    mean = jnp.mean(x, axis=(0, 1, 2))
    var = jnp.mean(jnp.square(x - mean), axis=(0, 1, 2))
    y = (x - mean) / jnp.sqrt(var + BN_EPS) * p[name + "/scale"] \
        + p[name + "/bias"]
    new = {name + "/mean": momentum * stats[name + "/mean"]
           + (1 - momentum) * mean,
           name + "/var": momentum * stats[name + "/var"]
           + (1 - momentum) * var}
    return y, new


def relu(x):
    return jnp.maximum(x, 0)
