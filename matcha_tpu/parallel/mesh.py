"""Device mesh helpers for the virtual-worker axis.

The framework's parallelism model (SURVEY.md §2.6): decentralized data
parallelism as **one mesh axis of N virtual workers**.  N may exceed the
physical chip count C; workers are then *folded* — each chip carries
``L = N // C`` consecutive worker rows, and gossip edges are split into
intra-chip gathers and inter-chip collective permutes (see
``gossip.build_folded_plan``).
"""

from __future__ import annotations

from typing import Sequence

import jax
import numpy as np
from jax.sharding import Mesh, NamedSharding, PartitionSpec as P

WORKER_AXIS = "workers"

__all__ = ["WORKER_AXIS", "WorkerFoldError", "worker_mesh", "shard_workers",
           "replicated", "fold_dims"]


class WorkerFoldError(ValueError):
    """The workers cannot be laid out on the devices asked for: more
    devices requested than exist, or a worker count the mesh does not
    divide.  Never resolved by quietly using fewer chips."""


def worker_mesh(
    num_devices: int | None = None,
    axis: str = WORKER_AXIS,
    devices: Sequence[jax.Device] | None = None,
) -> Mesh:
    """1-D mesh over (a prefix of) the available devices."""
    devs = list(devices if devices is not None else jax.devices())
    if num_devices is not None:
        if num_devices > len(devs):
            raise WorkerFoldError(
                f"asked for {num_devices} devices, have {len(devs)}")
        devs = devs[:num_devices]
    return Mesh(np.array(devs), (axis,))


def fold_dims(num_workers: int, mesh: Mesh, axis: str = WORKER_AXIS) -> tuple[int, int]:
    """``(C, L)``: chips and workers-per-chip for folding N workers onto the mesh."""
    C = mesh.shape[axis]
    if num_workers % C:
        raise WorkerFoldError(
            f"num_workers={num_workers} must be divisible by mesh axis size "
            f"{C}: pass devices=<a divisor of {num_workers}> (1 keeps the "
            f"whole fleet on one chip) or change the worker count"
        )
    return C, num_workers // C


def _is_prng_key_leaf(a, axis_size: int | None = None) -> bool:
    """A PRNG key by what the leaf *is*, not what it's named: a typed key
    array (extended dtype) or the raw ``uint32[2]`` form PRNGKey returns.

    The raw form is a heuristic: when the mesh axis size is exactly 2, a
    genuine per-worker ``uint32[2]`` leaf is indistinguishable from a raw key
    and would be replicated rather than sharded — warn so the ambiguity is
    loud, and resolve it by converting keys with ``jax.random.key`` (typed
    keys are recognized exactly) or widening the worker leaf's dtype
    (ADVICE r2)."""
    try:
        if jax.dtypes.issubdtype(a.dtype, jax.dtypes.prng_key):
            return True
    except (AttributeError, TypeError):
        pass
    raw_key = (getattr(a, "ndim", None) == 1 and a.shape == (2,)
               and a.dtype == np.uint32)
    if raw_key and axis_size == 2:
        import warnings

        warnings.warn(
            "shard_workers: uint32[2] leaf on a 2-wide worker axis is "
            "ambiguous (raw PRNG key vs per-worker rows); replicating as a "
            "key. Use jax.random.key() typed keys for exact recognition.",
            stacklevel=3,
        )
    return raw_key


def shard_workers(x, mesh: Mesh, axis: str = WORKER_AXIS):
    """Place ``[N, ...]`` arrays with the leading axis sharded over the mesh.

    Two kinds of leaves are *per-program* state, not per-worker rows, and
    replicate instead: scalars (step counters) and PRNG keys (the key a
    stochastic compressor carries — its leading dim is key-shape, not
    workers, and the communicators' shard_map specs declare it replicated;
    recognized by dtype/shape, so a model submodule merely *named* ``key``
    still shards normally).  Everything else must fold: a leading dim not
    divisible by the axis size is a loud error, never a silent
    re-placement."""
    def put(a):
        if getattr(a, "ndim", 0) == 0 or _is_prng_key_leaf(a, mesh.shape[axis]):
            return jax.device_put(a, NamedSharding(mesh, P()))
        # canonical spec: NO trailing Nones.  P(axis, None, None) and
        # P(axis) describe the same placement but compare unequal in the
        # jit cache key, so a state placed with the padded spec missed the
        # cache against the compiled epoch's own outputs (short spec) and
        # silently recompiled the entire epoch program at epoch 1 on every
        # mesh run — one full wasted XLA compile, invisible until the obs
        # retrace watch journaled it (tests/test_obs.py pins cache_size).
        return jax.device_put(a, NamedSharding(mesh, P(axis)))

    return jax.tree_util.tree_map(put, x)


def replicated(x, mesh: Mesh):
    """Replicate small arrays (flags, step counters) across the mesh."""
    def put(a):
        return jax.device_put(a, NamedSharding(mesh, P()))

    return jax.tree_util.tree_map(put, x)
