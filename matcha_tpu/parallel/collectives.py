"""Centralized collectives over the worker axis.

TPU-native equivalents of the reference's MPI AllReduce paths:
``centralizedCommunicator.averaging`` (communicator.py:56-67) and the one-time
init sync ``sync_allreduce`` (train_mpi.py:34-56).  On a ``[N, ...]`` worker
array the global average is just a mean over the leading axis — XLA lowers it
to ``all-reduce`` over ICI when the axis is sharded.
"""

from __future__ import annotations

import jax
import jax.numpy as jnp

__all__ = ["allreduce_mean", "broadcast_worker0", "masked_mean_rows",
           "masked_allreduce_mean", "worker_disagreement",
           "worker_deviation_rows", "worker_square_rows"]


def allreduce_mean(x: jax.Array) -> jax.Array:
    """Replace every worker's row with the global average (AllReduce/size)."""
    mean = jnp.mean(x, axis=0, keepdims=True)
    return jnp.broadcast_to(mean, x.shape)


def masked_mean_rows(x: jax.Array, alive: jax.Array) -> jax.Array:
    """Mean of the rows where ``alive > 0`` — the survivors' consensus point.

    ``alive: f32[N]``.  Masked rows are excluded with ``where``, not a
    multiply: the whole point of the mask is quarantining non-finite rows,
    and ``0·NaN = NaN`` would leak the poison straight into the mean.  With
    no survivors at all the result is the zero vector (guarded denominator);
    callers that heal from this mean must gate on ``alive.sum() > 0``
    (``resilience.runtime`` does) so an all-dead step cannot silently zero
    the model.
    """
    w = alive.reshape((alive.shape[0],) + (1,) * (x.ndim - 1)).astype(x.dtype)
    kept = jnp.where(w > 0, x, jnp.zeros_like(x))
    # graftlint: disable=GL001 — rows pre-sealed by the where above; the
    # denominator multiply is a scalar survivor count, not a value mask
    return jnp.sum(w * kept, axis=0) / jnp.maximum(jnp.sum(alive), 1.0)


def masked_allreduce_mean(x: jax.Array, alive: jax.Array) -> jax.Array:
    """AllReduce-average over the alive rows only; dead rows keep their own
    values (they are quarantined, not overwritten — healing is a separate,
    explicit act in ``resilience.runtime``)."""
    mean = masked_mean_rows(x, alive)
    w = alive.reshape((alive.shape[0],) + (1,) * (x.ndim - 1)).astype(x.dtype)
    return jnp.where(w > 0, jnp.broadcast_to(mean, x.shape), x)


def broadcast_worker0(x: jax.Array) -> jax.Array:
    """Replace every worker's row with worker 0's (init-consensus alternative)."""
    return jnp.broadcast_to(x[0:1], x.shape)


def worker_disagreement(x: jax.Array, alive: jax.Array | None = None) -> jax.Array:
    """RMS distance of worker rows from consensus: ‖x − x̄‖ / √(N·D).

    The quantity the contraction bound ρ controls; the reference never
    measures it (SURVEY.md §5.5) — we expose it as a first-class metric.

    With ``alive`` the statistic is computed over survivors only (mean and
    RMS both restricted to alive rows): a quarantined worker's stale or
    healed-in-progress row must not be allowed to dominate the consensus
    metric the fault ledger and the plan verifier read.
    """
    if alive is None:
        centered = x - jnp.mean(x, axis=0, keepdims=True)
        return jnp.sqrt(jnp.mean(centered * centered))
    w = alive.reshape((alive.shape[0],) + (1,) * (x.ndim - 1)).astype(x.dtype)
    # where, not multiply: a quarantined row may be non-finite and 0·NaN=NaN
    centered = jnp.where(w > 0, x - masked_mean_rows(x, alive)[None],
                         jnp.zeros_like(x))
    # graftlint: disable=GL001 — scalar survivor count × row width, no values
    denom = jnp.maximum(jnp.sum(alive), 1.0) * (x.size // x.shape[0])
    return jnp.sqrt(jnp.sum(centered * centered) / denom)


def worker_deviation_rows(x: jax.Array,
                          alive: jax.Array | None = None) -> jax.Array:
    """Per-worker RMS distance from consensus: f32[N] — row i's
    ``‖x_i − x̄‖ / √D``.

    The per-worker decomposition of :func:`worker_disagreement` (the fleet
    scalar is the alive-weighted RMS of these rows): what the health
    plane's heartbeat carries so the anomaly detectors can name *which*
    replica is drifting, not just that the fleet is (DESIGN.md §17).  With
    ``alive`` the consensus point is the survivor mean and quarantined
    rows report 0 — their deviation is quarantine, not news; the
    participation counter is the signal that names them."""
    if alive is None:
        centered = x - jnp.mean(x, axis=0, keepdims=True)
    else:
        w = alive.reshape((alive.shape[0],) + (1,) * (x.ndim - 1)).astype(
            x.dtype)
        # where, not multiply: a quarantined row may be non-finite
        centered = jnp.where(w > 0, x - masked_mean_rows(x, alive)[None],
                             jnp.zeros_like(x))
    sq = (centered * centered).reshape(x.shape[0], -1)
    return jnp.sqrt(jnp.mean(sq, axis=1))


def worker_square_rows(leaves) -> jax.Array:
    """``f32[N]`` — worker i's ``sum((x_i - mean_j x_j)^2)`` over a list of
    ``[N, ...]`` leaves: what :func:`worker_deviation_rows` and
    :func:`worker_disagreement` reduce from the flat ``[N, D]`` state, a
    leaf at a time and with no flat copy."""
    total = jnp.zeros((leaves[0].shape[0],), jnp.float32)
    for leaf in leaves:
        x = leaf.reshape(leaf.shape[0], -1).astype(jnp.float32)
        centered = x - jnp.mean(x, axis=0, keepdims=True)
        total = total + jnp.sum(centered * centered, axis=1)
    return total
