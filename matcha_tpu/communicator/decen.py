"""Decentralized gossip communicator (D-PSGD / MATCHA hot path).

TPU-native re-design of ``decenCommunicator``
(/root/reference/communicator.py:79-158): the per-matching blocking
``sendrecv`` + axpy loop becomes one fused mixing expression

    x ← x + α·Σ_j flag_j·(x[π_j] − x)

with static permutations (gather backend for any N; explicit
shard_map+ppermute backend riding ICI when a mesh is given).  An all-zero
flag row yields zero weights ⇒ identity, reproducing the reference's
skip-iteration early return (communicator.py:140-141) without a branch.

Every backend accepts the resilience layer's optional survivor mask
(``step(..., alive)``): dead workers' exchanges collapse to self-loops with
the weight renormalized onto the survivor (see ``parallel.gossip``).
"""

from __future__ import annotations

from typing import Callable

import jax
import jax.numpy as jnp

from ..parallel import (
    dense_exchange_form,
    dense_gossip_fn,
    dense_gossip_leaves_fn,
    gossip_mix,
    gossip_mix_skip,
    involution_tables,
    resolve_wire_dtype,
    shard_map_gossip_fn,
)
from ..schedule import Schedule
from .base import Communicator

__all__ = ["GOSSIP_BACKENDS", "make_decen", "resolve_gossip_backend"]

#: every name ``TrainConfig.gossip_backend`` / :func:`make_decen` takes
GOSSIP_BACKENDS = ("auto", "dense", "gather", "skip", "shard_map")


def resolve_gossip_backend(schedule, mesh=None,
                           requested: str = "auto") -> dict:
    """Resolve a ``gossip_backend`` request to the backend actually built,
    returning the decision record for journaling.

    An explicit request passes through verbatim (the record says so).
    ``auto`` is ``shard_map`` when a real mesh exists (physical
    decentralization: ICI carries only gossip edges) and ``dense`` on one
    chip.  One resolver on purpose: :func:`make_decen` and the train loop
    both call it, so the journaled decision is definitionally the backend
    that compiled.  Where that backend is ``dense``, the record's
    ``exchange`` names the form it compiles to at this worker count
    (``parallel.gossip.dense_exchange_form``: ``streamed`` or ``mxu``, with
    the N and the crossover it was chosen from) — the one choice the
    one-chip exchange has, made from the static N inside
    ``gossip_mix_dense``.
    """
    if requested != "auto":
        record = {"requested": requested, "chosen": requested,
                  "reason": "explicit config; no selection ran"}
    elif not _single_chip(mesh):
        record = {"requested": "auto", "chosen": "shard_map",
                  "reason": f"multi-device mesh ({mesh.size} devices): "
                            f"worker-folded ppermute plan rides ICI"}
    else:
        record = {"requested": "auto", "chosen": "dense",
                  "reason": "one chip: the dense exchange, in the form its "
                            "worker count asks for"}
    if record["chosen"] == "dense":
        record["exchange"] = dense_exchange_form(
            schedule.num_workers, _single_chip(mesh))
    return record


def _single_chip(mesh) -> bool:
    return mesh is None or mesh.size == 1


def make_decen(
    schedule: Schedule,
    mesh=None,
    backend: str = "auto",
    compute_dtype=jnp.float32,
    wire_dtype=None,
) -> Communicator:
    """Build the gossip communicator for a schedule.

    ``backend``:
      * ``"dense"``     — ``x ← W_t x`` once a step, in the form the worker
                          count asks for (``parallel.gossip.
                          gossip_mix_dense``): on one chip up to
                          ``STREAM_MAX_WORKERS`` rows one streamed
                          vector-unit pass over the state, in place; above
                          that, or under a mesh, one MXU matmul.
      * ``"gather"``    — per-matching static gathers (any N under jit).
      * ``"skip"``      — per-matching ``lax.cond``: inactive matchings are
                          not executed, so the MATCHA budget buys back real
                          time where a matching's exchange is expensive.
                          With a mesh this is the folded shard_map plan with
                          the *collectives* inside the conds (the DCN story);
                          single-array otherwise, where the saving is
                          bounded by the cond identity-copy
                          (benchmarks/skip_microbench.py measures it).
                          Masked backends spend the same time at every
                          budget.
      * ``"shard_map"`` — explicit ppermute plan over ``mesh`` (worker-sharded,
                          the physical-decentralization path where ICI carries
                          only gossip edges).
      * ``"auto"``      — shard_map on a multi-device mesh, dense on one
                          chip (:func:`resolve_gossip_backend`).  The train
                          loop journals the decision record (``backend``
                          event).

    ``wire_dtype`` (``"f32"``/``"bf16"``/None): dtype of the *exchanged*
    tensors at the gossip boundary — bf16 halves the bytes every backend
    moves per step (ppermute blocks on ICI for shard_map, the HBM state
    stream for gather/skip, the MXU operand pass for dense) while
    master parameters and the delta accumulation stay f32.  For the MXU
    backends this rides the existing ``compute_dtype``/``mxu_precision``
    seam: bf16 wire ⇒ one native bf16 MXU pass with f32 accumulation
    (``preferred_element_type``); f32 wire keeps the exact HIGHEST-precision
    program.  The streamed small-N form of the dense exchange reads the
    same values (``W_t`` and the state rounded to the wire dtype, float32
    products and sums) but moves no fewer bytes: it rounds the float32 state
    as it reads it, on one chip, where nothing crosses a wire.  An explicit
    ``compute_dtype`` below f32 wins over the wire knob.
    """
    # the one validator of schedule-built tables (GL101's runtime half):
    # every row gather below reads what it returns
    perms, _ = involution_tables(schedule.perms)
    alpha = float(schedule.alpha)
    wire = resolve_wire_dtype(wire_dtype)
    if wire is not None and jnp.dtype(compute_dtype).itemsize >= 4:
        # the dense matmul *is* the exchange: its operand pass in the
        # wire dtype (f32 accumulate) is exactly the bf16-wire semantics
        compute_dtype = wire

    if backend == "auto":
        backend = resolve_gossip_backend(schedule, mesh)["chosen"]

    if backend == "gather":
        if perms.shape[1] >= 64:
            import warnings

            warnings.warn(
                f"gossip_backend='gather' walks the full state once per "
                f"matching and measures ~60x slower than 'dense' at "
                f"N={perms.shape[1]}. Use backend='dense' (single chip); "
                f"'gather' remains for small-N debugging and oracle tests.",
                stacklevel=2,
            )
        mix: Callable = lambda x, w, alive=None: gossip_mix(
            x, perms, w, alive, wire_dtype=wire)
    elif backend == "skip":
        if mesh is not None and mesh.size > 1:
            mix = shard_map_gossip_fn(perms, mesh, skip=True, wire_dtype=wire)
        else:
            mix = lambda x, w, alive=None: gossip_mix_skip(
                x, perms, w, alive, wire_dtype=wire)
    elif backend == "dense":
        mix = dense_gossip_fn(schedule.laplacians(), compute_dtype=compute_dtype,
                              single_chip=_single_chip(mesh))
    elif backend == "shard_map":
        if mesh is None:
            raise ValueError("shard_map backend needs a mesh")
        mix = shard_map_gossip_fn(perms, mesh, wire_dtype=wire)
    else:
        raise KeyError(f"unknown gossip backend '{backend}'; "
                       f"have {list(GOSSIP_BACKENDS)}")

    def init(flat: jax.Array):
        return ()

    def step(flat: jax.Array, carry, flags_t: jax.Array, alive=None):
        if alive is None:
            return mix(flat, alpha * flags_t), carry
        return mix(flat, alpha * flags_t, alive), carry

    # the tree form beside ``step``: the dense exchange where it is the
    # streamed pass (one chip, N <= STREAM_MAX_WORKERS) runs on the leaves
    leaves_step = None
    leaves_refusal = _leaves_refusal(backend, perms.shape[1], mesh)
    if leaves_refusal is None:
        mix_leaves = dense_gossip_leaves_fn(schedule.laplacians(),
                                            compute_dtype=compute_dtype)

        def leaves_step(leaves, carry, flags_t: jax.Array):
            mixed, sq = mix_leaves(leaves, alpha * flags_t)
            return mixed, carry, sq

    wire_tag = "" if wire is None else f",wire={jnp.dtype(wire).name}"
    return Communicator(
        name=f"decen[{backend}{wire_tag}]", init=init, step=step,
        leaves_step=leaves_step, leaves_refusal=leaves_refusal,
    )


def _leaves_refusal(backend: str, n: int, mesh) -> str | None:
    """Why a decen communicator's per-step exchange has no leaf form, or
    ``None`` where it has: the leaf form is the streamed pass, so it exists
    exactly where ``dense_exchange_form`` says ``streamed``."""
    if backend != "dense":
        return f"gossip backend '{backend}' is not the dense exchange"
    form = dense_exchange_form(n, _single_chip(mesh))
    if form["form"] == "streamed":
        return None
    if not form["single_chip"]:
        return f"a mesh of {mesh.size} devices shards the state"
    return (f"N = {n} > {form['crossover']}: the exchange is the MXU "
            f"product over the flat state")
