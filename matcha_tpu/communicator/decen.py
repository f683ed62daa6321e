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
the weight renormalized onto the survivor (see ``parallel.gossip``).  The
fused Pallas ``multi_step`` is flag-stream-only; ``Communicator.run``
routes masked chains through the per-step scan instead.
"""

from __future__ import annotations

from typing import Callable

import jax
import numpy as np

import jax.numpy as jnp

from ..parallel import (
    dense_exchange_form,
    dense_gossip_fn,
    gossip_mix,
    gossip_mix_skip,
    resolve_wire_dtype,
    shard_map_gossip_fn,
)
from ..schedule import Schedule
from .base import Communicator

__all__ = ["make_decen", "resolve_gossip_backend"]


def resolve_gossip_backend(schedule, mesh=None, requested: str = "auto",
                           dim=None, wire_dtype=None,
                           measured_vs_ceiling=None) -> dict:
    """Resolve a ``gossip_backend`` request to the backend actually built,
    returning the full decision record for journaling.

    Non-``auto`` requests pass through verbatim (the record says so).
    ``auto`` keeps the historical multi-device answer — ``shard_map`` when
    a real mesh exists (physical decentralization: ICI carries only gossip
    edges) — and on a single chip delegates the perm-vs-dense call to
    :func:`matcha_tpu.plan.cost.choose_gossip_backend`, the planner's
    per-backend cost ledger gated on the roofline's measured-vs-ceiling
    ratio.  One resolver on purpose: :func:`make_decen` and the train loop
    both call it, so the journaled decision is definitionally the backend
    that compiled.  Where that backend's per-step mix is the dense exchange
    (``dense``, ``fused``), the record's ``exchange`` names the form it
    compiles to at this worker count (``parallel.gossip.
    dense_exchange_form``: ``streamed`` or ``mxu``, with the N and the
    crossover it was chosen from).
    """
    if requested != "auto":
        record = {"requested": requested, "chosen": requested,
                  "reason": "explicit config; no selection ran"}
    elif not _single_chip(mesh):
        record = {"requested": "auto", "chosen": "shard_map",
                  "reason": f"multi-device mesh ({mesh.size} devices): "
                            f"worker-folded ppermute plan rides ICI"}
    else:
        from ..plan.cost import choose_gossip_backend

        record = choose_gossip_backend(
            schedule.num_workers, schedule.num_matchings, dim=dim,
            wire_dtype=wire_dtype,
            budget=float(np.mean(np.asarray(schedule.probs)))
            if len(schedule.probs) else None,
            topology=getattr(schedule, "name", None),
            measured_vs_ceiling=measured_vs_ceiling)
    if record["chosen"] in ("dense", "fused"):
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
    chunk: int = 1,
    block_d: int | None = None,
    w_window: int = 1,
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
      * ``"fused"``     — dense per-step, plus the Pallas multi-step kernel
                          (VMEM-resident state, streamed W_t stack) for whole
                          flag streams — the bench configuration.
      * ``"perm"``      — the permutation-form Pallas kernel for *every*
                          phase: each step is per-row partner copies +
                          weighted adds on a VMEM-resident state block,
                          reading only the ``[T, M]`` flag array (SMEM;
                          ~2000× less than the fused W stack at N=256).
                          Its resident blocks fit the scoped VMEM up to
                          ~5,400 workers.  Alive masks compose in-kernel
                          (per-edge ``alive_i·alive_{π_j(i)}`` gates), so
                          masked chains keep the fused launch
                          (``multi_step_masked``); bf16 wire rides the
                          ``resolve_wire_dtype`` seam with f32
                          accumulation.  Both Pallas backends compile for
                          the device on every platform but ``cpu``, where
                          they run under the Pallas interpreter (the
                          tier-1 mesh) — an accelerator never interprets.
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
      * ``"auto"``      — shard_map on a multi-device mesh; single-chip the
                          perm-vs-dense choice runs through
                          ``plan.cost.choose_gossip_backend`` (forced perm
                          beyond the representability wall, gated on the
                          roofline's measured-vs-ceiling ratio otherwise —
                          dense when no measurement exists).  The train
                          loop journals the decision record (``backend``
                          event) so drift can score it.

    ``chunk`` (fused backend only): collapse runs of ``chunk`` consecutive
    mixing matrices into their product before the Pallas kernel — exactly the
    same ``x_T`` by associativity at ~``chunk``× fewer apply-FLOPs (see
    ``compose_mixing_stack``).  Intermediate per-step iterates are then not
    materialized, so keep the default 1 for training loops that interleave
    gossip with SGD; raise it for consensus-only chains and the bench.

    ``block_d`` (fused/perm backends): the Pallas kernel's resident D-block
    size; None keeps the kernel's default.  For fused, per-step W-stream
    traffic is ``ceil(D/block_d)·N²``, so bigger blocks cut HBM traffic
    linearly until the [N, block_d] in+out blocks stop fitting the 16 MiB
    scoped VMEM — a request that cannot fit raises
    :class:`~matcha_tpu.parallel.GossipKernelResourceError` here, at build
    time (f32 state at N=256: 2048 fits, 4096 does not).

    ``w_window`` (fused backend only): consecutive ``W_t`` per D-block grid
    visit.  Unlike ``chunk`` this keeps the exact per-step arithmetic (every
    step's matmul executes in order) — it only amortizes grid overhead and
    enlarges W DMAs, so it is valid for the training-regime measurement.

    ``wire_dtype`` (``"f32"``/``"bf16"``/None): dtype of the *exchanged*
    tensors at the gossip boundary — bf16 halves the bytes every backend
    moves per step (ppermute blocks on ICI for shard_map, the HBM state
    stream for gather/skip, the MXU operand pass for dense/fused) while
    master parameters and the delta accumulation stay f32.  For the MXU
    backends this rides the existing ``compute_dtype``/``mxu_precision``
    seam: bf16 wire ⇒ one native bf16 MXU pass with f32 accumulation
    (``preferred_element_type``); f32 wire keeps the exact HIGHEST-precision
    program.  The streamed small-N form of the dense exchange reads the
    same values (``W_t`` and the state rounded to the wire dtype, float32
    products and sums) but moves no fewer bytes: it rounds the float32 state
    as it reads it, on one chip, where nothing crosses a wire.  An explicit
    ``compute_dtype`` below f32 wins over the wire knob (the bench passes
    bf16 state directly).
    """
    perms = np.asarray(schedule.perms)
    alpha = float(schedule.alpha)
    wire = resolve_wire_dtype(wire_dtype)
    state_itemsize = jnp.dtype(compute_dtype).itemsize
    if wire is not None and jnp.dtype(compute_dtype).itemsize >= 4:
        # the dense/fused matmul *is* the exchange: its operand pass in the
        # wire dtype (f32 accumulate) is exactly the bf16-wire semantics
        compute_dtype = wire

    if backend == "auto":
        backend = resolve_gossip_backend(schedule, mesh,
                                         wire_dtype=wire_dtype)["chosen"]

    if (backend not in ("fused", "perm") and block_d is not None) \
            or (backend != "fused" and w_window != 1):
        import warnings

        warnings.warn(
            f"block_d tunes the fused/perm backends' Pallas kernels and "
            f"w_window the fused one; backend '{backend}' ignores them. "
            f"Note the fused kernel runs multi-step *chains* "
            f"(Communicator.run / the comm-split timer) — the per-step "
            f"training mix is the dense exchange either way.",
            stacklevel=2,
        )


    multi_step = None
    multi_step_masked = None
    if backend == "gather":
        if perms.shape[1] >= 64:
            import warnings

            warnings.warn(
                f"gossip_backend='gather' walks the full state once per "
                f"matching and measures ~60x slower than 'dense'/'fused' at "
                f"N={perms.shape[1]} (README Performance table: 18 vs 4764+ "
                f"steps/s at N=256). Use backend='dense' (single chip) or "
                f"'fused'; 'gather' remains for small-N debugging and "
                f"oracle tests.",
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
    elif backend == "fused":
        from ..parallel import (
            build_mixing_stack,
            compose_mixing_stack,
            fused_gossip_run,
        )
        from ..parallel.pallas_gossip import check_fused_fits, pallas_interpret

        mix = dense_gossip_fn(schedule.laplacians(), compute_dtype=compute_dtype,
                              single_chip=_single_chip(mesh))
        laplacians = schedule.laplacians()
        interpret = pallas_interpret()

        kernel_kwargs = {} if block_d is None else {"block_d": block_d}
        if w_window > 1:
            kernel_kwargs["w_window"] = w_window
        # fail here, by name, rather than in Mosaic's allocator at the
        # first chain: the state rides in the caller's compute_dtype when
        # that is narrower than f32 (the bench), else f32 (training)
        check_fused_fits(perms.shape[1], state_itemsize=state_itemsize,
                         stack_itemsize=jnp.dtype(compute_dtype).itemsize,
                         **kernel_kwargs)

        def multi_step(flat, carry, flags):
            stack = build_mixing_stack(
                laplacians, alpha, flags, dtype=compute_dtype
            )
            if chunk > 1:
                stack = compose_mixing_stack(stack, chunk)
            return fused_gossip_run(flat, stack, interpret=interpret,
                                    **kernel_kwargs), carry

    elif backend == "perm":
        from ..parallel import involution_tables, perm_gossip_run
        from ..parallel.pallas_gossip import pallas_interpret

        perms_i32, partnered = involution_tables(perms)
        kernel_kwargs = {"wire_dtype": wire_dtype, "block_d": block_d,
                         "interpret": pallas_interpret()}

        # ONE kernel for every phase: the per-step training mix is the same
        # program at T=1 (`mix` receives the already-α-scaled weight row —
        # a [1, M] stream), and the chain forms scale the raw flags by α
        # exactly like gossip_mix's caller does, so step/multi_step/
        # masked-multi_step are the same arithmetic at every entry point.
        def mix(x, w, alive=None):
            return perm_gossip_run(x, w[None, :], perms_i32, partnered,
                                   alive=alive, **kernel_kwargs)

        def multi_step(flat, carry, flags):
            return perm_gossip_run(flat, alpha * flags, perms_i32,
                                   partnered, **kernel_kwargs), carry

        def multi_step_masked(flat, carry, flags, alive):
            return perm_gossip_run(flat, alpha * flags, perms_i32,
                                   partnered, alive=alive,
                                   **kernel_kwargs), carry

    elif backend == "shard_map":
        if mesh is None:
            raise ValueError("shard_map backend needs a mesh")
        mix = shard_map_gossip_fn(perms, mesh, wire_dtype=wire)
    else:
        raise KeyError(f"unknown gossip backend '{backend}'")

    def init(flat: jax.Array):
        return ()

    def step(flat: jax.Array, carry, flags_t: jax.Array, alive=None):
        if alive is None:
            return mix(flat, alpha * flags_t), carry
        return mix(flat, alpha * flags_t, alive), carry

    wire_tag = "" if wire is None else f",wire={jnp.dtype(wire).name}"
    return Communicator(
        name=f"decen[{backend}{wire_tag}]", init=init, step=step,
        multi_step=multi_step, multi_step_masked=multi_step_masked,
    )
