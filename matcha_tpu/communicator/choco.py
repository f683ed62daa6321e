"""CHOCO-SGD communicator: gossip on top-k-compressed model differences.

TPU-native re-design of ``ChocoCommunicator``
(/root/reference/communicator.py:161-268).  Reference semantics, batched over
the worker axis with on-device compression (no host round-trips):

    q_i           = compress(x_i − x̂_i)            (top-k keeps 1−ratio)
    s_i          += Σ_{j active, partnered} α·scatter(q_{π_j(i)})
    s_i          += (1 − d_i·α)·scatter(q_i)
    x̂_i          += scatter(q_i)
    x_i          += γ·(s_i − x̂_i)                   (γ = consensus_lr)

Persistent carry = {x̂, s} — zero-initialized like the reference's lazy init
(communicator.py:179-182), never decayed (quirk Q4, kept deliberately).
Both backends accept the resilience layer's survivor mask
(``step(..., alive)``): the partner tables are thinned per step by
``alive_i·alive_{π_j(i)}``, so a quarantined worker neither ships nor
receives compressed messages; its local {x̂, s} cycle keeps running
(unobservable while quarantined).  When the train step heals a worker it
zeroes that worker's carry rows (``resilience.runtime.mask_worker_rows``,
applied in ``train/state.py: make_train_step``) so the compression stream
restarts from the healed parameters.
Skipped iterations (all flags 0) leave *all* state untouched, matching the
reference's early return (communicator.py:249-250) — implemented by scaling
every update by an ``any_active`` mask so the compiled program stays static.

Backends
--------
``batched``
    The ``[N, D]`` single-array form: neighbor messages are static row
    gathers (``vals[π_j]``).  Any N under jit; the single-chip path.

``shard_map``
    Worker-sharded form for N virtual workers folded onto C chips.  Only the
    *compressed* ``(vals, idx)`` blocks — ``[L, k]`` per chip, k ≪ D — ride
    the ICI ``ppermute``s of the folded plan (one pair per matching × chip
    offset), mirroring how the reference ships only the sparse
    ``{values, indices}`` dict over the wire (communicator.py:214) rather
    than the dense model.  The scatter-adds into the chip-local ``s``/``x̂``
    blocks stay on-chip.  ``multi_step`` runs the whole flag stream as one
    ``lax.scan`` *inside* a single shard_map call, so per-step dispatch and
    re-entry costs are paid once per chain.
"""

from __future__ import annotations

import jax
import jax.numpy as jnp
import numpy as np
from jax import lax

from ..ops import DETERMINISTIC_COMPRESSORS, scatter_rows, select_compressor
from ..schedule import Schedule
from .base import Communicator

__all__ = ["make_choco"]


def _choco_core(vals, idx, x_hat, s, flat, flags_t, *, gather_msg, partnered_rows,
                matching_nonempty, alpha, consensus_lr, aligned_full=False):
    """Shared per-step CHOCO math given this block's top-k messages.

    ``gather_msg(j) -> (vals[π_j], idx[π_j])`` abstracts the neighbor
    exchange (row gather in the batched form; ppermute in the folded form).
    ``partnered_rows``: ``f32[M, R]`` partner mask for the R rows held here
    (may be traced); ``matching_nonempty``: static per-matching bools letting
    globally-empty matchings drop out of the compiled program.

    Keep-all fast path (``aligned_full``, set only for the exact ``top_k``
    compressor whose keep-all branch emits arange indices): when the
    message width equals the state width (a ratio-0 compression-warmup
    stage), every index row is arange and any gather of those rows is
    arange too — the scatters degenerate to dense weighted adds, which XLA
    fuses instead of lowering O(N·D) scatters.  Other compressors (e.g.
    random_k at k=D emits a *permutation*) keep the general scatter.
    """
    keep_all = aligned_full and vals.shape[-1] == s.shape[-1]

    def add(base, g_idx, g_vals, scale):
        if not keep_all:
            return scatter_rows(base, g_idx, g_vals, scale)
        sc = jnp.asarray(scale, base.dtype)
        if sc.ndim == 1:
            sc = sc[:, None]
        return base + sc * g_vals

    active = (jnp.sum(flags_t) > 0).astype(flat.dtype)  # 0 ⇒ frozen step
    partnered_rows = jnp.asarray(partnered_rows)
    for j in range(len(matching_nonempty)):
        if not matching_nonempty[j]:
            continue  # no edges anywhere: zero contribution, skip statically
        g_vals, g_idx = gather_msg(j)
        # graftlint: disable=GL001 — weights, not values: α·flag·partner is
        # the finite per-row scatter weight, never a value mask
        scale = active * flags_t[j] * alpha * partnered_rows[j]
        s = add(s, g_idx, g_vals, scale)

    # self message with per-row weight 1 − d_i·α (d = active degree)
    deg = partnered_rows.T @ flags_t  # [R]
    s = add(s, idx, vals, active * (1.0 - deg * alpha))
    x_hat = add(x_hat, idx, vals, active)
    flat = flat + active * consensus_lr * (s - x_hat)
    return flat, x_hat, s


def make_choco(
    schedule: Schedule,
    ratio: float = 0.9,
    consensus_lr: float = 0.1,
    mesh=None,
    backend: str = "auto",
    compressor: str = "top_k",
    seed: int = 0,
    wire_dtype=None,
) -> Communicator:
    """Build the CHOCO communicator.

    ``ratio`` follows reference semantics: keep the top ``1−ratio`` fraction
    (0.9 ⇒ ~10%; hard-coded at the reference call site train_mpi.py:79 —
    here a real parameter).  ``consensus_lr`` is γ (default matches
    train_mpi.py:228).  ``backend``: ``batched`` | ``shard_map`` | ``auto``
    (shard_map when a multi-device ``mesh`` is given).

    ``compressor`` selects from the ops registry (``COMPRESSOR_NAMES``:
    ``top_k`` | ``random_k`` | ``top_k_q8`` | ``top_k_approx``) — the
    extension point the reference reserves next to top-k
    (communicator.py:186-187).  The stochastic compressors thread a PRNG key
    through the carry (seeded by ``seed``), so runs stay reproducible and the
    whole chain remains one compiled program.  Note the batched and shard_map
    backends draw *different* key streams (per-array vs per-chip fold-in):
    bit-parity across backends holds only for the ``DETERMINISTIC_COMPRESSORS``
    (``top_k``, ``top_k_approx``), which carry no key at all.

    ``wire_dtype`` (``"f32"``/``"bf16"``/None): the compressed *values* are
    quantized to the wire dtype once, right after ``compress`` — every
    consumer (the neighbor exchange, the self message, and the ``x̂``
    update) reads the same quantized values, so this is exactly CHOCO with
    a ``quantize ∘ top-k`` compressor (still a δ-contraction) rather than a
    drifting wire approximation: what a worker applies to ``x̂`` is what its
    neighbors received.  In the shard_map backend the ICI ``ppermute``
    moves the values at the wire dtype (lossless re-cast: they are already
    wire-representable), halving the compressed message bytes; indices stay
    int32 either way.
    """
    from ..parallel import resolve_wire_dtype

    perms = np.asarray(schedule.perms)
    alpha = float(schedule.alpha)
    M, N = perms.shape
    wire = resolve_wire_dtype(wire_dtype)
    # partner masks: fixed points exchange nothing (communicator.py:210)
    partnered = (perms != np.arange(N)[None, :]).astype(np.float32)  # [M, N]
    nonempty = [bool(partnered[j].any()) for j in range(M)]
    base_compress = select_compressor(compressor)
    if wire is None:
        compress = base_compress
    else:
        def compress(q, ratio_, key):
            vals, idx = base_compress(q, ratio_, key)
            return vals.astype(wire).astype(q.dtype), idx
    stochastic = compressor not in DETERMINISTIC_COMPRESSORS
    cname = f"choco[r{ratio}" + ("" if compressor == "top_k" else f",{compressor}")
    if wire is not None:
        cname += f",wire={jnp.dtype(wire).name}"

    if backend == "auto":
        backend = "shard_map" if (mesh is not None and mesh.size > 1) else "batched"

    def init(flat: jax.Array):
        carry = {"x_hat": jnp.zeros_like(flat), "s": jnp.zeros_like(flat)}
        if stochastic:
            carry["key"] = jax.random.PRNGKey(seed)
        return carry

    def encode_probe(flat: jax.Array, x_hat: jax.Array) -> jax.Array:
        """Per-step encode cost model for the comm-split timer: the compress
        path (subtract + |·| top-k + gather), kept honestly state-evolving by
        CHOCO's own ``x̂ += scatter(q)`` update so XLA cannot hoist it out of
        the timing scan.  The extra [N,k] scatter is negligible next to the
        [N,D] top-k — mirrors the reference's encode window
        (communicator.py:184-196).  Stochastic compressors get a fixed key:
        the probe models cost, not the sample path."""
        vals, idx = compress(flat - x_hat, ratio, jax.random.PRNGKey(0))
        return scatter_rows(x_hat, idx, vals, 1.0)

    if backend == "batched":

        def step(flat: jax.Array, carry, flags_t: jax.Array, alive=None):
            if stochastic:
                new_key, sub = jax.random.split(carry["key"])
            else:
                new_key, sub = None, None
            vals, idx = compress(flat - carry["x_hat"], ratio, sub)

            def gather_msg(j):
                pi = perms[j]
                return vals[pi], idx[pi]

            # survivor mask: an edge exists only when both endpoints are
            # alive, so the partner table is thinned per-step exactly like
            # the decen edge gate (alive_i · alive_{π_j(i)}).  A dead
            # worker neither sends nor receives; its own {x̂, s} cycle keeps
            # running locally (harmless — quarantine makes it unobservable)
            # and healing resets its rows (resilience.runtime).
            partnered_eff = partnered
            if alive is not None:
                # graftlint: disable=GL001 — weights, not values: thins the
                # 0/1 partner table (edge weights), all factors finite
                partnered_eff = partnered * alive[None, :] * alive[perms]

            flat, x_hat, s = _choco_core(
                vals, idx, carry["x_hat"], carry["s"], flat, flags_t,
                gather_msg=gather_msg, partnered_rows=partnered_eff,
                matching_nonempty=nonempty,
                alpha=alpha, consensus_lr=consensus_lr,
                aligned_full=(compressor == "top_k"),
            )
            out = {"x_hat": x_hat, "s": s}
            if stochastic:
                out["key"] = new_key
            return flat, out

        return Communicator(name=cname + "]", init=init, step=step,
                            encode_probe=encode_probe)

    if backend != "shard_map":
        raise KeyError(f"unknown choco backend '{backend}'")
    if mesh is None:
        raise ValueError("shard_map backend needs a mesh")

    from jax import shard_map
    from jax.sharding import PartitionSpec as P

    from ..parallel import WORKER_AXIS, build_folded_plan

    axis = WORKER_AXIS
    C = mesh.shape[axis]
    plan = build_folded_plan(perms, C)
    L = plan.rows_per_chip
    partnered_blocks = partnered.reshape(M, C, L)  # [M, C, L]

    def chip_step(c, vals, idx, x_hat_blk, s_blk, flat_blk, flags_t,
                  alive=None):
        """One CHOCO step for this chip's [L, D] block, given its top-k."""

        def gather_msg(j):
            # reconstruct (vals, idx)[π_j] for local rows: only the [L, k]
            # compressed blocks move over ICI, never the dense state
            g_vals = jnp.zeros_like(vals)
            g_idx = jnp.zeros_like(idx)
            for part in plan.matchings[j]:
                if part.offset == 0:
                    yv, yi = vals, idx
                else:
                    # graftverify: bind C=1..8 part.offset=0..7
                    # (GL101: the ring table is a permutation for every
                    # binding; same shape as gossip_mix_folded's)
                    pairs = [((cc + part.offset) % C, cc) for cc in range(C)]
                    if wire is None:
                        yv = lax.ppermute(vals, axis, pairs)
                    else:
                        # values are already wire-representable (quantized at
                        # compress): the narrow ppermute is lossless and
                        # halves the compressed message bytes on ICI
                        yv = lax.ppermute(vals.astype(wire), axis,
                                          pairs).astype(vals.dtype)
                    yi = lax.ppermute(idx, axis, pairs)
                src = jnp.asarray(part.src_local)[c]  # [L]
                m = jnp.asarray(part.mask)[c]  # [L]
                g_vals = g_vals + m[:, None] * yv[src]
                g_idx = g_idx + m[:, None].astype(jnp.int32) * yi[src]
            return g_vals, g_idx

        partnered_rows = jnp.asarray(partnered_blocks)[:, c, :]  # [M, L]
        if alive is not None:
            # both-endpoints edge gate for this chip's rows: own alive ×
            # partner alive (partner index read from the replicated mask)
            sa = alive.reshape(C, L)[c]  # [L]
            pa = alive[jnp.asarray(perms)].reshape(M, C, L)[:, c, :]  # [M, L]
            # graftlint: disable=GL001 — weights, not values: the folded
            # twin of the batched partner-table thinning above
            partnered_rows = partnered_rows * sa[None, :] * pa
        return _choco_core(
            vals, idx, x_hat_blk, s_blk, flat_blk, flags_t,
            gather_msg=gather_msg, partnered_rows=partnered_rows,
            matching_nonempty=nonempty,
            alpha=alpha, consensus_lr=consensus_lr,
            aligned_full=(compressor == "top_k"),
        )

    def body_one(flat_blk, x_hat_blk, s_blk, flags_t, key, alive=None):
        c = lax.axis_index(axis)
        # per-chip key: fold the chip index so every block draws its own
        # stream from the one replicated step key
        sub = jax.random.fold_in(key, c) if stochastic else None
        vals, idx = compress(flat_blk - x_hat_blk, ratio, sub)
        return chip_step(c, vals, idx, x_hat_blk, s_blk, flat_blk, flags_t,
                         alive)

    def body_stream(flat_blk, x_hat_blk, s_blk, flags, key):
        # the key advances through the scan state exactly as the step
        # wrapper advances the carry key, so multi_step is arithmetically
        # identical to scanning step (the Communicator contract) and
        # run-composition over split flag streams reproduces one long run
        def scan_body(state, flags_t):
            f, xh, s, k = state
            if stochastic:
                nk, sub = jax.random.split(k)
            else:
                nk, sub = k, k
            f, xh, s = body_one(f, xh, s, flags_t, sub)
            return (f, xh, s, nk), None

        (f, xh, s, k), _ = lax.scan(
            scan_body, (flat_blk, x_hat_blk, s_blk, key), flags)
        return f, xh, s, k

    row = P(axis, None)
    sharded_one = shard_map(
        lambda f, xh, s, fl, k: body_one(f, xh, s, fl, k), mesh=mesh,
        in_specs=(row, row, row, P(), P()), out_specs=(row, row, row),
    )
    # masked variant: the survivor mask rides replicated, like the flags
    sharded_one_masked = shard_map(
        body_one, mesh=mesh,
        in_specs=(row, row, row, P(), P(), P()), out_specs=(row, row, row),
    )
    sharded_stream = shard_map(
        body_stream, mesh=mesh,
        in_specs=(row, row, row, P(), P()), out_specs=(row, row, row, P()),
    )
    _dummy = jnp.zeros((2,), jnp.uint32)  # top_k ignores its key argument

    def step(flat: jax.Array, carry, flags_t: jax.Array, alive=None):
        if stochastic:
            new_key, sub = jax.random.split(carry["key"])
        else:
            new_key, sub = None, _dummy
        if alive is None:
            flat, x_hat, s = sharded_one(flat, carry["x_hat"], carry["s"],
                                         flags_t, sub)
        else:
            flat, x_hat, s = sharded_one_masked(
                flat, carry["x_hat"], carry["s"], flags_t, sub,
                jnp.asarray(alive, flat.dtype))
        out = {"x_hat": x_hat, "s": s}
        if stochastic:
            out["key"] = new_key
        return flat, out

    def multi_step(flat: jax.Array, carry, flags: jax.Array):
        key = carry["key"] if stochastic else _dummy
        flat, x_hat, s, new_key = sharded_stream(
            flat, carry["x_hat"], carry["s"], flags, key)
        out = {"x_hat": x_hat, "s": s}
        if stochastic:
            out["key"] = new_key
        return flat, out

    return Communicator(
        name=cname + ",shard_map]", init=init, step=step,
        multi_step=multi_step, encode_probe=encode_probe,
    )
