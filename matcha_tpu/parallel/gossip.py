"""Gossip averaging — the performance-critical device primitive.

This is the TPU-native replacement for the reference's per-iteration MPI
exchange loop (``decenCommunicator.averaging``,
/root/reference/communicator.py:92-122): each rank's blocking
``sendrecv`` per active matching becomes a *static permutation* of the
worker axis, and the weighted accumulation becomes a fused multiply-add —
one XLA program, no host round-trips, no barriers (SPMD lockstep).

One gossip step with matchings ``π_j`` (involutions over workers, fixed
points = unmatched) and per-step weights ``w_j = α·flag_j``:

    x_i ← x_i + Σ_j w_j · (x_{π_j(i)} − x_i)

which equals the reference's ``(1 − deg·α)·x_i + α·Σ_active x_partner``
because fixed points contribute zero delta.

Alive masks (runtime resilience)
--------------------------------
Every backend accepts an optional traced ``alive: f32[N]`` survivor mask.
An edge of matching ``π_j`` is realized only when *both* endpoints are
alive: its per-slot weight is scaled by ``alive_i · alive_{π_j(i)}``.  A
dead worker's exchanges therefore become self-loops and the weight a
survivor would have sent to its dead partner stays on the survivor's own
row — the realized mixing matrix is ``W = I − Σ_j w_j·L_j^m`` with
``L_j^m`` the masked (still symmetric, zero-row-sum) Laplacian, so every
realized ``W`` remains doubly stochastic over the survivors.  This is what
makes MATCHA's expected-mixing convergence argument survive worker loss:
masking an edge is indistinguishable from its flag not having fired.
``alive=None`` (the default) compiles the exact pre-resilience program —
the hot path pays nothing for the feature it doesn't use.

Backends
--------
``gossip_mix``
    Gather form on a ``[N, ...]`` array.  Works for any N on any mesh under
    ``jit`` (XLA partitions the static gathers); also the single-chip
    simulation fast path, where every permutation is chip-local.

``gossip_mix_folded`` (+ ``build_folded_plan``)
    Explicit ``shard_map`` form for N virtual workers folded onto C chips
    (``L = N/C`` rows per chip).  Each matching is decomposed at trace time
    into chip-offset groups: offset 0 edges are local row gathers; each
    distinct offset ``d ≠ 0`` costs one ``lax.ppermute`` of the ``[L, ...]``
    block around the ring — riding ICI, deadlock-free by construction
    (SURVEY.md Q3).
"""

from __future__ import annotations

import dataclasses
from functools import partial
from typing import List, Sequence, Tuple

import jax
import jax.numpy as jnp
import numpy as np
from jax import lax

from .mesh import WORKER_AXIS

__all__ = [
    "gossip_mix",
    "gossip_mix_skip",
    "gossip_mix_dense",
    "involution_tables",
    "dense_exchange_form",
    "STREAM_MAX_WORKERS",
    "masked_laplacians",
    "matching_wire_bytes",
    "dense_gossip_fn",
    "dense_gossip_leaves_fn",
    "FoldedPlan",
    "build_folded_plan",
    "gossip_mix_folded",
    "mxu_precision",
    "resolve_wire_dtype",
    "shard_map_gossip_fn",
]


def resolve_wire_dtype(wire_dtype):
    """Normalize the wire-dtype knob to ``None`` (exact f32 program) or a
    jnp dtype the exchange casts to at the gossip boundary.

    ``"f32"``/``None`` compile the exact legacy program (no casts anywhere);
    ``"bf16"`` halves every exchanged byte: the permuted/gathered operand —
    the thing that actually crosses ICI in the folded plan, or streams
    through HBM in the single-chip forms — is bf16, while master parameters
    and the delta accumulation stay f32 (the ``mxu_precision`` seam's
    contract).  A jnp dtype passes through untouched.
    """
    if wire_dtype is None:
        return None
    if isinstance(wire_dtype, str):
        if wire_dtype in ("f32", "float32"):
            return None
        if wire_dtype in ("bf16", "bfloat16"):
            return jnp.bfloat16
        raise ValueError(f"unknown wire_dtype '{wire_dtype}' (f32|bf16)")
    dt = jnp.dtype(wire_dtype)
    return None if dt == jnp.dtype(jnp.float32) else dt


def mxu_precision(compute_dtype) -> lax.Precision:
    """Matmul precision that makes ``compute_dtype`` honest on TPU.

    TPU DEFAULT precision runs f32×f32 matmuls as a single bf16 MXU pass;
    f32 compute must request HIGHEST to actually be f32 (CPU/GPU are
    unaffected).  bf16 keeps DEFAULT — the native MXU input precision the
    perf path is specified in.
    """
    return (lax.Precision.HIGHEST
            if jnp.dtype(compute_dtype).itemsize >= 4 else lax.Precision.DEFAULT)


def matching_wire_bytes(decomposed, dim: int, wire_dtype=None) -> np.ndarray:
    """``f64[M]`` — bytes that cross the wire when matching ``j`` fires.

    The dense row-exchange account every backend realizes one way or
    another: each of matching ``j``'s ``E_j`` edges moves both endpoint
    rows (``2·E_j·dim`` values) at the wire dtype's width — the quantity
    the telemetry layer accumulates per step (``obs.telemetry``) and the
    roofline model prices per chain (``bench.roofline``).  Static numpy:
    the per-matching vector is baked into the compiled step as a constant,
    so the in-graph byte counter is one dot product with the flag row.
    CHOCO's *compressed* stream is deliberately not modeled here (the
    counter reports the uncompressed equivalent; the encode side is the
    comm-split timer's job).
    """
    dt = resolve_wire_dtype(wire_dtype)
    itemsize = 4 if dt is None else jnp.dtype(dt).itemsize
    return np.asarray([2.0 * len(m) * dim * itemsize for m in decomposed],
                      np.float64)


def _rows(mask: jax.Array, x: jax.Array) -> jax.Array:
    """Broadcast a per-row ``[R]`` mask over the trailing dims of ``[R, ...]``."""
    return mask.reshape(mask.shape + (1,) * (x.ndim - 1))


def involution_tables(perms) -> tuple[np.ndarray, np.ndarray]:
    """THE table seam of the row-gather exchanges (:func:`gossip_mix`,
    :func:`gossip_mix_skip`): validate + normalize matchings.

    ``perms``: ``int[M, N]`` — one total involution per matching (partner
    index, or self for unmatched slots), exactly ``Schedule.perms``.
    Returns ``(perms int32[M, N], partnered f32[M, N])`` with
    ``partnered[j, i] = 1`` iff slot ``i`` has a partner in matching ``j``.

    Every row is checked to be a *total involution* (``π[π[i]] == i`` with
    in-range entries) and a :class:`ValueError` names the first offender
    otherwise.  This is the runtime half of the GL101 contract: static
    tables are proven parametrically by graftverify; schedule-built tables
    are routed through this validator, so a gather against a non-involution
    — which would silently double- or zero-weight rows, the same corruption
    class as a one-sided ``ppermute`` — cannot reach the exchange either way.
    """
    p = np.asarray(perms)
    if p.ndim != 2:
        raise ValueError(f"perms must be [M, N], got shape {p.shape}")
    m, n = p.shape
    if not np.issubdtype(p.dtype, np.integer):
        raise ValueError(f"perms must be integer partner indices, "
                         f"got dtype {p.dtype}")
    if m and ((p < 0).any() or (p >= n).any()):
        j = int(np.argwhere((p < 0) | (p >= n))[0][0])
        raise ValueError(f"matching {j}: partner index out of range [0, {n})")
    rows = np.arange(n)
    for j in range(m):
        if not np.array_equal(p[j][p[j]], rows):
            bad = int(np.argwhere(p[j][p[j]] != rows)[0][0])
            raise ValueError(
                f"matching {j} is not an involution: "
                f"π(π({bad})) = {int(p[j][p[j]][bad])} != {bad} — a matching "
                f"must pair slots symmetrically (fixed points map to self)")
    return p.astype(np.int32), (p != rows[None, :]).astype(np.float32)


def gossip_mix(x: jax.Array, perms: np.ndarray, weights: jax.Array,
               alive: jax.Array | None = None,
               wire_dtype=None) -> jax.Array:
    """``x_i + Σ_j weights[j]·(x[π_j(i)] − x_i)`` over the leading axis.

    ``perms`` must be a *static* numpy ``int32[M, N]`` (part of the compiled
    program — this is what lets XLA lower each gather to a shuffle /
    collective-permute instead of a dynamic gather).  ``weights`` is a traced
    ``[M]`` vector, typically ``alpha * flags[t]`` — masking keeps the
    communication pattern static across steps so nothing recompiles
    (SURVEY.md §7 "per-step flag-dependent communication").

    ``alive``: optional traced ``f32[N]`` survivor mask — each edge's delta
    is additionally scaled by ``alive_i·alive_{π_j(i)}`` (see module
    docstring), keeping the realized mixing doubly stochastic over survivors.

    ``wire_dtype`` (see :func:`resolve_wire_dtype`): exchanged values are
    quantized once, *before* the permutes, and the delta is formed from the
    quantized values on both endpoints — edge (i, j) then contributes
    ``w·(x̃_j − x̃_i)`` to row i and exactly ``−`` that to row j (IEEE
    ``a − b == −(b − a)``), so pairwise cancellation — and with it exact
    worker-mean preservation — survives the bf16 wire bit-for-bit.  The
    accumulation into f32 ``x`` stays f32.
    """
    perms = np.asarray(perms)
    if perms.ndim != 2 or perms.shape[1] != x.shape[0]:
        raise ValueError(f"perms {perms.shape} incompatible with x {x.shape}")
    wire = resolve_wire_dtype(wire_dtype)
    xw = x if wire is None else x.astype(wire).astype(x.dtype)
    acc = jnp.zeros_like(x)
    for j in range(perms.shape[0]):
        pi = perms[j]
        if np.all(pi == np.arange(pi.shape[0])):
            continue  # empty matching: zero delta regardless of flag
        delta = xw[pi] - xw
        if alive is not None:
            # graftlint: disable=GL001 — weights, not values: the alive
            # product scales each edge's *weight*; non-finite rows are
            # sealed upstream (resilience.runtime.gossip_quarantined)
            delta = _rows(alive * alive[pi], delta) * delta
        acc = acc + weights[j] * delta
    return x + acc


def gossip_mix_skip(x: jax.Array, perms: np.ndarray, weights: jax.Array,
                    alive: jax.Array | None = None,
                    wire_dtype=None) -> jax.Array:
    """``gossip_mix`` with per-matching ``lax.cond`` instead of masking:
    an inactive matching costs *nothing at runtime* (XLA compiles both
    branches but executes only the taken one), so the MATCHA budget buys
    real time back, not just masked-out arithmetic.

    Trade-off (``benchmarks/skip_microbench.py`` is its harness; for
    today's code it is not measured on the chip): the cond's identity
    branch still writes a full-state buffer, so on-chip the saving exists
    only while per-matching work exceeds a state copy.  The
    regime this mechanism is actually for is the folded shard_map plan
    (``gossip_mix_folded(skip=True)``), where the cond skips the matching's
    cross-chip *collectives*.  Exact same arithmetic as ``gossip_mix`` for
    the executed matchings; an all-zero flag row is a pure identity.

    Do NOT call this under ``vmap``: batching lowers ``lax.cond`` to
    ``select``, which executes *both* branches every step — the result stays
    correct but every skip silently becomes masked work, erasing the
    backend's entire purpose.  ``x`` must be the top-level worker-stacked
    array; inside vmapped code use ``gossip_mix`` (masking) instead.

    ``alive`` masks edges *inside* the taken branch (the cond predicate
    stays the flag weight — the skip decision is a schedule property; worker
    death only reshapes the executed matching into survivor self-loops)."""
    perms = np.asarray(perms)
    if perms.ndim != 2 or perms.shape[1] != x.shape[0]:
        raise ValueError(f"perms {perms.shape} incompatible with x {x.shape}")
    wire = resolve_wire_dtype(wire_dtype)
    xw = x if wire is None else x.astype(wire).astype(x.dtype)
    out = x
    for j in range(perms.shape[0]):
        pi = perms[j]
        if np.all(pi == np.arange(pi.shape[0])):
            continue

        def exchange(o, w=weights[j], p=pi):
            delta = xw[p] - xw
            if alive is not None:
                # graftlint: disable=GL001 — weights, not values (same
                # sealed-input contract as gossip_mix above)
                delta = _rows(alive * alive[p], delta) * delta
            return o + w * delta

        # != 0 (not > 0) so skip stays exactly equivalent to masking for any
        # weight sign a future schedule might produce (ADVICE r2)
        out = lax.cond(weights[j] != 0, exchange, lambda o: o, out)
    return out


# ---------------------------------------------------------------------------
# Dense (MXU) backend
# ---------------------------------------------------------------------------

def masked_laplacians(laplacians: jax.Array, alive: jax.Array) -> jax.Array:
    """Survivor-masked Laplacian stack: edge (u, v) kept iff both alive.

    ``L_j = D_j − A_j``; masking scales the adjacency by
    ``alive_u·alive_v`` and recomputes the degree, so each masked matrix is
    still a Laplacian (symmetric, zero row sums) and the mixing built from
    it stays doubly stochastic.  Works for traced ``alive`` (runtime masks)
    and for float survival *probabilities* (the expected masked Laplacian
    under independent worker death — what the degraded-ρ predictor uses).
    """
    L = jnp.asarray(laplacians)
    n = L.shape[-1]
    eye = jnp.eye(n, dtype=L.dtype)
    adj = jnp.einsum("mn,nk->mnk", jnp.diagonal(L, axis1=-2, axis2=-1), eye) - L
    # graftlint: disable=GL001 — weights, not values: adjacency entries are
    # finite topology constants; the outer product rescales edge weights
    adj = adj * jnp.outer(alive, alive)[None, :, :]
    deg = jnp.sum(adj, axis=-1)
    return jnp.einsum("mn,nk->mnk", deg, eye) - adj


#: Largest worker count at which the one-chip exchange ``x ← W_t x`` runs as
#: the streamed vector-unit pass (``pallas_gossip.stream_mix``) and not as
#: the ``[N, N] x [N, D]`` MXU product.  Set from gossip-only 16-step chains
#: on one v5e chip, ms a step over a 2.34 GB float32 state (2.14 GB at
#: N = 2; my chip runs, PR 28, PERF.md section 6), product / streamed:
#:
#:     N =   2     8     16    24    32    48    64    128
#:         24.98 14.10 14.30 14.29 14.28 14.25 14.26 14.31   product
#:          6.71  7.20  7.22  8.04  9.36 12.74 22.53 52.98   streamed
#:
#: The product is bound by bytes at every N: a 7.1 ms pass and, because it
#: cannot write over the operand it reads, a 7.1 ms copy of the state beside
#: it, in a scanned chain and inside the train step alike (cell 1's traced
#: epoch program: ``copy.3059`` + ``fusion.1``, 14.2 ms a step; the token
#: cell's 24.9).  The streamed pass works in place; it is N multiply-adds an
#: element and leaves the bytes' shadow between N = 16 and 24.  It still wins
#: by a tenth at 48 (one chunk width tried) and loses at 64: 32 is the
#: largest N read with room to spare.  The cells run N = 2, 16 and 128.
STREAM_MAX_WORKERS = 32


def dense_exchange_form(n: int, single_chip: bool = True) -> dict:
    """Which form the dense exchange takes at worker count ``n``: the
    decision record the ``backend`` event carries.  ``streamed`` up to
    :data:`STREAM_MAX_WORKERS` rows on one chip; ``mxu`` above it, and
    wherever a mesh shards the state (a kernel is not partitioned; XLA's
    product is)."""
    streamed = single_chip and n <= STREAM_MAX_WORKERS
    return {"form": "streamed" if streamed else "mxu", "n": int(n),
            "crossover": STREAM_MAX_WORKERS, "single_chip": bool(single_chip)}


def _mixing_matrix(laplacians, weights, compute_dtype, alive=None):
    """``W_t = I - sum_j weights[j] L_j`` in ``compute_dtype``, the
    Laplacians masked by ``alive`` where given."""
    if alive is not None:
        laplacians = masked_laplacians(laplacians, alive)
    n = laplacians.shape[-1]
    W = jnp.eye(n, dtype=jnp.float32) - jnp.tensordot(weights, laplacians, axes=1)
    return W.astype(compute_dtype)


def gossip_mix_dense(
    x: jax.Array,
    laplacians: jax.Array,
    weights: jax.Array,
    compute_dtype=jnp.float32,
    alive: jax.Array | None = None,
    single_chip: bool = True,
) -> jax.Array:
    """One gossip step ``x ← W_t x`` with ``W_t = I − Σ_j weights[j]·L_j``
    built on the fly from the flag weights, in the form the static worker
    count ``N = x.shape[0]`` asks for (:func:`dense_exchange_form`):

    * ``N <= STREAM_MAX_WORKERS`` on one chip — **streamed**: one pass that
      reads each element of ``x`` once and writes each output once, ``N``
      float32 multiply-adds an element on the vector unit
      (``pallas_gossip.stream_mix``, the state aliased in place).  At
      N = 2 or 16 the product would fill 2 or 16 of the MXU's 128 rows six
      times over (``highest``) for arithmetic that the bytes' own stream
      hides.
    * above it — **mxu**: a single ``[N, N] x [N, D]`` matmul.  The gather
      form walks the state once *per matching*; the product is two passes
      plus MXU work that 128 or 256 rows fill, and W_t (``N×N``) is
      negligible.  With the worker state sharded along the *feature* axis
      the matmul is chip-local (``single_chip=False`` keeps this form at
      every N: XLA partitions a product, not a kernel).

    ``laplacians``: ``f32[M, N, N]`` stack (trace-time constant).
    ``compute_dtype``: below float32 (the bf16 wire) both forms round ``W_t``
    and ``x`` to it and accumulate in float32; at float32 the streamed form
    is exact float32 arithmetic, and the product requests HIGHEST — on TPU,
    DEFAULT degrades f32 operands to one bf16 MXU pass, invisible on the CPU
    test mesh but ~4e-2 rel err vs the exact gather path after 20 steps on
    hardware (r4 TPU gate finding).

    ``alive`` rebuilds the Laplacian stack through :func:`masked_laplacians`
    before forming ``W_t`` — two extra ``[M, N, N]`` elementwise passes, tiny
    next to the ``[N, D]`` state; both forms then mix with the masked W.
    """
    n = x.shape[0]
    W = _mixing_matrix(laplacians, weights, compute_dtype, alive)
    if dense_exchange_form(n, single_chip)["form"] == "streamed":
        from .pallas_gossip import pallas_interpret, stream_mix

        return stream_mix(x, W, wire_dtype=compute_dtype,
                          interpret=pallas_interpret())
    out = jax.lax.dot(
        W,
        x.astype(compute_dtype),
        precision=mxu_precision(compute_dtype),
        preferred_element_type=jnp.float32,
    )
    return out.astype(x.dtype)


def dense_gossip_fn(laplacians: np.ndarray, compute_dtype=jnp.float32,
                    single_chip: bool = True):
    """Build ``(x, weights[, alive]) -> x`` closing over the Laplacian stack."""
    L = jnp.asarray(np.asarray(laplacians), jnp.float32)

    def fn(x, weights, alive=None):
        return gossip_mix_dense(x, L, weights, compute_dtype=compute_dtype,
                                alive=alive, single_chip=single_chip)

    return fn


def dense_gossip_leaves_fn(laplacians: np.ndarray, compute_dtype=jnp.float32):
    """The leaf form of :func:`dense_gossip_fn` at the worker counts whose
    exchange is ``streamed``: ``(leaves, weights) -> (leaves', sq)`` over a
    list of ``[N, ...]`` parameter leaves, the same ``W_t`` from the same
    weights, every large leaf mixed in place where it lies and each worker's
    squared distance from the mean returned beside it
    (``pallas_gossip.tree_mix``).  No ``alive``: quarantine and heal work
    on rows of the flat state."""
    L = jnp.asarray(np.asarray(laplacians), jnp.float32)

    def fn(leaves, weights):
        from .pallas_gossip import pallas_interpret, tree_mix

        return tree_mix(leaves, _mixing_matrix(L, weights, compute_dtype),
                        wire_dtype=compute_dtype, interpret=pallas_interpret())

    return fn


# ---------------------------------------------------------------------------
# Folded shard_map backend
# ---------------------------------------------------------------------------

@dataclasses.dataclass(frozen=True)
class _OffsetPart:
    """Edges of one matching whose partner sits ``offset`` chips away."""

    offset: int
    src_local: np.ndarray  # int32[C, L] — partner's row within its chip's block
    mask: np.ndarray  # f32[C, L] — 1 where this offset applies


@dataclasses.dataclass(frozen=True)
class FoldedPlan:
    """Trace-time constant: per-matching chip-offset decomposition."""

    num_chips: int
    rows_per_chip: int
    matchings: Tuple[Tuple[_OffsetPart, ...], ...]

    @property
    def num_matchings(self) -> int:
        return len(self.matchings)

    @property
    def offsets_used(self) -> List[List[int]]:
        return [[p.offset for p in m] for m in self.matchings]

    def hop_accounting(self) -> List[List[Tuple[int, int, int]]]:
        """Per-matching ``(offset, slots, ring_hops)`` cost ledger.

        One entry per offset part: ``slots`` is how many of the N worker
        slots that part serves (mask population — fixed points land in the
        offset-0 part), and ``ring_hops`` is what the part's ``ppermute``
        costs on a bidirectional ICI ring: ``min(d, C − d)`` sequential hops
        for the whole ``[L, ...]`` block, 0 for the chip-local part.  This is
        the per-edge accounting the offline planner's link-cost model sums —
        exposed here, next to the execution plan it describes, so the cost
        model can never drift from what ``gossip_mix_folded`` actually runs.
        """
        C = self.num_chips
        out: List[List[Tuple[int, int, int]]] = []
        for parts in self.matchings:
            out.append([
                (p.offset, int(p.mask.sum()), min(p.offset, C - p.offset))
                for p in parts
            ])
        return out

    def matching_hop_units(self) -> np.ndarray:
        """f64[M] — total ring hops each matching costs per activation.

        The folded executor issues one ``ppermute`` per (matching, nonzero
        offset) regardless of how many edges share the offset, so the unit is
        hops-of-a-full-block, summed over the matching's nonzero offsets.
        All-local matchings (and any plan at C = 1) cost 0 — matching the
        measured single-chip regime where comm_time is flat across budgets
        (benchmarks/budget_sweep.json).
        """
        return np.asarray(
            [sum(h for (_, _, h) in m) for m in self.hop_accounting()],
            dtype=np.float64,
        )


def build_folded_plan(perms: np.ndarray, num_chips: int) -> FoldedPlan:
    """Split each matching permutation into intra-chip and inter-chip parts.

    Workers are laid out ``g = c*L + l`` (chip-major).  For each matching and
    each distinct chip offset ``d = (chip(π(g)) − chip(g)) mod C`` we emit a
    selection table: receiver chip ``c`` picks row ``π(g) mod L`` out of the
    block arriving from chip ``(c+d) mod C``.  Because π is a total involution
    (fixed points map to themselves at offset 0), the masks of all parts
    partition every slot — so the combined gather is exactly ``x[π]``.
    """
    perms = np.asarray(perms, dtype=np.int64)
    M, N = perms.shape
    C = int(num_chips)
    if N % C:
        raise ValueError(f"N={N} not divisible by num_chips={C}")
    L = N // C
    g = np.arange(N)
    matchings = []
    for j in range(M):
        p = perms[j]
        d_all = ((p // L) - (g // L)) % C  # [N]
        parts = []
        for d in sorted(set(int(v) for v in d_all)):
            sel = d_all == d
            src = np.where(sel, p % L, 0).reshape(C, L).astype(np.int32)
            mask = sel.astype(np.float32).reshape(C, L)
            parts.append(_OffsetPart(int(d), src, mask))
        matchings.append(tuple(parts))
    return FoldedPlan(C, L, tuple(matchings))


def gossip_mix_folded(
    x_blk: jax.Array,
    plan: FoldedPlan,
    weights: jax.Array,
    axis: str = WORKER_AXIS,
    skip: bool = False,
    alive: jax.Array | None = None,
    wire_dtype=None,
) -> jax.Array:
    """Per-chip body of the folded gossip step; call inside ``shard_map``.

    ``x_blk``: this chip's ``[L, ...]`` block of the ``[N, ...]`` worker array.
    One ``ppermute`` per (matching, nonzero offset); offset-0 edges are local
    row gathers.  Weights mask inactive matchings (communication is static).

    ``skip=True`` wraps each matching's exchange in ``lax.cond`` so an
    inactive matching's ``ppermute``s are not executed that step.  This is
    where cond-skipping genuinely pays: the avoided cost is a cross-chip
    (ICI/DCN) collective, not on-chip arithmetic — unlike the single-array
    ``gossip_mix_skip``, whose saving is bounded by the cond identity-copy
    (see benchmarks/skip_microbench.py).  The flag predicate is replicated
    (same schedule on every chip), so all chips take the same branch and the
    collective pattern stays deadlock-free.

    ``alive``: optional *replicated* ``f32[N]`` survivor mask — every chip
    sees the whole vector (it is N floats; the state blocks are what's
    sharded).  Each part's slots are additionally gated by
    ``alive[own row]·alive[partner row]``; the ``ppermute`` pattern itself
    stays static (a dead chip's block still circulates, weighted to zero),
    which is what keeps the collective schedule deadlock-free under faults.

    ``wire_dtype``: the ``ppermute`` operand — the bytes that actually ride
    ICI — is cast to this dtype before the exchange (bf16 halves every
    inter-chip hop), and the delta is formed from the quantized values on
    *both* endpoints in f32, so edge-pairwise cancellation (exact
    worker-mean preservation) survives the narrow wire; the f32 block
    accumulation is untouched.
    """
    C = plan.num_chips
    L = plan.rows_per_chip
    c = lax.axis_index(axis)
    alive2d = None if alive is None else alive.reshape(C, L)
    wire = resolve_wire_dtype(wire_dtype)
    # xw: the wire image of this chip's block — what ppermute moves and what
    # both sides of every delta read, cast back to f32 once per step
    xw_wire = x_blk if wire is None else x_blk.astype(wire)
    xw = x_blk if wire is None else xw_wire.astype(x_blk.dtype)
    acc = jnp.zeros_like(x_blk)
    for j, parts in enumerate(plan.matchings):

        def matching_delta(parts=parts):
            delta = jnp.zeros_like(x_blk)
            for part in parts:
                if part.offset == 0:
                    y = xw
                else:
                    # graftverify: bind C=1..8 part.offset=0..7
                    # (GL101 verifies the ring table is a permutation for
                    # every binding — offsets ≥ C wrap through the modulus)
                    pairs = [((cc + part.offset) % C, cc) for cc in range(C)]
                    y = lax.ppermute(xw_wire, axis, pairs).astype(x_blk.dtype)
                src = jnp.asarray(part.src_local)[c]  # [L]
                m = jnp.asarray(part.mask)[c]  # [L]
                if alive2d is not None:
                    # both-endpoints gate: own row × partner row (partner
                    # lives on chip c+offset, at its local row `src`)
                    # graftlint: disable=GL001 — mask algebra: 0/1 slot mask
                    # × 0/1 alive gates, all finite by construction
                    m = m * alive2d[c] * alive2d[(c + part.offset) % C][src]
                # masks partition all L slots ⇒ Σ_parts m·y[src] == x[π_j]
                delta = delta + _rows(m, x_blk) * (y[src] - xw)
            return delta

        if skip:
            acc = acc + lax.cond(
                weights[j] != 0,
                lambda w=weights[j], d=matching_delta: w * d(),
                lambda: jnp.zeros_like(x_blk),
            )
        else:
            acc = acc + weights[j] * matching_delta()
    return x_blk + acc


def shard_map_gossip_fn(perms: np.ndarray, mesh, axis: str = WORKER_AXIS,
                        skip: bool = False, wire_dtype=None):
    """Build a jittable ``(x[N,...], weights[M][, alive[N]]) -> x[N,...]``
    gossip function running as an explicit shard_map over ``mesh``.  ``skip``
    forwards to :func:`gossip_mix_folded` (cond-skip inactive matchings'
    collectives); ``wire_dtype`` likewise (bf16 halves the ppermute bytes on
    ICI).  ``alive=None`` traces the exact unmasked program; a survivor mask
    is passed replicated (``P()``), so every chip gates its edges
    identically."""
    from jax import shard_map
    from jax.sharding import PartitionSpec as P

    C = mesh.shape[axis]
    plan = build_folded_plan(np.asarray(perms), C)

    def body(x_blk, weights):
        return gossip_mix_folded(x_blk, plan, weights, axis=axis, skip=skip,
                                 wire_dtype=wire_dtype)

    def body_masked(x_blk, weights, alive):
        return gossip_mix_folded(x_blk, plan, weights, axis=axis, skip=skip,
                                 alive=alive, wire_dtype=wire_dtype)

    def fn(x, weights, alive=None):
        spec = P(axis, *([None] * (x.ndim - 1)))
        if alive is None:
            return shard_map(body, mesh=mesh, in_specs=(spec, P()),
                             out_specs=spec)(x, weights)
        return shard_map(body_masked, mesh=mesh, in_specs=(spec, P(), P()),
                         out_specs=spec)(x, weights, alive)

    return fn
