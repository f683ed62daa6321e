"""Communicator interface: the per-iteration consensus transform.

The reference's plugin seam (SURVEY.md §1) is ``communicator.communicate(model)``
— a stateful object mutating torch parameters over MPI.  The TPU-native form
is a *pure function pair* compatible with ``jit``/``scan``:

    carry0      = comm.init(flat0)                  # [N, D] -> carry pytree
    flat', c'   = comm.step(flat, carry, flags_t)   # one gossip iteration

``flat`` is the ``[N, D]`` stack of all workers' flattened parameters,
``flags_t`` the ``f32[M]`` activation row for this step.  Carries hold
persistent algorithm state (CHOCO's ``x_hat``/``s``) so checkpointing them is
trivial — the state the reference would silently lose on restart
(SURVEY.md §5.4).

Two-phase contract (overlapped pipelining, DESIGN.md §11)
---------------------------------------------------------
``step`` fuses *exchange* and *apply* into one transform, which puts the
gossip collectives on the critical path of every training step.  The
two-phase split breaks that dependence:

    delta, c' = comm.begin_mix(flat, carry, flags_t[, alive])  # issue
    flat'     = comm.apply_mix(flat, delta)                    # consume

``begin_mix`` performs the whole exchange for this step and returns the
*mixing delta* ``step(flat)[0] − flat`` instead of the mixed state;
``apply_mix`` is a pure elementwise add.  A pipelined train loop issues
``begin_mix`` at step *t* and applies the delta at step *t+1* — the
collective then has no consumer inside step *t+1*'s forward/backward, so
XLA is free to overlap ICI traffic with compute (arXiv:2410.11998's
overlap condition).  Because every mixing transform here preserves the
worker mean (doubly stochastic ``W``; CHOCO's telescoping ``s``/``x̂``),
the delta has exactly zero column-mean — applying it a step late never
moves the fleet average, only the per-worker spread (MATCHA's one-step
staleness argument: the contraction factor is perturbed, not the
convergence structure; see ``plan.spectral.stale_contraction_rho``).

``run_pipelined`` generalizes the schedule to bounded staleness
(consume-at-≤t+k, DESIGN.md §20): deltas age through a k-slot ring, the
k=1 case is this contract bitwise, and the same zero-column-mean argument
keeps the fleet average exact at any depth — only the contraction factor
pays for the delay (the staleness-extended ``stale_contraction_rho``).
"""

from __future__ import annotations

import dataclasses
from typing import Any, Callable, Tuple

import jax

__all__ = ["Communicator"]

StepFn = Callable[..., Tuple[jax.Array, Any]]


@dataclasses.dataclass(frozen=True)
class Communicator:
    """A named (init, step) pair; ``step`` must be jit/scan-compatible.

    ``step(flat, carry, flags_t)`` also accepts an optional fourth argument
    ``alive: f32[N]`` — the survivor mask of the resilience layer (see
    ``parallel.gossip`` module docstring): a dead worker's exchanges become
    self-loops with the weight renormalized onto the survivor, so every
    realized mixing matrix stays doubly stochastic over survivors.  Omitting
    it (or passing ``None``) compiles the exact unmasked program.

    ``multi_step``, when present, runs a whole flag stream in one launch
    (CHOCO's ``shard_map`` form scans the stream inside one ``shard_map``
    call) — arithmetically equivalent to scanning ``step``, used by ``run``
    for consensus-only phases and the comm-split timer.  It takes no
    survivor mask, so ``run`` scans ``step`` for every masked chain.

    ``leaves_step``, when present, is ``step`` over the parameter leaves
    where they lie (``parallel.pallas_gossip.tree_mix``): the same ``W_t``
    from the same flag row, and the squares the disagreement needs from the
    same pass.  ``train/state.py:exchange_plan`` decides whether a step may
    take it.

    ``encode_probe``, when present, is a scan-compatible stand-in for the
    per-step message *encode* work (CHOCO's compress path) —
    ``(flat, probe_state) -> probe_state`` with ``probe_state0 =
    zeros_like(flat)``.  The comm-split timer uses it to report encode time
    separately from exchange time, mirroring the reference's split timing of
    compression vs sendrecv (communicator.py:184-196,268).
    """

    name: str
    init: Callable[[jax.Array], Any]
    step: StepFn
    multi_step: Any = None  # Optional[(flat, carry, flags[T,M]) -> (flat, carry)]
    encode_probe: Any = None  # Optional[(flat, probe_state) -> probe_state]
    # the tree form of ``step`` (the one-chip streamed exchange of the dense
    # decen communicator alone): (leaves, carry, flags_t) -> (leaves', carry,
    # sq[N]) over the list of ``[N, ...]`` parameter leaves, no flat copy
    # built; ``sq`` is each worker's squared distance from the worker mean.
    # ``leaves_refusal`` says why a communicator has none.
    leaves_step: Any = None
    leaves_refusal: Any = None

    def begin_mix(self, flat: jax.Array, carry: Any, flags_t: jax.Array,
                  alive: Any = None):
        """Issue this step's exchange; returns ``(delta, carry')``.

        ``delta = step(flat)[0] − flat`` — all collectives (ppermute /
        gathers / the dense matmul) execute here; what crosses the phase
        boundary is a plain ``[N, D]`` array with zero column-mean.  The
        default derivation from ``step`` is exact for every backend: decen's
        delta is ``Σ_j w_j(x[π_j] − x)`` (the axpy accumulator itself),
        CHOCO's is ``γ·(s − x̂)``, centralized's is ``x̄ − x``.  Carry
        advances at *issue* time, so a pipelined chain threads carries
        identically to an eager one.
        """
        # named scope, not a wall-clock bracket: XLA fuses the exchange
        # into the surrounding step, so attribution must ride the op
        # metadata (utils.profiling.device_span) — every collective this
        # phase emits shows up under comm/begin_mix in a profiler trace
        with jax.named_scope("comm/begin_mix"):
            if alive is None:
                mixed, carry = self.step(flat, carry, flags_t)
            else:
                mixed, carry = self.step(flat, carry, flags_t, alive)
            return mixed - flat, carry

    def apply_mix(self, flat: jax.Array, delta: jax.Array) -> jax.Array:
        """Consume a ``begin_mix`` delta: a pure elementwise add, no
        collectives — safe to fuse into the next step's update math."""
        with jax.named_scope("comm/apply_mix"):
            return flat + delta

    def run_overlapped(self, flat: jax.Array, flags: jax.Array,
                       carry: Any = None, alive: Any = None,
                       drain: bool = True):
        """Scan the two-phase pipeline over a flag stream.

        Step *t* applies the delta issued at *t−1*, then issues its own —
        the software-pipelined schedule the overlapped train loop runs.  On
        a pure consensus chain (nothing mutates ``flat`` between issue and
        apply) the drained pipeline reproduces ``run`` *exactly*: the delta
        issued on ``x`` and applied to the same ``x`` is one eager step by
        construction.  (Exactly in real arithmetic — at f32 wire the fp
        difference is reassociation noise, ~1 ulp/step; a *quantizing* wire
        re-rounds the slightly different state, so bf16 drain-vs-eager
        agreement holds only to the 2⁻⁸-per-step noise scale the
        ``stale_contraction_rho`` budget already covers.)
        ``drain=True`` applies the final in-flight delta so
        the result is the full T-step chain; ``drain=False`` returns the
        visible (one-mix-behind) state plus the pending delta, which is
        what an epoch boundary in the pipelined train loop holds.

        ``alive``: optional ``f32[N]`` (constant) or ``f32[T, N]``
        (per-step) survivor mask, forwarded to ``begin_mix``.
        """
        import jax.numpy as jnp
        from jax import lax

        if carry is None:
            carry = self.init(flat)
        flags = jnp.asarray(flags, jnp.float32)
        pending = jnp.zeros_like(flat)
        if flags.shape[0] == 0:
            return (self.apply_mix(flat, pending), carry) if drain \
                else (flat, carry, pending)

        if alive is not None:
            alive = jnp.asarray(alive, jnp.float32)

        def body(state, xs):
            x, c, pend = state
            flags_t, alive_t = xs
            x = self.apply_mix(x, pend)
            pend, c = self.begin_mix(x, c, flags_t, alive_t)
            return (x, c, pend), None

        if alive is None or alive.ndim == 1:
            a = alive  # None or constant row: closed over, not scanned

            def body_const(state, flags_t):
                return body(state, (flags_t, a))

            (x, c, pending), _ = lax.scan(
                body_const, (flat, carry, pending), flags)
        else:
            (x, c, pending), _ = lax.scan(
                body, (flat, carry, pending), (flags, alive))
        if drain:
            return self.apply_mix(x, pending), c
        return x, c, pending

    def run_pipelined(self, flat: jax.Array, flags: jax.Array,
                      carry: Any = None, alive: Any = None,
                      staleness: int = 1, drain: bool = True):
        """Scan the bounded-staleness pipeline: consume-at-≤t+k.

        The k-slot generalization of :meth:`run_overlapped`: in-flight
        deltas age through a static-shape ``[K, N, D]`` pending ring.  Step
        *t* applies ring slot ``t mod K`` (the delta issued at *t−K* — a
        zero during the first K warmup steps), then issues its own exchange
        into the same slot.  ``staleness=1`` is bitwise the one-step
        pipeline (the ring degenerates to the single pending buffer,
        consumed and refilled in the identical order), pinned by
        ``tests/test_staleness.py`` on every backend.  For K > 1 the
        drained chain is *not* the eager W-chain — each delta is issued on
        a state missing its K−1 in-flight predecessors; the perturbation
        is the delayed-consensus recurrence ``plan.spectral.
        stale_contraction_rho(staleness=K)`` bounds — but every delta
        still has exactly zero column-mean, so the worker mean never
        moves, drained or not.  When the flag stream fires at most once
        every K steps (``local_steps ≥ K`` thinning), each delta is
        consumed before the next is issued and the drained chain *does*
        reproduce ``run`` exactly — the telescoping k=1 argument applies
        event-by-event.

        ``drain=True`` flushes the ring oldest-first so the result has
        realized every issued exchange; ``drain=False`` returns
        ``(visible_state, carry, ring)`` — what an epoch boundary of the
        k-deep train loop holds.  ``alive`` as in :meth:`run_overlapped`.
        """
        import jax.numpy as jnp
        from jax import lax

        k = int(staleness)
        if k < 1:
            raise ValueError(f"staleness must be >= 1, got {staleness}")
        if carry is None:
            carry = self.init(flat)
        flags = jnp.asarray(flags, jnp.float32)
        ring = jnp.zeros((k,) + flat.shape, flat.dtype)
        if flags.shape[0] == 0:
            return (flat, carry) if drain else (flat, carry, ring)
        if alive is not None:
            alive = jnp.asarray(alive, jnp.float32)

        def body(state, xs):
            x, c, pend, t = state
            flags_t, alive_t = xs
            slot = lax.rem(t, k)
            x = self.apply_mix(
                x, lax.dynamic_index_in_dim(pend, slot, 0, keepdims=False))
            d, c = self.begin_mix(x, c, flags_t, alive_t)
            pend = lax.dynamic_update_index_in_dim(pend, d, slot, 0)
            return (x, c, pend, t + 1), None

        t0 = jnp.zeros((), jnp.int32)
        if alive is None or alive.ndim == 1:
            a = alive  # None or constant row: closed over, not scanned

            def body_const(state, flags_t):
                return body(state, (flags_t, a))

            (x, c, ring, t), _ = lax.scan(
                body_const, (flat, carry, ring, t0), flags)
        else:
            (x, c, ring, t), _ = lax.scan(
                body, (flat, carry, ring, t0), (flags, alive))
        if not drain:
            return x, c, ring
        # flush oldest-first: after T steps slot (T+i) mod K holds the
        # delta issued at step T−K+i — issue order is the apply order
        for i in range(k):
            slot = lax.rem(t + i, k)
            x = self.apply_mix(
                x, lax.dynamic_index_in_dim(ring, slot, 0, keepdims=False))
        return x, c

    def run_elided(self, flat: jax.Array, flags: jax.Array,
                   local_every, carry: Any = None, alive: Any = None,
                   offset: int = 0):
        """Scan the chain with universal local-step elision (DESIGN.md §24)
        — the chain-level twin of the restructured epoch's scan body.

        Step *t* executes ``step`` only when ``(t + offset) % L == 0``; a
        thinned step takes the identity branch of a ``lax.cond`` and
        executes *nothing* — no mixing arithmetic, no exchange, no carry
        advance — instead of multiplying by the identity ``W`` a zeroed
        flag row builds.  ``local_every`` may be a python int or a traced
        ``i32[]`` (the hot-swappable ``serve.ControlKnobs`` knob): the
        predicate is a traced value either way, so one compiled program
        serves every cadence.  Equivalence contract (pinned by
        ``tests/test_overlap.py``): on a flag stream whose thinned rows
        are zero, ``run_elided == run`` on every backend — an all-zero row
        is identity mixing, so skipping it is exact (up to the carry of a
        *compressing* communicator, which no longer pays quantization on
        steps that exchange nothing — local steps mean no wire touch at
        all).  ``offset`` aligns the cursor mid-stream (an epoch slice
        starting at global step s passes ``offset=s``)."""
        import jax.numpy as jnp
        from jax import lax

        if carry is None:
            carry = self.init(flat)
        flags = jnp.asarray(flags, jnp.float32)
        if flags.shape[0] == 0:
            return flat, carry
        every = jnp.maximum(jnp.asarray(local_every, jnp.int32), 1)
        if alive is not None:
            alive = jnp.asarray(alive, jnp.float32)

        def body(state, xs):
            x, c, t = state
            flags_t, alive_t = xs

            def mix(xx, cc):
                if alive_t is None:
                    return self.step(xx, cc, flags_t)
                return self.step(xx, cc, flags_t, alive_t)

            x, c = lax.cond(lax.rem(t, every) == 0, mix,
                            lambda xx, cc: (xx, cc), x, c)
            return (x, c, t + 1), None

        t0 = jnp.asarray(int(offset), jnp.int32)
        if alive is None or alive.ndim == 1:
            a = alive  # None or constant row: closed over, not scanned

            def body_const(state, flags_t):
                return body(state, (flags_t, a))

            (x, c, _), _ = lax.scan(body_const, (flat, carry, t0), flags)
            return x, c
        (x, c, _), _ = lax.scan(body, (flat, carry, t0), (flags, alive))
        return x, c

    def run(self, flat: jax.Array, flags: jax.Array, carry: Any = None,
            alive: Any = None):
        """Scan the communicator over a whole flag stream (consensus-only runs,
        tests, and the comm-split timer).

        ``alive``: optional survivor mask — ``f32[N]`` (held constant for
        the chain) or ``f32[T, N]`` (per-step, scanned alongside the flags).
        Masked chains take the per-step scan — ``multi_step`` takes no
        mask, so bypassing it is a correctness requirement, not a missing
        optimization."""
        import jax.numpy as jnp
        from jax import lax

        if carry is None:
            carry = self.init(flat)

        flags = jnp.asarray(flags, jnp.float32)
        if flags.shape[0] == 0:  # empty stream: identity
            return flat, carry

        if alive is None:
            if self.multi_step is not None:
                return self.multi_step(flat, carry, flags)

            def body(state, flags_t):
                x, c = state
                x, c = self.step(x, c, flags_t)
                return (x, c), None

            (x, c), _ = lax.scan(body, (flat, carry), flags)
            return x, c

        alive = jnp.asarray(alive, jnp.float32)
        if alive.ndim == 1:
            def body_const(state, flags_t):
                x, c = state
                x, c = self.step(x, c, flags_t, alive)
                return (x, c), None

            (x, c), _ = lax.scan(body_const, (flat, carry), flags)
            return x, c

        def body_pair(state, fa):
            x, c = state
            flags_t, alive_t = fa
            x, c = self.step(x, c, flags_t, alive_t)
            return (x, c), None

        (x, c), _ = lax.scan(body_pair, (flat, carry), (flags, alive))
        return x, c
