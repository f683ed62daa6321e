"""Train state and the fused (SGD + gossip) step.

TPU-native re-design of the reference's inner loop
(/root/reference/train_mpi.py:109-145): forward/backward/SGD run *per virtual
worker* via ``vmap`` over the leading worker axis, then the communicator's
consensus transform runs on the flattened parameter stack — all inside one
jit-compiled function, so XLA fuses gossip permutes with the update math and
the whole step executes without host round-trips.

Reference-semantics notes:
* BatchNorm running statistics are per-worker state and are **not** gossiped —
  the reference averages only ``model.parameters()`` (communicator.py:21-22),
  and buffers are not parameters (SURVEY.md §7 BN note).
* The optimizer is torch-style SGD: weight decay added to the gradient before
  the momentum buffer, Nesterov lookahead, per-iteration LR schedule
  (train_mpi.py:87-92, 131).
* Workers start from an AllReduce average of their independent inits
  (train_mpi.py:97 ``sync_allreduce``).
"""

from __future__ import annotations

from typing import Any, Callable, Optional

import jax
import jax.numpy as jnp
import numpy as np
import optax
from flax import struct

from ..communicator import Communicator
from ..obs.telemetry import telemetry_step
from ..ops import WorkerFlattener
from ..parallel import (allreduce_mean, leaf_views, worker_deviation_rows,
                        worker_disagreement, worker_square_rows)
from ..utils import cross_entropy_loss, device_span, top_k_accuracy

__all__ = ["TrainState", "init_train_state", "make_train_step", "make_eval_fn", "make_optimizer",
           "fwd_bwd_plan", "exchange_plan", "COUNTER_PREFIX"]

#: a step metric under this prefix is a count, not a mean: the loop sums it
#: over the epoch's steps and journals it in the period's ``counters``
COUNTER_PREFIX = "count/"

#: the minor dimension of a TPU vector register and of an HBM tile: channels
#: narrower than this leave lanes, and the bytes behind them, empty
LANES = 128


class TrainState(struct.PyTreeNode):
    params: Any  # pytree, leaves [N, ...]
    batch_stats: Any  # pytree, leaves [N, ...] (possibly empty dict)
    opt_state: Any
    comm_carry: Any
    step: jax.Array  # scalar int32 — also the schedule cursor (ckpt-critical)
    # in-flight mixing delta(s) of the overlapped pipeline (DESIGN.md §11,
    # §20): f32[N, D] at overlap="1step" with staleness 1 (the exchange
    # issued at step t−1, consumed at step t), a f32[N, K, D] pending RING
    # at staleness K ≥ 2 (slot t mod K holds the exchange issued at step
    # t−K; deltas age K steps before they are consumed), the empty tuple
    # when off — the eager path's pytree and checkpoints are unchanged.
    # Worker-major on purpose — every state leaf is, which is what lets
    # mask_worker_rows / shard_workers / state_finite_rows treat the ring
    # like any other per-worker slab (the chain-level
    # ``Communicator.run_pipelined`` uses the scan-natural [K, N, D]).
    # Part of the state on purpose: the pipeline survives epoch boundaries
    # and checkpoint/resume without a re-prime.
    mix_pending: Any = ()
    # per-worker, per-slot age counters of the pending ring (DESIGN.md
    # §20): i32[N, K] when staleness ≥ 2, the empty tuple otherwise.
    # Traced values riding the state — heal/leave events mark a worker's
    # slots empty (−1) without any shape change, and the telemetry
    # consumed-age histogram reads them — NEVER checkpointed (checkpoint.py
    # strips them like telemetry; resume rebuilds ages from the step
    # cursor's ring arithmetic).
    mix_ages: Any = ()
    # device-side step telemetry (DESIGN.md §14): an ``obs.Telemetry``
    # scalar pytree when observability is on, the empty tuple when off.
    # Carried in the state so the scanned epoch accumulates it without any
    # host round-trip; the loop reads it exactly once per epoch (at the
    # boundary that already synchronizes) and resets it.  Never
    # checkpointed: the loop strips it to ``()`` around save/restore, so
    # checkpoint pytrees are identical with telemetry on or off (and
    # pre-obs checkpoints restore unchanged).
    telemetry: Any = ()
    # elastic membership (DESIGN.md §16): an ``elastic.Membership`` pytree
    # (``alive: f32[N_pool]`` + ``alpha_scale`` scalar) when a membership
    # trace drives the run, the empty tuple otherwise.  A *step input* on
    # purpose: membership changes are value updates at epoch boundaries,
    # never shape changes, so the compiled epoch program is reused verbatim
    # across join/leave/rejoin (the no-retrace contract the §14 watch
    # enforces).  Like telemetry it is reconstructible host state
    # (checkpoints carry a membership sidecar instead) and is stripped to
    # ``()`` around save/restore — checkpoint pytrees never change.
    membership: Any = ()
    # run-controller knobs (DESIGN.md §22): a ``serve.ControlKnobs`` pytree
    # (``row_scale: f32[M]`` per-matching activation re-weight,
    # ``alpha_scale`` scalar, ``local_every`` i32 scalar gossip thinning)
    # when a controller supervises the run, the empty tuple otherwise.  A
    # *step input* exactly like membership: every hot-swap a control
    # document asks for (budget re-solve, α re-derivation, local-step
    # cadence) is a value update on these arrays at an epoch boundary —
    # shapes never change, so the compiled epoch program survives every
    # swap (the zero-retrace contract the §14 watch enforces).  Host-
    # reconstructible from the journaled control events; stripped to ``()``
    # around save/restore so checkpoint pytrees never change.
    control: Any = ()


def make_optimizer(
    lr_schedule: Callable,
    momentum: float = 0.9,
    weight_decay: float = 5e-4,
    nesterov: bool = True,
) -> optax.GradientTransformation:
    """torch.optim.SGD(momentum, weight_decay, nesterov) equivalent
    (train_mpi.py:87-92): wd folds into the gradient before the momentum trace."""
    return optax.chain(
        optax.add_decayed_weights(weight_decay),
        optax.sgd(lr_schedule, momentum=momentum, nesterov=nesterov),
    )


def init_train_state(
    model,
    input_shape,
    num_workers: int,
    optimizer: optax.GradientTransformation,
    communicator: Communicator,
    seed: int = 0,
    sync_init: bool = True,
    overlap: str = "off",
    staleness: int = 1,
) -> tuple[TrainState, WorkerFlattener]:
    """Per-worker independent inits (torch per-rank ``seed+rank``,
    train_mpi.py:61) followed by the reference's initial AllReduce sync.

    ``overlap="1step"`` primes ``mix_pending`` with the zero delta the
    pipelined step consumes at step 0; ``staleness=K ≥ 2`` primes the
    ``[N, K, D]`` pending ring plus its all-empty (−1) age counters;
    ``"off"`` leaves both the empty tuple so the eager state pytree (and
    its checkpoints) are unchanged."""
    # a model of hundreds of millions of parameters names its own dummy
    # input (``models/mellum2.py``), and inits and syncs as two programs:
    # run an operation at a time, each distinct leaf shape compiles its own
    # draw, slice and cast, 363 s on the v5e for 2 x 267 M parameters
    # (PERF.md section 6, PR 27).  The image models keep the eager path and
    # its numbers to the bit.
    own_input = hasattr(model, "dummy_input")
    compiled = jax.jit if own_input else (lambda f: f)
    dummy = (model.dummy_input(input_shape) if own_input
             else jnp.zeros((1,) + tuple(input_shape), jnp.float32))

    def init_one(key):
        variables = model.init(key, dummy, train=False)
        return variables.get("params"), variables.get("batch_stats", {})

    keys = jax.random.split(jax.random.PRNGKey(seed), num_workers)
    params, batch_stats = compiled(jax.vmap(init_one))(keys)

    flattener = WorkerFlattener(params)
    if sync_init:
        params = compiled(lambda p: flattener.unflatten(
            allreduce_mean(flattener.flatten(p))))(params)

    if staleness < 1:
        raise ValueError(f"staleness must be >= 1, got {staleness}")
    ring_on = overlap == "1step" and staleness > 1
    state = TrainState(
        params=params,
        batch_stats=batch_stats,
        opt_state=optimizer.init(params),
        comm_carry=communicator.init(flattener.flatten(params)),
        step=jnp.zeros((), jnp.int32),
        mix_pending=(
            jnp.zeros((num_workers, staleness, flattener.dim), jnp.float32)
            if ring_on
            else jnp.zeros((num_workers, flattener.dim), jnp.float32)
            if overlap == "1step" else ()),
        mix_ages=(jnp.full((num_workers, staleness), -1, jnp.int32)
                  if ring_on else ()),
    )
    return state, flattener


def fwd_bwd_plan(model, num_workers: int, grad_chunk: Optional[int] = None, *,
                 dropout: bool = False, faults: bool = False,
                 elastic: bool = False, worker_shards: int = 1) -> dict:
    """How ``make_train_step`` runs the forward/backward, and why: the
    payload of the journal's ``fwd_bwd`` event.

    Packed (``models/resnet.py:ResNet.packed_apply``): ``workers_per_pack``
    workers share one network that many times as wide, so a narrow model's
    channels fill the lanes; the ``packs_per_slab`` packs of a slab run one
    after another.  P is the largest count with ``P x model.pack_width <=
    LANES`` that divides the slab (``grad_chunk``, or all N).  Taken only
    where nothing else is asked of the per-worker path: packing gives up
    the isolation of workers (a zero block times a non-finite activation is
    NaN, so one worker's overflow reaches its pack), which quarantine and
    heal rely on; a model that already fills the lanes would pay P times
    the FLOPs for nothing; and a loop over packs cannot run over a worker
    axis that a mesh shards (``worker_shards`` devices).  ``reason`` names
    the first condition that keeps ``vmap`` over workers.  ``remat_keeps``,
    where the model has one: what its inner checkpoints keep by name for the
    backward pass (``models/qwen3_next.py``).
    """
    slab = grad_chunk or num_workers
    plan = {"packed": False, "workers_per_pack": 1, "packs_per_slab": slab}
    keeps = getattr(model, "remat_keeps", ())
    if keeps:
        plan["remat_keeps"] = list(keeps)
    width = getattr(model, "pack_width", None)
    if width is None:
        return {**plan, "reason": f"{type(model).__name__} has no packed form"}
    for blocked, reason in (
            (getattr(model, "remat", False), "remat"),
            (dropout, "dropout"),
            (faults, "a fault plan needs workers isolated from each other"),
            (elastic, "elastic membership needs workers isolated from each other"),
            (worker_shards > 1,
             f"the worker axis is sharded over {worker_shards} devices")):
        if blocked:
            return {**plan, "reason": reason}
    workers = next((p for p in range(LANES // width, 1, -1) if slab % p == 0), 1)
    if workers < 2:
        return {**plan, "reason": f"no P >= 2 with P x {width} <= {LANES} "
                                  f"divides the slab of {slab}"}
    return {"packed": True, "workers_per_pack": workers,
            "packs_per_slab": slab // workers}


def exchange_plan(communicator: Communicator, flattener: WorkerFlattener, *,
                  overlap: str = "off", staleness: int = 1,
                  faults: bool = False, elastic: bool = False) -> dict:
    """Where ``make_train_step`` runs the exchange, and why: what the
    journal's ``backend`` event carries in its ``exchange`` record beside
    the form (``parallel.gossip.dense_exchange_form``).

    ``layout`` ``leaves``: the step hands the updated parameter tree to the
    communicator's ``leaves_step``; every leaf ``leaf_views`` takes
    (``leaves_in_place`` of them) is mixed where it lies, the others
    (``small_buffer_elements``, all workers') as one small flat vector, and
    the disagreement comes from the squares the same pass returns.
    ``kernel_sites`` counts the Pallas kernels the step's program holds for
    it: one a distinct leaf shape and one for the small buffer.  ``flat``:
    the step builds the ``[N, D]`` state, runs ``step`` on it and un-builds
    it.  Separated at the level of the step, and not adapted: the leaf form
    is the eager exchange of the dense decen communicator where that is the
    streamed pass, and whatever keeps rows of the flat state as its own data
    (the pending delta, the ring, CHOCO's carry, quarantine and heal) keeps
    the flat step; ``reason`` names the first such thing.  The two share the
    construction of ``W_t`` and nothing else.
    """
    n = flattener.num_workers
    # a flat step whose exchange is the streamed pass holds that one kernel
    plan = {"layout": "flat",
            "kernel_sites": int(communicator.leaves_step is not None),
            "leaves_in_place": 0, "small_buffer_elements": 0}
    for blocked, reason in (
            (communicator.leaves_step is None,
             communicator.leaves_refusal
             or f"communicator '{communicator.name}' carries flat state"),
            (staleness > 1,
             f"the staleness ring ages [N, {staleness}, D] deltas"),
            (overlap != "off", "overlap parks the exchange as an [N, D] delta"),
            (faults, "a fault plan heals and quarantines rows of the flat state"),
            (elastic, "elastic membership masks rows of the flat state")):
        if blocked:
            return {**plan, "reason": reason}
    views = leaf_views(n, flattener.shapes, flattener.dtypes)
    in_place = [(view[:2], size) for view, size in zip(views, flattener.sizes)
                if not isinstance(view, str)]
    if not in_place:
        return {**plan, "reason": "no leaf passes the shape rule"}
    small = n * (flattener.dim - sum(size for _, size in in_place))
    return {"layout": "leaves",
            "kernel_sites": len({rc for rc, _ in in_place}) + (small > 0),
            "leaves_in_place": len(in_place), "small_buffer_elements": small}


def make_train_step(
    model,
    optimizer: optax.GradientTransformation,
    communicator: Communicator,
    flattener: WorkerFlattener,
    flags: np.ndarray,
    dropout: bool = False,
    lr_schedule: Optional[Callable] = None,
    grad_chunk: Optional[int] = None,
    faults=None,
    overlap: str = "off",
    staleness: int = 1,
    stale_alpha_scale: float = 1.0,
    telemetry=None,
    elastic: bool = False,
    control: bool = False,
    local_steps: int = 1,
    worker_shards: int = 1,
):
    """Build ``step(state, xb, yb[, rng]) -> (state, metrics)``.

    ``xb: [N, B, ...]``, ``yb: int[N, B]``.  The activation-flag stream is a
    trace-time constant array indexed by ``state.step`` — the whole schedule
    compiles into the program (SURVEY.md §5.8) and survives checkpoint/resume
    through the step cursor.

    ``faults``: optional ``resilience.RuntimeFaults`` — compiled fault-plan
    arrays indexed by the same cursor, exactly like the flags.  When given,
    each step (a) poisons the planned NaN-emitter rows, (b) detects
    non-finite rows, quarantines them from gossip, and heals them (and
    planned revivals) from the survivors' average — momentum and CHOCO-carry
    rows of healed workers are reset, and their BatchNorm running statistics
    are replaced by the donors' average (poisoned/stale stats cannot be
    kept, and variance cannot be zero-reset), so a revived replica restarts
    clean — and (c) runs the consensus transform under the survivor mask, so
    every realized mixing matrix stays doubly stochastic over the alive
    workers.  Link faults
    are not handled here: the caller pre-multiplies ``flags`` by the plan's
    ``link_up`` stream (both are static, so outages compile away).  With
    ``faults=None`` the exact pre-resilience step compiles.

    ``grad_chunk``: workers whose forward/backward runs concurrently.  The
    default vmaps all N at once — peak activation memory scales with N·B,
    which over-allocates HBM when many virtual workers fold onto one chip
    (256 × batch 32 ResNet-20 exceeds a v5e — r4 finding).  A value
    ``c < N`` computes gradients in N/c sequential ``lax.map`` slabs instead;
    workers are independent until the consensus transform, so the result is
    identical (tested) — it only caps the live activation set at c·B images.

    ``overlap`` (``"off"``/``"1step"``): the software-pipelined schedule
    (DESIGN.md §11).  At ``"1step"`` each step first *consumes* the mixing
    delta issued at step t−1 (``state.mix_pending``, a pure add), then
    *issues* this step's exchange via ``communicator.begin_mix`` and parks
    the result for step t+1.  The collective then has no consumer inside the
    next step's forward/backward, so XLA can overlap ICI traffic with
    compute.  Semantics: the post-SGD params at step t are mixed by ``W_t``
    exactly as eagerly — only the *gradient update* of step t+1 joins the
    consensus one round late (the one-step-stale scheme of
    arXiv:1905.09435's analysis; contraction-factor effect modeled in
    ``plan.spectral.stale_contraction_rho``).  The worker mean is untouched:
    every delta has zero column-mean.  Requires ``state.mix_pending`` to be
    a ``zeros([N, D])`` (``train/loop.py`` primes it).

    ``staleness`` (K ≥ 1, with ``overlap="1step"``): the bounded-staleness
    contract consume-at-≤t+K (DESIGN.md §20).  K = 1 compiles the exact
    committed one-step path above; K ≥ 2 ages in-flight deltas through the
    static-shape ``[N, K, D]`` ring in ``state.mix_pending`` — step t
    applies slot ``t mod K`` (the exchange issued at t−K), then issues its
    own into that slot — with ``state.mix_ages`` (i32[N, K]) tracking each
    row's age as a traced value (−1 = empty: warmup, healed, or vacant).
    Every membership/heal transition is a value update; shapes never
    change, so the zero-retrace contract extends to the ring unchanged.
    ``stale_alpha_scale``: trace-time damping of the executed mixing
    weight for the delayed dynamics (``plan.spectral.stale_alpha_rescale``
    — the solved α overdrives under a deep pipeline); it scales the
    communicator's flag row exactly like elastic ``alpha_scale`` does, and
    composes with it.  Telemetry's flag accounting stays unscaled — the
    matchings still fire; only their weight is damped.

    ``telemetry``: optional ``obs.TelemetrySpec`` — when given *and* the
    incoming ``state.telemetry`` is a real ``obs.Telemetry`` pytree, each
    step folds its counters (disagreement, wire bytes at the configured
    dtype, activated matchings, alive count, heal/stale/quantize events)
    into it with a handful of fused scalar adds.  No host interaction
    whatsoever happens here — the loop reads the accumulator once per
    epoch (DESIGN.md §14).  ``None`` (or an empty ``state.telemetry``
    slot) compiles the exact pre-observability program.

    ``elastic``: when True *and* ``state.membership`` is a real
    ``elastic.Membership`` pytree, the step consumes the pool-occupancy
    mask and the α re-plan as **runtime inputs** (DESIGN.md §16): the
    alive mask multiplies into the gossip survivor mask (composing with
    any fault plan), ``alpha_scale`` multiplies the flag row so the
    epoch-boundary re-derived mixing weight executes without recompiling
    anything, vacant slots are frozen at their leave-time values (their
    computed updates are discarded by a ``where`` — a rejoin must find the
    state the worker left, not un-mixed solo-SGD drift), and fleet metrics
    / telemetry average over live members only.  Everything is value-level:
    join, leave, and rejoin never change a shape, which is the whole
    no-retrace contract the §14 watch enforces.  ``False`` (or an empty
    slot) compiles the exact pre-elastic program.

    ``control``: when True *and* ``state.control`` is a real
    ``serve.ControlKnobs`` pytree, the step multiplies the communicator's
    flag row by the controller's runtime re-weighting (DESIGN.md §22):
    ``row_scale[j]`` re-weights matching j's executed activation (a budget
    hot-swap rides the committed flag stream by scaling each row to the
    re-solved probabilities, first-moment-exact), ``alpha_scale`` executes
    a re-derived α exactly (the same α·flag_j algebra elastic uses — the
    two compose by multiplication), and ``local_every`` thins gossip to
    every k-th step.  All value updates at epoch boundaries, shapes pinned
    — the zero-retrace contract.  ``False`` (or an empty slot) compiles
    the exact pre-serve program.

    ``local_steps`` (L ≥ 1): universal local-step elision (DESIGN.md §24).
    When L > 1 — or whenever ``control`` is live (the traced
    ``local_every`` knob may be hot-swapped above 1 at any boundary) — the
    gossip call compiles inside a ``lax.cond`` keyed on the step cursor:
    thinned steps (``step % L != 0``) take the identity branch and
    *execute nothing* — no MXU ``W_t @ x``, no Pallas gathers, no wire
    bytes — instead of multiplying by an identity ``W``.  The predicate is
    a traced value (static L or the ``local_every`` knob), so hot-swaps
    never retrace, and at L = 1 with no controller the cond is omitted
    entirely: the exact pre-elision program compiles bitwise.  Overlap
    semantics are preserved: ``apply_mix``/ring consumption stay
    unconditional (a thinned step parks a zero delta, so the consume is a
    no-op add exactly as the zero-weight path produced), only the *issue*
    — the expensive exchange — is elided.

    ``worker_shards``: devices the worker axis is sharded over (the mesh's
    size; 1 on one chip).  Read by ``fwd_bwd_plan`` alone.
    """
    flags_arr = jnp.asarray(np.asarray(flags), jnp.float32)  # [T, M]
    n_workers = flattener.num_workers
    if overlap not in ("off", "1step"):
        raise ValueError(f"overlap must be 'off' or '1step', got {overlap!r}")
    overlap_on = overlap == "1step"
    staleness = int(staleness)
    if staleness < 1:
        raise ValueError(f"staleness must be >= 1, got {staleness}")
    if staleness > 1 and not overlap_on:
        raise ValueError("staleness > 1 needs overlap='1step': the eager "
                         "path has no pending ring to age deltas through")
    ring_on = overlap_on and staleness > 1
    if not stale_alpha_scale > 0:
        raise ValueError(f"stale_alpha_scale must be > 0, got "
                         f"{stale_alpha_scale}")
    local_steps = int(local_steps)
    if local_steps < 1:
        raise ValueError(f"local_steps must be >= 1, got {local_steps}")
    # universal local-step elision (DESIGN.md §24): the gossip issue is
    # wrapped in a lax.cond whenever thinned steps can exist — statically
    # (local_steps > 1) or dynamically (a live controller may hot-swap
    # local_every above 1).  L = 1 without a controller compiles the exact
    # pre-elision program: no cond, bitwise unchanged.
    elide = control or local_steps > 1
    # the α damping is a trace-time constant scale on the communicator's
    # flag row (every backend's edge weight is α·flag_j); telemetry keeps
    # reading the unscaled flags_arr — the schedule still fires
    comm_flags_arr = (flags_arr * np.float32(stale_alpha_scale)
                      if stale_alpha_scale != 1.0 else flags_arr)
    if faults is not None:
        if faults.alive.shape != (flags_arr.shape[0], n_workers):
            raise ValueError(
                f"fault arrays {faults.alive.shape} do not match "
                f"(iterations={flags_arr.shape[0]}, workers={n_workers}); "
                f"compile the FaultPlan against this schedule")
        alive_arr = jnp.asarray(faults.alive, jnp.float32)      # [T, N]
        revive_arr = jnp.asarray(faults.revive, jnp.float32)    # [T, N]
        inject_arr = jnp.asarray(faults.nan_inject, jnp.float32)
    if grad_chunk is not None and not (1 <= grad_chunk <= n_workers):
        raise ValueError(f"grad_chunk {grad_chunk} must be in [1, {n_workers}]")
    if grad_chunk is not None and n_workers % grad_chunk:
        raise ValueError(
            f"grad_chunk {grad_chunk} must divide num_workers {n_workers}")

    # a model that supplies its loss (``models/mellum2.py``) is handed the
    # raw batch, and returns the loss with its accuracy and counters; the
    # label-a-row models below get cross-entropy over their logits
    own_loss = getattr(model, "supplies_loss", False)
    if own_loss and grad_chunk not in (None, 1):
        raise ValueError(
            f"grad_chunk {grad_chunk}: a model that supplies its loss runs "
            f"its workers one after another (grad_chunk 1 or unset)")

    def loss_fn(params, batch_stats, x, y, rng):
        if own_loss:
            loss, aux = model.apply({"params": params}, x, y,
                                    method="batch_loss")
            return loss, (batch_stats, aux)
        variables = {"params": params}
        if batch_stats:
            variables["batch_stats"] = batch_stats
        rngs = {"dropout": rng} if dropout else None
        out = model.apply(variables, x, train=True,
                          mutable=["batch_stats"] if batch_stats else [], rngs=rngs)
        logits, mutated = out if isinstance(out, tuple) else (out, {})
        loss = cross_entropy_loss(logits, y)
        return loss, (mutated.get("batch_stats", {}), logits)

    grad_fn = jax.value_and_grad(loss_fn, has_aux=True)

    def pack_grad_fn(params, batch_stats, x, y):
        # one pack: leaves [P, ...].  A worker's loss reads its own
        # parameters alone, so the gradient of the sum is every worker's
        # own gradient
        def summed(params):
            logits, stats = model.packed_apply(params, batch_stats, x)
            loss = cross_entropy_loss(logits, y)  # [P]
            return jnp.sum(loss), (loss, stats, logits)

        (_, (loss, stats, logits)), grads = jax.value_and_grad(
            summed, has_aux=True)(params)
        return (loss, (stats, logits)), grads

    plan = fwd_bwd_plan(model, n_workers, grad_chunk, dropout=dropout,
                        faults=faults is not None, elastic=elastic,
                        worker_shards=worker_shards)

    def packed_slab_grads(params, batch_stats, xb, yb, rngs):
        del rngs  # no dropout on this path
        per_pack, packs = plan["workers_per_pack"], plan["packs_per_slab"]
        # one pack after another: under ``vmap`` the packs' convolutions
        # become one grouped convolution, and cell 2's step took 105 ms
        # against 86 (PERF.md section 6, PR 30)
        out = jax.lax.map(lambda pack: pack_grad_fn(*pack), jax.tree.map(
            lambda a: a.reshape((packs, per_pack) + a.shape[1:]),
            (params, batch_stats, xb, yb)))
        return jax.tree.map(
            lambda a: a.reshape((packs * per_pack,) + a.shape[2:]), out)

    slab_grads = packed_slab_grads if plan["packed"] else jax.vmap(grad_fn)

    on_leaves = exchange_plan(
        communicator, flattener, overlap=overlap, staleness=staleness,
        faults=faults is not None, elastic=elastic)["layout"] == "leaves"

    def all_grads(params, batch_stats, xb, yb, rngs):
        if own_loss:
            # one worker after another, and no vmap: under one, a ``cond``
            # on the worker's own data would run both of its branches
            return jax.lax.map(lambda worker: grad_fn(*worker),
                               (params, batch_stats, xb, yb, rngs))
        if grad_chunk is None or grad_chunk == n_workers:
            return slab_grads(params, batch_stats, xb, yb, rngs)
        slabs = n_workers // grad_chunk
        split = lambda tree: jax.tree.map(
            lambda a: a.reshape((slabs, grad_chunk) + a.shape[1:]), tree)
        out = jax.lax.map(
            lambda slab: slab_grads(*slab),
            tuple(split(t) for t in (params, batch_stats, xb, yb, rngs)),
        )
        return jax.tree.map(
            lambda a: a.reshape((n_workers,) + a.shape[2:]), out)

    @jax.jit
    def step(state: TrainState, xb, yb, rng=None):
        n = n_workers
        if rng is None:
            rng = jax.random.PRNGKey(0)
        rngs = jax.random.split(jax.random.fold_in(rng, state.step), n)

        # device_span scopes: phase names ride the op metadata into the
        # profiler (utils.profiling) — XLA fuses across these boundaries,
        # so named scopes, not wall-clock brackets, are how the comp/comm
        # split stays attributable (DESIGN.md §14)
        with device_span("matcha/fwd_bwd"):
            (loss, (new_stats, outputs)), grads = all_grads(
                state.params, state.batch_stats, xb, yb, rngs
            )

        with device_span("matcha/sgd"):
            updates, opt_state = optimizer.update(grads, state.opt_state,
                                                  state.params)
            params = optax.apply_updates(state.params, updates)

        # consensus transform on the flattened parameter stack, or, where
        # the plan says so, on the leaves where they lie (no flat copy)
        flat = None if on_leaves else flattener.flatten(params)
        t = jnp.minimum(state.step, flags_arr.shape[0] - 1)
        comm_carry = state.comm_carry
        mix_pending = state.mix_pending
        mix_ages = state.mix_ages
        ring_dropped = jnp.zeros((), jnp.float32)
        # elastic membership (DESIGN.md §16): the pool mask and the α
        # re-plan arrive as runtime values riding the state — the same
        # compiled program serves every live set.  Every backend's per-step
        # edge weight is α·flag_j, so scaling the flag row by α′/α executes
        # the re-derived α′ exactly, on dense/gather/skip/folded alike.
        member = None
        comm_flags_t = comm_flags_arr[t]
        if elastic and not isinstance(state.membership, tuple):
            member = state.membership.alive
            comm_flags_t = comm_flags_arr[t] * state.membership.alpha_scale
        # run-controller knobs (DESIGN.md §22): pure multiplicative
        # re-weighting of the flag row — per-matching row_scale (budget
        # re-solve) and α′/α (mixing-weight re-derivation).  Composes with
        # the elastic α scale above; shapes never change, so every hot-swap
        # reuses this compiled program verbatim.  The local-step cadence is
        # deliberately NOT a zero-weight multiply anymore: it decides the
        # traced `do_mix` predicate below, and thinned steps skip the
        # gossip computation entirely (universal elision, DESIGN.md §24).
        local_every_t = None
        if control and not isinstance(state.control, tuple):
            knobs = state.control
            comm_flags_t = comm_flags_t * knobs.row_scale * knobs.alpha_scale
            local_every_t = jnp.maximum(knobs.local_every, 1)
        elif elide:
            local_every_t = jnp.asarray(np.int32(local_steps))
        do_mix = None
        if local_every_t is not None:
            do_mix = jax.lax.rem(state.step, local_every_t) == 0
        alive = None
        if faults is not None or member is not None:
            from ..resilience.runtime import (
                begin_mix_quarantined,
                gossip_quarantined,
                heal_and_mask,
                heal_worker_stat_rows,
                inject_nan_rows,
                mask_worker_rows,
            )

            with device_span("matcha/heal"):
                if faults is not None:
                    flat = inject_nan_rows(flat, inject_arr[t])
                    alive_t, revive_t = alive_arr[t], revive_arr[t]
                    if member is not None:
                        # compose: a vacant slot is dead regardless of the
                        # fault plan, and a planned revival of a vacant
                        # slot stays vacant (membership owns re-entry)
                        # graftlint: disable=GL001 — mask∘mask algebra on
                        # 0/1 plan arrays and the membership mask
                        alive_t = alive_t * member
                        revive_t = revive_t * member
                else:
                    alive_t = member
                    revive_t = jnp.zeros_like(member)
                flat, alive, healed, row_finite = heal_and_mask(
                    flat, alive_t, revive_t)
                keep = 1.0 - healed
                opt_state = mask_worker_rows(opt_state, keep, n)
                comm_carry = mask_worker_rows(comm_carry, keep, n)
                if overlap_on:
                    # a healed worker restarts from the survivors' average:
                    # the delta(s) issued from its pre-heal parameters are
                    # stale algorithm state like momentum, and are dropped
                    # with it — at staleness K the worker-major ring masks
                    # through the same call (its [N, K, D] rows ARE worker
                    # rows), with the slots marked empty and the real
                    # deltas dropped counted for telemetry
                    if ring_on:
                        gone = (mix_ages >= 0) & (keep[:, None] <= 0)
                        ring_dropped = ring_dropped + jnp.sum(
                            gone.astype(jnp.float32))
                        mix_ages = jnp.where(keep[:, None] > 0, mix_ages, -1)
                    mix_pending = mask_worker_rows(mix_pending, keep, n)
                # BN running stats can be neither kept (poisoned/stale) nor
                # zero-reset (variance 0 is not neutral): the healed worker
                # adopts the donors' statistics along with their parameters
                new_stats = heal_worker_stat_rows(new_stats, healed,
                                                  alive * keep, n)
        consumed_age = None
        if ring_on:
            # bounded staleness (DESIGN.md §20): consume ring slot t mod K
            # — the exchange issued at step t−K (zero through the K-step
            # warmup) — then issue this step's exchange into the same
            # slot.  The issued collectives have no consumer for K steps,
            # so XLA is free to run them under the next K
            # forward/backwards; ages are traced values, shapes never
            # change (the zero-retrace contract).
            slot = jax.lax.rem(state.step, jnp.int32(staleness))
            mix_ages = jnp.where(mix_ages >= 0, mix_ages + 1, mix_ages)
            consumed_age = jax.lax.dynamic_index_in_dim(
                mix_ages, slot, 1, keepdims=False)
            flat = communicator.apply_mix(
                flat, jax.lax.dynamic_index_in_dim(
                    mix_pending, slot, 1, keepdims=False))

            def _ring_issue(f, c):
                if alive is None:
                    d, c2 = communicator.begin_mix(f, c, comm_flags_t)
                    return d, c2, jnp.zeros((n,), jnp.int32)
                d, c2 = begin_mix_quarantined(
                    communicator.begin_mix, f, c, comm_flags_t,
                    alive, gate=row_finite)
                # dead/non-finite rows issued nothing real (their delta
                # rows are zeroed above): their slot entries stay empty
                return d, c2, jnp.where((alive > 0) & (row_finite > 0),
                                        0, -1).astype(jnp.int32)

            if do_mix is None:
                delta, carry, issued = _ring_issue(flat, comm_carry)
            else:
                # elided step: park a zero delta with the slot marked
                # empty (−1) — the consume at t+K is then a no-op add,
                # exactly what the zero-weight issue used to park, but
                # without executing the exchange
                delta, carry, issued = jax.lax.cond(
                    do_mix, _ring_issue,
                    lambda f, c: (jnp.zeros_like(f), c,
                                  jnp.full((n,), -1, jnp.int32)),
                    flat, comm_carry)
            mix_pending = jax.lax.dynamic_update_index_in_dim(
                mix_pending, delta, slot, 1)
            mix_ages = jax.lax.dynamic_update_index_in_dim(
                mix_ages, issued, slot, 1)
        elif overlap_on:
            # pipelined: consume the exchange issued at step t−1 (a pure
            # add — zero delta at step 0), then issue this step's exchange;
            # its collectives have no consumer until step t+1's apply, so
            # they are free to run under the next forward/backward
            flat = communicator.apply_mix(flat, mix_pending)

            def _issue(f, c):
                if alive is None:
                    return communicator.begin_mix(f, c, comm_flags_t)
                return begin_mix_quarantined(
                    communicator.begin_mix, f, c, comm_flags_t,
                    alive, gate=row_finite)

            if do_mix is None:
                mix_pending, carry = _issue(flat, comm_carry)
            else:
                # elided step: nothing goes in flight (zero pending), the
                # next step's apply is a no-op add — the consume side
                # stays unconditional so a real delta issued at a mix
                # step is still applied exactly one step later
                mix_pending, carry = jax.lax.cond(
                    do_mix, _issue,
                    lambda f, c: (jnp.zeros_like(f), c),
                    flat, comm_carry)
        else:
            def _eager_mix(f, c):
                if alive is None:
                    return communicator.step(f, c, comm_flags_t)
                return gossip_quarantined(
                    communicator.step, f, c, comm_flags_t, alive,
                    gate=row_finite)

            with device_span("comm/step"):
                if on_leaves:
                    def _leaves_mix(ls, c):
                        return communicator.leaves_step(ls, c, comm_flags_t)

                    leaves = flattener.treedef.flatten_up_to(params)
                    if do_mix is None:
                        leaves, carry, sq_rows = _leaves_mix(leaves, comm_carry)
                    else:
                        # a thinned step mixes nothing and still measures
                        leaves, carry, sq_rows = jax.lax.cond(
                            do_mix, _leaves_mix,
                            lambda ls, c: (ls, c, worker_square_rows(ls)),
                            leaves, comm_carry)
                elif do_mix is None:
                    flat, carry = _eager_mix(flat, comm_carry)
                else:
                    flat, carry = jax.lax.cond(
                        do_mix, _eager_mix, lambda f, c: (f, c),
                        flat, comm_carry)
        if on_leaves:
            params = flattener.treedef.unflatten(leaves)
            # what worker_disagreement and worker_deviation_rows reduce
            # from the flat state, from the squares the exchange's own pass
            # summed: sq_rows[i] = sum((x_i - mean_j x_j)^2)
            disagreement = jnp.sqrt(jnp.sum(sq_rows) / (n * flattener.dim))
            deviation_rows = jnp.sqrt(sq_rows / flattener.dim)
        else:
            params = flattener.unflatten(flat)
        if member is not None:
            # vacant slots are frozen at their leave-time values: the SPMD
            # program computed their updates (static shapes — it cannot
            # not), and this is where those updates are discarded.  A
            # rejoin must find the state the worker actually left; masked
            # gossip already self-loops these rows, so the freeze touches
            # only what SGD/BN wrote.
            from ..elastic.runtime import freeze_worker_rows

            params = freeze_worker_rows(params, state.params, member, n)
            new_stats = freeze_worker_rows(new_stats, state.batch_stats,
                                           member, n)
            opt_state = freeze_worker_rows(opt_state, state.opt_state,
                                           member, n)
            carry = freeze_worker_rows(carry, state.comm_carry, member, n)
            if overlap_on:
                # a vacant slot neither issues nor consumes mixing deltas —
                # zeroing every step also drops a leaver's stale in-flight
                # delta(s) the moment its slot vacates (at staleness K the
                # worker-major ring masks through the same call)
                if ring_on:
                    gone = (mix_ages >= 0) & (member[:, None] <= 0)
                    ring_dropped = ring_dropped + jnp.sum(
                        gone.astype(jnp.float32))
                    mix_ages = jnp.where(member[:, None] > 0, mix_ages, -1)
                mix_pending = mask_worker_rows(mix_pending, member, n)

        def _fleet_mean(v):
            """Mean over workers — quarantined rows excluded under faults.

            A plan-dead replica trains without consensus damping; its local
            loss may legitimately blow up while quarantined (it will be
            healed at revival).  Averaging it in would hand the divergence
            detector a NaN for a fleet that is healthy by the quarantine
            rules — the same exemption the full-state check applies.  NaN
            rows are excluded with ``where`` (0·NaN leaks).  A step with
            zero alive workers must not fabricate a perfect-looking 0.0:
            it falls back to the mean over the finite local values (the
            quarantined replicas are still computing), and to NaN — which
            the detector will see — only when nothing finite exists."""
            per_worker = v.reshape(v.shape[0], -1).mean(axis=1)
            if alive is None:
                return jnp.mean(per_worker)
            kept = jnp.where(alive > 0, per_worker, 0.0)
            fin = jnp.isfinite(per_worker).astype(per_worker.dtype)
            local = jnp.where(
                jnp.sum(fin) > 0,
                jnp.sum(jnp.where(fin > 0, per_worker, 0.0))
                / jnp.maximum(jnp.sum(fin), 1.0),
                jnp.nan)
            return jnp.where(jnp.sum(alive) > 0,
                             jnp.sum(kept) / jnp.maximum(jnp.sum(alive), 1.0),
                             local)

        metrics = {
            "loss": _fleet_mean(loss),
            "accuracy": _fleet_mean(outputs["accuracy"] if own_loss
                                    else top_k_accuracy(outputs, yb)),
            "disagreement": (disagreement if on_leaves
                             else worker_disagreement(flat, alive)),
            "lr": lr_schedule(state.step) if lr_schedule else jnp.asarray(0.0),
            "active_matchings": jnp.sum(flags_arr[t]),
        }
        if own_loss:
            # the model's counters, summed over the workers: the loop sums
            # them over the epoch's steps into the period's ``counters``
            metrics.update({COUNTER_PREFIX + k: jnp.sum(v, axis=0)
                            for k, v in outputs["counters"].items()})
        if faults is not None or member is not None:
            metrics["healed"] = jnp.sum(healed)
            metrics["alive_workers"] = jnp.sum(alive)
        new_tel = state.telemetry
        if telemetry is not None and not isinstance(state.telemetry, tuple):
            # pure scalar adds fused into the step — the structure check is
            # trace-time (the pytree shape is static), so a run without the
            # telemetry slot compiles the exact pre-observability program
            heal_count = metrics.get("healed")
            # wire accounting under elision: a thinned step exchanges
            # nothing, so its flag row counts zero bytes.  On the static
            # path the row is already zero (loop.py thins the stream);
            # the gate makes the traced local_every knob account the same
            tel_flags_t = flags_arr[t]
            if do_mix is not None:
                tel_flags_t = tel_flags_t * do_mix.astype(jnp.float32)
            new_tel = telemetry_step(
                state.telemetry, telemetry,
                disagreement=metrics["disagreement"],
                flags_t=tel_flags_t,
                alive_count=(metrics["alive_workers"]
                             if "alive_workers" in metrics
                             else jnp.asarray(np.float32(n))),
                healed=heal_count,
                # overlapped heal drops the healed rows' pending deltas;
                # the ring counts the actual (slot, worker) deltas zeroed
                stale_dropped=(ring_dropped if ring_on
                               else heal_count if overlap_on else None),
                # the consumed-age histogram (DESIGN.md §20): which age
                # each worker's consumed delta had this step
                consumed_age=consumed_age,
                # the health plane's attribution payload (DESIGN.md §17):
                # who participated this step, and each row's deviation
                # from consensus — fused adds like every other counter
                worker_alive=alive,
                worker_disagreement=(deviation_rows if on_leaves else
                                     worker_deviation_rows(flat, alive)),
            )
        return (
            state.replace(
                params=params,
                batch_stats=new_stats,
                opt_state=opt_state,
                comm_carry=carry,
                mix_pending=mix_pending if overlap_on else state.mix_pending,
                mix_ages=mix_ages if ring_on else state.mix_ages,
                telemetry=new_tel,
                step=state.step + 1,
            ),
            metrics,
        )

    return step


def make_eval_fn(model):
    """Build ``evaluate(params, batch_stats, x, y) -> (loss[N], acc[N])`` —
    every worker evaluates the full batch (matching the reference's
    every-rank-evaluates pattern, train_mpi.py:152, but in one vmap)."""

    if getattr(model, "supplies_loss", False):
        @jax.jit
        def evaluate_own(params, batch_stats, x, y):
            def one(worker):
                loss, aux = model.apply({"params": worker[0]}, x, y,
                                        method="batch_loss")
                return loss, aux["accuracy"]

            return jax.lax.map(one, (params, batch_stats))

        return evaluate_own

    @jax.jit
    def evaluate(params, batch_stats, x, y):
        def one(p, bs):
            variables = {"params": p}
            if bs:
                variables["batch_stats"] = bs
            logits = model.apply(variables, x, train=False)
            return cross_entropy_loss(logits, y), top_k_accuracy(logits, y)

        return jax.vmap(one)(params, batch_stats)

    return evaluate
