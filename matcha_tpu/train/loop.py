"""The training driver.

TPU-native twin of ``run(rank, size)`` (/root/reference/train_mpi.py:58-168):
builds topology → schedule → communicator → model → data → optimizer, syncs
initial replicas, then runs the epoch loop.  Differences by design:

* One SPMD program over N virtual workers (no MPI processes / barriers).
* The epoch's batches are scanned inside one compiled program
  (``scan_epoch=True``) so gossip never bounces to the host; a per-batch
  python loop is kept for debugging.
* comp/comm wall-clock split: XLA fuses compute and communication, so the
  reference's timer-around-sendrecv (train_mpi.py:138-143) cannot be
  reproduced literally.  Two-program split instead (SURVEY.md §5.1): each
  epoch's gossip chain is re-run in isolation (short sampled window, scaled)
  and its wall-clock is charged to ``comm_time``; ``comp_time`` is the
  remainder of the epoch.
"""

from __future__ import annotations

import contextlib
import dataclasses
import functools
import json
import os
import time
import warnings
from typing import Dict, List, Optional, Tuple

import jax
import jax.numpy as jnp
import numpy as np
from jax.sharding import NamedSharding, PartitionSpec

from ..communicator import select_communicator
from ..obs import CostLedger, DriftMonitor, Telemetry, compose_predicted_rho
from ..obs.telemetry import make_telemetry_spec, telemetry_flush
from ..utils import SpanRecorder, trace
from ..data import (
    WorkerBatches,
    load_npz,
    load_tokens,
    normalized_zero,
    partition_indices,
    photo_patches,
    synthetic_classification,
    synthetic_images,
    uci_digits,
)
from ..models import dataset_input_shape, select_model
from ..parallel import WORKER_AXIS, fold_dims, shard_workers, worker_mesh
from ..resilience.runtime import state_finite_rows
from ..schedule import Schedule, fixed_schedule, matcha_schedule
from ..topology import decompose, graph_size, make_graph, select_graph
from .checkpoint import restore_checkpoint, save_checkpoint
from .config import TrainConfig
from .lr import make_lr_schedule
from .recorder import Recorder
from .state import (COUNTER_PREFIX, TrainState, exchange_plan,
                    init_train_state, make_eval_fn, fwd_bwd_plan,
                    make_optimizer, make_train_step)

__all__ = ["build_schedule", "build_dataset", "train", "TrainResult",
           "TrainingDiverged"]


class TrainingDiverged(RuntimeError):
    """Raised when an epoch produces a non-finite loss or train state.

    The reference has no failure detection at all (SURVEY.md §5.3) — a NaN
    would silently propagate through gossip to every replica and surface as
    garbage accuracy many epochs later.  Detecting it at the epoch boundary
    costs a handful of reductions and names the epoch it happened in; the
    recorder is flushed first so the loss curve leading into the blow-up
    survives on disk.  The check covers the *whole* ``TrainState`` — an Inf
    living only in optimizer momentum is caught the epoch it appears, not an
    epoch later when it has already poisoned the parameters.

    With ``TrainConfig.max_recoveries > 0`` this exception is a last resort:
    the loop first rolls back to the last good state, backs the LR off, and
    re-derives α for the degraded link reliability (DESIGN.md §8) — it
    raises only after the retry budget is exhausted."""


def build_schedule(config: TrainConfig, iterations: int) -> Schedule:
    """Topology + schedule from config (train_mpi.py:69-75 equivalent)."""
    if config.graphid is not None:
        decomposed = select_graph(config.graphid)
        size = graph_size(config.graphid)
        if size != config.num_workers:
            raise ValueError(
                f"graphid {config.graphid} is a {size}-worker topology but "
                f"num_workers={config.num_workers}; set graphid=None to use a "
                f"generator topology of any size"
            )
    else:
        edges = make_graph(config.topology, config.num_workers, seed=config.seed)
        decomposed = decompose(edges, config.num_workers, seed=config.seed)
        size = config.num_workers

    if config.matcha:
        return matcha_schedule(
            decomposed, size, iterations, budget=config.budget, seed=config.seed
        )
    return fixed_schedule(
        decomposed, size, iterations, budget=config.budget,
        mode=config.fixed_mode, seed=config.seed,
    )


def build_dataset(config: TrainConfig):
    kwargs = config.dataset_kwargs or {}
    if config.dataset == "synthetic":
        return synthetic_classification(seed=config.seed, **kwargs)
    if config.dataset == "synthetic_image":
        return synthetic_images(seed=config.seed, **kwargs)
    if config.dataset == "digits":
        return uci_digits(seed=config.seed, **kwargs)
    if config.dataset == "photo_patches":
        return photo_patches(seed=config.seed, **kwargs)
    if config.datasetRoot is None:
        raise ValueError(
            f"dataset '{config.dataset}' needs datasetRoot pointing at an .npz "
            f"file (torchvision downloads are unavailable in this environment)"
        )
    if config.dataset == "tokens":
        return load_tokens(config.datasetRoot)
    return load_npz(config.datasetRoot, dataset=config.dataset)


class TrainResult:
    def __init__(self, state, recorder, schedule, history):
        self.state = state
        self.recorder = recorder
        self.schedule = schedule
        self.history = history  # list of per-epoch dicts


# graftcontract: root
def train(config: TrainConfig, resume_dir: Optional[str] = None,
          boundary_hook=None) -> TrainResult:
    # boundary_hook (DESIGN.md §22): the run controller's epoch-boundary
    # seam — called with a `_BoundarySeam` handle before each epoch's
    # membership/snapshot work.  Everything the hook can change is a
    # device-VALUE update (ControlKnobs riding TrainState, host-side drift
    # re-base, config fields the compiled program never traced), so a
    # supervised run compiles exactly the programs an unsupervised one
    # does — the zero-retrace contract extends to every hot-swap.
    if config.plan:
        # resolve the plan artifact's schedule choice (graph, budget, seed)
        # into the config before anything downstream reads those fields —
        # one path for CLI (--plan) and programmatic (TrainConfig(plan=...))
        from ..plan import apply_plan

        config = apply_plan(config)
    dataset = build_dataset(config)
    parts = partition_indices(
        len(dataset.x_train), config.num_workers, seed=config.seed,
        non_iid=config.non_iid, labels=dataset.y_train,
    )
    loader = WorkerBatches(
        dataset.x_train, dataset.y_train, parts, config.batch_size,
        seed=config.seed, augment=config.augment,
        pad_value=normalized_zero(config.dataset),
    )
    stacks = _HostStacks(loader, config.scan_chunk)  # kept for this call
    bpe = loader.batches_per_epoch
    total_steps = config.epochs * bpe

    schedule = build_schedule(config, total_steps + 1)

    # the *plan's* α — what the drift monitor predicts with.  alpha_override
    # executes a deliberately different α (the mis-plan chaos knob,
    # DESIGN.md §14): the prediction keeps the solved α, so the monitor
    # sees exactly the "planner claimed a contraction the runtime doesn't
    # deliver" discrepancy it exists to catch.
    plan_alpha = float(schedule.alpha)
    if config.alpha_override is not None:
        schedule = dataclasses.replace(
            schedule, alpha=float(config.alpha_override))

    # runtime fault plan (DESIGN.md §8): compiled against this schedule's
    # horizon into static alive/nan/link arrays, exactly like the flags.
    # Link outages fold into the flag stream right here — a severed link is
    # indistinguishable from its flag not firing, so the communicators need
    # no extra machinery for it (and it composes with any offline
    # `with_link_failures` thinning already baked into schedule.flags).
    faults = fault_plan = None
    if config.fault_plan is not None:
        from ..resilience import load_fault_plan

        fault_plan = load_fault_plan(config.fault_plan)
        faults = fault_plan.compile(schedule.iterations, config.num_workers,
                                    schedule.num_matchings)
    run_flags = (np.asarray(schedule.flags, np.float32) * faults.link_up
                 if faults is not None else schedule.flags)
    if config.local_steps > 1 and boundary_hook is None:
        # local SGD steps (DESIGN.md §20, §24): gossip fires only every
        # L-th step.  Static thinning of the flag stream keeps telemetry
        # and the comm-split timer honest (a zero row counts zero wire
        # bytes), and the step itself now *elides* thinned steps — the
        # gossip call compiles inside a lax.cond keyed on the step cursor
        # (make_train_step's local_steps), so dense stops executing the
        # identity mix instead of multiplying by it.
        # The schedule fingerprint stays the as-built stream: thinning is
        # config-derived, so a resume re-derives it identically.
        keep = (np.arange(len(run_flags)) % config.local_steps
                == 0).astype(np.float32)
        # graftlint: disable=GL001 — thinning 0/1 plan weights on host
        # numpy, same shape algebra as the link_up fold above
        run_flags = np.asarray(run_flags, np.float32) * keep[:, None]
        # (under a boundary_hook the static thinning is skipped: the
        # controller's traced `local_every` knob subsumes it — initialized
        # from config.local_steps below, hot-swappable at any boundary)
    # checkpoints always fingerprint the *as-built* schedule: recovery may
    # re-derive α (rebinding `schedule`), but no config could reproduce that
    # α at resume time — fingerprinting it would leave every post-recovery
    # checkpoint permanently unresumable.  A resumed run restarts at the
    # originally-solved α and re-derives again if faults recur; the flag
    # stream (what the cursor's meaning depends on) is identical either way.
    schedule0 = schedule

    # run-controller knobs (DESIGN.md §22): host mirror of the
    # serve.ControlKnobs pytree riding TrainState.control.  Identity
    # values (all-ones row scale, unit α scale, local_every from config)
    # make a supervised run numerically identical to an unsupervised one;
    # a control-doc apply just rewrites these host values and re-primes
    # the device copy at the next boundary — no program ever rebuilds.
    control_knobs: Optional[Dict] = None
    control_probs = None  # effective activation probs after a budget swap
    stop_requested = False
    if boundary_hook is not None:
        control_knobs = {
            "row_scale": np.ones(schedule.num_matchings, np.float32),
            "alpha_scale": 1.0,
            "local_every": max(int(config.local_steps), 1),
        }

    # elastic membership (DESIGN.md §16): the trace replays at epoch
    # boundaries through a deterministic host controller; the device sees
    # only the [N_pool] alive mask + α scale riding TrainState.membership.
    # Membership re-plans scale the *executed* α through the traced scalar,
    # so — unlike the recovery path's α re-derivation — nothing recompiles
    # and `schedule` itself is never rebound by a membership change.
    elastic_ctl = None
    membership_source = None
    if config.membership_live is not None:
        # the live half (DESIGN.md §17): membership events derived from
        # heartbeat liveness instead of a declaration — the controller and
        # everything downstream are identical (parity pinned by test)
        from ..elastic import LiveMembershipSource

        membership_source = LiveMembershipSource(
            config.membership_live, deadline=config.membership_deadline)
    elif config.membership_trace is not None:
        from ..elastic import load_membership_trace

        membership_source = load_membership_trace(config.membership_trace)
    if membership_source is not None:
        from ..elastic import ElasticController

        elastic_ctl = ElasticController(
            membership_source,
            config.num_workers,
            hysteresis=config.membership_hysteresis,
            bootstrap=config.membership_bootstrap,
        )

    # every visible device (or config.devices of them) carries workers; a
    # fold that cannot be built raises WorkerFoldError — putting the whole
    # fleet on the first chip of a multi-chip host is the caller's explicit
    # devices=1, never a fallback
    mesh = None
    if config.devices is None or config.devices > 1:
        mesh = worker_mesh(config.devices)
        if mesh.size == 1:
            mesh = None  # single chip: no worker axis to shard
        else:
            fold_dims(config.num_workers, mesh)
    worker_shards = 1 if mesh is None else mesh.size

    # gossip-backend resolution: resolve `auto` ONCE, here, and hand the
    # concrete backend to every _make_comm rebuild — the decision record is
    # journaled next to run_start (a `backend` event).  Non-decen
    # communicators have no gossip backend to resolve.
    backend_decision = None
    gossip_backend = config.gossip_backend
    if config.communicator == "decen":
        from ..communicator.decen import resolve_gossip_backend

        backend_decision = resolve_gossip_backend(
            schedule, mesh, requested=config.gossip_backend)
        gossip_backend = backend_decision["chosen"]

    def _make_comm(ratio: float):
        return select_communicator(
            config.communicator, schedule, mesh=mesh,
            ratio=ratio, consensus_lr=config.consensus_lr,
            backend=gossip_backend, compressor=config.compressor,
            seed=config.seed, wire_dtype=config.wire_dtype,
        )

    communicator = _make_comm(config.compress_ratio)

    model = select_model(config.model, config.dataset,
                         num_classes=dataset.num_classes, remat=config.remat,
                         **(config.model_kwargs or {}))

    # lr_scale is the recovery backoff (1.0 until a rollback); everything
    # LR-derived is built through here so retries rebuild consistently
    lr_scale = 1.0

    def _make_lr():
        return make_lr_schedule(
            config.lr * lr_scale, bpe, base_lr=config.base_lr * lr_scale,
            warmup=config.warmup, warmup_epochs=config.warmup_epochs,
            decay_epochs=config.decay_epochs,
            decay_factor=config.decay_factor,
        )

    lr_schedule = _make_lr()
    optimizer = make_optimizer(lr_schedule, config.momentum,
                               config.weight_decay, config.nesterov)

    input_shape = dataset.x_train.shape[1:]
    state, flattener = init_train_state(
        model, input_shape, config.num_workers, optimizer, communicator,
        seed=config.seed, overlap=config.overlap,
        staleness=config.staleness,
        sync_init=config.sync_init,
    )

    # bounded-staleness α damping (DESIGN.md §20): the MATCHA α is solved
    # for the eager dynamics and overdrives under a k-deep pipeline
    # (delayed overcompensation oscillates — ρ_eff > 1, MC-confirmed);
    # re-solve the damping scale against the delayed closed form and
    # execute it through the per-step flag row — the same value-level
    # seam as elastic alpha_scale, so the schedule, its fingerprint, and
    # every checkpoint stay untouched.  Recomputed by _build_programs on
    # every rebuild, so a recovery-path α re-derivation re-damps
    # consistently.  Only the decen communicator is modeled (the same
    # scope as the drift monitor); other communicators run undamped.
    def _stale_scale() -> float:
        if config.staleness > 1 and config.communicator == "decen":
            from ..plan.spectral import stale_alpha_rescale

            s, _ = stale_alpha_rescale(
                schedule.laplacians(), schedule.probs, float(schedule.alpha),
                staleness=config.staleness, local_steps=config.local_steps)
            return float(s)
        return 1.0

    stale_scale = _stale_scale()

    # in-graph telemetry (DESIGN.md §14): static per-matching exchange
    # accounting baked into the step; the accumulator rides TrainState and
    # is read once per epoch.  The "none" communicator moves nothing, so
    # its byte ledger is all-zero (matchings still count — the schedule
    # fires them, the wire just never sees them).
    tel_spec = None
    if config.telemetry:
        tel_dec = (schedule.decomposed if config.communicator != "none"
                   else [[] for _ in schedule.decomposed])
        tel_spec = make_telemetry_spec(
            tel_dec, flattener.dim, wire_dtype=config.wire_dtype,
            overlap=config.overlap, staleness=config.staleness)

    def _fresh_telemetry():
        """A new accumulator with the *state's* sharding: an unplaced
        zeros pytree next to mesh-replicated scalars would hand the jitted
        epoch a different input sharding and silently recompile it every
        epoch (the retrace watch caught exactly this).  Fresh buffers each
        time — the scanned epoch donates the state, so a reused template
        would be invalidated by the very epoch that consumed it."""
        tel = Telemetry.zeros(config.num_workers, config.staleness)
        return shard_workers(tel, mesh) if mesh is not None else tel

    def _fresh_membership():
        """Device image of the controller's (alive mask, α scale), rebuilt
        host-fresh every epoch with the same placement discipline as
        ``_fresh_telemetry``: the epoch program's input signature must be
        identical whether or not this boundary changed membership, or the
        change itself would recompile the step — the exact failure mode
        elastic membership exists to avoid."""
        from ..elastic.runtime import membership_arrays

        m = membership_arrays(elastic_ctl.alive_mask(),
                              elastic_ctl.alpha_scale)
        return shard_workers(m, mesh) if mesh is not None else m

    def _fresh_control():
        """Device image of the controller's knobs, rebuilt host-fresh at
        every boundary with the ``_fresh_telemetry`` placement discipline.
        Replicated — NOT ``shard_workers``: ``row_scale`` is ``[M]``
        (matchings, not workers), so worker-axis sharding would be a shape
        error on any real mesh."""
        from ..serve.runtime import control_arrays

        c = control_arrays(control_knobs["row_scale"],
                           control_knobs["alpha_scale"],
                           control_knobs["local_every"])
        if mesh is not None:
            c = jax.device_put(c, NamedSharding(mesh, PartitionSpec()))
        return c

    bootstrap_fn = None
    member_alive_np = None
    if elastic_ctl is not None:
        from ..elastic.runtime import make_bootstrap_fn

        bootstrap_fn = make_bootstrap_fn(flattener, config.num_workers)
        member_alive_np = elastic_ctl.alive_mask() > 0

    def _bootstrap_rows(state, joined, restored):
        """Jitted boundary surgery for (re)entering slots: donors are the
        continuing members — alive now, not themselves (re)entering."""
        alive = elastic_ctl.alive_mask()
        # graftlint: disable=GL001 — mask∘mask algebra on host 0/1 arrays
        donors = alive * (1.0 - joined) * (1.0 - restored)
        return bootstrap_fn(state, jnp.asarray(joined),
                            jnp.asarray(restored), jnp.asarray(donors))

    def _membership_sidecar():
        """What checkpoints record next to the state: who owns which pool
        slot (the row-mapping key for cross-occupancy restore) and the α
        re-plan in effect."""
        if elastic_ctl is None:
            return None
        return {"view": elastic_ctl.view.to_json(),
                "alpha": elastic_ctl.alpha,
                "rho": elastic_ctl.rho,
                "alpha_scale": elastic_ctl.alpha_scale}

    if tel_spec is not None:
        state = state.replace(telemetry=_fresh_telemetry())
    if elastic_ctl is not None:
        state = state.replace(membership=_fresh_membership())
    if mesh is not None:
        state = shard_workers(state, mesh)
    if control_knobs is not None:
        # after shard_workers: the [M] row_scale leaf must keep its
        # replicated placement (worker-axis sharding would reject it)
        state = state.replace(control=_fresh_control())

    def _make_step(comm):
        # reads `optimizer`, `lr_schedule`, `faults`, and `stale_scale` at
        # call time: the recovery path rebinds them (LR backoff, consumed
        # NaN events, re-damped α) and rebuilds, so retried epochs compile
        # against the updated program
        return make_train_step(
            model, optimizer, comm, flattener, run_flags,
            dropout=False, lr_schedule=lr_schedule,
            grad_chunk=config.grad_chunk, faults=faults,
            overlap=config.overlap, staleness=config.staleness,
            stale_alpha_scale=stale_scale, telemetry=tel_spec,
            elastic=elastic_ctl is not None,
            control=control_knobs is not None,
            local_steps=config.local_steps,
            worker_shards=worker_shards,
        )

    step_fn = None  # populated by _build_programs() below

    def _build_programs():
        """(Re)build every compiled program from the current locals —
        ``lr_scale``, ``schedule`` (possibly α-rederived), ``faults``
        (possibly with consumed NaN events).  One recipe for setup and for
        recovery retries, so the two can never drift apart.

        On comm_timer: the two-program comp/comm split (SURVEY.md §5.1)
        re-runs the epoch's gossip chain in isolation and charges its
        wall-clock to comm_time — XLA fuses gossip into the train step, so
        the reference's timer-around-sendrecv cannot bracket it.  Costs one
        extra gossip chain per epoch; measure_comm_split=False disables."""
        nonlocal lr_schedule, optimizer, communicator, step_fn, scan_step, \
            comm_timer, stale_scale
        stale_scale = _stale_scale()
        lr_schedule = _make_lr()
        optimizer = make_optimizer(lr_schedule, config.momentum,
                                   config.weight_decay, config.nesterov)
        communicator = _make_comm(config.compress_ratio)
        step_fn = _make_step(communicator)
        scan_step = _make_epoch_scan(step_fn) if config.scan_epoch else None
        comm_timer = (
            _make_comm_timer(communicator, flattener, ledger=cost_ledger)
            if config.measure_comm_split and config.communicator != "none"
            else None)
        _stages.clear()

    # CHOCO compression warmup: epochs < compress_warmup_epochs run at a
    # linearly ramped drop-ratio (0 at epoch 0 — dense-rate consensus while
    # replicas are far apart — reaching compress_ratio at the warmup edge).
    # Each distinct ratio is a different top-k size, i.e. a different static
    # shape, so each stage gets its own communicator + compiled step; the
    # {x̂, s} carry has ratio-independent shapes and flows across stages
    # unchanged.  After warmup the pre-built default-ratio programs run.
    def _effective_ratio(epoch: int) -> float:
        w = config.compress_warmup_epochs
        if not w or epoch >= w:
            return config.compress_ratio
        return config.compress_ratio * (epoch / w)

    _stages: Dict[float, tuple] = {}

    def _stage_fns(epoch: int):
        """(communicator, step_fn, scan_step, comm_timer) for this epoch."""
        ratio = _effective_ratio(epoch)
        if ratio == config.compress_ratio:
            return None  # default programs (built below, shared state)
        if ratio not in _stages:
            comm = _make_comm(ratio)
            sf = _make_step(comm)
            _stages[ratio] = (
                comm, sf,
                _make_epoch_scan(sf) if config.scan_epoch else None,
                _make_comm_timer(comm, flattener, ledger=cost_ledger)
                if config.measure_comm_split and config.communicator != "none"
                else None,
            )
        return _stages[ratio]

    start_epoch = 0
    if resume_dir is None:
        resume_dir = config.resume
    if resume_dir is not None:
        # --overlap / --staleness may differ from the run that wrote the
        # checkpoint, and orbax restores whatever mix_pending the
        # *checkpoint* holds only if the template has an array slot of the
        # saved SHAPE for it (a () template silently drops a saved delta —
        # verified against orbax directly; a wrong-shape probe fails the
        # restore).  Peek the checkpoint's own mix_pending shape ([N, D]
        # from a one-step run, [N, K', D] from a staleness ring, absent
        # from an eager run), restore through a probe of that shape, then
        # reconcile with this run's overlap/staleness contract.
        from .checkpoint import restore_with_fallback, saved_mix_pending_shape

        def _restore_template(step):
            probe_shape = saved_mix_pending_shape(resume_dir, epoch=step) \
                or (config.num_workers, flattener.dim)
            pend0 = jnp.zeros(probe_shape, jnp.float32)
            if mesh is not None:
                pend0 = shard_workers(pend0, mesh)  # match state's sharding
            return state.replace(mix_pending=pend0)

        # telemetry is never checkpointed (per-epoch scratch): the
        # save/restore pair strips it internally, and the caller's slot
        # passes through — re-primed fresh below either way (mix_ages
        # rides the same strip; the reconcile rebuilds it from the cursor).
        # The generation fallback ladder (DESIGN.md §23) replaces the bare
        # latest-step restore: a corrupted latest checkpoint quarantines
        # and falls back to the next-oldest instead of crash-looping the
        # supervisor's restart budget away; each quarantine is collected
        # here and journaled once the recorder exists below.
        recovery_notices = []
        state, last_epoch = restore_with_fallback(
            resume_dir, schedule=schedule, notices=recovery_notices,
            template_fn=_restore_template)
        start_epoch = last_epoch + 1
        state = _reconcile_mix_pending(state, config.overlap, communicator,
                                       flattener, config.num_workers,
                                       staleness=config.staleness)
        if elastic_ctl is not None:
            # reconstruct the controller state this boundary had (the trace
            # replays deterministically — byte-identical resume is pinned by
            # test), then map the restored rows onto the current occupancy:
            # a slot whose saved content belongs to a different worker (or
            # to nobody) bootstraps from the continuing members, which is
            # how one checkpoint restores onto a larger or smaller live set
            from .checkpoint import load_membership_sidecar

            if hasattr(membership_source, "seed_replay"):
                # a live source's poll cache died with the old process:
                # re-polling history against today's clock would diverge
                # from the run being resumed (a recovered host would
                # retro-actively never have left) — seed the cache from
                # the journal, its persisted copy.  A missing journal
                # (resume into a fresh savePath) replays live and lets
                # the sidecar reconcile + the next real poll converge.
                journal_path = os.path.join(
                    config.savePath, f"{config.name}_{config.model}",
                    "events.jsonl")
                if os.path.exists(journal_path):
                    from ..obs.journal import read_journal

                    membership_source.seed_replay(
                        read_journal(journal_path), start_epoch)
            elastic_ctl.replay_to(start_epoch, schedule)
            member_alive_np = elastic_ctl.alive_mask() > 0
            side = load_membership_sidecar(resume_dir, last_epoch)
            joined, restored = elastic_ctl.reconcile_restored(
                (side or {}).get("view"))
            if joined.any() or restored.any():
                state = _bootstrap_rows(state, joined, restored)
            state = state.replace(membership=_fresh_membership())
        if tel_spec is not None:
            state = state.replace(telemetry=_fresh_telemetry())
        if mesh is not None:  # reconcile may have created fresh zero rows
            if control_knobs is not None:
                # the setup path already primed the [M] knob leaf — drop
                # it before the worker-axis re-shard would reject it
                state = state.replace(control=())
            state = shard_workers(state, mesh)
        if control_knobs is not None:
            # checkpoints strip control (like telemetry); re-prime after
            # the shard so the [M] leaf keeps its replicated placement
            state = state.replace(control=_fresh_control())

    evaluate = make_eval_fn(model)
    recorder = Recorder(config, config.num_workers)
    # the loop's named host phases (utils.profiling.SPAN_NAMES), on the
    # journal's clock; one `spans` record an epoch period
    spans = SpanRecorder(origin=time.time() - recorder.start)
    # compiled-cost ledger (DESIGN.md §15): every distinct program this
    # loop runs is introspected once (.lower().compile().cost_analysis())
    # and journaled as a v2 `compile` event — FLOPs, boundary HBM bytes,
    # peak footprint, arg shardings, compile wall-time.  One extra AOT
    # compile per distinct program, gated with the rest of observability.
    cost_ledger = CostLedger(recorder.log_event) if config.telemetry else None
    # live health plane (DESIGN.md §17): one heartbeat per epoch to this
    # host's file under {run}/health/, plus the streaming anomaly
    # detectors over exactly those records.  Pure host code consuming
    # values already read at this boundary — needs save (a folder) and
    # telemetry (the per-worker stats ride the accumulator's one flush).
    health_emitter = anomaly_detector = None
    if config.health and config.save and config.telemetry:
        from ..obs.anomaly import AnomalyDetector
        from ..obs.health import HeartbeatEmitter

        health_emitter = HeartbeatEmitter(
            os.path.join(recorder.folder, "health"),
            host=f"host{jax.process_index()}")
        anomaly_detector = AnomalyDetector()

    def _member_workers(worker_stats):
        """Heartbeat payload: worker id → per-worker stats, member slots
        only (a vacant pool slot is nobody's worker — its frozen row's
        numbers would accuse a ghost)."""
        occupants = (elastic_ctl.view.occupants if elastic_ctl is not None
                     else [f"w{i}" for i in range(config.num_workers)])
        return {wid: {"slot": i,
                      "participation": worker_stats["worker_participation"][i],
                      "disagreement": worker_stats["worker_disagreement"][i]}
                for i, wid in enumerate(occupants) if wid is not None}
    if config.save and (start_epoch or (
            boundary_hook is not None
            and os.path.exists(recorder.journal.path))):
        # re-align the CSV series with the restored epoch: reload the
        # previous run's rows truncated to the checkpoint, so save() extends
        # the history instead of overwriting it (or double-appending the
        # replayed epochs on resume from an older checkpoint).  A
        # *supervised* run reloads the journal even at start_epoch 0: a
        # pre-first-checkpoint relaunch restarts training from scratch,
        # but the journal is the supervision record — wiping the previous
        # lifetime's control/promotion decisions would orphan the daemon's
        # own audit trail (unsupervised reruns into a reused folder keep
        # the historical rewrite semantics)
        recorder.load_previous(start_epoch)
    if resume_dir is not None:
        for n in recovery_notices:
            # the quarantine already happened during restore (before the
            # recorder existed) — journal it now so the move is on the
            # record: a quarantine nobody can read about is history
            # silently rewritten
            recorder.log_event("recovery", scope="checkpoint",
                               action="quarantine", reason=n["reason"],
                               epoch=n["step"], quarantined=n["path"])
    if fault_plan is not None:
        plan_events = fault_plan.to_json()["events"]
        already = any(e.get("kind") == "plan" and e.get("events") == plan_events
                      for e in recorder.faults)
        if not already:  # resume reloaded the ledger: don't duplicate it
            recorder.log_fault(
                "plan", name=fault_plan.name, events=plan_events,
                expected_alive=[float(v) for v in faults.expected_alive()],
                expected_link_up=[float(v) for v in faults.expected_link_up()],
            )

    # planner-drift monitor (DESIGN.md §14): the plan's full ρ composition
    # — solved α (NOT any override), staleness, wire quantization, fault
    # degradation — against the measured per-epoch contraction.  Only the
    # decen communicator is modeled by the spectral bound; CHOCO's γ-damped
    # consensus and the centralized AllReduce are out of its scope.
    def _compose_predicted():
        # worker availability composes multiplicatively: the fault plan's
        # expectation × the membership occupancy (a vacant slot is simply
        # dead to the mixing, whatever the fault plan thought of it)
        # graftcontract: sync — fault-plan availability expectations are
        # pure host numpy (no device value can reach this composition)
        fault_alive = (np.asarray(faults.expected_alive(), np.float64)
                       if faults is not None else None)
        # graftcontract: sync — controller occupancy mask, host-side state
        member_alive = (np.asarray(elastic_ctl.alive_mask(), np.float64)
                        if elastic_ctl is not None else None)
        if fault_alive is None:
            worker_alive = member_alive
        elif member_alive is None:
            worker_alive = fault_alive
        else:
            worker_alive = fault_alive * member_alive
        pred = compose_predicted_rho(
            # the plan in force is the staleness-damped α: the executor
            # scales the flag row by stale_scale, so the monitor must
            # predict the contraction of the mixing that actually runs
            schedule.laplacians(),
            # a controller budget swap re-weights the committed flag stream
            # to new effective activation probabilities (first-moment exact;
            # serve.control): the monitor must predict the mixing that runs
            (schedule.probs if control_probs is None else control_probs),
            plan_alpha * stale_scale,
            overlap=config.overlap, wire_dtype=config.wire_dtype,
            worker_alive=worker_alive,
            # graftcontract: sync — host fault-plan link expectation
            link_up=(np.asarray(faults.expected_link_up(), np.float64)
                     if faults is not None else None),
            staleness=config.staleness, local_steps=config.local_steps,
        )
        pred.update(steps_per_epoch=int(bpe),
                    tolerance=float(config.drift_tolerance),
                    patience=int(config.drift_patience),
                    plan_alpha=float(plan_alpha),
                    stale_alpha_scale=float(stale_scale),
                    executed_alpha=float(schedule.alpha) * float(stale_scale))
        return pred

    predicted = None
    drift_monitor = None
    if elastic_ctl is not None and elastic_ctl.alpha is not None:
        # a resumed run replayed membership re-plans above: the plan in
        # force is the re-folded α, not the schedule-built one
        plan_alpha = float(elastic_ctl.alpha)
    if config.telemetry and config.communicator == "decen":
        predicted = _compose_predicted()
        drift_monitor = DriftMonitor(
            predicted["rho"], int(bpe), tolerance=config.drift_tolerance,
            patience=config.drift_patience)
    # the run-lifecycle events ride the journal unconditionally — the
    # journal is the Recorder's record of the run (it subsumes the fault
    # ledger); config.telemetry gates only the in-graph accumulator, the
    # drift monitor, and their telemetry/drift events
    if start_epoch:
        # a resumed run may carry a *different* config (overlap, wire,
        # fault plan, tolerance): the live monitor predicts with the new
        # composition, so the journal must too, or a replay would hold the
        # post-resume epochs to the stale run_start plan
        recorder.log_event("resume", epoch=start_epoch,
                           config=_config_snapshot(config),
                           predicted=predicted or {})
    else:
        recorder.log_event("run_start",
                           config=_config_snapshot(config),
                           predicted=predicted or {})
    if backend_decision is not None:
        # the auto-resolution record (or the explicit pass-through): what
        # backend compiled and why — journaled unconditionally so a
        # questionable `auto` choice is always auditable post-hoc.  Where the
        # per-step mix is the dense exchange, its record also says where the
        # step runs it: on the parameter leaves where they lie or on a flat
        # copy of the state, with how many kernels and, on `flat`, why
        if "exchange" in backend_decision:
            backend_decision["exchange"].update(exchange_plan(
                communicator, flattener, overlap=config.overlap,
                staleness=config.staleness, faults=faults is not None,
                elastic=elastic_ctl is not None))
        # and, of a model with an expert layer, what runs its grouped
        # products: the kernel and its tiles a form and shape, or the stock
        # product and why
        if hasattr(model, "expert_products"):
            backend_decision["expert_products"] = model.expert_products(
                config.batch_size * model.row_positions(input_shape[0]),
                config.num_workers)
        recorder.log_event("backend", **backend_decision)
    # how the forward/backward runs (packs of workers side by side in the
    # lanes, or vmap over workers) and, where it is the latter, why
    recorder.log_event("fwd_bwd", **fwd_bwd_plan(
        model, config.num_workers, config.grad_chunk,
        faults=faults is not None, elastic=elastic_ctl is not None,
        worker_shards=worker_shards))
    rng = jax.random.PRNGKey(config.seed)
    history: List[Dict] = []

    scan_step = comm_timer = None
    _build_programs()

    # rollback-recovery bookkeeping (DESIGN.md §8).  The snapshot must be a
    # real device-side copy: the scanned epoch *donates* the state buffers,
    # so a held reference alone would be invalidated by the very epoch it is
    # supposed to guard against.
    recoveries_used = 0
    alpha_rederived = False
    emergency_written = False
    snapshot = None
    # telemetry is excluded from the divergence detector: its accumulator
    # sums fleet metrics that may legitimately go non-finite one step
    # before the detector's own exemption logic would excuse them (a
    # quarantined worker's spike), and it is scratch, not model state
    @jax.jit
    def finite_check(s):
        return state_finite_rows(s.replace(telemetry=()), config.num_workers)

    # retrace watch: the jitted epoch program's compile-cache size, read
    # for free after each epoch — a growing cache after the allowed shapes
    # (whole-epoch scan: 1; chunked scan: chunk + tail = 2) is the silent
    # recompile failure mode the sanitizer exists for (DESIGN.md §12); it
    # is journaled once per program instead of raising mid-run
    _retrace_flagged: set = set()
    _trace_allowance = (2 if config.scan_chunk else 1) if config.scan_epoch \
        else 1
    _step_label = "epoch_scan" if config.scan_epoch else "train_step"

    def _watch_retrace(fn):
        if not config.telemetry or fn is None:
            return
        count = getattr(fn, "_cache_size", lambda: None)()
        if count is not None and count > _trace_allowance \
                and id(fn) not in _retrace_flagged:
            _retrace_flagged.add(id(fn))
            # the cost ledger observed the growth-causing call before it
            # ran, so "the cache grew" arrives WITH the program that was
            # added and what it costs (its compile event shares this
            # fingerprint) — the §15 upgrade of this watch
            recorder.log_event(
                "retrace", label=_step_label, traces=int(count),
                fingerprint=(cost_ledger.last_fingerprint(_step_label)
                             if cost_ledger is not None else None))

    class _BoundarySeam:
        """The run controller's handle into the loop (DESIGN.md §22).

        Every mutator is a *value-level* change: knob updates ride the
        ControlKnobs pytree, drift re-bases swap host floats, and config
        edits touch only fields the compiled programs never traced — so
        the retrace watch stays silent across any sequence of hot-swaps.
        The controller side (serve.trainer.TrainerHarness) decides *what*
        to apply; this seam only knows *how* without recompiling."""

        def __init__(self):
            self.epoch = 0
            self.bpe = int(bpe)
            self.recorder = recorder
            self.schedule = schedule0
            self.flattener = flattener
            self.dataset = dataset
            self.num_workers = config.num_workers

        @property
        def config(self):
            return config

        @property
        def state(self):
            return state

        @property
        def evaluate(self):
            return evaluate

        def set_control(self, row_scale=None, alpha_scale=None,
                        local_every=None):
            """Rewrite the host knob mirror; the loop top re-primes the
            device copy before the epoch runs."""
            if row_scale is not None:
                control_knobs["row_scale"] = np.asarray(row_scale,
                                                        np.float32)
            if alpha_scale is not None:
                control_knobs["alpha_scale"] = float(alpha_scale)
            if local_every is not None:
                control_knobs["local_every"] = max(int(local_every), 1)

        def update_config(self, **fields):
            """Replace untraced config fields (drift tolerance/patience,
            local_steps bookkeeping, ...) — validated by TrainConfig's own
            __post_init__ via dataclasses.replace."""
            nonlocal config
            config = dataclasses.replace(config, **fields)

        def rebase_drift(self, alpha=None, probs=None):
            """Re-base the drift monitor's plan after a budget swap: the
            re-solved (α, p) IS the plan from here on — the same rule the
            recovery and membership re-plans follow."""
            nonlocal plan_alpha, predicted, drift_monitor, control_probs
            if alpha is not None:
                plan_alpha = float(alpha)
            if probs is not None:
                control_probs = np.asarray(probs, np.float64)
            if drift_monitor is not None:
                predicted = _compose_predicted()
                drift_monitor = DriftMonitor(
                    predicted["rho"], int(bpe),
                    tolerance=config.drift_tolerance,
                    patience=config.drift_patience)
            return predicted

        def checkpoint(self):
            """Checkpoint the last *completed* epoch's state on demand
            (pre-restart / pre-stop), reusing the cadence path's recipe."""
            if self.epoch == 0:
                return None  # nothing completed yet — nothing to save
            path = f"{config.savePath}/{config.name}_ckpt"
            with spans.span("checkpoint"):
                save_checkpoint(path, state, self.epoch - 1,
                                schedule=schedule0,
                                membership=_membership_sidecar())
                recorder.log_event("checkpoint", epoch=self.epoch - 1,
                                   path=path)
            return path

        def request_stop(self):
            """Stop cleanly before the next epoch: the loop breaks out to
            the normal drain + final recorder flush."""
            nonlocal stop_requested
            stop_requested = True

    seam = _BoundarySeam() if boundary_hook is not None else None

    epoch = start_epoch
    attempt = 0  # rollback retries of `epoch` so far
    period_samples = 0
    period_counters = None

    def _journal_period():
        """The period that just ended (none before the first) as one
        `spans` record: loop top to loop top, a rollback's `continue` and
        the stop's `break` included."""
        record = spans.end(samples=period_samples, **(
            {"counters": period_counters} if period_counters else {}))
        if record is not None:
            recorder.log_event("spans", **record)

    while epoch < config.epochs:
        _journal_period()
        spans.begin(f"{epoch}.{attempt}", epoch=epoch, attempt=attempt)
        period_samples = 0
        period_counters = None
        with spans.span("boundary_hook"):
            # chaos barrier (no-op unless armed): the campaign's SIGKILL-at-
            # epoch-boundary injector fires here, before any of this epoch's
            # host-state transitions (DESIGN.md §23)
            from ..chaos.taps import maybe_kill

            maybe_kill("epoch_boundary")
            if boundary_hook is not None:
                # the control plane's one entry point: apply pending control
                # documents, run the promotion cadence; `prime` below then
                # re-primes the device knob image (fresh every boundary,
                # like telemetry — one input placement signature whether or
                # not it changed).  A rollback retry re-enters this loop
                # top: the hook must be idempotent per control-doc version
                # (serve.trainer is).
                seam.epoch = epoch
                boundary_hook(seam)
        if stop_requested:
            break
        with spans.span("prime"):
            if boundary_hook is not None:
                state = state.replace(control=_fresh_control())
            if elastic_ctl is not None:
                # membership reconciliation — at this host boundary and
                # nowhere else (DESIGN.md §16).  advance() is idempotent per
                # epoch, so a rollback retry re-entering this loop top does
                # not re-apply the transition (the bootstrap is part of the
                # retry snapshot).
                trans = elastic_ctl.advance(epoch, schedule)
                if trans is not None:
                    member_alive_np = trans.new_alive > 0
                    if trans.joined.any() or trans.restored.any():
                        with spans.span("membership_bootstrap"):
                            state = _bootstrap_rows(state, trans.joined,
                                                    trans.restored)
                    new_pred = None
                    if trans.replanned:
                        # the re-folded α IS the plan from here on — the
                        # drift monitor and the journal both re-base,
                        # exactly like the recovery path's α re-derivation
                        # (§8)
                        plan_alpha = float(trans.alpha)
                        if drift_monitor is not None:
                            predicted = new_pred = _compose_predicted()
                            drift_monitor = DriftMonitor(
                                predicted["rho"], int(bpe),
                                tolerance=config.drift_tolerance,
                                patience=config.drift_patience)
                    recorder.log_event(
                        "membership", epoch=epoch,
                        old_alive=[float(v) for v in trans.old_alive],
                        new_alive=[float(v) for v in trans.new_alive],
                        trigger=list(trans.trigger),
                        alpha=float(trans.alpha),
                        rho=None if trans.rho is None else float(trans.rho),
                        alpha_scale=float(trans.alpha_scale),
                        replanned=bool(trans.replanned),
                        predicted=new_pred or {})
                # re-primed host-fresh EVERY epoch (transition or not), so
                # the compiled epoch program sees one input placement
                # signature — the same discipline as _fresh_telemetry, for
                # the same reason
                state = state.replace(membership=_fresh_membership())
            e_step, e_scan, e_timer = step_fn, scan_step, comm_timer
            stage = _stage_fns(epoch)
            if stage is not None:  # compression-warmup epoch: ramped-ratio programs
                _, e_step, e_scan, e_timer = stage
        with spans.span("snapshot"):
            if recoveries_used < config.max_recoveries:
                # budget exhausted ⇒ stop paying the copy (it could never
                # be used); the stale snapshot must not linger in HBM either
                snapshot = jax.tree_util.tree_map(jnp.copy, state)
            else:
                snapshot = None
        # device time by scope (DESIGN.md §15): exactly one clamped epoch
        # runs inside a jax.profiler trace window when trace_dir is set;
        # the epoch-boundary block_until_ready below sits INSIDE the
        # window so asynchronously dispatched kernels land in the capture
        # (the utils.profiling.trace contract).  The profiler's start and
        # its stop (which writes the capture: seconds) are the span
        # ``profile``, outside the two clock reads of ``epoch_time``
        tracing = (config.trace_dir is not None
                   and epoch == min(config.trace_epoch, config.epochs - 1))
        with contextlib.ExitStack() as profiler:
            if tracing:
                with spans.span("profile"):
                    profiler.enter_context(trace(config.trace_dir))
            t0 = time.time()
            if config.scan_epoch:
                state, epoch_metrics = _run_epoch_scanned(
                    e_scan, state, loader, stacks, epoch, rng,
                    config.scan_chunk,
                    spans, ledger=cost_ledger, label=_step_label, mesh=mesh,
                    row_tokens=(model.row_tokens if dataset.token_rows
                                else None))
            else:
                with spans.span("epoch_python"):
                    sums: Dict[str, float] = {}
                    counts: Dict[str, np.ndarray] = {}
                    count = 0
                    for xb, yb in loader.epoch(epoch):
                        xb, yb = jnp.asarray(xb), jnp.asarray(yb)
                        if cost_ledger is not None and count == 0:
                            # once per epoch is enough: batches share a
                            # shape, and the ledger dedups by program
                            # signature anyway
                            cost_ledger.observe(_step_label, e_step,
                                                state, xb, yb, rng)
                        state, m = e_step(state, xb, yb, rng)
                        # graftcontract: sync — the per-batch python path reads
                        # every step's metrics back by design (debug mode;
                        # scan_epoch=True is the zero-per-batch-sync path)
                        m = {k: np.asarray(v)[None] for k, v in m.items()}
                        m, step_counts = _split_counters(m)
                        for k, v in m.items():
                            sums[k] = sums.get(k, 0.0) + float(v[0])
                        for k, v in step_counts.items():
                            counts[k] = counts.get(k, 0.0) + v
                        count += 1
                    epoch_metrics = _with_counters(
                        {k: v / count for k, v in sums.items()}, counts)
            with spans.span("wait_device"):
                # graftcontract: sync — THE one deliberate per-epoch barrier
                # (wall-clock truth + everything below rides this sync)
                jax.block_until_ready(state.params)
            epoch_time = time.time() - t0
            if tracing:
                with spans.span("profile"):
                    profiler.close()
                    _journal_device_scopes(recorder, config.trace_dir, epoch)
        period_samples = bpe * config.num_workers * config.batch_size
        # what the model counted over the epoch (``COUNTER_PREFIX``): it
        # rides the period's ``spans`` record and the history, not the means
        period_counters = epoch_metrics.pop("counters", None)

        with spans.span("divergence_check"):
            if config.halt_on_divergence:
                loss_bad = not np.isfinite(epoch_metrics["loss"])
                # full-TrainState detector (params + BN stats + momentum + comm
                # carry): an Inf that so far lives only in momentum is caught
                # now, not an epoch later when it reaches the parameters.
                # Only workers currently quarantined by a *dead* event are
                # exempt — they are guaranteed a heal (params) + row reset
                # (momentum/carry) at revival.  Stragglers are never healed, so
                # their state must stay finite like anyone else's.
                # graftcontract: sync — divergence-detector readback, riding
                # the epoch-boundary barrier that already completed above
                finite_rows = np.asarray(finite_check(state))
                if faults is not None:
                    # graftcontract: sync — schedule-cursor read for the fault
                    # quarantine exemption (one scalar, already materialized)
                    cursor = max(min(int(np.asarray(state.step)) - 1,
                                     faults.iterations - 1), 0)
                    relevant = faults.dead_alive[cursor] > 0
                else:
                    relevant = np.ones_like(finite_rows)
                if member_alive_np is not None:
                    # vacant pool slots are frozen, quarantined rows — their
                    # content is nobody's training state until a (re)join
                    # bootstraps it, so it cannot convict the run
                    relevant = relevant & member_alive_np
                params_bad = bool(np.any(~finite_rows & relevant))
                if loss_bad or params_bad:
                    what = ("training loss " + str(epoch_metrics["loss"])) if loss_bad \
                        else "train state (params/BN stats/momentum/comm carry)"
                    if recoveries_used < config.max_recoveries and snapshot is not None:
                        # ---- recover instead of abort (DESIGN.md §8) --------
                        recoveries_used += 1
                        if config.save and not emergency_written and epoch > 0:
                            # last-good state, resumable with --resume
                            path = f"{config.savePath}/{config.name}_emergency"
                            with spans.span("checkpoint"):
                                # graftcontract: sync — emergency checkpoint:
                                # the last good state must reach disk now
                                save_checkpoint(path, snapshot, epoch - 1,
                                                schedule=schedule0,
                                                membership=_membership_sidecar())
                            emergency_written = True
                            recorder.log_fault("emergency_checkpoint",
                                               epoch=epoch, path=path)
                        if faults is not None:
                            # the chaos already happened: replaying the rolled-
                            # back window must not re-fire its NaN injections
                            lo = epoch * bpe
                            hi = min((epoch + 1) * bpe, faults.iterations)
                            faults = faults.without_nan_in(lo, hi)
                        lr_scale *= config.recovery_lr_backoff
                        if not alpha_rederived:
                            # re-derive α for the reliability actually realized:
                            # the fault plan's alive/link expectation (runtime
                            # degradation) or the schedule's own stored probs
                            # (already effective under offline link thinning) —
                            # effective_activation_probs finally feeding the
                            # solver at run time instead of only in offline
                            # studies
                            alpha_rederived = True
                            member_mask = (elastic_ctl.alive_mask()
                                           if elastic_ctl is not None else None)
                            if faults is not None:
                                from ..resilience import resolve_degraded_alpha

                                # membership occupancy composes into the solve
                                # (a vacant slot is dead whatever the fault
                                # plan expected) — same rule as the drift
                                # monitor's _compose_predicted
                                new_alpha, new_rho, _ = resolve_degraded_alpha(
                                    schedule, faults, worker_alive=member_mask)
                            elif member_mask is not None:
                                new_alpha, new_rho, _ = schedule.refold_for(
                                    member_mask)
                            else:
                                from ..schedule import solve_mixing_weight

                                new_alpha, new_rho = solve_mixing_weight(
                                    schedule.laplacians(), schedule.probs)
                            # the α actually executing is base × membership
                            # scale — that is what the re-derivation replaces
                            executed_alpha = float(schedule.alpha) * (
                                elastic_ctl.alpha_scale
                                if elastic_ctl is not None else 1.0)
                            if abs(new_alpha - executed_alpha) > 1e-9:
                                old_alpha = executed_alpha
                                schedule = dataclasses.replace(
                                    schedule, alpha=float(new_alpha))
                                if elastic_ctl is not None:
                                    # the composed solve subsumes the
                                    # membership re-fold: new_alpha IS the
                                    # executed α, so the controller re-bases
                                    # to scale 1 against the rebound schedule
                                    # (later membership folds re-derive
                                    # against the new base); the loop-top
                                    # _fresh_membership() re-primes the
                                    # device copy on the retry
                                    elastic_ctl.alpha = float(new_alpha)
                                    elastic_ctl.rho = float(new_rho)
                                    elastic_ctl.alpha_scale = 1.0
                                # the re-derived α IS the plan from here on:
                                # the drift monitor must predict with it, or
                                # every post-recovery epoch would be scored
                                # against a schedule that no longer runs —
                                # and the journal must carry the re-based
                                # prediction so `obs_tpu.py drift` replays
                                # against the same plan the live monitor used
                                plan_alpha = float(new_alpha)
                                new_pred = None
                                if drift_monitor is not None:
                                    predicted = new_pred = _compose_predicted()
                                    drift_monitor = DriftMonitor(
                                        predicted["rho"], int(bpe),
                                        tolerance=config.drift_tolerance,
                                        patience=config.drift_patience)
                                recorder.log_fault(
                                    "alpha_rederived", epoch=epoch,
                                    old=old_alpha,
                                    new=float(new_alpha), rho=float(new_rho),
                                    predicted=new_pred)
                        # rebuild the compiled programs against the updated
                        # lr_scale / α / consumed fault arrays — the same recipe
                        # setup used, so retries can never run a stale program
                        _build_programs()
                        recorder.log_fault(
                            "rollback", epoch=epoch, reason=what,
                            lr_scale=lr_scale, attempt=recoveries_used)
                        state = snapshot
                        snapshot = None
                        attempt += 1
                        continue  # retry this epoch from the last good state
                    # preserve the curve leading into the blow-up (flush beats the
                    # every-10-epochs cadence, which would drop up to 9 epochs)
                    recorder.add_epoch(
                        epoch_time=epoch_time, comp_time=epoch_time, comm_time=0.0,
                        train_acc=epoch_metrics["accuracy"],
                        train_loss=epoch_metrics["loss"],
                        test_acc=np.zeros(config.num_workers),
                        disagreement=epoch_metrics["disagreement"],
                    )
                    if config.save:
                        # graftcontract: sync — divergence-abort flush: the
                        # curve leading into the blow-up must survive on disk
                        recorder.save()
                    budget_note = (f", {recoveries_used}/{config.max_recoveries} "
                                   f"recoveries exhausted"
                                   if config.max_recoveries else "")
                    raise TrainingDiverged(
                        f"non-finite {what} in epoch {epoch} "
                        f"(lr={config.lr}, communicator={config.communicator}"
                        f"{budget_note})"
                    )

        comm_time = comm_encode_time = 0.0
        if e_timer is not None:
            window = run_flags[epoch * bpe : (epoch + 1) * bpe]
            with spans.span("comm_split_timer"):
                split = e_timer(state, window)
            comm_time = min(split["comm_time"], epoch_time)
            # encode is a component of comm_time, never exceeding it
            comm_encode_time = min(split["comm_encode_time"], comm_time)

        # evaluation: every worker on the full test set (train_mpi.py:152).
        # The whole [workers, batch] block runs as one vmapped forward, so
        # the per-worker slice shrinks as workers grow or activation memory
        # blows past HBM (16-worker WRN-28-10 at 512 OOMs a 16 GB chip).
        test_loss = test_acc = np.zeros(config.num_workers)
        eval_alive = None
        if config.eval_every and (epoch + 1) % config.eval_every == 0:
            with spans.span("evaluate"):
                # image rows by the chip's memory; token rows are thousands
                # of positions each, so a training batch of them at a time
                eval_batch = config.eval_batch or (
                    config.batch_size if dataset.token_rows
                    else max(16, 1024 // config.num_workers))
                test_loss, test_acc = _evaluate_in_batches(
                    evaluate, state, dataset.x_test, dataset.y_test,
                    batch=eval_batch, ledger=cost_ledger,
                    weigh=(model.judged_positions if dataset.token_rows
                           else None),
                )
                if faults is not None or member_alive_np is not None:
                    # same quarantine exemption as the train-side metrics: a
                    # plan-dead worker's (or vacant pool slot's) local state may
                    # legitimately be garbage — its eval entries become explicit
                    # NaN gaps instead of silently poisoning the tacc series and
                    # the test_*_mean history the sweep/verify consumers read
                    if faults is not None:
                        # graftcontract: sync — eval-side cursor read, same
                        # quarantine exemption as the train-side detector
                        cur = max(min(int(np.asarray(state.step)) - 1,
                                      faults.iterations - 1), 0)
                        eval_alive = faults.dead_alive[cur] > 0
                        if member_alive_np is not None:
                            eval_alive = eval_alive & member_alive_np
                    else:
                        eval_alive = member_alive_np
                    test_loss = np.where(eval_alive, test_loss, np.nan)
                    test_acc = np.where(eval_alive, test_acc, np.nan)

        with spans.span("record_epoch"):
            recorder.add_epoch(
                epoch_time=epoch_time,
                comp_time=epoch_time - comm_time,
                comm_time=comm_time,
                train_acc=epoch_metrics["accuracy"],
                train_loss=epoch_metrics["loss"],
                test_acc=test_acc,
                disagreement=epoch_metrics["disagreement"],
            )
            history.append({
                "epoch": epoch,
                **epoch_metrics,
                **({"counters": period_counters} if period_counters else {}),
                "test_acc_mean": _masked_mean(test_acc, eval_alive),
                "test_loss_mean": _masked_mean(test_loss, eval_alive),
                "epoch_time": epoch_time,
                "comm_time": comm_time,
                "comm_encode_time": comm_encode_time,
                "comm_exchange_time": comm_time - comm_encode_time,
            })

            if faults is not None and float(epoch_metrics.get("healed", 0.0)) > 0:
                recorder.log_fault(
                    "healed", epoch=epoch,
                    rows=float(epoch_metrics["healed"]) * bpe,
                    mean_alive=float(epoch_metrics.get("alive_workers",
                                                       config.num_workers)))

        if tel_spec is not None:
            with spans.span("telemetry_flush"):
                # graftcontract: sync — the ONE host read of the in-graph
                # telemetry accumulator, riding the epoch-boundary barrier
                # that already happened above; the accumulator then resets
                # for the next epoch's window
                tel = telemetry_flush(state.telemetry)
                # the per-worker stats ride the same flush but feed the
                # heartbeat, not the telemetry event (its scalar schema is
                # pinned; attribution lives in the health plane)
                worker_stats = {
                    "worker_participation": tel.pop("worker_participation"),
                    "worker_disagreement": tel.pop("worker_disagreement")}
                recorder.log_event("telemetry", epoch=epoch, **tel)
                state = state.replace(telemetry=_fresh_telemetry())
                if drift_monitor is not None:
                    drift = drift_monitor.observe(epoch,
                                                  tel["disagreement_mean"])
                    if drift is not None:
                        recorder.log_event("drift", **drift)
            if health_emitter is not None:
                with spans.span("heartbeat"):
                    # step is host arithmetic (epoch boundary × batches/epoch),
                    # NOT a device read — the zero-new-syncs contract
                    peak = max((e.get("peak_bytes") or 0.0
                                for e in cost_ledger.programs), default=0.0) \
                        if cost_ledger is not None else 0.0
                    # graftcontract: sync — per-epoch heartbeat emit (host
                    # values already read at this boundary; file write only)
                    hb = health_emitter.beat(
                        epoch=epoch, step=(epoch + 1) * bpe,
                        steps=tel["steps"], epoch_time=epoch_time,
                        comm_time=comm_time,
                        workers=_member_workers(worker_stats),
                        peak_bytes=peak or None)
                    recorder.log_event("heartbeat", **hb)
                    for a in anomaly_detector.observe(hb):
                        recorder.log_event("anomaly", **a)
                    for ev in health_emitter.drain_recovery():
                        # the heartbeat sink degraded or recovered: the run
                        # journal is the loud record a watcher reads when the
                        # per-host files themselves go quiet (DESIGN.md §23)
                        recorder.log_event("recovery", scope="io",
                                           action=ev["action"],
                                           reason=ev["reason"],
                                           sink=ev["sink"], epoch=epoch)
        _watch_retrace(e_scan if config.scan_epoch else e_step)

        if config.save and recorder.epochs_recorded % 10 == 0:
            with spans.span("recorder_flush"):
                # graftcontract: sync — recorder flush cadence parity
                # (train_mpi.py:159-160); append-only CSV + journal write
                recorder.save()
        if config.checkpoint_every and (epoch + 1) % config.checkpoint_every == 0:
            path = f"{config.savePath}/{config.name}_ckpt"
            with spans.span("checkpoint"):
                # graftcontract: sync — periodic checkpoint write at the
                # configured cadence (materializes the full TrainState)
                save_checkpoint(path, state, epoch, schedule=schedule0,
                                membership=_membership_sidecar())
                recorder.log_event("checkpoint", epoch=epoch, path=path)
        epoch += 1
        attempt = 0
    _journal_period()

    if config.overlap == "1step":
        # drain the pipeline: apply the in-flight delta(s) so the returned
        # parameters are the fully-mixed state — at staleness 1 the
        # pipelined chain has then realized exactly the same W-product as
        # the eager schedule (base.py: run_overlapped); a deeper ring
        # flushes oldest-first (base.py: run_pipelined's drain order).
        # Inside the run the pending state stays in TrainState
        # (checkpoints resume the pipeline without a re-prime); only the
        # result handed back drains.
        if config.staleness == 1:
            @jax.jit
            def _drain(s):
                flat = communicator.apply_mix(
                    flattener.flatten(s.params), s.mix_pending)
                return s.replace(params=flattener.unflatten(flat),
                                 mix_pending=jnp.zeros_like(s.mix_pending))
        else:
            # slot order is cursor arithmetic — a host int at this
            # boundary (training is over; the sync already happened)
            cursor = int(np.asarray(state.step))
            order = [(cursor + i) % config.staleness
                     for i in range(config.staleness)]

            @jax.jit
            def _drain(s):
                flat = flattener.flatten(s.params)
                for i in order:
                    flat = communicator.apply_mix(flat, s.mix_pending[:, i])
                return s.replace(
                    params=flattener.unflatten(flat),
                    mix_pending=jnp.zeros_like(s.mix_pending),
                    mix_ages=jnp.full_like(s.mix_ages, -1))

        if cost_ledger is not None:
            cost_ledger.observe("drain", _drain, state)
        state = _drain(state)
    if config.save:
        with spans.span("recorder_flush"):
            recorder.save()
    return TrainResult(state, recorder, schedule, history)


def _masked_mean(values, alive) -> float:
    """Mean of the non-quarantined entries of a per-worker eval series —
    the history's ``test_*_mean`` rule (quarantined/vacant rows are NaN
    gaps, not zeros)."""
    if alive is not None and alive.any():
        values = values[alive]
    # graftcontract: sync — host numpy mean over eval arrays the per-batch
    # eval readback already materialized
    return float(np.mean(values))


def _config_snapshot(config: TrainConfig) -> Dict:
    """JSON-safe view of the config for the journal's ``run_start`` event
    (the ExpDescription's structured twin).  Non-scalar fields (a parsed
    fault plan, dataset kwargs) are stringified rather than dropped — the
    journal records *that* they were set even when they don't serialize."""
    out: Dict = {}
    for field in dataclasses.fields(config):
        v = getattr(config, field.name)
        if isinstance(v, (str, int, float, bool, type(None))):
            out[field.name] = v
        elif isinstance(v, (list, tuple)) and all(
                isinstance(x, (str, int, float, bool)) for x in v):
            out[field.name] = list(v)
        else:
            out[field.name] = str(v)
    return out


def _journal_device_scopes(recorder, trace_dir: str, epoch: int) -> None:
    """What the capture just written under ``trace_dir`` says of the traced
    epoch: one ``device_scopes`` event (``obs.xprof.device_scopes``: device
    time by program and ``device_span``), and beside the capture
    ``scopes.json`` with that record and the map it was joined through
    (``{program: {instruction: [innermost scope, pass]}}``).  A capture
    with no device plane (the CPU) journals nothing and warns."""
    from ..obs.xprof import TraceParseError, device_scopes
    from ..utils.atomicio import atomic_publish

    maps: Dict = {}
    try:
        record = device_scopes(trace_dir, keep_maps=maps)
    except TraceParseError as e:
        warnings.warn(f"trace_dir: no device_scopes record ({e})")
        return
    atomic_publish(os.path.join(trace_dir, "scopes.json"), json.dumps({
        "record": record, "maps": {
            module: {name: [scopes[-1] if scopes else None, which]
                     for name, (scopes, which, _) in scope_map.items()}
            for module, scope_map in maps.items()}}), prefix=".scopes.")
    recorder.log_event("device_scopes", epoch=epoch, **record)


def _reconcile_mix_pending(state, overlap: str, communicator, flattener,
                           num_workers: int, staleness: int = 1):
    """Align a restored state's in-flight mix delta(s) with this run's
    ``--overlap`` / ``--staleness`` contract.

    An eager checkpoint carries no delta (``()``): resuming pipelined
    primes the zero delta/ring the first step consumes; resuming eagerly
    keeps the empty slot.  A pipelined checkpoint carries real in-flight
    state — ``[N, D]`` from a one-step run, ``[N, K', D]`` from a
    staleness-K′ ring:

    * same depth (K = K′): the pipeline continues seamlessly; ring age
      counters (never checkpointed) are rebuilt from the step cursor's
      ring arithmetic — slot s holds the delta issued at the last step
      ≡ s (mod K) before the cursor.
    * resuming eagerly: every in-flight delta *drains* into the
      parameters, oldest-first — silently dropping them would lose issued
      mixing steps.
    * a depth change (K ≠ K′, either direction): the pipeline is
      *flushed* at the boundary — all saved deltas drain oldest-first
      (their relative ages collapse to "now", a one-time perturbation no
      worse than the drain any exit performs), then a fresh zero pipeline
      primes at the new depth.  Slot arithmetic is mod-K of the cursor,
      so re-basing in place would mis-age every delta; the flush is the
      honest reconciliation.
    """
    pend = state.mix_pending
    ring_on = overlap == "1step" and staleness > 1
    fresh_pend = (
        jnp.zeros((num_workers, staleness, flattener.dim), jnp.float32)
        if ring_on
        else jnp.zeros((num_workers, flattener.dim), jnp.float32)
        if overlap == "1step" else ())
    fresh_ages = (jnp.full((num_workers, staleness), -1, jnp.int32)
                  if ring_on else ())
    if not hasattr(pend, "shape"):
        return state.replace(mix_pending=fresh_pend, mix_ages=fresh_ages)
    pend = jnp.asarray(pend)
    cursor = int(np.asarray(state.step))
    saved_k = int(pend.shape[1]) if pend.ndim == 3 else 1

    if overlap == "1step" and saved_k == staleness:
        if not ring_on:
            return state.replace(mix_ages=())  # one-step: seamless as ever
        # same-depth ring: rebuild ages from the cursor (slot s was issued
        # at the last step t' < cursor with t' ≡ s (mod K); empty before
        # the warmup filled it)
        ages = np.full((num_workers, staleness), -1, np.int64)
        for s in range(staleness):
            issued = cursor - 1 - ((cursor - 1 - s) % staleness)
            if issued >= 0:
                ages[:, s] = cursor - issued
        return state.replace(mix_ages=jnp.asarray(ages, jnp.int32))

    # drain oldest-first: slot (cursor + i) mod K' holds the delta issued
    # K'−i steps ago
    flat = flattener.flatten(state.params)
    if pend.ndim == 2:
        flat = communicator.apply_mix(flat, pend)
    else:
        for i in range(saved_k):
            flat = communicator.apply_mix(
                flat, pend[:, (cursor + i) % saved_k])
    return state.replace(params=flattener.unflatten(flat),
                         mix_pending=fresh_pend, mix_ages=fresh_ages)


def _make_comm_timer(communicator, flattener, sample_steps: int = 32,
                     ledger=None):
    """Jitted gossip-only chain, timed to a scalar readback (dispatch is
    asynchronous: the clock must stop on a value the host has read).

    Scaling to the full epoch uses the *marginal* per-step cost: two window
    lengths (k and 2k) are timed and the difference isolates the per-step
    rate from the fixed dispatch/launch overhead, which is paid once per
    chain — the round-1 linear n/k scaling multiplied that fixed cost ~50×
    into comm_time (ADVICE r1).  Estimate: ``t(n) ≈ t_2k + marginal·(n−2k)``.

    When the communicator exposes ``encode_probe`` (CHOCO), the compress
    path is additionally timed on its own scan and reported separately,
    mirroring the reference's encode-vs-sendrecv split
    (communicator.py:184-196,268).  Returns a dict:
    ``{"comm_time", "comm_encode_time"}`` (encode 0.0 for uncompressed)."""
    @jax.jit
    def chain(params, carry, flags):
        flat = flattener.flatten(params)
        out, _ = communicator.run(flat, flags, carry)
        return jnp.sum(out[:, :1].astype(jnp.float32))

    encode_chain = None
    if communicator.encode_probe is not None:
        @jax.jit
        def encode_chain(params, carry, flags):
            flat = flattener.flatten(params)

            def body(probe, _):
                return communicator.encode_probe(flat, probe), None

            probe, _ = jax.lax.scan(body, jnp.zeros_like(flat), flags)
            return jnp.sum(probe[:, :1].astype(jnp.float32))

    def extrapolate(fn, state, flags_window) -> float:
        """Measured t(k), t(2k) → marginal-cost estimate of t(n)."""
        n = len(flags_window)
        k = min(sample_steps, max(n // 2, 1))

        def timed(m: int) -> float:
            flags = jnp.asarray(flags_window[:m], jnp.float32)
            if ledger is not None:
                # the gossip-only chain is a program of the run like any
                # other: its two window lengths (k, 2k) are two distinct
                # compiled programs, each costed once on the ledger
                ledger.observe("gossip_chain", fn,
                               state.params, state.comm_carry, flags)
            float(fn(state.params, state.comm_carry, flags))  # warm/compile
            t0 = time.time()
            float(fn(state.params, state.comm_carry, flags))
            return time.time() - t0

        if n <= 2 * k:  # short epoch: just time the whole window
            return timed(n)
        t1, t2 = timed(k), timed(2 * k)
        marginal = max(t2 - t1, 0.0) / k
        return t2 + marginal * (n - 2 * k)

    def timer(state, flags_window) -> Dict[str, float]:
        out = {"comm_time": extrapolate(chain, state, flags_window),
               "comm_encode_time": 0.0}
        if encode_chain is not None:
            out["comm_encode_time"] = extrapolate(encode_chain, state, flags_window)
        return out

    return timer


def _make_epoch_scan(step_fn):
    # donate_argnums: the state (params + optimizer moments + CHOCO carry,
    # replicated N ways) is the dominant persistent buffer at 256 workers —
    # donation lets XLA write the output state into the input's memory
    # instead of double-buffering it.
    #
    # The scan body IS the restructured epoch of DESIGN.md §24: under
    # local-step elision the step_fn compiles the gossip call inside a
    # lax.cond keyed on the traced step cursor, so the one scanned program
    # executes fwd/bwd+SGD every body and the mix only in every L-th body.
    # A cond inside the body was chosen over a literal two-level
    # scan-of-fori_loop on purpose: group boundaries shift when the
    # local_every knob hot-swaps mid-run (and when bpe % L != 0 across
    # chunked epochs), and the cond form keeps ONE program shape through
    # every such change — the zero-retrace contract — while eliding
    # exactly the same work.
    @functools.partial(jax.jit, donate_argnums=(0,))
    def scan_step(state, xs, ys, rng):
        def body(s, batch):
            x, y = batch
            s, m = step_fn(s, x, y, rng)
            return s, m

        return jax.lax.scan(body, state, (xs, ys))

    return scan_step


def _put_batches(stack, mesh):
    """One host ``[steps, N, ...]`` stack → the device.  On a mesh the
    worker axis lands sharded like the state it meets (``P(None,
    WORKER_AXIS)``): a bare ``jnp.asarray`` would park the whole stack on
    device 0 and leave every epoch a reshard from that one chip."""
    if mesh is None:
        return jnp.asarray(stack)
    return jax.device_put(
        stack, NamedSharding(mesh, PartitionSpec(None, WORKER_AXIS)))


class _HostStacks:
    """The host ``[steps, N, B, ...]`` batch stacks of one ``train()`` call:
    allocated at their first use and kept, so that every later epoch's
    gather lands on pages the first one touched.  A fresh 604 MB stack an
    epoch ran at a tenth of a copy's speed, for the page faults under it
    (``PERF.md`` section 5).

    **Lifetime rule.**  ``jnp.asarray`` / ``device_put`` return before the
    copy to the device has read the host array (and the CPU backend may
    not copy at all but alias it).  So a stack is written again only after
    a readback of a program that consumed it: nothing else says that the
    copy, and every read of an alias, is over.  The whole-epoch path has
    one pair and meets the rule as the loop stands: the epoch's metrics
    are read back before the next epoch, or a rollback's re-run, stages.
    The chunked path stages segment k+1 while the device runs segment k,
    so it has two pairs and takes them in turn: segment k+1 gets the pair
    of segment k-1, whose metrics were forced after segment k's dispatch.
    No ``block_until_ready`` exists for the buffers' sake."""

    def __init__(self, loader: WorkerBatches, scan_chunk: Optional[int]):
        steps = min(scan_chunk or loader.batches_per_epoch,
                    loader.batches_per_epoch)
        lead = (steps, loader.num_workers, loader.batch_size)
        self._like = [(lead + a.shape[1:], a.dtype)
                      for a in (loader.x, loader.y)]
        self._pairs: Dict[int, Tuple[np.ndarray, np.ndarray]] = {}

    def pair(self, turn: int,
             steps: int) -> Tuple[np.ndarray, np.ndarray, int]:
        """``(xs, ys, reused)``: the leading ``steps`` of the ``turn``-th
        pair of stacks (a tail segment is shorter than its pair), and
        whether an earlier segment has filled that pair already (1) or it
        is allocated for this one (0)."""
        reused = turn in self._pairs
        if not reused:
            self._pairs[turn] = tuple(np.empty(shape, dtype)
                                      for shape, dtype in self._like)
        xs, ys = self._pairs[turn]
        return xs[:steps], ys[:steps], int(reused)


def _run_epoch_scanned(scan_step, state, loader: WorkerBatches,
                       stacks: _HostStacks, epoch: int,
                       rng, scan_chunk: Optional[int], spans, ledger=None,
                       label: str = "epoch_scan", mesh=None,
                       row_tokens=None):
    """One epoch through the scanned step, whole-epoch or chunk-pipelined.

    ``scan_chunk=None`` stages the full ``[steps, N, B, ...]`` stack (the
    round-3 behavior — cheapest dispatch, host memory ∝ epoch).  With a
    chunk, batches are staged ``[chunk, N, B, ...]`` at a time; because jax
    dispatch is asynchronous, staging segment k+1 on the host overlaps the
    device executing segment k — a two-deep host→device pipeline, on the
    two pairs of ``stacks`` in turn.  Metrics are weighted by segment
    length, so the epoch means are identical to the whole-epoch scan.

    Every phase is a span of ``spans`` (``utils.profiling.SPAN_NAMES``);
    a chunked epoch's carry ``segment=<i>``, one set per segment.
    """

    def tokens_of(xs):
        """A token job's ``dispatch`` says how many tokens it predicts:
        ``row_tokens(width)`` of every row, which is the model's to say
        (``TokenDecoder.row_tokens``: ``S`` of a next-token row ``[S + 1]``,
        ``S`` of a block-diffusion row ``[2 S]``), and None for image rows."""
        if row_tokens is None:
            return {}
        return {"tokens": int(np.prod(xs.shape[:-1]))
                * row_tokens(xs.shape[-1])}

    def run_segment(s, first, steps, **segment):
        """Gather steps ``[first, first + steps)`` into a kept stack, put
        it and dispatch it as one scanned segment, after the ledger (when
        on) has costed its program — a chunked epoch's tail is a second
        compiled shape and journals its own compile event."""
        xs, ys, reused = stacks.pair(segment.get("segment", 0) % 2, steps)
        with spans.span("load_batches", **segment):
            loader.epoch_into(epoch, xs, ys, first)
        with spans.span("stack_batches", reused=reused, **segment):
            # nothing is left to stack: the gather wrote the rows where
            # they go.  The span stays for its readers, and carries the
            # counter that says the pages under the stack were touched
            # before
            pass
        with spans.span("h2d", bytes=xs.nbytes + ys.nbytes, **segment):
            xs, ys = _put_batches(xs, mesh), _put_batches(ys, mesh)
        if ledger is not None:
            with spans.span("ledger_observe", **segment):
                ledger.observe(label, scan_step, s, xs, ys, rng)
        with spans.span("dispatch", steps=steps, **tokens_of(xs), **segment):
            return scan_step(s, xs, ys, rng)

    if not scan_chunk:
        state, metrics = run_segment(state, 0, loader.batches_per_epoch)
        with spans.span("wait_device"):
            means, counts = _split_counters(metrics)
            # graftcontract: sync — whole-epoch metrics readback: one forced
            # materialization per epoch, after the scan returns
            means = {k: float(np.mean(v)) for k, v in means.items()}
            return state, _with_counters(means, counts)

    sums: Dict[str, float] = {}
    counts: Dict[str, np.ndarray] = {}
    total = 0
    pending = None  # metrics of the in-flight segment (device may still run)

    def flush(metrics, n, segment):
        nonlocal total
        with spans.span("wait_device", segment=segment):
            means, segment_counts = _split_counters(metrics)
            for k, v in means.items():
                # graftcontract: sync — per-chunk metrics force, deliberately
                # AFTER the next segment's dispatch (the two-deep pipeline)
                sums[k] = sums.get(k, 0.0) + float(np.sum(v))
            for k, v in segment_counts.items():
                counts[k] = counts.get(k, 0.0) + v
        total += n

    # (a tail segment is its own compiled shape, at most once per run)
    starts = range(0, loader.batches_per_epoch, scan_chunk)
    for segment, first in enumerate(starts):
        steps = min(scan_chunk, loader.batches_per_epoch - first)
        # load + stack + H2D + dispatch FIRST, then force the previous
        # segment's metrics: the flush must not sit between the device
        # going idle and the next segment's dispatch, or the promised
        # overlap never happens (metrics are not donated, so reading them
        # after the next dispatch is safe)
        state, metrics = run_segment(state, first, steps, segment=segment)
        if pending is not None:
            flush(*pending)
        pending = (metrics, steps, segment)
    if pending is not None:
        flush(*pending)
    return state, _with_counters({k: v / total for k, v in sums.items()},
                                 counts)


def _split_counters(metrics):
    """A segment's stacked step metrics ``{name: [steps, ...]}`` as (those
    that are means, the counts ``{name: float64 array}`` summed over the
    steps): a model that supplies its loss returns counts under
    ``COUNTER_PREFIX`` (``train/state.py``)."""
    counts = {}
    for k, v in metrics.items():
        if k.startswith(COUNTER_PREFIX):
            # graftcontract: sync — the model's counts ride the readback of
            # the segment's means: same place, same moment
            counts[k[len(COUNTER_PREFIX):]] = np.asarray(v, np.float64).sum(0)
    return ({k: v for k, v in metrics.items()
             if not k.startswith(COUNTER_PREFIX)}, counts)


def _with_counters(epoch_metrics, counts):
    """The epoch's metrics, with its counts (where the model has any) as
    plain numbers and lists under ``"counters"``."""
    if counts:
        epoch_metrics["counters"] = {k: v.tolist() for k, v in counts.items()}
    return epoch_metrics


def _evaluate_in_batches(evaluate, state, x_test, y_test, batch: int = 512,
                         ledger=None, weigh=None):
    """Full-test-set eval (reference test() covers the partial tail batch too,
    util.py:422-432) — at most two compiled shapes: `batch` and the tail."""
    losses, accs, weights = [], [], []
    splits = list(range(0, len(x_test), batch))
    for i in splits:
        xl = jnp.asarray(x_test[i : i + batch])
        yl = jnp.asarray(y_test[i : i + batch])
        if ledger is not None:
            ledger.observe("evaluate", evaluate,
                           state.params, state.batch_stats, xl, yl)
        l, a = evaluate(state.params, state.batch_stats, xl, yl)
        # graftcontract: sync — per-eval-batch readback (eval cadence:
        # eval_every epochs, ≤ ceil(test/batch)+1 compiled shapes)
        losses.append(np.asarray(l))
        # graftcontract: sync — second half of the same eval readback
        accs.append(np.asarray(a))
        # a batch's mean is over its rows, or over the positions judged
        # (``weigh(x, y)``: a token model's own count of them)
        weights.append(len(yl) if weigh is None else weigh(
            x_test[i : i + batch], y_test[i : i + batch]))
    # graftcontract: sync — host batch-size weights (never device values)
    w = np.asarray(weights, np.float64)[:, None]
    return (
        (np.stack(losses) * w).sum(0) / w.sum(),
        (np.stack(accs) * w).sum(0) / w.sum(),
    )
