"""Training configuration.

Replaces the reference's argparse namespace (/root/reference/train_mpi.py:205-231)
with a typed dataclass.  Field names keep the reference's vocabulary where it
exists (budget, graphid, compress, consensus_lr, ...) so reference users map
configs 1:1; the ``default=True, action='store_true'`` anti-pattern flags
(SURVEY.md §5.6) become honest booleans, and previously hard-coded values
(Choco ratio, train_mpi.py:79) become real fields.
"""

from __future__ import annotations

import dataclasses
from typing import Optional, Tuple

__all__ = ["TrainConfig"]


@dataclasses.dataclass
class TrainConfig:
    # experiment identity (reference: --name/--description, required)
    name: str = "experiment"
    description: str = "matcha_tpu run"

    # model / data (reference: --model, --dataset, --bs)
    model: str = "resnet20"
    dataset: str = "synthetic"
    batch_size: int = 32  # per worker
    non_iid: bool = False
    augment: bool = False
    datasetRoot: Optional[str] = None  # .npz path for real datasets
    # extra kwargs for the synthetic dataset builders (num_train, separation,
    # ...) — lets benchmarks size/condition hermetic data without new flags
    dataset_kwargs: Optional[dict] = None
    # extra kwargs for the model's constructor, through
    # ``models.select_model``: a model whose sizes are not in its name takes
    # them here (``mellum2``: ``{"sizes": {...}}``, README "Training a
    # language model")
    model_kwargs: Optional[dict] = None

    # optimization (reference: --lr/--momentum/--epoch/--warmup/--nesterov + wd=5e-4)
    lr: float = 0.8
    momentum: float = 0.9
    weight_decay: float = 5e-4
    nesterov: bool = True
    epochs: int = 200
    warmup: bool = True
    warmup_epochs: int = 5
    base_lr: float = 0.1  # warmup start (train_mpi.py:183)
    decay_epochs: Tuple[int, ...] = (100, 150)  # train_mpi.py:181,194
    decay_factor: float = 0.1

    # topology / schedule (reference: --graphid/--budget/--matcha)
    num_workers: int = 8
    graphid: Optional[int] = 0  # zoo id; None → use topology generator
    topology: str = "ring"  # generator kind when graphid is None
    matcha: bool = True
    budget: float = 0.5
    fixed_mode: str = "all"  # D-PSGD flag mode: all|bernoulli|alternating
    seed: int = 9001  # reference --randomSeed default (train_mpi.py:230)
    # path to a plan_tpu.py artifact: resolves graph/budget/seed offline
    # (matcha_tpu.plan.apply_plan overrides those fields at train() entry,
    # so the schedule built is exactly the one the planner scored)
    plan: Optional[str] = None

    # communicator (reference: --compress/--consensus_lr; ratio was hard-coded)
    communicator: str = "decen"  # decen|choco|centralized|none
    compress_ratio: float = 0.9
    compressor: str = "top_k"  # choco message compressor (ops.COMPRESSOR_NAMES)
    consensus_lr: float = 0.1
    # CHOCO compression warmup: ramp the drop-ratio linearly from 0 (keep
    # everything — dense-speed consensus while the replicas are far apart)
    # to ``compress_ratio`` over this many epochs, then hold.  0 disables.
    # Each distinct per-epoch ratio compiles its own step program (the top-k
    # size is a static shape), so keep it small (≤ ~6).  The reference
    # hard-codes ratio 0.9 for the whole run (train_mpi.py:79); the warmup
    # addresses the compressed-consensus cold start that leaves 64-worker
    # top-k-10% runs far behind their uncompressed control early on.
    compress_warmup_epochs: int = 0
    # gossip backend (communicator.decen.GOSSIP_BACKENDS): dense (W_t x
    # once a step: one streamed pass at small N, an MXU matmul above),
    # gather, skip, shard_map, or auto (shard_map on a real mesh, dense on
    # one chip; the decision is journaled as a `backend` event)
    gossip_backend: str = "auto"
    # overlapped gossip pipeline (DESIGN.md §11): "1step" issues each step's
    # exchange via begin_mix and consumes it at the next step, so XLA can
    # hide ICI traffic under the next forward/backward; "off" is the eager
    # schedule (mixing on the critical path).  One-step-stale semantics: the
    # gradient update joins consensus one round late — contraction effect
    # predicted by `plan_tpu.py rho --overlap 1step`.
    overlap: str = "off"  # off|1step
    # bounded-staleness pipeline depth K (DESIGN.md §20): with overlap
    # "1step", in-flight mixing deltas age through a static-shape
    # [K, N, D] pending ring — issued at step t, consumed at t+K — so a
    # fast worker proceeds K steps before it needs a straggler's delta.
    # K=1 is the committed one-step pipeline, bitwise.  For K >= 2 the
    # loop damps the executed mixing weight for the delayed dynamics
    # (plan.spectral.stale_alpha_rescale — the eagerly-solved α oscillates
    # under deep delay; the damping rides the flag row like elastic
    # alpha_scale, so schedules, fingerprints, and checkpoints are
    # untouched) and the drift monitor predicts with the staleness-
    # composed ρ (`plan_tpu.py rho --staleness K`).
    staleness: int = 1
    # local SGD steps per gossip exchange (DESIGN.md §20): the flag stream
    # is statically thinned to every L-th row (skipped steps mix by I and
    # move zero wire bytes), so consensus contracts at rho^(1/L) per step
    # while gossip cost is paid 1/L as often.  Composes with staleness:
    # delays count in gossip-event units ceil(K/L), so local_steps >= K
    # telescopes exactly like the one-step pipeline.
    local_steps: int = 1
    # dtype of the exchanged tensors at the gossip boundary: "bf16" halves
    # bytes_per_step on every backend (ppermute blocks, gathered rows, the
    # MXU operand pass) while master params and accumulation stay f32;
    # "f32" compiles the exact legacy program
    wire_dtype: str = "f32"  # f32|bf16

    # logging / checkpointing (reference: --save/--savePath; ckpt is new — §5.4)
    save: bool = False
    savePath: str = "runs"
    checkpoint_every: int = 0  # epochs; 0 = disabled
    resume: Optional[str] = None  # checkpoint dir to resume from
    eval_every: int = 1
    # test-set eval slice per compiled call, per worker; 0 = auto-size so the
    # vmapped (workers × batch) forward stays within HBM for big models
    eval_batch: int = 0

    # resilience (DESIGN.md §8): runtime fault injection + rollback recovery
    # fault plan: a resilience.FaultPlan, a parsed dict, or a path to its
    # JSON (train_tpu.py --fault-plan) — compiled into static per-step
    # alive/nan/link arrays injected into the SPMD step for deterministic
    # chaos testing; None disables all fault machinery (the exact
    # pre-resilience program compiles)
    fault_plan: Optional[object] = None
    # rollback recovery: on a non-finite epoch, restore the last good state,
    # scale the LR by recovery_lr_backoff, re-derive alpha for the degraded
    # link reliability, and retry — up to this many times before raising
    # TrainingDiverged.  0 keeps the historical raise-immediately behavior.
    max_recoveries: int = 0
    recovery_lr_backoff: float = 0.5

    # elastic membership (DESIGN.md §16): a declarative churn trace —
    # an elastic.MembershipTrace, a parsed dict, or a path to its JSON
    # (train_tpu.py --membership-trace).  Events (join/leave/rejoin of
    # named workers) reconcile at epoch boundaries only; live workers map
    # onto the static num_workers-slot pool, so the compiled step is
    # reused verbatim across every change.  None disables all elastic
    # machinery (the exact pre-elastic program compiles).
    membership_trace: Optional[object] = None
    # epochs the membership must stay unchanged before α/ρ are re-derived
    # for the new live set (0 = eager re-plan at the change boundary; the
    # alive mask always applies immediately — masking is correctness, α is
    # optimization).  plan_tpu.py elasticity scores this trade-off offline.
    membership_hysteresis: int = 0
    # join/rejoin state bootstrap: "mean" initializes every (re)entering
    # worker's rows from the continuing members' average; "restore" lets a
    # rejoiner keep its own quarantined rows when its slot is untouched
    # and still finite (momentum/carry/overlap-delta reset either way).
    membership_bootstrap: str = "mean"
    # live membership (DESIGN.md §17): a heartbeat directory to watch (a
    # run's health/ dir, or any directory of per-host heartbeat files), or
    # — programmatically — membership_trace may itself be an
    # elastic.LiveMembershipSource.  Missed-deadline ⇒ leave, reappearance
    # ⇒ rejoin, through the same ElasticController the declared trace
    # drives (parity pinned by test).  Mutually exclusive with
    # membership_trace.
    membership_live: Optional[str] = None
    # seconds without a heartbeat before a member is presumed gone (and a
    # non-member's heartbeat counts as an arrival)
    membership_deadline: float = 60.0

    # observability (DESIGN.md §14).  telemetry=True threads the
    # obs.Telemetry scalar accumulator through the compiled step (a handful
    # of fused adds, read once per epoch — no per-step host sync) and arms
    # the drift monitor + retrace watch.  The unified events.jsonl journal
    # is a Recorder feature and rides save=True regardless — with telemetry
    # off it still records run_start/epoch/fault/checkpoint events, just no
    # telemetry flushes or drift trips.
    telemetry: bool = True
    # live health plane (DESIGN.md §17): append one heartbeat record per
    # epoch to {run}/health/{host}.jsonl (step progress, step-time EWMA,
    # comm/compute split, peak footprint, per-worker participation +
    # disagreement) and run the streaming anomaly detectors over it,
    # journaling `anomaly` events with an attributed cause.  Pure host
    # work riding the existing epoch sync — needs save (a run folder) and
    # telemetry (the per-worker stats) to be on; False disables only this.
    health: bool = True
    # drift monitor: journal a `drift` event when the measured per-epoch
    # disagreement contraction exceeds the plan's predicted factor
    # (rho^(steps/2), staleness/wire/fault-composed) by more than
    # drift_tolerance for drift_patience consecutive falsifiable epochs.
    # Runs only for the decen communicator (the one the spectral model
    # describes); telemetry=False disables it too.
    drift_tolerance: float = 0.25
    drift_patience: int = 2
    # device time by scope (DESIGN.md §15): when set, exactly one epoch
    # (trace_epoch, clamped to the run) is wrapped in a jax.profiler trace
    # written under this directory, under the span `profile`; after it the
    # loop reduces the capture (obs.xprof.device_scopes), journals the
    # `device_scopes` event and writes scopes.json beside the capture;
    # `obs_tpu.py profile <dir>` prints the same table later.  Epoch 1 by
    # default: epoch 0 would trace the compiles, not the steady state.
    trace_dir: Optional[str] = None
    trace_epoch: int = 1
    # initial-consensus sync (reference train_mpi.py:97 sync_allreduce).
    # False starts the workers at their independent inits — the
    # consensus-dominant regime drift diagnostics and pure-gossip studies
    # need (disagreement then *contracts* from a visible spread instead of
    # rising from zero toward the gradient-drift floor).
    sync_init: bool = True
    # deliberate mis-plan knob (chaos testing the drift monitor): execute
    # the schedule with this α while the drift monitor keeps comparing
    # against the *solved* α's predicted rho — exactly the "planner claimed
    # a contraction the runtime doesn't deliver" failure the monitor
    # exists to catch.  None = run the solved α (always, outside tests).
    alpha_override: Optional[float] = None

    # execution
    # memory/FLOPs trades for many-workers-per-chip folding (both exact):
    remat: bool = False  # block-level activation rematerialization
    grad_chunk: Optional[int] = None  # workers per fwd/bwd slab (None = all)
    scan_epoch: bool = True  # lax.scan over an epoch's batches (one program)
    # batches per scanned segment (None = whole epoch in one scan).  The
    # whole-epoch scan stages a [steps, N, B, ...] batch stack on host and
    # device — fine at bench scales, quadratic pain at 256-worker × real
    # dataset scale.  A chunk (e.g. 64) bounds staging memory to
    # [chunk, N, B, ...] and pipelines: segment k+1 is stacked on host while
    # the device still runs segment k (dispatch is async), so the device
    # never idles on input.  Two compiled shapes at most (chunk + tail).
    scan_chunk: Optional[int] = None
    devices: Optional[int] = None  # mesh size; None → all available
    measure_comm_split: bool = True  # two-program comp/comm timing (§5.1)
    halt_on_divergence: bool = True  # raise TrainingDiverged on NaN loss (§5.3)

    def __post_init__(self):
        if self.communicator not in ("decen", "choco", "centralized", "none"):
            raise ValueError(f"bad communicator '{self.communicator}'")
        from ..ops import COMPRESSOR_NAMES

        if self.compressor not in COMPRESSOR_NAMES:
            raise ValueError(f"bad compressor '{self.compressor}'; "
                             f"have {sorted(COMPRESSOR_NAMES)}")
        from ..communicator.decen import GOSSIP_BACKENDS

        if self.gossip_backend not in GOSSIP_BACKENDS:
            raise ValueError(f"bad gossip_backend '{self.gossip_backend}'; "
                             f"have {list(GOSSIP_BACKENDS)}")
        if self.num_workers < 2:
            raise ValueError("need at least 2 virtual workers")
        if not 0 <= self.budget <= 1:
            raise ValueError("budget must be in [0, 1]")
        if self.scan_chunk is not None and self.scan_chunk < 1:
            # a negative value would silently degenerate to the unbounded
            # whole-epoch stack via the tail path — the opposite of what
            # the knob promises
            raise ValueError("scan_chunk must be None or >= 1")
        if self.grad_chunk is not None:
            if self.grad_chunk < 1:
                raise ValueError("grad_chunk must be None or >= 1")
            if self.num_workers % self.grad_chunk:
                raise ValueError(
                    f"grad_chunk {self.grad_chunk} must divide "
                    f"num_workers {self.num_workers}")
        if self.overlap not in ("off", "1step"):
            raise ValueError(
                f"overlap must be 'off' or '1step', got {self.overlap!r}")
        if self.staleness < 1:
            raise ValueError(f"staleness must be >= 1, got {self.staleness}")
        if self.staleness > 1 and self.overlap != "1step":
            raise ValueError(
                "staleness > 1 needs overlap='1step': the eager schedule "
                "has no pending ring to age mixing deltas through")
        if self.local_steps < 1:
            raise ValueError(
                f"local_steps must be >= 1, got {self.local_steps}")
        if self.wire_dtype not in ("f32", "bf16"):
            raise ValueError(
                f"wire_dtype must be 'f32' or 'bf16', got {self.wire_dtype!r}")
        if self.compress_warmup_epochs < 0:
            raise ValueError("compress_warmup_epochs must be >= 0")
        if self.compress_warmup_epochs and self.communicator != "choco":
            raise ValueError(
                "compress_warmup_epochs only applies to the choco "
                "communicator (the only compressed one)")
        if self.max_recoveries < 0:
            raise ValueError("max_recoveries must be >= 0")
        if self.trace_epoch < 0:
            raise ValueError(
                f"trace_epoch must be >= 0, got {self.trace_epoch}")
        if not self.drift_tolerance > 0:
            raise ValueError(
                f"drift_tolerance must be > 0, got {self.drift_tolerance}")
        if self.drift_patience < 1:
            raise ValueError(
                f"drift_patience must be >= 1, got {self.drift_patience}")
        if self.alpha_override is not None and not self.alpha_override > 0:
            raise ValueError(
                f"alpha_override must be > 0, got {self.alpha_override}")
        if self.max_recoveries and not self.halt_on_divergence:
            raise ValueError(
                "max_recoveries needs halt_on_divergence=True — recovery is "
                "what the detector triggers; with detection off there is "
                "nothing to roll back from")
        if not 0.0 < self.recovery_lr_backoff <= 1.0:
            raise ValueError(
                f"recovery_lr_backoff must be in (0, 1], got "
                f"{self.recovery_lr_backoff}")
        if self.fault_plan is not None and self.communicator == "none":
            raise ValueError(
                "fault_plan needs a communicator: without gossip there are "
                "no links to fail and no peers to heal a worker from")
        if self.membership_hysteresis < 0:
            raise ValueError(
                f"membership_hysteresis must be >= 0, got "
                f"{self.membership_hysteresis}")
        if self.membership_bootstrap not in ("mean", "restore"):
            raise ValueError(
                f"membership_bootstrap must be 'mean' or 'restore', got "
                f"{self.membership_bootstrap!r}")
        if self.membership_trace is not None and self.communicator == "none":
            raise ValueError(
                "membership_trace needs a communicator: a joining worker "
                "bootstraps from its peers' consensus, which requires a "
                "mixing process to rejoin")
        if self.membership_live is not None:
            if self.membership_trace is not None:
                raise ValueError(
                    "membership_live and membership_trace are mutually "
                    "exclusive — one membership source per run (pass a "
                    "LiveMembershipSource as membership_trace for a "
                    "pre-built live source)")
            if self.communicator == "none":
                raise ValueError(
                    "membership_live needs a communicator: a joining worker "
                    "bootstraps from its peers' consensus, which requires a "
                    "mixing process to rejoin")
        if not self.membership_deadline > 0:
            raise ValueError(
                f"membership_deadline must be > 0, got "
                f"{self.membership_deadline}")
