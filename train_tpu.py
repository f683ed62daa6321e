#!/usr/bin/env python
"""CLI driver — the TPU-native twin of the reference's ``train_mpi.py``.

Same flag vocabulary (/root/reference/train_mpi.py:205-231) where applicable,
minus the MPI launcher: one process drives N virtual workers as mesh shards.

Examples
--------
D-PSGD on the 8-node ring, MLP on synthetic data::

    python train_tpu.py --name demo --model mlp --dataset synthetic \
        --graphid 5 --numworkers 8 --epoch 5 --lr 0.1 --no-matcha

MATCHA at budget 0.5 on the paper's 16-node ER graph (zoo id 4)::

    python train_tpu.py --name matcha-er --model resnet20 \
        --dataset synthetic_image --graphid 4 --numworkers 16 \
        --budget 0.5 --epoch 10

256 workers on a generated geometric topology with CHOCO compression::

    python train_tpu.py --name choco256 --model mlp --dataset synthetic \
        --graphid -1 --topology geometric --numworkers 256 \
        --compress --consensus-lr 0.1 --epoch 5
"""

from __future__ import annotations

import argparse
import json

from matcha_tpu.ops import COMPRESSOR_NAMES
from matcha_tpu.train import TrainConfig, train


def _json_argument(text):
    """A JSON object given inline or as the path of a file; None stays."""
    if text is None:
        return None
    if not text.lstrip().startswith("{"):
        with open(text) as f:
            text = f.read()
    return json.loads(text)


def parse_args(argv=None) -> TrainConfig:
    """The TrainConfig a command line describes (no side effects)."""
    return _parse(argv)[0]


def _parse(argv=None):
    """``(TrainConfig, --platform)`` for ``argv``."""
    p = argparse.ArgumentParser(description=__doc__,
                                formatter_class=argparse.RawDescriptionHelpFormatter)
    # reference flag names kept where they exist (train_mpi.py:205-231)
    p.add_argument("--name", default="experiment")
    p.add_argument("--description", default="matcha_tpu run")
    p.add_argument("--model", default="resnet20",
                   help="res|resnet<d>|VGG|vgg<d>|wrn|wrn-<d>-<k>|mlp|mellum2|"
                        "keye_vl2|qwen3_next|sdar")
    p.add_argument("--model-kwargs", default=None, dest="model_kwargs",
                   help="JSON (or the path of a JSON file) of keyword "
                        "arguments for the model: a model whose sizes are "
                        "not in its name takes them here, e.g. mellum2's "
                        "'{\"sizes\": {...}}'")
    p.add_argument("--lr", type=float, default=0.8)
    p.add_argument("--momentum", type=float, default=0.9)
    p.add_argument("--epoch", type=int, default=200, dest="epochs")
    p.add_argument("--bs", type=int, default=32, help="per-worker batch size")
    p.add_argument("--warmup", action=argparse.BooleanOptionalAction, default=True)
    p.add_argument("--nesterov", action=argparse.BooleanOptionalAction, default=True)
    p.add_argument("--matcha", action=argparse.BooleanOptionalAction, default=True)
    p.add_argument("--budget", type=float, default=0.5)
    p.add_argument("--plan", default=None,
                   help="plan_tpu.py artifact (plan.json): pre-resolves "
                        "graph/budget/flag-seed offline — overrides "
                        "--graphid/--topology/--numworkers/--budget/"
                        "--matcha/--randomSeed")
    p.add_argument("--graphid", type=int, default=0,
                   help="zoo topology id (0-5); -1 to generate --topology instead")
    p.add_argument("--topology", default="ring",
                   help="generator when --graphid -1 (ring|torus|erdos_renyi|geometric|...)")
    p.add_argument("--numworkers", type=int, default=8)
    p.add_argument("--dataset", default="synthetic",
                   help="synthetic|synthetic_image|digits|photo_patches|"
                        "cifar10|cifar100|emnist|imagenet|tokens (the last "
                        "five need --datasetRoot; digits/photo_patches are "
                        "real pixels bundled in-image; tokens is int32 ids "
                        "and document numbers for next-token training)")
    p.add_argument("--datasetRoot", default=None, help=".npz path for real datasets")
    p.add_argument("--noniid", action="store_true", help="label-skew partition")
    p.add_argument("--augment", action="store_true")
    p.add_argument("--savePath", default="runs")
    p.add_argument("--save", action="store_true")
    p.add_argument("--compress", action="store_true", help="CHOCO-SGD top-k gossip")
    p.add_argument("--ratio", type=float, default=0.9,
                   help="compression ratio (keep top 1-ratio); was hard-coded in the reference")
    p.add_argument("--compressor", default="top_k",
                   choices=list(COMPRESSOR_NAMES),
                   help="CHOCO message compressor (the reference's reserved "
                        "extension point, communicator.py:186-187)")
    p.add_argument("--consensus-lr", type=float, default=0.1, dest="consensus_lr")
    p.add_argument("--compress-warmup-epochs", type=int, default=0,
                   dest="compress_warmup_epochs",
                   help="ramp the CHOCO drop-ratio 0→--ratio over this many "
                        "epochs (dense-rate consensus while replicas are far "
                        "apart); each distinct ratio compiles its own step, "
                        "so keep small. 0 disables (reference behavior)")
    p.add_argument("--centralized", action="store_true", help="AllReduce baseline")
    p.add_argument("--randomSeed", type=int, default=9001, dest="seed")
    p.add_argument("--backend", default="auto",
                   help="gossip backend: dense|gather|skip|"
                        "shard_map|auto (skip = per-matching lax.cond; "
                        "inactive matchings cost nothing, so budget < 1 "
                        "buys real time; gather is a small-N debugging "
                        "path — ~60x slower than dense at N>=64 and "
                        "warns there; auto = shard_map on several devices, "
                        "dense on one chip, journaled as a `backend` event)")
    p.add_argument("--overlap", default="off", choices=["off", "1step"],
                   help="software-pipelined gossip: '1step' issues each "
                        "step's exchange (begin_mix) and consumes it at the "
                        "next step, so XLA overlaps ICI traffic with the "
                        "next fwd/bwd; one-step-stale semantics — see "
                        "plan_tpu.py rho --overlap for the predicted "
                        "contraction effect")
    p.add_argument("--staleness", type=int, default=1,
                   help="bounded-staleness pipeline depth K (needs "
                        "--overlap 1step): in-flight mixing deltas age "
                        "through a static [N, K, D] pending ring — issued "
                        "at step t, consumed at t+K — so fast workers run "
                        "K steps ahead of a straggler's delta.  K=1 is the "
                        "committed one-step pipeline bitwise; K>=2 damps "
                        "the executed mixing weight for the delayed "
                        "dynamics (plan_tpu.py rho --staleness K predicts "
                        "the composed contraction)")
    p.add_argument("--local-steps", type=int, default=1, dest="local_steps",
                   help="local SGD steps per gossip exchange: the flag "
                        "stream is statically thinned to every L-th row, "
                        "so gossip cost is paid 1/L as often and consensus "
                        "contracts at rho^(1/L) per step; composes with "
                        "--staleness (delays count in exchange units "
                        "ceil(K/L))")
    p.add_argument("--wire-dtype", default="f32", choices=["f32", "bf16"],
                   dest="wire_dtype",
                   help="dtype of the exchanged tensors at the gossip "
                        "boundary: bf16 halves bytes/step on every backend "
                        "(master params stay f32)")
    p.add_argument("--fixed-mode", default="all", dest="fixed_mode",
                   help="D-PSGD flag mode: all|bernoulli|alternating "
                        "(alternating = reference ring parity, SURVEY Q1)")
    p.add_argument("--scan-chunk", type=int, default=0, dest="scan_chunk",
                   help="batches per scanned segment (0 = whole-epoch scan); "
                        "bounds host staging memory and pipelines host "
                        "stacking against device execution at large scale")
    p.add_argument("--no-comm-split", action="store_true",
                   help="skip the per-epoch two-program comp/comm timing")
    p.add_argument("--remat", action="store_true",
                   help="block-level activation rematerialization (exact; "
                        "trades ~1/3 more fwd FLOPs for activation HBM)")
    p.add_argument("--grad-chunk", type=int, default=0, dest="grad_chunk",
                   help="workers per fwd/bwd slab (0 = all at once); caps "
                        "activation memory when folding many virtual "
                        "workers per chip")
    p.add_argument("--fault-plan", default=None, dest="fault_plan",
                   help="JSON fault plan (resilience.FaultPlan): dead "
                        "workers, stragglers, NaN emitters, link outages "
                        "over step ranges, injected deterministically into "
                        "the SPMD step; e.g. "
                        '\'{"events": [{"kind": "dead", "worker": 3, '
                        '"start": 100, "stop": 200}]}\' in a file')
    p.add_argument("--membership-trace", default=None,
                   dest="membership_trace",
                   help="JSON membership trace (elastic.MembershipTrace): "
                        "join/leave/rejoin events of named workers applied "
                        "at epoch boundaries — live workers map onto the "
                        "static worker pool, the compiled step never "
                        "retraces, and alpha/rho re-derive per live set; "
                        'e.g. \'{"events": [{"kind": "leave", "epoch": 2, '
                        '"worker": "w3"}]}\' in a file (DESIGN.md §16)')
    p.add_argument("--membership-hysteresis", type=int, default=0,
                   dest="membership_hysteresis",
                   help="epochs the membership must hold still before the "
                        "schedule is re-folded (alpha re-derived) for the "
                        "new live set; 0 = eager re-plan. The alive mask "
                        "always applies immediately. Score the trade-off "
                        "offline with plan_tpu.py elasticity")
    p.add_argument("--membership-bootstrap", default="mean",
                   choices=["mean", "restore"], dest="membership_bootstrap",
                   help="join/rejoin state policy: 'mean' bootstraps every "
                        "(re)entering worker from the continuing members' "
                        "average; 'restore' lets a rejoiner keep its own "
                        "quarantined rows when still finite")
    p.add_argument("--membership-live", default=None,
                   dest="membership_live",
                   help="heartbeat directory to drive membership from "
                        "LIVE instead of a declared trace (a run's "
                        "health/ dir on a shared FS): a member missing "
                        "its --membership-deadline leaves, a reappearing "
                        "worker rejoins — same controller, hysteresis, "
                        "and re-folds as --membership-trace "
                        "(DESIGN.md §17); mutually exclusive with it")
    p.add_argument("--membership-deadline", type=float, default=60.0,
                   dest="membership_deadline",
                   help="seconds without a heartbeat before a member is "
                        "presumed gone (with --membership-live)")
    p.add_argument("--no-health", action="store_true",
                   help="disable the live health plane (per-epoch "
                        "heartbeat records under {run}/health/ and the "
                        "streaming anomaly detectors — DESIGN.md §17); "
                        "heartbeats ride --save + telemetry and are pure "
                        "host work, so this exists for A/B, not speed")
    p.add_argument("--max-recoveries", type=int, default=0,
                   dest="max_recoveries",
                   help="on a non-finite epoch: roll back to the last good "
                        "state, back off the LR, re-derive alpha for the "
                        "degraded links, and retry up to this many times "
                        "before raising (0 = historical abort-on-NaN)")
    p.add_argument("--recovery-lr-backoff", type=float, default=0.5,
                   dest="recovery_lr_backoff",
                   help="LR scale applied per recovery attempt")
    p.add_argument("--checkpoint-every", type=int, default=0)
    p.add_argument("--resume", default=None, help="checkpoint dir to resume from")
    p.add_argument("--eval-every", type=int, default=1)
    p.add_argument("--eval-batch", type=int, default=0,
                   help="test-set slice per compiled eval call per worker; "
                        "0 auto-sizes to keep workers x batch within HBM")
    p.add_argument("--no-telemetry", action="store_true",
                   help="disable the in-graph step counters and the live "
                        "planner-drift monitor (DESIGN.md §14); the "
                        "events.jsonl run journal itself rides --save and "
                        "keeps recording epoch/fault/checkpoint events. "
                        "Telemetry is a handful of fused scalar adds read "
                        "once per epoch, so this exists for A/B "
                        "measurement, not for speed")
    p.add_argument("--drift-tolerance", type=float, default=0.25,
                   dest="drift_tolerance",
                   help="relative band over the predicted per-epoch "
                        "contraction factor before an epoch counts as "
                        "out-of-plan")
    p.add_argument("--drift-patience", type=int, default=2,
                   dest="drift_patience",
                   help="consecutive out-of-band epochs before a drift "
                        "event is journaled")
    p.add_argument("--no-sync-init", action="store_true",
                   help="skip the initial AllReduce sync of the per-worker "
                        "inits: starts the fleet at a visible disagreement "
                        "spread (consensus-dominant diagnostics runs)")
    p.add_argument("--alpha-override", type=float, default=None,
                   dest="alpha_override",
                   help="execute the schedule with this mixing weight while "
                        "the drift monitor keeps predicting with the solved "
                        "alpha — the deliberate mis-plan knob for chaos-"
                        "testing drift detection (obs_tpu.py drift)")
    p.add_argument("--trace-dir", default=None, dest="trace_dir",
                   help="capture one epoch (--trace-epoch) as a "
                        "jax.profiler trace under this dir; on the chip the "
                        "run journals a device_scopes event (device time by "
                        "program and device_span) and writes scopes.json "
                        "there; obs_tpu.py profile DIR prints the table "
                        "(DESIGN.md §15)")
    p.add_argument("--trace-epoch", type=int, default=1, dest="trace_epoch",
                   help="which epoch to trace (clamped to the run; default "
                        "1 so compiles don't drown the steady-state window)")
    p.add_argument("--platform", default=None, choices=["cpu", "tpu"],
                   help="pin the JAX platform before first use; default: "
                        "JAX's own choice (JAX_PLATFORMS, else the "
                        "accelerator if there is one)")
    args = p.parse_args(argv)

    if args.scan_chunk < 0:
        p.error("--scan-chunk must be >= 0 (0 = whole-epoch scan)")
    if args.compress and args.centralized:
        p.error("--compress and --centralized are mutually exclusive")
    communicator = ("choco" if args.compress
                    else "centralized" if args.centralized else "decen")
    cfg = TrainConfig(
        name=args.name, description=args.description, model=args.model,
        dataset=args.dataset, batch_size=args.bs, non_iid=args.noniid,
        augment=args.augment, datasetRoot=args.datasetRoot,
        model_kwargs=_json_argument(args.model_kwargs),
        lr=args.lr, momentum=args.momentum, nesterov=args.nesterov,
        epochs=args.epochs, warmup=args.warmup,
        num_workers=args.numworkers,
        graphid=None if args.graphid < 0 else args.graphid,
        topology=args.topology, matcha=args.matcha, budget=args.budget,
        plan=args.plan, seed=args.seed, communicator=communicator,
        compress_ratio=args.ratio, compressor=args.compressor,
        consensus_lr=args.consensus_lr,
        compress_warmup_epochs=args.compress_warmup_epochs,
        gossip_backend=args.backend,
        overlap=args.overlap, staleness=args.staleness,
        local_steps=args.local_steps,
        wire_dtype=args.wire_dtype, save=args.save, savePath=args.savePath,
        checkpoint_every=args.checkpoint_every, resume=args.resume,
        fault_plan=args.fault_plan, max_recoveries=args.max_recoveries,
        recovery_lr_backoff=args.recovery_lr_backoff,
        membership_trace=args.membership_trace,
        membership_hysteresis=args.membership_hysteresis,
        membership_bootstrap=args.membership_bootstrap,
        membership_live=args.membership_live,
        membership_deadline=args.membership_deadline,
        telemetry=not args.no_telemetry,
        health=not args.no_health,
        drift_tolerance=args.drift_tolerance,
        drift_patience=args.drift_patience,
        sync_init=not args.no_sync_init,
        alpha_override=args.alpha_override,
        eval_every=args.eval_every,
        eval_batch=args.eval_batch,
        fixed_mode=args.fixed_mode,
        measure_comm_split=not args.no_comm_split,
        scan_chunk=args.scan_chunk or None,
        remat=args.remat,
        grad_chunk=args.grad_chunk or None,
        trace_dir=args.trace_dir,
        trace_epoch=args.trace_epoch,
    )
    return cfg, args.platform


def main(argv=None):
    cfg, platform = _parse(argv)
    from matcha_tpu.utils import announce_devices, pin_platform

    pin_platform(platform)
    announce_devices(cfg.devices)
    result = train(cfg)
    for h in result.history:
        print(json.dumps({k: round(v, 5) if isinstance(v, float) else v
                          for k, v in h.items()}))
    return result


if __name__ == "__main__":
    main()
