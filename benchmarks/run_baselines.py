#!/usr/bin/env python
"""Run the five BASELINE.json reference configurations end-to-end.

The reference publishes no numbers (BASELINE.md), so what this harness
establishes is that every configuration the reference can express runs in
this framework, and what its measured comp/comm/epoch split and accuracy
trajectory are on the current hardware.  Real CIFAR/ImageNet data is not
downloadable in this environment; synthetic stand-ins with the right input
shapes exercise the identical compiled program shapes (model × workers ×
schedule).  Three tiers:

* ``--scale smoke``    — 1-2 epochs, chance-level accuracy by design: a
  **compile-smoke regression gate** only (the program shapes build, step,
  and record).  It demonstrates nothing about learning.
* ``--scale converge`` — the VERDICT r2 item-3 tier: same models and worker
  counts, separable synthetic clusters, enough epochs that every run must
  end far above chance (target ≥0.9); per-epoch accuracy curves are recorded
  so the MATCHA-vs-D-PSGD ordering is visible.  Artifact:
  ``baselines_converge.jsonl``.
* ``--scale full --data-root <npz dir>`` — the real experiment on a machine
  with the actual datasets.

Output: one JSON line per config with the recorder's series.
"""

from __future__ import annotations

import argparse
import dataclasses
import json
import os
import signal
import sys
import time

sys.path.insert(0, os.path.dirname(os.path.dirname(os.path.abspath(__file__))))

from matcha_tpu.train import TrainConfig, train  # noqa: E402

# The five reference configs (BASELINE.md; reference flags in parentheses).
CONFIGS = {
    # 1. ResNet / CIFAR-10, 8 workers, D-PSGD FixedProcessor graphid 0
    "dpsgd-resnet-cifar10-8w": TrainConfig(
        name="dpsgd-resnet-cifar10-8w", model="res", dataset="cifar10",
        num_workers=8, graphid=0, matcha=False, fixed_mode="all",
        lr=0.8, batch_size=32,
    ),
    # 2. VGG-16 / CIFAR-10, 8 workers, MATCHA budget 0.5
    "matcha-vgg16-cifar10-8w": TrainConfig(
        name="matcha-vgg16-cifar10-8w", model="VGG", dataset="cifar10",
        num_workers=8, graphid=0, matcha=True, budget=0.5,
        lr=0.8, batch_size=32,
    ),
    # 3. WRN-28-10 / CIFAR-100, 16 workers, MATCHA on the ER graph (zoo id 4)
    "matcha-wrn-cifar100-16w": TrainConfig(
        name="matcha-wrn-cifar100-16w", model="wrn", dataset="cifar100",
        num_workers=16, graphid=4, matcha=True, budget=0.5,
        lr=0.8, batch_size=32,
    ),
    # 4. ResNet / CIFAR-10, 64 workers, CHOCO + top-k
    "choco-resnet-cifar10-64w": TrainConfig(
        name="choco-resnet-cifar10-64w", model="resnet20", dataset="cifar10",
        num_workers=64, graphid=None, topology="geometric",
        matcha=True, budget=0.5, communicator="choco", compress_ratio=0.9,
        lr=0.8, batch_size=32,
    ),
    # 5. ResNet-50 / ImageNet, 256 workers, MATCHA sweep point
    "matcha-resnet50-imagenet-256w": TrainConfig(
        name="matcha-resnet50-imagenet-256w", model="resnet50",
        dataset="imagenet", num_workers=256, graphid=None,
        topology="geometric", matcha=True, budget=0.5,
        lr=0.8, batch_size=8,
    ),
    # Diagnostic (not one of the five BASELINE configs): config 4 without
    # compression — same 64 workers / ResNet-20 / MATCHA-0.5 geometric
    # graph, decen instead of CHOCO.  Separates "64-way conv training
    # learns in this framework" from "top-k-compressed consensus needs
    # bigger shards/longer horizons" when the config-4 converge runs
    # plateau (see CONVERGE_OVERRIDES note).
    "matcha-resnet-cifar10-64w-diag": TrainConfig(
        name="matcha-resnet-cifar10-64w-diag", model="resnet20",
        dataset="cifar10", num_workers=64, graphid=None,
        topology="geometric", matcha=True, budget=0.5,
        lr=0.8, batch_size=32,
    ),
    # Diagnostic: REAL pixels end to end.  The reference's EMNIST/MLP config
    # (util.py:165-254 + select_model 'mlp', util.py:267-268) on the only real
    # image pixels available without egress — scikit-learn's bundled UCI
    # handwritten digits (1,797 8×8 images; see data/datasets.py uci_digits).
    # Same MATCHA-0.5 gossip machinery as the paper configs; closes the
    # "no real pixels ever trained" gap (VERDICT r3 missing-6) at the scale
    # the environment permits.
    "matcha-mlp-digits-8w": TrainConfig(
        name="matcha-mlp-digits-8w", model="mlp", dataset="digits",
        num_workers=8, graphid=0, matcha=True, budget=0.5,
        lr=0.1, batch_size=16,
    ),
    # Diagnostic: real-RGB-pixel conv configs (VERDICT r4 item 4).  No real
    # CIFAR archive exists in-environment — the repo's CIFAR fixtures are
    # format-faithful NOISE (tests/fixtures/make_fixtures.py) — so
    # photo_patches (one class per real photograph baked into
    # site-packages, spatially disjoint train/test crops) is the largest
    # real-pixel conv task obtainable offline.  Shape of the reference's
    # core experiment (train_mpi.py:58-168): ResNet-20, 8 workers, D-PSGD
    # vs MATCHA 0.5 vs all-reduce control, augmentation on.
    "dpsgd-resnet-photo-8w": TrainConfig(
        name="dpsgd-resnet-photo-8w", model="resnet20",
        dataset="photo_patches", num_workers=8, graphid=0, matcha=False,
        fixed_mode="all", lr=0.1, batch_size=32, augment=True,
    ),
    "matcha-resnet-photo-8w": TrainConfig(
        name="matcha-resnet-photo-8w", model="resnet20",
        dataset="photo_patches", num_workers=8, graphid=0, matcha=True,
        budget=0.5, lr=0.1, batch_size=32, augment=True,
    ),
    "central-resnet-photo-8w": TrainConfig(
        name="central-resnet-photo-8w", model="resnet20",
        dataset="photo_patches", num_workers=8, graphid=0, matcha=False,
        communicator="centralized", lr=0.1, batch_size=32, augment=True,
    ),
    # Diagnostic: config 4 with compression warmup (the r5 mitigation for
    # the top-k-10% cold start): ratio ramps 0→0.9 over 4 epochs, then the
    # reference-exact compressed gossip runs.  Same shards/graph as the
    # plain converge rerun, so the pair isolates what warmup buys.
    "choco-resnet-cifar10-64w-warmup": TrainConfig(
        name="choco-resnet-cifar10-64w-warmup", model="resnet20",
        dataset="cifar10", num_workers=64, graphid=None,
        topology="geometric", matcha=True, budget=0.5,
        communicator="choco", compress_ratio=0.9,
        compress_warmup_epochs=4, lr=0.8, batch_size=32,
    ),
    # Diagnostic: the control the r5 warmup A/B is missing (ADVICE r5).
    # Fixed-schedule CHOCO — all matchings every step, γ=0.1 — on the same
    # 64-worker geometric graph: the regime where CHOCO's telescoping-s
    # assumption actually holds (W is constant).  Same 4-epoch compression
    # warmup as the A/B arm, so the compression trajectory is identical and
    # ONLY the schedule differs.  Separates "γ-damped mixing is too slow at
    # 64 workers" (this run also stalls) from "the time-varying-W
    # accumulator cross-terms are the bias" (this run learns while the
    # MATCHA-scheduled one stalls).
    "choco-resnet-cifar10-64w-fixed": TrainConfig(
        name="choco-resnet-cifar10-64w-fixed", model="resnet20",
        dataset="cifar10", num_workers=64, graphid=None,
        topology="geometric", matcha=False, fixed_mode="all",
        communicator="choco", compress_ratio=0.9, consensus_lr=0.1,
        compress_warmup_epochs=4, lr=0.8, batch_size=32,
    ),
    # Diagnostic: the 512-images/worker point of the CHOCO shard-size sweep
    # (64→256→512; VERDICT r4 item 1's alternate done-criterion).  Plain
    # reference semantics (no warmup), γ=0.1.  TPU-window only — ~8 h of
    # pure CPU otherwise.
    "choco-resnet-cifar10-64w-512shard": TrainConfig(
        name="choco-resnet-cifar10-64w-512shard", model="resnet20",
        dataset="cifar10", num_workers=64, graphid=None,
        topology="geometric", matcha=True, budget=0.5,
        communicator="choco", compress_ratio=0.9, lr=0.8, batch_size=32,
    ),
}

SMOKE_OVERRIDES = {
    # synthetic stand-ins with the dataset's input shape; tiny epochs.
    # Accuracy here is chance level BY DESIGN — this tier only gates that the
    # program shapes compile and step (see module docstring).
    "dpsgd-resnet-cifar10-8w": dict(dataset="synthetic_image", epochs=2),
    "matcha-vgg16-cifar10-8w": dict(dataset="synthetic_image", epochs=2),
    "matcha-wrn-cifar100-16w": dict(dataset="synthetic_image", epochs=1,
                                    batch_size=8),
    "choco-resnet-cifar10-64w": dict(dataset="synthetic_image", epochs=1,
                                     batch_size=8),
    "matcha-resnet50-imagenet-256w": dict(dataset="synthetic_image", epochs=1,
                                          batch_size=2, model="resnet20",
                                          num_workers=64),
    "matcha-resnet-cifar10-64w-diag": dict(dataset="synthetic_image", epochs=1,
                                           batch_size=8),
    "matcha-mlp-digits-8w": dict(epochs=2),  # real data IS the smoke payload
    # real pixels ARE the smoke payload here too; tiny crop counts
    "dpsgd-resnet-photo-8w": dict(
        epochs=1, batch_size=8,
        dataset_kwargs={"train_per_class": 32, "test_per_class": 8}),
    "matcha-resnet-photo-8w": dict(
        epochs=1, batch_size=8,
        dataset_kwargs={"train_per_class": 32, "test_per_class": 8}),
    "central-resnet-photo-8w": dict(
        epochs=1, batch_size=8,
        dataset_kwargs={"train_per_class": 32, "test_per_class": 8}),
    "choco-resnet-cifar10-64w-warmup": dict(
        dataset="synthetic_image", epochs=1, batch_size=8,
        compress_warmup_epochs=1),
    "choco-resnet-cifar10-64w-fixed": dict(
        dataset="synthetic_image", epochs=1, batch_size=8,
        compress_warmup_epochs=1),
    "choco-resnet-cifar10-64w-512shard": dict(
        dataset="synthetic_image", epochs=1, batch_size=8),
}

# Converging tier: separable synthetic clusters (the budget_sweep/_miniature
# recipe: separation 40 gives a conv stem a per-pixel signal it can fit
# within a miniature epoch budget), real models and worker counts, lr sized
# for stability on the synthetic task.  Every run must end ≫ chance (0.1).
_CONVERGE_DATA = dict(
    dataset="synthetic_image",
    dataset_kwargs={"num_train": 4096, "num_test": 1024, "separation": 40.0},
    lr=0.05, base_lr=0.05, batch_size=8, eval_every=1,
    # comm split ON (VERDICT r3 weak-2): converge artifacts must carry real
    # comm/encode shares, not 0.0 — costs one extra gossip chain per epoch
    measure_comm_split=True,
)
CONVERGE_OVERRIDES = {
    "dpsgd-resnet-cifar10-8w": dict(_CONVERGE_DATA, epochs=8),
    "matcha-vgg16-cifar10-8w": dict(_CONVERGE_DATA, epochs=8),
    # VERDICT r2 item 3 names these two: real WRN-28-10 at 16 workers and
    # the 64-worker CHOCO ResNet-20 (compressed gossip) must *learn*.
    # remat: WRN-28-10's un-rematted 16-worker vmapped backward is
    # activation-heavy (32x32x160 maps); block remat keeps it inside one
    # v5e's HBM without changing the arithmetic (tested exact)
    "matcha-wrn-cifar100-16w": dict(_CONVERGE_DATA, epochs=8, remat=True),
    # 64 workers need the same *per-worker* data density that converges at
    # 16 workers (256 images each, the budget_sweep/time_to_acc recipe that
    # reaches 0.97): two probes with 64-image shards plateaued at ~0.26
    # regardless of step count (10ep/batch8 = 80 steps and 24ep/batch4 =
    # 384 steps), so the shard size, not the step budget, was the limit.
    # consensus_lr: γ=0.3 with 256-image shards rose to 0.68 by epoch 5 and
    # then DECAYED to 0.44 (r4 committed line — consensus instability
    # compounding at 64 workers; both r3 γ=0.1 probes were stable, merely
    # data-starved), so γ backs off to the reference default 0.1 and the
    # horizon stretches to 12 epochs for the slower-but-stable consensus.
    # The smaller test set keeps single-core eval FLOPs from dominating.
    "choco-resnet-cifar10-64w": dict(
        _CONVERGE_DATA, epochs=12, consensus_lr=0.1,
        dataset_kwargs={"num_train": 16384, "num_test": 256,
                        "separation": 40.0}),
    # 256 workers x 224x224 ResNet-50: remat + 32-worker fwd/bwd slabs keep
    # the folded single-chip program inside HBM (activations dominate)
    "matcha-resnet50-imagenet-256w": dict(_CONVERGE_DATA, epochs=8,
                                          batch_size=4, remat=True,
                                          grad_chunk=32),
    # uncompressed control for the config-4 plateau: same shard size
    # (64 images/worker), same graph/budget — D-PSGD-style dense averaging
    # instead of top-k-10% CHOCO
    "matcha-resnet-cifar10-64w-diag": dict(
        _CONVERGE_DATA, epochs=12, batch_size=4,
        dataset_kwargs={"num_train": 4096, "num_test": 256,
                        "separation": 40.0}),
    # real pixels (UCI digits), NOT the synthetic recipe: the dataset is the
    # point of this config, so only budget/epoch knobs are tiered here
    "matcha-mlp-digits-8w": dict(epochs=30, eval_every=1,
                                 measure_comm_split=True),
    # real RGB pixels (photo_patches), NOT the synthetic recipe: default
    # build (768+128 crops/class × 8 photos), augmentation on, comm split
    # on for the MATCHA run (conv-model comm-share data, VERDICT r4 item 5)
    "dpsgd-resnet-photo-8w": dict(epochs=15, eval_every=1, lr=0.1,
                                  measure_comm_split=False),
    "matcha-resnet-photo-8w": dict(epochs=15, eval_every=1, lr=0.1,
                                   measure_comm_split=True),
    "central-resnet-photo-8w": dict(epochs=15, eval_every=1, lr=0.1,
                                    measure_comm_split=False),
    # config-4 shards/graph + 4-epoch ratio ramp; γ stays at the reference
    # default (the γ=0.3 run's late-epoch collapse was compression×large-γ —
    # with warmup the dense phase does the fast consensus instead)
    "choco-resnet-cifar10-64w-warmup": dict(
        _CONVERGE_DATA, epochs=12, consensus_lr=0.1,
        compress_warmup_epochs=4,
        dataset_kwargs={"num_train": 16384, "num_test": 256,
                        "separation": 40.0}),
    # same data/shards and the same 4-epoch ratio ramp as the warmup-quick
    # A/B arm (the setup where dense gossip reaches 0.9513 and
    # MATCHA-scheduled CHOCO stalls at 0.135) — only the schedule differs:
    # fixed all-matchings W every step
    "choco-resnet-cifar10-64w-fixed": dict(
        _CONVERGE_DATA, epochs=12, batch_size=4, consensus_lr=0.1,
        compress_warmup_epochs=4,
        dataset_kwargs={"num_train": 4096, "num_test": 256,
                        "separation": 40.0}),
    # 512 images/worker, same step budget per image (epochs scale down is
    # NOT applied: more steps is the point of bigger shards)
    "choco-resnet-cifar10-64w-512shard": dict(
        _CONVERGE_DATA, epochs=12, consensus_lr=0.1,
        dataset_kwargs={"num_train": 32768, "num_test": 256,
                        "separation": 40.0}),
}

# Exact mirror of the uncompressed diag control's converge setup (64-image
# shards, batch 4, 12 epochs — the config where dense gossip reaches 0.9513)
# but CHOCO + 4-epoch compression warmup: the tightest A/B for what warmup
# buys against the committed 0.26 plateau rows, and small enough to finish
# on the 1-core host.  Registered as its own converge entry.
CONFIGS["choco-resnet-cifar10-64w-warmup-quick"] = dataclasses.replace(
    CONFIGS["choco-resnet-cifar10-64w-warmup"],
    name="choco-resnet-cifar10-64w-warmup-quick")
SMOKE_OVERRIDES["choco-resnet-cifar10-64w-warmup-quick"] = dict(
    SMOKE_OVERRIDES["choco-resnet-cifar10-64w-warmup"])
CONVERGE_OVERRIDES["choco-resnet-cifar10-64w-warmup-quick"] = dict(
    _CONVERGE_DATA, epochs=12, batch_size=4, consensus_lr=0.1,
    compress_warmup_epochs=4,
    dataset_kwargs={"num_train": 4096, "num_test": 256, "separation": 40.0})


def main():
    p = argparse.ArgumentParser()
    p.add_argument("--scale", choices=["smoke", "converge", "full"],
                   default="smoke")
    p.add_argument("--data-root", default=None, help="dir of .npz datasets (full scale)")
    p.add_argument("--only", default=None, help="comma-separated config names")
    p.add_argument("--target", type=float, default=0.9,
                   help="converge tier: accuracy every run must reach")
    p.add_argument("--out", default=None,
                   help="also append JSON lines to this file")
    p.add_argument("--platform", default=None, choices=["cpu", "tpu"],
                   help="pin the JAX platform before first use (default: "
                        "JAX's own choice)")
    p.add_argument("--no-scan-epoch", action="store_true",
                   help="compile one train step instead of the whole epoch "
                        "scan — slower steps, minutes less XLA-CPU compile; "
                        "use for converge runs on a 1-core host")
    args = p.parse_args()
    from matcha_tpu.utils import pin_platform

    pin_platform(args.platform)

    names = list(CONFIGS) if args.only is None else args.only.split(",")
    failures = 0

    # Best-effort: convert a timeout-wrapper's SIGTERM into an exception the
    # per-config handler below records (and flushes) before the process
    # exits.  Python only delivers the signal at a bytecode boundary — TERM
    # arriving mid-XLA-call stays pending
    # until the C++ call returns, and `timeout -k` may SIGKILL first; the
    # `started` breadcrumb printed before train() is the guaranteed trace.
    def _sigterm(signum, frame):
        raise TimeoutError("SIGTERM (outer timeout wrapper)")

    signal.signal(signal.SIGTERM, _sigterm)
    out_f = None  # before the try: open() raising must not mask itself as UnboundLocalError
    try:
        out_f = open(args.out, "a") if args.out else None
        for cname in names:
            cfg = CONFIGS[cname]
            if args.scale == "smoke":
                cfg = dataclasses.replace(cfg, warmup=False, seed=0,
                                          **SMOKE_OVERRIDES[cname])
            elif args.scale == "converge":
                cfg = dataclasses.replace(cfg, warmup=False, seed=0,
                                          **CONVERGE_OVERRIDES[cname])
            elif args.data_root is not None:  # full scale with real npz data
                cfg = dataclasses.replace(
                    cfg, datasetRoot=os.path.join(args.data_root, f"{cfg.dataset}.npz")
                )
            if args.no_scan_epoch:
                cfg = dataclasses.replace(cfg, scan_epoch=False)
            t0 = time.time()
            timed_out = False
            # stderr breadcrumb (stdout and the JSONL stay records-only: a
            # `> results.jsonl` caller must not get comment lines): a
            # SIGKILLed run still shows which config was in flight
            print(f"# started {cname} ({args.scale})", file=sys.stderr,
                  flush=True)
            try:
                hist = train(cfg).history
            except Exception as e:  # one config failing must not eat the rest
                failures += 1
                timed_out = isinstance(e, TimeoutError)
                record = {
                    "config": cname, "scale": args.scale,
                    "wall_s": round(time.time() - t0, 2),
                    "error": f"{type(e).__name__}: {e}",
                }
            else:
                record = {
                    "config": cname,
                    "scale": args.scale,
                    "epochs": len(hist),
                    "wall_s": round(time.time() - t0, 2),
                    "final_loss": round(hist[-1]["loss"], 4),
                    "final_test_acc": round(hist[-1]["test_acc_mean"], 4),
                    "epoch_time_s": round(hist[-1]["epoch_time"], 3),
                    "comm_time_s": round(hist[-1]["comm_time"], 3),
                    "comm_share": round(
                        hist[-1]["comm_time"] / max(hist[-1]["epoch_time"], 1e-9), 4
                    ),
                    "comm_split_measured": cfg.measure_comm_split,
                }
                if args.scale == "converge":
                    curve = [round(float(h["test_acc_mean"]), 4) for h in hist]
                    reached = next((i + 1 for i, a in enumerate(curve)
                                    if a >= args.target), None)
                    record.update({
                        "test_acc_curve": curve,
                        "target_acc": args.target,
                        "target_reached": reached is not None,
                        "epochs_to_target": reached,
                    })
                    if reached is None:
                        # the tier's contract is "every run learns to
                        # target" — a miss is a gate failure, not a pass
                        failures += 1
            line = json.dumps(record)
            print(line, flush=True)
            if out_f:
                out_f.write(line + "\n")
                out_f.flush()  # a killed run must not eat completed configs
            if timed_out:
                break  # the wrapper wants us gone; don't start another config
    finally:
        if out_f:
            out_f.close()
    return 1 if failures else 0


if __name__ == "__main__":
    sys.exit(main())
