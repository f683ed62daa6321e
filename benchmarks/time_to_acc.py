#!/usr/bin/env python
"""Wall-clock to target test accuracy: D-PSGD vs MATCHA vs CHOCO.

BASELINE.json's metric has two clauses: gossip-steps/sec and
**wall-clock to target test-acc** — the quantity the MATCHA paper actually
optimizes (arXiv:1905.09435: same accuracy, less communication, therefore
less wall-clock per epoch on comm-bound clusters).  This harness measures the
second clause end-to-end on the current hardware: identical model/data/seeds,
three communication strategies, time to first reach a target accuracy.

Setup mirrors budget_sweep.py (ResNet-20, synthetic CIFAR-shaped clusters,
16 workers, zoo geometric graph id 2) so the two artifacts are comparable:

* ``dpsgd``       — FixedProcessor, all matchings every iteration (budget 1)
* ``matcha-0.5``  — MatchaProcessor at half the communication budget
* ``choco-0.5``   — same MATCHA schedule + top-k compression (keep 10%,
                    reference ratio 0.9, /root/reference/train_mpi.py:79)

For each run the artifact records the accuracy curve, the first epoch at
which the target is reached, cumulative wall-clock and cumulative
comm_time to that epoch (the recorder's two-program split, train/loop.py).

Run: ``python benchmarks/time_to_acc.py [--epochs E] [--target A] [--out P]``
(defaults sized for minutes on one TPU chip; CPU works too).
"""

from __future__ import annotations

import argparse
import json
import os
import sys

sys.path.insert(0, os.path.dirname(os.path.dirname(os.path.abspath(__file__))))

from _miniature import miniature_config, ratio_range, timing_stats  # noqa: E402
from matcha_tpu.train import train  # noqa: E402

RUNS = (
    ("dpsgd", dict(matcha=False, budget=1.0)),
    ("matcha-0.5", dict(matcha=True, budget=0.5)),
    ("choco-0.5", dict(matcha=True, budget=0.5, communicator="choco",
                       compress_ratio=0.9, consensus_lr=0.3)),
    # the comm-bound regime: the skip backend pays per *active* matching
    # (lax.cond instead of masking), modeling the per-edge costs of the
    # paper's clusters / DCN hops — here the budget buys measurable time
    ("dpsgd-skip", dict(matcha=False, budget=1.0, gossip_backend="skip")),
    ("matcha-0.5-skip", dict(matcha=True, budget=0.5, gossip_backend="skip")),
)


def run_one(label: str, overrides: dict, epochs: int, target: float,
            reps: int = 2):
    """Run the config ``reps`` times: accuracy is deterministic (same seed,
    same backend — rep 0's curve is recorded), wall-clock is not, so every
    timing field carries its per-rep values and noise band (VERDICT r2
    item 7; a shared chip showed ±10-15% run-to-run)."""
    accs = None
    epoch_times_reps, comm_times_reps = [], []
    for rep in range(reps):
        cfg = miniature_config(
            f"time-to-acc-{label}", epochs,
            description="wall-clock to target test accuracy (BASELINE metric, clause 2)",
            **overrides,
        )
        hist = train(cfg).history
        if accs is None:
            accs = [float(h["test_acc_mean"]) for h in hist]
        epoch_times_reps.append([float(h["epoch_time"]) for h in hist])
        comm_times_reps.append([float(h["comm_time"]) for h in hist])

    reached = next((i for i, a in enumerate(accs) if a >= target), None)
    k = None if reached is None else reached + 1
    ttt = None if k is None else timing_stats(
        [sum(t[:k]) for t in epoch_times_reps])
    ctt = None if k is None else timing_stats(
        [sum(c[:k]) for c in comm_times_reps])
    epoch_mean = timing_stats(
        [sum(t) / len(t) for t in epoch_times_reps])
    comm_mean = timing_stats(
        [sum(c) / len(c) for c in comm_times_reps])
    record = {
        "run": label,
        "target_acc": target,
        "reps": reps,
        "reached": reached is not None,
        "epochs_to_target": k,
        "time_to_target_s": None if ttt is None else ttt["mean"],
        "time_to_target_stats": ttt,
        "comm_time_to_target_s": None if ctt is None else ctt["mean"],
        "comm_time_to_target_stats": ctt,
        "final_test_acc": round(accs[-1], 4),
        "mean_epoch_time_s": epoch_mean["mean"],
        "mean_epoch_time_stats": epoch_mean,
        "mean_comm_time_s": comm_mean["mean"],
        "comm_share": round(comm_mean["mean"] / max(epoch_mean["mean"], 1e-9), 4),
        "test_acc_curve": [round(a, 4) for a in accs],
    }
    print(json.dumps(record), flush=True)
    return record


def main():
    p = argparse.ArgumentParser()
    p.add_argument("--epochs", type=int, default=12)
    p.add_argument("--target", type=float, default=0.97)
    p.add_argument("--reps", type=int, default=2,
                   help="timing repetitions per config (noise band)")
    p.add_argument("--out", default=os.path.join(
        os.path.dirname(os.path.abspath(__file__)), "time_to_acc.json"))
    args = p.parse_args()

    runs = [run_one(label, dict(ov), args.epochs, args.target, reps=args.reps)
            for label, ov in RUNS]

    by = {r["run"]: r for r in runs}
    summary = {
        "experiment": "wall-clock to target test accuracy "
                      "(ResNet-20, synthetic CIFAR shapes, 16 workers, graphid 2)",
        "target_acc": args.target,
        "epochs": args.epochs,
        "reps": args.reps,
        "runs": runs,
    }
    d, m = by.get("dpsgd"), by.get("matcha-0.5")
    if d and m and d["reached"] and m["reached"]:
        # the paper's economy: same target, fraction of the communication;
        # each ratio carries its cross-rep range — a claim inside the band
        # is noise, not a speedup
        summary["matcha_comm_time_ratio_vs_dpsgd"] = round(
            m["comm_time_to_target_s"] / max(d["comm_time_to_target_s"], 1e-9), 3)
        summary["matcha_comm_time_ratio_range"] = ratio_range(
            m["comm_time_to_target_stats"]["reps"],
            d["comm_time_to_target_stats"]["reps"])
        summary["matcha_wall_clock_ratio_vs_dpsgd"] = round(
            m["time_to_target_s"] / max(d["time_to_target_s"], 1e-9), 3)
        summary["matcha_wall_clock_ratio_range"] = ratio_range(
            m["time_to_target_stats"]["reps"],
            d["time_to_target_stats"]["reps"])
        # Context the ratios need: MATCHA's wall-clock economy presumes
        # communication dominates the iteration (the reference's MPI world,
        # where gossip is pickled host-memory sendrecv).  On this backend the
        # gossip chain is an on-chip program and comm_share is ~1-2%, so
        # wall-clock-to-target tracks *epochs*-to-target and a lower budget
        # only trades convergence speed for savings on an already-negligible
        # cost.  The budget knob matters again when the worker axis spans
        # hosts (DCN) — parallel/multihost.py — or for the reference's own
        # execution model; the single-chip artifact records the comm_share
        # that makes this explicit rather than claiming a speedup.
        summary["dpsgd_comm_share"] = d["comm_share"]
        summary["note"] = (
            "comm_share ~0.01-0.02 on one TPU chip: the gossip backend "
            "makes communication nearly free, so time-to-target follows "
            "epochs-to-target; MATCHA's budget economy targets comm-bound "
            "(multi-host/MPI) regimes, which this backend has designed away "
            "at single-chip scale"
        )
    ds, ms = by.get("dpsgd-skip"), by.get("matcha-0.5-skip")
    if ds and ms and ds["reached"] and ms["reached"]:
        # NOTE: the two-program comm timer cannot attribute the skip
        # backend's effect (the cond cost/saving lands inside the train
        # step, not the isolated gossip chain) — the per-step mechanism is
        # pinned by benchmarks/skip_microbench.py; this records the
        # end-to-end outcome only
        summary["skip_backend_wall_clock_ratio"] = round(
            ms["time_to_target_s"] / max(ds["time_to_target_s"], 1e-9), 3)
        summary["skip_backend_wall_clock_ratio_range"] = ratio_range(
            ms["time_to_target_stats"]["reps"],
            ds["time_to_target_stats"]["reps"])
    with open(args.out, "w") as f:
        json.dump(summary, f, indent=1)
    print(f"# wrote {args.out}", file=sys.stderr)


if __name__ == "__main__":
    main()
