#!/usr/bin/env python
"""Chaos on the 8-worker ring: kill a worker mid-run, drop 20% of links,
poison one replica with NaN — and watch training survive, heal, and land
within a whisker of the fault-free run.

This is the resilience subsystem end to end (DESIGN.md §8):

* the fault plan compiles into static per-step arrays, like the schedule;
* a dead worker's gossip edges become self-loops (the realized mixing stays
  doubly stochastic over survivors), and on revival it is healed from the
  masked gossip average of its alive neighbors;
* a NaN emitter is detected, quarantined, and healed inside the same
  compiled step — the poison never reaches another replica.

Runs on CPU in under a minute.  The same plan can be handed to the CLI::

    python train_tpu.py --name chaos --model mlp --graphid 5 --epoch 3 \
        --lr 0.1 --no-warmup --fault-plan plan.json --max-recoveries 2
"""

import os
import sys

sys.path.insert(0, os.path.dirname(os.path.dirname(os.path.abspath(__file__))))

# runs on the CPU unless JAX_PLATFORMS asks for something else (=tpu)
os.environ.setdefault("JAX_PLATFORMS", "cpu")

from matcha_tpu.resilience import FaultEvent, FaultPlan
from matcha_tpu.train import TrainConfig, train


def main():
    # 8 workers x 16 batches/epoch: steps 16-31 are epoch 1
    plan = FaultPlan(name="chaos-ring", events=(
        FaultEvent(kind="dead", worker=3, start=16, stop=32),
        FaultEvent(kind="nan", worker=5, start=20),
        FaultEvent(kind="flaky_link", start=0, drop_prob=0.2, seed=7),
    ))
    base = dict(
        name="chaos", model="mlp", dataset="synthetic", num_workers=8,
        graphid=5, batch_size=16, epochs=3, lr=0.1, warmup=False,
        matcha=True, budget=0.75, seed=3, save=False,
        measure_comm_split=False,
    )
    print("== chaos run: dead worker 3 (epoch 1), NaN emitter on worker 5, "
          "20% link drops ==")
    chaos = train(TrainConfig(fault_plan=plan, max_recoveries=2, **base))
    for h in chaos.history:
        print(f"  epoch {h['epoch']}: loss {h['loss']:.4f}  "
              f"alive {h['alive_workers']:.0f}/8  "
              f"healed/step {h['healed']:.3f}  "
              f"survivor disagreement {h['disagreement']:.2e}")
    print("  fault ledger:",
          [e["kind"] for e in chaos.recorder.faults])

    print("== fault-free control ==")
    ctl = train(TrainConfig(**base))
    for h in ctl.history:
        print(f"  epoch {h['epoch']}: loss {h['loss']:.4f}  "
              f"disagreement {h['disagreement']:.2e}")

    ratio = chaos.history[-1]["disagreement"] / ctl.history[-1]["disagreement"]
    print(f"final disagreement ratio chaos/control: {ratio:.2f}x "
          f"(acceptance bar: <= 2x)")
    assert ratio <= 2.0, ratio
    print("survived, healed, converged.")


if __name__ == "__main__":
    main()
