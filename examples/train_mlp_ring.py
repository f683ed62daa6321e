#!/usr/bin/env python
"""Minimum end-to-end slice (SURVEY.md §7): D-PSGD on an 8-worker ring.

MLP on synthetic data, 8 virtual workers on an 8-device mesh: the script
asks for 8 virtual CPU devices itself.  Asserts that training loss decreases and the
replicas' parameter disagreement shrinks — the two invariants decentralized
SGD must deliver.
"""

import os
import sys

sys.path.insert(0, os.path.dirname(os.path.dirname(os.path.abspath(__file__))))

N_WORKERS = 8

import jax

# 8 virtual CPU devices, pinned before the backend initializes (as
# tests/conftest.py does)
jax.config.update("jax_platforms", "cpu")
jax.config.update("jax_num_cpu_devices", N_WORKERS)

import numpy as np

from matcha_tpu import topology as tp
from matcha_tpu.train import TrainConfig, train


def main():
    assert len(jax.devices()) >= N_WORKERS, "need an 8-device mesh"
    cfg = TrainConfig(
        name="mlp-ring-demo",
        model="mlp",
        dataset="synthetic",
        graphid=5,  # the zoo's 8-node ring (reference util.py:336-337)
        num_workers=N_WORKERS,
        matcha=False,  # D-PSGD fixed schedule
        epochs=4,
        batch_size=16,
        lr=0.1,
        warmup=False,
        seed=0,
        save=False,
    )
    result = train(cfg)
    losses = [h["loss"] for h in result.history]
    disagreement = [h["disagreement"] for h in result.history]
    print("losses:", [round(float(l), 4) for l in losses])
    print("disagreement:", [round(float(d), 6) for d in disagreement])
    assert losses[-1] < losses[0], "training loss must decrease"
    # Replicas start identical (init allreduce), gradients inject disagreement
    # and gossip contracts it: it must stay bounded and fall from its peak as
    # the loss flattens.
    assert disagreement[-1] < max(disagreement), "gossip must contract disagreement"
    assert max(disagreement) < 0.1, "disagreement must stay bounded"
    print("OK: loss decreased and gossip kept replicas in consensus")


if __name__ == "__main__":
    main()
