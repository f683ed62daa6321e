"""Shared base config for the miniature paper-scale experiments.

budget_sweep.py and time_to_acc.py make claims that are only meaningful if
they run the *same* experiment (model, data, workers, topology, lr, seed) —
budget_sweep compares accuracy across budgets, time_to_acc compares
wall-clock across communicators at one budget.  This helper is the single
source of truth for that shared setup; each harness overrides only the axis
it sweeps.
"""

from __future__ import annotations

import os
import sys

sys.path.insert(0, os.path.dirname(os.path.dirname(os.path.abspath(__file__))))

from matcha_tpu.train import TrainConfig  # noqa: E402


def miniature_config(name: str, epochs: int, **overrides) -> TrainConfig:
    """ResNet-20 on synthetic CIFAR-shaped clusters, 16 workers, zoo
    geometric graph (graphid 2) — the miniature stand-in for the paper's
    CIFAR-10 experiments, sized to finish in minutes on one TPU chip."""
    base = dict(
        name=name,
        model="resnet20", dataset="synthetic_image", batch_size=8,
        # stronger cluster separation: CIFAR-sized convnets need a per-pixel
        # signal a 3×3-local stem can pick up within a miniature epoch budget
        dataset_kwargs={"num_train": 4096, "num_test": 1024, "separation": 40.0},
        num_workers=16, graphid=2, fixed_mode="all",
        lr=0.05, base_lr=0.05, warmup=False, epochs=epochs,
        decay_epochs=(int(epochs * 0.6), int(epochs * 0.8)),
        save=False, eval_every=1, measure_comm_split=True, seed=1,
    )
    base.update(overrides)
    return TrainConfig(**base)


def timing_stats(values):
    """Mean plus the observed cross-rep noise band for a wall-clock quantity.

    A shared chip showed ±10-15% run-to-run noise (VERDICT r2 item 7): a
    claimed 1.1-1.2× speedup is meaningless without the band that could
    manufacture or erase it, so every committed timing carries its reps and
    ``band = (max − min) / mean``."""
    vals = [float(v) for v in values]
    mean = sum(vals) / len(vals)
    return {
        "mean": round(mean, 4),
        "reps": [round(v, 4) for v in vals],
        "band": round((max(vals) - min(vals)) / max(mean, 1e-9), 4),
    }


def ratio_range(numers, denoms):
    """[worst, best] ratio over rep pairings — the honest bounds a
    mean-over-mean ratio lives inside."""
    lo = min(numers) / max(max(denoms), 1e-9)
    hi = max(numers) / max(min(denoms), 1e-9)
    return [round(lo, 3), round(hi, 3)]
