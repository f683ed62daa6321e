"""Operations that a step needs, from shapes alone: the numerator of the
utilization metrics.  No recomputation is counted."""

import importlib


def forward_macs(config) -> int:
    arch = importlib.import_module(
        f"chipbench.reference.{config['reference']}")
    return arch.forward_macs(config["sizes"])


def fwd_bwd_flops_per_step(config, workers: int, batch: int) -> float:
    """Forward plus backward (twice the forward) over every worker's batch:
    3 x 2 x MACs x samples."""
    return 3.0 * 2.0 * forward_macs(config) * workers * batch
