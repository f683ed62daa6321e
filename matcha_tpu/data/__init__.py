"""Data layer: partitioning across virtual workers, datasets, batched loading."""

from .datasets import (
    Dataset,
    NORMALIZATION,
    WorkerBatches,
    augment_crop_flip,
    judged_positions,
    load_npz,
    load_tokens,
    normalize,
    normalized_zero,
    synthetic_classification,
    photo_patches,
    synthetic_images,
    uci_digits,
)
from .partition import (
    partition_fractions,
    partition_indices,
    partition_label_skew,
    partition_uniform,
)

__all__ = [
    "Dataset",
    "NORMALIZATION",
    "WorkerBatches",
    "augment_crop_flip",
    "judged_positions",
    "load_npz",
    "load_tokens",
    "normalize",
    "normalized_zero",
    "partition_fractions",
    "partition_indices",
    "partition_label_skew",
    "partition_uniform",
    "synthetic_classification",
    "photo_patches",
    "synthetic_images",
    "uci_digits",
]
