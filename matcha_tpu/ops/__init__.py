"""Device-level primitive ops: batched flatten/unflatten, compressors, and
the expert layer's grouped matrix products (``ops.grouped``, imported by the
models that use it)."""

from .compress import (
    COMPRESSOR_NAMES,
    DETERMINISTIC_COMPRESSORS,
    batched_random_k,
    batched_top_k,
    batched_top_k_approx,
    batched_top_k_q8,
    quantize_stochastic,
    dense_from_sparse,
    scatter_rows,
    select_compressor,
    top_k_ratio_size,
)
from .flatten import WorkerFlattener, make_flattener

__all__ = [
    "COMPRESSOR_NAMES",
    "DETERMINISTIC_COMPRESSORS",
    "WorkerFlattener",
    "batched_random_k",
    "batched_top_k",
    "batched_top_k_approx",
    "batched_top_k_q8",
    "quantize_stochastic",
    "dense_from_sparse",
    "make_flattener",
    "scatter_rows",
    "select_compressor",
    "top_k_ratio_size",
]
