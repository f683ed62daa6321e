"""Communicator layer: per-iteration consensus transforms
(decen / choco / centralized / none), jit- and scan-compatible."""

from typing import Optional

from .base import Communicator
from .centralized import make_centralized, make_none
from .choco import make_choco
from .decen import make_decen

__all__ = [
    "Communicator",
    "make_centralized",
    "make_choco",
    "make_decen",
    "make_none",
    "select_communicator",
]


def select_communicator(
    name: str,
    schedule=None,
    mesh=None,
    ratio: float = 0.9,
    consensus_lr: float = 0.1,
    backend: str = "auto",
    compressor: str = "top_k",
    seed: int = 0,
    wire_dtype=None,
) -> Communicator:
    """Registry keyed by the reference's algorithm names (README.md:17-53):
    ``decen`` (D-PSGD/MATCHA), ``choco`` (CHOCO-SGD), ``centralized``
    (AllReduce baseline), ``none``.  ``compressor`` selects CHOCO's message
    compressor from the ops registry (``matcha_tpu.ops.COMPRESSOR_NAMES``);
    ``seed`` seeds the stochastic compressors' PRNG carry.  ``wire_dtype``
    (``"f32"``/``"bf16"``) narrows the exchanged tensors at the gossip
    boundary for every communicator except ``none`` (which exchanges
    nothing)."""
    if name == "decen":
        return make_decen(schedule, mesh=mesh, backend=backend,
                          wire_dtype=wire_dtype)
    if name == "choco":
        if backend == "skip":
            raise ValueError(
                "choco has no 'skip' backend (its exchange is already "
                "sparse); use communicator='decen' with backend='skip', or "
                "a masked choco backend")
        # map the gossip backend vocabulary onto choco's two forms: the
        # dense/gather spellings are both the single-array batched path
        choco_backend = backend if backend in ("auto", "shard_map") else "batched"
        return make_choco(schedule, ratio=ratio, consensus_lr=consensus_lr,
                          mesh=mesh, backend=choco_backend,
                          compressor=compressor, seed=seed,
                          wire_dtype=wire_dtype)
    if name == "centralized":
        return make_centralized(wire_dtype=wire_dtype)
    if name == "none":
        return make_none()
    raise KeyError(f"unknown communicator '{name}'")
