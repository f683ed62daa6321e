"""Test harness: run JAX on 8 virtual CPU devices so shard_map/ppermute
semantics are exercised without a TPU pod (SURVEY.md §4).

Pinned through ``jax.config`` — backends initialize lazily, so this holds
whatever ``JAX_PLATFORMS`` says and however early jax was imported."""

import os

os.environ.setdefault("JAX_ENABLE_X64", "0")

import jax
import pytest

jax.config.update("jax_platforms", "cpu")
jax.config.update("jax_num_cpu_devices", 8)


@pytest.fixture
def small_leaves(monkeypatch):
    """The leaves route's shape rule with its size and padding constants
    opened up, so that the leaves of a test-sized model pass it (what a
    shape must hold of the tree, ``_LEAF_SHAPE_SHARE``, stays)."""
    from matcha_tpu.parallel import pallas_gossip

    monkeypatch.setattr(pallas_gossip, "_LEAF_MIN_ELEMENTS", 1)
    monkeypatch.setattr(pallas_gossip, "_LEAF_PAD_SHARE", 1e9)
