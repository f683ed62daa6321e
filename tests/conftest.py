"""Test harness: run JAX on 8 virtual CPU devices so shard_map/ppermute
semantics are exercised without a TPU pod (SURVEY.md §4).

Pinned through ``jax.config`` — backends initialize lazily, so this holds
whatever ``JAX_PLATFORMS`` says and however early jax was imported."""

import os

os.environ.setdefault("JAX_ENABLE_X64", "0")

import jax

jax.config.update("jax_platforms", "cpu")
jax.config.update("jax_num_cpu_devices", 8)
