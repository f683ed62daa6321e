"""JAX platform pinning and the one compile-cache seam.

Every entry point that compiles (``train_tpu.py``, ``chip_smoke.py``,
``python -m matcha_tpu.serve.trainer``, the benchmark harnesses) passes
through :func:`pin_platform` before its first backend
use, so they all share one persistent XLA compile cache:

* ``JAX_COMPILATION_CACHE_DIR`` set — JAX reads it itself; nothing here
  sets a cache directory in code, so the environment places the cache.
* unset — ``<checkout>/.jax_cache``, derived from this package's own
  location.  The path is part of every cache key, so it is fixed: never
  the home directory, a temp name, a pid or the time.
"""

from __future__ import annotations

import os
import sys
from pathlib import Path
from typing import Optional

__all__ = ["CACHE_ENV", "announce_devices", "compile_cache_dir",
           "pin_platform"]

CACHE_ENV = "JAX_COMPILATION_CACHE_DIR"

#: the in-tree default, listed in ``.gitignore``
_REPO_CACHE = Path(__file__).resolve().parents[2] / ".jax_cache"


def compile_cache_dir() -> str:
    """Where the persistent compile cache lives for this process."""
    return os.environ.get(CACHE_ENV) or str(_REPO_CACHE)


def pin_platform(name: Optional[str]) -> None:
    """Place the compile cache, then pin the JAX platform
    (``"cpu"``/``"tpu"``) when ``name`` is given.

    ``None`` keeps the environment's default platform.  Must run before
    the first ``jax.devices()``/jit — jax.config cannot retarget an
    initialized backend.
    """
    import jax

    if not os.environ.get(CACHE_ENV):
        jax.config.update("jax_compilation_cache_dir", str(_REPO_CACHE))
    # cache every program, however quick its compile: a second process of
    # the same command should compile nothing the first one did
    jax.config.update("jax_persistent_cache_min_compile_time_secs", 0.0)
    if name:
        jax.config.update("jax_platforms", name)


def announce_devices(mesh_size: Optional[int] = None) -> None:
    """One stderr line naming what this process is about to train on:
    platform, ``device_kind``, device count, and the worker-mesh size
    (``TrainConfig.devices``; ``None`` means every device)."""
    import jax

    devices = jax.devices()
    print(f"# devices: platform={devices[0].platform} "
          f"device_kind={devices[0].device_kind!r} count={len(devices)} "
          f"mesh={len(devices) if mesh_size is None else mesh_size}",
          file=sys.stderr, flush=True)
