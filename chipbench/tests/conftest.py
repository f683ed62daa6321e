"""Tests of the benchmark itself: ``python -m pytest chipbench/tests``, on the
CPU with four virtual devices.  Outside the repo's tier-1 suite."""

import os
import sys
from pathlib import Path

os.environ.setdefault("JAX_PLATFORMS", "cpu")
sys.path.insert(0, str(Path(__file__).resolve().parents[2]))

import jax  # noqa: E402

jax.config.update("jax_num_cpu_devices", 4)
