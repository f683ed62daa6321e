"""The test harness's own promises (``tests/conftest.py``).

A test process, and every Python child it starts, takes ``BLAS_THREADS``
threads of the machine for its linear algebra and not one a core: it holds
wherever an import is reordered or a new plugin loads NumPy earlier still.
And the files that start first are files: a renamed one would fall back
among the short ones without a word.  The compile cache every entry point
and this harness share is placed by one seam (``utils/platform.py``)."""

import json
import os
import subprocess
import sys
from pathlib import Path

import pytest
import threadpoolctl

REPO = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))

_POOLS = ("import json, numpy, scipy.linalg, threadpoolctl; "
          "print(json.dumps(threadpoolctl.threadpool_info()))")


@pytest.fixture
def harness(request):
    """``tests/conftest.py`` as pytest loaded it."""
    return request.config.pluginmanager.getplugin(
        str(Path(__file__).with_name("conftest.py")))


def _blas_pools(info):
    pools = [(p["filepath"], p["num_threads"]) for p in info
             if p["user_api"] == "blas"]
    assert pools, info
    return pools


def test_blas_pools_of_the_process_and_of_a_child_hold_the_cap(harness):
    import numpy  # noqa: F401
    import scipy.linalg  # noqa: F401  (SciPy carries an OpenBLAS of its own)

    cap = harness.BLAS_THREADS
    here = _blas_pools(threadpoolctl.threadpool_info())
    assert [n for _, n in here] == [cap] * len(here), here
    child = subprocess.run([sys.executable, "-c", _POOLS], check=True,
                           capture_output=True, text=True, timeout=120)
    there = _blas_pools(json.loads(child.stdout))
    assert len(there) == len(here)
    assert [n for _, n in there] == [cap] * len(there), there


def test_the_files_that_start_first_are_files(harness):
    names = harness.LONGEST_FIRST
    assert len(set(names)) == len(names)
    missing = [name for name in names
               if not Path(__file__).with_name(name + ".py").is_file()]
    assert not missing, missing


def test_compile_cache_placed_by_env_else_fixed_in_tree(monkeypatch):
    """The one compile-cache seam every entry point passes: with
    JAX_COMPILATION_CACHE_DIR set, nothing sets a cache directory in code
    (JAX reads the variable itself); unset, it is <checkout>/.jax_cache —
    the same path from any process and any working directory."""
    import jax

    from matcha_tpu.utils import compile_cache_dir, pin_platform

    calls = []
    monkeypatch.setattr(jax.config, "update",
                        lambda key, value: calls.append((key, value)))
    monkeypatch.setenv("JAX_COMPILATION_CACHE_DIR", "/placed/from/outside")
    pin_platform(None)
    assert "jax_compilation_cache_dir" not in dict(calls)
    assert compile_cache_dir() == "/placed/from/outside"

    calls.clear()
    monkeypatch.delenv("JAX_COMPILATION_CACHE_DIR")
    pin_platform(None)
    in_tree = os.path.join(REPO, ".jax_cache")
    assert dict(calls)["jax_compilation_cache_dir"] == in_tree
    assert "jax_platforms" not in dict(calls)
    env = {k: v for k, v in os.environ.items()
           if k != "JAX_COMPILATION_CACHE_DIR"}
    other = subprocess.run(
        [sys.executable, "-c",
         "from matcha_tpu.utils import compile_cache_dir; "
         "print(compile_cache_dir())"],
        capture_output=True, text=True, timeout=120, cwd="/",
        env={**env, "PYTHONPATH": REPO})
    assert other.stdout.strip() == in_tree, other.stderr[-500:]
