"""The test harness's own promises (``tests/conftest.py``).

A test process, and every Python child it starts, takes ``BLAS_THREADS``
threads of the machine for its linear algebra and not one a core: it holds
wherever an import is reordered or a new plugin loads NumPy earlier still.
And the files that start first are files: a renamed one would fall back
among the short ones without a word."""

import json
import subprocess
import sys
from pathlib import Path

import pytest
import threadpoolctl

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
