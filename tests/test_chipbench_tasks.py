"""Tier-1 collects the benchmark's own tests of its task seam
(``chipbench/tests/test_tasks.py``: the image task as the parent's to the
bit, ``next_token``'s layout, chain, mask and loss, a toy token configuration
through ``stage_job``, ``run_reference`` and ``check.compare``), which the
driver's command, ``tests/`` alone, would not run."""

import importlib.util
import sys
from pathlib import Path

THERE = Path(__file__).resolve().parents[1] / "chipbench" / "tests"
sys.path.insert(0, str(THERE))  # it imports ``toy_lm``, which lies beside it

_spec = importlib.util.spec_from_file_location(
    "chipbench_tests_test_tasks", THERE / "test_tasks.py")
_module = importlib.util.module_from_spec(_spec)
sys.modules[_spec.name] = _module
_spec.loader.exec_module(_module)

# its tests and the fixtures they ask for, under their own names
globals().update({name: thing for name, thing in vars(_module).items()
                  if not name.startswith("__")})
