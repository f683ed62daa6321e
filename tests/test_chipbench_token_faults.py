"""Tier-1 collects the token cell's six controls
(``chipbench/tests/test_token_cell_faults.py``: each fault of
``planted_faults.py`` in the program alone, rehearsed tiny through
``check.compare``, has to read ``correct`` false), which the driver's
command, ``tests/`` alone, would not run.  A file of its own, so that it
runs beside ``test_chipbench_cells.py`` and not after it."""

import importlib.util
import sys
from pathlib import Path

THERE = Path(__file__).resolve().parents[1] / "chipbench" / "tests"
sys.path.insert(0, str(THERE))  # ``planted_faults`` and ``test_cells_on_cpu``

import reference_once  # noqa: E402  (beside this file)

# a fault lies in the program alone: the reference is computed once a
# question, not once a fault (ROADMAP D11)
reference_once.install()

_spec = importlib.util.spec_from_file_location(
    "chipbench_tests_test_token_cell_faults",
    THERE / "test_token_cell_faults.py")
_module = importlib.util.module_from_spec(_spec)
sys.modules[_spec.name] = _module
_spec.loader.exec_module(_module)

# its tests, under their own names
globals().update({name: thing for name, thing in vars(_module).items()
                  if name.startswith("test_")})
