"""Tier-1 collects the block-diffusion cell's controls
(``chipbench/tests/test_bd_cell_faults.py``: each fault of
``planted_faults_bd.py`` in the program alone, rehearsed tiny through
``check.compare``, has to read incorrect), as
``test_chipbench_dsa_faults.py`` collects the sparse-attention cell's.  A
file of its own, so that it runs beside the others."""

import importlib.util
import sys
from pathlib import Path

THERE = Path(__file__).resolve().parents[1] / "chipbench" / "tests"
sys.path.insert(0, str(THERE))  # ``planted_faults_bd``

_spec = importlib.util.spec_from_file_location(
    "chipbench_tests_test_bd_cell_faults", THERE / "test_bd_cell_faults.py")
_module = importlib.util.module_from_spec(_spec)
sys.modules[_spec.name] = _module
_spec.loader.exec_module(_module)

# its tests, under their own names
globals().update({name: thing for name, thing in vars(_module).items()
                  if name.startswith("test_")})
