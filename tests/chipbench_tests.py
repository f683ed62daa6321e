"""The benchmark's own test files, loaded from ``chipbench/tests`` for the
tier-1 collectors: the driver's command runs ``tests/`` alone, and a
collector (``tests/test_chipbench_*_faults.py``) or
``tests/test_chipbench_cells.py`` names the file whose cases or rehearsal it
wants.  A planted fault lies in the program alone, so the reference a cell
is held to is computed once a question and not once a fault
(``reference_once``, ROADMAP D11)."""

import importlib.util
import sys
from pathlib import Path

import reference_once  # beside this file

THERE = Path(__file__).resolve().parents[1] / "chipbench" / "tests"


def load(file_name):
    """The module of ``chipbench/tests/<file_name>``, executed once a
    process, with ``harness.run_reference`` behind the memo."""
    name = "chipbench_tests_" + Path(file_name).stem
    if name not in sys.modules:
        if str(THERE) not in sys.path:
            sys.path.insert(0, str(THERE))  # ``planted_faults*``, ``test_cells_on_cpu``
        reference_once.install()
        spec = importlib.util.spec_from_file_location(name, THERE / file_name)
        module = importlib.util.module_from_spec(spec)
        sys.modules[name] = module
        spec.loader.exec_module(module)
    return sys.modules[name]


def tests_of(file_name):
    """Its tests by name, for a collector's ``globals().update``."""
    return {name: thing for name, thing in vars(load(file_name)).items()
            if name.startswith("test_")}
