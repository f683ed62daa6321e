"""Tier-1 collects the token cell's six controls
(``chipbench/tests/test_token_cell_faults.py``: each fault of
``planted_faults.py`` in the program alone, rehearsed tiny through
``check.compare``, has to read ``correct`` false), which the driver's
command, ``tests/`` alone, would not run.  A file of its own, so that it
runs beside ``test_chipbench_cells.py`` and not after it."""

import chipbench_tests  # beside this file

globals().update(chipbench_tests.tests_of("test_token_cell_faults.py"))
