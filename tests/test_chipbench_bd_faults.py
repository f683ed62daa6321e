"""Tier-1 collects the block-diffusion cell's controls
(``chipbench/tests/test_bd_cell_faults.py``: each fault of
``planted_faults_bd.py`` in the program alone, rehearsed tiny through
``check.compare``, has to read incorrect), as
``test_chipbench_dsa_faults.py`` collects the sparse-attention cell's.  A
file of its own, so that it runs beside the others."""

import chipbench_tests  # beside this file

globals().update(chipbench_tests.tests_of("test_bd_cell_faults.py"))
