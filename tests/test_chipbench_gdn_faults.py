"""Tier-1 collects the linear-attention cell's controls
(``chipbench/tests/test_gdn_cell_faults.py``: each fault of
``planted_faults_gdn.py`` in the program alone, rehearsed tiny through
``check.compare``, has to read ``correct`` false), as
``test_chipbench_dsa_faults.py`` collects the sparse-attention cell's.  A
file of its own, so that the three run beside each other."""

import chipbench_tests  # beside this file

globals().update(chipbench_tests.tests_of("test_gdn_cell_faults.py"))
