"""Tier-1 collects the sparse-attention cell's controls
(``chipbench/tests/test_dsa_cell_faults.py``: each fault of
``planted_faults_dsa.py`` in the program alone, rehearsed tiny through
``check.compare``, has to read ``correct`` false), as
``test_chipbench_token_faults.py`` collects the other token cell's.  A file
of its own, so that the two run beside each other."""

import chipbench_tests  # beside this file

globals().update(chipbench_tests.tests_of("test_dsa_cell_faults.py"))
