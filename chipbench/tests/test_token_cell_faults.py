"""The token cell's six controls, rehearsed tiny on the CPU: each fault of
``planted_faults.py`` in the program alone has to read ``correct`` false
through ``check.compare``, by the limits named here."""

import pytest

from planted_faults import FAULTS, planted
from test_cells_on_cpu import over, rehearse

CELL = "mellum2-12b-a2.5b.ep8-s4k.w2-matcha"
CAUGHT_BY = {
    "bf16_wire": {"dparam_gap"},
    "no_exchange": {"disagree_gap"},
    "window_ignored": {"step1_momentum_gap"},
    "docs_ignored": {"step1_momentum_gap"},
    "no_renorm": {"step1_momentum_gap"},
    "expert_dropped": {"step1_momentum_gap"},
}


def test_every_fault_has_its_control():
    assert set(CAUGHT_BY) == set(FAULTS)


@pytest.mark.parametrize("fault", FAULTS)
def test_planted_fault_is_not_correct(fault):
    with planted(fault):
        line = rehearse(CELL)
    assert not line["correct"]
    assert CAUGHT_BY[fault] <= over(line), line["check"]
