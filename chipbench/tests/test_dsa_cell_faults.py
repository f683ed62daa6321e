"""The sparse-attention cell's controls, rehearsed tiny on the CPU: each
fault of ``planted_faults_dsa.py`` in the program alone has to read
``correct`` false through ``check.compare``, by the limits named here."""

import pytest

from planted_faults_dsa import FAULTS, planted
from test_cells_on_cpu import over, rehearse

CELL = "keye-vl2-30b-a3b.ep16-s8k.w2-matcha"
CAUGHT_BY = {
    "bf16_wire": {"dparam_gap"},
    "no_exchange": {"disagree_gap"},
    "selection_left_out": {"step1_momentum_gap"},
    "half_the_keys": {"step1_momentum_gap"},
    "indexer_loss_left_out": {"step1_momentum_gap"},
    "indexer_attached": {"step1_momentum_gap"},
    "bf16_index_scores": {"step1_momentum_gap"},
    "docs_ignored": {"step1_momentum_gap"},
}


def test_every_fault_has_its_control():
    assert set(CAUGHT_BY) == set(FAULTS)


@pytest.mark.parametrize("fault", FAULTS)
def test_planted_fault_is_not_correct(fault):
    with planted(fault):
        line = rehearse(CELL)
    assert not line["correct"]
    assert CAUGHT_BY[fault] <= over(line), line["check"]
