"""The linear-attention cell's controls, rehearsed tiny on the CPU: each
fault of ``planted_faults_gdn.py`` in the program alone has to read
``correct`` false through ``check.compare``, by the limits named here."""

import pytest

from planted_faults_gdn import FAULTS, planted
from test_cells_on_cpu import over, rehearse

CELL = "qwen3-next-80b-a3b.ep64-s8k.w2-matcha"
CAUGHT_BY = {
    "bf16_wire": {"dparam_gap"},
    "no_exchange": {"disagree_gap"},
    "state_not_reset": {"step1_momentum_gap"},
    "conv_leaks": {"step1_momentum_gap"},
    "beta_left_out": {"step1_momentum_gap", "step1_momentum_all_gap"},
    "decay_left_out": {"step1_momentum_gap", "step1_momentum_all_gap"},
    "l2norm_left_out": {"step1_momentum_gap", "step1_momentum_all_gap"},
    "output_gate_left_out": {"step1_momentum_gap", "step1_momentum_all_gap"},
    "rope_whole_head": {"step1_momentum_gap"},
    "shared_gate_left_out": {"step1_momentum_gap"},
    "fewer_experts_a_token": {"step1_momentum_gap"},
    "bf16_chunk_products": {"step1_momentum_gap"},
}


def test_every_fault_has_its_control():
    assert set(CAUGHT_BY) == set(FAULTS)


@pytest.mark.parametrize("fault", FAULTS)
def test_planted_fault_is_not_correct(fault):
    with planted(fault):
        line = rehearse(CELL)
    assert not line["correct"]
    assert CAUGHT_BY[fault] <= over(line), line["check"]
