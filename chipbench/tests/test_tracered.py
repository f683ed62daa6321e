"""The trace reduction against a small recorded trace with known answers
(``small_trace.textproto``, whose header works them out by hand)."""

from pathlib import Path

import pytest

from chipbench import tracered

MS = 1e-3


@pytest.fixture(scope="module")
def trace():
    from jax.profiler import ProfileData

    text = (Path(__file__).parent / "small_trace.textproto").read_text()
    return tracered.reduce_planes(
        tracered.read_planes(ProfileData.from_text_proto(text)))


def test_busy_is_the_union_of_operations_that_hold_no_other(trace):
    assert trace["window_s"] == pytest.approx(20 * MS)
    assert trace["busy_s"] == pytest.approx(8 * MS)
    assert trace["idle_share"] == pytest.approx(0.6)


def test_device_time_by_program_and_the_main_program(trace):
    assert trace["module_s"] == pytest.approx(
        {"jit_scan_step": 8 * MS, "jit_chain": 2 * MS})
    assert tracered.main_program_seconds(trace) == pytest.approx(8 * MS)


def test_breakdown_names_operations_and_gaps(trace):
    ops = dict(trace["breakdown"]["device_ops"])
    assert ops == pytest.approx({"fusion.1 f32[16,8]": 5 * MS,
                                 "fusion.9 f32[16,8]": 2 * MS,
                                 "copy.2 f32[16,8]": 1 * MS})
    assert "while.1" not in " ".join(ops)
    gaps = dict(trace["breakdown"]["idle_gaps"])
    assert gaps == pytest.approx({
        "unattributed, before the window's end": 5 * MS,
        "matcha/comm_split_timer": 2 * MS,
        "unattributed, before jit_chain": 2 * MS,
        "matcha/recorder_flush": 1 * MS,
        "unattributed, before jit_scan_step": 1 * MS,
        "unattributed, inside jit_scan_step": 1 * MS})
    assert sum(gaps.values()) == pytest.approx(
        trace["window_s"] - trace["busy_s"])


def test_interval_arithmetic():
    assert tracered.merge([(3, 4), (0, 2), (1, 2.5)]) == [(0, 2.5), (3, 4)]
    assert tracered.intersect_len([(0, 2.5), (3, 4)], [(2, 3.5)]) == 1.0
    assert tracered.span_len([(0, 2.5), (3, 4)]) == 3.5
