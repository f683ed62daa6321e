"""The four token cells rehearsed tiny on the CPU from ``stage_job`` to
``check.compare``, as ``chipbench/tests/test_cells_on_cpu.py`` rehearses
every cell of ``BENCHMARK.json`` outside tier-1 (the two conv cells take a
minute and half a minute there; these fit here), and the control that has
to fail.  This file runs the two softmax-attention cells;
``test_chipbench_cells_more.py`` runs the same cases on the other two, beside
it (a cell is two rehearsals, a minute of a busy machine)."""

import json

import pytest

import chipbench_tests  # beside this file
from chipbench import catalog

#: each token cell, and the counters' metrics that are its own
CELLS = {
    "mellum2-12b-a2.5b.ep8-s4k.w2-matcha": {
        "moe_load_max_over_mean", "moe_slot_fill_pct", "loss_positions_pct"},
    "keye-vl2-30b-a3b.ep16-s8k.w2-matcha": {
        "dsa_selecting_pct", "dsa_keys_kept_pct", "dsa_indexer_kl"},
    "qwen3-next-80b-a3b.ep64-s8k.w2-matcha": {
        "gdn_chunks_reset_pct", "gdn_decay_mean"},
    "sdar-30b-a3b.ep16-s4k.w2-matcha": {
        "bd_masked_pct", "bd_scored_over_visible", "bd_masked_slots_pct"},
}
COUNTER_METRICS = sorted(set().union(*CELLS.values()))
HERE, MORE = list(CELLS)[:2], list(CELLS)[2:]

# its one rehearsal run, not its tests.  The control plants its fault in the
# program alone: the reference it is held to is the sound run's, computed
# once (``chipbench_tests.load``)
rehearse = chipbench_tests.load("test_cells_on_cpu.py").rehearse


@pytest.fixture(scope="module", params=HERE)
def cell(request):
    return request.param


@pytest.fixture(scope="module")
def line(cell):
    return rehearse(cell, trace=1)


def test_program_agrees_with_reference(line):
    assert line["correct"], line["check"]
    assert line["attempted"] >= 1 and line["failed"] == 0
    assert line["loss"][1] < line["loss"][0]
    json.dumps(line)


def test_counters_fill_the_cells_own_metrics(cell, line):
    """A traced run's line: on the CPU no device trace is read, and the
    metrics that read the program's counters and spans still report, each
    in the cell that lists it and in no other."""
    bench = catalog.benchmark()
    listed = [m for m in bench["per_layer"] if cell in m.get("workloads", ())]
    mine = {m["name"] for m in listed if m["source"] == "program_counter"}
    assert mine == CELLS[cell]
    # the cell's other own metrics read the device trace's scopes (PR 37,
    # tests/test_device_scopes.py): nothing to read on the CPU
    scoped = {m["name"] for m in listed} - mine
    assert scoped and not scoped & set(line["metrics"])
    assert all(m["source"] == "device_trace" for m in listed
               if m["name"] in scoped)
    got = {k: v["value"] for k, v in line["metrics"].items()}
    assert mine <= set(got) and not (set(COUNTER_METRICS) - mine) & set(got)
    if "moe_slot_fill_pct" in mine:
        assert 0 < got["moe_slot_fill_pct"] <= 100
        assert 1 <= got["moe_load_max_over_mean"] <= 2  # 2 experts held
        assert 90 < got["loss_positions_pct"] <= 100
    elif "bd_masked_pct" in mine:  # 64 tokens a row in blocks of 32 queries
        assert 35 < got["bd_masked_pct"] < 70  # 52.5 of many blocks; 256 here
        # 8,192 pairs scored a row for the 4,352 that the four rules let see
        # in one document (1.88), and fewer where a row holds two
        assert 1.88 <= got["bd_scored_over_visible"] < 4
        assert 0 < got["bd_masked_slots_pct"] < 100
    elif "gdn_decay_mean" in mine:  # 8 chunks a row, a start in a few
        assert 0 < got["gdn_chunks_reset_pct"] < 50
        assert 0 < got["gdn_decay_mean"] < 1
    else:  # 64 positions, 16 kept: most queries see more than they keep
        assert 0 < got["dsa_selecting_pct"] < 100
        assert 0 < got["dsa_keys_kept_pct"] < 100
        assert got["dsa_indexer_kl"] > 0
    assert {"stage_gb_per_s", "comm_timer_ms", "stage_ms",
            "span_cover_pct"} <= set(got)


def test_readers_return_nothing_for_a_program_without_counters(line):
    """The parent's program journals no ``counters`` and no ``tokens``: the
    readers give None, and the line leaves the metric out."""
    run = {"events": [{"kind": "spans", "epoch": 0, "samples": 8,
                       "period": "0.0", "spans": [
                           {"name": "dispatch", "t0": 0, "t1": 1, "steps": 2,
                            "parent": "0.0"}]}],
           "epochs": [{"epoch": 0}], "traced": None}
    for name in COUNTER_METRICS:
        assert catalog.load_reader(name)(run) is None


def test_control_exchange_left_out_is_not_correct(cell):
    def no_gossip(job):
        job["train_config"]["communicator"] = "none"

    line = rehearse(cell, edit=no_gossip)
    assert not line["correct"]
    assert line["check"]["disagree_gap"][0] > line["check"]["disagree_gap"][1]
