"""The loop's named host phases (ISSUE 24): ``utils.profiling.SpanRecorder``
and what a tiny ``train()`` of two workers records through it, on each of
the loop's three epoch paths, each run under a ``jax.profiler`` session."""

import glob
import os
from collections import Counter

import jax
import numpy as np
import pytest

from matcha_tpu.obs.journal import validate_event
from matcha_tpu.obs.timeline import build_timeline, validate_trace
from matcha_tpu.train import TrainConfig, train
from matcha_tpu.utils import SPAN_NAMES, SpanRecorder

pytestmark = pytest.mark.obs

WORKERS, BATCH, STEPS, EPOCHS, CHUNK = 2, 8, 5, 3, 2
SHAPE = (28, 28, 1)
PATHS = {"whole_epoch": {}, "chunked": {"scan_chunk": CHUNK},
         "per_batch": {"scan_epoch": False}}
#: what one period of each path records under the period, in order
STAGED = ["stack_batches", "h2d", "ledger_observe", "dispatch"]
INSIDE = {
    "whole_epoch": ["load_batches"] + STAGED + ["wait_device"] * 2,
    # (the flush of a segment's metrics comes after the next one's dispatch)
    "chunked": (["load_batches"] + STAGED
                + (["load_batches"] + STAGED + ["wait_device"]) * 2
                + ["wait_device"] * 2),
    "per_batch": ["epoch_python", "wait_device"],
}
BEFORE = ["boundary_hook", "prime", "snapshot"]
AFTER = ["divergence_check", "comm_split_timer", "evaluate", "record_epoch",
         "telemetry_flush", "heartbeat", "checkpoint"]


def host_annotations(trace_dir):
    """``[(start_s, name, seconds)]`` of the ``matcha/`` events on the
    profiler's host plane, in start order."""
    from jax.profiler import ProfileData

    path = max(glob.glob(os.path.join(trace_dir, "**", "*.xplane.pb"),
                         recursive=True), key=os.path.getmtime)
    return sorted(
        (ev.start_ns * 1e-9, ev.name, ev.duration_ns * 1e-9)
        for plane in ProfileData.from_file(path).planes
        if plane.name == "/host:CPU"
        for line in plane.lines for ev in line.events
        if ev.name.startswith("matcha/"))


on_every_path = pytest.mark.parametrize("run", list(PATHS), indirect=True)


@pytest.fixture(scope="module")
def run(request, tmp_path_factory):
    """One journaled ``train()`` on the path, everything the loop can do at
    a boundary switched on, under a profiler session: (path, the journal's
    ``spans`` records, all its events, the host plane's annotations)."""
    tmp = tmp_path_factory.mktemp(request.param)
    config = TrainConfig(
        name="spans", model="mlp", dataset="synthetic",
        dataset_kwargs={"num_train": WORKERS * BATCH * STEPS, "num_test": 16,
                        "shape": SHAPE},
        num_workers=WORKERS, graphid=None, topology="complete",
        batch_size=BATCH,
        epochs=EPOCHS, lr=0.05, warmup=False, matcha=True, budget=0.5,
        seed=3, eval_every=1, checkpoint_every=1, max_recoveries=1,
        devices=1, save=True, savePath=str(tmp), **PATHS[request.param])
    options = jax.profiler.ProfileOptions()
    options.python_tracer_level = 0
    jax.profiler.start_trace(str(tmp / "trace"), profiler_options=options)
    try:
        result = train(config, boundary_hook=lambda seam: None)
    finally:
        jax.profiler.stop_trace()
    events = result.recorder.events
    return (request.param, [e for e in events if e["kind"] == "spans"],
            events, host_annotations(str(tmp / "trace")))


@on_every_path
def test_a_run_emits_the_vocabulary_in_loop_order(run):
    path, records, _, _ = run
    assert len(records) == EPOCHS
    assert [r["period"] for r in records] == ["0.0", "1.0", "2.0"]
    for r in records:
        assert [s["name"] for s in r["spans"]] == \
            BEFORE + INSIDE[path] + AFTER
        assert {s["parent"] for s in r["spans"]} == {r["period"]}
    first_seen = list(dict.fromkeys(s["name"] for s in records[0]["spans"]))
    assert first_seen == [n for n in SPAN_NAMES if n in first_seen]


def test_every_loop_path_is_named_in_the_vocabulary():
    """Names this file's runs do not reach (a flush every tenth epoch, a
    membership join, the epoch ``trace_dir`` captures:
    ``tests/test_perfobs.py``) are still the tuple's: nothing else is."""
    assert set(SPAN_NAMES) == set(BEFORE + AFTER + sum(INSIDE.values(), [])
                                  ) | {"membership_bootstrap",
                                       "recorder_flush", "profile"}
    assert len(set(SPAN_NAMES)) == len(SPAN_NAMES)


@on_every_path
def test_spans_partition_the_period(run):
    _, records, _, _ = run
    for r in records:
        edges = [(s["t0"], s["t1"]) for s in r["spans"]]
        assert all(lo <= hi for lo, hi in edges)
        assert all(a[1] <= b[0] for a, b in zip(edges, edges[1:]))
        assert r["t0"] <= edges[0][0] and edges[-1][1] <= r["t1"]
        covered = sum(hi - lo for lo, hi in edges)
        assert covered >= 0.95 * (r["t1"] - r["t0"])
    assert all(a["t1"] <= b["t0"] for a, b in zip(records, records[1:]))


@on_every_path
def test_counts_at_the_boundaries(run):
    path, records, _, _ = run
    per_step = WORKERS * BATCH * (int(np.prod(SHAPE)) * 4 + 4)  # f32 x, i32 y
    for r in records:
        assert r["samples"] == WORKERS * BATCH * STEPS
        counted = {s["name"]: set(s) - {"name", "t0", "t1", "parent",
                                        "segment"} for s in r["spans"]}
        assert {k: v for k, v in counted.items() if v} == (
            {} if path == "per_batch"
            else {"stack_batches": {"reused"}, "h2d": {"bytes"},
                  "dispatch": {"steps"}})
        if path != "per_batch":
            assert sum(s["bytes"] for s in r["spans"]
                       if s["name"] == "h2d") == per_step * STEPS
            assert sum(s["steps"] for s in r["spans"]
                       if s["name"] == "dispatch") == STEPS


@pytest.mark.parametrize("run", ["whole_epoch", "chunked"], indirect=True)
def test_stack_batches_says_whether_the_stack_was_filled_before(run):
    """``reused`` 0 where the segment's stack was allocated for it (the
    first epoch's one, or the first use of each of the chunked path's two),
    1 in every segment after: the kept stacks engaged."""
    path, records, _, _ = run
    reused = [[s["reused"] for s in r["spans"] if s["name"] == "stack_batches"]
              for r in records]
    assert reused == ([[0], [1], [1]] if path == "whole_epoch"
                      else [[0, 0, 1], [1, 1, 1], [1, 1, 1]])


@pytest.mark.parametrize("run", ["chunked"], indirect=True)
def test_chunked_path_stages_and_dispatches_once_a_segment(run):
    _, records, _, _ = run
    per_step = WORKERS * BATCH * (int(np.prod(SHAPE)) * 4 + 4)
    for r in records:
        for name in ["load_batches"] + STAGED:
            assert [s["segment"] for s in r["spans"]
                    if s["name"] == name] == [0, 1, 2]
        assert [s.get("segment") for s in r["spans"]
                if s["name"] == "wait_device"] == [0, 1, 2, None]
        assert [s["steps"] for s in r["spans"]
                if s["name"] == "dispatch"] == [2, 2, 1]
        assert [s["bytes"] for s in r["spans"] if s["name"] == "h2d"] == \
            [2 * per_step, 2 * per_step, per_step]


@on_every_path
def test_profiler_holds_one_event_per_recorded_span(run):
    """The shared clock: each recorded span is one ``matcha/<name>`` event
    of the host plane, in the same order, inside the recorded bracket."""
    _, records, _, annotations = run
    recorded = [s for r in records for s in r["spans"]]
    # (the final flush after the loop belongs to no period's record)
    assert [name for _, name, _ in annotations] == \
        ["matcha/" + s["name"] for s in recorded] + ["matcha/recorder_flush"]
    offsets = []
    for (start, _, seconds), s in zip(annotations, recorded):
        assert seconds <= s["t1"] - s["t0"] + 1e-4
        offsets.append(start - s["t0"])
    assert max(offsets) - min(offsets) < 0.25


@on_every_path
def test_epoch_program_is_compiled_once_a_shape(run):
    path, _, events, _ = run
    label = "train_step" if path == "per_batch" else "epoch_scan"
    assert not [e for e in events if e["kind"] == "retrace"]
    compiled = [e for e in events
                if e["kind"] == "compile" and e["label"] == label]
    assert len(compiled) == (2 if path == "chunked" else 1)


@on_every_path
def test_journal_validates_and_the_timeline_round_trips(run):
    _, records, events, _ = run
    assert [p for e in events for p in validate_event(e)] == []
    trace = build_timeline(events)
    assert validate_trace(trace) == []
    drawn = [e for e in trace["traceEvents"] if e.get("ph") == "X"]
    by_name = Counter(e["name"].split(" #")[0] for e in drawn)
    assert by_name["period"] == EPOCHS
    for name, n in Counter(s["name"] for r in records
                           for s in r["spans"]).items():
        assert by_name[name] == n, name
    first = records[0]["spans"][0]
    hook = next(e for e in drawn if e["name"] == "boundary_hook")
    assert hook["ts"] == pytest.approx(first["t0"] * 1e6)
    assert hook["dur"] == pytest.approx((first["t1"] - first["t0"]) * 1e6)
    # every checkpoint has its span: none is drawn as a zero-length mark
    marks = [e for e in trace["traceEvents"] if e["name"] == "checkpoint"]
    assert sorted(e["ph"] for e in marks) == ["X"] * EPOCHS + ["i"] * EPOCHS
    assert all(e["dur"] > 0 for e in marks if e["ph"] == "X")


def test_timeline_keeps_the_mark_where_no_span_was_recorded():
    """A journal from before v8 (or a checkpoint outside any period) has
    only the completion time: the zero-length mark stays."""
    from matcha_tpu.obs.journal import make_event

    old = [make_event("run_start", 0.0, config={}, predicted={}),
           make_event("checkpoint", 2.0, epoch=0, path="p")]
    marks = [e for e in build_timeline(old)["traceEvents"]
             if e["name"] == "checkpoint"]
    assert [(e["ph"], e["dur"]) for e in marks] == [("X", 0.0)]


# --------------------------------------------------------------- the recorder

def test_recorder_keeps_name_times_parent_and_counts():
    spans = SpanRecorder(origin=100.0)
    assert spans.end() is None
    spans.begin("4.1", epoch=4, attempt=1)
    with spans.span("h2d", segment=2, bytes=64):
        pass
    with spans.span("dispatch", steps=3):
        pass
    period = spans.end(samples=7)
    assert {k: period[k] for k in ("period", "epoch", "attempt", "samples")} \
        == {"period": "4.1", "epoch": 4, "attempt": 1, "samples": 7}
    h2d, dispatch = period["spans"]
    assert h2d == {"name": "h2d", "t0": h2d["t0"], "t1": h2d["t1"],
                   "parent": "4.1", "segment": 2, "bytes": 64}
    assert dispatch["steps"] == 3
    assert 100.0 <= period["t0"] <= h2d["t0"] <= h2d["t1"] <= dispatch["t0"] \
        <= dispatch["t1"] <= period["t1"] < 101.0
    assert spans.spans == [] and spans.end() is None


def test_a_span_inside_another_is_its_child_and_splits_it_in_the_profiler(
        tmp_path):
    """The seam's on-demand checkpoint inside ``boundary_hook``: a child in
    memory; the profiler sees hook, checkpoint, hook — none inside another
    (the benchmark's reduction adds each name's cover of a gap)."""
    spans = SpanRecorder()
    spans.begin("0.0")
    jax.profiler.start_trace(str(tmp_path))
    try:
        with spans.span("boundary_hook"):
            with spans.span("checkpoint"):
                pass
        with pytest.raises(KeyError):
            with spans.span("prime"):
                raise KeyError("closes the span all the same")
    finally:
        jax.profiler.stop_trace()
    hook, checkpoint, prime = spans.end()["spans"]
    assert (hook["parent"], checkpoint["parent"], prime["parent"]) == \
        ("0.0", "0.0/boundary_hook", "0.0")
    assert hook["t0"] <= checkpoint["t0"] <= checkpoint["t1"] <= hook["t1"] \
        <= prime["t0"] <= prime["t1"]
    seen = host_annotations(str(tmp_path))
    assert [name for _, name, _ in seen] == [
        "matcha/boundary_hook", "matcha/checkpoint", "matcha/boundary_hook",
        "matcha/prime"]
    ends = [start + seconds for start, _, seconds in seen]
    assert all(end <= nxt[0] + 1e-9 for end, nxt in zip(ends, seen[1:]))
