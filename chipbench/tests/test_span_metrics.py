"""The readers of the loop's ``spans`` records on a small hand-made run with
answers by arithmetic, traced and untraced, and one traced rehearsal of a
cell that has to print each of them."""

import json

import pytest

from chipbench import catalog, harness

SPAN_METRICS = ["stage_ms", "stage_gb_per_s", "device_wait_ms",
                "comm_timer_ms", "boundary_rest_ms", "span_cover_pct",
                "idle_named_pct"]


def period(epoch, slow=1.0, steps=4, hook=0.010):
    """One epoch period of 1 s (``slow`` stretches staging and the timer):
    each leaf starts where the one before it ends, but for 10 ms under no
    name before the hook."""
    lengths = [
        ("boundary_hook", hook, {}), ("prime", 0.002, {}),
        ("snapshot", 0.003, {}),
        ("load_batches", 0.040 * slow, {}),
        ("stack_batches", 0.100 * slow, {}),
        ("h2d", 0.060 * slow, {"bytes": 400_000_000}),
        ("ledger_observe", 0.001, {}), ("dispatch", 0.004, {"steps": steps}),
        ("wait_device", 0.596, {}), ("wait_device", 0.0, {}),
        ("divergence_check", 0.005, {}),
        ("comm_split_timer", 0.100 * slow, {}),
        ("record_epoch", 0.001, {}), ("telemetry_flush", 0.020, {}),
        ("heartbeat", 0.004, {}), ("checkpoint", 0.034, {}),
    ]
    t0 = 100.0 + 10 * epoch
    t, spans = t0 + 0.010, []
    for name, seconds, counts in lengths:
        spans.append({"name": name, "t0": t, "t1": t + seconds,
                      "parent": f"{epoch}.0", **counts})
        t += seconds
    # a save the hook asked for: inside the hook, and counted with it
    spans.append({"name": "checkpoint", "t0": t0 + 0.011, "t1": t0 + 0.015,
                  "parent": f"{epoch}.0/boundary_hook"})
    return {"v": 8, "kind": "spans", "t": t, "epoch": epoch, "attempt": 0,
            "period": f"{epoch}.0", "t0": t0, "t1": t, "samples": 512,
            "spans": spans}


def make_run(traced=None, trace=None, periods=None):
    """A window of epochs 2..6.  Epochs 2 and 3 (under the profiler in a
    traced run) stage twice as slowly, and the hook of epoch 4 (which stops
    the profiler there) takes 2 s."""
    if periods is None:
        periods = [period(2, slow=2.0), period(3, slow=2.0),
                   period(4, hook=2.0), period(5), period(6)]
    events = [{"v": 8, "kind": "run_start", "t": 0.0},
              # before the window, and a stop's period that trained nothing
              period(1, slow=5.0), *periods,
              dict(period(7), samples=0)]
    return {"events": events, "trace": trace, "traced": traced,
            "epochs": [{"epoch": k} for k in range(2, 7)], "steps": 4}


def read(metric, run):
    return catalog.load_reader(metric)(run)


def test_traced_run_reads_staging_under_the_profiler_and_the_rest_after():
    run = make_run(traced=(2, 4))  # 2 and 3 under it; 4, 5 and 6 after
    assert read("stage_ms", run) == pytest.approx(400.0 / 4)
    assert read("stage_gb_per_s", run) == pytest.approx(0.4 / 0.4)
    assert read("device_wait_ms", run) == pytest.approx(600.0 / 4)
    assert read("comm_timer_ms", run) == pytest.approx(100.0)
    # hook 10, prime 2, snapshot 3, check 5, record 1, flush 20, heartbeat
    # 4, checkpoint 34; the hook's own checkpoint is inside the hook's 10
    assert read("boundary_rest_ms", run) == pytest.approx(79.0)
    assert read("span_cover_pct", run) == pytest.approx(100 * 0.98 / 0.99)
    # a window that the profiler's stop leaves one epoch: the hook that
    # stopped it (and wrote the trace for 2 s) is the harness's, not counted
    run["epochs"] = run["epochs"][:3]
    assert read("boundary_rest_ms", run) == pytest.approx(69.0)
    assert read("comm_timer_ms", run) == pytest.approx(100.0)
    assert read("stage_ms", run) == pytest.approx(100.0)


def test_untraced_run_reads_every_epoch_of_the_window():
    run = make_run()  # medians over 2x, 2x, 1x, 1x, 1x; hooks 10 ms but one
    assert read("stage_ms", run) == pytest.approx(50.0)
    assert read("comm_timer_ms", run) == pytest.approx(100.0)
    assert read("boundary_rest_ms", run) == pytest.approx(79.0)
    slow = make_run(periods=[period(k, slow=2.0) for k in range(2, 7)])
    assert read("stage_ms", slow) == pytest.approx(100.0)
    assert read("stage_gb_per_s", slow) == pytest.approx(1.0)
    assert read("comm_timer_ms", slow) == pytest.approx(200.0)
    # the worst epoch: 10 ms under no name of 1.29 s
    assert read("span_cover_pct", slow) == pytest.approx(100 * 1.28 / 1.29)


def test_a_window_with_no_epoch_of_the_kind_reads_what_it_has():
    run = make_run(traced=(5, 7))  # none after the stop: all five are read
    assert read("comm_timer_ms", run) == pytest.approx(100.0)
    run = make_run(traced=(0, 2))  # none under it in the window
    assert read("stage_ms", run) == pytest.approx(50.0)


@pytest.mark.parametrize("metric", SPAN_METRICS[:-1])
def test_a_program_that_journals_no_spans_reads_nothing(metric):
    """The parent commit: the reader returns None and the line leaves the
    metric out."""
    run = make_run(periods=[])
    run["events"] = [e for e in run["events"] if e["kind"] != "spans"] + [
        {"v": 7, "kind": "epoch", "t": 1.0, "epoch": 2}]
    assert read(metric, run) is None


def test_idle_named_pct_reads_the_listed_gaps():
    gaps = [["unattributed, before jit_scan_step", 0.25],
            ["matcha/stack_batches", 2.0], ["matcha/load_batches", 0.5],
            ["chipbench/hook", 0.25], ["matcha/h2d", 1.0]]
    run = make_run(trace={"breakdown": {"idle_gaps": gaps}})
    assert read("idle_named_pct", run) == pytest.approx(100 * 3.5 / 4.0)
    run = make_run(trace={"breakdown": {"idle_gaps": []}})
    assert read("idle_named_pct", run) is None
    assert read("idle_named_pct", make_run(trace=None)) is None


def test_benchmark_lists_each_reader_for_every_cell():
    bench = catalog.benchmark()
    listed = {m["name"]: m for m in bench["per_layer"]}
    for name in SPAN_METRICS:
        assert "workloads" not in listed[name]
        assert callable(catalog.load_reader(name))
    assert [m["name"] for m in bench["per_layer"]][-len(SPAN_METRICS):] \
        == SPAN_METRICS


def test_traced_rehearsal_prints_every_span_metric(capsys):
    cell = catalog.benchmark()["workloads"][1]["name"]
    code = harness.main(["--workload", cell, "--seed", "2147483999",
                         "--seconds", "0.5", "--trace", "1",
                         "--rehearse-on-cpu"])
    assert code == 2
    line = json.loads(capsys.readouterr().out.strip().splitlines()[-1])
    got = line["metrics"]
    assert set(SPAN_METRICS[:-1]) <= set(got)  # (no device plane on the CPU)
    assert "idle_named_pct" not in got
    assert got["span_cover_pct"]["value"] > 90.0
    assert got["stage_ms"]["value"] > 0 and got["device_wait_ms"]["value"] > 0
    steps = got["stage_ms"]["value"] + got["device_wait_ms"]["value"]
    assert steps == pytest.approx(got["step_ms.p50"]["value"], rel=0.5)
