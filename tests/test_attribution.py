"""Attribution plane (ISSUE 11): measured link costs, timeline, critical path.

Layered like the subsystem: the ridge estimator's recovery/identifiability
contract over synthetic planted scenarios, the flag-stream reconstruction
pinned against the committed reference journal's telemetry, the
``measured_link_costs.json`` artifact vs planlint PL009–011, the
``CostModel`` bridge, the Chrome-trace timeline export's schema +
round-trip guarantees, the per-epoch critical-path analysis, and the
``obs_tpu.py attribute | timeline`` CLI exit codes the acceptance criteria
pin (recover planted costs; exit non-zero on an unidentifiable run).
"""

import json
import os
import pathlib
import subprocess
import sys

import numpy as np
import pytest

from matcha_tpu.obs.attribution import (
    attribute_run,
    critical_path_report,
    design_matrix,
    estimate_matching_seconds,
    link_costs_artifact,
    reconstruct_schedule_arrays,
    render_attribution,
)
from matcha_tpu.obs.journal import make_event, read_journal, validate_event
from matcha_tpu.obs.timeline import (
    build_timeline,
    render_timeline_summary,
    validate_trace,
)

pytestmark = [pytest.mark.obs, pytest.mark.attribution]

REPO = pathlib.Path(__file__).resolve().parents[1]
REF_JOURNAL = REPO / "benchmarks" / "events_ring8.jsonl"
REF_COSTS = REPO / "benchmarks" / "measured_link_costs_ring8.json"

#: the reference journal's schedule (graphid 5 = ring-8), as journaled
RING8_CFG = {"graphid": 5, "num_workers": 8, "budget": 0.5, "seed": 3,
             "matcha": True, "topology": "ring"}


def _planted_events(theta, base=0.05, spe=4, epochs=12, cfg=RING8_CFG,
                    noise=0.0, seed=0):
    """A synthetic journal: run_start + epoch events whose comm seconds are
    ``base + A·θ`` over the reconstructed activation design matrix."""
    flags, _, _, _ = reconstruct_schedule_arrays(cfg, epochs * spe + 1)
    A = design_matrix(flags, spe, range(epochs))
    y = base + A @ np.asarray(theta, np.float64)
    if noise:
        y = y + np.random.default_rng(seed).normal(0.0, noise, size=y.shape)
    events = [make_event("run_start", 0.0, config=dict(cfg),
                         predicted={"steps_per_epoch": spe})]
    for e in range(epochs):
        events.append(make_event(
            "epoch", float(e + 1), epoch=e, epoch_time=1.0,
            comp_time=max(1.0 - float(y[e]), 0.0), comm_time=float(y[e]),
            train_loss=1.0, disagreement=0.1))
    return events, A, y


# ---------------------------------------------------------------- estimator

def test_estimator_recovers_planted_costs_exactly():
    """Acceptance pin: on a synthetic journal with planted per-matching
    costs, every identifiable cost is recovered within tolerance."""
    theta = [0.02, 0.06]
    events, _, _ = _planted_events(theta)
    report = attribute_run(events)
    assert report["identifiable"] == [True, True]
    assert report["per_matching_seconds"] == pytest.approx(theta, rel=1e-3)
    assert report["base_seconds"] == pytest.approx(0.05, rel=1e-3)
    assert report["reason"] is None
    # the CIs are honest about a near-exact fit
    assert all(ci < 1e-6 for ci in report["ci95"])


def test_estimator_recovers_under_noise_within_ci():
    theta = [0.03, 0.09]
    events, _, _ = _planted_events(theta, noise=1e-3, epochs=30)
    report = attribute_run(events)
    assert report["identifiable"] == [True, True]
    for j, t in enumerate(theta):
        err = abs(report["per_matching_seconds"][j] - t)
        assert err < 0.01, f"matching {j}: {err}"
        # the 95% CI should usually cover; allow 4x slack for one draw
        assert err < 4 * report["ci95"][j] + 1e-6


def test_noise_dominated_fit_clamps_at_zero_and_artifact_verifies():
    """Regression: a matching whose true cost is below timer noise fits
    slightly negative — the estimate must clamp to 0 (the
    calibrate_cost_model rule) so `attribute --out` never writes an
    artifact its own PL010 verifier rejects on ordinary noisy runs."""
    rng = np.random.default_rng(5)
    A = rng.integers(2, 9, size=(12, 2)).astype(float)
    # tiny true costs, noise an order of magnitude larger
    y = 0.05 + A @ np.array([3e-4, 2e-4]) + rng.normal(0, 0.01, 12)
    negatives = 0
    for seed in range(12):
        yk = 0.05 + A @ np.array([3e-4, 2e-4]) \
            + np.random.default_rng(seed).normal(0, 0.01, 12)
        fit = estimate_matching_seconds(A, yk)
        assert fit["base_seconds"] >= 0.0
        for s, ident in zip(fit["per_matching_seconds"],
                            fit["identifiable"]):
            if ident:
                assert s >= 0.0
                negatives += s == 0.0
    assert negatives > 0, "no draw clamped — the regression is not exercised"
    # the CI of a clamped coordinate stays honest (raw-fit width, not 0)
    fit = estimate_matching_seconds(A, y)
    assert all(ci is None or ci > 0 for ci in fit["ci95"])


def test_degenerate_identical_flags_report_unidentifiable():
    """Acceptance pin: all-epochs-identical flags must report
    *unidentifiable*, never emit noise as fact."""
    A = np.tile([[2.0, 1.0]], (8, 1))
    fit = estimate_matching_seconds(A, np.full(8, 0.3))
    assert fit["identifiable"] == [False, False]
    assert fit["per_matching_seconds"] == [None, None]
    assert "constant design" in fit["reason"]
    # the base still reports the honest mean
    assert fit["base_seconds"] == pytest.approx(0.3)


def test_all_zero_comm_series_is_no_signal_not_free_links():
    fit = estimate_matching_seconds(
        np.array([[1.0, 0.0], [0.0, 1.0], [1.0, 1.0]]), np.zeros(3))
    assert fit["identifiable"] == [False, False]
    assert "no comm signal" in fit["reason"]


def test_collinear_pair_unidentifiable_but_separable_column_exact():
    """Two matchings moving in lockstep can only be priced jointly — both
    report unidentifiable — while the separable column is recovered
    exactly (the min-norm fit does not let the dropped pair bias it)."""
    A = np.array([[1., 1., 0.], [2., 2., 1.], [0., 0., 2.], [3., 3., 1.]])
    y = A @ np.array([0.1, 0.2, 0.3]) + 0.05
    fit = estimate_matching_seconds(A, y)
    assert fit["identifiable"] == [False, False, True]
    assert fit["per_matching_seconds"][:2] == [None, None]
    assert fit["per_matching_seconds"][2] == pytest.approx(0.3, rel=1e-4)


def test_fewer_epochs_than_parameters_flags_deficiency():
    # 2 epochs cannot separate base + 2 matchings: rank-deficient
    flags, _, _, _ = reconstruct_schedule_arrays(RING8_CFG, 9)
    A = design_matrix(flags, 4, range(2))
    fit = estimate_matching_seconds(A, np.array([0.1, 0.2]))
    assert not all(fit["identifiable"])


def test_reconstruction_matches_journaled_telemetry():
    """The regenerated flag stream is pinned against the committed
    journal's device-side counter: per-epoch mean active matchings must
    match to float exactness — the executed stream IS the reconstructed
    one."""
    events = read_journal(str(REF_JOURNAL))
    report = attribute_run(events, comm_seconds=np.linspace(
        0.1, 0.5, 8))  # any non-degenerate series; flags_check is the pin
    assert report["flags_check"]["epochs_checked"] == 8
    assert report["flags_check"]["max_abs_err"] == pytest.approx(0.0,
                                                                 abs=1e-9)
    assert report["flags_check"]["consistent"]


def test_attribute_run_rejects_unusable_journals():
    with pytest.raises(ValueError, match="run_start"):
        attribute_run([make_event("resume", 0.0, epoch=1)])
    events = [make_event("run_start", 0.0, config=dict(RING8_CFG),
                         predicted={"steps_per_epoch": 4})]
    with pytest.raises(ValueError, match="at least 2"):
        attribute_run(events)


def test_per_link_decomposition_sums_and_folds():
    theta = [0.02, 0.06]
    events, _, _ = _planted_events(theta)
    # 2 chips: the ring-8 decomposition has inter-chip edges whose hop
    # weighting must absorb more of the matching's seconds
    report = attribute_run(events, num_chips=2)
    assert report["hop_check_vs_folded_plan"]
    for j, t in enumerate(theta):
        share = sum(l["seconds"] for l in report["per_link"]
                    if l["matching"] == j)
        assert share == pytest.approx(
            report["per_matching_seconds"][j], rel=1e-6)
    hops = {l["hops"] for l in report["per_link"]}
    assert hops - {0}, "2-chip fold should produce inter-chip edges"
    # within a matching, an inter-chip edge costs more than a local one
    for j in range(2):
        by_hops = {}
        for l in report["per_link"]:
            if l["matching"] == j:
                by_hops.setdefault(l["hops"], l["seconds"])
        if len(by_hops) > 1:
            assert by_hops[max(by_hops)] > by_hops[0]


# ---------------------------------------------------------------- artifact

def test_committed_link_costs_artifact_verifies_and_matches_journal():
    from matcha_tpu.analysis import lint_link_costs_data

    data = json.loads(REF_COSTS.read_text())
    assert lint_link_costs_data(data, str(REF_COSTS)) == []
    events = read_journal(str(REF_JOURNAL))
    [attr] = [e for e in events if e["kind"] == "attribution"]
    per = {r["matching"]: r["seconds"] for r in data["per_matching"]}
    for j, s in enumerate(attr["per_matching_seconds"]):
        assert per[j] == pytest.approx(s)


def test_planlint_flags_tampered_link_costs(tmp_path):
    from matcha_tpu.analysis import lint_link_costs_data

    base = json.loads(REF_COSTS.read_text())

    def rules(mutate):
        data = json.loads(json.dumps(base))
        mutate(data)
        return {v.rule for v in lint_link_costs_data(data, "t.json")}

    def neg(d):
        d["per_matching"][0]["seconds"] = -0.5
        for l in d["per_link"]:
            if l["matching"] == 0:
                l["seconds"] = -0.5 / sum(
                    1 for x in d["per_link"] if x["matching"] == 0)

    assert "PL010" in rules(neg)
    assert "PL010" in rules(
        lambda d: d["per_matching"].append(
            {**d["per_matching"][1], "matching": 7}))
    assert "PL010" in rules(
        lambda d: d["per_link"][0].update(u=0, v=5))  # not a ring-8 edge
    assert "PL010" in rules(
        lambda d: d["per_link"][0].update(
            seconds=d["per_link"][0]["seconds"] * 3))  # shares leak
    assert "PL011" in rules(
        lambda d: d["per_matching"][0].update(identifiable=False))
    assert "PL011" in rules(
        lambda d: d["per_matching"][0].update(ci95=1e6))
    assert "PL009" in rules(lambda d: d.update(format="bogus/9"))
    assert "PL009" in rules(lambda d: d.pop("per_matching"))
    # structurally-malformed edits must be verdicts, never tracebacks
    # (round-2 review finding: a hand-tampered file aborted the scan)
    assert "PL009" in rules(lambda d: d.update(per_matching=[1, 2]))
    assert "PL009" in rules(lambda d: d.update(per_link={"oops": 1}))
    assert "PL010" in rules(
        lambda d: d["per_link"][0].update(matching="zero"))
    assert "PL010" in rules(lambda d: d["per_link"][0].update(u="a"))
    assert "PL010" in rules(
        lambda d: d["per_link"][0].update(seconds="fast"))
    # the committed artifact itself is clean
    assert lint_link_costs_data(base, str(REF_COSTS)) == []


def test_link_costs_discovered_by_plan_scan(tmp_path):
    from matcha_tpu.analysis import discover_plan_files, lint_plan_paths

    good = tmp_path / "measured_link_costs.json"
    good.write_text(REF_COSTS.read_text())
    files = discover_plan_files([tmp_path])
    assert good in files
    violations, checked = lint_plan_paths([tmp_path])
    assert good in checked and violations == []


def test_cost_model_bridge_from_measured_link_costs():
    from matcha_tpu.plan import CostModel

    model = CostModel.from_measured_link_costs(str(REF_COSTS))
    # single-chip artifact: every hop unit is 0 — the slope is honestly
    # unidentifiable and the base absorbs mean(θ) + base/steps
    assert model.per_hop_s == 0.0
    assert "unidentifiable" in model.source or model.per_hop_s == 0.0
    data = json.loads(REF_COSTS.read_text())
    theta = [r["seconds"] for r in data["per_matching"]]
    expected = float(np.mean(theta)) + data["base_seconds"] / data[
        "steps_per_epoch"]
    assert model.step_seconds(0.0) == pytest.approx(expected, rel=1e-6)
    assert model.fit["epochs_used"] == data["epochs_used"]
    # an unidentifiable artifact must refuse to calibrate
    bad = json.loads(REF_COSTS.read_text())
    for r in bad["per_matching"]:
        r["identifiable"] = False
        r["seconds"] = None
    with pytest.raises(ValueError, match="identifiable"):
        CostModel.from_measured_link_costs(bad)


def test_calibrate_cost_model_records_provenance():
    from matcha_tpu.plan import calibrate_cost_model

    m = calibrate_cost_model([(0.0, 1.0), (2.0, 2.0)], source="bench",
                             fit={"budgets": [0.25, 0.5]})
    assert m.fit["samples"] == 2
    assert m.fit["units_max"] == 2.0
    assert m.fit["budgets"] == [0.25, 0.5]
    # round-trips through the artifact json
    from matcha_tpu.plan.cost import CostModel

    assert CostModel.from_json(m.to_json()).fit == m.fit


# ---------------------------------------------------------------- timeline

def test_timeline_roundtrips_reference_journal():
    """Acceptance pin: the trace validates against the trace_event schema
    and round-trips every journal event exactly once."""
    events = read_journal(str(REF_JOURNAL))
    trace = build_timeline(events, source="ref")
    assert validate_trace(trace) == []
    srcs = {e["args"]["src"] for e in trace["traceEvents"]
            if e.get("ph") != "M"}
    assert srcs == {f"journal:{i}" for i in range(len(events))}
    # heartbeats became compute+comm span pairs on the host track
    hb_idx = [i for i, e in enumerate(events) if e["kind"] == "heartbeat"]
    for i in hb_idx:
        names = sorted(e["name"] for e in trace["traceEvents"]
                       if e.get("args", {}).get("src") == f"journal:{i}")
        assert names == ["comm", "compute"]
    # one host track + the journal track and its host-phases thread, named
    metas = [e for e in trace["traceEvents"] if e.get("ph") == "M"]
    assert {m["args"]["name"] for m in metas} == {
        "journal", "host host0", "host phases"}
    assert "Perfetto" in render_timeline_summary(trace) or \
        "perfetto" in render_timeline_summary(trace)


def test_timeline_merges_heartbeat_files_on_one_clock(tmp_path):
    """Heartbeat files carry absolute unix t; records mirrored in the
    journal align the host clock, unmirrored records land once each, and
    mirrored ones are not duplicated."""
    events = read_journal(str(REF_JOURNAL))
    hb_events = [e for e in events if e["kind"] == "heartbeat"]
    offset = 1.7e9
    file_records = [{**e, "t": float(e["t"]) + offset} for e in hb_events]
    # one extra record the journal never mirrored (host1, epoch 0)
    extra = {**hb_events[0], "host": "host1", "t": offset + 2.0}
    trace = build_timeline(
        events, {"host0": file_records, "host1": [extra]}, source="ref")
    assert validate_trace(trace) == []
    srcs = {e["args"]["src"] for e in trace["traceEvents"]
            if e.get("ph") != "M"}
    # mirrored file records deduped; exactly one hb:* source (host1's)
    hb_srcs = {s for s in srcs if s.startswith("hb:")}
    assert hb_srcs == {"hb:host1:0"}
    assert trace["otherData"]["heartbeat_file_records"] == 1
    # the aligned record sits on the run clock, not at unix-epoch scale
    host1 = [e for e in trace["traceEvents"]
             if e.get("args", {}).get("src") == "hb:host1:0"]
    assert all(e["ts"] < 1e9 for e in host1)  # < 1000 s in us


def test_validate_trace_catches_schema_and_roundtrip_violations():
    events = read_journal(str(REF_JOURNAL))[:5]
    trace = build_timeline(events)
    assert validate_trace(trace) == []
    broken = json.loads(json.dumps(trace))
    broken["traceEvents"][1]["ph"] = "Z"
    assert any("phase" in p for p in validate_trace(broken))
    dropped = json.loads(json.dumps(trace))
    dropped["traceEvents"] = [
        e for e in dropped["traceEvents"]
        if e.get("args", {}).get("src") != "journal:0"]
    assert any("dropped" in p for p in validate_trace(dropped))
    doubled = json.loads(json.dumps(trace))
    dup = [e for e in doubled["traceEvents"]
           if e.get("args", {}).get("src") == "journal:1"][0]
    doubled["traceEvents"].append(json.loads(json.dumps(dup)))
    assert any("twice" in p for p in validate_trace(doubled))
    negspan = json.loads(json.dumps(trace))
    span = [e for e in negspan["traceEvents"] if e.get("ph") == "X"][0]
    span["dur"] = -5.0
    assert any("dur" in p for p in validate_trace(negspan))


# ------------------------------------------------------------ critical path

def test_critical_path_names_gating_host_and_tax():
    def hb(host, epoch, comp, comm, t):
        return make_event("heartbeat", t, host=host, epoch=epoch,
                          step=(epoch + 1) * 4, step_time=0.1,
                          step_time_ewma=0.1, comp_time=comp,
                          comm_time=comm, peak_bytes=None, workers={})

    events = []
    for e in range(3):
        events.append(hb("h0", e, 1.0, 0.2, float(e)))
        events.append(hb("h1", e, 1.0, 0.1, float(e)))
        slow = 2.0 if e == 1 else 1.0
        events.append(hb("h2", e, slow, 0.1, float(e)))
    cp = critical_path_report(events)
    assert [r["epoch"] for r in cp["rows"]] == [0, 1, 2]
    gate = {r["epoch"]: r["gated_by"] for r in cp["rows"]}
    assert gate[1] == "h2"
    assert gate[0] == "h0" and gate[2] == "h0"  # comm 0.2 > 0.1
    # epoch 1 totals: h0=1.2, h1=1.1, h2=2.1 -> median 1.2, tax 0.9
    row1 = cp["rows"][1]
    assert row1["tax_seconds"] == pytest.approx(2.1 - 1.2)
    assert cp["tax_by_host"]["h2"] == pytest.approx(0.9)
    assert cp["total_tax_seconds"] == pytest.approx(
        sum(r["tax_seconds"] for r in cp["rows"]))


def test_attribute_report_carries_critical_path_with_top_matching():
    theta = [0.02, 0.06]
    events, A, y = _planted_events(theta, epochs=8)
    for e in range(8):
        events.append(make_event(
            "heartbeat", float(e + 1), host="host0", epoch=e,
            step=(e + 1) * 4, step_time=0.25, step_time_ewma=0.25,
            comp_time=1.0 - float(y[e]), comm_time=float(y[e]),
            peak_bytes=None, workers={}))
    report = attribute_run(events)
    cp = report["critical_path"]
    assert len(cp["rows"]) == 8
    recovered = np.asarray(report["per_matching_seconds"], np.float64)
    for r in cp["rows"]:
        assert r["gated_by"] == "host0"
        assert r["tax_seconds"] == 0.0  # single host: no straggler tax
        i = report["epochs"].index(r["epoch"])
        assert r["top_matching"] == int(np.argmax(A[i] * recovered))
    text = render_attribution(report)
    assert "critical path" in text
    assert "verdict" in text


def test_watch_rows_carry_critical_path_tax(tmp_path):
    from matcha_tpu.obs.health import HeartbeatEmitter, fleet_status

    hdir = tmp_path / "health"
    for host, epoch_time in (("hostA", 1.0), ("hostB", 1.5)):
        em = HeartbeatEmitter(str(hdir), host=host)
        for e in range(3):
            em.beat(epoch=e, step=(e + 1) * 4, steps=4.0,
                    epoch_time=epoch_time, comm_time=0.1,
                    workers={f"w{host[-1]}": {
                        "slot": 0, "participation": 1.0,
                        "disagreement": 0.01}})
    status = fleet_status(str(tmp_path), deadline=86400)
    by_host = {r["host"]: r for r in status["rows"]}
    # hostB gates every epoch barrier: 1.5 s vs the 1.25 s fleet median —
    # 0.25 s tax per epoch, 3 epochs in the tail window
    assert by_host["hostB"]["crit_tax_s"] == pytest.approx(0.75)
    assert by_host["hostA"]["crit_tax_s"] == 0.0
    from matcha_tpu.obs.health import render_watch

    assert "crit[s]" in render_watch(status)


# ------------------------------------------------------------------- CLI

def _cli(*args):
    proc = subprocess.run(
        [sys.executable, str(REPO / "obs_tpu.py"), *args],
        capture_output=True, text=True, timeout=300,
        env={**os.environ, "JAX_PLATFORMS": "cpu"})
    return proc.returncode, proc.stdout, proc.stderr


@pytest.mark.slow
def test_cli_attribute_recovers_planted_and_writes_artifact(tmp_path):
    events, _, _ = _planted_events([0.02, 0.06])
    journal = tmp_path / "events.jsonl"
    journal.write_text("".join(
        json.dumps(e, sort_keys=True) + "\n" for e in events))
    out = tmp_path / "measured_link_costs.json"
    side = tmp_path / "attr_journal.jsonl"
    rc, stdout, stderr = _cli("attribute", str(journal), "--out", str(out),
                              "--journal", str(side))
    assert rc == 0, stderr
    assert "2/2 matchings identifiable" in stdout
    data = json.loads(out.read_text())
    assert data["format"] == "matcha_tpu.link_costs/1"
    from matcha_tpu.analysis import lint_link_costs_data

    assert lint_link_costs_data(data, str(out)) == []
    from matcha_tpu.obs.journal import SCHEMA_VERSION

    [event] = read_journal(str(side))
    assert event["kind"] == "attribution" and event["v"] == SCHEMA_VERSION
    assert validate_event(event) == []


@pytest.mark.slow
def test_cli_attribute_exits_nonzero_on_unidentifiable_run(tmp_path):
    """Acceptance pin: attributing an unidentifiable run exits non-zero
    and writes no artifact."""
    # the committed reference journal's real comm series is all-zero
    # (measure_comm_split off on CPU): no signal -> unidentifiable
    out = tmp_path / "costs.json"
    rc, stdout, stderr = _cli("attribute", str(REF_JOURNAL),
                              "--out", str(out))
    assert rc == 1
    assert "unidentifiable" in stderr
    assert not out.exists()


def test_plan_verify_link_costs_error_containment(tmp_path, capsys):
    """Round-2 review finding: a bad --link-costs artifact must become a
    violation in the printed verify report + exit 1 — never a traceback
    that swallows the run-consistency verdict computed above it."""
    import plan_tpu
    from matcha_tpu.plan import save_plan, sweep

    plan_path = tmp_path / "plan.json"
    save_plan(sweep([{"graphid": 0}], [0.5], seed=9001, solver_iters=200),
              str(plan_path))
    run_dir = str(REPO / "tests" / "fixtures" / "recorder_mini"
                  / "recorder-mini_mlp")
    for bad in ({"format": "nope/9"},               # wrong family
                {"format": "matcha_tpu.link_costs/1",
                 "schedule": {}, "per_matching": [1, 2], "per_link": [],
                 "base_seconds": 0.1, "epochs_used": 4}):  # malformed rows
        bad_path = tmp_path / "bad.json"
        bad_path.write_text(json.dumps(bad))
        rc = plan_tpu.main(["verify", "--plan", str(plan_path),
                            "--run-dir", run_dir, "--steps-per-epoch", "4",
                            "--link-costs", str(bad_path)])
        out = capsys.readouterr().out
        assert rc == 1
        report = json.loads(out)
        assert report["link_costs"]["violations"], report["link_costs"]
    # an unreadable path is contained the same way
    rc = plan_tpu.main(["verify", "--plan", str(plan_path),
                        "--run-dir", run_dir, "--steps-per-epoch", "4",
                        "--link-costs", str(tmp_path / "missing.json")])
    report = json.loads(capsys.readouterr().out)
    assert rc == 1 and "unusable" in str(report["link_costs"]["violations"])


@pytest.mark.slow
def test_cli_timeline_writes_validated_trace(tmp_path):
    out = tmp_path / "trace.json"
    rc, stdout, stderr = _cli("timeline", str(REF_JOURNAL),
                              "--out", str(out))
    assert rc == 0, stderr
    trace = json.loads(out.read_text())
    assert validate_trace(trace) == []
    n_events = len(read_journal(str(REF_JOURNAL)))
    assert trace["otherData"]["journal_events"] == n_events
    assert "trace events" in stdout
