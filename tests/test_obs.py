"""Observability subsystem (ISSUE 7): telemetry, journal, drift, CLI.

Layered like the subsystem: pure units (wire-byte accounting, schema
validation, the drift monitor's band logic), the Recorder's append-only
CSV + journal sink contracts, profiling helpers, and two end-to-end CPU
ring-8 MATCHA runs shared module-wide — a *consistent* one (measured
contraction within the predicted ρ band) and a deliberately *mis-planned*
one (``alpha_override`` executes 5% of the solved α while the monitor
predicts with the solved α) that must trip a ``drift`` journal event.
"""

import dataclasses
import json
import os
import pathlib

import numpy as np
import pytest

from matcha_tpu.obs import (
    DriftMonitor,
    Telemetry,
    append_journal_record,
    compose_predicted_rho,
    drift_report,
    epoch_series,
    make_event,
    read_journal,
    validate_event,
)
from matcha_tpu.obs.telemetry import make_telemetry_spec
from matcha_tpu.parallel.gossip import matching_wire_bytes
from matcha_tpu.train import TrainConfig, train
from matcha_tpu.train.recorder import Recorder

pytestmark = pytest.mark.obs

REPO = pathlib.Path(__file__).resolve().parents[1]

# ring-8 MATCHA at budget 0.5, pure gossip (lr 0) from an *unsynced* init:
# the consensus-dominant regime where per-epoch contraction is measurable
# against rho — the same recipe as the committed reference journal
BASE = TrainConfig(
    name="obs", model="mlp", dataset="synthetic",
    dataset_kwargs={"num_train": 256, "num_test": 32},
    num_workers=8, graphid=5, batch_size=8, epochs=6, lr=0.0,
    warmup=False, momentum=0.0, weight_decay=0.0, matcha=True, budget=0.5,
    seed=3, save=True, sync_init=False, eval_every=0,
    measure_comm_split=False,
)


@pytest.fixture(scope="module")
def ring8_run(tmp_path_factory):
    root = tmp_path_factory.mktemp("obs_ring8")
    cfg = dataclasses.replace(BASE, name="ring8", savePath=str(root))
    result = train(cfg)
    return result, str(root / "ring8_mlp")


@pytest.fixture(scope="module")
def misplan_run(tmp_path_factory):
    root = tmp_path_factory.mktemp("obs_misplan")
    cfg = dataclasses.replace(BASE, name="misplan", savePath=str(root),
                              alpha_override=0.03)
    result = train(cfg)
    return result, str(root / "misplan_mlp")


# ---------------------------------------------------------------- telemetry

def test_telemetry_accumulates_against_static_accounting(ring8_run):
    """Per-epoch counters must equal the schedule's own static accounting:
    steps = batches/epoch, matchings = the flag rows' sum, wire bytes = the
    fired matchings' dense exchange at f32 — the device-side accumulator
    is bookkeeping, not an estimate."""
    result, _ = ring8_run
    events = result.recorder.events
    epochs, steps = epoch_series(events, "telemetry", "steps")
    assert epochs == list(range(BASE.epochs))
    assert all(s == 4.0 for s in steps)  # 256 train / 8 workers / bs 8
    flags = np.asarray(result.schedule.flags, np.float64)
    bytes_vec = matching_wire_bytes(result.schedule.decomposed,
                                    _flat_dim(result), "f32")
    _, wire = epoch_series(events, "telemetry", "wire_bytes")
    _, match = epoch_series(events, "telemetry", "matchings_mean")
    for e in range(BASE.epochs):
        rows = flags[e * 4:(e + 1) * 4]
        assert match[e] == pytest.approx(rows.sum() / 4.0)
        # f32 accumulator vs f64 reference: exact to f32 resolution
        assert wire[e] == pytest.approx(float(rows.sum(0) @ bytes_vec),
                                        rel=1e-5)
    _, alive = epoch_series(events, "telemetry", "alive_min")
    assert all(a == 8.0 for a in alive)
    _, quant = epoch_series(events, "telemetry", "quantized_values")
    assert all(q == 0.0 for q in quant)  # f32 wire quantizes nothing


def _flat_dim(result) -> int:
    leaves = [np.asarray(v) for v in
              __import__("jax").tree_util.tree_leaves(result.state.params)]
    return sum(int(np.prod(l.shape[1:])) for l in leaves)


def test_matching_wire_bytes_static_and_bf16_halves():
    dec = [[(0, 1), (2, 3)], [(1, 2)]]
    f32 = matching_wire_bytes(dec, dim=10, wire_dtype="f32")
    bf16 = matching_wire_bytes(dec, dim=10, wire_dtype="bf16")
    assert f32.tolist() == [2 * 2 * 10 * 4, 2 * 1 * 10 * 4]
    assert (bf16 * 2 == f32).all()
    spec32 = make_telemetry_spec(dec, 10, wire_dtype="f32")
    spec16 = make_telemetry_spec(dec, 10, wire_dtype="bf16", overlap="1step")
    assert not spec32.quantizing and not spec32.overlap
    assert spec16.quantizing and spec16.overlap
    assert (spec16.wire_values_per_matching
            == spec32.wire_values_per_matching).all()


def test_telemetry_never_trips_retrace_watch(ring8_run):
    """The accumulator is part of the scanned carry: if it caused
    per-epoch recompiles the journal would record a retrace event.

    Regression pin: the watch's first-ever run caught a real one —
    ``shard_workers`` placed state with ``P('workers', None, ...)`` while
    the compiled epoch returned ``P('workers')``; the specs describe the
    same placement but miss the jit cache, so every mesh run silently
    recompiled the whole epoch program at epoch 1 (fixed in
    ``parallel/mesh.py``).  Under the 8-device conftest mesh this test
    re-trips on any such cache-key drift."""
    result, _ = ring8_run
    assert not [e for e in result.recorder.events
                if e["kind"] == "retrace"]


def test_overlap_bf16_counters_journal(tmp_path):
    """The pipelined + narrow-wire run journals what it does: every step
    consumes a one-step-stale mix, and every fired matching's exchanged
    values count as quantized (bf16 wire) with bytes exactly half of the
    f32 ledger for the same flags."""
    cfg = dataclasses.replace(
        BASE, name="ov", savePath=str(tmp_path), epochs=2,
        overlap="1step", wire_dtype="bf16",
        dataset_kwargs={"num_train": 64, "num_test": 32})
    result = train(cfg)
    events = result.recorder.events
    _, steps = epoch_series(events, "telemetry", "steps")
    _, stale = epoch_series(events, "telemetry", "stale_steps")
    assert stale == steps  # every pipelined step consumes a stale mix
    _, quant = epoch_series(events, "telemetry", "quantized_values")
    _, wire = epoch_series(events, "telemetry", "wire_bytes")
    flags = np.asarray(result.schedule.flags, np.float64)
    bytes_bf16 = matching_wire_bytes(result.schedule.decomposed,
                                     _flat_dim(result), "bf16")
    bpe = int(steps[0])
    for e in range(cfg.epochs):
        rows = flags[e * bpe:(e + 1) * bpe]
        assert wire[e] == pytest.approx(float(rows.sum(0) @ bytes_bf16),
                                        rel=1e-5, abs=1e-6)
        # value count x 2 bytes == byte count (bf16 ledger is half of f32);
        # an epoch whose flags never fired legitimately counts zero
        assert quant[e] * 2 == pytest.approx(wire[e], rel=1e-5, abs=1e-6)


# ------------------------------------------------------------------ journal

def test_reference_journal_validates_line_by_line():
    """The committed artifact pins the schema: every line must validate,
    and the kinds the docs promise must actually occur.  Re-pinned at v2
    (ISSUE 8): the journal now carries the cost ledger's `compile` event
    for the scanned-epoch program, populated on this CPU backend.  ISSUE 9
    re-pins with the elastic `membership` kind: the reference recipe churns
    w3 (leave @2, rejoin @5), so both transitions — and their re-derived
    α/ρ — are committed evidence, not just vocabulary.  ISSUE 10 re-pins
    at v3 with the live health plane: the recipe gained a period-4
    fault-plan straggler on w5 (4-step epochs ⇒ participation exactly
    0.25), so the journal commits one `heartbeat` per epoch and the
    streaming detector's `straggler` `anomaly` verdicts naming w5.
    ISSUE 11 re-pins at v4 with the attribution plane: the regeneration
    script appends one `attribution` event from a planted heterogeneous-
    link scenario (matching 1 priced 3x matching 0), so the estimator's
    recovered per-matching seconds are committed evidence too.  ISSUE 17
    re-pins at v6 with the serve plane riding the same run through the
    REAL TrainerHarness: one `backend` selection record (the v5 kind,
    journaled since ISSUE 13 but first committed here), one `promotion`
    (the consensus mean promoted at epoch 4, mid-churn), and one applied
    `control` hot-swap (budget 0.5 -> 0.35 at the epoch-6 boundary, after
    the rejoin re-fold) carrying the re-based drift prediction — which is
    exactly what keeps `obs_tpu drift` exit 0 on this journal
    (test_cli_drift_exit_codes): the replay re-bases at the swap like the
    live monitor did.  ISSUE 30 re-pins at v9 with the step's `fwd_bwd`
    record; ISSUE 24 re-pinned at v8 with the loop's `spans` records.  ISSUE 18 re-pinned at v7 with the recovery ladder:
    the recipe checkpoints every epoch (`checkpoint` events + digest
    sidecars) and the regeneration script bit-flips the newest
    generation, lets the sidecar convict it, quarantines it through the
    real helpers, and appends the resulting `recovery` event."""
    events = read_journal(str(REPO / "benchmarks" / "events_ring8.jsonl"))
    assert events, "reference journal is empty"
    for i, e in enumerate(events):
        assert validate_event(e) == [], f"line {i + 1}: {validate_event(e)}"
    assert {e["v"] for e in events} == {9}
    kinds = {e["kind"] for e in events}
    assert {"run_start", "epoch", "telemetry", "compile",
            "membership", "heartbeat", "anomaly", "attribution",
            "backend", "control", "promotion", "checkpoint",
            "recovery", "spans", "fwd_bwd"} <= kinds
    # v9 (ISSUE 30): one `fwd_bwd` record a run; this run's model is an MLP
    # under a fault plan and a membership trace, so it names a reason
    (plan,) = [e for e in events if e["kind"] == "fwd_bwd"]
    assert plan["packed"] is False and plan["workers_per_pack"] == 1
    assert "no packed form" in plan["reason"]
    # v8 (ISSUE 24): one `spans` record an epoch period, the rejoin's
    # bootstrap a child of `prime`
    periods = [e for e in events if e["kind"] == "spans"]
    assert [e["period"] for e in periods] == [f"{k}.0" for k in range(8)]
    assert [s["parent"] for e in periods for s in e["spans"]
            if s["name"] == "membership_bootstrap"] == ["5.0/prime"]
    leave, rejoin = [e for e in events if e["kind"] == "membership"]
    assert (leave["epoch"], rejoin["epoch"]) == (2, 5)
    assert [t["kind"] for t in leave["trigger"]] == ["leave"]
    assert [t["kind"] for t in rejoin["trigger"]] == ["rejoin"]
    assert (sum(leave["old_alive"]), sum(leave["new_alive"])) == (8.0, 7.0)
    assert (sum(rejoin["old_alive"]), sum(rejoin["new_alive"])) == (7.0, 8.0)
    for m in (leave, rejoin):
        assert m["replanned"] is True  # hysteresis 0: eager re-fold
        assert 0.0 < m["alpha"] < 1.0 and 0.0 < m["rho"] < 1.0
    # w3's leave disconnects a ring edge pair ⇒ the 7-live set contracts
    # worse than the full ring; the rejoin re-folds back to the pool plan
    # exactly (alpha_scale 1 = executed α IS the schedule-built α again)
    assert leave["rho"] > rejoin["rho"]
    assert leave["alpha_scale"] != pytest.approx(1.0)
    assert rejoin["alpha_scale"] == pytest.approx(1.0)
    # v3 health plane: one heartbeat per epoch, member slots only (w3's
    # vacancy window drops it from the roster), and the straggler's
    # participation — 1 step in 4 — is committed as exactly 0.25
    heartbeats = [e for e in events if e["kind"] == "heartbeat"]
    assert [e["epoch"] for e in heartbeats] == list(range(8))
    assert all(e["host"] == "host0" for e in heartbeats)
    assert sorted(heartbeats[0]["workers"]) == [f"w{i}" for i in range(8)]
    assert all("w3" not in e["workers"] for e in heartbeats[2:5])
    assert "w3" in heartbeats[5]["workers"]
    for e in heartbeats:
        assert e["workers"]["w5"]["participation"] == pytest.approx(0.25)
        assert e["step_time"] > 0 and e["step_time_ewma"] > 0
        assert e["comp_time"] >= 0 and e["comm_time"] >= 0
    stragglers = [e for e in events if e["kind"] == "anomaly"
                  and e["cause"] == "straggler"]
    assert [e["subject"] for e in stragglers] == ["w5"] * 8
    assert all(e["value"] == pytest.approx(0.25)
               and e["value"] < e["threshold"] for e in stragglers)
    # the fault-plan declaration (`plan`) now precedes run_start: the
    # recorder journals the compiled fault horizon before the run record
    [start] = [e for e in events if e["kind"] == "run_start"]
    assert 0.0 < start["predicted"]["rho"] < 1.0
    assert start["predicted"]["steps_per_epoch"] == 4
    [compile_e] = [e for e in events if e["kind"] == "compile"]
    assert compile_e["label"] == "epoch_scan"
    assert compile_e["flops"] > 0 and compile_e["hbm_bytes"] > 0
    assert compile_e["peak_bytes"] > 0 and compile_e["compile_seconds"] > 0
    # the journal's telemetry series is strictly ordered and parseable
    epochs, d = epoch_series(events, "telemetry", "disagreement_mean")
    assert epochs == sorted(epochs) and len(epochs) >= 6
    assert all(v > 0 for v in d)
    # v4 attribution plane: the planted heterogeneous-link scenario is
    # recovered — both matchings identifiable, matching 1 priced 3x
    # matching 0 (the regeneration script's PLANTED_MATCHING_SECONDS)
    [attr] = [e for e in events if e["kind"] == "attribution"]
    assert attr["source"].startswith("planted:")
    assert attr["identifiable"] == [True, True]
    theta = attr["per_matching_seconds"]
    assert theta[0] == pytest.approx(0.02, rel=1e-3)
    assert theta[1] == pytest.approx(0.06, rel=1e-3)
    assert attr["base_seconds"] == pytest.approx(0.01, rel=1e-3)
    # v6 serve plane: one applied hot-swap through the real value path
    # (re-solved row scaling, re-based prediction riding the event) and
    # one promotion decision with its gating held-out metric — and the
    # zero-retrace contract holds on the committed run itself
    [swap] = [e for e in events if e["kind"] == "control"]
    assert (swap["action"], swap["applied"], swap["epoch"]) \
        == ("apply", True, 6)
    assert swap["version"] == 1
    assert swap["fields"]["budget"]["budget"] == pytest.approx(0.35)
    assert len(swap["fields"]["budget"]["row_scale"]) == 2  # per-matching
    assert 0.0 < swap["predicted"]["rho"] < 1.0
    [promo] = [e for e in events if e["kind"] == "promotion"]
    assert (promo["action"], promo["epoch"], promo["serving_epoch"]) \
        == ("promote", 4, 4)
    assert 0.0 <= promo["metric"] <= 1.0 and len(promo["content_hash"]) == 16
    # v7 recovery plane: per-epoch checkpoints and the quarantine the
    # regeneration script forced through the real ladder helpers (a
    # bit-flipped newest generation convicted by its digest sidecar)
    checkpoints = [e for e in events if e["kind"] == "checkpoint"]
    assert [e["epoch"] for e in checkpoints] == list(range(8))
    [recovery] = [e for e in events if e["kind"] == "recovery"]
    assert (recovery["scope"], recovery["action"]) \
        == ("checkpoint", "quarantine")
    assert recovery["epoch"] == 7
    assert "digest verification failed" in recovery["reason"]
    assert recovery["quarantined"].endswith("quarantine-7")
    assert not [e for e in events if e["kind"] == "retrace"]


def test_validate_event_rejects_drift():
    ok = make_event("telemetry", 1.0, epoch=0, steps=4.0,
                    disagreement_mean=0.1, disagreement_last=0.1,
                    wire_bytes=1.0, matchings_mean=1.0, alive_mean=8.0)
    assert validate_event(ok) == []
    assert validate_event({"v": 3, "kind": "telemetry", "t": 0.0})
    assert any("unknown kind" in p
               for p in validate_event(make_event("nonsense", 0.0)))
    assert any("missing" in p
               for p in validate_event(make_event("drift", 0.0)))
    assert any("t=" in p for p in
               validate_event({"v": 1, "kind": "resume", "t": -1.0}))


def test_v1_events_validate_verbatim_and_v2_kinds_are_versioned():
    """The v1→v2 bump is additive: a v1 writer's events validate under the
    v2 reader unchanged, the new kinds are in the vocabulary, and a
    `compile`/`profile` event claiming v=1 is a lying envelope."""
    from matcha_tpu.obs.journal import EVENT_KINDS, V2_KINDS

    assert V2_KINDS == {"compile", "profile", "device_scopes", "membership"}
    assert V2_KINDS <= EVENT_KINDS
    v1 = {"v": 1, "kind": "resume", "t": 0.5, "epoch": 3}
    assert validate_event(v1) == []
    member = {"v": 2, "kind": "membership", "t": 1.0, "epoch": 2,
              "old_alive": [1.0, 1.0], "new_alive": [1.0, 0.0],
              "trigger": [{"kind": "leave", "epoch": 2, "worker": "w1"}],
              "alpha": 0.5, "rho": 0.9, "replanned": True}
    assert validate_event(member) == []
    assert any("v2 kind" in p
               for p in validate_event({**member, "v": 1}))
    assert any("missing" in p for p in validate_event(
        {k: v for k, v in member.items() if k != "alpha"}))
    v1_epoch = {"v": 1, "kind": "epoch", "t": 1.0, "epoch": 0,
                "epoch_time": 1.0, "comp_time": 1.0, "comm_time": 0.0,
                "train_loss": 2.3, "disagreement": 0.1}
    assert validate_event(v1_epoch) == []
    lying = {"v": 1, "kind": "compile", "t": 0.0, "label": "x",
             "fingerprint": "f", "compile_seconds": 0.1, "flops": 1.0,
             "hbm_bytes": 1.0, "peak_bytes": 1.0}
    assert any("v2 kind" in p for p in validate_event(lying))
    assert validate_event({**lying, "v": 2}) == []


def test_v3_kinds_are_versioned_and_v2_events_validate_verbatim():
    """The v2→v3 bump (ISSUE 10) is additive the same way: every v2
    event validates verbatim under the v3 reader, and a `heartbeat` /
    `anomaly` event claiming v<=2 is a lying envelope."""
    from matcha_tpu.obs.journal import EVENT_KINDS, V3_KINDS

    assert V3_KINDS == {"heartbeat", "anomaly"}
    assert V3_KINDS <= EVENT_KINDS
    hb = {"v": 3, "kind": "heartbeat", "t": 1.0, "host": "host0",
          "epoch": 0, "step": 4, "step_time": 0.1, "step_time_ewma": 0.1,
          "comp_time": 0.3, "comm_time": 0.1, "peak_bytes": None,
          "workers": {"w0": {"slot": 0, "participation": 1.0,
                             "disagreement": 0.01}}}
    anomaly = {"v": 3, "kind": "anomaly", "t": 1.0, "epoch": 0,
               "subject": "w5", "cause": "straggler", "value": 0.25,
               "threshold": 0.9}
    for event in (hb, anomaly):
        assert validate_event(event) == []
        assert any("v3 kind" in p
                   for p in validate_event({**event, "v": 2}))
        assert any("v3 kind" in p
                   for p in validate_event({**event, "v": 1}))
        assert any("missing" in p for p in validate_event(
            {k: v for k, v in event.items() if k != "epoch"}))
    # pre-bump events are untouched: a v2 membership/compile event and a
    # v1 epoch event all still validate verbatim under the v3 reader
    v2 = {"v": 2, "kind": "compile", "t": 0.0, "label": "x",
          "fingerprint": "f", "compile_seconds": 0.1, "flops": 1.0,
          "hbm_bytes": 1.0, "peak_bytes": 1.0}
    assert validate_event(v2) == []
    # a corrupt sub-v1 envelope on a kind with no pinned minimum must
    # report problems, not KeyError out of the reader
    problems = validate_event({"v": 0, "kind": "epoch", "t": 1.0})
    assert any("v1 kind" in p for p in problems)
    assert any("v=0" in p for p in problems)


def test_v4_kinds_are_versioned_and_v3_events_validate_verbatim():
    """The v3→v4 bump (ISSUE 11) is additive the same way: every v3 event
    validates verbatim under the v4 reader, and an `attribution` event
    claiming v<=3 is a lying envelope."""
    from matcha_tpu.obs.journal import EVENT_KINDS, V4_KINDS

    assert V4_KINDS == {"attribution"}
    assert V4_KINDS <= EVENT_KINDS
    attr = {"v": 4, "kind": "attribution", "t": 1.0, "epochs_used": 8,
            "matchings": 2, "identifiable": [True, False],
            "base_seconds": 0.01, "per_matching_seconds": [0.02, None],
            "source": "journal:epoch.comm_time"}
    assert validate_event(attr) == []
    for v in (1, 2, 3):
        assert any("v4 kind" in p
                   for p in validate_event({**attr, "v": v}))
    assert any("missing" in p for p in validate_event(
        {k: v for k, v in attr.items() if k != "identifiable"}))
    # pre-bump events are untouched under the v4 reader
    v3 = {"v": 3, "kind": "anomaly", "t": 1.0, "epoch": 0, "subject": "w5",
          "cause": "straggler", "value": 0.25, "threshold": 0.9}
    assert validate_event(v3) == []


def test_v6_kinds_are_versioned_and_v1_to_v5_validate_verbatim(tmp_path):
    """The v5→v6 bump (ISSUE 17) is additive the same way: one sample
    event per pre-bump version (v1 resume, v2 membership, v3 heartbeat,
    v4 attribution, v5 backend) validates verbatim under the v6 reader
    AND round-trips byte-identically through the journal writer — both
    directions of compatibility.  A `control` / `promotion` event
    claiming v<=5 is a lying envelope."""
    from matcha_tpu.obs.journal import (
        EVENT_KINDS,
        KIND_MIN_VERSION,
        V5_KINDS,
        V6_KINDS,
    )

    assert V5_KINDS == {"backend"}
    assert V6_KINDS == {"control", "promotion"}
    assert V6_KINDS <= EVENT_KINDS
    control = {"v": 6, "kind": "control", "t": 1.0, "epoch": 3,
               "action": "apply", "applied": True, "version": 2,
               "reason": "value-scope fields ['budget']",
               "fields": {"budget": {"budget": 0.25}}}
    promotion = {"v": 6, "kind": "promotion", "t": 1.0, "epoch": 4,
                 "action": "rollback", "metric": 0.61, "test_loss": 1.2,
                 "serving_epoch": 2, "content_hash": "ab" * 8}
    for event in (control, promotion):
        assert KIND_MIN_VERSION[event["kind"]] == 6
        assert validate_event(event) == []
        for v in (1, 2, 3, 4, 5):
            assert any("v6 kind" in p
                       for p in validate_event({**event, "v": v}))
    assert any("missing" in p for p in validate_event(
        {k: v for k, v in control.items() if k != "applied"}))
    assert any("missing" in p for p in validate_event(
        {k: v for k, v in promotion.items() if k != "metric"}))
    # one pre-bump writer per version, verbatim-valid both directions:
    # the v6 reader accepts each, and the journal writer round-trips the
    # exact lines (a v6 writer never rewrites history it appends after)
    pre_bump = [
        {"v": 1, "kind": "resume", "t": 0.5, "epoch": 3},
        {"v": 2, "kind": "membership", "t": 1.0, "epoch": 2,
         "old_alive": [1.0, 1.0], "new_alive": [1.0, 0.0],
         "trigger": [{"kind": "leave", "epoch": 2, "worker": "w1"}],
         "alpha": 0.5, "rho": 0.9, "replanned": True},
        {"v": 3, "kind": "heartbeat", "t": 1.0, "host": "host0",
         "epoch": 0, "step": 4, "step_time": 0.1, "step_time_ewma": 0.1,
         "comp_time": 0.3, "comm_time": 0.1, "peak_bytes": None,
         "workers": {"w0": {"slot": 0, "participation": 1.0,
                            "disagreement": 0.01}}},
        {"v": 4, "kind": "attribution", "t": 1.0, "epochs_used": 8,
         "matchings": 2, "identifiable": [True, True],
         "base_seconds": 0.01, "per_matching_seconds": [0.02, 0.06],
         "source": "journal:epoch.comm_time"},
        {"v": 5, "kind": "backend", "t": 1.0, "requested": "auto",
         "chosen": "fused", "reason": "measured within gate"},
    ]
    path = tmp_path / "pre_bump.jsonl"
    with open(path, "w") as f:
        for e in pre_bump:
            assert validate_event(e) == [], e["kind"]
            f.write(json.dumps(e) + "\n")
    before = path.read_bytes()
    append_journal_record(str(path), "control", epoch=1, action="stop",
                          applied=True, reason="operator stop document")
    assert read_journal(str(path))[:-1] == pre_bump  # grown, not rewritten
    assert path.read_bytes().startswith(before)


def test_v7_recovery_kind_is_versioned_and_v6_validates_verbatim():
    """The v6→v7 bump (ISSUE 18) is additive: `recovery` is the one new
    kind, it requires its scope/action/reason payload, and a `recovery`
    event claiming v<=6 is a lying envelope; v6 serve-plane events
    validate verbatim under the v7 reader."""
    from matcha_tpu.obs.journal import (
        EVENT_KINDS,
        KIND_MIN_VERSION,
        SCHEMA_VERSION,
        V7_KINDS,
    )

    assert SCHEMA_VERSION >= 7
    assert V7_KINDS == {"recovery"}
    assert V7_KINDS <= EVENT_KINDS
    recovery = {"v": 7, "kind": "recovery", "t": 1.0, "epoch": 3,
                "scope": "checkpoint", "action": "quarantine",
                "reason": "digest verification failed: a.bin: "
                          "content hash mismatch",
                "quarantined": "runs/x_ckpt/quarantine-3"}
    assert KIND_MIN_VERSION["recovery"] == 7
    assert validate_event(recovery) == []
    for v in (1, 2, 3, 4, 5, 6):
        assert any("v7 kind" in p
                   for p in validate_event({**recovery, "v": v}))
    assert any("missing" in p for p in validate_event(
        {k: v for k, v in recovery.items() if k != "scope"}))
    v6_control = {"v": 6, "kind": "control", "t": 1.0, "epoch": 3,
                  "action": "apply", "applied": True, "version": 2,
                  "reason": "value-scope fields ['budget']",
                  "fields": {"budget": {"budget": 0.25}}}
    assert validate_event(v6_control) == []


def test_v8_spans_kind_is_versioned_and_v7_validates_verbatim():
    """The v7→v8 bump (ISSUE 24) is additive: `spans` is the one new kind,
    it requires the period's identity, bounds, sample count and span list,
    and a `spans` event claiming v<=7 is a lying envelope; a v7 `recovery`
    event validates verbatim under the v8 reader."""
    from matcha_tpu.obs.journal import (
        EVENT_KINDS,
        KIND_MIN_VERSION,
        SCHEMA_VERSION,
        V8_KINDS,
    )

    assert SCHEMA_VERSION >= 8
    assert V8_KINDS == {"spans"} and V8_KINDS <= EVENT_KINDS
    assert KIND_MIN_VERSION["spans"] == 8
    record = {"v": 8, "kind": "spans", "t": 9.0, "epoch": 3, "attempt": 1,
              "period": "3.1", "t0": 4.0, "t1": 9.0, "samples": 4096,
              "spans": [{"name": "h2d", "t0": 4.5, "t1": 4.75,
                         "parent": "3.1", "bytes": 1 << 20}]}
    assert validate_event(record) == []
    for v in range(1, 8):
        assert any("v8 kind" in p
                   for p in validate_event({**record, "v": v}))
    assert any("missing" in p for p in validate_event(
        {k: v for k, v in record.items() if k != "spans"}))
    v7_recovery = {"v": 7, "kind": "recovery", "t": 1.0, "scope": "io",
                   "action": "degraded", "reason": "ENOSPC",
                   "sink": "recorder"}
    assert validate_event(v7_recovery) == []


def test_v9_fwd_bwd_kind_is_versioned_and_v8_validates_verbatim():
    """The v8→v9 bump (ISSUE 30) is additive: `fwd_bwd` is the one new
    kind, it requires the three numbers of the plan (`reason` rides along
    on the per-worker path), and a `fwd_bwd` event claiming v<=8 is a lying
    envelope; a v8 `spans` event validates verbatim under the v9 reader."""
    from matcha_tpu.obs.journal import (
        EVENT_KINDS,
        KIND_MIN_VERSION,
        SCHEMA_VERSION,
        V9_KINDS,
    )

    assert SCHEMA_VERSION == 9
    assert V9_KINDS == {"fwd_bwd"} and V9_KINDS <= EVENT_KINDS
    assert KIND_MIN_VERSION["fwd_bwd"] == 9
    record = {"v": 9, "kind": "fwd_bwd", "t": 0.5, "packed": True,
              "workers_per_pack": 8, "packs_per_slab": 8}
    assert validate_event(record) == []
    assert validate_event({**record, "packed": False, "workers_per_pack": 1,
                           "packs_per_slab": 64, "reason": "remat"}) == []
    for v in range(1, 9):
        assert any("v9 kind" in p
                   for p in validate_event({**record, "v": v}))
    assert any("missing" in p for p in validate_event(
        {k: v for k, v in record.items() if k != "workers_per_pack"}))
    v8_spans = {"v": 8, "kind": "spans", "t": 9.0, "epoch": 3, "attempt": 1,
                "period": "3.1", "t0": 4.0, "t1": 9.0, "samples": 4096,
                "spans": []}
    assert validate_event(v8_spans) == []


def test_read_journal_tail_is_bounded_and_exact(tmp_path):
    """ISSUE 8 satellite: `tail` must cost O(tail bytes), not O(file).
    A synthetic 10k-event journal: the bounded reverse read returns
    exactly the full read's tail while touching only the last blocks."""
    from matcha_tpu.obs import read_journal_tail
    from matcha_tpu.obs.journal import _tail_lines

    path = tmp_path / "big.jsonl"
    with open(path, "w") as f:
        for i in range(10_000):
            f.write(json.dumps({"v": 2, "kind": "resume", "t": float(i),
                                "epoch": i}) + "\n")
    full = read_journal(str(path))
    for n in (1, 5, 20, 10_001):
        assert read_journal_tail(str(path), n) == full[-n:]
    assert read_journal_tail(str(path), 0) == []

    class CountingFile:
        def __init__(self, f):
            self._f = f
            self.bytes_read = 0

        def seek(self, *a):
            return self._f.seek(*a)

        def tell(self):
            return self._f.tell()

        def read(self, n):
            self.bytes_read += n
            return self._f.read(n)

    size = path.stat().st_size
    with open(path, "rb") as raw:
        cf = CountingFile(raw)
        lines = _tail_lines(cf, 20, block=4096)
    assert len(lines) == 20
    assert cf.bytes_read <= 2 * 4096 < size  # bounded: ~one block of ~500kB

    # blank separator lines cost extra block reads but never shrink the
    # result below the n events the file actually holds (review finding:
    # a newline-counting stop condition returned 2 of 5 here)
    gappy = tmp_path / "gappy.jsonl"
    with open(gappy, "w") as f:
        for i in range(10):
            f.write(json.dumps({"v": 2, "kind": "resume", "t": float(i),
                                "epoch": i}) + "\n\n\n")
    got = read_journal_tail(str(gappy), 5, block=32)
    assert got == read_journal(str(gappy))[-5:] and len(got) == 5

    # crash-truncated final line: dropped, like read_journal(repair=True)
    with open(path, "a") as f:
        f.write('{"v": 2, "kind": "ep')
    tail = read_journal_tail(str(path), 3)
    assert tail == full[-3:]
    # malformed line mid-window is corruption: loud
    bad = tmp_path / "bad.jsonl"
    bad.write_text('{"v": 1, "kind": "resume", "t": 0.0}\nnot json\n'
                   '{"v": 1, "kind": "resume", "t": 1.0}\n')
    with pytest.raises(ValueError, match="malformed journal line"):
        read_journal_tail(str(bad), 3)


def test_run_journal_is_written_and_faults_view_absent(ring8_run):
    """A fault-free saved run writes events.jsonl but no faults.json —
    the ledger is a view that only materializes when fault events exist."""
    _, run_dir = ring8_run
    assert os.path.exists(os.path.join(run_dir, "events.jsonl"))
    assert not os.path.exists(os.path.join(run_dir, "faults.json"))
    disk = read_journal(os.path.join(run_dir, "events.jsonl"))
    assert [e["kind"] for e in disk][0] == "run_start"


def test_plan_verify_reads_ledger_from_journal(tmp_path):
    """`plan verify` back-compat: a run dir holding only events.jsonl (no
    faults.json view) still yields the degradation summary."""
    from matcha_tpu.plan.verify import load_fault_ledger

    run = tmp_path / "run"
    run.mkdir()
    ev = make_event("plan", 0.1, name="chaos", events=[],
                    expected_alive=[1.0, 0.5], expected_link_up=[0.9])
    (run / "events.jsonl").write_text(json.dumps(ev) + "\n")
    ledger = load_fault_ledger(str(run))
    assert ledger is not None
    assert ledger["expected_alive"] == [1.0, 0.5]
    assert load_fault_ledger(str(tmp_path / "nowhere")) is None


# ----------------------------------------------------------------- recorder

def _mini_config(tmp_path, name="rec"):
    return dataclasses.replace(BASE, name=name, savePath=str(tmp_path),
                               epochs=25)


def _feed(recorder, rng, epochs):
    for _ in range(epochs):
        recorder.add_epoch(
            epoch_time=float(rng.uniform(1, 2)),
            comp_time=float(rng.uniform(0.5, 1)),
            comm_time=float(rng.uniform(0, 0.5)),
            train_acc=rng.uniform(size=recorder.num_workers),
            train_loss=rng.uniform(size=recorder.num_workers),
            test_acc=rng.uniform(size=recorder.num_workers),
            disagreement=float(rng.uniform()),
        )


def test_recorder_append_only_flush_is_byte_identical(tmp_path, monkeypatch):
    """ISSUE 7 satellite: incremental flushes (the O(1)-per-flush append
    path) must produce byte-for-byte the CSVs a single full rewrite
    would.  Identical data through both recorders; one saves at the
    10-epoch cadence + final, the other exactly once.  The wall clock is
    faked deterministic — ``recordtime`` is a real series and must byte-
    compare too."""
    import matcha_tpu.train.recorder as recorder_mod

    fake = {"now": 1000.0}

    def fake_time():
        fake["now"] += 0.125
        return fake["now"]

    monkeypatch.setattr(recorder_mod.time, "time", fake_time)
    cfg_a = _mini_config(tmp_path / "a")
    cfg_b = _mini_config(tmp_path / "b")
    # run A fully, then rewind the fake clock and run B: save() never reads
    # the clock, so both recorders see the identical timestamp stream and
    # even the recordtime series must byte-compare
    rec_a = Recorder(cfg_a, 4)
    rng_a = np.random.default_rng(7)
    for flush_at in (10, 10, 5):  # 25 epochs in three uneven flushes
        _feed(rec_a, rng_a, flush_at)
        rec_a.save()
    fake["now"] = 1000.0
    rec_b = Recorder(cfg_b, 4)
    _feed(rec_b, np.random.default_rng(7), 25)
    rec_b.save()
    logs_a = sorted(p.name for p in pathlib.Path(rec_a.folder).glob("*.log"))
    logs_b = sorted(p.name for p in pathlib.Path(rec_b.folder).glob("*.log"))
    assert logs_a == logs_b and len(logs_a) == 4 * 8  # 4 ranks x 8 series
    for name in logs_a:
        a = (pathlib.Path(rec_a.folder) / name).read_bytes()
        b = (pathlib.Path(rec_b.folder) / name).read_bytes()
        assert a == b, f"append-only flush diverged from full write: {name}"
        assert len(a.splitlines()) == 25


def test_recorder_append_only_rewrites_after_resume(tmp_path):
    """After load_previous the disk file may hold MORE rows than memory
    (resume from an older checkpoint): the next save must truncate-rewrite,
    not append — and the journal must extend, never rewrite."""
    cfg = _mini_config(tmp_path)
    rec = Recorder(cfg, 4)
    _feed(rec, np.random.default_rng(0), 10)
    rec.save()
    events_before = len(read_journal(rec.journal.path))
    rec2 = Recorder(cfg, 4)
    assert rec2.load_previous(6) == 6  # resume at epoch 6: truncates to 6
    _feed(rec2, np.random.default_rng(1), 2)
    rec2.save()
    a_log = next(pathlib.Path(rec2.folder).glob("*-r0-losses.log"))
    assert len(a_log.read_bytes().splitlines()) == 8  # 6 kept + 2 new
    events_after = read_journal(rec2.journal.path)
    assert len(events_after) == events_before + 2  # extended, not rewritten
    assert [e["kind"] for e in events_after[:events_before]] \
        == [e["kind"] for e in read_journal(rec.journal.path)][:events_before]


def test_journal_repairs_crash_truncated_tail(tmp_path):
    """A crash mid-append leaves a partial final line: strict reads stay
    loud, the resume path repairs (drops the tail) and schedules a full
    rewrite so the next flush leaves a whole file — never a broken line
    buried mid-stream."""
    cfg = _mini_config(tmp_path)
    rec = Recorder(cfg, 4)
    _feed(rec, np.random.default_rng(0), 3)
    rec.save()
    whole = len(read_journal(rec.journal.path))
    with open(rec.journal.path, "a") as f:
        f.write('{"v": 1, "kind": "epo')  # the crash-truncated tail
    with pytest.raises(ValueError, match="malformed journal line"):
        read_journal(rec.journal.path)
    rec2 = Recorder(cfg, 4)
    rec2.load_previous(3)
    # parsed prefix, tail dropped — and the repair journals itself as a
    # v7 `recovery` event (ISSUE 18: silent repair is history rewritten)
    assert len(rec2.events) == whole + 1
    repair = rec2.events[-1]
    assert (repair["kind"], repair["scope"], repair["action"]) \
        == ("recovery", "journal", "repair")
    _feed(rec2, np.random.default_rng(1), 1)
    rec2.save()
    healed = read_journal(rec2.journal.path)  # strict read: whole again
    # prefix + the repair record + the one post-resume epoch
    assert len(healed) == whole + 2
    # a malformed line mid-file is corruption, not a crash tail: loud even
    # with repair on
    bad = tmp_path / "bad.jsonl"
    bad.write_text('{"v": 1, "kind": "resume", "t": 0.0}\nnot json\n'
                   '{"v": 1, "kind": "resume", "t": 1.0}\n')
    with pytest.raises(ValueError):
        read_journal(str(bad), repair=True)


def test_recorder_faults_view_round_trips(tmp_path):
    cfg = _mini_config(tmp_path)
    rec = Recorder(cfg, 4)
    rec.log_fault("rollback", epoch=3, reason="test", lr_scale=0.5,
                  attempt=1)
    _feed(rec, np.random.default_rng(0), 1)
    rec.save()
    with open(os.path.join(rec.folder, "faults.json")) as f:
        ledger = json.load(f)
    [entry] = ledger["events"]
    assert entry["kind"] == "rollback" and entry["epoch"] == 3
    assert "recordtime" in entry and "v" not in entry  # historical shape
    # and the same event is in the journal with the envelope
    journal = read_journal(rec.journal.path)
    assert [e for e in journal if e["kind"] == "rollback"]


# ---------------------------------------------------------------- profiling

def test_trace_creates_nonempty_trace_dir(tmp_path):
    """ISSUE 7 satellite: `trace` must create the log dir and produce a
    non-empty capture on CPU (the TensorBoard/Perfetto artifact path)."""
    import jax
    import jax.numpy as jnp

    from matcha_tpu.utils import trace

    log_dir = tmp_path / "tb" / "nested"
    f = jax.jit(lambda x: jnp.sum(x * x))
    f(jnp.ones(16))  # compile outside the trace window
    with trace(str(log_dir)):
        out = f(jnp.ones(16))
        jax.block_until_ready(out)
    produced = [p for p in log_dir.rglob("*") if p.is_file()]
    assert produced, "profiler trace produced no files"
    assert any(p.stat().st_size > 0 for p in produced)


def test_host_span_and_device_span_nest_in_jit_without_retrace():
    """ISSUE 7 satellite: both span helpers must be trace-pure — a step
    using them compiles once and never again (the retrace sanitizer is
    the arbiter, same as for the production step)."""
    import jax
    import jax.numpy as jnp

    from matcha_tpu.analysis.sanitizer import check_single_trace, retrace_guard
    from matcha_tpu.utils import SpanRecorder, device_span

    def step(x):
        with device_span("test/phase_a"):
            y = x * 2.0
        with device_span("test/phase_b"):
            with device_span("test/nested"):
                return jnp.sum(y)

    guarded, counter = retrace_guard(jax.jit(step))
    with SpanRecorder().span("test/host_phase"):
        for _ in range(4):
            guarded(jnp.ones(8)).block_until_ready()
    check_single_trace(counter, "span step")
    assert counter.count == 1


# -------------------------------------------------------------------- drift

def test_drift_monitor_band_logic_units():
    fast = DriftMonitor(0.6, 2, tolerance=0.25, patience=2)
    d = 1.0
    assert all(fast.observe(e, d * (0.55 ** e)) is None for e in range(8))
    flat = DriftMonitor(0.6, 2, tolerance=0.25, patience=2)
    trips = [flat.observe(e, 1.0 * (0.97 ** e)) for e in range(8)]
    assert any(t is not None for t in trips)
    first = next(t for t in trips if t is not None)
    assert first["measured_factor"] > first["predicted_factor"] * 1.25
    with pytest.raises(ValueError):
        DriftMonitor(0.5, 0)
    with pytest.raises(ValueError):
        DriftMonitor(0.5, 2, patience=0)


def test_drift_report_rebases_on_alpha_rederivation():
    """Replay parity with the live monitor: a mid-run α re-derivation
    re-based the live prediction, so the replay must re-base at the same
    epoch — the same decaying series that trips against the original
    (optimistic) ρ is in-band once the journaled re-derivation applies.
    An explicit --rho what-if still overrides everything."""
    def journal(with_rederivation):
        events = [make_event("run_start", 0.0, config={},
                             predicted={"rho": 0.09, "steps_per_epoch": 2,
                                        "tolerance": 0.25, "patience": 2})]
        d = 1.0
        for ep in range(6):
            if with_rederivation and ep == 1:
                events.append(make_event(
                    "alpha_rederived", float(ep), epoch=ep, old=0.6,
                    new=0.2, rho=0.8, predicted={"rho": 0.8}))
            events.append(make_event(
                "telemetry", float(ep), epoch=ep, steps=2.0,
                disagreement_mean=d, disagreement_last=d, wire_bytes=1.0,
                matchings_mean=1.0, alive_mean=8.0))
            d *= 0.8
        return events

    tripped = drift_report(journal(with_rederivation=False))
    assert not tripped["consistent"]  # 0.8/epoch vs rho 0.09: drift
    rebased = drift_report(journal(with_rederivation=True))
    assert rebased["consistent"]      # re-derived plan promises 0.8: in band
    what_if = drift_report(journal(with_rederivation=True), rho=0.09,
                           patience=1)
    assert not what_if["consistent"]  # explicit --rho wins over re-basing
    assert rebased["rebases"] == 1 and tripped["rebases"] == 0
    # counters accumulate across plan segments instead of resetting
    assert rebased["checked_epochs"] >= tripped["checked_epochs"] - 1


def test_drift_what_if_ignores_live_journaled_events(misplan_run):
    """`--rho` asks "would this run have satisfied THAT plan?" — the live
    drift events were scored against the ORIGINAL plan and must not veto
    the what-if answer.  The mis-planned run, scored against the rho its
    overridden alpha actually delivers (≈1 ⇒ predicted factor 1), is
    consistent; without the override the journaled events still damn it."""
    import obs_tpu

    _, run_dir = misplan_run
    assert obs_tpu.main(["drift", run_dir]) == 1
    assert obs_tpu.main(["drift", run_dir, "--rho", "0.9999"]) == 0


def test_compose_predicted_rho_consistency():
    from matcha_tpu.schedule.solvers import contraction_rho
    from matcha_tpu.topology import matching_laplacians, select_graph

    dec = select_graph(5)  # 8-node ring
    Ls = matching_laplacians(dec, 8)
    probs = np.full(len(dec), 0.7)
    base = compose_predicted_rho(Ls, probs, 0.5)
    assert base["rho"] == pytest.approx(
        float(contraction_rho(Ls, probs, 0.5)))
    assert base["wire_eps"] == 0.0
    bf16 = compose_predicted_rho(Ls, probs, 0.5, wire_dtype="bf16")
    assert bf16["rho"] > base["rho"]  # quantization can only slow the bound
    assert bf16["floor_rel"] == pytest.approx(2.0 * 2.0 ** -8)
    degraded = compose_predicted_rho(Ls, probs, 0.5,
                                     worker_alive=np.full(8, 0.8))
    assert degraded["rho"] >= base["rho"]  # deaths only slow contraction
    assert degraded["rho_base"] == base["rho_base"]


def test_ring8_run_is_within_predicted_band(ring8_run):
    """Acceptance: the CPU ring-8 MATCHA run's measured per-epoch
    contraction stays inside the predicted ρ tolerance band — no drift
    journaled live, none found on replay."""
    result, run_dir = ring8_run
    assert not [e for e in result.recorder.events if e["kind"] == "drift"]
    report = drift_report(read_journal(os.path.join(run_dir,
                                                    "events.jsonl")))
    assert report["consistent"]
    assert report["violations"] == 0
    assert report["predicted_factor"] == pytest.approx(
        report["rho"] ** (report["steps_per_epoch"] / 2.0))


def test_misplanned_alpha_trips_drift(misplan_run):
    """Acceptance: executing 5% of the solved α while the monitor predicts
    with the solved α must journal a drift event (live) and replay as
    PLANNER DRIFT — and the run_start records both alphas so the journal
    is self-explaining."""
    result, run_dir = misplan_run
    drift = [e for e in result.recorder.events if e["kind"] == "drift"]
    assert drift, "mis-planned run journaled no drift event"
    assert drift[0]["measured_factor"] > drift[0]["predicted_factor"]
    events = read_journal(os.path.join(run_dir, "events.jsonl"))
    start = events[0]
    assert start["predicted"]["executed_alpha"] == pytest.approx(0.03)
    assert start["predicted"]["plan_alpha"] > 0.1
    report = drift_report(events)
    assert not report["consistent"]
    assert report["journaled"]


# ---------------------------------------------------------------------- CLI

def test_cli_summary_tail_and_markdown(ring8_run, tmp_path, capsys):
    import obs_tpu

    _, run_dir = ring8_run
    md = tmp_path / "summary.md"
    assert obs_tpu.main(["summary", run_dir, "--md", str(md)]) == 0
    out = capsys.readouterr().out
    assert "total wire bytes" in out and "rho=" in out
    text = md.read_text()
    assert text.startswith("# Run journal") and "| epoch |" in text
    assert obs_tpu.main(["tail", run_dir, "-n", "5"]) == 0
    assert "telemetry" in capsys.readouterr().out


def test_summarize_dedupes_replayed_membership_events():
    """A crash-resume replays its boundary reconciliation, journaling the
    same membership transition again — summarize() must keep the latest
    per epoch (the telemetry/epoch dedupe contract, journal.py), not list
    the 8→7 transition twice."""
    from matcha_tpu.obs.report import summarize

    mem = {"v": 2, "kind": "membership", "epoch": 2,
           "old_alive": [1.0] * 8, "new_alive": [1.0] * 7 + [0.0],
           "trigger": [{"kind": "leave", "epoch": 2, "worker": "w7"}],
           "alpha": 0.5, "rho": 0.9, "replanned": True}
    events = [{**mem, "t": 1.0},
              {**mem, "t": 9.0, "alpha": 0.6},  # the resume's replay
              {**mem, "t": 5.0, "epoch": 4, "trigger": []}]
    digest = summarize(events)
    assert [e["epoch"] for e in digest["membership"]] == [2, 4]
    assert digest["membership"][0]["alpha"] == 0.6  # latest wins


def test_cli_drift_exit_codes(ring8_run, misplan_run, capsys):
    import obs_tpu

    _, good = ring8_run
    _, bad = misplan_run
    assert obs_tpu.main(["drift", good]) == 0
    assert "within the predicted tolerance band" in capsys.readouterr().out
    assert obs_tpu.main(["drift", bad]) == 1
    assert "PLANNER DRIFT" in capsys.readouterr().out
    # what-if override: the good run scored against an absurdly optimistic
    # plan (rho -> 0.01) must fail the band (patience 1: the floor guard
    # leaves few checked epochs in a fast-converging run)
    assert obs_tpu.main(["drift", good, "--rho", "0.01",
                         "--patience", "1"]) == 1
    capsys.readouterr()
    assert obs_tpu.main(["drift", str(REPO / "benchmarks"
                                      / "events_ring8.jsonl")]) == 0


def test_cli_compare_mixes_bench_records_and_journals(ring8_run, tmp_path,
                                                      capsys):
    import obs_tpu

    _, run_dir = ring8_run
    journal = tmp_path / "bench_journal.jsonl"
    record = {"metric": "gossip-steps/sec", "value": 123.4,
              "unit": "gossip_steps_per_sec", "vs_baseline": 0.02,
              "backend": "dense"}
    append_journal_record(str(journal), "bench", record=record,
                          status="measured")
    # a driver-style capture whose command failed: rc and a tail, no record
    failed = tmp_path / "BENCH_r01.json"
    failed.write_text(json.dumps(
        {"n": 1, "cmd": "python bench.py", "rc": 1,
         "tail": "Traceback (most recent call last):\n", "parsed": None}))
    rc = obs_tpu.main(["compare", str(journal), str(failed), run_dir])
    assert rc == 0
    out = capsys.readouterr().out
    assert "123.4" in out and "BENCH_r01.json" in out
    assert obs_tpu.main(["compare", str(tmp_path / "missing.jsonl")]) == 2


def test_cli_compare_names_missing_bench_siblings(tmp_path, capsys):
    """Completeness (ISSUE 19): comparing a strict subset of a directory's
    BENCH_r*.json records names every omitted sibling in the output —
    the committed trajectory can never silently shrink — and the full set
    renders clean."""
    import obs_tpu

    for r in (1, 2, 3):
        (tmp_path / f"BENCH_r0{r}.json").write_text(json.dumps(
            {"metric": "gossip-steps/sec", "value": 100.0 + r,
             "unit": "gossip_steps_per_sec", "vs_baseline": 0.02,
             "backend": "dense"}))
    assert obs_tpu.main(["compare", str(tmp_path / "BENCH_r01.json"),
                         str(tmp_path / "BENCH_r03.json")]) == 0
    out = capsys.readouterr().out
    assert "missing from table: BENCH_r02.json" in out
    assert "BENCH_r01.json" in out and "unreadable" not in out
    # the complete set is clean
    assert obs_tpu.main(
        ["compare"] + [str(tmp_path / f"BENCH_r0{r}.json")
                       for r in (1, 2, 3)]) == 0
    assert "missing from table" not in capsys.readouterr().out


def test_cli_compare_reads_multichip_records(tmp_path, capsys):
    """ISSUE 8 satellite: the MULTICHIP_r*.json dryrun stamps (in-tree
    since r1) land in the same compare table — n_devices as the value,
    ok/rc/skipped as the verdict column."""
    import obs_tpu

    rc = obs_tpu.main(["compare", str(REPO / "MULTICHIP_r01.json"),
                       str(REPO / "MULTICHIP_r05.json")])
    assert rc == 0
    out = capsys.readouterr().out
    assert "multichip_dryrun_devices" in out
    assert out.count(" ok ") >= 2 or out.count("ok") >= 2
    # a failed dryrun shows its rc instead of a silent ok
    failed = tmp_path / "MULTICHIP_bad.json"
    failed.write_text(json.dumps(
        {"n_devices": 4, "rc": 7, "ok": False, "skipped": False}))
    skipped = tmp_path / "MULTICHIP_skip.json"
    skipped.write_text(json.dumps(
        {"n_devices": 0, "rc": 0, "ok": False, "skipped": True}))
    assert obs_tpu.main(["compare", str(failed), str(skipped)]) == 0
    out = capsys.readouterr().out
    assert "rc=7" in out and "skipped" in out


def test_bench_journal_sink_appends_valid_event(tmp_path, capsys):
    """``obs_tpu.py roofline --journal`` mirrors its report as a `bench`
    event the journal's schema validates (``append_journal_record``, the
    one-shot appender of standalone emitters); without the option it
    writes nothing."""
    import obs_tpu

    path = tmp_path / "j.jsonl"
    argv = ["roofline", "--workers", "4", "--topology", "ring",
            "--dim", "512", "--measured", "5000.1"]
    assert obs_tpu.main(argv) == 0
    assert not path.exists()
    assert obs_tpu.main(argv + ["--journal", str(path)]) == 0
    capsys.readouterr()
    [event] = read_journal(str(path))
    assert validate_event(event) == []
    assert event["kind"] == "bench"
    assert event["record"]["unit"] == "roofline_report"
    assert event["record"]["roofline"]["measured_steps_per_sec"] == 5000.1


# ------------------------------------------------------------- checkpointing

def test_checkpoint_resume_with_telemetry(tmp_path):
    """Telemetry is stripped from checkpoints and re-attached on resume:
    a checkpointed+resumed run keeps journaling telemetry for the resumed
    epochs and appends a `resume` event after the original journal."""
    root = tmp_path / "ckpt"
    cfg = dataclasses.replace(
        BASE, name="resume", savePath=str(root), epochs=2,
        checkpoint_every=2,
        dataset_kwargs={"num_train": 64, "num_test": 32})
    train(cfg)
    ckpt = str(root / "resume_ckpt")
    cfg2 = dataclasses.replace(cfg, epochs=4, resume=ckpt)
    result = train(cfg2)
    events = result.recorder.events
    kinds = [e["kind"] for e in events]
    assert "resume" in kinds and "checkpoint" in kinds
    epochs, steps = epoch_series(events, "telemetry", "steps")
    assert epochs == [0, 1, 2, 3]  # pre-crash + resumed epochs all present
    assert all(s > 0 for s in steps)
