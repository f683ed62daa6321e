"""Chaos harness: taps, injectors, best-effort IO, the recovery ladder,
and the seeded campaign (DESIGN.md §23).

Fast tests here run in tier-1; the full 26-seed campaign e2e is marked
``slow`` (it supervises dozens of real trainer subprocesses) and runs in
the dedicated chaos lane / TPU session instead.
"""

import dataclasses
import json
import os
import random
import signal
import time

import pytest

from matcha_tpu.chaos import BARRIERS, maybe_kill, taps
from matcha_tpu.chaos.campaign import (
    FAMILIES,
    FaultSpec,
    run_trial,
    schedule_for_seed,
)
from matcha_tpu.chaos.injectors import (
    bitflip_checkpoint,
    corrupt_journal_midstream,
    delete_checkpoint_file,
    stale_checkpoint_tempfile,
    tear_journal_tail,
    torn_control_tempfile,
)
from matcha_tpu.chaos.invariants import (
    EXPECTED_RECOVERY,
    EXPECTED_RESTARTS,
    check_invariants,
    final_epoch_row,
)
from matcha_tpu.obs import bestio
from matcha_tpu.obs.bestio import (
    BestEffortSink,
    DirectFS,
    FaultyFS,
    get_fs,
    install_fs,
    wall_clock,
)
from matcha_tpu.obs.journal import (
    append_journal_record,
    read_journal,
    salvage_journal,
)
from matcha_tpu.serve.control import load_control, write_control
from matcha_tpu.serve.controller import Controller, ServeConfig
from matcha_tpu.train import TrainConfig, train
from matcha_tpu.train.checkpoint import (
    checkpoint_digest,
    latest_step,
    quarantine_step,
    restore_with_fallback,
    save_checkpoint,
    verify_checkpoint_digest,
)

pytestmark = pytest.mark.chaos


@pytest.fixture(autouse=True)
def _clean_chaos_seams(monkeypatch):
    """Every test leaves the process-global seams unarmed: the tap spec
    cache re-reads the (monkeypatch-restored) environment and the fs
    seam falls back to DirectFS on next use."""
    yield
    taps.reset()
    install_fs(None)


# ------------------------------------------------------- seeded schedules

def test_schedule_for_seed_is_pure_and_covers_every_family():
    first = [schedule_for_seed(s) for s in range(30)]
    again = [schedule_for_seed(s) for s in range(30)]
    assert first == again
    assert {s.family for s in first} == set(FAMILIES)
    # one full rotation: seeds 0..len-1 hit each family exactly once
    assert [schedule_for_seed(s).family
            for s in range(len(FAMILIES))] == list(FAMILIES)


def test_fault_spec_json_roundtrip():
    spec = schedule_for_seed(11)
    assert FaultSpec(**spec.to_json()) == spec


def test_every_family_has_pinned_expectations():
    assert set(EXPECTED_RESTARTS) == set(FAMILIES)
    assert set(EXPECTED_RECOVERY) == set(FAMILIES)
    # kill families charge exactly one restart; everything else must be
    # absorbed in-process
    for family in FAMILIES:
        expected = 1 if family.startswith("kill_") else 0
        assert EXPECTED_RESTARTS[family] == expected, family


# ----------------------------------------------------------------- the taps

def _arm(monkeypatch, tmp_path, barrier, count=1, signal_name="USR1"):
    marker = str(tmp_path / "fired")
    monkeypatch.setenv(taps.ENV_KILL, json.dumps(
        {"barrier": barrier, "count": count, "signal": signal_name,
         "marker": marker}))
    taps.reset()
    return marker


def test_tap_unarmed_is_a_noop(monkeypatch):
    monkeypatch.delenv(taps.ENV_KILL, raising=False)
    taps.reset()
    for barrier in BARRIERS:
        maybe_kill(barrier)  # must not raise, must not signal


def test_tap_fires_on_the_scheduled_occurrence_with_marker(monkeypatch,
                                                           tmp_path):
    fired = []
    prev = signal.signal(signal.SIGUSR1, lambda *_: fired.append(1))
    try:
        marker = _arm(monkeypatch, tmp_path, "mid_save", count=2)
        maybe_kill("epoch_boundary")  # wrong barrier: never counts
        maybe_kill("mid_save")        # occurrence 1 of 2: passes clean
        assert not fired and not os.path.exists(marker)
        maybe_kill("mid_save")        # occurrence 2: fires
        assert fired == [1]
        assert os.path.exists(marker)
        # the marker is the cross-lifetime memory: same env, same tap,
        # but it already fired — a relaunch runs the barrier clean
        maybe_kill("mid_save")
        assert fired == [1]
    finally:
        signal.signal(signal.SIGUSR1, prev)


def test_tap_preexisting_marker_means_already_fired(monkeypatch, tmp_path):
    marker = _arm(monkeypatch, tmp_path, "epoch_boundary")
    with open(marker, "w"):
        pass
    maybe_kill("epoch_boundary")  # would SIGUSR1 us if it fired
    assert os.path.getsize(marker) == 0


@pytest.mark.parametrize("raw", [
    "not json", '{"count": 1}', '{"barrier": "nope", "marker": "/x"}',
    '{"barrier": "mid_save"}',  # marker missing
])
def test_tap_malformed_spec_disarms_silently(monkeypatch, raw):
    monkeypatch.setenv(taps.ENV_KILL, raw)
    taps.reset()
    for barrier in BARRIERS:
        maybe_kill(barrier)  # chaos must never break a real run


# ------------------------------------------------------------- the fs seam

def test_faultyfs_enospc_window_and_match_gate(tmp_path):
    fs = FaultyFS(mode="enospc", match="health", after=1, count=2)
    hp = str(tmp_path / "health-x.json")
    other = str(tmp_path / "other.json")
    with fs.open(hp, "w") as f:       # matching op 1: before the window
        f.write("a")
    with fs.open(other, "w") as f:    # non-matching: never counted
        f.write("b")
    for _ in range(2):                # ops 2 and 3: the fault window
        with pytest.raises(OSError, match="no space left"):
            fs.open(hp, "w")
    with fs.open(hp, "w") as f:       # op 4: the device healed
        f.write("c")
    with fs.open(hp) as f:            # reads never trip
        assert f.read() == "c"


def test_faultyfs_slow_mode_delays_and_replace_trips(tmp_path):
    fs = FaultyFS(mode="slow", delay=0.15, count=1)
    src, dst = str(tmp_path / "a"), str(tmp_path / "b")
    with open(src, "w") as f:
        f.write("x")
    t0 = time.monotonic()
    fs.replace(src, dst)
    assert time.monotonic() - t0 >= 0.15
    assert os.path.exists(dst)


def test_get_fs_env_parse_and_malformed_fallback(monkeypatch):
    install_fs(None)
    monkeypatch.setenv(bestio.ENV_FS, json.dumps(
        {"mode": "enospc", "match": "health", "count": 3}))
    fs = get_fs()
    assert isinstance(fs, FaultyFS) and fs.count == 3
    install_fs(None)
    monkeypatch.setenv(bestio.ENV_FS, "{broken")
    fs = get_fs()
    assert type(fs) is DirectFS  # malformed spec must not break a run


def test_wall_clock_applies_injected_skew(monkeypatch):
    monkeypatch.setenv(bestio.ENV_SKEW, "600")
    assert wall_clock() - time.time() > 590
    monkeypatch.setenv(bestio.ENV_SKEW, "garbage")
    assert abs(wall_clock() - time.time()) < 5


# ------------------------------------------------------- best-effort sink

def test_sink_failure_degrades_loudly_then_restores():
    sink = BestEffortSink("t", deadline=2.0, retries=1, backoff=0.01,
                          cooldown=0.2)
    calls = []

    def failing():
        calls.append(1)
        raise OSError("chaos: no space left on device")

    assert sink.write(failing) is False
    assert len(calls) == 2          # one retry, then the breaker trips
    assert sink.degraded
    events = sink.drain()
    assert [e["action"] for e in events] == ["degraded"]
    assert events[0]["scope"] == "io" and events[0]["sink"] == "t"
    assert "no space left" in events[0]["reason"]
    # breaker open: drops without touching the write path
    assert sink.write(failing) is False
    assert len(calls) == 2
    time.sleep(0.25)                # cooldown elapsed: probe write
    assert sink.write(lambda: None) is True
    assert not sink.degraded
    restored = sink.drain()
    assert [e["action"] for e in restored] == ["restored"]


def test_sink_hung_write_is_abandoned_within_the_deadline():
    sink = BestEffortSink("t", deadline=0.2, retries=0, cooldown=10.0)
    t0 = time.monotonic()
    assert sink.write(lambda: time.sleep(1.0)) is False
    assert time.monotonic() - t0 < 0.8  # one deadline, not one sleep
    assert sink.degraded
    # while the abandoned thread is stuck, writes skip fast
    t0 = time.monotonic()
    assert sink.write(lambda: None) is False
    assert time.monotonic() - t0 < 0.1
    assert any("hung" in e["reason"] or "deadline" in e["reason"]
               for e in sink.drain())


# ------------------------------------------------- journal torn/corrupt

def _seed_journal(path, n=5):
    for i in range(n):
        append_journal_record(str(path), "recovery", scope="io",
                              action="restored", reason=f"seed {i}",
                              epoch=i)
    return str(path)


def test_torn_tail_repairs_but_strict_read_raises(tmp_path):
    rng = random.Random(0)
    path = _seed_journal(tmp_path / "events.jsonl")
    evidence = tear_journal_tail(path, rng)
    assert evidence["cut_bytes"] >= 2
    with pytest.raises(ValueError, match="malformed journal line"):
        read_journal(path)
    assert [e["epoch"] for e in read_journal(path, repair=True)] == list(
        range(4))
    # salvage on a tail-only tear: prefix returned, nothing quarantined
    events, quarantined, problem = salvage_journal(path)
    assert len(events) == 4 and quarantined is None
    assert "tail" in problem


def test_midstream_corruption_salvages_prefix_and_quarantines(tmp_path):
    rng = random.Random(1)
    path = _seed_journal(tmp_path / "events.jsonl")
    evidence = corrupt_journal_midstream(path, rng)
    # repair only forgives the tail: interior damage still raises — and
    # as a malformed-line ValueError with the line number, even though
    # the injected bytes are not UTF-8
    with pytest.raises(ValueError, match="malformed journal line"):
        read_journal(path, repair=True)
    events, quarantined, problem = salvage_journal(path)
    assert len(events) == evidence["line"]  # the prefix before the damage
    assert quarantined == path + ".corrupt-1"
    assert os.path.exists(quarantined) and not os.path.exists(path)
    assert "mid-stream" in problem


# ------------------------------------- digest sidecar + quarantine ladder

def _fabricate_step(root, step=7):
    d = os.path.join(str(root), str(step))
    os.makedirs(os.path.join(d, "sub"))
    with open(os.path.join(d, "a.bin"), "wb") as f:
        f.write(b"\x00" * 64)
    with open(os.path.join(d, "sub", "b.bin"), "wb") as f:
        f.write(b"payload")
    digest = checkpoint_digest(str(root), step)
    with open(os.path.join(str(root), f"digest-{step}.json"), "w") as f:
        json.dump(digest, f)
    return str(root), step


def test_digest_verifies_then_catches_every_corruption_mode(tmp_path):
    root, step = _fabricate_step(tmp_path)
    assert verify_checkpoint_digest(root, step) == []
    bitflip_checkpoint(root, step, random.Random(0))
    problems = verify_checkpoint_digest(root, step)
    assert problems and "hash mismatch" in problems[0]


def test_digest_catches_missing_and_unexpected_files(tmp_path):
    root, step = _fabricate_step(tmp_path)
    delete_checkpoint_file(root, step, random.Random(2))
    assert any("missing" in p for p in verify_checkpoint_digest(root, step))
    with open(os.path.join(root, str(step), "extra.bin"), "wb") as f:
        f.write(b"x")
    assert any("unexpected" in p
               for p in verify_checkpoint_digest(root, step))


def test_no_sidecar_means_unverifiable_accepted(tmp_path):
    root, step = _fabricate_step(tmp_path)
    os.remove(os.path.join(root, f"digest-{step}.json"))
    assert verify_checkpoint_digest(root, step) is None


def test_quarantine_step_moves_generation_and_sidecars_aside(tmp_path):
    root, step = _fabricate_step(tmp_path)
    with open(os.path.join(root, f"schedule-{step}.json"), "w") as f:
        f.write("{}")
    q1 = quarantine_step(root, step)
    assert q1 == os.path.join(root, f"quarantine-{step}")
    assert not os.path.exists(os.path.join(root, str(step)))
    assert os.path.isdir(os.path.join(q1, str(step)))
    assert os.path.exists(os.path.join(q1, f"digest-{step}.json"))
    assert os.path.exists(os.path.join(q1, f"schedule-{step}.json"))
    # a recreated step at the same number quarantines to a fresh dir
    os.makedirs(os.path.join(root, str(step)))
    q2 = quarantine_step(root, step)
    assert q2 == os.path.join(root, f"quarantine-{step}-2")
    # quarantine dirs are invisible to the step scanner
    assert latest_step(root) is None


# -------------------------------------- recovery ladder e2e (satellite)

CHAOS_CFG = TrainConfig(
    name="cz",
    model="mlp",
    dataset="synthetic",
    dataset_kwargs={"num_train": 64, "num_test": 16},
    num_workers=4, devices=1,
    graphid=None,
    topology="ring",
    batch_size=8,
    epochs=2,
    lr=0.05,
    warmup=False,
    matcha=True,
    budget=0.5,
    seed=3,
    save=True,
    eval_every=0,
    checkpoint_every=1,
    measure_comm_split=False,
)


def test_partial_step_dir_falls_back_and_later_save_does_not_trip(tmp_path):
    """ISSUE 18 satellite: kill -9 mid-orbax-save leaves a partial step
    directory — resume must restore the previous generation (quarantining
    the damage, journaled), and the very next save at the colliding step
    number must land clean."""
    cfg = dataclasses.replace(CHAOS_CFG, savePath=str(tmp_path))
    train(cfg)
    ckpt = f"{cfg.savePath}/{cfg.name}_ckpt"
    assert latest_step(ckpt) == 1
    # the torn-save state: step 1 committed no sidecar (the kill landed
    # before it) and lost part of its payload mid-write
    os.remove(os.path.join(ckpt, "digest-1.json"))
    step_dir = os.path.join(ckpt, "1")
    for base, _dirs, names in os.walk(step_dir):
        for name in names:
            os.remove(os.path.join(base, name))
    # resume: the ladder must quarantine step 1, restore step 0, and the
    # epoch-1 re-save must not trip over the quarantined leftover
    cfg2 = dataclasses.replace(cfg, epochs=3)
    r2 = train(cfg2, resume_dir=ckpt)
    assert r2.history[0]["epoch"] == 1  # resumed from generation 0
    assert latest_step(ckpt) == 2
    assert os.path.isdir(os.path.join(ckpt, "quarantine-1"))
    events = read_journal(f"{cfg.savePath}/{cfg.name}_{cfg.model}"
                          "/events.jsonl")
    recoveries = [e for e in events if e["kind"] == "recovery"]
    assert any(e["scope"] == "checkpoint" and e["action"] == "quarantine"
               for e in recoveries)
    # the replacement generation at step 1 carries a verifying digest
    assert verify_checkpoint_digest(ckpt, 1) == []


def test_restore_with_fallback_skips_digest_corrupt_latest(tmp_path):
    cfg = dataclasses.replace(CHAOS_CFG, savePath=str(tmp_path))
    r1 = train(cfg)
    ckpt = f"{cfg.savePath}/{cfg.name}_ckpt"
    bitflip_checkpoint(ckpt, 1, random.Random(5))
    notices = []
    state, epoch = restore_with_fallback(ckpt, template=r1.state,
                                         notices=notices)
    assert epoch == 0
    assert [n["step"] for n in notices] == [1]
    assert "digest verification failed" in notices[0]["reason"]
    assert os.path.isdir(notices[0]["path"])
    # the damaged generation moved aside: a fresh save at step 1 lands
    save_checkpoint(ckpt, state, 1)
    assert verify_checkpoint_digest(ckpt, 1) == []


def test_restore_with_fallback_every_generation_dead_raises(tmp_path):
    cfg = dataclasses.replace(CHAOS_CFG, savePath=str(tmp_path))
    r1 = train(cfg)
    ckpt = f"{cfg.savePath}/{cfg.name}_ckpt"
    for step in (0, 1):
        bitflip_checkpoint(ckpt, step, random.Random(step))
    with pytest.raises(ValueError, match="every checkpoint generation"):
        restore_with_fallback(ckpt, template=r1.state)
    with pytest.raises(FileNotFoundError):
        restore_with_fallback(str(tmp_path / "empty"), template=r1.state)


# ------------------------------------------- torn control publish (satellite)

def test_torn_control_tempfile_is_invisible_to_the_watcher(tmp_path):
    path = str(tmp_path / "control.json")
    write_control(path, {"version": 1, "budget": 0.25})
    evidence = torn_control_tempfile(path, version=99)
    assert os.path.exists(evidence["path"])  # the torn tmp is on disk
    raw, problems = load_control(path)
    assert raw == {"version": 1, "budget": 0.25} and not problems
    # with nothing published, a torn tmp alone means "no document" — not
    # an unreadable one
    alone = str(tmp_path / "other" / "control.json")
    torn_control_tempfile(alone)
    assert load_control(alone) == (None, [])


def test_stale_checkpoint_tempfile_never_blocks_the_ladder(tmp_path):
    cfg = dataclasses.replace(CHAOS_CFG, savePath=str(tmp_path))
    r1 = train(cfg)
    ckpt = f"{cfg.savePath}/{cfg.name}_ckpt"
    stale_checkpoint_tempfile(ckpt, 1)
    notices = []
    _state, epoch = restore_with_fallback(ckpt, template=r1.state,
                                          notices=notices)
    assert epoch == 1 and notices == []  # the stale tmp is inert


# ------------------------------------------------- supervisor satellites

def _controller(tmp_path, **kw):
    ctl = Controller(ServeConfig(
        config={"name": "c", "model": "mlp", "savePath": str(tmp_path)},
        **kw))
    os.makedirs(ctl.run_dir, exist_ok=True)
    return ctl


def test_serve_config_validates_chaos_fields(tmp_path):
    for bad in ({"refill_epochs": -1}, {"crash_window": -0.5}):
        with pytest.raises(ValueError):
            ServeConfig(config={"savePath": str(tmp_path)}, **bad)


def test_jitter_seed_pins_the_backoff_rng(tmp_path):
    a = _controller(tmp_path, jitter_seed=5)
    b = _controller(tmp_path, jitter_seed=5)
    assert [a._rng.random() for _ in range(4)] == [
        b._rng.random() for _ in range(4)]


def test_refill_restores_credits_for_checkpointed_progress(tmp_path):
    ctl = _controller(tmp_path, refill_epochs=2)
    ctl.restarts_used = 2
    ctl._maybe_refill(3)   # first observation only sets the base
    assert ctl.restarts_used == 2
    ctl._maybe_refill(7)   # 4 clean epochs at K=2 → 2 credits back
    assert ctl.restarts_used == 0
    events = read_journal(ctl.journal_path)
    refills = [e for e in events if e["kind"] == "recovery"
               and e["scope"] == "budget"]
    assert len(refills) == 1 and refills[0]["action"] == "refill"
    # never refills below zero used, and progress=None never counts
    ctl._maybe_refill(None)
    ctl._maybe_refill(20)
    assert ctl.restarts_used == 0
    assert len([e for e in read_journal(ctl.journal_path)
                if e["kind"] == "recovery"]) == 1


def test_crash_loop_escalates_to_checkpoint_quarantine(tmp_path):
    ctl = _controller(tmp_path, crash_window=60.0)
    os.makedirs(os.path.join(ctl.ckpt_dir, "4"))
    assert ctl._maybe_escalate(7, 4, 100.0) is False  # first crash
    assert ctl._maybe_escalate(8, 4, 101.0) is False  # different signature
    assert ctl._maybe_escalate(8, 4, 102.0) is True   # the loop: same, fast
    assert os.path.isdir(os.path.join(ctl.ckpt_dir, "quarantine-4"))
    events = [e for e in read_journal(ctl.journal_path)
              if e["kind"] == "recovery"]
    assert events[-1]["scope"] == "checkpoint"
    assert events[-1]["action"] == "quarantine"
    # the signature's cause was removed: the streak resets
    assert ctl._maybe_escalate(8, 3, 103.0) is False


def test_crash_loop_outside_the_window_never_escalates(tmp_path):
    ctl = _controller(tmp_path, crash_window=5.0)
    os.makedirs(os.path.join(ctl.ckpt_dir, "2"))
    assert ctl._maybe_escalate(9, 2, 100.0) is False
    assert ctl._maybe_escalate(9, 2, 200.0) is False  # 100s apart: unrelated
    assert ctl._maybe_escalate(9, None, 201.0) is False  # no checkpoint yet
    assert os.path.isdir(os.path.join(ctl.ckpt_dir, "2"))


# ------------------------------------------------------- invariant suite

def _fabricated_trial(tmp_path, family="clock_skew", epochs=4, rc=0,
                      restarts=0):
    path = str(tmp_path / "events.jsonl")
    for i in range(epochs):
        append_journal_record(
            path, "epoch", epoch=i, epoch_time=0.1, comp_time=0.05,
            comm_time=0.05, train_loss=1.0 - 0.1 * i, train_acc=0.5,
            test_acc_mean=0.5, disagreement=0.01)
    return {"seed": 0, "family": family, "rc": rc,
            "restarts_used": restarts, "journal_path": path,
            "serving_dir": None, "expect_epochs": epochs}


def test_invariants_pass_on_a_clean_fabricated_trial(tmp_path):
    assert check_invariants(_fabricated_trial(tmp_path)) == []


def test_invariants_catch_silent_death_and_wrong_accounting(tmp_path):
    trial = _fabricated_trial(tmp_path, rc=1)
    assert any(v.startswith("terminal-loud") for v in
               check_invariants(trial))
    trial = _fabricated_trial(tmp_path / "b", restarts=1)
    violations = check_invariants(trial)
    assert any("restart-accounting" in v for v in violations)


def test_invariants_catch_missing_final_epoch_and_twin_drift(tmp_path):
    trial = _fabricated_trial(tmp_path, epochs=3)
    trial["expect_epochs"] = 4  # the run claims rc 0 short of the goal
    assert any("final epoch" in v for v in check_invariants(trial))
    trial = _fabricated_trial(tmp_path / "b")
    row = final_epoch_row(read_journal(trial["journal_path"]))
    trial["twin_row"] = (row[0], row[1] + 1e-9, row[2], row[3], row[4])
    assert any(v.startswith("twin-fidelity")
               for v in check_invariants(trial))


def test_invariants_reject_ghost_torn_control_version(tmp_path):
    trial = _fabricated_trial(tmp_path, family="control_torn_tmp")
    trial["evidence"] = {"version": 99}
    assert check_invariants(trial) == []  # the ghost was never observed
    append_journal_record(
        trial["journal_path"], "control", epoch=2, action="apply",
        applied=True, reason="chaos ghost", version=99,
        fields={"budget": 0.25})
    assert any("torn" in v for v in check_invariants(trial))


# --------------------------------------------------- the campaign (slow)

@pytest.mark.slow
def test_campaign_single_durable_trial_end_to_end(tmp_path):
    """One real supervised trial (corrupt-latest): the headline
    acceptance — recovery from an older generation charging zero
    restarts — without the full campaign's wall-clock."""
    trial = run_trial(schedule_for_seed(0), str(tmp_path))
    assert trial["family"] == "ckpt_bitflip"
    assert trial["ok"], trial["violations"]
    assert trial["rc"] == 0 and trial["restarts_used"] == 0


@pytest.mark.slow
def test_campaign_all_families_pass_invariants(tmp_path):
    """The acceptance campaign: >= 25 seeded trials spanning every
    injector family, each judged by the pinned invariant suite."""
    from matcha_tpu.chaos.campaign import render_report, run_campaign

    campaign = run_campaign(range(26), str(tmp_path), log=print)
    assert campaign["trials"] == 26
    assert set(campaign["families"]) == set(FAMILIES)
    assert campaign["ok"], campaign["failed_seeds"]
    by_family = {}
    for r in campaign["results"]:
        by_family.setdefault(r["family"], []).append(r)
    # corrupted-latest recovered in-process from an older generation
    for r in by_family["ckpt_bitflip"]:
        assert r["restarts_used"] == 0 and r["rc"] == 0
    # kill-mid-save resumed to a final row byte-identical to its twin
    for r in by_family["kill_mid_save"]:
        assert r["restarts_used"] == 1
        assert tuple(r["twin_row"]) == final_epoch_row(
            read_journal(r["journal_path"]))
    report = render_report(campaign)
    assert "verdict: **PASS**" in report
