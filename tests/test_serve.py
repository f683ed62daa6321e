"""Production run controller (ISSUE 17): supervised daemon, hot-swap
control plane, checkpoint promotion, health endpoint.

Layered like the subsystem: control-document units (validation, atomic
publish, load semantics), the budget re-solve's first-moment identity,
promotion's promote/rollback state machine and tamper refusal, the
``fleet_verdict`` three-way parity pin (library == ``watch --once`` ==
``/healthz``), endpoint routing (multi-tenant ``?run=``), the in-process
e2e set the acceptance criteria name — identity knobs byte-match an
unsupervised run, a mid-run budget hot-swap with zero retraces, a forced
eval regression rolling the serving pointer back, a ``stop`` document
draining cleanly — and the slow subprocess e2e: kill -9 mid-run with a
supervised resume whose recorder/promotion state matches the
uninterrupted run's exactly.
"""

import dataclasses
import json
import os
import threading
import time
import urllib.error
import urllib.request

import numpy as np
import pytest

import obs_tpu
import serve_tpu
from matcha_tpu.obs import fleet_verdict, read_journal, validate_event
from matcha_tpu.obs.health import heartbeat_path
from matcha_tpu.obs.journal import SCHEMA_VERSION
from matcha_tpu.plan import resolve_budget_swap
from matcha_tpu.serve import (
    Controller,
    ControlKnobs,
    PromotionTampered,
    RESTART_EXIT,
    ServeConfig,
    ServeEndpoint,
    config_fingerprint,
    current_manifest,
    decide_promotion,
    load_control,
    prune_serving,
    validate_control,
    verify_promoted,
    write_candidate,
    write_control,
)
from matcha_tpu.serve.trainer import TrainerHarness
from matcha_tpu.train import TrainConfig, build_schedule, latest_step, train

pytestmark = pytest.mark.serve

# the serve recipe: ring-8 MATCHA, 4 steps/epoch, checkpoint every epoch
# (the supervisor's resume granularity IS the checkpoint cadence)
BASE = TrainConfig(
    name="serve", model="mlp", dataset="synthetic",
    dataset_kwargs={"num_train": 256, "num_test": 32},
    num_workers=8, graphid=5, batch_size=8, epochs=3, lr=0.05,
    warmup=False, matcha=True, budget=0.5, seed=3, save=True,
    eval_every=0, checkpoint_every=1, measure_comm_split=False,
)


def _journal(run_dir):
    return read_journal(os.path.join(run_dir, "events.jsonl"))


def _spec(tmp_path, **over):
    spec = {"control_path": None, "serving_dir": None, "promote_every": 0,
            "promote_margin": 0.0, "promote_keep": 3, "eval_batch": 256}
    spec.update(over)
    return spec


# ------------------------------------------------------ control documents

def test_validate_control_accepts_and_rejects():
    assert validate_control({"version": 1}) == []
    assert validate_control({"version": 3, "budget": 0.25,
                             "local_steps": 2, "staleness": 2,
                             "drift_tolerance": 0.5, "drift_patience": 4,
                             "membership_hysteresis": 1,
                             "membership_bootstrap": "mean"}) == []
    assert validate_control({"version": 2, "stop": True}) == []
    # one problem string per defect, nothing silently dropped
    problems = validate_control({"version": 0, "budget": 1.5,
                                 "stop": "yes", "mystery": 1,
                                 "local_steps": 0,
                                 "membership_bootstrap": "maybe"})
    text = "; ".join(problems)
    for needle in ("version", "budget", "stop", "mystery", "local_steps",
                   "membership_bootstrap"):
        assert needle in text, needle
    # bools are not ints; floats are not ints; missing version rejects
    assert validate_control({"version": True})
    assert validate_control({"version": 1, "local_steps": 2.0})
    assert validate_control({"budget": 0.5})
    assert validate_control([1, 2]) == ["control document must be a JSON "
                                        "object, got list"]


def test_write_control_atomic_and_refuses_invalid(tmp_path):
    path = str(tmp_path / "deep" / "control.json")
    write_control(path, {"version": 1, "budget": 0.25})
    raw, problems = load_control(path)
    assert problems == [] and raw == {"version": 1, "budget": 0.25}
    with pytest.raises(ValueError, match="budget"):
        write_control(path, {"version": 2, "budget": 7})
    # the failed write left the previous document intact and no temp junk
    raw, _ = load_control(path)
    assert raw["version"] == 1
    assert [f for f in os.listdir(tmp_path / "deep")
            if f.startswith(".control")] == []


def test_load_control_missing_and_corrupt(tmp_path):
    assert load_control(str(tmp_path / "nope.json")) == (None, [])
    bad = tmp_path / "control.json"
    bad.write_text("{not json")
    raw, problems = load_control(str(bad))
    assert raw == {} and "unreadable" in problems[0]


# ------------------------------------------------------- budget re-solve

def test_resolve_budget_swap_first_moment_exact():
    schedule = build_schedule(BASE, 10)
    swap = resolve_budget_swap(schedule, 0.25)
    p_old = np.asarray(schedule.probs, np.float64)
    alive = p_old > 1e-9
    # the defining identity: scaling the committed stream reproduces the
    # re-solved plan's first moment wherever the stream can deliver it
    np.testing.assert_allclose((swap["row_scale"] * p_old)[alive],
                               np.asarray(swap["probs"])[alive],
                               rtol=1e-12)
    assert (np.asarray(swap["probs"])[~alive] == 0).all()
    assert swap["alpha"] == pytest.approx(
        float(schedule.alpha) * swap["alpha_scale"])
    assert swap["unreachable"] >= 0 and 0 < swap["rho"] < 1


def test_resolve_budget_swap_identity_and_validation():
    schedule = build_schedule(BASE, 10)
    same = resolve_budget_swap(schedule, BASE.budget)
    # same budget, same deterministic solver: identity knobs
    np.testing.assert_allclose(
        same["row_scale"][np.asarray(schedule.probs) > 1e-9], 1.0,
        rtol=1e-6)
    assert same["alpha_scale"] == pytest.approx(1.0, rel=1e-6)
    with pytest.raises(ValueError, match="budget"):
        resolve_budget_swap(schedule, 1.5)


def test_control_knobs_identity():
    knobs = ControlKnobs.fresh(5)
    assert np.asarray(knobs.row_scale).tolist() == [1.0] * 5
    assert float(knobs.alpha_scale) == 1.0
    assert int(knobs.local_every) == 1
    # local_every clamps at 1: a zero cadence would divide the step index
    from matcha_tpu.serve import control_arrays

    assert int(control_arrays([1.0], 1.0, 0).local_every) == 1


# ------------------------------------------------------------- promotion

def _candidate(serving_dir, epoch, acc, seed=0):
    rng = np.random.default_rng(seed + epoch)
    return write_candidate(
        serving_dir, epoch, step=epoch * 4,
        arrays={"params_flat": rng.normal(size=(8,)).astype(np.float32)},
        metrics={"test_acc": acc, "test_loss": 1.0 - acc},
        fingerprint="fp", journal_offset=epoch)


def test_promotion_state_machine(tmp_path):
    sdir = str(tmp_path / "serving")
    # first candidate always promotes (nothing to regress against)
    action, serving = decide_promotion(sdir, _candidate(sdir, 1, 0.50))
    assert action == "promote" and serving["epoch"] == 1
    # improvement promotes
    action, serving = decide_promotion(sdir, _candidate(sdir, 2, 0.60))
    assert action == "promote" and serving["epoch"] == 2
    # regression rolls back: the pointer keeps the previous manifest, the
    # candidate stays on disk for forensics
    action, serving = decide_promotion(sdir, _candidate(sdir, 3, 0.10))
    assert action == "rollback" and serving["epoch"] == 2
    assert current_manifest(sdir)["epoch"] == 2
    assert os.path.exists(os.path.join(sdir, "promoted-e00003.npz"))
    # a drop within margin is not a regression
    action, serving = decide_promotion(sdir, _candidate(sdir, 4, 0.55),
                                       margin=0.1)
    assert action == "promote" and serving["epoch"] == 4
    assert verify_promoted(sdir)["epoch"] == 4
    # retention: keep=1 prunes everything but the newest — and never the
    # pointer's target even when it is not the newest
    decide_promotion(sdir, _candidate(sdir, 5, 0.0))  # rollback: pin e4
    removed = prune_serving(sdir, keep=1)
    left = sorted(f for f in os.listdir(sdir) if f.endswith(".npz"))
    assert "promoted-e00004.npz" in left  # the pinned serving target
    assert "promoted-e00005.npz" in left  # the newest
    assert all(f.startswith("promoted-e0000") for f in removed)
    assert verify_promoted(sdir)["epoch"] == 4


def test_verify_promoted_tamper_refuses(tmp_path):
    sdir = str(tmp_path / "serving")
    with pytest.raises(PromotionTampered, match="nothing promoted"):
        verify_promoted(sdir or str(tmp_path))
    decide_promotion(sdir, _candidate(sdir, 1, 0.5))
    assert serve_tpu.main(["verify", sdir]) == 0
    # flip one artifact byte: content hash mismatch, CLI exits non-zero
    npz = os.path.join(sdir, "promoted-e00001.npz")
    blob = bytearray(open(npz, "rb").read())
    blob[len(blob) // 2] ^= 0xFF
    open(npz, "wb").write(bytes(blob))
    with pytest.raises(PromotionTampered, match="hash mismatch"):
        verify_promoted(sdir)
    assert serve_tpu.main(["verify", sdir]) == 1
    # an edited manifest (metric inflation) breaks its own signature
    decide_promotion(sdir, _candidate(sdir, 1, 0.5))  # restore artifact
    pointer = os.path.join(sdir, "MANIFEST.json")
    manifest = json.load(open(pointer))
    manifest["metrics"]["test_acc"] = 0.99
    json.dump(manifest, open(pointer, "w"))
    with pytest.raises(PromotionTampered, match="signature"):
        verify_promoted(sdir)
    # a manifest naming a missing artifact refuses too (acc 1.0 beats the
    # inflated pointer, so this promotes cleanly over the tampered one)
    decide_promotion(sdir, _candidate(sdir, 2, 1.0))
    os.unlink(os.path.join(sdir, "promoted-e00002.npz"))
    with pytest.raises(PromotionTampered, match="missing"):
        verify_promoted(sdir)


def test_config_fingerprint_dataclass_dict_parity():
    assert config_fingerprint(BASE) == config_fingerprint(
        dataclasses.asdict(BASE))
    assert config_fingerprint(BASE) != config_fingerprint(
        dataclasses.replace(BASE, budget=0.9))


# ------------------------------------------- fleet verdict parity + HTTP

def _beat(health_dir, host, workers, dead=()):
    event = {
        "v": 3, "kind": "heartbeat", "t": time.time(), "host": host,
        "epoch": 0, "step": 4, "step_time": 0.1, "step_time_ewma": 0.1,
        "comp_time": 0.3, "comm_time": 0.1, "peak_bytes": None,
        "workers": {w: {"slot": i,
                        "participation": 0.0 if w in dead else 1.0,
                        "disagreement": 0.0}
                    for i, w in enumerate(workers)},
    }
    assert validate_event(event) == []
    os.makedirs(health_dir, exist_ok=True)
    with open(heartbeat_path(health_dir, host), "a") as f:
        f.write(json.dumps(event) + "\n")


class _StubRun:
    """The endpoint's duck-typed controller: file facts, no subprocess."""

    def __init__(self, run_dir, serving_dir):
        self.run_dir = run_dir
        self.serving_dir = serving_dir

    def status(self):
        return {"name": os.path.basename(self.run_dir), "lifetimes": 1}


def _get(port, path):
    try:
        with urllib.request.urlopen(
                f"http://127.0.0.1:{port}{path}", timeout=10) as r:
            return r.status, json.loads(r.read())
    except urllib.error.HTTPError as e:
        return e.code, json.loads(e.read())


def test_fleet_verdict_three_way_parity(tmp_path, capsys):
    """The acceptance pin: the library verdict, ``watch --once``'s exit
    code, and ``/healthz`` can never disagree — all three read
    ``obs.health.fleet_verdict``."""
    healthy = str(tmp_path / "healthy")
    flagged = str(tmp_path / "flagged")
    void = str(tmp_path / "void")
    _beat(healthy, "host0", ["w0", "w1", "w2", "w3"])
    _beat(flagged, "host0", ["w0", "w1", "w2", "w3"], dead=("w1",))
    os.makedirs(void)

    runs = {name: _StubRun(d, d) for name, d in
            [("healthy", healthy), ("flagged", flagged), ("void", void)]}
    endpoint = ServeEndpoint(runs).start()
    try:
        for name, want in (("healthy", 0), ("flagged", 1), ("void", 2)):
            rc, status = fleet_verdict(runs[name].run_dir)
            assert rc == want
            assert (status is None) == (want == 2)
            assert obs_tpu.main(["watch", runs[name].run_dir,
                                 "--once"]) == want
            code, body = _get(endpoint.port, f"/healthz?run={name}")
            assert code == (200 if want == 0 else 503)
            assert body["verdict"] == want and body["ok"] == (want == 0)
            if want == 2:
                assert "no heartbeat evidence" in body["reason"]
            else:
                assert body["flagged"] == (want == 1)
        capsys.readouterr()
    finally:
        endpoint.stop()


def test_endpoint_routing_multi_tenant(tmp_path):
    a_dir, b_dir = str(tmp_path / "a"), str(tmp_path / "b")
    a_serving, b_serving = str(tmp_path / "a_s"), str(tmp_path / "b_s")
    _beat(a_dir, "host0", ["w0", "w1", "w2", "w3"])
    decide_promotion(a_serving, _candidate(a_serving, 1, 0.5))
    decide_promotion(b_serving, _candidate(b_serving, 1, 0.5))
    manifest = json.load(open(os.path.join(b_serving, "MANIFEST.json")))
    manifest["metrics"]["test_acc"] = 1.0  # tamper b's serving truth
    json.dump(manifest, open(os.path.join(b_serving, "MANIFEST.json"), "w"))

    endpoint = ServeEndpoint({
        "a": _StubRun(a_dir, a_serving),
        "b": _StubRun(b_dir, b_serving)}).start()
    try:
        port = endpoint.port
        code, body = _get(port, "/status?run=a")
        assert code == 200 and body["name"] == "a"
        assert body["fleet_verdict"] == 0 and not body["fleet"]["flagged"]
        # multi-tenant without ?run= is ambiguous, not a guess
        code, body = _get(port, "/status")
        assert code == 404 and body["runs"] == ["a", "b"]
        code, body = _get(port, "/status?run=zzz")
        assert code == 404
        code, body = _get(port, "/promoted?run=a")
        assert code == 200 and body["verified"]
        assert body["manifest"]["epoch"] == 1
        # b's tampered manifest: 503, never the manifest
        code, body = _get(port, "/promoted?run=b")
        assert code == 503 and not body["verified"]
        assert "manifest" not in body and "signature" in body["error"]
        code, body = _get(port, "/nope?run=a")
        assert code == 404 and "/healthz" in body["routes"]
    finally:
        endpoint.stop()
    with pytest.raises(ValueError, match="at least one run"):
        ServeEndpoint({})


# ---------------------------------------------------- in-process e2e set

@pytest.mark.slow
def test_identity_knobs_match_unsupervised_run(tmp_path):
    """A supervised run that never receives a control document is
    numerically identical to a plain ``train()`` — the knobs multiply by
    exactly 1.0, so every recorded metric matches to the last bit."""
    plain = dataclasses.replace(BASE, name="plain", epochs=2,
                                savePath=str(tmp_path))
    train(plain)
    supervised = dataclasses.replace(BASE, name="sup", epochs=2,
                                     savePath=str(tmp_path))
    harness = TrainerHarness(_spec(tmp_path))
    train(supervised, boundary_hook=harness.on_boundary)

    def metric_rows(run_dir):
        return [(e["epoch"], e["train_loss"], e["train_acc"],
                 e["test_acc_mean"], e["disagreement"])
                for e in _journal(run_dir) if e["kind"] == "epoch"]

    plain_rows = metric_rows(str(tmp_path / "plain_mlp"))
    assert len(plain_rows) == 2
    assert plain_rows == metric_rows(str(tmp_path / "sup_mlp"))
    assert not harness.restart_requested


def test_hot_swap_budget_mid_run_zero_retrace(tmp_path):
    """The tentpole pin: a budget re-solve published mid-run applies at
    the next epoch boundary as pure value updates — the journal carries
    the decision, the retrace watch stays silent."""
    control = str(tmp_path / "control.json")
    harness = TrainerHarness(_spec(tmp_path, control_path=control))
    published = []

    def hook(seam):
        if seam.epoch == 2 and not published:
            write_control(control, {"version": 1, "budget": 0.2})
            published.append(True)
        harness.on_boundary(seam)

    cfg = dataclasses.replace(BASE, name="swap", epochs=4,
                              savePath=str(tmp_path))
    result = train(cfg, boundary_hook=hook)
    assert len(result.history) == 4  # the run completed under new knobs
    events = _journal(str(tmp_path / "swap_mlp"))
    controls = [e for e in events if e["kind"] == "control"]
    assert [(e["action"], e["applied"], e["epoch"], e["version"])
            for e in controls] == [("apply", True, 2, 1)]
    detail = controls[0]["fields"]["budget"]
    assert detail["budget"] == 0.2 and 0 < detail["rho"] < 1
    assert controls[0]["v"] == SCHEMA_VERSION
    assert [e for e in events if e["kind"] == "retrace"] == []


def test_hot_swap_local_every_single_epoch_program(tmp_path, monkeypatch):
    """ISSUE 19 pin: a ``local_steps`` hot-swap through control.json rides
    the traced ``local_every`` knob of the universally-elided epoch —
    ``check_single_trace`` proves exactly ONE epoch program was ever
    compiled across the swap (the elision cond's predicate is a value,
    not a shape), on top of the journal's own silent retrace watch."""
    import matcha_tpu.train.loop as loop_mod
    from matcha_tpu.analysis import check_single_trace, retrace_guard

    real = loop_mod._make_epoch_scan
    counters = []

    def spy(step_fn):
        wrapped, counter = retrace_guard(real(step_fn))
        counters.append(counter)
        return wrapped

    monkeypatch.setattr(loop_mod, "_make_epoch_scan", spy)
    control = str(tmp_path / "control.json")
    harness = TrainerHarness(_spec(tmp_path, control_path=control))
    published = []

    def hook(seam):
        if seam.epoch == 1 and not published:
            write_control(control, {"version": 1, "local_steps": 2})
            published.append(True)
        harness.on_boundary(seam)

    cfg = dataclasses.replace(BASE, name="lswap", epochs=4,
                              savePath=str(tmp_path))
    result = train(cfg, boundary_hook=hook)
    assert len(result.history) == 4
    events = _journal(str(tmp_path / "lswap_mlp"))
    controls = [e for e in events if e["kind"] == "control"]
    assert [(e["action"], e["applied"], e["epoch"]) for e in controls] == \
        [("apply", True, 1)]
    assert [e for e in events if e["kind"] == "retrace"] == []
    assert len(counters) == 1  # one epoch program built, period
    check_single_trace(counters[0], label="epoch_scan(local_every swap)")


def test_invalid_document_rejected_whole(tmp_path):
    """One bad field rejects everything: the valid budget half must NOT
    apply when the restart half cannot construct a config."""
    control = str(tmp_path / "control.json")
    # staleness=2 needs overlap='1step'; BASE is eager — cross-field bad
    with open(control, "w") as f:
        json.dump({"version": 1, "budget": 0.25, "staleness": 2}, f)
    harness = TrainerHarness(_spec(tmp_path, control_path=control))
    cfg = dataclasses.replace(BASE, name="rej", epochs=2,
                              savePath=str(tmp_path))
    result = train(cfg, boundary_hook=harness.on_boundary)
    assert len(result.history) == 2 and not harness.restart_requested
    controls = [e for e in _journal(str(tmp_path / "rej_mlp"))
                if e["kind"] == "control"]
    # rejected once (stat-signature memoized), never applied
    assert [(e["action"], e["applied"]) for e in controls] == \
        [("reject", False)]
    assert "running config" in controls[0]["reason"]


def test_forced_regression_rolls_back_serving_pointer(tmp_path,
                                                      monkeypatch):
    """The acceptance scenario: promotion eval regresses → the serving
    pointer re-points to the previous manifest, journaled as a
    ``promotion`` event with ``action='rollback'``."""
    import matcha_tpu.serve.trainer as trainer_mod

    accs = iter([0.75, 0.10])  # second eval regresses hard

    def fake_metrics(evaluate, state, x_test, y_test, batch=256):
        acc = next(accs)
        return {"test_acc": acc, "test_loss": 1.0 - acc}

    monkeypatch.setattr(trainer_mod, "consensus_metrics", fake_metrics)
    serving = str(tmp_path / "serving")
    harness = TrainerHarness(_spec(tmp_path, serving_dir=serving,
                                   promote_every=1))
    cfg = dataclasses.replace(BASE, name="roll", epochs=3,
                              savePath=str(tmp_path))
    train(cfg, boundary_hook=harness.on_boundary)

    promos = [e for e in _journal(str(tmp_path / "roll_mlp"))
              if e["kind"] == "promotion"]
    assert [(e["action"], e["epoch"], e["serving_epoch"])
            for e in promos] == [("promote", 1, 1), ("rollback", 2, 1)]
    assert promos[0]["metric"] == pytest.approx(0.75)
    # the pointer survived the regression — and still verifies end-to-end
    manifest = verify_promoted(serving)
    assert manifest["epoch"] == 1
    assert manifest["metrics"]["test_acc"] == pytest.approx(0.75)
    # the regressed candidate stayed on disk for forensics
    assert os.path.exists(os.path.join(serving, "promoted-e00002.npz"))


def test_stop_document_checkpoints_and_drains(tmp_path):
    control = str(tmp_path / "control.json")
    harness = TrainerHarness(_spec(tmp_path, control_path=control))

    def hook(seam):
        if seam.epoch == 1:
            write_control(control, {"version": 1, "stop": True})
        harness.on_boundary(seam)

    cfg = dataclasses.replace(BASE, name="halt", epochs=5,
                              savePath=str(tmp_path))
    result = train(cfg, boundary_hook=hook)
    assert len(result.history) == 1  # stopped at the epoch-1 boundary
    events = _journal(str(tmp_path / "halt_mlp"))
    stops = [e for e in events if e["kind"] == "control"]
    assert [(e["action"], e["applied"]) for e in stops] == [("stop", True)]
    # the stop checkpointed the completed epoch before draining
    ckpts = [e for e in events if e["kind"] == "checkpoint"]
    assert any(e["epoch"] == 0 for e in ckpts)
    assert latest_step(str(tmp_path / "halt_ckpt")) is not None


# -------------------------------------------------- supervisor (no jax)

class _FakeProc:
    def __init__(self, rc):
        self._rc = rc

    def wait(self):
        return self._rc

    def poll(self):
        return self._rc


def test_supervisor_initializes_no_jax_backend(tmp_path):
    """One process per chip: the supervisor imports the train package
    (config validation, checkpoint progress) but must never touch a JAX
    backend, or it would hold the chip its trainer child needs.  A fresh
    interpreter walks the whole supervisor surface around a lifetime —
    the daemon CLI module, spec publication, restart-field merge, progress
    read, status, the endpoint — with a child that exits at once."""
    import subprocess
    import sys

    script = f"""
import sys
import serve_tpu
from matcha_tpu.serve import (Controller, ServeConfig, ServeEndpoint,
                              write_control)
cfg = dict(name="nochip", model="mlp", dataset="synthetic", num_workers=4,
           graphid=None, topology="ring", savePath={str(tmp_path)!r})
ctl = Controller(ServeConfig(config=cfg, restart_budget=0))
write_control(ctl.control_path, {{"version": 1, "budget": 0.25}})
ctl._launch = lambda: __import__("subprocess").Popen(
    [sys.executable, "-c", "pass"])
endpoint = ServeEndpoint({{"nochip": ctl}}, port=0).start()
ctl._write_spec()
assert ctl.run() == 0
ctl._merge_restart_fields(); ctl._progress(); ctl.status()
endpoint.stop()
from jax._src import xla_bridge
assert not xla_bridge.backends_are_initialized(), "supervisor holds a backend"
print("supervisor-clean")
"""
    repo = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
    proc = subprocess.run([sys.executable, "-c", script], cwd=repo,
                          capture_output=True, text=True, timeout=120)
    assert proc.returncode == 0, proc.stderr[-2000:]
    assert "supervisor-clean" in proc.stdout


def test_controller_budget_charges_and_aborts(tmp_path, monkeypatch):
    """Crash-loop policy without spawning a trainer: every crash charges
    the budget and journals; exhaustion aborts with the crash's code."""
    cfg = dict(name="crashy", model="mlp", savePath=str(tmp_path))
    ctl = Controller(ServeConfig(config=cfg, restart_budget=2,
                                 backoff=0.01, backoff_max=0.02))
    monkeypatch.setattr(ctl, "_launch", lambda: _FakeProc(7))
    assert ctl.run() == 7
    assert ctl.restarts_used == 3 and ctl.lifetimes == 0  # _launch faked
    events = read_journal(ctl.journal_path)
    assert [(e["action"], e["applied"], e["epoch"]) for e in events] == \
        [("restart", True, -1), ("restart", True, -1),
         ("abort", False, -1)]
    assert all(e["v"] == SCHEMA_VERSION and validate_event(e) == []
               for e in events)
    status = ctl.status()
    assert status["last_exit"] == 7 and not status["trainer_alive"]


def test_controller_restart_exit_merges_without_charging(tmp_path,
                                                         monkeypatch):
    cfg = dict(name="merge", model="mlp", savePath=str(tmp_path),
               overlap="1step")
    ctl = Controller(ServeConfig(config=cfg, restart_budget=0))
    write_control(ctl.control_path, {"version": 1, "staleness": 2})
    codes = iter([RESTART_EXIT, 0])
    monkeypatch.setattr(ctl, "_launch", lambda: _FakeProc(next(codes)))
    assert ctl.run() == 0
    assert ctl.restarts_used == 0  # deliberate restarts are free
    assert ctl.config["staleness"] == 2
    relaunches = [e for e in read_journal(ctl.journal_path)
                  if e["action"] == "relaunch"]
    assert len(relaunches) == 1 and relaunches[0]["fields"] == \
        {"staleness": 2}
    # an invalid merge (staleness without overlap) journals a reject and
    # leaves the config alone instead of crash-looping the next lifetime
    ctl2 = Controller(ServeConfig(config=dict(name="bad", model="mlp",
                                              savePath=str(tmp_path)),
                                  restart_budget=0))
    write_control(ctl2.control_path, {"version": 1, "staleness": 2})
    codes2 = iter([RESTART_EXIT, 0])
    monkeypatch.setattr(ctl2, "_launch", lambda: _FakeProc(next(codes2)))
    assert ctl2.run() == 0
    assert "staleness" not in ctl2.config
    rejects = [e for e in read_journal(ctl2.journal_path)
               if e["action"] == "reject"]
    assert rejects and "merge invalid" in rejects[0]["reason"]


# --------------------------------------------------- subprocess e2e (slow)

@pytest.mark.slow
def test_daemon_kill9_supervised_resume_matches_uninterrupted(tmp_path):
    """The crash-survival pin: kill -9 the trainer mid-run; the
    supervisor charges one restart, relaunches from the checkpoint, and
    the finished run's recorder metrics and promoted consensus artifact
    are identical to an uninterrupted supervised run's."""
    def controller(name, root):
        cfg = dataclasses.replace(BASE, name=name, epochs=6,
                                  savePath=str(root))
        return Controller(ServeConfig(
            config=dataclasses.asdict(cfg), promote_every=5,
            restart_budget=2, backoff=0.1))

    # run A: uninterrupted reference
    ref = controller("ref", tmp_path / "ref")
    assert ref.run() == 0 and ref.restarts_used == 0

    # run B: killed with SIGKILL right after the first checkpoint lands
    victim = controller("vic", tmp_path / "vic")
    rc_box = {}
    thread = threading.Thread(target=lambda: rc_box.update(
        rc=victim.run()), daemon=True)
    thread.start()
    deadline = time.time() + 300
    while time.time() < deadline:
        proc = victim._proc
        if proc is not None and latest_step(victim.ckpt_dir) is not None:
            proc.kill()  # SIGKILL: no atexit, no flush, no mercy
            break
        time.sleep(0.02)
    else:
        pytest.fail("first checkpoint never appeared")
    thread.join(timeout=300)
    assert not thread.is_alive() and rc_box["rc"] == 0
    assert victim.restarts_used == 1 and victim.lifetimes == 2

    # the supervisor's decision is on the record, at supervisor scope
    restarts = [e for e in read_journal(victim.journal_path)
                if e["kind"] == "control" and e["action"] == "restart"]
    assert len(restarts) == 1 and restarts[0]["epoch"] == -1
    assert "crashed" in restarts[0]["reason"]

    def final_epoch_row(ctl):
        epochs = [e for e in read_journal(ctl.journal_path)
                  if e["kind"] == "epoch"]
        last = max(epochs, key=lambda e: e["epoch"])
        return (last["epoch"], last["train_loss"], last["train_acc"],
                last["test_acc_mean"], last["disagreement"])

    # identical final recorder row — exact float equality, not approx
    assert final_epoch_row(victim) == final_epoch_row(ref)
    # identical promoted consensus artifact, array for array
    for ctl in (ref, victim):
        assert verify_promoted(ctl.serving_dir)["epoch"] == 5
    with np.load(os.path.join(ref.serving_dir,
                              "promoted-e00005.npz")) as a, \
            np.load(os.path.join(victim.serving_dir,
                                 "promoted-e00005.npz")) as b:
        assert sorted(a.files) == sorted(b.files)
        for key in a.files:
            np.testing.assert_array_equal(a[key], b[key])


@pytest.mark.slow
def test_serve_cli_daemon_with_endpoint_and_stop(tmp_path):
    """Daemon start through the real CLI path: Controller + endpoint up,
    ``/status`` answers while training, a ``stop`` document drains the
    run to exit 0."""
    cfg = dataclasses.replace(BASE, name="cli", epochs=50,
                              savePath=str(tmp_path))
    ctl = Controller(ServeConfig(config=dataclasses.asdict(cfg),
                                 restart_budget=0))
    endpoint = ServeEndpoint({"cli": ctl}).start()
    rc_box = {}
    thread = threading.Thread(target=lambda: rc_box.update(rc=ctl.run()),
                              daemon=True)
    thread.start()
    try:
        deadline = time.time() + 300
        while time.time() < deadline:
            code, body = _get(endpoint.port, "/status")
            assert code == 200
            if body["trainer_alive"] and \
                    latest_step(ctl.ckpt_dir) is not None:
                break
            time.sleep(0.1)
        else:
            pytest.fail("trainer never reported alive with a checkpoint")
        assert body["lifetimes"] == 1 and body["restart_budget"] == 0
        # stop it through the operator path: the control CLI
        assert serve_tpu.main(["control", "--out", ctl.control_path,
                               "--version", "1", "--stop"]) == 0
        thread.join(timeout=300)
        assert not thread.is_alive() and rc_box["rc"] == 0
    finally:
        endpoint.stop()
        ctl.shutdown()
    stops = [e for e in read_journal(ctl.journal_path)
             if e["kind"] == "control" and e["action"] == "stop"]
    assert len(stops) == 1 and stops[0]["applied"]
