"""Bounded-staleness gossip (ISSUE 14): the consume-at-≤t+k contract.

Property families, all CPU-cheap, all under the ``async`` marker (tier-1
and the ci/lint.sh async lane):

* **k=1 bitwise** — ``run_pipelined(staleness=1)`` IS the committed
  one-step pipeline, bit-for-bit, on every backend × alive mask × wire
  dtype.  The ring is the one-slot buffer when K=1; any arithmetic drift
  here would silently fork the committed overlap semantics.
* **Telescoping drain** — when the flag stream fires at most once every K
  steps (local_steps ≥ K thinning), each delta is consumed before the
  next is issued, so the drained K-deep chain reproduces the eager chain
  exactly (the k=1 argument, event by event).  Centralized is excluded on
  purpose: it AllReduces every step regardless of flags, so thinning
  does not thin it.
* **Mean preservation** — however deep the ring, every in-flight delta
  has zero column-mean: the visible state keeps the exact worker mean and
  the ring is about to move it by zero.
* **Predictor ≥ MC** — the staleness-extended ``stale_contraction_rho``
  bounds the ring-recurrence MC simulator across the zoo, k ∈ {2, 4},
  ± bf16, ± local steps — the same invariant as the eager and one-step
  bounds; and the delayed-overcompensation divergence at the eagerly
  solved α is real (MC confirms ρ > 1), which is what
  ``stale_alpha_rescale``'s damping exists to fix.
* **Executor contracts** — staleness=1 training is bitwise the committed
  overlap="1step" run; the k-deep run trains, drains, journals the
  contract, and the drift monitor stays quiet at k=2 on ring-8 (the
  acceptance gate); resume reconciles the pending ring across a
  ``--staleness`` change in both directions; churn under a staleness
  ring stays zero-retrace.
"""

import dataclasses
import json
import os

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from matcha_tpu import topology as tp
from gossip_cases import alive_of, make_comm, sched_of  # beside this file
from matcha_tpu.schedule import matcha_schedule
from matcha_tpu.schedule.solvers import (
    solve_activation_probabilities,
    solve_mixing_weight,
)

# the `async` lane marker (ci/lint.sh runs it standalone); getattr spelling
# because `async` is a Python keyword
pytestmark = getattr(pytest.mark, "async")

SIZE = tp.graph_size(0)
SCHED = matcha_schedule(tp.select_graph(0), SIZE, iterations=12, budget=0.5,
                        seed=3)

BACKENDS = ["gather", "dense", "skip", "dense-mxu", "choco", "centralized"]


def _sched(backend):
    return sched_of(backend, SCHED)


def _make(backend, wire=None):
    return make_comm(backend, SCHED, wire)


def _alive(backend):
    return alive_of(_sched(backend))


def _x0(d=21, seed=0, n=SIZE):
    return jnp.asarray(
        np.random.default_rng(seed).normal(size=(n, d)).astype(np.float32))


def _thinned_flags(local_steps: int, reps: int = 1, sched=SCHED):
    flags = np.tile(np.asarray(sched.flags, np.float32), (reps, 1))
    flags[np.arange(len(flags)) % local_steps != 0] = 0.0
    return flags


# ---------------------------------------------------------------- ring chain

@pytest.mark.parametrize("masked", [False, True], ids=["full", "alive-mask"])
@pytest.mark.parametrize("wire", [None, "bf16"], ids=["f32", "bf16"])
@pytest.mark.parametrize("backend", BACKENDS)
def test_ring_k1_bitwise_matches_overlapped(backend, wire, masked):
    """staleness=1 IS the committed one-step pipeline, bit-for-bit, on
    every backend × alive mask × wire dtype — state AND carry."""
    comm = _make(backend, wire)
    sched = _sched(backend)
    alive = _alive(backend) if masked else None
    x0 = _x0(n=sched.num_workers)
    ov, co = jax.jit(
        lambda x: comm.run_overlapped(x, sched.flags, alive=alive))(x0)
    pp, cp = jax.jit(
        lambda x: comm.run_pipelined(x, sched.flags, alive=alive,
                                     staleness=1))(x0)
    np.testing.assert_array_equal(np.asarray(ov), np.asarray(pp))
    for a, b in zip(jax.tree_util.tree_leaves(co),
                    jax.tree_util.tree_leaves(cp)):
        np.testing.assert_array_equal(np.asarray(a), np.asarray(b))


@pytest.mark.parametrize("masked", [False, True], ids=["full", "alive-mask"])
@pytest.mark.parametrize("k", [2, 3])
@pytest.mark.parametrize("backend",
                         ["gather", "dense", "skip", "dense-mxu", "choco"])
def test_kdeep_drain_telescopes_when_thinned(backend, k, masked):
    """local_steps ≥ K: every delta is consumed before the next is issued,
    so the drained K-deep pipeline == the eager chain on the thinned
    stream (the constructive consume-before-reissue argument).  All
    flag-driven backends; centralized ignores flags by design."""
    comm = _make(backend)
    sched = _sched(backend)
    alive = _alive(backend) if masked else None
    flags = _thinned_flags(local_steps=k, reps=2, sched=sched)
    x0 = _x0(d=13, seed=5, n=sched.num_workers)
    eager, _ = jax.jit(lambda x: comm.run(x, flags, alive=alive))(x0)
    piped, _ = jax.jit(
        lambda x: comm.run_pipelined(x, flags, alive=alive, staleness=k))(x0)
    np.testing.assert_allclose(np.asarray(eager), np.asarray(piped),
                               rtol=1e-5, atol=1e-6)


@pytest.mark.parametrize("wire", [None, "bf16"], ids=["f32", "bf16"])
@pytest.mark.parametrize("backend", ["gather", "dense", "skip", "choco"])
def test_kdeep_ring_preserves_worker_mean(backend, wire):
    """The visible (undrained) k=2 state keeps the exact worker mean, and
    every in-flight ring slot is a zero-column-mean delta — delayed
    consumption can reorder the mixing, never move the average."""
    comm = _make(backend, wire)
    x0 = _x0(d=17, seed=1)
    x, _, ring = jax.jit(
        lambda x: comm.run_pipelined(x, SCHED.flags, staleness=2,
                                     drain=False))(x0)
    exact = wire is None or backend in ("gather", "skip", "choco")
    # the dense bf16 reduction rounds through bf16 arithmetic once per
    # applied delta; two deltas in flight double the k=1 budget
    atol = 2e-5 if exact else 1e-2
    np.testing.assert_allclose(np.asarray(x).mean(axis=0),
                               np.asarray(x0).mean(axis=0), atol=atol)
    np.testing.assert_allclose(np.asarray(ring).mean(axis=1), 0.0, atol=atol)


# ------------------------------------------------------------- the predictor

def test_staleness_spec_validation():
    from matcha_tpu.plan import (
        normalize_staleness,
        parse_staleness_spec,
        stale_contraction_rho,
    )

    assert normalize_staleness(3) == {3: 1.0}
    assert normalize_staleness({1: 1.0, 4: 3.0}) == {1: 0.25, 4: 0.75}
    assert parse_staleness_spec("2") == {2: 1.0}
    assert parse_staleness_spec("1:0.75,4:0.25") == {1: 0.75, 4: 0.25}
    for bad in (0, -1, {0: 1.0}, {2: -1.0}, {}, "x:y"):
        with pytest.raises(ValueError):
            (parse_staleness_spec(bad) if isinstance(bad, str)
             else normalize_staleness(bad))
    Ls = tp.matching_laplacians(tp.select_graph(0), SIZE)
    p = solve_activation_probabilities(Ls, 0.5, iters=300)
    alpha, _ = solve_mixing_weight(Ls, p)
    with pytest.raises(ValueError, match="overlap"):
        stale_contraction_rho(Ls, p, alpha, overlap="off", staleness=2)
    with pytest.raises(ValueError, match="local_steps"):
        stale_contraction_rho(Ls, p, alpha, local_steps=0)


@pytest.mark.parametrize("gid", [0, 5])
def test_stale_rho_staleness_bounds_ring_mc(gid):
    """Predictor ≥ measured, k-deep edition: the staleness-extended ρ
    bounds the ring-recurrence MC across the zoo at k ∈ {2, 4}, with and
    without the bf16 wire and local steps — the same MC ≤ ρ invariant as
    the eager and one-step tests, same finite-sample headroom."""
    from matcha_tpu.plan import simulate_consensus, stale_contraction_rho

    size = tp.graph_size(gid)
    dec = tp.select_graph(gid)
    Ls = tp.matching_laplacians(dec, size)
    p = solve_activation_probabilities(Ls, 0.5, iters=600)
    alpha, rho = solve_mixing_weight(Ls, p)
    for k, L, wire in ((2, 1, None), (4, 1, None), (2, 2, None),
                       (2, 1, "bf16")):
        pred = stale_contraction_rho(Ls, p, alpha, overlap="1step",
                                     staleness=k, local_steps=L,
                                     wire_dtype=wire)
        assert np.isfinite(pred)
        sim = simulate_consensus(dec, size, p, alpha, steps=120, trials=4,
                                 seed=3, laplacians=Ls, overlap="1step",
                                 staleness=k, local_steps=L, wire_dtype=wire)
        emp = sim.empirical_rate()
        assert emp <= pred * 1.02, (gid, k, L, wire, emp, pred)
        assert sim.rho_bound == pytest.approx(pred)
    # consistency: k=1 keeps the eager bound exactly; deeper delay only
    # inflates; local_steps ≥ k telescopes back to the thinned eager rate
    assert stale_contraction_rho(Ls, p, alpha, staleness=1) \
        == pytest.approx(rho)
    k2 = stale_contraction_rho(Ls, p, alpha, staleness=2)
    k4 = stale_contraction_rho(Ls, p, alpha, staleness=4)
    assert rho <= k2 <= k4
    assert stale_contraction_rho(Ls, p, alpha, staleness=2, local_steps=2) \
        == pytest.approx(rho ** 0.5)
    # a distribution sits between its point-mass extremes
    mixed = stale_contraction_rho(Ls, p, alpha, staleness={1: 0.5, 2: 0.5})
    assert rho <= mixed <= k2


def test_stale_alpha_rescale_stabilizes():
    """At the eagerly-solved α a k=2 pipeline genuinely diverges (delayed
    overcompensation: ρ > 1, and the MC ring recurrence confirms it) —
    and the damped α the executor actually runs restores ρ < 1 with the
    bound still ≥ MC.  This is the physics the --staleness path's
    automatic damping exists for."""
    from matcha_tpu.plan import simulate_consensus, stale_alpha_rescale, \
        stale_contraction_rho

    gid = 5
    size = tp.graph_size(gid)
    dec = tp.select_graph(gid)
    Ls = tp.matching_laplacians(dec, size)
    p = solve_activation_probabilities(Ls, 0.5, iters=600)
    alpha, _ = solve_mixing_weight(Ls, p)
    raw = stale_contraction_rho(Ls, p, alpha, staleness=2)
    assert raw > 1.0  # the instability is real, not a bound artifact
    sim_raw = simulate_consensus(dec, size, p, alpha, steps=120, trials=4,
                                 seed=3, laplacians=Ls, overlap="1step",
                                 staleness=2)
    assert sim_raw.empirical_rate() > 1.0
    scale, damped = stale_alpha_rescale(Ls, p, alpha, staleness=2)
    assert 0 < scale < 1 and damped < 1.0
    sim = simulate_consensus(dec, size, p, alpha * scale, steps=120,
                             trials=4, seed=3, laplacians=Ls,
                             overlap="1step", staleness=2)
    assert sim.empirical_rate() <= damped * 1.02
    # no re-damping where the telescoping argument applies (k_ev = 1)
    assert stale_alpha_rescale(Ls, p, alpha, staleness=2, local_steps=2) \
        == (1.0, pytest.approx(stale_contraction_rho(
            Ls, p, alpha, staleness=2, local_steps=2)))


# ------------------------------------------------------- fleet wall-clock

def test_fleet_wallclock_model_recovers_straggler_tax():
    """The bench grid's modeled claim, pinned: under a planted period-4
    straggler, the k=1 bounded model IS the barrier model (one
    outstanding exchange = wait on every peer's previous round), k ≥ 2
    strictly reduces modeled fleet wall-clock, and the recovery never
    exceeds the barrier-vs-ideal tax."""
    from matcha_tpu.plan import simulate_fleet_wallclock, \
        straggler_step_times

    t = straggler_step_times(8, 64, straggler=0, period=4, slowdown=4.0,
                             seed=1)
    base = simulate_fleet_wallclock(t, staleness=1)
    assert base["bounded_seconds"] == pytest.approx(base["barrier_seconds"])
    k2 = simulate_fleet_wallclock(t, staleness=2)
    assert k2["bounded_seconds"] < base["barrier_seconds"]
    assert 0 < k2["recovered_seconds"] <= k2["tax_seconds"] + 1e-9
    assert 0 < k2["recovered_fraction"] <= 1.0
    # local_steps fold into event depth: ceil(2/2) = 1 -> barrier again
    l2 = simulate_fleet_wallclock(t, staleness=2, local_steps=2)
    assert l2["bounded_seconds"] == pytest.approx(base["barrier_seconds"])
    with pytest.raises(ValueError, match="rounds"):
        simulate_fleet_wallclock(np.ones(5), staleness=2)


# ------------------------------------------------------------- the executor

def _cfg(tmp_path, **kw):
    from matcha_tpu.train import TrainConfig

    base = dict(
        name="stale", model="mlp", dataset="synthetic",
        dataset_kwargs={"num_train": 512, "num_test": 128},
        num_workers=8, graphid=5, matcha=False, epochs=2, lr=0.05,
        batch_size=16, eval_every=0, save=False, savePath=str(tmp_path),
        measure_comm_split=False, overlap="1step")
    base.update(kw)
    return TrainConfig(**base)


def test_config_validation():
    from matcha_tpu.train import TrainConfig

    with pytest.raises(ValueError, match="staleness"):
        TrainConfig(staleness=0)
    with pytest.raises(ValueError, match="overlap"):
        TrainConfig(staleness=2, overlap="off")
    with pytest.raises(ValueError, match="local_steps"):
        TrainConfig(local_steps=0)
    assert TrainConfig(staleness=2, overlap="1step").staleness == 2


def test_staleness1_training_bitwise_matches_overlap(tmp_path):
    """--staleness 1 reproduces the committed --overlap 1step run bitwise:
    identical final parameters on the same data/schedule (the acceptance
    bar — the new contract at depth 1 IS the old contract)."""
    from matcha_tpu.train import train

    a = train(_cfg(tmp_path, name="ov"))
    b = train(_cfg(tmp_path, name="k1", staleness=1))
    fa = jax.tree_util.tree_leaves(a.state.params)
    fb = jax.tree_util.tree_leaves(b.state.params)
    for x, y in zip(fa, fb):
        np.testing.assert_array_equal(np.asarray(x), np.asarray(y))
    np.testing.assert_array_equal(np.asarray(a.state.mix_pending),
                                  np.asarray(b.state.mix_pending))


def test_kdeep_training_e2e_with_drift_monitor(tmp_path):
    """Ring-8 CPU run at k=2 (the acceptance gate): trains finite, the
    journal records the async contract additively (staleness, local
    steps, damping scale, composed ρ), telemetry's consumed-age histogram
    fills at age K, the returned state is drained, and the drift monitor
    stays quiet — replay exits consistent."""
    from matcha_tpu.obs.drift import drift_report
    from matcha_tpu.train import train

    cfg = _cfg(tmp_path, name="k2", staleness=2, epochs=3, save=True)
    r = train(cfg)
    assert np.isfinite(r.history[-1]["loss"])
    # drained: no un-applied exchange rides out; ages all empty
    np.testing.assert_array_equal(np.asarray(r.state.mix_pending), 0.0)
    assert r.state.mix_pending.shape[:2] == (8, 2)
    np.testing.assert_array_equal(np.asarray(r.state.mix_ages), -1)
    events = [json.loads(line) for line in
              open(os.path.join(tmp_path, "k2_mlp", "events.jsonl"))]
    start = next(e for e in events if e["kind"] == "run_start")
    pred = start["predicted"]
    assert pred["staleness"] == 2 and pred["local_steps"] == 1
    assert 0 < pred["stale_alpha_scale"] < 1  # k=2 at L=1 must damp
    assert pred["rho"] < 1.0
    assert start["config"]["staleness"] == 2
    tel = [e for e in events if e["kind"] == "telemetry"]
    hist = np.asarray(tel[-1]["stale_age_hist"])
    assert hist.shape == (3,)
    assert hist[2] > 0  # steady state consumes age-K deltas
    assert not any(e["kind"] == "drift" for e in events)
    rep = drift_report(events)
    assert rep["consistent"]


def test_kdeep_training_with_local_steps(tmp_path):
    """k=2 × local_steps=2: the telescoping regime — no damping needed
    (event depth 1), wire bytes drop with the thinned stream."""
    from matcha_tpu.train import train

    r = train(_cfg(tmp_path, name="k2l2", staleness=2, local_steps=2,
                   save=True))
    assert np.isfinite(r.history[-1]["loss"])
    events = [json.loads(line) for line in
              open(os.path.join(tmp_path, "k2l2_mlp", "events.jsonl"))]
    start = next(e for e in events if e["kind"] == "run_start")
    assert start["predicted"]["stale_alpha_scale"] == 1.0
    dense = train(_cfg(tmp_path, name="dense-ctrl", save=True))
    ev2 = [json.loads(line) for line in
           open(os.path.join(tmp_path, "dense-ctrl_mlp", "events.jsonl"))]
    tel_thin = next(e for e in events if e["kind"] == "telemetry")
    tel_full = next(e for e in ev2 if e["kind"] == "telemetry")
    assert tel_thin["wire_bytes"] < 0.75 * tel_full["wire_bytes"]
    assert np.isfinite(dense.history[-1]["loss"])


def test_resume_across_staleness_change(tmp_path):
    """A checkpoint written at one --staleness must resume at another, in
    both directions: same depth continues seamlessly (ages rebuilt from
    the cursor); a depth change (including →1 and →off) flushes the saved
    ring oldest-first instead of silently dropping issued exchanges."""
    from matcha_tpu.train import train
    from matcha_tpu.train.checkpoint import saved_mix_pending_shape

    base = _cfg(tmp_path, name="ck", staleness=2, save=True,
                checkpoint_every=1)
    train(base)
    ckpt = f"{base.savePath}/{base.name}_ckpt"
    assert saved_mix_pending_shape(ckpt) is not None
    assert saved_mix_pending_shape(ckpt)[1] == 2

    same = dataclasses.replace(base, name="ck-same", epochs=3,
                               checkpoint_every=0, save=False)
    r = train(same, resume_dir=ckpt)
    assert r.history[0]["epoch"] == 2
    assert np.asarray(r.state.mix_pending).shape[1] == 2
    assert np.isfinite(r.history[-1]["loss"])

    deeper = dataclasses.replace(base, name="ck-k4", epochs=3, staleness=4,
                                 checkpoint_every=0, save=False)
    r = train(deeper, resume_dir=ckpt)
    assert np.asarray(r.state.mix_pending).shape[1] == 4
    assert np.isfinite(r.history[-1]["loss"])

    down = dataclasses.replace(base, name="ck-k1", epochs=3, staleness=1,
                               checkpoint_every=0, save=False)
    r = train(down, resume_dir=ckpt)
    assert np.asarray(r.state.mix_pending).ndim == 2
    assert np.isfinite(r.history[-1]["loss"])

    off = dataclasses.replace(base, name="ck-off", epochs=3, staleness=1,
                              overlap="off", checkpoint_every=0, save=False)
    r = train(off, resume_dir=ckpt)
    assert r.state.mix_pending == () and r.state.mix_ages == ()
    assert np.isfinite(r.history[-1]["loss"])

    # eager checkpoint → staleness ring: the ring primes from zero
    eager = _cfg(tmp_path, name="eg", overlap="off", save=True,
                 checkpoint_every=1)
    eager = dataclasses.replace(eager, staleness=1)
    train(eager)
    up = dataclasses.replace(base, name="eg-up", epochs=3,
                             checkpoint_every=0, save=False)
    r = train(up, resume_dir=f"{tmp_path}/eg_ckpt")
    assert np.asarray(r.state.mix_pending).shape[1] == 2
    assert np.isfinite(r.history[-1]["loss"])


def test_reconcile_ring_drain_exact():
    """The depth-change flush applies the saved ring oldest-first — exact
    arithmetic, unit-tested so the flush can never silently become a drop
    (the same pin test_reconcile_mix_pending_drains_delta holds for the
    one-step delta)."""
    from matcha_tpu.ops import WorkerFlattener
    from matcha_tpu.train.loop import _reconcile_mix_pending
    from matcha_tpu.train.state import TrainState

    rng = np.random.default_rng(3)
    params = {"w": jnp.asarray(rng.normal(size=(SIZE, 4, 3))
                               .astype(np.float32))}
    flattener = WorkerFlattener(params)
    ring = jnp.asarray(rng.normal(size=(SIZE, 3, 12)).astype(np.float32))
    cursor = 7
    state = TrainState(params=params, batch_stats={}, opt_state={},
                       comm_carry=(), step=jnp.asarray(cursor, jnp.int32),
                       mix_pending=ring)
    comm = _make("gather")
    out = _reconcile_mix_pending(state, "off", comm, flattener, SIZE,
                                 staleness=1)
    want = flattener.flatten(params)
    for i in range(3):
        want = want + ring[:, (cursor + i) % 3]
    np.testing.assert_allclose(
        np.asarray(flattener.flatten(out.params)), np.asarray(want),
        rtol=1e-6)
    assert out.mix_pending == () and out.mix_ages == ()
    # same depth: ring kept, ages rebuilt mature from the cursor
    kept = _reconcile_mix_pending(state, "1step", comm, flattener, SIZE,
                                  staleness=3)
    assert kept.mix_pending is ring
    ages = np.asarray(kept.mix_ages)
    assert ages.shape == (SIZE, 3)
    assert sorted(ages[0].tolist()) == [1, 2, 3]
    # depth change: flushed then re-primed at the new depth
    moved = _reconcile_mix_pending(state, "1step", comm, flattener, SIZE,
                                   staleness=2)
    assert np.asarray(moved.mix_pending).shape == (SIZE, 2, 12)
    np.testing.assert_array_equal(np.asarray(moved.mix_pending), 0.0)
    np.testing.assert_array_equal(np.asarray(moved.mix_ages), -1)
    np.testing.assert_allclose(
        np.asarray(flattener.flatten(moved.params)), np.asarray(want),
        rtol=1e-6)


def test_zero_retrace_under_churn_with_ring():
    """check_single_trace on the compiled k=2 step while membership values
    change (join/leave as value updates): the staleness ring must not add
    a single retrace — the elastic no-retrace contract extends to it."""
    from matcha_tpu.analysis import check_single_trace, retrace_guard
    from matcha_tpu.elastic.runtime import membership_arrays
    from matcha_tpu.models import select_model
    from matcha_tpu.train.state import (
        init_train_state,
        make_optimizer,
        make_train_step,
    )
    from matcha_tpu.train.lr import make_lr_schedule

    n = SIZE
    sched = SCHED
    comm = _make("dense")
    model = select_model("mlp", "synthetic", num_classes=4)
    lr = make_lr_schedule(0.05, 4, warmup=False)
    opt = make_optimizer(lr)
    state, flattener = init_train_state(
        model, (16,), n, opt, comm, overlap="1step", staleness=2)
    step = make_train_step(model, opt, comm, flattener, sched.flags,
                           lr_schedule=lr, overlap="1step", staleness=2,
                           elastic=True)
    xb = jnp.asarray(np.random.default_rng(0)
                     .normal(size=(n, 4, 16)).astype(np.float32))
    yb = jnp.asarray(np.zeros((n, 4), np.int32))
    guarded, counter = retrace_guard(step)
    for alive in ([1] * n, [1] * (n - 1) + [0],
                  [1, 0] + [1] * (n - 2), [1] * n):
        member = membership_arrays(np.asarray(alive, np.float32), 1.0)
        state = state.replace(membership=member)
        state, _ = guarded(state, xb, yb)
    jax.block_until_ready(state.params)
    check_single_trace(counter, label="staleness_ring_step")
    assert np.asarray(state.mix_ages).shape == (n, 2)


@pytest.mark.faults
def test_kdeep_with_fault_plan(tmp_path):
    """Chaos × k-deep ring: a NaN-poisoned worker is healed mid-run at
    staleness 2 — its whole ring column (two real in-flight deltas) is
    dropped with its momentum, training stays finite, and exactly those
    drops land in the telemetry counter.  (A dead→revive cycle drops
    nothing: a quarantined worker issues no deltas while dead, and the
    ring's counter — unlike the legacy heal-count proxy — says so.)"""
    from matcha_tpu.train import train

    cfg = _cfg(tmp_path, name="k2-faults", staleness=2, save=True,
               wire_dtype="bf16",
               fault_plan={"events": [
                   {"kind": "nan", "worker": 3, "start": 6},
                   {"kind": "dead", "worker": 5, "start": 10, "stop": 14},
               ]})
    r = train(cfg)
    assert np.isfinite(r.history[-1]["loss"])
    assert np.all(np.isfinite(np.asarray(r.state.mix_pending)))
    events = [json.loads(line) for line in
              open(os.path.join(tmp_path, "k2-faults_mlp", "events.jsonl"))]
    dropped = sum(e["stale_dropped"] for e in events
                  if e["kind"] == "telemetry")
    assert dropped >= 2  # the healed worker's K in-flight deltas
