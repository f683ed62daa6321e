"""Overlapped (two-phase) gossip pipeline + bf16 wire tests (ISSUE 4).

Three property families, all cheap enough for the default lane:

* **Drain equivalence** — the pipelined schedule (`begin_mix` at t, apply at
  t+1) realizes the identical W-chain on a pure consensus stream: after one
  drain step `run_overlapped == run` for every backend, with and without a
  survivor mask.  This is the constructive form of the one-step-staleness
  argument the train loop relies on.
* **Mean preservation** — one-step-delayed mixing never moves the worker
  mean: every `begin_mix` delta has zero column-mean (doubly stochastic W;
  CHOCO's telescoping s/x̂), and on the edgewise backends the bf16 wire
  keeps this *exact* (quantize-before-exchange makes edge contributions
  cancel pairwise in IEEE arithmetic).
* **bf16 wire parity** — one gossip step at wire bf16 deviates from the f32
  path by at most 2⁻⁸ relative (bf16 keeps 8 significand bits), and the
  staleness-adjusted ρ predictor bounds the pipelined MC simulator exactly
  as the eager bound bounds the eager simulator (same MC ≤ ρ invariant as
  tests/test_plan.py).
"""

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from gossip_cases import alive_of, make_comm, sched_of  # beside this file
from matcha_tpu import topology as tp
from matcha_tpu.communicator import make_choco, make_decen
from matcha_tpu.parallel import shard_workers, worker_mesh
from matcha_tpu.schedule import matcha_schedule
from matcha_tpu.schedule.solvers import (
    solve_activation_probabilities,
    solve_mixing_weight,
)

SIZE = tp.graph_size(0)
SCHED = matcha_schedule(tp.select_graph(0), SIZE, iterations=10, budget=0.5,
                        seed=3)
BACKENDS = ["gather", "dense", "skip", "dense-mxu", "choco", "centralized"]


def _sched(backend):
    return sched_of(backend, SCHED)


def _make(backend, wire=None):
    return make_comm(backend, SCHED, wire)


def _x0(d=21, seed=0, n=SIZE):
    return jnp.asarray(
        np.random.default_rng(seed).normal(size=(n, d)).astype(np.float32))


@pytest.mark.parametrize("masked", [False, True], ids=["full", "alive-mask"])
@pytest.mark.parametrize("backend", BACKENDS)
def test_delayed_mix_drains_to_eager(backend, masked):
    """Pipelined chain + one drain step == eager chain, every backend,
    with and without a dead worker: drain equivalence must hold under an
    arbitrary survivor mask (the masked W stays doubly stochastic over
    survivors, so the delayed-apply argument is unchanged)."""
    comm = _make(backend)
    sched = _sched(backend)
    alive = alive_of(sched) if masked else None
    x0 = _x0(n=sched.num_workers)
    eager, ce = jax.jit(lambda x: comm.run(x, sched.flags, alive=alive))(x0)
    over, co = jax.jit(
        lambda x: comm.run_overlapped(x, sched.flags, alive=alive))(x0)
    np.testing.assert_allclose(np.asarray(eager), np.asarray(over),
                               rtol=1e-5, atol=1e-6)
    # carries thread identically (issue-time advance): CHOCO's {x̂, s}
    for a, b in zip(jax.tree_util.tree_leaves(ce),
                    jax.tree_util.tree_leaves(co)):
        np.testing.assert_allclose(np.asarray(a), np.asarray(b),
                                   rtol=1e-5, atol=1e-6)


@pytest.mark.parametrize("backend", ["gather", "dense", "choco"])
def test_delayed_mix_drains_to_eager_per_step_mask(backend):
    """Same drain equivalence under a *time-varying* survivor mask
    (f32[T, N]: workers die and revive mid-chain) — the mask applies at
    issue time in both schedules, so the argument is unchanged."""
    comm = _make(backend)
    rng = np.random.default_rng(9)
    alive = (rng.random((SCHED.flags.shape[0], SIZE)) > 0.25) \
        .astype(np.float32)
    alive[:, 0] = 1.0  # at least one permanent survivor
    x0 = _x0(d=13, seed=5)
    eager, _ = jax.jit(lambda x: comm.run(x, SCHED.flags, alive=alive))(x0)
    over, _ = jax.jit(
        lambda x: comm.run_overlapped(x, SCHED.flags, alive=alive))(x0)
    np.testing.assert_allclose(np.asarray(eager), np.asarray(over),
                               rtol=1e-5, atol=1e-6)


@pytest.mark.parametrize("wire", [None, "bf16"], ids=["f32", "bf16"])
@pytest.mark.parametrize("backend",
                         ["gather", "dense", "skip", "choco", "centralized"])
def test_delayed_mix_preserves_worker_mean(backend, wire):
    """The visible (undrained) pipelined state keeps the exact worker mean:
    deltas applied late are still zero-column-mean deltas.  On the edgewise
    backends the bf16 wire preserves the mean to f32 rounding (pairwise
    cancellation of quantized edge deltas); the dense/centralized reductions
    round through bf16 arithmetic, bounded by the 2⁻⁸ wire budget."""
    comm = _make(backend, wire)
    x0 = _x0(d=17, seed=1)
    x, _, pending = jax.jit(
        lambda x: comm.run_overlapped(x, SCHED.flags, drain=False))(x0)
    exact = wire is None or backend in ("gather", "skip", "choco")
    atol = 2e-5 if exact else 5e-3
    np.testing.assert_allclose(np.asarray(x).mean(axis=0),
                               np.asarray(x0).mean(axis=0), atol=atol)
    # the in-flight delta itself must not be about to move the mean either
    np.testing.assert_allclose(np.asarray(pending).mean(axis=0), 0.0,
                               atol=atol)


@pytest.mark.parametrize("backend", BACKENDS)
def test_bf16_wire_one_step_parity(backend):
    """One gossip step at wire bf16 stays within 2⁻⁸ relative of the f32
    path — the quantization budget `stale_contraction_rho` models and the
    acceptance bound of ISSUE 4."""
    f32c = _make(backend)
    b16c = _make(backend, wire="bf16")
    sched = _sched(backend)
    x0 = _x0(d=33, seed=2, n=sched.num_workers)
    flags0 = jnp.asarray(sched.flags[0], jnp.float32)
    a, _ = f32c.step(x0, f32c.init(x0), flags0)
    b, _ = b16c.step(x0, b16c.init(x0), flags0)
    scale = float(jnp.max(jnp.abs(a)))
    rel = float(jnp.max(jnp.abs(a - b))) / scale
    assert rel <= 2.0 ** -8, (backend, rel)


def test_wire_dtype_rejects_unknown():
    with pytest.raises(ValueError, match="wire_dtype"):
        make_decen(SCHED, backend="dense", wire_dtype="fp8")


def test_bf16_wire_has_consensus_floor():
    """The multiplicative ρ_eff model is a rate claim *above* the wire's
    resolution floor: the executor quantizes the full state, so once
    disagreement sits below the bf16 ulp of the parameter scale, exchanged
    differences lose resolution and contraction stalls near the floor
    instead of continuing geometrically.  Pins `wire_disagreement_floor`
    against the real executor — the honest limit `plan_tpu.py rho
    --wire-dtype bf16` reports as `disagreement_floor_rel`."""
    from matcha_tpu.parallel import worker_disagreement
    from matcha_tpu.plan import wire_disagreement_floor

    rng = np.random.default_rng(11)
    mean = rng.normal(size=(1, 64)).astype(np.float32)  # parameter scale ~1
    x0 = jnp.asarray(mean + 1e-6 * rng.normal(size=(SIZE, 64))
                     .astype(np.float32))
    d0 = float(worker_disagreement(x0))
    scale = float(np.sqrt(np.mean(mean ** 2)))
    floor = wire_disagreement_floor("bf16", scale)
    assert d0 < floor  # start already below the wire's resolution

    # the schedule's own flag stream, repeated (all-ones would overdrive
    # alpha, which is solved for the *expected* activation, not full)
    flags = np.tile(np.asarray(SCHED.flags, np.float32), (5, 1))
    xT, _ = jax.jit(lambda x: _make("gather", wire="bf16").run(x, flags))(x0)
    dT = float(worker_disagreement(xT))
    # stays bounded by the floor (granularity noise cannot blow up)...
    assert dT <= floor, (dT, floor)
    # ...but does NOT contract geometrically: the same 50 scheduled steps
    # crush disagreement by over an order of magnitude in f32, while the
    # bf16 wire — its resolution already exhausted — stalls near the start
    f32T, _ = jax.jit(lambda x: _make("gather").run(x, flags))(x0)
    assert float(worker_disagreement(f32T)) < 0.1 * d0
    assert dT > 0.02 * d0, (dT, d0)
    assert wire_disagreement_floor("f32") == 0.0


def test_shard_map_overlap_and_wire_parity():
    """Folded shard_map (ppermute on ICI): drain equivalence on the mesh,
    and the bf16 ppermute path matches the single-array bf16 gather path —
    the two executors quantize at the same boundary by construction."""
    if jax.device_count() < 8:
        pytest.skip("needs 8 devices")
    mesh = worker_mesh(8)
    n = 16
    sched = matcha_schedule(tp.select_graph(2), n, iterations=8, budget=0.5,
                            seed=1)
    x0 = np.random.default_rng(4).normal(size=(n, 19)).astype(np.float32)
    comm = make_decen(sched, mesh=mesh, backend="shard_map")
    xs = shard_workers(jnp.asarray(x0), mesh)
    eager, _ = jax.jit(lambda x: comm.run(x, sched.flags))(xs)
    over, _ = jax.jit(lambda x: comm.run_overlapped(x, sched.flags))(xs)
    np.testing.assert_allclose(np.asarray(eager), np.asarray(over),
                               rtol=1e-5, atol=1e-6)
    wired = make_decen(sched, mesh=mesh, backend="shard_map",
                       wire_dtype="bf16")
    gathered = make_decen(sched, backend="gather", wire_dtype="bf16")
    a, _ = jax.jit(lambda x: wired.run(x, sched.flags[:4]))(xs)
    b, _ = gathered.run(jnp.asarray(x0), sched.flags[:4])
    np.testing.assert_allclose(np.asarray(a), np.asarray(b),
                               rtol=1e-5, atol=1e-6)


def test_choco_shard_map_wire_parity():
    """CHOCO's compressed bf16 wire: the folded ppermute backend and the
    batched gather backend quantize identically (deterministic top-k)."""
    if jax.device_count() < 8:
        pytest.skip("needs 8 devices")
    mesh = worker_mesh(8)
    sched = matcha_schedule(tp.select_graph(0), 8, iterations=6, budget=0.5,
                            seed=7)
    x0 = np.random.default_rng(6).normal(size=(8, 21)).astype(np.float32)
    a, _ = make_choco(sched, ratio=0.7, consensus_lr=0.3,
                      wire_dtype="bf16").run(jnp.asarray(x0), sched.flags)
    comm = make_choco(sched, ratio=0.7, consensus_lr=0.3, mesh=mesh,
                      backend="shard_map", wire_dtype="bf16")
    xs = shard_workers(jnp.asarray(x0), mesh)
    b, _ = jax.jit(comm.run)(xs, sched.flags)
    np.testing.assert_allclose(np.asarray(a), np.asarray(b),
                               rtol=1e-5, atol=1e-6)


@pytest.mark.parametrize("gid", [0, 5])
def test_stale_rho_bounds_pipelined_mc(gid):
    """Predictor ≥ measured, pipelined edition: the staleness-adjusted ρ
    bounds the MC empirical rate of the *pipelined* recurrence (with and
    without the bf16 wire) — the same invariant, same 2% finite-sample
    headroom, as the eager zoo test in tests/test_plan.py."""
    from matcha_tpu.plan import simulate_consensus, stale_contraction_rho

    size = tp.graph_size(gid)
    dec = tp.select_graph(gid)
    Ls = tp.matching_laplacians(dec, size)
    p = solve_activation_probabilities(Ls, 0.5, iters=600)
    alpha, rho = solve_mixing_weight(Ls, p)
    for wire in (None, "bf16"):
        pred = stale_contraction_rho(Ls, p, alpha, overlap="1step",
                                     wire_dtype=wire)
        assert np.isfinite(pred)
        sim = simulate_consensus(dec, size, p, alpha, steps=60, trials=4,
                                 seed=3, laplacians=Ls, overlap="1step",
                                 wire_dtype=wire)
        emp = sim.empirical_rate()
        assert emp <= pred * 1.02, (gid, wire, emp, pred)
        assert sim.rho_bound == pytest.approx(pred)
    # consistency: f32 pipeline keeps the eager bound exactly; bf16 can
    # only inflate it (bounded noise is never a speedup claim)
    assert stale_contraction_rho(Ls, p, alpha, wire_dtype=None) \
        == pytest.approx(rho)
    assert stale_contraction_rho(Ls, p, alpha, wire_dtype="bf16") >= rho


def test_overlap_training_e2e():
    """The pipelined train loop end-to-end: overlap=1step + bf16 wire
    trains to the same neighborhood as the eager schedule (one-step
    staleness perturbs constants, not convergence), the drained result is
    finite, and mix_pending is zeroed on the returned state."""
    from matcha_tpu.train import TrainConfig, train

    def run(overlap, wire):
        cfg = TrainConfig(
            name=f"ov-{overlap}-{wire}", model="mlp", dataset="synthetic",
            dataset_kwargs={"num_train": 512, "num_test": 128},
            num_workers=8, graphid=5, matcha=False, epochs=2, lr=0.05,
            batch_size=16, eval_every=0, save=False,
            measure_comm_split=False, overlap=overlap, wire_dtype=wire)
        return train(cfg)

    eager = run("off", "f32")
    piped = run("1step", "bf16")
    le = eager.history[-1]["loss"]
    lp = piped.history[-1]["loss"]
    assert np.isfinite(lp)
    assert abs(lp - le) <= 0.25 * abs(le) + 0.05, (le, lp)
    # drained: the returned state carries no un-applied exchange
    np.testing.assert_array_equal(np.asarray(piped.state.mix_pending), 0.0)
    # pipeline must actually have been primed (state pytree carries [N, D])
    assert piped.state.mix_pending.shape[0] == 8
    assert eager.state.mix_pending == ()


def test_resume_across_overlap_change(tmp_path):
    """A checkpoint written under one --overlap setting must resume under
    the other: off→1step primes the zero in-flight delta (an eager
    checkpoint has none); 1step→off drains the saved delta into the params
    instead of silently dropping a mixing step."""
    import dataclasses

    from matcha_tpu.train import TrainConfig, train

    base = TrainConfig(
        name="ovck", model="mlp", dataset="synthetic",
        dataset_kwargs={"num_train": 256, "num_test": 64},
        num_workers=8, graphid=5, matcha=False, epochs=1, lr=0.05,
        batch_size=16, eval_every=0, measure_comm_split=False,
        save=False, savePath=str(tmp_path), checkpoint_every=1)
    train(base)  # eager checkpoint at epoch 0
    ckpt = f"{base.savePath}/{base.name}_ckpt"

    up = dataclasses.replace(base, epochs=2, checkpoint_every=1,
                             overlap="1step", wire_dtype="bf16")
    r_up = train(up, resume_dir=ckpt)  # off → 1step: pending primed
    assert r_up.history[0]["epoch"] == 1
    assert np.isfinite(r_up.history[-1]["loss"])

    # the pipelined run's checkpoint holds a real in-flight delta (restore
    # through an array-slot template — a () template would drop it): the
    # eager resume below has an actual delta to drain, not a vacuous zero
    from matcha_tpu.train.checkpoint import restore_checkpoint

    ck_state, ck_epoch = restore_checkpoint(
        ckpt, r_up.state.replace(
            mix_pending=jnp.zeros_like(r_up.state.mix_pending)))
    assert ck_epoch == 1
    assert float(jnp.sum(jnp.abs(ck_state.mix_pending))) > 0.0

    down = dataclasses.replace(base, epochs=3, checkpoint_every=0)
    r_down = train(down, resume_dir=ckpt)  # 1step → off: pending drained
    assert r_down.history[0]["epoch"] == 2
    assert np.isfinite(r_down.history[-1]["loss"])
    assert r_down.state.mix_pending == ()


def test_reconcile_mix_pending_drains_delta():
    """The 1step→off reconcile applies the saved delta to the params —
    exact arithmetic, unit-tested so the drain can never silently become a
    drop again (it did once: a ()-slot restore template made orbax discard
    the saved delta before the drain branch could see it)."""
    from matcha_tpu.ops import WorkerFlattener
    from matcha_tpu.train.loop import _reconcile_mix_pending
    from matcha_tpu.train.state import TrainState

    params = {"w": jnp.asarray(
        np.random.default_rng(3).normal(size=(SIZE, 4, 3)).astype(np.float32))}
    flattener = WorkerFlattener(params)
    delta = jnp.asarray(np.random.default_rng(4)
                        .normal(size=(SIZE, 12)).astype(np.float32))
    state = TrainState(params=params, batch_stats={}, opt_state={},
                       comm_carry=(), step=jnp.zeros((), jnp.int32),
                       mix_pending=delta)
    comm = _make("gather")
    out = _reconcile_mix_pending(state, "off", comm, flattener, SIZE)
    want = flattener.unflatten(flattener.flatten(params) + delta)
    np.testing.assert_allclose(np.asarray(out.params["w"]),
                               np.asarray(want["w"]), rtol=1e-6)
    assert out.mix_pending == ()
    # 1step keeps the delta untouched; () primes zeros only for 1step
    assert _reconcile_mix_pending(state, "1step", comm, flattener,
                                  SIZE).mix_pending is delta
    empty = state.replace(mix_pending=())
    assert _reconcile_mix_pending(
        empty, "1step", comm, flattener, SIZE).mix_pending.shape == (SIZE, 12)
    assert _reconcile_mix_pending(empty, "off", comm, flattener,
                                  SIZE).mix_pending == ()


# ---------------------------------------------------------------------------
# Universal local-step elision (DESIGN.md §24, ISSUE 19): the restructured
# epoch executes the mix only on every L-th step — a lax.cond identity
# branch, not a multiply-by-identity — and `Communicator.run_elided` is the
# chain-level twin of that scan body.  Two equivalence contracts:
#
# * compaction (every backend, carry included): eliding steps t % L != 0 is
#   the same chain as running only the executed rows `flags[::L]` — elided
#   steps execute *nothing*, so even a compressing carry (CHOCO's x̂/s) and
#   a flag-blind reducer (centralized) agree bitwise.
# * thinned-stream (flag-thinning backends): on a stream whose thinned rows
#   are zeroed, `run_elided == run` — an all-zero row is identity mixing,
#   so skipping it is exact.  This is the semantics `--local-steps` pinned
#   before elision went universal; centralized (flag-blind) and choco
#   (zero-row steps still advance x̂) are excluded by construction.
# ---------------------------------------------------------------------------

ELISION_L = 3


@pytest.mark.parametrize("masked", [False, True], ids=["full", "alive-mask"])
@pytest.mark.parametrize("backend", BACKENDS)
def test_run_elided_matches_compacted_chain(backend, masked):
    """run_elided(flags, L) == run(flags[::L]) on every backend: an elided
    step executes nothing — no arithmetic, no wire, no carry advance."""
    comm = _make(backend)
    sched = _sched(backend)
    alive = alive_of(sched) if masked else None
    x0 = _x0(d=19, seed=7, n=sched.num_workers)
    flags = jnp.asarray(sched.flags, jnp.float32)
    el, ce = jax.jit(lambda x: comm.run_elided(
        x, flags, ELISION_L, alive=alive))(x0)
    ref, cr = jax.jit(lambda x: comm.run(
        x, flags[::ELISION_L], alive=alive))(x0)
    np.testing.assert_allclose(np.asarray(el), np.asarray(ref),
                               rtol=1e-5, atol=1e-6)
    for a, b in zip(jax.tree_util.tree_leaves(ce),
                    jax.tree_util.tree_leaves(cr)):
        np.testing.assert_allclose(np.asarray(a), np.asarray(b),
                                   rtol=1e-5, atol=1e-6)


@pytest.mark.parametrize("wire", [None, "bf16"], ids=["f32", "bf16"])
@pytest.mark.parametrize("masked", [False, True], ids=["full", "alive-mask"])
@pytest.mark.parametrize("backend", ["gather", "dense", "skip", "dense-mxu"])
def test_run_elided_matches_thinned_stream(backend, masked, wire):
    """run_elided(full flags, L) == run(thinned flags): eliding a step is
    exactly what multiplying by the identity a zero row builds used to be —
    the drain-equivalence contract of the restructured epoch, on every
    flag-thinning backend × alive mask × wire dtype."""
    comm = _make(backend, wire)
    sched = _sched(backend)
    alive = alive_of(sched) if masked else None
    x0 = _x0(d=23, seed=8, n=sched.num_workers)
    flags = np.asarray(sched.flags, np.float32).copy()
    # end on an executed step: under the bf16 wire the dense exchange rounds
    # the state it reads, so a zero row after the last executed step leaves
    # the state rounded where elision leaves it as it was (an earlier zero
    # row is absorbed by the next step's own rounding)
    flags = flags[:len(flags) - (len(flags) - 1) % ELISION_L]
    thinned = flags.copy()
    thinned[np.arange(len(thinned)) % ELISION_L != 0] = 0.0
    el, _ = jax.jit(lambda x: comm.run_elided(
        x, jnp.asarray(flags), ELISION_L, alive=alive))(x0)
    ref, _ = jax.jit(lambda x: comm.run(
        x, jnp.asarray(thinned), alive=alive))(x0)
    np.testing.assert_allclose(np.asarray(el), np.asarray(ref),
                               rtol=1e-5, atol=1e-6)


def test_run_elided_offset_and_traced_every():
    """Mid-stream alignment and hot-swappability: splitting a stream at an
    arbitrary boundary and resuming with ``offset=s`` is the same chain,
    and ``local_every`` may arrive as a traced i32 scalar (the ControlKnobs
    slot) without changing the result."""
    comm = _make("gather")
    x0 = _x0(d=11, seed=9)
    flags = jnp.asarray(SCHED.flags, jnp.float32)
    whole, cw = comm.run_elided(x0, flags, ELISION_L)
    s = 4  # deliberately NOT a multiple of L: the cursor must carry over
    x1, c1 = comm.run_elided(x0, flags[:s], ELISION_L)
    x2, c2 = comm.run_elided(x1, flags[s:], ELISION_L, carry=c1, offset=s)
    np.testing.assert_array_equal(np.asarray(whole), np.asarray(x2))
    traced, _ = jax.jit(
        lambda x, ev: comm.run_elided(x, flags, ev))(
            x0, jnp.asarray(ELISION_L, jnp.int32))
    np.testing.assert_array_equal(np.asarray(whole), np.asarray(traced))
    # L=1 elides nothing: exactly the plain chain
    all_of_it, _ = comm.run_elided(x0, flags, 1)
    ref, _ = comm.run(x0, flags)
    np.testing.assert_allclose(np.asarray(all_of_it), np.asarray(ref),
                               rtol=1e-6, atol=1e-7)


def test_elision_ledger_2x_reduction():
    """Acceptance pin (ISSUE 19): for dense at L=4, the compiled-cost
    ledger's per-epoch gossip-attributed boundary bytes drop ≥2× vs L=1 —
    the thinned steps' programs are *gone*, not multiplied by I.  The
    ratio is exactly T/ceil(T/L) (every executed step pays the same
    per-step program)."""
    from matcha_tpu.obs.costs import elision_epoch_costs

    t_steps = 40
    c1 = elision_epoch_costs(SIZE, 1024, SCHED.decomposed, backend="dense",
                             t_steps=t_steps, local_every=1)
    c4 = elision_epoch_costs(SIZE, 1024, SCHED.decomposed, backend="dense",
                             t_steps=t_steps, local_every=4)
    assert c1["exec_steps"] == t_steps
    assert c4["exec_steps"] == -(-t_steps // 4)
    ratio = c1["gossip_hbm_bytes_per_epoch"] \
        / c4["gossip_hbm_bytes_per_epoch"]
    assert ratio >= 2.0, ratio
    # L=1 prices the exact unthinned chain: per-epoch == per-step × T
    assert c1["gossip_hbm_bytes_per_epoch"] == pytest.approx(
        c1["gossip_hbm_bytes_per_step"] * t_steps)


@pytest.mark.parametrize("backend", ["dense", "skip"])
def test_elided_epoch_matches_eager_chain(backend):
    """Drain equivalence at the train-loop level: the scanned L-body epoch
    (one compiled program, gossip under a traced cond) reaches the same
    state as the eager per-step chain at local_steps=4 — the restructure
    moved *where* the thinning executes, not what it computes."""
    import dataclasses

    from matcha_tpu.train import TrainConfig, train

    base = TrainConfig(
        name=f"elide-{backend}", model="mlp", dataset="synthetic",
        dataset_kwargs={"num_train": 256, "num_test": 64},
        num_workers=SIZE, graphid=0, budget=0.5, epochs=2, lr=0.05,
        batch_size=16, eval_every=0, save=False, measure_comm_split=False,
        gossip_backend=backend, local_steps=4, scan_epoch=True)
    scanned = train(base)
    eager = train(dataclasses.replace(base, scan_epoch=False))
    ls, le = scanned.history[-1]["loss"], eager.history[-1]["loss"]
    assert np.isfinite(ls) and np.isfinite(le)
    np.testing.assert_allclose(ls, le, rtol=1e-4)
    for a, b in zip(jax.tree_util.tree_leaves(scanned.state.params),
                    jax.tree_util.tree_leaves(eager.state.params)):
        np.testing.assert_allclose(np.asarray(a), np.asarray(b),
                                   rtol=1e-4, atol=1e-5)


@pytest.mark.faults
def test_overlap_with_fault_plan():
    """Chaos × pipeline: a worker dies mid-run under overlap=1step — the
    healed worker's stale in-flight delta is dropped with its momentum, and
    training stays finite (acceptance: the chaos examples still converge
    under arbitrary alive masks)."""
    from matcha_tpu.train import TrainConfig, train

    cfg = TrainConfig(
        name="ov-faults", model="mlp", dataset="synthetic",
        dataset_kwargs={"num_train": 512, "num_test": 128},
        num_workers=8, graphid=5, matcha=False, epochs=2, lr=0.05,
        batch_size=16, eval_every=0, save=False, measure_comm_split=False,
        overlap="1step", wire_dtype="bf16",
        fault_plan={"events": [
            {"kind": "dead", "worker": 3, "start": 2, "stop": 5},
        ]})
    result = train(cfg)
    assert np.isfinite(result.history[-1]["loss"])
    assert np.all(np.isfinite(np.asarray(result.state.mix_pending)))
