"""Permutation-form Pallas gossip backend (ISSUE 13).

The perm kernel reads only the ``[T, M]`` flag array (SMEM scalars) and
applies each matching as per-row partner copies on a VMEM-resident state
block.  On CPU it runs under the Pallas interpreter — same program text,
no Mosaic (``tests/test_pallas.py`` lowers and compiles it for the TPU) —
and must be **bitwise** the compiled gather oracle (a ``lax.scan`` over
``gossip_mix``) in f32, masked or not, on any wire.  (An *eager*
op-by-op gather chain differs from any compiled form at the 1-ulp
FMA-contraction scale; that is XLA, not the kernel — the oracle here is
compiled on purpose.)

Marker: ``perm`` — the ci/lint.sh perm lane runs this file standalone.
"""

import numpy as np
import pytest

import jax
import jax.numpy as jnp
from jax import lax

from matcha_tpu import topology as tp
from matcha_tpu.communicator import make_decen
from matcha_tpu.parallel import (
    gossip_mix,
    involution_tables,
    perm_gossip_run,
)
from matcha_tpu.schedule import matcha_schedule

pytestmark = pytest.mark.perm


def _schedule(n=8, iterations=13, budget=0.6, seed=0):
    dec = tp.decompose(tp.ring_graph(n), n, seed=0)
    return matcha_schedule(dec, n, iterations=iterations, budget=budget,
                           seed=seed)


def _oracle(sched, x, weights, alive=None, wire=None):
    """The gather oracle, compiled: lax.scan over gossip_mix — the exact
    program the parity contract names."""
    perms = np.asarray(sched.perms)

    @jax.jit
    def run(x, w):
        def body(s, wt):
            return gossip_mix(s, perms, wt, alive, wire_dtype=wire), None
        return lax.scan(body, x, w)[0]

    return run(x, weights)


def _tables(sched):
    return involution_tables(sched.perms)


def _state(n, d=37, seed=0, dtype=jnp.float32):
    return jnp.asarray(np.random.default_rng(seed).normal(size=(n, d)),
                       dtype)


def _weights(sched):
    return sched.alpha * jnp.asarray(sched.flags, jnp.float32)


# ------------------------------------------------------------------ parity

def test_perm_f32_exact_vs_gather_oracle():
    sched = _schedule()
    pi, pr = _tables(sched)
    x = _state(sched.num_workers)
    w = _weights(sched)
    out = perm_gossip_run(x, w, pi, pr, interpret=True)
    ref = _oracle(sched, x, w)
    np.testing.assert_array_equal(np.asarray(out), np.asarray(ref))


def test_perm_f32_exact_under_any_alive_mask():
    sched = _schedule()
    pi, pr = _tables(sched)
    n = sched.num_workers
    x = _state(n)
    w = _weights(sched)
    rng = np.random.default_rng(3)
    for trial in range(4):
        alive = jnp.asarray((rng.random(n) > 0.4).astype(np.float32))
        out = perm_gossip_run(x, w, pi, pr, alive=alive, interpret=True)
        ref = _oracle(sched, x, w, alive=alive)
        np.testing.assert_array_equal(np.asarray(out), np.asarray(ref))


def test_perm_bf16_wire_parity():
    """bf16 wire: bitwise the compiled bf16-wire gather oracle, and within
    the 2^-8-per-step rounding budget of the exact f32 chain."""
    sched = _schedule()
    pi, pr = _tables(sched)
    x = _state(sched.num_workers)
    w = _weights(sched)
    out = perm_gossip_run(x, w, pi, pr, wire_dtype="bf16", interpret=True)
    ref = _oracle(sched, x, w, wire=jnp.bfloat16)
    np.testing.assert_array_equal(np.asarray(out), np.asarray(ref))
    exact = _oracle(sched, x, w)
    rel = float(jnp.max(jnp.abs(out - exact)) / jnp.max(jnp.abs(exact)))
    assert rel <= 2 ** -8, f"bf16 wire drift {rel} above the 2^-8 budget"


def test_perm_bf16_state_accumulates_f32():
    """bf16 state end-to-end (the bench configuration): the kernel's f32
    accumulation must keep a T-step chain within the per-step bf16 budget
    of the f32 chain — a bf16 accumulator would compound far past it."""
    sched = _schedule(iterations=24)
    pi, pr = _tables(sched)
    x32 = _state(sched.num_workers)
    w = _weights(sched)
    out = perm_gossip_run(x32.astype(jnp.bfloat16), w, pi, pr,
                          interpret=True)
    assert out.dtype == jnp.bfloat16
    exact = _oracle(sched, x32, w)
    rel = float(jnp.max(jnp.abs(out.astype(jnp.float32) - exact))
                / jnp.max(jnp.abs(exact)))
    assert rel <= 24 * 2 ** -8


def test_perm_block_tiling_invariance_and_long_streams():
    """block_d (including a non-divisor: padded edge block) retiles columns
    only and never changes bits; and a flag stream longer than one launch's
    SMEM share runs as consecutive launches of the identical chain."""
    from matcha_tpu.parallel import pallas_gossip

    sched = _schedule()
    pi, pr = _tables(sched)
    x = _state(sched.num_workers)
    w = _weights(sched)
    base = perm_gossip_run(x, w, pi, pr, interpret=True)
    for bd in (16, 32, 4096):
        out = perm_gossip_run(x, w, pi, pr, block_d=bd, interpret=True)
        np.testing.assert_array_equal(np.asarray(out), np.asarray(base))
    # shrink the SMEM budget until 13 steps need three launches
    m, n = pi.shape
    with pytest.MonkeyPatch.context() as mp:
        mp.setattr(pallas_gossip, "_PERM_SMEM_BYTES", 8 * m * n + 5 * 4 * m)
        # block_d=64 is a static argument not traced above, so this call
        # re-traces and reads the patched budget
        split = perm_gossip_run(x, w, pi, pr, block_d=64, interpret=True)
    np.testing.assert_array_equal(np.asarray(split), np.asarray(base))


# -------------------------------------------------- stochasticity property

def test_perm_doubly_stochastic_under_any_alive_mask():
    """Property: the realized mixing preserves the worker sum (column
    means) for EVERY alive mask — dead rows are untouched, survivors
    exchange doubly-stochastically — and a constant vector is a fixed
    point over the survivors (row sums = 1)."""
    sched = _schedule(n=12, iterations=9)
    pi, pr = _tables(sched)
    n = sched.num_workers
    w = _weights(sched)
    rng = np.random.default_rng(7)
    for trial in range(6):
        alive = (rng.random(n) > rng.uniform(0, 0.8)).astype(np.float32)
        x = _state(n, seed=trial)
        out = perm_gossip_run(x, w, pi, pr, alive=jnp.asarray(alive),
                              interpret=True)
        # column sums preserved (doubly stochastic: mass moves, never
        # appears or disappears)
        np.testing.assert_allclose(np.asarray(out).sum(0),
                                   np.asarray(x).sum(0), rtol=2e-5,
                                   atol=2e-5)
        # dead rows bitwise frozen (their exchanges are self-loops)
        dead = np.flatnonzero(alive == 0)
        np.testing.assert_array_equal(np.asarray(out)[dead],
                                      np.asarray(x)[dead])
        # constant vector fixed point (row sums = 1 over survivors)
        ones = jnp.ones((n, 8), jnp.float32)
        fixed = perm_gossip_run(ones, w, pi, pr, alive=jnp.asarray(alive),
                                interpret=True)
        np.testing.assert_allclose(np.asarray(fixed), 1.0, atol=1e-6)


# ----------------------------------------------------- communicator seams

def test_perm_backend_run_matches_gather_backend():
    sched = _schedule()
    x = _state(sched.num_workers, d=40)
    flags = jnp.asarray(sched.flags, jnp.float32)
    perm = make_decen(sched, backend="perm")
    gather = make_decen(sched, backend="gather")
    assert perm.multi_step is not None
    assert perm.multi_step_masked is not None
    xp, _ = perm.run(x, flags)
    xg, _ = gather.run(x, flags)
    np.testing.assert_array_equal(np.asarray(xp), np.asarray(xg))
    # masked chains keep the fused launch (multi_step_masked) and still
    # match the gather backend's per-step masked scan bitwise
    alive = jnp.asarray(np.r_[np.ones(sched.num_workers - 2), 0.0, 1.0],
                        jnp.float32)
    xpm, _ = perm.run(x, flags, alive=alive)
    xgm, _ = gather.run(x, flags, alive=alive)
    np.testing.assert_array_equal(np.asarray(xpm), np.asarray(xgm))


def test_perm_overlap_drain_equivalence():
    """The begin_mix/apply_mix pipeline, drained, reproduces the eager
    chain exactly — the two-phase seam contract (base.py docstring) for
    the perm backend, f32 wire."""
    sched = _schedule()
    x = _state(sched.num_workers, d=33)
    flags = jnp.asarray(sched.flags, jnp.float32)
    perm = make_decen(sched, backend="perm")
    eager, _ = perm.run(x, flags)
    drained, _ = perm.run_overlapped(x, flags, drain=True)
    np.testing.assert_array_equal(np.asarray(eager), np.asarray(drained))
    # undrained: the visible state is one mix behind + the pending delta
    vis, _, pending = perm.run_overlapped(x, flags, drain=False)
    np.testing.assert_array_equal(np.asarray(vis + pending),
                                  np.asarray(eager))


def test_perm_overlap_drain_equivalence_masked_bf16():
    sched = _schedule()
    n = sched.num_workers
    x = _state(n, d=33)
    flags = jnp.asarray(sched.flags, jnp.float32)
    alive = jnp.asarray(np.r_[np.ones(n - 1), 0.0], jnp.float32)
    perm = make_decen(sched, backend="perm", wire_dtype="bf16")
    eager, _ = perm.run(x, flags, alive=alive)
    drained, _ = perm.run_overlapped(x, flags, alive=alive, drain=True)
    # a quantizing wire re-rounds the pipeline's slightly different
    # intermediate states: agreement holds to the 2^-8-per-step budget
    # the stale-contraction model already carries (base.py docstring)
    err = float(jnp.max(jnp.abs(drained - eager))
                / (jnp.max(jnp.abs(eager)) + 1e-30))
    assert err <= flags.shape[0] * 2 ** -8


def test_perm_empty_and_degenerate_windows():
    """Planlint-style degeneracy: an all-flags-zero window is the identity
    BITWISE (every weight is 0, every delta accumulates nothing), an empty
    stream returns the state object unchanged, and zero windows compose
    with real ones."""
    sched = _schedule(iterations=6)
    pi, pr = _tables(sched)
    x = _state(sched.num_workers)
    m = sched.num_matchings
    zeros = jnp.zeros((6, m), jnp.float32)
    out = perm_gossip_run(x, zeros, pi, pr, interpret=True)
    np.testing.assert_array_equal(np.asarray(out), np.asarray(x))
    # bf16 wire: the quantization a zero step computes is discarded —
    # identity must survive the narrow wire bitwise too
    outw = perm_gossip_run(x, zeros, pi, pr, wire_dtype="bf16",
                           interpret=True)
    np.testing.assert_array_equal(np.asarray(outw), np.asarray(x))
    comm = make_decen(sched, backend="perm")
    empty = np.zeros((0, m), np.float32)
    oute, _ = comm.run(x, empty)
    np.testing.assert_array_equal(np.asarray(oute), np.asarray(x))
    # a zero prefix before real flags = the real chain
    w = _weights(sched)
    both = perm_gossip_run(x, jnp.concatenate([zeros, w]), pi, pr,
                           interpret=True)
    real = perm_gossip_run(x, w, pi, pr, interpret=True)
    np.testing.assert_array_equal(np.asarray(both), np.asarray(real))


def test_perm_zero_retrace_under_changing_membership():
    """check_single_trace on the jitted masked chain while the alive mask
    changes value (same shape) every call — membership churn must never
    recompile the perm kernel (its mask is a traced input)."""
    from matcha_tpu.analysis import check_single_trace, retrace_guard

    sched = _schedule()
    n = sched.num_workers
    comm = make_decen(sched, backend="perm")
    flags = jnp.asarray(sched.flags, jnp.float32)

    @jax.jit
    def chain(x, alive):
        return comm.run(x, flags, alive=alive)[0]

    guarded, counter = retrace_guard(chain)
    x = _state(n)
    rng = np.random.default_rng(11)
    out = None
    for _ in range(4):
        alive = jnp.asarray((rng.random(n) > 0.3).astype(np.float32))
        out = guarded(x, alive)
    jax.block_until_ready(out)
    check_single_trace(counter, label="perm_masked_chain")
    assert counter.count == 1


# ------------------------------------------------ selection + observability

def test_auto_backend_resolution_and_gate():
    from matcha_tpu.communicator.decen import resolve_gossip_backend
    from matcha_tpu.plan.cost import (
        PERM_FORCED_WORKERS,
        choose_gossip_backend,
    )

    sched = _schedule()
    # no measurement: auto must keep the committed dense path and say why
    d = resolve_gossip_backend(sched, None)
    assert d["chosen"] == "dense" and d["requested"] == "auto"
    assert "measured" in d["reason"]
    # at the roofline: the structural lever is the only one left
    d = resolve_gossip_backend(sched, None, measured_vs_ceiling=0.91)
    assert d["chosen"] == "perm"
    # below the gate: headroom remains
    d = resolve_gossip_backend(sched, None, measured_vs_ceiling=0.5)
    assert d["chosen"] == "dense"
    # representability wall: forced perm, measurement or not
    d = choose_gossip_backend(PERM_FORCED_WORKERS, 10)
    assert d["chosen"] == "perm" and "unrepresentable" in d["reason"]
    # explicit requests pass through verbatim; a backend whose per-step mix
    # is the dense exchange also says which form that compiles to (PR 28)
    d = resolve_gossip_backend(sched, None, requested="fused")
    assert d.pop("exchange")["form"] in ("streamed", "mxu")
    assert d == {"requested": "fused", "chosen": "fused",
                 "reason": "explicit config; no selection ran"}
    assert resolve_gossip_backend(sched, None, requested="perm") == {
        "requested": "perm", "chosen": "perm",
        "reason": "explicit config; no selection ran"}
    # the byte ledger: flag stream ≪ W stack, ratio carried in the record
    d = resolve_gossip_backend(sched, None)
    assert d["stream_ratio_fused_over_perm"] > 1
    assert d["entries"]["perm"]["stream_bytes_per_step"] \
        < d["entries"]["fused"]["stream_bytes_per_step"]


def test_train_journal_carries_backend_decision(tmp_path):
    """An auto run journals its backend choice as a v5 `backend` event —
    the acceptance criterion's journaled-decision half — and an explicit
    perm run trains end-to-end on the interpret path."""
    from matcha_tpu.obs.journal import read_journal, validate_event
    from matcha_tpu.train import TrainConfig, train

    base = dict(
        name="permauto", model="mlp", dataset="synthetic",
        dataset_kwargs={"num_train": 64, "num_test": 32},
        num_workers=4, devices=1, graphid=None, topology="ring", batch_size=8,
        epochs=1, lr=0.05, warmup=False, eval_every=1,
        measure_comm_split=False, save=True, savePath=str(tmp_path),
        health=False,
    )
    train(TrainConfig(**base))
    events = read_journal(
        str(tmp_path / "permauto_mlp" / "events.jsonl"))
    backend_events = [e for e in events if e["kind"] == "backend"]
    assert len(backend_events) == 1
    e = backend_events[0]
    assert validate_event(e) == []
    assert e["requested"] == "auto" and e["chosen"] == "dense"
    assert "reason" in e

    cfg = TrainConfig(**{**base, "name": "permforce",
                         "gossip_backend": "perm"})
    result = train(cfg)
    assert np.isfinite(result.history[-1]["loss"])
    events = read_journal(
        str(tmp_path / "permforce_mlp" / "events.jsonl"))
    e = next(ev for ev in events if ev["kind"] == "backend")
    assert e["chosen"] == "perm" and e["requested"] == "perm"

    # the production gate input: an operator feeds the roofline's
    # measured/ceiling ratio through config and auto promotes perm
    train(TrainConfig(**{**base, "name": "permgated",
                         "gossip_measured_vs_ceiling": 0.91}))
    events = read_journal(
        str(tmp_path / "permgated_mlp" / "events.jsonl"))
    e = next(ev for ev in events if ev["kind"] == "backend")
    assert e["requested"] == "auto" and e["chosen"] == "perm"
    assert e["measured_vs_ceiling"] == 0.91
    with pytest.raises(ValueError, match="gossip_measured_vs_ceiling"):
        TrainConfig(**{**base, "gossip_measured_vs_ceiling": -0.5})


def test_roofline_perm_vs_fused_extraction():
    """roofline_report prices the perm chain from extracted compiled
    costs; the compare emits the flag-stream ≪ W-stack ratio with each
    measured ratio naming its denominator backend."""
    import math

    from matcha_tpu.obs.costs import roofline_compare, roofline_report

    n = 16
    dec = tp.decompose(tp.ring_graph(n), n, seed=0)
    rep = roofline_report(n, 2048, dec, backend="perm",
                          measured_steps_per_sec=100.0)
    assert rep["backend"] == "perm"
    assert rep["measured_vs_ceiling_backend"] == "perm"
    for k in ("flops_per_step", "hbm_bytes_per_step",
              "compute_bound_steps_per_sec", "hbm_bound_steps_per_sec"):
        assert math.isfinite(rep[k]) and rep[k] > 0
    # the extracted boundary bytes match the hand model (exact: both are
    # shape arithmetic)
    assert abs(rep["hbm_vs_model"] - 1.0) < 0.05
    cmp = roofline_compare(n, 2048, dec, measured_steps_per_sec=100.0)
    assert cmp["hbm_ratio_fused_over_perm"] > 5
    assert "measured_vs_ceiling" in cmp["perm"]
    assert "measured_vs_ceiling" not in cmp["fused"]
    assert cmp["fused"]["stream_hbm_bytes_per_step"] \
        > cmp["perm"]["stream_hbm_bytes_per_step"]
