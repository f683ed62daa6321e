"""Communicator golden tests against independent numpy simulations of the
reference per-rank semantics (communicator.py:79-268)."""

import json

import jax
import jax.numpy as jnp
import numpy as np
import pytest

import chip_smoke
from matcha_tpu import topology as tp
from matcha_tpu.communicator import (
    make_centralized,
    make_choco,
    make_decen,
    make_none,
    select_communicator,
)
from matcha_tpu.ops import top_k_ratio_size
from matcha_tpu.schedule import fixed_schedule, matcha_schedule
from matcha_tpu.parallel import worker_mesh, shard_workers


def random_state(n, d, seed=0):
    return np.random.default_rng(seed).normal(size=(n, d)).astype(np.float32)


# ------------------------------------------------------------------- decen

def numpy_decen_reference(x0, sched, T):
    """Per-rank mirror of decenCommunicator.averaging (communicator.py:92-122)."""
    x = x0.astype(np.float64).copy()
    nbrs = sched.neighbors_info
    alpha = sched.alpha
    for t in range(T):
        flags = sched.flags[t]
        if flags.sum() == 0:
            continue
        new = np.zeros_like(x)
        for i in range(x.shape[0]):
            deg = 0
            for j, f in enumerate(flags):
                if f and nbrs[j][i] != -1:
                    deg += 1
                    new[i] += alpha * x[nbrs[j][i]]
            new[i] += (1 - deg * alpha) * x[i]
        x = new
    return x


@pytest.mark.parametrize("gid", [0, 5])
def test_decen_matches_reference_simulation(gid):
    size = tp.graph_size(gid)
    sched = matcha_schedule(tp.select_graph(gid), size, iterations=30, budget=0.5, seed=3)
    comm = make_decen(sched)
    x0 = random_state(size, 25, seed=gid)
    got, _ = jax.jit(comm.run)(jnp.asarray(x0), sched.flags)
    want = numpy_decen_reference(x0, sched, 30)
    np.testing.assert_allclose(np.asarray(got), want, rtol=2e-4, atol=2e-5)


def test_decen_skip_iterations_are_identity():
    sched = fixed_schedule(tp.select_graph(5), 8, iterations=4, mode="bernoulli", budget=0.0)
    assert sched.flags.sum() == 0
    comm = make_decen(sched)
    x0 = jnp.asarray(random_state(8, 7))
    got, _ = comm.run(x0, sched.flags)
    np.testing.assert_array_equal(np.asarray(got), np.asarray(x0))


@pytest.mark.parametrize("compute", [jnp.float32, jnp.bfloat16],
                         ids=["f32", "bf16"])
@pytest.mark.parametrize("form", ["streamed", "mxu"])
def test_dense_chain_matches_gather_oracle(form, compute):
    """The chain the comm-split timer runs (``Communicator.run`` over
    ``dense``, a scan of its step) against the per-matching ``gather``
    chain, in both forms of the dense exchange.  A ``compute_dtype`` below
    float32 is the bf16 wire by another name: the same program, and within
    the wire's budget of a step (2^-8 of the largest value) of the oracle
    that rounds what it exchanges."""
    from gossip_cases import MXU_SCHED  # beside this file

    from matcha_tpu.parallel import dense_exchange_form

    sched = MXU_SCHED if form == "mxu" else matcha_schedule(
        tp.select_graph(0), 8, iterations=12, budget=0.6, seed=0)
    n = sched.num_workers
    assert dense_exchange_form(n)["form"] == form
    wire = None if compute == jnp.float32 else "bf16"
    x = jnp.asarray(random_state(n, 40, seed=n))
    flags = jnp.asarray(sched.flags, jnp.float32)
    dense = make_decen(sched, backend="dense", compute_dtype=compute)
    assert dense.multi_step is None  # the chain is the scan of the step
    got, _ = jax.jit(dense.run)(x, flags)
    want, _ = jax.jit(make_decen(sched, backend="gather",
                                 wire_dtype=wire).run)(x, flags)
    assert got.dtype == x.dtype
    assert float(jnp.max(jnp.abs(want - x))) > 0.1  # the stream mixed
    if wire is None:
        np.testing.assert_allclose(np.asarray(got), np.asarray(want),
                                   rtol=1e-5, atol=1e-6)
        return
    budget = len(flags) * 2.0 ** -8 * float(jnp.max(jnp.abs(want)))
    np.testing.assert_allclose(np.asarray(got), np.asarray(want), rtol=0,
                               atol=budget)
    by_wire, _ = jax.jit(make_decen(sched, backend="dense",
                                    wire_dtype=wire).run)(x, flags)
    np.testing.assert_array_equal(np.asarray(got), np.asarray(by_wire))


@pytest.mark.parametrize("n,wire", chip_smoke.KERNEL_CASES)
def test_chip_smoke_kernel_phase_rehearsed_on_the_cpu(n, wire, monkeypatch,
                                                      capsys):
    """``chip_smoke.py``'s kernel phase itself, a case at a time at a tiny
    width: the exchange ``train()`` runs against the ``gather`` oracle, one
    worker count on each side of the crossover, the oracle over three
    slabs of columns with a ragged last one."""
    from matcha_tpu.parallel import STREAM_MAX_WORKERS

    sizes = {size for size, _ in chip_smoke.KERNEL_CASES}
    assert min(sizes) <= STREAM_MAX_WORKERS < max(sizes)
    monkeypatch.setattr(chip_smoke, "ORACLE_COLS", 48)
    [row] = chip_smoke.kernel_phase(100, [(n, wire)])
    assert (row["kernel"], row["oracle"]) == (
        "streamed" if n <= STREAM_MAX_WORKERS else "mxu", "gather")
    assert (row["n"], row["dim"], row["wire"]) == (n, 100, wire)
    assert row["rel_err"] <= row["tol"]
    assert f"# kernel {json.dumps(row)}" in capsys.readouterr().out


def test_decen_shard_map_backend_parity():
    if jax.device_count() < 8:
        pytest.skip("needs 8 devices")
    mesh = worker_mesh(8)
    sched = matcha_schedule(tp.select_graph(2), 16, iterations=12, budget=0.5, seed=1)
    x0 = random_state(16, 19, seed=4)
    a, _ = make_decen(sched).run(jnp.asarray(x0), sched.flags)
    comm = make_decen(sched, mesh=mesh, backend="shard_map")
    xs = shard_workers(jnp.asarray(x0), mesh)
    b, _ = jax.jit(comm.run)(xs, sched.flags)
    np.testing.assert_allclose(np.asarray(a), np.asarray(b), rtol=1e-5, atol=1e-6)


# ------------------------------------------------------------------- choco

def numpy_choco_reference(x0, sched, ratio, gamma, T):
    """Per-rank mirror of ChocoCommunicator (communicator.py:161-268)."""
    x = x0.astype(np.float64).copy()
    N, D = x.shape
    x_hat = np.zeros_like(x)
    s = np.zeros_like(x)
    k = top_k_ratio_size(D, ratio)
    nbrs = sched.neighbors_info
    alpha = sched.alpha
    for t in range(T):
        flags = sched.flags[t]
        if flags.sum() == 0:
            continue  # reference early-return: nothing mutates
        q = x - x_hat
        idxs = [np.argsort(-np.abs(q[i]), kind="stable")[:k] for i in range(N)]
        vals = [q[i][idxs[i]] for i in range(N)]
        for i in range(N):
            deg = 0
            for j, f in enumerate(flags):
                if f and nbrs[j][i] != -1:
                    deg += 1
                    p = nbrs[j][i]
                    np.add.at(s[i], idxs[p], alpha * vals[p])
            np.add.at(s[i], idxs[i], (1 - deg * alpha) * vals[i])
            np.add.at(x_hat[i], idxs[i], vals[i])
            x[i] += gamma * (s[i] - x_hat[i])
    return x


@pytest.mark.parametrize("ratio", [0.0, 0.5, 0.9])
def test_choco_matches_reference_simulation(ratio):
    size = 8
    sched = matcha_schedule(tp.select_graph(0), size, iterations=15, budget=0.5, seed=7)
    comm = make_choco(sched, ratio=ratio, consensus_lr=0.3)
    x0 = random_state(size, 21, seed=5)
    got, carry = jax.jit(comm.run)(jnp.asarray(x0), sched.flags)
    want = numpy_choco_reference(x0, sched, ratio, 0.3, 15)
    np.testing.assert_allclose(np.asarray(got), want, rtol=3e-4, atol=3e-5)
    assert set(carry) == {"x_hat", "s"}


def test_choco_keep_all_gamma1_equals_decen():
    """CHOCO with no compression and consensus_lr=1 is exactly D-PSGD —
    *provided the mixing matrix is constant across steps* (with varying W_t
    the telescoped s accumulator picks up (W_t−W_{t'}) cross terms; the
    SURVEY.md §4 equivalence needs both γ=1 and a fixed schedule)."""
    size = 8
    sched = fixed_schedule(tp.select_graph(5), size, iterations=20)
    x0 = random_state(size, 15, seed=9)
    a, _ = make_decen(sched).run(jnp.asarray(x0), sched.flags)
    b, _ = make_choco(sched, ratio=0.0, consensus_lr=1.0).run(jnp.asarray(x0), sched.flags)
    np.testing.assert_allclose(np.asarray(a), np.asarray(b), rtol=1e-4, atol=1e-5)


def test_choco_shard_map_backend_parity():
    """Folded shard_map CHOCO must be bit-compatible with the batched form
    (VERDICT r1 W3): same schedule, same state, per-step parity on an
    8-device mesh (one worker per chip)."""
    if jax.device_count() < 8:
        pytest.skip("needs 8 devices")
    mesh = worker_mesh(8)
    sched = matcha_schedule(tp.select_graph(0), 8, iterations=12, budget=0.5, seed=7)
    x0 = random_state(8, 21, seed=6)
    a, ca = make_choco(sched, ratio=0.7, consensus_lr=0.3).run(
        jnp.asarray(x0), sched.flags)
    comm = make_choco(sched, ratio=0.7, consensus_lr=0.3, mesh=mesh,
                      backend="shard_map")
    assert comm.multi_step is not None
    xs = shard_workers(jnp.asarray(x0), mesh)
    b, cb = jax.jit(comm.run)(xs, sched.flags)
    np.testing.assert_allclose(np.asarray(a), np.asarray(b), rtol=1e-5, atol=1e-6)
    np.testing.assert_allclose(
        np.asarray(ca["s"]), np.asarray(cb["s"]), rtol=1e-5, atol=1e-6)
    np.testing.assert_allclose(
        np.asarray(ca["x_hat"]), np.asarray(cb["x_hat"]), rtol=1e-5, atol=1e-6)


def test_choco_shard_map_folded_64_workers():
    """BASELINE config 4 shape in miniature: 64 virtual workers folded onto
    8 chips (L=8 rows per chip), golden-tested against the numpy per-rank
    simulation of the reference (communicator.py:161-268)."""
    if jax.device_count() < 8:
        pytest.skip("needs 8 devices")
    mesh = worker_mesh(8)
    n = 64
    edges = tp.make_graph("ring", n)
    sched = matcha_schedule(tp.decompose(edges, n, seed=0), n,
                            iterations=10, budget=0.75, seed=2)
    x0 = random_state(n, 13, seed=8)
    comm = make_choco(sched, ratio=0.5, consensus_lr=0.4, mesh=mesh,
                      backend="shard_map")
    xs = shard_workers(jnp.asarray(x0), mesh)
    got, _ = jax.jit(comm.run)(xs, sched.flags)
    want = numpy_choco_reference(x0, sched, 0.5, 0.4, 10)
    np.testing.assert_allclose(np.asarray(got), want, rtol=3e-4, atol=3e-5)


def test_choco_skip_iterations_freeze_all_state():
    sched = fixed_schedule(tp.select_graph(5), 8, iterations=3, mode="bernoulli", budget=0.0)
    comm = make_choco(sched, ratio=0.5)
    x0 = jnp.asarray(random_state(8, 9))
    carry0 = comm.init(x0)
    got, carry = comm.run(x0, sched.flags, carry0)
    np.testing.assert_array_equal(np.asarray(got), np.asarray(x0))
    np.testing.assert_array_equal(np.asarray(carry["x_hat"]), 0)
    np.testing.assert_array_equal(np.asarray(carry["s"]), 0)


def test_choco_contracts_disagreement():
    from matcha_tpu.parallel import worker_disagreement

    sched = fixed_schedule(tp.select_graph(5), 8, iterations=400)
    comm = make_choco(sched, ratio=0.7, consensus_lr=0.3)
    x0 = jnp.asarray(random_state(8, 30, seed=1))
    xT, _ = jax.jit(comm.run)(x0, sched.flags)
    assert float(worker_disagreement(xT)) < 0.05 * float(worker_disagreement(x0))


@pytest.mark.parametrize("compressor", ["random_k", "top_k_q8"])
def test_choco_stochastic_compressors_contract(compressor):
    """The registry compressors behind the reference's reserved extension
    point (communicator.py:186-187): CHOCO must still drive consensus with a
    random-k sparsifier and with 8-bit stochastically-quantized top-k.  The
    PRNG key rides in the carry, so the chain stays one compiled program and
    a rerun from the same seed is bit-identical."""
    from matcha_tpu.parallel import worker_disagreement

    sched = fixed_schedule(tp.select_graph(5), 8, iterations=400)
    comm = make_choco(sched, ratio=0.7, consensus_lr=0.3,
                      compressor=compressor, seed=5)
    x0 = jnp.asarray(random_state(8, 30, seed=1))
    carry0 = comm.init(x0)
    assert "key" in carry0  # stochastic ⇒ key is part of the carried state
    xT, carry = jax.jit(comm.run)(x0, sched.flags)
    assert float(worker_disagreement(xT)) < 0.1 * float(worker_disagreement(x0))
    assert not np.array_equal(np.asarray(carry["key"]), np.asarray(carry0["key"]))
    xT2, _ = jax.jit(comm.run)(x0, sched.flags)
    np.testing.assert_array_equal(np.asarray(xT), np.asarray(xT2))


def test_choco_stochastic_shard_map_contracts():
    """Stochastic compressor through the folded shard_map backend: per-chip
    fold-in keys draw different streams than the batched form (documented in
    make_choco), so this asserts consensus behavior, not cross-backend bit
    parity.  Within the backend, multi_step must equal scanning step (the
    Communicator contract): same key schedule, bit-identical state."""
    if jax.device_count() < 8:
        pytest.skip("needs 8 devices")
    from matcha_tpu.parallel import worker_disagreement

    mesh = worker_mesh(8)
    n = 16
    sched = fixed_schedule(tp.decompose(tp.make_graph("ring", n), n, seed=0),
                           n, iterations=300)
    comm = make_choco(sched, ratio=0.5, consensus_lr=0.3, mesh=mesh,
                      backend="shard_map", compressor="random_k", seed=3)
    assert comm.multi_step is not None
    x0 = jnp.asarray(random_state(n, 13, seed=2))
    xs = shard_workers(x0, mesh)
    xT, carry = jax.jit(comm.run)(xs, sched.flags)
    assert float(worker_disagreement(xT)) < 0.1 * float(worker_disagreement(x0))
    assert "key" in carry

    # multi_step (one shard_map scan) ≡ per-step driving: the key schedule is
    # bit-identical (same split-per-step recurrence), the state agrees up to
    # f32 reassociation between the one-scan and per-step compiled programs.
    # The per-step driver is jitted ONCE and reused — driving comm.step
    # eagerly re-traced the shard_map program on every call and was the
    # single most expensive line in tier-1 (~140 s for 8 steps vs ~2 s
    # compiled; ISSUE 6 wall-clock audit), without asserting anything more.
    flags8 = sched.flags[:8]
    a, ca = comm.multi_step(xs, comm.init(xs), jnp.asarray(flags8, jnp.float32))
    step_j = jax.jit(comm.step)
    b, cb = xs, comm.init(xs)
    for t in range(8):
        b, cb = step_j(b, cb, jnp.asarray(flags8[t], jnp.float32))
    np.testing.assert_array_equal(np.asarray(ca["key"]), np.asarray(cb["key"]))
    np.testing.assert_allclose(np.asarray(a), np.asarray(b), rtol=1e-5, atol=1e-6)
    np.testing.assert_allclose(np.asarray(ca["s"]), np.asarray(cb["s"]),
                               rtol=1e-5, atol=1e-6)


# ------------------------------------------------- centralized / none / registry

def test_centralized_is_row_mean():
    comm = make_centralized()
    x0 = random_state(8, 12)
    got, _ = comm.run(jnp.asarray(x0), np.ones((1, 1)))
    np.testing.assert_allclose(
        np.asarray(got), np.tile(x0.mean(0, keepdims=True), (8, 1)), rtol=1e-5
    )


def test_none_is_identity():
    comm = make_none()
    x0 = jnp.asarray(random_state(8, 6))
    got, _ = comm.run(x0, np.ones((5, 2)))
    np.testing.assert_array_equal(np.asarray(got), np.asarray(x0))


def test_registry():
    sched = fixed_schedule(tp.select_graph(5), 8, iterations=2)
    assert select_communicator("decen", sched).name.startswith("decen")
    assert select_communicator("choco", sched).name.startswith("choco")
    if jax.device_count() >= 8:
        # the training path must reach the sharded choco backend (and map the
        # gossip-backend vocabulary onto choco's batched form)
        mesh = worker_mesh(8)
        assert "shard_map" in select_communicator("choco", sched, mesh=mesh).name
        assert "shard_map" not in select_communicator(
            "choco", sched, mesh=mesh, backend="dense").name
    assert select_communicator("centralized").name == "centralized"
    assert select_communicator("none").name == "none"
    with pytest.raises(KeyError):
        select_communicator("quantum")


def test_select_communicator_plumbs_compressor_seed():
    """--randomSeed must reach the stochastic compressor's PRNG carry: same
    seed reproduces the chain bit-for-bit, different seeds draw different
    sample paths."""
    from matcha_tpu.communicator import select_communicator

    sched = fixed_schedule(tp.select_graph(5), 8, iterations=40)
    x0 = jnp.asarray(random_state(8, 17, seed=4))

    def run(seed):
        comm = select_communicator("choco", sched, compressor="random_k",
                                   ratio=0.5, seed=seed)
        xT, _ = comm.run(x0, sched.flags)
        return np.asarray(xT)

    a, b, c = run(1), run(1), run(2)
    np.testing.assert_array_equal(a, b)
    assert not np.array_equal(a, c)


def test_gather_backend_warns_at_large_n():
    """'gather' at N>=64 is a shipped footgun (~60x slower than dense at
    N=256) — selecting it must warn loudly; small N and the fast backends
    stay silent (VERDICT r2 item 5)."""
    import warnings

    from matcha_tpu import topology as tp
    from matcha_tpu.schedule import fixed_schedule

    n = 64
    dec = tp.decompose(tp.make_graph("ring", n), n, seed=0)
    sched = fixed_schedule(dec, n, iterations=2)
    with pytest.warns(UserWarning, match="gather"):
        make_decen(sched, backend="gather")
    small = fixed_schedule(tp.decompose(tp.make_graph("ring", 8), 8, seed=0),
                           8, iterations=2)
    with warnings.catch_warnings():
        warnings.simplefilter("error")
        make_decen(small, backend="gather")
        make_decen(sched, backend="dense")


def _refused_config(backend):
    from matcha_tpu.train import TrainConfig

    return lambda: TrainConfig(gossip_backend=backend)


def _refused_make_decen(**kwargs):
    return lambda: make_decen(
        fixed_schedule(tp.select_graph(5), 8, iterations=2), **kwargs)


def _refused_select_communicator(**kwargs):
    return lambda: select_communicator(
        "decen", fixed_schedule(tp.select_graph(5), 8, iterations=2),
        **kwargs)


def _refused_cli(*argv):
    import train_tpu

    return lambda: train_tpu.parse_args(list(argv))


def _refused_obs_cli(*argv):
    import obs_tpu

    return lambda: obs_tpu.main(list(argv))


def _refused_elision_costs():
    from matcha_tpu.obs.costs import elision_epoch_costs

    elision_epoch_costs(8, 64, tp.select_graph(5), backend="fused")


#: the five names an unknown backend is told there are
_FIVE = r"\['auto', 'dense', 'gather', 'skip', 'shard_map'\]"


@pytest.mark.parametrize("call,error,names", [
    (_refused_config("perm"), ValueError, "perm.*" + _FIVE),
    (_refused_make_decen(backend="perm"), KeyError, "perm.*" + _FIVE),
    (_refused_cli("--backend", "perm"), ValueError, "perm.*" + _FIVE),
    # argparse refuses an option it does not know with exit status 2
    (_refused_cli("--gossip-measured-ratio", "0.9"), SystemExit, "2"),
    (_refused_cli("--gossip-measured-vs-ceiling", "0.9"), SystemExit, "2"),
    (_refused_cli("--gossip-measured-source", "x.json"), SystemExit, "2"),
    (_refused_cli("--block-d", "4096"), SystemExit, "2"),
    (_refused_cli("--w-window", "4"), SystemExit, "2"),
    (_refused_config("fused"), ValueError, "fused.*" + _FIVE),
    (_refused_make_decen(backend="fused"), KeyError, "fused.*" + _FIVE),
    (_refused_cli("--backend", "fused"), ValueError, "fused.*" + _FIVE),
    (_refused_make_decen(chunk=2), TypeError, "chunk"),
    (_refused_make_decen(block_d=4096), TypeError, "block_d"),
    (_refused_make_decen(w_window=4), TypeError, "w_window"),
    (_refused_select_communicator(block_d=4096), TypeError, "block_d"),
    (_refused_obs_cli("roofline", "--backend", "fused"), SystemExit, "2"),
    (_refused_obs_cli("roofline", "--measured-backend", "dense"),
     SystemExit, "2"),
    (_refused_elision_costs, ValueError, r"fused.*\(dense\|skip\)"),
], ids=["TrainConfig", "make_decen", "cli-backend", "cli-measured-ratio",
        "cli-measured-vs-ceiling", "cli-measured-source", "cli-block-d",
        "cli-w-window", "TrainConfig-fused", "make_decen-fused",
        "cli-backend-fused", "make_decen-chunk", "make_decen-block_d",
        "make_decen-w_window", "select_communicator-block_d",
        "obs-roofline-backend", "obs-roofline-measured-backend",
        "elision-costs-fused"])
def test_what_pr29_and_pr45_removed_is_refused_by_name(call, error, names):
    """The permutation-form backend, the measurement its gate asked the
    user for and the two kernel-tuning flags are gone (PR 29), and so are
    the ``fused`` backend, its three arguments, its cost model and the
    roofline's two options that chose a backend (PR 45): each is refused
    where an unknown name is refused, and an unknown backend is told which
    five there are."""
    with pytest.raises(error, match=names):
        call()


def test_choco_approx_topk_contracts():
    """CHOCO with the TPU-native approximate top-k (``top_k_approx``): the
    compressor is deterministic (no PRNG carry needed) and still a
    δ-contraction, so consensus must contract exactly like exact top-k's
    path — the registry entry exists for the TPU encode-cost regime
    (lax.approx_max_k's PartialReduce lowering vs full-sort lax.top_k)."""
    from matcha_tpu.parallel import worker_disagreement

    sched = fixed_schedule(tp.select_graph(5), 8, iterations=400)
    comm = make_choco(sched, ratio=0.7, consensus_lr=0.3,
                      compressor="top_k_approx")
    x0 = jnp.asarray(random_state(8, 30, seed=1))
    xT, _ = jax.jit(comm.run)(x0, sched.flags)
    assert float(worker_disagreement(xT)) < 0.05 * float(worker_disagreement(x0))
    # deterministic: rerun is bit-identical
    xT2, _ = jax.jit(comm.run)(x0, sched.flags)
    np.testing.assert_array_equal(np.asarray(xT), np.asarray(xT2))
