"""Golden tests: every gossip backend must equal the dense ``W_t @ X`` oracle
(SURVEY.md §4 'Golden test')."""

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from matcha_tpu import topology as tp
from matcha_tpu.parallel import (
    allreduce_mean,
    build_folded_plan,
    gossip_mix,
    shard_map_gossip_fn,
    shard_workers,
    worker_disagreement,
    worker_mesh,
)
from matcha_tpu.schedule import fixed_schedule, matcha_schedule


def dense_oracle(x, schedule, t):
    W = schedule.mixing_matrix_at(t)
    return W @ x


def random_state(n, d, seed=0):
    return np.random.default_rng(seed).normal(size=(n, d)).astype(np.float32)


@pytest.mark.parametrize("gid", [0, 2, 4, 5])
def test_gather_backend_matches_dense_oracle(gid):
    size = tp.graph_size(gid)
    sched = matcha_schedule(tp.select_graph(gid), size, iterations=20, budget=0.6, seed=4)
    x = random_state(size, 37, seed=gid)
    for t in [0, 3, 7, 19]:
        weights = sched.alpha * jnp.asarray(sched.flags[t], jnp.float32)
        got = np.asarray(gossip_mix(jnp.asarray(x), sched.perms, weights))
        want = dense_oracle(x, sched, t)
        np.testing.assert_allclose(got, want, rtol=1e-5, atol=1e-5)


def test_gather_backend_zero_flags_is_identity():
    sched = fixed_schedule(tp.select_graph(0), 8, iterations=2)
    x = jnp.asarray(random_state(8, 11))
    out = gossip_mix(x, sched.perms, jnp.zeros(5))
    np.testing.assert_array_equal(np.asarray(out), np.asarray(x))


def test_gather_backend_under_jit_and_scan():
    """Whole flag stream consumed inside one compiled scan — no host round-trips."""
    size = 8
    sched = matcha_schedule(tp.select_graph(0), size, iterations=50, budget=0.5, seed=0)
    x0 = random_state(size, 13, seed=1)
    flags = jnp.asarray(sched.flags, jnp.float32)

    @jax.jit
    def run(x, flags):
        def step(x, flags_t):
            return gossip_mix(x, sched.perms, sched.alpha * flags_t), None

        return jax.lax.scan(step, x, flags)[0]

    got = np.asarray(run(jnp.asarray(x0), flags))
    want = x0.copy()
    for t in range(50):
        want = dense_oracle(want, sched, t)
    np.testing.assert_allclose(got, want, rtol=2e-4, atol=2e-4)


# ------------------------------------------------------------- dense backend

@pytest.mark.parametrize("gid", [0, 4])
def test_dense_backend_matches_dense_oracle(gid):
    from matcha_tpu.parallel import dense_gossip_fn

    size = tp.graph_size(gid)
    sched = matcha_schedule(tp.select_graph(gid), size, iterations=10, budget=0.6, seed=7)
    fn = jax.jit(dense_gossip_fn(sched.laplacians()))
    x = random_state(size, 33, seed=gid)
    for t in [0, 4, 9]:
        weights = sched.alpha * jnp.asarray(sched.flags[t], jnp.float32)
        got = np.asarray(fn(jnp.asarray(x), weights))
        want = dense_oracle(x, sched, t)
        np.testing.assert_allclose(got, want, rtol=1e-5, atol=1e-5)


def test_dense_backend_bf16_close_to_oracle():
    from matcha_tpu.parallel import dense_gossip_fn

    sched = fixed_schedule(tp.select_graph(5), 8, iterations=2)
    fn = jax.jit(dense_gossip_fn(sched.laplacians(), compute_dtype=jnp.bfloat16))
    x = random_state(8, 64, seed=3)
    weights = sched.alpha * jnp.asarray(sched.flags[0], jnp.float32)
    got = np.asarray(fn(jnp.asarray(x), weights))
    want = dense_oracle(x, sched, 0)
    # bf16 mantissa ~8 bits; f32 accumulation keeps the error small
    np.testing.assert_allclose(got, want, rtol=2e-2, atol=2e-2)


# ------------------------------------------------------------- folded plan

def test_folded_plan_partitions_slots():
    sched = matcha_schedule(tp.select_graph(2), 16, iterations=4, budget=0.7, seed=2)
    plan = build_folded_plan(sched.perms, num_chips=8)
    assert plan.num_chips == 8 and plan.rows_per_chip == 2
    for j, parts in enumerate(plan.matchings):
        total = sum(p.mask for p in parts)
        np.testing.assert_array_equal(total, np.ones((8, 2), np.float32))


@pytest.mark.parametrize("num_chips", [1, 2, 4, 8])
def test_folded_plan_reconstructs_permutation(num_chips):
    sched = matcha_schedule(tp.select_graph(4), 16, iterations=4, budget=0.5, seed=3)
    L = 16 // num_chips
    plan = build_folded_plan(sched.perms, num_chips)
    x = random_state(16, 5)
    for j, parts in enumerate(plan.matchings):
        # emulate the gather each chip performs
        recon = np.zeros_like(x)
        blocks = x.reshape(num_chips, L, -1)
        for part in parts:
            src_blocks = np.roll(np.arange(num_chips), -part.offset)  # chip c reads chip c+d
            for c in range(num_chips):
                y = blocks[src_blocks[c]]
                recon[c * L : (c + 1) * L] += part.mask[c][:, None] * y[part.src_local[c]]
        np.testing.assert_array_equal(recon, x[sched.perms[j]])


# ------------------------------------------------- shard_map backend (8 dev)

def need_8_devices():
    return pytest.mark.skipif(
        jax.device_count() < 8, reason="needs 8 virtual devices (see conftest)"
    )


@need_8_devices()
@pytest.mark.parametrize("gid,size", [(0, 8), (5, 8), (2, 16), (3, 16)])
def test_shard_map_backend_matches_dense_oracle(gid, size):
    mesh = worker_mesh(8)
    sched = matcha_schedule(tp.select_graph(gid), size, iterations=10, budget=0.6, seed=5)
    fn = jax.jit(shard_map_gossip_fn(sched.perms, mesh))
    x = random_state(size, 29, seed=gid + 10)
    xs = shard_workers(jnp.asarray(x), mesh)
    for t in [0, 2, 9]:
        weights = sched.alpha * jnp.asarray(sched.flags[t], jnp.float32)
        got = np.asarray(fn(xs, weights))
        want = dense_oracle(x, sched, t)
        np.testing.assert_allclose(got, want, rtol=1e-5, atol=1e-5)


@need_8_devices()
def test_shard_map_backend_folded_256_workers():
    """256 virtual workers on 8 chips — 32 rows per chip."""
    mesh = worker_mesh(8)
    n = 256
    edges = tp.make_graph("geometric", n, seed=0)
    dec = tp.decompose(edges, n, seed=0)
    sched = fixed_schedule(dec, n, iterations=3)
    fn = jax.jit(shard_map_gossip_fn(sched.perms, mesh))
    x = random_state(n, 17, seed=9)
    xs = shard_workers(jnp.asarray(x), mesh)
    weights = sched.alpha * jnp.asarray(sched.flags[0], jnp.float32)
    got = np.asarray(fn(xs, weights))
    want = dense_oracle(x, sched, 0)
    np.testing.assert_allclose(got, want, rtol=1e-4, atol=1e-4)


@need_8_devices()
def test_gather_backend_agrees_with_shard_map_backend():
    mesh = worker_mesh(8)
    sched = matcha_schedule(tp.select_graph(1), 16, iterations=5, budget=0.4, seed=6)
    x = random_state(16, 23, seed=3)
    weights = sched.alpha * jnp.asarray(sched.flags[1], jnp.float32)
    a = np.asarray(gossip_mix(jnp.asarray(x), sched.perms, weights))
    fn = jax.jit(shard_map_gossip_fn(sched.perms, mesh))
    b = np.asarray(fn(shard_workers(jnp.asarray(x), mesh), weights))
    np.testing.assert_allclose(a, b, rtol=1e-5, atol=1e-6)


# ------------------------------------------------------------- collectives

def test_allreduce_mean_and_disagreement():
    x = random_state(8, 10)
    out = np.asarray(allreduce_mean(jnp.asarray(x)))
    np.testing.assert_allclose(out, np.tile(x.mean(0, keepdims=True), (8, 1)), rtol=1e-6)
    assert float(worker_disagreement(jnp.asarray(out))) < 1e-6
    assert float(worker_disagreement(jnp.asarray(x))) > 0.5


def test_gossip_contracts_disagreement():
    """Consensus-only integration test (SURVEY.md §4): repeated gossip must
    contract disagreement at (better than) the rho bound."""
    sched = matcha_schedule(tp.select_graph(0), 8, iterations=300, budget=0.5, seed=8)
    x = jnp.asarray(random_state(8, 40, seed=2))
    d0 = float(worker_disagreement(x))

    def step(x, flags_t):
        return gossip_mix(x, sched.perms, sched.alpha * flags_t), None

    xT = jax.lax.scan(step, x, jnp.asarray(sched.flags, jnp.float32))[0]
    dT = float(worker_disagreement(xT))
    assert dT < d0 * 1e-3, (d0, dT)
    # and the mean is preserved (doubly stochastic mixing)
    np.testing.assert_allclose(
        np.asarray(x).mean(0), np.asarray(xT).mean(0), rtol=1e-4, atol=1e-5
    )


def test_dense_backend_feature_sharded_parity():
    """The README/DESIGN scaling claim for the dense path: with the
    worker state sharded along the *feature* axis, the N×N mixing matmul is
    chip-local (each chip mixes its own D-slice; zero collectives needed for
    gossip itself).  Run the dense backend under jit with x sharded over 8
    devices on axis 1 and require bit-parity with the unsharded result."""
    from jax.sharding import Mesh, NamedSharding, PartitionSpec as P

    from matcha_tpu.communicator import make_decen

    sched = matcha_schedule(tp.select_graph(0), 8, iterations=10, budget=0.5, seed=3)
    x = jnp.asarray(random_state(8, 64, seed=11))
    comm = make_decen(sched, backend="dense")
    want, _ = jax.jit(comm.run)(x, sched.flags)

    mesh = Mesh(np.array(jax.devices()[:8]), ("features",))
    xs = jax.device_put(x, NamedSharding(mesh, P(None, "features")))
    got, _ = jax.jit(comm.run)(xs, sched.flags)
    # partitioned compilation may re-associate fusions, so tight allclose
    # rather than bitwise equality
    np.testing.assert_allclose(np.asarray(want), np.asarray(got),
                               rtol=1e-6, atol=1e-6)


def test_shard_workers_replicates_key_leaves_and_rejects_bad_folds():
    """PRNG-key leaves (a stochastic compressor's carried state, recognized
    by dtype/shape rather than pytree name) replicate; worker rows shard —
    including a float tensor that merely *sits under* a key named "key"
    (flax attention modules do); a leading dim that cannot fold over the
    axis stays a loud error, not a silent re-placement."""
    if jax.device_count() < 8:
        pytest.skip("needs 8 devices")
    mesh = worker_mesh(8)
    state = {"x": jnp.zeros((8, 4)), "key": jax.random.PRNGKey(0),
             "attn": {"key": {"kernel": jnp.zeros((8, 4))}}}
    out = shard_workers(state, mesh)
    assert out["key"].sharding.is_fully_replicated
    assert not out["x"].sharding.is_fully_replicated
    assert not out["attn"]["key"]["kernel"].sharding.is_fully_replicated
    with pytest.raises(ValueError):
        shard_workers({"x": jnp.zeros((3, 4))}, mesh)


@pytest.mark.parametrize("gid", [0, 2, 5])
def test_skip_backend_matches_dense_oracle(gid):
    """The cond-skipping form must compute exactly what masking computes —
    only the runtime cost of inactive matchings differs."""
    from matcha_tpu.parallel import gossip_mix_skip

    size = tp.graph_size(gid)
    sched = matcha_schedule(tp.select_graph(gid), size, iterations=20,
                            budget=0.4, seed=4)
    x = random_state(size, 37, seed=gid)
    for t in [0, 3, 7, 19]:
        weights = sched.alpha * jnp.asarray(sched.flags[t], jnp.float32)
        got = np.asarray(jax.jit(
            lambda xx, w: gossip_mix_skip(xx, sched.perms, w)
        )(jnp.asarray(x), weights))
        want = dense_oracle(x, sched, t)
        np.testing.assert_allclose(got, want, rtol=1e-5, atol=1e-5)


def test_skip_backend_zero_flags_is_identity_and_scans():
    from matcha_tpu.communicator import make_decen
    from matcha_tpu.parallel import gossip_mix_skip

    sched = fixed_schedule(tp.select_graph(0), 8, iterations=3,
                           mode="bernoulli", budget=0.0)
    x = jnp.asarray(random_state(8, 11))
    out = gossip_mix_skip(x, sched.perms, jnp.zeros(sched.perms.shape[0]))
    np.testing.assert_array_equal(np.asarray(out), np.asarray(x))
    # whole varying-flag stream through the communicator under jit+scan
    sched2 = matcha_schedule(tp.select_graph(0), 8, iterations=30,
                             budget=0.5, seed=2)
    comm_skip = make_decen(sched2, backend="skip")
    comm_mask = make_decen(sched2, backend="gather")
    x0 = jnp.asarray(random_state(8, 13, seed=3))
    a, _ = jax.jit(comm_skip.run)(x0, sched2.flags)
    b, _ = jax.jit(comm_mask.run)(x0, sched2.flags)
    np.testing.assert_allclose(np.asarray(a), np.asarray(b), rtol=1e-5, atol=1e-6)


def test_skip_backend_shard_map_matches_masked():
    """skip=True on the folded shard_map plan (collectives inside lax.cond)
    must equal the masked folded plan on the same varying-flag stream —
    64 workers folded onto 8 chips, including all-inactive steps."""
    if jax.device_count() < 8:
        pytest.skip("needs 8 devices")
    from matcha_tpu.communicator import make_decen

    mesh = worker_mesh(8)
    n = 64
    sched = matcha_schedule(tp.decompose(tp.make_graph("geometric", n, seed=3),
                                         n, seed=0),
                            n, iterations=12, budget=0.3, seed=5)
    # force one all-inactive step so the fully-skipped path is exercised too
    flags = np.asarray(sched.flags).copy()
    flags[5] = 0
    x0 = jnp.asarray(random_state(n, 9, seed=7))
    xs = shard_workers(x0, mesh)
    a, _ = jax.jit(make_decen(sched, mesh=mesh, backend="skip").run)(xs, flags)
    b, _ = jax.jit(make_decen(sched, mesh=mesh, backend="shard_map").run)(
        xs, flags)
    np.testing.assert_allclose(np.asarray(a), np.asarray(b),
                               rtol=1e-5, atol=1e-6)


def test_choco_skip_backend_is_a_named_error():
    from matcha_tpu.communicator import select_communicator

    sched = fixed_schedule(tp.select_graph(5), 8, iterations=2)
    with pytest.raises(ValueError, match="skip"):
        select_communicator("choco", sched, backend="skip")


def test_skip_backend_negative_weights_match_masking():
    """The cond predicate is ``weight != 0`` (not ``> 0``): a hypothetical
    negative mixing weight must take the exchange branch exactly like the
    masked backends apply it (ADVICE r2)."""
    from matcha_tpu.parallel import gossip_mix_skip

    sched = fixed_schedule(tp.select_graph(5), 8, iterations=2)
    x = jnp.asarray(random_state(8, 17, seed=9))
    weights = jnp.asarray([-0.3, 0.0])  # negative active, zero inactive
    got = jax.jit(lambda xx, w: gossip_mix_skip(xx, sched.perms, w))(x, weights)
    want = gossip_mix(x, sched.perms, weights)
    np.testing.assert_allclose(np.asarray(got), np.asarray(want),
                               rtol=1e-6, atol=1e-6)


def test_shard_workers_warns_on_ambiguous_uint32_pair_axis2():
    """On a 2-wide worker axis a raw ``uint32[2]`` leaf is ambiguous (key vs
    per-worker rows); the heuristic must fire loudly, not silently (ADVICE
    r2).  Typed keys stay silent on any axis."""
    import warnings

    if jax.device_count() < 2:
        pytest.skip("needs 2 devices")
    mesh2 = worker_mesh(2)
    raw = {"leaf": jnp.zeros((2,), jnp.uint32)}
    with pytest.warns(UserWarning, match="ambiguous"):
        out = shard_workers(raw, mesh2)
    assert out["leaf"].sharding.is_fully_replicated
    with warnings.catch_warnings():
        warnings.simplefilter("error")
        out = shard_workers({"k": jax.random.key(0)}, mesh2)
    assert out["k"].sharding.is_fully_replicated


def test_mxu_precision_contract():
    """f32 compute must request HIGHEST (TPU DEFAULT degrades f32 matmuls to
    one bf16 MXU pass — the r4 on-device gate caught a 4e-2 drift from the
    exact gather path); bf16 keeps DEFAULT, the native MXU input precision
    the perf path is specified in (gossip.py mxu_precision)."""
    from matcha_tpu.parallel.gossip import mxu_precision

    assert mxu_precision(jnp.float32) == jax.lax.Precision.HIGHEST
    assert mxu_precision(jnp.float64) == jax.lax.Precision.HIGHEST
    assert mxu_precision(jnp.bfloat16) == jax.lax.Precision.DEFAULT
    assert mxu_precision(jnp.float16) == jax.lax.Precision.DEFAULT
