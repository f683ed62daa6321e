"""The train step under the packed forward/backward's plan (PERF.md section
6, PR 30; the form itself and the plan are ``tests/test_packed_fwd_bwd.py``).
Here, on the CPU at ``highest``: every condition that needs workers isolated
(or gains nothing) keeps the per-worker program to the byte, the packed step
is another program with the same result, a non-finite worker stays alone
where a fault plan is compiled in, and ``train()`` journals which ran.
"""

import hashlib

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from matcha_tpu import topology as tp
from matcha_tpu.communicator import make_decen
from matcha_tpu.models import ResNet
from matcha_tpu.ops import WorkerFlattener
from matcha_tpu.resilience import FaultEvent, FaultPlan
from matcha_tpu.schedule import fixed_schedule
from matcha_tpu.train import TrainConfig, make_lr_schedule, train
from matcha_tpu.train.state import (
    init_train_state,
    make_optimizer,
    make_train_step,
)
from test_packed_fwd_bwd import (  # beside this file
    IMAGE,
    PerWorkerResNet,
    _assert_trees_close,
    _batch,
    _highest,  # noqa: F401  (autouse here too)
)


# ------------------------------------------------------------------ the step

def _step(model, n, grad_chunk=None, faults=None, shapes_only=False, **kwargs):
    sched = fixed_schedule(tp.decompose(tp.ring_graph(n), n, seed=0), n,
                           iterations=4)
    comm = make_decen(sched, backend="dense")
    lr = make_lr_schedule(0.05, 2, warmup=False)
    optimizer = make_optimizer(lr)
    # jitted: eagerly, every leaf's draw is a program of its own
    init = lambda: init_train_state(model, IMAGE, n, optimizer, comm, seed=0)[0]
    state = jax.eval_shape(init) if shapes_only else jax.jit(init)()
    flattener = WorkerFlattener(state.params)
    if faults is not None:
        faults = faults.compile(sched.iterations, n, sched.num_matchings)
    step = make_train_step(model, optimizer, comm, flattener, sched.flags,
                           lr_schedule=lr, grad_chunk=grad_chunk,
                           faults=faults, **kwargs)
    return step, state


def _lowered_sha(model, n, grad_chunk=None, rng=False, **kwargs):
    step, state = _step(model, n, grad_chunk, shapes_only=True, **kwargs)
    x, y = _batch(n, 2)
    args = (state, x, y) + ((jax.random.PRNGKey(0),) if rng else ())
    return hashlib.sha256(step.lower(*args).as_text().encode()).hexdigest()


@pytest.mark.parametrize("case,n,chunk,model_kwargs,kwargs", [
    ("no P divides the slab", 11, None, {}, {}),
    ("slabs of one", 4, 1, {}, {}),
    ("remat", 4, None, {"remat": True}, {}),
    ("dropout", 4, None, {}, {"dropout": True}),
    ("fault plan", 4, None, {}, {"faults": FaultPlan(events=())}),
])
def test_fallback_lowers_to_the_per_worker_program(case, n, chunk,
                                                   model_kwargs, kwargs):
    """Where the plan says no, the step is the program of a model that has
    no packed form at all: the same lowered text."""
    ours = _lowered_sha(ResNet(depth=8, **model_kwargs), n, chunk,
                        rng="dropout" in kwargs, **kwargs)
    plain = _lowered_sha(PerWorkerResNet(depth=8, **model_kwargs), n, chunk,
                         rng="dropout" in kwargs, **kwargs)
    assert ours == plain, case


def test_packed_step_is_another_program_with_the_same_result():
    """Through ``make_train_step`` with slabs (8 workers, ``grad_chunk`` 4:
    two slabs of one pack of 4): the lowered text differs from the
    per-worker program's, and one step lands on the same state."""
    n, chunk = 8, 4
    x, y = _batch(n, 2)
    out = {}
    for name, model in (("packed", ResNet(depth=8)),
                        ("per_worker", PerWorkerResNet(depth=8))):
        step, state = _step(model, n, chunk)
        text = step.lower(state, x, y).as_text()
        new_state, metrics = step(state, x, y)
        out[name] = (text, new_state, metrics)
    assert out["packed"][0] != out["per_worker"][0]
    (_, got, got_m), (_, want, want_m) = out["packed"], out["per_worker"]
    _assert_trees_close(got.params, want.params, 1e-5)
    _assert_trees_close(got.batch_stats, want.batch_stats, 1e-5)
    _assert_trees_close(got.opt_state, want.opt_state, 5e-4)
    for key in ("loss", "accuracy", "disagreement"):
        np.testing.assert_allclose(float(got_m[key]), float(want_m[key]),
                                   rtol=1e-5, atol=1e-7)


# ----------------------------------------------------------------- isolation

@pytest.mark.faults
def test_with_a_fault_plan_a_non_finite_worker_stays_alone():
    """A fault plan compiled in: the step keeps ``vmap`` over workers, so a
    worker whose batch is NaN poisons itself alone; it is quarantined and
    healed, and every other worker's parameters stay finite and its own."""
    n = 4
    model = ResNet(depth=8)
    plan = FaultPlan(events=(FaultEvent(kind="dead", worker=3, start=2,
                                        stop=3),))
    step, state = _step(model, n, faults=plan)
    x, y = _batch(n, 2)
    new_state, metrics = step(state, x.at[1].set(jnp.nan), y)
    assert float(metrics["healed"]) == 1.0
    rows = np.asarray(jax.vmap(lambda p: jnp.stack(
        [jnp.all(jnp.isfinite(a)) for a in jax.tree.leaves(p)]).all())(
            new_state.params))
    assert rows.tolist() == [True] * n  # worker 1 healed from the survivors
    # the others took the step they take when worker 1's batch is sound:
    # nothing of worker 1's forward/backward reached them before the exchange
    clean_state, _ = step(state, x, y)
    before = jax.tree.leaves(state.batch_stats)[0]
    for w in (0, 2, 3):
        for a, b in zip(jax.tree.leaves(new_state.batch_stats),
                        jax.tree.leaves(clean_state.batch_stats)):
            np.testing.assert_array_equal(np.asarray(a[w]), np.asarray(b[w]))
    assert not np.array_equal(
        np.asarray(jax.tree.leaves(new_state.batch_stats)[0][0]),
        np.asarray(before[0]))


@pytest.mark.faults
@pytest.mark.parametrize("extra,packed,reason", [
    ({}, True, None),
    ({"fault_plan": FaultPlan(events=())}, False, "fault plan"),
    ({"remat": True}, False, "remat"),
])
def test_train_journals_how_the_forward_backward_runs(extra, packed, reason):
    """One ``fwd_bwd`` event a run, beside ``backend``: packed with P and
    the packs of a slab, or the condition that kept the per-worker path."""
    config = TrainConfig(
        name="packed", model="resnet8", dataset="synthetic_image",
        dataset_kwargs={"num_train": 16, "num_test": 8}, num_workers=4,
        topology="ring", graphid=None, batch_size=2, epochs=1, lr=0.05,
        warmup=False, matcha=False, seed=1, save=False, eval_every=0,
        measure_comm_split=False, devices=1, **extra)
    result = train(config)
    assert np.isfinite(result.history[-1]["loss"])
    (event,) = [e for e in result.recorder.events if e["kind"] == "fwd_bwd"]
    assert event["packed"] is packed
    if packed:
        assert (event["workers_per_pack"], event["packs_per_slab"]) == (4, 1)
        assert "reason" not in event
    else:
        assert (event["workers_per_pack"], event["packs_per_slab"]) == (1, 4)
        assert reason in event["reason"]
