"""Worker packing of the forward/backward (PERF.md section 6, PR 30).

``ResNet.packed_apply`` runs P workers as one network P times as wide with
block-diagonal convolution kernels; ``fwd_bwd_plan`` decides where
``make_train_step`` may use it.  Here, on the CPU at ``highest``: the packed
form equals ``vmap`` over workers to float32 rounding, every condition that
needs workers isolated (or gains nothing) keeps the per-worker program to the
byte, and a non-finite worker stays alone where a fault plan is compiled in.
"""

import hashlib

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from matcha_tpu import topology as tp
from matcha_tpu.communicator import make_decen
from matcha_tpu.models import MLP, ResNet, WideResNet
from matcha_tpu.ops import WorkerFlattener
from matcha_tpu.resilience import FaultEvent, FaultPlan
from matcha_tpu.schedule import fixed_schedule
from matcha_tpu.train import TrainConfig, make_lr_schedule, train
from matcha_tpu.train.state import (
    fwd_bwd_plan,
    init_train_state,
    make_optimizer,
    make_train_step,
)
from matcha_tpu.utils import cross_entropy_loss

IMAGE = (32, 32, 3)


class PerWorkerResNet(ResNet):
    """The same model with its packed form hidden: what the parent ran."""

    pack_width = None


@pytest.fixture(autouse=True)
def _highest():
    with jax.default_matmul_precision("highest"):
        yield


def _workers(model, n, seed=0):
    """``n`` workers' variables (leaves ``[n, ...]``) with every leaf moved
    off its initial value, biases and running statistics included."""
    def init_one(key):
        v = model.init(key, jnp.zeros((1,) + IMAGE), train=False)
        return v["params"], v["batch_stats"]

    def jitter(tree, key, scale):
        leaves, treedef = jax.tree.flatten(tree)
        keys = jax.random.split(key, len(leaves))
        return jax.tree.unflatten(treedef, [
            a + scale * jax.random.uniform(k, a.shape)
            for a, k in zip(leaves, keys)])

    @jax.jit  # eagerly, every leaf's draw is a program of its own
    def build(key):
        params, stats = jax.vmap(init_one)(jax.random.split(key, n))
        return (jitter(params, jax.random.fold_in(key, 1), 0.05),
                jitter(stats, jax.random.fold_in(key, 2), 0.5))

    return build(jax.random.PRNGKey(seed))


def _batch(n, batch, seed=3):
    x = jax.random.normal(jax.random.PRNGKey(seed), (n, batch) + IMAGE)
    y = jax.random.randint(jax.random.PRNGKey(seed + 1), (n, batch), 0, 10)
    return x, y


def _per_worker(model, params, stats, x, y):
    def one(p, s, x, y):
        def loss_fn(p):
            logits, mutated = model.apply(
                {"params": p, "batch_stats": s}, x, train=True,
                mutable=["batch_stats"])
            return cross_entropy_loss(logits, y), (mutated["batch_stats"], logits)

        return jax.value_and_grad(loss_fn, has_aux=True)(p)

    return jax.jit(jax.vmap(one))(params, stats, x, y)


def _packed(model, params, stats, x, y):
    def summed(p):
        logits, new_stats = model.packed_apply(p, stats, x)
        loss = cross_entropy_loss(logits, y)
        return jnp.sum(loss), (loss, new_stats, logits)

    (_, (loss, new_stats, logits)), grads = jax.jit(
        jax.value_and_grad(summed, has_aux=True))(params)
    return (loss, (new_stats, logits)), grads


def _assert_trees_close(got, want, tol):
    """Same structure, same leaf shapes, and every leaf within ``tol`` of
    the largest entry of the whole reference tree (a convolution's bias
    ahead of a batch norm has a gradient that is rounding alone)."""
    assert jax.tree.structure(got) == jax.tree.structure(want)
    scale = max(float(jnp.max(jnp.abs(a))) for a in jax.tree.leaves(want))
    for (path, a), b in zip(jax.tree_util.tree_leaves_with_path(want),
                            jax.tree.leaves(got)):
        assert a.shape == b.shape, jax.tree_util.keystr(path)
        np.testing.assert_allclose(
            np.asarray(b), np.asarray(a), rtol=0, atol=tol * scale,
            err_msg=jax.tree_util.keystr(path))


# resnet8 has one block a stage, resnet20 three; the first block of stages 1
# and 2 strides by 2 behind a shortcut_conv / shortcut_bn; resnet50 is the
# bottleneck block of the same class
@pytest.mark.parametrize("depth,workers,batch", [
    (8, 2, 4), (8, 4, 2), (8, 8, 2), (20, 2, 2), (20, 8, 2), (50, 2, 2)])
def test_packed_form_equals_vmap_over_workers(depth, workers, batch):
    model = ResNet(depth=depth, num_classes=10)
    params, stats = _workers(model, workers)
    x, y = _batch(workers, batch)
    (loss, (new_stats, logits)), grads = _per_worker(model, params, stats, x, y)
    (p_loss, (p_stats, p_logits)), p_grads = _packed(model, params, stats, x, y)

    assert any("shortcut_conv" in block for block in params.values())
    np.testing.assert_allclose(np.asarray(p_loss), np.asarray(loss), rtol=2e-6)
    np.testing.assert_allclose(np.asarray(p_logits), np.asarray(logits),
                               rtol=0, atol=2e-5)
    # the gradient is the model's own tree: same names, leaves [P, ...]
    assert jax.tree.structure(p_grads) == jax.tree.structure(params)
    # float32 summation order: most leaves agree to 1e-6 of the largest
    # entry; a pre-activation within rounding of 0 lands on the other side
    # of its ReLU once in a few thousand entries and moves one by 1e-4
    _assert_trees_close(p_grads, grads, 5e-4)
    _assert_trees_close(p_stats, new_stats, 1e-5)
    # and the statistics moved: momentum 0.9 on every running mean
    assert float(jnp.max(jnp.abs(p_stats["stem_bn"]["mean"]
                                 - stats["stem_bn"]["mean"]))) > 1e-3


def test_a_worker_in_a_pack_is_not_isolated_from_the_others():
    """Why the plan keeps ``vmap`` wherever a worker may go non-finite and
    be healed: in a pack, a zero block times a NaN is NaN."""
    model = ResNet(depth=8, num_classes=10)
    params, stats = _workers(model, 4)
    x, y = _batch(4, 2)
    x = x.at[1].set(jnp.nan)
    (loss, _), _ = _per_worker(model, params, stats, x, y)
    (p_loss, _), _ = _packed(model, params, stats, x, y)
    assert np.isfinite(np.asarray(loss)).tolist() == [True, False, True, True]
    assert not np.isfinite(np.asarray(p_loss)).any()


# ------------------------------------------------------------------ the plan

def test_plan_packs_the_cifar_resnet_by_shapes_alone():
    model = ResNet(depth=20)
    # cell 2: 128 workers in slabs of 64, 16 channels at the narrowest
    assert fwd_bwd_plan(model, 128, 64) == {
        "packed": True, "workers_per_pack": 8, "packs_per_slab": 8}
    assert fwd_bwd_plan(model, 128) == {
        "packed": True, "workers_per_pack": 8, "packs_per_slab": 16}
    # the largest P that divides the slab, full lanes or not
    assert fwd_bwd_plan(model, 12)["workers_per_pack"] == 6
    assert fwd_bwd_plan(model, 7)["workers_per_pack"] == 7
    assert fwd_bwd_plan(model, 8, 4)["workers_per_pack"] == 4
    assert fwd_bwd_plan(model, 6, 2)["workers_per_pack"] == 2


@pytest.mark.parametrize("model,workers,chunk,kwargs,reason", [
    (ResNet(depth=20), 11, None, {}, "divides the slab of 11"),
    (ResNet(depth=20), 8, 1, {}, "divides the slab of 1"),
    (ResNet(depth=20), 16, None, {"worker_shards": 4}, "sharded over 4 devices"),
    (ResNet(depth=20, remat=True), 8, None, {}, "remat"),
    (ResNet(depth=20), 8, None, {"dropout": True}, "dropout"),
    (ResNet(depth=20), 8, None, {"faults": True}, "fault plan"),
    (ResNet(depth=20), 8, None, {"elastic": True}, "elastic membership"),
    (WideResNet(depth=10, widen_factor=1), 8, None, {},
     "WideResNet has no packed form"),
    (MLP(num_classes=10), 8, None, {}, "MLP has no packed form"),
    (PerWorkerResNet(depth=20), 8, None, {}, "has no packed form"),
])
def test_plan_keeps_the_per_worker_path_and_says_why(model, workers, chunk,
                                                     kwargs, reason):
    plan = fwd_bwd_plan(model, workers, chunk, **kwargs)
    assert plan["packed"] is False and plan["workers_per_pack"] == 1
    assert plan["packs_per_slab"] == (chunk or workers)
    assert reason in plan["reason"]


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
