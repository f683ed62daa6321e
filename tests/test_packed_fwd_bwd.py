"""Worker packing of the forward/backward (PERF.md section 6, PR 30).

``ResNet.packed_apply`` runs P workers as one network P times as wide with
block-diagonal convolution kernels; ``fwd_bwd_plan`` decides where
``make_train_step`` may use it.  Here, on the CPU at ``highest``: the packed
form equals ``vmap`` over workers to float32 rounding, a worker in a pack is
not isolated, and the plan says where the step packs.  What the step does
with the plan (the per-worker program kept to the byte, a non-finite worker
left alone where a fault plan is compiled in, the journal) is
``tests/test_packed_step.py``: two files, so that ``loadfile`` runs them
beside each other.
"""

import functools

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from matcha_tpu.models import MLP, ResNet, WideResNet
from matcha_tpu.train.state import fwd_bwd_plan
from matcha_tpu.utils import cross_entropy_loss

IMAGE = (32, 32, 3)


class PerWorkerResNet(ResNet):
    """The same model with its packed form hidden: what the parent ran."""

    pack_width = None


@pytest.fixture(autouse=True)
def _highest():
    with jax.default_matmul_precision("highest"):
        yield


@functools.cache
def _workers(model, n, seed=0):
    """``n`` workers' variables (leaves ``[n, ...]``) with every leaf moved
    off its initial value, biases and running statistics included; drawn
    once a model and worker count."""
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


@functools.cache  # one jitted function a model: a shape compiles once a file
def _per_worker_of(model):
    def one(p, s, x, y):
        def loss_fn(p):
            logits, mutated = model.apply(
                {"params": p, "batch_stats": s}, x, train=True,
                mutable=["batch_stats"])
            return cross_entropy_loss(logits, y), (mutated["batch_stats"], logits)

        return jax.value_and_grad(loss_fn, has_aux=True)(p)

    return jax.jit(jax.vmap(one))


def _per_worker(model, params, stats, x, y):
    return _per_worker_of(model)(params, stats, x, y)


@functools.cache
def _packed_of(model):
    def summed(p, stats, x, y):
        logits, new_stats = model.packed_apply(p, stats, x)
        loss = cross_entropy_loss(logits, y)
        return jnp.sum(loss), (loss, new_stats, logits)

    return jax.jit(jax.value_and_grad(summed, has_aux=True))


def _packed(model, params, stats, x, y):
    (_, (loss, new_stats, logits)), grads = _packed_of(model)(
        params, stats, x, y)
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
