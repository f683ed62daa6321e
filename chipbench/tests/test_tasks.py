"""The task seam: ``image_classes`` is the parent's generator, input
preparation and loss to the bit; ``next_token`` makes the traffic its
docstring states and its loss tells a shifted target and an ignored
boundary from a sound one; and a toy token configuration runs from
``stage_job`` through ``run_reference`` to ``check.compare`` on the lines of
``harness.py`` and ``reference/step.py`` that the image cells run through,
against a NumPy step written here."""

import hashlib
import sys
import types

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from chipbench import catalog, check, harness, work
from chipbench.reference import layers
from chipbench.tasks import image_classes, next_token

import toy_lm  # beside this file: the toy architecture

# ---- image_classes: today's behaviour moved ------------------------------

# sha256 over the four arrays of the parent's ``data.py:make_dataset`` at
# 192 training and 38 test rows (chipbench at PR 25, commit 6906129)
PARENT_DIGESTS = {
    ("resnet20-c10", 7):
        "7b37f06ebd1253c5761bf7dba57905cf3bcb865b570af71c87677eee4dff5440",
    ("resnet20-c10", 2147480011):
        "0a402f80aded59b76adc940191321a669e1794c8208eea6095940c3773a4b419",
    ("wrn28-10-c100", 7):
        "7c5bbe26bac533d9dd04530b59debfa6ec42da23c5dc817c15ef06d8594aeb69",
    ("wrn28-10-c100", 2147480011):
        "8c5736be225cfb825146ea8d67ddab28b53555ccf8c196d1cd275c7194deb5f6",
}


def config_of(name):
    bench = catalog.benchmark()
    cell = next(w["name"] for w in bench["workloads"] if w["config"] == name)
    return catalog.load_cell(cell)[2]


@pytest.mark.parametrize("name, seed", sorted(PARENT_DIGESTS))
def test_image_classes_make_is_the_parents_dataset(name, seed):
    config = config_of(name)
    assert catalog.load_task(config) is image_classes  # no ``task`` key
    data = image_classes.make(seed, 192, 38, config)
    h = hashlib.sha256()
    for k in ("x_train", "y_train", "x_test", "y_test"):
        for part in (k, str(data[k].dtype), str(data[k].shape)):
            h.update(part.encode())
        h.update(data[k].tobytes())
    assert h.hexdigest() == PARENT_DIGESTS[name, seed]


@pytest.mark.parametrize("name", ["resnet20-c10", "wrn28-10-c100"])
def test_image_classes_prepare_and_loss_are_the_parents_bitwise(name):
    config = config_of(name)
    classes = config["sizes"]["num_classes"]
    rng = np.random.default_rng(3)
    x_u8 = rng.integers(0, 256, (8, 32, 32, 3), dtype=np.uint8)
    y = rng.integers(0, classes, 8, dtype=np.int32)
    logits = rng.normal(0, 3, (8, classes)).astype(np.float32)
    mean = np.asarray(config["input_mean"], np.float32)
    std = np.asarray(config["input_std"], np.float32)

    @jax.jit
    def parent(x_u8, y, logits):  # reference/step.py at PR 25, lines 43-58
        x = (x_u8.astype(jnp.float32) / 255.0 - mean) / std
        logp = logits - jax.scipy.special.logsumexp(
            logits, axis=-1, keepdims=True)
        nll = -jnp.take_along_axis(logp, y[:, None], axis=-1)[:, 0]
        return x, jnp.mean(nll)

    @jax.jit
    def moved(x_u8, y, logits):
        x, targets = image_classes.prepare(x_u8, y, config)
        return x, image_classes.loss(logits, targets)

    for got, want in zip(moved(x_u8, y, logits), parent(x_u8, y, logits)):
        assert np.asarray(got).tobytes() == np.asarray(want).tobytes()


def test_unknown_task_raises_with_the_list():
    with pytest.raises(KeyError) as e:
        catalog.load_task({"name": "some-config", "task": "speech_frames"})
    assert "speech_frames" in str(e.value)
    assert "image_classes" in str(e.value) and "next_token" in str(e.value)


# ---- next_token.make ------------------------------------------------------

SMALL = {"sizes": {"seq_len": 256, "vocab_held": 64}}     # many tokens an id
LONG = {"sizes": {"seq_len": 2048, "vocab_held": 12544}}  # documents whole


@pytest.fixture(scope="module")
def small():
    return next_token.make(11, 64, 16, SMALL)


@pytest.fixture(scope="module")
def long_rows():
    return next_token.make(2147480011, 256, 8, LONG)


def test_next_token_rows_have_the_stated_layout(small):
    assert set(small) == {"x_train", "y_train", "x_test", "y_test"}
    for split, n in (("train", 64), ("test", 16)):
        for k in ("x_", "y_"):
            assert small[k + split].shape == (n, 257)
            assert small[k + split].dtype == np.int32
        ids = small["x_" + split]
        assert ids.min() >= 0 and ids.max() < 64
        assert len(np.unique(ids)) == 64  # every id of the slice occurs
    assert len({r.tobytes() for r in small["x_train"]}) == 64  # none alike


def test_next_token_same_seed_same_bytes_and_seeds_differ(small):
    again = next_token.make(11, 64, 16, SMALL)
    other = next_token.make(12, 64, 16, SMALL)
    for k in small:
        assert small[k].tobytes() == again[k].tobytes()
    assert small["x_train"].tobytes() != other["x_train"].tobytes()
    assert small["x_train"].tobytes() != small["x_test"][:16].tobytes()


def test_next_token_large_seed_and_every_id_of_a_real_slice(long_rows):
    assert len(np.unique(long_rows["x_train"])) == 12544
    assert long_rows["x_train"].max() == 12543


def test_next_token_document_lengths_are_heavy_tailed(long_rows):
    docs = long_rows["y_train"].reshape(-1)
    lengths = np.bincount(docs)[:-1]  # the last is cut by the split's end
    assert len(lengths) > 150
    assert lengths.min() >= 16 and lengths.max() <= 4 * 2048
    # log-normal, median 1,024 and sigma 1.2: the 95th percentile is 7,370
    assert 750 <= np.median(lengths) <= 1400
    assert 4500 <= np.percentile(lengths, 95) <= 4 * 2048
    assert np.mean(lengths == 4 * 2048) < 0.1  # the clip is the tail's end


def test_next_token_document_split_over_rows_keeps_its_number(small):
    docs = small["y_train"]
    steps = np.diff(docs.reshape(-1))
    assert set(np.unique(steps)) == {0, 1}  # packed end to end, in order
    assert docs[0, 0] == 0
    assert (docs[:-1, -1] == docs[1:, 0]).any()  # one goes on in the next
    assert (docs[:, 0] != docs[:, -1]).any()  # and one ends inside a row


def test_next_token_ids_follow_a_sparse_chain(small):
    """Eight favoured successors at 0.7, uniform otherwise: the eight most
    frequent successors of an id take 0.7 + 0.3 x 8/64 of its transitions,
    less what a document's first id breaks."""
    ids, docs = small["x_train"].reshape(-1), small["y_train"].reshape(-1)
    inside = docs[1:] == docs[:-1]
    counts = np.zeros((64, 64), np.int64)
    np.add.at(counts, (ids[:-1][inside], ids[1:][inside]), 1)
    top8 = np.sort(counts, axis=1)[:, -8:].sum() / counts.sum()
    assert 0.68 <= top8 <= 0.82
    # and a document's first id is a uniform draw, not a successor
    first = ~inside
    follows = counts[ids[:-1][first], ids[1:][first]] > np.sort(
        counts, axis=1)[ids[:-1][first], -9]
    assert follows.mean() < 0.4


# ---- next_token.prepare and loss ------------------------------------------


def numpy_loss(logits, targets, judged):
    """Mean over the judged positions of softmax cross-entropy, float64."""
    z = logits.astype(np.float64)
    top = z.max(-1, keepdims=True)
    lse = np.log(np.exp(z - top).sum(-1)) + top[..., 0]
    picked = np.take_along_axis(z, targets[..., None], -1)[..., 0]
    return ((lse - picked) * judged).sum() / judged.sum()


@pytest.fixture(scope="module")
def batch(small):
    """Eight rows in which a document ends, and logits that put the next id
    first wherever a model could know it: not where it starts a document."""
    ends = small["y_train"][:, 0] != small["y_train"][:, -1]
    ids, docs = small["x_train"][ends][:8], small["y_train"][ends][:8]
    known = docs[:, 1:] == docs[:, :-1]
    assert len(ids) == 8 and 8 <= (~known).sum() < known.size / 4
    rng = np.random.default_rng(5)
    logits = rng.normal(0, 1, (8, 256, 64)).astype(np.float32)
    rows, cols = np.nonzero(known)
    logits[rows, cols, ids[:, 1:][known]] += 8.0
    return ids, docs, known, logits


def test_next_token_prepare_shifts_and_masks(batch):
    ids, docs, known, _ = batch
    inputs, targets = next_token.prepare(ids, docs, SMALL)
    assert np.array_equal(inputs["ids"], ids[:, :-1])
    assert np.array_equal(inputs["docs"], docs[:, :-1])
    assert np.array_equal(targets, np.where(known, ids[:, 1:], -1))


def test_next_token_loss_is_the_float64_mean_over_judged_positions(batch):
    ids, docs, known, logits = batch
    _, targets = next_token.prepare(ids, docs, SMALL)
    got = jax.jit(next_token.loss)(logits, targets)
    assert got.dtype == jnp.float32
    want = numpy_loss(logits, ids[:, 1:], known)
    assert abs(float(got) - want) <= 2e-6 * want
    # bfloat16 outputs are judged in float32
    low = jax.jit(next_token.loss)(logits.astype(jnp.bfloat16), targets)
    assert low.dtype == jnp.float32 and abs(float(low) - want) < 0.02 * want


@pytest.mark.parametrize("fault", ["targets_not_shifted", "boundary_unmasked"])
def test_next_token_loss_tells_a_planted_fault(batch, fault):
    ids, docs, known, logits = batch
    sound = numpy_loss(logits, ids[:, 1:], known)
    if fault == "targets_not_shifted":  # asked to predict its own input
        targets = np.where(known, ids[:, :-1], -1)
        want = numpy_loss(logits, ids[:, :-1], known)
    else:  # every position judged, a document's first id among them
        targets = ids[:, 1:]
        want = numpy_loss(logits, ids[:, 1:], np.ones_like(known))
    got = float(jax.jit(next_token.loss)(logits, targets))
    assert abs(got - want) <= 2e-6 * want  # the loss of what it was given
    assert got > 1.2 * sound  # and not the sound one


# ---- the toy token configuration through the harness's own lines ----------

TOY = {"name": "toy-lm", "task": "next_token", "reference": "toy_lm",
       "sizes": {"seq_len": 24, "vocab_held": 40, "hidden": 12}}
JOB = {"name": "toy-lm.w3", "config": "toy-lm", "chips": 1,
       "train_config": {"num_workers": 3, "batch_size": 2, "lr": 0.1,
                        "momentum": 0.9, "weight_decay": 0.0005,
                        "nesterov": True, "seed": 5},
       "data": {"steps_per_epoch": 2, "test_fraction": 0.5},
       "reference_block": 2}
PERMS, ALPHA = np.array([[1, 0, 2]]), 0.3  # one matching: workers 0 and 1


def numpy_epoch(params, ids_all, docs_all, idx, hyper):
    """The reference's epoch over the toy model, by hand in float64."""
    p = {k: v.astype(np.float64) for k, v in params.items()}
    mom = {k: np.zeros_like(v) for k, v in p.items()}
    lr, mu, wd = hyper["lr"], hyper["momentum"], hyper["weight_decay"]
    losses = []
    for rows in idx:  # [N, B]
        grads = {k: np.zeros_like(v) for k, v in p.items()}
        step_losses = []
        for w, r in enumerate(rows):
            ids, docs = ids_all[r], docs_all[r]
            x, y = ids[:, :-1], ids[:, 1:]
            judged = (docs[:, 1:] == docs[:, :-1]).astype(np.float64)
            emb, proj, head = (p[k][w] for k in (
                "embed/embedding", "proj/kernel", "head/kernel"))
            h = emb[x]
            z = np.tanh(h @ proj)
            logits = z @ head
            step_losses.append(numpy_loss(logits, y, judged))
            soft = np.exp(logits - logits.max(-1, keepdims=True))
            soft /= soft.sum(-1, keepdims=True)
            soft[np.arange(x.shape[0])[:, None],
                 np.arange(x.shape[1])[None], y] -= 1.0
            dlogits = soft * judged[..., None] / judged.sum()
            grads["head/kernel"][w] = np.einsum("bsk,bsv->kv", z, dlogits)
            dpre = (dlogits @ head.T) * (1 - z * z)
            grads["proj/kernel"][w] = np.einsum("bsh,bsk->hk", h, dpre)
            np.add.at(grads["embed/embedding"][w], x, dpre @ proj.T)
        for k in p:
            g = grads[k] + wd * p[k]
            mom[k] = g + mu * mom[k]
            x = p[k] - lr * (g + mu * mom[k])  # Nesterov
            p[k] = x - ALPHA * (x - x[PERMS[0]])  # the one matching fires
        losses.append(step_losses)
    return p, mom, np.asarray(losses)


@pytest.mark.parametrize("compute", ["stated", "highest"])
def test_toy_token_configuration_through_the_harness(tmp_path, monkeypatch,
                                                     compute):
    monkeypatch.setitem(sys.modules, "chipbench.reference.toy_lm", toy_lm)
    steps = JOB["data"]["steps_per_epoch"]
    data, train_config = harness.stage_job(JOB, TOY, 2147480099, steps,
                                           tmp_path)
    with np.load(tmp_path / "data.npz") as written:
        assert written["x_train"].shape == (3 * 2 * steps, 25)
        assert written["x_train"].tobytes() == data["x_train"].tobytes()

    sizes = TOY["sizes"]
    rng = np.random.default_rng(1)
    first = {"embed/embedding": (3, sizes["vocab_held"], sizes["hidden"]),
             "proj/kernel": (3, sizes["hidden"], sizes["hidden"]),
             "head/kernel": (3, sizes["hidden"], sizes["vocab_held"])}
    first = {k: rng.normal(0, 0.5, s).astype(np.float32)
             for k, s in first.items()}
    hook = types.SimpleNamespace(
        first={"params": first, "stats": {}},
        schedule=types.SimpleNamespace(
            perms=PERMS, flags=np.ones((steps, 1), np.float32), alpha=ALPHA))
    program, losses = harness.run_reference(TOY, JOB, hook, data,
                                            train_config, compute)
    assert losses.shape == (steps, 3) and losses[-1].mean() < losses[0].mean()

    idx = harness.first_epoch_rows(train_config, len(data["x_train"]))
    p, mom, want_losses = numpy_epoch(first, data["x_train"], data["y_train"],
                                      idx, JOB["train_config"])
    to_f32 = lambda t: {k: v.astype(np.float32) for k, v in t.items()}
    reference = jax.device_get(check.summarize(to_f32(p), to_f32(mom), first))
    numbers = check.compare(program, float(losses.mean()), reference,
                            want_losses)
    ok, lines = check.verdict(numbers, dict.fromkeys(
        ("loss_gap", "momentum_gap", "dparam_gap", "disagree_gap"), 1e-4))
    assert ok, lines


def test_ops_carries_the_precision_beside_conv_and_dot():
    ops = layers.make_ops(jax.lax.Precision.HIGHEST)
    assert ops.precision == jax.lax.Precision.HIGHEST
    p = {"d/kernel": jnp.ones((4, 2)), "d/bias": jnp.arange(2.0)}
    assert np.array_equal(ops.dot(jnp.ones((3, 4)), p, "d"),
                          np.full((3, 2), 4.0) + np.arange(2.0))
    # and an architecture that never looks at it computes what it did
    assert work.forward_macs(config_of("resnet20-c10")) == 40_813_184
