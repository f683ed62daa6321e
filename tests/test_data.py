import numpy as np
import pytest

from matcha_tpu.data import (
    WorkerBatches,
    augment_crop_flip,
    load_npz,
    normalize,
    partition_indices,
    partition_label_skew,
    partition_uniform,
    synthetic_classification,
    synthetic_images,
)


def test_partition_uniform_disjoint_and_seeded():
    parts = partition_uniform(1000, 8, seed=7)
    assert len(parts) == 8
    assert all(len(p) == 125 for p in parts)
    allidx = np.concatenate(parts)
    assert len(set(allidx.tolist())) == 1000
    parts2 = partition_uniform(1000, 8, seed=7)
    for a, b in zip(parts, parts2):
        np.testing.assert_array_equal(a, b)
    parts3 = partition_uniform(1000, 8, seed=8)
    assert not np.array_equal(parts[0], parts3[0])


def test_partition_label_skew_majority():
    rng = np.random.default_rng(0)
    labels = rng.integers(0, 10, size=2000)
    parts = partition_label_skew(labels, 10, seed=3, major_ratio=0.4)
    assert all(len(p) == 200 for p in parts)
    # disjoint
    allidx = np.concatenate(parts)
    assert len(set(allidx.tolist())) == len(allidx)
    # each worker's major class is overrepresented vs uniform (10%)
    for w, p in enumerate(parts):
        frac = (labels[p] == w % 10).mean()
        assert frac > 0.3, (w, frac)


def test_partition_indices_dispatch():
    with pytest.raises(ValueError):
        partition_indices(100, 4, non_iid=True)
    parts = partition_indices(100, 4, non_iid=False)
    assert len(parts) == 4


def test_synthetic_dataset_learnable_structure():
    ds = synthetic_classification(num_train=512, num_test=128, seed=0)
    assert ds.x_train.shape == (512, 28, 28, 1)
    assert ds.y_train.shape == (512,) and ds.y_train.dtype == np.int32
    # nearest-centroid accuracy should beat chance by a lot
    centers = np.stack([
        ds.x_train[ds.y_train == c].reshape(-1, 784).mean(0) for c in range(10)
    ])
    pred = np.argmin(
        ((ds.x_test.reshape(-1, 784)[:, None] - centers[None]) ** 2).sum(-1), axis=1
    )
    assert (pred == ds.y_test).mean() > 0.5


def test_synthetic_images_shape():
    ds = synthetic_images(num_train=64, num_test=16)
    assert ds.x_train.shape == (64, 32, 32, 3)


def test_normalize_reference_constants():
    x = np.full((2, 4, 4, 3), 255, np.uint8)
    out = normalize(x, "cifar10")
    want = (1.0 - np.array([0.4914, 0.4822, 0.4465])) / np.array([0.2023, 0.1994, 0.2010])
    np.testing.assert_allclose(out[0, 0, 0], want, rtol=1e-5)


def test_load_npz_roundtrip(tmp_path):
    p = tmp_path / "toy.npz"
    np.savez(
        p,
        x_train=np.random.randint(0, 255, (20, 3, 8, 8), np.uint8),  # NCHW on purpose
        y_train=np.arange(20) % 5,
        x_test=np.random.randint(0, 255, (10, 3, 8, 8), np.uint8),
        y_test=np.arange(10) % 5,
    )
    ds = load_npz(str(p), dataset="cifar10")
    assert ds.x_train.shape == (20, 8, 8, 3)  # transposed to NHWC
    assert ds.num_classes == 5
    assert ds.x_train.dtype == np.float32


def test_augment_crop_flip_preserves_shape():
    rng = np.random.default_rng(0)
    x = rng.normal(size=(6, 32, 32, 3)).astype(np.float32)
    out = augment_crop_flip(x, rng)
    assert out.shape == x.shape
    assert not np.allclose(out, x)


def test_worker_batches_layout_and_determinism():
    ds = synthetic_classification(num_train=800, seed=1)
    parts = partition_uniform(800, 8, seed=2)
    wb = WorkerBatches(ds.x_train, ds.y_train, parts, batch_size=16, seed=5)
    assert wb.batches_per_epoch == 100 // 16
    batches = list(wb.epoch(0))
    assert len(batches) == wb.batches_per_epoch
    xb, yb = batches[0]
    assert xb.shape == (8, 16, 28, 28, 1) and yb.shape == (8, 16)
    # deterministic given (seed, epoch); different across epochs
    xb2, yb2 = next(iter(wb.epoch(0)))
    np.testing.assert_array_equal(xb, xb2)
    xb3, _ = next(iter(wb.epoch(1)))
    assert not np.array_equal(xb, xb3)


@pytest.fixture(scope="module")
def image_loaders():
    """One loader an ``augment`` setting over the same 5-step epoch of four
    workers, with what ``epoch(2)`` yields, stacked."""
    ds = synthetic_images(num_train=4 * 8 * 5 + 7, num_test=8, seed=1)
    parts = partition_uniform(len(ds.x_train), 4, seed=2)
    out = {}
    for augment in (False, True):
        wb = WorkerBatches(ds.x_train, ds.y_train, parts, batch_size=8,
                           seed=5, augment=augment, pad_value=-1.5)
        assert wb.batches_per_epoch == 5
        xs, ys = (np.stack(a) for a in zip(*wb.epoch(2)))
        out[augment] = wb, xs, ys
    assert not np.array_equal(out[False][1], out[True][1])
    return out


@pytest.mark.parametrize("augment", [False, True])
@pytest.mark.parametrize("first,steps", [(0, 5), (0, 2), (2, 2), (4, 1)],
                         ids=["whole", "head", "middle", "tail"])
def test_worker_batches_epoch_into_is_epoch_bit_for_bit(
        image_loaders, augment, first, steps):
    """The write-into form against ``np.stack`` over ``epoch(e)``: the same
    permutations and, augmenting, the same draws of the epoch's one stream
    wherever the slice starts — into the leading ``steps`` of a longer
    stack that is not empty, as the loop's kept stacks are."""
    wb, xs, ys = image_loaders[augment]
    xs_out = np.full((3 if steps < 5 else 5,) + xs.shape[1:], np.nan,
                     xs.dtype)
    ys_out = np.full(xs_out.shape[:3], -1, ys.dtype)
    assert wb.epoch_into(2, xs_out[:steps], ys_out[:steps], first) is None
    np.testing.assert_array_equal(xs_out[:steps], xs[first:first + steps])
    np.testing.assert_array_equal(ys_out[:steps], ys[first:first + steps])
    assert np.isnan(xs_out[steps:]).all() and (ys_out[steps:] == -1).all()


@pytest.mark.parametrize("first,steps,label_steps", [(4, 2, 2), (-1, 2, 2),
                                                     (0, 2, 3)])
def test_worker_batches_epoch_into_rejects_steps_outside_the_epoch(
        image_loaders, first, steps, label_steps):
    wb, xs, ys = image_loaders[False]
    with pytest.raises(ValueError, match="steps"):
        wb.epoch_into(0, np.empty_like(xs[:steps]),
                      np.empty_like(ys[:label_steps]), first)


def test_worker_batches_rejects_oversized_batch():
    ds = synthetic_classification(num_train=64)
    parts = partition_uniform(64, 8)
    with pytest.raises(ValueError):
        WorkerBatches(ds.x_train, ds.y_train, parts, batch_size=16)


def test_partition_fractions_reference_semantics():
    from matcha_tpu.data import partition_fractions

    parts = partition_fractions(103, [0.5, 0.3, 0.2], seed=7)
    # int() truncation semantics (util.py:55-58)
    assert [len(p) for p in parts] == [51, 30, 20]
    allidx = np.concatenate(parts)
    assert len(np.unique(allidx)) == len(allidx)  # disjoint
    # deterministic under seed
    again = partition_fractions(103, [0.5, 0.3, 0.2], seed=7)
    assert all(np.array_equal(a, b) for a, b in zip(parts, again))
    with pytest.raises(ValueError):
        partition_fractions(10, [0.8, 0.4])


def test_photo_patches_real_pixels():
    """Real photographs from site-packages → 32x32 patch classes; the build
    is deterministic and its statistics are photo-like (not the noise the
    CIFAR fixtures contain)."""
    from matcha_tpu.data import photo_patches

    d = photo_patches(train_per_class=24, test_per_class=8, seed=1)
    assert d.num_classes >= 4
    assert d.x_train.shape == (24 * d.num_classes, 32, 32, 3)
    assert d.x_test.shape == (8 * d.num_classes, 32, 32, 3)
    assert set(np.unique(d.y_train)) == set(range(d.num_classes))
    again = photo_patches(train_per_class=24, test_per_class=8, seed=1)
    assert np.array_equal(d.x_train, again.x_train)
    # real photos have strong spatial autocorrelation; uniform noise has
    # none.  Mean |neighbor delta| of normalized noise would be ~1.1 std
    # units; photos sit far below.
    dx = np.abs(np.diff(d.x_train, axis=2)).mean()
    assert dx < 0.5, f"patches look like noise (mean neighbor delta {dx:.2f})"


def test_photo_patches_trains_in_loop():
    """The dataset rides the full train() pipeline (augment on) and a tiny
    MLP separates several of the 8 photo classes within two epochs."""
    from matcha_tpu.train import TrainConfig, train

    cfg = TrainConfig(
        name="photo-t", model="mlp", dataset="photo_patches",
        dataset_kwargs={"train_per_class": 64, "test_per_class": 16},
        num_workers=4, devices=1, graphid=None, topology="ring", batch_size=16,
        epochs=2, lr=0.05, warmup=False, matcha=True, budget=0.5, seed=0,
        save=False, eval_every=1, augment=True, measure_comm_split=False,
    )
    hist = train(cfg).history
    assert hist[-1]["loss"] < hist[0]["loss"]
    assert hist[-1]["test_acc_mean"] > 1.0 / 8 + 0.05  # above chance
