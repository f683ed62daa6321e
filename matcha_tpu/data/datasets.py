"""Datasets and worker-batched loading.

The reference loads CIFAR/EMNIST/ImageNet through torchvision
(/root/reference/util.py:115-254).  torchvision is unavailable in this image
and the environment has no network egress, so real datasets load from local
``.npz`` files (standard ``x_train/y_train/x_test/y_test`` keys, images NHWC
uint8 or float); synthetic Gaussian-cluster datasets provide hermetic
end-to-end runs and tests.  Per-dataset normalization constants match the
reference transforms (util.py:118-123, 151-160, 223-233).

The loader yields batches stacked over the worker axis — ``x: [N, B, ...]``,
``y: [N, B]`` — the layout the vmapped train step consumes.
"""

from __future__ import annotations

import dataclasses
import itertools
from typing import Iterator, List, Tuple

import numpy as np

__all__ = [
    "Dataset",
    "synthetic_classification",
    "synthetic_images",
    "uci_digits",
    "photo_patches",
    "load_npz",
    "load_tokens",
    "judged_positions",
    "normalize",
    "augment_crop_flip",
    "WorkerBatches",
    "NORMALIZATION",
]

# (mean, std) per channel — reference transforms (util.py:120-123, 157-160)
NORMALIZATION = {
    "cifar10": ((0.4914, 0.4822, 0.4465), (0.2023, 0.1994, 0.2010)),
    "cifar100": ((0.5071, 0.4867, 0.4408), (0.2675, 0.2565, 0.2761)),
    "imagenet": ((0.485, 0.456, 0.406), (0.229, 0.224, 0.225)),
    "emnist": ((0.1307,), (0.3081,)),
    # UCI handwritten digits (scikit-learn's bundled copy), constants over
    # the full 1,797-image set after the /16 range scale — fixed like the
    # torchvision-style constants above, not recomputed per split
    "digits": ((0.3053,), (0.376,)),
    # photo_patches (the real-RGB-pixel dataset built from photographs baked
    # into the image's site-packages — see photo_patches()); constants over
    # the default build's train split, fixed like the rest
    "photo_patches": ((0.3268, 0.3297, 0.4519), (0.2842, 0.2408, 0.2898)),
}


@dataclasses.dataclass
class Dataset:
    x_train: np.ndarray  # [n, H, W, C] float32 (normalized) or raw
    y_train: np.ndarray  # [n] int32
    x_test: np.ndarray
    y_test: np.ndarray
    num_classes: int
    name: str = "dataset"
    #: rows are token ids with what the task needs beside them as ``y``
    #: (``load_tokens``: the layout is the task's), not an image and its label
    token_rows: bool = False


def normalize(x: np.ndarray, dataset: str) -> np.ndarray:
    """uint8/float [.., H, W, C] → normalized float32."""
    x = np.asarray(x, dtype=np.float32)
    if x.max() > 2.0:  # raw pixel range
        x = x / 255.0
    if dataset in NORMALIZATION:
        mean, std = NORMALIZATION[dataset]
        x = (x - np.asarray(mean, np.float32)) / np.asarray(std, np.float32)
    return x


def synthetic_classification(
    num_train: int = 2048,
    num_test: int = 512,
    shape: Tuple[int, ...] = (28, 28, 1),
    num_classes: int = 10,
    seed: int = 0,
    separation: float = 4.0,
) -> Dataset:
    """Gaussian class clusters — linearly separable enough that loss curves
    and consensus behavior are meaningful in seconds."""
    rng = np.random.default_rng(seed)
    dim = int(np.prod(shape))
    centers = rng.normal(size=(num_classes, dim)).astype(np.float32)
    centers *= separation / np.linalg.norm(centers, axis=1, keepdims=True)

    def make(n):
        y = rng.integers(0, num_classes, size=n)
        x = centers[y] + rng.normal(scale=1.0, size=(n, dim)).astype(np.float32)
        return x.reshape((n,) + shape).astype(np.float32), y.astype(np.int32)

    x_tr, y_tr = make(num_train)
    x_te, y_te = make(num_test)
    return Dataset(x_tr, y_tr, x_te, y_te, num_classes, name="synthetic")


def synthetic_images(
    num_train: int = 2048, num_test: int = 512, seed: int = 0,
    separation: float = 4.0,
) -> Dataset:
    """CIFAR-shaped synthetic data ([32,32,3], 10 classes)."""
    ds = synthetic_classification(num_train, num_test, (32, 32, 3), 10, seed,
                                  separation=separation)
    return dataclasses.replace(ds, name="synthetic_image")


def uci_digits(num_test: int = 360, seed: int = 0) -> Dataset:
    """Real handwritten-digit pixels, fully offline: scikit-learn's bundled
    UCI ML handwritten digits (1,797 8×8 grayscale images, 10 classes).

    This is the real-pixel stand-in for the reference's EMNIST/MLP
    configuration (util.py:165-254 builds EMNIST loaders; select_model maps
    ``mlp`` to the 784-500-500 net, util.py:267-268): the environment has no
    network egress and no torchvision, so EMNIST itself cannot be fetched —
    these are the only real image pixels shipped inside the image's baked
    packages.  Pixels are scaled to [0, 1] (the range ToTensor() gives the
    reference's transforms) and standardized with the fixed ``digits``
    constants; the train/test split is a seeded permutation, deterministic
    for a given ``(num_test, seed)``.
    """
    from sklearn.datasets import load_digits  # baked into the image

    d = load_digits()
    x = (d.images.astype(np.float32) / 16.0)[..., None]  # [1797, 8, 8, 1]
    y = d.target.astype(np.int32)
    if not 0 < num_test < len(y):
        raise ValueError(
            f"num_test={num_test} must leave both splits non-empty "
            f"(dataset has {len(y)} images)"
        )
    mean, std = NORMALIZATION["digits"]
    x = (x - np.float32(mean[0])) / np.float32(std[0])
    order = np.random.default_rng(seed).permutation(len(y))
    test, train = order[:num_test], order[num_test:]
    return Dataset(x[train], y[train], x[test], y[test], 10, name="digits")


# Real photographs shipped inside the image's baked site-packages (module →
# relative path).  Each becomes one class of photo_patches; paths resolve via
# find_spec so nothing here imports (pygame's __init__ prints a banner).
_PHOTO_SOURCES = (
    ("china", "sklearn", "datasets/images/china.jpg"),
    ("flower", "sklearn", "datasets/images/flower.jpg"),
    ("hopper", "matplotlib", "mpl-data/sample_data/grace_hopper.jpg"),
    ("fist", "pygame", "examples/data/fist.png"),
    ("canyon", "pygame", "examples/data/arraydemo.bmp"),
    ("freedom", "pygame", "docs/generated/_images/intro_freedom.jpg"),
    ("blade", "pygame", "docs/generated/_images/intro_blade.jpg"),
    ("room", "pygame", "docs/generated/_images/camera_background.jpg"),
)


def photo_patches(
    train_per_class: int = 768,
    test_per_class: int = 128,
    patch: int = 32,
    seed: int = 0,
) -> Dataset:
    """Real-photograph patch classification, fully offline.

    The environment has no network egress and no real CIFAR archive (the
    repo's CIFAR *fixtures* are format-faithful random noise — see
    tests/fixtures/make_fixtures.py), so this is the in-environment analog
    of the reference's CIFAR conv-net configs (util.py:117-149): one class
    per distinct real photograph baked into site-packages, ``patch²`` RGB
    crops sampled from it.  Train and test crops come from spatially
    DISJOINT, adjacent image regions — train pixels end at column
    ``split−1``, test pixels start at column ``split`` (no shared pixel,
    but no gap either) — so test accuracy measures generalization to
    unseen pixels of the scene, not crop memorization.  Raw [0,1] pixels are
    standardized with the fixed ``photo_patches`` constants.

    Sources that are missing on a stripped install are skipped;
    ``num_classes`` is however many resolve (≥4 required).  Deterministic
    for a given seed.
    """
    import importlib.util

    rng = np.random.default_rng(seed)
    xs_tr, ys_tr, xs_te, ys_te = [], [], [], []
    label = 0
    names = []
    for name, module, rel in _PHOTO_SOURCES:
        spec = importlib.util.find_spec(module)
        if spec is None or not spec.submodule_search_locations:
            continue
        path = f"{spec.submodule_search_locations[0]}/{rel}"
        try:
            from PIL import Image

            img = np.asarray(Image.open(path).convert("RGB"), np.float32) / 255.0
        # graftlint: disable=GL006 — best-effort asset probe: a stripped
        # install skips the class; the count check below still raises
        except Exception:  # noqa: BLE001 — stripped install: skip the class
            continue
        h, w = img.shape[:2]
        split = int(0.7 * w)
        # train x-origin ∈ [0, split−patch] ⇒ train pixels end at column
        # split−1; test x-origin ∈ [split, w−patch] ⇒ test pixels start at
        # column split.  Disjoint by construction, no shared pixel.
        if h < patch or split - patch < 1 or w - patch < split:
            continue

        def crops(n, x_lo, x_hi):
            ox = rng.integers(x_lo, x_hi + 1, size=n)
            oy = rng.integers(0, h - patch + 1, size=n)
            return np.stack([img[y : y + patch, x : x + patch]
                             for y, x in zip(oy, ox)])

        xs_tr.append(crops(train_per_class, 0, split - patch))
        xs_te.append(crops(test_per_class, split, w - patch))
        ys_tr.append(np.full(train_per_class, label, np.int32))
        ys_te.append(np.full(test_per_class, label, np.int32))
        names.append(name)
        label += 1
    if label < 4:
        raise RuntimeError(
            f"photo_patches found only {label} source photographs "
            f"({names}); need >= 4 for a meaningful task"
        )
    mean, std = NORMALIZATION["photo_patches"]
    norm = lambda x: (x - np.asarray(mean, np.float32)) / np.asarray(std, np.float32)
    return Dataset(
        norm(np.concatenate(xs_tr)), np.concatenate(ys_tr),
        norm(np.concatenate(xs_te)), np.concatenate(ys_te),
        label, name="photo_patches",
    )


def load_npz(path: str, dataset: str = "cifar10", num_classes: int | None = None) -> Dataset:
    """Load ``x_train/y_train/x_test/y_test`` arrays and apply the reference
    normalization for ``dataset``."""
    with np.load(path) as z:
        x_tr, y_tr = z["x_train"], z["y_train"]
        x_te, y_te = z["x_test"], z["y_test"]
    if x_tr.ndim == 4 and x_tr.shape[1] in (1, 3) and x_tr.shape[-1] not in (1, 3):
        x_tr = x_tr.transpose(0, 2, 3, 1)  # NCHW → NHWC
        x_te = x_te.transpose(0, 2, 3, 1)
    classes = int(num_classes or (int(y_tr.max()) + 1))
    return Dataset(
        normalize(x_tr, dataset),
        y_tr.reshape(-1).astype(np.int32),
        normalize(x_te, dataset),
        y_te.reshape(-1).astype(np.int32),
        classes,
        name=dataset,
    )


def load_tokens(path: str) -> Dataset:
    """A token data set: ``x_*`` and ``y_*``, two int32 arrays of one shape
    ``[n, width]``, kept as they are and fed row for row.  What a row holds
    is the task's, and the model that trains on it says how it reads it
    (``TokenDecoder.row_tokens``, ``row_positions``, ``judged_positions``);
    the loader and the loop read nothing off a row's width.  The two layouts
    there are:

    * next-token prediction (``chipbench/tasks/next_token.py:make``;
      ``mellum2``, ``keye_vl2``, ``qwen3_next``): ``x`` ``[n, S + 1]`` token
      ids, ``y`` ``[n, S + 1]`` the number of the document each position
      belongs to (documents packed end to end).  The model cuts a row into
      ``S`` inputs and the ``S`` next ids, and masks attention and the loss
      by the document numbers.
    * block diffusion (``chipbench/tasks/block_diffusion.py:make``;
      ``sdar``): ``x`` ``[n, 2 S]``, the row's noisy copy (the ``[MASK]`` id
      where a position is masked) and then its clean copy; ``y`` ``[n, 2
      S]``, the ``S`` document numbers and then, a position, the noise level
      ``t`` of its block in 65,536ths.  The model runs all ``2 S`` positions
      through every layer and predicts the ``S`` tokens' own ids at the
      masked positions.

    ``num_classes`` is the largest id + 1."""
    with np.load(path) as z:
        split = {k: np.ascontiguousarray(z[k], dtype=np.int32)
                 for k in ("x_train", "y_train", "x_test", "y_test")}
    for half in ("train", "test"):
        x, y = split["x_" + half], split["y_" + half]
        if x.ndim != 2 or x.shape != y.shape or x.shape[1] < 2:
            raise ValueError(
                f"{path}: x_{half} {x.shape} and y_{half} {y.shape} must be "
                f"the same [n, width] ids and document numbers (with what "
                f"else the task lays beside them)")
    return Dataset(split["x_train"], split["y_train"], split["x_test"],
                   split["y_test"], int(split["x_train"].max()) + 1,
                   name="tokens", token_rows=True)


def judged_positions(docs: np.ndarray) -> int:
    """Positions of next-token rows ``docs[n, S + 1]`` that carry a loss:
    those whose next id lies in the same document.  (A block-diffusion row's
    are its masked positions: ``models/sdar.py:Sdar.judged_positions``.)"""
    return int(np.sum(docs[:, 1:] == docs[:, :-1]))


def normalized_zero(dataset: str) -> np.ndarray:
    """The value a raw black pixel takes after normalization: ``(0−mean)/std``.
    The reference augments *before* normalizing (RandomCrop pads with 0, then
    Normalize — util.py:118-123); since our pipeline normalizes at load time,
    crop borders must be padded with this value to match that distribution."""
    if dataset not in NORMALIZATION:
        return np.zeros(1, np.float32)
    mean, std = NORMALIZATION[dataset]
    return (-np.asarray(mean, np.float32)) / np.asarray(std, np.float32)


def _augment_apply_python(
    x: np.ndarray, pad: int, pad_value, offs: np.ndarray, flip: np.ndarray
) -> np.ndarray:
    """Pure-Python apply path for precomputed (offs, flip) draws."""
    n, h, w, c = x.shape
    padded = np.broadcast_to(
        np.asarray(pad_value, np.float32), (n, h + 2 * pad, w + 2 * pad, c)
    ).copy()
    padded[:, pad : pad + h, pad : pad + w, :] = x
    out = np.empty_like(x)
    for i in range(n):
        oy, ox = offs[i]
        img = padded[i, oy : oy + h, ox : ox + w]
        out[i] = img[:, ::-1] if flip[i] else img
    return out


def _crop_flip_draws(rng: np.random.Generator, n: int, pad: int = 4):
    """What :func:`augment_crop_flip` takes from ``rng`` for ``n`` images:
    (crop offsets ``[n, 2]``, flip mask ``[n]``)."""
    return rng.integers(0, 2 * pad + 1, size=(n, 2)), rng.random(n) < 0.5


def augment_crop_flip(
    x: np.ndarray,
    rng: np.random.Generator,
    pad: int = 4,
    pad_value: np.ndarray | float = 0.0,
) -> np.ndarray:
    """Random crop (pad ``pad`` with ``pad_value``) + horizontal flip — the
    reference's CIFAR train transform (util.py:118-119).
    Pass ``pad_value=normalized_zero(dataset)`` for post-normalization parity.

    The random draws happen here in numpy (so the sample path is identical
    either way); the copy work dispatches to the native C++ kernel when the
    library is available *and* the call is in the kernel's domain — float32
    images, pad value broadcastable per channel — falling back to the Python
    loop otherwise, so output dtype/values never depend on whether g++ was
    around (``tests/test_native.py`` asserts the two apply paths bit-agree).
    A RuntimeError from the kernel propagates: with draws generated here its
    invariant guards cannot legitimately fire, so one firing is a real bug."""
    n, _, _, c = x.shape
    offs, flip = _crop_flip_draws(rng, n, pad)

    use_native = x.dtype == np.float32
    if use_native:
        try:
            np.broadcast_to(np.asarray(pad_value, np.float32), (c,))
        except ValueError:
            use_native = False
    if use_native:
        from ..native import native_augment_crop_flip

        out = native_augment_crop_flip(x, pad, pad_value, offs, flip)
        if out is not None:
            return out
    return _augment_apply_python(x, pad, pad_value, offs, flip)


class WorkerBatches:
    """Per-epoch iterator over worker-stacked batches.

    Each worker shuffles its own partition independently each epoch (seeded
    by (seed, epoch, worker)), mirroring per-rank DataLoader shuffling in the
    reference (util.py:132-135); batches are stacked to ``[N, B, ...]`` with
    static shapes (partial tail batches dropped, matching drop-last loaders).
    """

    def __init__(
        self,
        x: np.ndarray,
        y: np.ndarray,
        partitions: List[np.ndarray],
        batch_size: int,
        seed: int = 0,
        augment: bool = False,
        pad_value: np.ndarray | float = 0.0,
    ):
        self.x, self.y = x, y
        self.partitions = partitions
        self.batch_size = int(batch_size)
        self.seed = seed
        self.augment = augment
        self.pad_value = pad_value
        per = min(len(p) for p in partitions)
        self.batches_per_epoch = per // self.batch_size
        if self.batches_per_epoch == 0:
            raise ValueError(
                f"batch_size {batch_size} exceeds smallest partition ({per} examples)"
            )

    @property
    def num_workers(self) -> int:
        return len(self.partitions)

    def _epoch_rows(self, epoch: int) -> Iterator[np.ndarray]:
        """``idx[N, B]`` of each step of the epoch: row ``w`` is the next
        ``B`` examples of worker ``w``'s own shuffle of its partition."""
        B = self.batch_size
        orders = []
        for w, part in enumerate(self.partitions):
            rng = np.random.default_rng((self.seed, epoch, w))
            orders.append(part[rng.permutation(len(part))])
        for b in range(self.batches_per_epoch):
            yield np.stack([o[b * B : (b + 1) * B] for o in orders])

    def _augment(self, xb: np.ndarray, aug_rng) -> np.ndarray:
        flat = xb.reshape((-1,) + xb.shape[2:])
        return augment_crop_flip(
            flat, aug_rng, pad_value=self.pad_value).reshape(xb.shape)

    def epoch(self, epoch: int) -> Iterator[Tuple[np.ndarray, np.ndarray]]:
        aug_rng = np.random.default_rng((self.seed, epoch, 10**6))
        for idx in self._epoch_rows(epoch):
            xb = self.x[idx]  # [N, B, ...]
            if self.augment:
                xb = self._augment(xb, aug_rng)
            yield xb, self.y[idx]

    def epoch_into(self, epoch: int, xs_out: np.ndarray, ys_out: np.ndarray,
                   first: int = 0) -> None:
        """Write steps ``[first, first + len(xs_out))`` of the epoch into
        ``xs_out[k]``, ``ys_out[k]``: bit for bit what :meth:`epoch` yields
        at those steps, gathered straight into arrays the caller keeps
        (``[steps, N, B, ...]``, C-contiguous, of ``x``'s and ``y``'s dtype)
        instead of into a fresh ``[N, B, ...]`` array a step.

        ``mode="clip"`` is what lets ``np.take`` write through ``out``: the
        default ``"raise"`` gathers into a buffer of its own and copies.
        Nothing is clipped: every index comes from the partitions."""
        steps = len(xs_out)
        if not 0 <= first <= first + steps <= self.batches_per_epoch \
                or len(ys_out) != steps:
            raise ValueError(
                f"steps [{first}, {first + steps}) of an epoch of "
                f"{self.batches_per_epoch}, into {len(ys_out)} label steps")
        aug_rng = np.random.default_rng((self.seed, epoch, 10**6))
        rows = itertools.islice(self._epoch_rows(epoch), first + steps)
        for b, idx in enumerate(rows):
            if b < first:
                if self.augment:
                    # one stream an epoch: the steps before ``first`` take
                    # their draws from it all the same
                    _crop_flip_draws(aug_rng, idx.size)
                continue
            x_out = xs_out[b - first]
            np.take(self.x, idx, axis=0, out=x_out, mode="clip")
            np.take(self.y, idx, axis=0, out=ys_out[b - first], mode="clip")
            if self.augment:
                x_out[...] = self._augment(x_out, aug_rng)
