"""Dataset collection — parity with the reference's `DatasetCollection`
(`code/distributed_training/dataset/dataset_collection.py:28-69`), which
dispatches on a string type: 'Imagenet' (ImageFolder), 'CUB200'
(pandas-joined custom set), 'CIFAR10', 'Place365'.

TPU-era redesign:
* Datasets yield NumPy arrays (NHWC uint8 + int labels); all device
  placement is the loader's job, so the input path never routes through a
  "device 0" (the reference's known DP bottleneck, `Readme.md:15`).
* A deterministic `'Synthetic'` type is first-class so tests and CI never
  download anything (the reference downloads CIFAR-10 on every rank —
  `model_parallel.py:89-97`).
* CIFAR-10 reads the standard binary batches from disk when present.
"""

from __future__ import annotations

import dataclasses
import os
import pickle
import tarfile
from typing import Optional, Tuple

import numpy as np

# Channel statistics used by the reference transforms
# (`data_parallel.py:31-41` for CIFAR, `utils.py:13-14` for ImageNet-style).
CIFAR10_MEAN = np.array([0.4914, 0.4822, 0.4465], np.float32)
CIFAR10_STD = np.array([0.2023, 0.1994, 0.2010], np.float32)
IMAGENET_MEAN = np.array([0.485, 0.456, 0.406], np.float32)
IMAGENET_STD = np.array([0.229, 0.224, 0.225], np.float32)


@dataclasses.dataclass
class ArrayDataset:
    """In-memory dataset: images NHWC uint8 (or, for `kind='text'`,
    int32 token ids (N, T) still under the `images` field — the Loader
    treats text batches as raw pass-through), labels int64."""

    images: np.ndarray
    labels: np.ndarray
    num_classes: int
    kind: str = "image"  # 'image' | 'text' — drives Loader defaults

    def __len__(self) -> int:
        return len(self.labels)

    def gather(self, idx) -> Tuple[np.ndarray, np.ndarray]:
        return self.images[idx], self.labels[idx]


@dataclasses.dataclass
class LazyImageFolder:
    """Disk-backed ImageFolder split: holds paths + labels, decodes only
    the indices a batch asks for (`gather`). This is what lets the input
    pipeline hold ImageNet-scale trees without decoding the world up
    front; combined with the Loader's prefetch thread the decode overlaps
    the device step."""

    paths: list
    labels: np.ndarray
    num_classes: int
    image_size: int = 224

    def __len__(self) -> int:
        return len(self.labels)

    def gather(self, idx) -> Tuple[np.ndarray, np.ndarray]:
        from PIL import Image  # lazy; PIL ships with the torch stack

        images = np.empty(
            (len(idx), self.image_size, self.image_size, 3), np.uint8
        )
        for row, i in enumerate(np.asarray(idx)):
            with Image.open(self.paths[i]) as im:
                images[row] = np.asarray(
                    im.convert("RGB").resize(
                        (self.image_size, self.image_size)
                    ),
                    np.uint8,
                )
        return images, self.labels[idx]


def synthetic(
    num_examples: int = 2048,
    image_size: int = 32,
    num_classes: int = 10,
    seed: int = 0,
) -> ArrayDataset:
    """Deterministic fake data with learnable class structure (each class
    has a distinct mean image) so convergence smoke tests are meaningful.

    The class means are drawn from a FIXED rng independent of `seed`, so
    train (seed=1) and val (seed=2) splits share one task and val accuracy
    is a real generalization signal."""
    class_rng = np.random.RandomState(1234)
    class_means = class_rng.randint(0, 256, size=(num_classes, 1, 1, 3))
    rng = np.random.RandomState(seed)
    labels = rng.randint(0, num_classes, size=(num_examples,))
    noise = rng.randint(-40, 40, size=(num_examples, image_size, image_size, 3))
    images = np.clip(class_means[labels] + noise, 0, 255).astype(np.uint8)
    return ArrayDataset(images, labels.astype(np.int64), num_classes)


def synthetic_textures(
    num_examples: int = 2048,
    image_size: int = 32,
    num_classes: int = 10,
    seed: int = 0,
) -> ArrayDataset:
    """Procedural-texture classification with GENUINE generalization
    structure: each class is a texture FAMILY (two sinusoidal gratings
    with class-specific orientations/frequencies), and every sample
    draws fresh phases, amplitudes, a random spatial shift and pixel
    noise. Unlike `synthetic` (fixed class-mean images, which a
    2.3M-param model simply memorizes), no two samples
    share pixels, so val accuracy measures the learned texture
    statistics, not recall.

    Class parameters come from a FIXED rng independent of `seed`:
    train/val splits with different seeds share one task."""
    class_rng = np.random.RandomState(977)
    thetas = class_rng.uniform(0, np.pi, size=(num_classes, 2))
    freqs = class_rng.uniform(2.0, 6.0, size=(num_classes, 2))
    colors = class_rng.uniform(0.3, 1.0, size=(num_classes, 2, 3))
    rng = np.random.RandomState(seed)
    labels = rng.randint(0, num_classes, size=(num_examples,))
    yy, xx = np.meshgrid(
        np.linspace(0, 2 * np.pi, image_size),
        np.linspace(0, 2 * np.pi, image_size),
        indexing="ij",
    )
    images = np.empty(
        (num_examples, image_size, image_size, 3), np.float32
    )
    # Float64 temporaries (waves, noise) are built per CHUNK so peak RAM
    # stays ~tens of MB at the 50k size instead of multi-GB. NumPy fills
    # arrays in draw order, so chunked draws are bit-identical to the
    # full-size draws this replaced.
    chunk = 4096
    for g in range(2):  # two gratings per class, summed
        phase = rng.uniform(0, 2 * np.pi, size=(num_examples, 1, 1))
        amp = rng.uniform(0.6, 1.4, size=(num_examples, 1, 1))
        for s in range(0, num_examples, chunk):
            sl = slice(s, min(s + chunk, num_examples))
            lab = labels[sl]
            th = thetas[lab, g][:, None, None]
            fr = freqs[lab, g][:, None, None]
            wave = amp[sl] * np.sin(
                fr * (np.cos(th) * xx[None] + np.sin(th) * yy[None])
                + phase[sl]
            )
            contrib = wave[..., None] * colors[lab, g][:, None, None, :]
            # f64 sum, cast on assignment — the rounding the original
            # full-array formulation produced.
            images[sl] = contrib if g == 0 else images[sl] + contrib
    # Heavy pixel noise keeps the task in the discriminating mid-range
    # (tinycnn reaches ~80-90% in a few epochs, not an instant 100%).
    for s in range(0, num_examples, chunk):
        sl = slice(s, min(s + chunk, num_examples))
        images[sl] += rng.normal(0.0, 1.2, size=images[sl].shape)
    lo, hi = -3.0, 3.0
    # In-place, same op order as `(clip(x)-lo)/(hi-lo)*255` — no extra
    # full-size f32 temporaries.
    np.clip(images, lo, hi, out=images)
    images -= lo
    images /= hi - lo
    images *= 255.0
    return ArrayDataset(
        images.astype(np.uint8), labels.astype(np.int64), num_classes
    )


def synthetic_text(
    num_examples: int = 2048,
    seq_len: int = 64,
    num_classes: int = 4,
    vocab_size: int = 512,
    seed: int = 0,
) -> ArrayDataset:
    """Deterministic text-CLASSIFICATION dataset: each class is its own
    first-order Markov chain over tokens [1, vocab) (0 stays reserved
    for padding — BERT's attention mask is `ids != 0`), so a model can
    classify by transition statistics — a real, learnable signal for the
    transformer-family engines (the text twin of `synthetic`'s
    class-mean images).

    Like `synthetic`, the per-class chains come from a FIXED rng
    independent of `seed`, so train/val splits with different seeds
    share one task and val accuracy measures generalization."""
    v = vocab_size - 1  # usable tokens 1..vocab-1
    class_rng = np.random.RandomState(4321)
    # Per-class transition logits with strong structure (peaked rows).
    trans = class_rng.dirichlet(
        np.full(v, 0.05), size=(num_classes, v)
    )  # (C, v, v) rows sum to 1
    rng = np.random.RandomState(seed)
    labels = rng.randint(0, num_classes, size=(num_examples,))
    ids = np.empty((num_examples, seq_len), np.int32)
    ids[:, 0] = rng.randint(0, v, size=num_examples)
    # Vectorized walk: one step for ALL sequences at a time via inverse-
    # CDF sampling against each row's class-specific transition row.
    cdf = np.cumsum(trans, axis=-1)  # (C, v, v)
    for t in range(1, seq_len):
        u = rng.rand(num_examples, 1)
        row_cdf = cdf[labels, ids[:, t - 1]]  # (N, v)
        # Clip: a float cumsum row can top out at 1-eps rather than 1.0,
        # and a u above it would index one past the table.
        ids[:, t] = np.minimum((u > row_cdf).sum(axis=1), v - 1)
    return ArrayDataset(
        ids + 1, labels.astype(np.int64), num_classes, kind="text"
    )


def _load_cifar10_batches(root: str) -> Optional[Tuple[np.ndarray, ...]]:
    """Read the python-version CIFAR-10 batches (cifar-10-batches-py) if the
    archive or extracted dir exists under `root`. No network access."""
    d = os.path.join(root, "cifar-10-batches-py")
    tar = os.path.join(root, "cifar-10-python.tar.gz")
    if not os.path.isdir(d) and os.path.isfile(tar):
        with tarfile.open(tar) as tf:
            tf.extractall(root, filter="data")
    if not os.path.isdir(d):
        return None

    def read(name):
        with open(os.path.join(d, name), "rb") as f:
            entry = pickle.load(f, encoding="bytes")
        x = entry[b"data"].reshape(-1, 3, 32, 32).transpose(0, 2, 3, 1)
        y = np.asarray(entry[b"labels"], np.int64)
        return x, y

    xs, ys = zip(*(read(f"data_batch_{i}") for i in range(1, 6)))
    xt, yt = read("test_batch")
    return np.concatenate(xs), np.concatenate(ys), xt, yt


def cifar10(root: str = "./data", *, fallback_synthetic: bool = True):
    """CIFAR-10 train/val pair (`dataset_collection.py:62-65`). Falls back
    to class-structured synthetic data when the files are absent so every
    entry point runs hermetically."""
    loaded = _load_cifar10_batches(root)
    if loaded is None:
        if not fallback_synthetic:
            raise FileNotFoundError(f"CIFAR-10 not found under {root}")
        return (
            synthetic(50_000, 32, 10, seed=1),
            synthetic(10_000, 32, 10, seed=2),
        )
    xtr, ytr, xte, yte = loaded
    return ArrayDataset(xtr, ytr, 10), ArrayDataset(xte, yte, 10)


_IMG_EXTS = {
    ".jpg", ".jpeg", ".png", ".bmp", ".gif", ".webp", ".ppm", ".pgm",
    ".tif", ".tiff",
}


def image_folder(root: str, split_dirs=("train", "val"), image_size: int = 224,
                 *, lazy: bool = True):
    """ImageFolder-style tree ('Imagenet'/'Place365' types,
    `dataset_collection.py:36-47,66-69`). `lazy=True` (default) returns
    `LazyImageFolder` splits that decode per batch on demand — the
    chip-rate path for large trees; `lazy=False` eagerly decodes into an
    in-memory `ArrayDataset` (handy for small fixtures/tests). Decoding
    uses torch's bundled PIL; the batched crop/flip/normalize hot loop is
    the C++ `native/` module either way."""
    out = []
    for split in split_dirs:
        base = os.path.join(root, split)
        classes = sorted(
            d for d in os.listdir(base)
            if os.path.isdir(os.path.join(base, d))
        )
        idx = {c: i for i, c in enumerate(classes)}
        paths, labels = [], []
        for c in classes:
            cdir = os.path.join(base, c)
            for fname in sorted(os.listdir(cdir)):
                # Extension filter (torchvision ImageFolder semantics):
                # a stray .DS_Store / checksum file must not become a
                # mid-epoch decode error hours into a lazy run.
                if os.path.splitext(fname)[1].lower() not in _IMG_EXTS:
                    continue
                paths.append(os.path.join(cdir, fname))
                labels.append(idx[c])
        ds = LazyImageFolder(
            paths, np.asarray(labels, np.int64), len(classes), image_size
        )
        if not lazy:
            images, lab = ds.gather(np.arange(len(ds)))
            ds = ArrayDataset(images, lab, ds.num_classes)
        out.append(ds)
    return tuple(out)


def cub200(root: str, image_size: int = 224):
    """CUB-200-2011 via its images.txt / train_test_split.txt /
    image_class_labels.txt metadata — same join the reference does with
    pandas (`dataset_collection.py:8-27`), without the pandas dependency."""
    from PIL import Image

    def read_table(name):
        with open(os.path.join(root, name)) as f:
            return [line.split() for line in f.read().splitlines() if line]

    paths = {int(i): p for i, p in read_table("images.txt")}
    is_train = {int(i): v == "1" for i, v in read_table("train_test_split.txt")}
    label = {int(i): int(l) - 1 for i, l in read_table("image_class_labels.txt")}

    splits = {True: ([], []), False: ([], [])}
    for i, rel in sorted(paths.items()):
        with Image.open(os.path.join(root, "images", rel)) as im:
            arr = np.asarray(
                im.convert("RGB").resize((image_size, image_size)), np.uint8
            )
        imgs, labs = splits[is_train[i]]
        imgs.append(arr)
        labs.append(label[i])
    train = ArrayDataset(
        np.stack(splits[True][0]), np.asarray(splits[True][1], np.int64), 200
    )
    val = ArrayDataset(
        np.stack(splits[False][0]), np.asarray(splits[False][1], np.int64), 200
    )
    return train, val


class DatasetCollection:
    """String-keyed factory with the reference's exact API shape:
    `DatasetCollection(type, path, compose_train, compose_val).init() ->
    (train, val)` (`dataset_collection.py:28-35`). Types: 'CIFAR10',
    'Imagenet', 'CUB200', 'Place365', plus 'Synthetic' and
    'SyntheticText' (token-id classification for the transformer
    family).

    `compose_train` / `compose_val` mirror the reference's
    caller-supplied torchvision Compose arguments: per-batch callables
    `(images, labels) -> (images, labels)` applied by the Loader INSTEAD
    of its built-in augment/normalize path (`Loader.transform`). Leave
    them None for the reference's default CIFAR transforms."""

    def __init__(self, dataset_type: str, dataset_path: str = "./data",
                 compose_train=None, compose_val=None,
                 image_size: int = 224):
        self.dataset_type = dataset_type
        self.dataset_path = dataset_path
        self.compose_train = compose_train
        self.compose_val = compose_val
        self.image_size = image_size

    def init(self):
        t = self.dataset_type
        if t == "CIFAR10":
            return cifar10(self.dataset_path)
        if t == "Synthetic":
            return synthetic(2048, 32, 10, seed=1), synthetic(512, 32, 10, seed=2)
        if t == "SyntheticText":
            return (
                synthetic_text(4096, 64, 4, seed=1),
                synthetic_text(1024, 64, 4, seed=2),
            )
        if t == "SyntheticTextures":
            return (
                synthetic_textures(50_000, 32, 10, seed=1),
                synthetic_textures(10_000, 32, 10, seed=2),
            )
        if t in ("Imagenet", "Place365"):
            return image_folder(self.dataset_path, image_size=self.image_size)
        if t == "CUB200":
            return cub200(self.dataset_path, image_size=self.image_size)
        raise ValueError(f"unknown dataset type {t!r}")
