"""Per-host sharded input pipeline with prefetch and a native hot loop.

Fixes the two input-path defects SURVEY.md calls out:
* the reference has **no DistributedSampler** — every rank shuffles the
  whole dataset independently (`utils.py:21` `train_sampler=None`); here
  each host deterministically owns a disjoint shard per epoch.
* the reference funnels all data through device 0 (`Readme.md:15`); here
  each host feeds only its local shard, and the engine's `shard_batch`
  places it along the 'data' mesh axis.

Augmentations are the reference's CIFAR train transforms
(`data_parallel.py:32-37`): random crop 32 with padding 4, random
horizontal flip, normalize. Two implementations with identical numerics:
a vectorized NumPy path, and the C++ native module
(`native/augment.cpp`, std::thread pool, GIL released) used
automatically when it builds. `workers` (the CLI's `-j`) sets both the
native thread count and the number of batches prepared concurrently;
`prefetch` batches are staged ahead of the training loop so augmentation
overlaps the device step.
"""

from __future__ import annotations

import dataclasses
import queue
import threading
from typing import Iterator, Optional, Tuple

import numpy as np

from distributed_model_parallel_tpu import native
from distributed_model_parallel_tpu.data.datasets import ArrayDataset


def random_crop_flip(
    images: np.ndarray,
    rng: np.random.RandomState,
    padding: int = 4,
) -> np.ndarray:
    """Batched RandomCrop(pad)+RandomHorizontalFlip on uint8 NHWC,
    vectorized: one sliding-window view + one fancy-index gather, no
    per-image Python loop."""
    ys, xs, flips = _draw_augment(rng, len(images), padding)
    return _crop_flip_numpy(images, ys, xs, flips, padding)


def _draw_augment(rng: np.random.RandomState, n: int, padding: int):
    ys = rng.randint(0, 2 * padding + 1, size=n)
    xs = rng.randint(0, 2 * padding + 1, size=n)
    flips = rng.rand(n) < 0.5
    return ys, xs, flips


def _crop_flip_numpy(images, ys, xs, flips, padding):
    n, h, w, c = images.shape
    padded = np.pad(
        images,
        ((0, 0), (padding, padding), (padding, padding), (0, 0)),
        mode="constant",
    )
    # (n, 2p+1, 2p+1, c, h, w) view; gather each image's window.
    windows = np.lib.stride_tricks.sliding_window_view(
        padded, (h, w), axis=(1, 2)
    )
    out = windows[np.arange(n), ys, xs]          # (n, c, h, w)
    out = np.ascontiguousarray(out.transpose(0, 2, 3, 1))  # NHWC
    out[flips] = out[flips, :, ::-1]
    return out


def normalize(images: np.ndarray, mean: np.ndarray, std: np.ndarray) -> np.ndarray:
    return (images.astype(np.float32) / 255.0 - mean) / std


def device_normalizer(mean: np.ndarray, std: np.ndarray):
    """The same `/255 - mean / std` normalize as a jit-traceable device
    transform, for `Engine.input_transform`. Pair with
    `Loader(device_normalize=True)`: the batch crosses the host->device
    link as uint8 (4x fewer bytes than host-normalized f32) and XLA
    fuses the normalize into the first conv's input."""
    mean = np.asarray(mean, np.float32)
    std = np.asarray(std, np.float32)

    def transform(images):
        import jax.numpy as jnp  # keep this module importable without jax

        m = jnp.asarray(mean)
        s = jnp.asarray(std)
        return (images.astype(jnp.float32) / 255.0 - m) / s

    return transform


@dataclasses.dataclass
class Loader:
    """Deterministic, host-sharded batch iterator.

    `process_index/process_count` implement the missing DistributedSampler:
    after the global epoch shuffle (seeded by epoch, identical on all
    hosts), each host takes every `process_count`-th index. `drop_last` is
    forced on for training so batch shapes are static for XLA; with
    `drop_last=False` a ragged final batch is padded back to `batch_size`
    with label -1 rows (masked out by metrics) for the same reason.

    `batch_size` is this host's PER-HOST batch; `cli.common.build_loaders`
    divides the user-facing global batch by `jax.process_count()` before
    constructing Loaders.

    `workers` (the reference's `-j`, `model_parallel.py:31-33`) sets the
    C++ augmentation module's per-batch thread-pool size (it does not add
    Python-side concurrency; on the NumPy fallback it is a no-op).
    `prefetch` > 0 runs ONE background producer thread staging up to
    `prefetch` ready batches ahead of the training loop — with the native
    backend the augmentation call releases the GIL, so staging genuinely
    overlaps the device step. Augmentation draws are keyed by (seed,
    epoch, host, batch index), so results are identical for every
    `workers`/`prefetch` setting and for the native vs NumPy backends
    (`use_native=None` auto-detects)."""

    dataset: ArrayDataset
    batch_size: int
    shuffle: bool = True
    augment: bool = False
    mean: Optional[np.ndarray] = None
    std: Optional[np.ndarray] = None
    seed: int = 0
    process_index: int = 0
    process_count: int = 1
    drop_last: bool = True
    workers: int = 1
    prefetch: int = 2
    use_native: Optional[bool] = None  # None = auto-detect
    # Yield AUGMENTED UINT8 batches (no host normalize, no float cast):
    # the engine normalizes on device via `input_transform =
    # device_normalizer(mean, std)`. Cuts host->device bytes 4x.
    device_normalize: bool = False
    # Yield gathered batches untouched (no augment, no normalize, no
    # dtype cast) — for non-image data (token ids) where /255 would be
    # nonsense. Ragged-final-batch padding still applies.
    raw: bool = False
    # Caller-supplied per-batch transform `(arrays, labels) -> (arrays,
    # labels)` REPLACING the built-in augment/normalize — the
    # reference's compose_train/compose_val surface
    # (`dataset_collection.py:28-35`). Runs on host, before padding.
    transform: Optional[callable] = None

    def __post_init__(self):
        if self.batch_size < 1:
            raise ValueError(f"batch_size must be >= 1, got {self.batch_size}")
        if self.device_normalize and self.use_native is True:
            raise ValueError(
                "device_normalize=True conflicts with use_native=True: "
                "the native hot loop is the fused host-side "
                "augment+NORMALIZE; with device-side normalization the "
                "augmentation runs the vectorized NumPy uint8 path"
            )
        if self.use_native is True and self.mean is None:
            raise ValueError(
                "use_native=True requires mean/std (the native hot loop "
                "is the fused augment+normalize)"
            )
        self._epoch = 0

    def set_epoch(self, epoch: int) -> None:
        self._epoch = epoch

    def __len__(self) -> int:
        # Every host sees the same padded shard size (ceil(n/P)), so batch
        # counts agree across hosts — without this, a host with a shorter
        # shard exits its epoch loop early and the remaining hosts hang in
        # the next collective (torch's DistributedSampler pads for the same
        # reason).
        per_host = -(-len(self.dataset) // self.process_count)
        if self.drop_last:
            return per_host // self.batch_size
        return -(-per_host // self.batch_size)

    # ------------------------------------------------------------ batches

    def _native_ok(self) -> bool:
        if self.use_native is False:
            return False
        ok = native.available()
        if self.use_native is True and not ok:
            raise RuntimeError(
                "use_native=True but the native library failed to build"
            )
        return ok

    def _gather(self, idx):
        ds = self.dataset
        if hasattr(ds, "gather"):
            return ds.gather(idx)
        return ds.images[idx], ds.labels[idx]

    def _make_batch(self, b: int, idx, use_native: bool):
        """Assemble batch `b` (gather, augment, normalize, pad). Pure
        function of (seed, epoch, host, b) — order-independent by
        construction, which is what pins the determinism guarantee."""
        images, labels = self._gather(idx)
        aug_rng = np.random.RandomState(
            ((self.seed + self._epoch) * 1009 + self.process_index) * 7919
            + b
        )
        if self.transform is not None:
            images, labels = self.transform(images, labels)
        elif self.raw:
            pass  # token ids etc.: ship exactly what the dataset holds
        elif self.device_normalize:
            # Engine-side normalize: ship the (augmented) uint8 bytes.
            # The augmentation draws use the SAME keyed RNG stream, so a
            # device_normalize run sees identical crops/flips to a
            # host-normalize run of the same (seed, epoch, host, batch).
            if self.augment:
                ys, xs, flips = _draw_augment(aug_rng, len(images), 4)
                images = _crop_flip_numpy(images, ys, xs, flips, 4)
        elif self.augment:
            ys, xs, flips = _draw_augment(aug_rng, len(images), 4)
            if (use_native and self.mean is not None
                    and images.dtype == np.uint8):
                images = native.augment_normalize(
                    images, ys, xs, flips, 4, self.mean, self.std,
                    workers=self.workers,
                )
            else:
                images = _crop_flip_numpy(images, ys, xs, flips, 4)
                images = self._normalize_np(images)
        elif use_native and self.mean is not None and images.dtype == np.uint8:
            images = native.normalize(
                images, self.mean, self.std, workers=self.workers
            )
        else:
            images = self._normalize_np(images)
        if len(idx) < self.batch_size:
            # Ragged final batch (drop_last=False): pad to the static
            # batch shape so XLA never sees a second shape and the
            # 'data'-axis sharding stays divisible. Padding rows carry
            # label -1; metrics/losses mask them out (metrics.py
            # valid_count).
            pad_n = self.batch_size - len(idx)
            images = np.concatenate(
                [images, np.zeros((pad_n,) + images.shape[1:], images.dtype)]
            )
            labels = np.concatenate(
                [labels, np.full((pad_n,), -1, labels.dtype)]
            )
        return images, labels

    def __iter__(self) -> Iterator[Tuple[np.ndarray, np.ndarray]]:
        n = len(self.dataset)
        rng = np.random.RandomState(self.seed + self._epoch)
        order = rng.permutation(n) if self.shuffle else np.arange(n)
        # Pad to a multiple of process_count by wrapping (DistributedSampler
        # semantics) so every host's strided shard has identical length.
        per_host = -(-n // self.process_count)
        pad = per_host * self.process_count - n
        if pad:
            # np.tile handles pad > n (tiny dataset, many hosts) — torch's
            # DistributedSampler repeats the index list the same way.
            order = np.concatenate([order, np.tile(order, -(-pad // n))[:pad]])
        mine = order[self.process_index::self.process_count]
        nb = len(self)
        use_native = self._native_ok() and self.mean is not None
        batches = (
            mine[b * self.batch_size:(b + 1) * self.batch_size]
            for b in range(nb)
        )
        indexed = (
            (b, idx) for b, idx in enumerate(batches) if len(idx) > 0
        )
        if self.prefetch <= 0:
            # Synchronous path: `workers` still sizes the native pool
            # inside each _make_batch call; there is no Python thread.
            for b, idx in indexed:
                yield self._make_batch(b, idx, use_native)
            return
        yield from self._prefetched(indexed, use_native)

    def _normalize_np(self, images):
        if self.mean is not None:
            return normalize(images, self.mean, self.std)
        return images.astype(np.float32) / 255.0

    def _prefetched(self, indexed, use_native: bool):
        """Producer thread keeps up to `prefetch` ready batches in a
        bounded queue; with the native backend the augmentation call
        releases the GIL, so production genuinely overlaps the consumer's
        device step. Batches are yielded strictly in order (determinism
        is per-batch-seeded either way). The consumer may abandon the
        iterator early (e.g. Trainer's --steps-per-epoch truncation);
        the finally block stops and joins the producer so no thread or
        staged batch outlives the epoch."""
        depth = max(self.prefetch, 1)
        q: "queue.Queue" = queue.Queue(maxsize=depth)
        sentinel = object()
        stop = threading.Event()
        error = []

        def put_until_stop(item) -> bool:
            """Blocking put that gives up when the consumer signalled
            stop (early abandon). The SENTINEL must go through this too:
            a put_nowait sentinel can be dropped while the queue is still
            full of the last batches, deadlocking a consumer that then
            waits forever on q.get()."""
            while not stop.is_set():
                try:
                    q.put(item, timeout=0.1)
                    return True
                except queue.Full:
                    continue
            return False

        def produce():
            try:
                for b, idx in indexed:
                    if stop.is_set():
                        return
                    if not put_until_stop(
                        self._make_batch(b, idx, use_native)
                    ):
                        return
            except BaseException as e:  # noqa: BLE001 — surfaced below
                error.append(e)
            finally:
                put_until_stop(sentinel)

        t = threading.Thread(target=produce, daemon=True)
        t.start()
        try:
            while True:
                item = q.get()
                if item is sentinel:
                    break
                yield item
        finally:
            stop.set()
            try:  # unblock a producer stuck on a full queue
                while True:
                    q.get_nowait()
            except queue.Empty:
                pass
            t.join(timeout=10)
        if error:
            raise error[0]
