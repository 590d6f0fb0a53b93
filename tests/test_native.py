"""Native C++ input-path tests: the augment/normalize hot loop
(`native/augment.cpp`) must be bit-exact with the NumPy reference, and
the Loader's prefetch/worker settings must never change the data.
"""

import time

import numpy as np
import pytest

from distributed_model_parallel_tpu import native
from distributed_model_parallel_tpu.data.datasets import (
    CIFAR10_MEAN,
    CIFAR10_STD,
    synthetic,
)
from distributed_model_parallel_tpu.data.loader import (
    Loader,
    _crop_flip_numpy,
    _draw_augment,
    normalize,
)

needs_native = pytest.mark.skipif(
    not native.available(), reason="native library failed to build"
)


def _images(n=64, hw=32, seed=0):
    rng = np.random.RandomState(seed)
    return rng.randint(0, 256, size=(n, hw, hw, 3)).astype(np.uint8)


@needs_native
@pytest.mark.parametrize("workers", [1, 4])
def test_augment_normalize_bit_exact(workers):
    """C++ crop+flip+normalize == NumPy crop+flip+normalize, bitwise
    (same draws, same f32 op order), at any thread count."""
    images = _images()
    rng = np.random.RandomState(7)
    ys, xs, flips = _draw_augment(rng, len(images), 4)
    want = normalize(
        _crop_flip_numpy(images, ys, xs, flips, 4),
        CIFAR10_MEAN, CIFAR10_STD,
    ).astype(np.float32)
    got = native.augment_normalize(
        images, ys, xs, flips, 4, CIFAR10_MEAN, CIFAR10_STD,
        workers=workers,
    )
    np.testing.assert_array_equal(got, want)


@needs_native
def test_normalize_only_bit_exact():
    images = _images(n=16)
    want = normalize(images, CIFAR10_MEAN, CIFAR10_STD).astype(np.float32)
    got = native.normalize(images, CIFAR10_MEAN, CIFAR10_STD, workers=2)
    np.testing.assert_array_equal(got, want)


def _loader_epochs(**kw):
    ds = synthetic(num_examples=256, num_classes=4, image_size=32, seed=0)
    loader = Loader(
        ds, batch_size=32, shuffle=True, augment=True,
        mean=CIFAR10_MEAN, std=CIFAR10_STD, seed=3, **kw,
    )
    loader.set_epoch(1)
    return [(im.copy(), lb.copy()) for im, lb in loader]


@needs_native
def test_loader_identical_across_backends_and_workers():
    """The Loader's batches are a pure function of (seed, epoch, host,
    batch index): native vs NumPy backend, any workers/prefetch depth —
    identical streams. (This is what makes `-j` a pure throughput knob.)"""
    base = _loader_epochs(use_native=False, workers=1, prefetch=0)
    for kw in (
        dict(use_native=True, workers=1, prefetch=0),
        dict(use_native=True, workers=4, prefetch=2),
        dict(use_native=False, workers=1, prefetch=2),
    ):
        other = _loader_epochs(**kw)
        assert len(other) == len(base)
        for (im_a, lb_a), (im_b, lb_b) in zip(base, other):
            np.testing.assert_array_equal(lb_a, lb_b)
            np.testing.assert_array_equal(im_a, im_b)


def test_prefetch_propagates_worker_errors():
    """An exception inside the producer thread surfaces to the consumer
    (not a silent truncated epoch)."""

    class Broken:
        num_classes = 4

        def __len__(self):
            return 64

        def gather(self, idx):
            raise RuntimeError("disk on fire")

    loader = Loader(
        Broken(), batch_size=16, shuffle=False, prefetch=2,
        mean=CIFAR10_MEAN, std=CIFAR10_STD,
    )
    with pytest.raises(RuntimeError, match="disk on fire"):
        list(loader)


@needs_native
def test_native_micro_bench_reports():
    """Loader micro-bench: the native path sustains a
    real rate on this host. The floor is deliberately modest — this CI
    host is 1 core — the point is the harness exists and the number is
    reported; on a TPU host `-j` scales the pool."""
    images = _images(n=512)
    rng = np.random.RandomState(0)
    ys, xs, flips = _draw_augment(rng, len(images), 4)
    t0 = time.perf_counter()
    reps = 4
    for _ in range(reps):
        native.augment_normalize(
            images, ys, xs, flips, 4, CIFAR10_MEAN, CIFAR10_STD, workers=1
        )
    rate = len(images) * reps / (time.perf_counter() - t0)
    print(f"\nnative augment+normalize: {rate:.0f} img/s (1 thread)")
    assert rate > 500  # 32x32 imgs; even 1 slow core clears this easily


def test_prefetch_producer_stops_on_early_abandon():
    """Abandoning the iterator mid-epoch (Trainer's --steps-per-epoch
    truncation) must stop and join the producer thread — no thread or
    staged batches may outlive the epoch."""
    import threading

    base_threads = threading.active_count()
    ds = synthetic(num_examples=512, num_classes=4, image_size=32, seed=0)
    loader = Loader(
        ds, batch_size=16, shuffle=False, augment=True,
        mean=CIFAR10_MEAN, std=CIFAR10_STD, prefetch=2,
    )
    it = iter(loader)
    next(it)
    next(it)
    it.close()  # GeneratorExit at the yield -> finally stops producer
    deadline = time.time() + 5
    while threading.active_count() > base_threads and time.time() < deadline:
        time.sleep(0.05)
    assert threading.active_count() == base_threads


# ------------------------------------------- which binary may be loaded


@pytest.fixture
def fresh_native(tmp_path, monkeypatch):
    """The loader pointed at a private copy of the source, with its
    once-per-process state reset (and restored afterwards)."""
    import shutil

    src = tmp_path / "augment.cpp"
    shutil.copy(native._SRC, src)
    monkeypatch.setattr(native, "_DIR", str(tmp_path))
    monkeypatch.setattr(native, "_SRC", str(src))
    monkeypatch.setattr(native, "_lib", None)
    monkeypatch.setattr(native, "_tried", False)
    return src


@needs_native
def test_library_is_keyed_on_the_source_hash(fresh_native, tmp_path):
    """The binary's name carries a hash of augment.cpp, so only a
    library built from THIS source loads: a planted file under the old
    mtime-keyed name is ignored, and editing the source changes the
    name (the next process rebuilds) whatever the mtimes say."""
    import hashlib
    import os

    planted = tmp_path / "libdmp_native.so"
    planted.write_bytes(b"not a shared object")
    os.utime(planted, (2**31, 2**31))  # newer than any source
    want = os.path.join(
        str(tmp_path),
        "libdmp_native-"
        + hashlib.sha256(fresh_native.read_bytes()).hexdigest()[:8] + ".so",
    )
    assert native._so_path() == want and not os.path.exists(want)
    assert native.lib() is not None  # built, then loaded, from want
    assert os.path.exists(want)
    assert not list(tmp_path.glob("*.tmp"))  # renamed into place
    fresh_native.write_text(fresh_native.read_text() + "\n// edited\n")
    assert native._so_path() != want


def test_missing_toolchain_says_so_once_and_loader_takes_numpy(
    fresh_native, monkeypatch, capfd
):
    """No g++: lib() is None — not an exception — stderr names the path
    taken exactly once, and the Loader's batches are the NumPy path's."""
    def no_compiler(*args, **kwargs):
        raise FileNotFoundError("g++")

    monkeypatch.setattr(native.subprocess, "run", no_compiler)
    assert native.lib() is None
    assert native.lib() is None and not native.available()
    err = capfd.readouterr().err
    assert err.count("[native]") == 1
    assert "NumPy path" in err and "FileNotFoundError" in err
    auto = _loader_epochs(use_native=None, workers=1, prefetch=0)
    numpy_path = _loader_epochs(use_native=False, workers=1, prefetch=0)
    for (im_a, lb_a), (im_b, lb_b) in zip(auto, numpy_path):
        np.testing.assert_array_equal(im_a, im_b)
        np.testing.assert_array_equal(lb_a, lb_b)
    with pytest.raises(RuntimeError, match="failed to build"):
        _loader_epochs(use_native=True)
