"""The reference's open question, answered: shuffle's per-batch cost.

The reference measured 0.89 s/batch with shuffle=True vs 0.32 without
on its torch DataLoader and left it a mystery (`Readme.md:296-301`).
Hypothesis encoded here: the cost was never the permutation (an O(N)
numpy shuffle is microseconds per batch) — it is MEMORY LOCALITY of the
per-sample gather. A shuffled epoch gathers 512 rows scattered across
the whole 150 MB array (one cache-missing random access per row), a
sequential epoch reads contiguously; torch pays it per SAMPLE in Python
`__getitem__` + collate, amplifying the miss cost.

This script measures, on this framework's batched loader:
  1. pure batch production (no device, no training): shuffle on/off,
     augment on/off, prefetch on/off;
  2. the same with a sorted-within-batch gather (locality restored
     while keeping the epoch-level permutation) — isolating the
     locality effect from everything else.

Writes experiments/shuffle_cost.json.

Run on a QUIET host: python experiments/shuffle_cost.py
"""

from __future__ import annotations

import json
import os
import sys
import time

sys.path.insert(0, os.path.dirname(os.path.dirname(os.path.abspath(__file__))))

import numpy as np  # noqa: E402

from distributed_model_parallel_tpu.data.datasets import (  # noqa: E402
    CIFAR10_MEAN,
    CIFAR10_STD,
    synthetic,
)
from distributed_model_parallel_tpu.data.loader import Loader  # noqa: E402

N = 50_000
BATCH = 512


def time_epoch(loader, epochs=2):
    """s/batch over `epochs` full iterations (first epoch warms page
    cache/native build; the SECOND is reported)."""
    per = []
    for ep in range(epochs):
        loader.set_epoch(ep)
        t0 = time.perf_counter()
        n = 0
        for _ in loader:
            n += 1
        per.append((time.perf_counter() - t0) / n)
    return per[-1], n


def main():
    ds = synthetic(N, 32, 10, seed=1)
    rows = []
    for shuffle in (False, True):
        for augment in (False, True):
            for prefetch in (0, 2):
                loader = Loader(
                    ds, batch_size=BATCH, shuffle=shuffle,
                    augment=augment, mean=CIFAR10_MEAN, std=CIFAR10_STD,
                    prefetch=prefetch,
                )
                s, n = time_epoch(loader)
                rows.append({
                    "shuffle": shuffle, "augment": augment,
                    "prefetch": prefetch,
                    "s_per_batch": round(s, 5), "batches": n,
                })
                print(rows[-1], flush=True)

    # Locality probe: same epoch permutation, but each BATCH's indices
    # sorted before the gather (permutation across batches preserved).
    class SortedGatherLoader(Loader):
        def _make_batch(self, b, idx, use_native):
            return super()._make_batch(b, np.sort(idx), use_native)

    loader = SortedGatherLoader(
        ds, batch_size=BATCH, shuffle=True, augment=True,
        mean=CIFAR10_MEAN, std=CIFAR10_STD, prefetch=0,
    )
    s, _ = time_epoch(loader)
    rows.append({
        "shuffle": True, "augment": True, "prefetch": 0,
        "sorted_within_batch": True, "s_per_batch": round(s, 5),
    })
    print(rows[-1], flush=True)

    path = os.path.join(os.path.dirname(os.path.abspath(__file__)),
                        "shuffle_cost.json")
    with open(path, "w") as f:
        json.dump({"n": N, "batch": BATCH, "rows": rows}, f, indent=1)
    print(f"wrote {path}")


if __name__ == "__main__":
    main()
