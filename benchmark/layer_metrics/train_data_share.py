"""Layer: training/trainer.py, data/. Time the Trainer's loop spent pulling
batches from the loader (`EpochStats.data_time`) over the epochs' wall
time. With one-deep prefetch it overlaps the device; it shows when the
input pipeline becomes the longer leg.
"""

from benchmark.harness.stats import untraced


def compute(record):
    epochs = untraced(record["epochs"])
    return 100.0 * sum(e["data_s"] for e in epochs) / sum(
        e["wall_s"] for e in epochs
    )
