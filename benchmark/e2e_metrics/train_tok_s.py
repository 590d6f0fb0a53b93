"""Tokens of the whole `Trainer.train_epoch` calls completed in the window
over their wall time. Each epoch ends in the Trainer's own value fetch,
so the time is the device's; the input pipeline runs. An epoch that
carried the device trace is left out.
"""

from benchmark.harness.stats import untraced


def compute(record):
    epochs = untraced(record["epochs"])
    steps = sum(e["steps"] for e in epochs)
    return steps * record["tokens_per_step"] / sum(e["wall_s"] for e in epochs)
