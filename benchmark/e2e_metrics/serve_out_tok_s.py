"""Generated tokens per second while the drain's queue was not empty:
the tokens emitted from the start of the one measured `engine.run` to
the first token of the request that got one last, over that time
(`harness/stats.saturated`, from `Scheduler.finished`). The drain-out
after it, in which slots empty and nothing refills them, is left out:
it says how a drain ends, not what the system sustains. Host clock.
"""

from benchmark.harness.stats import saturated


def compute(record):
    window = saturated(record["finished"])
    return window["tokens"] / window["seconds"]
