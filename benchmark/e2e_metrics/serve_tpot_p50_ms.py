"""Median over finished requests of the time per output token: from a
request's first token to its last, over the tokens after the first
(`Scheduler.finished`: `(total_s - prefill_s) / (len(tokens) - 1)`).
It holds everything a user waits through between two tokens: the decode
step, other slots' prefill chunks, copy-on-write and host sampling.
"""

from benchmark.harness.stats import percentile, tpot_ms


def compute(record):
    return percentile(tpot_ms(record["finished"]), 50)
