"""The 90th percentile over finished requests of the time per output token
(see serve_tpot_p50_ms): the highest percentile that keeps ten samples
beyond it at 100 requests. The driver marks the run incorrect when the
drain finished too few requests for that.
"""

from benchmark.harness.stats import percentile, tpot_ms


def compute(record):
    return percentile(tpot_ms(record["finished"]), 90)
