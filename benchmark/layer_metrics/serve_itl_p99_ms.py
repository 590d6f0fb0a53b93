"""Layer: serving/scheduler.py. 99th percentile of the gaps between two
tokens of one request, over every gap of the drain
(`paged_stats["timeline"]`): the pass in which the scheduler put other
slots' prefill chunks between a user's two tokens. A drain of fewer
than 1,000 gaps has not ten samples beyond it and reports none.
"""

from benchmark.harness.timeline import gap_percentile_ms


def compute(record):
    return gap_percentile_ms(record, 99.0)
