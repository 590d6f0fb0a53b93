"""Layer: serving/scheduler.py. Median over the drain's requests of the
time from admission to the first token (`paged_stats["timeline"]`): the
prompt's chunks, the decode steps they share passes with, and the wait
for the last chunk's fetch. Not time to first token from arrival: every
request of a drain is handed over at its start, so the queue wait
before admission is its place in the list (`queued_ms` in the `info`
line).
"""

from benchmark.harness.timeline import timeline


def compute(record):
    t = timeline(record)
    return None if t is None else t["first_token_ms"]["p50"]
