"""Layer: serving/engine.py host loop. `sample` span time over the
drain's wall time: the per-slot pick of the next token on the host from
the fetched logits, with the bookkeeping and evictions of that loop.
"""

from benchmark.harness.iteration import span_share


def compute(record):
    return span_share(record, "sample")
