"""Layer: serving/engine.py host loop. The median gap between two
tokens of one request, over EVERY gap of the drain, from the stamp each
emitted token carries (`paged_stats["timeline"]`, the host's clock when
the step's fetch returned): one whole pass as its user feels it, other
slots' prefill chunks, copy-on-write, admission and sampling included.
`serve_tpot_p50_ms` is the median over requests of each one's MEAN gap.
"""

from benchmark.harness.timeline import gap_percentile_ms


def compute(record):
    return gap_percentile_ms(record, 50.0)
