"""Layer: serving/scheduler.py + engine.py. 90th percentile of the
`engine_iter` span: one whole pass of the paged loop — admission, one
prefill chunk for EVERY ingesting slot, copy-on-write, the decode step,
sampling. It is the gap between two tokens of a decoding user, so its
tail is what `serve_tpot_p90_ms` is made of.
"""

from benchmark.harness.stats import percentile


def compute(record):
    iters = [s.dur for s in record["host_spans"] if s.name == "engine_iter"]
    if not iters:
        return None
    return 1e3 * percentile(iters, 90)
