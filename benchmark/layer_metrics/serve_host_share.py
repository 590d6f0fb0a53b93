"""Layer: serving/engine.py host loop. Share of the drain's wall time
outside the `decode_step` and `prefill_chunk` spans: admission, page
bookkeeping, copy-on-write, host-side sampling, Python. (A chunk that
does not finish its prompt is dispatched without a fetch, so its device
time lands in the next span that blocks; the split between the two
spans is blurred, their sum is not.)
"""

from benchmark.harness.stats import span_seconds


def compute(record):
    spans = record["host_spans"]
    if not spans:
        return None
    inside = span_seconds(spans, "decode_step") + span_seconds(
        spans, "prefill_chunk"
    )
    return 100.0 * (1.0 - inside / record["window_s"])
