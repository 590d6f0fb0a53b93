"""Layer: model step. `prefill_chunk` span time over `prefill_chunk` +
`decode_step` span time: how much of the stepping is prompt ingestion.
See serve_host_share for what blurs the split.
"""

from benchmark.harness.stats import span_seconds


def compute(record):
    spans = record["host_spans"]
    prefill = span_seconds(spans, "prefill_chunk")
    total = prefill + span_seconds(spans, "decode_step")
    if not total:
        return None
    return 100.0 * prefill / total
