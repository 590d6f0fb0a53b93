"""Layer: model step (serving/decode.py, models/gpt.py). Median
`decode_step` span: upload of tokens and positions, the compiled step
for every slot, and the fetch of the whole (slots, vocab) logits.
"""

from benchmark.harness.stats import percentile


def compute(record):
    steps = [s.dur for s in record["host_spans"] if s.name == "decode_step"]
    if not steps:
        return None
    return 1e3 * percentile(steps, 50)
