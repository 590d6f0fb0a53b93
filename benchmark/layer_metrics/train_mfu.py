"""Layer: models/gpt.py blocks. Model FLOP/s utilisation: tokens per second
times the FLOPs a token's forward and backward passes require
(`harness/flops.py`; recomputation not counted, causal attention counted
as the algorithm needs it) over chips x the bf16 peak by `device_kind`.
An end-to-end utilisation, not a kernel's roofline share.
"""

from benchmark.harness.peaks import peaks_for
from benchmark.harness.stats import untraced


def compute(record):
    epochs = untraced(record["epochs"])
    tokens = sum(e["steps"] for e in epochs) * record["tokens_per_step"]
    rate = tokens / sum(e["wall_s"] for e in epochs)
    peak = record["device"]["count"] * peaks_for(
        record["device"]["kind"]
    ).bf16_flops
    return 100.0 * rate * record["train_flops_per_token"] / peak
