"""Layer: serving/engine.py host loop. `logits_fetch` span time over the
drain's wall time: the device-to-host copy of the (slots, vocab) f32
logits after every decode step and of one row after a prompt's last
chunk, the device's wait (`device_wait`, its own span) left out. What
sampling on the device would save.
"""

from benchmark.harness.iteration import span_share


def compute(record):
    return span_share(record, "logits_fetch")
