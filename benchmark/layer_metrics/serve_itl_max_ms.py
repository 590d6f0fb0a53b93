"""Layer: serving/engine.py host loop. The longest any request of the
drain waited between two of its tokens (`paged_stats["timeline"]`; the
`info` line says when, `itl_max_at_s`, and to whom, `itl_max_rid`): a
stall's size, or its absence. One sample: read it beside
`longest_passes` and `gc`, not as a rate.
"""

from benchmark.harness.timeline import timeline


def compute(record):
    t = timeline(record)
    return None if t is None else t["itl_ms"]["max"]
