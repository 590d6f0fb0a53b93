"""Layer: serving/scheduler.py. Share of the positions the chunked
prefill program ran over that were padding: 1 - prompt positions
ingested over positions computed (`paged_stats`, exact counts: every
chunk is padded to `prefill_chunk`, so a prompt's last chunk computes a
tail nobody asked for). A program without the two counters reads as
nothing.
"""


def compute(record):
    paged = record["paged"] or {}
    computed = paged.get("prefill_positions_computed")
    if not computed:
        return None
    return 100.0 * (1.0 - paged["prefill_positions_valid"] / computed)
