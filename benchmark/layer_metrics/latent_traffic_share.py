"""Layer: serving/kv_cache.py. The part of a decode step's least HBM
traffic that is cached latent rows: what the configuration's builder
counts for the live rows of the advancing slots at the drain's mean
occupancy and mean live length (`decode_step_cost` at that length less
`decode_step_cost` at none: the rows' VALUES, read once; the pool
stores a row in whole lane tiles and `paged_stats["latent_pool_bytes"]`
counts that) over what it counts for the whole step (the weights its
picks reach, the live rows, the logits). The twin of
`state_traffic_share`. A program without a latent pool reads as nothing.
"""


def compute(record):
    paged = record["paged"] or {}
    if (not paged.get("latent_pool_bytes") or not record["decode_steps"]
            or not record["finished"]):
        return None
    slots = record["step_occupancy_sum"] / record["decode_steps"]
    live = sum(
        f["prompt_len"] + f["n_tokens"] / 2.0 for f in record["finished"]
    ) / len(record["finished"])
    _, nbytes = record["decode_step_cost"](slots, live)
    _, without = record["decode_step_cost"](slots, 0.0)
    return 100.0 * (nbytes - without) / nbytes
