"""Layer: serving/kv_cache.py. The part of a decode step's least HBM
traffic that is recurrent state: the bytes of the state pool one step
reads and writes at the drain's mean occupancy (each advancing slot's
row of `state_pool_bytes` in and out; `paged_stats`, exact) over the
bytes the configuration's builder counts for that step
(`decode_step_cost`: weights, live keys and values, state, logits). A
program without a state pool reads as nothing.
"""


def compute(record):
    pool = (record["paged"] or {}).get("state_pool_bytes")
    if not pool or not record["decode_steps"] or not record["finished"]:
        return None
    slots = record["step_occupancy_sum"] / record["decode_steps"]
    live = sum(
        f["prompt_len"] + f["n_tokens"] / 2.0 for f in record["finished"]
    ) / len(record["finished"])
    _, nbytes = record["decode_step_cost"](slots, live)
    return 100.0 * 2.0 * slots * (pool / record["slots"]) / nbytes
