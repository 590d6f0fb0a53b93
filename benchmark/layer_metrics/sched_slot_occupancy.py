"""Layer: serving/scheduler.py. Occupied slot-steps over all slot-steps of
the drain's decode steps (the scheduler's `goodput`, from its exact
per-step counts): how full continuous batching keeps the batch.
"""

def compute(record):
    if not record["decode_steps"]:
        return None
    return 100.0 * record["step_occupancy_sum"] / (
        record["decode_steps"] * record["slots"]
    )
