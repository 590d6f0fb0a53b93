"""Layer: serving/scheduler.py. Slot-steps left free because nothing
waited any more (the end of the drain's list) over all slot-steps of
the drain's decode steps (`paged_stats`, exact counts). It says how the
drain ends, not what the system sustains: `serve_out_tok_s` leaves that
stretch out, `sched_slot_occupancy` does not, and this is the
difference.
"""

from benchmark.harness.iteration import slot_step_share


def compute(record):
    return slot_step_share(record, "slot_steps_drain_out")
