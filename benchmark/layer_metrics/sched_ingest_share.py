"""Layer: serving/scheduler.py. Slot-steps of slots still ingesting
their prompt (admitted, occupied, emitting nothing yet) over all
slot-steps of the drain's decode steps (`paged_stats`, exact counts):
the part of `sched_slot_occupancy`'s shortfall that a chunk budget
moves.
"""

from benchmark.harness.iteration import slot_step_share


def compute(record):
    return slot_step_share(record, "slot_steps_ingesting")
