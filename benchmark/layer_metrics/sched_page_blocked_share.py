"""Layer: serving/kv_cache.py. Slot-steps left free because the page
pool could not hold the next waiting request (the iteration's admission
loop left on `can_hold` false), over all slot-steps of the drain's
decode steps (`paged_stats`, exact counts). What a larger pool or
prefix eviction would win back; exactly 0 where the pool never limits.
"""

from benchmark.harness.iteration import slot_step_share


def compute(record):
    return slot_step_share(record, "slot_steps_page_blocked")
