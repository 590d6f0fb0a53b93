"""Layer: collectives (XLA all-gather / all-reduce / reduce-scatter under
parallel/plan.py). Time from each collective's start to its end on
device 0 — synchronous operations, and `-start` to `-done` of
asynchronous ones — over the traced window.
"""

def compute(record):
    trace = record["device_trace"]
    if not trace or not trace["coll_total_s"]:
        return None
    return 100.0 * trace["coll_total_s"] / trace["device0_window_s"]
