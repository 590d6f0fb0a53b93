"""Layer: collectives. The part of the traced window device 0's operation
line spent inside collective operations (a synchronous collective, or
the wait in an asynchronous one's `-done`): time in which no compute
ran on that device.
"""

def compute(record):
    trace = record["device_trace"]
    if not trace or not trace["coll_total_s"]:
        return None
    return 100.0 * trace["coll_exposed_s"] / trace["device0_window_s"]
