"""Layer: device. Share of the traced window in which no operation ran on
the device: 1 - (union of device-op intervals) / (first operation to
last), from the `.xplane.pb`, averaged over the chips. The window is
a slice of the steady drain.
"""

def compute(record):
    trace = record["device_trace"]
    if not trace:
        return None
    return 100.0 * (1.0 - trace["busy_s"] / trace["window_s"])
