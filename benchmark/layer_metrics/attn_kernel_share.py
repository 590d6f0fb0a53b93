"""Layer: ops/pallas_attention.py. Device time in Mosaic kernels — the
`custom-call` instructions of the step, which in this cell are the flash
attention forward kernel and its two backward kernels, three a layer —
over the device's busy time in the traced window, on device 0. (The
kernels carry no names of their own yet; a roofline share per kernel
waits for that.)
"""


def compute(record):
    trace = record["device_trace"]
    if not trace or not trace["kernel_s"]:
        return None
    return 100.0 * trace["kernel_s"] / trace["device0_busy_s"]
