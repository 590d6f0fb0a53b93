"""Layer: engines (serving/engine.py). Peak bytes taken on the fullest
device, in GB: the buffers' peak plus the peak reservation for the step
programs' temporaries (`harness/device.memory_peak_bytes`), of the
chip's 16.
"""

def compute(record):
    if not record["memory_peak_bytes"]:
        return None
    return record["memory_peak_bytes"] / 1e9
