"""Layer: model step. Roofline share of the compiled chunked-prefill
step: the least time one chunk could take on this chip over the time
the chunk program takes ON THE DEVICE, from the trace (the median run of
the program the engine jits as `chunk_prefill_step` on the first
device's `XLA Modules` line, inside the traced slice).

The least time is the larger of operations over peak bf16 FLOP/s and
bytes over peak HBM bytes/s, both counted by the configuration's builder
(`chunk_prefill_cost(chunk, start)`: the blocks' products on every
position of the padded chunk, causal attention over the prefix and the
chunk, the recurrence, the head on one row; every weight once, the
slot's state in and out) at the drain's mean chunk start (every finished
prompt's chunks begin at 0, chunk, 2 x chunk, ...). A record whose
driver hands no such count, or a trace without that program, reads as
nothing.
"""

from benchmark.harness.peaks import peaks_for

PROGRAM = "jit_chunk_prefill_step"


def compute(record):
    trace = record["device_trace"]
    step_s = (trace or {}).get("program_median_s", {}).get(PROGRAM)
    cost, chunk = record.get("chunk_prefill_cost"), record.get("prefill_chunk")
    if not step_s or not cost or not chunk or not record["finished"]:
        return None
    starts = [
        at for f in record["finished"]
        for at in range(0, f["prompt_len"], chunk)
    ]
    peaks = peaks_for(record["device"]["kind"])
    operations, nbytes = cost(chunk, sum(starts) / len(starts))
    least = max(operations / peaks.bf16_flops, nbytes / peaks.hbm_bytes_s)
    return 100.0 * least / step_s
