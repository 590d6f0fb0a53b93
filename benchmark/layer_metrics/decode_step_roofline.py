"""Layer: model step. Roofline share of the compiled decode step: the
least time one step could take on this chip over the time the step
takes ON THE DEVICE, from the trace (the median run of the program the
engine jits as `paged_decode_step` on the first device's `XLA Modules`
line, inside the traced slice). Host time — launch, the logits fetch —
is no part of it.

The least time is the larger of operations over peak bf16 FLOP/s and
bytes over peak HBM bytes/s (`harness/flops.py`: every weight once at
its stored width, the live keys and values of the advancing slots once
at the cache's width, the logits out), at the drain's mean occupancy
and mean live length; the widths are the configuration's
(`precision.parameters`, `serving.compute_dtype`). For GPT-2 on a v5e
the BYTES bound it: GPT-2 small's step needs 0.75 ms of HBM traffic
and 0.03 ms of arithmetic.
"""

from benchmark.harness import flops
from benchmark.harness.peaks import peaks_for

PROGRAM = "jit_paged_decode_step"


def compute(record):
    trace = record["device_trace"]
    step_s = (trace or {}).get("program_median_s", {}).get(PROGRAM)
    if not step_s or not record["decode_steps"]:
        return None
    peaks = peaks_for(record["device"]["kind"])
    slots = record["step_occupancy_sum"] / record["decode_steps"]
    live = sum(
        f["prompt_len"] + f["n_tokens"] / 2.0 for f in record["finished"]
    ) / len(record["finished"])
    widths = record["widths"]
    least = max(
        flops.decode_step_flops(record["shape"], slots, live)
        / peaks.bf16_flops,
        flops.decode_step_bytes(
            record["shape"], slots, live,
            widths["weight_bytes"], widths["cache_bytes"],
        ) / peaks.hbm_bytes_s,
    )
    return 100.0 * least / step_s
