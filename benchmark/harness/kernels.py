"""Mosaic kernels told apart by name.

A kernel's instruction name in the device trace is built from the
`jax.named_scope`s around its call (`trace_reduce.reduce_device`)
unless the kernel names itself (JAX's grouped product is `gmm` and
`tgmm` under any scope; my chip run, PR 29), so the own seconds of one
layer's kernels are the entries of `kernel_seconds` whose name carries
a part that the reader states. Their least time comes from the
configuration's builder (`kernel_cost(kernel, shape)` -> operations and
HBM bytes of one training step, forward and backward, no
recomputation), which the record names under `shape["builder"]`. A
record without such kernels, or of a family whose builder states no
such cost, reads as nothing (`None`), never as an error.
"""

from __future__ import annotations

from typing import Optional

from benchmark.harness import manifest
from benchmark.harness.peaks import peaks_for


def named_seconds(record: dict, part: str) -> float:
    """Own seconds on device 0, in the traced window, of the kernels
    whose name carries `part`."""
    trace = record.get("device_trace") or {}
    return sum(
        s for name, s in trace.get("kernel_seconds", {}).items()
        if part in name
    )


def named_share(record: dict, part: str) -> Optional[float]:
    seconds = named_seconds(record, part)
    if not seconds:
        return None
    return 100.0 * seconds / record["device_trace"]["device0_busy_s"]


def named_roofline(record: dict, part: str, kernel: str) -> Optional[float]:
    """The least time of the builder's `kernel` for the traced steps
    (the larger of operations over peak bf16 FLOP/s and bytes over peak
    HBM bytes/s) over the seconds of the kernels named `part`.
    Recomputation under `--remat` runs the forward kernels twice and
    counts once, so it lowers the share."""
    seconds = named_seconds(record, part)
    shape = record.get("shape") or {}
    if not seconds or "builder" not in shape:
        return None
    builder = manifest.load_module("builder", shape["builder"])
    if not hasattr(builder, "kernel_cost"):
        return None
    steps = sum(e["steps"] for e in record["epochs"] if e["traced"])
    operations, nbytes = builder.kernel_cost(kernel, shape)
    peaks = peaks_for(record["device"]["kind"])
    least = max(operations / peaks.bf16_flops, nbytes / peaks.hbm_bytes_s)
    return 100.0 * least * steps / seconds
