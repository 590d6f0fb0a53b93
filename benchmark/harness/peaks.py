"""Published peaks of one chip, keyed by JAX's `device_kind`.

Source: Google Cloud documentation, "TPU v5e" system architecture page:
197 TFLOP/s bf16 and 819 GB/s of HBM bandwidth (the page also gives
393 TOP/s int8, 16 GB of HBM and 1,600 Gbit/s of chip-to-chip
interconnect: a benchmark PR adds a field with the first reader that
needs it). JAX reports that chip as `device_kind` "TPU v5 lite" (chip
run, PR 21). A kind that is not in the table is an error, never a
default. (The bf16 figure is copied from `bench.py`'s PEAK_BF16_TFLOPS,
which a later PR may delete.)
"""

from __future__ import annotations

import dataclasses


@dataclasses.dataclass(frozen=True)
class Peaks:
    bf16_flops: float   # FLOP/s
    hbm_bytes_s: float  # bytes/s


PEAKS = {
    "TPU v5 lite": Peaks(bf16_flops=197e12, hbm_bytes_s=819e9),
}


def peaks_for(device_kind: str) -> Peaks:
    try:
        return PEAKS[device_kind]
    except KeyError:
        raise ValueError(
            f"no peaks known for device_kind {device_kind!r}; add it to "
            "benchmark/harness/peaks.py with its source before reporting "
            "a utilisation"
        ) from None
