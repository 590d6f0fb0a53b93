"""What the readers of the drain's timeline share: the block
`Scheduler.timeline` reduces, once a drain, from the stamp every
emitted token carries and the row every pass of `_run_paged` leaves
(`paged_stats["timeline"]`; recorded in every run, traced or not). A
program that records none (a parent commit), or an engine that is not
paged, gives None, and the metric is left out of the line."""

from __future__ import annotations

from typing import Optional

from benchmark.harness.stats import MIN_TAIL_SAMPLES, samples_beyond


def timeline(record: dict) -> Optional[dict]:
    paged = record.get("paged")
    return paged.get("timeline") if paged else None


def gap_percentile_ms(record: dict, q: float) -> Optional[float]:
    """The q-th percentile of the gaps between two tokens of one
    request, every gap of the drain pooled (`itl_ms`); None where the
    drain has not ten gaps beyond it."""
    t = timeline(record)
    if t is None or samples_beyond(t["gaps"], q) < MIN_TAIL_SAMPLES:
        return None
    return t["itl_ms"][f"p{q:g}"]
