"""What the readers of one engine iteration share: the spans and the
slot-step tally `ServingEngine._run_paged` records inside one pass of
its loop. A program that records neither (a parent commit) gives None,
and the metric is left out of the line."""

from __future__ import annotations

from typing import Optional


def span_share(record: dict, name: str) -> Optional[float]:
    """Total time of the host spans called `name`, in % of the drain's
    wall time; None where the run recorded no such span."""
    durs = [s.dur for s in record["host_spans"] if s.name == name]
    if not durs:
        return None
    return 100.0 * sum(durs) / record["window_s"]


def slot_step_share(record: dict, key: str) -> Optional[float]:
    """`paged_stats[key]`, an exact count of slot-steps, in % of all
    slot-steps of the drain's decode steps (the denominator of
    `sched_slot_occupancy`, so the shares and it add up)."""
    paged = record["paged"]
    if not paged or key not in paged or not record["decode_steps"]:
        return None
    return 100.0 * paged[key] / (record["decode_steps"] * record["slots"])
