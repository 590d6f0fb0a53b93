"""Layer: serving/scheduler.py as the engine drives it. `admit` span
time over the drain's wall time: the admission loop of every iteration —
`PagedCacheHost.can_hold` (a walk over every admitted slot's block
table, once per iteration while a request waits), `admit`, `reserve`
and the prefix lookup.
"""

from benchmark.harness.iteration import span_share


def compute(record):
    return span_share(record, "admit")
