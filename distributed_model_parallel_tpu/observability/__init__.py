"""Observability: the span tracer (`trace.py` — host-side runtime
timeline, Chrome trace export; the benchmark reads its spans) and the
metrics registry (`metrics.py` — counters/gauges/histograms with
streaming quantiles, Prometheus + JSON export, the ONE percentile
rule). Imports nothing else of the package. INTERNALS.md §13–§14."""

from distributed_model_parallel_tpu.observability.metrics import (  # noqa: F401,E501
    MetricsRegistry,
    exact_quantile,
    get_metrics,
    set_metrics,
)
from distributed_model_parallel_tpu.observability.trace import (  # noqa: F401
    Tracer,
    disable,
    enable,
    get_tracer,
    set_tracer,
)

__all__ = [
    "MetricsRegistry",
    "Tracer",
    "disable",
    "enable",
    "exact_quantile",
    "get_metrics",
    "get_tracer",
    "set_metrics",
    "set_tracer",
]
