"""Layer: device. Share of the traced window (one whole epoch) in which no
operation ran on the device. The same reading as
`serve_device_idle_share`, under a name of its own because a metric
names the one end-to-end metric it moves.
"""

from benchmark.layer_metrics.serve_device_idle_share import (  # noqa: F401
    compute,
)
