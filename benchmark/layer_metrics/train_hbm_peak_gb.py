"""Layer: engines (parallel/*.py). Peak bytes taken on the fullest device,
in GB: what the job's state and the step program's temporaries take of
the chip's 16. The same reading as `serve_hbm_peak_gb`, under a name of
its own because a metric names the one end-to-end metric it moves.
"""

from benchmark.layer_metrics.serve_hbm_peak_gb import compute  # noqa: F401
