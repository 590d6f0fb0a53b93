"""Layer: ops/grouped_matmul.py under the sparse expert layer. Roofline
share of the grouped products: the least time the held experts' rows
need a step, forward and backward, at the rows they EXPECT (tokens x
experts per token x held / router width; the live count is the
program's `moe_picks_held`, which no reader is handed yet), from the
builder's `kernel_cost("moe", ...)`, over the kernels' own device
seconds a step. The operations bound it, barely: every held expert's
weights cross HBM three times for 512 rows. The forward products run
twice a step under `--remat` and are counted once.
"""

from benchmark.harness.kernels import named_roofline


def compute(record):
    return named_roofline(record, "gmm", "moe")
