"""Layer: ops/grouped_matmul.py under a SERVED sparse expert layer. Own
device seconds of the Mosaic kernels whose name carries `gmm` (JAX's
Pallas grouped product over the rows sorted by expert: a kernel that
names itself keeps its name under any scope) over device 0's busy time
in the traced slice of the drain. `moe_kernel_share`'s twin for the
cells that report `serve_out_tok_s`. A trace without such kernels reads
as nothing.
"""

from benchmark.harness.kernels import named_share


def compute(record):
    return named_share(record, "gmm")
