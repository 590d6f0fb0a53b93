"""Layer: ops/grouped_matmul.py under the sparse expert layer. Own
device seconds of the Mosaic kernels whose name carries `gmm`: JAX's
Pallas grouped product over the rows sorted by expert, forward (`gmm`)
and its two backward products (`gmm`, `tgmm`). They are called under
`jax.named_scope("moe")`, but a kernel that names itself keeps its name
under any scope (my chip run, PR 29). Over device 0's busy time in the
traced window.
"""

from benchmark.harness.kernels import named_share


def compute(record):
    return named_share(record, "gmm")
