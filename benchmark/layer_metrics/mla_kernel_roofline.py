"""Layer: ops/pallas_attention.py under the latent-attention mixer.
Roofline share of the flash kernels at q.k 192 / v 128: the least time
one step's causal attention needs, forward and backward (the builder's
`kernel_cost("mla", ...)`: 8 dqk + 6 dv operations a query-key pair;
the OPERATIONS bound it, the traffic of q, k, v and their gradients is
a hundredth of that), over the kernels' own device seconds a step. The
forward kernel runs twice a step under `--remat` and is counted once.
"""

from benchmark.harness.kernels import named_roofline


def compute(record):
    return named_roofline(record, "mla", "mla")
