"""Layer: ops/pallas_attention.py under the latent-attention mixer. Own
device seconds of the Mosaic kernels whose name carries the scope `mla`
(the flash forward kernel and its two backward kernels at q.k 192 / v
128, called under `jax.named_scope("mla")` by models/kimi_linear.py)
over device 0's busy time in the traced window.
"""

from benchmark.harness.kernels import named_share


def compute(record):
    return named_share(record, "mla")
