"""distributed_model_parallel_tpu — a TPU-native distributed training framework.

A ground-up JAX/XLA re-design of the capabilities of
`timmywanttolearn/distributed_model_parallel` (reference mounted at
/root/reference): data-parallel training (the scatter / replicate /
parallel_apply / gather path of `torch.nn.DataParallel` and the bucketed
DDP Reducer, re-expressed as XLA collectives over a named device mesh),
pipeline model parallelism (the reference's autograd-transparent
`dist.send/recv` stage transport, re-expressed as `lax.ppermute` under
`shard_map` with static shapes), tensor, sequence/context, and expert
(MoE) parallelism, the model zoo (MobileNetV2 and variants, ResNet,
BERT, a GPT-style causal LM, MoE transformer blocks), the dataset
collection, and the trainer surface (SGD / AdamW + cosine decay + warmup,
acc1/acc5 metrics, best-acc checkpointing with resume, elastic
restarts). Mechanics: INTERNALS.md; measurements: PERF.md.

Package layout:
  runtime/   mesh + multi-host bootstrap (replaces dist.init_process_group)
  models/    pure-functional model zoo (param/state pytrees, NHWC)
  ops/       attention cores: XLA, ring / Ulysses sequence-parallel,
             Pallas flash kernel
  parallel/  DP / DDP / FSDP / pipeline / tensor-parallel /
             sequence-parallel / expert-parallel engines
  serving/   autoregressive inference: slot-paged KV cache, continuous
             batching, decode-time TP rings (INTERNALS.md §9)
  data/      dataset collection + per-host sharded, prefetching input
             pipeline
  training/  trainer loops, optimizer/schedule, metrics, checkpointing,
             elastic restart driver
  native/    C++ runtime components (input-pipeline hot loop)
"""

__version__ = "0.1.0"

from distributed_model_parallel_tpu.runtime.mesh import (  # noqa: F401
    MeshSpec,
    make_mesh,
    local_mesh,
)
