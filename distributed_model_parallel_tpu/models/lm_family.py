"""What the causal-LM engine needs of a model family, and no more.

`CausalLMSequenceParallelEngine` trains any decoder whose configuration
answers `lm_family()` with one of these: the parameter tree's init, the
stem, the list of blocks given an attention function, the head, the
targets. `models/gpt.GPTConfig` is the first implementation,
`models/kimi_linear.KimiLinearConfig` the second; the engine spells no
family's fields.
"""

from __future__ import annotations

import dataclasses
from typing import Any, Callable, Dict, List, Optional

from distributed_model_parallel_tpu.models import layers as L


@dataclasses.dataclass(frozen=True)
class LMFamily:
    name: str
    num_layers: int
    # The width the collective-matmul policy chunks over 'seq'.
    ffn_dim: int
    max_position: int
    # () -> the whole model as a Layer whose params and state are
    # {"stem", "blocks": {"0", ...}, "head"}: used for init alone.
    model: Callable[[], L.Layer]
    # attention_fn -> the decoder blocks on the (hidden, mask) pair.
    blocks: Callable[[Any], List[L.Layer]]
    # (stem params, local ids, ctx, 'seq' shard index) -> (hidden, mask)
    stem: Callable[[Any, Any, L.Context, Any], Any]
    # (head params, hidden) -> float32 logits
    head: Callable[[Any, Any], Any]
    # host ids (B, T) -> next-token targets, -1 where nothing is scored
    targets: Callable[[Any], Any]
    # blocks' post-forward state -> {counter name: scalar} the engine
    # adds to its step metrics; None for a family without counters.
    counters: Optional[Callable[[Dict[str, Any]], Dict[str, Any]]] = None
    # {counter name: "sum" | "max"}: how a counter combines over shards
    # and steps (the engine's `metric_reductions`; the four metrics every
    # engine has are sums and are not listed).
    counter_reductions: Dict[str, str] = dataclasses.field(
        default_factory=dict
    )
    # Why the blocks cannot run with 'seq' > 1 (state that is not passed
    # between shards), or None where they can.
    seq_shards_missing: Optional[str] = None
    # Why the engine must refuse this configuration outright, or None.
    refused: Optional[str] = None
    # What `cli/lm.py` records in a checkpoint about the architecture.
    checkpoint_extra: Dict[str, Any] = dataclasses.field(
        default_factory=dict
    )

