"""What the causal-LM engine needs of a model family, and no more.

`CausalLMSequenceParallelEngine` trains any decoder whose configuration
answers `lm_family()` with one of these: the parameter tree's init, the
stem, the list of blocks given an attention function, the head, the
targets. `models/gpt.GPTConfig` is the first implementation,
`models/kimi_linear.KimiLinearConfig` the second; the engine spells no
family's fields.

`ServingEngine` serves any decoder whose configuration answers
`serving_family()` with a `ServingFamily`: the same init, a stem for
each kind of step, the blocks given an attention function AND a state
function, the head, and per layer what the layer keeps between steps
(`LayerCache`: pages of keys and values, pages of ONE latent row a
token, or arrays of constant size per slot). `models/gpt.GPTConfig`
answers with what the engine did before the seam;
`models/jamba.JambaConfig` is the first with state,
`models/glm_moe.GlmMoeConfig` the first with latent pages (and with
expert layers, whose counters the engine sums).
"""

from __future__ import annotations

import dataclasses
from typing import Any, Callable, Dict, List, Optional, Tuple

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
    # (head params, hidden) -> (rows, matrix): what the vocabulary
    # product takes, `head` being `rows.astype(float32) @ matrix`: the
    # hidden rows after whatever the head does first (a final norm,
    # or nothing) and the (dim, vocab) matrix. A step that wants the
    # loss and not the logits hands them to `ops/head_loss.head_loss`.
    head_operands: Callable[[Any, Any], Tuple[Any, Any]]
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



@dataclasses.dataclass(frozen=True)
class LayerCache:
    """What one layer keeps between two steps of a sequence: pages of
    keys and values (`kv_heads` > 0: that many cached heads of
    `head_dim`, however many query heads read them), pages of latent
    rows (`latent_dim` > 0: ONE vector of that many values a token, no
    heads and no separate values: a latent mixer's compressed row and
    its shared rotary key), or `state`, arrays of constant size per
    slot, {name: (shape of one slot's, dtype or None for the
    activations')}."""

    kv_heads: int = 0
    head_dim: int = 0
    latent_dim: int = 0
    state: Dict[str, Tuple[Tuple[int, ...], Any]] = dataclasses.field(
        default_factory=dict
    )


@dataclasses.dataclass(frozen=True)
class ServingFamily:
    name: str
    vocab_size: int
    max_position: int
    # () -> the whole model as a Layer, params {"stem", "blocks": {"0",
    # ...}, "head"}: init and checkpoint interop alone.
    model: Callable[[], L.Layer]
    # (attention_fn, state_fn) -> the decoder blocks on the (hidden,
    # mask) pair. A layer that holds pages calls `attention_fn(q, k, v,
    # mask)` once; a layer that holds state calls `state_fn(advance)`
    # once, `advance(state) -> (output, new state)` on its own
    # `LayerCache.state` arrays with a leading row axis; both in layer
    # order. The mask says which positions are real (a chunk's tail is
    # not) and a state-holding layer must not advance on the others.
    # A layer that holds LATENT pages calls `attention_fn(q_nope,
    # q_rope, c, k_rope, w_kvb, mask, dims)` once instead
    # (`ops/latent_attention.latent_causal_attention`'s signature:
    # rotary parts unrotated, since the function alone knows each
    # token's position, caches the row `[c, rotated k_rope]` and
    # attends over the rows kept so far).
    blocks: Callable[[Any, Any], List[L.Layer]]
    # (whole params, tokens (slots,), positions (slots,), dtype)
    # -> (slots, 1, dim): every slot's next token at its own position
    decode_stem: Callable[[Any, Any, Any, Any], Any]
    # (whole params, ids (1, T), start, dtype) -> (1, T, dim)
    chunk_stem: Callable[[Any, Any, Any, Any], Any]
    # (whole params, ids (B, T), offset, dtype) -> (B, T, dim)
    prefill_stem: Callable[[Any, Any, Any, Any], Any]
    # (whole params, tokens (slots, T), positions (slots,), dtype)
    # -> (slots, T, dim)
    verify_stem: Callable[[Any, Any, Any, Any], Any]
    # (whole params, hidden (B, T, dim)) -> float32 logits (B, T, vocab)
    head: Callable[[Any, Any], Any]
    # (whole params, hidden (1, T, dim), row) -> float32 logits (vocab,)
    # of that one row
    head_row: Callable[[Any, Any, Any], Any]
    # one per layer, in layer order
    layers: Tuple[LayerCache, ...]
    # Weights at rest: `model().init` gives float32; the engine casts
    # the tree to this under the same jit. None keeps float32.
    param_dtype: Any = None
    # (positions of one chunk) -> the program the layers that keep a
    # state compile their recurrence to over such a stretch, by name
    # ("kernel" | "loop"): what the model's own selector answers for
    # the widths of this family. None for a family that keeps none.
    chunk_state_program: Optional[Callable[[int], str]] = None
    # The decode step's mask says which SLOTS are live (a family whose
    # layers route rows and count them); False hands every slot's row
    # as real, as the step did before such a family.
    masks_inactive: bool = False
    # (blocks' post-forward state, "decode" | "chunk") -> {counter
    # name: scalar} the engine accumulates on the device beside the
    # cache and reports in `paged_stats`; None for a family without.
    counters: Optional[Callable[[Dict[str, Any], str], Dict[str, Any]]] = None
    # {counter name: "sum" | "max" | "last"}: how a counter combines
    # over steps. "last" keeps the step's own value, an int32 array of
    # one entry a row of the step (`counter_rows[name]` its shape after
    # the rows): the decode step's under `<name>_decode`, one a slot,
    # the chunk step's under `<name>_chunk`, one a position; in the
    # cache tree a step hands back, not in `paged_stats`.
    counter_reductions: Dict[str, str] = dataclasses.field(
        default_factory=dict
    )
    counter_rows: Dict[str, Tuple[int, ...]] = dataclasses.field(
        default_factory=dict
    )
    # Widths the tp decode rings chunk over 'model', {label: n}.
    ring_widths: Dict[str, int] = dataclasses.field(default_factory=dict)
    # What of the engine this family cannot run yet, {option: the
    # mechanism that is missing}; the engine refuses each at
    # construction under the option's name.
    missing: Dict[str, str] = dataclasses.field(default_factory=dict)
