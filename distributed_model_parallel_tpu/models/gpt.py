"""Decoder-only causal language model (GPT-1-style, post-LN).

The reference has no attention model at all; BERT covers the encoder
side of this framework's transformer capability, and this module covers
the decoder side — the consumer of `causal=True` attention
(`ops/attention.py`, `ops/ring_attention.py`, `ops/pallas_attention.py`
all accept it, so the same model runs dense, sequence-parallel, or on
the flash kernel by swapping `attention_fn`).

Shapes: int32 ids (B, T) -> logits (B, T, vocab). Training uses
`lm_loss` (next-token shift, padding-aware). The decoder block IS the
encoder block with a causal attention_fn — post-LN, like GPT-1; the
blocks reuse `models/transformer.py` wholesale, so TP's MEGATRON_RULES,
the pipeline stage splitter, AND the collective-matmul hook
(`layers.project`; chunked ppermute rings under
`collective_matmul=True`, `ops/collective_matmul.py`) apply to the
block stack unchanged.
(The classification engines' train loops expect (B, C) logits + integer
labels; LM training drives this model with `lm_loss` under plain
jit/grad — see tests/test_gpt.py for the data-parallel recipe.)
"""

from __future__ import annotations

import dataclasses
from functools import partial
from typing import List, Optional

import jax
import jax.numpy as jnp

from distributed_model_parallel_tpu.models import layers as L
from distributed_model_parallel_tpu.models.transformer import (
    AttentionFn,
    encoder_layer,
)
from distributed_model_parallel_tpu.ops.attention import (
    dot_product_attention,
)
from distributed_model_parallel_tpu.training.metrics import cross_entropy


@dataclasses.dataclass(frozen=True)
class GPTConfig:
    vocab_size: int = 50257
    dim: int = 768
    num_layers: int = 12
    num_heads: int = 12
    ffn_dim: int = 3072
    max_position: int = 1024
    dropout_rate: float = 0.1
    # id treated as padding in the ATTENTION mask; None = every position
    # is real (fixed-length LM batches). Loss exclusion is separate:
    # use `lm_loss_fn(cfg)` (or pass pad_token_id to `lm_loss`) so pad
    # targets are masked there too.
    pad_token_id: Optional[int] = None
    # Mixture-of-Experts: num_experts > 0 swaps the FFN of every
    # `moe_every`-th decoder block for a routed MoE (`models/moe.py`,
    # same alternating recipe as BertConfig). Train with the EP engines
    # (`parallel/expert_parallel.ExpertParallelLMEngine`; the
    # sequence-parallel LM engine computes its loss per shard and
    # refuses MoE configs).
    num_experts: int = 0
    moe_every: int = 2
    moe_top_k: int = 2
    moe_capacity_factor: float = 1.25

    def lm_family(self):
        """What `CausalLMSequenceParallelEngine` needs of this family
        (`models/lm_family.py`)."""
        from distributed_model_parallel_tpu.models.lm_family import (
            LMFamily,
        )

        drop = L.dropout(self.dropout_rate)

        def stem(params, ids, ctx, seq_index):
            # The position slice starts at this 'seq' shard's global
            # offset (the dense stem would give shards 1..N-1 local
            # offsets).
            tl = ids.shape[1]
            pos = jax.lax.dynamic_slice_in_dim(
                params["position"], seq_index * tl, tl, axis=0
            )
            return stem_apply(params, ids, self, drop, ctx, positions=pos)

        return LMFamily(
            name="gpt",
            num_layers=self.num_layers,
            ffn_dim=self.ffn_dim,
            max_position=self.max_position,
            model=partial(gpt_lm, self),
            blocks=partial(decoder_blocks, self),
            stem=stem,
            head=head_apply,
            head_operands=head_operands,
            targets=partial(lm_targets, pad_token_id=self.pad_token_id),
            # Per-shard routing under 'seq' sharding breaks the dense
            # capacity semantics and the moe_aux leaves never reach the
            # differentiated loss.
            refused=(
                "GPTConfig.num_experts > 0 is not supported by "
                "CausalLMSequenceParallelEngine; train MoE LMs with "
                "parallel/expert_parallel.ExpertParallelLMEngine "
                "(cli/lm.py --moe-experts)."
                if self.num_experts > 0 else None
            ),
            # `cli/serve.py --checkpoint` fails fast on these fields
            # when the serve flags disagree with what was trained (it
            # refuses MoE checkpoints by `num_experts`).
            checkpoint_extra={"gpt_config": {
                "vocab_size": self.vocab_size,
                "dim": self.dim,
                "num_layers": self.num_layers,
                "num_heads": self.num_heads,
                "ffn_dim": self.ffn_dim,
                "max_position": self.max_position,
                "num_experts": self.num_experts,
            }},
        )

    def serving_family(self):
        """What `ServingEngine` needs of this family
        (`models/lm_family.ServingFamily`): every layer holds pages of
        all its heads, nothing holds state, nothing is missing."""
        from distributed_model_parallel_tpu.models.lm_family import (
            LayerCache,
            ServingFamily,
        )

        if self.dim % self.num_heads:
            raise ValueError(
                f"dim {self.dim} not divisible by heads {self.num_heads}"
            )
        top = self.max_position - 1

        def head_row(params, h, row):
            return jax.lax.dynamic_index_in_dim(
                head_apply(params["head"], h)[0], row, axis=0,
                keepdims=False,
            )

        return ServingFamily(
            name="gpt",
            vocab_size=self.vocab_size,
            max_position=self.max_position,
            model=partial(gpt_lm, self),
            blocks=lambda attention_fn, state_fn: decoder_blocks(
                self, attention_fn
            ),
            decode_stem=lambda params, tokens, positions, dtype:
                decode_stem(
                    params["stem"], tokens, jnp.clip(positions, 0, top),
                    dtype,
                ),
            chunk_stem=lambda params, ids, start, dtype: chunk_stem(
                params["stem"], ids, start, dtype
            ),
            prefill_stem=lambda params, ids, offset, dtype: prefill_stem(
                params["stem"], ids, offset, dtype
            ),
            verify_stem=lambda params, tokens, positions, dtype:
                verify_stem(params["stem"], tokens, positions, dtype),
            head=lambda params, h: head_apply(params["head"], h),
            head_row=head_row,
            layers=(LayerCache(
                kv_heads=self.num_heads,
                head_dim=self.dim // self.num_heads,
            ),) * self.num_layers,
            ring_widths={
                "qkv width (3*dim)": 3 * self.dim, "dim": self.dim,
                "ffn_dim": self.ffn_dim,
            },
        )


def stem_apply(params, ids, cfg: GPTConfig, drop: L.Layer, ctx, *,
               positions=None):
    """The LM stem math, shared by the dense `_lm_stem` Layer and the
    sequence-parallel engine (which passes its shard's `positions`
    slice) — one copy, no drift. Returns (hidden, mask)."""
    mask = (
        jnp.ones(ids.shape, jnp.bool_) if cfg.pad_token_id is None
        else ids != cfg.pad_token_id
    )
    pos = (
        params["position"][: ids.shape[1]] if positions is None
        else positions
    )
    h = jnp.take(params["word"], ids, axis=0) + pos[None]
    if ctx.dtype is not None:
        h = h.astype(ctx.dtype)
    h, _ = drop.apply({}, {}, h, ctx)
    return h, mask


# ------------------------------------------------ serving's stems
#
# One per kind of step of `serving/engine.ServingEngine`; each embeds
# tokens at positions the dense `stem_apply` cannot express.


def decode_stem(stem_params, tokens, positions, dtype):
    """One-token stem: word embedding of each slot's incoming token plus
    ITS OWN position row — the dense `gpt.stem_apply` broadcasts one
    shared position slice over the batch, which cannot express a ragged
    (mixed-position) decode batch, so the gather is per-slot here.
    tokens/positions (slots,) -> h (slots, 1, dim)."""
    h = jnp.take(stem_params["word"], tokens, axis=0)[:, None, :]
    pos = jnp.take(stem_params["position"], positions, axis=0)[:, None, :]
    h = h + pos
    if dtype is not None:
        h = h.astype(dtype)
    return h


def chunk_stem(stem_params, ids, start, dtype):
    """Chunked-prefill stem: (1, T) ids embedded at global positions
    start + [0, T) with PER-TOKEN position gathers (clipped — padding
    rows past the chunk's valid length may index beyond the table;
    their outputs are discarded). `prefill_stem`'s dynamic_slice would
    CLAMP the whole slice when start + T overruns the table, silently
    shifting every position row — the per-token gather cannot."""
    t = ids.shape[1]
    pos_ids = jnp.clip(
        start + jnp.arange(t), 0, stem_params["position"].shape[0] - 1
    )
    h = jnp.take(stem_params["word"], ids, axis=0) \
        + jnp.take(stem_params["position"], pos_ids, axis=0)[None]
    if dtype is not None:
        h = h.astype(dtype)
    return h


def verify_stem(stem_params, tokens, positions, dtype):
    """Speculative verify stem: each slot's (T,) token span embedded at
    ITS OWN positions `positions[s] + [0, T)` — the batched cousin of
    `chunk_stem` (same clipped per-token position gathers; padding rows
    past the table are discarded by the verify masks) crossed with
    `decode_stem`'s per-slot raggedness. tokens (slots, T),
    positions (slots,) -> h (slots, T, dim)."""
    t = tokens.shape[1]
    pos_ids = jnp.clip(
        positions[:, None] + jnp.arange(t)[None, :],
        0, stem_params["position"].shape[0] - 1,
    )
    h = jnp.take(stem_params["word"], tokens, axis=0) \
        + jnp.take(stem_params["position"], pos_ids, axis=0)
    if dtype is not None:
        h = h.astype(dtype)
    return h


def prefill_stem(stem_params, ids, offset, dtype):
    """Prompt stem over (B, T) ids starting at global position `offset`
    (0 for the dense layouts; the shard's global offset under 'seq'
    sharding, mirroring the SP training engines)."""
    t = ids.shape[1]
    pos = jax.lax.dynamic_slice_in_dim(
        stem_params["position"], offset, t, axis=0
    )
    h = jnp.take(stem_params["word"], ids, axis=0) + pos[None]
    if dtype is not None:
        h = h.astype(dtype)
    return h


def head_operands(params, h):
    """(rows, matrix) of the vocabulary product: nothing comes before
    it (`models/lm_family.LMFamily.head_operands`)."""
    return h, params["w"]


def head_apply(params, h):
    """Untied vocabulary projection; logits in f32. Shared by the dense
    Layer and the sequence-parallel engine."""
    rows, matrix = head_operands(params, h)
    return rows.astype(jnp.float32) @ matrix


def _lm_stem(cfg: GPTConfig) -> L.Layer:
    """token + position embeddings, dropout. Output (hidden, mask)."""
    drop = L.dropout(cfg.dropout_rate)

    def init(key):
        kw, kp = jax.random.split(key)
        return {
            "word": 0.02 * jax.random.normal(
                kw, (cfg.vocab_size, cfg.dim)
            ),
            "position": 0.02 * jax.random.normal(
                kp, (cfg.max_position, cfg.dim)
            ),
        }, {}

    def apply(params, state, ids, ctx):
        return stem_apply(params, ids, cfg, drop, ctx), state

    return L.Layer(init, apply)


def _lm_head(cfg: GPTConfig) -> L.Layer:
    """Untied projection to the vocabulary; logits in f32."""

    def init(key):
        return {
            "w": 0.02 * jax.random.normal(key, (cfg.dim, cfg.vocab_size))
        }, {}

    def apply(params, state, x, ctx):
        h, _ = x
        return head_apply(params, h), state

    return L.Layer(init, apply)


def decoder_blocks(
    cfg: GPTConfig, attention_fn: Optional[AttentionFn] = None
) -> List[L.Layer]:
    attn = attention_fn or partial(dot_product_attention, causal=True)
    if cfg.num_experts > 0 and cfg.moe_every < 1:
        raise ValueError(
            f"moe_every must be >= 1 when num_experts > 0, got "
            f"{cfg.moe_every} (1 = every layer, 2 = every other, ...)"
        )
    blocks = []
    for i in range(cfg.num_layers):
        if cfg.num_experts > 0 and (i + 1) % cfg.moe_every == 0:
            from distributed_model_parallel_tpu.models.moe import (
                moe_encoder_layer,
            )

            blocks.append(moe_encoder_layer(
                cfg.dim, cfg.num_heads, cfg.ffn_dim, cfg.num_experts,
                top_k=cfg.moe_top_k,
                capacity_factor=cfg.moe_capacity_factor,
                dropout_rate=cfg.dropout_rate, eps=1e-5,
                attention_fn=attn,
            ))
        else:
            blocks.append(encoder_layer(
                cfg.dim, cfg.num_heads, cfg.ffn_dim,
                dropout_rate=cfg.dropout_rate, eps=1e-5,
                attention_fn=attn,
            ))
    return blocks


def gpt_lm(
    cfg: GPTConfig, *, attention_fn: Optional[AttentionFn] = None,
    remat: bool = False,
) -> L.Layer:
    """Full LM: ids (B, T) -> logits (B, T, vocab).

    Pass `attention_fn=partial(flash_attention, causal=True)` for the
    Pallas kernel. For sequence parallelism, shard the BLOCK stack
    (`decoder_blocks` with `partial(ring_attention, axis_name='seq',
    causal=True)`) under shard_map — the stem must stay unsharded (or
    shard-aware): it indexes position embeddings with LOCAL offsets, so
    running the full model seq-sharded would give shards 1..N-1 wrong
    positions (see tests/test_gpt.py for the working recipe; a fully
    seq-sharded stem needs the SequenceParallelEngine position-offset
    treatment)."""
    from distributed_model_parallel_tpu.models import staging

    blocks = decoder_blocks(cfg, attention_fn)
    if remat:
        blocks = [L.remat(b) for b in blocks]
    return staging.staged_model(_lm_stem(cfg), blocks, _lm_head(cfg))


def _lm_head_flat(cfg: GPTConfig) -> L.Layer:
    """The LM head for PIPELINE stages: same params as `_lm_head` (an
    untied `w` — checkpoints interoperate), but logits flattened
    (B, T, V) -> (B*T, V) to satisfy `PipelineEngine`'s (rows, classes)
    last-stage contract. Feed targets pre-flattened the same way:
    `lm_targets(ids).reshape(-1)` (row order matches — batch-major,
    token-minor on both sides)."""
    inner = _lm_head(cfg)

    def apply(params, state, x, ctx):
        logits, state = inner.apply(params, state, x, ctx)
        b, t, v = logits.shape
        return logits.reshape(b * t, v), state

    return L.Layer(inner.init, apply)


def split_stages(
    num_stages: int,
    cfg: GPTConfig,
    *,
    boundaries=None,
    attention_fn: Optional[AttentionFn] = None,
) -> List[L.Layer]:
    """Pipeline stages for the decoder LM: stem (token+position
    embeddings) on stage 0, decoder blocks distributed, flattening LM
    head on the last stage — the same staging convention as
    `models/bert.py::split_stages` (the wire carries the (hidden, mask)
    pair between stages). Drive with `PipelineEngine` and labels
    `lm_targets(ids).reshape(-1)`; the engine normalizes its loss by the
    VALID (label != -1) row count, so gradients match the dense
    per-token mean-loss convention of `lm_loss`."""
    from distributed_model_parallel_tpu.models import staging

    blocks = decoder_blocks(cfg, attention_fn)
    cuts = staging.split_points(num_stages, boundaries, len(blocks))
    return staging.assemble_stages(
        blocks, _lm_stem(cfg), _lm_head_flat(cfg), cuts
    )


def lm_loss_fn(cfg: GPTConfig):
    """`lm_loss` bound to the config's pad_token_id — use this instead
    of raw `lm_loss` so loss masking can't silently fall out of sync
    with the attention mask."""
    return partial(lm_loss, pad_token_id=cfg.pad_token_id)


def lm_targets(ids, pad_token_id: Optional[int] = None):
    """Per-position next-token targets: targets[t] = ids[t+1], with the
    final position (and padding) marked -1 (the exclusion label
    `training/metrics.cross_entropy` masks).

    Computed on the HOST so sequence-parallel training can shard targets
    alongside ids — every shard then scores its own positions locally,
    including the shard-boundary token, with no cross-shard fetch."""
    import numpy as np

    # int32 BEFORE the -1 fills: in an unsigned ids dtype the sentinel
    # would wrap to a huge valid-looking label and defeat the exclusion.
    ids = np.asarray(ids).astype(np.int32)
    targets = np.concatenate(
        [ids[:, 1:], np.full((ids.shape[0], 1), -1, np.int32)], axis=1
    )
    if pad_token_id is not None:
        targets = np.where(targets == pad_token_id, -1, targets)
    return targets.astype(np.int32)


def lm_loss(logits: jax.Array, ids: jax.Array,
            pad_token_id: Optional[int] = None) -> jax.Array:
    """Next-token cross-entropy: position t predicts ids[t+1]; padding
    targets (== pad_token_id) are excluded via the label -1 convention
    `training/metrics.cross_entropy` already masks."""
    targets = ids[:, 1:]
    if pad_token_id is not None:
        targets = jnp.where(targets == pad_token_id, -1, targets)
    logits = logits[:, :-1, :]
    b, t, v = logits.shape
    return cross_entropy(
        logits.reshape(b * t, v), targets.reshape(b * t)
    )
