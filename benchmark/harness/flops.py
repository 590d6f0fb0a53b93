"""Operations and bytes the algorithm needs, computed from shapes.

Matrix-multiply work only (embedding lookups, LayerNorm, softmax and
GELU are not counted). Attention is counted as the causal algorithm
needs it: a query at position t scores t + 1 keys, so a sequence of T
tokens costs T (T + 1) / 2 query-key pairs, not T^2. (The common
"6 N + 12 L d T" convention counts the masked half too; it gives 0.855
GFLOP a token for GPT-2 small at T = 1024 where this file gives 0.799.)
Recomputed operations (`--remat`) are never counted.
"""

from __future__ import annotations


def block_matmul_params(dim: int, ffn_dim: int) -> int:
    """Weights one block multiplies by: qkv (d x 3d), out (d x d),
    ffn in (d x f) and out (f x d)."""
    return 4 * dim * dim + 2 * dim * ffn_dim


def matmul_params(shape: dict) -> int:
    """Weights every token is multiplied by: the blocks and the untied
    vocabulary head (the embedding tables are lookups)."""
    return (
        shape["n_layer"] * block_matmul_params(shape["n_embd"], shape["n_inner"])
        + shape["n_embd"] * shape["vocab_size"]
    )


def total_params(shape: dict) -> int:
    """Every stored parameter of this repo's GPT: embeddings, blocks
    with biases and two LayerNorms, untied head without bias."""
    d, f = shape["n_embd"], shape["n_inner"]
    per_block = block_matmul_params(d, f) + (3 * d + d + f + d) + 4 * d
    return (
        shape["vocab_size"] * d + shape["n_positions"] * d
        + shape["n_layer"] * per_block + d * shape["vocab_size"]
    )


def forward_flops_per_token(shape: dict, seq_len: int) -> float:
    """Forward pass of one token of a `seq_len` causal sequence, averaged
    over the sequence: 2 FLOPs per weight, and per layer 2 x 2 x d for
    each of the (seq_len + 1) / 2 keys an average query attends to."""
    attention = (
        shape["n_layer"] * 4 * shape["n_embd"] * (seq_len + 1) / 2
    )
    return 2.0 * matmul_params(shape) + attention


def train_flops_per_token(shape: dict, seq_len: int) -> float:
    """Forward + backward: the backward pass costs twice the forward."""
    return 3.0 * forward_flops_per_token(shape, seq_len)


def decode_step_flops(shape: dict, slots: float, live_tokens: float) -> float:
    """One decode step that advances `slots` sequences whose caches hold
    `live_tokens` positions each (averages are fine: it is linear)."""
    attention = shape["n_layer"] * 4 * shape["n_embd"] * live_tokens
    return slots * (2.0 * matmul_params(shape) + attention)


def decode_step_bytes(shape: dict, slots: float, live_tokens: float,
                      weight_bytes: int, cache_bytes: int) -> float:
    """HBM bytes one decode step has to move: every weight once, the
    live keys and values of every advancing slot once, the logits out."""
    kv = (
        2 * shape["n_layer"] * shape["n_embd"] * live_tokens * slots
        * cache_bytes
    )
    logits = slots * shape["vocab_size"] * 4
    return matmul_params(shape) * weight_bytes + kv + logits
