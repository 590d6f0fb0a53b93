"""Scaled dot-product attention cores.

The plain XLA version lives here as the numerical reference and CPU/test
path; the sequence-parallel variants — `ops.ring_attention.ring_attention`
(KV rotating over the 'seq' axis) and `ulysses_attention` (all-to-all
head/sequence re-shard) — are drop-in replacements, because everything
routes through the `attention_fn(q, k, v, mask)` signature.

Shapes follow the TPU-friendly convention (B, T, H, Dh) — batch, sequence,
heads, head_dim — so the head axis is adjacent to the feature axis XLA
tiles onto the MXU, and sequence sharding (ring attention / Ulysses) maps
onto axis 1 without transposes.
"""

from __future__ import annotations

from typing import Optional

import jax
import jax.numpy as jnp


def dot_product_attention(
    q: jax.Array,
    k: jax.Array,
    v: jax.Array,
    mask: Optional[jax.Array] = None,
    *,
    scale: Optional[float] = None,
    causal: bool = False,
) -> jax.Array:
    """softmax(q k^T / sqrt(dh)) v over (B, T, H, Dh) tensors.

    `mask`: boolean (B, Tkv) key-validity mask (True = attend) or a
    broadcastable additive-logit-compatible boolean of shape
    (B, 1|H, Tq, Tkv). `causal=True` additionally restricts each query
    to keys at its own position or earlier (decoder-style models).
    Computation in f32 regardless of input dtype (softmax stability on
    bf16 inputs), result cast back.
    """
    dh = q.shape[-1]
    scale = scale if scale is not None else 1.0 / jnp.sqrt(dh).astype(
        jnp.float32
    )
    qf = q.astype(jnp.float32)
    kf = k.astype(jnp.float32)
    # (B, H, Tq, Tkv)
    logits = jnp.einsum("bqhd,bkhd->bhqk", qf, kf) * scale
    neg = jnp.finfo(jnp.float32).min
    if mask is not None:
        if mask.ndim == 2:  # (B, Tkv) key mask
            mask = mask[:, None, None, :]
        logits = jnp.where(mask, logits, neg)
    if causal:
        tq, tk = q.shape[1], k.shape[1]
        tri = (
            jnp.arange(tq)[:, None] >= jnp.arange(tk)[None, :]
        )  # (Tq, Tkv)
        logits = jnp.where(tri[None, None, :, :], logits, neg)
    weights = jax.nn.softmax(logits, axis=-1)
    out = jnp.einsum("bhqk,bkhd->bqhd", weights, v.astype(jnp.float32))
    return out.astype(q.dtype)


def grouped_query_attention(
    q: jax.Array,
    k: jax.Array,
    v: jax.Array,
    mask: Optional[jax.Array] = None,
    *,
    scale: Optional[float] = None,
    causal: bool = False,
) -> jax.Array:
    """`dot_product_attention` where k and v carry FEWER heads than q:
    q (B, Tq, H, Dh) over k, v (B, Tkv, Hkv, Dh) with H = G * Hkv, query
    head h reading cached head h // G. The cached heads are never
    repeated: the G query heads of a group become G more query rows of
    their one cached head, and the result is laid back out by head.
    With H == Hkv this IS `dot_product_attention`. A 4-d mask is
    (B|1, 1, Tq, Tkv): one per query position, shared by the heads."""
    b, tq, h, dh = q.shape
    hkv = k.shape[2]
    if h == hkv:
        return dot_product_attention(
            q, k, v, mask, scale=scale, causal=causal
        )
    g = h // hkv
    if g * hkv != h:
        raise ValueError(f"{h} query heads over {hkv} cached heads")
    rows = q.reshape(b, tq, hkv, g, dh).swapaxes(2, 3).reshape(
        b, tq * g, hkv, dh
    )
    if mask is not None and mask.ndim == 4:
        mask = jnp.repeat(mask, g, axis=2)
    if causal:
        tri = (
            jnp.repeat(jnp.arange(tq), g)[:, None]
            >= jnp.arange(k.shape[1])[None, :]
        )[None, None]
        if mask is None:
            mask = tri
        else:
            mask = tri & (
                mask[:, None, None, :] if mask.ndim == 2 else mask
            )
    out = dot_product_attention(rows, k, v, mask, scale=scale)
    return out.reshape(b, tq, g, hkv, dh).swapaxes(2, 3).reshape(
        b, tq, h, dh
    )
