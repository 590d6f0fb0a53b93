"""Sequence/context parallelism: ring attention + Ulysses all-to-all.

Long-context attention on a `'seq'`-sharded mesh axis. Both ops are
drop-in `attention_fn`s for the transformer layers
(`models/transformer.py`) when the encoder runs inside `shard_map` with
activations sharded over the sequence dimension — the TPU-native
equivalents of the GPU world's Ring Attention (Liu et al.) and
DeepSpeed-Ulysses. Absent from the reference (SURVEY.md §2.3: no
attention models at all); first-class here because long-context is part
of this framework's capability surface.

* `ring_attention`: K/V (+ key mask) blocks rotate around the ring via
  `lax.ppermute` while each device accumulates its local queries' output
  with the online-softmax (flash) recurrence in f32. Memory per device is
  O(T/N · T/N) per block pair instead of O(T²); the N permute hops ride
  ICI and overlap with the einsums. Exact — not an approximation.
* `ulysses_attention`: two `lax.all_to_all`s re-shard (B, T/N, H, dh) ->
  (B, T, H/N, dh), run ordinary attention with full sequence per head
  locally, and shard back. One collective pair per layer; requires
  H % N == 0.
* `ring_flash_attention`: the ring with the fused Pallas flash kernels
  (`ops/pallas_attention.py`) as the per-hop core and a custom ring
  backward — per-device attention memory O(T/N) instead of the plain
  ring's O((T/N)²) logits tile per hop. The distributed long-context
  hot path.

All three match `dot_product_attention` numerically
(tests/test_sequence_parallel.py, forward AND gradients), support the
(B, Tkv) key-validity mask, and take `causal=True` for decoder-style
models (the rings apply it as a block-index predicate on the rotating
KV blocks; Ulysses applies the ordinary triangle after its all-to-all).
Precision: ring/ulysses accumulate in f32 end to end and cast back to
the input dtype; ring_flash's kernel path follows the flash kernel's
contract (f32 softmax/accumulators in VMEM, per-hop partial outputs
rounded to the input dtype before the f32 log-sum-exp merge — the bf16
tolerance tests cover this).
"""

from __future__ import annotations

import math
from functools import partial
from typing import Optional

import jax
import jax.numpy as jnp
from jax import lax

from distributed_model_parallel_tpu.ops.attention import (
    dot_product_attention,
)

_NEG = jnp.finfo(jnp.float32).min


def ring_attention(
    q: jax.Array,
    k: jax.Array,
    v: jax.Array,
    mask: Optional[jax.Array] = None,
    *,
    axis_name: str = "seq",
    scale: Optional[float] = None,
    causal: bool = False,
) -> jax.Array:
    """Exact attention over a ring of sequence shards.

    Call inside `shard_map` with q/k/v sharded over `axis_name` on the
    sequence axis: local shapes (B, T/N, H, dh), `mask` (B, T/N) key
    validity. Returns the local queries' attention over the FULL global
    key/value sequence.

    `causal=True` applies GLOBAL-position causality with a block-level
    predicate: the KV block arriving at ring step r originated on shard
    (self - r) mod n, so it is fully visible when its shard index is
    below ours, fully hidden when above, and lower-triangular for the
    local block — no per-element global-index bookkeeping crosses the
    wire.
    """
    dh = q.shape[-1]
    scale = scale if scale is not None else 1.0 / math.sqrt(dh)
    b, tq, h, _ = q.shape
    n = lax.psum(1, axis_name)  # static ring size
    s_idx = lax.axis_index(axis_name)
    qf = q.astype(jnp.float32) * scale
    # K/V ride the ring in f32 ON PURPOSE (2x the wire bytes of the
    # bf16 input): the dk/dv cotangents retrace the reversed ring in
    # the SAME dtype, so an input-dtype wire would accumulate each
    # block's gradient through n-1 bf16 roundings — breaking the
    # module contract ("accumulate in f32 end to end"). hlolint's
    # `bf16-ring-upcast` rule exempts the `kv_ring`-scoped permutes
    # for exactly this reason.
    kb = k.astype(jnp.float32)
    vb = v.astype(jnp.float32)
    maskb = (
        mask if mask is not None
        else jnp.ones(k.shape[:2], dtype=jnp.bool_)
    )
    perm = [(i, (i + 1) % n) for i in range(n)]

    # Online-softmax accumulators (flash recurrence), all f32.
    m0 = jnp.full((b, h, tq), _NEG, jnp.float32)       # running max
    l0 = jnp.zeros((b, h, tq), jnp.float32)            # running denom
    o0 = jnp.zeros((b, tq, h, dh), jnp.float32)        # running numerator

    def accumulate(acc, kb, vb, maskb, tri=None):
        m, l, o = acc
        logits = jnp.einsum("bqhd,bkhd->bhqk", qf, kb)
        logits = jnp.where(maskb[:, None, None, :], logits, _NEG)
        if tri is not None:  # causal local block: (tq, tkv) triangle
            logits = jnp.where(tri[None, None, :, :], logits, _NEG)
        m_new = jnp.maximum(m, jnp.max(logits, axis=-1))
        # exp(_NEG - m_new) underflows to 0 for any finite m_new; a fully
        # masked ring (pad-only rows) keeps l == 0 and is guarded below.
        p = jnp.exp(logits - m_new[..., None])
        corr = jnp.exp(m - m_new)
        l = l * corr + jnp.sum(p, axis=-1)
        o = o * jnp.transpose(corr, (0, 2, 1))[..., None] + jnp.einsum(
            "bhqk,bkhd->bqhd", p, vb
        )
        return m_new, l, o

    def body(r, carry):
        # Rotate THEN accumulate: the local block is consumed before the
        # loop, so exactly n-1 ring hops happen in total (a rotate-last
        # loop would pay one extra full K/V transfer whose result is
        # discarded — pure ICI waste on the long-context hot path).
        acc, kb, vb, maskb = carry
        # The scope names these permutes in the traced jaxpr so the
        # hlolint `bf16-ring-upcast` rule can exempt the deliberately
        # f32 KV wire without unpinning the collective-matmul rings.
        with jax.named_scope("kv_ring"):
            kb, vb, maskb = (
                lax.ppermute(x, axis_name, perm)
                for x in (kb, vb, maskb)
            )
        if causal:
            # Block arriving at step r originated on shard (s - r - 1)
            # mod n: visible iff it sits strictly below us in the global
            # order. Fully-hidden blocks SKIP their einsums entirely
            # (lax.cond, runtime-predicated) — the rotation above stays
            # unconditional because every device must feed the ring —
            # so causal rings pay ~half the attention FLOPs, like the
            # flash kernel's frontier predicate.
            src = (s_idx - r - 1) % n
            visible = src < s_idx
            acc = lax.cond(
                visible,
                lambda a: accumulate(a, kb, vb, maskb & visible),
                lambda a: a,
                acc,
            )
        else:
            acc = accumulate(acc, kb, vb, maskb)
        return acc, kb, vb, maskb

    tri = None
    if causal:
        tri = (
            jnp.arange(tq)[:, None] >= jnp.arange(k.shape[1])[None, :]
        )
    acc = accumulate((m0, l0, o0), kb, vb, maskb, tri)  # local block first
    (m, l, o), *_ = lax.fori_loop(0, n - 1, body, (acc, kb, vb, maskb))
    denom = jnp.where(l > 0, l, 1.0)
    out = o / jnp.transpose(denom, (0, 2, 1))[..., None]
    return out.astype(q.dtype)


def ulysses_attention(
    q: jax.Array,
    k: jax.Array,
    v: jax.Array,
    mask: Optional[jax.Array] = None,
    *,
    axis_name: str = "seq",
    scale: Optional[float] = None,
    causal: bool = False,
    attention_impl=None,
) -> jax.Array:
    """All-to-all sequence parallelism (DeepSpeed-Ulysses layout swap).

    Call inside `shard_map` with q/k/v sharded over `axis_name` on the
    sequence axis, heads divisible by the axis size: re-shards to
    head-parallel, runs ordinary full-sequence attention locally, and
    re-shards back to sequence-parallel.

    `attention_impl` is the local full-sequence core (default
    `dot_product_attention`); pass `pallas_attention.flash_attention`
    (the `'ulysses_flash'` registry entry) to keep the local O(T²)
    probability tiles in VMEM — same motivation as ring_flash.
    """
    n = lax.psum(1, axis_name)
    h = q.shape[2]
    if h % n:
        raise ValueError(
            f"ulysses needs heads ({h}) divisible by '{axis_name}' "
            f"axis size ({n})"
        )

    def to_heads(x):  # (B, T/N, H, dh) -> (B, T, H/N, dh)
        return lax.all_to_all(
            x, axis_name, split_axis=2, concat_axis=1, tiled=True
        )

    def to_seq(x):  # inverse
        return lax.all_to_all(
            x, axis_name, split_axis=1, concat_axis=2, tiled=True
        )

    full_mask = None
    if mask is not None:
        full_mask = lax.all_gather(mask, axis_name, axis=1, tiled=True)
    # After the all-to-all each device sees the FULL sequence for its
    # heads, so causality is the ordinary triangular mask locally.
    impl = attention_impl or dot_product_attention
    out = impl(
        to_heads(q), to_heads(k), to_heads(v), full_mask, scale=scale,
        causal=causal,
    )
    return to_seq(out)


# ------------------------------------------------ ring x flash composition


def _dense_pair_fwd(q, k, v, maskb, scale, causal):
    """One (local-q x resident-KV-block) attention in plain einsums:
    normalized output (f32) + per-row logsumexp (B, H, Tq) with -inf for
    rows this block contributes nothing to. CI fallback for shapes the
    Pallas kernels can't tile; the math twin of `_pair_kernel_fwd`."""
    s = jnp.einsum(
        "bqhd,bkhd->bhqk",
        q.astype(jnp.float32) * scale, k.astype(jnp.float32),
    )
    if maskb is not None:
        s = jnp.where(maskb[:, None, None, :], s, _NEG)
    if causal:
        tq, tk = q.shape[1], k.shape[1]
        tri = jnp.arange(tq)[:, None] >= jnp.arange(tk)[None, :]
        s = jnp.where(tri[None, None], s, _NEG)
    m = jnp.max(s, axis=-1)                              # (B, H, Tq)
    p = jnp.where(s == _NEG, 0.0, jnp.exp(s - m[..., None]))
    l = jnp.sum(p, axis=-1)
    o = jnp.einsum("bhqk,bkhd->bqhd", p, v.astype(jnp.float32))
    o = o / jnp.transpose(jnp.where(l > 0, l, 1.0), (0, 2, 1))[..., None]
    lse = jnp.where(l > 0, m + jnp.log(jnp.where(l > 0, l, 1.0)), -jnp.inf)
    return o, lse


def _dense_pair_bwd(q, k, v, maskb, out, lse, g, scale, causal):
    """Backward twin of `_dense_pair_fwd` under the GLOBAL lse: p is the
    block's share of the full-softmax probabilities, so the returned
    (dq-contribution, dk, dv) are exact pieces of the ring total."""
    qf, kf, vf = (x.astype(jnp.float32) for x in (q, k, v))
    gf, of = g.astype(jnp.float32), out.astype(jnp.float32)
    s = jnp.einsum("bqhd,bkhd->bhqk", qf * scale, kf)
    if maskb is not None:
        s = jnp.where(maskb[:, None, None, :], s, _NEG)
    if causal:
        tq, tk = q.shape[1], k.shape[1]
        tri = jnp.arange(tq)[:, None] >= jnp.arange(tk)[None, :]
        s = jnp.where(tri[None, None], s, _NEG)
    p = jnp.exp(s - lse[..., None])                      # +inf lse -> 0
    delta = jnp.transpose(jnp.sum(gf * of, axis=-1), (0, 2, 1))
    dp = jnp.einsum("bqhd,bkhd->bhqk", gf, vf)
    ds = p * (dp - delta[..., None])
    dq = jnp.einsum("bhqk,bkhd->bqhd", ds, kf) * scale
    dk = jnp.einsum("bhqk,bqhd->bkhd", ds, qf) * scale
    dv = jnp.einsum("bhqk,bqhd->bkhd", p, gf)
    return dq, dk, dv


def _pair_blocks(tq, tk):
    from distributed_model_parallel_tpu.ops.pallas_attention import (
        DEFAULT_BLOCK_Q,
        DEFAULT_BLOCK_K,
        _blocks_viable,
    )

    return _blocks_viable(tq, tk, DEFAULT_BLOCK_Q, DEFAULT_BLOCK_K)


def _pair_fwd(q, k, v, maskb, scale, causal, interpret):
    """Block-pair attention dispatch: Pallas flash kernel when the
    shapes tile (TPU hot path — nothing O(Tq·Tk) leaves VMEM), dense
    einsums otherwise (CI shapes). Returns (o_f32, lse (B,H,Tq))."""
    blocks = _pair_blocks(q.shape[1], k.shape[1])
    if blocks is None:
        return _dense_pair_fwd(q, k, v, maskb, scale, causal)
    from distributed_model_parallel_tpu.ops.pallas_attention import (
        _flash_forward,
    )

    out, lse = _flash_forward(
        q, k, v, maskb, scale, blocks[0], blocks[1], interpret,
        causal=causal, need_lse=True,
    )
    lse = lse[..., 0]
    # kernel sentinel: +inf for empty rows; the hop merge wants -inf
    return out.astype(jnp.float32), jnp.where(
        jnp.isposinf(lse), -jnp.inf, lse
    )


def _pair_bwd(q, k, v, maskb, out, lse, g, scale, causal, interpret):
    """(dq-contribution, dk, dv) for one block pair under the global lse
    ((B,H,Tq), +inf sentinel for empty rows)."""
    blocks = _pair_blocks(q.shape[1], k.shape[1])
    if blocks is None:
        return _dense_pair_bwd(q, k, v, maskb, out, lse, g, scale, causal)
    from distributed_model_parallel_tpu.ops.pallas_attention import (
        _LANES,
        _flash_backward,
    )

    b, tq, h, _ = q.shape
    lse_b = jnp.broadcast_to(lse[..., None], (b, h, tq, _LANES))
    return _flash_backward(
        q, k, v, maskb, out, lse_b, g, scale, blocks[0], blocks[1],
        interpret, causal,
    )


def _merge_hop(o_acc, lse_acc, o_b, lse_b):
    """Log-sum-exp merge of two NORMALIZED partial attentions."""
    lse_new = jnp.logaddexp(lse_acc, lse_b)
    w_acc = jnp.exp(lse_acc - lse_new)                   # (B, H, Tq)
    w_b = jnp.exp(lse_b - lse_new)
    to_bthd = lambda x: jnp.transpose(x, (0, 2, 1))[..., None]
    # -inf - -inf = nan guard: empty-so-far rows have w = 0 via where
    w_acc = jnp.where(jnp.isneginf(lse_acc), 0.0, w_acc)
    w_b = jnp.where(jnp.isneginf(lse_b), 0.0, w_b)
    return o_acc * to_bthd(w_acc) + o_b * to_bthd(w_b), lse_new


@partial(jax.custom_vjp, nondiff_argnums=(4, 5, 6))
def _ring_flash(q, k, v, mask, axis_name, scale, causal):
    out, _ = _ring_flash_fwd_impl(q, k, v, mask, axis_name, scale, causal)
    return out


def _ring_flash_fwd_impl(q, k, v, mask, axis_name, scale, causal):
    n = lax.psum(1, axis_name)
    s_idx = lax.axis_index(axis_name)
    interpret = jax.default_backend() != "tpu"
    perm = [(i, (i + 1) % n) for i in range(n)]

    # Local block first (triangular under causality), then n-1 hops.
    # mask=None stays None end to end (no dummy all-ones row rotating).
    o_acc, lse_acc = _pair_fwd(q, k, v, mask, scale, causal, interpret)
    kb, vb, mb = k, v, mask
    for r in range(n - 1):
        kb, vb = (lax.ppermute(x, axis_name, perm) for x in (kb, vb))
        if mb is not None:
            mb = lax.ppermute(mb, axis_name, perm)
        if causal:
            src = (s_idx - r - 1) % n
            visible = src < s_idx

            def live(args):
                o_acc, lse_acc = args
                o_b, lse_b = _pair_fwd(
                    q, kb, vb, mb, scale, False, interpret
                )
                return _merge_hop(o_acc, lse_acc, o_b, lse_b)

            o_acc, lse_acc = lax.cond(
                visible, live, lambda a: a, (o_acc, lse_acc)
            )
        else:
            o_b, lse_b = _pair_fwd(q, kb, vb, mb, scale, False, interpret)
            o_acc, lse_acc = _merge_hop(o_acc, lse_acc, o_b, lse_b)
    out = o_acc.astype(q.dtype)
    # Backward sentinel: rows no block contributed to carry +inf so the
    # per-pair backward recomputes p == 0 there (flash convention).
    lse_res = jnp.where(jnp.isneginf(lse_acc), jnp.inf, lse_acc)
    return out, lse_res


def _ring_flash_fwd(q, k, v, mask, axis_name, scale, causal):
    out, lse = _ring_flash_fwd_impl(
        q, k, v, mask, axis_name, scale, causal
    )
    return out, (q, k, v, mask, out, lse)


def _ring_flash_bwd(axis_name, scale, causal, res, g):
    q, k, v, mask, out, lse = res
    n = lax.psum(1, axis_name)
    s_idx = lax.axis_index(axis_name)
    interpret = jax.default_backend() != "tpu"
    perm = [(i, (i + 1) % n) for i in range(n)]

    # Local block (triangular under causality): dq accumulates locally,
    # dk/dv accumulate in buffers that ROTATE WITH their block and are
    # delivered home by one final hop.
    dq, dk_acc, dv_acc = _pair_bwd(
        q, k, v, mask, out, lse, g, scale, causal, interpret
    )
    dq = dq.astype(jnp.float32)
    dk_acc = dk_acc.astype(jnp.float32)
    dv_acc = dv_acc.astype(jnp.float32)
    kb, vb, mb = k, v, mask
    for r in range(n - 1):
        kb, vb, dk_acc, dv_acc = (
            lax.ppermute(x, axis_name, perm)
            for x in (kb, vb, dk_acc, dv_acc)
        )
        if mb is not None:
            mb = lax.ppermute(mb, axis_name, perm)
        if causal:
            src = (s_idx - r - 1) % n
            visible = src < s_idx

            def live(args):
                dq, dk_acc, dv_acc = args
                dq_c, dk_b, dv_b = _pair_bwd(
                    q, kb, vb, mb, out, lse, g, scale, False, interpret
                )
                return (
                    dq + dq_c.astype(jnp.float32),
                    dk_acc + dk_b.astype(jnp.float32),
                    dv_acc + dv_b.astype(jnp.float32),
                )

            dq, dk_acc, dv_acc = lax.cond(
                visible, live, lambda a: a, (dq, dk_acc, dv_acc)
            )
        else:
            dq_c, dk_b, dv_b = _pair_bwd(
                q, kb, vb, mb, out, lse, g, scale, False, interpret
            )
            dq = dq + dq_c.astype(jnp.float32)
            dk_acc = dk_acc + dk_b.astype(jnp.float32)
            dv_acc = dv_acc + dv_b.astype(jnp.float32)
    # The accumulator for block (s+1) sits on device s after n-1 hops;
    # one more rotation delivers every block's gradient to its owner.
    dk_acc, dv_acc = (
        lax.ppermute(x, axis_name, perm) for x in (dk_acc, dv_acc)
    )
    return (
        dq.astype(q.dtype), dk_acc.astype(k.dtype),
        dv_acc.astype(v.dtype), None,
    )


_ring_flash.defvjp(_ring_flash_fwd, _ring_flash_bwd)


def ring_flash_attention(
    q: jax.Array,
    k: jax.Array,
    v: jax.Array,
    mask: Optional[jax.Array] = None,
    *,
    axis_name: str = "seq",
    scale: Optional[float] = None,
    causal: bool = False,
) -> jax.Array:
    """Ring attention with the Pallas flash kernels as the per-hop core:
    the distributed long-context hot path. The plain `ring_attention`
    materializes an O(Tl x Tl) logits tile per hop in HBM; here every
    hop runs the fused kernel (forward AND the ring's backward), so
    per-device attention memory is O(Tl) regardless of the global
    sequence length, and hop compute rides the MXU at the flash
    kernel's rate. Exact — the hops merge by log-sum-exp, and the
    backward recomputes each block's probabilities under the GLOBAL
    logsumexp, rotating dk/dv accumulators home around the ring.

    Same contract as `ring_attention` (call inside `shard_map`, local
    shapes (B, T/N, H, dh), optional (B, T/N) key-validity mask,
    `causal=True` with block-level visibility + skipped hidden hops).
    Shapes the kernels can't tile (tiny CI blocks) fall back to dense
    per-hop math with identical semantics.
    """
    scale = scale if scale is not None else 1.0 / math.sqrt(q.shape[-1])
    return _ring_flash(q, k, v, mask, axis_name, scale, causal)
