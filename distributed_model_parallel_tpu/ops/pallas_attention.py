"""Fused flash-attention kernels in Pallas (TPU) — forward AND backward.

The attention hot op, tiled for the MXU with online softmax so the
(Tq, Tkv) logits matrix never materializes in HBM: the grid streams
(block_q x block_k) tiles, q@k^T runs on the MXU in f32, and the running
max / denominator / numerator live in VMEM scratch across the k-block
grid steps (TPU grids iterate the last axis innermost, and scratch
persists across steps — the canonical Pallas flash pattern).

Backward is fused too: the forward saves only the per-row logsumexp
(LSE — O(T), not O(T²)); the backward recomputes each (bq, bk)
probability tile from q/k/LSE in VMEM and accumulates
  dq += scale · dS @ K       (one kernel, k-blocks innermost)
  dv += Pᵀ @ dO,  dk += scale · dSᵀ @ Q   (one kernel, q-blocks innermost)
with dS = P ∘ (dO @ Vᵀ − Δ), Δ = rowsum(dO ∘ O) computed cheaply in XLA.
Nothing O(T²) ever leaves VMEM in either direction.

TPU layout notes (Mosaic requires a block's last two dims to be
(8k, 128k) multiples or to equal the array dims):
* Per-row stats (LSE, Δ) are stored lane-broadcast as (B, H, Tq, 128)
  f32 — the same layout the reference TPU flash kernels use — so their
  (1, 1, bq, 128) blocks tile legally; kernels read lane 0.
* The (B, Tkv) key-validity mask is reshaped (B, 1, Tkv) and each grid
  step loads the whole row, slicing its (bk,) window with `pl.dslice`
  — legal for every block size, and a Tkv-byte row of int8 is free.

Contract and scope:
* Same contract as `dot_product_attention`: (B, T, H, Dh) tensors,
  optional (B, Tkv) key-validity mask, `causal=True` for decoder models,
  computes f32, returns q.dtype. v (and so the output) may have another
  width than q.k (latent attention: 192 / 128).
* Sequence lengths must divide the block sizes (the wrapper shrinks
  blocks to fit when the sequence is shorter); lengths with no
  multiple-of-8 divisor >= 8 fall back to the XLA path — forward and
  backward stay consistent either way. Composes with ring / Ulysses
  sequence parallelism, which shard T across chips before any kernel
  runs.
* On non-TPU backends the kernels run in Pallas interpret mode (slow,
  CI-only) so the numerics are testable on the 8-virtual-device mesh.
* `local_causal_attention` is the attention of an engine whose sequence
  is whole on a chip: it picks these kernels or the dense XLA graph per
  call from the backend, the lengths and the mask's shape (the dense
  graph off a TPU, always), so no flag has to.
"""

from __future__ import annotations

import functools
import math
from typing import Optional

import jax
import jax.numpy as jnp
from jax import lax
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu

from distributed_model_parallel_tpu.ops.attention import (
    dot_product_attention,
)

_NEG = jnp.finfo(jnp.float32).min
_LANES = 128  # lane-broadcast width for per-row stats (see module doc)
# v5e-tuned default tiles (see flash_attention docstring); shared with
# the ring_flash per-hop dispatch so a retune applies everywhere.
DEFAULT_BLOCK_Q = 512
DEFAULT_BLOCK_K = 1024


def _mask_window(mask_ref, ki: int, bk: int):
    """(1, bk) bool validity window from the whole-row (1, 1, Tkv) mask.
    Kept rank-2 — Mosaic's vector layouts want >= 2D operands."""
    if mask_ref is None:
        return None
    return mask_ref[0, :, pl.dslice(ki * bk, bk)] != 0


def _tile_logits(q, k, scale, valid, causal, qi, ki, bq, bk):
    """One (bq, bk) logits tile: scale·q@kᵀ with mask/causal applied —
    shared by the forward recurrence and both backward kernels so the
    recomputed probabilities match the saved LSE bit-for-bit.

    q/k stay in their storage dtype (bf16 inputs hit the MXU's native
    bf16 path — ~4x the f32 matmul rate on v5e) with f32 accumulation;
    the scale is applied to the f32 product, exactly."""
    s = scale * lax.dot_general(  # (bq, bk) on the MXU
        q, k,
        dimension_numbers=(((1,), (1,)), ((), ())),
        preferred_element_type=jnp.float32,
    )
    if valid is not None:  # static: masked kernel variant only
        s = jnp.where(valid, s, _NEG)  # valid is (1, bk), broadcasts
    if causal:  # global row >= global col within this tile pair
        rows = qi * bq + lax.broadcasted_iota(jnp.int32, (bq, bk), 0)
        cols = ki * bk + lax.broadcasted_iota(jnp.int32, (bq, bk), 1)
        s = jnp.where(rows >= cols, s, _NEG)
    return s


def _rows(ref):
    """Lane-0 column of a lane-broadcast (1, 1, bq, 128) stats block ->
    (bq, 1)."""
    return ref[0, 0][:, 0:1]


def _flash_step(q_ref, k_ref, v_ref, mask_ref, o_ref, lse_ref,
                m_scr, l_scr, acc_scr, scale: float, nk: int,
                causal: bool = False):
    ki = pl.program_id(3)
    qi = pl.program_id(2)  # hoisted: program_id may not be called inside
    bq = q_ref.shape[2]    # the pl.when branch (no lowering rule there)
    bk = k_ref.shape[2]

    @pl.when(ki == 0)
    def _():
        m_scr[:] = jnp.full_like(m_scr[:], _NEG)
        l_scr[:] = jnp.zeros_like(l_scr[:])
        acc_scr[:] = jnp.zeros_like(acc_scr[:])

    def compute():
        q = q_ref[0, 0]                                  # (bq, dh)
        k = k_ref[0, 0]                                  # (bk, dh)
        v = v_ref[0, 0]                                  # (bk, dh)
        valid = _mask_window(mask_ref, ki, bk)
        s = _tile_logits(q, k, scale, valid, causal, qi, ki, bq, bk)

        m_prev = m_scr[:]                                # (bq, 1)
        m_new = jnp.maximum(m_prev, jnp.max(s, axis=-1, keepdims=True))
        p = jnp.exp(s - m_new)                           # (bq, bk) f32
        if valid is not None or causal:
            # exp(_NEG - m_new) underflows to 0 for any finite m_new, but
            # a row that is masked in EVERY tile so far has m_new == _NEG
            # and would get p == exp(0) == 1 on its masked entries; zero
            # them explicitly so l stays 0 and finalize emits out == 0.
            p = jnp.where(s == _NEG, 0.0, p)
        corr = jnp.exp(m_prev - m_new)                   # (bq, 1)
        l_scr[:] = l_scr[:] * corr + jnp.sum(p, axis=-1, keepdims=True)
        # p rides the MXU in the value dtype (bf16 for bf16 models —
        # p in [0,1] loses nothing material); accumulation stays f32.
        acc_scr[:] = acc_scr[:] * corr + lax.dot_general(
            p.astype(v.dtype), v,
            dimension_numbers=(((1,), (0,)), ((), ())),
            preferred_element_type=jnp.float32,
        )
        m_scr[:] = m_new

    if causal:
        # Skip tiles strictly above the causal frontier: their logits
        # would all be _NEG and contribute nothing, but the MXU work and
        # K/V DMA are ~half the grid at long T — predicate them away.
        pl.when(ki * bk <= qi * bq + bq - 1)(compute)
    else:
        compute()

    @pl.when(ki == nk - 1)
    def _():
        l = l_scr[:]                                     # (bq, 1)
        denom = jnp.where(l > 0, l, 1.0)
        o_ref[0, 0] = (acc_scr[:] / denom).astype(o_ref.dtype)
        if lse_ref is not None:
            # Per-row logsumexp, the only O(T) residual the backward
            # needs. Fully-masked rows (l == 0) store +inf so the
            # backward's exp(s - lse) recomputes p == 0 => zero
            # gradients, matching the forward's zero output there.
            lse = jnp.where(l > 0, m_scr[:] + jnp.log(denom), jnp.inf)
            lse_ref[0, 0] = lax.broadcast_in_dim(
                lse, lse_ref.shape[2:], (0, 1)
            )


def _fwd_kernel(*refs, scale: float, nk: int, causal: bool,
                has_mask: bool, with_lse: bool):
    """Shared forward kernel body; operand list is
    q, k, v[, mask], o[, lse], m_scr, l_scr, acc_scr — the mask row and
    the LSE output are static build-time options (inference drops LSE so
    the opaque pallas_call never writes a residual nothing reads)."""
    i = 3
    mask_ref = refs[i] if has_mask else None
    i += int(has_mask)
    o_ref = refs[i]
    lse_ref = refs[i + 1] if with_lse else None
    m_scr, l_scr, acc_scr = refs[-3:]
    _flash_step(refs[0], refs[1], refs[2], mask_ref, o_ref, lse_ref,
                m_scr, l_scr, acc_scr, scale, nk, causal)


def _pick_block(t: int, want: int) -> int:
    """Largest multiple-of-8 divisor of `t` that is <= want (block shapes
    must tile the sequence exactly; Mosaic wants sublane multiples of 8).
    Returns 0 when none exists."""
    b = min(want, t)
    while b >= 8:
        if t % b == 0 and b % 8 == 0:
            return b
        b -= 1
    return 0


def _blocks_viable(tq: int, tk: int, block_q: int, block_k: int):
    bq = _pick_block(tq, block_q)
    bk = _pick_block(tk, block_k)
    # Awkward sequence lengths (primes, odd) have no viable tiling — a
    # silent performance cliff and a Mosaic lowering error. The XLA path
    # is the better program there.
    return (bq, bk) if bq and bk else None


def _row_stats_spec(bq):
    return pl.BlockSpec(
        (1, 1, bq, _LANES), lambda bi, hi, qi, ki: (bi, hi, qi, 0)
    )


def _whole_mask_spec(tk):
    return pl.BlockSpec((1, 1, tk), lambda bi, hi, qi, ki: (bi, 0, 0))


def _flash_forward(q, k, v, mask, scale, block_q, block_k, interpret,
                   causal=False, need_lse=False):
    """Returns (out, lse) from the fused kernel — lse lane-broadcast as
    (B, H, Tq, 128), or None unless `need_lse` (the vjp forward) — or
    (xla_out, None) on the small-block fallback."""
    b, tq, h, dh = q.shape
    tk, dv = k.shape[1], v.shape[-1]
    blocks = _blocks_viable(tq, tk, block_q, block_k)
    if blocks is None:
        return dot_product_attention(
            q, k, v, mask, scale=scale, causal=causal
        ), None
    bq, bk = blocks
    nq, nk = tq // bq, tk // bk

    # (B, H, T, Dh) layout for clean (seq, head_dim) blocks.
    qt = jnp.transpose(q, (0, 2, 1, 3))
    kt = jnp.transpose(k, (0, 2, 1, 3))
    vt = jnp.transpose(v, (0, 2, 1, 3))

    qspec = pl.BlockSpec((1, 1, bq, dh), lambda bi, hi, qi, ki: (bi, hi, qi, 0))
    kspec = pl.BlockSpec((1, 1, bk, dh), lambda bi, hi, qi, ki: (bi, hi, ki, 0))
    vspec = pl.BlockSpec((1, 1, bk, dv), lambda bi, hi, qi, ki: (bi, hi, ki, 0))
    operands = [qt, kt, vt]
    in_specs = [qspec, kspec, vspec]
    if mask is not None:
        operands.append(mask.astype(jnp.int8)[:, None, :])
        in_specs.append(_whole_mask_spec(tk))
    kernel = functools.partial(
        _fwd_kernel, scale=scale, nk=nk, causal=causal,
        has_mask=mask is not None, with_lse=need_lse,
    )
    out_specs = [
        pl.BlockSpec((1, 1, bq, dv), lambda bi, hi, qi, ki: (bi, hi, qi, 0))
    ]
    out_shape = [jax.ShapeDtypeStruct((b, h, tq, dv), q.dtype)]
    if need_lse:
        out_specs.append(_row_stats_spec(bq))
        out_shape.append(
            jax.ShapeDtypeStruct((b, h, tq, _LANES), jnp.float32)
        )
    res = pl.pallas_call(
        kernel,
        grid=(b, h, nq, nk),
        in_specs=in_specs,
        out_specs=out_specs,
        out_shape=out_shape,
        scratch_shapes=[
            pltpu.VMEM((bq, 1), jnp.float32),   # running max
            pltpu.VMEM((bq, 1), jnp.float32),   # running denominator
            pltpu.VMEM((bq, dv), jnp.float32),  # running numerator
        ],
        interpret=interpret,
    )(*operands)
    if need_lse:
        out, lse = res
    else:
        (out,), lse = res, None
    return jnp.transpose(out, (0, 2, 1, 3)), lse


# ------------------------------------------------------------- backward


def _bwd_dq_step(q_ref, k_ref, v_ref, do_ref, lse_ref, delta_ref, mask_ref,
                 dq_ref, dq_scr, scale: float, nk: int, causal: bool):
    ki = pl.program_id(3)
    qi = pl.program_id(2)
    bq = q_ref.shape[2]
    bk = k_ref.shape[2]

    @pl.when(ki == 0)
    def _():
        dq_scr[:] = jnp.zeros_like(dq_scr[:])

    def compute():
        q = q_ref[0, 0]
        k = k_ref[0, 0]
        v = v_ref[0, 0]
        do = do_ref[0, 0]
        valid = _mask_window(mask_ref, ki, bk)
        s = _tile_logits(q, k, scale, valid, causal, qi, ki, bq, bk)
        p = jnp.exp(s - _rows(lse_ref))                  # (bq, bk) f32
        dp = lax.dot_general(                            # dO @ Vᵀ
            do, v,
            dimension_numbers=(((1,), (1,)), ((), ())),
            preferred_element_type=jnp.float32,
        )
        ds = p * (dp - _rows(delta_ref))                 # f32
        dq_scr[:] = dq_scr[:] + scale * lax.dot_general(
            ds.astype(k.dtype), k,  # MXU-native dtype, f32 accumulate
            dimension_numbers=(((1,), (0,)), ((), ())),
            preferred_element_type=jnp.float32,
        )

    if causal:
        pl.when(ki * bk <= qi * bq + bq - 1)(compute)
    else:
        compute()

    @pl.when(ki == nk - 1)
    def _():
        dq_ref[0, 0] = dq_scr[:].astype(dq_ref.dtype)


def _bwd_dkv_step(q_ref, k_ref, v_ref, do_ref, lse_ref, delta_ref, mask_ref,
                  dk_ref, dv_ref, dk_scr, dv_scr,
                  scale: float, nq: int, causal: bool):
    qi = pl.program_id(3)  # q-blocks innermost in this kernel
    ki = pl.program_id(2)
    bq = q_ref.shape[2]
    bk = k_ref.shape[2]

    @pl.when(qi == 0)
    def _():
        dk_scr[:] = jnp.zeros_like(dk_scr[:])
        dv_scr[:] = jnp.zeros_like(dv_scr[:])

    def compute():
        q = q_ref[0, 0]
        k = k_ref[0, 0]
        v = v_ref[0, 0]
        do = do_ref[0, 0]
        valid = _mask_window(mask_ref, ki, bk)
        s = _tile_logits(q, k, scale, valid, causal, qi, ki, bq, bk)
        p = jnp.exp(s - _rows(lse_ref))                  # (bq, bk) f32
        dv_scr[:] = dv_scr[:] + lax.dot_general(         # Pᵀ @ dO
            p.astype(do.dtype), do,
            dimension_numbers=(((0,), (0,)), ((), ())),
            preferred_element_type=jnp.float32,
        )
        dp = lax.dot_general(                            # dO @ Vᵀ
            do, v,
            dimension_numbers=(((1,), (1,)), ((), ())),
            preferred_element_type=jnp.float32,
        )
        ds = p * (dp - _rows(delta_ref))                 # f32
        dk_scr[:] = dk_scr[:] + scale * lax.dot_general(  # dSᵀ @ Q
            ds.astype(q.dtype), q,
            dimension_numbers=(((0,), (0,)), ((), ())),
            preferred_element_type=jnp.float32,
        )

    if causal:
        # Only q blocks at or below the causal frontier of this k block
        # contribute; earlier q blocks see an all-masked tile.
        pl.when(qi * bq + bq - 1 >= ki * bk)(compute)
    else:
        compute()

    @pl.when(qi == nq - 1)
    def _():
        dk_ref[0, 0] = dk_scr[:].astype(dk_ref.dtype)
        dv_ref[0, 0] = dv_scr[:].astype(dv_ref.dtype)


def _flash_backward(q, k, v, mask, out, lse, g, scale, bq, bk,
                    interpret, causal):
    b, tq, h, dh = q.shape
    tk, dv = k.shape[1], v.shape[-1]
    nq, nk = tq // bq, tk // bk

    qt = jnp.transpose(q, (0, 2, 1, 3))
    kt = jnp.transpose(k, (0, 2, 1, 3))
    vt = jnp.transpose(v, (0, 2, 1, 3))
    dot = jnp.transpose(g, (0, 2, 1, 3))
    # Δ_i = Σ_d dO_id · O_id — O(B·H·T·Dh) elementwise work; XLA fuses
    # this more cheaply than a kernel would. Lane-broadcast like LSE.
    delta = jnp.broadcast_to(
        jnp.sum(
            dot.astype(jnp.float32)
            * jnp.transpose(out, (0, 2, 1, 3)).astype(jnp.float32),
            axis=-1, keepdims=True,
        ),
        (b, h, tq, _LANES),
    )

    mask3 = None if mask is None else mask.astype(jnp.int8)[:, None, :]

    # dq: iterate k blocks innermost, accumulate into a (bq, dh) scratch.
    qspec = pl.BlockSpec(
        (1, 1, bq, dh), lambda bi, hi, qi, ki: (bi, hi, qi, 0)
    )
    kspec = pl.BlockSpec(
        (1, 1, bk, dh), lambda bi, hi, qi, ki: (bi, hi, ki, 0)
    )
    # v and dO (and dv below) carry the value width, which latent
    # attention makes narrower than q.k's; equal widths give the same
    # specs as before.
    vspec = pl.BlockSpec(
        (1, 1, bk, dv), lambda bi, hi, qi, ki: (bi, hi, ki, 0)
    )
    dospec = pl.BlockSpec(
        (1, 1, bq, dv), lambda bi, hi, qi, ki: (bi, hi, qi, 0)
    )
    dq_ops = [qt, kt, vt, dot, lse, delta]
    dq_specs = [qspec, kspec, vspec, dospec, _row_stats_spec(bq),
                _row_stats_spec(bq)]
    if mask3 is not None:
        dq_ops.append(mask3)
        dq_specs.append(_whole_mask_spec(tk))

    def dq_kernel(*refs):
        if mask3 is not None:
            q_r, k_r, v_r, do_r, lse_r, dl_r, m_r, dq_r, scr = refs
        else:
            (q_r, k_r, v_r, do_r, lse_r, dl_r, dq_r, scr), m_r = refs, None
        _bwd_dq_step(q_r, k_r, v_r, do_r, lse_r, dl_r, m_r, dq_r, scr,
                     scale, nk, causal)

    dq = pl.pallas_call(
        dq_kernel,
        grid=(b, h, nq, nk),
        in_specs=dq_specs,
        out_specs=qspec,
        out_shape=jax.ShapeDtypeStruct((b, h, tq, dh), q.dtype),
        scratch_shapes=[pltpu.VMEM((bq, dh), jnp.float32)],
        interpret=interpret,
    )(*dq_ops)

    # dk/dv: iterate q blocks innermost (note the swapped grid axes — the
    # index maps below read grid position 2 as ki, 3 as qi).
    kv_qspec = pl.BlockSpec(
        (1, 1, bq, dh), lambda bi, hi, ki, qi: (bi, hi, qi, 0)
    )
    kv_kspec = pl.BlockSpec(
        (1, 1, bk, dh), lambda bi, hi, ki, qi: (bi, hi, ki, 0)
    )
    kv_vspec = pl.BlockSpec(
        (1, 1, bk, dv), lambda bi, hi, ki, qi: (bi, hi, ki, 0)
    )
    kv_dospec = pl.BlockSpec(
        (1, 1, bq, dv), lambda bi, hi, ki, qi: (bi, hi, qi, 0)
    )
    kv_rowq = pl.BlockSpec(
        (1, 1, bq, _LANES), lambda bi, hi, ki, qi: (bi, hi, qi, 0)
    )
    dkv_ops = [qt, kt, vt, dot, lse, delta]
    dkv_specs = [kv_qspec, kv_kspec, kv_vspec, kv_dospec, kv_rowq, kv_rowq]
    if mask3 is not None:
        dkv_ops.append(mask3)
        # _whole_mask_spec's index map ignores the two block grid axes,
        # so it is correct here despite this kernel's swapped grid.
        dkv_specs.append(_whole_mask_spec(tk))

    def dkv_kernel(*refs):
        if mask3 is not None:
            (q_r, k_r, v_r, do_r, lse_r, dl_r, m_r,
             dk_r, dv_r, kscr, vscr) = refs
        else:
            (q_r, k_r, v_r, do_r, lse_r, dl_r,
             dk_r, dv_r, kscr, vscr), m_r = refs, None
        _bwd_dkv_step(q_r, k_r, v_r, do_r, lse_r, dl_r, m_r,
                      dk_r, dv_r, kscr, vscr, scale, nq, causal)

    dk, dv = pl.pallas_call(
        dkv_kernel,
        grid=(b, h, nk, nq),
        in_specs=dkv_specs,
        out_specs=[kv_kspec, kv_vspec],
        out_shape=[
            jax.ShapeDtypeStruct((b, h, tk, dh), k.dtype),
            jax.ShapeDtypeStruct((b, h, tk, dv), v.dtype),
        ],
        scratch_shapes=[
            pltpu.VMEM((bk, dh), jnp.float32),
            pltpu.VMEM((bk, dv), jnp.float32),
        ],
        interpret=interpret,
    )(*dkv_ops)

    to_bthd = lambda x: jnp.transpose(x, (0, 2, 1, 3))
    return to_bthd(dq), to_bthd(dk), to_bthd(dv)


@functools.partial(jax.custom_vjp, nondiff_argnums=(4, 5, 6, 7, 8))
def _flash(q, k, v, mask, scale, block_q, block_k, interpret, causal):
    out, _ = _flash_forward(
        q, k, v, mask, scale, block_q, block_k, interpret, causal
    )
    return out


def _flash_fwd(q, k, v, mask, scale, block_q, block_k, interpret, causal):
    out, lse = _flash_forward(
        q, k, v, mask, scale, block_q, block_k, interpret, causal,
        need_lse=True,
    )
    return out, (q, k, v, mask, out, lse)


def _flash_bwd(scale, block_q, block_k, interpret, causal, res, g):
    q, k, v, mask, out, lse = res
    blocks = _blocks_viable(q.shape[1], k.shape[1], block_q, block_k)
    if lse is not None and blocks is not None:
        dq, dk, dv = _flash_backward(
            q, k, v, mask, out, lse, g, scale, *blocks, interpret, causal
        )
        return dq, dk, dv, None
    # Small-block fallback: the forward ran through XLA, so recompute
    # the XLA graph's exact gradients.
    _, vjp = jax.vjp(
        lambda q, k, v: dot_product_attention(
            q, k, v, mask, scale=scale, causal=causal
        ),
        q, k, v,
    )
    dq, dk, dv = vjp(g)
    return dq, dk, dv, None


_flash.defvjp(_flash_fwd, _flash_bwd)


def flash_attention(
    q: jax.Array,
    k: jax.Array,
    v: jax.Array,
    mask: Optional[jax.Array] = None,
    *,
    scale: Optional[float] = None,
    causal: bool = False,
    block_q: int = DEFAULT_BLOCK_Q,
    block_k: int = DEFAULT_BLOCK_K,
    interpret: Optional[bool] = None,
) -> jax.Array:
    """Drop-in `attention_fn` backed by the Pallas flash kernels.

    Default blocks (512, 1024) are tuned on v5e: the (bq, bk) grid-step
    count — not matmul rate — capped throughput at the old (128, 128)
    (measured 7 -> 24 TF/s forward at T=8k, B=2, H=8, dh=64; shorter
    sequences shrink blocks to fit automatically).

    `interpret=None` auto-selects: compiled on TPU, interpreter
    elsewhere (tests). See module docstring for scope.
    """
    if interpret is None:
        interpret = jax.default_backend() != "tpu"
    scale = scale if scale is not None else 1.0 / math.sqrt(q.shape[-1])
    if mask is not None and mask.ndim != 2:
        raise NotImplementedError(
            "flash_attention supports (B, Tkv) key-validity masks; use "
            "dot_product_attention for general logit masks"
        )
    return _flash(q, k, v, mask, scale, block_q, block_k, interpret, causal)


def _on_tpu() -> bool:
    # The selector's own predicate, apart from `flash_attention`'s
    # `interpret` default: a test that answers "tpu" here still runs
    # the kernels in the interpreter.
    return jax.default_backend() == "tpu"


def local_attention_kind(tq: int, tk: int, mask) -> str:
    """Which program `local_causal_attention` runs for these static
    facts: `"flash"` on a TPU when the mask is None or a (B, Tkv)
    key-validity mask and the default blocks tile (Tq, Tkv); `"dense"`
    anywhere else. Off a TPU it is always `"dense"`, never the Pallas
    interpreter: nobody deploys that, and every CPU test compiles the
    XLA program."""
    if (
        _on_tpu()
        and (mask is None or mask.ndim == 2)
        and _blocks_viable(tq, tk, DEFAULT_BLOCK_Q, DEFAULT_BLOCK_K)
    ):
        return "flash"
    return "dense"


def local_causal_attention(
    q: jax.Array,
    k: jax.Array,
    v: jax.Array,
    mask: Optional[jax.Array] = None,
    *,
    scale: Optional[float] = None,
) -> jax.Array:
    """Drop-in `attention_fn` for exact causal attention over a sequence
    held whole on one device. One algorithm, two programs for it — the
    flash kernels or the dense XLA graph — picked per call by
    `local_attention_kind` from what the call can observe (the backend,
    the sequence lengths, the mask's shape)."""
    kind = local_attention_kind(q.shape[1], k.shape[1], mask)
    attend = flash_attention if kind == "flash" else dot_product_attention
    return attend(q, k, v, mask, scale=scale, causal=True)
