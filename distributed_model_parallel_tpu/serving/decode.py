"""Incremental (KV-cached) decode and prefill, threaded through the
existing `attention_fn(q, k, v, mask)` seam.

The decoder blocks are NOT rewritten for inference: `gpt.decoder_blocks`
builds the same `models/transformer.py` layers the training engines run,
and the cache plumbing rides the `attention_fn` parameter — per traced
step a fresh recorder object is constructed, the blocks are rebuilt
around it (Layer construction is just closures; params come from the
dense `gpt_lm` pytree, so checkpoints and the TP/SP training engines'
states serve unchanged), and each block's single `attention_fn` call
becomes one layer's cache update + incremental attention:

  * decode (`CacheAttention`): the block hands over the NEW token's
    q/k/v (B, 1, H, Dh); the recorder writes k/v into layer `i` of the
    cache at each slot's own position (a ragged batch — every slot sits
    at a different position), then attends q against the full cached
    prefix through `ops.attention.dot_product_attention` with a
    per-slot key-validity mask — the same core the dense model runs,
    so logits are pinned identical to full recompute
    (tests/test_serving.py).
  * sp decode (`SeqShardedCacheAttention`): the cache's position axis
    is sharded over 'seq'; each shard attends q over ITS positions and
    the partial results merge with the online-softmax recurrence
    (pmax of the running max, psum of the exp-sums and weighted
    values) — the same flash-style merge `ops/ring_attention.py` uses,
    exact, not approximate.
  * prefill (`PrefillRecorder`): wraps any causal attention core
    (dense `dot_product_attention` or, under the sp layout,
    `ring_attention` over 'seq' — long prefill reuses the training
    ring) and captures each layer's full-prompt K/V for the cache
    write.

Decode-time TP projections ride the latency-hiding rings
(`DecodeCollectiveMatmul`): at decode the sequence axis is one token,
so the chunked `ag_matmul`/`matmul_rs` rings run over the SLOT-BATCH
axis instead — the residual stream between blocks is slot-sharded over
'model' (the decode analog of the Megatron-SP layout), column
projections gather slots via S-1 ppermute hops, row projections
reduce-scatter partial sums back, and no monolithic all-gather touches
the opted-in path (pinned by the hlolint `serve-decode-ring` rule:
exactly 4·L·(S-1) permutes per decode step).
"""

from __future__ import annotations

import dataclasses
from functools import partial
from typing import Any, List, Optional

import jax
import jax.numpy as jnp
from jax import lax
from jax.sharding import Mesh, PartitionSpec as P

from distributed_model_parallel_tpu.models.gpt import (  # noqa: F401
    chunk_stem,
    decode_stem,
    prefill_stem,
    verify_stem,
)
from distributed_model_parallel_tpu.ops.attention import (
    grouped_query_attention,
)
from distributed_model_parallel_tpu.ops.collective_matmul import (
    ag_matmul,
    ag_matmul_quant,
    matmul_rs,
    matmul_rs_quant,
)
from distributed_model_parallel_tpu.ops.latent_attention import (
    latent_attention_blocks,
    paged_decode_attention,
    rope,
)
from distributed_model_parallel_tpu.ops.quant_matmul import quant_dot
from distributed_model_parallel_tpu.runtime.compat import shard_map


# ----------------------------------------------------- cache utilities


def write_position(cache_layer, new, positions, active):
    """Write each slot's (1, H, Dh) update at its own position along
    the cache's position axis; inactive slots keep their old row
    (admission gaps must not smear garbage into recycled slots).
    cache_layer (slots, max_len, H, Dh), new (slots, 1, H, Dh)."""
    upd = jax.vmap(
        lambda c, u, p: lax.dynamic_update_slice_in_dim(
            c, u.astype(c.dtype), p, axis=0
        )
    )(cache_layer, new, positions)
    return jnp.where(active[:, None, None, None], upd, cache_layer)


# ------------------------------------------------- decode attention fns


class CacheAttention:
    """attention_fn for one traced decode step, replicated/TP layouts.

    Construct fresh per trace with the incoming cache; each block's
    call consumes the next layer index in order (the blocks apply
    sequentially, so call order IS layer order). After the blocks run,
    `.k`/`.v` hold the updated stacked caches."""

    def __init__(self, k, v, positions, active):
        self.k = k  # (layers, slots, max_len, H, Dh)
        self.v = v
        self.positions = positions  # (slots,) write/attend position
        self.active = active  # (slots,) bool
        self.layer = 0

    def __call__(self, q, k_new, v_new, mask):
        i = self.layer
        self.layer += 1
        kc = write_position(self.k[i], k_new, self.positions, self.active)
        vc = write_position(self.v[i], v_new, self.positions, self.active)
        self.k = self.k.at[i].set(kc)
        self.v = self.v.at[i].set(vc)
        # Keys at the slot's position or earlier are the live prefix
        # (the new token was just written AT the position); later
        # positions are zero padding or a recycled slot's stale tail.
        valid = (
            jnp.arange(kc.shape[1])[None, :] <= self.positions[:, None]
        )
        return grouped_query_attention(q, kc, vc, mask=valid)


def _sp_online_softmax_attend(q, kc, vc, valid, axis):
    """The exact cross-shard attention merge both sp decode recorders
    share (contiguous AND paged — ONE copy, so the paged==contiguous
    logit-parity pin can never be broken by the two drifting apart):
    each shard scores q against ITS local keys under `valid`
    (slots, local_kv), then the partial softmaxes combine via the
    online recurrence — pmax of the running max, one psum each for the
    exp-sums and weighted values."""
    dh = q.shape[-1]
    scale = 1.0 / jnp.sqrt(dh).astype(jnp.float32)
    qf = q.astype(jnp.float32)
    logits = jnp.einsum(
        "bqhd,bkhd->bhqk", qf, kc.astype(jnp.float32)
    ) * scale  # (slots, H, 1, local_kv)
    neg = jnp.finfo(jnp.float32).min
    logits = jnp.where(valid[:, None, None, :], logits, neg)
    m = lax.pmax(jnp.max(logits, axis=-1), axis)  # (slots, H, 1)
    p = jnp.exp(logits - m[..., None])
    p = jnp.where(valid[:, None, None, :], p, 0.0)
    denom = lax.psum(jnp.sum(p, axis=-1), axis)  # (slots, H, 1)
    num = lax.psum(
        jnp.einsum("bhqk,bkhd->bqhd", p, vc.astype(jnp.float32)),
        axis,
    )  # (slots, 1, H, Dh)
    out = num / jnp.swapaxes(denom, 1, 2)[..., None]
    return out.astype(q.dtype)


class SeqShardedCacheAttention:
    """attention_fn for one traced decode step under the sp layout —
    call INSIDE shard_map over `axis`, with the cache's position axis
    sharded: local cache (layers, slots, max_len/S, H, Dh).

    Each shard writes the new K/V only if it owns the slot's position,
    attends q over its own positions, and the partial softmaxes merge
    exactly via the online recurrence (pmax/psum over `axis`)."""

    def __init__(self, k, v, positions, active, *, axis: str = "seq"):
        self.k = k
        self.v = v
        self.positions = positions
        self.active = active
        self.axis = axis
        self.layer = 0

    def _write(self, cache_layer, new):
        chunk = cache_layer.shape[1]
        idx = lax.axis_index(self.axis)
        local_p = self.positions - idx * chunk
        owns = (local_p >= 0) & (local_p < chunk) & self.active
        upd = jax.vmap(
            lambda c, u, p: lax.dynamic_update_slice_in_dim(
                c, u.astype(c.dtype), p, axis=0
            )
        )(cache_layer, new, jnp.clip(local_p, 0, chunk - 1))
        return jnp.where(owns[:, None, None, None], upd, cache_layer)

    def __call__(self, q, k_new, v_new, mask):
        i = self.layer
        self.layer += 1
        kc = self._write(self.k[i], k_new)
        vc = self._write(self.v[i], v_new)
        self.k = self.k.at[i].set(kc)
        self.v = self.v.at[i].set(vc)
        chunk = kc.shape[1]
        idx = lax.axis_index(self.axis)
        # Global validity of THIS shard's positions: every global
        # position <= the slot's position lives on exactly one shard,
        # so the union over shards is the dense prefix mask.
        gpos = idx * chunk + jnp.arange(chunk)
        valid = gpos[None, :] <= self.positions[:, None]  # (slots, C)
        return _sp_online_softmax_attend(q, kc, vc, valid, self.axis)


class PrefillRecorder:
    """attention_fn wrapper for the prefill pass: runs `core` (causal
    dense attention, or `ring_attention` under the sp layout) unchanged
    and captures each layer's K/V for the cache write."""

    def __init__(self, core):
        self.core = core
        self.ks: List[jax.Array] = []
        self.vs: List[jax.Array] = []

    def __call__(self, q, k, v, mask):
        self.ks.append(k)
        self.vs.append(v)
        return self.core(q, k, v, mask)


# ------------------------------------------------- paged attention fns
#
# The paged twins of the recorders above: K/V live in a page POOL
# (L, num_pages, page_size, H, Dh) and each slot reaches its positions
# through a block table (slots, pages_per_slot) of pool page ids (-1 =
# unallocated). Every recorder gathers the slot's pages into the same
# position-ordered view the contiguous cache stores directly — so the
# attention math (and therefore the logits) is IDENTICAL, and only the
# storage granularity changes. Gathers/scatters are local indexing ops,
# never collectives, so the decode step's collective inventory (hlolint
# `serve-decode-ring`) is untouched by paging.


def _gather_pages(pool_layer, block_table, heads: int):
    """(num_pages, page, H, Dh) x (slots, P) -> position-ordered view
    (slots, P*page, H, Dh); a pool whose pages fold the heads into the
    row, (num_pages, page, H*Dh), gives the same view. Unallocated
    entries (-1) clamp-gather page 0; their positions sit beyond every
    slot's live length, so the validity masks keep them invisible."""
    pages = jnp.take(
        pool_layer, jnp.clip(block_table, 0, pool_layer.shape[0] - 1),
        axis=0,
    )  # (slots, P, page, H, Dh)
    s, p, page = pages.shape[:3]
    return pages.reshape(s, p * page, heads, -1)


def _as_pages(view, pool_layer):
    """A position-ordered view (rows, T, H, Dh) cut back into the
    pool's own pages: (rows, T/page, *pool_layer.shape[1:])."""
    return view.reshape(view.shape[0], -1, *pool_layer.shape[1:])


def _scatter_written_page(pool_layer, view, block_table, positions,
                          active, page_size):
    """Write back ONLY the page each slot's decode write landed in.
    Inactive slots (and unallocated entries) scatter out of bounds and
    drop — the pool is untouched for them. Distinct live slots write
    distinct pool pages (the host's copy-on-write keeps write pages
    private), so the scatter has no duplicate indices."""
    s = view.shape[0]
    num_pages = pool_layer.shape[0]
    pages = _as_pages(view, pool_layer)
    wp = positions // page_size  # (slots,) slot-local page index
    written = jnp.take_along_axis(
        pages, jnp.expand_dims(wp, tuple(range(1, pages.ndim))), axis=1
    )[:, 0]  # (slots, page, H, Dh)
    dst = jnp.take_along_axis(block_table, wp[:, None], axis=1)[:, 0]
    dst = jnp.where(active & (dst >= 0), dst, num_pages)  # OOB -> drop
    return pool_layer.at[dst].set(written, mode="drop")


class PagedCacheAttention:
    """attention_fn for one traced PAGED decode step, replicated/TP
    layouts: gather the slot's pages through the block table, write the
    new token at its own position, attend over the gathered view with
    the same per-slot validity mask as `CacheAttention` (logit parity
    is pinned paged == contiguous == dense), then scatter back only the
    written page."""

    def __init__(self, k, v, block_table, positions, active,
                 page_size: int):
        self.k = k  # (layers, num_pages, page, H, Dh)
        self.v = v
        self.bt = block_table  # (slots, pages_per_slot) int32
        self.positions = positions  # (slots,) write/attend position
        self.active = active  # (slots,) bool
        self.page = page_size
        self.layer = 0

    def __call__(self, q, k_new, v_new, mask):
        i = self.layer
        self.layer += 1
        heads = k_new.shape[2]
        kview = _gather_pages(self.k[i], self.bt, heads)
        vview = _gather_pages(self.v[i], self.bt, heads)
        kc = write_position(kview, k_new, self.positions, self.active)
        vc = write_position(vview, v_new, self.positions, self.active)
        self.k = self.k.at[i].set(_scatter_written_page(
            self.k[i], kc, self.bt, self.positions, self.active,
            self.page,
        ))
        self.v = self.v.at[i].set(_scatter_written_page(
            self.v[i], vc, self.bt, self.positions, self.active,
            self.page,
        ))
        valid = (
            jnp.arange(kc.shape[1])[None, :] <= self.positions[:, None]
        )
        return grouped_query_attention(q, kc, vc, mask=valid)


class PagedSeqShardedCacheAttention:
    """Paged attention_fn for one traced decode step under the sp
    layout — call INSIDE shard_map over `axis`, with each PAGE's
    position axis sharded: local pool (layers, num_pages, page/S, H,
    Dh). Each shard owns positions [idx*psub, (idx+1)*psub) of EVERY
    page, writes the new K/V only if it owns the slot's within-page
    offset, and the per-shard partial softmaxes merge exactly via the
    online recurrence (pmax/psum over `axis`) — the paged twin of
    `SeqShardedCacheAttention`."""

    def __init__(self, k, v, block_table, positions, active,
                 page_size: int, *, axis: str = "seq"):
        self.k = k
        self.v = v
        self.bt = block_table
        self.positions = positions
        self.active = active
        self.page = page_size
        self.axis = axis
        self.layer = 0

    def _local(self, view_len, psub):
        """Global position of each local view element."""
        f = jnp.arange(view_len)
        idx = lax.axis_index(self.axis)
        return (f // psub) * self.page + idx * psub + (f % psub)

    def __call__(self, q, k_new, v_new, mask):
        i = self.layer
        self.layer += 1
        psub = self.k.shape[2]  # page/S positions per shard
        idx = lax.axis_index(self.axis)
        kview = _gather_pages(self.k[i], self.bt, k_new.shape[2])
        vview = _gather_pages(self.v[i], self.bt, k_new.shape[2])
        # Write the new token if THIS shard owns its within-page
        # offset; the local flat index of global position p is
        # (p // page) * psub + (p % page) % psub.
        p = self.positions
        off = p % self.page
        owns = (off // psub == idx) & self.active
        local = (p // self.page) * psub + off % psub
        kw = write_position(kview, k_new, local, owns)
        vw = write_position(vview, v_new, local, owns)
        self.k = self.k.at[i].set(_scatter_written_page(
            self.k[i], kw, self.bt, local, owns, psub,
        ))
        self.v = self.v.at[i].set(_scatter_written_page(
            self.v[i], vw, self.bt, local, owns, psub,
        ))
        gpos = self._local(kw.shape[1], psub)
        valid = gpos[None, :] <= p[:, None]  # (slots, view)
        return _sp_online_softmax_attend(q, kw, vw, valid, self.axis)


class PagedChunkAttention:
    """attention_fn for ONE chunked-prefill step of ONE slot
    (replicated/TP layouts): the chunk's queries (positions
    [start, start+n) for n = chunk length) attend causally over the
    slot's already-cached prefix PLUS the chunk itself, and the
    chunk's K/V lands in the slot's pages.

    The write is a gather-from-chunk select over the whole view (no
    dynamic-slice clamping hazards near max_len): view element at
    global position g takes chunk element g - start when
    start <= g < start + chunk. Chunk PADDING beyond the valid length
    also lands in the view, but padding positions are either
    overwritten by the next chunk / the first decode write (which
    start exactly at start + n_valid) or sit beyond the slot's length
    and stay masked — the same stale-tail discipline the contiguous
    cache relies on. Scatter-back rewrites only the chunk//page + 1
    pages the chunk region can touch (a static count; pages past the
    block table or unallocated entries drop) — never the whole slot,
    and never a prefix-cache SHARED page, since ingestion always
    resumes at or after the matched boundary on freshly allocated
    pages."""

    def __init__(self, k, v, bt_row, start, page_size: int):
        self.k = k
        self.v = v
        self.bt = bt_row  # (pages_per_slot,) int32
        self.start = start  # int32 global position of chunk token 0
        self.page = page_size
        self.layer = 0

    def _write_chunk(self, view, new):
        """view (1, view_len, H, Dh) <- new (1, chunk, H, Dh) at
        [start, start+chunk)."""
        chunk = new.shape[1]
        g = jnp.arange(view.shape[1])
        c = jnp.clip(g - self.start, 0, chunk - 1)
        cand = jnp.take(new[0], c, axis=0)[None].astype(view.dtype)
        inside = (g >= self.start) & (g < self.start + chunk)
        return jnp.where(inside[None, :, None, None], cand, view)

    def _scatter_touched(self, pool_layer, view, chunk: int):
        """Write back the slot-local pages overlapping
        [start, start + chunk): the last touched page index is
        (start + chunk - 1) // page, so with start possibly one short
        of a boundary the span is at most (chunk-1)//page + 2 pages —
        NOT chunk//page + 1, which undercounts whenever the chunk sits
        unaligned (pinned by the logit-parity test at
        prefill_chunk=3 / page_size=4). A trailing index past the real
        span rewrites a just-gathered page with its own bytes."""
        num_pages = pool_layer.shape[0]
        pages = view.reshape(-1, *pool_layer.shape[1:])
        idx = self.start // self.page + jnp.arange(
            (chunk - 1) // self.page + 2
        )
        safe = jnp.clip(idx, 0, pages.shape[0] - 1)
        touched = jnp.take(pages, safe, axis=0)
        dst = jnp.take(self.bt, safe, axis=0)
        ok = (idx < pages.shape[0]) & (dst >= 0)
        dst = jnp.where(ok, dst, num_pages)  # OOB -> drop
        return pool_layer.at[dst].set(touched, mode="drop")

    def __call__(self, q, k_new, v_new, mask):
        i = self.layer
        self.layer += 1
        chunk, heads = k_new.shape[1], k_new.shape[2]
        kview = self._write_chunk(
            _gather_pages(self.k[i], self.bt[None], heads)[0][None], k_new
        )
        vview = self._write_chunk(
            _gather_pages(self.v[i], self.bt[None], heads)[0][None], v_new
        )
        self.k = self.k.at[i].set(
            self._scatter_touched(self.k[i], kview, chunk)
        )
        self.v = self.v.at[i].set(
            self._scatter_touched(self.v[i], vview, chunk)
        )
        # Causal across the prefix boundary: query at global position
        # start + t sees every cached position <= start + t.
        tq = q.shape[1]
        qpos = self.start + jnp.arange(tq)
        valid = (
            jnp.arange(kview.shape[1])[None, :] <= qpos[:, None]
        )  # (Tq, view)
        return grouped_query_attention(
            q, kview, vview, mask=valid[None, None]
        )


class PagedVerifyAttention:
    """attention_fn for ONE speculative VERIFY step over the whole slot
    batch (replicated/TP layouts): every slot's (k+1)-token span — its
    current last token plus the k draft proposals — attends causally
    over the slot's cached prefix PLUS the span itself, exactly the
    `PagedChunkAttention` causal-over-cached-prefix machinery batched
    over slots (each slot at its OWN start position, like
    `PagedCacheAttention`'s ragged batch).

    Writes are the chunk recorder's gather-select over the gathered
    view (no dynamic-slice clamping near max_len), gated per slot on
    `active`; scatter-back rewrites only the (T-1)//page + 2 pages each
    slot's span can touch (a static count — unallocated entries and
    inactive slots drop). The span lands in the cache BEFORE acceptance
    is known: rejected suffix tokens are rolled back host-side by
    truncating the block table (`PagedCacheHost.truncate`) — pages are
    freed, never copied, and stale K/V inside the kept tail stays
    masked by the slot's position like any recycled slot's."""

    def __init__(self, k, v, block_table, positions, active,
                 page_size: int):
        self.k = k  # (layers, num_pages, page, H, Dh)
        self.v = v
        self.bt = block_table  # (slots, pages_per_slot) int32
        self.positions = positions  # (slots,) span START position
        self.active = active  # (slots,) bool
        self.page = page_size
        self.layer = 0

    def _write_span(self, view, new):
        """view (slots, view_len, H, Dh) <- new (slots, T, H, Dh) at
        [pos_s, pos_s + T) per slot; inactive slots keep their view."""
        t = new.shape[1]
        g = jnp.arange(view.shape[1])  # (view,)
        c = jnp.clip(g[None, :] - self.positions[:, None], 0, t - 1)
        cand = jnp.take_along_axis(
            new, c[:, :, None, None], axis=1
        ).astype(view.dtype)  # (slots, view, H, Dh)
        inside = (
            (g[None, :] >= self.positions[:, None])
            & (g[None, :] < self.positions[:, None] + t)
            & self.active[:, None]
        )
        return jnp.where(inside[:, :, None, None], cand, view)

    def _scatter_span(self, pool_layer, view, t: int):
        """Write back each slot's touched pages — the span [pos, pos+t)
        overlaps at most (t-1)//page + 2 slot-local pages (the
        `PagedChunkAttention._scatter_touched` count, batched). A
        trailing index past the real span rewrites a just-gathered page
        with its own bytes; OOB / unallocated / inactive drop. Distinct
        live slots write distinct pool pages (the host's copy-on-write
        keeps write pages private), so the flattened scatter has no
        duplicate indices."""
        num_pages = pool_layer.shape[0]
        pages = _as_pages(view, pool_layer)
        n_touch = (t - 1) // self.page + 2
        idx = (
            self.positions[:, None] // self.page
            + jnp.arange(n_touch)[None, :]
        )  # (slots, n_touch) slot-local page indices
        safe = jnp.clip(idx, 0, pages.shape[1] - 1)
        touched = jnp.take_along_axis(
            pages, jnp.expand_dims(safe, tuple(range(2, pages.ndim))),
            axis=1,
        )  # (slots, n_touch, page, H, Dh)
        dst = jnp.take_along_axis(self.bt, safe, axis=1)
        ok = (idx < pages.shape[1]) & (dst >= 0) \
            & self.active[:, None]
        dst = jnp.where(ok, dst, num_pages)  # OOB -> drop
        return pool_layer.at[dst.reshape(-1)].set(
            touched.reshape(-1, *pool_layer.shape[1:]), mode="drop",
        )

    def __call__(self, q, k_new, v_new, mask):
        i = self.layer
        self.layer += 1
        t, heads = k_new.shape[1], k_new.shape[2]
        kview = self._write_span(
            _gather_pages(self.k[i], self.bt, heads), k_new
        )
        vview = self._write_span(
            _gather_pages(self.v[i], self.bt, heads), v_new
        )
        self.k = self.k.at[i].set(self._scatter_span(self.k[i], kview, t))
        self.v = self.v.at[i].set(self._scatter_span(self.v[i], vview, t))
        # Causal across the prefix boundary, per slot: query token j of
        # slot s sits at global position pos_s + j and sees every cached
        # position <= pos_s + j — row 0 conditions on exactly the real
        # prefix, row j on the prefix plus the first j span tokens, so
        # accepted rows reproduce plain decode's logits position for
        # position.
        qpos = self.positions[:, None] + jnp.arange(t)[None, :]
        valid = (
            jnp.arange(kview.shape[1])[None, None, :]
            <= qpos[:, :, None]
        )  # (slots, Tq, view)
        return grouped_query_attention(
            q, kview, vview, mask=valid[:, None]
        )


# ----------------------------------------------- latent attention fns
#
# The recorders of a family whose layers keep LATENT pages
# (`models/lm_family.LayerCache.latent_dim`): one pool a layer,
# {layer: (num_pages, page, width)}, a token's row `[c, rotated rope
# key]` and zeros up to whole lane tiles (`serving/kv_cache.py`). A
# latent mixer calls `attention_fn(q_nope, q_rope, c, k_rope,
# w_kvb, mask, dims)` with its rotary parts UNROTATED: the recorder
# rotates them in float32 at the positions it holds, writes the new
# rows into the pool FIRST and attends over the pool, so a key is
# rotated once, rounded once, and read the same by the step that wrote
# it and by every later one. Nothing per head is ever cached. The calls
# come in layer order; after the blocks run, `.pools` is the updated
# tree.


class _LatentRecorder:
    def __init__(self, pools: dict, page_size: int):
        self.pools = dict(pools)
        self._layers = iter(sorted(pools, key=int))
        self.page = page_size

    def _rows(self, c, k_rope, pos, dims, pool):
        """(B, T, rank), (B, T, rope) unrotated, pos (B, T) -> the
        rows to cache (B, T, the pool's width) in the pool's dtype:
        `[c, rotated k_rope, zeros]`."""
        b, t, _ = c.shape
        return jnp.concatenate([
            c, rope(k_rope, pos, dims.theta).astype(c.dtype),
            jnp.zeros((b, t, pool.shape[-1] - dims.row), c.dtype),
        ], -1).astype(pool.dtype)


class PagedLatentDecode(_LatentRecorder):
    """attention function for one traced PAGED decode step of a latent
    family: every active slot's new row lands at its own position (one
    row scattered a slot; an inactive slot's drops out of bounds), then
    each slot's query attends ABSORBED over the rows its block table
    reaches, up to its position (`ops/latent_attention.
    paged_decode_attention`: on a TPU a kernel that walks each slot's
    pages up to its own length)."""

    def __init__(self, pools, block_table, positions, active,
                 page_size: int):
        super().__init__(pools, page_size)
        self.bt = block_table  # (slots, pages_per_slot) int32
        self.positions = positions  # (slots,) write/attend position
        self.active = active  # (slots,) bool

    def __call__(self, q_nope, q_rope, c, k_rope, w_kvb, mask, dims):
        layer = next(self._layers)
        pool = self.pools[layer]
        num_pages = pool.shape[0]
        pos = self.positions[:, None]
        rows = self._rows(c, k_rope, pos, dims, pool)[:, 0]
        dst = jnp.take_along_axis(
            self.bt, self.positions[:, None] // self.page, axis=1
        )[:, 0]
        dst = jnp.where(self.active & (dst >= 0), dst, num_pages)
        pool = pool.at[dst, self.positions % self.page].set(
            rows, mode="drop"
        )
        self.pools[layer] = pool
        return paged_decode_attention(
            q_nope, rope(q_rope, pos, dims.theta).astype(q_nope.dtype),
            pool, self.bt, self.positions, self.active, w_kvb, dims,
        )


class PagedLatentChunk(_LatentRecorder):
    """attention function for ONE chunked-prefill step of ONE slot of a
    latent family: the chunk's first `n_valid` rows land in the slot's
    pages at [start, start + n_valid) (the padded tail is not written:
    the pages it would touch are rewritten with what they held), then
    the chunk's queries attend causally over the slot's rows stretch by
    stretch, as many stretches as reach start + chunk
    (`ops/latent_attention.latent_attention_blocks`: the work follows
    the slot's live length). A stretch is a whole number of pages, at
    most `KEY_BLOCK` rows, and divides the slot's window."""

    KEY_BLOCK = 1024

    def __init__(self, pools, bt_row, start, n_valid, page_size: int):
        super().__init__(pools, page_size)
        self.bt = bt_row  # (pages_per_slot,) int32
        self.start = start  # int32 global position of chunk token 0
        self.n_valid = n_valid  # int32 real tokens of the chunk
        per_slot = bt_row.shape[0]
        self.per = max(
            n for n in range(1, per_slot + 1)
            if per_slot % n == 0
            and n * page_size <= max(self.KEY_BLOCK, page_size)
        )

    def _write(self, pool, rows):
        """pool <- rows (chunk, row) at [start, start + n_valid): the
        (chunk - 1) // page + 2 pages the chunk can touch are gathered,
        merged and scattered back whole (unallocated entries and pages
        past the table drop)."""
        chunk, page = rows.shape[0], self.page
        num_pages, per_slot = pool.shape[0], self.bt.shape[0]
        idx = self.start // page + jnp.arange((chunk - 1) // page + 2)
        dst = jnp.take(self.bt, jnp.clip(idx, 0, per_slot - 1), axis=0)
        held = jnp.take(pool, dst, axis=0, mode="clip")
        t = idx[:, None] * page + jnp.arange(page)[None, :] - self.start
        new = jnp.take(rows, jnp.clip(t, 0, chunk - 1), axis=0)
        inside = (t >= 0) & (t < self.n_valid)
        dst = jnp.where((idx < per_slot) & (dst >= 0), dst, num_pages)
        return pool.at[dst].set(
            jnp.where(inside[..., None], new, held), mode="drop"
        )

    def __call__(self, q_nope, q_rope, c, k_rope, w_kvb, mask, dims):
        layer = next(self._layers)
        pool = self.pools[layer]
        chunk = c.shape[1]
        pos = (self.start + jnp.arange(chunk))[None]
        pool = self._write(
            pool, self._rows(c, k_rope, pos, dims, pool)[0]
        )
        self.pools[layer] = pool
        per, block = self.per, self.per * self.page
        # The slot's window gathered ONCE, outside the loop (one slot's
        # rows are a few MB): a loop that reads the pool itself makes
        # the compiler copy the whole pool into and out of it.
        view = jnp.take(pool, self.bt, axis=0, mode="clip").reshape(
            1, -1, pool.shape[-1]
        )

        def fetch(j):
            return lax.dynamic_slice_in_dim(view, j * block, block, axis=1)

        n_blocks = jnp.minimum(
            (self.start + chunk + block - 1) // block,
            self.bt.shape[0] // per,
        )
        return latent_attention_blocks(
            q_nope, rope(q_rope, pos, dims.theta).astype(q_nope.dtype),
            fetch, n_blocks, block, pool.shape[-1], pos[0], w_kvb, dims,
        )


# ------------------------------------------------------ state functions
#
# The state-pool twins of the attention recorders: a layer that keeps
# arrays of constant size per sequence (`models/lm_family.LayerCache.
# state`) calls `state_fn(advance)` once per step, `advance(state) ->
# (output, new state)` on arrays with a leading row axis. Construct one
# fresh per trace with the incoming `cache["state"]` tree; the calls
# come in layer order, so each consumes the next state-holding layer.
# After the blocks run, `.state` is the updated tree.


class _StateRecorder:
    def __init__(self, state: dict):
        self.state = dict(state)
        self._layers = iter(sorted(state, key=int))

    def __call__(self, advance):
        layer = next(self._layers)
        out, new = advance(self.read(self.state[layer]))
        self.state[layer] = self.write(self.state[layer], new)
        return out


class SlotStateDecode(_StateRecorder):
    """state_fn for one traced decode step: every slot's state advances
    by its one token; a slot that is not `active` keeps the state it
    had (an empty slot's row is garbage nobody reads, a slot still
    ingesting its prompt must not be disturbed)."""

    def __init__(self, state: dict, active):
        super().__init__(state)
        self.active = active  # (slots,) bool

    def read(self, arrays):
        return arrays

    def write(self, arrays, new):
        keep = lambda n, o: jnp.where(
            jnp.expand_dims(self.active, tuple(range(1, o.ndim))),
            n.astype(o.dtype), o,
        )
        return {name: keep(new[name], old) for name, old in arrays.items()}


class SlotStateChunk(_StateRecorder):
    """state_fn for ONE chunked-prefill step of ONE slot: the chunk
    starts from the state the chunk before it left in the slot's row,
    or from zeros when it is the prompt's first (`start == 0`): that is
    the whole reset of a recycled slot, no program and no host copy of
    its own. What the layer hands back (it must not have advanced over
    the chunk's padded tail) replaces the row."""

    def __init__(self, state: dict, slot, start):
        super().__init__(state)
        self.slot = slot    # int32 row of the state pool
        self.start = start  # int32 global position of chunk token 0

    def read(self, arrays):
        row = lambda a: lax.dynamic_slice_in_dim(a, self.slot, 1, axis=0)
        return {
            name: jnp.where(self.start == 0, jnp.zeros_like(row(a)), row(a))
            for name, a in arrays.items()
        }

    def write(self, arrays, new):
        return {
            name: lax.dynamic_update_slice_in_dim(
                old, new[name].astype(old.dtype), self.slot, axis=0
            )
            for name, old in arrays.items()
        }


# ---------------------------------------- decode-time collective matmul


@dataclasses.dataclass(frozen=True)
class DecodeCollectiveMatmul:
    """Latency-hiding policy for TP DECODE steps (`Context.matmul` ->
    `layers.project`, the same hook the training engines thread).

    At decode the token axis is 1, so the training policy's
    sequence-chunked rings have nothing to ring over; the slot-batch
    axis is the long one instead. Column projections (qkv / ffn-in)
    enter slot-sharded and gather the batch through the `ag_matmul`
    ring (S-1 ppermutes, each hop overlapping the chunk dot); row
    projections (attn-out / ffn-out) reduce-scatter partial sums back
    onto the slot shards via `matmul_rs`. Between the pairs,
    activations sit exactly where the declarative TP layout puts them
    (head/feature-sharded), so the cache attention is untouched; the
    residual stream between blocks rides slot-sharded over `axis` —
    the decode analog of the Megatron-SP layout.

    `compute_dtype` ("bf16" | "int8" | None) injects a quantized
    per-chunk GEMM into the fold bodies (`ops/quant_matmul.quant_dot`):
    the ring permute chain stays byte-identical — same hops, same
    payload dtype, `serve-decode-ring` still pins 4·L·(S-1) — and only
    the chunk dot arithmetic changes (`decode-quantized-matmul` pins
    the chunk-dot dtypes from the jaxpr)."""

    mesh: Mesh
    axis: str = "model"
    attn: bool = True
    ffn: bool = True
    compute_dtype: Optional[str] = None

    def _check(self, rows: int, features: int, fdim: str) -> None:
        size = self.mesh.shape[self.axis]
        if rows % size:
            raise ValueError(
                f"decode collective_matmul rings over the slot-token "
                f"batch: {rows} rows not divisible by the {size}-way "
                f"'{self.axis}' axis"
            )
        if features % size:
            raise ValueError(
                f"decode collective_matmul: {fdim} ({features}) not "
                f"divisible by the {size}-way '{self.axis}' axis"
            )

    def column(self, h, w, b):
        """(slots, T, D) -> (slots, T, F) F-sharded; the flattened
        slots*T row batch gathered via the ag_matmul ring. T is 1 for a
        decode step and k+1 for a speculative verify step — the SAME
        ring either way (hop count depends only on the axis size), which
        is the hlolint `spec-verify-step` contract: k extra tokens ride
        the one chain, they never cost k chains. num_slots % S == 0
        (the engine guard) keeps the flattened row count divisible for
        every T."""
        rows = h.shape[0] * h.shape[1]
        self._check(rows, w.shape[-1], "output features")
        fn = shard_map(
            partial(
                _decode_column, axis_name=self.axis,
                mode=self.compute_dtype,
            ),
            mesh=self.mesh,
            in_specs=(P(self.axis, None), P(None, self.axis),
                      P(self.axis)),
            out_specs=P(None, self.axis),
            check_vma=False,
        )
        # The named scope is the hlolint anchor: `serve-decode-ring` /
        # `spec-verify-step` count exactly these permutes (GSPMD's own
        # resharding permutes around the regions stay untagged).
        with jax.named_scope("serve_ring"):
            y = fn(h.reshape(rows, h.shape[-1]), w, b)
        return y.reshape(h.shape[0], h.shape[1], -1)

    def row(self, h, w, b):
        """(slots, T, F) F-sharded -> (slots, T, D); partial sums
        reduce-scattered onto the flattened slot-token row shards via
        the matmul_rs ring (same T generalization as `column`)."""
        rows = h.shape[0] * h.shape[1]
        self._check(rows, w.shape[0], "input features")
        fn = shard_map(
            partial(
                _decode_row, axis_name=self.axis,
                mode=self.compute_dtype,
            ),
            mesh=self.mesh,
            in_specs=(P(None, self.axis), P(self.axis, None), P()),
            out_specs=P(self.axis, None),
            check_vma=False,
        )
        with jax.named_scope("serve_ring"):
            y = fn(h.reshape(rows, h.shape[-1]), w, b)
        return y.reshape(h.shape[0], h.shape[1], -1)


def _decode_column(hl, wl, bl, *, axis_name, mode=None):
    dot = quant_dot(mode)
    if dot is None:
        return ag_matmul(hl, wl, axis_name) + bl
    y = ag_matmul_quant(hl, wl, axis_name, dot)
    return y + bl.astype(y.dtype)


def _decode_row(hl, wl, b, *, axis_name, mode=None):
    dot = quant_dot(mode)
    if dot is None:
        return matmul_rs(hl, wl, axis_name) + b
    y = matmul_rs_quant(hl, wl, axis_name, dot)
    return y + b.astype(y.dtype)


def decode_ring_permutes(num_layers: int, size: int) -> int:
    """The exact collective-permute count of one opted-in decode step:
    4 projection rings per block (qkv, attn-out, ffn-in, ffn-out),
    S-1 hops each, no backward — the hlolint `serve-decode-ring` pin."""
    return 4 * num_layers * (size - 1)


__all__ = [
    "CacheAttention",
    "DecodeCollectiveMatmul",
    "PagedCacheAttention",
    "PagedChunkAttention",
    "PagedLatentChunk",
    "PagedLatentDecode",
    "PagedSeqShardedCacheAttention",
    "PagedVerifyAttention",
    "PrefillRecorder",
    "SeqShardedCacheAttention",
    "SlotStateChunk",
    "SlotStateDecode",
    "chunk_stem",
    "decode_ring_permutes",
    "decode_stem",
    "prefill_stem",
    "verify_stem",
    "write_position",
]
