"""Multi-head latent attention over ONE cached row a token.

A latent mixer (`models/glm_moe.py`) keeps, for every token, the
normalised compressed row `c` (`rank` values) and one rotary key `kr`
(`rope` values) that all heads share: `row = [c, kr]`. A head's key is
`[c W_UK_h, kr]`, its value `c W_UV_h`, `W_kvb = [W_UK_h, W_UV_h]_h` of
shape (rank, heads * (nope + dv)). Two programs give the same numbers:

- EXPANDED: keys and values are made from the rows (`rows W_kvb`, once
  a key), then plain softmax attention per head. A key costs
  `rank * heads * (nope + dv)` products to expand and every query
  `heads * (nope + rope + dv)` a key.
- ABSORBED: the query is folded through the key half of `W_kvb`
  (`q_abs_h = q_nope_h W_UK_h^T`, `rank` values), scores are `[q_abs_h,
  q_rope_h] . row`, the probabilities weigh the ROWS, and the result is
  unfolded through the value half. Nothing per head is made of a key;
  every query costs `heads * (2 rank + rope)` a key.

`latent_kind` picks per call from the shapes alone (one query a
sequence, the decode step, is absorbed; a chunk of a few hundred
queries over one sequence's keys is expanded), as
`ops/ssm_scan.scan_kind` picks its program: no flag, environment
variable or configuration key does.

The decode step's absorbed attention over PAGES is
`paged_decode_attention`: one folded query a slot over the rows its
block table reaches. `decode_kind` picks its program the same way: on a
TPU, for a pool whose rows are whole lane tiles, JAX's Pallas paged
attention (`jax.experimental.pallas.ops.tpu.paged_attention`, the
instruction `paged_attention` in a trace): all heads of a slot are
query heads over ONE cached head whose keys AND values are the rows, so
the kernel walks each slot's pages up to its own length and reads
nothing else; anywhere else (every other backend, never the Pallas
interpreter) the slots' whole windows are gathered and attended in XLA.

Products run in the inputs' dtype with float32 accumulation; the
rotation, the scores' scaling, the softmax and its sums are float32.
`rope` pairs value i with value i + rope/2 (rotate-half).
"""

from __future__ import annotations

import dataclasses

import jax
import jax.numpy as jnp

F32 = jnp.float32
NEG = jnp.finfo(jnp.float32).min


@dataclasses.dataclass(frozen=True)
class LatentDims:
    """Widths of one latent mixer: `rank` compressed values and `rope`
    rotary ones a cached row, `heads` of `nope` + `rope` key values and
    `dv` value values, the rotation's base, the scores' scale."""

    heads: int
    rank: int
    nope: int
    rope: int
    dv: int
    theta: float
    scale: float

    @property
    def row(self) -> int:
        return self.rank + self.rope


def rope(x, positions, theta: float):
    """Rotate x (B, T, R) or (B, T, H, R) at integer positions (B, T):
    float32 in, float32 out (the caller rounds). Pairs (i, i + R/2),
    frequency i of `theta ** (-i / (R/2))`."""
    half = x.shape[-1] // 2
    inv = theta ** (-jnp.arange(half, dtype=F32) / half)
    ang = positions.astype(F32)[..., None] * inv
    if x.ndim == 4:
        ang = ang[:, :, None, :]
    cos, sin = jnp.cos(ang), jnp.sin(ang)
    xf = x.astype(F32)
    a, b = xf[..., :half], xf[..., half:]
    return jnp.concatenate([a * cos - b * sin, b * cos + a * sin], -1)


def latent_kind(queries: int, dims: LatentDims) -> str:
    """"absorbed" | "expanded": which form costs fewer products a key
    for `queries` query positions of one sequence (module doc)."""
    d = dims
    absorbed = queries * d.heads * (2 * d.rank + d.rope)
    expanded = d.heads * (d.nope + d.dv) * (
        d.rank + queries
    ) + queries * d.heads * d.rope
    return "absorbed" if absorbed <= expanded else "expanded"


def _halves(w_kvb, dims: LatentDims):
    w = w_kvb.reshape(dims.rank, dims.heads, dims.nope + dims.dv)
    return w[..., :dims.nope], w[..., dims.nope:]


def _dot(spec, a, b):
    return jnp.einsum(spec, a, b, preferred_element_type=F32)


def fold_queries(q_nope, q_rope, w_kvb, dims: LatentDims, width: int):
    """(B, Tq, H, nope), (B, Tq, H, rope) rotated -> (B, Tq, H,
    `width`): what scores a cached row of `width` >= rank + rope
    stored values directly (zeros against the row's padding)."""
    w_uk, _ = _halves(w_kvb, dims)
    b, t, h, _ = q_nope.shape
    q_abs = _dot("bqhn,rhn->bqhr", q_nope, w_uk).astype(q_nope.dtype)
    return jnp.concatenate([
        q_abs, q_rope.astype(q_nope.dtype),
        jnp.zeros((b, t, h, width - dims.row), q_nope.dtype),
    ], -1)


def unfold_values(o_rows, w_kvb, dims: LatentDims):
    """(B, Tq, H, >= rank) weighted rows -> (B, Tq, H, dv)."""
    _, w_uv = _halves(w_kvb, dims)
    return _dot(
        "bqhr,rhv->bqhv", o_rows[..., :dims.rank], w_uv
    ).astype(o_rows.dtype)


def expand_rows(rows, w_kvb, dims: LatentDims):
    """rows (B, L, >= rank + rope) -> keys (B, L, H, nope + rope),
    values (B, L, H, dv): every head's own key and value of every
    row."""
    b, n, _ = rows.shape
    kv = _dot("blr,rf->blf", rows[..., :dims.rank], w_kvb).astype(
        rows.dtype
    ).reshape(b, n, dims.heads, dims.nope + dims.dv)
    kr = jnp.broadcast_to(
        rows[:, :, None, dims.rank:dims.row], (b, n, dims.heads, dims.rope)
    )
    return (jnp.concatenate([kv[..., :dims.nope], kr], -1),
            kv[..., dims.nope:])


def block_scores(kind: str, q, rows, w_kvb, dims: LatentDims):
    """Scores of one stretch of cached rows and what the probabilities
    will weigh: `q` is `fold_queries`' result for "absorbed" and the
    (B, Tq, H, nope + rope) queries for "expanded"; rows (B, L, >= row:
    a stored row may end in zeros up to whole lane tiles).
    -> (scores (B, H, Tq, L) float32, scaled; values (B, L, H|1, width))."""
    if kind == "absorbed":
        s = _dot("bqhr,blr->bhql", q, rows)
        return s * dims.scale, rows[:, :, None, :]
    k, v = expand_rows(rows, w_kvb, dims)
    return _dot("bqhd,blhd->bhql", q, k) * dims.scale, v


def weigh(p, values):
    """p (B, H, Tq, L) float32 over values (B, L, H|1, width) ->
    (B, Tq, H, width) float32."""
    p = p.astype(values.dtype)
    if values.shape[2] == 1:
        return _dot("bhql,blr->bqhr", p, values[:, :, 0])
    return _dot("bhql,blhd->bqhd", p, values)


def _queries(kind: str, q_nope, q_rope, w_kvb, dims, width: int):
    """What scores a stretch of rows in `kind`'s form."""
    if kind == "absorbed":
        return fold_queries(q_nope, q_rope, w_kvb, dims, width)
    return jnp.concatenate([q_nope, q_rope.astype(q_nope.dtype)], -1)


def _result(kind: str, o, w_kvb, dims):
    """The weighted values (B, Tq, H, width) -> (B, Tq, H, dv)."""
    return unfold_values(o, w_kvb, dims) if kind == "absorbed" else o


def latent_attention(q_nope, q_rope, rows, w_kvb, valid,
                     dims: LatentDims, kind: str = None):
    """Softmax attention of rotated queries over cached rows in one
    piece: q_nope (B, Tq, H, nope), q_rope (B, Tq, H, rope) ROTATED,
    rows (B, L, >= rank + rope) with their keys rotated, valid (B, Tq,
    L) -> (B, Tq, H, dv). `kind` None lets `latent_kind` pick."""
    kind = kind or latent_kind(q_nope.shape[1], dims)
    q = _queries(kind, q_nope, q_rope, w_kvb, dims, rows.shape[-1])
    s, values = block_scores(kind, q, rows, w_kvb, dims)
    s = jnp.where(valid[:, None], s, NEG)
    o = weigh(jax.nn.softmax(s, axis=-1), values).astype(q_nope.dtype)
    return _result(kind, o, w_kvb, dims)


LANES, SUBLANES = 128, 8
# Pages of one slot the kernel fetches and scores at a time.
KERNEL_PAGES = 8


def _on_tpu() -> bool:
    # The selector's own predicate (a test answers for it).
    return jax.default_backend() == "tpu"


def decode_kind(width: int, page: int, pages_per_slot: int) -> str:
    """Which program `paged_decode_attention` runs for these static
    facts: `"kernel"` on a TPU for rows of whole lane tiles, pages of
    whole sublanes and a block table the kernel's stretch divides;
    `"gather"` anywhere else (module doc)."""
    if (
        _on_tpu()
        and width % LANES == 0
        and page % SUBLANES == 0
        and pages_per_slot % min(KERNEL_PAGES, pages_per_slot) == 0
    ):
        return "kernel"
    return "gather"


def paged_decode_attention(q_nope, q_rope, pool, block_table, positions,
                           active, w_kvb, dims: LatentDims):
    """One decode step's attention of a latent layer, ABSORBED: q_nope
    (slots, 1, H, nope), q_rope (slots, 1, H, rope) ROTATED, pool
    (num_pages, page, width) with the step's rows already written,
    block_table (slots, pages_per_slot) of page ids (-1: none),
    positions (slots,) each slot's newest row, active (slots,) -> (slots,
    1, H, dv). A slot attends over its rows 0..position; an inactive
    slot's result is unspecified and finite."""
    slots, per_slot = block_table.shape
    num_pages, page, width = pool.shape
    # (an unallocated entry reads a page the slot's length hides)
    pages = jnp.clip(block_table, 0, num_pages - 1)
    if decode_kind(width, page, per_slot) == "kernel":
        from jax.experimental.pallas.ops.tpu.paged_attention import (
            paged_attention,
        )

        q = fold_queries(q_nope, q_rope, w_kvb, dims, width)[:, 0]
        q = (q.astype(F32) * dims.scale).astype(q.dtype)
        rows = pool[None]  # ONE cached head: keys and values alike
        with jax.named_scope("latent_decode"):
            o = paged_attention(
                q, rows, rows, jnp.where(active, positions + 1, 1), pages,
                pages_per_compute_block=min(KERNEL_PAGES, per_slot),
            )
        return unfold_values(o[:, None].astype(q.dtype), w_kvb, dims)
    # ("clip", not the default "fill": one pass less over the view)
    view = jnp.take(pool, pages, axis=0, mode="clip").reshape(
        slots, -1, width
    )
    valid = (
        jnp.arange(view.shape[1])[None, None, :]
        <= positions[:, None, None]
    )
    return latent_attention(
        q_nope, q_rope, view, w_kvb, valid, dims, kind="absorbed"
    )


def latent_attention_blocks(q_nope, q_rope, fetch, n_blocks, block: int,
                            width: int, q_pos, w_kvb, dims: LatentDims):
    """The same attention for ONE sequence's queries over as many
    stretches of `block` cached rows as hold a key some query may see:
    `fetch(j)` -> rows (1, block, `width`) at key positions `j * block ..`,
    `n_blocks` (traced) how many stretches to visit, q_pos (Tq,) each
    query's position: it sees keys at or before it. Online softmax
    between stretches (float32 running maximum, sum and weighted
    values), so that no score matrix over all keys ever exists and the
    work follows the sequence's live length, not the cache's."""
    tq, h = q_nope.shape[1], dims.heads
    kind = latent_kind(tq, dims)
    q = _queries(kind, q_nope, q_rope, w_kvb, dims, width)
    if kind == "expanded":
        width = dims.dv  # what the probabilities weigh

    def body(j, carry):
        m, l, acc = carry
        s, values = block_scores(kind, q, fetch(j), w_kvb, dims)
        k_pos = j * block + jnp.arange(block)
        seen = (k_pos[None, :] <= q_pos[:, None])[None, None]
        s = jnp.where(seen, s, NEG)
        m_new = jnp.maximum(m, jnp.max(s, axis=-1))
        alpha = jnp.exp(m - m_new)
        p = jnp.where(seen, jnp.exp(s - m_new[..., None]), 0.0)
        l = l * alpha + jnp.sum(p, axis=-1)
        scale_acc = jnp.swapaxes(alpha, 1, 2)[..., None]
        return m_new, l, acc * scale_acc + weigh(p, values)

    m, l, acc = jax.lax.fori_loop(0, n_blocks, body, (
        jnp.full((1, h, tq), NEG, F32), jnp.zeros((1, h, tq), F32),
        jnp.zeros((1, tq, h, width), F32),
    ))
    o = (acc / jnp.swapaxes(l, 1, 2)[..., None]).astype(q_nope.dtype)
    return _result(kind, o, w_kvb, dims)


def latent_causal_attention(q_nope, q_rope, c, k_rope, w_kvb, mask,
                            dims: LatentDims):
    """What a latent mixer calls when no cache is in play: whole
    sequences from position 0. q_rope (B, T, H, rope) and k_rope (B, T,
    rope) come UNROTATED, c (B, T, rank) normalised; rotates both at
    0..T-1 and attends causally. `mask` is not read: a padded position
    lies behind every real one."""
    b, t = c.shape[:2]
    dt = c.dtype
    pos = jnp.broadcast_to(jnp.arange(t), (b, t))
    rows = jnp.concatenate(
        [c, rope(k_rope, pos, dims.theta).astype(dt)], -1
    )
    causal = jnp.arange(t)[:, None] >= jnp.arange(t)[None, :]
    return latent_attention(
        q_nope, rope(q_rope, pos, dims.theta).astype(dt), rows, w_kvb,
        jnp.broadcast_to(causal, (b, t, t)), dims,
    )
