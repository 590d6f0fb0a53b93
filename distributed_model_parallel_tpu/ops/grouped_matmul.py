"""Rows sorted by group times one weight matrix per group, at the cost
of the rows that are live.

The sparse expert layer (`models/moe.py::held_experts_feed_forward`)
sorts its picks by expert, so the rows of expert e are contiguous and
`group_sizes[e]` long; the rows past `sum(group_sizes)` belong to no
expert this chip holds. The buffer is sized for the worst case (every
pick on a held expert) because a smaller one would have to drop picks;
the product must not pay for that size. JAX's Pallas grouped product
(`jax.experimental.pallas.ops.tpu.megablox`) visits only the row tiles
a group reaches (its grid's middle extent is computed from
`group_sizes` at run time), forward and in both backward products.

`grouped_matmul` picks tiles for it, runs it in interpret mode off the
TPU (as the flash kernels do), and zeroes the rows no group reached,
which the kernel leaves unwritten, unless its caller reads none of them.

`sort_rows` and `combine_rows` are the row movements around it, as
gathers in both directions: the transpose of a gather is a scatter-add,
which the TPU serialises, but the transpose of a gather along a
permutation is the gather along its inverse. `combine_rows` brings the
sorted rows back one pick slot at a time and weighs them as it goes, so
no buffer of every pick's row in token order is ever built.
"""

from __future__ import annotations

from functools import partial

import jax
import jax.numpy as jnp
from jax.experimental.pallas.ops.tpu.megablox import ops as megablox

# Row tile: a held expert sees ~512 rows a step in the benchmark's cell
# (2.5 tiles of 256, 80 % of the rows computed are live; 512 would
# halve that). Column tiles: large multiples of the lane width.
ROW_TILE = 256
COL_TILE = 1024
LANES = 128


def _tile(dim: int, want: int, unit: int) -> int:
    """Largest multiple of `unit` that divides `dim` and is <= `want`;
    `dim` itself where none exists (a block as wide as the array is
    always legal)."""
    for t in range(min(want, dim) // unit * unit, 0, -unit):
        if dim % t == 0:
            return t
    return dim


def grouped_matmul(rows, weights, group_sizes, zero_tail=True):
    """rows (M, K) sorted by group, weights (G, K, N), group_sizes (G,)
    int32 -> (M, N) in rows' dtype: row r of group g times weights[g];
    rows past `sum(group_sizes)` come out zero, or, with `zero_tail`
    False, as the kernel left them, for a caller that reads none of them
    and hands back no gradient for them."""
    m, k = rows.shape
    n = weights.shape[-1]
    tiling = (_tile(m, ROW_TILE, 8), _tile(k, COL_TILE, LANES),
              _tile(n, COL_TILE, LANES))
    if m % tiling[0]:
        raise ValueError(f"{m} rows do not tile by {tiling[0]}")
    # The kernel writes no row that no group reaches, forward or in the
    # product that gives the rows' gradient: both selects zero what it
    # left (the first one's transpose masks the gradient). The second is
    # a pass over every row, which a caller that selects the tail away
    # itself need not pay.
    live = (jnp.arange(m) < jnp.sum(group_sizes))[:, None]
    out = megablox.gmm(
        jnp.where(live, rows, 0), weights.astype(rows.dtype),
        group_sizes.astype(jnp.int32), rows.dtype, tiling, None, None,
        False, jax.default_backend() != "tpu",
    )
    return jnp.where(live, out, 0) if zero_tail else out


@partial(jax.custom_vjp, nondiff_argnums=(3,))
def sort_rows(x, order, inverse, k: int):
    """x (N, D), each row wanted k times -> (N * k, D) with row i the
    copy `order[i]` of `x` repeated k times (copy j belongs to token
    j // k). `inverse` is the inverse permutation of `order`."""
    return jnp.take(x, order // k, axis=0)


def _sort_rows_fwd(x, order, inverse, k):
    return sort_rows(x, order, inverse, k), (inverse, x.shape[0])


def _sort_rows_bwd(k, res, g):
    inverse, n = res
    back = jnp.take(g, inverse, axis=0).reshape(n, k, g.shape[-1])
    return back.astype(jnp.float32).sum(axis=1).astype(g.dtype), None, None


sort_rows.defvjp(_sort_rows_fwd, _sort_rows_bwd)


@jax.custom_vjp
def combine_rows(rows, order, inverse, weights, held):
    """rows (N * k, D) sorted by `order` (see `sort_rows`), weights and
    held (N, k) -> (N, D) in rows' dtype: token n's sum over its picks j
    of weights[n, j] times the pick's row, rows[inverse[n * k + j]], in
    float32 and rounded once. A pick that `held` does not mark is
    selected away, not weighted by zero: rows past the grouped product's
    live end may be unwritten, and 0 x NaN is NaN. The rows come back
    one pick slot at a time, N rows a gather, each summed into the
    accumulator: no (N * k, D) buffer in token order is built."""
    n, k = weights.shape
    slots = inverse.reshape(n, k)
    out = jnp.zeros((n, rows.shape[1]), jnp.float32)
    for j in range(k):
        row = jnp.take(rows, slots[:, j], axis=0).astype(jnp.float32)
        out = out + jnp.where(
            held[:, j, None], row * weights[:, j, None].astype(jnp.float32),
            0.0,
        )
    return out.astype(rows.dtype)


def _combine_rows_fwd(rows, order, inverse, weights, held):
    out = combine_rows(rows, order, inverse, weights, held)
    return out, (rows, order, inverse, weights, held)


def _combine_rows_bwd(res, g):
    rows, order, inverse, weights, held = res
    n, k = weights.shape
    # Both gradients come from each sorted row's token's cotangent, a
    # gather along `order`: the row's is it weighted, zero for a row no
    # held pick owns; the weight's is its product with the row, taken
    # in the sorted order and brought back along `inverse` (a gather of
    # one number a pick), the absent picks' selected away.
    back = jnp.take(g, order // k, axis=0).astype(jnp.float32)
    scale = jnp.take(
        jnp.where(held, weights.astype(jnp.float32), 0.0).reshape(-1),
        order,
    )
    d_rows = (back * scale[:, None]).astype(rows.dtype)
    dots = jnp.sum(back * rows.astype(jnp.float32), axis=-1)
    d_weights = jnp.where(
        held, jnp.take(dots, inverse).reshape(n, k), 0.0
    ).astype(weights.dtype)
    return d_rows, None, None, d_weights, None


combine_rows.defvjp(_combine_rows_fwd, _combine_rows_bwd)
