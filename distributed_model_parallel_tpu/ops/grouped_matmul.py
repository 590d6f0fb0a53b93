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
which the kernel leaves unwritten.

`sort_rows` / `unsort_rows` are the two row movements around it as
gathers in both directions: the transpose of a gather is a scatter-add,
which the TPU serialises, but the transpose of a gather along a
permutation is the gather along its inverse.
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


def grouped_matmul(rows, weights, group_sizes):
    """rows (M, K) sorted by group, weights (G, K, N), group_sizes (G,)
    int32 -> (M, N) in rows' dtype: row r of group g times weights[g];
    rows past `sum(group_sizes)` come out zero."""
    m, k = rows.shape
    n = weights.shape[-1]
    tiling = (_tile(m, ROW_TILE, 8), _tile(k, COL_TILE, LANES),
              _tile(n, COL_TILE, LANES))
    if m % tiling[0]:
        raise ValueError(f"{m} rows do not tile by {tiling[0]}")
    # The kernel writes no row that no group reaches, forward or in the
    # product that gives the rows' gradient: both selects zero what it
    # left (the first one's transpose masks the gradient).
    live = (jnp.arange(m) < jnp.sum(group_sizes))[:, None]
    out = megablox.gmm(
        jnp.where(live, rows, 0), weights.astype(rows.dtype),
        group_sizes.astype(jnp.int32), rows.dtype, tiling, None, None,
        False, jax.default_backend() != "tpu",
    )
    return jnp.where(live, out, 0)


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
def unsort_rows(y, order, inverse):
    """The rows of `y` (sorted) back in the order they had before
    `order` sorted them."""
    return jnp.take(y, inverse, axis=0)


def _unsort_rows_fwd(y, order, inverse):
    return unsort_rows(y, order, inverse), order


def _unsort_rows_bwd(order, g):
    return jnp.take(g, order, axis=0), None, None


unsort_rows.defvjp(_unsort_rows_fwd, _unsort_rows_bwd)
