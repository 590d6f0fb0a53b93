"""The vocabulary head and its loss as one op.

A causal-LM step reads four sums off its logits (the `_metrics`
contract of `parallel/data_parallel.py`): the token loss, the count of
scored rows, and how many labels rank first and among the first five.
`head_loss` computes them from what the head's product takes, the
hidden rows (rows, dim) after whatever the head does first, the matrix
(dim, vocab) and the labels (rows,), and, under `jax.custom_vjp`, the
gradients to the rows and to the matrix. The `rows x vocab` plane is
walked in pieces: a piece of logits is made by the product the head
makes (float32 logits from operands rounded as the backend's default
precision rounds them: not at all on the CPU, to bfloat16 on a TPU),
used for the log-sum-exp, the label's logit, the rank count and its part
of both gradient products, and dropped. No float32 array of the whole
plane is laid out again, reduced in passes of its own or kept with a
gradient of its own size. Label -1 marks a row that is not scored: it
is left out of every sum and gets no gradient. The rank is
`label_rank`'s: a class outranks the label when its logit is larger, or
equal at a lower index.

One algorithm, two programs for it, picked per call by `head_loss_kind`
from what the call can observe (the backend and the rows):

`"blocks"`: a `lax.scan` over blocks of rows in plain `jax.numpy`, a
block holding the whole vocabulary, so its log-sum-exp is known inside
the block and the gradients are formed in the same sweep (three
products; the backward pass only scales them by the cotangent). Every
backend that is not a TPU runs this, and a TPU for a handful of rows.

`"kernel"`: two Mosaic kernels, both named `head_loss`. The forward one
walks (row tile, vocabulary tile) and keeps a running maximum and sum
and the rank count on the chip; under differentiation it also writes
each tile of float32 logits out, once, behind its product. The backward
one reads each tile back, once, forms the tile of the logits' gradient
in float32 and multiplies it into the rows' gradient, resident on the
chip for a stretch of rows, and into the matrix's, resident for a
vocabulary tile: three products, and the only pass over the plane in
HBM is that one write and that one read, both hidden behind the
products (making the logits a second time instead, a fourth product,
measured 25 % slower on the v5e: PERF.md section 6, PR 39).
"""

from __future__ import annotations

from functools import partial
from typing import Dict, Optional

import jax
import jax.numpy as jnp
from jax import lax
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu

from distributed_model_parallel_tpu.training.metrics import (
    label_rank,
    rank_correct,
    valid_count,
)

SUMS = ("loss_sum", "count", "correct1", "correct5")

# Logits one block of the "blocks" program holds at once (rows of the
# block x vocabulary): large enough that the matrix's gradient, read
# and written once a block, costs little beside the block's products.
BLOCK_ELEMENTS = 1 << 27


def head_loss(rows: jax.Array, matrix: jax.Array,
              labels: jax.Array) -> Dict[str, jax.Array]:
    """{"loss_sum", "count", "correct1", "correct5"}, float32 scalars,
    of `rows.astype(float32) @ matrix` against `labels`: what
    `_metrics(cross_entropy(logits, labels), logits, labels)` gives,
    without the logits. rows (..., dim) in any float dtype, matrix
    (dim, vocab), labels (...) int32 with -1 for a row that is not
    scored. Differentiable in `rows` and `matrix` through `loss_sum`."""
    dim = rows.shape[-1]
    flat, flat_labels = rows.reshape(-1, dim), labels.reshape(-1)
    kind = head_loss_kind(flat.shape[0])
    sums = (_kernel_sums if kind == "kernel" else _block_sums)(
        flat, matrix, flat_labels
    )
    return dict(zip(SUMS, sums))


def _on_tpu() -> bool:
    return jax.default_backend() == "tpu"


def head_loss_kind(rows: int) -> str:
    """Which program `head_loss` runs for a call of so many rows:
    `"kernel"` on a TPU where they are enough to fill a tile (the
    kernels take any width and vocabulary: their tiles follow the
    width, a ragged last vocabulary tile is masked); `"blocks"`
    anywhere else, never the Pallas interpreter."""
    if _on_tpu() and rows >= ROW_TILE:
        return "kernel"
    return "blocks"


def _padded(flat, labels, multiple):
    """Rows padded to a whole number of `multiple`, the padding
    labelled -1."""
    pad = -flat.shape[0] % multiple
    if pad:
        flat = jnp.pad(flat, ((0, pad), (0, 0)))
        labels = jnp.pad(labels, (0, pad), constant_values=-1)
    return flat, labels


def _row_sums(loss, rank, labels):
    """The four sums from each row's loss and rank."""
    return (
        jnp.sum(jnp.where(labels >= 0, loss, 0.0)), valid_count(labels),
        rank_correct(rank, labels, 1), rank_correct(rank, labels, 5),
    )


# ------------------------------------------------------------ "blocks"

def _block_rows(rows: int, vocab: int) -> int:
    """Rows of one block: BLOCK_ELEMENTS of logits at most, the rows
    dealt evenly over as few blocks as that takes, in whole eights."""
    fit = max(8, BLOCK_ELEMENTS // vocab // 8 * 8)
    blocks = -(-rows // fit)
    return -(-rows // (8 * blocks)) * 8


def _sweep_blocks(flat, matrix, labels, with_gradients: bool):
    """The four sums and, if asked, d loss_sum / d (rows, matrix),
    block of rows by block of rows."""
    f32 = jnp.float32
    rows, dim = flat.shape
    block = _block_rows(rows, matrix.shape[1])
    padded, padded_labels = _padded(flat, labels, block)
    n = padded.shape[0] // block

    def one(carry, xs):
        sums, d_matrix = carry
        h, lab = xs
        logits = h.astype(f32) @ matrix
        valid, safe = lab >= 0, jnp.maximum(lab, 0)[:, None]
        top = jnp.max(logits, axis=-1, keepdims=True)
        e = jnp.exp(logits - top)
        z = jnp.sum(e, axis=-1, keepdims=True)
        label_logit = jnp.take_along_axis(logits, safe, axis=-1)
        loss = (top + jnp.log(z) - label_logit)[:, 0]
        sums = tuple(a + b for a, b in zip(
            sums, _row_sums(loss, label_rank(logits, lab), lab)
        ))
        if not with_gradients:
            return (sums, d_matrix), None
        index = lax.broadcasted_iota(jnp.int32, logits.shape, 1)
        d_logits = jnp.where(
            valid[:, None], e / z - (index == safe).astype(f32), 0.0
        )
        d_matrix = d_matrix + h.astype(f32).T @ d_logits
        return (sums, d_matrix), (d_logits @ matrix.T).astype(h.dtype)

    zeros = (jnp.zeros((), f32),) * 4
    d_matrix = jnp.zeros(matrix.shape, f32) if with_gradients else None
    (sums, d_matrix), d_rows = lax.scan(
        one, (zeros, d_matrix),
        (padded.reshape(n, block, dim), padded_labels.reshape(n, block)),
    )
    if not with_gradients:
        return sums, None
    return sums, (
        d_rows.reshape(n * block, dim)[:rows],
        d_matrix.astype(matrix.dtype),
    )


@jax.custom_vjp
def _block_sums(flat, matrix, labels):
    return _sweep_blocks(flat, matrix, labels, False)[0]


def _block_sums_fwd(flat, matrix, labels):
    return _sweep_blocks(flat, matrix, labels, True)


def _block_sums_bwd(gradients, cotangents):
    # the loss is what the step differentiates: the gradients were
    # formed in the forward sweep for a cotangent of one
    scale = cotangents[0]
    return tuple(
        (g * scale).astype(g.dtype) for g in gradients
    ) + (None,)


_block_sums.defvjp(_block_sums_fwd, _block_sums_bwd)


# ------------------------------------------------------------ "kernel"

# Rows of the smallest tile: the fewest a call must have for the
# kernels to be worth their launch.
ROW_TILE = 512
# What the kernels may take of the chip's fast memory (a v5e core has
# 128 MiB), and how much of it the backward kernel gives the stretch of
# rows whose gradient stays resident.
VMEM_LIMIT = 100 << 20
STRETCH_BYTES = 24 << 20

_NT = (((1,), (1,)), ((), ()))  # a @ b.T


def _tiles(dim: int):
    """(rows, vocabulary entries) of one tile of logits for this width.
    A tile's operands, its float32 logits and the products' results sit
    in fast memory beside the resident gradients; on the v5e, forward
    and backward at 16,384 rows (PERF.md section 6, PR 39): 768 wide
    26.1 ms at (1024, 1024), 26.5 at (512, 2048), 27.8 at (512, 1024),
    30.0 at (512, 512); 2,304 wide 30.1 ms at (512, 1024), 31.4 at
    (512, 512), and 1,024 rows do not fit."""
    return (1024, 1024) if dim <= 1024 else (512, 1024)


def _forward_kernel(h_ref, wt_ref, lab_ref, t_ref, *rest, vocab: int,
                    keep: bool):
    """One (row tile, vocabulary tile) of the forward sweep, the
    vocabulary innermost: the tile's logits, into the rows' running
    maximum, sum of exponentials and count of entries that outrank the
    label (whose logit `t_ref` brings), and with `keep` out to HBM for
    the backward sweep."""
    f32 = jnp.float32
    if keep:
        lse_ref, rank_ref, s_ref, top_scr, sum_scr, rank_scr = rest
    else:
        lse_ref, rank_ref, top_scr, sum_scr, rank_scr = rest
    j, last = pl.program_id(1), pl.num_programs(1) - 1
    tile_v = wt_ref.shape[0]

    @pl.when(j == 0)
    def _():
        top_scr[...] = jnp.full_like(top_scr, -jnp.inf)
        sum_scr[...] = jnp.zeros_like(sum_scr)
        rank_scr[...] = jnp.zeros_like(rank_scr)

    def tile(ragged: bool):
        s = lax.dot_general(
            h_ref[...], wt_ref[...], _NT, preferred_element_type=f32
        )
        if keep:
            s_ref[...] = s
        col = j * tile_v + lax.broadcasted_iota(jnp.int32, s.shape, 1)
        if ragged:
            s = jnp.where(col < vocab, s, -jnp.inf)
        before = top_scr[...]
        top = jnp.maximum(before, jnp.max(s, axis=1, keepdims=True))
        sum_scr[...] = sum_scr[...] * jnp.exp(before - top) + jnp.sum(
            jnp.exp(s - top), axis=1, keepdims=True
        )
        top_scr[...] = top
        lab, t = lab_ref[...], t_ref[...]
        # label_rank's order; the label's own entry never counts,
        # however its logit here rounds against `t`
        ahead = ((s > t) & (col != lab)) | ((s == t) & (col < lab))
        rank_scr[...] += jnp.sum(
            jnp.where(ahead, 1.0, 0.0), axis=1, keepdims=True
        )

    if vocab % tile_v:
        pl.when(j < last)(partial(tile, False))
        pl.when(j == last)(partial(tile, True))
    else:
        tile(False)

    @pl.when(j == last)
    def _():
        lse_ref[...] = top_scr[...] + jnp.log(sum_scr[...])
        rank_ref[...] = rank_scr[...]


def _backward_kernel(s_ref, ht_ref, wt_ref, lab_ref, lse_ref, scale_ref,
                     *rest, accumulate: bool):
    """One (vocabulary tile, row tile) of the backward sweep over a
    stretch of rows, the rows innermost: the tile of logits the forward
    sweep kept, the tile of d loss_sum / d logits from it in float32,
    and its two products, into the stretch's rows' gradient (resident
    for the whole call) and the vocabulary tile's matrix gradient
    (resident while the rows go by; with `accumulate` it starts from
    what earlier stretches left). The vocabulary's padding holds zero
    columns of the matrix: they add nothing to the rows' gradient, and
    their own lies outside the matrix's."""
    f32 = jnp.float32
    dh_ref, dw_ref = rest[-2:]
    j, i = pl.program_id(0), pl.program_id(1)
    tile_r, tile_v = s_ref.shape
    wt = wt_ref[...]
    col = j * tile_v + lax.broadcasted_iota(jnp.int32, s_ref.shape, 1)
    p = jnp.exp(s_ref[...] - lse_ref[...])
    p = jnp.where(col == lab_ref[...], p - 1.0, p) * scale_ref[...]
    # rounded on its way into the products, as their other operands are
    p = p.astype(wt.dtype)
    d_rows = jnp.dot(p, wt, preferred_element_type=f32)
    d_matrix = jnp.dot(ht_ref[...], p, preferred_element_type=f32)
    rows = pl.ds(pl.multiple_of(i * tile_r, tile_r), tile_r)

    @pl.when(j == 0)
    def _():
        dh_ref[rows, :] = d_rows

    @pl.when(j > 0)
    def _():
        dh_ref[rows, :] += d_rows

    @pl.when(i == 0)
    def _():
        dw_ref[...] = d_matrix + rest[0][...] if accumulate else d_matrix

    @pl.when(i > 0)
    def _():
        dw_ref[...] += d_matrix


def _params(*semantics):
    return pltpu.CompilerParams(
        dimension_semantics=semantics, vmem_limit_bytes=VMEM_LIMIT
    )


# Jitted on their own, as `ops/ssm_scan._scan_call`: a program that
# holds several such calls traces and lowers each kernel once.
@partial(jax.jit, static_argnames=("vocab", "keep", "interpret"))
def _forward_call(h, wt, labels, label_logit, *, vocab, keep, interpret):
    """(log-sum-exp, rank[, logits]) of every row: h (rows, dim) and wt
    (padded vocabulary, dim) in the products' dtype, rows and vocabulary
    whole tiles; labels and label_logit (rows, 1)."""
    f32 = jnp.float32
    rows, dim = h.shape
    padded = wt.shape[0]
    tile_r, tile_v = _tiles(dim)
    column = pl.BlockSpec((tile_r, 1), lambda i, j: (i, 0))
    out_shape = [jax.ShapeDtypeStruct((rows, 1), f32)] * 2
    out_specs = [column, column]
    if keep:
        out_shape.append(jax.ShapeDtypeStruct((rows, padded), f32))
        out_specs.append(pl.BlockSpec((tile_r, tile_v), lambda i, j: (i, j)))
    return pl.pallas_call(
        partial(_forward_kernel, vocab=vocab, keep=keep),
        out_shape=out_shape,
        grid=(rows // tile_r, padded // tile_v),
        in_specs=[
            pl.BlockSpec((tile_r, dim), lambda i, j: (i, 0)),
            pl.BlockSpec((tile_v, dim), lambda i, j: (j, 0)),
            column, column,
        ],
        out_specs=out_specs,
        scratch_shapes=[pltpu.VMEM((tile_r, 1), f32)] * 3,
        compiler_params=_params("parallel", "arbitrary"),
        cost_estimate=pl.CostEstimate(
            flops=2 * rows * dim * padded,
            transcendentals=rows * padded,
            bytes_accessed=(
                2 * dim * (rows + padded * rows // tile_r)
                + 4 * rows * padded * keep
            ),
        ),
        interpret=interpret,
        name="head_loss",
    )(h, wt, labels, label_logit)


@partial(jax.jit, static_argnames=("start", "rows", "vocab", "interpret"),
         donate_argnames=("d_matrix",))
def _backward_call(logits, ht, wt, labels, lse, scale, d_matrix, *,
                   start, rows, vocab, interpret):
    """(d rows (rows, dim) float32, d matrix (dim, vocab) float32) of
    the stretch of `rows` rows from row `start` (whole tiles both);
    `d_matrix` is what earlier stretches summed, or None for the first;
    `ht` (dim, all rows) is the forward sweep's rows transposed."""
    f32 = jnp.float32
    padded, dim = wt.shape
    tile_r, tile_v = _tiles(dim)
    first = start // tile_r
    accumulate = d_matrix is not None
    column = pl.BlockSpec((tile_r, 1), lambda j, i: (first + i, 0))
    matrix_tile = pl.BlockSpec((dim, tile_v), lambda j, i: (0, j))
    operands = [logits, ht, wt, labels, lse, scale]
    in_specs = [
        pl.BlockSpec((tile_r, tile_v), lambda j, i: (first + i, j)),
        pl.BlockSpec((dim, tile_r), lambda j, i: (0, first + i)),
        pl.BlockSpec((tile_v, dim), lambda j, i: (j, 0)),
        column, column, column,
    ]
    if accumulate:
        operands.append(d_matrix)
        in_specs.append(matrix_tile)
    return pl.pallas_call(
        partial(_backward_kernel, accumulate=accumulate),
        out_shape=(
            jax.ShapeDtypeStruct((rows, dim), f32),
            jax.ShapeDtypeStruct((dim, vocab), f32),
        ),
        grid=(padded // tile_v, rows // tile_r),
        in_specs=in_specs,
        out_specs=(
            pl.BlockSpec((rows, dim), lambda j, i: (0, 0)),
            matrix_tile,
        ),
        input_output_aliases={6: 1} if accumulate else {},
        compiler_params=_params("arbitrary", "arbitrary"),
        cost_estimate=pl.CostEstimate(
            flops=4 * rows * dim * padded,
            transcendentals=rows * padded,
            bytes_accessed=(
                4 * rows * padded + 2 * dim * rows * padded // tile_v
                + 2 * dim * padded + 4 * dim * rows
                + 4 * dim * vocab * (1 + accumulate)
            ),
        ),
        interpret=interpret,
        name="head_loss",
    )(*operands)


def _stretch_rows(rows: int, dim: int) -> int:
    """Rows of one call of the backward kernel: whole tiles whose
    float32 gradient fits STRETCH_BYTES, dealt evenly."""
    tile_r = _tiles(dim)[0]
    tiles = rows // tile_r
    fit = max(1, STRETCH_BYTES // (4 * dim * tile_r))
    calls = -(-tiles // fit)
    return -(-tiles // calls) * tile_r


def _kernel_forward(flat, matrix, labels, interpret, keep):
    """The four sums through the forward kernel, and what the backward
    sweep needs of it. The kernels' operands are rounded as a TPU's
    default precision rounds a float32 product's: rows in whole tiles
    (padding labelled -1), the matrix transposed, (vocabulary in whole
    tiles, dim), its padding zero. The label's logit is made here, from
    the label's row of that matrix, so that the sweep can count the
    rank as it goes."""
    f32, bf16 = jnp.float32, jnp.bfloat16
    dim, vocab = matrix.shape
    tile_r, tile_v = _tiles(dim)
    h, lab = _padded(flat.astype(bf16), labels, tile_r)
    lab = lab[:, None]
    wt = jnp.pad(matrix.T.astype(bf16), ((0, -vocab % tile_v), (0, 0)))
    picked = jnp.take(wt, jnp.maximum(lab[:, 0], 0), axis=0)
    label_logit = jnp.sum(
        h.astype(f32) * picked.astype(f32), axis=-1, keepdims=True
    )
    lse, rank, *logits = _forward_call(
        h, wt, lab, label_logit, vocab=vocab, keep=keep,
        interpret=interpret,
    )
    sums = _row_sums((lse - label_logit)[:, 0], rank[:, 0], lab[:, 0])
    return sums, (h, wt, lab, lse, *logits)


@partial(jax.custom_vjp, nondiff_argnums=(3,))
def _kernel_sums_at(flat, matrix, labels, interpret):
    return _kernel_forward(flat, matrix, labels, interpret, False)[0]


def _kernel_sums_fwd(flat, matrix, labels, interpret):
    sums, kept = _kernel_forward(flat, matrix, labels, interpret, True)
    # what the gradients have to look like: shapes and dtypes alone
    like = (
        jnp.zeros((flat.shape[0], 0), flat.dtype),
        jnp.zeros((0, matrix.shape[1]), matrix.dtype),
    )
    return sums, (kept, like)


def _kernel_sums_bwd(interpret, residuals, cotangents):
    (h, wt, lab, lse, logits), (like_rows, like_matrix) = residuals
    scale = jnp.where(lab >= 0, cotangents[0], 0.0).astype(jnp.float32)
    stretch = _stretch_rows(*h.shape)
    ht, d_rows, d_matrix = h.T, [], None
    for start in range(0, h.shape[0], stretch):
        d_part, d_matrix = _backward_call(
            logits, ht, wt, lab, lse, scale, d_matrix, start=start,
            rows=min(stretch, h.shape[0] - start),
            vocab=like_matrix.shape[1], interpret=interpret,
        )
        d_rows.append(d_part)
    d_rows = jnp.concatenate(d_rows)[:like_rows.shape[0]]
    return (
        d_rows.astype(like_rows.dtype), d_matrix.astype(like_matrix.dtype),
        None,
    )


_kernel_sums_at.defvjp(_kernel_sums_fwd, _kernel_sums_bwd)


def _kernel_sums(flat, matrix, labels, *, interpret: Optional[bool] = None):
    """`head_loss`'s sums through the two Mosaic kernels.
    `interpret=None`: compiled on a TPU, the interpreter elsewhere."""
    if interpret is None:
        interpret = not _on_tpu()
    return _kernel_sums_at(flat, matrix, labels, interpret)


__all__ = ["BLOCK_ELEMENTS", "SUMS", "head_loss", "head_loss_kind"]
