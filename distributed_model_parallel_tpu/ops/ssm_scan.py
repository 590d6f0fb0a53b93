"""Mamba-1's selective state-space recurrence, with the state carried
in and out, and the short causal convolution before it.

Per channel d and state index n (A < 0, everything float32):

    h_t[n, d] = exp(delta_t[d] A[n, d]) h_(t-1)[n, d]
                + delta_t[d] x_t[d] B_t[n]
    y_t[d]    = sum_n h_t[n, d] C_t[n]

The state is laid out (N, D), not (D, N): the channel axis is the long
one (thousands) and sits on the TPU's 128 lanes, where N = 16 would be
padded eightfold.

`selective_scan` walks a stretch of T positions from a given state
(prefill, one chunk at a time, and the dense forward pass from zeros);
`selective_step` is its body for one position of every row at once
(decode, all slots). The walk is one algorithm with two programs,
picked per call by `scan_kind` from what the call can observe:
`selective_scan_loop`, a compiled loop of `selective_step` whose state
goes out to HBM and back between a few small fusions a position, and
`selective_scan_kernel`, ONE Mosaic kernel (it names itself `ssm_scan`)
that keeps the state block on the chip over the whole stretch: on a TPU
for a stretch long enough to pay for it at widths that tile, the loop
for one position, for widths that do not tile and on every other
backend (INTERNALS.md section 20). Both do the same float32 arithmetic
per element. The walk is sequential in time: the factors
exp(delta_t A) are formed per position in float32 and multiplied into
the state, never divided out of a running product (which underflows
within a few hundred positions at Mamba's step sizes). A position whose
`valid` flag is off has its delta forced to 0, so its factor is 1 and
its input 0: it leaves the state as it was.

`conv_carry` is the depthwise causal convolution of width K over a
stretch that continues an earlier one: it takes the K - 1 inputs kept
from before the stretch and hands back the K - 1 to keep after it (the
last VALID ones, so a padded tail keeps nothing).
"""

from __future__ import annotations

from functools import partial
from typing import Optional, Tuple

import jax
import jax.numpy as jnp
from jax import lax
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu

# Positions advanced per iteration of the compiled loop: the body is a
# few elementwise passes over (N, D), so the loop's own overhead is what
# unrolling buys back. 8 was taken, not measured against its neighbours
# on the chip (PERF.md section 7).
UNROLL = 8


def selective_step(x, delta, a, b, c, h) -> Tuple[jax.Array, jax.Array]:
    """One position of every row: x, delta (S, D); a (N, D); b, c
    (S, N); h (S, N, D). Returns (y (S, D) float32, new h). The
    arithmetic is float32; the new state is handed back in the dtype
    the state came in, which is float32 wherever the state is kept so
    (a pool kept in less rounds its state at every position)."""
    f32 = jnp.float32
    delta = delta.astype(f32)
    factor = jnp.exp(delta[:, None, :] * a.astype(f32)[None])
    push = (delta * x.astype(f32))[:, None, :] * b.astype(f32)[:, :, None]
    new = (factor * h.astype(f32) + push).astype(h.dtype)
    y = jnp.sum(new.astype(f32) * c.astype(f32)[:, :, None], axis=1)
    return y, new


def _masked(delta, valid):
    delta = delta.astype(jnp.float32)
    if valid is None:
        return delta
    return jnp.where(valid[:, :, None], delta, 0.0)


def selective_scan(x, delta, a, b, c, h0,
                   valid: Optional[jax.Array] = None
                   ) -> Tuple[jax.Array, jax.Array]:
    """T positions of each row, in order, from the state `h0`: x, delta
    (B, T, D); a (N, D); b, c (B, T, N); h0 (B, N, D); valid (B, T)
    bool or None. Returns (y (B, T, D) float32, the state after the
    last position (B, N, D) in h0's dtype). One recurrence, two
    programs for it, picked per call by `scan_kind` from what the call
    can observe (the backend, T, D, N)."""
    t, d = x.shape[1:]
    walk = (
        selective_scan_kernel if scan_kind(t, d, a.shape[0]) == "kernel"
        else selective_scan_loop
    )
    return walk(x, delta, a, b, c, h0, valid)


def selective_scan_loop(x, delta, a, b, c, h0,
                        valid: Optional[jax.Array] = None
                        ) -> Tuple[jax.Array, jax.Array]:
    """`selective_scan` as a compiled loop of `selective_step`."""
    delta = _masked(delta, valid)
    over_time = lambda z: jnp.swapaxes(z, 0, 1)

    def body(h, step):
        x_t, delta_t, b_t, c_t = step
        y_t, h = selective_step(x_t, delta_t, a, b_t, c_t, h)
        return h, y_t

    h, y = lax.scan(
        body, h0,
        (over_time(x), over_time(delta), over_time(b), over_time(c)),
        unroll=min(UNROLL, x.shape[1]),
    )
    return over_time(y), h


LANES, SUBLANES = 128, 8
# The kernel's own blocks, positions and channels of one grid step,
# measured on the v5e at (1, 512, 5120), N 16 (PERF.md section 6):
# 0.097 ms a call at (128, 1024), 0.104 at (128, 512), 0.114 at (256,
# 256). At N 16 they take 7 MB of VMEM, b and c's blocks 4 of it.
BLOCK_T, BLOCK_D = 128, 1024
# A stretch shorter than this stays on the loop: at D 5120, N 16 on the
# v5e the two cost the same at 32 positions (0.021 / 0.022 ms) and the
# loop twice the kernel at 64 (0.039 / 0.021 ms).
KERNEL_MIN_T = 64


def _on_tpu() -> bool:
    # The selector's own predicate, apart from the kernel's `interpret`
    # default: a test that answers "tpu" here still runs the kernel in
    # the interpreter.
    return jax.default_backend() == "tpu"


def _block(dim: int, want: int, unit: int) -> int:
    """Largest multiple of `unit` that divides `dim` and is <= `want`
    (0 where none exists)."""
    for size in range(min(want, dim) // unit * unit, 0, -unit):
        if dim % size == 0:
            return size
    return 0


def scan_kind(t: int, d: int, n: int) -> str:
    """Which program `selective_scan` runs for these static facts:
    `"kernel"` on a TPU for a stretch of at least KERNEL_MIN_T
    positions whose T, D and N tile (whole sublanes of positions and of
    state indices, whole lanes of channels); `"loop"` anywhere else:
    the decode step's one position, a width that does not tile, and
    every backend that is not a TPU, never the Pallas interpreter."""
    if (
        _on_tpu()
        and t >= KERNEL_MIN_T
        and t % SUBLANES == 0
        and n % SUBLANES == 0
        and d % LANES == 0
    ):
        return "kernel"
    return "loop"


def _scan_kernel(x_ref, delta_ref, a_ref, b_ref, c_ref, h0_ref,
                 y_ref, h_ref, h_scr):
    """One grid step: `block_t` positions of `block_d` channels of one
    row. The grid is (rows, stretches of positions, blocks of channels)
    with the channels innermost, so b and c (whose blocks do not depend
    on the channel block) are fetched once a stretch; the state of
    every channel block waits in `h_scr` for the next stretch."""
    f32 = jnp.float32
    k, j = pl.program_id(1), pl.program_id(2)
    block_t, block_d = x_ref.shape
    tiles = [slice(i, i + LANES) for i in range(0, block_d, LANES)]

    @pl.when(k == 0)
    def _():
        h_scr[j] = h0_ref[...].astype(f32)

    row = lax.broadcasted_iota(jnp.int32, (SUBLANES, LANES), 0)

    def sums_over_sublanes(parts):
        """Eight (8, 128) registers -> one whose row s is the sum of
        part s over its sublanes: three rounds that fold two registers
        into one (a select, two rotations, an add) where a sum a
        position would take three rotations and adds each, and then a
        row to place."""
        shift = SUBLANES // 2
        while shift:
            low, half = (row & shift) == 0, len(parts) // 2
            parts = [
                jnp.where(low, p, q) + jnp.where(
                    low, pltpu.roll(p, SUBLANES - shift, 0),
                    pltpu.roll(q, shift, 0))
                for p, q in zip(parts[:half], parts[half:])
            ]
            shift //= 2
        return parts[0]

    def eight_positions(g, hs):
        t0 = pl.multiple_of(g * SUBLANES, SUBLANES)
        new_hs = []
        for lanes, h in zip(tiles, hs):
            delta = delta_ref[pl.ds(t0, SUBLANES), lanes]
            dx = delta * x_ref[pl.ds(t0, SUBLANES), lanes].astype(f32)
            a = a_ref[:, lanes]
            parts = []
            for s in range(SUBLANES):
                # selective_step's arithmetic, element for element
                factor = jnp.exp(delta[s:s + 1] * a)
                h = factor * h + dx[s:s + 1] * b_ref[t0 + s]
                # rounded as the loop rounds a state held in less
                # (nothing where it is held in float32)
                h = h.astype(h_ref.dtype).astype(f32)
                hc = h * c_ref[t0 + s]
                parts.append(sum(
                    hc[i:i + SUBLANES] for i in range(0, hc.shape[0], SUBLANES)
                ))
            y_ref[pl.ds(t0, SUBLANES), lanes] = sums_over_sublanes(parts)
            new_hs.append(h)
        return tuple(new_hs)

    hs = lax.fori_loop(
        0, block_t // SUBLANES, eight_positions,
        tuple(h_scr[j, :, lanes] for lanes in tiles),
    )
    for lanes, h in zip(tiles, hs):
        h_scr[j, :, lanes] = h
        h_ref[j, :, lanes] = h.astype(h_ref.dtype)


# Jitted on its own: a program of 26 such layers then traces and lowers
# the kernel once, not 26 times (9.5 s against 0.3 s of every start of
# the chunk program, compile cache or not; compiled here for a v5e).
@partial(jax.jit, static_argnames=("block_t", "block_d", "interpret"))
def _scan_call(x, delta, a, b, c, h0, *, block_t, block_d, interpret):
    f32 = jnp.float32
    rows, t, d = x.shape
    n = a.shape[0]
    over_lanes = lambda z: jnp.broadcast_to(
        z.astype(f32)[..., None], (rows, t, n, LANES)
    )
    stretch = pl.BlockSpec((None, block_t, block_d), lambda i, k, j: (i, k, j))
    spread = pl.BlockSpec(
        (None, block_t, n, LANES), lambda i, k, j: (i, k, 0, 0)
    )
    # every channel block's state: in by the block, out once a row (an
    # output block visited again after another is not defined)
    states = (d // block_d, n, block_d)
    y, h = pl.pallas_call(
        _scan_kernel,
        out_shape=(
            jax.ShapeDtypeStruct((rows, t, d), f32),
            jax.ShapeDtypeStruct((rows, *states), h0.dtype),
        ),
        grid=(rows, t // block_t, d // block_d),
        in_specs=[
            stretch, stretch,
            pl.BlockSpec((n, block_d), lambda i, k, j: (0, j)),
            spread, spread,
            pl.BlockSpec((None, n, block_d), lambda i, k, j: (i, 0, j)),
        ],
        out_specs=(
            stretch,
            pl.BlockSpec((None, *states), lambda i, k, j: (i, 0, 0, 0)),
        ),
        scratch_shapes=[pltpu.VMEM(states, f32)],
        compiler_params=pltpu.CompilerParams(
            dimension_semantics=("parallel", "arbitrary", "arbitrary"),
        ),
        cost_estimate=pl.CostEstimate(
            flops=7 * rows * t * n * d,
            transcendentals=rows * t * n * d,
            bytes_accessed=4 * rows * t * (3 * d + 2 * n * LANES),
        ),
        interpret=interpret,
        name="ssm_scan",
    )(x, delta, a.astype(f32), over_lanes(b), over_lanes(c), h0)
    return y, jnp.moveaxis(h, 1, 2).reshape(h0.shape)


def selective_scan_kernel(x, delta, a, b, c, h0,
                          valid: Optional[jax.Array] = None, *,
                          interpret: Optional[bool] = None
                          ) -> Tuple[jax.Array, jax.Array]:
    """`selective_scan` as ONE Mosaic kernel, named `ssm_scan`: the
    state block (N, block_d) stays on the chip from `h0` to the last
    position, N on the sublanes and the channels on the lanes. A
    position's delta and delta x are rows of (T, block_d) blocks, spread
    over the sublanes; its B and C come in spread over the lanes, (T, N,
    128) made once per call by XLA, so that the walk relays nothing out.
    T and N must be multiples of 8, D of 128 (`scan_kind`).
    `interpret=None`: compiled on a TPU, the interpreter elsewhere."""
    if interpret is None:
        interpret = jax.default_backend() != "tpu"
    t, d = x.shape[1:]
    n = a.shape[0]
    block_t = _block(t, BLOCK_T, SUBLANES)
    block_d = _block(d, BLOCK_D, LANES)
    if not (block_t and block_d) or n % SUBLANES:
        raise ValueError(
            f"ssm_scan: T {t}, D {d}, N {n} do not tile by "
            f"({SUBLANES}, {LANES}, {SUBLANES})"
        )
    return _scan_call(
        x, _masked(delta, valid), a, b, c, h0,
        block_t=block_t, block_d=block_d, interpret=interpret,
    )


def conv_carry(u, w, bias, kept, n_valid) -> Tuple[jax.Array, jax.Array]:
    """`y_t = bias + sum_k w[k] u_(t-K+1+k)` over a stretch u (B, T, D)
    that follows the inputs `kept` (B, K - 1, D); w (K, D), bias (D,).
    `n_valid` (B,) int32: how many leading positions of the stretch are
    real. Returns (y (B, T, D) float32, for the caller's activation to
    round once, and the K - 1 inputs before position `n_valid`: the
    stretch's own last ones, or what was kept where the stretch is
    shorter than that)."""
    k, t = w.shape[0], u.shape[1]
    line = jnp.concatenate([kept.astype(u.dtype), u], axis=1)
    y = bias.astype(jnp.float32) + sum(
        line[:, i:i + t].astype(jnp.float32) * w[i].astype(jnp.float32)
        for i in range(k)
    )
    # position j of `line` is input j - (K - 1) of the stretch, so the
    # K - 1 inputs before stretch position n_valid start at line[n_valid]
    new_kept = jax.vmap(
        lambda row, n: lax.dynamic_slice_in_dim(row, n, k - 1, axis=0)
    )(line, n_valid)
    return y, new_kept.astype(kept.dtype)


__all__ = [
    "UNROLL", "conv_carry", "scan_kind", "selective_scan",
    "selective_scan_kernel", "selective_scan_loop", "selective_step",
]
