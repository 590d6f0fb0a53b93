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
(decode, all slots). The walk is sequential in time: the factors
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

from typing import Optional, Tuple

import jax
import jax.numpy as jnp
from jax import lax

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


def selective_scan(x, delta, a, b, c, h0,
                   valid: Optional[jax.Array] = None
                   ) -> Tuple[jax.Array, jax.Array]:
    """T positions of each row, in order, from the state `h0`: x, delta
    (B, T, D); a (N, D); b, c (B, T, N); h0 (B, N, D); valid (B, T)
    bool or None. Returns (y (B, T, D) float32, the state after the
    last position (B, N, D) in h0's dtype)."""
    f32 = jnp.float32
    delta = delta.astype(f32)
    if valid is not None:
        delta = jnp.where(valid[:, :, None], delta, 0.0)
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


__all__ = ["UNROLL", "conv_carry", "selective_scan", "selective_step"]
