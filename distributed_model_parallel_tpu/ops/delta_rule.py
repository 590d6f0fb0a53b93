"""The gated delta rule with a decay per channel, in chunked form.

The recurrence of a delta-rule linear-attention head (Kimi Delta
Attention's; per head a state S of shape (dk, dv)):

    S_t = (I - b_t k_t k_t^T) Diag(exp g_t) S_{t-1} + b_t k_t v_t^T
    o_t = S_t^T q_t

with g_t <= 0 the log of the decay (one number per key channel) and b_t
in (0, 1) the write strength. Token by token that is T dependent steps
of rank-one work; `gated_delta_rule` computes the same outputs chunk by
chunk: inside a chunk of C tokens one unit-lower-triangular system, and
between chunks the state alone.

Within a chunk that starts from S_0, with G_i = g_1 + ... + g_i,

    u_i = b_i (v_i - (k_i * exp G_i)^T S_0 - sum_{j<i} A_ij u_j)
    S_i = Diag(exp G_i) S_0 + sum_{j<=i} Diag(exp(G_i - G_j)) k_j u_j^T
    o_i = (q_i * exp G_i)^T S_0 + sum_{j<=i} B_ij u_j
    A_ij = sum_c k_ic k_jc exp(G_ic - G_jc)   (j < i)
    B_ij = sum_c q_ic k_jc exp(G_ic - G_jc)   (j <= i)

so (I + Diag(b) A) U = Diag(b) (V - (K * exp G) S_0) is solved once
for the two right-hand sides that do not depend on S_0 (by the
inverse of the unit-triangular system, built from matrix products:
`_unit_lower_inverse`), and the scan over chunks carries S only.

Every exponent taken is a difference G_i - G_j with j <= i, or G_i
itself: never positive, so nothing overflows however strong the decay
(`exp(-G_j)`, which the factorised form of A needs, overflows float32
after a handful of tokens at g = -20). The price is that A and B are
reduced from a (C, C, dk) tensor on the vector unit and not a matrix
product; a later kernel can split the chunk into sub-blocks whose
off-diagonal pairs factorise safely. Decay, state and the triangular
solve are float32, and the products with the state run at `highest`
precision: on the TPU a float32 product at the default precision
rounds its operands to bfloat16, which for a state that is carried
over the whole sequence is the lower precision the configuration's
tolerance is meant to catch.

Differentiated by autodiff; the scan's body is rematerialised, so the
backward pass stores one state per chunk and recomputes the rest.
"""

from __future__ import annotations

import jax
import jax.numpy as jnp
from jax import lax

DEFAULT_CHUNK = 64
_HIGHEST = lax.Precision.HIGHEST


def _unit_lower_inverse(n):
    """(I + N)^-1 for strictly lower-triangular N (..., C, C), C a power
    of two >= 8, from matrix products alone (XLA's triangular solve is
    a custom call on the TPU, slow at this size and slower differentiated).

    The 8 x 8 diagonal blocks are inverted by the finite series of a
    nilpotent matrix, (I - N)(I + N^2)(I + N^4) (N^8 = 0; powers of so
    small a block stay small); pairs of inverted blocks then merge by
    [[A, 0], [C, D]]^-1 = [[A^-1, 0], [-D^-1 C A^-1, D^-1]] until one
    block is left. The series is NOT used for the whole chunk: with
    repeated keys the powers of a 64 x 64 N reach 1e10 and cancel."""
    c = n.shape[-1]
    if c < 8 or c & (c - 1):
        raise ValueError(f"the chunk has to be a power of two >= 8, got {c}")
    mm = lambda x, y: jnp.matmul(x, y, precision=_HIGHEST)

    def blocks(size, row, col):
        """The (size x size) blocks at block row 2i + row, block column
        2i + col (i alone where row is None), stacked on a new axis -3
        from static slices."""
        step = size if row is None else 2 * size
        return jnp.stack([
            n[..., o + (row or 0) * size:o + ((row or 0) + 1) * size,
              o + (col or 0) * size:o + ((col or 0) + 1) * size]
            for o in range(0, c, step)
        ], axis=-3)

    size = 8
    eye = jnp.eye(size, dtype=n.dtype)
    d = blocks(size, None, None)
    d2 = mm(d, d)
    inv = mm(mm(eye - d, eye + d2), eye + mm(d2, d2))
    while size < c:
        below = blocks(size, 1, 0)    # under the diagonal of each pair
        upper, lower = inv[..., 0::2, :, :], inv[..., 1::2, :, :]
        corner = -mm(mm(lower, below), upper)
        inv = jnp.concatenate([
            jnp.concatenate([upper, jnp.zeros_like(upper)], axis=-1),
            jnp.concatenate([corner, lower], axis=-1),
        ], axis=-2)
        size *= 2
    return inv[..., 0, :, :]


def _chunk_step(s0, chunk):
    """One chunk for every (batch, head): float32 state (B, H, dk, dv)
    -> (new state, outputs (B, H, C, dv))."""
    q, k, v, g, beta = (x.astype(jnp.float32) for x in chunk)
    c, dv = q.shape[-2], v.shape[-1]
    gc = jnp.cumsum(g, axis=-2)                          # G_i, <= 0
    diff = gc[..., :, None, :] - gc[..., None, :, :]     # G_i - G_j
    lower = jnp.tril(jnp.ones((c, c), jnp.bool_))        # j <= i
    decay = jnp.exp(jnp.where(lower[..., None], diff, -jnp.inf))
    kd = k[..., None, :, :] * decay                      # k_j e^(G_i-G_j)
    a = jnp.tril(jnp.sum(k[..., :, None, :] * kd, axis=-1), -1)
    b = jnp.sum(q[..., :, None, :] * kd, axis=-1)
    eg = jnp.exp(gc)
    rhs = beta[..., None] * jnp.concatenate([v, k * eg], axis=-1)
    solved = jnp.matmul(
        _unit_lower_inverse(beta[..., None] * a), rhs, precision=_HIGHEST
    )
    u = solved[..., :dv] - jnp.matmul(
        solved[..., dv:], s0, precision=_HIGHEST
    )
    out = jnp.matmul(q * eg, s0, precision=_HIGHEST) + jnp.matmul(
        b, u, precision=_HIGHEST
    )
    g_end = gc[..., -1:, :]                              # G_C
    new = jnp.swapaxes(jnp.exp(g_end), -1, -2) * s0 + jnp.matmul(
        jnp.swapaxes(k * jnp.exp(g_end - gc), -1, -2), u,
        precision=_HIGHEST,
    )
    return new, out


def gated_delta_rule(q, k, v, g, beta, *, chunk: int = DEFAULT_CHUNK):
    """q, k (B, T, H, dk), v (B, T, H, dv), g (B, T, H, dk) the log
    decay (<= 0; float32), beta (B, T, H) -> outputs (B, T, H, dv) in
    v's dtype. The arithmetic is float32 whatever q, k, v come in. The
    state starts at zero and is carried between chunks in float32. T
    need not be a multiple of `chunk`: the tail is padded with tokens
    that neither decay nor write."""
    bsz, t, h, dk = q.shape
    dv = v.shape[-1]
    pad = -t % chunk
    n = (t + pad) // chunk

    def chunks(x):
        """(B, T, H, ...) -> (n, B, H, C, ...) in x's own dtype: the
        scan's operands (and their cotangents) stay as narrow as the
        caller made them; a chunk is widened to float32 as it is
        taken."""
        if pad:
            x = jnp.pad(x, [(0, 0), (0, pad)] + [(0, 0)] * (x.ndim - 2))
        x = x.reshape((bsz, n, chunk) + x.shape[2:])
        return jnp.moveaxis(jnp.moveaxis(x, 1, 0), 2, 3)

    _, out = lax.scan(
        jax.checkpoint(_chunk_step),
        jnp.zeros((bsz, h, dk, dv), jnp.float32),
        (chunks(q), chunks(k), chunks(v), chunks(g), chunks(beta)),
    )
    # (n, B, H, C, dv) -> (B, T, H, dv)
    out = jnp.moveaxis(out, 0, 1)                        # (B, n, H, C, dv)
    out = jnp.moveaxis(out, 2, 3).reshape(bsz, n * chunk, h, dv)
    return out[:, :t].astype(v.dtype)


def gated_delta_rule_stepwise(q, k, v, g, beta):
    """The recurrence as written, one token a step: what the chunked
    form is tested against (the benchmark's reference keeps its own
    copy). Same contract as `gated_delta_rule`; float32 throughout."""
    bsz, _, h, dk = q.shape
    dv = v.shape[-1]

    def step(s, x):
        qt, kt, vt, gt, bt = x                           # (B, H, ...)
        s = jnp.exp(gt)[..., None] * s
        read = jnp.einsum("bhk,bhkv->bhv", kt, s, precision=_HIGHEST)
        s = s + (bt[..., None] * kt)[..., None] * (vt - read)[..., None, :]
        return s, jnp.einsum("bhk,bhkv->bhv", qt, s, precision=_HIGHEST)

    xs = tuple(
        jnp.moveaxis(x.astype(jnp.float32), 1, 0)
        for x in (q, k, v, g, beta)
    )
    _, out = lax.scan(step, jnp.zeros((bsz, h, dk, dv), jnp.float32), xs)
    return jnp.moveaxis(out, 0, 1).astype(v.dtype)
