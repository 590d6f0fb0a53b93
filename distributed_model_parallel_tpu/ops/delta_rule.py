"""The gated delta rule, in chunked form, with a decay per key channel
or one a head.

The recurrence of a delta-rule linear-attention head (per head a state
S of shape (dk, dv)):

    S_t = (I - b_t k_t k_t^T) Diag(exp g_t) S_{t-1} + b_t k_t v_t^T
    o_t = S_t^T q_t

with g_t <= 0 the log of the decay and b_t in (0, 1) the write
strength. g_t is one number per key channel (Kimi Delta Attention's,
g (..., dk)) or one a head (Gated DeltaNet's, g (..., 1)); `decay_kind`
names the two by g's trailing axis, and everything but A, B and the
sums G below takes either shape and broadcasts a trailing 1. Token by
token that is T dependent steps of rank-one work; `gated_delta_rule` computes the same outputs chunk by
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
A chunk's work splits in two: what does not read S_0 (G, the decay
factors, A, B, the inverse and both solutions; `_chunk_terms`) and
what does (`_state_step`): one product of the state with rows stacked
from solved K and q * exp G, and one of U with rows stacked from B and
the decayed keys, in place of four products.

With a decay per channel, A and B are built from sub-blocks of `_SUB`
rows (`_chunk_products`). Inside a sub-block
they are reduced on the vector unit from a (_SUB, _SUB, dk) tensor
exp(G_i - G_j), j <= i, the differences taken from a cumulative sum
that restarts at the sub-block (the more exact: under strong decay
|G| passes 1,000 within a chunk). Every pair of sub-blocks below the
diagonal is a matrix product through a reference token R that lies
between them, last token of the column block <= R < first token of
the row block:

    G_i - G_j = (G_i - G_R) + (G_R - G_j)
    A_ij = (k_i * exp(G_i - G_R)) . (k_j * exp(G_R - G_j))

and the same with q_i for B. G only falls along the chunk and
j <= R < i, so both brackets are <= 0: each factor is a row scaled
into [0, 1]. The pairs are taken as `_unit_lower_inverse` takes its
merges: neighbouring sub-blocks through the last token of the left
one, then neighbouring pairs of them likewise, so R is always the
token before the row block, both brackets are sums of g inside one
block of the pair, and a chunk of 64 takes three products for A and
three for B. No exponent taken anywhere is positive, so nothing
overflows however strong the decay (`exp(-G_j)`, which the one-product
form of A needs, overflows float32 after a handful of tokens at
g = -20); a factor that underflows to 0 does so no sooner than
exp(G_i - G_j) itself, which is no larger than either factor: it is
the true value's own underflow.

With one decay a head, exp(G_ic - G_jc) is one number a pair, so A
and B are plain products masked by one (C, C) matrix (`_head_products`):

    Gamma_ij = exp(G_i - G_j)   (j <= i; 0 above the diagonal)
    A = tril(K K^T * Gamma, -1),   B = Q K^T * Gamma

G_i - G_j is taken as the sum of the g between j and i (one product of
the chunk's g with two triangles of ones), never as the difference of
two sums over the chunk: under strong decay those reach -1,000 and
more, where float32 cancellation would move a pair's factor by 1e-4.
G_i and G_C - G_i are that product's first column (plus g_1) and last
row, so the path takes no cumulative sum (on the TPU one is a window
of C steps, as slow over one column as over 128: PERF.md section 6).

Decay, state and the triangular solve are float32, and every product
runs at `highest` precision: on the TPU a float32 product at the
default precision rounds its operands to bfloat16, which for a state
that is carried over the whole sequence is the lower precision the
configuration's tolerance is meant to catch.

Differentiated by autodiff; the scan's body is rematerialised, so the
backward pass stores one state per chunk and recomputes the rest.
"""

from __future__ import annotations

import jax
import jax.numpy as jnp
from jax import lax

DEFAULT_CHUNK = 64
# Rows of a sub-block of the chunk: A and B are reduced from a decay tensor
# inside one and are matrix products between two. 16 on the v5e: 8 and 32
# are no faster there (PERF.md, PR 37).
_SUB = 16
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
        # pairs by a reshape: strided picks compile to gathers, which
        # cost kimilin_train_8k its whole gain from the state step
        pairs = inv.reshape(inv.shape[:-3] + (-1, 2) + inv.shape[-2:])
        upper, lower = pairs[..., 0, :, :], pairs[..., 1, :, :]
        corner = -mm(mm(lower, below), upper)
        inv = jnp.concatenate([
            jnp.concatenate([upper, jnp.zeros_like(upper)], axis=-1),
            jnp.concatenate([corner, lower], axis=-1),
        ], axis=-2)
        size *= 2
    return inv[..., 0, :, :]


def decay_kind(g) -> str:
    """The path the op takes for a log decay g (..., dk) or (..., 1):
    "head" where the trailing axis is 1 (one decay a head, Gated
    DeltaNet's), else "channel" (one a key channel, Kimi Delta
    Attention's)."""
    return "head" if g.shape[-1] == 1 else "channel"


def _head_products(q, k, g):
    """A (strictly lower) and B (lower) of one chunk, both (..., C, C),
    from float32 q, k (..., C, dk) and one log decay a head g
    (..., C, 1), and the sums G_i and G_C - G_i, both (..., C, 1): the
    module docstring's head form, every sum of g an entry of one
    product."""
    c = g.shape[-2]
    upto = jnp.tril(jnp.ones((c, c), g.dtype))           # m <= i
    # gaps_ij = sum over j < m <= i of g_m = G_i - G_j (for j <= i)
    gaps = jnp.matmul(upto * jnp.swapaxes(g, -1, -2),
                      jnp.tril(jnp.ones((c, c), g.dtype), -1),
                      precision=_HIGHEST)
    gamma = jnp.exp(jnp.where(upto > 0, gaps, -jnp.inf))
    # K K^T and Q K^T as one product over rows stacked from k and q
    products = jnp.einsum(
        "...ic,...jc->...ij", jnp.concatenate([k, q], axis=-2), k,
        precision=_HIGHEST,
    )
    return (
        jnp.tril(products[..., :c, :] * gamma, -1),
        products[..., c:, :] * gamma,
        g[..., :1, :] + gaps[..., :1],                   # G_i
        jnp.swapaxes(gaps[..., -1:, :], -1, -2),         # G_C - G_i
    )


def _chunk_products(q, k, g):
    """A (strictly lower) and B (lower) of one chunk, both (..., C, C),
    from float32 q, k and log decay g (..., C, dk): the module
    docstring's sub-block form."""
    c, dk = k.shape[-2:]
    sub = min(_SUB, c)
    # Sums of g that restart at every sub-block: `upto` from the block's
    # start to t, with t (G_t - G_(start - 1)); `after` what follows t
    # in its block (G_end - G_t).
    gb = g.reshape(g.shape[:-2] + (c // sub, sub, dk))
    later = jnp.pad(
        gb[..., 1:, :], [(0, 0)] * (gb.ndim - 2) + [(0, 1), (0, 0)])
    upto = jnp.cumsum(gb, axis=-2).reshape(g.shape)
    after = lax.cumsum(later, axis=gb.ndim - 2, reverse=True).reshape(g.shape)
    lower = jnp.tril(jnp.ones((sub, sub), jnp.bool_))    # j <= i

    def place(block, row, col):
        """`block` at (row, col) of a (C, C) of zeros."""
        rows, cols = block.shape[-2:]
        return jnp.pad(block, [(0, 0)] * (block.ndim - 2) + [
            (row, c - row - rows), (col, c - col - cols)])

    a = b = 0.0
    for lo in range(0, c, sub):
        rows = slice(lo, lo + sub)
        gi = upto[..., rows, :]
        diff = gi[..., :, None, :] - gi[..., None, :, :]   # G_i - G_j
        decay = jnp.exp(jnp.where(lower[..., None], diff, -jnp.inf))
        kd = k[..., None, rows, :] * decay                 # k_j e^(G_i-G_j)
        a += place(
            jnp.tril(jnp.sum(k[..., rows, None, :] * kd, axis=-1), -1),
            lo, lo)
        b += place(jnp.sum(q[..., rows, None, :] * kd, axis=-1), lo, lo)
    # Under the diagonal: pairs of neighbouring blocks of n tokens, the
    # right one's rows against the left one's columns through R, the
    # left one's last token; then the sums restart every 2 n tokens.
    n = sub
    while n < c:
        if n > sub:
            upto, after = _restart_every(n, upto, after)
        for lo in range(0, c, 2 * n):
            left, right = slice(lo, lo + n), slice(lo + n, lo + 2 * n)
            scale = jnp.exp(upto[..., right, :])           # e^(G_i - G_R)
            cols = k[..., left, :] * jnp.exp(after[..., left, :])
            under = lambda x: jnp.einsum(
                "...ic,...jc->...ij", x[..., right, :] * scale, cols,
                precision=_HIGHEST,
            )
            a += place(under(k), lo + n, lo)
            b += place(under(q), lo + n, lo)
        n *= 2
    return a, b


def _restart_every(n, upto, after):
    """Sums (..., C, dk) that restart every n / 2 tokens -> every n: in
    each pair of halves the right one's `upto` takes the left one's
    total, and the left one's `after` the right one's."""
    half = n // 2
    uptos, afters = [], []
    for lo in range(0, upto.shape[-2], n):
        left, right = slice(lo, lo + half), slice(lo + half, lo + n)
        uptos += [upto[..., left, :],
                  upto[..., right, :] + upto[..., lo + half - 1:lo + half, :]]
        afters += [after[..., left, :] + upto[..., lo + n - 1:lo + n, :],
                   after[..., right, :]]
    return (jnp.concatenate(uptos, axis=-2),
            jnp.concatenate(afters, axis=-2))


def _chunk_terms(chunk):
    """What a chunk (B, H, C, ...) needs from its inputs that does not
    depend on the state it starts from (g (..., C, dk) or (..., C, 1),
    `decay_kind`):

    - `reads` (..., 2C, dk): rows (solved K; q * exp G), which multiply
      the state;
    - `solved_v` (..., C, dv);
    - `writes` (..., C + dk, C): rows (B; (k * exp(G_C - G))^T), which
      multiply U;
    - `decay` (..., dk | 1, 1): exp(G_C) for the state's rows.
    """
    q, k, v, g, beta = (x.astype(jnp.float32) for x in chunk)
    dv = v.shape[-1]
    # (the channel path's operations in the order they always had)
    head = decay_kind(g) == "head"
    if head:
        with jax.named_scope("kda_chunk"):
            a, b, gc, after = _head_products(q, k, g)
    else:
        gc = jnp.cumsum(g, axis=-2)                      # G_i, <= 0
    eg = jnp.exp(gc)
    with jax.named_scope("kda_chunk"):
        if not head:
            a, b = _chunk_products(q, k, g)
        rhs = beta[..., None] * jnp.concatenate([v, k * eg], axis=-1)
        solved = jnp.matmul(
            _unit_lower_inverse(beta[..., None] * a), rhs,
            precision=_HIGHEST,
        )
    g_end = gc[..., -1:, :]                              # G_C
    reads = jnp.concatenate([solved[..., dv:], q * eg], axis=-2)
    if not head:
        after = g_end - gc                               # G_C - G_i
    writes = jnp.concatenate(
        [b, jnp.swapaxes(k * jnp.exp(after), -1, -2)], axis=-2)
    decay = jnp.swapaxes(jnp.exp(g_end), -1, -2)
    return reads, solved[..., :dv], writes, decay


def _state_step(s0, terms):
    """One chunk's work with the state (B, H, dk, dv), given its
    `_chunk_terms`: u = solved V - solved K S_0, outputs (q e^G) S_0 +
    B u, new state e^(G_C) S_0 + (k e^(G_C - G))^T u."""
    reads, solved_v, writes, decay = terms
    c = solved_v.shape[-2]
    read = jnp.matmul(reads, s0, precision=_HIGHEST)
    u = solved_v - read[..., :c, :]
    written = jnp.matmul(writes, u, precision=_HIGHEST)
    out = read[..., c:, :] + written[..., :c, :]
    return decay * s0 + written[..., c:, :], out


def _chunk_step(s0, chunk):
    """One chunk for every (batch, head): float32 state (B, H, dk, dv)
    -> (new state, outputs (B, H, C, dv))."""
    return _state_step(s0, _chunk_terms(chunk))


def gated_delta_rule(q, k, v, g, beta, *, chunk: int = DEFAULT_CHUNK):
    """q, k (B, T, H, dk), v (B, T, H, dv), g (B, T, H, dk) or
    (B, T, H, 1) the log decay (<= 0; float32; `decay_kind`), beta
    (B, T, H) -> outputs (B, T, H, dv) in v's dtype. The arithmetic is
    float32 whatever q, k, v come in. The state starts at zero and is
    carried between chunks in float32. T need not be a multiple of
    `chunk`: the tail is padded with tokens that neither decay nor
    write."""
    bsz, _, h, dk = q.shape
    s0 = jnp.zeros((bsz, h, dk, v.shape[-1]), jnp.float32)
    return gated_delta_rule_from(q, k, v, g, beta, s0, chunk=chunk)[0]


def gated_delta_rule_from(q, k, v, g, beta, s0, *,
                          chunk: int = DEFAULT_CHUNK):
    """`gated_delta_rule` from the state `s0` (B, H, dk, dv) (a served
    sequence's, left by its previous chunk) -> (outputs, the float32
    state after the last position). A caller whose own tail is padding
    gives it g = 0 and beta = 0, and the state passes it unchanged."""
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

    state, out = lax.scan(
        jax.checkpoint(_chunk_step),
        s0.astype(jnp.float32),
        (chunks(q), chunks(k), chunks(v), chunks(g), chunks(beta)),
    )
    # (n, B, H, C, dv) -> (B, T, H, dv)
    out = jnp.moveaxis(out, 0, 1)                        # (B, n, H, C, dv)
    out = jnp.moveaxis(out, 2, 3).reshape(bsz, n * chunk, h, dv)
    return out[:, :t].astype(v.dtype), state


def gated_delta_rule_step(q, k, v, g, beta, s):
    """One token of every row from the state it has: q, k (B, H, dk),
    v (B, H, dv), g (B, H, dk) or (B, H, 1), beta (B, H), s
    (B, H, dk, dv) -> (outputs (B, H, dv) float32, the new float32
    state). The recurrence as written, float32 at `highest`."""
    qt, kt, vt, gt, bt = (x.astype(jnp.float32) for x in (q, k, v, g, beta))
    s = jnp.exp(gt)[..., None] * s.astype(jnp.float32)
    read = jnp.einsum("bhk,bhkv->bhv", kt, s, precision=_HIGHEST)
    s = s + (bt[..., None] * kt)[..., None] * (vt - read)[..., None, :]
    return jnp.einsum("bhk,bhkv->bhv", qt, s, precision=_HIGHEST), s


def gated_delta_rule_stepwise(q, k, v, g, beta):
    """The recurrence as written, one token a step
    (`gated_delta_rule_step`): what the chunked form is tested against
    (the benchmark's reference keeps its own copy). Same contract as
    `gated_delta_rule`; float32 throughout."""
    bsz, _, h, dk = q.shape
    dv = v.shape[-1]

    def step(s, x):
        out, s = gated_delta_rule_step(*x, s)
        return s, out

    xs = tuple(
        jnp.moveaxis(x.astype(jnp.float32), 1, 0)
        for x in (q, k, v, g, beta)
    )
    _, out = lax.scan(step, jnp.zeros((bsz, h, dk, dv), jnp.float32), xs)
    return jnp.moveaxis(out, 0, 1).astype(v.dtype)
