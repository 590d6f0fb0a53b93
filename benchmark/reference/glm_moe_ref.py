"""The plain reference for the GLM-4 MoE "lite" configurations: the
forward pass in straightforward float32 `jax.numpy`, every matrix
product at `highest` precision, the EXPANDED form of latent attention
alone (every head's keys and values made from the compressed rows),
dense causal attention over the whole sequence, a loop over the routed
experts with a dense product of ALL rows each (expert after expert, one
float32 copy at a time, so that at the published widths it fits beside
9 GB of weights), no cache, no chunks, no batching tricks, no kernels, and
nothing imported from the package under test: it takes the same
parameter pytree (whatever dtype it rests in: a layer's weights are
upcast as the layer is reached) and is otherwise independent of it.

The equations (`arch` gives the sizes the tree's shapes do not):

  block    h = x + mixer(RMSNorm(x));  y = h + ff(RMSNorm(h)), eps
  mixer    cq = RMSNorm(x W_qa);  q = cq W_qb -> per head q_nope, q_rope
           [c_raw, kr] = x W_kva;  c = RMSNorm(c_raw)
           kr = RoPE(kr, pos) (one for all heads), q_rope = RoPE(q_rope, pos)
           [k_nope_h, v_h] = c W_kvb;  k_h = [k_nope_h, kr]
           causal softmax(q_h . k_h * (nope + rope)^-1/2) v_h;  W_o
  RoPE     all `rope` values, base theta, value i paired with i + rope/2:
           angle_i = pos * theta^(-i / (rope/2))
  ff       layer < first_k_dense_replace: (silu(x W_g) * x W_u) W_d
           else s = sigmoid(x W_r); the top_k of largest s + bias;
           weights routed_scale * s_picked / sum(s_picked);
           sum_e w_e expert_e(x) + shared(x), each a gated MLP
  head     one RMSNorm after the last block, logits = h W (untied)

Departures from the release, each stated: the rotation pairs (i, i +
rope/2) where the release interleaves (2i, 2i + 1): a fixed permutation
of the columns of W_qb and W_kva, indistinguishable on random weights;
every matrix starts normal with sigma 0.02 (the program's init; the
release's initializer_range is not among the keys); the release's
next-token-prediction layer is absent (it adds nothing to these
logits). `n_group` 1 and `topk_group` 1 make the grouped top-k a plain
one.

`forward(forced=)` makes the expert layers take, at the rows it
returns, experts handed in from outside in the place of their routers'
choice, and says how much worse than the router's own each choice is by
the router's own scores (`_route`: the regret). A model that rounds its
activations resolves a near-tie among a router's scores the other way
now and then and is then a model with one expert exchanged: held to
this reference made to take the same experts, with the regret held
beside it, it is compared row by row whatever its ties.

`latent_rows` is the first layer's cached rows `[c, rotated kr]` apart
from the model: what the rows a served model's programs left in its
pool are held against. A model that keeps its activations in less than
float32 rounds what its matrices take and give, so `latent_rows` takes
`handed_on`, a rounding applied to the block's norm, to W_kva's product
and to the finished row (c after its norm, kr after its rotation), and
to nothing inside the norm's statistics or the rotation, which the
configuration keeps float32. `router_case` and `picks` are the first
expert layer's router apart from the model, as
`reference/kimi_linear_ref.py` has them.
"""

from __future__ import annotations

import jax
import jax.numpy as jnp

F32 = jnp.float32


def _up(tree):
    return jax.tree_util.tree_map(lambda w: w.astype(F32), tree)


def _rms_norm(x, scale, eps):
    return x * jax.lax.rsqrt(
        jnp.mean(jnp.square(x), axis=-1, keepdims=True) + eps
    ) * scale


def _rope(x, pos, theta, rnd=lambda v: v):
    """x (..., T, R) or (B, T, H, R) with pos (T,): rotate-half. `rnd`
    rounds the angles, the factors and the products (the control that
    shows what a rotation in less than float32 would read)."""
    half = x.shape[-1] // 2
    inv = theta ** (-jnp.arange(half, dtype=F32) / half)
    ang = rnd(rnd(pos.astype(F32))[:, None] * rnd(inv)[None, :])  # (T, half)
    if x.ndim == 4:
        ang = ang[:, None, :]
    cos, sin = rnd(jnp.cos(ang)), rnd(jnp.sin(ang))
    a, b = x[..., :half], x[..., half:]
    return jnp.concatenate([
        rnd(rnd(a * cos) - rnd(b * sin)), rnd(rnd(b * cos) + rnd(a * sin)),
    ], -1)


def _gated_mlp(x, w_in, w_out):
    gate, up = jnp.split(x @ w_in.astype(F32), 2, axis=-1)
    return (jax.nn.silu(gate) * up) @ w_out.astype(F32)


def _mixer(x, p, arch):
    b, t, _ = x.shape
    h, rank, nope, rope, dv = (
        arch[k] for k in ("heads", "rank", "nope", "rope", "dv"))
    eps, pos = arch["eps"], jnp.arange(t)
    cq = _rms_norm(x @ p["w_qa"], p["q_norm"], eps)
    q = (cq @ p["w_qb"]).reshape(b, t, h, nope + rope)
    q = jnp.concatenate(
        [q[..., :nope], _rope(q[..., nope:], pos, arch["theta"])], -1)
    c_raw, kr = jnp.split(x @ p["w_kva"], [rank], axis=-1)
    c = _rms_norm(c_raw, p["kv_norm"], eps)
    kr = _rope(kr, pos, arch["theta"])
    kv = (c @ p["w_kvb"]).reshape(b, t, h, nope + dv)
    k = jnp.concatenate([
        kv[..., :nope], jnp.broadcast_to(kr[:, :, None, :], (b, t, h, rope)),
    ], -1)
    scores = jnp.einsum("bqhd,bkhd->bhqk", q, k) * (nope + rope) ** -0.5
    causal = pos[:, None] >= pos[None, :]
    scores = jnp.where(causal[None, None], scores, -jnp.inf)
    o = jnp.einsum(
        "bhqk,bkhd->bqhd", jax.nn.softmax(scores, axis=-1), kv[..., nope:])
    return o.reshape(b, t, h * dv) @ p["w_o"]


def _route(flat, router_w, bias, arch, router_dtype=F32, forced=None):
    """(expert ids (N, k), weights (N, k), regret (N,)) over all the
    experts. `router_dtype` other than float32 is the lower-precision
    control. `forced` ((N, k) expert ids, negative where the router's
    own choice stands) puts another choice in the place of the
    router's: the weights are these experts' own scores renormalised,
    and `regret` says how much worse than the router's own the choice
    is, the k-th largest of score + bias less the smallest among the
    experts taken: 0 for the router's own, the width of a near-tie for
    a choice that resolved one the other way."""
    k = arch["top_k"]
    scores = jax.nn.sigmoid(
        (flat.astype(router_dtype) @ router_w.astype(router_dtype))
        .astype(F32))
    biased = scores + bias.astype(F32)
    top, ids = jax.lax.top_k(biased, k)
    if forced is not None:
        ids = jnp.where(forced >= 0, forced, ids)
    picked = jnp.take_along_axis(scores, ids, axis=-1)
    weights = (arch["routed_scale"] * picked
               / jnp.sum(picked, axis=-1, keepdims=True))
    regret = top[:, -1] - jnp.min(
        jnp.take_along_axis(biased, ids, axis=-1), axis=-1)
    return ids, weights, regret


def _experts(x, p, bias, arch, forced=None):
    """The shared expert plus every routed expert's part, one expert's
    float32 copy at a time -> (result, the regret (B, T) of the experts
    taken: 0 unless `forced` (B, T, k) put others in the place of the
    router's own)."""
    b, t, d = x.shape
    flat = x.reshape(b * t, d)
    ids, weights, regret = _route(
        flat, p["router"]["w"], bias, arch,
        forced=None if forced is None else forced.reshape(b * t, -1))

    def add_expert(e, out):
        w_e = jnp.sum(jnp.where(ids == e, weights, 0.0), axis=-1)
        return out + w_e[:, None] * _gated_mlp(
            flat, p["experts"]["w_in"][e], p["experts"]["w_out"][e])

    # a loop the compiler keeps a loop: expert after expert
    out = jax.lax.fori_loop(
        0, p["experts"]["w_in"].shape[0], add_expert,
        _gated_mlp(flat, p["shared"]["w_in"], p["shared"]["w_out"]))
    return out.reshape(b, t, d), regret.reshape(b, t)


def forward(params, ids, *, arch, rows_from: int = 0, forced=None):
    """ids (B, T) -> float32 logits (B, T - rows_from, vocab) of the
    positions from `rows_from` on (the head alone skips the others: at
    the published widths a row of logits is 0.6 MB).

    `forced` (B, T - rows_from, expert layers, k) makes every expert
    layer take THESE experts at those positions in the place of its
    router's choice (the earlier positions keep the routers'), and the
    result is (logits, regret (B, T - rows_from, expert layers)): what
    a model that rounds its activations computes where it resolved a
    near-tie among a router's scores the other way, and how wide each
    such tie was by this router's own float32 scores (`_route`)."""
    regrets = []
    with jax.default_matmul_precision("highest"):
        x = params["stem"]["word"][ids].astype(F32)
        for i in range(len(params["blocks"])):
            p = params["blocks"][str(i)]
            x = x + _mixer(
                _rms_norm(x, p["norm1"].astype(F32), arch["eps"]),
                _up(p["mixer"]), arch)
            v = _rms_norm(x, p["norm2"].astype(F32), arch["eps"])
            if "router" in p["ffn"]:
                take = None
                if forced is not None:
                    own = jnp.full(
                        (*ids.shape, arch["top_k"]), -1, jnp.int32)
                    take = own.at[:, rows_from:].set(
                        forced[:, :, len(regrets)])
                part, regret = _experts(
                    v, p["ffn"], p["router_bias"], arch, take)
                x = x + part
                regrets.append(regret[:, rows_from:])
            else:
                x = x + _gated_mlp(v, p["ffn"]["w_in"], p["ffn"]["w_out"])
        head = params["head"]
        logits = _rms_norm(
            x[:, rows_from:], head["norm"].astype(F32), arch["eps"]
        ) @ head["w"].astype(F32)
    if forced is not None:
        return logits, jnp.stack(regrets, axis=-1)
    return logits


def latent_rows(params, ids, *, arch, handed_on=None, rotation=None):
    """The FIRST layer's cached rows of ids (B, T) from position 0:
    (B, T, rank + rope) float32, `[c, rotated kr]` (module doc).
    `rotation` is `_rope`'s `rnd`: the lower-precision control."""
    rnd = handed_on or (lambda v: v)
    with jax.default_matmul_precision("highest"):
        p = params["blocks"]["0"]
        m = _up(p["mixer"])
        x = rnd(_rms_norm(
            params["stem"]["word"][ids].astype(F32),
            p["norm1"].astype(F32), arch["eps"]))
        c_raw, kr = jnp.split(rnd(x @ m["w_kva"]), [arch["rank"]], axis=-1)
        c = _rms_norm(c_raw, m["kv_norm"], arch["eps"])
        kr = _rope(kr, jnp.arange(ids.shape[1]), arch["theta"],
                   rotation or (lambda v: v))
        return rnd(jnp.concatenate([c, kr], -1))


def router_case(params, ids, *, arch):
    """(rows (N, D), router weights (D, experts), bias (experts,)) of
    the FIRST expert layer, fed the normed embedding of these ids:
    unit-scale rows, as the layer's input is at initialisation."""
    layer = next(
        i for i in range(len(params["blocks"]))
        if "router" in params["blocks"][str(i)]["ffn"])
    p = params["blocks"][str(layer)]
    x = _rms_norm(params["stem"]["word"][ids].astype(F32),
                  p["norm2"].astype(F32), arch["eps"])
    return (x.reshape(-1, x.shape[-1]), p["ffn"]["router"]["w"],
            p["router_bias"])


def picks(flat, router_w, bias, *, arch, router_dtype=F32):
    """The chosen experts of each row, (N, k), in ascending id."""
    with jax.default_matmul_precision("highest"):
        ids, _, _ = _route(flat, router_w, bias, arch, router_dtype)
    return jnp.sort(ids, axis=-1)
