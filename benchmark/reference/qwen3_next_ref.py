"""The plain reference for the Qwen3-Next configurations: the forward
pass in straightforward float32 `jax.numpy`, every matrix product at
`highest` precision, the delta rule token by token (`lax.scan` over
positions), dense causal attention over the whole sequence, a loop over
the held routed experts with a dense product of ALL rows each (expert
after expert, one float32 copy at a time, so that at the published
widths it fits beside 8 GB of weights), no cache, no chunks, no
kernels, and nothing imported from the package under test: it takes
the same parameter pytree (whatever dtype it rests in: a layer's
weights are upcast as the layer is reached) and is otherwise
independent of it.

The equations (`arch` gives the sizes the tree's shapes do not):

  norm      x / rms(x) * (1 + w)  (zero-centred), eps
  block     h = x + mixer(norm(x));  y = h + moe(norm(h))
  gdn       [q k v z] = x W_qkvz (q, k: hk heads of dk; v, z: hv of dv)
            [b a] = x W_ba;  [q k v] = silu(causal depthwise conv, K taps)
            q, k repeated to hv heads (head j reads key head j // (hv/hk))
            q = l2norm(q) / sqrt(dk);  k = l2norm(k)
            beta = sigmoid(b);  g = -exp(A_log) * softplus(a + dt_bias)
            S_t = e^g_t S_(t-1) + beta_t k_t (v_t - e^g_t S_(t-1)^T k_t)^T
            o_t = S_t^T q_t;  o = rmsnorm(o) * w * silu(z);  W_out
  attention [q gate] = x W_q (per head: head_dim of query, then of gate)
            k, v = x W_k, x W_v;  q, k = norm(q), norm(k) per head
            the first `rot` values of q and k rotated (rotate-half,
            angle_i = pos * theta^(-i / (rot/2)), value i with i + rot/2)
            causal softmax(q k^T * head_dim^-1/2) v (query head j reads
            cached head j // group);  o * sigmoid(gate);  W_o
  moe       p = softmax(x W_r) over ALL experts;  the top_k of largest p,
            renormalised to sum 1;  sum over the HELD experts e picked
            of w_e expert_e(x) + sigmoid(x w_sg) shared(x), each a
            SiLU-gated MLP
  head      norm after the last block, logits = h W (untied)

Departures from the release, each stated in the configuration's
`assumed`: W_qkvz and W_ba take their columns contiguous ([q k v z],
[b a]) where the release groups them by key head: a fixed permutation
of the columns, indistinguishable on random weights; every matrix
starts normal with sigma 0.02; the next-token-prediction layer is
absent (it adds nothing to these logits).

`forward(forced=)` makes the expert layers take, at every position,
experts handed in from outside in the place of their routers' choice, and says how much worse than the router's own each choice is by
the router's own probabilities (`_route`: the regret), as
`reference/glm_moe_ref.py` does.

`first_gdn` is the first delta-rule layer apart from the model: its
state S and its convolution's kept inputs after a sequence, what a
served model's programs left in its pool is held against. A model that
keeps its activations in less than float32 rounds what its matrices
take, so it takes `handed_on`, a rounding applied to the embedding and
to the block's norm, which W_qkvz and W_ba multiply, and to nothing
after them: the configuration keeps the products and everything from
them to the recurrence float32. `state_dtype` keeps the recurrence's
state in another dtype (the control). `attention_keys` is the first attention layer's cached
keys (rotated) from the whole forward pass; `rotation` rounds the
rotation's angles, factors and products (the control). `router_case`,
`picks` and `first_experts` are the first layer's router and expert
layer apart from the model.
"""

from __future__ import annotations

import jax
import jax.numpy as jnp

F32 = jnp.float32


def _up(tree):
    return jax.tree_util.tree_map(lambda w: w.astype(F32), tree)


def _plain(v):
    return v


def _rms(x, eps):
    return x * jax.lax.rsqrt(
        jnp.mean(jnp.square(x), axis=-1, keepdims=True) + eps)


def _norm(x, w, eps):
    return _rms(x, eps) * (1.0 + w.astype(F32))


def _rope(x, pos, theta, rot, rnd=_plain):
    """x (B, T, H, Dh), pos (T,): the first `rot` values rotated
    (rotate-half), the rest as they are. `rnd` rounds the positions,
    angles, factors and products (the control)."""
    half = rot // 2
    inv = theta ** (-jnp.arange(half, dtype=F32) / half)
    ang = rnd(rnd(pos.astype(F32))[:, None] * rnd(inv)[None, :])
    cos, sin = rnd(jnp.cos(ang))[:, None, :], rnd(jnp.sin(ang))[:, None, :]
    a, b = x[..., :half], x[..., half:rot]
    return jnp.concatenate([
        rnd(rnd(a * cos) - rnd(b * sin)), rnd(rnd(b * cos) + rnd(a * sin)),
        x[..., rot:],
    ], -1)


def _gated_mlp(x, w_in, w_out):
    gate, up = jnp.split(x @ w_in.astype(F32), 2, axis=-1)
    return (jax.nn.silu(gate) * up) @ w_out.astype(F32)


def _unit(y):
    return y * jax.lax.rsqrt(jnp.sum(y * y, axis=-1, keepdims=True) + 1e-6)


def delta_rule(q, k, v, g, beta, state_dtype=F32):
    """Token by token from a zero state: q, k (B, T, H, dk), v (B, T, H,
    dv), g, beta (B, T, H) -> (outputs (B, T, H, dv), S after the last
    position (B, H, dk, dv) float32). `state_dtype` keeps S in it."""
    b, _, h, dk = q.shape

    def step(s, at):
        qt, kt, vt, gt, bt = at
        s = (jnp.exp(gt)[..., None, None] * s.astype(F32))
        read = jnp.einsum("bhk,bhkv->bhv", kt, s)
        s = s + (bt[..., None] * kt)[..., None] * (vt - read)[..., None, :]
        s = s.astype(state_dtype)
        return s, jnp.einsum("bhk,bhkv->bhv", qt, s.astype(F32))

    over_time = lambda x: jnp.swapaxes(x, 0, 1)
    s, out = jax.lax.scan(
        step, jnp.zeros((b, h, dk, v.shape[-1]), state_dtype),
        tuple(over_time(x) for x in (q, k, v, g, beta)))
    return over_time(out), s.astype(F32)


def _gdn_inputs(x, p, arch):
    """A delta-rule mixer up to its recurrence -> (q, k, v, g, beta, z,
    the convolution's last K - 1 inputs (B, K - 1, channels))."""
    b, t, _ = x.shape
    hk, hv, dk, dv = (arch[n] for n in ("hk", "hv", "dk", "dv"))
    taps = p["conv_w"].shape[0]
    qkv, z = jnp.split(x @ p["w_qkvz"], [2 * hk * dk + hv * dv], -1)
    beta_in, a = jnp.split(x @ p["w_ba"], 2, axis=-1)
    padded = jnp.pad(qkv, ((0, 0), (taps - 1, 0), (0, 0)))
    c = jax.nn.silu(sum(
        padded[:, i:i + t] * p["conv_w"][i] for i in range(taps)))
    q, k, v = jnp.split(c, [hk * dk, 2 * hk * dk], axis=-1)
    group = lambda y: jnp.repeat(y.reshape(b, t, hk, dk), hv // hk, axis=2)
    q = _unit(group(q)) * dk ** -0.5
    k = _unit(group(k))
    g = -jnp.exp(p["a_log"]) * jax.nn.softplus(a + p["dt_bias"])
    return (q, k, v.reshape(b, t, hv, dv), g, jax.nn.sigmoid(beta_in),
            z.reshape(b, t, hv, dv), padded[:, -(taps - 1):])


def _gdn(x, p, arch, state_dtype=F32):
    q, k, v, g, beta, z, _ = _gdn_inputs(x, p, arch)
    o, _ = delta_rule(q, k, v, g, beta, state_dtype)
    o = _rms(o, arch["eps"]) * p["o_norm"] * jax.nn.silu(z)
    b, t = x.shape[:2]
    return o.reshape(b, t, -1) @ p["w_out"]


def _attention_qkv(x, p, arch, rnd=_plain):
    b, t, _ = x.shape
    h, hkv, dh = arch["heads"], arch["kv_heads"], arch["head_dim"]
    pos = jnp.arange(t)
    q, gate = jnp.split((x @ p["w_q"]).reshape(b, t, h, 2 * dh), 2, -1)
    q = _norm(q, p["q_norm"], arch["eps"])
    k = _norm((x @ p["w_k"]).reshape(b, t, hkv, dh), p["k_norm"], arch["eps"])
    v = (x @ p["w_v"]).reshape(b, t, hkv, dh)
    rope = lambda y: _rope(y, pos, arch["theta"], arch["rot"], rnd)
    return rope(q), rope(k), v, gate


def _attention(x, p, arch):
    b, t, _ = x.shape
    h, hkv, dh = arch["heads"], arch["kv_heads"], arch["head_dim"]
    q, k, v, gate = _attention_qkv(x, p, arch)
    k, v = (jnp.repeat(y, h // hkv, axis=2) for y in (k, v))
    scores = jnp.einsum("bqhd,bkhd->bhqk", q, k) * dh ** -0.5
    causal = jnp.arange(t)[:, None] >= jnp.arange(t)[None, :]
    scores = jnp.where(causal[None, None], scores, -jnp.inf)
    o = jnp.einsum("bhqk,bkhd->bqhd", jax.nn.softmax(scores, axis=-1), v)
    return (o * jax.nn.sigmoid(gate)).reshape(b, t, h * dh) @ p["w_o"]


def _route(flat, router_w, arch, router_dtype=F32, forced=None):
    """(expert ids (N, k), weights (N, k), regret (N,)) over ALL the
    experts: softmax probabilities, the top_k largest renormalised to
    sum 1. `router_dtype` other than float32 is the control. `forced`
    ((N, k) ids, negative where the router's own choice stands) puts
    another choice in the router's place, weighted by those experts'
    own probabilities renormalised; `regret` is the k-th largest
    probability less the smallest among the experts taken (0 for the
    router's own choice)."""
    probs = jax.nn.softmax(
        (flat.astype(router_dtype) @ router_w.astype(router_dtype))
        .astype(F32), axis=-1)
    top, ids = jax.lax.top_k(probs, arch["top_k"])
    if forced is not None:
        ids = jnp.where(forced >= 0, forced, ids)
    picked = jnp.take_along_axis(probs, ids, axis=-1)
    regret = top[:, -1] - jnp.min(picked, axis=-1)
    return ids, picked / jnp.sum(picked, axis=-1, keepdims=True), regret


def _moe(x, p, arch, forced=None):
    """The gated shared expert plus the HELD routed experts' part, one
    expert's float32 copy at a time -> (result, regret (B, T))."""
    b, t, d = x.shape
    first = arch["experts_held"][0]
    flat = x.reshape(b * t, d)
    ids, weights, regret = _route(
        flat, p["router"]["w"], arch,
        forced=None if forced is None else forced.reshape(b * t, -1))
    shared = _gated_mlp(flat, p["shared"]["w_in"], p["shared"]["w_out"])
    shared = shared * jax.nn.sigmoid(
        flat @ p["shared_gate"]["w"].astype(F32))

    def add_expert(e, out):
        w_e = jnp.sum(jnp.where(ids == first + e, weights, 0.0), axis=-1)
        return out + w_e[:, None] * _gated_mlp(
            flat, p["experts"]["w_in"][e], p["experts"]["w_out"][e])

    # a loop the compiler keeps a loop: expert after expert
    out = jax.lax.fori_loop(
        0, p["experts"]["w_in"].shape[0], add_expert, shared)
    return out.reshape(b, t, d), regret.reshape(b, t)


def _small(p):
    """A block's weights but the routed experts', upcast."""
    return {
        **{k: _up(v) for k, v in p.items() if k != "ffn"},
        "ffn": {**{k: _up(v) for k, v in p["ffn"].items()
                   if k != "experts"}, "experts": p["ffn"]["experts"]},
    }


def forward(params, ids, *, arch, rows_from: int = 0, forced=None):
    """ids (B, T) -> float32 logits (B, T - rows_from, vocab) of the
    positions from `rows_from` on. `forced` (B, T, layers, k) makes every
    expert layer take THESE experts at every position (negative ids:
    the router's own), and the result is (logits, regret (B, T -
    rows_from, layers)): every position, since a recurrence whose decay
    is strong reads the last few tokens' layers closely."""
    regrets = []
    eps = arch["eps"]
    with jax.default_matmul_precision("highest"):
        x = params["stem"]["word"][ids].astype(F32)
        for i in range(len(params["blocks"])):
            p = _small(params["blocks"][str(i)])
            inner = _norm(x, p["norm1"], eps)
            x = x + (
                _attention(inner, p["mixer"], arch)
                if "w_q" in p["mixer"] else _gdn(inner, p["mixer"], arch)
            )
            part, regret = _moe(_norm(x, p["norm2"], eps), p["ffn"], arch,
                                None if forced is None else forced[:, :, i])
            x = x + part
            regrets.append(regret[:, rows_from:])
        head = params["head"]
        logits = _norm(x[:, rows_from:], head["norm"], eps) @ head["w"].astype(
            F32)
    if forced is not None:
        return logits, jnp.stack(regrets, axis=-1)
    return logits


def first_gdn(params, ids, *, arch, handed_on=_plain, state_dtype=F32):
    """The FIRST layer (a delta-rule layer) after ids (B, T) from a zero
    state: {"s": S (B, hv, dk, dv), "conv": the convolution's last K - 1
    inputs (B, K - 1, channels)}, float32 (module doc)."""
    with jax.default_matmul_precision("highest"):
        p = _up(params["blocks"]["0"])
        if "w_qkvz" not in p["mixer"]:
            raise NotImplementedError("the first layer is not a delta-rule "
                                      "layer")
        x = params["stem"]["word"][ids].astype(F32)
        q, k, v, g, beta, _, kept = _gdn_inputs(
            handed_on(_norm(handed_on(x), p["norm1"], arch["eps"])),
            p["mixer"], arch)
        _, s = delta_rule(q, k, v, g, beta, state_dtype)
        return {"s": s, "conv": kept}


def attention_keys(params, ids, *, arch, rotation=_plain):
    """The FIRST attention layer's keys of ids (B, T) from position 0,
    rotated: (B, T, kv_heads, head_dim) float32. `rotation` rounds the
    rotation (the control)."""
    eps = arch["eps"]
    with jax.default_matmul_precision("highest"):
        x = params["stem"]["word"][ids].astype(F32)
        for i in range(len(params["blocks"])):
            p = _small(params["blocks"][str(i)])
            inner = _norm(x, p["norm1"], eps)
            if "w_q" in p["mixer"]:
                return _attention_qkv(inner, p["mixer"], arch, rotation)[1]
            x = x + _gdn(inner, p["mixer"], arch)
            x = x + _moe(_norm(x, p["norm2"], eps), p["ffn"], arch)[0]
    raise NotImplementedError("no attention layer")


def first_experts(params, rows, forced, *, arch):
    """The FIRST layer's expert layer on rows (N, D), made to take the
    experts `forced` (N, k): (N, D) float32."""
    with jax.default_matmul_precision("highest"):
        ffn = _small(params["blocks"]["0"])["ffn"]
        return _moe(rows[None].astype(F32), ffn, arch, forced[None])[0][0]


def picks(flat, router_w, *, arch, router_dtype=F32):
    """The chosen experts of each row, (N, k), in ascending id."""
    with jax.default_matmul_precision("highest"):
        ids, _, _ = _route(flat, router_w, arch, router_dtype)
    return jnp.sort(ids, axis=-1)
