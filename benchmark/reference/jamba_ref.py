"""The plain reference for the Jamba configurations: the forward pass in
straightforward float32 `jax.numpy`, every matrix product at `highest`
precision, the state-space recurrence token by token (`lax.scan` over
positions), dense causal attention, no cache, no chunks, no kernels,
and nothing imported from the package under test: it takes the same
parameter pytree (whatever dtype it rests in: a layer's weights are
upcast as the layer is reached, never the whole tree at once) and is
otherwise independent of it.

The equations (sizes from the tree's own shapes; d_in, N, R, K below):

  block      h = x + mixer(RMSNorm(x));  y = h + mlp(RMSNorm(h))
  mlp        (silu(v W_gate) * (v W_up)) W_down, no biases
  attention  q = x W_q (`num_heads` heads), k, v = x W_k, x W_v
             (`num_kv_heads` heads: query head j reads head j // group),
             causal softmax(q k^T / sqrt(head width)) v, W_o; no biases,
             no rotation, no window
  mamba      [u, z] = x W_in;  c_t = silu(b_conv + sum_k w_conv[k] u_(t-K+1+k))
             [dt, B, C] = c W_x, an RMSNorm with its own scale on each
             delta = softplus(dt W_dt + b_dt);  A = -exp(A_log)
             h_t = exp(delta_t (x) A) * h_(t-1) + (delta_t c_t) (x) B_t
             y_t = h_t C_t + D * c_t;  output (y * silu(z)) W_out
  head       one RMSNorm after the last block, logits = h E^T against
             the token embedding E itself (tied)

A layer is a Mamba layer iff its mixer holds `w_in`. The tree keeps
`A_log` and the convolution's taps with the channel axis last ((N, d_in)
and (K, d_in)); here the state is (d_in, N). Departures from the
published model: none known.

`recurrence_case` and `recurrence` are the first Mamba layer's
recurrence apart from the model: what a served model's state after a
prompt is held against when the question is the recurrence's own
precision. A model that keeps its activations in less than float32 has
moved that layer's inputs by more than a rounded state would move its
state, so `recurrence_case` takes `handed_on`, a rounding applied to
what a matrix product takes and gives (the block's norm and W_in's
product, c into W_x and its product, dt's norm into W_dt) and to
nothing of the recurrence, which the configuration keeps float32 (c,
delta, B and C reach it as they are made): the inputs a model of that
precision computes, by these equations and no code of the model's.
`state_dtype` computes the recurrence's state, its step sizes and its
factors in another dtype (the control that shows what a lower precision
than the configuration states would read); everything else stays
float32.
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


def _attention(x, p, num_heads: int, num_kv_heads: int):
    b, t, d = x.shape
    dh = p["w_q"].shape[1] // num_heads
    group = num_heads // num_kv_heads
    q = (x @ p["w_q"]).reshape(b, t, num_heads, dh)
    k = (x @ p["w_k"]).reshape(b, t, num_kv_heads, dh)
    v = (x @ p["w_v"]).reshape(b, t, num_kv_heads, dh)
    k, v = jnp.repeat(k, group, axis=2), jnp.repeat(v, group, axis=2)
    scores = jnp.einsum("bqhd,bkhd->bhqk", q, k) / jnp.sqrt(F32(dh))
    causal = jnp.arange(t)[:, None] >= jnp.arange(t)[None, :]
    scores = jnp.where(causal[None, None], scores, -jnp.inf)
    out = jnp.einsum("bhqk,bkhd->bqhd", jax.nn.softmax(scores, axis=-1), v)
    return out.reshape(b, t, num_heads * dh) @ p["w_o"]


def _recurrence_inputs(x, p, eps, handed_on=lambda v: v):
    """A Mamba mixer up to its recurrence: (c, z, delta, B, C, A) with
    c, z, delta (B, T, d_in), B, C (B, T, N), A (d_in, N). `handed_on`
    rounds what a matrix product takes and gives (module docstring)."""
    t = x.shape[1]
    taps = p["conv_w"].shape[0]
    n, r = p["a_log"].shape[0], p["w_dt"].shape[0]
    u, z = jnp.split(handed_on(x @ p["w_in"]), 2, axis=-1)
    padded = jnp.pad(u, ((0, 0), (taps - 1, 0), (0, 0)))
    c = jax.nn.silu(p["conv_b"] + sum(
        padded[:, k:k + t] * p["conv_w"][k] for k in range(taps)
    ))
    dt, bm, cm = jnp.split(
        handed_on(handed_on(c) @ p["w_x"]), [r, r + n], axis=-1)
    dt = handed_on(_rms_norm(dt, p["dt_norm"], eps))
    bm = _rms_norm(bm, p["b_norm"], eps)
    cm = _rms_norm(cm, p["c_norm"], eps)
    delta = jax.nn.softplus(dt @ p["w_dt"] + p["dt_bias"])
    return c, z, delta, bm, cm, -jnp.exp(p["a_log"]).T


def recurrence(c, delta, bm, cm, a, state_dtype=F32):
    """The selective recurrence token by token from a zero state:
    h_t = exp(delta_t (x) A) * h_(t-1) + (delta_t c_t) (x) B_t, y_t =
    h_t C_t. -> (y (B, T, d_in), h after the last position (B, d_in,
    N)). `state_dtype` keeps the state, delta and the factors in it."""
    delta = delta.astype(state_dtype)

    def step(h, at):
        delta_t, c_t, b_t, c_out = at
        factor = jnp.exp(
            delta_t.astype(F32)[:, :, None] * a[None]
        ).astype(state_dtype)
        push = (delta_t.astype(F32) * c_t)[:, :, None] * b_t[:, None, :]
        h = (factor.astype(F32) * h.astype(F32) + push).astype(state_dtype)
        return h, jnp.einsum("bdn,bn->bd", h.astype(F32), c_out)

    over_time = lambda v: jnp.swapaxes(v, 0, 1)
    h, y = jax.lax.scan(
        step, jnp.zeros((c.shape[0], *a.shape), state_dtype),
        (over_time(delta), over_time(c), over_time(bm), over_time(cm)),
    )
    return over_time(y), h.astype(F32)


def _mamba(x, p, eps, state_dtype):
    """-> (the mixer's output (B, T, d), the state after the last
    position (B, d_in, N))."""
    c, z, delta, bm, cm, a = _recurrence_inputs(x, p, eps)
    y, h = recurrence(c, delta, bm, cm, a, state_dtype)
    y = y + p["d"] * c
    return (y * jax.nn.silu(z)) @ p["w_out"], h


def recurrence_case(params, ids, *, eps: float, handed_on=lambda v: v,
                    **_):
    """The inputs of the FIRST layer's recurrence on `ids` (B, T):
    (c, delta, B, C, A), as a model computes them that rounds its
    activations by `handed_on` (module docstring)."""
    with jax.default_matmul_precision("highest"):
        x = handed_on(params["stem"]["word"].astype(F32)[ids])
        p = _up(params["blocks"]["0"])
        if "w_in" not in p["mixer"]:
            raise NotImplementedError(
                "the first layer is not a Mamba layer: run the layers "
                "before it first")
        c, _, delta, bm, cm, a = _recurrence_inputs(
            handed_on(_rms_norm(x, p["norm1"], eps)), p["mixer"], eps,
            handed_on)
        return c, delta, bm, cm, a


def forward_with_states(params, ids, *, num_heads: int, num_kv_heads: int,
                        eps: float, state_dtype=F32):
    """ids (B, T) int -> (logits (B, T, vocab) float32, {layer index:
    that Mamba layer's state after the last position, (B, d_in, N)})."""
    with jax.default_matmul_precision("highest"):
        word = params["stem"]["word"].astype(F32)
        x = word[ids]
        states = {}
        for i in range(len(params["blocks"])):
            p = _up(params["blocks"][str(i)])
            inner = _rms_norm(x, p["norm1"], eps)
            if "w_in" in p["mixer"]:
                mixed, states[i] = _mamba(inner, p["mixer"], eps, state_dtype)
            else:
                mixed = _attention(inner, p["mixer"], num_heads, num_kv_heads)
            x = x + mixed
            v = _rms_norm(x, p["norm2"], eps)
            m = p["mlp"]
            x = x + (jax.nn.silu(v @ m["w_gate"]) * (v @ m["w_up"])) @ m["w_down"]
        x = _rms_norm(x, params["head"]["norm"].astype(F32), eps)
        return x @ word.T, states


def forward(params, ids, **args):
    """ids (B, T) int -> logits (B, T, vocab) float32."""
    return forward_with_states(params, ids, **args)[0]
