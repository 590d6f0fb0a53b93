"""The plain reference for the Kimi-Linear configuration: forward pass
and next-token loss in straightforward float32 `jax.numpy`, every
matrix product at `highest` precision (on a TPU a float32 product
otherwise runs in one bf16 pass). No kernel, no chunked algebra, no
sorted rows, and nothing imported from the package under test: it takes
the program's parameter tree and is otherwise independent of it.

What it follows is the release's description (`config.json` of
moonshotai/Kimi-Linear-48B-A3B-Instruct and the model card's layer
equations): pre-norm layers `h = x + Mix(RMSNorm(x))`, `y = h +
FFN(RMSNorm(h))`, a final RMSNorm, an untied head, no positions; KDA
mixers (the gated delta rule with a decay per channel, run here TOKEN
BY TOKEN) and latent-attention mixers by the published layer lists; a
dense SiLU-gated MLP in the leading layers and one shared plus routed
experts after them. Each departure from that description is a comment
at its line; all of them are listed in the configuration file under
`assumed`.

Only memory shapes what is blocked: the recurrence is a scan over
blocks of tokens whose inner scan is rematerialised, attention takes
its queries a block at a time, and the head and the loss take one
sequence at a time, so that a batch of 2 x 8,192 fits beside the
training state it is compared with; each layer and each block of
queries is rematerialised when the loss is differentiated
(`jax.checkpoint` changes no value), so that `jax.grad` of it fits too.
"""

from __future__ import annotations

import jax
import jax.numpy as jnp
from jax import lax

TIME_BLOCK = 64      # tokens per rematerialised block of the recurrence
QUERY_BLOCK = 256    # queries per block of the attention


def _rms_norm(x, scale, eps):
    return x * lax.rsqrt(jnp.mean(x * x, axis=-1, keepdims=True) + eps) * scale


def _gated_mlp(x, w):
    # `w_in` holds the gate's columns, then the up projection's (the
    # release stores gate_proj and up_proj apart: a layout, not a
    # departure).
    gate, up = jnp.split(x @ w["w_in"], 2, axis=-1)
    return (jax.nn.silu(gate) * up) @ w["w_out"]


def _causal_conv(x, w):
    """Depthwise, causal, width K: y_t = sum_i w_i x_(t-K+1+i). The
    release's short convolution has no bias."""
    k, t = w.shape[0], x.shape[1]
    padded = jnp.pad(x, ((0, 0), (k - 1, 0), (0, 0)))
    return sum(padded[:, i:i + t] * w[i] for i in range(k))


def _delta_rule(q, k, v, g, beta, state_dtype=jnp.float32):
    """S_t = (I - b_t k_t k_t^T) Diag(exp g_t) S_(t-1) + b_t k_t v_t^T,
    o_t = S_t^T q_t, one token a step from S = 0. (B, T, H, .) in,
    (B, T, H, dv) out. `state_dtype` other than float32 is the
    lower-precision control."""
    b, t, h, dk = q.shape
    dv = v.shape[-1]
    pad = -t % TIME_BLOCK
    n = (t + pad) // TIME_BLOCK

    def blocks(x):
        # tail tokens that neither decay (g = 0) nor write (beta = 0)
        x = jnp.pad(x, [(0, 0), (0, pad)] + [(0, 0)] * (x.ndim - 2))
        x = jnp.moveaxis(x, 1, 0)                       # (T, B, H, ...)
        return x.reshape((n, TIME_BLOCK) + x.shape[1:])

    def token(s, x):
        qt, kt, vt, gt, bt = x
        s = jnp.exp(gt)[..., None] * s.astype(jnp.float32)
        read = jnp.einsum("bhk,bhkv->bhv", kt, s)
        s = s + (bt[..., None] * kt)[..., None] * (vt - read)[..., None, :]
        return s.astype(state_dtype), jnp.einsum("bhk,bhkv->bhv", qt, s)

    block = jax.checkpoint(lambda s, xs: lax.scan(token, s, xs))
    _, out = lax.scan(
        block, jnp.zeros((b, h, dk, dv), state_dtype),
        tuple(blocks(x) for x in (q, k, v, g, beta)),
    )
    return jnp.moveaxis(out.reshape((n * TIME_BLOCK,) + out.shape[2:]),
                        0, 1)[:, :t]


def _kda_inputs(x, p, arch):
    """What the mixer hands its delta rule: q, k, v, the log decay g
    (B, T, H, dh) and the write strength beta (B, T, H)."""
    b, t, _ = x.shape
    h, dh = arch["kda_num_heads"], arch["kda_head_dim"]
    heads = lambda y: y.reshape(b, t, h, dh)
    q, k, v = (
        heads(jax.nn.silu(_causal_conv(x @ p["w_" + n], p["conv_" + n])))
        for n in "qkv"
    )
    # L2 normalisation with the reference kernels' 1e-6 under the root
    # (the config states none).
    unit = lambda y: y * lax.rsqrt(
        jnp.sum(y * y, axis=-1, keepdims=True) + 1e-6
    )
    q, k = unit(q) * dh ** -0.5, unit(k)
    # Decay per channel through a low-rank map whose rank is the head's
    # width (the config gives no rank).
    g = -jnp.exp(p["a_log"])[:, None] * heads(
        jax.nn.softplus((x @ p["f_down"]) @ p["f_up"] + p["dt_bias"])
    )
    return q, k, v, g, jax.nn.sigmoid(x @ p["w_beta"])


def _kda(x, p, arch, state_dtype):
    b, t, _ = x.shape
    h, dh = arch["kda_num_heads"], arch["kda_head_dim"]
    heads = lambda y: y.reshape(b, t, h, dh)
    o = _delta_rule(*_kda_inputs(x, p, arch), state_dtype)
    gate = jax.nn.sigmoid(heads((x @ p["g_down"]) @ p["g_up"]))
    o = _rms_norm(o, p["o_norm"], arch["rms_norm_eps"]) * gate
    return o.reshape(b, t, h * dh) @ p["w_o"]


def _mla(x, p, arch):
    b, t, _ = x.shape
    h = arch["num_attention_heads"]
    nope, pe, dv, rank = (arch[k] for k in (
        "qk_nope_head_dim", "qk_rope_head_dim", "v_head_dim", "kv_lora_rank"
    ))
    q = (x @ p["w_q"]).reshape(b, t, h, nope + pe)      # q_lora_rank null
    c, k_pe = jnp.split(x @ p["w_kva"], [rank], axis=-1)
    kv = (_rms_norm(c, p["kv_norm"], arch["rms_norm_eps"]) @ p["w_kvb"])
    kv = kv.reshape(b, t, h, nope + dv)
    # mla_use_nope: the "rope" part of q and k is NOT rotated; k's is
    # one vector shared by all heads.
    k = jnp.concatenate([
        kv[..., :nope], jnp.broadcast_to(k_pe[:, :, None], (b, t, h, pe))
    ], axis=-1)
    v = kv[..., nope:]
    block = min(QUERY_BLOCK, t)
    if t % block:
        block = t
    cols = jnp.arange(t)

    def attend(start):
        rows = start + jnp.arange(block)
        qb = lax.dynamic_slice_in_dim(q, start, block, axis=1)
        s = jnp.einsum("bqhd,bkhd->bhqk", qb, k) * (nope + pe) ** -0.5
        s = jnp.where(rows[:, None] >= cols[None, :], s, -jnp.inf)
        return jnp.einsum("bhqk,bkhd->bqhd", jax.nn.softmax(s, axis=-1), v)

    o = lax.map(jax.checkpoint(attend), jnp.arange(0, t, block))
    o = jnp.moveaxis(o, 0, 1).reshape(b, t, h * dv)
    return o @ p["w_o"]


def _route(flat, router_w, arch, router_bias, router_dtype):
    """(expert ids (N, k), weights (N, k)) of flat (N, D) over ALL the
    router's experts. `router_dtype` other than float32 is the
    lower-precision control."""
    scores = jax.nn.sigmoid(
        (flat.astype(router_dtype) @ router_w.astype(router_dtype))
        .astype(jnp.float32)
    )
    # The correction bias steers the choice alone; the config gives no
    # rule to update it, so it stays at its initial zero unless the
    # caller hands one in. One group (`num_expert_group` 1): the
    # grouped top-k is a plain top-k.
    _, ids = lax.top_k(scores + router_bias, arch["num_experts_per_token"])
    picked = jnp.take_along_axis(scores, ids, axis=-1)
    # moe_renormalize over all chosen, absent or not, times the factor
    return ids, (arch["routed_scaling_factor"] * picked
                 / jnp.sum(picked, axis=-1, keepdims=True))


def _experts(x, p, arch, router_bias, router_dtype):
    """One shared expert plus the held routed experts' part."""
    b, t, d = x.shape
    flat = x.reshape(b * t, d)
    first, past = arch["experts_held"]
    ids, weights = _route(
        flat, p["router"]["w"], arch, router_bias, router_dtype)
    out = _gated_mlp(flat, p["shared"])
    for e in range(first, past):
        # What an expert this chip does not hold would have added is
        # left out: the chips that hold it add that part.
        w_e = jnp.sum(jnp.where(ids == e, weights, 0.0), axis=-1)
        out = out + w_e[:, None] * _gated_mlp(flat, {
            "w_in": p["experts"]["w_in"][e - first],
            "w_out": p["experts"]["w_out"][e - first],
        })
    return out.reshape(b, t, d)


def hidden(params, ids, arch, *, router_bias=None,
           state_dtype=jnp.float32, router_dtype=jnp.float32):
    """ids (B, T) -> the last layer's output before the final norm."""
    eps = arch["rms_norm_eps"]

    def one_layer(layer, x, p, bias):                   # lists are 1-based
        normed = _rms_norm(x, p["norm1"], eps)
        if layer in arch["kda_layers"]:
            x = x + _kda(normed, p["mixer"], arch, state_dtype)
        else:
            x = x + _mla(normed, p["mixer"], arch)
        normed = _rms_norm(x, p["norm2"], eps)
        if layer <= arch["first_k_dense_replace"]:
            return x + _gated_mlp(normed, p["ffn"])
        return x + _experts(normed, p["ffn"], arch, bias, router_dtype)

    x = params["stem"]["word"][ids]                     # no positions
    for i in range(arch["num_hidden_layers"]):
        bias = 0.0 if router_bias is None else router_bias[str(i)]
        x = jax.checkpoint(one_layer, static_argnums=0)(
            i + 1, x, params["blocks"][str(i)], bias)
    return x


def forward(params, ids, arch, **control):
    """ids (B, T) int -> logits (B, T, vocab held) float32."""
    with jax.default_matmul_precision("highest"):
        x = hidden(params, ids, arch, **control)
        x = _rms_norm(x, params["head"]["norm"], arch["rms_norm_eps"])
        return x @ params["head"]["w"]


def next_token_loss(params, ids, arch, **control):
    """(sum over positions of -log p(ids[t+1] | ids[:t+1]), positions):
    every position but the last of each sequence predicts its
    successor. The vocabulary is the slice this chip holds."""
    with jax.default_matmul_precision("highest"):
        x = hidden(params, ids, arch, **control)
        x = _rms_norm(x, params["head"]["norm"], arch["rms_norm_eps"])

        def one(args):
            xs, seq = args
            logp = jax.nn.log_softmax(xs[:-1] @ params["head"]["w"], axis=-1)
            return -jnp.sum(
                jnp.take_along_axis(logp, seq[1:, None], axis=-1)
            )

        total = jnp.sum(lax.map(one, (x, ids)))
    return total, ids[:, 1:].size


# ------------------------------------------------ the stated precision
#
# What the configuration's `precision` block states in float32 inside a
# bfloat16 step — the delta rule's decay, state and solve, the router's
# scores — cannot be told from the step's loss: bfloat16 activations
# move it more. The pieces below let the cell's comparison hand the
# SAME inputs to the program's float32 part and to this file's, at the
# sizes the cell times (`builders/kimi_linear.precision_readings`).


def recurrence_case(params, ids, arch):
    """(q, k, v, g, beta), float32, as the FIRST layer's mixer makes
    them from this batch with these parameters (its input is the normed
    embedding; the published pattern starts with a KDA layer)."""
    if 1 not in arch["kda_layers"]:
        raise ValueError("the first layer is no KDA layer")
    p = params["blocks"]["0"]
    with jax.default_matmul_precision("highest"):
        x = _rms_norm(params["stem"]["word"][ids], p["norm1"],
                      arch["rms_norm_eps"])
        return _kda_inputs(x, p["mixer"], arch)


def recurrence(q, k, v, g, beta, state_dtype=jnp.float32):
    """The delta rule token by token, (B, T, H, dv) float32."""
    with jax.default_matmul_precision("highest"):
        return _delta_rule(q, k, v, g, beta, state_dtype)


def router_case(params, ids, arch):
    """(rows (N, D), router weights (D, experts)) of the FIRST expert
    layer, fed the normed embedding of this batch: unit-scale rows, as
    the layer's input is at initialisation."""
    p = params["blocks"][str(arch["first_k_dense_replace"])]
    x = _rms_norm(params["stem"]["word"][ids], p["norm2"],
                  arch["rms_norm_eps"])
    return x.reshape(-1, x.shape[-1]), p["ffn"]["router"]["w"]


def picks(flat, router_w, arch, router_dtype=jnp.float32):
    """The chosen experts of each row, (N, k), in ascending id."""
    with jax.default_matmul_precision("highest"):
        ids, _ = _route(flat, router_w, arch, 0.0, router_dtype)
    return jnp.sort(ids, axis=-1)
