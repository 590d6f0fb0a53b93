"""The plain reference for the GPT-2 configurations: forward pass and
next-token loss in straightforward float32 `jax.numpy`, every matrix
product at `highest` precision (on a TPU a float32 product otherwise
runs in one bf16 pass). No kernels, no cache, no batching tricks, and
nothing imported from the package under test: it takes the same
parameter pytree and is otherwise independent of it.

What it computes is GPT-2's arithmetic *as this repository defines the
model* (`models/gpt.py`), which departs from the GPT-2 release in four
places, all stated in the configuration files under `assumed`:

  * post-LayerNorm blocks, `h = LN(h + f(h))`, as in GPT-1; the release
    normalises before each sub-layer and once more before the head;
  * no final LayerNorm (the tree has no parameters for one);
  * exact (erf) GELU; the release uses the tanh approximation;
  * an untied vocabulary head without bias; the release ties it to the
    token embedding.

Widths, depth, head size, learned positions, causal full attention and
the 4x inner width are the release's.
"""

from __future__ import annotations

import jax
import jax.numpy as jnp

LN_EPS = 1e-5


def _layernorm(x, p):
    mean = jnp.mean(x, axis=-1, keepdims=True)
    var = jnp.mean(jnp.square(x - mean), axis=-1, keepdims=True)
    return (x - mean) / jnp.sqrt(var + LN_EPS) * p["scale"] + p["bias"]


def _linear(x, p):
    return x @ p["w"] + p["b"]


def _attention(x, p, num_heads: int):
    b, t, d = x.shape
    dh = d // num_heads
    q, k, v = jnp.split(_linear(x, p["qkv"]), 3, axis=-1)
    q = q.reshape(b, t, num_heads, dh)
    k = k.reshape(b, t, num_heads, dh)
    v = v.reshape(b, t, num_heads, dh)
    scores = jnp.einsum("bqhd,bkhd->bhqk", q, k) / jnp.sqrt(
        jnp.float32(dh)
    )
    causal = jnp.arange(t)[:, None] >= jnp.arange(t)[None, :]
    scores = jnp.where(causal[None, None], scores, -jnp.inf)
    weights = jax.nn.softmax(scores, axis=-1)
    out = jnp.einsum("bhqk,bkhd->bqhd", weights, v).reshape(b, t, d)
    return _linear(out, p["out"])


def _block(x, p, num_heads: int):
    x = _layernorm(x + _attention(x, p["attn"], num_heads), p["ln1"])
    f = _linear(
        jax.nn.gelu(_linear(x, p["ffn"]["in"]), approximate=False),
        p["ffn"]["out"],
    )
    return _layernorm(x + f, p["ln2"])


def forward(params, ids, num_heads: int):
    """ids (B, T) int -> logits (B, T, vocab) float32."""
    with jax.default_matmul_precision("highest"):
        t = ids.shape[1]
        x = params["stem"]["word"][ids] + params["stem"]["position"][:t]
        for i in range(len(params["blocks"])):
            x = _block(x, params["blocks"][str(i)], num_heads)
        return x @ params["head"]["w"]


def next_token_loss(params, ids, num_heads: int):
    """(sum over positions of -log p(ids[t+1] | ids[:t+1]), positions):
    every position but the last of each sequence predicts its successor."""
    logits = forward(params, ids, num_heads)[:, :-1]
    targets = ids[:, 1:]
    logp = jax.nn.log_softmax(logits, axis=-1)
    picked = jnp.take_along_axis(logp, targets[..., None], axis=-1)
    return -jnp.sum(picked), targets.size
