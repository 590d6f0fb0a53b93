"""Qwen3-Next (`qwen3_next`): a hybrid decoder of gated delta-rule
linear-attention mixers (Gated DeltaNet) and gated softmax attention
with partial rotary positions, every layer over a sparse expert layer
with a gated shared expert.

Every layer is pre-norm: `h = x + Mixer(Norm(x))`, `y = h +
MoE(Norm(h))`; one Norm after the last block, then an untied vocabulary
projection; no biases anywhere. Every Norm is a zero-centred RMSNorm,
`x / rms(x) * (1 + w)` with w starting at 0. Layer i (0-based) attends
iff `(i + 1) % full_attention_interval == 0`, else it is a delta-rule
layer.

- Delta-rule mixer (Hk key heads, Hv value heads of dk and dv, conv
  width K): `[q k v z] = x W_qkvz` (q, k Hk heads; v, z Hv heads),
  `[b a] = x W_ba` (Hv each); `[q k v] = silu(causal_conv([q k v]))`,
  depthwise, no bias; q and k repeated to Hv heads (head j reads key
  head j // (Hv / Hk)), `q <- l2norm(q) / sqrt(dk)`, `k <- l2norm(k)`;
  `beta = sigmoid(b)`, `g = -exp(A_log) * softplus(a + dt_bias)`, one
  log decay per value head, handed to `ops/delta_rule.py`'s recurrence
  as one (a trailing axis of 1: its head-decay path; float32 state at
  `highest`);
  `o = RMSNorm(o) * w * silu(z)` per head (w starting at 1), `W_out`.
  Everything from the two in-projections to the recurrence (the
  convolution, its kept inputs, q, k, v, g, beta) is float32. Between
  two steps of a sequence a layer keeps the convolution's last K - 1
  inputs and the recurrence's state S (Hv, dk, dv), both float32,
  reached through the `state_fn` its blocks are built with
  (`models/lm_family.py`): a whole sequence from zeros, one chunk of a
  prompt from where the last one stopped (its padded tail neither
  decays nor writes: g = 0, beta = 0 there), or one token of every slot
  (`gated_delta_rule_step`).
- Gated attention (H query heads over Hkv cached heads of `head_dim`):
  `[q gate] = x W_q` (per head `head_dim` of query, then `head_dim` of
  gate), `k, v = x W_k, x W_v`; q and k through a zero-centred RMSNorm
  per head; the first `partial_rotary_factor * head_dim` values of q
  and k rotated at their token's position (rotate-half, base
  `rope_theta`); causal softmax at scale head_dim^-1/2; `o *
  sigmoid(gate)`, `W_o`. The layer hands its attention function q and
  k UNROTATED: the function, which alone knows each token's position,
  rotates, caches the key rotated and attends (`LayerCache.rotary`;
  without a cache `rotary_causal_attention`).
- MoE: `models/moe.py`'s `held_experts_feed_forward` with the softmax
  router over all `num_experts` (float32 at `highest`), the
  `num_experts_per_tok` largest renormalised to sum 1
  (`norm_topk_prob`), of which this chip computes the picks that land
  on `experts_held`, plus a shared expert of
  `shared_expert_intermediate_size` scaled by `sigmoid(x w_sg)`. Rows
  the mask calls not real reach no expert and no counter.

The release's next-token-prediction layer is not built.
`jax.named_scope("gdn" | "attn" | "moe")` wrap the layers.

Training this family (`cli.lm`) is not built.
"""

from __future__ import annotations

import dataclasses
import math
from functools import partial
from typing import List, Tuple

import jax
import jax.numpy as jnp

from distributed_model_parallel_tpu.models import layers as L
from distributed_model_parallel_tpu.models.glm_moe import step_counters
from distributed_model_parallel_tpu.models.jamba import whole_sequence
from distributed_model_parallel_tpu.models.kimi_linear import rms_norm
from distributed_model_parallel_tpu.models.lm_family import (
    LayerCache,
    ServingFamily,
)
from distributed_model_parallel_tpu.models.moe import (
    SERVING_COUNTERS,
    held_experts_feed_forward,
    widened,
)
from distributed_model_parallel_tpu.ops.attention import (
    grouped_query_attention,
)
from distributed_model_parallel_tpu.ops.delta_rule import (
    decay_kind,
    gated_delta_rule_from,
    gated_delta_rule_step,
)
from distributed_model_parallel_tpu.ops.latent_attention import rotate_part
from distributed_model_parallel_tpu.ops.ssm_scan import conv_carry

MODEL_TYPE = "qwen3_next"
# Every matrix starts normal with this sigma (the release's
# initializer_range is not among the keys a configuration carries).
INIT_SIGMA = 0.02
# What a served step leaves of its first expert layer, float32, a row a
# token: the rows the layer took (normed) and the rows it gave (held
# experts and the gated shared one), so that a check can hold the timed
# programs' own expert layer against a reference.
FIRST_ROWS = ("moe_rows_in", "moe_rows_out")


@dataclasses.dataclass(frozen=True)
class Qwen3NextConfig:
    vocab_size: int
    hidden_size: int
    num_hidden_layers: int
    num_attention_heads: int
    num_key_value_heads: int
    head_dim: int
    rotary_dim: int
    rope_theta: float
    linear_num_key_heads: int
    linear_num_value_heads: int
    linear_key_head_dim: int
    linear_value_head_dim: int
    linear_conv_kernel_dim: int
    full_attention_interval: int
    num_experts: int
    num_experts_per_tok: int
    moe_intermediate_size: int
    shared_expert_intermediate_size: int
    experts_held: Tuple[int, int]
    rms_norm_eps: float
    max_position: int
    # Weights at rest when served (`precision.parameters` of a
    # benchmark file; the release's `torch_dtype`): a dtype's name.
    param_dtype: str = "float32"

    def mixer_kind(self, layer: int) -> str:
        """`layer` is 0-based, as in the release's `layer_types`."""
        attends = (layer + 1) % self.full_attention_interval == 0
        return "attn" if attends else "gdn"

    @property
    def conv_dim(self) -> int:
        """Channels of the delta-rule layer's convolution: q, k, v."""
        return (
            2 * self.linear_num_key_heads * self.linear_key_head_dim
            + self.linear_num_value_heads * self.linear_value_head_dim
        )

    def state_shapes(self) -> dict:
        """What one delta-rule layer keeps for one sequence."""
        return {
            "conv": ((self.linear_conv_kernel_dim - 1, self.conv_dim),
                     jnp.float32),
            "s": ((self.linear_num_value_heads, self.linear_key_head_dim,
                   self.linear_value_head_dim), jnp.float32),
        }

    def serving_family(self) -> ServingFamily:
        """What `ServingEngine` needs of this family: a state per slot
        for the delta-rule layers, pages of rotated keys for the
        attention layers, weights in `param_dtype`, the decode step's
        mask over live slots (the expert layers must not route the
        others), the expert layers' counters, and by name what the
        engine cannot do over a recurrent state yet."""
        eps = self.rms_norm_eps

        def embed(params, ids, dtype):
            h = jnp.take(params["stem"]["word"], ids, axis=0)
            return h if dtype is None else h.astype(dtype)

        # positions enter inside the attention seam, not here
        stem = lambda params, ids, at, dtype: embed(params, ids, dtype)

        def head_row(params, h, row):
            one = jax.lax.dynamic_slice_in_dim(h, row, 1, axis=1)
            return head_apply(params, one, eps=eps)[0, 0]

        state = (
            "a delta-rule layer's state and its convolution's kept inputs "
            "are one array per slot (serving/kv_cache.py's state pool)"
        )
        return ServingFamily(
            name=MODEL_TYPE,
            vocab_size=self.vocab_size,
            max_position=self.max_position,
            model=partial(qwen3_next_lm, self),
            blocks=partial(decoder_blocks, self),
            decode_stem=lambda params, tokens, positions, dtype: embed(
                params, tokens, dtype
            )[:, None, :],
            chunk_stem=stem, prefill_stem=stem, verify_stem=stem,
            head=partial(head_apply, eps=eps),
            head_row=head_row,
            layers=tuple(
                LayerCache(kv_heads=self.num_key_value_heads,
                           head_dim=self.head_dim,
                           rotary=(self.rotary_dim, self.rope_theta))
                if self.mixer_kind(i) == "attn"
                else LayerCache(state=self.state_shapes())
                for i in range(self.num_hidden_layers)
            ),
            param_dtype=jnp.dtype(self.param_dtype),
            chunk_decay_kind=decay_kind(jax.eval_shape(
                head_decay, jax.ShapeDtypeStruct(
                    (1, 1, self.linear_num_value_heads), jnp.float32))),
            masks_inactive=True,
            counters=served_counters,
            counter_reductions={
                **SERVING_COUNTERS, **{name: "last" for name in FIRST_ROWS},
            },
            counter_rows={
                "moe_chosen": (
                    self.num_hidden_layers, self.num_experts_per_tok,
                ),
                **{name: (self.hidden_size,) for name in FIRST_ROWS},
            },
            counter_dtypes={name: jnp.float32 for name in FIRST_ROWS},
            missing={
                "prefix_cache": (
                    state + ": a shared prefix page carries keys and "
                    "values but no state to resume from; snapshots of "
                    "the state at page boundaries are not built"
                ),
                "speculative_k": (
                    state + ": a state that has advanced over a rejected "
                    "draft cannot be rolled back, and the release's "
                    "next-token-prediction layer is not built"
                ),
                "layout=tp": (
                    state + ", replicated: the heads of a state and of "
                    "its projections have no partition over 'model'"
                ),
                "layout=sp": (
                    state + ": a state is not passed between 'seq' "
                    "shards"
                ),
                "page_size=None": (
                    state + " beside the PAGE pool: the contiguous "
                    "cache's steps do not carry it"
                ),
                "prefill_chunk=None": (
                    state + ": the monolithic prefill step pads every "
                    "prompt to prefill_len and does not carry state; "
                    "chunked prefill does, chunk by chunk"
                ),
            },
        )


_SUPPORTED = {
    "hidden_act": "silu",
    "decoder_sparse_step": 1,
    "mlp_only_layers": [],
    "norm_topk_prob": True,
    "rope_scaling": None,
    "tie_word_embeddings": False,
    "use_sliding_window": False,
}


def config_from_dict(d: dict) -> Qwen3NextConfig:
    """The source's own keys (`config.json` of the release) -> the
    config, plus `experts_held = [first, past_last]`, the range of
    expert ids whose weights this chip holds (all of them if absent).
    `intermediate_size` is the width of a dense layer, and with
    `mlp_only_layers` empty and `decoder_sparse_step` 1 no layer is
    dense; `torch_dtype`, where given, is the dtype the weights rest
    in."""
    for key, want in _SUPPORTED.items():
        if key in d and d[key] != want:
            raise NotImplementedError(
                f"{MODEL_TYPE}: {key}={d[key]!r} is not built "
                f"(only {want!r} is)"
            )
    experts = int(d["num_experts"])
    first, past = d.get("experts_held", (0, experts))
    head_dim = int(d["head_dim"])
    cfg = Qwen3NextConfig(
        vocab_size=int(d["vocab_size"]),
        hidden_size=int(d["hidden_size"]),
        num_hidden_layers=int(d["num_hidden_layers"]),
        num_attention_heads=int(d["num_attention_heads"]),
        num_key_value_heads=int(d["num_key_value_heads"]),
        head_dim=head_dim,
        rotary_dim=int(round(float(d["partial_rotary_factor"]) * head_dim)),
        rope_theta=float(d["rope_theta"]),
        linear_num_key_heads=int(d["linear_num_key_heads"]),
        linear_num_value_heads=int(d["linear_num_value_heads"]),
        linear_key_head_dim=int(d["linear_key_head_dim"]),
        linear_value_head_dim=int(d["linear_value_head_dim"]),
        linear_conv_kernel_dim=int(d["linear_conv_kernel_dim"]),
        full_attention_interval=int(d["full_attention_interval"]),
        num_experts=experts,
        num_experts_per_tok=int(d["num_experts_per_tok"]),
        moe_intermediate_size=int(d["moe_intermediate_size"]),
        shared_expert_intermediate_size=int(
            d["shared_expert_intermediate_size"]),
        experts_held=(int(first), int(past)),
        rms_norm_eps=float(d["rms_norm_eps"]),
        max_position=int(d["max_position_embeddings"]),
        param_dtype=str(d.get("torch_dtype", "float32")),
    )
    if cfg.rotary_dim % 2 or not 0 < cfg.rotary_dim <= head_dim:
        raise ValueError(
            f"{MODEL_TYPE}: partial_rotary_factor rotates {cfg.rotary_dim} "
            f"of {head_dim} values: the rotation pairs them"
        )
    if cfg.num_attention_heads % cfg.num_key_value_heads:
        raise ValueError(
            f"{MODEL_TYPE}: {cfg.num_attention_heads} query heads do not "
            f"group over {cfg.num_key_value_heads} key/value heads"
        )
    if cfg.linear_num_value_heads % cfg.linear_num_key_heads:
        raise ValueError(
            f"{MODEL_TYPE}: {cfg.linear_num_value_heads} value heads do "
            f"not group over {cfg.linear_num_key_heads} key heads"
        )
    if not 1 <= cfg.num_experts_per_tok <= experts:
        raise ValueError(
            f"{MODEL_TYPE}: num_experts_per_tok {cfg.num_experts_per_tok}"
            f" of num_experts {experts}"
        )
    return cfg


# ------------------------------------------------------------- pieces


def _normal(key, shape, scale=INIT_SIGMA):
    return scale * jax.random.normal(key, shape)


def zero_centred_rms_norm(w, x, eps: float):
    """`x / rms(x) * (1 + w)`, statistics in float32, in x's dtype."""
    xf = x.astype(jnp.float32)
    y = xf * jax.lax.rsqrt(jnp.mean(jnp.square(xf), -1, keepdims=True) + eps)
    return (y * (1.0 + w.astype(jnp.float32))).astype(x.dtype)


def rotary_causal_attention(q, k, v, mask, *, rotary):
    """The attention function of a pass over whole sequences from
    position 0: q and k rotated (`rotary`: values, base), then causal
    grouped attention. `mask` (B, T) says which keys are real."""
    b, t = q.shape[:2]
    pos = jnp.broadcast_to(jnp.arange(t), (b, t))
    return grouped_query_attention(
        rotate_part(q, pos, rotary), rotate_part(k, pos, rotary), v,
        mask=mask, causal=True,
    )


def head_decay(g):
    """The mixer's log decay, one a value head (b, t, hv), as the delta
    rule takes it: (b, t, hv, 1), the op's head-decay path."""
    return g[..., None]


def delta_mixer(cfg: Qwen3NextConfig, state_fn) -> L.Layer:
    d, k_conv = cfg.hidden_size, cfg.linear_conv_kernel_dim
    hk, hv = cfg.linear_num_key_heads, cfg.linear_num_value_heads
    dk, dv = cfg.linear_key_head_dim, cfg.linear_value_head_dim
    key_dim, value_dim = hk * dk, hv * dv
    eps = cfg.rms_norm_eps
    shapes = cfg.state_shapes()

    def init(key):
        ks = jax.random.split(key, 6)
        return {
            "w_qkvz": _normal(ks[0], (d, 2 * key_dim + 2 * value_dim)),
            "w_ba": _normal(ks[1], (d, 2 * hv)),
            "conv_w": jax.random.uniform(
                ks[2], (k_conv, cfg.conv_dim), minval=-1.0, maxval=1.0
            ) / math.sqrt(k_conv),
            "dt_bias": jnp.ones((hv,)),
            "a_log": jnp.log(jax.random.uniform(
                ks[3], (hv,), minval=1.0, maxval=16.0
            )),
            "o_norm": jnp.ones((dv,)),
            "w_out": _normal(ks[4], (value_dim, d)),
        }, {}

    def apply(params, x, mask):
        b, t, _ = x.shape
        f32 = jnp.float32
        # the recurrence's inputs stay float32 from the products on: a
        # rounding to the activations' dtype that the compiler may drop
        # in one program and keep in another is no precision to state
        w = lambda name: params[name].astype(x.dtype)
        made = lambda name: jnp.matmul(
            x, w(name), preferred_element_type=f32)
        qkv, z = jnp.split(made("w_qkvz"), [2 * key_dim + value_dim], -1)
        beta_in, a = jnp.split(made("w_ba"), 2, axis=-1)
        valid = jnp.ones((b, t), jnp.bool_) if mask is None else mask
        n_valid = jnp.sum(valid, axis=1).astype(jnp.int32)
        # a padded position neither decays nor writes the state
        beta = jnp.where(valid[..., None], jax.nn.sigmoid(beta_in), 0.0)
        g = jnp.where(
            valid[..., None],
            -jnp.exp(params["a_log"].astype(f32))
            * jax.nn.softplus(a + params["dt_bias"].astype(f32)),
            0.0,
        )

        def unit(y):
            y = y.astype(f32)
            return y * jax.lax.rsqrt(jnp.sum(y * y, -1, keepdims=True) + 1e-6)

        def advance(state):
            if state is None:
                state = {
                    name: jnp.zeros((b, *shape), dtype or x.dtype)
                    for name, (shape, dtype) in shapes.items()
                }
            c, kept = conv_carry(
                qkv, params["conv_w"], jnp.zeros((cfg.conv_dim,)),
                state["conv"], n_valid,
            )
            q, k, v = jnp.split(jax.nn.silu(c), [key_dim, 2 * key_dim], -1)
            group = lambda y: jnp.repeat(
                y.reshape(b, t, hk, dk), hv // hk, axis=2)
            q = unit(group(q)) * dk ** -0.5
            k = unit(group(k))
            v = v.reshape(b, t, hv, dv)
            gh = head_decay(g)
            if t == 1:
                o, s = gated_delta_rule_step(
                    q[:, 0], k[:, 0], v[:, 0], gh[:, 0], beta[:, 0],
                    state["s"],
                )
                o = o[:, None]
            else:
                o, s = gated_delta_rule_from(q, k, v, gh, beta, state["s"])
            return o, {"conv": kept, "s": s}

        o = rms_norm(params["o_norm"], state_fn(advance), eps)
        o = (o * jax.nn.silu(z.reshape(b, t, hv, dv))).astype(x.dtype)
        return o.reshape(b, t, value_dim) @ w("w_out")

    return L.Layer(init, apply)


def gated_attention(cfg: Qwen3NextConfig, attention_fn) -> L.Layer:
    d, h, hkv, dh = (
        cfg.hidden_size, cfg.num_attention_heads,
        cfg.num_key_value_heads, cfg.head_dim,
    )
    eps = cfg.rms_norm_eps

    def init(key):
        ks = jax.random.split(key, 4)
        return {
            "w_q": _normal(ks[0], (d, h * 2 * dh)),
            "w_k": _normal(ks[1], (d, hkv * dh)),
            "w_v": _normal(ks[2], (d, hkv * dh)),
            "q_norm": jnp.zeros((dh,)),
            "k_norm": jnp.zeros((dh,)),
            "w_o": _normal(ks[3], (h * dh, d)),
        }, {}

    def apply(params, x, mask):
        b, t, _ = x.shape
        w = lambda name: params[name].astype(x.dtype)
        q, gate = jnp.split(
            (x @ w("w_q")).reshape(b, t, h, 2 * dh), 2, axis=-1)
        q = zero_centred_rms_norm(params["q_norm"], q, eps)
        k = zero_centred_rms_norm(
            params["k_norm"], (x @ w("w_k")).reshape(b, t, hkv, dh), eps)
        v = (x @ w("w_v")).reshape(b, t, hkv, dh)
        o = attention_fn(q, k, v, mask)
        o = o * jax.nn.sigmoid(gate.astype(jnp.float32)).astype(x.dtype)
        return o.reshape(b, t, h * dh) @ w("w_o")

    return L.Layer(init, apply)


def expert_layer(cfg: Qwen3NextConfig) -> L.Layer:
    """Every layer's feed-forward: the held experts of a softmax router
    over all of them, and the gated shared expert."""
    return held_experts_feed_forward(
        cfg.hidden_size, cfg.moe_intermediate_size, cfg.num_experts,
        cfg.experts_held, top_k=cfg.num_experts_per_tok,
        shared_hidden_dim=cfg.shared_expert_intermediate_size,
        init_scale=INIT_SIGMA, router="softmax", shared_gate=True,
    )


def decoder_block(cfg: Qwen3NextConfig, layer: int, attention_fn,
                  state_fn) -> L.Layer:
    """`layer` is 0-based. Input and output are the (hidden, mask) pair
    the engines' block stacks pass along; the mask (B, T) says which
    positions are real, None that all are. The block's post-forward
    state is its expert layer's counters (and, in the first layer, the
    rows the expert layer took and gave: `FIRST_ROWS`)."""
    kind = cfg.mixer_kind(layer)
    mixer = (
        gated_attention(cfg, attention_fn) if kind == "attn"
        else delta_mixer(cfg, state_fn)
    )
    ffn = expert_layer(cfg)
    eps = cfg.rms_norm_eps
    no_bias = {"router_bias": jnp.zeros((cfg.num_experts,), jnp.float32)}

    def init(key):
        km, kf = jax.random.split(key)
        return {
            "norm1": jnp.zeros((cfg.hidden_size,)),
            "mixer": mixer.init(km)[0],
            "norm2": jnp.zeros((cfg.hidden_size,)),
            "ffn": ffn.init(kf)[0],
        }, {}

    def apply(params, state, x, ctx):
        h, mask = x
        with jax.named_scope(kind):
            h = h + mixer.apply(
                params["mixer"],
                zero_centred_rms_norm(params["norm1"], h, eps), mask,
            )
        with jax.named_scope("moe"):
            rows = zero_centred_rms_norm(params["norm2"], h, eps)
            (out, _), new_state = ffn.apply(
                params["ffn"], no_bias, (rows, mask), ctx,
            )
        new_state = {
            k: v for k, v in new_state.items() if k != "router_bias"
        }
        if layer == 0:
            # what the first expert layer took and gave, a row a token
            for name, y in zip(FIRST_ROWS, (rows, out)):
                new_state[name] = widened(y.reshape(-1, y.shape[-1]))
        return (h + out, mask), new_state

    return L.Layer(init, apply)


def served_counters(blocks_state, kind: str) -> dict:
    """`glm_moe.step_counters` over the expert layers, and the first
    layer's `FIRST_ROWS` as the step left them."""
    return {**step_counters(blocks_state, kind),
            **{name: blocks_state["0"][name] for name in FIRST_ROWS}}


def decoder_blocks(cfg: Qwen3NextConfig, attention_fn=None,
                   state_fn=None) -> List[L.Layer]:
    attn = attention_fn or partial(
        rotary_causal_attention, rotary=(cfg.rotary_dim, cfg.rope_theta))
    return [
        decoder_block(cfg, layer, attn, state_fn or whole_sequence)
        for layer in range(cfg.num_hidden_layers)
    ]


def head_apply(params, h, *, eps: float):
    """Final zero-centred RMSNorm, then the untied vocabulary
    projection, float32 logits. Takes the WHOLE parameter tree."""
    x = zero_centred_rms_norm(params["head"]["norm"], h, eps)
    return jnp.einsum(
        "btd,dv->btv", x, params["head"]["w"].astype(x.dtype),
        preferred_element_type=jnp.float32,
    )


def qwen3_next_lm(cfg: Qwen3NextConfig, *, attention_fn=None) -> L.Layer:
    """Full LM: ids (B, T) -> float32 logits (B, T, vocab), every
    sequence from position 0 and a zero state. Params {"stem":
    {"word"}, "blocks": {"0", ...}, "head": {"norm", "w"}}."""
    stack = L.sequential(*decoder_blocks(cfg, attention_fn))

    def init(key):
        ke, kb, kh = jax.random.split(key, 3)
        blocks_params, blocks_state = stack.init(kb)
        return {
            "stem": {"word": _normal(
                ke, (cfg.vocab_size, cfg.hidden_size)
            )},
            "blocks": blocks_params,
            "head": {
                "norm": jnp.zeros((cfg.hidden_size,)),
                "w": _normal(kh, (cfg.hidden_size, cfg.vocab_size)),
            },
        }, {"stem": {}, "blocks": blocks_state, "head": {}}

    def apply(params, state, ids, ctx):
        h = jnp.take(params["stem"]["word"], ids, axis=0)
        if ctx.dtype is not None:
            h = h.astype(ctx.dtype)
        (h, _), _ = stack.apply(
            params["blocks"], state["blocks"], (h, None), ctx
        )
        return head_apply(params, h, eps=cfg.rms_norm_eps), state

    return L.Layer(init, apply)
