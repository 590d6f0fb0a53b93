"""Kimi-Linear: a hybrid decoder of gated-delta-rule (KDA) and latent
attention (MLA) mixers over a sparse expert FFN.

Every layer is pre-norm: `h = x + Mix(RMSNorm(x))`, `y = h +
FFN(RMSNorm(h))`; a final RMSNorm before an untied head; no position
signal anywhere (`mla_use_nope`: the KDA layers carry order). Which
mixer a layer has comes from the published 1-based lists
(`linear_attn_config.kda_layers` / `full_attn_layers`); the first
`first_k_dense_replace` layers have a dense SiLU-gated MLP, every later
one a shared expert plus routed experts (`models/moe.py::
held_experts_feed_forward`), of which a chip may hold a range.

- KDA mixer: `q, k, v = SiLU(conv_causal(W x))` (depthwise, width
  `short_conv_kernel_size`), q and k L2-normalised per head and q
  scaled by dk^-1/2; per-channel log decay `g = -exp(A_head) *
  softplus(W_f_up W_f_down x + dt_bias)`; write strength `beta =
  sigmoid(W_beta x)` per head; the gated delta rule
  (`ops/delta_rule.py`); output `W_o (RMSNorm_head(o) *
  sigmoid(W_g_up W_g_down x))`. The two low-rank maps have the head's
  width as their rank.
- MLA mixer (no query compression, no rotation): `q = W_q x` split per
  head into nope + pe widths; `[c, k_pe] = W_kva x`; `[k_nope, v] =
  W_kvb RMSNorm(c)` per head; `k = [k_nope, k_pe shared by all heads]`;
  causal softmax attention with scale (nope + pe)^-1/2 through the
  engine's attention function (v narrower than q.k); `W_o`.

`jax.named_scope("kda" | "mla" | "moe")` wrap the two mixers and the
expert layer: a Mosaic kernel's name in a device trace is built from
the scopes it is called under.
"""

from __future__ import annotations

import dataclasses
import math
from functools import partial
from typing import List, Tuple

import jax
import jax.numpy as jnp

from distributed_model_parallel_tpu.models import layers as L
from distributed_model_parallel_tpu.models.lm_family import LMFamily
from distributed_model_parallel_tpu.models.moe import (
    COUNTERS,
    gated_mlp,
    held_experts_feed_forward,
)
from distributed_model_parallel_tpu.ops.attention import (
    dot_product_attention,
)
from distributed_model_parallel_tpu.ops.delta_rule import (
    gated_delta_rule,
)

MODEL_TYPE = "kimi_linear"
# Every matrix starts normal with this sigma (the release's
# initializer_range is not among the keys a configuration carries).
INIT_SIGMA = 0.02


@dataclasses.dataclass(frozen=True)
class KimiLinearConfig:
    vocab_size: int
    hidden_size: int
    num_hidden_layers: int
    kda_layers: Tuple[int, ...]        # 1-based, as published
    full_attn_layers: Tuple[int, ...]  # 1-based, as published
    kda_num_heads: int
    kda_head_dim: int
    short_conv_kernel_size: int
    num_attention_heads: int
    qk_nope_head_dim: int
    qk_rope_head_dim: int
    v_head_dim: int
    kv_lora_rank: int
    intermediate_size: int
    moe_intermediate_size: int
    num_experts: int                   # the router's width
    experts_held: Tuple[int, int]      # ids [first, past_last) held here
    num_experts_per_token: int
    num_shared_experts: int
    routed_scaling_factor: float
    first_k_dense_replace: int
    rms_norm_eps: float
    max_position: int

    def mixer_kind(self, layer: int) -> str:
        """`layer` is 1-based, as in the published lists."""
        if layer in self.kda_layers:
            return "kda"
        if layer in self.full_attn_layers:
            return "mla"
        raise ValueError(
            f"layer {layer} is in neither kda_layers nor full_attn_layers"
        )

    def lm_family(self) -> LMFamily:
        return LMFamily(
            name=MODEL_TYPE,
            num_layers=self.num_hidden_layers,
            ffn_dim=self.intermediate_size,
            max_position=self.max_position,
            model=partial(kimi_linear_lm, self),
            blocks=partial(decoder_blocks, self),
            stem=lambda params, ids, ctx, seq_index: stem_apply(
                params, ids, ctx
            ),
            head=partial(head_apply, eps=self.rms_norm_eps),
            head_operands=partial(head_operands, eps=self.rms_norm_eps),
            targets=_lm_targets,
            counters=step_counters,
            counter_reductions=dict(COUNTERS),
            seq_shards_missing=(
                "the KDA state is not passed between 'seq' shards "
                "(ops/delta_rule.py starts every shard from a zero state)"
            ),
            checkpoint_extra={"lm_family": {
                "model_type": MODEL_TYPE,
                **{f.name: getattr(self, f.name)
                   for f in dataclasses.fields(self)},
            }},
        )


_SUPPORTED = {
    "hidden_act": "silu",
    "q_lora_rank": None,
    "mla_use_nope": True,
    "moe_router_activation_func": "sigmoid",
    "moe_renormalize": True,
    "moe_layer_freq": 1,
    "num_expert_group": 1,
    "topk_group": 1,
    "num_nextn_predict_layers": 0,
    "tie_word_embeddings": False,
    "rope_scaling": None,
}


def config_from_dict(d: dict) -> KimiLinearConfig:
    """The source's own keys (`config.json` of the release) -> the
    config, and one key beside them for a chip that holds a share of
    the experts: `experts_held = [first, past_last]`, the range of
    expert ids whose weights are here (absent: all `num_experts`).
    `num_experts` is always the router's width, as in the release. The
    layer lists keep their published entries and the ones past
    `num_hidden_layers` are not built."""
    for key, want in _SUPPORTED.items():
        if key in d and d[key] != want:
            raise NotImplementedError(
                f"{MODEL_TYPE}: {key}={d[key]!r} is not built "
                f"(only {want!r} is)"
            )
    lin = d["linear_attn_config"]
    depth = int(d["num_hidden_layers"])
    experts = int(d["num_experts"])
    first, past = d.get("experts_held", (0, experts))
    kept = lambda xs: tuple(int(i) for i in xs if int(i) <= depth)
    cfg = KimiLinearConfig(
        vocab_size=int(d["vocab_size"]),
        hidden_size=int(d["hidden_size"]),
        num_hidden_layers=depth,
        kda_layers=kept(lin["kda_layers"]),
        full_attn_layers=kept(lin["full_attn_layers"]),
        kda_num_heads=int(lin["num_heads"]),
        kda_head_dim=int(lin["head_dim"]),
        short_conv_kernel_size=int(lin["short_conv_kernel_size"]),
        num_attention_heads=int(d["num_attention_heads"]),
        qk_nope_head_dim=int(d["qk_nope_head_dim"]),
        qk_rope_head_dim=int(d["qk_rope_head_dim"]),
        v_head_dim=int(d["v_head_dim"]),
        kv_lora_rank=int(d["kv_lora_rank"]),
        intermediate_size=int(d["intermediate_size"]),
        moe_intermediate_size=int(d["moe_intermediate_size"]),
        num_experts=experts,
        experts_held=(int(first), int(past)),
        num_experts_per_token=int(d["num_experts_per_token"]),
        num_shared_experts=int(d["num_shared_experts"]),
        routed_scaling_factor=float(d["routed_scaling_factor"]),
        first_k_dense_replace=int(d["first_k_dense_replace"]),
        rms_norm_eps=float(d["rms_norm_eps"]),
        max_position=int(d["model_max_length"]),
    )
    if cfg.num_shared_experts != 1:
        raise NotImplementedError(
            f"{MODEL_TYPE}: num_shared_experts={cfg.num_shared_experts} "
            "is not built (only 1 is)"
        )
    for layer in range(1, depth + 1):
        cfg.mixer_kind(layer)
    return cfg


# ------------------------------------------------------------- pieces


def rms_norm(scale, x, eps: float):
    xf = x.astype(jnp.float32)
    y = xf * jax.lax.rsqrt(jnp.mean(jnp.square(xf), -1, keepdims=True) + eps)
    return (y * scale).astype(x.dtype)


def _normal(key, shape, scale):
    return scale * jax.random.normal(key, shape)


def causal_conv(x, w):
    """Depthwise causal convolution over time: x (B, T, C), w (K, C);
    `y_t = sum_i w_i x_(t-K+1+i)`, zeros before the start."""
    k, t = w.shape[0], x.shape[1]
    padded = jnp.pad(x, ((0, 0), (k - 1, 0), (0, 0)))
    return sum(
        padded[:, i:i + t] * w[i].astype(x.dtype) for i in range(k)
    )


def kda_mixer(cfg: KimiLinearConfig) -> L.Layer:
    d, h, dh = cfg.hidden_size, cfg.kda_num_heads, cfg.kda_head_dim
    width, kernel, sigma = h * dh, cfg.short_conv_kernel_size, INIT_SIGMA

    def init(key):
        ks = jax.random.split(key, 14)
        conv = lambda k: jax.random.uniform(
            k, (kernel, width), minval=-1.0, maxval=1.0
        ) / math.sqrt(kernel)
        # A in [1, 16) and a time step in [1e-3, 1e-1), log-uniform,
        # stored through the inverse of softplus: the gated-delta
        # family's customary start.
        dt = jnp.exp(jax.random.uniform(
            ks[12], (width,), minval=math.log(1e-3), maxval=math.log(1e-1)
        ))
        return {
            "w_q": _normal(ks[0], (d, width), sigma),
            "w_k": _normal(ks[1], (d, width), sigma),
            "w_v": _normal(ks[2], (d, width), sigma),
            "conv_q": conv(ks[3]),
            "conv_k": conv(ks[4]),
            "conv_v": conv(ks[5]),
            "f_down": _normal(ks[6], (d, dh), sigma),
            "f_up": _normal(ks[7], (dh, width), sigma),
            "dt_bias": dt + jnp.log(-jnp.expm1(-dt)),
            "a_log": jnp.log(jax.random.uniform(
                ks[13], (h,), minval=1.0, maxval=16.0
            )),
            "w_beta": _normal(ks[8], (d, h), sigma),
            "g_down": _normal(ks[9], (d, dh), sigma),
            "g_up": _normal(ks[10], (dh, width), sigma),
            "o_norm": jnp.ones((dh,)),
            "w_o": _normal(ks[11], (width, d), sigma),
        }, {}

    def apply(params, state, x, ctx):
        b, t, _ = x.shape
        w = lambda name: params[name].astype(x.dtype)
        heads = lambda y: y.reshape(b, t, h, dh)
        q, k, v = (
            heads(jax.nn.silu(
                causal_conv(x @ w("w_" + n), params["conv_" + n])
            )) for n in "qkv"
        )
        unit = lambda y: y.astype(jnp.float32) * jax.lax.rsqrt(
            jnp.sum(jnp.square(y.astype(jnp.float32)), -1, keepdims=True)
            + 1e-6
        )
        # normalised in float32, handed on as activations (x's dtype)
        q = (unit(q) * dh ** -0.5).astype(x.dtype)
        k = unit(k).astype(x.dtype)
        f = ((x @ w("f_down")) @ w("f_up")).astype(jnp.float32)
        g = -jnp.exp(params["a_log"])[:, None] * heads(
            jax.nn.softplus(f + params["dt_bias"])
        )
        beta = jax.nn.sigmoid((x @ w("w_beta")).astype(jnp.float32))
        o = gated_delta_rule(q, k, v, g, beta)
        gate = jax.nn.sigmoid(heads((x @ w("g_down")) @ w("g_up")))
        o = rms_norm(params["o_norm"], o, cfg.rms_norm_eps) * gate
        return o.reshape(b, t, width) @ w("w_o"), state

    return L.Layer(init, apply)


def mla_mixer(cfg: KimiLinearConfig, attention_fn) -> L.Layer:
    d, h = cfg.hidden_size, cfg.num_attention_heads
    nope, pe, dv = (
        cfg.qk_nope_head_dim, cfg.qk_rope_head_dim, cfg.v_head_dim
    )
    rank, sigma = cfg.kv_lora_rank, INIT_SIGMA

    def init(key):
        ks = jax.random.split(key, 4)
        return {
            "w_q": _normal(ks[0], (d, h * (nope + pe)), sigma),
            "w_kva": _normal(ks[1], (d, rank + pe), sigma),
            "kv_norm": jnp.ones((rank,)),
            "w_kvb": _normal(ks[2], (rank, h * (nope + dv)), sigma),
            "w_o": _normal(ks[3], (h * dv, d), sigma),
        }, {}

    def apply(params, state, x, ctx):
        b, t, _ = x.shape
        w = lambda name: params[name].astype(x.dtype)
        q = (x @ w("w_q")).reshape(b, t, h, nope + pe)
        c, k_pe = jnp.split(x @ w("w_kva"), [rank], axis=-1)
        kv = (
            rms_norm(params["kv_norm"], c, cfg.rms_norm_eps) @ w("w_kvb")
        ).reshape(b, t, h, nope + dv)
        k = jnp.concatenate([
            kv[..., :nope],
            jnp.broadcast_to(k_pe[:, :, None, :], (b, t, h, pe)),
        ], axis=-1)
        o = attention_fn(
            q, k, kv[..., nope:], None, scale=(nope + pe) ** -0.5
        )
        return o.reshape(b, t, h * dv) @ w("w_o"), state

    return L.Layer(init, apply)


def dense_ffn(cfg: KimiLinearConfig) -> L.Layer:
    d, f, sigma = cfg.hidden_size, cfg.intermediate_size, INIT_SIGMA

    def init(key):
        ki, ko = jax.random.split(key)
        return {"w_in": _normal(ki, (d, 2 * f), sigma),
                "w_out": _normal(ko, (f, d), sigma)}, {}

    def apply(params, state, x, ctx):
        h, mask = x
        return (gated_mlp(params, h), mask), state

    return L.Layer(init, apply)


def decoder_block(cfg: KimiLinearConfig, layer: int,
                  attention_fn) -> L.Layer:
    """`layer` is 1-based. Input and output are the (hidden, mask)
    pair the engine's block stack passes along; the mask is not read
    (fixed-length batches, no padding id)."""
    kind = cfg.mixer_kind(layer)
    mixer = (
        kda_mixer(cfg) if kind == "kda" else mla_mixer(cfg, attention_fn)
    )
    sparse = layer > cfg.first_k_dense_replace
    ffn = (
        held_experts_feed_forward(
            cfg.hidden_size, cfg.moe_intermediate_size, cfg.num_experts,
            cfg.experts_held, top_k=cfg.num_experts_per_token,
            shared_hidden_dim=(
                cfg.num_shared_experts * cfg.moe_intermediate_size
            ),
            routed_scale=cfg.routed_scaling_factor,
            init_scale=INIT_SIGMA,
        ) if sparse else dense_ffn(cfg)
    )
    eps = cfg.rms_norm_eps

    def init(key):
        km, kf = jax.random.split(key)
        mp, _ = mixer.init(km)
        fp, fs = ffn.init(kf)
        return {
            "norm1": jnp.ones((cfg.hidden_size,)), "mixer": mp,
            "norm2": jnp.ones((cfg.hidden_size,)), "ffn": fp,
        }, fs

    def apply(params, state, x, ctx):
        h, mask = x
        with jax.named_scope(kind):
            mixed, _ = mixer.apply(
                params["mixer"], {}, rms_norm(params["norm1"], h, eps), ctx
            )
        h = h + mixed
        with jax.named_scope("moe" if sparse else "ffn"):
            (out, _), new_state = ffn.apply(
                params["ffn"], state,
                (rms_norm(params["norm2"], h, eps), mask), ctx,
            )
        return (h + out, mask), new_state

    return L.Layer(init, apply)


def decoder_blocks(cfg: KimiLinearConfig,
                   attention_fn=None) -> List[L.Layer]:
    attn = attention_fn or partial(dot_product_attention, causal=True)
    return [
        decoder_block(cfg, layer, attn)
        for layer in range(1, cfg.num_hidden_layers + 1)
    ]


def stem_apply(params, ids, ctx):
    """Token embedding alone: no positions. Returns (hidden, mask)."""
    h = jnp.take(params["word"], ids, axis=0)
    if ctx.dtype is not None:
        h = h.astype(ctx.dtype)
    return h, None


def head_operands(params, h, *, eps: float):
    """(rows, matrix) of the vocabulary product: the final RMSNorm
    comes before it (`models/lm_family.LMFamily.head_operands`)."""
    return rms_norm(params["norm"], h, eps), params["w"]


def head_apply(params, h, *, eps: float):
    """Final RMSNorm, then the untied vocabulary projection; float32
    logits, as `models/gpt.head_apply`."""
    rows, matrix = head_operands(params, h, eps=eps)
    return rows.astype(jnp.float32) @ matrix


def _stem(cfg: KimiLinearConfig) -> L.Layer:
    def init(key):
        return {"word": _normal(
            key, (cfg.vocab_size, cfg.hidden_size), INIT_SIGMA
        )}, {}

    return L.Layer(
        init, lambda p, s, ids, ctx: (stem_apply(p, ids, ctx), s)
    )


def _head(cfg: KimiLinearConfig) -> L.Layer:
    def init(key):
        return {
            "norm": jnp.ones((cfg.hidden_size,)),
            "w": _normal(
                key, (cfg.hidden_size, cfg.vocab_size), INIT_SIGMA
            ),
        }, {}

    def apply(params, state, x, ctx):
        return head_apply(params, x[0], eps=cfg.rms_norm_eps), state

    return L.Layer(init, apply)


def kimi_linear_lm(cfg: KimiLinearConfig, *, attention_fn=None,
                   remat: bool = False) -> L.Layer:
    """Full LM: ids (B, T) -> float32 logits (B, T, vocab)."""
    from distributed_model_parallel_tpu.models import staging

    blocks = decoder_blocks(cfg, attention_fn)
    if remat:
        blocks = [L.remat(b) for b in blocks]
    return staging.staged_model(_stem(cfg), blocks, _head(cfg))


def _lm_targets(ids):
    from distributed_model_parallel_tpu.models.gpt import lm_targets

    return lm_targets(ids, None)


def step_counters(blocks_state) -> dict:
    """The expert layers' counters of one forward pass, combined over
    the layers as `COUNTERS` says."""
    over = {"sum": jnp.sum, "max": jnp.max}
    layers = [s for s in blocks_state.values() if "moe_picks_held" in s]
    return {
        name: over[how](jnp.stack([s[name] for s in layers]))
        for name, how in COUNTERS.items()
    }
