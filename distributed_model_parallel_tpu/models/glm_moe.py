"""GLM-4 MoE "lite" (`glm4_moe_lite`): a decoder of latent-attention
mixers with a compressed query and rotary positions over sparse expert
layers with one shared expert.

Every layer is pre-norm: `h = x + Mixer(RMSNorm(x))`, `y = h +
FF(RMSNorm(h))`; one RMSNorm after the last block, then an untied
vocabulary projection; no biases anywhere.

- Mixer (H heads): `cq = RMSNorm(x W_qa)` (`q_lora_rank`), `q = cq
  W_qb` -> per head `q_nope` (`qk_nope_head_dim`) and `q_rope`
  (`qk_rope_head_dim`); `[c_raw, kr] = x W_kva` (`kv_lora_rank` +
  `qk_rope_head_dim`), `c = RMSNorm(c_raw)`. `q_rope` and `kr` (one for
  all heads) are rotated at their token's position (base `rope_theta`,
  all `qk_rope_head_dim` values, pairs (i, i + half): a fixed
  permutation of the release's interleaved columns). A head's key is
  `[c W_UK_h, kr]`, its value `c W_UV_h` (`W_kvb` holds both), scores
  scale by `(nope + rope) ** -0.5`, the result leaves through `W_o`.
  What a token leaves behind for later ones is ONE row `[c, kr]`
  (`kv_lora_rank + qk_rope_head_dim` values, the key half rotated):
  the mixer hands its attention function the UNROTATED `q_rope` and
  `kr`, `c`, `q_nope` and `W_kvb`, and the function, which alone knows
  each token's position and where the earlier rows are kept, rotates,
  caches and attends (`ops/latent_attention.py`: absorbed or expanded
  by the step's shape). Without a cache that is
  `latent_causal_attention`.
- Feed-forward: layers below `first_k_dense_replace` a SiLU-gated MLP
  of `intermediate_size`; the others `models/moe.py`'s
  `held_experts_feed_forward` with every one of `n_routed_experts`
  held: a sigmoid router in float32, the `num_experts_per_tok` of
  largest score + `e_score_correction_bias`, weights renormalised to
  sum `routed_scaling_factor`, plus `n_shared_experts` shared experts
  as one MLP of their summed width. Rows the mask calls not real (a
  chunk's padded tail, an inactive slot) reach no expert and no counter.

The release's next-token-prediction layer (`num_nextn_predict_layers`)
is not built: it adds nothing to the next token's logits, and drafting
from it needs a verify step over latent pages (ROADMAP Queue 2a).
`jax.named_scope("mla" | "moe" | "mlp")` wrap the layers.

Training this family (`cli.lm`) is not built.
"""

from __future__ import annotations

import dataclasses
from functools import partial
from typing import List

import jax
import jax.numpy as jnp

from distributed_model_parallel_tpu.models import layers as L
from distributed_model_parallel_tpu.models.kimi_linear import rms_norm
from distributed_model_parallel_tpu.models.lm_family import (
    LayerCache,
    ServingFamily,
)
from distributed_model_parallel_tpu.models.moe import (
    SERVING_COUNTERS,
    gated_mlp,
    held_experts_feed_forward,
)
from distributed_model_parallel_tpu.ops.latent_attention import (
    LatentDims,
    latent_causal_attention,
)

MODEL_TYPE = "glm4_moe_lite"
# Every matrix starts normal with this sigma (the release's
# initializer_range is not among the keys a configuration carries).
INIT_SIGMA = 0.02


@dataclasses.dataclass(frozen=True)
class GlmMoeConfig:
    vocab_size: int
    hidden_size: int
    num_hidden_layers: int
    num_attention_heads: int
    q_lora_rank: int
    kv_lora_rank: int
    qk_nope_head_dim: int
    qk_rope_head_dim: int
    v_head_dim: int
    intermediate_size: int
    moe_intermediate_size: int
    n_routed_experts: int
    n_shared_experts: int
    num_experts_per_tok: int
    routed_scaling_factor: float
    first_k_dense_replace: int
    rope_theta: float
    rms_norm_eps: float
    max_position: int
    # Weights at rest when served (`precision.parameters` of a
    # benchmark file; the release's `torch_dtype`): a dtype's name.
    param_dtype: str = "float32"

    @property
    def latent_dims(self) -> LatentDims:
        return LatentDims(
            heads=self.num_attention_heads, rank=self.kv_lora_rank,
            nope=self.qk_nope_head_dim, rope=self.qk_rope_head_dim,
            dv=self.v_head_dim, theta=float(self.rope_theta),
            scale=(self.qk_nope_head_dim + self.qk_rope_head_dim) ** -0.5,
        )

    def sparse(self, layer: int) -> bool:
        """`layer` is 0-based: does it route over the experts?"""
        return layer >= self.first_k_dense_replace

    def serving_family(self) -> ServingFamily:
        """What `ServingEngine` needs of this family: every layer keeps
        latent pages, weights rest in `param_dtype`, the decode step's
        mask says which slots are live (the expert layers must not
        route the others), and the expert layers' counters."""
        eps = self.rms_norm_eps

        def embed(params, ids, dtype):
            h = jnp.take(params["stem"]["word"], ids, axis=0)
            return h if dtype is None else h.astype(dtype)

        # positions enter inside the attention seam, not here
        stem = lambda params, ids, at, dtype: embed(params, ids, dtype)

        def head_row(params, h, row):
            one = jax.lax.dynamic_slice_in_dim(h, row, 1, axis=1)
            return head_apply(params, one, eps=eps)[0, 0]

        latent = (
            "a layer's cache is one pool of latent rows a page "
            "(serving/kv_cache.py's latent pool)"
        )
        return ServingFamily(
            name=MODEL_TYPE,
            vocab_size=self.vocab_size,
            max_position=self.max_position,
            model=partial(glm_moe_lm, self),
            blocks=lambda attention_fn, state_fn=None: decoder_blocks(
                self, attention_fn
            ),
            decode_stem=lambda params, tokens, positions, dtype: embed(
                params, tokens, dtype
            )[:, None, :],
            chunk_stem=stem, prefill_stem=stem, verify_stem=stem,
            head=partial(head_apply, eps=eps),
            head_row=head_row,
            layers=tuple(
                LayerCache(latent_dim=self.latent_dims.row)
                for _ in range(self.num_hidden_layers)
            ),
            param_dtype=jnp.dtype(self.param_dtype),
            masks_inactive=True,
            counters=step_counters,
            counter_reductions=dict(SERVING_COUNTERS),
            counter_rows={"moe_chosen": (
                self.num_hidden_layers - self.first_k_dense_replace,
                self.num_experts_per_tok,
            )},
            missing={
                "speculative_k": (
                    "the release's next-token-prediction layer is not "
                    "built and the verify step has no recorder over "
                    "latent pages (serving/speculative.py reads K and V "
                    "pools)"
                ),
                "layout=tp": (
                    latent + ", replicated: a latent row has no head "
                    "axis to shard over 'model'"
                ),
                "layout=sp": (
                    latent + ": the 'seq'-sharded decode merges per-head "
                    "keys and values, not latent rows"
                ),
                "page_size=None": (
                    latent + ": the contiguous cache's steps keep K and "
                    "V stripes per head"
                ),
                "prefill_chunk=None": (
                    latent + ": the monolithic prefill step records "
                    "per-head keys and values; chunked prefill writes "
                    "latent rows"
                ),
            },
        )


_SUPPORTED = {
    "attention_bias": False,
    "hidden_act": "silu",
    "n_group": 1,
    "topk_group": 1,
    "topk_method": "noaux_tc",
    "norm_topk_prob": True,
    "rope_scaling": None,
    "partial_rotary_factor": 1,
    "tie_word_embeddings": False,
}


def config_from_dict(d: dict) -> GlmMoeConfig:
    """The source's own keys (`config.json` of the release) -> the
    config. `num_key_value_heads` equals the heads and selects nothing
    (a latent row has no heads); `num_nextn_predict_layers` is not
    built (module doc). `torch_dtype`, where given, is the dtype the
    weights rest in."""
    for key, want in _SUPPORTED.items():
        if key in d and d[key] != want:
            raise NotImplementedError(
                f"{MODEL_TYPE}: {key}={d[key]!r} is not built "
                f"(only {want!r} is)"
            )
    cfg = GlmMoeConfig(
        vocab_size=int(d["vocab_size"]),
        hidden_size=int(d["hidden_size"]),
        num_hidden_layers=int(d["num_hidden_layers"]),
        num_attention_heads=int(d["num_attention_heads"]),
        q_lora_rank=int(d["q_lora_rank"]),
        kv_lora_rank=int(d["kv_lora_rank"]),
        qk_nope_head_dim=int(d["qk_nope_head_dim"]),
        qk_rope_head_dim=int(d["qk_rope_head_dim"]),
        v_head_dim=int(d["v_head_dim"]),
        intermediate_size=int(d["intermediate_size"]),
        moe_intermediate_size=int(d["moe_intermediate_size"]),
        n_routed_experts=int(d["n_routed_experts"]),
        n_shared_experts=int(d["n_shared_experts"]),
        num_experts_per_tok=int(d["num_experts_per_tok"]),
        routed_scaling_factor=float(d["routed_scaling_factor"]),
        first_k_dense_replace=int(d["first_k_dense_replace"]),
        rope_theta=float(d["rope_theta"]),
        rms_norm_eps=float(d["rms_norm_eps"]),
        max_position=int(d["max_position_embeddings"]),
        param_dtype=str(d.get("torch_dtype", "float32")),
    )
    if cfg.qk_rope_head_dim % 2:
        raise ValueError(
            f"{MODEL_TYPE}: qk_rope_head_dim {cfg.qk_rope_head_dim} is "
            "odd: the rotation pairs its values"
        )
    if not 1 <= cfg.num_experts_per_tok <= cfg.n_routed_experts:
        raise ValueError(
            f"{MODEL_TYPE}: num_experts_per_tok {cfg.num_experts_per_tok}"
            f" of n_routed_experts {cfg.n_routed_experts}"
        )
    return cfg


# ------------------------------------------------------------- pieces


def _normal(key, shape, scale=INIT_SIGMA):
    return scale * jax.random.normal(key, shape)


def latent_mixer(cfg: GlmMoeConfig, attention_fn) -> L.Layer:
    d, dims = cfg.hidden_size, cfg.latent_dims
    h, rq = dims.heads, cfg.q_lora_rank
    eps = cfg.rms_norm_eps

    def init(key):
        ks = jax.random.split(key, 5)
        return {
            "w_qa": _normal(ks[0], (d, rq)),
            "q_norm": jnp.ones((rq,)),
            "w_qb": _normal(ks[1], (rq, h * (dims.nope + dims.rope))),
            "w_kva": _normal(ks[2], (d, dims.row)),
            "kv_norm": jnp.ones((dims.rank,)),
            "w_kvb": _normal(ks[3], (dims.rank, h * (dims.nope + dims.dv))),
            "w_o": _normal(ks[4], (h * dims.dv, d)),
        }, {}

    def apply(params, x, mask):
        b, t, _ = x.shape
        w = lambda name: params[name].astype(x.dtype)
        cq = rms_norm(params["q_norm"], x @ w("w_qa"), eps)
        q = (cq @ w("w_qb")).reshape(b, t, h, dims.nope + dims.rope)
        c, k_rope = jnp.split(x @ w("w_kva"), [dims.rank], axis=-1)
        o = attention_fn(
            q[..., :dims.nope], q[..., dims.nope:],
            rms_norm(params["kv_norm"], c, eps), k_rope, w("w_kvb"),
            mask, dims,
        )
        return o.reshape(b, t, h * dims.dv) @ w("w_o")

    return L.Layer(init, apply)


def dense_ffn(cfg: GlmMoeConfig) -> L.Layer:
    d, f = cfg.hidden_size, cfg.intermediate_size

    def init(key):
        ki, ko = jax.random.split(key)
        return {"w_in": _normal(ki, (d, 2 * f)),
                "w_out": _normal(ko, (f, d))}, {}

    def apply(params, state, x, ctx):
        h, mask = x
        return (gated_mlp(params, h), mask), state

    return L.Layer(init, apply)


def decoder_block(cfg: GlmMoeConfig, layer: int, attention_fn) -> L.Layer:
    """`layer` is 0-based. Input and output are the (hidden, mask) pair
    the engines' block stacks pass along; the mask (B, T) says which
    positions are real, None that all are. The block's post-forward
    state is its expert layer's counters ({} for a dense layer)."""
    mixer = latent_mixer(cfg, attention_fn)
    sparse = cfg.sparse(layer)
    ffn = (
        held_experts_feed_forward(
            cfg.hidden_size, cfg.moe_intermediate_size,
            cfg.n_routed_experts, (0, cfg.n_routed_experts),
            top_k=cfg.num_experts_per_tok,
            shared_hidden_dim=(
                cfg.n_shared_experts * cfg.moe_intermediate_size
            ),
            routed_scale=cfg.routed_scaling_factor,
            init_scale=INIT_SIGMA,
        ) if sparse else dense_ffn(cfg)
    )
    eps = cfg.rms_norm_eps

    def init(key):
        km, kf = jax.random.split(key)
        params = {
            "norm1": jnp.ones((cfg.hidden_size,)),
            "mixer": mixer.init(km)[0],
            "norm2": jnp.ones((cfg.hidden_size,)),
            "ffn": ffn.init(kf)[0],
        }
        if sparse:
            # the release's `e_score_correction_bias`: a parameter here
            # (a served tree has no state), steering the choice alone
            params["router_bias"] = jnp.zeros((cfg.n_routed_experts,))
        return params, {}

    def apply(params, state, x, ctx):
        h, mask = x
        with jax.named_scope("mla"):
            h = h + mixer.apply(
                params["mixer"], rms_norm(params["norm1"], h, eps), mask
            )
        with jax.named_scope("moe" if sparse else "mlp"):
            ffn_state = (
                {"router_bias": params["router_bias"].astype(jnp.float32)}
                if sparse else {}
            )
            (out, _), new_state = ffn.apply(
                params["ffn"], ffn_state,
                (rms_norm(params["norm2"], h, eps), mask), ctx,
            )
        new_state = {
            k: v for k, v in new_state.items() if k != "router_bias"
        }
        return (h + out, mask), new_state

    return L.Layer(init, apply)


def decoder_blocks(cfg: GlmMoeConfig, attention_fn=None) -> List[L.Layer]:
    attn = attention_fn or latent_causal_attention
    return [
        decoder_block(cfg, layer, attn)
        for layer in range(cfg.num_hidden_layers)
    ]


def step_counters(blocks_state, kind: str) -> dict:
    """The expert layers' counters of one step, combined over the
    layers as `SERVING_COUNTERS` says (the experts each row chose are
    kept layer by layer: (rows, expert layers, k)). `kind` is the
    step's ("decode" | "chunk"): the experts a step reaches are counted
    over decode steps alone, where they set the weight bytes a step
    must read."""
    over = {"sum": jnp.sum, "max": jnp.max,
            "last": lambda x: jnp.moveaxis(x, 0, 1)}
    layers = [s for s in blocks_state.values() if "moe_picks" in s]
    out = {
        name: over[how](jnp.stack([s[name] for s in layers]))
        for name, how in SERVING_COUNTERS.items()
    }
    if kind != "decode":
        out["moe_experts_hit"] = jnp.zeros_like(out["moe_experts_hit"])
    return out


def head_apply(params, h, *, eps: float):
    """Final RMSNorm, then the untied vocabulary projection, float32
    logits. Takes the WHOLE parameter tree."""
    x = rms_norm(params["head"]["norm"], h, eps)
    return jnp.einsum(
        "btd,dv->btv", x, params["head"]["w"].astype(x.dtype),
        preferred_element_type=jnp.float32,
    )


def glm_moe_lm(cfg: GlmMoeConfig, *, attention_fn=None) -> L.Layer:
    """Full LM: ids (B, T) -> float32 logits (B, T, vocab), every
    sequence from position 0. Params {"stem": {"word"}, "blocks":
    {"0", ...}, "head": {"norm", "w"}}."""
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
                "norm": jnp.ones((cfg.hidden_size,)),
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
