"""Jamba: a hybrid decoder of Mamba-1 state-space mixers and a few
multi-query attention mixers over a dense SiLU-gated MLP.

Every layer is pre-norm: `h = x + Mix(RMSNorm(x))`, `y = h +
MLP(RMSNorm(h))`, `MLP(v) = (silu(v W_gate) * (v W_up)) W_down`; one
RMSNorm after the last block, then logits against the token embedding
itself (the head is tied); no position signal of any kind (the
state-space layers carry order). Layer i (0-based) attends iff
`i % attn_layer_period == attn_layer_offset`, else it is a Mamba layer.

- Attention mixer: `q = x W_q` (`num_attention_heads` heads), `k, v =
  x W_k, x W_v` (`num_key_value_heads` heads, fewer), causal softmax
  attention at scale head_dim^-1/2 through the engine's attention
  function, `W_o`; no biases, no rotation, no window.
- Mamba-1 mixer (d_in = `mamba_expand` x hidden, N = `mamba_d_state`,
  R = `mamba_dt_rank`, K = `mamba_d_conv`): `[u, z] = x W_in`; `c =
  silu(conv_causal(u) + bias)` (depthwise, width K); `[dt, B, C] =
  c W_x`, each through an RMSNorm of its own (Jamba's addition to
  Mamba); `delta = softplus(dt W_dt + b_dt)`; the selective recurrence
  (`ops/ssm_scan.py`) with `A = -exp(A_log)`, its state, `delta`, the
  factors and its inputs c, B, C in float32 as they are made (what a
  matrix is multiplied by is rounded to the activations' dtype, the
  recurrence's inputs are not: the compiler dropped that rounding in
  the chunk program and kept it elsewhere, PERF.md section 6);
  `y + D * c`; output `(y * silu(z)) W_out`. `selective_scan` picks
  its program per call: on a TPU a chunk's stretch runs as one kernel
  with the state resident on the chip, one position (the decode step)
  and every other backend as the compiled loop.
  Between two steps of a sequence a layer keeps the convolution's last
  K - 1 inputs and the recurrence's state: it reaches them through the
  `state_fn` its blocks are built with (`models/lm_family.py`), so the
  same blocks run a whole sequence from zeros, one chunk of a prompt
  from where the last chunk stopped, or one token of every slot.

`A_log` and the state are laid out (N, d_in), the channels on the
lanes. `jax.named_scope("ssm" | "attn")` wrap the mixers.

Training this family (`cli.lm`) is not built.
"""

from __future__ import annotations

import dataclasses
import math
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
from distributed_model_parallel_tpu.ops.attention import (
    grouped_query_attention,
)
from distributed_model_parallel_tpu.ops.ssm_scan import (
    conv_carry,
    scan_kind,
    selective_scan,
)

MODEL_TYPE = "jamba"
# Every matrix starts normal with this sigma (the release's
# initializer_range is not among the keys a configuration carries).
INIT_SIGMA = 0.02


@dataclasses.dataclass(frozen=True)
class JambaConfig:
    vocab_size: int
    hidden_size: int
    num_hidden_layers: int
    num_attention_heads: int
    num_key_value_heads: int
    intermediate_size: int
    attn_layer_period: int
    attn_layer_offset: int
    mamba_d_state: int
    mamba_d_conv: int
    mamba_dt_rank: int
    mamba_expand: int
    rms_norm_eps: float
    max_position: int
    # Weights at rest when served (`precision.parameters` of a
    # benchmark file; the release's `torch_dtype`): a dtype's name.
    param_dtype: str = "float32"

    @property
    def head_dim(self) -> int:
        return self.hidden_size // self.num_attention_heads

    @property
    def d_inner(self) -> int:
        return self.mamba_expand * self.hidden_size

    def mixer_kind(self, layer: int) -> str:
        """`layer` is 0-based, as in the release's offset and period."""
        attends = layer % self.attn_layer_period == self.attn_layer_offset
        return "attn" if attends else "ssm"

    def state_shapes(self) -> dict:
        """What one Mamba layer keeps for one sequence."""
        return {
            "conv": ((self.mamba_d_conv - 1, self.d_inner), None),
            "h": ((self.mamba_d_state, self.d_inner), jnp.float32),
        }

    def serving_family(self) -> ServingFamily:
        """What `ServingEngine` needs of this family: 2 kinds of layer
        cache, bfloat16 at rest if the configuration says so, and by
        name what the engine cannot do over a recurrent state yet."""
        eps = self.rms_norm_eps

        def embed(params, ids, dtype):
            h = jnp.take(params["stem"]["word"], ids, axis=0)
            return h if dtype is None else h.astype(dtype)

        stem = lambda params, ids, at, dtype: embed(params, ids, dtype)

        def head_row(params, h, row):
            one = jax.lax.dynamic_slice_in_dim(h, row, 1, axis=1)
            return head_apply(params, one, eps=eps)[0, 0]

        state = (
            "a layer's recurrent state and the convolution's kept inputs "
            "are one array per slot (serving/kv_cache.py's state pool)"
        )
        return ServingFamily(
            name=MODEL_TYPE,
            vocab_size=self.vocab_size,
            max_position=self.max_position,
            model=partial(jamba_lm, self),
            blocks=partial(decoder_blocks, self),
            decode_stem=lambda params, tokens, positions, dtype: embed(
                params, tokens, dtype
            )[:, None, :],
            # no positions: a chunk, a prompt and a verify span embed alike
            chunk_stem=stem, prefill_stem=stem, verify_stem=stem,
            head=partial(head_apply, eps=eps),
            head_row=head_row,
            layers=tuple(
                LayerCache(kv_heads=self.num_key_value_heads,
                           head_dim=self.head_dim)
                if self.mixer_kind(i) == "attn"
                else LayerCache(state=self.state_shapes())
                for i in range(self.num_hidden_layers)
            ),
            param_dtype=jnp.dtype(self.param_dtype),
            chunk_state_program=lambda chunk: scan_kind(
                chunk, self.d_inner, self.mamba_d_state
            ),
            missing={
                "prefix_cache": (
                    state + ": a shared prefix page carries keys and "
                    "values but no state to resume from; snapshots of "
                    "the state at page boundaries are not built"
                ),
                "speculative_k": (
                    state + ": a rejected draft suffix is rolled back by "
                    "truncating the block table, and a state that has "
                    "advanced over it cannot be rolled back; no copy of "
                    "the state before the verify step is kept"
                ),
                "layout=tp": (
                    state + ", replicated: the channels of a state and "
                    "of its projections have no partition over 'model'"
                ),
                "layout=sp": (
                    state + ": a state is not passed between 'seq' "
                    "shards (ops/ssm_scan.py walks one shard's positions)"
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
    "mamba_conv_bias": True,
    "mamba_proj_bias": False,
    "num_experts": 1,
    "sliding_window": None,
    "tie_word_embeddings": True,
}


def config_from_dict(d: dict) -> JambaConfig:
    """The source's own keys (`config.json` of the release) -> the
    config. `num_experts` 1 makes every feed-forward the dense MLP, so
    the `expert_layer_*` keys select nothing; `use_mamba_kernels` and
    `num_logits_to_keep` steer the release's own code and nothing here.
    `torch_dtype`, where given, is the dtype the weights rest in."""
    for key, want in _SUPPORTED.items():
        if key in d and d[key] != want:
            raise NotImplementedError(
                f"{MODEL_TYPE}: {key}={d[key]!r} is not built "
                f"(only {want!r} is)"
            )
    cfg = JambaConfig(
        vocab_size=int(d["vocab_size"]),
        hidden_size=int(d["hidden_size"]),
        num_hidden_layers=int(d["num_hidden_layers"]),
        num_attention_heads=int(d["num_attention_heads"]),
        num_key_value_heads=int(d["num_key_value_heads"]),
        intermediate_size=int(d["intermediate_size"]),
        attn_layer_period=int(d["attn_layer_period"]),
        attn_layer_offset=int(d["attn_layer_offset"]),
        mamba_d_state=int(d["mamba_d_state"]),
        mamba_d_conv=int(d["mamba_d_conv"]),
        mamba_dt_rank=int(d["mamba_dt_rank"]),
        mamba_expand=int(d["mamba_expand"]),
        rms_norm_eps=float(d["rms_norm_eps"]),
        max_position=int(d["max_position_embeddings"]),
        param_dtype=str(d.get("torch_dtype", "float32")),
    )
    if cfg.hidden_size % cfg.num_attention_heads:
        raise ValueError(
            f"{MODEL_TYPE}: hidden_size {cfg.hidden_size} is not a "
            f"multiple of num_attention_heads {cfg.num_attention_heads}"
        )
    if cfg.num_attention_heads % cfg.num_key_value_heads:
        raise ValueError(
            f"{MODEL_TYPE}: {cfg.num_attention_heads} query heads do not "
            f"group over {cfg.num_key_value_heads} key/value heads"
        )
    return cfg


# ------------------------------------------------------------- pieces


def _normal(key, shape, scale):
    return scale * jax.random.normal(key, shape)


def whole_sequence(advance):
    """The state function of a pass over whole sequences from their
    start: zero state in, the state after the last position dropped."""
    return advance(None)[0]


def attention_mixer(cfg: JambaConfig, attention_fn) -> L.Layer:
    d, h, hkv, dh = (
        cfg.hidden_size, cfg.num_attention_heads,
        cfg.num_key_value_heads, cfg.head_dim,
    )

    def init(key):
        ks = jax.random.split(key, 4)
        return {
            "w_q": _normal(ks[0], (d, h * dh), INIT_SIGMA),
            "w_k": _normal(ks[1], (d, hkv * dh), INIT_SIGMA),
            "w_v": _normal(ks[2], (d, hkv * dh), INIT_SIGMA),
            "w_o": _normal(ks[3], (h * dh, d), INIT_SIGMA),
        }, {}

    def apply(params, x, mask):
        b, t, _ = x.shape
        w = lambda name: params[name].astype(x.dtype)
        q = (x @ w("w_q")).reshape(b, t, h, dh)
        k = (x @ w("w_k")).reshape(b, t, hkv, dh)
        v = (x @ w("w_v")).reshape(b, t, hkv, dh)
        o = attention_fn(q, k, v, mask)
        return o.reshape(b, t, h * dh) @ w("w_o")

    return L.Layer(init, apply)


def mamba_mixer(cfg: JambaConfig, state_fn) -> L.Layer:
    d, d_in, n, r, k = (
        cfg.hidden_size, cfg.d_inner, cfg.mamba_d_state,
        cfg.mamba_dt_rank, cfg.mamba_d_conv,
    )
    eps = cfg.rms_norm_eps
    shapes = cfg.state_shapes()

    def init(key):
        ks = jax.random.split(key, 7)
        # Mamba's customary start: A = -(1..N) for every channel, a
        # time step log-uniform in [1e-3, 1e-1) stored through the
        # inverse of softplus, D = 1.
        dt = jnp.exp(jax.random.uniform(
            ks[5], (d_in,), minval=math.log(1e-3), maxval=math.log(1e-1)
        ))
        return {
            "w_in": _normal(ks[0], (d, 2 * d_in), INIT_SIGMA),
            "conv_w": jax.random.uniform(
                ks[1], (k, d_in), minval=-1.0, maxval=1.0
            ) / math.sqrt(k),
            "conv_b": _normal(ks[2], (d_in,), INIT_SIGMA),
            "w_x": _normal(ks[3], (d_in, r + 2 * n), INIT_SIGMA),
            "dt_norm": jnp.ones((r,)),
            "b_norm": jnp.ones((n,)),
            "c_norm": jnp.ones((n,)),
            "w_dt": _normal(ks[4], (r, d_in), r ** -0.5),
            "dt_bias": dt + jnp.log(-jnp.expm1(-dt)),
            "a_log": jnp.broadcast_to(
                jnp.log(jnp.arange(1, n + 1, dtype=jnp.float32))[:, None],
                (n, d_in),
            ),
            "d": jnp.ones((d_in,)),
            "w_out": _normal(ks[6], (d_in, d), INIT_SIGMA),
        }, {}

    def apply(params, x, mask):
        b, t, _ = x.shape
        f32 = jnp.float32
        w = lambda name: params[name].astype(x.dtype)
        u, z = jnp.split(x @ w("w_in"), 2, axis=-1)
        valid = jnp.ones((b, t), jnp.bool_) if mask is None else mask
        n_valid = jnp.sum(valid, axis=1).astype(jnp.int32)

        def advance(state):
            if state is None:
                state = {
                    name: jnp.zeros((b, *shape), dtype or x.dtype)
                    for name, (shape, dtype) in shapes.items()
                }
            c, kept = conv_carry(
                u, params["conv_w"], params["conv_b"], state["conv"],
                n_valid,
            )
            # the recurrence takes c, B and C in float32 as they are
            # made; only what a matrix is multiplied by is rounded
            c = jax.nn.silu(c)
            dt, bm, cm = jnp.split(
                c.astype(x.dtype) @ w("w_x"), [r, r + n], axis=-1
            )
            dt = rms_norm(params["dt_norm"], dt, eps)
            bm = rms_norm(params["b_norm"], bm.astype(f32), eps)
            cm = rms_norm(params["c_norm"], cm.astype(f32), eps)
            delta = jax.nn.softplus(
                jnp.matmul(dt, w("w_dt"), preferred_element_type=f32)
                + params["dt_bias"].astype(f32)
            )
            y, h = selective_scan(
                c, delta, -jnp.exp(params["a_log"].astype(f32)), bm, cm,
                state["h"], valid,
            )
            y = y + params["d"].astype(f32) * c
            return y.astype(x.dtype), {"conv": kept, "h": h}

        y = state_fn(advance)
        return (y * jax.nn.silu(z)) @ w("w_out")

    return L.Layer(init, apply)


def gated_mlp(cfg: JambaConfig) -> L.Layer:
    d, f = cfg.hidden_size, cfg.intermediate_size

    def init(key):
        ks = jax.random.split(key, 3)
        return {
            "w_gate": _normal(ks[0], (d, f), INIT_SIGMA),
            "w_up": _normal(ks[1], (d, f), INIT_SIGMA),
            "w_down": _normal(ks[2], (f, d), INIT_SIGMA),
        }, {}

    def apply(params, x):
        w = lambda name: params[name].astype(x.dtype)
        return (jax.nn.silu(x @ w("w_gate")) * (x @ w("w_up"))) @ w("w_down")

    return L.Layer(init, apply)


def decoder_block(cfg: JambaConfig, layer: int, attention_fn,
                  state_fn) -> L.Layer:
    """`layer` is 0-based. Input and output are the (hidden, mask) pair
    the engines' block stacks pass along; the mask (B, T) says which
    positions are real, None that all are."""
    kind = cfg.mixer_kind(layer)
    mixer = (
        attention_mixer(cfg, attention_fn) if kind == "attn"
        else mamba_mixer(cfg, state_fn)
    )
    mlp = gated_mlp(cfg)
    eps = cfg.rms_norm_eps

    def init(key):
        km, kf = jax.random.split(key)
        return {
            "norm1": jnp.ones((cfg.hidden_size,)),
            "mixer": mixer.init(km)[0],
            "norm2": jnp.ones((cfg.hidden_size,)),
            "mlp": mlp.init(kf)[0],
        }, {}

    def apply(params, state, x, ctx):
        h, mask = x
        with jax.named_scope(kind):
            h = h + mixer.apply(
                params["mixer"], rms_norm(params["norm1"], h, eps), mask
            )
        with jax.named_scope("mlp"):
            h = h + mlp.apply(
                params["mlp"], rms_norm(params["norm2"], h, eps)
            )
        return (h, mask), state

    return L.Layer(init, apply)


def decoder_blocks(cfg: JambaConfig, attention_fn=None,
                   state_fn=None) -> List[L.Layer]:
    attn = attention_fn or partial(grouped_query_attention, causal=True)
    return [
        decoder_block(cfg, layer, attn, state_fn or whole_sequence)
        for layer in range(cfg.num_hidden_layers)
    ]


def head_apply(params, h, *, eps: float):
    """Final RMSNorm, then logits against the token embedding (the head
    is tied to it), float32. Takes the WHOLE parameter tree."""
    x = rms_norm(params["head"]["norm"], h, eps)
    return jnp.einsum(
        "btd,vd->btv", x, params["stem"]["word"].astype(x.dtype),
        preferred_element_type=jnp.float32,
    )


def jamba_lm(cfg: JambaConfig, *, attention_fn=None) -> L.Layer:
    """Full LM: ids (B, T) -> float32 logits (B, T, vocab), every
    sequence from a zero state. Params {"stem": {"word"}, "blocks":
    {"0", ...}, "head": {"norm"}}."""
    blocks = decoder_blocks(cfg, attention_fn)
    stack = L.sequential(*blocks)

    def init(key):
        ke, kb = jax.random.split(key)
        blocks_params, blocks_state = stack.init(kb)
        return {
            "stem": {"word": _normal(
                ke, (cfg.vocab_size, cfg.hidden_size), INIT_SIGMA
            )},
            "blocks": blocks_params,
            "head": {"norm": jnp.ones((cfg.hidden_size,))},
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
