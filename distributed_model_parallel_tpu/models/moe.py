"""Mixture-of-Experts feed-forward — the routed FFN behind expert
parallelism (`parallel/expert_parallel.py`).

Absent from the reference (SURVEY.md §2.3: "EP — absent, non-goal"); it
exists here because the framework treats every parallelism axis as
first-class. The design is the dense-dispatch GShard/Switch formulation,
chosen FOR the TPU: routing is expressed as einsums against one-hot
dispatch/combine tensors — static shapes, no gather/scatter, everything
on the MXU — so under GSPMD the expert dimension shards over the
`'expert'` mesh axis and the partitioner inserts the token all-to-alls
that GPU MoE stacks hand-write. When an engine threads a policy into
`Context.expert_dispatch` (`ExpertParallelEngine(dispatch=
"hierarchical")` / the DDP engines' `expert_dispatch` knob), the expert
FFN instead runs through the hand-rolled two-level exchange of
`ops/expert_dispatch.py` — routing math here is untouched either way.

Mechanics per token (top-k routing with capacity):
  * router logits -> softmax gates (f32), masked tokens zeroed;
  * k rounds of argmax pick distinct experts; each round assigns the
    token a position in that expert's buffer via a cumulative count,
    tokens past the capacity C = ceil(top_k * T * capacity_factor / E)
    are DROPPED (their combine weight is 0 — the residual stream
    carries them unchanged, the standard Switch behavior);
  * chosen gates renormalize over the kept experts;
  * dispatch einsum packs (B, T, D) -> (E, B, C, D), the per-expert
    FFN runs as batched matmuls over the leading E axis, and the
    combine einsum scatters back weighted by the gates.

The load-balance auxiliary loss (Switch eq. 4: E * Σ_e f_e · p_e,
pre-scaled by `aux_loss_weight`) is returned through the layer STATE
under the reserved key `"moe_aux"`; engines add every `moe_aux` leaf of
the post-forward state to the training loss (see
`parallel/data_parallel.py::aux_loss`), which keeps `Layer`'s
(params, state, x) contract intact — no side-channel plumbing through
the module tree.
"""

from __future__ import annotations

import math

import jax
import jax.numpy as jnp

from distributed_model_parallel_tpu.models import layers as L
from distributed_model_parallel_tpu.models.transformer import (
    AttentionFn,
    multi_head_attention,
)
from distributed_model_parallel_tpu.ops.attention import dot_product_attention

AUX_KEY = "moe_aux"


def expert_ffn(w, xin, dtype=None):
    """The per-expert FFN (dense -> gelu -> dense), batched over the
    leading expert axis: xin (E', rows, C, D) -> (E', rows, C, D) with
    weight leaves leading E'. E' is the FULL expert stack on the GSPMD
    path and a device's E/S block inside the hand-rolled exchange
    (`ops/expert_dispatch.py`) — one copy of the math, no drift.
    Params are f32 masters cast per-use to the compute dtype."""
    dt = dtype if dtype is not None else xin.dtype
    y = jnp.einsum("ebcd,edh->ebch", xin, w["w_in"].astype(dt))
    y = jax.nn.gelu(
        y + w["b_in"][:, None, None, :].astype(dt), approximate=False
    )
    y = jnp.einsum("ebch,ehd->ebcd", y, w["w_out"].astype(dt))
    return y + w["b_out"][:, None, None, :].astype(dt)


def moe_feed_forward(
    dim: int,
    hidden_dim: int,
    num_experts: int,
    *,
    top_k: int = 2,
    capacity_factor: float = 1.25,
    aux_loss_weight: float = 1e-2,
    dropout_rate: float = 0.0,
) -> L.Layer:
    """Drop-in replacement for `transformer.feed_forward` on the
    (hidden, mask) pair: each token runs through its top-k of
    `num_experts` expert FFNs (dense -> gelu -> dense), gate-weighted.

    Expert weights are stacked on a leading E axis — the axis
    `parallel.expert_parallel.EXPERT_RULES` shards over 'expert'.
    """
    if not 1 <= top_k <= num_experts:
        raise ValueError(
            f"top_k {top_k} must be in [1, num_experts {num_experts}]"
        )
    e, k = num_experts, top_k
    drop = L.dropout(dropout_rate)

    def init(key):
        kr, ki, ko = jax.random.split(key, 3)
        params = {
            "router": {"w": 0.02 * jax.random.normal(kr, (dim, e))},
            "experts": {
                "w_in": 0.02 * jax.random.normal(ki, (e, dim, hidden_dim)),
                "b_in": jnp.zeros((e, hidden_dim)),
                "w_out": 0.02 * jax.random.normal(ko, (e, hidden_dim, dim)),
                "b_out": jnp.zeros((e, dim)),
            },
        }
        return params, {AUX_KEY: jnp.zeros((), jnp.float32)}

    def apply(params, state, x, ctx):
        h, mask = x
        b, t, _ = h.shape
        cap = max(1, math.ceil(k * t * capacity_factor / e))

        # Routing in f32 regardless of compute dtype: softmax + cumsum
        # position bookkeeping are precision-sensitive and tiny.
        gates = jax.nn.softmax(
            h.astype(jnp.float32) @ params["router"]["w"].astype(jnp.float32)
        )  # (B, T, E)
        if mask is not None:
            gates = gates * mask[..., None]

        remaining = gates
        counts = jnp.zeros((b, e), jnp.int32)  # tokens KEPT per expert
        chosen = []  # (gate (B,T), expert one-hot (B,T,E), position (B,T))
        top1_assign = None  # round-0 PRE-capacity picks, for the aux loss
        for _ in range(k):
            idx = jnp.argmax(remaining, axis=-1)               # (B, T)
            raw = jax.nn.one_hot(idx, e, dtype=jnp.int32)      # (B, T, E)
            gate = jnp.sum(remaining * raw, axis=-1)           # (B, T)
            # Only tokens with a live gate claim a buffer rank: a masked
            # token's all-zero row argmaxes to expert 0, and counting it
            # in the cumsum would let a later round reuse an occupied
            # slot (two tokens summed into one capacity row).
            eligible = raw * (gate > 0)[..., None].astype(jnp.int32)
            # Buffer slot: tokens earlier in the sequence fill first;
            # previous rounds' KEPT assignments (counts) offset this
            # round's. Kept ranks are consecutive (overflow ranks are
            # all >= cap), so counts is exactly the next free slot.
            pos_in_e = (
                jnp.cumsum(eligible, axis=1) - eligible + counts[:, None, :]
            )
            pos = jnp.sum(pos_in_e * eligible, axis=-1)        # (B, T)
            if top1_assign is None:
                top1_assign = eligible
            keep = (pos < cap) & (gate > 0)
            kept = eligible * keep[..., None].astype(jnp.int32)
            counts = counts + jnp.sum(kept, axis=1)
            chosen.append((gate * keep, kept, pos))
            # Retire this round's PICK (eligible, not just kept) so a
            # token whose first choice overflowed falls to its genuine
            # second choice next round instead of re-picking a full
            # expert and being dropped outright.
            remaining = remaining * (1 - eligible.astype(gates.dtype))

        denom = sum(g for g, _, _ in chosen) + 1e-9
        combine = sum(  # (B, T, E, C): gate weight at the token's slot
            (g / denom)[..., None, None]
            * oh[..., None]
            * jax.nn.one_hot(p, cap)[:, :, None, :]
            for g, oh, p in chosen
        )
        dispatch = (combine > 0).astype(h.dtype)

        w = params["experts"]
        if ctx.expert_dispatch is not None:
            # Hand-rolled hierarchical token exchange
            # (`ops/expert_dispatch.py`): the policy runs the same
            # pack -> FFN -> unpack math with the (E, B, C, D) buffers
            # physically moved over explicit moe_ring permutes instead
            # of a partitioner-inserted flat all-to-all. Routing above
            # is per-sample, so it stays on the GSPMD side untouched.
            out = ctx.expert_dispatch(
                h, dispatch, combine.astype(h.dtype), w
            )
        else:
            xin = jnp.einsum("btec,btd->ebcd", dispatch, h)
            y = expert_ffn(w, xin, dtype=h.dtype)
            out = jnp.einsum(
                "btec,ebcd->btd", combine.astype(h.dtype), y
            )
        # Dedicated child lane for the one stochastic site: drawing from
        # the parent ctx rng reused the lane the enclosing block already
        # handed out, correlating the MoE mask with sibling layers'
        # masks; child(1) mirrors the composed-model global-index
        # contract `stage_apply_fns` reproduces (pinned in
        # tests/test_expert_parallel.py).
        out, _ = drop.apply({}, {}, out, ctx.child(1))

        # Switch load-balance loss: E * Σ_e (assigned fraction f_e) ·
        # (mean router prob p_e), over VALID tokens. f_e counts the
        # router's PRE-capacity top-1 picks: post-drop counts saturate at
        # the capacity exactly when an expert is overloaded, which would
        # blind the penalty to the collapse it exists to prevent.
        n_valid = (
            jnp.sum(mask.astype(jnp.float32))
            if mask is not None
            else jnp.float32(b * t)
        ) + 1e-9
        f_e = (
            jnp.sum(top1_assign.astype(jnp.float32), axis=(0, 1)) / n_valid
        )
        p_e = jnp.sum(gates, axis=(0, 1)) / n_valid
        aux = aux_loss_weight * e * jnp.sum(f_e * p_e)
        return (out, mask), {AUX_KEY: aux}

    return L.Layer(init, apply)


def moe_encoder_layer(
    dim: int,
    num_heads: int,
    hidden_dim: int,
    num_experts: int,
    *,
    top_k: int = 2,
    capacity_factor: float = 1.25,
    aux_loss_weight: float = 1e-2,
    dropout_rate: float = 0.0,
    eps: float = 1e-12,
    attention_fn: AttentionFn = dot_product_attention,
) -> L.Layer:
    """BERT post-LN block with the FFN replaced by a routed MoE:
    LN(h + Attn(h)); LN(h + MoE(h)). Shape-compatible with
    `transformer.encoder_layer`, so MoE and dense blocks interleave in
    one `sequential` stack (the usual every-other-layer MoE recipe)."""
    attn = multi_head_attention(
        dim, num_heads, dropout_rate=dropout_rate, attention_fn=attention_fn
    )
    moe = moe_feed_forward(
        dim, hidden_dim, num_experts, top_k=top_k,
        capacity_factor=capacity_factor, aux_loss_weight=aux_loss_weight,
        dropout_rate=dropout_rate,
    )
    ln1 = L.layernorm(dim, eps=eps)
    ln2 = L.layernorm(dim, eps=eps)

    def init(key):
        ka, km, k1, k2 = jax.random.split(key, 4)
        mp, ms = moe.init(km)
        return (
            {
                "attn": attn.init(ka)[0],
                "ln1": ln1.init(k1)[0],
                "moe": mp,
                "ln2": ln2.init(k2)[0],
            },
            {"moe": ms},
        )

    def apply(params, state, x, ctx):
        h, mask = x
        (a, _), _ = attn.apply(params["attn"], {}, (h, mask), ctx.child(0))
        h, _ = ln1.apply(params["ln1"], {}, h + a, ctx)
        (f, mask), moe_state = moe.apply(
            params["moe"], state.get("moe", {}), (h, mask), ctx.child(1)
        )
        h, _ = ln2.apply(params["ln2"], {}, h + f, ctx)
        return (h, mask), {"moe": moe_state}

    return L.Layer(init, apply)


# ---------------------------------------------------------------------
# A sparse expert layer that holds a range of the experts and drops
# nothing (the expert-parallel share of one chip).

# The step's counters and how each combines over layers, shards and
# steps (what `LMFamily.counter_reductions` hands the engine).
COUNTERS = {
    "moe_picks_held": "sum",       # picks that landed on a held expert
    "moe_expert_rows_max": "max",  # the fullest held expert's rows
    "moe_picks_dropped": "sum",    # held picks outside their expert's rows
}
# What a SERVED expert layer counts instead (a step whose mask says
# which rows are real; `ServingFamily.counter_reductions`).
SERVING_COUNTERS = {
    "moe_picks": "sum",            # real rows' picks routed to an expert
    "moe_experts_hit": "sum",      # held experts with at least one row
    "moe_expert_rows_max": "max",  # the fullest held expert's rows
    "moe_rows_masked": "sum",      # picks of rows that are not real
    # the experts each row of the LAST step chose, (rows, k): what the
    # step itself routed by, for whoever holds it to a reference
    "moe_chosen": "last",
}


def gated_mlp(w, x):
    """SiLU-gated MLP: `w["w_in"]` (D, 2F) holds the gate's columns and
    then the up projection's, `w["w_out"]` is (F, D). Weights are cast
    to x's dtype per use."""
    gate, up = jnp.split(x @ w["w_in"].astype(x.dtype), 2, axis=-1)
    return (jax.nn.silu(gate) * up) @ w["w_out"].astype(x.dtype)


def route(scores_in, router_w, bias, top_k: int, scale: float):
    """Sigmoid router over ALL experts -> (expert ids (N, k), weights
    (N, k) float32). The k experts are chosen by score + bias (the
    correction bias steers the choice alone); the weights are the
    chosen scores renormalised to sum 1, times `scale`. Float32 at
    `highest`: on the TPU the default rounds both operands to bfloat16
    first, which reorders near-ties among 256 scores."""
    scores = jax.nn.sigmoid(jnp.matmul(
        scores_in.astype(jnp.float32), router_w.astype(jnp.float32),
        precision=jax.lax.Precision.HIGHEST,
    ))
    _, ids = jax.lax.top_k(scores + bias, top_k)
    picked = jnp.take_along_axis(scores, ids, axis=-1)
    return ids, scale * picked / jnp.sum(picked, axis=-1, keepdims=True)


def held_experts_feed_forward(
    dim: int,
    hidden_dim: int,
    num_experts: int,
    experts_held: tuple,
    *,
    top_k: int,
    shared_hidden_dim: int,
    routed_scale: float = 1.0,
    init_scale: float = 0.02,
) -> L.Layer:
    """One shared expert plus `num_experts` routed ones of which this
    chip holds the ids `experts_held = (first, past_last)`: it routes
    over all of them, computes its own experts' part of the result and
    drops no pick. A pick of an absent expert adds nothing (its weight
    still counts in the renormalisation: the chips that hold it add
    that part); with every expert held this is the whole layer. Where
    the (hidden, mask) pair carries a mask, the rows it calls not real
    (a served chunk's padded tail, an inactive slot) are sorted behind
    every held expert's rows like absent picks: they are neither
    multiplied nor counted, and the state that comes back holds
    `SERVING_COUNTERS` instead of `COUNTERS`. The training engines
    pass no mask, and their step is what it was.

    No capacity (`held_experts_part`). Params: `router.w`
    (D, num_experts), `experts.w_in` (held, D, 2F) gate then up,
    `experts.w_out` (held, F, D), `shared` a `gated_mlp`. State:
    `router_bias` (num_experts,), a buffer no gradient reaches and
    nothing updates, and the step's counters (`COUNTERS`), which the
    LM engine adds to its metrics.
    """
    first, past = experts_held
    held = past - first
    if not (0 <= first < past <= num_experts):
        raise ValueError(
            f"experts_held {experts_held} is no range of the "
            f"{num_experts} experts"
        )
    if not 1 <= top_k <= num_experts:
        raise ValueError(
            f"top_k {top_k} must be in [1, num_experts {num_experts}]"
        )

    def init(key):
        kr, ki, ko, ksi, kso = jax.random.split(key, 5)
        normal = lambda k, shape: init_scale * jax.random.normal(k, shape)
        params = {
            "router": {"w": normal(kr, (dim, num_experts))},
            "experts": {
                "w_in": normal(ki, (held, dim, 2 * hidden_dim)),
                "w_out": normal(ko, (held, hidden_dim, dim)),
            },
            "shared": {
                "w_in": normal(ksi, (dim, 2 * shared_hidden_dim)),
                "w_out": normal(kso, (shared_hidden_dim, dim)),
            },
        }
        state = {"router_bias": jnp.zeros((num_experts,), jnp.float32)}
        state.update({c: jnp.zeros((), jnp.float32) for c in COUNTERS})
        return params, state

    def apply(params, state, x, ctx):
        h, mask = x
        b, t, d = h.shape
        flat = h.reshape(b * t, d)
        ids, weights = route(
            flat, params["router"]["w"],
            jax.lax.stop_gradient(state["router_bias"]), top_k,
            routed_scale,
        )
        real = None if mask is None else mask.reshape(b * t)
        routed, sizes, n_held, n_placed = held_experts_part(
            params["experts"], flat, ids, weights, first, real
        )
        out = routed + gated_mlp(params["shared"], flat)
        rows_max = jnp.max(sizes).astype(jnp.float32)
        if real is None:
            counters = {
                "moe_picks_held": n_held,
                "moe_expert_rows_max": rows_max,
                "moe_picks_dropped": n_held - n_placed,
            }
        else:
            counters = {
                "moe_picks": n_held,
                "moe_experts_hit": jnp.sum(sizes > 0).astype(jnp.float32),
                "moe_expert_rows_max": rows_max,
                "moe_rows_masked": top_k * jnp.sum(~real).astype(
                    jnp.float32
                ),
                "moe_chosen": ids.astype(jnp.int32),
            }
        return (out.reshape(b, t, d), mask), {
            "router_bias": state["router_bias"], **counters
        }

    return L.Layer(init, apply)


def held_experts_part(w, flat, ids, weights, first, real=None):
    """The part of the routed result that the experts `first ..
    first + held - 1` give, `held = w["w_in"].shape[0]`: flat (N, D),
    ids and weights (N, k) from `route` -> (part (N, D), rows of each
    held expert (held,), picks that landed on a held expert, those of
    them whose row in the sorted buffer lies among its expert's rows:
    where the sort put it, not what the mask says, so a buffer that ran
    out or a sort that misplaced a pick would show). `first` may be
    traced (the test that adds up all the shares maps over it). `real`
    (N,) bool, where given, says which rows exist: the picks of the
    others count as absent.

    Picks are sorted by expert, absent ones behind every held expert's
    rows, into a buffer of N * k rows — the worst case, since a smaller
    one would drop picks — and multiplied group by group at the cost of
    the live rows alone."""
    from distributed_model_parallel_tpu.ops.grouped_matmul import (
        grouped_matmul,
        sort_rows,
        unsort_rows,
    )

    held, top_k = w["w_in"].shape[0], ids.shape[-1]
    is_held = (ids >= first) & (ids < first + held)
    if real is not None:
        is_held = is_held & real[:, None]
    group = jnp.where(is_held, ids - first, held).reshape(-1)
    order = jnp.argsort(group, stable=True)
    inverse = jnp.zeros_like(order).at[order].set(
        jnp.arange(order.shape[0], dtype=order.dtype),
        unique_indices=True,
    )
    sizes = jnp.sum(
        jax.nn.one_hot(group, held + 1, dtype=jnp.int32), axis=0
    )[:held]
    rows = sort_rows(flat, order, inverse, top_k)
    gate, up = jnp.split(
        grouped_matmul(rows, w["w_in"], sizes), 2, axis=-1
    )
    rows = grouped_matmul(jax.nn.silu(gate) * up, w["w_out"], sizes)
    picks = unsort_rows(rows, order, inverse).reshape(
        flat.shape[0], top_k, flat.shape[1]
    )
    part = jnp.sum(
        picks.astype(jnp.float32)
        * jnp.where(is_held, weights, 0.0)[..., None],
        axis=1,
    ).astype(flat.dtype)
    return (part, sizes, jnp.sum(is_held).astype(jnp.float32),
            picks_placed(group, inverse, sizes))


def picks_placed(group, inverse, sizes):
    """How many picks of a held expert have their row among that
    expert's rows of the sorted buffer: group (M,) the held expert of
    each pick (`len(sizes)` for an absent one), inverse (M,) the row
    each pick was sorted to, sizes (held,). Expert e's rows are
    [ends[e] - sizes[e], ends[e]): what the grouped product multiplies
    by e's weights."""
    held = sizes.shape[0]
    ends = jnp.cumsum(sizes)
    own = jnp.minimum(group, held - 1)
    placed = (group < held) & (inverse >= (ends - sizes)[own]) & (
        inverse < ends[own]
    )
    return jnp.sum(placed).astype(jnp.float32)
