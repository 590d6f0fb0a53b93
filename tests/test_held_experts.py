"""The sparse expert layer that holds a range of the experts and drops
nothing (`models/moe.py::held_experts_feed_forward`): every pick on one
held expert, every pick on held experts (the buffer's worst case), no
pick on any, and the tie to the whole model: the parts of all 32 shares,
the shared expert counted once, add up to the uncut layer's output,
which is also the plain reference's."""

import os
import sys

import jax
import jax.numpy as jnp
import pytest

ROOT = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
sys.path.insert(0, ROOT)

from benchmark.reference import kimi_linear_ref as ref  # noqa: E402
from distributed_model_parallel_tpu.models import moe  # noqa: E402
from distributed_model_parallel_tpu.models.layers import Context  # noqa: E402

D, F, E, K, SCALE = 32, 16, 64, 4, 2.446
TOKENS = (2, 16)


def layer(held, top_k=K):
    return moe.held_experts_feed_forward(
        D, F, E, held, top_k=top_k, shared_hidden_dim=F,
        routed_scale=SCALE, init_scale=0.3,
    )


def run(l, params, state, x):
    (out, _), after = jax.jit(
        lambda p, s, xx: l.apply(p, s, (xx, None), Context(train=True))
    )(params, state, x)
    return out, after


def steered(state, experts):
    """A correction bias that puts every token's picks on `experts`."""
    bias = jnp.zeros((E,)).at[jnp.array(experts)].set(10.0)
    return {**state, "router_bias": bias}


def reference(params, x, held, bias=0.0, top_k=K):
    arch = {"experts_held": held, "num_experts_per_token": top_k,
            "routed_scaling_factor": SCALE}
    with jax.default_matmul_precision("highest"):
        return ref._experts(x, params, arch, bias, jnp.float32)


@pytest.fixture(scope="module")
def x():
    return jax.random.normal(jax.random.PRNGKey(1), TOKENS + (D,))


def test_every_pick_on_one_held_expert_drops_nothing(x):
    l = layer((0, 8), top_k=1)
    params, state = l.init(jax.random.PRNGKey(0))
    out, after = run(l, params, steered(state, [3]), x)
    n = TOKENS[0] * TOKENS[1]
    assert float(after["moe_picks_held"]) == n
    assert float(after["moe_expert_rows_max"]) == n
    assert float(after["moe_picks_dropped"]) == 0
    # top-1 renormalised is weight 1, times the factor, for EVERY token
    w3 = {k: v[3] for k, v in params["experts"].items()}
    want = moe.gated_mlp(params["shared"], x) + SCALE * moe.gated_mlp(w3, x)
    assert float(jnp.abs(out - want).max()) < 1e-5


def test_every_pick_on_held_experts_fills_the_worst_case_buffer(x):
    l = layer((4, 8))
    params, state = l.init(jax.random.PRNGKey(0))
    state = steered(state, [4, 5, 6, 7])
    out, after = run(l, params, state, x)
    n = TOKENS[0] * TOKENS[1]
    assert float(after["moe_picks_held"]) == n * K
    assert float(after["moe_expert_rows_max"]) == n
    assert float(after["moe_picks_dropped"]) == 0
    want = reference(params, x, (4, 8), state["router_bias"])
    assert float(jnp.abs(out - want).max()) < 1e-5


def test_no_pick_on_any_held_expert_leaves_the_shared_expert(x):
    l = layer((0, 8))
    params, state = l.init(jax.random.PRNGKey(0))
    out, after = run(l, params, steered(state, [20, 21, 22, 23]), x)
    assert float(after["moe_picks_held"]) == 0
    assert float(after["moe_expert_rows_max"]) == 0
    assert float(after["moe_picks_dropped"]) == 0
    want = moe.gated_mlp(params["shared"], x)
    assert float(jnp.abs(out - want).max()) < 1e-6
    # and no gradient reaches the experts it holds, nor the bias
    grads = jax.grad(lambda p: jnp.sum(l.apply(
        p, steered(state, [20, 21, 22, 23]), (x, None),
        Context(train=True))[0][0] ** 2))(params)
    assert float(jnp.abs(grads["experts"]["w_in"]).max()) == 0


def test_the_bias_steers_the_choice_and_not_the_weights(x):
    l = layer((0, E))
    params, state = l.init(jax.random.PRNGKey(0))
    bias = 0.3 * jax.random.normal(jax.random.PRNGKey(7), (E,))
    out, _ = run(l, params, {**state, "router_bias": bias}, x)
    want = reference(params, x, (0, E), bias)
    assert float(jnp.abs(out - want).max()) < 1e-5
    unbiased, _ = run(l, params, state, x)
    assert float(jnp.abs(out - unbiased).max()) > 1e-3


def test_the_parts_of_all_32_shares_add_up_to_the_uncut_layer(x):
    """32 chips hold 2 experts each; the shared expert is computed on
    every chip alike and counted once."""
    whole = layer((0, E))
    params, state = whole.init(jax.random.PRNGKey(0))
    uncut, after = run(whole, params, state, x)
    assert float(after["moe_picks_held"]) == TOKENS[0] * TOKENS[1] * K
    assert float(jnp.abs(uncut - reference(params, x, (0, E))).max()) < 1e-5

    flat = x.reshape(-1, D)
    ids, weights = moe.route(
        flat, params["router"]["w"], state["router_bias"], K, SCALE)

    def share(first):
        mine = jax.tree_util.tree_map(
            lambda w: jax.lax.dynamic_slice_in_dim(w, first, 2, axis=0),
            params["experts"])
        part, _, held, placed = moe.held_experts_part(
            mine, flat, ids, weights, first)
        return part, held - placed

    parts, dropped = jax.jit(
        lambda: jax.lax.map(share, jnp.arange(0, E, 2)))()
    assert parts.shape[0] == 32 and float(jnp.abs(dropped).max()) == 0
    total = jnp.sum(parts, axis=0) + moe.gated_mlp(params["shared"], flat)
    assert float(jnp.abs(total.reshape(x.shape) - uncut).max()) < 1e-5

    # one share as the layer itself builds it (rank 3 of 32)
    third = layer((6, 8))
    mine = {**params, "experts": jax.tree_util.tree_map(
        lambda w: w[6:8], params["experts"])}
    out, _ = run(third, mine, state, x)
    want = parts[3].reshape(x.shape) + moe.gated_mlp(params["shared"], x)
    assert float(jnp.abs(out - want).max()) < 1e-5


def test_gradients_equal_the_plain_references(x):
    l = layer((8, 16))
    params, state = l.init(jax.random.PRNGKey(2))
    loss = lambda p, xx: jnp.sum(jnp.sin(l.apply(
        p, state, (xx, None), Context(train=True))[0][0]))
    want = lambda p, xx: jnp.sum(jnp.sin(reference(p, xx, (8, 16))))
    for a, b in zip(jax.tree_util.tree_leaves(jax.grad(loss, (0, 1))(params, x)),
                    jax.tree_util.tree_leaves(jax.grad(want, (0, 1))(params, x))):
        assert float(jnp.abs(a - b).max()) < 1e-4 * max(
            1.0, float(jnp.abs(b).max()))


def test_a_range_outside_the_experts_is_refused():
    with pytest.raises(ValueError, match="no range"):
        layer((60, 70))


def test_the_dropped_counter_reads_where_the_sort_put_a_pick():
    """`moe_picks_dropped` is held picks less the picks whose row lies
    among their expert's rows: a buffer that ends early or a sort that
    misplaces a pick shows, where a count from the mask alone could
    not."""
    group = jnp.array([2, 0, 1, 0, 2, 1, 0, 2])      # 2 = absent (held 2)
    order = jnp.argsort(group, stable=True)
    inverse = jnp.zeros_like(order).at[order].set(jnp.arange(8))
    sizes = jnp.array([3, 2])
    assert float(moe.picks_placed(group, inverse, sizes)) == 5
    # every row one further on: each expert's last pick leaves its rows
    assert float(moe.picks_placed(group, inverse + 1, sizes)) == 3
    # a buffer of 4 rows: the pick sorted to row 4 has none
    assert float(moe.picks_placed(
        group, jnp.where(inverse < 4, inverse, 8), sizes)) == 4
