"""The sparse expert layer that holds a range of the experts and drops
nothing (`models/moe.py::held_experts_feed_forward`): every pick on one
held expert, every pick on held experts (the buffer's worst case), no
pick on any, and the tie to the whole model: the parts of all 32 shares,
the shared expert counted once, add up to the uncut layer's output,
which is also the plain reference's. And the combine that brings the
picks back (`ops/grouped_matmul.combine_rows`) against the unsort and
float32 sum it replaced."""

import os
import re
import sys
import types

import jax
import jax.numpy as jnp
import numpy as np
import pytest

ROOT = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
sys.path.insert(0, ROOT)

from benchmark.reference import kimi_linear_ref as ref  # noqa: E402
from distributed_model_parallel_tpu.models import moe  # noqa: E402
from distributed_model_parallel_tpu.models.layers import Context  # noqa: E402
from distributed_model_parallel_tpu.ops import grouped_matmul as gm  # noqa: E402

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
        part, _, held, placed, _ = moe.held_experts_part(
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


def picks(seed, n=48):
    """A sorted buffer's routing: held (n, K), order and inverse as
    `held_experts_part` makes them (absent picks behind 4 experts'
    rows), weights (n, K)."""
    kh, kg, kw = jax.random.split(jax.random.PRNGKey(seed), 3)
    held = jax.random.bernoulli(kh, 0.6, (n, K))
    group = jnp.where(held, jax.random.randint(kg, (n, K), 0, 4), 4)
    order = jnp.argsort(group.reshape(-1), stable=True)
    inverse = jnp.zeros_like(order).at[order].set(jnp.arange(n * K))
    return held, order, inverse, jax.random.uniform(kw, (n, K))


def unsort_and_sum(rows, order, inverse, weights, held):
    """What `held_experts_part` did before `combine_rows`: every pick's
    row back in token order, widened, weighted and summed over k."""
    n, k = weights.shape
    back = jnp.take(rows, inverse, axis=0).reshape(n, k, rows.shape[1])
    return jnp.sum(back.astype(jnp.float32)
                   * jnp.where(held, weights, 0.0)[..., None],
                   axis=1).astype(rows.dtype)


@pytest.mark.parametrize("dtype", ["float32", "bfloat16"])
def test_the_combine_equals_the_unsort_and_float32_sum(dtype):
    held, order, inverse, weights = picks(0)
    rows = jax.random.normal(jax.random.PRNGKey(3), (held.size, D), dtype)
    got = jax.jit(gm.combine_rows)(rows, order, inverse, weights, held)
    assert got.dtype == rows.dtype and got.shape == (held.shape[0], D)
    old = jax.jit(unsort_and_sum)(rows, order, inverse, weights, held)
    if dtype == "float32":
        # the picks added in slot order, each product rounded to float32
        back = np.asarray(rows)[np.asarray(inverse)].reshape(
            held.shape + (D,))
        w = np.where(np.asarray(held), np.asarray(weights), 0.0).astype(
            np.float32)
        want = np.zeros((held.shape[0], D), np.float32)
        for j in range(held.shape[1]):
            want = want + back[:, j] * w[:, j, None]
        assert np.array_equal(np.asarray(got), want)
        # jnp.sum adds the same picks in an order of its own
        assert float(jnp.abs(got - old).max()) <= 4 * np.finfo(
            np.float32).eps * float(jnp.abs(old).max())
    else:
        # rounded once from float32 sums that differ at most in their
        # order: within one bfloat16 step of the old result
        step = jnp.abs(jnp.nextafter(old, jnp.inf * jnp.sign(old)) - old)
        wide = lambda a: a.astype(jnp.float32)
        assert bool(jnp.all(jnp.abs(wide(got) - wide(old)) <= wide(step)))


@pytest.mark.parametrize("dtype", ["float32", "bfloat16"])
def test_the_combine_gradients_equal_autodiff_of_the_plain_form(dtype):
    held, order, inverse, weights = picks(1)
    rows = jax.random.normal(jax.random.PRNGKey(4), (held.size, D), dtype)

    # a linear loss: both forms are handed the same cotangent, whatever
    # order their forward sums took
    g = jax.random.normal(jax.random.PRNGKey(5), (held.shape[0], D))

    def grads(fn):
        loss = lambda r, w: jnp.sum(
            fn(r, order, inverse, w, held).astype(jnp.float32) * g)
        return jax.jit(jax.grad(loss, (0, 1)))(rows, weights)

    (d_rows, d_w), (want_rows, want_w) = (
        grads(gm.combine_rows), grads(unsort_and_sum))
    assert d_rows.dtype == rows.dtype and d_w.dtype == weights.dtype
    # a row no held pick owns has no gradient; nor has such a weight
    unowned = ~held.reshape(-1)[order]
    assert float(jnp.abs(d_rows[unowned].astype(jnp.float32)).max()) == 0
    assert float(jnp.abs(d_w[~held]).max()) == 0
    # the rows' gradient is the same products, gathered the other way
    assert bool(jnp.array_equal(d_rows, want_rows))
    assert float(jnp.abs(d_w - want_w).max()) <= 1e-5 * max(
        1.0, float(jnp.abs(want_w).max()))


def test_rows_past_the_live_end_leave_the_combine_finite():
    """The grouped product leaves the rows past its live end unwritten:
    whatever they hold (NaN here) is selected away, forward and in
    both gradients, not weighted by zero."""
    held, order, inverse, weights = picks(2)
    rows = jax.random.normal(jax.random.PRNGKey(5), (held.size, D))
    live = jnp.arange(held.size) < jnp.sum(held)
    poisoned = jnp.where(live[:, None], rows, jnp.nan)
    loss = lambda r, w: jnp.sum(jnp.sin(
        gm.combine_rows(r, order, inverse, w, held)))
    for got, want in zip(
            jax.jit(jax.value_and_grad(loss, (0, 1)))(poisoned, weights),
            jax.jit(jax.value_and_grad(loss, (0, 1)))(
                jnp.where(live[:, None], rows, 0.0), weights)):
        for a, b in zip(jax.tree_util.tree_leaves(got),
                        jax.tree_util.tree_leaves(want)):
            assert bool(jnp.all(jnp.isfinite(a)))
            assert bool(jnp.array_equal(a, b))


def test_an_unwritten_product_tail_leaves_the_layer_finite(x, monkeypatch):
    """The layer with a grouped product that writes NaN past the live
    end, where the kernel writes nothing: its output and every gradient
    are those of the sound layer."""
    l = layer((8, 16))
    params, state = l.init(jax.random.PRNGKey(2))
    loss = lambda p, xx: jnp.sum(jnp.sin(l.apply(
        p, state, (xx, None), Context(train=True))[0][0]))
    run = jax.jit(jax.value_and_grad(loss, (0, 1)))
    want = run(params, x)

    sound = gm.megablox.gmm

    def unwritten_tail(lhs, rhs, group_sizes, *rest):
        out = sound(lhs, rhs, group_sizes, *rest)
        live = jnp.arange(out.shape[0]) < jnp.sum(group_sizes)
        return jnp.where(live[:, None], out, jnp.nan)

    monkeypatch.setattr(gm, "megablox", types.SimpleNamespace(
        gmm=unwritten_tail))
    got = jax.jit(jax.value_and_grad(loss, (0, 1)))(params, x)
    for a, b in zip(jax.tree_util.tree_leaves(got),
                    jax.tree_util.tree_leaves(want)):
        assert bool(jnp.all(jnp.isfinite(a)))
        assert float(jnp.abs(a - b).max()) <= 1e-6 * max(
            1.0, float(jnp.abs(b).max()))


ROWS_SCATTERED = re.compile(r"\w+:\w+\[(\d+),(\d+)\] = scatter-add")


def test_the_layer_gradient_scatters_no_rows():
    """Every row movement of the expert layer's gradient is a gather:
    the only scatter-adds are the grouped product's group bookkeeping
    (a vector), none over a buffer of rows, which the TPU serialises."""
    held, order, inverse, weights = picks(3)
    flat = jax.random.normal(jax.random.PRNGKey(6), (held.shape[0], D))
    l = layer((0, 8))
    params, _ = l.init(jax.random.PRNGKey(0))
    ids = jnp.argsort(jax.random.uniform(
        jax.random.PRNGKey(7), (held.shape[0], E)), axis=-1)[:, :K]
    loss = lambda w, f, ww: jnp.sum(moe.held_experts_part(
        w, f, ids, ww, 0)[0] ** 2)
    text = str(jax.make_jaxpr(jax.grad(loss, (0, 1, 2)))(
        params["experts"], flat, weights))
    assert "custom_vjp_call" in text
    assert not ROWS_SCATTERED.findall(text)
    # the detector sees a row gather's plain transpose
    plain = str(jax.make_jaxpr(jax.grad(lambda r: jnp.sum(
        unsort_and_sum(r, order, inverse, weights, held) ** 2)))(
        jax.random.normal(jax.random.PRNGKey(8), (held.size, D))))
    assert ROWS_SCATTERED.findall(plain)
