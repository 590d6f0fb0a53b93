"""The chunked gated delta rule (`ops/delta_rule.py`) against the
recurrence one token a step: lengths that are and are not a multiple
of the chunk, no decay, mild decay, and a decay so strong that any
`exp(-G)` would overflow float32. The chunk step's work with the state,
two products over stacked rows, against the four products it replaced.
And the chunk's A and B, which come from sub-blocks and matrix
products, against the (C, C, dk) reduction they replaced. The path for
one decay a head (g (..., 1)) against the channel path with g broadcast
and the recurrence, and the channel path held to the program it was."""

import collections

import math

import jax
import jax.numpy as jnp
import pytest

from distributed_model_parallel_tpu.ops.delta_rule import (
    _SUB,
    _chunk_products,
    _chunk_step,
    _chunk_terms,
    _unit_lower_inverse,
    decay_kind,
    gated_delta_rule,
    gated_delta_rule_from,
    gated_delta_rule_step,
    gated_delta_rule_stepwise,
)


def inputs(t, decay, seed=0, b=2, h=3, dk=16, dv=8):
    ks = jax.random.split(jax.random.PRNGKey(seed), 5)
    unit = lambda x: x / jnp.linalg.norm(x, axis=-1, keepdims=True)
    q = unit(jax.random.normal(ks[0], (b, t, h, dk)))
    k = unit(jax.random.normal(ks[1], (b, t, h, dk)))
    v = jax.random.normal(ks[2], (b, t, h, dv))
    g = -decay * jax.random.uniform(
        ks[3], (b, t, h, dk), minval=0.5, maxval=1.5)
    beta = jax.nn.sigmoid(jax.random.normal(ks[4], (b, t, h)))
    return q, k, v, g, beta


# (192, 64, 80.0): every factor between two sub-blocks underflows to 0
CASES = [
    (t, chunk, decay)
    for t, chunk in [(64, 64), (128, 64), (100, 64), (37, 16), (5, 16)]
    for decay in [0.0, 0.05, 20.0]
] + [(192, 64, 80.0)]


@pytest.mark.parametrize("t,chunk,decay", CASES)
def test_chunked_equals_token_by_token(t, chunk, decay):
    args = inputs(t, decay)
    chunked = gated_delta_rule(*args, chunk=chunk)
    stepwise = gated_delta_rule_stepwise(*args)
    assert chunked.shape == stepwise.shape == (2, t, 3, 8)
    assert bool(jnp.isfinite(chunked).all())
    assert float(jnp.abs(chunked - stepwise).max()) < 5e-6


@pytest.mark.parametrize("t,decay", [(100, 0.0), (100, 0.05), (100, 20.0),
                                     (192, 80.0)])
def test_chunked_gradients_equal_token_by_token(t, decay):
    args = inputs(t, decay, seed=1)
    weight = jnp.cos(jnp.arange(8.0))

    def grads(fn):
        return jax.grad(
            lambda *a: jnp.sum(fn(*a) * weight), argnums=(0, 1, 2, 3, 4)
        )(*args)

    for name, a, b in zip("qkvgb", grads(gated_delta_rule),
                          grads(gated_delta_rule_stepwise)):
        assert bool(jnp.isfinite(a).all()), name
        # float32 rounding against the leaf's own scale; under strong
        # decay the decay's gradient is ~1e-5 and the others ~1
        bound = 1e-5 * max(float(jnp.abs(b).max()), 1e-2)
        assert float(jnp.abs(a - b).max()) < bound, (name, decay)


def whole_chunk_products(q, k, g):
    """A and B as `_chunk_step` reduced them before the sub-blocks:
    from exp(G_i - G_j) as one (C, C, dk) tensor."""
    c = q.shape[-2]
    gc = jnp.cumsum(g, axis=-2)
    diff = gc[..., :, None, :] - gc[..., None, :, :]
    lower = jnp.tril(jnp.ones((c, c), jnp.bool_))
    decay = jnp.exp(jnp.where(lower[..., None], diff, -jnp.inf))
    kd = k[..., None, :, :] * decay
    a = jnp.tril(jnp.sum(k[..., :, None, :] * kd, axis=-1), -1)
    return a, jnp.sum(q[..., :, None, :] * kd, axis=-1)


def one_chunk(decay, c=64, dk=128, dv=24, h=2):
    """(q, k, v, g, beta) of one chunk as the scan hands it over:
    (1, h, C, ...)."""
    return tuple(
        jnp.moveaxis(x, 1, 2)
        for x in inputs(c, decay, seed=4, b=1, h=h, dk=dk, dv=dv)
    )


@pytest.mark.parametrize("decay", [0.0, 0.05, 2.0, 20.0, "mixed"])
def test_sub_block_products_equal_the_whole_chunk_reduction(decay):
    """At the benchmark's (C, dk) = (64, 128); "mixed" is a head whose
    even channels never decay and whose odd ones lose e^-40 a token."""
    q, k, _, g, _ = one_chunk(1.0 if decay == "mixed" else decay)
    if decay == "mixed":
        g = jnp.broadcast_to(
            jnp.where(jnp.arange(128) % 2 == 0, 0.0, -40.0), g.shape)
    got, want = _chunk_products(q, k, g), whole_chunk_products(q, k, g)
    for name, x, y in zip("AB", got, want):
        assert x.shape == y.shape == (1, 2, 64, 64)
        assert bool(jnp.isfinite(x).all()), name
        assert float(jnp.abs(x - y).max()) < 5e-6, name
    assert float(jnp.abs(jnp.triu(got[0])).max()) == 0.0
    assert float(jnp.abs(jnp.triu(got[1], 1)).max()) == 0.0


def equations(jaxpr):
    for eqn in jaxpr.eqns:
        yield eqn
        for sub in jax.core.jaxprs_in_params(eqn.params):
            yield from equations(sub)


def test_no_chunk_wide_decay_tensor_and_products_below_the_diagonal():
    """One chunk step at (C, dk, dv) = (64, 128, 24) for one (batch,
    head): nothing it computes is larger than a quarter of C * C * dk,
    and what matrix products over the channels give (other than those
    with the state, which carry dv) covers every entry of A and of B
    below the diagonal sub-blocks, however the pairs are arranged."""
    c, dk, dv = 64, 128, 24
    chunk = one_chunk(0.05, c, dk, dv, h=1)
    jaxpr = jax.make_jaxpr(_chunk_step)(jnp.zeros((1, 1, dk, dv)), chunk)
    largest, below = 0, 0
    for eqn in equations(jaxpr.jaxpr):
        for var in eqn.outvars:
            largest = max(largest, math.prod(var.aval.shape))
        if eqn.primitive.name != "dot_general":
            continue
        (lhs_c, _), _ = eqn.params["dimension_numbers"]
        lhs, rhs = (v.aval.shape for v in eqn.invars)
        if [lhs[i] for i in lhs_c] == [dk] and dv not in lhs + rhs:
            below += math.prod(eqn.outvars[0].aval.shape)
    assert _SUB * _SUB * dk <= largest <= c * c * dk // 4
    # half of what lies off the c / _SUB diagonal sub-blocks, for A and B
    assert below == c * c - c * _SUB


def per_chunk_scan(q, k, v, g, beta, *, chunk):
    """`gated_delta_rule` as it was before its work with the state was
    stacked: four products with S_0 and U a chunk."""
    bsz, t, h, dk = q.shape
    dv = v.shape[-1]
    pad = -t % chunk
    n = (t + pad) // chunk

    def step(s0, x):
        q, k, v, g, beta = (y.astype(jnp.float32) for y in x)
        gc = jnp.cumsum(g, axis=-2)
        eg = jnp.exp(gc)
        a, b = _chunk_products(q, k, g)
        rhs = beta[..., None] * jnp.concatenate([v, k * eg], axis=-1)
        solved = jnp.matmul(_unit_lower_inverse(beta[..., None] * a), rhs,
                            precision="highest")
        mm = lambda x, y: jnp.matmul(x, y, precision="highest")
        u = solved[..., :dv] - mm(solved[..., dv:], s0)
        out = mm(q * eg, s0) + mm(b, u)
        g_end = gc[..., -1:, :]
        new = jnp.swapaxes(jnp.exp(g_end), -1, -2) * s0 + mm(
            jnp.swapaxes(k * jnp.exp(g_end - gc), -1, -2), u)
        return new, out

    def chunks(x):
        x = jnp.pad(x, [(0, 0), (0, pad)] + [(0, 0)] * (x.ndim - 2))
        x = x.reshape((bsz, n, chunk) + x.shape[2:])
        return jnp.moveaxis(jnp.moveaxis(x, 1, 0), 2, 3)

    _, out = jax.lax.scan(
        jax.checkpoint(step), jnp.zeros((bsz, h, dk, dv)),
        tuple(chunks(x) for x in (q, k, v, g, beta)))
    out = jnp.moveaxis(jnp.moveaxis(out, 0, 1), 2, 3)
    return out.reshape(bsz, n * chunk, h, dv)[:, :t]


def repeated_keys(args):
    """Every token of a sequence writes under one of two keys."""
    q, k, v, g, beta = args
    return q, jnp.where((jnp.arange(k.shape[1]) % 3 == 0)[:, None, None],
                        k[:, :1], k[:, 1:2]), v, g, beta


# (T, chunk): 13 chunks of 16, 3 of 64 (T a multiple of neither), 5 of
# 8, and 2 whole chunks of 64 with no decay
@pytest.mark.parametrize("keys", ["random", "repeated"])
@pytest.mark.parametrize("t,chunk,decay", [
    (200, 16, 0.05), (200, 16, 20.0), (150, 64, 0.05), (150, 64, 20.0),
    (37, 8, 0.05), (128, 64, 0.0),
])
def test_stacked_state_step_equals_four_products_and_token_by_token(
        t, chunk, decay, keys):
    args = inputs(t, decay, seed=5)
    if keys == "repeated":
        args = repeated_keys(args)
    weight = jnp.cos(jnp.arange(8.0))
    stacked = lambda *a: gated_delta_rule(*a, chunk=chunk)
    four = lambda *a: per_chunk_scan(*a, chunk=chunk)
    outs = [fn(*args) for fn in (stacked, four, gated_delta_rule_stepwise)]
    assert bool(jnp.isfinite(outs[0]).all())
    assert float(jnp.abs(outs[0] - outs[1]).max()) < 1e-6
    assert float(jnp.abs(outs[0] - outs[2]).max()) < 5e-6

    def grads(fn):
        return jax.grad(
            lambda *a: jnp.sum(fn(*a) * weight), argnums=(0, 1, 2, 3, 4)
        )(*args)

    for name, a, b, c in zip("qkvgb", grads(stacked), grads(four),
                             grads(gated_delta_rule_stepwise)):
        assert bool(jnp.isfinite(a).all()), name
        scale = max(float(jnp.abs(c).max()), 1e-2)
        assert float(jnp.abs(a - b).max()) < 1e-6 * scale, name
        assert float(jnp.abs(a - c).max()) < 1e-5 * scale, name


def scans(jaxpr):
    return [e for e in equations(jaxpr) if e.primitive.name == "scan"]


@pytest.mark.parametrize("chunks", [1, 3, 13])
def test_one_scan_of_a_step_per_chunk(chunks):
    """The op's whole program holds ONE scan, a step a chunk: the work
    with the state is not a loop of its own."""
    t, chunk = 8 * chunks - 5, 8
    args = inputs(t, 0.05, b=1, h=1, dk=8, dv=4)
    jaxpr = jax.make_jaxpr(
        lambda *a: gated_delta_rule(*a, chunk=chunk))(*args).jaxpr
    loops = scans(jaxpr)
    assert len(loops) == 1
    assert loops[0].params["length"] == chunks


def test_strong_decay_forgets_the_past():
    """With g <= -20 a step the state holds the current token alone:
    o_t = beta_t (k_t . q_t) v_t."""
    q, k, v, g, beta = inputs(64, 40.0, seed=2)
    out = gated_delta_rule(q, k, v, g, beta)
    want = (beta * jnp.sum(q * k, axis=-1))[..., None] * v
    assert float(jnp.abs(out - want).max()) < 1e-6


def test_bfloat16_state_is_a_measurably_lower_precision():
    """The control the benchmark's limit is set against (its reference
    carries the state in bfloat16 on request; the op has no such mode):
    a state rounded to bfloat16 after every token moves the outputs by
    far more than the chunked form's float32 rounding does."""
    args = inputs(256, 0.05, seed=3)
    exact = gated_delta_rule_stepwise(*args)

    def rounded_state(q, k, v, g, beta):
        def step(s, x):
            qt, kt, vt, gt, bt = x
            s = jnp.exp(gt)[..., None] * s.astype(jnp.float32)
            read = jnp.einsum("bhk,bhkv->bhv", kt, s)
            s = s + (bt[..., None] * kt)[..., None] * (vt - read)[..., None, :]
            return s.astype(jnp.bfloat16), jnp.einsum("bhk,bhkv->bhv", qt, s)

        xs = tuple(jnp.moveaxis(x, 1, 0) for x in (q, k, v, g, beta))
        s0 = jnp.zeros(q.shape[:1] + q.shape[2:] + v.shape[-1:], jnp.bfloat16)
        return jnp.moveaxis(jax.lax.scan(step, s0, xs)[1], 0, 1)

    f32 = gated_delta_rule(*args)
    assert float(jnp.abs(f32 - exact).max()) < 5e-6
    assert float(jnp.abs(rounded_state(*args) - exact).max()) > 1e-3


def test_decay_kind_follows_the_trailing_axis():
    assert decay_kind(jnp.zeros((2, 64, 32, 1))) == "head"
    assert decay_kind(jnp.zeros((2, 64, 32, 128))) == "channel"
    assert decay_kind(jax.ShapeDtypeStruct((3, 1), jnp.float32)) == "head"


def head_decays(decay, t, seed, b=2, h=3):
    """g (b, t, h, 1); "mixed": even heads never decay, odd ones lose
    e^-40 and e^-0.05 on alternate tokens."""
    if decay == "mixed":
        odd = jnp.where(jnp.arange(t) % 2 == 0, -40.0, -0.05)[:, None]
        g = jnp.where(jnp.arange(h) % 2 == 0, 0.0, odd)
        return jnp.broadcast_to(g[None, :, :, None], (b, t, h, 1))
    return -decay * jax.random.uniform(
        jax.random.PRNGKey(seed), (b, t, h, 1), minval=0.5, maxval=1.5)


def stepwise_from(q, k, v, g, beta, s0):
    """The recurrence as written from the state s0, one token a step
    -> (outputs, the state after the last)."""
    def step(s, x):
        out, s = gated_delta_rule_step(*x, s)
        return s, out

    s, out = jax.lax.scan(
        step, s0, tuple(jnp.moveaxis(x, 1, 0) for x in (q, k, v, g, beta)))
    return jnp.moveaxis(out, 0, 1), s


# (T, chunk): T a multiple of no chunk, so every case ends in a padded
# tail; 64 and 128 the chunks the served cell may take
@pytest.mark.parametrize("decay", [0.0, 0.05, 20.0, "mixed"])
@pytest.mark.parametrize("t,chunk", [(150, 64), (150, 128), (37, 16)])
def test_head_path_equals_the_channel_path_and_token_by_token(
        t, chunk, decay):
    """From a nonzero state: outputs, the last state and the gradients
    of both, the head path (g (..., 1)) against the channel path with g
    broadcast over the key channels and against the recurrence."""
    q, k, v, _, beta = inputs(t, 0.0, seed=6)
    g = head_decays(decay, t, seed=7)
    s0 = jax.random.normal(jax.random.PRNGKey(8), (2, 3, 16, 8))
    head = lambda *a: gated_delta_rule_from(*a, chunk=chunk)
    channel = lambda q, k, v, g, beta, s0: gated_delta_rule_from(
        q, k, v, jnp.broadcast_to(g, q.shape), beta, s0, chunk=chunk)
    args = (q, k, v, g, beta, s0)
    runs = [jax.jit(fn)(*args) for fn in (head, channel, stepwise_from)]
    for got, other, want in zip(*runs):
        assert bool(jnp.isfinite(got).all())
        scale = max(float(jnp.abs(want).max()), 1.0)
        assert float(jnp.abs(got - other).max()) < 5e-6 * scale
        assert float(jnp.abs(got - want).max()) < 5e-6 * scale

    w_out = jnp.cos(jnp.arange(8.0))
    w_state = jnp.sin(jnp.arange(16.0 * 8).reshape(16, 8))

    def grads(fn):
        def loss(*a):
            out, state = fn(*a)
            return jnp.sum(out * w_out) + jnp.sum(state * w_state)
        return jax.jit(jax.grad(loss, argnums=tuple(range(6))))(*args)

    for name, a, b, c in zip(("q", "k", "v", "g", "beta", "s0"), grads(head),
                             grads(channel), grads(stepwise_from)):
        assert a.shape == c.shape, name
        assert bool(jnp.isfinite(a).all()), name
        # float32 rounding against the leaf's own scale; under strong
        # decay the decay's gradient (~5e-5) is a difference of terms of
        # ~1, which float32 leaves 2e-7 apart
        bound = 1e-5 * max(float(jnp.abs(c).max()), 2e-2)
        assert float(jnp.abs(a - b).max()) < bound, (name, decay)
        assert float(jnp.abs(a - c).max()) < bound, (name, decay)


def test_head_path_takes_no_tensor_a_key_channel_wide_per_pair():
    """One chunk step at (C, dk, dv) = (64, 128, 24) with one decay a
    head: no exponential wider than the (C, C) decay matrix, nothing
    larger than the (2C, dk) rows that read the state, no sub-block
    reduction, and no cumulative sum: every sum of g comes out of the
    one product that makes the decay matrix."""
    c, dk, dv = 64, 128, 24
    q, k, v, _, beta = one_chunk(0.0, c, dk, dv, h=1)
    g = -jnp.ones(v.shape[:-1] + (1,))
    jaxpr = jax.make_jaxpr(_chunk_step)(
        jnp.zeros((1, 1, dk, dv)), (q, k, v, g, beta))
    largest, exps = 0, []
    for eqn in equations(jaxpr.jaxpr):
        size = max(math.prod(var.aval.shape) for var in eqn.outvars)
        largest = max(largest, size)
        if eqn.primitive.name == "exp":
            exps.append(size)
        assert eqn.primitive.name not in ("reduce_sum", "cumsum"), eqn
    assert largest <= 2 * c * dk
    assert max(exps) == c * c and len(exps) <= 4


# `_chunk_terms` at kimilin_train_8k's shapes ((2, 32) chunks of 64, 128
# a head, bfloat16 q, k, v, a decay a key channel), primitive by
# primitive as the program was before the head path came: the path the
# Kimi cell times is the same program
CHANNEL_PATH = {
    "add": 26, "broadcast_in_dim": 77, "concatenate": 17,
    "convert_element_type": 23, "cumsum": 3, "dot_general": 17, "eq": 1,
    "exp": 13, "gather": 12, "ge": 5, "iota": 12, "jit": 26, "mul": 26,
    "neg": 3, "pad": 15, "reduce_sum": 8, "reshape": 6, "select_n": 9,
    "slice": 57, "squeeze": 9, "sub": 6, "transpose": 4,
}


def test_the_channel_path_is_the_program_it_was():
    f32, bf16 = jnp.float32, jnp.bfloat16
    b, h, c, d = 2, 32, 64, 128
    chunk = tuple(jax.ShapeDtypeStruct(shape, dtype) for shape, dtype in [
        ((b, h, c, d), bf16), ((b, h, c, d), bf16), ((b, h, c, d), bf16),
        ((b, h, c, d), f32), ((b, h, c), f32)])
    jaxpr = jax.make_jaxpr(_chunk_terms)(chunk).jaxpr
    counts = collections.Counter(
        e.primitive.name for e in equations(jaxpr))
    assert dict(counts) == CHANNEL_PATH
    assert len(jaxpr.eqns) == 293
