"""The chunked gated delta rule (`ops/delta_rule.py`) against the
recurrence one token a step: lengths that are and are not a multiple
of the chunk, no decay, mild decay, and a decay so strong that any
`exp(-G)` would overflow float32."""

import jax
import jax.numpy as jnp
import pytest

from distributed_model_parallel_tpu.ops.delta_rule import (
    gated_delta_rule,
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


@pytest.mark.parametrize("decay", [0.0, 0.05, 20.0])
@pytest.mark.parametrize("t,chunk", [(64, 64), (128, 64), (100, 64),
                                     (37, 16), (5, 16)])
def test_chunked_equals_token_by_token(t, chunk, decay):
    args = inputs(t, decay)
    chunked = gated_delta_rule(*args, chunk=chunk)
    stepwise = gated_delta_rule_stepwise(*args)
    assert chunked.shape == stepwise.shape == (2, t, 3, 8)
    assert bool(jnp.isfinite(chunked).all())
    assert float(jnp.abs(chunked - stepwise).max()) < 5e-6


@pytest.mark.parametrize("decay", [0.0, 0.05, 20.0])
def test_chunked_gradients_equal_token_by_token(decay):
    args = inputs(100, decay, seed=1)
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
