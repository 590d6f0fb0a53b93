"""The selective scan, its one-token step and the carried convolution
(`ops/ssm_scan.py`) against the recurrence written token by token."""

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from distributed_model_parallel_tpu.ops import ssm_scan
from distributed_model_parallel_tpu.ops.ssm_scan import (
    conv_carry,
    selective_scan,
    selective_step,
)

B, T, D, N, K = 2, 37, 24, 4, 4


@pytest.fixture(scope="module")
def inputs():
    ks = jax.random.split(jax.random.PRNGKey(7), 8)
    return {
        "x": jax.random.normal(ks[0], (B, T, D)),
        "delta": jax.nn.softplus(jax.random.normal(ks[1], (B, T, D)) - 1.0),
        "a": -jnp.exp(jax.random.normal(ks[2], (N, D))),
        "b": jax.random.normal(ks[3], (B, T, N)),
        "c": jax.random.normal(ks[4], (B, T, N)),
        "h0": jax.random.normal(ks[5], (B, N, D)),
        "u": jax.random.normal(ks[6], (B, T, D)),
        "w": jax.random.normal(ks[7], (K, D)),
    }


def token_by_token(x, delta, a, b, c, h0):
    """The recurrence as the equations state it, in numpy float64."""
    x, delta, a, b, c = (np.asarray(v, np.float64) for v in (x, delta, a, b, c))
    h = np.asarray(h0, np.float64).copy()
    ys = []
    for t in range(x.shape[1]):
        factor = np.exp(delta[:, t, None, :] * a[None])
        h = factor * h + (delta[:, t] * x[:, t])[:, None, :] * b[:, t, :, None]
        ys.append(np.einsum("bnd,bn->bd", h, c[:, t]))
    return np.stack(ys, axis=1), h


def scan(i, h0=None, valid=None):
    return selective_scan(
        i["x"], i["delta"], i["a"], i["b"], i["c"],
        i["h0"] if h0 is None else h0, valid)


@pytest.mark.parametrize("unroll", [1, 8, 64])
def test_scan_equals_the_token_by_token_recurrence(inputs, unroll,
                                                   monkeypatch):
    monkeypatch.setattr(ssm_scan, "UNROLL", unroll)
    y, h = scan(inputs)
    want_y, want_h = token_by_token(*(inputs[k] for k in
                                      ("x", "delta", "a", "b", "c", "h0")))
    assert y.dtype == h.dtype == jnp.float32
    np.testing.assert_allclose(y, want_y, rtol=1e-5, atol=1e-5)
    np.testing.assert_allclose(h, want_h, rtol=1e-5, atol=1e-5)


def test_one_token_step_is_the_scans_body(inputs):
    i = inputs
    y, h = selective_step(
        i["x"][:, 0], i["delta"][:, 0], i["a"], i["b"][:, 0], i["c"][:, 0],
        i["h0"])
    want_y, want_h = scan({k: (v[:, :1] if k in "x delta b c".split() else v)
                           for k, v in i.items()})
    np.testing.assert_allclose(y, want_y[:, 0], rtol=1e-6, atol=1e-6)
    np.testing.assert_allclose(h, want_h, rtol=1e-6, atol=1e-6)


@pytest.mark.parametrize("cuts", [(16,), (5, 6, 30), (1, 2, 3, 36)])
def test_chunk_by_chunk_with_carried_state_equals_one_pass(inputs, cuts):
    want_y, want_h = scan(inputs)
    h, ys, at = inputs["h0"], [], 0
    for cut in (*cuts, T):
        part = {k: (v[:, at:cut] if k in "x delta b c".split() else v)
                for k, v in inputs.items()}
        y, h = scan(part, h0=h)
        ys.append(y)
        at = cut
    np.testing.assert_allclose(jnp.concatenate(ys, 1), want_y, rtol=1e-5,
                               atol=1e-5)
    np.testing.assert_allclose(h, want_h, rtol=1e-5, atol=1e-5)


@pytest.mark.parametrize("n_valid", [0, 1, 20, T])
def test_a_masked_tail_leaves_the_state_as_it_was(inputs, n_valid):
    valid = jnp.broadcast_to(jnp.arange(T)[None] < n_valid, (B, T))
    y, h = scan(inputs, valid=valid)
    head = {k: (v[:, :n_valid] if k in "x delta b c".split() else v)
            for k, v in inputs.items()}
    if n_valid:
        want_y, want_h = scan(head)
        np.testing.assert_allclose(y[:, :n_valid], want_y, rtol=1e-5,
                                   atol=1e-6)
        np.testing.assert_allclose(h, want_h, rtol=1e-5, atol=1e-6)
    else:
        # exactly: a factor of 1 and an input of 0 change no bit
        np.testing.assert_array_equal(np.asarray(h), np.asarray(inputs["h0"]))


def test_the_state_keeps_the_dtype_it_is_held_in(inputs):
    _, h32 = scan(inputs)
    _, h16 = scan(inputs, h0=inputs["h0"].astype(jnp.bfloat16))
    assert h32.dtype == jnp.float32 and h16.dtype == jnp.bfloat16
    err = np.abs(np.asarray(h16, np.float32) - np.asarray(h32)).max()
    assert 1e-4 < err < 0.2  # rounded at every position: seen, not wild


def test_no_underflow_over_a_long_stretch():
    """Factors multiplied in one at a time stay finite where a running
    product divided back out would be 0 / 0 after a few hundred steps."""
    t = 2048
    x = jnp.ones((1, t, 8))
    delta = jnp.full((1, t, 8), 0.3)
    a = -jnp.arange(1.0, 5.0)[:, None] * jnp.ones((4, 8)) * 4.0
    bc = jnp.ones((1, t, 4))
    y, h = selective_scan(x, delta, a, bc, bc, jnp.zeros((1, 4, 8)))
    assert np.isfinite(np.asarray(y)).all() and np.isfinite(np.asarray(h)).all()
    # the fixed point of h = f h + 0.3 with f = exp(0.3 a)
    f = np.exp(0.3 * np.asarray(a))
    np.testing.assert_allclose(h[0], 0.3 / (1 - f), rtol=1e-5)


def conv_whole(u, w, bias):
    u, w = np.asarray(u, np.float64), np.asarray(w, np.float64)
    padded = np.pad(u, ((0, 0), (K - 1, 0), (0, 0)))
    return np.asarray(bias, np.float64) + sum(
        padded[:, k:k + u.shape[1]] * w[k] for k in range(K))


@pytest.mark.parametrize("cuts", [(), (16,), (1, 2, 3), (2, 35)])
def test_conv_chunk_by_chunk_equals_one_pass(inputs, cuts):
    u, w = inputs["u"], inputs["w"]
    bias = jnp.linspace(-1, 1, D)
    kept, ys, at = jnp.zeros((B, K - 1, D)), [], 0
    for cut in (*cuts, T):
        y, kept = conv_carry(u[:, at:cut], w, bias, kept,
                             jnp.full((B,), cut - at, jnp.int32))
        ys.append(y)
        at = cut
    np.testing.assert_allclose(jnp.concatenate(ys, 1), conv_whole(u, w, bias),
                               rtol=1e-5, atol=1e-5)
    np.testing.assert_array_equal(np.asarray(kept), np.asarray(u[:, -(K - 1):]))


@pytest.mark.parametrize("n_valid", [0, 1, 2, 3, 10])
def test_conv_keeps_the_last_valid_inputs_not_the_padded_tail(inputs, n_valid):
    u = inputs["u"]
    before = jnp.arange(B * (K - 1) * D, dtype=jnp.float32).reshape(B, K - 1, D)
    _, kept = conv_carry(u, inputs["w"], jnp.zeros((D,)), before,
                         jnp.full((B,), n_valid, jnp.int32))
    line = np.concatenate([np.asarray(before), np.asarray(u)], axis=1)
    np.testing.assert_array_equal(
        np.asarray(kept), line[:, n_valid:n_valid + K - 1])
