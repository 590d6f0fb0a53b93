"""The selective scan, its one-token step and the carried convolution
(`ops/ssm_scan.py`) against the recurrence written token by token; the
scan as one kernel (through the Pallas interpreter, at toy sizes)
against the scan as a loop, and the selector between the two."""

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from distributed_model_parallel_tpu.ops import ssm_scan
from distributed_model_parallel_tpu.ops.ssm_scan import (
    conv_carry,
    scan_kind,
    selective_scan,
    selective_scan_kernel,
    selective_scan_loop,
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


# ------------------------------------------------------- the kernel
# (T, D, N) the kernel tiles, with the blocks (positions, channels) it
# is given here: several stretches and several channel blocks a row.
KERNEL_SHAPES = [
    pytest.param((64, 256, 8), (16, 128), id="4x2-blocks"),
    pytest.param((32, 384, 16), (8, 128), id="4x3-blocks-N16"),
    pytest.param((128, 128, 8), (128, 128), id="one-block"),
    pytest.param((48, 512, 8), (48, 256), id="one-stretch-2-tiles-a-block"),
]


def kernel_inputs(t, d, n, rows=B, seed=11):
    ks = jax.random.split(jax.random.PRNGKey(seed), 6)
    return {
        "x": jax.random.normal(ks[0], (rows, t, d)),
        "delta": jax.nn.softplus(jax.random.normal(ks[1], (rows, t, d)) - 1.0),
        "a": -jnp.exp(jax.random.normal(ks[2], (n, d))),
        "b": jax.random.normal(ks[3], (rows, t, n)),
        "c": jax.random.normal(ks[4], (rows, t, n)),
        "h0": jax.random.normal(ks[5], (rows, n, d)),
    }


def both(i, monkeypatch, blocks, h0=None, valid=None):
    """((y, h) of the loop, (y, h) of the kernel) on the same inputs."""
    monkeypatch.setattr(ssm_scan, "BLOCK_T", blocks[0])
    monkeypatch.setattr(ssm_scan, "BLOCK_D", blocks[1])
    args = (i["x"], i["delta"], i["a"], i["b"], i["c"],
            i["h0"] if h0 is None else h0, valid)
    return selective_scan_loop(*args), selective_scan_kernel(*args)


def assert_equal_float32(got, want):
    # the same arithmetic per element; y sums its N terms in another order
    for g, w in zip(got, want):
        assert g.dtype == w.dtype and g.shape == w.shape
        scale = float(jnp.max(jnp.abs(w.astype(jnp.float32))))
        np.testing.assert_allclose(
            np.asarray(g, np.float32), np.asarray(w, np.float32),
            rtol=1e-6, atol=1e-6 * scale)


@pytest.mark.parametrize("shape, blocks", KERNEL_SHAPES)
def test_kernel_equals_the_loop_from_a_state_that_is_not_zero(
        shape, blocks, monkeypatch):
    want, got = both(kernel_inputs(*shape), monkeypatch, blocks)
    assert got[0].dtype == got[1].dtype == jnp.float32
    assert_equal_float32(got, want)


@pytest.mark.parametrize("cuts", [(32,), (8, 24, 56)])
def test_kernel_chunk_by_chunk_with_carried_state_equals_one_pass(
        cuts, monkeypatch):
    t = 64
    i = kernel_inputs(t, 256, 8)
    (want_y, want_h), _ = both(i, monkeypatch, (8, 128))
    h, ys, at = i["h0"], [], 0
    for cut in (*cuts, t):
        part = {k: (v[:, at:cut] if k in "x delta b c".split() else v)
                for k, v in i.items()}
        y, h = selective_scan_kernel(
            part["x"], part["delta"], part["a"], part["b"], part["c"], h)
        ys.append(y)
        at = cut
    assert_equal_float32((jnp.concatenate(ys, 1), h), (want_y, want_h))


@pytest.mark.parametrize("n_valid", [0, 1, 13, 40])
def test_kernel_masked_tail_leaves_the_state_as_it_was(n_valid, monkeypatch):
    t = 40
    i = kernel_inputs(t, 128, 8)
    valid = jnp.arange(t)[None] < jnp.asarray([[n_valid], [t]])
    want, got = both(i, monkeypatch, (8, 128), valid=valid)
    assert_equal_float32(got, want)
    if n_valid == 0:
        # exactly: a factor of 1 and an input of 0 change no bit
        np.testing.assert_array_equal(
            np.asarray(got[1][0]), np.asarray(i["h0"][0]))


def test_kernel_keeps_the_state_in_the_dtype_it_is_held_in(monkeypatch):
    i = kernel_inputs(32, 128, 16)
    want, got = both(i, monkeypatch, (16, 128),
                     h0=i["h0"].astype(jnp.bfloat16))
    assert got[1].dtype == jnp.bfloat16 and got[0].dtype == jnp.float32
    # rounded at every position, as the loop rounds it: the same bits
    np.testing.assert_array_equal(
        np.asarray(got[1], np.float32), np.asarray(want[1], np.float32))


def test_kernel_does_not_underflow_over_a_long_stretch(monkeypatch):
    t, d, n = 2048, 128, 8
    monkeypatch.setattr(ssm_scan, "BLOCK_T", 256)
    x = jnp.ones((1, t, d))
    delta = jnp.full((1, t, d), 0.3)
    a = -jnp.arange(1.0, n + 1)[:, None] * jnp.ones((n, d)) * 4.0
    bc = jnp.ones((1, t, n))
    y, h = selective_scan_kernel(x, delta, a, bc, bc, jnp.zeros((1, n, d)))
    assert np.isfinite(np.asarray(y)).all() and np.isfinite(np.asarray(h)).all()
    f = np.exp(0.3 * np.asarray(a))
    np.testing.assert_allclose(h[0], 0.3 / (1 - f), rtol=1e-5)


def test_kernel_refuses_widths_that_do_not_tile():
    i = kernel_inputs(37, 24, 4)
    with pytest.raises(ValueError, match="do not tile"):
        selective_scan_kernel(i["x"], i["delta"], i["a"], i["b"], i["c"],
                              i["h0"])


# ----------------------------------------------------- the selector
def traced(fn, i):
    # (a fresh function each time: a trace is cached by function and
    # shapes, and the selector's answer is not part of that key)
    return str(jax.make_jaxpr(lambda *args: fn(*args))(
        i["x"], i["delta"], i["a"], i["b"], i["c"], i["h0"]))


def test_off_a_tpu_the_scan_is_the_loop_bit_for_bit(inputs):
    big = kernel_inputs(512, 256, 16, rows=1)
    for i in (inputs, big):
        t, d = i["x"].shape[1:]
        assert scan_kind(t, d, i["a"].shape[0]) == "loop"
        graph = traced(selective_scan, i)
        assert "pallas_call" not in graph
        assert graph == traced(selective_scan_loop, i)
    got, want = scan(inputs), selective_scan_loop(
        *(inputs[k] for k in ("x", "delta", "a", "b", "c", "h0")))
    for g, w in zip(got, want):
        np.testing.assert_array_equal(np.asarray(g), np.asarray(w))


@pytest.mark.parametrize("t, d, n, want", [
    pytest.param(512, 5120, 16, "kernel", id="the-cells-chunk"),
    pytest.param(64, 128, 8, "kernel", id="the-shortest-that-pays"),
    pytest.param(1, 5120, 16, "loop", id="one-position-the-decode-step"),
    pytest.param(56, 5120, 16, "loop", id="a-short-stretch"),
    pytest.param(250, 5120, 16, "loop", id="positions-not-whole-sublanes"),
    pytest.param(512, 5119, 16, "loop", id="a-prime-width"),
    pytest.param(512, 5120, 4, "loop", id="a-state-of-half-a-sublane-tile"),
])
def test_on_a_tpu_the_selector_picks_from_the_stretch_and_the_widths(
        t, d, n, want, monkeypatch):
    monkeypatch.setattr(ssm_scan, "_on_tpu", lambda: True)
    assert scan_kind(t, d, n) == want
    if t * d <= 512 * 256:  # trace the small ones: one kernel or none
        graph = traced(selective_scan, kernel_inputs(t, d, n, rows=1))
        assert graph.count("pallas_call") == (want == "kernel")


def test_on_a_tpu_a_chunk_traces_one_kernel_named_ssm_scan(monkeypatch):
    monkeypatch.setattr(ssm_scan, "_on_tpu", lambda: True)
    graph = traced(selective_scan, kernel_inputs(512, 256, 16, rows=1))
    assert graph.count("pallas_call") == 1 and "ssm_scan" in graph
    # and no loop of `selective_step` beside it
    assert f"unroll={ssm_scan.UNROLL}" not in graph
    assert f"unroll={ssm_scan.UNROLL}" in traced(
        selective_scan_loop, kernel_inputs(512, 256, 16, rows=1))


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
