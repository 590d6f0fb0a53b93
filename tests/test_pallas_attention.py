"""Pallas flash-attention kernel tests (interpret mode on the CPU mesh;
the same kernel compiles natively on TPU)."""

import functools

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from distributed_model_parallel_tpu.ops.attention import (
    dot_product_attention,
)
from distributed_model_parallel_tpu.ops import pallas_attention
from distributed_model_parallel_tpu.ops.pallas_attention import (
    flash_attention,
    local_attention_kind,
    local_causal_attention,
)

B, T, H, DH = 2, 256, 4, 32


def _qkv(seed=0, dtype=jnp.float32, t=T):
    rng = np.random.RandomState(seed)
    mk = lambda: jnp.asarray(rng.randn(B, t, H, DH).astype(np.float32), dtype)
    q, k, v = mk(), mk(), mk()
    mask = jnp.asarray(rng.rand(B, t) > 0.2).at[:, 0].set(True)
    return q, k, v, mask


def test_forward_matches_reference():
    q, k, v, mask = _qkv()
    want = dot_product_attention(q, k, v, mask)
    got = flash_attention(q, k, v, mask)
    np.testing.assert_allclose(
        np.asarray(got), np.asarray(want), rtol=1e-5, atol=1e-5
    )


def test_forward_no_mask_and_odd_lengths():
    """Sequence lengths that don't divide the default blocks shrink the
    block size instead of failing."""
    q, k, v, _ = _qkv(seed=2, t=96)  # 96 % 128 != 0
    want = dot_product_attention(q, k, v)
    got = flash_attention(q, k, v)
    np.testing.assert_allclose(
        np.asarray(got), np.asarray(want), rtol=1e-5, atol=1e-5
    )


def test_multiple_k_blocks_exercise_online_softmax():
    q, k, v, mask = _qkv(seed=3)
    want = dot_product_attention(q, k, v, mask)
    got = flash_attention(q, k, v, mask, block_q=64, block_k=64)  # 4 k-steps
    np.testing.assert_allclose(
        np.asarray(got), np.asarray(want), rtol=1e-5, atol=1e-5
    )


def test_bf16_output_dtype():
    q, k, v, mask = _qkv(seed=4, dtype=jnp.bfloat16)
    got = flash_attention(q, k, v, mask)
    assert got.dtype == jnp.bfloat16
    want = dot_product_attention(q, k, v, mask)
    np.testing.assert_allclose(
        np.asarray(got, np.float32), np.asarray(want, np.float32),
        rtol=5e-2, atol=5e-2,
    )


def _grad_check(q, k, v, mask, causal=False, rtol=2e-4, atol=2e-5, **kw):
    def loss_flash(q, k, v):
        return jnp.sum(
            jnp.square(flash_attention(q, k, v, mask, causal=causal, **kw))
        )

    def loss_ref(q, k, v):
        return jnp.sum(
            jnp.square(dot_product_attention(q, k, v, mask, causal=causal))
        )

    got = jax.grad(loss_flash, argnums=(0, 1, 2))(q, k, v)
    want = jax.grad(loss_ref, argnums=(0, 1, 2))(q, k, v)
    for g, w, name in zip(got, want, "qkv"):
        np.testing.assert_allclose(
            np.asarray(g, np.float32), np.asarray(w, np.float32),
            rtol=rtol, atol=atol, err_msg=f"grad wrt {name}",
        )


def test_gradients_match_reference():
    """Fused Pallas backward (dq and dk/dv kernels) gives reference grads
    with a key-validity mask."""
    q, k, v, mask = _qkv(seed=5, t=64)
    _grad_check(q, k, v, mask)


def test_gradients_multiple_blocks():
    """Backward accumulation across several q- and k-blocks."""
    q, k, v, mask = _qkv(seed=9)
    _grad_check(q, k, v, mask, block_q=64, block_k=64)


def test_gradients_causal():
    """Causal backward: the frontier predicate skips dead tiles in both
    kernels without dropping live contributions."""
    q, k, v, _ = _qkv(seed=10)
    _grad_check(q, k, v, None, causal=True, block_q=64, block_k=64)


def test_gradients_causal_with_mask():
    """Causal frontier predicate composed with a key-validity mask, all
    of dq/dk/dv — guards the interaction between _bwd_dkv_step's
    frontier skip and _mask_window."""
    q, k, v, mask = _qkv(seed=13)
    _grad_check(q, k, v, mask, causal=True, block_q=64, block_k=64)


def test_gradients_bf16():
    q, k, v, _ = _qkv(seed=11, dtype=jnp.bfloat16, t=128)
    got = jax.grad(
        lambda q, k, v: jnp.sum(
            flash_attention(q, k, v).astype(jnp.float32) ** 2
        ),
        argnums=(0, 1, 2),
    )(q, k, v)
    want = jax.grad(
        lambda q, k, v: jnp.sum(
            dot_product_attention(q, k, v).astype(jnp.float32) ** 2
        ),
        argnums=(0, 1, 2),
    )(q, k, v)
    for g, w, name in zip(got, want, "qkv"):
        assert g.dtype == jnp.bfloat16
        np.testing.assert_allclose(
            np.asarray(g, np.float32), np.asarray(w, np.float32),
            rtol=1e-1, atol=1e-1, err_msg=f"grad wrt {name}",
        )


def test_gradients_fully_masked_row():
    """A batch row whose keys are ALL masked: forward outputs zeros and
    the fused backward's +inf LSE sentinel produces zero gradients
    instead of NaN."""
    q, k, v, _ = _qkv(seed=12, t=64)
    mask = jnp.ones((B, 64), bool).at[1, :].set(False)
    out = flash_attention(q, k, v, mask)
    np.testing.assert_array_equal(np.asarray(out[1]), 0.0)
    grads = jax.grad(
        lambda q, k, v: jnp.sum(jnp.square(flash_attention(q, k, v, mask))),
        argnums=(0, 1, 2),
    )(q, k, v)
    for g in grads:
        assert bool(jnp.all(jnp.isfinite(g)))
        np.testing.assert_array_equal(np.asarray(g[1]), 0.0)


def test_encoder_layer_with_flash_attention():
    """flash_attention is a drop-in attention_fn for the transformer."""
    from distributed_model_parallel_tpu.models import layers as L
    from distributed_model_parallel_tpu.models.transformer import (
        encoder_layer,
    )

    dim, heads = 32, 4
    flash_layer = encoder_layer(dim, heads, 64, attention_fn=flash_attention)
    ref_layer = encoder_layer(dim, heads, 64)
    params, _ = ref_layer.init(jax.random.PRNGKey(0))
    rng = np.random.RandomState(7)
    hseq = jnp.asarray(rng.randn(B, 64, dim).astype(np.float32))
    mask = jnp.asarray(rng.rand(B, 64) > 0.2).at[:, 0].set(True)
    (want, _), _ = ref_layer.apply(params, {}, (hseq, mask), L.Context())
    (got, _), _ = flash_layer.apply(params, {}, (hseq, mask), L.Context())
    np.testing.assert_allclose(
        np.asarray(got), np.asarray(want), rtol=1e-5, atol=1e-5
    )


def test_general_mask_rejected():
    q, k, v, _ = _qkv(t=64)
    full_mask = jnp.ones((B, 1, 64, 64), bool)
    with pytest.raises(NotImplementedError):
        flash_attention(q, k, v, full_mask)


def test_prime_length_falls_back_to_xla_path():
    """Sequence lengths whose divisors are all < 8 (e.g. primes) take the
    XLA reference path instead of a sub-sublane-block kernel."""
    q, k, v, _ = _qkv(seed=8, t=17)
    want = dot_product_attention(q, k, v)
    got = flash_attention(q, k, v)
    np.testing.assert_allclose(
        np.asarray(got), np.asarray(want), rtol=1e-5, atol=1e-5
    )


def test_tileable_shapes_trace_the_kernel_not_the_reference():
    """No quiet reference: at a length the kernels can tile, forward
    and both backward kernels are `pallas_call`s in the traced step
    (what chip_smoke.py asserts from the lowered text on the chip); the
    documented shape rule (no multiple-of-8 divisor) is the only road
    to the XLA path."""
    q, k, v, mask = _qkv(seed=21, t=64)
    step = jax.grad(
        lambda q: jnp.sum(flash_attention(q, k, v, mask, causal=True) ** 2)
    )
    assert str(jax.make_jaxpr(step)(q)).count("pallas_call") == 3
    q17, k17, v17, _ = _qkv(seed=8, t=17)
    assert "pallas_call" not in str(
        jax.make_jaxpr(lambda q: flash_attention(q, k17, v17))(q17)
    )


def test_flash_dh128_matches_xla():
    """dh=128 (the transformer-base head dim, and the MXU-width lane
    count) through the fused kernels — forward and gradients — matches
    the dense reference; guards the experiments/flash_attention_bench
    dh sweep."""
    from distributed_model_parallel_tpu.ops.attention import (
        dot_product_attention,
    )
    from distributed_model_parallel_tpu.ops.pallas_attention import (
        flash_attention,
    )

    rng = np.random.RandomState(7)
    mk = lambda: jnp.asarray(rng.randn(1, 256, 2, 128).astype(np.float32))
    q, k, v = mk(), mk(), mk()
    want = dot_product_attention(q, k, v)
    got = flash_attention(q, k, v, block_q=128, block_k=128)
    np.testing.assert_allclose(
        np.asarray(got), np.asarray(want), rtol=2e-5, atol=2e-5
    )
    g1 = jax.grad(lambda k: jnp.sum(
        flash_attention(q, k, v, block_q=128, block_k=128) ** 2
    ))(k)
    g2 = jax.grad(lambda k: jnp.sum(
        dot_product_attention(q, k, v) ** 2
    ))(k)
    np.testing.assert_allclose(
        np.asarray(g1), np.asarray(g2), rtol=2e-4, atol=2e-5
    )


# ------------------------------------------- the kernel under L.remat


def _remat_attention(attention_fn):
    """`attention_fn(q, k, v, mask)` as a layer under `L.remat`, the way
    an engine with `remat=True` runs it: the backward pass recomputes
    the forward kernel."""
    from distributed_model_parallel_tpu.models import layers as L

    layer = L.remat(L.Layer(
        lambda rng: ({}, {}),
        lambda p, s, x, ctx: (attention_fn(*x), s),
    ))

    def loss(q, k, v, mask):
        out, _ = layer.apply({}, {}, (q, k, v, mask), L.Context(train=True))
        return jnp.sum(jnp.square(out.astype(jnp.float32))), out

    return jax.jit(jax.value_and_grad(loss, argnums=(0, 1, 2), has_aux=True))


@pytest.mark.parametrize("heads,dh,t,dtype,rtol,atol", [
    # GPT-2 XL's attention as plan fsdp4 runs it on one chip: 25 heads
    # of 64, a padding mask, bf16, remat (gpt2xl_train_fsdp4).
    (25, 64, 128, jnp.bfloat16, 1e-1, 1e-1),
    (25, 64, 128, jnp.float32, 2e-4, 2e-5),
    (4, 32, 256, jnp.bfloat16, 1e-1, 1e-1),
], ids=["xl-25x64-bf16", "xl-25x64-f32", "4x32-bf16"])
def test_causal_masked_under_remat_matches_dense(
    heads, dh, t, dtype, rtol, atol
):
    """Kernel against dense, causal with a padding mask, forward and
    all three gradients, both under `L.remat`."""
    rng = np.random.RandomState(31)
    mk = lambda: jnp.asarray(
        rng.randn(2, t, heads, dh).astype(np.float32), dtype
    )
    q, k, v = mk(), mk(), mk()
    # a padding mask: each row valid up to its own length
    mask = jnp.arange(t)[None, :] < jnp.asarray([[t], [t - 37]])
    ((_, got), got_g) = _remat_attention(
        functools.partial(flash_attention, causal=True)
    )(q, k, v, mask)
    ((_, want), want_g) = _remat_attention(
        functools.partial(dot_product_attention, causal=True)
    )(q, k, v, mask)
    assert got.dtype == dtype
    np.testing.assert_allclose(
        np.asarray(got, np.float32), np.asarray(want, np.float32),
        rtol=rtol, atol=atol,
    )
    for g, w, name in zip(got_g, want_g, "qkv"):
        assert g.dtype == dtype
        np.testing.assert_allclose(
            np.asarray(g, np.float32), np.asarray(w, np.float32),
            rtol=rtol, atol=atol, err_msg=f"grad wrt {name}",
        )


# ------------------- the selector: local_causal_attention (ISSUE 31)


def test_local_attention_off_tpu_is_the_dense_graph_bit_for_bit():
    """On the CPU backend the selector IS
    `dot_product_attention(causal=True)`: the same values to the bit,
    no `pallas_call` in the jaxpr (never the interpreter), whatever the
    shape."""
    q, k, v, mask = _qkv(seed=41, t=64)
    got = local_causal_attention(q, k, v, mask)
    want = dot_product_attention(q, k, v, mask, causal=True)
    np.testing.assert_array_equal(np.asarray(got), np.asarray(want))
    assert local_attention_kind(64, 64, mask) == "dense"
    assert local_attention_kind(1024, 1024, None) == "dense"
    jaxpr = str(jax.make_jaxpr(
        jax.grad(lambda q: jnp.sum(local_causal_attention(q, k, v, mask)))
    )(q))
    assert "pallas_call" not in jaxpr


@pytest.mark.parametrize("t,mask_kind,want", [
    (64, "keys", "flash"),
    (64, "none", "flash"),
    (17, "keys", "dense"),   # a prime length: no tiling
    (64, "logits", "dense"),  # a (B, 1, Tq, Tkv) mask: not the kernel's
], ids=["tileable-keymask", "tileable-nomask", "prime", "4d-mask"])
def test_local_attention_on_tpu_picks_from_shapes(
    monkeypatch, t, mask_kind, want
):
    """With the backend predicate saying "tpu" (the kernels still run in
    the interpreter here): a tileable length under no mask or a (B, Tkv)
    mask traces the kernels, forward and both backward; a prime length
    and a 4-D logit mask take the dense graph. Both agree with dense."""
    monkeypatch.setattr(pallas_attention, "_on_tpu", lambda: True)
    q, k, v, keys = _qkv(seed=43, t=t)
    mask = {
        "keys": keys, "none": None,
        "logits": jnp.broadcast_to(keys[:, None, None, :], (B, 1, t, t)),
    }[mask_kind]
    jaxpr = str(jax.make_jaxpr(jax.grad(
        lambda q: jnp.sum(local_causal_attention(q, k, v, mask) ** 2)
    ))(q))
    assert local_attention_kind(t, t, mask) == want
    assert jaxpr.count("pallas_call") == (3 if want == "flash" else 0)
    np.testing.assert_allclose(
        np.asarray(local_causal_attention(q, k, v, mask)),
        np.asarray(dot_product_attention(q, k, v, mask, causal=True)),
        rtol=1e-5, atol=1e-5,
    )
