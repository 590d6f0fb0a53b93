"""`flash_attention` with v narrower than q.k (latent attention's 192 /
128) against dense attention, forward and backward, through the kernels
in interpret mode; and with equal widths the kernel it always emitted."""

from functools import partial

import jax
import jax.numpy as jnp
import pytest

from distributed_model_parallel_tpu.ops.attention import (
    dot_product_attention,
)
from distributed_model_parallel_tpu.ops.pallas_attention import (
    flash_attention,
)
from distributed_model_parallel_tpu.ops.ring_attention import (
    ring_flash_attention,
)


def qkv(dh, dv, t=128, b=1, h=2, seed=0):
    ks = jax.random.split(jax.random.PRNGKey(seed), 3)
    return (jax.random.normal(ks[0], (b, t, h, dh)),
            jax.random.normal(ks[1], (b, t, h, dh)),
            jax.random.normal(ks[2], (b, t, h, dv)))


@pytest.mark.parametrize("dh,dv", [(192, 128), (48, 32), (64, 64)])
def test_flash_forward_and_backward_with_a_narrower_value(dh, dv):
    q, k, v = qkv(dh, dv)
    flash = partial(flash_attention, causal=True, block_q=64, block_k=64,
                    scale=dh ** -0.5)
    dense = partial(dot_product_attention, causal=True, scale=dh ** -0.5)
    out = flash(q, k, v)
    assert out.shape == (1, 128, 2, dv)
    assert float(jnp.abs(out - dense(q, k, v)).max()) < 2e-5
    weight = jnp.sin(jnp.arange(float(dv)))
    grads = lambda fn: jax.grad(
        lambda *a: jnp.sum(fn(*a) * weight), argnums=(0, 1, 2))(q, k, v)
    for name, a, b in zip("qkv", grads(flash), grads(dense)):
        assert a.shape == b.shape
        assert float(jnp.abs(a - b).max()) < 1e-4, name


def test_ring_flash_on_one_shard_takes_the_narrower_value():
    """What the LM engine calls with 'seq' = 1."""
    from jax.sharding import Mesh, PartitionSpec as P

    from distributed_model_parallel_tpu.runtime.compat import shard_map

    q, k, v = qkv(192, 128, t=64)
    mesh = Mesh(jax.devices()[:1], ("seq",))
    ring = shard_map(
        partial(ring_flash_attention, axis_name="seq", causal=True,
                scale=192 ** -0.5),
        mesh=mesh, in_specs=(P(), P(), P()), out_specs=P(),
        check_vma=False,
    )
    dense = partial(dot_product_attention, causal=True, scale=192 ** -0.5)
    assert float(jnp.abs(ring(q, k, v) - dense(q, k, v)).max()) < 2e-5
    ga = jax.grad(lambda *a: jnp.sum(ring(*a) ** 2), argnums=(0, 1, 2))(q, k, v)
    gb = jax.grad(lambda *a: jnp.sum(dense(*a) ** 2), argnums=(0, 1, 2))(q, k, v)
    for a, b in zip(ga, gb):
        assert float(jnp.abs(a - b).max()) < 2e-4


def test_equal_widths_emit_the_same_kernels_as_before():
    """The forward kernel's operand and result shapes with dh == dv are
    what they were: the value's width only replaces dh where it was
    v's. (`gpt2s_train` may not move.)"""
    q, k, v = qkv(64, 64)
    text = jax.jit(partial(flash_attention, causal=True, block_q=64,
                           block_k=64)).lower(q, k, v).as_text()
    assert "1x2x128x64" in text and "x192" not in text
