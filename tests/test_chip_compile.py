"""The kernels of the timed paths compiled for the chip at the widths
the cells run, by the TPU's compiler for a v5e that is described and
not attached (no chip time, ~2 s a kernel): what Mosaic refuses — a
slice off the tiling, too much VMEM — the interpreter lets pass.

The topology is described inside a fixture, never at import: one
process at a time may load the TPU's library, and every xdist worker
imports every test file. Keep such tests in this ONE file.
"""

import os
from functools import partial

import jax
import jax.numpy as jnp
import pytest
from jax.sharding import SingleDeviceSharding


@pytest.fixture(scope="module")
def one_chip():
    from jax.experimental import topologies

    os.environ.setdefault("TPU_LOG_DIR", "disabled")
    try:
        topo = topologies.get_topology_desc(
            platform="tpu", topology_name="v5e:2x2")
    except Exception as e:  # no TPU compiler here, or its library is held
        pytest.skip(f"no v5e:2x2 topology can be described here: {e}")
    return SingleDeviceSharding(topo.devices[0])


@pytest.mark.parametrize("state", ["float32", "bfloat16"])
def test_ssm_scan_compiles_for_a_v5e_at_the_jamba_cells_chunk(
        one_chip, state):
    """(1, 512, 5120), N 16: `jamba2_serve_docs`'s chunk program runs
    this 26 times a chunk. Compiled, not interpreted: one Mosaic call
    named `ssm_scan` and no compiled loop of positions beside it."""
    from distributed_model_parallel_tpu.ops.ssm_scan import (
        selective_scan_kernel,
    )

    rows, t, d, n = 1, 512, 5120, 16
    arg = lambda shape, dtype=jnp.float32: jax.ShapeDtypeStruct(
        shape, dtype, sharding=one_chip)
    text = jax.jit(
        partial(selective_scan_kernel, interpret=False)
    ).lower(
        arg((rows, t, d)), arg((rows, t, d)), arg((n, d)),
        arg((rows, t, n)), arg((rows, t, n)),
        arg((rows, n, d), jnp.dtype(state)), arg((rows, t), jnp.bool_),
    ).compile().as_text()
    assert text.count("tpu_custom_call") == 1 and "ssm_scan" in text
    assert " while(" not in text


def test_latent_decode_attention_compiles_for_a_v5e_at_the_glm_cells_widths(
        one_chip, monkeypatch):
    """32 slots of 20 heads over a pool of 6144 pages of 64 rows of
    640 stored values, a block table of 256 pages a slot:
    `glm47f_serve_longdoc`'s decode step runs this 7 times. Compiled,
    not interpreted: one Mosaic call named `paged_attention`, and no
    gathered view of every slot's whole window beside it."""
    from distributed_model_parallel_tpu.ops import latent_attention as LA

    monkeypatch.setattr(LA, "_on_tpu", lambda: True)
    dims = LA.LatentDims(heads=20, rank=512, nope=192, rope=64, dv=256,
                         theta=1e6, scale=256 ** -0.5)
    assert LA.decode_kind(640, 64, 256) == "kernel"
    assert LA.decode_kind(576, 64, 256) == "gather"
    bf16 = jnp.bfloat16
    arg = lambda shape, dtype=bf16: jax.ShapeDtypeStruct(
        shape, dtype, sharding=one_chip)
    # (the tests' default of `highest` products is not the program's,
    # and Mosaic refuses the kernel's own product under it)
    with jax.default_matmul_precision("default"):
        text = jax.jit(partial(LA.paged_decode_attention, dims=dims)).lower(
            arg((32, 1, 20, 192)), arg((32, 1, 20, 64)),
            arg((6144, 64, 640)), arg((32, 256), jnp.int32),
            arg((32,), jnp.int32), arg((32,), jnp.bool_),
            arg((512, 20 * 448)),
        ).compile().as_text()
    assert text.count("tpu_custom_call") == 1 and "paged_attention" in text
    assert "bf16[32,16384,640]" not in text and "[8192,64,640]" not in text


@pytest.mark.parametrize(
    "cell, rows, dim, vocab",
    [("gpt2s_train", 16384, 768, 50257),
     ("gpt2xl_train_fsdp4", 2048, 1600, 50257),
     ("kimilin_train_8k", 16384, 2304, 20480)],
)
def test_head_loss_compiles_for_a_v5e_at_the_training_cells_shapes(
        one_chip, monkeypatch, cell, rows, dim, vocab):
    """A chip's rows of a step, the head's width and vocabulary of the
    three training cells: the selector picks the kernels there, forward
    and backward compile inside their fast-memory limit (1,600 is not
    whole lanes, 50,257 not whole tiles), every Mosaic call names itself
    `head_loss`, and the one float32 array of the whole plane is the
    kept logits: nothing lays it out again or keeps a gradient its
    size."""
    from distributed_model_parallel_tpu.ops import head_loss as HL

    monkeypatch.setattr(HL, "_on_tpu", lambda: True)
    assert HL.head_loss_kind(rows) == "kernel"

    def step(h, w, labels):
        def loss(h, w):
            m = HL.head_loss(h, w, labels)
            return m["loss_sum"], m

        return jax.value_and_grad(loss, argnums=(0, 1), has_aux=True)(h, w)

    arg = lambda shape, dtype: jax.ShapeDtypeStruct(
        shape, dtype, sharding=one_chip)
    with jax.default_matmul_precision("default"):
        text = jax.jit(step).lower(
            arg((rows, dim), jnp.bfloat16), arg((dim, vocab), jnp.float32),
            arg((rows,), jnp.int32),
        ).compile().as_text()
    calls = [ln for ln in text.splitlines() if "tpu_custom_call" in ln]
    stretches = -(-rows // HL._stretch_rows(rows, dim))
    assert len(calls) == 1 + stretches
    assert all("head_loss" in ln for ln in calls)
    # the plane is the forward call's result and the backward calls'
    # operand, and nothing else's
    tile_v = HL._tiles(dim)[1]
    plane = f"f32[{rows},{-(-vocab // tile_v) * tile_v}]"
    touching = [ln for ln in text.splitlines() if plane in ln]
    assert touching and (
        vocab % tile_v == 0 or f"f32[{rows},{vocab}]" not in text
    )
    assert all(
        "tpu_custom_call" in ln or " get-tuple-element(" in ln
        for ln in touching
    ), touching
