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
