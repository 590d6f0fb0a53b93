"""Mixed-precision (bf16) path tests, on the 8-virtual-device CPU mesh.

The reference is f32-only CUDA; bf16 compute is TPU-native table stakes
(the MXU's matmul dtype), so every engine grows a `compute_dtype` knob:
activations bf16, params/optimizer/loss f32. These tests pin

* numerical closeness of the bf16 step to the f32 step (bf16 has ~3
  decimal digits; tolerances sized to that),
* that the pipeline wire buffer actually carries bf16 (half the ppermute
  bytes), not silently up-cast f32,
* that integer-input models (BERT) pick up the compute dtype at the
  embedding (`Context.dtype`).
"""

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from distributed_model_parallel_tpu.models import layers as L
from distributed_model_parallel_tpu.models import tinycnn
from distributed_model_parallel_tpu.models.tinycnn import tiny_cnn
from distributed_model_parallel_tpu.parallel.data_parallel import (
    DataParallelEngine,
    DDPEngine,
)
from distributed_model_parallel_tpu.parallel.pipeline import (
    PipelineEngine,
    _wire_dtype,
)
from distributed_model_parallel_tpu.runtime.mesh import MeshSpec, make_mesh
from distributed_model_parallel_tpu.training.optim import SGD

BATCH = 16


def _batch(key, size=BATCH):
    kx, ky = jax.random.split(key)
    images = jax.random.normal(kx, (size, 32, 32, 3))
    labels = jax.random.randint(ky, (size,), 0, 10)
    return images, labels


def _run_steps(engine, n=3, lr=0.05):
    ts = engine.init_state(jax.random.PRNGKey(0))
    images, labels = engine.shard_batch(*_batch(jax.random.PRNGKey(7)))
    losses = []
    for _ in range(n):
        ts, m = engine.train_step(ts, images, labels, lr)
        losses.append(float(m["loss_sum"]) / float(m["count"]))
    return ts, losses


def test_dp_bf16_close_to_f32():
    """GSPMD engine: 3 bf16 steps track the f32 trajectory within bf16
    tolerance, and params stay f32 master copies."""
    mesh = make_mesh(MeshSpec(data=8))
    f32 = DataParallelEngine(tiny_cnn(10), SGD(), mesh, donate=False)
    bf16 = DataParallelEngine(
        tiny_cnn(10), SGD(), mesh, donate=False,
        compute_dtype=jnp.bfloat16,
    )
    _, losses_f32 = _run_steps(f32)
    ts_bf16, losses_bf16 = _run_steps(bf16)
    np.testing.assert_allclose(losses_bf16, losses_f32, rtol=5e-2)
    assert losses_bf16[-1] < losses_bf16[0]
    for leaf in jax.tree_util.tree_leaves(ts_bf16.params):
        assert leaf.dtype == jnp.float32


def test_ddp_bf16_close_to_f32():
    """shard_map engine (explicit pmean all-reduce), SyncBN, bf16."""
    mesh = make_mesh(MeshSpec(data=8))
    f32 = DDPEngine(tiny_cnn(10), SGD(), mesh, sync_bn=True, donate=False)
    bf16 = DDPEngine(
        tiny_cnn(10), SGD(), mesh, sync_bn=True, donate=False,
        compute_dtype=jnp.bfloat16,
    )
    _, losses_f32 = _run_steps(f32)
    _, losses_bf16 = _run_steps(bf16)
    np.testing.assert_allclose(losses_bf16, losses_f32, rtol=5e-2)


@pytest.mark.slow
def test_pipeline_bf16_close_to_f32():
    """4-stage pipeline, bf16 activations over the ppermute wire.
    `slow` (tier-1 budget): test_pipeline_bf16_stage_local_combo below
    keeps the pipeline+bf16 wire coverage in tier-1 (same engine, plus
    the stage-local layout)."""
    mesh = make_mesh(MeshSpec(data=2, stage=4))
    stages = tinycnn.split_stages(4, 10)
    f32 = PipelineEngine(
        stages, SGD(), mesh, num_microbatches=2, donate=False
    )
    bf16 = PipelineEngine(
        stages, SGD(), mesh, num_microbatches=2, donate=False,
        compute_dtype=jnp.bfloat16,
    )
    _, losses_f32 = _run_steps(f32)
    _, losses_bf16 = _run_steps(bf16)
    np.testing.assert_allclose(losses_bf16, losses_f32, rtol=8e-2)


def test_wire_dtype_follows_activations():
    """bf16 activations (+ bool masks riding along) give a bf16 wire;
    pure-f32 stage I/O keeps an f32 wire."""
    bf_h = jax.ShapeDtypeStruct((2, 8, 4), jnp.bfloat16)
    mask = jax.ShapeDtypeStruct((2, 8), jnp.bool_)
    f32_h = jax.ShapeDtypeStruct((2, 8, 4), jnp.float32)
    assert _wire_dtype([((bf_h, mask), (bf_h, mask))]) == jnp.bfloat16
    assert _wire_dtype([(f32_h, f32_h)]) == jnp.float32


def test_embedding_casts_to_ctx_dtype():
    """Integer-input models enter the compute dtype at the embedding —
    the `Context.dtype` hook the engines set."""
    emb = L.embedding(16, 8)
    params, state = emb.init(jax.random.PRNGKey(0))
    ids = jnp.array([[1, 2], [3, 4]])
    out_f32, _ = emb.apply(params, state, ids, L.Context())
    out_bf16, _ = emb.apply(
        params, state, ids, L.Context(dtype=jnp.bfloat16)
    )
    assert out_f32.dtype == jnp.float32
    assert out_bf16.dtype == jnp.bfloat16


def test_profiler_trace_captured(tmp_path):
    """`TrainerConfig.profile_dir` writes a jax.profiler trace (the
    SURVEY §5 tracing-subsystem row)."""
    from distributed_model_parallel_tpu.data.datasets import synthetic
    from distributed_model_parallel_tpu.data.loader import Loader
    from distributed_model_parallel_tpu.training.trainer import (
        Trainer,
        TrainerConfig,
    )

    mesh = make_mesh(MeshSpec(data=8))
    engine = DataParallelEngine(tiny_cnn(10), SGD(), mesh)
    ds = synthetic(num_examples=64, num_classes=10, image_size=8, seed=0)
    train = Loader(ds, batch_size=16, shuffle=True, seed=0)
    prof_dir = tmp_path / "trace"
    cfg = TrainerConfig(
        epochs=1, base_lr=0.05, print_freq=0,
        log_dir=str(tmp_path / "log"), checkpoint_dir=str(tmp_path / "ckpt"),
        profile_dir=str(prof_dir),
    )
    trainer = Trainer(engine, train, None, cfg, rng=jax.random.PRNGKey(0))
    trainer.fit()
    trace_files = list(prof_dir.rglob("*"))
    assert any(f.is_file() for f in trace_files), (
        "profile_dir produced no trace files"
    )


@pytest.mark.slow
def test_pipeline_bf16_stage_local_combo():
    """The two pipeline options compose: bf16 activations/wire WITH
    stage-local (1/S-sharded) parameter storage. `slow` (tier-1
    budget); tier-1 twins: test_pipeline_bf16_close_to_f32 (the bf16
    half) + test_pipeline's stage-local storage pins (the sharding
    half)."""
    mesh = make_mesh(MeshSpec(data=2, stage=4))
    stages = tinycnn.split_stages(4, 10)
    f32 = PipelineEngine(
        stages, SGD(), mesh, num_microbatches=2, donate=False,
        stage_local_params=True,
    )
    bf16 = PipelineEngine(
        stages, SGD(), mesh, num_microbatches=2, donate=False,
        stage_local_params=True, compute_dtype=jnp.bfloat16,
    )
    _, losses_f32 = _run_steps(f32)
    ts_bf16, losses_bf16 = _run_steps(bf16)
    np.testing.assert_allclose(losses_bf16, losses_f32, rtol=8e-2)
    # storage stays f32 master rows, sharded 1/S
    assert ts_bf16.params.dtype == jnp.float32
    assert {s.data.shape[0] for s in ts_bf16.params.addressable_shards} == {1}
