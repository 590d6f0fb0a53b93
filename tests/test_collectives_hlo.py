"""Compiled-HLO assertions: each engine's train step must contain the
collectives INTERNALS.md's inventory claims — a CI guard that a future
refactor can't silently drop an all-reduce (numerics tests would catch
the wrong RESULT, but only on multi-sample tolerance; this pins the
mechanism).

The parsing/counting/reachability machinery that used to live here as
private helpers is now the shared static-analysis library
(`distributed_model_parallel_tpu/analysis/` — this PR's tentpole): the
text-level pins import `collective_counts`/`has_op_with_result`/
`nonscalar_all_reduce_count`, and the dependency pins run on
`parse_hlo`'s instruction graph (`HloModule.tagged`/`depends_on`, the
same conservative reachability). tests/test_hlolint.py lints the full
engine matrix through the same library's rule registry."""

import re
from functools import partial

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from distributed_model_parallel_tpu.analysis.hlo import (
    collective_counts as _collective_counts,
    has_op_with_result as _has_op_with_result,
    nonscalar_all_reduce_count as _nonscalar_all_reduce_count,
    parse_hlo,
)
from distributed_model_parallel_tpu.analysis.lint import (
    image_batch as _batch,
    staged_mlp as _staged_mlp,
)
from distributed_model_parallel_tpu.models.tinycnn import tiny_cnn
from distributed_model_parallel_tpu.runtime.mesh import MeshSpec, make_mesh
from distributed_model_parallel_tpu.training.optim import SGD


def _hlo(engine, *args):
    return engine.train_step.lower(*args).compile().as_text()


def test_ddp_step_contains_grad_all_reduce():
    from distributed_model_parallel_tpu.parallel.data_parallel import (
        DDPEngine,
    )

    mesh = make_mesh(MeshSpec(data=8))
    eng = DDPEngine(tiny_cnn(4), SGD(), mesh, donate=False)
    ts = eng.init_state(jax.random.PRNGKey(0))
    im, lb = eng.shard_batch(*_batch(16))
    hlo = _hlo(eng, ts, im, lb, jnp.float32(0.1))
    assert "all-reduce" in hlo


def test_gspmd_step_contains_partitioner_all_reduce():
    from distributed_model_parallel_tpu.parallel.data_parallel import (
        DataParallelEngine,
    )

    mesh = make_mesh(MeshSpec(data=8))
    eng = DataParallelEngine(tiny_cnn(4), SGD(), mesh, donate=False)
    ts = eng.init_state(jax.random.PRNGKey(0))
    im, lb = eng.shard_batch(*_batch(16))
    hlo = _hlo(eng, ts, im, lb, jnp.float32(0.1))
    # The partitioner derives the gradient all-reduce from the shardings.
    assert "all-reduce" in hlo


def test_pipeline_step_contains_collective_permute():
    from distributed_model_parallel_tpu.parallel.pipeline import (
        PipelineEngine,
    )
    from distributed_model_parallel_tpu.models import layers as L

    mesh = make_mesh(MeshSpec(data=2, stage=4))
    stages = [
        L.sequential(L.conv2d(3, 8, 3, padding=1), L.relu()),
        L.sequential(L.conv2d(8, 8, 3, padding=1), L.relu()),
        L.sequential(L.conv2d(8, 8, 3, padding=1), L.relu()),
        L.sequential(L.global_avg_pool(), L.linear(8, 4)),
    ]
    eng = PipelineEngine(stages, SGD(), mesh, num_microbatches=2,
                         donate=False)
    ts = eng.init_state(jax.random.PRNGKey(0))
    im, lb = eng.shard_batch(*_batch(8))
    hlo = _hlo(eng, ts, im, lb, jnp.float32(0.1))
    assert "collective-permute" in hlo   # the activation wire
    assert "all-reduce" in hlo           # grad psum('stage')+pmean('data')


def test_tp_step_contains_megatron_all_reduce():
    from distributed_model_parallel_tpu.models.bert import (
        BertConfig,
        bert_for_classification,
    )
    from distributed_model_parallel_tpu.parallel.tensor_parallel import (
        TensorParallelEngine,
    )

    cfg = BertConfig(vocab_size=64, hidden_size=16, num_layers=1,
                     num_heads=4, intermediate_size=32, max_position=8,
                     dropout_rate=0.0)
    mesh = make_mesh(MeshSpec(data=2, model=4))
    eng = TensorParallelEngine(
        bert_for_classification(4, cfg), SGD(), mesh, donate=False
    )
    ts = eng.init_state(jax.random.PRNGKey(0))
    rng = np.random.RandomState(0)
    ids = rng.randint(1, 64, size=(8, 8)).astype(np.int32)
    lb = rng.randint(0, 4, size=(8,)).astype(np.int32)
    ids, lb = eng.shard_batch(ids, lb)
    hlo = _hlo(eng, ts, ids, lb, jnp.float32(0.1))
    # Row-parallel matmul partial sums -> the Megatron f/g all-reduce.
    assert "all-reduce" in hlo


def test_sp_ring_step_contains_permute_chain():
    from distributed_model_parallel_tpu.models.bert import BertConfig
    from distributed_model_parallel_tpu.parallel.sequence_parallel import (
        SequenceParallelEngine,
    )

    cfg = BertConfig(vocab_size=64, hidden_size=16, num_layers=1,
                     num_heads=4, intermediate_size=32, max_position=16,
                     dropout_rate=0.0)
    mesh = make_mesh(MeshSpec(data=2, seq=4))
    eng = SequenceParallelEngine(cfg, 4, SGD(), mesh, donate=False)
    ts = eng.init_state(jax.random.PRNGKey(0))
    rng = np.random.RandomState(0)
    ids = rng.randint(1, 64, size=(8, 16)).astype(np.int32)
    lb = rng.randint(0, 4, size=(8,)).astype(np.int32)
    ids, lb = eng.shard_batch(ids, lb)
    hlo = _hlo(eng, ts, ids, lb, jnp.float32(0.1))
    assert "collective-permute" in hlo   # the KV ring
    assert "all-reduce" in hlo           # grad psum('seq')+pmean('data')


# ------------------------------------------------ collective matmul
# The latency-hiding chunked rings (`ops/collective_matmul.py`): an
# opted-in matmul must lower to the S-1 `collective-permute` chain with
# NO monolithic all-gather / reduce-scatter left on it, forward and
# backward both (the custom-vjp dual kernels are themselves chunked).


@pytest.mark.parametrize("size", [2, 4, 8])
def test_ag_matmul_lowers_to_s_minus_1_permutes(size):
    from jax.sharding import Mesh, PartitionSpec as P

    from distributed_model_parallel_tpu.ops.collective_matmul import (
        ag_matmul,
    )
    from distributed_model_parallel_tpu.runtime.compat import shard_map

    mesh = Mesh(np.array(jax.devices()[:size]), ("m",))
    x = jnp.zeros((2, 4 * size, 16), jnp.float32)
    w = jnp.zeros((16, 8 * size), jnp.float32)
    fn = jax.jit(shard_map(
        partial(ag_matmul, axis_name="m"), mesh=mesh,
        in_specs=(P(None, "m", None), P(None, "m")),
        out_specs=P(None, None, "m"), check_vma=False,
    ))
    c = _collective_counts(fn.lower(x, w).compile().as_text())
    assert c["collective-permute"] == size - 1
    assert c["all-gather"] == 0 and c["reduce-scatter"] == 0
    assert c["all-reduce"] == 0


@pytest.mark.parametrize("size", [2, 4, 8])
def test_matmul_rs_lowers_to_s_minus_1_permutes(size):
    from jax.sharding import Mesh, PartitionSpec as P

    from distributed_model_parallel_tpu.ops.collective_matmul import (
        matmul_rs,
    )
    from distributed_model_parallel_tpu.runtime.compat import shard_map

    mesh = Mesh(np.array(jax.devices()[:size]), ("m",))
    x = jnp.zeros((2, 4 * size, 8 * size), jnp.float32)
    w = jnp.zeros((8 * size, 16), jnp.float32)
    fn = jax.jit(shard_map(
        partial(matmul_rs, axis_name="m"), mesh=mesh,
        in_specs=(P(None, None, "m"), P("m", None)),
        out_specs=P(None, "m", None), check_vma=False,
    ))
    c = _collective_counts(fn.lower(x, w).compile().as_text())
    assert c["collective-permute"] == size - 1
    assert c["all-gather"] == 0 and c["reduce-scatter"] == 0
    assert c["all-reduce"] == 0


def test_collective_matmul_ffn_pair_forward_and_backward_chunked():
    """The column->row FFN pair through the jit-level policy: forward is
    exactly 2(S-1) permutes; jax.grad through the custom vjps is the
    dual-kernel 5(S-1) total (fwd 2 + ag-bwd 2 + rs-bwd 1 rings) — and
    neither direction contains a monolithic all-gather/reduce-scatter."""
    from jax.sharding import NamedSharding, PartitionSpec as P

    from distributed_model_parallel_tpu.ops.collective_matmul import (
        CollectiveMatmul,
    )

    size = 4
    mesh = make_mesh(MeshSpec(data=2, model=size))
    policy = CollectiveMatmul(mesh=mesh, axis="model")
    hs = NamedSharding(mesh, P("data", None, None))
    h = jnp.zeros((8, 8, 32), jnp.float32)
    w1, b1 = jnp.zeros((32, 64)), jnp.zeros((64,))
    w2, b2 = jnp.zeros((64, 32)), jnp.zeros((32,))

    def pair(h, w1, b1, w2, b2):
        y = jax.nn.gelu(policy.column(h, w1, b1), approximate=False)
        return policy.row(y, w2, b2)

    out_s = NamedSharding(mesh, P("data", "model", None))
    fwd = jax.jit(pair, in_shardings=(hs, None, None, None, None),
                  out_shardings=out_s)
    c = _collective_counts(
        fwd.lower(h, w1, b1, w2, b2).compile().as_text()
    )
    assert c["collective-permute"] == 2 * (size - 1)
    assert c["all-gather"] == 0 and c["reduce-scatter"] == 0
    assert c["all-reduce"] == 0

    grad = jax.jit(
        jax.grad(
            lambda *a: jnp.sum(pair(*a) ** 2), argnums=(0, 1, 2, 3, 4)
        ),
        in_shardings=(hs, None, None, None, None),
    )
    cg = _collective_counts(
        grad.lower(h, w1, b1, w2, b2).compile().as_text()
    )
    assert cg["collective-permute"] == 5 * (size - 1)
    assert cg["all-gather"] == 0 and cg["reduce-scatter"] == 0


def test_collective_matmul_block_has_no_monolithic_collectives():
    """A full encoder block under the policy: all four opted-in
    projections ring (>= 4(S-1) permutes — the partitioner may add its
    own resharding permutes) and the block forward contains NO
    all-gather / reduce-scatter / all-reduce at all."""
    from jax.sharding import NamedSharding, PartitionSpec as P

    from distributed_model_parallel_tpu.models import layers as L
    from distributed_model_parallel_tpu.models.transformer import (
        encoder_layer,
    )
    from distributed_model_parallel_tpu.ops.collective_matmul import (
        CollectiveMatmul,
    )

    size = 4
    mesh = make_mesh(MeshSpec(data=2, model=size))
    policy = CollectiveMatmul(mesh=mesh, axis="model")
    blk = encoder_layer(32, 4, 64, dropout_rate=0.0)
    params, _ = blk.init(jax.random.PRNGKey(0))
    ctx = L.Context(train=False, matmul=policy)
    h = jnp.zeros((8, 8, 32), jnp.float32)
    mask = jnp.ones((8, 8), bool)
    hs = NamedSharding(mesh, P("data", None, None))
    out_s = NamedSharding(mesh, P("data", "model", None))

    fwd = jax.jit(
        lambda p, h, m: blk.apply(p, {}, (h, m), ctx)[0][0],
        in_shardings=(None, hs, None), out_shardings=out_s,
    )
    c = _collective_counts(fwd.lower(params, h, mask).compile().as_text())
    assert c["collective-permute"] >= 4 * (size - 1)
    assert c["all-gather"] == 0 and c["reduce-scatter"] == 0
    assert c["all-reduce"] == 0


def test_tp_collective_matmul_step_swaps_gathers_for_permutes():
    """Engine level: turning collective_matmul on must multiply the
    permute count (the rings) and strictly shrink the all-gather count
    (the monolithic collectives it replaces) in the SAME train step."""
    from distributed_model_parallel_tpu.models.bert import (
        BertConfig,
        bert_for_classification,
    )
    from distributed_model_parallel_tpu.parallel.tensor_parallel import (
        TensorParallelEngine,
    )

    cfg = BertConfig(vocab_size=64, hidden_size=32, num_layers=1,
                     num_heads=4, intermediate_size=64, max_position=8,
                     dropout_rate=0.0)
    mesh = make_mesh(MeshSpec(data=2, model=4))
    model = bert_for_classification(4, cfg)
    rng = np.random.RandomState(0)
    ids = rng.randint(1, 64, size=(8, 8)).astype(np.int32)
    lb = rng.randint(0, 4, size=(8,)).astype(np.int32)
    counts = {}
    for cm in (False, True):
        eng = TensorParallelEngine(
            model, SGD(), mesh, donate=False, collective_matmul=cm
        )
        ts = eng.init_state(jax.random.PRNGKey(0))
        a, b = eng.shard_batch(ids, lb)
        counts[cm] = _collective_counts(
            _hlo(eng, ts, a, b, jnp.float32(0.1))
        )
    # 1 block = 4 ring sites; fwd+bwd >= 10(S-1) = 30 ring permutes.
    assert (counts[True]["collective-permute"]
            >= counts[False]["collective-permute"] + 30)
    assert counts[True]["all-gather"] < counts[False]["all-gather"]


# ------------------------------------------------------------- FSDP
# ZeRO-3's two collectives, pinned from the lowered step: the forward
# all-gathers each sharded weight before use, and the backward
# REDUCE-SCATTERS each sharded leaf's gradient (never a plain
# all-reduce handing every device the full gradient).


def test_fsdp_step_gathers_weights_and_reduce_scatters_grads():
    """Structural FSDP collective story on a pure-matmul MLP.

    Shapes put the step in the ZeRO regime (batch rows >> hidden dim):
    the partitioner must choose weight-stationary-sharded lowering —
    all-gather each weight before its matmul, scatter each gradient —
    rather than gathering the (here larger) activations.

    The backward assertion accepts the two spellings of reduce-scatter:
    the fused `reduce-scatter` op (TPU/GPU pipelines), or the SPMD
    partitioner's unfused pair — an all-reduce of the full-size f32
    gradient immediately dynamic-sliced to this device's 1/N shard —
    which is what the CPU pipeline emits (its ReduceScatterCreator pass
    doesn't run there). Both are pinned by shape for the (128,128)
    leaf: the full gradient must be reduced AND a 1/8 shard sliced out
    of it; a refactor that hands every device a full REPLICATED
    gradient (plain DDP all-reduce, no scatter) fails the slice pin."""
    from distributed_model_parallel_tpu.models import layers as L
    from distributed_model_parallel_tpu.parallel.fsdp import FSDPEngine

    mesh = make_mesh(MeshSpec(data=8))
    model = L.sequential(
        L.flatten(),                 # (B, 8, 8, 3) -> (B, 192)
        L.linear(192, 128),
        L.relu(),
        L.linear(128, 128),
        L.relu(),
        L.linear(128, 4),
    )
    eng = FSDPEngine(model, SGD(), mesh, donate=False)
    ts = eng.init_state(jax.random.PRNGKey(0))
    im, lb = eng.shard_batch(*_batch(1024))
    hlo = _hlo(eng, ts, im, lb, jnp.float32(0.1))

    # Forward: the (128,128) weight is all-gathered from its (16,128)
    # 'data' shards right before its matmul.
    assert _has_op_with_result(hlo, "all-gather", "f32[128,128]")

    if "reduce-scatter" not in hlo:
        # Unfused reduce-scatter: full-size gradient all-reduce ...
        assert _has_op_with_result(hlo, "all-reduce", "f32[128,128]")
        # ... immediately scattered: a 1/8 dynamic-slice of the reduced
        # gradient (shape-pinned to the (128,128) leaf's shard).
        assert ("dynamic_slice_sizes={16,128}" in hlo
                or "dynamic_slice_sizes={128,16}" in hlo)


# ------------------------------------------- bucketed grad reduction
# The DDP-Reducer path (`ops/grad_reduction.py`): an opted-in step must
# reduce gradients through per-bucket chunked rings — 2(S-1)
# collective-permutes per bucket (reduce-scatter + all-gather) — with
# NO monolithic grad-sized all-reduce over the full data axis left in
# the program. Scalar all-reduces (the metrics psums) are allowed; the
# pin distinguishes them by result shape.


def _mlp():
    """BN-free classifier: model_state is empty, so the only all-reduces
    a DDP step may contain are the gradient reduction and the scalar
    metrics psums — the pin isolates the reducer."""
    from distributed_model_parallel_tpu.models import layers as L

    return L.sequential(
        L.flatten(),
        L.linear(192, 64),
        L.relu(),
        L.linear(64, 64),
        L.relu(),
        L.linear(64, 4),
    )


def _n_buckets(engine, bucket_mb):
    from distributed_model_parallel_tpu.ops.grad_reduction import (
        plan_buckets,
    )

    key_aval = jax.ShapeDtypeStruct((2,), jnp.uint32)
    p_aval, _ = jax.eval_shape(engine.model.init, key_aval)
    return len(
        plan_buckets(jax.tree_util.tree_leaves(p_aval), bucket_mb)
    )


def test_ddp_bucketed_step_rings_instead_of_monolithic_all_reduce():
    """Plain ('data',) mesh, S=8: the opted-in step carries exactly
    2(S-1) permutes per bucket and ZERO grad-sized all-reduces; the
    monolithic twin keeps its fused grad all-reduce and no rings."""
    from distributed_model_parallel_tpu.parallel.data_parallel import (
        DDPEngine,
    )

    mesh = make_mesh(MeshSpec(data=8))
    bucket_mb = 0.02
    hlos = {}
    for gr in ("monolithic", "bucketed"):
        eng = DDPEngine(
            _mlp(), SGD(), mesh, donate=False,
            grad_reduction=gr, bucket_mb=bucket_mb,
        )
        ts = eng.init_state(jax.random.PRNGKey(0))
        im, lb = eng.shard_batch(*_batch(16))
        hlos[gr] = _hlo(eng, ts, im, lb, jnp.float32(0.1))
        if gr == "bucketed":
            n_buckets = _n_buckets(eng, bucket_mb)

    assert n_buckets >= 2  # the cap actually split the pytree
    c = _collective_counts(hlos["bucketed"])
    assert c["collective-permute"] == 2 * (8 - 1) * n_buckets
    assert c["all-gather"] == 0 and c["reduce-scatter"] == 0
    assert _nonscalar_all_reduce_count(hlos["bucketed"]) == 0

    c_mono = _collective_counts(hlos["monolithic"])
    assert c_mono["collective-permute"] == 0
    assert _nonscalar_all_reduce_count(hlos["monolithic"]) >= 1


def test_ddp_bucketed_hybrid_step_one_dcn_all_reduce_per_bucket():
    """2×4 dcn×ici mesh: per bucket, 2(ici-1) ring permutes plus ONE
    cross-slice all-reduce — carrying only the 1/ici shard, pinned by
    its result bytes — and nothing grad-sized beyond those."""
    from distributed_model_parallel_tpu.ops.grad_reduction import (
        plan_buckets,
    )
    from distributed_model_parallel_tpu.parallel.data_parallel import (
        DDPEngine,
    )

    mesh = make_mesh(MeshSpec(data=8, dcn=2))
    bucket_mb = 0.02
    eng = DDPEngine(
        _mlp(), SGD(), mesh, donate=False,
        grad_reduction="bucketed", bucket_mb=bucket_mb,
    )
    ts = eng.init_state(jax.random.PRNGKey(0))
    im, lb = eng.shard_batch(*_batch(16))
    hlo = _hlo(eng, ts, im, lb, jnp.float32(0.1))

    key_aval = jax.ShapeDtypeStruct((2,), jnp.uint32)
    p_aval, _ = jax.eval_shape(eng.model.init, key_aval)
    buckets = plan_buckets(
        jax.tree_util.tree_leaves(p_aval), bucket_mb
    )
    assert len(buckets) >= 2
    c = _collective_counts(hlo)
    assert c["collective-permute"] == 2 * (4 - 1) * len(buckets)
    assert c["all-gather"] == 0 and c["reduce-scatter"] == 0
    # one cross-slice (dcn) all-reduce per bucket — the only
    # non-scalar all-reduces in the step...
    assert _nonscalar_all_reduce_count(hlo) == len(buckets)
    # ...and each carries the bucket's 1/ici shard, not the full bucket.
    for b in buckets:
        padded = b.size + (-b.size % 4)
        assert _has_op_with_result(
            hlo, "all-reduce", f"f32[{padded // 4}]"
        ), (b.size, padded)


def test_fsdp_bucketed_step_gathers_weights_and_rings_grads():
    """The explicit bucketed-FSDP step: per-leaf weight all-gathers on
    entry (the ZeRO-3 collective, now explicit) and per-bucket ring
    permutes for the gradients — no grad-sized all-reduce, no
    monolithic reduce-scatter."""
    from distributed_model_parallel_tpu.parallel.fsdp import FSDPEngine

    mesh = make_mesh(MeshSpec(data=8))
    bucket_mb = 0.02
    eng = FSDPEngine(
        _mlp(), SGD(), mesh, donate=False, min_shard_elems=64,
        grad_reduction="bucketed", bucket_mb=bucket_mb,
    )
    ts = eng.init_state(jax.random.PRNGKey(0))
    im, lb = eng.shard_batch(*_batch(1024))
    hlo = _hlo(eng, ts, im, lb, jnp.float32(0.1))
    n_buckets = _n_buckets(eng, bucket_mb)

    c = _collective_counts(hlo)
    assert c["all-gather"] >= 1  # sharded weights materialize per leaf
    assert c["collective-permute"] == 2 * (8 - 1) * n_buckets
    assert c["reduce-scatter"] == 0
    assert _nonscalar_all_reduce_count(hlo) == 0


# ------------------------------------- overlapped backward (deps)
# The stagewise-backward reducer (`grad_reduction="overlapped"`): the
# eager firing is verified STRUCTURALLY, from the dependency graph of
# the compiled HLO — the first-fired bucket's ring collectives (the
# LAST stage's, late layers first) must have no transitive dependency
# on stage 0's backward ops, and the FSDP prefetch all-gather for stage
# k-1 must not depend on any stage's bucket rings. Instructions are
# identified by the `jax.named_scope` tags the engines trace them
# under (`grad_reduce_stage{k}`, `bwd_stage{k}`,
# `prefetch_gather_stage{k}` — carried into compiled HLO as
# metadata op_name). The instruction graph and its conservative
# reachability are the shared library's (`analysis.hlo.parse_hlo` —
# the promoted form of the `_hlo_graph`/`_depends_on` helpers that
# used to live here).


@pytest.mark.parametrize("s", [2, 4, 8])
def test_ddp_overlapped_first_bucket_free_of_stage0_backward(s):
    """The ISSUE's tentpole pin: with grad_reduction='overlapped' and S
    backward segments, the FIRST-fired bucket's ring permutes (stage
    S-1's — late layers differentiate first) have NO transitive
    dependency on stage 0's backward ops, so XLA may schedule them
    beside the remaining backward. Positive control: stage 0's own
    bucket (fired last) MUST depend on stage 0's backward."""
    from distributed_model_parallel_tpu.parallel.data_parallel import (
        DDPEngine,
    )

    mesh = make_mesh(MeshSpec(data=8))
    eng = DDPEngine(
        _staged_mlp(8), SGD(), mesh, donate=False,
        grad_reduction="overlapped", overlap_stages=s, bucket_mb=0.001,
    )
    ts = eng.init_state(jax.random.PRNGKey(0))
    im, lb = eng.shard_batch(*_batch(16))
    mod = parse_hlo(_hlo(eng, ts, im, lb, jnp.float32(0.1)))

    first = mod.tagged(
        f"grad_reduce_stage{s - 1}", "collective-permute"
    )
    bwd0 = set(mod.tagged("bwd_stage0"))
    assert first, "first-fired bucket emitted no ring permutes"
    assert bwd0, "stage 0 backward left no tagged ops"
    for p in first:
        assert not mod.depends_on(p, bwd0), (
            f"S={s}: first bucket permute {p} depends on stage-0 "
            "backward — the eager firing serialized"
        )
    # Positive control — the dependency analysis is not vacuous.
    last = mod.tagged("grad_reduce_stage0", "collective-permute")
    assert last and all(mod.depends_on(p, bwd0) for p in last)


def test_ddp_overlapped_keeps_ring_structure_and_no_grad_all_reduce():
    """The overlapped step keeps the bucketed lowering per segment:
    2(S_data-1) permutes per bucket summed over the per-stage bucket
    plans, zero monolithic all-gather/reduce-scatter, zero grad-sized
    all-reduce."""
    from distributed_model_parallel_tpu.models import staging
    from distributed_model_parallel_tpu.ops.grad_reduction import (
        plan_buckets,
    )
    from distributed_model_parallel_tpu.parallel.data_parallel import (
        DDPEngine,
    )

    mesh = make_mesh(MeshSpec(data=8))
    bucket_mb = 0.001
    model = _staged_mlp(8)
    eng = DDPEngine(
        model, SGD(), mesh, donate=False,
        grad_reduction="overlapped", overlap_stages=4,
        bucket_mb=bucket_mb,
    )
    ts = eng.init_state(jax.random.PRNGKey(0))
    im, lb = eng.shard_batch(*_batch(16))
    hlo = _hlo(eng, ts, im, lb, jnp.float32(0.1))

    key_aval = jax.ShapeDtypeStruct((2,), jnp.uint32)
    p_aval, _ = jax.eval_shape(model.init, key_aval)
    cuts = staging.split_points(4, None, 8)
    n_buckets = sum(
        len(plan_buckets(jax.tree_util.tree_leaves(sp), bucket_mb))
        for sp in staging.partition_tree(p_aval, cuts)
    )
    assert n_buckets >= 5  # per-stage plans actually split the pytree
    c = _collective_counts(hlo)
    assert c["collective-permute"] == 2 * (8 - 1) * n_buckets
    assert c["all-gather"] == 0 and c["reduce-scatter"] == 0
    assert _nonscalar_all_reduce_count(hlo) == 0


@pytest.mark.parametrize("s", [2, 4, 8])
def test_fsdp_overlapped_prefetch_gather_free_of_reduce(s):
    """ZeRO overlap pin: the backward loop's prefetched all-gather of
    stage k-1's weights (issued during stage k's backward) depends only
    on the parameter shards — never on ANY stage's bucket rings (a
    superset of the ISSUE's 'not on stage k's reduce-scatter'), so the
    scheduler may hoist it behind the in-flight reduction."""
    from distributed_model_parallel_tpu.parallel.fsdp import FSDPEngine

    mesh = make_mesh(MeshSpec(data=8))
    eng = FSDPEngine(
        _staged_mlp(8, width=128), SGD(), mesh, donate=False,
        min_shard_elems=64, grad_reduction="overlapped",
        overlap_stages=s, bucket_mb=0.02,
    )
    ts = eng.init_state(jax.random.PRNGKey(0))
    im, lb = eng.shard_batch(*_batch(64))
    mod = parse_hlo(_hlo(eng, ts, im, lb, jnp.float32(0.1)))

    reduce_ops = set(mod.tagged("grad_reduce_stage0"))
    for k in range(s):
        reduce_ops |= set(mod.tagged(f"grad_reduce_stage{k}"))
    assert reduce_ops
    for k in range(s - 1):
        gathers = mod.tagged(
            f"prefetch_gather_stage{k}", "all-gather"
        )
        assert gathers, f"no prefetched all-gather for stage {k}"
        for g in gathers:
            assert not mod.depends_on(g, reduce_ops), (
                f"S={s}: prefetch gather {g} (stage {k}) depends on a "
                "bucket reduction — the ZeRO overlap serialized"
            )


def test_sp_ulysses_step_contains_all_to_all():
    from distributed_model_parallel_tpu.models.bert import BertConfig
    from distributed_model_parallel_tpu.parallel.sequence_parallel import (
        SequenceParallelEngine,
    )

    cfg = BertConfig(vocab_size=64, hidden_size=16, num_layers=1,
                     num_heads=4, intermediate_size=32, max_position=16,
                     dropout_rate=0.0)
    mesh = make_mesh(MeshSpec(data=2, seq=4))
    eng = SequenceParallelEngine(
        cfg, 4, SGD(), mesh, attention="ulysses", donate=False
    )
    ts = eng.init_state(jax.random.PRNGKey(0))
    rng = np.random.RandomState(0)
    ids = rng.randint(1, 64, size=(8, 16)).astype(np.int32)
    lb = rng.randint(0, 4, size=(8,)).astype(np.int32)
    ids, lb = eng.shard_batch(ids, lb)
    hlo = _hlo(eng, ts, ids, lb, jnp.float32(0.1))
    assert "all-to-all" in hlo


# ------------------------------------------------ the step's metrics
# `_metrics` counts top-1 / top-5 hits from the label's rank
# (`training/metrics.py label_rank`): one compare-and-count pass. A
# `lax.top_k` there lowers on the v5e to a sort of the whole vocabulary
# for every row (83 % of GPT-2 small's step, PERF.md PR 26); numerics
# tests cannot see it come back, so the lowered step is pinned.

_SORT_OPS = re.compile(
    r"\b(?:stablehlo|mhlo|chlo)\.(?:sort|top_k|topk)\b|TopK"
)


def test_sort_op_pattern_sees_a_top_k():
    text = jax.jit(lambda x: jax.lax.top_k(x, 5)).lower(
        jnp.ones((4, 61))
    ).as_text()
    assert _SORT_OPS.search(text)
    text = jax.jit(lambda x: jnp.sort(x, axis=-1)).lower(
        jnp.ones((4, 61))
    ).as_text()
    assert _SORT_OPS.search(text)


@pytest.mark.parametrize("build", ["sp_lm", "fsdp_plan"])
def test_lm_train_step_lowers_without_sort_or_top_k(build):
    from distributed_model_parallel_tpu.models.gpt import GPTConfig

    cfg = GPTConfig(vocab_size=61, dim=32, num_layers=2, num_heads=4,
                    ffn_dim=64, max_position=16, dropout_rate=0.0)
    if build == "sp_lm":
        from distributed_model_parallel_tpu.parallel.sequence_parallel import (
            CausalLMSequenceParallelEngine,
        )

        eng = CausalLMSequenceParallelEngine(
            cfg, SGD(), make_mesh(MeshSpec(data=2, seq=4)), donate=False
        )
    else:
        from distributed_model_parallel_tpu.parallel.plan import (
            build_plan_engine,
        )

        eng = build_plan_engine(cfg, SGD(), "fsdp4", donate=False)
    ts = eng.init_state(jax.random.PRNGKey(0))
    ids = np.random.RandomState(0).randint(
        1, cfg.vocab_size, size=(8, 16)
    ).astype(np.int32)
    ids_s, tg_s = eng.shard_batch(ids)
    text = eng.train_step.lower(ts, ids_s, tg_s, jnp.float32(0.1)).as_text()
    found = _SORT_OPS.search(text)
    assert found is None, found.group(0)
    assert "compare" in text     # the rank's pass is there


# ---------------------- an fsdp plan's collectives, block by block
# ISSUE 33: a plan with fsdp gathers each block inside the block scan
# (one ahead) and differentiates with respect to its 1/dp rows, so a
# block's gradient leaves the backward scan as the gather's transpose,
# a float32 reduce-scatter over 'data'. Numerics cannot see a whole
# float32 gradient all-reduced and sliced afterwards (the result is
# the same and the chip pays twice the bytes, exposed), so the traced
# step and its lowering are pinned.

# Widths at which no leaf's 1/2 or 1/4 has another leaf's whole shape
# (vectors of 32, 96, 80): the pins below tell the two apart by shape.
_PLAN_CFG = dict(vocab_size=61, dim=32, num_layers=4, num_heads=4,
                 ffn_dim=80, max_position=16, dropout_rate=0.0)


def _plan_step(spec, compute_dtype=None):
    """(engine, traced collectives, StableHLO text) of one train step
    of `spec` at toy widths. A traced collective is (primitive, axis
    names, operand shape, result shape, dtype, scans around it)."""
    from distributed_model_parallel_tpu.analysis.lint import _subjaxprs
    from distributed_model_parallel_tpu.models.gpt import GPTConfig
    from distributed_model_parallel_tpu.parallel.plan import (
        build_plan_engine, parse_plan,
    )

    plan = parse_plan(spec)
    eng = build_plan_engine(
        GPTConfig(**_PLAN_CFG), SGD(), plan, donate=False,
        force_composed=True, compute_dtype=compute_dtype,
        min_shard_elems=16,
        devices=jax.devices()[: plan.num_devices],
    )
    ts = eng.init_state(jax.random.PRNGKey(0))
    ids = np.random.RandomState(0).randint(
        1, 61, size=(4 * plan.dp * plan.pp, 16)
    ).astype(np.int32)
    args = (ts, *eng.shard_batch(ids), jnp.float32(0.1))
    names = {"all_gather": "axis_name", "reduce_scatter": "axis_name",
             "psum": "axes"}
    found, seen = [], set()

    def walk(jaxpr, depth):
        if id(jaxpr) in seen:
            return
        seen.add(id(jaxpr))
        for eqn in jaxpr.eqns:
            key = names.get(eqn.primitive.name)
            if key is not None:
                axes = eqn.params[key]
                axes = axes if isinstance(axes, tuple) else (axes,)
                for v, o in zip(eqn.invars, eqn.outvars):
                    found.append((
                        eqn.primitive.name, axes, tuple(v.aval.shape),
                        tuple(o.aval.shape), str(v.aval.dtype), depth,
                    ))
            inner = depth + (eqn.primitive.name == "scan")
            for p in eqn.params.values():
                for sub in _subjaxprs(p):
                    walk(sub, inner)

    walk(jax.make_jaxpr(eng.train_step)(*args).jaxpr, 0)
    return eng, found, eng.train_step.lower(*args).as_text()


def _block_leaves(eng):
    """{whole per-block shape: 1/dp shape} of the block leaves fsdp
    shards, matrices and vectors apart."""
    from jax.sharding import PartitionSpec as P

    specs = eng.state_partition_specs().params["blocks"]["0"]
    avals = jax.eval_shape(
        eng._full.init, jax.ShapeDtypeStruct((2,), jnp.uint32)
    )[0]["blocks"]["0"]
    dp = eng.plan.dp
    matrices, vectors = {}, {}
    for spec, aval in zip(
        jax.tree_util.tree_leaves(
            specs, is_leaf=lambda x: isinstance(x, P)
        ),
        jax.tree_util.tree_leaves(avals),
    ):
        d = [i for i, part in enumerate(spec) if part is not None]
        assert d, "min_shard_elems=16 shards every block leaf here"
        shard = tuple(
            n // dp if i == d[0] else n for i, n in enumerate(aval.shape)
        )
        (matrices if aval.ndim >= 2 else vectors)[aval.shape] = shard
    return matrices, vectors


_MLIR_TENSOR = re.compile(r"tensor<((?:\d+x)*)(\w+)>")


def _stablehlo_operands(text, op):
    """[(dims, element type)] of every `stablehlo.<op>` operand."""
    out = []
    lines = text.splitlines()
    for i, line in enumerate(lines):
        if f'"stablehlo.{op}"' not in line:
            continue
        if line.rstrip().endswith("({"):
            # a reduction carries its region; the types close it
            line = next(
                ln for ln in lines[i:] if ln.lstrip().startswith("}) : (")
            )
        sig = line[line.rindex(" : (") + 4:]
        for dims, el in _MLIR_TENSOR.findall(sig[:sig.index(") -> ")]):
            out.append((tuple(int(n) for n in dims.split("x") if n), el))
    return out


@pytest.mark.parametrize("compute_dtype", [None, jnp.bfloat16],
                         ids=["f32", "bf16"])
@pytest.mark.parametrize("spec", ["fsdp4", "pp2xfsdp2", "pp2-1f1bxfsdp2"])
def test_fsdp_plan_gathers_and_reduces_block_by_block(spec, compute_dtype):
    eng, found, text = _plan_step(spec, compute_dtype)
    matrices, vectors = _block_leaves(eng)
    whole = set(matrices) | set(vectors)
    assert eng.fsdp_exchange is not None

    # a leaf's whole shape, alone or stacked as the engine stacks it:
    # (layers, ...) or (virtual chunks, layers a chunk, ...)
    layers, chunks = _PLAN_CFG["num_layers"], eng.plan.virtual_stages
    stacks = ((), (layers,),
              (chunks, layers // (eng.plan.pp * chunks)))

    def ends_whole(shape):
        return any(shape == st + w for st in stacks for w in whole)

    # nothing a block keeps 1/dp of is all-reduced whole — in the
    # trace, and in what it lowered to
    for prim, axes, shape, _, _, _ in found:
        assert not (prim == "psum" and "data" in axes
                    and ends_whole(shape)), (axes, shape)
    for dims, _ in _stablehlo_operands(text, "all_reduce"):
        assert not ends_whole(dims), dims
    # the matrices: gathered INSIDE the block scan, one block's worth
    # (never (layers, ...)), in the compute dtype; each one's gradient
    # reduce-scattered inside the backward scan, float32, 'data' alone
    wire = "bfloat16" if compute_dtype is not None else "float32"
    scatters = [f for f in found if f[0] == "reduce_scatter"]
    assert scatters and all(
        axes == ("data",) and dt == "float32"
        for _, axes, _, _, dt, _ in scatters
    )
    # (one collective a block: the matrices travel as rows of one
    # buffer, `dim` wide, so its size is the block's, not the stack's)
    in_scan = [f for f in found if f[0] == "all_gather" and f[5] > 0]
    block_elems = sum(int(np.prod(w)) for w in matrices)
    assert in_scan and all(
        int(np.prod(f[3])) == block_elems for f in in_scan
    )
    assert all(f[4] == wire and f[1] == ("data",) for f in in_scan)
    for shard in matrices.values():
        assert any(
            out == shard and depth > 0
            for _, _, _, out, _, depth in scatters
        ), shard
    # every vector: whole for all blocks from one gather outside the
    # scans, reduce-scattered once, float32
    for shard in vectors.values():
        assert any(
            out[-len(shard):] == shard and depth == 0
            for _, _, _, out, _, depth in scatters
        ), shard
    # the lowering keeps the precision: no reduce_scatter of bf16
    lowered = _stablehlo_operands(text, "reduce_scatter")
    assert lowered and {el for _, el in lowered} == {"f32"}


def test_plan_without_fsdp_keeps_its_one_fused_psum():
    eng, found, text = _plan_step("dp4")
    assert eng.fsdp_exchange is None
    assert {f[0] for f in found} == {"psum"}
    assert {f[1] for f in found} == {("stage", "data", "seq")}
    assert not _stablehlo_operands(text, "reduce_scatter")
    assert not _stablehlo_operands(text, "all_gather")
    # the whole (layers, ...) stacks meet in it
    layers = _PLAN_CFG["num_layers"]
    assert any(
        len(dims) == 3 and dims[0] == layers
        for dims, _ in _stablehlo_operands(text, "all_reduce")
    )


# ----------------------- the vocabulary head and its loss as one op
# ISSUE 39: the LM engine's step and a one-stage plan's tick hand the
# head's rows and matrix to `ops/head_loss.head_loss`, which walks the
# rows x vocabulary plane in pieces. Numerics cannot see the whole
# float32 logits come back (the sums are the same and the chip pays
# passes over gigabytes), so the lowered step is pinned: no float32
# array of a shard's whole (rows, vocabulary), however its leading axes
# are cut.

_HEAD_VOCAB = 61


def _whole_plane_tensors(text, rows, vocab=_HEAD_VOCAB):
    """Every float32 tensor type in `text` that holds `rows` x `vocab`
    logits or more: (..., vocab) with the leading axes' product >=
    rows."""
    found = set()
    for dims, el in _MLIR_TENSOR.findall(text):
        dims = tuple(int(n) for n in dims.split("x") if n)
        if (el == "f32" and len(dims) >= 2 and dims[-1] == vocab
                and int(np.prod(dims[:-1])) >= rows):
            found.add(dims)
    return found


def _old_head_loss(rows, matrix, labels):
    """The step's sums as both engines made them until PR 39: the whole
    float32 logits, `cross_entropy` and `_metrics` over them, autodiff
    behind. Kept here as the reference the op is held to."""
    from distributed_model_parallel_tpu.parallel.data_parallel import (
        _metrics,
    )
    from distributed_model_parallel_tpu.training.metrics import (
        cross_entropy,
    )

    logits = (rows.astype(jnp.float32) @ matrix).reshape(
        -1, matrix.shape[1]
    )
    flat = labels.reshape(-1)
    return _metrics(cross_entropy(logits, flat), logits, flat)


def _head_loss_engine(build):
    """(engine, sequences a step) at toy widths at which a shard holds
    64 rows a step, more than one block of the op (the caller shrinks
    the block) and no other array's leading size."""
    from distributed_model_parallel_tpu.models.gpt import GPTConfig

    cfg = GPTConfig(vocab_size=_HEAD_VOCAB, dim=32, num_layers=2,
                    num_heads=4, ffn_dim=80, max_position=16,
                    dropout_rate=0.0)
    if build == "sp_lm":
        from distributed_model_parallel_tpu.parallel.sequence_parallel import (
            CausalLMSequenceParallelEngine,
        )

        eng = CausalLMSequenceParallelEngine(
            cfg, SGD(), make_mesh(MeshSpec(data=2, seq=4)), donate=False
        )
        return eng, 32
    from distributed_model_parallel_tpu.parallel.plan import (
        build_plan_engine,
    )

    eng = build_plan_engine(
        cfg, SGD(), "fsdp4", donate=False, force_composed=True,
        min_shard_elems=16, devices=jax.devices()[:4],
    )
    return eng, 16


_SHARD_ROWS = 64


def _head_loss_batch(eng, sequences):
    ids = np.random.RandomState(0).randint(
        1, _HEAD_VOCAB, size=(sequences, 16)
    ).astype(np.int32)
    return eng.shard_batch(ids)


def test_whole_plane_pattern_sees_the_old_formulation():
    text = jax.jit(_old_head_loss).lower(
        jnp.ones((4, 16, 32)), jnp.ones((32, _HEAD_VOCAB)),
        jnp.ones((4, 16), jnp.int32),
    ).as_text()
    assert _whole_plane_tensors(text, 64)
    # and a block of fewer rows is not the plane
    assert not _whole_plane_tensors(
        "tensor<16x61xf32> tensor<2x16x32xf32> tensor<64x61xi32>", 64
    )


@pytest.mark.parametrize("build", ["sp_lm", "fsdp_plan"])
def test_lm_train_step_holds_no_whole_float32_logits(monkeypatch, build):
    from distributed_model_parallel_tpu.ops import head_loss as HL

    # blocks of 16 rows: four a shard
    monkeypatch.setattr(HL, "BLOCK_ELEMENTS", 16 * _HEAD_VOCAB)
    eng, sequences = _head_loss_engine(build)
    ts = eng.init_state(jax.random.PRNGKey(0))
    ids_s, tg_s = _head_loss_batch(eng, sequences)
    for step, args in (
        (eng.train_step, (ts, ids_s, tg_s, jnp.float32(0.1))),
        (eng.eval_step, (ts, ids_s, tg_s)),
    ):
        text = step.lower(*args).as_text()
        assert not _whole_plane_tensors(text, _SHARD_ROWS)
        # the blocks are there, and the rank's pass in them
        assert _whole_plane_tensors(text, 16) and "compare" in text


@pytest.mark.parametrize("build", ["sp_lm", "fsdp_plan"])
def test_lm_three_steps_equal_the_old_formulation(monkeypatch, build):
    from distributed_model_parallel_tpu.ops import head_loss as HL
    from distributed_model_parallel_tpu.parallel import (
        plan, sequence_parallel,
    )

    monkeypatch.setattr(HL, "BLOCK_ELEMENTS", 16 * _HEAD_VOCAB)
    module = sequence_parallel if build == "sp_lm" else plan

    def run(patched):
        with monkeypatch.context() as m:
            if patched:
                m.setattr(module, "head_loss", _old_head_loss)
            eng, sequences = _head_loss_engine(build)
            ts = eng.init_state(jax.random.PRNGKey(0))
            ids_s, tg_s = _head_loss_batch(eng, sequences)
            text = eng.train_step.lower(
                ts, ids_s, tg_s, jnp.float32(0.1)
            ).as_text()
            assert bool(
                _whole_plane_tensors(text, _SHARD_ROWS)
            ) == patched
            metrics = []
            for _ in range(3):
                ts, m_step = eng.train_step(
                    ts, ids_s, tg_s, jnp.float32(0.1)
                )
                metrics.append(jax.device_get(m_step))
            metrics.append(jax.device_get(eng.eval_step(ts, ids_s, tg_s)))
            return jax.device_get(ts.params), metrics

    params, metrics = run(False)
    old_params, old_metrics = run(True)
    for got, want in zip(metrics, old_metrics):
        assert got["count"] == want["count"] > 0
        assert got["correct1"] == want["correct1"]
        assert got["correct5"] == want["correct5"]
        np.testing.assert_allclose(
            got["loss_sum"], want["loss_sum"], rtol=1e-5
        )
    assert metrics[2]["loss_sum"] < metrics[0]["loss_sum"]
    for got, want in zip(
        jax.tree_util.tree_leaves(params),
        jax.tree_util.tree_leaves(old_params),
    ):
        np.testing.assert_allclose(got, want, rtol=2e-4, atol=2e-5)


def test_two_stage_plan_still_puts_its_logits_on_the_wire(monkeypatch):
    """With more than one stage the tick's logits are the wire's
    payload (the last stage packs them into the buffer every stage
    permutes), so the tick keeps `head_apply` + `_local_sums`: decided
    by the plan's stage count, nothing else."""
    from distributed_model_parallel_tpu.ops import head_loss as HL

    monkeypatch.setattr(HL, "BLOCK_ELEMENTS", 16 * _HEAD_VOCAB)
    eng, found, text = _plan_step("pp2xdp2")
    # a microbatch's logits, whole, and the wire sized to carry them
    mb_rows = 4 * 16
    assert _whole_plane_tensors(text, mb_rows)
    wire = mb_rows * _PLAN_CFG["vocab_size"]
    assert f"tensor<{wire}xf32>" in text
    assert '"stablehlo.collective_permute"' in text
    # and the one-stage plan of the same widths holds neither
    _, _, text = _plan_step("dp4")
    assert not _whole_plane_tensors(text, mb_rows)
    assert f"tensor<{wire}xf32>" not in text
