"""Ring attention + Ulysses sequence-parallelism tests (8-device mesh).

Correctness bar: sequence-sharded attention must equal the unsharded
`dot_product_attention` — forward AND gradients — because both are exact
rearrangements, not approximations.
"""

from functools import partial

import jax
import jax.numpy as jnp
import numpy as np
import pytest
from distributed_model_parallel_tpu.runtime.compat import shard_map
from jax.sharding import PartitionSpec as P

from distributed_model_parallel_tpu.models import layers as L
from distributed_model_parallel_tpu.models.transformer import encoder_layer
from distributed_model_parallel_tpu.ops.attention import (
    dot_product_attention,
)
from distributed_model_parallel_tpu.ops.ring_attention import (
    ring_attention,
    ulysses_attention,
)
from distributed_model_parallel_tpu.runtime.mesh import MeshSpec, make_mesh

B, T, H, DH = 2, 16, 4, 8
SP = 4  # 'seq' axis size


@pytest.fixture(scope="module")
def sp_mesh():
    return make_mesh(MeshSpec(data=2, seq=SP))


def _qkv(seed=0, dtype=jnp.float32):
    rng = np.random.RandomState(seed)
    mk = lambda: jnp.asarray(
        rng.randn(B, T, H, DH).astype(np.float32), dtype
    )
    q, k, v = mk(), mk(), mk()
    mask = jnp.asarray(rng.rand(B, T) > 0.2)
    mask = mask.at[:, 0].set(True)  # at least one valid key per row
    return q, k, v, mask


def _sharded_attn(attn_fn, mesh):
    spec = P(None, ("seq",))
    return jax.jit(
        shard_map(
            partial(attn_fn, axis_name="seq"),
            mesh=mesh,
            in_specs=(spec, spec, spec, P(None, ("seq",))),
            out_specs=spec,
            check_vma=False,
        )
    )


@pytest.mark.parametrize("attn_fn", [ring_attention, ulysses_attention])
def test_forward_matches_full_attention(sp_mesh, attn_fn):
    q, k, v, mask = _qkv()
    want = dot_product_attention(q, k, v, mask)
    got = _sharded_attn(attn_fn, sp_mesh)(q, k, v, mask)
    np.testing.assert_allclose(
        np.asarray(got), np.asarray(want), rtol=1e-5, atol=1e-5
    )


@pytest.mark.parametrize("causal", [False, True])
@pytest.mark.parametrize("attn_fn", [ring_attention, ulysses_attention])
def test_gradients_match_full_attention(sp_mesh, attn_fn, causal):
    """Cotangents cross shards through the reversed ppermutes /
    all-to-alls; the grads wrt q, k, v must match the dense reference —
    with and without the causal block predicate."""
    q, k, v, mask = _qkv(seed=3)
    spec = P(None, ("seq",))
    sharded = jax.jit(
        shard_map(
            partial(attn_fn, axis_name="seq", causal=causal),
            mesh=sp_mesh,
            in_specs=(spec, spec, spec, P(None, ("seq",))),
            out_specs=spec,
            check_vma=False,
        )
    )

    def loss_sharded(q, k, v):
        return jnp.sum(jnp.square(sharded(q, k, v, mask)))

    def loss_dense(q, k, v):
        return jnp.sum(jnp.square(
            dot_product_attention(q, k, v, mask, causal=causal)
        ))

    got = jax.grad(loss_sharded, argnums=(0, 1, 2))(q, k, v)
    want = jax.grad(loss_dense, argnums=(0, 1, 2))(q, k, v)
    for g, w, name in zip(got, want, "qkv"):
        np.testing.assert_allclose(
            np.asarray(g), np.asarray(w), rtol=2e-4, atol=2e-5,
            err_msg=f"grad wrt {name}",
        )


def test_ring_bf16_roundtrip(sp_mesh):
    """bf16 inputs: accumulate in f32, return bf16, close to the dense
    bf16 reference."""
    q, k, v, mask = _qkv(seed=5, dtype=jnp.bfloat16)
    want = dot_product_attention(q, k, v, mask)
    got = _sharded_attn(ring_attention, sp_mesh)(q, k, v, mask)
    assert got.dtype == jnp.bfloat16
    np.testing.assert_allclose(
        np.asarray(got, np.float32), np.asarray(want, np.float32),
        rtol=5e-2, atol=5e-2,
    )


def test_encoder_stack_runs_sequence_parallel(sp_mesh):
    """A 2-layer transformer encoder stack running fully seq-sharded with
    ring attention == the same stack unsharded: sequence parallelism is a
    layout choice, invisible to the math. (LayerNorm/FFN are per-token,
    so only attention needs the ring.)"""
    dim, heads, ffn = 32, 4, 64
    stack_ring = L.sequential(
        encoder_layer(dim, heads, ffn, attention_fn=partial(
            ring_attention, axis_name="seq")),
        encoder_layer(dim, heads, ffn, attention_fn=partial(
            ring_attention, axis_name="seq")),
    )
    stack_dense = L.sequential(
        encoder_layer(dim, heads, ffn),
        encoder_layer(dim, heads, ffn),
    )
    params, _ = stack_dense.init(jax.random.PRNGKey(0))
    rng = np.random.RandomState(7)
    hseq = jnp.asarray(rng.randn(B, T, dim).astype(np.float32))
    mask = jnp.asarray(rng.rand(B, T) > 0.2).at[:, 0].set(True)

    (want, _), _ = stack_dense.apply(
        params, {"0": {}, "1": {}}, (hseq, mask), L.Context()
    )

    @jax.jit
    @partial(
        shard_map,
        mesh=sp_mesh,
        in_specs=(P(), (P(None, ("seq",)), P(None, ("seq",)))),
        out_specs=P(None, ("seq",)),
        check_vma=False,
    )
    def sp_forward(params, x):
        (h, _), _ = stack_ring.apply(
            params, {"0": {}, "1": {}}, x, L.Context()
        )
        return h

    got = sp_forward(params, (hseq, mask))
    np.testing.assert_allclose(
        np.asarray(got), np.asarray(want), rtol=1e-5, atol=1e-5
    )


# ---------------------------------------------------------------------------
# SequenceParallelEngine: full TRAINING with 'seq'-sharded activations.
# ---------------------------------------------------------------------------


@pytest.mark.parametrize("attention", ["ring", "ulysses"])
def test_sequence_parallel_engine_matches_dense_dp(sp_mesh, attention):
    """Training with activations sharded T/4 over 'seq' must follow the
    SAME trajectory as dense 8-way data parallelism: context parallelism
    is a memory layout, not a different optimizer."""
    from distributed_model_parallel_tpu.models.bert import (
        BertConfig,
        bert_for_classification,
    )
    from distributed_model_parallel_tpu.parallel.data_parallel import (
        DataParallelEngine,
    )
    from distributed_model_parallel_tpu.parallel.sequence_parallel import (
        SequenceParallelEngine,
    )
    from distributed_model_parallel_tpu.training.optim import SGD

    # One encoder layer: halves the two CPU-mesh compiles; multi-layer
    # composition under 'seq' sharding is covered by the two-layer
    # stack forward test above.
    cfg = BertConfig(
        vocab_size=67, hidden_size=32, num_layers=1, num_heads=4,
        intermediate_size=64, max_position=T, dropout_rate=0.0,
    )
    rng = np.random.RandomState(0)
    ids = rng.randint(1, 67, size=(8, T)).astype(np.int32)
    ids[:, -3:] = 0  # pad tail
    labels = rng.randint(0, 4, size=(8,)).astype(np.int32)

    sp = SequenceParallelEngine(
        cfg, 4, SGD(), sp_mesh, attention=attention, donate=False
    )
    ts_sp = sp.init_state(jax.random.PRNGKey(0))
    ids_sp, labels_sp = sp.shard_batch(ids, labels)

    dense_mesh = make_mesh(MeshSpec(data=8))
    dp = DataParallelEngine(
        bert_for_classification(4, cfg), SGD(), dense_mesh, donate=False
    )
    ts_dp = dp.init_state(jax.random.PRNGKey(0))
    ids_dp, labels_dp = dp.shard_batch(ids, labels)

    for step in range(3):
        ts_sp, m_sp = sp.train_step(
            ts_sp, ids_sp, labels_sp, jnp.float32(0.05)
        )
        ts_dp, m_dp = dp.train_step(
            ts_dp, ids_dp, labels_dp, jnp.float32(0.05)
        )
        np.testing.assert_allclose(
            float(m_sp["loss_sum"]), float(m_dp["loss_sum"]),
            rtol=1e-4, err_msg=f"step {step} loss",
        )
        np.testing.assert_allclose(
            float(m_sp["correct1"]), float(m_dp["correct1"]), atol=0.5,
        )
    for (path, a), b in zip(
        jax.tree_util.tree_leaves_with_path(ts_dp.params),
        jax.tree_util.tree_leaves(ts_sp.params),
    ):
        np.testing.assert_allclose(
            np.asarray(a), np.asarray(b), rtol=2e-4, atol=2e-5,
            err_msg=jax.tree_util.keystr(path),
        )


def test_sequence_parallel_eval_and_checkpoint_interop(sp_mesh):
    """Eval path works, and the param pytree is structurally identical to
    the dense BERT's (checkpoints/transplants interoperate)."""
    from distributed_model_parallel_tpu.models.bert import (
        BertConfig,
        bert_for_classification,
    )
    from distributed_model_parallel_tpu.parallel.sequence_parallel import (
        SequenceParallelEngine,
    )
    from distributed_model_parallel_tpu.training.optim import SGD

    cfg = BertConfig(
        vocab_size=67, hidden_size=32, num_layers=1, num_heads=4,
        intermediate_size=64, max_position=T, dropout_rate=0.0,
    )
    sp = SequenceParallelEngine(cfg, 4, SGD(), sp_mesh, donate=False)
    ts = sp.init_state(jax.random.PRNGKey(1))
    dense_params, _ = bert_for_classification(4, cfg).init(
        jax.random.PRNGKey(1)
    )
    assert (
        jax.tree_util.tree_structure(ts.params)
        == jax.tree_util.tree_structure(dense_params)
    )
    rng = np.random.RandomState(1)
    ids = rng.randint(1, 67, size=(8, T)).astype(np.int32)
    labels = rng.randint(0, 4, size=(8,)).astype(np.int32)
    m = sp.eval_step(ts, *sp.shard_batch(ids, labels))
    assert float(m["count"]) == 8
    assert np.isfinite(float(m["loss_sum"]))


def test_shard_batch_rejects_overlong_sequences(sp_mesh):
    """Both SP engines' forward passes slice the position table with
    dynamic_slice, which CLAMPS out-of-range starts — so a T beyond
    max_position would silently reuse the last position rows on later
    'seq' shards. shard_batch must refuse instead; T == max_position is
    the boundary and must pass."""
    from distributed_model_parallel_tpu.models.bert import BertConfig
    from distributed_model_parallel_tpu.models.gpt import GPTConfig
    from distributed_model_parallel_tpu.parallel.sequence_parallel import (
        CausalLMSequenceParallelEngine,
        SequenceParallelEngine,
    )
    from distributed_model_parallel_tpu.training.optim import SGD

    bert_cfg = BertConfig(
        vocab_size=67, hidden_size=32, num_layers=1, num_heads=4,
        intermediate_size=64, max_position=T, dropout_rate=0.0,
    )
    sp = SequenceParallelEngine(bert_cfg, 4, SGD(), sp_mesh, donate=False)
    rng = np.random.RandomState(0)
    labels = rng.randint(0, 4, size=(8,)).astype(np.int32)
    ok = rng.randint(1, 67, size=(8, T)).astype(np.int32)
    sp.shard_batch(ok, labels)  # boundary length passes
    too_long = rng.randint(1, 67, size=(8, 2 * T)).astype(np.int32)
    with pytest.raises(ValueError, match="max_position"):
        sp.shard_batch(too_long, labels)

    gpt_cfg = GPTConfig(
        vocab_size=61, dim=32, num_layers=1, num_heads=4, ffn_dim=64,
        max_position=T, dropout_rate=0.0,
    )
    lm = CausalLMSequenceParallelEngine(gpt_cfg, SGD(), sp_mesh, donate=False)
    lm.shard_batch(rng.randint(1, 61, size=(8, T)).astype(np.int32))
    with pytest.raises(ValueError, match="max_position"):
        lm.shard_batch(rng.randint(1, 61, size=(8, 2 * T)).astype(np.int32))


# ---------------------------------------------------------------------------
# Causal attention (decoder-style) across all attention implementations.
# ---------------------------------------------------------------------------


def test_causal_dense_reference_is_triangular():
    """Numpy ground truth: each query only attends to keys <= its
    position."""
    q, k, v, _ = _qkv(seed=9)
    out = dot_product_attention(q, k, v, causal=True)
    # Query 0 can only see key 0: its output must equal v[:, 0] exactly.
    np.testing.assert_allclose(
        np.asarray(out[:, 0]), np.asarray(v[:, 0]), rtol=1e-6
    )
    # And changing a FUTURE key must not change past outputs.
    v2 = v.at[:, -1].set(0.0)
    out2 = dot_product_attention(q, k, v2, causal=True)
    np.testing.assert_allclose(
        np.asarray(out[:, :-1]), np.asarray(out2[:, :-1]), rtol=1e-6
    )


@pytest.mark.parametrize("attn_fn", [ring_attention, ulysses_attention])
def test_causal_sharded_matches_dense(sp_mesh, attn_fn):
    """Causality with global positions survives sequence sharding: the
    ring's block-index predicate == the dense triangle."""
    q, k, v, mask = _qkv(seed=10)
    want = dot_product_attention(q, k, v, mask, causal=True)
    spec = P(None, ("seq",))
    sharded = jax.jit(
        shard_map(
            partial(attn_fn, axis_name="seq", causal=True),
            mesh=sp_mesh,
            in_specs=(spec, spec, spec, P(None, ("seq",))),
            out_specs=spec,
            check_vma=False,
        )
    )
    got = sharded(q, k, v, mask)
    np.testing.assert_allclose(
        np.asarray(got), np.asarray(want), rtol=1e-5, atol=1e-5
    )


def test_causal_flash_matches_dense():
    from distributed_model_parallel_tpu.ops.pallas_attention import (
        flash_attention,
    )

    rng = np.random.RandomState(11)
    t = 128
    mk = lambda: jnp.asarray(rng.randn(2, t, 4, 16).astype(np.float32))
    q, k, v = mk(), mk(), mk()
    mask = jnp.asarray(rng.rand(2, t) > 0.2).at[:, 0].set(True)
    want = dot_product_attention(q, k, v, mask, causal=True)
    got = flash_attention(q, k, v, mask, causal=True, block_q=32, block_k=32)
    np.testing.assert_allclose(
        np.asarray(got), np.asarray(want), rtol=1e-5, atol=1e-5
    )
    # grads through the causal custom_vjp
    g1 = jax.grad(lambda q: jnp.sum(jnp.square(
        flash_attention(q, k, v, mask, causal=True, block_q=32, block_k=32)
    )))(q)
    g2 = jax.grad(lambda q: jnp.sum(jnp.square(
        dot_product_attention(q, k, v, mask, causal=True)
    )))(q)
    np.testing.assert_allclose(
        np.asarray(g1), np.asarray(g2), rtol=2e-4, atol=2e-5
    )


@pytest.mark.parametrize("causal", [False, True])
def test_ring_flash_matches_full_attention(sp_mesh, causal):
    """ring_flash_attention (per-hop flash kernels + LSE merge) must
    equal dense attention — forward and all three gradients, with a
    padding mask, causal and not. On the CI mesh the tiny blocks take
    the dense per-hop fallback; the merge/rotation logic is identical."""
    from distributed_model_parallel_tpu.ops.ring_attention import (
        ring_flash_attention,
    )

    q, k, v, mask = _qkv(seed=21)
    spec = P(None, ("seq",))
    sharded = jax.jit(
        shard_map(
            partial(ring_flash_attention, axis_name="seq", causal=causal),
            mesh=sp_mesh,
            in_specs=(spec, spec, spec, P(None, ("seq",))),
            out_specs=spec,
            check_vma=False,
        )
    )
    want = dot_product_attention(q, k, v, mask, causal=causal)
    got = sharded(q, k, v, mask)
    np.testing.assert_allclose(
        np.asarray(got), np.asarray(want), rtol=1e-5, atol=1e-5
    )

    def loss_sharded(q, k, v):
        return jnp.sum(jnp.square(sharded(q, k, v, mask)))

    def loss_dense(q, k, v):
        return jnp.sum(jnp.square(
            dot_product_attention(q, k, v, mask, causal=causal)
        ))

    got_g = jax.grad(loss_sharded, argnums=(0, 1, 2))(q, k, v)
    want_g = jax.grad(loss_dense, argnums=(0, 1, 2))(q, k, v)
    for gg, wg, name in zip(got_g, want_g, "qkv"):
        np.testing.assert_allclose(
            np.asarray(gg), np.asarray(wg), rtol=2e-4, atol=2e-5,
            err_msg=f"grad wrt {name} (causal={causal})",
        )


def test_ring_flash_no_mask(sp_mesh):
    from distributed_model_parallel_tpu.ops.ring_attention import (
        ring_flash_attention,
    )

    q, k, v, _ = _qkv(seed=22)
    spec = P(None, ("seq",))
    sharded = jax.jit(
        shard_map(
            partial(ring_flash_attention, axis_name="seq"),
            mesh=sp_mesh,
            in_specs=(spec, spec, spec),
            out_specs=spec,
            check_vma=False,
        )
    )
    want = dot_product_attention(q, k, v)
    got = sharded(q, k, v)
    np.testing.assert_allclose(
        np.asarray(got), np.asarray(want), rtol=1e-5, atol=1e-5
    )
    g = jax.grad(
        lambda k: jnp.sum(jnp.square(sharded(q, k, v)))
    )(k)
    gw = jax.grad(
        lambda k: jnp.sum(jnp.square(dot_product_attention(q, k, v)))
    )(k)
    np.testing.assert_allclose(
        np.asarray(g), np.asarray(gw), rtol=2e-4, atol=2e-5
    )


def test_ring_flash_kernel_path_multihop(sp_mesh):
    """Shapes large enough that every hop runs the PALLAS kernels
    (interpret mode here): the LSE merge and the rotating dk/dv
    delivery are exercised with the production per-hop core, not the
    dense fallback."""
    from distributed_model_parallel_tpu.ops.ring_attention import (
        ring_flash_attention,
    )

    b, t, h, dh = 1, 512, 2, 16  # Tl = 128 per shard -> kernel path
    rng = np.random.RandomState(0)
    mk = lambda: jnp.asarray(rng.randn(b, t, h, dh).astype(np.float32))
    q, k, v = mk(), mk(), mk()
    # A mask exercises the kernel path's per-hop mask rotation, the
    # whole-mask BlockSpec, and the +/-inf LSE sentinel conversion.
    mask = jnp.asarray(rng.rand(b, t) > 0.2).at[:, 0].set(True)
    spec = P(None, ("seq",))
    f = jax.jit(shard_map(
        partial(ring_flash_attention, axis_name="seq", causal=True),
        mesh=sp_mesh,
        in_specs=(spec, spec, spec, P(None, ("seq",))),
        out_specs=spec,
        check_vma=False,
    ))
    want = dot_product_attention(q, k, v, mask, causal=True)
    np.testing.assert_allclose(
        np.asarray(f(q, k, v, mask)), np.asarray(want),
        rtol=2e-5, atol=2e-5,
    )
    g = jax.grad(lambda k: jnp.sum(f(q, k, v, mask) ** 2))(k)
    gw = jax.grad(
        lambda k: jnp.sum(
            dot_product_attention(q, k, v, mask, causal=True) ** 2
        )
    )(k)
    np.testing.assert_allclose(
        np.asarray(g), np.asarray(gw), rtol=2e-4, atol=2e-5
    )


def test_lm_engine_ring_flash_trains():
    """attention='ring_flash' drops into the causal-LM engine."""
    from distributed_model_parallel_tpu.models.gpt import GPTConfig
    from distributed_model_parallel_tpu.parallel.sequence_parallel import (
        CausalLMSequenceParallelEngine,
    )
    from distributed_model_parallel_tpu.training.optim import SGD

    cfg = GPTConfig(
        vocab_size=61, dim=32, num_layers=1, num_heads=4, ffn_dim=64,
        max_position=16, dropout_rate=0.0,
    )
    mesh = make_mesh(MeshSpec(data=2, seq=4))
    eng = CausalLMSequenceParallelEngine(
        cfg, SGD(), mesh, attention="ring_flash", donate=False
    )
    ts = eng.init_state(jax.random.PRNGKey(0))
    rng = np.random.RandomState(1)
    ids = rng.randint(1, 61, size=(8, 16)).astype(np.int32)
    i, t = eng.shard_batch(ids)
    losses = []
    for _ in range(4):
        ts, m = eng.train_step(ts, i, t, jnp.float32(0.3))
        losses.append(float(m["loss_sum"]) / float(m["count"]))
    assert losses[-1] < losses[0]


def test_ulysses_flash_matches_dense(sp_mesh):
    """Ulysses with the Pallas kernel as its local core == dense
    attention, forward and gradients (kernel-viable local length)."""
    from distributed_model_parallel_tpu.parallel.sequence_parallel import (
        ATTENTION,
    )

    b, t, h, dh = 1, 128, 4, 16
    rng = np.random.RandomState(3)
    mk = lambda: jnp.asarray(rng.randn(b, t, h, dh).astype(np.float32))
    q, k, v = mk(), mk(), mk()
    mask = jnp.asarray(rng.rand(b, t) > 0.2).at[:, 0].set(True)
    spec = P(None, ("seq",))
    f = jax.jit(shard_map(
        partial(ATTENTION["ulysses_flash"], axis_name="seq", causal=True),
        mesh=sp_mesh,
        in_specs=(spec, spec, spec, P(None, ("seq",))),
        out_specs=spec,
        check_vma=False,
    ))
    want = dot_product_attention(q, k, v, mask, causal=True)
    np.testing.assert_allclose(
        np.asarray(f(q, k, v, mask)), np.asarray(want),
        rtol=2e-5, atol=2e-5,
    )
    g = jax.grad(lambda v: jnp.sum(f(q, k, v, mask) ** 2))(v)
    gw = jax.grad(
        lambda v: jnp.sum(
            dot_product_attention(q, k, v, mask, causal=True) ** 2
        )
    )(v)
    np.testing.assert_allclose(
        np.asarray(g), np.asarray(gw), rtol=2e-4, atol=2e-5
    )
