"""Causal LM (GPT-style decoder) tests, including the data-parallel
training recipe and the flash/ring attention_fn swaps."""

import os
from functools import partial

import jax
import jax.numpy as jnp
import numpy as np
import pytest
from jax.sharding import NamedSharding, PartitionSpec as P

from distributed_model_parallel_tpu.models import layers as L
from distributed_model_parallel_tpu.models.gpt import (
    GPTConfig,
    gpt_lm,
    lm_loss,
)
from distributed_model_parallel_tpu.runtime.mesh import MeshSpec, make_mesh

TINY = GPTConfig(
    vocab_size=61, dim=32, num_layers=2, num_heads=4, ffn_dim=64,
    max_position=32, dropout_rate=0.0,
)
B, T = 8, 16


def _ids(seed=0):
    rng = np.random.RandomState(seed)
    return rng.randint(1, TINY.vocab_size, size=(B, T)).astype(np.int32)


def test_shapes_and_causality():
    model = gpt_lm(TINY)
    params, state = model.init(jax.random.PRNGKey(0))
    ids = jnp.asarray(_ids())
    logits, _ = model.apply(params, state, ids, L.Context(train=False))
    assert logits.shape == (B, T, TINY.vocab_size)
    assert logits.dtype == jnp.float32
    # Causality: editing a FUTURE token must not change past logits.
    ids2 = ids.at[:, -1].set((ids[:, -1] % (TINY.vocab_size - 1)) + 1)
    logits2, _ = model.apply(params, state, ids2, L.Context(train=False))
    np.testing.assert_allclose(
        np.asarray(logits[:, :-1]), np.asarray(logits2[:, :-1]),
        rtol=1e-6,
    )
    assert not np.allclose(
        np.asarray(logits[:, -1]), np.asarray(logits2[:, -1])
    )


def test_lm_loss_shift_and_padding():
    cfg = GPTConfig(**{**TINY.__dict__, "pad_token_id": 0})
    model = gpt_lm(cfg)
    params, state = model.init(jax.random.PRNGKey(0))
    ids = _ids()
    ids[:, -4:] = 0  # pad tail
    logits, _ = model.apply(
        params, state, jnp.asarray(ids), L.Context(train=False)
    )
    loss = lm_loss(logits, jnp.asarray(ids), pad_token_id=0)
    assert np.isfinite(float(loss))
    # Fully padded targets -> loss ignores them: perturbing logits at
    # padded target positions must not change the loss.
    logits_pad = logits.at[:, -4:, :].add(100.0)
    loss2 = lm_loss(logits_pad, jnp.asarray(ids), pad_token_id=0)
    # positions -4..-2 predict padded targets; -5 predicts the first pad
    np.testing.assert_allclose(float(loss), float(loss2), rtol=1e-6)


def test_data_parallel_lm_training_learns():
    """The LM training recipe: batch sharded over 'data' under plain
    jit, grads derived by the partitioner — memorize a fixed corpus."""
    mesh = make_mesh(MeshSpec(data=8))
    repl = NamedSharding(mesh, P())
    bsh = NamedSharding(mesh, P(("data",)))
    model = gpt_lm(TINY)
    params, state = model.init(jax.random.PRNGKey(0))
    ids = jax.device_put(jnp.asarray(_ids(seed=4)), bsh)
    params = jax.device_put(params, repl)

    @partial(jax.jit, in_shardings=(repl, bsh), out_shardings=(repl, None),
             donate_argnums=(0,))
    def step(params, ids):
        def loss_fn(p):
            logits, _ = model.apply(p, state, ids, L.Context(train=True))
            return lm_loss(logits, ids)

        loss, grads = jax.value_and_grad(loss_fn)(params)
        params2 = jax.tree_util.tree_map(
            lambda p, g: p - 0.5 * g, params, grads
        )
        return params2, loss

    losses = []
    # 40 plain-SGD steps: enough to halve the loss across JAX versions
    # (convergence speed drifts slightly with backend numerics; 25 steps
    # landed at 0.54x on jax 0.4.37's CPU backend).
    for _ in range(40):
        params, loss = step(params, ids)
        losses.append(float(loss))
    assert losses[-1] < losses[0] * 0.5, losses[::6]


@pytest.mark.parametrize("kind", ["flash", "ring"])
def test_attention_fn_swaps_match_dense(kind):
    """The same LM runs on the Pallas flash kernel or sequence-parallel
    ring attention with identical logits."""
    model_dense = gpt_lm(TINY)
    params, state = model_dense.init(jax.random.PRNGKey(1))
    ids = jnp.asarray(_ids(seed=2))
    want, _ = model_dense.apply(params, state, ids, L.Context(train=False))

    if kind == "flash":
        from distributed_model_parallel_tpu.ops.pallas_attention import (
            flash_attention,
        )

        model = gpt_lm(
            TINY,
            attention_fn=partial(
                flash_attention, causal=True, block_q=8, block_k=8
            ),
        )
        got, _ = model.apply(params, state, ids, L.Context(train=False))
    else:
        from distributed_model_parallel_tpu.runtime.compat import shard_map
        from distributed_model_parallel_tpu.models.gpt import (
            _lm_stem,
            decoder_blocks,
        )
        from distributed_model_parallel_tpu.ops.ring_attention import (
            ring_attention,
        )

        mesh = make_mesh(MeshSpec(data=2, seq=4))
        ring_blocks = L.sequential(*decoder_blocks(
            TINY, partial(ring_attention, axis_name="seq", causal=True)
        ))
        bstate = {str(i): {} for i in range(TINY.num_layers)}

        # Stem/head are per-token; only attention crosses tokens, so the
        # block stack + head run seq-sharded. (Position offsets in a
        # seq-sharded STEM are the SequenceParallelEngine's job; here the
        # dense stem runs first and its output is sharded.)
        @jax.jit
        @partial(
            shard_map, mesh=mesh,
            in_specs=(P(), (P(None, ("seq",)), P(None, ("seq",)))),
            out_specs=P(None, ("seq",)),
            check_vma=False,
        )
        def blocks_sp(p, x):
            (h, _), _ = ring_blocks.apply(
                p["blocks"], bstate, x, L.Context()
            )
            return h.astype(jnp.float32) @ p["head"]["w"]

        (hh, mm), _ = _lm_stem(TINY).apply(
            params["stem"], {}, ids, L.Context(train=False)
        )
        got = blocks_sp(params, (hh, mm))

    np.testing.assert_allclose(
        np.asarray(got), np.asarray(want), rtol=1e-4, atol=1e-4
    )


def test_lm_loss_fn_binds_pad_id():
    from distributed_model_parallel_tpu.models.gpt import lm_loss_fn

    cfg = GPTConfig(**{**TINY.__dict__, "pad_token_id": 0})
    model = gpt_lm(cfg)
    params, state = model.init(jax.random.PRNGKey(0))
    ids = _ids()
    ids[:, -4:] = 0
    logits, _ = model.apply(
        params, state, jnp.asarray(ids), L.Context(train=False)
    )
    bound = lm_loss_fn(cfg)(logits, jnp.asarray(ids))
    explicit = lm_loss(logits, jnp.asarray(ids), pad_token_id=0)
    np.testing.assert_allclose(float(bound), float(explicit))


def test_causal_lm_sequence_parallel_matches_dense():
    """CausalLMSequenceParallelEngine (data=2, seq=4) follows the SAME
    trajectory as a dense jit LM step: per-shard next-token loss sums +
    one grad psum equal the dense mean-loss gradient exactly."""
    from distributed_model_parallel_tpu.parallel.sequence_parallel import (
        CausalLMSequenceParallelEngine,
    )
    from distributed_model_parallel_tpu.training.optim import SGD

    mesh = make_mesh(MeshSpec(data=2, seq=4))
    eng = CausalLMSequenceParallelEngine(TINY, SGD(), mesh, donate=False)
    ts = eng.init_state(jax.random.PRNGKey(0))
    ids = _ids(seed=7)
    ids_s, targets_s = eng.shard_batch(ids)

    # dense twin, same init, plain full-batch grad of the mean loss
    model = gpt_lm(TINY)
    params, state = model.init(jax.random.PRNGKey(0))
    opt = SGD()
    opt_state = opt.init(params)
    idsj = jnp.asarray(ids)

    @jax.jit
    def dense_step(params, opt_state):
        def loss_fn(p):
            logits, _ = model.apply(p, state, idsj, L.Context(train=True))
            return lm_loss(logits, idsj)

        loss, grads = jax.value_and_grad(loss_fn)(params)
        params, opt_state = opt.update(params, opt_state, grads,
                                       jnp.float32(0.1))
        return params, opt_state, loss

    for step_i in range(3):
        ts, m = eng.train_step(ts, ids_s, targets_s, jnp.float32(0.1))
        params, opt_state, dense_loss = dense_step(params, opt_state)
        sp_loss = float(m["loss_sum"]) / float(m["count"])
        np.testing.assert_allclose(
            sp_loss, float(dense_loss), rtol=1e-5,
            err_msg=f"step {step_i}",
        )
    for (path, a), b in zip(
        jax.tree_util.tree_leaves_with_path(params),
        jax.tree_util.tree_leaves(ts.params),
    ):
        np.testing.assert_allclose(
            np.asarray(a), np.asarray(b), rtol=2e-4, atol=2e-5,
            err_msg=jax.tree_util.keystr(path),
        )
    # eval path agrees with a dense eval loss too
    ev = eng.eval_step(ts, ids_s, targets_s)
    logits, _ = model.apply(params, state, idsj, L.Context(train=False))
    np.testing.assert_allclose(
        float(ev["loss_sum"]) / float(ev["count"]),
        float(lm_loss(logits, idsj)), rtol=1e-5,
    )


def test_lm_targets_shift_and_padding():
    from distributed_model_parallel_tpu.models.gpt import lm_targets

    ids = np.array([[5, 6, 7, 0]], np.int32)
    t = lm_targets(ids, pad_token_id=0)
    np.testing.assert_array_equal(t, [[6, 7, -1, -1]])
    t2 = lm_targets(ids)  # no padding semantics
    np.testing.assert_array_equal(t2, [[6, 7, 0, -1]])


def test_lm_corpus_and_loader_deterministic():
    from distributed_model_parallel_tpu.data.lm import (
        LMLoader,
        chain_entropy,
        synthetic_corpus,
    )

    c1 = synthetic_corpus(64, 4096, seed=3)
    c2 = synthetic_corpus(64, 4096, seed=3)
    np.testing.assert_array_equal(c1, c2)
    assert c1.min() >= 1  # id 0 reserved for padding
    # same chain, different walk: a different stream over the SAME
    # transition support (that's what makes it a usable val split)
    cv = synthetic_corpus(64, 4096, seed=3, stream_seed=99)
    assert not np.array_equal(c1, cv)
    bigrams = lambda c: {(a, b) for a, b in zip(c[:-1], c[1:])}
    novel = bigrams(cv) - bigrams(c1)
    assert len(novel) / len(bigrams(cv)) < 0.2
    floor = chain_entropy(64, seed=3)
    assert 0.5 < floor < np.log(4) + 0.01  # branching=4 bounds it
    ld = LMLoader(c1, batch_size=4, seq_len=32, seed=0)
    ld.set_epoch(1)
    a = [ids.copy() for ids, _ in ld]
    ld2 = LMLoader(c1, batch_size=4, seq_len=32, seed=0)
    ld2.set_epoch(1)
    b = [ids.copy() for ids, _ in ld2]
    for x, y in zip(a, b):
        np.testing.assert_array_equal(x, y)
    assert len(a) == len(ld) == 32


def test_lm_cli_smoke(tmp_path, monkeypatch):
    """The LM pretraining entry point runs end to end (seq-sharded mesh,
    AdamW, Markov corpus) and the loss moves toward the printed floor."""
    monkeypatch.chdir(tmp_path)
    from distributed_model_parallel_tpu.cli.lm import main

    res = main([
        "--vocab-size", "64", "--dim", "32", "--layers", "1",
        "--heads", "4", "--seq-len", "32", "-b", "8",
        "--epochs", "2", "--steps-per-epoch", "6", "--lr", "3e-3",
        "--seq-shards", "4", "--corpus-tokens", str(1 << 13),
        "--log-file", "lm.txt",
    ])
    assert len(res["history"]) == 2
    h = res["history"]
    assert h[-1]["train"]["loss"] < h[0]["train"]["loss"]
    assert os.path.isfile(tmp_path / "log" / "lm.txt")
