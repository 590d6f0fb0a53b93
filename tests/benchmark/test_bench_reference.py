"""The plain reference against the program at toy widths on the CPU:
the dense model, the paged engine (prefill into pages, then decode
through the cache) and the training loss."""

import copy
import json
import os
import sys

import jax
import jax.numpy as jnp
import numpy as np
import pytest

ROOT = os.path.dirname(os.path.dirname(os.path.dirname(os.path.abspath(__file__))))
sys.path.insert(0, ROOT)

from benchmark.builders import gpt as builder  # noqa: E402
from benchmark.drivers import serve_drain  # noqa: E402
from benchmark.reference import gpt2_ref  # noqa: E402


def toy_config(compute_dtype="f32"):
    with open(os.path.join(ROOT, "benchmark", "configs", "gpt2-small.json")) as f:
        config = builder.rehearse(json.load(f))
    config["serving"]["compute_dtype"] = compute_dtype
    return config


@pytest.fixture(scope="module")
def toy():
    from distributed_model_parallel_tpu.models.gpt import gpt_lm

    config = toy_config()
    cfg = builder.gpt_config(config, 64)
    model = gpt_lm(cfg)
    params, state = model.init(jax.random.PRNGKey(3))
    # biases and LayerNorm parameters start at 0 and 1: move them, or a
    # reference that dropped one would still agree
    leaves, tree = jax.tree_util.tree_flatten(params)
    keys = jax.random.split(jax.random.PRNGKey(4), len(leaves))
    params = jax.tree_util.tree_unflatten(tree, [
        leaf + 0.05 * jax.random.normal(k, leaf.shape)
        for leaf, k in zip(leaves, keys)
    ])
    ids = jax.random.randint(jax.random.PRNGKey(5), (3, 64), 1, cfg.vocab_size)
    return config, cfg, model, params, state, ids


def test_reference_imports_nothing_from_the_package():
    with open(gpt2_ref.__file__) as f:
        source = f.read()
    assert "import distributed_model_parallel_tpu" not in source
    assert "from distributed_model_parallel_tpu" not in source


def test_reference_forward_equals_the_dense_model(toy):
    from distributed_model_parallel_tpu.models.layers import Context

    _, cfg, model, params, state, ids = toy
    want, _ = model.apply(params, state, ids, Context(train=False))
    got = gpt2_ref.forward(params, ids, cfg.num_heads)
    assert got.shape == (3, 64, cfg.vocab_size) and got.dtype == jnp.float32
    # float32 on both sides; the orders of summation differ
    np.testing.assert_allclose(got, want, rtol=2e-4, atol=2e-5)


def test_reference_loss_equals_the_programs_lm_loss(toy):
    from distributed_model_parallel_tpu.models.gpt import lm_loss
    from distributed_model_parallel_tpu.models.layers import Context

    _, cfg, model, params, state, ids = toy
    logits, _ = model.apply(params, state, ids, Context(train=False))
    total, count = gpt2_ref.next_token_loss(params, ids, cfg.num_heads)
    assert count == 3 * 63
    assert float(total) / count == pytest.approx(
        float(lm_loss(logits, ids, cfg.pad_token_id)), rel=1e-5)


@pytest.mark.parametrize("dtype,worst", [("f32", 2e-4), ("bf16", 3e-2)])
def test_paged_engine_agrees_with_the_reference(dtype, worst):
    """`check_against_reference` is the comparison behind `correct` in
    the serving cells: chunked prefill into pages, then decode through
    the cache, against the reference's full forward pass."""
    config = toy_config(dtype)
    engine = builder.serving_engine(config)
    params = jax.jit(engine.init_params)(jax.random.PRNGKey(0))
    got = serve_drain.check_against_reference(
        engine, params, config, 0, builder.shape(config), gpt2_ref
    )
    errs = [got["logit_err_prefill"], *got["logit_err_decode"]]
    assert len(errs) == 1 + serve_drain.CHECK_DECODE
    assert max(errs) < worst
    assert got["ok"]
    # the spies are gone again
    assert engine.chunk_prefill.__name__ != "spy_chunk"


def test_the_check_fails_when_the_arithmetic_is_wrong():
    """A model whose head is off by a few percent is not `correct`."""
    config = toy_config("f32")
    engine = builder.serving_engine(config)
    params = jax.jit(engine.init_params)(jax.random.PRNGKey(0))
    wrong = copy.copy(params)
    wrong["head"] = {"w": params["head"]["w"] * 1.1}

    class Skewed:
        @staticmethod
        def forward(_params, ids, num_heads):
            return gpt2_ref.forward(wrong, ids, num_heads)

    got = serve_drain.check_against_reference(
        engine, params, config, 0, builder.shape(config), Skewed
    )
    assert not got["ok"]
