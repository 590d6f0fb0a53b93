"""The Jamba family (`models/jamba.py`): its forward pass against the
plain reference, the built tree's count at the published widths, and
which layers attend."""

import dataclasses
import importlib.util
import json
import os

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from distributed_model_parallel_tpu.models import jamba
from distributed_model_parallel_tpu.models import layers as L

ROOT = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
# The catalog's row for AI21-Jamba2-3B (`config` of
# /opt/skills/guides/model-configs/architectures.jsonl), as published.
PUBLISHED = {
    "attn_layer_offset": 7, "attn_layer_period": 14,
    "expert_layer_offset": 1, "expert_layer_period": 2,
    "hidden_act": "silu", "hidden_size": 2560, "intermediate_size": 8192,
    "mamba_conv_bias": True, "mamba_d_conv": 4, "mamba_d_state": 16,
    "mamba_dt_rank": 160, "mamba_expand": 2, "mamba_proj_bias": False,
    "max_position_embeddings": 262144, "model_type": "jamba",
    "num_attention_heads": 20, "num_experts": 1, "num_experts_per_tok": 1,
    "num_hidden_layers": 28, "num_key_value_heads": 1,
    "num_logits_to_keep": 1, "rms_norm_eps": 1e-06, "sliding_window": None,
    "tie_word_embeddings": True, "use_mamba_kernels": True,
    "vocab_size": 65536,
}
TINY = {**PUBLISHED, "vocab_size": 97, "hidden_size": 32,
        "intermediate_size": 64, "num_attention_heads": 4,
        "num_hidden_layers": 5, "attn_layer_period": 4,
        "attn_layer_offset": 1, "mamba_d_state": 4, "mamba_dt_rank": 6}


def reference():
    spec = importlib.util.spec_from_file_location(
        "jamba_ref", os.path.join(ROOT, "benchmark/reference/jamba_ref.py"))
    module = importlib.util.module_from_spec(spec)
    spec.loader.exec_module(module)
    return module


def count(tree):
    return sum(int(np.prod(x.shape)) for x in jax.tree_util.tree_leaves(tree))


def test_the_built_tree_has_the_published_count_layer_by_layer():
    cfg = jamba.config_from_dict(PUBLISHED)
    params, _ = jax.eval_shape(
        jamba.jamba_lm(cfg).init, jax.ShapeDtypeStruct((2,), jnp.uint32))
    assert count(params) == 3_029_337_472
    kinds = [cfg.mixer_kind(i) for i in range(28)]
    assert [i for i, k in enumerate(kinds) if k == "attn"] == [7, 21]
    # by hand: mixer (in_proj, conv + bias, x_proj, dt_proj + bias,
    # A_log, D, three small norms, out_proj) + MLP + two block norms
    mlp, norms = 3 * 2560 * 8192, 2 * 2560
    ssm = (2560 * 10240 + 5120 * 4 + 5120 + 5120 * 192 + 160 * 5120 + 5120
           + 5120 * 16 + 5120 + 192 + 5120 * 2560) + mlp + norms
    attn = 2 * 2560 * 2560 + 2 * 2560 * 128 + mlp + norms
    rest = 65536 * 2560 + 2560
    assert (ssm, attn, rest) == (104_161_472, 76_682_240, 167_774_720)
    for i, kind in enumerate(kinds):
        assert count(params["blocks"][str(i)]) == (
            attn if kind == "attn" else ssm), i
    mixer = params["blocks"]["0"]["mixer"]
    assert count(mixer) == 41_241_792
    assert mixer["a_log"].shape == (16, 5120)  # channels on the lanes
    assert count(params["stem"]) + count(params["head"]) == rest
    assert 26 * ssm + 2 * attn + rest == 3_029_337_472


@pytest.mark.parametrize("key,value", [
    ("num_experts", 16), ("sliding_window", 4096), ("hidden_act", "gelu"),
    ("tie_word_embeddings", False), ("mamba_proj_bias", True),
])
def test_what_is_not_built_is_refused_by_its_key(key, value):
    with pytest.raises(NotImplementedError, match=key):
        jamba.config_from_dict({**PUBLISHED, key: value})


def test_forward_equals_the_plain_reference_on_seeded_weights():
    cfg = jamba.config_from_dict(TINY)
    model = jamba.jamba_lm(cfg)
    params, state = model.init(jax.random.PRNGKey(3))
    # every vector off its initial value, so each one is seen to matter
    leaves, tree = jax.tree_util.tree_flatten(params)
    keys = jax.random.split(jax.random.PRNGKey(4), len(leaves))
    params = jax.tree_util.tree_unflatten(tree, [
        x + 0.1 * jax.random.normal(k, x.shape) if x.ndim == 1 else x
        for x, k in zip(leaves, keys)])
    ids = jax.random.randint(jax.random.PRNGKey(5), (2, 41), 0, 97)
    got, _ = model.apply(params, state, ids, L.Context(train=False))
    want = reference().forward(
        params, ids, num_heads=4, num_kv_heads=1, eps=1e-6)
    assert got.dtype == jnp.float32 and got.shape == (2, 41, 97)
    np.testing.assert_allclose(got, want, rtol=1e-5, atol=1e-5)


def test_the_reference_imports_nothing_from_the_program():
    with open(os.path.join(ROOT, "benchmark/reference/jamba_ref.py")) as f:
        text = f.read()
    assert "distributed_model_parallel_tpu" not in text.split('"""', 2)[2]
    assert 'default_matmul_precision("highest")' in text


def test_the_serving_family_states_each_layers_cache_and_what_is_missing():
    cfg = jamba.config_from_dict({**PUBLISHED, "torch_dtype": "bfloat16"})
    fam = cfg.serving_family()
    assert fam.param_dtype == jnp.bfloat16
    paged = [i for i, lc in enumerate(fam.layers) if lc.kv_heads]
    assert paged == [7, 21]
    assert (fam.layers[7].kv_heads, fam.layers[7].head_dim) == (1, 128)
    state = fam.layers[0].state
    assert state["h"] == ((16, 5120), jnp.float32)
    assert state["conv"] == ((3, 5120), None)
    assert set(fam.missing) == {
        "prefix_cache", "speculative_k", "layout=tp", "layout=sp",
        "page_size=None", "prefill_chunk=None"}
    # 358,400 bytes a layer a slot with bfloat16 activations
    per_layer = 16 * 5120 * 4 + 3 * 5120 * 2
    assert per_layer == 358_400 and 26 * per_layer == 9_318_400


def test_the_benchmark_file_is_the_catalogs_row_key_by_key():
    with open(os.path.join(ROOT, "benchmark/configs/jamba2-3b.json")) as f:
        config = json.load(f)
    for key, value in PUBLISHED.items():
        assert config[key] == value, key
    assert config["reduced"] == []
    cfg = jamba.config_from_dict(config)
    assert dataclasses.asdict(cfg)["param_dtype"] == "bfloat16"
