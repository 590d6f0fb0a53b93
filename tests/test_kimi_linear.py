"""The Kimi-Linear family (`models/kimi_linear.py`) against its plain
reference at a small size with seeded random weights — logits, loss and
gradients of a 5-layer model that has every kind of layer — and on the
normal path: `CausalLMSequenceParallelEngine` behind the family seam,
`cli.lm --model-config`, its fail-fast guards, the step counters."""

import json
import os
import sys

import jax
import jax.numpy as jnp
import numpy as np
import pytest
from jax.sharding import Mesh

ROOT = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
sys.path.insert(0, ROOT)

from benchmark.reference import kimi_linear_ref as ref  # noqa: E402
from distributed_model_parallel_tpu.models import kimi_linear as kl  # noqa: E402
from distributed_model_parallel_tpu.models.layers import Context  # noqa: E402
from distributed_model_parallel_tpu.parallel.sequence_parallel import (  # noqa: E402
    CausalLMSequenceParallelEngine,
)
from distributed_model_parallel_tpu.training.optim import AdamW  # noqa: E402

# The release's keys at toy sizes: one dense layer, then KDA, KDA, MLA,
# KDA; 4 of 16 experts held, 4 a token.
TOY = {
    "model_type": "kimi_linear", "vocab_size": 96, "hidden_size": 32,
    "num_hidden_layers": 5, "num_attention_heads": 2,
    "num_key_value_heads": 2, "head_dim": 16, "hidden_act": "silu",
    "intermediate_size": 64, "moe_intermediate_size": 16,
    "kv_lora_rank": 12, "q_lora_rank": None, "qk_nope_head_dim": 16,
    "qk_rope_head_dim": 8, "v_head_dim": 16, "mla_use_nope": True,
    "linear_attn_config": {
        "full_attn_layers": [4, 8], "kda_layers": [1, 2, 3, 5, 6, 7],
        "head_dim": 16, "num_heads": 2, "short_conv_kernel_size": 4},
    "first_k_dense_replace": 1, "moe_layer_freq": 1,
    "moe_renormalize": True, "moe_router_activation_func": "sigmoid",
    "num_expert_group": 1, "topk_group": 1, "use_grouped_topk": True,
    "num_experts": 16, "experts_held": [0, 4],
    "num_experts_per_token": 4, "num_shared_experts": 1,
    "routed_scaling_factor": 2.446, "num_nextn_predict_layers": 0,
    "rms_norm_eps": 1e-5, "rope_scaling": None, "rope_theta": 10000,
    "tie_word_embeddings": False, "model_max_length": 4096,
}
SEQ = 80  # not a multiple of the delta rule's chunk


def arch_of(cfg):
    return {f: getattr(cfg, f) for f in (
        "num_hidden_layers", "kda_layers", "full_attn_layers",
        "kda_num_heads", "kda_head_dim", "num_attention_heads",
        "qk_nope_head_dim", "qk_rope_head_dim", "v_head_dim",
        "kv_lora_rank", "experts_held", "num_experts_per_token",
        "routed_scaling_factor", "first_k_dense_replace", "rms_norm_eps")}


@pytest.fixture(scope="module")
def toy():
    cfg = kl.config_from_dict(TOY)
    model = kl.kimi_linear_lm(cfg)
    params, state = model.init(jax.random.PRNGKey(3))
    # norm scales start at 1 and a_log / dt_bias in a narrow band: move
    # every leaf, or a reference that dropped one would still agree
    leaves, tree = jax.tree_util.tree_flatten(params)
    keys = jax.random.split(jax.random.PRNGKey(4), len(leaves))
    params = jax.tree_util.tree_unflatten(tree, [
        leaf + 0.05 * jax.random.normal(k, leaf.shape)
        for leaf, k in zip(leaves, keys)])
    ids = jax.random.randint(jax.random.PRNGKey(5), (2, SEQ), 0, 96)
    return cfg, model, params, state, ids


def program_loss(model, params, state, ids, dtype=None):
    logits, _ = model.apply(
        params, state, ids, Context(train=True, dtype=dtype))
    logp = jax.nn.log_softmax(logits[:, :-1], axis=-1)
    return -jnp.sum(jnp.take_along_axis(logp, ids[:, 1:, None], axis=-1))


def test_the_toy_has_every_kind_of_layer(toy):
    cfg = toy[0]
    assert [cfg.mixer_kind(i) for i in range(1, 6)] == [
        "kda", "kda", "kda", "mla", "kda"]
    assert cfg.num_experts == 16 and cfg.experts_held == (0, 4)
    assert "router" in toy[2]["blocks"]["1"]["ffn"]
    assert "router" not in toy[2]["blocks"]["0"]["ffn"]


def test_a_range_of_experts_other_than_the_first_is_told_by_the_file():
    """Rank 2 of 4: `experts_held` beside the release's keys, whose
    `num_experts` stays the router's width."""
    cfg = kl.config_from_dict({**TOY, "experts_held": [8, 12]})
    assert cfg.num_experts == 16 and cfg.experts_held == (8, 12)
    whole = kl.config_from_dict(
        {k: v for k, v in TOY.items() if k != "experts_held"})
    assert whole.experts_held == (0, 16)
    model = kl.kimi_linear_lm(cfg)
    params, state = model.init(jax.random.PRNGKey(6))
    ids = jax.random.randint(jax.random.PRNGKey(7), (1, 24), 0, 96)
    logits, _ = model.apply(params, state, ids, Context(train=True))
    want = ref.forward(params, ids, arch_of(cfg))
    assert float(jnp.abs(logits - want).max()) < 1e-4 * float(
        jnp.abs(want).max())
    # another rank's weights are another model
    other = ref.forward(params, ids, {**arch_of(cfg), "experts_held": (0, 4)})
    assert float(jnp.abs(other - want).max()) > 1e-3 * float(
        jnp.abs(want).max())


def test_reference_imports_nothing_from_the_package():
    with open(ref.__file__) as f:
        source = f.read()
    assert "import distributed_model_parallel_tpu" not in source
    assert "from distributed_model_parallel_tpu" not in source


def test_logits_and_loss_equal_the_references(toy):
    cfg, model, params, state, ids = toy
    logits, after = jax.jit(lambda p, i: model.apply(
        p, state, i, Context(train=True)))(params, ids)
    want = jax.jit(lambda p, i: ref.forward(p, i, arch_of(cfg)))(params, ids)
    assert logits.shape == want.shape == (2, SEQ, 96)
    assert float(jnp.abs(logits - want).max()) < 1e-4 * float(
        jnp.abs(want).max())
    total, count = ref.next_token_loss(params, ids, arch_of(cfg))
    assert count == 2 * (SEQ - 1)
    ours = program_loss(model, params, state, ids)
    assert abs(float(ours) - float(total)) < 1e-4 * float(total)
    for i in range(1, 5):  # the expert layers report their counters
        assert float(after["blocks"][str(i)]["moe_picks_dropped"]) == 0
        assert float(after["blocks"][str(i)]["moe_picks_held"]) > 0


def test_gradients_equal_the_references(toy):
    cfg, model, params, state, ids = toy
    ours = jax.jit(jax.grad(
        lambda p: program_loss(model, p, state, ids)))(params)
    want = jax.jit(jax.grad(
        lambda p: ref.next_token_loss(p, ids, arch_of(cfg))[0]))(params)
    flat, _ = jax.tree_util.tree_flatten_with_path(ours)
    for (path, a), b in zip(flat, jax.tree_util.tree_leaves(want)):
        name = jax.tree_util.keystr(path)
        scale = max(float(jnp.abs(b).max()), 1e-3)
        assert float(jnp.abs(a - b).max()) < 1e-4 * scale, name
        assert float(jnp.abs(b).max()) > 0, name  # every leaf is reached


def test_bfloat16_compute_stays_within_its_band(toy):
    """bfloat16 activations over float32 weights, as the benchmark's
    cell trains: logits within 8 % of the reference's largest (a pick
    that flips on a rounded score moves one token's logits by whole
    percents), the loss within 0.5 %. (float32 is held to 1e-4 above.)"""
    cfg, model, params, state, ids = toy
    logits, _ = model.apply(
        params, state, ids, Context(train=True, dtype=jnp.bfloat16))
    want = ref.forward(params, ids, arch_of(cfg))
    err = float(jnp.abs(logits - want).max() / jnp.abs(want).max())
    assert 1e-4 < err < 8e-2, err
    total, _ = ref.next_token_loss(params, ids, arch_of(cfg))
    ours = program_loss(model, params, state, ids, jnp.bfloat16)
    assert abs(float(ours) - float(total)) < 5e-3 * float(total)


def test_the_lower_precision_controls_move_the_reference(toy):
    """What the benchmark's tolerance has to catch: the delta rule's
    state, or the router's scores, in bfloat16."""
    cfg, _, params, _, ids = toy
    arch = arch_of(cfg)
    exact, _ = ref.next_token_loss(params, ids, arch)
    state16, _ = ref.next_token_loss(
        params, ids, arch, state_dtype=jnp.bfloat16)
    assert abs(float(state16) - float(exact)) > 1e-5 * float(exact)
    logits = ref.forward(params, ids, arch)
    router16 = ref.forward(params, ids, arch, router_dtype=jnp.bfloat16)
    assert float(jnp.abs(router16 - logits).max()) > 0


def one_chip_mesh():
    return Mesh(np.array(jax.devices()[:1]).reshape(1, 1), ("data", "seq"))


def engine_for(cfg, **kw):
    return CausalLMSequenceParallelEngine(
        cfg, AdamW(), one_chip_mesh(), attention="ring_flash", **kw)


def test_the_engine_trains_it_and_the_loss_of_20_steps_falls(toy):
    cfg = toy[0]
    engine = engine_for(cfg, remat=True)
    ts = engine.init_state(jax.random.PRNGKey(0))
    rng = np.random.default_rng(0)
    # a corpus with structure: each token is its predecessor plus one
    start = rng.integers(0, 96, size=(4, 1))
    ids = (start + np.arange(64)[None, :]) % 96
    batch = engine.shard_batch(ids.astype(np.int32))
    losses = []
    for _ in range(20):
        ts, m = engine.train_step(ts, *batch, jnp.float32(3e-3))
        losses.append(float(m["loss_sum"]) / float(m["count"]))
        assert float(m["moe_picks_dropped"]) == 0
        assert float(m["moe_picks_held"]) > 0
        assert 0 < float(m["moe_expert_rows_max"]) <= 4 * 64
    assert np.isfinite(losses).all()
    assert losses[-1] < 0.7 * losses[0], losses
    # the correction bias is a buffer: nothing moved it
    assert float(jnp.abs(
        ts.model_state["blocks"]["1"]["router_bias"]).max()) == 0
    # the step's first loss is the reference's on the same parameters
    ts0 = engine.init_state(jax.random.PRNGKey(0))
    total, count = ref.next_token_loss(
        ts0.params, jnp.asarray(ids), arch_of(cfg))
    assert abs(losses[0] - float(total) / count) < 1e-4 * losses[0]


def test_gpt_still_goes_through_the_same_seam():
    from distributed_model_parallel_tpu.models.gpt import GPTConfig

    cfg = GPTConfig(vocab_size=64, dim=32, num_layers=2, num_heads=2,
                    ffn_dim=64, max_position=32, dropout_rate=0.0,
                    pad_token_id=0)
    fam = cfg.lm_family()
    assert fam.name == "gpt" and fam.counters is None
    assert fam.checkpoint_extra["gpt_config"]["num_experts"] == 0
    engine = engine_for(cfg)
    ts = engine.init_state(jax.random.PRNGKey(0))
    ids = np.random.default_rng(1).integers(1, 64, size=(2, 32))
    _, m = engine.train_step(
        ts, *engine.shard_batch(ids.astype(np.int32)), jnp.float32(1e-3))
    assert set(m) == {"loss_sum", "correct1", "correct5", "count"}
    import dataclasses
    with pytest.raises(NotImplementedError, match="num_experts"):
        engine_for(dataclasses.replace(cfg, num_experts=4))


def test_the_engine_refuses_what_the_family_lacks(toy):
    cfg = toy[0]
    two = Mesh(np.array(jax.devices()[:2]).reshape(1, 2), ("data", "seq"))
    with pytest.raises(NotImplementedError, match="KDA state"):
        CausalLMSequenceParallelEngine(cfg, AdamW(), two)
    with pytest.raises(NotImplementedError, match="overlapped"):
        engine_for(cfg, grad_reduction="overlapped")


@pytest.mark.parametrize("key,value", [
    ("q_lora_rank", 1536), ("mla_use_nope", False), ("num_expert_group", 8),
    ("moe_router_activation_func", "softmax"), ("tie_word_embeddings", True),
    ("num_shared_experts", 2),
])
def test_a_config_that_asks_for_what_is_not_built_is_refused(key, value):
    with pytest.raises(NotImplementedError, match=key):
        kl.config_from_dict({**TOY, key: value})


def _train_toy(lm, config_file, tmp_path):
    return lm.main([
        "--model-config", config_file, "--seq-len", "32", "-b", "8",
        "--epochs", "2", "--steps-per-epoch", "2", "--corpus-tokens",
        "4096", "--lr", "3e-3", "--attention", "ring_flash", "--remat",
        "--log-file", str(tmp_path / "log.txt"),
        "--checkpoint-dir", str(tmp_path / "ckpt"),
    ])


@pytest.fixture
def config_file(tmp_path):
    path = tmp_path / "toy-kimi.json"
    path.write_text(json.dumps(TOY))
    return str(path)


@pytest.mark.parametrize("extra,named", [
    (["--dim", "64"], "--dim"),
    (["--layers", "2"], "--layers"),
    (["--moe-experts", "8"], "--moe-experts"),
    (["--seq-shards", "2"], "--seq-shards"),
    (["--plan", "dp2"], "--plan"),
    (["--pipeline-stages", "2", "--microbatches", "2"], "--pipeline-stages"),
    (["--grad-reduction", "overlapped"], "--grad-reduction overlapped"),
])
def test_cli_lm_names_the_conflict_with_model_config(
        config_file, extra, named):
    from distributed_model_parallel_tpu.cli import lm

    with pytest.raises(SystemExit) as e:
        lm.main(["--model-config", config_file, "--seq-len", "32",
                 "-b", "8", *extra])
    assert named in str(e.value) and "--model-config" in str(e.value)


def test_cli_lm_refuses_a_model_type_it_does_not_build(tmp_path):
    from distributed_model_parallel_tpu.cli import lm

    path = tmp_path / "other.json"
    path.write_text(json.dumps({**TOY, "model_type": "other"}))
    with pytest.raises(SystemExit, match="model_type 'other'"):
        lm.main(["--model-config", str(path)])


def test_cli_lm_trains_it_and_serve_refuses_its_checkpoint(
        config_file, tmp_path, capsys):
    from distributed_model_parallel_tpu.cli import lm, serve
    from distributed_model_parallel_tpu.observability import metrics

    registry = metrics.MetricsRegistry(enabled=True)
    metrics.set_metrics(registry)
    try:
        out = _train_toy(lm, config_file, tmp_path)
    finally:
        metrics.set_metrics(None)
    # the Trainer carried the step counters into the metrics registry
    gauges = {k: g.value for k, g in registry._gauges.items()}
    assert gauges["moe_picks_dropped"] == 0
    assert gauges["moe_picks_held"] > 0 and gauges["moe_expert_rows_max"] > 0
    assert set(gauges) <= set(metrics.METRIC_NAMES)

    assert out["history"][-1]["train"]["loss"] < out["history"][0]["train"]["loss"]
    counters = out["history"][-1]["train"]["counters"]
    assert counters["moe_picks_dropped"] == 0
    assert counters["moe_picks_held"] > 0
    assert 0 < counters["moe_expert_rows_max"] <= 8 * 32
    with open(tmp_path / "log.txt") as f:
        assert "moe_picks_held" in f.read().splitlines()[-1]
    with pytest.raises(SystemExit) as e:
        serve.main(["--checkpoint", str(tmp_path / "ckpt"),
                    "--vocab-size", "96", "--max-len", "32"])
    assert "lm_family.model_type='kimi_linear'" in str(e.value)
