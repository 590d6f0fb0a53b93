"""The Qwen3-Next family (`models/qwen3_next.py`) at toy widths with
seeded random weights: its full forward pass against the plain
reference, chunked prefill with a padded tail and then decode through
pages and a state pool against the reference's forward pass, the delta
rule from a given state (chunked and one token at a time) against the
recurrence as written, keys cached rotated in part, the held halves of
an expert layer adding up to the whole layer, the softmax router, and
every refusal by name."""

import importlib.util
import os

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from distributed_model_parallel_tpu.models import layers as L
from distributed_model_parallel_tpu.models import moe, qwen3_next
from distributed_model_parallel_tpu.ops.delta_rule import (
    gated_delta_rule,
    gated_delta_rule_from,
    gated_delta_rule_step,
)
from distributed_model_parallel_tpu.serving.engine import ServingEngine
from distributed_model_parallel_tpu.serving.scheduler import Request

ROOT = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
# Qwen3-Next-80B-A3B-Instruct's config.json, as published.
PUBLISHED = {
    "decoder_sparse_step": 1, "full_attention_interval": 4, "head_dim": 256,
    "hidden_act": "silu", "hidden_size": 2048, "intermediate_size": 5120,
    "linear_conv_kernel_dim": 4, "linear_key_head_dim": 128,
    "linear_num_key_heads": 16, "linear_num_value_heads": 32,
    "linear_value_head_dim": 128, "max_position_embeddings": 262144,
    "mlp_only_layers": [], "model_type": "qwen3_next",
    "moe_intermediate_size": 512, "norm_topk_prob": True,
    "num_attention_heads": 16, "num_experts": 512, "num_experts_per_tok": 10,
    "num_hidden_layers": 48, "num_key_value_heads": 2,
    "partial_rotary_factor": 0.25, "rms_norm_eps": 1e-06,
    "rope_scaling": None, "rope_theta": 10000000,
    "shared_expert_intermediate_size": 512, "tie_word_embeddings": False,
    "use_sliding_window": False, "vocab_size": 151936,
}
TINY = {**PUBLISHED, "vocab_size": 97, "hidden_size": 32,
        "num_hidden_layers": 4, "num_attention_heads": 4,
        "num_key_value_heads": 2, "head_dim": 128, "rope_theta": 10000,
        "linear_num_key_heads": 2, "linear_num_value_heads": 4,
        "linear_key_head_dim": 8, "linear_value_head_dim": 8,
        "num_experts": 16, "num_experts_per_tok": 4,
        "moe_intermediate_size": 8, "shared_expert_intermediate_size": 8,
        "max_position_embeddings": 4096, "experts_held": [0, 8]}
ARCH = {"hk": 2, "hv": 4, "dk": 8, "dv": 8, "heads": 4, "kv_heads": 2,
        "head_dim": 128, "rot": 32, "theta": 10000.0, "eps": 1e-6,
        "top_k": 4, "experts_held": (0, 8)}


def load(path):
    spec = importlib.util.spec_from_file_location(
        os.path.basename(path)[:-3], os.path.join(ROOT, path))
    module = importlib.util.module_from_spec(spec)
    spec.loader.exec_module(module)
    return module


def reference():
    return load("benchmark/reference/qwen3_next_ref.py")


def salted(params, key):
    """The norms' weights away from their start, so that a norm applied
    as `w` where it is `1 + w` (or the reverse) shows."""
    leaves, tree = jax.tree_util.tree_flatten_with_path(params)
    keys = jax.random.split(key, len(leaves))
    return jax.tree_util.tree_unflatten(tree, [
        x + 0.3 * jax.random.normal(k, x.shape)
        if "norm" in jax.tree_util.keystr(path) else x
        for (path, x), k in zip(leaves, keys)])


@pytest.fixture(scope="module")
def cfg():
    return qwen3_next.config_from_dict(TINY)


@pytest.fixture(scope="module")
def engine(cfg):
    return ServingEngine(cfg, None, layout="replicated", num_slots=3,
                         max_len=256, page_size=8, prefill_chunk=16)


@pytest.fixture(scope="module")
def params(engine):
    return salted(engine.init_params(jax.random.PRNGKey(3)),
                  jax.random.PRNGKey(4))


def test_the_full_forward_equals_the_reference(cfg):
    model = qwen3_next.qwen3_next_lm(cfg)
    params, state = model.init(jax.random.PRNGKey(0))
    params = salted(params, jax.random.PRNGKey(1))
    ids = jax.random.randint(jax.random.PRNGKey(2), (2, 37), 1, 97)
    with jax.default_matmul_precision("highest"):
        got, _ = jax.jit(
            lambda p, x: model.apply(p, state, x, L.Context(train=False))
        )(params, ids)
        want = jax.jit(lambda p, x: reference().forward(p, x, arch=ARCH))(
            params, ids)
    assert float(jnp.abs(got - want).max() / jnp.abs(want).max()) < 1e-5
    assert [cfg.mixer_kind(i) for i in range(4)] == [
        "gdn", "gdn", "gdn", "attn"]


def drive(engine, params, prompt, decodes, extra=()):
    """One engine.run of `prompt` (and `extra` requests) -> (rows of
    logits at the prompt's last position and after each of its first
    `decodes` decode steps, the tokens fed, the cache tree after the
    last of those steps)."""
    seen = {"rows": [], "slot": None, "cache": None}
    chunk_prefill, decode_step = engine.chunk_prefill, engine.decode_step

    def spy_chunk(*step):
        cache, logits = chunk_prefill(*step)
        if int(step[4]) + int(step[5]) == prompt.size:
            seen["rows"].append(np.asarray(logits))
            seen["slot"] = int(step[6])
        return cache, logits

    def spy_decode(*step):
        cache, logits = decode_step(*step)
        slot = seen["slot"]
        if slot is not None and len(seen["rows"]) <= decodes and bool(
                step[5][slot]):
            seen["rows"].append(np.asarray(logits)[slot])
            seen["cache"] = cache
            seen["bt"] = np.asarray(step[2])[slot]
        return cache, logits

    engine.chunk_prefill, engine.decode_step = spy_chunk, spy_decode
    try:
        sched = engine.run(params, [Request(
            rid="p", prompt=prompt, max_new_tokens=decodes + 1), *extra])
    finally:
        engine.chunk_prefill, engine.decode_step = chunk_prefill, decode_step
    tokens = next(f.tokens for f in sched.finished if f.rid == "p")
    ids = np.concatenate([prompt, np.asarray(tokens[:decodes], np.int32)])
    return np.stack(seen["rows"]), ids, seen, sched


def test_chunked_prefill_and_decode_equal_the_reference(engine, params):
    """41 tokens: two whole chunks of 16 and a third of 9 with a padded
    tail, then 6 decode steps through the pages and the state pool,
    beside two other requests in other slots."""
    rng = np.random.default_rng(5)
    prompt = rng.integers(1, 97, size=41, dtype=np.int32)
    extra = [Request(rid=f"o{i}", prompt=rng.integers(
        1, 97, size=n, dtype=np.int32), max_new_tokens=3)
        for i, n in enumerate((5, 23))]
    rows, ids, _, sched = drive(engine, params, prompt, 6, extra)
    with jax.default_matmul_precision("highest"):
        want = np.asarray(reference().forward(
            params, ids[None], arch=ARCH, rows_from=40))[0]
    err = np.abs(rows - want).max(axis=1) / np.abs(want).max(axis=1)
    assert rows.shape == (7, 97) and err.max() < 1e-5, err
    # every real row's picks counted, the padded tails' not at all
    real = sched.paged_stats["prefill_positions_valid"] + sum(
        sched.step_occupancy)
    assert sched.paged_stats["moe_picks"] == 4 * 4 * real
    assert 0 < sched.paged_stats["moe_picks_held"] < 4 * 4 * real


def test_every_chunk_runs_the_head_decay_path(engine, params):
    """The family answers `decay_kind` of the decay its mixer hands the
    op, and `state_head_decay_chunks` counts every chunk of a drain:
    prompts of one, two and three chunks, padded tails among them."""
    assert engine.chunk_decay_kind == "head"
    rng = np.random.default_rng(6)
    lengths = (41, 5, 16, 23)
    sched = engine.run(params, [
        Request(rid=i, prompt=rng.integers(1, 97, size=n, dtype=np.int32),
                max_new_tokens=2)
        for i, n in enumerate(lengths)])
    stats = sched.paged_stats
    chunks = sum(-(-n // 16) for n in lengths)
    assert stats["state_head_decay_chunks"] == chunks == 7
    assert stats["prefill_positions_computed"] == 16 * chunks
    assert stats["state_kernel_chunks"] == 0


def test_the_state_and_the_rotated_keys_are_the_references(engine, params):
    """What the programs left in the cache after 41 tokens and 6 decode
    steps: the first layer's S and kept convolution inputs, and the
    attention layer's keys, the first 32 of 128 values of each head
    rotated, the other 96 as projected."""
    builder = load("benchmark/builders/qwen3_next.py")
    ref = reference()
    prompt = np.random.default_rng(7).integers(1, 97, size=41,
                                               dtype=np.int32)
    _, ids, seen, _ = drive(engine, params, prompt, 6)
    held = builder.slot_state(seen["cache"], seen["slot"])
    keys = builder.slot_keys(seen["cache"], seen["bt"], ids.size)
    with jax.default_matmul_precision("highest"):
        want = ref.first_gdn(params, ids[None], arch=ARCH)
        want_keys = np.asarray(ref.attention_keys(
            params, ids[None], arch=ARCH))[0]
        unrotated = np.asarray(ref.attention_keys(
            params, ids[None], arch={**ARCH, "rot": 0}))[0]
    dist = lambda a, b: np.linalg.norm(a - b) / np.linalg.norm(b)
    assert dist(held["s"], np.asarray(want["s"][0])) < 1e-5
    assert dist(held["conv"], np.asarray(want["conv"][0])) < 1e-6
    assert keys.shape == (47, 2, 128) and dist(keys, want_keys) < 1e-5
    assert dist(keys[..., 32:], unrotated[..., 32:]) < 1e-5
    # position 0 is not turned; later positions are
    assert dist(keys[0], unrotated[0]) < 1e-6
    assert dist(keys[1:, :, :32], unrotated[1:, :, :32]) > 0.1


def test_the_pool_is_heads_major_and_the_state_is_float32(engine):
    cache = engine.init_cache()
    assert engine.heads_major
    assert cache["k"].shape == (1, 3 * 32, 2, 8, 128)
    assert engine.rotary == (32, 10000.0)
    state = cache["state"]
    assert sorted(state) == ["0", "1", "2"]
    assert state["0"]["s"].shape == (3, 4, 8, 8)
    assert state["0"]["s"].dtype == jnp.float32
    assert state["0"]["conv"].shape == (3, 3, 2 * 2 * 8 + 4 * 8)


def delta_inputs(key, t, h=3, dk=8, dv=4):
    ks = jax.random.split(key, 6)
    unit = lambda x: x / jnp.linalg.norm(x, axis=-1, keepdims=True)
    q = unit(jax.random.normal(ks[0], (2, t, h, dk)))
    k = unit(jax.random.normal(ks[1], (2, t, h, dk)))
    v = jax.random.normal(ks[2], (2, t, h, dv))
    # one decay per head, broadcast over the key channels
    g = jnp.broadcast_to(
        -jax.random.uniform(ks[3], (2, t, h, 1), maxval=0.5), (2, t, h, dk))
    beta = jax.random.uniform(ks[4], (2, t, h))
    s0 = jax.random.normal(ks[5], (2, h, dk, dv))
    return q, k, v, g, beta, s0


def stepwise_from(q, k, v, g, beta, s0):
    """The recurrence as written from the state s0, one token a step
    (`gated_delta_rule_step`) -> (outputs, the state after the last)."""
    outs, s = [], s0
    for t in range(q.shape[1]):
        o, s = gated_delta_rule_step(q[:, t], k[:, t], v[:, t], g[:, t],
                                     beta[:, t], s)
        outs.append(o)
    return jnp.stack(outs, 1), s


def test_the_chunked_op_from_a_state_equals_the_recurrence(monkeypatch):
    """From a given state, over a chunk boundary and a padded tail (40
    tokens in chunks of 16): the outputs and the state after the last
    token, against the recurrence one token a step; padding positions
    given g = 0, beta = 0 leave the state as it was."""
    q, k, v, g, beta, s0 = delta_inputs(jax.random.PRNGKey(0), 40)
    chunked = jax.jit(lambda *a: gated_delta_rule_from(*a, chunk=16))
    pad = lambda x: jnp.pad(x, [(0, 0), (0, 8)] + [(0, 0)] * (x.ndim - 2))
    with jax.default_matmul_precision("highest"):
        out, s = chunked(q, k, v, g, beta, s0)
        want, want_s = jax.jit(stepwise_from)(q, k, v, g, beta, s0)
        # the same 40 tokens then 8 that neither decay nor write
        _, padded_s = chunked(*(pad(x) for x in (q, k, v, g, beta)), s0)
    assert float(jnp.abs(out - want).max()) < 1e-4
    assert float(jnp.abs(s - want_s).max()) < 1e-4
    assert float(jnp.abs(padded_s - s).max()) < 1e-6
    # from a zero state the op is `gated_delta_rule`'s outputs
    plain = gated_delta_rule(q, k, v, g, beta, chunk=16)
    zero = gated_delta_rule_from(q, k, v, g, beta, jnp.zeros_like(s0),
                                 chunk=16)[0]
    assert float(jnp.abs(plain - zero).max()) == 0.0


def test_the_one_token_step_continues_a_state():
    """Two halves, the second one token at a time from the state the
    chunked op left, equal the whole sequence stepwise."""
    q, k, v, g, beta, s0 = delta_inputs(jax.random.PRNGKey(1), 24)
    step = jax.jit(gated_delta_rule_step)
    with jax.default_matmul_precision("highest"):
        want, want_s = jax.jit(stepwise_from)(q, k, v, g, beta, s0)
        _, s = jax.jit(lambda *a: gated_delta_rule_from(*a, chunk=16))(
            *(x[:, :16] for x in (q, k, v, g, beta)), s0)
        outs = []
        for t in range(16, 24):
            o, s = step(q[:, t], k[:, t], v[:, t], g[:, t], beta[:, t], s)
            outs.append(o)
    assert float(jnp.abs(jnp.stack(outs, 1) - want[:, 16:]).max()) < 1e-4
    assert float(jnp.abs(s - want_s).max()) < 1e-4


def expert_layer(held, shared_gate=True):
    return moe.held_experts_feed_forward(
        32, 8, 16, held, top_k=4, shared_hidden_dim=8, router="softmax",
        shared_gate=shared_gate)


def test_the_two_held_halves_and_one_shared_expert_are_the_whole_layer():
    whole = expert_layer((0, 16))
    params, state = whole.init(jax.random.PRNGKey(0))
    x = jax.random.normal(jax.random.PRNGKey(1), (2, 9, 32))
    ctx = L.Context(train=False)
    run = lambda layer, p: jax.jit(
        lambda p: layer.apply(p, state, (x, None), ctx)[0][0])(p)
    half = lambda first: {**params, "experts": jax.tree_util.tree_map(
        lambda w: w[first:first + 8], params["experts"])}
    no_shared = lambda p: {**p, "shared": jax.tree_util.tree_map(
        jnp.zeros_like, p["shared"])}
    with jax.default_matmul_precision("highest"):
        uncut = run(whole, params)
        low = run(expert_layer((0, 8)), half(0))
        high = run(expert_layer((8, 16)), no_shared(half(8)))
    assert float(jnp.abs(low + high - uncut).max()) < 1e-5
    ref = reference()
    with jax.default_matmul_precision("highest"):
        want, _ = jax.jit(lambda p: ref._moe(
            x, p, {"top_k": 4, "experts_held": (0, 16)}))(params)
    assert float(jnp.abs(uncut - want).max()) < 1e-5


def test_the_softmax_router_takes_the_largest_probabilities():
    flat = jax.random.normal(jax.random.PRNGKey(2), (12, 32))
    w = jax.random.normal(jax.random.PRNGKey(3), (32, 16))
    ids, weights = moe.route_softmax(flat, w, jnp.zeros((16,)), 4, 1.0)
    probs = jax.nn.softmax(flat @ w, axis=-1)
    want = np.argsort(-np.asarray(probs), axis=-1)[:, :4]
    assert (np.sort(np.asarray(ids), -1) == np.sort(want, -1)).all()
    picked = np.take_along_axis(np.asarray(probs), np.asarray(ids), -1)
    np.testing.assert_allclose(
        weights, picked / picked.sum(-1, keepdims=True), rtol=1e-5)
    np.testing.assert_allclose(np.asarray(weights).sum(-1), 1.0, rtol=1e-6)


@pytest.mark.parametrize("key, value", [
    ("use_sliding_window", True), ("rope_scaling", {"type": "yarn"}),
    ("mlp_only_layers", [0]), ("decoder_sparse_step", 2),
    ("tie_word_embeddings", True)])
def test_what_the_family_does_not_build_is_refused_by_the_keys_name(
        key, value):
    with pytest.raises(NotImplementedError, match=key):
        qwen3_next.config_from_dict({**TINY, key: value})


@pytest.mark.parametrize("option, kwargs", [
    ("prefix_cache", {"prefix_cache": True}),
    ("page_size=None", {"page_size": None, "prefill_chunk": None}),
    ("prefill_chunk=None", {"prefill_chunk": None}),
])
def test_what_a_state_cannot_do_yet_is_refused_under_its_name(
        cfg, option, kwargs):
    settings = {"layout": "replicated", "num_slots": 2, "max_len": 64,
                "page_size": 8, "prefill_chunk": 16, **kwargs}
    with pytest.raises(ValueError, match=option):
        ServingEngine(cfg, None, **settings)


def test_cli_serve_starts_the_family_and_says_what_the_cache_holds(
        tmp_path, capsys):
    import json

    from distributed_model_parallel_tpu.cli import serve

    path = tmp_path / "tiny.json"
    path.write_text(json.dumps({**TINY, "max_position_embeddings": 64}))
    out = serve.main([
        "--model-config", str(path), "--num-slots", "2", "--max-len", "64",
        "--page-size", "8", "--prefill-chunk", "16", "--num-requests", "3",
        "--prompt-len-min", "5", "--prompt-len-max", "20",
        "--max-new-tokens", "3", "--seed", "1",
    ])
    printed = capsys.readouterr().out
    lines = [line for line in printed.splitlines() if "rotated keys" in line]
    assert len(lines) == 1 and lines[0].startswith("==> qwen3_next:")
    assert printed.count(
        "==> qwen3_next: a chunk of 16 positions runs the delta rule's "
        "head-decay path") == 1
    assert "2 heads of 128" in lines[0]
    assert "experts 0-7 of 16 held" in lines[0]
    assert "MB of state a slot over 3 layers" in lines[0]
    assert len(out["requests"]) == 3
    with pytest.raises(SystemExit, match="prefix_cache is not built"):
        serve.main(["--model-config", str(path), "--num-slots", "2",
                    "--max-len", "64", "--page-size", "8",
                    "--prefill-chunk", "16", "--prefix-cache"])
