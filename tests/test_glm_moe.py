"""The GLM-4 MoE "lite" family (`models/glm_moe.py`): its forward pass
against the plain reference, the two forms of latent attention against
each other, the expert layer with every expert held against the
reference's dense per-expert layer, rows that are not real kept out of
the experts and the counters, every refusal by name, and the built
tree's count at the published widths."""

import dataclasses
import importlib.util
import os

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from distributed_model_parallel_tpu.models import glm_moe, moe
from distributed_model_parallel_tpu.models import layers as L
from distributed_model_parallel_tpu.ops import latent_attention as LA

ROOT = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
# The catalog's row for GLM-4.7-Flash (`config` of
# /opt/skills/guides/model-configs/architectures.jsonl), as published.
PUBLISHED = {
    "attention_bias": False, "hidden_act": "silu", "hidden_size": 2048,
    "intermediate_size": 10240, "max_position_embeddings": 202752,
    "model_type": "glm4_moe_lite", "moe_intermediate_size": 1536,
    "topk_method": "noaux_tc", "norm_topk_prob": True,
    "num_attention_heads": 20, "n_group": 1, "topk_group": 1,
    "n_routed_experts": 64, "n_shared_experts": 1,
    "routed_scaling_factor": 1.8, "num_experts_per_tok": 4,
    "first_k_dense_replace": 1, "num_hidden_layers": 47,
    "num_key_value_heads": 20, "num_nextn_predict_layers": 1,
    "partial_rotary_factor": 1, "rms_norm_eps": 1e-05,
    "rope_scaling": None, "rope_theta": 1000000,
    "tie_word_embeddings": False, "q_lora_rank": 768, "kv_lora_rank": 512,
    "qk_nope_head_dim": 192, "qk_rope_head_dim": 64, "v_head_dim": 256,
    "vocab_size": 154880,
}
TINY = {**PUBLISHED, "vocab_size": 97, "hidden_size": 32,
        "intermediate_size": 48, "moe_intermediate_size": 16,
        "num_attention_heads": 2, "num_key_value_heads": 2,
        "q_lora_rank": 12, "kv_lora_rank": 16, "qk_nope_head_dim": 6,
        "qk_rope_head_dim": 4, "v_head_dim": 8, "n_routed_experts": 8,
        "num_experts_per_tok": 2, "num_hidden_layers": 3,
        "rope_theta": 10000, "max_position_embeddings": 4096}


def reference():
    spec = importlib.util.spec_from_file_location(
        "glm_moe_ref", os.path.join(ROOT, "benchmark/reference/glm_moe_ref.py"))
    module = importlib.util.module_from_spec(spec)
    spec.loader.exec_module(module)
    return module


def arch_of(cfg):
    d = cfg.latent_dims
    return {"heads": d.heads, "rank": d.rank, "nope": d.nope, "rope": d.rope,
            "dv": d.dv, "theta": d.theta, "eps": cfg.rms_norm_eps,
            "top_k": cfg.num_experts_per_tok,
            "routed_scale": cfg.routed_scaling_factor}


def count(tree):
    return sum(int(np.prod(x.shape)) for x in jax.tree_util.tree_leaves(tree))


def test_the_built_tree_has_the_cuts_count_layer_by_layer():
    cfg = glm_moe.config_from_dict({**PUBLISHED, "num_hidden_layers": 7})
    params, _ = jax.eval_shape(
        glm_moe.glm_moe_lm(cfg).init, jax.ShapeDtypeStruct((2,), jnp.uint32))
    assert count(params) == 4_530_936_960
    # by hand: W_qa, its norm, W_qb, W_kva, its norm, W_kvb, W_o
    mixer = (2048 * 768 + 768 + 768 * 20 * 256 + 2048 * 576 + 512
             + 512 * 20 * 448 + 20 * 256 * 2048)
    assert mixer == 21_759_232
    expert = 3 * 2048 * 1536
    blocks = params["blocks"]
    assert count(blocks["0"]) == mixer + 3 * 2048 * 10240 + 2 * 2048 \
        == 84_677_888
    for layer in "123456":
        assert count(blocks[layer]) == (
            mixer + 65 * expert + 2048 * 64 + 64 + 2 * 2048) == 635_311_424
        assert blocks[layer]["ffn"]["experts"]["w_in"].shape == (
            64, 2048, 3072)
    assert count(params["stem"]) == 154880 * 2048
    assert count(params["head"]) == 154880 * 2048 + 2048
    assert [cfg.sparse(i) for i in range(7)] == [False] + [True] * 6
    fam = cfg.serving_family()
    assert [lc.latent_dim for lc in fam.layers] == [576] * 7
    assert all(not lc.kv_heads and not lc.state for lc in fam.layers)


def test_the_full_forward_equals_the_reference():
    cfg = glm_moe.config_from_dict(TINY)
    model = glm_moe.glm_moe_lm(cfg)
    params, state = model.init(jax.random.PRNGKey(0))
    # a router bias that changes the choice and not the weights
    for layer in ("1", "2"):
        params["blocks"][layer]["router_bias"] = 0.3 * jax.random.normal(
            jax.random.PRNGKey(int(layer)), (8,))
    ids = np.random.default_rng(0).integers(1, 97, size=(2, 37))
    got, _ = model.apply(params, state, ids, L.Context(train=False))
    want = reference().forward(params, ids, arch=arch_of(cfg))
    np.testing.assert_allclose(got, want, rtol=2e-5, atol=2e-5)
    tail = reference().forward(params, ids, arch=arch_of(cfg), rows_from=30)
    np.testing.assert_allclose(tail, want[:, 30:], rtol=1e-6, atol=1e-6)
    # made to take the experts its own routers chose: the same logits,
    # no regret; with one near-tie resolved the other way: other logits
    # at that row alone, and the tie's width as the regret
    ref, arch = reference(), arch_of(cfg)
    own = []
    for layer in ("1", "2"):
        own.append(chosen_by_the_reference(ref, params, ids, arch, layer))
    own = np.stack(own, axis=2)[:, 30:]  # (B, rows, expert layers, k)
    same, regret = ref.forward(
        params, ids, arch=arch, rows_from=30, forced=jnp.asarray(own))
    np.testing.assert_allclose(same, tail, rtol=1e-6, atol=1e-6)
    assert regret.shape == (2, 7, 2) and not np.asarray(regret).any()
    other = own.copy()
    other[1, 3, 0, 0] = next(
        e for e in range(8) if e not in own[1, 3, 0])
    moved, regret = ref.forward(
        params, ids, arch=arch, rows_from=30, forced=jnp.asarray(other))
    regret = np.asarray(regret)
    assert regret[1, 3, 0] > 0 and not regret[0].any()
    far = np.abs(np.asarray(moved) - np.asarray(tail)).max(axis=-1)
    assert far[1, 3] > 1e-4 and far[0].max() < 1e-6 and far[1, :3].max() < 1e-6


def chosen_by_the_reference(ref, params, ids, arch, layer):
    """The experts the reference's router of one layer chooses on the
    reference's own inputs to it: (B, T, k)."""
    hidden = {}
    experts = ref._experts

    def keep(x, p, bias, arch_, forced=None):
        if p is params["blocks"][layer]["ffn"]:
            hidden["x"] = x
        return experts(x, p, bias, arch_, forced)

    ref._experts = keep
    try:
        ref.forward(params, ids, arch=arch)
    finally:
        ref._experts = experts
    b, t, d = hidden["x"].shape
    chose, _, _ = ref._route(
        hidden["x"].reshape(b * t, d),
        params["blocks"][layer]["ffn"]["router"]["w"],
        params["blocks"][layer]["router_bias"], arch)
    return np.asarray(chose).reshape(b, t, -1)


@pytest.fixture(scope="module")
def attention_case():
    dims = LA.LatentDims(heads=3, rank=16, nope=6, rope=4, dv=8,
                         theta=10000.0, scale=10 ** -0.5)
    rng = np.random.default_rng(1)
    f = lambda *shape: jnp.asarray(rng.normal(size=shape), jnp.float32)
    b, t = 2, 24
    return dims, (f(b, t, 3, 6), f(b, t, 3, 4), f(b, t, 16), f(b, t, 4),
                  0.3 * f(16, 3 * 14))


def test_absorbed_equals_expanded_and_the_selector_reads_the_shape(
        attention_case):
    dims, (q_nope, q_rope, c, k_rope, w_kvb) = attention_case
    b, t = c.shape[:2]
    pos = jnp.broadcast_to(jnp.arange(t), (b, t))
    rows = jnp.concatenate([c, LA.rope(k_rope, pos, dims.theta)], -1)
    q_r = LA.rope(q_rope, pos, dims.theta)
    causal = jnp.broadcast_to(
        jnp.arange(t)[:, None] >= jnp.arange(t)[None, :], (b, t, t))
    absorbed, expanded = (
        LA.latent_attention(q_nope, q_r, rows, w_kvb, causal, dims, kind)
        for kind in ("absorbed", "expanded"))
    np.testing.assert_allclose(absorbed, expanded, rtol=2e-5, atol=2e-5)
    # a row stored with zeros up to a lane tile reads the same
    padded = jnp.pad(rows, ((0, 0), (0, 0), (0, 12)))
    for kind, want in (("absorbed", absorbed), ("expanded", expanded)):
        np.testing.assert_allclose(
            LA.latent_attention(q_nope, q_r, padded, w_kvb, causal, dims,
                                kind), want, rtol=1e-6, atol=1e-6)
    whole = LA.latent_causal_attention(
        q_nope, q_rope, c, k_rope, w_kvb, None, dims)
    np.testing.assert_allclose(whole, expanded, rtol=2e-5, atol=2e-5)
    # one query a sequence folds the query; a chunk expands the keys
    full = glm_moe.config_from_dict(PUBLISHED).latent_dims
    assert LA.latent_kind(1, full) == "absorbed"
    assert LA.latent_kind(398, full) == "absorbed"
    assert LA.latent_kind(399, full) == "expanded"
    assert LA.latent_kind(1024, full) == "expanded"


@pytest.mark.parametrize("tq", [1, 24])
def test_attention_stretch_by_stretch_equals_attention_in_one_piece(
        attention_case, tq):
    """Both forms (one query folds, 24 expand at these widths... the
    selector decides), over 3 stretches of 8 of which the last is past
    some queries' positions."""
    dims, (q_nope, q_rope, c, k_rope, w_kvb) = attention_case
    t = c.shape[1]
    pos = jnp.arange(t)[None]
    rows = jnp.concatenate([c[:1], LA.rope(k_rope[:1], pos, dims.theta)], -1)
    q_pos = jnp.arange(t - tq, t)
    qn, qr = q_nope[:1, t - tq:], LA.rope(
        q_rope[:1, t - tq:], q_pos[None], dims.theta)
    seen = (jnp.arange(t)[None, :] <= q_pos[:, None])[None]
    want = LA.latent_attention(qn, qr, rows, w_kvb, seen, dims)
    fetch = lambda j: jax.lax.dynamic_slice_in_dim(rows, j * 8, 8, axis=1)
    got = jax.jit(lambda n: LA.latent_attention_blocks(
        qn, qr, fetch, n, 8, dims.row, q_pos, w_kvb, dims))(3)
    np.testing.assert_allclose(got, want, rtol=2e-5, atol=2e-5)


def test_the_rotation_pairs_halves_and_composes():
    x = jnp.asarray(np.random.default_rng(2).normal(size=(1, 5, 8)))
    pos = jnp.asarray([[0, 1, 2, 7, 300]])
    r = LA.rope(x, pos, 10000.0)
    np.testing.assert_allclose(r[0, 0], x[0, 0], atol=1e-7)  # position 0
    np.testing.assert_allclose(  # a rotation keeps every pair's length
        r[..., :4] ** 2 + r[..., 4:] ** 2,
        x[..., :4] ** 2 + x[..., 4:] ** 2, rtol=1e-5)
    twice = LA.rope(LA.rope(x, pos, 10000.0), pos, 10000.0)
    np.testing.assert_allclose(
        twice, LA.rope(x, 2 * pos, 10000.0), rtol=1e-4, atol=1e-5)
    # the scores of a rotated pair depend on the distance alone
    q, k = x[:, :1], x[:, 1:2]
    at = lambda a, b: jnp.sum(
        LA.rope(q, jnp.asarray([[a]]), 100.0)
        * LA.rope(k, jnp.asarray([[b]]), 100.0))
    np.testing.assert_allclose(at(9, 4), at(25, 20), rtol=1e-4)


def expert_layer(cfg):
    return moe.held_experts_feed_forward(
        cfg.hidden_size, cfg.moe_intermediate_size, cfg.n_routed_experts,
        (0, cfg.n_routed_experts), top_k=cfg.num_experts_per_tok,
        shared_hidden_dim=cfg.moe_intermediate_size,
        routed_scale=cfg.routed_scaling_factor)


def test_every_expert_held_equals_the_references_dense_expert_layer():
    cfg = glm_moe.config_from_dict(TINY)
    layer = expert_layer(cfg)
    params, state = layer.init(jax.random.PRNGKey(4))
    bias = 0.2 * jax.random.normal(jax.random.PRNGKey(5), (8,))
    x = jax.random.normal(jax.random.PRNGKey(6), (2, 9, 32))
    (got, _), counters = layer.apply(
        params, {**state, "router_bias": bias}, (x, None), L.Context())
    want, regret = reference()._experts(x, params, bias, arch_of(cfg))
    np.testing.assert_allclose(got, want, rtol=2e-5, atol=2e-5)
    assert not np.asarray(regret).any()  # the router's own choice
    # no mask: the training engines' counters, as they were
    assert set(counters) == {"router_bias", *moe.COUNTERS}
    assert float(counters["moe_picks_held"]) == 2 * 9 * 2
    assert float(counters["moe_picks_dropped"]) == 0


def test_rows_that_are_not_real_reach_no_expert_and_no_counter():
    cfg = glm_moe.config_from_dict(TINY)
    layer = expert_layer(cfg)
    params, state = layer.init(jax.random.PRNGKey(4))
    x = jax.random.normal(jax.random.PRNGKey(6), (2, 9, 32))
    mask = jnp.arange(9)[None, :] < jnp.asarray([[9], [4]])
    (got, _), counters = layer.apply(params, state, (x, mask), L.Context())
    assert set(counters) == {"router_bias", *moe.SERVING_COUNTERS}
    assert float(counters["moe_picks"]) == (9 + 4) * 2
    assert float(counters["moe_rows_masked"]) == 5 * 2
    # real rows come out as they do unmasked; the others get the shared
    # expert alone, whatever they hold
    (whole, _), _ = layer.apply(params, state, (x, None), L.Context())
    np.testing.assert_allclose(got[0], whole[0], rtol=1e-6, atol=1e-6)
    np.testing.assert_allclose(got[1, :4], whole[1, :4], rtol=1e-5, atol=1e-6)
    shared = moe.gated_mlp(params["shared"], x[1, 4:])
    np.testing.assert_allclose(got[1, 4:], shared, rtol=1e-6, atol=1e-6)
    # the experts the real rows reach, counted from the picks themselves
    ids, _ = moe.route(x.reshape(18, 32), params["router"]["w"],
                       state["router_bias"], 2, cfg.routed_scaling_factor)
    real = np.asarray(mask).reshape(18)
    # what the layer says it routed by: the router's choice, row by row
    assert counters["moe_chosen"].dtype == jnp.int32
    np.testing.assert_array_equal(counters["moe_chosen"], ids)
    reached = np.unique(np.asarray(ids)[real])
    assert float(counters["moe_experts_hit"]) == reached.size
    assert float(counters["moe_expert_rows_max"]) == np.bincount(
        np.asarray(ids)[real].reshape(-1)).max()
    # a garbage row that is masked moves nothing real
    poisoned = x.at[1, 4:].set(1e4)
    (again, _), _ = layer.apply(params, state, (poisoned, mask), L.Context())
    np.testing.assert_array_equal(np.asarray(again[0]), np.asarray(got[0]))
    np.testing.assert_array_equal(
        np.asarray(again[1, :4]), np.asarray(got[1, :4]))


@pytest.mark.parametrize("key, value", [
    ("n_group", 2), ("topk_group", 2), ("rope_scaling", {"factor": 4.0}),
    ("attention_bias", True), ("topk_method", "greedy"),
    ("norm_topk_prob", False), ("hidden_act", "gelu"),
    ("partial_rotary_factor", 0.5), ("tie_word_embeddings", True),
])
def test_what_the_family_cannot_honour_is_refused_by_the_keys_name(
        key, value):
    with pytest.raises(NotImplementedError, match=key):
        glm_moe.config_from_dict({**TINY, key: value})


def test_a_missing_key_and_a_bad_count_are_refused():
    with pytest.raises(KeyError, match="q_lora_rank"):
        glm_moe.config_from_dict(
            {k: v for k, v in TINY.items() if k != "q_lora_rank"})
    with pytest.raises(ValueError, match="qk_rope_head_dim"):
        glm_moe.config_from_dict({**TINY, "qk_rope_head_dim": 5})
    with pytest.raises(ValueError, match="num_experts_per_tok"):
        glm_moe.config_from_dict({**TINY, "num_experts_per_tok": 9})
    cfg = glm_moe.config_from_dict({**TINY, "torch_dtype": "bfloat16"})
    assert cfg.param_dtype == "bfloat16"
    assert dataclasses.replace(cfg, first_k_dense_replace=3).sparse(2) is False


def _paged_case(seed=5):
    """Five slots at positions of their own over shuffled pages of 8
    rows stored 128 wide, two of the kernel's stretches a slot: one
    slot inactive, the table's unallocated entries -1."""
    dims = LA.LatentDims(heads=3, rank=16, nope=6, rope=4, dv=8,
                         theta=10000.0, scale=10 ** -0.5)
    rng = np.random.default_rng(seed)
    # (float32 values a bfloat16 holds: the kernel reads a cached row
    # as one, and this CPU multiplies no bfloat16)
    f = lambda *shape: jnp.asarray(
        rng.normal(size=shape), jnp.bfloat16).astype(jnp.float32)
    slots, page, per_slot, num_pages = 5, 8, 16, 96
    positions = np.asarray([0, 7, 8, 37, 127], np.int32)
    active = np.asarray([True, True, False, True, True])
    table = np.full((slots, per_slot), -1, np.int32)
    free = rng.permutation(num_pages)
    for s, pos in enumerate(positions):
        n = pos // page + 1
        table[s, :n], free = free[:n], free[n:]
    pool = f(num_pages, page, 128).at[:, :, dims.row:].set(0)
    return dims, (f(slots, 1, 3, 6), f(slots, 1, 3, 4), pool,
                  jnp.asarray(table), jnp.asarray(positions),
                  jnp.asarray(active), 0.3 * f(16, 3 * 14))


def test_the_decode_steps_kernel_equals_attention_over_each_slots_rows(
        monkeypatch, tol=2e-5):
    """`paged_decode_attention` as a TPU runs it (JAX's Pallas paged
    attention, here through the interpreter) against `latent_attention`
    over each slot's own rows gathered by hand, and against the program
    every other backend runs: the heads as query heads of one cached
    head, the folded scale, the lengths from the positions, the clipped
    block table, the row's zero padding."""
    from jax.experimental.pallas import tpu as pltpu

    dims, case = _paged_case()
    q_nope, q_rope, pool, table, positions, active, w_kvb = case
    gathered = LA.paged_decode_attention(*case, dims)
    assert LA.decode_kind(128, 8, 16) == "gather"  # no TPU here
    monkeypatch.setattr(LA, "_on_tpu", lambda: True)
    assert LA.decode_kind(128, 8, 16) == "kernel"
    assert LA.decode_kind(20, 8, 16) == "gather"  # rows off the lane tiles
    with pltpu.force_tpu_interpret_mode():
        got = LA.paged_decode_attention(*case, dims)
    assert got.shape == (5, 1, 3, 8) and got.dtype == jnp.float32
    assert np.isfinite(np.asarray(got, np.float32)).all()
    live = np.nonzero(np.asarray(active))[0]
    for s in live:
        n = int(positions[s]) + 1
        pages = np.asarray(table[s, :-(-n // 8)])
        rows = pool[pages].reshape(1, -1, 128)[:, :n]
        want = LA.latent_attention(
            q_nope[s:s + 1], q_rope[s:s + 1], rows, w_kvb,
            jnp.ones((1, 1, n), bool), dims, kind="absorbed")
        np.testing.assert_allclose(
            np.asarray(got[s], np.float32), np.asarray(want[0], np.float32),
            rtol=tol, atol=tol, err_msg=f"slot {s}")
    np.testing.assert_allclose(
        np.asarray(got, np.float32)[live],
        np.asarray(gathered, np.float32)[live], rtol=tol, atol=tol)
