"""A model that caches ONE latent row a token, rotary key inside it,
and routes its rows over experts, served through `ServingEngine.run`:
the latent pool, chunked prefill with padded tails then the absorbed
step at every slot's own position against the reference's full forward
pass, a prefix attached from the cache with a copied page, a recycled
slot, the expert layers' counters, the refusals by name, and
`cli.serve --model-config`."""

import json

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from distributed_model_parallel_tpu.models import glm_moe
from distributed_model_parallel_tpu.serving import decode as D
from distributed_model_parallel_tpu.serving.engine import ServingEngine
from distributed_model_parallel_tpu.serving.kv_cache import (
    PagedKVCacheSpec,
    copy_page,
    init_paged_cache,
)
from distributed_model_parallel_tpu.serving.scheduler import Request

from test_glm_moe import PUBLISHED, TINY, arch_of, reference

CFG = glm_moe.config_from_dict(TINY)
SLOTS, CHUNK, PAGE = 3, 8, 4


@pytest.fixture(scope="module")
def engine():
    return ServingEngine(CFG, None, num_slots=SLOTS, max_len=64,
                         page_size=PAGE, prefill_chunk=CHUNK,
                         prefix_cache=True)


@pytest.fixture(scope="module")
def params(engine):
    params = engine.init_params(jax.random.PRNGKey(0))
    for layer in ("1", "2"):  # a bias that moves the router's choice
        params["blocks"][layer]["router_bias"] = 0.3 * jax.random.normal(
            jax.random.PRNGKey(int(layer)), (8,))
    return params


def full_forward(params, ids):
    """The REFERENCE's logits on one whole sequence."""
    return np.asarray(reference().forward(
        params, np.asarray(ids)[None], arch=arch_of(CFG)))[0]


def spied_run(engine, params, requests):
    """-> (scheduler, {rid: logit rows in emission order}, the cache tree
    the last step handed back) of one run. A request attached whole
    from the prefix cache has no chunk: its rows start at the decode
    step that reads its prompt's last token."""
    rows, slot_of, pending, left = {}, {}, [], {}
    chunk_prefill, decode_step = engine.chunk_prefill, engine.decode_step

    def spy_chunk(p, cache, bt_row, ids, start, n_valid):
        cache, logits = chunk_prefill(p, cache, bt_row, ids, start, n_valid)
        left["cache"] = cache
        end, tail = int(start) + int(n_valid), np.asarray(ids)[0, :int(n_valid)]
        for r in requests:  # the last chunk of a prompt not yet seen
            if (r.rid not in rows and r.prompt.size == end
                    and np.array_equal(r.prompt[int(start):], tail)):
                rows[r.rid] = [np.asarray(logits)]
                pending.append(r.rid)
                break
        return cache, logits

    def spy_decode(p, cache, bt, positions, tokens, active):
        cache, logits = decode_step(p, cache, bt, positions, tokens, active)
        left["cache"], left["bt"] = cache, np.asarray(bt)
        for slot in map(int, np.nonzero(np.asarray(active))[0]):
            pos, tok = int(positions[slot]), int(tokens[slot])
            if slot not in slot_of:
                rid = next((i for i in pending
                            if requests[i].prompt.size == pos), None)
                if rid is not None:
                    pending.remove(rid)
                else:  # attached whole: decoding its last prompt token
                    rid = next(
                        r.rid for r in requests
                        if r.rid not in rows and r.prompt.size - 1 == pos
                        and int(r.prompt[-1]) == tok)
                    rows[rid] = []
                slot_of[slot] = rid
            rid = slot_of[slot]
            rows[rid].append(np.asarray(logits)[slot])
            if len(rows[rid]) == requests[rid].max_new_tokens:
                del slot_of[slot]  # its last token: the slot is free
        return cache, logits

    engine.chunk_prefill, engine.decode_step = spy_chunk, spy_decode
    try:
        sched = engine.run(params, requests)
    finally:
        engine.chunk_prefill, engine.decode_step = chunk_prefill, decode_step
    return sched, rows, left["cache"], left["bt"]


def requests_of(lengths, new_tokens=5, seed=0):
    rng = np.random.default_rng(seed)
    return [
        Request(rid=i, prompt=rng.integers(1, 97, size=n).astype(np.int32),
                max_new_tokens=new_tokens)
        for i, n in enumerate(lengths)
    ]


# more requests than slots: slots are recycled; lengths below, at and
# over one chunk and a multiple of it; one prompt of a single token
LENGTHS = [19, 3, 8, 27, 16, 9, 1, 33]


def check_rows(params, requests, sched, rows, tol=2e-5):
    for f in sched.finished:
        prompt = requests[f.rid].prompt
        ids = np.concatenate([prompt, np.asarray(f.tokens[:-1], np.int32)])
        want = full_forward(params, ids)[prompt.size - 1:]
        got = np.stack(rows[f.rid])[:len(want)]
        np.testing.assert_allclose(got, want, rtol=tol, atol=tol,
                                   err_msg=f"request {f.rid}")


def test_chunked_prefill_then_the_absorbed_step_equal_the_reference(
        engine, params):
    """Several chunks with padded tails, then decode steps in which the
    slots sit at positions of their own (the rotation is per slot), and
    slots that are recycled."""
    requests = requests_of(LENGTHS)
    sched, rows, _, _ = spied_run(engine, params, requests)
    assert len(sched.finished) == len(requests)
    check_rows(params, requests, sched, rows)
    stats = sched.paged_stats
    # (with the prefix cache on, the one-token prompt has nothing to
    # ingest: the decode step reads its token at position 0)
    chunked = [n for n in LENGTHS if n > 1]
    assert stats["prefill_positions_valid"] == sum(chunked)
    assert stats["prefill_positions_computed"] == CHUNK * sum(
        -(-n // CHUNK) for n in chunked)
    # a real row is a prompt position a chunk ingested or a slot a
    # decode step advanced: its picks, in both expert layers, and no
    # other's; the tails' and the inactive slots' are counted apart
    steps = len(sched.step_occupancy)
    advanced = sum(sched.step_occupancy)
    assert stats["moe_picks"] == 2 * 2 * (sum(chunked) + advanced)
    tails = stats["prefill_positions_computed"] - sum(chunked)
    assert stats["moe_rows_masked"] == 2 * 2 * (
        tails + SLOTS * steps - advanced)
    assert 0 < stats["moe_experts_hit"] <= 2 * 8 * steps
    assert stats["moe_experts_hit"] >= 2 * steps  # a live step reaches one
    assert 1 <= stats["moe_expert_rows_max"] <= 2 * CHUNK
    assert stats["state_pool_bytes"] == 0
    assert engine.chunk_decay_kind is None
    assert stats["state_head_decay_chunks"] == 0
    assert stats["latent_pool_bytes"] == (
        engine.paged_spec.num_pages * PAGE * 3 * 128 * 4)  # as stored


def test_a_prefix_attached_from_the_cache_gives_the_fresh_prompts_logits(
        engine, params):
    """A document asked again whole (attached with its partial page,
    which is copied before the first write) and with another question
    (its full pages attached, the rest ingested), after the slots have
    been through other requests."""
    rng = np.random.default_rng(3)
    document = rng.integers(1, 97, size=21).astype(np.int32)
    asks = [
        document,
        rng.integers(1, 97, size=5).astype(np.int32),
        rng.integers(1, 97, size=12).astype(np.int32),
        rng.integers(1, 97, size=7).astype(np.int32),
        document,  # 5 full pages and the partial one
        np.concatenate([document, rng.integers(1, 97, size=6).astype(np.int32)]),
    ]
    requests = [Request(rid=i, prompt=p, max_new_tokens=6)
                for i, p in enumerate(asks)]
    sched, rows, _, _ = spied_run(engine, params, requests)
    assert len(sched.finished) == len(requests)
    check_rows(params, requests, sched, rows)
    assert sched.prefix_stats["hits"] == 2
    assert sched.prefix_stats["tokens_reused"] == 21 + 20
    assert sched.paged_stats["cow_copies"] >= 1
    # the same question through attached pages: the first asking's rows
    np.testing.assert_allclose(
        np.stack(rows[4]), np.stack(rows[0]), rtol=2e-5, atol=2e-5)
    # the second asking ingested nothing: no chunk ran for it
    assert sched.paged_stats["prefill_positions_valid"] == (
        21 + 5 + 12 + 7 + (27 - 20))


def test_the_pool_holds_one_row_a_token_and_nothing_per_head(engine, params):
    cache = jax.eval_shape(engine.init_cache)
    assert set(cache) == {"latent", "counters"}
    assert set(cache["latent"]) == {"0", "1", "2"}
    spec = engine.paged_spec
    assert spec.latent_dim == 20 and spec.latent_width == 128
    for pool in cache["latent"].values():
        assert pool.shape == (spec.num_pages, PAGE, 128)
    # bytes are what the device stores: a row in whole lane tiles
    assert spec.page_bytes == 3 * PAGE * 128 * 4
    assert engine._slot_stripe_bytes == 3 * 64 * 128 * 4
    # what the engine's programs leave there: [c, rotated key, zeros]
    requests = requests_of([13], new_tokens=3)
    sched, _, left, bt = spied_run(engine, params, requests)
    ids = np.concatenate(
        [requests[0].prompt, np.asarray(sched.finished[0].tokens[:2])])
    want = np.asarray(reference().latent_rows(
        params, ids[None], arch=arch_of(CFG)))[0]
    pool = np.asarray(left["latent"]["0"])
    # the request sat in slot 0; its pages by its row of the block
    # table (the partial page its prompt registered was copied before
    # the first decode write)
    table = bt[0, :-(-ids.size // PAGE)]
    assert (table >= 0).all() and len(set(table.tolist())) == table.size
    got = pool[table].reshape(-1, 128)[:ids.size]
    np.testing.assert_allclose(got[:, :20], want, rtol=2e-5, atol=2e-5)
    assert not got[:, 20:].any()


def test_the_published_widths_cache_576_values_a_token():
    """8,064 bytes a token over the cut's 7 layers in bfloat16 (stored
    as 640 values a row: 8,960), against 143,360 for per-head keys and
    values; shapes alone, nothing is allocated."""
    cfg = glm_moe.config_from_dict(
        {**PUBLISHED, "num_hidden_layers": 7, "torch_dtype": "bfloat16"})
    eng = ServingEngine(cfg, None, num_slots=32, max_len=16384, page_size=64,
                        num_pages=4096, prefill_chunk=1024, prefix_cache=True,
                        compute_dtype="bf16")
    spec = eng.paged_spec
    assert (spec.latent_dim, spec.latent_width, spec.num_layers) == (
        576, 640, 7)
    values = spec.num_layers * spec.latent_dim * 2
    assert values == 8064 and spec.page_bytes == 64 * 8960  # as stored
    assert spec.num_pages * 64 * values == 2_113_929_216
    assert spec.num_pages * spec.page_bytes == 2_348_810_240
    cache = jax.eval_shape(eng.init_cache)
    assert "k" not in cache and "v" not in cache
    assert {p.shape for p in cache["latent"].values()} == {(4096, 64, 640)}
    assert {p.dtype for p in cache["latent"].values()} == {
        jnp.dtype(jnp.bfloat16)}
    per_head = 7 * 20 * (256 + 256) * 2
    assert per_head == 143_360 and per_head // 8064 == 17


def test_a_copied_page_is_the_source_page_in_every_layer():
    spec = PagedKVCacheSpec(
        num_layers=2, num_slots=1, max_len=8, page_size=4, num_pages=5,
        num_heads=0, head_dim=0, latent_dim=6)
    cache = init_paged_cache(spec)
    cache["latent"] = {
        k: jnp.arange(5 * 4 * 128, dtype=jnp.float32).reshape(5, 4, 128)
        * (int(k) + 1) for k in cache["latent"]}
    cache["counters"] = {"n": jnp.float32(3)}
    out = copy_page(cache, jnp.int32(1), jnp.int32(3))
    for k, pool in out["latent"].items():
        np.testing.assert_array_equal(pool[3], cache["latent"][k][1])
        np.testing.assert_array_equal(pool[:3], cache["latent"][k][:3])
        np.testing.assert_array_equal(pool[4], cache["latent"][k][4])
    assert float(out["counters"]["n"]) == 3
    with pytest.raises(ValueError, match="latent pool has no head axis"):
        spec.validate("tp", None)


def test_a_chunks_padded_tail_is_not_written(engine, params):
    """The rows past `n_valid` keep what the pool held."""
    host = engine.new_host()
    host.ensure_pages(0, 16)
    cache = engine.init_cache()
    cache["latent"] = {k: p + 7.0 for k, p in cache["latent"].items()}
    ids = np.zeros((1, CHUNK), np.int32)
    ids[0, :3] = [5, 9, 11]
    cache, _ = engine.chunk_prefill(
        params, cache, host.device_row(0), ids, np.int32(4), np.int32(3))
    pool = np.asarray(cache["latent"]["1"])
    rows = pool[host.block_tables[0, :4]].reshape(16, 128)
    assert (rows[:4] == 7.0).all() and (rows[7:] == 7.0).all()
    assert not (rows[4:7, :20] == 7.0).any() and not rows[4:7, 20:].any()
    untouched = np.delete(pool, host.block_tables[0, :4], axis=0)
    assert (untouched == 7.0).all()


def test_an_inactive_slots_rows_are_untouched_by_a_decode_step(
        engine, params):
    host = engine.new_host()
    for slot in range(SLOTS):
        host.ensure_pages(slot, 8)
    cache = engine.init_cache()
    cache["latent"] = {k: p + 7.0 for k, p in cache["latent"].items()}
    active = np.asarray([True, False, True])
    cache, logits = engine.decode_step(
        params, cache, host.device_table(), np.asarray([2, 5, 7], np.int32),
        np.asarray([4, 8, 15], np.int32), active)
    pool = np.asarray(cache["latent"]["2"])
    of = lambda slot: pool[host.block_tables[slot, :2]].reshape(8, 128)
    assert (of(1) == 7.0).all()
    for slot, pos in ((0, 2), (2, 7)):
        written = (of(slot)[:, :20] != 7.0).all(axis=1)
        assert written.tolist() == [p == pos for p in range(8)]
    assert np.isfinite(np.asarray(logits)[active]).all()
    counters = jax.device_get(cache["counters"])
    assert {v.dtype for v in counters.values()} == {np.dtype(np.int32)}
    assert counters["moe_picks"] == 2 * 2 * 2
    assert counters["moe_rows_masked"] == 2 * 2 * 1


def test_a_drain_through_the_decode_steps_kernel_gives_the_references_rows(
        params, monkeypatch):
    """The decode step as a TPU runs it (`decode_kind` answering
    "kernel", the Pallas paged attention through the interpreter)
    inside the engine: slots at positions of their own, inactive slots,
    a recycled slot, against the reference's full forward pass. The
    kernel reads a cached row as a bfloat16, so these float32 rows come
    back rounded: near the reference, not equal to it."""
    from jax.experimental.pallas import tpu as pltpu

    from distributed_model_parallel_tpu.ops import latent_attention as LA

    calls = []
    kind = LA.decode_kind
    monkeypatch.setattr(LA, "_on_tpu", lambda: True)
    monkeypatch.setattr(
        LA, "decode_kind", lambda *a: calls.append(kind(*a)) or calls[-1])
    eng = ServingEngine(CFG, None, num_slots=SLOTS, max_len=64, page_size=8,
                        prefill_chunk=CHUNK, prefix_cache=True)
    requests = requests_of([19, 3, 27, 9], new_tokens=3, seed=4)
    with pltpu.force_tpu_interpret_mode():
        sched, rows, _, _ = spied_run(eng, params, requests)
    assert len(sched.finished) == len(requests)
    assert calls == ["kernel"] * 3  # traced once, a latent layer each
    for f in sched.finished:
        prompt = requests[f.rid].prompt
        ids = np.concatenate([prompt, np.asarray(f.tokens[:-1], np.int32)])
        want = full_forward(params, ids)[prompt.size - 1:]
        got = np.stack(rows[f.rid])[:len(want)]
        assert np.abs(got - want).max() < 0.02 * np.abs(want).max(), f.rid


@pytest.mark.parametrize("name, kwargs", [
    ("speculative_k", {"speculative_k": 2}),
    ("layout=tp", {"layout": "tp"}),
    ("layout=sp", {"layout": "sp"}),
    ("page_size=None", {"page_size": None, "prefill_chunk": None,
                        "prefix_cache": False}),
    ("prefill_chunk=None", {"prefill_chunk": None, "prefix_cache": False}),
])
def test_what_latent_pages_cannot_do_yet_is_refused_under_its_name(
        name, kwargs):
    base = {"num_slots": 2, "max_len": 64, "page_size": 4,
            "prefill_chunk": 8, "prefix_cache": True}
    with pytest.raises(ValueError) as refused:
        ServingEngine(CFG, None, **{**base, **kwargs})
    message = str(refused.value)
    assert message.startswith(
        f"{name} is not built for the glm4_moe_lite family")
    assert "latent" in message or "next-token-prediction" in message


def test_the_recorders_are_the_engines_and_the_seam_is_one(engine):
    assert engine.family.name == "glm4_moe_lite"
    assert engine.latent_dim == 20 and engine.state_spec is None
    assert engine.family.masks_inactive and engine.family.counters
    assert D.PagedLatentDecode.__mro__[1] is D.PagedLatentChunk.__mro__[1]


def test_cli_serve_starts_the_family_and_says_what_the_cache_holds(
        tmp_path, capsys):
    from distributed_model_parallel_tpu.cli import serve

    path = tmp_path / "tiny.json"
    path.write_text(json.dumps({**TINY, "max_position_embeddings": 64}))
    out = serve.main([
        "--model-config", str(path), "--num-slots", "2", "--max-len", "64",
        "--page-size", "4", "--prefill-chunk", "8", "--prefix-cache",
        "--num-requests", "3", "--prompt-len-min", "5",
        "--prompt-len-max", "11", "--max-new-tokens", "3", "--seed", "1",
    ])
    printed = capsys.readouterr().out
    lines = [l for l in printed.splitlines() if "latent row" in l]
    assert len(lines) == 1
    assert "glm4_moe_lite: the cache holds one latent row of 20 values" \
        in lines[0]
    assert "stored as 128" in lines[0] and "nothing per head" in lines[0]
    assert len(out["requests"]) == 3
    with pytest.raises(SystemExit, match="speculative_k is not built"):
        serve.main(["--model-config", str(path), "--num-slots", "2",
                    "--max-len", "64", "--page-size", "4",
                    "--prefill-chunk", "8", "--speculative-k", "2"])
