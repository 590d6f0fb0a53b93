"""A model that keeps a recurrent state per slot, served through
`ServingEngine.run`: the state pool beside the page pool, chunked
prefill with carried state and padded tails, the one-token recurrence
over all slots, grouped query heads over one paged head, the refusals
by name, and the GPT engine unchanged behind the same seam."""

import dataclasses

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from distributed_model_parallel_tpu.models import jamba
from distributed_model_parallel_tpu.models import layers as L
from distributed_model_parallel_tpu.models.gpt import GPTConfig
from distributed_model_parallel_tpu.serving import decode as D
from distributed_model_parallel_tpu.serving.engine import ServingEngine
from distributed_model_parallel_tpu.serving.scheduler import Request

CFG = jamba.JambaConfig(
    vocab_size=97, hidden_size=32, num_hidden_layers=5,
    num_attention_heads=4, num_key_value_heads=1, intermediate_size=64,
    attn_layer_period=4, attn_layer_offset=1, mamba_d_state=4,
    mamba_d_conv=4, mamba_dt_rank=6, mamba_expand=2, rms_norm_eps=1e-6,
    max_position=4096,
)
SLOTS, CHUNK, PAGE = 3, 8, 4


@pytest.fixture(scope="module")
def engine():
    return ServingEngine(CFG, None, num_slots=SLOTS, max_len=64,
                         page_size=PAGE, prefill_chunk=CHUNK)


@pytest.fixture(scope="module")
def params(engine):
    return engine.init_params(jax.random.PRNGKey(0))


def full_forward(engine, params, ids):
    """Logits of the family's dense model on one whole sequence."""
    _, state = jax.eval_shape(engine._full.init, jax.random.PRNGKey(0))
    logits, _ = engine._full.apply(
        params, state, np.asarray(ids)[None], L.Context(train=False))
    return np.asarray(logits[0])


def spied_run(engine, params, requests):
    """(scheduler, {rid: logit rows in emission order}) of one run."""
    rows, slot_of, ingesting = {}, {}, {}
    chunk_prefill, decode_step = engine.chunk_prefill, engine.decode_step
    by_prompt = {r.prompt.tobytes(): r for r in requests}

    def spy_chunk(p, cache, bt_row, ids, start, n_valid, slot):
        cache, logits = chunk_prefill(
            p, cache, bt_row, ids, start, n_valid, slot)
        slot = int(slot)
        got = ingesting.setdefault(slot, [])
        got.extend(np.asarray(ids)[0, :int(n_valid)].tolist())
        req = by_prompt.get(np.asarray(got, np.int32).tobytes())
        if req is not None and len(got) == req.prompt.size:
            slot_of[slot] = req.rid
            rows[req.rid] = [np.asarray(logits)]
            ingesting[slot] = []
        return cache, logits

    def spy_decode(p, cache, bt, positions, tokens, active):
        cache, logits = decode_step(p, cache, bt, positions, tokens, active)
        for slot in np.nonzero(np.asarray(active))[0]:
            rows[slot_of[int(slot)]].append(np.asarray(logits)[slot])
        return cache, logits

    engine.chunk_prefill, engine.decode_step = spy_chunk, spy_decode
    try:
        sched = engine.run(params, requests)
    finally:
        engine.chunk_prefill, engine.decode_step = chunk_prefill, decode_step
    return sched, rows


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


def test_chunked_prefill_and_decode_equal_the_full_forward(engine, params):
    requests = requests_of(LENGTHS)
    sched, rows = spied_run(engine, params, requests)
    assert len(sched.finished) == len(requests)
    for f in sched.finished:
        prompt = requests[f.rid].prompt
        ids = np.concatenate([prompt, np.asarray(f.tokens[:-1], np.int32)])
        want = full_forward(engine, params, ids)[prompt.size - 1:]
        got = np.stack(rows[f.rid])[:len(want)]
        np.testing.assert_allclose(got, want, rtol=1e-5, atol=1e-5,
                                   err_msg=f"request {f.rid}")
    stats = sched.paged_stats
    assert stats["prefill_positions_valid"] == sum(LENGTHS)
    assert stats["prefill_positions_computed"] == CHUNK * sum(
        -(-n // CHUNK) for n in LENGTHS)
    assert stats["state_resets"] == len(LENGTHS)
    assert stats["state_pool_bytes"] == engine.state_spec.pool_bytes > 0
    # off a TPU the recurrence is the loop, in every chunk
    assert engine.chunk_state_program == "loop"
    assert stats["state_kernel_chunks"] == 0
    # a Mamba layer has no delta rule
    assert engine.chunk_decay_kind is None
    assert stats["state_head_decay_chunks"] == 0
    assert stats["cow_copies"] == 0


def test_greedy_picks_on_the_device_what_the_host_would_of_the_row(
        engine, params, monkeypatch):
    """The paged loop fetches ids, not logits: every emitted token is
    NumPy's argmax of the row its step computed, the first of equals."""
    from distributed_model_parallel_tpu.serving import engine as E

    shapes = []
    pick = E.greedy_pick
    monkeypatch.setattr(E, "greedy_pick", lambda logits: (
        shapes.append(logits.shape), pick(logits))[1])
    requests = requests_of(LENGTHS)
    sched, rows = spied_run(engine, params, requests)
    for f in sched.finished:
        assert list(f.tokens) == [int(r.argmax()) for r in rows[f.rid]]
    assert set(shapes) == {(CFG.vocab_size,), (SLOTS, CFG.vocab_size)}
    ties = jnp.asarray([[0., 2., 2., 1.], [3., 3., 3., 3.]])
    got = pick(ties)
    assert got.dtype == jnp.int32 and got.tolist() == [1, 0]


def test_a_recycled_slot_starts_from_a_zero_state(engine, params):
    """The same prompt before and after every slot has held another
    sequence: the same logits, bit for bit."""
    rng = np.random.default_rng(5)
    prompt = rng.integers(1, 97, size=2 * CHUNK + 3).astype(np.int32)
    fillers = requests_of([11, 7, 14], new_tokens=3, seed=6)
    requests = (
        [Request(rid="first", prompt=prompt, max_new_tokens=4)]
        + [dataclasses.replace(r, rid=f"f{r.rid}") for r in fillers]
        + [Request(rid="again", prompt=prompt.copy(), max_new_tokens=4)]
    )
    # the two long prompts are the same bytes: tell them apart by order
    seen = []
    chunk_prefill = engine.chunk_prefill

    def spy(p, cache, bt_row, ids, start, n_valid, slot):
        cache, logits = chunk_prefill(
            p, cache, bt_row, ids, start, n_valid, slot)
        if int(start) + int(n_valid) == prompt.size:
            seen.append((int(slot), np.asarray(logits)))
        return cache, logits

    engine.chunk_prefill = spy
    try:
        sched = engine.run(params, requests)
    finally:
        engine.chunk_prefill = chunk_prefill
    tokens = {f.rid: f.tokens for f in sched.finished}
    assert tokens["first"] == tokens["again"]
    (_, first), (_, again) = seen
    np.testing.assert_array_equal(first, again)
    want = full_forward(engine, params, prompt)[-1]
    np.testing.assert_allclose(again, want, rtol=1e-5, atol=1e-5)


def test_an_inactive_slots_state_is_untouched_by_a_decode_step(engine, params):
    cache = engine.init_cache()
    cache["state"] = jax.tree_util.tree_map(
        lambda x: jnp.arange(x.size, dtype=jnp.float32).reshape(x.shape)
        .astype(x.dtype) / x.size, cache["state"])
    before = jax.tree_util.tree_map(np.asarray, cache["state"])
    host = engine.new_host()
    host.ensure_pages(1, 1)
    active = np.array([False, True, False])
    new_cache, _ = engine.decode_step(
        params, cache, host.device_table(), jnp.zeros((SLOTS,), jnp.int32),
        jnp.ones((SLOTS,), jnp.int32), jnp.asarray(active))
    for layer, arrays in new_cache["state"].items():
        for name, after in arrays.items():
            old = before[layer][name]
            np.testing.assert_array_equal(np.asarray(after)[[0, 2]],
                                          old[[0, 2]])
            assert not np.array_equal(np.asarray(after)[1], old[1])
    assert new_cache["state"]["0"]["h"].dtype == jnp.float32


def test_the_state_pool_lives_in_the_cache_tree_and_is_counted(engine):
    cache = jax.eval_shape(engine.init_cache)
    d_in = CFG.d_inner
    assert sorted(cache["state"], key=int) == ["0", "2", "3", "4"]
    assert cache["state"]["0"]["h"].shape == (SLOTS, 4, d_in)
    assert cache["state"]["0"]["conv"].shape == (SLOTS, 3, d_in)
    # one paged layer of one K/V head, folded into the page's row
    assert cache["k"].shape == (1, SLOTS * 64 // PAGE, PAGE, 8)
    assert engine.paged_spec.num_heads == 1
    per_slot = 4 * (4 * d_in * 4 + 3 * d_in * 4)
    assert engine.state_spec.slot_bytes == per_slot
    assert engine._slot_stripe_bytes == 2 * 64 * 8 * 4 + per_slot
    # the slot is the chunk step's own argument, not part of the row
    assert engine.new_host().device_row(2).shape == (64 // PAGE,)


def test_twenty_query_heads_read_one_paged_head():
    """The paged recorders on grouped heads against dense attention
    over the repeated head; the pool holds the one head, folded."""
    rng = np.random.default_rng(1)
    h, dh, page, t = 20, 8, 4, 11
    q, k, v = (jnp.asarray(rng.normal(size=(1, t, n, dh)), jnp.float32)
               for n in (h, 1, 1))
    pool = jnp.zeros((1, 8, page, dh))
    bt = jnp.asarray([0, 1, 2, 3], jnp.int32)
    rec = D.PagedChunkAttention(pool, pool, bt, jnp.int32(0), page)
    out = rec(q, k, v, None)
    dense = jax.nn.softmax(jnp.where(
        jnp.tril(jnp.ones((t, t), bool))[None],
        jnp.einsum("qhd,kd->hqk", q[0], k[0, :, 0]) / np.sqrt(dh), -jnp.inf,
    ), -1)
    want = jnp.einsum("hqk,kd->qhd", dense, v[0, :, 0])
    np.testing.assert_allclose(out[0], want, rtol=1e-5, atol=1e-5)
    np.testing.assert_array_equal(
        np.asarray(rec.k[0, :3]).reshape(12, dh)[:t], np.asarray(k[0, :, 0]))
    # one more token through the decode recorder, slot 0 of 1
    q1, k1, v1 = (jnp.asarray(rng.normal(size=(1, 1, n, dh)), jnp.float32)
                  for n in (h, 1, 1))
    dec = D.PagedCacheAttention(rec.k, rec.v, bt[None], jnp.asarray([t]),
                                jnp.asarray([True]), page)
    got = dec(q1, k1, v1, None)
    keys = jnp.concatenate([k[0, :, 0], k1[0, :, 0]])
    vals = jnp.concatenate([v[0, :, 0], v1[0, :, 0]])
    w = jax.nn.softmax(jnp.einsum("hd,kd->hk", q1[0, 0], keys) / np.sqrt(dh))
    np.testing.assert_allclose(got[0, 0], w @ vals, rtol=1e-5, atol=1e-5)


@pytest.mark.parametrize("name,kwargs", [
    ("prefix_cache", {"prefix_cache": True}),
    ("speculative_k", {"speculative_k": 2}),
    ("layout=tp", {"layout": "tp"}),
    ("layout=sp", {"layout": "sp"}),
    ("page_size=None", {"page_size": None, "prefill_chunk": None}),
    ("prefill_chunk=None", {"prefill_chunk": None}),
])
def test_what_a_state_cannot_do_yet_is_refused_under_its_name(name, kwargs):
    base = {"num_slots": 2, "max_len": 64, "page_size": 4, "prefill_chunk": 8}
    with pytest.raises(ValueError) as refused:
        ServingEngine(CFG, None, **{**base, **kwargs})
    message = str(refused.value)
    assert message.startswith(f"{name} is not built for the jamba family")
    assert "state" in message


def test_weights_rest_in_the_dtype_the_configuration_states():
    cfg = dataclasses.replace(CFG, param_dtype="bfloat16")
    eng = ServingEngine(cfg, None, num_slots=2, max_len=32, page_size=4,
                        prefill_chunk=8, compute_dtype="bf16")
    params = jax.jit(eng.init_params)(jax.random.PRNGKey(1))
    assert {x.dtype for x in jax.tree_util.tree_leaves(params)} == {
        jnp.dtype(jnp.bfloat16)}
    cache = jax.eval_shape(eng.init_cache)
    assert cache["state"]["0"]["h"].dtype == jnp.float32
    assert cache["state"]["0"]["conv"].dtype == jnp.bfloat16
    assert cache["k"].dtype == jnp.bfloat16
    sched = eng.run(params, requests_of([13, 5], new_tokens=3))
    assert all(len(f.tokens) == 3 for f in sched.finished)
    prompt = requests_of([13, 5])[0].prompt
    got = np.asarray(eng.chunk_prefill(
        params, eng.init_cache(), _row(eng, 0, 16),
        jnp.asarray(prompt[None, :8]), jnp.int32(0), jnp.int32(8),
        jnp.int32(0))[1])
    assert got.dtype == np.float32 and got.shape == (97,)


def _row(engine, slot, n_tokens):
    host = engine.new_host()
    host.ensure_pages(slot, n_tokens)
    return host.device_row(slot)


def drained(cfg, lengths, chunk):
    """(engine, scheduler, the state pool the drain left) of a toy
    drain over 2 slots."""
    eng = ServingEngine(cfg, None, num_slots=2, max_len=256, page_size=16,
                        prefill_chunk=chunk)
    params = eng.init_params(jax.random.PRNGKey(3))
    left = {}
    chunk_prefill, decode_step = eng.chunk_prefill, eng.decode_step

    def keep(step):
        def spy(*args):
            cache, out = step(*args)
            left["state"] = cache["state"]
            return cache, out
        return spy

    eng.chunk_prefill, eng.decode_step = keep(chunk_prefill), keep(decode_step)
    try:
        sched = eng.run(params, requests_of(lengths, new_tokens=4, seed=5))
    finally:
        eng.chunk_prefill, eng.decode_step = chunk_prefill, decode_step
    return eng, sched, jax.tree_util.tree_map(np.asarray, left["state"])


def test_the_chunk_program_with_the_kernel_drains_as_the_loop_does(
        monkeypatch):
    """The selector answering "kernel" (as on a TPU; the kernel itself
    through the interpreter): the same greedy tokens and the same state
    pool as the loop, a recycled slot and padded tails among the
    requests; `state_kernel_chunks` counts the chunks dispatched."""
    from distributed_model_parallel_tpu.ops import ssm_scan

    # widths the kernel tiles: 128 channels, a state of 8, chunks of 64
    cfg = dataclasses.replace(
        CFG, hidden_size=64, num_hidden_layers=3, attn_layer_period=3,
        mamba_d_state=8)
    chunk, lengths = 64, [70, 3, 64, 130, 20]
    loop_eng, loop, loop_state = drained(cfg, lengths, chunk)
    assert loop_eng.chunk_state_program == "loop"
    assert loop.paged_stats["state_kernel_chunks"] == 0

    monkeypatch.setattr(ssm_scan, "_on_tpu", lambda: True)
    eng, sched, state = drained(cfg, lengths, chunk)
    assert eng.chunk_state_program == "kernel"
    chunks = sum(-(-n // chunk) for n in lengths)
    stats = sched.paged_stats
    assert stats["state_kernel_chunks"] == chunks
    assert stats["prefill_positions_computed"] == chunks * chunk
    assert stats["state_resets"] == len(lengths)  # 5 prompts over 2 slots
    tokens = lambda s: {f.rid: list(f.tokens) for f in s.finished}
    assert tokens(sched) == tokens(loop) and len(tokens(sched)) == len(lengths)
    for got, want in zip(jax.tree_util.tree_leaves(state),
                         jax.tree_util.tree_leaves(loop_state)):
        np.testing.assert_allclose(got, want, rtol=1e-5, atol=1e-6)
    # the chunk program holds one kernel a state layer, the decode step
    # (one position) none
    params = jax.eval_shape(eng.init_params, jax.random.PRNGKey(0))
    cache = jax.eval_shape(eng.init_cache)
    slots = np.zeros((2,), np.int32)
    chunk_graph = str(jax.make_jaxpr(eng.chunk_prefill)(
        params, cache, eng.new_host().device_row(0),
        np.zeros((1, chunk), np.int32), np.int32(0), np.int32(chunk),
        np.int32(0)))
    decode_graph = str(jax.make_jaxpr(eng.decode_step)(
        params, cache, eng.new_host().device_table(), slots, slots,
        slots.astype(bool)))
    # (the kernel is traced once, `name=ssm_scan`, and called a layer)
    assert chunk_graph.count("name=ssm_scan") == 1
    assert chunk_graph.count("jaxpr=_scan_call") == 2
    assert "pallas_call" not in decode_graph


def test_the_gpt_engine_answers_the_same_seam_with_what_it_did():
    cfg = GPTConfig(vocab_size=61, dim=32, num_layers=2, num_heads=4,
                    ffn_dim=64, max_position=64, dropout_rate=0.0,
                    pad_token_id=0)
    eng = ServingEngine(cfg, None, num_slots=2, max_len=32, page_size=4,
                        prefill_chunk=8, prefix_cache=True)
    fam = eng.family
    assert fam.name == "gpt" and not fam.missing and fam.param_dtype is None
    assert {(lc.kv_heads, lc.head_dim) for lc in fam.layers} == {(4, 8)}
    assert eng.state_spec is None
    cache = jax.eval_shape(eng.init_cache)
    assert set(cache) == {"k", "v"}
    assert cache["k"].shape == (2, 16, 4, 4, 8)  # heads not folded
    assert eng.new_host().device_row(1).shape == (8,)
    params = eng.init_params(jax.random.PRNGKey(0))
    assert params["stem"]["word"].dtype == jnp.float32
    sched = eng.run(params, [Request(
        rid=0, prompt=np.arange(1, 12, dtype=np.int32), max_new_tokens=4)])
    stats = sched.paged_stats
    assert stats["state_pool_bytes"] == 0 and stats["state_resets"] == 0
    assert eng.chunk_state_program is None
    assert stats["state_kernel_chunks"] == 0
    assert (stats["prefill_positions_valid"],
            stats["prefill_positions_computed"]) == (11, 16)
    # the programs keep the names the benchmark's readers look for
    assert eng.decode_step.__name__ == "paged_decode_step"
    assert eng.chunk_prefill.__name__ == "chunk_prefill_step"
