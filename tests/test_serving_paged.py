"""Paged KV cache + chunked prefill + prefix caching pins (ISSUE 15,
`serving/kv_cache.py` / `serving/decode.py` / `serving/engine.py`).

The load-bearing pins:

* **Logit parity** — the paged decode step is LOGIT-IDENTICAL (rtol
  1e-5) to dense full recompute for the replicated/TP/SP layouts, on
  ragged batches whose sequences straddle >= 3 pages, including a
  recycled slot mid-run. Paging is a storage change, never a math
  change.
* **Memory structure** — allocated pages for a ragged batch track live
  tokens: <= ceil(tokens/page) + one partial page per live sequence,
  and strictly under the contiguous layout's slots*max_len stripes
  (the PagedAttention waste claim, asserted from the pool
  bookkeeping).
* **Chunked prefill trajectory** — a chunk-ingested prompt produces
  byte-identical greedy tokens to the monolithic prefill and the
  contiguous engine.
* **Prefix caching** — a repeated prompt HITS (pages shared, prefill
  skipped), a divergent prompt resumes ingestion at the first
  unmatched page, and a write into a shared page copies first
  (copy-on-write), with the original sequence unperturbed.
"""

import numpy as np
import pytest

import jax
import jax.numpy as jnp

from distributed_model_parallel_tpu.models.gpt import GPTConfig, gpt_lm
from distributed_model_parallel_tpu.models.layers import Context
from distributed_model_parallel_tpu.runtime.mesh import MeshSpec, make_mesh
from distributed_model_parallel_tpu.serving.engine import ServingEngine
from distributed_model_parallel_tpu.serving.kv_cache import (
    PagedKVCacheSpec,
    PagePool,
    PrefixCache,
    SlotAllocator,
)
from distributed_model_parallel_tpu.serving.sampling import (
    SamplingConfig,
    SlotSampler,
)
from distributed_model_parallel_tpu.serving.scheduler import Request

CFG = GPTConfig(
    vocab_size=61, dim=16, num_layers=2, num_heads=4, ffn_dim=32,
    max_position=16, dropout_rate=0.0,
)
# Ragged on purpose; with page_size=4 the 5-token prompt's decode walk
# crosses into its third page by step 4 (position 8).
PROMPT_LENS = (3, 5, 2)


@pytest.fixture(scope="module")
def dense():
    """Shared dense twin: params + a full-recompute next-token oracle."""
    model = gpt_lm(CFG)
    params, state = model.init(jax.random.PRNGKey(0))

    def next_logits(ids):
        ids = jnp.asarray(np.asarray(ids, np.int32))[None]
        logits, _ = model.apply(params, state, ids, Context(train=False))
        return np.asarray(logits[0, -1])

    return params, next_logits


def _prompts(seed=0):
    rng = np.random.RandomState(seed)
    return [
        rng.randint(1, CFG.vocab_size, size=n).astype(np.int32)
        for n in PROMPT_LENS
    ]


def _greedy(next_logits, prompt, n):
    ids = list(prompt)
    out = []
    for _ in range(n):
        tok = int(next_logits(ids).argmax())
        out.append(tok)
        ids.append(tok)
    return out


def _assert_paged_decode_parity(eng, dense, *, steps=6, rtol=1e-5):
    """Monolithic-paged prefill of a ragged batch, `steps` decode
    tokens (sequences straddle >= 3 pages at page_size=4), then a
    RECYCLED slot (pages returned to the pool, fresh prompt lands on a
    recycled page set) — every emitted logit row vs dense full
    recompute."""
    params, next_logits = dense
    params = eng.place_params(params)
    prompts = _prompts()[: min(eng.num_slots, 3)]
    host = eng.new_host()
    cache = eng.init_cache()
    tokens = np.zeros((eng.num_slots,), np.int32)
    positions = np.zeros((eng.num_slots,), np.int32)
    active = np.zeros((eng.num_slots,), bool)
    seqs = {}

    def ingest(slot, prompt):
        nonlocal cache
        host.ensure_pages(slot, int(prompt.size))
        ids, length = eng.pad_prompt(prompt)
        cache, nl = eng.prefill(
            params, cache, host.device_table()[slot], ids, length
        )
        np.testing.assert_allclose(
            np.asarray(nl), next_logits(prompt), rtol=rtol, atol=1e-6
        )
        tok = int(np.asarray(nl).argmax())
        seqs[slot] = list(prompt) + [tok]
        tokens[slot] = tok
        positions[slot] = prompt.size
        active[slot] = True

    def step_all(n):
        nonlocal cache
        for _ in range(n):
            for slot in np.nonzero(active)[0]:
                cache = host.ensure_writable(
                    cache, int(slot), int(positions[slot])
                )
            cache, logits = eng.decode_step(
                params, cache, host.device_table(),
                jnp.asarray(positions), jnp.asarray(tokens),
                jnp.asarray(active),
            )
            logits = np.asarray(logits)
            for slot in seqs:
                np.testing.assert_allclose(
                    logits[slot], next_logits(seqs[slot]),
                    rtol=rtol, atol=1e-6,
                )
                tok = int(logits[slot].argmax())
                seqs[slot].append(tok)
                tokens[slot] = tok
                positions[slot] += 1

    for slot, prompt in enumerate(prompts):
        ingest(slot, prompt)
    step_all(steps)
    # The 5-token prompt has decoded to position 5+6=11: pages 0..2 of
    # page_size 4 — the >= 3-page straddle the acceptance pin names.
    assert int(positions[1]) // eng.paged_spec.page_size >= 2
    # Recycle slot 0: its PAGES return to the pool; a fresh prompt
    # re-allocates (possibly the same page ids, content overwritten up
    # to its own length) while the other slots decode on.
    before = host.pool.pages_in_use
    host.release(0)
    assert host.pool.pages_in_use < before
    positions[0] = 0
    del seqs[0]
    ingest(0, _prompts(seed=9)[2])
    step_all(2)


# ------------------------------------------------------------- layouts


def test_paged_decode_matches_dense_replicated(dense):
    eng = ServingEngine(
        CFG, num_slots=4, max_len=16, prefill_len=8, page_size=4
    )
    _assert_paged_decode_parity(eng, dense)


def test_paged_decode_matches_dense_page2(dense):
    """page_size=2: a 5-token prompt spans 3 pages at PREFILL time
    already, and decode crosses a page boundary every other step."""
    eng = ServingEngine(
        CFG, num_slots=4, max_len=16, prefill_len=8, page_size=2
    )
    _assert_paged_decode_parity(eng, dense)


@pytest.mark.parametrize("s", [2, 4])
def test_paged_decode_matches_dense_tp(s, dense, devices):
    """TP paged: pool heads-sharded over 'model', block-table gathers
    local per shard."""
    mesh = make_mesh(MeshSpec(data=1, model=s), devices=devices[:s])
    eng = ServingEngine(
        CFG, mesh, layout="tp", num_slots=4, max_len=16, prefill_len=8,
        page_size=4,
    )
    _assert_paged_decode_parity(eng, dense)


@pytest.mark.parametrize("s", [2, 4])
def test_paged_decode_matches_dense_tp_collective_matmul(
    s, dense, devices,
):
    """Opted-in decode rings over the PAGED cache: the ring projections
    and the block-table gathers compose without touching each other's
    math (the HLO side — identical 4L(S-1) tagged permute chain — is
    the serve/S2/pg8/cm hlolint combo)."""
    mesh = make_mesh(MeshSpec(data=1, model=s), devices=devices[:s])
    eng = ServingEngine(
        CFG, mesh, layout="tp", num_slots=4, max_len=16, prefill_len=8,
        page_size=4, collective_matmul=True,
    )
    _assert_paged_decode_parity(eng, dense)


@pytest.mark.parametrize("s", [2, 4])
def test_paged_decode_matches_dense_sp(s, dense, devices):
    """SP paged: each shard owns a contiguous slice of EVERY page's
    positions; the per-shard partial attentions merge via the exact
    online-softmax recurrence."""
    mesh = make_mesh(MeshSpec(data=1, seq=s), devices=devices[:s])
    eng = ServingEngine(
        CFG, mesh, layout="sp", num_slots=4, max_len=16, prefill_len=8,
        page_size=4,
    )
    _assert_paged_decode_parity(eng, dense)


# ------------------------------------------- chunked prefill + pooling


def test_chunked_prefill_matches_monolithic_and_contiguous(dense):
    """The chunked-prefill trajectory pin: greedy tokens from the
    chunk-ingested paged engine == monolithic paged == the contiguous
    engine == dense greedy, under admission pressure (5 requests over
    2 slots, slot recycling, a prompt that is not chunk-aligned)."""
    params, next_logits = dense
    prompts = _prompts() + _prompts(seed=3)[:2]
    reqs = [
        Request(rid=i, prompt=p, max_new_tokens=4)
        for i, p in enumerate(prompts)
    ]
    runs = {}
    # (Non-chunk-aligned ingestion is pinned at LOGIT level by
    # test_unaligned_chunk_ingest_logit_parity — a fourth engine here
    # would re-cover it at trajectory level for another compile's
    # worth of tier-1 budget.)
    for key, kw in (
        ("contiguous", {}),
        ("paged", {"page_size": 4}),
        ("chunked", {"page_size": 4, "prefill_chunk": 4}),
    ):
        eng = ServingEngine(
            CFG, num_slots=2, max_len=16, prefill_len=8, **kw
        )
        sched = eng.run(eng.place_params(params), list(reqs))
        assert len(sched.finished) == len(reqs)
        runs[key] = {
            f.rid: f.tokens for f in sched.finished
        }
    expect = {
        i: _greedy(next_logits, p, 4) for i, p in enumerate(prompts)
    }
    for key, toks in runs.items():
        assert toks == expect, f"{key} diverged from dense greedy"


def test_unaligned_chunk_ingest_logit_parity(dense):
    """LOGIT-level pin for chunks that straddle page boundaries
    (prefill_chunk=3 over page_size=4: every chunk after the first
    starts mid-page, so the scatter-back must cover
    (chunk-1)//page + 2 pages — an undercount silently zeroes K/V at
    the straddled position, which a token-trajectory check can miss
    when magnitudes are tiny; regression for exactly that bug)."""
    params, next_logits = dense
    eng = ServingEngine(
        CFG, num_slots=2, max_len=16, prefill_len=8, page_size=4,
        prefill_chunk=3,
    )
    placed = eng.place_params(params)
    host = eng.new_host()
    cache = eng.init_cache()
    prompt = _prompts()[1]  # 5 tokens: chunks [0,3) + [3,5) span pages
    host.ensure_pages(0, int(prompt.size))
    start = 0
    while start < prompt.size:
        n = min(3, int(prompt.size) - start)
        ids = np.zeros((1, 3), np.int32)
        ids[0, :n] = prompt[start:start + n]
        cache, nl = eng.chunk_prefill(
            placed, cache, host.device_table()[0], jnp.asarray(ids),
            jnp.int32(start), jnp.int32(n),
        )
        start += n
    np.testing.assert_allclose(
        np.asarray(nl), next_logits(prompt), rtol=1e-5, atol=1e-6
    )
    # Decode reads the POOL (not the chunk step's view): a dropped
    # scatter page would surface here as wrong logits.
    seq = list(prompt) + [int(np.asarray(nl).argmax())]
    tokens = np.zeros((2,), np.int32)
    tokens[0] = seq[-1]
    positions = np.array([prompt.size, 0], np.int32)
    active = np.array([True, False])
    for _ in range(3):
        cache = host.ensure_writable(cache, 0, int(positions[0]))
        cache, logits = eng.decode_step(
            placed, cache, host.device_table(),
            jnp.asarray(positions), jnp.asarray(tokens),
            jnp.asarray(active),
        )
        np.testing.assert_allclose(
            np.asarray(logits)[0], next_logits(seq),
            rtol=1e-5, atol=1e-6,
        )
        seq.append(int(np.asarray(logits)[0].argmax()))
        tokens[0] = seq[-1]
        positions[0] += 1


def test_chunked_lifts_prefill_len_cap(dense):
    """Chunked ingestion walks the prompt in place, so a prompt longer
    than the monolithic prefill_len pad serves fine (up to
    max_len - 1)."""
    params, next_logits = dense
    long_prompt = np.random.RandomState(5).randint(
        1, CFG.vocab_size, size=12
    ).astype(np.int32)
    eng = ServingEngine(
        CFG, num_slots=2, max_len=16, prefill_len=8, page_size=4,
        prefill_chunk=4,
    )
    sched = eng.run(eng.place_params(params), [
        Request(rid=0, prompt=long_prompt, max_new_tokens=3),
    ])
    assert sched.finished[0].tokens == _greedy(
        next_logits, long_prompt, 3
    )
    # The monolithic paged engine still enforces the pad cap.
    eng2 = ServingEngine(
        CFG, num_slots=2, max_len=16, prefill_len=8, page_size=4
    )
    with pytest.raises(ValueError, match="prefill_len"):
        eng2.run(eng2.place_params(params), [
            Request(rid=0, prompt=long_prompt, max_new_tokens=3),
        ])


def test_paged_memory_scales_with_live_tokens(dense):
    """The structural memory pin (acceptance criterion): after a
    ragged batch prefills, allocated pages == sum(ceil(len_i/page))
    <= ceil(total/page) + one partial page per live sequence, and the
    paged bytes sit strictly under the contiguous layout's
    slots*max_len stripes. Eviction returns PAGES (the recycled-slot
    half of the claim)."""
    params, _ = dense
    page = 4
    eng = ServingEngine(
        CFG, num_slots=4, max_len=16, prefill_len=8, page_size=page
    )
    placed = eng.place_params(params)
    host = eng.new_host()
    cache = eng.init_cache()
    prompts = _prompts()
    for slot, prompt in enumerate(prompts):
        host.ensure_pages(slot, int(prompt.size))
        ids, length = eng.pad_prompt(prompt)
        cache, _nl = eng.prefill(
            placed, cache, host.device_table()[slot], ids, length
        )
    lens = [int(p.size) for p in prompts]
    expect_pages = sum(-(-n // page) for n in lens)
    assert host.pool.pages_in_use == expect_pages
    total = sum(lens)
    assert expect_pages <= -(-total // page) + len(lens)  # +slack
    spec = eng.paged_spec
    contiguous_bytes = eng.num_slots * eng._slot_stripe_bytes
    assert host.pool.kv_cache_bytes == expect_pages * spec.page_bytes
    assert host.pool.kv_cache_bytes < contiguous_bytes
    # The SlotAllocator seam reports the contiguous layout's charge:
    # a max_len stripe per LIVE slot, position-independent.
    alloc = SlotAllocator(4, bytes_per_slot=eng._slot_stripe_bytes)
    for _ in prompts:
        alloc.alloc()
    assert alloc.kv_cache_bytes == 3 * eng._slot_stripe_bytes
    assert host.pool.kv_cache_bytes < alloc.kv_cache_bytes
    # Eviction returns pages, not a stripe.
    host.release(1)  # the 5-token slot: 2 pages
    assert host.pool.pages_in_use == expect_pages - 2


def test_undersized_pool_defers_admission_and_completes(dense):
    """Admission reserves each sequence's WHOLE page budget (prompt +
    max_new_tokens), so a pool too small for two concurrent sequences
    serves them one after the other — deferred, never crashed mid-run
    — and every greedy token still matches dense recompute. The
    exhaustion message itself is pinned at the PagePool level
    (test_page_pool_refcounts_and_reuse)."""
    params, next_logits = dense
    eng = ServingEngine(
        CFG, num_slots=2, max_len=16, prefill_len=8, page_size=4,
        num_pages=4, prefill_chunk=4,  # one 5+8-token sequence's worth
    )
    reqs = [
        Request(rid=i, prompt=_prompts()[1], max_new_tokens=8)
        for i in range(2)
    ]
    sched = eng.run(eng.place_params(params), reqs)
    assert len(sched.finished) == 2
    expect = _greedy(next_logits, _prompts()[1], 8)
    assert all(f.tokens == expect for f in sched.finished)
    rep = sched.latency_report()
    # The two sequences never overlapped: peak allocation is one
    # sequence's pages, bounded by the tiny pool.
    assert rep["paged"]["pages_in_use_peak"] <= 4
    # Only one slot was ever decode-active at a time.
    assert rep["mean_batch_occupancy"] == 1.0


# ------------------------------------------------------- prefix cache


def test_prefix_cache_hit_miss_cow(dense):
    """Hit / miss / copy-on-write in one trace: request A (miss)
    ingests and registers; B (identical prompt) skips its prefill via
    the full hit and COW-copies the shared partial page before its
    first write; C (shares only the first page) resumes ingestion at
    the divergent page. All three match dense greedy — sharing never
    perturbs anyone's logits."""
    params, next_logits = dense
    rng = np.random.RandomState(7)
    base = rng.randint(1, CFG.vocab_size, size=6).astype(np.int32)
    divergent = base.copy()
    divergent[4:] = (divergent[4:] % (CFG.vocab_size - 2)) + 1
    if np.array_equal(divergent, base):  # belt and braces
        divergent[4] = (divergent[4] % (CFG.vocab_size - 2)) + 1
    eng = ServingEngine(
        CFG, num_slots=1, max_len=16, prefill_len=8, page_size=4,
        prefill_chunk=4, prefix_cache=True,
    )
    placed = eng.place_params(params)
    # num_slots=1 serializes admissions, so B and C really see A's
    # registered pages.
    sched = eng.run(placed, [
        Request(rid="A", prompt=base, max_new_tokens=3),
        Request(rid="B", prompt=base, max_new_tokens=3),
        Request(rid="C", prompt=divergent, max_new_tokens=3),
    ])
    by_rid = {f.rid: f for f in sched.finished}
    assert by_rid["A"].tokens == _greedy(next_logits, base, 3)
    assert by_rid["B"].tokens == by_rid["A"].tokens
    assert by_rid["C"].tokens == _greedy(next_logits, divergent, 3)
    rep = sched.latency_report()
    # A missed; B full-hit (6/6 tokens); C partial-hit (page 0 = 4
    # tokens of 6).
    assert rep["prefix_cache"]["hits"] == 2
    assert rep["prefix_cache"]["misses"] == 1
    assert rep["prefix_cache"]["tokens_reused"] == 6 + 4
    # B wrote into A's registered partial page -> at least one COW
    # copy (A's own continuation writes trigger one too).
    assert rep["paged"]["cow_copies"] >= 1


def test_prefix_cache_survives_eviction_and_shares_pages(dense):
    """Cached pages outlive the slot that produced them (the cache
    holds its own pool reference), and a later identical prompt reuses
    the SAME page ids instead of re-allocating."""
    params, _ = dense
    prompt = _prompts()[1]  # 5 tokens: one full page + one partial
    eng = ServingEngine(
        CFG, num_slots=1, max_len=16, prefill_len=8, page_size=4,
        prefill_chunk=4, prefix_cache=True,
    )
    placed = eng.place_params(params)
    sched = eng.run(placed, [
        Request(rid=0, prompt=prompt, max_new_tokens=2),
        Request(rid=1, prompt=prompt, max_new_tokens=2),
    ])
    rep = sched.latency_report()
    assert rep["prefix_cache"]["hits"] == 1
    # Full page + partial page both reused: the whole 5-token prompt.
    assert rep["prefix_cache"]["tokens_reused"] == 5
    # Shared pages persisted after request 0's slot was recycled, so
    # the peak stays under two independent ingests' worth.
    assert rep["paged"]["pages_in_use_peak"] <= 4


# ----------------------------------------------- allocator/cache units


def test_page_pool_refcounts_and_reuse():
    pool = PagePool(3, page_bytes=10)
    a, b = pool.alloc(), pool.alloc()
    assert (a, b) == (0, 1)
    assert pool.pages_in_use == 2 and pool.kv_cache_bytes == 20
    pool.incref(a)
    assert not pool.decref(a)  # shared: still live
    assert pool.decref(a)      # last ref: freed
    assert pool.alloc() == 0   # lowest free, deterministic
    with pytest.raises(ValueError, match="not live"):
        pool.decref(2)
    pool.alloc()
    with pytest.raises(RuntimeError, match="exhausted"):
        pool.alloc()


def test_prefix_cache_match_register_evict():
    pool = PagePool(8, page_bytes=1)
    cache = PrefixCache(pool, page_size=4)
    prompt = np.arange(1, 7, dtype=np.int32)  # 6 tokens: 1 full + tail
    p0, p1 = pool.alloc(), pool.alloc()
    cache.register(prompt, [p0, p1])
    assert pool.refcount(p0) == 2 and pool.refcount(p1) == 2
    pages, covered = cache.match(prompt)
    assert pages == [p0, p1] and covered == 6
    assert cache.hits == 1 and cache.tokens_reused == 6
    # A prompt sharing only the first page matches just that page.
    other = prompt.copy()
    other[5] = 60
    pages2, covered2 = cache.match(other)
    assert pages2 == [p0] and covered2 == 4
    # Nothing matches a cold prompt.
    pages3, covered3 = cache.match(np.array([9, 9], np.int32))
    assert pages3 == [] and covered3 == 0 and cache.misses == 1
    # Release the borrower refs, put the CHAIN ROOT at the LRU front
    # (a full-prompt match touches root then partial, leaving the
    # root older), then evict: dropping the root must CASCADE to the
    # partial entry chained off it — a child whose parent is gone can
    # never match again, so it must not linger holding a pool ref.
    pages4, _ = cache.match(prompt)
    for pid in pages + pages2 + pages4:
        pool.decref(pid)
    pool.decref(p0)
    pool.decref(p1)  # the original owner's refs
    assert cache.evictable == 2
    assert cache.release_unused(1) == 2  # root evicts -> subtree goes
    assert pool.pages_in_use == 0 and len(cache) == 0
    assert cache.release_unused(1) == 0  # nothing left


def test_paged_spec_and_engine_guards(devices):
    spec = PagedKVCacheSpec(
        num_layers=2, num_slots=4, max_len=16, page_size=4,
        num_pages=16, num_heads=4, head_dim=4,
    )
    assert spec.pages_per_slot == 4
    with pytest.raises(ValueError, match="divide max_len"):
        PagedKVCacheSpec(
            num_layers=2, num_slots=4, max_len=16, page_size=5,
            num_pages=16, num_heads=4, head_dim=4,
        ).validate("replicated", None)
    with pytest.raises(ValueError, match="one full-length"):
        PagedKVCacheSpec(
            num_layers=2, num_slots=4, max_len=16, page_size=4,
            num_pages=2, num_heads=4, head_dim=4,
        ).validate("replicated", None)
    smesh = make_mesh(MeshSpec(data=1, seq=4), devices=devices[:4])
    with pytest.raises(ValueError, match="page_size"):
        PagedKVCacheSpec(
            num_layers=2, num_slots=4, max_len=16, page_size=2,
            num_pages=32, num_heads=4, head_dim=4,
        ).validate("sp", smesh)
    # Engine-level surface guards.
    with pytest.raises(ValueError, match="prefill_chunk"):
        ServingEngine(CFG, max_len=16, prefill_chunk=4)
    with pytest.raises(ValueError, match="prefix_cache"):
        ServingEngine(CFG, max_len=16, prefix_cache=True)
    with pytest.raises(ValueError, match="chunked prefill"):
        ServingEngine(
            CFG, max_len=16, page_size=4, prefix_cache=True
        )
    with pytest.raises(ValueError, match="sp"):
        ServingEngine(
            CFG, make_mesh(MeshSpec(data=1, seq=2),
                           devices=devices[:2]),
            layout="sp", max_len=16, prefill_len=8, page_size=4,
            prefill_chunk=4,
        )


# ------------------------------------------------------------ sampling


def test_sampling_greedy_default_bit_stable(dense):
    """temperature 0 == the pre-sampling argmax path, byte-identical,
    on both cache layouts."""
    params, next_logits = dense
    req = [Request(rid=0, prompt=_prompts()[0], max_new_tokens=4)]
    for kw in ({}, {"page_size": 4, "prefill_chunk": 4}):
        eng = ServingEngine(
            CFG, num_slots=2, max_len=16, prefill_len=8, **kw
        )
        placed = eng.place_params(params)
        plain = eng.run(placed, list(req))
        zero = eng.run(
            placed, list(req), sampling=SamplingConfig(temperature=0.0)
        )
        expect = _greedy(next_logits, _prompts()[0], 4)
        assert plain.finished[0].tokens == expect
        assert zero.finished[0].tokens == expect


def test_sampling_deterministic_per_slot_lane(dense):
    """A fixed (seed, trace) reproduces sampled tokens exactly, and
    different seeds diverge (the draws are really used)."""
    params, _ = dense
    eng = ServingEngine(CFG, num_slots=2, max_len=16, prefill_len=8)
    placed = eng.place_params(params)
    reqs = [
        Request(rid=i, prompt=p, max_new_tokens=6)
        for i, p in enumerate(_prompts())
    ]
    cfg = SamplingConfig(temperature=1.5, top_k=16, top_p=0.9, seed=3)
    a = eng.run(placed, list(reqs), sampling=cfg)
    b = eng.run(placed, list(reqs), sampling=cfg)
    toks = lambda s: [f.tokens for f in s.finished]  # noqa: E731
    assert toks(a) == toks(b)
    c = eng.run(
        placed, list(reqs),
        sampling=SamplingConfig(temperature=1.5, top_k=16, top_p=0.9,
                                seed=4),
    )
    assert toks(a) != toks(c)


def test_sampler_filters_and_validation():
    logits = np.array([0.0, 3.0, 2.0, 1.0, -1.0])
    # top_k=1 is greedy whatever the temperature.
    s = SlotSampler(SamplingConfig(temperature=5.0, top_k=1), 1)
    assert all(s.pick(logits, 0) == 1 for _ in range(8))
    # A tiny nucleus degenerates to greedy (argmax always survives).
    s = SlotSampler(SamplingConfig(temperature=5.0, top_p=1e-9), 1)
    assert all(s.pick(logits, 0) == 1 for _ in range(8))
    # top_k bounds the support even at high temperature.
    s = SlotSampler(SamplingConfig(temperature=50.0, top_k=3), 1)
    assert {s.pick(logits, 0) for _ in range(64)} <= {1, 2, 3}
    with pytest.raises(ValueError, match="temperature"):
        SamplingConfig(temperature=-1)
    with pytest.raises(ValueError, match="top_p"):
        SamplingConfig(temperature=1, top_p=0)
    with pytest.raises(ValueError, match="top_k"):
        SamplingConfig(temperature=1, top_k=-1)
    with pytest.raises(ValueError, match="greedy"):
        SamplingConfig(top_k=5)
