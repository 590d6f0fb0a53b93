"""Speculative decoding pins (ISSUE 18, `serving/speculative.py` /
`ServingEngine.verify_step` / `cli/serve.py` flags).

The load-bearing pins:

* **Greedy losslessness** — speculative greedy through `eng.run` is
  BIT-IDENTICAL to the non-speculative greedy engine for the
  replicated/TP/TP+collective-matmul layouts, with a random (almost
  always wrong) draft, under admission pressure (requests > slots, so
  slots recycle mid-run). Speculation is a scheduling change, never a
  token change.
* **Rollback returns pages** — a rejected suffix rolls back by
  `PagedCacheHost.truncate`; at page_size=2 a verify round writes
  past a page boundary, so rejections free pool pages (asserted from
  the pool bookkeeping through wrapped hosts on BOTH caches), and
  parity still holds.
* **Full accept** — a draft that IS the target's prefix (trailing
  residual blocks zeroed; GPT has no final LN) pins accept_rate == 1.0
  and mean_accept_len == k+1: acceptance measures draft quality, not
  machinery luck.
* **Sampled losslessness** — `rejection_verify`'s emitted-token
  marginal equals the target's filtered distribution for ANY draft
  (statistical pin over Philox lanes), plus the p==q all-accept and
  zero-overlap always-reject corners.
* **Guards** — engine- and CLI-level misconfigurations (non-paged
  draft, sp layout, k without pages, lockstep mismatches, draft flags
  without k, negative arrival knobs) fail loudly before any compile.

S=4 layout sweeps are `slow` (tier-1 budget) with named tier-1 twins,
per the budget-rebalance convention.
"""

import dataclasses

import numpy as np
import pytest

import jax

from distributed_model_parallel_tpu.models.gpt import GPTConfig
from distributed_model_parallel_tpu.observability import metrics
from distributed_model_parallel_tpu.runtime.mesh import (
    MeshSpec,
    make_mesh,
)
from distributed_model_parallel_tpu.serving.engine import ServingEngine
from distributed_model_parallel_tpu.serving.sampling import (
    SamplingConfig,
    SlotSampler,
)
from distributed_model_parallel_tpu.serving.scheduler import Request
from distributed_model_parallel_tpu.serving.speculative import (
    check_draft_engine,
    greedy_verify,
    rejection_verify,
)

CFG = GPTConfig(
    vocab_size=61, dim=16, num_layers=2, num_heads=4, ffn_dim=32,
    max_position=16, dropout_rate=0.0,
)
# A fresh-init 1-layer draft: wrong about almost every token (random
# weights disagree), so greedy parity is exercised through REJECTED
# suffixes, not lucky accepts.
DRAFT_CFG = dataclasses.replace(CFG, num_layers=1)

# page_size=2 with k=2: a verify round writes up to 3 positions —
# past a page boundary — so the shared run exercises rollback page
# frees, not just truncation-in-place. num_slots=4 divides both tp
# shard counts below, letting the layout tests reuse the shared
# fixture's draft engine and baseline tokens.
ENGINE_KW = dict(
    num_slots=4, max_len=16, prefill_len=8, page_size=2,
    prefill_chunk=4,
)


def _requests(n=6, seed=0, max_new=5):
    """Ragged prompts, more requests than slots: slots recycle
    mid-run (the admission/evict path under speculation)."""
    rng = np.random.RandomState(seed)
    return [
        Request(
            rid=i,
            prompt=rng.randint(
                1, CFG.vocab_size, size=int(rng.randint(2, 7))
            ).astype(np.int32),
            max_new_tokens=max_new,
        )
        for i in range(n)
    ]


def _spec_engines(k=2, mesh=None, layout_kw=None, **overrides):
    """Target (speculative_k=k) + plain twin + draft, all sharing the
    lockstep fields. The draft always runs replicated — proposals are
    host-side token ids, so the draft's layout is independent of the
    target's."""
    kw = dict(ENGINE_KW, **overrides)
    layout_kw = layout_kw or {}
    args = (CFG, mesh) if mesh is not None else (CFG,)
    target = ServingEngine(*args, speculative_k=k, **layout_kw, **kw)
    plain = ServingEngine(*args, **layout_kw, **kw)
    # The draft never shares target-side features (prefix_cache is
    # rejected by check_draft_engine) — only the lockstep fields.
    dkw = {key: v for key, v in kw.items() if key != "prefix_cache"}
    draft = ServingEngine(DRAFT_CFG, **dkw)
    return target, plain, draft


def _run_pair(target, plain, draft, reqs, *, sampling=None):
    """Run the same request set speculatively and plainly; return
    (spec tokens by rid, plain tokens by rid, spec scheduler)."""
    params = target.init_params(jax.random.PRNGKey(0))
    dparams = draft.init_params(jax.random.PRNGKey(7))
    sspec = target.run(
        params, [dataclasses.replace(r) for r in reqs],
        sampling, draft=draft, draft_params=dparams,
    )
    splain = plain.run(
        params, [dataclasses.replace(r) for r in reqs], sampling
    )
    assert len(sspec.finished) == len(reqs)
    assert len(splain.finished) == len(reqs)
    return (
        {f.rid: f.tokens for f in sspec.finished},
        {f.rid: f.tokens for f in splain.finished},
        sspec,
    )


def _wrap_truncate(eng):
    """Instrument the engine's future host: record how many pool pages
    each `truncate` call returns."""
    freed = []
    orig_new_host = eng.new_host

    def new_host():
        host = orig_new_host()
        orig_truncate = host.truncate

        def truncate(slot, n_tokens):
            before = host.pool.pages_in_use
            orig_truncate(slot, n_tokens)
            freed.append(before - host.pool.pages_in_use)

        host.truncate = truncate
        return host

    eng.new_host = new_host
    return freed


# --------------------------------------------- greedy parity (layouts)


@pytest.fixture(scope="module")
def spec_run_k2():
    """ONE shared replicated k=2 speculative-vs-plain run (compiles
    are the tier-1 cost driver): engines + params + both token maps +
    the speculative scheduler + a metrics snapshot + truncate-wrapped
    page-free ledgers, reused by the parity / rollback / metrics /
    sampled / full-accept / tp-layout tests below."""
    target, plain, draft = _spec_engines(k=2)
    target_freed = _wrap_truncate(target)
    draft_freed = _wrap_truncate(draft)
    params = target.init_params(jax.random.PRNGKey(0))
    dparams = draft.init_params(jax.random.PRNGKey(7))
    reqs = _requests()
    mx = metrics.enable()
    try:
        sched = target.run(
            params, [dataclasses.replace(r) for r in reqs],
            draft=draft, draft_params=dparams,
        )
        hist = mx.histogram("serve_spec_accept_len")
        snapshot = {
            "counters": mx.to_json()["counters"],
            "accept_len_count": hist.count if hist else 0,
        }
    finally:
        metrics.set_metrics(None)
    splain = plain.run(params, [dataclasses.replace(r) for r in reqs])
    return {
        "target": target, "plain": plain, "draft": draft,
        "params": params, "dparams": dparams, "reqs": reqs,
        "spec": {f.rid: f.tokens for f in sched.finished},
        "base": {f.rid: f.tokens for f in splain.finished},
        "sched": sched, "metrics": snapshot,
        "target_freed": list(target_freed),
        "draft_freed": list(draft_freed),
    }


def test_spec_greedy_matches_plain_replicated(spec_run_k2):
    """The tentpole pin: speculative greedy == plain greedy,
    bit-identical, with slot recycling (5 requests over 2 slots) and a
    random draft (rejections dominate)."""
    r = spec_run_k2
    assert len(r["spec"]) == len(r["reqs"])
    assert r["spec"] == r["base"]
    rep = r["sched"].latency_report()
    assert rep["speculative"]["k"] == 2
    # Every token except each request's prefill-produced first one
    # came out of a verify round (no slot neared max_len, so the
    # degrade-to-plain-decode path never fired here).
    assert rep["speculative"]["spec_tokens"] == sum(
        len(t) for t in r["spec"].values()
    ) - len(r["spec"])


@pytest.mark.slow
def test_spec_greedy_matches_plain_replicated_k4():
    """k=4 parity: deeper lookahead, same acceptance rule. `slow`
    (tier-1 budget); tier-1 twin:
    test_spec_greedy_matches_plain_replicated (k=2 on the same
    propose/verify/accept path — only the compiled verify width
    changes)."""
    target, plain, draft = _spec_engines(k=4)
    spec, base, _ = _run_pair(target, plain, draft, _requests())
    assert spec == base


def _run_spec_tp(s, devices, spec_run_k2, *, cm=False):
    """TP speculative target reusing the shared fixture's compiled
    draft, dense params (via `place_params`, the documented drop-in
    path — init values are layout-independent) and replicated plain
    baseline (tp plain == replicated plain is pinned by
    test_serving_paged's layout parity)."""
    mesh = make_mesh(MeshSpec(data=1, model=s), devices=devices[:s])
    target = ServingEngine(
        CFG, mesh, layout="tp", collective_matmul=cm,
        speculative_k=2, **ENGINE_KW,
    )
    sched = target.run(
        target.place_params(spec_run_k2["params"]),
        [dataclasses.replace(r) for r in spec_run_k2["reqs"]],
        draft=spec_run_k2["draft"],
        draft_params=spec_run_k2["dparams"],
    )
    assert {f.rid: f.tokens for f in sched.finished} \
        == spec_run_k2["base"]


@pytest.mark.parametrize("s", [
    2, pytest.param(4, marks=pytest.mark.slow),
])
def test_spec_greedy_matches_plain_tp(s, devices, spec_run_k2):
    """TP target + replicated draft: verify rides the tp chunk-shaped
    paged path; proposals cross as host token ids. S=4 is `slow`;
    tier-1 twin: the S=2 case on the same code path."""
    _run_spec_tp(s, devices, spec_run_k2)


@pytest.mark.parametrize("s", [
    2, pytest.param(4, marks=pytest.mark.slow),
])
def test_spec_greedy_matches_plain_tp_collective_matmul(
    s, devices, spec_run_k2
):
    """Opted-in decode rings under the verify step (the
    serve/S2/pg8/cm/spec2 hlolint combo's runtime twin). S=4 is
    `slow`; tier-1 twin: the S=2 case."""
    _run_spec_tp(s, devices, spec_run_k2, cm=True)


# ------------------------------------------------ rollback frees pages


def test_rejected_suffix_rollback_returns_pages(spec_run_k2):
    """page_size=2 with k=2: a verify round writes up to 3 positions —
    past a page boundary — so a first-position rejection leaves a
    wholly-stale page that `truncate` must return to the pool. Pinned
    through the pool bookkeeping on BOTH hosts of the shared run
    (whose parity the tentpole test asserts)."""
    rep = spec_run_k2["sched"].latency_report()["speculative"]
    # The random draft must actually have been rejected somewhere…
    assert rep["accept_rate"] < 1.0
    # …and at least one rollback returned whole pages on each cache.
    freed_t = spec_run_k2["target_freed"]
    freed_d = spec_run_k2["draft_freed"]
    assert freed_t and max(freed_t) > 0
    assert freed_d and max(freed_d) > 0


# --------------------------------------------- exact-prefix full accept


def test_exact_prefix_draft_full_accept(spec_run_k2):
    """A 1-layer draft holding the target's stem + block 0 + head,
    against a 2-layer target whose block 1 is identity (residual
    branch outputs zeroed; GPT has no final LN): the draft's logits
    ARE the target's, so every proposal survives — accept_rate == 1.0,
    mean_accept_len == k+1, and the emitted tokens still match plain
    greedy. Reuses the shared trio's compiled engines with SURGICAL
    params."""
    k = 2
    target = spec_run_k2["target"]
    plain = spec_run_k2["plain"]
    draft = spec_run_k2["draft"]
    # tree.map rebuilds the dict containers, so the surgery below
    # never touches the fixture's own params.
    params = jax.tree.map(lambda x: x, spec_run_k2["params"])
    for branch in ("attn", "ffn"):
        out = params["blocks"]["1"][branch]["out"]
        out["w"] = out["w"] * 0
        out["b"] = out["b"] * 0
    dparams = jax.tree.map(lambda x: x, spec_run_k2["dparams"])
    dparams["stem"] = params["stem"]
    dparams["blocks"]["0"] = params["blocks"]["0"]
    dparams["head"] = params["head"]
    reqs = spec_run_k2["reqs"]
    sspec = target.run(
        params, [dataclasses.replace(r) for r in reqs],
        draft=draft, draft_params=dparams,
    )
    splain = plain.run(params, [dataclasses.replace(r) for r in reqs])
    assert {f.rid: f.tokens for f in sspec.finished} == {
        f.rid: f.tokens for f in splain.finished
    }
    rep = sspec.latency_report()["speculative"]
    assert rep["accept_rate"] == 1.0
    assert rep["mean_accept_len"] == k + 1


# -------------------------------------------------- sampled (lossless)


def test_spec_sampled_runs_lossless_smoke(spec_run_k2):
    """Sampled speculative decoding completes the request set and
    emits the right token COUNTS (per-token values are random but the
    budget/eviction bookkeeping must hold under rejection draws).
    Reuses the shared trio's compiled engines — sampling is host-side
    over already-fetched logits, so the compiled steps are the same."""
    target = spec_run_k2["target"]
    draft = spec_run_k2["draft"]
    reqs = _requests()
    sched = target.run(
        spec_run_k2["params"], reqs,
        SamplingConfig(temperature=1.0, top_k=8, seed=3),
        draft=draft, draft_params=spec_run_k2["dparams"],
    )
    assert len(sched.finished) == len(reqs)
    for f in sched.finished:
        want = next(r.max_new_tokens for r in reqs if r.rid == f.rid)
        assert len(f.tokens) == want
        assert all(0 <= t < CFG.vocab_size for t in f.tokens)


def test_rejection_verify_marginal_is_target_distribution():
    """The losslessness theorem, statistically: over many Philox
    lanes, the FIRST emitted token's empirical marginal equals the
    target's filtered distribution p — even though the proposals come
    from a very different (peaked) draft q. Any accept/reject
    bookkeeping error shows up as mass displaced toward q's mode."""
    vocab, k, trials = 5, 2, 4000
    rng = np.random.RandomState(0)
    rows = rng.randn(k + 1, vocab)  # target logits per position
    q = np.full(vocab, 0.02)
    q[3] = 1.0 - 0.02 * (vocab - 1)  # draft: peaked on token 3
    counts = np.zeros(vocab)
    for t in range(trials):
        sampler = SlotSampler(
            SamplingConfig(temperature=1.0, seed=t), 1
        )
        d = sampler.sample_dist(q, 0)  # proposal drawn FROM q
        emitted = rejection_verify(
            rows, np.asarray([d, d], np.int64), [q, q], sampler, 0
        )
        counts[emitted[0]] += 1
    p = SlotSampler(
        SamplingConfig(temperature=1.0, seed=0), 1
    ).dist(rows[0])
    np.testing.assert_allclose(counts / trials, p, atol=0.03)


def test_rejection_verify_identical_dists_accept_all():
    """q == p accepts every proposal with probability 1 (the coin is
    u*q[d] <= p[d]); the round ends with a bonus draw from p."""
    vocab, k = 7, 3
    rng = np.random.RandomState(1)
    rows = rng.randn(k + 1, vocab)
    sampler = SlotSampler(SamplingConfig(temperature=1.0, seed=5), 1)
    dists = [sampler.dist(rows[i]) for i in range(k)]
    proposals = np.asarray(
        [sampler.sample_dist(dists[i], 0) for i in range(k)], np.int64
    )
    emitted = rejection_verify(rows, proposals, dists, sampler, 0)
    assert emitted[:k] == list(proposals)
    assert len(emitted) == k + 1
    assert 0 <= emitted[k] < vocab


def test_rejection_verify_zero_overlap_always_corrects():
    """p puts ZERO mass on the proposal -> the coin cannot accept
    (u*q[d] <= 0 has probability 0 for u in (0,1)); the correction
    comes from the residual normalize(max(p-q, 0)), which also
    excludes the proposal."""
    vocab = 4
    p = np.asarray([0.5, 0.5, 0.0, 0.0])
    q = np.asarray([0.0, 0.0, 1.0, 0.0])
    rows = np.log(np.maximum(p, 1e-12))[None]  # dist(rows[0]) ~= p
    for seed in range(16):
        sampler = SlotSampler(
            SamplingConfig(temperature=1.0, seed=seed), 1
        )
        emitted = rejection_verify(
            rows, np.asarray([2], np.int64), [q], sampler, 0
        )
        assert len(emitted) == 1  # suffix rejected at position 0
        assert emitted[0] in (0, 1)  # drawn from the residual = p


def test_rejection_verify_deterministic_per_seed():
    """Same lane seed -> byte-identical emission (the reproducibility
    contract sampling.py pins, extended through the rejection rule)."""
    vocab, k = 6, 2
    rng = np.random.RandomState(2)
    rows = rng.randn(k + 1, vocab)
    q = np.full(vocab, 1.0 / vocab)
    runs = []
    for _ in range(2):
        sampler = SlotSampler(
            SamplingConfig(temperature=1.0, seed=11), 1
        )
        runs.append(rejection_verify(
            rows, np.asarray([1, 4], np.int64), [q, q], sampler, 0
        ))
    assert runs[0] == runs[1]


# ------------------------------------------------- greedy_verify units


def test_greedy_verify_emits_longest_matching_prefix():
    vocab = 8
    rows = np.zeros((3, vocab))
    rows[0, 2] = rows[1, 5] = rows[2, 1] = 1.0  # target argmaxes
    # Full match -> k accepts + bonus (the row-k argmax).
    assert greedy_verify(rows, np.asarray([2, 5])) == [2, 5, 1]
    # Mismatch at position 1 -> the target's own token corrects and
    # the suffix is dropped.
    assert greedy_verify(rows, np.asarray([2, 3])) == [2, 5]
    assert greedy_verify(rows, np.asarray([7, 5])) == [2]


# ---------------------------------------------- prefix-cache interplay


@pytest.mark.slow
def test_spec_with_target_prefix_cache_hits_and_parity():
    """The prefix cache stays a TARGET-side feature under speculation:
    a repeated prompt hits (counter increments), the draft ingests
    every prompt itself, and the emitted tokens still match plain
    greedy. `slow` (tier-1 budget); tier-1 twins:
    test_spec_greedy_matches_plain_replicated (speculative parity on
    the same engines) + test_serving_paged's prefix-cache hit pins
    (the cache itself, non-speculative)."""
    # 2 slots so the later identical prompts arrive AFTER the first
    # wave's prefill has populated the cache (4 slots would admit all
    # four at once and every lookup would miss).
    target, plain, draft = _spec_engines(
        k=2, prefix_cache=True, num_slots=2
    )
    prompt = np.arange(1, 7, dtype=np.int32)  # covers whole pages
    reqs = [
        Request(rid=i, prompt=prompt.copy(), max_new_tokens=4)
        for i in range(4)
    ]
    mx = metrics.enable()
    try:
        spec, base, _ = _run_pair(target, plain, draft, reqs)
        assert spec == base
        hits = mx.to_json()["counters"]["serve_prefix_hits_total"]
        assert hits > 0
    finally:
        metrics.set_metrics(None)


# -------------------------------------------------------- observability


def test_spec_metrics_histogram_and_counter(spec_run_k2):
    """serve_spec_accept_len observes once per verify round and
    serve_spec_tokens_total counts every speculative-round token —
    both must reconcile with the scheduler's own report (snapshot
    captured by the shared fixture's metered run)."""
    rep = spec_run_k2["sched"].latency_report()["speculative"]
    snap = spec_run_k2["metrics"]
    assert snap["accept_len_count"] == rep["verify_rounds"]
    assert snap["counters"]["serve_spec_tokens_total"] \
        == rep["spec_tokens"]


# --------------------------------------------------------------- guards


def test_check_draft_engine_guards():
    target = ServingEngine(CFG, speculative_k=2, **ENGINE_KW)
    with pytest.raises(ValueError, match="PAGED draft"):
        check_draft_engine(
            target,
            ServingEngine(DRAFT_CFG, num_slots=2, max_len=16,
                          prefill_len=8),
        )
    with pytest.raises(ValueError, match="non-speculative"):
        check_draft_engine(
            target,
            ServingEngine(DRAFT_CFG, speculative_k=2, **ENGINE_KW),
        )
    with pytest.raises(ValueError, match="target-side"):
        check_draft_engine(
            target,
            ServingEngine(DRAFT_CFG, prefix_cache=True, **ENGINE_KW),
        )
    with pytest.raises(ValueError, match="lockstep"):
        check_draft_engine(
            target,
            ServingEngine(DRAFT_CFG, **dict(ENGINE_KW, num_slots=2)),
        )


def test_engine_speculative_guards(devices):
    with pytest.raises(ValueError, match=r"\[1, 8\]"):
        ServingEngine(CFG, **dict(ENGINE_KW, speculative_k=9))
    with pytest.raises(ValueError, match="BLOCK TABLE"):
        ServingEngine(
            CFG, num_slots=2, max_len=16, prefill_len=8,
            speculative_k=2,
        )
    with pytest.raises(ValueError, match="sp "):
        ServingEngine(
            CFG,
            make_mesh(MeshSpec(data=1, seq=2), devices=devices[:2]),
            layout="sp", num_slots=2, max_len=16, prefill_len=8,
            page_size=4, speculative_k=2,
        )
    with pytest.raises(ValueError, match="leaves no"):
        ServingEngine(
            CFG, num_slots=2, max_len=8, prefill_len=4, page_size=4,
            speculative_k=8,
        )
    # run()-time pairing: k without a draft, and a draft without k.
    target, plain, draft = _spec_engines(k=2)
    params = target.init_params(jax.random.PRNGKey(0))
    with pytest.raises(ValueError, match="needs a proposer"):
        target.run(params, _requests(n=1))
    with pytest.raises(ValueError, match="speculative_k > 0 on the"):
        plain.run(
            params, _requests(n=1), draft=draft,
            draft_params=draft.init_params(jax.random.PRNGKey(7)),
        )


def test_serve_cli_speculative_flag_guards():
    """The CLI backstop (cli/common.check_serving_args): speculative
    and arrival misconfigurations die with flag vocabulary BEFORE any
    mesh or engine exists."""
    from distributed_model_parallel_tpu.cli import serve

    with pytest.raises(SystemExit):  # k out of range
        serve.main(["--speculative-k", "9"])
    with pytest.raises(SystemExit):  # rollback needs pages
        serve.main(["--speculative-k", "2"])
    with pytest.raises(SystemExit):  # no verify path under sp
        serve.main(["--layout", "sp", "--seq-shards", "2",
                    "--page-size", "16", "--speculative-k", "2"])
    with pytest.raises(SystemExit):  # k+1 must fit under max-len
        serve.main(["--page-size", "4", "--max-len", "8",
                    "--speculative-k", "8"])
    with pytest.raises(SystemExit):  # draft flags need k >= 1
        serve.main(["--speculative-draft-layers", "2"])
    with pytest.raises(SystemExit):  # checkpoint XOR fresh-init layers
        serve.main(["--page-size", "16", "--speculative-k", "2",
                    "--speculative-draft", "/tmp/nowhere",
                    "--speculative-draft-layers", "2"])
    with pytest.raises(SystemExit):  # negative draft depth
        serve.main(["--page-size", "16", "--speculative-k", "2",
                    "--speculative-draft-layers", "-1"])
    with pytest.raises(SystemExit):  # offered load can't be negative
        serve.main(["--arrival-rate", "-1"])
    with pytest.raises(SystemExit):  # a burst is >= 1 requests
        serve.main(["--arrival-rate", "2", "--arrival-burst", "0"])
    with pytest.raises(SystemExit):  # burst needs a rate
        serve.main(["--arrival-burst", "4"])


def test_synthetic_arrivals_deterministic_and_bursty():
    from distributed_model_parallel_tpu.cli import serve

    args = serve.build_parser().parse_args(
        ["--arrival-rate", "10", "--arrival-burst", "3",
         "--num-requests", "8", "--seed", "5"]
    )
    a = serve.synthetic_arrivals(args)
    b = serve.synthetic_arrivals(args)
    np.testing.assert_array_equal(a, b)  # deterministic in --seed
    assert a.shape == (8,)
    assert np.all(np.diff(a) >= 0)  # submission order
    # Burst structure: requests 0-2 share an event time, 3-5 the next.
    assert a[0] == a[1] == a[2]
    assert a[3] == a[4] == a[5]
    assert a[3] > a[0]
    # Rate 0 is the legacy all-at-t=0 trace.
    args0 = serve.build_parser().parse_args(["--num-requests", "4"])
    np.testing.assert_array_equal(
        serve.synthetic_arrivals(args0), np.zeros(4)
    )
