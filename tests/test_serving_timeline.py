"""Every emitted token carries its time, and every pass of the paged
loop its row (ISSUE 38): `Scheduler.emit`, `Scheduler.passes` and
their reduction, `Scheduler.timeline`.

Toy engines on the CPU under a tracer whose injected clock counts its
own reads (every reading is one tick later than the last), so that a
time is a count of what the loop did and equalities are exact. Each
case runs over the loops it applies to: `paged` (chunked prefill, the
prefix cache), `unchunked` (paged, one whole prefill a prompt),
`contiguous` and `speculative`; the last two record no passes.
"""

import collections
import gc

import numpy as np
import pytest

import jax

from distributed_model_parallel_tpu.models.gpt import GPTConfig
from distributed_model_parallel_tpu.observability import trace
from distributed_model_parallel_tpu.serving.engine import ServingEngine
from distributed_model_parallel_tpu.serving.scheduler import (
    LONGEST_PASSES,
    Request,
    Scheduler,
)

CFG = GPTConfig(
    vocab_size=61, dim=16, num_layers=2, num_heads=4, ffn_dim=32,
    max_position=16, dropout_rate=0.0,
)
PAGED = dict(num_slots=2, max_len=16, prefill_len=8, page_size=4)
LOOPS = ("paged", "unchunked", "contiguous", "speculative")
WITH_PASSES = ("paged", "unchunked")
TOKEN_KEYS = {
    "tokens", "gaps", "itl_ms", "itl_max_at_s", "itl_max_rid",
    "decode_span_s", "first_token_ms", "queued_ms",
}
PASS_KEYS = {
    "passes", "pass_ms", "wall_s", "stretch_s", "host_s",
    "longest_passes", "slowest_passes", "gc",
}


class CountingClock:
    """1, 2, 3, ...: every reading is one tick later than the last."""

    def __init__(self):
        self.t = 0.0

    def __call__(self) -> float:
        self.t += 1.0
        return self.t


def prompt(seed, n):
    return np.random.RandomState(seed).randint(
        1, CFG.vocab_size, size=n
    ).astype(np.int32)


def requests():
    """More requests than slots, prompts of one and two chunks, none
    shared (so every first token comes from a prefill), budgets that
    end in different passes and, in the paged loop, two that end in the
    same step."""
    return [
        Request(rid="a", prompt=prompt(1, 7), max_new_tokens=4),
        Request(rid="b", prompt=prompt(2, 3), max_new_tokens=5),
        Request(rid="c", prompt=prompt(3, 5), max_new_tokens=3),
        Request(rid="d", prompt=prompt(4, 2), max_new_tokens=1),
        Request(rid="e", prompt=prompt(5, 6), max_new_tokens=4),
    ]


@pytest.fixture(scope="module")
def engines():
    """loop -> (engine, params, keyword arguments of `run`)."""
    key = jax.random.PRNGKey(0)
    paged = ServingEngine(CFG, prefill_chunk=4, prefix_cache=True, **PAGED)
    unchunked = ServingEngine(CFG, **PAGED)
    contiguous = ServingEngine(CFG, num_slots=2, max_len=16, prefill_len=8)
    target = ServingEngine(CFG, prefill_chunk=4, speculative_k=2, **PAGED)
    draft = ServingEngine(CFG, prefill_chunk=4, **PAGED)
    params = paged.init_params(key)
    return {
        "paged": (paged, params, {}),
        "unchunked": (unchunked, params, {}),
        "contiguous": (contiguous, params, {}),
        "speculative": (target, params, {
            "draft": draft,
            "draft_params": draft.init_params(jax.random.PRNGKey(7)),
        }),
    }


def drain(engines, loop, reqs=None, enabled=False):
    """(scheduler, tracer) of one run under a fresh counting clock."""
    engine, params, kw = engines[loop]
    tracer = trace.Tracer(clock=CountingClock(), enabled=enabled)
    trace.set_tracer(tracer)
    try:
        sched = engine.run(params, reqs or requests(), **kw)
    finally:
        trace.set_tracer(None)
    return sched, tracer


@pytest.fixture(scope="module")
def drained(engines):
    """loop -> scheduler of the untraced run of `requests()`."""
    return {loop: drain(engines, loop)[0] for loop in LOOPS}


def stamps(f):
    """A finished request's token times on the clock itself."""
    return [f.t_submit + t for t in f.token_t]


# ------------------------------------------------------------ a token


def test_emit_stamps_every_token_and_keeps_the_step_duration_apart():
    trace.set_tracer(trace.Tracer(clock=CountingClock()))
    try:
        sched = Scheduler(num_slots=1, max_len=8)
        sched.submit(Request(rid="r", prompt=np.array([1, 2]),
                             max_new_tokens=3))        # t_submit 2
        seq = sched.admit()                            # t_admit 3
        sched.emit(seq, 7, 10.0, step_s=4.0)   # the first: no step kept
        sched.emit(seq, 8, 12.5, step_s=1.5)
        sched.emit(seq, 9, 12.5)               # a stamp and no duration
        fin = sched.finish(seq.slot)                   # evicted at 4
    finally:
        trace.set_tracer(None)
    assert seq.t_first_token == 10.0 and seq.generated == [7, 8, 9]
    assert fin.token_t == [8.0, 10.5, 10.5] and fin.decode_s == [1.5]
    assert fin.prefill_s == fin.token_t[0] and fin.queued_s == 1.0
    assert fin.t_submit == 2.0 == sched.t_origin
    t = sched.timeline()
    assert (t["tokens"], t["gaps"]) == (3, 2)
    assert t["itl_ms"]["max"] == 2500.0 and t["itl_ms"]["mean"] == 1250.0
    assert (t["itl_max_at_s"], t["itl_max_rid"]) == (10.5, "'r'")
    assert t["first_token_ms"]["p50"] == 7000.0
    assert t["queued_ms"]["max"] == 1000.0
    assert "passes" not in t and "gc" not in t


@pytest.mark.parametrize("loop", LOOPS)
def test_every_token_has_a_stamp_and_the_first_is_the_prefill_leg(
        drained, loop):
    sched = drained[loop]
    assert len(sched.finished) == len(requests())
    for f in sched.finished:
        assert len(f.token_t) == len(f.tokens) >= 1
        assert f.token_t[0] == f.prefill_s  # exactly: one reading
        assert f.token_t == sorted(f.token_t)
        assert 0.0 < f.queued_s < f.prefill_s
        # the other list is the step's duration, once a later token
        assert len(f.decode_s) == len(f.tokens) - 1


@pytest.mark.parametrize("loop", LOOPS)
def test_the_last_stamp_is_the_eviction_less_what_the_pass_did_after_it(
        drained, loop):
    """Untraced, the only readings between a step's stamp and a
    request's eviction are the evictions of that step: the k-th request
    a step finishes leaves k ticks after the stamp. (The speculative
    loop stamps a prompt's first token inside its ingest, whose closing
    reading then lies before the eviction of a request that asked for
    one token.)"""
    evicted = collections.Counter()
    for f in drained[loop].finished:
        last = stamps(f)[-1]
        evicted[last] += 1
        after = evicted[last] + (loop == "speculative" and len(f.tokens) == 1)
        assert f.total_s - f.prefill_s == (
            f.token_t[-1] - f.token_t[0] + after
        )
    if loop == "paged":
        assert max(evicted.values()) == 2  # a and b leave one step


@pytest.mark.parametrize("loop", LOOPS)
def test_slots_that_decode_in_one_step_share_its_stamp(drained, loop):
    """No prompt is shared, so every token after a request's first left
    a batch step: the requests on each such stamp, in time's order, are
    the occupancy of the steps, one by one."""
    sched = drained[loop]
    on_stamp = collections.defaultdict(set)
    for f in sched.finished:
        for t in stamps(f)[1:]:
            on_stamp[t].add(f.rid)
    assert [len(on_stamp[t]) for t in sorted(on_stamp)] == (
        sched.step_occupancy
    )
    assert max(sched.step_occupancy) == 2
    # and no first token shares a stamp with anything
    firsts = [stamps(f)[0] for f in sched.finished]
    assert len(set(firsts)) == len(firsts) and not set(firsts) & set(on_stamp)


@pytest.mark.parametrize("loop", LOOPS)
def test_the_pooled_gaps_add_up_to_the_requests_decode_spans(drained, loop):
    sched = drained[loop]
    t = sched.latency_report()["timeline"]
    fins = sched.finished
    assert t["tokens"] == sum(len(f.tokens) for f in fins)
    assert t["gaps"] == t["tokens"] - len(fins)
    spans = sum(f.token_t[-1] - f.token_t[0] for f in fins)
    assert t["decode_span_s"] == spans
    assert t["itl_ms"]["mean"] * t["gaps"] == pytest.approx(1e3 * spans)
    gaps = sorted(
        b - a for f in fins for a, b in zip(f.token_t, f.token_t[1:])
    )
    assert t["itl_ms"]["max"] == 1e3 * gaps[-1]
    assert t["itl_ms"]["p50"] == 1e3 * float(np.percentile(gaps, 50))
    assert t["itl_ms"]["p99"] == pytest.approx(
        1e3 * float(np.percentile(gaps, 99))
    )
    # the longest gap: whose it was, and when its second token came
    longest = [
        (f.rid, f.t_submit + b) for f in fins
        for a, b in zip(f.token_t, f.token_t[1:]) if b - a == gaps[-1]
    ]
    assert (eval(t["itl_max_rid"]), sched.t_origin + t["itl_max_at_s"]) in (
        longest
    )
    assert t["first_token_ms"]["max"] == 1e3 * max(
        f.prefill_s - f.queued_s for f in fins
    )
    assert t["queued_ms"]["max"] == 1e3 * max(f.queued_s for f in fins)


@pytest.mark.parametrize("loop", LOOPS)
def test_the_report_carries_the_block_under_one_key(drained, loop):
    sched = drained[loop]
    report = sched.latency_report()
    has_passes = loop in WITH_PASSES
    assert set(report["timeline"]) == TOKEN_KEYS | (
        PASS_KEYS if has_passes else set()
    )
    assert bool(sched.passes) == has_passes
    if has_passes:
        assert report["timeline"] is sched.paged_stats["timeline"]
    # once: the page accounting beside it holds counts alone
    assert "timeline" not in report.get("paged", {})


# ------------------------------------------------------------- a pass


@pytest.mark.parametrize("loop", WITH_PASSES)
def test_pass_walls_tile_the_loop_and_divide_into_stretches_and_host(
        drained, loop):
    sched = drained[loop]
    rows, t = sched.passes, sched.paged_stats["timeline"]
    assert all(a.end == b.start for a, b in zip(rows, rows[1:]))
    walls = [r.end - r.start for r in rows]
    assert sum(walls) == rows[-1].end - rows[0].start == t["wall_s"]
    assert t["passes"] == len(rows) > 3
    stretches = t["stretch_s"]["prefill_chunk"] + t["stretch_s"]["decode_step"]
    assert t["host_s"] + stretches == t["wall_s"]
    assert t["stretch_s"]["prefill_chunk"] == sum(r.prefill_s for r in rows)
    assert t["stretch_s"]["decode_step"] == sum(r.decode_s for r in rows)
    assert t["host_s"] > 0 and all(
        r.prefill_s + r.decode_s < w for r, w in zip(rows, walls)
    )
    # what a row counts: the launches, the step's slots, the queue
    assert sum(r.chunks for r in rows) == {"paged": 8, "unchunked": 5}[loop]
    assert [r.decoding for r in rows if r.decoding] == sched.step_occupancy
    assert rows[0].waiting == 5 and rows[-1].waiting == 0
    assert all((r.decode_s > 0) == (r.decoding > 0) for r in rows)
    assert t["pass_ms"]["max"] == 1e3 * max(walls)


@pytest.mark.parametrize("loop", WITH_PASSES)
@pytest.mark.parametrize("ranked", ["longest_passes", "slowest_passes"])
def test_the_listed_passes_are_rows_named_by_their_index(
        drained, loop, ranked):
    """Five by wall, five by wall over what the pass launched (its
    chunks and its decode step)."""
    sched = drained[loop]
    rows, t = sched.passes, sched.paged_stats["timeline"]
    by = {"longest_passes": "wall_ms", "slowest_passes": "per_launch_ms"}[
        ranked
    ]

    def row(n):
        r = rows[n]
        wall = r.end - r.start
        return {
            "index": n, "at_s": r.start - sched.t_origin,
            "wall_ms": 1e3 * wall,
            "per_launch_ms": round(
                1e3 * wall / (r.chunks + (r.decoding > 0)), 3
            ),
            "chunks": r.chunks, "decoding": r.decoding,
            "waiting": r.waiting,
            "host_ms": 1e3 * (wall - r.prefill_s - r.decode_s),
        }

    listed = t[ranked]
    assert len(listed) == min(LONGEST_PASSES, len(rows))
    assert [p[by] for p in listed] == sorted(
        (row(n)[by] for n in range(len(rows))), reverse=True
    )[:len(listed)]
    assert all(p == row(p["index"]) for p in listed)


def test_a_stall_in_a_short_pass_tops_the_second_list_alone(
        engines, monkeypatch):
    """A drain opens with every slot ingesting: those passes are the
    longest of any run. A decode step that stalls later, in a pass that
    launched nothing else, is not among the longest; by what it held it
    is the slowest."""
    engine, params, _ = engines["paged"]

    class Clock(CountingClock):
        stall = 0.0

        def __call__(self):
            self.t += self.stall
            self.stall = 0.0
            return super().__call__()

    clock = Clock()
    step, calls = engine.decode_step, []

    def stalling_step(*args):
        calls.append(1)
        if len(calls) == 6:
            clock.stall = 3.0
        return step(*args)

    monkeypatch.setattr(engine, "decode_step", stalling_step)
    trace.set_tracer(trace.Tracer(clock=clock))
    try:
        sched = engine.run(params, [
            Request(rid=i, prompt=prompt(i, 7), max_new_tokens=8)
            for i in range(2)
        ] + [Request(rid=2, prompt=prompt(2, 7), max_new_tokens=2)])
    finally:
        trace.set_tracer(None)
    t = sched.paged_stats["timeline"]
    stalled = next(n for n, r in enumerate(sched.passes) if r.decode_s > 3)
    assert sched.passes[stalled].chunks == 0
    assert t["slowest_passes"][0]["index"] == stalled
    assert t["longest_passes"][0]["index"] != stalled
    assert t["longest_passes"][0]["chunks"] == 2


def test_a_prompt_of_two_chunks_delays_the_other_slots_tokens(engines):
    """The guide's "a long prompt in a step delays token generation for
    the rest of the batch": while `long` ingests, `short`'s gaps hold
    the chunk beside them, which the step's own duration (`decode_s`,
    what `decode_p50_ms` reads) does not."""
    sched, _ = drain(engines, "paged", [
        Request(rid="short", prompt=prompt(1, 3), max_new_tokens=6),
        Request(rid="long", prompt=prompt(2, 7), max_new_tokens=4),
    ])
    rows = sched.passes
    short = next(f for f in sched.finished if f.rid == "short")
    # pass 0: short's one chunk (its first token), long's first chunk,
    # then the step that gives short its second token
    assert (rows[0].chunks, rows[0].decoding) == (2, 1)
    assert (rows[1].chunks, rows[1].decoding) == (1, 2)
    at = stamps(short)
    assert rows[0].start < at[0] < at[1] < rows[0].end < at[2] < rows[1].end
    gaps = np.diff(short.token_t)
    # ... so its first gap holds long's chunk and the step
    chunk = rows[0].prefill_s / 2
    assert gaps[0] > chunk + rows[0].decode_s > short.decode_s[0]
    # pass 1 evicts nobody, so from step to step is the pass's wall
    assert gaps[1] == rows[1].end - rows[1].start
    assert gaps[1] >= rows[1].prefill_s + rows[1].decode_s > short.decode_s[1]
    # once long decodes beside it, a gap is a pass with no chunk in it
    assert rows[2].chunks == 0 and gaps[2] < gaps[1]


def test_row_n_and_the_span_engine_iter_index_n_are_one_interval(engines):
    sched, tracer = drain(engines, "paged", enabled=True)
    origin = 1.0  # the tracer's first reading of its clock
    events = [e for e in tracer.to_chrome()["traceEvents"]
              if e["ph"] == "X" and e["tid"] < 1000]
    iters = {e["args"]["index"]: e for e in events
             if e["name"] == "engine_iter"}
    assert sorted(iters) == list(range(len(sched.passes)))
    for n, row in enumerate(sched.passes):
        e = iters[n]
        assert e["ts"] == 1e6 * (row.start - origin)
        assert e["ts"] + e["dur"] == 1e6 * (row.end - origin)
        assert e["args"]["waiting"] == row.waiting
        inside = [x for x in events if x.get("args", {}).get("iter") == n]
        assert all(e["ts"] < x["ts"] and x["ts"] + x["dur"] < e["ts"] + e["dur"]
                   for x in inside)
        chunks = [x for x in inside if x["name"] == "prefill_chunk"]
        steps = [x for x in inside if x["name"] == "decode_step"]
        assert len(chunks) == row.chunks and len(steps) == bool(row.decoding)
        assert len(inside) == len(chunks) + len(steps)
        # a stretch is its span and the two readings around it
        assert row.prefill_s == sum(x["dur"] / 1e6 + 2 for x in chunks)
        assert row.decode_s == sum(x["dur"] / 1e6 + 2 for x in steps)
        assert all(x["args"]["active"] == row.decoding for x in steps)
    every = [x for x in events if x["name"] in ("prefill_chunk", "decode_step")]
    assert all("iter" in x["args"] for x in every)


# ------------------------------------------- tracing on, tracing off


@pytest.mark.parametrize("loop", LOOPS)
def test_tracing_changes_what_is_exported_never_what_is_recorded(
        engines, drained, loop, monkeypatch):
    waits = []
    ready = jax.block_until_ready
    monkeypatch.setattr(
        jax, "block_until_ready",
        lambda x: (waits.append(1), ready(x))[1],
    )
    off, tracer = drain(engines, loop)
    assert len(tracer) == 0 and not waits
    on, tracer = drain(engines, loop, enabled=True)
    assert len(tracer) > 0

    def counts(sched):
        t = sched.latency_report()["timeline"]
        return (
            t["tokens"], t["gaps"], t.get("passes"),
            [(r.chunks, r.decoding, r.waiting) for r in sched.passes],
            {f.rid: (f.tokens, len(f.token_t)) for f in sched.finished},
        )

    assert counts(off) == counts(on) == counts(drained[loop])
    # the times are a run's own: traced, the spans' readings are in them
    assert on.latency_report()["timeline"]["itl_ms"]["max"] > (
        off.latency_report()["timeline"]["itl_ms"]["max"]
    )


# ------------------------------------------------------ the collector


def test_the_collectors_pauses_are_counted_for_the_length_of_the_drain(
        engines, monkeypatch):
    engine, params, _ = engines["paged"]
    before = list(gc.callbacks)
    step = engine.decode_step

    def collecting_step(*args):
        assert len(gc.callbacks) == len(before) + 1
        gc.collect()
        return step(*args)

    monkeypatch.setattr(engine, "decode_step", collecting_step)
    sched = engine.run(params, requests())
    assert gc.callbacks == before
    seen = sched.paged_stats["timeline"]["gc"]
    assert seen["collections"] >= len(sched.step_occupancy) > 0
    assert 0.0 < 1e-3 * seen["pause_max_ms"] <= seen["pause_s"] + 1e-6


@pytest.mark.parametrize("fault", ["refused", "in_a_pass"])
def test_the_collectors_hook_is_gone_after_run_raises(
        engines, monkeypatch, fault):
    engine, params, _ = engines["paged"]
    before = list(gc.callbacks)
    if fault == "refused":
        reqs = [Request(rid="long", prompt=prompt(1, 16), max_new_tokens=1)]
        error = ValueError
    else:
        def broken(*args):
            raise RuntimeError("the step failed")

        monkeypatch.setattr(engine, "decode_step", broken)
        reqs, error = requests(), RuntimeError
    with pytest.raises(error):
        engine.run(params, reqs)
    assert gc.callbacks == before
