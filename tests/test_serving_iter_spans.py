"""One pass of `ServingEngine._run_paged`, seen from inside (ISSUE 24):
the `engine_iter` span with its children, and the slot-step tally in
`paged_stats`.

A toy paged engine with chunked prefill and the prefix cache runs under
a tracer whose injected clock counts its own reads, so containment and
coverage are exact: between two children of a `decode_step` lies one
tick of the clock and nothing else.
"""

import numpy as np
import pytest

import jax

from distributed_model_parallel_tpu.models.gpt import GPTConfig
from distributed_model_parallel_tpu.observability import trace
from distributed_model_parallel_tpu.observability.metrics import (
    TRACE_EVENT_NAMES,
)
from distributed_model_parallel_tpu.serving.engine import ServingEngine
from distributed_model_parallel_tpu.serving.scheduler import Request

CFG = GPTConfig(
    vocab_size=61, dim=16, num_layers=2, num_heads=4, ffn_dim=32,
    max_position=16, dropout_rate=0.0,
)
STEP_CHILDREN = ("dispatch", "device_wait", "logits_fetch")
ITER_CHILDREN = ("decode_step", "prefill_chunk", "admit", "cow", "sample")
TALLY = (
    "slot_steps_ingesting", "slot_steps_page_blocked",
    "slot_steps_drain_out", "slot_steps_free_other",
)


class CountingClock:
    """1, 2, 3, ...: every reading is one tick later than the last."""

    def __init__(self):
        self.t = 0.0

    def __call__(self) -> float:
        self.t += 1.0
        return self.t


def make_engine(num_slots, num_pages=None):
    eng = ServingEngine(
        CFG, num_slots=num_slots, max_len=16, prefill_len=8, page_size=4,
        num_pages=num_pages, prefill_chunk=4, prefix_cache=True,
    )
    return eng, eng.init_params(jax.random.PRNGKey(0))


def prompt(seed, n):
    return np.random.RandomState(seed).randint(
        1, CFG.vocab_size, size=n
    ).astype(np.int32)


@pytest.fixture(scope="module")
def two_slots():
    return make_engine(2)


def mixed_requests():
    """More requests than slots; a two-chunk prompt (its first chunk is
    dispatched without a fetch), a repeated prompt (prefix hit, then
    copy-on-write of the shared partial page) and one-chunk prompts."""
    seven, three = prompt(1, 7), prompt(2, 3)
    return [
        Request(rid="a", prompt=seven, max_new_tokens=4),
        Request(rid="b", prompt=three, max_new_tokens=3),
        Request(rid="c", prompt=seven, max_new_tokens=3),
        Request(rid="d", prompt=prompt(3, 5), max_new_tokens=2),
        Request(rid="e", prompt=three, max_new_tokens=2),
    ]


def run_traced(engine, params, requests):
    """(scheduler, loop-thread spans as (name, start, end, args) in
    ticks of the counting clock)."""
    tracer = trace.Tracer(clock=CountingClock(), enabled=True)
    trace.set_tracer(tracer)
    try:
        sched = engine.run(params, requests)
    finally:
        trace.set_tracer(None)
    spans = [
        (e["name"], round(e["ts"] / 1e6), round((e["ts"] + e["dur"]) / 1e6),
         e.get("args", {}))
        for e in tracer.to_chrome()["traceEvents"]
        if e["ph"] == "X" and e["tid"] < 1000
    ]
    return sched, spans


def named(spans, *names):
    return sorted((s for s in spans if s[0] in names), key=lambda s: s[1])


def inside(child, parents):
    return [p for p in parents if p[1] < child[1] and child[2] < p[2]]


@pytest.fixture(scope="module")
def traced(two_slots):
    return run_traced(*two_slots, mixed_requests())


def test_every_loop_span_is_documented_and_counted(traced):
    sched, spans = traced
    assert {s[0] for s in spans} == set(ITER_CHILDREN + STEP_CHILDREN) | {
        "engine_iter"
    } <= set(TRACE_EVENT_NAMES)
    # the run met a prefix hit and a copy-on-write under the spans
    assert sched.prefix_stats["hits"] and sched.paged_stats["cow_copies"]
    steps = len(sched.step_occupancy)
    chunks = named(spans, "prefill_chunk")
    # a prompt's last chunk fetches its logits and picks the first token
    samples = named(spans, "sample")
    finishing = sum(
        1 for c in chunks if any(inside(s, [c]) for s in samples)
    )
    assert len(named(spans, "decode_step")) == steps > 0
    assert len(chunks) > finishing > 0  # some chunk left without a fetch
    assert len(named(spans, "dispatch")) == steps + len(chunks)
    assert len(named(spans, "device_wait")) == steps + finishing
    assert len(named(spans, "logits_fetch")) == steps + finishing
    assert len(samples) == steps + finishing
    assert len(named(spans, "cow")) == steps
    assert len(named(spans, "admit")) == len(named(spans, "engine_iter"))


def test_step_children_lie_inside_a_step_and_steps_inside_an_iteration(
        traced):
    _, spans = traced
    steps = named(spans, "decode_step", "prefill_chunk")
    iters = named(spans, "engine_iter")
    for child in named(spans, *STEP_CHILDREN):
        assert len(inside(child, steps)) == 1, child
    for child in named(spans, *ITER_CHILDREN):
        assert len(inside(child, iters)) == 1, child
    # a decode step's sampling follows the step; a chunk's lies in it
    for s in named(spans, "sample"):
        assert len(inside(s, steps)) <= 1
        assert all(p[0] == "prefill_chunk" for p in inside(s, steps))


def test_iterations_tile_the_loop_and_carry_the_queue_at_their_start(
        traced):
    sched, spans = traced
    iters = named(spans, "engine_iter")
    # a pass begins at the reading the last one ended with (ISSUE 38)
    assert all(a[2] == b[1] for a, b in zip(iters, iters[1:]))
    assert all(set(i[3]) == {"index", "waiting", "ingesting", "active"}
               for i in iters)
    assert [i[3]["index"] for i in iters] == list(range(len(iters)))
    first, last = iters[0][3], iters[-1][3]
    assert first == {"index": 0, "waiting": 5, "ingesting": 0, "active": 0}
    assert last["waiting"] == 0 and last["active"] >= 1
    assert any(i[3]["ingesting"] for i in iters)  # the two-chunk prompt
    # a pass that ran a decode step holds exactly one
    per_iter = [len([d for d in named(spans, "decode_step")
                     if inside(d, [i])]) for i in iters]
    assert set(per_iter) <= {0, 1} and sum(per_iter) == len(
        sched.step_occupancy
    )


def test_children_cover_a_decode_step_but_for_the_clocks_own_ticks(traced):
    _, spans = traced
    for step in named(spans, "decode_step"):
        kids = [k for k in named(spans, *STEP_CHILDREN)
                if inside(k, [step])]
        assert [k[0] for k in kids] == list(STEP_CHILDREN)
        edges = [step[1]] + [t for k in kids for t in k[1:3]] + [step[2]]
        # parent start, child start: one tick; child end, next child
        # start: one tick; last child end, parent end: one tick
        gaps = [b - a for a, b in zip(edges[::2], edges[1::2])]
        assert gaps == [1] * (len(kids) + 1), (step, kids)


def without_times(paged):
    return {k: v for k, v in paged.items() if k != "timeline"}


def timeline_counts(sched):
    """What of `paged_stats["timeline"]` is a count: the tokens, the
    gaps, the passes, and every pass's row."""
    t = sched.paged_stats["timeline"]
    return (
        t["tokens"], t["gaps"], t["passes"],
        [(r.chunks, r.decoding, r.waiting) for r in sched.passes],
    )


def fresh_requests(seed, prompt_len, budgets):
    """Unshared prompts of one length, one request per token budget."""
    return [
        Request(rid=i, prompt=prompt(seed + i, prompt_len),
                max_new_tokens=budget)
        for i, budget in enumerate(budgets)
    ]


# One way each to leave slots empty: (slots, pages), the requests, the
# counts that must be positive, the counts that must be zero.
SLOT_STEP_CASES = {
    "ample_pool": (
        (2, None), mixed_requests(),
        ["slot_steps_ingesting", "slot_steps_drain_out"],
        ["slot_steps_page_blocked", "admit_page_blocked_iters"],
    ),
    # 4 pages hold one 5 + 8-token budget: the second request waits for
    # pages beside a free slot
    "pool_holds_one_request": (
        (2, 4), fresh_requests(10, 5, (8, 8, 8)),
        ["slot_steps_page_blocked", "admit_page_blocked_iters"], [],
    ),
    "list_shorter_than_slots": (
        (4, None), fresh_requests(20, 3, (4, 4)),
        ["slot_steps_drain_out"],
        ["slot_steps_page_blocked", "slot_steps_ingesting",
         "slot_steps_free_other"],
    ),
    # request 0 is done with the token of its one chunk, after this
    # pass's admission found no free slot for request 2
    "slot_freed_after_admission": (
        (2, None), fresh_requests(30, 3, (1, 4, 4)),
        ["slot_steps_free_other"], ["slot_steps_page_blocked"],
    ),
}


@pytest.mark.parametrize("name", sorted(SLOT_STEP_CASES))
def test_slot_step_identity_holds_exactly(two_slots, name):
    (num_slots, num_pages), requests, positive, zero = SLOT_STEP_CASES[name]
    engine, params = (
        two_slots if (num_slots, num_pages) == (2, None)
        else make_engine(num_slots, num_pages)
    )
    sched = engine.run(params, requests)
    assert len(sched.finished) == len(requests)
    paged = sched.paged_stats
    assert sum(sched.step_occupancy) + sum(paged[k] for k in TALLY) == (
        num_slots * len(sched.step_occupancy)
    )
    assert all(paged[k] > 0 for k in positive), paged
    assert all(paged[k] == 0 for k in zero), paged
    # the report carries the tally with the rest of the page accounting,
    # and the one entry that holds times under a key of its own
    report = sched.latency_report()
    assert report["timeline"] == paged["timeline"]
    assert report["paged"] == without_times(paged)


def test_tracing_off_records_nothing_and_never_waits_twice(
        two_slots, traced, monkeypatch):
    engine, params = two_slots
    waits = []
    ready = jax.block_until_ready
    monkeypatch.setattr(
        jax, "block_until_ready",
        lambda x: (waits.append(1), ready(x))[1],
    )
    tracer = trace.Tracer(clock=CountingClock())  # tracing stays OFF
    trace.set_tracer(tracer)
    try:
        off = engine.run(params, mixed_requests())
    finally:
        trace.set_tracer(None)
    assert len(tracer) == 0 and not waits
    on, spans = run_traced(engine, params, mixed_requests())
    assert len(waits) == len(named(spans, "device_wait")) > 0
    tokens = {f.rid: f.tokens for f in traced[0].finished}
    assert {f.rid: f.tokens for f in off.finished} == tokens
    assert {f.rid: f.tokens for f in on.finished} == tokens
    # every count repeats, traced or not; `timeline` alone holds times,
    # which are a run's own, and of it the counts repeat too
    assert without_times(off.paged_stats) == without_times(
        on.paged_stats
    ) == without_times(traced[0].paged_stats)
    assert timeline_counts(off) == timeline_counts(on) == timeline_counts(
        traced[0]
    )
