"""Continuous-batching request scheduler (Orca, OSDI 2022 — PAPERS.md).

Iteration-level scheduling: the unit of work is one engine STEP, not
one request. Every step the engine (a) admits waiting requests into
free cache slots (prefill), (b) runs ONE jitted decode step for the
whole mixed-position batch, and (c) evicts finished sequences, whose
slots recycle immediately — a long request never holds the batch
hostage, and a short one never waits for the batch to drain.

The scheduler is deliberately host-side and tiny: FIFO admission over
a `SlotAllocator` free list, per-sequence bookkeeping (generated
tokens, timing legs for the latency report). Policy experiments
(priority, preemption) swap this class without touching the engine.

Every token leaves a serving loop through `Scheduler.emit`, which
stamps it with the time the host had it, on the tracer's clock
(`Sequence.token_t`); the paged loop leaves one row a pass
(`Scheduler.passes`). `Scheduler.timeline` reduces both, once, after the
drain: the gaps between a request's tokens, admission to first token,
queue wait, and how each pass divided between the device's stretches
and the host. Recorded in every run, whether or not the tracer is on.
"""

from __future__ import annotations

import dataclasses
from collections import deque
from typing import Any, Deque, Dict, List, NamedTuple, Optional

import numpy as np

from distributed_model_parallel_tpu.observability.metrics import (
    exact_quantile,
    get_metrics,
)
from distributed_model_parallel_tpu.observability.trace import get_tracer
from distributed_model_parallel_tpu.serving.kv_cache import SlotAllocator


@dataclasses.dataclass
class Request:
    """One generation request. `prompt` is a 1-D int32 token vector;
    generation stops after `max_new_tokens` or at `eos_id`."""

    rid: Any
    prompt: np.ndarray
    max_new_tokens: int = 16
    eos_id: Optional[int] = None

    def __post_init__(self):
        self.prompt = np.asarray(self.prompt, np.int32).reshape(-1)
        if self.prompt.size < 1:
            raise ValueError(f"request {self.rid!r}: empty prompt")
        if self.max_new_tokens < 1:
            raise ValueError(
                f"request {self.rid!r}: max_new_tokens must be >= 1"
            )


@dataclasses.dataclass
class Sequence:
    """A live (admitted) request: its slot, generated tokens, and the
    timing legs the latency report is built from. Two lists of times,
    both filled by `Scheduler.emit` alone: `token_t[i]` is WHEN the
    host had token i (a reading of the tracer's clock; tokens that
    leave one step share it), `token_times` is HOW LONG the step that
    produced a token took on the host (the `decode_step` stretch,
    counted once for every slot it served; no entry for the first
    token): not a time of day, and blind to whatever ran between two
    of a request's steps."""

    request: Request
    slot: int
    t_submit: float
    t_admit: float = 0.0
    t_first_token: float = 0.0
    token_times: List[float] = dataclasses.field(default_factory=list)
    token_t: List[float] = dataclasses.field(default_factory=list)
    generated: List[int] = dataclasses.field(default_factory=list)

    @property
    def position(self) -> int:
        """Next write position: prompt + tokens generated so far."""
        return int(self.request.prompt.size) + len(self.generated)

    def done(self, max_len: int) -> bool:
        r = self.request
        if len(self.generated) >= r.max_new_tokens:
            return True
        if r.eos_id is not None and self.generated \
                and self.generated[-1] == r.eos_id:
            return True
        # Out of cache positions: the slot cannot hold another token.
        return self.position >= max_len


@dataclasses.dataclass
class FinishedSequence:
    """What an evicted request leaves behind. Seconds throughout;
    `token_t` counts from the request's own submission, so
    `token_t[0] == prefill_s` and `token_t[i] - token_t[i - 1]` is the
    gap its user felt before token i. `decode_s` is the other list
    (`Sequence.token_times`): the producing step's host duration, one
    entry a token after the first, which `decode_p50_ms` summarises."""

    rid: Any
    prompt_len: int
    tokens: List[int]
    prefill_s: float  # submit -> first token (queueing + prefill)
    decode_s: List[float]  # the producing step's duration, per token
    total_s: float  # submit -> eviction
    queued_s: float = 0.0  # submit -> admission
    token_t: List[float] = dataclasses.field(default_factory=list)
    t_submit: float = 0.0  # on the tracer's clock


class PassRow(NamedTuple):
    """One pass of the paged loop, as the loop itself saw it: readings
    of the tracer's clock, counts it had at hand, and the seconds it
    spent inside its `prefill_chunk` (or unchunked `prefill`) and
    `decode_step` stretches. The rest of the pass is the host's."""

    start: float
    end: float
    chunks: int    # prefill launches of the pass
    decoding: int  # slots its decode step served (0: no step)
    waiting: int   # the queue's length at its start
    prefill_s: float
    decode_s: float


class Scheduler:
    """FIFO continuous batching over `num_slots` cache slots, and the
    one place a serving loop's tokens and passes are timed: `emit`
    takes every token with the stamp its loop already read, `finish`
    turns the stamps into a request's `token_t`, `passes` takes the
    paged loop's row a pass, `timeline` reduces them. All of it on
    `get_tracer().now()`, in every run (the tracer decides what is
    EXPORTED as spans, not what is recorded here)."""

    def __init__(self, num_slots: int, max_len: int, *,
                 bytes_per_slot: int = 0):
        self.slots = SlotAllocator(
            num_slots, bytes_per_slot=bytes_per_slot
        )
        self.max_len = max_len
        # (t_submit, request) pairs: the submit time travels WITH the
        # queue entry, so caller-supplied rids need not be unique.
        self.waiting: Deque[tuple] = deque()
        self.active: Dict[int, Sequence] = {}
        self.finished: List[FinishedSequence] = []
        # Per-step occupancy samples (engine.run reports each decode
        # step's active-slot count via record_decode_step): the goodput
        # denominator — every slot-step a sequence did NOT occupy was
        # capacity the batch paid for and wasted.
        self.step_occupancy: List[int] = []
        # Per-ITERATION useful-work samples (record_iteration): how
        # many slots advanced — decoded a token, ingested a prefill
        # chunk, or took a monolithic prefill — in each engine
        # iteration. A monolithic prefill is an iteration where ONE
        # slot worked while the rest of the batch waited; chunked
        # prefill shares its iteration with the in-flight decode step,
        # which is exactly the admission stall Orca's iteration-level
        # scheduling removes (`mean_iter_occupancy` in the report).
        self.iter_occupancy: List[int] = []
        # Attached by the paged engine loop (serving/engine.py):
        # page-pool accounting and prefix-cache hit stats.
        self.paged_stats: Optional[dict] = None
        self.prefix_stats: Optional[dict] = None
        # Attached by the speculative loop (serving/speculative.py):
        # per-slot emitted-token count of every verify round (1..k+1
        # each — accepted draft prefix + correction/bonus token) and
        # the configured draft length k. Feeds the `speculative`
        # section of latency_report.
        self.spec_accept_lens: List[int] = []
        self.spec_k: Optional[int] = None
        # The paged loop's row a pass (pass n is `passes[n]` and,
        # traced, the span `engine_iter index=n`), and the first
        # submission's time: the origin `timeline` counts `at_s` from.
        self.passes: List[PassRow] = []
        self.t_origin: Optional[float] = None

    # ------------------------------------------------------- lifecycle

    def submit(self, request: Request) -> None:
        if request.prompt.size >= self.max_len:
            raise ValueError(
                f"request {request.rid!r}: prompt length "
                f"{request.prompt.size} leaves no room to generate "
                f"(cache max_len {self.max_len})"
            )
        # Timestamps ride the tracer's clock (trace.Tracer.now):
        # identical to time.perf_counter by default, and the only
        # domain the request-lifecycle spans emitted at finish() may
        # mix with — an injected test clock stays coherent end to end.
        now = get_tracer().now()
        if self.t_origin is None:
            self.t_origin = now
        self.waiting.append((now, request))

    def can_admit(self) -> bool:
        return bool(self.waiting) and self.slots.free_slots > 0

    def admit(self) -> Sequence:
        """Pop the next waiting request into the lowest free slot."""
        t_submit, request = self.waiting.popleft()
        slot = self.slots.alloc()
        seq = Sequence(
            request=request, slot=slot,
            t_submit=t_submit,
            t_admit=get_tracer().now(),
        )
        self.active[slot] = seq
        return seq

    def emit(self, seq: Sequence, token: int, now: float,
             step_s: Optional[float] = None) -> None:
        """The one place a token leaves a serving loop. `now` is the
        reading of the tracer's clock the loop took when the step's
        fetch returned (tokens of one step, or of one verify round,
        share it: their user gets them together); `step_s` the
        producing step's host duration, kept for every token but a
        request's first (`token_times`)."""
        if not seq.generated:
            seq.t_first_token = now
        elif step_s is not None:
            seq.token_times.append(step_s)
        seq.generated.append(int(token))
        seq.token_t.append(now)

    def finish(self, slot: int) -> FinishedSequence:
        """Evict a finished sequence and recycle its slot."""
        seq = self.active.pop(slot)
        self.slots.free(slot)
        now = get_tracer().now()
        fin = FinishedSequence(
            rid=seq.request.rid,
            prompt_len=int(seq.request.prompt.size),
            tokens=list(seq.generated),
            prefill_s=seq.t_first_token - seq.t_submit,
            decode_s=list(seq.token_times),
            total_s=now - seq.t_submit,
            queued_s=seq.t_admit - seq.t_submit,
            token_t=[t - seq.t_submit for t in seq.token_t],
            t_submit=seq.t_submit,
        )
        self.finished.append(fin)
        # Request-lifecycle spans, emitted ONCE at eviction when every
        # leg's timestamp is known (queue = submit->admit, prefill =
        # admit->first token, decode = first token->eviction), each
        # request on its own named track. One branch when tracing is
        # off (observability/trace.py).
        tracer = get_tracer()
        if tracer.enabled:
            tid = tracer.track_id(f"request {seq.request.rid!r}")
            tracer.complete(
                "queued", seq.t_submit, seq.t_admit, tid=tid
            )
            tracer.complete(
                "prefill", seq.t_admit, seq.t_first_token, tid=tid,
                prompt_len=fin.prompt_len,
            )
            tracer.complete(
                "decode", seq.t_first_token, now, tid=tid,
                tokens=len(fin.tokens), slot=slot,
            )
        # Request-lifecycle histograms (observability/metrics.py; one
        # branch when disabled): queued / TTFT legs and every token's
        # decode latency — the distributions the latency report's
        # quantiles summarize, live on the exposition surface.
        mx = get_metrics()
        if mx.enabled:
            mx.observe("serve_queued_s", fin.queued_s)
            mx.observe("serve_ttft_s", fin.prefill_s)
            for t in fin.decode_s:
                mx.observe("serve_token_s", t)
        return fin

    def record_decode_step(self, n_active: int) -> None:
        """One engine decode step's occupancy sample (engine.run calls
        this after every mixed-position batch step; the per-token
        latency legs already live on each Sequence, so occupancy is the
        only new information)."""
        self.step_occupancy.append(int(n_active))
        mx = get_metrics()
        if mx.enabled:
            mx.gauge("serve_batch_occupancy", int(n_active))
            mx.inc("serve_tokens_total", int(n_active))

    def record_verify_step(self, n_active: int, n_tokens: int) -> None:
        """One speculative verify step: `n_active` slots verified a
        draft block and emitted `n_tokens` tokens between them (1..k+1
        per slot). Occupancy samples stay per-STEP (the goodput
        denominator is slot-steps, and a verify step occupies a slot
        exactly like a decode step); the token counter advances by the
        tokens actually emitted."""
        self.step_occupancy.append(int(n_active))
        mx = get_metrics()
        if mx.enabled:
            mx.gauge("serve_batch_occupancy", int(n_active))
            mx.inc("serve_tokens_total", int(n_tokens))

    def record_accept_len(self, n_emitted: int) -> None:
        """One slot's emitted-token count for one verify round
        (accepted draft prefix + the correction/bonus token): the
        acceptance-length histogram behind the report's
        `mean_accept_len`."""
        self.spec_accept_lens.append(int(n_emitted))
        mx = get_metrics()
        if mx.enabled:
            mx.observe("serve_spec_accept_len", float(n_emitted))
            mx.inc("serve_spec_tokens_total", int(n_emitted))

    def record_iteration(self, n_useful: int) -> None:
        """One engine iteration's useful-slot count (decoding slots +
        slots that ingested prefill work this iteration) — the
        admission-stall series: see `iter_occupancy`."""
        self.iter_occupancy.append(int(n_useful))

    def has_work(self) -> bool:
        return bool(self.waiting) or bool(self.active)

    # --------------------------------------------------------- reports

    def timeline(self, gc_pauses: Optional[List[float]] = None) -> dict:
        """The drain from inside, reduced once from what `emit` and
        `passes` kept (plain numbers; ms rounded to the
        microsecond). The token half, for every loop: `itl_ms` over
        EVERY gap between two tokens of one request, all requests
        pooled, with when (`itl_max_at_s`, from the first submission)
        and to whom (`itl_max_rid`) the longest happened;
        `first_token_ms` from admission; `queued_ms`. The pass half,
        where the loop recorded passes: their walls, the seconds inside
        the `prefill_chunk` and `decode_step` stretches, the rest as
        `host_s`, and two lists of five passes with what each held:
        the longest, and the slowest for what they held (wall over the
        pass's launches, its chunks and its decode step: a drain opens
        with every slot ingesting at once, and those few passes are the
        longest of any run, so a stall in a later, short pass shows in
        the second list alone). `gc_pauses` are the collector's pauses
        during the drain."""
        fins = self.finished
        origin = self.t_origin or 0.0
        gaps, spans = [], 0.0
        worst, worst_at, worst_rid = 0.0, None, None
        for f in fins:
            t = np.asarray(f.token_t, np.float64)
            if t.size < 2:
                continue
            g = np.diff(t)
            gaps.append(g)
            spans += float(t[-1] - t[0])
            i = int(g.argmax())
            if g[i] > worst:
                worst, worst_rid = float(g[i]), repr(f.rid)
                worst_at = round(f.t_submit + float(t[i + 1]) - origin, 6)
        pooled = np.sort(np.concatenate(gaps)).tolist() if gaps else []
        out = {
            "tokens": sum(len(f.token_t) for f in fins),
            "gaps": len(pooled),
            "itl_ms": {
                **_pcts(pooled, (50, 90, 99)),
                "max": _ms(pooled[-1]) if pooled else None,
                "mean": _ms(sum(pooled) / len(pooled)) if pooled else None,
            },
            "itl_max_at_s": worst_at,
            "itl_max_rid": worst_rid,
            # Σ over requests of last stamp − first: = mean gap × gaps
            "decode_span_s": round(spans, 6),
            "first_token_ms": _pcts(
                [f.prefill_s - f.queued_s for f in fins], (50, 90, 100)
            ),
            "queued_ms": _pcts([f.queued_s for f in fins], (50, 90, 100)),
        }
        if self.passes:
            rows = self.passes
            walls = [r.end - r.start for r in rows]
            prefill = sum(r.prefill_s for r in rows)
            decode = sum(r.decode_s for r in rows)
            per_launch = [
                w / max(r.chunks + (r.decoding > 0), 1)
                for w, r in zip(walls, rows)
            ]

            def top(by):
                order = sorted(
                    range(len(rows)), key=by.__getitem__, reverse=True
                )
                return [
                    {
                        "index": n,
                        "at_s": round(rows[n].start - origin, 6),
                        "wall_ms": _ms(walls[n]),
                        "per_launch_ms": _ms(per_launch[n]),
                        "chunks": rows[n].chunks,
                        "decoding": rows[n].decoding,
                        "waiting": rows[n].waiting,
                        "host_ms": _ms(
                            walls[n] - rows[n].prefill_s - rows[n].decode_s
                        ),
                    }
                    for n in order[:LONGEST_PASSES]
                ]

            out.update({
                "passes": len(rows),
                "pass_ms": _pcts(walls, (50, 90, 99, 100)),
                "wall_s": round(sum(walls), 6),
                "stretch_s": {
                    "prefill_chunk": round(prefill, 6),
                    "decode_step": round(decode, 6),
                },
                "host_s": round(sum(walls) - prefill - decode, 6),
                "longest_passes": top(walls),
                "slowest_passes": top(per_launch),
            })
        if gc_pauses is not None:
            out["gc"] = {
                "collections": len(gc_pauses),
                "pause_s": round(sum(gc_pauses, 0.0), 6),
                "pause_max_ms": _ms(max(gc_pauses, default=0.0)),
            }
        return out

    def latency_report(self) -> dict:
        """Aggregate tokens/sec and per-token p50/p99 over the finished
        set, split by leg (prefill = submit->first token, decode =
        the producing step's duration once a slot, `token_times`: the
        gap a user felt between two tokens is `timeline`'s `itl_ms`),
        plus batch-occupancy telemetry:
        `mean_batch_occupancy` is active slots per decode step and
        `goodput` the useful fraction of slot-steps (each active slot
        yields exactly one token per step, so occupied/total slot-steps
        IS tokens-out over token capacity — the continuous-batching
        claim as a number)."""
        fins = self.finished
        decode = [t for f in fins for t in f.decode_s]
        prefill = [f.prefill_s for f in fins]
        n_tokens = int(sum(len(f.tokens) for f in fins))
        total = max((f.total_s for f in fins), default=0.0)
        occ = np.asarray(self.step_occupancy, np.float64)
        goodput = (
            round(
                float(occ.sum()) / (occ.size * self.slots.num_slots), 4
            )
            if occ.size else None
        )
        mx = get_metrics()
        if mx.enabled and goodput is not None:
            mx.gauge("serve_goodput", goodput)
        iters = np.asarray(self.iter_occupancy, np.float64)
        out = {
            "requests": len(fins),
            "generated_tokens": n_tokens,
            "tokens_per_s": (
                round(n_tokens / total, 2) if total > 0 else 0.0
            ),
            "prefill_p50_ms": _pct(prefill, 50),
            "prefill_p99_ms": _pct(prefill, 99),
            "ttft_p99_ms": _pct(prefill, 99),  # prefill leg IS TTFT
            "decode_p50_ms": _pct(decode, 50),
            "decode_p99_ms": _pct(decode, 99),
            "decode_steps": int(occ.size),
            "mean_batch_occupancy": (
                round(float(occ.mean()), 3) if occ.size else None
            ),
            # Useful slots per engine ITERATION (prefill work counted
            # alongside decode — see record_iteration): the series the
            # chunked-prefill claim is judged on.
            "engine_iterations": int(iters.size),
            "mean_iter_occupancy": (
                round(float(iters.mean()), 3) if iters.size else None
            ),
            "goodput": goodput,
        }
        # The timeline once, under one key whatever the loop: the paged
        # loop reduced it after its last eviction (with its passes and
        # the collector's pauses); the others have the token half.
        paged = dict(self.paged_stats or {})
        out["timeline"] = paged.pop("timeline", None) or self.timeline()
        if self.paged_stats is not None:
            out["paged"] = paged
        if self.prefix_stats is not None:
            out["prefix_cache"] = dict(self.prefix_stats)
        if self.spec_accept_lens:
            lens = np.asarray(self.spec_accept_lens, np.float64)
            k = self.spec_k or 0
            # Emitted = accepted drafts + one guaranteed correction/
            # bonus token per round, so accept_rate strips the
            # guaranteed token before dividing by the k drafts offered.
            drafted = lens.size * max(k, 1)
            out["speculative"] = {
                "k": k,
                "verify_rounds": int(lens.size),
                "mean_accept_len": round(float(lens.mean()), 3),
                "accept_rate": round(
                    float((lens - 1.0).sum()) / drafted, 4
                ),
                "spec_tokens": int(lens.sum()),
            }
        return out


# How many passes each of `timeline`'s two lists keeps whole.
LONGEST_PASSES = 5


def _ms(seconds: float) -> float:
    return round(seconds * 1e3, 3)


def _pcts(xs, qs) -> dict:
    """{"p50": ms, ...} of a seconds sample (`_pct`); 100 is "max"."""
    return {("max" if q == 100 else f"p{q:g}"): _pct(xs, q) for q in qs}


def _pct(xs, q: float):
    """Milliseconds quantile of a seconds sample list through the
    repo's ONE percentile rule (`observability/metrics.exact_quantile`
    — regression-pinned equal to the retired `numpy.percentile` math
    on canned latencies); None when empty."""
    v = exact_quantile(xs, q)
    return None if v is None else round(v * 1e3, 3)


__all__ = [
    "FinishedSequence",
    "PassRow",
    "Request",
    "Scheduler",
    "Sequence",
]
