"""Driver of the serving cells: one saturated drain through the
program's own loop, `ServingEngine.run`.

`run` takes a finished list of requests and drives it to completion;
nothing in the program admits by wall clock. So the queue is never
empty: this is the regime above the knee, judged on tokens per second
and on time per output token. Set-up builds the engine as
`cli/serve.main` does, makes the weights on the device in one jitted
call from the seed, checks logits against the plain reference, and runs
a short warm-up drain (one request per slot, outputs cut to a few
tokens) that executes every compiled shape and host path once. The
measured drain is ONE `engine.run` over a fixed amount of work: the
requests that the cell's stated rate — found once, on the chip, when
the cell was defined — offers in `--seconds`. A faster program drains it
sooner; the work stays the same.
"""

from __future__ import annotations

import copy
import functools
import time

import numpy as np

from benchmark.harness import manifest, spans
from benchmark.harness.device import (
    CompileCounter,
    memory_peak_bytes,
    seed_key,
)
from benchmark.harness.progress import progress
from benchmark.harness.stats import (
    MIN_TAIL_SAMPLES,
    samples_beyond,
    saturated,
    tpot_ms,
)

# The reference check: one prompt of this many tokens through chunked
# prefill into pages, then this many decode steps through the cache.
# Not a multiple of the page size, so the first decode step writes into
# a page the prefix cache shares and the copy-on-write program runs too.
CHECK_PROMPT = 250
CHECK_DECODE = 3
# Where the traced slice begins, as a share of --seconds, unless the mix
# says otherwise: a third into the window, in its steady part.
TRACE_AFTER_SHARE = 0.35
# Toy rehearsal (CPU, tests only): a drain of a few requests, over in a
# fraction of --seconds, so the slice is taken from its start.
REHEARSAL = {"trace_slice_s": 0.3, "trace_after_share": 0.0}
# The tail the cell reports; the drain has to finish enough requests
# with a gap between tokens for ten samples to lie beyond it.
TAIL_PERCENTILE = 90.0


def rehearse(traffic: dict) -> dict:
    out = copy.deepcopy(traffic)
    out.update(REHEARSAL)
    return out


def check_against_reference(engine, params, config: dict, seed: int,
                            sizes: dict, reference) -> dict:
    """Logits of the paged engine against the reference's full forward
    pass: at the last prompt position (after chunked prefill into pages)
    and at the first decode steps (through the cache). Returns the
    largest difference as a share of the reference's largest logit."""
    import jax

    from distributed_model_parallel_tpu.serving.scheduler import Request

    prompt = np.random.default_rng([seed, 0xC4EC]).integers(
        1, sizes["vocab_size"], size=CHECK_PROMPT, dtype=np.int32
    )
    seen = {"prefill": None, "decode": []}
    chunk_prefill, decode_step = engine.chunk_prefill, engine.decode_step

    def spy_chunk(p, cache, bt_row, ids, start, n_valid):
        cache, logits = chunk_prefill(p, cache, bt_row, ids, start, n_valid)
        if int(start) + int(n_valid) == prompt.size:
            seen["prefill"] = np.asarray(logits)
        return cache, logits

    def spy_decode(*step_args):
        cache, logits = decode_step(*step_args)
        seen["decode"].append(np.asarray(logits)[0])  # the lone request
        return cache, logits

    engine.chunk_prefill, engine.decode_step = spy_chunk, spy_decode
    try:
        sched = engine.run(params, [Request(
            rid="check", prompt=prompt, max_new_tokens=CHECK_DECODE + 1
        )])
    finally:
        engine.chunk_prefill, engine.decode_step = chunk_prefill, decode_step
    tokens = sched.finished[0].tokens
    if len(tokens) != CHECK_DECODE + 1 or seen["prefill"] is None:
        raise RuntimeError(f"the check request did not finish: {tokens}")
    ids = np.concatenate([prompt, np.asarray(tokens[:CHECK_DECODE], np.int32)])
    want = np.asarray(jax.jit(functools.partial(
        reference.forward, num_heads=sizes["n_head"]
    ))(params, ids[None]))[0, CHECK_PROMPT - 1:]
    got = np.stack([seen["prefill"], *seen["decode"][:CHECK_DECODE]])
    if got.shape != want.shape or not np.isfinite(got).all():
        raise RuntimeError(f"bad logits from the engine: {got.shape}")
    errs = np.abs(got - want).max(axis=1) / np.abs(want).max(axis=1)
    tol = config["tolerance"]["serve_logits"]
    return {
        "logit_err_prefill": float(errs[0]),
        "logit_err_decode": [float(e) for e in errs[1:]],
        "logit_tol": tol,
        "ok": bool(errs.max() <= tol),
    }


def to_requests(generated: list) -> list:
    from distributed_model_parallel_tpu.serving.scheduler import Request

    return [
        Request(rid=g["rid"], prompt=g["prompt"],
                max_new_tokens=g["max_new_tokens"])
        for g in generated
    ]


def stated_rate(cell) -> float:
    """Requests per second the cell offers: found once on the chip for
    this configuration under this mix, stated in the cell's own file."""
    try:
        return float(cell.params["drain_requests_per_s"])
    except KeyError:
        raise KeyError(
            f"benchmark/cells/{cell.name}.json has to state "
            "drain_requests_per_s: the rate of requests the saturated "
            "engine takes in, found by a drain on the chip"
        ) from None


def drain_size(rate: float, seconds: float) -> int:
    """Requests of the measured drain: what `rate` offers in `seconds`.
    The queue empties when the last of them is admitted; those still in
    their slots then finish in the drain-out."""
    return max(1, round(rate * seconds))


def run(cell, args, t_process: float) -> dict:
    import jax

    config, traffic = cell.config, cell.traffic
    builder = manifest.load_module("builder", config["builder"])
    generator = manifest.load_module("generator", traffic["generator"])
    reference = manifest.load_module("reference", config["reference"])
    sizes = builder.shape(config)
    serving = config["serving"]
    slots = serving["num_slots"]

    engine = builder.serving_engine(config)
    params = jax.jit(engine.init_params)(seed_key(args.seed))
    jax.block_until_ready(params)
    progress(t_process, "engine built, weights made")
    check = check_against_reference(
        engine, params, config, args.seed, sizes, reference
    )
    progress(t_process, f"reference check: {check}")

    n = drain_size(stated_rate(cell), args.seconds)
    stream = generator.generate(
        traffic["requests"], vocab_size=sizes["vocab_size"],
        max_len=serving["max_len"], seed=args.seed, n=slots + n,
    )
    warm = [
        {**g, "max_new_tokens": min(g["max_new_tokens"],
                                    traffic["warmup_max_new_tokens"])}
        for g in stream[:slots]
    ]
    t0 = time.perf_counter()
    engine.run(params, to_requests(warm))
    warm_s = time.perf_counter() - t0
    progress(t_process, f"warm-up drain: {slots} requests in {warm_s:.1f}s; "
                        f"measured drain: {n} requests")
    generated = stream[slots:]
    requests = to_requests(generated)

    compiles = CompileCounter()
    host = device_trace = None
    with spans.trace_dir() as tdir:
        profiler = None
        if args.trace:
            host = spans.HostSpans()
            after = traffic.get("trace_after_share", TRACE_AFTER_SHARE)
            profiler = spans.SliceProfiler(
                tdir, after * args.seconds, traffic["trace_slice_s"]
            )
            profiler.start()
        compiles.start()
        t_start = time.perf_counter()
        sched = engine.run(params, requests)
        wall = time.perf_counter() - t_start
        compiles.stop()
        progress(t_process, f"measured drain: {wall:.1f}s")
        if args.trace:
            profiler.finish()
            host_spans = host.collect()
            device_trace = spans.reduce_dir(tdir, host_spans)

    asked = {g["rid"]: g["max_new_tokens"] for g in generated}
    finished = [
        {"rid": f.rid, "prompt_len": f.prompt_len, "n_tokens": len(f.tokens),
         "prefill_s": f.prefill_s, "total_s": f.total_s}
        for f in sched.finished
    ]
    done = sum(1 for f in finished if f["n_tokens"] == asked[f["rid"]])
    gaps = len(tpot_ms(finished))
    notes = []
    if not check["ok"]:
        notes.append(f"logits left the reference: {check}")
    if compiles.count:
        notes.append(f"{compiles.count} programs compiled or loaded "
                     "inside the measured window")
    if (samples_beyond(gaps, TAIL_PERCENTILE) < MIN_TAIL_SAMPLES
            and not args.rehearsal):
        notes.append(
            f"only {gaps} requests with a gap between tokens finished: "
            f"p{TAIL_PERCENTILE:g} needs {MIN_TAIL_SAMPLES} beyond it — "
            "the cell is sized too small for this window"
        )
    occupancy = sched.step_occupancy
    return {
        "setup_s": t_start - t_process,
        "window_s": wall,
        "attempted": len(requests),
        "failed": len(requests) - done,
        "correct": not notes,
        "notes": notes,
        "check": check,
        "compiles_in_window": compiles.count,
        "memory_peak_bytes": memory_peak_bytes(),
        "finished": finished,
        "warmup": {"requests": slots, "seconds": warm_s},
        "saturated": saturated(finished),
        "slots": slots,
        "step_occupancy_sum": int(sum(occupancy)),
        "decode_steps": len(occupancy),
        "paged": sched.paged_stats,
        "prefix": sched.prefix_stats,
        "prompt_tokens": int(sum(g["prompt"].size for g in generated)),
        "shape": sizes,
        "widths": builder.serving_widths(config),
        "host_spans": host_spans if args.trace else [],
        "device_trace": device_trace,
    }
