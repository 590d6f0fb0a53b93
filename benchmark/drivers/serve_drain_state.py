"""Driver of a serving cell whose model keeps a recurrent state per slot
beside its pages: `serve_drain`, whole, with a check against the
reference of its own in place of `serve_drain`'s.

`serve_drain` holds one 250-token prompt's logits against the plain
reference: one prefill chunk here, in a slot nobody has used. This
driver's check does that too (the same prompt, alone, under the same
names). What a state kept per slot can get wrong lies elsewhere, so
before the warm-up it sends ONE more `engine.run`: a short probe
prompt, a long prompt of 2 x `prefill_chunk` + 173 tokens that decodes
`LONG_DECODE` tokens, one short request for every slot, and the probe
again, and holds

(a) the long prompt's logits at its last position and at every decode
    step after it to the reference's full forward pass, under
    `tolerance.serve_logits`: the state is carried over three chunks,
    the last of which has a padded tail that must not advance it, and
    then through the one-token recurrence;
(b) the probe's second sending to its first (the same four logit
    rows), under `tolerance.recycled_slot`: there are more requests
    than slots, so the second sending waits for a slot that another
    sequence has held and left, and has to start it from a zero state.
    The probe is short because a recurrent state forgets: over the long
    prompt what a slot held before has decayed to nothing, over 29
    tokens it has not;
(c) what the builder reads of the state that the engine's own programs
    left in the long prompt's slot (`slot_state`, taken from the cache
    tree as its last decode step hands it back: three runs of the chunk
    program, then `LONG_DECODE` of the decode step) against the
    reference's after the same tokens (`state_readings` -> {name:
    reading}), each under `tolerance[name]`: a state, a step size or a
    factor kept in less than the configuration states, anywhere in the
    two timed programs, reads over one of them.

Which arrays of the cache are state, and how far they lie from the
reference, is the builder's; this file spells no family's keys.

Why a check of its own: the chunk step of a family with a state pool
takes a seventh argument, the slot (its row of the pool), and
`serve_drain.check_against_reference` spies `chunk_prefill(p, cache,
bt_row, ids, start, n_valid)` with exactly six. This file's spies pass
on whatever the host loop hands them. `serve_drain.run` is called as it
is, with this file's check in the place of its own for the length of
the call (as `train_job_precision` wraps the Trainer): the comparisons
need the engine and the weights `serve_drain.run` makes, and a second
copy of the weights would not fit the chip.
"""

from __future__ import annotations

import functools

import numpy as np

from benchmark.drivers import serve_drain
from benchmark.drivers.serve_drain import rehearse  # noqa: F401
from benchmark.harness import manifest

# The long prompt: two whole chunks and a third that is part padding,
# then this many decode steps before its state is read.
LONG_CHUNKS = 2
LONG_TAIL = 173
LONG_DECODE = 16
# The probe sent before and after: one chunk, most of it padding.
PROBE_PROMPT = 29
# The requests between the probe's two sendings: one for every slot.
FILLER_PROMPT = 40
FILLER_NEW_TOKENS = 2
# `chunk_prefill`'s arguments by position (a state pool's step)
START, N_VALID, SLOT = 4, 5, 6


def watch(engine, params, requests, decodes: dict, state_of=None):
    """One `engine.run` of `requests` with the chunk step and the decode
    step spied. -> (scheduler, sendings): an entry for each request
    whose prompt length is a key of `decodes`, in the order their last
    chunks ran: its prompt's length `size`, the `slot` it sat in, its
    logit `rows` (after the last chunk, then after each of its first
    `decodes[size]` decode steps) and, with `state_of`, `state`:
    `state_of(cache, slot)` on the cache tree as the last of those
    decode steps handed it back."""
    sendings = []
    chunk_prefill, decode_step = engine.chunk_prefill, engine.decode_step

    def spy_chunk(*step):
        cache, logits = chunk_prefill(*step)
        size = int(step[START]) + int(step[N_VALID])
        if size in decodes:  # the last chunk of a watched prompt
            sendings.append({"size": size, "slot": int(step[SLOT]),
                             "rows": [np.asarray(logits)], "state": None})
        return cache, logits

    def spy_decode(*step):
        cache, logits = decode_step(*step)
        active = np.asarray(step[-1])
        for sent in sendings:
            held = len(sent["rows"]) - 1
            if held < decodes[sent["size"]] and active[sent["slot"]]:
                sent["rows"].append(np.asarray(logits)[sent["slot"]])
                if state_of and held + 1 == decodes[sent["size"]]:
                    sent["state"] = state_of(cache, sent["slot"])
        return cache, logits

    engine.chunk_prefill, engine.decode_step = spy_chunk, spy_decode
    try:
        sched = engine.run(params, requests)
    finally:
        engine.chunk_prefill, engine.decode_step = chunk_prefill, decode_step
    if (len(sched.finished) != len(requests)
            or any(len(s["rows"]) != decodes[s["size"]] + 1
                   for s in sendings)):
        raise RuntimeError(
            f"the check's requests did not finish: "
            f"{[(s['size'], len(s['rows'])) for s in sendings]}, "
            f"{len(sched.finished)} of {len(requests)} requests"
        )
    for sent in sendings:
        sent["rows"] = np.stack(sent["rows"])
        if not np.isfinite(sent["rows"]).all():
            raise RuntimeError("logits that are not finite from the engine")
    return sched, sendings


def check_against_reference(engine, params, config: dict, seed: int,
                            sizes: dict, reference) -> dict:
    """`serve_drain`'s check and comparisons (a), (b), (c) of the
    module's docstring; `ok` is their conjunction."""
    import jax

    from distributed_model_parallel_tpu.serving.scheduler import Request

    builder = manifest.load_module("builder", config["builder"])
    serving, tol = config["serving"], config["tolerance"]
    forward = jax.jit(functools.partial(
        reference.forward, **builder.reference_args(config)))
    share = lambda diff, of: np.abs(diff).max(axis=1) / np.abs(of).max(axis=1)

    def fed(prompt, sched, rid, n):
        """The prompt and the first `n` tokens decoded after it."""
        tokens = next(f.tokens for f in sched.finished if f.rid == rid)
        return np.concatenate([prompt, np.asarray(tokens[:n], np.int32)])

    def logit_errs(ids, prompt_size, rows):
        want = np.asarray(forward(params, ids[None]))[0, prompt_size - 1:]
        return share(rows - want, want)

    # serve_drain's own: its prompt, alone, in a slot nobody has used
    lone = np.random.default_rng([seed, 0xC4EC]).integers(
        1, sizes["vocab_size"], size=serve_drain.CHECK_PROMPT, dtype=np.int32)
    decode = serve_drain.CHECK_DECODE
    sched, (sent,) = watch(
        engine, params,
        [Request(rid="check", prompt=lone, max_new_tokens=decode + 1)],
        {lone.size: decode})
    errs = logit_errs(fed(lone, sched, "check", decode), lone.size,
                      sent["rows"])

    rng = np.random.default_rng([seed, 0x57A7E])
    draw = lambda n: rng.integers(1, sizes["vocab_size"], size=n,
                                  dtype=np.int32)
    prompt = draw(LONG_CHUNKS * serving["prefill_chunk"] + LONG_TAIL)
    probe = draw(PROBE_PROMPT)
    requests = [
        Request(rid="probe", prompt=probe, max_new_tokens=decode + 1),
        Request(rid="long", prompt=prompt, max_new_tokens=LONG_DECODE + 1),
    ] + [
        Request(rid=f"filler{i}", prompt=draw(FILLER_PROMPT),
                max_new_tokens=FILLER_NEW_TOKENS)
        for i in range(serving["num_slots"])
    ] + [Request(rid="probe again", prompt=probe, max_new_tokens=decode + 1)]
    sched, sendings = watch(
        engine, params, requests,
        {probe.size: decode, prompt.size: LONG_DECODE}, builder.slot_state)
    probes = [s for s in sendings if s["size"] == probe.size]
    longs = [s for s in sendings if s["size"] == prompt.size]
    if (len(probes), len(longs)) != (2, 1):
        raise RuntimeError(
            f"the state check watched {[s['size'] for s in sendings]}")
    # the scheduler admits in submission order, so the probe's first
    # sending sat in a slot nobody had used and its second in one that
    # a request before it has used and left
    first, again = (s["rows"] for s in probes)
    ids = fed(prompt, sched, "long", LONG_DECODE)
    carried = logit_errs(ids, prompt.size, longs[0]["rows"])
    recycled = share(again - first, first)
    readings = builder.state_readings(
        config, reference, params, ids, longs[0]["state"])
    limits = {name: tol[name] for name in readings}
    return {
        "logit_err_prefill": float(errs[0]),
        "logit_err_decode": [float(e) for e in errs[1:]],
        "logit_tol": tol["serve_logits"],
        "state_tokens": int(ids.size),
        "state_slots": [s["slot"] for s in (*probes, *longs)],
        "carried_logit_err": [float(e) for e in carried],
        "recycled_logit_diff": [float(e) for e in recycled],
        "recycled_tol": tol["recycled_slot"],
        "state_readings": readings,
        "state_limits": limits,
        "ok": bool(
            max(errs.max(), carried.max()) <= tol["serve_logits"]
            and recycled.max() <= tol["recycled_slot"]
            and all(readings[n] <= limits[n] for n in readings)
        ),
    }


def run(cell, args, t_process: float) -> dict:
    config = cell.config
    builder = manifest.load_module("builder", config["builder"])
    base = serve_drain.check_against_reference
    serve_drain.check_against_reference = check_against_reference
    try:
        record = serve_drain.run(cell, args, t_process)
    finally:
        serve_drain.check_against_reference = base
    # for the readers of the chunk program and of the state pool
    record["prefill_chunk"] = config["serving"]["prefill_chunk"]
    record["chunk_prefill_cost"] = functools.partial(
        builder.chunk_prefill_cost, config)
    return record
