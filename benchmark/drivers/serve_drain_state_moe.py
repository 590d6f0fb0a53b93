"""Driver of a serving cell whose model keeps a recurrent state per slot
beside pages of rotated keys and routes its rows over a held share of
its experts: `serve_drain`, whole, with a check against the reference
of its own in place of `serve_drain`'s. It joins `serve_drain_state`'s
state checks to `serve_drain_latent`'s reference made to take the
experts the steps took, and edits neither.

Before the warm-up it sends TWO `engine.run`s and holds, at the sizes
the cell times and from what the timed programs themselves produce,

(a) `recycled_slot`: `serve_drain_state`'s probe (a short prompt, one
    short request for every slot, the probe again, greedy): the second
    sending waits for a slot another sequence has held and left and has
    to start it from a zero state, so its logit rows equal the first's;
(b) `serve_logits`: a prompt of 2 x `prefill_chunk` + 173 tokens (three
    runs of the chunk program, the last with a padded tail that must
    neither advance the state nor be written or routed) and then
    `LONG_DECODE` decode steps with sampled tokens, EVERY row by itself
    against the reference's full forward pass made to take, at EVERY
    position, the experts the steps themselves took
    (`serve_drain_latent`'s reason: a model that rounds its activations
    resolves a near-tie among a router's 512 probabilities the other
    way now and then; every position, not the rows alone, since the
    delta rule's strong decay makes a row read the last few tokens'
    layers closely: left to its own routers there, the reference read
    0.073 from a sound row); the reading is the largest row's
    difference;
(c) `router_regret`: how much worse than the reference's own router's
    choice the steps' experts are by that router's float32
    probabilities, over every row and layer;
(d) what the builder reads of what the programs left in the cache tree
    in (b): the first delta-rule layer's state S and its convolution's
    kept inputs after the prompt's last chunk and after the last decode
    step, the larger distance of the two (`state_readings` ->
    `delta_state`, `conv_state`; at random weights the recurrence's
    decay is strong, and the state after 48 more tokens has forgotten
    what the chunk program did), the attention layer's cached rotated
    keys (`kv_reading` -> `kv_rows`), what the first expert layer took,
    gave and chose in the chunk program at the prompt's positions and
    in the decode step at EVERY live slot (the fillers decode beside
    the prompt through all its steps) against the reference's router
    and expert layer on the rows the layer took (`router_reading` ->
    `router_picks`, `experts_reading` -> `moe_rows`), and the expert
    layers' own count of the picks they routed in (b)'s run against the
    rows that were real (`picks_reading` -> `moe_picks`).

Every reading is held under `tolerance[name]`; `ok` is their
conjunction. Which arrays of the cache are state, keys or experts, and
how far they lie from the reference, is the builder's.
"""

from __future__ import annotations

import copy
import functools

import numpy as np

from benchmark.drivers import serve_drain, serve_drain_state
from benchmark.drivers.serve_drain_latent import ACTIVE, BT, POSITIONS
from benchmark.drivers.serve_drain_state import N_VALID, SLOT, START
from benchmark.harness import manifest

# The long prompt: two whole chunks and a third that is part padding,
# then this many decode steps before its state and keys are read.
LONG_CHUNKS = 2
LONG_TAIL = 173
LONG_DECODE = 48
# The requests beside it, one for every slot, decoding beside it through
# all of its decode steps.
FILLER_PROMPT = serve_drain_state.FILLER_PROMPT
FILLER_NEW_TOKENS = 2 * LONG_DECODE
# A rehearsal's mix: every prompt and output length divided by this, so
# that documents of tens of thousands of tokens fit the toy max_len.
REHEARSAL_SHRINK = 16


def rehearse(traffic: dict) -> dict:
    out = serve_drain.rehearse(traffic)
    for part in ("suffix", "output"):
        spec = out["requests"][part] = copy.deepcopy(out["requests"][part])
        for key in ("median", "min", "max"):
            if key in spec:
                spec[key] = max(1, spec[key] // REHEARSAL_SHRINK)
    return out


def watch_long(engine, params, requests, prompt, decodes: int, builder,
               sampling):
    """One `engine.run` of `requests` with the chunk step and the decode
    step spied for the one sending of `prompt` -> (scheduler, sent):
    its `slot`, its logit `rows` (at the prompt's last position, then
    after each of its next `decodes` decode steps), the `experts` every
    position of the prompt and of those steps took, what the first
    expert layer took, gave and chose (`layer_rows`, the builder's
    `step_rows`) at the prompt's positions and at every live slot of
    those decode steps, the slot's `prompt_state` on the cache tree as
    the prompt's last chunk handed it back, and on the cache tree as the
    last of those decode steps handed it back the slot's `state` and
    its cached `keys`."""
    sent = {"slot": None, "rows": [], "experts": [], "chunks": {},
            "layer_rows": []}
    chunk_prefill, decode_step = engine.chunk_prefill, engine.decode_step

    def spy_chunk(*step):
        cache, logits = chunk_prefill(*step)
        start, n = int(step[START]), int(step[N_VALID])
        if np.array_equal(np.asarray(step[3])[0, :n],
                          prompt[start:start + n]):
            sent["chunks"][start] = builder.step_experts(
                cache, "chunk", slice(0, n))
            sent["layer_rows"].append(
                builder.step_rows(cache, "chunk", slice(0, n)))
            if start + n == prompt.size:
                slot = int(step[SLOT])
                sent.update(slot=slot, rows=[np.asarray(logits)],
                            experts=[sent["chunks"][start][-1]],
                            prompt_state=builder.slot_state(cache, slot))
        return cache, logits

    def spy_decode(*step):
        cache, logits = decode_step(*step)
        slot = sent["slot"]
        if (slot is not None and np.asarray(step[ACTIVE])[slot]
                and len(sent["rows"]) <= decodes):
            sent["rows"].append(np.asarray(logits)[slot])
            sent["experts"].append(builder.step_experts(cache, "decode", slot))
            sent["layer_rows"].append(builder.step_rows(
                cache, "decode", np.flatnonzero(np.asarray(step[ACTIVE]))))
            if len(sent["rows"]) == decodes + 1:
                sent["state"] = builder.slot_state(cache, slot)
                sent["keys"] = builder.slot_keys(
                    cache, np.asarray(step[BT])[slot],
                    int(np.asarray(step[POSITIONS])[slot]) + 1)
        return cache, logits

    engine.chunk_prefill, engine.decode_step = spy_chunk, spy_decode
    try:
        sched = engine.run(params, requests, sampling)
    finally:
        engine.chunk_prefill, engine.decode_step = chunk_prefill, decode_step
    if (len(sched.finished) != len(requests)
            or len(sent["rows"]) != decodes + 1):
        raise RuntimeError(
            f"the check's requests did not finish: {len(sent['rows'])} rows, "
            f"{len(sched.finished)} of {len(requests)} requests")
    sent["rows"] = np.stack(sent["rows"])
    sent["layer_rows"] = {
        name: np.concatenate([r[name] for r in sent["layer_rows"]])
        for name in sent["layer_rows"][0]}
    # every position's experts: the prompt's chunk by chunk, then each
    # decode step's (the first row's are the last chunk's last)
    sent["experts"] = np.concatenate(
        [sent["chunks"][at] for at in sorted(sent["chunks"])]
        + [np.stack(sent["experts"][1:])])
    if not np.isfinite(sent["rows"]).all():
        raise RuntimeError("logits that are not finite from the engine")
    return sched, sent


def check_against_reference(engine, params, config: dict, seed: int,
                            sizes: dict, reference) -> dict:
    """Comparisons (a) to (d) of the module's docstring."""
    import jax

    from distributed_model_parallel_tpu.serving.sampling import (
        SamplingConfig,
    )
    from distributed_model_parallel_tpu.serving.scheduler import Request

    builder = manifest.load_module("builder", config["builder"])
    serving, tol = config["serving"], config["tolerance"]
    share = lambda diff, of: np.abs(diff).max(axis=1) / np.abs(of).max(axis=1)
    rng = np.random.default_rng([seed, 0x5A7E])
    draw = lambda n: rng.integers(1, sizes["vocab_size"], size=n,
                                  dtype=np.int32)

    # (a) the probe, in a slot nobody has used and in a recycled one
    probe, decode = draw(serve_drain_state.PROBE_PROMPT), (
        serve_drain.CHECK_DECODE)
    _, probes = serve_drain_state.watch(
        engine, params,
        [Request(rid="probe", prompt=probe, max_new_tokens=decode + 1)]
        + [Request(rid=f"filler{i}", prompt=draw(FILLER_PROMPT),
                   max_new_tokens=serve_drain_state.FILLER_NEW_TOKENS)
           for i in range(serving["num_slots"])]
        + [Request(rid="probe again", prompt=probe,
                   max_new_tokens=decode + 1)],
        {probe.size: decode})
    first, again = (s["rows"] for s in probes)
    recycled = share(again - first, first)

    # (b)-(d) the long prompt. Tokens are SAMPLED here (the drain itself
    # is greedy): on random weights greedy decoding falls into a few
    # tokens, and rows that read the same token are one case many times.
    prompt = draw(LONG_CHUNKS * serving["prefill_chunk"] + LONG_TAIL)
    sched, sent = watch_long(
        engine, params,
        [Request(rid="long", prompt=prompt, max_new_tokens=LONG_DECODE + 1)]
        + [Request(rid=f"filler{i}", prompt=draw(FILLER_PROMPT),
                   max_new_tokens=FILLER_NEW_TOKENS)
           for i in range(serving["num_slots"])],
        prompt, LONG_DECODE, builder,
        SamplingConfig(temperature=1.0, seed=seed % (2 ** 31)))
    tokens = next(f.tokens for f in sched.finished if f.rid == "long")
    ids = np.concatenate([prompt, np.asarray(tokens[:LONG_DECODE], np.int32)])
    want, regret = jax.jit(functools.partial(
        reference.forward, rows_from=prompt.size - 1,
        **builder.reference_args(config)))(
            params, ids[None], forced=sent["experts"][None])
    want, regret = np.asarray(want)[0], np.asarray(regret)[0]
    errs = share(sent["rows"] - want, want)
    # the state as the chunk program left it (a recurrence whose decay
    # is strong forgets a padded tail within a few tokens) and as the
    # decode steps left it
    states = [
        builder.state_readings(config, reference, params, fed, held)
        for fed, held in ((prompt, sent["prompt_state"]),
                          (ids, sent["state"]))
    ]
    readings = {
        "serve_logits": float(errs.max()),
        "recycled_slot": float(recycled.max()),
        "router_regret": float(regret.max()),
        **{name: max(s[name] for s in states) for name in states[0]},
        **builder.kv_reading(config, reference, params, ids, sent["keys"]),
        **builder.router_reading(config, reference, params,
                                 sent["layer_rows"]),
        **builder.experts_reading(config, reference, params,
                                  sent["layer_rows"]),
        # the rows this run's steps routed, by their own count, against
        # the rows that were real
        "moe_picks": builder.picks_reading(
            config, sched.paged_stats,
            sched.paged_stats["prefill_positions_valid"]
            + sum(sched.step_occupancy)),
    }
    limits = {name: tol[name] for name in readings}
    return {
        "logit_err_prefill": float(errs[0]),
        "logit_err_decode": [float(e) for e in errs[1:]],
        "logit_tol": tol["serve_logits"],
        "check_tokens": int(ids.size),
        # the first expert layer's rows held against the reference: the
        # prompt's positions, then every live slot of its decode steps
        "expert_rows": int(sent["layer_rows"]["taken"].shape[0]),
        "expert_rows_decoding": int(
            sent["layer_rows"]["taken"].shape[0] - prompt.size),
        "check_slots": [s["slot"] for s in probes] + [sent["slot"]],
        "recycled_logit_diff": [float(e) for e in recycled],
        # rows in which a step's experts are not the reference's own
        # router's choice in some layer, and the widest tie by layer
        "rows": int(regret.shape[0]),
        "rows_a_tie_went_the_other_way": int((regret > 0).any(-1).sum()),
        "regret_by_layer": [float(r) for r in regret.max(axis=0)],
        "readings": readings,
        "limits": limits,
        "ok": all(readings[n] <= limits[n] for n in readings),
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
