"""Driver of a serving cell whose model caches ONE latent row a token
(rotary key inside it) and routes its rows over experts: `serve_drain`,
whole, with a check against the reference of its own in place of
`serve_drain`'s.

Before the warm-up it sends ONE `engine.run`: a document of 2 x
`prefill_chunk` + 173 tokens that decodes `LONG_DECODE` tokens, one
short request for every slot, and the same document again, and holds,
at the sizes the cell times and from what the timed programs themselves
produce,

(a) `serve_logits`: the document's logits at its last position and at
    every decode step after it, EVERY row by itself, against the
    reference's full forward pass: rows written into latent pages by
    three runs of the chunk program (the last with a padded tail that
    must neither be written nor routed), attended expanded, then the
    absorbed step through the pages, every slot at a position of its
    own. The reading is the LARGEST row's difference. A model whose
    activations are rounded resolves a near-tie among a router's 64
    scores the other way now and then, and from there on the row
    answers another question (one expert of four exchanged: 0.1-0.5 on
    the logits where a sound row lies 0.010-0.015; a fifth of the rows
    on the chip, PERF.md section 6). So the reference is made to take,
    at these rows, the experts the steps themselves took (each step
    leaves them in the cache tree it hands back, `step_experts`;
    `reference.forward(forced=)`): a row is then held whatever its
    routers' ties, and
(b) `router_regret` holds the choice itself: over every row and expert
    layer, how much worse than the reference's own router's choice the
    step's experts are BY THAT ROUTER'S float32 SCORES on the same
    path, the last chosen score less the smallest one taken: 0 where
    they agree, the width of the tie where a near-tie went the other
    way, a tenth or more for a router that chose wrongly;
(c) `shared_prefix`: the second sending's rows the same way, against
    the reference fed ITS tokens and experts. The fillers keep every
    slot busy until the first has registered its pages, so the second
    is ATTACHED from the prefix cache, pages and partial last page,
    skips its prefill, and its first write copies the shared page first
    (the check raises if the cache did not hit or nothing was copied):
    the right numbers have to come back through pages another sequence
    wrote, the first row from the absorbed step where the first
    sending's came from the chunk program. (The two sendings' first
    rows against each other are reported, and held by nothing: each
    draws its own tokens after it);
(d) what the builder reads beside them (`latent_readings` -> {name:
    reading}), each under `tolerance[name]`: of the rows the engine's
    own programs left in the first layer's pool for the document and
    its decoded tokens (`slot_rows`, from the cache tree as the last of
    those decode steps handed it back), of the router's picks, and of
    the expert layers' own count of the rows they routed in this run
    against the rows that were real (a padded tail or an inactive slot
    that is routed moves no logit: only the count shows it).

Which arrays of the cache are latent rows, and how far they lie from
the reference, is the builder's; this file spells no family's keys.
`serve_drain.run` is called as it is, with this file's check in the
place of its own for the length of the call (as `serve_drain_state`
does): the comparisons need the engine and the weights it makes, and a
second copy of the weights would not fit the chip.
"""

from __future__ import annotations

import functools

import numpy as np

from benchmark.drivers import serve_drain
from benchmark.drivers.serve_drain import rehearse  # noqa: F401
from benchmark.harness import manifest

# The document: two whole chunks and a third that is part padding, then
# this many decode steps before its rows are read.
LONG_CHUNKS = 2
LONG_TAIL = 173
LONG_DECODE = 48
# The requests between the document's two sendings, one for every slot:
# they hold their slots past the document's third chunk, so that the
# second sending is admitted after the first has registered its pages.
FILLER_PROMPT = 40
FILLER_NEW_TOKENS = 6
# the steps' arguments by position
BT, POSITIONS, TOKENS, ACTIVE = 2, 3, 4, 5
START, N_VALID = 4, 5


def watch(engine, params, requests, prompt, decodes: int, rows_of=None,
          sampling=None, experts_of=None):
    """One `engine.run` of `requests` (tokens drawn as `sampling` says)
    with the chunk step and the decode step spied, for the sendings of
    `prompt`. -> (scheduler, sendings):
    an entry for each sending in the order they began to decode: the
    `slot` it sat in, whether it was `attached` whole from the prefix
    cache (its first row then comes from the decode step at the
    prompt's last position, not from a chunk), its logit `rows` (at the
    prompt's last position, then after each of its next `decodes`
    decode steps), with `experts_of` the `experts` behind each row
    (`experts_of(cache, "chunk" | "decode", the row's place in the
    step)` on the cache tree its step handed back) and, with `rows_of`,
    `held`: `rows_of(cache, the slot's row of the block table, tokens
    cached)` on the cache tree as the last of those decode steps handed
    it back."""
    sendings = []
    chunk_prefill, decode_step = engine.chunk_prefill, engine.decode_step
    size, last = int(prompt.size), int(prompt[-1])

    def spy_chunk(*step):
        cache, logits = chunk_prefill(*step)
        if int(step[START]) + int(step[N_VALID]) == size and np.array_equal(
                np.asarray(step[3])[0, :int(step[N_VALID])],
                prompt[int(step[START]):]):
            sendings.append({
                "slot": None, "attached": False, "held": None,
                "rows": [np.asarray(logits)],
                "experts": [experts_of(cache, "chunk", int(step[N_VALID]) - 1)
                            ] if experts_of else []})
        return cache, logits

    def spy_decode(*step):
        cache, logits = decode_step(*step)
        positions, tokens, active = (
            np.asarray(step[i]) for i in (POSITIONS, TOKENS, ACTIVE))
        known = {s["slot"] for s in sendings}
        for slot in np.nonzero(active)[0]:
            if slot in known:
                continue
            if positions[slot] == size - 1 and tokens[slot] == last:
                sendings.append({"slot": int(slot), "attached": True,
                                 "rows": [], "experts": [], "held": None})
            elif positions[slot] == size:
                # the sending whose last chunk just ran takes its slot
                sent = next((s for s in sendings if s["slot"] is None), None)
                if sent is not None:
                    sent["slot"] = int(slot)
        for sent in sendings:
            slot = sent["slot"]
            if (slot is None or not active[slot]
                    or len(sent["rows"]) > decodes):
                continue
            sent["rows"].append(np.asarray(logits)[slot])
            if experts_of:
                sent["experts"].append(experts_of(cache, "decode", slot))
            if rows_of and len(sent["rows"]) == decodes + 1:
                sent["held"] = rows_of(
                    cache, np.asarray(step[BT])[slot],
                    int(positions[slot]) + 1)
        return cache, logits

    engine.chunk_prefill, engine.decode_step = spy_chunk, spy_decode
    try:
        sched = engine.run(params, requests, sampling)
    finally:
        engine.chunk_prefill, engine.decode_step = chunk_prefill, decode_step
    if (len(sched.finished) != len(requests)
            or any(len(s["rows"]) != decodes + 1 for s in sendings)):
        raise RuntimeError(
            f"the check's requests did not finish: "
            f"{[(s['slot'], len(s['rows'])) for s in sendings]}, "
            f"{len(sched.finished)} of {len(requests)} requests"
        )
    for sent in sendings:
        sent["rows"] = np.stack(sent["rows"])
        sent["experts"] = np.stack(sent["experts"]) if experts_of else None
        if not np.isfinite(sent["rows"]).all():
            raise RuntimeError("logits that are not finite from the engine")
    return sched, sendings


def check_against_reference(engine, params, config: dict, seed: int,
                            sizes: dict, reference) -> dict:
    """Comparisons (a) to (d) of the module's docstring; `ok` is their
    conjunction."""
    import jax

    from distributed_model_parallel_tpu.serving.sampling import (
        SamplingConfig,
    )
    from distributed_model_parallel_tpu.serving.scheduler import Request

    builder = manifest.load_module("builder", config["builder"])
    serving, tol = config["serving"], config["tolerance"]
    share = lambda diff, of: np.abs(diff).max(axis=1) / np.abs(of).max(axis=1)

    rng = np.random.default_rng([seed, 0x1A7E])
    draw = lambda n: rng.integers(1, sizes["vocab_size"], size=n,
                                  dtype=np.int32)
    prompt = draw(LONG_CHUNKS * serving["prefill_chunk"] + LONG_TAIL)
    requests = [
        Request(rid="document", prompt=prompt,
                max_new_tokens=LONG_DECODE + 1),
    ] + [
        Request(rid=f"filler{i}", prompt=draw(FILLER_PROMPT),
                max_new_tokens=FILLER_NEW_TOKENS)
        for i in range(serving["num_slots"])
    ] + [Request(rid="document again", prompt=prompt,
                 max_new_tokens=LONG_DECODE + 1)]
    # Tokens are SAMPLED here (the drain itself is greedy): on random
    # weights greedy decoding falls into a few tokens, and rows that
    # read the same token are one case many times (they share even
    # their routers' near-ties: 9 of a sending's 17 rows on seed
    # 3600000302, chip runs, PR 36). Drawn tokens make the rows
    # independent cases.
    sched, sendings = watch(
        engine, params, requests, prompt, LONG_DECODE, builder.slot_rows,
        SamplingConfig(temperature=1.0, seed=seed % (2 ** 31)),
        builder.step_experts)
    if [s["attached"] for s in sendings] != [False, True] or not (
            sched.prefix_stats["tokens_reused"] >= prompt.size
            and sched.paged_stats["cow_copies"] >= 1):
        raise RuntimeError(
            f"the document's second sending was not attached from the "
            f"prefix cache with a copied page: "
            f"{[(s['slot'], s['attached']) for s in sendings]}, "
            f"{sched.prefix_stats}, cow_copies "
            f"{sched.paged_stats['cow_copies']}"
        )
    first, again = sendings
    forward = jax.jit(functools.partial(
        reference.forward, rows_from=prompt.size - 1,
        **builder.reference_args(config)))

    def fed(rid):
        """The prompt and the first tokens decoded after it."""
        tokens = next(f.tokens for f in sched.finished if f.rid == rid)
        return np.concatenate(
            [prompt, np.asarray(tokens[:LONG_DECODE], np.int32)])

    def against_reference(sent, ids):
        """Each row's distance from the reference fed these tokens and
        made to take the experts the steps took, and the regret of
        those experts row by row and layer by layer."""
        want, regret = forward(
            params, ids[None], forced=sent["experts"][None])
        want = np.asarray(want)[0]
        return share(sent["rows"] - want, want), np.asarray(regret)[0]

    # Each sending draws its own tokens: each is held to the reference
    # fed ITS tokens.
    ids, ids_again = fed("document"), fed("document again")
    errs, regret = against_reference(first, ids)
    errs_again, regret_again = against_reference(again, ids_again)
    regrets = np.concatenate([regret, regret_again])
    # Row k of the two sendings answers the same question while their
    # first k tokens agree: reported, and held by nothing.
    same = int(np.argmin(np.append(ids == ids_again, False))) - prompt.size
    same = min(same, LONG_DECODE) + 1
    between = share(
        again["rows"][:same] - first["rows"][:same], first["rows"][:same])
    tolerance = tol["serve_logits"]
    readings = {
        "serve_logits": float(errs.max()),
        "shared_prefix": float(errs_again.max()),
        "router_regret": float(regrets.max()),
        **builder.latent_readings(
            config, reference, params, ids, first["held"]),
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
        "logit_tol": tolerance,
        "check_tokens": int(ids.size),
        "check_slots": [first["slot"], again["slot"]],
        "attached_logit_err": [float(e) for e in errs_again],
        # rows (of the two sendings' together) in which a step's
        # experts are not the reference's own router's choice in some
        # layer, and the widest such tie layer by layer
        "rows": int(regrets.shape[0]),
        "rows_a_tie_went_the_other_way": int((regrets > 0).any(-1).sum()),
        "regret_by_layer": [float(r) for r in regrets.max(axis=0)],
        "attached_against_first": [float(e) for e in between],
        "prefix_tokens_reused": int(sched.prefix_stats["tokens_reused"]),
        "cow_copies": int(sched.paged_stats["cow_copies"]),
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
    # for the reader of the chunk program
    record["prefill_chunk"] = config["serving"]["prefill_chunk"]
    record["chunk_prefill_cost"] = functools.partial(
        builder.chunk_prefill_cost, config)
    return record
