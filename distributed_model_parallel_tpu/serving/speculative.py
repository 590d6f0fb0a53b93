"""Speculative decoding on the paged serving engine (Leviathan et al.,
ICML'23 — PAPERS.md): draft-propose, one-pass verify, lossless accept.

Every served token normally costs one full target-model iteration, and
at decode batch sizes that iteration is WEIGHT-BOUND — the HBM stream
of the parameters dwarfs the math of one token. A small DRAFT model
proposes k tokens per slot (k cheap iterations of a model a fraction
of the size), and the target then scores all k+1 positions in ONE
chunk-shaped verify step (`ServingEngine.verify_step`, built from the
same gather/span-write/scatter machinery as chunked prefill): the
weight stream is paid once for k+1 positions instead of once per
token, so every accepted draft token is nearly free target compute.

The three invariants this module owns:

* **Losslessness.** Greedy mode emits the longest draft prefix that
  matches the target's own argmaxes plus the target's correction (or
  bonus) token — BIT-IDENTICAL to the non-speculative greedy engine,
  pinned in tests/test_serving_speculative.py. Sampled mode applies
  the standard rejection rule per position on the slot's own Philox
  lane (`SlotSampler.dist/uniform/sample_dist`): accept draft token d
  with probability min(1, p(d)/q(d)); on the first rejection draw the
  correction from normalize(max(p-q, 0)); after k acceptances draw the
  bonus from p — the emitted distribution is exactly the target's,
  for ANY draft. Per-slot lane discipline survives: a slot's draw
  count depends only on its own proposal/accept history (k proposal
  draws + one coin per scored draft token + one residual-or-bonus
  draw per round), never on the other slots' schedule.

* **Rollback is a block-table edit.** A rejected suffix rolls both
  caches back via `PagedCacheHost.truncate` — pages wholly past the
  kept span return to the pool (refcount decrements), stale K/V inside
  the kept final page stays masked by the slot's position exactly like
  a recycled slot's. KV bytes are never copied.

* **Degrade, don't die.** When any active slot is within k+1 positions
  of `max_len`, the iteration falls back to ONE plain decode step for
  the whole batch (the compiled verify shape is fixed at k+1 — a
  shorter span would be a recompile); the sequence finishes exactly as
  the non-speculative engine would.

Draft-cache bookkeeping (`draft_n[slot]` = positions the draft cache
holds): a proposal round writes positions pos..pos+k-1 into the draft
(the round feeds [last_token, d_1..d_{k-1}]), so a FULL accept (k+1
emitted) leaves the draft one position behind — the next round opens
with one batched catch-up decode step feeding the known token at that
hole (logits discarded) for exactly the slots that need it. A partial
accept truncates the draft to the kept span, which it covers already.

The prefix cache (PR 15) remains a TARGET-side feature: a cached
prompt still skips target prefill, but the draft always ingests the
prompt itself (its cache holds different values — draft-model K/V —
so target prefix pages are unusable by construction; documented and
tested in tests/test_serving_speculative.py).
"""

from __future__ import annotations

from typing import List, Optional, Sequence

import jax.numpy as jnp
import numpy as np

from distributed_model_parallel_tpu.observability.metrics import (
    get_metrics,
)
from distributed_model_parallel_tpu.observability.trace import get_tracer
from distributed_model_parallel_tpu.serving.sampling import SlotSampler
from distributed_model_parallel_tpu.serving.scheduler import (
    Request,
    Scheduler,
)

__all__ = [
    "check_draft_engine",
    "greedy_verify",
    "rejection_verify",
    "run_speculative",
]


# ------------------------------------------------- acceptance (pure)


def greedy_verify(rows: np.ndarray, proposals: np.ndarray) -> List[int]:
    """Greedy acceptance for one slot: `rows` is the verify step's
    (k+1, vocab) logits (row i = the target's distribution AFTER the
    i-th fed token), `proposals` the k draft tokens. Emits the longest
    prefix of proposals matching the target's argmaxes, then the
    target's own next token (the correction on a mismatch, the bonus
    after a full match) — exactly the tokens non-speculative greedy
    decode would have produced, one target iteration at a time."""
    k = int(proposals.shape[0])
    emitted: List[int] = []
    for i in range(k):
        t = int(np.argmax(rows[i]))
        emitted.append(t)
        if t != int(proposals[i]):
            return emitted  # correction token; suffix rejected
    emitted.append(int(np.argmax(rows[k])))  # bonus
    return emitted


def rejection_verify(rows: np.ndarray, proposals: np.ndarray,
                     draft_dists: Sequence[np.ndarray],
                     sampler: SlotSampler, slot: int) -> List[int]:
    """Lossless rejection-sampling acceptance for one slot (module
    docstring). `draft_dists[i]` is the draft's filtered distribution
    q_i the i-th proposal was drawn from; the target's p_i comes from
    the verify logits through the SAME filter pipeline
    (`SlotSampler.dist`). All randomness rides the slot's own lane."""
    k = int(proposals.shape[0])
    emitted: List[int] = []
    for i in range(k):
        p = sampler.dist(rows[i])
        q = draft_dists[i]
        d = int(proposals[i])
        # Accept with probability min(1, p[d]/q[d]); q[d] > 0 because
        # d was drawn from q. u*q[d] <= p[d] avoids the division.
        if sampler.uniform(slot) * q[d] <= p[d]:
            emitted.append(d)
            continue
        residual = np.maximum(p - q, 0.0)
        total = residual.sum()
        if total <= 0.0:
            # p <= q everywhere can only reject with probability 0;
            # guard the measure-zero numerical corner by falling back
            # to p itself (still the target's distribution).
            residual, total = p, p.sum()
        emitted.append(sampler.sample_dist(residual / total, slot))
        return emitted
    emitted.append(sampler.sample_dist(sampler.dist(rows[k]), slot))
    return emitted


# -------------------------------------------------------- guards


def check_draft_engine(target, draft) -> None:
    """Fail fast on a draft engine the loop cannot drive in lockstep
    with the target (cli/common.check_serving_args rejects most of
    these from flags; this is the engine-level backstop)."""
    if draft.paged_spec is None:
        raise ValueError(
            "speculative decoding needs a PAGED draft engine "
            "(rollback truncates the block table): set page_size on "
            "the draft"
        )
    if draft.speculative_k:
        raise ValueError(
            "the draft engine must itself be non-speculative "
            f"(draft.speculative_k={draft.speculative_k})"
        )
    if draft.prefix_cache:
        raise ValueError(
            "prefix caching is a target-side feature: the draft "
            "always ingests prompts itself (its K/V differ from the "
            "target's) — construct the draft with prefix_cache=False"
        )
    for field in ("num_slots", "max_len", "prefill_len",
                  "prefill_chunk"):
        tv, dv = getattr(target, field), getattr(draft, field)
        if tv != dv:
            raise ValueError(
                f"draft engine must match the target's {field} so "
                f"admission and ingest run in lockstep: target {tv}, "
                f"draft {dv}"
            )


# ------------------------------------------------------ the loop


def run_speculative(target, params, requests: Sequence[Request],
                    sampler: Optional[SlotSampler], draft,
                    draft_params) -> Scheduler:
    """Drive `requests` to completion on the TARGET engine with
    `draft` proposing `target.speculative_k` tokens per slot per
    round. Mirrors `ServingEngine._run_paged`'s admission/ingest/evict
    structure; the decode step is replaced by draft-propose +
    one-pass-verify + lossless-accept rounds (module docstring)."""
    check_draft_engine(target, draft)
    k = target.speculative_k
    tracer = get_tracer()
    mx = get_metrics()
    host = target.new_host()
    dhost = draft.new_host()
    sched = Scheduler(
        target.num_slots, target.max_len,
        bytes_per_slot=target._slot_stripe_bytes,
    )
    sched.spec_k = k
    chunked = bool(target.prefill_chunk)
    cap = (target.max_len - 1) if chunked else target.prefill_len
    for r in requests:
        if r.prompt.size > cap:
            raise ValueError(
                f"request {r.rid!r}: prompt length {r.prompt.size} "
                f"exceeds "
                + (f"max_len - 1 = {cap}" if chunked
                   else f"prefill_len {cap}")
            )
        sched.submit(r)
    cache = target.init_cache()
    dcache = draft.init_cache()
    positions = np.zeros((target.num_slots,), np.int32)
    tokens = np.zeros((target.num_slots,), np.int32)
    active = np.zeros((target.num_slots,), bool)
    # Positions the draft cache holds for each slot (module docstring).
    draft_n = np.zeros((target.num_slots,), np.int32)
    # slot -> [prompt, target next-ingest pos (None = covered/done),
    #          draft next-ingest pos, accumulated seconds]
    ingest: dict = {}

    def token_at(seq, p: int) -> int:
        """The sequence's token at absolute position p (prompt, then
        generated) — the draft catch-up step's input."""
        np_len = int(seq.request.prompt.size)
        if p < np_len:
            return int(seq.request.prompt[p])
        return int(seq.generated[p - np_len])

    def evict(slot):
        sched.finish(slot)
        active[slot] = False
        host.release(slot)
        dhost.release(slot)

    while sched.has_work() or ingest:
        useful = 0
        # ---- admission: free slots AND page headroom on BOTH pools --
        # The verify step writes up to k+1 positions past the current
        # one, which near the end of a sequence can overshoot its
        # prompt+max_new_tokens budget — the reservation covers the
        # overshoot so a committed slot can always allocate.
        while sched.can_admit():
            nxt = sched.waiting[0][1]
            budget = min(
                int(nxt.prompt.size) + int(nxt.max_new_tokens) + k,
                target.max_len,
            )
            if not (host.can_hold(budget) and dhost.can_hold(budget)):
                break
            seq = sched.admit()
            host.reserve(seq.slot, budget)
            dhost.reserve(seq.slot, budget)
            prompt = seq.request.prompt
            covered = host.attach_prefix(seq.slot, prompt)
            if mx.enabled and host.prefix is not None:
                mx.inc(
                    "serve_prefix_hits_total", 1 if covered else 0
                )
            if not chunked:
                # Monolithic prefill on BOTH engines; the draft's
                # logits are discarded (proposals start next round).
                host.ensure_pages(seq.slot, int(prompt.size))
                dhost.ensure_pages(seq.slot, int(prompt.size))
                ids, length = target.pad_prompt(prompt)
                t0 = tracer.now()
                with tracer.span(
                    "prefill", rid=repr(seq.request.rid),
                    slot=seq.slot,
                ):
                    cache, nl = target.prefill(
                        params, cache,
                        host.device_row(seq.slot), ids, length,
                    )
                    dcache, _ = draft.prefill(
                        draft_params, dcache,
                        dhost.device_row(seq.slot), ids, length,
                    )
                    tok = target._pick(sampler, nl, seq.slot)
                sched.emit(seq, tok, tracer.now())
                sched.record_iteration(1)
                if mx.enabled:
                    mx.observe(
                        "serve_prefill_s", seq.t_first_token - t0
                    )
                    mx.inc("serve_tokens_total", 1)
                tokens[seq.slot] = tok
                positions[seq.slot] = prompt.size
                draft_n[seq.slot] = prompt.size
                active[seq.slot] = True
                if seq.done(target.max_len):
                    evict(seq.slot)
            else:
                # Chunked: the slot activates once BOTH ingests finish
                # (a full target prefix hit skips only the target's).
                t_next = (
                    None if covered >= prompt.size - 1 else covered
                )
                ingest[seq.slot] = [prompt, t_next, 0, 0.0]
        # ---- ingestion: one chunk per engine per slot per iteration -
        for slot in sorted(ingest):
            prompt, t_next, d_next, acc = ingest[slot]
            seq = sched.active[slot]
            t0 = tracer.now()
            if t_next is not None:
                n = min(target.prefill_chunk, int(prompt.size) - t_next)
                host.ensure_pages(slot, t_next + n)
                ids = np.zeros((1, target.prefill_chunk), np.int32)
                ids[0, :n] = prompt[t_next:t_next + n]
                with tracer.span(
                    "prefill_chunk", rid=repr(seq.request.rid),
                    slot=slot, start=t_next,
                ):
                    cache, nl = target.chunk_prefill(
                        params, cache, host.device_row(slot),
                        jnp.asarray(ids), jnp.int32(t_next),
                        jnp.int32(n),
                    )
                    if t_next + n >= prompt.size:
                        # The first token, stamped when the target had
                        # it (the slot decodes once the draft's ingest
                        # is done too, perhaps a pass later).
                        tok = target._pick(sampler, nl, slot)
                        sched.emit(seq, tok, tracer.now())
                        tokens[slot] = tok
                        positions[slot] = prompt.size
                        host.register_prefix(slot, prompt)
                        t_next = None
                    else:
                        t_next += n
            if d_next < prompt.size:
                n = min(target.prefill_chunk, int(prompt.size) - d_next)
                dhost.ensure_pages(slot, d_next + n)
                ids = np.zeros((1, target.prefill_chunk), np.int32)
                ids[0, :n] = prompt[d_next:d_next + n]
                with tracer.span(
                    "prefill_chunk", rid=repr(seq.request.rid),
                    slot=slot, start=d_next,
                ):
                    dcache, _ = draft.chunk_prefill(
                        draft_params, dcache, dhost.device_row(slot),
                        jnp.asarray(ids), jnp.int32(d_next),
                        jnp.int32(n),
                    )
                d_next += n
            dt = tracer.now() - t0
            useful += 1
            if t_next is None and d_next >= prompt.size:
                del ingest[slot]
                if not seq.generated:
                    # Full target prefix hit: the first token comes
                    # from the first round; decode the last prompt
                    # token at its own position.
                    positions[slot] = prompt.size - 1
                    tokens[slot] = int(prompt[-1])
                elif mx.enabled:
                    mx.observe("serve_prefill_s", acc + dt)
                    mx.inc("serve_tokens_total", 1)
                # The draft holds [0, prompt.size) either way; with a
                # prefix hit the first proposal step rewrites position
                # prompt.size-1 with identical content.
                draft_n[slot] = positions[slot]
                active[slot] = True
                if seq.done(target.max_len):
                    evict(slot)
            else:
                ingest[slot][1] = t_next
                ingest[slot][2] = d_next
                ingest[slot][3] = acc + dt
        # ---- one speculative round (or plain-decode fallback) -------
        n_active = int(active.sum())
        if n_active:
            live = np.nonzero(active)[0]
            room = bool(
                (positions[live] + k + 1 <= target.max_len).all()
            )
            if not room:
                # Degrade: one plain decode step for the whole batch
                # (fixed verify shape cannot shrink near max_len).
                for slot in live:
                    cache = host.ensure_writable(
                        cache, int(slot), int(positions[slot])
                    )
                t0 = tracer.now()
                with tracer.span("decode_step", active=n_active):
                    cache, logits = target.decode_step(
                        params, cache, host.device_table(),
                        jnp.asarray(positions), jnp.asarray(tokens),
                        jnp.asarray(active),
                    )
                    logits_np = np.asarray(logits)
                t1 = tracer.now()
                dt = t1 - t0
                sched.record_decode_step(n_active)
                if mx.enabled:
                    mx.observe("serve_decode_step_s", dt)
                useful += n_active
                for slot, seq in list(sched.active.items()):
                    if slot in ingest or not active[slot]:
                        continue
                    tok = target._pick(sampler, logits_np[slot], slot)
                    sched.emit(seq, tok, t1, dt)
                    tokens[slot] = tok
                    positions[slot] += 1
                    # The plain step leaves the draft further behind;
                    # the catch-up loop below replays the known tokens
                    # once the batch returns to speculative rounds.
                    if seq.done(target.max_len):
                        evict(slot)
            else:
                t0 = tracer.now()
                # 1. Draft catch-up: slots whose cache is short take
                # batched decode steps replaying the KNOWN tokens at
                # the missing positions (logits discarded). A full
                # accept leaves exactly one hole (the bonus token);
                # plain-decode fallback rounds can leave more.
                with tracer.span(
                    "draft_round", active=n_active, k=k
                ):
                    while True:
                        sync = active & (draft_n < positions)
                        if not sync.any():
                            break
                        stoks = tokens.copy()
                        spos = positions.copy()
                        for slot in np.nonzero(sync)[0]:
                            p = int(draft_n[slot])
                            stoks[slot] = token_at(
                                sched.active[int(slot)], p
                            )
                            spos[slot] = p
                            dcache = dhost.ensure_writable(
                                dcache, int(slot), p
                            )
                        dcache, _ = draft.decode_step(
                            draft_params, dcache, dhost.device_table(),
                            jnp.asarray(spos), jnp.asarray(stoks),
                            jnp.asarray(sync),
                        )
                        draft_n[sync] += 1
                    # 2. k proposal steps over the active set.
                    proposals = np.zeros(
                        (target.num_slots, k), np.int32
                    )
                    draft_dists: List[np.ndarray] = []
                    cur_tok = tokens.copy()
                    cur_pos = positions.copy()
                    for i in range(k):
                        for slot in live:
                            dcache = dhost.ensure_writable(
                                dcache, int(slot), int(cur_pos[slot])
                            )
                        dcache, dlogits = draft.decode_step(
                            draft_params, dcache, dhost.device_table(),
                            jnp.asarray(cur_pos),
                            jnp.asarray(cur_tok), jnp.asarray(active),
                        )
                        dlog = np.asarray(dlogits)
                        if sampler is not None:
                            qs = np.zeros(
                                (target.num_slots, dlog.shape[-1]),
                                np.float64,
                            )
                        for slot in live:
                            if sampler is None:
                                d = int(np.argmax(dlog[slot]))
                            else:
                                qs[slot] = sampler.dist(dlog[slot])
                                d = sampler.sample_dist(
                                    qs[slot], int(slot)
                                )
                            proposals[slot, i] = d
                        if sampler is not None:
                            draft_dists.append(qs)
                        draft_n[live] = cur_pos[live] + 1
                        cur_tok = proposals[:, i].copy()
                        cur_pos = cur_pos + 1
                # 3. One chunk-shaped verify step: the target scores
                # [last_token, d_1..d_k] at positions pos..pos+k.
                tokens_chunk = np.concatenate(
                    [tokens[:, None], proposals], axis=1
                ).astype(np.int32)
                for slot in live:
                    for p in range(
                        int(positions[slot]),
                        int(positions[slot]) + k + 1,
                    ):
                        cache = host.ensure_writable(
                            cache, int(slot), p
                        )
                with tracer.span("verify_step", active=n_active):
                    cache, vlogits = target.verify_step(
                        params, cache, host.device_table(),
                        jnp.asarray(positions),
                        jnp.asarray(tokens_chunk), jnp.asarray(active),
                    )
                    vlog = np.asarray(vlogits)
                t1 = tracer.now()
                dt = t1 - t0
                useful += n_active
                # 4. Accept/rollback per slot, on the host.
                total_emitted = 0
                for slot, seq in list(sched.active.items()):
                    if slot in ingest or not active[slot]:
                        continue
                    if sampler is None:
                        emitted = greedy_verify(
                            vlog[slot], proposals[slot]
                        )
                    else:
                        emitted = rejection_verify(
                            vlog[slot], proposals[slot],
                            [q[slot] for q in draft_dists],
                            sampler, slot,
                        )
                    sched.record_accept_len(len(emitted))
                    kept = 0
                    finished = False
                    per_tok = dt / len(emitted)
                    for tok in emitted:
                        # a round's tokens reach their user together
                        sched.emit(seq, tok, t1, per_tok)
                        kept += 1
                        if seq.done(target.max_len):
                            finished = True
                            break
                    total_emitted += kept
                    positions[slot] += kept
                    tokens[slot] = int(seq.generated[-1])
                    if finished:
                        evict(slot)
                        continue
                    if kept < k + 1:
                        # Rejected suffix: both caches roll back by
                        # truncating the block table — pages past the
                        # kept span return to the pool, no KV copies.
                        host.truncate(slot, int(positions[slot]))
                        dhost.truncate(slot, int(positions[slot]))
                        draft_n[slot] = positions[slot]
                    # kept == k+1: the draft is one position short
                    # (the bonus token's hole) — next round's catch-up
                    # step fills it.
                sched.record_verify_step(n_active, total_emitted)
        if mx.enabled:
            mx.gauge("serve_kv_pages_in_use", host.pool.pages_in_use)
        if useful:
            sched.record_iteration(useful)
        elif not ingest and not sched.active and sched.waiting:
            raise RuntimeError(
                "page pool cannot hold the next waiting prompt "
                f"({int(sched.waiting[0][1].prompt.size)} tokens, "
                f"{host.pool.free_pages} target / "
                f"{dhost.pool.free_pages} draft free pages of "
                f"{target.paged_spec.page_size}) — size the pools "
                "larger (num_pages / --kv-pages)"
            )
    sched.paged_stats = {
        "page_size": target.paged_spec.page_size,
        "num_pages": target.paged_spec.num_pages,
        "pages_in_use_peak": host.pages_in_use_peak,
        "kv_cache_bytes_peak": (
            host.pages_in_use_peak * target.paged_spec.page_bytes
        ),
        "contiguous_bytes": (
            target.num_slots * target._slot_stripe_bytes
        ),
        "cow_copies": host.cow_copies,
        "draft_pages_in_use_peak": dhost.pages_in_use_peak,
    }
    if host.prefix is not None:
        total_prompt = sum(int(r.prompt.size) for r in requests)
        sched.prefix_stats = {
            "hits": host.prefix.hits,
            "misses": host.prefix.misses,
            "tokens_reused": host.prefix.tokens_reused,
            "prefix_hit_pct": round(
                100.0 * host.prefix.tokens_reused
                / max(total_prompt, 1), 2
            ),
        }
    return sched
