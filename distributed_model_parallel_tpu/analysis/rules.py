"""The collective-contract rule registry.

Each rule encodes ONE contract the repo claims in prose (INTERNALS §3c/
§3e/§3f/§5c) as a check over a parsed+classified HLO
module. Rules are severity-tagged and declare their own applicability
over a `LintTarget` (the engine/mode/mesh description the lint driver
fills in when it lowers a combo), so the same registry runs over the
whole engine matrix and each combo is judged only against the contracts
it opted into.

Adding a rule (INTERNALS §8b has the walkthrough):

    @rule(
        id="my-rule", severity="error", source="PR N",
        contract="one sentence of what must hold",
        applies=lambda t: t.engine == "ddp",
    )
    def _my_rule(ctx: LintContext) -> list:
        ...return [ctx.finding("my-rule", "what went wrong")]

plus one positive (violation detected) and one negative (clean) test in
tests/test_hlo_rules.py — the conftest meta-check fails collection when
a registered rule is missing either polarity.

Intended deviations are EXEMPTIONS, not deleted rules: a `LintTarget`
carries `exemptions={rule_id: reason}`, the finding is still computed
and reported but does not count as a violation, and the reason string
is printed beside it — the contract stays visible where it is waived.

No jax at module level: the registry must be importable by conftest
(for the coverage meta-check) and by golden-file tests without a
backend.
"""

from __future__ import annotations

import dataclasses
from collections import Counter
from typing import Callable, Dict, List, Optional, Tuple

from distributed_model_parallel_tpu.analysis.collectives import (
    ClassifiedCollective,
    MeshModel,
    classify,
    monolithic_over,
    nonscalar_all_reduces,
    ring_permutes_over,
)
from distributed_model_parallel_tpu.analysis.hlo import (
    DTYPE_BYTES,
    HloModule,
    parse_hlo,
)


@dataclasses.dataclass(frozen=True)
class LintTarget:
    """What the lint driver lowered: engine, mode, mesh facts, and the
    expectations rules pin against (bucket plans, at-rest layouts).
    Everything beyond `name`/`engine` has a safe default so golden
    tests can construct minimal targets."""

    name: str
    # dp | ddp | fsdp | tp | sp | sp_lm | pipeline | serve | cm_ag |
    # cm_rs
    engine: str
    grad_reduction: str = "monolithic"
    collective_matmul: bool = False
    bf16: bool = False
    donate: bool = False

    # Mesh facts (filled from the mesh the combo was lowered on).
    data_axes: Tuple[str, ...] = ("data",)
    ici_axis: Optional[str] = "data"
    dcn_axis: Optional[str] = None
    ici_size: int = 1
    dcn_size: int = 1
    cm_axis: Optional[str] = None  # the axis opted-in rings run over
    cm_size: int = 0

    # Reducer expectations: per backward segment, a tuple of
    # (padded_elems, dtype_token) bucket descriptors — one segment for
    # "bucketed", `overlap_segments` of them for "overlapped".
    bucket_plans: Tuple[Tuple[Tuple[int, str], ...], ...] = ()
    overlap_segments: int = 0

    # Collective-matmul expectations.
    expected_permutes: Optional[int] = None  # op-level exact pin
    cm_min_ring_permutes: int = 0  # engine-level floor
    # Serving decode expectation (engine == "serve", opted-in rings):
    # the exact `serve_ring`-tagged permute count of one decode step,
    # 4 projection rings per block x (S-1) hops (PR 7).
    serve_decode_permutes: Optional[int] = None
    # Speculative-verify expectation (ISSUE 18, engine == "serve" with
    # speculative_k > 0): the verify step scores k+1 positions per slot
    # in one pass, and its ring inventory must be EXACTLY one decode
    # step's — the same 4*layers*(S-1) `serve_ring` permutes, zero
    # monolithic collectives over the TP axis (rule spec-verify-step).
    speculative_k: int = 0
    spec_verify_permutes: Optional[int] = None
    # jaxpr metadata: ((axis_names, dtype_token, scope), ...) for every
    # `ppermute` equation in the traced step. Compiled CPU HLO cannot
    # carry dtype contracts (the backend's float-normalization pass
    # legalizes bf16 collectives to f32 + converts), so the bf16 ring
    # rule reads the trace-level dtypes instead; `scope` is the
    # equation's name_stack string (see lint.jaxpr_ppermute_dtypes).
    ring_dtypes: Tuple[Tuple[Tuple[str, ...], str, str], ...] = ()

    # At-rest / donation expectations.
    fsdp_full_leaf_shapes: Tuple[Tuple[int, ...], ...] = ()
    n_param_leaves: int = 0
    # Non-scalar all-reduce allowlist: BN state / batch-stat shapes.
    state_leaf_shapes: Tuple[Tuple[int, ...], ...] = ()

    # MoE dispatch expectations (engine == "ep"): which exchange the
    # combo opted into, and — for "hierarchical" — the EXACT count of
    # `moe_ring`-scoped collective-permutes one train step must carry
    # (2 x exchange_permutes(ici, dcn) per MoE layer: forward pair +
    # its mirrored backward; `ops/expert_dispatch.py`).
    moe_dispatch: str = "gspmd"
    moe_ring_permutes: Optional[int] = None

    # Compressed-'dcn'-wire expectations (`ops/wire_codec.py`, rule
    # `dcn-compressed-payload`). `dcn_ring_records` is the traced-jaxpr
    # record of EVERY ppermute equation — ((axis_names, dtype_token,
    # scope, n_elems), ...) — because compiled CPU HLO float-normalizes
    # bf16 collectives to f32 (the bf16-ring-upcast precedent), so the
    # wire dtype/byte contract lives at trace level. One of the two
    # expectations pins the payload hops: `dcn_wire_chunks` is the
    # exact multiset of (n_elems, wire_dtype_token) per hop (the
    # reducer paths, computable from the bucket plans), and
    # `dcn_wire_hops` is the exact hop COUNT when per-hop shapes are
    # model-dependent (the MoE exchange: 4(K-1) per routed layer).
    dcn_compression: str = "none"
    dcn_wire_chunks: Tuple[Tuple[int, str], ...] = ()
    dcn_wire_hops: Optional[int] = None
    # ISSUE 16 satellite: the exact (n_elems, wire_dtype_token)
    # multiset of FSDP's compressed WEIGHT-gather ring hops (the
    # `fsdp_gather`-scoped dcn_wire records, kept separate from the
    # gradient-bucket hops above) — (K-1) hops of full_leaf/K elems per
    # dcn-crossing leaf per gather, x2 under "overlapped" (forward
    # gather + backward regather).
    dcn_gather_chunks: Tuple[Tuple[int, str], ...] = ()
    dcn_ring_records: Tuple[
        Tuple[Tuple[str, ...], str, str, int], ...
    ] = ()

    # Quantized-decode expectations (`ops/quant_matmul.py`, rule
    # `decode-quantized-matmul`). `decode_dot_records` is the
    # traced-jaxpr record of EVERY `dot_general` equation in the decode
    # step — ((lhs_dtype_token, rhs_dtype_token, rhs_shape), ...) —
    # because compiled CPU HLO normalizes the quantized dots back to
    # f32 (the bf16-ring-upcast precedent), so the compute-dtype
    # contract lives at trace level. `quant_dot_count` is the exact
    # quantized projection-dot count (4L per step declaratively, 4LS
    # with the opted-in rings: S chunk dots per ring). The head matmul
    # (`head_weight_shape`) deliberately stays f32 — logits feed
    # sampling.
    compute_dtype: Optional[str] = None
    decode_dot_records: Tuple[
        Tuple[str, str, Tuple[int, ...]], ...
    ] = ()
    quant_dot_count: Optional[int] = None
    head_weight_shape: Optional[Tuple[int, ...]] = None

    # Composed-plan expectations (ISSUE 19, engine == "plan"):
    # `plan_axes` is the ordered {axis: ways} assignment of the
    # lowered ParallelPlan's ('stage', 'data', 'seq') mesh;
    # `plan_collective_records` is the traced-jaxpr record of EVERY
    # named-axis collective equation in one train step —
    # ((primitive, axis_names, dtype_token, scope, n_elems), ...) —
    # trace-level like the other named-axis contracts because
    # compiled CPU HLO normalizes dtypes and flattens axis names to
    # replica groups (see lint.jaxpr_collective_records).
    plan_axes: Tuple[Tuple[str, int], ...] = ()
    plan_collective_records: Tuple[
        Tuple[str, Tuple[str, ...], str, str, int], ...
    ] = ()
    # The plan's pipeline schedule (ISSUE 20): keys the plan-wire-
    # fabric rule's static ppermute-count pin (gpipe traces forward +
    # transpose; a scheduled plan traces the tick program's up + down
    # wires — and NEVER more, because schedules replay TABLES inside
    # one scan rather than unrolling per-tick programs).
    plan_schedule: str = "gpipe"
    plan_virtual: int = 1
    # Whether the plan shards parameters over 'data' (ISSUE 33): keys
    # which gradient contract plan-grad-fabric holds the step to.
    plan_fsdp: bool = False

    # rule_id -> reason; the finding is reported but not counted
    # (module docstring).
    exemptions: Dict[str, str] = dataclasses.field(default_factory=dict)


@dataclasses.dataclass(frozen=True)
class Finding:
    rule: str
    severity: str
    message: str
    instruction: Optional[str] = None
    exempted: bool = False
    exemption_reason: str = ""


@dataclasses.dataclass
class LintContext:
    """One lowered combo, parsed and classified, handed to every
    applicable rule."""

    target: LintTarget
    module: HloModule
    mesh: MeshModel
    collectives: List[ClassifiedCollective]

    @classmethod
    def build(cls, target: LintTarget, hlo_text: str,
              mesh: MeshModel) -> "LintContext":
        module = parse_hlo(hlo_text)
        return cls(
            target=target,
            module=module,
            mesh=mesh,
            collectives=classify(module, mesh),
        )

    def finding(self, rule_id: str, message: str,
                instruction: Optional[str] = None) -> Finding:
        sev = REGISTRY[rule_id].severity
        return Finding(rule_id, sev, message, instruction)

    # Shared helpers -------------------------------------------------

    def data_ring_permutes(self) -> List[ClassifiedCollective]:
        return ring_permutes_over(self.collectives, self.target.ici_axis)

    def total_buckets(self) -> int:
        return sum(len(p) for p in self.target.bucket_plans)

    def dcn_shard_shapes(self) -> Counter:
        """Expected multiset of (shape, dtype) for the per-bucket
        cross-slice all-reduce: each bucket's 1/ici shard of its padded
        flat buffer."""
        t = self.target
        c: Counter = Counter()
        for plan in t.bucket_plans:
            for padded, dt in plan:
                c[((padded // t.ici_size,), dt)] += 1
        return c


@dataclasses.dataclass(frozen=True)
class Rule:
    id: str
    severity: str  # "error" | "warn"
    contract: str
    source: str  # the PR whose claim this encodes
    applies: Callable[[LintTarget], bool]
    check: Callable[[LintContext], List[Finding]]


REGISTRY: Dict[str, Rule] = {}


def rule(*, id: str, severity: str, contract: str, source: str,
         applies: Callable[[LintTarget], bool]):
    def deco(fn):
        if id in REGISTRY:
            raise ValueError(f"duplicate rule id {id!r}")
        REGISTRY[id] = Rule(id, severity, contract, source, applies, fn)
        return fn
    return deco


def run_rules(ctx: LintContext) -> List[Finding]:
    """Run every applicable rule; exempted findings come back flagged
    (reported, not counted — the driver's summary distinguishes)."""
    out: List[Finding] = []
    for r in REGISTRY.values():
        if not r.applies(ctx.target):
            continue
        for f in r.check(ctx):
            reason = ctx.target.exemptions.get(r.id)
            if reason is not None:
                f = dataclasses.replace(
                    f, exempted=True, exemption_reason=reason
                )
            out.append(f)
    return out


def _is_reducer(t: LintTarget) -> bool:
    # Compressed-monolithic counts too: dcn_compression on a
    # "monolithic" step routes the reduction through ONE flat bucket
    # per dtype (the engines' single-bucket path), so the bucket-ring
    # and no-grad-all-reduce contracts apply to it unchanged.
    return (
        (t.grad_reduction in ("bucketed", "overlapped")
         or t.dcn_compression != "none")
        and t.engine in ("ddp", "fsdp", "sp_lm")
    )


# ------------------------------------------------------------------ rules


@rule(
    id="dcn-grad-all-reduce", severity="error", source="PR 4",
    contract=(
        "On bucketed/overlapped paths over a hybrid dcn x ici mesh, no "
        "all-reduce crossing 'dcn' may carry more than one bucket's "
        "1/ici shard — the slow fabric never sees a full gradient."
    ),
    applies=lambda t: _is_reducer(t) and t.dcn_size > 1,
)
def _dcn_grad_all_reduce(ctx: LintContext) -> List[Finding]:
    t = ctx.target
    limit = 0
    for plan in t.bucket_plans:
        for padded, dt in plan:
            itemsize = DTYPE_BYTES.get(dt, 4)
            limit = max(limit, (padded // t.ici_size) * itemsize)
    out = []
    for c in nonscalar_all_reduces(ctx.collectives):
        if c.crosses(t.dcn_axis) and c.payload_bytes > limit:
            out.append(ctx.finding(
                "dcn-grad-all-reduce",
                f"{c.name}: {c.payload_bytes} B all-reduce crosses "
                f"'{t.dcn_axis}' (largest allowed bucket shard: "
                f"{limit} B)",
                c.name,
            ))
    return out


@rule(
    id="bucket-ring-permutes", severity="error", source="PR 4",
    contract=(
        "Each bucket reduces as chunked ppermute rings: exactly "
        "2(S-1) collective-permutes per bucket over the intra-slice "
        "fabric (ring reduce-scatter + ring all-gather), summed over "
        "the per-segment bucket plans."
    ),
    applies=_is_reducer,
)
def _bucket_ring_permutes(ctx: LintContext) -> List[Finding]:
    t = ctx.target
    expected = 2 * (t.ici_size - 1) * ctx.total_buckets()
    actual = len(ctx.data_ring_permutes())
    if actual != expected:
        return [ctx.finding(
            "bucket-ring-permutes",
            f"{actual} ring permutes over '{t.ici_axis}', expected "
            f"2*({t.ici_size}-1)*{ctx.total_buckets()} = {expected}",
        )]
    return []


@rule(
    id="dcn-bucket-psum-shard", severity="error", source="PR 4",
    contract=(
        "On a hybrid mesh, each bucket crosses 'dcn' exactly once, as "
        "an all-reduce shape-pinned at the bucket's 1/ici shard of its "
        "padded flat buffer. (Compressed combos carry NO dcn "
        "all-reduce at all — their hop contract is "
        "dcn-compressed-payload's.)"
    ),
    applies=lambda t: _is_reducer(t) and t.dcn_size > 1
    and t.dcn_compression == "none",
)
def _dcn_bucket_psum_shard(ctx: LintContext) -> List[Finding]:
    t = ctx.target
    expected = ctx.dcn_shard_shapes()
    actual: Counter = Counter()
    for c in nonscalar_all_reduces(ctx.collectives):
        if c.axes is not None and c.axes == {t.dcn_axis}:
            for b in c.instruction.buffers:
                actual[(b.shape, b.dtype)] += 1
    if actual != expected:
        return [ctx.finding(
            "dcn-bucket-psum-shard",
            f"dcn-only all-reduce shapes {dict(actual)} != expected "
            f"per-bucket 1/ici shards {dict(expected)}",
        )]
    return []


@rule(
    id="no-grad-all-reduce", severity="error", source="PR 4",
    contract=(
        "Bucketed/overlapped steps keep ZERO grad-sized all-reduces "
        "over the data fabric: every non-scalar all-reduce touching "
        "the data axes must be either a pinned per-bucket dcn shard or "
        "a BatchNorm statistics reduction (state-leaf shaped)."
    ),
    applies=_is_reducer,
)
def _no_grad_all_reduce(ctx: LintContext) -> List[Finding]:
    t = ctx.target
    allowed_state = set(t.state_leaf_shapes)
    dcn_shards = ctx.dcn_shard_shapes()
    out = []
    for c in nonscalar_all_reduces(ctx.collectives):
        if c.axes is None:
            out.append(ctx.finding(
                "no-grad-all-reduce",
                f"{c.name}: unclassifiable replica groups on a "
                "non-scalar all-reduce",
                c.name,
            ))
            continue
        if not (c.axes & set(t.data_axes)):
            continue  # another fabric's reduction ('seq', 'stage', ...)
        if c.axes == {t.dcn_axis}:
            # EVERY buffer must match a pinned shard: a combiner-fused
            # tuple all-reduce must not smuggle a grad-sized buffer
            # over 'dcn' behind one legitimate bucket shard.
            if c.instruction.buffers and all(
                (b.shape, b.dtype) in dcn_shards
                for b in c.instruction.buffers
            ):
                continue  # the pinned cross-slice bucket hop
        if all(b.shape in allowed_state for b in c.instruction.buffers):
            continue  # BN running-stat / batch-stat psum
        out.append(ctx.finding(
            "no-grad-all-reduce",
            f"{c.name}: non-scalar all-reduce over {sorted(c.axes)} "
            f"carrying {c.shapes} — grad-sized traffic outside the "
            "bucket rings",
            c.name,
        ))
    return out


@rule(
    id="cm-ring-permutes", severity="error", source="PR 2",
    contract=(
        "A collective-matmul ring is exactly S-1 collective-permutes "
        "per kernel (op-level pin); an opted-in engine step carries at "
        "least its projection sites' worth of ring permutes over the "
        "cm axis."
    ),
    applies=lambda t: t.engine in ("cm_ag", "cm_rs")
    or (t.collective_matmul and t.cm_axis is not None),
)
def _cm_ring_permutes(ctx: LintContext) -> List[Finding]:
    t = ctx.target
    rings = ring_permutes_over(ctx.collectives, t.cm_axis)
    out = []
    if t.expected_permutes is not None:
        if len(rings) != t.expected_permutes:
            out.append(ctx.finding(
                "cm-ring-permutes",
                f"{len(rings)} ring permutes over '{t.cm_axis}', "
                f"expected exactly {t.expected_permutes}",
            ))
    elif len(rings) < t.cm_min_ring_permutes:
        out.append(ctx.finding(
            "cm-ring-permutes",
            f"{len(rings)} ring permutes over '{t.cm_axis}', expected "
            f">= {t.cm_min_ring_permutes} (the opted-in projection "
            "sites' rings)",
        ))
    return out


@rule(
    id="cm-monolithic-collective", severity="error", source="PR 2",
    contract=(
        "An opted-in collective-matmul site leaves NO monolithic "
        "all-gather/reduce-scatter on its axis: op-level kernels must "
        "be permute-only; SP engine steps (whose only cm-axis gathers "
        "would be the rings' replacements) must keep zero. The TP "
        "engine is judged only at op level — its embedding/head keep "
        "legitimate partitioner gathers."
    ),
    applies=lambda t: t.engine in ("cm_ag", "cm_rs")
    or (t.collective_matmul and t.engine in ("sp", "sp_lm")),
)
def _cm_monolithic(ctx: LintContext) -> List[Finding]:
    t = ctx.target
    out = []
    if t.engine in ("cm_ag", "cm_rs"):
        bad = [
            c for c in ctx.collectives
            if c.kind in ("all-gather", "reduce-scatter", "all-reduce")
        ]
    else:
        bad = monolithic_over(ctx.collectives, t.cm_axis)
    for c in bad:
        out.append(ctx.finding(
            "cm-monolithic-collective",
            f"{c.name}: monolithic {c.kind} on the opted-in "
            f"'{t.cm_axis}' ring path",
            c.name,
        ))
    return out


@rule(
    id="fsdp-at-rest-sharded", severity="error", source="PR 2/PR 4",
    contract=(
        "FSDP parameters are never fully materialized at rest: no "
        "entry parameter of the compiled step may carry the FULL shape "
        "of a shardable leaf (every leaf >= min_shard_elems with a "
        "divisible dim lives 1/N on device)."
    ),
    applies=lambda t: t.engine == "fsdp",
)
def _fsdp_at_rest(ctx: LintContext) -> List[Finding]:
    t = ctx.target
    out = []
    if not t.fsdp_full_leaf_shapes:
        return [ctx.finding(
            "fsdp-at-rest-sharded",
            "the at-rest policy shards nothing (no shardable leaves) — "
            "the contract is vacuous for this model/mesh",
        )]
    full = set(t.fsdp_full_leaf_shapes)
    for p in ctx.module.entry_parameters():
        for b in p.buffers:
            if b.shape in full:
                out.append(ctx.finding(
                    "fsdp-at-rest-sharded",
                    f"entry parameter {p.name} carries full shape "
                    f"{b.shape} of a shardable leaf — materialized at "
                    "rest",
                    p.name,
                ))
    return out


@rule(
    id="overlap-first-bucket-free", severity="error", source="PR 5",
    contract=(
        "Under grad_reduction='overlapped', the FIRST-fired bucket's "
        "ring permutes (last segment's — late layers differentiate "
        "first) carry no transitive dependency on segment 0's backward "
        "ops; segment 0's own bucket MUST depend on them (the control "
        "that keeps the analysis non-vacuous)."
    ),
    applies=lambda t: t.grad_reduction == "overlapped"
    and t.engine in ("ddp", "fsdp", "sp_lm") and t.ici_size > 1,
)
def _overlap_first_bucket(ctx: LintContext) -> List[Finding]:
    t = ctx.target
    m = ctx.module
    s = t.overlap_segments
    first = m.tagged(f"grad_reduce_stage{s - 1}", "collective-permute")
    bwd0 = set(m.tagged("bwd_stage0"))
    out = []
    if not first:
        out.append(ctx.finding(
            "overlap-first-bucket-free",
            f"no ring permutes tagged grad_reduce_stage{s - 1} — the "
            "first-fired bucket left no trace (tags moved?)",
        ))
    if not bwd0:
        out.append(ctx.finding(
            "overlap-first-bucket-free",
            "no ops tagged bwd_stage0 — segment-0 backward left no "
            "trace (tags moved?)",
        ))
    if out:
        return out
    for p in first:
        if m.depends_on(p, bwd0):
            out.append(ctx.finding(
                "overlap-first-bucket-free",
                f"first-fired bucket permute {p} depends on segment-0 "
                "backward — the eager firing serialized",
                p,
            ))
    last = m.tagged("grad_reduce_stage0", "collective-permute")
    if not last or not all(m.depends_on(p, bwd0) for p in last):
        out.append(ctx.finding(
            "overlap-first-bucket-free",
            "positive control failed: segment 0's own bucket does not "
            "depend on segment-0 backward — the dependency analysis "
            "is vacuous",
        ))
    return out


@rule(
    id="prefetch-gather-free", severity="error", source="PR 5",
    contract=(
        "FSDP overlapped: the prefetched all-gather of segment k-1's "
        "weights depends only on the parameter shards — never on ANY "
        "segment's bucket-ring ops — so the scheduler may hoist it "
        "behind the in-flight reduction."
    ),
    applies=lambda t: t.engine == "fsdp"
    and t.grad_reduction == "overlapped",
)
def _prefetch_gather_free(ctx: LintContext) -> List[Finding]:
    t = ctx.target
    m = ctx.module
    reduce_ops: set = set()
    for k in range(t.overlap_segments):
        reduce_ops |= set(m.tagged(f"grad_reduce_stage{k}"))
    out = []
    if not reduce_ops:
        return [ctx.finding(
            "prefetch-gather-free",
            "no grad_reduce_stage* tagged ops — the reduction left no "
            "trace (tags moved?)",
        )]
    for k in range(t.overlap_segments - 1):
        gathers = m.tagged(f"prefetch_gather_stage{k}", "all-gather")
        if not gathers:
            out.append(ctx.finding(
                "prefetch-gather-free",
                f"no prefetched all-gather tagged "
                f"prefetch_gather_stage{k}",
            ))
            continue
        for g in gathers:
            if m.depends_on(g, reduce_ops):
                out.append(ctx.finding(
                    "prefetch-gather-free",
                    f"prefetch gather {g} (segment {k}) depends on a "
                    "bucket reduction — the ZeRO overlap serialized",
                    g,
                ))
    return out


@rule(
    id="serve-decode-ring", severity="error", source="PR 7",
    contract=(
        "An opted-in serving decode step rides the chunked rings: "
        "exactly 4*layers*(S-1) `serve_ring`-tagged collective-"
        "permutes (one ag_matmul/matmul_rs ring per qkv / attn-out / "
        "ffn-in / ffn-out projection, no backward) and ZERO monolithic "
        "all-gather/reduce-scatter crossing the TP axis — the decode "
        "projections never fall back to the partitioner's fused "
        "collectives."
    ),
    applies=lambda t: t.engine == "serve" and t.collective_matmul
    and not t.speculative_k,
)
def _serve_decode_ring(ctx: LintContext) -> List[Finding]:
    t = ctx.target
    out = []
    if t.serve_decode_permutes is None:
        return [ctx.finding(
            "serve-decode-ring",
            "no serve_decode_permutes expectation on an opted-in "
            "serving combo — the ring pin was not checked",
        )]
    tagged = ctx.module.tagged("serve_ring", "collective-permute")
    if len(tagged) != t.serve_decode_permutes:
        out.append(ctx.finding(
            "serve-decode-ring",
            f"{len(tagged)} serve_ring-tagged permutes, expected "
            f"exactly {t.serve_decode_permutes} (4 rings/block x "
            "(S-1) hops)",
        ))
    for c in monolithic_over(ctx.collectives, t.cm_axis):
        out.append(ctx.finding(
            "serve-decode-ring",
            f"{c.name}: monolithic {c.kind} crossing '{t.cm_axis}' on "
            "an opted-in decode step",
            c.name,
        ))
    return out


@rule(
    id="spec-verify-step", severity="error", source="ISSUE 18",
    contract=(
        "A speculative verify step on an opted-in serving combo "
        "amortizes k+1 scored positions over ONE decode step's wire "
        "traffic: exactly 4*layers*(S-1) `serve_ring`-tagged "
        "collective-permutes (the chunk axis rides the rings' local "
        "operand, never the fabric) and ZERO monolithic all-gather/"
        "reduce-scatter crossing the TP axis — if verify cost scaled "
        "with k on the wire, speculative decoding's win would vanish "
        "at exactly the batch sizes it targets."
    ),
    applies=lambda t: t.engine == "serve" and t.collective_matmul
    and t.speculative_k > 0,
)
def _spec_verify_step(ctx: LintContext) -> List[Finding]:
    t = ctx.target
    out = []
    if t.spec_verify_permutes is None:
        return [ctx.finding(
            "spec-verify-step",
            "no spec_verify_permutes expectation on a speculative "
            "serving combo — the verify ring pin was not checked",
        )]
    tagged = ctx.module.tagged("serve_ring", "collective-permute")
    if len(tagged) != t.spec_verify_permutes:
        out.append(ctx.finding(
            "spec-verify-step",
            f"{len(tagged)} serve_ring-tagged permutes in the verify "
            f"step, expected exactly {t.spec_verify_permutes} — one "
            f"decode step's inventory (4 rings/block x (S-1) hops), "
            f"independent of k={t.speculative_k}",
        ))
    for c in monolithic_over(ctx.collectives, t.cm_axis):
        out.append(ctx.finding(
            "spec-verify-step",
            f"{c.name}: monolithic {c.kind} crossing '{t.cm_axis}' in "
            "a speculative verify step",
            c.name,
        ))
    return out


_QUANT_DOT_PAIR = {"int8": ("s8", "s8"), "bf16": ("bf16", "bf16")}


@rule(
    id="decode-quantized-matmul", severity="error", source="ISSUE 16",
    contract=(
        "An opted-in quantized decode step runs EVERY projection GEMM "
        "in the declared arithmetic: exactly 4*layers quantized "
        "dot_generals per step (4*layers*S with the opted-in rings — "
        "S chunk dots per ring), ZERO f32 dot_generals on projection "
        "shapes, and the head matmul still f32 (logits feed "
        "sampling). Pinned from the traced jaxpr "
        "(`lint.jaxpr_dot_records`): compiled CPU HLO normalizes "
        "int8/bf16 dots back to f32, the bf16-ring-upcast precedent."
    ),
    applies=lambda t: (
        t.engine == "serve" and t.compute_dtype is not None
    ),
)
def _decode_quantized_matmul(ctx: LintContext) -> List[Finding]:
    t = ctx.target
    out = []
    pair = _QUANT_DOT_PAIR.get(t.compute_dtype)
    if pair is None:
        return [ctx.finding(
            "decode-quantized-matmul",
            f"unknown compute_dtype {t.compute_dtype!r} — the "
            "quantized-dot pin was not checked",
        )]
    if not t.decode_dot_records or t.quant_dot_count is None:
        return [ctx.finding(
            "decode-quantized-matmul",
            "no decode_dot_records/quant_dot_count expectation on a "
            "quantized serving combo — the compute-dtype pin was not "
            "checked",
        )]
    # Projection dots are the rank-2-rhs dot_generals that are not the
    # head matmul (attention's qk/av dots carry batched rank-3+ rhs).
    quantized = [
        r for r in t.decode_dot_records if (r[0], r[1]) == pair
    ]
    if len(quantized) != t.quant_dot_count:
        out.append(ctx.finding(
            "decode-quantized-matmul",
            f"{len(quantized)} {t.compute_dtype} dot_generals in the "
            f"decode trace, expected exactly {t.quant_dot_count} "
            "(4 projections/block"
            + (" x S chunk dots per ring" if t.collective_matmul
               else "") + ")",
        ))
    f32_proj = [
        r for r in t.decode_dot_records
        if (r[0], r[1]) == ("f32", "f32") and len(r[2]) == 2
        and r[2] != t.head_weight_shape
    ]
    for lhs, rhs, shape in f32_proj:
        out.append(ctx.finding(
            "decode-quantized-matmul",
            f"f32 dot_general on projection shape {shape} in an "
            f"opted-in {t.compute_dtype} decode step — the projection "
            "fell back to f32 arithmetic",
        ))
    if t.head_weight_shape is not None:
        head = [
            r for r in t.decode_dot_records
            if r[2] == t.head_weight_shape
        ]
        if not head:
            out.append(ctx.finding(
                "decode-quantized-matmul",
                f"no dot_general on the head shape "
                f"{t.head_weight_shape} — the head-matmul-stays-f32 "
                "pin was not checked",
            ))
        for lhs, rhs, shape in head:
            if (lhs, rhs) != ("f32", "f32"):
                out.append(ctx.finding(
                    "decode-quantized-matmul",
                    f"head matmul {shape} traced {lhs}x{rhs}; the "
                    "head stays f32 — logits feed sampling",
                ))
    return out


# Named-scope exemption for bf16-ring-upcast: permutes whose trace
# scope carries one of these names ride f32 ON PURPOSE and are not
# upcast findings. `kv_ring` is ring attention's K/V rotation
# (ops/ring_attention.py): its dk/dv cotangents retrace the reversed
# ring in the wire dtype, so a bf16 wire would accumulate each block's
# gradient through n-1 bf16 roundings — the module's contract is
# "accumulate in f32 end to end", and the wire pays 2x bytes for it.
# Matched as a whole scope-name WORD (\b-delimited), never a substring:
# a future `qkv_ring` or `kv_ring_cache` scope must not inherit the
# exemption silently.
BF16_RING_EXEMPT_SCOPES = ("kv_ring",)


def _scope_exempt(scope: str) -> bool:
    import re as _re

    return any(
        _re.search(rf"\b{_re.escape(s)}\b", scope)
        for s in BF16_RING_EXEMPT_SCOPES
    )


@rule(
    id="bf16-ring-upcast", severity="error", source="PR 2/PR 6",
    contract=(
        "Inside an opted-in bf16 region (compute_dtype=bfloat16 with "
        "collective-matmul rings), every ppermute over the cm axis "
        "carries a bf16 payload — an f32 permute is a silent upcast "
        "doubling the ring bytes. Checked from the traced jaxpr (the "
        "CPU backend's float-normalization pass rewrites compiled-HLO "
        "collectives to f32, so only trace-level dtypes carry this "
        "contract). Scopes in BF16_RING_EXEMPT_SCOPES (the KV ring's "
        "deliberate f32 wire) are exempt."
    ),
    applies=lambda t: t.bf16 and (
        t.engine in ("cm_ag", "cm_rs") or t.collective_matmul
    ),
)
def _bf16_ring_upcast(ctx: LintContext) -> List[Finding]:
    t = ctx.target
    if not t.ring_dtypes:
        return [ctx.finding(
            "bf16-ring-upcast",
            "no jaxpr ppermute dtypes collected for a bf16 ring combo "
            "— the dtype contract was not checked",
        )]
    out = []
    for axes, dt, scope in t.ring_dtypes:
        if _scope_exempt(scope):
            continue
        if t.cm_axis in axes and dt == "f32":
            out.append(ctx.finding(
                "bf16-ring-upcast",
                f"f32 ppermute over '{t.cm_axis}' in the traced step "
                f"(scope {scope!r}) — silent upcast on an opted-in "
                "bf16 ring",
            ))
    return out


@rule(
    id="moe-hierarchical-a2a", severity="error", source="PR 10",
    contract=(
        "An opted-in hierarchical MoE step keeps the token exchange on "
        "the explicit two-level path: ZERO token-sized all-to-all "
        "touching the data fabric (the flat exchange the partitioner "
        "would insert — on a hybrid mesh it would drag the full "
        "payload across 'dcn'), and EXACTLY the expected moe_ring-"
        "scoped collective-permute chain (2(I-1)+2(K-1) per exchange "
        "pair, doubled by the mirrored backward — "
        "ops/expert_dispatch.exchange_permutes)."
    ),
    applies=lambda t: t.engine == "ep"
    and t.moe_dispatch == "hierarchical",
)
def _moe_hierarchical_a2a(ctx: LintContext) -> List[Finding]:
    import re as _re

    t = ctx.target
    if t.moe_ring_permutes is None:
        return [ctx.finding(
            "moe-hierarchical-a2a",
            "no moe_ring_permutes expectation on an opted-in MoE combo "
            "— the exchange chain was not checked",
        )]
    out = []
    # Word-matched, not tagged(): the backward hops surface as
    # `transpose(moe_ring)` in op_name, which the trailing-slash form
    # would miss; \b keeps a future moe_ring2 scope from inheriting.
    tagged = [
        i for i in ctx.module.collectives()
        if i.base_op == "collective-permute"
        and _re.search(r"\bmoe_ring\b", i.op_name)
    ]
    if len(tagged) != t.moe_ring_permutes:
        out.append(ctx.finding(
            "moe-hierarchical-a2a",
            f"{len(tagged)} moe_ring-scoped permutes, expected exactly "
            f"{t.moe_ring_permutes} (2(I-1)+2(K-1) per exchange pair, "
            "forward + mirrored backward)",
        ))
    for c in ctx.collectives:
        if c.kind == "all-to-all" and any(
            c.crosses(a) for a in t.data_axes
        ):
            out.append(ctx.finding(
                "moe-hierarchical-a2a",
                f"{c.name}: {c.payload_bytes} B all-to-all touching the "
                f"data fabric {tuple(t.data_axes)} — the flat token "
                "exchange survived on an opted-in step",
                c.name,
            ))
    return out


# Wire-dtype tokens per compression mode (`ops/wire_codec.py`): the
# dtype every payload hop of an opted-in step must carry — bf16 halves
# the f32 bytes, int8 quarters them (+ one f32 scalar sidecar per hop).
DCN_WIRE_TOKEN = {"bf16": "bf16", "int8": "s8"}


def _scope_word(word: str, scope: str) -> bool:
    import re as _re

    return bool(_re.search(rf"\b{_re.escape(word)}\b", scope))


@rule(
    id="dcn-compressed-payload", severity="error", source="PR 11",
    contract=(
        "An opted-in compressed step keeps EVERY cross-'dcn' hop on "
        "the wire codec: each traced dcn-crossing ppermute is either a "
        "dcn_wire-scoped payload in the wire dtype (shape-pinned at "
        "1/2 resp. 1/4 the f32 bytes — the regrouped chunk's element "
        "count at the wire itemsize; FSDP's weight-gather ring hops "
        "pin their own fsdp_gather multiset) or, under int8, its "
        "one-scalar f32 dcn_scale sidecar; and ZERO f32 grad-, "
        "weight- or dispatch-sized "
        "payload crosses 'dcn' in the compiled HLO (no non-scalar "
        "all-reduce outside the BN-state allowlist, no all-to-all, no "
        "all-gather/reduce-scatter). Checked from the traced jaxpr "
        "like bf16-ring-upcast — the CPU backend float-normalizes "
        "bf16 collectives in compiled HLO."
    ),
    applies=lambda t: t.dcn_compression != "none" and t.dcn_size > 1,
)
def _dcn_compressed_payload(ctx: LintContext) -> List[Finding]:
    t = ctx.target
    out: List[Finding] = []
    wire = DCN_WIRE_TOKEN[t.dcn_compression]

    if not t.dcn_ring_records:
        out.append(ctx.finding(
            "dcn-compressed-payload",
            "no traced ppermute records collected for a compressed "
            "combo — the wire dtype/byte contract was not checked",
        ))
        return out

    payload: List[Tuple[int, str]] = []
    gather_payload: List[Tuple[int, str]] = []
    sidecars: List[Tuple[str, int]] = []
    for axes, dt, scope, elems in t.dcn_ring_records:
        if t.dcn_axis not in axes:
            continue  # intra-slice / other-fabric traffic
        if _scope_word("dcn_wire", scope):
            # FSDP's compressed weight-gather hops carry their own
            # scope word so they pin against `dcn_gather_chunks`, not
            # the gradient-bucket multiset (ISSUE 16 satellite).
            if _scope_word("fsdp_gather", scope):
                gather_payload.append((elems, dt))
            else:
                payload.append((elems, dt))
        elif _scope_word("dcn_scale", scope):
            sidecars.append((dt, elems))
        else:
            out.append(ctx.finding(
                "dcn-compressed-payload",
                f"uncoded ppermute crosses '{t.dcn_axis}' on an "
                f"opted-in step ({elems} x {dt}, scope {scope!r}) — "
                "traffic outside the wire codec",
            ))

    # Payload pin: exact multiset of (elems, wire dtype) when the
    # builder can compute it (bucket plans), exact hop count otherwise.
    if t.dcn_wire_chunks:
        expected = Counter(t.dcn_wire_chunks)
        actual = Counter(payload)
        if actual != expected:
            out.append(ctx.finding(
                "dcn-compressed-payload",
                f"dcn_wire payload hops {dict(actual)} != expected "
                f"compressed chunks {dict(expected)} (elems x "
                "wire-dtype per hop)",
            ))
    elif t.dcn_wire_hops is not None:
        if len(payload) != t.dcn_wire_hops:
            out.append(ctx.finding(
                "dcn-compressed-payload",
                f"{len(payload)} dcn_wire payload hops, expected "
                f"exactly {t.dcn_wire_hops}",
            ))
        for elems, dt in payload:
            if dt != wire:
                out.append(ctx.finding(
                    "dcn-compressed-payload",
                    f"dcn_wire payload hop carries {dt} ({elems} "
                    f"elems), expected the {wire} wire dtype",
                ))
    else:
        out.append(ctx.finding(
            "dcn-compressed-payload",
            "no dcn_wire_chunks/dcn_wire_hops expectation on a "
            "compressed combo — the payload pin was not checked",
        ))

    # Weight-gather pin (ISSUE 16 satellite): FSDP's dcn gather leg
    # rides the codec too — the fsdp_gather-scoped hops must match the
    # builder's per-leaf ring-gather multiset exactly (both directions:
    # an uncompressed fused gather shows up as a missing hop here AND
    # as a monolithic dcn all-gather in the compiled-HLO half below).
    expected_g = Counter(t.dcn_gather_chunks)
    actual_g = Counter(gather_payload)
    if actual_g != expected_g:
        out.append(ctx.finding(
            "dcn-compressed-payload",
            f"fsdp_gather dcn_wire hops {dict(actual_g)} != expected "
            f"compressed weight-gather chunks {dict(expected_g)} "
            "(elems x wire-dtype per ring hop)",
        ))

    # Sidecar accounting: one f32 scalar per int8 payload hop (bucket
    # AND gather hops), none otherwise.
    n_coded = len(payload) + len(gather_payload)
    if t.dcn_compression == "int8":
        bad = [s for s in sidecars if s != ("f32", 1)]
        for dt, elems in bad:
            out.append(ctx.finding(
                "dcn-compressed-payload",
                f"dcn_scale sidecar is {elems} x {dt}, expected one "
                "f32 scalar per hop",
            ))
        if not bad and len(sidecars) != n_coded:
            out.append(ctx.finding(
                "dcn-compressed-payload",
                f"{len(sidecars)} dcn_scale sidecars for "
                f"{n_coded} int8 payload hops — expected one per "
                "hop",
            ))
    elif sidecars:
        out.append(ctx.finding(
            "dcn-compressed-payload",
            f"{len(sidecars)} dcn_scale sidecar(s) on a "
            f"{t.dcn_compression} combo — the cast codec has no scale",
        ))

    # Compiled-HLO half: zero f32 grad-/dispatch-sized payload crosses
    # 'dcn' in any monolithic form. On the reducer engines EVERY
    # non-state dcn all-reduce / gather is contraband; the EP engine's
    # gradient reduction legitimately stays on the partitioner's fused
    # collectives (only the DISPATCH is compressed there), so for it
    # only the token-sized all-to-all is forbidden — the shape the
    # flat exchange would take across the slice boundary.
    if t.engine in ("ddp", "fsdp", "sp_lm"):
        allowed_state = set(t.state_leaf_shapes)
        for c in nonscalar_all_reduces(ctx.collectives):
            if not c.crosses(t.dcn_axis):
                continue
            if all(
                b.shape in allowed_state
                for b in c.instruction.buffers
            ):
                continue  # BN running-stat / batch-stat psum
            out.append(ctx.finding(
                "dcn-compressed-payload",
                f"{c.name}: {c.payload_bytes} B all-reduce crosses "
                f"'{t.dcn_axis}' on a compressed step — uncompressed "
                "payload on the slow fabric",
                c.name,
            ))
        # The gather ban covers all three reducer engines: ddp/sp_lm
        # never legitimately gather across 'dcn', and FSDP's per-leaf
        # weight all-gathers — which DO cross the joint fabric — ride
        # the codec on an opted-in step since ISSUE 16
        # (`parallel/fsdp._coded_dcn_gather`: ici-only all-gather +
        # coded dcn ring), so a fused gather crossing 'dcn' here means
        # a leaf fell off the compressed path.
        for c in ctx.collectives:
            if c.kind in ("all-gather", "reduce-scatter") \
                    and c.crosses(t.dcn_axis):
                out.append(ctx.finding(
                    "dcn-compressed-payload",
                    f"{c.name}: monolithic {c.kind} crosses "
                    f"'{t.dcn_axis}' on a compressed step",
                    c.name,
                ))
    for c in ctx.collectives:
        if c.kind == "all-to-all" and c.crosses(t.dcn_axis):
            out.append(ctx.finding(
                "dcn-compressed-payload",
                f"{c.name}: {c.payload_bytes} B all-to-all crosses "
                f"'{t.dcn_axis}' on a compressed step — the flat "
                "dispatch payload on the slow fabric",
                c.name,
            ))
    return out


@rule(
    id="donated-step-aliased", severity="warn", source="PR 1/PR 6",
    contract=(
        "A train step built with donate=True must alias its state "
        "buffers input->output (one alias entry per parameter/optimizer "
        "leaf); a missing alias table double-buffers the whole state "
        "every step."
    ),
    applies=lambda t: t.donate and t.n_param_leaves > 0,
)
def _donated_step_aliased(ctx: LintContext) -> List[Finding]:
    t = ctx.target
    n = ctx.module.input_output_aliases
    if n < t.n_param_leaves:
        return [ctx.finding(
            "donated-step-aliased",
            f"input_output_alias covers {n} buffers, expected at least "
            f"{t.n_param_leaves} (the parameter/optimizer leaves) — "
            "the donated state is double-buffered",
        )]
    return []


@rule(
    id="collective-fabric-known", severity="warn", source="PR 6",
    contract=(
        "Every collective's replica groups / permute pairs resolve to "
        "mesh coordinates — an unclassifiable collective means the "
        "fabric rules above ran blind on it."
    ),
    applies=lambda t: True,
)
def _collective_fabric_known(ctx: LintContext) -> List[Finding]:
    out = []
    for c in ctx.collectives:
        has_membership = (
            c.instruction.replica_groups is not None
            or c.instruction.source_target_pairs is not None
        )
        if has_membership and c.axes is None:
            out.append(ctx.finding(
                "collective-fabric-known",
                f"{c.name}: {c.kind} membership does not resolve to "
                "mesh coordinates",
                c.name,
            ))
    return out


# The 'seq'-ring scope words a composed plan may carry: ring
# attention's K/V rotation plus the two collective-matmul rings
# (`ops/ring_attention.py`, `ops/collective_matmul.py`). Word-matched
# (\b), same discipline as BF16_RING_EXEMPT_SCOPES.
PLAN_SEQ_SCOPE_WORDS = ("kv_ring", "ag_matmul", "matmul_rs")


# Static plan_wire ppermute count per pipeline schedule (ISSUE 20).
# Both tick programs trace exactly TWO stage ppermutes: gpipe's
# forward hop + its autodiff transpose, a scheduled plan's up + down
# wires inside the one table-replayed tick body. The pin is the
# table-driven-replay contract itself — an unrolled schedule (or a
# per-tick lax.switch lowering) would multiply this count by the tick
# count.
PLAN_WIRE_PPERMUTES = {"gpipe": 2, "1f1b": 2, "interleaved": 2}


@rule(
    id="plan-wire-fabric", severity="error", source="ISSUE 19",
    contract=(
        "A composed plan's pipeline wire rides the stage fabric (the "
        "plan mesh's DCN contract) and nothing else: every "
        "`plan_wire`-scoped collective in the traced step is a "
        "ppermute over exactly ('stage',), and a pp>1 plan traces "
        "the schedule's exact static count (PLAN_WIRE_PPERMUTES: "
        "gpipe = forward + transpose; 1f1b/interleaved = the tick "
        "table's up + down wires) — more means the schedule unrolled "
        "instead of replaying its table."
    ),
    applies=lambda t: t.engine == "plan",
)
def _plan_wire_fabric(ctx: LintContext) -> List[Finding]:
    t = ctx.target
    axes_of = dict(t.plan_axes)
    wire = [
        r for r in t.plan_collective_records
        if _scope_word("plan_wire", r[3])
    ]
    if axes_of.get("stage", 1) > 1 and not wire:
        return [ctx.finding(
            "plan-wire-fabric",
            "no plan_wire-scoped collectives traced on a pp>1 plan — "
            "the wire pin was not checked",
        )]
    out = []
    for prim, axes, dt, scope, elems in wire:
        if prim != "ppermute" or tuple(axes) != ("stage",):
            out.append(ctx.finding(
                "plan-wire-fabric",
                f"plan_wire {prim} over {tuple(axes)} ({elems} x "
                f"{dt}, scope {scope!r}) — the activation wire is a "
                "ppermute over ('stage',) only",
            ))
    expected = PLAN_WIRE_PPERMUTES.get(t.plan_schedule)
    if (axes_of.get("stage", 1) > 1 and expected is not None
            and len(wire) != expected):
        out.append(ctx.finding(
            "plan-wire-fabric",
            f"{len(wire)} plan_wire ppermute(s) traced under the "
            f"{t.plan_schedule!r} schedule — the tick program pins "
            f"exactly {expected} (table-driven replay, not an "
            "unrolled per-tick program)",
        ))
    return out


@rule(
    id="plan-seq-fabric", severity="error", source="ISSUE 19",
    contract=(
        "A composed plan keeps its sequence-axis rings on the ICI "
        "fabric: every kv_ring / ag_matmul / matmul_rs-scoped "
        "collective rides exactly ('seq',) — never 'stage' or "
        "'data' — and an sp>1 ring-attention plan must trace at "
        "least one kv_ring hop."
    ),
    applies=lambda t: t.engine == "plan",
)
def _plan_seq_fabric(ctx: LintContext) -> List[Finding]:
    t = ctx.target
    axes_of = dict(t.plan_axes)
    rings = [
        r for r in t.plan_collective_records
        if any(_scope_word(w, r[3]) for w in PLAN_SEQ_SCOPE_WORDS)
    ]
    if axes_of.get("seq", 1) > 1 and not rings:
        return [ctx.finding(
            "plan-seq-fabric",
            "no seq-ring collectives traced on an sp>1 plan — the "
            "ring pin was not checked",
        )]
    out = []
    for prim, axes, dt, scope, elems in rings:
        if tuple(axes) != ("seq",):
            out.append(ctx.finding(
                "plan-seq-fabric",
                f"seq-ring {prim} (scope {scope!r}) over "
                f"{tuple(axes)} — the rings ride ('seq',) only",
            ))
    return out


@rule(
    id="plan-grad-fabric", severity="error", source="ISSUE 19, 33",
    contract=(
        "A composed plan WITHOUT fsdp reduces gradients as ONE fused "
        "psum over the full ('stage', 'data', 'seq') tuple under the "
        "`plan_grad` scope (complementary stage pieces + seq "
        "partials + data replicas in a single rendezvous — never a "
        "per-axis cascade). An fsdp plan never all-reduces what it "
        "keeps a 1/dp of: its weight materialization is "
        "`plan_fsdp_gather`-scoped all-gathers over ('data',) only, "
        "per block; the gather's transpose — a block's gradient — "
        "is a float32 `reduce_scatter` over ('data',) only under "
        "the same scope; under `plan_grad` the leaves gathered once "
        "(stem, head, the blocks' vectors) reduce-scatter in float32 "
        "over ('data',) only, and what is left is one psum over the "
        "'stage'/'seq' axes that exist (shards) or over the full "
        "tuple (the leaves fsdp left replicated)."
    ),
    applies=lambda t: t.engine == "plan",
)
def _plan_grad_fabric(ctx: LintContext) -> List[Finding]:
    t = ctx.target
    grads = [
        r for r in t.plan_collective_records
        if _scope_word("plan_grad", r[3])
    ]
    if not grads:
        return [ctx.finding(
            "plan-grad-fabric",
            "no plan_grad-scoped collectives traced — the "
            "fused-reduction pin was not checked",
        )]
    full = ("data", "seq", "stage")
    # What the data-axis reduce-scatter leaves to sum over shards.
    rest = tuple(sorted(
        ax for ax, ways in t.plan_axes
        if ax in ("stage", "seq") and ways > 1
    ))
    out = []
    for prim, axes, dt, scope, elems in grads:
        axes_s = tuple(sorted(axes))
        if not t.plan_fsdp:
            if prim != "psum" or axes_s != full:
                out.append(ctx.finding(
                    "plan-grad-fabric",
                    f"plan_grad {prim} over {tuple(axes)} — the "
                    "gradient reduction is one fused psum over "
                    "('stage', 'data', 'seq')",
                ))
        elif prim == "reduce_scatter":
            if tuple(axes) != ("data",) or dt != "f32":
                out.append(ctx.finding(
                    "plan-grad-fabric",
                    f"plan_grad reduce_scatter of {dt} over "
                    f"{tuple(axes)} — an fsdp plan reduce-scatters "
                    "float32 over ('data',) only",
                ))
        elif prim != "psum" or axes_s not in (full, rest):
            out.append(ctx.finding(
                "plan-grad-fabric",
                f"plan_grad {prim} over {tuple(axes)} — beside its "
                "reduce-scatters an fsdp plan sums over "
                f"{rest or '(nothing)'} (shards) or over ('stage', "
                "'data', 'seq') (replicated leaves), never over "
                "'data' apart",
            ))
    gathers = [
        r for r in t.plan_collective_records
        if _scope_word("plan_fsdp_gather", r[3])
    ]
    if t.plan_fsdp and not any(
        r[0] == "reduce_scatter" for r in gathers
    ):
        out.append(ctx.finding(
            "plan-grad-fabric",
            "an fsdp plan traced no plan_fsdp_gather reduce_scatter "
            "— its block gradients do not leave the backward scan "
            "as the per-block gather's transpose",
        ))
    for prim, axes, dt, scope, elems in gathers:
        if prim == "reduce_scatter" and t.plan_fsdp:
            if tuple(axes) != ("data",) or dt != "f32":
                out.append(ctx.finding(
                    "plan-grad-fabric",
                    f"plan_fsdp_gather reduce_scatter of {dt} over "
                    f"{tuple(axes)} — a block's gradient reduces in "
                    "float32 over ('data',) only",
                ))
        elif prim != "all_gather" or tuple(axes) != ("data",):
            out.append(ctx.finding(
                "plan-grad-fabric",
                f"plan_fsdp_gather {prim} over {tuple(axes)} — the "
                "ZeRO-3 weight gather rides ('data',) only",
            ))
    return out


__all__ = [
    "Finding",
    "LintContext",
    "LintTarget",
    "REGISTRY",
    "Rule",
    "rule",
    "run_rules",
]
