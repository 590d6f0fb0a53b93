"""One `ParallelPlan`: composable PP x TP/SP x FSDP-DP x EP over the
factored mesh (ISSUE 19).

The per-axis engines (`pipeline.py`, `sequence_parallel.py`, `fsdp.py`,
`expert_parallel.py`) each own a whole mesh; this module composes their
mechanisms into ONE engine driven by a declarative plan

    ParallelPlan(pp=S_pp, tp_or_sp=S_tp, dp=S_dp, fsdp=..., ep=S_ep)

assigned onto the stage-major ('stage', 'data', 'seq') mesh of
`runtime.mesh.make_plan_mesh` — the Megatron-LM SC'21 composition
(Narayanan et al., PAPERS.md): pipeline stages across the slow fabric
(stage outermost = DCN; their only traffic is one activation ppermute
per tick), tensor/sequence sharding within a slice ('seq' innermost =
ICI neighbors for the ring-attention / collective-matmul rings),
ZeRO-style FSDP data parallelism on the remainder, and the expert axis
riding the data fabric (DeepSpeed-MoE, Rajbhandari ICML'22).

Why one fully-MANUAL shard_map: on this jax (0.4.37) a partial-auto
shard_map (manual 'stage', GSPMD inside) dies in XLA SPMD partitioning
(PartitionId UNIMPLEMENTED / IsManualSubgroup check-fail), so hybrid
manual-over-auto composition is not a viable substrate. Every axis's
mechanism therefore composes at the shard_map level, reusing the
single-axis engines' building blocks verbatim:

  stage — the gpipe fill-drain tick loop of `PipelineEngine`
          (`pipeline_forward`): M + S - 1 ticks, one packed-activation
          ppermute per tick (scope `plan_wire`), loss ONLY on the last
          stage with NO psum before grad (under check_vma=False a
          differentiated psum mis-scales cotangents; the reversed
          ppermutes alone carry the true cotangents upstream). The
          per-tick program is UNIFORM across stages — every device
          runs stem + (its stage's block slice, a `dynamic_slice` of
          the STACKED block params scanned with one shared block
          apply) + head, with `where`-selects on the stage index for
          the wire/loss — never `lax.switch` over per-stage closures:
          a 'seq' collective inside a stage-selected branch lowers to
          ONE collective op spanning all devices while only that
          stage's devices execute it, which deadlocks the SPMD
          runtime at the rendezvous.
  seq   — `CausalLMSequenceParallelEngine`'s per-shard GPT math: the
          shard-aware position slice, ring attention with causal=True
          over 'seq', host-side `lm_targets` sharded alongside the ids
          so every shard scores its own tokens locally, optional
          `LocalCollectiveMatmul(axis='seq')` FFN rings. This is the
          plan's `tp_or_sp` leg (Megatron-SP: sequence sharding with
          TP-style rings within ICI).
  data  — the SP/DDP gradient discipline: per-device grads are
          complementary pieces (zero off-stage, partial per seq shard,
          per-replica sums over 'data'), so ONE fused psum over
          ('stage', 'data', 'seq') (scope `plan_grad`) divided by the
          global valid-token count reproduces the dense mean-loss
          gradient exactly. `fsdp=True` instead shards parameters and
          optimizer moments 1/dp at rest (`fsdp.fsdp_specs` over
          'data') and never holds the model or its gradient whole —
          ZeRO-3 on the plan's data axis (ISSUE 33): the block scan
          all-gathers each block's matrices as it reaches them, one
          block ahead and in the compute dtype (scope
          `plan_fsdp_gather`), the step differentiates with respect
          to the 1/dp rows, so each block's gradient leaves the
          backward scan as the gather's transpose, a float32
          reduce-scatter over 'data', while the block before it
          computes; stem, head and the blocks' vectors gather once a
          step and reduce-scatter under `plan_grad`; what is left to
          sum is 'stage' and 'seq', over shards.
  ep    — experts ride the data axes: an `ep > 1` plan routes through
          `ExpertParallelLMEngine`'s hierarchical dispatch (the EP x DP
          composition that engine already is). The manual composed
          engine refuses MoE configs (the per-stage aux-loss channel
          through the gpipe scalar is future work — see ROADMAP).

Every single-axis engine is the degenerate 1-on-the-other-axes plan:
`build_plan_engine` routes pp-only plans to `LMPipelineEngine`, sp-only
plans to `CausalLMSequenceParallelEngine`, ep plans to
`ExpertParallelLMEngine`, and everything genuinely composed (or
fsdp-sharded) to `ComposedPlanEngine`. Parity — degenerate == existing
engine == dense, and composed PP2xSP2xDP2 == dense at rtol 1e-5 — is
pinned in tests/test_plan.py; the per-axis fabric contract of the
composed lowering is linted by the `plan-*` rules (`analysis/rules.py`).
"""

from __future__ import annotations

import dataclasses
import re
from functools import partial
from typing import Any, Optional

import jax
import jax.numpy as jnp
from jax import lax
from jax.sharding import Mesh, NamedSharding, PartitionSpec as P

from distributed_model_parallel_tpu.models import layers as L
from distributed_model_parallel_tpu.models.staging import (
    stack_block_params,
)
from distributed_model_parallel_tpu.ops.head_loss import head_loss
from distributed_model_parallel_tpu.parallel.data_parallel import (
    TrainState,
    _metrics,
    _place_batch,
)
from distributed_model_parallel_tpu.parallel.pipeline import (
    PIPE_BWD,
    PIPE_FWD,
    PIPE_IDLE,
)
from distributed_model_parallel_tpu.parallel.sequence_parallel import (
    ATTENTION,
    _check_seq_len,
    _seq_matmul_policy,
)
from distributed_model_parallel_tpu.runtime.compat import shard_map
from distributed_model_parallel_tpu.runtime.mesh import make_plan_mesh
from distributed_model_parallel_tpu.training.metrics import cross_entropy

PLAN_AXES = ("pp", "tp_or_sp", "dp", "ep")
# Spec-string vocabulary: every alias maps to its ParallelPlan field.
# "sp" and "tp" both mean the tp_or_sp axis (the within-ICI leg is
# implemented as Megatron-SP sequence sharding with TP-style rings);
# "fsdp" means the dp axis with parameter sharding on.
_TOKEN_FIELD = {
    "pp": "pp", "sp": "tp_or_sp", "tp": "tp_or_sp",
    "dp": "dp", "fsdp": "dp", "ep": "ep",
}
# The pp token optionally carries the pipeline SCHEDULE as a dashed
# suffix: `pp2-1f1b` (PipeDream-flush), `pp4-int2` (Megatron
# interleaved with V=2 virtual chunks per stage). No suffix = gpipe.
_TOKEN_RE = re.compile(
    r"^(pp|sp|tp|dp|fsdp|ep)(\d+)(?:-(1f1b|int(\d+)))?$"
)
PLAN_SCHEDULES = ("gpipe", "1f1b", "interleaved")


@dataclasses.dataclass(frozen=True)
class ParallelPlan:
    """Declarative axis assignment: how many ways each parallelism axis
    runs. `fsdp` shards parameters/moments over the dp axis (ZeRO-3);
    `tp_or_sp` is the within-slice tensor/sequence leg. The product of
    all axes is the device count the plan occupies."""

    pp: int = 1
    tp_or_sp: int = 1
    dp: int = 1
    ep: int = 1
    fsdp: bool = False
    # Pipeline schedule for the pp axis — execution-only (never part of
    # the parameter layout): "gpipe" (fill-drain), "1f1b"
    # (PipeDream-flush, O(S) activation stash), or "interleaved"
    # (Megatron virtual pipeline; `virtual_stages` chunks per stage).
    schedule: str = "gpipe"
    virtual_stages: int = 1

    def __post_init__(self):
        for name in PLAN_AXES:
            v = getattr(self, name)
            if not isinstance(v, int) or v < 1:
                raise ValueError(
                    f"ParallelPlan.{name} must be an int >= 1, got {v!r}"
                )
        if self.fsdp and self.dp < 2:
            raise ValueError(
                "ParallelPlan(fsdp=True) shards parameters over the dp "
                f"axis; dp={self.dp} leaves nothing to shard"
            )
        if self.schedule not in PLAN_SCHEDULES:
            raise ValueError(
                f"ParallelPlan.schedule must be one of "
                f"{PLAN_SCHEDULES}, got {self.schedule!r} (the --plan "
                "pp token sets it: pp2, pp2-1f1b, pp4-int2)"
            )
        if not isinstance(self.virtual_stages, int) or \
                self.virtual_stages < 1:
            raise ValueError(
                "ParallelPlan.virtual_stages must be an int >= 1, got "
                f"{self.virtual_stages!r}"
            )
        if self.schedule == "interleaved" and self.virtual_stages < 2:
            raise ValueError(
                "ParallelPlan.schedule='interleaved' needs "
                "virtual_stages >= 2 (the --plan token spells it "
                "pp<S>-int<V>, e.g. pp4-int2); V=1 interleaving IS "
                "1f1b — spell it pp<S>-1f1b"
            )
        if self.schedule != "interleaved" and self.virtual_stages != 1:
            raise ValueError(
                f"ParallelPlan.virtual_stages={self.virtual_stages} "
                f"only rides schedule='interleaved', not "
                f"{self.schedule!r}"
            )
        if self.schedule != "gpipe" and self.pp < 2:
            raise ValueError(
                f"ParallelPlan.schedule={self.schedule!r} schedules "
                f"the pp axis, but pp={self.pp} has no pipeline — give "
                "the --plan a pp token >= 2 (e.g. pp2-1f1b)"
            )

    @property
    def num_devices(self) -> int:
        return self.pp * self.tp_or_sp * self.dp * self.ep

    @property
    def spec(self) -> str:
        """Canonical spec string (`parse_plan` round-trips it)."""
        bits = []
        if self.pp > 1:
            sched = (
                "" if self.schedule == "gpipe"
                else "-1f1b" if self.schedule == "1f1b"
                else f"-int{self.virtual_stages}"
            )
            bits.append(f"pp{self.pp}{sched}")
        if self.tp_or_sp > 1:
            bits.append(f"sp{self.tp_or_sp}")
        if self.dp > 1 or not bits:
            bits.append(("fsdp" if self.fsdp else "dp") + str(self.dp))
        if self.ep > 1:
            bits.append(f"ep{self.ep}")
        return "x".join(bits)


def parse_plan(spec: str) -> ParallelPlan:
    """`"pp2xsp2xdp2"` -> ParallelPlan(pp=2, tp_or_sp=2, dp=2).

    Tokens are axis-name + ways, joined by 'x': pp / sp (alias tp) /
    dp / fsdp (dp with parameter sharding) / ep. Each axis may appear
    once; omitted axes default to 1. The pp token may carry a pipeline
    schedule suffix — `pp2-1f1b` or `pp4-int2` (interleaved, V=2
    chunks per stage) — default gpipe; a trailing dash before the next
    'x' is tolerated (`pp2-1f1b-xsp2` == `pp2-1f1bxsp2`)."""
    fields: dict = {}
    fsdp = False
    schedule, virtual = "gpipe", 1
    for token in str(spec).strip().lower().split("x"):
        # The dashed schedule suffix makes `pp2-1f1b-xsp2` a natural
        # way to write the spec; strip the dangling separator.
        token = token.strip().rstrip("-")
        m = _TOKEN_RE.match(token)
        if not m:
            raise ValueError(
                f"bad plan token {token!r} in {spec!r}: expected "
                "<axis><ways> with axis in pp/sp/tp/dp/fsdp/ep and an "
                "optional pp schedule suffix (e.g. 'pp2xsp2xdp2', "
                "'fsdp4', 'pp2-1f1bxdp4', 'pp4-int2')"
            )
        name, ways, sched_sfx = m.group(1), int(m.group(2)), m.group(3)
        field = _TOKEN_FIELD[name]
        if field in fields:
            raise ValueError(
                f"plan {spec!r} names the {field} axis twice"
            )
        fields[field] = ways
        if name == "fsdp":
            fsdp = True
        if sched_sfx is not None:
            if name != "pp":
                raise ValueError(
                    f"plan {spec!r}: the schedule suffix "
                    f"'-{sched_sfx}' rides the pp token only "
                    f"(ParallelPlan.schedule schedules the pipeline "
                    f"axis), not {name!r}"
                )
            if sched_sfx == "1f1b":
                schedule = "1f1b"
            else:
                virtual = int(m.group(4))
                if virtual < 2:
                    raise ValueError(
                        f"plan {spec!r}: interleaving needs >= 2 "
                        "virtual chunks per stage (pp<S>-int<V> with "
                        "V >= 2); V=1 interleaving IS 1f1b — spell "
                        "it pp<S>-1f1b"
                    )
                schedule = "interleaved"
    return ParallelPlan(
        fsdp=fsdp, schedule=schedule, virtual_stages=virtual, **fields
    )


def _local_sums(logits, targets):
    """Per-shard metric SUMS over this shard's tokens (the
    `CausalLMSequenceParallelEngine.local_sums` contract, one copy for
    the composed engine)."""
    b, tl, v = logits.shape
    flat_logits = logits.reshape(b * tl, v)
    flat_t = targets.reshape(b * tl)
    return _metrics(
        cross_entropy(flat_logits, flat_t), flat_logits, flat_t
    )


# Collective scope words the plan lint rules pin (`analysis/rules.py`):
# the pipeline wire, the fused gradient reduction, the FSDP weight
# gather. (The 'seq' rings carry their own op scopes — kv_ring,
# ag_matmul, matmul_rs.)
WIRE_SCOPE = "plan_wire"
GRAD_SCOPE = "plan_grad"
GATHER_SCOPE = "plan_fsdp_gather"


@dataclasses.dataclass
class ComposedPlanEngine:
    """GPT LM training under a genuinely composed ParallelPlan: one
    fully-manual shard_map over the stage-major ('stage', 'data',
    'seq') plan mesh (module docstring).

    Parameters are identical in structure to `gpt_lm(cfg)` — the
    CANONICAL (dense) pytree, replicated over 'stage' and 'seq' at
    rest — so dense checkpoints and every other engine's
    `to_canonical` form interoperate; with `plan.fsdp` each leaf is
    additionally sharded 1/dp over 'data' (`fsdp.fsdp_specs`), the
    optimizer moments follow it, and the sharded-checkpoint manifest
    records the layout through the same `state_partition_specs` seam
    as `FSDPEngine` (cross-plan resharding is pinned in
    tests/test_checkpoint_sharded.py)."""

    cfg: Any  # models.gpt.GPTConfig
    optimizer: Any  # SGD | AdamW (init/update/state_shardings protocol)
    mesh: Mesh
    plan: ParallelPlan = ParallelPlan()
    # Microbatch count for the gpipe tick loop (None = the stage count,
    # the minimum that fills the pipeline).
    num_microbatches: Optional[int] = None
    attention: str = "ring"
    donate: bool = True
    compute_dtype: Any = None
    remat: bool = False
    # FFN pair as chunked ppermute rings over 'seq' (default off) — see
    # SequenceParallelEngine.collective_matmul.
    collective_matmul: bool = False
    # FSDP leaves below this many elements stay replicated.
    min_shard_elems: int = 1024

    def __post_init__(self):
        from distributed_model_parallel_tpu.models.gpt import (
            decoder_blocks,
            gpt_lm,
            head_apply as lm_head_apply,
            head_operands as lm_head_operands,
            lm_targets,
            stem_apply as lm_stem_apply,
        )
        from distributed_model_parallel_tpu.ops.pallas_attention import (
            local_attention_kind,
            local_causal_attention,
        )

        mesh = self.mesh
        plan = self.plan
        for ax, ways in (
            ("stage", plan.pp), ("data", plan.dp), ("seq", plan.tp_or_sp)
        ):
            if ax not in mesh.axis_names:
                raise ValueError(
                    f"composed-plan mesh needs a '{ax}' axis "
                    f"(make_plan_mesh); got {mesh.axis_names}"
                )
            if int(mesh.shape[ax]) != ways:
                raise ValueError(
                    f"plan {plan.spec!r} wants {ways}-way '{ax}' but the "
                    f"mesh carries {int(mesh.shape[ax])}"
                )
        if plan.ep > 1:
            raise NotImplementedError(
                "ComposedPlanEngine does not run the expert axis; "
                "ep > 1 plans route through "
                "parallel/expert_parallel.ExpertParallelLMEngine "
                "(build_plan_engine does this)"
            )
        cfg = self.cfg
        if getattr(cfg, "num_experts", 0) > 0:
            # Same objection as the SP engines: the per-stage MoE
            # aux-loss channel through the gpipe loss scalar is not
            # built; the MoE text path is ExpertParallelLMEngine.
            raise NotImplementedError(
                "GPTConfig.num_experts > 0 is not supported by "
                "ComposedPlanEngine; train MoE LMs with an ep plan "
                "(parallel/expert_parallel.ExpertParallelLMEngine)."
            )
        if self.attention not in ATTENTION:
            raise ValueError(
                f"attention must be one of {sorted(ATTENTION)}, "
                f"got {self.attention!r}"
            )
        S = plan.pp
        Vs = plan.virtual_stages
        C = S * Vs  # logical pipeline depth (chunks across all stages)
        M = self.num_microbatches or (
            C if plan.schedule == "interleaved" else S
        )
        if M < S:
            raise ValueError(
                f"num_microbatches={M} (--microbatches) cannot fill "
                f"a {S}-stage pipeline (need M >= ParallelPlan.pp)"
            )
        if plan.schedule == "interleaved" and M < C:
            raise ValueError(
                f"num_microbatches={M} (--microbatches) cannot fill "
                f"the interleaved pipeline of plan {plan.spec!r}: its "
                f"ParallelPlan.virtual_stages={Vs} runs pp*V={C} "
                "logical chunks (need num_microbatches >= pp*V)"
            )
        self.num_microbatches = M
        if cfg.num_layers % C:
            # The uniform tick program slices a STACKED block-param
            # tensor by (chunk, stage) index, so every logical chunk
            # must carry the same number of blocks. Uneven cuts are
            # the single-axis pipeline's territory.
            raise ValueError(
                f"plan {plan.spec!r} cuts the block stack into "
                f"pp*virtual_stages={C} uniform chunks, which must "
                f"divide cfg.num_layers={cfg.num_layers} (--layers; "
                "uneven cuts -> parallel/pipeline.LMPipelineEngine)"
            )
        # Scheduled tick tables (ISSUE 20): the plan's schedule field
        # selects the tick program. gpipe keeps the autodiff fill-drain
        # loop; 1f1b / interleaved replay the single-axis engine's
        # static (tick, microbatch, chunk, direction) tables with a
        # hand-scheduled per-tick vjp. The schedule is EXECUTION-ONLY:
        # parameter layout, checkpoints, and the canonical seam are
        # identical across schedules of the same axis factorization.
        self._sched = None
        self._last_sched_trace = None
        if plan.schedule != "gpipe":
            import numpy as np

            from distributed_model_parallel_tpu.parallel.pipeline import (
                ScheduleTicks,
                build_1f1b_schedule,
                build_interleaved_schedule,
            )

            if plan.schedule == "1f1b":
                s1 = build_1f1b_schedule(S, M)
                zc = np.zeros((s1.num_ticks, S), np.int32)
                self._sched = ScheduleTicks(
                    s1.work, s1.micro, zc,
                    s1.recv_fwd, s1.recv_fwd_m, zc,
                    s1.recv_bwd, s1.recv_bwd_m, zc,
                    s1.num_ticks, s1.stash_depth, s1.cot_depth, 1,
                )
            else:
                self._sched = build_interleaved_schedule(S, M, Vs)
        self._lm_targets = partial(
            lm_targets, pad_token_id=cfg.pad_token_id
        )
        sp = plan.tp_or_sp
        # With the sequence whole on a chip (sp == 1) `attention` — how
        # the 'seq' axis is distributed — has nothing to say: the
        # engine attends locally and the call itself picks the flash
        # kernels or the dense XLA graph from what it can observe
        # (ops/pallas_attention.local_causal_attention). Which one the
        # step holds, "flash" | "dense" (None under sp > 1): the
        # selector's answer at cfg.max_position until a step is traced,
        # then what that trace picked at the batch's own length.
        self.local_attention = None
        if sp > 1:
            attn_fn = partial(
                ATTENTION[self.attention], axis_name="seq", causal=True
            )
        else:
            self.local_attention = local_attention_kind(
                cfg.max_position, cfg.max_position, None
            )

            def attn_fn(q, k, v, mask=None):
                self.local_attention = local_attention_kind(
                    q.shape[1], k.shape[1], mask
                )
                return local_causal_attention(q, k, v, mask)
        self._matmul = _seq_matmul_policy(
            self.collective_matmul and sp > 1, cfg.ffn_dim, sp
        )
        mm = self._matmul
        self._repl = NamedSharding(mesh, P())
        self._batch = NamedSharding(mesh, P(("data",), ("seq",)))
        # Dense-parameter twin: init AND the canonical checkpoint form.
        self._full = gpt_lm(cfg)
        # With num_experts == 0 (enforced above) every decoder block is
        # the same encoder_layer module — one shared apply over stacked
        # per-block params is exact.
        block = decoder_blocks(cfg, attn_fn)[0]
        block_apply = (L.remat(block) if self.remat else block).apply
        Lps = cfg.num_layers // S  # blocks per stage (uniform)
        drop = L.dropout(cfg.dropout_rate)
        cdt = self.compute_dtype
        wire_dt = jnp.dtype(cdt if cdt is not None else jnp.float32)
        V = cfg.vocab_size
        D = cfg.dim
        reduce_axes = ("stage", "data", "seq")
        self._reduce_axes = reduce_axes

        fsdp = plan.fsdp
        # Where an fsdp plan materializes its parameters and reduces
        # their gradients, in words for a log line (None without
        # fsdp: the parameters are replicated and whole gradients meet
        # in the one fused psum).
        self.fsdp_exchange = None
        if fsdp:
            self.fsdp_exchange = (
                f"all-gather {wire_dt.name} per block, one ahead; "
                "reduce-scatter float32"
            )
            from distributed_model_parallel_tpu.parallel.fsdp import (
                fsdp_specs,
            )

            key_aval = jax.ShapeDtypeStruct((2,), jnp.uint32)
            p_aval, s_aval = jax.eval_shape(self._full.init, key_aval)
            pspecs = fsdp_specs(
                p_aval, plan.dp,
                min_shard_elems=self.min_shard_elems, axes="data",
            )
            is_spec = lambda x: isinstance(x, P)  # noqa: E731
            param_sh = jax.tree_util.tree_map(
                lambda spec: NamedSharding(mesh, spec), pspecs,
                is_leaf=is_spec,
            )
            self._state_sh = TrainState(
                param_sh,
                jax.tree_util.tree_map(lambda _: self._repl, s_aval),
                self.optimizer.state_shardings(param_sh, self._repl),
                self._repl,
            )
            state_specs = TrainState(
                pspecs,
                jax.tree_util.tree_map(lambda _: P(), s_aval),
                self.optimizer.state_shardings(pspecs, P()),
                P(),
            )
            # The sharded-checkpoint spec seam (FSDPEngine convention).
            self._state_pspecs = state_specs
            n_dp = plan.dp

            def _sharded_dim(spec):
                for d, part in enumerate(spec):
                    if part is not None:
                        return d
                return None

            def _gather_leaf(leaf, spec, off=0):
                """All-gather one 1/dp leaf over 'data', float32 as
                it rests. `off` shifts the sharded dim past leading
                stack/chunk axes."""
                d = _sharded_dim(spec)
                if d is None:
                    return leaf
                return lax.all_gather(
                    leaf, "data", axis=d + off, tiled=True
                )

            def _in_scan(spec):
                """Which block leaves gather per block inside the
                scan: the matrices fsdp shards (all but a thousandth
                of a block's bytes). The vectors of every block gather
                once beside stem and head: a dozen small collectives
                in the scan's body buy nothing, and XLA merges their
                reductions with the matrices' into one all-reduce."""
                return _sharded_dim(spec) is not None and len(spec) >= 2

            @partial(jax.custom_vjp, nondiff_argnums=(1,))
            def _gather_matrices(mats, dims):
                """All-gather one block's matrices over 'data' (mats[i]
                is 1/dp along dims[i]) as ONE collective in the
                COMPUTE dtype. One: XLA:TPU keeps a single
                asynchronous all-gather in flight, and of four to a
                block it hides the two small ones behind the block's
                products and runs the two large ones on their own —
                so each matrix travels sharded-dim-first as rows of
                one buffer per row width (a GPT block's matrices all
                have `dim` for it). Compute dtype: the block casts
                every matrix to the activation dtype at the product
                (`L._proj`), so casting ahead of the gather is
                bit-identical forward and halves its bytes. The
                transpose converts each device's cotangent to float32
                BEFORE it reduces — the sum is the parameters'
                precision whatever the wire carried forward — and
                reduce-scatters each matrix over a leading axis of dp
                slabs: scattered along a minor dimension whose shard
                does not fill the chip's tiles, XLA:TPU all-reduces
                the whole matrix and slices it."""
                rows = []
                for m, d in zip(mats, dims):
                    x = jnp.moveaxis(m, d, 0)
                    x = x if cdt is None else x.astype(cdt)
                    rows.append(x.reshape(x.shape[0], -1))
                out = [None] * len(mats)
                for width in sorted({r.shape[1] for r in rows}):
                    group = [
                        i for i, r in enumerate(rows)
                        if r.shape[1] == width
                    ]
                    slabs = lax.all_gather(
                        jnp.concatenate([rows[i] for i in group]),
                        "data", axis=0, tiled=False,
                    )
                    at = 0
                    for i in group:
                        m, d, n = mats[i], dims[i], rows[i].shape[0]
                        rest = m.shape[:d] + m.shape[d + 1:]
                        out[i] = jnp.moveaxis(
                            slabs[:, at:at + n].reshape(
                                (n_dp * n,) + rest
                            ),
                            0, d,
                        )
                        at += n
                return tuple(out)

            def _gather_matrices_fwd(mats, dims):
                return _gather_matrices(mats, dims), None

            def _gather_matrices_bwd(dims, _, cts):
                def reduce(ct, d):
                    sh = ct.shape
                    slabs = jnp.moveaxis(
                        ct.astype(jnp.float32).reshape(
                            sh[:d] + (n_dp, sh[d] // n_dp) + sh[d + 1:]
                        ),
                        d, 0,
                    )
                    return lax.psum_scatter(
                        slabs, "data", scatter_dimension=0, tiled=False
                    )

                return (tuple(
                    reduce(ct, d) for ct, d in zip(cts, dims)
                ),)

            _gather_matrices.defvjp(
                _gather_matrices_fwd, _gather_matrices_bwd
            )

            # Per-parameter layout note: fsdp_specs is shape-driven
            # and every decoder block has identical leaf shapes, so
            # one block's spec tree describes them all.
            block_pspecs = pspecs["blocks"]["0"]

            def gather_block(shard):
                """One block's matrices, whole, from this device's
                1/dp rows (scope `plan_fsdp_gather`). Under autodiff
                the transpose is that block's float32 reduce-scatter
                over 'data'."""
                leaves, treedef = jax.tree_util.tree_flatten(shard)
                specs = treedef.flatten_up_to(block_pspecs)
                picked = [
                    i for i, spec in enumerate(specs) if _in_scan(spec)
                ]
                with jax.named_scope(GATHER_SCOPE):
                    whole = _gather_matrices(
                        tuple(leaves[i] for i in picked),
                        tuple(_sharded_dim(specs[i]) for i in picked),
                    )
                for i, leaf in zip(picked, whole):
                    leaves[i] = leaf
                return jax.tree_util.tree_unflatten(treedef, leaves)

            def _block_and_gather(params, state, x, ctx):
                """Block j from its gathered params beside the gather
                of block j+1: the scan body of an fsdp plan."""
                pb, nxt = params
                # Tie block j+1's rows to block j's input: the gather
                # is issued when block j starts, not earlier. Without
                # the tie the gathers depend on nothing a tick
                # computes, and autodiff's partial evaluation hoists
                # ALL of them out of the tick loop into a scan of
                # their own — the whole model gathered ahead of the
                # forward pass again.
                h, mask = x
                nxt, h = lax.optimization_barrier((nxt, h))
                y, _ = block.apply(pb, {}, (h, mask), ctx)
                return (y, gather_block(nxt)), state

            # Under remat the gather sits INSIDE the checkpoint with
            # the block: jax.checkpoint stages its body into the
            # differentiated scan, and a gather left outside it runs
            # in a scan of its own ahead of the whole forward pass.
            # (The recomputation's gather has no reader and is dead
            # code; the gathered block itself is the body's input and
            # is saved.)
            block_and_gather = L.Layer(None, _block_and_gather)
            if self.remat:
                block_and_gather = L.remat(block_and_gather)
            block_and_gather = block_and_gather.apply

            # What the data-axis reduce-scatter leaves to sum: the
            # other mesh axes that exist (a size-1 axis still costs an
            # all-reduce pass over its operand on the device).
            rest_axes = tuple(
                ax for ax in ("stage", "seq") if int(mesh.shape[ax]) > 1
            )

            def reduce_rest(x):
                return lax.psum(x, rest_axes) if rest_axes else x

            def reduce_whole(x, spec, off=0):
                """A gradient held whole (its leaf was gathered once,
                outside the scans): reduce-scatter over 'data' onto
                this device's rows, then what is left. `off` shifts
                the sharded dim past a leading stack axis."""
                d = _sharded_dim(spec)
                if d is None:
                    return lax.psum(x, reduce_axes)
                return reduce_rest(lax.psum_scatter(
                    x, "data", scatter_dimension=d + off, tiled=True
                ))

            def reduce_block(x, spec):
                """A stacked (layers, ...) block gradient: a matrix
                left the backward scan already reduce-scattered over
                'data'; a vector is whole (gathered once for every
                block), as is a leaf fsdp left replicated."""
                if _in_scan(spec):
                    return reduce_rest(x)
                return reduce_whole(x, spec, 1)
        else:
            state_specs = P()
            # The manifest seam still declares the full layout for
            # replicated plans: every leaf P() (the canonical at-rest
            # form), so layout-aware tooling reads ONE convention
            # across plans.
            key_aval = jax.ShapeDtypeStruct((2,), jnp.uint32)
            p_aval, s_aval = jax.eval_shape(self._full.init, key_aval)
            repl_specs = jax.tree_util.tree_map(lambda _: P(), p_aval)
            self._state_pspecs = TrainState(
                repl_specs,
                jax.tree_util.tree_map(lambda _: P(), s_aval),
                self.optimizer.state_shardings(repl_specs, P()),
                P(),
            )

        def gather_stage_mat(params, n_virtual):
            """This device's execution bundle {stem, chunks, head}:
            `chunks` leaves are (n_virtual, Lpc, ...) rows of the
            STACKED block params for the logical chunks v*S + s_idx
            this stage runs (n_virtual=1 is the gpipe stage slice;
            the interleaved train path passes the plan's
            virtual_stages). For fsdp plans `chunks` STAYS 1/dp over
            'data': the block scan gathers each block as it reaches
            it (`scan_blocks`), and the step differentiates with
            respect to these sharded rows. Stem and head, used once a
            step, gather whole here (scope `plan_fsdp_gather`);
            `finish_grads` reduce-scatters their gradients."""
            n_chunk_layers = cfg.num_layers // (S * n_virtual)
            s_idx = lax.axis_index("stage")
            stacked = stack_block_params(
                params["blocks"], cfg.num_layers
            )

            def chunk_rows(leaf):
                return jnp.stack([
                    lax.dynamic_slice_in_dim(
                        leaf, (v * S + s_idx) * n_chunk_layers,
                        n_chunk_layers, axis=0,
                    )
                    for v in range(n_virtual)
                ])

            chunks = jax.tree_util.tree_map(chunk_rows, stacked)
            stem, head = params["stem"], params["head"]
            if fsdp:
                with jax.named_scope(GATHER_SCOPE):
                    chunks = jax.tree_util.tree_map(
                        # The (chunk, layer) axes sit ahead of the
                        # leaf's own dims: the sharded dim moved by 2.
                        lambda leaf, spec: (
                            leaf if _in_scan(spec)
                            else _gather_leaf(leaf, spec, 2)
                        ),
                        chunks, block_pspecs,
                    )
                    stem = jax.tree_util.tree_map(
                        _gather_leaf, stem, pspecs["stem"]
                    )
                    head = jax.tree_util.tree_map(
                        _gather_leaf, head, pspecs["head"]
                    )
            return {"stem": stem, "chunks": chunks, "head": head}

        def scan_blocks(rows, blk_ids, x, block_ctx):
            """Run the blocks whose stacked params are `rows` (n, ...)
            over the carry `x` = (h, mask), one shared block apply
            under the dense Context.child chain. For an fsdp plan the
            matrices of `rows` are this device's 1/dp: the scan
            gathers ONE block AHEAD — the gathered block rides the
            carry, the body runs block j from it while the all-gather
            of block j+1 is in flight. Under autodiff that carry's
            cotangent is block j+1's whole gradient, reduce-scattered
            over 'data' while block j's backward pass computes: the
            prefetch forward IS the overlap backward. The last block
            runs after the scan (nothing is left to gather beside
            it)."""

            def run(apply, pb, x, j):
                y, _ = apply(pb, {}, x, block_ctx.child(j))
                return y

            if not fsdp:
                def blk(x, sl):
                    pb, j = sl
                    return run(block_apply, pb, x, j), None

                x, _ = lax.scan(blk, x, (rows, blk_ids))
                return x

            def row(sl):
                return jax.tree_util.tree_map(lambda r: r[sl], rows)

            def blk(carry, sl):
                x, pb = carry
                nxt, j = sl
                return run(block_and_gather, (pb, nxt), x, j), None

            carry = (x, gather_block(row(0)))
            if blk_ids.shape[0] > 1:
                carry, _ = lax.scan(
                    blk, carry, (row(slice(1, None)), blk_ids[:-1])
                )
            x, last = carry
            return run(block_apply, last, x, blk_ids[-1])

        def finish_grads(g_mat, n_virtual, n_global):
            """Shared gradient post-processing for EVERY schedule:
            the per-chunk block grads back in the full stacked form
            (zeros off-chunk — exactly the transpose of the chunk
            slice), the reduction (scope `plan_grad`), the dense
            mean-loss normalization, then unstack to the canonical
            per-block tree. A plan without fsdp holds whole gradients
            and reduces them with ONE fused psum over ('stage',
            'data', 'seq'). An fsdp plan's block gradients arrive
            from the backward scan already summed over 'data' and
            1/dp (the per-block gather's transpose); stem and head
            are reduce-scattered here; what is left to sum is the
            'stage' and 'seq' axes that exist, over shards. Every
            operand and sum is float32."""
            n_chunk_layers = cfg.num_layers // (S * n_virtual)
            s_idx = lax.axis_index("stage")

            def scatter(leaf):
                if fsdp and S * n_virtual == 1:
                    # The one chunk IS the stack: no zero-filled copy.
                    # (Left to fsdp plans: the others keep the program
                    # they lowered to before ISSUE 33.)
                    return leaf[0]
                full = jnp.zeros(
                    (cfg.num_layers,) + leaf.shape[2:], leaf.dtype
                )
                for v in range(n_virtual):
                    full = lax.dynamic_update_slice_in_dim(
                        full, leaf[v],
                        (v * S + s_idx) * n_chunk_layers, axis=0,
                    )
                return full

            g = {
                "stem": g_mat["stem"],
                "blocks": jax.tree_util.tree_map(
                    scatter, g_mat["chunks"]
                ),
                "head": g_mat["head"],
            }
            with jax.named_scope(GRAD_SCOPE):
                if fsdp:
                    g = {
                        "stem": jax.tree_util.tree_map(
                            reduce_whole, g["stem"], pspecs["stem"]
                        ),
                        "blocks": jax.tree_util.tree_map(
                            reduce_block, g["blocks"], block_pspecs
                        ),
                        "head": jax.tree_util.tree_map(
                            reduce_whole, g["head"], pspecs["head"]
                        ),
                    }
                else:
                    g = jax.tree_util.tree_map(
                        lambda x: lax.psum(x, reduce_axes), g
                    )
            g = jax.tree_util.tree_map(
                lambda x: x / jnp.maximum(n_global, 1.0), g
            )
            return {
                "stem": g["stem"],
                "blocks": {
                    str(j): jax.tree_util.tree_map(
                        lambda x: x[j], g["blocks"]
                    )
                    for j in range(cfg.num_layers)
                },
                "head": g["head"],
            }

        def run_ticks(mat, ids, targets, step, train):
            """The gpipe fill-drain tick program on ONE device
            (`pipeline_forward`'s discipline composed with the SP
            per-shard math), as a UNIFORM per-device program: every
            tick every device runs stem + its stage's block slice (a
            `dynamic_slice` of the STACKED block params, scanned with
            the one shared block apply and the dense Context.child
            chain — stem -> ctx.child(0), block j ->
            ctx.child(1).child(j)) + head, with `where`-selects on the
            stage index picking what reaches the wire and the loss.
            Stage selection must NOT be `lax.switch` over per-stage
            closures: a 'seq' ring collective inside a branch lowers
            to ONE op whose rendezvous spans all devices, but only
            that stage's devices execute the branch — the rest never
            arrive, and the runtime deadlocks. M + S - 1 ticks, one
            `plan_wire` ppermute over 'stage' per tick. Returns the
            LOCAL metric sums (loss masked to the last stage; no psum
            — pipeline autodiff discipline)."""
            bl, tl = ids.shape
            if bl % M:
                raise ValueError(
                    f"local batch {bl} not divisible by "
                    f"num_microbatches {M}"
                )
            mb = bl // M
            h_elems = mb * tl * D
            wire_elems = h_elems + mb * tl  # (h, mask) pair
            # (the last stage's logits ride the same buffer; one stage
            # has no wire and makes no logits)
            buf_size = (
                wire_elems if S == 1 else max(wire_elems, mb * tl * V)
            )
            s_idx = lax.axis_index("stage")
            is_first = s_idx == 0
            is_last = s_idx == S - 1
            q_idx = lax.axis_index("seq")
            ids_mbs = ids.reshape(M, mb, tl)
            tg_mbs = targets.reshape(M, mb, tl)
            rng_base = jax.random.fold_in(
                jax.random.fold_in(
                    jax.random.fold_in(jax.random.PRNGKey(0), step),
                    lax.axis_index("data"),
                ),
                lax.axis_index("seq"),
            )
            # This stage's uniform Lps-block slice, already cut (for
            # fsdp still 1/dp: scan_blocks gathers) by
            # gather_stage_mat's n_virtual=1 layout; finish_grads
            # scatters grads back to exactly these rows (zeros
            # elsewhere), so the stage-psum reassembles the dense
            # gradient.
            my_blocks = jax.tree_util.tree_map(
                lambda x: x[0], mat["chunks"]
            )
            blk_ids = s_idx * Lps + jnp.arange(Lps)

            def pack(flat):
                pad = buf_size - flat.shape[0]
                return jnp.pad(flat, (0, pad)) if pad else flat

            def pack_pair(h, mask):
                return pack(jnp.concatenate([
                    h.astype(wire_dt).reshape(-1),
                    mask.astype(wire_dt).reshape(-1),
                ]))

            def pack_logits(logits):
                return pack(logits.astype(wire_dt).reshape(-1))

            def unpack(buf):
                h = buf[:h_elems].reshape(mb, tl, D)
                mask = buf[h_elems:wire_elems].reshape(mb, tl) > 0.5
                return h, mask

            zeros_m = {
                k: jnp.float32(0.0)
                for k in ("loss_sum", "correct1", "correct5", "count")
            }

            def tick(carry, t):
                buf, m_acc = carry
                m = t - s_idx
                valid = (m >= 0) & (m < M)
                m_safe = jnp.clip(m, 0, M - 1)
                ids_mb = lax.dynamic_index_in_dim(
                    ids_mbs, m_safe, keepdims=False
                )
                tg_mb = lax.dynamic_index_in_dim(
                    tg_mbs, m_safe, keepdims=False
                )
                # Per-(stage, microbatch) dropout key (the pipeline
                # engine's convention).
                rng = jax.random.fold_in(
                    jax.random.fold_in(rng_base, s_idx), m_safe
                )
                ctx = L.Context(
                    train=train, rng=rng, dtype=cdt, matmul=mm
                )
                # Stem on EVERY device (uniform program); only stage
                # 0 keeps its result. Position slice is seq-shard
                # aware, like the SP engines.
                pos = lax.dynamic_slice_in_dim(
                    mat["stem"]["position"], q_idx * tl, tl, axis=0
                )
                h0, mask0 = lm_stem_apply(
                    mat["stem"], ids_mb, cfg, drop, ctx.child(0),
                    positions=pos,
                )
                h_in, mask_in = unpack(buf)
                h = jnp.where(is_first, h0.astype(h_in.dtype), h_in)
                # Bubble ticks carry an all-False wire mask; fall
                # back to the (benign) stem mask there so attention
                # never sees a fully-masked row.
                mask = jnp.where(is_first | ~valid, mask0, mask_in)
                h, mask = scan_blocks(
                    my_blocks, blk_ids, (h, mask), ctx.child(1)
                )
                # Loss counts only on the last stage's valid ticks;
                # stays LOCAL (no psum before grad).
                w = (valid & is_last).astype(jnp.float32)
                if S == 1:
                    # One stage: nothing reads the tick's logits but
                    # the loss, so the head's product and the loss are
                    # one op that never holds them whole.
                    m_tick = head_loss(
                        *lm_head_operands(mat["head"], h), tg_mb
                    )
                else:
                    # Head on EVERY device; only the last stage's
                    # logits reach the loss/wire.
                    logits = lm_head_apply(mat["head"], h)
                    y_pad = jnp.where(
                        is_last, pack_logits(logits), pack_pair(h, mask)
                    )
                    # Mask bubble ticks so garbage never reaches the
                    # wire or the loss.
                    y_pad = jnp.where(
                        valid, y_pad, jnp.zeros_like(y_pad)
                    )
                    m_tick = _local_sums(
                        logits.astype(jnp.float32), tg_mb
                    )
                    with jax.named_scope(WIRE_SCOPE):
                        buf = lax.ppermute(
                            y_pad, "stage",
                            [(i, i + 1) for i in range(S - 1)],
                        )
                m_acc = {
                    k: m_acc[k] + m_tick[k] * w for k in m_acc
                }
                return (buf, m_acc), None

            buf0 = jnp.zeros((buf_size,), wire_dt)
            (_, m_acc), _ = lax.scan(
                tick, (buf0, zeros_m), jnp.arange(M + S - 1)
            )
            return m_acc

        sched = self._sched

        def sched_ticks(mat, ids, targets, step):
            """The table-driven scheduled tick program (1F1B when
            V == 1, Megatron interleaved when V > 1) — the composed
            counterpart of `pipeline.pipeline_ticks`, kept UNIFORM
            across stages: every tick every device runs the full
            chunk program (stem + its chunk's block scan + head)
            under `jax.vjp` with where-masked seeds — the backward
            seed is the delivered cotangent (or the loss gradient on
            the last logical chunk) on backward ticks, zero
            otherwise, so forward/idle ticks contribute exactly-zero
            gradients (vjp is linear in the seed). `lax.cond` over
            the work kind is NOT allowed here, unlike the single-axis
            engine: at sp > 1 the 'seq' ring collectives live inside
            the chunk apply, and a collective inside a branch only
            some devices execute deadlocks the SPMD rendezvous —
            uniformity costs ~2x masked chunk compute per tick and
            buys composability with the seq axis. Two `plan_wire`
            ppermutes per tick (activations up, cotangents down;
            chains under 1F1B, rings under interleaving — the wrap
            edge carries chunk-boundary hops). Forward ticks stash
            the chunk's input window in a per-chunk ring buffer (V*R
            rows — the O(S) activation bound, independent of M);
            backward ticks re-read the slot and recompute under the
            same (logical chunk, microbatch) dropout key. Returns
            (local metric sums, unnormalized mat-space grads) — the
            same contract `finish_grads` consumes on the gpipe
            path."""
            bl, tl = ids.shape
            if bl % M:
                raise ValueError(
                    f"local batch {bl} not divisible by "
                    f"num_microbatches {M}"
                )
            mb = bl // M
            h_elems = mb * tl * D
            wire_elems = h_elems + mb * tl  # (h, mask) pair
            buf_size = max(wire_elems, mb * tl * V)
            T, R, Rc = (
                sched.num_ticks, sched.stash_depth, sched.cot_depth
            )
            # Trace-time record for the structural memory tests: the
            # activation stash traced into this step is (V*R, buf).
            self._last_sched_trace = {
                "num_ticks": T, "stash_depth": R, "cot_depth": Rc,
                "buf_size": buf_size, "num_virtual": Vs,
            }
            work_tab = jnp.asarray(sched.work)
            micro_tab = jnp.asarray(sched.micro)
            chunk_tab = jnp.asarray(sched.chunk)
            recv_f = jnp.asarray(sched.recv_fwd)
            recv_f_m = jnp.asarray(sched.recv_fwd_m)
            recv_f_c = jnp.asarray(sched.recv_fwd_c)
            recv_b = jnp.asarray(sched.recv_bwd)
            recv_b_m = jnp.asarray(sched.recv_bwd_m)
            recv_b_c = jnp.asarray(sched.recv_bwd_c)
            s_idx = lax.axis_index("stage")
            q_idx = lax.axis_index("seq")
            ids_mbs = ids.reshape(M, mb, tl)
            tg_mbs = targets.reshape(M, mb, tl)
            rng_base = jax.random.fold_in(
                jax.random.fold_in(
                    jax.random.fold_in(jax.random.PRNGKey(0), step),
                    lax.axis_index("data"),
                ),
                lax.axis_index("seq"),
            )
            Lpc = cfg.num_layers // C  # blocks per logical chunk

            def pack(flat):
                pad = buf_size - flat.shape[0]
                return jnp.pad(flat, (0, pad)) if pad else flat

            def pack_pair(h, mask):
                return pack(jnp.concatenate([
                    h.astype(wire_dt).reshape(-1),
                    mask.astype(wire_dt).reshape(-1),
                ]))

            def pack_logits(logits):
                return pack(logits.astype(wire_dt).reshape(-1))

            def unpack(buf):
                h = buf[:h_elems].reshape(mb, tl, D)
                mask = buf[h_elems:wire_elems].reshape(mb, tl) > 0.5
                return h, mask

            zeros_m = {
                k: jnp.float32(0.0)
                for k in ("loss_sum", "correct1", "correct5", "count")
            }
            zeros_buf = jnp.zeros((buf_size,), wire_dt)
            if sched.num_virtual == 1:
                up_pairs = [(i, i + 1) for i in range(S - 1)]
                down_pairs = [(i + 1, i) for i in range(S - 1)]
            else:
                # Ring wires: the wrap edge is the chunk-boundary hop
                # (logical v*S+S-1 -> (v+1)*S crosses device S-1 ->
                # device 0).
                up_pairs = [(i, (i + 1) % S) for i in range(S)]
                down_pairs = [((i + 1) % S, i) for i in range(S)]

            def tick(carry, t):
                up_buf, down_buf, stash, cots, m_acc, g_acc = carry
                w = work_tab[t, s_idx]
                m = micro_tab[t, s_idx]
                v = chunk_tab[t, s_idx]
                # Receive-before-compute: the wire buffers hold tick
                # t-1's permute output; the static tables say whether
                # that payload is real and which (chunk, microbatch)
                # ring slot it belongs in.
                slot = recv_f_c[t, s_idx] * R + recv_f_m[t, s_idx] % R
                stash = lax.dynamic_update_index_in_dim(
                    stash,
                    jnp.where(
                        recv_f[t, s_idx], up_buf,
                        lax.dynamic_index_in_dim(stash, slot, 0, False),
                    ),
                    slot, 0,
                )
                cslot = (
                    recv_b_c[t, s_idx] * Rc + recv_b_m[t, s_idx] % Rc
                )
                cots = lax.dynamic_update_index_in_dim(
                    cots,
                    jnp.where(
                        recv_b[t, s_idx], down_buf,
                        lax.dynamic_index_in_dim(cots, cslot, 0, False),
                    ),
                    cslot, 0,
                )
                l = v * S + s_idx  # logical chunk index
                is_first_l = l == 0
                is_last_l = l == C - 1
                valid = w != PIPE_IDLE
                ids_mb = lax.dynamic_index_in_dim(
                    ids_mbs, m, keepdims=False
                )
                tg_mb = lax.dynamic_index_in_dim(
                    tg_mbs, m, keepdims=False
                )
                # Per-(logical chunk, microbatch) dropout key —
                # identical at the forward tick and its backward-tick
                # recompute (and == the gpipe key when V == 1).
                rng = jax.random.fold_in(
                    jax.random.fold_in(rng_base, l), m
                )
                ctx = L.Context(
                    train=True, rng=rng, dtype=cdt, matmul=mm
                )
                x_in = lax.dynamic_index_in_dim(
                    stash, v * R + m % R, 0, False
                )

                def f(mat_, x_buf):
                    pos = lax.dynamic_slice_in_dim(
                        mat_["stem"]["position"], q_idx * tl, tl,
                        axis=0,
                    )
                    h0, mask0 = lm_stem_apply(
                        mat_["stem"], ids_mb, cfg, drop, ctx.child(0),
                        positions=pos,
                    )
                    h_in, mask_in = unpack(x_buf)
                    h = jnp.where(
                        is_first_l, h0.astype(h_in.dtype), h_in
                    )
                    # Idle ticks carry an all-False wire mask; fall
                    # back to the (benign) stem mask there so
                    # attention never sees a fully-masked row.
                    mask = jnp.where(
                        is_first_l | ~valid, mask0, mask_in
                    )
                    cp = jax.tree_util.tree_map(
                        lambda a: lax.dynamic_index_in_dim(
                            a, v, 0, False
                        ),
                        mat_["chunks"],
                    )
                    blk_ids = l * Lpc + jnp.arange(Lpc)
                    h, mask = scan_blocks(
                        cp, blk_ids, (h, mask), ctx.child(1)
                    )
                    logits = lm_head_apply(mat_["head"], h)
                    y_pad = jnp.where(
                        is_last_l, pack_logits(logits),
                        pack_pair(h, mask),
                    )
                    y_pad = jnp.where(
                        valid, y_pad, jnp.zeros_like(y_pad)
                    )
                    m_tick = _local_sums(
                        logits.astype(jnp.float32), tg_mb
                    )
                    return (y_pad, m_tick["loss_sum"]), m_tick

                is_bwd = w == PIPE_BWD
                (y_pad, _), vjp_fn, m_tick = jax.vjp(
                    f, mat, x_in, has_aux=True
                )
                # Seeds: the delivered cotangent on middle-chunk
                # backward ticks, d(loss_sum)=1 on last-chunk
                # backward ticks, zero everywhere else — so the vjp
                # of a forward/idle tick is exactly zero and the
                # unconditional accumulate below is exact.
                y_bar = jnp.where(
                    is_bwd & ~is_last_l,
                    lax.dynamic_index_in_dim(
                        cots, v * Rc + m % Rc, 0, False
                    ),
                    zeros_buf,
                )
                loss_bar = jnp.where(
                    is_bwd & is_last_l,
                    jnp.float32(1.0), jnp.float32(0.0),
                )
                g_mat_t, g_x = vjp_fn((y_bar, loss_bar))
                g_acc = jax.tree_util.tree_map(
                    jnp.add, g_acc, g_mat_t
                )
                # Metrics count each microbatch ONCE: at its
                # last-logical-chunk forward tick (the gpipe loop's
                # valid & is_last weight, table-driven).
                w_m = (
                    (w == PIPE_FWD) & is_last_l
                ).astype(jnp.float32)
                m_acc = {
                    k: m_acc[k] + m_tick[k] * w_m for k in m_acc
                }
                up = jnp.where(w == PIPE_FWD, y_pad, zeros_buf)
                down = jnp.where(is_bwd, g_x, zeros_buf)
                with jax.named_scope(WIRE_SCOPE):
                    up_buf = lax.ppermute(up, "stage", up_pairs)
                    down_buf = lax.ppermute(
                        down, "stage", down_pairs
                    )
                return (
                    up_buf, down_buf, stash, cots, m_acc, g_acc
                ), None

            g0 = jax.tree_util.tree_map(jnp.zeros_like, mat)
            carry0 = (
                zeros_buf, zeros_buf,
                jnp.zeros((Vs * R, buf_size), wire_dt),
                jnp.zeros((Vs * Rc, buf_size), wire_dt),
                zeros_m, g0,
            )
            (_, _, _, _, m_acc, g_acc), _ = lax.scan(
                tick, carry0, jnp.arange(T)
            )
            return m_acc, g_acc

        def shard_step(ts: TrainState, ids, targets, lr):
            mat = gather_stage_mat(ts.params, Vs)
            if sched is None:
                def loss_fn(mat_):
                    m = run_ticks(mat_, ids, targets, ts.step, True)
                    # LOCAL token-loss sum (pipeline discipline).
                    return m["loss_sum"], m

                (_, m), g_mat = jax.value_and_grad(
                    loss_fn, has_aux=True
                )(mat)
            else:
                m, g_mat = sched_ticks(mat, ids, targets, ts.step)
            n_global = lax.psum(m["count"], reduce_axes)
            # Complementary pieces on every axis: zero off-stage,
            # partial per 'seq' shard, per-replica sums over 'data' —
            # ONE fused psum, then the dense mean-loss normalization
            # (both inside finish_grads).
            grads = finish_grads(g_mat, Vs, n_global)
            params, opt_state = self.optimizer.update(
                ts.params, ts.opt_state, grads, lr
            )
            new_ts = TrainState(
                params, ts.model_state, opt_state, ts.step + 1
            )
            return new_ts, {
                k: lax.psum(v, reduce_axes) for k, v in m.items()
            }

        def shard_eval(ts: TrainState, ids, targets):
            # Eval ALWAYS runs the gpipe forward program over the
            # n_virtual=1 stage layout: the schedule only reorders
            # the train-time backward, so there is nothing for eval
            # to schedule (schedule is execution-only).
            m = run_ticks(
                gather_stage_mat(ts.params, 1), ids, targets,
                ts.step, False,
            )
            return {k: lax.psum(v, reduce_axes) for k, v in m.items()}

        donate = (0,) if self.donate else ()
        self.train_step = jax.jit(
            shard_map(
                shard_step, mesh=mesh,
                in_specs=(
                    state_specs, P(("data",), ("seq",)),
                    P(("data",), ("seq",)), P(),
                ),
                out_specs=(state_specs, P()),
                check_vma=False,
            ),
            donate_argnums=donate,
        )
        self.eval_step = jax.jit(
            shard_map(
                shard_eval, mesh=mesh,
                in_specs=(
                    state_specs, P(("data",), ("seq",)),
                    P(("data",), ("seq",)),
                ),
                out_specs=P(),
                check_vma=False,
            )
        )

    def init_state(self, rng: jax.Array) -> TrainState:
        params, model_state = self._full.init(rng)
        opt_state = self.optimizer.init(params)
        ts = TrainState(
            params, model_state, opt_state, jnp.zeros((), jnp.int32)
        )
        sh = self._state_sh if self.plan.fsdp else self._repl
        return jax.device_put(ts, sh)

    def shard_batch(self, ids, labels=None):
        """ids (B, T) -> (ids, next-token targets), both sharded over
        ('data', 'seq') — the SP engine's host-side target convention,
        replicated over 'stage'. `labels` is ignored (signature-uniform
        with the other LM engines)."""
        _check_seq_len(ids, self.cfg.max_position, "GPTConfig")
        targets = self._lm_targets(ids)
        ids_arr = _place_batch((ids,), self._batch)[0]
        targets_arr = _place_batch((targets,), self._batch)[0]
        return ids_arr, targets_arr

    # ------------------------------------------------ checkpoint seams

    def state_partition_specs(self) -> TrainState:
        """The PartitionSpec pytree of the runtime TrainState layout —
        the sharded-checkpoint manifest seam (the FSDPEngine
        convention): fsdp plans declare their 1/dp 'data' leaves,
        replicated plans an all-P() tree."""
        return self._state_pspecs

    def to_canonical(self, ts: TrainState) -> TrainState:
        """Host-complete (numpy) TrainState for checkpointing. The
        runtime tree already HAS canonical (dense `gpt_lm`) structure;
        this only gathers values — one leaf at a time, so the device
        transient stays a single unsharded leaf (matters for fsdp
        plans, whose params/moments are 1/dp over 'data')."""
        from distributed_model_parallel_tpu.training.checkpoint import (
            tree_to_host,
        )

        return tree_to_host(ts)

    def from_canonical(self, ts: TrainState) -> TrainState:
        """Place a canonical (host-complete) TrainState into this
        plan's runtime layout — the cross-plan RESHARD seam: the
        canonical form carries no mesh, so a checkpoint saved under a
        pp2xsp2 plan lands here as full host arrays and this
        device_put re-slices them for THIS plan's mesh (replicated, or
        1/dp over 'data' when the plan is fsdp)."""
        sh = self._state_sh if self.plan.fsdp else self._repl
        return jax.device_put(ts, sh)

    def to_canonical_sharded(self, ts: TrainState) -> TrainState:
        """Sharded-checkpoint seam (`checkpointing/save.py`): the
        runtime TrainState already has canonical TREE structure, so
        the sharded save path persists the device-sharded leaves
        directly and each process writes only its addressable chunks
        (no gather — pinned in tests/test_checkpoint_sharded.py)."""
        return ts


def build_plan_engine(
    cfg: Any,
    optimizer: Any,
    plan: ParallelPlan | str,
    *,
    devices=None,
    num_microbatches: Optional[int] = None,
    attention: str = "ring",
    collective_matmul: bool = False,
    compute_dtype: Any = None,
    remat: bool = False,
    donate: bool = True,
    force_composed: bool = False,
    min_shard_elems: int = 1024,
):
    """The one engine entry point: a GPT(-MoE) config plus a
    ParallelPlan (or its spec string) returns the engine that runs it —
    the composed manual engine for genuinely multi-axis plans, the
    existing single-axis engine when the plan is its degenerate
    1-on-the-other-axes form (the degenerate-plan map, INTERNALS §19):

        pp-only           -> LMPipelineEngine     (gpipe, 'stage')
        sp-only (x dp)    -> CausalLMSequenceParallelEngine
        ep (x dp)         -> ExpertParallelLMEngine (hierarchical,
                             experts riding the data axes)
        dp-only / fsdp /
        multi-axis        -> ComposedPlanEngine on make_plan_mesh

    `force_composed=True` skips the degenerate routing (the parity
    tests drive both sides of the map through one call site)."""
    if isinstance(plan, str):
        plan = parse_plan(plan)
    devices = list(devices if devices is not None else jax.devices())
    if plan.num_devices > len(devices):
        raise ValueError(
            f"plan {plan.spec!r} needs {plan.num_devices} devices, "
            f"{len(devices)} present"
        )
    moe = getattr(cfg, "num_experts", 0) > 0
    if plan.ep > 1 or (moe and not force_composed):
        if plan.pp > 1 or plan.tp_or_sp > 1 or plan.fsdp:
            offending = ", ".join(
                f"{name}={v}" for name, v in (
                    ("pp", plan.pp), ("tp_or_sp", plan.tp_or_sp),
                    ("fsdp", plan.fsdp),
                ) if v not in (1, False)
            )
            raise NotImplementedError(
                f"plan {plan.spec!r}: ParallelPlan.ep={plan.ep} "
                "composes with the dp field only (experts ride the "
                "data fabric through ExpertParallelLMEngine), but "
                f"this --plan also sets {offending} — drop those "
                "tokens from --plan, or drop its ep token"
            )
        if not moe:
            raise ValueError(
                f"plan {plan.spec!r} has ep={plan.ep} but the config "
                "has no experts (GPTConfig.num_experts == 0)"
            )
        from distributed_model_parallel_tpu.parallel.expert_parallel import (
            ExpertParallelLMEngine,
        )
        from distributed_model_parallel_tpu.runtime.mesh import (
            MeshSpec, make_mesh,
        )

        n = plan.ep * plan.dp
        mesh = make_mesh(MeshSpec(data=n), devices=devices[:n])
        return ExpertParallelLMEngine(
            cfg, optimizer, mesh, dispatch="hierarchical",
            donate=donate, compute_dtype=compute_dtype,
        )
    axes_used = sum(
        1 for w in (plan.pp, plan.tp_or_sp, plan.dp) if w > 1
    )
    composed = force_composed or plan.fsdp or axes_used > 1
    if not composed and plan.pp > 1:
        from distributed_model_parallel_tpu.models.gpt import (
            split_stages,
        )
        from distributed_model_parallel_tpu.parallel.pipeline import (
            LMPipelineEngine,
        )
        from distributed_model_parallel_tpu.runtime.mesh import (
            MeshSpec, make_mesh,
        )

        n = plan.pp * plan.dp
        mesh = make_mesh(
            MeshSpec(data=plan.dp, stage=plan.pp), devices=devices[:n]
        )
        # The schedule degenerates with the plan: a pp-only scheduled
        # plan IS the single-axis engine's 1f1b / interleaved program
        # (interleaving splits the model into pp*V round-robin
        # chunks).
        return LMPipelineEngine(
            split_stages(plan.pp * plan.virtual_stages, cfg),
            optimizer, mesh,
            num_microbatches=num_microbatches or (
                plan.pp * plan.virtual_stages
                if plan.schedule == "interleaved" else plan.pp
            ),
            donate=donate, compute_dtype=compute_dtype, remat=remat,
            pad_token_id=cfg.pad_token_id, schedule=plan.schedule,
            virtual_stages=plan.virtual_stages,
        )
    if not composed and plan.tp_or_sp > 1:
        from distributed_model_parallel_tpu.parallel.sequence_parallel import (
            CausalLMSequenceParallelEngine,
        )
        from distributed_model_parallel_tpu.runtime.mesh import (
            MeshSpec, make_mesh,
        )

        n = plan.tp_or_sp * plan.dp
        mesh = make_mesh(
            MeshSpec(data=plan.dp, seq=plan.tp_or_sp),
            devices=devices[:n],
        )
        return CausalLMSequenceParallelEngine(
            cfg, optimizer, mesh, attention=attention, donate=donate,
            compute_dtype=compute_dtype, remat=remat,
            collective_matmul=collective_matmul,
        )
    mesh = make_plan_mesh(
        plan.pp, plan.dp, plan.tp_or_sp,
        devices=devices[: plan.num_devices],
    )
    return ComposedPlanEngine(
        cfg, optimizer, mesh, plan=plan,
        num_microbatches=num_microbatches, attention=attention,
        donate=donate, compute_dtype=compute_dtype, remat=remat,
        collective_matmul=collective_matmul,
        min_shard_elems=min_shard_elems,
    )


__all__ = [
    "ComposedPlanEngine",
    "PLAN_SCHEDULES",
    "ParallelPlan",
    "build_plan_engine",
    "parse_plan",
]
