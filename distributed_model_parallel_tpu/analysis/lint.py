"""Lint driver: lower engine x model x mode combos on a virtual mesh
and run the rule registry over the compiled HLO.

`tools/hlolint` is the CLI; tests/test_hlolint.py runs a tier-1 subset
plus the full matrix (slow). Per-combo results stream as the
established partial-JSON convention (`{"leg": ..., "partial": true}`
lines, one per finished combo), so a wedged or killed run still shows
exactly which combos were judged; the final summary is one JSON object
with the violation count.

Heavy imports (jax, engines) are function-local: the registry and
parser stay importable without a backend, and the CLI can force the
CPU platform before anything dials a device.
"""

from __future__ import annotations

import dataclasses
import json
import sys
from typing import Callable, List, Optional, Sequence, Tuple

from distributed_model_parallel_tpu.analysis.collectives import MeshModel
from distributed_model_parallel_tpu.analysis.rules import (
    Finding,
    LintContext,
    LintTarget,
    REGISTRY,
    run_rules,
)

_DTYPE_TOKEN = {
    "float32": "f32", "bfloat16": "bf16", "float16": "f16",
    "float64": "f64", "int8": "s8", "uint8": "u8",
}


@dataclasses.dataclass(frozen=True)
class Combo:
    """One cell of the engine x mode x mesh matrix. `size` is the
    engine's PRIMARY parallel axis: the data axis for dp/ddp/fsdp/sp_lm,
    'model' for tp, serve, and the cm_* op kernels, 'seq' for sp,
    'stage' for pipeline."""

    engine: str
    size: int
    grad_reduction: str = "monolithic"
    dcn: int = 1
    collective_matmul: bool = False
    bf16: bool = False
    model: str = "mlp"  # mlp | tinycnn (ddp/fsdp families)
    # MoE dispatch mode (engine == "ep"): "gspmd" = partitioner-chosen
    # flat exchange over 'expert'; "hierarchical" = the explicit
    # two-level moe_ring exchange over the (factored) data fabric,
    # "+ov" chunk-overlapped (`ops/expert_dispatch.py`).
    moe_dispatch: str = "gspmd"
    moe_overlap: bool = False

    # Cross-slice wire compression ("none" | "bf16" | "int8") — the
    # `dcn_compression` knob on the reducer engines / the hierarchical
    # MoE dispatch (`ops/wire_codec.py`, rule dcn-compressed-payload).
    dcn_compression: str = "none"

    # Paged serving (engine == "serve", ISSUE 15): page_size None
    # keeps the PR 7 contiguous slot cache (every pre-existing serve
    # combo name byte-stable); set = the block-paged decode step.
    page_size: Optional[int] = None

    # Quantized decode arithmetic (engine == "serve", ISSUE 16): None
    # keeps the f32 projections (every pre-existing serve combo name
    # byte-stable); "bf16"/"int8" opt the decode
    # projection GEMMs into `ops/quant_matmul.py` (rule
    # decode-quantized-matmul).
    compute_dtype: Optional[str] = None

    # Speculative decoding (engine == "serve", ISSUE 18): 0 keeps the
    # plain decode step (every pre-existing serve combo name
    # byte-stable); k > 0 lowers the VERIFY step instead —
    # the (slots, k+1) chunk-shaped pass rule spec-verify-step pins at
    # one decode step's ring inventory. Requires page_size (rollback
    # truncates the block table).
    speculative_k: int = 0

    # Composed ParallelPlan spec (engine == "plan", ISSUE 19): the
    # `parse_plan` spec string (e.g. "pp2xsp2xdp2", or the scheduled
    # "pp2-1f1bxdp4" / "pp2-int2xdp2" forms, ISSUE 20) the builder
    # lowers through ComposedPlanEngine. None everywhere else (every
    # pre-existing combo name stays byte-stable).
    plan: Optional[str] = None

    # Pipeline fill depth for plan combos (ISSUE 20): 0 keeps the
    # engine default (M = pp * V — every pre-existing plan combo name
    # byte-stable); set = the engine's `num_microbatches`.
    num_microbatches: int = 0

    @property
    def name(self) -> str:
        bits = [self.engine, f"S{self.size}"]
        if self.dcn > 1:
            bits.append(f"dcn{self.dcn}")
        if self.engine in ("ddp", "fsdp", "sp_lm"):
            bits.append(self.grad_reduction)
        if self.engine == "ep":
            bits.append(self.moe_dispatch)
            if self.moe_overlap:
                bits.append("ov")
        if self.plan is not None:
            bits.append(self.plan)
        if self.num_microbatches:
            bits.append(f"M{self.num_microbatches}")
        if self.dcn_compression != "none":
            bits.append(f"wire-{self.dcn_compression}")
        if self.page_size is not None:
            bits.append(f"pg{self.page_size}")
        if self.model != "mlp":
            bits.append(self.model)
        if self.collective_matmul:
            bits.append("cm")
        if self.bf16:
            bits.append("bf16")
        if self.compute_dtype is not None:
            bits.append(f"q-{self.compute_dtype}")
        if self.speculative_k:
            bits.append(f"spec{self.speculative_k}")
        return "/".join(bits)


@dataclasses.dataclass
class LintReport:
    combo: Combo
    target: LintTarget
    findings: List[Finding]
    n_collectives: int

    @property
    def violations(self) -> List[Finding]:
        return [f for f in self.findings if not f.exempted]

    @property
    def errors(self) -> List[Finding]:
        return [f for f in self.violations if f.severity == "error"]


# ------------------------------------------------------------ models


def staged_mlp(n_blocks=8, width=32, classes=4):
    """BN-free stem/blocks/head MLP: no model_state, so the only
    data-fabric all-reduces an opted-in step may carry are the pinned
    bucket hops — the model the reducer rules are sharpest on. Public:
    tests/test_collectives_hlo.py pins against the SAME builder so the
    lint matrix and the HLO pin tests can never desynchronize."""
    from distributed_model_parallel_tpu.models import layers as L
    from distributed_model_parallel_tpu.models import staging

    stem = L.sequential(L.flatten(), L.linear(192, width), L.relu())
    blocks = [
        L.sequential(L.linear(width, width), L.relu())
        for _ in range(n_blocks)
    ]
    return staging.staged_model(stem, blocks, L.linear(width, classes))


def moe_classifier(num_experts: int, dim: int = 16, seq: int = 8,
                   num_classes: int = 4, top_k: int = 2,
                   capacity_factor: float = 1.25):
    """Tiny one-block MoE classifier (tokens (B, T, D) -> logits) —
    ONE routed layer so the moe_ring permute pin is exact. Public and
    imported by tests/test_expert_dispatch.py, so the lint matrix and
    the parity tests lower the SAME model (the staged_mlp/image_batch
    no-desync convention)."""
    import jax

    from distributed_model_parallel_tpu.models import layers as L
    from distributed_model_parallel_tpu.models.moe import (
        moe_encoder_layer,
    )

    block = moe_encoder_layer(
        dim, 2, 2 * dim, num_experts, top_k=top_k,
        capacity_factor=capacity_factor, dropout_rate=0.0,
    )
    head = L.linear(dim, num_classes)

    def init(key):
        kb, kh = jax.random.split(key)
        bp, bs = block.init(kb)
        return {"block": bp, "head": head.init(kh)[0]}, {"block": bs}

    def apply(params, state, x, ctx):
        (h, _), bs = block.apply(
            params["block"], state.get("block", {}), (x, None), ctx
        )
        logits, _ = head.apply(params["head"], {}, h.mean(axis=1), ctx)
        return logits, {"block": bs}

    return L.Layer(init, apply)


def _bert_cfg(model_size: int):
    from distributed_model_parallel_tpu.models.bert import BertConfig

    return BertConfig(
        vocab_size=64, hidden_size=32, num_layers=1,
        num_heads=max(2, model_size), intermediate_size=64,
        max_position=16, dropout_rate=0.0,
    )


def _gpt_cfg():
    from distributed_model_parallel_tpu.models.gpt import GPTConfig

    return GPTConfig(
        vocab_size=61, dim=16, num_layers=4, num_heads=2, ffn_dim=32,
        max_position=16, dropout_rate=0.0,
    )


def image_batch(n, hw=8, classes=4, seed=0):
    """Deterministic fake image batch (shared with the pin tests)."""
    import numpy as np

    rng = np.random.RandomState(seed)
    return (
        rng.rand(n, hw, hw, 3).astype(np.float32),
        rng.randint(0, classes, size=(n,)).astype(np.int32),
    )


# ------------------------------------------------------- expectations


def _token(dtype) -> str:
    import numpy as np

    return _DTYPE_TOKEN.get(np.dtype(dtype).name, "f32")


def _bucket_plan(leaves, bucket_mb: float, pad_multiple: int):
    """[(padded_elems, dtype_token)] for one segment's gradient tree —
    the shape the per-bucket collectives are pinned against.
    `pad_multiple` comes from `grad_reduction.bucket_pad_multiple` (the
    ici ring size, times the dcn factor on compressed combos)."""
    from distributed_model_parallel_tpu.ops.grad_reduction import (
        plan_buckets,
    )

    out = []
    for b in plan_buckets(leaves, bucket_mb):
        padded = b.size + (-b.size % pad_multiple)
        out.append((padded, _token(b.dtype)))
    return tuple(out)


def _reducer_plans(model, grad_reduction: str, ici_size: int,
                   dcn_size: int = 1, dcn_compression: str = "none"):
    """Per-segment bucket plans + segment count for a staged model —
    one segment for 'bucketed', split_points segments for
    'overlapped', one WHOLE-TREE bucket per dtype for compressed
    'monolithic' (the engines' single-flat-bucket path). Empty for
    uncompressed 'monolithic'."""
    import jax
    import jax.numpy as jnp

    from distributed_model_parallel_tpu.models import staging
    from distributed_model_parallel_tpu.ops.grad_reduction import (
        MONOLITHIC_BUCKET_MB,
        bucket_pad_multiple,
    )

    pad_mult = bucket_pad_multiple(ici_size, dcn_size, dcn_compression)
    key_aval = jax.ShapeDtypeStruct((2,), jnp.uint32)
    p_aval, s_aval = jax.eval_shape(model.init, key_aval)
    state_shapes = tuple(
        tuple(leaf.shape)
        for leaf in jax.tree_util.tree_leaves(s_aval)
    )
    if grad_reduction == "monolithic" and dcn_compression != "none":
        plans = (_bucket_plan(
            jax.tree_util.tree_leaves(p_aval), MONOLITHIC_BUCKET_MB,
            pad_mult,
        ),)
        return plans, 0, state_shapes
    if grad_reduction == "bucketed":
        plans = (_bucket_plan(
            jax.tree_util.tree_leaves(p_aval), BUCKET_MB, pad_mult
        ),)
        return plans, 0, state_shapes
    if grad_reduction == "overlapped":
        n = staging.resolve_overlap_segments(
            len(model.parts.blocks), 0, "lint"
        )
        cuts = staging.split_points(n, None, len(model.parts.blocks))
        plans = tuple(
            _bucket_plan(
                jax.tree_util.tree_leaves(sp), BUCKET_MB, pad_mult
            )
            for sp in staging.partition_tree(p_aval, cuts)
        )
        return plans, n, state_shapes
    return (), 0, state_shapes


def _wire_chunk_expectations(plans, ici_size: int, dcn_size: int,
                             dcn_compression: str):
    """Expected (elems, wire_dtype_token) multiset of the compressed
    'dcn' payload hops: each bucket's 1/ici shard re-chunks across the
    K slices and crosses 2(K-1) times (exchange + gather,
    `grad_reduction.compressed_dcn_psum`)."""
    if dcn_compression == "none" or dcn_size <= 1:
        return ()
    from distributed_model_parallel_tpu.analysis.rules import (
        DCN_WIRE_TOKEN,
    )

    # Every payload hop carries the WIRE dtype regardless of the
    # bucket's math dtype (wire_encode casts unconditionally).
    wire = DCN_WIRE_TOKEN[dcn_compression]
    chunks = []
    for plan in plans:
        for padded, _dt in plan:
            nl = padded // (ici_size * dcn_size)
            chunks += [(nl, wire)] * (2 * (dcn_size - 1))
    return tuple(chunks)


def _fsdp_gather_chunk_expectations(
    full_leaf_shapes, dcn_size: int, dcn_compression: str,
    gathers_per_leaf: int,
):
    """Expected (n_elems, wire_dtype) multiset of FSDP's compressed
    WEIGHT-gather ring hops (ISSUE 16 satellite,
    `parallel/fsdp._coded_dcn_gather`): each dcn-crossing leaf crosses
    'dcn' in (K-1) coded hops of full_leaf/K elems per gather —
    `gathers_per_leaf` is 1 for the single-entry steps, 2 under
    "overlapped" (forward gather + backward regather)."""
    if dcn_compression == "none" or dcn_size <= 1:
        return ()
    import math as _math

    from distributed_model_parallel_tpu.analysis.rules import (
        DCN_WIRE_TOKEN,
    )

    wire = DCN_WIRE_TOKEN[dcn_compression]
    chunks = []
    for shape in full_leaf_shapes:
        hop = _math.prod(shape) // dcn_size
        chunks += [(hop, wire)] * ((dcn_size - 1) * gathers_per_leaf)
    return tuple(chunks)


def _n_param_leaves(ts) -> int:
    import jax

    return len(jax.tree_util.tree_leaves(ts.params)) + len(
        jax.tree_util.tree_leaves(ts.opt_state)
    )


def _subjaxprs(v):
    """Jaxprs nested in one equation param (cond branches, scan/while
    bodies, shard_map/pjit callees)."""
    from jax.extend import core

    if isinstance(v, core.ClosedJaxpr):
        yield v.jaxpr
    elif isinstance(v, core.Jaxpr):
        yield v
    elif isinstance(v, (tuple, list)):
        for x in v:
            yield from _subjaxprs(x)


def jaxpr_ppermute_records(fn, *args):
    """((axis_names, dtype_token, scope, n_elems), ...) for every
    `ppermute` equation in fn's jaxpr, sub-jaxprs included — the
    trace-level record the bf16-ring and compressed-wire rules read
    (compiled CPU HLO normalizes bf16 collectives to f32, so dtype
    contracts must come from the trace). `scope` is the equation's
    name_stack string (named_scope names survive jvp and transpose,
    e.g. 'transpose(jvp(kv_ring))'), which is how the rules
    distinguish the deliberately-f32 KV ring from the cm rings and a
    `dcn_wire` payload hop from its `dcn_scale` sidecar."""
    import math as _math

    import jax

    closed = jax.make_jaxpr(fn)(*args)
    out = []
    seen = set()

    def walk(jaxpr):
        if id(jaxpr) in seen:
            return
        seen.add(id(jaxpr))
        for eqn in jaxpr.eqns:
            if eqn.primitive.name == "ppermute":
                axes = eqn.params.get("axis_name")
                axes = axes if isinstance(axes, tuple) else (axes,)
                aval = eqn.invars[0].aval
                dt = str(aval.dtype)
                out.append((
                    tuple(str(a) for a in axes),
                    _DTYPE_TOKEN.get(dt, dt),
                    str(eqn.source_info.name_stack),
                    int(_math.prod(aval.shape)) if aval.shape else 1,
                ))
            for v in eqn.params.values():
                for sub in _subjaxprs(v):
                    walk(sub)

    walk(closed.jaxpr)
    return tuple(out)


# Named-axis collectives the plan fabric rules read, with the eqn
# param their axis names live under (ppermute-family primitives carry
# `axis_name`; the reduction family carries `axes`, possibly mixed
# with positional ints which are not named-axis traffic).
_COLLECTIVE_AXIS_PARAM = {
    "ppermute": "axis_name",
    "all_gather": "axis_name",
    "all_to_all": "axis_name",
    "reduce_scatter": "axis_name",
    "psum": "axes",
    "pmax": "axes",
    "pmin": "axes",
}


def jaxpr_collective_records(fn, *args):
    """((primitive, axis_names, dtype_token, scope, n_elems), ...) for
    every named-axis collective equation in fn's jaxpr, sub-jaxprs
    included — the multi-primitive generalization of
    `jaxpr_ppermute_records` the composed-plan fabric rules read
    (`LintTarget.plan_collective_records`): compiled HLO flattens axis
    names to replica groups and normalizes dtypes, so an axis->fabric
    contract must be pinned at trace level. Positional (int) axes are
    dropped from the record — they are intra-shard reductions, not
    fabric traffic."""
    import math as _math

    import jax

    closed = jax.make_jaxpr(fn)(*args)
    out = []
    seen = set()

    def walk(jaxpr):
        if id(jaxpr) in seen:
            return
        seen.add(id(jaxpr))
        for eqn in jaxpr.eqns:
            key = _COLLECTIVE_AXIS_PARAM.get(eqn.primitive.name)
            if key is not None:
                axes = eqn.params.get(key)
                axes = axes if isinstance(axes, tuple) else (axes,)
                names = tuple(
                    str(a) for a in axes if isinstance(a, str)
                )
                if names:
                    aval = eqn.invars[0].aval
                    dt = str(aval.dtype)
                    n_elems = sum(
                        int(_math.prod(v.aval.shape))
                        if v.aval.shape else 1
                        for v in eqn.invars
                        if hasattr(v.aval, "shape")
                    )
                    out.append((
                        eqn.primitive.name,
                        names,
                        _DTYPE_TOKEN.get(dt, dt),
                        str(eqn.source_info.name_stack),
                        n_elems,
                    ))
            for v in eqn.params.values():
                for sub in _subjaxprs(v):
                    walk(sub)

    walk(closed.jaxpr)
    return tuple(out)


def jaxpr_ppermute_dtypes(fn, *args):
    """The (axis_names, dtype_token, scope) cut of
    `jaxpr_ppermute_records` — the record shape `LintTarget.ring_dtypes`
    carries for the bf16-ring-upcast rule."""
    return tuple(r[:3] for r in jaxpr_ppermute_records(fn, *args))


def jaxpr_dot_records(fn, *args):
    """((lhs_dtype_token, rhs_dtype_token, rhs_shape), ...) for every
    `dot_general` equation in fn's jaxpr, sub-jaxprs (pjit bodies,
    shard_map fold bodies) included — the quant twin of
    `jaxpr_ppermute_records`. Compiled CPU HLO normalizes int8/bf16
    dots back to f32, so the `decode-quantized-matmul` rule pins the
    compute-dtype contract from these trace-level records
    (`LintTarget.decode_dot_records`)."""
    import jax

    closed = jax.make_jaxpr(fn)(*args)
    out = []
    seen = set()

    def walk(jaxpr):
        if id(jaxpr) in seen:
            return
        seen.add(id(jaxpr))
        for eqn in jaxpr.eqns:
            if eqn.primitive.name == "dot_general":
                lhs = str(eqn.invars[0].aval.dtype)
                rhs = str(eqn.invars[1].aval.dtype)
                out.append((
                    _DTYPE_TOKEN.get(lhs, lhs),
                    _DTYPE_TOKEN.get(rhs, rhs),
                    tuple(int(d) for d in eqn.invars[1].aval.shape),
                ))
            for v in eqn.params.values():
                for sub in _subjaxprs(v):
                    walk(sub)

    walk(closed.jaxpr)
    return tuple(out)


def _mesh_facts(mesh):
    from distributed_model_parallel_tpu.runtime.mesh import (
        data_hierarchy_axes,
    )

    d_axes, ici_axis, dcn_axis = data_hierarchy_axes(mesh)
    return dict(
        data_axes=tuple(d_axes),
        ici_axis=ici_axis,
        dcn_axis=dcn_axis,
        ici_size=int(mesh.shape[ici_axis]),
        dcn_size=int(mesh.shape[dcn_axis]) if dcn_axis else 1,
    )


# ----------------------------------------------------------- builders

BUCKET_MB = 0.02  # small enough that every lint model splits >1 bucket


def _build_data_engine(combo: Combo, devices):
    """ddp / fsdp / dp over a data(-factored) mesh."""
    import jax
    import jax.numpy as jnp

    from distributed_model_parallel_tpu.models.tinycnn import tiny_cnn
    from distributed_model_parallel_tpu.runtime.mesh import (
        MeshSpec, make_mesh,
    )
    from distributed_model_parallel_tpu.training.optim import SGD

    s = combo.size
    mesh = make_mesh(
        MeshSpec(data=s, dcn=combo.dcn), devices=devices[:s]
    )
    facts = _mesh_facts(mesh)
    if combo.model == "tinycnn":
        model = tiny_cnn(4)
    else:
        model = staged_mlp(width=128 if combo.engine == "fsdp" else 32)
    cdt = jnp.bfloat16 if combo.bf16 else None
    kwargs = dict(donate=True, compute_dtype=cdt)
    full_leaf_shapes: Tuple = ()
    if combo.engine == "dp":
        from distributed_model_parallel_tpu.parallel.data_parallel import (
            DataParallelEngine,
        )

        eng = DataParallelEngine(model, SGD(), mesh, **kwargs)
    elif combo.engine == "ddp":
        from distributed_model_parallel_tpu.parallel.data_parallel import (
            DDPEngine,
        )

        eng = DDPEngine(
            model, SGD(), mesh, grad_reduction=combo.grad_reduction,
            bucket_mb=BUCKET_MB,
            dcn_compression=combo.dcn_compression, **kwargs,
        )
    else:  # fsdp
        from distributed_model_parallel_tpu.parallel.fsdp import (
            FSDPEngine, fsdp_specs,
        )
        from distributed_model_parallel_tpu.runtime.mesh import (
            data_axis_names, data_axis_size,
        )

        min_elems = 64
        eng = FSDPEngine(
            model, SGD(), mesh, min_shard_elems=min_elems,
            grad_reduction=combo.grad_reduction, bucket_mb=BUCKET_MB,
            dcn_compression=combo.dcn_compression, **kwargs,
        )
        from jax.sharding import PartitionSpec as P

        key_aval = jax.ShapeDtypeStruct((2,), jnp.uint32)
        p_aval, _ = jax.eval_shape(model.init, key_aval)
        specs = fsdp_specs(
            p_aval, data_axis_size(mesh), min_shard_elems=min_elems,
            axes=data_axis_names(mesh),
        )
        is_spec = lambda x: isinstance(x, P)  # noqa: E731
        shapes = []
        for leaf, spec in zip(
            jax.tree_util.tree_leaves(p_aval),
            jax.tree_util.tree_leaves(specs, is_leaf=is_spec),
        ):
            if any(part is not None for part in spec):
                shapes.append(tuple(leaf.shape))
        full_leaf_shapes = tuple(shapes)

    plans, n_seg, state_shapes = _reducer_plans(
        model, combo.grad_reduction, facts["ici_size"],
        facts["dcn_size"], combo.dcn_compression,
    )
    ts = eng.init_state(jax.random.PRNGKey(0))
    im, lb = eng.shard_batch(*image_batch(16 * (s // 2 or 1)))
    hlo = eng.train_step.lower(
        ts, im, lb, jnp.float32(0.1)
    ).compile().as_text()
    dcn_records = (
        jaxpr_ppermute_records(eng.train_step, ts, im, lb,
                               jnp.float32(0.1))
        if combo.dcn_compression != "none" else ()
    )
    target = LintTarget(
        name=combo.name, engine=combo.engine,
        grad_reduction=combo.grad_reduction, bf16=combo.bf16,
        donate=True, bucket_plans=plans, overlap_segments=n_seg,
        state_leaf_shapes=state_shapes,
        fsdp_full_leaf_shapes=full_leaf_shapes,
        dcn_compression=combo.dcn_compression,
        dcn_wire_chunks=_wire_chunk_expectations(
            plans, facts["ici_size"], facts["dcn_size"],
            combo.dcn_compression,
        ),
        dcn_gather_chunks=_fsdp_gather_chunk_expectations(
            full_leaf_shapes, facts["dcn_size"],
            combo.dcn_compression,
            2 if combo.grad_reduction == "overlapped" else 1,
        ),
        dcn_ring_records=dcn_records,
        n_param_leaves=_n_param_leaves(ts), **facts,
    )
    return target, hlo, mesh


def _build_tp(combo: Combo, devices):
    import jax
    import jax.numpy as jnp
    import numpy as np

    from distributed_model_parallel_tpu.models.bert import (
        bert_for_classification,
    )
    from distributed_model_parallel_tpu.parallel.tensor_parallel import (
        TensorParallelEngine,
    )
    from distributed_model_parallel_tpu.runtime.mesh import (
        MeshSpec, make_mesh,
    )
    from distributed_model_parallel_tpu.training.optim import SGD

    s = combo.size
    dp = 2 if 2 * s <= len(devices) else 1
    mesh = make_mesh(
        MeshSpec(data=dp, model=s), devices=devices[: dp * s]
    )
    cfg = _bert_cfg(s)
    eng = TensorParallelEngine(
        bert_for_classification(4, cfg), SGD(), mesh, donate=True,
        collective_matmul=combo.collective_matmul,
        compute_dtype=jnp.bfloat16 if combo.bf16 else None,
    )
    ts = eng.init_state(jax.random.PRNGKey(0))
    rng = np.random.RandomState(0)
    ids = rng.randint(1, 64, size=(4 * dp, 8)).astype(np.int32)
    lb = rng.randint(0, 4, size=(4 * dp,)).astype(np.int32)
    ids, lb = eng.shard_batch(ids, lb)
    hlo = eng.train_step.lower(
        ts, ids, lb, jnp.float32(0.1)
    ).compile().as_text()
    ring_dtypes = (
        jaxpr_ppermute_dtypes(eng.train_step, ts, ids, lb,
                              jnp.float32(0.1))
        if combo.bf16 else ()
    )
    target = LintTarget(
        name=combo.name, engine="tp", donate=True, bf16=combo.bf16,
        ring_dtypes=ring_dtypes,
        collective_matmul=combo.collective_matmul,
        cm_axis="model" if combo.collective_matmul else None,
        cm_size=s,
        # 1 block = 4 opted-in projections; fwd 4(S-1) rings + the
        # custom-vjp dual kernels >= 6(S-1) more (PR 2's engine pin).
        cm_min_ring_permutes=10 * (s - 1),
        n_param_leaves=_n_param_leaves(ts), **_mesh_facts(mesh),
    )
    return target, hlo, mesh


def _build_sp(combo: Combo, devices):
    import jax
    import jax.numpy as jnp
    import numpy as np

    from distributed_model_parallel_tpu.parallel.sequence_parallel import (
        SequenceParallelEngine,
    )
    from distributed_model_parallel_tpu.runtime.mesh import (
        MeshSpec, make_mesh,
    )
    from distributed_model_parallel_tpu.training.optim import SGD

    s = combo.size
    dp = 2 if 2 * s <= len(devices) else 1
    mesh = make_mesh(
        MeshSpec(data=dp, seq=s), devices=devices[: dp * s]
    )
    cfg = _bert_cfg(4)
    eng = SequenceParallelEngine(
        cfg, 4, SGD(), mesh, donate=True,
        collective_matmul=combo.collective_matmul,
        compute_dtype=jnp.bfloat16 if combo.bf16 else None,
    )
    ts = eng.init_state(jax.random.PRNGKey(0))
    rng = np.random.RandomState(0)
    ids = rng.randint(1, 64, size=(4 * dp, 16)).astype(np.int32)
    lb = rng.randint(0, 4, size=(4 * dp,)).astype(np.int32)
    ids, lb = eng.shard_batch(ids, lb)
    hlo = eng.train_step.lower(
        ts, ids, lb, jnp.float32(0.1)
    ).compile().as_text()
    ring_dtypes = (
        jaxpr_ppermute_dtypes(eng.train_step, ts, ids, lb,
                              jnp.float32(0.1))
        if combo.bf16 else ()
    )
    target = LintTarget(
        name=combo.name, engine="sp", donate=True, bf16=combo.bf16,
        ring_dtypes=ring_dtypes,
        collective_matmul=combo.collective_matmul,
        cm_axis="seq" if combo.collective_matmul else None,
        cm_size=s,
        # 1 block's FFN pair per step: fwd 2(S-1) rings + dual-kernel
        # bwd 3(S-1) rings = 5(S-1) hops (PR 2's kernel accounting);
        # the KV ring's hops ride the same axis, so this is a floor.
        cm_min_ring_permutes=5 * (s - 1),
        n_param_leaves=_n_param_leaves(ts), **_mesh_facts(mesh),
    )
    return target, hlo, mesh


def _build_sp_lm(combo: Combo, devices):
    import jax
    import jax.numpy as jnp
    import numpy as np

    from distributed_model_parallel_tpu.models.gpt import gpt_lm
    from distributed_model_parallel_tpu.parallel.sequence_parallel import (
        CausalLMSequenceParallelEngine,
    )
    from distributed_model_parallel_tpu.runtime.mesh import (
        MeshSpec, make_mesh,
    )
    from distributed_model_parallel_tpu.training.optim import SGD

    s = combo.size  # the DATA axis (the bucket rings' fabric)
    seq = 2
    mesh = make_mesh(
        MeshSpec(data=s, seq=seq, dcn=combo.dcn),
        devices=devices[: s * seq],
    )
    facts = _mesh_facts(mesh)
    cfg = _gpt_cfg()
    eng = CausalLMSequenceParallelEngine(
        cfg, SGD(), mesh, donate=True,
        grad_reduction=combo.grad_reduction, bucket_mb=BUCKET_MB,
        collective_matmul=combo.collective_matmul,
        dcn_compression=combo.dcn_compression,
    )
    ts = eng.init_state(jax.random.PRNGKey(0))
    rng = np.random.RandomState(0)
    ids = rng.randint(1, 61, size=(4 * s, 16)).astype(np.int32)
    ids, tg = eng.shard_batch(ids)
    hlo = eng.train_step.lower(
        ts, ids, tg, jnp.float32(0.1)
    ).compile().as_text()

    # Reducer expectations over the LM's stem/blocks/head params —
    # gpt_lm builds through the staged substrate, so the shared
    # expectation builder serves it like the image engines (one copy
    # of the monolithic-compressed/bucketed/overlapped plan logic).
    plans, n_seg, _ = _reducer_plans(
        gpt_lm(cfg), combo.grad_reduction,
        facts["ici_size"], facts["dcn_size"], combo.dcn_compression,
    )
    dcn_records = (
        jaxpr_ppermute_records(eng.train_step, ts, ids, tg,
                               jnp.float32(0.1))
        if combo.dcn_compression != "none" else ()
    )
    target = LintTarget(
        name=combo.name, engine="sp_lm",
        grad_reduction=combo.grad_reduction, donate=True,
        collective_matmul=combo.collective_matmul,
        cm_axis="seq" if combo.collective_matmul else None,
        cm_size=seq,
        cm_min_ring_permutes=5 * (seq - 1) * cfg.num_layers,
        bucket_plans=plans, overlap_segments=n_seg,
        dcn_compression=combo.dcn_compression,
        dcn_wire_chunks=_wire_chunk_expectations(
            plans, facts["ici_size"], facts["dcn_size"],
            combo.dcn_compression,
        ),
        dcn_ring_records=dcn_records,
        n_param_leaves=_n_param_leaves(ts), **facts,
    )
    return target, hlo, mesh


def _build_ep(combo: Combo, devices):
    """MoE expert-parallel train steps (`parallel/expert_parallel.py`).
    `moe_dispatch="gspmd"`: the original 'expert'-axis layout on a
    (data=2, expert=S) mesh, judged by the generic rules only.
    `moe_dispatch="hierarchical"` (+overlap): the explicit two-level
    exchange over a (data=S[, dcn]) fabric — rule `moe-hierarchical-
    a2a` pins the exact moe_ring chain and the absence of any flat
    all-to-all on the data axes."""
    import jax
    import jax.numpy as jnp
    import numpy as np

    from distributed_model_parallel_tpu.ops.expert_dispatch import (
        exchange_permutes,
    )
    from distributed_model_parallel_tpu.parallel.expert_parallel import (
        ExpertParallelEngine,
    )
    from distributed_model_parallel_tpu.runtime.mesh import (
        MeshSpec, make_mesh,
    )
    from distributed_model_parallel_tpu.training.optim import SGD

    s = combo.size
    dim, seq = 16, 8
    if combo.moe_dispatch == "hierarchical":
        mesh = make_mesh(
            MeshSpec(data=s, dcn=combo.dcn), devices=devices[:s]
        )
        eng = ExpertParallelEngine(
            moe_classifier(s, dim=dim), SGD(), mesh, donate=True,
            dispatch="hierarchical", overlap=combo.moe_overlap,
            dcn_compression=combo.dcn_compression,
        )
        facts = _mesh_facts(mesh)
        # One MoE layer, fwd exchange pair + mirrored backward.
        expected = 2 * exchange_permutes(
            facts["ici_size"], facts["dcn_size"]
        )
    else:
        dp = 2 if 2 * s <= len(devices) else 1
        mesh = make_mesh(
            MeshSpec(data=dp, expert=s), devices=devices[: dp * s]
        )
        eng = ExpertParallelEngine(
            moe_classifier(s, dim=dim), SGD(), mesh, donate=True
        )
        facts = _mesh_facts(mesh)
        expected = None
    ts = eng.init_state(jax.random.PRNGKey(0))
    rng = np.random.RandomState(0)
    n = max(8, int(mesh.shape[facts["ici_axis"]]) * facts["dcn_size"])
    x = rng.randn(n, seq, dim).astype(np.float32)
    lb = rng.randint(0, 4, size=(n,)).astype(np.int32)
    xs, lbs = eng.shard_batch(x, lb)
    hlo = eng.train_step.lower(
        ts, xs, lbs, jnp.float32(0.1)
    ).compile().as_text()
    # Compressed exchange: per routed layer the 'dcn' stage crosses
    # 2(K-1) hops per direction pair (dispatch + combine, or the
    # overlapped ring's in+out), doubled by the mirrored backward =
    # 4(K-1) dcn_wire payload hops (one routed layer here). The chunk
    # SHAPES are model-dependent, so the rule pins hop count + wire
    # dtype (`dcn_wire_hops`) instead of a byte multiset.
    wire_hops = None
    dcn_records = ()
    if combo.dcn_compression != "none":
        wire_hops = 4 * (facts["dcn_size"] - 1)
        dcn_records = jaxpr_ppermute_records(
            eng.train_step, ts, xs, lbs, jnp.float32(0.1)
        )
    target = LintTarget(
        name=combo.name, engine="ep", donate=True,
        moe_dispatch=combo.moe_dispatch,
        moe_ring_permutes=expected,
        dcn_compression=combo.dcn_compression,
        dcn_wire_hops=wire_hops,
        dcn_ring_records=dcn_records,
        n_param_leaves=_n_param_leaves(ts), **facts,
    )
    return target, hlo, mesh


def _build_pipeline(combo: Combo, devices):
    import jax
    import jax.numpy as jnp

    from distributed_model_parallel_tpu.models.tinycnn import split_stages
    from distributed_model_parallel_tpu.parallel.pipeline import (
        PipelineEngine,
    )
    from distributed_model_parallel_tpu.runtime.mesh import (
        MeshSpec, make_mesh,
    )
    from distributed_model_parallel_tpu.training.optim import SGD

    s = combo.size
    dp = max(1, len(devices) // s)
    mesh = make_mesh(
        MeshSpec(data=dp, stage=s), devices=devices[: dp * s]
    )
    eng = PipelineEngine(
        split_stages(s, 4), SGD(), mesh, num_microbatches=2,
        donate=True,
    )
    ts = eng.init_state(jax.random.PRNGKey(0))
    im, lb = eng.shard_batch(*image_batch(4 * dp))
    hlo = eng.train_step.lower(
        ts, im, lb, jnp.float32(0.1)
    ).compile().as_text()
    target = LintTarget(
        name=combo.name, engine="pipeline", donate=True,
        n_param_leaves=_n_param_leaves(ts), **_mesh_facts(mesh),
    )
    return target, hlo, mesh


def _build_cm_op(combo: Combo, devices):
    """Op-level kernel targets: the exact S-1 pin on ag_matmul /
    matmul_rs, matching PR 2's kernel tests."""
    from functools import partial

    import jax
    import jax.numpy as jnp
    import numpy as np
    from jax.sharding import Mesh, PartitionSpec as P

    from distributed_model_parallel_tpu.ops.collective_matmul import (
        ag_matmul, matmul_rs,
    )
    from distributed_model_parallel_tpu.runtime.compat import shard_map

    s = combo.size
    mesh = Mesh(np.array(devices[:s]), ("model",))
    dt = jnp.bfloat16 if combo.bf16 else jnp.float32
    if combo.engine == "cm_ag":
        x = jnp.zeros((2, 4 * s, 16), dt)
        w = jnp.zeros((16, 8 * s), dt)
        fn = jax.jit(shard_map(
            partial(ag_matmul, axis_name="model"), mesh=mesh,
            in_specs=(P(None, "model", None), P(None, "model")),
            out_specs=P(None, None, "model"), check_vma=False,
        ))
    else:
        x = jnp.zeros((2, 4 * s, 8 * s), dt)
        w = jnp.zeros((8 * s, 16), dt)
        fn = jax.jit(shard_map(
            partial(matmul_rs, axis_name="model"), mesh=mesh,
            in_specs=(P(None, None, "model"), P("model", None)),
            out_specs=P(None, "model", None), check_vma=False,
        ))
    hlo = fn.lower(x, w).compile().as_text()
    target = LintTarget(
        name=combo.name, engine=combo.engine, bf16=combo.bf16,
        data_axes=(), ici_axis=None, ici_size=1,
        cm_axis="model", cm_size=s, expected_permutes=s - 1,
    )
    return target, hlo, mesh


def _build_serve(combo: Combo, devices):
    """Serving decode-step targets (`serving/engine.py`, tp layout):
    the jitted mixed-position token step over the slot-paged KV cache,
    declarative or with the opted-in decode rings. The lint pins the
    PR 7 contract: an opted-in step carries exactly 4*L*(S-1)
    `serve_ring`-tagged permutes and no monolithic all-gather over
    'model' (rule `serve-decode-ring`)."""
    import jax
    import jax.numpy as jnp

    from distributed_model_parallel_tpu.models.gpt import GPTConfig
    from distributed_model_parallel_tpu.runtime.mesh import (
        MeshSpec, make_mesh,
    )
    from distributed_model_parallel_tpu.serving.decode import (
        decode_ring_permutes,
    )
    from distributed_model_parallel_tpu.serving.engine import (
        ServingEngine,
    )

    s = combo.size
    mesh = make_mesh(MeshSpec(data=1, model=s), devices=devices[:s])
    cfg = GPTConfig(
        vocab_size=61, dim=16, num_layers=2, num_heads=4, ffn_dim=32,
        max_position=16, dropout_rate=0.0,
    )
    eng = ServingEngine(
        cfg, mesh, layout="tp", num_slots=2 * s, max_len=16,
        prefill_len=8, collective_matmul=combo.collective_matmul,
        compute_dtype=(
            combo.compute_dtype
            or (jnp.bfloat16 if combo.bf16 else None)
        ),
        page_size=combo.page_size,
        speculative_k=combo.speculative_k,
    )
    params = eng.init_params(jax.random.PRNGKey(0))
    cache = eng.init_cache()
    tokens = jnp.zeros((eng.num_slots,), jnp.int32)
    active = jnp.ones((eng.num_slots,), jnp.bool_)
    if combo.speculative_k:
        # The VERIFY step (ISSUE 18): scores k+1 positions per slot in
        # one chunk-shaped pass. Rule spec-verify-step pins its ring
        # inventory at ONE decode step's — the chunk axis must ride
        # the rings' local operand, never the fabric.
        host = eng.new_host()
        for slot in range(eng.num_slots):
            host.ensure_pages(slot, 8 + combo.speculative_k + 1)
        positions = jnp.full((eng.num_slots,), 8, jnp.int32)
        tokens_chunk = jnp.zeros(
            (eng.num_slots, combo.speculative_k + 1), jnp.int32
        )
        step_args = (
            params, cache, host.device_table(), positions,
            tokens_chunk, active,
        )
        expected = (
            decode_ring_permutes(cfg.num_layers, s)
            if combo.collective_matmul else None
        )
        hlo = eng.verify_step.lower(*step_args).compile().as_text()
        target = LintTarget(
            name=combo.name, engine="serve", donate=True,
            bf16=combo.bf16,
            collective_matmul=combo.collective_matmul,
            cm_axis="model" if combo.collective_matmul else None,
            cm_size=s,
            cm_min_ring_permutes=expected or 0,
            speculative_k=combo.speculative_k,
            spec_verify_permutes=expected,
            n_param_leaves=2,  # the paged cache donates {k, v}
            **_mesh_facts(mesh),
        )
        return target, hlo, mesh
    if combo.page_size is not None:
        # The paged step: block-table gathers/scatters are LOCAL
        # indexing ops, so the decode collective inventory — and
        # therefore every rule expectation below — must be identical
        # to the contiguous step's (the acceptance pin: paging never
        # buys memory with extra wire traffic).
        host = eng.new_host()
        for slot in range(eng.num_slots):
            host.ensure_pages(slot, 8)
        positions = jnp.full((eng.num_slots,), 8, jnp.int32)
        step_args = (
            params, cache, host.device_table(), positions, tokens,
            active,
        )
        n_donated = 2  # the paged cache donates {k, v}
    else:
        step_args = (params, cache, tokens, active)
        n_donated = 3  # {k, v, lengths}
    hlo = eng.decode_step.lower(*step_args).compile().as_text()
    expected = (
        decode_ring_permutes(cfg.num_layers, s)
        if combo.collective_matmul else None
    )
    # Quantized-decode expectations (rule decode-quantized-matmul):
    # trace-level dot records, since compiled CPU HLO normalizes the
    # int8/bf16 dots back to f32. 4 opted-in projections per block,
    # each lowering to S chunk dots under the rings (1 declaratively).
    dot_records = (
        jaxpr_dot_records(eng.decode_step, *step_args)
        if combo.compute_dtype else ()
    )
    quant_dots = (
        4 * cfg.num_layers * (s if combo.collective_matmul else 1)
        if combo.compute_dtype else None
    )
    target = LintTarget(
        name=combo.name, engine="serve", donate=True, bf16=combo.bf16,
        collective_matmul=combo.collective_matmul,
        cm_axis="model" if combo.collective_matmul else None,
        cm_size=s,
        # Floor for the shared cm-ring-permutes rule (GSPMD adds its
        # own resharding permutes on top); the exact tagged pin is
        # serve-decode-ring's.
        cm_min_ring_permutes=expected or 0,
        serve_decode_permutes=expected,
        # The decode step donates the cache leaves.
        n_param_leaves=n_donated,
        compute_dtype=combo.compute_dtype,
        decode_dot_records=dot_records,
        quant_dot_count=quant_dots,
        head_weight_shape=(cfg.dim, cfg.vocab_size),
        **_mesh_facts(mesh),
    )
    return target, hlo, mesh


def _build_plan(combo: Combo, devices):
    """Composed-ParallelPlan train steps (`parallel/plan.py`, ISSUE
    19) on the stage-major ('stage', 'data', 'seq') plan mesh. The
    three plan-* fabric rules read `plan_collective_records` — the
    trace-level inventory from `jaxpr_collective_records` — because
    every contract here is a named-axis one: the plan_wire ppermute
    rides ('stage',), the kv_ring/cm rings ride ('seq',), and the
    plan_grad reduction is one fused psum over all three axes — or,
    for an fsdp plan, per-block plan_fsdp_gather all-gathers and
    their float32 reduce-scatters over ('data',) alone, with one psum
    over the axes that are left (`plan_fsdp` says which contract)."""
    import jax
    import jax.numpy as jnp
    import numpy as np

    from distributed_model_parallel_tpu.parallel.plan import (
        ComposedPlanEngine, parse_plan,
    )
    from distributed_model_parallel_tpu.runtime.mesh import (
        make_plan_mesh,
    )
    from distributed_model_parallel_tpu.training.optim import SGD

    plan = parse_plan(combo.plan)
    if plan.num_devices != combo.size:
        raise ValueError(
            f"combo size {combo.size} != plan {plan.spec!r} device "
            f"count {plan.num_devices}"
        )
    mesh = make_plan_mesh(
        plan.pp, plan.dp, plan.tp_or_sp,
        devices=devices[: plan.num_devices],
    )
    cfg = _gpt_cfg()
    chunks = plan.pp * plan.virtual_stages
    if cfg.num_layers % chunks:
        # Deep-pipeline specs (pp8 at S8) and interleaved ones need a
        # chunk-divisible stack; widen the proxy to pp*V layers.
        import dataclasses as _dc

        cfg = _dc.replace(cfg, num_layers=chunks)
    eng = ComposedPlanEngine(
        cfg, SGD(), mesh, plan, min_shard_elems=64,
        num_microbatches=combo.num_microbatches or None,
    )
    ts = eng.init_state(jax.random.PRNGKey(0))
    rng = np.random.RandomState(0)
    ids = rng.randint(
        1, 61, size=(4 * plan.dp * plan.pp, 16)
    ).astype(np.int32)
    ids, tg = eng.shard_batch(ids)
    hlo = eng.train_step.lower(
        ts, ids, tg, jnp.float32(0.1)
    ).compile().as_text()
    records = jaxpr_collective_records(
        eng.train_step, ts, ids, tg, jnp.float32(0.1)
    )
    target = LintTarget(
        name=combo.name, engine="plan", donate=True,
        plan_axes=(
            ("stage", plan.pp), ("data", plan.dp),
            ("seq", plan.tp_or_sp),
        ),
        plan_collective_records=records,
        plan_schedule=plan.schedule,
        plan_virtual=plan.virtual_stages,
        plan_fsdp=plan.fsdp,
        n_param_leaves=_n_param_leaves(ts),
        **_mesh_facts(mesh),
    )
    return target, hlo, mesh


_BUILDERS: dict = {
    "dp": _build_data_engine,
    "ddp": _build_data_engine,
    "fsdp": _build_data_engine,
    "tp": _build_tp,
    "sp": _build_sp,
    "sp_lm": _build_sp_lm,
    "pipeline": _build_pipeline,
    "cm_ag": _build_cm_op,
    "cm_rs": _build_cm_op,
    "serve": _build_serve,
    "ep": _build_ep,
    "plan": _build_plan,
}


def lower_combo(combo: Combo, devices=None):
    """Lower one combo through its builder: (LintTarget, compiled HLO
    text, mesh) — what the rule driver (`lint_combo`) judges."""
    import jax

    devices = list(devices if devices is not None else jax.devices())
    return _BUILDERS[combo.engine](combo, devices)


def lint_combo(combo: Combo, devices=None) -> LintReport:
    target, hlo, mesh = lower_combo(combo, devices)
    mesh_model = MeshModel.from_mesh(mesh)
    ctx = LintContext.build(target, hlo, mesh_model)
    return LintReport(
        combo=combo,
        target=target,
        findings=run_rules(ctx),
        n_collectives=len(ctx.collectives),
    )


# ------------------------------------------------------------ matrix


def full_matrix() -> List[Combo]:
    """The engine x mode matrix the acceptance criteria name: every
    engine at S in {2,4,8} on its primary axis, DDP/FSDP/CausalLM-SP in
    all three reduction modes, collective_matmul off/on, hybrid
    2 x (S/2) dcn x ici meshes for the reducer paths, the serving
    decode steps (declarative + opted-in rings), plus the bf16 ring
    combos and the tinycnn (BatchNorm) pre-gate twins."""
    combos: List[Combo] = []
    for s in (2, 4, 8):
        combos += [Combo("cm_ag", s), Combo("cm_rs", s)]
        combos.append(Combo("dp", s))
        for gr in ("monolithic", "bucketed", "overlapped"):
            combos.append(Combo("ddp", s, grad_reduction=gr))
            combos.append(Combo("fsdp", s, grad_reduction=gr))
        combos.append(Combo("tp", s))
        combos.append(Combo("tp", s, collective_matmul=True))
        combos.append(Combo("sp", s))
        combos.append(Combo("sp", s, collective_matmul=True))
    for s in (4, 8):  # hybrid 2 x (S/2) dcn x ici
        for gr in ("bucketed", "overlapped"):
            combos.append(Combo("ddp", s, grad_reduction=gr, dcn=2))
            combos.append(Combo("fsdp", s, grad_reduction=gr, dcn=2))
    for s in (2, 4):  # sp_lm: data axis x seq=2 (2S devices)
        for gr in ("monolithic", "bucketed", "overlapped"):
            combos.append(Combo("sp_lm", s, grad_reduction=gr))
    combos.append(Combo("sp_lm", 4, grad_reduction="bucketed", dcn=2))
    combos.append(Combo("sp_lm", 2, collective_matmul=True))
    for s in (2, 4):  # serving decode step, declarative + opted-in
        combos.append(Combo("serve", s))
        combos.append(Combo("serve", s, collective_matmul=True))
    # Paged serving decode (ISSUE 15): the block-table gathers must
    # not change the decode collective inventory — same serve-decode-
    # ring pin (4L(S-1) tagged permutes, zero monolithic all-gather)
    # on the paged step, declarative and opted-in.
    combos.append(Combo("serve", 2, page_size=8))
    combos.append(Combo("serve", 2, page_size=8,
                        collective_matmul=True))
    combos.append(Combo("serve", 4, page_size=8,
                        collective_matmul=True))
    # Quantized decode compute (ISSUE 16, rule decode-quantized-
    # matmul): int8/bf16 projection GEMMs on the declarative and
    # opted-in-ring decode steps — the ring pin (serve-decode-ring)
    # must stay CLEAN on the same combos, since only the chunk dot
    # arithmetic changes; one paged+ring+int8 combo closes the
    # paging x rings x quantization triangle. (serve/S2/cm/q-int8
    # rides in via pregate_matrix().)
    combos.append(Combo("serve", 2, compute_dtype="int8"))
    combos.append(Combo("serve", 4, collective_matmul=True,
                        compute_dtype="int8"))
    combos.append(Combo("serve", 2, compute_dtype="bf16"))
    combos.append(Combo("serve", 2, collective_matmul=True,
                        compute_dtype="bf16"))
    combos.append(Combo("serve", 2, page_size=8,
                        collective_matmul=True,
                        compute_dtype="int8"))
    # Speculative verify step (ISSUE 18, rule spec-verify-step): the
    # one-pass verify must carry exactly one decode step's ring
    # inventory — pinned at S in {2, 4} and k in {2, 4} on the
    # paged+ringed layout, plus a declarative paged combo (generic
    # rules only) so the k>0 lowering itself stays covered without
    # rings. (serve/S2/pg8/cm/spec2 rides in via pregate_matrix().)
    combos.append(Combo("serve", 2, page_size=8, speculative_k=2))
    combos.append(Combo("serve", 4, page_size=8,
                        collective_matmul=True, speculative_k=2))
    combos.append(Combo("serve", 2, page_size=8,
                        collective_matmul=True, speculative_k=4))
    combos += [Combo("pipeline", 2), Combo("pipeline", 4)]
    # Composed ParallelPlan lowerings (ISSUE 19): the genuinely
    # composed 3-axis plan on all 8 devices plus its fsdp-sharded
    # twin — rules plan-wire-fabric / plan-seq-fabric /
    # plan-grad-fabric pin each axis's collectives to its contracted
    # fabric in the composed lowering. (The 4-device pp2xsp2 plan
    # rides in via pregate_matrix().)
    combos.append(Combo("plan", 8, plan="pp2xsp2xdp2"))
    combos.append(Combo("plan", 8, plan="pp2xsp2xfsdp2"))
    # Scheduled tick programs (ISSUE 20): the 1f1b 3-axis plan, the
    # interleaved V=2 plan over the fsdp per-parameter layout, and the
    # gpipe/1f1b twins at M=4 (M just above pp) — plan-wire-fabric
    # pins the per-schedule static ppermute count.
    combos.append(Combo("plan", 8, plan="pp2-1f1bxsp2xdp2"))
    combos.append(Combo("plan", 8, plan="pp2-int2xfsdp4"))
    combos.append(
        Combo("plan", 8, plan="pp2xdp4", num_microbatches=4)
    )
    combos.append(
        Combo("plan", 8, plan="pp2-1f1bxdp4", num_microbatches=4)
    )
    combos.append(
        Combo("plan", 8, plan="pp2-int2xdp4", num_microbatches=4)
    )
    combos.append(Combo("tp", 4, collective_matmul=True, bf16=True))
    combos.append(Combo("sp", 4, collective_matmul=True, bf16=True))
    # MoE dispatch (PR 10): the GSPMD 'expert'-axis baseline plus the
    # hierarchical exchange at S in {4, 8}, overlapped, and on a
    # 2 x (S/2) hybrid fabric — rule moe-hierarchical-a2a's pins.
    combos.append(Combo("ep", 4))  # gspmd baseline
    combos.append(Combo("ep", 4, moe_dispatch="hierarchical"))
    combos.append(
        Combo("ep", 4, moe_dispatch="hierarchical", moe_overlap=True)
    )
    combos.append(
        Combo("ep", 8, dcn=2, moe_dispatch="hierarchical",
              moe_overlap=True)
    )
    # Quantized 'dcn' wire (PR 11, rule dcn-compressed-payload): the
    # compressed cross-slice hop on every engine that exposes it —
    # reducer modes x {bf16, int8} incl. the monolithic single-bucket
    # path, the CausalLM-SP data buckets, and the hierarchical MoE
    # dispatch (unfused + overlapped).
    combos.append(Combo("ddp", 4, grad_reduction="bucketed", dcn=2,
                        dcn_compression="bf16"))
    combos.append(Combo("ddp", 8, grad_reduction="overlapped", dcn=2,
                        dcn_compression="int8"))
    combos.append(Combo("ddp", 4, grad_reduction="monolithic", dcn=2,
                        dcn_compression="int8"))
    combos.append(Combo("fsdp", 4, grad_reduction="bucketed", dcn=2,
                        dcn_compression="bf16"))
    combos.append(Combo("fsdp", 8, grad_reduction="overlapped", dcn=2,
                        dcn_compression="int8"))
    combos.append(Combo("fsdp", 8, grad_reduction="monolithic", dcn=2,
                        dcn_compression="int8"))
    combos.append(Combo("sp_lm", 4, grad_reduction="bucketed", dcn=2,
                        dcn_compression="bf16"))
    combos.append(Combo("sp_lm", 4, grad_reduction="overlapped",
                        dcn=2, dcn_compression="int8"))
    combos.append(Combo("ep", 4, dcn=2, moe_dispatch="hierarchical",
                        dcn_compression="bf16"))
    combos.append(
        Combo("ep", 8, dcn=2, moe_dispatch="hierarchical",
              moe_overlap=True, dcn_compression="int8")
    )
    combos += pregate_matrix()
    return combos


def pregate_matrix() -> List[Combo]:
    """The tier-1 pre-gate subset (tools/tier1.sh): tinycnn DDP + FSDP
    overlapped — the deepest rule stack (rings + overlap deps + BN
    allowlist + at-rest) — plus one tinycnn-sized hierarchical MoE
    combo on a hybrid fabric, so a dispatch regression fails in seconds
    with `moe-hierarchical-a2a` named, one tinycnn-sized quantized
    hybrid combo so a broken wire codec fails with
    `dcn-compressed-payload` named, one quantized ringed serve
    combo so a broken quantized decode path fails with
    `decode-quantized-matmul` (or a broken ring with
    `serve-decode-ring`) named, and one speculative paged+ringed serve
    combo so a verify step that falls off the rings fails with
    `spec-verify-step` named, and one tiny-GPT-sized composed-plan
    combo (ISSUE 19) so a composed lowering whose collectives leave
    their contracted fabric fails with a plan-* rule named."""
    return [
        Combo("ddp", 8, grad_reduction="overlapped", model="tinycnn"),
        Combo("fsdp", 8, grad_reduction="overlapped", model="tinycnn"),
        Combo("ep", 4, dcn=2, moe_dispatch="hierarchical",
              moe_overlap=True),
        Combo("ddp", 4, grad_reduction="bucketed", dcn=2,
              dcn_compression="int8", model="tinycnn"),
        Combo("serve", 2, collective_matmul=True,
              compute_dtype="int8"),
        Combo("serve", 2, page_size=8, collective_matmul=True,
              speculative_k=2),
        Combo("plan", 4, plan="pp2xsp2"),
    ]


# ------------------------------------------------------------ report


def format_report(rep: LintReport) -> str:
    lines = [
        f"[hlolint] {rep.combo.name}: {rep.n_collectives} collectives, "
        f"{len(rep.violations)} violation(s)"
        + (f", {len(rep.findings) - len(rep.violations)} exempted"
           if len(rep.findings) != len(rep.violations) else "")
    ]
    for f in rep.findings:
        mark = "EXEMPT" if f.exempted else f.severity.upper()
        lines.append(f"[hlolint]   {mark} {f.rule}: {f.message}"
                     + (f" (exempt: {f.exemption_reason})"
                        if f.exempted else ""))
    return "\n".join(lines)


def run(combos: Sequence[Combo], devices=None,
        emit: Callable[[str], None] = print) -> dict:
    """Lint each combo, streaming one partial-JSON line per finished
    combo; returns (and emits) the final summary object."""
    reports = []
    for combo in combos:
        try:
            rep = lint_combo(combo, devices)
        except Exception as e:  # a combo that fails to lower is a finding
            emit(f"[hlolint] {combo.name}: LOWERING FAILED: {e!r}")
            emit(json.dumps({
                "leg": {"name": combo.name, "error": repr(e)},
                "partial": True,
            }))
            reports.append(None)
            continue
        emit(format_report(rep))
        emit(json.dumps({
            "leg": {
                "name": combo.name,
                "violations": len(rep.violations),
                "exempted": len(rep.findings) - len(rep.violations),
                "collectives": rep.n_collectives,
            },
            "partial": True,
        }))
        reports.append(rep)
    ok = [r for r in reports if r is not None]
    summary = {
        "hlo_lint": {
            "targets": len(combos),
            "lowered": len(ok),
            "rules": len(REGISTRY),
            "violations": sum(len(r.violations) for r in ok),
            # A combo that fails to LOWER is an error too: an engine
            # regression that crashes lowering must fail the gates, not
            # slip past them with zero rule findings.
            "errors": sum(len(r.errors) for r in ok)
            + (len(combos) - len(ok)),
            "exempted": sum(
                len(r.findings) - len(r.violations) for r in ok
            ),
            "failed_targets": sorted(
                {r.combo.name for r in ok if r.errors}
                | {c.name for c, r in zip(combos, reports)
                   if r is None}
            ),
        }
    }
    emit(json.dumps(summary))
    return summary


def main(argv: Optional[List[str]] = None) -> int:
    import argparse

    parser = argparse.ArgumentParser(
        prog="hlolint",
        description=(
            "Static HLO collective-contract linter: lower engine x "
            "mode combos on a virtual CPU mesh and check the rule "
            "registry (INTERNALS.md section 8b)."
        ),
    )
    parser.add_argument(
        "--pregate", action="store_true",
        help="tier-1 pre-gate subset (tinycnn DDP/FSDP overlapped)",
    )
    parser.add_argument(
        "--filter", default=None,
        help="regex over combo names (e.g. 'ddp.*dcn')",
    )
    parser.add_argument(
        "--list-rules", action="store_true",
        help="print the rule catalog and exit",
    )
    parser.add_argument("--devices", type=int, default=8)
    args = parser.parse_args(argv)

    if args.list_rules:
        for r in REGISTRY.values():
            print(f"{r.id:32s} {r.severity:5s} [{r.source}] "
                  f"{r.contract}")
        return 0

    # Virtual CPU devices BEFORE any backend initializes: the linter
    # reads lowered HLO and jaxprs, a structural check with no chip.
    from distributed_model_parallel_tpu.runtime.platform import force_cpu

    force_cpu(args.devices)

    combos = pregate_matrix() if args.pregate else full_matrix()
    if args.filter:
        import re

        combos = [c for c in combos if re.search(args.filter, c.name)]
    if not combos:
        print("[hlolint] no combos match", file=sys.stderr)
        return 2
    summary = run(combos)
    return 1 if summary["hlo_lint"]["errors"] else 0


if __name__ == "__main__":
    sys.exit(main())
