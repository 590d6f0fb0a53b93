"""64-way structural scaling evidence (BASELINE.json north star:
>=90% weak-scaling efficiency at 64 chips).

This host has ONE real chip, so the evidence is structural + modeled:

1. Lower the ResNet-50 DDP train step on a 64-device virtual mesh and
   read the collective structure out of the StableHLO: every gradient
   leaf's all-reduce, with its byte count (static truth about what the
   program asks the network for).
2. Compile (XLA optimization pipeline, 64-way) the SAME ResNet-50 step
   and capture ITS OWN post-optimization all-reduce op count and bytes
   (the flagship model's own compile is what the cost model must
   eat, not a shape extrapolated from tinycnn's optimized HLO).
   The tinycnn compile+run stays as a cheap liveness check of the
   64-way program.
3. Feed ResNet-50's own post-optimization all-reduce bytes (and op
   count, via an alpha-beta ring model) plus the measured single-chip
   step time (BENCH_r*) and the public v5e ICI bandwidth into the
   standard ring all-reduce cost model to predict weak-scaling
   efficiency at 64 chips — both for this backend's unfused lowering
   and for a bucketed one.

Writes experiments/scaling64.json.

Run: python experiments/scaling64.py   (CPU-only, no TPU needed)
"""

from __future__ import annotations

import json
import os
import re
import sys

sys.path.insert(0, os.path.dirname(os.path.dirname(os.path.abspath(__file__))))

from distributed_model_parallel_tpu.runtime.platform import force_cpu  # noqa: E402

force_cpu(64)

import jax  # noqa: E402
import jax.numpy as jnp  # noqa: E402
import numpy as np  # noqa: E402

from distributed_model_parallel_tpu.models.resnet import resnet50  # noqa: E402
from distributed_model_parallel_tpu.models.tinycnn import tiny_cnn  # noqa: E402
from distributed_model_parallel_tpu.observability import cost  # noqa: E402
from distributed_model_parallel_tpu.parallel.data_parallel import (  # noqa: E402
    DDPEngine,
)
from distributed_model_parallel_tpu.runtime.mesh import (  # noqa: E402
    MeshSpec,
    make_mesh,
)
from distributed_model_parallel_tpu.training.optim import SGD  # noqa: E402

N = 64
PER_CHIP_BATCH = 256

# Measured on one v5e chip before PR 1 (BENCH_r04.json): ResNet-50
# bs256 bf16, 2489 img/s/chip -> 0.1029 s/step, MFU 0.30.
MEASURED_STEP_S = 256 / 2489.0
# Per-fabric alpha/beta constants: ONE home, shared with the static
# cost engine (`observability/cost.py` — provenance documented there),
# so this script's hand-derived rows and the checked `tools/costgate`
# ledger can never drift apart. Each §3 row below is ASSERTED against
# the cost engine's closed-form prediction within 1%
# (`_assert_cost_engine_agrees`).
BW_ICI_EFFECTIVE = cost.BW_ICI_EFFECTIVE
ALPHA_HOP_S = cost.ALPHA_HOP_S
BW_DCN_EFFECTIVE = cost.BW_DCN_EFFECTIVE
ALPHA_DCN_HOP_S = cost.ALPHA_DCN_HOP_S
# Two-level (dcn × ici) hierarchy for the bucketed reducer
# (`ops/grad_reduction.py`): a 64-chip job as 2 slices × 32 chips.
DCN_SLICES = 2
BUCKET_MB = 25.0  # the reducer's default bucket_cap_mb
# MoE dispatch (step 3c): one routed layer's token exchange, sized for
# a GPT-MoE block — per-chip token load, model dim, top-k routing with
# the Switch capacity factor. The dispatch buffer each device must
# exchange is ~top_k * capacity_factor * tokens * dim bytes.
MOE_TOKENS_PER_CHIP = 4096   # B*T tokens resident per chip
MOE_DIM = 1024
MOE_TOP_K = 2
MOE_CAPACITY_FACTOR = 1.25
MOE_FFN_HIDDEN = 4 * MOE_DIM
# Per-chip dense-equivalent MXU throughput for hiding the exchange
# (peak bf16 ~197 TF/s on v5e at a conservative 0.3 MFU).
MOE_EFFECTIVE_FLOPS = 197e12 * 0.3


def optimized_all_reduce_bytes(text):
    """(op count, total reduced bytes) from POST-OPTIMIZATION HLO text.
    The op's OUTPUT shape(s) lead its definition — `%all-reduce.N =
    f32[1,1,256,1024]{3,2,1,0} all-reduce(...)`, or a parenthesized
    tuple for fused/async variants — so parse the text between '=' and
    the op name. `-done` ops are excluded (they'd double-count their
    `-start`), and an async `-start` op's tuple shape is (aliased
    operands, results), so only HALF its listed buffers are reduced
    bytes — counting both would double the beta term."""
    dt_bytes = {"f32": 4, "bf16": 2, "f16": 2, "f64": 8, "s32": 4,
                "u32": 4, "pred": 1}
    n_ops = 0
    total_bytes = 0
    for m in re.finditer(
        r"=\s*((?:\([^)]*\))|(?:\S+))\s+all-reduce(-start)?\(", text
    ):
        n_ops += 1
        op_bytes = 0
        for dt, dims in re.findall(r"([a-z0-9]+)\[([0-9,]*)\]",
                                   m.group(1)):
            nelems = 1
            for d in dims.split(","):
                if d:
                    nelems *= int(d)
            op_bytes += nelems * dt_bytes.get(dt, 4)
        if m.group(2) and m.group(1).startswith("("):
            op_bytes //= 2  # (operands, results) alias tuple
        total_bytes += op_bytes
    return n_ops, total_bytes


def stablehlo_all_reduce_bytes(text):
    """(op count, total reduced bytes) from StableHLO text. The op's
    operand signature `: (tensor<...>) -> ...` trails the (multi-line)
    reducer region, so scan from each op start to its signature."""
    dt_bytes = {"f32": 4, "bf16": 2, "f16": 2, "f64": 8, "i32": 4}
    n_ops = 0
    total_bytes = 0
    sig = re.compile(r":\s*\(tensor<([^>]+)>\)")
    for m in re.finditer(r'"?stablehlo\.all_reduce"?', text):
        s = sig.search(text, m.end())
        if not s:
            continue
        n_ops += 1
        dims = s.group(1).split("x")
        nelems = 1
        for d in dims[:-1]:
            if d.isdigit():
                nelems *= int(d)
        total_bytes += nelems * dt_bytes.get(dims[-1], 4)
    return n_ops, total_bytes


def _assert_cost_engine_agrees(label, hand_s, engine_s):
    """The drift tripwire: a §3 row's hand arithmetic and the cost
    engine's closed-form prediction must agree within 1% — edit one
    without the other and this script fails, not the prose."""
    if abs(hand_s - engine_s) > 0.01 * max(abs(hand_s), 1e-12):
        raise AssertionError(
            f"{label}: hand-derived {hand_s:.6e}s disagrees with the "
            f"cost engine's {engine_s:.6e}s by more than 1% — "
            "observability/cost.py and scaling64.py drifted"
        )


def main():
    mesh = make_mesh(MeshSpec(data=N))
    assert mesh.shape["data"] == N

    # ---- 1. ResNet-50 DDP: lower (SPMD trace) and read the asks ------
    eng = DDPEngine(
        resnet50(1000), SGD(momentum=0.9), mesh,
        compute_dtype=jnp.bfloat16, donate=False,
    )
    state_aval = jax.eval_shape(eng.init_state, jax.ShapeDtypeStruct(
        (2,), jnp.uint32))
    n_params = sum(
        int(np.prod(l.shape))
        for l in jax.tree_util.tree_leaves(state_aval.params)
    )
    imgs = jax.ShapeDtypeStruct((N * PER_CHIP_BATCH, 224, 224, 3),
                                jnp.float32)
    lbls = jax.ShapeDtypeStruct((N * PER_CHIP_BATCH,), jnp.int32)
    lowered = eng.train_step.lower(
        state_aval, imgs, lbls, jax.ShapeDtypeStruct((), jnp.float32)
    )
    text = lowered.as_text()
    n_ar, ar_bytes = stablehlo_all_reduce_bytes(text)
    grad_bytes_f32 = n_params * 4
    print(f"ResNet-50 params: {n_params/1e6:.1f} M "
          f"({grad_bytes_f32/1e6:.1f} MB f32 grads)")
    print(f"StableHLO all_reduce ops: {n_ar}, reduced bytes: "
          f"{ar_bytes/1e6:.1f} MB")

    # ---- 2. ResNet-50's OWN 64-way post-optimization collectives -----
    # (compile-only: ~20 s on this host; nothing executes). The op
    # count/bytes feeding the cost model now come from the flagship
    # model's own optimized program instead of a tinycnn extrapolation.
    rn_compiled = lowered.compile()
    n_opt_ar, opt_ar_bytes = optimized_all_reduce_bytes(
        rn_compiled.as_text()
    )
    print(f"ResNet-50 64-way optimized HLO: {n_opt_ar} all-reduce ops, "
          f"{opt_ar_bytes/1e6:.1f} MB reduced "
          f"(combiner {'ran' if n_opt_ar < n_ar else 'did NOT run'} on "
          f"this backend)")

    # ---- 2b. tinycnn 64-way compile + ONE real step: liveness check --
    small = DDPEngine(tiny_cnn(10), SGD(), mesh, donate=False)
    ts = small.init_state(jax.random.PRNGKey(0))
    x = np.random.RandomState(0).rand(N * 4, 8, 8, 3).astype(np.float32)
    y = np.random.RandomState(1).randint(0, 10, N * 4).astype(np.int32)
    xs, ys = small.shard_batch(x, y)
    compiled = small.train_step.lower(
        ts, xs, ys, jnp.float32(0.1)
    ).compile()
    n_small_ar, _ = optimized_all_reduce_bytes(compiled.as_text())
    small_leaves = len(jax.tree_util.tree_leaves(ts.params))
    ts2, m = compiled(ts, xs, ys, jnp.float32(0.1))
    loss0 = float(m["loss_sum"]) / float(m["count"])
    print(f"tinycnn 64-way liveness: {small_leaves} grad leaves -> "
          f"{n_small_ar} optimized all-reduce ops; one step ran, "
          f"loss {loss0:.3f}")

    # ---- 3. ring all-reduce cost model on the MEASURED lowering ------
    # Ring all-reduce moves 2*(N-1)/N * bytes per chip (beta term) and
    # pays 2*(N-1) latency hops PER OP (alpha term) — the alpha term
    # only matters because step 2 shows this backend keeps ResNet-50's
    # per-leaf reduces unfused. XLA overlaps comm with the remaining
    # backward, so bound both ends: zero overlap (worst) and full
    # overlap (best ~= max(compute, comm)). The bucketed-bound row is
    # the same bytes in ONE fused op — the TPU pipeline's all-reduce
    # combiner / the DDP Reducer's bucketing — since this CPU backend's
    # unfused lowering is a backend artifact, not a program property
    # (the StableHLO asks are identical).
    beta_s = 2 * (N - 1) / N * opt_ar_bytes / BW_ICI_EFFECTIVE
    alpha_s = n_opt_ar * 2 * (N - 1) * ALPHA_HOP_S
    alpha_bucketed_s = 1 * 2 * (N - 1) * ALPHA_HOP_S
    comm_s = beta_s + alpha_s
    comm_bucketed_s = beta_s + alpha_bucketed_s
    eff_no_overlap = MEASURED_STEP_S / (MEASURED_STEP_S + comm_s)
    eff_overlap = MEASURED_STEP_S / max(MEASURED_STEP_S, comm_s)
    eff_bucketed = MEASURED_STEP_S / (MEASURED_STEP_S + comm_bucketed_s)
    print(f"ring all-reduce (as lowered, {n_opt_ar} ops): "
          f"{beta_s*1e3:.2f} ms bandwidth + {alpha_s*1e3:.2f} ms "
          f"latency vs step {MEASURED_STEP_S*1e3:.1f} ms")
    print(f"predicted weak-scaling efficiency @64: "
          f"{eff_no_overlap:.3f} (no overlap, as lowered) .. "
          f"{eff_overlap:.3f} (full overlap); "
          f"{eff_bucketed:.3f} (no overlap, bucketed)")
    _assert_cost_engine_agrees(
        "ring all-reduce (as lowered)", comm_s,
        cost.ring_all_reduce_s(opt_ar_bytes, N, n_ops=n_opt_ar),
    )
    _assert_cost_engine_agrees(
        "ring all-reduce (bucketed)", comm_bucketed_s,
        cost.ring_all_reduce_s(opt_ar_bytes, N, n_ops=1),
    )

    # ---- 3b. two-level alpha-beta: the hierarchical bucketed reducer -
    # 64 chips as DCN_SLICES slices × ici chips. A FLAT 64-ring would
    # push the full gradient through the slice boundary at DCN
    # bandwidth (its slowest link gates the ring); the hierarchical
    # reducer (`ops/grad_reduction.py` — reduce-scatter over 'ici',
    # all-reduce of the 1/ici shard over 'dcn', all-gather back) keeps
    # the DCN bytes at 1/ici of the payload. Alpha counts per-bucket
    # hops (the Reducer's ~25 MB buckets), each fabric at its own hop
    # cost.
    ici = N // DCN_SLICES
    n_buckets = max(1, -(-opt_ar_bytes // int(BUCKET_MB * 2**20)))
    beta_flat_dcn_s = 2 * (N - 1) / N * opt_ar_bytes / BW_DCN_EFFECTIVE
    comm_flat_dcn_s = beta_flat_dcn_s + alpha_bucketed_s
    beta_two_level_s = (
        2 * (ici - 1) / ici * opt_ar_bytes / BW_ICI_EFFECTIVE
        + 2 * (DCN_SLICES - 1) / DCN_SLICES
        * (opt_ar_bytes / ici) / BW_DCN_EFFECTIVE
    )
    alpha_two_level_s = n_buckets * (
        2 * (ici - 1) * ALPHA_HOP_S
        + 2 * (DCN_SLICES - 1) * ALPHA_DCN_HOP_S
    )
    comm_two_level_s = beta_two_level_s + alpha_two_level_s
    eff_flat_dcn = MEASURED_STEP_S / (MEASURED_STEP_S + comm_flat_dcn_s)
    eff_two_level = MEASURED_STEP_S / (
        MEASURED_STEP_S + comm_two_level_s
    )
    eff_two_level_overlap = MEASURED_STEP_S / max(
        MEASURED_STEP_S, comm_two_level_s
    )
    print(f"two-level ({DCN_SLICES}x{ici} dcn*ici, {n_buckets} buckets "
          f"of {BUCKET_MB:.0f} MB): {beta_two_level_s*1e3:.2f} ms "
          f"bandwidth + {alpha_two_level_s*1e3:.2f} ms latency "
          f"(flat ring gated by DCN: {beta_flat_dcn_s*1e3:.2f} ms)")
    print(f"predicted weak-scaling efficiency @64 across 2 slices: "
          f"{eff_flat_dcn:.3f} (flat ring over DCN) -> "
          f"{eff_two_level:.3f} (hierarchical bucketed, no overlap) .. "
          f"{eff_two_level_overlap:.3f} (full overlap)")
    _assert_cost_engine_agrees(
        "flat ring over dcn", comm_flat_dcn_s,
        cost.ring_all_reduce_s(
            opt_ar_bytes, N, n_ops=1, bw=BW_DCN_EFFECTIVE
        ),
    )
    _assert_cost_engine_agrees(
        "two-level bucketed reducer", comm_two_level_s,
        cost.two_level_all_reduce_s(
            opt_ar_bytes, ici, DCN_SLICES, n_buckets=n_buckets
        ),
    )

    # ---- 3b'. compressed 'dcn' wire on the bucketed reducer ----------
    # (`ops/wire_codec.py`, PR 11). The intra-slice legs stay f32; only
    # the cross-slice term scales with the wire itemsize. int8 adds one
    # f32 scale sidecar per hop (4 B x 2(K-1) x n_buckets — noise) and
    # one extra tiny ppermute per payload hop (counted in alpha).
    dcn_beta_f32_s = (
        2 * (DCN_SLICES - 1) / DCN_SLICES
        * (opt_ar_bytes / ici) / BW_DCN_EFFECTIVE
    )
    wire_rows = {}
    for wire, wbytes, sidecar_hops in (
        ("bf16", 2, 0), ("int8", 1, 1)
    ):
        dcn_beta_wire_s = dcn_beta_f32_s * wbytes / 4
        beta_wire_s = (
            2 * (ici - 1) / ici * opt_ar_bytes / BW_ICI_EFFECTIVE
            + dcn_beta_wire_s
        )
        alpha_wire_s = n_buckets * (
            2 * (ici - 1) * ALPHA_HOP_S
            + (1 + sidecar_hops) * 2 * (DCN_SLICES - 1)
            * ALPHA_DCN_HOP_S
        )
        comm_wire_s = beta_wire_s + alpha_wire_s
        eff_wire = MEASURED_STEP_S / (MEASURED_STEP_S + comm_wire_s)
        wire_rows[wire] = dict(
            dcn_beta_s=round(dcn_beta_wire_s, 6),
            comm_s=round(comm_wire_s, 6),
            eff=round(eff_wire, 4),
        )
        print(f"compressed grad wire ({wire}): dcn leg "
              f"{dcn_beta_f32_s*1e3:.2f} -> {dcn_beta_wire_s*1e3:.2f} "
              f"ms, total comm {comm_wire_s*1e3:.2f} ms, "
              f"efficiency {eff_wire:.3f} (f32 hierarchical: "
              f"{eff_two_level:.3f})")
        _assert_cost_engine_agrees(
            f"compressed grad wire ({wire})", comm_wire_s,
            cost.two_level_all_reduce_s(
                opt_ar_bytes, ici, DCN_SLICES, n_buckets=n_buckets,
                wire=wire,
            ),
        )

    # ---- 3c. two-level a2a: the hierarchical MoE token exchange ------
    # One routed layer's dispatch+combine at 64 chips as DCN_SLICES x
    # ici (`ops/expert_dispatch.py`). The FLAT all-to-all sends each of
    # the S-1 partners X/S bytes: (K-1)*ici of those messages cross the
    # slice boundary — the alpha term pays (K-1)*ici DCN hops and the
    # full (K-1)/K of the payload rides DCN. The HIERARCHICAL exchange
    # moves the same cross-slice bytes (tokens must cross) but as K-1
    # contiguous messages of the 1/ici-regrouped shard — ici x fewer
    # DCN hops — and the (ici-1)/ici intra-slice share rides ICI
    # exclusively. OVERLAPPED additionally hides the exchange behind
    # the per-chunk expert FFN (the chunked ppermute decomposition).
    moe_x_elems = int(
        MOE_TOP_K * MOE_CAPACITY_FACTOR * MOE_TOKENS_PER_CHIP * MOE_DIM
    )
    moe_x_bytes = moe_x_elems * 2  # bf16 activations (the §3c shape)
    # per-exchange (dispatch or combine), per device:
    a2a_flat_s = (
        (DCN_SLICES - 1) / DCN_SLICES * moe_x_bytes / BW_DCN_EFFECTIVE
        + (ici - 1) / N * moe_x_bytes / BW_ICI_EFFECTIVE
        + (DCN_SLICES - 1) * ici * ALPHA_DCN_HOP_S
        + (ici - 1) * ALPHA_HOP_S
    )
    a2a_two_level_s = (
        (DCN_SLICES - 1) / DCN_SLICES * moe_x_bytes / BW_DCN_EFFECTIVE
        + (ici - 1) / ici * moe_x_bytes / BW_ICI_EFFECTIVE
        + (DCN_SLICES - 1) * ALPHA_DCN_HOP_S
        + (ici - 1) * ALPHA_HOP_S
    )
    # Expert FFN compute available to hide behind (per device, all its
    # routed tokens through the two dense matmuls):
    moe_ffn_flops = (
        4 * MOE_TOP_K * MOE_CAPACITY_FACTOR * MOE_TOKENS_PER_CHIP
        * MOE_DIM * MOE_FFN_HIDDEN
    )
    moe_ffn_s = moe_ffn_flops / MOE_EFFECTIVE_FLOPS
    moe_layer_flat_s = 2 * a2a_flat_s + moe_ffn_s
    moe_layer_two_level_s = 2 * a2a_two_level_s + moe_ffn_s
    moe_layer_overlap_s = max(2 * a2a_two_level_s, moe_ffn_s)
    print(f"MoE a2a ({DCN_SLICES}x{ici} dcn*ici, "
          f"{moe_x_bytes/1e6:.1f} MB dispatch buffer/chip): "
          f"flat {a2a_flat_s*1e3:.2f} ms/exchange "
          f"({(DCN_SLICES-1)*ici} DCN hops) -> two-level "
          f"{a2a_two_level_s*1e3:.2f} ms ({DCN_SLICES-1} DCN hop)")
    _assert_cost_engine_agrees(
        "MoE flat a2a", a2a_flat_s,
        cost.flat_all_to_all_s(moe_x_elems, 2, ici, DCN_SLICES),
    )
    _assert_cost_engine_agrees(
        "MoE two-level a2a", a2a_two_level_s,
        cost.hierarchical_all_to_all_s(
            moe_x_elems, 2, ici, DCN_SLICES
        ),
    )
    print(f"per MoE layer (2 exchanges + FFN {moe_ffn_s*1e3:.2f} ms): "
          f"flat {moe_layer_flat_s*1e3:.2f} ms, hierarchical "
          f"{moe_layer_two_level_s*1e3:.2f} ms, overlapped "
          f"{moe_layer_overlap_s*1e3:.2f} ms "
          f"(exchange {'hidden' if moe_ffn_s >= 2*a2a_two_level_s else 'exposed'})")

    # ---- 3c'. compressed 'dcn' wire on the MoE dispatch --------------
    # The intra-slice regroup stays at the activation dtype (bf16
    # here); only the cross-slice messages scale with the wire
    # itemsize. f32 is the uncompressed worst case (f32 activations,
    # no codec); int8 quarters it.
    moe_wire_rows = {}
    for wire, wbytes in (("f32", 4), ("bf16", 2), ("int8", 1)):
        dcn_leg_s = (
            (DCN_SLICES - 1) / DCN_SLICES
            * (moe_x_elems * wbytes) / BW_DCN_EFFECTIVE
        )
        a2a_wire_s = (
            dcn_leg_s
            + (ici - 1) / ici * moe_x_bytes / BW_ICI_EFFECTIVE
            + (DCN_SLICES - 1) * ALPHA_DCN_HOP_S
            + (ici - 1) * ALPHA_HOP_S
        )
        layer_s = 2 * a2a_wire_s + moe_ffn_s
        layer_overlap_s = max(2 * a2a_wire_s, moe_ffn_s)
        moe_wire_rows[wire] = dict(
            a2a_s=round(a2a_wire_s, 6),
            layer_s=round(layer_s, 6),
            layer_overlapped_s=round(layer_overlap_s, 6),
        )
        print(f"compressed dispatch wire ({wire}): "
              f"{a2a_wire_s*1e3:.2f} ms/exchange, per layer "
              f"{layer_s*1e3:.2f} ms unfused / "
              f"{layer_overlap_s*1e3:.2f} ms overlapped")
        _assert_cost_engine_agrees(
            f"compressed dispatch wire ({wire})", a2a_wire_s,
            cost.hierarchical_all_to_all_s(
                moe_x_elems, 2, ici, DCN_SLICES, wire=wire
            ),
        )

    # ---- 3e. tuner argmin rows (tuning/search.py closed forms) -------
    # The auto-tuner's answer for this @64 2x32 cell, next to the
    # hand-picked §3a-§3d rows: enumerate each family's knob space and
    # score with the SAME closed forms the rows above assert against.
    # The hand configurations are points IN the searched space, so the
    # argmin can never predict WORSE than them — asserted, like the
    # cost-engine agreement tripwire.
    from distributed_model_parallel_tpu.tuning.search import (
        closed_form_argmin,
    )

    grad_knobs, grad_argmin_s = closed_form_argmin(
        "ddp",
        {"grad_bytes": opt_ar_bytes, "n_blocks": 16},
        ici, DCN_SLICES,
    )
    print(f"tuner argmin (grad reduction @{DCN_SLICES}x{ici}): "
          f"{json.dumps(grad_knobs, sort_keys=True)} -> "
          f"{grad_argmin_s*1e3:.2f} ms (hand §3b bucketed row: "
          f"{comm_two_level_s*1e3:.2f} ms)")
    assert grad_argmin_s <= comm_two_level_s * (1 + 1e-9), (
        f"tuner argmin {grad_argmin_s:.6e}s predicts WORSE than the "
        f"hand §3b configuration {comm_two_level_s:.6e}s — the hand "
        "config is in the search space, so the search is broken"
    )
    moe_knobs, moe_argmin_s = closed_form_argmin(
        "ep",
        {"elems": moe_x_elems, "itemsize": 2},
        ici, DCN_SLICES,
    )
    moe_hand_pair_s = 2 * a2a_two_level_s  # §3c dispatch+combine
    print(f"tuner argmin (MoE dispatch @{DCN_SLICES}x{ici}): "
          f"{json.dumps(moe_knobs, sort_keys=True)} -> "
          f"{moe_argmin_s*1e3:.2f} ms/exchange pair (hand §3c "
          f"hierarchical pair: {moe_hand_pair_s*1e3:.2f} ms)")
    assert moe_argmin_s <= moe_hand_pair_s * (1 + 1e-9), (
        f"tuner argmin {moe_argmin_s:.6e}s predicts WORSE than the "
        f"hand §3c configuration {moe_hand_pair_s:.6e}s — the hand "
        "config is in the search space, so the search is broken"
    )
    tuned_rows = {
        "grad_reduction": {
            "knobs": grad_knobs,
            "predicted_s": round(grad_argmin_s, 6),
            "hand_two_level_s": round(comm_two_level_s, 6),
        },
        "moe_dispatch": {
            "knobs": moe_knobs,
            "predicted_exchange_pair_s": round(moe_argmin_s, 6),
            "hand_exchange_pair_s": round(moe_hand_pair_s, 6),
        },
    }

    # ---- 3f. composed-plan rows (ISSUE 19, parallel/plan.py) ---------
    # The plan family searches WHOLE mesh factorizations of the same
    # 2x32 fabric: a GPT-XL-ish training step (dim 1024, 16 layers,
    # vocab 32k, seq 2048, 8-row microbatches) under
    # `cost.composed_plan_step_s` — gpipe wire ticks across 'dcn',
    # ring-attention KV hops on 'ici', ONE fused gradient psum priced
    # as §3b's two-level form. The single-axis degenerate specs (dp64,
    # fsdp64, pp64) are points IN the plan space, so the tuner's
    # argmin can never predict worse than the best of them — asserted
    # like the §3e rows. NOTE the closed forms price what the program
    # ASKS THE NETWORK for (no compute/memory term), so pure-dp
    # factorizations — whose only collective is the fused psum —
    # structurally dominate at this payload; the anatomy rows record
    # what each added axis COSTS in asked bytes, which is the real
    # content of the comparison (pp/sp buy memory headroom the model
    # doesn't price).
    from distributed_model_parallel_tpu.tuning.search import (
        closed_form_step_s,
    )

    PLAN_DIM = 1024
    PLAN_VOCAB = 32768
    PLAN_LAYERS = 16
    PLAN_SEQ = 2048
    PLAN_MB = 8
    # ~12 D^2 per decoder block (QKV+proj 4D^2, FFN pair 8D^2) plus
    # the tied embedding/head table.
    plan_grad_bytes = (
        PLAN_LAYERS * 12 * PLAN_DIM * PLAN_DIM
        + PLAN_VOCAB * PLAN_DIM
    ) * 4
    plan_payload = {
        "grad_bytes": plan_grad_bytes, "mb": PLAN_MB,
        "seq_len": PLAN_SEQ, "dim": PLAN_DIM, "vocab": PLAN_VOCAB,
        "n_layers": PLAN_LAYERS,
    }
    plan_knobs, plan_argmin_s = closed_form_argmin(
        "plan", plan_payload, ici, DCN_SLICES,
    )
    # Hand dp64 row: the dp-only composed plan's one collective is the
    # fused psum over all 64 devices — at 2 slices the hierarchical
    # decomposition IS §3b's two-level form at one bucket.
    hand_dp64_s = cost.two_level_all_reduce_s(
        plan_grad_bytes, ici, DCN_SLICES, n_buckets=1
    )
    _assert_cost_engine_agrees(
        "composed-plan dp64 fused psum", hand_dp64_s,
        closed_form_step_s(
            "plan", {"plan": "dp64"}, plan_payload, ici, DCN_SLICES
        ),
    )
    plan_single_axis = {}
    for spec in ("dp64", "fsdp64", "pp64"):
        s = closed_form_step_s(
            "plan", {"plan": spec}, plan_payload, ici, DCN_SLICES
        )
        plan_single_axis[spec] = round(s, 6)
        assert plan_argmin_s <= s * (1 + 1e-9), (
            f"plan-family argmin {plan_argmin_s:.6e}s predicts WORSE "
            f"than the single-axis plan {spec} at {s:.6e}s — "
            "single-axis specs are in the plan space, so the search "
            "is broken"
        )
    # Anatomy: what each composed axis ADDS on top of the fused psum.
    plan_anatomy = {
        spec: round(closed_form_step_s(
            "plan", {"plan": spec}, plan_payload, ici, DCN_SLICES
        ), 6)
        for spec in ("pp2xdp32", "sp2xdp32", "pp2xsp2xdp16",
                     "pp2xsp2xfsdp16")
    }
    print(f"tuner argmin (composed plan @{DCN_SLICES}x{ici}): "
          f"{json.dumps(plan_knobs, sort_keys=True)} -> "
          f"{plan_argmin_s*1e3:.2f} ms (best single-axis: "
          f"{min(plan_single_axis.values())*1e3:.2f} ms; composed "
          f"pp2xsp2xdp16: {plan_anatomy['pp2xsp2xdp16']*1e3:.2f} ms)")
    # Scheduled-plan rows (ISSUE 20): the gpipe/1f1b/int2 twins of
    # ONE pp2 factorization at fixed M=4, priced with the compute x
    # bubble fold ('params' in the payload turns it on) ON TOP of the
    # asked-bytes wire terms. The schedule changes only the tick
    # program, so the twins share layouts and collectives; the rows
    # record what each schedule's bubble costs (gpipe/1f1b (M+pp-1)/M,
    # interleaved (VM+pp-1)/VM) against its extra wire ticks. The
    # gpipe plan is a POINT in the scheduled space, so the argmin
    # over the grown space can never predict worse than it — the
    # never-worse-than-gpipe assertion, like §3e's.
    plan_sched_payload = dict(plan_payload, params=plan_grad_bytes // 4)
    plan_sched = {}
    for spec in ("pp2xdp32", "pp2-1f1bxdp32", "pp2-int2xdp32"):
        plan_sched[spec] = round(closed_form_step_s(
            "plan", {"plan": spec, "num_microbatches": 4},
            plan_sched_payload, ici, DCN_SLICES,
        ), 6)
    sched_knobs, sched_argmin_s = closed_form_argmin(
        "plan", plan_sched_payload, ici, DCN_SLICES,
    )
    assert sched_argmin_s <= plan_sched["pp2xdp32"] * (1 + 1e-9), (
        f"scheduled-plan argmin {sched_argmin_s:.6e}s predicts WORSE "
        f"than the gpipe pp2xdp32/M4 row "
        f"{plan_sched['pp2xdp32']:.6e}s — the gpipe plan is a point "
        "in the scheduled space, so the search is broken"
    )
    print(f"tuner argmin (scheduled plan @{DCN_SLICES}x{ici}, with "
          f"compute fold): {json.dumps(sched_knobs, sort_keys=True)} "
          f"-> {sched_argmin_s*1e3:.2f} ms (gpipe twin @M4: "
          f"{plan_sched['pp2xdp32']*1e3:.2f} ms, 1f1b: "
          f"{plan_sched['pp2-1f1bxdp32']*1e3:.2f} ms, int2: "
          f"{plan_sched['pp2-int2xdp32']*1e3:.2f} ms)")
    plan_rows = {
        "payload": plan_payload,
        "argmin": {
            "knobs": plan_knobs,
            "predicted_s": round(plan_argmin_s, 6),
        },
        "single_axis_s": plan_single_axis,
        "composed_anatomy_s": plan_anatomy,
        "scheduled_twins_s": plan_sched,
        "scheduled_argmin": {
            "knobs": sched_knobs,
            "predicted_s": round(sched_argmin_s, 6),
        },
    }

    out = {
        "n_devices": N,
        "per_chip_batch": PER_CHIP_BATCH,
        "model": "resnet50",
        "params_m": round(n_params / 1e6, 2),
        "grad_bytes_f32": grad_bytes_f32,
        "stablehlo_all_reduce_ops": n_ar,
        "stablehlo_all_reduce_bytes": ar_bytes,
        "resnet50_optimized_all_reduce_ops": n_opt_ar,
        "resnet50_optimized_all_reduce_bytes": opt_ar_bytes,
        "tinycnn_grad_leaves": small_leaves,
        "tinycnn_optimized_all_reduce_ops": n_small_ar,
        "tinycnn_64way_step_loss": loss0,
        "measured_step_s_1chip": round(MEASURED_STEP_S, 5),
        "ici_bw_effective_bytes_per_s": BW_ICI_EFFECTIVE,
        "alpha_hop_s": ALPHA_HOP_S,
        "ring_allreduce_beta_s": round(beta_s, 6),
        "ring_allreduce_alpha_s": round(alpha_s, 6),
        "ring_allreduce_s": round(comm_s, 6),
        "predicted_weak_scaling_eff_64_no_overlap": round(
            eff_no_overlap, 4),
        "predicted_weak_scaling_eff_64_full_overlap": round(
            eff_overlap, 4),
        "predicted_weak_scaling_eff_64_bucketed_no_overlap": round(
            eff_bucketed, 4),
        # two-level (dcn × ici) hierarchical bucketed reducer row
        "dcn_slices": DCN_SLICES,
        "dcn_bw_effective_bytes_per_s": BW_DCN_EFFECTIVE,
        "alpha_dcn_hop_s": ALPHA_DCN_HOP_S,
        "bucket_mb": BUCKET_MB,
        "n_buckets": int(n_buckets),
        "ring_allreduce_flat_over_dcn_s": round(comm_flat_dcn_s, 6),
        "two_level_beta_s": round(beta_two_level_s, 6),
        "two_level_alpha_s": round(alpha_two_level_s, 6),
        "two_level_s": round(comm_two_level_s, 6),
        "predicted_weak_scaling_eff_64_2slice_flat_ring": round(
            eff_flat_dcn, 4),
        "predicted_weak_scaling_eff_64_2slice_hierarchical": round(
            eff_two_level, 4),
        "predicted_weak_scaling_eff_64_2slice_hierarchical_overlap":
            round(eff_two_level_overlap, 4),
        # two-level MoE token-exchange row (ops/expert_dispatch.py)
        "moe_dispatch_bytes_per_chip": moe_x_bytes,
        "moe_a2a_flat_s": round(a2a_flat_s, 6),
        "moe_a2a_two_level_s": round(a2a_two_level_s, 6),
        "moe_ffn_s": round(moe_ffn_s, 6),
        "moe_layer_flat_s": round(moe_layer_flat_s, 6),
        "moe_layer_hierarchical_s": round(moe_layer_two_level_s, 6),
        "moe_layer_overlapped_s": round(moe_layer_overlap_s, 6),
        "moe_dcn_hops_flat": (DCN_SLICES - 1) * ici,
        "moe_dcn_hops_hierarchical": DCN_SLICES - 1,
        # compressed 'dcn' wire rows (PR 11, ops/wire_codec.py)
        "grad_wire_rows": wire_rows,
        "moe_wire_rows": moe_wire_rows,
        # tuner argmin rows (tuning/search.py closed forms) — asserted
        # never worse than the hand §3b/§3c configurations above
        "tuned_rows": tuned_rows,
        # composed-plan factorization rows (ISSUE 19) — argmin
        # asserted never worse than every single-axis degenerate spec
        "plan_rows": plan_rows,
    }
    path = os.path.join(os.path.dirname(os.path.abspath(__file__)),
                        "scaling64.json")
    with open(path, "w") as f:
        json.dump(out, f, indent=1)
    print(f"wrote {path}")


if __name__ == "__main__":
    main()
