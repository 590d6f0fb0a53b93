"""Per-rule positive/negative tests for the collective-contract
registry (`analysis/rules.py`): every rule is exercised on canned HLO
(and canned LintTargets) with one case where the contract is VIOLATED
(the rule must fire) and one where it holds (the rule must stay
silent). The conftest meta-check walks the `hlo_rule(<id>, <polarity>)`
markers and fails collection if a registered rule is missing either
polarity — a rule nobody can trip is a rule nobody can trust.

No lowering here: synthetic modules keep these tier-1 fast. The live
negatives (real engines lint clean) are tests/test_hlolint.py."""

import pytest

from distributed_model_parallel_tpu.analysis.collectives import MeshModel
from distributed_model_parallel_tpu.analysis.rules import (
    LintContext,
    LintTarget,
    REGISTRY,
)

MESH8 = MeshModel(
    axis_names=("data",), shape=(8,), coords={d: (d,) for d in range(8)}
)
MESH_2x4 = MeshModel(
    axis_names=("dcn", "ici"), shape=(2, 4),
    coords={d: (d // 4, d % 4) for d in range(8)},
)
MESH_M4 = MeshModel(
    axis_names=("model",), shape=(4,), coords={d: (d,) for d in range(4)}
)

ICI_PAIRS = "{0,1},{1,2},{2,3},{3,0},{4,5},{5,6},{6,7},{7,4}"
DATA_PAIRS = "{0,1},{1,2},{2,3},{3,4},{4,5},{5,6},{6,7},{7,0}"
ICI_GROUPS = "{{0,1,2,3},{4,5,6,7}}"
DCN_GROUPS = "{{0,4},{1,5},{2,6},{3,7}}"
# permute pairs that stay WITHIN 'dcn' on MESH_2x4 (cross-slice hops)
DCN_PAIRS_2x4 = "{0,4},{4,0},{1,5},{5,1},{2,6},{6,2},{3,7},{7,3}"
M4_PAIRS = "{0,1},{1,2},{2,3},{3,0}"


def module(body_lines, header_extra="", params=("p: f32[64]",)):
    """Wrap instruction lines into a minimal parseable module."""
    plist = ", ".join(params)
    body = ["  %{} = {} parameter({})".format(
        p.split(":")[0], p.split(": ")[1] + "{0}", i
    ) for i, p in enumerate(params)]
    body += ["  " + ln.strip() for ln in body_lines]
    body.append("  ROOT %ret = f32[] constant(0)")
    return (
        "HloModule m" + header_extra + "\n\n"
        + "ENTRY %main (" + plist + ") -> f32[] {\n"
        + "\n".join(body) + "\n}\n"
    )


def perm(name, operand, pairs, shape="f32[16]", tag=None):
    meta = (
        ', metadata={op_name="jit(f)/%s/ppermute"}' % tag if tag else ""
    )
    return (
        "%{n} = {s}{{0}} collective-permute({s}{{0}} %{o}), "
        "source_target_pairs={{{p}}}{m}".format(
            n=name, s=shape, o=operand, p=pairs, m=meta
        )
    )


def allreduce(name, operand, groups, shape="f32[16]", tag=None):
    meta = (
        ', metadata={op_name="jit(f)/%s/psum"}' % tag if tag else ""
    )
    return (
        "%{n} = {s}{{0}} all-reduce({s}{{0}} %{o}), "
        "replica_groups={g}, use_global_device_ids=true{m}".format(
            n=name, s=shape, o=operand, g=groups, m=meta
        )
    )


def check(rule_id, target, hlo, mesh):
    rule = REGISTRY[rule_id]
    assert rule.applies(target), (
        f"{rule_id} should apply to this target"
    )
    return rule.check(LintContext.build(target, hlo, mesh))


def hybrid_reducer_target(**kw):
    base = dict(
        name="t", engine="ddp", grad_reduction="bucketed",
        data_axes=("dcn", "ici"), ici_axis="ici", dcn_axis="dcn",
        ici_size=4, dcn_size=2,
        bucket_plans=(((64, "f32"),),),  # one 64-elem padded bucket
    )
    base.update(kw)
    return LintTarget(**base)


def plain_reducer_target(**kw):
    base = dict(
        name="t", engine="ddp", grad_reduction="bucketed",
        data_axes=("data",), ici_axis="data", ici_size=8,
        bucket_plans=(((64, "f32"),),),
    )
    base.update(kw)
    return LintTarget(**base)


# ------------------------------------------------ dcn-grad-all-reduce


@pytest.mark.hlo_rule("dcn-grad-all-reduce", "positive")
def test_dcn_grad_all_reduce_fires_on_full_bucket_over_dcn():
    # 64-elem f32 over 'dcn' = 256 B > the 16-elem (64 B) 1/ici shard.
    hlo = module([allreduce("ar", "p", DCN_GROUPS, shape="f32[64]")])
    found = check(
        "dcn-grad-all-reduce", hybrid_reducer_target(), hlo, MESH_2x4
    )
    assert found and "crosses 'dcn'" in found[0].message


@pytest.mark.hlo_rule("dcn-grad-all-reduce", "negative")
def test_dcn_grad_all_reduce_allows_shard_sized_hop():
    hlo = module([allreduce("ar", "p", DCN_GROUPS, shape="f32[16]")])
    assert check(
        "dcn-grad-all-reduce", hybrid_reducer_target(), hlo, MESH_2x4
    ) == []


# ------------------------------------------------ bucket-ring-permutes


@pytest.mark.hlo_rule("bucket-ring-permutes", "positive")
def test_bucket_ring_permutes_fires_on_missing_hop():
    # expected 2*(4-1)*1 = 6 ici permutes; provide 5.
    lines = [perm(f"cp{i}", "p", ICI_PAIRS) for i in range(5)]
    found = check(
        "bucket-ring-permutes", hybrid_reducer_target(), module(lines),
        MESH_2x4,
    )
    assert found and "expected 2*(4-1)*1 = 6" in found[0].message


@pytest.mark.hlo_rule("bucket-ring-permutes", "negative")
def test_bucket_ring_permutes_exact_count_is_clean():
    lines = [perm(f"cp{i}", "p", ICI_PAIRS) for i in range(6)]
    # a 'dcn'-crossing permute must NOT count toward the ici rings
    lines.append(perm("cpx", "p", "{0,4},{4,0}"))
    assert check(
        "bucket-ring-permutes", hybrid_reducer_target(), module(lines),
        MESH_2x4,
    ) == []


# ---------------------------------------------- dcn-bucket-psum-shard


@pytest.mark.hlo_rule("dcn-bucket-psum-shard", "positive")
def test_dcn_bucket_psum_shard_fires_on_wrong_shape():
    hlo = module([allreduce("ar", "p", DCN_GROUPS, shape="f32[64]")])
    found = check(
        "dcn-bucket-psum-shard", hybrid_reducer_target(), hlo, MESH_2x4
    )
    assert found and "1/ici shards" in found[0].message


@pytest.mark.hlo_rule("dcn-bucket-psum-shard", "negative")
def test_dcn_bucket_psum_shard_pinned_shape_is_clean():
    hlo = module([allreduce("ar", "p", DCN_GROUPS, shape="f32[16]")])
    assert check(
        "dcn-bucket-psum-shard", hybrid_reducer_target(), hlo, MESH_2x4
    ) == []


# -------------------------------------------------- no-grad-all-reduce


@pytest.mark.hlo_rule("no-grad-all-reduce", "positive")
def test_no_grad_all_reduce_fires_on_fused_grad_reduction():
    hlo = module(
        [allreduce("ar", "p", "{{0,1,2,3,4,5,6,7}}", shape="f32[100]")]
    )
    found = check(
        "no-grad-all-reduce",
        plain_reducer_target(state_leaf_shapes=((16,),)), hlo, MESH8,
    )
    assert found and "grad-sized" in found[0].message


@pytest.mark.hlo_rule("no-grad-all-reduce", "negative")
def test_no_grad_all_reduce_allows_bn_stats_and_scalars():
    hlo = module([
        allreduce("bn", "p", "{{0,1,2,3,4,5,6,7}}", shape="f32[16]"),
        allreduce("m", "p", "{{0,1,2,3,4,5,6,7}}", shape="f32[]"),
    ])
    assert check(
        "no-grad-all-reduce",
        plain_reducer_target(state_leaf_shapes=((16,),)), hlo, MESH8,
    ) == []


def test_no_grad_all_reduce_fused_tuple_cannot_smuggle_over_dcn():
    """A combiner-fused tuple all-reduce whose FIRST buffer matches a
    pinned 1/ici bucket shard must still fire when any OTHER buffer is
    grad-sized — every buffer is checked against the allowlist."""
    hlo = module(
        [
            "%art = (f32[16]{0}, f32[100]{0}) all-reduce(f32[16]{0} %p, "
            "f32[100]{0} %p2), replica_groups=" + DCN_GROUPS
            + ", use_global_device_ids=true",
        ],
        params=("p: f32[16]", "p2: f32[100]"),
    )
    found = check(
        "no-grad-all-reduce", hybrid_reducer_target(), hlo, MESH_2x4
    )
    assert found and "grad-sized" in found[0].message


# -------------------------------------------------- cm-ring-permutes


def cm_op_target(**kw):
    base = dict(
        name="t", engine="cm_ag", data_axes=(), ici_axis=None,
        ici_size=1, cm_axis="model", cm_size=4, expected_permutes=3,
    )
    base.update(kw)
    return LintTarget(**base)


@pytest.mark.hlo_rule("cm-ring-permutes", "positive")
def test_cm_ring_permutes_fires_on_short_chain():
    lines = [perm(f"cp{i}", "p", M4_PAIRS) for i in range(2)]
    found = check("cm-ring-permutes", cm_op_target(), module(lines),
                  MESH_M4)
    assert found and "expected exactly 3" in found[0].message


@pytest.mark.hlo_rule("cm-ring-permutes", "negative")
def test_cm_ring_permutes_s_minus_1_is_clean():
    lines = [perm(f"cp{i}", "p", M4_PAIRS) for i in range(3)]
    assert check(
        "cm-ring-permutes", cm_op_target(), module(lines), MESH_M4
    ) == []


# ------------------------------------------- cm-monolithic-collective


@pytest.mark.hlo_rule("cm-monolithic-collective", "positive")
def test_cm_monolithic_fires_on_surviving_all_gather():
    hlo = module([
        perm("cp0", "p", M4_PAIRS),
        "%ag = f32[64]{0} all-gather(f32[64]{0} %p), "
        "replica_groups={{0,1,2,3}}, dimensions={0}, "
        "use_global_device_ids=true",
    ])
    found = check(
        "cm-monolithic-collective", cm_op_target(), hlo, MESH_M4
    )
    assert found and "monolithic all-gather" in found[0].message


@pytest.mark.hlo_rule("cm-monolithic-collective", "negative")
def test_cm_monolithic_permute_only_kernel_is_clean():
    lines = [perm(f"cp{i}", "p", M4_PAIRS) for i in range(3)]
    assert check(
        "cm-monolithic-collective", cm_op_target(), module(lines),
        MESH_M4,
    ) == []


# ------------------------------------------------- serve-decode-ring


def serve_target(**kw):
    base = dict(
        name="t", engine="serve", collective_matmul=True,
        data_axes=(), ici_axis=None, ici_size=1,
        cm_axis="model", cm_size=4, serve_decode_permutes=2,
    )
    base.update(kw)
    return LintTarget(**base)


@pytest.mark.hlo_rule("serve-decode-ring", "positive")
def test_serve_decode_ring_fires_on_short_chain_and_gather():
    # One tagged permute where two are pinned, plus a surviving
    # monolithic all-gather over the TP axis: both findings fire.
    hlo = module([
        perm("cp0", "p", M4_PAIRS, tag="serve_ring"),
        "%ag = f32[64]{0} all-gather(f32[64]{0} %p), "
        "replica_groups={{0,1,2,3}}, dimensions={0}, "
        "use_global_device_ids=true",
    ])
    found = check("serve-decode-ring", serve_target(), hlo, MESH_M4)
    msgs = "; ".join(f.message for f in found)
    assert "expected exactly 2" in msgs
    assert "monolithic all-gather" in msgs


@pytest.mark.hlo_rule("serve-decode-ring", "negative")
def test_serve_decode_ring_tagged_chain_is_clean():
    # The pinned tagged count, plus an UNTAGGED permute (GSPMD's own
    # resharding traffic) that must not be counted against the pin.
    hlo = module([
        perm("cp0", "p", M4_PAIRS, tag="serve_ring"),
        perm("cp1", "cp0", M4_PAIRS, tag="serve_ring"),
        perm("cp2", "cp1", M4_PAIRS),
    ])
    assert check(
        "serve-decode-ring", serve_target(), hlo, MESH_M4
    ) == []


def test_serve_decode_ring_missing_expectation_is_a_finding():
    """An opted-in serving combo whose builder forgot the permute
    expectation must surface, not silently pass."""
    hlo = module([perm("cp0", "p", M4_PAIRS, tag="serve_ring")])
    found = check(
        "serve-decode-ring",
        serve_target(serve_decode_permutes=None), hlo, MESH_M4,
    )
    assert found and "was not checked" in found[0].message


# --------------------------------------------------- spec-verify-step


def spec_target(**kw):
    base = dict(
        name="t", engine="serve", collective_matmul=True,
        data_axes=(), ici_axis=None, ici_size=1,
        cm_axis="model", cm_size=4, speculative_k=2,
        spec_verify_permutes=2,
    )
    base.update(kw)
    return LintTarget(**base)


@pytest.mark.hlo_rule("spec-verify-step", "positive")
def test_spec_verify_step_fires_on_k_scaled_rings_and_gather():
    # A verify step whose ring count scaled with the chunk (3 tagged
    # permutes where ONE decode step's 2 are pinned) plus a surviving
    # monolithic all-gather over the TP axis: both findings fire.
    hlo = module([
        perm("cp0", "p", M4_PAIRS, tag="serve_ring"),
        perm("cp1", "cp0", M4_PAIRS, tag="serve_ring"),
        perm("cp2", "cp1", M4_PAIRS, tag="serve_ring"),
        "%ag = f32[64]{0} all-gather(f32[64]{0} %p), "
        "replica_groups={{0,1,2,3}}, dimensions={0}, "
        "use_global_device_ids=true",
    ])
    found = check("spec-verify-step", spec_target(), hlo, MESH_M4)
    msgs = "; ".join(f.message for f in found)
    assert "expected exactly 2" in msgs
    assert "independent of k=2" in msgs
    assert "monolithic all-gather" in msgs


@pytest.mark.hlo_rule("spec-verify-step", "negative")
def test_spec_verify_step_decode_inventory_is_clean():
    # Exactly one decode step's tagged rings; an UNTAGGED permute
    # (GSPMD's own resharding traffic) must not count against the pin.
    hlo = module([
        perm("cp0", "p", M4_PAIRS, tag="serve_ring"),
        perm("cp1", "cp0", M4_PAIRS, tag="serve_ring"),
        perm("cp2", "cp1", M4_PAIRS),
    ])
    assert check(
        "spec-verify-step", spec_target(), hlo, MESH_M4
    ) == []


def test_spec_verify_step_missing_expectation_is_a_finding():
    """A speculative combo whose builder forgot the verify-ring
    expectation must surface, not silently pass."""
    hlo = module([perm("cp0", "p", M4_PAIRS, tag="serve_ring")])
    found = check(
        "spec-verify-step",
        spec_target(spec_verify_permutes=None), hlo, MESH_M4,
    )
    assert found and "was not checked" in found[0].message


def test_spec_verify_step_and_decode_ring_never_double_fire():
    """A speculative target is judged by spec-verify-step only: the
    decode-ring pin defers (its expectation describes the decode
    step's HLO, and a speculative combo lowers the verify step)."""
    assert REGISTRY["spec-verify-step"].applies(spec_target())
    assert not REGISTRY["serve-decode-ring"].applies(spec_target())
    assert REGISTRY["serve-decode-ring"].applies(serve_target())
    assert not REGISTRY["spec-verify-step"].applies(serve_target())


# --------------------------------------------------- fsdp-at-rest-sharded


def fsdp_target(**kw):
    base = dict(
        name="t", engine="fsdp", data_axes=("data",), ici_axis="data",
        ici_size=8, fsdp_full_leaf_shapes=((128, 128),),
    )
    base.update(kw)
    return LintTarget(**base)


@pytest.mark.hlo_rule("fsdp-at-rest-sharded", "positive")
def test_fsdp_at_rest_fires_on_full_leaf_at_rest():
    hlo = module([], params=("p: f32[128,128]",))
    found = check("fsdp-at-rest-sharded", fsdp_target(), hlo, MESH8)
    assert found and "materialized at rest" in found[0].message


@pytest.mark.hlo_rule("fsdp-at-rest-sharded", "negative")
def test_fsdp_at_rest_sharded_entry_is_clean():
    hlo = module([], params=("p: f32[16,128]",))
    assert check("fsdp-at-rest-sharded", fsdp_target(), hlo, MESH8) == []


def test_fsdp_at_rest_vacuous_policy_is_a_finding():
    """A model/mesh where the policy shards nothing must surface, not
    silently pass."""
    hlo = module([], params=("p: f32[16,128]",))
    found = check(
        "fsdp-at-rest-sharded", fsdp_target(fsdp_full_leaf_shapes=()),
        hlo, MESH8,
    )
    assert found and "vacuous" in found[0].message


# ---------------------------------------------- overlap-first-bucket-free


def overlap_target(**kw):
    base = dict(
        name="t", engine="ddp", grad_reduction="overlapped",
        data_axes=("data",), ici_axis="data", ici_size=8,
        overlap_segments=2, bucket_plans=(((64, "f32"),), ((64, "f32"),)),
    )
    base.update(kw)
    return LintTarget(**base)


def overlap_module(first_operand):
    """bwd_stage1 -> grad_reduce_stage1 permute (first-fired, operand
    configurable) and bwd_stage0 -> grad_reduce_stage0 permute (the
    positive control)."""
    return module([
        '%b1 = f32[16]{0} negate(f32[16]{0} %p), '
        'metadata={op_name="jit(f)/bwd_stage1/neg"}',
        perm("g1", first_operand, DATA_PAIRS, tag="grad_reduce_stage1"),
        '%b0 = f32[16]{0} negate(f32[16]{0} %b1), '
        'metadata={op_name="jit(f)/bwd_stage0/neg"}',
        perm("g0", "b0", DATA_PAIRS, tag="grad_reduce_stage0"),
    ])


@pytest.mark.hlo_rule("overlap-first-bucket-free", "positive")
def test_overlap_first_bucket_fires_on_serialized_firing():
    # the first-fired bucket's permute consumes stage-0 backward output
    found = check(
        "overlap-first-bucket-free", overlap_target(),
        overlap_module("b0"), MESH8,
    )
    assert found and "serialized" in found[0].message


@pytest.mark.hlo_rule("overlap-first-bucket-free", "negative")
def test_overlap_first_bucket_independent_is_clean():
    assert check(
        "overlap-first-bucket-free", overlap_target(),
        overlap_module("b1"), MESH8,
    ) == []


def test_overlap_missing_tags_is_a_finding():
    """Renamed scopes must fail loudly, not let the pin rot."""
    hlo = module([perm("cp", "p", DATA_PAIRS)])
    found = check(
        "overlap-first-bucket-free", overlap_target(), hlo, MESH8
    )
    assert found and any("tags moved" in f.message for f in found)


# ------------------------------------------------- prefetch-gather-free


def fsdp_overlap_target(**kw):
    base = dict(
        name="t", engine="fsdp", grad_reduction="overlapped",
        data_axes=("data",), ici_axis="data", ici_size=8,
        overlap_segments=2, bucket_plans=(((64, "f32"),), ((64, "f32"),)),
        fsdp_full_leaf_shapes=((128, 128),),
    )
    base.update(kw)
    return LintTarget(**base)


def prefetch_module(gather_operand):
    return module([
        perm("r1", "p", DATA_PAIRS, tag="grad_reduce_stage1"),
        perm("r0", "p", DATA_PAIRS, tag="grad_reduce_stage0"),
        "%pg = f32[128]{0} all-gather(f32[16]{0} %" + gather_operand
        + "), replica_groups={{0,1,2,3,4,5,6,7}}, dimensions={0}, "
        'use_global_device_ids=true, metadata={op_name='
        '"jit(f)/prefetch_gather_stage0/all_gather"}',
    ], params=("p: f32[16]",))


@pytest.mark.hlo_rule("prefetch-gather-free", "positive")
def test_prefetch_gather_fires_when_fed_by_reduction():
    found = check(
        "prefetch-gather-free", fsdp_overlap_target(),
        prefetch_module("r1"), MESH8,
    )
    assert found and "overlap serialized" in found[0].message


@pytest.mark.hlo_rule("prefetch-gather-free", "negative")
def test_prefetch_gather_from_shards_is_clean():
    assert check(
        "prefetch-gather-free", fsdp_overlap_target(),
        prefetch_module("p"), MESH8,
    ) == []


# --------------------------------------------------- bf16-ring-upcast


def bf16_target(**kw):
    base = dict(
        name="t", engine="tp", collective_matmul=True, bf16=True,
        cm_axis="model", cm_size=4, cm_min_ring_permutes=0,
        data_axes=("data",), ici_axis="data", ici_size=2,
        ring_dtypes=(
            (("model",), "bf16", "jvp(ag_matmul)"),
            (("data",), "f32", "jvp(bucket_ring)"),
        ),
    )
    base.update(kw)
    return LintTarget(**base)


@pytest.mark.hlo_rule("bf16-ring-upcast", "positive")
def test_bf16_ring_upcast_fires_on_f32_cm_ring():
    found = check(
        "bf16-ring-upcast",
        bf16_target(ring_dtypes=((("model",), "f32", "jvp(ag_matmul)"),)),
        module([]), MESH8,
    )
    assert found and "silent upcast" in found[0].message


@pytest.mark.hlo_rule("bf16-ring-upcast", "negative")
def test_bf16_ring_upcast_bf16_rings_clean_f32_buckets_allowed():
    # grad-bucket rings over the data axis legitimately stay f32
    # (f32 master params); only the cm axis is pinned.
    assert check(
        "bf16-ring-upcast", bf16_target(), module([]), MESH8
    ) == []


def test_bf16_ring_upcast_exempts_the_kv_ring_scope():
    """The deliberately-f32 KV wire (accumulate-in-f32 contract,
    ops/ring_attention.py) is a named-scope exemption, not a finding —
    forward AND its transposed backward permutes."""
    assert check(
        "bf16-ring-upcast",
        bf16_target(
            cm_axis="seq",
            ring_dtypes=(
                (("seq",), "f32", "jvp(kv_ring)"),
                (("seq",), "f32", "transpose(jvp(kv_ring))"),
                (("seq",), "bf16", "jvp(ag_matmul)"),
            ),
        ),
        module([]), MESH8,
    ) == []


def test_bf16_ring_upcast_exemption_is_whole_word_not_substring():
    """A scope merely CONTAINING an exempt name (qkv_ring,
    kv_ring_cache) must not inherit the exemption."""
    found = check(
        "bf16-ring-upcast",
        bf16_target(ring_dtypes=(
            (("model",), "f32", "jvp(qkv_ring)"),
            (("model",), "f32", "jvp(kv_ring_cache)"),
        )),
        module([]), MESH8,
    )
    assert len(found) == 2


def test_bf16_ring_upcast_requires_jaxpr_data():
    found = check(
        "bf16-ring-upcast", bf16_target(ring_dtypes=()), module([]),
        MESH8,
    )
    assert found and "not checked" in found[0].message


# ------------------------------------------------ moe-hierarchical-a2a


def alltoall(name, operand, groups, shape="f32[16]"):
    return (
        "%{n} = {s}{{0}} all-to-all({s}{{0}} %{o}), "
        "replica_groups={g}, use_global_device_ids=true".format(
            n=name, s=shape, o=operand, g=groups
        )
    )


def moe_perm(name, operand, pairs, tag="moe_ring"):
    return perm(name, operand, pairs, tag=tag)


def moe_target(**kw):
    base = dict(
        name="t", engine="ep", moe_dispatch="hierarchical",
        data_axes=("dcn", "ici"), ici_axis="ici", dcn_axis="dcn",
        ici_size=4, dcn_size=2,
        # 1 MoE layer on a 2x4 fabric: 2*(2*(4-1) + 2*(2-1)) = 16.
        moe_ring_permutes=16,
    )
    base.update(kw)
    return LintTarget(**base)


@pytest.mark.hlo_rule("moe-hierarchical-a2a", "positive")
def test_moe_hierarchical_fires_on_flat_a2a_and_short_chain():
    # A surviving all-to-all over 'dcn' plus only one tagged hop: both
    # halves of the contract violated.
    lines = [
        alltoall("a2a", "p", DCN_GROUPS),
        moe_perm("cp0", "p", ICI_PAIRS),
    ]
    found = check("moe-hierarchical-a2a", moe_target(), module(lines),
                  MESH_2x4)
    msgs = " | ".join(f.message for f in found)
    assert "expected exactly 16" in msgs
    assert "all-to-all touching the data fabric" in msgs


@pytest.mark.hlo_rule("moe-hierarchical-a2a", "negative")
def test_moe_hierarchical_tagged_chain_is_clean():
    # 12 ici hops + 4 dcn hops (2 exchanges' worth fwd+bwd on 2x4,
    # transpose-spelled scopes included), no all-to-all anywhere.
    lines = (
        [moe_perm(f"ci{i}", "p", ICI_PAIRS) for i in range(9)]
        + [perm(f"ct{i}", "p", ICI_PAIRS, tag="transpose(moe_ring)")
           for i in range(3)]
        + [moe_perm(f"cd{i}", "p", DCN_PAIRS_2x4) for i in range(4)]
    )
    assert check(
        "moe-hierarchical-a2a", moe_target(), module(lines), MESH_2x4
    ) == []


def test_moe_hierarchical_missing_expectation_is_a_finding():
    found = check(
        "moe-hierarchical-a2a", moe_target(moe_ring_permutes=None),
        module([]), MESH_2x4,
    )
    assert found and "not checked" in found[0].message


def test_moe_hierarchical_untagged_permutes_do_not_count():
    # The right hop count but none scoped moe_ring: the chain pin must
    # fire (GSPMD resharding permutes are not the exchange).
    lines = [perm(f"cp{i}", "p", ICI_PAIRS) for i in range(16)]
    found = check(
        "moe-hierarchical-a2a", moe_target(), module(lines), MESH_2x4
    )
    assert found and "0 moe_ring-scoped" in found[0].message


# ---------------------------------------------- dcn-compressed-payload


def compressed_target(**kw):
    """DDP bucketed+int8 on the 2x4 hybrid: one 64-elem padded bucket
    -> 1/ici shard 16 elems -> 2(K-1)=2 dcn hops of 8 int8 elems each,
    one f32 scalar sidecar per hop."""
    base = dict(
        name="t", engine="ddp", grad_reduction="bucketed",
        data_axes=("dcn", "ici"), ici_axis="ici", dcn_axis="dcn",
        ici_size=4, dcn_size=2,
        bucket_plans=(((64, "f32"),),),
        dcn_compression="int8",
        dcn_wire_chunks=((8, "s8"), (8, "s8")),
        dcn_ring_records=(
            (("dcn",), "s8", "jit(f)/dcn_wire", 8),
            (("dcn",), "f32", "jit(f)/dcn_scale", 1),
            (("dcn",), "s8", "jit(f)/dcn_wire", 8),
            (("dcn",), "f32", "jit(f)/dcn_scale", 1),
            # intra-slice ring traffic stays f32 and must be ignored
            (("ici",), "f32", "jit(f)/bucket_ring", 16),
        ),
    )
    base.update(kw)
    return LintTarget(**base)


@pytest.mark.hlo_rule("dcn-compressed-payload", "positive")
def test_dcn_compressed_fires_on_f32_hop_and_grad_all_reduce():
    # An UNCODED f32 ppermute crossing 'dcn' in the trace, a payload
    # hop in the wrong dtype, AND a grad-sized f32 all-reduce crossing
    # 'dcn' in the compiled HLO: every half of the contract fires.
    hlo = module([allreduce("ar", "p", DCN_GROUPS, shape="f32[100]")],
                 params=("p: f32[100]",))
    found = check(
        "dcn-compressed-payload",
        compressed_target(dcn_ring_records=(
            (("dcn",), "f32", "jit(f)/bwd", 64),
            (("dcn",), "f32", "jit(f)/dcn_wire", 8),
            (("dcn",), "f32", "jit(f)/dcn_wire", 8),
        )),
        hlo, MESH_2x4,
    )
    msgs = " | ".join(f.message for f in found)
    assert "uncoded ppermute crosses 'dcn'" in msgs
    assert "expected compressed chunks" in msgs
    assert "all-reduce crosses 'dcn'" in msgs


@pytest.mark.hlo_rule("dcn-compressed-payload", "negative")
def test_dcn_compressed_pinned_wire_is_clean():
    # The exact chunk multiset in int8 + one sidecar per hop + a
    # state-shaped BN psum (allowlisted) + scalar metrics: clean.
    hlo = module([
        allreduce("bn", "p", "{{0,1,2,3,4,5,6,7}}", shape="f32[16]"),
        allreduce("m", "p", "{{0,1,2,3,4,5,6,7}}", shape="f32[]"),
    ])
    assert check(
        "dcn-compressed-payload",
        compressed_target(state_leaf_shapes=((16,),)), hlo, MESH_2x4,
    ) == []


def test_dcn_compressed_missing_records_is_a_finding():
    """A compressed combo whose builder collected no trace records must
    surface, not silently pass."""
    found = check(
        "dcn-compressed-payload",
        compressed_target(dcn_ring_records=()), module([]), MESH_2x4,
    )
    assert found and "not checked" in found[0].message


def test_dcn_compressed_missing_expectation_is_a_finding():
    found = check(
        "dcn-compressed-payload",
        compressed_target(dcn_wire_chunks=(), dcn_wire_hops=None),
        module([]), MESH_2x4,
    )
    assert found and any(
        "payload pin was not checked" in f.message for f in found
    )


def test_dcn_compressed_sidecar_accounting():
    """int8 demands exactly one f32 scalar sidecar per payload hop; a
    bf16 combo must carry none."""
    found = check(
        "dcn-compressed-payload",
        compressed_target(dcn_ring_records=(
            (("dcn",), "s8", "jit(f)/dcn_wire", 8),
            (("dcn",), "s8", "jit(f)/dcn_wire", 8),
            (("dcn",), "f32", "jit(f)/dcn_scale", 1),
        )),
        module([]), MESH_2x4,
    )
    assert found and "1 dcn_scale sidecars for 2" in found[0].message
    found = check(
        "dcn-compressed-payload",
        compressed_target(
            dcn_compression="bf16",
            dcn_wire_chunks=((8, "bf16"), (8, "bf16")),
            dcn_ring_records=(
                (("dcn",), "bf16", "jit(f)/dcn_wire", 8),
                (("dcn",), "bf16", "jit(f)/dcn_wire", 8),
                (("dcn",), "f32", "jit(f)/dcn_scale", 1),
            ),
        ),
        module([]), MESH_2x4,
    )
    assert found and "cast codec has no scale" in found[0].message


def test_dcn_compressed_hop_count_pin_for_moe():
    """The EP form of the pin: hop COUNT + wire dtype (chunk shapes are
    model-dependent), plus the dispatch-sized all-to-all ban."""
    ep = compressed_target(
        engine="ep", grad_reduction="monolithic",
        moe_dispatch="hierarchical", bucket_plans=(),
        dcn_wire_chunks=(), dcn_wire_hops=4,
        dcn_ring_records=tuple(
            (("dcn",), "s8", "jit(f)/moe_ring/dcn_wire", 48)
            for _ in range(4)
        ) + tuple(
            (("dcn",), "f32", "jit(f)/dcn_scale", 1) for _ in range(4)
        ),
    )
    assert check("dcn-compressed-payload", ep, module([]), MESH_2x4) == []
    # short chain + a surviving flat all-to-all over 'dcn'
    import dataclasses

    bad = check(
        "dcn-compressed-payload",
        dataclasses.replace(ep, dcn_ring_records=(
            (("dcn",), "s8", "jit(f)/moe_ring/dcn_wire", 48),
            (("dcn",), "f32", "jit(f)/dcn_scale", 1),
        )),
        module([alltoall("a2a", "p", DCN_GROUPS)]), MESH_2x4,
    )
    msgs = " | ".join(f.message for f in bad)
    assert "expected exactly 4" in msgs
    assert "all-to-all crosses 'dcn'" in msgs


def test_dcn_compressed_fsdp_gather_pin():
    """The FSDP half of the pin (ISSUE 16 satellite): the weight
    gather's dcn leg must appear as fsdp_gather-scoped coded ring hops
    matching the builder's multiset, and a fused all-gather crossing
    'dcn' is contraband on the compressed step (a leaf that fell off
    `_coded_dcn_gather`)."""
    fsdp = compressed_target(
        engine="fsdp", grad_reduction="monolithic",
        dcn_gather_chunks=((32, "s8"), (32, "s8")),
        dcn_ring_records=compressed_target().dcn_ring_records + (
            (("dcn",), "s8", "jit(f)/fsdp_gather/dcn_wire", 32),
            (("dcn",), "f32", "jit(f)/fsdp_gather/dcn_scale", 1),
            (("dcn",), "s8", "jit(f)/fsdp_gather/dcn_wire", 32),
            (("dcn",), "f32", "jit(f)/fsdp_gather/dcn_scale", 1),
        ),
    )
    assert check(
        "dcn-compressed-payload", fsdp, module([]), MESH_2x4
    ) == []
    # Gather hops missing from the trace + a surviving fused gather
    # over 'dcn' in the compiled HLO: both halves fire.
    import dataclasses

    bad = check(
        "dcn-compressed-payload",
        dataclasses.replace(
            fsdp, dcn_ring_records=compressed_target().dcn_ring_records,
        ),
        module([
            "%ag = f32[128]{0} all-gather(f32[64]{0} %p), "
            "replica_groups=" + DCN_GROUPS + ", dimensions={0}, "
            "use_global_device_ids=true",
        ]),
        MESH_2x4,
    )
    msgs = " | ".join(f.message for f in bad)
    assert "expected compressed weight-gather chunks" in msgs
    assert "monolithic all-gather crosses 'dcn'" in msgs


# ------------------------------------------------ decode-quantized-matmul


_QUANT_DOTS = tuple(("s8", "s8", (16, 48)) for _ in range(8))


def quant_serve_target(**kw):
    """Quantized serve decode on a single-host trace: 8 int8 projection
    dots (4 per layer x 2 layers), the f32 head, and one batched
    attention dot (rank-4 rhs — never counted as a projection)."""
    base = dict(
        name="t", engine="serve",
        data_axes=(), ici_axis=None, ici_size=1,
        compute_dtype="int8", quant_dot_count=8,
        head_weight_shape=(16, 61),
        decode_dot_records=_QUANT_DOTS + (
            ("f32", "f32", (16, 61)),
            ("f32", "f32", (2, 4, 16, 4)),
        ),
    )
    base.update(kw)
    return LintTarget(**base)


@pytest.mark.hlo_rule("decode-quantized-matmul", "positive")
def test_decode_quantized_fires_on_f32_projection_and_quantized_head():
    # 6 of 8 projections quantized, one fell back to f32, and the head
    # got quantized: the count pin, the zero-f32-projection pin and the
    # head-stays-f32 pin all fire.
    found = check(
        "decode-quantized-matmul",
        quant_serve_target(decode_dot_records=_QUANT_DOTS[:6] + (
            ("f32", "f32", (16, 48)),
            ("s8", "s8", (16, 61)),
        )),
        module([]), MESH_M4,
    )
    msgs = " | ".join(f.message for f in found)
    assert "expected exactly 8" in msgs
    assert "fell back to f32 arithmetic" in msgs
    assert "head stays f32" in msgs


@pytest.mark.hlo_rule("decode-quantized-matmul", "negative")
def test_decode_quantized_pinned_trace_is_clean():
    assert check(
        "decode-quantized-matmul", quant_serve_target(), module([]),
        MESH_M4,
    ) == []


def test_decode_quantized_missing_records_is_a_finding():
    """A quantized combo whose builder collected no dot records must
    surface, not silently pass."""
    found = check(
        "decode-quantized-matmul",
        quant_serve_target(decode_dot_records=(), quant_dot_count=None),
        module([]), MESH_M4,
    )
    assert found and "was not checked" in found[0].message


def test_decode_quantized_missing_head_record_is_a_finding():
    found = check(
        "decode-quantized-matmul",
        quant_serve_target(decode_dot_records=_QUANT_DOTS),
        module([]), MESH_M4,
    )
    assert found and any(
        "head-matmul-stays-f32 pin was not checked" in f.message
        for f in found
    )


# ------------------------------------------------- donated-step-aliased


@pytest.mark.hlo_rule("donated-step-aliased", "positive")
def test_donated_step_fires_without_alias_table():
    t = LintTarget(name="t", engine="ddp", donate=True, n_param_leaves=3)
    found = check("donated-step-aliased", t, module([]), MESH8)
    assert found and "double-buffered" in found[0].message


@pytest.mark.hlo_rule("donated-step-aliased", "negative")
def test_donated_step_with_alias_table_is_clean():
    t = LintTarget(name="t", engine="ddp", donate=True, n_param_leaves=3)
    hlo = module(
        [],
        header_extra=(
            ", input_output_alias={ {0}: (0, {}, may-alias), "
            "{1}: (1, {}, may-alias), {2}: (2, {}, may-alias) }"
        ),
    )
    assert check("donated-step-aliased", t, hlo, MESH8) == []


# --------------------------------------------- collective-fabric-known


@pytest.mark.hlo_rule("collective-fabric-known", "positive")
def test_fabric_known_fires_on_unresolvable_ids():
    hlo = module([allreduce("ar", "p", "{{0,9}}", shape="f32[16]")])
    t = LintTarget(name="t", engine="ddp")
    found = check("collective-fabric-known", t, hlo, MESH8)
    assert found and "does not resolve" in found[0].message


@pytest.mark.hlo_rule("collective-fabric-known", "negative")
def test_fabric_known_resolvable_ids_clean():
    hlo = module([allreduce("ar", "p", ICI_GROUPS, shape="f32[16]")])
    t = LintTarget(name="t", engine="ddp")
    assert check("collective-fabric-known", t, hlo, MESH8) == []


# ------------------------------------------------------ registry meta


def test_registry_shape():
    """>= 8 severity-tagged rules, each with contract + source + a
    callable applicability predicate (the acceptance-criteria floor)."""
    assert len(REGISTRY) >= 8
    for r in REGISTRY.values():
        assert r.severity in ("error", "warn")
        assert r.contract and r.source
        assert callable(r.applies) and callable(r.check)


def test_exemptions_report_but_do_not_count():
    from distributed_model_parallel_tpu.analysis.rules import run_rules

    t = LintTarget(
        name="t", engine="ddp", donate=True, n_param_leaves=3,
        exemptions={
            "donated-step-aliased": "intentional: lowering-only probe"
        },
    )
    ctx = LintContext.build(t, module([]), MESH8)
    found = [f for f in run_rules(ctx) if f.rule == "donated-step-aliased"]
    assert found and found[0].exempted
    assert "lowering-only" in found[0].exemption_reason


# ------------------------------------------- plan-* fabric rules


def plan_target(**kw):
    """Canned composed-plan target: a 2x2x2 PP x SP x DP plan whose
    traced collective inventory is exactly the contract — TWO
    plan_wire ppermutes on ('stage',) (the table-driven tick
    program's static count, for every schedule: forward wire +
    autodiff transpose under gpipe, up + down wires scheduled), one
    kv_ring hop on ('seq',), one fused plan_grad psum over all three
    axes."""
    base = dict(
        name="t", engine="plan",
        data_axes=("data",), ici_axis="data", ici_size=2,
        plan_axes=(("stage", 2), ("data", 2), ("seq", 2)),
        plan_collective_records=(
            ("ppermute", ("stage",), "f32",
             "jit(f)/plan_wire/ppermute", 64),
            ("ppermute", ("stage",), "f32",
             "jit(f)/transpose(plan_wire)/ppermute", 64),
            ("ppermute", ("seq",), "f32",
             "jit(f)/kv_ring/ppermute", 64),
            ("psum", ("stage", "data", "seq"), "f32",
             "jit(f)/plan_grad/psum", 64),
        ),
    )
    base.update(kw)
    return LintTarget(**base)


@pytest.mark.hlo_rule("plan-wire-fabric", "positive")
def test_plan_wire_fires_off_stage_axis():
    # The activation wire riding 'data' instead of 'stage' — the
    # composition put pipeline traffic on the wrong fabric.
    t = plan_target(plan_collective_records=(
        ("ppermute", ("data",), "f32",
         "jit(f)/plan_wire/ppermute", 64),
        ("psum", ("stage", "data", "seq"), "f32",
         "jit(f)/plan_grad/psum", 64),
    ))
    found = check("plan-wire-fabric", t, module([]), MESH8)
    assert found and "('stage',)" in found[0].message
    # Vacuity guard: a pp>1 plan with NO wire records also fires.
    t2 = plan_target(plan_collective_records=(
        ("psum", ("stage", "data", "seq"), "f32",
         "jit(f)/plan_grad/psum", 64),
    ))
    found2 = check("plan-wire-fabric", t2, module([]), MESH8)
    assert found2 and "not checked" in found2[0].message


@pytest.mark.hlo_rule("plan-wire-fabric", "negative")
def test_plan_wire_stage_only_clean():
    assert check(
        "plan-wire-fabric", plan_target(), module([]), MESH8
    ) == []
    # The scheduled twins trace the SAME static wire count — the
    # schedule-symmetric inventory the ISSUE 20 tick tables pin.
    for sched, v in (("1f1b", 1), ("interleaved", 2)):
        assert check(
            "plan-wire-fabric",
            plan_target(plan_schedule=sched, plan_virtual=v),
            module([]), MESH8,
        ) == []


@pytest.mark.hlo_rule("plan-wire-fabric", "positive")
def test_plan_wire_count_pins_table_driven_replay():
    # An UNROLLED per-tick program would trace O(ticks) stage
    # ppermutes; the rule pins the per-schedule static count (2) so
    # a replay regression cannot land silently.
    t = plan_target(
        plan_schedule="1f1b",
        plan_collective_records=(
            ("ppermute", ("stage",), "f32",
             "jit(f)/plan_wire/ppermute", 64),
            ("ppermute", ("stage",), "f32",
             "jit(f)/plan_wire/ppermute", 64),
            ("ppermute", ("stage",), "f32",
             "jit(f)/plan_wire/ppermute", 64),
            ("psum", ("stage", "data", "seq"), "f32",
             "jit(f)/plan_grad/psum", 64),
        ),
    )
    found = check("plan-wire-fabric", t, module([]), MESH8)
    assert found and "table-driven replay" in found[0].message


@pytest.mark.hlo_rule("plan-seq-fabric", "positive")
def test_plan_seq_fires_on_ring_off_seq_axis():
    # A kv_ring hop crossing 'stage' — the ring attention rotation
    # left the ICI fabric.
    t = plan_target(plan_collective_records=(
        ("ppermute", ("stage",), "f32",
         "jit(f)/plan_wire/ppermute", 64),
        ("ppermute", ("stage",), "f32",
         "jit(f)/kv_ring/ppermute", 64),
        ("psum", ("stage", "data", "seq"), "f32",
         "jit(f)/plan_grad/psum", 64),
    ))
    found = check("plan-seq-fabric", t, module([]), MESH8)
    assert found and "('seq',)" in found[0].message


@pytest.mark.hlo_rule("plan-seq-fabric", "negative")
def test_plan_seq_rings_on_seq_clean():
    assert check(
        "plan-seq-fabric", plan_target(), module([]), MESH8
    ) == []


@pytest.mark.hlo_rule("plan-grad-fabric", "positive")
def test_plan_grad_fires_on_partial_axis_psum():
    # A per-axis cascade ('data'-only psum under plan_grad) instead
    # of the single fused three-axis rendezvous.
    t = plan_target(plan_collective_records=(
        ("ppermute", ("stage",), "f32",
         "jit(f)/plan_wire/ppermute", 64),
        ("psum", ("data",), "f32", "jit(f)/plan_grad/psum", 64),
    ))
    found = check("plan-grad-fabric", t, module([]), MESH8)
    assert found and "fused psum" in found[0].message
    # An FSDP weight gather off the 'data' axis fires too.
    t2 = plan_target(plan_collective_records=(
        ("psum", ("stage", "data", "seq"), "f32",
         "jit(f)/plan_grad/psum", 64),
        ("all_gather", ("seq",), "f32",
         "jit(f)/plan_fsdp_gather/all_gather", 64),
    ))
    found2 = check("plan-grad-fabric", t2, module([]), MESH8)
    assert found2 and "plan_fsdp_gather" in found2[0].message


# An fsdp plan's traced inventory as ISSUE 33 leaves it (pp2 x sp2 x
# fsdp2): per-block gathers and their float32 reduce-scatters over
# ('data',) under plan_fsdp_gather; under plan_grad the leaves
# gathered once reduce-scatter, shards sum over what is left, and a
# leaf fsdp left replicated sums over everything.
FSDP_PLAN_RECORDS = (
    ("ppermute", ("stage",), "f32", "jit(f)/plan_wire/ppermute", 64),
    ("ppermute", ("stage",), "f32",
     "jit(f)/transpose(plan_wire)/ppermute", 64),
    ("all_gather", ("data",), "bf16",
     "jit(f)/while/body/plan_fsdp_gather/all_gather", 64),
    ("reduce_scatter", ("data",), "f32",
     "jit(f)/transpose/while/body/plan_fsdp_gather/reduce_scatter", 64),
    ("reduce_scatter", ("data",), "f32",
     "jit(f)/plan_grad/reduce_scatter", 64),
    ("psum", ("stage", "seq"), "f32", "jit(f)/plan_grad/psum", 64),
    ("psum", ("stage", "data", "seq"), "f32",
     "jit(f)/plan_grad/psum", 8),
)


def _fsdp_records(*swap):
    """FSDP_PLAN_RECORDS with the records whose (primitive, scope
    word) match replaced: swap = ((prim, word), new record | None)."""
    out = []
    for r in FSDP_PLAN_RECORDS:
        for (prim, word), new in swap:
            if r[0] == prim and word in r[3]:
                r = new
                break
        if r is not None:
            out.append(r)
    return tuple(out)


@pytest.mark.hlo_rule("plan-grad-fabric", "positive")
@pytest.mark.parametrize("swap,says", [
    # the whole gradient all-reduced over 'data' and sliced after:
    # what ISSUE 33 removed
    ((("psum", "plan_grad"),
      ("psum", ("data",), "f32", "jit(f)/plan_grad/psum", 64)),
     "never over 'data' apart"),
    # a bfloat16 reduction is a different result, not a faster one
    ((("reduce_scatter", "plan_fsdp_gather"),
      ("reduce_scatter", ("data",), "bf16",
       "jit(f)/transpose/plan_fsdp_gather/reduce_scatter", 64)),
     "float32 over ('data',) only"),
    ((("reduce_scatter", "plan_grad"),
      ("reduce_scatter", ("data", "seq"), "f32",
       "jit(f)/plan_grad/reduce_scatter", 64)),
     "float32 over ('data',) only"),
    # the blocks gathered but differentiated whole: no transpose of
    # the per-block gather in the backward scan
    ((("reduce_scatter", "plan_fsdp_gather"), None),
     "do not leave the backward scan"),
    ((("all_gather", "plan_fsdp_gather"),
      ("all_gather", ("stage",), "bf16",
       "jit(f)/plan_fsdp_gather/all_gather", 64)),
     "rides ('data',) only"),
], ids=["data_psum", "bf16_block_reduction", "scatter_off_data",
        "no_block_reduce_scatter", "gather_off_data"])
def test_plan_grad_fsdp_contract_fires(swap, says):
    t = plan_target(
        plan_fsdp=True, plan_collective_records=_fsdp_records(swap)
    )
    found = check("plan-grad-fabric", t, module([]), MESH8)
    assert found and any(says in f.message for f in found)


@pytest.mark.hlo_rule("plan-grad-fabric", "negative")
@pytest.mark.parametrize("axes,records", [
    ((("stage", 2), ("data", 2), ("seq", 2)), FSDP_PLAN_RECORDS),
    # fsdp4 alone: nothing is left to sum beside the reduce-scatters
    ((("stage", 1), ("data", 4), ("seq", 1)), tuple(
        r for r in FSDP_PLAN_RECORDS
        if r[0] != "ppermute" and r[1] != ("stage", "seq")
    )),
], ids=["pp2xsp2xfsdp2", "fsdp4"])
def test_plan_grad_fsdp_contract_clean(axes, records):
    t = plan_target(
        plan_fsdp=True, plan_axes=axes, plan_collective_records=records
    )
    assert check("plan-grad-fabric", t, module([]), MESH8) == []
    # ...and the same inventory under a plan WITHOUT fsdp is a
    # per-axis cascade: the one-fused-psum contract still stands there
    t2 = plan_target(plan_axes=axes, plan_collective_records=records)
    assert check("plan-grad-fabric", t2, module([]), MESH8)


@pytest.mark.hlo_rule("plan-grad-fabric", "negative")
def test_plan_grad_fused_psum_and_data_gather_clean():
    t = plan_target(plan_collective_records=(
        ("ppermute", ("stage",), "f32",
         "jit(f)/plan_wire/ppermute", 64),
        ("psum", ("stage", "data", "seq"), "f32",
         "jit(f)/plan_grad/psum", 64),
        ("all_gather", ("data",), "f32",
         "jit(f)/plan_fsdp_gather/all_gather", 64),
    ))
    assert check("plan-grad-fabric", t, module([]), MESH8) == []
