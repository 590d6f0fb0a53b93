"""Composable mesh-axis plans (`parallel/plan.py`, ISSUE 19).

Correctness bar: every factorization of the SAME GPT config is an exact
rearrangement of the dense computation, not an approximation — so each
plan's per-token loss, metrics, and multi-step trajectory are pinned
against the one-device dense `gpt_lm` step at rtol 1e-5, and the
degenerate-plan map (`build_plan_engine` routing a single-axis plan to
the existing single-axis engine) is pinned as a type contract.
"""

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from distributed_model_parallel_tpu.models import layers as L
from distributed_model_parallel_tpu.models.gpt import (
    GPTConfig,
    gpt_lm,
    lm_loss,
)
from distributed_model_parallel_tpu.parallel.plan import (
    ComposedPlanEngine,
    ParallelPlan,
    build_plan_engine,
    parse_plan,
)
from distributed_model_parallel_tpu.training.optim import SGD

TINY = GPTConfig(
    vocab_size=61, dim=32, num_layers=4, num_heads=4, ffn_dim=64,
    max_position=16, dropout_rate=0.0,
)
B, T = 8, 16
LR = 0.1


def _ids(seed=0):
    rng = np.random.RandomState(seed)
    return rng.randint(1, TINY.vocab_size, size=(B, T)).astype(np.int32)


def _dense_step_fn(cfg, ids):
    """One jitted dense train step over the full batch — the ground
    truth every factorization must reproduce."""
    model = gpt_lm(cfg)
    params, state = model.init(jax.random.PRNGKey(0))
    opt = SGD()
    opt_state = opt.init(params)
    idsj = jnp.asarray(ids)

    @jax.jit
    def step(params, opt_state):
        def loss_fn(p):
            logits, _ = model.apply(
                p, state, idsj, L.Context(train=True)
            )
            return lm_loss(logits, idsj)

        loss, grads = jax.value_and_grad(loss_fn)(params)
        params, opt_state = opt.update(
            params, opt_state, grads, jnp.float32(LR)
        )
        return params, opt_state, loss

    return step, params, opt_state, model, state, idsj


def _run_parity(spec, n_steps=3, rtol_params=2e-4):
    """Train `n_steps` under `spec` and densely; assert the loss
    trajectory matches at rtol 1e-5 and final params at rtol_params."""
    eng = build_plan_engine(TINY, SGD(), spec, donate=False)
    ts = eng.init_state(jax.random.PRNGKey(0))
    ids = _ids(seed=7)
    ids_s, tg_s = eng.shard_batch(ids)
    step, params, opt_state, model, state, idsj = _dense_step_fn(
        TINY, ids
    )
    for i in range(n_steps):
        ts, m = eng.train_step(ts, ids_s, tg_s, jnp.float32(LR))
        params, opt_state, dense_loss = step(params, opt_state)
        np.testing.assert_allclose(
            float(m["loss_sum"]) / float(m["count"]),
            float(dense_loss), rtol=1e-5,
            err_msg=f"{spec} diverged from dense at step {i}",
        )
        assert float(m["count"]) == B * (T - 1)
    got = eng.to_canonical(ts).params
    for (path, a), b in zip(
        jax.tree_util.tree_leaves_with_path(params),
        jax.tree_util.tree_leaves(got),
    ):
        np.testing.assert_allclose(
            np.asarray(a), np.asarray(b), rtol=rtol_params, atol=2e-5,
            err_msg=f"{spec}: {jax.tree_util.keystr(path)}",
        )
    # eval path agrees with the dense eval loss on the trained params
    ev = eng.eval_step(ts, ids_s, tg_s)
    logits, _ = model.apply(params, state, idsj, L.Context(train=False))
    np.testing.assert_allclose(
        float(ev["loss_sum"]) / float(ev["count"]),
        float(lm_loss(logits, idsj)), rtol=1e-5,
    )


# ------------------------------------------------------------ the spec


def test_parse_plan_fields_and_spec_roundtrip():
    p = parse_plan("pp2xsp2xdp2")
    assert (p.pp, p.tp_or_sp, p.dp, p.ep, p.fsdp) == (2, 2, 2, 1, False)
    assert p.num_devices == 8
    assert parse_plan(p.spec) == p
    q = parse_plan("pp2xfsdp4")
    assert q.fsdp and q.dp == 4 and q.num_devices == 8
    assert parse_plan(q.spec) == q
    # tp is an alias for the within-'ici' model axis
    assert parse_plan("tp4").tp_or_sp == 4
    assert parse_plan("dp1") == ParallelPlan()


@pytest.mark.parametrize("bad", [
    "", "pp2x", "xx4", "pp2xpp2", "sp2xtp2", "dp3x2", "pp0",
])
def test_parse_plan_rejects_bad_specs(bad):
    with pytest.raises(ValueError):
        parse_plan(bad)


# ------------------------------------------- the degenerate-plan map


def test_degenerate_plans_route_to_single_axis_engines():
    """The INTERNALS §19 map as a type contract: each existing
    single-axis engine IS the degenerate form of its plan."""
    from distributed_model_parallel_tpu.parallel.pipeline import (
        LMPipelineEngine,
    )
    from distributed_model_parallel_tpu.parallel.sequence_parallel import (
        CausalLMSequenceParallelEngine,
    )

    assert isinstance(
        build_plan_engine(TINY, SGD(), "pp2", donate=False),
        LMPipelineEngine,
    )
    assert isinstance(
        build_plan_engine(TINY, SGD(), "sp2", donate=False),
        CausalLMSequenceParallelEngine,
    )
    for spec in ("dp8", "fsdp4", "pp2xdp2", "sp2xdp2"):
        assert isinstance(
            build_plan_engine(TINY, SGD(), spec, donate=False),
            ComposedPlanEngine,
        ), spec


def test_build_plan_engine_refusals():
    import dataclasses

    with pytest.raises(ValueError, match="devices"):
        build_plan_engine(TINY, SGD(), "dp64")
    with pytest.raises(ValueError, match="no experts"):
        build_plan_engine(TINY, SGD(), "ep2")
    moe_cfg = dataclasses.replace(TINY, num_experts=4)
    # The refusal names the offending ParallelPlan FIELD and the flag
    # that sets it (ISSUE 20's guard convention), not a roadmap item.
    with pytest.raises(NotImplementedError, match="ParallelPlan.ep"):
        build_plan_engine(moe_cfg, SGD(), "pp2xep2")
    with pytest.raises(NotImplementedError, match="--plan"):
        build_plan_engine(moe_cfg, SGD(), "sp2xep2")
    # uniform stage slices: pp must divide the layer stack
    with pytest.raises(ValueError, match="num_layers"):
        build_plan_engine(
            TINY, SGD(), "pp8", force_composed=True,
        )
    # the tick loop cannot fill a pipeline with fewer microbatches
    # than stages
    with pytest.raises(ValueError, match="num_microbatches"):
        build_plan_engine(
            TINY, SGD(), "pp2xdp2", num_microbatches=1,
        )


# --------------------------------------------------- parity vs dense


def test_composed_2x2x2_matches_dense_trajectory():
    """THE acceptance pin (ISSUE 19): the pp2 x sp2 x dp2 composed
    plan on the 8-device mesh follows the dense 3-step trajectory —
    losses, token counts, final params, eval — at rtol 1e-5."""
    _run_parity("pp2xsp2xdp2")


# ------------------------------------------- scheduled plans (ISSUE 20)


def test_parse_plan_schedule_suffix_roundtrip():
    """`-1f1b` / `-int<V>` on the pp token are ParallelPlan.schedule /
    .virtual_stages; the spec string round-trips, including the dashed
    `pp2-1f1b-xsp2` form the checkpoint satellite saves under."""
    p = parse_plan("pp2-1f1bxsp2xdp2")
    assert (p.pp, p.tp_or_sp, p.dp) == (2, 2, 2)
    assert p.schedule == "1f1b" and p.virtual_stages == 1
    assert parse_plan(p.spec) == p
    q = parse_plan("pp4-int2xdp2")
    assert q.schedule == "interleaved" and q.virtual_stages == 2
    assert parse_plan(q.spec) == q
    # dashed-separator tolerance: `pp2-1f1b-xsp2` == `pp2-1f1bxsp2`
    assert parse_plan("pp2-1f1b-xsp2") == parse_plan("pp2-1f1bxsp2")
    # default stays gpipe and prints without a suffix
    assert parse_plan("pp2xdp2").schedule == "gpipe"
    assert "-" not in parse_plan("pp2xdp2").spec


@pytest.mark.parametrize("bad", [
    "pp2-int1",     # V=1 interleaving is spelled 1f1b
    "sp2-1f1b",     # schedule suffix only composes with the pp token
    "dp4-int2",
    "pp1-1f1b",     # a schedule needs a pipeline (pp >= 2)
    "pp2-gpipe",    # gpipe is the default, not a suffix
])
def test_parse_plan_rejects_bad_schedule_specs(bad):
    with pytest.raises(ValueError):
        parse_plan(bad)


def test_scheduled_plan_guards_name_field_and_flag():
    """ISSUE 20 guard convention: refusals name the ParallelPlan field
    AND the flag that sets it, fail-fast at build time."""
    # interleaved needs M >= pp * V to fill every virtual stage
    with pytest.raises(ValueError, match="num_microbatches"):
        build_plan_engine(
            TINY, SGD(), "pp2-int2xdp2", num_microbatches=2,
        )
    # V * pp must divide the block count (TINY has 4 layers)
    with pytest.raises(ValueError, match="num_layers"):
        build_plan_engine(TINY, SGD(), "pp2-int4xdp2")
    with pytest.raises(ValueError, match="virtual_stages"):
        ParallelPlan(pp=2, schedule="interleaved", virtual_stages=1)
    with pytest.raises(ValueError, match="schedule"):
        ParallelPlan(pp=1, schedule="1f1b")


def test_fsdp_per_parameter_layout():
    """The plan's fsdp bit uses the single-axis FSDPEngine's
    per-parameter layout (ISSUE 20), not whole-leaf 1/dp: leaves under
    `min_shard_elems` stay replicated P(), big leaves shard 1/dp on
    'data', and AdamW moments sit alongside their parameter with the
    SAME per-leaf spec."""
    from jax.sharding import PartitionSpec as P

    from distributed_model_parallel_tpu.training.optim import AdamW

    eng = build_plan_engine(TINY, AdamW(), "fsdp8", donate=False)
    specs = eng.state_partition_specs()
    is_spec = lambda x: isinstance(x, P)  # noqa: E731
    leaves = jax.tree_util.tree_leaves(specs.params, is_leaf=is_spec)
    sharded = [s for s in leaves if s != P()]
    repl = [s for s in leaves if s == P()]
    # per-parameter means BOTH kinds coexist in one params tree
    assert sharded, "no leaf sharded — not an fsdp layout"
    assert repl, "every leaf sharded — min_shard_elems ignored"
    assert all(
        any(part == "data" for part in s if part is not None)
        for s in sharded
    )
    # moments mirror the per-leaf layout exactly
    assert jax.tree_util.tree_leaves(
        specs.opt_state.mu, is_leaf=is_spec
    ) == leaves
    assert jax.tree_util.tree_leaves(
        specs.opt_state.nu, is_leaf=is_spec
    ) == leaves


def test_composed_1f1b_matches_dense_trajectory():
    """THE acceptance pin (ISSUE 20): the pp2-1f1b x sp2 x dp2
    scheduled plan on the 8-device mesh follows the dense 3-step
    trajectory — losses, token counts, final params, eval — at
    rtol 1e-5."""
    _run_parity("pp2-1f1bxsp2xdp2")


def test_1f1b_bit_identical_to_gpipe_twin():
    """At M == S the 1F1B table IS the gpipe fill-drain order (all
    forwards, then all backwards, same microbatch order), so the final
    params after 3 steps must be BIT-identical to the gpipe twin —
    the 'math-preserving schedule' half of the ISSUE 20 parity bar."""
    finals = []
    for spec in ("pp2xdp4", "pp2-1f1bxdp4"):
        eng = build_plan_engine(TINY, SGD(), spec, donate=False)
        ts = eng.init_state(jax.random.PRNGKey(0))
        ids = _ids(seed=11)
        ids_s, tg_s = eng.shard_batch(ids)
        for _ in range(3):
            ts, _ = eng.train_step(ts, ids_s, tg_s, jnp.float32(LR))
        finals.append(eng.to_canonical(ts).params)
    for (path, a), b in zip(
        jax.tree_util.tree_leaves_with_path(finals[0]),
        jax.tree_util.tree_leaves(finals[1]),
    ):
        assert np.array_equal(np.asarray(a), np.asarray(b)), (
            f"1f1b twin diverged bitwise: {jax.tree_util.keystr(path)}"
        )


def _payload_leading_dims(lowered_text, min_payload=2048):
    """Leading dims of every f32 buffer in the lowered StableHLO
    (`tensor<AxB..xf32>`) whose per-row payload is at least
    `min_payload` elements — the activation stacks; tiny control
    tensors are noise."""
    import re as _re

    dims = set()
    for m in _re.finditer(r"tensor<(\d+(?:x\d+)+)xf32>", lowered_text):
        shape = [int(x) for x in m.group(1).split("x")]
        payload = 1
        for d in shape[1:]:
            payload *= d
        if payload >= min_payload:
            dims.add(shape[0])
    return dims


def test_1f1b_activation_memory_structurally_o_s_not_o_m():
    """The structural O(S)-vs-O(M) pin (ISSUE 20) from lowered HLO:
    at M=8 >> S=2 the gpipe program stacks per-microbatch residuals
    (an f32 buffer with leading dim >= M appears), while the 1F1B
    program's largest leading dim stays below M — its stash depth is
    min(S, M), independent of M."""
    rng = np.random.RandomState(2)
    ids = rng.randint(1, TINY.vocab_size, size=(16, T)).astype(np.int32)
    dims = {}
    for spec in ("pp2xdp2", "pp2-1f1bxdp2"):
        eng = build_plan_engine(
            TINY, SGD(), spec, num_microbatches=8, donate=False,
        )
        ts = eng.init_state(jax.random.PRNGKey(0))
        ids_s, tg_s = eng.shard_batch(ids)
        txt = eng.train_step.lower(
            ts, ids_s, tg_s, jnp.float32(LR)
        ).as_text()
        dims[spec] = _payload_leading_dims(txt)
    M, S = 8, 2
    assert max(dims["pp2xdp2"]) >= M, dims
    # 1f1b: stacked block params give leading dim num_layers=4; no
    # activation stack reaches M
    assert max(dims["pp2-1f1bxdp2"]) < M, dims
    # and the schedule table itself pins the tight O(S) bound
    eng = build_plan_engine(
        TINY, SGD(), "pp2-1f1bxdp2", num_microbatches=8, donate=False,
    )
    assert eng._sched.stash_depth <= min(S, M)


def test_scheduled_layouts_identical_to_gpipe_twin():
    """Schedule is execution-only: a scheduled plan declares the SAME
    state_partition_specs as its gpipe twin (checkpoints reshard
    across schedules through the canonical seam for free)."""
    for a, b in (
        ("pp2xsp2xdp2", "pp2-1f1bxsp2xdp2"),
        ("pp2xfsdp4", "pp2-int2xfsdp4"),
    ):
        sa = build_plan_engine(
            TINY, SGD(), a, donate=False
        ).state_partition_specs()
        sb = build_plan_engine(
            TINY, SGD(), b, donate=False
        ).state_partition_specs()
        assert jax.tree_util.tree_structure(sa) == \
            jax.tree_util.tree_structure(sb)
        assert jax.tree_util.tree_leaves(sa) == \
            jax.tree_util.tree_leaves(sb), (a, b)


def test_degenerate_scheduled_plan_routes_to_pipeline_engine():
    """A pp-only scheduled plan routes to the single-axis
    LMPipelineEngine with the schedule and V threaded through (the
    degenerate-plan map extends to schedules)."""
    from distributed_model_parallel_tpu.parallel.pipeline import (
        LMPipelineEngine,
    )

    eng = build_plan_engine(TINY, SGD(), "pp2-1f1b", donate=False)
    assert isinstance(eng, LMPipelineEngine)
    assert eng.schedule == "1f1b"
    eng = build_plan_engine(TINY, SGD(), "pp2-int2", donate=False)
    assert isinstance(eng, LMPipelineEngine)
    assert eng.schedule == "interleaved" and eng.virtual_stages == 2


# ------------------------------------------------- layout declarations


def test_state_partition_specs_shapes_match_state():
    """The manifest seam declares one spec per TrainState leaf for
    BOTH plan classes: all-P() for a replicated plan, 1/dp 'data'
    leaves for an fsdp plan."""
    from jax.sharding import PartitionSpec as P

    repl = build_plan_engine(TINY, SGD(), "pp2xsp2xdp2", donate=False)
    ts = repl.init_state(jax.random.PRNGKey(0))
    specs = repl.state_partition_specs()
    is_spec = lambda x: isinstance(x, P)  # noqa: E731
    flat = jax.tree_util.tree_leaves(specs, is_leaf=is_spec)
    assert len(flat) == len(jax.tree_util.tree_leaves(ts))
    assert all(s == P() for s in flat)

    fs = build_plan_engine(TINY, SGD(), "fsdp8", donate=False)
    fs_specs = jax.tree_util.tree_leaves(
        fs.state_partition_specs().params, is_leaf=is_spec,
    )
    assert any("data" in (s[0] or ()) if len(s) else False
               for s in fs_specs if s != P())


# ---------------- the gradient itself, not only trajectories (ISSUE 33)


def _dense_gradient(cfg, ids):
    """The one-device float32 gradient of the mean token loss."""
    model = gpt_lm(cfg)
    params, state = model.init(jax.random.PRNGKey(0))
    idsj = jnp.asarray(ids)

    def loss_fn(p):
        logits, _ = model.apply(p, state, idsj, L.Context(train=True))
        return lm_loss(logits, idsj)

    return jax.grad(loss_fn)(params)


def _first_step_gradient(spec, ids, **kw):
    """The gradient a plan's first step hands its optimizer: with
    momentum and decay off, SGD's buffer after that step IS it."""
    eng = build_plan_engine(
        TINY, SGD(momentum=0.0, weight_decay=0.0), spec, donate=False,
        force_composed=True, min_shard_elems=16, **kw,
    )
    ts = eng.init_state(jax.random.PRNGKey(0))
    ts, _ = eng.train_step(ts, *eng.shard_batch(ids), jnp.float32(LR))
    return ts.opt_state.momentum


@pytest.mark.parametrize("remat", [False, True], ids=["plain", "remat"])
@pytest.mark.parametrize("compute_dtype", [None, jnp.bfloat16],
                         ids=["f32", "bf16"])
def test_fsdp_gradient_equals_the_dense_one_leaf_by_leaf(
    remat, compute_dtype
):
    """One step of `fsdp4`, every leaf of the gradient at float32
    tolerance: against the dense one-device gradient in float32; in
    bfloat16 against `dp4`, the same per-device program whose whole
    float32 gradients meet in the one fused psum (each device's dW is
    rounded to bfloat16 once before it is summed, which one dense
    pass over the whole batch does not reproduce bit for bit) — a
    reduce-scatter in the compute dtype would miss that by three
    orders of magnitude more. Every leaf is still 1/4 where fsdp
    shards it."""
    ids = _ids(seed=5)
    got = _first_step_gradient(
        "fsdp4", ids, remat=remat, compute_dtype=compute_dtype
    )
    if compute_dtype is None:
        want = _dense_gradient(TINY, ids)
    else:
        want = _first_step_gradient(
            "dp4", ids, remat=remat, compute_dtype=compute_dtype
        )
    sharded = 0
    for (path, w), g in zip(
        jax.tree_util.tree_leaves_with_path(want),
        jax.tree_util.tree_leaves(got),
    ):
        sharded += g.addressable_shards[0].data.shape != g.shape
        assert g.dtype == jnp.float32
        np.testing.assert_allclose(
            np.asarray(g), np.asarray(w), rtol=2e-5,
            atol=2e-6 * float(jnp.max(jnp.abs(w))) + 1e-9,
            err_msg=jax.tree_util.keystr(path),
        )
    # at this min_shard_elems the vectors shard too: nothing is left
    # replicated
    assert sharded == len(jax.tree_util.tree_leaves(got))


# ------------------------- local attention under sp == 1 (ISSUE 31)


def _fsdp_two_steps(compute_dtype):
    """Two steps of `fsdp4` with remat at TINY widths: the losses, and
    the norm of the parameters' change over the first step (SGD: lr
    times the gradient norm). Also the engine, for its field, and the
    number of kernels its traced step holds."""
    eng = build_plan_engine(
        TINY, SGD(), "fsdp4", donate=False, remat=True,
        compute_dtype=compute_dtype,
    )
    ts0 = eng.init_state(jax.random.PRNGKey(0))
    ids_s, tg_s = eng.shard_batch(_ids(seed=11))
    ts, losses = ts0, []
    moved = None
    for _ in range(2):
        ts, m = eng.train_step(ts, ids_s, tg_s, jnp.float32(LR))
        losses.append(float(m["loss_sum"]) / float(m["count"]))
        if moved is None:
            moved = float(jnp.sqrt(sum(
                jnp.sum(jnp.square(a - b)) for a, b in zip(
                    jax.tree_util.tree_leaves(ts.params),
                    jax.tree_util.tree_leaves(ts0.params),
                )
            )))
    kernels = str(jax.make_jaxpr(eng.train_step)(
        ts0, ids_s, tg_s, jnp.float32(LR)
    )).count("pallas_call")
    return eng, losses, moved, kernels


@pytest.mark.parametrize("compute_dtype,rtol", [
    (None, 1e-5), (jnp.bfloat16, 5e-3),
], ids=["f32", "bf16"])
def test_fsdp_step_with_the_kernel_forced_matches_dense(
    monkeypatch, compute_dtype, rtol
):
    """The one place a CPU test runs the kernels through a plan, and it
    says so: with the selector's backend predicate patched to "tpu"
    (the kernels run in the interpreter) an fsdp4 step with remat
    attends with flash, and its losses and gradient norm are the dense
    step's — to float32 rounding in float32, inside the flash tests'
    band in bfloat16."""
    from distributed_model_parallel_tpu.ops import pallas_attention

    dense, dense_losses, dense_moved, n = _fsdp_two_steps(compute_dtype)
    assert dense.local_attention == "dense" and n == 0
    monkeypatch.setattr(pallas_attention, "_on_tpu", lambda: True)
    flash, flash_losses, flash_moved, n = _fsdp_two_steps(compute_dtype)
    # forward, the remat's forward, and the two backward kernels:
    # once in the block scan's body and once in the last block, which
    # an fsdp plan runs after its scan (nothing is left to gather
    # beside it, ISSUE 33)
    assert flash.local_attention == "flash" and n == 8
    np.testing.assert_allclose(flash_losses, dense_losses, rtol=rtol)
    np.testing.assert_allclose(flash_moved, dense_moved, rtol=rtol)
    assert dense_moved > 0


def test_local_attention_field_says_what_the_step_holds(monkeypatch):
    """`ComposedPlanEngine.local_attention`: None where the sequence is
    sharded (the `attention` argument rules there); otherwise the
    selector's answer — "dense" off a TPU; on one, "flash" at a length
    the kernels tile and "dense" at one they cannot, re-decided when a
    step is traced at the batch's own length."""
    from distributed_model_parallel_tpu.ops import pallas_attention

    assert build_plan_engine(
        TINY, SGD(), "pp2xsp2xdp2", donate=False
    ).local_attention is None
    for spec in ("fsdp4", "dp8", "pp2xdp2"):
        assert build_plan_engine(
            TINY, SGD(), spec, donate=False
        ).local_attention == "dense"
    monkeypatch.setattr(pallas_attention, "_on_tpu", lambda: True)
    eng = build_plan_engine(TINY, SGD(), "dp2", donate=False,
                            force_composed=True)
    assert eng.local_attention == "flash"  # at max_position = 16
    # a batch of 13 tokens a sequence has no tiling: the trace says so
    ids = _ids(seed=3)[:, :13]
    jax.eval_shape(
        eng.train_step, eng.init_state(jax.random.PRNGKey(0)),
        *eng.shard_batch(ids), jnp.float32(LR),
    )
    assert eng.local_attention == "dense"
