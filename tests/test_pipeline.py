"""Pipeline-MP engine tests on the 8-device CPU mesh.

Parity methodology (SURVEY.md §4): the reference validated its pipeline by
showing it learns the same as single-device/data-parallel training
(`Readme.md:283-294`); here the check is exact — pipeline forward equals
the sequential composition, and the pipeline gradient step equals the
single-device gradient step to float tolerance.
"""

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from distributed_model_parallel_tpu.models import layers as L
from distributed_model_parallel_tpu.models import mobilenetv2
from distributed_model_parallel_tpu.parallel.pipeline import PipelineEngine
from distributed_model_parallel_tpu.runtime.mesh import MeshSpec, make_mesh
from distributed_model_parallel_tpu.training.metrics import cross_entropy
from distributed_model_parallel_tpu.training.optim import SGD


def tiny_stages(num_classes=4):
    """A 4-stage BN-free CNN: heterogeneous activation shapes across the
    cuts (32ch 8x8 -> 8ch 8x8 -> 16ch 4x4 -> logits), exercising the padded
    ppermute buffer."""
    return [
        L.sequential(L.conv2d(3, 32, 3, stride=1, padding=1), L.relu()),
        L.sequential(L.conv2d(32, 8, 3, stride=1, padding=1), L.relu()),
        L.sequential(L.conv2d(8, 16, 3, stride=2, padding=1), L.relu()),
        L.sequential(L.global_avg_pool(), L.linear(16, num_classes)),
    ]


def batch(n=16, hw=8, num_classes=4, seed=0):
    rng = np.random.RandomState(seed)
    images = rng.rand(n, hw, hw, 3).astype(np.float32)
    labels = rng.randint(0, num_classes, size=(n,)).astype(np.int32)
    return jnp.asarray(images), jnp.asarray(labels)


@pytest.fixture()
def pp_mesh():
    return make_mesh(MeshSpec(data=2, stage=4))


def seq_reference(stages, params, state, images, labels, train=True):
    """Single-device composition of the stages (the ground truth the
    reference could only approximate with convergence curves)."""
    full = L.sequential(*stages)
    seq_params = {str(i): p for i, p in enumerate(params)}
    seq_state = {str(i): s for i, s in enumerate(state)}

    def loss_fn(p):
        logits, new_s = full.apply(
            p, seq_state, images, L.Context(train=train)
        )
        return cross_entropy(logits, labels), logits

    (loss, logits), grads = jax.value_and_grad(loss_fn, has_aux=True)(
        seq_params
    )
    return loss, logits, grads


def test_eval_matches_sequential(pp_mesh):
    stages = tiny_stages()
    engine = PipelineEngine(stages, SGD(), pp_mesh, num_microbatches=2)
    ts = engine.init_state(jax.random.PRNGKey(0))
    images, labels = batch()
    m = engine.eval_step(ts, *engine.shard_batch(images, labels))
    loss, logits, _ = seq_reference(
        stages, ts.params, ts.model_state, images, labels, train=False
    )
    np.testing.assert_allclose(
        float(m["loss_sum"]) / float(m["count"]), float(loss),
        rtol=1e-5, atol=1e-6,
    )
    assert float(m["count"]) == 16


@pytest.mark.parametrize("microbatches", [1, 4])
def test_train_step_matches_single_device(pp_mesh, microbatches):
    """One pipeline SGD step == one single-device SGD step (BN-free model,
    so microbatching is gradient-exact: GPipe sums microbatch grads)."""
    stages = tiny_stages()
    engine = PipelineEngine(
        stages, SGD(momentum=0.9, weight_decay=1e-4), pp_mesh,
        num_microbatches=microbatches,
    )
    ts = engine.init_state(jax.random.PRNGKey(1))
    images, labels = batch()
    lr = jnp.float32(0.1)

    _, _, grads = seq_reference(
        stages, ts.params, ts.model_state, images, labels
    )
    opt = SGD(momentum=0.9, weight_decay=1e-4)
    seq_params = {str(i): p for i, p in enumerate(ts.params)}
    expect_params, _ = opt.update(
        seq_params, opt.init(seq_params), grads, lr
    )

    new_ts, metrics = engine.train_step(
        ts, *engine.shard_batch(images, labels), lr
    )
    got = {str(i): p for i, p in enumerate(new_ts.params)}
    flat_a = jax.tree_util.tree_leaves_with_path(expect_params)
    flat_b = jax.tree_util.tree_leaves(got)
    assert len(flat_a) == len(flat_b)
    for (path, a), b in zip(flat_a, flat_b):
        np.testing.assert_allclose(
            np.asarray(a), np.asarray(b), rtol=2e-4, atol=2e-5,
            err_msg=jax.tree_util.keystr(path),
        )
    assert np.isfinite(float(metrics["loss_sum"]))


def _pipeline_learns(stages, pp_mesh, hw):
    engine = PipelineEngine(stages, SGD(), pp_mesh, num_microbatches=2)
    ts = engine.init_state(jax.random.PRNGKey(0))
    images, labels = batch(n=16, hw=hw)
    images, labels = engine.shard_batch(images, labels)
    losses = []
    for _ in range(4):
        ts, m = engine.train_step(ts, images, labels, jnp.float32(0.05))
        losses.append(float(m["loss_sum"]) / float(m["count"]))
    assert losses[-1] < losses[0]


def test_pipeline_learns_tinycnn(pp_mesh):
    """Convergence smoke on a real BN model split into 4 stages — the
    cheap twin of the MobileNetV2 flagship test below (same engine,
    microbatching, BN-state masking paths)."""
    from distributed_model_parallel_tpu.models import tinycnn

    _pipeline_learns(tinycnn.split_stages(4, num_classes=4), pp_mesh, hw=8)


@pytest.mark.slow
def test_pipeline_learns_mobilenet(pp_mesh):
    """Convergence smoke on the real flagship split: MobileNetV2 with the
    reference's exact ws=4 boundaries (`model_parallel.py:102-144`).
    Tier-1 twin: test_pipeline_learns (the same _pipeline_learns
    assertions on the tiny stages)."""
    stages = mobilenetv2.split_stages(4, num_classes=4, boundaries=[3, 9, 15])
    _pipeline_learns(stages, pp_mesh, hw=32)


def test_stage_axis_size_mismatch_raises(pp_mesh):
    with pytest.raises(ValueError, match="stage"):
        PipelineEngine(tiny_stages()[:3], SGD(), pp_mesh)


def bn_stages(num_classes=4):
    """4 stages, three of them with BatchNorm — exercises the bubble
    masking of BN-state updates and the masked psum reassembly, the
    subtlest code in the pipeline."""
    def convbn(cin, cout, stride=1):
        return L.sequential(
            L.conv2d(cin, cout, 3, stride=stride, padding=1),
            L.batchnorm2d(cout),
            L.relu(),
        )

    return [
        convbn(3, 8),
        convbn(8, 8),
        convbn(8, 8, stride=2),
        L.sequential(L.global_avg_pool(), L.linear(8, num_classes)),
    ]


@pytest.mark.slow
def test_pipeline_bn_microbatch_state_and_grads_match_sequential(pp_mesh):
    """Direct numerical test of pipeline+BN microbatching. `slow`
    (tier-1 budget); tier-1 twins:
    test_stage_local_matches_replicated[bn_stages] (BN stages, same
    mesh) and test_pipeline_schedule.py::
    test_1f1b_bn_running_stats_match_gpipe (the BN microbatch fold).
    With M microbatches on a (data=2, stage=4) mesh,

    * each stage's BN running stats must equal the SEQUENTIAL fold of the
      per-(shard, microbatch) updates, pmean-ed over 'data' (sync_bn=False
      persists the shard-average, `pipeline.py` train step);
    * the SGD step must equal the single-device step on the loss
      mean_CE(concat of per-(shard, microbatch) forwards with
      per-chunk BN batch stats).
    """
    M = 4
    D = 2
    stages = bn_stages()
    engine = PipelineEngine(
        stages, SGD(momentum=0.9, weight_decay=1e-4), pp_mesh,
        num_microbatches=M,
    )
    ts = engine.init_state(jax.random.PRNGKey(3))
    images, labels = batch(n=16, hw=8, seed=5)
    n_local = images.shape[0] // D
    mb = n_local // M

    # ---- sequential reference: fold per (shard, microbatch) ----------
    shard_states = []
    all_logits_fn_inputs = []  # (shard, microbatch) image chunks in order
    for d in range(D):
        state_d = ts.model_state
        for m in range(M):
            lo = d * n_local + m * mb
            chunk = images[lo:lo + mb]
            all_logits_fn_inputs.append((d, m, chunk))
            x = chunk
            new_state_d = []
            for i, stage in enumerate(stages):
                x, s_i = stage.apply(
                    ts.params[i], state_d[i], x, L.Context(train=True)
                )
                new_state_d.append(s_i)
            state_d = tuple(new_state_d)
        shard_states.append(state_d)
    # sync_bn=False: persisted stats are the pmean over 'data'.
    want_state = jax.tree_util.tree_map(
        lambda *leaves: sum(leaves) / D, *shard_states
    )

    def seq_loss(params):
        logits = []
        for d, m, chunk in all_logits_fn_inputs:
            x = chunk
            for i, stage in enumerate(stages):
                x, _ = stage.apply(
                    params[i], ts.model_state[i], x, L.Context(train=True)
                )
            logits.append(x)
        logits = jnp.concatenate(logits)
        # per-(shard,mb) order == row order, so labels align.
        return cross_entropy(logits, labels)

    grads = jax.grad(seq_loss)(ts.params)
    opt = SGD(momentum=0.9, weight_decay=1e-4)
    want_params, _ = opt.update(ts.params, opt.init(ts.params), grads, 0.1)

    # ---- the pipeline step ------------------------------------------
    new_ts, _ = engine.train_step(
        ts, *engine.shard_batch(images, labels), jnp.float32(0.1)
    )

    for i in range(4):
        for (path, a), b in zip(
            jax.tree_util.tree_leaves_with_path(want_state[i]),
            jax.tree_util.tree_leaves(new_ts.model_state[i]),
        ):
            np.testing.assert_allclose(
                np.asarray(a), np.asarray(b), rtol=1e-5, atol=1e-6,
                err_msg=f"BN state stage {i} {jax.tree_util.keystr(path)}",
            )
        for (path, a), b in zip(
            jax.tree_util.tree_leaves_with_path(want_params[i]),
            jax.tree_util.tree_leaves(new_ts.params[i]),
        ):
            np.testing.assert_allclose(
                np.asarray(a), np.asarray(b), rtol=2e-4, atol=2e-5,
                err_msg=f"params stage {i} {jax.tree_util.keystr(path)}",
            )


# ---------------------------------------------------------------------------
# Stage-local parameter storage: params / BN state /
# momentum sharded over 'stage' so each device stores ~1/S of the model.
# ---------------------------------------------------------------------------


def _run_steps(engine, images, labels, n=3, lr=0.1):
    ts = engine.init_state(jax.random.PRNGKey(1))
    sb = engine.shard_batch(images, labels)
    losses = []
    for _ in range(n):
        ts, m = engine.train_step(ts, *sb, jnp.float32(lr))
        losses.append(float(m["loss_sum"]) / float(m["count"]))
    return ts, losses


@pytest.mark.parametrize("stages_fn", [tiny_stages, bn_stages])
def test_stage_local_matches_replicated(pp_mesh, stages_fn):
    """stage_local_params=True must be a pure storage-layout change: the
    training trajectory equals the replicated representation's (same init
    seed), including BN running stats."""
    stages = stages_fn()
    images, labels = batch(n=16, hw=8, seed=5)
    repl = PipelineEngine(
        stages, SGD(momentum=0.9), pp_mesh, num_microbatches=2,
        donate=False,
    )
    local = PipelineEngine(
        stages, SGD(momentum=0.9), pp_mesh, num_microbatches=2,
        donate=False, stage_local_params=True,
    )
    ts_r, losses_r = _run_steps(repl, images, labels)
    ts_l, losses_l = _run_steps(local, images, labels)
    np.testing.assert_allclose(losses_l, losses_r, rtol=1e-5)
    got = local.params_tree(ts_l)
    for i, want in enumerate(repl.params_tree(ts_r)):
        for (path, a), b in zip(
            jax.tree_util.tree_leaves_with_path(want),
            jax.tree_util.tree_leaves(got[i]),
        ):
            np.testing.assert_allclose(
                np.asarray(a), np.asarray(b), rtol=2e-4, atol=2e-5,
                err_msg=f"stage {i} {jax.tree_util.keystr(path)}",
            )


def test_stage_local_memory_is_one_over_s(pp_mesh):
    """Each device's addressable params shard is the (1, maxP) slice —
    bounded by the LARGEST stage, not the sum of all stages. This is the
    memory scaling that makes pipeline MP a memory tool (the reason the
    reference split its model across GPUs, `model_parallel.py:99-157`)."""
    stages = tiny_stages()
    engine = PipelineEngine(
        stages, SGD(), pp_mesh, stage_local_params=True
    )
    ts = engine.init_state(jax.random.PRNGKey(0))
    S = engine.num_stages
    assert ts.params.shape == (S, engine._psize)
    for shard in ts.params.addressable_shards:
        assert shard.data.shape == (1, engine._psize)
    # The per-device slice is strictly smaller than the whole model.
    total_params = sum(
        np.prod(l.shape)
        for a in engine._param_avals
        for l in jax.tree_util.tree_leaves(a)
    )
    assert engine._psize < total_params
    # Momentum rides the same layout.
    assert ts.opt_state.momentum.shape == (S, engine._psize)


def test_stage_local_eval_matches_sequential(pp_mesh):
    stages = tiny_stages()
    engine = PipelineEngine(
        stages, SGD(), pp_mesh, num_microbatches=2,
        stage_local_params=True,
    )
    ts = engine.init_state(jax.random.PRNGKey(0))
    images, labels = batch()
    m = engine.eval_step(ts, *engine.shard_batch(images, labels))
    params = engine.params_tree(ts)
    state = tuple(
        stage.init(jax.random.PRNGKey(9))[1] for stage in stages
    )  # stateless stages: empty dicts in the right structure
    loss, logits, _ = seq_reference(
        stages, params, state, images, labels, train=False
    )
    np.testing.assert_allclose(
        float(m["loss_sum"]) / float(m["count"]), float(loss),
        rtol=1e-5, atol=1e-6,
    )


def test_stage_local_checkpoint_interop(pp_mesh, tmp_path):
    """Checkpoints are written in canonical per-stage-pytree form, so a
    run with stage_local_params=True can be resumed without the flag and
    vice versa (layout is a runtime choice, not a checkpoint format)."""
    from distributed_model_parallel_tpu.training.checkpoint import (
        restore_checkpoint,
        save_checkpoint,
    )

    stages = bn_stages()
    images, labels = batch(n=16, hw=8, seed=5)
    local = PipelineEngine(
        stages, SGD(), pp_mesh, num_microbatches=2, donate=False,
        stage_local_params=True,
    )
    ts_l, _ = _run_steps(local, images, labels, n=2)
    save_checkpoint(
        str(tmp_path), local.to_canonical(ts_l), acc=50.0, epoch=1
    )

    repl = PipelineEngine(
        stages, SGD(), pp_mesh, num_microbatches=2, donate=False,
    )
    ts_r = repl.init_state(jax.random.PRNGKey(42))  # different init
    restored, acc, epoch = restore_checkpoint(
        str(tmp_path), repl.to_canonical(ts_r)
    )
    ts_r2 = repl.from_canonical(restored)
    assert acc == 50.0 and epoch == 1
    want = local.params_tree(ts_l)
    for i, got in enumerate(repl.params_tree(ts_r2)):
        for a, b in zip(
            jax.tree_util.tree_leaves(want[i]),
            jax.tree_util.tree_leaves(got),
        ):
            np.testing.assert_allclose(np.asarray(a), np.asarray(b))
    # And back: the replicated checkpoint loads into a stage-local engine.
    restored2, _, _ = restore_checkpoint(
        str(tmp_path), local.to_canonical(local.init_state(jax.random.PRNGKey(7)))
    )
    ts_l2 = local.from_canonical(restored2)
    step_out, _ = local.train_step(
        ts_l2, *local.shard_batch(images, labels), jnp.float32(0.05)
    )
    assert int(step_out.step) == int(ts_l.step) + 1


@pytest.mark.parametrize("stage_local", [False, True])
def test_pipeline_gradients_equal_pure_jax_grad(pp_mesh, stage_local):
    """The check_vma=False soundness canary.

    The pipeline backward relies on a hand-reasoned argument: under
    `check_vma=False` the loss is kept LOCAL (no psum before grad) so
    autodiff never transposes a cross-device reduction, and the reversed
    ppermutes alone carry true cotangents upstream (`pipeline.py`
    pipeline_forward notes). This test pins that argument numerically:
    with momentum=0, wd=0, lr=1, one SGD step satisfies
    grads == params_before - params_after, which must equal
    `jax.grad` of the sequential composition on the SAME global batch.
    If a JAX upgrade ever changes psum/ppermute transpose semantics
    underneath shard_map, this fails loudly instead of silently
    mis-scaling gradients.
    """
    stages = tiny_stages()
    engine = PipelineEngine(
        stages, SGD(momentum=0.0, weight_decay=0.0), pp_mesh,
        num_microbatches=2, donate=False, stage_local_params=stage_local,
    )
    ts = engine.init_state(jax.random.PRNGKey(2))
    images, labels = batch(n=16, hw=8, seed=11)

    params_before = engine.params_tree(ts)
    new_ts, _ = engine.train_step(
        ts, *engine.shard_batch(images, labels), jnp.float32(1.0)
    )
    params_after = engine.params_tree(new_ts)
    got_grads = jax.tree_util.tree_map(
        lambda a, b: np.asarray(a) - np.asarray(b),
        params_before, params_after,
    )

    state0 = tuple(stage.init(jax.random.PRNGKey(9))[1] for stage in stages)
    _, _, want_grads = seq_reference(
        stages, params_before, state0, images, labels, train=True
    )
    for i in range(len(stages)):
        want_leaves = jax.tree_util.tree_leaves_with_path(want_grads[str(i)])
        got_leaves = jax.tree_util.tree_leaves(got_grads[i])
        assert len(want_leaves) == len(got_leaves), f"stage {i} structure"
        for (path, w), g in zip(want_leaves, got_leaves):
            np.testing.assert_allclose(
                g, np.asarray(w), rtol=2e-4, atol=1e-6,
                err_msg=f"stage {i} {jax.tree_util.keystr(path)}",
            )


def test_opt_field_classification_uses_declaration(pp_mesh):
    """Regression for the shape-heuristic hazard (ADVICE r3 #2): an
    optimizer field that HAPPENS to be shaped exactly like the packed
    (num_stages, psize) buffer but is declared replicated must survive
    to_canonical/from_canonical untouched — the walk keys on the
    optimizer's state_shardings declaration, not on shapes. A
    declaration that uses neither protocol argument raises."""
    from typing import Any, NamedTuple

    class TrapState(NamedTuple):
        momentum: Any  # param-following (packed in stage-local mode)
        aux: Any       # replicated — but shaped (S, psize) by malice

    class TrapSGD:
        def init(self, params):
            mom = jax.tree_util.tree_map(jnp.zeros_like, params)
            leaves = jax.tree_util.tree_leaves(params)
            aux = (
                jnp.full(leaves[0].shape, 7.0, jnp.float32)
                if leaves else jnp.zeros(())
            )
            return TrapState(mom, aux)

        def update(self, params, state, grads, lr):
            mom = jax.tree_util.tree_map(
                lambda m, g: 0.9 * m + g, state.momentum, grads
            )
            new_p = jax.tree_util.tree_map(
                lambda p, m: p - lr * m, params, mom
            )
            return new_p, TrapState(mom, state.aux)

        def state_shardings(self, param_shardings, replicated):
            return TrapState(param_shardings, replicated)

    eng = PipelineEngine(
        tiny_stages(), TrapSGD(), pp_mesh, num_microbatches=2,
        donate=False, stage_local_params=True,
    )
    assert eng._opt_param_fields() == {"momentum": True, "aux": False}
    ts = eng.init_state(jax.random.PRNGKey(0))
    images, labels = batch(n=16, hw=8, seed=11)
    ts, _ = eng.train_step(
        ts, *eng.shard_batch(images, labels), jnp.float32(0.05)
    )
    assert ts.opt_state.aux.shape == (4, eng._psize)  # the trap shape

    canon = eng.to_canonical(ts)
    # momentum unpacks to per-stage pytrees; aux must stay ONE array.
    assert isinstance(canon.opt_state.momentum, tuple)
    assert len(canon.opt_state.momentum) == 4
    assert getattr(canon.opt_state.aux, "shape", None) == (4, eng._psize)
    np.testing.assert_allclose(np.asarray(canon.opt_state.aux), 7.0)

    ts2 = eng.from_canonical(canon)
    assert ts2.opt_state.aux.shape == (4, eng._psize)
    ts3, _ = eng.train_step(
        ts2, *eng.shard_batch(images, labels), jnp.float32(0.05)
    )
    assert int(ts3.step) == int(ts.step) + 1

    class BadDecl(TrapSGD):
        def state_shardings(self, param_shardings, replicated):
            return TrapState(param_shardings, "weird")

    # A declaration built from neither protocol argument is rejected at
    # engine CONSTRUCTION (the probe runs in __post_init__ so the error
    # is loud and early, not an opaque spec failure inside the first
    # step build or checkpoint).
    with pytest.raises(ValueError, match="state_shardings"):
        PipelineEngine(
            tiny_stages(), BadDecl(), pp_mesh, num_microbatches=2,
            donate=False, stage_local_params=True,
        )
