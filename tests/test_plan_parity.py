"""Composed plans against the dense step, one case per factorization
of the 8-device world (`parallel/plan.py`): the engine the four-chip
cell `gpt2xl_train_fsdp4` runs, pinned on every layout it can take.
The assertion and its tiny model are `test_plan.py`'s (`_run_parity`);
the cases live in a file of their own so that, with tests dealt to
workers by file, no one worker carries every composed compile.
"""

import jax
import jax.numpy as jnp
import numpy as np
import pytest
from test_plan import LR, TINY, _dense_step_fn, _ids, _run_parity

from distributed_model_parallel_tpu.parallel.plan import build_plan_engine
from distributed_model_parallel_tpu.training.optim import SGD


def test_composed_dp_only_matches_dense_trajectory():
    """The pure-data composed program (no stage wire, no seq ring —
    the degenerate tick loop) is still exactly dense."""
    _run_parity("dp8")


def test_composed_fsdp_matches_dense_trajectory():
    """ZeRO-3 on the plan's data axis: 1/dp params + moments with the
    plan_fsdp_gather materialization, same trajectory as dense."""
    _run_parity("pp2xfsdp4")


def test_degenerate_composed_matches_forced_composed():
    """Both sides of the degenerate map agree: the single-axis SP
    engine and the force_composed ComposedPlanEngine produce the same
    loss for the same plan, params, and batch."""
    ids = _ids(seed=3)
    losses = []
    for force in (False, True):
        eng = build_plan_engine(
            TINY, SGD(), "sp2", donate=False, force_composed=force,
        )
        ts = eng.init_state(jax.random.PRNGKey(0))
        ids_s, tg_s = eng.shard_batch(ids)
        _, m = eng.train_step(ts, ids_s, tg_s, jnp.float32(LR))
        losses.append(float(m["loss_sum"]) / float(m["count"]))
    np.testing.assert_allclose(losses[0], losses[1], rtol=1e-5)


@pytest.mark.parametrize("spec", [
    "fsdp8", "pp2xdp4", "sp2xdp4", "pp4xdp2", "sp4xdp2",
    "pp2xfsdp2", "sp2xfsdp4", "pp2xsp2xfsdp2", "pp2xsp4",
])
def test_plan_parity_sweep(spec):
    """Every remaining factorization of the 8-device world follows the
    dense trajectory (`fsdp8` is the cell's own layout at this size)."""
    _run_parity(spec)


def test_composed_plan_num_microbatches_above_pp():
    """M > S: extra microbatches drain through the same tick program
    (M + S - 1 ticks) without changing the math."""
    eng = build_plan_engine(
        TINY, SGD(), "pp2xdp2", num_microbatches=4, donate=False,
    )
    ts = eng.init_state(jax.random.PRNGKey(0))
    ids = _ids(seed=5)
    ids_s, tg_s = eng.shard_batch(ids)
    step, params, opt_state, *_ = _dense_step_fn(TINY, ids)
    ts, m = eng.train_step(ts, ids_s, tg_s, jnp.float32(LR))
    _, _, dense_loss = step(params, opt_state)
    np.testing.assert_allclose(
        float(m["loss_sum"]) / float(m["count"]), float(dense_loss),
        rtol=1e-5,
    )


def test_composed_interleaved_matches_dense_trajectory():
    """Interleaved V=2 (two virtual stages per device, M=4 default)
    follows the dense trajectory."""
    _run_parity("pp2-int2xdp2")


def test_composed_1f1b_fsdp_matches_dense_trajectory():
    """1F1B over the per-parameter fsdp layout: scheduled per-block
    gathers compose with ZeRO-3 sharding and stay exactly dense."""
    _run_parity("pp2-1f1bxfsdp4")
