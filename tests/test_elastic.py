"""Elastic restart tests: fail-fast + resume-from-checkpoint loop
(SURVEY.md §5 failure-detection row)."""

import jax
import numpy as np
import pytest

from distributed_model_parallel_tpu.data.datasets import synthetic
from distributed_model_parallel_tpu.data.loader import Loader
from distributed_model_parallel_tpu.models.tinycnn import tiny_cnn
from distributed_model_parallel_tpu.parallel.data_parallel import (
    DataParallelEngine,
)
from distributed_model_parallel_tpu.runtime.mesh import MeshSpec, make_mesh
from distributed_model_parallel_tpu.training.checkpoint import latest_exists
from distributed_model_parallel_tpu.training.elastic import elastic_fit
from distributed_model_parallel_tpu.training.optim import SGD
from distributed_model_parallel_tpu.training.trainer import (
    Trainer,
    TrainerConfig,
)


class FlakyEngine:
    """Engine wrapper that dies once at a chosen train step — the
    single-controller stand-in for a lost host (whose collective error
    surfaces exactly like this: an exception out of train_step)."""

    def __init__(self, inner, fail_at_call: int):
        self.inner = inner
        self.fail_at_call = fail_at_call
        self.calls = 0
        self.already_failed = False

    def __getattr__(self, name):
        return getattr(self.inner, name)

    def train_step(self, *args):
        self.calls += 1
        if not self.already_failed and self.calls == self.fail_at_call:
            self.already_failed = True
            raise RuntimeError("injected host failure")
        return self.inner.train_step(*args)


def _factory(tmp_path, engine, epochs=4):
    ds = synthetic(num_examples=128, num_classes=4, image_size=8, seed=0)
    trainers = []

    def make_trainer(restart: bool) -> Trainer:
        cfg = TrainerConfig(
            epochs=epochs, base_lr=0.05, t_max=epochs, warmup_period=1,
            print_freq=0,
            log_dir=str(tmp_path / "log"),
            checkpoint_dir=str(tmp_path / "ckpt"),
            resume=restart and latest_exists(str(tmp_path / "ckpt"), "last"),
            save_last=True,
        )
        train = Loader(ds, batch_size=32, shuffle=True, seed=0)
        val = Loader(ds, batch_size=32, shuffle=False)
        t = Trainer(engine, train, val, cfg, rng=jax.random.PRNGKey(0))
        trainers.append(t)
        return t

    return make_trainer, trainers


def test_elastic_restarts_from_last_checkpoint(tmp_path):
    mesh = make_mesh(MeshSpec(data=8))
    engine = FlakyEngine(
        DataParallelEngine(tiny_cnn(4), SGD(), mesh, donate=False),
        fail_at_call=7,  # dies in epoch 1 (4 steps/epoch)
    )
    make_trainer, trainers = _factory(tmp_path, engine)
    result = elastic_fit(make_trainer, max_restarts=2)

    assert len(trainers) == 2                # one restart
    assert trainers[0].start_epoch == 0
    # Epoch 0 completed + save_last ran before the injected failure, so
    # the restart resumes at epoch 1 — at most the failed epoch is lost.
    assert trainers[1].start_epoch == 1
    total_epochs = {h["epoch"] for h in result["history"]}
    assert total_epochs == {1, 2, 3}         # final attempt's epochs
    assert latest_exists(str(tmp_path / "ckpt"), "last")


def test_elastic_gives_up_after_budget(tmp_path):
    class AlwaysDies:
        def __init__(self, inner):
            self.inner = inner

        def __getattr__(self, name):
            return getattr(self.inner, name)

        def train_step(self, *args):
            raise RuntimeError("permanent failure")

    mesh = make_mesh(MeshSpec(data=8))
    engine = AlwaysDies(
        DataParallelEngine(tiny_cnn(4), SGD(), mesh, donate=False)
    )
    make_trainer, trainers = _factory(tmp_path, engine)
    with pytest.raises(RuntimeError, match="permanent failure"):
        elastic_fit(make_trainer, max_restarts=2, backoff_seconds=0.01)
    assert len(trainers) == 3  # initial + 2 restarts, then fail-fast


# ------------------------------------------- backoff schedule + summary


def test_backoff_schedule_exponential_with_cap():
    from distributed_model_parallel_tpu.training.elastic import (
        backoff_schedule,
    )

    assert [
        backoff_schedule(k, 1.0, 60.0) for k in (1, 2, 3, 4)
    ] == [1.0, 2.0, 4.0, 8.0]
    # The cap clamps, never amplifies.
    assert backoff_schedule(10, 1.0, 60.0) == 60.0
    assert backoff_schedule(1, 5.0, 2.0) == 2.0
    with pytest.raises(ValueError):
        backoff_schedule(0, 1.0, 60.0)


class _DiesNTimes:
    """Trainer stand-in: fit() raises `exc` the first `n` calls, then
    returns a minimal summary — no engine/mesh needed to test the
    supervisor's schedule."""

    def __init__(self, n, exc=RuntimeError):
        self.n = n
        self.exc = exc

    def fit(self):
        if self.n > 0:
            self.n -= 1
            raise self.exc(f"boom ({self.n} left)")
        return {"best_acc": 0.0, "history": []}


def test_elastic_backoff_sleeps_and_summary(monkeypatch):
    from distributed_model_parallel_tpu.training import elastic

    sleeps = []
    monkeypatch.setattr(elastic.time, "sleep", sleeps.append)
    box = _DiesNTimes(3, ValueError)
    result = elastic.elastic_fit(
        lambda resume: box,
        max_restarts=3,
        backoff_seconds=0.5,
        max_backoff_seconds=1.5,
        jitter=lambda attempt: 0.01 * attempt,
    )
    # Exponential 0.5, 1.0, then capped at 1.5 — plus the jitter hook.
    assert sleeps == pytest.approx([0.51, 1.02, 1.53])
    el = result["elastic"]
    assert el["attempts"] == 4
    assert [r["error_type"] for r in el["restarts"]] == ["ValueError"] * 3
    assert [r["attempt"] for r in el["restarts"]] == [1, 2, 3]
    assert [r["backoff_s"] for r in el["restarts"]] == pytest.approx(
        [0.51, 1.02, 1.53]
    )


def test_elastic_retry_on_narrowing(monkeypatch):
    """retry_on=(TypeError,) must NOT absorb a ValueError — it
    propagates immediately, zero restarts."""
    from distributed_model_parallel_tpu.training import elastic

    sleeps = []
    monkeypatch.setattr(elastic.time, "sleep", sleeps.append)
    calls = []

    def make_trainer(resume):
        calls.append(resume)
        return _DiesNTimes(5, ValueError)

    with pytest.raises(ValueError, match="boom"):
        elastic.elastic_fit(
            make_trainer, max_restarts=3, retry_on=(TypeError,),
        )
    assert calls == [False] and sleeps == []
    # ... while a matching type does retry.
    calls.clear()
    box = _DiesNTimes(1, TypeError)
    result = elastic.elastic_fit(
        lambda resume: (calls.append(resume), box)[1],
        max_restarts=3, retry_on=(TypeError,), backoff_seconds=0.0,
    )
    assert calls == [False, True]
    assert result["elastic"]["restarts"][0]["error_type"] == "TypeError"


# ----------------------------------------------------- elastic resize


def test_elastic_resize_restores_sharded_checkpoint_onto_bigger_mesh(
    tmp_path,
):
    """Genuine elasticity: an S=4 FSDP run dies after its first epoch's
    sharded save; the restart's `make_trainer(resume, topology)`
    receives the manifest's saved topology (data=4) and rebuilds onto
    the FULL 8-device mesh — the resharding restore places the state
    bit-exact (acceptance: S=4 -> S=8 through elastic_fit's resize
    path)."""
    from distributed_model_parallel_tpu.checkpointing import (
        restore_checkpoint,
    )
    from distributed_model_parallel_tpu.parallel.fsdp import FSDPEngine

    ds = synthetic(num_examples=128, num_classes=4, image_size=8, seed=0)
    ckdir = str(tmp_path / "ckpt")
    devs = jax.devices()
    topologies = []
    trainers = []
    restored_canonicals = []

    def build_engine(n_data):
        mesh = make_mesh(MeshSpec(data=n_data), devices=devs[:n_data])
        inner = FSDPEngine(
            tiny_cnn(4), SGD(), mesh, donate=False, min_shard_elems=64
        )
        return inner

    def make_trainer(restart, topology):
        topologies.append(topology)
        if not restart:
            engine = FlakyEngine(
                build_engine(4), fail_at_call=7,  # dies in epoch 1
            )
        else:
            # The preempted slice came back bigger: resize to all 8
            # devices; the restore reshards the S=4 state to fit.
            assert topology is not None
            assert topology["mesh_axes"]["data"] == 4
            engine = build_engine(8)
        cfg = TrainerConfig(
            epochs=3, base_lr=0.05, t_max=3, warmup_period=1,
            print_freq=0,
            log_dir=str(tmp_path / "log"),
            checkpoint_dir=ckdir,
            resume=restart and latest_exists(ckdir, "last"),
            save_last=True,
            checkpoint_format="sharded",
        )
        train = Loader(ds, batch_size=32, shuffle=True, seed=0)
        val = Loader(ds, batch_size=32, shuffle=False)
        t = Trainer(engine, train, val, cfg, rng=jax.random.PRNGKey(0))
        trainers.append(t)
        if restart:
            # Bit-exact reshard through the elastic path, checked at
            # restart time (before this trainer overwrites 'last' with
            # later epochs): what the S=8 trainer starts from equals
            # the S=4 checkpoint on disk, reassembled independently.
            started_from = jax.tree_util.tree_map(
                lambda x: np.asarray(x),
                jax.device_get(t._to_canonical(t.state)),
            )
            expected, _, _ = restore_checkpoint(
                ckdir, started_from, name="last"
            )
            for a, b in zip(
                jax.tree_util.tree_leaves(expected),
                jax.tree_util.tree_leaves(started_from),
            ):
                np.testing.assert_array_equal(np.asarray(a), np.asarray(b))
            restored_canonicals.append(started_from)
        return t

    result = elastic_fit(
        make_trainer, max_restarts=2, backoff_seconds=0.01,
        checkpoint_dir=ckdir,
    )
    assert len(trainers) == 2
    assert topologies[0] is None  # first attempt: nothing saved yet
    assert trainers[1].start_epoch == 1  # lost at most the failed epoch
    assert {h["epoch"] for h in result["history"]} == {1, 2}
    assert result["elastic"]["restarts"][0]["error_type"] == "RuntimeError"
    assert restored_canonicals, "restart never verified the reshard"
