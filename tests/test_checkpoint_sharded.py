"""Sharded / async / resharding checkpoint tests (`checkpointing/`).

Covers the ISSUE 8 acceptance contracts:
* sharded save reaches NO cross-process gather (process_allgather and
  the legacy canonical gather are monkeypatch-poisoned);
* an S=4 FSDP checkpoint restores BIT-EXACT onto S=8, S=2 and a
  hybrid 2×2 dcn×ici mesh, and a TP checkpoint reshards likewise;
* async save: the step path is not blocked on file I/O (timed, with an
  artificially slow writer), a mid-write crash leaves the previous
  manifest restorable, and write errors surface — never silently;
* legacy `.npz` checkpoints stay restorable behind the same unified
  `restore_checkpoint` signature;
* the truncated-archive regression for `training/checkpoint.py`
  (corrupt reads route through the placeholder+agree path).
"""

import os
import time

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from distributed_model_parallel_tpu.checkpointing import (
    load_manifest,
    manifest_exists,
    restore_checkpoint,
    restore_subtree,
    save_sharded,
    saved_topology,
    AsyncCheckpointer,
)
from distributed_model_parallel_tpu.checkpointing import writer as writer_mod
from distributed_model_parallel_tpu.models.tinycnn import tiny_cnn
from distributed_model_parallel_tpu.parallel.fsdp import FSDPEngine
from distributed_model_parallel_tpu.runtime.mesh import (
    MeshSpec,
    make_mesh,
    mesh_axes,
    spec_from_axes,
)
from distributed_model_parallel_tpu.training.optim import SGD
from distributed_model_parallel_tpu.training import checkpoint as legacy


def _fsdp_engine(n, devices=None, dcn=1):
    mesh = make_mesh(
        MeshSpec(data=n, dcn=dcn),
        devices=devices if devices is not None else jax.devices()[:n],
    )
    return FSDPEngine(
        tiny_cnn(4), SGD(), mesh, donate=False, min_shard_elems=64
    )


def _host_tree(tree):
    return jax.tree_util.tree_map(
        lambda x: np.asarray(x), jax.device_get(tree)
    )


def _assert_trees_equal(a, b):
    la = jax.tree_util.tree_leaves(a)
    lb = jax.tree_util.tree_leaves(b)
    assert len(la) == len(lb)
    for x, y in zip(la, lb):
        np.testing.assert_array_equal(np.asarray(x), np.asarray(y))


# --------------------------------------------------------- sharded save


def test_sharded_save_writes_manifest_and_shards(tmp_path):
    eng = _fsdp_engine(4)
    state = eng.init_state(jax.random.PRNGKey(0))
    path = save_sharded(str(tmp_path), state, acc=91.25, epoch=7)
    assert os.path.isfile(path)
    m = load_manifest(str(tmp_path))
    assert m.acc == pytest.approx(91.25) and m.epoch == 7
    assert m.mesh_axes["data"] == 4
    # Every leaf's chunks tile its global shape exactly once.
    for key, rec in m.leaves.items():
        covered = np.zeros(rec.shape, np.int32)
        for ch in rec.chunks:
            region = tuple(
                slice(s, s + n) for s, n in zip(ch.start, ch.shape)
            )
            covered[region] += 1
        assert (covered == 1).all(), f"{key} not tiled exactly once"
    # Spec recorded for the FSDP-sharded leaves (largest divisible dim
    # over the data axes) and replicated for the step counter.
    assert m.leaves["step"].spec == []
    sharded_specs = [
        rec.spec for rec in m.leaves.values()
        if any(e is not None for e in rec.spec)
    ]
    assert sharded_specs, "no leaf recorded a sharded PartitionSpec"


def test_sharded_save_never_gathers(tmp_path, monkeypatch):
    """The acceptance pin: NO cross-process all-gather of sharded
    leaves on the sharded save path — both the legacy per-leaf
    process_allgather and the canonical-form gather are poisoned."""
    from jax.experimental import multihost_utils

    def boom(*a, **k):
        raise AssertionError(
            "process_allgather reached on the sharded save path"
        )

    monkeypatch.setattr(multihost_utils, "process_allgather", boom)
    monkeypatch.setattr(legacy, "tree_to_host", boom)
    monkeypatch.setattr(legacy, "_host_leaf", boom)
    eng = _fsdp_engine(4)
    state = eng.init_state(jax.random.PRNGKey(0))
    save_sharded(str(tmp_path), state, acc=0.0, epoch=0)
    # ... and the round trip still restores bit-exact.
    template = _host_tree(state)
    restored, _, _ = restore_checkpoint(str(tmp_path), template)
    _assert_trees_equal(template, restored)


# --------------------------------------------------- resharding restore


@pytest.mark.parametrize("target", ["S2", "S8", "hybrid2x2"])
def test_fsdp_reshard_restore_bit_exact(tmp_path, target):
    """S=4 FSDP checkpoint -> S=2 / S=8 / hybrid 2×(2) dcn×ici mesh,
    restored TrainState == canonical source at rtol 0 (exact bytes)."""
    src_eng = _fsdp_engine(4)
    state = src_eng.init_state(jax.random.PRNGKey(0))
    save_sharded(str(tmp_path), state, acc=1.0, epoch=2)
    if target == "S2":
        dst_eng = _fsdp_engine(2)
    elif target == "S8":
        dst_eng = _fsdp_engine(8)
    else:
        dst_eng = _fsdp_engine(4, dcn=2)
    template = _host_tree(dst_eng.init_state(jax.random.PRNGKey(1)))
    restored, acc, epoch = restore_checkpoint(str(tmp_path), template)
    assert acc == pytest.approx(1.0) and epoch == 2
    placed = dst_eng.from_canonical(restored)
    _assert_trees_equal(_host_tree(state), _host_tree(placed))


def test_fsdp_reshard_post_restore_trajectory_twin(tmp_path):
    """3-step post-restore trajectory at S=8 == the same 3 steps from
    the un-checkpointed state placed at S=8 directly — the checkpoint
    round trip adds exactly nothing."""
    rng = np.random.RandomState(0)
    batches = [
        (
            rng.rand(16, 8, 8, 3).astype(np.float32),
            rng.randint(0, 4, size=(16,)).astype(np.int32),
        )
        for _ in range(3)
    ]
    src_eng = _fsdp_engine(4)
    state = src_eng.init_state(jax.random.PRNGKey(0))
    save_sharded(str(tmp_path), state, acc=0.0, epoch=0)

    def three_steps(eng, start):
        s = start
        for imgs, lbls in batches:
            ib, lb = eng.shard_batch(imgs, lbls)
            s, _ = eng.train_step(s, ib, lb, jnp.float32(0.05))
        return _host_tree(s)

    dst_eng = _fsdp_engine(8)
    # Reference: the canonical source placed directly (no file round
    # trip) onto the S=8 mesh.
    ref = three_steps(dst_eng, dst_eng.from_canonical(_host_tree(state)))
    template = _host_tree(dst_eng.init_state(jax.random.PRNGKey(1)))
    restored, _, _ = restore_checkpoint(str(tmp_path), template)
    got = three_steps(dst_eng, dst_eng.from_canonical(restored))
    _assert_trees_equal(ref, got)


def test_tp_reshard_restore_bit_exact(tmp_path):
    """Megatron-sharded (TP) state saved at model=4 restores exactly at
    model=2 through the same manifest path."""
    from distributed_model_parallel_tpu.models.bert import (
        BertConfig,
        bert_for_classification,
    )
    from distributed_model_parallel_tpu.parallel.tensor_parallel import (
        TensorParallelEngine,
    )

    model = bert_for_classification(
        4,
        BertConfig(
            vocab_size=64, hidden_size=16, num_layers=1, num_heads=4,
            intermediate_size=32, max_position=8, dropout_rate=0.0,
        ),
    )
    devs = jax.devices()
    eng4 = TensorParallelEngine(
        model, SGD(), make_mesh(MeshSpec(data=1, model=4),
                                devices=devs[:4]),
        donate=False,
    )
    state = eng4.init_state(jax.random.PRNGKey(0))
    save_sharded(str(tmp_path), state, acc=0.0, epoch=0)
    m = load_manifest(str(tmp_path))
    assert m.mesh_axes["model"] == 4
    eng2 = TensorParallelEngine(
        model, SGD(), make_mesh(MeshSpec(data=1, model=2),
                                devices=devs[:2]),
        donate=False,
    )
    template = _host_tree(eng2.init_state(jax.random.PRNGKey(1)))
    restored, _, _ = restore_checkpoint(str(tmp_path), template)
    placed = eng2.from_canonical(restored)
    _assert_trees_equal(_host_tree(state), _host_tree(placed))


def test_cross_plan_reshard_pp2xsp2_to_fsdp4_and_back(tmp_path):
    """Cross-PLAN resharding (ISSUE 19): GPT LM state saved under the
    composed pp2 x sp2 plan restores BIT-EXACT under the 4-way FSDP
    plan — whose params/moments live 1/4 over 'data' — and a save
    from the fsdp side round-trips back onto the pp2xsp2 mesh, all
    through the same manifest seams (`state_partition_specs` +
    to/from_canonical) the single-axis engines use."""
    from distributed_model_parallel_tpu.models.gpt import GPTConfig
    from distributed_model_parallel_tpu.parallel.plan import (
        build_plan_engine,
    )

    cfg = GPTConfig(
        vocab_size=61, dim=16, num_layers=4, num_heads=2, ffn_dim=32,
        max_position=16, dropout_rate=0.0,
    )
    src = build_plan_engine(cfg, SGD(), "pp2xsp2", donate=False)
    dst = build_plan_engine(cfg, SGD(), "fsdp4", donate=False)
    state = src.init_state(jax.random.PRNGKey(0))
    d_a = os.path.join(str(tmp_path), "a")
    save_sharded(d_a, src.to_canonical_sharded(state), acc=3.0, epoch=1)
    m = load_manifest(d_a)
    assert m.mesh_axes["stage"] == 2 and m.mesh_axes["seq"] == 2
    template = _host_tree(dst.init_state(jax.random.PRNGKey(1)))
    restored, acc, epoch = restore_checkpoint(d_a, template)
    assert acc == pytest.approx(3.0) and epoch == 1
    placed = dst.from_canonical(restored)
    _assert_trees_equal(_host_tree(state), _host_tree(placed))
    # ... and back: the fsdp-sharded leaves reassemble through the
    # manifest's spec records onto the composed pp2xsp2 mesh.
    d_b = os.path.join(str(tmp_path), "b")
    save_sharded(d_b, dst.to_canonical_sharded(placed), acc=4.0,
                 epoch=2)
    m2 = load_manifest(d_b)
    assert m2.mesh_axes["data"] == 4
    template2 = _host_tree(src.init_state(jax.random.PRNGKey(2)))
    back, _, _ = restore_checkpoint(d_b, template2)
    replaced = src.from_canonical(back)
    _assert_trees_equal(_host_tree(state), _host_tree(replaced))
    # the round-tripped state still TRAINS under the destination plan
    rng = np.random.RandomState(0)
    ids = rng.randint(1, 61, size=(8, 16)).astype(np.int32)
    ids_s, tg_s = src.shard_batch(ids)
    st2, metrics = src.train_step(replaced, ids_s, tg_s,
                                  jnp.float32(0.1))
    assert np.isfinite(float(metrics["loss_sum"]))


def test_cross_plan_reshard_covers_schedule_changes(tmp_path):
    """Cross-plan resharding over a SCHEDULE change (ISSUE 20): state
    saved under the 1F1B-scheduled `pp2-1f1b-xsp2` plan restores
    BIT-EXACT under the gpipe `pp2xdp4` plan and round-trips back —
    the schedule is execution-only and never serialized into the
    layouts, so the scheduled save's manifest is byte-free of any
    schedule record and restores through the same canonical seam."""
    import glob
    import json

    from distributed_model_parallel_tpu.models.gpt import GPTConfig
    from distributed_model_parallel_tpu.parallel.plan import (
        build_plan_engine,
    )

    cfg = GPTConfig(
        vocab_size=61, dim=16, num_layers=4, num_heads=2, ffn_dim=32,
        max_position=16, dropout_rate=0.0,
    )
    src = build_plan_engine(cfg, SGD(), "pp2-1f1b-xsp2", donate=False)
    dst = build_plan_engine(cfg, SGD(), "pp2xdp4", donate=False)
    state = src.init_state(jax.random.PRNGKey(0))
    d_a = os.path.join(str(tmp_path), "a")
    save_sharded(d_a, src.to_canonical_sharded(state), acc=3.0, epoch=1)
    # The schedule never reaches the serialized layouts: the manifest
    # records meshes and per-leaf specs only, so the scheduled plan's
    # checkpoint is indistinguishable from its gpipe twin's.
    (mpath,) = glob.glob(os.path.join(d_a, "*.manifest.json"))
    mtext = open(mpath).read()
    assert "1f1b" not in mtext and "schedule" not in mtext
    json.loads(mtext)  # stays a valid manifest
    m = load_manifest(d_a)
    assert m.mesh_axes["stage"] == 2 and m.mesh_axes["seq"] == 2
    template = _host_tree(dst.init_state(jax.random.PRNGKey(1)))
    restored, acc, epoch = restore_checkpoint(d_a, template)
    assert acc == pytest.approx(3.0) and epoch == 1
    placed = dst.from_canonical(restored)
    _assert_trees_equal(_host_tree(state), _host_tree(placed))
    # ... and back through the canonical seam onto the scheduled plan.
    d_b = os.path.join(str(tmp_path), "b")
    save_sharded(d_b, dst.to_canonical_sharded(placed), acc=4.0,
                 epoch=2)
    template2 = _host_tree(src.init_state(jax.random.PRNGKey(2)))
    back, _, _ = restore_checkpoint(d_b, template2)
    replaced = src.from_canonical(back)
    _assert_trees_equal(_host_tree(state), _host_tree(replaced))
    # the restored state still trains under the 1F1B tick program
    rng = np.random.RandomState(0)
    ids = rng.randint(1, 61, size=(8, 16)).astype(np.int32)
    ids_s, tg_s = src.shard_batch(ids)
    st2, metrics = src.train_step(replaced, ids_s, tg_s,
                                  jnp.float32(0.1))
    assert np.isfinite(float(metrics["loss_sum"]))


def test_manifest_specs_match_engine_partition_specs(tmp_path):
    """The manifest records each leaf's PartitionSpec from the LIVE
    arrays; the engine declares its layout through the
    `state_partition_specs` seam — the two must agree, or the manifest
    is describing a layout nobody runs (layout-aware tooling reads the
    manifest, the partitioner reads the engine)."""
    from jax.sharding import PartitionSpec as P

    from distributed_model_parallel_tpu.checkpointing.manifest import (
        spec_to_json,
    )
    from distributed_model_parallel_tpu.training.checkpoint import (
        _path_str,
    )

    def norm(entries):
        # 'x' and ['x'] spell the same single-axis entry; trailing
        # replicated dims are spelling too.
        out = [
            [e] if isinstance(e, str) else (e or None)
            for e in entries
        ]
        while out and out[-1] is None:
            out.pop()
        return out

    eng = _fsdp_engine(4)
    state = eng.init_state(jax.random.PRNGKey(0))
    save_sharded(str(tmp_path), state, acc=0.0, epoch=0)
    m = load_manifest(str(tmp_path))
    declared = {
        _path_str(path): spec_to_json(spec)
        for path, spec in jax.tree_util.tree_flatten_with_path(
            eng.state_partition_specs(),
            is_leaf=lambda x: isinstance(x, P),
        )[0]
    }
    assert set(declared) == set(m.leaves)
    for key, rec in m.leaves.items():
        assert norm(rec.spec) == norm(declared[key]), key


def test_saved_topology_and_spec_roundtrip(tmp_path):
    eng = _fsdp_engine(4, dcn=2)
    state = eng.init_state(jax.random.PRNGKey(0))
    save_sharded(str(tmp_path), state, acc=0.0, epoch=5)
    topo = saved_topology(str(tmp_path))
    assert topo["epoch"] == 5 and topo["format"] == "sharded"
    assert topo["mesh_axes"]["dcn"] == 2 and topo["mesh_axes"]["ici"] == 2
    # mesh_axes -> MeshSpec -> mesh reproduces the factorization.
    spec = spec_from_axes(topo["mesh_axes"])
    mesh = make_mesh(spec, devices=jax.devices()[:4])
    assert mesh_axes(mesh) == topo["mesh_axes"]
    # Legacy checkpoints record no topology.
    assert saved_topology(str(tmp_path), "nope") is None


def test_restore_subtree_params_only(tmp_path):
    eng = _fsdp_engine(4)
    state = eng.init_state(jax.random.PRNGKey(0))
    save_sharded(
        str(tmp_path), state, acc=3.0, epoch=1,
        extra={"gpt_config": {"dim": 16}},
    )
    host = _host_tree(state)
    params, meta = restore_subtree(str(tmp_path), host.params)
    _assert_trees_equal(host.params, params)
    assert meta["gpt_config"]["dim"] == 16 and meta["format"] == "sharded"
    # Shape mismatches fail fast naming the leaf.
    bad = jax.tree_util.tree_map(
        lambda x: np.zeros(x.shape + (2,), x.dtype), host.params
    )
    with pytest.raises(ValueError, match="has shape"):
        restore_subtree(str(tmp_path), bad)


# ------------------------------------------------------------ async save


def _slow_writer(monkeypatch, delay_s, record=None):
    real = writer_mod._write_shard

    def slow(path, arrays):
        time.sleep(delay_s)
        real(path, arrays)
        if record is not None:
            record.append(path)

    monkeypatch.setattr(writer_mod, "_write_shard", slow)


def test_async_save_does_not_block_next_step(tmp_path, monkeypatch):
    """Train step N+1 must run while save N's file I/O is still in
    flight: with a 1.5 s artificial writer delay, the save call returns
    and a full train step completes well inside the delay window."""
    eng = _fsdp_engine(4)
    state = eng.init_state(jax.random.PRNGKey(0))
    imgs = np.random.RandomState(0).rand(8, 8, 8, 3).astype(np.float32)
    lbls = np.zeros((8,), np.int32)
    ib, lb = eng.shard_batch(imgs, lbls)
    # Compile + warm the step OUTSIDE the timed window.
    warm, _ = eng.train_step(state, ib, lb, jnp.float32(0.05))
    jax.block_until_ready(warm)

    delay = 1.5
    _slow_writer(monkeypatch, delay)
    writer = AsyncCheckpointer()
    t0 = time.perf_counter()
    handle = save_sharded(
        str(tmp_path), state, acc=0.0, epoch=0, writer=writer
    )
    new_state, _ = eng.train_step(state, ib, lb, jnp.float32(0.05))
    jax.block_until_ready(new_state)
    stepped_at = time.perf_counter() - t0
    assert not handle.done(), (
        "slow write finished before the next step — the timing "
        "assertion below would be vacuous"
    )
    assert stepped_at < delay, (
        f"step N+1 took {stepped_at:.2f}s from save start — blocked on "
        f"the {delay}s writer"
    )
    writer.wait()
    assert handle.done() and manifest_exists(str(tmp_path))
    template = _host_tree(state)
    restored, _, _ = restore_checkpoint(str(tmp_path), template)
    _assert_trees_equal(template, restored)


def test_back_to_back_async_saves_get_distinct_save_ids(
    tmp_path, monkeypatch
):
    """A save snapshotted while its predecessor is STILL WRITING must
    not reuse the predecessor's save-id (the manifest on disk doesn't
    know about in-flight saves) — shard-filename uniqueness is what the
    crash discipline rests on."""
    eng = _fsdp_engine(4)
    s0 = eng.init_state(jax.random.PRNGKey(0))
    s1 = eng.init_state(jax.random.PRNGKey(1))
    _slow_writer(monkeypatch, 0.3)
    writer = AsyncCheckpointer()
    h0 = save_sharded(str(tmp_path), s0, acc=0.0, epoch=0, writer=writer)
    assert not h0.done()  # predecessor in flight while we snapshot
    save_sharded(str(tmp_path), s1, acc=0.0, epoch=1, writer=writer)
    writer.wait()
    m = load_manifest(str(tmp_path))
    assert m.save_id == 1 and m.epoch == 1
    restored, _, epoch = restore_checkpoint(
        str(tmp_path), _host_tree(s1)
    )
    assert epoch == 1
    _assert_trees_equal(_host_tree(s1), restored)


def test_mid_write_crash_preserves_previous_checkpoint(
    tmp_path, monkeypatch
):
    """A crash mid-write of save N+1 leaves save N fully restorable:
    shard files carry per-save ids and the manifest commits last."""
    eng = _fsdp_engine(4)
    s0 = eng.init_state(jax.random.PRNGKey(0))
    s1 = eng.init_state(jax.random.PRNGKey(7))
    save_sharded(str(tmp_path), s0, acc=10.0, epoch=0)

    real = writer_mod._write_shard

    def crashing(path, arrays):
        # Tear realistically: leave a partial tmp behind, then die
        # before the rename.
        with open(path + ".tmp", "wb") as f:
            f.write(b"\x00" * 128)
        raise RuntimeError("disk went away mid-write")

    monkeypatch.setattr(writer_mod, "_write_shard", crashing)
    with pytest.raises(RuntimeError, match="disk went away"):
        save_sharded(str(tmp_path), s1, acc=20.0, epoch=1)
    monkeypatch.setattr(writer_mod, "_write_shard", real)

    template = _host_tree(s0)
    restored, acc, epoch = restore_checkpoint(str(tmp_path), template)
    assert acc == pytest.approx(10.0) and epoch == 0
    _assert_trees_equal(template, restored)


def test_async_write_error_surfaces_at_next_save(tmp_path, monkeypatch):
    """Writer failures are NEVER silent: the next save (via
    `AsyncCheckpointer.check`) or `wait()` re-raises them."""
    eng = _fsdp_engine(4)
    state = eng.init_state(jax.random.PRNGKey(0))

    def crashing(path, arrays):
        raise OSError("quota exceeded")

    monkeypatch.setattr(writer_mod, "_write_shard", crashing)
    writer = AsyncCheckpointer()
    handle = save_sharded(
        str(tmp_path), state, acc=0.0, epoch=0, writer=writer
    )
    with pytest.raises(OSError, match="quota exceeded"):
        handle.wait(timeout=30)
    # The next save's pre-flight check re-raises the stored failure.
    with pytest.raises(OSError, match="quota exceeded"):
        writer.check()
    # ... exactly once; wait() after surfacing is clean.
    writer.wait()


def test_trainer_rejects_sharded_for_restructuring_engines(tmp_path):
    """An engine whose canonical form RESTRUCTURES state (to_canonical
    without the to_canonical_sharded seam) cannot be written
    shard-for-shard — the trainer says so instead of writing a
    checkpoint whose tree paths no other topology could read."""
    from distributed_model_parallel_tpu.data.datasets import synthetic
    from distributed_model_parallel_tpu.data.loader import Loader
    from distributed_model_parallel_tpu.parallel.data_parallel import (
        DataParallelEngine,
    )
    from distributed_model_parallel_tpu.training.trainer import (
        Trainer,
        TrainerConfig,
    )

    class PackedEngine:
        """Stand-in for the pipeline engines' stage-local packing."""

        def __init__(self, inner):
            self.inner = inner

        def __getattr__(self, name):
            if name == "to_canonical_sharded":
                raise AttributeError(name)
            return getattr(self.inner, name)

        def to_canonical(self, ts):
            return ts

    mesh = make_mesh(MeshSpec(data=8))
    engine = PackedEngine(
        DataParallelEngine(tiny_cnn(4), SGD(), mesh, donate=False)
    )
    ds = synthetic(num_examples=32, num_classes=4, image_size=8, seed=0)
    cfg = TrainerConfig(
        epochs=1, print_freq=0, checkpoint_dir=str(tmp_path),
        checkpoint_format="sharded", save_best=False, save_last=True,
    )
    t = Trainer(
        engine, Loader(ds, batch_size=32), None, cfg,
        rng=jax.random.PRNGKey(0),
    )
    with pytest.raises(ValueError, match="to_canonical_sharded"):
        t._checkpoint_payload()


# ------------------------------------------------- legacy interop + S1


def test_legacy_npz_restores_through_unified_reader(tmp_path):
    """Old-format checkpoints keep working behind the same
    `restore_checkpoint` signature (acceptance: legacy unchanged)."""
    eng = _fsdp_engine(4)
    state = eng.init_state(jax.random.PRNGKey(0))
    canonical = eng.to_canonical(state)
    legacy.save_checkpoint(
        str(tmp_path), canonical, acc=55.5, epoch=9
    )
    assert not manifest_exists(str(tmp_path))
    restored, acc, epoch = restore_checkpoint(
        str(tmp_path), _host_tree(state)
    )
    assert acc == pytest.approx(55.5) and epoch == 9
    _assert_trees_equal(_host_tree(state), restored)


def _truncate(path):
    size = os.path.getsize(path)
    with open(path, "r+b") as f:
        f.truncate(size // 2)


def test_truncated_archive_raises_single_process(tmp_path):
    """S1 regression: a truncated `.npz` fails the restore loudly (the
    captured error re-raises after the agreement step) instead of
    silently returning placeholder zeros."""
    eng = _fsdp_engine(4)
    state = eng.init_state(jax.random.PRNGKey(0))
    canonical = eng.to_canonical(state)
    npz = legacy.save_checkpoint(str(tmp_path), canonical, acc=1, epoch=0)
    _truncate(npz)
    with pytest.raises(Exception):
        legacy.restore_checkpoint(str(tmp_path), _host_tree(state))


def test_truncated_archive_nonzero_host_uses_placeholder_path(
    tmp_path, monkeypatch
):
    """S1 regression, simulated non-zero host: a corrupt archive on a
    host that shares the filesystem must route through the SAME
    placeholder+agree path as a host without the file — reaching the
    broadcast (host 0 deadlocks if it doesn't) and adopting host-0's
    verdict rather than raising one-sidedly."""
    eng = _fsdp_engine(4)
    state = eng.init_state(jax.random.PRNGKey(0))
    canonical = eng.to_canonical(state)
    npz = legacy.save_checkpoint(str(tmp_path), canonical, acc=1, epoch=0)
    _truncate(npz)

    from jax.experimental import multihost_utils

    broadcasts = []

    def fake_broadcast(x):
        # Host-0 succeeded in this scenario: the ok flag it would
        # broadcast is 1; the state tuple passes through (host 0's
        # payload has identical structure).
        broadcasts.append(x)
        if len(broadcasts) == 1:
            return np.int32(1)
        return x

    monkeypatch.setattr(jax, "process_index", lambda: 1)
    monkeypatch.setattr(jax, "process_count", lambda: 2)
    monkeypatch.setattr(
        multihost_utils, "broadcast_one_to_all", fake_broadcast
    )
    template = _host_tree(state)
    restored, acc, epoch = legacy.restore_checkpoint(
        str(tmp_path), template
    )
    # Reached BOTH broadcasts (agreement then payload) without raising;
    # the local corrupt read was discarded for placeholders.
    assert len(broadcasts) == 2
    for leaf in jax.tree_util.tree_leaves(restored):
        assert not np.any(np.asarray(leaf))


def test_checkpoint_epoch_reads_manifest(tmp_path):
    eng = _fsdp_engine(4)
    state = eng.init_state(jax.random.PRNGKey(0))
    save_sharded(str(tmp_path), state, acc=0.0, epoch=11, name="last")
    assert legacy.latest_exists(str(tmp_path), "last")
    assert legacy.checkpoint_epoch(str(tmp_path), "last") == 11
    assert legacy.checkpoint_epoch(str(tmp_path), "ckpt") is None


def test_successive_saves_gc_stale_shards(tmp_path):
    eng = _fsdp_engine(4)
    s0 = eng.init_state(jax.random.PRNGKey(0))
    s1 = eng.init_state(jax.random.PRNGKey(1))
    save_sharded(str(tmp_path), s0, acc=0.0, epoch=0)
    save_sharded(str(tmp_path), s1, acc=0.0, epoch=1)
    shards = [
        f for f in os.listdir(str(tmp_path)) if ".shard" in f
    ]
    # Only the committed save's shard files remain.
    assert shards and all(".s1." in f for f in shards)
    restored, _, epoch = restore_checkpoint(
        str(tmp_path), _host_tree(s1)
    )
    assert epoch == 1
    _assert_trees_equal(_host_tree(s1), restored)
