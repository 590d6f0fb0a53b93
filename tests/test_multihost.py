"""REAL multi-host integration: two OS processes form a jax.distributed
CPU cluster (4 virtual devices each -> one 8-device global mesh) and
train in lockstep — the per-host input sharding
(`make_array_from_process_local_data`), cross-process collectives, and
the host-0-writes / all-hosts-broadcast checkpoint protocol all execute
for real, not on a simulated mesh.

This is the test the reference cannot have (its multi-node story was
'assume 2-4 local GPUs and localhost TCP', never tested — SURVEY.md §4).
"""

import os
import socket
import subprocess
import sys

import pytest

_WORKER = r"""
import os, sys
proc_id = int(sys.argv[1]); nproc = int(sys.argv[2]); port = sys.argv[3]
ckpt_dir = sys.argv[4]
import jax
jax.config.update("jax_platforms", "cpu")
jax.distributed.initialize(
    f"127.0.0.1:{port}", num_processes=nproc, process_id=proc_id
)
import numpy as np
import jax.numpy as jnp
# repo root arrives via PYTHONPATH from the spawning test
from distributed_model_parallel_tpu.runtime.mesh import MeshSpec, make_mesh
from distributed_model_parallel_tpu.parallel.data_parallel import DDPEngine
from distributed_model_parallel_tpu.models.tinycnn import tiny_cnn
from distributed_model_parallel_tpu.training.checkpoint import (
    restore_checkpoint, save_checkpoint,
)
from distributed_model_parallel_tpu.training.optim import SGD

assert jax.process_count() == nproc, jax.process_count()
assert len(jax.devices()) == 4 * nproc

mesh = make_mesh(MeshSpec(data=-1))
eng = DDPEngine(tiny_cnn(10), SGD(), mesh, donate=False)
ts = eng.init_state(jax.random.PRNGKey(0))
rng = np.random.RandomState(proc_id)  # DIFFERENT local shard per host
x = rng.rand(8, 8, 8, 3).astype(np.float32)
y = rng.randint(0, 10, size=(8,)).astype(np.int32)
xs, ys = eng.shard_batch(x, y)  # multi-host path: process-local data
losses = []
for _ in range(2):
    ts, m = eng.train_step(ts, xs, ys, jnp.float32(0.05))
    losses.append(float(m["loss_sum"]))

# host-0 writes; every host calls (the non-0 call is a no-op)
save_checkpoint(ckpt_dir, ts, acc=55.5, epoch=3)
template = eng.init_state(jax.random.PRNGKey(9))
restored, acc, epoch = restore_checkpoint(ckpt_dir, template)
assert (acc, epoch) == (55.5, 3), (acc, epoch)
ts2, m2 = eng.train_step(restored, xs, ys, jnp.float32(0.05))
ts1, m1 = eng.train_step(ts, xs, ys, jnp.float32(0.05))
assert abs(float(m2["loss_sum"]) - float(m1["loss_sum"])) < 1e-4

# ---- sharded-engine (ZeRO-3) checkpoint across the REAL cluster ------
# FSDP leaves span both processes (not fully addressable), the exact
# deployment where a bare device_get checkpoint crashes; the
# canonical path must all-gather, save on host 0,
# broadcast-restore, re-shard, and continue identically.
from distributed_model_parallel_tpu.parallel.fsdp import FSDPEngine

feng = FSDPEngine(tiny_cnn(10), SGD(), mesh, donate=False,
                  min_shard_elems=16)
fts = feng.init_state(jax.random.PRNGKey(1))
big = max(jax.tree_util.tree_leaves(fts.params), key=lambda l: l.size)
assert not big.is_fully_addressable  # the crash precondition is REAL
fxs, fys = feng.shard_batch(x, y)
for _ in range(2):
    fts, _ = feng.train_step(fts, fxs, fys, jnp.float32(0.05))
canon = feng.to_canonical(fts)       # collective: every process calls
save_checkpoint(ckpt_dir + "_fsdp", canon, acc=11.25, epoch=4)
template = feng.to_canonical(feng.init_state(jax.random.PRNGKey(7)))
frestored, facc, fepoch = restore_checkpoint(ckpt_dir + "_fsdp", template)
assert (facc, fepoch) == (11.25, 4), (facc, fepoch)
fts2 = feng.from_canonical(frestored)
ra, ma = feng.train_step(fts2, fxs, fys, jnp.float32(0.05))
rb, mb = feng.train_step(fts, fxs, fys, jnp.float32(0.05))
assert abs(float(ma["loss_sum"]) - float(mb["loss_sum"])) < 1e-4, (
    float(ma["loss_sum"]), float(mb["loss_sum"]))

# GLOBAL metric sums must agree bit-for-bit across hosts
print(f"RESULT {proc_id} " + " ".join(f"{l:.6f}" for l in losses), flush=True)
"""


def _free_port() -> int:
    s = socket.socket()
    s.bind(("127.0.0.1", 0))
    port = s.getsockname()[1]
    s.close()
    return port


@pytest.mark.skipif(
    os.environ.get("DMP_SKIP_MULTIHOST") == "1",
    reason="multi-process cluster disabled by env",
)
def test_two_process_cluster_trains_and_checkpoints(tmp_path):
    worker = tmp_path / "worker.py"
    worker.write_text(_WORKER)
    repo = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
    env = dict(os.environ)
    env["XLA_FLAGS"] = "--xla_force_host_platform_device_count=4"
    env["JAX_PLATFORMS"] = "cpu"
    env["PYTHONPATH"] = repo + os.pathsep + env.get("PYTHONPATH", "")

    def spawn_cluster(port):
        procs = [
            subprocess.Popen(
                [sys.executable, str(worker), str(i), "2", str(port),
                 str(tmp_path / "ckpt")],
                stdout=subprocess.PIPE, stderr=subprocess.STDOUT, text=True,
                env=env, cwd=repo,
            )
            for i in range(2)
        ]
        outs = []
        try:
            for p in procs:
                out, _ = p.communicate(timeout=300)
                outs.append(out)
        finally:
            # Never leak the sibling: a crashed/timed-out worker leaves
            # the other blocked in the coordinator handshake or a
            # collective.
            for p in procs:
                if p.poll() is None:
                    p.kill()
                    p.communicate()
        return procs, outs

    # The free-port probe has a close-then-reuse window (the coordinator
    # binds seconds later, after interpreter + jax import); retry with a
    # fresh port if the rendezvous lost that race.
    for attempt in range(3):
        procs, outs = spawn_cluster(_free_port())
        if all(p.returncode == 0 for p in procs):
            break
        bind_race = any(
            "already in use" in out.lower() or "bind" in out.lower()
            for out in outs
        )
        if not (bind_race and attempt < 2):
            break
    for p, out in zip(procs, outs):
        assert p.returncode == 0, out[-2000:]
    results = {}
    for out in outs:
        for line in out.splitlines():
            if line.startswith("RESULT "):
                _, pid, *losses = line.split()
                results[pid] = losses
    assert set(results) == {"0", "1"}, outs
    # global loss sums identical on both hosts: the psum really crossed
    # process boundaries and both saw the same global batch
    assert results["0"] == results["1"], results
