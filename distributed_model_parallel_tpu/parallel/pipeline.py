"""Pipeline model parallelism — SPMD over the `'stage'` mesh axis.

The TPU-native re-design of the reference's hand-rolled cross-process
pipeline (`code/distributed_training/model_parallel.py` +
`code/distributed_training/distributed_layers.py` +
`code/distributed_training/utils.py:34-210`):

reference (rank-scripted, NCCL P2P)          here (mesh-declarative, XLA)
--------------------------------------------  --------------------------------
one OS process per rank, role picked by       one SPMD program; every device
`if rank == 0 / < ws-1 / == ws-1`             runs `lax.switch(axis_index
(`model_parallel.py:99-157`)                  ('stage'), branches)` on its own
                                              stage's weights
`dist.send`/`dist.recv` with a runtime        `lax.ppermute` of a fixed-size
dim/size handshake per transfer               activation buffer; shapes are
(`distributed_layers.py:11-13,40-47`)         static at trace time, handshake
                                              deleted (SURVEY.md §7 hard parts)
`ForwardSend_BackwardReceive` /               plain `jax.grad` through the
`ForwardReceive_BackwardSend` autograd        scan: the transpose of ppermute
pair + the dummy-gradient `output.            IS the reversed permute, so the
backward(recv_size)` hack                     backward schedule emerges from
(`distributed_layers.py:7-62`,                autodiff instead of a hand-built
`utils.py:61-62`)                             protocol
exactly ONE batch in flight => all stages     GPipe fill-drain over
but one idle (`Readme.md:283-292`: MP is      `num_microbatches` M: scan over
4x slower than DP)                            T = M + S - 1 ticks, stage s
                                              works on microbatch t - s;
                                              M=1 reproduces the reference's
                                              single-batch schedule exactly

Three schedules (INTERNALS.md §3b/§3d): `schedule="gpipe"` (above —
backward is autodiff through the tick scan, O(M) live activations per
stage), `schedule="1f1b"` (PipeDream-flush — a hand-scheduled
forward+backward tick program from `build_1f1b_schedule`, per-stage
activation stash bounded by a min(S, M)-deep ring, so M scales until
the bubble is negligible at O(S) memory), and
`schedule="interleaved"` (Megatron's interleaved virtual pipeline,
Narayanan et al. SC'21 — each device owns `virtual_stages=V`
NON-contiguous model chunks, activations ring-route S·V-1 logical hops
over S physical devices, and the bubble floor drops from
(S-1)/(M+S-1) to (S-1)/(V·M+S-1)). Gradients/trajectories are
identical across all three (tests/test_pipeline_schedule.py).

Combinable with data parallelism: a (data=D, stage=S) mesh runs D
independent pipelines, gradients pmean over 'data' and psum over 'stage'
in the same fused reduction.

Design notes:
* Stage parameter STORAGE is a mode: the default replicates the per-stage
  tuple on every device (each device *computes* only its own stage via
  the switch branch — fine at reference scale, MobileNetV2 ~2.3M params);
  `stage_local_params=True` stores params/momentum/BN state as (S, maxP)
  arrays sharded over 'stage' so each device holds ~1/S of the model —
  the memory scaling that makes pipeline MP a memory tool.
* Activations cross stages in one flat buffer padded to the largest
  inter-stage tensor, so every ppermute has one static shape. The buffer
  dtype is the common type of all stage-I/O leaves (bf16 under mixed
  precision — half the ICI bytes of f32). Stage I/O shapes come from a
  setup-time `jax.eval_shape` chain over the stages — the static
  replacement for the reference's per-transfer dim/size messages.
* Invalid ticks (pipeline bubble) still execute the branch on a zeros
  buffer (SPMD lockstep); their outputs and BN-state updates are masked.
"""

from __future__ import annotations

import dataclasses
import math
from functools import partial
from typing import Any, List, NamedTuple, Sequence, Tuple

import jax
import jax.numpy as jnp
import numpy as np
from jax import lax
from jax.sharding import Mesh, NamedSharding, PartitionSpec as P
from distributed_model_parallel_tpu.runtime.compat import shard_map

from distributed_model_parallel_tpu.models.layers import Context, Layer
from distributed_model_parallel_tpu.models.layers import remat as remat_layer
from distributed_model_parallel_tpu.models.staging import (
    chunk_owner,
    logical_of_row,
    row_of_logical,
    stage_io_avals,
)
from distributed_model_parallel_tpu.parallel.data_parallel import (
    TrainState,
    _cast_input,
    _place_batch,
)
from distributed_model_parallel_tpu.training.metrics import (
    cross_entropy,
    label_rank,
    rank_correct,
    valid_count,
)
from distributed_model_parallel_tpu.training.optim import SGD


def _tree_size(aval_tree) -> int:
    """Total element count of a pytree of avals/arrays."""
    return sum(
        math.prod(leaf.shape)
        for leaf in jax.tree_util.tree_leaves(aval_tree)
    )


def _wire_dtype(avals) -> jnp.dtype:
    """Dtype of the inter-stage wire buffer: the common type of every
    stage-I/O leaf. bf16 activations give a bf16 wire (half the ppermute
    bytes of f32); bool masks riding alongside (BERT's (hidden, mask) pair)
    promote into it losslessly (0/1 exact in every float dtype)."""
    dtypes = {
        leaf.dtype
        for in_aval, out_aval in avals
        for leaf in jax.tree_util.tree_leaves((in_aval, out_aval))
    }
    return jnp.result_type(*dtypes) if dtypes else jnp.dtype(jnp.float32)


def _pack(tree, buf_size: int, dtype=jnp.float32) -> jax.Array:
    """Pytree of arrays -> one flat buffer of `dtype` padded to `buf_size`
    (the wire format between stages; one static ppermute shape for
    everything). Also the storage format for stage-local parameters."""
    flats = [
        leaf.astype(dtype).reshape(-1)
        for leaf in jax.tree_util.tree_leaves(tree)
    ]
    if not flats:
        return jnp.zeros((buf_size,), dtype)
    flat = jnp.concatenate(flats) if len(flats) > 1 else flats[0]
    return jnp.zeros((buf_size,), dtype).at[: flat.shape[0]].set(flat)


def _to_host(x):
    """Global array -> host numpy, multi-host safe: a 'stage'-sharded
    array's rows may live on OTHER hosts (non-fully-addressable), where
    plain device_get raises — allgather across processes instead."""
    import numpy as np

    if isinstance(x, jax.Array) and not x.is_fully_addressable:
        from jax.experimental import multihost_utils

        return np.asarray(multihost_utils.process_allgather(x, tiled=True))
    return np.asarray(jax.device_get(x))


def _pack_np(tree, buf_size: int):
    """Host-side `_pack` (f32 numpy): used when staging per-stage rows
    through host memory must not create device buffers."""
    import numpy as np

    flats = [
        np.asarray(leaf, np.float32).ravel()
        for leaf in jax.tree_util.tree_leaves(tree)
    ]
    row = np.zeros((buf_size,), np.float32)
    if flats:
        flat = np.concatenate(flats) if len(flats) > 1 else flats[0]
        row[: flat.shape[0]] = flat
    return row


def _unpack(buf: jax.Array, aval_tree):
    """Inverse of `_pack` given the target aval pytree."""
    leaves, treedef = jax.tree_util.tree_flatten(aval_tree)
    out, offset = [], 0
    for leaf in leaves:
        n = math.prod(leaf.shape)
        out.append(
            buf[offset:offset + n].reshape(leaf.shape).astype(leaf.dtype)
        )
        offset += n
    return jax.tree_util.tree_unflatten(treedef, out)


# ---------------------------------------------------------------------------
# 1F1B (PipeDream-flush) tick schedule — built on the host at setup time.
# ---------------------------------------------------------------------------

# Per-(tick, stage) work kinds. IDLE ticks are pipeline bubble: the SPMD
# program still executes a (masked) forward branch in lockstep.
PIPE_IDLE, PIPE_FWD, PIPE_BWD = 0, 1, 2


class Schedule1F1B(NamedTuple):
    """Static tick tables for the 1F1B schedule, all shaped (T, S).

    `work[t, s]` / `micro[t, s]` say what stage s computes at tick t;
    `recv_fwd*` / `recv_bwd*` say whether the activation (up) / cotangent
    (down) wire buffer a stage holds at the START of tick t carries a
    valid payload, and for which microbatch — the receive side of the
    schedule, derived from the sender side one tick earlier. Ring depths
    are the peak number of simultaneously-live activations / cotangents
    at any stage: the O(S) memory bound that is the point of 1F1B."""

    work: np.ndarray
    micro: np.ndarray
    recv_fwd: np.ndarray
    recv_fwd_m: np.ndarray
    recv_bwd: np.ndarray
    recv_bwd_m: np.ndarray
    num_ticks: int
    stash_depth: int
    cot_depth: int


def _min_ring_depth(intervals_per_slotkey: dict, max_key: int) -> int:
    """Smallest ring depth R such that assigning key k to slot k % R never
    overlaps two live intervals [start, end] (inclusive; arrival happens
    BEFORE compute within a tick, so reuse must be strictly later)."""
    for depth in range(1, max_key + 2):
        ok = True
        for (s, m), (start, _end) in intervals_per_slotkey.items():
            prev = intervals_per_slotkey.get((s, m - depth))
            if prev is not None and start <= prev[1]:
                ok = False
                break
        if ok:
            return depth
    return max_key + 1


def build_1f1b_schedule(num_stages: int, num_microbatches: int) -> Schedule1F1B:
    """One-forward-one-backward (PipeDream-flush) tick program.

    Stage s warms up with min(S-1-s, M) forwards, then alternates
    (forward, backward) pairs, then drains the remaining backwards —
    Megatron's non-interleaved 1F1B work order. Ticks are assigned by a
    greedy lockstep simulation: at each tick a stage runs the head of its
    work queue iff its dependencies completed at an EARLIER tick (one
    ppermute hop separates producer and consumer), else it idles. The
    program length never exceeds 2M + 2(S-1) — the same fill+drain span
    as GPipe's forward+backward — while the number of microbatch
    activations any stage holds live stays <= min(S, M), independent of M
    (GPipe-through-autodiff holds all M)."""
    S, M = num_stages, num_microbatches
    if S < 1 or M < 1:
        raise ValueError(f"need S >= 1, M >= 1; got S={S}, M={M}")
    queues = []
    for s in range(S):
        warm = min(S - 1 - s, M)
        q = [(PIPE_FWD, m) for m in range(warm)]
        for i in range(M - warm):
            q.append((PIPE_FWD, warm + i))
            q.append((PIPE_BWD, i))
        q.extend((PIPE_BWD, m) for m in range(M - warm, M))
        queues.append(q)

    done_f = [[None] * M for _ in range(S)]  # tick stage s finished fwd m
    done_b = [[None] * M for _ in range(S)]
    heads = [0] * S
    work_rows, micro_rows = [], []
    t = 0
    while any(heads[s] < len(queues[s]) for s in range(S)):
        if t > 2 * M + 2 * S:  # greedy 1F1B provably fits well inside this
            raise RuntimeError(
                f"1F1B schedule deadlocked at tick {t} (S={S}, M={M})"
            )
        row_w, row_m = [PIPE_IDLE] * S, [0] * S
        for s in range(S):
            if heads[s] >= len(queues[s]):
                continue
            kind, m = queues[s][heads[s]]
            if kind == PIPE_FWD:
                ready = s == 0 or (
                    done_f[s - 1][m] is not None and done_f[s - 1][m] < t
                )
            else:
                ready = done_f[s][m] is not None and done_f[s][m] < t
                if s < S - 1:
                    ready = ready and (
                        done_b[s + 1][m] is not None and done_b[s + 1][m] < t
                    )
            if ready:
                row_w[s], row_m[s] = kind, m
        # Commit after scanning every stage: this tick's completions become
        # visible only from t+1 (the `< t` checks above), matching the
        # one-tick ppermute latency of the lockstep SPMD program.
        for s in range(S):
            if row_w[s] == PIPE_FWD:
                done_f[s][row_m[s]] = t
                heads[s] += 1
            elif row_w[s] == PIPE_BWD:
                done_b[s][row_m[s]] = t
                heads[s] += 1
        work_rows.append(row_w)
        micro_rows.append(row_m)
        t += 1

    T = t
    assert T <= 2 * M + 2 * (S - 1) or S == 1, (T, S, M)
    work = np.asarray(work_rows, np.int32)
    micro = np.asarray(micro_rows, np.int32)

    # Receive tables: what the wire buffers hold at the START of tick t is
    # whatever the neighbor put on them at tick t-1.
    recv_fwd = np.zeros((T, S), bool)
    recv_fwd_m = np.zeros((T, S), np.int32)
    recv_bwd = np.zeros((T, S), bool)
    recv_bwd_m = np.zeros((T, S), np.int32)
    for tt in range(1, T):
        for s in range(S):
            if s >= 1 and work[tt - 1, s - 1] == PIPE_FWD:
                recv_fwd[tt, s] = True
                recv_fwd_m[tt, s] = micro[tt - 1, s - 1]
            if s <= S - 2 and work[tt - 1, s + 1] == PIPE_BWD:
                recv_bwd[tt, s] = True
                recv_bwd_m[tt, s] = micro[tt - 1, s + 1]

    # Ring depths from the exact live intervals (inclusive ticks):
    # * activation stash at stage s>=1: arrival F(s-1,m)+1 .. consumption
    #   by the backward B(s,m) (stage 0 reads the resident input batch
    #   directly and never stashes);
    # * cotangent at stage s<=S-2: arrival B(s+1,m)+1 .. B(s,m).
    stash_iv = {
        (s, m): (done_f[s - 1][m] + 1, done_b[s][m])
        for s in range(1, S)
        for m in range(M)
    }
    cot_iv = {
        (s, m): (done_b[s + 1][m] + 1, done_b[s][m])
        for s in range(S - 1)
        for m in range(M)
    }
    stash_depth = _min_ring_depth(stash_iv, M - 1) if stash_iv else 1
    cot_depth = _min_ring_depth(cot_iv, M - 1) if cot_iv else 1
    if stash_depth > min(S, M):
        raise RuntimeError(  # the O(S) guarantee this schedule exists for
            f"1F1B stash depth {stash_depth} exceeds min(S, M)="
            f"{min(S, M)} at S={S}, M={M}"
        )
    return Schedule1F1B(
        work, micro, recv_fwd, recv_fwd_m, recv_bwd, recv_bwd_m,
        T, stash_depth, cot_depth,
    )


# ---------------------------------------------------------------------------
# Interleaved virtual-pipeline tick schedule (Megatron SC'21) — the (T, S, V)
# generalization of the 1F1B tables. V=1 reduces EXACTLY to
# `build_1f1b_schedule` (pinned by tests/test_pipeline_schedule.py).
# ---------------------------------------------------------------------------


class ScheduleTicks(NamedTuple):
    """Static tick tables generalized over `virtual_stages` V, all shaped
    (T, S). Each physical stage owns V model chunks; `chunk[t, s]` names
    which of device s's chunks runs at tick t (the logical pipeline stage
    is `chunk * S + s`, so device s owns logical stages {s, s+S, ...} —
    Megatron's round-robin chunk placement). The recv tables gain a
    chunk column: the activation (up-ring) / cotangent (down-ring) wire
    payload a device holds at the START of tick t belongs to ring slot
    `recv_*_c * depth + recv_*_m % depth`. Ring depths are PER-CHUNK:
    the stash array is (V * stash_depth, buf)."""

    work: np.ndarray
    micro: np.ndarray
    chunk: np.ndarray
    recv_fwd: np.ndarray
    recv_fwd_m: np.ndarray
    recv_fwd_c: np.ndarray
    recv_bwd: np.ndarray
    recv_bwd_m: np.ndarray
    recv_bwd_c: np.ndarray
    num_ticks: int
    stash_depth: int
    cot_depth: int
    num_virtual: int


def build_interleaved_schedule(
    num_stages: int, num_microbatches: int, virtual_stages: int = 1
) -> ScheduleTicks:
    """Interleaved 1F1B tick program over S devices × V chunks each.

    Work order per device is Megatron's (Narayanan et al., SC'21,
    `megatron/core/pipeline_parallel/schedules.py`): microbatches are
    processed in groups of S — forward k runs chunk (k//S) % V on
    microbatch (k//(S·V))·S + k%S, backwards mirror with the chunk
    order reversed — with warmup 2(S-1-s) + (V-1)·S forwards before the
    first backward (V=1 keeps the non-interleaved min(S-1-s, M), which
    makes the V=1 tables bit-identical to `build_1f1b_schedule`). Ticks
    are assigned by the same greedy lockstep simulation: dependencies
    are between LOGICAL stages l = v·S + s (one ring-ppermute hop, so a
    consumer runs strictly after its producer's tick).

    The payoff is the span: T = 2MV + 2(S-1) chunk-ticks for 2MV
    chunk-ticks of work per device, i.e. an idle fraction of
    (S-1)/(V·M+S-1) — the 1F1B bubble divided by V (each chunk-tick is
    1/V of a stage-tick of compute, so the fill/drain cost shrinks by V
    while total compute is unchanged). The price is stash memory: early
    chunks' activations live until their late backwards, so the
    per-chunk ring depth grows past min(S, M) (bounded below by the
    exact live intervals, asserted <= min(M, 2S) here) and there are V
    rings. Megatron requires M % S == 0 for V > 1; so do we.
    """
    S, M, V = num_stages, num_microbatches, virtual_stages
    if S < 1 or M < 1 or V < 1:
        raise ValueError(f"need S, M, V >= 1; got S={S}, M={M}, V={V}")
    if V > 1 and S < 2:
        raise ValueError(
            f"interleaving needs >= 2 physical stages, got S={S}"
        )
    if V > 1 and M % S:
        raise ValueError(
            f"interleaved schedule needs num_microbatches divisible by "
            f"num_stages (Megatron's round-robin microbatch groups); "
            f"got M={M}, S={S}"
        )
    C = S * V          # logical pipeline depth
    total = M * V      # forward (and backward) chunk-ticks per device

    def fwd_item(k):
        return (PIPE_FWD, (k // C) * S + k % S, (k // S) % V)

    def bwd_item(k):
        return (PIPE_BWD, (k // C) * S + k % S, V - 1 - (k // S) % V)

    queues = []
    for s in range(S):
        warm = (
            min(S - 1 - s, M) if V == 1
            else min(2 * (S - 1 - s) + (V - 1) * S, total)
        )
        q = [fwd_item(k) for k in range(warm)]
        for i in range(total - warm):
            q.append(fwd_item(warm + i))
            q.append(bwd_item(i))
        q.extend(bwd_item(i) for i in range(total - warm, total))
        queues.append(q)

    done_f = [[None] * M for _ in range(C)]  # tick logical l finished fwd m
    done_b = [[None] * M for _ in range(C)]
    heads = [0] * S
    work_rows, micro_rows, chunk_rows = [], [], []
    t = 0
    while any(heads[s] < len(queues[s]) for s in range(S)):
        if t > 2 * total + 4 * C:
            raise RuntimeError(
                f"interleaved schedule deadlocked at tick {t} "
                f"(S={S}, M={M}, V={V})"
            )
        row_w = [PIPE_IDLE] * S
        row_m = [0] * S
        row_c = [0] * S
        for s in range(S):
            if heads[s] >= len(queues[s]):
                continue
            kind, m, v = queues[s][heads[s]]
            l = v * S + s
            if kind == PIPE_FWD:
                ready = l == 0 or (
                    done_f[l - 1][m] is not None and done_f[l - 1][m] < t
                )
            else:
                ready = done_f[l][m] is not None and done_f[l][m] < t
                if l < C - 1:
                    ready = ready and (
                        done_b[l + 1][m] is not None and done_b[l + 1][m] < t
                    )
            if ready:
                row_w[s], row_m[s], row_c[s] = kind, m, v
        # Commit after scanning every stage (one-tick ppermute latency).
        for s in range(S):
            l = row_c[s] * S + s
            if row_w[s] == PIPE_FWD:
                done_f[l][row_m[s]] = t
                heads[s] += 1
            elif row_w[s] == PIPE_BWD:
                done_b[l][row_m[s]] = t
                heads[s] += 1
        work_rows.append(row_w)
        micro_rows.append(row_m)
        chunk_rows.append(row_c)
        t += 1

    T = t
    # The bubble guarantee the schedule exists for: fill+drain only ever
    # costs the FIRST/LAST chunk's pipeline, 2(S-1) chunk-ticks total.
    assert T <= 2 * total + 2 * (S - 1) or S == 1, (T, S, M, V)
    work = np.asarray(work_rows, np.int32)
    micro = np.asarray(micro_rows, np.int32)
    chunk = np.asarray(chunk_rows, np.int32)

    # Receive tables. The wire is a RING: up payloads come from device
    # (s-1) mod S, down payloads from (s+1) mod S — the wrap edge is how
    # an activation crosses a chunk boundary (logical v·S+S-1 -> (v+1)·S
    # lives on device S-1 -> device 0). For V == 1 the wrap edge never
    # carries a valid payload (its sender would be the last / first
    # logical stage), so these tables equal the 1F1B chain tables.
    recv_fwd = np.zeros((T, S), bool)
    recv_fwd_m = np.zeros((T, S), np.int32)
    recv_fwd_c = np.zeros((T, S), np.int32)
    recv_bwd = np.zeros((T, S), bool)
    recv_bwd_m = np.zeros((T, S), np.int32)
    recv_bwd_c = np.zeros((T, S), np.int32)
    if S > 1:
        for tt in range(1, T):
            for s in range(S):
                sp = (s - 1) % S
                if work[tt - 1, sp] == PIPE_FWD:
                    l = chunk[tt - 1, sp] * S + sp
                    if l < C - 1:
                        recv_fwd[tt, s] = True
                        recv_fwd_m[tt, s] = micro[tt - 1, sp]
                        recv_fwd_c[tt, s] = (l + 1) // S
                sn = (s + 1) % S
                if work[tt - 1, sn] == PIPE_BWD:
                    l = chunk[tt - 1, sn] * S + sn
                    if l > 0:
                        recv_bwd[tt, s] = True
                        recv_bwd_m[tt, s] = micro[tt - 1, sn]
                        recv_bwd_c[tt, s] = (l - 1) // S
    # Per-chunk ring depths from the exact live intervals, keyed by
    # ((device, chunk), m) so reuse conflicts are checked within each
    # chunk's own ring (slot = chunk * depth + m % depth).
    stash_iv = {}
    cot_iv = {}
    for s in range(S):
        for v in range(V):
            l = v * S + s
            for m in range(M):
                if l >= 1:
                    stash_iv[((s, v), m)] = (
                        done_f[l - 1][m] + 1, done_b[l][m]
                    )
                if l <= C - 2:
                    cot_iv[((s, v), m)] = (
                        done_b[l + 1][m] + 1, done_b[l][m]
                    )
    stash_depth = _min_ring_depth(stash_iv, M - 1) if stash_iv else 1
    cot_depth = _min_ring_depth(cot_iv, M - 1) if cot_iv else 1
    if stash_depth > min(M, 2 * S if V > 1 else S):
        raise RuntimeError(
            f"interleaved stash depth {stash_depth} exceeds the "
            f"documented bound min(M, 2S) at S={S}, M={M}, V={V}"
        )
    return ScheduleTicks(
        work, micro, chunk,
        recv_fwd, recv_fwd_m, recv_fwd_c,
        recv_bwd, recv_bwd_m, recv_bwd_c,
        T, stash_depth, cot_depth, V,
    )


@dataclasses.dataclass
class PipelineEngine:
    """GPipe-style pipeline engine over the `'stage'` mesh axis.

    `stages` is the output of a model family's `split_stages` (e.g.
    `mobilenetv2.split_stages(4, boundaries=[3, 9, 15])` for the
    reference's exact ws=4 partition). `num_microbatches=1` is the
    reference's schedule (one batch in flight); raise it to fill the
    pipeline (bubble fraction (S-1)/(M+S-1))."""

    stages: List[Layer]
    optimizer: Any  # SGD | AdamW (init/update/state_shardings protocol)
    mesh: Mesh
    num_microbatches: int = 1
    sync_bn: bool = False
    donate: bool = True
    compute_dtype: Any = None  # mixed precision; see DataParallelEngine
    # Rematerialize each stage's forward during backward (jax.checkpoint).
    remat: bool = False
    # Stage-local parameter storage: params / BN state / momentum live as
    # (S, maxP) f32 arrays sharded over 'stage', so each device STORES
    # ~1/S of the model instead of all of it — the memory scaling that is
    # the reason pipeline MP exists (the reference splits the model across
    # GPUs for exactly this, `model_parallel.py:99-157`). Each device
    # unpacks only its own stage's slice inside the step; gradients stay
    # local to their stage's devices (no psum over 'stage' needed).
    # False keeps the replicated representation (params as a per-stage
    # tuple of pytrees on every device).
    stage_local_params: bool = False
    # Pipeline schedule:
    # * "gpipe" — fill-drain: all M forwards, then all M backwards (the
    #   backward derived by autodiff through the tick scan). Live
    #   activation memory grows O(M) per stage: the memory the schedule
    #   needs grows exactly as fast as raising M shrinks the bubble.
    # * "1f1b"  — PipeDream-flush: warmup, then each stage alternates one
    #   forward and one backward tick (hand-scheduled vjp per stage, same
    #   2(M+S-1)-tick span). Live activations are capped by a
    #   min(S, M)-deep ring buffer, independent of M — so microbatch
    #   count can scale until the bubble is negligible. Gradients and BN
    #   state match "gpipe" exactly (same per-microbatch math, same
    #   fold order); only the schedule and its memory change.
    # * "interleaved" — Megatron's interleaved virtual pipeline
    #   (Narayanan et al. SC'21): `stages` holds S·V chunks, device s
    #   owns the NON-contiguous set {s, s+S, ...}, and the 1F1B tick
    #   program generalizes to (microbatch, chunk) pairs riding a RING
    #   ppermute (the wrap edge carries chunk-boundary hops). Each
    #   chunk-tick is 1/V of a stage-tick of compute, so the fill/drain
    #   bubble drops to (S-1)/(V·M+S-1) — the 1F1B floor divided by V —
    #   at the price of deeper activation rings (V rings of depth
    #   <= min(M, 2S) instead of one of depth min(S, M)) and one
    #   ppermute per chunk-tick. Needs M % S == 0 when V > 1.
    schedule: str = "gpipe"
    # Model chunks per device under schedule="interleaved" (V). 1 keeps
    # one chunk per device (the plain 1F1B tick tables).
    virtual_stages: int = 1

    def __post_init__(self):
        mesh = self.mesh
        if self.schedule not in ("gpipe", "1f1b", "interleaved"):
            raise ValueError(
                f"schedule must be 'gpipe', '1f1b' or 'interleaved', "
                f"got {self.schedule!r}"
            )
        if self.virtual_stages < 1:
            raise ValueError(
                f"virtual_stages must be >= 1, got {self.virtual_stages}"
            )
        if self.virtual_stages > 1 and self.schedule != "interleaved":
            raise ValueError(
                "virtual_stages > 1 requires schedule='interleaved' "
                "(gpipe/1f1b run exactly one chunk per device)"
            )
        if "stage" not in mesh.axis_names:
            raise ValueError("pipeline mesh needs a 'stage' axis")
        self.num_stages = mesh.shape["stage"]
        # V chunks per device; C = S·V logical pipeline stages. For the
        # non-interleaved schedules V == 1 and chunks == stages.
        self._V = self.virtual_stages if self.schedule == "interleaved" \
            else 1
        self.num_chunks = self.num_stages * self._V
        if self.num_chunks != len(self.stages):
            raise ValueError(
                f"{len(self.stages)} stage chunks but mesh 'stage' axis "
                f"size {self.num_stages} x virtual_stages {self._V} "
                f"needs {self.num_chunks}"
            )
        self._repl = NamedSharding(mesh, P())
        self._batch = NamedSharding(mesh, P(("data",)))

        # Per-stage param/state avals from an abstract trace of init —
        # the static metadata both param representations are built from.
        key_aval = jax.ShapeDtypeStruct((2,), jnp.uint32)
        self._param_avals, self._state_avals = [], []
        for stage in self.stages:
            p_aval, s_aval = jax.eval_shape(stage.init, key_aval)
            self._param_avals.append(p_aval)
            self._state_avals.append(s_aval)
        # MoE aux losses ride the layer state ("moe_aux" leaves), and the
        # pipeline computes its loss on the LAST stage's devices only —
        # folding other stages' aux in would need a differentiated
        # psum('stage'), which this engine's autodiff discipline excludes
        # (see _make_step). Refuse loudly rather than silently training
        # an unbalanced router (only the GSPMD engines consume moe_aux).
        for s_aval in self._state_avals:
            for path, _ in jax.tree_util.tree_leaves_with_path(s_aval):
                if path and getattr(path[-1], "key", None) == "moe_aux":
                    raise NotImplementedError(
                        "MoE layers are not supported inside PipelineEngine "
                        "stages: the load-balance aux loss cannot reach the "
                        "last-stage loss without a differentiated 'stage' "
                        "collective. Train MoE models with the DP / DDP / "
                        "TensorParallel / ExpertParallel engines."
                    )
        self._psize = max(
            (_tree_size(a) for a in self._param_avals), default=1
        ) or 1
        self._ssize = max(
            (_tree_size(a) for a in self._state_avals), default=1
        ) or 1
        self._stage_sh = NamedSharding(mesh, P(("stage",)))
        if self.stage_local_params:
            # Validate the optimizer's state_shardings declaration NOW:
            # a field built from neither protocol argument would otherwise
            # surface as an opaque trace/spec error inside the first
            # checkpoint or step build (and legacy shard_map validates
            # specs eagerly). Construction is where a protocol violation
            # should be loud.
            self._opt_param_fields()
        # Hand-scheduled tick tables are static in (S, M, V): build once,
        # fail early. "1f1b" rides the generalized builder at V=1, whose
        # tables are bit-identical to `build_1f1b_schedule`'s
        # (tests/test_pipeline_schedule.py pins the reduction).
        self._sched = (
            build_interleaved_schedule(
                self.num_stages, self.num_microbatches, self._V
            )
            if self.schedule in ("1f1b", "interleaved") else None
        )

        donate = (0,) if self.donate else ()
        self.train_step = jax.jit(
            self._make_step(train=True), donate_argnums=donate
        )
        self.eval_step = jax.jit(self._make_step(train=False))

    # ------------------------------------------------------------ setup

    def init_state(self, rng: jax.Array) -> TrainState:
        if not self.stage_local_params:
            params, state = [], []
            for i, stage in enumerate(self.stages):
                p, s = stage.init(jax.random.fold_in(rng, i))
                params.append(p)
                state.append(s)
            params, state = tuple(params), tuple(state)
            opt_state = self.optimizer.init(params)
            ts = TrainState(
                params, state, opt_state, jnp.zeros((), jnp.int32)
            )
            return jax.device_put(ts, self._repl)
        # Stage-local: per-chunk flats become rows of (S·V, maxP) /
        # (S·V, maxS) arrays sharded over 'stage'. Rows are DEVICE-MAJOR
        # (`staging.row_of_logical`): row s·V + v holds logical chunk
        # v·S + s, so the P('stage') sharding lands each device's V
        # interleaved chunks on it as local rows 0..V-1 (identity when
        # V == 1). Each chunk is initialized, moved to HOST memory, and
        # packed there before the next chunk initializes (so at most ONE
        # chunk's params are device-resident at a time), then the stacked
        # array materializes shard-by-shard (make_array_from_callback) —
        # the point of this mode is that the whole model doesn't fit per
        # device, so init must never assemble it on one.
        p_rows, s_rows = [], []
        for r in range(self.num_chunks):
            i = logical_of_row(r, self.num_stages, self._V)
            p, s = self.stages[i].init(jax.random.fold_in(rng, i))
            p_rows.append(_pack_np(jax.device_get(p), self._psize))
            s_rows.append(_pack_np(jax.device_get(s), self._ssize))
            del p, s
        flat_p = self._stack_local(p_rows)
        flat_s = self._stack_local(s_rows)
        # zeros_like keeps the 'stage' sharding for param-shaped buffers;
        # scalar fields (AdamW's count) come back process-local and must
        # be placed on the mesh like `step` below — state_shardings says
        # which is which.
        opt_state = jax.device_put(
            self.optimizer.init(flat_p),
            self.optimizer.state_shardings(self._stage_sh, self._repl),
        )
        return TrainState(
            flat_p, flat_s, opt_state,
            jax.device_put(jnp.zeros((), jnp.int32), self._repl),
        )

    def _stack_local(self, np_rows) -> jax.Array:
        """[per-stage 1-D host rows] -> (S, width) array sharded
        P('stage'), materialized shard-by-shard so the full stack never
        exists on one device."""
        import numpy as np

        np_rows = np.stack(np_rows)
        return jax.make_array_from_callback(
            np_rows.shape, self._stage_sh, lambda idx: np_rows[idx]
        )

    def params_tree(self, ts: TrainState):
        """The per-stage tuple-of-pytrees view of `ts.params`, whichever
        representation the engine uses — for checkpoint interop, weight
        transplant, and tests."""
        if not self.stage_local_params:
            return ts.params
        flat = _to_host(ts.params)
        return tuple(
            _unpack(
                flat[row_of_logical(i, self.num_stages, self._V)],
                self._param_avals[i],
            )
            for i in range(self.num_chunks)
        )

    # ---------------------------------------------- checkpoint canonical

    def _unpack_stages(self, flat_host, avals):
        """Device-major packed rows -> LOGICAL-order per-chunk tuple (the
        canonical checkpoint order; identity permutation at V == 1)."""
        return tuple(
            _unpack(
                flat_host[row_of_logical(i, self.num_stages, self._V)],
                avals[i],
            )
            for i in range(self.num_chunks)
        )

    def _opt_param_fields(self) -> dict:
        """Which optimizer-state fields follow the params (and are
        therefore packed (S, maxP) in stage-local mode) versus stay
        replicated — read from the optimizer's own `state_shardings`
        DECLARATION via a sentinel probe, NOT from shape or tuple-length
        heuristics: a future field that merely *happens* to be shaped
        (num_stages, psize), or a length-S tuple, must not silently
        mis-serialize (ADVICE r3 #2)."""
        p_mark, r_mark = object(), object()
        decl = self.optimizer.state_shardings(p_mark, r_mark)
        fields = {}
        for k, v in decl._asdict().items():
            if v is p_mark:
                fields[k] = True
            elif v is r_mark:
                fields[k] = False
            else:
                raise ValueError(
                    f"optimizer.state_shardings built field {k!r} from "
                    f"neither the param-sharding pytree nor the "
                    f"replicated sharding; PipelineEngine cannot infer "
                    f"its checkpoint layout. Declare each field as one "
                    f"of the two protocol arguments."
                )
        return fields

    def to_canonical(self, ts: TrainState) -> TrainState:
        """TrainState in the layout-independent checkpoint form: params /
        BN state / optimizer buffers as per-stage tuples of pytrees with
        real layer paths and shapes. Checkpoints written this way are
        interchangeable between stage_local_params modes (and validate
        per-layer structure on restore, which a packed (S, maxP) leaf
        cannot).

        Optimizer-state protocol: a NamedTuple whose fields are either
        param-shaped buffers (packed (S, maxP) here — SGD momentum,
        AdamW moments) or replicated scalars (AdamW's count); which is
        which comes from the optimizer's `state_shardings` declaration
        (`_opt_param_fields`)."""
        if not self.stage_local_params:
            return ts
        follows = self._opt_param_fields()

        def canon_opt_field(k, v):
            if follows[k]:
                return self._unpack_stages(_to_host(v), self._param_avals)
            return v

        opt_c = type(ts.opt_state)(
            **{
                k: canon_opt_field(k, v)
                for k, v in ts.opt_state._asdict().items()
            }
        )
        state = self._unpack_stages(
            _to_host(ts.model_state), self._state_avals
        )
        return TrainState(self.params_tree(ts), state, opt_c, ts.step)

    def from_canonical(self, ts: TrainState) -> TrainState:
        """Inverse of `to_canonical`: re-pack a canonical TrainState into
        this engine's runtime layout and placement."""
        if not self.stage_local_params:
            return jax.device_put(ts, self._repl)

        def rows(tree_tuple, size):
            """Logical-order per-chunk tuple -> device-major packed rows
            (the storage layout `init_state` builds)."""
            return [
                _pack_np(
                    tree_tuple[logical_of_row(r, self.num_stages, self._V)],
                    size,
                )
                for r in range(self.num_chunks)
            ]

        flat_p = self._stack_local(rows(ts.params, self._psize))
        flat_s = self._stack_local(rows(ts.model_state, self._ssize))

        follows = self._opt_param_fields()

        def pack_opt_field(k, v):
            if follows[k]:
                return self._stack_local(rows(v, self._psize))
            return jax.device_put(jnp.asarray(v), self._repl)

        opt_p = type(ts.opt_state)(
            **{
                k: pack_opt_field(k, v)
                for k, v in ts.opt_state._asdict().items()
            }
        )
        return TrainState(
            flat_p, flat_s, opt_p,
            jax.device_put(jnp.asarray(ts.step), self._repl),
        )

    def shard_batch(self, images, labels):
        return _place_batch((images, labels), self._batch)

    def _stage_avals(self, x_aval, train: bool):
        """(input_avals, output_avals) per stage — `staging.stage_io_avals`
        on this engine's abstract params/state; everything crosses stages
        packed into one flat buffer of the common wire dtype."""
        return stage_io_avals(
            self.stages, self._param_avals, self._state_avals, x_aval,
            Context(train=train, dtype=self.compute_dtype),
        )

    # ------------------------------------------------------- the program

    def _make_step(self, train: bool):
        S = self.num_stages
        M = self.num_microbatches
        V = self._V
        C = self.num_chunks
        mesh = self.mesh
        bn_axis = "data" if self.sync_bn else None
        cdt = self.compute_dtype
        local = self.stage_local_params
        exec_stages = (
            [remat_layer(s) for s in self.stages] if self.remat
            else self.stages
        )

        def stage_params(params, i, row=0):
            """Logical chunk i's param pytree from either representation.
            In stage-local mode every device holds ONLY its own chunks'
            (V, maxP) slice, device-major, so local row `row` (= the
            chunk index v on the owning device) selects it; the unpack
            is differentiable, so the grad wrt the flat slice is the
            full chunk-i gradient."""
            return _unpack(params[row], self._param_avals[i]) if local \
                else params[i]

        def stage_state(state, i, row=0):
            return _unpack(state[row], self._state_avals[i]) if local \
                else state[i]

        def program_setup(images):
            """Static per-trace metadata shared by both schedules: cast
            input, microbatch split, the stage-I/O aval chain, the logits
            contract of the last stage, and the wire buffer format."""
            images = _cast_input(images, cdt)
            n_local = images.shape[0]
            if n_local % M:
                raise ValueError(
                    f"local batch {n_local} not divisible by "
                    f"num_microbatches {M}"
                )
            mb = n_local // M
            x_aval = jax.ShapeDtypeStruct(
                (mb,) + images.shape[1:], images.dtype
            )
            avals = self._stage_avals(x_aval, train)
            out_leaves = jax.tree_util.tree_leaves(avals[-1][1])
            if len(out_leaves) != 1 or len(out_leaves[0].shape) != 2:
                raise ValueError(
                    "last pipeline stage must output a single (rows, "
                    f"classes) logits array, got {avals[-1][1]} — "
                    "classification heads emit (microbatch, classes); "
                    "token-level (LM) heads flatten to (microbatch*T, "
                    "vocab) (models/gpt.py split_stages)"
                )
            # Logits rows per microbatch, from the traced aval — mb for
            # classification heads, mb*T for token-level LM heads (whose
            # labels arrive pre-flattened to (B*T,) so rows line up).
            rows, num_classes = out_leaves[0].shape
            buf_size = max(_tree_size(out) for _, out in avals)
            wire_dt = _wire_dtype(avals)
            return images, mb, avals, rows, num_classes, buf_size, wire_dt

        def pipeline_forward(params, model_state, images, labels, step):
            """Runs on ONE device (inside shard_map): the full fill-drain
            schedule for this device's stage. Returns (sum CE over local
            batch, logits for the local batch, updated state)."""
            images, mb, avals, rows, num_classes, buf_size, wire_dt = (
                program_setup(images)
            )
            s_idx = lax.axis_index("stage")

            def make_branch(i):
                in_aval = avals[i][0]

                def branch(operand):
                    state, buf, images_mb, rng = operand
                    ctx = Context(
                        train=train, bn_axis=bn_axis, rng=rng, dtype=cdt
                    )
                    if i == 0:
                        x = images_mb
                    else:
                        x = _unpack(buf, in_aval)
                    y, new_si = exec_stages[i].apply(
                        stage_params(params, i), stage_state(state, i),
                        x, ctx,
                    )
                    y_pad = _pack(y, buf_size, wire_dt)
                    if local:
                        new_state = _pack(new_si, self._ssize)[None, :]
                    else:
                        new_state = tuple(
                            new_si if j == i else state[j] for j in range(S)
                        )
                    return y_pad, new_state

                return branch

            branches = [make_branch(i) for i in range(S)]
            images_mbs = images.reshape((M, mb) + images.shape[1:])
            rng_base = jax.random.fold_in(
                jax.random.fold_in(jax.random.PRNGKey(0), step),
                lax.axis_index("data"),
            )

            def tick(carry, t):
                buf, state, out_stack = carry
                m = t - s_idx
                valid = (m >= 0) & (m < M)
                m_safe = jnp.clip(m, 0, M - 1)
                images_mb = lax.dynamic_index_in_dim(
                    images_mbs, m_safe, keepdims=False
                )
                # Per-(stage, microbatch) dropout key: every stage draws
                # independent masks for each microbatch of this step.
                rng = jax.random.fold_in(
                    jax.random.fold_in(rng_base, s_idx), m_safe
                )
                y_pad, new_state = lax.switch(
                    s_idx, branches, (state, buf, images_mb, rng)
                )
                # Mask bubble ticks: keep old BN stats, zero the output so
                # garbage never reaches the logits stack.
                state = jax.tree_util.tree_map(
                    lambda new, old: jnp.where(valid, new, old),
                    new_state, state,
                )
                y_pad = jnp.where(valid, y_pad, jnp.zeros_like(y_pad))
                # Logits stack stays f32 regardless of the wire dtype so
                # the loss/metrics see the same precision on every path.
                logits_mb = (
                    y_pad[: rows * num_classes]
                    .reshape(rows, num_classes)
                    .astype(jnp.float32)
                )
                out_stack = lax.dynamic_update_index_in_dim(
                    out_stack,
                    jnp.where(
                        valid,
                        logits_mb,
                        lax.dynamic_index_in_dim(out_stack, m_safe, 0, False),
                    ),
                    m_safe,
                    axis=0,
                )
                if S > 1:
                    buf = lax.ppermute(
                        y_pad, "stage", [(i, i + 1) for i in range(S - 1)]
                    )
                return (buf, state, out_stack), None

            buf0 = jnp.zeros((buf_size,), wire_dt)
            out0 = jnp.zeros((M, rows, num_classes), jnp.float32)
            (buf, new_state, out_stack), _ = lax.scan(
                tick,
                (buf0, model_state, out0),
                jnp.arange(M + S - 1),
            )
            logits = out_stack.reshape(M * rows, num_classes)
            # CE only counts on the last stage (the only device whose
            # out_stack holds real logits). NO psum here: the loss must stay
            # local so autodiff never transposes a cross-device reduction
            # (under check_vma=False a differentiated psum mis-scales
            # cotangents); the reversed ppermutes alone carry the true
            # cotangents upstream, and callers psum the VALUE for
            # reporting after grad.
            is_last = (s_idx == S - 1).astype(logits.dtype)
            loss_sum = (
                cross_entropy(logits, labels) * valid_count(labels) * is_last
            )
            return loss_sum, (logits, new_state, is_last)

        sched = self._sched
        interleaved = self.schedule == "interleaved"

        def pipeline_ticks(params, model_state, images, labels, step,
                           run_backward: bool):
            """Hand-scheduled tick program on ONE device — 1F1B
            (PipeDream-flush) when V == 1, Megatron's interleaved
            virtual pipeline when V > 1. Unlike `pipeline_forward`
            (whose backward is autodiff through the whole tick scan,
            saving every tick's residuals — O(M) live activations), this
            runs the static `build_interleaved_schedule` tick tables:
            each tick names a (microbatch, chunk) pair; forward ticks
            stash only the chunk's in-flight input window into a
            per-chunk ring buffer (V·R rows, slot v·R + m mod R);
            backward ticks re-run the chunk under `jax.vjp` on the
            stashed input (recompute is exact: BN normalizes with batch
            statistics in train mode, and the (logical chunk,
            microbatch) dropout key is deterministic), seed it with the
            cotangent the down-wire delivered (or the loss gradient on
            the last logical chunk), accumulate the parameter gradient
            in place, and send the input-cotangent one hop upstream.
            Two wires run concurrently — activations ppermute up,
            cotangents ppermute down. Under 1F1B the wires are chains;
            under interleaving they are RINGS, whose wrap edge carries a
            chunk-boundary hop (logical v·S+S-1 -> (v+1)·S crosses from
            device S-1 back to device 0), so activations traverse all
            S·V-1 logical hops over S physical devices.

            Returns (loss_sum, logits, new_state, grads, is_last); grads
            are the UNNORMALIZED sum over microbatches (the caller
            divides by its loss normalizer — a linear pull-out of the
            same scaling `jax.grad` applies under "gpipe").

            `run_backward=False` replays only the forward ticks (the
            interleaved EVAL path: backward/bubble ticks skip the chunk
            apply via `lax.cond`, the cotangent wire/ring is elided,
            grads return None) — the forward-side receive tables and
            ring slots are valid on their own because a slot's forward
            consumption always precedes the backward consumption it was
            sized for."""
            images, mb, avals, rows, num_classes, buf_size, wire_dt = (
                program_setup(images)
            )
            T, R, Rc = sched.num_ticks, sched.stash_depth, sched.cot_depth
            # Trace-time record for the structural memory tests: the
            # activation stash traced into this step is (V*R, buf_size).
            self._last_1f1b_trace = {
                "num_ticks": T, "stash_depth": R, "cot_depth": Rc,
                "buf_size": buf_size, "num_virtual": V,
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
            images_mbs = images.reshape((M, mb) + images.shape[1:])
            labels_mbs = labels.reshape((M, -1)) if run_backward else None
            rng_base = jax.random.fold_in(
                jax.random.fold_in(jax.random.PRNGKey(0), step),
                lax.axis_index("data"),
            )

            def make_chunk(i, vv):
                """Device i's chunk vv = logical pipeline stage vv*S+i
                (round-robin placement; V=1 keeps chunk i on device
                i)."""
                l = vv * S + i
                in_aval = avals[l][0]

                def run(operand):
                    state, stash, cots, grads, m, w, rng = operand
                    ctx = Context(
                        train=train, bn_axis=bn_axis, rng=rng, dtype=cdt
                    )
                    p_l = stage_params(params, l, vv)
                    s_l = stage_state(state, l, vv)
                    # Logical chunk 0's input batch is device-resident,
                    # so it is never stashed: both work kinds index
                    # images_mbs.
                    if l == 0:
                        x = lax.dynamic_index_in_dim(images_mbs, m, 0, False)
                    else:
                        x = _unpack(
                            lax.dynamic_index_in_dim(
                                stash, vv * R + m % R, 0, False
                            ),
                            in_aval,
                        )

                    def fwd(_):
                        y, new_si = exec_stages[l].apply(p_l, s_l, x, ctx)
                        y_pad = _pack(y, buf_size, wire_dt)
                        # Bubble (idle) ticks run this branch on garbage
                        # in SPMD lockstep: mask state and output.
                        valid = w == PIPE_FWD
                        if local:
                            packed = _pack(new_si, self._ssize)
                            new_state = state.at[vv].set(
                                jnp.where(valid, packed, state[vv])
                            )
                        else:
                            masked = jax.tree_util.tree_map(
                                lambda new, old: jnp.where(valid, new, old),
                                new_si, state[l],
                            )
                            new_state = tuple(
                                masked if j == l else state[j]
                                for j in range(C)
                            )
                        y_pad = jnp.where(
                            valid, y_pad, jnp.zeros_like(y_pad)
                        )
                        if not run_backward:
                            return y_pad, new_state
                        return (
                            y_pad, jnp.zeros((buf_size,), wire_dt),
                            new_state, grads,
                        )

                    if not run_backward:
                        # Eval replays the train tables, where half the
                        # ticks are backward work. Executing the masked
                        # forward there (the train path's SPMD-lockstep
                        # convention for bubble ticks) would double eval
                        # compute — the cond skips the chunk apply at
                        # runtime instead. Safe per-device: no
                        # collective lives inside the branch (the ring
                        # ppermute is outside, in `tick`).
                        return lax.cond(
                            w == PIPE_FWD,
                            fwd,
                            lambda _: (
                                jnp.zeros((buf_size,), wire_dt), state,
                            ),
                            0,
                        )

                    def bwd(_):
                        if l == C - 1:
                            lbl = lax.dynamic_index_in_dim(
                                labels_mbs, m, 0, False
                            )

                            def f(p, xx):
                                y, _ = exec_stages[l].apply(p, s_l, xx, ctx)
                                y_pad = _pack(y, buf_size, wire_dt)
                                logits_mb = (
                                    y_pad[: rows * num_classes]
                                    .reshape(rows, num_classes)
                                    .astype(jnp.float32)
                                )
                                return (
                                    cross_entropy(logits_mb, lbl)
                                    * valid_count(lbl)
                                )

                            _, vjp_fn = jax.vjp(f, p_l, x)
                            gp, gx = vjp_fn(jnp.ones((), jnp.float32))
                        else:

                            def f(p, xx):
                                y, _ = exec_stages[l].apply(p, s_l, xx, ctx)
                                return _pack(y, buf_size, wire_dt)

                            _, vjp_fn = jax.vjp(f, p_l, x)
                            gp, gx = vjp_fn(
                                lax.dynamic_index_in_dim(
                                    cots, vv * Rc + m % Rc, 0, False
                                )
                            )
                        # Logical chunk 0 has no upstream (and in LM
                        # mode an integer input whose cotangent is
                        # symbolic-zero).
                        down = (
                            jnp.zeros((buf_size,), wire_dt) if l == 0
                            else _pack(gx, buf_size, wire_dt)
                        )
                        if local:
                            new_grads = grads.at[vv].add(
                                _pack(gp, self._psize)
                            )
                        else:
                            g_l = jax.tree_util.tree_map(
                                jnp.add, grads[l], gp
                            )
                            new_grads = tuple(
                                g_l if j == l else grads[j]
                                for j in range(C)
                            )
                        return (
                            jnp.zeros((buf_size,), wire_dt), down, state,
                            new_grads,
                        )

                    return lax.cond(w == PIPE_BWD, bwd, fwd, 0)

                return run

            def make_branch(i):
                runs = [make_chunk(i, vv) for vv in range(V)]

                def branch(operand):
                    state, stash, cots, grads, m, v, w, rng = operand
                    inner = (state, stash, cots, grads, m, w, rng)
                    if V == 1:
                        return runs[0](inner)
                    return lax.switch(v, runs, inner)

                return branch

            branches = [make_branch(i) for i in range(S)]
            if interleaved:
                # Ring wires: the wrap edge is the chunk-boundary hop.
                up_pairs = [(i, (i + 1) % S) for i in range(S)]
                down_pairs = [((i + 1) % S, i) for i in range(S)]
            else:
                up_pairs = [(i, i + 1) for i in range(S - 1)]
                down_pairs = [(i + 1, i) for i in range(S - 1)]

            def tick(carry, t):
                if run_backward:
                    (up_buf, down_buf, stash, cots, state, out_stack,
                     grads) = carry
                else:
                    up_buf, stash, state, out_stack = carry
                    down_buf = None
                    cots = grads = jnp.zeros((), jnp.float32)
                w = work_tab[t, s_idx]
                m = micro_tab[t, s_idx]
                v = chunk_tab[t, s_idx]
                # Receive: the wire buffers hold tick t-1's permute
                # output; the static tables say whether that payload is
                # real and which (chunk, microbatch) ring slot it
                # belongs in (receive-before-compute, so a tick may
                # consume the activation/cotangent that just arrived).
                slot = recv_f_c[t, s_idx] * R + recv_f_m[t, s_idx] % R
                stash = lax.dynamic_update_index_in_dim(
                    stash,
                    jnp.where(
                        recv_f[t, s_idx], up_buf,
                        lax.dynamic_index_in_dim(stash, slot, 0, False),
                    ),
                    slot, 0,
                )
                if run_backward:
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
                # Per-(logical chunk, microbatch) dropout key — identical
                # at the forward tick and its backward-tick recompute
                # (v*S + s_idx == s_idx when V == 1).
                rng = jax.random.fold_in(
                    jax.random.fold_in(rng_base, v * S + s_idx), m
                )
                operand = (state, stash, cots, grads, m, v, w, rng)
                if run_backward:
                    up_out, down_out, state, grads = lax.switch(
                        s_idx, branches, operand
                    )
                else:
                    up_out, state = lax.switch(s_idx, branches, operand)
                write = (
                    (w == PIPE_FWD) & (s_idx == S - 1) & (v == V - 1)
                )
                logits_mb = (
                    up_out[: rows * num_classes]
                    .reshape(rows, num_classes)
                    .astype(jnp.float32)
                )
                out_stack = lax.dynamic_update_index_in_dim(
                    out_stack,
                    jnp.where(
                        write, logits_mb,
                        lax.dynamic_index_in_dim(out_stack, m, 0, False),
                    ),
                    m, 0,
                )
                if S > 1:
                    up_buf = lax.ppermute(up_out, "stage", up_pairs)
                    if run_backward:
                        down_buf = lax.ppermute(
                            down_out, "stage", down_pairs
                        )
                else:
                    up_buf = up_out
                    if run_backward:
                        down_buf = down_out
                if run_backward:
                    return (
                        up_buf, down_buf, stash, cots, state, out_stack,
                        grads,
                    ), None
                return (up_buf, stash, state, out_stack), None

            if run_backward:
                if local:
                    grads0 = jnp.zeros((V, self._psize), jnp.float32)
                else:
                    grads0 = jax.tree_util.tree_map(jnp.zeros_like, params)
                carry0 = (
                    jnp.zeros((buf_size,), wire_dt),
                    jnp.zeros((buf_size,), wire_dt),
                    # per-chunk activation rings (row v*R + m%R)
                    jnp.zeros((V * R, buf_size), wire_dt),
                    # per-chunk cotangent rings
                    jnp.zeros((V * Rc, buf_size), wire_dt),
                    model_state,
                    jnp.zeros((M, rows, num_classes), jnp.float32),
                    grads0,
                )
                (_, _, _, _, new_state, out_stack, grads), _ = lax.scan(
                    tick, carry0, jnp.arange(T)
                )
            else:
                carry0 = (
                    jnp.zeros((buf_size,), wire_dt),
                    jnp.zeros((V * R, buf_size), wire_dt),
                    model_state,
                    jnp.zeros((M, rows, num_classes), jnp.float32),
                )
                (_, _, new_state, out_stack), _ = lax.scan(
                    tick, carry0, jnp.arange(T)
                )
                grads = None
            logits = out_stack.reshape(M * rows, num_classes)
            is_last = (s_idx == S - 1).astype(logits.dtype)
            loss_sum = (
                cross_entropy(logits, labels) * valid_count(labels) * is_last
            )
            return loss_sum, logits, new_state, grads, is_last

        def reassemble_state(new_state, s_idx):
            """Each device updated only its own chunks' BN state; rebuild
            the replicated tuple by masked psum over 'stage'."""
            out = []
            for i in range(C):
                mask = (s_idx == chunk_owner(i, S)).astype(jnp.float32)
                out.append(
                    jax.tree_util.tree_map(
                        lambda v: lax.psum(v * mask, "stage"), new_state[i]
                    )
                )
            return tuple(out)

        def metrics_from(logits, labels, loss_sum, is_last):
            rank = label_rank(logits, labels)
            m = {
                "loss_sum": lax.psum(loss_sum, "stage"),
                "correct1": lax.psum(
                    rank_correct(rank, labels, 1) * is_last, "stage"
                ),
                "correct5": lax.psum(
                    rank_correct(rank, labels, 5) * is_last, "stage"
                ),
                "count": valid_count(labels),
            }
            return {k: lax.psum(v, "data") for k, v in m.items()}

        # shard_map spec for the TrainState: stage-local params ride the
        # 'stage' axis (each device gets its (1, maxP) slice); the
        # replicated representation is a plain P() prefix. The optimizer
        # state's spec comes from the optimizer itself (state_shardings:
        # param-shaped buffers follow the packed params, scalars like
        # AdamW's step count stay replicated).
        if local:
            st = P(("stage",))
            ts_spec = TrainState(
                st, st, self.optimizer.state_shardings(st, P()), P()
            )
        else:
            ts_spec = P()

        if train:

            @partial(
                shard_map,
                mesh=mesh,
                in_specs=(ts_spec, P(("data",)), P(("data",)), P()),
                out_specs=(ts_spec, P()),
                check_vma=False,
            )
            def step(ts: TrainState, images, labels, lr):
                s_idx = lax.axis_index("stage")

                # Normalize by the VALID row count (labels != -1), like
                # the dense engines' cross_entropy mean: for LM heads
                # that's per valid token (each sequence's final position
                # and pad targets carry -1), for classification it is
                # the unpadded batch — so gradient scale matches the
                # dense convention for both head kinds and does not
                # drift with the pad fraction. Local (this shard's
                # labels), keeping the no-collectives-before-grad
                # discipline.
                loss_norm = jnp.maximum(valid_count(labels), 1.0)

                if sched is not None:  # "1f1b" or "interleaved"
                    # Hand-scheduled fwd+bwd: grads come back as the
                    # unnormalized microbatch sum; dividing by loss_norm
                    # is the same linear scaling jax.grad applies to the
                    # gpipe loss below.
                    loss_sum, logits, new_state, grads, is_last = (
                        pipeline_ticks(
                            ts.params, ts.model_state, images, labels,
                            ts.step, run_backward=True,
                        )
                    )
                    grads = jax.tree_util.tree_map(
                        lambda g: g / loss_norm, grads
                    )
                    loss = loss_sum / loss_norm
                else:

                    def loss_fn(params):
                        loss_sum, aux = pipeline_forward(
                            params, ts.model_state, images, labels, ts.step
                        )
                        return loss_sum / loss_norm, aux

                    (loss, (logits, new_state, is_last)), grads = (
                        jax.value_and_grad(loss_fn, has_aux=True)(ts.params)
                    )
                if local:
                    # Each device's flat grad IS its stage's full gradient
                    # (cotangents crossed stages through the reversed
                    # ppermutes); only the data-parallel mean remains.
                    grads = lax.pmean(grads, "data")
                else:
                    # Stage-i grads are nonzero only on stage-i devices;
                    # the psum over 'stage' + pmean over 'data' is the
                    # single fused all-reduce replacing per-rank
                    # optimizers (`model_parallel.py:105-149`) and the
                    # DDP Reducer.
                    grads = jax.tree_util.tree_map(
                        lambda g: lax.pmean(lax.psum(g, "stage"), "data"),
                        grads,
                    )
                    new_state = reassemble_state(new_state, s_idx)
                if not self.sync_bn:
                    new_state = lax.pmean(new_state, "data")
                params, opt_state = self.optimizer.update(
                    ts.params, ts.opt_state, grads, lr
                )
                new_ts = TrainState(
                    params, new_state, opt_state, ts.step + 1
                )
                loss_sum = loss * loss_norm
                return new_ts, metrics_from(logits, labels, loss_sum, is_last)

            return step

        @partial(
            shard_map,
            mesh=mesh,
            in_specs=(ts_spec, P(("data",)), P(("data",))),
            out_specs=P(),
            check_vma=False,
        )
        def evstep(ts: TrainState, images, labels):
            if interleaved:
                # The fill-drain forward assumes one chunk per device;
                # interleaved eval replays the tick tables' forward
                # entries instead (backward ticks are masked no-ops).
                loss_sum, logits, _, _, is_last = pipeline_ticks(
                    ts.params, ts.model_state, images, labels, ts.step,
                    run_backward=False,
                )
            else:
                loss_sum, (logits, _, is_last) = pipeline_forward(
                    ts.params, ts.model_state, images, labels, ts.step
                )
            return metrics_from(logits, labels, loss_sum, is_last)

        return evstep


@dataclasses.dataclass
class LMPipelineEngine(PipelineEngine):
    """PipelineEngine for decoder-LM stages (`models/gpt.py
    split_stages`): `shard_batch` derives the flattened next-token
    targets from the ids on the HOST (`gpt.lm_targets` — the final
    position and pad targets carry -1, masked by the loss), so the
    uniform `(inputs, labels)` loader contract — `data/lm.py LMLoader`
    yields `(ids, ids)` — drives LM training unchanged. The engine's
    (rows, vocab) last-stage contract and valid-count loss normalization
    make gradients match the dense `lm_loss` convention."""

    pad_token_id: Any = None

    def shard_batch(self, ids, labels=None):
        import numpy as np

        from distributed_model_parallel_tpu.models.gpt import lm_targets

        targets = lm_targets(ids, self.pad_token_id).reshape(-1)
        return _place_batch(
            (np.asarray(ids, np.int32), targets), self._batch
        )
