"""Data-parallel engines — the core of the port (SURVEY.md §5.8 north star).

Two engines, mirroring the two things the reference has/documents:

`DataParallelEngine` (GSPMD): one `jax.jit`-compiled train step with the
batch sharded over the `'data'` mesh axis and params replicated. This single
compiled program subsumes the whole `nn.DataParallel` machinery the
reference's Readme dissects —
  scatter            (`Readme.md:19-29`)  → input NamedSharding P('data')
  replicate/broadcast (`Readme.md:30,49-56`) → param NamedSharding P()
  parallel_apply threads (`Readme.md:70-107`) → SPMD lockstep execution
  gather             (`Readme.md:109-143`) → outputs stay sharded; only
                                             scalar metrics are pulled back
— and the documented DDP C++ Reducer (`Readme.md:145-157`): XLA fuses and
overlaps the gradient all-reduce with the backward pass, which is exactly
what the bucketed Reducer hand-implements. Under plain jit, BatchNorm batch
statistics are computed over the *global* batch (SyncBN semantics) because
the mean is a global reduction.

`DDPEngine` (shard_map): the same step with *explicit* per-shard autodiff
and an explicit `lax.pmean` of the gradient pytree over `'data'` — the
declarative equivalent of DDP's ring all-reduce, kept for (a) per-replica
BatchNorm semantics faithful to `nn.DataParallel` (no SyncBN in reference
code), and (b) showing the collective structure explicitly, which also
gives XLA a single fused reduction instead of per-bucket ops.
`grad_reduction="bucketed"` swaps that monolithic pmean for the
Reducer-faithful path (`ops/grad_reduction.py`): ~`bucket_mb` flat
buckets in reverse registration order, each reduced as chunked ppermute
rings — hierarchically (reduce-scatter over 'ici', cross-slice
all-reduce over 'dcn' on the 1/N shard, all-gather back) when the mesh
is a hybrid `MeshSpec(dcn=K)` one. `grad_reduction="overlapped"` fires
those same buckets EAGERLY from a stagewise backward
(`models/staging.stagewise_value_and_grad`, INTERNALS §3f): per-segment
vjp closures run late-layers-first and hand each completed segment's
grads to the rings before the earlier segments' backward exists — the
Reducer's autograd-hook overlap, expressed as data dependence.

Both engines run on either mesh family: the data-parallel world is
`data_axis_names(mesh)` — ('data',) on a plain mesh, ('dcn', 'ici') on
a hybrid one — everywhere a batch is sharded or a gradient reduced.

Both engines produce bit-comparable training trajectories when BN modes
match (tested on the 8-device CPU mesh).
"""

from __future__ import annotations

import dataclasses
from functools import partial
from typing import Any, NamedTuple, Optional

import jax
import jax.numpy as jnp
from jax import lax
from jax.sharding import Mesh, NamedSharding, PartitionSpec as P
from distributed_model_parallel_tpu.runtime.compat import shard_map

from distributed_model_parallel_tpu.models import staging
from distributed_model_parallel_tpu.models.layers import Context, Layer
from distributed_model_parallel_tpu.ops.grad_reduction import (
    MONOLITHIC_BUCKET_MB,
    bucketed_pmean,
    data_replica_index,
)
from distributed_model_parallel_tpu.ops.wire_codec import (
    check_compression,
    require_dcn_axis,
)
from distributed_model_parallel_tpu.runtime.mesh import (
    data_axis_names,
    data_hierarchy_axes,
)
from distributed_model_parallel_tpu.training.metrics import (
    cross_entropy,
    label_rank,
    rank_correct,
    valid_count,
)
from distributed_model_parallel_tpu.training.optim import SGD, SGDState


def _place_batch(arrays, sharding: NamedSharding):
    """Host batch → global array sharded along 'data'.

    Single-host: a straight `device_put` split across local devices. On a
    multi-host mesh each host hands in only its *local* shard (the Loader's
    per-host contract), so the global array must be assembled from
    process-local data — `device_put` would wrongly treat the local shard
    as the full global batch.
    """
    if jax.process_count() == 1:
        return tuple(jax.device_put(a, sharding) for a in arrays)
    return tuple(
        jax.make_array_from_process_local_data(sharding, a) for a in arrays
    )


class TrainState(NamedTuple):
    """The replicated training pytree: the equivalent of the reference's
    (net.state_dict, optimizer, epoch) triple (`data_parallel.py:146-151`)."""

    params: Any
    model_state: Any  # BN running stats
    opt_state: Any  # optimizer NamedTuple (SGDState / AdamWState)
    step: jax.Array


def _cast_input(x, dtype):
    """Cast a floating input batch to the engine's compute dtype (mixed
    precision). Integer inputs (token ids) pass through — for those the
    cast happens at the first floating-point source layer via
    `Context.dtype` (see `models/layers.py` embedding)."""
    if dtype is None or not jnp.issubdtype(x.dtype, jnp.floating):
        return x
    return x.astype(dtype)


def _apply_input_transform(tf, x, step, train):
    """Run the engine's `input_transform` inside the compiled step.
    Transforms with `wants_ctx = True` (the device-cache pipeline,
    which needs per-step RNG and the train/eval distinction) receive
    (step, train); plain transforms (device_normalizer) get the batch
    alone."""
    if tf is None:
        return x
    if getattr(tf, "wants_ctx", False):
        return tf(x, step=step, train=train)
    return tf(x)


def aux_loss(state):
    """Sum of differentiable penalties layers stash in their post-forward
    state under the reserved key `"moe_aux"` (`models/moe.py`'s
    load-balance loss). The GSPMD engines (DP / DDP / TensorParallel /
    ExpertParallel) add this to the training loss they differentiate;
    metrics keep reporting plain cross-entropy. PipelineEngine and
    SequenceParallelEngine reject MoE models at construction (their
    losses live on one stage/shard, which would silently drop the aux
    leaves). Returns 0.0 (a no-op addend) when the model has no such
    layers."""
    total = 0.0
    for path, leaf in jax.tree_util.tree_leaves_with_path(state):
        if path and getattr(path[-1], "key", None) == "moe_aux":
            total = total + leaf
    return total


def _metrics(loss, logits, labels):
    # `loss` is the mean over valid rows; padding rows (label -1, from the
    # Loader's static-shape padding of a ragged final val batch) are
    # excluded from every numerator and denominator.
    n = valid_count(labels)
    rank = label_rank(logits, labels)
    return {
        "loss_sum": loss * n,
        "correct1": rank_correct(rank, labels, 1),
        "correct5": rank_correct(rank, labels, 5),
        "count": n,
    }


@dataclasses.dataclass
class DataParallelEngine:
    """GSPMD data parallelism: batch sharded on 'data', params replicated,
    collectives inserted by the XLA SPMD partitioner."""

    model: Layer
    optimizer: Any  # SGD | AdamW (init/update/state_shardings protocol)
    mesh: Mesh
    donate: bool = True
    # Mixed precision: activations/compute in this dtype (e.g. jnp.bfloat16
    # — the TPU MXU's native matmul dtype), params/optimizer/loss in f32.
    # None keeps the input dtype (f32 path).
    compute_dtype: Any = None
    # Applied to the input batch INSIDE the compiled step, before the
    # compute-dtype cast. Pair with `Loader(device_normalize=True)` /
    # `device_normalizer(mean, std)` so image batches cross the
    # host->device link as uint8 (4x fewer bytes than host-normalized
    # f32) and are normalized on device.
    input_transform: Any = None
    # NOTE: rematerialization lives at MODEL construction (per-block
    # `remat=True` on the model builders / `layers.remat`): a whole-model
    # checkpoint would re-live every residual at the start of backprop
    # and save no peak HBM.

    def __post_init__(self):
        mesh = self.mesh
        self._repl = NamedSharding(mesh, P())
        self._batch = NamedSharding(mesh, P(data_axis_names(mesh)))
        cdt = self.compute_dtype
        tf = self.input_transform
        model = self.model

        def train_step(ts: TrainState, images, labels, lr):
            # Deterministic per-step dropout key (global batch => one key;
            # the partitioner shards the mask with the activations).
            rng = jax.random.fold_in(jax.random.PRNGKey(0), ts.step)
            images_c = _cast_input(
                _apply_input_transform(tf, images, ts.step, True), cdt
            )

            def loss_fn(params, model_state):
                logits, new_state = model.apply(
                    params, model_state, images_c,
                    Context(train=True, rng=rng, dtype=cdt),
                )
                ce = cross_entropy(logits, labels)
                # MoE load-balance penalties ride the state (aux_loss
                # docstring); metrics stay plain CE.
                return ce + aux_loss(new_state), (new_state, logits, ce)

            (_, (new_state, logits, ce)), grads = jax.value_and_grad(
                loss_fn, has_aux=True
            )(ts.params, ts.model_state)
            params, opt_state = self.optimizer.update(
                ts.params, ts.opt_state, grads, lr
            )
            new_ts = TrainState(params, new_state, opt_state, ts.step + 1)
            return new_ts, _metrics(ce, logits, labels)

        def eval_step(ts: TrainState, images, labels):
            images_c = _cast_input(
                _apply_input_transform(tf, images, ts.step, False), cdt
            )
            logits, _ = self.model.apply(  # eval: no backward, no remat
                ts.params, ts.model_state, images_c,
                Context(train=False, dtype=cdt),
            )
            loss = cross_entropy(logits, labels)
            return _metrics(loss, logits, labels)

        donate = (0,) if self.donate else ()
        self.train_step = jax.jit(
            train_step,
            in_shardings=(self._repl, self._batch, self._batch, None),
            out_shardings=(self._repl, self._repl),
            donate_argnums=donate,
        )
        self.eval_step = jax.jit(
            eval_step,
            in_shardings=(self._repl, self._batch, self._batch),
            out_shardings=self._repl,
        )

    def init_state(self, rng: jax.Array) -> TrainState:
        params, model_state = self.model.init(rng)
        opt_state = self.optimizer.init(params)
        ts = TrainState(
            params, model_state, opt_state, jnp.zeros((), jnp.int32)
        )
        return jax.device_put(ts, self._repl)

    def shard_batch(self, images, labels):
        """Place a host batch onto the mesh, split along 'data' — the
        scatter that never touches a device 0."""
        return _place_batch((images, labels), self._batch)


@dataclasses.dataclass
class DDPEngine:
    """Explicit-collective data parallelism under `shard_map`.

    Per-shard forward/backward + one `lax.pmean` of the grad pytree =
    the DDP Reducer's bucketed ring all-reduce collapsed into a single
    fused collective (`Readme.md:14,145-157`).

    sync_bn=False (default) reproduces `nn.DataParallel`'s per-replica BN:
    each shard normalizes with its own batch statistics. Running stats are
    pmean-ed before persisting so the saved state is deterministic (the
    reference effectively keeps device-0 stats; documented deviation).
    sync_bn=True computes global batch statistics via pmean inside BN —
    the SyncBatchNorm the BERT config demands (BASELINE.json).
    """

    model: Layer
    optimizer: Any  # SGD | AdamW (init/update/state_shardings protocol)
    mesh: Mesh
    sync_bn: bool = False
    donate: bool = True
    compute_dtype: Any = None  # see DataParallelEngine
    input_transform: Any = None  # see DataParallelEngine
    # "monolithic": one fused pmean of the whole grad pytree (default —
    # the single-collective lowering). "bucketed": the DDP-Reducer path
    # (`ops/grad_reduction.py`) — `bucket_mb` flat buckets in reverse
    # registration order, each a chunked-ppermute ring reduce-scatter/
    # all-gather over the intra-slice fabric with a single cross-slice
    # all-reduce on the 1/N shard when the mesh carries a 'dcn' factor.
    # "overlapped": the bucketed path FIRED EAGERLY from a stagewise
    # backward (`models/staging.stagewise_value_and_grad`): the model is
    # cut at `overlap_stages` block boundaries, per-stage vjp closures
    # run in reverse, and stage k's bucket rings are handed off before
    # stage k-1's backward exists — so the reduction is data-dependent
    # only on stages >= k and XLA can schedule it beside the remaining
    # backward dots (the Reducer's autograd-hook overlap, Li VLDB'20).
    # Same math in all three (parity at rtol 1e-5,
    # tests/test_grad_reduction.py; dependency pins in
    # tests/test_collectives_hlo.py).
    grad_reduction: str = "monolithic"
    bucket_mb: float = 25.0
    # Backward segment count under "overlapped" (0 = auto: min(4, number
    # of model blocks)); cuts reuse the pipeline engines' block
    # partitioning (`models/staging.split_points`).
    overlap_stages: int = 0
    # MoE expert dispatch inside the shard_map step. None (default):
    # every replica computes ALL experts' dense einsums locally (plain
    # data parallelism). "hierarchical": the expert FFN is sharded 1/S
    # over the data fabric through the explicit two-level moe_ring
    # exchange (`ops/expert_dispatch.LocalExpertDispatch` — the
    # shard_map-level policy: weights stay replicated in storage, each
    # shard slices its E/S block by fabric index, and the data-axis
    # gradient reduction reassembles the block-disjoint cotangents).
    # Composes with grad_reduction="overlapped": the stagewise VJP's
    # per-stage moe_aux cotangent channel carries the router penalty
    # while each segment's bucket rings fire eagerly.
    expert_dispatch: Optional[str] = None
    # Chunk the hierarchical exchange so per-chunk expert FFN compute
    # overlaps the next hop (expert_dispatch="hierarchical" only).
    expert_overlap: bool = False
    # Compress the cross-slice 'dcn' hop of EVERY explicit exchange in
    # the step — the bucket reduction's per-bucket shard exchange and
    # the hierarchical MoE dispatch's regrouped messages — to this wire
    # dtype ("none" | "bf16" | "int8", `ops/wire_codec.py`). Master
    # weights, the intra-slice rings, and every accumulate stay in the
    # math dtype; requires a MeshSpec(dcn=K) factored mesh. Under
    # grad_reduction="monolithic" the reduction lowers through ONE flat
    # bucket per dtype (the monolithic pmean has no dcn seam to
    # compress), keeping the single-flat-buffer shape while the 'dcn'
    # hop rides the wire dtype.
    dcn_compression: str = "none"

    def __post_init__(self):
        if self.grad_reduction not in (
            "monolithic", "bucketed", "overlapped"
        ):
            raise ValueError(
                "grad_reduction must be 'monolithic', 'bucketed' or "
                f"'overlapped', got {self.grad_reduction!r}"
            )
        check_compression(self.dcn_compression)
        if self.expert_dispatch not in (None, "hierarchical"):
            raise ValueError(
                "expert_dispatch must be None or 'hierarchical', got "
                f"{self.expert_dispatch!r}"
            )
        if self.expert_overlap and self.expert_dispatch is None:
            raise ValueError(
                "expert_overlap=True chunks the hierarchical MoE "
                "exchange; set expert_dispatch='hierarchical'"
            )
        overlapped = self.grad_reduction == "overlapped"
        if overlapped:
            n_stages = staging.resolve_overlap_stages(
                self.model.parts, self.overlap_stages, "DDPEngine"
            )
            cuts = staging.split_points(
                n_stages, None, len(self.model.parts.blocks)
            )
            parts = self.model.parts
        mesh = self.mesh
        d_axes, ici_axis, dcn_axis = data_hierarchy_axes(mesh)
        wire = require_dcn_axis(self.dcn_compression, dcn_axis)
        self._repl = NamedSharding(mesh, P())
        self._batch = NamedSharding(mesh, P(d_axes))
        bn_axis = d_axes if self.sync_bn else None
        cdt = self.compute_dtype
        tf = self.input_transform
        model = self.model
        bucketed = self.grad_reduction == "bucketed"
        bucket_mb = self.bucket_mb
        ed = None
        if self.expert_dispatch == "hierarchical":
            from distributed_model_parallel_tpu.ops.expert_dispatch import (
                LocalExpertDispatch,
            )

            ed = LocalExpertDispatch(
                ici_axis=ici_axis, dcn_axis=dcn_axis,
                overlap=self.expert_overlap, dcn_compression=wire,
            )

        @partial(
            shard_map,
            mesh=mesh,
            in_specs=(P(), P(d_axes), P(d_axes), P()),
            out_specs=(P(), P()),
            check_vma=False,
        )
        def shard_step(ts: TrainState, images, labels, lr):
            # Per-shard dropout key: fold in the data-replica index so
            # every replica draws independent masks (per-replica
            # semantics, like the reference's per-device threads).
            rng = jax.random.fold_in(
                jax.random.fold_in(jax.random.PRNGKey(0), ts.step),
                data_replica_index(d_axes),
            )

            images_c = _cast_input(
                _apply_input_transform(tf, images, ts.step, True), cdt
            )
            ctx = Context(
                train=True, bn_axis=bn_axis, rng=rng, dtype=cdt,
                expert_dispatch=ed,
            )

            if overlapped:
                # Stagewise backward with eager bucket firing: stage
                # k's grads ride their rings while stage k-1 is still
                # differentiating (class docstring; the Reducer's
                # autograd-hook overlap as explicit data dependence).
                def reduce_stage(k, stage_grads):
                    with jax.named_scope(f"grad_reduce_stage{k}"):
                        return bucketed_pmean(
                            stage_grads, ici_axis, dcn_axis,
                            bucket_mb=bucket_mb, dcn_compression=wire,
                        )

                def loss_head(logits):
                    ce = cross_entropy(logits, labels)
                    return ce, (logits, ce)

                _, (logits, ce), stage_grads, stage_states = (
                    staging.stagewise_value_and_grad(
                        staging.stage_apply_fns(parts, cuts, ctx),
                        loss_head,
                        staging.partition_tree(ts.params, cuts),
                        staging.partition_tree(ts.model_state, cuts),
                        images_c,
                        aux_of_state=aux_loss,
                        on_stage_grads=reduce_stage,
                    )
                )
                grads = staging.unpartition_tree(stage_grads, cuts)
                new_state = staging.unpartition_tree(stage_states, cuts)
            else:
                def loss_fn(params, model_state):
                    logits, new_state = model.apply(
                        params, model_state, images_c, ctx
                    )
                    ce = cross_entropy(logits, labels)
                    return ce + aux_loss(new_state), (
                        new_state, logits, ce
                    )

                (_, (new_state, logits, ce)), grads = jax.value_and_grad(
                    loss_fn, has_aux=True
                )(ts.params, ts.model_state)
                if bucketed:
                    # The Reducer path: per-bucket rings, hierarchical
                    # over a dcn×ici mesh (`ops/grad_reduction.py`).
                    grads = bucketed_pmean(
                        grads, ici_axis, dcn_axis, bucket_mb=bucket_mb,
                        dcn_compression=wire,
                    )
                elif wire != "none":
                    # Monolithic + compression: one flat bucket per
                    # dtype through the hierarchical path, so the 'dcn'
                    # hop has a seam to compress (class docstring).
                    grads = bucketed_pmean(
                        grads, ici_axis, dcn_axis,
                        bucket_mb=MONOLITHIC_BUCKET_MB,
                        dcn_compression=wire,
                    )
                else:
                    # THE all-reduce: mean-over-global-batch gradient in
                    # one fused collective (replaces Reducer buckets +
                    # NCCL ring).
                    grads = lax.pmean(grads, d_axes)
            loss = ce
            if not self.sync_bn:
                # Deterministic persisted stats (see class docstring).
                new_state = lax.pmean(new_state, d_axes)
            params, opt_state = self.optimizer.update(
                ts.params, ts.opt_state, grads, lr
            )
            new_ts = TrainState(params, new_state, opt_state, ts.step + 1)
            m = _metrics(loss, logits, labels)
            m = jax.tree_util.tree_map(
                lambda v: lax.psum(v, d_axes), m
            )
            return new_ts, m

        @partial(
            shard_map,
            mesh=mesh,
            in_specs=(P(), P(d_axes), P(d_axes)),
            out_specs=P(),
            check_vma=False,
        )
        def shard_eval(ts: TrainState, images, labels):
            images_c = _cast_input(
                _apply_input_transform(tf, images, ts.step, False), cdt
            )
            logits, _ = self.model.apply(
                ts.params, ts.model_state, images_c,
                Context(train=False, dtype=cdt, expert_dispatch=ed),
            )
            loss = cross_entropy(logits, labels)
            m = _metrics(loss, logits, labels)
            return jax.tree_util.tree_map(
                lambda v: lax.psum(v, d_axes), m
            )

        donate = (0,) if self.donate else ()
        self.train_step = jax.jit(shard_step, donate_argnums=donate)
        self.eval_step = jax.jit(shard_eval)

    def init_state(self, rng: jax.Array) -> TrainState:
        params, model_state = self.model.init(rng)
        opt_state = self.optimizer.init(params)
        ts = TrainState(
            params, model_state, opt_state, jnp.zeros((), jnp.int32)
        )
        return jax.device_put(ts, self._repl)

    def shard_batch(self, images, labels):
        return _place_batch((images, labels), self._batch)
