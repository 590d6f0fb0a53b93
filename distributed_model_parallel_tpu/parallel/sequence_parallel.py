"""Sequence/context-parallel training engine — activations sharded over
`'seq'`.

Long-context training for the transformer family: token activations
(B, T, D) are sharded T/N per device over the `'seq'` mesh axis, so the
per-device activation (and attention working-set) memory scales 1/N with
the ring — the reason context parallelism exists. Attention is the only
cross-token op; it runs through `ops.ring_attention.ring_attention`
(K/V rotating over ICI, exact online-softmax) or `ulysses_attention`
(all-to-all head scatter). Everything else (LayerNorm, FFN, dropout) is
per-token and needs no communication. Parameters stay replicated
(compose with the 'model' axis / TensorParallelEngine for weight
sharding).

Mirrors the pipeline engine's autodiff discipline (`parallel/pipeline.py`):
the loss is computed ONLY on the shard that owns the [CLS] token (global
position 0 lives on seq-shard 0) and kept local — no psum before
`jax.grad` — so under `check_vma=False` no differentiated cross-device
reduction exists; the reversed ring permutes / all-to-alls alone carry
cotangents between shards, and the complementary per-shard param grads
are psum'd over 'seq' after grad (+ pmean over 'data').

The reference has nothing in this category (SURVEY.md §5: long-context
"entirely absent"); this engine exists because the framework treats
long-sequence training as first-class.
"""

from __future__ import annotations

import dataclasses
from functools import partial
from typing import Any

import jax
import jax.numpy as jnp
from jax import lax

from distributed_model_parallel_tpu.runtime.compat import shard_map
from jax.sharding import Mesh, NamedSharding, PartitionSpec as P

from distributed_model_parallel_tpu.models import layers as L
from distributed_model_parallel_tpu.models.bert import (
    BertConfig,
    _cls_head,
    _embeddings,
    _encoder_blocks,
    embed_apply,
    head_apply,
)
from distributed_model_parallel_tpu.ops.ring_attention import (
    ring_attention,
    ring_flash_attention,
    ulysses_attention,
)
from distributed_model_parallel_tpu.ops.grad_reduction import (
    MONOLITHIC_BUCKET_MB,
    bucketed_psum,
    data_replica_index,
)
from distributed_model_parallel_tpu.ops.head_loss import head_loss
from distributed_model_parallel_tpu.ops.wire_codec import require_dcn_axis
from distributed_model_parallel_tpu.parallel.data_parallel import (
    TrainState,
    _metrics,
    _place_batch,
)
from distributed_model_parallel_tpu.runtime.mesh import (
    data_hierarchy_axes,
)
from distributed_model_parallel_tpu.training.metrics import cross_entropy
from distributed_model_parallel_tpu.training.optim import SGD

def _ulysses_flash(*args, **kw):
    from distributed_model_parallel_tpu.ops.pallas_attention import (
        flash_attention,
    )

    return ulysses_attention(*args, attention_impl=flash_attention, **kw)


ATTENTION = {
    "ring": ring_attention,
    "ring_flash": ring_flash_attention,  # Pallas kernels per hop
    "ulysses": ulysses_attention,
    "ulysses_flash": _ulysses_flash,     # Pallas kernel as the local core
}


def _check_seq_len(ids, max_position: int, cfg_name: str) -> None:
    """Refuse global sequence lengths past the position table.

    Both SP engines' forwards slice the table with `dynamic_slice`, which
    CLAMPS out-of-range starts — shards past the table end would silently
    reuse the last position rows instead of failing like the dense stem's
    broadcast does. Validate in shard_batch, where the first real batch's
    T is known."""
    if ids.shape[1] > max_position:
        raise ValueError(
            f"global sequence length {ids.shape[1]} exceeds the "
            f"position table (max_position={max_position}); later 'seq' "
            f"shards would silently reuse position rows. Raise "
            f"{cfg_name}.max_position to at least the sequence length."
        )


def _seq_matmul_policy(enabled: bool, ffn_dim: int, seq_shards: int):
    """Collective-matmul policy for the SP engines (or None when off):
    `LocalCollectiveMatmul` over 'seq', FFN pair only — validated here so
    a non-divisible FFN width fails at construction, not an epoch in."""
    if not enabled:
        return None
    if ffn_dim % seq_shards:
        raise ValueError(
            f"collective_matmul=True chunks the FFN width over the "
            f"'seq' axis: intermediate/ffn dim {ffn_dim} must be "
            f"divisible by the {seq_shards} sequence shards"
        )
    from distributed_model_parallel_tpu.ops.collective_matmul import (
        LocalCollectiveMatmul,
    )

    return LocalCollectiveMatmul(axis="seq")


@dataclasses.dataclass
class SequenceParallelEngine:
    """BERT-family classification training with 'seq'-sharded activations.

    Parameters are IDENTICAL in structure to
    `bert_for_classification(num_classes, cfg)` — checkpoints and the
    transformers-weight transplant (tests/test_bert.py) interoperate.
    The global sequence length must be divisible by the 'seq' axis size
    (and, for 'ulysses', heads by the axis size)."""

    cfg: BertConfig
    num_classes: int
    optimizer: Any  # SGD | AdamW (init/update/state_shardings protocol)
    mesh: Mesh
    attention: str = "ring"
    donate: bool = True
    compute_dtype: Any = None
    # Rematerialize each transformer block during backward (jax.checkpoint).
    remat: bool = False
    # Latency-hiding collective matmul (default off): the FFN pair of
    # every block runs as chunked ppermute rings over 'seq' — each shard
    # slices its column/row block of the (replicated-in-storage) FFN
    # weights, gathers tokens via ag_matmul and scatters partial sums
    # back via matmul_rs, overlapping every hop with the chunk dot
    # (`ops/collective_matmul.py::LocalCollectiveMatmul`). Attention
    # projections stay local (their outputs feed the K/V ring). Same
    # math — parity pinned in tests/test_collective_matmul.py.
    collective_matmul: bool = False

    def __post_init__(self):
        mesh = self.mesh
        if "seq" not in mesh.axis_names:
            raise ValueError("sequence-parallel mesh needs a 'seq' axis")
        if self.attention not in ATTENTION:
            raise ValueError(
                f"attention must be one of {sorted(ATTENTION)}, "
                f"got {self.attention!r}"
            )
        cfg = self.cfg
        if getattr(cfg, "num_experts", 0) > 0:
            # MoE routing is per-shard under 'seq' sharding and the loss
            # lives on the [CLS] shard only, so the moe_aux load-balance
            # leaves would be silently dropped (and per-shard capacity
            # semantics differ from the dense model). Refuse loudly —
            # the GSPMD engines (DP/DDP/TP/EP) are the MoE path.
            raise NotImplementedError(
                "BertConfig.num_experts > 0 is not supported by "
                "SequenceParallelEngine; train MoE models with the "
                "DP / DDP / TensorParallel / ExpertParallel engines."
            )
        attn_fn = partial(ATTENTION[self.attention], axis_name="seq")
        self._matmul = _seq_matmul_policy(
            self.collective_matmul, cfg.intermediate_size,
            mesh.shape["seq"],
        )
        mm = self._matmul
        self._repl = NamedSharding(mesh, P())
        self._batch = NamedSharding(mesh, P(("data",), ("seq",)))
        self._labels = NamedSharding(mesh, P(("data",)))
        block_list = _encoder_blocks(cfg, attn_fn)
        if self.remat:
            block_list = [L.remat(b) for b in block_list]
        self._blocks = L.sequential(*block_list)
        self._full = L.named([
            ("stem", _embeddings(cfg)),
            ("blocks", self._blocks),
            ("head", _cls_head(cfg, self.num_classes)),
        ])
        self._ln = L.layernorm(cfg.hidden_size, eps=cfg.layer_norm_eps)
        self._drop = L.dropout(cfg.dropout_rate)
        # Encoder layers are stateless; sequential still wants its keyed
        # (empty) state dict.
        blocks_state = {str(i): {} for i in range(cfg.num_layers)}
        cdt = self.compute_dtype

        def forward(params, ids, ctx):
            """Seq-sharded forward on ONE device: local ids (Bl, Tl).
            The SAME stem/head math as the dense model (shared
            `embed_apply`/`head_apply` from models/bert.py), with the two
            position-dependent pieces made shard-aware: the position
            embedding slice starts at this shard's global offset, and the
            [CLS] pooler reads shard 0's local token 0."""
            tl = ids.shape[1]
            s_idx = lax.axis_index("seq")
            pos = lax.dynamic_slice_in_dim(
                params["stem"]["position"], s_idx * tl, tl, axis=0
            )
            h, mask = embed_apply(
                params["stem"], ids, cfg, self._ln, self._drop,
                ctx.child(0), positions=pos,
            )
            (h, _), _ = self._blocks.apply(
                params["blocks"], blocks_state, (h, mask), ctx.child(1)
            )
            logits = head_apply(params["head"], h[:, 0, :])
            # Only seq-shard 0's position 0 is the global [CLS]; other
            # shards' logits are garbage and masked out of loss/metrics.
            is_cls_shard = (s_idx == 0).astype(logits.dtype)
            return logits, is_cls_shard

        def shard_step(ts: TrainState, ids, labels, lr):
            rng = jax.random.fold_in(
                jax.random.fold_in(
                    jax.random.fold_in(jax.random.PRNGKey(0), ts.step),
                    lax.axis_index("data"),
                ),
                lax.axis_index("seq"),
            )
            ctx = L.Context(train=True, rng=rng, dtype=cdt, matmul=mm)

            def loss_fn(params):
                logits, is_cls = forward(params, ids, ctx)
                # Local loss (pipeline discipline: no psum before grad).
                loss = cross_entropy(logits, labels) * is_cls
                return loss, (logits, is_cls)

            (loss, (logits, is_cls)), grads = jax.value_and_grad(
                loss_fn, has_aux=True
            )(ts.params)
            # Per-shard grads are complementary pieces of the total
            # (each shard's tokens feed the rings); sum over 'seq',
            # average over 'data' — one fused all-reduce.
            grads = jax.tree_util.tree_map(
                lambda g: lax.pmean(lax.psum(g, "seq"), "data"), grads
            )
            params, opt_state = self.optimizer.update(
                ts.params, ts.opt_state, grads, lr
            )
            new_ts = TrainState(
                params, ts.model_state, opt_state, ts.step + 1
            )
            m = _metrics(loss, logits, labels)
            m = {
                k: lax.psum(v * is_cls, ("seq", "data"))
                for k, v in m.items()
            }
            return new_ts, m

        def shard_eval(ts: TrainState, ids, labels):
            logits, is_cls = forward(
                ts.params, ids,
                L.Context(train=False, dtype=cdt, matmul=mm),
            )
            loss = cross_entropy(logits, labels) * is_cls
            m = _metrics(loss, logits, labels)
            return {
                k: lax.psum(v * is_cls, ("seq", "data"))
                for k, v in m.items()
            }

        donate = (0,) if self.donate else ()
        self.train_step = jax.jit(
            shard_map(
                shard_step, mesh=mesh,
                in_specs=(P(), P(("data",), ("seq",)), P(("data",)), P()),
                out_specs=(P(), P()),
                check_vma=False,
            ),
            donate_argnums=donate,
        )
        self.eval_step = jax.jit(
            shard_map(
                shard_eval, mesh=mesh,
                in_specs=(P(), P(("data",), ("seq",)), P(("data",))),
                out_specs=P(),
                check_vma=False,
            )
        )

    def init_state(self, rng: jax.Array) -> TrainState:
        params, model_state = self._full.init(rng)
        opt_state = self.optimizer.init(params)
        ts = TrainState(
            params, model_state, opt_state, jnp.zeros((), jnp.int32)
        )
        return jax.device_put(ts, self._repl)

    def shard_batch(self, ids, labels):
        """ids shard over ('data', 'seq'); labels over 'data' only."""
        _check_seq_len(ids, self.cfg.max_position, "BertConfig")
        ids_arr = _place_batch((ids,), self._batch)[0]
        labels_arr = _place_batch((labels,), self._labels)[0]
        return ids_arr, labels_arr


@dataclasses.dataclass
class CausalLMSequenceParallelEngine:
    """Decoder-only (GPT-family) LANGUAGE-MODEL training with
    'seq'-sharded activations — the long-context path for `models/gpt`.

    Parameters are identical in structure to `gpt_lm(cfg)`, so dense
    checkpoints interoperate. Unlike the classification engine (whose
    loss lives on the [CLS] shard alone), the next-token loss decomposes
    per position: `shard_batch` builds targets on the HOST
    (`models.gpt.lm_targets` — shard-boundary tokens included) and
    shards them alongside the ids, so every shard scores its own tokens
    with NO differentiated cross-shard reduction. Per-shard gradients of
    the local loss SUM are complementary pieces of the total; one fused
    `psum('seq','data')` after `jax.grad`, divided by the global valid-
    token count, yields exactly the dense mean-loss gradient.

    The attention rings rotate K/V with `causal=True`: blocks arriving
    from later shards are fully hidden, the resident block is
    triangular (`ops/ring_attention.py`)."""

    # A configuration that answers `lm_family()` (models/lm_family.py):
    # models.gpt.GPTConfig, models.kimi_linear.KimiLinearConfig.
    cfg: Any
    optimizer: Any  # SGD | AdamW (init/update/state_shardings protocol)
    mesh: Mesh
    attention: str = "ring"
    donate: bool = True
    compute_dtype: Any = None
    remat: bool = False
    # FFN pair as chunked ppermute rings over 'seq' (default off) — see
    # SequenceParallelEngine.collective_matmul.
    collective_matmul: bool = False
    # Gradient reduction over the DATA axes (the 'seq' psum is separate:
    # per-shard grads are complementary pieces, summed first either
    # way). "monolithic": one fused psum over ('seq', data axes).
    # "bucketed": Reducer-style flat buckets over the data fabric(s) —
    # ring reduce-scatter over 'ici', cross-slice all-reduce over 'dcn',
    # ring all-gather (`ops/grad_reduction.py`); hierarchy-aware on a
    # `MeshSpec(dcn=K)` mesh. "overlapped": the bucketed path fired
    # EAGERLY from a stagewise backward — decoder blocks are cut into
    # `overlap_stages` segments (`models/staging.split_points`), per-
    # segment vjp closures run late-layers-first, and each completed
    # segment's 'seq' psum + data-bucket rings launch before the earlier
    # segments' backward exists (tests/test_collectives_hlo.py pins the
    # dependency structure; parity in tests/test_grad_reduction.py).
    grad_reduction: str = "monolithic"
    bucket_mb: float = 25.0
    # Backward segment count under "overlapped" (0 = auto: min(4,
    # cfg.num_layers)).
    overlap_stages: int = 0
    # Compress the cross-slice 'dcn' hop of the DATA-axis bucket
    # reduction to this wire dtype ("none" | "bf16" | "int8",
    # `ops/wire_codec.py`) — the 'seq' psum (complementary per-shard
    # pieces, intra-slice) stays in the math dtype. Requires a
    # MeshSpec(dcn=K) mesh; under grad_reduction="monolithic" the data
    # reduction lowers through one flat bucket per dtype so the 'dcn'
    # hop has a seam to compress (see DDPEngine.dcn_compression).
    dcn_compression: str = "none"

    def __post_init__(self):
        mesh = self.mesh
        if "seq" not in mesh.axis_names:
            raise ValueError("sequence-parallel mesh needs a 'seq' axis")
        if self.attention not in ATTENTION:
            raise ValueError(
                f"attention must be one of {sorted(ATTENTION)}, "
                f"got {self.attention!r}"
            )
        if self.grad_reduction not in (
            "monolithic", "bucketed", "overlapped"
        ):
            raise ValueError(
                "grad_reduction must be 'monolithic', 'bucketed' or "
                f"'overlapped', got {self.grad_reduction!r}"
            )
        d_axes, ici_axis, dcn_axis = data_hierarchy_axes(mesh)
        bucketed = self.grad_reduction == "bucketed"
        overlapped = self.grad_reduction == "overlapped"
        bucket_mb = self.bucket_mb
        wire = require_dcn_axis(self.dcn_compression, dcn_axis)
        # Monolithic + compression routes the data reduction through
        # one flat bucket per dtype (class docstring).
        use_buckets = bucketed or (wire != "none" and not overlapped)
        data_bucket_mb = (
            bucket_mb if self.grad_reduction != "monolithic"
            else MONOLITHIC_BUCKET_MB
        )
        # The one seam to the model family (`models/lm_family.py`):
        # init, stem, blocks, head, targets. Nothing below spells a
        # family's fields.
        fam = self.cfg.lm_family()
        self._family = fam
        if fam.refused:
            raise NotImplementedError(fam.refused)
        if mesh.shape["seq"] > 1 and fam.seq_shards_missing:
            raise NotImplementedError(
                f"{fam.name}: a mesh with 'seq' = {mesh.shape['seq']} "
                f"is not supported: {fam.seq_shards_missing}"
            )
        if overlapped and fam.counters is not None:
            raise NotImplementedError(
                f"{fam.name}: grad_reduction='overlapped' cuts the "
                "block stack into stagewise segments that carry no "
                "block state, which this family's expert layers need"
            )
        if overlapped:
            if fam.num_layers < 2:
                raise ValueError(
                    "CausalLMSequenceParallelEngine: grad_reduction="
                    "'overlapped' splits the decoder stack into >= 2 "
                    f"backward segments; cfg.num_layers={fam.num_layers}"
                )
            from distributed_model_parallel_tpu.models.staging import (
                resolve_overlap_segments,
                split_points,
            )

            n_over = resolve_overlap_segments(
                fam.num_layers, self.overlap_stages,
                "CausalLMSequenceParallelEngine", noun="decoder blocks",
            )
            over_cuts = split_points(n_over, None, fam.num_layers)
        attn_fn = partial(
            ATTENTION[self.attention], axis_name="seq", causal=True
        )
        self._matmul = _seq_matmul_policy(
            self.collective_matmul, fam.ffn_dim, mesh.shape["seq"]
        )
        mm = self._matmul
        self._repl = NamedSharding(mesh, P())
        self._batch = NamedSharding(mesh, P(d_axes, ("seq",)))
        # Dense-parameter twin used ONLY for init (identical pytree).
        self._full = fam.model()
        block_list = fam.blocks(attn_fn)
        if self.remat:
            block_list = [L.remat(b) for b in block_list]
        blocks = L.sequential(*block_list)
        cdt = self.compute_dtype

        def forward(params, blocks_state, ids, targets, ctx):
            """Per-shard forward: local ids and targets (Bl, Tl) ->
            the step's metric SUMS over this shard's tokens (the shared
            `_metrics` contract on the flattened token axis) with the
            family's step counters. The SAME stem/head math as the
            dense model, the stem told this shard's index so that what
            depends on position starts at the shard's global offset;
            the head's product and the loss are one op
            (`ops/head_loss.py`), which never holds the logits whole.
            `blocks_state` is the blocks' non-trained buffers
            (`TrainState.model_state["blocks"]`); the state the blocks
            hand back carries the counters and is not kept."""
            h, mask = fam.stem(
                params["stem"], ids, ctx.child(0), lax.axis_index("seq")
            )
            (h, _), after = blocks.apply(
                params["blocks"], blocks_state, (h, mask), ctx.child(1)
            )
            counters = fam.counters(after) if fam.counters else {}
            rows, matrix = fam.head_operands(params["head"], h)
            return {**head_loss(rows, matrix, targets), **counters}

        def local_sums(logits, targets):
            """The same sums from logits, for the stagewise backward,
            whose last segment closes with the head."""
            b, tl, v = logits.shape
            flat_logits = logits.reshape(b * tl, v)
            flat_t = targets.reshape(b * tl)
            return _metrics(
                cross_entropy(flat_logits, flat_t), flat_logits, flat_t
            )

        # How each step metric combines over shards and, for the
        # Trainer and `training/multistep.py`, over steps: the family's
        # counters say; every other metric is a sum.
        self.metric_reductions = dict(fam.counter_reductions)
        over_shards = {"sum": lax.psum, "max": lax.pmax}

        def reduced(m):
            """The step's metrics over every shard."""
            return {
                k: over_shards[self.metric_reductions.get(k, "sum")](
                    v, reduce_axes
                ) for k, v in m.items()
            }

        def overlap_stage_fns(ctx):
            """Per-segment closures for the stagewise backward: the SAME
            stem/blocks/head math as `forward` (identical Context.child
            folding: stem -> ctx.child(0), block j -> ctx.child(1)
            .child(j)), cut at `over_cuts` block boundaries. Stage 0
            takes the local ids; the (hidden, mask) pair rides between
            segments; the LM head closes the last one."""
            block_ctx = ctx.child(1)
            fns = []
            n_over = len(over_cuts) - 1
            for i in range(n_over):
                def fn(p, _state, x, i=i):
                    k = 0
                    if i == 0:
                        y = fam.stem(
                            p["0"], x, ctx.child(0),
                            lax.axis_index("seq"),
                        )
                        k = 1
                    else:
                        y = x
                    for j in range(over_cuts[i], over_cuts[i + 1]):
                        y, _ = block_list[j].apply(
                            p[str(k)], {}, y, block_ctx.child(j)
                        )
                        k += 1
                    if i == n_over - 1:
                        h, _mask = y
                        y = fam.head(p[str(k)], h)
                    return y, {}

                fns.append(fn)
            return fns

        reduce_axes = ("seq",) + d_axes

        def shard_step(ts: TrainState, ids, targets, lr):
            rng = jax.random.fold_in(
                jax.random.fold_in(
                    jax.random.fold_in(jax.random.PRNGKey(0), ts.step),
                    data_replica_index(d_axes),
                ),
                lax.axis_index("seq"),
            )
            ctx = L.Context(train=True, rng=rng, dtype=cdt, matmul=mm)

            if overlapped:
                from distributed_model_parallel_tpu.models.staging import (
                    partition_tree,
                    stagewise_value_and_grad,
                    unpartition_tree,
                )

                def loss_head(logits):
                    m = local_sums(logits, targets)
                    # LOCAL token-loss sum (pipeline discipline: no
                    # psum before grad).
                    return m["loss_sum"], m

                def reduce_stage(k, stage_grads):
                    # 'seq' first (complementary per-shard pieces),
                    # then the Reducer buckets over the data fabric(s)
                    # — fired while earlier segments still
                    # differentiate.
                    with jax.named_scope(f"grad_reduce_stage{k}"):
                        return bucketed_psum(
                            jax.tree_util.tree_map(
                                lambda g: lax.psum(g, "seq"),
                                stage_grads,
                            ),
                            ici_axis, dcn_axis, bucket_mb=bucket_mb,
                            dcn_compression=wire,
                        )

                stage_params = partition_tree(ts.params, over_cuts)
                _, m, stage_grads, _ = stagewise_value_and_grad(
                    overlap_stage_fns(ctx), loss_head, stage_params,
                    [None] * (len(over_cuts) - 1), ids,
                    on_stage_grads=reduce_stage,
                )
                n_global = lax.psum(m["count"], reduce_axes)
                grads = jax.tree_util.tree_map(
                    lambda g: g / jnp.maximum(n_global, 1.0),
                    unpartition_tree(stage_grads, over_cuts),
                )
            else:
                def loss_fn(params):
                    m = forward(
                        params, ts.model_state["blocks"], ids, targets,
                        ctx,
                    )
                    # LOCAL token-loss sum (pipeline discipline: no psum
                    # before grad).
                    return m["loss_sum"], m

                (_, m), grads = jax.value_and_grad(
                    loss_fn, has_aux=True
                )(ts.params)
                n_global = lax.psum(m["count"], reduce_axes)
                if use_buckets:
                    # 'seq' first (complementary per-shard pieces — one
                    # fused psum over the TP-style axis), then the
                    # Reducer-style buckets over the data fabric(s).
                    grads = bucketed_psum(
                        jax.tree_util.tree_map(
                            lambda g: lax.psum(g, "seq"), grads
                        ),
                        ici_axis, dcn_axis, bucket_mb=data_bucket_mb,
                        dcn_compression=wire,
                    )
                    grads = jax.tree_util.tree_map(
                        lambda g: g / jnp.maximum(n_global, 1.0), grads
                    )
                else:
                    grads = jax.tree_util.tree_map(
                        lambda g: lax.psum(g, reduce_axes)
                        / jnp.maximum(n_global, 1.0),
                        grads,
                    )
            params, opt_state = self.optimizer.update(
                ts.params, ts.opt_state, grads, lr
            )
            new_ts = TrainState(
                params, ts.model_state, opt_state, ts.step + 1
            )
            return new_ts, reduced(m)

        def shard_eval(ts: TrainState, ids, targets):
            return reduced(forward(
                ts.params, ts.model_state["blocks"], ids, targets,
                L.Context(train=False, dtype=cdt, matmul=mm),
            ))

        donate = (0,) if self.donate else ()
        self.train_step = jax.jit(
            shard_map(
                shard_step, mesh=mesh,
                in_specs=(
                    P(), P(d_axes, ("seq",)), P(d_axes, ("seq",)),
                    P(),
                ),
                out_specs=(P(), P()),
                check_vma=False,
            ),
            donate_argnums=donate,
        )
        self.eval_step = jax.jit(
            shard_map(
                shard_eval, mesh=mesh,
                in_specs=(
                    P(), P(d_axes, ("seq",)), P(d_axes, ("seq",)),
                ),
                out_specs=P(),
                check_vma=False,
            )
        )

    def init_state(self, rng: jax.Array) -> TrainState:
        params, model_state = self._full.init(rng)
        opt_state = self.optimizer.init(params)
        ts = TrainState(
            params, model_state, opt_state, jnp.zeros((), jnp.int32)
        )
        return jax.device_put(ts, self._repl)

    def shard_batch(self, ids, labels=None):
        """ids (B, T) -> (ids, next-token targets), both sharded over
        ('data', 'seq'). `labels` is ignored (the LM's targets are the
        shifted ids); the parameter keeps the engine signature-uniform
        with the classification engines."""
        _check_seq_len(
            ids, self._family.max_position, type(self.cfg).__name__
        )
        targets = self._family.targets(ids)
        ids_arr = _place_batch((ids,), self._batch)[0]
        targets_arr = _place_batch((targets,), self._batch)[0]
        return ids_arr, targets_arr
