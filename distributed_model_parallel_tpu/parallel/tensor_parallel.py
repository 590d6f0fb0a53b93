"""Tensor parallelism over the `'model'` mesh axis — GSPMD style.

The reference has no tensor parallelism (SURVEY.md §2.3: absent); this
engine exists because the framework treats the `'model'` axis as
first-class (`runtime/mesh.py`). The design is deliberately NOT a
Megatron-style hand-written f/g collective pair: on TPU the idiomatic
mechanism is sharding ANNOTATIONS — place the Megatron layout on the
weight pytree and let XLA's SPMD partitioner insert the all-reduces the
f/g autograd functions hand-code on GPU:

    column-parallel (qkv / ffn-in):  W (D, kD)  -> P(None, 'model')
    row-parallel    (attn-out / ffn-out): W (kD, D) -> P('model', None)
    column-parallel bias (kD,)       -> P('model')
    everything else (LN, embeddings, head) replicated -> P()

The partitioner propagates: activations after a column-parallel matmul
are head/feature-sharded, the attention einsum runs head-sharded, and the
row-parallel matmul produces the partial sums whose psum over 'model' XLA
inserts exactly where Megatron's `g` function calls all_reduce. Gradient
collectives come out of the transpose automatically.

Composes with data parallelism on a (data, model) mesh: batch sharded
over 'data', weights over 'model', one jit program for both.

`MEGATRON_RULES` matches the transformer/BERT layer tree
(`models/transformer.py`, `models/bert.py`); `rules` accepts any
(path-regex, PartitionSpec) list for other model families.
"""

from __future__ import annotations

import dataclasses
import re
from typing import Any, Sequence, Tuple

import jax
import jax.numpy as jnp
from jax.sharding import Mesh, NamedSharding, PartitionSpec as P

from distributed_model_parallel_tpu.models.layers import Context, Layer
from distributed_model_parallel_tpu.parallel.data_parallel import (
    TrainState,
    _apply_input_transform,
    _cast_input,
    _metrics,
    _place_batch,
    aux_loss,
)
from distributed_model_parallel_tpu.runtime.mesh import data_axis_names
from distributed_model_parallel_tpu.training.checkpoint import _path_str
from distributed_model_parallel_tpu.training.metrics import cross_entropy
from distributed_model_parallel_tpu.training.optim import SGD

# Megatron sharding layout for the transformer block tree
# (models/transformer.py param paths: attn.qkv/attn.out, ffn.in/ffn.out).
MEGATRON_RULES: Tuple[Tuple[str, P], ...] = (
    (r"attn/qkv/w$", P(None, "model")),
    (r"attn/qkv/b$", P("model")),
    (r"attn/out/w$", P("model", None)),
    (r"ffn/in/w$", P(None, "model")),
    (r"ffn/in/b$", P("model")),
    (r"ffn/out/w$", P("model", None)),
)


def shard_specs(params, rules: Sequence[Tuple[str, P]]):
    """Pytree of PartitionSpecs for `params`: first rule whose regex
    matches the 'a/b/c' path wins; unmatched leaves are replicated."""
    compiled = [(re.compile(pat), spec) for pat, spec in rules]

    def spec_of(path, leaf):
        s = _path_str(path)
        for pat, spec in compiled:
            if pat.search(s):
                return spec
        return P()

    return jax.tree_util.tree_map_with_path(spec_of, params)


@dataclasses.dataclass
class TensorParallelEngine:
    """GSPMD tensor(+data) parallelism: weights sharded over 'model' by
    path rules, batch sharded over 'data', XLA inserts the Megatron
    collectives. API-compatible with the other engines (train_step /
    eval_step / shard_batch / init_state)."""

    model: Layer
    optimizer: Any  # SGD | AdamW (init/update/state_shardings protocol)
    mesh: Mesh
    rules: Sequence[Tuple[str, P]] = MEGATRON_RULES
    donate: bool = True
    compute_dtype: Any = None  # see DataParallelEngine
    input_transform: Any = None  # see DataParallelEngine
    # Latency-hiding collective matmul (default off): run the opted-in
    # Megatron projections as chunked ppermute rings that overlap each
    # ICI hop with the partial dot already on hand, instead of leaving
    # the partitioner's monolithic collectives to the scheduler
    # (`ops/collective_matmul.py`). Same math (parity pinned at rtol
    # 1e-5 in tests/test_collective_matmul.py); between blocks the
    # residual stream rides sequence-sharded over 'model' (Megatron-SP).
    # Transformer-family models only: the policy reaches the qkv/out and
    # ffn in/out projections through `Context.matmul` -> layers.project.
    collective_matmul: bool = False
    # (remat lives at model construction — see DataParallelEngine note)

    def __post_init__(self):
        mesh = self.mesh
        # The mesh must carry every axis the rules shard over ('model'
        # for MEGATRON_RULES, 'expert' for EXPERT_RULES, both when the
        # rule sets are concatenated).
        needed = set()
        for _, spec in self.rules:
            for part in spec:
                if part is None:
                    continue
                parts = part if isinstance(part, tuple) else (part,)
                needed.update(parts)
        missing = needed - set(mesh.axis_names)
        if missing:
            raise ValueError(
                f"mesh is missing axes {sorted(missing)} required by the "
                f"sharding rules (mesh axes: {mesh.axis_names})"
            )
        self._repl = NamedSharding(mesh, P())
        self._batch = NamedSharding(mesh, P(data_axis_names(mesh)))
        self._matmul = None
        if self.collective_matmul:
            if "model" not in mesh.axis_names:
                raise ValueError(
                    "collective_matmul=True needs a 'model' mesh axis to "
                    "ring over (the Megatron projection axis); this mesh "
                    f"has {mesh.axis_names}"
                )
            from distributed_model_parallel_tpu.ops.collective_matmul import (
                CollectiveMatmul,
            )

            self._matmul = CollectiveMatmul(
                mesh=mesh, axis="model",
                batch_axes=tuple(
                    a for a in data_axis_names(mesh)
                    if a in mesh.axis_names
                ),
            )
        mm = self._matmul
        # Hand-rolled MoE exchange policy, set by ExpertParallelEngine
        # (dispatch="hierarchical") BEFORE delegating here; consumed by
        # models/moe.py via Context.expert_dispatch.
        ed = getattr(self, "_expert_dispatch", None)
        cdt = self.compute_dtype
        tf = self.input_transform
        model = self.model

        def train_step(ts: TrainState, inputs, labels, lr):
            rng = jax.random.fold_in(jax.random.PRNGKey(0), ts.step)
            inputs_c = _cast_input(
                _apply_input_transform(tf, inputs, ts.step, True), cdt
            )

            def loss_fn(params, model_state):
                logits, new_state = model.apply(
                    params, model_state, inputs_c,
                    Context(train=True, rng=rng, dtype=cdt, matmul=mm,
                            expert_dispatch=ed),
                )
                loss, m = self.loss_and_metrics(logits, labels)
                return loss + aux_loss(new_state), (new_state, m)

            (_, (new_state, m)), grads = jax.value_and_grad(
                loss_fn, has_aux=True
            )(ts.params, ts.model_state)
            params, opt_state = self.optimizer.update(
                ts.params, ts.opt_state, grads, lr
            )
            new_ts = TrainState(params, new_state, opt_state, ts.step + 1)
            return new_ts, m

        def eval_step(ts: TrainState, inputs, labels):
            inputs_c = _cast_input(
                _apply_input_transform(tf, inputs, ts.step, False), cdt
            )
            logits, _ = self.model.apply(
                ts.params, ts.model_state, inputs_c,
                Context(train=False, dtype=cdt, matmul=mm,
                        expert_dispatch=ed),
            )
            _, m = self.loss_and_metrics(logits, labels)
            return m

        # State shardings are fixed by the rules and the model structure
        # (known from an abstract trace of init); jit pins them in/out so
        # the partitioner keeps weights resident in their 'model' shards
        # across steps (no per-step resharding).
        key_aval = jax.ShapeDtypeStruct((2,), jnp.uint32)
        p_aval, s_aval = jax.eval_shape(self.model.init, key_aval)
        pspecs = self.param_specs(p_aval)
        # The spec seam: the PartitionSpec pytree for the whole
        # TrainState, exposed via `state_partition_specs` so checkpoint
        # tooling and tests can read the engine's layout without
        # reverse-engineering it from live arrays.
        self._state_pspecs = TrainState(
            pspecs,
            jax.tree_util.tree_map(lambda _: P(), s_aval),
            self.optimizer.state_shardings(pspecs, P()),
            P(),
        )
        param_sh = jax.tree_util.tree_map(
            lambda spec: NamedSharding(mesh, spec),
            pspecs,
            is_leaf=lambda x: isinstance(x, P),
        )
        self._state_sh = TrainState(
            param_sh,
            jax.tree_util.tree_map(lambda _: self._repl, s_aval),
            # Optimizer buffers shard exactly like their parameters
            # (each optimizer declares its own state layout).
            self.optimizer.state_shardings(param_sh, self._repl),
            self._repl,
        )
        sh = self._state_sh
        donate = (0,) if self.donate else ()
        self.train_step = jax.jit(
            train_step,
            in_shardings=(sh, self._batch, self._batch, None),
            out_shardings=(sh, self._repl),
            donate_argnums=donate,
        )
        self.eval_step = jax.jit(
            eval_step,
            in_shardings=(sh, self._batch, self._batch),
            out_shardings=self._repl,
        )

    def loss_and_metrics(self, logits, labels):
        """The differentiated loss + engine metrics for one batch —
        classification cross-entropy here; `ExpertParallelLMEngine`
        overrides with the token-level next-token loss. The scalar is
        what `train_step` differentiates (MoE aux penalties are added
        by the caller); metrics keep the `_metrics` psum contract."""
        ce = cross_entropy(logits, labels)
        return ce, _metrics(ce, logits, labels)

    def param_specs(self, p_aval):
        """PartitionSpec pytree for the parameters — rule-driven here;
        subclasses (FSDPEngine) override with shape-driven policies."""
        return shard_specs(p_aval, self.rules)

    def init_state(self, rng: jax.Array) -> TrainState:
        params, model_state = self.model.init(rng)
        opt_state = self.optimizer.init(params)
        ts = TrainState(
            params, model_state, opt_state, jnp.zeros((), jnp.int32)
        )
        return jax.device_put(ts, self._state_sh)

    # ---------------------------------------------- checkpoint canonical

    def to_canonical(self, ts: TrainState) -> TrainState:
        """Host-complete (numpy) TrainState for checkpointing. On a
        multi-host mesh this engine's params and optimizer moments are
        sharded across processes ('model' rules here, 'data' under
        FSDPEngine) and thus NOT fully addressable — a bare
        `jax.device_get` in `save_checkpoint` would crash exactly on the
        ZeRO-3/TP deployments that shard. Leaves
        are all-gathered one at a time (`tree_to_host`), so the device
        transient is a single unsharded leaf. COLLECTIVE on a
        multi-process mesh: every process must call this together."""
        from distributed_model_parallel_tpu.training.checkpoint import (
            tree_to_host,
        )

        return tree_to_host(ts)

    def from_canonical(self, ts: TrainState) -> TrainState:
        """Place a canonical (host-complete) TrainState back into this
        engine's sharded runtime layout. All processes must pass the
        same values (restore_checkpoint broadcasts host-0's read).

        This is also the RESHARD seam (`checkpointing/restore.py`): the
        canonical form carries no mesh, so a checkpoint taken at one
        factorization (S=4 FSDP, a 2×2 dcn×ici hybrid, ...) lands here
        as full host arrays and this device_put re-slices them for the
        CURRENT mesh — elastic resize needs no format conversion."""
        return jax.device_put(ts, self._state_sh)

    def to_canonical_sharded(self, ts: TrainState) -> TrainState:
        """Sharded-checkpoint seam (`checkpointing/save.py`): this
        engine's runtime TrainState already has canonical TREE
        structure — `to_canonical` only gathers values to host — so the
        sharded save path persists the device-sharded leaves directly.
        Each process then writes only its addressable chunks and the
        per-leaf `process_allgather` of the legacy path is never
        reached (pinned in tests/test_checkpoint_sharded.py). Engines
        whose canonical form RESTRUCTURES state (pipeline stage-local
        packing) deliberately do not define this method; the trainer
        falls back with an actionable error."""
        return ts

    def state_partition_specs(self) -> TrainState:
        """The PartitionSpec pytree of the runtime TrainState layout —
        what a sharded checkpoint manifest records per leaf."""
        return self._state_pspecs

    def shard_batch(self, inputs, labels):
        return _place_batch((inputs, labels), self._batch)
