"""Multi-step compiled dispatch — k optimizer steps per device program.

The reference's hot loop (`utils.py:42-72`) is one CUDA launch sequence
per Python iteration; CUDA's stream queue hides the per-step launch
latency. Under JAX each per-step `jit` dispatch is a host round trip
that async dispatch hides only while the host stays ahead of the
device; when the step is short the dispatch is what the loop waits on
(its share on the v5e: not measured).

`compile_multi_step(engine, k)` removes it structurally: ONE jitted
program stacks k already-sharded batches and runs k sequential train
steps under `lax.scan`, so the per-step trajectory (step counter,
dropout folding, optimizer updates) matches k separate
`engine.train_step` calls to numerical tolerance (same math; XLA may
fuse across step boundaries differently — pinned at rtol 1e-5 by
tests/test_trainer.py) while the host pays one dispatch per k steps. Batches still transfer
asynchronously one by one (`shard_batch`), so input staging overlaps
the previous group's compute.

Works with any engine exposing the uniform protocol
`train_step(state, x, y, lr) -> (state, metrics)`: the engine's own
jitted step (jit- or shard_map-built) is traced inline into the scan
body, keeping its sharding annotations as constraints. That includes
steps that are themselves scans — PipelineEngine's tick programs (both
the gpipe fill-drain and the hand-scheduled 1f1b forward+backward) nest
as inner scans, pinned by tests/test_pipeline_schedule.py.
"""

from __future__ import annotations

from typing import Any, Callable, Tuple

import jax
import jax.numpy as jnp
from jax import lax


_PAIR = {"sum": jnp.add, "max": jnp.maximum}
_OVER = {"sum": jnp.sum, "max": jnp.max}


def add_step_metrics(a: dict, b: dict, reductions=None) -> dict:
    """Two steps' metric dicts into one. An engine's metrics are sums;
    one whose step has another kind says so in `metric_reductions`
    ({name: "sum" | "max"}, names not listed are sums), which the
    caller hands in."""
    if not reductions:
        return jax.tree_util.tree_map(jnp.add, a, b)
    return {
        k: jax.tree_util.tree_map(
            _PAIR[reductions.get(k, "sum")], a[k], b[k]
        ) for k in a
    }


def _over_steps(per_step: dict, reductions=None) -> dict:
    """Metrics stacked on a leading step axis -> one dict, by the rule
    of `add_step_metrics`."""
    if not reductions:
        return jax.tree_util.tree_map(
            lambda x: jnp.sum(x, axis=0), per_step
        )
    return {
        k: jax.tree_util.tree_map(
            lambda x, how=reductions.get(k, "sum"): _OVER[how](x, axis=0),
            v,
        ) for k, v in per_step.items()
    }


def compile_multi_step(engine: Any, k: int) -> Callable:
    """Build `fn(state, batches, lr) -> (state, summed_metrics)` running
    `k` train steps in one compiled program.

    `batches` is a tuple of `k` batch tuples as returned by
    `engine.shard_batch` (already device-placed). The returned metrics
    dict holds the SUM over the k steps of the engine's per-step metric
    sums — the same value accumulating k per-step results would give.

    k=1 is a passthrough: a one-step scan whose state/metrics match a
    single `engine.train_step` call (pinned in tests/test_multistep.py)
    — callers can treat every dispatch uniformly instead of special-
    casing the last short group of an epoch.
    """
    if k < 1:
        raise ValueError(f"steps_per_dispatch must be >= 1, got {k}")
    reductions = getattr(engine, "metric_reductions", None)

    def k_steps(state, batches: Tuple, lr):
        # Leaf-wise stack of the k batch tuples -> scan operands with a
        # leading step axis. Device-side: the k inputs were placed by
        # shard_batch; the stack is a cheap on-device concatenation.
        stacked = jax.tree_util.tree_map(
            lambda *xs: jnp.stack(xs), *batches
        )

        def body(s, batch):
            s2, m = engine.train_step(s, *batch, lr)
            return s2, m

        state, per_step = lax.scan(body, state, stacked)
        return state, _over_steps(per_step, reductions)

    return jax.jit(k_steps, donate_argnums=(0,))


def compile_multi_eval(engine: Any, k: int) -> Callable:
    """Eval twin of `compile_multi_step`: `fn(state, batches) ->
    summed_metrics` evaluating k batches in one compiled program
    (state is read-only — no carry, a plain scan over the stack).
    k=1 is a passthrough, like `compile_multi_step`."""
    if k < 1:
        raise ValueError(f"steps_per_dispatch must be >= 1, got {k}")
    reductions = getattr(engine, "metric_reductions", None)

    def k_evals(state, batches: Tuple):
        stacked = jax.tree_util.tree_map(
            lambda *xs: jnp.stack(xs), *batches
        )

        def body(carry, batch):
            return carry, engine.eval_step(state, *batch)

        _, per_step = lax.scan(body, 0, stacked)
        return _over_steps(per_step, reductions)

    return jax.jit(k_evals)


def group_batches(iterator, k: int):
    """Pull up to `k` items from `iterator`; a short list means the
    iterator was exhausted (the caller's per-step fallback path)."""
    group = []
    while len(group) < k:
        try:
            group.append(next(iterator))
        except StopIteration:
            break
    return group


__all__ = [
    "add_step_metrics", "compile_multi_eval", "compile_multi_step",
    "group_batches",
]
