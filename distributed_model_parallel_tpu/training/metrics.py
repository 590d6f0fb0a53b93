"""Loss + accuracy metrics matching the reference trainer.

* cross-entropy from logits = `nn.CrossEntropyLoss` (`data_parallel.py:89`)
* `accuracy(output, target, topk=(1,5))` = `utils.py:215-229`, returning
  percentages. The reference sorts with `output.topk`; here the label's
  rank is counted directly (`label_rank`), with a top-k's tie order
  (equal logits: lower index first), so the counts are the same, exactly.
* `Meter` = the running averages the reference accumulates by hand
  (`utils.py:36-76`: batch_time_avg / data_time_avg / acc1_avg / loss_avg).
"""

from __future__ import annotations

import dataclasses

import jax
import jax.numpy as jnp


def valid_count(labels: jax.Array) -> jax.Array:
    """Number of real (non-padding) samples in the batch. Padding rows are
    marked with label -1 by the Loader when it pads a ragged final val
    batch to a static shape; full training batches have no padding, so
    this equals the batch size there."""
    return jnp.sum((labels >= 0).astype(jnp.float32))


def cross_entropy(logits: jax.Array, labels: jax.Array) -> jax.Array:
    """Mean softmax cross-entropy over the *valid* rows of the batch,
    computed in f32. Padding rows (label -1, see `valid_count`) contribute
    zero loss and zero count."""
    logits = logits.astype(jnp.float32)
    valid = (labels >= 0).astype(jnp.float32)
    safe = jnp.maximum(labels, 0)
    logz = jax.nn.logsumexp(logits, axis=-1)
    true_logit = jnp.take_along_axis(logits, safe[:, None], axis=-1)[:, 0]
    per_example = (logz - true_logit) * valid
    return jnp.sum(per_example) / jnp.maximum(jnp.sum(valid), 1.0)


def label_rank(logits: jax.Array, labels: jax.Array) -> jax.Array:
    """Per row, how many classes outrank the label: int32, 0 when the
    label holds the largest logit. A class outranks the label when its
    logit is larger, or equal at a lower index: the tie order XLA's
    top-k documents, so `rank < k` is exactly "the label is among the
    indices a top-k of `k` returns" for every finite input (a constant
    row ranks label j at j). One compare-and-count pass over the logits
    in the dtype they arrive in; nothing is sorted. Padding rows (label
    -1) are ranked as label 0: `rank_correct` leaves them out."""
    safe = jnp.maximum(labels, 0)[:, None]
    true_logit = jnp.take_along_axis(logits, safe, axis=-1)
    index = jax.lax.broadcasted_iota(
        jnp.int32, logits.shape, logits.ndim - 1
    )
    # a tie outranks the label only from a lower index
    ahead = jnp.where(
        index < safe, logits >= true_logit, logits > true_logit
    )
    return jnp.sum(ahead, axis=-1, dtype=jnp.int32)


def rank_correct(rank: jax.Array, labels: jax.Array, k: int) -> jax.Array:
    """Count of valid samples whose `label_rank` is under `k` (sum, not
    %, so counts psum correctly across shards). Exact, not approximate.
    A `k` at or over the number of classes counts every valid row, so
    acc5 is well-defined on few-class heads; padding rows (label -1)
    never count."""
    return jnp.sum(((rank < k) & (labels >= 0)).astype(jnp.float32))


def topk_correct(logits: jax.Array, labels: jax.Array, k: int) -> jax.Array:
    """Count of valid samples whose label is in the top-k logits: the
    numerator of reference `accuracy` (`utils.py:215-229`), through
    `label_rank`. A step that wants several k ranks once and calls
    `rank_correct` for each."""
    return rank_correct(label_rank(logits, labels), labels, k)


def accuracy(logits: jax.Array, labels: jax.Array, topk=(1,)) -> list[jax.Array]:
    """Percentage top-k accuracies — same contract as reference
    `accuracy` (`utils.py:215-229`)."""
    n = labels.shape[0]
    rank = label_rank(logits, labels)
    return [100.0 * rank_correct(rank, labels, k) / n for k in topk]


@dataclasses.dataclass
class Meter:
    """Streaming average (host-side)."""

    total: float = 0.0
    count: int = 0

    def update(self, value: float, n: int = 1) -> None:
        self.total += float(value) * n
        self.count += n

    @property
    def avg(self) -> float:
        return self.total / max(self.count, 1)
