"""`training/metrics.py`'s accuracy counts against a plain `lax.top_k`
reference: `label_rank` counts the classes that outrank the label in one
compare-and-count pass, and the counts must equal the sorted top-k's
EXACTLY: ties in `top_k`'s order (equal values: lower index first),
padding rows (label -1) never counted, k at or over the number of
classes counting every valid row."""

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from distributed_model_parallel_tpu.training.metrics import (
    accuracy,
    label_rank,
    rank_correct,
    topk_correct,
)

ROWS = 64


def _reference_hits(logits, labels, k):
    """What `topk_correct` was before: membership in `lax.top_k`'s
    indices, k clamped to the number of classes."""
    _, pred = jax.lax.top_k(logits, min(k, logits.shape[-1]))
    hit = jnp.any(pred == labels[:, None], axis=-1)
    return hit & (labels >= 0)


def _case(classes, dtype, seed):
    """Random rows, then rows built for the tie order: logits drawn
    from five values (ties everywhere), all-equal rows, the label tied
    with one lower and with one higher index, the label tied with a
    whole block around it, and padding rows."""
    rng = np.random.RandomState(seed)
    logits = rng.randn(ROWS, classes).astype(np.float32)
    labels = rng.randint(0, classes, size=ROWS).astype(np.int32)
    logits[8:16] = rng.randint(0, 5, size=(8, classes))
    logits[16:24] = 0.25
    labels[16], labels[17] = 0, classes - 1
    for r in range(24, 32):     # the label's twin sits below its index
        labels[r] = rng.randint(1, classes)
        logits[r, rng.randint(0, labels[r])] = logits[r, labels[r]]
    for r in range(32, 40):     # ... and above it
        labels[r] = rng.randint(0, classes - 1)
        logits[r, rng.randint(labels[r] + 1, classes)] = logits[r, labels[r]]
    for r in range(40, 48):     # the row's maximum, shared by 7 classes
        labels[r] = rng.randint(3, classes - 3)
        logits[r, labels[r] - 3:labels[r] + 4] = 9.0
    labels[48:56] = -1          # padding; some over an all-equal row
    logits[52:56] = -1.5
    return jnp.asarray(logits, dtype), jnp.asarray(labels)


@pytest.mark.parametrize("k", [1, 5, 10, 2000])
@pytest.mark.parametrize("classes", [10, 1009])
@pytest.mark.parametrize("dtype", [jnp.float32, jnp.bfloat16],
                         ids=["f32", "bf16"])
def test_counts_equal_lax_top_k(dtype, classes, k):
    logits, labels = _case(classes, dtype, seed=classes + k)
    want = _reference_hits(logits, labels, k)
    rank = label_rank(logits, labels)
    assert rank.dtype == jnp.int32 and rank.shape == labels.shape
    got = (rank < k) & (labels >= 0)
    np.testing.assert_array_equal(np.asarray(got), np.asarray(want))
    assert not np.asarray(got)[48:56].any()       # padding never counts
    count = topk_correct(logits, labels, k)
    assert count.dtype == jnp.float32 and count.shape == ()
    assert float(count) == float(jnp.sum(want.astype(jnp.float32)))
    assert float(rank_correct(rank, labels, k)) == float(count)
    if k >= classes:
        assert float(count) == float(jnp.sum(labels >= 0))


@pytest.mark.parametrize("classes", [10, 1009])
def test_constant_row_ranks_label_at_its_index(classes):
    """A fresh model's constant logits: `top_k` returns indices
    0..k-1, so label j is a top-k hit exactly when j < k."""
    labels = jnp.arange(classes, dtype=jnp.int32)
    logits = jnp.full((classes, classes), 0.5, jnp.float32)
    np.testing.assert_array_equal(
        np.asarray(label_rank(logits, labels)), np.arange(classes)
    )
    assert float(topk_correct(logits, labels, 5)) == 5.0


def test_accuracy_percentages_match_reference_contract():
    logits, labels = _case(10, jnp.float32, seed=3)
    valid = labels >= 0
    acc1, acc5 = accuracy(logits[valid], labels[valid], topk=(1, 5))
    n = int(valid.sum())
    for got, k in ((acc1, 1), (acc5, 5)):
        want = 100.0 * float(
            jnp.sum(_reference_hits(logits, labels, k))
        ) / n
        assert float(got) == pytest.approx(want, rel=1e-6)

