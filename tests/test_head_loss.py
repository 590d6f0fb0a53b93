"""`ops/head_loss.py` against what it replaced in the LM engines' steps:
`head` + `training/metrics.cross_entropy` + `_metrics` under autodiff, on
the same inputs. The row blocks (what every CPU run takes) are held to
float32 agreement; the Mosaic kernels run through the Pallas interpreter
and are held to a reference that rounds where a TPU's default precision
rounds a float32 product's operands (to bfloat16), the logits' gradient
among them. Counts are exact wherever the products are."""

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from distributed_model_parallel_tpu.ops import head_loss as HL
from distributed_model_parallel_tpu.parallel.data_parallel import _metrics
from distributed_model_parallel_tpu.training.metrics import cross_entropy

f32, bf16 = jnp.float32, jnp.bfloat16


def old_sums(rows, matrix, labels):
    """The step's sums as the engines made them until PR 39."""
    logits = rows.astype(f32) @ matrix
    return _metrics(cross_entropy(logits, labels), logits, labels)


def graded(sums_of, rows, matrix, labels):
    """({the four sums}, (d rows, d matrix)) of loss_sum."""
    def loss(rows, matrix):
        m = sums_of(rows, matrix, labels)
        return m["loss_sum"], m

    (_, m), grads = jax.value_and_grad(
        loss, argnums=(0, 1), has_aux=True
    )(rows, matrix)
    return m, grads


def draw(rows, dim, vocab, dtype, seed=0, unscored=4):
    k = jax.random.split(jax.random.PRNGKey(seed), 4)
    h = jax.random.normal(k[0], (rows, dim)).astype(dtype)
    w = 0.3 * jax.random.normal(k[1], (dim, vocab))
    # labels that are hits, near misses and misses: the row's own best
    # class, its fourth best, and a random one, a third each
    logits = h.astype(f32) @ w
    order = jnp.argsort(-logits, axis=-1)
    pick = jnp.arange(rows) % 3
    labels = jnp.where(
        pick == 0, order[:, 0],
        jnp.where(pick == 1, order[:, 3],
                  jax.random.randint(k[2], (rows,), 0, vocab)),
    ).astype(jnp.int32)
    drop = jax.random.permutation(k[3], rows)[:unscored]
    return h, w, labels.at[drop].set(-1)


def through(program):
    return lambda h, w, lab: dict(zip(HL.SUMS, program(h, w, lab)))


def assert_sums(got, want, loss_rtol=1e-5):
    for name in ("count", "correct1", "correct5"):
        assert float(got[name]) == float(want[name]), name
    np.testing.assert_allclose(
        float(got["loss_sum"]), float(want["loss_sum"]), rtol=loss_rtol
    )


def assert_grads(got, want, rtol):
    for g, w in zip(got, want):
        assert g.dtype == w.dtype and g.shape == w.shape
        g, w = np.asarray(g, np.float32), np.asarray(w, np.float32)
        assert np.abs(g - w).max() <= rtol * np.abs(w).max()


# ------------------------------------------------------- the row blocks

@pytest.mark.parametrize("dtype", [f32, bf16], ids=["f32", "bf16"])
@pytest.mark.parametrize("vocab", [1009, 1024])
@pytest.mark.parametrize("rows", [64, 77], ids=["whole", "ragged"])
def test_blocks_match_the_old_formulation(monkeypatch, dtype, vocab, rows):
    # blocks of 16 rows: four whole ones, or four and a ragged fifth
    monkeypatch.setattr(HL, "BLOCK_ELEMENTS", 16 * vocab)
    assert HL._block_rows(rows, vocab) == 16
    h, w, lab = draw(rows, 32, vocab, dtype)
    want, want_g = graded(old_sums, h, w, lab)
    got, got_g = graded(HL.head_loss, h, w, lab)
    assert float(want["correct1"]) >= rows // 3 - 4   # hits are there
    assert float(want["correct5"]) > float(want["correct1"])
    assert_sums(got, want)
    # a bfloat16 row gradient is the float32 one rounded once
    assert_grads(got_g, want_g, 1e-5 if dtype == f32 else 1e-2)


@pytest.mark.parametrize("dtype", [f32, bf16], ids=["f32", "bf16"])
def test_blocks_at_gpt2s_vocabulary(monkeypatch, dtype):
    vocab = 50257
    monkeypatch.setattr(HL, "BLOCK_ELEMENTS", 8 * vocab)
    h, w, lab = draw(20, 16, vocab, dtype, unscored=2)
    want, want_g = graded(old_sums, h, w, lab)
    got, got_g = graded(HL.head_loss, h, w, lab)
    assert_sums(got, want)
    assert_grads(got_g, want_g, 1e-5 if dtype == f32 else 1e-2)


def test_a_batch_of_sequences_is_its_flattened_rows():
    h, w, lab = draw(24, 16, 61, f32)
    flat = HL.head_loss(h, w, lab)
    shaped = HL.head_loss(h.reshape(2, 12, 16), w, lab.reshape(2, 12))
    for name in HL.SUMS:
        assert float(flat[name]) == float(shaped[name])


@pytest.mark.parametrize("program", ["blocks", "kernel"])
def test_an_all_padding_batch_sums_to_nothing(monkeypatch, program):
    small_kernel(monkeypatch)
    h, w, _ = draw(40, 128, 300, f32)
    lab = jnp.full((40,), -1, jnp.int32)
    sums = through(HL._block_sums if program == "blocks" else HL._kernel_sums)
    got, (dh, dw) = graded(sums, h, w, lab)
    assert all(float(got[name]) == 0.0 for name in HL.SUMS)
    assert not np.asarray(dh).any() and not np.asarray(dw).any()


@pytest.mark.parametrize("program", ["blocks", "kernel"])
def test_gradients_scale_with_the_cotangent(monkeypatch, program):
    small_kernel(monkeypatch)
    h, w, lab = draw(40, 128, 300, f32)
    sums = through(HL._block_sums if program == "blocks" else HL._kernel_sums)
    _, once = graded(sums, h, w, lab)
    # a power of two, so that it rounds nowhere
    _, twice = graded(
        lambda *xs: {k: 2.0 * v for k, v in sums(*xs).items()}, h, w, lab
    )
    for a, b in zip(once, twice):
        assert np.abs(np.asarray(a)).max() > 0
        np.testing.assert_allclose(2.0 * np.asarray(a), np.asarray(b),
                                   rtol=1e-6, atol=1e-7)


# ------------------------------------------------------------- the ties
# Small whole numbers: every product and every sum is exact in bfloat16
# operands and float32 accumulation alike, so equal logits are equal to
# the bit in both programs and the counts have to be exact.

def tie_case(kind, vocab=300, dim=128):
    rows = 12
    h = np.zeros((rows, dim), np.float32)
    w = np.zeros((dim, vocab), np.float32)
    h[:, 0] = 1.0
    if kind == "all_equal":
        # every logit of every row is 0: label j ranks j
        lab = np.array([0, 1, 4, 5, 7, vocab - 1, 0, 2, 3, 6, -1, 4])
        return h, w, lab, float((lab == 0).sum()), float(
            ((lab >= 0) & (lab < 5)).sum()
        )
    # classes 10..15 hold the row's largest logit, 2.0; the rest 0
    w[0, 10:16] = 2.0
    if kind == "tied_from_below":
        # label 15: five equal logits at lower indices outrank it
        lab = np.full((rows,), 15)
        return h, w, lab, 0.0, 0.0
    if kind == "tied_from_above":
        # label 10: the equal logits sit at higher indices and do not
        lab = np.full((rows,), 10)
        return h, w, lab, float(rows), float(rows)
    # label 14: four outrank it, so it is fifth: top-5 and not top-1
    lab = np.full((rows,), 14)
    return h, w, lab, 0.0, float(rows)


@pytest.mark.parametrize("program", ["blocks", "kernel"])
@pytest.mark.parametrize(
    "kind", ["all_equal", "tied_from_below", "tied_from_above", "fifth"]
)
def test_tie_order_is_label_ranks_and_counts_are_exact(
        monkeypatch, program, kind):
    small_kernel(monkeypatch)
    monkeypatch.setattr(HL, "BLOCK_ELEMENTS", 8 * 300)
    h, w, lab, correct1, correct5 = tie_case(kind)
    h, w, lab = jnp.asarray(h), jnp.asarray(w), jnp.asarray(lab, jnp.int32)
    sums = through(HL._block_sums if program == "blocks" else HL._kernel_sums)
    got = sums(h, w, lab)
    want = old_sums(h, w, lab)
    assert float(got["correct1"]) == float(want["correct1"]) == correct1
    assert float(got["correct5"]) == float(want["correct5"]) == correct5
    assert float(got["count"]) == float(want["count"])
    np.testing.assert_allclose(
        float(got["loss_sum"]), float(want["loss_sum"]), rtol=1e-6
    )


# ---------------------------------------------------------- the kernels

def small_kernel(monkeypatch):
    """Tiles and stretches small enough for the interpreter, and more
    than one of each at the tests' sizes."""
    monkeypatch.setattr(HL, "_tiles", lambda dim: (64, 256))
    monkeypatch.setattr(HL, "STRETCH_BYTES", 2 * 64 * 128 * 4)


def rounded_sums(rows, matrix, labels):
    """The old formulation with its product's operands rounded as a
    TPU's default precision rounds them."""
    return old_sums(
        rows.astype(bf16).astype(rows.dtype),
        matrix.astype(bf16).astype(f32), labels,
    )


def rounded_gradients(rows, matrix, labels):
    """d loss_sum / d (rows, matrix) with every product's operands
    rounded to bfloat16 and float32 accumulation: the logits and their
    gradient are float32, and rounded only on their way into a
    product."""
    h = rows.astype(bf16).astype(f32)
    w = matrix.astype(bf16).astype(f32)
    logits = h @ w
    valid = (labels >= 0)[:, None]
    d_logits = jnp.where(
        valid,
        jax.nn.softmax(logits, axis=-1)
        - jax.nn.one_hot(jnp.maximum(labels, 0), matrix.shape[1]),
        0.0,
    ).astype(bf16).astype(f32)
    return (d_logits @ w.T).astype(rows.dtype), h.T @ d_logits


@pytest.mark.parametrize("dtype", [f32, bf16], ids=["f32", "bf16"])
@pytest.mark.parametrize("vocab", [1009, 1024])
@pytest.mark.parametrize("rows", [256, 200], ids=["whole", "ragged"])
def test_kernels_through_the_interpreter(monkeypatch, dtype, vocab, rows):
    small_kernel(monkeypatch)
    assert HL._stretch_rows(256, 128) == 128     # two stretches
    h, w, lab = draw(rows, 128, vocab, dtype)
    want = rounded_sums(h, w, lab)
    got, got_g = graded(through(HL._kernel_sums), h, w, lab)
    assert_sums(got, want, loss_rtol=2e-6)
    assert float(got["correct1"]) >= rows // 3 - 4
    # (an entry of the logits' gradient a last bit apart on its way
    # into bfloat16 rounds the other way: a few entries in a million)
    assert_grads(got_g, rounded_gradients(h, w, lab),
                 1e-3 if dtype == f32 else 1e-2)
    # and the unrounded old formulation is a rounding away
    _, old_g = graded(old_sums, h, w, lab)
    assert_grads(got_g, old_g, 2e-2)


def test_kernel_at_gpt2s_vocabulary_few_rows(monkeypatch):
    monkeypatch.setattr(HL, "_tiles", lambda dim: (64, 4096))
    h, w, lab = draw(40, 128, 50257, bf16, unscored=3)
    got, got_g = graded(through(HL._kernel_sums), h, w, lab)
    assert_sums(got, rounded_sums(h, w, lab), loss_rtol=2e-6)
    assert_grads(got_g, rounded_gradients(h, w, lab), 1e-2)


def test_the_selector_reads_backend_and_rows(monkeypatch):
    # off a TPU: the row blocks, never the interpreter
    assert HL.head_loss_kind(16384) == "blocks"
    monkeypatch.setattr(HL, "_on_tpu", lambda: True)
    assert HL.head_loss_kind(16384) == "kernel"
    assert HL.head_loss_kind(HL.ROW_TILE) == "kernel"
    # a handful of rows fills no tile
    assert HL.head_loss_kind(64) == "blocks"
