"""Quantized matmul primitives for the decode hot floor — int8 with
absmax scales, plus the bf16 cast path, behind one seam.

PR 11 compressed the *wire* (`ops/wire_codec.py`); this module applies
the same absmax-scale machinery to the *compute*: the serving engine's
per-token latency floor is its f32 projection GEMMs, which the MXU runs
at 2-4x the throughput in bf16/int8 (Wang et al. ASPLOS'23 is the
decomposition anchor; Tang et al. 1-bit Adam the absmax-scale one —
both PAPERS.md).

Scale layout (the int8 contract):

* weights  — per-OUTPUT-CHANNEL absmax: `w (K, N)` quantizes against
  `wscale (N,) = max(|w|, axis=0) / 127` (floored at `ABSMAX_FLOOR`,
  the wire codec's denormal guard). Static per weight, so a real
  deployment quantizes once; here it folds into the traced step.
* activations — per-TOKEN dynamic absmax: `x (M, K)` quantizes against
  `xscale (M, 1) = max(|x|, axis=-1) / 127`, recomputed every call
  (decode activations change every token; a static scale would clip).
* accumulate in int32 on the MXU (`preferred_element_type`), dequantize
  on exit: `y = acc_i32 * xscale * wscale` in f32 — int8 values are
  never summed in int8, mirroring the wire codec's
  decode-then-accumulate rule. The elementwise bound per operand is
  absmax/254, same as the wire's.

Dual path, same shape as `pallas_attention.flash_attention`:

  mode   | TPU                      | CPU / other backends
  -------|--------------------------|------------------------------
  int8   | Pallas kernel (quantize  | dtype-pinned `lax.dot_general`
         | + s8xs8 MXU dot in VMEM) | (s8 x s8 -> i32), same math
  bf16   | XLA (the MXU's native    | XLA bf16 dot — same cast path
         | bf16 path; no kernel     | everywhere
         | needed)                  |
  f32    | plain `x @ w`            | plain `x @ w`

`path=None` selects the Pallas kernel on a TPU backend and the
`lax.dot_general` twin elsewhere — so a CPU trace of an opted-in decode
step carries real int8 `dot_general` equations, which is exactly what
the hlolint rule `decode-quantized-matmul` pins from the jaxpr (compiled
CPU HLO normalizes dtypes, so the contract lives at trace level, like
`bf16-ring-upcast`). Tests drive the kernel explicitly with
`path="pallas"` (interpret mode off-TPU).

`QuantMatmul` is the `Context.matmul` policy the serving engine threads
for non-ring int8 decode; the ring layouts inject `quant_dot(mode)`
into the collective-matmul fold bodies instead
(`ops/collective_matmul.py`) so the ppermute chain stays byte-identical
and only the per-chunk GEMM dtype changes.
"""

from __future__ import annotations

import dataclasses
from typing import Callable, Optional

import jax
import jax.numpy as jnp
from jax import lax
from jax.experimental import pallas as pl

from distributed_model_parallel_tpu.ops.wire_codec import ABSMAX_FLOOR

# The engine/CLI surface (`compute_dtype` on ServingEngine,
# `--compute-dtype` on cli/serve.py). "f32" is the identity.
COMPUTE_DTYPES = ("f32", "bf16", "int8")


def check_compute_dtype(name: str) -> str:
    if name not in COMPUTE_DTYPES:
        raise ValueError(
            f"compute_dtype must be one of {COMPUTE_DTYPES}, got "
            f"{name!r}"
        )
    return name


def normalize_compute_dtype(value) -> str:
    """Engine-surface normalization: the ServingEngine historically
    accepted a dtype object (`compute_dtype=jnp.bfloat16`); the knob
    surface is the string triple. Both map onto COMPUTE_DTYPES."""
    if value is None:
        return "f32"
    if isinstance(value, str):
        return check_compute_dtype(value)
    try:
        dt = jnp.dtype(value)
    except TypeError:
        raise ValueError(
            f"compute_dtype must be one of {COMPUTE_DTYPES} or a "
            f"dtype, got {value!r}"
        )
    if dt == jnp.dtype(jnp.bfloat16):
        return "bf16"
    if dt == jnp.dtype(jnp.float32):
        return "f32"
    raise ValueError(
        f"compute_dtype dtype {dt} unsupported; use one of "
        f"{COMPUTE_DTYPES}"
    )


# ------------------------------------------------------------ quantize


def quantize_weight(w):
    """w (K, N) -> (wq int8 (K, N), wscale f32 (N,)): per-output-channel
    absmax scales (module docstring). Floored like the wire codec so an
    all-zero column still decodes to exact zeros."""
    wf = w.astype(jnp.float32)
    absmax = jnp.max(jnp.abs(wf), axis=0)
    scale = jnp.maximum(absmax, ABSMAX_FLOOR) / 127.0
    q = jnp.clip(jnp.round(wf / scale[None, :]), -127.0, 127.0)
    return q.astype(jnp.int8), scale


def quantize_rows(x):
    """x (M, K) -> (q int8 (M, K), xscale f32 (M, 1)): per-token dynamic
    absmax scales."""
    xf = x.astype(jnp.float32)
    absmax = jnp.max(jnp.abs(xf), axis=-1, keepdims=True)
    scale = jnp.maximum(absmax, ABSMAX_FLOOR) / 127.0
    q = jnp.clip(jnp.round(xf / scale), -127.0, 127.0)
    return q.astype(jnp.int8), scale


# ----------------------------------------------------------- int8 paths


def _int8_matmul_xla(x2, w):
    """The dtype-pinned fallback: quantize, one s8 x s8 -> i32
    `dot_general`, dequantize. The int8 operand dtypes in this trace are
    the hlolint `decode-quantized-matmul` contract."""
    q, xscale = quantize_rows(x2)
    wq, wscale = quantize_weight(w)
    acc = lax.dot_general(
        q, wq,
        dimension_numbers=(((1,), (0,)), ((), ())),
        preferred_element_type=jnp.int32,
    )
    return acc.astype(jnp.float32) * xscale * wscale[None, :]


def _int8_kernel(x_ref, wq_ref, ws_ref, o_ref):
    """One (bm, K) row block: dynamic row quantization in VMEM, the
    s8 x s8 MXU dot accumulating in i32, dequantize on exit. The weight
    arrives pre-quantized (its scale is static; recomputing it per grid
    step would waste VPU work)."""
    x = x_ref[...].astype(jnp.float32)                  # (bm, K)
    absmax = jnp.max(jnp.abs(x), axis=-1, keepdims=True)
    scale = jnp.maximum(absmax, ABSMAX_FLOOR) / 127.0
    q = jnp.clip(jnp.round(x / scale), -127.0, 127.0).astype(jnp.int8)
    acc = lax.dot_general(                              # MXU, i32 acc
        q, wq_ref[...],
        dimension_numbers=(((1,), (0,)), ((), ())),
        preferred_element_type=jnp.int32,
    )
    o_ref[...] = acc.astype(jnp.float32) * scale * ws_ref[...]


def _pick_rows(m: int, want: int = 128) -> int:
    """Largest multiple-of-8 divisor of m that is <= want, else m itself
    (a whole-array block is always a legal Mosaic tiling)."""
    b = min(want, m)
    while b >= 8:
        if m % b == 0 and b % 8 == 0:
            return b
        b -= 1
    return m


def _int8_matmul_pallas(x2, w, interpret):
    m, k = x2.shape
    n = w.shape[-1]
    wq, wscale = quantize_weight(w)  # static per weight; stays in XLA
    bm = _pick_rows(m)
    grid = (m // bm,) if m % bm == 0 else (1,)
    if grid == (1,):
        bm = m
    return pl.pallas_call(
        _int8_kernel,
        grid=grid,
        in_specs=[
            pl.BlockSpec((bm, k), lambda i: (i, 0)),
            pl.BlockSpec((k, n), lambda i: (0, 0)),
            pl.BlockSpec((1, n), lambda i: (0, 0)),
        ],
        out_specs=pl.BlockSpec((bm, n), lambda i: (i, 0)),
        out_shape=jax.ShapeDtypeStruct((m, n), jnp.float32),
        interpret=interpret,
    )(x2.astype(jnp.float32), wq, wscale[None, :])


# --------------------------------------------------------------- public


def quant_matmul(
    x,
    w,
    mode: str = "int8",
    *,
    path: Optional[str] = None,
    interpret: Optional[bool] = None,
):
    """x (..., K) @ w (K, N) in `mode` arithmetic.

    mode "f32" is the identity dot; "bf16" casts both operands and
    returns bf16 (the MXU's native half path — downstream layers follow
    x.dtype, the mixed-precision convention); "int8" quantizes per the
    module contract and returns f32.

    `path` selects the int8 implementation: None auto-picks the Pallas
    kernel on TPU and the `lax.dot_general` fallback elsewhere (module
    docstring); "pallas" / "xla" force one (tests drive the kernel in
    interpret mode off-TPU). `interpret=None` auto-selects like
    `flash_attention`."""
    check_compute_dtype(mode)
    if mode == "f32":
        return x @ w
    if mode == "bf16":
        return x.astype(jnp.bfloat16) @ w.astype(jnp.bfloat16)
    if path is None:
        path = "pallas" if jax.default_backend() == "tpu" else "xla"
    lead = x.shape[:-1]
    x2 = x.reshape(-1, x.shape[-1])
    if path == "pallas":
        if interpret is None:
            interpret = jax.default_backend() != "tpu"
        y = _int8_matmul_pallas(x2, w, interpret)
    elif path == "xla":
        y = _int8_matmul_xla(x2, w)
    else:
        raise ValueError(
            f"path must be None, 'pallas' or 'xla', got {path!r}"
        )
    return y.reshape(*lead, w.shape[-1])


def quant_dot(mode: Optional[str]) -> Optional[Callable]:
    """The chunk-GEMM to inject into a collective-matmul ring fold
    (`ops/collective_matmul.py`): None for f32 (the fold keeps its
    plain `chunk @ w`, byte-identical lowering), else a 2-arg dot in
    `mode` arithmetic. Always the XLA-auto path — inside a shard_map
    fold the chunk dots are the lint rule's jaxpr anchor on CPU, and
    auto still picks the kernel on TPU."""
    if mode is None or mode == "f32":
        return None
    check_compute_dtype(mode)
    return lambda a, b: quant_matmul(a, b, mode)


@dataclasses.dataclass(frozen=True)
class QuantMatmul:
    """`Context.matmul` policy for NON-ring quantized decode
    (replicated / tp-without-rings layouts): every opted-in projection
    — column and row alike — runs through `quant_matmul`; under the tp
    layout GSPMD partitions the int8 dot and all-reduces the
    DEQUANTIZED f32 partials (decode-then-accumulate holds across
    shards: each shard's partial product is dequantized against its own
    weight-block scales before the sum)."""

    mode: str = "int8"
    attn: bool = True
    ffn: bool = True

    def _mm(self, h, w, b):
        y = quant_matmul(h, w, self.mode)
        return y + b.astype(y.dtype)

    def column(self, h, w, b):
        return self._mm(h, w, b)

    def row(self, h, w, b):
        return self._mm(h, w, b)


__all__ = [
    "COMPUTE_DTYPES",
    "QuantMatmul",
    "check_compute_dtype",
    "normalize_compute_dtype",
    "quant_dot",
    "quant_matmul",
    "quantize_rows",
    "quantize_weight",
]
