"""Wire compression for cross-slice ('dcn') traffic — the codec layer
under the two hierarchical exchanges.

Both hierarchical exchanges in this repo — the bucketed/overlapped
gradient reducer (`ops/grad_reduction.py`) and the two-level MoE expert
dispatch (`ops/expert_dispatch.py`) — already isolate the slow
cross-slice fabric onto a 1/ici-regrouped shard: the 'dcn' hop is the
ONE place a payload is both large and riding a link an order of
magnitude slower than ICI. That is exactly where
payload compression pays, and it is the seam PyTorch DDP exposes as
comm hooks on its bucketed Reducer (Li et al., VLDB 2020) and
DeepSpeed-MoE cheapens its expert exchange through (Rajbhandari et al.,
ICML 2022); 1-bit Adam (Tang et al., ICML 2021, PAPERS.md) is the
take-it-to-the-limit anchor for compressing exactly the gradient
exchange while master state stays full precision.

Two codecs, selected by name (`dcn_compression` on the engines,
`--dcn-compression` on the CLIs):

* `"bf16"` — cast-codec: encode = cast to bfloat16 (same exponent range
  as f32, 8 mantissa bits), decode = cast back. Halves the 'dcn' bytes;
  elementwise error <= 2^-8 relative (one rounding per hop).
* `"int8"` — absmax-scale codec: encode computes one f32 scale
  `max(|x|)/127` over the hop's chunk (a sub-range of a flat BUCKET on
  the gradient path, one regrouped message on the dispatch path),
  quantizes to int8, and ships the scale as a sidecar; decode multiplies
  back. Quarters the 'dcn' bytes (+4 B sidecar per hop); elementwise
  error <= chunk_absmax/254 per hop — the per-bucket bound the parity
  tests pin (INTERNALS §12 documents the accumulation: a K-slice
  reduction crosses the codec once per received chunk plus once on the
  gather, so the reduced value is within (K+1)·absmax/254 of the f32
  sum).

Everything INTRA-slice stays in the math dtype (f32 master weights and
f32 rings are untouched — compression is a property of the 'dcn' wire,
never of the accumulate), and int8 never sums in int8: the compressed
reduction (`ops/grad_reduction.compressed_dcn_psum`) exchanges encoded
chunks, decodes, and accumulates in the bucket dtype — a
reduce-scatter-then-all-gather over 'dcn' in the wire dtype rather
than a wire-dtype all-reduce.

`coded_ppermute` is the one primitive both consumers ride: encode →
`lax.ppermute` → decode, with the payload hop under the `dcn_wire`
named scope and the int8 scale sidecar under `dcn_scale` — the scopes
hlolint's byte-aware rule `dcn-compressed-payload` pins (every 'dcn'
hop of an opted-in step carries the wire dtype at the regrouped chunk
shape; zero f32 grad- or dispatch-sized payload crosses 'dcn'). Its
`jax.custom_vjp` sends the COTANGENT through the same codec over the
inverse permutation, so the backward rides the wire dtype too (the
straight-through convention: the quantizer's rounding is not
differentiated — its jacobian is zero a.e. — the wire is).
"""

from __future__ import annotations

from functools import partial
from typing import Optional, Tuple

import jax
import jax.numpy as jnp
from jax import lax

# The engine/CLI surface: "none" keeps the f32 wire byte-identical to
# the uncompressed lowering.
COMPRESSION_MODES: Tuple[str, ...] = ("none", "bf16", "int8")

# Named scopes the payload / scale-sidecar hops carry — what hlolint's
# `dcn-compressed-payload` rule reads out of the traced jaxpr (compiled
# CPU HLO float-normalizes bf16 collectives to f32, so the dtype
# contract lives at trace level, like `bf16-ring-upcast`).
WIRE_SCOPE = "dcn_wire"
SCALE_SCOPE = "dcn_scale"

# Zero-chunk guard: an all-zero chunk gets this scale instead of 0/127
# (decode is still exactly zero — every quantized value is 0). The
# floor is 127x f32's smallest NORMAL magnitude so the derived scale
# `floor/127` itself stays normal — a DENORMAL scale flushes to zero
# under the backend's FTZ and 0-divides the whole chunk. Chunks of
# denormal gradients quantize against the floored scale; the
# elementwise bound everywhere is max(absmax, ABSMAX_FLOOR)/254.
ABSMAX_FLOOR = 127 * 1.1754944e-38


def check_compression(name: str) -> str:
    """Validate a compression-mode name (engines and the bucketed
    reducer call this at construction so a typo fails loudly)."""
    if name not in COMPRESSION_MODES:
        raise ValueError(
            f"dcn_compression must be one of {COMPRESSION_MODES}, got "
            f"{name!r}"
        )
    return name


def require_dcn_axis(name: str, dcn_axis, what: str = "hop") -> str:
    """The one guard every consumer shares: a compressed wire needs a
    cross-slice fabric to cross. Validates the mode name too, so one
    call at engine construction covers both failure modes."""
    check_compression(name)
    if name != "none" and dcn_axis is None:
        raise ValueError(
            f"dcn_compression compresses the cross-slice {what}; this "
            "mesh carries no 'dcn' axis — factor the data axis with "
            "MeshSpec(dcn=K) (--dcn-slices on the CLIs)"
        )
    return name


def wire_itemsize(wire: str) -> int:
    """Bytes per element on the 'dcn' wire (the 1/2 resp. 1/4 of the
    f32 bytes the hlolint rule shape-pins; sidecars excluded)."""
    return {"none": 4, "bf16": 2, "int8": 1}[wire]


def wire_encode(wire: str, x):
    """x -> (payload, scale). `scale` is None for the cast codecs and
    a () f32 sidecar for int8 (absmax/127 over the whole chunk)."""
    if wire == "bf16":
        return x.astype(jnp.bfloat16), None
    if wire == "int8":
        xf = x.astype(jnp.float32)
        absmax = jnp.max(jnp.abs(xf))
        scale = jnp.maximum(absmax, ABSMAX_FLOOR) / 127.0
        q = jnp.clip(jnp.round(xf / scale), -127.0, 127.0)
        return q.astype(jnp.int8), scale
    return x, None


def wire_decode(wire: str, payload, scale, dtype):
    """Inverse of `wire_encode`, back to the chunk's math dtype."""
    if wire == "bf16":
        return payload.astype(dtype)
    if wire == "int8":
        return (payload.astype(jnp.float32) * scale).astype(dtype)
    return payload


def _coded_ppermute_impl(x, axis_name, perm, wire, tag):
    """encode -> ppermute -> decode, scopes applied per hop. With
    `wire="none"` this is a plain (optionally `tag`-scoped) ppermute —
    byte-identical to the uncompressed lowering."""
    perm = list(perm)
    if wire == "none":
        if tag is None:
            return lax.ppermute(x, axis_name, perm)
        with jax.named_scope(tag):
            return lax.ppermute(x, axis_name, perm)

    payload, scale = wire_encode(wire, x)
    if tag is None:
        with jax.named_scope(WIRE_SCOPE):
            payload = lax.ppermute(payload, axis_name, perm)
    else:
        with jax.named_scope(tag):
            with jax.named_scope(WIRE_SCOPE):
                payload = lax.ppermute(payload, axis_name, perm)
    if scale is not None:
        # The sidecar rides the SAME permutation but its own scope: it
        # must not count toward payload-hop pins (e.g. the moe_ring
        # chain of `moe-hierarchical-a2a`), and the hlolint rule
        # accounts for it separately (one f32 scalar per int8 hop).
        with jax.named_scope(SCALE_SCOPE):
            scale = lax.ppermute(scale, axis_name, perm)
    return wire_decode(wire, payload, scale, x.dtype)


@partial(jax.custom_vjp, nondiff_argnums=(1, 2, 3, 4))
def coded_ppermute(x, axis_name, perm, wire: str = "none",
                   tag: Optional[str] = None):
    """A `lax.ppermute` whose payload crosses the wire compressed.

    `perm` must be a hashable tuple of (src, dst) pairs. The backward
    runs the cotangent through the SAME codec over the inverse
    permutation (module docstring) — which is what keeps the transposed
    MoE exchange and the overlapped FFN ring's backward on the
    compressed wire instead of silently falling back to f32."""
    return _coded_ppermute_impl(x, axis_name, perm, wire, tag)


def _coded_fwd(x, axis_name, perm, wire, tag):
    return _coded_ppermute_impl(x, axis_name, perm, wire, tag), None


def _coded_bwd(axis_name, perm, wire, tag, _, g):
    inv = tuple((dst, src) for src, dst in perm)
    return (_coded_ppermute_impl(g, axis_name, inv, wire, tag),)


coded_ppermute.defvjp(_coded_fwd, _coded_bwd)


__all__ = [
    "ABSMAX_FLOOR",
    "COMPRESSION_MODES",
    "SCALE_SCOPE",
    "WIRE_SCOPE",
    "check_compression",
    "coded_ppermute",
    "require_dcn_axis",
    "wire_decode",
    "wire_encode",
    "wire_itemsize",
]
