"""Real-TPU attention micro-benchmark: Pallas flash kernels vs the XLA
dot-product path, forward and forward+backward, across sequence lengths,
head dims (64 AND 128), and causal masking.

Timing uses value-fetch synchronization (bench.py `_sync`): each
measured window ends in a scalar fetch that cannot complete before the
chained work ran.

Usage (on a host with a TPU):
    python experiments/flash_attention_bench.py \
        [--out experiments/flash_attention_bench.json]
    python experiments/flash_attention_bench.py --block-sweep
Prints one markdown row per (dh, T, path, causal); the XLA path skips
lengths whose (B, H, T, T) f32 logits would not fit HBM. `--block-sweep`
instead tunes (block_q, block_k) at T=8192 for both head dims.
"""

from __future__ import annotations

import argparse
import itertools
import json
import time

import jax
import jax.numpy as jnp
import numpy as np

from distributed_model_parallel_tpu.ops.attention import (
    dot_product_attention,
)
from distributed_model_parallel_tpu.ops.pallas_attention import (
    flash_attention,
)

B, H = 2, 8


def _qkv(t, dh, dtype=jnp.bfloat16, seed=0):
    rng = np.random.RandomState(seed)
    mk = lambda: jnp.asarray(
        rng.randn(B, t, H, dh).astype(np.float32), dtype
    )
    return mk(), mk(), mk()


def _time(fn, *args, iters=20, warmup=3):
    """Simple timing with a value-fetch barrier."""
    out = None
    for _ in range(warmup):
        out = fn(*args)
    float(jnp.sum(out))  # sync warmup
    t0 = time.perf_counter()
    for _ in range(iters):
        out = fn(*args)
    float(jnp.sum(out))  # the fetch IS the barrier
    return (time.perf_counter() - t0) / iters


def attention_tflops(t, dh, seconds, bwd=False, causal=False):
    """2 matmuls of 2*B*H*T^2*DH flops each forward; backward ~2.5x the
    forward matmul work (dq, dk, dv, plus the recomputed logits).
    Causal attention computes half the tiles, so half the flops."""
    fwd = 4 * B * H * t * t * dh * (0.5 if causal else 1.0)
    total = fwd * (1 + 2.5) if bwd else fwd
    return total / seconds / 1e12


def measure(fn, q, k, v, causal, t, dh, **kw):
    f = jax.jit(lambda q, k, v: fn(q, k, v, causal=causal, **kw))
    g = jax.jit(
        jax.grad(
            lambda q, k, v: jnp.sum(
                fn(q, k, v, causal=causal, **kw).astype(jnp.float32) ** 2
            ),
            argnums=(0, 1, 2),
        )
    )
    tf = _time(f, q, k, v)
    tg = _time(lambda *a: g(*a)[0], q, k, v)
    return {
        "fwd_ms": round(tf * 1e3, 2),
        "fwd_tflops": round(
            attention_tflops(t, dh, tf, causal=causal), 1
        ),
        "fwdbwd_ms": round(tg * 1e3, 2),
        "fwdbwd_tflops": round(
            attention_tflops(t, dh, tg, True, causal=causal), 1
        ),
    }


def main_sweep(args):
    dev = jax.devices()[0]
    print(f"device: {dev.device_kind} ({dev.platform})")
    rows = []
    print("| dh | T | path | causal | fwd ms | fwd TF/s "
          "| fwd+bwd ms | fwd+bwd TF/s |")
    print("|---|---|---|---|---|---|---|---|")
    for dh in (64, 128):
        for t in (1024, 2048, 4096, 8192, 16384, 32768):
            q, k, v = _qkv(t, dh)
            # XLA materializes (B, H, T, T) f32 logits (+ probs in the
            # backward): cap where that no longer fits the 16 GB HBM.
            xla_ok = B * H * t * t * 4 * 3 < 12e9
            paths = [("flash", flash_attention)] + (
                [("xla", dot_product_attention)] if xla_ok else []
            )
            for name, fn in paths:
                for causal in (False, True):
                    r = {"dh": dh, "T": t, "path": name,
                         "causal": causal}
                    r.update(measure(fn, q, k, v, causal, t, dh))
                    rows.append(r)
                    print(
                        f"| {dh} | {t} | {name} | {causal} "
                        f"| {r['fwd_ms']} | {r['fwd_tflops']} "
                        f"| {r['fwdbwd_ms']} | {r['fwdbwd_tflops']} |",
                        flush=True,
                    )
    # causal-skip speedup at long T (flash path): wall-clock ratio
    for dh in (64, 128):
        for t in (8192, 16384, 32768):
            pair = {
                r["causal"]: r for r in rows
                if r["dh"] == dh and r["T"] == t and r["path"] == "flash"
            }
            if len(pair) == 2:
                print(
                    f"causal-skip speedup dh={dh} T={t}: "
                    f"fwd {pair[False]['fwd_ms']/pair[True]['fwd_ms']:.2f}x "
                    f"fwd+bwd {pair[False]['fwdbwd_ms']/pair[True]['fwdbwd_ms']:.2f}x",
                    flush=True,
                )
    if args.out:
        with open(args.out, "w") as f:
            json.dump(
                {"device": dev.device_kind, "B": B, "H": H, "rows": rows},
                f, indent=2,
            )


def main_block_sweep(args):
    """(block_q, block_k) tuning at T=8192 for both head dims — the
    retune the round-3 verdict asked for (one retune ever, dh=64)."""
    dev = jax.devices()[0]
    print(f"device: {dev.device_kind} ({dev.platform})")
    t = 8192
    results = []
    for dh in (64, 128):
        q, k, v = _qkv(t, dh)
        best = None
        for bq, bk in itertools.product(
            (256, 512, 1024), (256, 512, 1024, 2048)
        ):
            try:
                ms = _time(
                    jax.jit(
                        lambda q, k, v, bq=bq, bk=bk: flash_attention(
                            q, k, v, block_q=bq, block_k=bk
                        )
                    ),
                    q, k, v, iters=10,
                ) * 1e3
            except Exception as e:  # noqa: BLE001 — invalid tile combos
                print(f"dh={dh} bq={bq} bk={bk}: {type(e).__name__}")
                continue
            print(f"dh={dh} bq={bq} bk={bk}: {ms:.2f} ms", flush=True)
            results.append({"dh": dh, "block_q": bq, "block_k": bk,
                            "fwd_ms": round(ms, 2)})
            if best is None or ms < best[0]:
                best = (ms, bq, bk)
        if best is None:
            print(f"dh={dh}: NO tile config compiled on this backend",
                  flush=True)
        else:
            print(f"BEST dh={dh}: block_q={best[1]} block_k={best[2]} "
                  f"({best[0]:.2f} ms)", flush=True)
    if args.out:
        with open(args.out, "w") as f:
            json.dump({"device": dev.device_kind, "T": t,
                       "rows": results}, f, indent=2)


def main():
    ap = argparse.ArgumentParser()
    ap.add_argument("--out", default=None)
    ap.add_argument("--block-sweep", action="store_true")
    args = ap.parse_args()
    if args.block_sweep:
        main_block_sweep(args)
    else:
        main_sweep(args)


if __name__ == "__main__":
    main()
