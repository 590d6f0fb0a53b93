"""Time `ops/head_loss.py`'s programs against the formulation it
replaced, alone on the chip, at the training cells' shapes.

    chiprun -- python3 experiments/head_loss_probe.py [--shapes gpt2s,...]

For each shape (rows, dim, vocab): the step's four sums and both
gradients from (a) `head` + `cross_entropy` + `_metrics` under autodiff,
as the engines ran until PR 39, (b) the row blocks in XLA, (c) the
Mosaic kernels at the tiles the op picks and at any others asked for;
milliseconds a call (forward and
backward, a value fetched after every call) and how far each lies from
(a). Nothing here is a cell's number: it says which program the
selector should pick (PERF.md section 6, PR 39).
"""

from __future__ import annotations

import argparse
import json
import os
import sys
import time

sys.path.insert(0, os.path.dirname(os.path.dirname(os.path.abspath(__file__))))

SHAPES = {
    "gpt2s": (16384, 768, 50257),
    "gpt2xl": (2048, 1600, 50257),
    "kimilin": (16384, 2304, 20480),
}


def main(argv=None) -> int:
    parser = argparse.ArgumentParser(description=__doc__.splitlines()[0])
    parser.add_argument("--shapes", default="gpt2s,gpt2xl,kimilin")
    parser.add_argument("--calls", type=int, default=8)
    parser.add_argument("--block-elements", type=lambda s: [
        int(n) for n in s.split(",") if n], default=[1 << 27])
    parser.add_argument("--tiles", type=lambda s: [
        tuple(int(n) for n in t.split("x")) for t in s.split(",") if t
    ], default=[], help="tiles to try beside the chosen, e.g. 512x512")
    parser.add_argument("--toy", action="store_true",
                        help="tiny shapes, for a dry run on the CPU")
    args = parser.parse_args(argv)

    import jax
    import jax.numpy as jnp

    from distributed_model_parallel_tpu.ops import head_loss as HL
    from distributed_model_parallel_tpu.parallel.data_parallel import _metrics
    from distributed_model_parallel_tpu.training.metrics import cross_entropy

    def reference(h, w, lab):
        logits = h.astype(jnp.float32) @ w
        return _metrics(cross_entropy(logits, lab), logits, lab)

    def graded(sums_of):
        def step(h, w, lab):
            def loss(h, w):
                m = sums_of(h, w, lab)
                return m["loss_sum"], m
            return jax.value_and_grad(loss, argnums=(0, 1), has_aux=True)(h, w)
        return jax.jit(step)

    def timed(fn, *xs):
        out = fn(*xs)
        jax.block_until_ready(out)
        t0 = time.perf_counter()
        for _ in range(args.calls):
            out = fn(*xs)
            float(out[0][0])
        jax.block_until_ready(out)
        return (time.perf_counter() - t0) / args.calls * 1e3, out

    def apart(a, b):
        a, b = a.astype(jnp.float32), b.astype(jnp.float32)
        return float(jnp.max(jnp.abs(a - b)) / jnp.max(jnp.abs(a)))

    def through(program):
        return lambda h, w, lab: dict(zip(HL.SUMS, program(h, w, lab)))

    for name in args.shapes.split(","):
        rows, dim, vocab = SHAPES[name]
        if args.toy:
            rows, dim, vocab = 2048, 128, 1300 + 7
        k = jax.random.split(jax.random.PRNGKey(39), 3)
        h = jax.random.normal(k[0], (rows, dim)).astype(jnp.bfloat16)
        w = 0.02 * jax.random.normal(k[1], (dim, vocab))
        lab = jax.random.randint(k[2], (rows,), -1, vocab)
        ms, ((_, m0), (dh0, dw0)) = timed(graded(reference), h, w, lab)
        print(json.dumps({"shape": name, "program": "reference", "ms": ms,
                          **{s: float(m0[s]) for s in HL.SUMS}}), flush=True)

        def report(label, program):
            jax.clear_caches()
            try:
                ms, ((_, m), (dh, dw)) = timed(graded(through(program)),
                                               h, w, lab)
            except Exception as e:  # a tile the compiler refuses
                print(json.dumps({"shape": name, "program": label,
                                  "failed": str(e)[-300:]}), flush=True)
                return
            print(json.dumps({
                "shape": name, "program": label, "ms": ms,
                **{s: float(m[s]) - float(m0[s]) for s in HL.SUMS},
                "d_rows": apart(dh0, dh), "d_matrix": apart(dw0, dw),
            }), flush=True)

        for elements in args.block_elements:
            HL.BLOCK_ELEMENTS = elements
            report(f"blocks/{HL._block_rows(rows, vocab)}", HL._block_sums)
        chosen = HL._tiles
        for tiles in [None] + args.tiles:
            HL._tiles = chosen if tiles is None else (lambda dim: tiles)
            tile_r, tile_v = HL._tiles(dim)
            report(f"kernel/{tile_r}x{tile_v}", HL._kernel_sums)
        HL._tiles = chosen
    return 0


if __name__ == "__main__":
    sys.exit(main())
