"""Time `ops/delta_rule.gated_delta_rule` alone on the chip at
`kimilin_train_8k`'s shapes, beside other copies of the op.

    python3 experiments/delta_rule_probe.py \\
        [--compare path/to/other/ops/delta_rule.py ...] \\
        [--swap path/to/other/ops/delta_rule.py:_unit_lower_inverse ...]

One layer-pass of the cell: q, k, v (2, 8192, 32, 128) in bfloat16, g
and beta in float32, chunk 64. For each program: milliseconds a call of
the forward and of the forward + backward (a value fetched after every
call), the compiled temporaries of the forward + backward, and how far
its outputs and gradients lie from the first program's. The programs
are the op as it is; each `--compare` module as it is (an older op);
and the op with each `--swap` function taken from the module named. One
JSON line a program. Nothing here is a cell's number: it says which
form the op should take (PERF.md section 6). It needs the TPU;
`--toy` is a dry run on the CPU at tiny shapes.
"""

from __future__ import annotations

import argparse
import importlib.util
import json
import os
import sys
import time

sys.path.insert(0, os.path.dirname(os.path.dirname(os.path.abspath(__file__))))


def load(path):
    spec = importlib.util.spec_from_file_location(
        f"compared_{abs(hash(path))}", path)
    module = importlib.util.module_from_spec(spec)
    spec.loader.exec_module(module)
    return module


def main(argv=None) -> int:
    parser = argparse.ArgumentParser(description=__doc__.splitlines()[0])
    parser.add_argument("--compare", action="append", default=[],
                        help="another ops/delta_rule.py, timed as it is")
    parser.add_argument("--swap", action="append", default=[],
                        help="FILE:NAME, a function of another "
                             "ops/delta_rule.py tried in the op")
    parser.add_argument("--calls", type=int, default=8)
    parser.add_argument("--toy", action="store_true",
                        help="tiny shapes, for a dry run on the CPU")
    args = parser.parse_args(argv)

    import jax
    import jax.numpy as jnp

    from distributed_model_parallel_tpu.ops import delta_rule as DR

    b, t, h, d = (1, 300, 2, 16) if args.toy else (2, 8192, 32, 128)
    ks = jax.random.split(jax.random.PRNGKey(42), 5)
    unit = lambda x: x / jnp.linalg.norm(x, axis=-1, keepdims=True)
    q = (unit(jax.random.normal(ks[0], (b, t, h, d))) * d ** -0.5).astype(
        jnp.bfloat16)
    k = unit(jax.random.normal(ks[1], (b, t, h, d))).astype(jnp.bfloat16)
    v = jax.random.normal(ks[2], (b, t, h, d)).astype(jnp.bfloat16)
    g = -jax.random.uniform(ks[3], (b, t, h, d), minval=0.0, maxval=0.2)
    beta = jax.nn.sigmoid(jax.random.normal(ks[4], (b, t, h)))
    xs = (q, k, v, g, beta)
    weight = jnp.cos(jnp.arange(d, dtype=jnp.float32))

    def programs():
        yield "ops/delta_rule.py", DR, {}
        for path in args.compare:
            yield path, load(path), {}
        for swap in args.swap:
            path, name = swap.rsplit(":", 1)
            yield f"ops/delta_rule.py+{swap}", DR, {
                name: getattr(load(path), name)}

    def timed(fn):
        out = fn(*xs)
        jax.block_until_ready(out)
        t0 = time.perf_counter()
        for _ in range(args.calls):
            out = fn(*xs)
            float(jax.tree_util.tree_leaves(out)[0].ravel()[0])
        jax.block_until_ready(out)
        return (time.perf_counter() - t0) / args.calls * 1e3, out

    def apart(a, b):
        a, b = a.astype(jnp.float32), b.astype(jnp.float32)
        return float(jnp.max(jnp.abs(a - b)) / jnp.max(jnp.abs(a)))

    first = None
    for label, module, patch in programs():
        kept = {name: getattr(module, name) for name in patch}
        for name, value in patch.items():
            setattr(module, name, value)
        jax.clear_caches()
        try:
            op = lambda *a: module.gated_delta_rule(*a).astype(jnp.float32)
            forward = jax.jit(op)
            both = jax.jit(jax.value_and_grad(
                lambda *a: jnp.sum(op(*a) * weight), argnums=(0, 1, 2, 3, 4)))
            temp = both.lower(*xs).compile().memory_analysis()
            fwd_ms, out = timed(forward)
            both_ms, (_, grads) = timed(both)
        finally:
            for name, value in kept.items():
                setattr(module, name, value)
        line = {"program": label, "forward_ms": fwd_ms,
                "forward_backward_ms": both_ms,
                "temp_gib": None if temp is None
                else temp.temp_size_in_bytes / 2 ** 30}
        if first is None:
            first = out, grads
        else:
            line["d_out"] = apart(first[0], out)
            line.update({f"d_{n}": apart(a, b)
                         for n, a, b in zip("qkvgb", first[1], grads)})
        print(json.dumps(line), flush=True)
    return 0


if __name__ == "__main__":
    sys.exit(main())
