"""Time `ops/delta_rule` alone on the chip at a cell's shapes, beside
other copies or paths of the op.

    python3 experiments/delta_rule_probe.py [--cell kimilin_train_8k] \\
        [--compare path/to/other/ops/delta_rule.py ...] \\
        [--swap path/to/other/ops/delta_rule.py:_unit_lower_inverse ...]
    python3 experiments/delta_rule_probe.py --cell qwen3next_serve_longread

`kimilin_train_8k`: one layer-pass of the cell, `gated_delta_rule` on
q, k, v (2, 8192, 32, 128) in bfloat16, g (one decay a key channel) and
beta in float32, chunk 64. For each program: milliseconds a call of the
forward and of the forward + backward (a value fetched after every
call), the compiled temporaries of the forward + backward, and how far
its outputs and gradients lie from the first program's. The programs
are the op as it is; each `--compare` module as it is (an older op);
and the op with each `--swap` function taken from the module named.

`qwen3next_serve_longread`: one delta-rule layer of one chunk of the
served chunk program, `gated_delta_rule_from` a nonzero state on q, k,
v (1, 2048, 32, 128) in float32 with one decay a head, g between -1 and
-20 a token. Forward only (the cell serves): milliseconds a call, the
compiled temporaries, how far the outputs and the state lie from the
first program's, and how far from the recurrence token by token in
float64 on the host (`e_out`, `e_state`). The programs are the channel
path with g broadcast over the key channels (what the cell ran before
the head path), the head path at chunks of 64 and 128, each
`--compare` module with g broadcast, and the head path with each
`--swap` function.

`--trace DIR` adds each program's device time a call (the median run
of its compiled program in a profile of a few calls) and its ten
longest operations: the host's launch and fetch are in the wall a call
and not there.

One JSON line a program. Nothing here is a cell's number: it says which
form the op should take (PERF.md section 6). It needs the TPU; `--toy`
is a dry run on the CPU at tiny shapes.
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
    parser.add_argument("--cell", default="kimilin_train_8k",
                        choices=["kimilin_train_8k",
                                 "qwen3next_serve_longread"],
                        help="whose shapes and paths")
    parser.add_argument("--compare", action="append", default=[],
                        help="another ops/delta_rule.py, timed as it is")
    parser.add_argument("--swap", action="append", default=[],
                        help="FILE:NAME, a function of another "
                             "ops/delta_rule.py tried in the op")
    parser.add_argument("--calls", type=int, default=8)
    parser.add_argument("--trace", metavar="DIR",
                        help="also profile a few calls of each program "
                             "under DIR and print its device time a call "
                             "and its longest operations")
    parser.add_argument("--toy", action="store_true",
                        help="tiny shapes, for a dry run on the CPU")
    args = parser.parse_args(argv)

    import jax
    import jax.numpy as jnp

    from distributed_model_parallel_tpu.ops import delta_rule as DR

    served = args.cell == "qwen3next_serve_longread"
    if served:
        b, t, h, d = (1, 300, 2, 16) if args.toy else (1, 2048, 32, 128)
    else:
        b, t, h, d = (1, 300, 2, 16) if args.toy else (2, 8192, 32, 128)
    ks = jax.random.split(jax.random.PRNGKey(42), 6)
    unit = lambda x: x / jnp.linalg.norm(x, axis=-1, keepdims=True)
    dtype = jnp.float32 if served else jnp.bfloat16
    q = (unit(jax.random.normal(ks[0], (b, t, h, d))) * d ** -0.5).astype(
        dtype)
    k = unit(jax.random.normal(ks[1], (b, t, h, d))).astype(dtype)
    v = jax.random.normal(ks[2], (b, t, h, d)).astype(dtype)
    beta = jax.nn.sigmoid(jax.random.normal(ks[4], (b, t, h)))
    if served:
        g = -jax.random.uniform(ks[3], (b, t, h, 1), minval=1.0,
                                maxval=20.0)
        s0 = 0.1 * jax.random.normal(ks[5], (b, h, d, d))
        xs = (q, k, v, g, beta, s0)
    else:
        g = -jax.random.uniform(ks[3], (b, t, h, d), minval=0.0, maxval=0.2)
        xs = (q, k, v, g, beta)
    weight = jnp.cos(jnp.arange(d, dtype=jnp.float32))

    def channel(module):
        """The op from a state with g broadcast over the key channels."""
        return lambda q, k, v, g, beta, s0: module.gated_delta_rule_from(
            q, k, v, jnp.broadcast_to(g, q.shape), beta, s0)

    def programs():
        """(label, module, {name: function patched in}, op)."""
        if served:
            yield "channel, g broadcast, chunk 64", DR, {}, channel(DR)
            for chunk in (64, 128):
                yield f"head, chunk {chunk}", DR, {}, (
                    lambda *a, chunk=chunk: DR.gated_delta_rule_from(
                        *a, chunk=chunk))
            for path in args.compare:
                module = load(path)
                yield f"{path}, g broadcast", module, {}, channel(module)
            for swap in args.swap:
                path, name = swap.rsplit(":", 1)
                yield f"head, chunk 64, +{swap}", DR, {
                    name: getattr(load(path), name)}, DR.gated_delta_rule_from
            return
        yield "ops/delta_rule.py", DR, {}, DR.gated_delta_rule
        for path in args.compare:
            module = load(path)
            yield path, module, {}, module.gated_delta_rule
        for swap in args.swap:
            path, name = swap.rsplit(":", 1)
            yield f"ops/delta_rule.py+{swap}", DR, {
                name: getattr(load(path), name)}, DR.gated_delta_rule

    def timed(fn):
        out = fn(*xs)
        jax.block_until_ready(out)
        t0 = time.perf_counter()
        for _ in range(args.calls):
            out = fn(*xs)
            float(jax.tree_util.tree_leaves(out)[0].ravel()[0])
        jax.block_until_ready(out)
        return (time.perf_counter() - t0) / args.calls * 1e3, out

    def profiled(fn, where):
        """Device seconds a call (the median run of the program) and
        the ten longest operations by own seconds a call."""
        from benchmark.harness import trace_reduce

        calls = 5
        with jax.profiler.trace(where):
            for _ in range(calls):
                jax.block_until_ready(fn(*xs))
        got = trace_reduce.read_xplane(trace_reduce.find_xplane(where))
        if not got.programs:   # no device plane: the CPU
            return {"device_ms": None}
        runs = max(got.programs.values(), key=sum)
        device = got.devices[sorted(got.devices)[0]]
        own = trace_reduce.reduce_device(device)["self"]
        return {
            "device_ms": 1e3 * sorted(runs)[len(runs) // 2],
            "top_ms": [[name, 1e3 * sec / calls]
                       for name, sec in trace_reduce.top(own)],
        }

    def recurrence64():
        """The recurrence as written, float64, on the host: (outputs,
        the state after the last token)."""
        import numpy as np

        q64, k64, v64, g64, b64, s = (
            np.asarray(x, np.float64) for x in xs)
        out = np.zeros(v64.shape)
        for i in range(t):
            s = np.exp(g64[:, i])[..., None] * s
            read = np.einsum("bhk,bhkv->bhv", k64[:, i], s)
            s = s + (b64[:, i][..., None] * k64[:, i])[..., None] * (
                v64[:, i] - read)[..., None, :]
            out[:, i] = np.einsum("bhk,bhkv->bhv", q64[:, i], s)
        return {"out": out, "state": s}

    def apart(a, b):
        a, b = a.astype(jnp.float32), b.astype(jnp.float32)
        return float(jnp.max(jnp.abs(a - b)) / jnp.max(jnp.abs(a)))

    first = None
    exact = recurrence64() if served else None
    for label, module, patch, raw in programs():
        kept = {name: getattr(module, name) for name in patch}
        for name, value in patch.items():
            setattr(module, name, value)
        jax.clear_caches()
        try:
            if served:
                forward = jax.jit(raw)
                temp = forward.lower(*xs).compile().memory_analysis()
                fwd_ms, out = timed(forward)
                line = {"program": label, "forward_ms": fwd_ms}
                compared = dict(zip(("out", "state"), out))
                if args.trace:
                    line.update(profiled(forward, os.path.join(
                        args.trace, str(len(os.listdir(args.trace))
                                        if os.path.isdir(args.trace)
                                        else 0))))
            else:
                op = lambda *a: raw(*a).astype(jnp.float32)
                forward = jax.jit(op)
                both = jax.jit(jax.value_and_grad(
                    lambda *a: jnp.sum(op(*a) * weight),
                    argnums=(0, 1, 2, 3, 4)))
                temp = both.lower(*xs).compile().memory_analysis()
                fwd_ms, out = timed(forward)
                both_ms, (_, grads) = timed(both)
                line = {"program": label, "forward_ms": fwd_ms,
                        "forward_backward_ms": both_ms}
                compared = {"out": out, **dict(zip("qkvgb", grads))}
        finally:
            for name, value in kept.items():
                setattr(module, name, value)
        line["temp_gib"] = (None if temp is None
                            else temp.temp_size_in_bytes / 2 ** 30)
        if exact is not None:
            line.update({f"e_{n}": apart(jnp.asarray(exact[n]), x)
                         for n, x in compared.items()})
        if first is None:
            first = compared
        else:
            line.update({f"d_{n}": apart(first[n], x)
                         for n, x in compared.items()})
        print(json.dumps(line), flush=True)
    return 0


if __name__ == "__main__":
    sys.exit(main())
