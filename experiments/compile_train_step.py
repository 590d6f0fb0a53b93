"""Compile a training cell's whole step for a described v5e, no chip.

    JAX_PLATFORMS=cpu python3 experiments/compile_train_step.py \\
        [--workload kimilin_train_8k] [--take-gib 0.3] [--hlo step.txt]

`cli.lm.main` builds the cell's engine exactly as the benchmark's run
does, but over the DESCRIBED device (this script hands it that in
place of `jax.devices()` and answers "tpu" to the ops that ask for the
backend, so the Pallas kernels are the chip's and not the
interpreter's); the engine's `train_step` is then lowered on shapes and
compiled by the TPU's compiler. What the chip's compiler would refuse
it refuses here, to the byte: `kimilin_train_8k` sits at 16.8 of the
chip's 16.9 GB, and a step 0.27 GiB too large read "Used 16.01G of
15.75G hbm" here as on the chip (PERF.md §6, PR 37). ~70 s a compile.

`--take-gib` adds an argument of that size that is only passed
through (it costs twice that, as argument and as result): how much room
the step can give up and still compile says whether its peak is what
it needs or what the scheduler took. A four-chip cell compiles over the
described 2x2 host with its state laid out as the plan engine says
(`gpt2xl_train_fsdp4`, ~100 s; the memory is a chip's). Nothing runs,
so this says nothing about results or times.
"""

from __future__ import annotations

import argparse
import os
import sys
import tempfile
import time

os.environ.setdefault("TPU_LOG_DIR", "disabled")
sys.path.insert(0, os.path.dirname(os.path.dirname(os.path.abspath(__file__))))


def main(argv=None) -> int:
    parser = argparse.ArgumentParser(description=__doc__.splitlines()[0])
    parser.add_argument("--workload", default="kimilin_train_8k")
    parser.add_argument("--take-gib", type=float, default=0.0)
    parser.add_argument("--hlo", help="write the compiled HLO text here")
    args = parser.parse_args(argv)

    import jax
    import jax.numpy as jnp
    from jax.experimental import topologies
    from jax.sharding import NamedSharding, PartitionSpec

    topo = topologies.get_topology_desc(
        platform="tpu", topology_name="v5e:2x2")
    described = list(topo.devices)
    jax.devices = jax.local_devices = lambda *a, **k: described
    jax.default_backend = lambda: "tpu"

    from benchmark.harness import manifest
    from distributed_model_parallel_tpu.cli import lm
    from distributed_model_parallel_tpu.parallel.sequence_parallel import (
        TrainState,
    )

    cell = manifest.resolve_cell(manifest.load_manifest(), args.workload)
    del described[cell.chips:]
    builder = manifest.load_module("builder", cell.config["builder"])

    class EngineBuilt(Exception):
        pass

    def stop_at_trainer(engine, *_, **__):
        raise EngineBuilt(engine)

    lm.Trainer = stop_at_trainer
    with tempfile.TemporaryDirectory() as out:
        try:
            lm.main(builder.lm_argv(cell.config, cell.traffic, 1, out))
        except EngineBuilt as built:
            engine = built.args[0]

    whole = NamedSharding(engine.mesh, PartitionSpec())
    shaped = lambda shape, dtype, sharding=whole: jax.ShapeDtypeStruct(
        shape, dtype, sharding=sharding)

    def init(rng):
        params, model_state = engine._full.init(rng)
        return TrainState(params, model_state, engine.optimizer.init(params),
                          jnp.zeros((), jnp.int32))

    state = jax.eval_shape(init, jax.ShapeDtypeStruct((2,), jnp.uint32))
    if cell.chips == 1:
        layout = jax.tree_util.tree_map(lambda _: whole, state)
    else:
        # a plan engine says how its state lies over the chips
        layout = jax.tree_util.tree_map(
            lambda spec: NamedSharding(engine.mesh, spec),
            engine.state_partition_specs(),
            is_leaf=lambda x: isinstance(x, PartitionSpec),
        )
    state = jax.tree_util.tree_map(
        lambda x, sharding: shaped(x.shape, x.dtype, sharding), state, layout)
    training = cell.config["training"]
    ids = shaped((training["batch_size"], training["seq_len"]), jnp.int32,
                 engine._batch)
    lr = shaped((), jnp.float32)
    t0 = time.time()
    if args.take_gib:
        step = engine.train_step
        lowered = jax.jit(
            lambda s, x, y, r, ballast: (step(s, x, y, r), ballast * 2.0),
            donate_argnums=(0,),
        ).lower(state, ids, ids, lr,
                shaped((int(args.take_gib * 2 ** 28),), jnp.float32))
    else:
        lowered = engine.train_step.lower(state, ids, ids, lr)
    compiled = lowered.compile()
    memory = compiled.memory_analysis()
    print(f"{cell.name}: compiled for {described[0].device_kind} in "
          f"{time.time() - t0:.0f} s; temporaries "
          f"{memory.temp_size_in_bytes / 2 ** 30:.3f} GiB, arguments "
          f"{memory.argument_size_in_bytes / 2 ** 30:.3f} GiB")
    if args.hlo:
        with open(args.hlo, "w") as f:
            f.write(compiled.as_text())
    return 0


if __name__ == "__main__":
    sys.exit(main())
