#!/usr/bin/env python3
"""The served Jamba configuration against its plain reference at the
published widths, with the lower-precision controls and the planted
faults: what `benchmark/configs/jamba2-3b.json`'s tolerances were set
from.

    chiprun -- python3 experiments/jamba_compare.py --seeds 2 --faults [--toy]

For each seed: weights from the seed (one jitted call, bfloat16 at
rest), then `serve_drain_state.check_against_reference` on a fresh
engine (the cell's own check: a prompt of 2 chunks + 173 tokens carried
over three chunks with a padded tail and 16 decode steps, a probe sent
again into a recycled slot, the recurrent state read out of the cache),
and the reference's CONTROL: the reference computed with its
recurrence's state, step sizes and factors in bfloat16, read against
itself in float32. With `--faults`, on the first seed, the same check
with each fault of `tests/benchmark/test_bench_jamba.FAULTS` planted in
the program (a bfloat16 pool; bfloat16 step sizes, factors and state
inside the one-position step with the pool left float32; a skipped
reset; an unmasked tail): each has to come out not `ok`. The
`bfloat16_step` plant patches `selective_step`, which on the chip only
the decode step runs since the chunk program's recurrence is the
`ssm_scan` kernel: here that plant also pins `ops/ssm_scan.py`'s
selector to the loop, so that the fault is in BOTH timed
programs and the check is shown to catch it in each. One JSON line
a reading; `--toy` runs the rehearsal's widths on the CPU to try the
script, its numbers mean nothing. ~1.5 min a check on the chip.
"""

from __future__ import annotations

import argparse
import importlib.util
import json
import os
import sys
import time

ROOT = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
sys.path.insert(0, ROOT)


def main() -> int:
    parser = argparse.ArgumentParser()
    parser.add_argument("--seeds", type=int, default=2)
    parser.add_argument("--first-seed", type=int, default=3400000301)
    parser.add_argument("--faults", action="store_true")
    parser.add_argument("--toy", action="store_true")
    args = parser.parse_args()

    from benchmark.harness import manifest
    from benchmark.harness.device import seed_key
    from distributed_model_parallel_tpu.runtime.platform import (
        enable_compile_cache,
        force_cpu,
    )

    if args.toy:
        force_cpu(1)
    enable_compile_cache()
    import jax
    import jax.numpy as jnp
    import pytest

    from distributed_model_parallel_tpu.ops import ssm_scan

    spec = importlib.util.spec_from_file_location(
        "planted", os.path.join(ROOT, "tests/benchmark/test_bench_jamba.py"))
    planted = importlib.util.module_from_spec(spec)
    spec.loader.exec_module(planted)

    cell = manifest.resolve_cell(manifest.load_manifest(), "jamba2_serve_docs")
    builder = manifest.load_module("builder", cell.config["builder"])
    reference = manifest.load_module("reference", cell.config["reference"])
    driver = manifest.load_module("driver", cell.traffic["driver"])
    config = builder.rehearse(cell.config) if args.toy else cell.config
    sizes = builder.shape(config)
    # what the check handed the builder: the tokens and the state held
    state_readings, seen = builder.state_readings, {}

    def keep(config, reference, params, ids, held):
        seen.update(ids=ids, held=held)
        return state_readings(config, reference, params, ids, held)

    builder.state_readings = keep
    driver.manifest.load_module = (
        lambda kind, name, _load=driver.manifest.load_module:
        builder if (kind, name) == ("builder", cell.config["builder"])
        else _load(kind, name))
    t0 = time.perf_counter()

    def report(seed, what, **more):
        print(json.dumps({
            "seed": seed, "what": what,
            "s": round(time.perf_counter() - t0, 1),
            "device": jax.devices()[0].device_kind, **more,
        }), flush=True)

    def check(seed, params):
        """The cell's check on a FRESH engine, so that what is planted
        is what its steps are traced from."""
        out = driver.check_against_reference(
            builder.serving_engine(config), params, config, seed, sizes,
            reference)
        return {k: out[k] for k in (
            "logit_err_prefill", "logit_err_decode", "carried_logit_err",
            "recycled_logit_diff", "state_readings", "state_slots", "ok")}

    for seed in range(args.first_seed, args.first_seed + args.seeds):
        params = jax.jit(builder.serving_engine(config).init_params)(
            seed_key(seed))
        report(seed, "program", **check(seed, params))
        report(
            seed, "program_state_by_layer",
            distances=builder.state_distances(
                config, reference, params, seen["ids"], seen["held"]))
        report(
            seed, "reference_bfloat16_state",
            readings=state_readings(
                config, reference, params, seen["ids"], None,
                state_dtype=jnp.bfloat16))
        if args.faults and seed == args.first_seed:
            for name, plant in planted.FAULTS.items():
                with pytest.MonkeyPatch.context() as patch:
                    if name == "bfloat16_step":
                        # planted in the loop's body, which the kernel
                        # does not call: the chunk program on the loop
                        patch.setattr(ssm_scan, "_on_tpu", lambda: False)
                    plant(patch)
                    report(seed, name, **check(seed, params))
        del params
    return 0


if __name__ == "__main__":
    sys.exit(main())
