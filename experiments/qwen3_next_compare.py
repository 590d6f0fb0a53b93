#!/usr/bin/env python3
"""The served Qwen3-Next configuration against its plain reference at
the published widths, with the lower-precision controls and the planted
faults: what `benchmark/configs/qwen3-next-80b-a3b.json`'s tolerances
were set from.

    python3 experiments/qwen3_next_compare.py --seeds 2 --faults [--toy]

For each seed: weights from the seed (one jitted call, bfloat16 at
rest), then `serve_drain_state_moe.check_against_reference` on a fresh
engine (the cell's own check: a probe sent into a fresh slot and again
into a recycled one; a prompt of 2 chunks + 173 tokens through three
runs of the chunk program and 48 decode steps, every row against the
reference made to take the steps' experts; the first delta-rule layer's
state after the prompt and after the decode steps; the attention
layer's cached rotated keys; the router's picks; the expert layers' own
count of the rows they routed), and the reference's CONTROLS read
against itself in float32: its delta-rule state kept in bfloat16, its
rotation in bfloat16, its router in bfloat16.

With `--faults`, on the first seed, the same check with each fault of
`tests/benchmark/test_bench_qwen3_next.FAULTS` planted in the program
(the delta rule's state rounded to bfloat16 in both programs; keys
cached unrotated; the rotation in bfloat16; a padded tail that decays
and writes the state; one held expert's rows zeroed; a router that
takes the least likely expert for its last; expert layers that route
the rows the mask calls not real): each has to come out not `ok`, by
the reading `SEEN_BY` names. One JSON line a reading; `--toy` runs the
rehearsal's widths on the CPU to try the script, its numbers mean
nothing.
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

CELL = "qwen3next_serve_longread"


def main() -> int:
    parser = argparse.ArgumentParser()
    parser.add_argument("--seeds", type=int, default=2)
    parser.add_argument("--first-seed", type=int, default=4400000301)
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
    import pytest

    spec = importlib.util.spec_from_file_location(
        "planted",
        os.path.join(ROOT, "tests/benchmark/test_bench_qwen3_next.py"))
    planted = importlib.util.module_from_spec(spec)
    spec.loader.exec_module(planted)

    cell = manifest.resolve_cell(manifest.load_manifest(), CELL)
    builder = manifest.load_module("builder", cell.config["builder"])
    reference = manifest.load_module("reference", cell.config["reference"])
    driver = manifest.load_module("driver", cell.traffic["driver"])
    config = builder.rehearse(cell.config) if args.toy else cell.config
    sizes = builder.shape(config)
    # what the check handed the builder: the tokens of each state read,
    # the rows the first expert layer took and chose
    state_readings, fed = builder.state_readings, []
    router_reading, layer_rows = builder.router_reading, []

    def keep(config, reference, params, ids, held, state_dtype=None):
        fed.append(ids)
        return state_readings(config, reference, params, ids, held,
                              state_dtype)

    def keep_rows(config, reference, params, rows, control=None):
        layer_rows[:] = [rows]
        return router_reading(config, reference, params, rows, control)

    builder.state_readings, builder.router_reading = keep, keep_rows
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
        fed.clear()
        out = driver.check_against_reference(
            builder.serving_engine(config), params, config, seed, sizes,
            reference)
        rows = [out["logit_err_prefill"], *out["logit_err_decode"]]
        return {
            "readings": out["readings"], "ok": out["ok"],
            "rows_largest": max(rows), "rows_smallest": min(rows),
            "rows_far": sum(e > out["logit_tol"] for e in rows),
            "rows": out["rows"],
            "expert_rows": out["expert_rows"],
            "expert_rows_decoding": out["expert_rows_decoding"],
            "rows_a_tie_went_the_other_way": out[
                "rows_a_tie_went_the_other_way"],
            "regret_by_layer": out["regret_by_layer"],
            "recycled": out["recycled_logit_diff"],
        }

    for seed in range(args.first_seed, args.first_seed + args.seeds):
        params = jax.jit(builder.serving_engine(config).init_params)(
            seed_key(seed))
        report(seed, "program", **check(seed, params))
        prompt, ids = fed
        report(seed, "reference_controls", readings={
            "delta_state_bfloat16": {
                what: state_readings(config, reference, params, tokens,
                                     None, "bfloat16")
                for what, tokens in (("prompt", prompt), ("decoded", ids))},
            **builder.kv_reading(config, reference, params, ids, None,
                                 rotation="bfloat16"),
            **router_reading(config, reference, params, layer_rows[0],
                             control="bfloat16"),
        })
        if args.faults and seed == args.first_seed:
            for name, plant in planted.FAULTS.items():
                with pytest.MonkeyPatch.context() as patch:
                    plant(patch)
                    report(seed, name, seen_by=planted.SEEN_BY[name],
                           **check(seed, params))
        del params
    return 0


if __name__ == "__main__":
    sys.exit(main())
