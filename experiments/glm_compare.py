#!/usr/bin/env python3
"""The served GLM-4.7-Flash configuration against its plain reference at
the published widths, with the lower-precision controls and the planted
faults: what `benchmark/configs/glm-4.7-flash.json`'s tolerances were
set from.

    chiprun -- python3 experiments/glm_compare.py --seeds 2 --faults [--toy]

For each seed: weights from the seed (one jitted call, bfloat16 at
rest), then `serve_drain_latent.check_against_reference` on a fresh
engine (the cell's own check: a document of 2 chunks + 173 tokens
written into latent pages by three runs of the chunk program, 48 decode
steps through the pages, one short request for every slot, the same
document attached again from the prefix cache with a copied page; the
first layer's rows read out of the pool; the router's picks; the expert
layers' own count of the rows they routed), and the reference's
CONTROL: its first layer's rows with the rotation in bfloat16 and its
router in bfloat16, read against itself in float32.

`--rows` looks at why the check makes the reference take the experts
the steps took: each of the check's rows is printed with its distance
from the reference LEFT TO ITS OWN ROUTERS, its distance from the
reference made to take the step's experts (`reference.forward(forced=)`,
what the check holds), the expert layers in which the step resolved a
near-tie the other way than the reference's router, and the widest such
tie (`router_regret`). What the check rests on: the far rows are
exactly the rows with such a tie, the ties are narrow, and with the
experts forced every row is near.

With `--faults`, on the first seed, the same check with each fault of
`tests/benchmark/test_bench_glm.FAULTS` planted in the program (a
bfloat16 router; a bfloat16 rotation; a shared rotary key cached
unrotated; expert layers that route the rows the mask calls not real;
one expert whose rows come back as zeros, which spoils a minority of
the rows; a router that takes the worst expert for its last): each has to come out not `ok`, by the reading `SEEN_BY`
names. One JSON line a reading; `--toy` runs the rehearsal's widths on
the CPU to try the script, its numbers mean nothing. ~1 min a check on
the chip.
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
    parser.add_argument("--first-seed", type=int, default=3600000301)
    parser.add_argument("--faults", action="store_true")
    parser.add_argument("--rows", action="store_true")
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
    import functools

    import jax
    import jax.numpy as jnp
    import numpy as np
    import pytest

    spec = importlib.util.spec_from_file_location(
        "planted", os.path.join(ROOT, "tests/benchmark/test_bench_glm.py"))
    planted = importlib.util.module_from_spec(spec)
    spec.loader.exec_module(planted)

    cell = manifest.resolve_cell(
        manifest.load_manifest(), "glm47f_serve_longdoc")
    builder = manifest.load_module("builder", cell.config["builder"])
    reference = manifest.load_module("reference", cell.config["reference"])
    driver = manifest.load_module("driver", cell.traffic["driver"])
    config = builder.rehearse(cell.config) if args.toy else cell.config
    sizes = builder.shape(config)
    # what the check handed the builder: the tokens
    latent_readings, seen = builder.latent_readings, {}

    def keep(config, reference, params, ids, held):
        seen.update(ids=ids)
        return latent_readings(config, reference, params, ids, held)

    builder.latent_readings = keep
    watch = driver.watch

    def watching(*a, **k):
        sched, sendings = watch(*a, **k)
        seen.update(sched=sched, sendings=sendings)
        return sched, sendings

    driver.watch = watching
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
        rows = [out["logit_err_prefill"], *out["logit_err_decode"]]
        far = lambda errs: sum(e > out["logit_tol"] for e in errs)
        seen.update(check=out)
        return {
            "readings": out["readings"], "ok": out["ok"],
            "rows_largest": max(rows), "rows_smallest": min(rows),
            "rows_far": [far(rows), far(out["attached_logit_err"])],
            "rows": out["rows"],
            "rows_a_tie_went_the_other_way": out[
                "rows_a_tie_went_the_other_way"],
            "regret_by_layer": out["regret_by_layer"],
            "attached_largest": max(out["attached_logit_err"]),
            "attached_smallest": min(out["attached_logit_err"]),
        }

    def rows_table(seed, params):
        """The check, and every one of its rows without the experts
        forced (module doc)."""
        summary = check(seed, params)
        out, sendings, sched = seen["check"], seen["sendings"], seen["sched"]
        size = out["check_tokens"] - driver.LONG_DECODE
        share = lambda diff, of: (
            np.abs(diff).max(axis=1) / np.abs(of).max(axis=1))
        forward = jax.jit(functools.partial(
            reference.forward, rows_from=size - 1,
            **builder.reference_args(config)))
        for which, (sent, rid) in enumerate(zip(
                sendings, ("document", "document again"))):
            tokens = next(f.tokens for f in sched.finished if f.rid == rid)
            ids = np.concatenate([
                np.asarray(seen["ids"][:size]),
                np.asarray(tokens[:driver.LONG_DECODE], np.int32)])
            own = np.asarray(forward(params, ids[None]))[0]
            forced, regret = forward(
                params, ids[None], forced=sent["experts"][None])
            forced, regret = np.asarray(forced)[0], np.asarray(regret)[0]
            alone = share(sent["rows"] - own, own)
            errs = share(sent["rows"] - forced, forced)
            far, tied = alone > out["logit_tol"], (regret > 0).any(-1)
            report(seed, f"rows_of_sending_{which}", rows=[
                [round(float(a), 4), round(float(e), 4),
                 np.nonzero(regret[r])[0].tolist(),
                 round(float(regret[r].max()), 5)]
                for r, (a, e) in enumerate(zip(alone, errs))
            ], columns=["distance_from_the_reference_left_to_its_routers",
                        "distance_with_the_steps_experts",
                        "layers_where_a_tie_went_the_other_way",
                        "widest_of_them"],
                far_alone=int(far.sum()),
                far_rows_with_a_tie=int((far & tied).sum()),
                near_rows_with_a_tie=int((~far & tied).sum()),
                largest_near_row_alone=float(alone[~far].max()),
                largest_with_the_steps_experts=float(errs.max()),
                widest_tie=float(regret.max()),
            )
        return summary

    for seed in range(args.first_seed, args.first_seed + args.seeds):
        params = jax.jit(builder.serving_engine(config).init_params)(
            seed_key(seed))
        report(seed, "program", **(
            rows_table if args.rows else check)(seed, params))
        report(
            seed, "reference_bfloat16_rotation_and_router",
            readings=latent_readings(
                config, reference, params, seen["ids"], None,
                control=jnp.bfloat16))
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
