#!/usr/bin/env python3
"""One cell, one run: `python benchmark/run.py --workload <name> --seed <n>
--seconds <s> --trace <0|1>`.

One process (it alone touches JAX): build, check against the plain
reference, warm up, measure about `--seconds`, print ONE JSON object as
the last line of standard output, exit 0. With `--trace 0` the line
carries the cell's end-to-end metrics, with `--trace 1` its per-layer
metrics, the device's busy time in the traced window and a breakdown.
Without a TPU holding exactly the cell's chips it prints no result and
exits 2; `--rehearsal` runs toy widths on the CPU (for the tests: its
numbers mean nothing and its line says `"platform": "cpu"`).
"""

from __future__ import annotations

import time

T_PROCESS = time.perf_counter()  # set-up is counted from here

import argparse  # noqa: E402
import dataclasses  # noqa: E402
import json  # noqa: E402
import os  # noqa: E402
import sys  # noqa: E402

ROOT = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
if ROOT not in sys.path:
    sys.path.insert(0, ROOT)

from benchmark.harness import device, manifest  # noqa: E402


def metric_values(kind: str, cell, record: dict, rehearsal: bool) -> dict:
    """{name: {"value", "unit"}} from the readers the manifest names; a
    reader that finds nothing to read is left out."""
    out = {}
    for entry in getattr(cell, kind):
        reader = manifest.load_module(kind, entry["name"])
        try:
            value = reader.compute(record)
        except ValueError:
            # the CPU has no row in the peak table; on a chip that is
            # an error, in a rehearsal the metric is left out
            if not rehearsal:
                raise
            value = None
        if value is not None:
            out[entry["name"]] = {
                "value": float(value), "unit": entry["unit"]
            }
    return out


def main(argv=None, t_process: float = None) -> int:
    t_process = T_PROCESS if t_process is None else t_process
    parser = argparse.ArgumentParser(description=__doc__.split("\n")[0])
    parser.add_argument("--workload", required=True)
    parser.add_argument("--seed", type=int, required=True)
    parser.add_argument("--seconds", type=float, required=True)
    parser.add_argument("--trace", type=int, choices=(0, 1), default=0)
    parser.add_argument(
        "--rehearsal", action="store_true",
        help="toy widths on the CPU, for the tests; not a measurement",
    )
    args = parser.parse_args(argv)

    cell = manifest.resolve_cell(manifest.load_manifest(), args.workload)
    driver = manifest.load_module("driver", cell.traffic["driver"])
    try:
        from distributed_model_parallel_tpu.runtime.platform import (
            enable_compile_cache,
            force_cpu,
        )
    except ImportError as e:
        print(f"benchmark/run.py: the program is not importable from "
              f"{ROOT}: {e}", file=sys.stderr)
        return 2
    if args.rehearsal:
        # Virtual CPU devices, as many as the cell has chips; a process
        # whose backend is already up (the tests) keeps what it has.
        force_cpu(cell.chips)
        builder = manifest.load_module("builder", cell.config["builder"])
        cell = dataclasses.replace(
            cell, config=builder.rehearse(cell.config),
            traffic=driver.rehearse(cell.traffic),
        )
    # <checkout>/.jax_cache (or JAX_COMPILATION_CACHE_DIR): a fixed path,
    # so every run of a cell after the first finds its programs.
    cache_dir = enable_compile_cache()
    if args.rehearsal:
        import jax

        # Reloaded XLA:CPU executables only add loader warnings.
        jax.config.update("jax_enable_compilation_cache", False)
    try:
        where = device.describe(cell.chips, args.rehearsal)
    except device.NoAccelerator as e:
        print(f"benchmark/run.py: {e}", file=sys.stderr)
        return 2

    record = driver.run(cell, args, t_process)
    record["device"] = where
    for note in record["notes"]:
        print(f"benchmark/run.py: NOT CORRECT: {note}", file=sys.stderr)

    trace = record["device_trace"]
    line = {
        "correct": record["correct"],
        "attempted": record["attempted"],
        "failed": record["failed"],
        "metrics": metric_values(
            "per_layer" if args.trace else "end_to_end", cell, record,
            args.rehearsal,
        ),
        "device": {**where, "memory_peak_bytes": record["memory_peak_bytes"]},
    }
    if args.trace:
        line["device"]["busy_s"] = trace["busy_s"]
        line["device"]["window_s"] = trace["window_s"]
        line["breakdown"] = {
            "device_ops": trace["device_ops"],
            "idle_gaps": trace["idle_gaps"],
        }
    # What the run did, for whoever reads the log; the driver reads only
    # the last line.
    print(json.dumps({"info": {
        "workload": cell.name, "seed": args.seed, "seconds": args.seconds,
        "rehearsal": args.rehearsal, "compile_cache": cache_dir,
        "setup_s": record["setup_s"], "window_s": record["window_s"],
        "compiles_in_window": record["compiles_in_window"],
        "check": record["check"], "notes": record["notes"],
        "memory_stats": device.memory_stats_of_first(),
        **{k: record[k] for k in ("warmup", "saturated", "paged", "prefix")
           if k in record},
        **({"trace": {k: trace[k] for k in (
            "devices", "clock_synced", "longest_gap_s", "coll_total_s",
            "coll_exposed_s", "kernel_s", "program_seconds",
            "program_median_s")}}
           if trace else {}),
    }}), flush=True)
    print(json.dumps(line), flush=True)
    return 0


if __name__ == "__main__":
    sys.exit(main())
