#!/usr/bin/env python3
"""Repeatability of one cell, measured as the driver measures it: sets of
runs of the same code, each run a new process with another seed, and for
each metric the spread of a set (distance between its quartiles over its
median). `PERF.md`'s repeatability record and the bounds in
BENCHMARK.json come from this tool's output on the chip.

    python benchmark/repeat.py --workload gpt2s_train --sets 2 --runs 6 \
        --out chiprun_out/repeat_gpt2s_train.json

This process never touches JAX (a parent that did would hold the chip).
"""

from __future__ import annotations

import argparse
import json
import os
import subprocess
import sys
import time

ROOT = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
if ROOT not in sys.path:
    sys.path.insert(0, ROOT)

from benchmark.harness.manifest import load_manifest  # noqa: E402
from benchmark.harness.stats import percentile, spread  # noqa: E402


def one_run(workload: str, seed: int, seconds: float, trace: int) -> dict:
    t0 = time.perf_counter()
    proc = subprocess.run(
        [sys.executable, os.path.join(ROOT, "benchmark", "run.py"),
         "--workload", workload, "--seed", str(seed),
         "--seconds", str(seconds), "--trace", str(trace)],
        cwd=ROOT, stdout=subprocess.PIPE, stderr=subprocess.PIPE, text=True,
    )
    wall = time.perf_counter() - t0
    lines = proc.stdout.strip().splitlines()
    if proc.returncode or not lines:
        sys.stderr.write(proc.stderr[-4000:])
        raise SystemExit(
            f"{workload} seed {seed}: exit {proc.returncode} after "
            f"{wall:.0f}s"
        )
    out = json.loads(lines[-1])
    out["process_s"] = wall
    out["seed"] = seed
    out["info"] = next(
        (json.loads(x)["info"] for x in reversed(lines[:-1])
         if x.startswith('{"info"')), None,
    )
    return out


def summarise(runs: list) -> dict:
    out = {}
    for name in runs[0]["metrics"]:
        values = [r["metrics"][name]["value"] for r in runs]
        out[name] = {
            "median": percentile(values, 50),
            "spread": spread(values),
            "values": values,
        }
    return out


def main(argv=None) -> int:
    p = argparse.ArgumentParser(description=__doc__.split("\n")[0])
    p.add_argument("--workload", required=True)
    p.add_argument("--sets", type=int, default=2)
    p.add_argument("--runs", type=int, default=6)
    p.add_argument("--seconds", type=float, default=None,
                   help="default: run_seconds of BENCHMARK.json")
    p.add_argument("--first-seed", type=int, default=100)
    p.add_argument("--trace", type=int, choices=(0, 1), default=0)
    p.add_argument("--out", default=None)
    args = p.parse_args(argv)
    seconds = args.seconds or load_manifest()["run_seconds"]

    sets = []
    seed = args.first_seed
    for s in range(args.sets):
        runs = []
        for _ in range(args.runs):
            run = one_run(args.workload, seed, seconds, args.trace)
            seed += 1
            runs.append(run)
            print(f"set {s} seed {run['seed']} correct={run['correct']} "
                  f"process={run['process_s']:.1f}s " + " ".join(
                      f"{k}={v['value']:.6g}"
                      for k, v in run["metrics"].items()), flush=True)
        sets.append({"runs": runs, "summary": summarise(runs)})
    for name in sets[0]["summary"]:
        row = [f"{name:>24}"]
        for s in sets:
            m = s["summary"][name]
            row.append(f"median {m['median']:.6g} spread "
                       f"{100 * m['spread']:.3f}%")
        print("  |  ".join(row), flush=True)
    if args.out:
        os.makedirs(os.path.dirname(os.path.abspath(args.out)), exist_ok=True)
        with open(args.out, "w") as f:
            json.dump({"workload": args.workload, "seconds": seconds,
                       "sets": sets}, f, indent=1)
    return 0


if __name__ == "__main__":
    sys.exit(main())
