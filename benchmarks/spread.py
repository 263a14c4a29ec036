#!/usr/bin/env python3
"""Sets of runs of one cell, and the spread of each metric: what a new
cell's bounds are set from.

    python3 benchmarks/spread.py --workload <cell> [--sets 2]
        [--seeds 101,2147483749,...] [--seconds <run_seconds>] [--out <file>]

Runs the benchmark's own command as a child process for each seed of
each set (this parent never touches JAX, so the child owns the chip),
the same seeds in every set, and prints for each end-to-end metric and
set: median, and the distance between the quartiles as a share of the
median (``statistics.quantiles(n=4)``, the contract's spread).  The first
run's ``setup_s`` is left out: it compiles.  On the chip, run it as
``chiprun --chips 1 -- python3 benchmarks/spread.py ...`` so that the
runs of a cell share one call and one compile cache.
"""

from __future__ import annotations

import argparse
import json
import os
import statistics
import subprocess
import sys
import time

BENCH_DIR = os.path.dirname(os.path.abspath(__file__))
ROOT = os.path.dirname(BENCH_DIR)
sys.path.insert(0, ROOT)

SEEDS = "101,2147483749,303,2147483951,505,2147484153"


def one_run(bench: dict, cell: str, seed: int, seconds: int,
            rehearse: bool):
    command = bench["command"] + [
        "--workload", cell, "--seed", str(seed),
        "--seconds", str(seconds), "--trace", "0",
    ] + (["--rehearse"] if rehearse else [])
    began = time.perf_counter()
    done = subprocess.run(command, cwd=ROOT, capture_output=True, text=True)
    lines = done.stdout.strip().splitlines()
    if done.returncode != 0 or not lines:
        print(f"seed {seed}: exit {done.returncode}\n{done.stderr[-2000:]}",
              file=sys.stderr, flush=True)
        return None
    return dict(seed=seed, wall_s=time.perf_counter() - began,
                last=json.loads(lines[-1]),
                earlier=[json.loads(l) for l in lines[:-1]
                         if l.startswith("{")])


def main() -> int:
    from benchmarks.harness import loading
    from benchmarks.harness.stats import iqr_share

    bench = loading.load_benchmark()
    parser = argparse.ArgumentParser(description=__doc__.split("\n")[0])
    parser.add_argument("--workload", required=True)
    parser.add_argument("--sets", type=int, default=2)
    parser.add_argument("--seeds", default=SEEDS)
    parser.add_argument("--seconds", type=int, default=bench["run_seconds"])
    parser.add_argument("--out", default=None,
                        help="append every run as a JSON line to this file")
    parser.add_argument("--rehearse", action="store_true",
                        help="the CPU walk-through: no number, control "
                             "flow only")
    args = parser.parse_args()
    loading.find_cell(bench, args.workload)
    seeds = [int(s) for s in args.seeds.split(",")]

    sets = []
    for k in range(args.sets):
        rows = []
        for seed in seeds:
            run = one_run(bench, args.workload, seed, args.seconds,
                          args.rehearse)
            if run is None:
                continue
            rows.append(run)
            last = run["last"]
            print(json.dumps(dict(
                set=k, seed=seed, correct=last["correct"],
                attempted=last["attempted"], failed=last["failed"],
                wall_s=run["wall_s"],
                memory_peak_bytes=last["device"]["memory_peak_bytes"],
                **{n: m["value"] for n, m in last["metrics"].items()},
            )), flush=True)
            if args.out:
                with open(args.out, "a") as fh:
                    fh.write(json.dumps(dict(
                        cell=args.workload, set=k, seconds=args.seconds,
                        **run)) + "\n")
        sets.append(rows)

    names = sorted({n for rows in sets for r in rows
                    for n in r["last"]["metrics"]})
    for name in names:
        for k, rows in enumerate(sets):
            values = [r["last"]["metrics"][name]["value"] for r in rows]
            values = [v for v in values if v is not None]
            if name == "setup_s" and k == 0:
                values = values[1:]  # the first run compiles
            if len(values) >= 2:
                print(json.dumps(dict(
                    metric=name, set=k, runs=len(values),
                    median=statistics.median(values),
                    spread=iqr_share(values),
                    min=min(values), max=max(values),
                )), flush=True)
    return 0


if __name__ == "__main__":
    sys.exit(main())
