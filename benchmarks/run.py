#!/usr/bin/env python3
"""The benchmark's command: one cell, one process, one last line.

    python3 benchmarks/run.py --workload <cell> --seed <n> --seconds <s> --trace <0|1>

Fails (non-zero, no result line) unless JAX reports a TPU with the chips
the cell asks for.  Loads the cell's files by name, lets the cell's
driver build, warm and measure, reads each metric through its own
reader, and prints ``correct / attempted / failed / metrics / device``
(and ``breakdown`` with ``--trace 1``) as the last line of stdout.
Earlier lines are one JSON object each, for a reader.

``--rehearse`` walks the same control flow on the CPU at the tiny sizes
of each file's ``rehearse`` block.  It prints no time, rate or share
under any metric's name: only exact counts the program made.
"""

from __future__ import annotations

import time

T0 = time.perf_counter()  # set-up is counted from here

import argparse
import os
import sys

BENCH_DIR = os.path.dirname(os.path.abspath(__file__))
ROOT = os.path.dirname(BENCH_DIR)
sys.path.insert(0, ROOT)


def main() -> int:
    parser = argparse.ArgumentParser(description=__doc__.split("\n")[0])
    parser.add_argument("--workload", required=True)
    parser.add_argument("--seed", type=int, default=0)
    parser.add_argument("--seconds", type=float, default=None)
    parser.add_argument("--trace", type=int, choices=(0, 1), default=0)
    parser.add_argument("--rehearse", action="store_true",
                        help="tiny sizes on the CPU; prints no device number")
    args = parser.parse_args()

    from benchmarks.harness import loading, runtime

    bench, cell, config, traffic = loading.load_cell(
        args.workload, args.rehearse)
    seconds = args.seconds if args.seconds is not None \
        else float(bench["run_seconds"])
    chips = int(cell["chips"])

    import importlib.util

    if importlib.util.find_spec("skycomputing_tpu") is None:
        print(f"benchmark: the program (skycomputing_tpu) is not in {ROOT}; "
              f"the benchmark measures it and has no copy", file=sys.stderr)
        return 2

    devices = runtime.claim_devices(chips, args.rehearse)
    if devices is None:
        return 2
    import jax

    out_dir = os.path.join(ROOT, ".bench_out", cell["name"])
    os.makedirs(out_dir, exist_ok=True)
    ctx = runtime.Context(
        cell=cell, config=config, traffic=traffic,
        seed=int(args.seed), seconds=seconds, rehearse=args.rehearse,
        t0=T0, root=ROOT, out_dir=out_dir,
        loads=runtime.ProgramLoads(),
        tracer=runtime.TraceSession(
            enabled=bool(args.trace) and not args.rehearse,
            out_dir=os.path.join(out_dir, "trace"), window_s=seconds,
        ),
        devices=devices,
    )
    runtime.emit(
        event="start", cell=cell["name"], seed=ctx.seed, seconds=seconds,
        trace=args.trace, rehearse=args.rehearse, jax=jax.__version__,
        device=dict(platform=devices[0].platform,
                    kind=devices[0].device_kind, count=len(devices)),
    )
    record = loading.load_driver(traffic["driver"]).run(ctx)
    record["device_kind"] = devices[0].device_kind

    kind = "per_layer" if args.trace else "end_to_end"
    metrics = {}
    for metric in loading.cell_metrics(bench, cell["name"], kind):
        if args.rehearse and metric["source"] != "program_counter":
            value = None  # a CPU run has no time, rate or share to give
        else:
            value = loading.metric_reader(kind, metric["name"]).read(record)
            if value is None:
                continue  # nothing to read: the metric is left out
        metrics[metric["name"]] = dict(value=value, unit=metric["unit"])

    device = runtime.device_record(devices, chips)
    print(runtime.last_line(record, metrics, device), flush=True)
    return 0


if __name__ == "__main__":
    sys.exit(main())
