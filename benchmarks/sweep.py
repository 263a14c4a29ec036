#!/usr/bin/env python3
"""The one-off rate sweep that fixes an open-loop cell's rate.

    python3 benchmarks/sweep.py --workload <cell> --base-rate <requests/s>
        [--factors 0.4,0.6,0.8,1.0] [--seconds 20] [--seed 0]

Offers the LENGTHS of the cell's traffic mix as an open loop (seeded
Poisson arrivals, each request timed from its due instant) at each of a
few rates.  Run ONCE on the chip when an open-loop cell is defined; the
rate found goes into the new cell's traffic file as a number and the
table into ``PERF.md``.  A cell never searches for its rate at run time.
One process (one owner of the chip) runs the cell's own driver at each
rate in turn; the engine's programs are traced and compiled once.  Each earlier line is one rate;
the last line names the knee: the highest rate at which requests
completed per second stay within 5% of requests offered per second and
the queue is no deeper at the window's end than at its middle.
"""

from __future__ import annotations

import time

T0 = time.perf_counter()

import argparse
import copy
import json
import os
import sys

BENCH_DIR = os.path.dirname(os.path.abspath(__file__))
ROOT = os.path.dirname(BENCH_DIR)
sys.path.insert(0, ROOT)


def main() -> int:
    parser = argparse.ArgumentParser(description=__doc__.split("\n")[0])
    parser.add_argument("--workload", required=True)
    parser.add_argument("--base-rate", type=float, required=True)
    parser.add_argument("--factors", default="0.4,0.6,0.8,1.0")
    parser.add_argument("--seconds", type=float, default=20.0)
    parser.add_argument("--seed", type=int, default=0)
    parser.add_argument("--ramp-seconds", type=float, default=5.0,
                        help="open-loop seconds before each window opens")
    parser.add_argument("--rehearse", action="store_true")
    args = parser.parse_args()

    from benchmarks.harness import loading, runtime
    from benchmarks.harness.stats import first_token_times, ms, percentile

    _, cell, config, traffic = loading.load_cell(args.workload,
                                                 args.rehearse)
    devices = runtime.claim_devices(int(cell["chips"]), args.rehearse)
    if devices is None:
        return 2
    driver = loading.load_driver(traffic["driver"])
    loads = runtime.ProgramLoads()
    rows = []
    for factor in (float(f) for f in args.factors.split(",")):
        mix = copy.deepcopy(traffic)
        mix["arrivals"] = dict(kind="poisson",
                               rate_per_s=args.base_rate * factor)
        mix["ramp_s"] = args.ramp_seconds
        ctx = runtime.Context(
            cell=cell, config=config, traffic=mix, seed=args.seed,
            seconds=args.seconds, rehearse=args.rehearse,
            t0=time.perf_counter(), root=ROOT,
            out_dir=os.path.join(ROOT, ".bench_out", "sweep"), loads=loads,
            tracer=runtime.TraceSession(False, "", args.seconds),
            emit=lambda **kw: None, devices=devices,
        )
        rec = driver.run(ctx)
        start, end = rec["window"]
        offered = sum(1 for t in rec["due"].values() if start <= t < end)
        ttft = first_token_times(rec["due"], rec["stamps"], rec["window"],
                                 rec["lost"])
        row = dict(
            factor=factor, rate_per_s=mix["arrivals"]["rate_per_s"],
            offered_per_s=offered / rec["window_s"],
            completed_per_s=(rec["attempted"] - rec["failed"])
            / rec["window_s"],
            queue_depth_mid=rec["queue_depth_mid"],
            queue_depth_end=rec["queue_depth_end"],
            ttft_p50_ms=ms(percentile(ttft, 50)),
            ttft_p95_ms=ms(percentile(ttft, 95)),
            tpot_p95_ms=ms(percentile(rec["token_gaps_s"], 95)),
            tokens_per_s=rec["tokens_emitted"] / rec["window_s"],
            correct=rec["correct"],
        )
        row["sustained"] = bool(
            row["completed_per_s"] >= 0.95 * row["offered_per_s"]
            and row["queue_depth_end"] <= row["queue_depth_mid"]
        )
        rows.append(row)
        print(json.dumps(row), flush=True)
    held = [r for r in rows if r["sustained"]]
    print(json.dumps(dict(
        knee_rate_per_s=max((r["rate_per_s"] for r in held), default=None),
        device=dict(platform=devices[0].platform,
                    kind=devices[0].device_kind, count=len(devices)),
        seconds_each=args.seconds, total_s=time.perf_counter() - T0,
    )), flush=True)
    return 0


if __name__ == "__main__":
    sys.exit(main())
