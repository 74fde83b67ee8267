"""Readings that set a cell's correctness limit; not part of a benchmark run.

    python3 benchmarks/chip/control.py --workload <name> --seconds <s> \\
        --seeds <n> [<n> ...] [--rate <requests/s> ...] \\
        [--plant <fault> | --free-gaps]

For each seed, in one process: one run of the cell as the benchmark makes
it, then the check read twice over the same sample, once for what the
program served (served_*) and once for the control (control_*: the
reference computed in int8, `chipbench.reference`). The control is judged
by the cell's own limits, each served_* number read as its control_* one.
Prints one JSON line per seed with those readings, `correct` (the
program's verdict), `control_correct` (the control's, which has to be
false), each compared number beside its limit, and the run's end-to-end
metrics. A limit's lower reading is the largest served_* number over a
dozen seeds or more, its upper one the smallest control_* number over three
or more.

--rate replaces an open-loop mix's arrival rate, for the sweep that finds
the knee (its TTFT tail at each rate is in the printed metrics).
--plant breaks the timed path with one of `chipbench.faults` and reads the
program only: `correct` has to be false.
--free-gaps draws an open loop's gaps between arrivals freely from the
exponential (Poisson arrivals, as the program's own workload generator
does) instead of the stratified set; sizes stay stratified.
"""
import argparse
import dataclasses
import json
import os
import sys
import time

import numpy as np

sys.path.insert(0, os.path.dirname(os.path.abspath(__file__)))

from chipbench import BENCH_DIR, CHECKOUT, harness, spec, traffic  # noqa: E402
from chipbench.faults import FAULTS  # noqa: E402


def free_gaps(seed: int):
    """exponential_gaps' stand-in: free draws at the rate, from the seed."""
    rng = np.random.default_rng([int(seed), 5])
    return lambda rate, n, _duration: rng.exponential(1.0 / rate, n)


def main(argv=None) -> int:
    ap = argparse.ArgumentParser(description=__doc__.split("\n\n")[0])
    ap.add_argument("--workload", required=True)
    ap.add_argument("--seconds", type=float, required=True)
    ap.add_argument("--seeds", type=int, nargs="+", required=True)
    ap.add_argument("--rate", type=float, nargs="*", default=[])
    how = ap.add_mutually_exclusive_group()
    how.add_argument("--plant", choices=sorted(FAULTS))
    how.add_argument("--free-gaps", action="store_true")
    args = ap.parse_args(argv)
    cell = spec.load_cell(CHECKOUT, BENCH_DIR, args.workload)
    stratified = traffic.exponential_gaps
    for rate in args.rate or [None]:
        c = cell if rate is None else dataclasses.replace(
            cell, traffic=dict(cell.traffic, rate_per_s=rate))
        for seed in args.seeds:
            traffic.exponential_gaps = free_gaps(seed) if args.free_gaps \
                else stratified
            res = harness.run(c, BENCH_DIR, CHECKOUT, seed, args.seconds,
                              False, t_start=time.perf_counter(),
                              plant=FAULTS.get(args.plant),
                              control=not (args.plant or args.free_gaps))
            line = {"seed": seed, "rate": rate, "plant": args.plant,
                    "free_gaps": args.free_gaps,
                    **res.get("readings", {}), "correct": res["correct"]}
            if "control_correct" in res:
                line["control_correct"] = res["control_correct"]
                line["control_checks"] = res["control_checks"]
            line.update({
                "attempted": res["attempted"], "failed": res["failed"],
                "metrics": {k: v["value"] for k, v in res["metrics"].items()},
                "memory_peak_bytes": res["device"]["memory_peak_bytes"],
                "checks": res["checks"]})
            print(json.dumps(line), flush=True)
    return 0


if __name__ == "__main__":
    sys.exit(main())
