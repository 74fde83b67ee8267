"""Run one cell of the chip benchmark once.

    python3 benchmarks/chip/run.py --workload <name> --seed <n> \\
        --seconds <s> --trace <0|1>

from the root of a checkout. The cell (configuration x traffic mix) is
looked up in BENCHMARK.json; its files are found by name under this
directory (see chipbench/spec.py). The run loads, warms up, measures for
--seconds, checks what it served against the plain float32 reference and
prints one JSON object as the last line of stdout: `correct`, `attempted`,
`failed`, `metrics` (the cell's end-to-end metrics, or with --trace 1 its
per-layer ones), `device`, with --trace 1 `breakdown`, and last `checks`,
each number compared beside its limit. The same checks end stderr.

It exits non-zero, printing no result, when JAX finds no TPU or fewer chips
than the cell asks for, or when the program's sources are not beside it.
"""
import time

T_START = time.perf_counter()

import argparse  # noqa: E402
import json  # noqa: E402
import os  # noqa: E402
import sys  # noqa: E402

HERE = os.path.dirname(os.path.abspath(__file__))
sys.path.insert(0, HERE)

from chipbench import BENCH_DIR, CHECKOUT, harness, spec, system  # noqa: E402


def main(argv=None) -> int:
    ap = argparse.ArgumentParser(description=__doc__.split("\n\n")[0])
    ap.add_argument("--workload", required=True)
    ap.add_argument("--seed", type=int, required=True)
    ap.add_argument("--seconds", type=float, required=True)
    ap.add_argument("--trace", type=int, choices=(0, 1), default=0)
    args = ap.parse_args(argv)
    try:
        cell = spec.load_cell(CHECKOUT, BENCH_DIR, args.workload)
        result = harness.run(cell, BENCH_DIR, CHECKOUT, args.seed,
                             args.seconds, bool(args.trace),
                             t_start=T_START)
    except (harness.NoChip, system.ProgramMissing, spec.SpecError) as err:
        print(f"[chipbench] cannot run: {err}", file=sys.stderr, flush=True)
        return 2
    print(json.dumps(result), flush=True)
    return 0


if __name__ == "__main__":
    sys.exit(main())
