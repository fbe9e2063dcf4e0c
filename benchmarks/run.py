#!/usr/bin/env python3
"""Run one cell of BENCHMARK.json once:

    python3 benchmarks/run.py --workload <name> --seed <n> --seconds <s> --trace <0|1>

Refuses to run without a TPU holding the chips the cell asks for. The last
line of standard output is the result object (see harness.py)."""

import time

T_START = time.perf_counter()

import argparse  # noqa: E402
import os  # noqa: E402
import sys  # noqa: E402

sys.path.insert(0, os.path.dirname(os.path.dirname(os.path.abspath(__file__))))


def main() -> None:
    ap = argparse.ArgumentParser(description=__doc__)
    ap.add_argument("--workload", required=True)
    ap.add_argument("--seed", type=int, required=True)
    ap.add_argument("--seconds", type=float, required=True)
    ap.add_argument("--trace", type=int, choices=(0, 1), default=0)
    args = ap.parse_args()
    from benchmarks import harness
    harness.emit(harness.run_cell(args.workload, args.seed, args.seconds,
                                  bool(args.trace), T_START))


if __name__ == "__main__":
    main()
