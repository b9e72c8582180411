#!/usr/bin/env python3
"""Run one benchmark cell once on the chip and print its result line.

  python3 bench/run.py --workload <cell> --seed <n> --seconds <s> --trace <0|1>

The cell, its configuration, its traffic mix and its metrics are found
by name from BENCHMARK.json (see bench/harness/__init__.py). With
`--trace 0` the result carries the cell's end-to-end metrics, with
`--trace 1` its per-layer metrics, read from a profiler trace of the
first part of the window. The last line of standard output is one JSON
object: correct, attempted, failed, metrics, device (and with a trace,
breakdown), and last the numbers the comparison checked, each with its
limit; the same numbers are the last lines of standard error. Without
a TPU, or with fewer chips than the cell asks for, it exits non-zero
and prints no result.
"""
import time

T_START = time.perf_counter()

import argparse  # noqa: E402
import json  # noqa: E402
import os  # noqa: E402
import sys  # noqa: E402

sys.path.insert(0, os.path.dirname(os.path.abspath(__file__)))

from harness import runner, spec  # noqa: E402


def parse(argv=None):
    ap = argparse.ArgumentParser(description=__doc__.split("\n")[0])
    ap.add_argument("--workload", required=True)
    ap.add_argument("--seed", type=int, required=True)
    ap.add_argument("--seconds", type=float, required=True)
    ap.add_argument("--trace", type=int, choices=(0, 1), default=0)
    return ap.parse_args(argv)


def main(argv=None) -> int:
    args = parse(argv)
    cell = spec.load_cell(args.workload)
    result = runner.run(cell, args.seed, args.seconds, bool(args.trace),
                        T_START)
    print(json.dumps(result), flush=True)
    return 0


if __name__ == "__main__":
    sys.exit(main())
