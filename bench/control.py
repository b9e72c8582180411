#!/usr/bin/env python3
"""Run a cell's control: the program with the configuration's `control`
override switched on, which breaks one guarantee the configuration
states, so the comparison has to come out not correct.

  python3 bench/control.py --workload <cell> --seed <n> --seconds <s> \
      [--drain-cap <s>]

The override is data in the configuration file (`control`: engine or
problem settings merged over the configuration's own, and `why`). The
benchmark's own runs never run it. Prints the run's result line; exits
non-zero without a TPU.
"""
import time

T_START = time.perf_counter()

import argparse  # noqa: E402
import json  # noqa: E402
import os  # noqa: E402
import sys  # noqa: E402

sys.path.insert(0, os.path.dirname(os.path.abspath(__file__)))

from harness import runner, spec  # noqa: E402


def with_control(cell: spec.Cell, drain_cap=None) -> spec.Cell:
    """The cell with its configuration's control switched on: the
    program runs with the override, while the reference keeps the
    configuration as stated. `drain_cap` replaces the mix's drain cap
    (seconds), for a control whose fault shows before the drain."""
    traffic = cell.traffic if drain_cap is None else dict(
        cell.traffic, drain_cap_s=drain_cap)
    return cell._replace(config=dict(cell.config,
                                     override=cell.config["control"]),
                         traffic=traffic)


def main(argv=None) -> int:
    ap = argparse.ArgumentParser(description=__doc__.split("\n")[0])
    ap.add_argument("--workload", required=True)
    ap.add_argument("--seed", type=int, required=True)
    ap.add_argument("--seconds", type=float, required=True)
    ap.add_argument("--drain-cap", type=float, default=None)
    args = ap.parse_args(argv)
    cell = with_control(spec.load_cell(args.workload), args.drain_cap)
    result = runner.run(cell, args.seed, args.seconds, False, T_START)
    print(json.dumps(result), flush=True)
    return 0


if __name__ == "__main__":
    sys.exit(main())
