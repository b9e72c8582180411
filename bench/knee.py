#!/usr/bin/env python3
"""Find the knee of a served cell once, by a sweep on the chip.

  python3 bench/knee.py --workload <cell> --seed <n> --seconds <s> \
      --rates 50,100,200,...

One process, one engine: set-up as the cell's run does it, then for
each offered rate a window of `--seconds` of the cell's traffic at that
rate followed by a drain, printing per rate the decision latency p50 and
p95, the failures, the pumps, and the age of the oldest unsettled update
at the pumps of the window's first and second half. The knee is the
highest rate at which that age does not grow from the first half to the
second and nothing fails; a cell runs at about four fifths of it. Every
window is also held to the cell's comparison. Prints one JSON line per
rate; without a TPU it exits non-zero.
"""
import time

T_START = time.perf_counter()

import argparse  # noqa: E402
import json  # noqa: E402
import os  # noqa: E402
import sys  # noqa: E402

import numpy as np  # noqa: E402

sys.path.insert(0, os.path.dirname(os.path.abspath(__file__)))

from harness import check, device, spec  # noqa: E402
from harness.context import Context  # noqa: E402
from harness.drive import Window  # noqa: E402
from harness.latency import percentiles_ms  # noqa: E402


def oldest_unsettled(due, lat, ends) -> np.ndarray:
    """At each pump end, the age of the oldest update due and not yet
    settled (0 where none is open)."""
    out = np.zeros(len(ends))
    for i, e in enumerate(ends):
        open_ = (due <= e) & (due + lat > e)
        if open_.any():
            out[i] = e - due[open_].min()
    return out


def main(argv=None) -> int:
    ap = argparse.ArgumentParser(description=__doc__.split("\n")[0])
    ap.add_argument("--workload", required=True)
    ap.add_argument("--seed", type=int, required=True)
    ap.add_argument("--seconds", type=float, required=True)
    ap.add_argument("--rates", required=True)
    ap.add_argument("--warm", type=int, default=0,
                    help="largest flush size warmed in set-up (default: "
                    "1.5 x the fastest rate + 16)")
    args = ap.parse_args(argv)
    cell = spec.load_cell(args.workload)
    rates = [float(r) for r in args.rates.split(",")]
    # every flush size the fastest rate can bring, warmed in set-up
    warm = args.warm or int(max(rates) * 1.5) + 16
    cell = cell._replace(traffic=dict(cell.traffic, warm_batch_max=max(
        warm, int(cell.traffic.get("warm_batch_max", 0)))))
    dev = device.require_tpu(int(cell.workload["chips"]))
    device.enable_cache()
    with device.CompileCounter() as counter:
        win = Window(cell, args.seed, args.seconds)
        win.setup(counter)
        print(json.dumps({"setup_s": win.clock() - T_START,
                          "device": dev, **counter.mark()}), flush=True)
        for rate in rates:
            before = counter.mark()
            n_trace = len(win.server.trace)
            win.cell = cell._replace(traffic=dict(cell.traffic,
                                                  rate_per_s=rate))
            win.window(counter)
            win.drain()
            ctx = Context(win, {}, 0.0)
            lat, failed = ctx.latencies()
            due = win.w0 + win.sched.due
            p = win.pumps
            sl = ctx.window_pumps()
            ends = np.asarray(p.end[sl])
            age = oldest_unsettled(due, lat, ends)
            mid = win.w0 + args.seconds / 2
            flushes = [len(f[0]) for f in p.flush[sl]]
            rec = {"rate_per_s": rate, "updates": int(lat.size),
                   "failed": int(failed.sum()),
                   "pumps": sl.stop - sl.start,
                   "cycles_per_s": win.window_cycles / (win.w1 - win.w0),
                   **{f"decision_ms_{k}": v
                      for k, v in percentiles_ms(lat).items()},
                   "oldest_ms_first_half": float(age[ends < mid].max(
                       initial=0) * 1e3),
                   "oldest_ms_second_half": float(age[ends >= mid].max(
                       initial=0) * 1e3),
                   "settled_share": float(np.mean(p.settled[sl])),
                   "flush_max": int(max(flushes, default=0)),
                   "window_programs_compiled": (
                       counter.compiles - counter.cache_hits
                       - before["compiles"] + before["cache_hits"]),
                   "epochs_closed": [r["cycles"] for r in
                                     win.server.trace[n_trace:]
                                     if r["kind"] == "settle"],
                   "drain_s": win.drain_end - win.w1}
            print(json.dumps(rec), flush=True)
    final = win.final_state()
    rule = spec.reference_rule(cell.config["problem"]["name"])
    numbers = check.compare(
        rule, cell.config["problem"], win.values0, win.addrs, win.pumps,
        win.transitions, final)
    print(json.dumps({"checks": {n: v for n, v, _ in numbers}}),
          flush=True)
    return 0


if __name__ == "__main__":
    sys.exit(main())
