"""One run of one cell: set-up, window, drain, check, metrics."""
from __future__ import annotations

import sys
import time
from typing import Callable, Dict, Optional

from . import check, device, spec
from . import traffic as T
from .context import Context
from .drive import Window
from .trace import Tracer


def log(msg: str) -> None:
    print(f"[bench] {msg}", file=sys.stderr, flush=True)


def run(cell: spec.Cell, seed: int, seconds: float, trace: bool,
        t_start: float, faults: Optional[Callable] = None) -> Dict:
    """The result object of one run. `faults`, for the harness's own
    tests, is called with the built engine and server before the
    window."""
    chips = int(cell.workload["chips"])
    dev = device.require_tpu(chips)
    peaks = spec.peaks(dev["kind"])
    device.enable_cache()
    t_chip = time.perf_counter()
    tracer = Tracer(cell.traffic) if trace else None
    with device.CompileCounter() as counter:
        win = Window(cell, seed, seconds, tracer=tracer)
        win.setup(counter)
        if faults is not None:
            faults(win)
        win.window(counter)
        dev["memory_peak_bytes"] = device.peak_bytes(chips)
        win.drain()
    final = win.final_state()
    rule = spec.reference_rule(cell.config["problem"]["name"])
    numbers = check.compare(
        rule, cell.config["problem"], win.values0, win.addrs, win.pumps,
        win.transitions, final)
    correct = all(v <= lim for _, v, lim in numbers)

    ctx = Context(win, peaks, setup_s=win.w0 - t_start,
                  trace=tracer.reduce() if tracer else None)
    metrics = {}
    for m in (cell.per_layer if trace else cell.end_to_end):
        value = spec.metric_reader(m["name"]).read(ctx)
        if value is not None:
            metrics[m["name"]] = {"value": value, "unit": m["unit"]}
    attempted, failed = ctx.operations()
    result = {"correct": correct, "attempted": attempted, "failed": failed,
              "metrics": metrics, "device": dev}
    if trace and ctx.trace is not None:
        dev["busy_s"] = ctx.trace.busy_s
        dev["window_s"] = ctx.trace.window_s
        result["breakdown"] = ctx.trace.breakdown()

    s, w = win.marks["setup"], win.marks["window"]
    prev, phases = t_start, []
    for name, t in dict(chip=t_chip, **win.setup_phases).items():
        phases.append(f"{name} {t - prev:.3f} s")
        prev = t
    log("set-up: " + ", ".join(phases))
    d = win.drain_stats
    settled_in_window = sum(win.pumps.settled[ctx.window_pumps()])
    log(f"drain (untimed): {d['pumps']} pumps, {d['cycles']} cycles, "
        f"{d['seconds']:.3f} s; window pumps that ended settled: "
        f"{settled_in_window} of "
        f"{win.last_window_pump - win.first_window_pump}")
    log(f"cell {cell.name} seed {seed}: setup {ctx.setup_s:.3f} s, window "
        f"{win.w1 - win.w0:.3f} s, {win.window_cycles} cycles in "
        f"{win.last_window_pump - win.first_window_pump} pumps, "
        f"attempted {attempted}, failed {failed}")
    joins, leaves = (int((win.sched.kind == k).sum())
                     for k in (T.JOIN, T.LEAVE))
    log(f"membership: joins {joins}, leaves {leaves}, stale_dropped "
        f"{win.server.stale_dropped}, final n {final['addrs'].size}")
    log(f"programs: set-up {s['compiles']} ({s['compile_s']:.3f} s), "
        f"{s['cache_hits']} of them loaded from the compile cache; window "
        f"{w['compiles'] - s['compiles']}, "
        f"{w['cache_hits'] - s['cache_hits']} of them from the cache")
    for name, value, limit in numbers:
        log(f"check {name} = {value} (limit {limit})")
    result["checks"] = {name: {"value": value, "limit": limit}
                        for name, value, limit in numbers}
    return result
