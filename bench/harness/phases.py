"""Per-layer readings of the program's own spans and cycle scopes.

Phase metrics: device ms per cycle of each group of the superstep's
named phases (`repro.runtime.tracing.PHASES`). Each `jit__steps_impl`
op's self time (harness/trace.attribute) goes to the phase that
`JaxEngine.op_phases()` names for its HLO instruction; the profiler
trace drops HLO metadata, so the map comes from the compiled program.

Gap metrics: device-idle ms per pump while the host is inside one of a
stage's program spans (`repro.runtime.tracing.SPANS`): the part of the
traced window in which no op ran on the chip and such a span was open,
by intervals, averaged over the chips used.

A program without the map or the spans reads nothing (None).
"""
from __future__ import annotations

from typing import Dict, List, Optional, Tuple

import numpy as np

from .readers import STEPS_PROGRAM

PHASE_GROUPS = {
    "deliver": ("cycle.scan", "cycle.descent"),
    "accept": ("cycle.accept",),
    "react": ("cycle.react",),
    "stage": ("cycle.wheel", "cycle.stage", "cycle.probe", "cycle.append",
              "cycle.account"),
}
GAP_SPANS = {
    "output": ("engine.knowledge", "engine.readback"),
    "publish": ("serve.diff", "serve.deliver", "serve.account"),
    "flush": ("serve.ingest", "engine.scatter", "engine.react"),
}

Intervals = List[Tuple[float, float]]


def op_phases(ctx) -> Optional[Dict[str, str]]:
    """{HLO instruction name: cycle phase} of the run's superstep
    program, read once per run; None where the engine has no map."""
    if "_op_phases" not in vars(ctx):
        read = getattr(ctx.engine, "op_phases", None)
        ctx._op_phases = (read() or None) if read is not None else None
    return ctx._op_phases


def instruction(op_text: str) -> str:
    """`%fusion.483 = u32[...] fusion(...)` -> `fusion.483`."""
    return op_text.split(" ", 1)[0].lstrip("%")


def steps_self_ns(tr, phases: Dict[str, str]) -> Dict[str, float]:
    """Self ns of the superstep's ops inside the traced window by cycle
    phase ("" for ops the map names no phase for), averaged over
    chips."""
    per = []
    for evs in tr.ops.values():
        by: Dict[str, float] = {}
        for e in evs:
            if e.module == STEPS_PROGRAM and tr._inside(e):
                p = phases.get(instruction(e.name), "")
                by[p] = by.get(p, 0.0) + e.self_ns
        per.append(by)
    keys = {k for by in per for k in by}
    return {k: float(np.mean([by.get(k, 0.0) for by in per])) for k in keys}


def phase_ms(ctx, group: str) -> Optional[float]:
    tr = ctx.trace
    if tr is None or tr.cycles <= 0:
        return None
    phases = op_phases(ctx)
    if not phases:
        return None
    by = steps_self_ns(tr, phases)
    ns = sum(by.get(p, 0.0) for p in PHASE_GROUPS[group])
    if ns <= 0:
        return None
    return ns / tr.cycles / 1e6


def merged(intervals: Intervals, lo: float, hi: float) -> Intervals:
    """Sorted disjoint [start, end) intervals covering `intervals`
    clipped to [lo, hi)."""
    out: Intervals = []
    for s, e in sorted(intervals):
        s, e = max(s, lo), min(e, hi)
        if e <= s:
            continue
        if out and s <= out[-1][1]:
            out[-1] = (out[-1][0], max(out[-1][1], e))
        else:
            out.append((s, e))
    return out


def overlap_ns(a: Intervals, b: Intervals) -> float:
    """Length of the intersection of two merged interval lists."""
    total, i, j = 0.0, 0, 0
    while i < len(a) and j < len(b):
        s, e = max(a[i][0], b[j][0]), min(a[i][1], b[j][1])
        if e > s:
            total += e - s
        if a[i][1] < b[j][1]:
            i += 1
        else:
            j += 1
    return total


def idle_in_spans_ns(tr, names) -> Optional[float]:
    """Device-idle ns inside the union of the host spans `names`, over
    the traced window, averaged over chips; None without such spans."""
    spans = merged([(s.start, s.start + s.dur) for s in tr.spans
                    if s.name in names], tr.lo, tr.hi)
    if not spans or not tr.ops:
        return None
    inside = sum(e - s for s, e in spans)
    per = [inside - overlap_ns(spans, merged(
        [(e.start, e.start + e.dur) for e in evs], tr.lo, tr.hi))
        for evs in tr.ops.values()]
    return float(np.mean(per))


def gap_ms(ctx, stage: str) -> Optional[float]:
    tr = ctx.trace
    if tr is None or tr.pumps <= 0:
        return None
    ns = idle_in_spans_ns(tr, GAP_SPANS[stage])
    if ns is None:
        return None
    return ns / tr.pumps / 1e6


def deliver_device_ms(ctx):
    """Device ms per cycle of cycle.scan and cycle.descent: the due-slot
    read, the fault plane, Alg. 1 delivery and the descent tail."""
    return phase_ms(ctx, "deliver")


def accept_device_ms(ctx):
    """Device ms per cycle of cycle.accept: the dedup election and the
    link writes."""
    return phase_ms(ctx, "accept")


def react_device_ms(ctx):
    """Device ms per cycle of cycle.react: test() and Send on the
    touched peers, with the threshold kernel."""
    return phase_ms(ctx, "react")


def stage_device_ms(ctx):
    """Device ms per cycle of wheel maintenance, staging, the probe,
    the boundary exchange and appends, and the accounting."""
    return phase_ms(ctx, "stage")


def output_gap_ms(ctx):
    """Device-idle ms per pump inside `outputs()`: the knowledge-output
    dispatch and the blocking readback."""
    return gap_ms(ctx, "output")


def publish_gap_ms(ctx):
    """Device-idle ms per pump inside the publish (diff, delivery) and
    the pump's convergence accounting."""
    return gap_ms(ctx, "publish")


def flush_gap_ms(ctx):
    """Device-idle ms per pump inside the ingest and the flush's
    scatter and react dispatches."""
    return gap_ms(ctx, "flush")
