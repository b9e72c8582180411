"""Profiler trace of the first part of the window, and its reduction.

In a traced run the trace covers the window's first `trace_pumps`
pumps or `trace_seconds` seconds, whichever ends first, inside one host
span `bench.traced`, whose length is `window_s`; the rest of the window
runs untraced. On each TPU device plane the
reduction reads the "XLA Ops" line (one event per operation run on the
chip, named by its HLO text) and the "XLA Modules" line (one event per
program run, `jit_<name>(<fingerprint>)`); an op belongs to the program
run whose interval holds its start. On the host planes it reads the
harness's spans (harness/drive.py). Busy time is the union of the op
intervals inside the traced span, averaged over the chips used.
"""
from __future__ import annotations

import collections
import glob
import gzip
import re
import os
import shutil
from typing import Dict, List, NamedTuple, Tuple

import numpy as np

from .spec import BENCH

TRACE_DIR = os.path.join(BENCH, ".traces", "last")
TRACED = "bench.traced"


class Event(NamedTuple):
    name: str      # an op's HLO text, a program's or a span's name
    module: str    # the program an op ran in ("" for programs, spans)
    start: float   # ns on the trace's clock
    dur: float     # ns
    self_ns: float = 0.0   # an op's time not covered by ops nested in it


# The four wheel kernels are Pallas calls (`custom_call_target=
# "tpu_custom_call"`) that carry no name of their own today: every one
# is built from a function called `kern`, and the trace names the op
# after its place in the program (`%body.37`). They are told apart by
# how many arrays each returns.
PALLAS = 'custom_call_target="tpu_custom_call"'
KERNEL_BY_OUTPUTS = {9: "dedup", 5: "descent", 3: "threshold",
                     1: "enqueue"}
_ARRAY = re.compile(r"[a-z0-9]+\[[0-9,]*\]\{")


def pallas_kernel(op_text: str) -> str:
    """The wheel kernel an op is ("" if it is no Pallas call)."""
    if PALLAS not in op_text:
        return ""
    out = op_text.split(" = ", 1)[1].split(" custom-call(", 1)[0]
    return KERNEL_BY_OUTPUTS.get(len(_ARRAY.findall(out)), "pallas")


def program_name(module: str) -> str:
    """`jit__steps_impl(9275285723466863581)` -> `jit__steps_impl`."""
    return module.split("(", 1)[0]


class Tracer:
    """Starts and stops the profiler around the first part of a window."""

    def __init__(self, traffic: Dict, out_dir: str = TRACE_DIR):
        self.pumps = int(traffic.get("trace_pumps", 1 << 30))
        self.seconds = float(traffic.get("trace_seconds", 1e9))
        self.out_dir = out_dir
        self.cycles = 0
        self.pump_count = 0

    def start(self, engine) -> None:
        import jax

        shutil.rmtree(self.out_dir, ignore_errors=True)
        opts = jax.profiler.ProfileOptions()
        opts.python_tracer_level = 0
        opts.host_tracer_level = 2
        jax.profiler.start_trace(self.out_dir, profiler_options=opts)
        self._span = jax.profiler.TraceAnnotation(TRACED)
        self._span.__enter__()
        self._engine = engine
        self._t0 = int(engine.t)

    def done(self, elapsed: float, pumps: int) -> bool:
        self.pump_count = pumps
        return pumps >= self.pumps or elapsed >= self.seconds

    def stop(self) -> None:
        import jax

        self.cycles = int(self._engine.t) - self._t0
        self._span.__exit__(None, None, None)
        jax.profiler.stop_trace()

    def reduce(self) -> "Reduced":
        files = glob.glob(os.path.join(self.out_dir, "**", "*.xplane.pb"),
                          recursive=True)
        if not files:
            return None
        return Reduced.from_file(files[0], self.cycles, self.pump_count)


def union_ns(intervals: List[Tuple[float, float]], lo: float,
             hi: float) -> float:
    """Length of the union of [start, end) intervals, clipped to [lo, hi)."""
    total, cur_s, cur_e = 0.0, None, None
    for s, e in sorted(intervals):
        s, e = max(s, lo), min(e, hi)
        if e <= s:
            continue
        if cur_e is None or s > cur_e:
            if cur_e is not None:
                total += cur_e - cur_s
            cur_s, cur_e = s, e
        else:
            cur_e = max(cur_e, e)
    if cur_e is not None:
        total += cur_e - cur_s
    return total


class Reduced:
    """Device ops, device programs and host spans of one traced window."""

    def __init__(self, ops: Dict[str, List[Event]],
                 modules: Dict[str, List[Event]], spans: List[Event],
                 cycles: int, pumps: int):
        self.ops = ops            # device plane -> op events
        self.modules = modules    # device plane -> program events
        self.spans = spans        # host spans
        self.cycles = cycles
        self.pumps = pumps
        traced = [s for s in spans if s.name == TRACED]
        if traced:
            self.lo = traced[0].start
            self.hi = traced[0].start + traced[0].dur
        else:
            every = [e for evs in ops.values() for e in evs]
            self.lo = min(e.start for e in every)
            self.hi = max(e.start + e.dur for e in every)
        self.window_s = (self.hi - self.lo) / 1e9
        busy = [union_ns([(e.start, e.start + e.dur) for e in evs],
                         self.lo, self.hi) for evs in ops.values()]
        self.busy_s = float(np.mean(busy)) / 1e9 if busy else 0.0

    @classmethod
    def from_file(cls, path: str, cycles: int, pumps: int) -> "Reduced":
        """Reduce an `.xplane.pb` file, or its gzip (`.gz`)."""
        from jax.profiler import ProfileData

        if path.endswith(".gz"):
            with gzip.open(path, "rb") as fh:
                pd = ProfileData.from_serialized_xspace(fh.read())
        else:
            pd = ProfileData.from_file(path)
        ops, modules, spans = {}, {}, []
        for plane in pd.planes:
            if plane.name.startswith("/device:TPU:") and \
                    "SparseCore" not in plane.name:
                raw_ops, raw_mods = [], []
                for line in plane.lines:
                    if line.name == "XLA Ops":
                        raw_ops = [(e.name, e.start_ns, e.duration_ns)
                                   for e in line.events]
                    elif line.name == "XLA Modules":
                        raw_mods = [Event(program_name(e.name), "",
                                          e.start_ns, e.duration_ns)
                                    for e in line.events]
                modules[plane.name] = raw_mods
                ops[plane.name] = attribute(raw_ops, raw_mods)
            elif plane.name.startswith("/host:"):
                for line in plane.lines:
                    for e in line.events:
                        if e.name == TRACED or e.name.startswith(
                                ("serve.", "engine.", "bench.")):
                            spans.append(Event(e.name, "", e.start_ns,
                                               e.duration_ns))
        return cls(ops, modules, spans, cycles, pumps)

    # -- readings --------------------------------------------------------------

    def _inside(self, e: Event) -> bool:
        return e.start < self.hi and e.start + e.dur > self.lo

    def _clipped(self, e: Event) -> float:
        """The part of an event's duration inside the traced window."""
        return min(e.start + e.dur, self.hi) - max(e.start, self.lo)

    def op_ns(self, match) -> float:
        """Device ns, averaged over chips, of ops whose (name, module)
        satisfy `match`, inside the traced window."""
        per = [sum(self._clipped(e) for e in evs
                   if self._inside(e) and match(e))
               for evs in self.ops.values()]
        return float(np.mean(per)) if per else 0.0

    def op_count(self, match) -> float:
        per = [sum(1 for e in evs if self._inside(e) and match(e))
               for evs in self.ops.values()]
        return float(np.mean(per)) if per else 0.0

    def kernel(self, kind: str, program: str = "jit__steps_impl"):
        """(device ns, calls) of one wheel kernel inside `program`,
        averaged over chips."""
        match = (lambda e: e.module == program
                 and pallas_kernel(e.name) == kind)
        return self.op_ns(match), self.op_count(match)

    def module_ns(self, match) -> float:
        """Device ns, averaged over chips, of program runs whose name
        satisfies `match`, inside the traced window."""
        per = [sum(self._clipped(e) for e in evs
                   if self._inside(e) and match(e.name))
               for evs in self.modules.values()]
        return float(np.mean(per)) if per else 0.0

    def breakdown(self, top: int = 10) -> Dict:
        """The device ops that took most time, and the longest idle gaps
        of the first chip, each by the host span it fell in."""
        plane = sorted(self.ops)[0] if self.ops else None
        evs = [e for e in self.ops.get(plane, []) if self._inside(e)]
        by_op = collections.Counter()
        for e in evs:
            by_op[short_name(e)] += e.self_ns / 1e9
        gaps = []
        end = self.lo
        for e in sorted(evs, key=lambda e: e.start):
            if e.start > end:
                gaps.append((e.start - end, end))
            end = max(end, e.start + e.dur)
        if self.hi > end:
            gaps.append((self.hi - end, end))
        gaps.sort(reverse=True)
        named = collections.Counter()
        for g, at in gaps:
            named[self.host_span_at(at + g / 2)] += g / 1e9
        return {"device_ops": [[k, v] for k, v in by_op.most_common(top)],
                "idle_gaps": [[k, v] for k, v in named.most_common(top)]}

    def host_span_at(self, t: float) -> str:
        """The innermost harness span open at trace time `t`."""
        best, width = "host (no span)", None
        for s in self.spans:
            if s.name == TRACED:
                continue
            if s.start <= t < s.start + s.dur and (width is None
                                                    or s.dur < width):
                best, width = s.name, s.dur
        return best


def short_name(e: Event) -> str:
    """`program/op` for the breakdown, with the wheel kernel named."""
    op = e.name.split(" ", 1)[0]
    k = pallas_kernel(e.name)
    return f"{e.module or '?'}/{op}" + (f" [pallas {k}]" if k else "")


def attribute(raw_ops, modules: List[Event]) -> List[Event]:
    """Op events with the program each ran in (the program run whose
    interval holds the op's start) and their self time (nested ops,
    such as a while loop's body, are taken out of the op holding
    them)."""
    raw_ops = sorted(raw_ops, key=lambda o: (o[1], -o[2]))
    mods = sorted(modules, key=lambda m: m.start)
    starts = [m.start for m in mods]
    out: List[Event] = []
    selfs: List[float] = []
    stack: List[int] = []   # indices of ops still open
    for name, start, dur in raw_ops:
        while stack and out[stack[-1]].start + out[stack[-1]].dur <= start:
            stack.pop()
        if stack:
            selfs[stack[-1]] -= dur
        k = int(np.searchsorted(starts, start, side="right")) - 1
        mod = mods[k].name if k >= 0 and start < mods[k].start + \
            mods[k].dur else ""
        out.append(Event(name, mod, start, dur))
        selfs.append(dur)
        stack.append(len(out) - 1)
    return [e._replace(self_ns=max(sn, 0.0)) for e, sn in zip(out, selfs)]
