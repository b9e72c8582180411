"""The served window: set-up, warm-up, the measured window, the drain.

The window drives `ThresholdServer.submit`, `join`, `leave_addr` and
`pump()` over `make_engine("jax", ...)` exactly as users call them:
each entry of the schedule through its own entry point, in due order.
Host spans come from the harness's own files: `jax.profiler.
TraceAnnotation` wrappers put on the instance around the engine's
`apply_coalesced`, `step` and `outputs` and the notifier's `publish`,
and `bench.membership` around each join and leave; no program file is
touched.
Each pump is timed on the harness's clock after `pump()` returns, and
`pump()` returns only after `outputs()` has read the device back.
"""
from __future__ import annotations

import collections
import os
import sys
import time
from typing import Callable, Dict, List, Optional

import jax
import numpy as np

from . import traffic as T
from .spec import ROOT, Cell

SPANS = {  # span name -> (attribute path on the server, method)
    "serve.flush": ("engine", "apply_coalesced"),
    "engine.step": ("engine", "step"),
    "engine.outputs": ("engine", "outputs"),
    "serve.publish": ("notifier", "publish"),
}


def _program():
    src = os.path.join(ROOT, "src")
    if src not in sys.path:
        sys.path.insert(0, src)
    from repro.core.dht import Ring
    from repro.engine import make_engine
    from repro.engine.problems import get_problem
    from repro.launch.serve import ThresholdServer

    return Ring, make_engine, get_problem, ThresholdServer


class HostSpans:
    """Wraps the layer entry points on one server instance: each call is
    a `TraceAnnotation` span in the profiler's trace and adds its host
    seconds to `secs[name]`."""

    def __init__(self, server, clock: Callable[[], float]):
        self.secs = collections.Counter()
        self.calls = collections.Counter()
        for name, (holder, meth) in SPANS.items():
            obj = getattr(server, holder)
            setattr(obj, meth, self._wrap(name, getattr(obj, meth), clock,
                                          jax.profiler.TraceAnnotation))

    def _wrap(self, name, fn, clock, annotation):
        def wrapped(*args, **kwargs):
            t = clock()
            with annotation(name):
                out = fn(*args, **kwargs)
            self.secs[name] += clock() - t
            self.calls[name] += 1
            return out
        return wrapped

    def reset(self) -> None:
        self.secs.clear()
        self.calls.clear()


class Pumps:
    """Every pump in order: start, end, settled, engine cycle after it,
    the flush it applied (submitted addresses and values, in submit
    order), the membership events called since the pump before it (in
    call order: ("join", addr, value) or ("leave", addr, None)), and how
    many transitions existed after it."""

    def __init__(self):
        self.start: List[float] = []
        self.end: List[float] = []
        self.settled: List[bool] = []
        self.t: List[int] = []
        self.flush: List = []
        self.events: List[List] = []
        self.transitions_upto: List[int] = []
        self.ok: List[bool] = []


def build(cell: Cell, seed: int):
    """Ring, data and engine from the seed; returns the pieces."""
    Ring, make_engine, get_problem, ThresholdServer = _program()
    cfg = cell.config
    # a configuration may fix its deployment (ring, data, hot peers,
    # update values) so that every seed serves the same work, in
    # another order (bench/harness/traffic.py)
    st = T.streams(cfg, seed)
    ring = Ring.random(cfg["n"], cfg["d"], seed=st["ring_seed"])
    values0, params = st["values0"], st["params"]
    # `override` (bench/control.py) switches the program off what the
    # configuration states; the reference never sees it
    over = cfg.get("override", {})
    prob = dict(cfg["problem"], **over.get("problem", {}))
    problem = get_problem(prob.pop("name"), **prob)
    engine = make_engine("jax", ring, values0, seed=st["engine_seed"],
                         problem=problem,
                         **dict(cfg["engine"], **over.get("engine", {})))
    server = ThresholdServer(engine, window=cfg["window"])
    addrs = np.asarray(ring.addrs, np.uint64)
    churn = T.Churn(addrs, cfg["d"], seed, st["deployment_seed"]) \
        if "membership" in cell.traffic else None
    return {"server": server, "engine": engine, "values0": values0,
            "params": params, "addrs": addrs, "churn": churn,
            "traffic_rng": st["traffic_rng"],
            "deployment_rng": st["deployment_rng"]}


class Window:
    """One run of a cell: everything the metrics and the check read."""

    def __init__(self, cell: Cell, seed: int, seconds: float,
                 tracer=None, clock: Callable[[], float] = time.perf_counter):
        self.cell, self.seed, self.seconds = cell, seed, float(seconds)
        self.tracer = tracer
        self.clock = clock
        self._annotation = jax.profiler.TraceAnnotation
        self.pumps = Pumps()
        self.transitions: List = []
        self._events: List = []        # called since the last pump
        self.marks: Dict[str, Dict] = {}

    # -- phases ---------------------------------------------------------------

    def setup(self, counter) -> None:
        tr = self.cell.traffic
        b = build(self.cell, self.seed)
        self.__dict__.update(b)
        self.sched = T.updates_only(np.zeros(0), np.zeros(0, np.int64),
                                    np.zeros(0))
        self._next = self._flushed = 0
        self.server.subscribe(self.transitions.append)
        self.spans = HostSpans(self.server, self.clock)
        # set-up phases after the chip is found: ring, data, engine and
        # its programs; the settle; the warm-up pumps
        self.setup_phases = {"build": self.clock()}
        if tr.get("settle"):
            cap = self.clock() + float(tr["settle_cap_s"])
            self._pump_until_settled(cap, "set-up settle")
        self.setup_phases["settle"] = self.clock()
        for _ in range(int(tr.get("warmup_pumps", 0))):
            self._pump_and_log()
        self._warm_flush_sizes(int(tr.get("warm_batch_max", 0)))
        if self.churn is not None:
            self._warm_membership(float(tr.get("settle_cap_s", 0)))
        self.engine.block_until_ready()
        self.setup_phases["warm-up"] = self.clock()
        self.marks["setup"] = counter.mark()

    def window(self, counter) -> None:
        tr = self.cell.traffic
        n = self.addrs.size
        tracing = self.tracer is not None
        self.sched = T.schedule(tr, self.cell.config["data"], self.params,
                                self.seconds, n, self.traffic_rng,
                                self.deployment_rng, self.churn)
        N = self.sched.due.size
        self.submit_t = np.full(N, np.nan)
        self._next = self._flushed = 0
        self.first_window_pump = len(self.pumps.start)
        self.t0 = int(self.engine.t)
        self.spans.reset()
        if tracing:
            self.tracer.start(self.engine)
        w0 = self.clock()
        self.w0 = w0
        due_abs = w0 + self.sched.due
        while True:
            now = self.clock()
            hi = int(np.searchsorted(due_abs, now, side="right"))
            self._submit(hi)
            self._pump_and_log()
            end = self.pumps.end[-1]
            if tracing and self.tracer.done(end - w0,
                                            len(self.pumps.end)
                                            - self.first_window_pump):
                # the trace covers the window's first part; the run goes
                # on untraced, so its work and its drain are the same
                self.tracer.stop()
                tracing = False
            if end - w0 >= self.seconds:
                break
        if tracing:
            self.tracer.stop()
        self.w1 = self.pumps.end[-1]
        self.last_window_pump = len(self.pumps.start)
        self.window_cycles = int(self.engine.t) - self.t0
        self.window_spans = dict(self.spans.secs)
        self.window_calls = dict(self.spans.calls)
        self.marks["window"] = counter.mark()

    def drain(self) -> None:
        """Arrivals stop; submit what was due in the window and pump until
        the server is settled, up to the mix's drain cap. Not timed: the
        drain brings every answer the window left open (the decision of
        each update, or the end of a cold start's storm) to the check."""
        self._submit(self.sched.due.size)
        first, t0, c0 = len(self.pumps.end), int(self.engine.t), self.clock()
        cap = float(self.cell.traffic.get("drain_cap_s", 0))
        if cap > 0 and not (self.server.settled and
                            self._flushed == self._next):
            self._pump_until_settled(self.clock() + cap, None)
        self.drain_end = self.pumps.end[-1]
        self.drain_stats = {"pumps": len(self.pumps.end) - first,
                            "cycles": int(self.engine.t) - t0,
                            "seconds": self.clock() - c0}

    # -- pieces ---------------------------------------------------------------

    def _submit(self, hi: int) -> None:
        """Send the arrivals [next, hi) of the schedule, in order, each
        to its own entry point; record when each call returned."""
        s = self.sched
        if hi <= self._next:
            return
        with self._annotation("bench.submit"):
            for i in range(self._next, hi):
                if s.kind[i]:
                    self._member(int(s.kind[i]), int(s.addr[i]),
                                 s.values[i].item())
                else:
                    self.server.submit(int(self.addrs[s.peer[i]]),
                                       s.values[i].item())
                self.submit_t[i] = self.clock()
        self._next = hi

    def _member(self, kind: int, addr: int, value) -> None:
        """One join or leave through the server, in a span of its own,
        recorded for the next pump."""
        with self._annotation("bench.membership"):
            if kind == T.JOIN:
                self.server.join(addr, value)
            else:
                self.server.leave_addr(addr)
        self._events.append(("join", addr, value) if kind == T.JOIN
                            else ("leave", addr, None))

    def _warm_membership(self, cap: float) -> None:
        """One join at a fresh address, then its leave, each followed by
        a pump, through the server and recorded: every program a join or
        a leave dispatches is built (or loaded) before the window. Then
        pumps until the server is settled again, up to `cap` seconds."""
        addr = int(self.churn.fresh(1)[0])
        value = T.draw_data(self.cell.config["data"],
                            self.churn.deployment, 1, self.params)[0]
        self._member(T.JOIN, addr, value.item())
        self._pump_and_log()
        self._member(T.LEAVE, addr, None)
        self._pump_and_log()
        if not self.server.settled:
            self._pump_until_settled(self.clock() + cap, None)

    def _warm_flush_sizes(self, k_max: int) -> None:
        """One pump for every flush size 1..`k_max`, through the server:
        each submits the current value of k peers again, so the data and
        the decision stay as they are while every program a flush of k
        peers dispatches is built (or loaded) before the window."""
        for k in range(1, k_max + 1):
            peers = np.arange(k, dtype=np.int64)
            vals = self.values0[peers]
            for p, v in zip(peers, vals):
                self.server.submit(int(self.addrs[p]), v.item())
            self._pump(self.addrs[peers], vals)

    def _pump_and_log(self) -> None:
        """One pump; its flush is every update submitted since the last."""
        lo, hi = self._flushed, self._next
        s = self.sched
        up = s.kind[lo:hi] == T.UPDATE
        self._pump(self.addrs[s.peer[lo:hi][up]], s.values[lo:hi][up])
        self._flushed = hi

    def _pump(self, addrs, values) -> None:
        p = self.pumps
        ps = self.clock()
        with self._annotation("bench.pump"):
            self.server.pump()
        pe = self.clock()
        p.start.append(ps)
        p.end.append(pe)
        p.settled.append(bool(self.server.settled))
        p.t.append(int(self.engine.t))
        p.flush.append((np.asarray(addrs, np.uint64), np.asarray(values)))
        p.events.append(self._events)
        self._events = []
        p.transitions_upto.append(len(self.transitions))
        if self.cell.traffic.get("operation") == "window":
            p.ok.append(self._window_ok())

    def _window_ok(self) -> bool:
        """A served pump of a mix without updates: no row dropped, rows
        conserved."""
        try:
            cons = self.engine.check_conservation()
        except AssertionError:
            return False
        return cons["dropped"] == 0

    def _pump_until_settled(self, cap: float, what: Optional[str]) -> None:
        while True:
            self._pump_and_log()
            if self.server.settled or self.clock() > cap:
                break
        if what and not self.server.settled:
            raise SystemExit(f"{what}: not settled within the cap "
                             f"(t={self.engine.t})")

    # -- readback after the run --------------------------------------------------

    def final_state(self) -> Dict:
        """The end readback, through the engine's public entry points."""
        eng = self.engine
        try:
            eng.check_conservation()
            conserved = True
        except AssertionError:
            conserved = False
        return {
            "addrs": np.asarray(eng.ring.addrs, np.uint64),
            "data": eng.data(), "outputs": np.asarray(eng.outputs()),
            "conserved": conserved, "dropped": int(eng.dropped),
            "t": int(eng.t), "settled": bool(self.server.settled),
            "cycles_asked": len(self.pumps.t) * self.server.window,
        }
