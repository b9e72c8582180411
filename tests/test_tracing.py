"""The serve path's own trace record: host spans inside `pump()` (read
back from a profiler trace), named cycle phases mapped onto the
compiled superstep (`JaxEngine.op_phases`), and the server's `trace`
list (settle records only, their wall time read after the readback).

The profiler trace is read through `ProfileData` by event names, starts
and durations only (iterating an event's `.stats` is deprecated).
"""
from __future__ import annotations

import glob
import os
import re

import numpy as np
import pytest

import jax

from repro.core.dht import Ring
from repro.engine import make_engine
from repro.engine.base import FaultConfig
from repro.launch.serve import ThresholdServer
from repro.runtime import tracing

PUMPS = 3


def _majority(n, seed, backend="jax", **kw):
    rng = np.random.default_rng(seed)
    ring = Ring.random(n, 32, seed=seed)
    votes = (rng.random(n) < 0.4).astype(np.int64)
    return ring, make_engine(backend, ring, votes, seed=seed + 1, **kw)


@pytest.fixture(scope="module")
def host_spans(tmp_path_factory):
    """(name, start_ns, end_ns) of every program span in a profiler
    trace of 3 pumps of a 256-peer jax engine, each pump flushing one
    changed vote."""
    from jax.profiler import ProfileData

    ring, eng = _majority(256, seed=11, capacity_per_peer=8)
    server = ThresholdServer(eng, window=4)
    votes = np.asarray(eng.votes())

    def pump(i):
        server.submit(int(ring.addrs[i]), 1 - int(votes[i]))
        server.pump()

    pump(0)  # build every program outside the trace
    out = str(tmp_path_factory.mktemp("profile"))
    jax.profiler.start_trace(out)
    try:
        for i in range(1, PUMPS + 1):
            pump(i)
    finally:
        jax.profiler.stop_trace()
    path, = glob.glob(os.path.join(out, "**", "*.xplane.pb"), recursive=True)
    spans = []
    for plane in ProfileData.from_file(path).planes:
        if not plane.name.startswith("/host:"):
            continue
        for line in plane.lines:
            for e in line.events:
                if e.name in tracing.SPANS:
                    spans.append((e.name, e.start_ns,
                                  e.start_ns + e.duration_ns))
    return spans


@pytest.mark.parametrize("name", tracing.SPANS)
def test_every_span_once_per_pump_inside_it(host_spans, name):
    pumps = [(s, e) for n, s, e in host_spans if n == "serve.pump"]
    mine = [(s, e) for n, s, e in host_spans if n == name]
    assert len(pumps) == PUMPS and len(mine) == PUMPS
    for s, e in mine:
        assert sum(ps <= s and e <= pe for ps, pe in pumps) == 1


def test_spans_follow_the_pump_in_order(host_spans):
    """Within each pump the stages run in the order of the serve path,
    and none overlaps the next."""
    order = ("serve.ingest", "engine.scatter", "engine.react",
             "engine.dispatch", "engine.knowledge", "engine.readback",
             "serve.diff", "serve.deliver", "serve.account")
    pumps = sorted((s, e) for n, s, e in host_spans if n == "serve.pump")
    for ps, pe in pumps:
        inner = sorted((s, e, n) for n, s, e in host_spans
                       if n != "serve.pump" and ps <= s and e <= pe)
        assert tuple(n for _, _, n in inner) == order
        assert all(a[1] <= b[0] for a, b in zip(inner, inner[1:]))


def test_names_are_distinct_and_documented():
    assert len(set(tracing.SPANS)) == len(tracing.SPANS) == 10
    assert len(set(tracing.PHASES)) == len(tracing.PHASES) == 9
    assert all(name in tracing.__doc__ for name in tracing.SPANS)
    assert all(name.startswith("cycle.") for name in tracing.PHASES)


@pytest.fixture(scope="module")
def armed_engine():
    """A small jax engine with the fault plane armed, so the probe
    section is in the cycle program; one step built its program."""
    _, eng = _majority(128, seed=5, capacity_per_peer=8,
                       faults=FaultConfig(suspect_after=6))
    eng.step(2)
    return eng


@pytest.fixture(scope="module")
def phase_map(armed_engine):
    from jax import monitoring

    built = []

    def on_duration(event, secs, **_):
        if event == "/jax/core/compile/backend_compile_duration":
            built.append(secs)

    monitoring.register_event_duration_secs_listener(on_duration)
    try:
        phases = armed_engine.op_phases()
    finally:
        monitoring.unregister_event_duration_listener(on_duration)
    return phases, built


def test_op_phases_builds_no_program(phase_map):
    _, built = phase_map
    assert built == []


def test_op_phases_names_every_scoped_instruction(armed_engine, phase_map):
    phases, _ = phase_map
    text = armed_engine._steps.lower(
        armed_engine._st, jax.numpy.asarray(1, jax.numpy.int32)
    ).compile().as_text()
    scoped = {}
    for line in text.splitlines():
        m = re.match(r"\s*(?:ROOT )?%?([\w.\-]+) = ", line)
        hit = re.search(r'op_name="[^"]*/(cycle\.\w+)', line)
        if m and hit:
            scoped[m.group(1)] = hit.group(1)
    assert scoped
    assert {k: phases.get(k) for k in scoped} == scoped
    assert set(phases.values()) <= set(tracing.PHASES)


@pytest.mark.parametrize("phase", tracing.PHASES)
def test_every_cycle_phase_has_instructions(phase_map, phase):
    phases, _ = phase_map
    assert phase in set(phases.values())


def test_op_phases_parses_compiled_text():
    """Scoped instructions keep their phase; one without metadata takes
    its operand's phase, else its user's; one linked to no scoped
    instruction has none."""
    text = "\n".join([
        '  %p = f32[8]{0} parameter(0)',
        '  %buf = f32[8]{0} custom-call(), custom_call_target="AllocateBuffer"',
        '  %fusion.7 = f32[8]{0} fusion(%p, %buf), kind=kLoop, calls=%f.7, '
        'metadata={op_name="jit(_steps_impl)/while/body/cycle.accept/add"}',
        '  ROOT %copy.2 = f32[8]{0} copy(%fusion.7), metadata={op_name='
        '"jit(_steps_impl)/shard_map/while/body/cycle.stage"}',
        '  %copy.3 = f32[8]{0} copy(%copy.2)',
        '  %add.1 = f32[8]{0} add(%a, %b), metadata={op_name="jit(f)/add"}',
    ])
    assert tracing.op_phases(text) == {
        "fusion.7": "cycle.accept", "copy.2": "cycle.stage",
        "copy.3": "cycle.stage", "p": "cycle.accept", "buf": "cycle.accept"}


def _server_with_clock(readback_s, publish_s):
    """A numpy-engine server whose clock moves only inside `outputs()`
    (by `readback_s`) and the publish (by `publish_s`)."""
    ring, eng = _majority(16, seed=5, backend="numpy")
    now = [0.0]
    server = ThresholdServer(eng, window=4, clock=lambda: now[0])

    def timed(fn, secs):
        def call(*a, **k):
            out = fn(*a, **k)
            now[0] += secs
            return out
        return call

    eng.outputs = timed(eng.outputs, readback_s)
    server.notifier.publish = timed(server.notifier.publish, publish_s)
    return ring, eng, server


def test_settle_wall_ms_is_read_after_the_readback_and_publish():
    ring, eng, server = _server_with_clock(readback_s=10.0, publish_s=1.0)
    while not server.settled:
        server.pump()
    server.trace.clear()
    server.submit(int(ring.addrs[0]), 1 - int(np.asarray(eng.votes())[0]))
    pumps = 0
    while True:
        server.pump()
        pumps += 1
        if server.settled:
            break
    settle, = server.trace
    # the epoch opens before the disturbing pump's readback and closes
    # after the settling pump's: every pump of it counts in full
    assert settle["wall_ms"] == pytest.approx(pumps * 11.0 * 1e3)
    assert settle["cycles"] == pumps * 4


def test_settled_pumps_leave_the_trace_empty():
    ring, eng, server = _server_with_clock(readback_s=0.0, publish_s=0.0)
    while not server.settled or eng.in_flight:  # quiescent
        server.pump()
    server.trace.clear()
    before = server.stats()
    for _ in range(50):
        server.pump()
        assert server.settled
    assert server.trace == []
    assert server.stats()["flushes"] - before["flushes"] == 50
