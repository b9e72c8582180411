"""The phase and gap readings (harness/phases.py): interval arithmetic
and grouping on hand-built traces, then on a small trace recorded on one
TPU v5 lite chip with the program's own spans and its superstep's
op_phases map (bench/testdata/)."""
import json
import os

import pytest

from harness import phases as PH
from harness import spec
from harness import trace as T
from harness.context import Context

DATA = os.path.join(spec.BENCH, "testdata")
CHIP = "/device:TPU:0"
PHASE_METRICS = ("deliver_device_ms", "accept_device_ms", "react_device_ms",
                 "stage_device_ms")
GAP_METRICS = ("output_gap_ms", "publish_gap_ms")


def test_merged_clips_and_joins():
    iv = [(5, 15), (0, 10), (20, 30), (30, 31), (40, 50)]
    assert PH.merged(iv, 0, 100) == [(0, 15), (20, 31), (40, 50)]
    assert PH.merged(iv, 8, 25) == [(8, 15), (20, 25)]
    assert PH.merged([], 0, 10) == []


def test_overlap_of_interval_lists():
    a = [(0, 10), (20, 30)]
    b = [(5, 25), (28, 40)]
    assert PH.overlap_ns(a, b) == 5 + 5 + 2
    assert PH.overlap_ns(a, []) == 0
    assert PH.overlap_ns(a, a) == 20


def _span(name, s, e):
    return T.Event(name, "", s, e - s)


class _Engine:
    def __init__(self, phases=None):
        if phases is not None:
            self.op_phases = lambda: phases


class _Win:
    def __init__(self, engine):
        self.engine = engine


def _ctx(tr, phases=None):
    return Context(_Win(_Engine(phases)), {}, 0.0, trace=tr)


def _hand_trace():
    """Two pumps over [0, 200): ops busy [0, 40) and [100, 140), one
    superstep run each; the output and publish spans of each pump lie in
    its idle part."""
    mods = [T.Event("jit__steps_impl", "", 0, 40),
            T.Event("jit__steps_impl", "", 100, 40)]
    raw = [("%fusion.1 = f", 0, 10), ("%wheel_dedup.2 = k", 10, 15),
           ("%copy.3 = c", 25, 5), ("%fusion.9 = g", 30, 10),
           ("%fusion.1 = f", 100, 10), ("%wheel_dedup.2 = k", 110, 15),
           ("%copy.3 = c", 125, 5), ("%fusion.9 = g", 130, 10)]
    ops = {CHIP: T.attribute(raw, mods)}
    spans = [_span("bench.traced", 0, 200)]
    for p in (0, 100):
        spans += [_span("serve.pump", p, p + 95),
                  _span("engine.knowledge", p + 40, p + 45),
                  _span("engine.readback", p + 45, p + 60),
                  _span("serve.diff", p + 60, p + 70),
                  _span("serve.deliver", p + 70, p + 72),
                  _span("serve.account", p + 72, p + 80)]
    return T.Reduced(ops, {CHIP: mods}, spans, cycles=4, pumps=2)


PHASE_MAP = {"fusion.1": "cycle.scan", "wheel_dedup.2": "cycle.accept",
             "copy.3": "cycle.stage", "fusion.9": "cycle.other"}


def test_phase_groups_take_self_time_per_cycle():
    ctx = _ctx(_hand_trace(), PHASE_MAP)
    # 2 runs of each op over 4 cycles
    assert PH.deliver_device_ms(ctx) == pytest.approx(2 * 10 / 4 / 1e6)
    assert PH.accept_device_ms(ctx) == pytest.approx(2 * 15 / 4 / 1e6)
    assert PH.stage_device_ms(ctx) == pytest.approx(2 * 5 / 4 / 1e6)
    assert PH.react_device_ms(ctx) is None   # no op in the phase
    # the op of a phase in no group (fusion.9) is in no metric
    by = PH.steps_self_ns(ctx.trace, PHASE_MAP)
    assert by["cycle.other"] == 20 and sum(by.values()) == 80


def test_gaps_are_idle_time_inside_the_named_spans():
    ctx = _ctx(_hand_trace(), PHASE_MAP)
    # outputs: [40, 60) idle in each pump (ops end at 40 and 140)
    assert PH.output_gap_ms(ctx) == pytest.approx(20 / 1e6)
    # publish: [60, 80) idle in each pump
    assert PH.publish_gap_ms(ctx) == pytest.approx(20 / 1e6)
    assert PH.flush_gap_ms(ctx) is None     # no flush span in the trace


def test_a_program_without_spans_or_map_reads_nothing():
    tr = _hand_trace()
    tr.spans = [s for s in tr.spans if s.name == "bench.traced"]
    ctx = _ctx(tr, phases=None)
    for name in PHASE_METRICS + GAP_METRICS + ("flush_gap_ms",):
        assert getattr(PH, name)(ctx) is None
    assert PH.deliver_device_ms(_ctx(tr, phases={})) is None
    assert PH.output_gap_ms(_ctx(None)) is None


def test_metric_files_read_the_phase_module():
    for name in PHASE_METRICS + GAP_METRICS:
        for fam in ("storm", "served"):
            reader = spec.metric_reader(f"{name}.{fam}")
            assert reader.read is getattr(PH, name)
    assert spec.metric_reader("flush_gap_ms").read is PH.flush_gap_ms


# Recorded on the chip (TPU v5 lite) by bench/run.py's run of the
# steady cell cut to 4,096 peers (pad 8192), 50 updates/s: 3 traced
# pumps, 24 cycles; with it the superstep's op_phases() map.
RECORDED = os.path.join(DATA, "tiny-mean-3pumps-spans.xplane.pb.gz")
RECORDED_PHASES = os.path.join(DATA, "tiny-mean-3pumps-spans.op_phases.json")
ON_CHIP = {"deliver_device_ms.served": 0.221429875,
           "accept_device_ms.served": 0.17745583333333334,
           "react_device_ms.served": 0.210865625,
           "stage_device_ms.served": 0.9438795,
           "output_gap_ms.served": 13.938978333333335,
           "publish_gap_ms.served": 0.04945666666666666,
           "flush_gap_ms": 4.829142666666667}
WRAPPER = {  # program span -> the harness span (harness/drive.py) around it
    "serve.pump": "bench.pump", "serve.ingest": "bench.pump",
    "engine.scatter": "serve.flush", "engine.react": "serve.flush",
    "engine.dispatch": "engine.step", "engine.knowledge": "engine.outputs",
    "engine.readback": "engine.outputs", "serve.diff": "serve.publish",
    "serve.deliver": "serve.publish", "serve.account": "bench.pump"}


@pytest.fixture(scope="module")
def recorded():
    with open(RECORDED_PHASES) as fh:
        phases = json.load(fh)
    return _ctx(T.Reduced.from_file(RECORDED, cycles=24, pumps=3), phases)


def test_recorded_phases_sum_to_the_module_time(recorded):
    total = sum(getattr(PH, m)(recorded) for m in PHASE_METRICS)
    cycle = spec.metric_reader("cycle_device_ms.served").read(recorded)
    assert total == pytest.approx(cycle, rel=0.03)
    by = PH.steps_self_ns(recorded.trace, recorded.engine.op_phases())
    assert by.get("", 0.0) <= 0.02 * sum(by.values())


@pytest.mark.parametrize("name", sorted(WRAPPER))
def test_recorded_program_span_inside_its_wrapper(recorded, name):
    """The program's spans and the harness's share the trace's clock."""
    spans = recorded.trace.spans
    mine = [s for s in spans if s.name == name]
    outer = [s for s in spans if s.name == WRAPPER[name]]
    assert mine
    for s in mine:
        assert any(o.start <= s.start and s.start + s.dur <= o.start + o.dur
                   for o in outer), s


def test_recorded_gaps_are_at_most_the_idle_time(recorded):
    tr = recorded.trace
    idle_ms = (tr.window_s - tr.busy_s) * 1e3 / tr.pumps
    gaps = [PH.output_gap_ms(recorded), PH.publish_gap_ms(recorded),
            PH.flush_gap_ms(recorded)]
    assert all(g is not None and g >= 0 for g in gaps)
    assert sum(gaps) <= idle_ms * (1 + 1e-9)


@pytest.mark.parametrize("name", sorted(ON_CHIP))
def test_recorded_reads_what_the_chip_run_printed(recorded, name):
    assert spec.metric_reader(name).read(recorded) == pytest.approx(
        ON_CHIP[name], rel=1e-12)
