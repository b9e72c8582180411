"""The reduction from a profiler trace to per-layer metrics, on a small
trace recorded on one TPU v5 lite chip (bench/testdata/)."""
import os

import pytest

from harness import trace as T
from harness.context import Context
from harness import spec

DATA = os.path.join(spec.BENCH, "testdata")


def test_union_clips_and_merges():
    iv = [(0, 10), (5, 15), (20, 30), (29, 31), (40, 50)]
    assert T.union_ns(iv, 0, 100) == 15 + 11 + 10
    assert T.union_ns(iv, 8, 25) == 7 + 5
    assert T.union_ns([], 0, 10) == 0


def test_nested_ops_get_self_time():
    mods = [T.Event("jit__steps_impl", "", 0, 100)]
    ops = [("%while.1 = loop", 0, 100), ("%body.2 = inner", 10, 30),
           ("%fusion.3 = f", 50, 20)]
    evs = T.attribute(ops, mods)
    by = {e.name.split(" ")[0]: e for e in evs}
    assert by["%while.1"].self_ns == 50
    assert by["%body.2"].self_ns == 30
    assert all(e.module == "jit__steps_impl" for e in evs)


KERNEL_OPS = {
    "dedup": '%body.37 = (s32[66048,1]{1,0:T(8,128)}, s32[66048,1]{1,0}, '
             's32[66048,1]{1,0}, s32[66048,3]{1,0}, s32[66048,1]{1,0}, '
             's32[66048,1]{1,0}, s32[66048,1]{1,0}, s32[66048,1]{1,0}, '
             's32[66048,1]{1,0}) custom-call(s32[66048,1]{1,0} %a), '
             'custom_call_target="tpu_custom_call"',
    "enqueue": '%body.39 = u32[262656,8]{1,0:T(8,128)} custom-call('
               'u32[262656,8]{1,0} %x), custom_call_target="tpu_custom_call"',
    "threshold": '%body.38 = (s32[3,69632]{1,0}, s32[1,69632]{1,0}, '
                 's32[6,69632]{1,0}) custom-call(s32[6,69632]{1,0} %p), '
                 'custom_call_target="tpu_custom_call"',
}


@pytest.mark.parametrize("kind", sorted(KERNEL_OPS))
def test_wheel_kernels_told_apart(kind):
    assert T.pallas_kernel(KERNEL_OPS[kind]) == kind


def test_non_kernel_op_is_none():
    assert T.pallas_kernel("%fusion.1 = u32[8]{0} fusion(u32[8]{0} %a)") == ""
    assert T.program_name("jit__steps_impl(9275285723466863581)") == \
        "jit__steps_impl"


# What bench/run.py printed for this trace on the chip (TPU v5 lite):
# 3 pumps, 24 cycles of a 4,096-peer mean monitor (pad 8192, default
# work budget: 8 lanes of 144 drain rows, WW = 1152).
RECORDED = os.path.join(DATA, "tiny-mean-3pumps.xplane.pb.gz")
ON_CHIP = {"device_idle_share.served": 57.1722972854382,
           "cycle_device_ms.served": 1.5544465416666668,
           "wheel_kernel_ms.served": 0.15564491666666666,
           "dedup_roofline.served": 0.026829480507415175,
           "flush_device_ms": 1.312743}


class _Engine:
    lanes, window_l = 8, 144


class _Win:
    engine = _Engine()


@pytest.fixture(scope="module")
def recorded():
    return T.Reduced.from_file(RECORDED, cycles=24, pumps=3)


def test_recorded_trace_window_and_busy(recorded):
    assert recorded.window_s == pytest.approx(0.096573193, abs=1e-9)
    assert recorded.busy_s == pytest.approx(0.04136008, abs=1e-9)
    assert 0 < recorded.busy_s < recorded.window_s
    names = {m.name for evs in recorded.modules.values() for m in evs}
    assert {"jit__steps_impl", "jit__react_impl"} <= names


def test_recorded_trace_finds_every_wheel_kernel(recorded):
    for kind in ("dedup", "descent", "threshold", "enqueue"):
        ns, calls = recorded.kernel(kind)
        assert calls == 24 and ns > 0, kind


@pytest.mark.parametrize("name", sorted(ON_CHIP))
def test_recorded_trace_reads_what_the_chip_run_printed(recorded, name):
    ctx = Context(_Win(), spec.peaks("TPU v5 lite"), 0.0, trace=recorded)
    value = spec.metric_reader(name).read(ctx)
    assert value == pytest.approx(ON_CHIP[name], rel=1e-12)


@pytest.mark.parametrize("name", sorted(n for n in ON_CHIP
                                         if n.endswith(".served")))
def test_storm_metric_reads_the_same_quantity(recorded, name):
    """A quantity's `.storm` and `.served` metrics are one reduction."""
    ctx = Context(_Win(), spec.peaks("TPU v5 lite"), 0.0, trace=recorded)
    storm = name.replace(".served", ".storm")
    assert spec.metric_reader(storm).read(ctx) == ON_CHIP[name]


def test_roofline_is_a_share(recorded):
    ctx = Context(_Win(), spec.peaks("TPU v5 lite"), 0.0, trace=recorded)
    for fam in ("storm", "served"):
        value = spec.metric_reader(f"dedup_roofline.{fam}").read(ctx)
        assert 0 < value <= 100


def test_breakdown_names_ops_and_gaps(recorded):
    b = recorded.breakdown()
    assert 0 < len(b["device_ops"]) <= 10 and 0 < len(b["idle_gaps"]) <= 10
    assert b["device_ops"][0][0].startswith("jit__")
    gaps = sum(v for _, v in b["idle_gaps"])
    assert gaps == pytest.approx(recorded.window_s - recorded.busy_s,
                                 rel=1e-6)


def test_no_trace_reads_nothing():
    ctx = Context(_Win(), {}, 0.0, trace=None)
    for name in ON_CHIP:
        assert spec.metric_reader(name).read(ctx) is None
