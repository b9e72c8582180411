"""The reductions the per-layer and end-to-end metric files share.

A quantity measured in cells that report different end-to-end metrics
has one metric per family of cells (`<quantity>.storm` in cold starts,
`<quantity>.served` under client traffic), each its own file under
`bench/metrics/`; both read the quantity here. A reader that finds
nothing to read returns None and the metric is left out of the line.
"""
from __future__ import annotations

STEPS_PROGRAM = "jit__steps_impl"     # JaxEngine._steps: the superstep
WHEEL_KERNELS = ("dedup", "descent", "threshold", "enqueue")

# dedup: per window row the flat link index, the two accept flags, the
# row's sequence number and the link's last sequence number read once
# (5 x 4 bytes); the decisions written once (winner, loser, fresh,
# alert_write, is_rep and three alert-force bits: 8 x 1 byte)
DEDUP_READ_BYTES = 5 * 4
DEDUP_WRITE_BYTES = 8 * 1


def cycles_per_s(ctx):
    """Engine cycles completed on the served path in the window, over
    the window's wall time. A cycle counts once the pump that ran it has
    returned; the window ends at the return of its last pump."""
    w = ctx.win
    return w.window_cycles / (w.w1 - w.w0)


def device_idle_share(ctx):
    """Share of the traced window in which no operation ran on the
    chip, in %: 1 - (union of the device op intervals) / window."""
    tr = ctx.trace
    if tr is None or not tr.ops or tr.window_s <= 0:
        return None
    return 100.0 * (1.0 - tr.busy_s / tr.window_s)


def cycle_device_ms(ctx):
    """Device ms per engine cycle of the superstep program (traced as
    `jit__steps_impl`), over the cycles the traced pumps ran."""
    tr = ctx.trace
    if tr is None or tr.cycles <= 0:
        return None
    ns = tr.module_ns(lambda name: name == STEPS_PROGRAM)
    if ns <= 0:
        return None
    return ns / tr.cycles / 1e6


def wheel_kernel_ms(ctx):
    """Device ms per engine cycle of the four wheel kernels
    (kernels/wheel: dedup, descent, threshold, enqueue) inside the
    superstep program, over the cycles the traced pumps ran."""
    tr = ctx.trace
    if tr is None or tr.cycles <= 0:
        return None
    ns = sum(tr.kernel(k)[0] for k in WHEEL_KERNELS)
    if ns <= 0:
        return None
    return ns / tr.cycles / 1e6


def dedup_question_bytes(ww: int) -> int:
    return ww * (DEDUP_READ_BYTES + DEDUP_WRITE_BYTES)


def dedup_roofline(ctx):
    """The due-scan election kernel's share of its roofline, in %.

    The election's question is answered from the drain window alone, so
    its bytes are the window's inputs read once and its decisions
    written once; the ideal time is those bytes over the chip's HBM
    bandwidth, whatever implementation answers the question. No
    operation count of the all-pairs form enters it. WW is the engine's
    drain window, lanes x per-lane window rows."""
    tr = ctx.trace
    if tr is None:
        return None
    ns, calls = tr.kernel("dedup")
    if calls <= 0 or ns <= 0:
        return None
    eng = ctx.engine
    ww = int(eng.lanes) * int(eng.window_l)
    ideal_s = dedup_question_bytes(ww) / float(ctx.peaks["hbm_bytes_per_s"])
    return 100.0 * ideal_s / (ns / calls / 1e9)


def publish_host_ms(ctx):
    """Host ms per window spent in the notifier's publish (the diff of
    every peer's output against the last window and the delivery of
    transitions), from the harness's span around
    `DecisionNotifier.publish`, over the measured window."""
    w = ctx.win
    calls = w.window_calls.get("serve.publish", 0)
    if not calls:
        return None
    return w.window_spans["serve.publish"] / calls * 1e3
