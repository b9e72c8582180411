"""Parity tests for the delivery-wheel Pallas kernels (kernels.wheel).

Every kernel runs here in `interpret=True` mode against its XLA-path
reference — the reference IS the semantics (DESIGN.md §Kernels), so the
contract is bit-identical equality, not tolerance. The suite closes the
loop at three levels:

  * kernel vs reference on adversarial standalone inputs (padding,
    ragged tails, multi-block grids);
  * reference vs the engine's own formulation (`descent_reference` vs
    `deliver_network_step`, `_common.in_segment` vs
    `JaxEngine._in_segment`) — the standalone mirrors may not drift;
  * engine trajectories with kernels ON (`kernel="pallas"`, interpret
    on CPU) vs OFF (`kernel="ref"`) — full `DeviceState` equality
    through deferral pressure and churn.

CPU CI runs all of this in the fast suite (the `pallas` marker selects
just these: ``-m pallas``).
"""
from __future__ import annotations

import numpy as np
import pytest

import jax.numpy as jnp

from repro.core.dht import Ring
from repro.engine import protocol as proto
from repro.engine.jax_backend import (NDIR, JaxEngine, deliver_network_step)
from repro.engine.problems import get_problem
from repro.kernels.wheel import WHEEL_KERNELS
from repro.kernels.wheel._common import in_segment
from repro.kernels.wheel.descent import descent_reference, descent_tail_kernel
from repro.kernels.wheel.due_dedup import (dedup_grid, due_dedup_kernel,
                                           due_dedup_reference)
from repro.kernels.wheel.enqueue import (stage_rows_kernel,
                                         stage_rows_reference)
from repro.kernels.wheel.threshold_step import threshold_step_kernel

pytestmark = pytest.mark.pallas


def _eq(a, b, msg=""):
    np.testing.assert_array_equal(np.asarray(a), np.asarray(b), err_msg=msg)


# -- threshold_step: problem-generic fused margin/test/Send ---------------

@pytest.mark.parametrize("problem,dw", [("majority", 1), ("mean", 1),
                                        ("l2", 2)])
@pytest.mark.parametrize("n", [8, 100, 2048 + 17])
def test_threshold_step_matches_rules(problem, dw, n):
    p = get_problem(problem)
    pw = p.payload_width
    rng = np.random.default_rng(n * 7 + dw)
    in_pay = jnp.asarray(rng.integers(-40, 41, (n, NDIR, pw)), jnp.int32)
    out_pay = jnp.asarray(rng.integers(-40, 41, (n, NDIR, pw)), jnp.int32)
    x = jnp.asarray(rng.integers(-300, 301, (n, p.data_width)), jnp.int32)
    # the engine's payload columns: column c*3 + v = component c, dir v
    cols = lambda a: jnp.concatenate([a[..., c] for c in range(pw)], -1)
    want = proto.threshold_rules(p, jnp, in_pay, out_pay, x)
    got = threshold_step_kernel(p, cols(in_pay), cols(out_pay), x,
                                block=256, interpret=True)
    for w, g, name in zip(want[:2], got[:2], ("viol", "out")):
        _eq(g, w, f"{problem} {name}")
    _eq(got[2], cols(want[2]), f"{problem} pay")


def test_threshold_step_l2_consts_roundtrip():
    """L2's direction cover is baked into the trace as float32 scalars
    (a TPU kernel body may hold no array constant and no gather). The
    unrolled column-form test must reproduce the matrix form of the
    rule — projections against the cover as an array, argmax, gather —
    exactly, on both the engines' row layout and the kernels'
    lane-dense planes."""
    p = get_problem("l2")
    rng = np.random.default_rng(0)
    agg = rng.integers(-500, 501, (33, NDIR, 3)).astype(np.int32)
    k = rng.integers(-500, 501, (33, 3)).astype(np.int32)
    # can be hit exactly: a zero payload sits on every face of the cover
    agg[0] = 0
    k[0] = 0

    def proj(pay):
        f = pay.astype(np.float32)
        acc = f[..., 0, None] * p.U[:, 0]
        for j in range(1, p.data_width):
            acc = acc + f[..., j, None] * p.U[:, j]
        return acc - p.Tf * f[..., p.data_width, None]

    pk = proj(k)
    out = pk.max(-1) >= 0
    pa, pka = proj(agg), proj(k[:, None, :] - agg)
    viol_m = ((pa >= 0) & (pka < 0)) | ((pa < 0) & (pka > 0))
    sel = np.take_along_axis(viol_m, pk.argmax(-1)[:, None, None], -1)[..., 0]
    send = np.where(out[:, None], sel, viol_m.any(-1))
    for xp in (np, jnp):
        got = p.test(xp, xp.asarray(agg), xp.asarray(k))
        _eq(got[0], send)
        _eq(got[1], out)
    cols = p.test_cols(
        jnp, [jnp.asarray(agg[..., c].T) for c in range(3)],
        [jnp.asarray(k[None, :, c]) for c in range(3)])
    _eq(cols[0], send.T)
    _eq(cols[1][0], out)


# -- due_dedup: window-local winner/representative/force election ---------

def _dedup_inputs(ww, nl, seed, alert_frac=0.2):
    rng = np.random.default_rng(seed)
    flat = jnp.asarray(rng.integers(0, nl, ww), jnp.int32)
    acc = rng.random(ww) < 0.6
    is_alert = rng.random(ww) < alert_frac
    acc_d = jnp.asarray(acc & ~is_alert)
    acc_a = jnp.asarray(acc & is_alert)
    w_seq = jnp.asarray(rng.integers(0, 50, ww), jnp.int32)
    link_seq = jnp.asarray(rng.integers(0, 50, ww), jnp.int32)
    return flat, acc_d, acc_a, w_seq, link_seq


@pytest.mark.parametrize("ww,block", [(64, 64), (100, 32), (576, 512),
                                      (576, 128)])
@pytest.mark.parametrize("seed", [0, 3])
def test_due_dedup_matches_plane(ww, block, seed):
    # few links => heavy collisions: the dedup election actually works
    nl = max(ww // 3, NDIR)
    args = _dedup_inputs(ww, nl, seed)
    want = due_dedup_reference(*args, nl=nl)
    got = due_dedup_kernel(*args, block=block, interpret=True)
    names = ("winner", "loser", "fresh", "alert_write", "is_rep", "aforce")
    for w, g, name in zip(want, got, names):
        _eq(g, w, f"ww={ww} block={block} {name}")


def test_due_dedup_no_alerts():
    """All-data windows (the steady-state cycle) still elect correctly."""
    ww, nl = 128, 24
    flat, acc_d, _, w_seq, link_seq = _dedup_inputs(ww, nl, 11, alert_frac=0)
    acc_a = jnp.zeros(ww, bool)
    want = due_dedup_reference(flat, acc_d, acc_a, w_seq, link_seq, nl=nl)
    got = due_dedup_kernel(flat, acc_d, acc_a, w_seq, link_seq,
                           block=64, interpret=True)
    for w, g in zip(want, got):
        _eq(g, w)
    assert not np.asarray(got[3]).any()  # no alert_write without alerts


def _lane_dedup_inputs(lanes, ww_l, seed, alert_frac=0.2):
    """A lane-major window under the ownership rule: every row's link
    lies in its own lane's link range (lane l's peers own links
    [l * 3P, (l + 1) * 3P)), with few peers a lane, so links collide."""
    rng = np.random.default_rng(seed)
    links = NDIR * max(ww_l // 9, 1)  # links per lane
    lane = np.repeat(np.arange(lanes), ww_l)
    flat = jnp.asarray(lane * links + rng.integers(0, links, lanes * ww_l),
                       jnp.int32)
    _, acc_d, acc_a, w_seq, link_seq = _dedup_inputs(lanes * ww_l, links,
                                                     seed, alert_frac)
    return (flat, acc_d, acc_a, w_seq, link_seq), lanes * links


@pytest.mark.parametrize("lanes,ww_l,block", [
    (1, 80, 64), (2, 80, 64), (8, 80, 512), (2, 257, 128), (8, 257, 512)])
@pytest.mark.parametrize("alert_frac", [0.2, 0.0])
def test_due_dedup_lanes_match_plane(lanes, ww_l, block, alert_frac):
    """The lane-blocked grid compares rows of one lane only; under the
    ownership rule that is the whole-window election, row for row. Lane
    widths are no multiple of the block (80 is the engine's `window_l`
    at pad 64; 257 stands for the 2^19 cell's 4,112)."""
    args, nl = _lane_dedup_inputs(lanes, ww_l, lanes * 31 + ww_l,
                                  alert_frac)
    want = due_dedup_reference(*args, nl=nl)
    got = due_dedup_kernel(*args, lanes=lanes, block=block, interpret=True)
    names = ("winner", "loser", "fresh", "alert_write", "is_rep", "aforce")
    for w, g, name in zip(want, got, names):
        _eq(g, w, f"lanes={lanes} ww_l={ww_l} block={block} {name}")
    if not alert_frac:
        assert not np.asarray(got[3]).any()


@pytest.mark.parametrize("ww_l,block,visited,whole", [
    (4112, 512, 648, 4225),    # 2^19 cell: 8 x 9^2 of 65^2 block pairs
    (2064, 512, 200, 1089),    # 2^16 cell (pad 2^17): 8 x 5^2 of 33^2
    (4112, 256, 2312, 16641),  # 8 x 17^2 of 129^2
    (2064, 256, 648, 4225),    # 8 x 9^2 of 65^2
])
def test_dedup_grid_pins_the_cells(ww_l, block, visited, whole):
    assert dedup_grid(ww_l, 8, block) == (visited, whole)
    # one lane is the whole-window grid
    assert dedup_grid(8 * ww_l, 1, block) == (whole, whole)


def test_dedup_grid_narrow_lane():
    """A lane narrower than the block is one block of its own width: the
    engine's `window_l` at pad 64 (80 rows a lane, 8 lanes)."""
    assert dedup_grid(80, 8) == (8, 64)
    assert dedup_grid(4112, 8) == dedup_grid(4112, 8, 512)


def test_engine_dedup_grid_share():
    """The engine reports the share of the all-pairs grid its election
    visits, from the window's shape; none where the XLA plane elects."""
    ring = Ring.random(48, d=16, seed=5)
    votes = np.zeros(48, np.int64)
    ker = JaxEngine(ring, votes, kernel="pallas")
    visited, whole = dedup_grid(ker.window_l, ker.lanes)
    assert ker.lanes == 8 and ker.dedup_grid_share == visited / whole
    assert JaxEngine(ring, votes, kernel="ref").dedup_grid_share is None


# -- stage_rows: ordinal-keyed delay classes + DELIVER_T stamping ---------

@pytest.mark.parametrize("m,roww", [(2304, 8), (2310, 9), (40, 8)])
def test_stage_rows_matches_reference(m, roww):
    rng = np.random.default_rng(m)
    rows = jnp.asarray(
        rng.integers(0, 2**32, (m, roww), dtype=np.uint64).astype(np.uint32))
    alert = jnp.asarray(rng.random(m) < 0.15)
    mask = rng.random(m) < 0.6
    # ordinal as the engine builds it: rank of the row among the live
    # rows of its staging block (-1 before the first live row)
    ordinal = jnp.asarray(np.cumsum(mask.astype(np.int32)) - 1)
    perm = jnp.asarray(rng.permutation(10) + 1, jnp.int32)
    t = jnp.asarray(97, jnp.int32)
    dt_col = roww - 1
    want = stage_rows_reference(rows, alert, ordinal, perm, t, dt_col)
    got = stage_rows_kernel(rows, alert, ordinal, perm, t, dt_col,
                            interpret=True)
    _eq(got, want, "staged")
    # and the reference must equal the stated semantics row by row
    wn = np.asarray(want)
    on = np.asarray(ordinal)
    an = np.asarray(alert)
    pn = np.asarray(perm)
    _eq(wn[:, :dt_col], np.asarray(rows)[:, :dt_col], "non-DT columns")
    due = np.where(an, 97 + 1, 97 + pn[on % 10]).astype(np.uint32)
    _eq(wn[:, dt_col], due, "DELIVER_T semantics")


# -- descent: the R1 internal-descent tail --------------------------------

def _descent_inputs(m, seed=0, d=16, n=64):
    """Routing-consistent inputs from a real ring (owner tables the way
    the cycle builds them)."""
    rng = np.random.default_rng(seed)
    ring = Ring.random(n, d, seed=seed + 1)
    votes = rng.integers(0, 2, n)
    eng = JaxEngine(ring, votes, seed=seed, kernel="ref")
    st = eng._st
    dest = jnp.asarray(
        rng.integers(0, 2**d, m, dtype=np.uint64).astype(np.uint32))
    origin = jnp.asarray(np.asarray(st.addrs)[rng.integers(0, n, m)])
    owner = eng._owner_of(st.addrs, st.n_live, dest)
    a_prev, a_self = st.prev[owner], st.addrs[owner]
    kw = dict(
        origin=origin, dest=dest,
        edge=jnp.asarray(rng.integers(0, 2**d, m, dtype=np.uint64)
                         .astype(np.uint32)),
        has_edge=jnp.asarray(rng.random(m) < 0.7),
        live=jnp.asarray(rng.random(m) < 0.8),
        entry=jnp.asarray(rng.random(m) < 0.5),
        pos_i=st.pos[owner], a_prev=a_prev, a_self=a_self,
        self_seg=JaxEngine._in_segment(origin, a_prev, a_self),
        max_addr=st.addrs[st.n_live - 1],
    )
    return kw, d


@pytest.mark.parametrize("m,block", [(64, 64), (200, 64)])
def test_descent_tail_kernel_matches_reference(m, block):
    kw, d = _descent_inputs(m, seed=m)
    args = (kw["origin"], kw["dest"], kw["edge"], kw["has_edge"], kw["live"],
            kw["entry"], kw["pos_i"], kw["a_prev"], kw["a_self"],
            kw["self_seg"], kw["max_addr"])
    want = descent_reference(*args, d=d)
    got = descent_tail_kernel(*args, d=d, block=block, interpret=True)
    for w, g, name in zip(want, got, ("acc", "drop", "o_dest", "o_edge",
                                      "o_he")):
        _eq(g, w, f"m={m} block={block} {name}")


def test_descent_reference_is_deliver_network_step():
    """The standalone reference may not drift from the engine's
    `deliver_network_step` — identical loop on identical inputs."""
    kw, d = _descent_inputs(150, seed=5)
    want = deliver_network_step(d=d, **kw)
    got = descent_reference(
        kw["origin"], kw["dest"], kw["edge"], kw["has_edge"], kw["live"],
        kw["entry"], kw["pos_i"], kw["a_prev"], kw["a_self"],
        kw["self_seg"], kw["max_addr"], d=d)
    for w, g in zip(want, got):
        _eq(g, w)


def test_common_in_segment_matches_engine():
    rng = np.random.default_rng(2)
    addr, a_prev, a_self = (
        jnp.asarray(rng.integers(0, 2**32, 4096, dtype=np.uint64)
                    .astype(np.uint32)) for _ in range(3))
    _eq(in_segment(addr, a_prev, a_self),
        JaxEngine._in_segment(addr, a_prev, a_self))


# -- engine level: kernels ON vs OFF, full-state equality -----------------

def _state_equal(ref, ker, tag):
    for f in ref._st._fields:
        _eq(getattr(ker._st, f), getattr(ref._st, f), f"{tag}: {f}")


def _pair(ring, votes, problem="majority", **kw):
    ref = JaxEngine(ring, votes, seed=9, problem=problem, kernel="ref", **kw)
    ker = JaxEngine(ring, votes, seed=9, problem=problem, kernel="pallas",
                    **kw)
    assert ker._wk == frozenset(WHEEL_KERNELS)
    assert not ref._wk
    return ref, ker


@pytest.mark.parametrize("problem", ["majority", "mean", "l2"])
def test_engine_wheel_kernels_bit_identical(problem):
    rng = np.random.default_rng(3)
    n = 48
    ring = Ring.random(n, d=16, seed=5)
    if problem == "majority":
        votes = rng.integers(0, 2, n)
    elif problem == "mean":
        votes = rng.integers(-8, 9, (n, 1))
    else:
        votes = rng.normal(0, 1.0, (n, 2))  # mixed inside/outside: traffic
    ref, ker = _pair(ring, votes, problem)
    for step in range(4):
        ref.step(cycles=3)
        ker.step(cycles=3)
        _state_equal(ref, ker, f"{problem} step {step}")
        _eq(ker.outputs(), ref.outputs())


def test_engine_wheel_kernels_under_deferral_and_churn():
    """Tiny work_budget forces slips/revolution waits (the LATE-bit
    accounting path) and joins/leaves force alerts (the aforce path) —
    kernels must track the XLA trajectory through both."""
    n = 200
    rng = np.random.default_rng(1)
    votes = rng.integers(0, 2, n)
    ring = Ring.random(n, d=18, seed=2)
    ref, ker = _pair(ring, votes, work_budget=32)
    for step in range(8):
        ref.step(cycles=2)
        ker.step(cycles=2)
        _state_equal(ref, ker, f"defer step {step}")
    assert ref.deferred > 0  # the budget squeeze actually engaged
    assert ref.deferral_rate == ker.deferral_rate > 0

    ref, ker = _pair(ring, votes)
    ref.step(cycles=2)
    ker.step(cycles=2)
    for i, a in enumerate((1234567, 424242)):
        ref.join(a)
        ker.join(a)
        ref.step(cycles=4)
        ker.step(cycles=4)
        _state_equal(ref, ker, f"join {i}")
    ref.leave(3)
    ker.leave(3)
    ref.step(cycles=6)
    ker.step(cycles=6)
    _state_equal(ref, ker, "leave")


def test_engine_wheel_kernel_subset_and_validation():
    """`wheel_kernels` selects individual kernels (each has its own
    fallback flag); unknown names fail fast."""
    n = 32
    rng = np.random.default_rng(4)
    votes = rng.integers(0, 2, n)
    ring = Ring.random(n, d=16, seed=7)
    ref = JaxEngine(ring, votes, seed=3, kernel="ref")
    one = JaxEngine(ring, votes, seed=3, kernel="pallas",
                    wheel_kernels=("enqueue",))
    assert one._wk == {"enqueue"}
    ref.step(cycles=4)
    one.step(cycles=4)
    _state_equal(ref, one, "enqueue-only")
    off = JaxEngine(ring, votes, seed=3, kernel="pallas",
                    wheel_kernels="none")
    assert not off._wk
    with pytest.raises(ValueError, match="unknown wheel kernels"):
        JaxEngine(ring, votes, seed=3, wheel_kernels=("bogus",))


def test_deferred_counts_each_row_once():
    """The LATE bit stops the historical standing-backlog recount:
    deferred must stay well below (backlog x residence-cycles)."""
    n = 200
    rng = np.random.default_rng(8)
    votes = rng.integers(0, 2, n)
    ring = Ring.random(n, d=18, seed=3)
    eng = JaxEngine(ring, votes, seed=1, kernel="ref", work_budget=32)
    eng.step(cycles=1)  # init storm lands in the wheel
    # budget is per lane now: a (lane, slot) cell above lane_budget
    # must wait for a later cycle
    backlog = max(int(np.asarray(eng._st.wcnt).max()) - eng.lane_budget, 0)
    assert backlog > 0, "config must actually overflow the budget"
    eng.step(cycles=30)
    # once-per-row: bounded by total rows ever enqueued (~3n + resends),
    # NOT by backlog x 30 cycles of residence
    assert eng.deferred < 3 * n + eng.messages_sent
    assert eng.deferral_rate == eng.deferred / eng.messages_sent
