"""Engine layer: backend protocol parity (numpy reference vs device).

Three levels of equivalence, strongest first:
  1. rule level — `engine.protocol` functions produce bit-identical
     results on numpy and jnp arrays (no RNG involved);
  2. step level — one network delivery through `routing.step_batch`
     (numpy) and through the jax engine's deliver loop classify every
     message identically;
  3. system level — full 1,024-peer majority-voting runs on both
     backends converge to the same outputs with message counts inside
     the seeded-RNG tolerance documented in DESIGN.md §Engine.
"""
import numpy as np
import pytest

import jax.numpy as jnp

from repro.core import addressing as A
from repro.core import routing as R
from repro.core.dht import Ring
from repro.engine import BACKENDS, make_engine
from repro.engine import protocol as P


def _votes(n, mu, rng):
    k = int(round(n * mu))
    v = np.zeros(n, np.int64)
    v[rng.choice(n, k, replace=False)] = 1
    return v


# ---------------------------------------------------------------------------
# 1. rule level
# ---------------------------------------------------------------------------

def test_send_fields_numpy_vs_jnp():
    ring = Ring.random(500, 32, seed=1)
    pos = ring.positions()
    rng = np.random.default_rng(2)
    peers = rng.integers(0, ring.n, 3000)
    dirs = rng.integers(0, 3, 3000)
    out_np = P.send_fields(
        np, pos[peers], dirs, ring.addrs[peers], ring.prev[peers], ring.d
    )
    out_j = P.send_fields(
        jnp,
        jnp.asarray(pos[peers].astype(np.uint32)), jnp.asarray(dirs),
        jnp.asarray(ring.addrs[peers].astype(np.uint32)),
        jnp.asarray(ring.prev[peers].astype(np.uint32)), ring.d,
    )
    for a, b in zip(out_np, out_j):
        np.testing.assert_array_equal(
            np.asarray(a, np.uint64), np.asarray(b, np.uint64)
        )


def test_majority_rules_numpy_vs_jnp():
    rng = np.random.default_rng(3)
    n = 4000
    io = rng.integers(0, 40, (n, 3))
    it = io + rng.integers(0, 40, (n, 3))
    oo = rng.integers(0, 40, (n, 3))
    ot = oo + rng.integers(0, 40, (n, 3))
    x = rng.integers(0, 2, n)
    out_np = P.majority_rules(io, it, oo, ot, x)
    out_j = P.majority_rules(
        jnp.asarray(io, jnp.int32), jnp.asarray(it, jnp.int32),
        jnp.asarray(oo, jnp.int32), jnp.asarray(ot, jnp.int32),
        jnp.asarray(x, jnp.int32),
    )
    for a, b in zip(out_np, out_j):
        np.testing.assert_array_equal(np.asarray(a, np.int64),
                                      np.asarray(b, np.int64))


@pytest.mark.parametrize("m,seed", [(1, 0), (1000, 1), (4096, 2)])
def test_group_ranks_sort_and_scan_agree(m, seed):
    """The wheel's append ranks: the sort form (off the TPU) and the
    masked-cumsum form (compiled for the TPU) give one exact result,
    a plain per-group counter over the live rows."""
    from repro.engine.jax_backend import JaxEngine

    rng = np.random.default_rng(seed)
    ng = 88
    g = rng.integers(0, ng, m)
    live = rng.random(m) < 0.6
    want_rank = np.zeros(m, np.int64)
    want_counts = np.zeros(ng, np.int64)
    for i in np.flatnonzero(live):
        want_rank[i] = want_counts[g[i]]
        want_counts[g[i]] += 1
    for scan in (False, True):
        rank, counts = JaxEngine._group_ranks(
            jnp.asarray(g, jnp.int32), jnp.asarray(live), ng, scan=scan)
        np.testing.assert_array_equal(np.asarray(rank), want_rank)
        np.testing.assert_array_equal(np.asarray(counts), want_counts)


def test_jax_engine_same_run_under_either_rank_form(monkeypatch):
    """A whole device-engine run is the same with the TPU's rank form
    forced on the CPU: outputs, cycles, messages and drops."""
    from functools import partial

    from repro.engine.jax_backend import JaxEngine

    ring = Ring.random(300, 32, seed=4)
    votes = _votes(300, 0.4, np.random.default_rng(4))

    def run():
        eng = make_engine("jax", ring, votes, seed=5)
        eng.step(80)
        return (np.asarray(eng.outputs()), eng.t, eng.messages_sent,
                eng.dropped)

    base = run()
    calls = []
    scan = partial(JaxEngine._group_ranks, scan=True)
    monkeypatch.setattr(JaxEngine, "_group_ranks", staticmethod(
        lambda *a, **k: calls.append(1) or scan(*a, **k)))
    forced = run()
    assert calls, "the forced rank form was never traced"
    np.testing.assert_array_equal(forced[0], base[0])
    assert forced[1:] == base[1:]


# ---------------------------------------------------------------------------
# 2. step level — numpy step_batch vs the jax engine's delivery loop
# ---------------------------------------------------------------------------

def _jax_network_step(ring, origin, dest, edge, has_edge):
    """One network delivery through the device engine's own routing code
    (`deliver_network_step` — the function `_cycle_impl` executes)."""
    from repro.engine.jax_backend import JaxEngine, deliver_network_step

    n, d = ring.n, ring.d
    addrs = jnp.asarray(ring.addrs.astype(np.uint32))
    prev = jnp.roll(addrs, 1)
    pos = jnp.asarray(ring.positions().astype(np.uint32))
    oj = jnp.asarray(origin.astype(np.uint32))
    dj = jnp.asarray(dest.astype(np.uint32))
    owner = jnp.searchsorted(addrs, dj, side="left") % n
    pos_i, a_prev, a_self = pos[owner], prev[owner], addrs[owner]
    acc, drop, od, oe, ohe = deliver_network_step(
        origin=oj, dest=dj, edge=jnp.asarray(edge.astype(np.uint32)),
        has_edge=jnp.asarray(has_edge),
        live=jnp.ones(origin.shape[0], bool),
        pos_i=pos_i, a_prev=a_prev, a_self=a_self,
        self_seg=JaxEngine._in_segment(oj, a_prev, a_self),
        max_addr=addrs[-1], d=d,
    )
    status = np.where(np.asarray(acc), R.ACCEPT,
                      np.where(np.asarray(drop), R.DROP, R.FORWARD))
    return status, np.asarray(owner), np.asarray(od), np.asarray(oe), np.asarray(ohe)


@pytest.mark.slow
def test_delivery_exact_parity_multihop():
    """Every message classifies identically in both backends, hop by hop,
    until the whole batch has been accepted or dropped (no RNG here)."""
    ring = Ring.random(300, 32, seed=5)
    pos = ring.positions()
    rng = np.random.default_rng(7)
    k = 2000
    peers = rng.integers(0, ring.n, k)
    dirs = rng.integers(0, 3, k)
    valid, origin, dest, edge, has_edge = R.send_batch(ring, peers, dirs, pos=pos)
    v = np.nonzero(valid)[0]
    origin, dest, edge, has_edge = origin[v], dest[v], edge[v], has_edge[v]
    hops = 0
    while origin.size and hops < ring.d + 2:
        status, owner, nd, ne, nhe = R.step_batch(
            ring, origin, dest, edge, has_edge, pos=pos
        )
        status_j, owner_j, od, oe, ohe = _jax_network_step(
            ring, origin, dest, edge, has_edge
        )
        np.testing.assert_array_equal(status_j, status)
        np.testing.assert_array_equal(owner_j, owner)
        f = status == R.FORWARD
        np.testing.assert_array_equal(od[f], nd[f].astype(np.uint32))
        np.testing.assert_array_equal(oe[f], ne[f].astype(np.uint32))
        np.testing.assert_array_equal(ohe[f], nhe[f])
        origin, dest, edge, has_edge = origin[f], nd[f], ne[f], nhe[f]
        hops += 1
    assert origin.size == 0, "messages did not terminate"


# ---------------------------------------------------------------------------
# 3. system level — the acceptance-criterion parity run
# ---------------------------------------------------------------------------

@pytest.mark.slow
def test_engine_parity_1024_peers():
    """1,024-peer run: numpy and device backends (Pallas kernel in
    interpret mode) converge to identical outputs; message counts agree
    within the seeded-RNG tolerance (DESIGN.md §Engine documents 20%)."""
    n = 1024
    rng = np.random.default_rng(0)
    ring = Ring.random(n, 32, seed=0)
    votes = _votes(n, 0.3, rng)

    jx = make_engine("jax", ring, votes, seed=1, kernel="pallas")
    nu = make_engine("numpy", ring, votes, seed=1)
    r_j = jx.run_until_converged(truth=0, max_cycles=20_000)
    r_n = nu.run_until_converged(truth=0, max_cycles=20_000)
    assert r_j["converged"] == 1.0 and r_n["converged"] == 1.0
    np.testing.assert_array_equal(jx.outputs(), nu.outputs())
    assert jx.dropped == 0
    assert abs(r_j["messages"] - r_n["messages"]) <= 0.2 * r_n["messages"]

    # vote flip (paper §4.2.1) reconverges identically too
    new = _votes(n, 0.7, rng)
    for eng in (jx, nu):
        chg = np.nonzero(new != eng.votes())[0]
        eng.set_votes(chg, new[chg])
    r_j2 = jx.run_until_converged(truth=1, max_cycles=20_000)
    r_n2 = nu.run_until_converged(truth=1, max_cycles=20_000)
    assert r_j2["converged"] == 1.0 and r_n2["converged"] == 1.0
    np.testing.assert_array_equal(jx.outputs(), nu.outputs())
    assert abs(r_j2["messages"] - r_n2["messages"]) <= 0.2 * r_n2["messages"]


# ---------------------------------------------------------------------------
# 4. churn — Alg. 2 in both backends
# ---------------------------------------------------------------------------

def _make_schedule(ring0, events, seed, p_leave=0.5):
    """Shared seeded schedule (repro.core.churn) as (ops, snaps)."""
    from repro.core.churn import random_schedule

    s = random_schedule(ring0, events, seed, p_leave=p_leave)
    return s, s.snaps


def _apply_schedule(eng, sched, spacing):
    for op in sched.ops:
        if op[0] == "join":
            eng.join(op[1], vote=op[2])
        else:
            eng.leave(op[1])
        eng.step(spacing)


def _route_event_alerts_jax(ring, a_im2, a_im1, a_i):
    """Route one churn event's <= 6 ALERTs through the device engine's
    own functions (`protocol.alert_plan` + `deliver_network_step`),
    batched per hop. Returns per-alert (accepting peer or None, trace or
    None) — the same classification record `notify.route_alert_trace`
    produces on the numpy path."""
    from repro.engine.jax_backend import JaxEngine, deliver_network_step

    d = ring.d
    addrs = jnp.asarray(ring.addrs.astype(np.uint32))
    prev = jnp.roll(addrs, 1)
    pos = jnp.asarray(ring.positions().astype(np.uint32))
    u32 = lambda v: jnp.asarray(v, jnp.uint32)
    pos_fix, pos_var = P.change_positions(jnp, u32(a_im2), u32(a_im1),
                                          u32(a_i), d)
    ap, adirs = P.alert_plan(jnp, pos_fix, pos_var)
    own0 = jnp.searchsorted(addrs, ap, side="left") % ring.n
    valid, origin, dest, edge, has_edge = P.send_fields(
        jnp, ap, adirs, addrs[own0], prev[own0], d
    )
    live = valid
    accepted = np.full(6, -1, np.int64)
    traces = [[] if bool(valid[q]) else None for q in range(6)]
    for _ in range(d + 2):
        if not bool(live.any()):
            break
        owner = (jnp.searchsorted(addrs, dest, side="left") % ring.n)
        acc, drop, od, oe, ohe = deliver_network_step(
            origin=origin, dest=dest, edge=edge, has_edge=has_edge,
            live=live, pos_i=pos[owner], a_prev=prev[owner],
            a_self=addrs[owner],
            self_seg=JaxEngine._in_segment(origin, prev[owner], addrs[owner]),
            max_addr=addrs[-1], d=d,
        )
        lv, av = np.asarray(live), np.asarray(acc)
        dv, ov = np.asarray(dest), np.asarray(owner)
        for q in range(6):
            if lv[q]:
                traces[q].append((int(dv[q]), int(ov[q])))
                if av[q]:
                    accepted[q] = int(ov[q])
        live = live & ~acc & ~drop
        dest, edge, has_edge = od, oe, ohe
    assert not bool(live.any()), "alert routing did not terminate"
    return [(None if accepted[q] < 0 else int(accepted[q]), traces[q])
            for q in range(6)]


def _assert_alert_classification_parity(snaps):
    """Every ALERT delivery of every churn event classifies bit-
    identically on the numpy reference path and the device path."""
    from repro.core import notify as N

    n_alerts = n_hops = 0
    for ring_after, a_im2, a_im1, a_i in snaps:
        pos = ring_after.positions()
        alerts = N.alerts_for_change(a_im2, a_im1, a_i, ring_after.d,
                                     ring_after.addrs.dtype)
        jax_side = _route_event_alerts_jax(ring_after, a_im2, a_im1, a_i)
        for alert, (peer_j, trace_j) in zip(alerts, jax_side):
            peer_np, trace_np = N.route_alert_trace(ring_after, alert, pos=pos)
            assert peer_j == peer_np, (alert, peer_j, peer_np)
            if trace_np is None:
                assert trace_j is None
                continue
            got = [(h.dest, h.peer) for h in trace_np]
            assert trace_j == got, (alert, trace_j, got)
            n_alerts += 1
            n_hops += len(got)
            if peer_np is not None:
                d_np = N.alert_direction(alert.from_pos, int(pos[peer_np]),
                                         ring_after.d,
                                         ring_after.addrs.dtype.type)
                d_j = int(A.direction_of(
                    jnp.asarray(alert.from_pos, jnp.uint32),
                    jnp.asarray(int(pos[peer_np]), jnp.uint32), ring_after.d,
                ))
                assert d_j == d_np
    assert n_alerts > 0 and n_hops >= n_alerts


def test_churn_alert_classification_parity_small():
    """Fast version of the churn parity harness: every ALERT delivery
    over 8 events classifies identically in both backends' routers."""
    ring = Ring.random(48, 32, seed=11)
    _, snaps = _make_schedule(ring, events=8, seed=12)
    _assert_alert_classification_parity(snaps)


def test_engine_churn_parity_small():
    """Identical join/leave schedule on both backends: same final
    outputs, no device drops, message counts within the envelope."""
    n = 64
    rng = np.random.default_rng(21)
    ring = Ring.random(n, 32, seed=21)
    votes = _votes(n, 0.3, rng)
    jx = make_engine("jax", ring, votes, seed=5, kernel="ref")
    nu = make_engine("numpy", ring, votes, seed=5)
    sched, _ = _make_schedule(ring, events=6, seed=22)
    for eng in (jx, nu):
        assert eng.run_until_converged(truth=0,
                                       max_cycles=10_000)["converged"] == 1.0
        _apply_schedule(eng, sched, spacing=25)
    v = nu.votes()
    np.testing.assert_array_equal(jx.votes(), v)
    truth = int(2 * v.sum() >= v.size)
    r_j = jx.run_until_converged(truth=truth, max_cycles=20_000)
    r_n = nu.run_until_converged(truth=truth, max_cycles=20_000)
    assert r_j["converged"] == 1.0 and r_n["converged"] == 1.0
    assert jx.dropped == 0 and r_j["invalid"] == 0.0
    np.testing.assert_array_equal(jx.outputs(), nu.outputs())
    assert abs(jx.messages_sent - nu.messages_sent) <= 0.2 * nu.messages_sent


@pytest.mark.slow
@pytest.mark.churn
def test_engine_churn_parity_1024_peers():
    """The acceptance-criterion run: 1,024 peers, >= 32 interleaved
    join/leave events. Both backends re-converge to the true majority
    with dropped == 0, every ALERT delivery classifies bit-identically,
    and total message counts stay within the 20% envelope."""
    n = 1024
    rng = np.random.default_rng(0)
    ring = Ring.random(n, 32, seed=0)
    votes = _votes(n, 0.3, rng)
    sched, snaps = _make_schedule(ring, events=32, seed=1)
    _assert_alert_classification_parity(snaps)

    jx = make_engine("jax", ring, votes, seed=2, kernel="ref")
    nu = make_engine("numpy", ring, votes, seed=2)
    for eng in (jx, nu):
        assert eng.run_until_converged(truth=0,
                                       max_cycles=20_000)["converged"] == 1.0
        _apply_schedule(eng, sched, spacing=20)
    v = nu.votes()
    np.testing.assert_array_equal(jx.votes(), v)
    truth = int(2 * v.sum() >= v.size)
    r_j = jx.run_until_converged(truth=truth, max_cycles=20_000)
    r_n = nu.run_until_converged(truth=truth, max_cycles=20_000)
    assert r_j["converged"] == 1.0 and r_n["converged"] == 1.0
    assert jx.dropped == 0 and r_j["invalid"] == 0.0
    np.testing.assert_array_equal(jx.outputs(), nu.outputs())
    assert abs(jx.messages_sent - nu.messages_sent) <= 0.2 * nu.messages_sent


def test_jax_engine_churn_deterministic():
    """Same seed + same schedule => identical trajectory (outputs,
    messages_sent, deferred, dropped), independent of numpy's *global*
    RNG state."""
    n = 96
    rng = np.random.default_rng(3)
    ring = Ring.random(n, 32, seed=3)
    votes = _votes(n, 0.4, rng)
    sched, _ = _make_schedule(ring, events=6, seed=4)

    def run(global_seed):
        np.random.seed(global_seed)  # must not influence the engine
        eng = make_engine("jax", ring, votes, seed=9, kernel="ref")
        traj = []
        eng.step(40)
        for ev in sched.ops:
            if ev[0] == "join":
                eng.join(ev[1], vote=ev[2])
            else:
                eng.leave(ev[1])
            eng.step(20)
            np.random.random(100)  # perturb global state mid-run too
            traj.append((eng.t, eng.messages_sent, eng.deferred,
                         eng.dropped, eng.outputs().tolist()))
        return traj

    assert run(123) == run(987654)


def test_jax_engine_churn_under_budget_pressure():
    """ALERT rows outrank data in the per-cycle work buffer: even a
    binding budget (deferred > 0) must not let a mover's re-sent data
    overtake its alert and be zeroed retroactively — the run still
    re-converges and matches the reference outputs."""
    n = 96
    rng = np.random.default_rng(31)
    ring = Ring.random(n, 32, seed=31)
    votes = _votes(n, 0.35, rng)
    jx = make_engine("jax", ring, votes, seed=7, kernel="ref",
                     work_budget=24)
    nu = make_engine("numpy", ring, votes, seed=7)
    sched, _ = _make_schedule(ring, events=8, seed=32)
    for eng in (jx, nu):
        assert eng.run_until_converged(truth=0,
                                       max_cycles=20_000)["converged"] == 1.0
        _apply_schedule(eng, sched, spacing=30)
    assert jx.deferred > 0  # the budget did bind
    v = nu.votes()
    truth = int(2 * v.sum() >= v.size)
    r_j = jx.run_until_converged(truth=truth, max_cycles=30_000)
    r_n = nu.run_until_converged(truth=truth, max_cycles=30_000)
    assert r_j["converged"] == 1.0 and r_n["converged"] == 1.0
    assert jx.dropped == 0
    np.testing.assert_array_equal(jx.outputs(), nu.outputs())


def test_jax_engine_churn_grow_repads():
    """Joins past the padded capacity trigger the grow + re-jit path and
    the run stays correct."""
    n = 24
    rng = np.random.default_rng(5)
    ring = Ring.random(n, 32, seed=5)
    votes = _votes(n, 0.25, rng)
    eng = make_engine("jax", ring, votes, seed=6, kernel="ref", pad_to=26)
    nu = make_engine("numpy", ring, votes, seed=6)
    sched, _ = _make_schedule(ring, events=8, seed=7, p_leave=0.0)
    for e in (eng, nu):
        assert e.run_until_converged(truth=0,
                                     max_cycles=10_000)["converged"] == 1.0
        _apply_schedule(e, sched, spacing=25)
    assert eng.n == n + 8 and eng.pad >= eng.n
    v = nu.votes()
    truth = int(2 * v.sum() >= v.size)
    assert eng.run_until_converged(truth=truth,
                                   max_cycles=20_000)["converged"] == 1.0
    assert nu.run_until_converged(truth=truth,
                                  max_cycles=20_000)["converged"] == 1.0
    np.testing.assert_array_equal(eng.outputs(), nu.outputs())
    assert eng.dropped == 0


def test_engine_churn_api_guards():
    ring = Ring.random(4, 32, seed=8)
    votes = np.zeros(4, np.int64)
    for backend in BACKENDS:
        eng = make_engine(backend, ring, votes, seed=0)
        with pytest.raises(ValueError):
            eng.join(int(ring.addrs[0]))  # occupied address
        eng.leave(2)
        eng.leave(1)
        eng.leave(0)
        with pytest.raises(ValueError):
            eng.leave(0)  # cannot empty the ring
        assert eng.votes().shape == (1,)


def test_jax_engine_budget_overflow_defers_not_drops():
    """A tiny work budget must slip deliveries (deferred counter), never
    lose them; the run still converges."""
    n = 300
    rng = np.random.default_rng(1)
    ring = Ring.random(n, 32, seed=1)
    votes = _votes(n, 0.35, rng)
    eng = make_engine("jax", ring, votes, seed=2, kernel="ref",
                      work_budget=64)
    res = eng.run_until_converged(truth=0, max_cycles=20_000)
    assert res["converged"] == 1.0
    assert eng.deferred > 0  # the budget did bind
    assert eng.dropped == 0  # but nothing was lost


def test_jax_engine_capacity_overflow_counts_drops():
    """Exhausting the table records drops instead of corrupting state."""
    n = 300
    rng = np.random.default_rng(2)
    ring = Ring.random(n, 32, seed=2)
    votes = _votes(n, 0.4, rng)
    eng = make_engine("jax", ring, votes, seed=3, kernel="ref",
                      capacity_per_peer=1, pad_to=n)
    eng.step(30)
    assert eng.dropped > 0
    assert 0 <= eng.in_flight <= eng.capacity


def test_jax_engine_overflow_flags_run_invalid():
    """A device run that lost messages to table overflow must surface
    dropped > 0 and an invalid-flagged result — never a quietly wrong
    free-list. After the overflow the engine still steps: the slot
    accounting stays within [0, capacity] and drops only grow."""
    n = 300
    rng = np.random.default_rng(9)
    ring = Ring.random(n, 32, seed=9)
    votes = _votes(n, 0.45, rng)
    eng = make_engine("jax", ring, votes, seed=4, kernel="ref",
                      capacity_per_peer=1, pad_to=n)
    res = eng.run_until_converged(truth=0, max_cycles=300)
    assert eng.dropped > 0
    assert res["invalid"] == 1.0
    d0 = eng.dropped
    for _ in range(5):
        eng.step(10)
        assert 0 <= eng.in_flight <= eng.capacity
        assert eng.dropped >= d0
    # a healthy run is never flagged
    ok = make_engine("jax", ring, votes, seed=4, kernel="ref")
    res2 = ok.run_until_converged(truth=0, max_cycles=20_000)
    assert res2["converged"] == 1.0 and res2["invalid"] == 0.0
    assert ok.dropped == 0


def test_engine_api_surface():
    ring = Ring.random(64, 32, seed=3)
    votes = np.zeros(64, np.int64)
    with pytest.raises(ValueError):
        make_engine("cuda", ring, votes)
    with pytest.raises(ValueError):
        make_engine("jax", Ring.random(64, 48, seed=3), votes)
    with pytest.raises(ValueError):
        make_engine("jax", ring, votes, kernel="warp")
    for backend in BACKENDS:
        eng = make_engine(backend, ring, votes, seed=0)
        assert eng.backend == backend
        assert eng.messages_sent == 0  # unanimity: init sends nothing
        eng.step(5)
        assert (eng.outputs() == 0).all()
        assert eng.votes().shape == (64,)
