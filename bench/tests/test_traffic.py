"""The one generator: deterministic for a seed, the same work for every
seed, the zipf shape YCSB asks for, and membership churn with Weibull
sessions."""
import hashlib
import math

import numpy as np
import pytest

from harness import spec
from harness import traffic as T

MIX = {"kind": "open_loop", "rate_per_s": 500.0,
       "keys": {"dist": "zipf", "s": 0.99}}
DATA = {"dist": "normal", "offsets": [-0.6, 0.6], "sd": 0.8}


def _sched(seed, mix=MIX, seconds=20.0, n=4096, deployment=None):
    rng = np.random.default_rng(seed)
    dep = np.random.default_rng(seed if deployment is None else deployment)
    params = T.data_params(DATA, dep)
    return T.schedule(mix, DATA, params, seconds, n, rng, dep)


def test_same_seed_same_schedule():
    a, b = _sched(2**33 + 5), _sched(2**33 + 5)
    for x, y in zip(a, b):
        np.testing.assert_array_equal(x, y)


def test_other_seed_same_work_other_order():
    a, b = _sched(1), _sched(2)
    assert a.due.size == b.due.size == 10_000
    np.testing.assert_allclose(np.sort(np.diff(a.due, prepend=0.0)),
                               np.sort(np.diff(b.due, prepend=0.0)),
                               rtol=0, atol=1e-9)
    assert not np.array_equal(a.peer, b.peer)
    assert 0 < a.due[0] and a.due[-1] < 20.0


def test_arrivals_poisson_rate():
    s = _sched(3)
    gaps = np.diff(s.due)
    assert abs(gaps.mean() * 500.0 - 1.0) < 0.01
    # exponential: the coefficient of variation is 1
    assert abs(gaps.std() / gaps.mean() - 1.0) < 0.05


@pytest.mark.parametrize("s_exp", [0.99, 0.5])
def test_zipf_shape(s_exp):
    n, k = 1000, 200_000
    u = T.quantiles(k)
    ranks = T.zipf_ranks(s_exp, n, u)
    counts = np.bincount(ranks, minlength=n).astype(float)
    w = 1.0 / np.arange(1, n + 1) ** s_exp
    expect = k * w / w.sum()
    top = slice(0, 50)
    np.testing.assert_allclose(counts[top], expect[top], rtol=0.01,
                               atol=1.0)
    # the log-log slope of frequency against rank is -s
    r = np.arange(1, 101)
    slope = np.polyfit(np.log(r), np.log(counts[:100]), 1)[0]
    assert abs(slope + s_exp) < 0.02


def test_hot_ranks_map_to_seeded_peers():
    a, b = _sched(10), _sched(11)
    hot_a = np.bincount(a.peer).argmax()
    hot_b = np.bincount(b.peer).argmax()
    assert np.bincount(a.peer).max() == np.bincount(b.peer).max()
    assert hot_a != hot_b


def test_fixed_deployment_same_updates_other_order():
    a, b = _sched(1, deployment=99), _sched(2, deployment=99)
    assert np.array_equal(np.sort(a.values), np.sort(b.values))
    assert np.array_equal(np.sort(a.peer), np.sort(b.peer))
    assert not np.array_equal(a.peer, b.peer)


def test_no_traffic_mix_is_empty():
    s = T.schedule({"kind": "none"}, DATA, {}, 10.0, 100,
                   np.random.default_rng(0), np.random.default_rng(1))
    assert s.due.size == 0


def test_uniform_keys_cover_peers():
    mix = dict(MIX, keys={"dist": "uniform"})
    s = _sched(4, mix=mix, n=100)
    assert np.bincount(s.peer, minlength=100).min() == 100


# SHA-256 of the cells' schedules (due, peer, values) at 51 s, as the
# generator drew them before it learned membership churn: a mix without
# `membership` draws exactly what it drew then
PINNED = {
    ("mainline-512k-majority.coldstart", 7):
        "ab6d7502306a3c8140cad4e8c3548090b54f72bc6abb663b74e15f0fdf15f709",
    ("mainline-512k-majority.coldstart", 5400000303):
        "ab6d7502306a3c8140cad4e8c3548090b54f72bc6abb663b74e15f0fdf15f709",
    ("mainline-64k-mean.steady", 7):
        "3571d3096f093250ad13e04dd8d96756422507550ae680295961c4768f375c81",
    ("mainline-64k-mean.steady", 5400000303):
        "117b75dc98c9481f8c83257d496f118616e0b0f3873c91dbdce493a0b49ae4d0",
}


def digest(s: T.Schedule) -> str:
    h = hashlib.sha256()
    for a in (s.due, s.peer, s.values):
        a = np.ascontiguousarray(a)
        h.update(str(a.dtype).encode())
        h.update(str(a.shape).encode())
        h.update(a.tobytes())
    return h.hexdigest()


@pytest.mark.parametrize("name,seed", sorted(PINNED))
def test_cell_schedules_are_pinned(name, seed):
    cell = spec.load_cell(name)
    cfg = cell.config
    st = T.streams(cfg, seed)
    s = T.schedule(cell.traffic, cfg["data"], st["params"], 51.0, cfg["n"],
                   st["traffic_rng"], st["deployment_rng"])
    assert digest(s) == PINNED[name, seed]
    assert not s.kind.any() and not s.addr.any()


# a churn mix for the tests alone: Weibull sessions of shape 0.5 and
# E[S] = 1 h (scale 1800 s), over 4096 peers for 600 s
CHURN = {"kind": "none", "membership": {"sessions": {
    "dist": "weibull", "shape": 0.5, "scale_s": 1800.0}}}
CHURN_N, CHURN_S = 4096, 600.0
LAW = T.Weibull(0.5, 1800.0)


def _ring(n=CHURN_N, seed=5):
    return np.sort(np.random.default_rng(seed).choice(
        2**32, n, replace=False).astype(np.uint64))


def _churn(seed, mix=CHURN, deployment=None, updates=False):
    addrs = _ring()
    churn = T.Churn(addrs, 32, seed, seed if deployment is None
                    else deployment)
    if updates:
        mix = dict(MIX, membership=mix["membership"], rate_per_s=5.0)
    rng = np.random.default_rng(seed)
    dep = np.random.default_rng(seed if deployment is None else deployment)
    s = T.schedule(mix, DATA, T.data_params(DATA, dep), CHURN_S, CHURN_N,
                   rng, dep, churn)
    return s, addrs


def test_churn_same_counts_every_seed_other_order():
    a, _ = _churn(1, deployment=7)
    b, _ = _churn(2**33 + 3, deployment=7)
    for kind in (T.JOIN, T.LEAVE):
        assert (a.kind == kind).sum() == (b.kind == kind).sum() > 0
    joins = a.kind == T.JOIN
    # the deployment's joiners, values and leavers; the seed's order
    assert np.array_equal(np.sort(a.addr[joins]),
                          np.sort(b.addr[b.kind == T.JOIN]))
    assert np.array_equal(np.sort(a.values[joins]),
                          np.sort(b.values[b.kind == T.JOIN]))
    # the start peers that leave are the deployment's; which joiners
    # leave follows the seed's pairing of sessions, their number does not
    a_old = a.addr[(a.kind == T.LEAVE) & np.isin(a.addr, _ring())]
    b_old = b.addr[(b.kind == T.LEAVE) & np.isin(b.addr, _ring())]
    assert np.array_equal(np.sort(a_old), np.sort(b_old))
    assert not np.array_equal(a.addr, b.addr)
    ga = np.sort(np.diff(a.due[joins], prepend=0.0))
    gb = np.sort(np.diff(b.due[b.kind == T.JOIN], prepend=0.0))
    np.testing.assert_allclose(ga, gb, rtol=0, atol=1e-9)


def test_churn_join_rate():
    s, _ = _churn(3)
    rate = (s.kind == T.JOIN).sum() / CHURN_S
    assert abs(rate / (CHURN_N / LAW.mean) - 1) < 0.01


def test_churn_session_median():
    churn = T.Churn(_ring(), 32, 4, 4)
    s, sessions = T.membership(CHURN["membership"], DATA,
                                    {"offset": 0.6}, CHURN_S, churn)
    joins = s.kind == T.JOIN
    assert sessions.size == joins.sum()
    assert abs(np.median(sessions) / (1800.0 * math.log(2) ** 2) - 1) < 0.02
    # a joiner leaves exactly where its session ends inside the window
    t_join = dict(zip(s.addr[joins].tolist(), s.due[joins]))
    s_join = dict(zip(s.addr[joins].tolist(),
                      sessions[np.argsort(np.argsort(s.due[joins]))]))
    left = {a: t for a, t, kd in zip(s.addr.tolist(), s.due, s.kind)
            if kd == T.LEAVE and a in t_join}
    assert left
    for a, t0 in t_join.items():
        ends = t0 + s_join[a]
        if ends < CHURN_S:
            assert left[a] == pytest.approx(ends)
        else:
            assert a not in left


def test_churn_pairing_keeps_the_session_quantiles():
    rng = np.random.default_rng(9)
    k = 683
    sessions = rng.permutation(LAW.ppf(T.quantiles(k)))
    remaining = CHURN_S - np.sort(rng.random(k)) * CHURN_S
    out = T._pair_sessions(remaining, sessions, 150, rng)
    assert np.array_equal(np.sort(out), np.sort(sessions))
    assert (out < remaining).sum() == 150
    assert abs(np.median(out) / (1800.0 * math.log(2) ** 2) - 1) < 0.02


def test_churn_mean_population():
    s, _ = _churn(5)
    step = np.where(s.kind == T.JOIN, 1, -1)
    pop = np.concatenate([[CHURN_N], CHURN_N + np.cumsum(step)])
    t = np.concatenate([[0.0], s.due, [CHURN_S]])
    mean = (pop * np.diff(t)).sum() / CHURN_S
    assert abs(mean / CHURN_N - 1) < 0.01


def test_churn_addresses_fresh_and_leaves_live():
    s, addrs = _churn(6, updates=True)
    assert (s.kind == T.UPDATE).sum() == 3000
    assert np.all(np.diff(s.due) >= 0)
    joined = s.addr[s.kind == T.JOIN]
    assert np.unique(joined).size == joined.size
    assert not np.isin(joined, addrs).any()
    assert (joined < 2**32).all()
    live = set(addrs.tolist())
    ever = set(live)
    for kind, a in zip(s.kind, s.addr.tolist()):
        if kind == T.JOIN:
            assert a not in ever
            live.add(a)
            ever.add(a)
        elif kind == T.LEAVE:
            assert a in live
            live.remove(a)
    # updates keep their keys over the peers live at the start
    up = s.kind == T.UPDATE
    assert (s.peer[up] >= 0).all() and (s.peer[~up] == -1).all()


def test_churn_warm_up_address_never_drawn_again():
    addrs = _ring()
    churn = T.Churn(addrs, 32, 1, 1)
    warm = churn.fresh(1)
    rng = np.random.default_rng(1)
    s = T.schedule(CHURN, DATA, {"offset": 0.6}, CHURN_S, CHURN_N, rng,
                   rng, churn)
    assert not np.isin(warm, s.addr).any()


def test_mix_with_membership_needs_the_addresses():
    with pytest.raises(ValueError):
        T.schedule(CHURN, DATA, {}, 10.0, 100, np.random.default_rng(0),
                   np.random.default_rng(1))
