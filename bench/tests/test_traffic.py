"""The one generator: deterministic for a seed, the same work for every
seed, and the zipf shape YCSB asks for."""
import numpy as np
import pytest

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
