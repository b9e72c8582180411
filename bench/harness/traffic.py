"""The one traffic generator: every mix is a data file of its parameters.

A mix (bench/traffic/<name>.json) has a `kind`:

  "none"       no client updates; the window serves whatever the
               engine is doing (a freshly installed monitor's storm).
  "open_loop"  independent clients: `rate_per_s` arrivals a second,
               due by wall clock whatever the server does. Keys follow
               `keys` ("zipf" with exponent `s`, or "uniform") over the
               live peers, hot ranks mapped to peers from the seed;
               values follow the configuration's `data` distribution.

Every seed gets the same amount of work: the same number of arrivals
and the same multiset of inter-arrival gaps and key ranks (quantiles of
their distributions), in a seeded order. Where a configuration states
a `deployment_seed`, the ring, the data, which peers are hot and the
multiset of update values are that deployment's, and `--seed` changes
only the order of the arrivals.

A mix may also carry membership churn, beside its updates:

  "membership": {"sessions": {"dist": "weibull", "shape": k,
                              "scale_s": lambda}}

Session lengths follow a Weibull law (Stutzbach & Rejaie,
"Understanding Churn in Peer-to-Peer Networks", IMC 2006), and the
population is stationary, an equilibrium renewal process around the n
peers live at the window's start, with E[S] = lambda * Gamma(1 + 1/k):

  joins   fresh peers arrive at rate n / E[S] (Poisson gaps), each with
          its own session; a joiner leaves where its session ends
          inside the window. A joiner's address is uniform over the
          ring's 2^d ids, never one that is live or was ever used, and
          its value comes from the configuration's `data` law.
  leaves  peers live at the start leave at times drawn from the
          equilibrium residual life G(t) = (1/E[S]) int_0^t (1 - F(s))
          ds, restricted to the window; each leaves at most once, the
          leavers chosen without replacement.

The same work for every seed holds here too: the numbers of joins and
of leaves, the multiset of join gaps, of sessions and of the start
peers' leave times are quantiles, and `--seed` orders them (which
joiner draws which session is ordered by the seed, then swapped until
the number of joiners that leave in the window is its expected count).
Which start peers leave and the joiners' addresses and values are the
deployment's. Every membership draw comes from generators of its own
(`Churn`), so a mix without `membership` draws what it always drew.

Updates keep their zipf keys over the peers live at the window's start.
An update whose peer has left by the pump that flushes it is dropped,
by the server (`stale_dropped`) and by the reference alike; joiners
take no client updates in the window.
"""
from __future__ import annotations

import math
from typing import Dict, NamedTuple

import numpy as np

# the kind of a schedule entry, and the server entry point it goes to
UPDATE, JOIN, LEAVE = 0, 1, 2   # submit, join(addr, value), leave_addr(addr)
# the tag that separates the membership draws from every other stream
MEMBERSHIP_STREAM = 0x6D656D62


class Schedule(NamedTuple):
    due: np.ndarray      # (N,) arrival offsets from the window start, s
    peer: np.ndarray     # (N,) update: index into the peers live at the
    #                      window's start (ascending addrs); else -1
    values: np.ndarray   # (N,) or (N, D) raw values: an update's, or a
    #                      joiner's first value (a leave's is 0)
    kind: np.ndarray     # (N,) int8 UPDATE, JOIN or LEAVE
    addr: np.ndarray     # (N,) uint64 the joiner's or leaver's address


def updates_only(due, peer, values) -> Schedule:
    """A schedule of client updates alone."""
    return Schedule(due, peer, values, np.zeros(due.size, np.int8),
                    np.zeros(due.size, np.uint64))


def streams(cfg: Dict, seed: int) -> Dict:
    """Every generator and seed of a run, drawn from `--seed` and the
    configuration's `deployment_seed` (bench/harness/drive.py builds
    the ring and the engine from them): the ring's and the engine's
    seeds, the value stream's parameters, the initial values, and the
    generators the window's schedule draws from."""
    dep_seed = cfg.get("deployment_seed", seed)
    dep = np.random.default_rng(dep_seed)
    ring_seed, engine_seed, data_seed, _ = (
        int(s) for s in dep.integers(0, 2**31 - 1, 4))
    traffic_seed = int(np.random.default_rng(seed).integers(
        0, 2**31 - 1, 4)[3])
    data_rng = np.random.default_rng(data_seed)
    params = data_params(cfg["data"], data_rng)
    values0 = draw_data(cfg["data"], data_rng, cfg["n"], params)
    return {"ring_seed": ring_seed, "engine_seed": engine_seed,
            "params": params, "values0": values0,
            "traffic_rng": np.random.default_rng(traffic_seed),
            "deployment_rng": data_rng, "deployment_seed": dep_seed}


class Churn:
    """The address book and the generators of a mix's membership draws:
    `order` from `--seed`, `deployment` from the deployment's seed, both
    apart from every other stream."""

    def __init__(self, addrs: np.ndarray, d: int, seed: int,
                 deployment_seed: int):
        self.addrs = np.asarray(addrs, np.uint64)   # live at the start
        self.used = np.sort(self.addrs)             # ever live
        self.d = int(d)
        self.order = np.random.default_rng([seed, MEMBERSHIP_STREAM])
        self.deployment = np.random.default_rng(
            [deployment_seed, MEMBERSHIP_STREAM])

    def fresh(self, k: int) -> np.ndarray:
        """k addresses uniform over the ring's ids, none live or ever
        used, in the deployment generator's order; they count as used
        from now on."""
        out = np.zeros(0, np.uint64)
        while out.size < k:
            cand = self.deployment.integers(0, 2**self.d, 2 * (k - out.size)
                                            + 8, dtype=np.uint64)
            cand = cand[~np.isin(cand, self.used)]
            _, first = np.unique(cand, return_index=True)
            cand = cand[np.sort(first)][: k - out.size]
            out = np.concatenate([out, cand])
            self.used = np.union1d(self.used, cand)
        return out


def draw_data(data: Dict, rng: np.random.Generator, k: int,
              params: Dict) -> np.ndarray:
    """k raw per-peer values from a configuration's `data` entry."""
    if data["dist"] == "bernoulli":
        return (rng.random(k) < data["p"]).astype(np.int64)
    if data["dist"] == "normal":
        return rng.normal(params["offset"], data["sd"], k)
    raise ValueError(f"unknown data distribution {data['dist']!r}")


def data_params(data: Dict, rng: np.random.Generator) -> Dict:
    """Per-run parameters of the value stream, drawn once from the seed
    (the normal stream's side of the threshold)."""
    if data["dist"] == "normal":
        return {"offset": float(rng.choice(data["offsets"]))}
    return {}


def quantiles(k: int) -> np.ndarray:
    """k evenly spaced probabilities, the midpoints of k equal bins."""
    return (np.arange(k) + 0.5) / k


def zipf_ranks(s: float, n: int, u: np.ndarray) -> np.ndarray:
    """Ranks 0..n-1 at probabilities `u` of a zipf(s) law over n items
    (YCSB's request distribution; rank 0 is the hottest)."""
    w = 1.0 / np.arange(1, n + 1, dtype=np.float64) ** s
    cdf = np.cumsum(w)
    cdf /= cdf[-1]
    return np.minimum(np.searchsorted(cdf, u, side="right"), n - 1)


def poisson_due(rate: float, seconds: float, k: int,
                rng: np.random.Generator) -> np.ndarray:
    """k arrival offsets inside [0, seconds): the k quantiles of
    exponential gaps at `rate`, in the order of `rng`, scaled so the
    last falls half a gap before the end."""
    u = quantiles(k)
    gaps = rng.permutation(-np.log1p(-u) / rate)
    due = np.cumsum(gaps)
    due *= seconds / max(due[-1], 1e-12) * (1 - 0.5 / k)
    return due


def schedule(traffic: Dict, data: Dict, params: Dict, seconds: float,
             n_live: int, rng: np.random.Generator,
             deployment: np.random.Generator,
             churn: "Churn | None" = None) -> Schedule:
    """The arrivals of one measured window of `seconds` seconds, in due
    order. Which peers are hot and the multiset of update values come
    from the `deployment` generator; the order of gaps, key ranks and
    values from the run's `rng`. A mix with `membership` adds its joins
    and leaves, drawn from `churn` alone."""
    ups = _updates(traffic, data, params, seconds, n_live, rng, deployment)
    if "membership" not in traffic:
        return ups
    if churn is None:
        raise ValueError("a mix with membership needs the live addresses")
    return merge(ups, membership(traffic["membership"], data, params,
                                 seconds, churn)[0])


def _updates(traffic: Dict, data: Dict, params: Dict, seconds: float,
             n_live: int, rng: np.random.Generator,
             deployment: np.random.Generator) -> Schedule:
    if traffic["kind"] == "none":
        return updates_only(np.zeros(0), np.zeros(0, np.int64), np.zeros(0))
    if traffic["kind"] != "open_loop":
        raise ValueError(f"unknown traffic kind {traffic['kind']!r}")
    rate = float(traffic["rate_per_s"])
    k = max(int(round(rate * seconds)), 1)
    u = quantiles(k)
    due = poisson_due(rate, seconds, k, rng)
    keys = traffic["keys"]
    if keys["dist"] == "zipf":
        ranks = rng.permutation(zipf_ranks(keys["s"], n_live, u))
    elif keys["dist"] == "uniform":
        ranks = rng.permutation(np.floor(u * n_live).astype(np.int64))
    else:
        raise ValueError(f"unknown key distribution {keys['dist']!r}")
    hot = deployment.permutation(n_live)   # rank -> peer
    values = rng.permutation(draw_data(data, deployment, k, params))
    return updates_only(due, hot[ranks], values)


def merge(a: Schedule, b: Schedule) -> Schedule:
    """The entries of both schedules in due order (`a` first on a tie)."""
    if a.due.size == 0:
        return b
    order = np.argsort(np.concatenate([a.due, b.due]), kind="stable")
    return Schedule(*(np.concatenate([x, y])[order]
                      for x, y in zip(a, b)))


class Weibull(NamedTuple):
    """The session law: F(s) = 1 - exp(-(s / scale)^shape)."""
    shape: float
    scale: float

    @property
    def mean(self) -> float:
        return self.scale * math.gamma(1.0 + 1.0 / self.shape)

    def cdf(self, s):
        return -np.expm1(-(np.asarray(s, np.float64) / self.scale)
                         ** self.shape)

    def ppf(self, u):
        return self.scale * (-np.log1p(-np.asarray(u, np.float64))) ** (
            1.0 / self.shape)

    def residual(self, seconds: float, points: int = 1 << 16):
        """(t, G(t)) on a grid over [0, seconds]: the equilibrium
        residual life's CDF, by the trapezoid rule."""
        t = np.linspace(0.0, seconds, points)
        surv = 1.0 - self.cdf(t)
        g = np.concatenate([[0.0], np.cumsum(
            (surv[1:] + surv[:-1]) * 0.5 * np.diff(t))]) / self.mean
        return t, g


def session_law(spec: Dict) -> Weibull:
    if spec["dist"] != "weibull":
        raise ValueError(f"unknown session distribution {spec['dist']!r}")
    return Weibull(float(spec["shape"]), float(spec["scale_s"]))


def membership(spec: Dict, data: Dict, params: Dict, seconds: float,
               churn: Churn):
    """(the joins and leaves of one window, in due order; the session
    each joiner drew, in join order): module docstring."""
    law = session_law(spec["sessions"])
    n = churn.addrs.size
    t, g = law.residual(seconds)
    # start peers that leave: quantiles of G restricted to the window
    n_old = int(round(n * g[-1]))
    old_due = churn.order.permutation(
        np.interp(quantiles(n_old) * g[-1], g, t)) if n_old else \
        np.zeros(0)
    leavers = churn.addrs[churn.deployment.choice(n, n_old, replace=False)]
    # joiners: Poisson at n / E[S], each with its own session
    n_new = int(round(n * seconds / law.mean))
    join_due = poisson_due(n / law.mean, seconds, n_new, churn.order) \
        if n_new else np.zeros(0)
    addrs = churn.order.permutation(churn.fresh(n_new))
    values = churn.order.permutation(
        draw_data(data, churn.deployment, n_new, params))
    sessions = churn.order.permutation(law.ppf(quantiles(n_new)))
    # joiners that leave: n / E[S] * int_0^T F = n T / E[S] - n G(T)
    mean_f = 1.0 - law.mean * g[-1] / seconds
    sessions = _pair_sessions(seconds - join_due, sessions,
                              int(round(n_new * mean_f)), churn.order)
    gone = join_due + sessions < seconds
    due = np.concatenate([join_due, join_due[gone] + sessions[gone],
                          old_due])
    kind = np.concatenate([np.full(n_new, JOIN, np.int8),
                           np.full(int(gone.sum()) + n_old, LEAVE, np.int8)])
    addr = np.concatenate([addrs, addrs[gone], leavers]).astype(np.uint64)
    vals = np.concatenate([values, np.zeros(
        (int(gone.sum()) + n_old,) + values.shape[1:], values.dtype)])
    order = np.argsort(due, kind="stable")
    return Schedule(due[order], np.full(due.size, -1, np.int64),
                    vals[order], kind[order], addr[order]), sessions


def _pair_sessions(remaining: np.ndarray, sessions: np.ndarray,
                   target: int, rng: np.random.Generator) -> np.ndarray:
    """`sessions` (one per joiner, in the seed's order) with pairs of
    joiners' sessions swapped, in the seed's order, until exactly
    `target` sessions end inside the joiners' `remaining` windows. Only
    swaps that move the count one step toward the target are kept."""
    s = sessions.copy()
    k = s.size
    count = int((s < remaining).sum())
    tries = 0
    while count != target:
        step = 1 if target > count else -1
        a, b = rng.integers(0, k, 2)
        before = int(s[a] < remaining[a]) + int(s[b] < remaining[b])
        after = int(s[b] < remaining[a]) + int(s[a] < remaining[b])
        if after - before == step:
            s[a], s[b] = s[b], s[a]
            count += step
        tries += 1
        if tries > 1000 * k:
            raise ValueError(f"no pairing of {k} sessions ends {target} "
                             "inside the window")
    return s
