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
"""
from __future__ import annotations

from typing import Dict, NamedTuple

import numpy as np


class Schedule(NamedTuple):
    due: np.ndarray      # (N,) arrival offsets from the window start, s
    peer: np.ndarray     # (N,) index into the live peers (ascending addrs)
    values: np.ndarray   # (N,) or (N, D) raw values


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


def schedule(traffic: Dict, data: Dict, params: Dict, seconds: float,
             n_live: int, rng: np.random.Generator,
             deployment: np.random.Generator) -> Schedule:
    """The arrivals of one measured window of `seconds` seconds. Which
    peers are hot and the multiset of update values come from the
    `deployment` generator; the order of gaps, key ranks and values
    from the run's `rng`."""
    if traffic["kind"] == "none":
        return Schedule(np.zeros(0), np.zeros(0, np.int64), np.zeros(0))
    if traffic["kind"] != "open_loop":
        raise ValueError(f"unknown traffic kind {traffic['kind']!r}")
    rate = float(traffic["rate_per_s"])
    k = max(int(round(rate * seconds)), 1)
    u = quantiles(k)
    gaps = rng.permutation(-np.log1p(-u) / rate)
    due = np.cumsum(gaps)
    due *= seconds / max(due[-1], 1e-12) * (1 - 0.5 / k)
    keys = traffic["keys"]
    if keys["dist"] == "zipf":
        ranks = rng.permutation(zipf_ranks(keys["s"], n_live, u))
    elif keys["dist"] == "uniform":
        ranks = rng.permutation(np.floor(u * n_live).astype(np.int64))
    else:
        raise ValueError(f"unknown key distribution {keys['dist']!r}")
    hot = deployment.permutation(n_live)   # rank -> peer
    values = rng.permutation(draw_data(data, deployment, k, params))
    return Schedule(due, hot[ranks], values)
