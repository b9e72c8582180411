"""Per-update decision latency on the served path, censored failures
included.

An update's decision latency runs from its due time to the return of
the first pump that started after the update was submitted and ended
with the server settled (every peer's output on the truth of the
current data). A join or a leave is an operation in the same way: from
its due time to the first pump that started after the call returned
and ended settled. An operation that no pump settles before the drain
cap is a failure; it enters the percentiles at its censored age,
measured to the end of the drain, so a stall cannot hide by failing.
"""
from __future__ import annotations

from typing import Dict

import numpy as np


def decision_latencies(due: np.ndarray, submit: np.ndarray,
                       pump_start: np.ndarray, pump_end: np.ndarray,
                       pump_settled: np.ndarray, drain_end: float):
    """(latency_s (N,), failed (N,) bool) for updates due at `due` and
    submitted at `submit`, given every pump's start, end and settled
    flag in order. All times on one clock, in seconds."""
    due = np.asarray(due, np.float64)
    submit = np.asarray(submit, np.float64)
    idx = np.flatnonzero(np.asarray(pump_settled, bool))
    starts = np.asarray(pump_start, np.float64)[idx]
    ends = np.asarray(pump_end, np.float64)[idx]
    j = np.searchsorted(starts, submit, side="left")
    failed = j >= idx.size
    done = ends[np.minimum(j, max(idx.size - 1, 0))] if idx.size else \
        np.zeros_like(due)
    lat = np.where(failed, drain_end - due, done - due)
    return lat, failed


def percentiles_ms(lat_s: np.ndarray) -> Dict[str, float]:
    """p50 and p95 in milliseconds (numpy's linear interpolation)."""
    if lat_s.size == 0:
        return {}
    return {"p50": float(np.percentile(lat_s, 50) * 1e3),
            "p95": float(np.percentile(lat_s, 95) * 1e3)}
