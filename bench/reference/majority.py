"""Plain reference of the majority decision (Wolff & Schuster's local
majority vote, as the DHT paper runs it): is the fraction of peers
voting 1 at least one half?

Data are exact 0/1 votes; a peer's payload is (ones, count). The
decision of a payload is 1 iff 2 * ones - count >= 0.
"""
import numpy as np

DATA_WIDTH = 1


def quantize(values, problem):
    """Raw votes -> the (k, 1) int64 data rows the decision is over."""
    v = np.asarray(values).astype(np.int64).reshape(-1, 1)
    if not np.isin(v, (0, 1)).all():
        raise ValueError("majority votes are 0 or 1")
    return v


def margin(sums, count, problem):
    """Signed margin of payloads with vote sums `sums` (..., 1)."""
    return 2 * np.asarray(sums, np.int64)[..., 0] - np.asarray(count,
                                                                 np.int64)
