"""Plain reference of the mean monitor (Wolff, "Local Thresholding in
General Network Graphs", section 3): is the network-wide mean of a
scalar stream at least tau?

Values are fixed point with `scale` steps per unit, q = round(x *
scale) (round half to even), and the decision is exact in integers:
mean >= tau  <=>  sum(q) - round(tau * scale) * count >= 0.
"""
import numpy as np

DATA_WIDTH = 1


def quantize(values, problem):
    """Raw values -> the (k, 1) int64 fixed-point data rows."""
    x = np.asarray(values, np.float64).reshape(-1, 1)
    return np.rint(x * int(problem["scale"])).astype(np.int64)


def margin(sums, count, problem):
    """Signed margin of payloads with value sums `sums` (..., 1)."""
    t = int(np.rint(float(problem["tau"]) * int(problem["scale"])))
    return (np.asarray(sums, np.int64)[..., 0]
            - t * np.asarray(count, np.int64))
