"""Per-update decision latency, censored failures included."""
import numpy as np

from harness.latency import decision_latencies, percentiles_ms


def test_first_settled_pump_after_submit():
    # pumps: [0,1) settled, [1,2) not, [2,3) settled, [3,4) not
    start = [0.0, 1.0, 2.0, 3.0]
    end = [1.0, 2.0, 3.0, 4.0]
    settled = [True, False, True, False]
    due = np.array([-0.5, 0.2, 0.9, 2.5])
    submit = np.array([-0.4, 1.0, 1.5, 3.1])
    lat, failed = decision_latencies(due, submit, start, end, settled,
                                     drain_end=10.0)
    # update 0 is flushed by pump 0 (settled): 1.0 - (-0.5)
    # update 1, submitted at 1.0, is flushed by pump 1 (start 1.0 >= 1.0),
    # which ends unsettled; pump 2 settles at 3.0: 3.0 - 0.2
    # update 2 likewise settles at 3.0
    # update 3 is never settled: censored at the drain end 10.0
    np.testing.assert_allclose(lat, [1.5, 2.8, 2.1, 7.5])
    assert failed.tolist() == [False, False, False, True]


def test_censored_failures_enter_the_tail():
    start = np.arange(10.0)
    end = start + 1.0
    settled = np.zeros(10, bool)
    settled[:5] = True
    due = np.arange(10.0) - 0.5
    submit = due + 0.1
    lat, failed = decision_latencies(due, submit, start, end, settled,
                                     drain_end=30.0)
    assert failed.sum() == 5
    p = percentiles_ms(lat)
    assert p["p95"] > 20_000          # the stalled updates set the tail
    assert p["p50"] == np.percentile(lat, 50) * 1e3


def test_no_settled_pump_fails_everything():
    lat, failed = decision_latencies([0.0, 1.0], [0.0, 1.0], [0.5, 1.5],
                                     [1.5, 2.5], [False, False], 5.0)
    assert failed.all()
    np.testing.assert_allclose(lat, [5.0, 4.0])


def test_empty():
    assert percentiles_ms(np.zeros(0)) == {}
