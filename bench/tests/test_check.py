"""The comparison under membership churn, on hand-built pump records:
each problem the check has to see, fed straight into `compare()`."""
from types import SimpleNamespace
from typing import NamedTuple

import numpy as np

from harness import check, spec

MAJORITY = spec.reference_rule("majority")
PROBLEM = {"name": "majority"}
ADDRS = np.array([10, 20, 30], np.uint64)
VOTES = np.array([1, 0, 0])            # one vote of three: decision 0


class Tr(NamedTuple):
    t: int
    peers: frozenset
    output: int


def pumps(*records):
    """Pump records from (flush, events, settled, transitions_upto)."""
    p = SimpleNamespace(flush=[], events=[], settled=[],
                        transitions_upto=[])
    for flush, events, settled, upto in records:
        a, v = flush
        p.flush.append((np.asarray(a, np.uint64), np.asarray(v)))
        p.events.append(list(events))
        p.settled.append(settled)
        p.transitions_upto.append(upto)
    return p


NO_FLUSH = ([], [])


def final(addrs, data, outputs, settled=True):
    return {"addrs": np.asarray(addrs, np.uint64),
            "data": np.asarray(data, np.int64).reshape(-1, 1),
            "outputs": np.asarray(outputs, np.int64),
            "dropped": 0, "conserved": True, "settled": settled,
            "t": 8, "cycles_asked": 8}


def numbers(p, transitions, fin):
    return {name: value for name, value, _ in check.compare(
        MAJORITY, PROBLEM, VOTES, ADDRS, p, transitions, fin)}


def test_sound_records_check_clean():
    p = pumps((NO_FLUSH, [], True, 1))
    got = numbers(p, [Tr(8, frozenset([10, 20, 30]), 0)],
                  final(ADDRS, VOTES, [0, 0, 0]))
    assert set(got.values()) == {0}


def test_join_that_flips_the_truth():
    # a joiner voting 1 makes two of four: the decision turns to 1
    p = pumps((NO_FLUSH, [("join", 25, 1)], True, 1))
    fin = final([10, 20, 25, 30], [1, 0, 1, 0], [1, 1, 1, 1])
    sound = numbers(p, [Tr(8, frozenset([10, 20, 25, 30]), 1)], fin)
    assert set(sound.values()) == {0}
    # outputs still on the old decision are off the new truth
    stale = numbers(pumps((NO_FLUSH, [("join", 25, 1)], True, 2)),
                    [Tr(8, frozenset([10, 20, 30]), 0),
                     Tr(8, frozenset([25]), 0)],
                    final([10, 20, 25, 30], [1, 0, 1, 0], [0, 0, 0, 0]))
    assert stale["settled_off_truth"] == 4
    assert stale["readback_mismatch"] == 0


def test_leave_drops_a_pending_update_on_both_sides():
    # peer 20 gets an update, then leaves before the pump that flushes
    # it: the update is dropped, and the decision is over 10 and 30
    p = pumps(((np.array([20]), np.array([1])), [("leave", 20, None)],
               True, 1))
    fin = final([10, 30], [1, 0], [1, 1])
    got = numbers(p, [Tr(8, frozenset([10, 30]), 1)], fin)
    assert set(got.values()) == {0}
    # an engine that applied it to the next live peer disagrees
    wrong = numbers(p, [Tr(8, frozenset([10, 30]), 1)],
                    final([10, 30], [1, 1], [1, 1]))
    assert wrong["data_mismatch"] == 1


def test_address_missing_on_the_engine_side():
    p = pumps((NO_FLUSH, [("join", 25, 0)], True, 1))
    trs = [Tr(8, frozenset([10, 20, 25, 30]), 0)]
    got = numbers(p, trs, final([10, 20, 30], [1, 0, 0], [0, 0, 0]))
    assert got["data_mismatch"] == 1
    # and one live on the engine's side alone counts the same way
    kept = numbers(pumps((NO_FLUSH, [("leave", 30, None)], True, 1)),
                   [Tr(8, frozenset([10, 20]), 0)],
                   final([10, 20, 30], [1, 0, 0], [0, 0, 0]))
    assert kept["data_mismatch"] == 1


def test_joiner_never_published():
    p = pumps((NO_FLUSH, [("join", 25, 0)], True, 1),
              (NO_FLUSH, [], True, 1))
    got = numbers(p, [Tr(8, frozenset([10, 20, 30]), 0)],
                  final([10, 20, 25, 30], [1, 0, 0, 0], [0, 0, 0, 0]))
    # the joiner has no output yet at both settled pumps
    assert got["settled_off_truth"] == 2
    assert got["readback_mismatch"] == 1
    assert got["data_mismatch"] == 0
