"""The comparison that decides `correct`.

What the served path produced is held against the plain reference
(bench/reference/<problem>.py), which shares no code and no state with
the program: it quantizes the raw values the harness generated and
submitted, coalesces each flush last-writer-wins by itself, and
decides by its own rule. Every number compared is a count of
disagreements with the limit 0:

  data_mismatch        peers whose device data plane differs from the
                       reference's replay of the initial values and of
                       every flush (ingestion, coalescing, quantization)
  settled_off_truth    over every pump that ended with the server
                       settled: peers whose published output (the
                       subscriber's stream of transitions) differs from
                       the reference decision of the data at that pump
                       (flush react, cycle body, wheel kernels, publish)
  readback_mismatch    peers whose last published output differs from
                       the final `outputs()` readback (publish)
  unsettled_at_end     1 if the drain ended with the server unsettled:
                       an answer the window left open never came (the
                       decision of an update, or the end of a cold
                       start's storm, whose settled outputs are then
                       held to the reference by `settled_off_truth`)
  dropped              wheel rows lost to arena overflow (the
                       engine's `dropped`)
  conservation_broken  1 if the engine's `check_conservation()` failed
  cycles_missing       cycles the pumps asked for and the engine did
                       not advance

Only public entry points are read: `data()`, `outputs()`, `dropped`,
`check_conservation()`, `t` and the published transitions.
"""
from __future__ import annotations

from typing import Dict, List, Sequence, Tuple

import numpy as np


class ReferenceData:
    """The reference's own data plane: quantized values, replayed flush
    by flush, last writer wins."""

    def __init__(self, rule, problem: Dict, values: np.ndarray):
        self.rule, self.problem = rule, problem
        self.data = rule.quantize(values, problem)
        self.sums = self.data.sum(0)
        self.count = self.data.shape[0]

    def apply(self, peers: np.ndarray, values: np.ndarray) -> None:
        """One flush: the submits of one pump, in submit order."""
        if len(peers) == 0:
            return
        peers = np.asarray(peers, np.int64)
        rev = peers[::-1]
        _, first = np.unique(rev, return_index=True)
        keep = len(peers) - 1 - first           # last submit per peer
        p = peers[keep]
        new = self.rule.quantize(np.asarray(values)[keep], self.problem)
        self.sums = self.sums + (new - self.data[p]).sum(0)
        self.data[p] = new

    def truth(self) -> int:
        return int(self.rule.margin(self.sums, self.count, self.problem)
                   >= 0)


def compare(rule, problem: Dict, values0: np.ndarray, addrs: np.ndarray,
            flushes: Sequence[Tuple[np.ndarray, np.ndarray]],
            settled: Sequence[bool], transitions_upto: Sequence[int],
            transitions: List, final: Dict) -> List[Tuple[str, float, float]]:
    """Numbers compared, each (name, value, limit).

    `flushes[i]` and `settled[i]` belong to pump i; `transitions_upto[i]`
    is how many published transitions existed when pump i returned.
    `final` holds the end readback: data, outputs, dropped, conserved,
    settled, t and cycles_asked."""
    ref = ReferenceData(rule, problem, values0)
    n = addrs.size
    mirror = np.full(n, -1, np.int64)
    off = 0
    k = 0
    for i, (peers, vals) in enumerate(flushes):
        ref.apply(peers, vals)
        while k < transitions_upto[i]:
            tr = transitions[k]
            a = np.fromiter(tr.peers, np.uint64, len(tr.peers))
            mirror[np.searchsorted(addrs, a)] = tr.output
            k += 1
        if settled[i]:
            off += int((mirror != ref.truth()).sum())
    data_bad = int((np.asarray(final["data"], np.int64)
                    != ref.data).any(axis=1).sum())
    outs = np.asarray(final["outputs"], np.int64)
    return [
        ("data_mismatch", data_bad, 0),
        ("settled_off_truth", off, 0),
        ("readback_mismatch", int((mirror != outs).sum()), 0),
        ("unsettled_at_end", int(not final["settled"]), 0),
        ("dropped", int(final["dropped"]), 0),
        ("conservation_broken", int(not final["conserved"]), 0),
        ("cycles_missing", abs(int(final["cycles_asked"] - final["t"])), 0),
    ]
