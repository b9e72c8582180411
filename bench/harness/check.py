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

Under membership churn everything is keyed by ring address. The
reference holds the live addresses with their quantized rows; at each
pump it applies the joins (with the joiner's quantized value) and the
leaves called since the pump before, in call order, then the pump's
flush last-writer-wins over the addresses live at that pump (an update
to a departed address is dropped, as the server drops it), and decides
over the live count. The mirror of published outputs follows the same
live set: a joiner starts at "no output yet" (-1), a departed address
leaves it. `settled_off_truth` counts live addresses; `data_mismatch`
and `readback_mismatch` compare over the final live set, and
`data_mismatch` also counts every address live on one side only (the
engine's side is `engine.ring.addrs`, the set the server itself
resolves addresses against).

Only public entry points are read: `data()`, `outputs()`, `dropped`,
`check_conservation()`, `t`, `ring.addrs` and the published
transitions.
"""
from __future__ import annotations

from typing import Dict, List, Tuple

import numpy as np


class ReferenceData:
    """The reference's own data plane: the live addresses, ascending,
    with their quantized rows, replayed event by event and flush by
    flush, last writer wins."""

    def __init__(self, rule, problem: Dict, addrs: np.ndarray,
                 values: np.ndarray):
        self.rule, self.problem = rule, problem
        self.addrs = np.asarray(addrs, np.uint64)
        self.data = rule.quantize(values, problem)
        self.sums = self.data.sum(0)

    @property
    def count(self) -> int:
        return self.addrs.size

    def find(self, addrs) -> Tuple[np.ndarray, np.ndarray]:
        """(index, live) of each address in the live set."""
        a = np.asarray(addrs, np.uint64)
        idx = np.searchsorted(self.addrs, a)
        live = (idx < self.count) & (
            self.addrs[np.minimum(idx, max(self.count - 1, 0))] == a)
        return idx, live

    def join(self, addr: int, value) -> int:
        """A peer joins at `addr` with raw `value`; returns its index."""
        k, live = self.find([addr])
        if live[0]:
            raise ValueError(f"address {addr} joined twice")
        row = self.rule.quantize(np.asarray([value]), self.problem)
        self.addrs = np.insert(self.addrs, k[0], np.uint64(addr))
        self.data = np.insert(self.data, k[0], row, axis=0)
        self.sums = self.sums + row.sum(0)
        return int(k[0])

    def leave(self, addr: int) -> int:
        """The peer at `addr` departs; returns the index it held."""
        k, live = self.find([addr])
        if not live[0]:
            raise ValueError(f"no live peer at address {addr}")
        self.sums = self.sums - self.data[k[0]]
        self.addrs = np.delete(self.addrs, k[0])
        self.data = np.delete(self.data, k[0], axis=0)
        return int(k[0])

    def apply(self, addrs: np.ndarray, values: np.ndarray) -> None:
        """One flush: the submits of one pump, in submit order."""
        if len(addrs) == 0:
            return
        addrs = np.asarray(addrs, np.uint64)
        rev = addrs[::-1]
        _, first = np.unique(rev, return_index=True)
        keep = len(addrs) - 1 - first           # last submit per address
        idx, live = self.find(addrs[keep])
        keep, p = keep[live], idx[live]         # departed: dropped
        if keep.size == 0:
            return
        new = self.rule.quantize(np.asarray(values)[keep], self.problem)
        self.sums = self.sums + (new - self.data[p]).sum(0)
        self.data[p] = new

    def truth(self) -> int:
        return int(self.rule.margin(self.sums, self.count, self.problem)
                   >= 0)


def compare(rule, problem: Dict, values0: np.ndarray, addrs: np.ndarray,
            pumps, transitions: List,
            final: Dict) -> List[Tuple[str, float, float]]:
    """Numbers compared, each (name, value, limit).

    `values0` are the raw initial values of the peers at `addrs`
    (ascending). `pumps` holds one entry per pump, in order, in the
    lists `flush` ((addresses, raw values) submitted, in submit order),
    `events` (("join", addr, value) or ("leave", addr, _) called since
    the pump before, in call order), `settled`, and `transitions_upto`
    (how many published transitions existed when the pump returned).
    `final` holds the end readback: addrs (the engine's live ring),
    data, outputs, dropped, conserved, settled, t and cycles_asked."""
    ref = ReferenceData(rule, problem, addrs, values0)
    mirror = np.full(ref.count, -1, np.int64)
    off = 0
    k = 0
    for i, (flush, events) in enumerate(zip(pumps.flush, pumps.events)):
        for kind, addr, value in events:
            if kind == "join":
                mirror = np.insert(mirror, ref.join(addr, value), -1)
            else:
                mirror = np.delete(mirror, ref.leave(addr))
        ref.apply(*flush)
        while k < pumps.transitions_upto[i]:
            tr = transitions[k]
            a = np.fromiter(tr.peers, np.uint64, len(tr.peers))
            idx, live = ref.find(a)
            mirror[idx[live]] = tr.output
            k += 1
        if pumps.settled[i]:
            off += int((mirror != ref.truth()).sum())
    eng_addrs = np.asarray(final["addrs"], np.uint64)
    common, ie, ir = np.intersect1d(eng_addrs, ref.addrs,
                                    assume_unique=True, return_indices=True)
    one_side = eng_addrs.size + ref.count - 2 * common.size
    data = np.asarray(final["data"], np.int64)
    data_bad = int((data[ie] != ref.data[ir]).any(axis=1).sum()) + one_side
    # the final readback over the reference's live set; an address the
    # engine no longer holds has no readback and counts
    outs = np.full(ref.count, -1, np.int64)
    outs[ir] = np.asarray(final["outputs"], np.int64)[ie]
    return [
        ("data_mismatch", data_bad, 0),
        ("settled_off_truth", off, 0),
        ("readback_mismatch", int((mirror != outs).sum()), 0),
        ("unsettled_at_end", int(not final["settled"]), 0),
        ("dropped", int(final["dropped"]), 0),
        ("conservation_broken", int(not final["conserved"]), 0),
        ("cycles_missing", abs(int(final["cycles_asked"] - final["t"])), 0),
    ]
