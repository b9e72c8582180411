"""What a metric reader sees of one run (bench/metrics/<name>.py
modules define `read(ctx) -> float | None`)."""
from __future__ import annotations

from typing import Dict, Optional, Tuple

import numpy as np

from .latency import decision_latencies


class Context:
    def __init__(self, win, peaks: Dict, setup_s: float, trace=None):
        self.win = win
        self.peaks = peaks
        self.setup_s = setup_s
        self.trace = trace      # harness.trace.Reduced, or None
        self._lat: Optional[Tuple[np.ndarray, np.ndarray]] = None

    @property
    def engine(self):
        return self.win.engine

    def window_pumps(self) -> slice:
        return slice(self.win.first_window_pump, self.win.last_window_pump)

    def latencies(self) -> Tuple[np.ndarray, np.ndarray]:
        """(latency_s, failed) of every update, join and leave due in
        the window."""
        if self._lat is None:
            w, p = self.win, self.win.pumps
            self._lat = decision_latencies(
                w.w0 + w.sched.due, w.submit_t, p.start, p.end, p.settled,
                w.drain_end)
        return self._lat

    def operations(self) -> Tuple[int, int]:
        """(attempted, failed): updates, joins and leaves due in the
        window, or for a mix whose operation is the window the pumps of
        the window (each fails if it broke conservation or dropped a
        wheel row)."""
        if self.win.cell.traffic.get("operation") == "window":
            ok = self.win.pumps.ok[self.window_pumps()]
            return len(ok), int(sum(not v for v in ok))
        lat, failed = self.latencies()
        return int(lat.size), int(failed.sum())
