"""The chip the run is on: the check that it is one, compile counts,
the compile cache and peak memory. Nothing here falls back to the CPU."""
from __future__ import annotations

import os
from typing import Dict

from .spec import BENCH

COMPILE_EVENT = "/jax/core/compile/backend_compile_duration"
CACHE_HIT_EVENT = "/jax/compilation_cache/cache_hits"
CACHE_DIR = os.path.join(BENCH, ".jax_cache")


def require_tpu(chips: int) -> Dict:
    """The device JAX reports; SystemExit (non-zero, no result) unless
    it holds at least `chips` TPU chips."""
    import jax

    devs = jax.devices()
    d = devs[0]
    if d.platform != "tpu":
        raise SystemExit(f"no TPU: JAX sees {len(devs)} {d.platform} "
                         "device(s)")
    if len(devs) < chips:
        raise SystemExit(f"the cell needs {chips} chips; JAX sees "
                         f"{len(devs)}")
    return {"platform": d.platform, "kind": d.device_kind,
            "count": chips}


def enable_cache() -> str:
    """JAX's persistent compilation cache at a fixed path inside the
    checkout, every program cached, so only a checkout's first run of a
    cell compiles."""
    import jax

    os.makedirs(CACHE_DIR, exist_ok=True)
    jax.config.update("jax_compilation_cache_dir", CACHE_DIR)
    jax.config.update("jax_persistent_cache_min_compile_time_secs", 0.0)
    jax.config.update("jax_persistent_cache_min_entry_size_bytes", 0)
    return CACHE_DIR


class CompileCounter:
    """Counts programs built (JAX's backend-compile event, which also
    fires when a program is loaded from the persistent cache) with their
    seconds, and persistent-cache hits, from jax.monitoring; `mark()`
    snapshots the counts. Programs compiled = compiles - cache_hits."""

    def __init__(self):
        self.compiles = 0
        self.compile_s = 0.0
        self.cache_hits = 0

    def __enter__(self):
        from jax import monitoring

        def on_duration(event, secs, **_):
            if event == COMPILE_EVENT:
                self.compiles += 1
                self.compile_s += secs

        def on_event(event, **_):
            if event == CACHE_HIT_EVENT:
                self.cache_hits += 1

        self._cbs = (on_duration, on_event)
        monitoring.register_event_duration_secs_listener(on_duration)
        monitoring.register_event_listener(on_event)
        return self

    def __exit__(self, *exc):
        from jax import monitoring

        monitoring.unregister_event_duration_listener(self._cbs[0])
        monitoring.unregister_event_listener(self._cbs[1])

    def mark(self) -> Dict:
        return {"compiles": self.compiles, "compile_s": self.compile_s,
                "cache_hits": self.cache_hits}


def peak_bytes(chips: int) -> int:
    """Peak bytes in use on the fullest of the chips the cell uses."""
    import jax

    peaks = [(d.memory_stats() or {}).get("peak_bytes_in_use", 0)
             for d in jax.devices()[:chips]]
    return int(max(peaks))
