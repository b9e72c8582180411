#!/usr/bin/env python3
"""Smoke of the served path on a TPU, through the entry points users call.

One process drives `make_engine("jax", ...)` under `ThresholdServer` at
the size of a DHT monitoring deployment: a million-peer overlay (d=32
addresses, pad 2^20), the majority problem at vote density 0.4, wheel
capacity 8 per peer, an 8-cycle serve window — the committed serve and
sharded-row settings. It settles the initial message storm, then runs
a few bursts of client updates, each with one join and one leave, and
holds the result to the serve contract: settled, no wheel row dropped,
row conservation, every output on the host-side truth.

  python3 chip_smoke.py             one chip: the deployment, the kernel
                                    check, and an n=4096 replay against
                                    the numpy reference engine
  python3 chip_smoke.py --chips 4   the same deployment at mesh=4 against
                                    the single-device engine on device 0

The numbers it prints are smoke numbers, not benchmarks. The last line
of standard output is one JSON object,
``{"ok": true, "device": {"platform", "kind", "count"}}``; any failed
phase raises instead, and the script exits non-zero. It exits non-zero
without that line where JAX finds no TPU.
"""
from __future__ import annotations

import argparse
import json
import os
import sys
import time

ROOT = os.path.dirname(os.path.abspath(__file__))
for _p in (ROOT, os.path.join(ROOT, "src")):
    if _p not in sys.path:
        sys.path.insert(0, _p)

import numpy as np  # noqa: E402

N_DEPLOY = 1_000_000
VOTE_DENSITY = 0.4
WINDOW = 8
ENGINE_KW = {"capacity_per_peer": 8, "pad_to": 1 << 20,
             "work_budget": 1 << 16}
UPDATES = 4_000
BURSTS = 4
SETTLE_CAP = 1_000   # serve windows allowed per settle
REF_N = 4096


def log(msg: str) -> None:
    print(f"[smoke] {msg}", flush=True)


class CompileClock:
    """Sums JAX's backend-compile durations while it is open."""

    def __init__(self):
        self.secs = 0.0
        self.count = 0

    def __enter__(self):
        from jax import monitoring

        def on(event, secs, **_):
            if event == "/jax/core/compile/backend_compile_duration":
                self.secs += secs
                self.count += 1

        self._cb = on
        monitoring.register_event_duration_secs_listener(on)
        return self

    def __exit__(self, *exc):
        from jax import monitoring

        monitoring.unregister_event_duration_listener(self._cb)


def phase_device() -> dict:
    """The device JAX reports; raises SystemExit unless it is a TPU."""
    import jax

    devs = jax.devices()
    d = devs[0]
    if d.platform != "tpu":
        raise SystemExit(f"no TPU: JAX sees {len(devs)} {d.platform} "
                         "device(s)")
    log(f"device: platform={d.platform} kind={d.device_kind} "
        f"count={len(devs)}")
    return {"platform": d.platform, "kind": d.device_kind,
            "count": len(devs)}


def peak_bytes():
    import jax

    stats = jax.devices()[0].memory_stats() or {}
    return stats.get("peak_bytes_in_use")


def _settle(server, what: str) -> None:
    start = server.windows
    while True:
        server.pump()
        if server.settled:
            return
        if server.windows - start > SETTLE_CAP:
            raise AssertionError(
                f"{what}: not settled after {SETTLE_CAP} windows "
                f"(t={server.engine.t}, dropped={server.engine.dropped})")


def phase_serve(n: int = N_DEPLOY, updates: int = UPDATES,
                bursts: int = BURSTS, seed: int = 0, mesh=None,
                engine_kw: dict = ENGINE_KW, window: int = WINDOW,
                label: str = "serve") -> dict:
    """Serve the deployment: settle the initial storm, then `bursts`
    bursts of `updates // bursts` submits, one join and one leave each,
    every burst pumped until settled. Seeded and cycle-clocked, so two
    engines driven by it must agree bit for bit. Raises unless the run
    settled with dropped == 0, conserved its rows and left every output
    on the server's host-side truth."""
    from repro.core.dht import Ring
    from repro.engine import make_engine
    from repro.launch.serve import ThresholdServer

    rng = np.random.default_rng(seed)
    with CompileClock() as cc:
        t0 = time.perf_counter()
        ring = Ring.random(n, 32, seed=seed)
        votes = (rng.random(n) < VOTE_DENSITY).astype(np.int64)
        eng = make_engine("jax", ring, votes, seed=seed + 1,
                          problem="majority", mesh=mesh, **engine_kw)
        eng.block_until_ready()
        server = ThresholdServer(eng, window=window)
        setup_s = time.perf_counter() - t0
        setup_compile_s = cc.secs
        t1 = time.perf_counter()
        _settle(server, f"{label} initial storm")
        storm_s = time.perf_counter() - t1
        storm_windows = server.windows

        t2 = time.perf_counter()
        occupied = set(int(a) for a in ring.addrs)
        per_burst = max(updates // bursts, 1)
        for b in range(bursts):
            while True:
                a = int(rng.integers(1, 1 << 32))
                if a not in occupied:
                    break
            occupied.add(a)
            server.join(a, int(rng.integers(0, 2)))
            live = np.asarray(eng.ring.addrs)
            victim = int(live[int(rng.integers(live.size))])
            server.leave_addr(victim)
            occupied.discard(victim)
            live = np.asarray(eng.ring.addrs)
            for tgt, val in zip(rng.integers(0, live.size, per_burst),
                                rng.integers(0, 2, per_burst)):
                server.submit(int(live[tgt]), int(val))
            tb = time.perf_counter()
            _settle(server, f"{label} burst {b}")
            log(f"{label}: burst {b} settled at t={eng.t} "
                f"({time.perf_counter() - tb:.3f} s)")
        run_s = time.perf_counter() - t2
    total_compile_s = cc.secs

    st = server.stats()
    outputs = np.asarray(eng.outputs(), np.int64)
    truth = eng.problem.global_output(eng.data())
    cons = eng.check_conservation()
    rec = {
        "n": int(eng.n), "pad": int(eng.pad), "mesh": mesh,
        "settled": bool(server.settled), "dropped": int(eng.dropped),
        "conservation": cons, "truth": int(server.truth),
        "outputs_on_truth": bool((outputs == server.truth).all()),
        "cycles": int(eng.t), "messages": int(eng.messages_sent),
        "windows": st["windows"], "storm_windows": storm_windows,
        "transitions": st["transitions"], "applied": st["applied"],
        "submitted": st["submitted"],
        "setup_s": setup_s, "setup_compile_s": setup_compile_s,
        "storm_s": storm_s, "run_s": run_s,
        "compile_s": total_compile_s, "compiles": cc.count,
        "peak_bytes": peak_bytes(),
        "outputs": outputs, "engine": eng,
    }
    log(f"{label}: n={rec['n']} pad={rec['pad']} mesh={mesh} "
        f"settled={rec['settled']} dropped={rec['dropped']} "
        f"conservation={cons} truth={rec['truth']} "
        f"outputs_on_truth={rec['outputs_on_truth']}")
    log(f"{label} (smoke numbers, not a benchmark): cycles={rec['cycles']} "
        f"windows={rec['windows']} (initial storm {storm_windows}) "
        f"transitions={rec['transitions']} messages={rec['messages']} "
        f"applied={rec['applied']}/{rec['submitted']} submitted")
    log(f"{label} seconds (smoke numbers): setup={setup_s:.3f} "
        f"(of which compile {setup_compile_s:.3f}) "
        f"initial_storm={storm_s:.3f} bursts={run_s:.3f} "
        f"compile_total={total_compile_s:.3f} over {cc.count} compiles; "
        f"device peak_bytes_in_use={rec['peak_bytes']}")
    if not rec["settled"]:
        raise AssertionError(f"{label}: server did not settle")
    if rec["dropped"] != 0:
        raise AssertionError(f"{label}: dropped={rec['dropped']}")
    if not rec["outputs_on_truth"] or server.truth != truth:
        raise AssertionError(
            f"{label}: outputs off the truth (server {server.truth}, "
            f"data plane {truth})")
    return rec


def phase_kernels(eng) -> list:
    """Every delivery-wheel kernel compiled natively, and present in the
    engine's step program as a TPU custom call."""
    import jax.numpy as jnp

    from repro.kernels.wheel import WHEEL_KERNELS

    native = list(eng.native_kernels)
    if sorted(native) != sorted(WHEEL_KERNELS):
        raise AssertionError(
            f"wheel kernels not all native: {native} of {WHEEL_KERNELS}")
    text = eng._steps.lower(eng._st, jnp.asarray(1, jnp.int32)).as_text()
    calls = text.count("tpu_custom_call")
    if not calls:
        raise AssertionError("step program holds no tpu_custom_call")
    log(f"kernels: native={native} interpret=off; step program holds "
        f"{calls} tpu_custom_call site(s)")
    return native


def _replay(n: int, seed: int, backend: str, **engine_kw) -> dict:
    from repro.core.dht import Ring
    from repro.engine import make_engine
    from repro.launch.serve import (ThresholdServer, gen_workload,
                                    replay_workload)

    rng = np.random.default_rng(seed)
    ring = Ring.random(n, 32, seed=seed)
    votes = (rng.random(n) < VOTE_DENSITY).astype(np.int64)
    work = gen_workload(ring, "majority", windows=24, seed=seed + 3,
                        rate=40.0, p_churn=0.35, window_cycles=WINDOW)
    if backend == "jax":
        engine_kw["capacity_per_peer"] = 8
    eng = make_engine(backend, ring, votes, seed=seed + 7,
                      problem="majority", **engine_kw)
    server = ThresholdServer(eng, window=WINDOW)
    stream = []
    server.subscribe(lambda tr: stream.append(
        (tr.t, tuple(sorted(tr.peers)), tr.output)))
    replay_workload(server, work,
                    after_pump=lambda _i: getattr(
                        eng, "check_conservation", lambda: None)())
    truth = eng.problem.global_output(eng.data())
    res = eng.run_until_converged(truth, max_cycles=200_000)
    if res["converged"] != 1.0:
        raise AssertionError(f"{backend} replay did not converge: {res}")
    return {"outputs": np.asarray(eng.outputs(), np.int64),
            "data": np.asarray(eng.data(), np.int64), "truth": truth,
            "dropped": int(eng.dropped), "cycles": int(eng.t),
            "messages": int(eng.messages_sent), "stream": stream,
            "serve": {k: server.stats()[k] for k in (
                "submitted", "coalesced", "applied", "stale_dropped",
                "flushes")}}


def phase_reference(n: int = REF_N, seed: int = 5) -> None:
    """Replay one `gen_workload` trace through the numpy reference
    engine and through the jax engine on the default device, and
    through the jax engine's XLA reference path (no kernels) on the
    host CPU. Outputs, data, truth and serve counters must equal the
    numpy engine's; the transition stream, cycles and messages must
    equal the XLA reference's bit for bit (numpy draws its delays from
    another RNG, so its stream is legitimately re-timed)."""
    import jax

    ref = _replay(n, seed, "numpy")
    dev = _replay(n, seed, "jax")
    with jax.default_device(jax.devices("cpu")[0]):
        xla = _replay(n, seed, "jax", kernel="ref")
    for name, other in (("numpy", ref), ("cpu-xla", xla)):
        for k in ("truth", "serve"):
            if dev[k] != other[k]:
                raise AssertionError(
                    f"reference: {k} differs from {name}: {dev[k]} vs "
                    f"{other[k]}")
        for k in ("outputs", "data"):
            if not np.array_equal(dev[k], other[k]):
                raise AssertionError(f"reference: {k} differ from {name}")
        if dev["dropped"] or other["dropped"]:
            raise AssertionError(f"reference: drops ({name})")
    for k in ("stream", "cycles", "messages"):
        if dev[k] != xla[k]:
            raise AssertionError(f"reference: {k} differ from cpu-xla")
    log(f"reference n={n}: outputs, data, truth and serve counters equal "
        f"numpy's; transition stream ({len(dev['stream'])} transitions), "
        f"cycles={dev['cycles']} and messages={dev['messages']} equal the "
        f"cpu-xla engine's — equal")


def phase_mesh(chips: int = 4, **serve_kw) -> None:
    """The deployment at mesh=`chips` against the single-device engine
    on device 0 of this process: cycles, messages and outputs must be
    bit-identical."""
    recs = {}
    for mesh in (chips, None):
        label = f"mesh={chips}" if mesh else "single-device"
        rec = phase_serve(mesh=mesh, label=label, **serve_kw)
        rec.pop("engine")   # free its device state before the next run
        recs[label] = rec
    a, b = recs[f"mesh={chips}"], recs["single-device"]
    for k in ("cycles", "messages", "windows", "transitions"):
        if a[k] != b[k]:
            raise AssertionError(f"mesh: {k} differ: {a[k]} vs {b[k]}")
    if not np.array_equal(a["outputs"], b["outputs"]):
        raise AssertionError("mesh: outputs differ")
    log(f"mesh={chips} vs single-device: cycles={a['cycles']} "
        f"messages={a['messages']} windows={a['windows']} and "
        f"{a['outputs'].size} outputs bit-identical")


def main(argv=None) -> None:
    ap = argparse.ArgumentParser(description=__doc__.split("\n\n")[0])
    ap.add_argument("--chips", type=int, default=1, choices=(1, 4),
                    help="4: only the mesh=4 vs single-device comparison")
    args = ap.parse_args(argv)

    device = phase_device()
    if device["count"] < args.chips:
        raise SystemExit(f"--chips {args.chips} needs {args.chips} devices, "
                         f"JAX sees {device['count']}")
    from benchmarks.run import enable_compilation_cache

    log(f"compile cache: {enable_compilation_cache()}")
    t0 = time.perf_counter()
    if args.chips == 4:
        phase_mesh(chips=4)
    else:
        rec = phase_serve()
        phase_kernels(rec.pop("engine"))
        del rec
        t1 = time.perf_counter()
        phase_reference()
        log(f"reference seconds (smoke number): "
            f"{time.perf_counter() - t1:.3f}")
    log(f"all phases passed in {time.perf_counter() - t0:.3f} s")
    print(json.dumps({"ok": True, "device": device}), flush=True)


if __name__ == "__main__":
    main()
