"""Benchmark orchestrator — one section per paper table/figure plus the
systems layer. Prints ``name,key=value,...`` CSV lines.

  tree_properties    Fig 4.1a (depth/density) + 4.1b (stretch, hop dist)
  static_convergence Fig 4.2  (messages to convergence, local vs gossip)
  stationary         Fig 4.3  (accuracy/cost under churn; budget sweep)
  kernel_bench       Pallas-kernel oracles microbench (CPU-indicative)
  kernel_wheel       delivery-wheel kernels -> BENCH_kernels.json (gated)
  sync_comparison    trainer-level sync families (paper mode vs baselines)
  engine             numpy-vs-device engine cycles/sec -> BENCH_engine.json
  serve              streaming serve-layer load harness -> BENCH_serve.json
  churn              Alg. 2 join/leave reconvergence    -> BENCH_churn.json
  sweep              batched accuracy-vs-threshold grid -> BENCH_sweep.json
  roofline           summary of the dry-run roofline table (if present)

The majority-voting sections run on the engine backend selected with
``--backend {numpy,jax}`` (default numpy — the reference simulator).
The JAX persistent compilation cache is enabled (``JAX_COMPILATION_CACHE_DIR``
if set, else results/.jax_cache under the repo root) so the device
engine's superstep programs compile once across benchmark invocations
instead of ~4s of jit per size per run.

Run everything:   PYTHONPATH=src python -m benchmarks.run
One section:      PYTHONPATH=src python -m benchmarks.run --only stationary
Device engine:    PYTHONPATH=src python -m benchmarks.run --backend jax
CI perf gate:     PYTHONPATH=src python -m benchmarks.run --check-regression
CI smoke pass:    PYTHONPATH=src python -m benchmarks.run --smoke
                  (tiny n, 1-2 trials per suite, JSONs under
                  results/smoke/ so the committed baselines stay put;
                  finishes in ~2 min — the CI bench job runs this after
                  the regression gate and uploads the JSONs)
"""
from __future__ import annotations

import argparse
import os
import sys
import time

# the persistent XLA compilation cache when JAX_COMPILATION_CACHE_DIR is
# unset: a fixed path in the checkout (git-ignored), anchored on the
# repo root so every cwd shares it — the path is part of the cache key
REPO_ROOT = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
CACHE_DIR = os.path.join(REPO_ROOT, "results", ".jax_cache")
CACHE_KEY_FILE = "CACHE_KEY"


def csv(line: str):
    print(line, flush=True)


def section(name):
    print(f"### {name}", flush=True)


def cache_key() -> str:
    """What a persistent-cache entry's validity depends on: the jaxlib
    that serialized it and the engine program schema it was traced from
    (`repro.engine.ENGINE_SCHEMA`). Any mismatch means the cached
    executables were built against a different world."""
    import jaxlib

    from repro.engine import ENGINE_SCHEMA

    return f"jaxlib={jaxlib.__version__};engine_schema={ENGINE_SCHEMA}"


def validate_cache_dir(cache_dir: str, key: str = None, log=None) -> str:
    """Refuse to reuse a stale persistent XLA cache (the PR 8 scar:
    cache entries serialized against an older jaxlib/engine deserialized
    into executables that hung armed-engine runs ~1-in-3).

    The dir carries a `CACHE_KEY` marker written when this function
    creates it. Returns the action taken:

      * ``"fresh"``   — new or empty dir: marker written;
      * ``"match"``   — marker equals today's key: entries reusable;
      * ``"cleared"`` — marker differs: the dir is one this function
        made for another key, so it is torn down and re-marked
        (recompiling costs seconds, debugging a poisoned executable
        cost a day);
      * ``"foreign"`` — entries but no marker: a directory this
        function did not create. Nothing in it is deleted or marked;
        the caller must not use it."""
    import shutil

    key = key if key is not None else cache_key()
    marker = os.path.join(cache_dir, CACHE_KEY_FILE)
    entries = []
    if os.path.isdir(cache_dir):
        entries = [e for e in os.listdir(cache_dir) if e != CACHE_KEY_FILE]
    if os.path.exists(marker):
        with open(marker) as f:
            found = f.read().strip()
        if found == key:
            return "match"
        action = "cleared"
    elif entries:
        if log:
            log(f"jax_cache_foreign,dir={cache_dir},reason=unmarked_entries")
        return "foreign"
    else:
        action = "fresh"
    if action == "cleared":
        if log:
            log(f"jax_cache_cleared,dir={cache_dir},reason=key_mismatch")
        shutil.rmtree(cache_dir, ignore_errors=True)
    os.makedirs(cache_dir, exist_ok=True)
    with open(marker, "w") as f:
        f.write(key + "\n")
    return action


def enable_compilation_cache(cache_dir: str = CACHE_DIR):
    """Turn on JAX's persistent compilation cache and return its
    directory (None if it stays off). Must run before the first jit.

    Where ``JAX_COMPILATION_CACHE_DIR`` is set, JAX already reads it:
    the directory is placed from outside, so this sets none in code and
    touches nothing in it. Otherwise the cache lives at `cache_dir`
    (default: the repo-root-anchored `CACHE_DIR`), guarded by
    `validate_cache_dir`; a foreign directory there leaves the cache
    off."""
    import jax

    jax.config.update("jax_persistent_cache_min_compile_time_secs", 0.5)
    jax.config.update("jax_persistent_cache_min_entry_size_bytes", 0)
    if os.environ.get("JAX_COMPILATION_CACHE_DIR"):
        return os.environ["JAX_COMPILATION_CACHE_DIR"]
    cache_dir = os.path.abspath(cache_dir)
    if validate_cache_dir(cache_dir, log=csv) == "foreign":
        return None
    jax.config.update("jax_compilation_cache_dir", cache_dir)
    return cache_dir


def main() -> None:
    ap = argparse.ArgumentParser()
    ap.add_argument("--only", default=None)
    ap.add_argument("--backend", default="numpy", choices=("numpy", "jax"),
                    help="engine backend for the majority-voting sections")
    ap.add_argument("--check-regression", action="store_true",
                    help="re-measure the engine against the committed "
                         "results/BENCH_engine.json and exit non-zero on a "
                         ">30%% cycles/sec regression")
    ap.add_argument("--smoke", action="store_true",
                    help="tiny smoke pass (CI): one small size / 1-2 trials "
                         "per JSON-writing suite, outputs under "
                         "results/smoke/")
    ap.add_argument("--no-compilation-cache", action="store_true",
                    help="skip the persistent XLA compilation cache")
    args = ap.parse_args()

    if not args.no_compilation_cache:
        enable_compilation_cache()

    from benchmarks import (
        churn, engine_bench, kernel_bench, static_convergence, stationary,
        sweep, sync_comparison, tree_properties,
    )
    from benchmarks import serve as serve_bench

    if args.check_regression:
        section("check_regression")
        ok = engine_bench.check_regression(
            csv, max_n=1_000 if args.smoke else 10_000,
            sharded=not args.smoke)
        ok_k = kernel_bench.check_regression_kernels(csv)
        sys.exit(0 if (ok and ok_k) else 1)

    b = args.backend
    if args.smoke:
        smoke_dir = os.path.join("results", "smoke")
        os.makedirs(smoke_dir, exist_ok=True)
        sp = lambda name: os.path.join(smoke_dir, name)
        sections = [
            ("tree_properties", lambda c: tree_properties.run(
                c, **tree_properties.SMOKE, out_path=sp("BENCH_tree.json"))),
            ("kernel_bench", lambda c: kernel_bench.run(c)),
            ("kernel_wheel", lambda c: kernel_bench.run_wheel(
                c, ww=576, pad=2048, narrow=64,
                out_path=sp("BENCH_kernels.json"))),
            ("engine", lambda c: engine_bench.run(
                c, **engine_bench.SMOKE, out_path=sp("BENCH_engine.json"))),
            # sharded engine at CI scale: one subprocess with 8 virtual
            # host devices, merged into the smoke engine JSON (the same
            # smoke row check_regression re-runs against the committed
            # file — keep them one definition)
            ("engine_sharded", lambda c: engine_bench.run_sharded(
                c, rows=engine_bench.SHARDED_ROWS[:1],
                out_path=sp("BENCH_engine.json"))),
            # device churn re-enabled: the grow/re-pad path no longer
            # rebuilds the jitted programs (jax.jit retraces per shape),
            # so jax churn is one join/leave trace + reuse, not a per-
            # event re-jit storm
            # the fault row arms the fault plane end to end in CI: one
            # abrupt crash (detect -> evict -> reconverge) and one
            # mass-churn storm per backend at n=64
            # serve smoke: numpy + single-device jax open-loop streams
            # at tiny n (the CI serve job runs this plus the committed
            # gate via `python -m benchmarks.serve --check-regression`)
            ("serve", lambda c: serve_bench.run_smoke(
                c, out_dir=smoke_dir)),
            ("churn", lambda c: churn.run(
                c, sizes=(256,), events=4, backends=("numpy", "jax"),
                fault_sizes=(64,), fault_events=8,
                out_path=sp("BENCH_churn.json"))),
            ("sweep", lambda c: sweep.run(
                c, **sweep.SMOKE, margins=(0.3, 0.7), backend=b,
                out_path=sp("BENCH_sweep.json"))),
            ("sweep_mean", lambda c: sweep.run(
                c, **sweep.SMOKE, offsets=(-0.4, 0.4), problem="mean",
                backend=b, out_path=sp("BENCH_sweep.json"))),
            ("sweep_l2", lambda c: sweep.run(
                c, **sweep.SMOKE, offsets=(-0.4, 0.4), problem="l2",
                backend=b, out_path=sp("BENCH_sweep.json"))),
        ]
    else:
        sections = [
            ("tree_properties", lambda c: tree_properties.run(c)),
            ("static_convergence",
             lambda c: static_convergence.run(c, backend=b)),
            ("stationary", lambda c: stationary.run(c, backend=b)),
            ("kernel_bench", lambda c: kernel_bench.run(c)),
            ("kernel_wheel", lambda c: kernel_bench.run_wheel(c)),
            ("sync_comparison", lambda c: sync_comparison.run(c, backend=b)),
            ("engine", lambda c: engine_bench.run(c)),
            ("engine_sharded", lambda c: engine_bench.run_sharded(c)),
            ("serve", lambda c: serve_bench.run(c)),
            ("churn", lambda c: churn.run(c)),
            ("sweep", lambda c: sweep.run(c, backend=b)),
            ("sweep_mean", lambda c: sweep.run(c, backend=b, problem="mean")),
            ("sweep_l2", lambda c: sweep.run(c, backend=b, problem="l2")),
        ]
    for name, fn in sections:
        if args.only and args.only != name:
            continue
        section(name)
        t0 = time.time()
        fn(csv)
        csv(f"{name}_total,sec={time.time()-t0:.0f}")

    if not args.only or args.only == "roofline":
        section("roofline")
        try:
            from repro.analysis.roofline import load_records, roofline_row

            recs = load_records("results/dryrun")
            n_ok = 0
            for r in recs:
                row = roofline_row(r)
                if row:
                    n_ok += 1
                    csv(f"roofline,{row['arch']},{row['shape']},{row['mesh']},"
                        f"dominant={row['dominant']},"
                        f"mfu={row['roofline_mfu']*100:.1f}%")
            csv(f"roofline_total,cells={n_ok}")
        except Exception as e:  # dry-run results not generated yet
            csv(f"roofline_skipped,reason={type(e).__name__}")


if __name__ == "__main__":
    main()
