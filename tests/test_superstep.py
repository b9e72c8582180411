"""Superstep engine: scan-fused execution and vmapped trial batching.

The PR 3 contract (DESIGN.md §Engine):
  * `step(K)` — one jitted while_loop dispatch — is BIT-identical to K
    single-cycle dispatches (state, messages, deferred, wheel contents);
  * the chunked `run_until_converged` (on-device convergence predicate,
    one host sync per chunk) reports exactly the cycles/messages the
    per-cycle reference loop would;
  * a vmapped B-trial batch matches B serial runs trial-for-trial;
  * the delivery wheel never loses rows (deferral, not drops) and its
    occupancy counters stay within capacity.
"""
import json

import numpy as np
import pytest

import jax

from repro.core.dht import Ring
from repro.engine import make_engine


def _votes(n, mu, rng):
    k = int(round(n * mu))
    v = np.zeros(n, np.int64)
    v[rng.choice(n, k, replace=False)] = 1
    return v


def _assert_states_equal(e1, e2):
    h1, h2 = jax.device_get(e1._st), jax.device_get(e2._st)
    for field, a, b in zip(h1._fields, h1, h2):
        np.testing.assert_array_equal(np.asarray(a), np.asarray(b),
                                      err_msg=f"DeviceState.{field}")


# ---------------------------------------------------------------------------
# 1. superstep == K single steps, bit for bit
# ---------------------------------------------------------------------------

def test_superstep_bit_identical_to_single_steps():
    n = 192
    rng = np.random.default_rng(0)
    ring = Ring.random(n, 32, seed=0)
    votes = _votes(n, 0.4, rng)
    e1 = make_engine("jax", ring, votes, seed=3, kernel="ref")
    e2 = make_engine("jax", ring, votes, seed=3, kernel="ref")
    e1.step(41)
    for _ in range(41):
        e2.step(1)
    _assert_states_equal(e1, e2)
    assert (e1.t, e1.messages_sent, e1.deferred, e1.dropped) == \
           (e2.t, e2.messages_sent, e2.deferred, e2.dropped)


def test_superstep_bit_identical_under_budget_pressure():
    """Slip/leftover/spill paths active (deferred > 0) and still
    bit-identical across dispatch granularities."""
    n = 160
    rng = np.random.default_rng(1)
    ring = Ring.random(n, 32, seed=1)
    votes = _votes(n, 0.45, rng)
    e1 = make_engine("jax", ring, votes, seed=4, kernel="ref", work_budget=24)
    e2 = make_engine("jax", ring, votes, seed=4, kernel="ref", work_budget=24)
    e1.step(60)
    for k in (7, 13, 1, 25, 14):
        e2.step(k)
    assert e1.deferred > 0  # the budget did bind
    _assert_states_equal(e1, e2)


# ---------------------------------------------------------------------------
# 2. chunked convergence loop == per-cycle reference loop
# ---------------------------------------------------------------------------

@pytest.mark.parametrize("stable_for", [1, 5])
def test_chunked_convergence_matches_percycle_loop(stable_for):
    n = 128
    rng = np.random.default_rng(2)
    ring = Ring.random(n, 32, seed=2)
    votes = _votes(n, 0.3, rng)
    fast = make_engine("jax", ring, votes, seed=5, kernel="ref", chunk=64)
    res = fast.run_until_converged(truth=0, max_cycles=20_000,
                                   stable_for=stable_for)
    assert res["converged"] == 1.0

    # reference: same engine driven check-then-step one cycle at a time
    ref = make_engine("jax", ring, votes, seed=5, kernel="ref")
    stable = 0
    for _ in range(20_000):
        if (ref.outputs() == 0).all():
            stable += 1
            if stable >= stable_for:
                break
        else:
            stable = 0
        ref.step(1)
    assert res["cycles"] == ref.t
    assert res["messages"] == ref.messages_sent
    _assert_states_equal(fast, ref)


def test_chunked_convergence_respects_max_cycles():
    n = 64
    rng = np.random.default_rng(3)
    ring = Ring.random(n, 32, seed=3)
    votes = _votes(n, 0.3, rng)
    eng = make_engine("jax", ring, votes, seed=6, kernel="ref", chunk=32)
    res = eng.run_until_converged(truth=1, max_cycles=100)  # wrong truth
    assert res["converged"] == 0.0
    assert eng.t <= 100


# ---------------------------------------------------------------------------
# 3. vmapped batch == serial runs, trial for trial
# ---------------------------------------------------------------------------

def test_batched_matches_serial_trial_for_trial():
    B, n = 4, 160
    rng = np.random.default_rng(4)
    ring = Ring.random(n, 32, seed=4)
    votes = np.stack([_votes(n, mu, rng) for mu in (0.25, 0.45, 0.55, 0.7)])
    truths = (2 * votes.sum(1) >= n).astype(np.int64)

    bat = make_engine("jax", ring, votes, seed=11, batch=B, kernel="ref")
    res_b = bat.run_until_converged(truths)
    outs_b = bat.outputs()
    for b in range(B):
        ser = make_engine("jax", ring, votes[b], seed=11 + b, kernel="ref")
        res_s = ser.run_until_converged(int(truths[b]))
        assert res_s == res_b[b], f"trial {b}"
        np.testing.assert_array_equal(ser.outputs(), outs_b[b])
    assert all(r["converged"] == 1.0 for r in res_b)
    assert (bat.dropped == 0).all()


def test_batched_step_matches_serial():
    B, n = 3, 96
    rng = np.random.default_rng(5)
    rings = [Ring.random(n, 32, seed=20 + b) for b in range(B)]
    votes = np.stack([_votes(n, 0.4, rng) for _ in range(B)])
    bat = make_engine("jax", rings, votes, seed=30, batch=B, kernel="ref")
    bat.step(50)
    for b in range(B):
        ser = make_engine("jax", rings[b], votes[b], seed=30 + b, kernel="ref")
        ser.step(50)
        assert ser.messages_sent == int(bat.messages_sent[b])
        np.testing.assert_array_equal(ser.outputs(), bat.outputs()[b])


def test_batched_numpy_wrapper_and_set_votes():
    B, n = 2, 96
    rng = np.random.default_rng(6)
    ring = Ring.random(n, 32, seed=6)
    votes = np.stack([_votes(n, 0.3, rng) for _ in range(B)])
    jb = make_engine("jax", ring, votes, seed=40, batch=B, kernel="ref")
    nb = make_engine("numpy", ring, votes, seed=40, batch=B)
    for r in nb.run_until_converged(0) + jb.run_until_converged(0):
        assert r["converged"] == 1.0
    # ragged batched vote flip (idx -1 = no-op rows)
    idx = np.full((B, 3), -1)
    idx[0, :2] = [1, 2]
    idx[1, :1] = [5]
    val = np.ones((B, 3), np.int64)
    jb.set_votes(idx, val)
    nb.set_votes(idx, val)
    np.testing.assert_array_equal(jb.votes(), nb.votes())
    jb.step(400)
    nb.step(400)
    np.testing.assert_array_equal(jb.outputs(), nb.outputs())


def test_batched_api_guards():
    ring = Ring.random(32, 32, seed=7)
    votes = np.zeros((2, 32), np.int64)
    with pytest.raises(ValueError):  # votes must be (B, n)
        make_engine("jax", ring, votes[0], batch=2)
    with pytest.raises(ValueError):  # mismatched ring count
        from repro.engine.batched import BatchedJaxEngine

        BatchedJaxEngine([ring], votes)
    with pytest.raises(ValueError):  # mismatched (n, d)
        make_engine("jax", [ring, Ring.random(16, 32, seed=8)], votes, batch=2)


# ---------------------------------------------------------------------------
# 4. delivery-wheel invariants
# ---------------------------------------------------------------------------

def test_wheel_occupancy_and_no_silent_loss():
    n = 300
    rng = np.random.default_rng(8)
    ring = Ring.random(n, 32, seed=8)
    votes = _votes(n, 0.45, rng)
    eng = make_engine("jax", ring, votes, seed=9, kernel="ref", work_budget=64)
    for _ in range(12):
        eng.step(25)
        assert 0 <= eng.in_flight <= eng.capacity
        wcnt = np.asarray(eng._st.wcnt)
        acnt = np.asarray(eng._st.acnt)
        assert (wcnt >= 0).all() and (wcnt <= eng.slot_cap).all()
        assert (acnt >= 0).all() and (acnt <= 64).all()
    assert eng.deferred > 0   # the tiny budget did bind
    assert eng.dropped == 0   # but nothing was lost
    res = eng.run_until_converged(truth=0, max_cycles=30_000)
    assert res["converged"] == 1.0 and res["invalid"] == 0.0


# ---------------------------------------------------------------------------
# 5. bench smoke + regression checker (the CI perf gate machinery)
# ---------------------------------------------------------------------------

@pytest.mark.bench
def test_engine_bench_smoke(tmp_path):
    """Smoke-sized engine benchmark (the `--smoke` CI configuration):
    records both backends, preserves a baseline, and the regression
    checker consumes its own output."""
    from benchmarks import engine_bench

    out = tmp_path / "BENCH_engine.json"
    lines = []
    engine_bench.run(lines.append, **engine_bench.SMOKE, out_path=str(out))
    data = json.loads(out.read_text())
    assert data["rows"][0]["jax"]["dropped"] == 0
    assert data["rows"][0]["jax"]["cycles_per_sec"] > 0
    # second run demotes the first rows to the baseline and reports speedup
    engine_bench.run(lines.append, **engine_bench.SMOKE, out_path=str(out))
    data2 = json.loads(out.read_text())
    assert "baseline" in data2 and "jax_over_baseline" in data2["rows"][0]
    # regression checker: equal perf passes, an absurd committed value fails
    assert engine_bench.check_regression(lines.append, out_path=str(out),
                                         max_n=256)
    data2["rows"][0]["jax"]["cycles_per_sec"] = 1e9
    out.write_text(json.dumps(data2))
    assert not engine_bench.check_regression(lines.append, out_path=str(out),
                                             max_n=256)


@pytest.mark.bench
def test_engine_bench_warm_reuse():
    """A repeat `bench_backend` call with an identical config must hit
    the in-process engine cache: no reconstruction/re-jit (the cold
    ~2.5s setup_s), just a state-snapshot restore."""
    from benchmarks import engine_bench

    engine_bench._ENGINE_CACHE.clear()
    cold = engine_bench.bench_backend("jax", 256, cycles=5, reps=1)
    warm = engine_bench.bench_backend("jax", 256, cycles=5, reps=1)
    assert "engine_reused" not in cold
    assert warm.get("engine_reused") is True
    assert warm["setup_s"] < max(cold["setup_s"], 0.05)
    # both records carry the deferral-rate counter next to deferred
    for rec in (cold, warm):
        assert rec["deferral_rate"] == pytest.approx(
            rec["deferred"] / max(rec["messages"], 1), abs=1e-4)
    # identical measured work either way
    assert warm["messages"] == cold["messages"]
    assert warm["deferred"] == cold["deferred"]


@pytest.mark.bench
@pytest.mark.slow
def test_run_smoke_xla_cache_warm(tmp_path):
    """`benchmarks.run --only engine --smoke` twice from a fresh
    working dir: the first run populates the persistent XLA cache, the
    second must fully hit it (no new cache entries) and set up faster."""
    import os
    import re
    import subprocess
    import sys

    repo = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
    env = dict(os.environ)
    env["PYTHONPATH"] = repo + os.pathsep + os.path.join(repo, "src")
    env["JAX_PLATFORMS"] = "cpu"
    # the run must use its own cache under tmp_path, placed from outside
    # the way CI and the chip machine place theirs
    cache = tmp_path / "results" / ".jax_cache"
    env["JAX_COMPILATION_CACHE_DIR"] = str(cache)

    def smoke():
        r = subprocess.run(
            [sys.executable, "-m", "benchmarks.run", "--only", "engine",
             "--smoke"],
            capture_output=True, text=True, env=env, cwd=str(tmp_path),
            timeout=1200,
        )
        assert r.returncode == 0, r.stdout + r.stderr
        m = re.search(r"engine,n=\d+,backend=jax,.*setup_s=([\d.]+)",
                      r.stdout)
        assert m, r.stdout
        return float(m.group(1))

    cold_setup = smoke()
    entries = set(os.listdir(cache))
    assert entries, "first --smoke left no persistent XLA cache entries"
    warm_setup = smoke()
    assert set(os.listdir(cache)) == entries, \
        "second --smoke missed the persistent XLA cache (new entries)"
    assert warm_setup < max(cold_setup, 0.1)


@pytest.mark.bench
def test_sweep_smoke(tmp_path):
    from benchmarks import sweep

    out = tmp_path / "BENCH_sweep.json"
    lines = []
    sweep.run(lines.append, **sweep.SMOKE, margins=(0.3, 0.7),
              out_path=str(out))
    data = json.loads(out.read_text())
    assert data["batch"] == 4
    assert len(data["rows"]) == 2
    for row in data["rows"]:
        assert row["lsp_converge_rate"] == 1.0


@pytest.mark.bench
def test_sweep_problem_smoke(tmp_path):
    """`--problem {mean,l2}` grids merge under `problems.<name>` while
    the majority rows stay at the top level."""
    from benchmarks import sweep

    out = tmp_path / "BENCH_sweep.json"
    lines = []
    sweep.run(lines.append, **sweep.SMOKE, margins=(0.3, 0.7),
              out_path=str(out))
    for problem in ("mean", "l2"):
        sweep.run(lines.append, **sweep.SMOKE, offsets=(-0.4, 0.4),
                  problem=problem, out_path=str(out))
    data = json.loads(out.read_text())
    assert len(data["rows"]) == 2  # majority rows survived the merges
    for problem in ("mean", "l2"):
        grid = data["problems"][problem]
        assert len(grid["rows"]) == 2
        for row in grid["rows"]:
            assert row["converge_rate"] == 1.0
            assert row["msgs_per_peer"] > 0
