"""End-to-end behaviour: train loop with failure recovery, serving loop,
threshold-sync trainer, and a dry-run cell compile (subprocess)."""
import os
import subprocess
import sys

import numpy as np
import pytest


ROOT = os.path.abspath(os.path.join(os.path.dirname(__file__), ".."))


def _run(args, timeout=560):
    env = dict(os.environ)
    env["PYTHONPATH"] = os.path.join(ROOT, "src")
    env.pop("XLA_FLAGS", None)
    return subprocess.run([sys.executable, "-m"] + args, capture_output=True,
                          text=True, env=env, timeout=timeout, cwd=ROOT)


@pytest.mark.slow
def test_train_smoke_with_failure_recovery(tmp_path):
    r = _run([
        "repro.launch.train", "--arch", "smollm-135m", "--smoke",
        "--steps", "25", "--batch", "4", "--seq-len", "64",
        "--log-every", "5", "--ckpt-dir", str(tmp_path / "ck"),
        "--ckpt-every", "10", "--fail-at", "13",
    ])
    assert r.returncode == 0, r.stderr[-2000:]
    assert "failure at step 13" in r.stdout
    assert "step=20" in r.stdout  # resumed past the failure


@pytest.mark.slow
def test_threshold_sync_trainer():
    r = _run([
        "repro.launch.train", "--arch", "smollm-135m", "--smoke",
        "--sync", "threshold", "--pods", "2", "--steps", "15",
        "--batch", "4", "--seq-len", "32", "--tau", "0.001",
        "--max-inner", "8", "--log-every", "5",
    ])
    assert r.returncode == 0, r.stderr[-2000:]
    assert "total outer syncs" in r.stdout
    # bounded staleness forces at least one sync within 15 steps
    syncs = int(r.stdout.split("total outer syncs: ")[1].split("/")[0])
    assert syncs >= 1


@pytest.mark.slow
def test_serve_smoke():
    """The serve demo CLI end to end on the device engine: an
    open-loop update stream against a live 256-peer overlay."""
    r = _run(["repro.launch.serve", "--backend", "jax", "--n", "256"])
    assert r.returncode == 0, r.stderr[-2000:]
    assert "[serve] dropped = 0" in r.stdout, r.stdout[-2000:]


@pytest.mark.slow
def test_dryrun_cell_compiles():
    """One full-scale cell through the real dry-run path (512 devices)."""
    r = _run([
        "repro.launch.dryrun", "--arch", "xlstm-350m", "--shape",
        "long_500k", "--out", "/tmp/dryrun_test",
    ])
    assert r.returncode == 0, r.stderr[-2000:]
    assert '"status": "OK"' in r.stdout
