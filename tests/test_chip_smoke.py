"""`chip_smoke.py`'s phases at tiny n on the CPU.

The script itself refuses to run without a TPU; its phase functions
take sizes, so the control flow, the serve contract checks and the
reference comparison run here on CPU. The device assertion is the one
phase not driven here (it would refuse)."""
from __future__ import annotations

import importlib.util
import os

import numpy as np
import pytest

ROOT = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
TINY = {"n": 256, "updates": 120, "bursts": 2,
        "engine_kw": {"capacity_per_peer": 8}}


@pytest.fixture(scope="module")
def smoke():
    spec = importlib.util.spec_from_file_location(
        "chip_smoke", os.path.join(ROOT, "chip_smoke.py"))
    mod = importlib.util.module_from_spec(spec)
    spec.loader.exec_module(mod)
    return mod


def test_serve_phase_holds_the_serve_contract(smoke):
    rec = smoke.phase_serve(**TINY)
    assert rec["settled"] and rec["dropped"] == 0
    assert rec["outputs_on_truth"]
    cons = rec["conservation"]
    assert cons["enqueued"] == cons["retired"] + cons["live"]
    assert rec["applied"] > 0 and rec["transitions"] > 0
    assert rec["n"] == TINY["n"]  # one join and one leave per burst
    assert rec["outputs"].shape == (TINY["n"],)


def test_kernel_phase_refuses_interpreted_kernels(smoke):
    """Off the TPU every enabled wheel kernel runs interpreted, so the
    kernel phase must refuse — the smoke cannot pass with kernels off."""
    from repro.core.dht import Ring
    from repro.engine import make_engine

    ring = Ring.random(64, 32, seed=0)
    eng = make_engine("jax", ring, np.zeros(64, np.int64), kernel="pallas")
    assert eng.native_kernels == ()
    with pytest.raises(AssertionError, match="not all native"):
        smoke.phase_kernels(eng)


def test_reference_phase_matches_numpy(smoke):
    smoke.phase_reference(n=128, seed=3)


def test_mesh_phase_is_bit_identical(smoke):
    smoke.phase_mesh(chips=1, **TINY)
