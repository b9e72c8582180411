"""The delivery-wheel kernels compile natively for a v5e chip at the
widths the engine uses at pad 2^20.

Nothing runs: the TPU compiler, installed with jaxlib, compiles each
kernel for a described (not attached) `v5e:2x2` topology with one-chip
sharding, and refuses what the chip would refuse — a Mosaic construct
it cannot lower, or more scoped VMEM than a kernel may use. Interpret
mode (the CPU parity surface of tests/test_kernels.py) catches neither.

The topology is described inside a module fixture, never at import:
only one process may hold the TPU library, and every test worker
imports this file.
"""
from __future__ import annotations

import os

import numpy as np
import pytest

import jax
import jax.numpy as jnp

PAD = 1 << 20


@pytest.fixture(scope="module")
def topo():
    os.environ.setdefault("TPU_LOG_DIR", "disabled")
    from jax.experimental import topologies

    try:
        return topologies.get_topology_desc(platform="tpu",
                                            topology_name="v5e:2x2")
    except Exception as e:  # no TPU compiler in this installation
        pytest.skip(f"no v5e:2x2 topology can be described here: {e}")


@pytest.fixture(scope="module")
def one_chip(topo):
    from jax.sharding import SingleDeviceSharding

    return SingleDeviceSharding(topo.devices[0])


@pytest.fixture(scope="module")
def widths():
    """The engine's own kernel widths at pad 2^20 (default budget):
    drain window WW, narrow descent width NT, staged block rows."""
    from repro.core.dht import Ring
    from repro.engine.jax_backend import NDIR, JaxEngine

    eng = JaxEngine(Ring.random(64, 32, seed=0), np.zeros(64, np.int64),
                    pad_to=PAD, _defer_state=True)
    ww = eng.lanes * eng.window_l
    return {"ww": ww, "nt": eng.lanes * eng.narrow_l,
            "stage": ww * (1 + NDIR), "roww": eng.roww}


def _compile(fn, *shapes):
    compiled = jax.jit(fn).lower(*shapes).compile()
    assert "tpu_custom_call" in compiled.as_text()
    return compiled


@pytest.mark.parametrize("problem", ["majority", "mean", "l2"])
def test_threshold_step_compiles(one_chip, problem):
    from repro.engine.problems import get_problem
    from repro.kernels.wheel.threshold_step import threshold_step_kernel

    p = get_problem(problem)
    s = lambda *shape: jax.ShapeDtypeStruct(shape, jnp.int32,
                                            sharding=one_chip)
    pay = s(PAD, 3 * p.payload_width)  # payload columns
    _compile(lambda a, b, x: threshold_step_kernel(p, a, b, x,
                                                   interpret=False),
             pay, pay, s(PAD, p.data_width))


def test_descent_tail_compiles(one_chip, widths):
    from repro.kernels.wheel.descent import descent_tail_kernel

    nt = widths["nt"]
    u = jax.ShapeDtypeStruct((nt,), jnp.uint32, sharding=one_chip)
    b = jax.ShapeDtypeStruct((nt,), jnp.bool_, sharding=one_chip)
    _compile(lambda *a: descent_tail_kernel(*a, d=32, interpret=False),
             u, u, u, b, b, b, u, u, u, b,
             jax.ShapeDtypeStruct((), jnp.uint32, sharding=one_chip))


def test_due_dedup_compiles(one_chip, widths):
    from repro.kernels.wheel.due_dedup import due_dedup_kernel

    ww = widths["ww"]
    i = jax.ShapeDtypeStruct((ww,), jnp.int32, sharding=one_chip)
    b = jax.ShapeDtypeStruct((ww,), jnp.bool_, sharding=one_chip)
    _compile(lambda f, d, a, w, k: due_dedup_kernel(f, d, a, w, k,
                                                    interpret=False),
             i, b, b, i, i)


def test_stage_rows_compiles(one_chip, widths):
    from repro.kernels.wheel.enqueue import stage_rows_kernel

    m, roww = widths["stage"], widths["roww"]
    sh = lambda shape, dt: jax.ShapeDtypeStruct(shape, dt, sharding=one_chip)
    _compile(lambda r, a, o, p, t: stage_rows_kernel(
        r, a, o, p, t, roww - 1, interpret=False),
        sh((m, roww), jnp.uint32), sh((m,), jnp.bool_),
        sh((m,), jnp.int32), sh((10,), jnp.int32), sh((), jnp.int32))
