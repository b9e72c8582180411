"""Jit'd wrapper for the fused majority-voting step."""
from __future__ import annotations

from typing import Tuple

import jax.numpy as jnp

from repro.kernels import on_tpu

from .majority_step import majority_step_kernel
from .ref import majority_step_reference


def majority_step(
    in_ones, in_tot, out_ones, out_tot, x, use_kernel: bool = True
) -> Tuple[jnp.ndarray, jnp.ndarray, jnp.ndarray, jnp.ndarray]:
    """(viol (N,3) bool, output (N,), pay_ones (N,3), pay_tot (N,3))."""
    if use_kernel and x.shape[0] >= 8:
        return majority_step_kernel(
            in_ones, in_tot, out_ones, out_tot, x, interpret=not on_tpu()
        )
    viol, out, po, pt = majority_step_reference(
        jnp.asarray(in_ones, jnp.int32), jnp.asarray(in_tot, jnp.int32),
        jnp.asarray(out_ones, jnp.int32), jnp.asarray(out_tot, jnp.int32),
        jnp.asarray(x, jnp.int32),
    )
    return viol, out, po, pt
