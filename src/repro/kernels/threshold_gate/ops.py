"""Jit'd wrapper for threshold compression (no VJP: runs on gradients)."""
from __future__ import annotations

from typing import Tuple

import jax.numpy as jnp

from repro.kernels import on_tpu

from .ref import threshold_gate_reference
from .threshold_gate import threshold_gate_kernel


def threshold_gate(
    grad: jnp.ndarray,
    residual: jnp.ndarray,
    tau,
    use_kernel: bool = True,
) -> Tuple[jnp.ndarray, jnp.ndarray, jnp.ndarray]:
    """(send, new_residual, n_sent). See ref.py for semantics."""
    tau = jnp.asarray(tau, jnp.float32)
    if use_kernel and grad.size >= 8:
        return threshold_gate_kernel(grad, residual, tau,
                                     interpret=not on_tpu())
    return threshold_gate_reference(grad, residual, tau)
