"""Shared plumbing for the delivery-wheel Pallas kernels.

Every wheel kernel follows the `majority_step` conventions
(DESIGN.md §Kernels): a `use_kernel` dispatch flag with an XLA-path
reference that is the *definition* of the semantics, and `interpret`
defaulting to "everywhere but a real TPU" (interpret mode is the
parity-test surface, never the throughput path).
"""
from __future__ import annotations

import jax.numpy as jnp
from jax.experimental.pallas import tpu as pltpu

from repro.kernels import on_tpu  # noqa: F401  (re-export)


def compiler_params(interpret: bool, ndims: int = 1):
    """Parallel-grid compiler params, or None under interpret mode."""
    if interpret:
        return None
    return pltpu.CompilerParams(dimension_semantics=("parallel",) * ndims)


def in_segment(addr, a_prev, a_self):
    """Does `addr` fall in the ring segment (a_prev, a_self]? The one
    definition: the engine's `JaxEngine._in_segment` is this function.
    Bools combine by mask algebra — a TPU kernel cannot select bools."""
    wrapped = a_prev >= a_self
    inside = (addr > a_prev) & (addr <= a_self)
    inside_wrap = (addr > a_prev) | (addr <= a_self)
    return (wrapped & inside_wrap) | (~wrapped & inside)


def pad_to(a: jnp.ndarray, size: int, axis: int = 0, fill=0) -> jnp.ndarray:
    """Pad `a` along `axis` up to `size` rows with `fill`."""
    cur = a.shape[axis]
    if cur == size:
        return a
    widths = [(0, 0)] * a.ndim
    widths[axis] = (0, size - cur)
    return jnp.pad(a, widths, constant_values=fill)
