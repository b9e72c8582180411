"""Jit'd wrapper for the RG-LRU scan with custom VJP.

The backward of a diagonal linear recurrence is itself a (reversed) diagonal
linear recurrence:  given  h_t = a_t h_{t-1} + u_t  and cotangent g_t,
  dL/du_t = G_t   where  G_t = g_t + a_{t+1} G_{t+1}   (reverse scan)
  dL/da_t = G_t * h_{t-1}
  dL/dh0  = a_1 * G_1
so the VJP reuses the same kernel on time-reversed inputs.
"""
from __future__ import annotations

import functools
from typing import Optional, Tuple

import jax
import jax.numpy as jnp

from repro.kernels import on_tpu

from .ref import linear_scan_reference
from .rglru import rglru_scan


def _scan(a, u, h0, use_kernel):
    t, w = a.shape[1], a.shape[2]
    if use_kernel and t >= 8 and w >= 8:
        return rglru_scan(a, u, h0, interpret=not on_tpu())
    return linear_scan_reference(a, u, h0)


@functools.partial(jax.custom_vjp, nondiff_argnums=(3,))
def linear_scan(
    a: jnp.ndarray, u: jnp.ndarray, h0: Optional[jnp.ndarray] = None,
    use_kernel: bool = True,
) -> Tuple[jnp.ndarray, jnp.ndarray]:
    """h_t = a_t h_{t-1} + u_t. Returns (h (B,T,W), h_last (B,W))."""
    return _scan(a, u, h0, use_kernel)


def _fwd(a, u, h0, use_kernel):
    h, hlast = _scan(a, u, h0, use_kernel)
    return (h, hlast), (a, h, h0)


def _bwd(use_kernel, res, cts):
    a, h, h0 = res
    g, g_last = cts
    b, t, w = a.shape
    if h0 is None:
        h0 = jnp.zeros((b, w), a.dtype)
    g = g.at[:, -1].add(g_last)
    # reverse scan: G_t = g_t + a_{t+1} G_{t+1}
    a_rev = jnp.flip(jnp.concatenate([a[:, 1:], jnp.zeros((b, 1, w), a.dtype)], 1), 1)
    G_rev, _ = _scan(a_rev, jnp.flip(g, 1), None, use_kernel)
    G = jnp.flip(G_rev, 1)
    h_prev = jnp.concatenate([h0[:, None, :], h[:, :-1]], axis=1)
    da = G * h_prev
    du = G
    dh0 = a[:, 0] * G[:, 0]
    return da.astype(a.dtype), du.astype(a.dtype), dh0.astype(a.dtype)


linear_scan.defvjp(_fwd, _bwd)
