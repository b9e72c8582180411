"""RG-LRU linear-recurrence scan as a Pallas TPU kernel.

TPU adaptation of the recurrence h_t = a_t h_{t-1} + u_t:

  * grid = (B, W / BW, T / BT); time is the innermost sequential axis with
    the running state h in VMEM scratch, so HBM traffic is exactly one read
    of (a, u) and one write of h — the scan is bandwidth-bound and this is
    its roofline minimum.
  * Within a (BT, BW) tile the kernel runs a `lax.fori_loop` over the BT
    time steps of VREG-resident rows; BW = 128 lanes wide keeps the VPU
    fully occupied (the recurrence is elementwise — no MXU use).
  * Blocking T only changes *when* state crosses tiles, not the math:
    tile t consumes the scratch state left by tile t-1.

GPU-vs-TPU note (DESIGN.md §Hardware adaptation): the original Griffin
implementation leans on a custom CUDA scan over shared memory; on TPU the
natural analogue is exactly this VMEM-resident streaming scan.
"""
from __future__ import annotations

import functools
from typing import Optional, Tuple

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu


def _rglru_kernel(a_ref, u_ref, h0_ref, h_ref, hlast_ref, state_scr,
                  *, block_t: int, nt: int):
    ti = pl.program_id(2)

    @pl.when(ti == 0)
    def _init():
        state_scr[...] = h0_ref[0].astype(jnp.float32)

    a = a_ref[0].astype(jnp.float32)  # (BT, BW)
    u = u_ref[0].astype(jnp.float32)

    def body(t, h):
        h = a[t] * h + u[t]
        h_ref[0, t] = h.astype(h_ref.dtype)
        return h

    h = jax.lax.fori_loop(0, block_t, body, state_scr[...])
    state_scr[...] = h

    @pl.when(ti == nt - 1)
    def _fin():
        hlast_ref[0] = h.astype(hlast_ref.dtype)


def rglru_scan(
    a: jnp.ndarray,  # (B, T, W)
    u: jnp.ndarray,  # (B, T, W)
    h0: Optional[jnp.ndarray] = None,  # (B, W)
    block_t: int = 256,
    block_w: int = 128,
    interpret: bool = True,
) -> Tuple[jnp.ndarray, jnp.ndarray]:
    """Returns (h (B, T, W), h_final (B, W))."""
    b, t, w = a.shape
    if h0 is None:
        h0 = jnp.zeros((b, w), a.dtype)
    block_t = min(block_t, t)
    block_w = min(block_w, w)
    while t % block_t:
        block_t //= 2
    while w % block_w:
        block_w //= 2
    nt, nw = t // block_t, w // block_w

    kernel = functools.partial(_rglru_kernel, block_t=block_t, nt=nt)
    compiler_params = None
    if not interpret:
        compiler_params = pltpu.CompilerParams(
            dimension_semantics=("parallel", "parallel", "arbitrary")
        )
    h, hlast = pl.pallas_call(
        kernel,
        grid=(b, nw, nt),
        in_specs=[
            pl.BlockSpec((1, block_t, block_w), lambda bi, wi, ti: (bi, ti, wi)),
            pl.BlockSpec((1, block_t, block_w), lambda bi, wi, ti: (bi, ti, wi)),
            pl.BlockSpec((1, block_w), lambda bi, wi, ti: (bi, wi)),
        ],
        out_specs=[
            pl.BlockSpec((1, block_t, block_w), lambda bi, wi, ti: (bi, ti, wi)),
            pl.BlockSpec((1, block_w), lambda bi, wi, ti: (bi, wi)),
        ],
        out_shape=[
            jax.ShapeDtypeStruct((b, t, w), a.dtype),
            jax.ShapeDtypeStruct((b, w), a.dtype),
        ],
        scratch_shapes=[pltpu.VMEM((block_w,), jnp.float32)],
        interpret=interpret,
        compiler_params=compiler_params,
    )(a, u, h0)
    return h, hlast
