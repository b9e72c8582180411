"""Error-feedback threshold compression as a Pallas TPU kernel.

Fuses accumulate + threshold + residual-update + violation-count into one
bandwidth-bound pass (3 reads, 2 writes + one scalar per block), instead of
the 5-pass XLA decomposition. Layout:

  * inputs flattened to (N,) and tiled (BLOCK,) wide; BLOCK = 64k elements
    (256 KB fp32) keeps each pipeline stage well under VMEM while amortizing
    grid overhead;
  * tau arrives as a (1, 1) SMEM scalar — it changes every step in the
    adaptive-threshold controller, so it must not be baked into the program;
  * per-block counts are written to a (nblocks,) vector and summed by the
    caller (cheap, avoids cross-block atomics which TPUs do not have).
"""
from __future__ import annotations

import functools
from typing import Tuple

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu


def _tg_kernel(tau_ref, g_ref, r_ref, send_ref, newres_ref, cnt_ref):
    tau = tau_ref[0, 0]
    acc = g_ref[...].astype(jnp.float32) + r_ref[...].astype(jnp.float32)
    mask = jnp.abs(acc) >= tau
    send = jnp.where(mask, acc, 0.0)
    send_ref[...] = send.astype(send_ref.dtype)
    newres_ref[...] = (acc - send).astype(newres_ref.dtype)
    cnt_ref[0] = jnp.sum(mask.astype(jnp.int32))


def threshold_gate_kernel(
    grad: jnp.ndarray,  # any shape
    residual: jnp.ndarray,
    tau: jnp.ndarray,  # scalar
    block: int = 65536,
    interpret: bool = True,
) -> Tuple[jnp.ndarray, jnp.ndarray, jnp.ndarray]:
    shape = grad.shape
    g = grad.reshape(-1)
    r = residual.reshape(-1)
    n = g.shape[0]
    block = min(block, n)
    pad = (-n) % block
    if pad:
        g = jnp.pad(g, (0, pad))
        # pad residual with -inf-proof zeros; padded lanes produce send=0
        r = jnp.pad(r, (0, pad))
    nb = g.shape[0] // block
    tau2d = jnp.asarray(tau, jnp.float32).reshape(1, 1)

    compiler_params = None
    if not interpret:
        compiler_params = pltpu.CompilerParams(
            dimension_semantics=("arbitrary",)
        )
    send, newres, cnt = pl.pallas_call(
        _tg_kernel,
        grid=(nb,),
        in_specs=[
            pl.BlockSpec(memory_space=pltpu.MemorySpace.SMEM),
            pl.BlockSpec((block,), lambda i: (i,)),
            pl.BlockSpec((block,), lambda i: (i,)),
        ],
        out_specs=[
            pl.BlockSpec((block,), lambda i: (i,)),
            pl.BlockSpec((block,), lambda i: (i,)),
            pl.BlockSpec((1,), lambda i: (i,)),
        ],
        out_shape=[
            jax.ShapeDtypeStruct(g.shape, grad.dtype),
            jax.ShapeDtypeStruct(g.shape, residual.dtype),
            jax.ShapeDtypeStruct((nb,), jnp.int32),
        ],
        interpret=interpret,
        compiler_params=compiler_params,
    )(tau2d, g, r)
    if pad:
        # padded lanes: acc = 0 -> |acc| >= tau may count them when tau == 0
        pad_mask_count = jnp.where(jnp.asarray(tau, jnp.float32) <= 0.0, pad, 0)
        send, newres = send[:n], newres[:n]
        total = cnt.sum() - pad_mask_count
    else:
        total = cnt.sum()
    return send.reshape(shape), newres.reshape(shape), total.astype(jnp.int32)
