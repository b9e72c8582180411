"""Fused Alg. 3 test() as a Pallas TPU kernel.

The paper's per-peer violation test is the hot inner loop when thousands of
logical peers are simulated *on-device* (the `distributed.threshold_sync`
controller runs one logical peer per DP replica, and the in-network-compute
benchmarks run millions). The kernel fuses knowledge, agreement, violation,
output and Send-payload computation into a single VPU pass.

Layout: peers on the minor axis in (3, N) planes (direction-major), so each
direction's counters form contiguous 128-lane vectors; N is tiled BLOCK
lanes at a time. All counters are int32 — the threshold test 2*ones - total
is integer-exact (no fp rounding of the paper's (1,-1/2) functional).
"""
from __future__ import annotations

from typing import Tuple

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu


def _maj_kernel(in_ones_ref, in_tot_ref, out_ones_ref, out_tot_ref, x_ref,
                viol_ref, out_ref, pay_ones_ref, pay_tot_ref):
    in_ones = in_ones_ref[...]  # (3, BN)
    in_tot = in_tot_ref[...]
    out_ones = out_ones_ref[...]
    out_tot = out_tot_ref[...]
    x = x_ref[...]  # (1, BN)

    k_ones = jnp.sum(in_ones, 0, keepdims=True) + x  # (1, BN)
    k_tot = jnp.sum(in_tot, 0, keepdims=True) + 1
    a_ones = in_ones + out_ones
    a_tot = in_tot + out_tot
    ta = 2 * a_ones - a_tot
    tka = 2 * (k_ones - a_ones) - (k_tot - a_tot)
    viol = ((ta >= 0) & (tka < 0)) | ((ta < 0) & (tka > 0))
    viol_ref[...] = viol.astype(jnp.int32)
    out_ref[...] = (2 * k_ones - k_tot >= 0).astype(jnp.int32)
    pay_ones_ref[...] = k_ones - in_ones
    pay_tot_ref[...] = k_tot - in_tot


def majority_step_kernel(
    in_ones: jnp.ndarray,   # (N, 3) int32
    in_tot: jnp.ndarray,
    out_ones: jnp.ndarray,
    out_tot: jnp.ndarray,
    x: jnp.ndarray,         # (N,)
    block: int = 4096,
    interpret: bool = True,
) -> Tuple[jnp.ndarray, jnp.ndarray, jnp.ndarray, jnp.ndarray]:
    n = x.shape[0]
    block = min(block, n)
    pad = (-n) % block
    tr = lambda a: jnp.pad(a.astype(jnp.int32).T, ((0, 0), (0, pad)))  # (3, N+)
    io, it, oo, ot = tr(in_ones), tr(in_tot), tr(out_ones), tr(out_tot)
    xv = jnp.pad(x.astype(jnp.int32)[None, :], ((0, 0), (0, pad)))
    nb = (n + pad) // block

    compiler_params = None
    if not interpret:
        compiler_params = pltpu.CompilerParams(
            dimension_semantics=("parallel",)
        )
    spec3 = pl.BlockSpec((3, block), lambda i: (0, i))
    spec1 = pl.BlockSpec((1, block), lambda i: (0, i))
    viol, out, pay_ones, pay_tot = pl.pallas_call(
        _maj_kernel,
        grid=(nb,),
        in_specs=[spec3, spec3, spec3, spec3, spec1],
        out_specs=[spec3, spec1, spec3, spec3],
        out_shape=[
            jax.ShapeDtypeStruct((3, n + pad), jnp.int32),
            jax.ShapeDtypeStruct((1, n + pad), jnp.int32),
            jax.ShapeDtypeStruct((3, n + pad), jnp.int32),
            jax.ShapeDtypeStruct((3, n + pad), jnp.int32),
        ],
        interpret=interpret,
        compiler_params=compiler_params,
    )(io, it, oo, ot, xv)
    return (
        viol[:, :n].T.astype(bool),
        out[0, :n],
        pay_ones[:, :n].T,
        pay_tot[:, :n].T,
    )
