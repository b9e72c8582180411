"""Flash attention Pallas TPU kernel (causal / sliding-window, GQA).

Online-softmax blockwise attention in the FlashAttention-2 style, adapted to
the TPU memory hierarchy:

  * grid = (B * Hq, Sq / BQ, Skv / BK); the KV axis is the innermost
    ("arbitrary") dimension so the (m, l, acc) running state lives in VMEM
    scratch across KV steps while Q/K/V blocks are streamed HBM -> VMEM by
    the BlockSpec pipeline.
  * BQ/BK default to 128/256 so QK^T and PV land on MXU-aligned shapes;
    Dh is expected to be a multiple of 128 on real hardware (pad otherwise;
    interpret-mode tests also sweep unaligned shapes).
  * GQA is folded into the K/V index_map (kv head = q head // group) — no
    materialized head repetition, which keeps HBM traffic at Hkv scale.
  * VMEM budget at defaults: q 128x128x4 + k/v 2x256x128x4 + acc 128x128x4
    + m/l 2x128x4 ~ 0.4 MB per double-buffered pipeline stage — far under
    the ~16 MB/core VMEM, leaving room for the pipeline's second buffer.

The backward pass recomputes from the reference under `jax.custom_vjp` (see
ops.py): on TPU the XLA-fused backward of the reference formula is close to
a hand-written bwd kernel at these head dims, and keeping one kernel keeps
the sweep-test matrix tractable.
"""
from __future__ import annotations

import functools
from typing import Optional

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu

NEG_INF = -1e30


def _flash_kernel(
    q_ref, k_ref, v_ref, o_ref, m_scr, l_scr, acc_scr,
    *, scale: float, causal: bool, window: Optional[int],
    block_q: int, block_k: int, nk: int, q_offset: int,
):
    qi = pl.program_id(1)
    ki = pl.program_id(2)

    @pl.when(ki == 0)
    def _init():
        m_scr[...] = jnp.full_like(m_scr, NEG_INF)
        l_scr[...] = jnp.zeros_like(l_scr)
        acc_scr[...] = jnp.zeros_like(acc_scr)

    # block visibility (H1 on TPU): fully-masked blocks skip the MXU work —
    # the grid still visits them (static TPU grids) but pays only the guard
    visible = jnp.bool_(True)
    if causal:
        visible &= ki * block_k <= q_offset + qi * block_q + block_q - 1
    if window is not None:
        visible &= (ki + 1) * block_k - 1 > q_offset + qi * block_q - window

    @pl.when(visible)
    def _body():
        q = q_ref[0].astype(jnp.float32)  # (BQ, Dh)
        k = k_ref[0].astype(jnp.float32)  # (BK, Dh)
        v = v_ref[0].astype(jnp.float32)  # (BK, Dh)

        s = jax.lax.dot_general(
            q, k, (((1,), (1,)), ((), ())), preferred_element_type=jnp.float32
        ) * scale  # (BQ, BK)

        qpos = q_offset + qi * block_q + jax.lax.broadcasted_iota(
            jnp.int32, (block_q, block_k), 0
        )
        kpos = ki * block_k + jax.lax.broadcasted_iota(
            jnp.int32, (block_q, block_k), 1
        )
        mask = jnp.ones((block_q, block_k), bool)
        if causal:
            mask &= kpos <= qpos
        if window is not None:
            mask &= kpos > qpos - window
        s = jnp.where(mask, s, NEG_INF)

        m_prev = m_scr[...]
        m_new = jnp.maximum(m_prev, jnp.max(s, axis=-1))
        alpha = jnp.exp(m_prev - m_new)
        p = jnp.exp(s - m_new[:, None])
        p = jnp.where(mask, p, 0.0)
        l_scr[...] = l_scr[...] * alpha + jnp.sum(p, axis=-1)
        acc_scr[...] = acc_scr[...] * alpha[:, None] + jax.lax.dot_general(
            p, v, (((1,), (0,)), ((), ())), preferred_element_type=jnp.float32
        )
        m_scr[...] = m_new

    @pl.when(ki == nk - 1)
    def _out():
        l = jnp.maximum(l_scr[...], 1e-30)
        o_ref[0] = (acc_scr[...] / l[:, None]).astype(o_ref.dtype)


def flash_attention_fwd(
    q: jnp.ndarray,  # (B, Hq, Sq, Dh)
    k: jnp.ndarray,  # (B, Hkv, Skv, Dh)
    v: jnp.ndarray,
    causal: bool = True,
    window: Optional[int] = None,
    scale: Optional[float] = None,
    q_offset: int = 0,
    block_q: int = 128,
    block_k: int = 256,
    interpret: bool = True,
) -> jnp.ndarray:
    """Blockwise attention; see module docstring. Returns (B, Hq, Sq, Dh)."""
    b, hq, sq, dh = q.shape
    _, hkv, skv, _ = k.shape
    dhv = v.shape[-1]
    assert hq % hkv == 0, "GQA requires Hq % Hkv == 0"
    g = hq // hkv
    if scale is None:
        scale = dh ** -0.5
    block_q = min(block_q, sq)
    block_k = min(block_k, skv)
    while sq % block_q:
        block_q //= 2
    while skv % block_k:
        block_k //= 2
    assert block_q >= 1 and block_k >= 1, (sq, block_q, skv, block_k)
    nq, nk = sq // block_q, skv // block_k

    qr = q.reshape(b * hq, sq, dh)
    kr = k.reshape(b * hkv, skv, dh)
    vr = v.reshape(b * hkv, skv, dhv)

    def kv_index(bh, qi, ki):
        return (bh // hq * hkv + (bh % hq) // g, ki, 0)

    kernel = functools.partial(
        _flash_kernel, scale=scale, causal=causal, window=window,
        block_q=block_q, block_k=block_k, nk=nk, q_offset=q_offset,
    )
    compiler_params = None
    if not interpret:
        compiler_params = pltpu.CompilerParams(
            dimension_semantics=("parallel", "parallel", "arbitrary")
        )
    out = pl.pallas_call(
        kernel,
        grid=(b * hq, nq, nk),
        in_specs=[
            pl.BlockSpec((1, block_q, dh), lambda bh, qi, ki: (bh, qi, 0)),
            pl.BlockSpec((1, block_k, dh), kv_index),
            pl.BlockSpec((1, block_k, dhv), kv_index),
        ],
        out_specs=pl.BlockSpec((1, block_q, dhv), lambda bh, qi, ki: (bh, qi, 0)),
        out_shape=jax.ShapeDtypeStruct((b * hq, sq, dhv), q.dtype),
        scratch_shapes=[
            pltpu.VMEM((block_q,), jnp.float32),
            pltpu.VMEM((block_q,), jnp.float32),
            pltpu.VMEM((block_q, dhv), jnp.float32),
        ],
        interpret=interpret,
        compiler_params=compiler_params,
    )(qr, kr, vr)
    return out.reshape(b, hq, sq, dhv)
