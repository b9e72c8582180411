"""Jit'd public wrapper for flash attention.

Dispatch order:
  1. Pallas kernel (TPU, block-aligned shapes) — forward; its VJP
     recomputes through the XLA flash path (same O(S*Dh) memory).
  2. `flash_attention_xla` — lax.scan online-softmax with a hand-written
     FA2 backward; the path every non-TPU compile (incl. the dry-run) uses.
  3. `mha_reference` — naive oracle, small/ragged shapes and tests only.
"""
from __future__ import annotations

import functools
from typing import Optional

import jax
import jax.numpy as jnp

from repro.kernels import on_tpu

from .flash_attention import flash_attention_fwd
from .ref import mha_reference
from .xla_ref import flash_attention_xla


def flash_attention(
    q, k, v,
    causal: bool = True,
    window: Optional[int] = None,
    scale: Optional[float] = None,
    q_offset: int = 0,
    use_kernel: bool = True,
):
    """(B,Hq,Sq,Dh) x (B,Hkv,Skv,Dh) -> (B,Hq,Sq,Dhv). Differentiable."""
    sq, skv = q.shape[2], k.shape[2]
    aligned = sq % 128 == 0 and skv % 128 == 0
    if use_kernel and on_tpu() and aligned:
        return _pallas_path(q, k, v, causal, window, scale, q_offset)
    if sq * skv >= 128 * 128 and skv % 16 == 0:
        return flash_attention_xla(q, k, v, causal, window, scale, q_offset)
    return mha_reference(q, k, v, causal=causal, window=window, scale=scale,
                         q_offset=q_offset)


@functools.partial(jax.custom_vjp, nondiff_argnums=(3, 4, 5, 6))
def _pallas_path(q, k, v, causal, window, scale, q_offset):
    return flash_attention_fwd(q, k, v, causal=causal, window=window,
                               scale=scale, q_offset=q_offset,
                               interpret=not on_tpu())


def _pl_fwd(q, k, v, causal, window, scale, q_offset):
    return _pallas_path(q, k, v, causal, window, scale, q_offset), (q, k, v)


def _pl_bwd(causal, window, scale, q_offset, res, g):
    q, k, v = res
    _, vjp = jax.vjp(
        lambda a, b, c: flash_attention_xla(a, b, c, causal, window, scale,
                                            q_offset), q, k, v)
    return vjp(g)


_pallas_path.defvjp(_pl_fwd, _pl_bwd)


def decode_attention(
    q: jnp.ndarray,  # (B, Hq, 1, Dh)
    k_cache: jnp.ndarray,  # (B, Hkv, S, Dh)
    v_cache: jnp.ndarray,
    length: Optional[jnp.ndarray] = None,  # (B,) valid lengths
    window: Optional[int] = None,
    scale: Optional[float] = None,
) -> jnp.ndarray:
    """Single-token decode attention over a KV cache (memory-bound; XLA).

    Masks positions >= length (per batch) and, with a window, positions
    <= length - window. The new token's K/V must already be in the cache.
    """
    b, hq, _, dh = q.shape
    _, hkv, s, _ = k_cache.shape
    dhv = v_cache.shape[-1]
    g = hq // hkv
    if scale is None:
        scale = dh ** -0.5
    qf = q.astype(jnp.float32).reshape(b, hkv, g, dh)
    sc = jnp.einsum("bhgd,bhkd->bhgk", qf, k_cache.astype(jnp.float32)) * scale
    kpos = jnp.arange(s)[None, :]
    if length is None:
        length = jnp.full((b,), s, jnp.int32)
    valid = kpos < length[:, None]
    if window is not None:
        valid &= kpos >= length[:, None] - window
    sc = jnp.where(valid[:, None, None, :], sc, -1e30)
    p = jax.nn.softmax(sc, axis=-1)
    o = jnp.einsum("bhgk,bhkd->bhgd", p, v_cache.astype(jnp.float32))
    return o.reshape(b, hq, 1, dhv).astype(q.dtype)
