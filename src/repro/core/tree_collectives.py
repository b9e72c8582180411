"""Binary-tree collectives over a mesh axis, from the paper's addressing.

Devices on an axis of size P are peers on a ring with equally-spaced
addresses (device i owns ((i-1)*S, i*S], S = 2^d / P). For power-of-two P
the induced tree (paper §2) is the PERFECT binary tree, computable locally:

    parent(i)  = i - m            if i & (m << 1)   (m = lowbit(i))
                 (i + m) mod P    otherwise          — and parent of the
                 top node 2^(k-1) is the root 0
    children(i = p*2^k)           = i ± 2^(k-1)      (CW / CCW)

which is exactly UP/CW/CCW of `repro.core.addressing` evaluated at address
i*S. The collectives below schedule one `lax.ppermute` per tree level:

    tree_reduce      convergecast: leaves->root,  log2(P) steps
    tree_broadcast   root->leaves,                log2(P) steps
    tree_all_reduce  convergecast + broadcast,  2*log2(P) steps

Cost model (DESIGN.md §6): latency 2*log2(P)*alpha vs ring's 2*(P-1)*alpha;
bandwidth ~2x ring for large tensors. Use for small/latency-bound tensors
(violation votes, alerts, control state) and cross-pod reduction of
*compressed* gradients; keep XLA's ring all-reduce for bulk dense grads.

All functions are shard_map-kernels: call them inside
`shard_map(..., mesh, in_specs=P(axis_name, ...), ...)` or via the
`*_spmd` wrappers that set that up.
"""
from __future__ import annotations

import functools
from typing import Optional

import jax
import jax.numpy as jnp
from jax import shard_map
from jax.sharding import Mesh, NamedSharding, PartitionSpec as P


def _levels(p: int) -> int:
    assert p & (p - 1) == 0 and p > 0, f"tree collectives need 2^k devices, got {p}"
    return p.bit_length() - 1


def _parent(i: int, p: int) -> int:
    m = i & (-i)
    if i == 0:
        return 0
    if i == m and (i << 1) == p:  # top node 2^(k-1) -> root 0
        return 0
    return i - m if i & (m << 1) else (i + m) % p


def _level_nodes(axis_size: int, lvl: int):
    """Nodes whose lowbit is 2^lvl (tree depth k - lvl), excluding the root.

    Each parent has one CW child (parent = i - m) and one CCW child
    (parent = i + m); they are sent in two ppermute rounds because a
    ppermute destination must be unique. On a torus the sibling transfers
    use opposite-direction links, so the two rounds overlap on hardware.
    """
    nodes = [
        i for i in range(axis_size)
        if i != 0 and (i & ((1 << (lvl + 1)) - 1)) == (1 << lvl)
    ]
    m = 1 << lvl
    cw = [i for i in nodes if i & (m << 1) or (i << 1) == axis_size]
    ccw = [i for i in nodes if i not in cw]
    return cw, ccw


def _masked_add(x, recv, idx, perm, combine):
    if not perm:
        return x
    is_recv = jnp.zeros((), bool)
    for (_, dst) in perm:
        is_recv = is_recv | (idx == dst)
    return jnp.where(is_recv, combine(x, recv), x)


def tree_reduce(x: jnp.ndarray, axis_name: str, axis_size: int) -> jnp.ndarray:
    """Convergecast sum: the root (index 0) holds the total; others hold
    partials. Two ppermutes per level (CW/CCW siblings), leaves first
    (paper: messages routed UP accumulate the subtree's knowledge)."""
    k = _levels(axis_size)
    idx = jax.lax.axis_index(axis_name)
    for lvl in range(k):
        cw, ccw = _level_nodes(axis_size, lvl)
        for group in (cw, ccw):
            perm = [(i, _parent(i, axis_size)) for i in group]
            if not perm:
                continue
            recv = jax.lax.ppermute(x, axis_name, perm)
            x = _masked_add(x, recv, idx, perm, lambda a, b: a + b)
    return x


def tree_broadcast(x: jnp.ndarray, axis_name: str, axis_size: int) -> jnp.ndarray:
    """Root's value to everyone, top level first."""
    k = _levels(axis_size)
    idx = jax.lax.axis_index(axis_name)
    for lvl in reversed(range(k)):
        cw, ccw = _level_nodes(axis_size, lvl)
        for group in (cw, ccw):
            perm = [(_parent(i, axis_size), i) for i in group]
            if not perm:
                continue
            recv = jax.lax.ppermute(x, axis_name, perm)
            x = _masked_add(x, recv, idx, perm, lambda a, b: b)
    return x


def tree_all_reduce(x: jnp.ndarray, axis_name: str, axis_size: int) -> jnp.ndarray:
    return tree_broadcast(tree_reduce(x, axis_name, axis_size), axis_name, axis_size)


def tree_all_reduce_spmd(x, mesh: Mesh, axis_name: str):
    """Replicated-in, replicated-out tree all-reduce over `axis_name`."""
    size = mesh.shape[axis_name]
    other = tuple(a for a in mesh.axis_names if a != axis_name)

    @functools.partial(
        shard_map, mesh=mesh, in_specs=P(), out_specs=P(),
        check_vma=False,
    )
    def run(v):
        return tree_all_reduce(v, axis_name, size)

    return run(x)
