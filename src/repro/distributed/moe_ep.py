"""Expert-parallel MoE with explicit all-to-all dispatch (shard_map).

§Perf hillclimb H3. The baseline `layers.moe` builds a *global* (E, C, d)
buffer and lets GSPMD pick collectives for the scatter/gather across the
token(data)- and expert(model)-sharded operands; at DeepSeek scale the
compiler's choice costs ~17.5 TB/device of wire traffic per train step.
This module replaces the dispatch with the GShard/DeepSeek schedule where
the ONLY cross-device movement is token rows:

  per device (inside shard_map):
    route local tokens -> (dest expert-shard, local expert, weight)
    pack rows into (tp, C_send, d) per-destination buffers   [local scatter]
    lax.all_to_all over the expert axis                       [wire: rows]
    pack received rows into (E_loc, C_loc, d)                 [local scatter]
    expert FFN (batched matmul over E_loc)
    reverse the two packings + all_to_all                     [wire: rows]
    weighted combine into (T_dev, d)

Wire bytes per device per layer ~= 2 * T_dev * k * cf * d * dtype — the
information-theoretic floor for top-k EP (DeepSeek's node-limited routing
would shrink it further by restricting k to fewer shards; noted in
EXPERIMENTS.md as future work).

The mesh is provided by a module-level context (set by launch.dryrun /
launch.train before tracing) because ModelConfig must stay hashable.
"""
from __future__ import annotations

from typing import Optional, Tuple

import jax
import jax.numpy as jnp
from jax import shard_map
from jax.sharding import Mesh, PartitionSpec as P

F32 = jnp.float32

_CTX = {"mesh": None, "token_axes": ("data",), "expert_axis": "model"}


def set_moe_mesh(mesh: Optional[Mesh], token_axes=("data",),
                 expert_axis="model"):
    _CTX["mesh"] = mesh
    _CTX["token_axes"] = tuple(token_axes)
    _CTX["expert_axis"] = expert_axis


def current_moe_mesh():
    return _CTX["mesh"], _CTX["token_axes"], _CTX["expert_axis"]


def _pack(rows, dest, slot, keep, n_dest, cap):
    """Scatter rows (N, d) into (n_dest, cap, d) by (dest, slot)."""
    idx = jnp.where(keep, dest * cap + slot, n_dest * cap)
    buf = jnp.zeros((n_dest * cap + 1, rows.shape[-1]), rows.dtype)
    buf = buf.at[idx].set(rows)
    return buf[:-1].reshape(n_dest, cap, rows.shape[-1])


def moe_ep(p, x, cfg):
    """Drop-in for layers.moe when a mesh context is set."""
    mesh, token_axes, ax = current_moe_mesh()
    mo = cfg.moe
    tp = mesh.shape[ax]
    e_loc = mo.n_experts // tp
    b, s, d = x.shape

    def local(xt, router, router_bias, w_gate, w_up, w_down, shared):
        # xt: (B_loc, S, d) — REPLICATED across the expert axis. Each
        # model-rank dispatches only its 1/tp token slice (sequence-sharded
        # dispatch; without this every rank ships identical rows: 16x
        # redundant a2a AND expert compute — the refuted first cut of H3).
        t_full = xt.shape[0] * xt.shape[1]
        xf_full = xt.reshape(t_full, d)
        rank = jax.lax.axis_index(ax)
        t = t_full // tp
        xf = jax.lax.dynamic_slice_in_dim(xf_full, rank * t, t, 0)
        logits = (xf.astype(F32) @ router.astype(F32))  # (T, E) replicated W
        if mo.router == "sigmoid":
            scores = jax.nn.sigmoid(logits)
            sel = scores + router_bias[None, :]
        else:
            scores = jax.nn.softmax(logits, axis=-1)
            sel = scores
        topw, tope = jax.lax.top_k(sel, mo.top_k)  # (T, k)
        gatew = jnp.take_along_axis(scores, tope, axis=-1)
        if mo.router == "sigmoid":
            gatew = gatew / jnp.maximum(gatew.sum(-1, keepdims=True), 1e-9)

        flat_e = tope.reshape(-1)  # (T*k,)
        dest = flat_e // e_loc  # destination expert-shard
        local_e = flat_e % e_loc
        # send capacity per destination shard
        cap_s = int(t * mo.top_k / tp * mo.capacity_factor) + 1
        onehot = jax.nn.one_hot(dest, tp, dtype=jnp.int32)
        pos = jnp.cumsum(onehot, axis=0) - onehot
        slot = jnp.take_along_axis(pos, dest[:, None], axis=1)[:, 0]
        keep = slot < cap_s
        rows = jnp.repeat(xf, mo.top_k, axis=0)
        send = _pack(rows, dest, slot, keep, tp, cap_s)  # (tp, C, d)
        send_le = _pack(local_e[:, None].astype(xf.dtype), dest, slot, keep,
                        tp, cap_s)[..., 0]  # (tp, C) local expert ids
        send_ok = _pack(jnp.ones((t * mo.top_k, 1), xf.dtype), dest, slot,
                        keep, tp, cap_s)[..., 0]  # (tp, C) validity

        recv = jax.lax.all_to_all(send, ax, 0, 0, tiled=True)
        recv_le = jax.lax.all_to_all(send_le, ax, 0, 0, tiled=True)
        recv_ok = jax.lax.all_to_all(send_ok, ax, 0, 0, tiled=True)

        # pack received rows by local expert
        r = recv.reshape(tp * cap_s, d)
        rl = recv_le.reshape(-1).astype(jnp.int32)
        rok = recv_ok.reshape(-1) > 0.5
        # stage-1 already applied the capacity factor; sizing stage 2 at the
        # mean load avoids paying cf^2 in expert compute and HBM (Perf H5)
        cap_e = int(tp * cap_s / e_loc) + 1
        oh = jax.nn.one_hot(rl, e_loc, dtype=jnp.int32) * rok[:, None]
        pos2 = jnp.cumsum(oh, axis=0) - oh
        slot2 = jnp.take_along_axis(pos2, rl[:, None], axis=1)[:, 0]
        keep2 = rok & (slot2 < cap_e)
        ebuf = _pack(r, rl, slot2, keep2, e_loc, cap_e)  # (E_loc, C_e, d)

        up = jnp.einsum("ecd,edf->ecf", ebuf.astype(F32), w_up.astype(F32))
        gate = jnp.einsum("ecd,edf->ecf", ebuf.astype(F32), w_gate.astype(F32))
        h = jax.nn.silu(gate) * up
        out = jnp.einsum("ecf,efd->ecd", h, w_down.astype(F32)).astype(xf.dtype)

        # unpack: rows back to (tp*C) order, then reverse a2a
        flat_idx = jnp.where(keep2, rl * cap_e + slot2, e_loc * cap_e - 1)
        back = out.reshape(e_loc * cap_e, d)[flat_idx]
        back = jnp.where(keep2[:, None], back, 0.0).reshape(tp, cap_s, d)
        ret = jax.lax.all_to_all(back, ax, 0, 0, tiled=True)  # (tp, C, d)

        # combine at the source: row j of (dest, slot) came from token slot
        retf = ret.reshape(tp * cap_s, d)
        src_idx = jnp.where(keep, dest * cap_s + slot, tp * cap_s - 1)
        y = retf[src_idx]
        y = jnp.where(keep[:, None], y, 0.0)
        y = y * gatew.reshape(-1)[:, None].astype(y.dtype)
        y = y.reshape(t, mo.top_k, d).sum(axis=1)
        if shared is not None:
            from repro.models.layers import mlp

            y = y + mlp(shared, xf, "silu")
        # re-assemble the full token dim (outputs were token-sharded over
        # the expert axis for the dispatch)
        y_full = jax.lax.all_gather(y, ax, axis=0, tiled=True)
        return y_full.reshape(xt.shape)

    shared = p.get("shared")
    in_specs = (
        P(_CTX["token_axes"], None, None),  # x
        P(), P(),  # router, bias
        P(ax, None, None), P(ax, None, None), P(ax, None, None),  # experts
        (jax.tree.map(lambda _: P(), shared) if shared is not None else None),
    )
    fn = shard_map(
        local, mesh=mesh,
        in_specs=in_specs,
        out_specs=P(_CTX["token_axes"], None, None),
        check_vma=False,
    )
    return fn(x, p["router"], p["router_bias"], p["w_gate"], p["w_up"],
              p["w_down"], shared)
