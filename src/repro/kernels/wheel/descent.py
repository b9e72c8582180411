"""The R1 internal-descent tail as a blocked Pallas kernel.

After the cycle's two full-width `deliver_rules` steps, only a few
percent of the drain window is still descending; the engine compacts
the survivors to `narrow` width and finishes them with a live-mask
`lax.while_loop` (`jax_backend.deliver_network_step`). This kernel runs
that exact loop *blocked*: the survivor batch is tiled over a grid and
each block iterates its own while_loop in registers/VMEM — descent
depth is data-dependent per block, so blocks that settle early stop
early instead of riding the global worst case.

The loop body is `protocol.deliver_rules` traced with `xp = jnp` inside
the kernel (the same addressing bit algebra both backends share), so
parity against `descent_reference` — the loop `deliver_network_step`
runs — is by construction: identical ops on identical values. Rows whose block
terminates are masked, exactly like the reference's global live mask.

Bools cross the kernel boundary as int32, and the loop carries its
masks as int32 too (Mosaic neither carries nor selects bool vectors);
addresses stay uint32 throughout.
"""
from __future__ import annotations

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl

from repro.engine import protocol as proto
from repro.kernels.wheel._common import compiler_params, in_segment, on_tpu, pad_to

_I32 = jnp.int32
_U32 = jnp.uint32


def _descent_loop(origin, dest, edge, has_edge, live, entry, pos_i, a_prev,
                  a_self, self_seg, max_addr, d: int):
    """The shared while_loop body — called by both the reference (full
    width) and the kernel (per block). Returns (acc, drop, o_dest,
    o_edge, o_he) with the exact `deliver_network_step` semantics.

    The loop carries its masks as int32 0/1: Mosaic cannot carry bool
    vectors through a while loop, and the values are the same either
    way."""
    b = lambda m: m != 0
    i = lambda m: m.astype(_I32)

    def cond(c):
        return b(c[0]).any()

    def body(c):
        (lv, ent, cur_dest, cur_edge, cur_he,
         acc, drop, o_dest, o_edge, o_he) = c
        lv, ent, cur_he = b(lv), b(ent), b(cur_he)
        dlv = proto.deliver_rules(
            jnp, origin=origin, dest=cur_dest, edge=cur_edge,
            has_edge=cur_he, network_entry=ent, pos_i=pos_i,
            a_prev=a_prev, a_self=a_self, self_seg=self_seg,
            max_addr=max_addr, d=d, repair=True,
        )
        now_acc = lv & dlv.accept
        now_drop = lv & dlv.drop & ~dlv.accept
        moving = lv & ~dlv.accept & ~dlv.drop
        stay = moving & in_segment(dlv.new_dest, a_prev, a_self)
        fwd = moving & ~stay
        return (
            i(stay), i(ent & ~stay),
            jnp.where(stay, dlv.new_dest, cur_dest),
            jnp.where(stay, dlv.new_edge, cur_edge),
            jnp.where(stay, i(dlv.new_has_edge), i(cur_he)),
            acc | i(now_acc), drop | i(now_drop),
            jnp.where(fwd, dlv.new_dest, o_dest),
            jnp.where(fwd, dlv.new_edge, o_edge),
            jnp.where(fwd, i(dlv.new_has_edge), o_he),
        )

    zero = jnp.zeros(live.shape, _I32)
    init = (i(live), i(entry), dest, edge, i(has_edge),
            zero, zero, dest, edge, i(has_edge))
    (_, _, _, _, _, acc, drop, o_dest, o_edge, o_he) = jax.lax.while_loop(
        cond, body, init)
    return b(acc), b(drop), o_dest, o_edge, b(o_he)


def descent_reference(origin, dest, edge, has_edge, live, entry, pos_i,
                      a_prev, a_self, self_seg, max_addr, d: int):
    """XLA-path reference: one global while_loop over the whole batch —
    the loop `jax_backend.deliver_network_step` runs."""
    return _descent_loop(origin, dest, edge, has_edge, live, entry, pos_i,
                         a_prev, a_self, self_seg, max_addr, d)


def descent_tail_kernel(origin, dest, edge, has_edge, live, entry, pos_i,
                        a_prev, a_self, self_seg, max_addr, d: int,
                        block: int = 512, interpret: bool = True):
    m = origin.shape[0]
    block = min(block, max(m, 1))
    mp = m + (-m % block)
    nb = mp // block
    row_u = lambda a: pad_to(a.astype(_U32), mp)[None, :]
    row_b = lambda a: pad_to(a.astype(_I32), mp)[None, :]  # bools as i32

    def kern(orig_ref, dest_ref, edge_ref, he_ref, live_ref, ent_ref,
             pos_ref, aprev_ref, aself_ref, sseg_ref, ma_ref,
             acc_ref, drop_ref, od_ref, oe_ref, ohe_ref):
        b = lambda r: r[...] != 0
        acc, drop, od, oe, ohe = _descent_loop(
            orig_ref[...], dest_ref[...], edge_ref[...], b(he_ref),
            b(live_ref), b(ent_ref), pos_ref[...], aprev_ref[...],
            aself_ref[...], b(sseg_ref), ma_ref[0, 0], d)
        acc_ref[...] = acc.astype(_I32)
        drop_ref[...] = drop.astype(_I32)
        od_ref[...] = od
        oe_ref[...] = oe
        ohe_ref[...] = ohe.astype(_I32)

    spec = pl.BlockSpec((1, block), lambda i: (0, i))
    spec_s = pl.BlockSpec((1, 1), lambda i: (0, 0))
    shp_u = jax.ShapeDtypeStruct((1, mp), _U32)
    shp_i = jax.ShapeDtypeStruct((1, mp), _I32)
    acc, drop, od, oe, ohe = pl.pallas_call(
        kern,
        grid=(nb,),
        in_specs=[spec] * 10 + [spec_s],
        out_specs=[spec] * 5,
        out_shape=[shp_i, shp_i, shp_u, shp_u, shp_i],
        interpret=interpret,
        name="wheel_descent",
        compiler_params=compiler_params(interpret),
    )(row_u(origin), row_u(dest), row_u(edge), row_b(has_edge),
      row_b(live), row_b(entry), row_u(pos_i), row_u(a_prev),
      row_u(a_self), row_b(self_seg),
      jnp.asarray(max_addr, _U32).reshape(1, 1))
    sl = lambda a: a[0, :m]
    return (sl(acc).astype(bool), sl(drop).astype(bool),
            sl(od), sl(oe), sl(ohe).astype(bool))


def descent_tail(origin, dest, edge, has_edge, live, entry, pos_i, a_prev,
                 a_self, self_seg, max_addr, d: int, use_kernel: bool = True,
                 block: int = 512, interpret=None):
    """Dispatch: blocked Pallas descent, or the global-while reference."""
    if use_kernel and origin.shape[0] >= 8:
        if interpret is None:
            interpret = not on_tpu()
        return descent_tail_kernel(
            origin, dest, edge, has_edge, live, entry, pos_i, a_prev,
            a_self, self_seg, max_addr, d, block=block, interpret=interpret)
    return descent_reference(origin, dest, edge, has_edge, live, entry,
                             pos_i, a_prev, a_self, self_seg, max_addr, d)
