"""Fused due-scan accept-dedup kernel: window-local winner election.

The engine's ACCEPT phase elects one data winner per (peer, direction)
link per cycle, a representative window row per touched peer for the
react, and the alert force mask. The XLA path does this through the
dense per-link scatter-max plane (`PeerPlane.link_max` over pad*3
cells, then gathers back) — O(pad) memory traffic for an O(window)
question.

This kernel answers the question window-locally instead: for window
rows i, j (both <= WW), row j beats row i on the same link iff
``flat[j] == flat[i]`` — a blocked O(WW^2) all-pairs max that is pure
VPU compute (no scatter, no O(pad) plane). The window it sees is the
SHARD-LOCAL drain window: under the owner-partitioned wheel every row
already lives in the lane of its DEST owner, so winner election is
lane-local by invariant and the kernel runs per shard with no
collective either way. One fused pass accumulates, per window row,

  * ``best``  — max window index of an accepting DATA row on its link,
  * ``abest`` — same for ALERT rows,
  * ``rep``   — max accepting window index over the row's whole peer
                (the react representative, = peer_dirmax(max(best,
                abest))),
  * ``aforce``— per direction, did ANY alert accept at the row's peer,

and finalizes the elementwise decisions (winner / loser / fresh /
alert_write / is_rep) on the last j-block. Winner election is a
deterministic max, so the window-local and plane formulations are
bit-identical — `due_dedup_reference` below IS the plane formulation
(mirroring the engine's fallback path), and the parity tests drive the
kernel against it.

Grid: (i-blocks, j-blocks), j innermost and sequential (accumulation in
the output refs, init at j == 0); the i dimension is parallel.
"""
from __future__ import annotations

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl

from jax.experimental.pallas import tpu as pltpu
from repro.kernels.wheel._common import on_tpu, pad_to

_I32 = jnp.int32
NDIR = 3


def due_dedup_reference(flat, acc_d, acc_a, w_seq, link_seq, nl: int):
    """XLA path: the dense scatter-max plane formulation — a standalone
    mirror of the engine's `PeerPlane.link_max`/`link_read`/
    `peer_dirmax` sequence (single-device form). Returns
    (winner, loser, fresh, alert_write, is_rep (WW,) bool,
    aforce (WW, 3) bool)."""
    ww = flat.shape[0]
    wi = jnp.arange(ww, dtype=_I32)

    def plane(mask):
        return jnp.full(nl, -1, _I32).at[jnp.where(mask, flat, nl)].max(
            jnp.where(mask, wi, -1), mode="drop")

    best = plane(acc_d)
    abest = plane(acc_a)
    best_w = best[flat]
    abest_w = abest[flat]
    winner = acc_d & (wi == best_w)
    loser = acc_d & ~winner
    floor = jnp.where(abest_w >= 0, 0, link_seq)
    fresh = winner & (w_seq > floor)
    alert_write = acc_a & (best_w < 0)
    recv = flat // NDIR
    rep_w = jnp.maximum(best, abest).reshape(-1, NDIR).max(1)[recv]
    is_rep = (acc_d | acc_a) & (wi == rep_w)
    aforce = abest.reshape(-1, NDIR)[recv] >= 0
    return winner, loser, fresh, alert_write, is_rep, aforce


def due_dedup_kernel(flat, acc_d, acc_a, w_seq, link_seq,
                     block: int = 512, interpret: bool = True):
    ww = flat.shape[0]
    block = min(block, max(ww, 8))
    wwp = ww + (-ww % block)
    nb = wwp // block
    f = pad_to(flat.astype(_I32), wwp, fill=-1)
    ad = pad_to(acc_d.astype(_I32), wwp)
    aa = pad_to(acc_a.astype(_I32), wwp)
    col = lambda a: a[:, None]
    row = lambda a: a[None, :]

    def kern(fc_ref, fr_ref, adc_ref, adr_ref, aac_ref, aar_ref,
             wsc_ref, lsc_ref,
             best_ref, abest_ref, rep_ref, aforce_ref,
             win_ref, lose_ref, fresh_ref, aw_ref, isrep_ref):
        i = pl.program_id(0)
        j = pl.program_id(1)
        nj = pl.num_programs(1)
        fi = fc_ref[...]                                  # (BI, 1)
        fj = fr_ref[...]                                  # (1, BJ)
        dj = adr_ref[...] != 0
        aj = aar_ref[...] != 0
        wi_j = j * block + jax.lax.broadcasted_iota(_I32, (1, block), 1)
        match = fi == fj                                  # (BI, BJ)
        mx = lambda m: jnp.max(jnp.where(m, wi_j, -1), axis=1, keepdims=True)
        bst = mx(match & dj)
        abst = mx(match & aj)
        rmatch = (fi // NDIR) == (fj // NDIR)
        rp = mx(rmatch & (dj | aj))
        vj = fj % NDIR
        ind = lambda m: jnp.max(jnp.where(m, 1, 0), axis=1, keepdims=True)
        af = jnp.concatenate(
            [ind(rmatch & aj & (vj == dd)) for dd in range(NDIR)], axis=1)

        @pl.when(j == 0)
        def _init():
            best_ref[...] = bst
            abest_ref[...] = abst
            rep_ref[...] = rp
            aforce_ref[...] = af

        @pl.when(j != 0)
        def _accum():
            best_ref[...] = jnp.maximum(best_ref[...], bst)
            abest_ref[...] = jnp.maximum(abest_ref[...], abst)
            rep_ref[...] = jnp.maximum(rep_ref[...], rp)
            aforce_ref[...] = jnp.maximum(aforce_ref[...], af)

        @pl.when(j == nj - 1)
        def _finalize():
            wi_i = i * block + jax.lax.broadcasted_iota(_I32, (block, 1), 0)
            di = adc_ref[...] != 0
            ai = aac_ref[...] != 0
            b = best_ref[...]
            ab = abest_ref[...]
            win = di & (wi_i == b)
            win_ref[...] = win.astype(_I32)
            lose_ref[...] = (di & ~win).astype(_I32)
            floor = jnp.where(ab >= 0, 0, lsc_ref[...])
            fresh_ref[...] = (win & (wsc_ref[...] > floor)).astype(_I32)
            aw_ref[...] = (ai & (b < 0)).astype(_I32)
            isrep_ref[...] = ((di | ai) & (wi_i == rep_ref[...])).astype(_I32)

    cspec = pl.BlockSpec((block, 1), lambda i, j: (i, 0))
    rspec = pl.BlockSpec((1, block), lambda i, j: (0, j))
    shp1 = jax.ShapeDtypeStruct((wwp, 1), _I32)
    compiler_params = None
    if not interpret:
        compiler_params = pltpu.CompilerParams(
            dimension_semantics=("parallel", "arbitrary"))
    (best, abest, rep, aforce, win, lose, fresh, aw, isrep) = pl.pallas_call(
        kern,
        grid=(nb, nb),
        in_specs=[cspec, rspec, cspec, rspec, cspec, rspec, cspec, cspec],
        out_specs=[cspec, cspec, cspec,
                   pl.BlockSpec((block, NDIR), lambda i, j: (i, 0)),
                   cspec, cspec, cspec, cspec, cspec],
        out_shape=[shp1, shp1, shp1,
                   jax.ShapeDtypeStruct((wwp, NDIR), _I32),
                   shp1, shp1, shp1, shp1, shp1],
        interpret=interpret,
        compiler_params=compiler_params,
    )(col(f), row(f), col(ad), row(ad), col(aa), row(aa),
      col(pad_to(w_seq.astype(_I32), wwp)),
      col(pad_to(link_seq.astype(_I32), wwp)))
    sl = lambda a: a[:ww, 0].astype(bool)
    return (sl(win), sl(lose), sl(fresh), sl(aw), sl(isrep),
            aforce[:ww].astype(bool))


def due_dedup(flat, acc_d, acc_a, w_seq, link_seq, nl: int,
              use_kernel: bool = True, block: int = 512, interpret=None):
    """Dispatch: window-local Pallas election, or the dense-plane XLA
    reference (bit-identical — deterministic max election)."""
    if use_kernel and flat.shape[0] >= 8:
        if interpret is None:
            interpret = not on_tpu()
        return due_dedup_kernel(flat, acc_d, acc_a, w_seq, link_seq,
                                block=block, interpret=interpret)
    return due_dedup_reference(flat, acc_d, acc_a, w_seq, link_seq, nl)
