"""DELIVER_T staging kernel for the owner-partitioned wheel append.

Each cycle every lane stages ONE rigid block of rows that (re-)enter a
wheel — window re-entries followed by the NDIR send candidates, at fixed
block positions so the layout is mesh-invariant. The delay a data row
draws is keyed by its *ordinal* — the row's rank among the live rows of
ITS LANE's block (a lane-local cumsum, identical at any mesh size) —
through the cycle's pseudorandom permutation ``perm`` of 1..10; ALERT
rows are stamped due ``t + 1`` (the side-wheel drains them next cycle
ahead of the data budget). This kernel fuses the ordinal → delay-class
gather and the DELIVER_T column stamp into one blocked pass; the
per-(lane, slot) append ranking and the dynamic-update-slice arena
writes (dynamic indices — DMA territory, not vector compute) stay in
XLA on both paths.

Dead rows (mask bit clear in the exchange meta column) are stamped too —
their ordinal repeats the preceding live row's, which is itself
lane-local — so the staged block is bit-identical between the two paths
and across mesh sizes, dead slack included.

TPU layout note: ROWW (6 + P) rides the lane axis, far under the
128-lane tile — the kernel is DMA-shaped, not FLOP-shaped, which is
fine for what is a pure data-movement fusion (see DESIGN.md §Kernels).
"""
from __future__ import annotations

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl

from repro.kernels.wheel._common import compiler_params, on_tpu

_I32 = jnp.int32
_U32 = jnp.uint32
NCLASS = 10
_BM = 512  # row block per grid step


def stage_rows_reference(rows: jnp.ndarray, alert: jnp.ndarray,
                         ordinal: jnp.ndarray, perm: jnp.ndarray,
                         t: jnp.ndarray, dt_col: int) -> jnp.ndarray:
    """XLA path: rows (M, ROWW) uint32 with DELIVER_T stamped
    ``t + 1`` where `alert`, else ``t + perm[ordinal mod 10]``
    (floor mod: a leading dead row's ordinal of -1 reads class 9)."""
    cls = ordinal.astype(_I32) % NCLASS
    delay = jnp.where(alert, _I32(1), perm[cls].astype(_I32))
    due = (t.astype(_U32) + delay.astype(_U32))
    col = jnp.arange(rows.shape[1])
    return jnp.where(col[None, :] == dt_col, due[:, None], rows)


def stage_rows_kernel(rows: jnp.ndarray, alert: jnp.ndarray,
                      ordinal: jnp.ndarray, perm: jnp.ndarray,
                      t: jnp.ndarray, dt_col: int,
                      interpret: bool = True) -> jnp.ndarray:
    m, roww = rows.shape
    pm = -m % _BM
    if pm:
        rows = jnp.concatenate([rows, jnp.zeros((pm, roww), _U32)])
        alert = jnp.concatenate([alert, jnp.zeros(pm, bool)])
        ordinal = jnp.concatenate([ordinal, jnp.zeros(pm, _I32)])
    mp = rows.shape[0]

    def kern(rows_ref, al_ref, od_ref, perm_ref, t_ref, out_ref):
        rws = rows_ref[...]                                # (BM, ROWW)
        cls = od_ref[...][:, 0] % NCLASS                   # (BM,)
        delay = jnp.zeros_like(cls)
        for i in range(NCLASS):  # unrolled gather: perm is 10 wide
            delay = delay + jnp.where(cls == i, perm_ref[0, i], 0)
        delay = jnp.where(al_ref[...][:, 0] != 0, 1, delay)
        due = (t_ref[0, 0] + delay).astype(_U32)
        col = jax.lax.broadcasted_iota(_I32, (rws.shape[0], roww), 1)
        out_ref[...] = jnp.where(col == dt_col, due[:, None], rws)

    staged = pl.pallas_call(
        kern,
        grid=(mp // _BM,),
        in_specs=[
            pl.BlockSpec((_BM, roww), lambda b: (b, 0)),
            pl.BlockSpec((_BM, 1), lambda b: (b, 0)),
            pl.BlockSpec((_BM, 1), lambda b: (b, 0)),
            pl.BlockSpec((1, NCLASS), lambda b: (0, 0)),
            pl.BlockSpec((1, 1), lambda b: (0, 0)),
        ],
        out_specs=pl.BlockSpec((_BM, roww), lambda b: (b, 0)),
        out_shape=jax.ShapeDtypeStruct((mp, roww), _U32),
        interpret=interpret,
        name="wheel_enqueue",
        compiler_params=compiler_params(interpret),
    )(rows, alert.astype(_I32).reshape(mp, 1),
      ordinal.astype(_I32).reshape(mp, 1),
      jnp.asarray(perm, _I32).reshape(1, NCLASS),
      jnp.asarray(t, _I32).reshape(1, 1))
    return staged[:m]


def stage_rows(rows, alert, ordinal, perm, t, dt_col: int,
               use_kernel: bool = True, interpret=None) -> jnp.ndarray:
    """Dispatch: Pallas blocked staging, or the XLA reference."""
    if use_kernel and rows.shape[0] >= _BM:
        if interpret is None:
            interpret = not on_tpu()
        return stage_rows_kernel(rows, alert, ordinal, perm, t, dt_col,
                                 interpret=interpret)
    return stage_rows_reference(rows, alert, ordinal,
                                jnp.asarray(perm, _I32), t, dt_col)
