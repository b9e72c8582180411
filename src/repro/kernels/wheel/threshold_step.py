"""Problem-generic fused margin/test/Send payload kernel.

Generalizes the fused `majority_step` kernel to ANY `ThresholdProblem`
(payload width P = D + 1): one blocked pass computes the knowledge
K = sum_v X_in + [x, 1], the agreement A = X_in + X_out, the problem's
safe-zone violation test and the Send(v) payload K - X_in — exactly
`protocol.threshold_rules`, which is the XLA reference the dispatch
falls back to (and the bit-parity oracle for the kernel), in its
column form `protocol.threshold_rules_cols`.

The problem's column-form test (`test_cols`) is traced *inside* the
kernel body with `xp = jnp`, so region-wise tests (`L2Thresh`'s
tangent-half-space cover, argmax direction included) get the same fast
path as the linear problems — a new problem class needs no new kernel.

Layout: lane-dense planes, peers on the minor (128-lane) axis like
`majority_step`. The engine's (N, 3P) payload columns (column c*3 + v:
component c of direction v) transpose to a (3P, N) plane, so each
component is a (3, BN) block and the knowledge a (1, BN) row; the block
over N stays well inside the scoped VMEM limit at any P the engines
use. P is a compile-time parameter (baked into the block shapes),
matching the engine's per-problem row layout.
"""
from __future__ import annotations

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl

from repro.engine import protocol as proto
from repro.kernels.wheel._common import compiler_params, on_tpu, pad_to

_I32 = jnp.int32
NDIR = 3
LANE = 128


def threshold_step_kernel(problem, in_cols: jnp.ndarray,
                          out_cols: jnp.ndarray, x: jnp.ndarray,
                          block: int = 4096, interpret: bool = True):
    """(viol (N,3) bool, output (N,) int32, pay (N,3P) int32) for int32
    payload columns in_cols/out_cols (N,3P) and own data x (N,D)."""
    n, dw = x.shape
    pw = in_cols.shape[-1] // NDIR
    block = min(block, -(-max(n, 1) // LANE) * LANE)
    npad = -(-n // block) * block

    def planes(a):  # (N, 3P) -> (3P, npad), row c*3 + v
        return pad_to(a.astype(_I32).T, npad, axis=1)

    def kern(ip_ref, op_ref, x_ref, viol_ref, out_ref, pay_ref):
        rows = lambda ref, c: ref[proto.comp(c), :]              # (3, BN)
        ipc = [rows(ip_ref, c) for c in range(pw)]
        agg = [a + rows(op_ref, c) for c, a in enumerate(ipc)]
        own = [x_ref[c:c + 1, :] for c in range(dw)]
        own.append(jnp.ones_like(own[0]))
        k = [a[0:1] + a[1:2] + a[2:3] + o for a, o in zip(ipc, own)]
        send, out = problem.test_cols(jnp, agg, k)
        viol_ref[...] = send.astype(_I32)
        out_ref[...] = out.astype(_I32)
        for c in range(pw):
            pay_ref[proto.comp(c), :] = k[c] - ipc[c]

    spec = lambda rws: pl.BlockSpec((rws, block), lambda i: (0, i))
    shp = lambda rws: jax.ShapeDtypeStruct((rws, npad), _I32)
    viol, out, pay = pl.pallas_call(
        kern,
        grid=(npad // block,),
        in_specs=[spec(NDIR * pw), spec(NDIR * pw), spec(dw)],
        out_specs=[spec(NDIR), spec(1), spec(NDIR * pw)],
        out_shape=[shp(NDIR), shp(1), shp(NDIR * pw)],
        interpret=interpret,
        name="wheel_threshold",
        compiler_params=compiler_params(interpret),
    )(planes(in_cols), planes(out_cols), pad_to(x.astype(_I32).T, npad, axis=1))
    return viol[:, :n].T.astype(bool), out[0, :n], pay[:, :n].T


def threshold_step(problem, in_cols, out_cols, x, use_kernel: bool = True,
                   block: int = 4096, interpret=None):
    """Dispatch: the Pallas kernel, or the XLA-path reference
    (`protocol.threshold_rules_cols` — THE semantics; bit-identical)."""
    if use_kernel and x.shape[0] >= 8:
        if interpret is None:
            interpret = not on_tpu()
        return threshold_step_kernel(
            problem, in_cols, out_cols, x, block=block, interpret=interpret)
    return proto.threshold_rules_cols(
        problem, jnp, jnp.asarray(in_cols, _I32),
        jnp.asarray(out_cols, _I32), jnp.asarray(x, _I32))
