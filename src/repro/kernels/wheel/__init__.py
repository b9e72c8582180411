"""Pallas kernels for the delivery wheel's per-cycle hot loops.

Four kernels (DESIGN.md §Kernels), each dispatched behind a
`use_kernel` fallback flag with an XLA-path reference that *defines*
the semantics (the kernels are bit-identical to it — pinned by
tests/test_kernels.py in interpret mode on CPU CI):

  * `due_dedup`       — fused due-scan + accept-dedup: window-local
    winner / representative / alert-force election replacing the dense
    per-link scatter-max plane, within each owner lane;
  * `stage_rows`      — ordinal-ranked enqueue staging: the lane-local
    delay-class gather + DELIVER_T stamping of the cycle's rigid
    staging block in one blocked pass (mesh-invariant ordinals);
  * `descent_tail`    — the R1 internal-descent tail as a blocked
    kernel (per-block while_loop over `protocol.deliver_rules`);
  * `threshold_step`  — problem-generic fused margin/test/Send
    payloads, parameterized by payload width P (traces the problem's
    own column-form `test_cols` inside the kernel body).

The engine (`engine.jax_backend`) wires these into the cycle body
behind the `PeerPlane` layer. Every kernel operates on SHARD-LOCAL
windows under the owner-partitioned wheel — the sharded engine runs
them inside shard_map on its own lanes' data, no replicated window.
"""
from repro.kernels.wheel.descent import descent_reference, descent_tail
from repro.kernels.wheel.due_dedup import due_dedup, due_dedup_reference
from repro.kernels.wheel.enqueue import stage_rows, stage_rows_reference
from repro.kernels.wheel.threshold_step import threshold_step

WHEEL_KERNELS = ("dedup", "enqueue", "descent", "threshold")

__all__ = [
    "WHEEL_KERNELS", "due_dedup", "due_dedup_reference", "stage_rows",
    "stage_rows_reference", "descent_tail", "descent_reference",
    "threshold_step",
]
