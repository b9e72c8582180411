"""Device-resident majority-voting engine (scan-fused superstep execution).

Everything the numpy reference does per cycle — due-message delivery
through the Alg. 1 router, X_in acceptance with sequence dedup, the
Alg. 3 violation test, and the Send fan-out — runs on device over
fixed-shape arrays, and since PR 3 whole *runs* execute as single XLA
programs:

  * ``step(cycles=K)`` is ONE dispatch: the cycle body is the body of a
    jitted ``lax.while_loop`` (the superstep); ``run_until_converged``
    evaluates the Alg. 3 convergence predicate on device every cycle and
    early-exits through the loop carry, syncing with the host once per
    *chunk* (default 256 cycles) instead of twice per cycle;
  * the message store is an **owner-partitioned delivery wheel**: the
    peer rows are cut into ``lanes`` equal row blocks (the owner lanes),
    and each lane keeps its own wheel — messages bucketed by
    ``deliver_t mod (MAX_DELAY+1)`` into 11 dense per-slot row arenas
    (plus a small ALERT side-wheel) *of the lane that owns the
    destination address*. The per-cycle due-scan, the accept dedup
    election, the ALERT drain and the deferral accounting are all
    lane-local; the only lane-crossing step is the staged **boundary
    exchange** that routes freshly appended rows to their owner lane
    (identity on one device; one all-gather per cycle on a mesh, where
    `engine.sharded` shards the lane axis so per-device wheel memory is
    O(n/devices) — DESIGN.md §Sharding);
  * per-cycle work is *budgeted per lane*: the drain window is the first
    ``work_budget / lanes`` rows of each lane's due bucket (ALERT
    side-wheel rows always ride ahead of data). Over-budget rows slip
    one cycle into the next bucket; pathological bursts beyond that stay
    in place and are revisited a wheel revolution later (both counted
    ONCE per row in ``deferred`` via the LATE row bit — the protocol
    tolerates arbitrary delays by design);
  * the cycle's hot loops have Pallas kernel forms (`kernels.wheel`:
    fused due-scan/dedup election, the staged-row delay stamp, the
    blocked R1 descent tail, and the problem-generic fused threshold
    step) — each behind an individual `use_kernel` fallback flag,
    bit-identical to the XLA paths that remain THE semantic reference;
  * routing uses the jnp path of `core.addressing`'s bit algebra through
    the same `engine.protocol.deliver_rules` the numpy backend consumes;
    the R1 internal-descent loop is a `lax.while_loop` over live masks;
  * the in-cycle test/Send react is gather-based (`protocol.
    majority_rules` over the compacted acceptor set — work scales with
    the window, not with n); the fused Pallas ``majority_step`` kernel
    serves the full-width event paths (init, vote changes) and stays the
    TPU fast path there;
  * message delays are a per-cycle pseudorandom *permutation* of 1..10
    assigned by each staged row's ordinal WITHIN ITS LANE's append
    block (event-path enqueues keep the per-row splitmix hash). The
    lane-relative ordinal is what makes the delay assignment — and
    therefore the whole trajectory — independent of how many lanes are
    co-resident on a device (mesh-size invariance). Seeds still make
    runs reproducible and independent of numpy's global RNG state.

All RNG material (delay permutations, hash salts) lives inside
`DeviceState`, so the whole superstep `vmap`s over stacked states —
`engine.batched.BatchedJaxEngine` runs B independent trials as one
program on exactly this cycle body.

Every cycle-body access to the O(n) peer state (x / inbox / out) flows
through the `PeerPlane` layer below, and every lane-crossing wheel move
flows through its `exchange` / `lane_base` hooks; `engine.sharded`
swaps in collective implementations and runs this same cycle body under
`shard_map` with the peer plane AND the wheel's lane axis block-sharded
over a device mesh — trajectory bit-identical by construction
(DESIGN.md §Sharding).

Dynamic membership (Alg. 2, DESIGN.md §Churn): the ring lives *inside*
`DeviceState` as padded sorted-prefix tables — rows [0, n_live) hold the
occupied addresses ascending, rows above are 0xFFFFFFFF sentinels (the
occupancy mask is the prefix predicate `arange < n_live`) — so `join` /
`leave` are jitted gather-shifts plus one row scatter, and the owner
lookup stays a single padded binary search. A membership change moves
the owner-row boundaries, so the churn tail re-fences AND re-lanes the
in-flight wheel rows through the same boundary exchange (rows whose
destination now belongs to another lane migrate; stale-origin data rows
drop, per R3). ALERT messages ride the side-wheel at one cycle per hop.
Re-jit (recompilation) happens only when a join outgrows the padded
capacity and the tables are rebuilt one size up — the jitted program
objects are built ONCE and retrace per shape, so repeated churn at a
stable pad never recompiles.

Conservation invariant (checked by `check_conservation`): summed over
lanes, ``enqueued == retired + in_flight + dropped`` — every row ever
appended to a wheel arena is eventually drained (retired), still live,
or accounted as dropped. Per-lane counters make the sum exact with no
cross-shard double counting.

Addresses are uint32 on device (JAX default config has no uint64), so
rings must use d <= 32 bits. Counters are int32. Cross-backend
equivalence and the seeded-RNG tolerance are specified in DESIGN.md
§Engine.
"""
from __future__ import annotations

from typing import NamedTuple, Tuple

import numpy as np

import jax
import jax.numpy as jnp

from repro.core import addressing as A
from repro.core.dht import Ring
from repro.core.simulator import MAX_DELAY, MIN_DELAY
from repro.engine import protocol as P
from repro.engine.base import (EngineResult, coalesced_update,
                               run_convergence_loop)
from repro.engine.problems import Majority, get_problem
from repro.kernels import on_tpu
from repro.kernels.majority_step.ops import majority_step
from repro.kernels.wheel import (WHEEL_KERNELS, descent_reference,
                                 descent_tail, due_dedup, stage_rows,
                                 threshold_step)
from repro.kernels.wheel._common import in_segment

NDIR = 3
_I32 = jnp.int32
_U32 = jnp.uint32

# message-row columns (all uint32; ints bit-fit via wraparound, bools are
# 0/1). The row is ROWW = 6 + P wide for payload width P (problem layer):
# the 4 fixed router columns, P payload columns, then SEQ and DELIVER_T at
# PAY0 + P and PAY0 + P + 1. The majority problem (P = 2) keeps the
# historical 8-column layout below bit for bit.
ORIGIN, DEST, EDGE, HAS_EDGE, PAY0 = range(5)
PAY_ONES, PAY_TOT, SEQ, DELIVER_T = 4, 5, 6, 7  # majority (P = 2) layout
# the has_edge column packs a continuation flag in bit 1 (bit 0: has_edge):
# a row whose R1 internal descent outran the narrow-loop budget re-enters
# the wheel mid-descent with its network-entry already consumed
CONT = np.uint32(2)
# bit 2: the row already missed a drain window once (slipped a cycle or
# waited out a revolution). Pure accounting — the router never reads it;
# it keeps the deferral counter from recounting the same standing
# backlog row every cycle it sits over budget
LATE = np.uint32(4)
# bit 3: fault-plane liveness probe (DESIGN.md §10). Probe rows ride the
# ALERT side-wheel (1 cycle/hop control plane) but are NOT Alg. 2
# alerts: they never zero a link, never force the alert upcall, and are
# R3 origin-fenced at churn like ordinary traffic. An accepted probe
# refreshes the receiver's `heard` stamp and forces an unconditional
# Send(v) back — the ack that keeps quiet-but-alive links from aging
# into eviction
PROBE = np.uint32(8)
NO_MSG = np.uint32(0xFFFFFFFF)  # deliver_t sentinel: row is dead (fenced)
NO_ADDR = np.uint32(0xFFFFFFFF)  # padded-ring sentinel: row is vacant

SLOTS = MAX_DELAY + 1   # delivery-wheel slots; delays 1..10 never wrap a slot
NPERM = 16              # per-cycle delay permutations kept in DeviceState
ALERT_W = 64            # ALERT side-wheel row baseline (per-lane floor below)
MAX_LANES = 8           # owner-lane count cap (= max supported mesh size)
# staged boundary-exchange meta column bits (row is live / is an ALERT)
META_LIVE = np.uint32(1)
META_ALERT = np.uint32(2)


def _next_pow2(v: int) -> int:
    p = 1
    while p < v:
        p <<= 1
    return p


# Device payload layout. A peer's three per-direction payloads are kept
# as one (..., 3P) row of "payload columns", component-major: column
# c*3 + v holds component c of direction v — the layout of the X_out
# columns of `DeviceState.out`, and, transposed, the lane-dense planes
# of the `threshold_step` kernel. No device program builds a (..., 3, P)
# array: on the TPU such a tiny-minor-dims relayout costs 40-230 s of
# compile (tests/test_tpu_compile.py keeps the kernels honest; the
# engine programs were rehearsed the same way).


def peer_links(inbox, pw: int):
    """(..., rows*3, P+1) per-link inbox -> (..., rows, 3(P+1)) one row
    per peer, column v*(P+1) + c (a row-major view, no data movement)."""
    return inbox.reshape(*inbox.shape[:-2], -1, NDIR * (pw + 1))


def link_cols(rows, pw: int):
    """(..., 3(P+1)) peer link rows -> (..., 3P) received payload
    columns (component-major, column c*3 + v)."""
    return jnp.stack([rows[..., v * (pw + 1) + c]
                      for c in range(pw) for v in range(NDIR)], axis=-1)


def knowledge(problem, inbox, x, pd: int):
    """(..., pd, P) knowledge payloads K = X_self + sum_v X_in from the
    flat per-link inbox. The ONE inbox-based definition — the
    convergence predicate, both engines' host-visible `outputs()`
    (batched included) and the churn mover payloads all read it; keep
    them in lockstep. `x` is the (..., pd, D) own-data plane."""
    pw = problem.payload_width
    r = peer_links(inbox, pw)
    k = jnp.stack([r[..., c] + r[..., pw + 1 + c] + r[..., 2 * (pw + 1) + c]
                   for c in range(pw)], axis=-1)
    one = jnp.ones_like(x[..., :1])
    return k + jnp.concatenate([x, one], axis=-1)


def knowledge_outputs(problem, inbox, x, pd: int):
    """(pd,) bool threshold outputs: the sign of margin(K)."""
    return problem.margin(jnp, knowledge(problem, inbox, x, pd)) >= 0


def _hash_u32(idx: jnp.ndarray, t: jnp.ndarray, salt: jnp.ndarray) -> jnp.ndarray:
    """The engine's integer mix as a raw uniform uint32 — shared by the
    event-delay hash below and the fault plane's per-row drop/delay
    draws (keyed on the GLOBAL window index so every mesh size draws the
    same faults)."""
    h = idx.astype(_U32) * _U32(0x9E3779B1)
    h = h + t.astype(_U32) * _U32(0x85EBCA77) + salt.astype(_U32)
    h = h ^ (h >> _U32(16))
    h = h * _U32(0x7FEB352D)
    h = h ^ (h >> _U32(15))
    h = h * _U32(0x846CA68B)
    h = h ^ (h >> _U32(16))
    return h


def _hash_delay(idx: jnp.ndarray, t: jnp.ndarray, salt: jnp.ndarray) -> jnp.ndarray:
    """Uniform 1..10 delay from (row, cycle, seed) via an integer mix
    (event-path enqueues; the cycle path uses permutation strides)."""
    h = _hash_u32(idx, t, salt)
    span = _U32(MAX_DELAY - MIN_DELAY + 1)
    return (MIN_DELAY + (h % span).astype(_I32)).astype(_I32)


def deliver_network_step(*, origin, dest, edge, has_edge, live, pos_i,
                         a_prev, a_self, self_seg, max_addr, d: int,
                         entry=None):
    """One *network* delivery for a batch of messages, R1 loop included.

    All inputs are equal-length arrays; `live` masks the rows to process
    (each costs exactly one network delivery). The R1 internal descent
    runs as a `lax.while_loop` over live masks: a peer keeps descending
    while the recalculated destination stays inside its own segment.
    Returns (accept, drop, fwd_dest, fwd_edge, fwd_has_edge) — rows that
    neither accept nor drop re-enter the network with the fwd_* fields.
    `entry` overrides the network-entry flags (the cycle passes False
    for rows resuming a partially-completed internal descent).

    This is THE delivery semantics of the device engine; the parity
    tests drive this exact function against `routing.step_batch`, for
    ordinary traffic and for Alg. 2 ALERTs alike (an ALERT differs only
    in riding the side-wheel, never in routing). The loop itself is
    `kernels.wheel.descent_reference`, which the blocked `descent_tail`
    kernel runs per block.
    """
    if entry is None:
        entry = jnp.ones(live.shape, bool)
    return descent_reference(origin, dest, edge, has_edge, live, entry,
                             pos_i, a_prev, a_self, self_seg, max_addr, d)


class DeviceState(NamedTuple):
    """Complete simulation state; every leaf is a device array.

    Peer rows are padded to `pad` entries; the occupied rows are the
    sorted prefix [0, n_live) (vacant address rows hold NO_ADDR). The
    wheel arenas and the wheel counters carry a leading owner-lane axis
    (L = `JaxEngine.lanes`; a row lives in the lane owning its DEST
    address) — `engine.sharded` shards exactly that axis, everything
    without it is replicated. `engine.batched` stacks a leading batch
    axis over every leaf and vmaps the cycle body — all RNG material is
    therefore state, not Python closure.
    """

    # Alg. 3 peer state (P = problem payload width; majority: D=1, P=2)
    x: jnp.ndarray      # (pad, D)      int32 own data (majority: votes)
    inbox: jnp.ndarray  # (pad*3, P+1)  int32 per-link [X_in payload, last_seq]
    out: jnp.ndarray    # (pad, 3P+1)   int32 [X_out component c per dir]*P, seq
    # ring membership (sorted-prefix padded tables; replicated)
    addrs: jnp.ndarray  # (pad,) uint32, ascending prefix then NO_ADDR
    prev: jnp.ndarray   # (pad,) uint32 predecessor addresses (cyclic)
    pos: jnp.ndarray    # (pad,) uint32 tree positions
    n_live: jnp.ndarray  # ()    int32 occupied row count
    # owner-partitioned delivery wheel: per-lane dense per-slot arenas
    # bucketed by deliver_t mod SLOTS
    wheel: jnp.ndarray   # (L, SLOTS, W_l, roww)  uint32 data rows
    wcnt: jnp.ndarray    # (L, SLOTS)             int32 live rows per slot
    awheel: jnp.ndarray  # (L, SLOTS, A_l, roww)  uint32 Alg. 2 ALERT rows
    acnt: jnp.ndarray    # (L, SLOTS)             int32
    # RNG material (state, so the superstep vmaps)
    perms: jnp.ndarray     # (NPERM, 10) int32 delay permutations of 1..10
    salt_enq: jnp.ndarray  # ()          uint32 event-path delay salt
    evt_ctr: jnp.ndarray   # ()          int32 event counter (delay decorrelator)
    # counters (per lane where the work is lane-local; hosts read sums)
    t: jnp.ndarray              # ()   int32
    messages_sent: jnp.ndarray  # (L,) int32 network deliveries consumed
    dropped: jnp.ndarray        # (L,) int32 arena overflow (should stay 0)
    deferred: jnp.ndarray       # (L,) int32 deliveries pushed past the budget
    enq: jnp.ndarray            # (L,) int32 rows ever appended (conservation)
    ret: jnp.ndarray            # (L,) int32 rows ever drained/retired
    # fault plane (DESIGN.md §10; all-zero and untouched when disarmed)
    dead: jnp.ndarray    # (pad,)   bool  crashed, not yet evicted (replicated)
    heard: jnp.ndarray   # (pad*3,) int32 last-accept cycle stamp per link
    probed: jnp.ndarray  # (pad*3,) int32 last-probe cycle stamp per link
    lost: jnp.ndarray    # (L,)     int32 rows destroyed by injected faults


class PeerPlane:
    """Access layer for the partitioned planes — the O(n) per-peer state
    leaves (`x`, `inbox`, `out`), the occupancy/convergence reductions
    over them, AND the owner-lane boundary hooks of the delivery wheel
    (`lane_base` / `exchange` / `shift_rows`). Every read or write the
    cycle body performs against those leaves goes through this object,
    and NOTHING else in the cycle does (the replicated ring tables and
    the scalar counters are read directly).

    This is the single-device implementation: plain gathers/scatters,
    global row indices ARE array indices, the exchange is the identity.
    `repro.engine.sharded` substitutes `ShardedPlane`, where each device
    holds one contiguous peer-row block plus the matching owner lanes,
    and the same methods become local ops plus the staged lane exchange
    — the cycle body itself is shared verbatim, which is what makes the
    sharded engine trajectory bit-identical to this one (DESIGN.md
    §Sharding).

    Index contract: `idx` arguments are GLOBAL row indices (peer rows
    for `*_peer`, flat peer*NDIR+dir links for `*_link`); scatter
    sentinels at `pad` / `pad * NDIR` drop. Gather `idx` must be valid
    rows — callers mask results instead (matching the historical code).
    Since every in-flight wheel row sits in the lane of its DEST owner,
    all drain-path peer/link accesses are lane-local by invariant; on
    the sharded plane they need no collective at all.
    """

    def __init__(self, eng: "JaxEngine"):
        self.eng = eng

    # -- gathers (window-sized idx -> values) -------------------------------
    def take_peer(self, arr: jnp.ndarray, idx: jnp.ndarray) -> jnp.ndarray:
        return arr[idx]

    def take_link(self, arr: jnp.ndarray, idx: jnp.ndarray) -> jnp.ndarray:
        return arr[idx]

    def take_peer_rep(self, arr: jnp.ndarray, idx: jnp.ndarray) -> jnp.ndarray:
        """Gather peer rows at GLOBAL indices with a REPLICATED result
        (churn movers — the rows may be owned by any shard, unlike the
        lane-local drain path). Identity gather here; masked local
        gather + one psum on the sharded plane. Event path only, never
        per-cycle."""
        return arr[idx]

    # -- scatters (window-sized rows into the plane; sentinel drops) --------
    def put_peer(self, arr: jnp.ndarray, idx: jnp.ndarray,
                 val: jnp.ndarray) -> jnp.ndarray:
        return arr.at[idx].set(val, mode="drop")

    def put_link(self, arr: jnp.ndarray, idx: jnp.ndarray,
                 val: jnp.ndarray) -> jnp.ndarray:
        return arr.at[idx].set(val, mode="drop")

    # -- per-link scatter-max dedup plane (accept winner election) ----------
    def link_max(self, idx: jnp.ndarray, val: jnp.ndarray,
                 mask: jnp.ndarray) -> jnp.ndarray:
        """Dense per-link max of `val` over the masked window rows
        (fill -1). The returned handle is only ever read back through
        `link_read` / `link_read3` / `peer_dirmax` — its layout is the
        plane's business (the sharded plane returns a local block; the
        drain path only ever reads links it owns, so no collective)."""
        nl = self.eng.pad * NDIR
        return jnp.full(nl, -1, _I32).at[jnp.where(mask, idx, nl)].max(
            jnp.where(mask, val, -1), mode="drop")

    def link_floor(self) -> jnp.ndarray:
        """The all-(-1) dedup plane (the no-alerts branch)."""
        return jnp.full(self.eng.pad * NDIR, -1, _I32)

    def link_read(self, dense: jnp.ndarray, idx: jnp.ndarray) -> jnp.ndarray:
        return dense[idx]

    def link_read3(self, dense: jnp.ndarray, rows: jnp.ndarray) -> jnp.ndarray:
        """All three link cells of peer `rows`: (m, NDIR)."""
        return dense.reshape(-1, NDIR)[rows]

    def peer_dirmax(self, dense: jnp.ndarray, rows: jnp.ndarray) -> jnp.ndarray:
        """Per-peer max over the NDIR link cells, read at `rows`."""
        return dense.reshape(-1, NDIR).max(1)[rows]

    # -- occupancy / reductions ---------------------------------------------
    def occ(self, st: "DeviceState") -> jnp.ndarray:
        """Occupancy mask over the plane's local rows (global row index
        < n_live — rows here are global)."""
        return jnp.arange(st.x.shape[0]) < st.n_live

    def all_true(self, v: jnp.ndarray) -> jnp.ndarray:
        """Scalar AND over a per-row predicate (replicated result)."""
        return v.all()

    # -- owner-lane boundary (wheel partition) ------------------------------
    def lane_base(self, n_loc: int) -> jnp.ndarray:
        """Global lane index of this plane's first local lane."""
        return jnp.zeros((), _I32)

    def exchange(self, arr: jnp.ndarray) -> jnp.ndarray:
        """Lane boundary exchange: (L_local, ...) staged per-lane blocks
        -> the (L, ...) GLOBAL lane-major concatenation, identical on
        every participant. Identity on one device; one tiled all_gather
        over the mesh axis on the sharded plane. Every appended wheel
        row rides this exactly once, so append ranks — and therefore
        slot offsets — are bit-identical at every mesh size."""
        return arr

    def shift_rows(self, arr: jnp.ndarray, src: jnp.ndarray) -> jnp.ndarray:
        """Gather-shift a peer-indexed table by the global source map
        `src` (join/leave row recompaction). The sharded plane routes
        this through an explicit all_gather + local slice — an event
        path, never per-cycle."""
        return arr[src]

    # -- event path (full-width reacts) -------------------------------------
    def local_tables(self, st: "DeviceState"):
        """The (pos, addrs, prev) rows matching the plane's local x
        rows (identity here; the sharded plane slices its block out of
        the replicated tables)."""
        return st.pos, st.addrs, st.prev

    def gather_events(self, *arrs: jnp.ndarray):
        """Assemble per-plane-row event rows into the GLOBAL row order
        the wheel append ranks over (identity here; the sharded plane
        all_gathers the shard blocks, which concatenate in block =
        global order)."""
        return arrs


class JaxEngine:
    """Device-backed `MajorityEngine` (see `repro.engine.base`)."""

    backend = "jax"

    def __init__(self, ring: Ring, votes: np.ndarray, seed: int = 0,
                 capacity_per_peer: int = 6, work_budget: int = 0,
                 kernel: str = "auto", pad_to: int = 0, chunk: int = 256,
                 problem=None, wheel_kernels="auto", faults=None,
                 _defer_state: bool = False):
        if ring.d > 32:
            raise ValueError(
                f"jax engine needs d <= 32 (uint32 addresses), got d={ring.d}"
            )
        if kernel not in ("auto", "pallas", "ref"):
            raise ValueError(f"kernel must be auto|pallas|ref, got {kernel!r}")
        self.problem = get_problem(problem)
        self.pw = int(self.problem.payload_width)   # P
        self.dw = int(self.problem.data_width)      # D
        # wheel row layout for this problem (majority keeps the 8-column
        # historical layout: SEQ=6, DELIVER_T=7)
        self._SEQ = PAY0 + self.pw
        self._DT = self._SEQ + 1
        self.roww = self._DT + 1
        assert votes.shape[0] == ring.n
        self.ring = ring
        self.n = int(ring.n)
        self.d = int(ring.d)
        self._cpp = int(capacity_per_peer)
        self._wb_req = int(work_budget)
        self.chunk = int(chunk)
        # "auto" uses the Pallas kernel only where it compiles natively;
        # off-TPU it falls back to the jnp oracle (interpret mode is for
        # parity tests, not throughput). The fused kernel implements the
        # majority rule only — other problems run the jnp rules.
        self._is_majority = isinstance(self.problem, Majority)
        kernel_on = kernel == "pallas" or (kernel == "auto" and on_tpu())
        self._use_kernel = kernel_on and self._is_majority
        # delivery-wheel kernels (kernels.wheel): each has an individual
        # XLA fallback; `wheel_kernels` selects the enabled subset by
        # name ("auto" = all of WHEEL_KERNELS, "none"/() = pure XLA).
        # Off-TPU the kernels run in interpret mode — parity surface,
        # not throughput — so the same kernel=pallas|auto policy gates
        # them as the majority kernel.
        if wheel_kernels in ("auto", None):
            wk_names = WHEEL_KERNELS
        elif wheel_kernels == "none":
            wk_names = ()
        else:
            wk_names = tuple(wheel_kernels)
        bad = set(wk_names) - set(WHEEL_KERNELS)
        if bad:
            raise ValueError(
                f"unknown wheel kernels {sorted(bad)}; "
                f"pick from {WHEEL_KERNELS}")
        self._wk = frozenset(wk_names) if kernel_on else frozenset()
        self._wk_interp = not on_tpu()
        # fault plane (DESIGN.md §10). Arming adds the probe side-channel
        # to the cycle program; disarmed engines trace the exact pre-fault
        # program (every fault branch is a Python-level `if` on the
        # config). Probe rows need the XLA election path, so the fused
        # dedup kernel is disabled while armed.
        self._faults = faults
        self._evictions = []
        # host overlay for the eviction sweep: (near_addr, dir) -> stamp.
        # The reference refreshes the routed ALERT *recipients'* `heard`
        # synchronously at churn; on device those links only refresh when
        # the routed alert row accepts, cycles later — the floor keeps
        # the sweep from reading the gap as silence (`_stamp_churn_floor`)
        self._heard_floor = {}
        self._evict_floor = -(1 << 30)  # conviction grace after evictions
        if faults is not None:
            self._wk = self._wk - {"dedup"}
            fr = np.random.default_rng(np.uint32(faults.seed) ^ 0xFA17)
            self._fsalt_drop = np.uint32(fr.integers(0, 2**32, dtype=np.uint64))
            self._fsalt_delay = np.uint32(fr.integers(0, 2**32, dtype=np.uint64))
            self._p_drop_thr = np.uint32(
                min(int(faults.p_drop * 2**32), 2**32 - 1))
            self._p_delay_thr = np.uint32(
                min(int(faults.p_delay * 2**32), 2**32 - 1))

        self.pad = int(pad_to) or _next_pow2(max(self.n + max(8, self.n // 8), 64))
        if self.pad < self.n:
            raise ValueError(f"pad_to={pad_to} below ring size {self.n}")
        self._size_tables()
        self._plane = self._make_plane()
        # jitted program objects are built ONCE; jax.jit retraces per
        # input shape, so a later `_grow` (pad change) compiles the new
        # shape on first use without discarding anything — no per-churn
        # re-jit storm
        self._make_programs()

        if _defer_state:  # engine.batched builds (stacked) state itself
            return
        st = self._initial_state(ring, votes, seed)
        occ = jnp.arange(self.pad) < st.n_live
        self._st = self._react(st, occ)

    def _size_tables(self):
        # owner-lane partition of the peer rows: lane = row // lane_rows.
        # The lane count is the largest power-of-two divisor of the pad,
        # capped at MAX_LANES (power-of-two pads — the default — always
        # get the full MAX_LANES; explicit odd pads degrade gracefully).
        # A sharded mesh must divide the lane count evenly.
        self.lanes = min(MAX_LANES, self.pad & -self.pad)
        self.lane_rows = self.pad // self.lanes
        L = self.lanes
        # drain-window budget: downstream scatter/deliver work per cycle
        # scales with this, so it tracks the steady active-phase due rate
        # (well under n/8 with 1..10-cycle delays); overflow only defers.
        # Budgeted PER LANE so the drain is lane-local and mesh-invariant
        b_req = self._wb_req or max(512, self.pad // 8)
        self.lane_budget = max(1, b_req // L)
        self.work_budget = self.lane_budget * L  # effective global budget
        # per-lane per-slot arena capacity; the wheel totals
        # L*SLOTS*cap live data rows (comparable to the historical global
        # slot_cap — the floors keep the tiny-capacity overflow tests
        # and the small-pad event storms behaving as before)
        self.lane_cap = max(4, min(128, 32 * self._cpp) // min(L, 4),
                            self._cpp * self.pad // (16 * L))
        self.slot_cap = self.lane_cap  # per-lane per-slot bound (tests)
        # ALERT side-wheel rows per lane per slot: >= 16 so two
        # back-to-back churn events (<= 12 routed alerts) never overflow
        # even if every alert lands in one lane's slot
        self.lane_alert_w = max(16, ALERT_W // L)
        if self._faults is not None:
            # armed: probe bursts synchronize (after a quiet stretch all
            # links suspect on the same cycle), and every probe in the
            # ring can target ONE owner's lane+slot (the root); size for
            # that worst case so detector traffic is never dropped
            self.lane_alert_w = max(self.lane_alert_w, 3 * self.pad + 16)
        # physical lane-slot width: capacity + slack for the widest
        # contiguous write — the one-cycle slip block (lane_budget rows).
        # Appends are ranked scatters bounded by `lane_cap`, so the slip
        # dynamic-update-slice is the only writer that needs slack
        self.lane_width = max(self.lane_cap, self.lane_budget) + self.lane_budget
        self.capacity = L * SLOTS * (self.lane_cap + self.lane_alert_w)
        # per-lane drain-window width (alerts ride ahead of data)
        self.window_l = self.lane_alert_w + self.lane_budget
        # R1 narrow-tail width PER LANE: after two full-width descent
        # steps only a few percent of the window is still descending
        # (measured); >= lane_alert_w + 8 so ALERTs can never spill into
        # the data wheel (they must forward at one cycle per hop)
        self.narrow_l = max(self.lane_alert_w + 8, self.window_l // 8)
        # churn-migration staging rows per lane (boundary re-lane)
        self.mig_w = max(32, self.lane_cap // 4)

    def _make_plane(self) -> PeerPlane:
        return PeerPlane(self)

    def _make_programs(self):
        self._react = jax.jit(self._react_impl, donate_argnums=(0,))
        self._join = jax.jit(self._join_impl, donate_argnums=(0,))
        self._leave = jax.jit(self._leave_impl, donate_argnums=(0,))
        self._steps = jax.jit(self._steps_impl, donate_argnums=(0,))
        self._chunk_run = jax.jit(self._chunk_impl, donate_argnums=(0,))
        self._conv = jax.jit(self._outputs_match)
        self._crash = jax.jit(self._crash_impl, donate_argnums=(0,))

    def _initial_state(self, ring: Ring, votes: np.ndarray,
                       seed: int) -> DeviceState:
        """Fresh `DeviceState` for (ring, votes, seed) — before the
        initialization react. Host-side so `engine.batched` can stack B
        of them cheaply."""
        pd, L = self.pad, self.lanes
        rng = np.random.default_rng(seed)
        salt = np.uint32(rng.integers(0, 2**32, dtype=np.uint64))
        perms = np.stack([rng.permutation(10) + MIN_DELAY
                          for _ in range(NPERM)]).astype(np.int32)
        addrs = np.full(pd, NO_ADDR, np.uint32)
        addrs[: self.n] = ring.addrs.astype(np.uint32)
        data = self.problem.init_state(votes)
        x = np.zeros((pd, self.dw), np.int32)
        x[: self.n] = data.astype(np.int32)
        st = DeviceState(
            x=jnp.asarray(x),
            inbox=jnp.zeros((pd * NDIR, self.pw + 1), _I32),
            out=jnp.zeros((pd, NDIR * self.pw + 1), _I32),
            addrs=jnp.asarray(addrs),
            prev=jnp.zeros(pd, _U32), pos=jnp.zeros(pd, _U32),
            n_live=jnp.asarray(self.n, _I32),
            wheel=jnp.zeros((L, SLOTS, self.lane_width, self.roww), _U32),
            wcnt=jnp.zeros((L, SLOTS), _I32),
            awheel=jnp.zeros((L, SLOTS, self.lane_alert_w, self.roww), _U32),
            acnt=jnp.zeros((L, SLOTS), _I32),
            perms=jnp.asarray(perms),
            salt_enq=jnp.asarray(salt, _U32),
            evt_ctr=jnp.zeros((), _I32),
            t=jnp.zeros((), _I32),
            messages_sent=jnp.zeros(L, _I32),
            dropped=jnp.zeros(L, _I32), deferred=jnp.zeros(L, _I32),
            enq=jnp.zeros(L, _I32), ret=jnp.zeros(L, _I32),
            dead=jnp.zeros(pd, bool),
            heard=jnp.zeros(pd * NDIR, _I32),
            probed=jnp.zeros(pd * NDIR, _I32),
            lost=jnp.zeros(L, _I32),
        )
        return st._replace(**self._ring_views(st.addrs, st.n_live))

    # -- shared jitted helpers ----------------------------------------------

    @staticmethod
    def _owner_of(addrs: jnp.ndarray, n_live: jnp.ndarray,
                  q: jnp.ndarray) -> jnp.ndarray:
        """Peer row owning each address (successor with wrap) — one
        binary search over the padded sorted-prefix table (the NO_ADDR
        sentinels sort above every query, so the search lands in
        [0, n_live] and one conditional subtract wraps it: a vector
        `%` by a traced divisor costs the TPU compiler ~40 s)."""
        i = jnp.searchsorted(addrs, q, side="left").astype(_I32)
        n = n_live.astype(_I32)
        return jnp.where(i >= n, i - n, i)

    def _lane_of(self, addrs: jnp.ndarray, n_live: jnp.ndarray,
                 dest: jnp.ndarray) -> jnp.ndarray:
        """Owner lane of each destination address: the ownership rule of
        the partitioned wheel (DESIGN.md §8)."""
        return (self._owner_of(addrs, n_live, dest)
                // self.lane_rows).astype(_I32)

    def _ring_views(self, addrs: jnp.ndarray, n_live: jnp.ndarray) -> dict:
        """Recompute prev/pos from the padded address table (vacant rows
        hold garbage; they are never dereferenced — owner lookups return
        occupied rows only). Row 0 wraps to the last occupied row by a
        select, not a vector `%` (~40 s of TPU compile)."""
        j = jnp.arange(addrs.shape[0], dtype=_I32) - 1
        prev = addrs[jnp.where(j < 0, n_live.astype(_I32) - 1, j)]
        pos = A.position_from_segment(prev, addrs, self.d)
        return {"prev": prev, "pos": pos}

    # does `addr` fall in the segment (a_prev, a_self]? O(1) ownership
    # test given the segment edges; the wrapped (root) segment has
    # a_prev >= a_self
    _in_segment = staticmethod(in_segment)

    @staticmethod
    def _compact(mask: jnp.ndarray, budget: int) -> Tuple[jnp.ndarray, jnp.ndarray]:
        """Indices of the first `budget` set bits of `mask`, gather-only.

        Returns (idx (budget,) int32 — len(mask) where exhausted — and the
        per-element ordinal cumsum of `mask`). searchsorted on the cumsum
        replaces the usual full-length scatter, which is far slower on
        CPU XLA than this gather-based form.
        """
        cum = jnp.cumsum(mask.astype(_I32))
        idx = jnp.searchsorted(
            cum, jnp.arange(1, budget + 1, dtype=_I32), side="left"
        ).astype(_I32)
        return idx, cum

    @staticmethod
    def _group_ranks(g: jnp.ndarray, live: jnp.ndarray, n_groups: int,
                     scan: bool = None):
        """Stable within-group ranks + per-group counts for a flat row
        batch: rank[i] = #live rows j < i with g[j] == g[i] (dead rows
        rank 0; callers never read them). The deterministic multi-append
        primitive of the partitioned wheel (ranks depend only on the
        GLOBAL row order, which the boundary exchange fixes lane-major,
        so appends are mesh-invariant).

        Two exact forms of one result, chosen by the platform compiled
        for (`scan=None`): off the TPU one stable argsort — O(m log m),
        the cheapest on a CPU; on the TPU one masked cumsum per group
        key — O(n_groups * m) elementwise work and no sort, since the
        TPU compiler spends 20-40 s on every sort and every engine
        program appends."""
        m = g.shape[0]
        key = jnp.where(live, g, n_groups).astype(_I32)
        if not (on_tpu() if scan is None else scan):
            order = jnp.argsort(key, stable=True).astype(_I32)
            ks = key[order]
            first = jnp.searchsorted(ks, ks, side="left").astype(_I32)
            rank = jnp.zeros(m, _I32).at[order].set(
                jnp.arange(m, dtype=_I32) - first)
            counts = jnp.zeros(n_groups + 1, _I32).at[key].add(1)
            return jnp.where(live, rank, 0), counts[:n_groups]

        def one(k, c):
            rank, counts = c
            hit = key == k
            cs = jnp.cumsum(hit.astype(_I32))
            return jnp.where(hit, cs - 1, rank), counts.at[k].set(cs[-1])

        return jax.lax.fori_loop(
            0, n_groups, one,
            (jnp.zeros(m, _I32), jnp.zeros(n_groups, _I32)))

    def _append_rows(self, buf, cnt, rows, lane, slot, live, cap, base):
        """Append the GLOBAL `rows` batch into the local lane arenas.

        `buf` (Ln, SLOTS, width, roww) / `cnt` (Ln, SLOTS) are the LOCAL
        lane block starting at global lane `base`; `rows` (m, roww) with
        per-row `lane`/`slot`/`live` describe the whole (replicated)
        exchange output. Rows land at cnt + stable-rank within their
        (lane, slot) group; overflow past `cap` drops. Returns
        (buf, cnt, attempted (Ln,), dropped (Ln,)) — attempted counts
        every live row destined to a local lane (conservation `enq`),
        dropped the overflowed ones."""
        Ln, width, roww = cnt.shape[0], buf.shape[2], buf.shape[3]
        ng = self.lanes * SLOTS
        g = lane * SLOTS + slot
        rank, counts = self._group_ranks(g, live, ng)
        lloc = lane - base
        owned = live & (lloc >= 0) & (lloc < Ln)
        lsafe = jnp.where(owned, lloc, 0)
        off = cnt[lsafe, slot] + rank
        ok = owned & (off < cap)
        flat = jnp.where(ok, (lsafe * SLOTS + slot) * width + off,
                         Ln * SLOTS * width)
        nbuf = buf.reshape(Ln * SLOTS * width, roww).at[flat].set(
            rows, mode="drop").reshape(buf.shape)
        counts_loc = jax.lax.dynamic_slice_in_dim(
            counts.reshape(self.lanes, SLOTS), base, Ln, axis=0)  # (Ln, SLOTS)
        added = jnp.minimum(counts_loc, cap - cnt)
        ncnt = cnt + added
        attempted = counts_loc.sum(1)
        return nbuf, ncnt, attempted, attempted - added.sum(1)

    def _pack_out(self, pay: jnp.ndarray, seq: jnp.ndarray) -> jnp.ndarray:
        """(..., 3P) payload columns + (...,) seq -> (..., 3P+1) out rows
        (the X_out columns ARE the payload column layout)."""
        return jnp.concatenate([pay, seq[..., None]], axis=-1)

    def _rules(self, in_cols, out_cols, x):
        """Problem-generic threshold rules dispatch over (N, 3P) payload
        columns: the fused Pallas `threshold_step` kernel when enabled
        (any problem — the kernel traces the problem's own `test_cols`),
        else the shared jnp rules. Returns (viol (N,3), out (N,),
        pay (N,3P)) — bit-identical either way."""
        if "threshold" in self._wk:
            return threshold_step(self.problem, in_cols, out_cols, x,
                                  use_kernel=True, interpret=self._wk_interp)
        return P.threshold_rules_cols(self.problem, jnp, in_cols, out_cols, x)

    def _test_phase(self, st: DeviceState):
        """Full-width threshold rules (event paths + parity surface):
        the fused Pallas kernel for the majority problem on TPU, the
        problem-generic `threshold_step` kernel when wheel kernels are
        on, the shared jnp rules elsewhere. Returns (viol (pd,3),
        pay (pd,3P) payload columns)."""
        pw = self.pw
        in_cols = link_cols(peer_links(st.inbox, pw), pw)
        out_cols = st.out[:, :NDIR * pw]
        if self._is_majority and "threshold" not in self._wk:
            viol, _, po, pt = majority_step(
                in_cols[:, 0:3], in_cols[:, 3:6], out_cols[:, 0:3],
                out_cols[:, 3:6], st.x[:, 0], use_kernel=self._use_kernel,
            )
            return viol, jnp.concatenate([po, pt], axis=-1)
        viol, _, pay = self._rules(in_cols, out_cols, st.x)
        return viol, pay

    def _outputs_match(self, st: DeviceState, truth: jnp.ndarray) -> jnp.ndarray:
        """Threshold convergence predicate, on device (the superstep's
        per-cycle early-exit check — output column only, no rule set).
        Works on the plane's local rows — under the sharded plane this is
        a per-shard scan plus one scalar psum."""
        pd = st.x.shape[0]
        out = knowledge_outputs(self.problem, st.inbox, st.x, pd).astype(_I32)
        occ = self._plane.occ(st)
        ok = self.problem.converged(jnp, out, truth) | ~occ
        if self._faults is not None:
            # crashed-but-unevicted peers have no say in convergence
            rows_l = (self._plane.lane_base(st.wcnt.shape[0])
                      * self.lane_rows + jnp.arange(pd, dtype=_I32))
            ok = ok | st.dead[rows_l]
        return self._plane.all_true(ok)

    # -- event-path enqueue (ranked append; any width, per-row hash delay) --

    def _enqueue_events(self, st: DeviceState, cand, origin, dest, edge,
                        has_edge, pay, seq,
                        alert: bool = False) -> DeviceState:
        """Append the `cand` rows of an *event* (init / data change /
        churn) to the wheel of the DEST owner's lane. The inputs are the
        GLOBAL event block (callers `gather_events` first), so the
        within-group append ranks are mesh-invariant; each plane appends
        only the rows whose owner lane it holds. ALERT rows go to the
        side-wheel, due immediately. All args are flat (m,) columns;
        `pay` is the sequence of the P payload components."""
        m = cand.shape[0]
        u = lambda a: a.astype(_U32)
        if alert:
            due = jnp.broadcast_to(st.t, (m,))
        else:
            due = st.t + _hash_delay(
                jnp.arange(m, dtype=_I32), st.t + st.evt_ctr, st.salt_enq
            )
        rows = jnp.stack(
            [u(origin), u(dest), u(edge), u(has_edge)]
            + [u(p) for p in pay]
            + [u(seq), u(due)],
            axis=1,
        )  # (m, roww)
        lane = self._lane_of(st.addrs, st.n_live, u(dest))
        slot = (due % SLOTS).astype(_I32)
        base = self._plane.lane_base(st.wcnt.shape[0])
        if alert:
            buf, cnt, cap = st.awheel, st.acnt, self.lane_alert_w
        else:
            buf, cnt, cap = st.wheel, st.wcnt, self.lane_cap
        buf, cnt, att, dro = self._append_rows(
            buf, cnt, rows, lane, slot, cand, cap, base)
        st = st._replace(enq=st.enq + att, dropped=st.dropped + dro,
                         evt_ctr=st.evt_ctr + 1)
        if alert:
            return st._replace(awheel=buf, acnt=cnt)
        return st._replace(wheel=buf, wcnt=cnt)

    def _react_impl(self, st: DeviceState, touched: jnp.ndarray) -> DeviceState:
        """Threshold test() + Send(v) for all `touched` peers (full-width
        event path: initialization and data changes). Elementwise
        full-width X_out/seq updates over the plane's local rows, then
        one event append for the sends — assembled into global row
        order through `plane.gather_events` (identity on one device, an
        all_gather on the sharded plane)."""
        pd, d = st.x.shape[0], self.d  # pd: plane-local rows
        if self._faults is not None:
            rows_l = (self._plane.lane_base(st.wcnt.shape[0])
                      * self.lane_rows + jnp.arange(pd, dtype=_I32))
            touched = touched & ~st.dead[rows_l]  # the dead never send
        viol, pay = self._test_phase(st)  # (pd,3), (pd,3P)
        eff = viol & touched[:, None]
        pw = self.pw
        seq = st.out[:, NDIR * pw] + eff.any(1).astype(_I32)
        new_pay = jnp.where(jnp.tile(eff, (1, pw)), pay, st.out[:, :NDIR * pw])
        st = st._replace(out=self._pack_out(new_pay, seq))
        pos_l, addrs_l, prev_l = self._plane.local_tables(st)
        dirs = jnp.broadcast_to(jnp.arange(NDIR, dtype=_I32)[None, :], (pd, NDIR))
        bc = lambda a: jnp.broadcast_to(a[:, None], (pd, NDIR))
        valid, origin, dest, edge, has_edge = P.send_fields(
            jnp, bc(pos_l), dirs, bc(addrs_l), bc(prev_l), d
        )
        cand = (eff & valid).reshape(-1)
        (cand, origin, dest, edge, has_edge, seq_g, *pay_g) = \
            self._plane.gather_events(
                cand, origin.reshape(-1), dest.reshape(-1),
                edge.reshape(-1), has_edge.reshape(-1), bc(seq).reshape(-1),
                *[a.reshape(-1) for a in P.comps(pay, pw)])
        return self._enqueue_events(
            st, cand, origin, dest, edge, has_edge, pay_g, seq_g,
            alert=False,
        )

    # -- the cycle (superstep body) ------------------------------------------

    def _cycle_impl(self, st: DeviceState) -> DeviceState:
        """One simulation cycle: drain each local lane's due bucket,
        route, accept, react; stage every re-entering/new row with its
        lane-relative delay ordinal; one boundary exchange routes the
        staged rows to their owner lanes for the ranked appends."""
        pd, d = self.pad, self.d  # GLOBAL pad: sentinel/index space (the
        # plane's x rows may be a shard-local block of it)
        L = self.lanes
        Bl, Al = self.lane_budget, self.lane_alert_w
        WWl, Wl, cap = self.window_l, self.lane_width, self.lane_cap
        roww = self.roww
        Ln = st.wcnt.shape[0]  # LOCAL lanes (= L on one device)
        WW = Ln * WWl          # local drain-window width, lane-major

        s = (st.t % SLOTS).astype(_I32)
        s1 = ((st.t + 1) % SLOTS).astype(_I32)
        # one materialized read of each lane's due slot: window, slip
        # block and leftover shift all source from `sbuf`, so the wheel
        # itself is only ever *written* below — XLA aliases the whole
        # update chain in place
        abuf = jax.lax.dynamic_slice(
            st.awheel, (0, s, 0, 0), (Ln, 1, Al, roww))[:, 0]
        sbuf = jax.lax.dynamic_slice(
            st.wheel, (0, s, 0, 0), (Ln, 1, Wl, roww))[:, 0]
        n_alert = jax.lax.dynamic_slice_in_dim(st.acnt, s, 1, axis=1)[:, 0]
        dcnt = jax.lax.dynamic_slice_in_dim(st.wcnt, s, 1, axis=1)[:, 0]
        n_data = jnp.minimum(dcnt, Bl)  # (Ln,)

        # lane-major window: per lane [A_l alert rows, B_l data rows].
        # The per-lane layout is mesh-size invariant, so every
        # within-lane index computed below is too
        w = jnp.concatenate([abuf, sbuf[:, :Bl]], axis=1).reshape(WW, roww)
        li = jnp.arange(WWl, dtype=_I32)
        is_alert_l = li < Al
        live = jnp.where(is_alert_l[None, :], li[None, :] < n_alert[:, None],
                         (li - Al)[None, :] < n_data[:, None]).reshape(WW)
        is_alert = jnp.broadcast_to(is_alert_l[None, :], (Ln, WWl)).reshape(WW)
        wi = jnp.arange(WW, dtype=_I32)
        has_alerts = n_alert.sum() > 0
        w_origin, w_dest, w_edge = w[:, ORIGIN], w[:, DEST], w[:, EDGE]
        w_has_edge = ((w[:, HAS_EDGE] & _U32(1)) != 0) & live
        w_cont = (w[:, HAS_EDGE] & CONT) != 0
        if self._faults is not None:
            # probe rows ride the alert side-wheel but are NOT alerts:
            # they route like data and on accept only refresh `heard`
            # and force the ack Send
            w_probe = (w[:, HAS_EDGE] & PROBE) != 0
            is_alert = is_alert & ~w_probe
        else:
            w_probe = jnp.zeros(WW, bool)
        w_pay = w[:, PAY0:PAY0 + self.pw]  # (WW, P) uint32 payload bits
        w_seq = w[:, self._SEQ].astype(_I32)

        owner = self._owner_of(st.addrs, st.n_live, w_dest)
        pos_i = st.pos[owner]
        a_prev = st.prev[owner]
        a_self = st.addrs[owner]
        self_seg = self._in_segment(w_origin, a_prev, a_self)
        max_addr = st.addrs[st.n_live - 1]

        # ---- injected fault plane at the due-scan (DESIGN.md §10).
        # Rows whose receiving owner has crashed die with it (any kind);
        # live data rows are independently dropped / re-delayed by
        # seeded hashes keyed on the GLOBAL window index, so numpy sees
        # the same policy and every mesh size draws identical faults.
        # Probes and Alg. 2 ALERTs ride the reliable control plane —
        # membership truth never forks. Lost / delayed rows are masked
        # out of `live` BEFORE routing: they are not charged this cycle
        # (a delayed row re-enters without CONT and is charged when it
        # actually delivers, matching the reference simulator).
        delay_m = jnp.zeros(WW, bool)
        if self._faults is not None:
            lost_m = live & st.dead[owner]
            is_data_row = ~is_alert & ~w_probe
            gwi = (wi + self._plane.lane_base(Ln) * WWl).astype(_U32)
            if self._faults.p_drop > 0.0:
                lost_m = lost_m | (live & is_data_row & (
                    _hash_u32(gwi, st.t, jnp.asarray(self._fsalt_drop))
                    < self._p_drop_thr))
            if self._faults.p_delay > 0.0:
                delay_m = (live & is_data_row & ~lost_m & (
                    _hash_u32(gwi, st.t, jnp.asarray(self._fsalt_delay))
                    < self._p_delay_thr))
            live = live & ~lost_m & ~delay_m
            n_lost_l = lost_m.reshape(Ln, WWl).sum(1).astype(_I32)

        # ---- Alg. 1 delivery, two-phase (shared rules with
        # deliver_network_step, restructured for the width/latency split:
        # two full-width descent steps settle all but a few percent of
        # the window; the while_loop tail then runs at narrow width).
        entry = live & ~w_cont
        lv, cur_d, cur_e, cur_h = live, w_dest, w_edge, w_has_edge
        false_b = jnp.zeros(WW, bool)
        acc, drop = false_b, false_b
        o_dest, o_edge, o_he = w_dest, w_edge, w_has_edge
        for _ in range(2):
            dlv = P.deliver_rules(
                jnp, origin=w_origin, dest=cur_d, edge=cur_e, has_edge=cur_h,
                network_entry=entry, pos_i=pos_i, a_prev=a_prev,
                a_self=a_self, self_seg=self_seg, max_addr=max_addr, d=d,
                repair=True,
            )
            moving = lv & ~dlv.accept & ~dlv.drop
            stay = moving & self._in_segment(dlv.new_dest, a_prev, a_self)
            fwdn = moving & ~stay
            acc = acc | (lv & dlv.accept)
            drop = drop | (lv & dlv.drop & ~dlv.accept)
            o_dest = jnp.where(fwdn, dlv.new_dest, o_dest)
            o_edge = jnp.where(fwdn, dlv.new_edge, o_edge)
            o_he = jnp.where(fwdn, dlv.new_has_edge, o_he)
            cur_d = jnp.where(stay, dlv.new_dest, cur_d)
            cur_e = jnp.where(stay, dlv.new_edge, cur_e)
            cur_h = jnp.where(stay, dlv.new_has_edge, cur_h)
            entry = entry & ~stay
            lv = stay
        # narrow tail: compact the survivors PER LANE (so the spill set
        # is lane-local, hence mesh-invariant; per-lane window order puts
        # alerts first and narrow_l >= lane_alert_w, so alerts always
        # fit — only data can spill)
        NWl = self.narrow_l
        NT = Ln * NWl
        lv_l = lv.reshape(Ln, WWl)
        sidx_l, scum_l = jax.vmap(lambda mk: self._compact(mk, NWl))(lv_l)
        spill = (lv_l & (scum_l > NWl)).reshape(WW)
        sok_l = sidx_l < WWl  # (Ln, NWl)
        sp = jnp.where(
            sok_l, sidx_l + (jnp.arange(Ln, dtype=_I32) * WWl)[:, None], 0
        ).reshape(NT)
        sok = sok_l.reshape(NT)
        if "descent" in self._wk:
            acc2, drop2, od2, oe2, ohe2 = descent_tail(
                w_origin[sp], cur_d[sp], cur_e[sp], cur_h[sp], sok,
                jnp.zeros(NT, bool), pos_i[sp], a_prev[sp], a_self[sp],
                self_seg[sp], max_addr, d,
                use_kernel=True, interpret=self._wk_interp,
            )
        else:
            acc2, drop2, od2, oe2, ohe2 = deliver_network_step(
                origin=w_origin[sp], dest=cur_d[sp], edge=cur_e[sp],
                has_edge=cur_h[sp], live=sok, pos_i=pos_i[sp],
                a_prev=a_prev[sp], a_self=a_self[sp], self_seg=self_seg[sp],
                max_addr=max_addr, d=d, entry=jnp.zeros(NT, bool),
            )
        pack = jnp.stack(
            [acc2.astype(_U32) | (drop2.astype(_U32) << 1), od2, oe2,
             ohe2.astype(_U32)], axis=1,
        )
        stage = jnp.zeros((WW, 4), _U32).at[jnp.where(sok, sp, WW)].set(
            pack, mode="drop")
        merged = lv & ~spill
        acc = acc | (merged & ((stage[:, 0] & 1) != 0))
        drop = drop | (merged & ((stage[:, 0] & 2) != 0))
        o_dest = jnp.where(merged, stage[:, 1], o_dest)
        o_edge = jnp.where(merged, stage[:, 2], o_edge)
        o_he = jnp.where(merged, stage[:, 3] != 0, o_he)
        fwd = live & ~acc & ~drop & ~spill

        # ---- ACCEPT. One data winner per (peer, dir) link per cycle;
        # colliding rows defer (re-enter the wheel) and the monotone
        # per-link seq floor orders them on redelivery. An accepted ALERT
        # zeroes the link and forces Send(v); a same-cycle data delivery
        # is logically newer than the alert (post-zero sequence floor).
        # Every acceptor's link belongs to the row's own lane (ownership
        # rule), so the whole phase is lane-local: the election compares
        # within-lane window indices only, and on the sharded plane no
        # collective runs here at all.
        recv = owner
        vdir = jnp.asarray(A.direction_of(w_origin, st.pos[recv], d), _I32)
        flat = recv * NDIR + vdir
        acc_d = acc & ~is_alert
        acc_a = acc & is_alert
        pl = self._plane  # all peer-plane access below goes through it
        sent = pd * NDIR  # scatter sentinel (owned by no plane row/shard)
        heard = st.heard
        if self._faults is not None:
            acc_p = acc & w_probe
            acc_d = acc_d & ~w_probe
            # every accept — data, duplicate, alert or probe — is proof
            # of life on that link (t is monotone, so max == set)
            heard = jnp.maximum(heard, pl.link_max(
                flat, jnp.broadcast_to(st.t.astype(_I32), (WW,)), acc))
        if "dedup" in self._wk:
            # window-local fused election: all decisions (including the
            # react representative and the alert force mask) come from an
            # O(WW^2) blocked all-pairs kernel over the window rows —
            # no O(pad) plane, no collectives
            link_seq = pl.take_link(st.inbox, flat)[:, self.pw]
            (winner, loser, fresh, alert_write, is_rep, aforce) = due_dedup(
                flat, acc_d, acc_a, w_seq, link_seq, nl=sent,
                use_kernel=True, interpret=self._wk_interp,
            )
            abest = None
        else:
            best = pl.link_max(flat, wi, acc_d)
            abest = jax.lax.cond(
                has_alerts,
                lambda: pl.link_max(flat, wi, acc_a),
                lambda: pl.link_floor(),
            )
            best_w = pl.link_read(best, flat)
            abest_w = pl.link_read(abest, flat)
            winner = acc_d & (wi == best_w)
            loser = acc_d & ~winner
            floor = jnp.where(abest_w >= 0, 0,
                              pl.take_link(st.inbox, flat)[:, self.pw])
            fresh = winner & (w_seq > floor)
            alert_write = acc_a & (best_w < 0)
            cand_rep = jnp.maximum(best, abest)
            if self._faults is not None:
                pbest = pl.link_max(flat, wi, acc_p)
                cand_rep = jnp.maximum(cand_rep, pbest)
            rep_w = pl.peer_dirmax(cand_rep, recv)  # (WW,)
            is_rep = acc & (wi == rep_w)
            aforce = None
        # one width-WW scatter: a window row is either a fresh data write
        # or an alert zeroing a link with no data winner (disjoint rows
        # AND disjoint links, so no duplicate indices)
        data_idx = jnp.where(fresh | alert_write, flat, sent)
        data_val = jnp.where(
            alert_write[:, None], 0,
            jnp.concatenate([w_pay.astype(_I32), w_seq[:, None]], axis=1),
        )
        inbox = pl.put_link(st.inbox, data_idx, data_val)
        st = st._replace(inbox=inbox)

        # ---- react: gather-based test() + Send on the touched peers
        # (one representative window row per peer; work ∝ window, not
        # pad). The react VALUES are computed at compacted positions for
        # work reduction, then scattered BACK to window-row positions —
        # the send block must stay in window order, because the staging
        # ordinals below are lane-relative (compacted positions mix
        # lanes and would make delays depend on lane co-residency)
        reps_w, _ = self._compact(is_rep, WW)
        rvalid = reps_w < WW
        reps_safe = jnp.where(rvalid, reps_w, 0)
        rp = jnp.where(rvalid, recv[reps_safe], 0)
        pw = self.pw
        rin = pl.take_peer(peer_links(inbox, pw), rp)   # (WW, 3(P+1))
        ro = pl.take_peer(st.out, rp)                    # (WW, 3P+1)
        viol, _, pay = self._rules(
            link_cols(rin, pw), ro[:, :NDIR * pw], pl.take_peer(st.x, rp)
        )
        if aforce is None:
            force = (pl.link_read3(abest, rp) >= 0) & has_alerts
        else:  # per-peer alert mask already elected window-locally
            force = aforce[reps_safe] & has_alerts
        if self._faults is not None:
            # probe ack: an accepted probe forces an unconditional
            # ordinary Send back on that link (anti-entropy — also
            # repairs whatever state the drop faults destroyed)
            force = force | (pl.link_read3(pbest, rp) >= 0)
        eff = (viol | force) & rvalid[:, None]
        seq2 = ro[:, NDIR * pw] + eff.any(1).astype(_I32)
        ro2 = self._pack_out(
            jnp.where(jnp.tile(eff, (1, pw)), pay, ro[:, :NDIR * pw]), seq2)
        st = st._replace(out=pl.put_peer(
            st.out, jnp.where(rvalid, rp, pd), ro2))

        dirs3 = jnp.broadcast_to(jnp.arange(NDIR, dtype=_I32)[None, :], (WW, NDIR))
        bc = lambda a: jnp.broadcast_to(a[:, None], (WW, NDIR))
        valid, s_origin, s_dest, s_edge, s_he = P.send_fields(
            jnp, bc(st.pos[rp]), dirs3, bc(st.addrs[rp]), bc(st.prev[rp]), d
        )
        # scatter the send block back to window-row positions (rep row i
        # owns window row reps_w[i]); invalid rep slots drop
        widx = jnp.where(rvalid, reps_safe, WW)

        def back(v):
            return jnp.zeros((WW,) + v.shape[1:], v.dtype).at[widx].set(
                v, mode="drop")

        cand = back(eff & valid)        # (WW, NDIR) bool, window order
        b_origin, b_dest = back(s_origin), back(s_dest)
        b_edge, b_he = back(s_edge), back(s_he.astype(_U32))
        b_pay = back(pay)               # (WW, 3P) payload columns
        b_seq = back(seq2)              # (WW,)

        # ---- wheel maintenance (lane-local): slip one cycle, shift
        # leftovers to the front (revisited a revolution later).
        # Everything below only *writes* the wheel (sources are `sbuf`),
        # keeping the donated update chain alias-clean.
        wcnt_s1 = jax.lax.dynamic_slice_in_dim(st.wcnt, s1, 1, axis=1)[:, 0]
        slip_avail = jnp.clip(dcnt - Bl, 0, Bl)
        slip_k = jnp.minimum(slip_avail, cap - wcnt_s1)  # (Ln,)
        leftover = jnp.clip(dcnt - Bl - slip_k, 0, Wl - 2 * Bl)
        # honest over-budget accounting: count each backlog row ONCE, the
        # first cycle it misses the drain window, then brand it LATE so a
        # standing backlog doesn't recount every cycle it sits over
        # budget — per lane, so the sum over lanes counts each row once
        # GLOBALLY no matter how lanes are distributed over devices
        tail = sbuf[:, Bl:]  # (Ln, Wl - Bl, roww)
        tail_live = (jnp.arange(Wl - Bl, dtype=_I32)[None, :]
                     < (dcnt - Bl)[:, None])
        n_late_new = (tail_live
                      & ((tail[:, :, HAS_EDGE] & LATE) == 0)).sum(1).astype(_I32)
        shifted = jax.vmap(
            lambda b, k: jax.lax.dynamic_slice(b, (Bl + k, 0),
                                               (Wl - 2 * Bl, roww))
        )(sbuf, slip_k)
        shifted = shifted.at[:, :, HAS_EDGE].set(
            shifted[:, :, HAS_EDGE] | LATE)
        wheel = jax.lax.dynamic_update_slice(
            st.wheel, shifted[:, None], (0, s, 0, 0))
        col = jnp.arange(SLOTS, dtype=_I32)[None, :]
        wcnt = jnp.where(col == s, leftover[:, None], st.wcnt)
        acnt = jnp.where(col == s, 0, st.acnt)
        # slip block: rows [B_l, 2B_l) of the drained slot, due next cycle
        slip_rows = sbuf[:, Bl:2 * Bl].at[:, :, self._DT].set(
            (st.t + 1).astype(_U32))
        slip_rows = slip_rows.at[:, :, HAS_EDGE].set(
            slip_rows[:, :, HAS_EDGE] | LATE)
        wheel = jax.vmap(
            lambda wl, r, c: jax.lax.dynamic_update_slice(wl, r[None],
                                                          (s1, c, 0))
        )(wheel, slip_rows, wcnt_s1)
        wcnt = jnp.where(col == s1, (wcnt_s1 + slip_k)[:, None], wcnt)

        # ---- staging: one rigid per-lane block of every row that
        # (re-)enters a wheel — [WWl re-entry rows at window positions |
        # 3*WWl send rows at window-row-major positions]. The delay
        # ordinal is the row's rank within ITS LANE's block (cumsum), so
        # delay assignment is mesh-invariant; the `stage_rows` kernel
        # stamps DELIVER_T (alerts: t+1, data: t + perm[ordinal mod 10])
        f_dest = jnp.where(fwd, o_dest, jnp.where(spill, cur_d, w_dest))
        f_edge = jnp.where(fwd, o_edge, jnp.where(spill, cur_e, w_edge))
        # losers and spills re-enter as continuations: their network hop
        # was already charged at first window entry
        f_he = (jnp.where(fwd, o_he, jnp.where(spill, cur_h, w_has_edge))
                .astype(_U32) | jnp.where(spill | loser, CONT, _U32(0)))
        if self._faults is not None:
            # forwarded probes keep their marker bit (o_he is a bare
            # bool); delayed rows re-enter as fresh deliveries, except
            # a delayed mid-descent spill keeps CONT so redelivery
            # resumes the descent instead of recounting a network entry
            f_he = (f_he | jnp.where(w_probe, PROBE, _U32(0))
                    | jnp.where(delay_m & w_cont, CONT, _U32(0)))
        re_rows = jnp.stack(
            [w_origin, f_dest, f_edge, f_he]
            + [w_pay[:, c] for c in range(self.pw)]
            + [w[:, self._SEQ], w[:, self._DT]],
            axis=1,
        ).reshape(Ln, WWl, roww)
        u = lambda a: a.reshape(-1).astype(_U32)
        send_rows = jnp.stack(
            [u(b_origin), u(b_dest), u(b_edge), u(b_he)]
            + [u(a) for a in P.comps(b_pay, pw)]
            + [u(bc(b_seq)), u(bc(b_seq))],
            axis=1,
        ).reshape(Ln, NDIR * WWl, roww)
        re_mask = (fwd | loser | spill | delay_m).reshape(Ln, WWl)
        re_alert = (fwd & (is_alert | w_probe)).reshape(Ln, WWl)
        blk_rows = jnp.concatenate([re_rows, send_rows], axis=1)
        blk_mask = jnp.concatenate(
            [re_mask, cand.reshape(Ln, NDIR * WWl)], axis=1)
        blk_alert = jnp.concatenate(
            [re_alert, jnp.zeros((Ln, NDIR * WWl), bool)], axis=1)
        ordinal = jnp.cumsum(blk_mask.astype(_I32), axis=1) - 1
        h = ((st.t + 1).astype(_U32) * _U32(0x9E3779B1) + st.salt_enq)
        perm = st.perms[(h >> _U32(28)).astype(_I32)]  # (10,) delays 1..10
        staged = stage_rows(
            blk_rows.reshape(-1, roww), blk_alert.reshape(-1),
            ordinal.reshape(-1), perm, st.t, dt_col=self._DT,
            use_kernel="enqueue" in self._wk, interpret=self._wk_interp,
        ).reshape(Ln, 4 * WWl, roww)
        meta = (blk_mask.astype(_U32) * META_LIVE
                | blk_alert.astype(_U32) * META_ALERT)
        pkt = jnp.concatenate([staged, meta[:, :, None]], axis=2)

        # ---- failure-detector probe emission (armed only): every local
        # peer row scans its links against the freshly-stamped `heard`;
        # links silent past `suspect_after` (and not re-probed within a
        # window) emit an empty-payload PROBE row, due next cycle on the
        # 1-cycle/hop side-wheel. Every structurally-valid link of a
        # live peer is monitored (`core.majority.monitored_links` — no
        # first-hop self test: descent through the peer's own segment
        # can still exit to a neighbor, and self-resolving links stay
        # fresh through their own probe accepts). The probe block rides
        # the same boundary
        # exchange as the cycle appends (local rows are lane-major, so
        # the reshape below lands each row in its own lane's block and
        # the exchange restores global lane-major order).
        probed = st.probed
        if self._faults is not None:
            f = self._faults
            nloc = heard.shape[0] // NDIR
            rows_g = (pl.lane_base(Ln) * self.lane_rows
                      + jnp.arange(nloc, dtype=_I32))
            pdirs = jnp.broadcast_to(
                jnp.arange(NDIR, dtype=_I32)[None, :], (nloc, NDIR))
            bcl = lambda a: jnp.broadcast_to(a[:, None], (nloc, NDIR))
            pvalid, p_org, p_dst, p_edge, p_he = P.send_fields(
                jnp, bcl(st.pos[rows_g]), pdirs, bcl(st.addrs[rows_g]),
                bcl(st.prev[rows_g]), d)
            mon = (pvalid & (rows_g < st.n_live)[:, None]
                   & ~st.dead[rows_g][:, None])
            want, _ = P.suspicion_rules(jnp, heard, probed, st.t,
                                        f.suspect_after, f.evict_after)
            emit = want.reshape(nloc, NDIR) & mon
            probed = jnp.where(emit.reshape(-1), st.t, probed)
            zrow = jnp.zeros((nloc, NDIR), _U32)
            due_p = jnp.broadcast_to((st.t + 1).astype(_U32), (nloc, NDIR))
            prows = jnp.stack(
                [p_org, p_dst, p_edge, p_he.astype(_U32) | PROBE]
                + [zrow] * self.pw + [zrow, due_p], axis=2,
            )  # (nloc, NDIR, roww)
            pmeta = emit.astype(_U32) * (META_LIVE | META_ALERT)
            ppkt = jnp.concatenate(
                [prows, pmeta[:, :, None]], axis=2,
            ).reshape(Ln, self.lane_rows * NDIR, roww + 1)
            pkt = jnp.concatenate([pkt, ppkt], axis=1)

        # ---- boundary exchange + ranked owner-lane appends: the ONE
        # lane-crossing step of the cycle. The exchange output is the
        # global lane-major staging order on every participant, so the
        # within-(lane, slot) append ranks are identical at any mesh size
        gpkt = pl.exchange(pkt)  # (L, 4*WWl [+ probe rows], roww + 1)
        grows = gpkt[:, :, :roww].reshape(-1, roww)
        gmeta = gpkt[:, :, roww].reshape(-1)
        glive = (gmeta & META_LIVE) != 0
        galert = (gmeta & META_ALERT) != 0
        glane = self._lane_of(st.addrs, st.n_live, grows[:, DEST])
        gslot = grows[:, self._DT].astype(_I32) % SLOTS
        base = pl.lane_base(Ln)
        wheel, wcnt, att_d, dro_d = self._append_rows(
            wheel, wcnt, grows, glane, gslot, glive & ~galert, cap, base)
        # ALERT appends are churn-only: cond-guarded on the (replicated)
        # gathered block, so every shard takes the same branch
        n_ga = (glive & galert).sum()

        def do_alerts(args):
            ab, ac = args
            return self._append_rows(
                ab, ac, grows, glane, gslot, glive & galert, Al, base)

        awheel, acnt, att_a, dro_a = jax.lax.cond(
            n_ga > 0, do_alerts,
            lambda a: (a[0], a[1], jnp.zeros(Ln, _I32), jnp.zeros(Ln, _I32)),
            (st.awheel, acnt),
        )

        # accounting (per lane; hosts read sums): every first-entry live
        # window row is one consumed network delivery; continuations
        # (mid-descent spills and collision-loser redeliveries) were
        # already charged
        n_defer_l = (loser | spill).reshape(Ln, WWl).sum(1).astype(_I32)
        if self._faults is not None:
            # armed accounting: only rows actually routed this cycle and
            # not already charged (CONT) consume a delivery; lost rows
            # retire into the fault ledger instead of `ret`
            n_charge_l = (live & ~w_cont).reshape(Ln, WWl).sum(1).astype(_I32)
            return st._replace(
                wheel=wheel, wcnt=wcnt, awheel=awheel, acnt=acnt,
                messages_sent=st.messages_sent + n_charge_l,
                deferred=st.deferred + n_late_new + n_defer_l,
                dropped=st.dropped + dro_d + dro_a,
                enq=st.enq + att_d + att_a,
                ret=st.ret + (n_alert + n_data) - n_lost_l,
                lost=st.lost + n_lost_l,
                heard=heard, probed=probed,
                t=st.t + 1,
            )
        n_cont_l = (live & w_cont).reshape(Ln, WWl).sum(1).astype(_I32)
        return st._replace(
            wheel=wheel, wcnt=wcnt, awheel=awheel, acnt=acnt,
            messages_sent=st.messages_sent + (n_alert + n_data) - n_cont_l,
            deferred=st.deferred + n_late_new + n_defer_l,
            dropped=st.dropped + dro_d + dro_a,
            enq=st.enq + att_d + att_a,
            ret=st.ret + n_alert + n_data,
            t=st.t + 1,
        )

    # -- superstep / chunked convergence ------------------------------------

    def _steps_impl(self, st: DeviceState, k: jnp.ndarray) -> DeviceState:
        """K cycles in one dispatch (`k` is traced: no re-jit per K)."""
        def body(c):
            return self._cycle_impl(c[0]), c[1] + 1

        st, _ = jax.lax.while_loop(
            lambda c: c[1] < k, body, (st, jnp.zeros((), _I32))
        )
        return st

    def _chunk_impl(self, st: DeviceState, truth: jnp.ndarray, k: jnp.ndarray,
                    stable: jnp.ndarray, stable_for: jnp.ndarray):
        """Up to `k` convergence-checked cycles in one dispatch.

        Per cycle (matching the reference loop exactly): evaluate the
        Alg. 3 predicate *before* stepping; a run of `stable_for`
        consecutive true checks exits without stepping further. Returns
        (state, stable, done, checks_used) — one host sync per chunk.
        """
        def cond(c):
            st, i, stable, done = c
            return (~done) & (i < k)

        def body(c):
            st, i, stable, done = c
            conv = self._outputs_match(st, truth)
            stable = jnp.where(conv, stable + 1, jnp.zeros((), _I32))
            done = stable >= stable_for
            st = jax.lax.cond(done, lambda x: x, self._cycle_impl, st)
            return st, i + 1, stable, done

        st, i, stable, done = jax.lax.while_loop(
            cond, body,
            (st, jnp.zeros((), _I32), stable, jnp.zeros((), bool)),
        )
        return st, stable, done, i

    # -- churn (Alg. 2) ------------------------------------------------------

    def _shift_peer_rows(self, st: DeviceState, src: jnp.ndarray) -> dict:
        """Gather-shift every peer-indexed table by the global source map
        `src` (join/leave row recompaction) — through the plane, so the
        sharded engine shifts its local blocks with one explicit
        all_gather instead of an inherited GSPMD program."""
        pl = self._plane
        link_src = (src[:, None] * NDIR
                    + jnp.arange(NDIR, dtype=_I32)[None, :]).reshape(-1)
        return {
            "x": pl.shift_rows(st.x, src), "out": pl.shift_rows(st.out, src),
            "inbox": pl.shift_rows(st.inbox, link_src),
            "addrs": st.addrs[src],
            # fault-plane stamps move with their peers (cheap event path;
            # zeros shift harmlessly when disarmed)
            "dead": st.dead[src],
            "heard": pl.shift_rows(st.heard, link_src),
            "probed": pl.shift_rows(st.probed, link_src),
        }

    def _join_impl(self, st: DeviceState, addr: jnp.ndarray,
                   vote: jnp.ndarray, k: jnp.ndarray) -> DeviceState:
        """Insert a peer row at `k` (gather-shift of the sorted prefix +
        one row write; `vote` is the joiner's (D,) data vector), then
        run the shared churn tail."""
        pdg = self.pad
        pl = self._plane
        idx = jnp.arange(pdg, dtype=_I32)
        src = jnp.where(idx <= k, idx, idx - 1)
        g = self._shift_peer_rows(st, src)
        n_live = st.n_live + 1
        lk = k * NDIR + jnp.arange(NDIR, dtype=_I32)
        tN = jnp.broadcast_to(st.t.astype(_I32), (NDIR,))
        st = st._replace(
            addrs=g["addrs"].at[k].set(addr),
            x=pl.put_peer(g["x"], k[None], vote[None].astype(_I32)),
            inbox=pl.put_link(g["inbox"], lk,
                              jnp.zeros((NDIR, self.pw + 1), _I32)),
            out=pl.put_peer(g["out"], k[None],
                            jnp.zeros((1, NDIR * self.pw + 1), _I32)),
            n_live=n_live,
            # the joiner starts alive with fresh detector stamps (a new
            # peer must get a full silence window before suspicion)
            dead=g["dead"].at[k].set(False),
            heard=pl.put_link(g["heard"], lk, tN),
            probed=pl.put_link(g["probed"], lk, tN),
        )
        st = st._replace(**self._ring_views(st.addrs, n_live))
        a_im2 = st.addrs[(k - 1) % n_live]
        a_i = st.addrs[(k + 1) % n_live]
        return self._churn_tail(st, a_im2, addr, a_i)

    def _leave_impl(self, st: DeviceState, k: jnp.ndarray) -> DeviceState:
        """Delete peer row `k` (gather-shift left + sentinel the vacated
        row), then run the shared churn tail."""
        pdg = self.pad
        pl = self._plane
        nb = st.n_live
        a_im1 = st.addrs[k]
        a_im2 = st.addrs[(k - 1) % nb]
        a_i = st.addrs[(k + 1) % nb]
        idx = jnp.arange(pdg, dtype=_I32)
        src = jnp.minimum(jnp.where(idx < k, idx, idx + 1), pdg - 1)
        last = nb - 1  # vacated row after the shift
        g = self._shift_peer_rows(st, src)
        ll = last * NDIR + jnp.arange(NDIR, dtype=_I32)
        st = st._replace(
            addrs=g["addrs"].at[last].set(NO_ADDR),
            x=pl.put_peer(g["x"], last[None],
                          jnp.zeros((1, self.dw), _I32)),
            inbox=pl.put_link(g["inbox"], ll,
                              jnp.zeros((NDIR, self.pw + 1), _I32)),
            out=pl.put_peer(g["out"], last[None],
                            jnp.zeros((1, NDIR * self.pw + 1), _I32)),
            n_live=last,
            dead=g["dead"].at[last].set(False),
            heard=pl.put_link(g["heard"], ll, jnp.zeros(NDIR, _I32)),
            probed=pl.put_link(g["probed"], ll, jnp.zeros(NDIR, _I32)),
        )
        st = st._replace(**self._ring_views(st.addrs, st.n_live))
        return self._churn_tail(st, a_im2, a_im1, a_i)

    def _crash_impl(self, st: DeviceState, k: jnp.ndarray) -> DeviceState:
        """Abrupt failure of peer row `k` (fault plane, DESIGN.md §10):
        the row's state zeroes and the dead flag raises — NO Alg. 2
        notification, no fence, no ring change. Rows already in flight
        toward the dead owner die lazily at the due-scan (charged to
        `lost`), so conservation stays exact without an arena sweep."""
        pl = self._plane
        lk = k * NDIR + jnp.arange(NDIR, dtype=_I32)
        return st._replace(
            dead=st.dead.at[k].set(True),
            x=pl.put_peer(st.x, k[None], jnp.zeros((1, self.dw), _I32)),
            inbox=pl.put_link(st.inbox, lk,
                              jnp.zeros((NDIR, self.pw + 1), _I32)),
            out=pl.put_peer(st.out, k[None],
                            jnp.zeros((1, NDIR * self.pw + 1), _I32)),
        )

    def _fence_and_migrate(self, st: DeviceState, pos_fix,
                           pos_var) -> DeviceState:
        """R3 fence + owner re-laning after a membership change.

        A join/leave moves the owner-ROW boundaries, so an in-flight row
        may now belong to another lane. Each local lane sweeps its
        arenas once: stale-origin data rows and dead rows drop (the
        fence; the ALERT side-wheel is never origin-fenced — routed
        ALERTs legitimately originate from the change positions),
        rows still owned stay compacted in place, and out-of-lane rows
        are collected (slot-major, deterministic) into a per-lane
        migration block that rides the same boundary exchange as cycle
        appends. Conservation: every removed row is retired; migrated
        rows re-enter through `enq`; a migration block overflow is
        counted in BOTH `enq` and `dropped` (the row was retired without
        a re-append) so the invariant stays exact and the loss visible.
        """
        Ln = st.wcnt.shape[0]
        roww = self.roww
        MW = self.mig_w
        base = self._plane.lane_base(Ln)
        lane_glob = base + jnp.arange(Ln, dtype=_I32)

        def sweep(buf, cnt, fence: bool):
            width = buf.shape[2]

            def one(b, c, lg):
                liveM = jnp.arange(width, dtype=_I32)[None, :] < c[:, None]
                rows = b.reshape(SLOTS * width, roww)
                lvf = liveM.reshape(-1)
                okrow = rows[:, self._DT] != NO_MSG
                if fence:
                    okrow = (okrow & (rows[:, ORIGIN] != pos_fix)
                             & (rows[:, ORIGIN] != pos_var))
                elif self._faults is not None:
                    # the ALERT side-wheel is never origin-fenced, but
                    # probe rows riding it are ordinary traffic under
                    # R3: a probe from a changed position is stale
                    pr = (rows[:, HAS_EDGE] & PROBE) != 0
                    okrow = okrow & ~(pr & ((rows[:, ORIGIN] == pos_fix)
                                            | (rows[:, ORIGIN] == pos_var)))
                inlane = self._lane_of(st.addrs, st.n_live,
                                       rows[:, DEST]) == lg
                keep = (lvf & okrow & inlane).reshape(SLOTS, width)
                move = lvf & okrow & ~inlane

                def cs(bs, ks):
                    i2, cum = self._compact(ks, width)
                    return bs[jnp.where(i2 < width, i2, 0)], cum[-1]

                nb, nc = jax.vmap(cs)(b, keep)
                midx, mcum = self._compact(move, MW)
                mok = midx < SLOTS * width
                mig = rows[jnp.where(mok, midx, 0)]
                lost = jnp.maximum(mcum[-1].astype(_I32) - MW, 0)
                removed = (c.sum() - nc.sum()).astype(_I32)
                return nb, nc.astype(_I32), mig, mok, removed, lost

            return jax.vmap(one)(buf, cnt, lane_glob)

        def relane(buf, cnt, cap, mig, mok):
            pkt = jnp.concatenate(
                [mig, (mok.astype(_U32) * META_LIVE)[:, :, None]], axis=2)
            g = self._plane.exchange(pkt)  # (L, MW, roww + 1)
            gr = g[:, :, :roww].reshape(-1, roww)
            gl = (g[:, :, roww].reshape(-1) & META_LIVE) != 0
            lane = self._lane_of(st.addrs, st.n_live, gr[:, DEST])
            slot = gr[:, self._DT].astype(_I32) % SLOTS
            return self._append_rows(buf, cnt, gr, lane, slot, gl, cap, base)

        wheel, wcnt, migd, mokd, rem_d, lost_d = sweep(st.wheel, st.wcnt, True)
        awheel, acnt, miga, moka, rem_a, lost_a = sweep(
            st.awheel, st.acnt, False)
        wheel, wcnt, att_d, dro_d = relane(wheel, wcnt, self.lane_cap,
                                           migd, mokd)
        awheel, acnt, att_a, dro_a = relane(awheel, acnt, self.lane_alert_w,
                                            miga, moka)
        return st._replace(
            wheel=wheel, wcnt=wcnt, awheel=awheel, acnt=acnt,
            ret=st.ret + rem_d + rem_a,
            enq=st.enq + att_d + att_a + lost_d + lost_a,
            dropped=st.dropped + dro_d + dro_a + lost_d + lost_a,
        )

    def _churn_tail(self, st: DeviceState, a_im2, a_im1, a_i) -> DeviceState:
        """Alg. 2 on device, mirroring `MajoritySimulator._apply_change`:

        1. fence + re-lane (R3 + ownership rule) — `_fence_and_migrate`;
        2. movers — peers whose post-change position IS pos_fix/pos_var —
           zero their whole X_in and send unconditionally everywhere;
        3. enqueue the <= 6 routed ALERT rows into the side-wheel (due
           immediately); the cycle loop delivers them through the same
           Alg. 1 router as data and fires the zero+Send upcall on
           accept.
        """
        pdg, d = self.pad, self.d
        pl = self._plane
        pw = self.pw
        pos_fix, pos_var = P.change_positions(jnp, a_im2, a_im1, a_i, d)
        st = self._fence_and_migrate(st, pos_fix, pos_var)

        cp = jnp.stack([pos_fix, pos_var])  # (2,)
        own = self._owner_of(st.addrs, st.n_live, cp)
        mover_rows = jnp.where(st.pos[own] == cp, own, pdg)
        mlinks = (mover_rows[:, None] * NDIR
                  + jnp.arange(NDIR, dtype=_I32)[None, :]).reshape(-1)
        st = st._replace(inbox=pl.put_link(
            st.inbox, jnp.where(mlinks < pdg * NDIR, mlinks, pdg * NDIR),
            jnp.zeros((2 * NDIR, pw + 1), _I32)))
        # movers: zero X_in done; unconditional Send in every direction
        # (test() re-run is subsumed — every direction sends)
        mv = mover_rows < pdg
        mp = jnp.where(mv, mover_rows, 0)
        if self._faults is not None:
            mv = mv & ~st.dead[mp]  # crashed peers are silent — no sends
        kloc = knowledge(self.problem, st.inbox, st.x, st.x.shape[0])
        kmp = pl.take_peer_rep(kloc, mp)  # (2, P), replicated
        seq2 = pl.take_peer_rep(st.out, mp)[:, NDIR * pw] + 1
        ro2 = self._pack_out(jnp.repeat(kmp, NDIR, axis=1), seq2)
        st = st._replace(out=pl.put_peer(
            st.out, jnp.where(mv, mp, pdg), ro2.astype(_I32)))
        dirs2 = jnp.broadcast_to(jnp.arange(NDIR, dtype=_I32)[None, :], (2, NDIR))
        bc2 = lambda a: jnp.broadcast_to(a[:, None], (2, NDIR))
        valid, origin, dest, edge, has_edge = P.send_fields(
            jnp, bc2(st.pos[mp]), dirs2, bc2(st.addrs[mp]), bc2(st.prev[mp]), d
        )
        st = self._enqueue_events(
            st, (valid & bc2(mv)).reshape(-1), origin.reshape(-1),
            dest.reshape(-1), edge.reshape(-1), has_edge.reshape(-1),
            [jnp.repeat(kmp[:, c], NDIR) for c in range(pw)],
            bc2(seq2).reshape(-1), alert=False,
        )

        ap, adirs = P.alert_plan(jnp, pos_fix, pos_var)  # (6,), (6,)
        aown = self._owner_of(st.addrs, st.n_live, ap)
        valid, origin, dest, edge, has_edge = P.send_fields(
            jnp, ap, adirs, st.addrs[aown], st.prev[aown], d
        )
        if self._faults is not None:
            valid = valid & ~st.dead[aown]  # the dead emit no ALERTs
            # a churn event is fresh news about the movers' links: the
            # detector must not age the NEW occupants on stamps carried
            # over from the old ones (the reference refreshes exactly the
            # mover rows synchronously in its alert upcall; the routed
            # ALERT recipients refresh on accept, and the host-side
            # `_heard_floor` bridges those cycles for the eviction sweep)
            st = st._replace(heard=jnp.maximum(st.heard, pl.link_max(
                mlinks, jnp.broadcast_to(st.t.astype(_I32), mlinks.shape),
                jnp.repeat(mv, NDIR))))
        zero6 = jnp.zeros(6, _U32)
        return self._enqueue_events(
            st, valid, origin, dest, edge, has_edge,
            [zero6] * pw, zero6, alert=True,
        )

    # -- engine API ----------------------------------------------------------

    @property
    def t(self) -> int:
        return int(self._st.t)

    @property
    def messages_sent(self) -> int:
        return int(np.asarray(self._st.messages_sent).sum())

    @property
    def in_flight(self) -> int:
        return int(self._st.wcnt.sum()) + int(self._st.acnt.sum())

    @property
    def dropped(self) -> int:
        """Messages lost to arena overflow; 0 unless capacity_per_peer is
        set too low (the numpy table grows instead — see DESIGN.md). A
        run with dropped > 0 is invalid (`run_until_converged` flags
        it)."""
        return int(np.asarray(self._st.dropped).sum())

    @property
    def deferred(self) -> int:
        """Deliveries pushed past their due time: over-budget rows slip
        one cycle or wait a wheel revolution (each row counted ONCE, the
        first cycle it misses its drain window — the LATE row bit stops
        recounts while a backlog stands), and same-link collision losers
        / mid-descent spills re-deliver later. Summed over lanes, so the
        figure is global and counts each row exactly once regardless of
        how the lanes are sharded."""
        return int(np.asarray(self._st.deferred).sum())

    @property
    def lost_to_fault(self) -> int:
        """Messages destroyed by the injected fault plane (crashed
        owners + `FaultConfig.p_drop`), itemized apart from `dropped`
        so engine bugs stay distinguishable from injected faults."""
        return int(np.asarray(self._st.lost).sum())

    @property
    def evictions(self):
        """[(cycle, address), ...] leaves the failure detector synthesized."""
        return list(self._evictions)

    def dead_mask(self) -> np.ndarray:
        """(n,) bool — crashed peers the detector has not yet evicted."""
        return np.asarray(self._st.dead)[: self.n].copy()

    def last_heard(self) -> np.ndarray:
        """(n,) cycle each peer's links last carried inbound traffic —
        the per-peer heartbeat `runtime.fault_tolerance` bridges from."""
        return np.asarray(self._st.heard).reshape(-1, NDIR)[: self.n].max(axis=1)

    @property
    def native_kernels(self) -> Tuple[str, ...]:
        """The wheel kernels this engine compiles natively: the enabled
        subset on a TPU, none elsewhere (enabled kernels then run in
        interpret mode, the parity surface)."""
        return () if self._wk_interp else tuple(sorted(self._wk))

    @property
    def deferral_rate(self) -> float:
        """Cumulative deferral events per consumed network delivery —
        the honest congestion figure for sizing `work_budget` (an
        init-storm transient shows up here, then decays)."""
        m = self.messages_sent
        return self.deferred / m if m else 0.0

    def check_conservation(self) -> dict:
        """The partitioned wheel's global row-conservation invariant:
        summed over lanes, every row ever appended (`enq`) is drained
        (`ret`), still live in an arena, or accounted `dropped`. Raises
        AssertionError on violation (a violation means a lane double
        counted or silently lost a row — exactly the regression class a
        sharded control plane invites); returns the figures."""
        st = self._st
        enq = int(np.asarray(st.enq).sum())
        ret = int(np.asarray(st.ret).sum())
        live = int(np.asarray(st.wcnt).sum()) + int(np.asarray(st.acnt).sum())
        dro = int(np.asarray(st.dropped).sum())
        lost = int(np.asarray(st.lost).sum())
        if enq != ret + live + dro + lost:
            raise AssertionError(
                f"wheel conservation violated: enqueued={enq} != "
                f"retired={ret} + live={live} + dropped={dro} + "
                f"lost_to_fault={lost}")
        return {"enqueued": enq, "retired": ret, "live": live,
                "dropped": dro, "lost_to_fault": lost}

    def outputs(self) -> np.ndarray:
        out = knowledge_outputs(self.problem, self._st.inbox, self._st.x,
                                self.pad)
        return np.asarray(out)[: self.n].astype(np.int64)

    def votes(self) -> np.ndarray:
        """(n,) scalar data (majority votes); (n, D) when D > 1."""
        x = np.asarray(self._st.x, dtype=np.int64)[: self.n]
        return x[:, 0] if self.dw == 1 else x

    def data(self) -> np.ndarray:
        """(n, D) quantized per-peer data plane (problem layer)."""
        return np.asarray(self._st.x, dtype=np.int64)[: self.n].copy()

    def set_votes(self, idx: np.ndarray, new_votes: np.ndarray) -> None:
        """Data-change upcall; `new_votes` is (k,) scalar data or (k, D)
        vectors in RAW units — quantized through the problem, exactly
        like `join`."""
        idx = np.asarray(idx)
        nd = self.problem.init_state(np.asarray(new_votes)).astype(np.int32)
        st = self._st
        x = st.x.at[jnp.asarray(idx)].set(jnp.asarray(nd))
        touched = jnp.zeros(self.pad, bool).at[jnp.asarray(idx)].set(True)
        self._st = self._react(st._replace(x=x), touched)

    def apply_coalesced(self, idx: np.ndarray, new_data: np.ndarray) -> int:
        """Serve-layer flush (see `repro.engine.base`): one coalesced
        batch applied as one batched `set_votes`, i.e. ONE full-width
        event-react dispatch — the wheel treats the flush exactly like
        any other data-change storm. Inherited unchanged by the
        mesh-sharded engine (its `_react` runs under shard_map)."""
        idx, vals = coalesced_update(idx, new_data, self.n)
        if idx.size:
            self.set_votes(idx, vals)
        return int(idx.size)

    def join(self, addr: int, vote=0) -> int:
        """Membership upcall: a peer joins at `addr` (Alg. 2) with scalar
        data or a (D,) vector. The padded tables absorb the row without
        recompilation; only outgrowing them triggers the (host-side)
        grow + re-pad path — and even that only retraces the programs
        for the new shape, it never rebuilds the jit objects."""
        ring_after, k = self.ring.join(int(addr))
        if ring_after.n > self.pad:
            self._grow(ring_after.n)
        self._st = self._join(
            self._st, jnp.asarray(np.uint32(addr)),
            jnp.asarray(self.problem.peer_data(vote).astype(np.int32)),
            jnp.asarray(k, _I32),
        )
        self.ring = ring_after
        self.n += 1
        if self._faults is not None:
            from repro.core import notify as N

            self._stamp_churn_floor(N.join_event(ring_after, k), ring_after)
        return k

    def leave(self, idx: int) -> None:
        """Membership upcall: peer `idx` departs (Alg. 2)."""
        if self.n <= 1:
            raise ValueError("cannot leave the last peer")
        if not 0 <= idx < self.n:
            raise IndexError(f"peer index {idx} out of range [0, {self.n})")
        ring_before = self.ring
        self._st = self._leave(self._st, jnp.asarray(idx, _I32))
        self.ring = ring_before.leave(idx)
        self.n -= 1
        if self._faults is not None:
            from repro.core import notify as N

            self._stamp_churn_floor(
                N.leave_event(self.ring, ring_before, idx), self.ring)

    def crash(self, idx: int) -> None:
        """Abrupt-failure upcall: peer `idx` vanishes silently — no
        Alg. 2 notification; its tree neighbors must discover the
        failure through the timeout detector. Requires an armed fault
        plane (``faults=`` at construction)."""
        if self._faults is None:
            raise RuntimeError(
                "crash() requires an armed fault plane (faults=FaultConfig)")
        if self.n <= 1:
            raise ValueError("cannot crash the last peer")
        if not 0 <= idx < self.n:
            raise IndexError(f"peer index {idx} out of range [0, {self.n})")
        if bool(np.asarray(self._st.dead)[idx]):
            raise ValueError(f"peer {idx} is already dead")
        self._st = self._crash(self._st, jnp.asarray(idx, _I32))

    def _stamp_churn_floor(self, ev, ring_after) -> None:
        """Record the synchronous `heard` refresh the reference performs
        at a churn event — movers (owners of the two change positions)
        on every direction, routed-ALERT recipients on the alerted one —
        keyed by (address, dir) so the stamps survive row shifts. The
        device links self-refresh when the routed alerts accept; until
        then the floor is what keeps `_fault_sweep` from evicting the
        freshly re-healed neighbors as silent."""
        t = int(self._st.t)
        pos = ring_after.positions()
        dt = ring_after.addrs.dtype
        for p in (ev.pos_fix, ev.pos_var):
            o = int(ring_after.owner(np.asarray([p], dt))[0])
            if int(pos[o]) == int(p):
                for dch in range(NDIR):
                    self._heard_floor[(int(ring_after.addrs[o]), dch)] = t
        for peer, dch in ev.notifs:
            self._heard_floor[(int(ring_after.addrs[peer]), int(dch))] = t

    def _fault_sweep(self) -> None:
        """Host-driven failure-detector eviction pass, run at dispatch
        boundaries. The device program handles the per-cycle half of the
        detector (probe emission + `heard` stamping); membership
        synthesis is an event path like join/leave, so it runs here:
        pull the stamps, elect the first-dark-hop accused peer
        (`core.majority.elect_eviction` — a stale link blames the first
        hop on its route that nobody fresh resolves to, so a route
        blocked by a dead transit hop convicts the dead hop, never the
        live endpoint behind it), and locally synthesize the Alg. 2
        leave — lowest address first, one per iteration, re-reading the
        shifted stamps until quiescent (a contiguous range failure
        cascades: each eviction contracts the ring and re-resolves the
        next dead neighbor)."""
        f = self._faults
        if f is None or not f.evict_after:
            return
        from repro.core.majority import (elect_eviction, eviction_grace,
                                         monitored_links)
        t = int(self._st.t)
        while self.n > 1:
            heard = np.asarray(self._st.heard).reshape(-1, NDIR)[: self.n]
            heard = np.maximum(heard, self._evict_floor)
            if self._heard_floor:
                row_of = {int(a): i for i, a in enumerate(self.ring.addrs)}
                for (a, dch), ts in self._heard_floor.items():
                    r = row_of.get(a)
                    if r is not None and heard[r, dch] < ts:
                        heard[r, dch] = ts
            probed = np.asarray(self._st.probed).reshape(-1, NDIR)[: self.n]
            dead = np.asarray(self._st.dead)[: self.n]
            _, evict = P.suspicion_rules(np, heard.ravel(), probed.ravel(),
                                         t, f.suspect_after, f.evict_after)
            pos = np.asarray(self.ring.positions())
            peers, dirs, mon = monitored_links(self.ring, pos, dead)
            if not (evict & mon).any():
                return
            target = elect_eviction(self.ring, pos, peers, dirs, mon, evict,
                                    heard.ravel(),
                                    eviction_grace(self.n, f.suspect_after))
            if target < 0:
                return
            self._evictions.append((t, int(self.ring.addrs[target])))
            self.leave(target)  # Alg. 2 verbatim: eviction IS a leave
            self._evict_floor = t - f.evict_after + eviction_grace(
                self.n, f.suspect_after)

    def _grow(self, need_n: int) -> None:
        """Re-pad every device table one size up. The jitted programs
        are NOT rebuilt — `jax.jit` retraces per shape on next use, so a
        grow costs one retrace per program instead of discarding every
        compiled entry (the historical rebuild caused a re-jit storm
        under churn). Wheel rows are re-laned host-side: the lane count/
        boundaries move with the pad, so every live row is re-placed in
        the lane owning its DEST under the new tables (stable
        (lane, slot, position) order, rank-capped like a device append).
        """
        host = jax.device_get(self._st)
        old_pad = self.pad
        self.pad = _next_pow2(need_n + max(8, need_n // 8))
        self._size_tables()
        pr = self.pad - old_pad

        def pad_rows(a, fill=0):
            extra = np.full((pr,) + a.shape[1:], fill, a.dtype)
            return np.concatenate([a, extra])

        addrs = pad_rows(np.asarray(host.addrs), NO_ADDR)
        n_live = int(host.n_live)

        def collect(buf, cnt):
            b, c = np.asarray(buf), np.asarray(cnt)
            out = [b[l, s, : c[l, s]]
                   for l in range(b.shape[0]) for s in range(SLOTS)]
            return (np.concatenate(out) if out
                    else np.zeros((0, self.roww), np.uint32))

        def place(rows, cap, width):
            L = self.lanes
            buf = np.zeros((L, SLOTS, width, self.roww), np.uint32)
            cnt = np.zeros((L, SLOTS), np.int32)
            lost = 0
            if rows.shape[0]:
                own = (np.searchsorted(addrs, rows[:, DEST], side="left")
                       % n_live)
                g = ((own // self.lane_rows) * SLOTS
                     + rows[:, self._DT].astype(np.int64) % SLOTS)
                order = np.argsort(g, kind="stable")
                gs = g[order]
                rank = np.arange(len(gs)) - np.searchsorted(gs, gs, "left")
                ok = rank < cap
                li, si = gs[ok] // SLOTS, gs[ok] % SLOTS
                buf[li, si, rank[ok]] = rows[order][ok]
                np.add.at(cnt, (li, si), 1)
                lost = int((~ok).sum())
            return buf, cnt, lost

        wheel, wcnt, lost_w = place(collect(host.wheel, host.wcnt),
                                    self.lane_cap, self.lane_width)
        awheel, acnt, lost_a = place(collect(host.awheel, host.acnt),
                                     self.lane_alert_w, self.lane_alert_w)

        def lane0(v, extra=0):
            # per-lane counters collapse into lane 0 (hosts read sums;
            # the old lane partition no longer exists)
            a = np.zeros(self.lanes, np.int32)
            a[0] = int(np.asarray(v).sum()) + extra
            return jnp.asarray(a)

        self._st = DeviceState(
            x=jnp.asarray(pad_rows(np.asarray(host.x))),
            inbox=jnp.asarray(np.concatenate([
                np.asarray(host.inbox),
                np.zeros((pr * NDIR, self.pw + 1), np.int32)])),
            out=jnp.asarray(pad_rows(np.asarray(host.out))),
            addrs=jnp.asarray(addrs),
            prev=jnp.asarray(pad_rows(np.asarray(host.prev))),
            pos=jnp.asarray(pad_rows(np.asarray(host.pos))),
            n_live=jnp.asarray(n_live, _I32),
            wheel=jnp.asarray(wheel), wcnt=jnp.asarray(wcnt),
            awheel=jnp.asarray(awheel), acnt=jnp.asarray(acnt),
            perms=jnp.asarray(np.asarray(host.perms)),
            salt_enq=jnp.asarray(np.uint32(host.salt_enq)),
            evt_ctr=jnp.asarray(int(host.evt_ctr), _I32),
            t=jnp.asarray(int(host.t), _I32),
            messages_sent=lane0(host.messages_sent),
            # re-laning truncation: the rows leave `live`, so they land
            # in `dropped` to keep enq == ret + live + dropped exact
            dropped=lane0(host.dropped, lost_w + lost_a),
            deferred=lane0(host.deferred),
            enq=lane0(host.enq), ret=lane0(host.ret),
            dead=jnp.asarray(pad_rows(np.asarray(host.dead))),
            heard=jnp.asarray(np.concatenate([
                np.asarray(host.heard),
                np.zeros(pr * NDIR, np.int32)])),
            probed=jnp.asarray(np.concatenate([
                np.asarray(host.probed),
                np.zeros(pr * NDIR, np.int32)])),
            lost=lane0(host.lost),
        )

    def step(self, cycles: int = 1) -> None:
        """Advance `cycles` cycles as ONE device dispatch (the superstep;
        bit-identical to `cycles` single-cycle dispatches — tested). With
        an armed fault plane the failure-detector eviction pass runs at
        the dispatch boundary (eviction granularity = step granularity;
        the reference evicts per cycle — drive `step(1)` for exact
        timing)."""
        self._st = self._steps(self._st, jnp.asarray(cycles, _I32))
        self._fault_sweep()

    def block_until_ready(self) -> None:
        jax.block_until_ready(self._st)

    def run_until_converged(self, truth: int, max_cycles: int = 200_000,
                            stable_for: int = 1) -> EngineResult:
        start_msgs = self.messages_sent
        truth_dev = jnp.asarray(truth, _I32)
        sf = jnp.asarray(stable_for, _I32)
        state = {"stable": jnp.zeros((), _I32)}

        def probe(budget: int) -> Tuple[bool, int]:
            st, stable, done, used = self._chunk_run(
                self._st, truth_dev, jnp.asarray(min(budget, self.chunk), _I32),
                state["stable"], sf,
            )
            self._st = st
            state["stable"] = stable
            self._fault_sweep()
            return bool(done), int(used)

        return run_convergence_loop(
            probe, max_cycles,
            cycles=lambda: self.t,
            messages=lambda: self.messages_sent - start_msgs,
            invalid=lambda: float(self.dropped > 0),
        )
