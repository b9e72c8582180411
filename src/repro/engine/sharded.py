"""Mesh-sharded superstep engine: owner-partitioned peer plane AND
delivery wheel over a JAX device mesh with `shard_map`.

The paper's protocol needs no global context — every peer talks only to
its parent and two descendants — which is exactly what makes the
simulation shardable. `ShardedJaxEngine` partitions BOTH planes by the
same ownership rule:

  * the **peer plane** (the O(n) per-peer state: own data `x`, the
    per-link `inbox`, the `out` rows) by contiguous address-space row
    blocks over a one-axis device mesh;
  * the **delivery wheel** by owner LANE: the engine splits the padded
    row space into `lanes` equal blocks, every wheel arena / count /
    per-lane counter carries a leading lane axis, and each shard holds
    exactly the lanes covering its peer-row block (`lanes % n_shards ==
    0` — both are powers of two). A message row lives in the lane of
    its DEST owner, so per-device wheel memory is O(n/devices) and the
    whole drain path — due-scan, routing, accept-dedup, ALERT
    side-wheel, budget/slip bookkeeping, deferral accounting — runs on
    rows this shard owns, with NO collective: every peer/link index a
    lane touches falls in the local peer block by the ownership
    invariant, so `ShardedPlane`'s gathers and scatters are pure local
    index translation.

What crosses shards each cycle is ONE boundary exchange: every lane
stages a rigid block of the rows that (re-)enter a wheel (re-entries +
send candidates, delay ordinals ranked lane-locally), and a single
tiled `all_gather` over the mesh axis hands every shard the global
lane-major staging order — from which each shard appends just the rows
its lanes own, at ranks computed from the SAME replicated block on
every mesh size. That, plus a scalar psum in the convergence predicate,
is the entire per-cycle collective footprint.

Because every exchanged value is an exact integer, the sharded
trajectory is **bit-identical** to the single-device jax engine — same
cycles, same message counts, same outputs, for every problem and
through churn — and therefore invariant in the mesh size
(tests/test_sharded.py pins 1/2/4/8 devices against each other and
against the unsharded engine; tests/_diff_harness.py replays fuzzed
event schedules across numpy/jax/sharded, wheel occupancy included).

Event paths also run under shard_map, collectives explicit:

  * full-width reacts (init storm, `set_votes`): per-shard elementwise
    test + `gather_events` into the replicated global event block each
    shard appends its lanes from;
  * Alg. 2 join/leave: row recompaction flows through
    `ShardedPlane.shift_rows` — one all_gather + local re-slice — and
    the post-churn fence/re-lane sweep reuses the SAME staged boundary
    exchange to migrate rows whose owner lane moved. No inherited
    global GSPMD program is left on the churn path (the historical
    GSPMD partitioning of the O(n) event scatter compiled
    pathologically at pad=2^20).

    from repro.engine import make_engine
    eng = make_engine("jax", ring, votes, mesh=8)   # 8-way sharded
    res = eng.run_until_converged(truth=1)

`mesh=` accepts a one-axis `jax.sharding.Mesh`, a device count, or
``True`` (all local devices); `launch.mesh.make_engine_mesh` builds the
canonical ("shard",) mesh. Constraints: `lanes % n_devices == 0` (the
engine carves 8 lanes out of any pad >= 8, so meshes of 1/2/4/8 always
fit) and no `batch=` (vmapped trials and mesh sharding compose in a
later PR). `resize_mesh()` re-partitions a LIVE engine onto a different
mesh — state is re-laid out, the trajectory continues bit-identically.
See DESIGN.md §Sharding for the partition layout and the
boundary-exchange invariants.
"""
from __future__ import annotations

from typing import Union

import numpy as np

import jax
import jax.numpy as jnp
from jax import shard_map
from jax.sharding import Mesh, NamedSharding, PartitionSpec as PS

from repro.core.dht import Ring
from repro.engine.jax_backend import (DeviceState, JaxEngine, NDIR, PeerPlane,
                                      _I32)

AXIS = "shard"  # the canonical engine mesh axis name


def as_engine_mesh(mesh: Union[Mesh, int, bool, None]) -> Mesh:
    """Resolve the `mesh=` engine kwarg to a one-axis Mesh: an existing
    one-axis Mesh passes through; an int takes the first that many local
    devices (`launch.mesh.make_engine_mesh`); True/None take all of
    them."""
    if isinstance(mesh, Mesh):
        if len(mesh.axis_names) != 1:
            raise ValueError(
                f"engine mesh must have ONE axis, got {mesh.axis_names}")
        return mesh
    from repro.launch.mesh import make_engine_mesh

    return make_engine_mesh(0 if (mesh is None or mesh is True) else int(mesh))


class ShardedPlane(PeerPlane):
    """Owner-partitioned `PeerPlane`: block-sharded peer rows + local
    owner lanes (module docstring). The drain path is pure local index
    translation — the ownership invariant (wheel rows live with their
    DEST owner's lane, lanes live with their peer block) guarantees
    every per-cycle peer/link access lands in the local block, so no
    psum/pmax rides the hot loop. Collectives appear only where the
    contract is explicitly global: the staged lane `exchange`, event
    `gather_events`, churn `shift_rows`/`take_peer_rep`, and the scalar
    convergence reduction. Instantiated inside the shard_map trace —
    `axis_index` is only meaningful there."""

    def __init__(self, eng: "ShardedJaxEngine", axis: str):
        super().__init__(eng)
        self.axis = axis

    def _loc(self, nloc: int, idx: jnp.ndarray):
        """Global row index -> (clamped local index, ownership mask)."""
        lo = jax.lax.axis_index(self.axis) * nloc
        loc = idx.astype(_I32) - lo
        ok = (loc >= 0) & (loc < nloc)
        return jnp.where(ok, loc, 0), ok

    def _take(self, arr, idx):
        # lane-local by invariant: mask only hygiene for dead-row
        # sentinels (their values never reach live state)
        loc, ok = self._loc(arr.shape[0], idx)
        v = arr[loc]
        okb = ok.reshape(ok.shape + (1,) * (v.ndim - ok.ndim))
        return jnp.where(okb, v, 0)

    take_peer = _take
    take_link = _take

    def take_peer_rep(self, arr, idx):
        v = self._take(arr, idx)
        return jax.lax.psum(v, self.axis)

    def _put(self, arr, idx, val):
        nloc = arr.shape[0]
        lo = jax.lax.axis_index(self.axis) * nloc
        loc = idx.astype(_I32) - lo
        ok = (loc >= 0) & (loc < nloc)
        return arr.at[jnp.where(ok, loc, nloc)].set(val, mode="drop")

    put_peer = _put
    put_link = _put

    @property
    def _nlinks_local(self) -> int:
        return self.eng.pad * NDIR // self.eng.n_shards

    def link_max(self, idx, val, mask):
        nloc = self._nlinks_local
        loc, owned = self._loc(nloc, idx)
        ok = mask & owned
        return jnp.full(nloc, -1, _I32).at[jnp.where(ok, loc, nloc)].max(
            jnp.where(ok, val, -1), mode="drop")

    def link_floor(self):
        return jnp.full(self._nlinks_local, -1, _I32)

    def link_read(self, dense, idx):
        loc, ok = self._loc(dense.shape[0], idx)
        return jnp.where(ok, dense[loc], -1)

    def link_read3(self, dense, rows):
        per = dense.reshape(-1, NDIR)
        loc, ok = self._loc(per.shape[0], rows)
        return jnp.where(ok[:, None], per[loc], -1)

    def peer_dirmax(self, dense, rows):
        per = dense.reshape(-1, NDIR).max(1)
        loc, ok = self._loc(per.shape[0], rows)
        return jnp.where(ok, per[loc], -1)

    def occ(self, st):
        pd_l = st.x.shape[0]
        lo = jax.lax.axis_index(self.axis) * pd_l
        return (lo + jnp.arange(pd_l)) < st.n_live

    def all_true(self, v):
        miss = (~v).any().astype(_I32)
        return jax.lax.psum(miss, self.axis) == 0

    # -- owner-lane boundary --------------------------------------------------

    def lane_base(self, n_loc: int) -> jnp.ndarray:
        return (jax.lax.axis_index(self.axis) * n_loc).astype(_I32)

    def exchange(self, arr):
        """THE per-cycle collective: local lanes' staged blocks ->
        the global lane-major staging order, replicated (tiled
        all_gather along the lane axis)."""
        return jax.lax.all_gather(arr, self.axis, axis=0, tiled=True)

    def shift_rows(self, arr, src):
        """Join/leave row recompaction as an explicit owner exchange:
        all_gather the blocks to the full table, apply this block's
        slice of the global source map, keep the local rows. Replaces
        the inherited global GSPMD gather of the pre-partition engine
        (which compiled pathologically at pad=2^20)."""
        nloc = arr.shape[0]
        lo = jax.lax.axis_index(self.axis) * nloc
        full = jax.lax.all_gather(arr, self.axis, axis=0, tiled=True)
        src_loc = jax.lax.dynamic_slice_in_dim(src.astype(_I32), lo, nloc)
        return full[src_loc]

    def local_tables(self, st):
        """This shard's block of the replicated ring tables — the rows
        matching its local x/out/inbox blocks."""
        pd_l = st.x.shape[0]
        lo = jax.lax.axis_index(self.axis) * pd_l
        sl = lambda a: jax.lax.dynamic_slice_in_dim(a, lo, pd_l)
        return sl(st.pos), sl(st.addrs), sl(st.prev)

    def gather_events(self, *arrs):
        """All_gather the shard blocks of an event (tiled): contiguous
        block sharding makes the concatenation exactly the global row
        order, so the wheel append ranks — and therefore the delay
        ordinals and slot offsets — are bit-identical to the
        single-device enqueue."""
        return tuple(
            jax.lax.all_gather(a, self.axis, axis=0, tiled=True)
            for a in arrs)


class ShardedJaxEngine(JaxEngine):
    """`JaxEngine` over a device mesh (module docstring). Same
    `MajorityEngine` contract, same trajectories, bit for bit."""

    backend = "jax"
    sharded = True

    def __init__(self, ring: Ring, votes: np.ndarray, seed: int = 0,
                 mesh: Union[Mesh, int, bool, None] = None, **kwargs):
        mesh = as_engine_mesh(mesh)
        self.mesh = mesh
        self.axis = mesh.axis_names[0]
        self.n_shards = int(mesh.devices.size)
        if self.n_shards & (self.n_shards - 1):
            raise ValueError(
                f"engine mesh size must be a power of two, got "
                f"{self.n_shards}")
        super().__init__(ring, votes, seed=seed, **kwargs)

    # -- program construction -----------------------------------------------

    def _state_specs(self) -> DeviceState:
        """PartitionSpec per DeviceState leaf: peer plane sharded by row
        blocks, wheel arenas + per-lane counters sharded by LANE blocks
        (the matching partition — lane l's rows are owned by the shard
        holding peer block l * lane_rows), ring tables and scalars
        replicated."""
        S, R = PS(self.axis), PS()
        return DeviceState(
            x=S, inbox=S, out=S,
            addrs=R, prev=R, pos=R, n_live=R,
            wheel=S, wcnt=S, awheel=S, acnt=S,
            perms=R, salt_enq=R, evt_ctr=R,
            t=R, messages_sent=S, dropped=S, deferred=S, enq=S, ret=S,
            # fault plane: detector stamps shard with their peer/link
            # blocks, the dead flags replicate with the ring tables
            dead=R, heard=S, probed=S, lost=S,
        )

    def _with_plane(self, fn):
        """Trace `fn` with the owner-partitioned plane installed
        (shard_map bodies trace inside jit, so the swap must wrap the
        traced call, not the program construction)."""
        def inner(st, *args):
            prev = self._plane
            self._plane = ShardedPlane(self, self.axis)
            try:
                return fn(st, *args)
            finally:
                self._plane = prev
        return inner

    def _make_programs(self):
        if self.lanes % self.n_shards:
            raise ValueError(
                f"mesh size {self.n_shards} does not divide the "
                f"{self.lanes} wheel lanes (pad={self.pad})")
        assert self.pad % self.n_shards == 0, (self.pad, self.n_shards)
        specs = self._state_specs()
        self._shardings = jax.tree.map(
            lambda s: NamedSharding(self.mesh, s), specs,
            is_leaf=lambda x: isinstance(x, PS))
        R = PS()
        sm = lambda fn, in_extra, out: shard_map(
            self._with_plane(fn), mesh=self.mesh,
            in_specs=(specs, *in_extra), out_specs=out, check_vma=False)
        # the hot path: superstep + convergence chunk under shard_map
        self._steps = jax.jit(sm(self._steps_impl, (R,), specs),
                              donate_argnums=(0,))
        self._chunk_run = jax.jit(
            sm(self._chunk_impl, (R, R, R, R), (specs, R, R, R)),
            donate_argnums=(0,))
        self._conv = jax.jit(sm(self._outputs_match, (R,), R))
        # full-width event reacts (init storm, set_votes): per-shard
        # elementwise test + the gather_events boundary into each
        # shard's lane appends
        self._react = jax.jit(sm(self._react_impl, (PS(self.axis),), specs),
                              donate_argnums=(0,))
        # churn: shard_map too — recompaction through shift_rows, the
        # fence/re-lane sweep through the staged lane exchange; no
        # global GSPMD program remains on this path
        self._join = jax.jit(sm(self._join_impl, (R, R, R), specs),
                             donate_argnums=(0,))
        self._leave = jax.jit(sm(self._leave_impl, (R,), specs),
                              donate_argnums=(0,))
        self._crash = jax.jit(sm(self._crash_impl, (R,), specs),
                              donate_argnums=(0,))

    def _initial_state(self, ring: Ring, votes: np.ndarray,
                       seed: int) -> DeviceState:
        st = super()._initial_state(ring, votes, seed)
        return jax.device_put(st, self._shardings)

    def _grow(self, need_n: int) -> None:
        # host re-lane + re-pad; the NamedShardings are shape-agnostic,
        # so no program or sharding rebuild — jit retraces per shape
        super()._grow(need_n)
        self._st = jax.device_put(self._st, self._shardings)

    def resize_mesh(self, mesh: Union[Mesh, int, bool, None]) -> None:
        """Re-partition the LIVE engine onto a different mesh. The lane
        layout is mesh-independent, so this is pure data movement: pull
        the state to host, swap the mesh, rebuild the shard_map programs
        for the new axis size, push the state back. The trajectory
        continues bit-identically (diff-harness pinned)."""
        host = jax.device_get(self._st)
        mesh = as_engine_mesh(mesh)
        n = int(mesh.devices.size)
        if n & (n - 1):
            raise ValueError(
                f"engine mesh size must be a power of two, got {n}")
        self.mesh, self.axis = mesh, mesh.axis_names[0]
        self.n_shards = n
        self._make_programs()
        self._st = jax.device_put(host, self._shardings)
