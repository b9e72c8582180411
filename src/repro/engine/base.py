"""Engine API: what a threshold-monitoring cycle engine must provide.

The contract is deliberately small — everything the benchmarks, the
examples and the elastic runtime need, and nothing tied to where the
state lives (host numpy vs device arrays). Methods take and return host
numpy values; backends move data as required.

Since the problem layer (PR 4, `engine.problems`) the decision rule is
pluggable: engines take a ``problem`` (a `ThresholdProblem` or a name),
per-peer state is a (D,)-vector, and `votes()` / `set_votes` remain the
scalar-data views (D = 1: majority votes, mean samples) while `data()`
exposes the full (n, D) quantized plane. `join` accepts scalar data or
a (D,) vector. `run_until_converged(truth)` checks the problem's
`converged` predicate (default: every peer outputs `truth`).

Since PR 2 the contract includes *dynamic membership* (Alg. 2): `join`
and `leave` change the ring mid-run. Both backends implement the same
upcall semantics (shared rules in `engine.protocol`, mechanics in
DESIGN.md §Churn):

  * the <= 6 tree-routed ALERTs of one change event are constructed
    from `protocol.change_positions` / `protocol.alert_plan` and
    delivered through the ordinary Alg. 1 router; an accepted ALERT
    zeroes X_in[v], sends unconditionally and re-runs test();
  * peers whose own tree position changed reset all their links the
    same way (bilateral reset — see DESIGN.md §Churn);
  * in-flight messages re-route against the changed ring; traffic
    originating from the two change positions is fenced (repair R3).
"""
from __future__ import annotations

from dataclasses import dataclass
from typing import Callable, Dict, Protocol, Tuple, runtime_checkable

import numpy as np

EngineResult = Dict[str, float]
# {"cycles", "messages", "converged", "invalid"} — `invalid` is 1.0 when
# the run lost messages to table overflow (device backend only; the host
# table grows instead). An invalid run's other numbers are meaningless:
# rerun with a larger capacity_per_peer.

# Compiled-program schema version. Bump whenever the DeviceState layout,
# the wheel row format, or the semantics of any jitted engine program
# change: the repo's own persistent XLA compilation cache dir is keyed
# on (jaxlib version, ENGINE_SCHEMA) by `benchmarks.run.validate_cache_dir`,
# so a cache dir serialized against an older engine is detected and
# cleared instead of deserializing into poisoned executables (the PR 8
# "stale .jax_cache hangs armed-engine runs" scar).
ENGINE_SCHEMA = 10


def coalesced_update(idx, new_data, n: int):
    """Validate one ingestion-ring flush batch (DESIGN.md §11).

    The serve layer coalesces client updates last-writer-wins per peer
    between supersteps, so a flush batch must carry AT MOST one row per
    peer: `idx` strictly ascending in [0, n), `new_data` one raw data
    row per index. Returns the arrays normalized to (int64 idx, data);
    raises on duplicate/unsorted indices or shape mismatch so a broken
    coalescer fails loudly instead of applying an ill-defined write
    order.
    """
    idx = np.asarray(idx, np.int64)
    vals = np.asarray(new_data)
    if idx.ndim != 1:
        raise ValueError(f"coalesced idx must be 1-D, got shape {idx.shape}")
    if vals.shape[:1] != idx.shape:
        raise ValueError(
            f"coalesced data rows {vals.shape} do not match idx {idx.shape}")
    if idx.size:
        if (np.diff(idx) <= 0).any():
            raise ValueError(
                "coalesced idx must be strictly ascending — last-writer-"
                "wins coalescing leaves exactly one value per peer")
        if idx[0] < 0 or idx[-1] >= n:
            raise IndexError(
                f"coalesced idx out of range [0, {n}): "
                f"[{idx[0]}, {idx[-1]}]")
    return idx, vals


@dataclass(frozen=True)
class FaultConfig:
    """Deterministic fault-plane configuration (DESIGN.md §10).

    Passing a `FaultConfig` to an engine (``faults=`` kwarg) arms three
    orthogonal fault mechanisms, all seeded and backend-reproducible:

    * ``crash(idx)`` becomes legal — the peer's rows zero silently, its
      lane-resident wheel messages are counted ``lost_to_fault``, and
      *no* Alg. 2 notification fires (abrupt failure, ROADMAP item 4);
    * per-delivery probabilistic faults at the due-scan: each due data
      message is independently dropped with ``p_drop`` or re-delayed
      with ``p_delay`` (drawn from `(seed, t, slot)` hashes so numpy /
      jax / sharded agree bit-for-bit). Alg. 2 ALERTs ride the reliable
      control plane and are exempt — membership truth never forks;
    * the timeout failure detector: per-direction `last_heard` stamps,
      probes after ``suspect_after`` silent cycles
      (`protocol.suspicion_rules`), and — when ``evict_after > 0`` — a
      locally synthesized Alg. 2 leave for the dead address once
      silence exceeds ``evict_after``.

    ``evict_after`` must stay 0 when only message faults are wanted:
    drops delay detection but must never change membership. Conversely
    crash tests keep ``p_drop = 0`` so eviction timing is exact.
    """

    p_drop: float = 0.0
    p_delay: float = 0.0
    suspect_after: int = 40
    evict_after: int = 0
    seed: int = 0

    def __post_init__(self):
        if not (0.0 <= self.p_drop <= 1.0 and 0.0 <= self.p_delay <= 1.0):
            raise ValueError("fault probabilities must lie in [0, 1]")
        if self.suspect_after < 1:
            raise ValueError("suspect_after must be >= 1")
        if self.evict_after < 0:
            raise ValueError("evict_after must be >= 0 (0 disables eviction)")
        if self.evict_after and self.evict_after <= self.suspect_after:
            raise ValueError("evict_after must exceed suspect_after")


def run_convergence_loop(
    probe: Callable[[int], Tuple[bool, int]],
    max_cycles: int,
    *,
    cycles: Callable[[], int],
    messages: Callable[[], int],
    invalid: Callable[[], float] = lambda: 0.0,
) -> EngineResult:
    """The one run-to-quiescence loop skeleton both backends share.

    The contract is the reference simulator's: up to `max_cycles`
    iterations of (convergence check; step), the check running *before*
    the step so the reported cycle is the paper's "first such cycle",
    with the `stable_for` bookkeeping hoisted behind `probe`.

    `probe(budget)` advances the engine by at most `budget` of those
    check+step iterations and returns `(done, used)`. The numpy backend
    probes one host cycle at a time (with a dirty-flag cache so the
    convergence check is only recomputed when an event could have moved
    an output); the jax backend probes a whole device chunk per call —
    the check runs on device every cycle and the host syncs once per
    chunk instead of twice per cycle. The mesh-sharded engine
    (`engine.sharded`) inherits the jax probe unchanged: the chunk is
    one shard_map program and the per-cycle check reduces across shards
    with a scalar psum, so this loop stays backend- and mesh-agnostic.
    """
    remaining = int(max_cycles)
    done = False
    while remaining > 0 and not done:
        done, used = probe(remaining)
        remaining -= max(int(used), 1)
    return {
        "cycles": cycles(),
        "messages": messages(),
        "converged": 1.0 if done else 0.0,
        "invalid": invalid(),
    }


@runtime_checkable
class MajorityEngine(Protocol):
    """Cycle-driven Alg. 1 + Alg. 2 + Alg. 3 co-simulation over a
    dynamic ring."""

    backend: str  # "numpy" | "jax"

    @property
    def t(self) -> int:
        """Current simulation cycle."""

    @property
    def messages_sent(self) -> int:
        """Network deliveries consumed so far (the paper's message unit),
        Alg. 2 ALERT routing included."""

    @property
    def dropped(self) -> int:
        """Messages lost to table overflow. Always 0 for the numpy
        backend (its table grows); a device run with dropped > 0 is
        invalid and `run_until_converged` flags it."""

    @property
    def lost_to_fault(self) -> int:
        """Messages destroyed by the *injected* fault plane (crashes,
        `FaultConfig.p_drop`). Itemized separately from `dropped` so
        engine bugs stay distinguishable from injected faults:
        `check_conservation` asserts
        enqueued == retired + in_flight + dropped + lost_to_fault."""

    def outputs(self) -> np.ndarray:
        """(n,) current 0/1 output of every peer (n tracks churn)."""

    def votes(self) -> np.ndarray:
        """(n,) current scalar data of every peer (majority: the vote);
        (n, D) for problems with data_width > 1."""

    def data(self) -> np.ndarray:
        """(n, D) quantized per-peer data plane (problem layer)."""

    def set_votes(self, idx: np.ndarray, new_votes: np.ndarray) -> None:
        """Data-change upcall: set X_self and re-run test() on `idx`;
        `new_votes` is (k,) scalar data or (k, D) vectors."""

    def apply_coalesced(self, idx: np.ndarray, new_data: np.ndarray) -> int:
        """Serve-layer flush upcall (DESIGN.md §11): apply one
        ingestion-ring batch — client updates coalesced last-writer-wins
        per peer since the previous superstep boundary — as a single
        batched `set_votes` riding the full-width event-react path.
        `idx` must be strictly ascending with one raw data row per
        index (`coalesced_update` validates); an empty batch is a no-op.
        Returns the number of peer rows applied. Uniform across the
        numpy / jax / mesh-sharded single-trial engines so the ingestion
        ring never needs backend branches."""

    def join(self, addr: int, vote: int = 0) -> int:
        """Membership upcall: a peer with `vote` joins at address `addr`
        (must be unoccupied). Emits the Alg. 2 ALERTs, re-routes
        in-flight traffic against the grown ring, and re-runs the
        Alg. 3 test on every affected peer. Returns the new peer's ring
        index (existing indices at or above it shift up by one)."""

    def leave(self, idx: int) -> None:
        """Membership upcall: peer `idx` departs. Emits the Alg. 2
        ALERTs on the shrunken ring; the departed peer's in-flight
        traffic is fenced. Indices above `idx` shift down by one.
        Raises ValueError on the last peer."""

    def step(self, cycles: int = 1) -> None:
        """Advance the simulation by `cycles` cycles."""

    def run_until_converged(self, truth: int, max_cycles: int = 200_000,
                            stable_for: int = 1) -> EngineResult:
        """Run until every peer outputs `truth` (checked each cycle,
        before stepping — the paper's 'first such cycle')."""
