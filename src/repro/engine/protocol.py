"""Alg. 1 / Alg. 2 / Alg. 3 protocol rules as backend-agnostic pure functions.

Every rule the paper states — the SEND construction, the DELIVER
classification (with the R1/R2 repairs, DESIGN.md §Faithfulness), the
Alg. 2 change-notification ALERT construction (`change_positions` /
`alert_plan`) and the threshold/violation algebra (`threshold_rules`,
generic over a `ThresholdProblem` — Alg. 3 majority is the default
instance, DESIGN.md §Problems) — lives here exactly once, written
against an explicit array namespace `xp` (``numpy`` or ``jax.numpy``).
The numpy reference simulator (`repro.core.routing` / `.majority`) and
the device engine (`repro.engine.jax_backend`) both consume these
functions, so the two backends cannot drift apart rule-by-rule; the
Pallas ``majority_step`` kernel implements `majority_rules` and is
checked against it in tests.

All functions are shape-polymorphic (scalars or batches), jit-safe on
the jnp path, and perform no data-dependent control flow. Ownership
lookups (who owns an address) are the DHT's job, not the protocol's —
callers pass the resolved `pos_i` / `a_prev` / `a_self` / `self_seg`
of the receiving peer.
"""
from __future__ import annotations

from typing import Any, NamedTuple, Tuple

from repro.core import addressing as A
from repro.core.addressing import CCW, CW, UP

Array = Any  # np.ndarray | jax.Array


def _zero_like(xp, a: Array) -> Array:
    return xp.zeros_like(a)


# ---------------------------------------------------------------------------
# Alg. 1 — SEND
# ---------------------------------------------------------------------------

def send_fields(xp, pos_p: Array, dirs: Array, a_self: Array, a_prev: Array,
                d: int) -> Tuple[Array, Array, Array, Array, Array]:
    """Downcall SEND for (position, direction) pairs, vectorized.

    `pos_p` is the sender's tree position, `a_self`/`a_prev` the segment
    edges of the peer performing the send (for ALERTs emulated from a
    foreign position these are still the *sender peer's* edges). Returns
    (valid, origin, dest, edge, has_edge); invalid sends are the
    structurally-missing directions (root UP/CCW, leaf CW/CCW) — the
    paper's "we prefer wasting those messages" stance.
    """
    leaf = A.is_leaf(pos_p)
    root = pos_p == 0
    dest = xp.where(
        dirs == UP, A.up(pos_p, d),
        xp.where(dirs == CW, A.cw(pos_p, d), A.ccw(pos_p, d)),
    ).astype(a_self.dtype)
    edge = xp.where(dirs == CW, a_self, a_prev).astype(a_self.dtype)
    has_edge = dirs != UP
    valid = xp.where(
        dirs == UP, ~root, xp.where(dirs == CW, ~leaf, ~leaf & ~root)
    )
    return valid, pos_p.astype(a_self.dtype), dest, edge, has_edge


# ---------------------------------------------------------------------------
# Alg. 1 — DELIVER (one local step at the owner peer)
# ---------------------------------------------------------------------------

class Delivery(NamedTuple):
    """Classification of one local Alg. 1 step (all arrays, same batch)."""

    accept: Array    # bool — dest == pos_i, foreign origin
    drop: Array      # bool — self-send / edge kill / address space exhausted
    new_dest: Array  # recalculated destination (meaningful where ~accept&~drop)
    new_edge: Array  # segment edge attached to the forward
    new_has_edge: Array  # bool — UP forwards carry no edge


def deliver_rules(xp, *, origin: Array, dest: Array, edge: Array,
                  has_edge: Array, network_entry: Array, pos_i: Array,
                  a_prev: Array, a_self: Array, self_seg: Array,
                  max_addr: Array, d: int, repair: bool = True) -> Delivery:
    """Alg. 1 upcall DELIVER at the peer owning `dest` — one step.

    `network_entry` is False while a peer keeps descending through its
    own segment (R1): the edge-based kill applies only to messages
    actually received from the network. `self_seg` marks messages whose
    origin position falls in the receiving peer's own segment (the
    paper's bounce-off-self rule; segment test so that emulated Alg. 2
    ALERTs behave, see core.notify). `max_addr` is the maximum occupied
    peer address — R2 root wrap (repair) descends CCW above it.

    The caller decides what to do with the result: forward through the
    DHT, keep descending locally (R1, when it still owns `new_dest`),
    or finalize accept/drop.
    """
    at_pos = dest == pos_i
    self_send = origin == pos_i
    accept = at_pos & ~self_send

    going_up = A.is_foreparent(dest, origin, d)
    in_cw = A.in_cw_subtree(origin, dest, d)
    kill_edge = xp.where(in_cw, a_prev, a_self)
    edge_kill = (
        network_entry & has_edge & (edge == kill_edge) & ~going_up & ~at_pos
    )
    leaf = A.is_leaf(dest) & ~going_up & ~at_pos
    drop = (at_pos & self_send) | edge_kill | leaf

    root_wrap = (
        (pos_i == 0) & (dest > max_addr) if repair else xp.zeros_like(at_pos)
    )
    # bool mask algebra, not selects (Pallas TPU kernels trace this
    # body and cannot select bools): ~root_wrap & (self_seg == in_cw)
    step_cw = ~root_wrap & ~(self_seg ^ in_cw)
    new_dest = xp.where(
        going_up, A.up(dest, d),
        xp.where(step_cw, A.cw(dest, d), A.ccw(dest, d)),
    ).astype(dest.dtype)
    new_edge = xp.where(
        going_up, _zero_like(xp, a_self), xp.where(step_cw, a_self, a_prev)
    ).astype(dest.dtype)
    new_has_edge = ~going_up
    return Delivery(accept, drop, new_dest, new_edge, new_has_edge)


def accept_direction(origin: Array, self_pos: Array, d: int) -> Array:
    """ACCEPT upcall: direction (UP/CW/CCW) the message arrived from."""
    return A.direction_of(origin, self_pos, d)


# ---------------------------------------------------------------------------
# Alg. 2 — tree change notification (ALERT construction)
# ---------------------------------------------------------------------------

def change_positions(xp, a_im2: Array, a_im1: Array, a_i: Array,
                     d: int) -> Tuple[Array, Array]:
    """(pos_fix, pos_var) of one predecessor change, Alg. 2 verbatim.

    The successor p_i observes its predecessor edge change between
    `a_im2` and `a_im1` (join: a_im1 appeared; leave: a_im1 departed).
    The two tree positions whose occupancy may have changed are

        pos_fix = Pos(a_im2, a_i)                   (the merged segment)
        pos_var = Pos(a_im1, a_i)   if Pos(a_im2, a_im1) == pos_fix
                  Pos(a_im2, a_im1) otherwise

    Vectorizes over events; shared by `core.notify` (numpy) and the
    device engine's jitted churn path (jnp).
    """
    pos_fix = A.position_from_segment(a_im2, a_i, d)
    pos_mid = A.position_from_segment(a_im2, a_im1, d)
    pos_new = A.position_from_segment(a_im1, a_i, d)
    pos_var = xp.where(pos_mid == pos_fix, pos_new, pos_mid)
    return pos_fix, pos_var


def alert_plan(xp, pos_fix: Array, pos_var: Array) -> Tuple[Array, Array]:
    """The <= 6 ALERT (position, direction) sends for one change event.

    Each of the two change positions is alerted in all three directions;
    structurally-missing directions (root UP/CCW, leaf CW/CCW) are culled
    later by `send_fields`' valid mask — the same wasting stance ordinary
    sends take. Returns (pos (6,), dirs (6,)).
    """
    pos = xp.stack([pos_fix, pos_fix, pos_fix, pos_var, pos_var, pos_var])
    dirs = xp.asarray([UP, CW, CCW, UP, CW, CCW])
    return pos, dirs


# ---------------------------------------------------------------------------
# Fault plane — timeout-based suspicion / eviction (DESIGN.md §10)
# ---------------------------------------------------------------------------

def suspicion_rules(xp, heard: Array, probed: Array, t: Array,
                    suspect_after: int, evict_after: int) -> Tuple[Array, Array]:
    """Per-link failure-detector masks from `last_heard` cycle stamps.

    `heard[l]` is the cycle the peer last accepted any traffic from
    tree-link `l`; `probed[l]` the cycle it last emitted a liveness
    probe on `l`. A link is *suspected* once silent for `suspect_after`
    cycles — the peer retries with an R3-fenced probe, rate-limited so
    one probe per `suspect_after` window is in flight — and the far
    peer is *evictable* once silent for `evict_after` cycles (the local
    Alg. 2 leave synthesis; `evict_after = 0` disables eviction so a
    lossy-but-alive network is never mistaken for membership change).

    Pure mask arithmetic over any number of links; callers AND the
    result with structural validity (`send_fields`' valid), occupancy
    and liveness of the suspecting peer itself.
    """
    silent = (t - heard).astype(heard.dtype)
    probe = (silent >= suspect_after) & ((t - probed) >= suspect_after)
    if evict_after > 0:
        evict = silent >= evict_after
    else:
        evict = xp.zeros(heard.shape, bool)
    return probe, evict


# ---------------------------------------------------------------------------
# Alg. 3 — threshold algebra (knowledge / agreement / violation / Send)
# ---------------------------------------------------------------------------

def comp(c: int) -> slice:
    """The columns of payload component c in the component-major
    (..., 3P) payload layout: column c*3 + v holds component c of
    direction v. The one statement of that layout — the device engine's
    payload columns, these rules and the `threshold_step` kernel's
    planes (rows there) all index through it."""
    return slice(c * 3, (c + 1) * 3)


def comps(cols: Array, pw: int) -> list:
    """(..., 3P) payload columns -> P per-component (..., 3) blocks."""
    return [cols[..., comp(c)] for c in range(pw)]


def thr2(ones: Array, total: Array) -> Array:
    """2 * thr(X): integer-exact sign of ones - total/2 (the paper's
    (1,-1/2)^t X functional, kept in integers)."""
    return 2 * ones - total


def threshold_rules_cols(problem, xp, in_cols: Array, out_cols: Array,
                         x: Array) -> Tuple[Array, Array, Array]:
    """The complete per-peer safe-zone test for ANY `ThresholdProblem`
    (`repro.engine.problems`), vectorized over peers, on payload
    columns.

    ``in_cols`` / ``out_cols`` are the (..., 3P) received/sent payloads,
    component-major: column c*3 + v holds component c (P = D + 1:
    vector-sum components then the count) of direction v. ``x`` is the
    (..., D) own data. Returns (viol (..., 3) bool, output (...,) int,
    pay (..., 3P)) where pay = K - X_in is the Send(v) payload restoring
    agreement A_{i,v} = K_i, in the same column layout.

    The Alg. 3 majority algebra is `problem=Majority()`; every step of
    this function then reduces to `majority_rules` bit for bit (pinned
    by tests). Pure arithmetic + the problem's margin — jit-safe, no
    data-dependent control flow.
    """
    pw = problem.payload_width
    ic = comps(in_cols, pw)                                      # (..., 3)
    own = [x[..., c:c + 1] for c in range(pw - 1)]
    own.append(xp.ones_like(x[..., :1]))
    k = [a[..., 0:1] + a[..., 1:2] + a[..., 2:3] + o
         for a, o in zip(ic, own)]                               # (..., 1)
    agg = [a + o for a, o in zip(ic, comps(out_cols, pw))]
    viol, output = problem.test_cols(xp, agg, k)
    pay = xp.concatenate([kc - a for kc, a in zip(k, ic)], axis=-1)
    return viol, output[..., 0].astype(in_cols.dtype), pay


def threshold_rules(problem, xp, in_pay: Array, out_pay: Array,
                    x: Array) -> Tuple[Array, Array, Array]:
    """`threshold_rules_cols` over (..., 3, P) received/sent payload
    arrays (the numpy engine's layout); pay comes back as (..., 3, P)."""
    pw = problem.payload_width
    cols = lambda a: xp.concatenate([a[..., c] for c in range(pw)], axis=-1)
    viol, output, pay = threshold_rules_cols(
        problem, xp, cols(in_pay), cols(out_pay), x)
    return viol, output, xp.stack(comps(pay, pw), axis=-1)


def majority_rules(in_ones: Array, in_tot: Array, out_ones: Array,
                   out_tot: Array, x: Array) -> Tuple[Array, Array, Array, Array]:
    """The per-peer Alg. 3 majority test, vectorized over peers — the
    `threshold_rules` payload algebra unpacked into the (ones, total)
    counter planes the Pallas ``majority_step`` kernel fuses.

    Inputs are the (N, 3) received/sent counter planes and the (N,) own
    votes. Returns (viol (N,3) bool, output (N,), pay_ones (N,3),
    pay_tot (N,3)) where pay = K - X_in is the Send(v) payload that
    restores agreement A_{i,v} = K_i. Pure arithmetic — works unchanged
    on numpy and jnp arrays.
    """
    k_ones = in_ones.sum(-1) + x  # (N,)
    k_tot = in_tot.sum(-1) + 1
    a_ones = in_ones + out_ones  # (N, 3)
    a_tot = in_tot + out_tot
    ta = thr2(a_ones, a_tot)
    tka = thr2(k_ones[..., None] - a_ones, k_tot[..., None] - a_tot)
    viol = ((ta >= 0) & (tka < 0)) | ((ta < 0) & (tka > 0))
    output = (thr2(k_ones, k_tot) >= 0).astype(in_ones.dtype)
    pay_ones = k_ones[..., None] - in_ones
    pay_tot = k_tot[..., None] - in_tot
    return viol, output, pay_ones, pay_tot
