"""d-bit address algebra for the binary tree routing protocol (paper §2).

The DHT address space is the set of d-bit strings. A tree *position* is an
address of the form ``p 1 0^k`` (prefix ``p``, a set bit, ``k`` trailing
zeros); the root is the all-zero address. The protocol's locality comes from
the fact that parent/descendant addresses are pure bit manipulations:

    CW [p 1 0^k] = p 1 1 0^(k-1)        (clockwise descendant)
    CCW[p 1 0^k] = p 0 1 0^(k-1)        (counterclockwise descendant)
    UP [p 1 1 0^j] = p 1 0^(j+1)        (it is a CW child)
    UP [p 0 1 0^j] = p 1 0^(j+1)        (it is a CCW child)
    CW [0^d]      = 1 0^(d-1)           (root's single descendant)

Every function in this module is dtype-generic: it accepts (arrays of)
``numpy`` unsigned integers (uint64 recommended, supports d <= 64) or JAX
unsigned arrays (uint32, d <= 32 — JAX default config has no uint64). All
functions are vectorized and jit-safe on the JAX path.

Conventions:
  * ``d`` is the address-space width in bits; ``mask = 2^d - 1``.
  * The root position is 0. ``UP(0) = 0`` by convention (the root has no
    parent); callers must check ``pos != 0`` where it matters.
  * "subtree of x" spans the address range ``(x - 2^k, x + 2^k - 1]`` where
    ``2^k = lowbit(x)`` (Appendix A, Lemma 1 proof); the root's subtree is
    the entire space.
"""
from __future__ import annotations

import functools
from typing import Any, Tuple

import numpy as np

Array = Any  # np.ndarray | jax.Array | scalar integer


def _wrapok(fn):
    """Run under np.errstate(over='ignore'): modular wrap is intentional."""

    @functools.wraps(fn)
    def inner(*args, **kwargs):
        with np.errstate(over="ignore"):
            return fn(*args, **kwargs)

    return inner


def _is_jax(a: Array) -> bool:
    try:
        import jax

        return isinstance(a, jax.Array)
    except ImportError:  # pragma: no cover
        return False


def _arr(a: Array) -> Array:
    """Coerce numpy scalars to 0-d arrays (modular wrap without warnings)."""
    return a if _is_jax(a) else np.asarray(a)


def _const(a: Array, v: int) -> Array:
    """A constant of the same dtype as `a` (works for np scalars/arrays & jax)."""
    if _is_jax(a):
        import jax.numpy as jnp

        return jnp.asarray(v, dtype=a.dtype)
    dt = np.asarray(a).dtype
    return dt.type(v)


def mask_of(d: int) -> int:
    return (1 << d) - 1


def _masked(a: Array, d: int) -> Array:
    return a & _const(a, mask_of(d))


@_wrapok
def lowbit(a: Array) -> Array:
    """Lowest set bit of each address; 0 for the root address 0."""
    a = _arr(a)
    one = _const(a, 1)
    return a & (~a + one)


def popcount(a: Array) -> Array:
    if _is_jax(a):
        import jax

        return jax.lax.population_count(a).astype(a.dtype)
    return np.bitwise_count(a).astype(np.asarray(a).dtype)


@_wrapok
def trailing_zeros(a: Array, d: int) -> Array:
    """Number of trailing zeros; returns d for the all-zero (root) address."""
    a = _arr(a)
    lb = lowbit(a)
    one = _const(a, 1)
    tz = popcount(lb - one)  # lowbit-1 has tz ones; for a==0 this is all-ones
    if _is_jax(a):
        import jax.numpy as jnp

        return jnp.where(a == 0, _const(a, d), tz)
    return np.where(np.asarray(a) == 0, _const(a, d), tz)


@_wrapok
def highbit(a: Array, d: int) -> Array:
    """Highest set bit of each address; 0 if the address is 0."""
    a = _arr(a)
    x = a
    shift = 1
    nbits = 64 if np.dtype(a.dtype).itemsize == 8 else 32
    while shift < nbits:
        x = x | (x >> _const(a, shift))
        shift <<= 1
    return _masked(x - (x >> _const(a, 1)), d)


def depth(pos: Array, d: int) -> Array:
    """Tree depth of a position: 0 for the root, else d - trailing_zeros."""
    return _const(pos, d) - trailing_zeros(pos, d)


@_wrapok
def up(pos: Array, d: int) -> Array:
    """Parent position. UP(root)=root. (paper §2: positions p110^j / p010^j)."""
    pos = _arr(pos)
    m = lowbit(pos)
    one = _const(pos, 1)
    m2 = _masked(m << one, d)  # bit above the lowbit (0 if lowbit is MSB)
    is_cw_child = (pos & m2) != 0
    up_cw = pos ^ m  # p110^j -> p10^(j+1)
    up_ccw = _masked((pos ^ m) | m2, d)  # p010^j -> p10^(j+1); MSB case -> 0 (root)
    if _is_jax(pos):
        import jax.numpy as jnp

        out = jnp.where(is_cw_child, up_cw, up_ccw)
        return jnp.where(pos == 0, pos, out)
    out = np.where(is_cw_child, up_cw, up_ccw)
    return np.where(np.asarray(pos) == 0, pos, out).astype(np.asarray(pos).dtype)


@_wrapok
def cw(pos: Array, d: int) -> Array:
    """Clockwise descendant address. CW(root) = 10^(d-1). Leaf -> returns pos
    unchanged (callers must test `has_descendants`)."""
    pos = _arr(pos)
    m = lowbit(pos)
    one = _const(pos, 1)
    child = pos | (m >> one)
    root_child = _const(pos, 1 << (d - 1))
    if _is_jax(pos):
        import jax.numpy as jnp

        return jnp.where(pos == 0, root_child, child)
    return np.where(np.asarray(pos) == 0, root_child, child).astype(
        np.asarray(pos).dtype
    )


@_wrapok
def ccw(pos: Array, d: int) -> Array:
    """Counterclockwise descendant address. Undefined for root (returns 0) and
    for leaves (returns pos ^ lowbit = the parent-side address; callers must
    test `has_descendants` / pos != 0)."""
    pos = _arr(pos)
    m = lowbit(pos)
    one = _const(pos, 1)
    child = (pos ^ m) | (m >> one)
    if _is_jax(pos):
        import jax.numpy as jnp

        return jnp.where(pos == 0, pos, child)
    return np.where(np.asarray(pos) == 0, pos, child).astype(np.asarray(pos).dtype)


def is_leaf(pos: Array) -> Array:
    """Addresses ending with a set bit (k = 0) have no descendants."""
    return (pos & _const(pos, 1)) != 0


def span(pos: Array) -> Array:
    """Half-width of the subtree address range: lowbit(pos); 0 for root."""
    return lowbit(pos)


@_wrapok
def in_subtree(x: Array, y: Array, d: int) -> Array:
    """Is address y inside the subtree rooted at position x (inclusive of x)?

    subtree(x) = (x - s, x + s - 1] with s = lowbit(x); root: everything.
    Modular arithmetic handles the MSB position whose range wraps nominally.
    """
    x, y = _arr(x), _arr(y)
    s = lowbit(x)
    one = _const(x, 1)
    lo = x - s  # exclusive lower bound
    size = _masked((s << one) - one, d)  # 2s - 1 addresses in the subtree
    rel = _masked(y - lo - one, d)
    # mask logic, not a select: Pallas TPU kernels cannot select bools
    return (x == 0) | (rel < size)


def is_foreparent(x: Array, y: Array, d: int) -> Array:
    """Is position x a strict ancestor of address y? (paper: 'fore-parent')."""
    return in_subtree(x, y, d) & (x != y)


@_wrapok
def in_cw_subtree(x: Array, y: Array, d: int) -> Array:
    """Is y inside the clockwise subtree of x?  range (x, x + s - 1]."""
    x, y = _arr(x), _arr(y)
    s = lowbit(x)
    one = _const(x, 1)
    rel = _masked(y - x - one, d)
    inside = rel < (s - one)
    root = x == 0  # CW subtree of the root is every non-zero address
    return (root & (y != 0)) | (~root & inside)


@_wrapok
def in_ccw_subtree(x: Array, y: Array, d: int) -> Array:
    """Is y inside the counterclockwise subtree of x?  range (x - s, x - 1]."""
    x, y = _arr(x), _arr(y)
    s = lowbit(x)
    one = _const(x, 1)
    rel = _masked(y - (x - s) - one, d)
    inside = rel < (s - one)
    if _is_jax(x):
        import jax.numpy as jnp

        return jnp.where(x == 0, jnp.zeros_like(inside), inside)
    return np.where(np.asarray(x) == 0, False, inside)


@_wrapok
def position_from_segment(prev: Array, self_addr: Array, d: int) -> Array:
    """Tree position of the peer owning segment (prev, self] (paper §2).

    Let p be the common prefix of prev and self with prev = p0X, self = p1Y;
    the position is p 1 0^k. The peer whose segment contains address 0 — the
    wrapped segment, i.e. prev >= self — takes the root position 0.
    """
    prev, self_addr = _arr(prev), _arr(self_addr)
    x = prev ^ self_addr
    h = highbit(x, d)  # the first differing bit
    one = _const(x, 1)
    low = h - one  # mask of bits strictly below the differing bit
    pos = self_addr & ~low
    is_root = prev >= self_addr  # wrapped segment contains 0 (addresses unique)
    if _is_jax(pos):
        import jax.numpy as jnp

        return jnp.where(is_root, jnp.zeros_like(pos), pos)
    return np.where(is_root, _const(pos, 0), pos).astype(np.asarray(pos).dtype)


def ring_positions(addrs_sorted: Array, d: int) -> Array:
    """Positions of all peers given the sorted ring of peer addresses.

    Peer i owns (addrs[i-1], addrs[i]] (cyclically); peer 0 (minimum address)
    owns the wrapped segment and is the root.
    """
    if _is_jax(addrs_sorted):
        import jax.numpy as jnp

        prev = jnp.roll(addrs_sorted, 1)
    else:
        prev = np.roll(addrs_sorted, 1)
    return position_from_segment(prev, addrs_sorted, d)


def direction_of(origin_pos: Array, self_pos: Array, d: int) -> Array:
    """Direction (0=UP, 1=CW, 2=CCW) of `origin_pos` as seen from `self_pos`.

    Used by ACCEPT upcalls (Alg. 2/3): a message from a fore-parent arrived
    from the UP neighbor; from the clockwise subtree — the CW neighbor; else
    the CCW neighbor.
    """
    from_up = is_foreparent(origin_pos, self_pos, d)
    from_cw = in_cw_subtree(self_pos, origin_pos, d)
    if _is_jax(self_pos):
        import jax.numpy as jnp

        return jnp.where(from_up, 0, jnp.where(from_cw, 1, 2))
    return np.where(from_up, 0, np.where(from_cw, 1, 2))


UP, CW, CCW = 0, 1, 2  # direction codes used across repro.core


def descendant(pos: Array, direction: int, d: int) -> Array:
    return cw(pos, d) if direction == CW else ccw(pos, d)


def random_ring(n: int, d: int, seed: int, dtype=np.uint64) -> np.ndarray:
    """n distinct random d-bit peer addresses, sorted ascending (numpy)."""
    if n > mask_of(d):
        raise ValueError(f"cannot place {n} peers in a {d}-bit space")
    rng = np.random.default_rng(seed)
    out = np.empty(0, dtype=dtype)
    need = n
    while need > 0:
        cand = rng.integers(0, mask_of(d), size=2 * need + 16, dtype=np.uint64)
        cand = (cand & np.uint64(mask_of(d))).astype(dtype)
        out = np.unique(np.concatenate([out, cand]))
        need = n - out.size
    if out.size > n:
        out = rng.choice(out, size=n, replace=False)
        out.sort()
    return out


def tree_neighbors_reference(addrs_sorted: np.ndarray, d: int) -> Tuple[np.ndarray, np.ndarray, np.ndarray]:
    """Ground-truth (UP, CW, CCW) peer indices for every peer, from Lemma 2.

    For peer i: the CW neighbor is the unique peer whose position is the
    fore-parent of all occupied positions in the subtree of CW[pos_i]
    (= minimum depth among them); symmetrically CCW. The UP neighbor is the
    owner-peer of the first ancestor address (walking UP from pos_i) that is
    some peer's position. Returns -1 where the neighbor does not exist.
    O(N log N); numpy only — used as the oracle in tests and by the
    change-notification checker.
    """
    n = addrs_sorted.size
    pos = ring_positions(addrs_sorted, d)
    pos_to_peer = {int(p): i for i, p in enumerate(pos)}
    dep = depth(pos, d).astype(np.int64)

    up_n = np.full(n, -1, dtype=np.int64)
    cw_n = np.full(n, -1, dtype=np.int64)
    ccw_n = np.full(n, -1, dtype=np.int64)

    # UP: walk ancestors until an occupied position.
    for i in range(n):
        p = int(pos[i])
        if p == 0:
            continue  # root
        cur = p
        while True:
            cur = int(up(np.asarray(cur, dtype=addrs_sorted.dtype), d))
            if cur in pos_to_peer:
                up_n[i] = pos_to_peer[cur]
                break
            if cur == 0:
                break  # 0 not occupied as a *position* only if no wrap peer; cannot happen
    # CW/CCW: the min-depth occupied position in each child subtree. Sort
    # peers by position; child subtrees are contiguous position ranges.
    order = np.argsort(pos, kind="stable")
    pos_sorted = pos[order]
    for i in range(n):
        p = pos[i]
        if int(p) == 0:
            # Root: CW subtree is every other peer.
            if n > 1:
                rest = np.arange(n) != i
                j = np.argmin(np.where(rest, dep, np.iinfo(np.int64).max))
                cw_n[i] = j
            continue
        s = int(lowbit(p))
        if s == 1:
            continue  # leaf address: no descendants
        # CW range (p, p + s - 1]; CCW range (p - s, p - 1] — contiguous, no wrap
        for (lo, hi, out) in (
            (int(p) + 1, int(p) + s - 1, cw_n),
            (int(p) - s + 1, int(p) - 1, ccw_n),
        ):
            a = np.searchsorted(pos_sorted, np.asarray(lo, dtype=pos.dtype), side="left")
            b = np.searchsorted(pos_sorted, np.asarray(hi, dtype=pos.dtype), side="right")
            if b > a:
                cand = order[a:b]
                out[i] = cand[np.argmin(dep[cand])]
    return up_n, cw_n, ccw_n
