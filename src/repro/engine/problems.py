"""Threshold problems — the pluggable decision rule behind Alg. 3.

The paper's majority vote is one instance of the *local thresholding*
family (Wolff, "Local Thresholding in General Network Graphs",
arXiv:1212.5880): peers hold small data vectors, messages carry additive
payloads ``(vector_sum, count)``, and each peer tests whether its
per-link agreement ``A`` and residual knowledge ``K - A`` fall on the
same side of a threshold surface. Everything else — the DHT tree, the
Alg. 1 router, the Alg. 2 churn notifications, the delivery wheel, the
superstep fusion and the vmapped trial batching — is problem-agnostic.

A `ThresholdProblem` supplies exactly what varies:

  * ``data_width``   — D, the per-peer data vector width (Majority: 1);
  * ``init_state``   — quantize raw per-peer data to the int64 (n, D)
    plane both backends consume (quantization happens ONCE on the host,
    so numpy and jax see bit-identical integers);
  * ``margin``       — the signed threshold functional over a payload
    ``(..., P)`` with ``P = D + 1`` (vector sum columns, count column).
    Must be side-effect-free, shape-polymorphic and dtype-stable across
    numpy and jnp (see DESIGN.md §Problems for the exactness contract);
  * ``test``         — the safe-zone violation test; the generic
    implementation (margins of ``A`` and ``K - A`` disagree in sign)
    matches Alg. 3 bit for bit and rarely needs overriding;
  * ``converged``    — per-peer convergence predicate against a target
    output (default: equality).

The protocol algebra consuming these lives in
`repro.engine.protocol.threshold_rules`; both cycle engines route every
test()/Send through it, so a new scenario is ONE small problem class —
not a backend fork.

Exactness contract (DESIGN.md §Problems): integer margins must fit the
device int32 range; float margins must be computed with an identical
float32 op sequence on both backends (see `L2Thresh.margin`'s unrolled
accumulation). `init_state` must return plain int64 numpy arrays.
"""
from __future__ import annotations

from typing import Any, Tuple

import numpy as np

Array = Any  # np.ndarray | jax.Array


class ThresholdProblem:
    """Base class: the generic safe-zone test over a problem `margin`."""

    name = "threshold"
    data_width = 1  # D — override (or set per instance)

    @property
    def payload_width(self) -> int:
        """P = D + 1: vector-sum columns plus the count column."""
        return self.data_width + 1

    # -- data ingestion (host side, once) -----------------------------------
    def init_state(self, data: np.ndarray) -> np.ndarray:
        """Quantize raw per-peer data to the (n, D) int64 plane. The
        default accepts integer data (n,) or (n, D) unchanged."""
        a = np.asarray(data)
        if not np.issubdtype(a.dtype, np.integer):
            raise TypeError(
                f"{self.name} expects integer data; override init_state "
                "to quantize floats")
        if a.ndim == 1:
            a = a[:, None]
        if a.ndim != 2 or a.shape[1] != self.data_width:
            raise ValueError(
                f"{self.name} data must be (n,) or (n, {self.data_width}), "
                f"got {a.shape}")
        return a.astype(np.int64)

    def peer_data(self, value) -> np.ndarray:
        """One joining peer's (D,) int64 data row (Alg. 2 `join`);
        scalars broadcast across the D components."""
        a = np.asarray(value)
        if a.ndim == 0:
            a = np.broadcast_to(a, (self.data_width,))
        return self.init_state(a[None, :])[0]

    # -- the decision rule ---------------------------------------------------
    # Each rule comes in column form: a payload is a sequence of its P
    # component arrays (sum columns, then the count), all of one shape
    # or broadcastable to one. The engines' row layout and the Pallas
    # kernels' lane-dense planes (peers on the minor axis) then share
    # one definition of the rule, and no rule needs an array constant
    # or a gather — neither of which a TPU kernel body may hold.

    def margin_cols(self, xp, cols) -> Array:
        """Signed distance of the payload with component arrays `cols`
        from the threshold surface; output 1 iff margin(K) >= 0. Must be
        exact (bit-equal) across numpy int64 and device int32/float32."""
        raise NotImplementedError

    def margin(self, xp, pay: Array) -> Array:
        """`margin_cols` over a (..., P) payload array."""
        return self.margin_cols(
            xp, [pay[..., c] for c in range(self.payload_width)])

    def test_cols(self, xp, agg, k) -> Tuple[Array, Array]:
        """The safe-zone test in column form: `agg` holds the P
        component arrays of the per-direction agreement A = X_in + X_out,
        `k` those of the knowledge, broadcastable against `agg`.
        Returns (send — margins of A and K - A disagree, the Alg. 3
        violation, shaped like `agg`'s columns; output — margin(K) >= 0,
        shaped like `k`'s columns)."""
        ta = self.margin_cols(xp, agg)
        tka = self.margin_cols(xp, [kc - ac for kc, ac in zip(k, agg)])
        send = ((ta >= 0) & (tka < 0)) | ((ta < 0) & (tka > 0))
        return send, self.margin_cols(xp, k) >= 0

    def test(self, xp, agg: Array, k: Array) -> Tuple[Array, Array]:
        """`test_cols` over arrays: ``agg`` (..., 3, P), ``k`` (..., P).
        Returns (send (..., 3) bool, output (...,) bool)."""
        pw = self.payload_width
        send, out = self.test_cols(
            xp, [agg[..., c] for c in range(pw)],
            [k[..., None, c] for c in range(pw)])
        return send, out[..., 0]

    # -- convergence ---------------------------------------------------------
    def converged(self, xp, outputs: Array, truth: Array) -> Array:
        """Per-peer convergence predicate (engines mask occupancy and
        reduce). Default: the peer outputs the target decision."""
        return outputs == truth

    def global_output(self, data: np.ndarray) -> int:
        """Ground-truth decision from the quantized (n, D) data plane
        (what every peer must converge to)."""
        k = np.concatenate(
            [data.sum(0).astype(np.int64), [np.int64(data.shape[0])]])
        return int(self.margin(np, k) >= 0)

    def __repr__(self):
        return f"{type(self).__name__}()"


class Majority(ThresholdProblem):
    """The paper's Alg. 3: is the fraction of 1-votes >= 1/2?

    Payload = (ones, total); margin = 2*ones - total (the paper's
    (1, -1/2)^t X functional kept in integers). Bit-identical to the
    pre-problem-layer engine on both backends — the golden-grid test
    (tests/test_problems.py) pins this.
    """

    name = "majority"
    data_width = 1

    def init_state(self, data: np.ndarray) -> np.ndarray:
        a = super().init_state(data)
        if not np.isin(a, (0, 1)).all():
            raise ValueError("majority votes must be 0/1")
        return a

    def margin_cols(self, xp, cols) -> Array:
        return 2 * cols[0] - cols[1]


class MeanMonitor(ThresholdProblem):
    """Mean monitoring (Wolff arXiv:1212.5880 §3): is the network-wide
    mean of a scalar stream above ``tau``?

    Raw floats are fixed-point quantized once on the host
    (``q = round(x * scale)``), and the margin stays integer-exact on
    both backends:  mean(x) >= tau  <=>  sum(q) - T*count >= 0  with
    ``T = round(tau * scale)``. Like Majority this is a *linear*
    threshold — the Alg. 3 quiescence argument carries over verbatim,
    majority being the (tau = 1/2 on 0/1 data) special case.

    Exactness bound: |sum(q)| + T*n must fit int32 for the device
    backend — with the default scale 256, |data| <= 100 holds to
    n ~ 8e4.
    """

    name = "mean"
    data_width = 1

    def __init__(self, tau: float = 0.0, scale: int = 256):
        self.tau = float(tau)
        self.scale = int(scale)
        self.T = int(round(self.tau * self.scale))

    def init_state(self, data: np.ndarray) -> np.ndarray:
        a = np.asarray(data, np.float64)
        if a.ndim == 1:
            a = a[:, None]
        if a.ndim != 2 or a.shape[1] != 1:
            raise ValueError(f"mean data must be (n,) or (n, 1), got {a.shape}")
        return np.round(a * self.scale).astype(np.int64)

    def margin_cols(self, xp, cols) -> Array:
        return cols[0] - self.T * cols[1]

    def __repr__(self):
        return f"MeanMonitor(tau={self.tau}, scale={self.scale})"


class L2Thresh(ThresholdProblem):
    """L2-norm thresholding — the canonical safe-zone instance (Wolff
    arXiv:1212.5880 §4): is ||mean vector|| >= tau for D-dimensional
    per-peer data?

    The outside-the-ball region is NOT convex, so the generic
    sign-disagreement test can quiesce globally wrong (observed: a few
    peers wedge on the wrong side). The paper's construction covers the
    outside with half-spaces *tangent to the sphere at a fixed direction
    set* U (``ndirs`` of them, frozen at construction so every peer and
    both backends share the cover):

      f_m(X) = <s, u_m> - T*c        (T = tau * scale, fixed point)
      margin(X) = max_m f_m(X)

    ``margin >= 0`` (the output) means X lies in SOME tangent half-space
    — each half-space is convex, and the complement (margin < 0, an
    intersection of half-space complements containing the open ball) is
    convex too. `test` then checks A and K - A against the *specific*
    convex region K itself occupies: the argmax half-space when K is
    outside, the complement intersection when inside. Violations are
    always locally resolvable (Send makes A = K, which satisfies its own
    region by construction), so the Alg. 3 quiescence argument applies
    region-wise.

    The finite cover decides a thin shell tau <= ||mean|| < tau/cos(pi/M)
    as "inside" (~2% for the default 16 directions in D = 2, exact for
    D = 1) — instances that razor-thin are outside the contract.

    Exactness: margins are float32 with *unrolled* elementwise
    accumulation (no library reductions that could reassociate), so
    numpy and XLA CPU produce bit-identical results.
    """

    name = "l2"

    def __init__(self, tau: float = 1.0, dim: int = 2, scale: int = 256,
                 ndirs: int = 16):
        self.tau = float(tau)
        self.data_width = int(dim)
        self.scale = int(scale)
        self.Tf = np.float32(self.tau * self.scale)
        self.U = self._direction_cover(self.data_width, int(ndirs))

    @staticmethod
    def _direction_cover(dim: int, ndirs: int) -> np.ndarray:
        """(M, D) float32 unit directions. D=1: exact {+1, -1}; D=2:
        evenly spaced angles; D>=3: the +/- axes plus a deterministic
        normalized-Gaussian fill (seeded — every instance with the same
        (dim, ndirs) shares the cover)."""
        if dim == 1:
            return np.asarray([[1.0], [-1.0]], np.float32)
        if dim == 2:
            ang = 2 * np.pi * np.arange(ndirs) / ndirs
            return np.stack([np.cos(ang), np.sin(ang)], 1).astype(np.float32)
        axes = np.concatenate([np.eye(dim), -np.eye(dim)])
        extra = max(ndirs - 2 * dim, 0)
        g = np.random.default_rng(dim * 1000 + ndirs).normal(
            size=(extra, dim))
        g /= np.linalg.norm(g, axis=1, keepdims=True)
        return np.concatenate([axes, g]).astype(np.float32)

    def init_state(self, data: np.ndarray) -> np.ndarray:
        a = np.asarray(data, np.float64)
        if a.ndim != 2 or a.shape[1] != self.data_width:
            raise ValueError(
                f"l2 data must be (n, {self.data_width}), got {a.shape}")
        return np.round(a * self.scale).astype(np.int64)

    def _proj(self, xp, cols):
        """The M tangent-half-space margins f_m = <s, u_m> - T*c, one
        array per direction. The cover's entries are float32 scalars
        baked into the trace, and the sum is unrolled in a fixed order."""
        d = self.data_width
        f = [c.astype(xp.float32) for c in cols]
        out = []
        for u in self.U:
            acc = f[0] * u[0]
            for j in range(1, d):
                acc = acc + f[j] * u[j]
            out.append(acc - self.Tf * f[d])
        return out

    def margin_cols(self, xp, cols) -> Array:
        pk = self._proj(xp, cols)
        best = pk[0]
        for p in pk[1:]:
            best = xp.maximum(best, p)
        return best

    def test_cols(self, xp, agg, k):
        """Region-wise safe-zone test. Each tangent functional f_m is
        *linear and additive*, so the paper's quiescence argument holds
        per functional; the nonlinearity lives only in which functionals
        a peer checks:

          * K outside (margin(K) >= 0): the generic asymmetric Alg. 3
            comparison on the argmax half-space f_m* — at quiescence
            f_m*(A) >= 0 on every link, so every neighbor sees
            cover-margin(A) >= 0;
          * K inside: the same comparison on EVERY f_m (violation if
            any m violates) — at quiescence f_m(A) < 0 for all m (a
            tolerated f_m(A) >= 0 would make f_m(K) >= 0, contradicting
            "inside"), so every neighbor sees cover-margin(A) < 0.

        A mixed-output edge therefore cannot be quiescent — outputs are
        constant across the tree at quiescence, exactly the majority
        lemma region-wise. Keeping the paper's (>= 0, < 0) / (< 0, > 0)
        asymmetry makes the zero payload (empty agreement in the first
        position, exhausted K - A in the second) behave exactly as in
        Alg. 3: empty agreements wake inside-deciding peers, exhausted
        residuals never re-violate (a symmetric region-membership test
        storms there — observed).

        m* is the FIRST maximal direction (argmax order), tracked by a
        strict running comparison; bools combine by mask algebra, not
        selects."""
        pk = self._proj(xp, k)
        pa = self._proj(xp, agg)
        pka = self._proj(xp, [kc - ac for kc, ac in zip(k, agg)])
        viol = [((a >= 0) & (r < 0)) | ((a < 0) & (r > 0))
                for a, r in zip(pa, pka)]
        best, sel, anyv = pk[0], viol[0], viol[0]
        for p, v in zip(pk[1:], viol[1:]):
            better = p > best
            best = xp.maximum(best, p)
            sel = (better & v) | (~better & sel)
            anyv = anyv | v
        out = best >= 0
        return (out & sel) | (~out & anyv), out

    def __repr__(self):
        return (f"L2Thresh(tau={self.tau}, dim={self.data_width}, "
                f"scale={self.scale}, ndirs={self.U.shape[0]})")



MAJORITY = Majority()  # the default problem (`get_problem(None)`); the
# engines select the fused Pallas fast path by isinstance(_, Majority),
# never by identity — get_problem("majority") returns a fresh instance

PROBLEMS = {"majority": Majority, "mean": MeanMonitor, "l2": L2Thresh}


def get_problem(spec, **kwargs) -> ThresholdProblem:
    """Resolve a problem instance from an instance, a name, or None
    (CLI plumbing: ``--problem {majority,mean,l2}``)."""
    if spec is None:
        return MAJORITY
    if isinstance(spec, ThresholdProblem):
        return spec
    if spec in PROBLEMS:
        return PROBLEMS[spec](**kwargs)
    raise ValueError(
        f"unknown threshold problem {spec!r}; want one of {sorted(PROBLEMS)}")
