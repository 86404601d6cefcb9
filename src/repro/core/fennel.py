"""Fennel one-pass streaming partitioning [Tsourakakis et al., WSDM'14].

Assign v to the block maximizing g(v, V_i) = w(N(v) ∩ V_i) − f(c(V_i)) with
f(x) = alpha * gamma * x^(gamma-1), alpha = m * k^(gamma-1) / n^gamma, subject
to the hard cap c(V_i) + c(v) <= L_max. Used three ways in this system:
 (1) standalone one-pass baseline,
 (2) BuffCut's immediate hub assignment (paper Alg. 1),
 (3) weighted variant for the coarsest-level initial partition (HeiStream).
Also provides LDG [Stanton & Kliot, KDD'12] as a second one-pass baseline,
and `fennel_gain_sequential`, the scalar host engine of the same scoring
rule that use (3) runs.
"""
from __future__ import annotations

import dataclasses
import math

import numpy as np

from repro.graphs.csr import CSRGraph
from repro.core._deprecation import require_csr, warn_legacy
from repro.core.metrics import l_max


@dataclasses.dataclass
class FennelParams:
    k: int
    n_total: float  # total node weight c(V) of the *full* graph (known a priori)
    m_total: float  # total edge weight of the full graph
    eps: float = 0.03
    gamma: float = 1.5

    @property
    def alpha(self) -> float:
        n = max(self.n_total, 1.0)
        return self.m_total * self.k ** (self.gamma - 1.0) / (n**self.gamma)

    @property
    def cap(self) -> float:
        return l_max(self.n_total, self.k, self.eps)


def fennel_penalty(loads: np.ndarray, p: FennelParams) -> np.ndarray:
    return p.alpha * p.gamma * np.power(np.maximum(loads, 0.0), p.gamma - 1.0)


def block_connectivity(
    nbrs: np.ndarray, nbr_w: np.ndarray, block: np.ndarray, k: int
) -> np.ndarray:
    """w(N(v) ∩ V_i) for all i — the inner op of every assignment decision."""
    conn = np.zeros(k, dtype=np.float64)
    if nbrs.size:
        b = block[nbrs]
        ok = b >= 0
        np.add.at(conn, b[ok], nbr_w[ok])
    return conn


def fennel_choose(
    nbrs: np.ndarray,
    nbr_w: np.ndarray,
    node_w: float,
    block: np.ndarray,
    loads: np.ndarray,
    p: FennelParams,
) -> int:
    """Pick the Fennel-optimal feasible block (deterministic tie-break)."""
    conn = block_connectivity(nbrs, nbr_w, block, p.k)
    score = conn - fennel_penalty(loads, p)
    feasible = loads + node_w <= p.cap
    if not feasible.any():  # degenerate: everything full — least-loaded
        return int(np.argmin(loads))
    score = np.where(feasible, score, -np.inf)
    best = score.max()
    cand = np.nonzero(score >= best - 1e-12)[0]
    if cand.size > 1:  # tie: least-loaded, then lowest id
        cand = cand[np.argsort(loads[cand], kind="stable")]
    return int(cand[0])


def _pow_scalar(g1: float):
    """Scalar twin of the `np.power(m, g1)` array loop: numpy special-cases
    exponents 2.0 (x*x), 0.5 (sqrt) and -1.0 (1/x) in its broadcast loop,
    so the scalar path must take the same fast paths to stay bit-identical;
    every other exponent matches scalar np.power exactly."""
    if g1 == 2.0:
        return lambda m: m * m
    if g1 == 0.5:
        return math.sqrt
    if g1 == -1.0:
        return lambda m: 1.0 / m
    return lambda m: float(np.power(m, g1))


def fennel_gain_sequential(
    indptr: np.ndarray,
    indices: np.ndarray,
    edge_w: np.ndarray,
    node_w: np.ndarray,
    order: np.ndarray,
    labels: np.ndarray,
    loads: np.ndarray,
    *,
    alpha: float,
    gamma: float,
    cap: float,
    k: int,
) -> None:
    """Sequential Fennel sweep over `order`, mutating labels/loads in place.

    The coarsest-level initial partition of core/multilevel.py: batches are
    ~10²-10³ nodes and k is small, so per-step numpy dispatch costs more
    than the arithmetic and this scalar loop is ~5x faster on the host.

    Bit-identity contract with the vectorized per-step loop this replaced
    (ell gather + np.bincount + fennel_penalty + np.argmax per step):
    connectivity accumulates float64 left-to-right in CSR adjacency order
    (== np.bincount's input-order adds, f32 weights cast exactly); the
    penalty is (alpha*gamma) * m**(gamma-1) with numpy's pow fast paths
    (`_pow_scalar`); feasible scores compare with strict `>` (first-max ==
    np.argmax); the all-infeasible fallback is first-min of loads (==
    np.argmin).  Scores never materialize for infeasible blocks — they were
    -inf under the mask and can't win argmax anyway.
    """
    ag = float(alpha) * float(gamma)
    powf = _pow_scalar(float(gamma) - 1.0)
    cap = float(cap)
    loads_l = loads.tolist()
    labels_l = labels.tolist()
    conn = [0.0] * k
    ip = indptr.tolist()
    # f8/f4 -> python float via tolist is value-exact (f4 widens losslessly)
    idx = indices.tolist()
    ew = edge_w.tolist()
    nws = node_w.tolist()
    rng = range(k)
    for v in order.tolist():
        for i in rng:
            conn[i] = 0.0
        for j in range(ip[v], ip[v + 1]):
            b = labels_l[idx[j]]
            if b >= 0:
                conn[b] += ew[j]
        nw = nws[v]
        best_i = -1
        best_s = -math.inf
        for i in rng:
            li = loads_l[i]
            if li + nw > cap:
                continue
            m = li if li > 0.0 else 0.0
            s = conn[i] - ag * powf(m)
            if s > best_s:
                best_s = s
                best_i = i
        if best_i < 0:
            best_i = loads_l.index(min(loads_l))
        labels_l[v] = best_i
        loads_l[best_i] = loads_l[best_i] + nw
    labels[:] = labels_l
    loads[:] = loads_l


def fennel_partition(
    g: CSRGraph, k: int, eps: float = 0.03, gamma: float = 1.5
) -> np.ndarray:
    """Deprecated shim — `repro.api.partition` is the front door."""
    warn_legacy("fennel_partition(g, k, eps, gamma)", "partition(g, driver='fennel', k=...)")
    return _fennel_partition(g, k, eps, gamma)


def _fennel_partition(
    g: CSRGraph, k: int, eps: float = 0.03, gamma: float = 1.5
) -> np.ndarray:
    """One-pass Fennel over the stream order (node id order)."""
    g = require_csr(g, "fennel")
    p = FennelParams(k=k, n_total=float(g.node_w.astype(np.float64).sum()),
                     m_total=g.total_edge_weight(), eps=eps, gamma=gamma)
    block = np.full(g.n, -1, dtype=np.int64)
    loads = np.zeros(k, dtype=np.float64)
    for v in range(g.n):
        i = fennel_choose(g.neighbors(v), g.neighbor_weights(v), float(g.node_w[v]), block, loads, p)
        block[v] = i
        loads[i] += g.node_w[v]
    return block


def ldg_partition(g: CSRGraph, k: int, eps: float = 0.03) -> np.ndarray:
    """Deprecated shim — `repro.api.partition` is the front door."""
    warn_legacy("ldg_partition(g, k, eps)", "partition(g, driver='ldg', k=...)")
    return _ldg_partition(g, k, eps)


def _ldg_partition(g: CSRGraph, k: int, eps: float = 0.03) -> np.ndarray:
    """Linear Deterministic Greedy: argmax |N(v) ∩ V_i| * (1 - c(V_i)/cap)."""
    g = require_csr(g, "ldg")
    cap = l_max(float(g.node_w.astype(np.float64).sum()), k, eps)
    block = np.full(g.n, -1, dtype=np.int64)
    loads = np.zeros(k, dtype=np.float64)
    for v in range(g.n):
        conn = block_connectivity(g.neighbors(v), g.neighbor_weights(v), block, k)
        score = conn * (1.0 - loads / cap)
        feasible = loads + g.node_w[v] <= cap
        score = np.where(feasible, score, -np.inf)
        if not feasible.any():
            i = int(np.argmin(loads))
        else:
            best = score.max()
            cand = np.nonzero(score >= best - 1e-12)[0]
            cand = cand[np.argsort(loads[cand], kind="stable")]
            i = int(cand[0])
        block[v] = i
        loads[i] += g.node_w[v]
    return block
