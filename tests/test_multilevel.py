"""Multilevel batch partitioner: coarsening, model graph, refinement."""
import numpy as np
import pytest
from hypothesis import given, settings, strategies as st

from repro.graphs import rmat_graph
from repro.core.fennel import FennelParams
from repro.core.batch_model import build_batch_model
from repro.core.multilevel import (
    MultilevelConfig,
    multilevel_partition,
    lp_cluster,
    contract,
    lp_refine,
)
from repro.core.metrics import edge_cut


def _params(g, k=4, eps=0.1):
    return FennelParams(k=k, n_total=float(g.node_w.sum()),
                        m_total=g.total_edge_weight(), eps=eps)


def test_batch_model_structure(small_rmat):
    g = small_rmat
    k = 4
    block = np.full(g.n, -1, dtype=np.int64)
    block[:100] = np.arange(100) % k  # first 100 assigned
    batch = np.arange(120, 180)
    model = build_batch_model(g, batch, block, k)
    assert model.graph.n == batch.size + k
    assert (model.pinned_block[: batch.size] == -1).all()
    assert np.array_equal(model.pinned_block[batch.size:], np.arange(k))
    # aux node weights are zero (loads tracked separately)
    assert np.allclose(model.graph.node_w[batch.size:], 0.0)
    # internal edge weight == edges among batch nodes in g
    in_b = np.zeros(g.n, bool)
    in_b[batch] = True
    expected = sum(
        w for v in batch for u, w in zip(g.neighbors(int(v)), g.neighbor_weights(int(v)))
        if in_b[u] and int(v) < u
    )
    got = 0.0
    for i in range(batch.size):
        for u, w in zip(model.graph.neighbors(i), model.graph.neighbor_weights(i)):
            if u < batch.size and i < u:
                got += w
    assert got == pytest.approx(expected)
    # aux edge weight for node v to block p == assigned-nbr weight in p
    for i, v in enumerate(batch[:10]):
        conn = np.zeros(k)
        for u, w in zip(g.neighbors(int(v)), g.neighbor_weights(int(v))):
            if block[u] >= 0:
                conn[block[u]] += w
        model_conn = np.zeros(k)
        for u, w in zip(model.graph.neighbors(i), model.graph.neighbor_weights(i)):
            if u >= batch.size:
                model_conn[u - batch.size] += w
        assert np.allclose(model_conn, conn)


def test_lp_cluster_respects_pins_and_caps(small_grid):
    g = small_grid
    pinned = np.full(g.n, -1, dtype=np.int64)
    pinned[:4] = np.arange(4)
    cap = 10.0
    cluster = lp_cluster(g, pinned, cap, iters=3, rng=np.random.default_rng(0))
    # pinned nodes stay singletons
    for v in range(4):
        assert cluster[v] == v
        assert (cluster[4:] != v).all()
    # cluster weights within cap
    sizes = np.bincount(cluster, minlength=g.n).astype(float)
    assert sizes.max() <= cap + 1e-6


def test_contract_preserves_total_edge_weight(small_grid):
    g = small_grid
    pinned = np.full(g.n, -1, dtype=np.int64)
    cluster = lp_cluster(g, pinned, 8.0, 2, np.random.default_rng(0))
    cg, cpin, node_map = contract(g, cluster, pinned)
    # total weight = internal (dropped) + kept; kept equals cross-cluster
    src = np.repeat(np.arange(g.n), np.diff(g.indptr))
    dst = g.indices
    cross = cluster[src] != cluster[dst]
    assert cg.total_edge_weight() == pytest.approx(g.edge_w[cross].sum() / 2)
    assert cg.node_w.sum() == pytest.approx(g.node_w.sum())


def test_multilevel_balanced_and_better_than_random(small_grid):
    g = small_grid
    k = 4
    p = _params(g, k)
    pinned = np.full(g.n, -1, dtype=np.int64)
    labels = multilevel_partition(g, pinned, p, np.zeros(k), MultilevelConfig())
    assert (labels >= 0).all() and (labels < k).all()
    loads = np.bincount(labels, weights=g.node_w, minlength=k)
    assert loads.max() <= p.cap + 1e-6
    rng = np.random.default_rng(0)
    assert edge_cut(g, labels) < edge_cut(g, rng.integers(0, k, g.n))


def test_multilevel_respects_existing_loads(small_grid):
    """With block 0 nearly full, new nodes must flow to other blocks.

    n_total must include the pre-existing load (as the streaming driver's
    FennelParams always does — it is the FULL graph weight)."""
    g = small_grid
    k = 4
    preload = 100.0
    p = FennelParams(
        k=k, n_total=float(g.node_w.sum()) + preload,
        m_total=g.total_edge_weight(), eps=0.05,
    )
    pinned = np.full(g.n, -1, dtype=np.int64)
    loads = np.zeros(k)
    loads[0] = preload
    labels = multilevel_partition(g, pinned, p, loads, MultilevelConfig())
    new_in_0 = g.node_w[labels == 0].sum()
    assert loads[0] + new_in_0 <= p.cap + 1e-6


def test_lp_refine_monotone(small_grid):
    g = small_grid
    k = 4
    p = _params(g, k)
    rng = np.random.default_rng(1)
    labels = rng.integers(0, k, g.n)
    pinned = np.full(g.n, -1, dtype=np.int64)
    loads = np.bincount(labels, weights=g.node_w, minlength=k).astype(np.float64)
    before = edge_cut(g, labels)
    refined, _ = lp_refine(g, labels, pinned, p, loads, rounds=4)
    assert edge_cut(g, refined) <= before


def test_histogram_engines_agree(small_rmat):
    """Sparse bincount, sort fallback and the ELL kernel path must produce
    the same (node, label) -> weight histogram."""
    from repro.core.histogram import (
        neighbor_label_weights, sorted_neighbor_label_weights,
        label_histogram_ell, best_label_per_src,
    )
    g = small_rmat
    rng = np.random.default_rng(0)
    for labels in (rng.integers(0, 17, g.n), rng.permutation(g.n).astype(np.int64)):
        s_new = neighbor_label_weights(g, labels)
        s_old = sorted_neighbor_label_weights(g, labels)
        d_new = {(int(a), int(b)): w for a, b, w in zip(*s_new)}
        d_old = {(int(a), int(b)): w for a, b, w in zip(*s_old)}
        assert d_new.keys() == d_old.keys()
        for key in d_new:
            assert d_new[key] == pytest.approx(d_old[key])
        counts, uniq = label_histogram_ell(g, labels, use_kernel=False)
        col = {int(l): j for j, l in enumerate(uniq)}
        for (v, l), w in d_old.items():
            assert counts[v, col[l]] == pytest.approx(w, rel=1e-5)
        assert np.count_nonzero(counts) == len(d_old)
        # best-move selection matches the seed's lexsort policy
        src, lab, wsum = s_old
        keep = lab != labels[src]
        movers, targets, gains = best_label_per_src(src[keep], lab[keep], wsum[keep], g.n)
        order = np.lexsort((lab[keep], -wsum[keep], src[keep]))
        first = np.ones(order.shape[0], dtype=bool)
        first[1:] = src[keep][order][1:] != src[keep][order][:-1]
        sel = order[first]
        assert np.array_equal(movers, src[keep][sel])
        assert np.array_equal(targets, lab[keep][sel])
        np.testing.assert_allclose(gains, wsum[keep][sel])


@pytest.mark.parametrize("ordering", ["natural", "bfs", "adversarial"])
@pytest.mark.parametrize("engine", ["sparse", "ell", "jax"])
def test_multilevel_engine_parity(engine, ordering, small_grid):
    """Every inner-op engine drives multilevel to the same partition, on
    high-locality (natural/BFS) and locality-destroyed (KONECT) orders."""
    from repro.graphs import apply_order, bfs_order, konect_order, source_order

    order = {"natural": source_order, "bfs": bfs_order,
             "adversarial": konect_order}[ordering]
    g = apply_order(small_grid, order(small_grid))
    k = 4
    p = _params(g, k)
    pinned = np.full(g.n, -1, dtype=np.int64)
    ref = multilevel_partition(g, pinned, p, np.zeros(k),
                               MultilevelConfig(engine="sparse"))
    got = multilevel_partition(g, pinned, p, np.zeros(k),
                               MultilevelConfig(engine=engine))
    assert edge_cut(g, got) == edge_cut(g, ref)
    loads = np.bincount(got, weights=g.node_w, minlength=k)
    assert loads.max() <= p.cap + 1e-6
    if engine == "jax":  # device engine pins exact labels, not just the cut
        assert np.array_equal(got, ref)


@given(st.integers(2, 8), st.integers(0, 10**6))
@settings(max_examples=10, deadline=None)
def test_multilevel_property(k, seed):
    g = rmat_graph(128, 6, seed=seed % 97)
    p = _params(g, k)
    pinned = np.full(g.n, -1, dtype=np.int64)
    labels = multilevel_partition(g, pinned, p, np.zeros(k),
                                  MultilevelConfig(seed=seed))
    assert (labels >= 0).all() and (labels < k).all()
    loads = np.bincount(labels, weights=g.node_w, minlength=k)
    assert loads.max() <= p.cap + 1e-6


# ------------------------------------------------- scalar gain engine pin


def _fennel_sequential_reference(g, order, labels, loads, *, alpha, gamma,
                                 cap, k):
    """The vectorized per-step loop `fennel_gain_sequential` replaced: ell
    gather + np.bincount + penalty + masked np.argmax per step."""
    labels = labels.copy()
    loads = loads.copy()
    ag = float(alpha) * float(gamma)
    for v in order.tolist():
        lo, hi = g.indptr[v], g.indptr[v + 1]
        nbr_lab = labels[g.indices[lo:hi]]
        keep = nbr_lab >= 0
        conn = np.bincount(nbr_lab[keep],
                           weights=g.edge_w[lo:hi][keep].astype(np.float64),
                           minlength=k)
        penalty = ag * np.power(np.maximum(loads, 0.0), float(gamma) - 1.0)
        nw = float(g.node_w[v])
        feasible = loads + nw <= cap
        if feasible.any():
            best = int(np.argmax(np.where(feasible, conn - penalty, -np.inf)))
        else:
            best = int(np.argmin(loads))
        labels[v] = best
        loads[best] = loads[best] + nw
    return labels, loads


@pytest.mark.parametrize("gamma", [1.25, 1.5, 3.0])
def test_fennel_gain_sequential_matches_vectorized_reference(gamma):
    """core/fennel.py::fennel_gain_sequential is bit-identical to
    the per-step numpy loop it replaced — the `_pow_scalar` fast paths
    (gamma-1 ∈ {0.25 generic, 0.5 sqrt, 2.0 square}) and the left-to-right
    connectivity adds are the contract (referenced by its docstring)."""
    from repro.core.fennel import fennel_gain_sequential

    rng = np.random.default_rng(13)
    g = rmat_graph(256, 6, seed=21)
    k = 5
    p = FennelParams(k=k, n_total=float(g.node_w.sum()),
                     m_total=g.total_edge_weight(), eps=0.08, gamma=gamma)
    # partially pinned start + matching loads, like a coarsest-level call
    labels0 = np.full(g.n, -1, dtype=np.int64)
    pin = rng.choice(g.n, 60, replace=False)
    labels0[pin] = rng.integers(0, k, pin.size)
    loads0 = np.bincount(labels0[pin], weights=g.node_w[pin],
                         minlength=k).astype(np.float64)
    free = np.nonzero(labels0 < 0)[0]
    order = free[np.lexsort((free, -g.node_w[free]))]

    ref_labels, ref_loads = _fennel_sequential_reference(
        g, order, labels0, loads0, alpha=p.alpha, gamma=p.gamma, cap=p.cap, k=k
    )
    got_labels = labels0.copy()
    got_loads = loads0.copy()
    fennel_gain_sequential(
        g.indptr, g.indices, g.edge_w, g.node_w, order, got_labels,
        got_loads, alpha=p.alpha, gamma=p.gamma, cap=p.cap, k=k,
    )
    assert np.array_equal(ref_labels, got_labels)
    assert np.array_equal(ref_loads, got_loads)  # bitwise, not approx
