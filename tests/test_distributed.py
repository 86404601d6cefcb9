"""Distributed runtime: GNN placement, and the vectorized driver's scores."""
import jax.numpy as jnp
import numpy as np

from repro.distributed.gnn_placement import place_graph, placement_report
from repro.core.vector_stream import score_kernel
from repro.core.scores import get_score
from repro.graphs import grid_mesh_graph, apply_order, random_order


def test_score_kernel_matches_scorespec():
    a = jnp.asarray(np.random.default_rng(0).random(50) * 8)
    d = jnp.asarray(np.random.default_rng(1).integers(1, 20, 50).astype(np.float64))
    q = jnp.asarray(np.random.default_rng(2).random(50) * 4)
    for kind in ("anr", "cbs", "haa", "nss"):
        spec = get_score(kind, d_max=100.0)
        got = score_kernel(a, d, q, kind=kind, d_max=100.0,
                           beta=spec.beta, theta=spec.theta, eta=spec.eta)
        want = spec(np.asarray(a), np.asarray(d), np.asarray(q))
        np.testing.assert_allclose(np.asarray(got), want, rtol=1e-6)


def test_buffcut_placement_beats_random():
    """The paper's systems payoff: BuffCut placement cuts halo bytes."""
    g = grid_mesh_graph(32)
    g = apply_order(g, random_order(g, 3))
    rep = placement_report(g, n_shards=8, d_feat=64)
    assert rep["buffcut"]["halo_MB_per_layer"] < rep["random"]["halo_MB_per_layer"] * 0.6
    assert rep["buffcut"]["load_imbalance"] < 1.2


def test_placement_reorder_contiguous():
    from repro.distributed.gnn_placement import reorder_for_shards
    g = grid_mesh_graph(16)
    p = place_graph(g, 4, method="hash")
    perm = reorder_for_shards(g, p)
    blocks = p.block[perm]
    assert (np.diff(blocks) >= 0).all()  # shard-major contiguous
