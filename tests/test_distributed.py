"""Distributed runtime on the host mesh: rules, overlap, placement, scores."""
import jax
import jax.numpy as jnp
import numpy as np
from jax.sharding import PartitionSpec as P

from repro.distributed.sharding import (
    lm_sharding_rules,
    lm_decode_sharding_rules,
    param_shardings,
)
from repro.distributed.overlap import (
    collective_matmul_allgather, allgather_matmul_reference,
)
from repro.distributed.gnn_placement import place_graph, placement_report
from repro.core.vector_stream import score_kernel
from repro.core.scores import get_score
from repro.graphs import grid_mesh_graph, apply_order, random_order


def test_lm_rules_cover_all_params():
    from repro.configs import get_arch
    from repro.models import transformer as tfm
    for arch in ("stablelm-3b", "moonshot-v1-16b-a3b"):
        spec = get_arch(arch)
        cfg = spec.smoke_config()
        params = jax.eval_shape(lambda: tfm.init_params(jax.random.PRNGKey(0), cfg))
        mesh = jax.make_mesh((1, 1), ("data", "model"))
        rules = lm_sharding_rules(moe=cfg.n_experts > 0)
        sh = param_shardings(rules, mesh, params)
        # every layer-stacked leaf must have a non-trivial template match
        flat = jax.tree_util.tree_flatten_with_path(sh)[0]
        assert len(flat) == len(jax.tree.leaves(params))


def test_opt_state_paths_match_param_rules():
    """m/<param> and v/<param> resolve to the same spec as <param>."""
    mesh = jax.make_mesh((1, 1), ("data", "model"))
    rules = lm_sharding_rules()
    assert rules.spec_for(mesh, "params", "m/wq") == rules.spec_for(mesh, "params", "wq")
    assert rules.spec_for(mesh, "params", "v/embed") == rules.spec_for(mesh, "params", "embed")


def test_decode_rules_fully_shard_weights():
    """Decode weights shard over BOTH axes — a 104B dense model cannot be
    'data'-replicated on 16 GB chips (EXPERIMENTS.md §Perf iter. 8)."""
    mesh = jax.make_mesh((1, 1), ("data", "model"))
    r = lm_decode_sharding_rules()
    for name in ("ffn_w1", "wq", "wo", "embed"):
        spec = str(r.spec_for(mesh, "params", name))
        assert "data" in spec and "model" in spec, (name, spec)


def test_collective_matmul_matches_reference():
    mesh = jax.make_mesh((1,), ("model",))
    x = jax.random.normal(jax.random.PRNGKey(0), (8, 16))
    w = jax.random.normal(jax.random.PRNGKey(1), (16, 4))
    f1 = jax.shard_map(
        lambda xl, w: collective_matmul_allgather(xl, w, "model"),
        mesh=mesh, in_specs=(P("model"), P()), out_specs=P("model"),
    )
    f2 = jax.shard_map(
        lambda xl, w: allgather_matmul_reference(xl, w, "model"),
        mesh=mesh, in_specs=(P("model"), P()), out_specs=P("model"),
    )
    np.testing.assert_allclose(np.asarray(f1(x, w)), np.asarray(f2(x, w)), rtol=1e-5)


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
