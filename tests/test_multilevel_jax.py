"""Device-resident multilevel engine: exact parity, bucketing, jit cache.

The contract under test (DESIGN.md §3.5): `MultilevelConfig(engine="jax")`
produces *identical labels* to the numpy `sparse` oracle at fixed seed on
integer-weight graphs — across aggregation modes, stream orderings and
whole streams — while compiling a bounded number of times thanks to pow2
shape bucketing.
"""
import numpy as np
import pytest

import repro.core.multilevel_jax as mlj
from repro.core import BuffCutConfig
from repro.core.batch_model import build_batch_model
from repro.core.fennel import FennelParams
from repro.core.multilevel import (
    MultilevelConfig,
    _accept_with_capacity,
    multilevel_partition,
)
from repro.core.vector_stream import buffcut_partition_vectorized
from repro.graphs import (
    apply_order,
    bfs_order,
    grid_mesh_graph,
    konect_order,
    rmat_graph,
    source_order,
)
from repro.graphs.csr import CSRGraph, bucket_size


def _params(g, k, eps=0.1):
    return FennelParams(k=k, n_total=float(g.node_w.sum()),
                        m_total=g.total_edge_weight(), eps=eps)


# a power-law graph with hubs and a planar mesh with none, built lazily so
# collection stays cheap
_GRAPHS = {
    "rmat": lambda: rmat_graph(512, 8, seed=3),
    "grid": lambda: grid_mesh_graph(24),
}
_ORDERED_GRAPHS = {
    "rmat": lambda: rmat_graph(384, 8, seed=11),
    "grid": lambda: grid_mesh_graph(20),
}


# ------------------------------------------------------------- bucketing

def test_bucket_size():
    assert [bucket_size(x) for x in (1, 63, 64, 65, 128, 129)] == \
        [64, 64, 64, 128, 128, 256]
    assert bucket_size(3, minimum=8) == 8
    assert bucket_size(9, minimum=8) == 16


def test_to_coo_padded_roundtrip():
    g = rmat_graph(64, 4, seed=0)
    n_pad, e_pad = 128, bucket_size(int(g.indices.size), minimum=128)
    src, dst, w = g.to_coo_padded(n_pad, e_pad)
    e = g.indices.size
    assert src.shape == (e_pad,)
    assert (src[e:] == n_pad).all() and (w[e:] == 0).all()
    # valid prefix reproduces the CSR exactly, in src-sorted order
    assert (np.diff(src[:e]) >= 0).all()
    rebuilt = CSRGraph.from_edges(
        g.n, np.stack([src[:e], dst[:e]], 1), edge_weights=w[:e])
    assert np.array_equal(rebuilt.indptr, g.indptr)
    with pytest.raises(ValueError):
        g.to_coo_padded(n_pad, e - 1)


def test_to_ell_padded_buckets():
    g = rmat_graph(100, 4, seed=0)
    nbr, wts, mask = g.to_ell_padded()
    assert nbr.shape[0] == 128  # rows bucketed to pow2
    assert nbr.shape[1] == bucket_size(g.max_degree, minimum=8)
    assert mask.sum() == g.indices.size
    # padded rows are all-invalid
    assert not mask[g.n:].any()


# ---------------------------------------------------- mode/label parity

@pytest.mark.parametrize("graph", sorted(_GRAPHS))
@pytest.mark.parametrize("k", [8, 32, 64, 128, 130])
@pytest.mark.parametrize("mode", ["dense", "sort", "ell"])
def test_jax_modes_match_sparse_oracle(mode, k, graph):
    """All three aggregation modes produce the sparse oracle's labels on a
    batch-model graph with pinned aux nodes and preexisting loads, from the
    paper's k = 32 up to k past the 128 label columns of one histogram
    tile, on a power-law graph and on a mesh."""
    rng = np.random.default_rng(0)
    g = _GRAPHS[graph]()
    p = _params(g, k, eps=0.05)
    block = np.full(g.n, -1, dtype=np.int64)
    block[:200] = rng.integers(0, k, 200)
    loads = np.bincount(block[:200], weights=g.node_w[:200],
                        minlength=k).astype(np.float64)
    model = build_batch_model(g, np.arange(200, 420), block, k)
    ref = multilevel_partition(model.graph, model.pinned_block, p, loads,
                               MultilevelConfig(engine="sparse"))
    old = mlj.MODE_OVERRIDE
    try:
        mlj.MODE_OVERRIDE = mode
        got = multilevel_partition(model.graph, model.pinned_block, p, loads,
                                   MultilevelConfig(engine="jax"))
    finally:
        mlj.MODE_OVERRIDE = old
    assert np.array_equal(ref, got)


@pytest.mark.parametrize("graph", sorted(_ORDERED_GRAPHS))
@pytest.mark.parametrize("k", [6, 64])
@pytest.mark.parametrize("ordering", ["natural", "bfs", "adversarial"])
def test_jax_exact_labels_across_orderings(ordering, k, graph):
    order = {"natural": source_order, "bfs": bfs_order,
             "adversarial": konect_order}[ordering]
    base = _ORDERED_GRAPHS[graph]()
    g = apply_order(base, order(base))
    p = _params(g, k)
    pinned = np.full(g.n, -1, dtype=np.int64)
    ref = multilevel_partition(g, pinned, p, np.zeros(k),
                               MultilevelConfig(engine="sparse"))
    got = multilevel_partition(g, pinned, p, np.zeros(k),
                               MultilevelConfig(engine="jax"))
    assert np.array_equal(ref, got)
    loads = np.bincount(got, weights=g.node_w, minlength=k)
    assert loads.max() <= p.cap + 1e-6


def test_jax_k_exceeds_node_bucket():
    """k larger than the graph's node bucket must not break the padded
    capacity/target domains (regression: k=100 on a 40-node graph)."""
    g = rmat_graph(40, 4, seed=0)
    k = 100
    p = _params(g, k)
    pinned = np.full(g.n, -1, dtype=np.int64)
    ref = multilevel_partition(g, pinned, p, np.zeros(k),
                               MultilevelConfig(engine="sparse"))
    got = multilevel_partition(g, pinned, p, np.zeros(k),
                               MultilevelConfig(engine="jax"))
    assert np.array_equal(ref, got)


# ------------------------------------------- capacity acceptance paths

def _accept_case(case: str, k: int, n_pad: int):
    """Movers, targets, gains, integer node weights and per-block
    capacities for one acceptance case."""
    rng = np.random.default_rng([k, n_pad, len(case)])
    movers = rng.random(n_pad) < 0.7
    targets = rng.integers(0, k, n_pad)
    node_w = rng.integers(1, 6, n_pad).astype(np.float64)
    gains = rng.integers(1, 64, n_pad).astype(np.float64)
    per_block = np.bincount(targets[movers], weights=node_w[movers],
                            minlength=k)
    capacity = np.floor(per_block * rng.uniform(0.2, 0.8, k))
    if case == "slack":
        capacity = per_block + rng.integers(0, 3, k)
    elif case == "ties":
        gains[:] = 3.0  # the id order alone decides
    elif case == "wide":
        # 48 significant bits, most of them below f32 precision
        gains = (rng.integers(1 << 47, 1 << 48, n_pad) * 2.0 ** -40)
        gains[1::3] = gains[::3][: gains[1::3].size]  # some exact ties
    elif case == "idle":
        movers &= rng.random(n_pad) < 0.3
        capacity[rng.random(k) < 0.4] = 0.0
    gains[~movers] = rng.normal(size=int((~movers).sum()))  # never read
    return movers, targets, gains, node_w, capacity


def _sorted_accept(movers, targets, gains, node_w, capacity):
    """Clustering's sorted acceptance, its pieces composed as the level
    loop dispatches them (under x64)."""
    import jax.numpy as jnp

    tgt, key_lo, key_hi = mlj._accept_keys(
        jnp.asarray(movers), jnp.asarray(targets.astype(np.int32)),
        jnp.asarray(gains))
    return np.asarray(mlj._accept_prefix(
        *mlj._acceptance_order(tgt, key_lo, key_hi), tgt,
        jnp.asarray(node_w), jnp.asarray(capacity)))


def _oracle_accept(movers, targets, gains, node_w, capacity):
    ids = np.nonzero(movers)[0]
    want = np.zeros(movers.shape[0], dtype=bool)
    want[ids] = _accept_with_capacity(ids, targets[ids], gains[ids], node_w,
                                      capacity)
    return want


@pytest.mark.parametrize("case", ["binding", "slack", "ties", "wide", "idle"])
@pytest.mark.parametrize("k,n_pad", [
    (k, n_pad) for k in (2, 8, 32, 64, 128) for n_pad in (64, 1024)
    if k <= n_pad
])
def test_accept_slots_and_sorted_match_oracle(k, n_pad, case):
    """Refine's acceptance over its k + 1 target slots and clustering's
    sorted acceptance accept the same movers as the numpy lexsort +
    grouped cumsum, on binding and slack capacities, equal gains (id
    tie-break), 48-bit gains, non-movers and zero-capacity blocks."""
    import jax
    import jax.numpy as jnp

    movers, targets, gains, node_w, capacity = _accept_case(case, k, n_pad)
    want = _oracle_accept(movers, targets, gains, node_w, capacity)
    padded = np.zeros(n_pad)
    padded[:k] = capacity
    with jax.enable_x64(True):
        args = [jnp.asarray(a) for a in (movers, targets.astype(np.int32),
                                         gains, node_w)]
        slots = np.asarray(mlj._accept_slots(
            *args, jnp.asarray(capacity), n_pad))
        ordered = _sorted_accept(movers, targets, gains, node_w, padded)
    assert np.array_equal(slots, want)
    assert np.array_equal(ordered, want)
    if case == "slack":
        assert np.array_equal(want, movers)
    elif case != "idle":
        assert 0 < want.sum() < movers.sum()  # the capacities bind


def _node_accept_case(case: str, n_pad: int):
    """Movers, node-id targets, gains, integer node weights and per-node
    capacities for one clustering acceptance case."""
    rng = np.random.default_rng([n_pad, len(case)])
    movers = rng.random(n_pad) < 0.7
    targets = rng.integers(0, n_pad, n_pad)
    node_w = rng.integers(1, 6, n_pad).astype(np.float64)
    gains = rng.integers(1, 64, n_pad).astype(np.float64)
    if case == "hub":
        # one target takes most movers (hundreds from n_pad 1,024 up)
        hub = int(rng.integers(0, n_pad))
        targets[rng.random(n_pad) < 0.6] = hub
    elif case == "distinct":
        targets = rng.permutation(n_pad)
    elif case == "ties":
        targets = rng.integers(0, max(n_pad // 16, 2), n_pad)
        gains[:] = 5.0  # the id order alone decides
    elif case == "wide":
        # 48 significant bits in eight buckets of one f32 value each, so
        # the bits below f32 precision alone order a bucket's gains
        top = (1 << 47) + (rng.integers(0, 8, n_pad) << 24)
        gains = (top + rng.integers(0, 1 << 24, n_pad)) * 2.0 ** -40
        gains[1::3] = gains[::3][: gains[1::3].size]  # some exact ties
        targets = rng.integers(0, max(n_pad // 8, 2), n_pad)
    elif case == "edge":
        # every row moves and every gain differs, so the smallest gain's
        # rank is n_pad - 1; its row and a quarter of the others target
        # n_pad - 1, where (target, gain rank) shares the sentinel's bits
        movers[:] = True
        gains = rng.permutation(n_pad).astype(np.float64) + 1.0
        targets[rng.random(n_pad) < 0.25] = n_pad - 1
        targets[np.argmin(gains)] = n_pad - 1
    per_node = np.bincount(targets[movers], weights=node_w[movers],
                           minlength=n_pad)
    capacity = np.floor(per_node * rng.uniform(0.2, 0.8, n_pad))
    if case == "distinct":
        capacity = np.where(rng.random(n_pad) < 0.5, per_node, 0.0)
    elif case == "zero":
        capacity[rng.random(n_pad) < 0.4] = 0.0
    gains[~movers] = rng.normal(size=int((~movers).sum()))  # never read
    return movers, targets, gains, node_w, capacity


@pytest.mark.parametrize("case", ["hub", "distinct", "ties", "wide", "zero",
                                  "edge"])
@pytest.mark.parametrize("n_pad", [64, 1024, 65536])
def test_accept_sorted_node_targets_match_oracle(n_pad, case):
    """Clustering's sorted acceptance over node-id targets accepts the
    numpy oracle's movers: a hub target with hundreds of movers under a
    binding capacity, all-distinct targets, equal gains (id order decides),
    48-bit gains with exact ties, zero-capacity targets, and a mover of
    target n_pad - 1 at the last gain rank, where its pair key shares the
    sentinel's bits at n_pad 2^16."""
    import jax

    movers, targets, gains, node_w, capacity = _node_accept_case(case, n_pad)
    want = _oracle_accept(movers, targets, gains, node_w, capacity)
    with jax.enable_x64(True):
        got = _sorted_accept(movers, targets, gains, node_w, capacity)
    assert np.array_equal(got, want)
    assert 0 < want.sum() < movers.sum()  # the capacities bind
    if case == "hub":
        hub = np.bincount(targets[movers]).argmax()
        assert want[movers & (targets == hub)].sum() > 1
    elif case == "edge":
        edge = movers & (targets == n_pad - 1)
        assert 0 < want[edge].sum() < edge.sum()


# ------------------------------------------------- stream-level contract

def _stream_cfg(engine, k=4, batch=64):
    return BuffCutConfig(
        k=k, buffer_size=2 * batch, batch_size=batch, d_max=512.0,
        ml=MultilevelConfig(engine=engine),
    )


def _vectorized(g, cfg):
    return buffcut_partition_vectorized(g, cfg, wave=8, chunk=8)


def _pipelined(g, cfg):
    from repro.core.pipeline import _buffcut_partition_pipelined

    return _buffcut_partition_pipelined(g, cfg)


@pytest.mark.parametrize("k", [4, 32, 64])
@pytest.mark.parametrize("driver", ["vectorized", "pipelined"])
def test_stream_driver_identical_blocks(driver, k):
    """The vectorized and the pipelined driver commit identical assignments
    batch after batch when the multilevel engine moves to the device."""
    run = {"vectorized": _vectorized, "pipelined": _pipelined}[driver]
    g = rmat_graph(768, 8, seed=2)
    b_sp, _ = run(g, _stream_cfg("sparse", k=k))
    b_jx, st = run(g, _stream_cfg("jax", k=k))
    assert np.array_equal(b_sp, b_jx)
    assert st.n_batches >= 5
    assert st.ml_time_s > 0.0


def test_pipelined_driver_times_its_vcycles():
    """The pipelined driver's partition worker keeps `ml_time_s` as the
    summed seconds of its V-cycle span, beside the other stage spans."""
    from repro.api import partition

    g = rmat_graph(768, 8, seed=2)
    st = partition(g, driver="buffcut-pipe", engine="jax", k=4, buffer_size=128,
                   batch_size=64, d_max=512.0).stats
    assert st.n_batches >= 5
    assert st.ml_time_s > 0.0
    assert st.ml_time_s == st.stage_s["buffcut.t3.vcycle"]
    assert {"buffcut.t2.block", "buffcut.t2.flush", "buffcut.t3.task_wait",
            "buffcut.t3.batch_model", "buffcut.t3.commit"} <= set(st.stage_s)


def test_jit_cache_bounded_over_stream():
    """Shape bucketing: a 20-batch stream compiles each engine entry point
    at most 3 times (uniform batches share one padded shape; the trailing
    flush may add a second)."""
    import jax

    n, batch, k = 1280, 64, 4
    g = rmat_graph(n, 8, seed=4)
    cfg = _stream_cfg("jax", k=k, batch=batch)
    jax.clear_caches()  # count this stream's compilations, not the session's
    mlj.reset_trace_counts()
    block, stats = buffcut_partition_vectorized(g, cfg, wave=8, chunk=8)
    assert stats.n_batches >= 20  # a 20+-batch stream, mixed full/partial
    assert (block >= 0).all()
    counts = mlj.trace_counts()
    assert counts, "engine never traced — did the jax engine run?"
    assert max(counts.values()) <= 3, counts


def test_stream_accept_counts():
    """A stream whose batches coarsen: `agg_counts` counts a refine call a
    level and `lp_iters` clustering rounds a coarsened level, under the
    `refine.` and `cluster.` keys, and each round's sorted acceptance under
    `accept.sorted`; the blocks are the sparse engine's."""
    def cfg(engine):
        return BuffCutConfig(
            k=4, buffer_size=512, batch_size=256, d_max=512.0,
            ml=MultilevelConfig(engine=engine, coarsen_target=32))

    g = rmat_graph(1024, 8, seed=5)
    b_sp, _ = buffcut_partition_vectorized(g, cfg("sparse"), wave=8, chunk=8)
    mlj.reset_agg_counts()
    b_jx, stats = buffcut_partition_vectorized(g, cfg("jax"), wave=8, chunk=8)
    assert np.array_equal(b_sp, b_jx)
    agg = mlj.agg_counts()
    refine = sum(v for key, v in agg.items() if key.startswith("refine."))
    cluster = sum(v for key, v in agg.items() if key.startswith("cluster."))
    accept = sum(v for key, v in agg.items() if key.startswith("accept."))
    assert refine + cluster + accept == sum(agg.values()), agg
    # every clustering round takes the sorted acceptance
    assert agg["accept.sorted"] == cluster
    # one refine call per level and a clustering round per lp_iters each
    assert refine > stats.n_batches
    assert cluster >= 2 * (refine - stats.n_batches)


def _mesh_vcycles(calls: int) -> None:
    """`calls` V-cycles of the jax engine over successive 256-node row
    batches of a triangulated 32 x 32 grid, each committed before the next
    (so later batches carry pinned aux nodes), coarsening to 32 free nodes."""
    g = grid_mesh_graph(32)
    k = 4
    p = _params(g, k)
    block = np.full(g.n, -1, dtype=np.int64)
    cfg = MultilevelConfig(engine="jax", coarsen_target=32)
    for b in range(calls):
        batch = np.arange(b * 256, (b + 1) * 256)
        model = build_batch_model(g, batch, block, k)
        loads = np.bincount(block[block >= 0], minlength=k).astype(np.float64)
        out = multilevel_partition(model.graph, model.pinned_block, p, loads, cfg)
        block[batch] = out[:batch.size]


@pytest.mark.parametrize("mode", ["ell", "dense"])
def test_agg_counts_level0_refine(mode, monkeypatch):
    """`refine.ell` counts the level-0 refine calls, one a V-cycle, where
    the ELL tiles are taken, and nothing where they are not; the coarse
    levels' refine calls and every clustering round count under the mode
    they ran in."""
    monkeypatch.setattr(mlj, "MODE_OVERRIDE", mode)
    mlj.reset_agg_counts()
    _mesh_vcycles(3)
    agg = mlj.agg_counts()
    assert agg.get("refine.ell", 0) == (3 if mode == "ell" else 0)
    assert agg["refine.dense"] > 0  # the coarse levels' refine calls
    assert agg[f"cluster.{mode}"] > 0  # level 0's clustering rounds
    assert set(agg) <= {f"{phase}.{m}" for phase in ("refine", "cluster")
                        for m in ("ell", "dense", "sort")} | {"accept.sorted"}


def test_reset_agg_counts():
    mlj._count_agg("refine.ell")
    assert mlj.agg_counts()["refine.ell"] > 0
    mlj.reset_agg_counts()
    assert mlj.agg_counts() == {}


@pytest.mark.parametrize("mode", ["ell", "dense"])
def test_pack_ell_span_once_per_batch(mode, monkeypatch):
    """`vcycle.pack_ell` is entered once a V-cycle, inside `vcycle.pack`,
    where the ELL tiles are packed, and never where they are not."""
    entered = []
    real = mlj.span

    class recording(real):
        def __enter__(self):
            entered.append(self._name)
            return super().__enter__()

    monkeypatch.setattr(mlj, "MODE_OVERRIDE", mode)
    monkeypatch.setattr(mlj, "span", recording)
    _mesh_vcycles(3)
    assert entered.count("vcycle.pack") == 3
    assert entered.count("vcycle.pack_ell") == (3 if mode == "ell" else 0)
    if mode == "ell":
        packs = [i for i, name in enumerate(entered) if name == "vcycle.pack"]
        ells = [i for i, name in enumerate(entered) if name == "vcycle.pack_ell"]
        assert all(a < b for a, b in zip(packs, ells))


@pytest.mark.parametrize("n_pad", [1 << 10, 1 << 16, 1 << 17])
def test_sort_aggregation_matches_numpy(n_pad):
    """The sort aggregation's packed (src, label) keys: u32 up to 2^16
    nodes — where the key (n_pad-1, n_pad-1) shares the sentinel's bits —
    and u64 beyond (δ = 65,536 batches), against a numpy group-by."""
    import jax
    import jax.numpy as jnp

    rng = np.random.default_rng(n_pad)
    e = 4096
    src = np.sort(rng.integers(n_pad - 64, n_pad, e)).astype(np.int32)
    dst = rng.integers(n_pad - 64, n_pad, e).astype(np.int32)
    w = rng.integers(1, 4, e).astype(np.float64)
    src[-16:] = dst[-16:] = n_pad  # padding entries
    labels = np.where(rng.random(n_pad) < 0.5, n_pad - 1,
                      rng.integers(n_pad - 8, n_pad, n_pad)).astype(np.int32)
    with jax.enable_x64(True):
        cur, best_w, best_lab = (np.asarray(a) for a in mlj._agg_sort(
            jnp.asarray(src), jnp.asarray(dst), jnp.asarray(w),
            jnp.asarray(labels), jnp.asarray(labels), None, n_pad))
    totals: dict = {}
    for s, d, x in zip(src[:-16], dst[:-16], w[:-16]):
        key = (int(s), int(labels[d]))
        totals[key] = totals.get(key, 0.0) + x
    for v in np.unique(src[:-16]):
        own = {lab: t for (s, lab), t in totals.items() if s == v}
        assert cur[v] == own.get(int(labels[v]), 0.0)
        cand = {lab: t for lab, t in own.items() if lab != labels[v]}
        if cand:
            top = max(cand.values())
            assert best_w[v] == top
            assert best_lab[v] == min(lab for lab, t in cand.items()
                                      if t == top)
        else:
            assert best_w[v] == -np.inf
