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
from repro.core.multilevel import MultilevelConfig, multilevel_partition
from repro.core.vector_stream import buffcut_partition_vectorized
from repro.graphs import (
    apply_order,
    bfs_order,
    konect_order,
    rmat_graph,
    source_order,
)
from repro.graphs.csr import CSRGraph, bucket_size


def _params(g, k, eps=0.1):
    return FennelParams(k=k, n_total=float(g.node_w.sum()),
                        m_total=g.total_edge_weight(), eps=eps)


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

@pytest.mark.parametrize("mode", ["dense", "sort", "ell"])
def test_jax_modes_match_sparse_oracle(mode):
    """All three aggregation modes produce the sparse oracle's labels on a
    batch-model graph with pinned aux nodes and preexisting loads."""
    rng = np.random.default_rng(0)
    g = rmat_graph(512, 8, seed=3)
    k = 8
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


@pytest.mark.parametrize("ordering", ["natural", "bfs", "adversarial"])
def test_jax_exact_labels_across_orderings(ordering):
    order = {"natural": source_order, "bfs": bfs_order,
             "adversarial": konect_order}[ordering]
    base = rmat_graph(384, 8, seed=11)
    g = apply_order(base, order(base))
    k = 6
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


# ------------------------------------------------- stream-level contract

def _stream_cfg(engine, k=4, batch=64):
    return BuffCutConfig(
        k=k, buffer_size=2 * batch, batch_size=batch, d_max=512.0,
        ml=MultilevelConfig(engine=engine),
    )


def test_stream_driver_identical_blocks():
    """The vectorized driver commits identical assignments batch after
    batch when the multilevel engine moves to the device."""
    g = rmat_graph(768, 8, seed=2)
    b_sp, _ = buffcut_partition_vectorized(g, _stream_cfg("sparse"),
                                           wave=8, chunk=8)
    b_jx, st = buffcut_partition_vectorized(g, _stream_cfg("jax"),
                                            wave=8, chunk=8)
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


def test_agg_autotune_identical_labels_and_converges():
    """cfg.ml.agg_autotune explores both aggregation modes per (phase,
    shape) then commits to the measured-fastest — exploration must never
    change a label, and after warmup every key has a decision."""
    g = rmat_graph(768, 8, seed=9)
    k = 6
    p = _params(g, k)
    pinned = np.full(g.n, -1, dtype=np.int64)
    ref = multilevel_partition(g, pinned, p, np.zeros(k),
                               MultilevelConfig(engine="jax"))
    mlj.reset_agg_tuner()
    try:
        cfg = MultilevelConfig(engine="jax", agg_autotune=True)
        # warmup + timed samples for both candidates, then the decided mode
        for _ in range(2 * (mlj._AggTuner.WARMUP + mlj._AggTuner.TIMED) + 1):
            got = multilevel_partition(g, pinned, p, np.zeros(k), cfg)
            assert np.array_equal(ref, got)  # exploration never leaks out
        decisions = mlj.agg_decisions()
        assert decisions, "tuner never converged to a decision"
        assert set(decisions.values()) <= {"dense", "sort"}
        for phase, n_pad, l_pad in decisions:
            assert phase in ("cluster", "refine")
            assert n_pad > 0 and l_pad > 0
    finally:
        mlj.reset_agg_tuner()


def test_agg_autotune_off_by_default():
    """MultilevelConfig defaults keep the tuner out of the loop (so jit
    compilation counts stay deterministic for the cache-bound test)."""
    assert MultilevelConfig().agg_autotune is False
    mlj.reset_agg_tuner()
    g = rmat_graph(256, 6, seed=2)
    p = _params(g, 4)
    pinned = np.full(g.n, -1, dtype=np.int64)
    multilevel_partition(g, pinned, p, np.zeros(4),
                         MultilevelConfig(engine="jax"))
    assert mlj.agg_decisions() == {}
    assert not mlj._TUNER._samples


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
