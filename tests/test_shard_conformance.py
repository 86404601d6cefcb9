"""Cross-shard conformance: the sharded multi-worker driver against the
sequential oracle (ISSUE 8 acceptance).

Pins, per DESIGN.md §13's determinism contract:
* W=1 sharded == sequential driver, bit-identical labels and stats;
* W ∈ {2, 4} × {text, packed} × {sparse, jax} deterministic across runs
  (round-indexed load-sync barrier — thread scheduling cannot leak in);
* thread and process backends produce identical labels;
* the merged `IncrementalCut` exactly equals an offline `edge_cut`
  recomputation, and the merged `block_loads` are exact;
* post-restream (priority) cut within a pinned tolerance of single-worker;
* `SharedLoads` property: any interleaving of per-worker delta publishes
  converges to the exact pinned-order global loads (hypothesis).
"""
import threading

import numpy as np
import pytest
from hypothesis import given, settings, strategies as st

from repro.api import DriverConfig, partition
from repro.core import BuffCutConfig, buffcut_partition, edge_cut
from repro.core.multilevel import MultilevelConfig
from repro.distributed.shard_driver import SharedLoads, shard_partition
from repro.graphs import DiskNodeStream, rmat_graph, write_metis, write_packed

WORKER_COUNTS = (2, 4)


@pytest.fixture(scope="module")
def base_graph():
    return rmat_graph(128, 5, seed=7)


@pytest.fixture(scope="module")
def disk_files(base_graph, tmp_path_factory):
    tmp = tmp_path_factory.mktemp("shard-conformance")
    text, packed = str(tmp / "g.graph"), str(tmp / "g.bcsr")
    write_metis(base_graph, text)
    write_packed(base_graph, packed)
    return {"text": text, "packed": packed}


def _cfg(engine: str = "sparse") -> BuffCutConfig:
    return BuffCutConfig(
        k=4, buffer_size=24, batch_size=12, d_max=48, score="haa",
        collect_stats=True, ml=MultilevelConfig(engine=engine),
    )


# ------------------------------------------------------------ W=1 identity


def test_w1_bit_identical_to_sequential(base_graph):
    """One shard *is* the sequential driver — same labels, same stats."""
    cfg = _cfg()
    b_seq, s_seq = buffcut_partition(base_graph, cfg)
    b_sh, s_sh, info = shard_partition(base_graph, cfg, workers=1)
    assert np.array_equal(b_seq, b_sh)
    assert s_seq.cut_weight == s_sh.cut_weight
    assert s_seq.balance == s_sh.balance
    assert s_seq.n_batches == s_sh.n_batches
    assert s_seq.ier_per_batch == s_sh.ier_per_batch
    assert s_seq.block_loads == s_sh.block_loads
    assert info["effective_workers"] == 1
    assert info["cut_cross_shard"] == 0.0


def test_w1_disk_bit_identical(disk_files):
    cfg = _cfg()
    b_seq, s_seq = buffcut_partition(DiskNodeStream(disk_files["packed"]), cfg)
    b_sh, s_sh, _ = shard_partition(
        DiskNodeStream(disk_files["packed"]), cfg, workers=1
    )
    assert np.array_equal(b_seq, b_sh)
    assert s_seq.cut_weight == s_sh.cut_weight


def test_more_workers_than_nodes():
    """W > n clamps to single-node shards; every label still lands."""
    g = rmat_graph(6, 3, seed=2)  # rmat rounds n up to a power of two
    cfg = BuffCutConfig(k=2, buffer_size=4, batch_size=2, ml=MultilevelConfig(engine="sparse"))
    labels, stats, info = shard_partition(g, cfg, workers=2 * g.n, load_sync_every=1)
    assert info["effective_workers"] == g.n
    assert labels.shape == (g.n,) and (labels >= 0).all() and (labels < 2).all()
    assert stats.cut_weight == edge_cut(g, labels)


# --------------------------------------- determinism + exactness, the matrix


@pytest.mark.parametrize("engine", ["sparse", "jax"])
@pytest.mark.parametrize("fmt", ["text", "packed"])
@pytest.mark.parametrize("workers", WORKER_COUNTS)
def test_sharded_deterministic_and_exact(workers, fmt, engine, base_graph, disk_files):
    """Same source, same W, same sync schedule → identical labels across
    runs; merged cut and loads exactly match an offline recomputation."""
    cfg = _cfg(engine)
    runs = [
        shard_partition(
            DiskNodeStream(disk_files[fmt]), cfg, workers=workers, load_sync_every=2
        )
        for _ in range(2)
    ]
    (b1, s1, i1), (b2, s2, _) = runs
    assert np.array_equal(b1, b2)
    assert s1.cut_weight == s2.cut_weight
    assert s1.block_loads == s2.block_loads
    # exactness: the merged IncrementalCut equals compute-from-scratch
    assert s1.cut_weight == edge_cut(base_graph, b1)
    ref_loads = np.zeros(cfg.k)
    np.add.at(ref_loads, b1, base_graph.node_w.astype(np.float64))
    assert np.array_equal(np.asarray(s1.block_loads), ref_loads)
    assert i1["cut_intra_shard"] + i1["cut_cross_shard"] == s1.cut_weight
    assert len(i1["per_worker"]) == workers
    assert all(r >= 1 for r in i1["sync_rounds"])


@pytest.mark.parametrize("workers", WORKER_COUNTS)
def test_thread_process_backend_parity(workers, base_graph):
    """Both backends run the same barrier logic → identical labels."""
    cfg = _cfg()
    bt, s_t, _ = shard_partition(
        base_graph, cfg, workers=workers, load_sync_every=2, backend="thread"
    )
    bp, s_p, info = shard_partition(
        base_graph, cfg, workers=workers, load_sync_every=2, backend="process"
    )
    assert np.array_equal(bt, bp)
    assert s_t.cut_weight == s_p.cut_weight
    assert s_t.block_loads == s_p.block_loads
    assert info["backend"] == "process"


@pytest.mark.parametrize("engine", ["jax", "ell", "auto"])
def test_process_backend_rejects_jax_engine(engine, base_graph, monkeypatch):
    """Every engine that runs jax is refused before anything forks; `auto`
    runs it wherever the kernels are on, as on a TPU."""
    from repro.kernels import ops

    monkeypatch.setattr(ops, "USE_KERNELS_DEFAULT", True)
    with pytest.raises(ValueError, match="fork"):
        shard_partition(base_graph, _cfg(engine), workers=2, backend="process")


@pytest.mark.parametrize("backend", ["thread", "process"])
def test_slow_shard_is_not_cut_short(backend, base_graph, monkeypatch):
    """The join timeout only bounds workers of an aborted pool: a healthy
    shard that takes longer (a cold jax engine compiles for minutes) still
    finishes, with the same labels."""
    import repro.distributed.shard_driver as sd

    cfg = _cfg()
    want, _, _ = shard_partition(base_graph, cfg, workers=2, backend=backend)
    monkeypatch.setattr(sd, "_JOIN_TIMEOUT_S", 1e-3)
    got, _, _ = shard_partition(base_graph, cfg, workers=2, backend=backend)
    assert np.array_equal(want, got)


def test_disk_matches_memory_sharded(base_graph, disk_files):
    """The sharded driver cannot tell where the stream came from either."""
    cfg = _cfg()
    bm, sm, _ = shard_partition(base_graph, cfg, workers=2, load_sync_every=2)
    bd, sd, _ = shard_partition(
        DiskNodeStream(disk_files["packed"]), cfg, workers=2, load_sync_every=2
    )
    assert np.array_equal(bm, bd)
    assert sm.cut_weight == sd.cut_weight
    assert sm.block_loads == sd.block_loads


# ----------------------------------------------------- restream reconcile


def test_post_restream_cut_within_tolerance(base_graph):
    """The reconcile pass recovers sharded quality to within 1.15x of the
    single-worker post-restream cut (pinned; deterministic inputs)."""
    kw = dict(k=4, buffer_size=24, batch_size=12, d_max=48, engine="sparse",
              restream_passes=1, restream_order="priority", prefetch_batches=0)
    r1 = partition(base_graph, **kw, workers=1)
    r4 = partition(base_graph, **kw, workers=4, load_sync_every=2)
    assert r4.stats.cut_weight <= 1.15 * r1.stats.cut_weight
    # the reconcile trace: pass 1 starts from the recorded pre-reconcile cut
    pre = r4.provenance["sharded"]["cut_pre_reconcile"]
    trace = r4.provenance["restream"]["passes"][0]
    assert trace["cut_before"] == pre
    assert trace["cut_after"] == r4.stats.cut_weight
    # restream seeding consumed the *exact* merged cut: final must agree
    # with an offline recomputation
    assert r4.stats.cut_weight == edge_cut(base_graph, r4.labels)


def test_api_rejects_shard_incapable_driver(base_graph):
    with pytest.raises(ValueError, match="does not support sharded"):
        partition(base_graph, k=4, driver="fennel", workers=2)


def test_config_serialization_round_trip():
    dc = DriverConfig.create(
        k=4, workers=4, load_sync_every=3, shard_backend="process"
    )
    rt = DriverConfig.from_json(dc.to_json())
    assert (rt.workers, rt.load_sync_every, rt.shard_backend) == (4, 3, "process")


# ------------------------------------------------------ SharedLoads property


@given(
    st.lists(
        st.tuples(
            st.integers(min_value=0, max_value=2),
            st.lists(
                st.floats(min_value=-8, max_value=8, allow_nan=False),
                min_size=3, max_size=3,
            ),
        ),
        min_size=0, max_size=24,
    )
)
@settings(max_examples=60, deadline=None)
def test_shared_loads_converges_exact(events):
    """Any sequence of per-worker delta publishes converges to the exact
    global loads: per-worker cumulative sums in publish order, workers
    summed in index order — bit-reproducible, no lost updates."""
    W, k = 3, 3
    sl = SharedLoads(W, k)
    ref = [np.zeros(k) for _ in range(W)]
    for w, delta in events:
        sl.publish(w, delta)
        ref[w] = ref[w] + np.asarray(delta, dtype=np.float64)
    for w in range(W):
        sl.finish(w)
    expect = np.zeros(k)
    for w in range(W):
        expect = expect + ref[w]
    assert np.array_equal(sl.total(), expect)


@given(st.integers(min_value=1, max_value=4), st.integers(min_value=1, max_value=5))
@settings(max_examples=25, deadline=None)
def test_shared_loads_threaded_interleaving(workers, rounds):
    """Concurrent workers publishing through the real barrier: every
    `others_at(w, r)` read is the pinned-order sum of the other workers'
    round-r cumulative loads, regardless of thread interleaving."""
    k = 2
    sl = SharedLoads(workers, k)
    seen: list = [None] * workers
    # worker w publishes delta [w+1, 0] each round: cum at round r is (r+1)*(w+1)
    def run(w):
        out = []
        for r in range(rounds):
            sl.publish(w, np.array([w + 1.0, 0.0]))
            out.append(sl.others_at(w, r))
        sl.finish(w)
        seen[w] = out

    threads = [threading.Thread(target=run, args=(w,)) for w in range(workers)]
    for t in threads:
        t.start()
    for t in threads:
        t.join(timeout=30.0)
    assert all(not t.is_alive() for t in threads)
    for w in range(workers):
        for r in range(rounds):
            expect = np.zeros(k)
            for o in range(workers):
                if o != w:
                    expect = expect + np.array([(r + 1.0) * (o + 1), 0.0])
            assert np.array_equal(seen[w][r], expect)
    total = sl.total()
    assert total[0] == sum(rounds * (w + 1.0) for w in range(workers))


def test_shared_loads_validation():
    sl = SharedLoads(2, 3)
    with pytest.raises(ValueError, match="worker index"):
        sl.publish(2, np.zeros(3))
    with pytest.raises(ValueError, match="shape"):
        sl.publish(0, np.zeros(4))
    sl.finish(0)
    with pytest.raises(ValueError, match="already finished"):
        sl.publish(0, np.zeros(3))
    with pytest.raises(ValueError, match="have not finished"):
        sl.total()
