"""API layer contract (ISSUE 4): the unified front door.

Pins (a) bit-identity between every legacy entry point and the API path
across drivers × multilevel engines × source kinds, (b) registry
completeness — every registered name runs and returns a valid
`PartitionResult`, (c) config + result JSON round-trips (golden), (d) the
validation / memory-only error contract, and (e) the `python -m repro` CLI
in-process.
"""
import json

import numpy as np
import pytest

from repro.graphs import (
    DiskNodeStream,
    NodeStream,
    apply_order,
    bfs_order,
    rmat_graph,
    write_metis,
    write_packed,
)
from repro.core import (
    BuffCutConfig,
    CuttanaConfig,
    MultilevelConfig,
    PipelineConfig,
    VectorizedConfig,
    buffcut_partition,
    buffcut_partition_pipelined,
    buffcut_partition_vectorized,
    heistream_partition,
)
from repro.api import (
    DriverConfig,
    PartitionResult,
    PartitionerSpec,
    list_partitioners,
    partition,
    register_partitioner,
    resolve_source,
)
from repro.api import registry as registry_mod
from repro.api.cli import main as cli_main

ALL_NAMES = (
    "buffcut", "buffcut-vec", "buffcut-pipe", "heistream", "cuttana",
    "fennel", "ldg",
)

LEGACY = {
    "buffcut": lambda s, cfg: buffcut_partition(s, cfg),
    "buffcut-vec": lambda s, cfg: buffcut_partition_vectorized(s, cfg, wave=1, chunk=1),
    "buffcut-pipe": lambda s, cfg: buffcut_partition_pipelined(s, cfg),
}


@pytest.fixture(scope="module")
def base_graph():
    return rmat_graph(128, 5, seed=7)


@pytest.fixture(scope="module")
def files(base_graph, tmp_path_factory):
    tmp = tmp_path_factory.mktemp("api")
    packed = str(tmp / "g.bcsr")
    write_packed(base_graph, packed)
    metis = str(tmp / "g.metis")
    write_metis(base_graph, metis)
    return {"binary": packed, "text": metis}


def _cfg(engine: str = "sparse") -> BuffCutConfig:
    # same shapes as tests/test_stream_conformance.py: shares the jit cache
    return BuffCutConfig(
        k=4, buffer_size=24, batch_size=12, d_max=48, score="haa",
        collect_stats=True, ml=MultilevelConfig(engine=engine),
    )


def _source(kind: str, base_graph, files):
    return base_graph if kind == "graph" else DiskNodeStream(files[kind])


# ---------------------------------------------------- shim == API identity


@pytest.mark.parametrize("source_kind", ["graph", "text", "binary"])
@pytest.mark.parametrize("engine", ["sparse", "jax"])
@pytest.mark.parametrize("driver", sorted(LEGACY))
def test_legacy_shim_bit_identical_to_api(driver, engine, source_kind, base_graph, files):
    """The deprecation shims and the API produce the same labels, bit for
    bit, on every driver × engine × source kind."""
    cfg = _cfg(engine)
    with pytest.warns(DeprecationWarning):
        legacy, _ = LEGACY[driver](_source(source_kind, base_graph, files), cfg)
    res = partition(
        _source(source_kind, base_graph, files),
        DriverConfig(driver=driver, buffcut=cfg),
    )
    assert res.provenance["driver"] == driver
    assert np.array_equal(legacy, res.labels)


def test_vectorized_kwargs_fold_into_config(base_graph):
    """Loose wave/chunk kwargs and VectorizedConfig are the same path."""
    cfg = _cfg()
    with pytest.warns(DeprecationWarning):
        legacy, _ = buffcut_partition_vectorized(base_graph, cfg, wave=4, chunk=8)
    res = partition(base_graph, cfg, driver="buffcut-vec", wave=4, chunk=8)
    assert np.array_equal(legacy, res.labels)


# ------------------------------------------------------------- registry


def test_registry_covers_all_seven():
    assert set(ALL_NAMES) <= set(list_partitioners())


@pytest.mark.parametrize("name", ALL_NAMES)
def test_every_registered_name_runs(name, base_graph):
    """Registry completeness: each name yields a valid PartitionResult."""
    res = partition(base_graph, _cfg(), driver=name)
    assert isinstance(res, PartitionResult)
    assert res.labels.shape == (base_graph.n,)
    assert res.labels.min() >= 0 and res.labels.max() < res.k == 4
    m = res.metrics()
    assert 0.0 <= m["cut_ratio"] <= 1.0
    assert m["balance"] >= 1.0 - 1e-9


def test_aliases_resolve():
    for alias, canonical in (
        ("sequential", "buffcut"),
        ("vectorized", "buffcut-vec"),
        ("pipelined", "buffcut-pipe"),
        ("buffcut-par", "buffcut-pipe"),
    ):
        assert registry_mod.get_partitioner(alias).name == canonical


def test_register_custom_partitioner(base_graph):
    spec = PartitionerSpec(
        name="api-test-zero",
        streaming=True,
        description="test-only",
        run=lambda src, dc: (np.zeros(src.stream.n, dtype=np.int64), None),
    )
    register_partitioner(spec)
    try:
        res = partition(base_graph, _cfg(), driver="api-test-zero")
        assert (res.labels == 0).all()
        with pytest.raises(ValueError, match="already registered"):
            register_partitioner(spec)
    finally:
        registry_mod._REGISTRY.pop("api-test-zero", None)


def test_overwrite_reclaims_alias(base_graph):
    """overwrite=True must also reclaim names that were aliases, so the
    replacement actually resolves."""
    saved_registry = dict(registry_mod._REGISTRY)
    saved_aliases = dict(registry_mod._ALIASES)
    try:
        spec = PartitionerSpec(
            name="vectorized",  # currently an alias of buffcut-vec
            streaming=True,
            run=lambda src, dc: (np.ones(src.stream.n, dtype=np.int64), None),
        )
        register_partitioner(spec, overwrite=True)
        assert registry_mod.get_partitioner("vectorized") is spec
        res = partition(base_graph, _cfg(), driver="vectorized")
        assert (res.labels == 1).all()
    finally:
        registry_mod._REGISTRY.clear()
        registry_mod._REGISTRY.update(saved_registry)
        registry_mod._ALIASES.clear()
        registry_mod._ALIASES.update(saved_aliases)


def test_foreign_stream_with_packed_path_materializes(base_graph, files):
    """A user stream exposing a `path` to a packed file must materialize via
    the packed reader (format is sniffed, not guessed from kind)."""
    s = _ForeignStream(base_graph)
    s.path = files["binary"]
    src = resolve_source(s)
    assert src.kind == "stream" and src.path == files["binary"]
    g = src.materialize()
    assert np.array_equal(g.indptr, base_graph.indptr)
    assert np.array_equal(g.indices, base_graph.indices)


def test_unknown_driver_names_the_registry(base_graph):
    with pytest.raises(KeyError, match="buffcut"):
        partition(base_graph, _cfg(), driver="does-not-exist")


def test_restream_post_pass_composes(base_graph):
    """restream_passes=N is exactly N manual restream() passes."""
    from repro.core import restream

    cfg = _cfg()
    r0 = partition(base_graph, cfg)
    r1 = partition(base_graph, cfg, restream_passes=1)
    assert r1.provenance["restream_passes"] == 1
    assert np.array_equal(r1.labels, restream(base_graph, r0.labels, cfg, 1))


# ------------------------------------------------------- source resolution


def test_resolve_source_kinds(base_graph, files):
    assert resolve_source(base_graph).kind == "graph"
    assert resolve_source(files["binary"]).kind == "packed"
    assert resolve_source(files["text"]).kind == "metis"
    assert resolve_source("gen:ring:n=16").kind == "generated"
    assert resolve_source(NodeStream(base_graph)).graph is base_graph
    ds = resolve_source(DiskNodeStream(files["binary"]))
    assert ds.kind == "stream" and ds.graph is None
    with pytest.raises(ValueError, match="family"):
        resolve_source("gen:nope:n=4")
    with pytest.raises(FileNotFoundError):
        resolve_source("no/such/file.bcsr")
    with pytest.raises(TypeError):
        resolve_source(42)


def test_all_source_kinds_agree(base_graph, files):
    cfg = _cfg()
    ref = partition(base_graph, cfg).labels
    for source in (
        files["text"],
        files["binary"],
        NodeStream(base_graph),
        DiskNodeStream(files["binary"]),
        "gen:rmat:n=128,avg_degree=5,seed=7",
    ):
        assert np.array_equal(ref, partition(source, cfg).labels), source


@pytest.mark.parametrize("name", ["heistream", "cuttana", "fennel", "ldg"])
def test_memory_only_rejects_disk_stream(name, files):
    with pytest.raises(TypeError, match="memory-only"):
        partition(files["binary"], _cfg(), driver=name)


def test_restream_on_disk_stream_matches_memory(base_graph, files):
    """ISSUE 5 tentpole: restream_passes works out-of-core and the labels
    are bit-identical to the in-memory restream path."""
    cfg = _cfg()
    r_mem = partition(base_graph, cfg, restream_passes=2)
    r_disk = partition(files["binary"], cfg, restream_passes=2)
    assert np.array_equal(r_mem.labels, r_disk.labels)
    assert r_mem.stats.cut_weight == r_disk.stats.cut_weight
    assert r_mem.stats.balance == r_disk.stats.balance
    # stats refresh: the streamed cut matches an offline recompute on the
    # *refined* labels (regression: it used to describe pass 1's labels)
    from repro.core import balance as balance_metric, edge_cut

    assert r_disk.stats.cut_weight == pytest.approx(edge_cut(base_graph, r_disk.labels))
    assert r_disk.stats.balance == pytest.approx(
        balance_metric(base_graph, r_disk.labels, r_disk.k)
    )
    # no resident graph on the disk path: cut_ratio comes from the stats
    assert r_disk.graph is None
    assert r_disk.cut_weight == r_mem.cut_weight


def test_restream_stats_refresh_after_refinement(base_graph):
    """Regression (ISSUE 5 satellite): StreamStats.cut_weight/balance and
    the serialized result must reflect the post-restream labels."""
    from repro.core import balance as balance_metric, edge_cut

    cfg = _cfg()
    res = partition(base_graph, cfg, restream_passes=2, restream_order="priority")
    assert res.stats.cut_weight == pytest.approx(edge_cut(base_graph, res.labels))
    assert res.stats.balance == pytest.approx(
        balance_metric(base_graph, res.labels, res.k)
    )
    blob = json.loads(res.to_json())
    assert blob["stats"]["cut_weight"] == pytest.approx(res.stats.cut_weight)
    log = blob["provenance"]["restream"]
    assert log["order"] == "priority" and len(log["passes"]) == 2
    assert log["passes"][-1]["cut_after"] == pytest.approx(res.stats.cut_weight)
    # canonical-totals parity (ISSUE 5 satellite): restream params came from
    # the same stream totals as the first-pass FennelParams
    assert log["n_total"] == blob["provenance"]["n_total"]
    assert log["m_total"] == blob["provenance"]["m_total"]


def test_restream_order_knob_routes_and_validates(base_graph):
    dc = DriverConfig.create(k=4, restream_passes=1, restream_order="priority")
    assert dc.restream_order == "priority"
    assert DriverConfig.from_json(dc.to_json()).restream_order == "priority"
    with pytest.raises(ValueError, match="restream_order"):
        DriverConfig.create(restream_order="bogus")


def test_materialize_unlocks_memory_only(base_graph, files):
    src = resolve_source(files["binary"])
    src.materialize()
    res = partition(src, _cfg(), driver="heistream")
    with pytest.warns(DeprecationWarning):
        ref, _ = heistream_partition(base_graph, _cfg())
    assert np.array_equal(res.labels, ref)


# ------------------------------------------------------------- orderings


def test_ordering_labels_in_input_numbering(base_graph):
    """ordering="bfs" equals the manual apply_order dance, with labels
    mapped back to the input's node ids."""
    cfg = _cfg()
    perm = bfs_order(base_graph)
    with pytest.warns(DeprecationWarning):
        ref, _ = buffcut_partition(apply_order(base_graph, perm), cfg)
    expected = np.empty_like(ref)
    expected[perm] = ref
    res = partition(base_graph, cfg, ordering="bfs")
    assert np.array_equal(res.labels, expected)
    # the cut is permutation-invariant: graph metric == streaming metric
    assert res.cut_weight == pytest.approx(res.stats.cut_weight)


class _ForeignStream:
    """A path-less, graph-less NodeStreamBase implementation (user code)."""

    def __new__(cls, g):
        from repro.graphs import NodeStreamBase

        class Impl(NodeStreamBase):
            def __init__(self, g_):
                self._inner = NodeStream(g_)
                self.n, self.m = g_.n, g_.m
                self.has_edge_w = self._inner.has_edge_w
                self.has_node_w = self._inner.has_node_w

            @property
            def n_total(self):
                return self._inner.n_total

            @property
            def m_total(self):
                return self._inner.m_total

            def __iter__(self):
                return iter(self._inner)

        return Impl(g)


def test_ordering_on_pathless_stream_materializes(base_graph):
    """A foreign stream with no file behind it still honors orderings (via
    materialization) instead of crashing in permute_to_disk."""
    cfg = _cfg()
    ref = partition(base_graph, cfg, ordering="random", order_seed=5)
    res = partition(_ForeignStream(base_graph), cfg, ordering="random", order_seed=5)
    assert np.array_equal(ref.labels, res.labels)


def test_ordering_preserves_io_chunk(files):
    """Realizing an ordering on disk keeps the source's tuned read-ahead
    window (the peak-resident-memory knob)."""
    from repro.api import DriverConfig, _realize_ordering

    src = resolve_source(DiskNodeStream(files["binary"], io_chunk_bytes=4096))
    dc = DriverConfig.create(k=4, ordering="random", order_seed=1)
    run_src, perm, tmp = _realize_ordering(src, dc)
    try:
        assert run_src.stream.io_chunk_bytes == 4096
    finally:
        tmp.cleanup()


def test_disk_random_ordering_matches_memory(base_graph, files):
    """Disk sources realize orderings via the on-disk permute pass and
    stay bit-identical to the in-memory apply_order path."""
    cfg = _cfg()
    a = partition(files["binary"], cfg, ordering="random", order_seed=3)
    b = partition(base_graph, cfg, ordering="random", order_seed=3)
    assert np.array_equal(a.labels, b.labels)


# ----------------------------------------------------- config validation


def test_config_validation_errors():
    with pytest.raises(ValueError, match="k must be >= 2"):
        BuffCutConfig(k=1)
    with pytest.raises(ValueError, match="eps"):
        BuffCutConfig(k=4, eps=0.0)
    with pytest.raises(ValueError, match="batch_size <= buffer_size"):
        BuffCutConfig(k=4, buffer_size=8, batch_size=16)
    with pytest.raises(ValueError, match="unknown score"):
        BuffCutConfig(k=4, score="bogus")
    with pytest.raises(ValueError, match="engine"):
        MultilevelConfig(engine="cuda")
    with pytest.raises(ValueError, match="wave"):
        VectorizedConfig(wave=0)
    with pytest.raises(ValueError, match="queue_depth"):
        PipelineConfig(queue_depth=0)
    with pytest.raises(ValueError, match="ordering"):
        DriverConfig(ordering="zigzag")
    with pytest.raises(ValueError, match="subpart_ratio"):
        CuttanaConfig(k=4, subpart_ratio=0)
    with pytest.raises(TypeError, match="unknown partition option"):
        DriverConfig.create(k=4, not_a_knob=1)


def test_q1_degeneracy_allowed():
    """buffer_size=1 (the paper's Q=1 -> HeiStream degeneracy) accepts any
    batch_size."""
    BuffCutConfig(k=4, buffer_size=1, batch_size=64)


# --------------------------------------------------------- serialization


def test_buffcut_config_json_roundtrip_golden():
    cfg = BuffCutConfig(
        k=8, eps=0.05, buffer_size=64, batch_size=32, d_max=100.0,
        score="cbs", disc_factor=500, gamma=1.25,
        ml=MultilevelConfig(engine="jax", seed=3), collect_stats=True,
    )
    assert BuffCutConfig.from_json(cfg.to_json()) == cfg
    assert cfg.to_dict() == {
        "k": 8, "eps": 0.05, "buffer_size": 64, "batch_size": 32,
        "d_max": 100.0, "score": "cbs", "disc_factor": 500, "gamma": 1.25,
        "ml": {
            "coarsen_target": 160, "max_levels": 10, "lp_iters": 2,
            "refine_rounds": 3, "min_shrink": 0.95, "seed": 3,
            "engine": "jax",
        },
        "collect_stats": True,
    }


def test_multilevel_config_json_roundtrip():
    ml = MultilevelConfig(coarsen_target=80, engine="ell", seed=9)
    assert MultilevelConfig.from_dict(ml.to_dict()) == ml


def test_driver_config_json_roundtrip():
    dc = DriverConfig.create(
        driver="cuttana", k=6, subpart_ratio=8, wave=4, queue_depth=2,
        ordering="bfs", engine="sparse",
    )
    dc2 = DriverConfig.from_json(dc.to_json())
    assert dc2 == dc
    assert isinstance(dc2.buffcut, CuttanaConfig)
    assert dc2.buffcut.subpart_ratio == 8
    assert dc2.vectorized.wave == 4 and dc2.pipeline.queue_depth == 2


def test_result_json_roundtrip(base_graph, tmp_path):
    res = partition(base_graph, _cfg(), driver="buffcut")
    path = str(tmp_path / "res.json")
    text = res.to_json(path)
    for r2 in (PartitionResult.from_json(text), PartitionResult.from_json(path)):
        assert np.array_equal(r2.labels, res.labels)
        assert r2.k == res.k
        assert r2.cut_ratio == pytest.approx(res.cut_ratio)
        assert r2.balance == pytest.approx(res.balance)
        assert r2.ier == pytest.approx(res.ier)
        assert r2.provenance == res.provenance
        assert r2.stats.n_batches == res.stats.n_batches
        assert r2.stats.cut_weight == res.stats.cut_weight
    # serialization is a fixed point
    assert PartitionResult.from_json(text).to_json() == text


def test_result_metrics_without_graph(files):
    """Out-of-core: quality metrics come from the streaming-measured stats,
    no resident graph needed."""
    res = partition(files["binary"], _cfg(), driver="buffcut")
    assert res.graph is None
    assert res.cut_weight == res.stats.cut_weight
    assert res.balance == res.stats.balance
    assert 0.0 < res.cut_ratio < 1.0


# ------------------------------------------------------------------ CLI


def test_cli_partition_json(files, tmp_path, capsys):
    out = str(tmp_path / "o.json")
    rc = cli_main([
        "partition", files["binary"], "-k", "4", "--driver", "pipelined",
        "--stats", "--json", out,
    ])
    assert rc == 0
    assert "cut_ratio=" in capsys.readouterr().out
    with open(out) as f:
        r = json.load(f)
    assert r["k"] == 4 and len(r["labels"]) == 128
    assert 0.0 <= r["metrics"]["cut_ratio"] <= 1.0
    assert r["provenance"]["driver"] == "buffcut-pipe"


def test_cli_gen_and_list(tmp_path, capsys):
    p = str(tmp_path / "m.bcsr")
    assert cli_main(["gen", "grid", "-o", p, "--param", "side=8"]) == 0
    assert cli_main(["partition", p, "-k", "4"]) == 0
    assert cli_main(["list", "-v"]) == 0
    out = capsys.readouterr().out
    for name in ALL_NAMES:
        assert name in out


def test_cli_error_paths(files, tmp_path, capsys):
    assert cli_main(["partition", str(tmp_path / "missing.bcsr"), "-k", "4"]) == 1
    assert cli_main(["partition", files["binary"], "-k", "4", "--driver", "nope"]) == 1
    assert cli_main(["partition", files["binary"], "-k", "4", "--driver", "heistream"]) == 1
    err = capsys.readouterr().err
    assert "memory-only" in err
