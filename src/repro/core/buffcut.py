"""BuffCut sequential driver — paper Algorithm 1.

Streamed nodes either bypass the buffer (hubs, d > D_max → immediate Fennel)
or enter the bounded priority buffer Q. When |Q| = Q_max the top-priority
node is evicted into the active batch; admissions immediately bump the
scores of buffered neighbors (IncreaseKey), which is what recovers locality
from adversarial orders. Full batches are partitioned jointly on the batch
model graph by the multilevel scheme; assignments commit and the process
repeats until the stream ends and the buffer is flushed.

The driver consumes only the `NodeStream` protocol (graphs/stream.py): a
CSRGraph argument is wrapped in the in-memory stream, a `DiskNodeStream`
partitions straight from disk.  Adjacency is retained solely for nodes that
are buffered, batched, or mid-hub-assignment (RescoreState's
AdjacencyCache) and released at commit, so peak resident memory is
buffer + batch + the stream's read-ahead window — measured, not modeled, in
`StreamStats.peak_resident_bytes` (paper §4 accounting).
"""
from __future__ import annotations

import dataclasses
import json
import time

import numpy as np

from repro.graphs.csr import CSRGraph
from repro.graphs.stream import NodeStreamBase, as_node_stream
from repro.core._deprecation import warn_legacy
from repro.core.buffer import BucketPQ
from repro.core.prefetch import maybe_prefetch
from repro.core.rescore import RescoreState
from repro.core.scores import SCORES, ScoreSpec, get_score
from repro.core.fennel import FennelParams, fennel_choose
from repro.core.batch_model import build_batch_model_from_adj
from repro.core.multilevel import MultilevelConfig, multilevel_partition_resilient
from repro.core.metrics import internal_edge_ratio_adj, streaming_cut_increment
from repro.core.checkpoint import (
    Checkpointer,
    check_resume,
    pack_bucket_pq,
    pack_rescore,
    unpack_bucket_pq,
    unpack_rescore,
)


@dataclasses.dataclass
class BuffCutConfig:
    k: int
    eps: float = 0.03
    buffer_size: int = 4096          # Q_max
    batch_size: int = 1024           # delta
    d_max: float = 10000.0           # hub threshold (paper default)
    score: str | ScoreSpec = "haa"
    disc_factor: int = 1000          # paper default
    gamma: float = 1.5
    ml: MultilevelConfig = dataclasses.field(default_factory=MultilevelConfig)
    collect_stats: bool = False

    def __post_init__(self) -> None:
        if self.k < 2:
            raise ValueError(
                f"BuffCutConfig.k must be >= 2 (got {self.k}): partitioning "
                "into fewer than 2 blocks is a no-op"
            )
        if self.eps <= 0:
            raise ValueError(
                f"BuffCutConfig.eps must be > 0 (got {self.eps}): the balance "
                "cap is (1+eps)*c(V)/k and eps=0 leaves no slack for streaming "
                "assignment (paper default: 0.03)"
            )
        if self.buffer_size < 1:
            raise ValueError(
                f"BuffCutConfig.buffer_size (Q_max) must be >= 1, got {self.buffer_size}"
            )
        if self.batch_size < 1:
            raise ValueError(
                f"BuffCutConfig.batch_size (delta) must be >= 1, got {self.batch_size}"
            )
        if self.batch_size > self.buffer_size and self.buffer_size != 1:
            # buffer_size == 1 is the paper's Q=1 degeneracy (contiguous
            # batches == HeiStream) and legitimately pairs with any delta.
            raise ValueError(
                f"BuffCutConfig requires batch_size <= buffer_size (got "
                f"batch_size={self.batch_size} > buffer_size={self.buffer_size}): "
                "a batch can never out-grow the buffer feeding it. Shrink "
                "batch_size, grow buffer_size, or set buffer_size=1 for the "
                "unbuffered contiguous-batch mode."
            )
        if self.d_max <= 0:
            raise ValueError(
                f"BuffCutConfig.d_max (hub threshold) must be > 0, got {self.d_max}"
            )
        if self.disc_factor < 1:
            raise ValueError(
                f"BuffCutConfig.disc_factor must be >= 1, got {self.disc_factor}"
            )
        if isinstance(self.score, str) and self.score.lower() not in SCORES:
            raise ValueError(
                f"unknown score {self.score!r}: known scores are "
                f"{sorted(SCORES)} (or pass a ScoreSpec instance)"
            )

    def score_spec(self) -> ScoreSpec:
        if isinstance(self.score, ScoreSpec):
            return dataclasses.replace(self.score, d_max=float(self.d_max))
        return get_score(self.score, d_max=float(self.d_max))

    # ------------------------------------------------------- serialization
    def to_dict(self) -> dict:
        out = {}
        for f in dataclasses.fields(self):
            v = getattr(self, f.name)
            if isinstance(v, ScoreSpec):
                v = dataclasses.asdict(v)
            elif isinstance(v, MultilevelConfig):
                v = v.to_dict()
            out[f.name] = v
        return out

    @classmethod
    def from_dict(cls, d: dict) -> "BuffCutConfig":
        d = dict(d)
        if isinstance(d.get("score"), dict):
            d["score"] = ScoreSpec(**d["score"])
        if isinstance(d.get("ml"), dict):
            d["ml"] = MultilevelConfig.from_dict(d["ml"])
        return cls(**d)

    def to_json(self) -> str:
        return json.dumps(self.to_dict(), sort_keys=True)

    @classmethod
    def from_json(cls, s: str) -> "BuffCutConfig":
        return cls.from_dict(json.loads(s))


@dataclasses.dataclass
class StreamStats:
    runtime_s: float = 0.0
    ml_time_s: float = 0.0            # time inside multilevel_partition
    n_batches: int = 0
    n_hubs: int = 0
    ier_per_batch: list = dataclasses.field(default_factory=list)
    peak_mem_items: int = 0           # buffer + batch + model working set
    evictions: list = dataclasses.field(default_factory=list)
    # streaming-measured fields (always filled; see DESIGN.md §4):
    cut_weight: float = 0.0           # exact edge cut, accumulated at commits
    balance: float = 0.0              # max load / (c(V)/k) at stream end
    peak_resident_bytes: int = 0      # retained adjacency + read-ahead, peak
    stream_bytes_read: int = 0        # bytes pulled from the stream backend
    # final per-block f64 loads — handed to restream_refine so a seeded
    # restream skips its loads/cut prelude replay (one whole-file read saved)
    block_loads: list = dataclasses.field(default_factory=list)
    # fault-tolerance accounting (DESIGN.md §11):
    io_retries: int = 0               # transient stream-IO errors absorbed
    engine_fallbacks: int = 0         # batches degraded jax -> sparse engine
    checkpoints_written: int = 0      # crash-safe snapshots persisted
    # host-clock seconds per stage span of the pipelined driver's T2 and
    # T3 threads (core/spans.py names; empty for the other drivers)
    stage_s: dict = dataclasses.field(default_factory=dict)

    def note_engine_fallback(self) -> None:
        """Bound as the drivers' `on_fallback` callback (a lambda cannot
        hold the assignment)."""
        self.engine_fallbacks += 1

    @property
    def mean_ier(self) -> float:
        return float(np.mean(self.ier_per_batch)) if self.ier_per_batch else 0.0

    def to_dict(self) -> dict:
        out = dataclasses.asdict(self)
        out["ier_per_batch"] = [float(x) for x in self.ier_per_batch]
        out["evictions"] = [int(x) for x in self.evictions]
        out["block_loads"] = [float(x) for x in self.block_loads]
        return out

    @classmethod
    def from_dict(cls, d: dict) -> "StreamStats":
        return cls(**d)


class _State(RescoreState):
    """Per-stream counters (core/rescore.py) with BucketPQ-mirrored
    membership: the drivers flip `member` at insert/extract so every bump
    is one batched adjacency-slice pass instead of a per-edge Python loop."""


def _apply(pq: BucketPQ, touched: np.ndarray, scores: np.ndarray) -> None:
    """Forward batched rescores to the PQ in adjacency (first-occurrence)
    order — the same IncreaseKey sequence the per-edge loop produced."""
    for w_, s in zip(touched.tolist(), scores.tolist()):
        pq.increase_key(w_, s)


def _bump_assigned(st: _State, pq: BucketPQ, u: int, was_buffered: bool) -> None:
    """Node u became assigned-or-batched: rescore its buffered neighbors."""
    _apply(pq, *st.bump_assigned(np.array([u], dtype=np.int64), was_buffered))


def _bump_block_counts(st: _State, pq: BucketPQ, u: int, blk: int) -> None:
    """CMS only: u got a *concrete* block; update buffered nbr majorities."""
    _apply(pq, *st.bump_block_counts(u, blk))


def _bump_buffered(st: _State, pq: BucketPQ, v: int) -> None:
    """NSS only: v entered the buffer; count mutual buffered neighbors."""
    _apply(pq, *st.bump_buffered(np.array([v], dtype=np.int64)))


def buffcut_partition(
    g: CSRGraph | NodeStreamBase, cfg: BuffCutConfig
) -> tuple[np.ndarray, StreamStats]:
    """Deprecated shim — `repro.api.partition` is the front door."""
    warn_legacy("buffcut_partition(g, cfg)", "partition(g, driver='buffcut', k=...)")
    return _buffcut_partition(g, cfg)


def _buffcut_partition(
    g: CSRGraph | NodeStreamBase,
    cfg: BuffCutConfig,
    *,
    prefetch_batches: int = 0,
    ckpt: Checkpointer | None = None,
    resume: dict | None = None,
    on_batch=None,
) -> tuple[np.ndarray, StreamStats]:
    # prefetch overlaps parsing with scoring, record order (and therefore
    # every label) untouched — tell()/resident_bytes stay consumer-truthful
    stream = maybe_prefetch(as_node_stream(g), prefetch_batches, cfg.batch_size)
    n = stream.n
    spec = cfg.score_spec()
    p = FennelParams(
        k=cfg.k,
        n_total=stream.n_total,
        m_total=stream.m_total,
        eps=cfg.eps,
        gamma=cfg.gamma,
    )
    st = _State(n, spec, cfg.k)
    pq = BucketPQ(spec.s_max, cfg.disc_factor)
    block = np.full(n, -1, dtype=np.int64)
    loads = np.zeros(cfg.k, dtype=np.float64)
    batch: list[int] = []
    stats = StreamStats()
    if resume is not None:
        check_resume(resume, "buffcut", cfg.to_json(), n)
        block[:] = resume["block"]
        loads[:] = resume["loads"]
        batch.extend(int(x) for x in np.asarray(resume["batch"]).tolist())
        stats = StreamStats.from_dict(resume["stats"])
        unpack_rescore(st, resume["state"])
        unpack_bucket_pq(pq, resume["pq"])
        if ckpt is not None:
            ckpt.mark(stats.n_batches)
    base_runtime = stats.runtime_s
    base_bytes = stats.stream_bytes_read
    base_retries = stats.io_retries
    t0 = time.perf_counter()

    def make_state() -> dict:
        sd = stats.to_dict()
        sd["runtime_s"] = base_runtime + (time.perf_counter() - t0)
        sd["stream_bytes_read"] = base_bytes + stream.bytes_read
        sd["io_retries"] = base_retries + int(getattr(stream, "io_retries", 0))
        # prior-run writes (resume base) + this run's + this very snapshot
        sd["checkpoints_written"] += ckpt.written + 1
        return {
            "kind": "buffcut",
            "config_json": cfg.to_json(),
            "n": n,
            "pos": stream.tell(),
            "block": block,
            "loads": loads,
            "batch": np.asarray(batch, dtype=np.int64),
            "stats": sd,
            "state": pack_rescore(st),
            "pq": pack_bucket_pq(pq),
        }

    def note_peak(extra: int = 0) -> None:
        resident = st.adj.resident_bytes + stream.resident_bytes + extra
        if resident > stats.peak_resident_bytes:
            stats.peak_resident_bytes = resident

    def commit_batch() -> None:
        if not batch:
            return
        bnodes = np.asarray(batch, dtype=np.int64)
        nbr_c, w_c, degs = st.adj.slice(bnodes)
        node_w_b = st.adj.node_weights(bnodes)
        model = build_batch_model_from_adj(
            n, bnodes, degs, nbr_c, w_c, node_w_b, block, cfg.k
        )
        t_ml = time.perf_counter()
        labels = multilevel_partition_resilient(
            model.graph, model.pinned_block, p, loads, cfg.ml,
            on_fallback=stats.note_engine_fallback,
        )
        stats.ml_time_s += time.perf_counter() - t_ml
        lab_b = labels[: bnodes.shape[0]]
        block[bnodes] = lab_b
        np.add.at(loads, lab_b, node_w_b.astype(np.float64))
        stats.cut_weight += streaming_cut_increment(bnodes, lab_b, degs, nbr_c, w_c, block)
        note_peak(model.graph.indices.nbytes + model.graph.edge_w.nbytes)
        if cfg.collect_stats:
            stats.ier_per_batch.append(internal_edge_ratio_adj(bnodes, nbr_c, w_c, n))
            stats.peak_mem_items = max(
                stats.peak_mem_items, len(pq) + len(batch) + model.graph.indices.shape[0]
            )
        stats.n_batches += 1
        # CMS: buffered neighbors now see concrete blocks
        if st.blk_w is not None:
            for u, b_ in zip(bnodes, lab_b):
                _bump_block_counts(st, pq, int(u), int(b_))
        st.release(bnodes)
        batch.clear()
        if on_batch is not None:
            # sharded load-sync hook (distributed/shard_driver.py): fires at
            # the commit boundary with the live per-block loads, which it may
            # rewrite in place to fold in other workers' published loads
            on_batch(stats.n_batches, loads)

    def evict_one() -> None:
        u = pq.extract_max()
        st.member[u] = False
        st.drop_block_counts(u)
        batch.append(u)
        if cfg.collect_stats:
            stats.evictions.append(u)
        _bump_assigned(st, pq, u, was_buffered=True)
        if len(batch) == cfg.batch_size:
            commit_batch()

    one = np.empty(1, dtype=np.int64)
    records = stream.iter_from(dict(resume["pos"])) if resume is not None else iter(stream)
    for v, nbrs, nbr_w, node_w in records:
        st.observe(v, nbrs, nbr_w, node_w)
        note_peak()
        if nbrs.size > cfg.d_max:  # hub bypass: assign immediately via Fennel
            i = fennel_choose(nbrs, nbr_w, node_w, block, loads, p)
            block[v] = i
            loads[i] += node_w
            stats.n_hubs += 1
            one[0] = v
            hnbr, hw, hdeg = st.adj.slice(one)
            stats.cut_weight += streaming_cut_increment(
                one, np.array([i], dtype=np.int64), hdeg, hnbr, hw, block
            )
            _bump_assigned(st, pq, v, was_buffered=False)
            _bump_block_counts(st, pq, v, i)
            st.release(one)
        else:
            _bump_buffered(st, pq, v)
            pq.insert(v, st.score(v))
            st.member[v] = True
            if cfg.collect_stats:
                stats.peak_mem_items = max(stats.peak_mem_items, len(pq) + len(batch))
        while len(pq) >= cfg.buffer_size and len(batch) < cfg.batch_size:
            evict_one()
        if ckpt is not None:
            # record boundary: hub fully committed or node buffered/evicted,
            # IncrementalCut bracket closed — everything is snapshotable
            ckpt.maybe_save(stats.n_batches, make_state)

    # flush (paper Alg. 1 tail)
    while len(pq) > 0:
        evict_one()
    commit_batch()
    stats.balance = float(loads.max() / (p.n_total / cfg.k)) if p.n_total > 0 else 1.0
    stats.block_loads = loads.tolist()
    stats.stream_bytes_read = base_bytes + stream.bytes_read
    stats.io_retries = base_retries + int(getattr(stream, "io_retries", 0))
    if ckpt is not None:
        stats.checkpoints_written += ckpt.written
    stats.runtime_s = base_runtime + (time.perf_counter() - t0)
    return block, stats
