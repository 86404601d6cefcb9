"""Pipelined BuffCut (paper §3.5 parallelization) — the out-of-core hot path.

The paper overlaps three stages with threads + lock-free queues:
  T1 I/O reader -> T2 priority-queue handler -> T3 partition worker.

T1 is the double-buffered prefetcher (core/prefetch.py): a background
thread parses batch *i+1* from the stream while T2 scores batch *i*,
handing records over in δ-batch-sized blocks (`PipelineConfig.
prefetch_batches` deep) so queue traffic is per-block, not per-record.
With ``prefetch_batches=0`` the same block iterator runs inline — record
sequence identical, no thread.

T2 is the **fused** per-record loop: score → buffer-insert → evict run in
plain python on scalar counters (`RescoreState.*_scalar`,
`ScoreSpec.scalar_fn`) instead of paying a numpy dispatch per record —
bitwise-identical state evolution to the batched bumps (the adds land in
adjacency order exactly like np.add.at, touched nodes rescore in
first-occurrence order after all adds; see rescore.py "scalar twins").

T3 receives self-contained payloads (the batch's retained adjacency),
never touching a graph object.  Labels leave the multilevel engine once
per δ-batch; because δ is fixed, the jax engine's pow2 shape bucketing
(csr.bucket_size inside multilevel_jax) means every full batch reuses the
same compiled shapes.  To keep scoring consistent with the sequential
semantics, nodes are treated as assigned the moment their batch task is
enqueued (paper: "as soon as their task is enqueued").

Tasks commit in enqueue order under one lock, so `block`/`loads` at every
commit equal the serial driver's state and the emitted labels are
bit-identical to `_buffcut_partition` for every `prefetch_batches` —
pinned by tests/test_stream_conformance.py::test_prefetch_sweep_bit_identical.

T3 has precedence over T2 for the host.  T2's loop is Python under the
interpreter lock; T3's batch model, commit and hub placement are chains of
numpy calls, each of which drops and retakes that lock, so beside a running
T2 every retake waits out the switch interval.  T3 closes a gate for its
host work and opens it for the V-cycle call and while it waits for a task;
T2 tests the gate once per record and, while it is closed, stands aside in
a wait that does not hold the lock (span `buffcut.t2.yield`).  The task
queue's `queue_depth` counts batch tasks only: hubs ride free (up to a
batch's worth of them), so T2 can queue a batch ahead during the call.

Shutdown is hardened (DESIGN.md §11): every blocking wait is bounded and
watches a shared stop event, worker exceptions are captured and re-raised
on the main thread, and a ``finally`` block poison-pills and joins the
worker *and* the prefetch pump with a timeout on *every* exit path.
Checkpoints quiesce the worker first (wait until every enqueued task has
committed) so the snapshot is taken at a true batch boundary.
"""
from __future__ import annotations

import dataclasses
import queue
import threading
import time

import numpy as np

from repro.graphs.csr import CSRGraph
from repro.graphs.stream import NodeStreamBase, as_node_stream
from repro.core._deprecation import warn_legacy
from repro.core.buffcut import BuffCutConfig, StreamStats, _State
from repro.core.buffer import BucketPQ
from repro.core.fennel import FennelParams, fennel_choose
from repro.core.batch_model import build_batch_model_from_adj
from repro.core.multilevel import multilevel_partition_resilient
from repro.core.metrics import internal_edge_ratio_adj, streaming_cut_increment
from repro.core.prefetch import PrefetchStream
from repro.core.spans import span
from repro.core.checkpoint import (
    Checkpointer,
    check_resume,
    pack_bucket_pq,
    pack_rescore,
    unpack_bucket_pq,
    unpack_rescore,
)

# granularity of the stop-event checks around blocking queue ops; small
# enough that teardown is prompt, large enough to stay off the profile
_POLL_S = 0.05
_JOIN_TIMEOUT_S = 5.0


@dataclasses.dataclass
class PipelineConfig:
    """Knobs of the pipelined driver (formerly loose kwargs).

    `prefetch_batches` is the T1 read-ahead depth in δ-batches: 0 parses
    inline (serial), 1 is classic double buffering, more deepens the
    window.  `queue_depth` bounds the batch tasks queued for T3; hub
    tasks ride free of it, so hubs between two batches never stop T2
    from queuing a batch ahead (at most `batch_size` hubs wait in the
    queue beyond its batch slots, which bounds a stream that is mostly
    hubs).  Like
    `queue_depth`, `prefetch_batches` changes throughput and staging
    residency, never labels.  `read_ahead` predates the block prefetcher
    and is kept for config compatibility; the prefetcher supersedes it.
    """

    queue_depth: int = 4        # batch tasks queued for T3 (hubs ride free)
    read_ahead: int = 64        # legacy T1 record-queue bound (superseded)
    prefetch_batches: int = 2   # T1 read-ahead depth, in δ-batch blocks

    def __post_init__(self) -> None:
        if self.queue_depth < 1:
            raise ValueError(
                f"PipelineConfig.queue_depth must be >= 1, got {self.queue_depth}"
            )
        if self.read_ahead < 1:
            raise ValueError(
                f"PipelineConfig.read_ahead must be >= 1, got {self.read_ahead}"
            )
        if self.prefetch_batches < 0:
            raise ValueError(
                "PipelineConfig.prefetch_batches must be >= 0, got "
                f"{self.prefetch_batches}"
            )

    def to_dict(self) -> dict:
        return dataclasses.asdict(self)

    @classmethod
    def from_dict(cls, d: dict) -> "PipelineConfig":
        return cls(**d)


def buffcut_partition_pipelined(
    g: CSRGraph | NodeStreamBase,
    cfg: BuffCutConfig,
    queue_depth: int = 4,
    read_ahead: int = 64,
    prefetch_batches: int = 2,
) -> tuple[np.ndarray, StreamStats]:
    """Deprecated shim — `repro.api.partition` is the front door; the loose
    queue_depth/read_ahead/prefetch_batches kwargs fold into
    `PipelineConfig`."""
    warn_legacy(
        "buffcut_partition_pipelined(g, cfg, queue_depth=..., read_ahead=...)",
        "partition(g, driver='buffcut-pipe', k=..., queue_depth=..., prefetch_batches=...)",
    )
    return _buffcut_partition_pipelined(
        g, cfg, PipelineConfig(
            queue_depth=queue_depth, read_ahead=read_ahead,
            prefetch_batches=prefetch_batches,
        )
    )


def _buffcut_partition_pipelined(
    g: CSRGraph | NodeStreamBase,
    cfg: BuffCutConfig,
    pipe: PipelineConfig | None = None,
    *,
    ckpt: Checkpointer | None = None,
    resume: dict | None = None,
) -> tuple[np.ndarray, StreamStats]:
    pipe = pipe if pipe is not None else PipelineConfig()
    stream = as_node_stream(g)
    blk = max(1, cfg.batch_size)
    if pipe.prefetch_batches > 0 and not isinstance(stream, PrefetchStream):
        stream = PrefetchStream(stream, depth=pipe.prefetch_batches, block=blk)
    n = stream.n
    spec = cfg.score_spec()
    p = FennelParams(
        k=cfg.k, n_total=stream.n_total, m_total=stream.m_total,
        eps=cfg.eps, gamma=cfg.gamma,
    )
    st = _State(n, spec, cfg.k)
    pq = BucketPQ(spec.s_max, cfg.disc_factor)
    block = np.full(n, -1, dtype=np.int64)
    loads = np.zeros(cfg.k, dtype=np.float64)
    # block, loads and stats are T3's: hubs are placed in T3 too, so T2
    # reads none of them and takes no snapshot (a checkpoint reads them
    # after quiesce()).  T2 owns the rescore state, the buffer and the
    # adjacency cache.
    lock = threading.Lock()
    # the T2 -> T3 handoff (DESIGN.md §11): tasks in enqueue order; `slots`
    # bounds the batch tasks queued to queue_depth, hubs and the poison pill
    # take none (the queue's own bound only caps the hubs waiting behind a
    # V-cycle at a batch's worth of records).  `gate` is open (set) unless
    # T3 runs host work: T2 stands aside between records while it is
    # closed, so T3's numpy chain does not wait on the interpreter lock
    # behind T2's per-record Python.
    task_q: queue.Queue = queue.Queue(maxsize=pipe.queue_depth + blk)
    slots = threading.Semaphore(pipe.queue_depth)
    gate = threading.Event()
    gate.set()
    stats = StreamStats()
    batch: list[int] = []
    # queue/prefetch knobs change throughput, never labels (tasks commit in
    # enqueue order under one lock), so only the BuffCut config is resume
    # identity
    if resume is not None:
        check_resume(resume, "buffcut-pipe", cfg.to_json(), n)
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

    # ---- shutdown plumbing (DESIGN.md §11)
    stop = threading.Event()
    worker_err: list[BaseException] = []
    done_cv = threading.Condition()
    counts = {"put": 0, "done": 0}  # tasks enqueued / tasks committed
    last_pos: dict | None = dict(resume["pos"]) if resume is not None else None

    def check_worker() -> None:
        if worker_err:
            raise worker_err[0]

    def make_state() -> dict:
        sd = stats.to_dict()
        sd["runtime_s"] = base_runtime + (time.perf_counter() - t0)
        sd["stream_bytes_read"] = base_bytes + stream.bytes_read
        sd["io_retries"] = base_retries + int(getattr(stream, "io_retries", 0))
        sd["checkpoints_written"] += ckpt.written + 1
        return {
            "kind": "buffcut-pipe",
            "config_json": cfg.to_json(),
            "n": n,
            "pos": dict(last_pos),
            "block": block,
            "loads": loads,
            "batch": np.asarray(batch, dtype=np.int64),
            "stats": sd,
            "state": pack_rescore(st),
            "pq": pack_bucket_pq(pq),
        }

    def quiesce() -> None:
        """Wait for T3 to drain every enqueued task, so block/loads/stats
        describe a closed batch boundary before the snapshot is built."""
        with done_cv:
            while counts["done"] < counts["put"]:
                check_worker()
                done_cv.wait(timeout=_POLL_S)
        check_worker()

    # bytes in batch/hub payloads queued or being processed by T3 (T2->T3):
    # released cache entries live on in payloads, so they stay in the
    # measured resident set until the worker finishes with them.  The T1
    # staging window (parsed-but-unconsumed blocks) is inside
    # stream.resident_bytes — PrefetchStream accounts its own queue.
    inflight = {"task_bytes": 0}
    # inflight gets its *own* lock: T2 must never wait on the commit lock
    # (T3 holds that across a whole multilevel partition) just to bump a
    # byte counter — that wait would serialize the very overlap the
    # pipeline exists for.  Lock order is commit-lock -> ilock only.
    ilock = threading.Lock()

    def _payload_bytes(arrays) -> int:
        return int(sum(a.nbytes for a in arrays if isinstance(a, np.ndarray)) + 64)

    def note_peak(extra: int = 0) -> None:
        with ilock:
            resident = (
                st.adj.resident_bytes + inflight["task_bytes"]
                + stream.resident_bytes + extra
            )
            if resident > stats.peak_resident_bytes:
                stats.peak_resident_bytes = resident

    stage_s = stats.stage_s

    def next_task():
        """T3's next queue item; None once stopped or poison-pilled.  A task
        already queued is taken with the gate as it is, so T3 keeps the host
        from one task to the next; the gate opens only when T3 must wait."""
        with span("buffcut.t3.task_wait", stage_s):
            try:
                return task_q.get_nowait()
            except queue.Empty:
                gate.set()
            while True:
                try:
                    return task_q.get(timeout=_POLL_S)
                except queue.Empty:
                    if stop.is_set():
                        return None

    def partition_worker() -> None:  # T3
        try:
            while True:
                item = next_task()
                if item is None:
                    return
                gate.clear()  # T3's host work: T2 stands aside
                kind, payload = item
                with lock:
                    if kind == "batch":
                        slots.release()  # the batch is off the queue
                        bnodes, degs, nbr_c, w_c, node_w_b = payload
                        with span("buffcut.t3.batch_model", stage_s):
                            model = build_batch_model_from_adj(
                                n, bnodes, degs, nbr_c, w_c, node_w_b, block, cfg.k
                            )
                            note_peak(
                                model.graph.indices.nbytes + model.graph.edge_w.nbytes
                            )
                        gate.set()  # T2 does its records during the call
                        with span("buffcut.t3.vcycle", stage_s):
                            labels = multilevel_partition_resilient(
                                model.graph, model.pinned_block, p, loads, cfg.ml,
                                on_fallback=stats.note_engine_fallback,
                            )
                        gate.clear()
                        stats.ml_time_s = stage_s["buffcut.t3.vcycle"]
                        with span("buffcut.t3.commit", stage_s):
                            lab_b = labels[: bnodes.shape[0]]
                            block[bnodes] = lab_b
                            np.add.at(loads, lab_b, node_w_b.astype(np.float64))
                            stats.cut_weight += streaming_cut_increment(
                                bnodes, lab_b, degs, nbr_c, w_c, block
                            )
                            stats.n_batches += 1
                            if cfg.collect_stats:
                                stats.ier_per_batch.append(
                                    internal_edge_ratio_adj(bnodes, nbr_c, w_c, n)
                                )
                    else:  # single hub task: payload carries the stream record
                        with span("buffcut.t3.hub", stage_s):
                            v, nbrs, nbr_w, node_w = payload
                            i = fennel_choose(nbrs, nbr_w, float(node_w), block, loads, p)
                            block[v] = i
                            loads[i] += np.float32(node_w)
                            hv = np.array([v], dtype=np.int64)
                            stats.cut_weight += streaming_cut_increment(
                                hv,
                                np.array([i], dtype=np.int64),
                                np.array([nbrs.size], dtype=np.int64),
                                nbrs.astype(np.int64),
                                nbr_w.astype(np.float64),
                                block,
                            )
                            stats.n_hubs += 1
                with ilock:
                    inflight["task_bytes"] -= _payload_bytes(payload)
                with done_cv:
                    counts["done"] += 1
                    done_cv.notify_all()
        except BaseException as e:
            worker_err.append(e)
            stop.set()
            with done_cv:
                done_cv.notify_all()
        finally:
            gate.set()  # no exit of T3 leaves T2 standing aside

    # daemon=True stays as a backstop, but the finally below always poison-
    # pills and joins, so normal operation never relies on it
    worker = threading.Thread(target=partition_worker, name="partition-worker",
                              daemon=True)
    worker.start()

    def put_task(item) -> None:
        check_worker()
        try:
            task_q.put_nowait(item)
        except queue.Full:  # a batch's worth of hubs waits for T3
            with span("buffcut.t2.put_wait", stage_s):
                while True:
                    try:
                        task_q.put(item, timeout=_POLL_S)
                        break
                    except queue.Full:
                        check_worker()
        if item is not None:  # the poison pill is not a task
            counts["put"] += 1

    gate_open = gate.is_set

    def stand_aside() -> None:
        """T2 waits, without the interpreter lock, until T3 opens the gate;
        bounded waits that watch `stop`."""
        with span("buffcut.t2.yield"):
            while not gate.wait(_POLL_S):
                if stop.is_set():
                    break
        check_worker()

    def flush_batch() -> None:
        if batch:
            with span("buffcut.t2.flush", stage_s):
                bnodes = np.asarray(batch, dtype=np.int64)
                nbr_c, w_c, degs = st.adj.slice(bnodes)
                node_w_b = st.adj.node_weights(bnodes)
                st.release(bnodes)  # payload is self-contained; cache shrinks now
                payload = (bnodes, degs, nbr_c, w_c, node_w_b)
                with ilock:
                    inflight["task_bytes"] += _payload_bytes(payload)
                if not slots.acquire(blocking=False):  # hubs take no slot
                    with span("buffcut.t2.put_wait", stage_s):
                        while not slots.acquire(timeout=_POLL_S):
                            check_worker()
                put_task(("batch", payload))
                batch.clear()

    def blocks():
        """(records, tokens) blocks: T1 prefetch thread when configured,
        inline chunking otherwise — identical record sequence either way."""
        start = dict(resume["pos"]) if resume is not None else None
        if isinstance(stream, PrefetchStream):
            yield from stream.blocks(start)
            return
        it = stream.iter_from(start) if start is not None else iter(stream)
        recs: list = []
        toks: list = []
        for rec in it:
            try:
                toks.append(stream.tell())
            except NotImplementedError:
                toks.append(None)
            recs.append(rec)
            if len(recs) == blk:
                yield recs, toks
                recs, toks = [], []
        if recs:
            yield recs, toks

    # ---- T2 (PQ handler): the fused scalar hot loop.  Everything per
    # record is python-float math on the shared RescoreState counters —
    # bitwise-identical to the batched bump path (rescore.py scalar twins).
    fscore = spec.scalar_fn()
    nss = spec.needs_buffered_count
    member = st.member
    adj = st.adj
    inc = pq.increase_key
    insert = pq.insert
    extract = pq.extract_max
    d_max = cfg.d_max
    buffer_size = cfg.buffer_size
    batch_size = cfg.batch_size

    try:
        for recs, toks in blocks():
            check_worker()
            with span("buffcut.t2.block", stage_s):
                for ri in range(len(recs)):
                    if not gate_open():
                        stand_aside()
                    v, nbrs, nbr_w, node_w = recs[ri]
                    st.observe_scalar(v, nbrs, nbr_w, node_w)
                    if nbrs.size > d_max:
                        payload = (v, nbrs, nbr_w, node_w)
                        with ilock:
                            inflight["task_bytes"] += _payload_bytes(payload)
                        put_task(("hub", payload))
                        st.bump_assigned_scalar(v, False, fscore, inc)  # enqueued == assigned
                        adj.drop_one(v)
                    else:
                        if nss:
                            st.bump_buffered_scalar(v, fscore, inc)
                        insert(v, st.score_scalar(v, fscore))
                        member[v] = True
                    while len(pq) >= buffer_size and len(batch) < batch_size:
                        u = extract()
                        member[u] = False
                        batch.append(u)
                        st.bump_assigned_scalar(u, True, fscore, inc)
                        if len(batch) == batch_size:
                            flush_batch()
                    pos = toks[ri]
                    if pos is not None:
                        last_pos = pos
                    if (ckpt is not None and last_pos is not None
                            and ckpt.due(stats.n_batches)):
                        quiesce()  # drain T3 so the snapshot sees a closed boundary
                        ckpt.maybe_save(stats.n_batches, make_state)
            note_peak()
        with span("buffcut.t2.block", stage_s):  # the buffer's final drain
            while len(pq) > 0:
                if not gate_open():
                    stand_aside()
                u = extract()
                member[u] = False
                batch.append(u)
                st.bump_assigned_scalar(u, True, fscore, inc)
                if len(batch) == batch_size:
                    flush_batch()
            flush_batch()
        quiesce()
        put_task(None)
        worker.join(timeout=_JOIN_TIMEOUT_S)
        check_worker()
    finally:
        # every exit path — normal, parse error, worker failure — tears the
        # pipeline down: wake anything blocked, then join with a timeout
        stop.set()
        worker.join(timeout=_JOIN_TIMEOUT_S)
        if isinstance(stream, PrefetchStream):
            stream.close()
    with lock:
        stats.balance = float(loads.max() / (p.n_total / cfg.k)) if p.n_total > 0 else 1.0
    stats.block_loads = loads.tolist()
    stats.stream_bytes_read = base_bytes + stream.bytes_read
    stats.io_retries = base_retries + int(getattr(stream, "io_retries", 0))
    if ckpt is not None:
        stats.checkpoints_written += ckpt.written
    stats.runtime_s = base_runtime + (time.perf_counter() - t0)
    return block, stats
