"""PrefetchStream conformance (ISSUE 7 acceptance): the background reader
changes *when* records are parsed, never *what* the partitioner sees.

Two contracts pinned here:

* **Bit-identity sweep** — labels are identical across
  ``prefetch_batches`` ∈ {0, 1, 2, 8} × all 3 drivers × both disk
  backends (packed binary, METIS text) × multilevel engines
  {sparse, jax}, and equal to the in-memory run.
* **No thread leaks** — the "prefetch-pump" thread is joined on every
  exit path: normal exhaustion, consumer abandon/`break`, parse errors
  surfacing mid-stream, and driver failures (referenced by
  core/prefetch.py and DESIGN.md §12.2).

The pipelined driver's T2 -> T3 handoff (DESIGN.md §11) is pinned on a
hub-heavy stream: labels equal the serial driver's at every task-queue
depth, hubs take no batch slot, and a partition worker that fails while
T2 stands aside at its gate ends the job without leaving a thread.
"""
import sys
import threading
import time

import numpy as np
import pytest

from repro.core import BuffCutConfig, PipelineConfig, VectorizedConfig
from repro.core import pipeline
from repro.core.buffcut import _buffcut_partition, _State
from repro.core.multilevel import MultilevelConfig
from repro.core.pipeline import _buffcut_partition_pipelined
from repro.core.vector_stream import _buffcut_partition_vectorized
from repro.core.prefetch import PrefetchStream, maybe_prefetch
from repro.graphs import (
    DiskNodeStream,
    StreamFormatError,
    apply_order,
    random_order,
    rmat_graph,
    write_metis,
    write_packed,
)

PF_SWEEP = (0, 1, 2, 8)

DRIVERS = {
    "sequential": lambda s, cfg, pf: _buffcut_partition(
        s, cfg, prefetch_batches=pf
    ),
    "vectorized": lambda s, cfg, pf: _buffcut_partition_vectorized(
        s, cfg, VectorizedConfig(wave=1, chunk=1), prefetch_batches=pf
    ),
    "pipelined": lambda s, cfg, pf: _buffcut_partition_pipelined(
        s, cfg, PipelineConfig(prefetch_batches=pf)
    ),
}


@pytest.fixture(scope="module")
def base_graph():
    return rmat_graph(128, 5, seed=7)


@pytest.fixture(scope="module")
def disk_files(base_graph, tmp_path_factory):
    tmp = tmp_path_factory.mktemp("prefetch")
    packed = str(tmp / "g.bcsr")
    text = str(tmp / "g.metis")
    write_packed(base_graph, packed)
    write_metis(base_graph, text)
    return {"packed": packed, "text": text}


def _cfg(engine: str) -> BuffCutConfig:
    return BuffCutConfig(
        k=4, buffer_size=24, batch_size=12, d_max=48, score="haa",
        collect_stats=True, ml=MultilevelConfig(engine=engine),
    )


def _open(disk_files, backend: str) -> DiskNodeStream:
    if backend == "text":
        # odd chunk size so record boundaries land mid-chunk
        return DiskNodeStream(disk_files["text"], io_chunk_bytes=97)
    return DiskNodeStream(disk_files["packed"])


def _pump_threads(names=("prefetch-pump",)) -> list:
    return [
        t for t in threading.enumerate()
        if t.name in names and t.is_alive()
    ]


# --------------------------------------------------------- bit-identity


@pytest.mark.parametrize("engine", ["sparse", "jax"])
@pytest.mark.parametrize("driver", sorted(DRIVERS))
def test_prefetch_sweep_bit_identical(driver, engine, base_graph, disk_files):
    """Sweeping the prefetch depth never changes a single label."""
    cfg = _cfg(engine)
    b_mem, s_mem = DRIVERS[driver](base_graph, cfg, 0)
    for backend in ("packed", "text"):
        for pf in PF_SWEEP:
            b, s = DRIVERS[driver](_open(disk_files, backend), cfg, pf)
            assert np.array_equal(b_mem, b), (backend, pf)
            assert s.cut_weight == s_mem.cut_weight, (backend, pf)
            assert s.balance == s_mem.balance, (backend, pf)
    assert not _pump_threads()


def test_record_iteration_matches_unwrapped(disk_files):
    """Record-granular consumption (the sequential/vectorized path) yields
    the same records in the same order as the raw stream, and the consumer-
    side tell() token resumes bit-identically."""
    plain = list(DiskNodeStream(disk_files["packed"]))
    ps = PrefetchStream(DiskNodeStream(disk_files["packed"]), depth=2, block=7)
    seen = []
    token = None
    for i, rec in enumerate(ps):
        seen.append(rec)
        if i == len(plain) // 2:
            token = ps.tell()  # consumer-truthful, not pump-side
    assert len(seen) == len(plain)
    for (u, nb, w, nw), (u2, nb2, w2, nw2) in zip(plain, seen):
        assert u == u2 and nw == nw2
        assert np.array_equal(nb, nb2) and np.array_equal(w, w2)
    # resume from the captured token == tail of the plain read
    tail = [u for u, *_ in DiskNodeStream(disk_files["packed"]).iter_from(token)]
    assert tail == [u for u, *_ in plain[len(plain) // 2 + 1:]]
    assert not _pump_threads()


# ------------------------------------------------------------ API edges


def test_constructor_validation(disk_files):
    s = DiskNodeStream(disk_files["packed"])
    with pytest.raises(ValueError):
        PrefetchStream(s, depth=0)
    with pytest.raises(ValueError):
        PrefetchStream(s, depth=1, block=0)


def test_maybe_prefetch_identity(disk_files):
    s = DiskNodeStream(disk_files["packed"])
    assert maybe_prefetch(s, 0, 16) is s          # 0 = do not wrap
    ps = maybe_prefetch(s, 2, 16)
    assert isinstance(ps, PrefetchStream)
    assert maybe_prefetch(ps, 2, 16) is ps        # never double-wrap


def test_tell_before_first_record_raises(disk_files):
    ps = PrefetchStream(DiskNodeStream(disk_files["packed"]), depth=1)
    with pytest.raises(NotImplementedError):
        ps.tell()
    ps.close()
    assert not _pump_threads()


def test_resident_bytes_counts_staging(disk_files):
    """While blocks sit in the queue, resident_bytes must see them."""
    ps = PrefetchStream(DiskNodeStream(disk_files["packed"]), depth=4, block=8)
    it = iter(ps)
    next(it)
    # let the pump fill the queue, then staging must be visible
    deadline = 100
    while ps.resident_bytes <= ps._inner.resident_bytes and deadline:
        deadline -= 1
        threading.Event().wait(0.01)
    assert ps.resident_bytes > ps._inner.resident_bytes
    ps.close()
    assert not _pump_threads()


# ----------------------------------------------------------- no leaks


def test_no_thread_leak_consumer_abandon(disk_files):
    """A consumer that breaks mid-stream (or drops the iterator) must not
    leave the pump parked on a full queue."""
    ps = PrefetchStream(DiskNodeStream(disk_files["packed"]), depth=1, block=4)
    for i, _rec in enumerate(ps):
        if i == 5:
            break
    ps.close()
    assert not _pump_threads()

    # generator dropped without break: close() still reaps the pump
    ps = PrefetchStream(DiskNodeStream(disk_files["packed"]), depth=1, block=4)
    it = iter(ps)
    next(it)
    del it
    ps.close()
    assert not _pump_threads()


def test_no_thread_leak_on_parse_error(base_graph, tmp_path):
    """A corrupt file raises StreamFormatError in the *consumer* and the
    pump is joined — errors cross the thread boundary, threads do not."""
    path = str(tmp_path / "bad.bcsr")
    write_packed(base_graph, path)
    with open(path, "r+b") as f:  # flip a payload byte -> section CRC fails
        f.seek(200)
        b = f.read(1)
        f.seek(200)
        f.write(bytes([b[0] ^ 0xFF]))
    ps = PrefetchStream(DiskNodeStream(path), depth=2, block=8)
    with pytest.raises(StreamFormatError):
        for _ in ps:
            pass
    assert not _pump_threads()


def test_no_thread_leak_on_driver_failure(base_graph, tmp_path):
    """Every driver's finally-path closes the prefetcher when the stream
    errors mid-partition."""
    path = str(tmp_path / "bad2.bcsr")
    write_packed(base_graph, path)
    with open(path, "r+b") as f:
        f.seek(300)
        b = f.read(1)
        f.seek(300)
        f.write(bytes([b[0] ^ 0xFF]))
    cfg = _cfg("sparse")
    for name, drv in sorted(DRIVERS.items()):
        with pytest.raises(StreamFormatError):
            drv(DiskNodeStream(path), cfg, 2)
        assert not _pump_threads(), name


# ------------------------------------------------ pipelined T2 -> T3 handoff

HUB_D_MAX = 16


@pytest.fixture(scope="module")
def hub_graph():
    """R-MAT hubs spread through the stream: 49 of 512 nodes have degree
    above HUB_D_MAX, about three between two batches of 32."""
    g = rmat_graph(512, 8, seed=3)
    return apply_order(g, random_order(g, seed=5))


def _hub_cfg(engine: str, d_max: int = HUB_D_MAX, batch: int = 32) -> BuffCutConfig:
    return BuffCutConfig(
        k=4, buffer_size=2 * batch, batch_size=batch, d_max=d_max, score="haa",
        ml=MultilevelConfig(engine=engine),
    )


class _Log:
    """T2's and T3's steps in the order they happen: span names on entry,
    and ("rec", is_hub) for each stream record T2 observes."""

    def __init__(self, monkeypatch, d_max: int = HUB_D_MAX):
        self.events: list = []
        real_span, real_observe = pipeline.span, _State.observe_scalar
        events = self.events

        class span(real_span):
            def __enter__(self):
                events.append(self._name)
                return super().__enter__()

        def observe(st, v, nbrs, nbr_w, node_w):
            events.append(("rec", nbrs.size > d_max))
            return real_observe(st, v, nbrs, nbr_w, node_w)

        monkeypatch.setattr(pipeline, "span", span)
        monkeypatch.setattr(_State, "observe_scalar", observe)

    def settle(self, quiet_s: float = 0.5, limit_s: float = 30.0) -> list:
        """The log once it has stopped growing for `quiet_s`."""
        t_end = time.monotonic() + limit_s
        n = -1
        while len(self.events) != n and time.monotonic() < t_end:
            n = len(self.events)
            time.sleep(quiet_s)
        return list(self.events)


def _t2_steps_behind_a_held_engine(g, cfg, monkeypatch) -> list:
    """Run the pipelined driver with one batch slot while the engine is held
    on an event; T2's records, flushes and put waits up to where T2 blocks,
    once it has stopped.  The job then finishes with the serial labels."""
    b_ser, _ = _buffcut_partition(g, cfg)
    log = _Log(monkeypatch, cfg.d_max)
    entered, release = threading.Event(), threading.Event()
    real_engine = pipeline.multilevel_partition_resilient

    def held_engine(*a, **kw):
        entered.set()
        release.wait(60)
        return real_engine(*a, **kw)

    monkeypatch.setattr(pipeline, "multilevel_partition_resilient", held_engine)
    out: dict = {}

    def job():
        out["b"], _ = _buffcut_partition_pipelined(
            g, cfg, PipelineConfig(queue_depth=1, prefetch_batches=0)
        )

    t = threading.Thread(target=job)
    t.start()
    try:
        assert entered.wait(30)
        snap = log.settle()
    finally:
        release.set()
        t.join(60)
    assert not t.is_alive()
    assert np.array_equal(out["b"], b_ser)
    return [e for e in snap if isinstance(e, tuple)
            or e in ("buffcut.t2.flush", "buffcut.t2.put_wait")]


@pytest.mark.parametrize("switch_s", [None, 1e-5])
@pytest.mark.parametrize("engine", ["sparse", "jax"])
@pytest.mark.parametrize("queue_depth", [1, 4])
def test_pipelined_matches_serial_on_hub_stream(queue_depth, engine, switch_s, hub_graph):
    """Hubs ride free of the batch slots and T2 stands aside for T3's host
    work; neither changes a label or a counter of the serial driver, also
    with the interpreter switching threads every 10 us."""
    cfg = _hub_cfg(engine)
    b_ser, s_ser = _buffcut_partition(hub_graph, cfg)
    old_switch = sys.getswitchinterval()
    try:
        if switch_s is not None:
            sys.setswitchinterval(switch_s)
        b, s = _buffcut_partition_pipelined(
            hub_graph, cfg, PipelineConfig(queue_depth=queue_depth, prefetch_batches=1)
        )
    finally:
        sys.setswitchinterval(old_switch)
    assert s_ser.n_hubs >= 40
    assert np.array_equal(b_ser, b)
    assert s.cut_weight == s_ser.cut_weight
    assert s.balance == s_ser.balance
    assert (s.n_batches, s.n_hubs) == (s_ser.n_batches, s_ser.n_hubs)


def test_hubs_take_no_batch_slot(hub_graph, monkeypatch):
    """With one batch slot and the engine held, T2 queues every hub that
    follows the queued batch without waiting, and blocks on the batch
    after it: batch 1 in the engine, batch 2 queued, batch 3 waiting."""
    t2 = _t2_steps_behind_a_held_engine(hub_graph, _hub_cfg("sparse"), monkeypatch)
    assert t2[-2:] == ["buffcut.t2.flush", "buffcut.t2.put_wait"]  # blocked on a batch
    waits = [i for i, e in enumerate(t2) if e == "buffcut.t2.put_wait"]
    assert all(t2[i - 1] == "buffcut.t2.flush" for i in waits)  # never on a hub
    flushes = [i for i, e in enumerate(t2) if e == "buffcut.t2.flush"]
    assert len(flushes) == 3
    hubs_after_queued = sum(e == ("rec", True) for e in t2[flushes[1]:flushes[2]])
    assert hubs_after_queued >= 2


def test_queued_hubs_capped_at_a_batch(hub_graph, monkeypatch):
    """Hubs ride free of the batch slots only up to a batch's worth of
    records: on a stream that is mostly hubs, T2 behind a held engine
    blocks on a hub once batch_size of them wait with the queued batch."""
    cfg = _hub_cfg("sparse", d_max=1, batch=4)
    t2 = _t2_steps_behind_a_held_engine(hub_graph, cfg, monkeypatch)
    assert t2[-2:] == [("rec", True), "buffcut.t2.put_wait"]  # blocked on a hub
    # batch 1 in the engine; batch 2 and batch_size hubs queued; one hub waits
    after_first = t2[t2.index("buffcut.t2.flush"):]
    assert after_first.count("buffcut.t2.flush") == 2
    assert after_first.count(("rec", True)) == cfg.batch_size + 1


def test_no_thread_leak_on_worker_failure_behind_the_gate(hub_graph, monkeypatch):
    """A batch model that raises while T2 stands aside at the closed gate
    ends the job with that error within seconds; no pipeline or prefetch
    thread outlives it."""
    log = _Log(monkeypatch)

    def failing_model(*a, **kw):
        t_end = time.monotonic() + 10
        while "buffcut.t2.yield" not in log.events and time.monotonic() < t_end:
            time.sleep(0.01)
        raise RuntimeError("batch model failed")

    monkeypatch.setattr(pipeline, "build_batch_model_from_adj", failing_model)
    baseline = threading.active_count()
    t0 = time.monotonic()
    with pytest.raises(RuntimeError, match="batch model failed"):
        _buffcut_partition_pipelined(hub_graph, _hub_cfg("sparse"), PipelineConfig())
    assert time.monotonic() - t0 < 5.0
    assert "buffcut.t2.yield" in log.events
    assert not _pump_threads(("prefetch-pump", "partition-worker"))
    assert threading.active_count() == baseline
