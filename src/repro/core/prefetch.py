"""Double-buffered stream prefetcher — stage (1) of the pipelined hot path.

`PrefetchStream` wraps any `NodeStreamBase` and moves record parsing onto a
background thread: while the consumer (a driver's score/evict/assign loop)
processes block *i*, the pump thread is already parsing block *i+1* from
disk.  Records travel through a bounded queue in **blocks** (default: the
driver's δ-batch size), not one at a time — a `queue.Queue` handoff costs
microseconds, which at per-record granularity would eat the entire win.

Semantics are deliberately boring — this class changes *when* records are
parsed, never *what* they contain:

* Records are yielded in exactly the order the inner stream produces them;
  labels downstream are bit-identical to the unwrapped stream (pinned by
  tests/test_stream_conformance.py across `prefetch_batches` settings).
* `tell()` returns the inner stream's resume token captured immediately
  after the last record the **consumer** has seen — not however far ahead
  the pump has read — so checkpoint/resume tokens mean the same thing with
  and without prefetching.
* `resident_bytes` counts the inner stream's residency **plus** every
  record currently staged in the queue or the consumer's current block, so
  the paper's memory accounting keeps seeing the true footprint.  The
  staging cost is bounded by `(depth + 1) * block` records.
* Pump-thread exceptions (parse errors, IO faults, truncation) are
  re-raised in the consumer at the position they occurred; the pump thread
  is joined on every exit path — normal exhaustion, consumer `break`,
  consumer exception — so no run leaks a thread
  (tests/test_prefetch.py::test_no_thread_leak_*).

`depth` maps 1:1 to `PipelineConfig.prefetch_batches`: 0 means "do not
wrap" (callers skip construction entirely), 1 is classic double buffering,
larger values deepen the read-ahead window.
"""
from __future__ import annotations

import itertools
import queue
import threading
from collections.abc import Iterator

import numpy as np

from repro.core.spans import span
from repro.graphs.stream import NodeStreamBase

# queue poll granularity: how often a blocked pump/consumer re-checks the
# stop event. Coarse is fine — it only bounds shutdown latency.
_POLL_S = 0.05
_JOIN_TIMEOUT_S = 5.0

# sentinel kinds on the queue
_BLOCK = 0
_DONE = 1
_ERR = 2


def _record_bytes(rec: tuple) -> int:
    """Staging cost of one queued record: its two arrays plus tuple/token
    overhead (same 32-byte fudge `AdjacencyCache.put` uses per entry)."""
    _, nbrs, w, _ = rec
    return int(nbrs.nbytes + w.nbytes + 64)


class PrefetchStream(NodeStreamBase):
    """Background-thread read-ahead over any node stream, block-granular.

    One iteration at a time: starting a new `__iter__`/`iter_from`/`blocks`
    shuts down the previous pump first (restream's multi-pass replay reuses
    the same wrapper once per pass, serially).
    """

    def __init__(self, inner: NodeStreamBase, *, depth: int, block: int = 256):
        if depth < 1:
            raise ValueError(f"prefetch depth must be >= 1, got {depth}")
        if block < 1:
            raise ValueError(f"prefetch block must be >= 1, got {block}")
        self._inner = inner
        self._depth = int(depth)
        self._block = int(block)
        self.n = inner.n
        self.m = inner.m
        self.has_edge_w = inner.has_edge_w
        self.has_node_w = inner.has_node_w
        self._q: "queue.Queue | None" = None
        self._thread: "threading.Thread | None" = None
        self._stop = threading.Event()
        self._staged_lock = threading.Lock()
        self._staged_bytes = 0
        self._last_pos: "dict | None" = None

    # ------------------------------------------------------- forwarded state
    @property
    def n_total(self) -> float:
        return self._inner.n_total

    @property
    def m_total(self) -> float:
        return self._inner.m_total

    @property
    def resident_bytes(self) -> int:
        return self._inner.resident_bytes + self._staged_bytes

    @property
    def bytes_read(self) -> int:
        return self._inner.bytes_read

    @property
    def io_retries(self) -> int:
        return getattr(self._inner, "io_retries", 0)

    def tell(self) -> dict:
        if self._last_pos is None:
            # no record consumed yet this iteration — the inner stream's
            # cursor is pump-side and would lie; there is nothing to resume
            raise NotImplementedError(
                "PrefetchStream.tell() before the first consumed record"
            )
        return dict(self._last_pos)

    # ------------------------------------------------------------- the pump
    def _pump(self, records: Iterator, q: "queue.Queue", stop: threading.Event) -> None:
        """Drain `records` into `q` in blocks, capturing the inner stream's
        resume token after every record (tokens ride alongside records so
        the consumer-side `tell()` is exact)."""
        inner = self._inner
        block_n = self._block
        try:
            while True:
                recs: list = []
                toks: list = []
                nbytes = 0
                with span("buffcut.t1.read"):
                    for rec in itertools.islice(records, block_n):
                        try:
                            toks.append(inner.tell())
                        except NotImplementedError:
                            toks.append(None)
                        recs.append(rec)
                        nbytes += _record_bytes(rec)
                if recs and not self._put(q, stop, (_BLOCK, recs, toks, nbytes)):
                    return
                if len(recs) < block_n:
                    break
            self._put(q, stop, (_DONE, None, None, 0))
        except BaseException as exc:  # noqa: BLE001 — forwarded, not dropped
            self._put(q, stop, (_ERR, exc, None, 0))

    def _put(self, q: "queue.Queue", stop: threading.Event, item: tuple) -> bool:
        if item[0] == _BLOCK:
            with self._staged_lock:
                self._staged_bytes += item[3]
        while not stop.is_set():
            try:
                q.put(item, timeout=_POLL_S)
                return True
            except queue.Full:
                continue
        # consumer went away: undo the staging accounting for this block
        if item[0] == _BLOCK:
            with self._staged_lock:
                self._staged_bytes -= item[3]
        return False

    def _start(self, records: Iterator) -> "queue.Queue":
        self._shutdown()
        self._stop = threading.Event()
        with self._staged_lock:
            self._staged_bytes = 0
        q: "queue.Queue" = queue.Queue(maxsize=self._depth)
        t = threading.Thread(
            target=self._pump,
            args=(records, q, self._stop),
            name="prefetch-pump",
            daemon=True,
        )
        self._q, self._thread = q, t
        t.start()
        return q

    def _shutdown(self) -> None:
        """Stop and join the active pump (idempotent, called on every exit
        path). Drains the queue so a pump blocked on put() wakes up."""
        t, q = self._thread, self._q
        if t is None:
            return
        self._stop.set()
        while t.is_alive():
            try:
                if q is not None:
                    q.get_nowait()
            except queue.Empty:
                pass
            t.join(timeout=_POLL_S)
            if not t.is_alive():
                break
        t.join(timeout=_JOIN_TIMEOUT_S)
        self._thread = None
        self._q = None
        with self._staged_lock:
            self._staged_bytes = 0

    # ---------------------------------------------------------- consumption
    def blocks(self, pos: "dict | None" = None) -> Iterator[tuple[list, list]]:
        """Yield (records, tokens) blocks — the zero-overhead path for the
        pipelined driver, which wants block granularity anyway.  `tokens[i]`
        is the resume token for the record after `records[i]` (None when the
        inner stream is not seekable)."""
        records = iter(self._inner) if pos is None else self._inner.iter_from(dict(pos))
        self._last_pos = dict(pos) if pos is not None else None
        q = self._start(records)
        try:
            while True:
                try:
                    kind, a, b, nbytes = q.get(timeout=_POLL_S)
                except queue.Empty:
                    continue
                if kind == _DONE:
                    return
                if kind == _ERR:
                    raise a
                try:
                    yield a, b
                finally:
                    # consumers own token bookkeeping (record iteration
                    # publishes per-record; the pipelined driver reads the
                    # token list directly) — only the staging bytes retire
                    with self._staged_lock:
                        self._staged_bytes -= nbytes
        finally:
            self._shutdown()

    def close(self) -> None:
        """Deterministically stop and join the pump thread.  Safe to call
        at any time, including when no iteration ever started; drivers call
        this from their ``finally`` so no exit path relies on the daemon
        flag."""
        self._shutdown()

    def _iter_records(self, pos: "dict | None") -> Iterator:
        # the token is published BEFORE the yield so a consumer calling
        # tell() while processing record i sees the token *after* record i —
        # the same cursor semantics as NodeStream.iter_from
        for recs, toks in self.blocks(pos):
            for i, rec in enumerate(recs):
                if toks[i] is not None:
                    self._last_pos = toks[i]
                yield rec

    def __iter__(self) -> Iterator[tuple[int, np.ndarray, np.ndarray, float]]:
        return self._iter_records(None)

    def iter_from(self, pos: dict) -> Iterator[tuple[int, np.ndarray, np.ndarray, float]]:
        return self._iter_records(dict(pos))


def maybe_prefetch(
    stream: NodeStreamBase, prefetch_batches: int, block: int
) -> NodeStreamBase:
    """Wrap `stream` in a PrefetchStream when `prefetch_batches > 0`; the
    shared entry point all four consumers (three drivers + restream) use so
    the knob means the same thing everywhere."""
    if prefetch_batches <= 0:
        return stream
    if isinstance(stream, PrefetchStream):
        return stream
    return PrefetchStream(stream, depth=prefetch_batches, block=block)
