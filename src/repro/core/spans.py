"""Named host spans at the stage boundaries of the pipelined driver and the
device V-cycle engine.

`span(name, into)` enters `jax.profiler.TraceAnnotation(name)`, so while a
profiler session records, the span lands on the profiler's host plane, on
the same clock as the device's operations; with `into` a dict it also adds
its host-clock seconds to `into[name]` (the drivers' `StreamStats.stage_s`).
When JAX has not been imported no profiler can be recording, so the span
only keeps time and a numpy-only run never imports JAX for it.  With no
profiler recording a span costs a microsecond or two of host time; sites
are per block, batch, task or device pull, never per stream record.

The names (T1, T2 and T3 are the pipelined driver's threads, core/pipeline.py;
each name carries its thread, since a trace shows every Python thread's line
under the same name):

  buffcut.t1.read        T1 reads and parses one block of records
                         (core/prefetch.py `PrefetchStream._pump`)
  buffcut.t2.block       T2's per-record loop over one block (or the
                         buffer's final drain): scoring, buffer insert
                         and extract, batch selection
  buffcut.t2.flush       T2 hands a batch to T3: adjacency slice, payload,
                         enqueue
  buffcut.t2.put_wait    T2 blocked behind T3 on a batch slot of the task
                         queue (or on a queue holding a batch's worth
                         of hubs)
  buffcut.t3.task_wait   T3 waiting for its next task, starved by T1 or T2
  buffcut.t3.batch_model T3 builds the batch model
  buffcut.t3.vcycle      T3's call of the multilevel engine on one batch
  buffcut.t3.commit      T3 commits a batch: labels, loads, cut, IER
  buffcut.t3.hub         T3 places one hub (degree above d_max) by Fennel
  vcycle.pack            the jax engine packs the batch and copies it up,
                         from entry to the first level's dispatch
                         (core/multilevel_jax.py)
  vcycle.sync            the jax engine's host waits on one device->host
                         pull: a level's scalars, the coarsest degree,
                         the labels back

These are the stages, `SPANS`.  A span nested inside a stage is not a
stage of its own:

  vcycle.pack_ell        inside vcycle.pack: the jax engine packs the
                         level-0 ELL tiles and copies them up, in a batch
                         whose level 0 aggregates through them
  buffcut.t2.yield       inside buffcut.t2.block: T2 stands aside at the
                         pipelined driver's gate while T3 runs host work
                         (batch model, commit, hubs); entered only when T2
                         blocks there, so its count is how often T2 stood
                         aside and its time what the gate cost T2
"""
from __future__ import annotations

import sys
import time

SPANS = (
    "buffcut.t1.read",
    "buffcut.t2.block",
    "buffcut.t2.flush",
    "buffcut.t2.put_wait",
    "buffcut.t3.task_wait",
    "buffcut.t3.batch_model",
    "buffcut.t3.vcycle",
    "buffcut.t3.commit",
    "buffcut.t3.hub",
    "vcycle.pack",
    "vcycle.sync",
)


class span:
    """Context manager: one named span, optionally timed into a dict."""

    __slots__ = ("_name", "_into", "_ann", "_t0")

    def __init__(self, name: str, into: dict | None = None):
        self._name = name
        self._into = into
        self._ann = None

    def __enter__(self) -> "span":
        profiler = sys.modules.get("jax.profiler")
        if profiler is not None:
            self._ann = profiler.TraceAnnotation(self._name)
            self._ann.__enter__()
        self._t0 = time.perf_counter()
        return self

    def __exit__(self, *exc) -> None:
        if self._into is not None:
            self._into[self._name] = (self._into.get(self._name, 0.0)
                                      + time.perf_counter() - self._t0)
        if self._ann is not None:
            self._ann.__exit__(*exc)
