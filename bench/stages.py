#!/usr/bin/env python3
"""The program's own stage spans in a profiler trace, and what the device
waited for under each.

The program marks the stages of its pipelined driver and of its V-cycle
engine with `jax.profiler.TraceAnnotation` spans (src/repro/core/spans.py
names and documents them), on the profiler's host plane and so on the
device trace's clock.  `extract_spans` reads them, with the host line
(thread) that holds each, and `reduce_events` adds to bench/trace.py's
reduction of the window:

  spans         per span name present in the window: `union_s`, the union
                of its intervals clipped to the window; `count`, the spans
                wholly inside it; `sum_s`, their summed seconds;
  idle_by_span  the window's device-idle seconds by the innermost
                partition-worker (`buffcut.t3.*`) or engine (`vcycle.*`)
                span over them, `none` where no such span is; the values
                sum to window_s - busy_s;
  t3_cover_s    the union of the partition worker's spans in the window;
  idle_gaps     bench/trace.py's longest gaps, each label followed by
                " / <span>", the innermost such span at the gap's midpoint,
                where one is there.

The readers `task_wait_share`, `batch_model_ms`, `commit_ms`, `pack_ms`,
`syncs_per_batch` and `vcycle_idle_share` in bench/metrics/ read these keys
and return None on a trace without the spans.  They are not entries of
BENCHMARK.json: bench/run.py reduces its trace with bench/trace.py, which
extracts the harness's own spans only.  This file runs a cell with them:

    python3 bench/stages.py --workload g500-s20.random --seed 7 \\
        --seconds 45 --traces 1 0

runs the cell once per entry of --traces in one process (1: traced, with
this reduction; 0: untraced) and prints one result line per run, carrying
the benchmark's end-to-end metrics, its per-layer metrics and the six
above on a traced run, and the stage reduction under `stages`.
"""
from __future__ import annotations

import argparse
import bisect
import json
import sys
import time
from pathlib import Path

ROOT = Path(__file__).resolve().parents[1]
sys.path.insert(0, str(ROOT))
sys.path.insert(0, str(ROOT / "src"))

from bench import trace  # noqa: E402

# the program's span names (src/repro/core/spans.py SPANS, held equal by
# bench/test_bench_stages.py)
PROGRAM_SPANS = (
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
T3 = tuple(s for s in PROGRAM_SPANS if s.startswith("buffcut.t3."))
ENGINE = tuple(s for s in PROGRAM_SPANS if s.startswith("vcycle."))
NONE = "none"

# the per-layer entries the six readers would take in BENCHMARK.json
METRICS = [
    {"name": "task_wait_share", "unit": "%", "better": "lower", "source": "host_clock",
     "layer": "stream, buffer and batch selection (T1/T2 host)", "moves": "nodes_per_s"},
    {"name": "batch_model_ms", "unit": "ms", "better": "lower", "source": "host_clock",
     "layer": "batch model (T3 host)", "moves": "nodes_per_s"},
    {"name": "commit_ms", "unit": "ms", "better": "lower", "source": "host_clock",
     "layer": "commit (T3 host)", "moves": "nodes_per_s"},
    {"name": "pack_ms", "unit": "ms", "better": "lower", "source": "host_clock",
     "layer": "V-cycle engine call: packing and copy up", "moves": "nodes_per_s"},
    {"name": "syncs_per_batch", "unit": "count", "better": "lower", "source": "program_counter",
     "layer": "V-cycle engine call: level loop", "moves": "nodes_per_s"},
    {"name": "vcycle_idle_share", "unit": "%", "better": "lower", "source": "device_trace",
     "layer": "V-cycle engine call: level loop", "moves": "nodes_per_s"},
]


def extract_spans(path: str, names: tuple = PROGRAM_SPANS) -> list:
    """[name, start_ns, dur_ns, line] of every span named in `names` on a
    host plane; `line` names the plane and the index of its line, one line
    per thread."""
    import jax

    pd = jax.profiler.ProfileData.from_file(path)
    out = []
    for plane in pd.planes:
        if plane.name.startswith("/host:"):
            for i, line in enumerate(plane.lines):
                key = f"{plane.name}#{i}"
                out.extend([e.name, e.start_ns, e.duration_ns, key]
                           for e in line.events if e.name in names)
    return out


def _innermost(segments: list, spans: list) -> list:
    """Label of each elementary segment [a, b): the covering span that
    started last (the shortest on a tie), or NONE."""
    labels = []
    for a, b in segments:
        best = None
        for s, e, name in spans:
            if s <= a and b <= e and (best is None or (s, -e) > best[:2]):
                best = (s, -e, name)
        labels.append(best[2] if best is not None else NONE)
    return labels


def stage_keys(ev: dict, window_span: str, inner_span: str,
               top: int = 10) -> dict:
    """The keys this module adds to bench/trace.py's reduction of the same
    events (whose window span is there)."""
    win = [s for s in ev["spans"] if s[0] == window_span]
    lo, hi = win[0][1], win[0][1] + win[0][2]
    prog = ev.get("program_spans", [])
    spans = {}
    for name in PROGRAM_SPANS:
        iv = [(s, s + d) for n, s, d, _ in prog if n == name]
        union = sum(e - s for s, e in trace._union(iv, lo, hi))
        inside = [e - s for s, e in iv if lo <= s and e <= hi]
        if union > 0 or inside:
            spans[name] = {"union_s": union * 1e-9, "count": len(inside),
                           "sum_s": sum(inside) * 1e-9}
    t3_cover = sum(e - s for s, e in trace._union(
        [(s, s + d) for n, s, d, _ in prog if n in T3], lo, hi))

    # device-idle intervals of the window, as bench/trace.py finds them
    ops = [(s, s + d) for s, d in ev["ops"] if lo <= s < hi]
    busy = trace._union(ops, lo, hi)
    edges = [lo] + [x for iv in busy for x in iv] + [hi]
    idle = [(s, e) for s, e in zip(edges[0::2], edges[1::2]) if e > s]

    # the innermost T3 or engine span, piecewise constant between their ends
    marks = [(max(s, lo), min(s + d, hi), n) for n, s, d, _ in prog
             if n in T3 + ENGINE and s < hi and s + d > lo]
    points = sorted({lo, hi, *(x for s, e, _ in marks for x in (s, e))})
    segments = list(zip(points[:-1], points[1:]))
    labels = _innermost(segments, marks)
    ends = [b for _, b in segments]

    def label_at(x):
        i = bisect.bisect_right(ends, x)
        return labels[i] if i < len(labels) else NONE

    idle_by_span: dict[str, float] = {}
    for a, b in idle:
        i = bisect.bisect_right(ends, a)
        while i < len(segments) and segments[i][0] < b:
            s, e = segments[i]
            part = min(e, b) - max(s, a)
            if part > 0:
                idle_by_span[labels[i]] = idle_by_span.get(labels[i], 0.0) + part * 1e-9
            i += 1

    inner = sorted((s, s + d) for name, s, d in ev["spans"] if name == inner_span)
    gaps = []
    for s, e in idle:
        mid = (s + e) / 2
        inside = any(a <= mid < b for a, b in inner)
        where = f"{'inside' if inside else 'outside'} {inner_span}"
        at = label_at(mid)
        if at != NONE:
            where += f" / {at}"
        gaps.append([where, (e - s) * 1e-9])
    return {"spans": spans, "idle_by_span": idle_by_span, "t3_cover_s": t3_cover * 1e-9,
            "idle_gaps": sorted(gaps, key=lambda g: -g[1])[:top]}


def reduce_events(ev: dict, window_span: str, inner_span: str,
                  top: int = 10) -> dict | None:
    """bench/trace.py's reduction with the stage keys added (and its gap
    labels extended); None where that reduction is None."""
    r = trace.reduce_events(ev, window_span, inner_span, top)
    if r is None:
        return None
    return {**r, **stage_keys(ev, window_span, inner_span, top)}


def reduce_trace(path: str, window_span: str, inner_span: str) -> dict | None:
    ev = trace.extract(path, (window_span, inner_span))
    ev["program_spans"] = extract_spans(path)
    return reduce_events(ev, window_span, inner_span)


def span_stats(m: dict, name: str) -> dict | None:
    """The reduction's entry for one span name in a run's record, or None
    where the run was not traced or the trace holds no such span."""
    t = m.get("trace")
    if not t or "spans" not in t or t["window_s"] <= 0:
        return None
    return t["spans"].get(name)


def run_cell(workload: str, seed: int, seconds: float, traced: bool, **kw) -> dict:
    """One run of the cell through bench/run.py, reduced by this module and
    reporting the six metrics besides the benchmark's own."""
    from bench import harness, run

    spec = kw.pop("spec", None) or harness.load_json(ROOT / "BENCHMARK.json")
    spec = {**spec, "per_layer": spec["per_layer"] + METRICS + spec["end_to_end"]}
    kept = {}

    def reduce(path, window_span, inner_span):
        kept["r"] = reduce_trace(path, window_span, inner_span)
        return kept["r"]

    real = trace.reduce_trace
    trace.reduce_trace = reduce
    try:
        result = run.run(workload, seed, seconds, traced, spec=spec, **kw)
    finally:
        trace.reduce_trace = real
    r = kept.get("r")
    if r is not None:
        result["stages"] = {"spans": r["spans"], "idle_by_span": r["idle_by_span"],
                            "t3_cover_share": 100.0 * r["t3_cover_s"] / r["window_s"]}
    return result


def main(argv=None) -> int:
    ap = argparse.ArgumentParser(description=__doc__.split("\n\n")[0])
    ap.add_argument("--workload", required=True)
    ap.add_argument("--seed", type=int, required=True)
    ap.add_argument("--seconds", type=float, required=True)
    ap.add_argument("--traces", type=int, nargs="+", choices=(0, 1), default=[1])
    args = ap.parse_args(argv)
    from bench import run
    from bench.harness import NoChip

    t_start = run.T_PROCESS
    for traced in args.traces:
        try:
            result = run_cell(args.workload, args.seed, args.seconds, bool(traced),
                              t_start=t_start)
        except NoChip as e:
            print(f"bench: {e}", file=sys.stderr, flush=True)
            return 2
        result["trace"] = traced
        print(json.dumps(result), flush=True)
        t_start = time.perf_counter()  # the next run's set-up starts here
    return 0


if __name__ == "__main__":
    sys.exit(main())
