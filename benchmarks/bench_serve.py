"""Serving-subsystem benchmark (ISSUE 9): resident lookup latency, sustained
update throughput, and post-refine quality vs a from-scratch repartition.

A web-rmat instance (the power-law family the dynamic-graph motivation
targets) is partitioned through `repro.api`, promoted into a resident
`PartitionService`, and driven with the seeded churn workload through a
`ServeSession` — the same path `python -m repro serve` exercises.  Two
replays of the identical op stream run per bench:

* an *untimed* replay on a fresh service that recomputes `edge_cut` on the
  exported graph after **every** update/refine and compares it to the
  resident incremental cut — the exactness invariant, checked at every
  checkpoint, not just at the end;
* the *timed* replay through the session front door, yielding p50/p99
  lookup latency and sustained update throughput (verification stays
  outside the timed regions — `run_workload`'s contract).

Both replays must land on bit-identical labels (service determinism), and
the post-refine cut must stay within `CUT_CEILING` of a from-scratch
repartition of the *mutated* graph — the quality bound that makes
incremental maintenance a real alternative to recomputing.

Results land in the ``serve`` section of BENCH_hotpath.json (merged, not
overwritten).  ``--gate`` (CI) enforces exactness at every checkpoint,
determinism, the cut ceiling, a CI-safe p99 lookup latency ceiling, and a
sustained update-throughput floor.
"""
from __future__ import annotations

import argparse
import json
import os
import sys
import time
from pathlib import Path

import numpy as np

sys.path.insert(0, str(Path(__file__).resolve().parent.parent / "src"))

CUT_CEILING = 1.10        # post-refine cut vs from-scratch on the mutated graph
P99_LOOKUP_MS = 25.0      # CI-safe ceiling; local p99 is tens of microseconds
UPDATES_FLOOR = 1000.0    # sustained edge ops/s through the session


def run_serve(smoke: bool = True) -> dict:
    from repro.graphs import rmat_graph
    from repro.api import partition
    from repro.core import edge_cut
    from repro.serve import ChurnSpec, ServeSession, churn_ops, run_workload

    n = 4096 if smoke else 16384
    k = 8
    g = rmat_graph(n, 8, seed=11)
    bc = {"buffer_size": max(n // 8, 64), "batch_size": max(n // 32, 32)}
    res = partition(g, driver="buffcut", k=k, **bc)
    spec = ChurnSpec(updates=64, ops=16, frac_del=0.25, node_adds=8,
                     lookup_every=2, lookup_size=512, refine_every=8, seed=7)
    ops = churn_ops(g, spec)

    # untimed replay: exactness after every update/refine checkpoint
    checker = res.into_service()
    checkpoints = 0
    exact_all = True
    for kind, payload in ops:
        if kind == "update":
            checker.update(**payload)
        elif kind == "refine":
            checker.refine(payload)
        else:
            continue
        checkpoints += 1
        if checker.cut_weight != edge_cut(checker.export_graph(),
                                          checker.labels):
            exact_all = False

    # timed replay through the session front door
    service = res.into_service()
    with ServeSession(service) as sess:
        summary = run_workload(sess, ops)

    exact_final = bool(
        service.cut_weight == edge_cut(service.export_graph(), service.labels)
    )
    deterministic = bool(np.array_equal(service.labels, checker.labels))

    # from-scratch repartition of the mutated graph: the quality reference
    # and the cost the incremental path avoids paying per churn batch
    mutated = service.export_graph()
    t0 = time.perf_counter()
    scratch = partition(mutated, driver="buffcut", k=k, **bc)
    scratch_s = time.perf_counter() - t0
    cut_vs_scratch = (service.cut_weight / scratch.cut_weight
                      if scratch.cut_weight > 0 else 1.0)

    out = {
        "n": int(service.n),
        "m": int(service.m),
        "k": k,
        "churn": {"updates": spec.updates, "ops_per_update": spec.ops,
                  "frac_del": spec.frac_del, "node_adds": spec.node_adds,
                  "edge_ops": summary["update"]["edge_ops"]},
        "initial_cut": float(res.cut_weight),
        "served_cut": float(service.cut_weight),
        "scratch_cut": float(scratch.cut_weight),
        "cut_vs_scratch": float(cut_vs_scratch),
        "scratch_repartition_s": scratch_s,
        "refine_total_s": summary["refine"]["total_s"],
        "lookup_p50_ms": summary["lookup"]["p50_ms"],
        "lookup_p99_ms": summary["lookup"]["p99_ms"],
        "lookups_per_s": summary["lookup"]["lookups_per_s"],
        "updates_per_s": summary["update"]["updates_per_s"],
        "exact_checkpoints": checkpoints,
        "exact_at_every_checkpoint": bool(exact_all),
        "exact_final": exact_final,
        "deterministic_replay": deterministic,
        "quality_ok": bool(cut_vs_scratch <= CUT_CEILING),
        "latency_ok": bool(summary["lookup"]["p99_ms"] <= P99_LOOKUP_MS),
        "throughput_ok": bool(summary["update"]["updates_per_s"]
                              >= UPDATES_FLOOR),
    }
    return out


def main() -> int:
    from repro.compile_cache import enable_compile_cache

    enable_compile_cache()
    ap = argparse.ArgumentParser()
    ap.add_argument("--smoke", action="store_true",
                    help="CI-sized run; merge into BENCH_hotpath.json")
    ap.add_argument("--gate", action="store_true",
                    help="exit nonzero unless exactness (every checkpoint), "
                         "determinism, the cut ceiling, and the CI-safe "
                         "latency/throughput bounds hold")
    ap.add_argument("--out", default="BENCH_hotpath.json")
    args = ap.parse_args()
    r = run_serve(smoke=args.smoke or args.gate)
    print(json.dumps(r, indent=2))
    report = {}
    if os.path.exists(args.out):
        report = json.loads(Path(args.out).read_text())
    report["serve"] = r
    Path(args.out).write_text(json.dumps(report, indent=2) + "\n")
    if args.gate:
        ok = (
            r["exact_at_every_checkpoint"] and r["exact_final"]
            and r["deterministic_replay"] and r["quality_ok"]
            and r["latency_ok"] and r["throughput_ok"]
        )
        if not ok:
            print("SERVE GATE FAILED", file=sys.stderr)
            return 1
        print(
            f"serve gate OK: exact at {r['exact_checkpoints']} checkpoints, "
            f"deterministic replay, cut {r['cut_vs_scratch']:.3f}x "
            f"from-scratch (ceiling {CUT_CEILING}x), lookup p99 "
            f"{r['lookup_p99_ms']:.3f} ms (ceiling {P99_LOOKUP_MS} ms), "
            f"{r['updates_per_s']:.0f} edge ops/s (floor {UPDATES_FLOOR:.0f})"
        )
    return 0


if __name__ == "__main__":
    sys.exit(main())
