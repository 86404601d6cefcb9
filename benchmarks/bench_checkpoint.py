"""Checkpoint overhead + crash-resume bench (ISSUE 6).

Measures what crash safety costs: the same out-of-core partition (disk grid,
driver + 2 restream passes) runs plain and with checkpointing at a sweep of
``checkpoint_every`` cadences, best-of-N wall clock each.  Each checkpointed
run must land on bit-identical labels, and resuming its final on-disk
snapshot must land on the same labels again — the recovery path is exercised
on every bench run, not only in the test suite.  Resume latency is split
into snapshot rehydration (load + CRC verify + unpack) and the full
resumed-run wall clock.

Snapshot cost is O(n) (the label array dominates the payload) while the
snapshot *count* is fixed per δ-batch, so relative overhead rises with graph
size at a fixed cadence; the sweep is the guidance for picking ``every``.
EXPERIMENTS.md §Checkpoint records the measured curve.

Results land in the ``checkpoint`` section of BENCH_hotpath.json (merged,
not overwritten).  ``--gate`` is the CI smoke: bit-identical labels with and
without checkpointing, successful resumes, and dense-cadence overhead under
a bound that's generous for shared-runner jitter.

Usage:  python benchmarks/bench_checkpoint.py [--smoke] [--gate] [--out PATH]
"""
from __future__ import annotations

import argparse
import json
import os
import sys
import tempfile
import time
from pathlib import Path

import numpy as np

sys.path.insert(0, str(Path(__file__).resolve().parent.parent / "src"))

# CI smoke bound on the densest cadence (every=8): measured ~4% on the smoke
# graph, but a best-of-3 on a loaded shared runner jitters on a ~1 s run
GATE_MAX_OVERHEAD = 0.15


def _best_of(fn, reps: int):
    best, last = float("inf"), None
    for _ in range(reps):
        t0 = time.perf_counter()
        last = fn()
        best = min(best, time.perf_counter() - t0)
    return best, last


def run(smoke: bool = True, reps: int = 3) -> dict:
    from repro.api import partition, resume
    from repro.core.checkpoint import load_checkpoint
    from repro.graphs import grid_mesh_to_disk

    side = 64 if smoke else 160            # n = 4096 / 25600
    sweep = (8, 32) if smoke else (8, 16, 32)
    kw = dict(
        driver="buffcut", k=4, buffer_size=256, batch_size=128, d_max=64.0,
        restream_passes=2, restream_order="priority",
    )
    out: dict = {"n": side * side, "reps": reps, "every": {}}
    with tempfile.TemporaryDirectory() as tmp:
        path = os.path.join(tmp, "grid.bcsr")
        grid_mesh_to_disk(side, path)
        t_plain, base = _best_of(lambda: partition(path, **kw), reps)
        out["plain_s"] = t_plain
        for every in sweep:
            cp = os.path.join(tmp, f"run-{every}.ckpt")
            t_ckpt, chk = _best_of(
                lambda cp=cp, every=every: partition(
                    path, checkpoint_path=cp, checkpoint_every=every, **kw),
                reps,
            )
            # crash-resume: the last snapshot on disk is a mid-restream
            # state; resuming it must rejoin the trajectory exactly
            t_load, _ = _best_of(lambda: load_checkpoint(cp), reps)
            t0 = time.perf_counter()
            res = resume(cp)
            t_resume = time.perf_counter() - t0
            out["every"][str(every)] = {
                "checkpoint_s": t_ckpt,
                "overhead": t_ckpt / t_plain - 1.0,
                "checkpoints_written": int(chk.stats.checkpoints_written),
                "ckpt_file_bytes": int(os.path.getsize(cp)),
                "rehydrate_s": t_load,
                "resume_s": t_resume,
                "labels_match_plain": bool(np.array_equal(chk.labels, base.labels)),
                "resume_bit_identical": bool(np.array_equal(res.labels, base.labels)),
            }
    rows = out["every"].values()
    out["labels_match_plain"] = all(r["labels_match_plain"] for r in rows)
    out["resume_bit_identical"] = all(r["resume_bit_identical"] for r in rows)
    out["overhead_densest"] = out["every"][str(min(sweep))]["overhead"]
    return out


def main() -> int:
    from repro.compile_cache import enable_compile_cache

    enable_compile_cache()
    ap = argparse.ArgumentParser()
    ap.add_argument("--smoke", action="store_true",
                    help="CI-sized run; merge into BENCH_hotpath.json")
    ap.add_argument("--gate", action="store_true",
                    help="exit nonzero unless checkpointing is bit-transparent, "
                         "every resume bit-matches, and densest-cadence "
                         f"overhead <= {GATE_MAX_OVERHEAD:.0%} (CI)")
    ap.add_argument("--out", default="BENCH_hotpath.json")
    args = ap.parse_args()
    r = run(smoke=args.smoke or args.gate)
    print(json.dumps(r, indent=2))
    report = {}
    if os.path.exists(args.out):
        report = json.loads(Path(args.out).read_text())
    report["checkpoint"] = r
    Path(args.out).write_text(json.dumps(report, indent=2) + "\n")
    if args.gate:
        ok = (r["labels_match_plain"] and r["resume_bit_identical"]
              and all(row["checkpoints_written"] >= 1 for row in r["every"].values())
              and r["overhead_densest"] <= GATE_MAX_OVERHEAD)
        if not ok:
            print("CHECKPOINT GATE FAILED", file=sys.stderr)
            return 1
        parts = ", ".join(
            f"every={e}: {row['overhead']:+.1%} ({row['checkpoints_written']} snaps)"
            for e, row in r["every"].items()
        )
        print(f"checkpoint gate OK: {parts}; labels bit-identical with and "
              f"without checkpointing, every resume bit-identical")
    return 0


if __name__ == "__main__":
    sys.exit(main())
