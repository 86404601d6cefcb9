"""Sharded multi-worker scaling + quality (ISSUE 8): nodes/s at
W ∈ {1, 2, 4} and cut degradation vs. single-worker, before and after the
restream reconcile pass.

A disk-resident web-rmat instance (the tuning set's power-law family — a
regular mesh is adversarial for contiguous-range sharding: every strip
re-tiles into its own k clusters and no single restream pass can merge
them) is partitioned through `shard_partition` with the ``process`` backend
(forked workers — real multi-core scaling; the thread backend is GIL-bound
on the ~90%-Python driver and only pins determinism), then reconciled with
two priority-order `restream_refine` passes seeded from the exact merged
cut/loads.  The W=2 run is also replayed on the thread backend and must
produce bit-identical labels — the conformance subset at bench scale.

Results land in the ``sharded`` section of BENCH_hotpath.json (merged, not
overwritten).  ``--gate`` (CI) enforces:

* post-restream cut at W=4 ≤ 1.10x the single-worker post-restream cut,
  and the merged incremental cut exactly equals an offline recompute
  (always enforced);
* W=4 ≥ 2.0x W=1 nodes/s — only where the hardware can deliver it
  (``os.cpu_count() >= 4``); containers with fewer cores get a bounded-
  overhead sanity floor (W=4 ≥ 0.35x W=1) and a loud note in the JSON
  instead of a vacuous pass.
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
sys.path.insert(0, str(Path(__file__).resolve().parent.parent))

SCALING_FLOOR = 2.0       # W=4 vs W=1 nodes/s, when cpu_count >= 4
SANITY_FLOOR = 0.35       # same ratio on starved hardware: overhead bound
CUT_CEILING = 1.10        # post-restream cut at W=4 vs single-worker
WORKER_COUNTS = (1, 2, 4)


RESTREAM_PASSES = 2


def run_sharded(smoke: bool = True) -> dict:
    from repro.graphs import DiskNodeStream, rmat_graph, write_packed
    from repro.core import BuffCutConfig, edge_cut, restream_refine
    from repro.core.multilevel import MultilevelConfig
    from repro.distributed.shard_driver import shard_partition

    n = 4096 if smoke else 16384
    io_chunk = 1 << 12
    # the host engine: forked process workers never run jax
    cfg = BuffCutConfig(k=8, buffer_size=256, batch_size=128, d_max=256,
                        ml=MultilevelConfig(engine="sparse"))
    out: dict = {
        "cpu_count": int(os.cpu_count() or 1),
        "backend": "process",
        "load_sync_every": 4,
        "restream_passes": RESTREAM_PASSES,
        "workers": {},
    }
    with tempfile.TemporaryDirectory() as tmp:
        path = os.path.join(tmp, "rmat.bcsr")
        g = rmat_graph(n, 8, seed=11)    # oracle copy; the runs stay on disk
        write_packed(g, path)
        out["n"], out["m"] = int(g.n), int(g.m)

        post_labels: dict = {}
        for w in WORKER_COUNTS:
            stream = DiskNodeStream(path, io_chunk_bytes=io_chunk)
            t0 = time.perf_counter()
            labels, stats, info = shard_partition(
                stream, cfg, workers=w, load_sync_every=4,
                backend="process" if w > 1 else "thread",
            )
            shard_s = time.perf_counter() - t0
            exact = edge_cut(g, labels)
            t0 = time.perf_counter()
            refined, rinfo = restream_refine(
                DiskNodeStream(path, io_chunk_bytes=io_chunk), labels, cfg,
                RESTREAM_PASSES,
                order="priority",
                initial_cut=stats.cut_weight,
                initial_loads=np.asarray(stats.block_loads),
            )
            restream_s = time.perf_counter() - t0
            post_labels[w] = refined
            out["workers"][f"w{w}"] = {
                "shard_s": shard_s,
                "nodes_per_s": float(g.n / shard_s),
                "cut_pre_restream": float(stats.cut_weight),
                "cut_is_exact": bool(stats.cut_weight == exact),
                "cut_post_restream": float(rinfo.cut_weight),
                "restream_s": restream_s,
                "sync_rounds": info.get("sync_rounds"),
                "balance": float(stats.balance),
            }

        # conformance subset at bench scale: both backends, same labels
        bt, _, _ = shard_partition(
            DiskNodeStream(path, io_chunk_bytes=io_chunk), cfg,
            workers=2, load_sync_every=4, backend="thread",
        )
        bp, _, _ = shard_partition(
            DiskNodeStream(path, io_chunk_bytes=io_chunk), cfg,
            workers=2, load_sync_every=4, backend="process",
        )
        out["backends_bit_identical"] = bool(np.array_equal(bt, bp))

    w1, w4 = out["workers"]["w1"], out["workers"]["w4"]
    out["speedup_w4"] = w4["nodes_per_s"] / w1["nodes_per_s"]
    out["cut_ratio_w4_pre"] = w4["cut_pre_restream"] / w1["cut_pre_restream"]
    out["cut_ratio_w4_post"] = w4["cut_post_restream"] / w1["cut_post_restream"]
    out["scaling_enforced"] = out["cpu_count"] >= 4
    floor = SCALING_FLOOR if out["scaling_enforced"] else SANITY_FLOOR
    out["scaling_floor"] = floor
    out["scaling_ok"] = out["speedup_w4"] >= floor
    if not out["scaling_enforced"]:
        out["note"] = (
            f"only {out['cpu_count']} CPU(s): the {SCALING_FLOOR}x scaling "
            f"floor is unenforceable here, applying the {SANITY_FLOOR}x "
            "bounded-overhead sanity floor instead"
        )
    out["quality_ok"] = out["cut_ratio_w4_post"] <= CUT_CEILING
    out["cut_is_exact"] = all(
        v["cut_is_exact"] for v in out["workers"].values()
    )
    return out


def main() -> int:
    ap = argparse.ArgumentParser()
    ap.add_argument("--smoke", action="store_true",
                    help="CI-sized run; merge into BENCH_hotpath.json")
    ap.add_argument("--gate", action="store_true",
                    help="exit nonzero unless scaling (hardware-aware), "
                         "post-restream quality and cut exactness hold (CI)")
    ap.add_argument("--out", default="BENCH_hotpath.json")
    args = ap.parse_args()
    r = run_sharded(smoke=args.smoke or args.gate)
    print(json.dumps(r, indent=2))
    report = {}
    if os.path.exists(args.out):
        report = json.loads(Path(args.out).read_text())
    report["sharded"] = r
    Path(args.out).write_text(json.dumps(report, indent=2) + "\n")
    if args.gate:
        ok = (
            r["scaling_ok"] and r["quality_ok"] and r["cut_is_exact"]
            and r["backends_bit_identical"]
        )
        if not ok:
            print("SHARDED GATE FAILED", file=sys.stderr)
            return 1
        print(
            f"sharded gate OK: W=4 {r['speedup_w4']:.2f}x W=1 nodes/s "
            f"(floor {r['scaling_floor']}x, {r['cpu_count']} cpu), "
            f"post-restream cut {r['cut_ratio_w4_post']:.3f}x single-worker "
            f"(ceiling {CUT_CEILING}x), merged cut exact, backends "
            "bit-identical"
        )
    return 0


if __name__ == "__main__":
    sys.exit(main())
