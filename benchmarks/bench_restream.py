"""Paper Table 2: parallelization + restreaming trade-offs (random order),
plus the out-of-core restream section (ISSUE 5).

Claims reproduced: the pipelined driver matches sequential quality (paper:
20.29 vs 20.48 cut%); restreaming passes monotonically improve cut at
linear-ish runtime growth (paper: 2 streams -14.6% cut at 1.44x runtime),
because later passes skip buffering; *prioritized* replay (Awadelkarim &
Ugander, arXiv:2007.03131) is exposed as the `restream_order` knob and
benchmarked against stream order.

Out-of-core section (``--smoke`` / ``--gate``): a disk-resident grid 16x the
buffer is partitioned and restreamed straight from `DiskNodeStream`; the
measured restream peak resident (batch / priority-buffer adjacency +
read-ahead + transient model) must stay under the modeled ceiling, labels
must bit-match the in-memory restream, and the incrementally maintained cut
must equal an offline recompute.  Results land in the ``restream_outofcore``
section of BENCH_hotpath.json (merged, not overwritten); ``--gate`` is the
CI smoke.
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


def run(verbose: bool = True) -> list[str]:
    from repro.graphs import apply_order, random_order
    from repro.api import partition
    from repro.core import restream, cut_ratio
    from benchmarks.common import tuning_set, default_cfg, csv_row, gmean_over_instances

    rows = []
    seq_cut, seq_rt, par_cut, par_rt = {}, {}, {}, {}
    stream_cut = {p: {} for p in range(1, 6)}
    stream_rt = {p: {} for p in range(1, 6)}
    prio_cut, prio_rt = {}, {}
    for gname, g in tuning_set().items():
        gr = apply_order(g, random_order(g, 100))
        cfg = default_cfg(g)
        t0 = time.perf_counter(); res_seq = partition(gr, cfg, driver="buffcut")
        seq_rt[gname] = time.perf_counter() - t0
        seq_cut[gname] = res_seq.cut_ratio * 100
        t0 = time.perf_counter(); res_par = partition(gr, cfg, driver="buffcut-pipe")
        par_rt[gname] = time.perf_counter() - t0
        par_cut[gname] = res_par.cut_ratio * 100
        block = res_seq.labels
        t_pass = seq_rt[gname]
        stream_cut[1][gname] = seq_cut[gname]
        stream_rt[1][gname] = t_pass
        for p in range(2, 6):
            t0 = time.perf_counter()
            block = restream(gr, block, cfg, 1)
            t_pass += time.perf_counter() - t0
            stream_cut[p][gname] = cut_ratio(gr, block) * 100
            stream_rt[p][gname] = t_pass
        # prioritized replay, same pass budget as the 2-streams row
        t0 = time.perf_counter()
        bp = restream(gr, res_seq.labels, cfg, 1, order="priority")
        prio_rt[gname] = seq_rt[gname] + time.perf_counter() - t0
        prio_cut[gname] = cut_ratio(gr, bp) * 100
    rows.append(csv_row("table2/sequential", gmean_over_instances(seq_rt) * 1e6,
                        f"cut%={gmean_over_instances(seq_cut):.2f}"))
    rows.append(csv_row("table2/parallel", gmean_over_instances(par_rt) * 1e6,
                        f"cut%={gmean_over_instances(par_cut):.2f}"))
    base_rt = gmean_over_instances(stream_rt[1])
    for p in range(1, 6):
        c = gmean_over_instances(stream_cut[p])
        rt = gmean_over_instances(stream_rt[p])
        rows.append(csv_row(f"table2/{p}_streams", rt * 1e6,
                            f"cut%={c:.2f};rel_runtime={rt/base_rt:.2f}x"))
    rows.append(csv_row("table2/2_streams_priority",
                        gmean_over_instances(prio_rt) * 1e6,
                        f"cut%={gmean_over_instances(prio_cut):.2f};"
                        f"rel_runtime={gmean_over_instances(prio_rt)/base_rt:.2f}x"))
    if verbose:
        for r in rows:
            print(r, flush=True)
    return rows


# ----------------------------------------------------------- out-of-core


def restream_resident_bound(cfg, max_deg: int, io_chunk_bytes: int) -> int:
    """Restream residency ceiling: the priority buffer (stream order uses
    none) + the batch adjacency at cache dtypes (transiently doubled by the
    model graph) + the reader window.  Labels (O(n)) and loads (O(k)) are
    the streaming budget, as in the first pass."""
    per_node = max_deg * 16 + 96
    return (cfg.buffer_size + 2 * cfg.batch_size + 2) * per_node \
        + 2 * io_chunk_bytes + per_node


def run_outofcore(smoke: bool = True, passes: int = 2) -> dict:
    from repro.graphs import DiskNodeStream, grid_mesh_to_disk, read_packed
    from repro.core import BuffCutConfig, edge_cut, restream_refine
    from repro.core.buffcut import _buffcut_partition

    side = 64 if smoke else 160            # n = 4096 / 25600
    io_chunk = 1 << 12
    cfg = BuffCutConfig(k=4, buffer_size=256, batch_size=128, d_max=64)
    out: dict = {"orders": {}}
    with tempfile.TemporaryDirectory() as tmp:
        path = os.path.join(tmp, "grid.bcsr")
        grid_mesh_to_disk(side, path)
        file_bytes = os.path.getsize(path)
        stream = DiskNodeStream(path, io_chunk_bytes=io_chunk)
        b0, s0 = _buffcut_partition(stream, cfg)
        bound = restream_resident_bound(cfg, max_deg=8, io_chunk_bytes=io_chunk)
        g = read_packed(path)               # oracle only; the run stays on disk
        b0_mem, s0_mem = _buffcut_partition(g, cfg)
        out.update({
            "n": int(stream.n), "m": int(stream.m),
            "graph_over_buffer": float(stream.n / cfg.buffer_size),
            "file_bytes": int(file_bytes),
            "passes": passes,
            "resident_bound_bytes": int(bound),
        })
        for order in ("stream", "priority"):
            t0 = time.perf_counter()
            b1, info = restream_refine(
                stream, b0, cfg, passes, order=order,
                initial_cut=s0.cut_weight,
                initial_loads=np.asarray(s0.block_loads),
            )
            rt = time.perf_counter() - t0
            b1_mem, _ = restream_refine(
                g, b0_mem, cfg, passes, order=order,
                initial_cut=s0_mem.cut_weight,
                initial_loads=np.asarray(s0_mem.block_loads),
            )
            exact = edge_cut(g, b1)
            out["orders"][order] = {
                "restream_s": rt,
                "cut_before": float(s0.cut_weight),
                "cut_after": float(info.cut_weight),
                "cut_exact_recompute": float(exact),
                "cut_is_exact": bool(np.isclose(info.cut_weight, exact)),
                "peak_resident_bytes": int(info.peak_resident_bytes),
                "within_bound": bool(info.peak_resident_bytes <= bound),
                "labels_match_memory": bool(np.array_equal(b1, b1_mem)),
                "stream_bytes_read": int(info.stream_bytes_read),
                "moved_per_pass": [p["moved"] for p in info.passes],
            }
        out["within_bound"] = all(o["within_bound"] for o in out["orders"].values())
        out["labels_match_memory"] = all(
            o["labels_match_memory"] for o in out["orders"].values()
        )
        out["cut_is_exact"] = all(o["cut_is_exact"] for o in out["orders"].values())
    return out


def main() -> int:
    from repro.compile_cache import enable_compile_cache

    enable_compile_cache()
    ap = argparse.ArgumentParser()
    ap.add_argument("--smoke", action="store_true",
                    help="small out-of-core run; merge into BENCH_hotpath.json")
    ap.add_argument("--gate", action="store_true",
                    help="exit nonzero unless peak resident <= bound, labels "
                         "bit-match memory and the incremental cut is exact (CI)")
    ap.add_argument("--table2", action="store_true",
                    help="also run the (slow) Table 2 sweep")
    ap.add_argument("--out", default="BENCH_hotpath.json")
    args = ap.parse_args()
    if args.table2 or not (args.smoke or args.gate):
        run()
        if not (args.smoke or args.gate):
            return 0
    r = run_outofcore(smoke=args.smoke)
    print(json.dumps(r, indent=2))
    report = {}
    if os.path.exists(args.out):
        report = json.loads(Path(args.out).read_text())
    report["restream_outofcore"] = r
    Path(args.out).write_text(json.dumps(report, indent=2) + "\n")
    if args.gate:
        ok = r["within_bound"] and r["labels_match_memory"] and r["cut_is_exact"]
        if not ok:
            print("RESTREAM OUT-OF-CORE GATE FAILED", file=sys.stderr)
            return 1
        peak = max(o["peak_resident_bytes"] for o in r["orders"].values())
        print(
            f"restream gate OK: peak {peak}b <= bound {r['resident_bound_bytes']}b "
            f"on a {r['graph_over_buffer']:.0f}x-buffer graph, labels bit-match "
            f"memory, incremental cut exact over {r['passes']} passes x "
            f"{list(r['orders'])}"
        )
    return 0


if __name__ == "__main__":
    sys.exit(main())
