"""Render BENCH_hotpath.json into an EXPERIMENTS.md table: every section,
including the `checkpoint` and `restream_outofcore` sections merged in by
bench_checkpoint.py and bench_restream.py.

    python benchmarks/summarize.py hotpath [BENCH_hotpath.json]
"""
from __future__ import annotations

import json
import sys


def hotpath_table(path: str = "BENCH_hotpath.json") -> str:
    """One row per BENCH_hotpath.json section — the headline number, the
    guard it is gated on, and whether the parity/bound checks held."""
    with open(path) as f:
        r = json.load(f)
    out = ["| section | headline | guard | parity/bound |",
           "|---|---|---|---|"]

    h = r.get("histogram")
    if h:
        out.append(f"| histogram | {h['speedup']:.1f}x vs seed (round0) "
                   f"| > 1.2x | exact-match asserted |")
    e = r.get("evict")
    if e:
        out.append(f"| evict | flatness {e['incremental_flatness']:.2f} over n "
                   f"| < 3.0 | scan growth {e['scan_growth']:.2f} |")
    ml = r.get("multilevel")
    if ml:
        out.append(f"| multilevel | jax {ml['jax_over_sparse']:.2f}x sparse "
                   f"| <= 6.0x | identical labels |")
    e2e = r.get("e2e")
    if e2e:
        rt = {k: v["runtime_s"] for k, v in e2e["engines"].items()}
        out.append(f"| e2e | " + ", ".join(f"{k} {v:.2f}s" for k, v in rt.items())
                   + " | — | equal cut_ratio |")
    oc = r.get("outofcore")
    if oc:
        spd = oc.get("pipeline_speedup")
        spd_s = f" ({spd:.1f}x serial)" if spd is not None else ""
        out.append(f"| outofcore | {oc['nodes_per_s']:.0f} nodes/s{spd_s} "
                   f"| peak <= bound + nodes/s floor "
                   f"| within_bound={oc['within_bound']}, "
                   f"labels_match={oc.get('labels_match_memory')} |")
    rs = r.get("restream_outofcore")
    if rs:
        orders = rs.get("orders", {})
        cuts = ", ".join(f"{o}: {row['cut_before']:.0f}→{row['cut_after']:.0f}"
                         for o, row in orders.items())
        out.append(f"| restream_outofcore | {cuts} "
                   f"| peak <= bound | exact_cut={rs.get('cut_is_exact')}, "
                   f"labels_match={rs.get('labels_match_memory')} |")
    sv = r.get("serve")
    if sv:
        out.append(f"| serve | lookup p99 {sv['lookup_p99_ms']:.3f} ms, "
                   f"{sv['updates_per_s']:.0f} edge ops/s, "
                   f"cut {sv['cut_vs_scratch']:.3f}x from-scratch "
                   f"| p99 <= 25 ms + >= 1000 ops/s + cut <= 1.10x "
                   f"| exact@{sv['exact_checkpoints']} checkpoints="
                   f"{sv['exact_at_every_checkpoint']}, "
                   f"deterministic={sv['deterministic_replay']} |")
    an = r.get("analysis")
    if an:
        out.append(f"| analysis | {an['files']} files / {an['rules']} rules in "
                   f"{an['wall_s']*1e3:.0f} ms ({an['ms_per_file']:.1f} ms/file) "
                   f"| <= 10 s whole-repo "
                   f"| clean={an['clean']}, {an['noqa_suppressed']} justified noqa |")
    ck = r.get("checkpoint")
    if ck:
        out.append(f"| checkpoint | densest-cadence overhead "
                   f"{ck['overhead_densest']:.1%} | <= 25% "
                   f"| resume_bit_identical={ck['resume_bit_identical']} |")
    sh = r.get("sharded")
    if sh:
        out.append(f"| sharded | W=4 {sh['speedup_w4']:.2f}x nodes/s, "
                   f"post-restream cut {sh['cut_ratio_w4_post']:.3f}x W=1 "
                   f"| >= {sh['scaling_floor']}x ({sh['cpu_count']} cpu) "
                   f"+ cut <= 1.10x "
                   f"| exact_cut={sh.get('cut_is_exact')}, "
                   f"backends_identical={sh.get('backends_bit_identical')} |")
    return "\n".join(out)


if __name__ == "__main__":
    if len(sys.argv) > 1 and sys.argv[1] != "hotpath":
        sys.exit(f"summarize.py: unknown mode {sys.argv[1]!r} (only 'hotpath')")
    print(hotpath_table(sys.argv[2] if len(sys.argv) > 2 else "BENCH_hotpath.json"))
